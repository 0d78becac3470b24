"""The main path's kernels and steps, compiled for a described TPU v5e.

Every other tier-1 test runs the Pallas kernels in interpret mode on
the CPU, which accepts programs the chip's compiler refuses: a slice
that is not a whole packed tile, a block whose last dim is not a lane
multiple, more scoped VMEM than the kernel asked for (all three were
found at Qwen3-8B widths — CHANGES.md, PR 24). libtpu is installed
here and compiles for a chip that is described and not attached, so
these cases ask it — shapes only, nothing runs — for what
chip_smoke.py will execute on one chip and on four, and assert by name
that the expected kernel is IN the compiled program (a route that gave
way to XLA compiles too).

The topology is described inside a module-scoped fixture and nowhere
else: only the xdist worker that is handed this file loads libtpu.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from triton_dist_tpu.lang import core
from triton_dist_tpu.models import Engine, ModelConfig
from triton_dist_tpu.models.dense import (
    cache_specs,
    param_shapes,
    param_specs,
)
from triton_dist_tpu.models.kv_cache import KVCache
from triton_dist_tpu.runtime import make_mesh

MAX_LEN = 2048
BF16 = jnp.bfloat16
SDS = jax.ShapeDtypeStruct


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs in /tmp
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def chip(topo, monkeypatch):
    """Steer every backend-dependent branch to the described chip: the
    kernels compile natively (not interpret), the perf model prices a
    v5e (not the CPU ranking stub), so the planner routes as it will
    on the device."""
    from triton_dist_tpu import perf_model

    monkeypatch.setattr(core, "backend_platform", lambda: "tpu")
    monkeypatch.setattr(core, "backend_device", lambda: topo.devices[0])
    perf_model.detect_chip.cache_clear()
    yield topo
    perf_model.detect_chip.cache_clear()


def _mesh(topo, n):
    return make_mesh((n,), ("tp",), devices=topo.devices)


def _engine(topo, n, num_layers=2):
    """Engine at Qwen3-8B widths over n described chips; params are
    shapes (a described device holds no array)."""
    cfg = ModelConfig.qwen3_8b(num_layers=num_layers,
                               max_positions=MAX_LEN)
    mesh = _mesh(topo, n)
    params = jax.tree.map(
        lambda shape, spec: SDS(shape, BF16,
                                sharding=NamedSharding(mesh, spec)),
        param_shapes(cfg, n), param_specs("tp"),
        is_leaf=lambda x: type(x) is tuple)
    return Engine(cfg, mesh, params=params, max_len=MAX_LEN)


def _step_args(eng, batch, seq):
    cfg, mesh = eng.cfg, eng.mesh
    kv = (cfg.num_layers, batch, MAX_LEN, cfg.num_kv_heads, cfg.head_dim)
    specs = cache_specs("tp")
    cache = KVCache(
        k=SDS(kv, BF16, sharding=NamedSharding(mesh, specs.k)),
        v=SDS(kv, BF16, sharding=NamedSharding(mesh, specs.v)),
        length=SDS((batch,), jnp.int32,
                   sharding=NamedSharding(mesh, specs.length)))
    tokens = SDS((batch, seq), jnp.int32,
                 sharding=NamedSharding(mesh, P()))
    return eng.params, tokens, cache


def _kernels(compiled) -> dict:
    return core.pallas_kernels_in(compiled.as_text())


def _shard_map_compile(mesh, fn, in_specs, out_specs, *shapes):
    args = [SDS(s, BF16, sharding=NamedSharding(mesh, spec))
            for s, spec in zip(shapes, in_specs)]
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False)).lower(*args).compile()


# -- one chip ---------------------------------------------------------------


def test_flash_prefill_s512(chip):
    """The kernel the seed's gate let through and Mosaic refused: 512
    query rows x 32 heads against a 2048-token cache."""
    from triton_dist_tpu.kernels.flash_prefill import (
        flash_prefill_fits,
        flash_prefill_local,
    )

    assert flash_prefill_fits(512, MAX_LEN, 32, 8, 128)
    mesh = _mesh(chip, 1)
    compiled = _shard_map_compile(
        mesh, lambda q, k, v: flash_prefill_local(q, k, v),
        (P(), P(), P()), P(),
        (1, 512, 32, 128), (1, MAX_LEN, 8, 128), (1, MAX_LEN, 8, 128))
    assert _kernels(compiled) == {"_fp_local_kernel": 1}


def test_engine_prefill_one_chip(chip):
    eng = _engine(chip, 1)
    compiled = eng._prefill.lower(*_step_args(eng, 1, 512)).compile()
    assert _kernels(compiled) == {"_fp_local_kernel": 1}


def test_engine_decode_one_chip(chip):
    """By design, not by accident: at world=1 there is nothing to
    overlap, so the `ar` decode step is XLA matmuls and XLA attention
    and holds no kernel of ours. chip_smoke.py prints the same."""
    eng = _engine(chip, 1)
    compiled = eng._decode.lower(*_step_args(eng, 1, 1)).compile()
    assert _kernels(compiled) == {}


def _serve_step_compiled(eng, slots, width, page=64):
    """The dense family's serve step at (slots, width), compiled."""
    max_pages = MAX_LEN // page
    mesh, cfg = eng.mesh, eng.cfg
    rep = NamedSharding(mesh, P())
    pool = SDS((cfg.num_layers, 1 + slots * max_pages, page,
                cfg.num_kv_heads, cfg.head_dim), BF16,
               sharding=NamedSharding(mesh, P(None, None, None, "tp")))
    return eng.make_serve_step(slots, width, page, max_pages).lower(
        eng.params, SDS((slots, width), jnp.int32, sharding=rep),
        (pool, pool), SDS((slots, max_pages), jnp.int32, sharding=rep),
        SDS((slots,), jnp.int32, sharding=rep),
        SDS((slots,), jnp.int32, sharding=rep),
        SDS((slots,), jnp.float32, sharding=rep),
        SDS((slots, 2), jnp.uint32, sharding=rep)).compile()


def test_serve_step_one_chip(chip):
    """The Scheduler's step at its default geometry for this model
    (4 slots x the chooser's 128-token chunk, 64-token pages)."""
    compiled = _serve_step_compiled(_engine(chip, 1), 4, 128)
    assert _kernels(compiled) == {"_fp_local_kernel": 1}


@pytest.mark.parametrize("tp, kernels, xla_collectives", [
    (1, {}, {}),
    (4, {"_one_shot_ar_kernel": 2}, {"all-gather": 1}),
], ids=["qwen3-8b.1chip", "qwen3-8b.tp4"])
def test_decode_only_serve_step(chip, tp, kernels, xla_collectives):
    """The width-1 step of the benchmark's two dense configurations
    (8 slots, 64-token pages; `Engine.serve_widths`): it compiles for
    the chip, and what it holds is listed. One chip: XLA matmuls and
    the dense attention chain over one query row, no kernel of ours
    (the prefill routes are behind `s > 1`). tp=4: the `ar` lowering
    at 8 rows is one `_one_shot_ar_kernel` behind each row-parallel
    projection (two in the layer scan's body, 72 calls a step at depth
    36) and XLA's all-gather of the logits — none of the wide step's
    `_gemm_rs_kernel*` / `_ring_ag_kernel`."""
    import re
    from collections import Counter

    eng = _engine(chip, tp)
    assert eng.serve_widths(128) == (1, 128)
    compiled = _serve_step_compiled(eng, 8, 1)
    assert _kernels(compiled) == kernels
    found = Counter(re.findall(
        r"= \S+ (all-reduce|all-gather|reduce-scatter|collective-permute"
        r"|all-to-all)[-\w]*\(", compiled.as_text()))
    assert dict(found) == xla_collectives


def _pool_moves_once(text, depth, slots, max_len, page, hkv, d):
    """The pool's round trip in a compiled serve program: the donated
    pool is updated IN PLACE — an array of the pool's shape is a
    parameter, a loop's element, or a `dynamic-update-slice` (alone or
    the root of a fusion), never a `copy` or a `transpose` — and no
    array of the whole view's shape is built, in any of the orders a
    gather or a restack of the layer scan would give it: each layer
    reads its own (slots, max_len, Hkv, D) view through the table."""
    import re

    maxp = max_len // page
    pool = f"bf16[{depth},{1 + slots * maxp},{page},{hkv},{d}]"
    made = re.findall(
        r"%(\S+) = " + re.escape(pool) + r"\S* ([a-z-]+)\(", text)
    assert made, "the pool's shape is not in the program"
    in_place = {"parameter", "get-tuple-element", "bitcast",
                "dynamic-update-slice", "fusion"}
    assert {op for _, op in made} <= in_place, made
    assert all("dynamic-update-slice" in name
               for name, op in made if op == "fusion"), made
    assert sum("dynamic-update-slice" in name + op
               for name, op in made) >= 2  # K and V are written
    tail = f"{hkv},{d}]"
    assert not [v for v in (f"[{depth},{slots},{max_len},{tail}",
                            f"[{depth},{slots},{maxp},{page},{tail}",
                            f"[{slots * maxp},{depth},{page},{tail}",
                            f"[{slots},{maxp},{depth},{page},{tail}")
                if v in text]


def _one_row_of_logits_a_slot(text, slots, width, vocab, tp=1):
    """The one-emission step's head reads one hidden row a slot
    (ISSUE 36): the compiled program holds (slots, V) float32 logits
    and no array with every row's, `f32[slots,width,...]` over the
    vocabulary, a chip's share of it, or anything wider than a model
    width (the all-rows form holds `f32[8,128,151936]`, at tp=4 also
    `f32[8,128,37984]` and four pieces `f32[8,128,30464]`)."""
    import re

    assert f"f32[{slots},{vocab}]" in text
    if width > 1:
        wide = set(map(int, re.findall(
            rf"f32\[{slots},{width},(\d+)\]", text)))
        assert not [v for v in wide
                    if v in (vocab, vocab // tp) or v > 20_000], wide


@pytest.mark.parametrize("width", [1, 128])
@pytest.mark.parametrize("tp", [1, 4], ids=["qwen3-8b.1chip", "qwen3-8b.tp4"])
def test_serve_step_moves_the_pool_s_bytes_once(chip, tp, width):
    """Both serve programs of both dense configurations (8 slots,
    64-token pages) hold no copy of the pool and no whole view
    (`_pool_moves_once`), and the decode-only one keeps nothing of a
    view's size alive from layer to layer; neither holds more than one
    row of logits a slot (`_one_row_of_logits_a_slot`)."""
    eng = _engine(chip, tp)
    cfg, slots, page = eng.cfg, 8, 64
    compiled = _serve_step_compiled(eng, slots, width, page)
    hkv = cfg.num_kv_heads // tp
    _pool_moves_once(compiled.as_text(), cfg.num_layers, slots, MAX_LEN,
                     page, hkv, cfg.head_dim)
    _one_row_of_logits_a_slot(compiled.as_text(), slots, width,
                              cfg.vocab_size, tp)
    if width == 1:
        one_layer_s_view = 2 * slots * MAX_LEN * hkv * cfg.head_dim * 2
        assert (compiled.memory_analysis().temp_size_in_bytes
                < one_layer_s_view)


def _hybrid_step_compiled(chip, cfg, slots, page, max_len):
    """A hybrid configuration's serve step at the chooser's chunk,
    compiled for the described chip from shapes alone."""
    from triton_dist_tpu.models import hybrid
    from triton_dist_tpu.perf_model import choose_chunk_for

    mesh = _mesh(chip, 1)
    rep = NamedSharding(mesh, P())
    params = {name: SDS(shape, BF16, sharding=rep)
              for name, shape, _ in hybrid.leaves(cfg)}
    eng = Engine(cfg, mesh, params=params, max_len=max_len)
    chunk = choose_chunk_for(cfg, 1, slots, max_len, "flash")
    assert chunk == 128
    max_pages = max_len // page
    pools = tuple(SDS((cfg.num_kv_layers, 1 + slots * max_pages, page,
                       heads, width), BF16, sharding=rep)
                  for heads, width in cfg.page_arrays)
    cache = pools + tuple(
        SDS(shape, dt, sharding=rep) for shape, dt in zip(
            hybrid.state_shapes(cfg, slots), (jnp.float32, BF16))) + tuple(
        SDS(shape, BF16, sharding=rep)
        for shape in hybrid.window_shapes(cfg, slots))
    return eng.make_serve_step(slots, chunk, page, max_pages).lower(
        params, SDS((slots, chunk), jnp.int32, sharding=rep), cache,
        SDS((slots, max_pages), jnp.int32, sharding=rep),
        SDS((slots,), jnp.int32, sharding=rep),
        SDS((slots,), jnp.int32, sharding=rep),
        SDS((slots,), jnp.float32, sharding=rep),
        SDS((slots, 2), jnp.uint32, sharding=rep)).compile()


def _experts_stream_through_the_kernel(text, cfg):
    """The held experts' products are `_moe_gmm_kernel`'s: no
    `ragged_dot` is left in the step, and no expert stack (or slice of
    one) is copied on its way to the kernel: the stacks go in as they
    lie in HBM."""
    assert "ragged-dot" not in text and "ragged_dot" not in text
    held, h, i = (cfg.num_experts_held, cfg.hidden_size,
                  cfg.moe_intermediate_size)
    moved = re.findall(
        rf"= bf16\[(?:\d+,)*{held},(?:{h},{2 * i}|{i},{h})\]\S* "
        r"(?:copy|fusion|dynamic-slice)\(", text)
    assert not moved, moved


def test_latent_serve_step_one_chip(chip):
    """The Kimi-Linear pattern at the benchmark's widths and geometry,
    cut to its first two periods and the short last one (11 blocks:
    the leading dense one, three runs of periods): the latent blocks
    run `_fp_local_kernel` with the value a column prefix of the key
    page, once a run, over ONE pool of 640-wide rows (Mosaic refuses a
    576-wide slice of a page: the row is padded to whole lanes), and
    the route says so by name."""
    from triton_dist_tpu.kernels import flash_prefill
    from triton_dist_tpu.plan.planner import (
        route_gated_attention,
        route_hybrid_attention,
    )

    max_len, slots, page = 8192, 8, 64
    full = (4, 8, 11)
    cfg = ModelConfig.kimi_linear_48b(
        num_layers=11, full_attn_layers=full,
        kda_layers=tuple(i for i in range(1, 12) if i not in full),
        experts_held=16, max_positions=max_len)
    assert cfg.page_arrays == ((1, 640),)
    assert route_hybrid_attention(cfg, slots, 128, max_len) == "pallas"
    with pytest.raises(NotImplementedError, match="no other route"):
        route_gated_attention(slots, 128, max_len, 32, 1, 576, "bfloat16",
                              v_prefix=512)
    with pytest.raises(NotImplementedError, match="one query row"):
        route_hybrid_attention(cfg, slots, 1, max_len)
    compiled = _hybrid_step_compiled(chip, cfg, slots, page, max_len)
    # the ten expert blocks' two products each, and no `ragged_dot`
    assert _kernels(compiled) == {"_fp_local_kernel": 3,
                                  "_moe_gmm_kernel": 20}
    _experts_stream_through_the_kernel(compiled.as_text(), cfg)
    launch = flash_prefill.last_launch()
    # 32 heads stacked into the rows: 4,096 rows in tiles of 512, one
    # stream of pages, keys 640 wide and values their first 512
    assert launch["grid"] == (slots, 8) and launch["q_rows"] == 512
    assert launch["widths"] == (640, 512) and launch["streams"] == 1
    text = compiled.as_text()
    assert "bf16[3,1025,64,1,640]" in text  # the one latent pool
    _one_row_of_logits_a_slot(text, slots, 128, cfg.vocab_size)
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9


def test_hybrid_serve_step_one_chip(chip):
    """The hybrid family's step at the benchmark's geometry (8 slots x
    the chooser's 128-token chunk, 64-token pages, 8,192 positions),
    two periods deep: the gated attention blocks run `_fp_local_kernel`
    at head size 256 with 16 q / 2 kv heads, and no layer's experts
    are sliced out of the stack (a slice that feeds the grouped matmul
    is a copy of every expert's weights every step: 3.2 GB of
    temporaries a period where the whole step needs under 2.5)."""
    max_len, slots, page = 8192, 8, 64
    cfg = ModelConfig.qwen3_next_80b(
        num_layers=8, experts_held=128, vocab_size=37_984,
        max_positions=max_len)
    compiled = _hybrid_step_compiled(chip, cfg, slots, page, max_len)
    # one scan body of a period: four expert blocks' two products each
    assert _kernels(compiled) == {"_fp_local_kernel": 1,
                                  "_moe_gmm_kernel": 8}
    _experts_stream_through_the_kernel(compiled.as_text(), cfg)
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9
    _one_row_of_logits_a_slot(compiled.as_text(), slots, 128, cfg.vocab_size)
    _pool_moves_once(compiled.as_text(), cfg.num_kv_layers, slots, max_len,
                     page, cfg.num_kv_heads, cfg.head_dim)


def test_window_and_global_serve_step_one_chip(chip):
    """K-EXAONE's pattern at the benchmark's widths, depth and geometry
    (blocks 0-7: the leading dense block and two periods `L L L G`; 16
    experts of 128 held, an eighth of the vocabulary; 8 slots x the
    chooser's 128-token chunk, 64-token pages, 8,192 positions): every
    attention block runs `_fp_local_kernel`, the six window blocks over
    their slot's tail and the chunk (256 positions, the window's bound
    in the kernel), the two global ones over the slot's pages; pages
    exist for the global blocks alone; the step fits the chip."""
    from triton_dist_tpu.kernels import flash_prefill
    from triton_dist_tpu.plan.planner import (
        route_hybrid_attention,
        route_window_attention,
    )

    max_len, slots, page = 8192, 8, 64
    cfg = ModelConfig.k_exaone_236b(
        num_layers=8, experts_held=16, vocab_size=19_200,
        max_positions=max_len)
    assert route_hybrid_attention(cfg, slots, 128, max_len) == "pallas"
    assert route_window_attention(cfg, slots, 128) == "pallas"
    with pytest.raises(NotImplementedError, match="one query row"):
        route_window_attention(cfg, slots, 1)
    compiled = _hybrid_step_compiled(chip, cfg, slots, page, max_len)
    # one body a period and kind: 2 scans x (window, global); the seven
    # expert blocks' two products each
    assert _kernels(compiled) == {"_fp_local_kernel": 8,
                                  "_moe_gmm_kernel": 14}
    _experts_stream_through_the_kernel(compiled.as_text(), cfg)
    launch = flash_prefill.last_launch()  # the last traced: a global block
    assert launch["widths"] == (128, 128) and launch["streams"] == 2
    text = compiled.as_text()
    assert "bf16[2,1025,64,8,128]" in text  # pages: the 2 global blocks'
    assert "bf16[6,8,128,8,128]" in text  # the 6 window blocks' tails
    # one row of logits a slot (the helper's bound on other widths does
    # not hold here: block 0's gate | up is 36,864 wide)
    assert "f32[8,19200]" in text and "f32[8,128,19200]" not in text
    mem = compiled.memory_analysis()
    print(f"k-exaone step: arguments {mem.argument_size_in_bytes} "
          f"temporaries {mem.temp_size_in_bytes} "
          f"output {mem.output_size_in_bytes} "
          f"alias {mem.alias_size_in_bytes}")
    # the weights 11.96 GB, the pages 0.54, the tails 0.025
    assert 12.3e9 < mem.argument_size_in_bytes < 12.8e9
    assert mem.temp_size_in_bytes < 2.5e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.6e9


def test_state_space_serve_step_one_chip(chip):
    """granite-4.0-h-micro's pattern at its published widths and the
    benchmark's geometry, cut to `M x5 A`, `M x9 A`, `M x4` (one of the
    three long periods): the attention blocks' heads of 64 reach
    `_fp_local_kernel` 128 wide, as their pages keep them (the head
    itself is refused: no silent dense chain), once a scan; the
    state-space mixer is XLA's (no other kernel); no expert layer, so
    no `_moe_gmm_kernel` and no counter; the tied head reads one row a
    slot."""
    from triton_dist_tpu.kernels import flash_prefill
    from triton_dist_tpu.plan.planner import (
        route_gated_attention,
        route_hybrid_attention,
    )

    max_len, slots, page = 8192, 8, 64
    m, a = "mamba", "attention"
    types = (m,) * 5 + (a,) + (m,) * 9 + (a,) + (m,) * 4
    cfg = ModelConfig.granite_4_h_micro(
        num_layers=20, first_k_dense=20, layer_types=types,
        max_positions=max_len)
    assert route_hybrid_attention(cfg, slots, 128, max_len) == "pallas"
    with pytest.raises(NotImplementedError, match="no other route"):
        route_gated_attention(slots, 128, max_len, 32, 8, 64, "bfloat16")
    compiled = _hybrid_step_compiled(chip, cfg, slots, page, max_len)
    assert _kernels(compiled) == {"_fp_local_kernel": 2}
    launch = flash_prefill.last_launch()
    assert launch["widths"] == (128, 128) and launch["streams"] == 2
    text = compiled.as_text()
    assert "bf16[2,1025,64,8,128]" in text  # pages: 2 attention blocks'
    assert "f32[18,8,64,64,128]" in text  # the 18 mixers' state
    assert "bf16[18,8,3,4352]" in text  # and convolution tails
    _one_row_of_logits_a_slot(text, slots, 128, cfg.vocab_size)
    mem = compiled.memory_analysis()
    # weights 3.40 GB (the embedding 0.41 of them), pages 0.54, state 0.30
    assert 4.1e9 < mem.argument_size_in_bytes < 4.4e9
    assert mem.temp_size_in_bytes < 2.5e9


def test_gated_attention_has_no_silent_route_on_the_chip(chip):
    """A head shape flash-prefill does not take is an error when the
    hybrid step is built, not a dense XLA chain in its place."""
    from triton_dist_tpu.plan.planner import route_gated_attention

    assert route_gated_attention(8, 128, 8192, 16, 2, 256,
                                 "bfloat16") == "pallas"
    with pytest.raises(NotImplementedError, match="no other route"):
        route_gated_attention(8, 128, 8192, 16, 2, 96, "bfloat16")


def test_grouped_matmul_has_no_silent_route_on_the_chip(chip):
    """The six (K, N) the hybrid cells serve, at their steps' rows,
    take the kernel on the chip; what it does not tile takes
    `ragged_dot` by its shape, in the open."""
    from triton_dist_tpu.kernels.grouped_gemm import grouped_gemm_route

    for rows, shapes in ((10_240, ((2048, 1024), (512, 2048))),
                         (8192, ((2304, 2048), (1024, 2304))),
                         (8192, ((6144, 4096), (2048, 6144)))):
        for k, n in shapes:
            assert grouped_gemm_route(rows, k, n) == "pallas", (k, n)
    assert grouped_gemm_route(8192, 2048, 96) == "xla"


def test_hybrid_family_keeps_the_chunk_s_width_on_the_chip(chip):
    """One query row never reaches `_fp_local_kernel` (gqa_attention's
    `s > 1` guard), so a width-1 step of the hybrid family would hold
    the dense chain its route refuses: the route says so, and the
    engine gives the family the one width."""
    from triton_dist_tpu.plan.planner import route_gated_attention

    with pytest.raises(NotImplementedError, match="one query row"):
        route_gated_attention(8, 1, 8192, 16, 2, 256, "bfloat16")
    cfg = ModelConfig.qwen3_next_80b(
        num_layers=4, experts_held=128, vocab_size=37_984,
        max_positions=8192)
    eng = Engine(cfg, _mesh(chip, 1), params={}, max_len=8192)
    assert eng.serve_widths(128) == (128,)


def test_mega_decode_step_one_chip(chip):
    """The megakernel exactly as MegaQwen3 builds it (graph, schedule,
    compile_graph), one decode step at batch 1. Its Mosaic compile
    takes over a minute at these widths — the slow case of this file."""
    from triton_dist_tpu.mega.kernel import _kv_chunk, compile_graph
    from triton_dist_tpu.mega.qwen3 import build_qwen3_graph
    from triton_dist_tpu.mega.scheduler import schedule_graph

    num_layers, batch = 1, 1
    cfg = ModelConfig.qwen3_8b(num_layers=num_layers,
                               max_positions=MAX_LEN)
    mb, _ = build_qwen3_graph(cfg, batch, 1, MAX_LEN, "tp")
    cm = compile_graph(mb.graph, schedule_graph(mb.graph), BF16,
                       name="mega_qwen3_tp1",
                       tiled_weights=("w_gate_up",))
    h, d, inter = cfg.hidden_size, cfg.head_dim, cfg.intermediate_size
    tn = cm.tile_cols("w_gate_up")
    chunk = _kv_chunk(MAX_LEN, 0)
    pages = batch * MAX_LEN // chunk
    ws = jax.eval_shape(lambda: cm.workspace(BF16))
    one = NamedSharding(_mesh(chip, 1), P())

    def sds(shape, dtype=BF16):
        return SDS(shape, dtype, sharding=one)

    weights = {
        "w_qkv": sds((num_layers, h, (cfg.num_q_heads
                                      + 2 * cfg.num_kv_heads) * d)),
        "w_o": sds((num_layers, cfg.num_q_heads * d, h)),
        "w_gate_up": sds((num_layers, 2 * inter // tn, h, tn)),
        "w_down": sds((num_layers, inter, h)),
    }
    kv = sds((num_layers, cfg.num_kv_heads, pages, chunk, d))
    compiled = jax.jit(cm.run).lower(
        sds((batch,), jnp.int32), sds((batch, pages), jnp.int32),
        sds(ws.shape), weights,
        sds(((4 * num_layers + 1) * 8, cm.norm_width), jnp.float32),
        sds((MAX_LEN * 8, d), jnp.float32), kv, kv).compile()
    assert _kernels(compiled) == {"mega_qwen3_tp1": 1}


# -- four chips: the cross-chip path ------------------------------------------


def test_mesh_ring_follows_ici(topo):
    """make_mesh lays tp neighbours on ICI neighbours: every hop of the
    ring, the wrap-around included, moves one step on the 2x2 grid."""
    ring = list(_mesh(topo, 4).devices.flat)
    for a, b in zip(ring, ring[1:] + ring[:1]):
        assert sum(abs(x - y) for x, y in zip(a.coords, b.coords)) == 1, (
            [d.coords for d in ring])


def test_ag_gemm_tp4(chip):
    """AG+GEMM with the MLP's silu_pair epilogue at the shape that was
    refused (a (4096, 3072) gate/up shard tiled (512, 192))."""
    from triton_dist_tpu.kernels.allgather_gemm import ag_gemm

    compiled = _shard_map_compile(
        _mesh(chip, 4),
        lambda a, g, u: ag_gemm(a, (g[0], u[0]), "tp",
                                epilogue="silu_pair"),
        (P("tp"), P("tp"), P("tp")), P(None, "tp"),
        (512, 4096), (4, 4096, 3072), (4, 4096, 3072))
    assert _kernels(compiled) == {"_ag_gemm_kernel": 1}


def test_gemm_rs_tp4(chip):
    """GEMM+RS at the MLP down-projection of a 512-token prefill."""
    from triton_dist_tpu.kernels.gemm_reduce_scatter import gemm_rs

    compiled = _shard_map_compile(
        _mesh(chip, 4), lambda a, b: gemm_rs(a, b[0], "tp"),
        (P(None, "tp"), P("tp")), P("tp"),
        (512, 4 * 3072), (4, 3072, 4096))
    assert _kernels(compiled) == {"_gemm_rs_kernel_streamed": 1}


@pytest.mark.parametrize("batch", [1, 8])
def test_ar_decode_step_tp4(chip, batch):
    """The `ar` decode step: one one-shot AR per projection back into
    the residual stream. batch=1 is the case Mosaic refused (a one-row
    bf16 slot is not a whole packed tile)."""
    eng = _engine(chip, 4)
    compiled = eng._decode.lower(*_step_args(eng, batch, 1)).compile()
    assert _kernels(compiled) == {"_one_shot_ar_kernel": 2}


def test_dist_prefill_step_tp4(chip):
    """The whole `dist` prefill step at S=512: AG+GEMM into QKV and
    into gate|up, GEMM+RS out of the o- and down-projections (resident
    and streamed regimes), flash attention between."""
    eng = _engine(chip, 4)
    compiled = eng._prefill.lower(*_step_args(eng, 1, 512)).compile()
    assert _kernels(compiled) == {
        "_ag_gemm_kernel": 2, "_fp_local_kernel": 1,
        "_gemm_rs_kernel": 1, "_gemm_rs_kernel_streamed": 1}


def test_watchdog_native_branch_tp4(chip):
    """faults.guard's bounded-poll loop over `pl.semaphore_read`: the
    installed interpreter has no rule for that primitive (the 18 tier-1
    tests that build a guard on the CPU mesh fail on it), Mosaic does —
    this compile is the only coverage the hardware branch has."""
    from triton_dist_tpu.faults import guard
    from triton_dist_tpu.kernels.allgather import ring_all_gather

    def gathered(x):
        with guard.building():
            out, gbuf = ring_all_gather(x, "tp")
        return out, gbuf[None]

    compiled = _shard_map_compile(
        _mesh(chip, 4), gathered, (P("tp"),), (P(), P("tp")), (512, 4096))
    assert _kernels(compiled) == {"_ring_ag_kernel": 1}


# -- chip_smoke.py's own control flow, rehearsed at a tiny size ---------------


@pytest.mark.parametrize("chips", [1, 4])
def test_chip_smoke_rehearsal(monkeypatch, capsys, chips):
    """The first two rehearsals of the on-chip-measurement guide, kept:
    chip_smoke.py's phases end to end on the CPU (interpret-mode
    kernels) on one device and on four virtual ones, at a tiny size.
    `main()` itself refuses a CPU, so the phases are driven through
    `run`; what only a chip has — kernels named in compiled text, chip
    coordinates, memory_stats — is what test_* above and the chip run
    check instead."""
    import numpy as np

    import chip_smoke

    monkeypatch.setattr(chip_smoke, "PROMPT_LENS", (4, 8, 8, 8))
    monkeypatch.setattr(chip_smoke, "NEW_TOKENS", 2)
    monkeypatch.setattr(chip_smoke, "MAX_LEN", 64)
    wanted = []
    monkeypatch.setattr(chip_smoke, "require",
                        lambda kernels, want, where: wanted.append(where))
    monkeypatch.setattr(chip_smoke, "check_ring", lambda mesh: None)
    monkeypatch.setattr(chip_smoke, "check_memory_spread",
                        lambda mesh, model_bytes: None)
    cfg = ModelConfig.tiny(max_positions=64)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in chip_smoke.PROMPT_LENS]
    chip_smoke.run(cfg, make_mesh((chips,), ("tp",)), 0, prompts,
                   cross_chip=chips > 1)
    out = capsys.readouterr().out
    assert "serve: 4/4 requests finished, 8 tokens streamed" in out
    assert "engine vs reference: largest difference" in out
    assert ("megakernel decode step" in out) == (chips == 1)
    assert "serve step" in wanted and "decode step" in wanted
