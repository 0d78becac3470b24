"""The serve step's head reads one row a slot (ISSUE 36).

When a step samples one token a slot (`per_pos` false) the hidden row
of column `n_valid - 1` is taken BEFORE the final RMS norm and the
vocabulary projection, so the norm, the head, the tp all-gather of the
logits and sampling see `(K, H) -> (K, V)` and the `(K, C, V)` float32
array is never built. These tests hold the dense family's step, at
both of its widths and on one device and four, to the all-rows form
(the per-position step, which still builds every row's logits and
takes the column afterwards); the hybrid members' are in
tests/test_qwen3_next.py and tests/test_kimi_linear.py.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from triton_dist_tpu.models import Engine, ModelConfig
from triton_dist_tpu.runtime import make_mesh
from triton_dist_tpu.serve import Scheduler
from triton_dist_tpu.spec import NgramDraft, SpecConfig

from _head_rows import (
    CHUNK,
    PAGE,
    SLOTS,
    assert_same_step,
    decoding_scheduler,
    step_args,
)

MAX_LEN = 64
# (devices, decode_mode): one chip; four with the replicated lowering
# and four with the sequence-sharded one (whose closing all-gather of
# the hidden rows precedes the take)
MESHES = [(1, "ar"), (4, "ar"), (4, "dist")]


def _engine(n, mode):
    mesh = make_mesh(mesh_shape=(n,), axis_names=("tp",),
                     devices=jax.devices()[:n])
    cfg = ModelConfig.tiny(num_q_heads=4, num_kv_heads=4,
                           max_positions=MAX_LEN)
    return Engine(cfg, mesh, decode_mode=mode, max_len=MAX_LEN,
                  donate_cache=False)


@pytest.fixture(scope="module", params=MESHES,
                ids=[f"tp{n}-{m}" for n, m in MESHES])
def eng(request):
    return _engine(*request.param)


@pytest.fixture(scope="module")
def sch(eng):
    return decoding_scheduler(eng, eng.cfg.vocab_size)


def _steps(eng, sch, width):
    geo = (SLOTS, width, PAGE, sch.pool.max_pages)
    return (eng.make_serve_step(*geo),
            eng.make_serve_step(*geo, per_pos=True))


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("width", [1, CHUNK])
def test_the_step_is_the_all_rows_form_with_the_column_taken_afterwards(
        eng, sch, width, sampled):
    """`tok` equal and `last` bitwise (the CPU backend's matmul rows do
    not depend on the row count in float32), the pool's new rows too,
    for n_valid in {0, 1, 3, chunk}, greedy and under the same keys."""
    one, every = _steps(eng, sch, width)
    tokens, table, lengths, n_valid, temps, keys = step_args(
        sch, width, sampled)
    got = one(eng.params, tokens, sch.pool.state, table, lengths, n_valid,
              temps, keys)
    assert got[0].shape == (SLOTS,)
    assert got[1].shape == (SLOTS, eng.cfg.vocab_size)
    # the per-position step samples column j under keys[:, j]: hand
    # every column its slot's key and read the column the slot emits
    keys_pp = jnp.broadcast_to(keys[:, None], (SLOTS, width, 2))
    tok_pp, last_pp, cache_pp, _ = every(
        eng.params, tokens, sch.pool.state, table, lengths, n_valid, temps,
        keys_pp)
    assert tok_pp.shape == (SLOTS, width)
    cols = np.maximum(np.asarray(n_valid) - 1, 0)
    want = (np.asarray(tok_pp)[np.arange(SLOTS), cols], last_pp, cache_pp)
    assert_same_step(got, want)
    if sampled:  # the sampled slots did not all fall back to the argmax
        assert (np.asarray(got[0]) != np.argmax(got[1], -1)).any()


def test_forward_rows_names_the_rows_the_head_reads(eng):
    """`head_cols`: None is the last column, an array one column a
    row, ALL_COLS every one; the three agree row for row."""
    from jax.sharding import PartitionSpec as P

    from triton_dist_tpu.models.dense import (
        ALL_COLS,
        cache_specs,
        forward_rows,
        param_specs,
    )

    cfg, axis = eng.cfg, eng.axis
    n = int(eng.mesh.shape[axis])
    cols = jnp.asarray([2, 0, 3, 1], jnp.int32)

    def per_rank(params, tokens, cache, cols):
        kw = dict(mode=eng.decode_mode, axis=axis)
        every, _ = forward_rows(cfg, params, tokens, cache,
                                head_cols=ALL_COLS, **kw)
        last, _ = forward_rows(cfg, params, tokens, cache, **kw)
        some, _ = forward_rows(cfg, params, tokens, cache, head_cols=cols,
                               **kw)
        return every, last, some

    fn = jax.jit(jax.shard_map(
        per_rank, mesh=eng.mesh,
        in_specs=(param_specs(axis), P(), cache_specs(axis), P()),
        out_specs=P(), check_vma=False))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 4)), jnp.int32)
    every, last, some = fn(eng.params, tokens, eng.new_cache(4), cols)
    assert every.shape == (4, 4, cfg.vocab_size) and n in (1, 4)
    np.testing.assert_array_equal(np.asarray(last), np.asarray(every[:, -1]))
    np.testing.assert_array_equal(
        np.asarray(some), np.asarray(every)[np.arange(4), np.asarray(cols)])


def _lowered(eng, sch, width, per_pos):
    fn = eng.make_serve_step(SLOTS, width, PAGE, sch.pool.max_pages,
                             per_pos=per_pos)
    tokens, table, lengths, n_valid, temps, keys = step_args(
        sch, width, False)
    if per_pos:
        keys = jnp.broadcast_to(keys[:, None], (SLOTS, width, 2))
    return fn.lower(eng.params, tokens, sch.pool.state, table, lengths,
                    n_valid, temps, keys).as_text()


def test_no_array_of_every_row_s_logits_is_in_the_one_emission_step(
        eng, sch):
    """The lowered wide step holds no (K, C, V) float32 array, whole or
    as a tp shard of the vocabulary; the per-position step holds one."""
    n = int(eng.mesh.shape[eng.axis])
    v = eng.cfg.vocab_size
    every = [f"tensor<{SLOTS}x{CHUNK}x{w}xf32>" for w in {v, v // n}]
    one = _lowered(eng, sch, CHUNK, per_pos=False)
    assert not [t for t in every if t in one]
    assert f"tensor<{SLOTS}x{v}xf32>" in one
    all_rows = _lowered(eng, sch, CHUNK, per_pos=True)
    assert [t for t in every if t in all_rows]


@pytest.mark.parametrize("spec_on", [False, True], ids=["plain", "spec"])
def test_the_counter_says_how_many_rows_went_through_the_head(spec_on):
    """`serve_head_rows`: `slots` a step of either width for the
    one-emission step, `slots x width` for the per-position one."""
    eng = _engine(1, "ar")
    kw = dict(spec=SpecConfig(k=2, draft=NgramDraft())) if spec_on else {}
    sch = Scheduler(eng, slots=SLOTS, chunk=CHUNK, page=PAGE, **kw)
    rng = np.random.default_rng(4)
    for n in (9, 6):
        sch.submit(list(map(int, rng.integers(0, eng.cfg.vocab_size, n))),
                   max_new_tokens=5)
    sch.run()
    c = sch.obs.snapshot()["counters"]
    widths = [h["width"] for h in sch.history if h.get("kind") == "step"]
    assert len(widths) == sch.worker.n_steps > 3
    if spec_on:
        assert c["serve_head_rows"] == SLOTS * sum(widths)
        assert CHUNK in widths
    else:
        assert c["serve_head_rows"] == SLOTS * len(widths)
        assert set(widths) == {1, CHUNK}  # both programs, one row a slot
