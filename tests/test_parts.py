"""The parts of a served step (layers/parts.py, ISSUE 40): the closed
list, the names each family's lowered serve step carries, and the
report that reads them back out of a profile
(`scripts/trace_report.py --device-parts`), its reducer over hand-made
tuples."""

import contextlib
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest

from triton_dist_tpu.layers.parts import PARTS, PREFIX, part
from triton_dist_tpu.models import Engine, ModelConfig
from triton_dist_tpu.runtime import make_mesh
from triton_dist_tpu.serve import Scheduler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAX_LEN = 64
COMMON = {"embed", "pool.gather", "pool.scatter", "attn.proj", "attn.core",
          "head", "sample"}
MOE = {"moe.route", "moe.dispatch", "moe.experts", "moe.combine",
       "moe.shared"}
MIXER = {"mixer.proj", "mixer.conv", "mixer.rule"}
FAMILIES = {
    "dense": (ModelConfig.tiny, COMMON | {"ffn.dense"}),
    "qwen3-next": (ModelConfig.tiny_next, COMMON | MOE | MIXER),
    # block 0 of the two is a dense MLP
    "kimi-linear": (ModelConfig.tiny_kimi,
                    COMMON | MOE | MIXER | {"ffn.dense"}),
    "k-exaone": (ModelConfig.tiny_exaone, COMMON | MOE | {"ffn.dense"}),
    # a state-space mixer under the delta nets' three names, a dense
    # MLP in every block, no expert layer
    "granite-4h": (ModelConfig.tiny_granite,
                   COMMON | MIXER | {"ffn.dense"}),
}


@pytest.fixture(scope="module")
def report():
    spec = importlib.util.spec_from_file_location(
        "_tdt_trace_report_parts",
        os.path.join(REPO, "scripts", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------- the list ----------


def test_the_list_is_closed_and_its_names_are_short():
    assert len(PARTS) == len(set(PARTS)) == 16
    assert all(re.fullmatch(r"[a-z]+(\.[a-z]+)?", p) for p in PARTS)
    with pytest.raises(ValueError, match="no part of the step"):
        part("attention")


def test_a_part_names_the_operations_traced_inside_it():
    @part("attn.core")
    def inner(x):
        return jnp.sin(x)

    def f(x):
        with part("embed"):
            y = x * 2.0
        return inner(y) + 1.0

    text = jax.jit(f).lower(jnp.ones((4,))).as_text(debug_info=True)
    assert f"{PREFIX}embed/mul" in text
    assert f"{PREFIX}attn.core/sin" in text
    assert not re.search(r"tdt\.[a-z.]+/add", text)  # outside both


# ---------- each family's serve step ----------


def _lowered_steps(make_cfg):
    """{width: the family's tiny serve step, lowered}."""
    mesh = make_mesh(mesh_shape=(1,), axis_names=("tp",))
    eng = Engine(make_cfg(max_positions=MAX_LEN), mesh, max_len=MAX_LEN,
                 fast_init=True)
    sch = Scheduler(eng, slots=2, page=8)
    pool, found = sch.pool, {}
    for width in eng.serve_widths(sch.chunk):
        fn = eng.make_serve_step(pool.slots, width, pool.page,
                                 pool.max_pages)
        found[width] = fn.lower(
            eng.params, jnp.zeros((pool.slots, width), jnp.int32),
            pool.state, jnp.asarray(pool.table), jnp.asarray(pool.lengths),
            jnp.zeros((pool.slots,), jnp.int32),
            jnp.zeros((pool.slots,), jnp.float32),
            jnp.zeros((pool.slots, 2), jnp.uint32))
    return found


def _lowered_parts(make_cfg):
    return {width: set(re.findall(
        re.escape(PREFIX) + r"([a-z_]+(?:\.[a-z_]+)*)",
        lowered.as_text(debug_info=True)))
        for width, lowered in _lowered_steps(make_cfg).items()}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_lowered_serve_step_names_the_family_s_parts(family):
    """Every width's lowered text (`as_text(debug_info=True)`, where an
    operation's location carries its scopes) holds every part the
    family uses and no `tdt.` name outside the list."""
    make_cfg, uses = FAMILIES[family]
    for width, found in _lowered_parts(make_cfg).items():
        assert found <= set(PARTS), (width, found - set(PARTS))
        assert found == uses, (width, found ^ uses)


# ---------- a scope changes no instruction ----------


@pytest.fixture(scope="module")
def hlo_compare():
    spec = importlib.util.spec_from_file_location(
        "_tdt_hlo_compare", os.path.join(REPO, "scripts", "hlo_compare.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@contextlib.contextmanager
def _no_parts():
    """`part(...)` names nothing while this is open, the decorators
    applied at import too: the name stack's context manager (what
    `jax.named_scope` returns, JAX 0.9) leaves a `tdt.` name out."""
    from jax._src import source_info_util as siu

    cm = siu.ExtendNameStackContextManager
    real = cm.__enter__

    def enter(self):
        if not self.name.startswith(PREFIX):
            return real(self)
        self.prev = siu._source_info_context.context  # __exit__ restores

    cm.__enter__ = enter
    try:
        yield
    finally:
        cm.__enter__ = real


@pytest.fixture
def metadata_in_the_cache_key():
    """JAX's persistent compile cache leaves metadata out of its key:
    the step compiled without the scopes would LOAD the executable
    compiled with them a moment before (docs/observability.md, "Mind
    the compile cache")."""
    flag = "jax_compilation_cache_include_metadata_in_key"
    jax.config.update(flag, True)
    yield
    jax.config.update(flag, False)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_scopes_change_no_instruction_of_the_compiled_step(
        family, hlo_compare, metadata_in_the_cache_key):
    """The optimised HLO of each width's step, compiled with the parts
    named and with none, metadata masked, holds the same instructions
    wired the same way (`scripts/hlo_compare.py`, which compares the
    real sizes compiled for the chip the same way). The compiler's
    NUMBERING of instruction names may shift with a scope, and does."""
    make_cfg, _uses = FAMILIES[family]
    named = _lowered_steps(make_cfg)
    with _no_parts():
        bare = _lowered_steps(make_cfg)
    assert sorted(named) == sorted(bare)
    for width in named:
        a = named[width].compile().as_text()
        b = bare[width].compile().as_text()
        assert PREFIX in a and PREFIX not in b, width
        a, b = hlo_compare.masked(a), hlo_compare.masked(b)
        assert PREFIX not in a
        count, shown = hlo_compare.differing_lines(
            hlo_compare.renamed(a), hlo_compare.renamed(b))
        assert count == 0, (width, shown)


HLO_A = """HloModule jit_step, is_scheduled=true

%fused.3 (param_0.8: f32[4], p.1: f32[4]) -> f32[4] {
  %param_0.8 = f32[4]{0} parameter(0)
  %p.1 = f32[4]{0} parameter(1)
  ROOT %add.7 = f32[4]{0} add(%param_0.8, %p.1), metadata={op_name="a/b"}
}

ENTRY %main.9 (x.1: f32[4]) -> f32[4] {
  %x.1 = f32[4]{0} parameter(0), metadata={op_name="x"}
  %c.2 = f32[4]{0} constant({0.5, 1.25, 2, 3})
  %k.4 = f32[4]{0} custom-call(%x.1), backend_config={"body":"loc(1)"}
  ROOT %f.5 = f32[4]{0} fusion(%k.4, %c.2), kind=kLoop, calls=%fused.3
}

FileNames
1 "a.py"
"""


def test_the_masks_of_the_hlo_comparison(hlo_compare, tmp_path, capsys):
    hc = hlo_compare
    # the other side: a scope's metadata, shifted numbers, another stem
    b = (HLO_A.replace('op_name="a/b"', 'op_name="tdt.head/a/b"')
         .replace("add.7", "add.11").replace("%k.4", "%k_k.6")
         .replace('loc(1)', 'loc(2)').replace('"a.py"', '"b.py"'))
    ma, mb = hc.masked(HLO_A), hc.masked(b)
    assert "metadata" not in ma and "a.py" not in ma and "loc(1)" not in ma
    assert hc.differing_lines(ma, mb)[0] == 3  # add, k and its user
    assert "constant({0.5, 1.25, 2, 3})" in hc.unnumbered(ma)
    assert "%add(" not in hc.unnumbered(ma) and "%add =" in hc.unnumbered(ma)
    assert "(param_0: f32[4], p: f32[4])" in hc.unnumbered(ma)
    assert hc.differing_lines(hc.unnumbered(ma), hc.unnumbered(mb))[0] == 2
    assert hc.renamed(ma) == hc.renamed(mb)
    # a real difference survives every mask: another operand order
    c = HLO_A.replace("fusion(%k.4, %c.2)", "fusion(%c.2, %k.4)")
    assert hc.differing_lines(hc.renamed(ma), hc.renamed(hc.masked(c)))[0] == 1
    # `diff` over two directories of dumps
    for name, text in (("a", HLO_A), ("b", b), ("c", c)):
        d = tmp_path / name
        d.mkdir()
        (d / "tiny.w1.masked.txt").write_text(hc.masked(text))
        (d / "tiny.w1.json").write_text('{"kernels": {"_k": 1}}')
    assert hc.main(["diff", str(tmp_path / "a"), str(tmp_path / "b")]) == 0
    assert "differ 3 as they are, 2 with names' numbers off, 0 with" in (
        capsys.readouterr().out)
    assert hc.main(["diff", str(tmp_path / "a"), str(tmp_path / "c")]) == 1
    assert "DIFFERS" in capsys.readouterr().out
    (tmp_path / "b" / "tiny.w1.json").write_text('{"kernels": {"_k": 2}}')
    assert hc.main(["diff", str(tmp_path / "a"), str(tmp_path / "b")]) == 1
    assert hc.main(["diff", str(tmp_path), str(tmp_path)]) == 1  # no dumps
    assert hc.main(["diff", str(tmp_path / "a")]) == 2
    capsys.readouterr()


# ---------- the report's reducer ----------

FUSION = "%fusion.12 = bf16[1024,4096]{1,0} fusion(%p.1), kind=kLoop"
DOT = "%convolution.7 = bf16[1024,12288]{1,0} convolution(%a, %b)"
COPY = "%copy.3 = bf16[8,128]{1,0} copy(%x)"
WHILE = "%while.5 = (s32[], bf16[8]) while(%tuple.1)"


def _one_run(t):
    """(events, module) of one run of `jit_step` at t: a `while` of
    60 ms that holds two of its body's operations (20 + 30) and 10 of
    its own, then an unscoped leaf of 5 and a head fusion of 15."""
    ms = 1e-3
    events = [
        (WHILE, "jit(step)/while", t, t + 60 * ms),
        (DOT, "jit(step)/while/body/tdt.attn.proj/dot_general",
         t + 1 * ms, t + 21 * ms),
        (FUSION, "jit(step)/while/body/tdt.attn.proj/tdt.attn.core/mul",
         t + 25 * ms, t + 55 * ms),
        (COPY, "jit(step)/copy", t + 60 * ms, t + 65 * ms),
        (FUSION.replace("fusion.12", "fusion.99"),
         "jit(step)/tdt.head/dot_general", t + 70 * ms, t + 85 * ms),
    ]
    return events, ("jit_step(123)", t, t + 90 * ms)


def test_part_of_takes_the_innermost_name_of_the_list(report):
    assert report.part_of("jit(f)/tdt.attn.proj/tdt.attn.core/mul",
                          PARTS) == "attn.core"
    assert report.part_of("jit(f)/while/body/add", PARTS) == "unscoped"
    assert report.part_of("jit(f)/tdt.nonsense/add", PARTS) == "unscoped"
    assert report.part_of("", PARTS) == report.part_of(None, PARTS)
    assert report.short_op(FUSION) == "fusion bf16[1024,4096]"
    assert report.short_op("_fp_local_kernel.6") == "_fp_local_kernel"


def test_the_reducer_over_two_runs_a_nested_while_and_an_unscoped_leaf(
        report):
    a, ma = _one_run(10.0)
    b, mb = _one_run(10.1)
    stray = (COPY, "", 9.99, 9.995)  # before either run
    table = report.reduce_device_parts(a + b + [stray], [ma, mb], PARTS)
    assert set(table) == {"jit_step(123)", "outside any program"}
    e = table["jit_step(123)"]
    assert e["runs"] == 2
    ms = {p: 1e3 * s / e["runs"] for p, s in e["parts"].items()}
    # the while's own 10 ms and the copy's 5 are in no part
    assert ms == pytest.approx({"attn.proj": 20.0, "attn.core": 30.0,
                                "head": 15.0, "unscoped": 15.0})
    assert 1e3 * e["total_s"] / 2 == pytest.approx(80.0)
    # busy is the union: the while covers its body, 60 + 5 + 15
    assert 1e3 * e["busy_s"] / 2 == pytest.approx(80.0)
    assert 1e3 * e["module_s"] / 2 == pytest.approx(90.0)
    # the largest operations, by short name and shape
    assert e["ops"]["attn.core"] == pytest.approx(
        {"fusion bf16[1024,4096]": 0.060})
    assert set(e["ops"]["unscoped"]) == {"while s32[]", "copy bf16[8,128]"}
    assert table["outside any program"]["parts"] == pytest.approx(
        {"unscoped": 0.005})


def _vint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message from (number, int | bytes | str) fields."""
    out = b""
    for num, val in fields:
        if isinstance(val, int):
            out += _vint(num << 3) + _vint(val)
        else:
            val = val.encode() if isinstance(val, str) else val
            out += _vint(num << 3 | 2) + _vint(len(val)) + val
    return out


def _xspace(modules=True):
    """A profile of one device plane by hand (xplane.proto's field
    numbers): one program run of 90 us holding a scoped fusion whose
    `tf_op` is a string, a kernel whose `tf_op` is a reference to a
    stat metadata's name, and a copy with none."""
    def stat_meta(i, name):
        return (5, _msg((1, i), (2, _msg((1, i), (2, name)))))

    def event_meta(i, name, *stats):
        return (4, _msg((1, i), (2, _msg(
            (1, i), (2, name), *((5, st) for st in stats)))))

    def event(meta, offset_us, dur_us):
        return (4, _msg((1, meta), (2, offset_us * 10**6),
                        (3, dur_us * 10**6)))

    plane = _msg(
        (1, 7), (2, "/device:TPU:0"),
        stat_meta(1, "tf_op"), stat_meta(2, "flops"),
        stat_meta(3, "jit(step)/tdt.attn.core/pallas_call:"),
        event_meta(10, "jit_step(99)"),
        event_meta(11, FUSION,
                   _msg((1, 2), (4, 4096)),
                   _msg((1, 1), (5, "jit(step)/tdt.head/dot_general:"))),
        event_meta(12, "%_fp_local_kernel.6 = bf16[8,128,4096]{2,1,0} "
                   "custom-call(%a)", _msg((1, 1), (7, 3))),
        event_meta(13, COPY),
        *([(3, _msg((1, 1), (2, "XLA Modules"), (3, 5_000),
                    event(10, 0, 90)))] if modules else []),
        (3, _msg((1, 2), (2, "XLA Ops"), (3, 5_000),
                 event(11, 0, 30), event(12, 30, 40), event(13, 80, 10))))
    host = _msg((1, 8), (2, "/host:CPU"),
                (3, _msg((1, 1), (2, "python"), event(1, 0, 5))))
    return _msg((1, host), (1, plane))


def test_the_loader_reads_op_names_off_the_event_metadata(report, tmp_path,
                                                          capsys):
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(_xspace())
    events, modules, plane = report.load_device_ops(str(path))
    assert plane == "/device:TPU:0"
    assert modules == [("jit_step(99)", pytest.approx(5e-6),
                        pytest.approx(95e-6))]
    assert [(report.short_op(n), report.part_of(o, PARTS),
             round(1e6 * (b - a))) for n, o, a, b in events] == [
        ("fusion bf16[1024,4096]", "head", 30),
        ("_fp_local_kernel bf16[8,128,4096]", "attn.core", 40),
        ("copy bf16[8,128]", "unscoped", 10)]
    assert report.main(["--device-parts", str(path)]) == 0
    out = capsys.readouterr().out
    assert "jit_step(99): 1 runs" in out and "attn.core" in out
    assert "unscoped 12.50% of the busy time" in out
    # a profile without a device plane (one taken on the CPU)
    cpu = tmp_path / "cpu.xplane.pb"
    cpu.write_bytes(_msg((1, _msg((1, 8), (2, "/host:CPU")))))
    assert report.main(["--device-parts", str(cpu)]) == 1
    assert "no device plane" in capsys.readouterr().err
    # one whose device plane counts no program runs: nothing to divide by
    cpu.write_bytes(_xspace(modules=False))
    assert report.main(["--device-parts", str(cpu)]) == 1
    assert "'XLA Modules' line" in capsys.readouterr().err


def test_device_parts_on_a_malformed_file_exits_non_zero(report, tmp_path,
                                                         capsys):
    bad = tmp_path / "torn.xplane.pb"
    bad.write_bytes(b"not a profile")
    assert report.main(["--device-parts", str(bad)]) == 1
    assert "malformed artifact" in capsys.readouterr().err
    assert report.main(["--device-parts", str(tmp_path / "absent.pb")]) == 1
    capsys.readouterr()
