"""Operation and byte counts against hand counts at one small shape,
the formula evaluator, and the peaks table."""

import json
import os

import pytest

from perfbench import work

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SIZES = dict(L=2, H=8, I=16, V=32, hq=4, hkv=2, d=4, tp=2, b=2)


def test_flash_prefill_counts():
    spec = work.load(REPO, "flash_prefill")
    # one slot prefilling n=3 rows on ctx=5 cached tokens; per chip
    # 2 q heads, 1 kv head, d=4, 2 layers. Row i sees 5+i+1 keys:
    # 6+7+8 = 21 pairs; QK^T and PV are 2*d flops each per pair.
    need = work.step_needs(spec, SIZES, [(3, 5, False)])
    assert need["flops"] == 2 * 2 * (2 * 2 * 4) * 21
    # bytes: q read + o written (2 * 3 rows * 2 heads * 4) and k + v
    # read (2 * 8 tokens * 1 head * 4), 2 bytes each, 2 layers
    assert need["hbm_bytes"] == 2 * 2 * (2 * 3 * 2 * 4 + 2 * 8 * 1 * 4)
    assert need["ici_bytes"] == 0
    # a decode row is n=1: one row over ctx+1 keys
    one = work.step_needs(spec, SIZES, [(1, 9, True)])
    assert one["flops"] == 2 * 2 * (2 * 2 * 4) * 10


def test_gemm_rs_counts():
    spec = work.load(REPO, "gemm_rs")
    need = work.step_needs(spec, SIZES, [(3, 0, False), (1, 7, True)])
    rows, k = 4, 2 * 4 + 16 // 2  # o-proj K = 8, down K = 8 per chip
    assert need["flops"] == 2 * 2 * rows * k * 8
    assert need["hbm_bytes"] == 2 * 2 * (k * 8 + rows * k
                                         + 2 * rows * 8 / 2)
    # two reduce-scatters a layer; each chip sends (tp-1)/tp of (rows, H)
    assert need["ici_bytes"] == 2 * 2 * 2 * rows * 8 * (2 - 1) / 2


def test_model_step_counts():
    spec = work.load(REPO, "model_step")
    per_token = 2 * (8 * (4 + 2 * 2) * 4 + 4 * 4 * 8 + 3 * 8 * 16)
    need = work.step_needs(spec, SIZES, [(2, 3, True)])
    attn = 4 * 4 * 4 * (4 + 5)  # rows see 4 and 5 keys, all 4 heads
    assert need["flops"] == 2 * (2 * per_token + attn) + 2 * 8 * 32
    quiet = work.step_needs(spec, SIZES, [(2, 3, False)])
    assert need["flops"] - quiet["flops"] == 2 * 8 * 32  # the head


def test_least_seconds_names_the_binding_peak():
    peaks = dict(bf16_flops_per_s=100.0, hbm_bytes_per_s=10.0,
                 ici_bytes_per_s=1.0)
    assert work.least_seconds(
        dict(flops=1000.0, hbm_bytes=10.0, ici_bytes=0.0), peaks) \
        == (10.0, "flops")
    assert work.least_seconds(
        dict(flops=100.0, hbm_bytes=50.0, ici_bytes=2.0), peaks) \
        == (5.0, "hbm_bytes")
    assert work.least_seconds(
        dict(flops=100.0, hbm_bytes=10.0, ici_bytes=7.0), peaks) \
        == (7.0, "ici_bytes")


@pytest.mark.parametrize("expr", [
    "__import__('os').system('true')", "L.__class__", "[1, 2]",
    "open('x')", "lambda: 1", "L if H else I", "L ** 99"])
def test_formulae_are_arithmetic_only(expr):
    with pytest.raises((ValueError, SyntaxError)):
        work.evaluate(expr, SIZES)


def test_formula_values():
    assert work.evaluate("L * (H + I) / tp", SIZES) == 2 * 24 / 2
    assert work.evaluate("max(H, I) - min(H, I)", SIZES) == 8


def test_peaks_table_knows_the_v5e_and_refuses_an_unknown_kind():
    peaks = work.peaks_for(REPO, "TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["ici_bytes_per_s"] == 1600e9 / 8
    assert "source" in peaks
    for kind in ("cpu", "TPU v4", ""):
        with pytest.raises(KeyError):
            work.peaks_for(REPO, kind)


def test_every_work_has_an_implementation_or_is_the_whole_step():
    base = os.path.join(REPO, "perfbench", "work")
    for name in sorted(os.listdir(base)):
        spec = work.load(REPO, name)
        assert spec["name"] == name
        if name != "model_step":
            assert work.patterns(spec), name
        with open(os.path.join(base, name, "work.json")) as f:
            json.load(f)
