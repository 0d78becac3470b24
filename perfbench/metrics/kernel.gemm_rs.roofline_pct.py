"""The row-parallel projections with their reduce-scatter against
their roofline (work `gemm_rs`; interconnect bytes among the bounds)."""

from perfbench.metrics._roofline import roofline_pct


def read(run):
    return roofline_pct(run, "gemm_rs")
