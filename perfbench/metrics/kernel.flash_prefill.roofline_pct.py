"""Attention's share of its roofline (work `flash_prefill`)."""

from perfbench.metrics._roofline import roofline_pct


def read(run):
    return roofline_pct(run, "flash_prefill")
