"""Attention's share of its roofline in a hybrid decoder's full
attention blocks (work `flash_prefill_full_layers`)."""

from perfbench.metrics._roofline import roofline_pct


def read(run):
    return roofline_pct(run, "flash_prefill_full_layers")
