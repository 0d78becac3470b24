"""Grouped-query attention's share of its roofline in the window and
the global blocks together (work `window_gqa_attn`: a window block's
rows are held to the keys the window lets them see, and to the 2 w
cached positions a step reads at most)."""

from perfbench.metrics._roofline import roofline_pct


def read(run):
    return roofline_pct(run, "window_gqa_attn")
