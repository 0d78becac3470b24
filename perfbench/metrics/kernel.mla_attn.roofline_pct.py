"""Latent attention's share of its roofline in the latent-attention
blocks (work `mla_attn`: the fewest operations and bytes ANY form
needs, so the absorbed form the program runs cannot pass 100%)."""

from perfbench.metrics._roofline import roofline_pct


def read(run):
    return roofline_pct(run, "mla_attn")
