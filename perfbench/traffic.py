"""The one traffic generator: a mix is a data file of parameters.

A mix file (`perfbench/traffic/<name>.json`) states the loop (closed or
open), the length distributions with their clips, how many distinct
requests the mix holds (`pool`) and, for an open loop, the arrival
rate. The set of (prompt, output) lengths and of inter-arrival gaps is
a pure function of the MIX: quantiles of the stated distributions,
paired by a fixed shuffle. `--seed` draws the token ids and the ORDER
in which that set is sent, so every seed offers the same work and two
seeds differ only in what meets what.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from statistics import NormalDist
from typing import List, Optional

import numpy as np

_SUFFIXES = (".json",)
_PAIRING_SEED = 20260930  # fixes which prompt length meets which output


@dataclasses.dataclass(frozen=True)
class Planned:
    """One request as the generator will send it."""

    index: int
    due_s: Optional[float]  # open loop: seconds after the window opens
    prompt: np.ndarray  # int32 token ids
    max_new_tokens: int


def load_mix(root: str, name: str) -> dict:
    for suffix in _SUFFIXES:
        path = os.path.join(root, "perfbench", "traffic", name + suffix)
        if os.path.exists(path):
            with open(path) as f:
                mix = json.load(f)
            mix["name"] = name
            return mix
    raise FileNotFoundError(f"no traffic mix file for {name!r} under "
                            "perfbench/traffic/")


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """n values of the stated distribution at the mid-quantiles
    (i + 0.5) / n, rounded and clipped to [min, max]."""
    u = (np.arange(n) + 0.5) / n
    kind = spec["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    elif kind == "uniform":
        vals = spec["min"] + u * (spec["max"] - spec["min"])
    elif kind == "fixed":
        vals = np.full(n, spec["value"], float)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo = spec.get("min", 1)
    hi = spec.get("max", float("inf"))
    return np.clip(np.rint(vals), lo, hi).astype(int)


def length_pool(mix: dict, max_len: int) -> List[tuple]:
    """The mix's fixed multiset of (prompt, output) lengths. An output
    is shortened where prompt + output would pass `max_len`."""
    n = int(mix["pool"])
    prompts = _quantiles(mix["prompt"], n)
    outputs = _quantiles(mix["output"], n)
    outputs = outputs[np.random.default_rng(_PAIRING_SEED).permutation(n)]
    pool = []
    for p, o in zip(prompts, outputs):
        p = int(min(p, max_len - 1))
        pool.append((p, int(max(1, min(o, max_len - p)))))
    return pool


def arrival_gaps(mix: dict, n: int) -> np.ndarray:
    """n inter-arrival gaps in seconds at `rate_per_s`: exponential
    quantiles (a Poisson process's gaps), the same set for every seed."""
    rate = float(mix["rate_per_s"])
    kind = mix.get("arrivals", "poisson")
    u = (np.arange(n) + 0.5) / n
    if kind == "poisson":
        gaps = -np.log1p(-u) / rate
        # the mid-quantile set's mean falls a little short of 1/rate
        return gaps * (1.0 / rate) / gaps.mean()
    if kind == "uniform":
        return np.full(n, 1.0 / rate)
    raise ValueError(f"unknown arrival process {kind!r}")


def plan(mix: dict, seed: int, vocab: int, max_len: int,
         horizon_s: float) -> List[Planned]:
    """Requests in sending order, enough to outlast `horizon_s`. The
    pool is run through in whole passes, each pass shuffled anew from
    the seed; an open loop gets due times from the gap set, shuffled
    the same way."""
    rng = np.random.default_rng(seed)
    pool = length_pool(mix, max_len)
    open_loop = mix["loop"] == "open"
    if open_loop:
        need = int(math.ceil(horizon_s * float(mix["rate_per_s"]) * 1.5)) + 8
    else:
        need = int(mix.get("max_requests", 1024))
    passes = -(-need // len(pool))
    out: List[Planned] = []
    t = 0.0
    for _ in range(passes):
        order = rng.permutation(len(pool))
        gaps = (arrival_gaps(mix, len(pool))[rng.permutation(len(pool))]
                if open_loop else None)
        for j, k in enumerate(order):
            p, o = pool[k]
            if open_loop:
                t += float(gaps[j])
                if t > horizon_s:
                    return out
            out.append(Planned(
                index=len(out), due_s=t if open_loop else None,
                prompt=rng.integers(0, vocab, p, dtype=np.int32),
                max_new_tokens=o))
            if len(out) >= need:
                return out
    return out
