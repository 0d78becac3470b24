"""On-chip config sweep for the AG+GEMM consumer at the bench shape.

Usage: python benchmark/sweep_ag_gemm.py  (real TPU; ~minutes)
Prints one line per config: tiles, cache mode, median ms, ratio vs XLA.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import jax                                                     # noqa: E402
import jax.numpy as jnp                                        # noqa: E402
import numpy as np                                             # noqa: E402
from jax.sharding import PartitionSpec as P                    # noqa: E402

from triton_dist_tpu.kernels import (                          # noqa: E402
    AgGemmConfig, ag_gemm, ag_gemm_ref,
)
from triton_dist_tpu.runtime import make_mesh                  # noqa: E402
from triton_dist_tpu.runtime.utils import slope_ratio_timer    # noqa: E402

M, K, N = 2048, 5120, 6400


def make_build(mesh, cfg, order="arrival"):
    """Chain builder; cfg=None -> the unfused XLA reference."""
    def build(k):
        def per_rank(x, w):
            def body(_, c):
                if cfg is not None:
                    h = ag_gemm(c, w, axis="tp", config=cfg,
                                force_kernel=True, c_order=order)
                else:
                    h = ag_gemm_ref(c, w, axis="tp")
                # barrier: keep XLA from sinking the carry slice into
                # its dot (see bench.bench_ag_gemm_kernel)
                h = jax.lax.optimization_barrier(h)
                return h[:M, :K].astype(c.dtype)

            out = jax.lax.fori_loop(0, k, body, x)
            return jnp.sum(out.astype(jnp.float32)).reshape(1)

        return jax.jit(jax.shard_map(
            per_rank, mesh=mesh, in_specs=(P("tp"), P(None, "tp")),
            out_specs=P("tp"), check_vma=False))

    return build


def main():
    mesh = make_mesh(mesh_shape=(1,), axis_names=("tp",))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((M, K)) * 0.02, jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((K, N)) * 0.02, jnp.bfloat16)

    # each config is measured INTERLEAVED with the XLA reference
    # (slope_ratio_timer: long-chain medians + Theil-Sen slopes — per-call
    # overhead jitters two-sided, so short paired diffs are meaningless;
    # see runtime.utils.slope_timer).
    xla_build = make_build(mesh, None)
    xla_cache = {}

    def xla_memo(k):
        if k not in xla_cache:
            xla_cache[k] = xla_build(k)
        return xla_cache[k]

    sweeps = [
        ("dbuf  tm256  tn3200 tk512", AgGemmConfig(256, 3200, 512)),
        ("dbuf  tm512  tn3200 tk512", AgGemmConfig(512, 3200, 512)),
        ("dbuf  tm512  tn1280 tk1024", AgGemmConfig(512, 1280, 1024)),
        ("dbuf  tm1024 tn1280 tk512", AgGemmConfig(1024, 1280, 512)),
        ("dbuf  tm512  tn1280 tk512", AgGemmConfig(512, 1280, 512)),
        ("dbuf  tm1024 tn640 tk512", AgGemmConfig(1024, 640, 512)),
        ("cache tm512  tn1280 tk512",
         AgGemmConfig(512, 1280, 512, cache_a=True)),
        ("cache tm512  tn1280 tk1024",
         AgGemmConfig(512, 1280, 1024, cache_a=True)),
        ("cache tm1024 tn640 tk256",
         AgGemmConfig(1024, 640, 256, cache_a=True)),
    ]
    for label, cfg in sweeps:
        try:
            r, pm, xm = slope_ratio_timer(make_build(mesh, cfg),
                                          xla_memo, (x, w))
            print(f"{label:28s} {pm:7.4f} ms  ratio {r:.3f} "
                  f"(xla {xm:.4f})", flush=True)
        except Exception as e:
            print(f"{label:28s} FAILED {str(e)[:120]}", flush=True)


if __name__ == "__main__":
    main()
