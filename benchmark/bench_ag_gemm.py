"""AG+GEMM sweep: fused kernel vs decomposed parts vs XLA reference.

TPU-native re-design of the reference's benchmark harness
(ref: benchmark/bench_allgather_gemm.py:60-127 — sweeps M and reports
torch ref / AG-only / GEMM-only / fused side by side so the overlap win
and each component's share are visible). Prints one table plus one JSON
line per row (driver-friendly).

Run:  python benchmark/bench_ag_gemm.py [--tpu] [--world N]
"""

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "examples"))
from common import bootstrap  # noqa: E402

jax, mesh = bootstrap(world=4)  # --world/--tpu parsed by bootstrap

import jax.numpy as jnp                                        # noqa: E402
import numpy as np                                             # noqa: E402
from jax.sharding import PartitionSpec as P                    # noqa: E402

from triton_dist_tpu.kernels import (                          # noqa: E402
    AgGemmConfig,
    ag_gemm,
    ag_gemm_ref,
    ring_all_gather,
)
from triton_dist_tpu.perf_model import estimate_ag_gemm_ms     # noqa: E402
from triton_dist_tpu.runtime.utils import (                    # noqa: E402
    chain_timer, slope_timer,
)

ON_TPU = jax.devices()[0].platform == "tpu"
# CPU interpret mode is ~1000x slower; keep shapes tiny there
MS = [2048, 4096, 8192] if ON_TPU else [64]
K = 5120 if ON_TPU else 128
# Qwen3-32B gate_up columns (ref bench shapes), divided per rank below
N_FULL = 6400 if ON_TPU else 512
DT = jnp.bfloat16 if ON_TPU else jnp.float32
K_HI = 101 if ON_TPU else 3


def _time(fn, a, b, a_spec=None):
    """Chain-timed per-iteration latency: k data-dependent calls inside
    one jit (RTT-proof; see runtime.utils.chain_timer). a_spec overrides
    the activation sharding (P(None) = pre-gathered/replicated)."""
    a_spec = P("tp") if a_spec is None else a_spec

    def build(k):
        def per_rank(a, b):
            def body(_, a):
                c = fn(a, b)
                # data dependency without changing the carried value
                return (a * (1.0 + 0.0 * jnp.sum(c.astype(jnp.float32)))
                        ).astype(a.dtype)

            out = jax.lax.fori_loop(0, k, body, a)
            return jnp.sum(out.astype(jnp.float32)).reshape(1)

        return jax.jit(jax.shard_map(
            per_rank, mesh=mesh, in_specs=(a_spec, P(None, "tp")),
            out_specs=P("tp"), check_vma=False,
        ))

    if ON_TPU:
        # long-chain Theil-Sen slopes (robust to two-sided per-call
        # overhead jitter; see runtime.utils.slope_timer)
        ms, _ = slope_timer(build, (a, b), ks=(1, K_HI // 2 + 1, K_HI))
    else:
        ms, _ = chain_timer(build, (a, b), k_hi=K_HI, pairs=2, warmup=2)
    return ms


def main():
    n = int(mesh.shape["tp"])
    N = N_FULL // n
    rng = np.random.default_rng(0)
    print(f"{'M':>6} {'xla_ms':>9} {'ag_ms':>9} {'gemm_ms':>9} "
          f"{'fused_ms':>9} {'model_ms':>9} {'speedup':>8}")
    for m in MS:
        a = jnp.asarray(rng.standard_normal((m, K)) * 0.02, DT)
        b = jnp.asarray(rng.standard_normal((K, N)) * 0.02, DT)
        cfg = AgGemmConfig(tile_m=min(1024, m // n),
                           tile_n=min(640, N), tile_k=min(1024, K))

        xla_ms = _time(lambda a, b: ag_gemm_ref(a, b, "tp"), a, b)
        ag_ms = _time(lambda a, b: ring_all_gather(a, "tp"), a, b)
        # pure-GEMM share: dot on the PRE-gathered (replicated) activation
        gemm_ms = _time(
            lambda a, b: jnp.dot(
                a, b, preferred_element_type=jnp.float32).astype(DT),
            a, b, a_spec=P(None))
        fused_ms = _time(
            lambda a, b: ag_gemm(a, b, "tp", config=cfg,
                                 force_kernel=True), a, b)
        model_ms = estimate_ag_gemm_ms(m, K, N, n, DT)
        print(f"{m:>6} {xla_ms:>9.3f} {ag_ms:>9.3f} {gemm_ms:>9.3f} "
              f"{fused_ms:>9.3f} {model_ms:>9.3f} "
              f"{xla_ms / fused_ms:>8.3f}")
        print(json.dumps({
            "bench": "ag_gemm", "m": m, "k": K, "n": N, "world": n,
            "xla_ms": round(xla_ms, 4), "ag_only_ms": round(ag_ms, 4),
            "gemm_only_ms": round(gemm_ms, 4),
            "fused_ms": round(fused_ms, 4),
            "model_ms": round(model_ms, 4),
        }))


if __name__ == "__main__":
    main()
