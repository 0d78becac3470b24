"""triton_dist_tpu.obs — always-on telemetry: metrics registry,
in-kernel stat rows, flight recorder, SLO health.

The SECOND tier of the observability story (docs/observability.md).
`trace/` is the deep-dive tier: opt-in per run, full event streams,
offline decode. `obs/` is the tier you leave ON under production
traffic:

  registry  counters / gauges / fixed-log-bucket histograms —
            pure-numpy, thread-safe, snapshot/delta/merge. The serve
            plane streams TTFT/TPOT at retirement, queue/pool/slot
            gauges per step, and admission/eviction/preemption/retry/
            quarantine/guard-trip counters by site.
  stats     O(1) in-kernel stat rows (one trailing (1, 8) SMEM row per
            core instead of a full trace buffer): accumulated
            sem_wait/dma_wait vticks, wire bytes by format, guard-trip
            counts — test-pinned to agree with `trace.attribution`'s
            per-region sums when both builds coexist on one run.
            Metered families: ag_gemm, the two-shot-AR ring legs
            (ring RS + ring AG, native and wire), LL-AG.
  recorder  flight recorder: a bounded ring of step snapshots
            (registry deltas + guard rows + scheduler state) dumped
            automatically on quarantine / DeadlineExceeded so every
            faults-plane trip ships its context.
  health    rolling-window SLO rules (ttft_p99, tokens/s floor,
            guard-trip rate) evaluated into a structured HealthStatus;
            `action="degrade"` rules feed the PR-9 degradation ladder
            (guard.degrade -> fallback="xla" routes).
  spans     the serve plane's host-span log: a bounded ring of
            (id, parent, name, t0, t1, step, request) records the
            scheduler and the worker write every step, mirrored as
            `tdt.*` annotations into any running jax.profiler session.
  export    Prometheus text format + JSON snapshots (the examples/11
            socket server's `/metrics` command; scripts/trace_report.py
            --metrics renders both snapshot and flight-dump files).

Zero cost when off (the trace/verify/faults discipline, test-enforced):
no active `obs.stats.building()` block means every metered kernel
traces a byte-identical program with unchanged `pallas_call_count`;
with metering ON, `bench.py --obs` hard-asserts the overhead on the
ag_gemm chain under 3%.
"""

from triton_dist_tpu.obs.registry import (  # noqa: F401
    Histogram,
    Registry,
    SNAPSHOT_MAGIC,
    log_buckets,
)
from triton_dist_tpu.obs import stats  # noqa: F401
from triton_dist_tpu.obs.stats import (  # noqa: F401
    KernelStats,
    STAT_WORDS,
    metered,
    record_stats,
)
from triton_dist_tpu.obs import trend  # noqa: F401
from triton_dist_tpu.obs.recorder import (  # noqa: F401
    FLIGHT_MAGIC,
    FlightRecorder,
    check_dump,
    load_dump,
)
from triton_dist_tpu.obs.health import (  # noqa: F401
    CRITICAL,
    DEGRADED,
    HEALTHY,
    HealthStatus,
    SLOMonitor,
    SLORule,
)
from triton_dist_tpu.obs.spans import (  # noqa: F401
    SpanLog,
    SpanRecord,
    default_log,
)
from triton_dist_tpu.obs.export import (  # noqa: F401
    load_snapshot,
    to_json,
    to_prometheus,
    write_snapshot,
)
