"""triton_dist_tpu.faults — guarded execution: deterministic fault
injection, bounded-wait watchdogs, graceful degradation.

The framework's thesis is explicit semaphore-granular overlap — which
means a single dropped signal, corrupted wire image, or stalled peer
hangs a kernel forever unless something bounds the wait. This package
is the robustness plane around that thesis (docs/robustness.md):

  plan    `FaultPlan` + `injecting()` — `shmem.straggler_delay`
          generalized into schedulable fault classes (delayed send,
          stalled rank, dropped signal, bit-flipped wire payload/scale,
          failed serve step) injected at the shmem-primitive layer, so
          every registered protocol chaos-tests without kernel changes.
  guard   `building()` — bounded-wait watchdogs on every
          signal_wait_until / barrier / delivery wait of the
          instrumented kernel families; on trip the kernel writes a
          structured error row and the host raises `DeadlineExceeded`
          (`guard.check`). Plus the degradation registry behind the
          collective entry points' `fallback="xla"` route.
  chaos   the (fault class x protocol) matrix harness: every cell must
          be detected-and-recovered or a loud structured error — never
          a hang, never a silently wrong result. Wired into
          `__graft_entry__`'s dryrun plane and tests/test_faults.py.
  errors  `FaultError` / `DeadlineExceeded` / `WireIntegrityError`.

Everything is zero-cost when off: no active plan and no active guard
build means every primitive takes its original code path — bit-identical
programs, unchanged `pallas_call_count` (test-enforced, the
trace/verify discipline).
"""

from triton_dist_tpu.faults.errors import (  # noqa: F401
    DeadlineExceeded,
    FaultError,
    WireIntegrityError,
)
from triton_dist_tpu.faults.guard import (  # noqa: F401
    GMAGIC,
    GUARD_WORDS,
    SITES,
    GuardBuild,
    GuardCtx,
    GuardTrip,
    building,
    check,
    decode,
    degrade,
    degraded,
    is_degraded,
    reset_degraded,
    site_name,
)
from triton_dist_tpu.faults.guard import (  # noqa: F401
    active_build as active_guard_build,
)
from triton_dist_tpu.faults.plan import (  # noqa: F401
    BitFlipPayload,
    BitFlipScale,
    DelayedSend,
    DroppedSignal,
    FailStep,
    FaultPlan,
    StalledRank,
    active,
    injecting,
)
