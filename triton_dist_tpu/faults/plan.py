"""Deterministic fault-injection plans — `lang/shmem.straggler_delay`
generalized into schedulable fault classes.

A FaultPlan is a trace-time object (the same activation discipline as
`trace.building()` and `verify.capturing()`): kernels constructed inside
a `faults.injecting(plan)` block compile the scheduled faults in at the
shmem-primitive layer, so EVERY registered protocol can be chaos-tested
without touching kernel code. Outside a plan the primitives take their
original code paths — one None-check, bit-identical programs, unchanged
`pallas_call_count` (test-enforced).

Fault classes (the classification of docs/robustness.md):

  DelayedSend(rank, nanos)   one rank stalls between kernel entry and
                             its sends — the classic race provocation
                             (straggler_delay, now schedulable per
                             protocol). Outcome class: RECOVERED (skew
                             only; outputs exact).
  StalledRank(rank)          the same injection at a deadline-scale
                             delay: the rank is "down" for longer than
                             any watchdog budget. Outcome: RECOVERED on
                             the lockstep interpreter (skew), watchdog
                             DETECTED on hardware.
  DroppedSignal(rank, label) rank's explicit semaphore signals (credit
                             grants, barrier contributions, notify ops)
                             are masked to inc=0 — the lost-message
                             fault. Outcome: DETECTED (a watchdog trips;
                             never a hang, never a silent wrong answer).
  BitFlipPayload / BitFlipScale
                             one bit of a wire image's payload bytes /
                             scale stripe flips at the pack edge (after
                             checksum embedding, so integrity checking
                             can see it). Outcome: DETECTED on
                             checksummed formats (WireIntegrityError),
                             quantified-drift otherwise.
  FailStep(at_step, error)   host-level serve-plane fault: the Worker
                             raises `error` instead of running step
                             `at_step`. Drives the scheduler's
                             degradation ladder (retry -> quarantine).

The drop mask is VALUE-level (`inc * (me != rank)`), never control-flow
divergence — the masked signal is exact on both the interpreter and
hardware.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Optional, Tuple

from triton_dist_tpu.faults.errors import (
    DeadlineExceeded,
    WireIntegrityError,
)

# Delay scales (interpreter-churn ticks / TPU nanos — see
# shmem.straggler_delay for the mapping). A stalled rank sleeps ~50x a
# delayed sender: longer than any test watchdog budget, still bounded so
# the lockstep interpreter completes.
DELAY_NANOS = 200_000
STALL_NANOS = 10_000_000


@dataclasses.dataclass(frozen=True)
class DelayedSend:
    rank: int
    nanos: int = DELAY_NANOS
    protocol: Optional[str] = None  # None = any protocol


@dataclasses.dataclass(frozen=True)
class StalledRank:
    rank: int
    nanos: int = STALL_NANOS
    protocol: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class DroppedSignal:
    rank: int
    label: Optional[str] = None  # match a site label ("credit",
    # "barrier", ...); None = every explicit signal the rank issues


@dataclasses.dataclass(frozen=True)
class BitFlipPayload:
    row: int = 0
    byte: int = 0   # payload column (clamped to the row width)
    bit: int = 0


@dataclasses.dataclass(frozen=True)
class BitFlipScale:
    row: int = 0
    byte: int = 0   # offset within the scale stripe
    bit: int = 0


@dataclasses.dataclass(frozen=True)
class FailStep:
    at_step: int
    times: int = 1
    error: str = "deadline"  # "deadline" | "integrity"

    def exception(self):
        if self.error == "integrity":
            return WireIntegrityError(
                f"injected wire-integrity failure at serve step "
                f"{self.at_step}")
        return DeadlineExceeded(
            f"injected step deadline at serve step {self.at_step}")


FAULT_CLASSES = (DelayedSend, StalledRank, DroppedSignal, BitFlipPayload,
                 BitFlipScale, FailStep)


class FaultPlan:
    """A deterministic schedule of faults. Immutable fault specs plus
    small runtime counters (FailStep consumption) — one plan is one
    chaos experiment."""

    def __init__(self, *faults):
        for f in faults:
            if not isinstance(f, FAULT_CLASSES):
                raise TypeError(
                    f"unknown fault {f!r} (one of "
                    f"{[c.__name__ for c in FAULT_CLASSES]})")
        self.faults = tuple(faults)
        self._step_fired: dict = {}

    def __repr__(self):
        return f"FaultPlan{self.faults!r}"

    # -- shmem-layer queries (trace-time) -------------------------------

    def straggler_for(self, protocol: str) -> Optional[Tuple[int, int]]:
        """(rank, nanos) the named protocol should inject at its
        straggler hook, or None. StalledRank dominates DelayedSend."""
        pick = None
        for f in self.faults:
            if isinstance(f, (DelayedSend, StalledRank)) and (
                    f.protocol is None or f.protocol == protocol):
                if pick is None or isinstance(f, StalledRank):
                    pick = (f.rank, f.nanos)
        return pick

    def dropped_signal_rank(self, label: Optional[str]) -> Optional[int]:
        """The rank whose explicit signals at `label`-class sites are
        masked to inc=0, or None."""
        for f in self.faults:
            if isinstance(f, DroppedSignal) and (
                    f.label is None or f.label == label):
                return f.rank
        return None

    def wire_flips(self):
        return [f for f in self.faults
                if isinstance(f, (BitFlipPayload, BitFlipScale))]

    def take_wire_flips(self):
        """The scheduled bit-flips, consumed at the FIRST send-edge
        encode of the traced program (later encodes — e.g. the per-hop
        requantization of a reduction ring — pass clean, so exactly one
        corruption enters the wire)."""
        if getattr(self, "_flips_taken", False):
            return []
        flips = self.wire_flips()
        if flips:
            self._flips_taken = True
        return flips

    # -- host-layer queries ---------------------------------------------

    def step_fault(self, step_index: int):
        """Exception to raise instead of running serve step
        `step_index`, or None. Each FailStep fires `times` times."""
        for f in self.faults:
            if isinstance(f, FailStep) and f.at_step == step_index:
                fired = self._step_fired.get(id(f), 0)
                if fired < f.times:
                    self._step_fired[id(f)] = fired + 1
                    return f.exception()
        return None


def scheduled_straggler(protocol: str, given=None):
    """Entry-point helper: an explicitly passed straggler wins;
    otherwise the active plan's schedule for `protocol` (None when no
    plan — the zero-cost-off path)."""
    if given is not None:
        return given
    p = active()
    return p.straggler_for(protocol) if p is not None else None


_STATE = threading.local()


def active() -> Optional[FaultPlan]:
    """The plan in effect at TRACE time (None = no injection). Like
    trace.active_build(): kernels consult it when constructed; flipping
    it after a jit cached its executable has no effect on that
    executable — chaos tests build fresh programs inside the block."""
    return getattr(_STATE, "plan", None)


@contextlib.contextmanager
def injecting(plan: FaultPlan):
    """Activate `plan` for kernels traced inside the block."""
    prev = getattr(_STATE, "plan", None)
    _STATE.plan = plan
    try:
        yield plan
    finally:
        _STATE.plan = prev
