"""Failure classification of the guarded-execution plane (docs/robustness.md).

Every guard in the framework converts a would-be hang or silent
corruption into exactly one of these exception classes, raised HOST-side
with the decoded evidence attached — the "fails loudly and attributably"
contract. Kernels never raise (they cannot); they write structured guard
rows (faults/guard.py) that the host decodes into these.
"""

from __future__ import annotations

from typing import List, Optional


class FaultError(RuntimeError):
    """Base class of every guarded-execution failure. The serve
    scheduler's degradation ladder (retry -> quarantine) catches this
    class — a FaultError is by definition a failure the plane knows how
    to degrade around, unlike a programming error, which stays loud."""


class DeadlineExceeded(FaultError):
    """A bounded-wait watchdog tripped: a semaphore wait (delivery,
    credit, barrier) did not satisfy within the kernel's deadline.
    Carries the decoded guard rows — (rank, site, slot, progress,
    expected, observed) per trip — so the failure is attributable to a
    specific semaphore slot on a specific rank."""

    def __init__(self, message: str, trips: Optional[List] = None):
        super().__init__(message)
        self.trips = list(trips or [])


class WireIntegrityError(FaultError):
    """A wire image failed its checksum at the consume edge: the payload
    or scale stripe was corrupted in flight (or by an injected bit
    flip). Carries the failing row indices when known."""

    def __init__(self, message: str, rows: Optional[List[int]] = None):
        super().__init__(message)
        self.rows = list(rows or [])
