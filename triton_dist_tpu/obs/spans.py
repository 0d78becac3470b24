"""obs.spans — the serve plane's one host-span log.

A `SpanLog` is a bounded ring of closed host spans on
`time.perf_counter_ns`, the clock of `Scheduler.history`,
`Request.phase_ns` and `Request.token_times`:

    SpanRecord(id, parent, name, t0_ns, t1_ns, step, request, counts)

`counts` is what the span's boundary knows beside its two stamps: a
small mapping of name -> int handed over at entry (`worker.step`
carries `width`, which compiled program ran, and `rows`, the valid
rows it held; a `jit.*` record's one entry, `fun`, is a str), None
where a span has nothing to count. Ratios are then taken where the
work happens, over the records themselves.

Always on: the scheduler and the worker write it at every step, with
no switch. `log.span(name)` stamps, nests (the parent is the innermost
span this thread has open) and appends one tuple on exit; it also holds
a `jax.profiler.TraceAnnotation("tdt." + name)` open for the same
interval, so any profiler session — an operator's, the benchmark's
traced run — shows the same spans on the device's clock. With no
session running the annotation is a flag test. `log.add(...)` records
what a `with` block cannot: a span that opened in another thread (a
request's queued phase opens in the client's `submit`), a span known
only once it has failed (a retried attempt), a zero-length mark.

Self time (docs/observability.md): a span's duration less what the
records naming it as `parent` cover. Only `span()` sets a parent, so
children always lie inside their parent; `add()` records are roots.

`jit.*` records: one process-wide `jax.monitoring` listener, installed
the first time a scheduler makes its log (`new_default_log`), writes a
root record into `default_log()` for each function JAX reports as
traced (`jit.trace`), lowered (`jit.lower`), compiled by the backend
(`jit.compile`) or loaded from the persistent cache (`jit.cache_load`):
t1 the moment of the report, t0 that less the reported seconds, `step`
the log's newest `worker.step`, the function's name under `counts`'
"fun". A `jit.trace` is written for an OUTERMOST trace only: JAX
reports one for every jitted helper a traced function calls, inside
that function's own seconds, so a report that arrives while its thread
still has a trace open writes nothing (the records' seconds then add
up, and a step's first call is one record, not two thousand). A
compile inside a served window is then an interval on the log's clock
beside the `worker.launch` it stalled.

Every `Scheduler` has a log of its own (`new_default_log()`), as it
has a registry of its own: request ids and step indices are per
scheduler. `default_log()` is the newest of those logs, held here so
that it outlives its scheduler for a reader that runs once the
scheduler is freed.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import List, Mapping, NamedTuple, Optional, Tuple

import jax
from jax.profiler import TraceAnnotation

ANNOTATION_PREFIX = "tdt."


class SpanRecord(NamedTuple):
    id: int
    parent: Optional[int]   # id of the enclosing span() of this thread
    name: str
    t0_ns: int
    t1_ns: int
    step: Optional[int]     # worker.n_steps when the round began
    request: Optional[int]  # Request.request_id
    counts: Optional[Mapping] = None  # name -> int (jit.*: str), at entry


class _OpenSpan:
    """One `with log.span(...)` block."""

    __slots__ = ("_log", "_name", "_step", "_request", "_counts", "_id",
                 "_parent", "_t0", "_annotation")

    def __init__(self, log, name, step, request, counts):
        self._log, self._name = log, name
        self._step, self._request, self._counts = step, request, counts

    def __enter__(self):
        log = self._log
        stack = log._stack()
        self._parent = stack[-1] if stack else None
        self._id = next(log._ids)
        stack.append(self._id)
        self._annotation = TraceAnnotation(ANNOTATION_PREFIX + self._name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        self._log._stack().pop()
        self._log._append(SpanRecord(
            self._id, self._parent, self._name, self._t0, t1, self._step,
            self._request, self._counts))
        return False


class SpanLog:
    def __init__(self, cap: int = 65536):
        self.cap = cap
        # records lost off the old end of the ring. Exact with one
        # writing thread (a scheduler's serving thread); with several
        # writers on one log two simultaneous drops may count as one
        self.dropped = 0
        # the step of the newest `worker.step` (the worker sets it):
        # what a `jit.*` record, written from outside any span, names
        self.step: Optional[int] = None
        self._ring: deque = deque(maxlen=cap)
        self._ids = itertools.count()  # next() is atomic
        self._local = threading.local()

    def _stack(self) -> List[int]:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def _append(self, rec: SpanRecord) -> None:
        if len(self._ring) == self.cap:
            self.dropped += 1
        self._ring.append(rec)

    def span(self, name: str, step: Optional[int] = None,
             request: Optional[int] = None,
             counts: Optional[Mapping] = None) -> _OpenSpan:
        """Context manager: one record from entry to exit (an exception
        closes it too), child of the span this thread has open."""
        return _OpenSpan(self, name, step, request, counts)

    def add(self, name: str, t0_ns: int, t1_ns: int,
            step: Optional[int] = None,
            request: Optional[int] = None,
            counts: Optional[Mapping] = None) -> None:
        """Record a span from its two stamps (t0 == t1: a mark)."""
        self._append(SpanRecord(next(self._ids), None, name, t0_ns, t1_ns,
                                step, request, counts))

    def records(self) -> List[SpanRecord]:
        """A snapshot, oldest first by the time each record CLOSED (a
        parent closes after its children)."""
        return list(self._ring)

    def __len__(self) -> int:
        return len(self._ring)

    def triples(self) -> List[Tuple[str, int, int]]:
        """The `(name, t0_ns, t1_ns)` view `trace.collect.Timeline`
        and the Perfetto exports take. A triple has no field for the
        request, so a request's record is named `req<N>/<phase>`: the
        track `write_request_trace` files it under."""
        return [(f"req{r.request}/{r.name[4:]}"
                 if r.request is not None and r.name.startswith("req.")
                 else r.name, r.t0_ns, r.t1_ns) for r in self.records()]


_default = SpanLog()


def new_default_log() -> SpanLog:
    """A new log, which `default_log()` gives until the next call: a
    `Scheduler` makes its own with this."""
    global _default
    _default = SpanLog()
    install_jit_listener()
    return _default


def default_log() -> SpanLog:
    """The log of the scheduler built last (an empty one before the
    first). The reference is to the log, not to the scheduler."""
    return _default


# -- jit.* records ----------------------------------------------------------

# the durations JAX 0.9 reports of a function (`jax._src.dispatch`,
# `jax._src.compiler`), each with `fun_name=` but the cache's
_JIT_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jit.cache_load",
}
_jit_local = threading.local()
_jit_listener_installed = False


def _on_jit_duration(event: str, seconds: float, **kw) -> None:
    """A listener cannot be taken off again: a dict lookup for every
    other event, and nothing it does may raise into JAX's compile."""
    name = _JIT_EVENTS.get(event)
    if name is None:
        return
    try:
        if name == "jit.cache_load":
            # the cache reports its hit INSIDE the backend-compile
            # stretch of the same thread, without the function's name:
            # that stretch, which follows, is the load's record
            _jit_local.hit = True
            return
        if name == "jit.compile" and getattr(_jit_local, "hit", False):
            _jit_local.hit = False
            name = "jit.cache_load"
        if name == "jit.trace" and not jax.core.trace_ctx.is_top_level():
            return  # a helper's, inside the seconds of the trace still open
        t1 = time.perf_counter_ns()
        fun = kw.get("fun_name")
        log = _default
        log.add(name, t1 - int(seconds * 1e9), t1, step=log.step,
                counts=None if fun is None else {"fun": str(fun)})
    except Exception:  # noqa: BLE001 — never into the compile
        pass


def install_jit_listener() -> None:
    """Register the `jit.*` listener with `jax.monitoring`, once a
    process however often it is called."""
    global _jit_listener_installed
    if _jit_listener_installed:
        return
    _jit_listener_installed = True
    jax.monitoring.register_event_duration_secs_listener(_on_jit_duration)
