"""Symbolic protocol capture — the front half of the static verifier.

`capturing(n)` opens a capture context: while it is active, the
`lang/shmem.py` primitives (`putmem_nbi`, `putmem_signal_nbi`,
`getmem_nbi`, `signal`, `signal_local`, `signal_wait_until`,
`barrier_all`, `neighbor_barrier`, `fcollect[_slots]`, `broadcast`)
RECORD a symbolic per-rank op sequence instead of executing, and this
module's `ref`/`sem`/`copy`/`read`/`write`/`when`/`tag` helpers supply
the pieces the shmem surface does not name (symmetric-buffer handles,
local async copies, raw ref access annotations, rank-divergent guards).

The recorded program is ONE op list parameterized over the rank symbol
`me` (every rank runs the same SPMD text); `engine.concretize`
evaluates it per rank at a small concrete team size. Loops over the
team (`range(1, n)`) run in python at capture time — `n` is concrete —
so only `me` (and anything derived from it) stays symbolic.

Zero cost when off: with no active capture, `active()` is None and the
shmem primitives take their normal device path untouched; capture adds
exactly one None-check per primitive call at TRACE time (never at run
time — the check is python, not program). tests/test_verify.py enforces
bit-identical outputs and unchanged pallas_call_count.

This module is dependency-free (no jax) so `lang/shmem.py` can import
it without cycles.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
from typing import Any, List, Optional, Tuple


# -- symbolic integer/boolean expressions -------------------------------------


class Sym:
    """Tiny symbolic scalar: an expression tree over int constants and
    named variables (`me`, plus anything a protocol introduces),
    evaluated by `ev` under a concrete environment. Supports the
    arithmetic the protocol models need (+ - * % // neg) and the
    comparisons `when()` guards take (== != < <= > >=).

    NOTE: `==`/`!=` build expressions (like jnp arrays), so Sym objects
    are not hashable/comparable as python values — keep them out of
    dict keys and sets.
    """

    __slots__ = ("op", "args")
    __hash__ = None  # rich comparisons build expressions

    def __init__(self, op: str, args: tuple):
        self.op = op
        self.args = args

    # construction helpers
    @staticmethod
    def var(name: str) -> "Sym":
        return Sym("var", (name,))

    @staticmethod
    def const(v: int) -> "Sym":
        return Sym("const", (int(v),))

    def _bin(self, op, other, swap=False):
        a, b = as_sym(other), self
        if not swap:
            a, b = b, a
        return Sym(op, (a, b))

    def __add__(self, o):
        return self._bin("+", o)

    def __radd__(self, o):
        return self._bin("+", o, swap=True)

    def __sub__(self, o):
        return self._bin("-", o)

    def __rsub__(self, o):
        return self._bin("-", o, swap=True)

    def __mul__(self, o):
        return self._bin("*", o)

    def __rmul__(self, o):
        return self._bin("*", o, swap=True)

    def __mod__(self, o):
        return self._bin("%", o)

    def __rmod__(self, o):
        return self._bin("%", o, swap=True)

    def __floordiv__(self, o):
        return self._bin("//", o)

    def __neg__(self):
        return Sym("-", (Sym.const(0), self))

    def __eq__(self, o):  # type: ignore[override]
        return self._bin("==", o)

    def __ne__(self, o):  # type: ignore[override]
        return self._bin("!=", o)

    def __lt__(self, o):
        return self._bin("<", o)

    def __le__(self, o):
        return self._bin("<=", o)

    def __gt__(self, o):
        return self._bin(">", o)

    def __ge__(self, o):
        return self._bin(">=", o)

    def __repr__(self):
        if self.op == "var":
            return self.args[0]
        if self.op == "const":
            return str(self.args[0])
        return f"({self.args[0]!r} {self.op} {self.args[1]!r})"


_OPS = {
    "+": lambda a, b: a + b,
    "-": lambda a, b: a - b,
    "*": lambda a, b: a * b,
    "%": lambda a, b: a % b,
    "//": lambda a, b: a // b,
    "==": lambda a, b: a == b,
    "!=": lambda a, b: a != b,
    "<": lambda a, b: a < b,
    "<=": lambda a, b: a <= b,
    ">": lambda a, b: a > b,
    ">=": lambda a, b: a >= b,
}


def as_sym(v) -> Sym:
    if isinstance(v, Sym):
        return v
    return Sym.const(v)


def ev(x, env: dict):
    """Evaluate a Sym (or pass through a python int/bool) under env."""
    if not isinstance(x, Sym):
        return x
    if x.op == "var":
        try:
            return env[x.args[0]]
        except KeyError:
            raise KeyError(
                f"unbound symbol {x.args[0]!r} at concretization "
                f"(env has {sorted(env)})"
            ) from None
    if x.op == "const":
        return x.args[0]
    return _OPS[x.op](ev(x.args[0], env), ev(x.args[1], env))


# -- symbolic refs / semaphores ----------------------------------------------


@dataclasses.dataclass(frozen=True)
class Slot:
    """A (buffer-or-semaphore, index-tuple) region. Indices may be Syms;
    region granularity is whatever the protocol model partitions the ref
    into — two accesses conflict only when their evaluated tuples are
    equal (disjoint-by-construction slices get distinct tuples)."""

    name: str
    idx: Tuple[Any, ...] = ()

    def key(self, env: dict) -> tuple:
        return (self.name,) + tuple(int(ev(i, env)) for i in self.idx)

    def __repr__(self):
        if not self.idx:
            return self.name
        return f"{self.name}[{', '.join(map(repr, self.idx))}]"


class SymRef:
    """Symbolic symmetric buffer: `.at(*idx)` names a slot region."""

    def __init__(self, name: str):
        self.name = name

    def at(self, *idx) -> Slot:
        return Slot(self.name, tuple(idx))

    def __repr__(self):
        return f"ref({self.name})"


class SymSem(SymRef):
    """Symbolic semaphore (array); `.at(*idx)` names one counter."""

    def __repr__(self):
        return f"sem({self.name})"


def _slot(x, what: str) -> Slot:
    if isinstance(x, Slot):
        return x
    if isinstance(x, SymRef):
        return x.at()
    raise TypeError(
        f"{what}: expected a verify ref/sem slot (verify.ref(...).at(...)),"
        f" got {type(x).__name__} — protocol models must pass symbolic "
        "handles, real kernel refs cannot be captured"
    )


# -- recorded ops -------------------------------------------------------------

# op kinds (engine.concretize consumes these)
PUT = "put"              # remote DMA: read src@me, write dst@pe, S/D tokens
COPY = "copy"            # local async copy: read src, write dst, token
SIGNAL = "signal"        # semaphore increment on rank `pe` (pe=None: me)
WAIT = "wait"            # consuming local semaphore wait
WAIT_SEND = "wait_send"  # PutHandle.wait_send (sugar: WAIT on send slot)
WAIT_RECV = "wait_recv"  # PutHandle.wait_recv (sugar: WAIT on recv slot)
BARRIER = "barrier"      # full-team barrier cut (matched by round)
READ = "read"            # raw ref read annotation
WRITE = "write"          # raw ref write annotation


@dataclasses.dataclass
class Op:
    kind: str
    # PUT/COPY: src, dst, send_sem, recv_sem / sem; SIGNAL/WAIT: sem,
    # amount (+ pe for SIGNAL); READ/WRITE: slot. All possibly symbolic.
    fields: dict
    guards: Tuple[Any, ...]  # Sym bool exprs; op active iff all true
    tag: Optional[dict]      # metadata (e.g. {'step': i, 'chunk': c})
    sid: int                 # capture-order id (stable handle linkage)

    def __repr__(self):
        g = f" if {list(self.guards)}" if self.guards else ""
        return f"<{self.kind} {self.fields}{g}>"


class SymPutHandle:
    """Capture-side PutHandle: records the matched waits. wait_recv
    waits THIS rank's incoming delivery on the same (symmetric) recv
    slot — the 'my put's recv is my inbox' SPMD symmetry of the real
    PutHandle."""

    def __init__(self, cap: "Capture", op: Op):
        self._cap = cap
        self._op = op

    def wait_send(self):
        self._cap.record(WAIT_SEND, sem=self._op.fields["send_sem"],
                         amount=1, origin=self._op.sid)

    def wait_recv(self):
        self._cap.record(WAIT_RECV, sem=self._op.fields["recv_sem"],
                         amount=1, origin=self._op.sid)

    def wait(self):
        self.wait_send()
        self.wait_recv()


class SymCopyHandle:
    def __init__(self, cap: "Capture", op: Op):
        self._cap = cap
        self._op = op

    def wait(self):
        self._cap.record(WAIT, sem=self._op.fields["sem"], amount=1,
                         origin=self._op.sid)


# -- the capture context ------------------------------------------------------


class Capture:
    """One recorded symbolic protocol: the SPMD op list + team size."""

    def __init__(self, n: int):
        if n < 2:
            raise ValueError(f"capture needs a team (n >= 2), got n={n}")
        self.n = int(n)
        self.ops: List[Op] = []
        self._guards: List[Any] = []
        self._tags: List[dict] = []
        self._ids = itertools.count()

    # rank/team symbols
    @property
    def me(self) -> Sym:
        return Sym.var("me")

    def record(self, kind: str, **fields) -> Op:
        tag: Optional[dict] = None
        if self._tags:
            tag = {}
            for t in self._tags:
                tag.update(t)
        op = Op(kind=kind, fields=fields, guards=tuple(self._guards),
                tag=tag, sid=next(self._ids))
        self.ops.append(op)
        return op

    # structured recorders used by shmem + the api helpers
    def put(self, dst, src, send_sem, recv_sem, pe) -> SymPutHandle:
        op = self.record(
            PUT, src=_slot(src, "put src"), dst=_slot(dst, "put dst"),
            send_sem=_slot(send_sem, "put send_sem"),
            recv_sem=_slot(recv_sem, "put recv_sem"), pe=pe,
        )
        return SymPutHandle(self, op)

    def copy(self, dst, src, sem) -> SymCopyHandle:
        op = self.record(
            COPY, src=_slot(src, "copy src"), dst=_slot(dst, "copy dst"),
            sem=_slot(sem, "copy sem"),
        )
        return SymCopyHandle(self, op)

    def signal(self, sem, amount, pe=None):
        self.record(SIGNAL, sem=_slot(sem, "signal sem"), amount=amount,
                    pe=pe)

    def wait(self, sem, amount):
        self.record(WAIT, sem=_slot(sem, "wait sem"), amount=amount,
                    origin=None)

    def barrier(self):
        self.record(BARRIER)

    def read(self, slot):
        self.record(READ, slot=_slot(slot, "read"))

    def write(self, slot):
        self.record(WRITE, slot=_slot(slot, "write"))

    @contextlib.contextmanager
    def when(self, cond):
        """Guard recorded ops on a symbolic predicate — the capture-side
        `pl.when` for rank-divergent protocols (broadcast root/non-root,
        p2p src/dst)."""
        self._guards.append(as_sym(cond))
        try:
            yield
        finally:
            self._guards.pop()

    @contextlib.contextmanager
    def tagging(self, **meta):
        """Attach metadata to every op recorded inside (nested tags
        merge). The engine carries tags onto HB edges — the verify-side
        half of the shared verify/trace event classification
        (trace.events.VERIFY_OP_REGIONS)."""
        self._tags.append(meta)
        try:
            yield
        finally:
            self._tags.pop()


_ACTIVE: Optional[Capture] = None


def active() -> Optional[Capture]:
    """The capture in effect (None = capture off — the normal path)."""
    return _ACTIVE


@contextlib.contextmanager
def capturing(n: int):
    """`with capturing(n) as cap:` — shmem primitives called inside
    record onto `cap.ops` instead of executing. Not reentrant."""
    global _ACTIVE
    if _ACTIVE is not None:
        raise RuntimeError("verify.capturing() blocks do not nest")
    _ACTIVE = cap = Capture(n)
    try:
        yield cap
    finally:
        _ACTIVE = None


def _require() -> Capture:
    if _ACTIVE is None:
        raise RuntimeError(
            "this verify helper is only meaningful inside a "
            "verify.capturing() block"
        )
    return _ACTIVE


# -- module-level protocol-author API (delegates to the active capture) -------


def ref(name: str) -> SymRef:
    return SymRef(name)


def sem(name: str) -> SymSem:
    return SymSem(name)


def me() -> Sym:
    """The rank symbol (shmem.my_pe under capture returns the same)."""
    _require()
    return Sym.var("me")


def nranks() -> int:
    return _require().n


def copy(dst, src, sem_slot) -> SymCopyHandle:
    """Local async copy (the pltpu.make_async_copy analog): reads src,
    writes dst, completion increments sem_slot; `.wait()` consumes it."""
    return _require().copy(dst, src, sem_slot)


def read(slot) -> None:
    """Annotate a raw ref read at this program point."""
    _require().read(slot)


def write(slot) -> None:
    """Annotate a raw ref write at this program point."""
    _require().write(slot)


def when(cond):
    return _require().when(cond)


def tag(**meta):
    return _require().tagging(**meta)
