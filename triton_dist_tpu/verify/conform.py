"""Kernel<->model conformance: prove the SHIPPED kernel implements the
registered protocol model (ISSUE 19 — closing the model-drift hole).

The static verifier (engine.py) proves race/deadlock/leak freedom over
hand-written protocol MODELS; its own docs named the resulting false
negative: a kernel change not mirrored in its model was invisible. This
module turns that caveat into a checked theorem:

  1. Under ``conform.recording()`` (the established zero-cost-off idiom
     of ``trace.building()`` / ``verify.capturing()``), every
     ``lang.core.tpu_call`` appends a trailing (1+cap, ROW_WORDS) i32
     SMEM output and the ``lang/shmem.py`` primitives append one row
     per sync op — kind, semaphore identity, peer, amount, destination
     byte extents — AS THE REAL KERNEL EXECUTES on the lockstep
     interpret mesh. Traced values (peers, slice starts) are stored by
     the device, so every rank's stream is CONCRETE even though the
     SPMD program is traced once.
  2. The checker concretizes the registered symbolic model at the same
     team size (engine.concretize — the exact machinery behind the
     PR-8 ``protocol_skeleton`` comparator) and demands per-rank stream
     equivalence: exact on the sync skeleton (op kinds, semaphore
     structure up to alpha-renaming, peers, amounts, program order
     modulo declared commutations) and region-consistent on data
     extents (puts the model sends to distinct slots must land in
     distinct/disjoint recorded regions; puts to the same slot must
     record identical extents).

Semaphore identity is compared by FIRST-USE canonicalization: the
model's slot keys and the kernel's (buffer, ref, index) triples are
each alpha-renamed to sequential ids in stream order, so "one shared
recv semaphore where the model declares per-step slots" diverges at
the first reuse — the drift class the mutants in tests/_mutants.py
seed. Ring-neighbor entry barriers are matched structurally (both
sides reduce to a reserved NBAR identity): the model shares one
symbolic ``__nbar__`` sem across barriers while the hardware scopes a
fresh collective semaphore per barrier, a naming difference with no
protocol content.

Zero cost when off: with no active recording, ``tpu_call`` takes its
original path (the instrument hook returns None before touching the
kwargs) and every shmem note is a single ``ctx() is None`` check at
trace time — instrumented kernels trace byte-identical programs
(pinned by tests/test_conform.py).

Known limits (docs/verification.md "Conformance"):
  - XLA-owned legs record nothing: kernels that route to lax
    collectives (broadcast under the legacy divergence-unsafe
    interpreter; the xslice DCN hop) are compared on their Pallas legs
    only, with the skip/scoping stated loudly per registration.
  - Region containment covers leading-dimension extents of DMA
    destinations; value-level semantics (what the bytes mean) stay
    with the numeric tests.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.lang import core as _core
from triton_dist_tpu.verify import capture as cap
from triton_dist_tpu.verify import engine

ROW_WORDS = 12
MAGIC = 0x7C0F  # 'conform' header tag (distinct from trace 0x7D7A)

# row kinds (word 0)
K_PUT = 1
K_SIG = 2
K_WAIT = 3
K_WSEND = 4
K_WRECV = 5
K_BAR = 6

# reserved semaphore token: ring-neighbor barrier sems (see module doc)
_NBAR_TOK = -9
NBAR = ("NBAR",)

# Row layouts (i32 words; unused words written 0 — SMEM is not
# zero-initialized, decode must never read an unwritten word):
#   PUT   [K_PUT, stok, sidx, rtok, ridx, peer, dtok, doff, dlen, nbytes]
#   SIG   [K_SIG, tok, idx, peer(-1=self), amount]
#   WAIT* [K_*,   tok, idx, amount]
#   BAR   [K_BAR]
# header row 0: [MAGIC, count, cap, collective_id(-1=none)]


# -- host-side activation context ---------------------------------------------


class Recording:
    """One active conformance recording: collects the trailing conform
    buffers of every tpu_call traced while active."""

    def __init__(self, cap_rows: int = 512):
        self.cap = int(cap_rows)
        self._stash: List[Any] = []

    def stash(self, buf) -> None:
        self._stash.append(buf)

    def collected(self) -> List[Any]:
        return list(self._stash)


_REC: Optional[Recording] = None


def active() -> Optional[Recording]:
    return _REC


@contextlib.contextmanager
def recording(cap_rows: int = 512):
    """Activate conformance recording for kernels traced inside the
    block. Contract: every ``tpu_call`` traced while active appends a
    trailing (1+cap, ROW_WORDS) i32 conform buffer output, stashed on
    the yielded Recording (``collected()``). Off = byte-identical
    programs."""
    global _REC
    prev = _REC
    _REC = Recording(cap_rows)
    try:
        yield _REC
    finally:
        _REC = prev


# -- in-kernel recorder (trace-time ambient) ----------------------------------


@dataclasses.dataclass
class ConformCtx:
    """Ambient during ONE instrumented kernel trace: the conform buffer
    ref, the cursor scratch, and the base-ref intern table (strong refs
    keep id() stable for the duration of the trace)."""

    buf: Any
    cur: Any
    cap: int
    interns: List[Any] = dataclasses.field(default_factory=list)
    _ids: Dict[int, int] = dataclasses.field(default_factory=dict)

    def intern(self, base) -> int:
        tok = self._ids.get(id(base))
        if tok is None:
            tok = len(self.interns)
            self._ids[id(base)] = tok
            self.interns.append(base)
        return tok


_CTX: Optional[ConformCtx] = None


def ctx() -> Optional[ConformCtx]:
    """The ambient recorder of the kernel trace in progress (None = the
    zero-cost-off path; every note below starts with this check)."""
    return _CTX


def _strides(shape: Tuple[int, ...]) -> Tuple[int, ...]:
    out, acc = [], 1
    for d in reversed(shape):
        out.append(acc)
        acc *= int(d)
    return tuple(reversed(out))


def _unwrap(ref):
    """(base ref, flat element offset, element count) of a possibly
    ``.at[...]``-transformed ref. Offsets may be traced (device writes
    the concrete value); counts and the base are static. A transform
    that cannot be read (bitcasts, gathered indexers) degrades to
    offset -1 / count -1, which the comparator skips conservatively."""
    transforms = []
    base = ref
    while hasattr(base, "transforms") and hasattr(base, "ref"):
        transforms = list(base.transforms) + transforms
        base = base.ref
    off: Any = 0
    known = True
    for t in transforms:
        idx = getattr(t, "indices", None)
        if idx is None:
            known = False
            break
        strides = _strides(tuple(t.shape))
        for k, ix in enumerate(idx):
            start = getattr(ix, "start", None)
            if start is not None:  # a Slice (possibly traced start)
                off = off + start * strides[k]
            else:  # an int index (traced even when written as a literal)
                off = off + ix * strides[k]
    try:
        count = 1
        for d in ref.shape:
            count *= int(d)
    except Exception:  # noqa: BLE001 - shape unavailable: degrade, never raise
        count = -1
    if not known:
        return base, -1, -1
    return base, off, count


def _ident(c: ConformCtx, sem_ref) -> Tuple[int, Any]:
    """(token, flat index) semaphore identity. The index may be traced;
    the device stores its per-rank concrete value."""
    base, off, _ = _unwrap(sem_ref)
    return c.intern(base), off


def _emit(c: ConformCtx, words: List[Any]) -> None:
    idx = c.cur[0]

    @pl.when(idx < c.cap)
    def _write():
        r = idx + 1
        for w in range(ROW_WORDS):
            v = words[w] if w < len(words) else 0
            c.buf[r, w] = jnp.asarray(v, jnp.int32)

    c.cur[0] = idx + 1
    c.buf[0, 1] = idx + 1  # total emits (count > cap flags overflow)


# -- the note API (shmem primitives + direct-DMA kernel sites) ----------------


def note_put(send_sem, recv_sem, pe, dst_ref, nbytes) -> Optional[tuple]:
    """Record one remote put. Returns the semaphore idents the matched
    wait notes need (threaded through PutHandle / kept by direct-DMA
    sites); None when recording is off."""
    c = _CTX
    if c is None:
        return None
    stok, sidx = _ident(c, send_sem)
    rtok, ridx = _ident(c, recv_sem)
    _, doff, dlen = _unwrap(dst_ref)
    dtok = c.intern(_unwrap(dst_ref)[0])
    _emit(c, [K_PUT, stok, sidx, rtok, ridx, pe, dtok, doff, dlen,
              int(nbytes)])
    return (stok, sidx, rtok, ridx)


def put_idents(send_sem, recv_sem) -> Optional[tuple]:
    """Semaphore idents of a put whose handle cannot be threaded to the
    wait site (e.g. the wait rebuilds the DMA descriptor in a later grid
    step). Pass the result to note_wait_send / note_wait_recv. None when
    recording is off."""
    c = _CTX
    if c is None:
        return None
    stok, sidx = _ident(c, send_sem)
    rtok, ridx = _ident(c, recv_sem)
    return (stok, sidx, rtok, ridx)


def note_wait_send(idents: Optional[tuple], amount: int = 1) -> None:
    c = _CTX
    if c is None or idents is None:
        return
    _emit(c, [K_WSEND, idents[0], idents[1], amount])


def note_wait_recv(idents: Optional[tuple], amount: int = 1) -> None:
    c = _CTX
    if c is None or idents is None:
        return
    _emit(c, [K_WRECV, idents[2], idents[3], amount])


def note_signal(sem_ref, amount, pe, nbar: bool = False) -> None:
    """pe None = self-signal (recorded -1, the decode-side self form)."""
    c = _CTX
    if c is None:
        return
    tok, idx = (_NBAR_TOK, 0) if nbar else _ident(c, sem_ref)
    _emit(c, [K_SIG, tok, idx, -1 if pe is None else pe, amount])


def note_wait(sem_ref, amount, nbar: bool = False) -> None:
    c = _CTX
    if c is None:
        return
    tok, idx = (_NBAR_TOK, 0) if nbar else _ident(c, sem_ref)
    _emit(c, [K_WAIT, tok, idx, amount])


def note_barrier() -> None:
    c = _CTX
    if c is None:
        return
    _emit(c, [K_BAR])


# -- tpu_call instrumentation -------------------------------------------------


def _conform_out_shape(rec: Recording):
    return jax.ShapeDtypeStruct((1 + rec.cap, ROW_WORDS), jnp.int32)


def _instrument(kernel, kwargs):
    """lang.core.tpu_call hook: with a recording active, rebuild the
    pallas_call with one appended SMEM output (the conform buffer) + a
    cursor scratch, wrap the kernel to install the ambient ConformCtx,
    and strip/stash the buffer from the results so callers see the
    original arity. Returns None when recording is off — tpu_call then
    takes its unmodified path (the zero-cost-off contract)."""
    rec = _REC
    if rec is None:
        return None
    kw = dict(kwargs)
    extra = _conform_out_shape(rec)
    gs = kw.pop("grid_spec", None)
    grid = gs.grid if gs is not None else kw.get("grid", ()) or ()
    grid_rank = len(grid) if isinstance(grid, (tuple, list)) else 1
    if gs is not None:
        outs = gs.out_specs
        outs = tuple(outs) if isinstance(outs, (tuple, list)) else (outs,)
        n_scr = len(gs.scratch_shapes)
        kw["grid_spec"] = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=gs.num_scalar_prefetch,
            grid=gs.grid,
            in_specs=gs.in_specs,
            out_specs=outs + (pl.BlockSpec(memory_space=pltpu.SMEM),),
            scratch_shapes=tuple(gs.scratch_shapes)
            + (pltpu.SMEM((2,), jnp.int32),),
        )
    else:
        osh = kw["out_shape"]
        n_out = len(osh) if isinstance(osh, (tuple, list)) else 1
        outs = kw.get("out_specs")
        if outs is None:
            outs = tuple(pl.BlockSpec(memory_space=pl.ANY)
                         for _ in range(n_out))
        elif isinstance(outs, (tuple, list)):
            outs = tuple(outs)
        else:
            outs = (outs,)
        kw["out_specs"] = outs + (pl.BlockSpec(memory_space=pltpu.SMEM),)
        scr = list(kw.get("scratch_shapes") or [])
        n_scr = len(scr)
        kw["scratch_shapes"] = scr + [pltpu.SMEM((2,), jnp.int32)]
    osh = kw["out_shape"]
    single_out = not isinstance(osh, (tuple, list))
    kw["out_shape"] = ((osh,) if single_out else tuple(osh)) + (extra,)
    cap_rows = rec.cap
    # collective_id keys the physical semaphore bank on hardware: calls
    # sharing an id reuse the same registers, so decode merges their
    # token namespaces (header word 3; -1 = no id, stay per-call)
    cid_code = getattr(kw.get("compiler_params"), "collective_id", None)
    cid_code = -1 if cid_code is None else int(cid_code)

    def wrapped(*args):
        global _CTX
        cur = args[-1]
        tail = len(args) - 1
        scr = args[tail - n_scr:tail]
        buf = args[tail - n_scr - 1]
        orig = args[:tail - n_scr - 1] + tuple(scr)
        c = ConformCtx(buf=buf, cur=cur, cap=cap_rows)

        # grid kernels re-enter the body per step; the SMEM buffer and
        # cursor persist, so init only on the first step
        first = jnp.bool_(True)
        for d in range(grid_rank):
            first = jnp.logical_and(first, pl.program_id(d) == 0)

        @pl.when(first)
        def _init():
            cur[0] = 0
            buf[0, 0] = MAGIC
            buf[0, 1] = 0
            buf[0, 2] = cap_rows
            buf[0, 3] = cid_code

        prev, _CTX = _CTX, c
        try:
            kernel(*orig)
        finally:
            _CTX = prev

    inner = pl.pallas_call(wrapped, **kw)

    def call(*a, **k):
        res = inner(*a, **k)
        rec.stash(res[-1])
        rest = tuple(res[:-1])
        return rest[0] if single_out else rest

    return call


# install the hook (conform is imported by the verify package __init__;
# lang.core stays free of any verify import — no layering cycle)
_core._CONFORM_INSTRUMENT = _instrument


# -- normalized ops + decode --------------------------------------------------


@dataclasses.dataclass(frozen=True)
class NOp:
    """One normalized protocol op, comparable across kernel and model.
    ``sems`` holds identity objects (canonicalized before comparison);
    ``region`` is the put destination — (buf, tok, off, len, nbytes)
    on the kernel side, the model's dst slot key on the model side."""

    kind: str
    sems: tuple = ()
    amount: Optional[int] = None
    peer: Optional[int] = None
    region: Optional[tuple] = None

    def brief(self) -> str:
        f = [self.kind]
        if self.sems:
            f.append("sems=" + "/".join(str(s) for s in self.sems))
        if self.amount is not None:
            f.append(f"amount={self.amount}")
        if self.peer is not None:
            f.append(f"peer={'self' if self.peer == -1 else self.peer}")
        return " ".join(f)


class ConformError(RuntimeError):
    pass


def _decode(bufs: List[np.ndarray], n: int,
            peer_xform: Optional[Callable] = None) -> List[List[NOp]]:
    """Gathered conform buffers -> per-rank NOp streams. ``bufs`` holds
    one (n*(1+cap), ROW_WORDS) array per instrumented pallas_call, in
    stash (= program) order; semaphore tokens are namespaced by buffer
    index so identities never collide across calls."""
    streams: List[List[NOp]] = [[] for _ in range(n)]
    for b, g in enumerate(bufs):
        arr = np.asarray(g)
        if arr.shape[0] % n or arr.shape[-1] != ROW_WORDS:
            raise ConformError(f"conform buffer {b}: bad shape {arr.shape}")
        arr = arr.reshape(n, arr.shape[0] // n, ROW_WORDS)
        for r in range(n):
            hdr = arr[r, 0]
            if int(hdr[0]) != MAGIC:
                continue  # sentinel: no instrumented op stream
            count, cap_rows = int(hdr[1]), int(hdr[2])
            # namespace: collective_id when stamped (same id = same
            # physical sem bank, identities persist across calls),
            # else unique per buffer
            sg = int(hdr[3]) if int(hdr[3]) >= 0 else -(b + 1)
            if count > cap_rows:
                raise ConformError(
                    f"conform buffer {b} rank {r}: {count} ops overflow "
                    f"cap {cap_rows} — raise recording(cap_rows=)")
            for i in range(count):
                row = [int(v) for v in arr[r, 1 + i]]
                k = row[0]
                if k == K_PUT:
                    peer = row[5]
                    if peer_xform is not None:
                        peer = peer_xform(r, peer)
                    streams[r].append(NOp(
                        "put",
                        sems=(_ksem(sg, row[1], row[2]),
                              _ksem(sg, row[3], row[4])),
                        peer=peer,
                        region=(sg, row[6], row[7], row[8], row[9])))
                elif k == K_SIG:
                    peer = row[3]
                    if peer >= 0 and peer_xform is not None:
                        peer = peer_xform(r, peer)
                    if peer == r:
                        peer = -1
                    streams[r].append(NOp(
                        "signal", sems=(_ksem(sg, row[1], row[2]),),
                        amount=row[4], peer=peer))
                elif k in (K_WAIT, K_WSEND, K_WRECV):
                    kind = {K_WAIT: "wait", K_WSEND: "wait_send",
                            K_WRECV: "wait_recv"}[k]
                    streams[r].append(NOp(
                        kind, sems=(_ksem(sg, row[1], row[2]),),
                        amount=row[3]))
                elif k == K_BAR:
                    streams[r].append(NOp("barrier"))
                else:
                    raise ConformError(
                        f"conform buffer {b} rank {r} row {i}: "
                        f"unknown kind {k}")
    return streams


def _ksem(b: int, tok: int, idx: int) -> tuple:
    if tok == _NBAR_TOK:
        return NBAR
    return ("K", b, tok, idx)


def _msem(key: tuple) -> tuple:
    name = key[0] if key else ""
    if isinstance(name, str) and name.endswith("nbar__"):
        return NBAR
    return ("M",) + tuple(key)


# -- recording harness --------------------------------------------------------


def collect_streams(mesh, axes, fn, in_specs, args,
                    cap_rows: int = 512,
                    peer_xform: Optional[Callable] = None,
                    ) -> List[List[NOp]]:
    """Run per-device ``fn(*args)`` shard_mapped over ``mesh`` with
    recording active; return the decoded per-rank op streams (rank
    order = mesh axis order over ``axes``). The kernel's outputs are
    discarded — only the conform buffers leave the shard_map."""
    axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
    n = 1
    for a in axes_t:
        n *= mesh.shape[a]
    sentinel = jnp.zeros((1, ROW_WORDS), jnp.int32)
    with recording(cap_rows) as rec:
        def run(*a):
            fn(*a)
            bufs = rec.collected()
            return tuple(bufs) if bufs else (sentinel,)

        out = jax.jit(jax.shard_map(
            run, mesh=mesh, in_specs=in_specs,
            out_specs=P(axes if isinstance(axes, str) else tuple(axes)),
            check_vma=False))(*args)
    return _decode([np.asarray(o) for o in out], n, peer_xform)


def model_streams(fn, n: int, params: Optional[dict] = None,
                  model_filter: Optional[Callable] = None,
                  ) -> List[List[NOp]]:
    """Concretize a protocol model at n -> per-rank NOp streams (the
    same normal form _decode produces for the kernel side)."""
    params = params or {}
    with cap.capturing(n) as c:
        fn(n, **params)
    # Local-copy completion waits (SymCopyHandle.wait: a WAIT whose
    # origin is a COPY op) are NOT conformance scope: the kernel's
    # pltpu.make_async_copy has no cross-rank content and is not
    # recorded. Protocol waits record origin=None; put-handle waits use
    # the distinct WAIT_SEND/WAIT_RECV kinds — no ambiguity.
    drop = {op.sid for op in c.ops
            if op.kind == cap.WAIT and op.fields.get("origin") is not None}
    progs = engine.concretize(c.ops, n)
    out: List[List[NOp]] = []
    for r, prog in enumerate(progs):
        ents: List[NOp] = []
        for op in prog:
            if op.kind not in engine.PROTOCOL_KINDS or op.sid in drop:
                continue
            if model_filter is not None and not model_filter(op):
                continue
            if op.kind == cap.PUT:
                ents.append(NOp(
                    "put",
                    sems=(_msem(op.f["send_sem"]),
                          _msem(op.f["recv_sem"])),
                    peer=op.f["pe"], region=tuple(op.f["dst"])))
            elif op.kind == cap.SIGNAL:
                pe = op.f["pe"]
                ents.append(NOp(
                    "signal", sems=(_msem(op.f["sem"]),),
                    amount=op.f["amount"], peer=-1 if pe == r else pe))
            elif op.kind in (cap.WAIT, cap.WAIT_SEND, cap.WAIT_RECV):
                kind = {cap.WAIT: "wait", cap.WAIT_SEND: "wait_send",
                        cap.WAIT_RECV: "wait_recv"}[op.kind]
                ents.append(NOp(kind, sems=(_msem(op.f["sem"]),),
                                amount=op.f["amount"]))
            elif op.kind == cap.BARRIER:
                ents.append(NOp("barrier"))
        out.append(ents)
    return out


# -- the comparator -----------------------------------------------------------


def _canon(stream: List[NOp]) -> List[NOp]:
    """Alpha-rename semaphore identities by first use (NBAR stays
    reserved): sem STRUCTURE is compared, never naming."""
    ids: Dict[tuple, tuple] = {}
    out = []
    for op in stream:
        sems = []
        for s in op.sems:
            if s == NBAR:
                sems.append(NBAR)
                continue
            c = ids.get(s)
            if c is None:
                c = ("s", len(ids))
                ids[s] = c
            sems.append(c)
        out.append(dataclasses.replace(op, sems=tuple(sems)))
    return out


def _sig(op: NOp) -> tuple:
    return (op.kind, op.sems, op.amount, op.peer)


def _sort_runs(stream: List[NOp], commute: tuple) -> List[NOp]:
    """Stable-sort maximal consecutive runs of same-kind ops whose kind
    is declared commutative (fan-out loops whose issue order carries no
    happens-before)."""
    out: List[NOp] = []
    i = 0
    while i < len(stream):
        j = i + 1
        k = stream[i].kind
        while (j < len(stream) and stream[j].kind == k
               and k in commute):
            j += 1
        run = stream[i:j]
        if len(run) > 1 and k in commute:
            run = sorted(run, key=lambda o: (_sig(o), o.region or ()))
        out.extend(run)
        i = j
    return out


def _region_findings(kops: List[NOp], mops: List[NOp], r: int
                     ) -> List[str]:
    """Data-extent containment over position-aligned puts: one model
    slot key -> one recorded region; distinct model keys -> distinct
    bases or disjoint [off, off+len) extents. Regions recorded as -1
    (unextractable) are skipped conservatively."""
    msgs: List[str] = []
    puts = [(k, m) for k, m in zip(kops, mops)
            if k.kind == "put" and m.kind == "put"]
    by_key: Dict[tuple, tuple] = {}
    for k, m in puts:
        reg = k.region
        if reg is None or reg[2] < 0 or reg[3] < 0:
            continue
        seen = by_key.get(m.region)
        if seen is None:
            by_key[m.region] = reg
        elif seen != reg:
            msgs.append(
                f"rank {r}: model slot {m.region} maps to two recorded "
                f"regions {seen} vs {reg}")
    keys = list(by_key.items())
    for i in range(len(keys)):
        for j in range(i + 1, len(keys)):
            (mk1, r1), (mk2, r2) = keys[i], keys[j]
            if r1[:2] != r2[:2]:
                continue  # different base refs: trivially disjoint
            o1, l1, o2, l2 = r1[2], r1[3], r2[2], r2[3]
            if o1 < o2 + l2 and o2 < o1 + l1:
                msgs.append(
                    f"rank {r}: model slots {mk1} and {mk2} are "
                    f"distinct but recorded regions overlap "
                    f"([{o1},{o1 + l1}) vs [{o2},{o2 + l2}))")
    return msgs


_MAX_FINDINGS = 3


def compare_streams(kstreams: List[List[NOp]],
                    mstreams: List[List[NOp]],
                    *, kernel: str = "?", n: int = 0,
                    params: Optional[dict] = None,
                    commute: tuple = (),
                    ) -> List[engine.Finding]:
    """Per-rank stream equivalence -> "model-drift" findings (empty =
    the kernel conforms to its model at this grid point)."""
    params = params or {}
    ptup = tuple(sorted(params.items()))
    msgs: List[str] = []
    for r in range(n):
        ks = _sort_runs(_canon(kstreams[r]), commute)
        ms = _sort_runs(_canon(mstreams[r]), commute)
        if not ks and ms:
            msgs.append(
                f"rank {r}: kernel recorded NO protocol ops but the "
                f"model declares {len(ms)} — the executed path records "
                "nothing (XLA fallback?) or the kernel lost its "
                "annotations")
            continue
        limit = min(len(ks), len(ms))
        diverged = False
        for i in range(limit):
            if _sig(ks[i]) != _sig(ms[i]):
                msgs.append(
                    f"rank {r} op {i}: kernel [{ks[i].brief()}] != "
                    f"model [{ms[i].brief()}]")
                diverged = True
                break
        if not diverged and len(ks) != len(ms):
            side = "kernel" if len(ks) > len(ms) else "model"
            extra = (ks if len(ks) > len(ms) else ms)[limit]
            msgs.append(
                f"rank {r}: {len(ks)} kernel ops vs {len(ms)} model "
                f"ops — first unmatched {side} op at {limit}: "
                f"[{extra.brief()}]")
            diverged = True
        if not diverged:
            msgs.extend(_region_findings(ks, ms, r))
        if len(msgs) >= _MAX_FINDINGS:
            break
    return [engine.Finding(engine.DRIFT, m, kernel=kernel, n=n,
                           params=ptup)
            for m in msgs[:_MAX_FINDINGS]]


# -- registration + runner ----------------------------------------------------


def team_mesh(shape, axis_names=("tp",)):
    """make_mesh over the first prod(shape) devices, or a Skip when the
    rig has fewer — the shared guard every conform runner leads with."""
    from triton_dist_tpu.runtime.init import make_mesh
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    need = 1
    for d in shape:
        need *= d
    have = len(jax.devices())
    if have < need:
        return Skip(f"needs {need} devices, rig has {have}")
    return make_mesh(shape, axis_names=axis_names)


@dataclasses.dataclass(frozen=True)
class Skip:
    """A conformance grid point this rig cannot execute (not enough
    devices; a grid the kernel does not define). Loud in the report,
    never a silent pass."""

    reason: str


@dataclasses.dataclass(frozen=True)
class ConformSpec:
    name: str                    # registry/protocol name
    runner: Callable             # fn(n, **params) -> streams | Skip
    grids: Tuple[Tuple[int, dict], ...]
    protocol: str                # @verify.protocol name to compare to
    commute: tuple = ()
    model_filter: Optional[Callable] = None  # (params) -> (COp -> bool)
    doc: str = ""


_CONFORM: Dict[str, ConformSpec] = {}


def conforms(name: str, grids: Tuple[Tuple[int, dict], ...],
             protocol: Optional[str] = None, commute: tuple = (),
             model_filter: Optional[Callable] = None, doc: str = ""):
    """Register a conformance runner beside a kernel's protocol model
    (import-time decorator in the kernel module). The runner executes
    the SHIPPED entry point on a real interpret mesh and returns the
    recorded streams (via collect_streams) or a Skip."""

    def deco(fn):
        _CONFORM[name] = ConformSpec(
            name=name, runner=fn, grids=tuple(grids),
            protocol=protocol or name, commute=tuple(commute),
            model_filter=model_filter, doc=doc)
        return fn

    return deco


def specs() -> Dict[str, ConformSpec]:
    """The conform registry (populated by registry.load_shipped() —
    registrations live in the kernel modules)."""
    from triton_dist_tpu.verify import registry
    registry.load_shipped()
    return dict(_CONFORM)


def record(name: str, n: int, **params):
    """Run one registered conformance runner (the recorded kernel-side
    streams, or Skip) — the entry the drift mutants build on."""
    sp = specs()[name]
    return sp.runner(n, **params)


def run_spec(spec: ConformSpec, n: int, params: dict):
    """One grid point: record the shipped kernel, concretize the model,
    compare. Returns a Skip or the (possibly empty) finding list."""
    from triton_dist_tpu.verify import registry
    shipped = registry.load_shipped()
    if spec.protocol not in shipped:
        raise ConformError(
            f"conform spec {spec.name!r} names unknown protocol "
            f"{spec.protocol!r}")
    got = spec.runner(n, **params)
    if isinstance(got, Skip):
        return got
    mf = spec.model_filter(params) if spec.model_filter else None
    model = model_streams(shipped[spec.protocol].fn, n, params,
                          model_filter=mf)
    return compare_streams(got, model, kernel=spec.name, n=n,
                           params=params, commute=spec.commute)


def check_shipped(names=None) -> Tuple[List[engine.Finding], List[str]]:
    """Every registered conformance grid point: (findings, skip lines).
    Clean = empty findings; skips are reported loudly by the CLI but do
    not fail the gate (each carries its rig reason)."""
    reg = specs()
    if names:
        missing = sorted(set(names) - set(reg))
        if missing:
            raise ConformError(f"unknown conform spec(s): {missing}")
        reg = {k: v for k, v in reg.items() if k in names}
    findings: List[engine.Finding] = []
    skips: List[str] = []
    for name in sorted(reg):
        spec = reg[name]
        for n, params in spec.grids:
            res = run_spec(spec, n, params)
            tag = f"{name} n={n}" + (f" {params}" if params else "")
            if isinstance(res, Skip):
                skips.append(f"{tag}: SKIP — {res.reason}")
            elif res:
                findings.extend(res)
            else:
                skips.append(f"{tag}: ok")
    return findings, skips
