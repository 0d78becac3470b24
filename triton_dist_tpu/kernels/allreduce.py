"""AllReduce kernels.

TPU-native re-design of the reference's 7-method AllReduce library
(ref: python/triton_dist/kernels/nvidia/allreduce.py:28-1208): one-shot push,
two-shot push, double-tree, one/two-shot multimem (NVLS). The TPU method
space:

  reference                         this file
  ---------                         ---------
  one-shot push (:333)              one_shot_all_reduce — full-mesh put of the
                                    local tensor to all peers + local sum
  two-shot push (:447)              two_shot_all_reduce — ring RS + ring AG
  multimem NVLS (:602-737)          method XLA — lax.psum (XLA owns the ICI
                                    reduction trees, the NVLS analog)
  auto-select by size/hw (:1101)    choose_allreduce_method
"""

from __future__ import annotations

import enum
import functools
from typing import Sequence, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.faults import guard as _guard
from triton_dist_tpu.lang import shmem
from triton_dist_tpu.lang.core import (
    cdiv,
    tpu_call,
    compiler_params,
    min_tile,
    next_collective_id,
    interpret_no_headroom,
    round_up,
)
from triton_dist_tpu.kernels.allgather import ring_all_gather
from triton_dist_tpu.kernels.reduce_scatter import ring_reduce_scatter
from triton_dist_tpu.obs import stats as _obs
from triton_dist_tpu.runtime.init import TP_AXIS
from triton_dist_tpu.wire import codec as wcodec


class AllReduceMethod(enum.Enum):
    Auto = "auto"
    OneShot = "one_shot"
    TwoShot = "two_shot"
    XLA = "xla"


_ONE_SHOT_MAX_BYTES = 256 << 10  # latency-bound regime (ref :1101-1126)
# One-shot materializes (n+1) tensor copies in VMEM; above this the kernel
# cannot compile under Mosaic — fall back (the chunked-entry rationale of
# ref allreduce.py:1129-1208). Same VMEM-resident budget convention as
# AgGemmConfig/GemmRsConfig.
_ONE_SHOT_VMEM_BUDGET = 14 << 20


def choose_allreduce_method(nbytes: int, n: int) -> AllReduceMethod:
    """Size/topology selection (ref auto-select, allreduce.py:1101-1126),
    backed by the analytic perf model: one-shot pays n-1 full-tensor
    sends (latency-optimal), two-shot is RS+AG (bandwidth-optimal); below
    the crossover the model favors one-shot, and a hard byte cap keeps
    the one-shot VMEM residents compilable."""
    from triton_dist_tpu.perf_model import estimate_ar_ms

    if nbytes > _ONE_SHOT_MAX_BYTES:
        return AllReduceMethod.TwoShot
    one = estimate_ar_ms(nbytes, n, method="one_shot")
    two = estimate_ar_ms(nbytes, n, method="two_shot")
    return (AllReduceMethod.OneShot if one <= two
            else AllReduceMethod.TwoShot)


def _one_shot_ar_kernel(axis: str, n: int, x_ref, o_ref, ws, acc, ld_sem,
                        send_sem, recv_sem):
    """One-shot AR: every rank puts its full tensor into every peer's
    workspace slot, then reduces locally (ref: allreduce.py:333-386)."""
    me = jax.lax.axis_index(axis)
    shmem.barrier_all(axis)

    cp = pltpu.make_async_copy(x_ref, ws.at[me], ld_sem)
    cp.start()
    handles = []
    for i in range(1, n):
        peer = jnp.mod(me + i, n)
        handles.append(shmem.putmem_nbi(
            ws.at[me], x_ref, send_sem, recv_sem, peer, axis))
    cp.wait()
    for h in handles:
        h.wait()

    acc[...] = ws[0]
    for r in range(1, n):
        acc[...] = acc[...] + ws[r]
    st = pltpu.make_async_copy(acc, o_ref, ld_sem)
    st.start()
    st.wait()


def one_shot_all_reduce(x: jax.Array, axis: str = TP_AXIS) -> jax.Array:
    """Latency-optimal AR of a per-device tensor. Call inside shard_map."""
    n = jax.lax.axis_size(axis)
    if n == 1:
        return x
    # The sum is elementwise, so the kernel runs on a lane-dense
    # (rows, 128) view padded to whole sublane tiles: a decode row
    # (B=1, H) as it stands gives Mosaic a (n, 1, H) workspace whose
    # one-row slot is not a whole packed tile ("slice shape must be
    # aligned to tiling" at B=1, bf16).
    sub, lane = min_tile(x.dtype)
    rows = round_up(cdiv(x.size, lane), sub)
    vmem_need = (n + 1) * rows * lane * x.dtype.itemsize
    if vmem_need > _ONE_SHOT_VMEM_BUDGET or interpret_no_headroom():
        return jax.lax.psum(x, axis)
    x2 = jnp.pad(x.reshape(-1), (0, rows * lane - x.size)).reshape(
        rows, lane)
    out = tpu_call(
        functools.partial(_one_shot_ar_kernel, axis, n),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((n,) + x2.shape, x.dtype),
            pltpu.VMEM(x2.shape, x.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=compiler_params(
            has_side_effects=True,
            collective_id=next_collective_id(f"one_shot_ar_{axis}"),
            vmem_limit_bytes=vmem_need + (2 << 20),
        ),
    )(x2)
    return out.reshape(-1)[:x.size].reshape(x.shape)


def two_shot_all_reduce(x: jax.Array, axis: str = TP_AXIS,
                        wire_format=None,
                        force_kernel: bool = False) -> jax.Array:
    """Bandwidth-optimal AR = ring RS + ring AG (ref: allreduce.py:447-526).

    Requires leading dim divisible by the axis size.

    wire_format ("fp8"/"int8"/wire.WireFormat; None = native) quantizes
    BOTH wire legs — the RS leg per hop (quantize at the send edge,
    f32 decode-add at the consume edge: _ring_rs_wire_kernel) and the
    AG leg once per reduced chunk (the gather forwards wire bytes
    unchanged) — at ~itemsize x fewer ICI bytes per hop and the drift
    measured by wire.numerics (EQuARX, arXiv 2506.17615). The semaphore
    protocols of both legs are format-invariant (verify-proved).
    Measured: [perf:allreduce_wire_fp8_vs_native=0.3-60.0] (r06
    cpu-world1 rig read 44.6 — world=1 reads the codec edge tax,
    interpreter-amplified on that rig; world>=2 on the default rig
    reads the ICI-bound wire win, modeled ~0.55x at n=8, so the band
    must span both regimes until a TPU artifact lands; see
    docs/performance.md "Quantized wire"/"Rigs").
    force_kernel: run the ring kernels even at world=1 (bench arms).

    Guarding (faults.guard.building active): one extra trailing output,
    the stacked (2, 1+cap, GUARD_WORDS) guard buffers of the RS and AG
    legs (both legs' watchdog trips are attributable separately).
    Metering (obs.stats.building active): one extra trailing output
    AFTER the guard buffer — the stacked (2, 1, STAT_WORDS) stat rows
    of the two legs (docs/observability.md "In-kernel stat rows")."""
    gbuild = _guard.active_build()
    obuild = _obs.active_build()
    if gbuild is None and obuild is None:
        scattered = ring_reduce_scatter(x, axis, wire_format=wire_format,
                                        force_kernel=force_kernel)
        return ring_all_gather(scattered, axis, wire_format=wire_format,
                               force_kernel=force_kernel)
    res_rs = ring_reduce_scatter(
        x, axis, wire_format=wire_format, force_kernel=force_kernel)
    res_rs = res_rs if isinstance(res_rs, tuple) else (res_rs,)
    res_ag = ring_all_gather(res_rs[0], axis, wire_format=wire_format,
                             force_kernel=force_kernel)
    res_ag = res_ag if isinstance(res_ag, tuple) else (res_ag,)
    out = (res_ag[0],)
    if gbuild is not None:
        out += (jnp.stack([res_rs[1], res_ag[1]]),)
    if obuild is not None:
        out += (jnp.stack([res_rs[-1], res_ag[-1]]),)
    return out


def all_reduce(
    x: jax.Array,
    axis: Union[str, Sequence[str]] = TP_AXIS,
    method: AllReduceMethod = AllReduceMethod.Auto,
    wire_format=None,
    error_budget: float = None,
) -> jax.Array:
    """AllReduce of a per-device tensor; per-device function.

    wire_format: payload encoding for the two-shot wire legs (see
    two_shot_all_reduce); "auto" asks perf_model.choose_wire_format for
    the fastest format whose modeled drift clears `error_budget`
    (default wire.DEFAULT_ERROR_BUDGET; budget 0.0 forces native).
    Quantized wire is a two-shot construct — it forces the TwoShot
    method (one-shot pushes full tensors whose local sum wants the
    native payload; XLA psum cannot express the codec)."""
    if not isinstance(axis, str):
        gbuild = _guard.active_build()
        obuild = _obs.active_build()
        out = x
        gbufs = []
        obufs = []
        for ax in tuple(axis):
            res = all_reduce(out, ax, method=method,
                             wire_format=wire_format,
                             error_budget=error_budget)
            if gbuild is None and obuild is None:
                out = res
                continue
            res = res if isinstance(res, tuple) else (res,)
            out = res[0]
            if gbuild is not None:
                # keep every stage's guard buffer — stripping them
                # would mute a tripped watchdog into a silently wrong
                # result (the failure class this plane exists to kill)
                g = res[1]
                gbufs.append(g if g.ndim == 3 else g[None])
            if obuild is not None:
                o = res[-1]
                obufs.append(o if o.ndim == 3 else o[None])
        ret = (out,)
        if gbuild is not None:
            ret += (jnp.concatenate(gbufs, axis=0),)
        if obuild is not None:
            ret += (jnp.concatenate(obufs, axis=0),)
        return ret if len(ret) > 1 else out

    n = jax.lax.axis_size(axis)
    nbytes = x.size * x.dtype.itemsize
    if wire_format == "auto":
        if x.shape[0] % n != 0:
            # the two-shot construct is inexpressible at this shape, so
            # the admissible format set is {native}: degrade to the
            # native method chain (which handles non-divisible shapes
            # via one-shot/XLA) instead of crashing world-size-dependently
            wire_format = None
        else:
            from triton_dist_tpu.perf_model import choose_wire_format

            wire_format = choose_wire_format(
                nbytes, n, dtype=x.dtype, error_budget=error_budget,
                collective="allreduce", row_width=x.shape[-1])
    if not wcodec.is_native(wire_format):
        if x.shape[0] % n != 0:
            # an EXPLICITLY requested quantized wire stays loud
            raise ValueError(
                f"quantized wire AR needs leading dim divisible by the "
                f"axis size (two-shot construct): {x.shape[0]} % {n}")
        return two_shot_all_reduce(x, axis, wire_format=wire_format)
    if method == AllReduceMethod.Auto:
        if x.shape[0] % n != 0:
            method = (
                AllReduceMethod.OneShot
                if nbytes <= _ONE_SHOT_MAX_BYTES
                else AllReduceMethod.XLA
            )
        else:
            method = choose_allreduce_method(nbytes, n)
    if method == AllReduceMethod.XLA:
        return _obs.with_stats(
            _obs.active_build(),
            _guard.with_guard(_guard.active_build(),
                              jax.lax.psum(x, axis)))
    if method == AllReduceMethod.OneShot:
        return _obs.with_stats(
            _obs.active_build(),
            _guard.with_guard(_guard.active_build(),
                              one_shot_all_reduce(x, axis)))
    return two_shot_all_reduce(x, axis)


PROTOCOL_NAME = "allreduce"  # degradation-registry key


def all_reduce_op(
    arr: jax.Array,
    mesh,
    axis: str = TP_AXIS,
    method: AllReduceMethod = AllReduceMethod.Auto,
    wire_format=None,
    fallback=None,
) -> jax.Array:
    """Host-level AR. `arr` stacks per-rank contributions: (n, ...), sharded
    on dim 0; returns the replicated sum over ranks
    (ref host entry: allreduce.py:1129-1208 chunked all_reduce).
    wire_format as in all_reduce (quantized = two-shot wire legs;
    "auto" defers to choose_wire_format inside the jitted program).

    fallback="xla" is the guard-tripped degradation route
    (docs/robustness.md): under an active guard build, a watchdog trip
    inside the ring kernels marks the protocol degraded and this call —
    and every later one — returns lax.psum's result instead of raising,
    so a degraded step completes rather than dies. Without fallback, a
    trip raises DeadlineExceeded with the decoded guard rows."""
    n = int(mesh.shape[axis])
    if arr.shape[0] != n:
        raise ValueError(
            f"all_reduce_op expects one stacked contribution per rank: "
            f"leading dim {arr.shape[0]} != axis size {n}"
        )
    if fallback not in (None, "xla"):
        raise ValueError(f"unknown fallback {fallback!r} (None or 'xla')")
    if fallback == "xla" and _guard.is_degraded(PROTOCOL_NAME):
        return _ar_xla_jit(mesh, axis)(arr)
    fmt = "auto" if wire_format == "auto" else wcodec.resolve(wire_format)
    gbuild = _guard.active_build()
    obuild = _obs.active_build()
    res = _ar_op_jit(mesh, axis, method, fmt, gbuild,
                     obuild is not None)(arr)
    if gbuild is None and obuild is None:
        return res
    res = res if isinstance(res, tuple) else (res,)
    out = res[0]
    import numpy as np

    if obuild is not None:
        _obs.consume_rows(res[-1], kernel=PROTOCOL_NAME)
    if gbuild is None:
        return out
    g = np.asarray(res[1])
    trips = _guard.decode(g)
    if trips:
        if fallback == "xla":
            _guard.degrade(PROTOCOL_NAME)
            return _ar_xla_jit(mesh, axis)(arr)
        _guard.check(g, context=PROTOCOL_NAME)
    return out


@functools.lru_cache(maxsize=None)
def _ar_op_jit(mesh, axis: str, method: AllReduceMethod, fmt,
               gbuild=None, metered: bool = False):
    from jax.sharding import PartitionSpec as P

    def fn(xs):
        import contextlib

        with _guard.building(gbuild.cap, gbuild.deadline) if gbuild \
                else contextlib.nullcontext(), \
                _obs.building() if metered else contextlib.nullcontext():
            res = all_reduce(xs[0], axis, method=method, wire_format=fmt)
        if gbuild is None and not metered:
            return res
        res = res if isinstance(res, tuple) else (res,)
        ret = (res[0],)
        if gbuild is not None:
            # normalize to (legs, 1+cap, WORDS) so the gathered global
            # is decode-ready regardless of which method path traced
            g = res[1]
            ret += (g[None] if g.ndim == 2 else g,)
        if metered:
            o = res[-1]
            ret += (o[None] if o.ndim == 2 else o,)
        return ret

    out_specs = P()
    if gbuild is not None or metered:
        out_specs = (P(),) + (P(axis),) * ((gbuild is not None)
                                           + bool(metered))
    return jax.jit(
        jax.shard_map(fn, mesh=mesh, in_specs=P(axis), out_specs=out_specs,
                      check_vma=False)
    )


@functools.lru_cache(maxsize=None)
def _ar_xla_jit(mesh, axis: str):
    """The degraded route: lax.psum (XLA owns the reduction trees) —
    no Pallas protocol to hang."""
    from jax.sharding import PartitionSpec as P

    return jax.jit(
        jax.shard_map(lambda xs: jax.lax.psum(xs[0], axis), mesh=mesh,
                      in_specs=P(axis), out_specs=P(), check_vma=False)
    )


# -- protocol models (static verifier, triton_dist_tpu.verify) ---------------

from triton_dist_tpu import verify as _v  # noqa: E402


@_v.protocol("allreduce",
             grid=({"method": "one_shot"}, {"method": "two_shot"},
                   {"method": "two_shot", "fmt": "fp8"},
                   {"method": "two_shot", "fmt": "int8"}),
             doc="one-shot full-mesh push AR / two-shot RS+AG ring "
                 "composition (fmt != native: both legs on the wire "
                 "image — same sync skeleton, verifier-proved)")
def _ar_protocol(n, method="one_shot", fmt="native"):
    if method == "two_shot":
        # the composition IS the protocol: ring RS then ring AG, each
        # with its own kernel-local semaphores (namespaced here so the
        # verifier sees two disjoint semaphore sets, as at run time);
        # fmt threads into both legs exactly as wire_format does
        from triton_dist_tpu.kernels.reduce_scatter import _rs_protocol
        from triton_dist_tpu.kernels.allgather import _ag_protocol

        _rs_protocol(n, prefix="rs.", fmt=fmt)
        _ag_protocol(n, method="ring", prefix="ag.", fmt=fmt)
        return
    assert fmt == "native", "one-shot AR has no quantized wire"
    me = shmem.my_pe(TP_AXIS)
    x, o = _v.ref("x"), _v.ref("o")
    ws, acc = _v.ref("ws"), _v.ref("acc")
    ld = _v.sem("ld_sem")
    send, recv = _v.sem("send_sem"), _v.sem("recv_sem")
    shmem.barrier_all(TP_AXIS)
    lc = _v.copy(ws.at(me), x.at(), ld.at())
    handles = [
        shmem.putmem_nbi(ws.at(me), x.at(), send.at(), recv.at(),
                         (me + i) % n, TP_AXIS)
        for i in range(1, n)
    ]
    lc.wait()
    for h in handles:
        h.wait()
    for r in range(n):
        _v.read(ws.at(r))  # the local reduction over all slots
    _v.write(acc.at())
    st = _v.copy(o.at(), acc.at(), ld.at())
    st.wait()


# -- conformance runner (verify.conform) --------------------------------------

from jax.sharding import PartitionSpec as _P  # noqa: E402

from triton_dist_tpu.verify import conform as _conform  # noqa: E402


@_conform.conforms(
    "allreduce",
    grids=((4, {"method": "one_shot"}), (4, {"method": "two_shot"}),
           (4, {"method": "two_shot", "fmt": "fp8"}),
           (4, {"method": "two_shot", "fmt": "int8"})),
    doc="one-shot workspace AR and two-shot RS+AG on the interpret mesh")
def _ar_conform(n, method="one_shot", fmt="native"):
    mesh = _conform.team_mesh(n, (TP_AXIS,))
    if isinstance(mesh, _conform.Skip):
        return mesh
    wf = None if fmt == "native" else fmt
    x = jnp.ones((8, 128), jnp.float32)
    if method == "one_shot":
        fn = lambda v: one_shot_all_reduce(v, TP_AXIS)  # noqa: E731
    else:
        fn = lambda v: two_shot_all_reduce(  # noqa: E731
            v, TP_AXIS, wire_format=wf)
    return _conform.collect_streams(
        mesh, TP_AXIS, fn, in_specs=_P(), args=(x,))
