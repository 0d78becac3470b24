"""Kernel<->model conformance tests (ISSUE 19): comparator unit
polarity, shipped-grid cleanliness, drift-mutant flagging, the
zero-cost-off pin, and the --conform CLI gate.

The heavy sweep (every registered grid point) lives in
`scripts/verify_kernels.py --conform` / the __graft_entry__ dryrun
plane; tier-1 pins the machinery on the cheapest real kernels
(ring_shift, one drift mutant) plus pure-python comparator units.
"""

import functools
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.lang.core import pallas_call_count
from triton_dist_tpu.verify import conform
from triton_dist_tpu.verify.conform import NOp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------- comparator units (pure python, no mesh) ----------


def test_canon_alpha_renames_but_keeps_nbar():
    s = [NOp("signal", sems=(("K", 0, 3, 1),), amount=1, peer=2),
         NOp("wait", sems=(conform.NBAR,), amount=1),
         NOp("wait", sems=(("K", 0, 3, 1),), amount=1)]
    c = conform._canon(s)
    assert c[0].sems == (("s", 0),)
    assert c[1].sems == (conform.NBAR,)  # reserved, never renamed
    assert c[2].sems == (("s", 0),)  # same identity -> same canon id


def test_compare_streams_equivalent_across_naming():
    """Kernel and model streams that differ ONLY in semaphore naming
    compare clean: structure, not names."""
    k = [[NOp("put", sems=(("K", 0, 0, 0), ("K", 0, 1, s)), peer=1,
              region=(0, 2, 0, 8, 32)),
          NOp("wait_send", sems=(("K", 0, 0, 0),), amount=1)]
         for s in range(2)][0]
    m = [NOp("put", sems=(("M", "snd"), ("M", "rcv")), peer=1,
             region=("out", 0)),
         NOp("wait_send", sems=(("M", "snd"),), amount=1)]
    assert conform.compare_streams([k], [m], kernel="t", n=1) == []


def test_compare_streams_flags_sem_structure_drift():
    """One shared slot where the model declares two distinct slots:
    diverges at the first reuse (the alpha-canonicalization drift)."""
    k = [NOp("wait", sems=(("K", 0, 0, 0),), amount=1),
         NOp("wait", sems=(("K", 0, 0, 0),), amount=1)]
    m = [NOp("wait", sems=(("M", "a"),), amount=1),
         NOp("wait", sems=(("M", "b"),), amount=1)]
    fs = conform.compare_streams([k], [m], kernel="t", n=1)
    assert fs and all(f.klass == "model-drift" for f in fs)
    assert "op 1" in fs[0].message


def test_compare_streams_flags_length_and_empty_kernel():
    m = [NOp("barrier"), NOp("barrier")]
    fs = conform.compare_streams([[NOp("barrier")]], [m], kernel="t",
                                 n=1)
    assert fs and "1 kernel ops vs 2 model ops" in fs[0].message
    fs = conform.compare_streams([[]], [m], kernel="t", n=1)
    assert fs and "XLA fallback" in fs[0].message


def test_compare_streams_region_consistency():
    """One model slot key landing on two recorded regions is drift even
    when the sync skeleton matches (the frozen-slot mutant class)."""
    def put(off, mslot):
        return NOp("put", sems=(("K", 0, 0, 0), ("K", 0, 1, 0)),
                   peer=1, region=(0, 2, off, 8, 32)), \
               NOp("put", sems=(("M", "s"), ("M", "r")), peer=1,
                   region=("out", mslot))

    k0, m0 = put(0, 0)
    k1, m1 = put(8, 0)  # same model slot, different recorded region
    fs = conform.compare_streams([[k0, k1]], [[m0, m1]], kernel="t",
                                 n=1)
    assert fs and "two recorded regions" in fs[0].message
    # distinct model slots with overlapping recorded extents also drift
    k1b = NOp("put", sems=(("K", 0, 0, 0), ("K", 0, 1, 0)), peer=1,
              region=(0, 2, 4, 8, 32))
    m1b = NOp("put", sems=(("M", "s"), ("M", "r")), peer=1,
              region=("out", 1))
    fs = conform.compare_streams([[k0, k1b]], [[m0, m1b]], kernel="t",
                                 n=1)
    assert fs and "overlap" in fs[0].message


def test_sort_runs_commute_normalizes_fanout_order():
    ops = [NOp("signal", sems=(("s", i),), amount=1, peer=i)
           for i in (2, 0, 1)]
    srt = conform._sort_runs(ops, commute=("signal",))
    assert [o.peer for o in srt] == [0, 1, 2]
    # undeclared kinds keep program order
    assert conform._sort_runs(ops, commute=()) == ops


def test_model_streams_drop_local_copy_waits():
    from triton_dist_tpu import verify as _v
    from triton_dist_tpu.lang import shmem

    def proto(n):
        me = shmem.my_pe("tp")
        _v.copy(_v.ref("o").at(me), _v.ref("x").at(),
                _v.sem("lsem").at()).wait()
        shmem.barrier_all("tp")

    ms = conform.model_streams(proto, 2)
    assert [op.kind for op in ms[0]] == ["barrier"]


# ---------- recorded-kernel polarity (real interpret mesh) ----------


def test_conform_clean_on_shipped_ring_shift():
    findings, report = conform.check_shipped(["ring_shift"])
    assert findings == []
    assert sorted(report) == [
        "ring_shift n=4 {'shift': 1}: ok",
        "ring_shift n=4 {'shift': 3}: ok"]


def test_conform_drift_mutant_flagged():
    import _mutants

    fs = _mutants._drift_ag_shared_recv_slot(4)
    assert fs and all(f.klass == "model-drift" for f in fs)


def test_conform_broadcast_skip_is_loud():
    findings, report = conform.check_shipped(["broadcast"])
    assert findings == []
    assert len(report) == 2
    assert all("SKIP" in ln and "rank-divergent" in ln for ln in report)


def test_conform_buffer_overflow_raises():
    from triton_dist_tpu.kernels.p2p import ring_shift

    mesh = conform.team_mesh(4, ("pp",))
    assert not isinstance(mesh, conform.Skip)
    x = jnp.ones((8, 128), jnp.float32)
    with pytest.raises(conform.ConformError, match="overflow"):
        conform.collect_streams(
            mesh, "pp", lambda v: ring_shift(v, 1, "pp"),
            in_specs=P(), args=(x,), cap_rows=1)


# ---------- zero cost when off (acceptance criterion) ----------


def test_recording_off_bit_identical_and_same_call_count():
    """Runs OUTSIDE conform.recording() are bitwise identical and trace
    the same number of pallas calls whether or not a recording ever
    happened — the instrument hook is trace-time ambient state with
    zero residue (mirrors verify.capturing's zero-cost pin)."""
    from triton_dist_tpu.kernels.p2p import ring_shift

    mesh = conform.team_mesh(4, ("pp",))
    assert not isinstance(mesh, conform.Skip)
    x = jnp.arange(4 * 8 * 128, dtype=jnp.float32).reshape(4 * 8, 128)

    def run():
        fn = functools.partial(ring_shift, shift=1, axis="pp")
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=P("pp"), out_specs=P("pp"),
            check_vma=False))(x)

    before = pallas_call_count()
    o1 = np.asarray(run())
    base_calls = pallas_call_count() - before
    assert base_calls > 0

    streams = conform.collect_streams(
        mesh, "pp", lambda v: ring_shift(v, 1, "pp"),
        in_specs=P(), args=(jnp.ones((8, 128), jnp.float32),))
    assert any(streams)  # the recording itself captured ops

    assert conform.active() is None  # no ambient residue
    before = pallas_call_count()
    o2 = np.asarray(run())
    assert pallas_call_count() - before == base_calls
    np.testing.assert_array_equal(o1, o2)


# ---------- CLI gate ----------


def test_verify_kernels_conform_cli_exit_codes():
    """--conform exits 0 on a clean subset and 1 when a registered
    conformance point drifts (injected spec, registry restored)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_tdt_conform_cli",
        os.path.join(REPO, "scripts", "verify_kernels.py"))
    cli = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli)

    name = "_test_drifting_conform"
    # runner returns an empty kernel stream against a non-empty model:
    # the cheapest possible drift (no kernel execution needed)
    conform._CONFORM[name] = conform.ConformSpec(
        name=name, runner=lambda n: [[] for _ in range(n)],
        grids=((4, {}),), protocol="ring_shift")
    try:
        assert cli.check_conform([name]) == 1
    finally:
        conform._CONFORM.pop(name, None)
    assert cli.check_conform(["broadcast"]) == 0  # loud-skip only
    p = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "scripts", "verify_kernels.py"),
         "--conform", "no_such_spec"],
        cwd=REPO, capture_output=True, text=True)
    assert p.returncode == 2
