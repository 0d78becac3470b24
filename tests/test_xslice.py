"""xslice tests: 2-level ICI+DCN collectives + disaggregated serving.

The tier-1 pins for ISSUE 18:

- the three hierarchical protocol models (xslice_allgather /
  xslice_reduce_scatter / xslice_allreduce) concretize CLEAN at every
  global rank of (slices=2, n_local=2) and (slices=2, n_local=4)
  grids, and their semaphore skeleton is wire-format invariant;
- the host collectives on a real ("dcn", "tp") virtual mesh match
  their flat one-level oracles (bitwise where the reduction order is
  preserved, within the codec's drift model where a wire format rides
  the DCN leg);
- migration images verify-or-raise: a native image round-trips
  bitwise, an fp8/int8 image reproduces EXACTLY wire.codec.roundtrip,
  and any corrupted/truncated image raises MigrationError — admission
  gates on decode success, so silent-wrong is structurally
  unreachable;
- the DisaggPair emits BITWISE the tokens of a single role="both"
  scheduler — greedy and sampled — including across a real
  2-OS-process run over a FileMigrationChannel (no shared memory);
- the DCN chaos cells classify every fault detected-or-recovered,
  never silent-wrong.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from triton_dist_tpu.models import Engine, ModelConfig
from triton_dist_tpu.runtime import make_mesh
from triton_dist_tpu.serve import Scheduler
from triton_dist_tpu.wire import WireFormat
from triton_dist_tpu.wire import codec as wcodec
from triton_dist_tpu.xslice import (
    DisaggPair,
    FileMigrationChannel,
    MigrationChannel,
    MigrationError,
    SliceTeam,
    decode_pages,
    encode_pages,
    hier_all_gather_op,
    hier_all_reduce_op,
    hier_reduce_scatter_op,
    make_xslice_mesh,
)
from triton_dist_tpu.xslice.migrate import MigrationRecord

GEO = dict(slots=3, chunk=4, page=8)


@pytest.fixture(scope="module")
def mesh1():
    return make_mesh(mesh_shape=(1,), axis_names=("tp",))


@pytest.fixture(scope="module")
def eng1(mesh1):
    cfg = ModelConfig.tiny(num_q_heads=4, num_kv_heads=2,
                           max_positions=64)
    return Engine(cfg, mesh1, decode_mode="ar", max_len=64,
                  donate_cache=False)


@pytest.fixture(scope="module")
def xmesh():
    """(slices=2, n_local=2) — the smallest genuinely hierarchical
    grid the 12-device virtual pool can host with spares."""
    return make_xslice_mesh(2, 2)


# ---------- SliceTeam rank arithmetic ----------


def test_slice_team_factorization():
    team = SliceTeam(slices=3, n_local=4)
    assert team.n == 12
    for g in range(team.n):
        sid, local = team.slice_of(g), team.local_of(g)
        assert team.globalize(sid, local) == g
        base, loc = team.split(g)
        assert base == sid * 4 and loc == local
    assert team.leaders() == [0, 4, 8]
    assert team.rail(5) == [1, 5, 9]
    assert team.rail(5) == team.rail(9)  # rails are slice-invariant


# ---------- verifier concretization (the tentpole's static oracle) ----------


def _shipped_xslice():
    from triton_dist_tpu.verify import registry

    shipped = registry.load_shipped()
    names = ["xslice_allgather", "xslice_reduce_scatter",
             "xslice_allreduce"]
    assert all(n in shipped for n in names), sorted(shipped)
    return {n: shipped[n] for n in names}


@pytest.mark.parametrize("name", ["xslice_allgather",
                                  "xslice_reduce_scatter",
                                  "xslice_allreduce"])
def test_xslice_protocols_verify_clean(name):
    """Each 2-level protocol concretizes at every global rank of the
    (slices=2, n=4) and (slices=2, n=8) grids with zero findings."""
    from triton_dist_tpu.verify import registry

    spec = _shipped_xslice()[name]
    assert spec.ns == (4, 8)
    assert all(g.get("slices") == 2 for g in spec.grid)
    findings = registry.verify_spec(spec)
    assert findings == [], [str(f) for f in findings]


def test_xslice_format_invariance():
    """fmt= changes only the local stage/consume dataflow on the DCN
    leg — the semaphore skeleton must be identical across the wire
    grid (native / fp8 / int8)."""
    from triton_dist_tpu.verify import registry

    _shipped_xslice()
    problems = registry.check_format_invariance(
        ["xslice_allgather", "xslice_reduce_scatter",
         "xslice_allreduce"])
    assert problems == [], problems


# ---------- host collectives on the (2, 2) virtual mesh ----------


def test_hier_all_gather_matches_flat(xmesh):
    team = SliceTeam(2, 2)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((team.n * 8, 16)), jnp.float32)
    out = hier_all_gather_op(x, xmesh)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
    # chunked pipelining is bitwise the unchunked path
    out2 = hier_all_gather_op(x, xmesh, chunks=2)
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(out))


def test_hier_reduce_scatter_matches_sum(xmesh):
    team = SliceTeam(2, 2)
    rng = np.random.default_rng(1)
    x = np.asarray(rng.standard_normal((team.n, team.n * 4, 8)),
                   np.float32)
    out = np.asarray(hier_reduce_scatter_op(jnp.asarray(x), xmesh))
    full = x.sum(axis=0)
    rows = full.shape[0] // team.n
    # rank g owns output chunk local(g) * slices + sid(g) (ICI-major)
    for g in range(team.n):
        chunk = team.local_of(g) * team.slices + team.slice_of(g)
        np.testing.assert_allclose(
            out[g * rows:(g + 1) * rows],
            full[chunk * rows:(chunk + 1) * rows], rtol=1e-5)


def test_hier_all_reduce_matches_sum(xmesh):
    team = SliceTeam(2, 2)
    rng = np.random.default_rng(2)
    x = np.asarray(rng.standard_normal((team.n, 16, 8)), np.float32)
    out = np.asarray(hier_all_reduce_op(jnp.asarray(x), xmesh))
    np.testing.assert_allclose(out, x.sum(axis=0), rtol=1e-5)
    out2 = np.asarray(hier_all_reduce_op(jnp.asarray(x), xmesh,
                                         chunks=2))
    np.testing.assert_array_equal(out2, out)


@pytest.mark.parametrize("fmt", ["fp8", "int8"])
def test_hier_wire_formats_bounded_error(xmesh, fmt):
    """A wire format on the DCN leg quantizes the inter-slice hop
    only; the result must stay within the codec's documented drift
    scale (loose band — the exact numerics are the codec's tests)."""
    team = SliceTeam(2, 2)
    rng = np.random.default_rng(3)
    x = np.asarray(rng.standard_normal((team.n, 16, 128)), np.float32)
    out = np.asarray(hier_all_reduce_op(jnp.asarray(x), xmesh,
                                        wire_format=fmt))
    ref = x.sum(axis=0)
    rel = np.abs(out - ref).max() / np.abs(ref).max()
    assert rel < 0.1, rel


# ---------- migration codec ----------


def _fake_pages(rng, pages=2, dtype=jnp.bfloat16):
    shape = (2, 2, pages, 8, 16)  # (L, Hkv, P, page, D)
    k = jnp.asarray(rng.standard_normal(shape), dtype)
    v = jnp.asarray(rng.standard_normal(shape), dtype)
    return k, v


def test_migration_native_roundtrip_bitwise():
    rng = np.random.default_rng(4)
    k, v = _fake_pages(rng)
    payload = encode_pages(k, v)
    k2, v2 = decode_pages(payload)
    np.testing.assert_array_equal(np.asarray(k2), np.asarray(k))
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(v))


@pytest.mark.parametrize("fmt", ["fp8", "int8",
                                 WireFormat("fp8", checksum=True)])
def test_migration_wire_matches_codec_roundtrip(fmt):
    """The fidelity contract: an fp8/int8-migrated image reproduces
    EXACTLY wire.codec.roundtrip — the codec's documented
    quantization, nothing more."""
    rng = np.random.default_rng(5)
    k, v = _fake_pages(rng)
    k2, v2 = decode_pages(encode_pages(k, v, wire_format=fmt))
    f = wcodec.resolve(fmt)
    for got, src in ((k2, k), (v2, v)):
        want = np.asarray(wcodec.roundtrip(
            jnp.asarray(src).reshape(-1, src.shape[-1]), f)).reshape(
                src.shape)
        np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("fmt", [None, "fp8"])
def test_migration_corruption_raises(fmt):
    rng = np.random.default_rng(6)
    k, v = _fake_pages(rng)
    payload = encode_pages(k, v, wire_format=fmt)
    bad = dict(payload)
    b = bad["k_bytes"].copy()
    b[3] ^= 0x40
    bad["k_bytes"] = b
    with pytest.raises(MigrationError):
        decode_pages(bad)
    trunc = dict(payload)
    trunc["v_bytes"] = trunc["v_bytes"][:-5]
    with pytest.raises(MigrationError):
        decode_pages(trunc)
    # the pristine payload still decodes (corruption copies)
    decode_pages(payload)


def test_migration_channel_chaos_knobs():
    ch = MigrationChannel()
    rng = np.random.default_rng(7)
    k, v = _fake_pages(rng)

    def rec(seq):
        return MigrationRecord(seq=seq, request_id=seq, prompt=(1, 2),
                               n_tokens=2, first_token=9,
                               payload=encode_pages(k, v), meta={})

    ch.drop_next = 1
    ch.send(rec(0))
    assert ch.recv() is None and ch.n_dropped == 1
    ch.send(rec(0))  # the resend arrives
    assert ch.recv().seq == 0
    ch.corrupt_next = 1
    ch.send(rec(1))
    got = ch.recv()
    with pytest.raises(MigrationError):
        decode_pages(got.payload)
    ch.ack(0)
    ch.nack(1)
    assert ch.pump_acks() == [("ack", 0), ("nack", 1)]
    assert ch.pump_acks() == []


def test_file_migration_channel(tmp_path):
    """The cross-process transport: atomic publication, attempt-counted
    resends, ack/nack markers — exercised through two independent
    endpoint objects over one directory (what the two OS processes
    hold)."""
    rng = np.random.default_rng(8)
    k, v = _fake_pages(rng)
    tx = FileMigrationChannel(tmp_path)
    rx = FileMigrationChannel(tmp_path)
    rec = MigrationRecord(seq=0, request_id=5, prompt=(3, 1, 4),
                          n_tokens=3, first_token=1,
                          payload=encode_pages(k, v, wire_format="fp8"),
                          meta={"max_new_tokens": 4})
    tx.send(rec)
    got = rx.recv()
    assert (got.seq, got.request_id, got.prompt) == (0, 5, (3, 1, 4))
    assert got.meta["max_new_tokens"] == 4
    k2, _ = decode_pages(got.payload)
    want = np.asarray(wcodec.roundtrip(
        jnp.asarray(k).reshape(-1, k.shape[-1]),
        wcodec.resolve("fp8"))).reshape(k.shape)
    np.testing.assert_array_equal(np.asarray(k2), want)
    assert rx.recv() is None  # consumed
    tx.send(rec)  # resend publishes a NEW attempt file
    assert rx.recv().seq == 0
    rx.ack(0)
    rx.nack(1)
    assert sorted(tx.pump_acks()) == [("ack", 0), ("nack", 1)]
    assert tx.pump_acks() == []


# ---------- disaggregated serving: the bit-identity oracle ----------


def _submit_all(target, prompts, gen, **kw):
    return [target.submit(p, max_new_tokens=gen, **kw) for p in prompts]


@pytest.fixture(scope="module")
def prompts(eng1):
    rng = np.random.default_rng(11)
    v = eng1.cfg.vocab_size
    return [list(map(int, rng.integers(0, v, n))) for n in (12, 10, 9)]


def _reference(eng, prompts, gen, **kw):
    sch = Scheduler(eng, **GEO)
    reqs = _submit_all(sch, prompts, gen, **kw)
    sch.run()
    return [r.out_tokens for r in reqs]


def test_disagg_bit_identity_greedy(eng1, prompts):
    ref = _reference(eng1, prompts, 6)
    pair = DisaggPair(eng1, prefill_kw=dict(GEO), decode_kw=dict(GEO))
    reqs = _submit_all(pair, prompts, 6)
    pair.run()
    assert [r.out_tokens for r in reqs] == ref
    m = pair.metrics()
    assert m["prefill"]["migrations_out"] == len(prompts)
    assert m["decode"]["migrations_in"] == len(prompts)
    assert m["prefill"]["migrations_failed"] == 0
    pair.prefill.pool.check()
    pair.decode.pool.check()


def test_disagg_bit_identity_sampled(eng1, prompts):
    kw = dict(temperature=0.8, seed=43)
    ref = _reference(eng1, prompts, 6, **kw)
    pair = DisaggPair(eng1, prefill_kw=dict(GEO), decode_kw=dict(GEO))
    reqs = _submit_all(pair, prompts, 6, **kw)
    pair.run()
    assert [r.out_tokens for r in reqs] == ref


def test_disagg_fp8_migration_reproduces_codec(eng1, prompts):
    """With an fp8 migration format the decode-side KV pages must be
    EXACTLY the codec roundtrip of the prefill-side pages (the
    documented fidelity contract — token bit-identity is the NATIVE
    oracle; quantized KV legitimately drifts downstream tokens)."""
    ch = MigrationChannel()
    orig_send = ch.send
    shipped = []

    def capture(rec):
        shipped.append(rec)
        orig_send(rec)

    ch.send = capture
    pair = DisaggPair(eng1, channel=ch, migration_format="fp8",
                      prefill_kw=dict(GEO), decode_kw=dict(GEO))
    reqs = _submit_all(pair, prompts[:1], 4)
    pair.run()
    assert reqs[0].out_tokens  # completed through the quantized image
    (rec,) = shipped
    k2, v2 = decode_pages(rec.payload)
    f = wcodec.resolve("fp8")
    for img in (k2, v2):
        rt = np.asarray(wcodec.roundtrip(
            jnp.asarray(img).reshape(-1, img.shape[-1]), f)).reshape(
                img.shape)
        np.testing.assert_array_equal(np.asarray(img), rt)


def test_disagg_ledger_five_phases(eng1, prompts):
    from triton_dist_tpu.trace.ledger import (
        build_ledger, check_close, check_ledger,
    )

    pair = DisaggPair(eng1, prefill_kw=dict(GEO), decode_kw=dict(GEO))
    reqs = _submit_all(pair, prompts, 4)
    pair.run()
    doc = check_ledger(build_ledger(pair.prefill))
    assert check_close(doc) == []
    for row in doc["requests"]:
        assert row["migrate_us"] > 0, row
        assert row["admit_us"] > 0, row
        assert row["prefill_us"] > 0 and row["decode_us"] > 0
    assert all(r.phase_ns.get("migrate", 0) > 0 for r in reqs)


def test_disagg_resend_recovers_dropped_record(eng1, prompts):
    ch = MigrationChannel()
    ch.drop_next = 1
    ref = _reference(eng1, prompts[:2], 4)
    pair = DisaggPair(eng1, channel=ch,
                      prefill_kw=dict(GEO, migration_resend_after=2,
                                      max_migration_retries=3),
                      decode_kw=dict(GEO))
    reqs = _submit_all(pair, prompts[:2], 4)
    pair.run()
    assert [r.out_tokens for r in reqs] == ref
    assert pair.prefill.metrics()["migrations_resent"] >= 1
    assert ch.n_dropped == 1


def test_disagg_nack_reencode_recovers_corruption(eng1, prompts):
    ch = MigrationChannel()
    ch.corrupt_next = 1
    ref = _reference(eng1, prompts[:2], 4)
    pair = DisaggPair(eng1, channel=ch,
                      prefill_kw=dict(GEO, migration_resend_after=2,
                                      max_migration_retries=3),
                      decode_kw=dict(GEO))
    reqs = _submit_all(pair, prompts[:2], 4)
    pair.run()
    assert [r.out_tokens for r in reqs] == ref
    assert pair.prefill.metrics()["migrations_nacked"] >= 1
    assert pair.decode.metrics()["migrations_rejected"] >= 1


def test_disagg_retry_exhaustion_fails_loud(eng1, prompts):
    ch = MigrationChannel()
    ch.drop_all = True
    pair = DisaggPair(eng1, channel=ch,
                      prefill_kw=dict(GEO, migration_resend_after=1,
                                      max_migration_retries=2),
                      decode_kw=dict(GEO))
    reqs = _submit_all(pair, prompts[:1], 4)
    pair.run()
    assert reqs[0].state.value == "failed"
    assert "migration failed" in reqs[0].finish_reason
    assert pair.prefill.metrics()["migrations_failed"] == 1
    pair.prefill.pool.check()  # held pages were released on the fail


# ---------- chaos cells (the DCN fault matrix) ----------


@pytest.mark.parametrize("fault,outcome", [
    ("none", "recovered"),
    ("delayed_send", "recovered"),
    ("bitflip_payload", "recovered"),
    ("dropped_signal", "detected"),
])
def test_chaos_serve_disagg_cells(mesh1, eng1, fault, outcome):
    from triton_dist_tpu.faults import chaos

    cell = chaos._run_serve_disagg(mesh1, fault, engine=eng1)
    assert cell.outcome == outcome, str(cell)


@pytest.mark.slow
@pytest.mark.parametrize("fault", ["stalled_rank", "bitflip_scale"])
def test_chaos_serve_disagg_persistent_cells(mesh1, eng1, fault):
    from triton_dist_tpu.faults import chaos

    cell = chaos._run_serve_disagg(mesh1, fault, engine=eng1)
    assert cell.outcome == "detected", str(cell)


# ---------- the 2-process DCN run (no shared memory) ----------

_DISAGG_WORKER = r"""
import json, os, sys, time
import jax

jax.config.update("jax_platforms", "cpu")
import numpy as np

from triton_dist_tpu.models import Engine, ModelConfig
from triton_dist_tpu.runtime import make_mesh
from triton_dist_tpu.serve import Scheduler
from triton_dist_tpu.xslice import FileMigrationChannel

role = sys.argv[1]
root = sys.argv[2]
GEO = dict(slots=3, chunk=4, page=8)
GEN = 4
cfg = ModelConfig.tiny(num_q_heads=4, num_kv_heads=2, max_positions=64)
mesh = make_mesh(mesh_shape=(1,), axis_names=("tp",))
eng = Engine(cfg, mesh, decode_mode="ar", max_len=64,
             donate_cache=False)  # seed=0: identical weights both sides
rng = np.random.default_rng(11)
prompts = [list(map(int, rng.integers(0, cfg.vocab_size, n)))
           for n in (12, 10)]
ch = FileMigrationChannel(root)
deadline = time.time() + 240
if role == "prefill":
    # the resend ladder counts this loop's 10 ms rounds: patient enough
    # (2 s a resend) for a decode process that comes up seconds later
    sch = Scheduler(eng, role="prefill", migrate_to=ch,
                    migration_resend_after=200, **GEO)
    reqs = [sch.submit(p, max_new_tokens=GEN) for p in prompts]
    while (sch._migrating or sch.queue.peek() is not None
           or sch.active):
        sch.step()
        assert time.time() < deadline, "prefill side stalled"
        time.sleep(0.01)
    assert sch.metrics()["migrations_out"] == len(prompts)
    assert sch.metrics()["migrations_acked"] == len(prompts)
    print("PREFILL_OK", flush=True)
else:
    sch = Scheduler(eng, role="decode", admit_from=ch, **GEO)
    done = []
    while len(done) < len(prompts):
        sch.step()
        done = [r for r in sch.requests if r.done]
        assert time.time() < deadline, "decode side stalled"
        time.sleep(0.01)
    out = {r.request_id: r.out_tokens for r in done}
    toks = [out[k] for k in sorted(out)]
    print("DECODE_OK " + json.dumps(toks), flush=True)
"""


def test_disagg_two_process_bit_identity(tmp_path, eng1, prompts):
    """The acceptance pin: a REAL disaggregated pair — prefill and
    decode schedulers in different OS processes, identical seeded
    engines, KV pages crossing as checksummed files (the DCN analog) —
    emits bitwise the single-scheduler reference tokens."""
    ref = _reference(eng1, prompts[:2], 4)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo
    env.pop("XLA_FLAGS", None)  # 1-device children; no virtual pool
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _DISAGG_WORKER, role,
             str(tmp_path)],
            env=env, cwd=repo, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        for role in ("prefill", "decode")
    ]
    outs = [p.communicate(timeout=300)[0] for p in procs]
    for role, p, out in zip(("prefill", "decode"), procs, outs):
        assert p.returncode == 0, f"{role} failed:\n{out}"
    assert "PREFILL_OK" in outs[0], outs[0]
    line = [ln for ln in outs[1].splitlines()
            if ln.startswith("DECODE_OK")][0]
    toks = json.loads(line[len("DECODE_OK "):])
    assert toks == ref, (toks, ref)


# ---------- perf model consistency (shapes only; values in test_tuning) ----


def test_xslice_estimator_degenerates_to_flat():
    from triton_dist_tpu import perf_model as pm

    assert pm.estimate_xslice_collective_ms(1 << 20, 4, 1) == \
        pm.estimate_ag_ms(1 << 20, 4)
    with pytest.raises(ValueError):
        pm.estimate_xslice_collective_ms(1, 2, 2, "bogus")
