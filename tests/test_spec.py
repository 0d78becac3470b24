"""Speculative decoding on the serve plane (ISSUE 14).

The load-bearing property: with spec ON, every request's token stream
is equal to the spec-OFF (and sequential) run — greedy and
sampled — because the per-position verify step
samples each column under the per-(seed, token-index) key the
sequential path would use, so the longest-accepted-prefix rule only
ever emits the model's own tokens. BITWISE where both runs compute
every token at one step width (the `step_widths` fixture's "wide": a
verify row always takes the `(slots, chunk)` step, and the spec-off
run is held to it too). With the worker's own choice a spec-off decode
row runs the `(slots, 1)` step, so the two runs differ in the widths
that computed a token: equal tokens on these float32 sizes, logits to
a tolerance (ISSUE 31, tests/_widths.py). Around it: the n-gram draft units,
the accept rule, the k chooser/pruner, the FailStep-during-verify
chaos cell (no double emission), metrics, and the bench schema.

Wall budget: ONE engine geometry per module (module-scoped fixtures);
the spec scheduler adds exactly one per_pos executable.
"""

import jax
import numpy as np
import pytest

from triton_dist_tpu.models import Engine, ModelConfig
from triton_dist_tpu.runtime import make_mesh
from triton_dist_tpu.serve import Scheduler
from triton_dist_tpu.serve import scheduler as scheduler_mod
from triton_dist_tpu.spec import NgramDraft, SpecConfig, accept_tokens
from triton_dist_tpu.spec.verify import draft_cap, verify_keys

GEO = dict(slots=3, chunk=6, page=8)
K = 4  # one spec width (= one per_pos/spec_k executable) per module
GEN = 16


def _spec():
    return SpecConfig(k=K, draft=NgramDraft())


@pytest.fixture(scope="module")
def mesh1():
    return make_mesh(mesh_shape=(1,), axis_names=("tp",))


@pytest.fixture(scope="module")
def eng1(mesh1):
    cfg = ModelConfig.tiny(num_q_heads=4, num_kv_heads=2,
                           max_positions=128)
    return Engine(cfg, mesh1, decode_mode="ar", max_len=128,
                  donate_cache=False)


@pytest.fixture(scope="module")
def prompts(eng1):
    rng = np.random.default_rng(3)
    v = eng1.cfg.vocab_size
    return [list(map(int, rng.integers(0, v, 10))) for _ in range(3)]


@pytest.fixture(scope="module")
def baseline(eng1, prompts):
    """Spec-off greedy reference + its step count (greedy decode of a
    random-weight model self-loops, so drafts really get accepted)."""
    sch = Scheduler(eng1, **GEO)
    reqs = [sch.submit(p, max_new_tokens=GEN) for p in prompts]
    sch.run()
    return [r.out_tokens for r in reqs], sch.worker.n_steps


# ---------- draft units ----------


def test_ngram_draft_finds_cycle():
    d = NgramDraft(n=3)
    hist = [1, 2, 3, 4, 2, 3]
    # trailing [2, 3] occurred at i=1; proposes what followed: [4, 2]
    assert d.propose(hist, 2) == [4, 2]
    assert d.propose(hist, 5) == [4, 2, 3]
    # deterministic (the retry contract)
    assert d.propose(hist, 2) == d.propose(hist, 2)


def test_ngram_draft_prefers_longest_then_most_recent():
    d = NgramDraft(n=3)
    # [7, 8] occurs twice earlier; the MOST RECENT one (i=3) wins
    hist = [7, 8, 1, 7, 8, 2, 7, 8]
    assert d.propose(hist, 1) == [2]
    # a full trailing 3-gram match beats the 2-gram
    hist2 = [5, 7, 8, 9, 1, 5, 7, 8]
    assert d.propose(hist2, 1) == [9]


def test_ngram_draft_empty_cases():
    d = NgramDraft(n=3)
    assert d.propose([], 4) == []
    assert d.propose([1], 4) == []
    assert d.propose([1, 2, 3], 0) == []
    assert d.propose([1, 2, 3], 4) == []  # no repeat anywhere


def test_draft_cap_bounds():
    # k, chunk-1, remaining-1 and the pool horizon all cap the width
    assert draft_cap(4, 6, 20, 0, 10, 128) == 4
    assert draft_cap(8, 6, 20, 0, 10, 128) == 5   # chunk - 1
    assert draft_cap(4, 6, 20, 8, 10, 128) == 1   # max_new - n_out - 1
    assert draft_cap(4, 6, 20, 9, 10, 128) == 0   # last token: no spec
    assert draft_cap(4, 6, 126, 0, 10, 128) == 2  # t_max - history
    assert draft_cap(0, 6, 20, 0, 10, 128) == 0   # k=0 = off


# ---------- the accept rule ----------


def test_accept_tokens_longest_prefix():
    # o = [5, 6, 7], d = [5, 6, 9]: accept 2, emit o_0..o_2
    assert accept_tokens([5, 6, 9], [5, 6, 7]) == [5, 6, 7]
    assert accept_tokens([9, 6, 9], [5, 6, 7]) == [5]  # reject at 0
    assert accept_tokens([5, 6, 7], [5, 6, 7, 8]) == [5, 6, 7, 8]
    assert accept_tokens([], [5]) == [5]  # kd=0: the plain step


def test_accept_tokens_eos_and_budget_cuts():
    assert accept_tokens([5, 6], [5, 6, 7], eos_id=6) == [5, 6]
    assert accept_tokens([5, 6], [5, 6, 7], max_emit=2) == [5, 6]
    assert accept_tokens([5, 6], [5, 6, 7], eos_id=9) == [5, 6, 7]


# ---------- bit-identity (the acceptance oracle) ----------


def test_spec_bitwise_greedy_and_saves_steps(eng1, prompts, baseline,
                                             step_widths):
    base, base_steps = baseline  # its tokens are those of either width
    sch = Scheduler(eng1, spec=_spec(), **GEO)
    reqs = [sch.submit(p, max_new_tokens=GEN) for p in prompts]
    sch.run()
    assert [r.out_tokens for r in reqs] == base
    m = sch.metrics()
    assert m["spec_proposed"] > 0 and m["spec_accepted"] > 0, (
        "greedy self-loops must drive acceptance on this traffic")
    assert sch.worker.n_steps < base_steps, (
        "accepted drafts must save device steps")
    assert 0 < m["spec_accept_rate"] <= 1
    assert sch.obs.hist_count("spec_accept_rate") > 0
    sch.pool.check()


def test_spec_bitwise_sampled(eng1, prompts, step_widths):
    def run(spec):
        sch = Scheduler(eng1, spec=spec, **GEO)
        reqs = [sch.submit(p, max_new_tokens=GEN, temperature=0.9,
                           seed=41 + i) for i, p in enumerate(prompts)]
        sch.run()
        return [r.out_tokens for r in reqs]

    assert run(_spec()) == run(None)


def test_spec_eos_mid_verify(eng1, prompts, baseline):
    """An eos landing INSIDE an accepted prefix truncates exactly
    where sequential decode would stop."""
    base, _ = baseline
    eos = base[0][8]
    idx = base[0].index(eos)
    sch = Scheduler(eng1, spec=_spec(), **GEO)
    req = sch.submit(prompts[0], max_new_tokens=GEN, eos_id=eos)
    sch.run()
    assert req.out_tokens == base[0][:idx + 1]
    assert req.finish_reason == "eos"
    sch.pool.check()


def test_spec_with_eviction_bitwise(eng1, prompts, baseline):
    """Spec + page pressure: verify rows grow pages like decode rows;
    eviction/requeue under spec stays bitwise."""
    base, _ = baseline
    sch = Scheduler(eng1, spec=_spec(), total_pages=7, **GEO)
    reqs = [sch.submit(p, max_new_tokens=GEN) for p in prompts]
    sch.run()
    assert sum(r.n_evictions for r in reqs) > 0, (
        "pool was not constrained enough to exercise eviction")
    assert [r.out_tokens for r in reqs] == base
    sch.pool.check()


# ---------- chaos: FailStep during a verify step ----------


def test_failstep_during_verify_no_double_emission(eng1, prompts,
                                                   baseline):
    """The chaos-cell property as a unit: a transient FailStep landing
    on a spec-verify step retries WITHOUT double-emitting accepted
    tokens (the deterministic draft rebuilds the identical row; the
    emission happens once, after the successful attempt)."""
    from triton_dist_tpu import faults

    base, _ = baseline
    sch = Scheduler(eng1, spec=_spec(), max_step_retries=2,
                    retry_backoff_s=0.0005, **GEO)
    reqs = [sch.submit(p, max_new_tokens=GEN) for p in prompts]
    # at_step 4: decode territory on this traffic (prompts are 10
    # tokens = 2 chunks; slot count 3 → step 4 is decode/verify)
    plan = faults.FaultPlan(faults.FailStep(at_step=4, times=1))
    with faults.injecting(plan):
        sch.run()
    m = sch.metrics()
    assert m["step_retries"] == 1 and m["quarantined"] == 0
    assert [r.out_tokens for r in reqs] == base
    sch.pool.check()


def test_chaos_serve_spec_cells(eng1):
    """The matrix cells land green: the clean column (which also runs
    the shared-page eviction polarity pair) and one transient class."""
    from triton_dist_tpu.faults import chaos

    cells = chaos.run_matrix(None, protocols=("serve_spec",),
                             faults=("none", "delayed_send"),
                             serve_engine=eng1)
    probs = chaos.check_matrix(cells)
    assert not probs, probs
    assert {c.fault: c.outcome for c in cells} == {
        "none": "recovered", "delayed_send": "recovered"}


# ---------- chooser / pruner ----------


def test_choose_spec_k_monotone_in_acceptance():
    from triton_dist_tpu.perf_model import (
        CHIPS,
        choose_spec_k,
        estimate_spec_step_ms,
        expected_spec_tokens,
    )

    chip = CHIPS["TPU v5 lite"]
    dims = dict(num_layers=36, hidden=4096, inter_loc=1536, hq_loc=4,
                hkv_loc=1, head_dim=128, vocab_loc=18992, chip=chip)
    ks = [choose_spec_k(accept_rate=p, **dims)
          for p in (0.0, 0.3, 0.6, 0.9)]
    assert ks == sorted(ks)
    assert ks[0] == 0 and ks[-1] >= 2  # off at 0, wide at high rates
    # k=0 is exactly the plain step per token
    t0 = estimate_spec_step_ms(k=0, accept_rate=0.5, **dims)
    t4 = estimate_spec_step_ms(k=4, accept_rate=0.9, **dims)
    assert t4 < t0
    assert expected_spec_tokens(0.0, 4) == 1.0
    assert expected_spec_tokens(1.0, 4) == 5.0


def test_adaptive_spec_k_decays_and_recovers(eng1):
    """ISSUE 17 satellite: the live EWMA drives choose_spec_k — the
    draft width decays to 0 under non-self-similar traffic (nothing
    accepted) and recovers monotonically as acceptance returns."""
    sch = Scheduler(eng1, spec=SpecConfig(k=K, draft=NgramDraft(),
                                          adaptive=True), **GEO)
    assert sch._live_spec_k() == K  # no evidence yet: configured k
    for _ in range(20):
        sch._note_accept_rate(0.0)
    assert sch._spec_ewma is not None and sch._spec_ewma < 0.05
    assert sch._live_spec_k() == 0  # spec effectively OFF
    ks = []
    for _ in range(40):
        sch._note_accept_rate(1.0)
        ks.append(sch._live_spec_k())
    assert ks == sorted(ks), "live k must recover monotonically"
    assert ks[-1] == K, "full acceptance restores the configured cap"
    assert max(ks) <= K, "adaptation never exceeds the spec.k cap"


def test_adaptive_off_keeps_configured_k(eng1):
    """Default SpecConfig (adaptive=False) is bitwise the pre-ISSUE-17
    behavior: observations do not fold, the live k is always spec.k."""
    sch = Scheduler(eng1, spec=_spec(), **GEO)
    sch._note_accept_rate(0.0)
    assert sch._spec_ewma is None
    assert sch._live_spec_k() == K


def test_adaptive_spec_bitwise_and_metrics_key(eng1, prompts, baseline):
    """Adaptation changes only what is PROPOSED: the emitted streams
    stay bitwise the spec-off reference, and metrics carries the live
    width under the always-present spec_k_live key."""
    base, _ = baseline
    sch = Scheduler(eng1, spec=SpecConfig(k=K, draft=NgramDraft(),
                                          adaptive=True), **GEO)
    reqs = [sch.submit(p, max_new_tokens=GEN) for p in prompts]
    sch.run()
    assert [r.out_tokens for r in reqs] == base
    m = sch.metrics()
    assert 0 <= m["spec_k_live"] <= K
    # spec off entirely: the key is still present (= 0)
    sch_off = Scheduler(eng1, **GEO)
    assert sch_off.metrics()["spec_k_live"] == 0


def test_spec_config_validates_ewma_alpha():
    with pytest.raises(AssertionError, match="ewma_alpha"):
        SpecConfig(k=2, ewma_alpha=0.0)
    with pytest.raises(AssertionError, match="ewma_alpha"):
        SpecConfig(k=2, ewma_alpha=1.5)


def test_prune_spec_ks_keeps_off_switch():
    from triton_dist_tpu.autotuner import prune_spec_ks, spec_k_space
    from triton_dist_tpu.perf_model import CHIPS

    chip = CHIPS["TPU v5 lite"]
    dims = dict(num_layers=36, hidden=4096, inter_loc=1536, hq_loc=4,
                hkv_loc=1, head_dim=128, vocab_loc=18992, chip=chip)
    assert 0 in spec_k_space()
    live = prune_spec_ks(accept_rate=0.0, top_n=2, **dims)
    assert 0 in live and len(live) <= 2
    hi = prune_spec_ks(accept_rate=0.9, top_n=3, **dims)
    assert 0 in hi and hi[0] > 0  # best-ranked first at high rates


# ---------- the step's keys come from the host alone (ISSUE 28) ----------


def _fold_in_key(seed, index):
    return np.asarray(jax.random.fold_in(jax.random.PRNGKey(seed), index))


def _per_row_keys(sch, plans, width):
    """The step's keys as the host loop drew them before ISSUE 28: one
    eager fold_in an emitted token (with spec on, a key a column of the
    step's own width)."""
    spec_on = sch.spec is not None
    keys = np.zeros((sch.pool.slots, width, 2) if spec_on
                    else (sch.pool.slots, 2), np.uint32)
    for slot, req, n, emits, drafts in plans:
        if not emits:
            continue
        n_out = len(req.out_tokens)
        if spec_on:
            base = n - 1 - len(drafts)
            for j in range(len(drafts) + 1):
                keys[slot, base + j] = _fold_in_key(req.seed, n_out + j)
        else:
            keys[slot] = _fold_in_key(req.seed, n_out)
    return keys


def test_verify_keys_row_is_the_key_stream():
    keys = verify_keys(seed=41, n_out=3, width=3, cols=GEO["chunk"])
    assert keys.shape == (GEO["chunk"], 2) and keys.dtype == np.uint32
    for j in range(3):
        np.testing.assert_array_equal(keys[j], _fold_in_key(41, 3 + j))
    assert not keys[3:].any()


def _forbid_jax_keys(patch):
    def called(*_a, **_k):
        raise AssertionError("the host loop called JAX for a key")

    patch.setattr(jax.random, "PRNGKey", called)
    patch.setattr(jax.random, "fold_in", called)


def _submit_mixed(sch, prompts):
    """One greedy request (it self-loops, so verify rows carry drafts)
    beside sampled ones: keys are drawn for both."""
    return [sch.submit(p, max_new_tokens=GEN,
                       temperature=0.0 if i == 0 else 0.9, seed=41 + i)
            for i, p in enumerate(prompts)]


@pytest.mark.parametrize("spec_on", [False, True], ids=["plain", "spec"])
def test_assemble_draws_keys_without_jax(eng1, prompts, monkeypatch,
                                         spec_on):
    sch = Scheduler(eng1, spec=_spec() if spec_on else None, **GEO)
    _submit_mixed(sch, prompts)
    widest = 0
    while sch.step():
        if not sch.active:
            continue
        step_idx = sch.worker.n_steps
        tokens, *_, want, plans = sch._assemble(step_idx)
        with monkeypatch.context() as patch:
            _forbid_jax_keys(patch)
            *_, got, plans_again = sch._assemble(step_idx)
        assert [p[:4] for p in plans_again] == [p[:4] for p in plans]
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(
            got, _per_row_keys(sch, plans, tokens.shape[1]))
        assert got.any()
        widest = max([widest] + [len(p[4]) for p in plans])
    assert (widest > 0) == spec_on, "no verify row carried a draft"


@pytest.mark.parametrize("spec_on", [False, True], ids=["plain", "spec"])
def test_streams_are_those_of_per_row_fold_in(eng1, prompts, monkeypatch,
                                              spec_on):
    def run():
        sch = Scheduler(eng1, spec=_spec() if spec_on else None, **GEO)
        reqs = _submit_mixed(sch, prompts)
        sch.run()
        return [r.out_tokens for r in reqs]

    with monkeypatch.context() as patch:
        _forbid_jax_keys(patch)
        got = run()
    with monkeypatch.context() as patch:
        patch.setattr(
            scheduler_mod, "sampling_keys",
            lambda seeds, idx: np.stack(
                [_fold_in_key(s, i) for s, i in zip(seeds, idx)]))
        want = run()
    assert got == want
    assert len({tuple(t) for t in got}) > 1


# ---------- wiring / guards ----------


def test_spec_needs_room_in_chunk(eng1):
    with pytest.raises(AssertionError, match="k\\+1 <= chunk"):
        Scheduler(eng1, spec=SpecConfig(k=8, draft=NgramDraft()),
                  slots=3, chunk=6, page=8)


def test_worker_per_pos_step_polarity(eng1):
    sch = Scheduler(eng1, spec=_spec(), **GEO)
    with pytest.raises(AssertionError, match="step_spec"):
        sch.worker.step(np.zeros((3, 6), np.int32),
                        np.zeros((3,), np.int32),
                        np.zeros((3,), np.float32),
                        np.zeros((3, 2), np.uint32))


def test_trend_directions_for_new_families():
    from triton_dist_tpu.obs.trend import higher_is_better

    assert higher_is_better("serve_spec_tokens_per_s")
    assert higher_is_better("spec_vs_plain_tokens")
    assert higher_is_better("spec_accept_rate")
    assert not higher_is_better("prefix_hit_ttft")
    assert not higher_is_better("prefix_hit_ttft_us")


def test_trend_picks_up_spec_families_from_artifacts():
    """The satellite pin: obs/trend reads the new families through the
    EXISTING artifact reader — no special-casing — so the committed
    r07 artifact must surface them in the series."""
    from triton_dist_tpu.obs import trend

    series = trend.bench_series()
    keys = {k for (k, _rig) in series}
    assert {"spec_vs_plain_tokens", "spec_accept_rate",
            "prefix_hit_ttft", "serve_spec_tokens_per_s"} <= keys, (
        sorted(keys))


# ---------- bench schema ----------


def test_bench_spec_schema_travels_together():
    import bench

    lvl = {"spec": {"tokens_per_s": 20.0},
           "plain": {"tokens_per_s": 18.0}}
    good = {
        "metric": "x", "value": 1.0, "unit": "r", "vs_baseline": 1.0,
        "serve_spec_tokens_per_s": 20.0,
        "serve_spec_plain_tokens_per_s": 18.0,
        "spec_vs_plain_tokens": 1.11, "spec_accept_rate": 0.4,
        "serve_spec_levels": {"qps4": dict(lvl), "qps32": dict(lvl)},
    }
    assert bench.check_result(good) == []
    bad = dict(good)
    del bad["spec_accept_rate"]
    assert any("travel together" in p for p in bench.check_result(bad))
    bad = dict(good)
    bad["serve_spec_levels"] = {"qps4": dict(lvl)}
    assert any(">= 2 QPS levels" in p for p in bench.check_result(bad))
    bad = dict(good)
    bad["spec_accept_rate"] = 1.5
    assert any("outside [0, 1]" in p for p in bench.check_result(bad))
    bad = dict(good)
    del bad["serve_spec_levels"]["qps4"]["plain"]
    assert any("tokens_per_s" in p for p in bench.check_result(bad))
