"""The generator is a pure function of --seed, honours the clips, and
gives every seed the same set of sizes and arrivals in another order."""

import os

import numpy as np
import pytest

from perfbench import traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MIXES = sorted(f[:-5] for f in os.listdir(
    os.path.join(REPO, "perfbench", "traffic")) if f.endswith(".json"))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = traffic.load_mix(REPO, name)
    a = traffic.plan(mix, 2**31 + 5, 151936, 2048, 50.0)
    b = traffic.plan(mix, 2**31 + 5, 151936, 2048, 50.0)
    c = traffic.plan(mix, 7, 151936, 2048, 50.0)
    assert len(a) == len(b) > 0
    assert all(np.array_equal(x.prompt, y.prompt)
               and x.max_new_tokens == y.max_new_tokens
               and x.due_s == y.due_s for x, y in zip(a, b))
    assert any(len(x.prompt) != len(y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_honour_the_clips_and_the_horizon(name):
    mix = traffic.load_mix(REPO, name)
    for p in traffic.plan(mix, 3, 151936, 2048, 50.0):
        assert mix["prompt"]["min"] <= len(p.prompt) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= p.max_new_tokens \
            <= mix["output"]["max"]
        assert len(p.prompt) + p.max_new_tokens <= 2048
        assert p.prompt.min() >= 0 and p.prompt.max() < 151936


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_sends_the_same_set_in_another_order(name):
    mix = traffic.load_mix(REPO, name)
    n = int(mix["pool"])
    sets = []
    for seed in (1, 2, 2**31 + 9):
        first_pass = traffic.plan(mix, seed, 1000, 2048, 1e6)[:n]
        sets.append(sorted((len(p.prompt), p.max_new_tokens)
                           for p in first_pass))
    assert sets[0] == sets[1] == sets[2]
    assert sets[0] == sorted(traffic.length_pool(mix, 2048))


def test_lognormal_pool_has_the_stated_median():
    mix = traffic.load_mix(REPO, "chat-closed")
    prompts = sorted(p for p, _ in traffic.length_pool(mix, 2048))
    mid = (prompts[len(prompts) // 2 - 1] + prompts[len(prompts) // 2]) / 2
    assert abs(mid - mix["prompt"]["median"]) < 0.05 * mix["prompt"]["median"]


def test_open_loop_due_times_follow_the_rate():
    mix = {"loop": "open", "arrivals": "poisson", "rate_per_s": 4.0,
           "pool": 16,
           "prompt": {"dist": "fixed", "value": 8},
           "output": {"dist": "fixed", "value": 2}}
    plan = traffic.plan(mix, 5, 100, 64, horizon_s=40.0)
    due = [p.due_s for p in plan]
    assert due == sorted(due) and due[-1] <= 40.0
    assert abs(len(plan) / 40.0 - 4.0) < 0.4  # the fixed rate, not a search
    gaps = sorted(np.diff([0.0] + due)[:16])
    other = traffic.plan(mix, 6, 100, 64, horizon_s=40.0)
    assert np.allclose(gaps, sorted(np.diff(
        [0.0] + [p.due_s for p in other])[:16]))


def test_output_shortened_where_the_horizon_would_be_passed():
    mix = {"loop": "closed", "clients": 1, "pool": 4,
           "prompt": {"dist": "fixed", "value": 60},
           "output": {"dist": "fixed", "value": 16}}
    assert all(p + o <= 64 for p, o in traffic.length_pool(mix, 64))


def test_unknown_mix_is_an_error():
    with pytest.raises(FileNotFoundError):
        traffic.load_mix(REPO, "no-such-mix")
