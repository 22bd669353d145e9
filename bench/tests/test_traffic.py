"""The generator: one seed, one sequence; every seed the same work."""
import collections

import numpy as np
import pytest

import traffic
from conftest import BENCH

MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))
BIG_SEED = 2**31 + 12345
SLOTS = 64


def load(name):
    return traffic.load(BENCH / "traffic" / f"{name}.json")


def take(mix, seed, n=300, slots=SLOTS):
    t = traffic.Traffic(mix, seed, slots, 49152)
    return [t.next() for _ in range(n)]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = load(name)
    a, b = take(mix, BIG_SEED), take(mix, BIG_SEED)
    assert [(r.prompt, r.max_new) for r in a] == [(r.prompt, r.max_new) for r in b]
    c = take(mix, 7)
    assert [r.prompt for r in a] != [r.prompt for r in c]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_within_declared_ranges(name):
    mix = load(name)
    p, m = mix["prompt_len"], mix["max_new"]
    for seed in (0, 1, BIG_SEED):
        reqs = take(mix, seed)
        for r in reqs:
            assert p["min"] <= len(r.prompt) <= p["max"]
            assert all(1 <= t < 49152 for t in r.prompt)
        # the slots' first requests are caught partway: 1..max tokens left
        assert all(1 <= r.max_new <= m["max"] for r in reqs[:SLOTS])
        assert all(m["min"] <= r.max_new <= m["max"] for r in reqs[SLOTS:])


@pytest.mark.parametrize("name", MIXES)
@pytest.mark.parametrize("slots", [32, 64])
def test_every_seed_asks_for_the_same_work(name, slots):
    """Each block of requests, and the slots' first requests, hold the
    same lengths whatever the seed."""
    mix = load(name)
    block = mix["block"]
    runs = [take(mix, seed, n=slots + 4 * block, slots=slots)
            for seed in (3, 4, BIG_SEED)]
    for lo, hi in [(0, slots)] + [(slots + i * block, slots + (i + 1) * block)
                                  for i in range(4)]:
        seen = [(collections.Counter(len(r.prompt) for r in reqs[lo:hi]),
                 collections.Counter(r.max_new for r in reqs[lo:hi]))
                for reqs in runs]
        assert seen[0] == seen[1] == seen[2], (lo, hi)
    orders = [[r.max_new for r in reqs[:slots]] for reqs in runs]
    assert orders[0] != orders[1]


def test_quantile_lengths_follow_the_distribution():
    spec = {"dist": "lognormal", "median": 64, "sigma": 0.5, "min": 1, "max": 10**6}
    v = traffic.lengths(spec, 1001)
    assert v[500] == 64 and v[0] < 64 < v[-1]
    u = traffic.lengths({"dist": "uniform", "min": 512, "max": 1536}, 1000)
    assert u.min() >= 512 and u.max() <= 1536 and abs(u.mean() - 1024) < 1


def test_residual_lengths_are_length_biased():
    """For lengths uniform in [a, b] a request caught at a random step
    has E[L^2] / (2 E[L]) tokens left on average, and any count below
    a equally likely."""
    spec = {"dist": "uniform", "min": 512, "max": 1536}
    r = traffic.residual_lengths(spec, 4096)
    mean_l, var_l = 1024.0, 1024.0**2 / 12
    assert abs(r.mean() - (mean_l**2 + var_l) / (2 * mean_l)) < 3
    assert r.min() >= 1 and r.max() <= 1536
    # below 512 the residual's density is flat: 1/E[L] per token
    assert abs(np.mean(r <= 256) - 256 / 1024) < 0.005


def test_prompt_buckets_follow_the_engine_rule():
    from repro.serve.engine import _next_pow2

    mix = {"prompt_len": {"min": 16, "max": 128}}
    assert traffic.prompt_buckets(mix, 2048, _next_pow2) == [16, 32, 64, 128]
    mix = {"prompt_len": {"min": 1000, "max": 1030}}
    assert traffic.prompt_buckets(mix, 2048, _next_pow2)[0] == 1024
