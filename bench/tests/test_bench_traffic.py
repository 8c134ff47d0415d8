"""The request generator: seeded determinism, stratified lengths and
Poisson arrivals."""
import json
import pathlib

import numpy as np
import pytest

from benchlib import spec

BENCH = pathlib.Path(__file__).resolve().parents[1]
GEN = spec.load_module(BENCH / "traffic" / "requests.py")
BIG_SEED = 2**31 + 12345


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["chat-poisson"])
def test_same_seed_same_requests(name):
    a = GEN.generate(mix(name), BIG_SEED, 1000)
    b = GEN.generate(mix(name), BIG_SEED, 1000)
    c = GEN.generate(mix(name), BIG_SEED + 1, 1000)
    assert len(a) == mix(name)["n_requests"]
    for x, y in zip(a, b):
        assert x["max_new"] == y["max_new"]
        assert x["arrival_step"] == y["arrival_step"]
        assert np.array_equal(x["prompt"], y["prompt"])
    assert any(not np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a, c))


@pytest.mark.parametrize("name", ["chat-poisson"])
def test_lengths_follow_the_mix(name):
    m = mix(name)
    reqs = GEN.generate(m, 7, 1000)
    p = np.array([len(r["prompt"]) for r in reqs])
    o = np.array([r["max_new"] for r in reqs])
    for arr, spec_ in ((p, m["prompt"]), (o, m["output"])):
        assert arr.min() >= spec_["min"] and arr.max() <= spec_["max"]
        med = np.median(arr)
        assert abs(np.log(med / spec_["median"])) < 0.1
        # log-lengths of the unclipped middle spread with sigma
        lo, hi = np.percentile(np.log(arr), [25, 75])
        assert abs((hi - lo) / 1.349 - spec_["sigma"]) < 0.12
    assert all(0 <= r["prompt"].min() and r["prompt"].max() < 1000
               for r in reqs)


def test_stratified_mix_differs_little_between_seeds():
    m = mix("chat-poisson")
    means = [np.mean([len(r["prompt"]) + r["max_new"]
                      for r in GEN.generate(m, s, 1000)[:128]])
             for s in (1, 2, 3, 4)]
    assert (max(means) - min(means)) / np.mean(means) < 0.05


def test_poisson_arrivals_at_the_rate():
    m = dict(mix("chat-poisson"), n_requests=8000)
    reqs = GEN.generate(m, 3, 1000)
    arr = np.array([r["arrival_step"] for r in reqs])
    assert (np.diff(arr) >= 0).all()
    rate = len(arr) / arr[-1]
    assert abs(rate / m["arrivals"]["rate_per_step"] - 1) < 0.05
    gaps = np.diff(arr)
    assert 0.8 < gaps.std() / gaps.mean() < 1.2     # exponential gaps


def test_arrival_counts_vary_as_poisson():
    """Arrivals per stretch of steps vary as a Poisson count does (variance
    near the mean), so the traffic keeps its bursts."""
    m = dict(mix("chat-poisson"), n_requests=8000)
    rate = m["arrivals"]["rate_per_step"]
    span = round(16 / rate)                         # ~16 arrivals a stretch
    arr = np.array([r["arrival_step"] for r in GEN.generate(m, BIG_SEED,
                                                            1000)])
    counts = np.bincount(arr // span)[:-1]
    assert 0.75 < counts.var() / counts.mean() < 1.3


def test_seeds_share_arrivals_and_lengths_in_another_order():
    """Every seed offers the same work: one arrival path, and the same
    lengths in each run of STRATUM requests, in another order."""
    m = mix("chat-poisson")
    a = GEN.generate(m, BIG_SEED, 1000)
    b = GEN.generate(m, 5, 1000)
    assert [r["arrival_step"] for r in a] == [r["arrival_step"] for r in b]
    k = GEN.STRATUM
    for key in ("prompt", "max_new"):
        def size(r):
            return len(r["prompt"]) if key == "prompt" else r["max_new"]
        la, lb = [size(r) for r in a[:4 * k]], [size(r) for r in b[:4 * k]]
        assert la != lb
        for i in range(0, 4 * k, k):
            assert sorted(la[i:i + k]) == sorted(lb[i:i + k])


def test_unknown_process_or_distribution_is_an_error():
    m = mix("chat-poisson")
    with pytest.raises(ValueError):
        GEN.generate(dict(m, arrivals={"process": "backlog"}), 1, 1000)
    with pytest.raises(ValueError):
        GEN.generate(dict(m, prompt=dict(m["prompt"], dist="uniform")),
                     1, 1000)
    assert GEN.max_tokens(m) == m["prompt"]["max"] + m["output"]["max"]
