"""Every traffic file (bench/traffic/*.json) through the one generator:
deterministic per seed, within its parameters, and the same work for
every seed (requests in one order, calls shuffled by the seed)."""

import collections
import glob
import os

import numpy as np
import pytest

from bench import generator

MIXES = sorted(os.path.basename(p)[:-len(".json")] for p in glob.glob(
    os.path.join(generator.TRAFFIC_DIR, "*.json")))
VOCAB = 151552
BIG_SEED = 2**31 + 977


def test_every_mix_is_found():
    assert {"stream", "decode-heavy", "prefill-heavy"} <= set(MIXES)


@pytest.mark.parametrize("name", MIXES)
def test_mix_is_deterministic_per_seed(name):
    mix = generator.load_mix(name)
    if mix["kind"] == "calls":
        for r in range(3):
            assert generator.calls(mix, BIG_SEED, r) == \
                generator.calls(mix, BIG_SEED, r)
    else:
        a = generator.requests(mix, BIG_SEED, VOCAB, rounds=2)
        b = generator.requests(mix, BIG_SEED, VOCAB, rounds=2)
        assert [r["max_new"] for r in a] == [r["max_new"] for r in b]
        assert all(np.array_equal(x["prompt"], y["prompt"])
                   for x, y in zip(a, b))
        c = generator.requests(mix, BIG_SEED + 1, VOCAB, rounds=2)
        assert any(not np.array_equal(x["prompt"], y["prompt"])
                   for x, y in zip(a, c))


@pytest.mark.parametrize("name", MIXES)
def test_mix_stays_within_its_parameters(name):
    mix = generator.load_mix(name)
    if mix["kind"] == "calls":
        arrays = dict(generator.arrays(mix))
        assert set(arrays.values()) == {1 << s for s in mix["sizes_log2"]}
        rnd = generator.calls(mix, 7, 0)
        assert len(rnd) == len(arrays) * len(mix["ops"])
        assert {op for op, _ in rnd} == set(mix["ops"])
        return
    reqs = generator.requests(mix, 7, VOCAB)
    assert len(reqs) == mix["round"] * mix["rounds"]
    spec = mix["max_new"]
    for r in reqs:
        assert len(r["prompt"]) in mix["prompt_buckets"]
        assert spec["min"] <= r["max_new"] <= spec["max"]
        assert len(r["prompt"]) + r["max_new"] <= mix["capacity"]
        assert r["prompt"].dtype == np.int32
        assert 0 <= r["prompt"].min() and r["prompt"].max() < VOCAB
    counts = collections.Counter(len(r["prompt"]) for r in reqs)
    for b, w in zip(mix["prompt_buckets"], mix["prompt_weights"]):
        assert counts[b] == pytest.approx(w * len(reqs))
    budgets = sorted(r["max_new"] for r in reqs)
    assert np.median(budgets) == pytest.approx(spec["median"], rel=0.15)
    warm = generator.warm_requests(mix, 7, VOCAB)
    assert len(warm) >= mix["slots"]
    assert {len(r["prompt"]) for r in warm} == set(mix["prompt_buckets"])


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_work(name):
    mix = generator.load_mix(name)
    if mix["kind"] == "calls":
        a = collections.Counter(generator.calls(mix, 1, 0))
        assert a == collections.Counter(generator.calls(mix, BIG_SEED, 5))
        return
    key = lambda rs: collections.Counter(  # noqa: E731
        (len(r["prompt"]), r["max_new"]) for r in rs)
    assert key(generator.requests(mix, 1, VOCAB)) == \
        key(generator.requests(mix, BIG_SEED, VOCAB))


@pytest.mark.parametrize("name", [m for m in MIXES if generator.load_mix(
    m)["kind"] == "requests"])
def test_requests_come_in_one_order_for_every_seed(name):
    mix = generator.load_mix(name)
    sizes = lambda rs: [(len(r["prompt"]), r["max_new"])  # noqa: E731
                        for r in rs]
    a = generator.requests(mix, 1, VOCAB, rounds=3)
    b = generator.requests(mix, BIG_SEED, VOCAB, rounds=3)
    assert sizes(a) == sizes(b)
    assert not np.array_equal(a[0]["prompt"], b[0]["prompt"])


def test_small_calls_draw_sizes_uniformly_per_round():
    mix = {"kind": "calls", "ops": ["reduce_sum", "squared_sum"],
           "sizes_log2": [12, 14, 16, 18, 20], "arrays_per_size": 4,
           "shuffle": True}
    sizes = dict(generator.arrays(mix))
    got = collections.Counter(sizes[a] for _, a in
                              generator.calls(mix, 3, 0))
    assert len(set(got.values())) == 1


def test_stream_alternates_ops():
    mix = generator.load_mix("stream")
    ops = [op for op, _ in generator.calls(mix, 3, 0)]
    assert ops == ["reduce_sum", "squared_sum"] * (len(ops) // 2)


def test_a_round_that_cannot_hold_the_weights_is_refused():
    mix = dict(generator.load_mix("decode-heavy"), round=7)
    with pytest.raises(ValueError):
        generator.requests(mix, 1, VOCAB)
