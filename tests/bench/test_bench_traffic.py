"""The seeded traffic generator: deterministic, Poisson, in proportion."""
import json

import numpy as np
import pytest

from bench.traffic import DELETE, INSERT, ContentStream, schedule
from bench.spec import ROOT

MIXES = ["ycsb-a", "ycsb-b"]


def mix(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_arrivals_and_make_ups(name):
    a = schedule(mix(name), 400.0, 30.0, seed=2**40 + 3)
    b = schedule(mix(name), 400.0, 30.0, seed=2**40 + 3)
    assert a == b


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_offers_the_same_arrivals_and_sizes(name):
    a = schedule(mix(name), 400.0, 30.0, seed=1)
    b = schedule(mix(name), 400.0, 30.0, seed=2)
    assert [(x.t, x.kind) for x in a] == [(x.t, x.kind) for x in b]
    assert sorted(x.make_up for x in a) == sorted(x.make_up for x in b)
    assert [x.make_up for x in a] != [x.make_up for x in b]


@pytest.mark.parametrize("name", MIXES)
def test_arrivals_are_poisson(name):
    arr = schedule(mix(name), 2000.0, 60.0, seed=5)
    for kind in ("query", "mutate"):
        t = np.asarray([a.t for a in arr if a.kind == kind])
        gaps = np.diff(t)
        rate = len(t) / 60.0
        # exponential gaps: mean 1/rate, coefficient of variation 1
        assert abs(gaps.mean() * rate - 1.0) < 0.1
        assert abs(gaps.std() / gaps.mean() - 1.0) < 0.1
        # uniform over the window: each quarter holds a quarter of them
        counts = np.histogram(t, bins=4, range=(0, 60.0))[0]
        assert np.all(np.abs(counts / len(t) - 0.25) < 0.05)


@pytest.mark.parametrize("name", MIXES)
def test_rows_follow_the_mix(name):
    m = mix(name)
    arr = schedule(m, 2000.0, 60.0, seed=9)
    q_rows = sum(a.kind == "query" for a in arr) * m["query_rows"]
    mutations = [a for a in arr if a.kind == "mutate"]
    m_rows = sum(len(a.make_up) for a in mutations)
    assert all(len(a.make_up) == m["mutation_rows"] for a in mutations)
    assert abs(q_rows / (q_rows + m_rows) - m["query_share"]) < 0.01
    kinds = np.concatenate([a.make_up for a in mutations])
    for kind, share in ((INSERT, m["insert"]), (DELETE, m["delete"])):
        assert abs((kinds == kind).mean() - share) < 0.03


def test_a_request_never_names_an_id_twice():
    ids = np.arange(300, dtype=np.int64)
    feats = {"dense:x": np.ones((300, 4), np.float32)}
    stream = ContentStream(ids, feats, 200, seed=3, jitter=0.05)
    live = set(range(200))
    rng = np.random.default_rng(0)
    for _ in range(200):
        make_up = tuple(rng.choice(3, 8, p=[0.3, 0.4, 0.3]))
        kinds, got, f = stream.mutation(make_up)
        assert len(set(got.tolist())) == len(got)
        for kind, pid in zip(kinds.tolist(), got.tolist()):
            if kind == INSERT:
                assert pid not in live
                live.add(pid)
            else:
                assert pid in live
                if kind == DELETE:
                    live.discard(pid)
        assert f["dense:x"].shape == (8, 4)
    assert live == set(stream.live)
