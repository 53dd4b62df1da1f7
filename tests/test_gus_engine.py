"""Serving-engine contract: mutation-log replay, freshness accounting,
straggler hedging against real replicas — plus edge cases of the
neighborhood RPC helpers (``_drop_self`` / ``neighbors_of_ids``)."""
import dataclasses
import time

import jax
import numpy as np
import pytest

from repro.ann.sharded_index import ShardedConfig
from repro.core import (BucketConfig, DynamicGUS, GusConfig, MutationBatch,
                        MUTATION_DELETE, MUTATION_INSERT)
from repro.core.gus import _drop_self
from repro.core.scorer import train_scorer
from repro.data.stream import MutationStream, StreamConfig
from repro.data.synthetic import OGB_ARXIV_LIKE, labeled_pairs, make_dataset
from repro.serve.engine import (EngineConfig, GusEngine,
                                ServingUnavailableError)
from repro.serve.faults import FaultInjector
from repro.utils import pow2_pad

DATA = dataclasses.replace(OGB_ARXIV_LIKE, n_points=400, n_clusters=8)
BUCKETS = BucketConfig(dense_tables=8, dense_bits=10, scalar_widths=(2.0,))


@pytest.fixture(scope="module")
def world():
    ids, feats, cluster = make_dataset(DATA)
    pf, lbl = labeled_pairs(feats, cluster, 1000, DATA.spec, seed=1)
    scorer, _ = train_scorer(jax.random.PRNGKey(0), DATA.spec, pf, lbl,
                             steps=60)
    return ids, feats, cluster, scorer


def _gus(scorer, **kw):
    defaults = dict(scann_nn=10, backend="brute")
    defaults.update(kw)
    return DynamicGUS(DATA.spec, BUCKETS, scorer, GusConfig(**defaults))


def _boot(gus, ids, feats, n=200):
    gus.bootstrap(ids[:n], {k: v[:n] for k, v in feats.items()})


# ------------------------------------------------------ mutation-log replay

def test_recover_replays_log_without_snapshot(world):
    ids, feats, cluster, scorer = world
    gus = _gus(scorer)
    _boot(gus, ids, feats)
    engine = GusEngine(gus, EngineConfig(snapshot_every=1000))  # never snaps
    stream = MutationStream(DATA, StreamConfig(batch_size=16, seed=2),
                            bootstrap_fraction=0.5)
    for _, mb in zip(range(5), stream):
        engine.submit_mutations(mb)
    assert len(engine.mutation_log) == 5
    # recovery target starts from the same bootstrap corpus, then replays
    fresh = _gus(scorer)
    _boot(fresh, ids, feats)
    engine2 = engine.recover(fresh)
    assert len(engine2.mutation_log) == 5
    qids = np.asarray(sorted(gus.store._rows))[:8]
    r1 = gus.neighbors_of_ids(qids, k=4)
    r2 = fresh.neighbors_of_ids(qids, k=4)
    np.testing.assert_allclose(np.sort(r1.distances, -1),
                               np.sort(r2.distances, -1), atol=1e-5)


def test_recover_bootstraps_replicas_from_snapshot(world):
    ids, feats, cluster, scorer = world
    gus = _gus(scorer)
    _boot(gus, ids, feats)
    engine = GusEngine(gus, EngineConfig(snapshot_every=2))
    stream = MutationStream(DATA, StreamConfig(batch_size=16, seed=3),
                            bootstrap_fraction=0.5)
    for _, mb in zip(range(3), stream):
        engine.submit_mutations(mb)
    assert engine.snapshot_state is not None
    fresh, replica = _gus(scorer), _gus(scorer)
    engine2 = engine.recover(fresh, replicas=[replica])
    assert set(replica.store._rows) == set(fresh.store._rows)
    assert len(engine2.replicas) == 1


def test_double_crash_keeps_snapshot_corpus(world):
    """A second crash before the recovered engine's next snapshot must not
    lose the snapshot corpus: recover() carries snapshot_state forward."""
    ids, feats, cluster, scorer = world
    gus = _gus(scorer)
    _boot(gus, ids, feats)
    engine = GusEngine(gus, EngineConfig(snapshot_every=2))
    stream = MutationStream(DATA, StreamConfig(batch_size=16, seed=7),
                            bootstrap_fraction=0.5)
    for _, mb in zip(range(3), stream):      # snapshot after 2, 1 in log
        engine.submit_mutations(mb)
    live = set(gus.store._rows)
    engine2 = engine.recover(_gus(scorer))   # crash #1
    assert engine2.snapshot_state is not None
    engine3 = engine2.recover(_gus(scorer))  # crash #2, no new snapshot
    assert set(engine3.gus.store._rows) == live


# ------------------------------------------------------ freshness accounting

def test_freshness_counts_every_mutation_batch(world):
    ids, feats, cluster, scorer = world
    gus = _gus(scorer)
    _boot(gus, ids, feats)
    engine = GusEngine(gus)
    for lo in (200, 216, 232):
        mb = MutationBatch(
            kinds=np.full(16, MUTATION_INSERT, np.int32),
            ids=ids[lo:lo + 16],
            features={k: v[lo:lo + 16] for k, v in feats.items()})
        engine.submit_mutations(mb)
    stats = engine.describe()
    assert stats["freshness"]["n"] == 3
    assert stats["freshness"]["p99_ms"] >= stats["freshness"]["p50_ms"]
    assert len(gus.index) == 200 + 48


# -------------------------------------------------------------- hedging

def test_hedge_uses_replicas_round_robin(world):
    ids, feats, cluster, scorer = world
    primary, rep_a, rep_b = (_gus(scorer) for _ in range(3))
    for g in (primary, rep_a, rep_b):
        _boot(g, ids, feats)
    # hedge_ms < 0: every query blows the deadline -> always hedge
    engine = GusEngine(primary, EngineConfig(hedge_ms=-1.0),
                       replicas=[rep_a, rep_b])
    q = {k: v[:1] for k, v in feats.items()}
    r1 = engine.query(q, k=5)
    r2 = engine.query(q, k=5)
    assert engine.hedged == 2
    assert engine.replica_hedges == [1, 1]          # round robin
    # replicas saw the same corpus -> identical exact answers
    np.testing.assert_array_equal(r1.ids, r2.ids)
    stats = engine.describe()
    assert stats["replica_hedges"] == [1, 1]


def test_hedge_replicas_stay_mutation_consistent(world):
    ids, feats, cluster, scorer = world
    primary, replica = _gus(scorer), _gus(scorer)
    for g in (primary, replica):
        _boot(g, ids, feats)
    engine = GusEngine(primary, EngineConfig(hedge_ms=-1.0),
                       replicas=[replica])
    dels = ids[:30]
    engine.submit_mutations(MutationBatch(
        kinds=np.full(30, MUTATION_DELETE, np.int32), ids=dels,
        features=None))
    assert len(replica.index) == len(primary.index) == 200 - 30
    res = engine.query({k: v[40:41] for k, v in feats.items()}, k=8)
    assert engine.replica_hedges == [1]             # answer came from replica
    assert not set(res.ids[res.ids >= 0].tolist()) & set(dels.tolist())


def _skipped(engine) -> int:
    return engine.obs.registry.get("engine_hedges_skipped_total").value


def test_hedge_without_replicas_reissues_primary(world):
    """A missed deadline with no replica reissues nothing: the primary's
    answer stands, counted as a skipped hedge, not as a hedge."""
    ids, feats, cluster, scorer = world
    gus = _gus(scorer)
    _boot(gus, ids, feats)
    engine = GusEngine(gus, EngineConfig(hedge_ms=-1.0))
    res = engine.query({k: v[:1] for k, v in feats.items()}, k=5)
    assert engine.hedged == 0 and engine.replica_hedges == []
    assert _skipped(engine) == 1
    assert engine.primary.served == 1
    assert engine.service.count == engine.serving.count == 1
    assert engine.hedge_wait.count == 0
    events = engine.obs.events
    assert events.events("hedge") == []
    ev = events.last("hedge_skipped")
    assert ev["seq"] == engine.seq and ev["primary_ms"] >= 0.0
    assert res.ids.shape == (1, 5)


def test_missed_deadline_without_replica_answers_once(world):
    """Under a missed deadline with no replica, route holds one
    answer_primary and no answer_hedge, and the served answer is the
    primary's own on the same padded features, bit for bit."""
    ids, feats, cluster, scorer = world
    gus = _gus(scorer)
    _boot(gus, ids, feats)
    engine = GusEngine(gus, EngineConfig(hedge_ms=-1.0))
    engine.obs.tracer.sample_every = 1
    n = 3
    q = {k: v[40:40 + n] for k, v in feats.items()}
    res = engine.query(q, k=5)
    tr = engine.obs.tracer.finished[-1]
    assert tr.problems() == []
    route = tr.spans.index(tr.find("route")[0])
    answers = [sp.name for sp in tr.spans if sp.parent == route]
    assert answers == ["answer_primary"]
    assert tr.find("answer_hedge") == []
    padded = pow2_pad(n, engine.cfg.query_batch)
    feats_p = {k: np.concatenate([v, np.repeat(v[-1:], padded - n, axis=0)])
               for k, v in q.items()}
    ref = gus.neighbors(feats_p, 5)
    np.testing.assert_array_equal(res.ids, ref.ids[:n])
    np.testing.assert_array_equal(res.distances, ref.distances[:n])


def test_missed_deadline_with_eligible_replica_still_hedges(world):
    """With one eligible replica a missed deadline still reissues on it:
    the replica's answer is served and no hedge is counted as skipped."""
    ids, feats, cluster, scorer = world
    primary, replica = _gus(scorer), _gus(scorer)
    for g in (primary, replica):
        _boot(g, ids, feats)
    engine = GusEngine(primary, EngineConfig(hedge_ms=-1.0),
                       replicas=[replica])
    engine.obs.tracer.sample_every = 1
    q = {k: v[40:41] for k, v in feats.items()}
    res = engine.query(q, k=5)
    assert engine.hedged == 1 and engine.replica_hedges == [1]
    assert _skipped(engine) == 0
    assert engine.obs.events.events("hedge_skipped") == []
    hedge_span = engine.obs.tracer.finished[-1].find("answer_hedge")[0]
    assert hedge_span.meta["member"] == "replica:0"
    ref = replica.neighbors(q, 5)
    np.testing.assert_array_equal(res.ids, ref.ids)


# ------------------------------------------- sharded backend through engine

def test_engine_on_sharded_backend(world):
    """The engine protocol is backend-agnostic: a 1-shard ShardedGusIndex
    (the shard_map programs on a single-device mesh) serves mutations and
    queries end-to-end."""
    ids, feats, cluster, scorer = world
    gus = _gus(scorer, backend="sharded",
               sharded=ShardedConfig(n_shards=1, d_proj=32, n_partitions=8,
                                     nprobe_local=0, reorder=1024, pq_m=4,
                                     kmeans_iters=4, pq_iters=2))
    _boot(gus, ids, feats)
    engine = GusEngine(gus)
    mb = MutationBatch(kinds=np.full(16, MUTATION_INSERT, np.int32),
                       ids=ids[200:216],
                       features={k: v[200:216] for k, v in feats.items()})
    engine.submit_mutations(mb)
    assert len(gus.index) == 216
    res = engine.query({k: v[200:201] for k, v in feats.items()}, k=3)
    assert res.ids[0, 0] == ids[200]                # finds itself
    assert engine.describe()["freshness"]["n"] == 1


# ------------------------------------------------------- span-tree tracing

def _trace_names(trace):
    return [s.name for s in trace.spans]


def test_query_trace_well_formed_under_hedge(world):
    """With always-on sampling, a hedged query leaves one well-formed
    span tree: engine-owned root, flush/catch_up/route stages, the
    primary answer carrying the injected straggler ms in metadata (not
    the bounds), and the hedged reissue."""
    ids, feats, cluster, scorer = world
    primary, replica = _gus(scorer), _gus(scorer)
    for g in (primary, replica):
        _boot(g, ids, feats)
    faults = FaultInjector()
    engine = GusEngine(primary, EngineConfig(hedge_ms=50.0),
                       replicas=[replica], faults=faults)
    engine.obs.tracer.sample_every = 1
    faults.slow(FaultInjector.PRIMARY, 500.0)
    engine.query({k: v[:1] for k, v in feats.items()}, k=5)
    tr = engine.obs.tracer.finished[-1]
    assert tr.problems() == []
    names = _trace_names(tr)
    assert names[0] == "engine"                       # engine owned the root
    for stage in ("engine_query", "flush", "catch_up", "route"):
        assert stage in names
    primary_span = tr.find("answer_primary")[0]
    assert primary_span.meta["member"] == "primary"
    assert primary_span.meta["extra_ms"] == 500.0     # injected, not slept
    assert primary_span.effective_ms >= 500.0
    hedge_span = tr.find("answer_hedge")[0]
    assert hedge_span.meta["member"] == "replica:0"
    # stage spans nest under the query span, answers under route
    route_idx = names.index("route")
    assert tr.spans[route_idx].parent == names.index("engine_query")
    assert tr.spans[names.index("answer_hedge")].parent == route_idx


def test_query_trace_well_formed_under_failover(world):
    ids, feats, cluster, scorer = world
    primary, replica = _gus(scorer), _gus(scorer)
    for g in (primary, replica):
        _boot(g, ids, feats)
    faults = FaultInjector()
    engine = GusEngine(primary, EngineConfig(), replicas=[replica],
                       faults=faults)
    engine.obs.tracer.sample_every = 1
    faults.kill(FaultInjector.PRIMARY)
    engine.query({k: v[:1] for k, v in feats.items()}, k=5)
    tr = engine.obs.tracer.finished[-1]
    assert tr.problems() == []
    assert tr.find("answer_primary") == []            # primary never answered
    fo = tr.find("answer_failover")[0]
    assert fo.meta["member"] == "replica:0"
    ev = engine.obs.events.last("failover")
    assert ev["member"] == "replica:0" and ev["seq"] == engine.seq


def test_unsampled_queries_leave_no_traces(world):
    ids, feats, cluster, scorer = world
    gus = _gus(scorer)
    _boot(gus, ids, feats)
    engine = GusEngine(gus)
    engine.obs.tracer.sample_every = 0
    for _ in range(3):
        engine.query({k: v[:1] for k, v in feats.items()}, k=5)
    assert len(engine.obs.tracer.finished) == 0
    assert engine.obs.tracer.started == 3             # decisions still taken
    assert engine.queries == 3                        # counters always on


SMALL_SHARDED = ShardedConfig(n_shards=1, d_proj=32, n_partitions=8,
                              nprobe_local=0, reorder=1024, pq_m=4,
                              kmeans_iters=4, pq_iters=2)


@pytest.fixture(scope="module")
def sharded_gus(world):
    """A booted one-shard sharded primary that the query-path span tests
    share (they only read it)."""
    ids, feats, cluster, scorer = world
    gus = _gus(scorer, backend="sharded", sharded=SMALL_SHARDED)
    _boot(gus, ids, feats)
    return gus


def _descends(trace, span, ancestor) -> bool:
    while span.parent >= 0:
        span = trace.spans[span.parent]
        if span is ancestor:
            return True
    return False


def _child_names(trace, parent) -> set:
    idx = trace.spans.index(parent)
    return {s.name for s in trace.spans if s.parent == idx}


def test_query_path_spans_nest_under_the_answer(world, sharded_gus):
    """On the sharded backend, the answer span is live around the
    member's answer: embed, shard_search (sketch, device waits) and score
    (gather, the weights' device wait) are its descendants."""
    ids, feats, cluster, scorer = world
    engine = GusEngine(sharded_gus, EngineConfig(hedge_ms=1e9))
    engine.obs.tracer.sample_every = 1
    engine.query({k: v[:2] for k, v in feats.items()}, k=5)
    tr = engine.obs.tracer.finished[-1]
    assert tr.problems() == []
    answer = tr.find("answer_primary")[0]
    assert _child_names(tr, answer) == {"embed", "shard_search", "score"}
    search = tr.find("shard_search")[0]
    assert _child_names(tr, search) == {"sketch", "device_wait"}
    assert _child_names(tr, tr.find("sketch")[0]) == {"device_wait"}
    score = tr.find("score")[0]
    assert _child_names(tr, score) == {"gather", "device_wait"}
    for name in ("embed", "shard_search", "sketch", "device_wait", "score",
                 "gather"):
        for sp in tr.find(name):
            assert _descends(tr, sp, answer), name
    assert tr.spans[answer.parent].name == "route"


def test_hedge_spans_match_the_hedge_counter(world, sharded_gus):
    """With a zero deadline every answer misses it: one answer_hedge span
    per counted hedge while a replica is eligible, and none, with a
    skipped hedge counted instead, while no other member is."""
    ids, feats, cluster, scorer = world
    replica = _gus(scorer)
    _boot(replica, ids, feats)
    faults = FaultInjector()
    engine = GusEngine(sharded_gus, EngineConfig(hedge_ms=0.0),
                       replicas=[replica], faults=faults)
    engine.obs.tracer.sample_every = 1
    hedges = engine.obs.registry.get("engine_hedges_total")

    def hedge_spans():
        return sum(len(tr.find("answer_hedge"))
                   for tr in engine.obs.tracer.finished)

    for lo in range(3):
        engine.query({k: v[lo:lo + 1] for k, v in feats.items()}, k=5)
    assert hedge_spans() == hedges.value == 3
    assert _skipped(engine) == 0
    faults.partition(0)                    # no other eligible member
    for lo in range(3):
        engine.query({k: v[lo:lo + 1] for k, v in feats.items()}, k=5)
    assert hedge_spans() == hedges.value == 3
    assert _skipped(engine) == 3
    assert all(tr.problems() == [] for tr in engine.obs.tracer.finished)


def test_pipelined_freshness_counts_submit_to_applied(world):
    """A pipelined batch applied 50 ms after its submit reads at least
    50 ms of freshness, and its apply_lag span, in a trace of its own,
    leaves the hand-off and flush spans that applied it as measured."""
    ids, feats, cluster, scorer = world
    gus = _gus(scorer)
    _boot(gus, ids, feats)
    engine = GusEngine(gus, EngineConfig(pipeline=True))
    tracer = engine.obs.tracer
    tracer.sample_every = 1
    delay_s = 0.05
    engine.submit_mutations(MutationBatch(
        kinds=np.full(16, MUTATION_INSERT, np.int32), ids=ids[200:216],
        features={k: v[200:216] for k, v in feats.items()}))
    assert engine.freshness.count == 0            # staged, not applied
    t_wait = tracer.clock()
    time.sleep(delay_s)
    engine.query({k: v[200:201] for k, v in feats.items()}, k=3)
    assert engine.freshness.count == 1
    assert engine.freshness.samples_ms[-1] >= delay_s * 1e3
    lag_traces = [tr for tr in tracer.finished if tr.find("apply_lag")]
    assert len(lag_traces) == 1
    lag = lag_traces[0].find("apply_lag")[0]
    assert lag is not lag_traces[0].root and lag.duration_ms >= delay_s * 1e3
    query = [tr for tr in tracer.finished if tr.find("handoff")][-1]
    assert query is not lag_traces[0] and query.problems() == []
    for name in ("handoff", "flush", "engine_query"):
        assert query.find(name)[0].t0 >= t_wait + delay_s, name
    assert lag.t0 < t_wait


def test_pipelined_loadgen_breakdown_counts_only_queries(world):
    """A traced, pipelined load run with mutations records apply_lag
    traces beside the request traces; the latency breakdown takes one
    service sample per query request and none per apply_lag."""
    from benchmarks.loadgen import LoadgenConfig, run_loadgen
    from repro.serve import Frontend, FrontendConfig

    ids, feats, cluster, scorer = world
    gus = _gus(scorer)
    _boot(gus, ids, feats)
    engine = GusEngine(gus, EngineConfig(pipeline=True))
    engine.obs.tracer.sample_every = 1
    fe = Frontend(engine, FrontendConfig(query_dispatch=2))
    stream = MutationStream(DATA, StreamConfig(batch_size=8, seed=5),
                            bootstrap_fraction=0.5)
    rep = run_loadgen(fe, stream, LoadgenConfig(
        mode="closed", requests=24, users=4, mutate_every=3, k=5))
    assert rep.lost == 0 and rep.errors == 0
    n_queries = rep.completed - len(engine.mutation_log)
    assert any(tr.find("apply_lag") for tr in engine.obs.tracer.finished)
    assert rep.breakdown["queue_wait"]["n"] == n_queries
    assert rep.breakdown["service"]["n"] == n_queries
    assert rep.breakdown["hedge_wait"]["n"] == n_queries


# ---------------------------------------- _drop_self / neighbors_of_ids

def test_drop_self_with_duplicate_candidate_ids():
    ids = np.asarray([[5, 5, 3, 7]])
    dists = np.asarray([[0.1, 0.2, 0.3, 0.4]], np.float32)
    out_ids, out_d = _drop_self(ids, dists, np.asarray([5]), k=3)
    # every copy of the self id is dropped, order preserved, padded to k
    assert out_ids.tolist() == [[3, 7, -1]]
    assert out_d[0, 2] == np.inf


def test_drop_self_trims_to_k():
    ids = np.asarray([[1, 2, 3, 4]])
    dists = np.asarray([[0.1, 0.2, 0.3, 0.4]], np.float32)
    out_ids, _ = _drop_self(ids, dists, np.asarray([9]), k=2)
    assert out_ids.tolist() == [[1, 2]]


def test_neighbors_k_larger_than_corpus(world):
    ids, feats, cluster, scorer = world
    gus = _gus(scorer)
    _boot(gus, ids, feats, n=4)
    res = gus.neighbors({k: v[:2] for k, v in feats.items()}, k=10)
    assert res.ids.shape == (2, 10)
    pad = res.ids < 0
    assert pad.any()                                 # corpus < k -> padding
    assert (res.weights[pad] == -np.inf).all()
    assert (res.distances[pad] == np.inf).all()
    # the live points themselves are all present
    assert set(res.ids[0][res.ids[0] >= 0].tolist()) == set(
        ids[:4].tolist())


def test_neighbors_of_ids_after_deleting_everything(world):
    ids, feats, cluster, scorer = world
    gus = _gus(scorer)
    _boot(gus, ids, feats, n=8)
    gus.mutate(MutationBatch(kinds=np.full(8, MUTATION_DELETE, np.int32),
                             ids=ids[:8], features=None))
    assert len(gus.index) == 0
    res = gus.neighbors({k: v[:3] for k, v in feats.items()}, k=5)
    assert (res.ids == -1).all()
    assert (res.weights == -np.inf).all()
    assert (res.distances == np.inf).all()


# ------------------------------------------------- fault injection (chaos)

def _fleet(world, n_replicas=2, **ecfg):
    ids, feats, cluster, scorer = world
    members = [_gus(scorer) for _ in range(n_replicas + 1)]
    for g in members:
        _boot(g, ids, feats)
    faults = FaultInjector()
    engine = GusEngine(members[0], EngineConfig(**ecfg),
                       replicas=members[1:], faults=faults)
    return engine, faults, feats


def test_dead_primary_fails_over_to_survivors(world):
    engine, faults, feats = _fleet(world)
    q = {k: v[:1] for k, v in feats.items()}
    faults.kill(FaultInjector.PRIMARY)
    faults.kill(0)                         # one replica dead too
    res = engine.query(q, k=5)
    assert res.ids.shape == (1, 5)
    survivor = engine.replica_set.members[1]
    dead = engine.replica_set.members[0]
    assert engine.failovers == 1
    assert survivor.failovers == 1 and survivor.served == 1
    assert dead.served == 0                # never answered from a dead replica
    assert engine.primary.served == 0
    st = engine.describe()
    assert st["failovers"] == 1
    assert st["replicas"][0]["alive"] is False


def test_all_dead_raises_explicit_unavailable(world):
    engine, faults, feats = _fleet(world, n_replicas=1)
    faults.kill(FaultInjector.PRIMARY)
    faults.kill(0)
    with pytest.raises(ServingUnavailableError):
        engine.query({k: v[:1] for k, v in feats.items()}, k=5)
    faults.revive(FaultInjector.PRIMARY)   # revival restores service
    res = engine.query({k: v[:1] for k, v in feats.items()}, k=5)
    assert res.ids.shape == (1, 5)


def test_slow_primary_hedges_and_p95_reflects_interference(world):
    engine, faults, feats = _fleet(world, n_replicas=1)
    q = {k: v[:1] for k, v in feats.items()}
    for _ in range(8):                     # baseline: fast, no hedges
        engine.query(q, k=5)
    assert engine.hedged == 0
    base_p95 = engine.describe()["serving"]["p95_ms"]
    faults.slow(FaultInjector.PRIMARY, 500.0)   # straggler: +500ms, no sleep
    for _ in range(2):
        engine.query(q, k=5)
    assert engine.hedged == 2              # deadline blown deterministically
    assert engine.replica_hedges == [2]    # both answers from the replica
    s = engine.describe()["serving"]
    assert s["max_ms"] >= 500.0            # interference visible in the tail
    assert s["p95_ms"] > base_p95
    faults.clear_slow(FaultInjector.PRIMARY)
    engine.query(q, k=5)
    assert engine.hedged == 2              # back to the fast path


def test_slow_replica_hedge_skips_to_next_eligible(world):
    engine, faults, feats = _fleet(world, n_replicas=2, hedge_ms=-1.0)
    q = {k: v[:1] for k, v in feats.items()}
    faults.kill(0)                         # dead replica must be skipped
    engine.query(q, k=5)
    engine.query(q, k=5)
    assert engine.replica_hedges == [0, 2]   # round robin over eligible only


def test_killed_replica_rejoins_with_catch_up(world):
    engine, faults, feats = _fleet(world, n_replicas=1,
                                   snapshot_every=1000, hedge_ms=-1.0)
    replica = engine.replica_set.members[0]
    faults.kill(0)
    stream = MutationStream(DATA, StreamConfig(batch_size=16, seed=21),
                            bootstrap_fraction=0.5)
    for _, mb in zip(range(3), stream):
        engine.submit_mutations(mb)        # replica misses all three
    assert replica.applied_seq == 0 and engine.seq == 3
    assert len(replica.gus.index) != len(engine.gus.index)
    faults.revive(0)
    res = engine.query({k: v[:1] for k, v in feats.items()}, k=5)
    # catch-up replayed the missed suffix before the replica served
    assert replica.catchups == 1 and replica.caught_up_batches == 3
    assert replica.applied_seq == engine.seq
    assert set(replica.gus.store._rows) == set(engine.gus.store._rows)
    assert replica.hedges == 1             # it answered this query
    assert res.ids.shape == (1, 5)


def test_revived_replica_rebootstraps_from_snapshot(world):
    """When the log no longer reaches back (a snapshot truncated it), the
    rejoining replica restores the snapshot corpus first, then replays."""
    engine, faults, feats = _fleet(world, n_replicas=1, snapshot_every=2)
    replica = engine.replica_set.members[0]
    faults.kill(0)
    stream = MutationStream(DATA, StreamConfig(batch_size=16, seed=22),
                            bootstrap_fraction=0.5)
    for _, mb in zip(range(3), stream):    # snapshot after 2, 1 in log
        engine.submit_mutations(mb)
    assert engine.seq_base == 2 and replica.applied_seq < engine.seq_base
    faults.revive(0)
    engine.query({k: v[:1] for k, v in feats.items()}, k=5)
    assert replica.applied_seq == engine.seq
    assert set(replica.gus.store._rows) == set(engine.gus.store._rows)


def test_partitioned_replica_excluded_until_heal(world):
    engine, faults, feats = _fleet(world, n_replicas=1, hedge_ms=-1.0,
                                   snapshot_every=1000)
    replica = engine.replica_set.members[0]
    q = {k: v[:1] for k, v in feats.items()}
    faults.partition(0)
    stream = MutationStream(DATA, StreamConfig(batch_size=16, seed=23),
                            bootstrap_fraction=0.5)
    engine.submit_mutations(next(iter(stream)))
    engine.query(q, k=5)                   # hedge finds no eligible replica
    assert engine.hedged == 0 and _skipped(engine) == 1
    assert engine.replica_hedges == [0]    # partitioned: stale, excluded
    assert engine.primary.served == 1      # the primary's answer stands
    faults.heal(0)
    engine.query(q, k=5)                   # heal + catch-up: eligible again
    assert engine.replica_hedges == [1]
    assert engine.hedged == 1 and _skipped(engine) == 1
    assert replica.applied_seq == engine.seq
