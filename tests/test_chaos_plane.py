"""Chaos tier: the serving plane under scripted faults and live traffic.

The invariants this tier pins (ISSUE 6 acceptance):

* **zero lost accepted requests** — every request the front-end admits
  receives exactly one terminal response, across replica kills, revives,
  stragglers, and partitions fired mid-traffic;
* **no answer from a dead replica** — a killed member's ``served``
  counter freezes until it is revived *and* caught up;
* **freshness rejoin** — a revived/healed member serves again only after
  catch-up restores its ``applied_seq`` to the committed sequence;
* the same invariants hold on real multi-pod meshes: 2 devices
  (2 pods x 1 shard) and 4 devices (2 pods x 2 shards), with each pod's
  ``ShardedGusIndex`` pinned to a disjoint device slice.

Everything is deterministic: faults are scripted at request-count
boundaries (never timers), traffic comes from seeded streams, and
injected straggler latency is added to measured time, never slept.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from benchmarks.loadgen import LoadgenConfig, run_loadgen
from repro.core import BucketConfig, DynamicGUS, GusConfig
from repro.core.maintenance import MaintenanceConfig
from repro.core.scorer import train_scorer
from repro.data.stream import MutationStream, StreamConfig
from repro.data.synthetic import OGB_ARXIV_LIKE, labeled_pairs, make_dataset
from repro.graph.cc import offline_components
from repro.graph.store import GraphConfig
from repro.serve import (EngineConfig, FaultInjector, Frontend,
                         FrontendConfig, GusEngine)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = dataclasses.replace(OGB_ARXIV_LIKE, n_points=300, n_clusters=8)
BUCKETS = BucketConfig(dense_tables=8, dense_bits=10, scalar_widths=(2.0,))


@pytest.fixture(scope="module")
def world():
    ids, feats, cluster = make_dataset(DATA)
    pf, lbl = labeled_pairs(feats, cluster, 600, DATA.spec, seed=1)
    scorer, _ = train_scorer(jax.random.PRNGKey(0), DATA.spec, pf, lbl,
                             steps=40)
    return ids, feats, scorer


def _gus(world, n=150):
    ids, feats, scorer = world
    gus = DynamicGUS(DATA.spec, BUCKETS, scorer,
                     GusConfig(scann_nn=10, backend="brute"))
    gus.bootstrap(ids[:n], {k: v[:n] for k, v in feats.items()})
    return gus


# --------------------------------------------------- 1 device (in-process)


@pytest.mark.chaos
def test_chaos_closed_loop_single_device(world):
    """Closed-loop traffic on the default single-device environment while
    the full fault script fires: kill -> straggler -> partition -> heal ->
    revive. Queues exceed the user count, so shedding is structurally
    impossible and every admitted request must complete."""
    faults = FaultInjector()
    engine = GusEngine(_gus(world), EngineConfig(snapshot_every=1000),
                       replicas=[_gus(world), _gus(world)], faults=faults)
    fe = Frontend(engine, FrontendConfig(query_queue=64, mutate_queue=64,
                                         query_dispatch=4,
                                         mutate_dispatch=2))
    stream = MutationStream(DATA, StreamConfig(batch_size=8, seed=17),
                            bootstrap_fraction=0.5)
    cfg = LoadgenConfig(mode="closed", requests=25, users=4,
                        mutate_every=5, k=5)
    reports = []

    def phase(tag):
        rep = run_loadgen(fe, stream, cfg)
        assert rep.lost == 0, (tag, rep.row())
        assert rep.shed == 0, (tag, rep.row())      # structurally impossible
        assert rep.errors == 0, (tag, rep.row())
        reports.append((tag, rep))
        return rep

    r0, r1 = engine.replica_set.members
    events = engine.obs.events                     # lifecycle event log
    phase("healthy")
    assert events.events("replica_down") == []     # healthy plane: no churn

    # -- replica 0 dies: it must not answer anything while down
    faults.kill(0)
    served_dead = r0.served
    faults.slow(FaultInjector.PRIMARY, 200.0)      # force hedging traffic
    mark, hedged_before = events.seq, engine.hedged
    phase("replica-dead+straggler")
    assert r0.served == served_dead                # zero answers while dead
    assert engine.hedged > 0 and r1.hedges > 0     # survivors carried it
    # the death was observed and attributed, and hedges left a record
    downs = events.events("replica_down", since=mark)
    assert [e["member"] for e in downs] == ["replica:0"]
    assert len(events.events("hedge", since=mark)) \
        == engine.hedged - hedged_before

    # -- partition replica 1: up, but stale -> excluded from hedging
    faults.partition(1)
    hedges_part = r1.hedges
    mark = events.seq
    phase("partitioned")
    assert r1.hedges == hedges_part                # stale: never eligible
    assert engine.primary.served > 0               # its answer stands
    parts = events.events("replica_partitioned", since=mark)
    assert [e["member"] for e in parts] == ["replica:1"]

    # -- heal + revive: both rejoin through freshness catch-up
    faults.heal(1)
    faults.revive(0)
    faults.clear_slow(FaultInjector.PRIMARY)
    mark = events.seq
    phase("recovered")
    assert r0.applied_seq == engine.seq            # caught up before serving
    assert r1.applied_seq == engine.seq
    assert r0.catchups >= 1 and r1.catchups >= 1
    # rejoin causality: up/healed transitions, then catch-up replays that
    # name the member and account for every missed batch
    assert [e["member"] for e in events.events("replica_up", since=mark)] \
        == ["replica:0"]
    assert [e["member"]
            for e in events.events("replica_healed", since=mark)] \
        == ["replica:1"]
    catch_ups = {e["member"]: e for e in events.events("catch_up",
                                                       since=mark)}
    assert {"replica:0", "replica:1"} <= set(catch_ups)
    assert all(e["batches"] >= 1 and e["seq"] <= engine.seq
               for e in catch_ups.values())
    assert not catch_ups["replica:1"]["rebootstrapped"]   # log reached back

    # -- post-recovery: revived replicas serve hedged traffic again
    faults.slow(FaultInjector.PRIMARY, 200.0)
    phase("hedging-after-recovery")
    assert r0.served > served_dead

    # global accounting closes across every phase
    total_accepted = sum(r.accepted for _, r in reports)
    total_done = sum(r.completed + r.errors for _, r in reports)
    assert total_accepted == total_done
    st = fe.describe()
    assert st["queued"] == {"query": 0, "mutate": 0}


@pytest.mark.chaos
def test_chaos_maintenance_plane_during_faults(world):
    """The concurrent maintenance plane rides through the fault script:
    the primary serves from versioned graph snapshots (staleness_bound=3)
    while a replica dies, the primary straggles, and the member rejoins.
    Invariants: zero lost accepted requests in every phase, the published
    view never lags the applied stream by more than the bound at any
    phase boundary, versions only move forward (no half-built snapshot
    is ever observable), and quiescence is exact."""
    ids, feats, scorer = world

    def mk(bound):
        gus = DynamicGUS(DATA.spec, BUCKETS, scorer, GusConfig(
            scann_nn=10, backend="brute",
            graph=GraphConfig(k=4, capacity=512),
            maintenance=MaintenanceConfig(staleness_bound=bound)))
        gus.bootstrap(ids[:150], {k: v[:150] for k, v in feats.items()})
        return gus

    faults = FaultInjector()
    engine = GusEngine(mk(3), EngineConfig(snapshot_every=1000,
                                           pipeline=True),
                       replicas=[mk(0), mk(0)], faults=faults)
    fe = Frontend(engine, FrontendConfig(query_queue=64, mutate_queue=64,
                                         query_dispatch=4,
                                         mutate_dispatch=2))
    stream = MutationStream(DATA, StreamConfig(batch_size=8, seed=23),
                            bootstrap_fraction=0.5)
    cfg = LoadgenConfig(mode="closed", requests=20, users=4,
                        mutate_every=4, k=5)
    pipe = engine.pipelines[0]
    assert pipe.bound == 3 and pipe.window_size() == 3   # pin is gone
    reports, versions = [], []

    def phase(tag):
        rep = run_loadgen(fe, stream, cfg)
        assert rep.lost == 0 and rep.shed == 0 and rep.errors == 0, \
            (tag, rep.row())
        view = engine.gus.graph.view()
        lag = engine.gus.seq_applied - view.seq
        assert 0 <= lag <= pipe.bound, (tag, lag)
        versions.append(view.version)
        reports.append((tag, rep))

    phase("healthy")
    faults.kill(0)                                 # replica dies mid-plane
    faults.slow(FaultInjector.PRIMARY, 200.0)      # and the primary lags
    phase("replica-dead+straggler")
    faults.revive(0)
    faults.clear_slow(FaultInjector.PRIMARY)
    phase("recovered")
    assert versions == sorted(versions)            # forward-only publishes
    assert pipe.worker.ticks > 0                   # the plane actually ran

    engine.flush()                                 # quiescence: exact again
    assert pipe.worker.lag() == 0 and pipe.worker.pending() == 0
    g = engine.gus.graph
    assert g.view().seq == engine.gus.seq_applied
    assert g.components() == offline_components(
        g.edges()[0], np.asarray(sorted(g.slot_of)))
    r0 = engine.replica_set.members[0]
    assert r0.applied_seq == engine.seq            # rejoined at freshness
    total_accepted = sum(r.accepted for _, r in reports)
    assert total_accepted == sum(r.completed + r.errors for _, r in reports)


@pytest.mark.chaos
def test_chaos_dead_primary_open_loop(world):
    """Open-loop arrivals against a dead primary: fail-over serves every
    accepted request from the replica; killing the replica too turns
    queries into explicit errors — never silence."""
    faults = FaultInjector()
    engine = GusEngine(_gus(world), EngineConfig(snapshot_every=1000),
                       replicas=[_gus(world)], faults=faults)
    fe = Frontend(engine, FrontendConfig(query_queue=256, mutate_queue=256))
    stream = MutationStream(DATA, StreamConfig(batch_size=8, seed=19),
                            bootstrap_fraction=0.5)
    faults.kill(FaultInjector.PRIMARY)
    rep = run_loadgen(fe, stream, LoadgenConfig(
        mode="open", requests=30, target_qps=10_000.0, mutate_every=6, k=5))
    assert rep.lost == 0 and rep.errors == 0
    assert engine.primary.served == 0
    assert engine.failovers > 0
    assert engine.replica_set.members[0].failovers == engine.failovers

    faults.kill(0)                                 # nobody left
    rep2 = run_loadgen(fe, stream, LoadgenConfig(
        mode="open", requests=12, target_qps=10_000.0, mutate_every=6, k=5))
    assert rep2.lost == 0                          # errors, not losses
    assert rep2.errors > 0


# ------------------------------------------- 2 / 4 devices (subprocess pods)


_POD_CODE = textwrap.dedent("""
    import dataclasses, json
    import jax
    import numpy as np
    from repro.ann.sharded_index import ShardedConfig
    from repro.core import BucketConfig, DynamicGUS, GusConfig
    from repro.core.scorer import train_scorer
    from repro.data.stream import MutationStream, StreamConfig
    from repro.data.synthetic import (OGB_ARXIV_LIKE, labeled_pairs,
                                      make_dataset)
    from repro.launch.mesh import make_pod_meshes
    from repro.serve import (EngineConfig, FaultInjector, Frontend,
                             FrontendConfig, GusEngine)
    from benchmarks.loadgen import LoadgenConfig, run_loadgen

    N_PODS, N_SHARDS = {n_pods}, {n_shards}
    DATA = dataclasses.replace(OGB_ARXIV_LIKE, n_points=300, n_clusters=8)
    ids, feats, cluster = make_dataset(DATA)
    pf, lbl = labeled_pairs(feats, cluster, 600, DATA.spec, seed=1)
    scorer, _ = train_scorer(jax.random.PRNGKey(0), DATA.spec, pf, lbl,
                             steps=30)

    # one pod mesh per replica group, over disjoint device slices
    meshes = make_pod_meshes(N_PODS, N_SHARDS)
    pod_devices = [set(d.id for d in m.devices.flat) for m in meshes]
    assert not (pod_devices[0] & pod_devices[1]), pod_devices

    def mk(pod):
        gus = DynamicGUS(DATA.spec, BucketConfig(
            dense_tables=8, dense_bits=10, scalar_widths=(2.0,)),
            scorer, GusConfig(scann_nn=10, backend="sharded",
                              sharded=ShardedConfig(
                                  n_shards=N_SHARDS, d_proj=32,
                                  n_partitions=8, nprobe_local=0,
                                  reorder=4096, pq_m=4, kmeans_iters=4,
                                  pq_iters=2, pod=pod)))
        gus.bootstrap(ids[:150],
                      {{k: v[:150] for k, v in feats.items()}})
        assert set(d.id for d in gus.index.mesh.devices.flat) \\
            == pod_devices[pod]
        return gus

    faults = FaultInjector()
    engine = GusEngine(mk(0), EngineConfig(snapshot_every=1000),
                       replicas=[mk(1)], faults=faults)
    fe = Frontend(engine, FrontendConfig(query_queue=64, mutate_queue=64,
                                         query_dispatch=4,
                                         mutate_dispatch=2))
    stream = MutationStream(DATA, StreamConfig(batch_size=8, seed=29),
                            bootstrap_fraction=0.5)
    cfg = LoadgenConfig(mode="closed", requests=15, users=3,
                        mutate_every=5, k=5)
    r0 = engine.replica_set.members[0]
    out = {{"pods": N_PODS, "shards": N_SHARDS, "phases": {{}}}}

    rep = run_loadgen(fe, stream, cfg)             # healthy
    out["phases"]["healthy"] = rep.row()

    faults.kill(0)                                 # replica pod dies
    served_dead = r0.served
    rep = run_loadgen(fe, stream, cfg)
    out["phases"]["replica_dead"] = rep.row()
    out["dead_served_delta"] = r0.served - served_dead

    faults.revive(0)                               # rejoin via catch-up
    faults.slow("primary", 200.0)                  # hedge to the rejoiner
    rep = run_loadgen(fe, stream, cfg)
    out["phases"]["recovered"] = rep.row()
    out["caught_up"] = bool(r0.applied_seq == engine.seq)
    out["catchups"] = r0.catchups
    out["revived_served_delta"] = r0.served - served_dead
    out["hedged"] = engine.hedged
    out["stores_equal"] = bool(
        set(r0.gus.store._rows) == set(engine.gus.store._rows))
    print(json.dumps(out))
""")


def _run_pod_chaos(n_devices: int, n_pods: int, n_shards: int) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + REPO
    code = _POD_CODE.format(n_pods=n_pods, n_shards=n_shards)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _assert_pod_invariants(res: dict) -> None:
    for tag, row in res["phases"].items():
        assert row["lost"] == 0, (tag, row)        # zero lost, every phase
        assert row["shed"] == 0, (tag, row)
        assert row["errors"] == 0, (tag, row)
    assert res["dead_served_delta"] == 0           # dead pod answered nothing
    assert res["caught_up"] and res["catchups"] >= 1
    assert res["stores_equal"]                     # rejoined at full freshness
    assert res["hedged"] > 0                       # straggler hedged to it
    assert res["revived_served_delta"] > 0         # and it served again


@pytest.mark.slow
@pytest.mark.chaos
def test_chaos_two_pods_one_shard():
    """2 devices: two single-shard pods. Replica-pod kill / revive /
    straggler under closed-loop traffic — zero lost accepted requests."""
    _assert_pod_invariants(_run_pod_chaos(2, n_pods=2, n_shards=1))


@pytest.mark.slow
@pytest.mark.chaos
def test_chaos_two_pods_two_shards():
    """4 devices: two pods x two index shards each — the same invariants
    on a mesh where each replica is itself a sharded index."""
    _assert_pod_invariants(_run_pod_chaos(4, n_pods=2, n_shards=2))
