"""The open-loop window's accounting, on a fake engine and a virtual
clock: latency from scheduled arrival over every request, visibility from
the engine's applied-batch counter, sheds and errors as failed."""
import numpy as np

from bench import harness
from bench.traffic import Arrival
from repro.core.types import NeighborResult
from repro.obs import Telemetry
from repro.serve.faults import FaultInjector
from repro.serve.frontend import Frontend, FrontendConfig


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class FakeGus:
    seq_applied = 0


class FakeEngine:
    """Answers a query in ``query_s`` of virtual time; applies submitted
    batches only when a query flushes them, as the pipelined engine does."""

    def __init__(self, clock, query_s=0.010):
        self.clock, self.query_s = clock, query_s
        self.obs, self.faults, self.gus = Telemetry(), FaultInjector(), \
            FakeGus()
        self.pending = 0

    def submit_mutations(self, batch):
        self.pending += 1

    def flush(self):
        self.gus.seq_applied += self.pending
        self.pending = 0

    def query(self, feats, k):
        self.flush()
        self.clock.t += self.query_s
        n = next(iter(feats.values())).shape[0]
        return NeighborResult(ids=np.zeros((n, k), np.int64),
                              weights=np.zeros((n, k), np.float32),
                              distances=np.zeros((n, k), np.float32))


class FakeContent:
    def query(self, rows):
        return {"dense:x": np.zeros((rows, 2), np.float32)}

    def mutation(self, make_up):
        n = len(make_up)
        return (np.zeros(n, np.int32), np.arange(n, dtype=np.int64),
                {"dense:x": np.zeros((n, 2), np.float32)})


def window(fe_cfg=FrontendConfig(), query_s=0.010):
    clock = Clock()
    engine = FakeEngine(clock, query_s)
    fe = Frontend(engine, fe_cfg, clock=clock)
    dep = harness.Deployment(cfg={}, engine=engine, fe=fe,
                             content=FakeContent(), boot_ids=None,
                             boot_feats=None, params={}, k=3, phases={})
    w = harness.Window(dep, harness.Annotations(False))

    def sleep(s):
        clock.t += s
    w.sleep = sleep
    return w, clock


def test_latency_counts_from_scheduled_arrival_over_every_request():
    # ten queries due at once: one step answers 8 of them (the front end's
    # dispatch bound), the next the other 2, each step 10 ms
    w, clock = window()
    w.run([Arrival(0.0, "query") for _ in range(10)], 1)
    lat = sorted(w.query_latencies())
    assert len(lat) == 10
    np.testing.assert_allclose(lat, [10.0] * 8 + [20.0] * 2, atol=1e-6)
    assert harness.percentile(w.query_latencies(), 50) == np.percentile(
        lat, 50)
    assert harness.percentile(w.query_latencies(), 95) == np.percentile(
        lat, 95)


def test_a_late_step_counts_for_requests_that_waited_behind_it():
    # a query due at 5 ms arrives while a 50 ms step runs: its latency
    # counts the wait from its scheduled arrival, not from its admission
    w, clock = window(query_s=0.050)
    w.run([Arrival(0.0, "query"), Arrival(0.005, "query")], 1)
    np.testing.assert_allclose(sorted(w.query_latencies()), [50.0, 95.0],
                               atol=1e-6)


def test_visibility_comes_from_the_applied_batch_count():
    # a mutation alone is dispatched but not applied; it becomes visible
    # at the end of the step whose query flushes it
    w, clock = window()
    w.run([Arrival(0.0, "mutate", (0,) * 8), Arrival(0.030, "query")], 1)
    (m,) = w.mutations.values()
    np.testing.assert_allclose(m["visible_ms"], 40.0, atol=1e-6)
    assert harness.visible_through(5, 2) == 3
    assert harness.visible_through(1, 2) == 0


def test_sheds_count_as_failed():
    w, clock = window(FrontendConfig(query_queue=2))
    w.run([Arrival(0.0, "query") for _ in range(5)], 1)
    assert w.attempted == 5
    assert w.failed == 3
    assert len(w.query_latencies()) == 2
    assert w.lost() == 0


def test_answers_past_the_drain_limit_are_lost_but_replayed(monkeypatch):
    # one 30 s query per step, limit 40 s past the last arrival: the first
    # query is answered in time; the mutation and the second query come
    # at 60 s, the third, still queued at the limit, at 90 s. All three
    # are lost, and the mutation is recorded as dispatched, so the
    # reference replays what the engine applied
    monkeypatch.setattr(harness, "DRAIN_LIMIT_S", 40.0)
    w, clock = window(FrontendConfig(query_dispatch=1), query_s=30.0)
    w.run([Arrival(0.0, "query")] * 3
          + [Arrival(0.001, "mutate", (0,) * 8)], 1)
    assert not w.busy()
    assert w.lost() == 3
    assert len(w.query_latencies()) == 3
    assert len(w.dep.dispatched) == 1
    assert w.failed == 0
