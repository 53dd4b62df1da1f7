"""Unit contract of the ``repro.obs`` telemetry plane: registry
get-or-create and exporters, span-tree well-formedness (including the
backdated ``add_span`` anchoring rule), the profiler annotation of live
spans, sampling arithmetic, the event ring, and the trace ->
latency-breakdown reconstruction."""
import contextlib
import json
import subprocess
import sys
from pathlib import Path

import pytest

from repro.obs import (DEFAULT_SAMPLE_EVERY, EventLog, MetricsRegistry,
                       NULL_TRACE, Telemetry, Trace, Tracer,
                       latency_breakdown)
from repro.utils.timing import percentiles

# ------------------------------------------------------------- registry


def test_registry_get_or_create_and_type_clash():
    reg = MetricsRegistry()
    c = reg.counter("engine_widgets_total", "widgets")
    assert reg.counter("engine_widgets_total") is c   # same instrument
    with pytest.raises(ValueError):                   # kind is sticky
        reg.gauge("engine_widgets_total")
    with pytest.raises(ValueError):                   # snake_case only
        reg.counter("Engine_Widgets")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert reg.names() == ["engine_widgets_total"]


def test_histogram_summary_matches_percentiles():
    reg = MetricsRegistry()
    h = reg.histogram("engine_demo_ms")
    samples = [0.2, 1.5, 3.0, 40.0, 900.0]
    for ms in samples:
        h.observe(ms)
    assert h.summary() == percentiles(samples)        # single implementation
    assert h.count == len(samples) and h.sum == pytest.approx(sum(samples))
    cum = h.cumulative()
    assert cum == sorted(cum) and cum[-1] == len(samples)
    h.reset()
    assert h.count == 0 and h.cumulative()[-1] == 0


def test_snapshot_delta_semantics():
    reg = MetricsRegistry()
    c, g, h = (reg.counter("obs_a_total"), reg.gauge("obs_b"),
               reg.histogram("obs_c_ms"))
    c.inc(3)
    g.set(7)
    h.observe(10.0)
    before = reg.snapshot()
    c.inc(2)
    g.set(4)                                          # gauges report current
    h.observe(30.0)
    d = reg.delta(before)
    assert d["obs_a_total"]["value"] == 2
    assert d["obs_b"]["value"] == 4
    assert d["obs_c_ms"] == {"type": "histogram", "count": 1, "sum": 30.0}


def test_exporters_round_trip():
    reg = MetricsRegistry()
    reg.counter("obs_events_total", "things").inc(2)
    reg.histogram("obs_lat_ms", "latency").observe(3.0)
    prom = reg.to_prometheus()
    assert "# TYPE obs_events_total counter" in prom
    assert "obs_events_total 2" in prom
    assert '# TYPE obs_lat_ms histogram' in prom
    assert 'obs_lat_ms_bucket{le="+Inf"} 1' in prom
    assert "obs_lat_ms_count 1" in prom
    snap = json.loads(reg.to_json())
    assert set(snap) == set(reg.names())
    assert snap["obs_lat_ms"]["count"] == 1


# ---------------------------------------------------------------- traces


def test_span_tree_well_formed():
    t = [0.0]
    tr = Trace("request", clock=lambda: t[0])
    with tr.span("engine_query"):
        t[0] = 1.0
        with tr.span("route", batch=2):
            t[0] = 2.0
    tr.finish()
    assert tr.problems() == []
    assert [s.name for s in tr.spans] == ["request", "engine_query", "route"]
    route = tr.find("route")[0]
    assert route.meta["batch"] == 2
    assert route.duration_ms == pytest.approx(1000.0)
    assert tr.spans[route.parent].name == "engine_query"


def test_add_span_backdating_widens_open_ancestors():
    t = [5.0]
    tr = Trace("request", clock=lambda: t[0])
    with tr.span("engine_query"):
        # a queue wait that started before the trace existed
        tr.add_span("queue_wait", 1.0, 5.0, rid=7)
        t[0] = 6.0
    tr.finish()
    assert tr.problems() == []                        # nothing escapes
    assert tr.root.t0 == 1.0                          # root widened
    assert tr.find("engine_query")[0].t0 == 1.0       # open ancestor widened


def test_problems_catches_malformed_trees():
    tr = Trace("request", clock=lambda: 0.0)
    with tr.span("child"):
        pass
    tr.finish()
    tr.spans[1].t0, tr.spans[1].t1 = -1.0, 2.0        # escapes the root
    assert any("escapes parent" in p for p in tr.problems())
    tr2 = Trace("request", clock=lambda: 0.0)
    with tr2.span("open"):
        assert any("never closed" in p for p in tr2.problems())


def test_effective_ms_carries_injected_latency():
    tr = Trace("request", clock=lambda: 0.0)
    sp = tr.add_span("answer_primary", 0.0, 0.001, extra_ms=500.0)
    assert sp.effective_ms == pytest.approx(501.0)


def test_tracer_sampling_arithmetic():
    off = Tracer(sample_every=0)
    assert all(off.trace("r") is NULL_TRACE for _ in range(5))
    every3 = Tracer(sample_every=3)
    kinds = [every3.trace("r").sampled for _ in range(9)]
    assert kinds == [True, False, False] * 3          # 1st, 4th, 7th
    assert every3.started == 9 and every3.sampled == 3
    for _ in range(4):
        every3.collect(every3.trace("r"))             # unsampled: dropped
    always = Tracer(sample_every=1)
    always.collect(always.trace("r"))
    assert len(always.finished) == 1
    assert always.finished[0].root.t1 is not None     # collect() finishes


def test_tracer_activate_is_ambient_and_nestable():
    tracer = Tracer(sample_every=1)
    tr = tracer.trace("request")
    with tracer.span("orphan"):                       # nothing active: no-op
        pass
    with tracer.activate(tr):
        with tracer.span("inner"):
            pass
        tracer.add_span("late", tr.root.t0, tr.root.t0)
    assert tracer.active is None                      # restored on exit
    assert [s.name for s in tr.spans] == ["request", "inner", "late"]


class FakeAnnotations:
    """An annotation factory that logs each enter and exit by name."""

    def __init__(self):
        self.log = []

    @contextlib.contextmanager
    def __call__(self, name):
        self.log.append(("enter", name))
        try:
            yield
        finally:
            self.log.append(("exit", name))


def test_live_spans_enter_one_annotation_each_nested_as_the_tree():
    ann = FakeAnnotations()
    tracer = Tracer(sample_every=1, annotation=ann)
    tr = tracer.trace("request")
    with tracer.activate(tr):
        with tracer.span("answer_primary"):
            with tracer.span("embed"):
                pass
            with tracer.span("score"):
                with tracer.span("gather"):
                    pass
        tracer.add_span("queue_wait", tr.root.t0, tr.root.t0)  # backdated
    tracer.collect(tr)
    assert ann.log == [
        ("enter", "answer_primary"), ("enter", "embed"), ("exit", "embed"),
        ("enter", "score"), ("enter", "gather"), ("exit", "gather"),
        ("exit", "score"), ("exit", "answer_primary")]
    assert tr.problems() == []


@pytest.mark.parametrize("sample_every", [1, 0],
                         ids=["null_trace", "sample_every_0"])
def test_unsampled_spans_enter_no_annotation(sample_every):
    """The shared NULL_TRACE, and every trace of a disabled tracer."""
    ann = FakeAnnotations()
    tracer = Tracer(sample_every=sample_every, annotation=ann)
    tr = tracer.trace("request") if sample_every == 0 else NULL_TRACE
    assert tr is NULL_TRACE
    with tracer.activate(tr):
        with tracer.span("answer_primary"):
            with tracer.span("embed"):
                pass
    with tr.span("direct"):
        pass
    assert ann.log == []


def test_add_apart_widens_no_open_span():
    t = [10.0]
    tracer = Tracer(sample_every=1, clock=lambda: t[0],
                    annotation=FakeAnnotations())
    tr = tracer.trace("request")
    with tracer.activate(tr):
        with tracer.span("handoff"):
            t[0] = 11.0
            tracer.add_apart("applied", "apply_lag", 2.0, 11.0)
    tracer.collect(tr)
    assert tr.find("handoff")[0].t0 == 10.0 and tr.root.t0 == 10.0
    apart = tracer.finished[0]
    assert apart.root.name == "applied" and apart.problems() == []
    (lag,) = apart.find("apply_lag")
    assert lag.parent == 0 and (lag.t0, lag.t1) == (2.0, 11.0)
    idle = Tracer(sample_every=1)
    assert idle.add_apart("applied", "apply_lag", 0.0, 1.0) is None


def test_obs_imports_without_jax():
    """The profiler annotation imports JAX only on the first live span."""
    import repro
    src = str(Path(repro.__path__[0]).parent)
    code = ("import sys; sys.path.insert(0, sys.argv[1]); import repro.obs; "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    assert subprocess.run([sys.executable, "-c", code, src]).returncode == 0


# ---------------------------------------------------------------- events


def test_event_log_ring_and_windows():
    log = EventLog(keep=4)
    first = log.emit("failover", member="r0")
    mark = log.seq
    for i in range(5):
        log.emit("hedge", primary_ms=float(i))
    assert len(log) == 4                              # bounded ring
    assert log.seq == 6                               # seq survives wrap
    assert first not in list(log)
    assert [e["primary_ms"] for e in log.events("hedge", since=mark)] \
        == [0.0, 1.0, 2.0, 3.0, 4.0][-4:]
    assert log.last("hedge")["primary_ms"] == 4.0
    assert log.counts() == {"hedge": 4}


# ----------------------------------------------------- latency breakdown


def test_latency_breakdown_reconstruction():
    t = [0.0]
    clock = lambda: t[0]                              # noqa: E731
    traces = []
    for svc_s, hedge_s, waits_s in ((0.010, 0.0, [0.001, 0.003]),
                                    (0.020, 0.050, [0.002])):
        tr = Trace("request", clock=clock)
        anchor = t[0]
        for w in waits_s:
            tr.add_span("queue_wait", anchor - w, anchor)
        with tr.span("engine_query"):
            tr.add_span("answer_primary", t[0], t[0] + svc_s)
            if hedge_s:
                tr.add_span("answer_hedge", t[0], t[0] + hedge_s)
            t[0] += svc_s + hedge_s
        traces.append(tr.finish())
        assert tr.problems() == []
    bd = latency_breakdown(traces)
    # per-request queue waits; group service/hedge attributed per request
    assert bd["queue_wait"]["n"] == 3
    assert bd["queue_wait"]["max_ms"] == pytest.approx(3.0)
    assert bd["service"]["n"] == 3
    assert bd["service"]["max_ms"] == pytest.approx(20.0)
    assert bd["hedge_wait"]["p50_ms"] == pytest.approx(0.0)
    assert bd["hedge_wait"]["max_ms"] == pytest.approx(50.0)


# ------------------------------------------------------------- telemetry


def test_telemetry_snapshot_shape():
    obs = Telemetry()
    assert obs.tracer.sample_every == DEFAULT_SAMPLE_EVERY
    obs.registry.counter("obs_t_total").inc()
    obs.events.emit("snapshot", rows=5)
    obs.tracer.sample_every = 1
    obs.tracer.collect(obs.tracer.trace("request"))
    snap = obs.snapshot()
    assert snap["metrics"]["obs_t_total"]["value"] == 1
    assert snap["events"] == [{"seq": 1, "kind": "snapshot", "rows": 5}]
    assert snap["traces"]["finished"] == 1
