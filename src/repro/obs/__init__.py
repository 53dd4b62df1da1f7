"""Telemetry plane: metrics registry, per-request tracing, lifecycle events.

  registry.py — ``MetricsRegistry``: counters / gauges / fixed-bucket
                histograms with cheap always-on recording, snapshot and
                delta semantics, JSON + Prometheus text exporters;
  trace.py    — ``Tracer``/``Trace``: sampled per-request span trees
                through admission -> routing/hedging -> shard fan-out ->
                mutation stages, plus ``latency_breakdown`` (queue-wait /
                service / hedge-wait percentiles from trace data);
  events.py   — ``EventLog``: structured lifecycle transitions
                (compaction, re-split, window close, replica
                kill/rejoin/catch-up, admission sheds) so chaos tests can
                assert *why*, not just *that*.

``Telemetry`` bundles the three behind one handle. ``GusEngine`` owns
one per serving plane and shares it with its ``Frontend``, its
``MutationPipeline``s, and (via ``bind_telemetry``) the primary's
``ShardedGusIndex``, so every instrument of one plane exports through a
single registry; components built standalone make their own. The
instrument catalog, naming conventions, sampling knobs, and exporter
formats are documented in docs/OBSERVABILITY.md and validated by
``tools/check_metrics.py`` in CI.
"""
from __future__ import annotations

import time

from repro.obs.events import Event, EventLog
from repro.obs.registry import (DEFAULT_MS_BUCKETS, Counter, Gauge,
                                Histogram, MetricsRegistry)
from repro.obs.trace import (APPLIED_ROOT, NULL_TRACE, NullTrace, Span,
                             Trace, Tracer, latency_breakdown)

# default per-request trace sampling: every 16th request group carries a
# span tree (0 = off, 1 = always-on; the overhead gate in
# benchmarks/latency.py measures this default against tracing off)
DEFAULT_SAMPLE_EVERY = 16


class Telemetry:
    """One serving plane's registry + tracer + event log."""

    def __init__(self, registry: MetricsRegistry | None = None,
                 tracer: Tracer | None = None,
                 events: EventLog | None = None,
                 sample_every: int = DEFAULT_SAMPLE_EVERY,
                 clock=time.perf_counter):
        self.registry = registry if registry is not None else \
            MetricsRegistry()
        self.tracer = tracer if tracer is not None else \
            Tracer(sample_every=sample_every, clock=clock)
        self.events = events if events is not None else EventLog()

    def snapshot(self) -> dict:
        """One self-describing dump: metrics, recent events, trace stats."""
        return {
            "metrics": self.registry.snapshot(),
            "events": [{"seq": e.seq, "kind": e.kind, **e.fields}
                       for e in self.events],
            "traces": self.tracer.describe(),
        }
