"""The per-layer readers of the query path's and the write path's spans:
nothing on a run without their spans, as on a program that does not
record them, and the value they should on a synthetic run."""
import numpy as np
import pytest

from bench.run import LayerRun
from bench.spec import layer_module

# span durations by name (ms), as ``run.span_readings`` gives them
SPANS = {
    "route": [70.0, 40.0, 50.0, 60.0],
    "answer_primary": [30.0, 40.0, 30.0, 40.0],
    "answer_hedge": [35.0],
    "embed": [1.0, 3.0],
    "sketch": [0.5, 1.5],
    "score": [10.0, 20.0],
    "device_wait": [2.0, 4.0, 6.0, 8.0, 5.0],
    "apply_lag": [float(v) for v in range(1, 101)],
}
# what the program recorded before the query path had spans of its own
OLD_SPANS = {"route": [70.0], "answer_primary": [60.0],
             "shard_search": [12.0], "handoff": [8.0], "flush": [9.0]}

CASES = [
    ("gus.embed_ms", 2.0),
    ("index.sketch_ms", 1.0),
    ("gus.score_ms", 15.0),
    ("gus.device_wait_ms", 25.0 / 5),       # 5 answers: 4 primary, 1 hedge
    ("engine.hedge_share", 25.0),           # 1 hedge over 4 dispatches
    ("pipeline.apply_lag_p95_ms", float(np.percentile(range(1, 101), 95))),
]


def layer_run(spans: dict) -> LayerRun:
    device = {"window_s": 1.0, "busy_s": 0.5, "devices": 1, "kernels": {}}
    return LayerRun(spans, device, 0, lambda calls: (0.0, "bytes"), [])


@pytest.mark.parametrize("metric,expected", CASES)
def test_a_reader_reads_its_spans(metric, expected):
    assert layer_module(metric).read(layer_run(SPANS)) == \
        pytest.approx(expected)


@pytest.mark.parametrize("metric", [m for m, _ in CASES])
def test_a_reader_without_its_spans_reports_nothing(metric):
    assert layer_module(metric).read(layer_run({})) is None


@pytest.mark.parametrize("metric", ["gus.embed_ms", "index.sketch_ms",
                                    "gus.score_ms", "gus.device_wait_ms",
                                    "pipeline.apply_lag_p95_ms"])
def test_a_reader_on_a_program_without_the_new_spans(metric):
    assert layer_module(metric).read(layer_run(OLD_SPANS)) is None


def test_no_hedge_reads_zero():
    run = layer_run({"route": [50.0, 60.0], "answer_primary": [40.0, 45.0]})
    assert layer_module("engine.hedge_share").read(run) == 0.0
