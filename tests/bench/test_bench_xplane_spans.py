"""An idle gap is named by the program span the host was in: the
program's spans enter profiler host annotations of their own names, so
the trace reduction reads them beside the harness's ``bench.*`` spans.
The trace is an XSpace text proto, as in ``test_bench_xplane.py``."""
import pytest
from jax.profiler import ProfileData

from bench import xplane

# times in ns (line start 1000 + offset): device ops [1000, 3000) and
# [6000, 8000); the window and one step span [1000, 9000); inside the
# step the host answers over [2000, 8800) and sketches over [3000, 5500)
TRACE = '''
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 2
    name: "python"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 8000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 8000000 }
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 6800000 }
    events { metadata_id: 4 offset_ps: 2000000 duration_ps: 2500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } }
  event_metadata { key: 3 value { id: 3 name: "answer_primary" } }
  event_metadata { key: 4 value { id: 4 name: "sketch" } }
}
'''


def test_idle_gaps_are_named_by_the_program_span(tmp_path, monkeypatch):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(TRACE))
    monkeypatch.setattr(xplane, "SHORT_GAP_NS", 100)   # name tiny gaps
    red = xplane.reduce(str(path), {})
    assert red["busy_s"] == pytest.approx(4_000e-9)
    # [3000, 6000) has its midpoint in the sketch, [8000, 9000) in the
    # answer after the sketch ended
    assert dict(red["idle_gaps"]) == {
        "bench.step > sketch": pytest.approx(3_000e-9),
        "bench.step > answer_primary": pytest.approx(1_000e-9)}
