"""The trace reduction, the peaks table and the fused kernel's least-work
count. The trace is written here as an XSpace text proto with the planes,
lines and names the reduction reads: a device op line, the host thread
with the harness's ``bench.*`` spans, and the kernel named in an op's
``tf_op`` metadata."""
import pytest
from jax.profiler import ProfileData

from bench import peaks, xplane
from bench.spec import layer_module

KERNELS = dict([layer_module("fused_query.device_ms").KERNEL])

# times in ns (line start 1000 + offset): device ops [1000, 3000) a
# fusion, [4000, 5000) and [5500, 10000) the fused kernel; the window is
# [1500, 11500); the host steps over [1000, 6000), runs a jitted call over
# [3000, 3800) inside it, and idles over [10000, 11500)
TRACE = '''
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000
             stats { metadata_id: 10
                     str_value: "jit(step)/jit(fused_query_kernel)/pallas_call" } }
    events { metadata_id: 3 offset_ps: 4500000 duration_ps: 4500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "custom-call.3" } }
  event_metadata { key: 3 value { id: 3 name: "_fused_kernel" } }
  stat_metadata { key: 10 value { id: 10 name: "tf_op" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 2
    name: "python"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 3 offset_ps: 2000000 duration_ps: 800000 }
    events { metadata_id: 4 offset_ps: 9000000 duration_ps: 1500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(query)" } }
  event_metadata { key: 4 value { id: 4 name: "bench.idle" } }
}
'''


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(TRACE))
    old = xplane.SHORT_GAP_NS
    xplane.SHORT_GAP_NS = 100           # name every gap of this tiny trace
    try:
        yield xplane.reduce(str(path), KERNELS)
    finally:
        xplane.SHORT_GAP_NS = old


def test_busy_and_idle_inside_the_window(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(10_000e-9)
    # busy: [1000, 3000) clipped to [1500, 3000), [4000, 5000), [5500,
    # 10000): 1500 + 1000 + 4500 ns
    assert reduced["busy_s"] == pytest.approx(7_000e-9)
    # gaps named by the host at their midpoint: [3000, 4000) inside the
    # jitted call, [5000, 5500) in the step, [10000, 11500) idle
    assert dict(reduced["idle_gaps"]) == {
        "bench.step > PjitFunction(query)": pytest.approx(1_000e-9),
        "bench.step": pytest.approx(500e-9),
        "bench.idle": pytest.approx(1_500e-9)}


def test_the_kernel_by_either_name(reduced):
    k = reduced["kernels"]["fused_query"]
    assert k["calls"] == 2
    assert k["device_s"] == pytest.approx(5_500e-9)
    ops = dict(reduced["device_ops"])
    assert ops["_fused_kernel"] == pytest.approx(4_500e-9)
    assert list(ops) == ["_fused_kernel", "fusion.1", "custom-call.3"]


def test_union_merges_and_clips():
    assert xplane.union([(0, 5), (3, 9), (12, 20)], 2, 15) == [[2, 9],
                                                                 [12, 15]]


def test_peaks_table():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["int8_ops"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_least_work_counts_live_copies():
    p = peaks.peaks("TPU v5 lite")
    one, bound = peaks.fused_query_least_s(10_000, 8, 256, p)
    two, _ = peaks.fused_query_least_s(20_000, 8, 256, p)
    assert bound == "bytes"
    assert two - one == pytest.approx(10_000 * 12 / 819e9)
    assert one == pytest.approx((10_000 * 12 + 8 * 256 * 4) / 819e9)


def test_least_work_counts_every_call_and_every_real_row():
    p = peaks.peaks("TPU v5 lite")
    one, _ = peaks.fused_query_least_s(10_000, 8, 256, p, calls=1, rows=8)
    four, _ = peaks.fused_query_least_s(10_000, 8, 256, p, calls=4, rows=8)
    assert four - one == pytest.approx(3 * 10_000 * 12 / 819e9)
    # more rows in one call is more least work, not less
    more, _ = peaks.fused_query_least_s(10_000, 8, 256, p, calls=1, rows=16)
    assert more - one == pytest.approx(8 * 8 * 256 * 4 / 819e9)
