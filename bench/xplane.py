"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

Busy time is the union of the intervals in which an operation ran on a
device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), inside
the window the harness marks with its ``bench.window`` host span. Idle
gaps of 0.1 ms or more are named by what the host was doing at their
midpoint: the innermost ``bench.*`` span, and inside it the innermost
other event on the same host thread.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Ev:
    name: str
    t0: int            # ns
    t1: int
    stats: dict


def _events(line) -> list:
    out = []
    for e in line.events:
        t0 = int(e.start_ns)
        out.append(Ev(e.name, t0, t0 + int(e.duration_ns),
                      dict(e.stats) if e.stats is not None else {}))
    return out


def find_trace(log_dir: str) -> str:
    found = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str):
    """(device op events per device plane, host threads' events)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = [_events(l) for l in plane.lines if l.name == OPS_LINE]
            if ops:
                devices[plane.name] = ops[0]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host[f"{plane.name}/{line.name}"] = _events(line)
    return devices, host


def union(intervals, lo: int, hi: int) -> list:
    """Merged [t0, t1) intervals clipped to [lo, hi)."""
    out = []
    for t0, t1 in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if t1 <= t0:
            continue
        if out and t0 <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t1)
        else:
            out.append([t0, t1])
    return out


def window(host: dict) -> tuple[int, int, str]:
    """The harness's window span and the host thread it ran on."""
    for thread, evs in host.items():
        for e in evs:
            if e.name == WINDOW_SPAN:
                return e.t0, e.t1, thread
    raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")


class HostLabels:
    """Innermost host activity at a time, on one thread."""

    SCAN = 4000           # events scanned back from a time

    def __init__(self, evs: list):
        evs = sorted((e for e in evs if e.name != WINDOW_SPAN),
                     key=lambda e: e.t0)
        self.bench = [e for e in evs if e.name.startswith("bench.")]
        self.other = [e for e in evs if not e.name.startswith("bench.")]
        self.b0 = [e.t0 for e in self.bench]
        self.o0 = [e.t0 for e in self.other]

    @classmethod
    def _inner(cls, evs, starts, t):
        i = bisect.bisect_right(starts, t)
        for e in reversed(evs[max(0, i - cls.SCAN):i]):
            if e.t1 > t:
                return e.name
        return None

    def __call__(self, t: int) -> str:
        bench = self._inner(self.bench, self.b0, t) or "bench.none"
        inner = self._inner(self.other, self.o0, t)
        return bench if inner is None else f"{bench} > {inner}"


SHORT_GAP_NS = 100_000    # gaps shorter than this are pooled unnamed


def reduce(path: str, kernels: dict, top: int = 10) -> dict:
    """Device numbers of the traced window.

    ``kernels`` maps a kernel's metric name to substrings of which one
    names its device op, in the op's name or one of its stats. Returns
    busy and window seconds (busy averaged over the devices that ran
    anything), per-kernel calls and device seconds, the device ops that
    took most time and the host activity during the longest idle time."""
    devices, host = load(path)
    lo, hi, thread = window(host)
    per_dev_busy, ops = [], collections.Counter()
    kstats = {k: {"calls": 0, "device_s": 0.0} for k in kernels}
    gaps = collections.Counter()
    label = HostLabels(host[thread])
    for evs in devices.values():
        inside = [e for e in evs if e.t1 > lo and e.t0 < hi]
        if not inside:
            continue
        merged = union(((e.t0, e.t1) for e in inside), lo, hi)
        per_dev_busy.append(sum(b - a for a, b in merged) / 1e9)
        for e in inside:
            dur = (min(e.t1, hi) - max(e.t0, lo)) / 1e9
            ops[e.name] += dur
            text = None
            for metric, markers in kernels.items():
                if text is None:
                    text = " ".join([e.name, *map(str, e.stats.values())])
                if any(m in text for m in markers):
                    kstats[metric]["calls"] += 1
                    kstats[metric]["device_s"] += dur
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b - a >= SHORT_GAP_NS:
                gaps[label((a + b) // 2)] += (b - a) / 1e9
            elif b > a:
                gaps["(gaps under 0.1 ms)"] += (b - a) / 1e9
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": (sum(per_dev_busy) / len(per_dev_busy)
                   if per_dev_busy else 0.0),
        "devices": len(per_dev_busy),
        "kernels": kstats,
        "device_ops": [[n, s] for n, s in ops.most_common(top)],
        "idle_gaps": [[n, s] for n, s in gaps.most_common(top)],
    }
