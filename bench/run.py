#!/usr/bin/env python3
"""Chip benchmark of the served path: one cell, one seed, one run.

    python bench/run.py --workload products.ycsb-a --seed 7 \\
        --seconds 51 --trace 0

The cell's configuration, traffic mix and offered rate are the files that
``BENCHMARK.json`` names (``bench/spec.py``). One process: set-up builds
the deployment from the seed and warms it up with the cell's own mix,
then the window offers the mix open loop for ``--seconds``, answers are
awaited, and the plain reference checks them. With ``--trace 0`` the
result carries the cell's end-to-end metrics; with ``--trace 1`` the
program's spans are recorded for every request and the window runs under
the profiler, and the result carries the per-layer metrics. The last line
of standard output is the result as JSON; the numbers compared for
``correct`` end standard error. Without a TPU, or with fewer chips than
the cell asks for, it exits with 2 and prints no result.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import shutil
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
# the TPU runtime would otherwise log to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parent.parent
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import check, harness, spec  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"
PROBES = 256


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class Compiles:
    """Counts programs JAX lowers (each new function or shape) and, of
    those, the ones loaded from the persistent compilation cache rather
    than compiled."""

    def __init__(self):
        import jax
        self.lowered = self.cached = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._hit)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1

    def _hit(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cached += 1

    def mark(self) -> tuple[int, int]:
        return self.lowered, self.cached


def configure_jax() -> str:
    """The program's fixed compile cache, holding every program."""
    import jax
    from repro.launch.cache import configure_compile_cache
    where = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class LayerRun:
    """What the per-layer readers read: span durations by name (ms), the
    trace's device numbers, the window's compile count, the fused
    kernel's least time over a number of its calls, and the queries'
    latencies."""

    def __init__(self, spans, device, compiles, least, query_ms):
        self.spans = spans
        self.device = device
        self.compiles_in_window = compiles
        self._least = least
        self.query_ms = query_ms      # every query's latency in the window

    def fused_query_least_s(self, calls: int):
        return self._least(calls)


def span_readings(tracer) -> tuple[dict, int]:
    """Span durations by name (ms), and the query rows the index searched
    (the ``batch`` of every ``shard_search`` span)."""
    out, rows = collections.defaultdict(list), 0
    for tr in tracer.finished:
        for sp in tr.spans[1:]:
            out[sp.name].append(sp.duration_ms)
            if sp.name == "shard_search":
                rows += int(sp.meta.get("batch", 0))
    return out, rows


def setup(cell, seed: int):
    """Build the deployment and warm it up; returns (deployment,
    compile counter)."""
    compiles = Compiles()
    dep = harness.build(cell, seed)
    for name, secs in dep.phases.items():
        log(f"setup {name} {secs:.3f}")
    t = time.perf_counter()
    w = harness.warm_up(dep, cell, seed, float(cell.mix["warm_seconds"]))
    log(f"setup warm_up_s {time.perf_counter() - t:.3f}; programs lowered "
        f"{compiles.mark()[0]}; warm-up steps: {w.step_summary()}")
    return dep, compiles


def run_cell(cell, seed: int, seconds: float, trace: bool,
             keep_trace: bool = False) -> tuple[dict, dict, dict]:
    """One run of ``cell``; returns (result, compared numbers, checker
    info)."""
    dep, compiles = setup(cell, seed)
    rec = observe(cell, dep, compiles, seed, seconds, trace, keep_trace)
    # the reference runs once the program's state is freed
    dep.engine = dep.fe = None
    return conclude(cell, dep, rec, seed)


def measure(cell, dep, compiles, seed: int, seconds: float, trace: bool,
            keep_trace: bool = False, **checker):
    """``observe`` then ``conclude``, keeping the deployment for another
    window; returns (result, compared numbers, checker info)."""
    rec = observe(cell, dep, compiles, seed, seconds, trace, keep_trace)
    return conclude(cell, dep, rec, seed, **checker)


def observe(cell, dep, compiles, seed: int, seconds: float, trace: bool,
            keep_trace: bool = False) -> dict:
    """The window and the probes on a warmed-up deployment, and what the
    comparison and the metrics need of the program afterwards."""
    import jax
    from repro.obs import Tracer

    dev = jax.devices()
    engine, gus = dep.engine, dep.engine.gus
    engine.obs.tracer = tracer = Tracer(sample_every=1 if trace else 0,
                                        keep=10**7)
    arrivals = harness.schedule(cell.mix, float(cell.rate["ops_per_s"]),
                                seconds, seed)
    copies0 = gus.index.occupancy()["live_rows"]
    setup_s = time.perf_counter() - T_START
    before = compiles.mark()
    log(f"setup_s {setup_s:.3f}; programs in set-up: {before[0]} lowered, "
        f"{before[1]} of them from the persistent cache")
    ann = harness.Annotations(trace)
    w = harness.Window(dep, ann, spans=trace)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    with ann(harness.WINDOW_SPAN):
        t0 = w.run(arrivals, int(cell.mix["query_rows"]))
        w.flush()
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    after = compiles.mark()
    in_window = after[0] - before[0]
    copies1 = gus.index.occupancy()["live_rows"]
    spans, rows = span_readings(tracer)
    engine.obs.tracer = Tracer(sample_every=0)
    late = w.lateness_ms
    log(f"window {window_s:.3f} s: {len(w.queries)} queries, "
        f"{len(w.mutations)} mutation requests accepted, {w.failed} failed, "
        f"{w.lost()} lost; programs in window: {in_window} lowered, "
        f"{after[1] - before[1]} of them from the persistent cache")
    log(f"window steps: {w.step_summary()}")
    log(f"generator lateness ms p50 {harness.percentile(late, 50)} "
        f"p95 {harness.percentile(late, 95)} max {max(late, default=0.0)}")

    probes = harness.probe_queries(dep, PROBES, seed)
    stats = dev[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    log(f"memory_peak_bytes {peak} of {stats.get('bytes_limit')}")
    return {"window": w, "probes": probes, "peak": peak, "trace": trace,
            "edges": gus.graph.edges() if gus.graph is not None else None,
            "pq": (gus.index.cfg.pq_m, gus.index.cfg.pq_centers),
            "copies": (copies0 + copies1) / 2, "spans": spans, "rows": rows,
            "in_window": in_window, "setup_s": setup_s,
            "keep_trace": keep_trace}


def conclude(cell, dep, rec: dict, seed: int, **checker):
    """The comparison with the reference, then the result line's fields;
    ``checker`` switches on the control or a fault (``check.Checker``).
    Returns (result, compared numbers, checker info)."""
    import jax

    dev = jax.devices()
    w, probes, trace = rec["window"], rec["probes"], rec["trace"]
    t = time.perf_counter()
    chk = check.Checker(dep, **checker)
    for q in check.sample(w.answered(), seed):
        chk.answer(q)
    for q in probes:
        chk.answer(q)
    if rec["edges"] is not None:
        chk.edges(*rec["edges"], seed)
    recall = chk.recall(probes, dep.k)
    chk.r["lost"] = w.lost()
    if chk.mismatched:
        chk.info["mismatched"] = chk.mismatched
    log(f"reference {time.perf_counter() - t:.3f} s: {chk.info}")

    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev), "memory_peak_bytes": rec["peak"]}
    result = {"correct": check.judge(chk.r, cell.config["limits"]),
              "attempted": w.attempted, "failed": w.failed + w.lost()}
    if trace:
        from bench import peaks, xplane
        mods = {m["name"]: spec.layer_module(m["name"], cell.root)
                for m in cell.per_layer}
        kernels = dict(getattr(mod, "KERNEL") for mod in mods.values()
                       if hasattr(mod, "KERNEL"))
        red = xplane.reduce(xplane.find_trace(str(TRACE_DIR)), kernels)
        if not rec["keep_trace"]:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
        least = (lambda calls: peaks.fused_query_least_s(
            rec["copies"], *rec["pq"], peaks.peaks(dev[0].device_kind),
            calls, rec["rows"]))
        run = LayerRun(rec["spans"], red, rec["in_window"], least,
                       w.query_latencies())
        units = {m["name"]: m["unit"] for m in cell.per_layer}
        metrics = {}
        for name, mod in mods.items():
            value = mod.read(run)
            if value is not None:
                metrics[name] = {"value": value, "unit": units[name]}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result.update(metrics=metrics, device=device,
                      breakdown={"device_ops": red["device_ops"],
                                 "idle_gaps": red["idle_gaps"]})
        log(f"trace: {red['kernels']}")
    else:
        lat = w.query_latencies()
        values = {
            "query_p50_ms": harness.percentile(lat, 50),
            "query_p95_ms": harness.percentile(lat, 95),
            "visible_p95_ms": harness.percentile(w.visible_latencies(), 95),
            "recall_at_10": recall,
            "setup_s": rec["setup_s"],
        }
        result.update(metrics={
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if values.get(m["name"]) is not None},
            device=device)
    log(f"recall_at_10 {recall}")
    compared = {name: {"value": chk.r[name], "limit": limit}
                for name, limit in cell.config["limits"].items()}
    result["compared"] = compared
    return result, compared, chk.info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", action="store_true",
                    help=f"leave the profiler trace under {TRACE_DIR}")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()
    if dev[0].platform != "tpu":
        print(f"[bench] no TPU: JAX's platform is {dev[0].platform!r}; the "
              "benchmark runs only on a TPU", file=sys.stderr)
        return 2
    cell = spec.load_cell(args.workload)
    if len(dev) < cell.chips:
        print(f"[bench] {args.workload} needs {cell.chips} chips, "
              f"{len(dev)} visible", file=sys.stderr)
        return 2
    log(f"{args.workload} seed {args.seed} on {len(dev)} x "
        f"{dev[0].device_kind}; compile cache {configure_jax()}")
    result, compared, info = run_cell(cell, args.seed, args.seconds,
                                bool(args.trace), args.keep_trace)
    for pair in info.get("mismatched", ()):
        print(f"[bench] mismatched pair {json.dumps(pair)}", file=sys.stderr)
    for name, c in compared.items():
        print(f"{name} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
