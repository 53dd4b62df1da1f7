#!/usr/bin/env python3
"""Find a cell's knee: the highest offered rate its mix sustains.

    python bench/sweep.py --workload products.ycsb-b --seed 3 \\
        --seconds 20 --rates 40,60,80,100

One set-up, then one open-loop window per rate, in order, each with a
fresh arrival set. A rate is sustained when nothing is shed or lost and
the queue does not grow over the window: the median latency of the last
quarter of arrivals stays within twice that of the first quarter. The
sweep stops at the first rate not sustained. Prints one JSON line per
rate and writes them to ``--out``. The cell's rate
(``bench/cells/<workload>.json``) is set to 0.8 of the knee by hand.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import harness, run, spec  # noqa: E402


def quarters(w, key: str, arrivals_t0: float, seconds: float):
    recs = [r for r in (w.queries if key == "latency_ms"
                        else w.mutations).values() if key in r]
    first = [r[key] for r in recs if r["due"] - arrivals_t0 < seconds / 4]
    last = [r[key] for r in recs if r["due"] - arrivals_t0 >= 3 * seconds / 4]
    return harness.percentile(first, 50), harness.percentile(last, 50)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True,
                    help="offered rows per second, comma-separated")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("[sweep] no TPU", file=sys.stderr)
        return 2
    run.configure_jax()
    cell = spec.load_cell(args.workload)
    t = time.perf_counter()
    dep, compiles = run.setup(cell, args.seed)
    print(f"[sweep] set-up {time.perf_counter() - t:.1f} s", flush=True)
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        arrivals = harness.schedule(cell.mix, rate, args.seconds, args.seed,
                                    phase=2 + i)
        w = harness.Window(dep, harness.Annotations(False))
        before = compiles.mark()
        t0 = w.run(arrivals, int(cell.mix["query_rows"]))
        w.flush()
        q1, q4 = quarters(w, "latency_ms", t0, args.seconds)
        lat = w.query_latencies()
        vis = w.visible_latencies()
        row = {
            "ops_per_s": rate, "elapsed_s": time.perf_counter() - t0,
            "queries": len(w.queries), "mutations": len(w.mutations),
            "failed": w.failed, "lost": w.lost(),
            "query_p50_ms": harness.percentile(lat, 50),
            "query_p95_ms": harness.percentile(lat, 95),
            "visible_p95_ms": harness.percentile(vis, 95),
            "first_quarter_p50_ms": q1, "last_quarter_p50_ms": q4,
            "lateness_p95_ms": harness.percentile(w.lateness_ms, 95),
            "steps": w.step_summary(),
            "lowered_in_window": compiles.mark()[0] - before[0],
        }
        row["sustained"] = bool(row["failed"] == 0 and row["lost"] == 0
                                and q1 is not None and q4 is not None
                                and q4 <= 2 * q1)
        rows.append(row)
        print(json.dumps(row), flush=True)
        if not row["sustained"]:
            break
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
