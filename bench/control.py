#!/usr/bin/env python3
"""Readings of the program and of its control, for setting the limits.

    python bench/control.py --workload products.ycsb-b \\
        --seeds 101,102,103 --seconds 10

For each seed: set-up, a window at the cell's own load, the probes, and
the comparison, as a run makes them. The same window is then judged
again: with the control in the program's place (the reference scorer
with bfloat16 matmul operands, on the same served pairs), with the
scorer at the ``high`` pass in its place (a reading), and, where the
cell keeps a graph, with every graph row handed to another point. One
JSON line per seed, with each judgement's numbers and ``correct``. The
benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import run, spec  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("[control] no TPU", file=sys.stderr)
        return 2
    run.configure_jax()
    cell = spec.load_cell(args.workload)
    judged = {"program": {}, "control": {"control": "bfloat16"},
              "high_pass": {"control": "high"}}
    if cell.config["graph_k"]:
        judged["rows_swapped"] = {"swap_rows": True}
    for seed in (int(s) for s in args.seeds.split(",")):
        dep, compiles = run.setup(cell, seed)
        rec = run.observe(cell, dep, compiles, seed, args.seconds, False)
        dep.engine = dep.fe = None
        line = {"workload": cell.name, "seed": seed}
        for name, kw in judged.items():
            result, compared, info = run.conclude(cell, dep, rec, seed, **kw)
            line[name] = {"correct": result["correct"],
                          **{k: v["value"] for k, v in compared.items()}}
        line.update(info=info, metrics=result["metrics"])
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
