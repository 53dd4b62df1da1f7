"""Shared fixtures of the benchmark's own tests: a benchmark root in a
temporary directory whose configurations are cut to a few hundred points,
so that a whole run fits on the CPU."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
# the maintained-graph deployment, which has no cell on the chip (its
# write path compiles programs in every window), so that the tests keep
# the graph's checks honest for the PR that gives it one
GRAPH_CONFIG = {"name": "arxiv-graph", "source": "https://ogb.stanford.edu/",
                "file": "bench/configs/arxiv-graph.json",
                "reduced": ["n_points"], "why": "maintained top-10 graph"}
GRAPH_CELL = {"name": "arxiv-graph.ycsb-a", "config": "arxiv-graph",
              "traffic": "ycsb-a", "chips": 1, "why": "graph upkeep"}


def make_root(dest: Path, n_points: int, ops_per_s: float) -> Path:
    """A copy of the benchmark's files with every corpus cut to
    ``n_points`` and every cell offered ``ops_per_s``."""
    (dest / "bench").mkdir(parents=True, exist_ok=True)
    for sub in ("layer_metrics", "traffic", "configs"):
        shutil.copytree(REPO / "bench" / sub, dest / "bench" / sub,
                        dirs_exist_ok=True)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    if "arxiv-graph" not in {c["name"] for c in bench["configs"]}:
        bench["configs"].append(GRAPH_CONFIG)
        bench["workloads"].append(GRAPH_CELL)
    for cfg in bench["configs"]:
        path = dest / cfg["file"]
        data = json.loads(path.read_text())
        data["n_points"] = n_points
        path.write_text(json.dumps(data))
    (dest / "bench" / "cells").mkdir(exist_ok=True)
    for w in bench["workloads"]:
        (dest / "bench" / "cells" / f"{w['name']}.json").write_text(
            json.dumps({"ops_per_s": ops_per_s}))
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return dest


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench_root"), 400, 20.0)
