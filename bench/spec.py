"""Finds a cell's files by the names in ``BENCHMARK.json``.

A configuration is ``bench/configs/<config>.json``, a traffic mix
``bench/traffic/<traffic>.json``, a cell's offered rate
``bench/cells/<workload>.json`` (two configurations share a mix but not a
knee), and a per-layer metric ``bench/layer_metrics/<metric>.py`` with a
``read(run)`` function. Adding any of them is adding one file.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    rate: dict
    chips: int
    end_to_end: list          # metric entries this cell reports
    per_layer: list
    root: Path = ROOT


def _listed(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(by_name)}")
    w = by_name[name]
    e2e = [m for m in bench["end_to_end"] if _listed(m, name)]
    e2e_names = {m["name"] for m in e2e}
    # a per-layer metric is reported where its end-to-end metric is
    layer = [m for m in bench["per_layer"]
             if _listed(m, name) and m["moves"] in e2e_names]
    return Cell(
        name=name,
        config=json.loads((root / "bench" / "configs"
                           / f"{w['config']}.json").read_text()),
        mix=json.loads((root / "bench" / "traffic"
                        / f"{w['traffic']}.json").read_text()),
        rate=json.loads((root / "bench" / "cells"
                         / f"{name}.json").read_text()),
        chips=int(w["chips"]), end_to_end=e2e, per_layer=layer, root=root)


def layer_module(metric: str, root: Path = ROOT):
    """A per-layer metric's own file: its ``read(run)`` function, and a
    ``KERNEL = (name, trace marker)`` where it reads a kernel."""
    path = root / "bench" / "layer_metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_layer_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
