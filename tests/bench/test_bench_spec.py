"""Adding a configuration, a traffic mix or a per-layer metric is adding
one file that the harness finds by name."""
import json

import pytest

from bench import spec


def test_a_cell_finds_its_files_by_name(tmp_path):
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "cells").mkdir()
    (tmp_path / "bench" / "layer_metrics").mkdir()
    (tmp_path / "bench" / "configs" / "cfg-x.json").write_text(
        json.dumps({"n_points": 7}))
    (tmp_path / "bench" / "traffic" / "mix-y.json").write_text(
        json.dumps({"query_share": 0.25}))
    (tmp_path / "bench" / "cells" / "cfg-x.mix-y.json").write_text(
        json.dumps({"ops_per_s": 3.5}))
    (tmp_path / "bench" / "layer_metrics" / "layer.thing_ms.py").write_text(
        "KERNEL = ('thing', 'marker')\n\n"
        "def read(run):\n    return run * 2\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": "cfg-x.mix-y", "config": "cfg-x",
                       "traffic": "mix-y", "chips": 1, "why": "w"}],
        "end_to_end": [
            {"name": "lat_ms", "unit": "ms"},
            {"name": "other_ms", "unit": "ms", "workloads": ["nope"]}],
        "per_layer": [
            {"name": "layer.thing_ms", "unit": "ms", "moves": "lat_ms"},
            {"name": "layer.other_ms", "unit": "ms", "moves": "other_ms"}],
    }))
    cell = spec.load_cell("cfg-x.mix-y", tmp_path)
    assert cell.config == {"n_points": 7}
    assert cell.mix == {"query_share": 0.25}
    assert cell.rate == {"ops_per_s": 3.5}
    assert [m["name"] for m in cell.end_to_end] == ["lat_ms"]
    assert [m["name"] for m in cell.per_layer] == ["layer.thing_ms"]
    mod = spec.layer_module("layer.thing_ms", tmp_path)
    assert mod.read(21) == 42
    assert mod.KERNEL == ("thing", "marker")
    with pytest.raises(SystemExit):
        spec.load_cell("missing", tmp_path)


def test_every_cell_of_the_benchmark_loads():
    import json as _json
    bench = _json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.rate["ops_per_s"] > 0
        for m in cell.per_layer:
            assert callable(spec.layer_module(m["name"]).read)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
