"""The comparison that decides ``correct`` catches a broken timed path.

One tiny arxiv-graph deployment (answers and the maintained graph) on the
CPU; each test drives a window through it with the path broken
underneath, and the checker must read ``correct`` false. Tests run in
file order: the dropped-deletes fault leaves the engine's corpus behind
the reference's for good, so it runs last."""
import numpy as np
import pytest

from bench import run, spec

SEED = 2**33 + 17
SECONDS = 1.5


@pytest.fixture(scope="module")
def deployment(tiny_root):
    cell = spec.load_cell("arxiv-graph.ycsb-a", tiny_root)
    cell.mix["warm_seconds"] = 1
    dep, compiles = run.setup(cell, SEED)
    return cell, dep, compiles


def measure(deployment, **kw):
    cell, dep, compiles = deployment
    return run.measure(cell, dep, compiles, SEED, SECONDS, False, **kw)


@pytest.mark.timeout(600)
def test_a_sound_run_is_correct_and_the_control_is_not(deployment):
    result, compared, info = measure(deployment)
    assert result["correct"], compared
    assert info["pairs"] > 0 and info["edges"] > 0 and info["rows"] > 0
    result, compared, _ = measure(deployment, control="bfloat16")
    assert not result["correct"], compared
    assert compared["weight_gap"]["value"] > compared["weight_gap"]["limit"]


@pytest.mark.timeout(600)
def test_inserted_points_never_linked(tiny_root, monkeypatch):
    """From the end of the graph's seeding on, the stream's inserts get a
    graph row but no edges: weights and live ends stay right, only the
    rows' membership can tell."""
    from bench import harness
    from repro.graph import store as store_mod
    real = store_mod.DynamicGraphStore.upsert
    cell = spec.load_cell("arxiv-graph.ycsb-a", tiny_root)
    dep = harness.build(cell, SEED)

    def unlinked(self, ids, result, purge=True):
        ids = np.asarray(ids).reshape(-1)
        known = np.asarray([int(p) in self.slot_of for p in ids], bool)
        if purge and not known.all():
            self.ensure_ids(ids[~known])
            ids, result = ids[known], type(result)(
                ids=np.asarray(result.ids)[known],
                weights=np.asarray(result.weights)[known],
                distances=np.asarray(result.distances)[known])
        return real(self, ids, result, purge)

    monkeypatch.setattr(store_mod.DynamicGraphStore, "upsert", unlinked)
    result, compared, _ = run.measure(cell, dep, run.Compiles(), SEED,
                                      SECONDS, False)
    assert not result["correct"], compared
    assert compared["graph_miss"]["value"] > compared["graph_miss"]["limit"]


@pytest.mark.timeout(600)
def test_the_checker_swaps_rows_for_the_upper_reading(deployment):
    result, compared, _ = measure(deployment, swap_rows=True)
    assert not result["correct"], compared
    assert compared["graph_miss"]["value"] > compared["graph_miss"]["limit"]


@pytest.mark.timeout(600)
@pytest.mark.parametrize("field", ["weights", "ids"])
def test_an_answer_altered_where_it_is_produced(deployment, monkeypatch,
                                                field):
    from repro.core import gus as gus_mod
    real = gus_mod.DynamicGUS._neighbors_impl

    def altered(self, *a, **kw):
        res = real(self, *a, **kw)
        if field == "weights":
            res.weights = res.weights + np.float32(0.01)
        else:
            res.ids = np.roll(res.ids, 1, axis=1)
        return res

    monkeypatch.setattr(gus_mod.DynamicGUS, "_neighbors_impl", altered)
    result, compared, info = measure(deployment)
    assert not result["correct"], compared
    if field == "ids":
        # the first mismatched pairs are kept for the next reader
        assert info["mismatched"] and {"id", "served_shared", "shared_min",
                                       "point_margin"} <= set(
            info["mismatched"][0])


@pytest.mark.timeout(600)
@pytest.mark.parametrize("left_out", ["every row", "half the rows"])
def test_rows_left_out_on_the_write_path(tiny_root, monkeypatch, left_out):
    """A mutation step that leaves the state unchanged, and one that
    applies half of each batch, on a deployment of their own."""
    from bench import harness
    from repro.core.types import MutationBatch
    from repro.serve import engine as engine_mod
    real = engine_mod.GusEngine.submit_mutations
    cell = spec.load_cell("arxiv-graph.ycsb-a", tiny_root)
    cell.mix["warm_seconds"] = 1
    dep, compiles = run.setup(cell, SEED)

    def partial(self, batch):
        n = np.asarray(batch.ids).size
        keep = np.arange(n) < (0 if left_out == "every row" else n // 2)
        real(self, MutationBatch(
            kinds=np.asarray(batch.kinds)[keep],
            ids=np.asarray(batch.ids)[keep],
            features={k: np.asarray(v)[keep]
                      for k, v in batch.features.items()}))

    monkeypatch.setattr(engine_mod.GusEngine, "submit_mutations", partial)
    result, compared, _ = run.measure(cell, dep, compiles, SEED, SECONDS,
                                      False)
    assert not result["correct"], compared


@pytest.mark.timeout(600)
def test_deletes_dropped_on_the_write_path(deployment, monkeypatch):
    from repro.core.types import MUTATION_DELETE, MutationBatch
    from repro.serve import engine as engine_mod
    real = engine_mod.GusEngine.submit_mutations

    def drop_deletes(self, batch):
        keep = np.asarray(batch.kinds) != MUTATION_DELETE
        real(self, MutationBatch(
            kinds=np.asarray(batch.kinds)[keep],
            ids=np.asarray(batch.ids)[keep],
            features={k: np.asarray(v)[keep]
                      for k, v in batch.features.items()}))

    monkeypatch.setattr(engine_mod.GusEngine, "submit_mutations",
                        drop_deletes)
    result, compared, _ = measure(deployment)
    assert not result["correct"], compared
    assert compared["dead_ids"]["value"] > 0
