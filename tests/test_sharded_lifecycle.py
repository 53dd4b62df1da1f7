"""Sharded slab lifecycle (ISSUE 5 acceptance suite).

``ShardedGusIndex`` must *maintain* capacity rather than recycle it:

* SOAR secondary copies in the sharded mutate path — two copies per point
  in distinct partitions of the owner shard, deduped at query time, with
  recall at matched k at least the single-copy baseline's;
* compaction — squeezing tombstoned slots out of the slabs is invisible
  to readers: search results are **bit-identical** before/after;
* wrap-under-churn — a stream whose appends wrap every slab >= 2x keeps
  every live row when auto-compaction is on (zero silent age-outs),
  where the plain ring buffer demonstrably loses rows;
* skew re-split — adversarially skewed owner hashing is repaired by
  ``resplit()`` (salt bump + re-insert through the route/mutate
  machinery), equivalent to a fresh build at the final salt.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.ann.brute import BruteIndex
from repro.ann.sharded_index import (ShardedConfig, ShardedGusIndex,
                                     blocks_under_cursor)
from repro.core import BucketConfig
from repro.core.embedding import EmbeddingGenerator
from repro.core.maintenance import MaintenanceConfig
from repro.data.synthetic import OGB_ARXIV_LIKE, make_dataset
from repro.kernels import ops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BASE = dict(n_shards=1, d_proj=32, n_partitions=8, nprobe_local=0,
            reorder=8192, pq_m=4, kmeans_iters=4, pq_iters=2)


@pytest.fixture(scope="module")
def corpus():
    data = dataclasses.replace(OGB_ARXIV_LIKE, n_points=600, n_clusters=10)
    ids, feats, _ = make_dataset(data)
    gen = EmbeddingGenerator.create(
        data.spec, BucketConfig(dense_tables=8, dense_bits=10,
                                scalar_widths=(2.0,)))
    return ids, gen(feats), gen


# ------------------------------------------------------------ SOAR copies


def test_soar_writes_two_copies(corpus):
    """Every point lands in its primary and a distinct SOAR secondary
    partition of the owner shard, both holding the point's id; the
    single-copy config keeps exactly one row per point."""
    ids, emb, gen = corpus
    idx = ShardedGusIndex(gen.k_max, ShardedConfig(**BASE))
    idx.build(ids, emb)
    valid = np.asarray(idx.state["valid"])
    row_ids = np.asarray(idx.state["row_ids"]).reshape(-1)
    assert int(valid.sum()) == 2 * len(idx)
    for pid in ids[:100].tolist():
        r1, r2 = idx.row_of[pid]
        assert r1 // idx.slab != r2 // idx.slab      # distinct partitions
        assert row_ids[r1] == pid and row_ids[r2] == pid
    one = ShardedGusIndex(gen.k_max, ShardedConfig(
        **BASE, maintenance=MaintenanceConfig(soar=-1.0)))
    one.build(ids, emb)
    assert int(np.asarray(one.state["valid"]).sum()) == len(one)
    assert all(len(v) == 1 for v in one.row_of.values())


def test_search_dedups_soar_copies(corpus):
    """Exhaustive probing visits both copies of every point; result rows
    must contain each id at most once and still match the brute oracle's
    exact-rescored distances."""
    ids, emb, gen = corpus
    idx = ShardedGusIndex(gen.k_max, ShardedConfig(**BASE))
    idx.build(ids, emb)
    brute = BruteIndex(gen.k_max)
    brute.upsert(ids, emb)
    _, b_d = brute.search(emb[:24], 6)
    s_ids, s_d = idx.search(emb[:24], 6)
    np.testing.assert_allclose(np.sort(b_d, -1), np.sort(s_d, -1),
                               atol=1e-4)
    for row in s_ids:
        live = row[row >= 0]
        assert len(set(live.tolist())) == len(live)


def test_soar_recall_at_least_single_copy(corpus):
    """Seeded mutation stream under limited probing: two-copy SOAR recall
    at matched k must be >= the single-copy sharded baseline (identical
    trained structures — same corpus, same seed)."""
    ids, emb, gen = corpus
    got = {}
    for name, lam in (("soar", 1.0), ("single", -1.0)):
        cfg = ShardedConfig(n_shards=1, d_proj=32, n_partitions=16,
                            nprobe_local=2, reorder=64, pq_m=4,
                            kmeans_iters=6, pq_iters=3,
                            maintenance=MaintenanceConfig(soar=lam))
        idx = ShardedGusIndex(gen.k_max, cfg)
        idx.build(ids[:300], emb[:300])
        for lo in range(300, 600, 64):               # the live stream
            idx.upsert(ids[lo:lo + 64], emb[lo:lo + 64])
        got[name], _ = idx.search(emb[:64], 10)
    brute = BruteIndex(gen.k_max)
    brute.upsert(ids, emb)
    b_ids, _ = brute.search(emb[:64], 10)

    def recall(s_ids):
        hit = tot = 0
        for r in range(b_ids.shape[0]):
            truth = set(b_ids[r][b_ids[r] >= 0].tolist())
            hit += len(truth & set(s_ids[r][s_ids[r] >= 0].tolist()))
            tot += len(truth)
        return hit / tot

    r_soar, r_single = recall(got["soar"]), recall(got["single"])
    assert r_soar >= r_single, (r_soar, r_single)
    assert r_soar > 0.5, r_soar


# ------------------------------------------------------------- compaction


def test_compaction_bit_identical(corpus):
    """compact() squeezes tombstones out (cursor drops, slots reclaimed),
    keeps the host id -> rows map exact against the device truth, and is
    bitwise invisible to search."""
    ids, emb, gen = corpus
    idx = ShardedGusIndex(gen.k_max, ShardedConfig(**BASE))
    idx.build(ids, emb)
    idx.delete(ids[100:300])
    idx.upsert(ids[100:150], emb[100:150])
    i1, d1 = idx.search(emb[:32], 8)
    cursor_before = int(idx._cursor.sum())
    rep = idx.compact()
    assert rep["reclaimed"] > 0
    assert int(idx._cursor.sum()) < cursor_before
    row_ids = np.asarray(idx.state["row_ids"]).reshape(-1)
    valid = np.asarray(idx.state["valid"]).reshape(-1)
    assert int(valid.sum()) == 2 * len(idx)
    for pid, rowvec in list(idx.row_of.items())[:200]:
        for row in rowvec:
            assert valid[row] and row_ids[row] == pid
    i2, d2 = idx.search(emb[:32], 8)
    np.testing.assert_array_equal(i1, i2)
    np.testing.assert_array_equal(d1, d2)


# ------------------------------------------------------- wrap under churn


def _churn(gen, ids, emb, rounds, *, auto, delete_per=16, insert_per=32):
    """Delete/insert churn sized to wrap the (deliberately small) slabs.
    Returns (index, live id set, emb row per live id, appended copies)."""
    cfg = ShardedConfig(n_shards=1, d_proj=32, n_partitions=4, slab=64,
                        nprobe_local=0, reorder=4096,
                        pq_m=4, kmeans_iters=4, pq_iters=2,
                        maintenance=MaintenanceConfig(headroom=2.0,
                                                      compact=auto))
    idx = ShardedGusIndex(gen.k_max, cfg)
    n0 = 96
    idx.build(ids[:n0], emb[:n0])
    emb_of = {int(p): i for i, p in enumerate(ids[:n0].tolist())}
    live = list(ids[:n0].tolist())
    appends = 2 * n0
    rng = np.random.default_rng(7)
    next_id = 100_000
    for _ in range(rounds):
        sel = sorted(rng.choice(len(live), delete_per, replace=False),
                     reverse=True)
        kill = [live.pop(int(j)) for j in sel]
        idx.delete(kill)
        for pid in kill:
            emb_of.pop(pid)
        new_ids = np.arange(next_id, next_id + insert_per, dtype=np.int64)
        next_id += insert_per
        srcs = rng.integers(0, len(ids), insert_per)
        idx.upsert(new_ids, emb[srcs])
        appends += 2 * insert_per
        live += new_ids.tolist()
        emb_of.update({int(p): int(s) for p, s in zip(new_ids, srcs)})
    return idx, set(live), emb_of, appends


def test_wrap_churn_retains_live_rows(corpus):
    """A churn stream whose appended copies exceed 2x the built slab
    capacity: auto-compaction (plus slab growth under genuine occupancy
    pressure) keeps every live row — zero silent age-outs — and search
    still matches a brute oracle over the surviving corpus."""
    ids, emb, gen = corpus
    idx, live, emb_of, appends = _churn(gen, ids, emb, rounds=14, auto=True)
    assert appends >= 2 * 4 * 128          # wrapped the built 4x128 slabs
    occ = idx.occupancy()
    assert occ["aged_out"] == 0
    assert occ["compactions"] >= 1
    assert set(idx.row_of) == live
    assert int(np.asarray(idx.state["valid"]).sum()) == 2 * len(live)
    # the retained rows actually serve: brute oracle over the live corpus
    order = sorted(live)
    rows = np.asarray([emb_of[p] for p in order])
    brute = BruteIndex(gen.k_max)
    brute.upsert(np.asarray(order, np.int64), emb[rows])
    _, b_d = brute.search(emb[:16], 6)
    _, s_d = idx.search(emb[:16], 6)
    np.testing.assert_allclose(np.sort(b_d, -1), np.sort(s_d, -1),
                               atol=1e-4)


def test_wrap_churn_without_auto_compact_ages_out(corpus):
    """The contrast run: same stream, auto_compact off — the ring buffer
    wraps onto live rows and silently drops them (the behavior this PR
    retires as the default)."""
    ids, emb, gen = corpus
    idx, live, _, _ = _churn(gen, ids, emb, rounds=14, auto=False)
    occ = idx.occupancy()
    assert occ["aged_out"] > 0
    assert len(idx.row_of) < len(live)


# -------------------------------------------------- kernel block occupancy


def test_live_block_share_follows_cursors(corpus):
    """``live_block_share`` (and its gauge) is the share of the shortlist
    kernel's block flags over the slabs with every partition probed: it
    follows an upsert and a compaction, and between a delete and the
    compaction it bounds the flags from above. Two partitions, so each
    point puts one SOAR copy in each: 600 copies per slab, 1,100 after
    500 inserts, 500 after 600 deletes and a compaction."""
    ids, emb, gen = corpus
    idx = ShardedGusIndex(gen.k_max, ShardedConfig(
        **{**BASE, "n_partitions": 2, "reorder": 64}))
    idx.build(ids, emb)
    gauge = idx.obs.registry.get("index_shortlist_live_block_share")

    def flag_share():
        valid = np.asarray(idx.state["valid"]).reshape(1, -1)
        return float(np.mean(np.asarray(ops.shortlist_block_live(
            valid, min(64, valid.shape[1])))))

    def share():
        got = idx.occupancy()["live_block_share"]
        assert gauge.value == got
        return got

    assert idx.slab == 8192                  # 16 blocks of 1,024 slots
    assert share() == flag_share() == 2 / 16
    idx.upsert(np.arange(10_000, 10_500), emb[:500])
    assert share() == flag_share() == 4 / 16
    idx.delete(ids)
    assert share() == 4 / 16 >= flag_share()
    idx.compact()
    assert share() == flag_share() == 2 / 16


@pytest.mark.parametrize("cursor,slab,shards,block_n,want", [
    ([0, 0, 0, 0], 256, 1, 1024, 0.0),
    ([0, 5, 0, 300], 512, 1, 1024, 1.0),        # partitions share blocks
    ([1536, 0], 1536, 1, 1024, 2 / 3),          # a slab straddles a block
    ([1536, 1], 1536, 1, 1024, 2 / 3),
    ([5, 0, 0, 0], 1024, 2, 1024, 0.25),         # shards lay out apart
    ([600, 1100], 8192, 1, 1024, 3 / 16),
])
def test_blocks_under_cursor_layouts(cursor, slab, shards, block_n, want):
    got = blocks_under_cursor(np.asarray(cursor), slab, shards, block_n)
    assert got == pytest.approx(want)


# --------------------------------------------------------- skew re-split


def test_resplit_noop_without_skew(corpus):
    """Single-shard meshes (and balanced fleets) never re-split."""
    ids, emb, gen = corpus
    idx = ShardedGusIndex(gen.k_max, ShardedConfig(**BASE))
    idx.build(ids[:100], emb[:100])
    assert idx.resplit(1.1) == 0
    assert idx.salt == 3 and idx.resplits == 0


@pytest.mark.slow
def test_resplit_rebalances_hot_shard():
    """Adversarial ids that all hash to shard 0 of a 4-shard mesh: the
    re-split bumps the owner-hash salt and re-inserts the hot shard's
    rows through the ordinary route/mutate machinery. Occupancy must end
    exactly where a fresh build at the final salt puts it, and search
    must keep returning the fresh-build oracle's distances."""
    code = textwrap.dedent("""
        import dataclasses, json
        import numpy as np
        import jax.numpy as jnp
        from repro.ann.sharded_index import ShardedConfig, ShardedGusIndex
        from repro.core.maintenance import MaintenanceConfig
        from repro.core import BucketConfig, hashing
        from repro.core.embedding import EmbeddingGenerator
        from repro.data.synthetic import OGB_ARXIV_LIKE, make_dataset

        data = dataclasses.replace(OGB_ARXIV_LIKE, n_points=600,
                                   n_clusters=10)
        _, feats, _ = make_dataset(data)
        gen = EmbeddingGenerator.create(
            data.spec, BucketConfig(dense_tables=8, dense_bits=10,
                                    scalar_widths=(2.0,)))
        emb = gen(feats)
        # adversarial ids: every one hashes to shard 0 under salt 3
        cand = np.arange(1, 40_000, dtype=np.int64)
        h = np.asarray(hashing.uhash(3, jnp.asarray(cand, jnp.uint32)))
        ids = cand[(h % np.uint32(4)) == 0][:600]
        assert len(ids) == 600

        # the armed policy also exercises the reentrancy guard: the
        # re-split's internal re-insert upserts call auto_resplit() again
        # and must no-op (salt bumps exactly once)
        cfg = ShardedConfig(n_shards=4, d_proj=32, n_partitions=8,
                            nprobe_local=0, reorder=4096, pq_m=4,
                            kmeans_iters=4, pq_iters=2,
                            maintenance=MaintenanceConfig(resplit=1.5))
        idx = ShardedGusIndex(gen.k_max, cfg)
        idx.build(ids, emb)
        before = idx.occupancy()
        moved = idx.resplit(1.5)
        after = idx.occupancy()
        assert idx.resplits == 1

        fresh = ShardedGusIndex(gen.k_max, cfg)
        fresh.salt = idx.salt                     # the post-resplit policy
        fresh.build(ids, emb)
        _, d_split = idx.search(emb[:24], 6)
        _, d_fresh = fresh.search(emb[:24], 6)
        print(json.dumps({
            "before_imbalance": before["shard_imbalance"],
            "after_imbalance": after["shard_imbalance"],
            "moved": moved,
            "aged_out": after["aged_out"],
            "salt": idx.salt,
            "shard_live_split": after["shard_live"],
            "shard_live_fresh": fresh.occupancy()["shard_live"],
            "search_equal": bool(np.allclose(
                np.sort(d_split, -1), np.sort(d_fresh, -1), atol=1e-4)),
        }))
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["moved"] > 0
    assert res["salt"] == 4
    assert res["aged_out"] == 0
    assert res["before_imbalance"] > 3.9          # everything on shard 0
    assert res["after_imbalance"] < 2.0           # spread across the mesh
    # identical placement policy => identical occupancy as a fresh build
    assert res["shard_live_split"] == res["shard_live_fresh"]
    assert res["search_equal"]


# ------------------------------------------------- query-load re-split

def test_query_load_counters_accumulate(corpus):
    """search() charges each returned candidate to the partition it was
    served from; build() resets the counters (fresh observation window)."""
    ids, emb, gen = corpus
    idx = ShardedGusIndex(gen.k_max, ShardedConfig(**BASE))
    idx.build(ids, emb)
    assert int(idx.query_load.sum()) == 0          # build queries nothing
    idx.search(emb[:16], 6)
    charged = int(idx.query_load.sum())
    assert charged > 0                             # hits were accounted
    occ = idx.occupancy()
    assert len(occ["shard_load"]) == 1
    assert occ["shard_load"][0] == charged
    assert occ["load_imbalance"] == 1.0            # one shard: no skew
    idx.build(ids[:100], emb[:100])                # rebuild resets
    assert int(idx.query_load.sum()) == 0


def test_resplit_rejects_unknown_metric(corpus):
    ids, emb, gen = corpus
    idx = ShardedGusIndex(gen.k_max, ShardedConfig(**BASE))
    idx.build(ids[:100], emb[:100])
    with pytest.raises(ValueError, match="resplit by"):
        idx.resplit(1.5, by="qps")
    with pytest.raises(ValueError, match="resplit_metric"):
        MaintenanceConfig(resplit_metric="qps")
    # the one-release shim folds the legacy spelling into the same check
    with pytest.raises(ValueError, match="resplit_metric"):
        with pytest.warns(DeprecationWarning):
            ShardedConfig(**BASE, resplit_by="qps")  # legacy-ok


@pytest.mark.slow
def test_resplit_by_query_load_moves_hot_read_shard():
    """Regression for the load-blind trigger: a 2-shard mesh whose
    *occupancy* is balanced but whose read traffic all lands on shard 0.
    The occupancy trigger must see nothing; the query-load trigger must
    move the hot shard's rows, reset the counters, and keep every answer
    identical (re-split is placement-only)."""
    code = textwrap.dedent("""
        import dataclasses, json
        import numpy as np
        import jax.numpy as jnp
        from repro.ann.sharded_index import ShardedConfig, ShardedGusIndex
        from repro.core.maintenance import MaintenanceConfig
        from repro.core import BucketConfig, hashing
        from repro.core.embedding import EmbeddingGenerator
        from repro.data.synthetic import OGB_ARXIV_LIKE, make_dataset

        data = dataclasses.replace(OGB_ARXIV_LIKE, n_points=400,
                                   n_clusters=2)
        _, feats, cluster = make_dataset(data)
        gen = EmbeddingGenerator.create(
            data.spec, BucketConfig(dense_tables=8, dense_bits=10,
                                    scalar_widths=(2.0,)))
        emb = gen(feats)
        # occupancy-balanced, read-skewed placement: cluster-0 points get
        # ids hashing to shard 0 under salt 3, cluster-1 points ids
        # hashing to shard 1 -- equal counts per shard, but queries drawn
        # from cluster 0 only ever hit shard 0's rows
        cand = np.arange(1, 200_000, dtype=np.int64)
        h = np.asarray(hashing.uhash(3, jnp.asarray(cand, jnp.uint32)))
        to0 = cand[(h % np.uint32(2)) == 0]
        to1 = cand[(h % np.uint32(2)) == 1]
        m = min(len(np.flatnonzero(cluster == 0)),
                len(np.flatnonzero(cluster == 1)), 150)
        assert m >= 60, m
        rows0 = np.flatnonzero(cluster == 0)[:m]
        rows1 = np.flatnonzero(cluster == 1)[:m]
        ids = np.concatenate([to0[:m], to1[:m]])
        order = np.concatenate([rows0, rows1])

        cfg = ShardedConfig(n_shards=2, d_proj=32, n_partitions=8,
                            nprobe_local=0, reorder=4096, pq_m=4,
                            kmeans_iters=4, pq_iters=2)
        idx = ShardedGusIndex(gen.k_max, cfg)
        idx.build(ids, emb[order])
        occ0 = idx.occupancy()

        q = emb[rows0[:min(32, m)]]               # cluster-0 reads only
        _, d_before = idx.search(q, 6)
        occ1 = idx.occupancy()
        by_occupancy = idx.resplit(1.5, by="occupancy")
        by_load = idx.resplit(1.5, by="load")
        _, d_after = idx.search(q, 6)
        occ2 = idx.occupancy()
        print(json.dumps({
            "shard_live": occ0["shard_live"],
            "occ_imbalance": occ0["shard_imbalance"],
            "load_imbalance": occ1["load_imbalance"],
            "by_occupancy": by_occupancy,
            "by_load": by_load,
            "salt": idx.salt,
            "aged_out": occ2["aged_out"],
            "load_after_reset": occ2["shard_load"],
            "search_equal": bool(np.allclose(
                np.sort(d_before, -1), np.sort(d_after, -1), atol=1e-4)),
        }))
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    # the trap this test pins: occupancy is balanced, so the old trigger
    # sees nothing to do...
    assert res["occ_imbalance"] < 1.5
    assert res["by_occupancy"] == 0
    # ...while the read traffic is almost entirely on shard 0
    assert res["load_imbalance"] > 1.5
    assert res["by_load"] > 0                     # load trigger moved it
    assert res["salt"] == 4
    assert res["aged_out"] == 0
    assert res["search_equal"]                    # placement-only change
    # counters reset after a load-driven move: the search after the split
    # is the only charge left
    assert sum(res["load_after_reset"]) > 0
