#!/usr/bin/env python3
"""Chip smoke: run the served write and query path once on a TPU, and check it.

    python chip_smoke.py              # one chip: the arxiv-like deployment
    python chip_smoke.py --chips 4    # four chips: sharded index + pod replicas

One process, no children. Without a TPU it exits non-zero before doing
anything (there is no CPU fallback). Every check raises on failure. The
last line of standard output is one JSON object naming the device; the
lines before it report smoke timings, which are not benchmark results.

One chip: every Pallas kernel against its oracle; the arxiv-like
deployment, cut from ogbn-arxiv's 169,343 points to 40,000 (the reason is
printed), bootstrapped through ``repro.launch.serve.build_engine`` on the
sharded backend with the maintained graph and the pipelined write path;
mutation batches and neighbourhood queries through
``serve.frontend.Frontend``; recall of the served top-k against exact kNN
(``BruteIndex``); and a ``ShardedGusIndex`` whose shortlist covers a
small corpus against ``BruteIndex`` distances, before and after churn.

Four chips, at the serving widths: a 4-shard ``ShardedGusIndex`` under
both merge schedules against ``BruteIndex`` distances, and an engine
whose primary and replica are 2-shard indexes on disjoint pod meshes
(``make_pod_meshes(2, 2)``) with every query hedged: once with a
shortlist covering a small corpus (top-k must equal exact kNN), once in
the served configuration on a 20,000-point corpus (recall against exact
kNN).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

ARXIV_POINTS = 169_343      # ogbn-arxiv's node count
SMOKE_POINTS = 40_000       # the one-chip smoke's cut of it
SIZE_CUT = (
    "the served sharded config probes every partition, so bootstrap graph "
    "seeding costs (bootstrapped points) x (slab slots), which grows as the "
    "square of the corpus; at 169,343 points it is ~18x the work of this "
    "cut, over an hour on one v5e, past this smoke's 20 min (PERF.md)")
POD_POINTS = 20_000         # corpus of the four-chip served-config pods
K = 10                      # neighbourhood size served and checked
# tie-tolerant recall@10 the served path must reach: it probes every
# partition and exact-rescores a 128-row PQ shortlist per query
MIN_RECALL = 0.75


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)
    log(f"ok: {what}")


# ---------------------------------------------------------------- kernels


def kernel_phase(seed: int) -> None:
    """Each compiled kernel against the repo's oracle on a small input."""
    import jax.numpy as jnp
    from repro.core.types import PAD_INDEX
    from repro.kernels import ops, ref

    rng = np.random.default_rng(seed)
    b, m, c, n, k = 16, 16, 256, 1000, 40
    lut = jnp.asarray(rng.normal(size=(b, m, c)), jnp.float32)
    codes = jnp.asarray(rng.integers(0, c, (b, n, m)), jnp.uint8)
    ids = jnp.asarray(rng.integers(0, n // 3, (b, n)), jnp.int32)
    valid = jnp.asarray(rng.random((b, n)) > 0.2)
    bias = jnp.asarray(rng.normal(size=(b, n)), jnp.float32)
    for quantized in (False, True):
        got = ops.pq_score_dedup_topk(lut, codes, ids, k, valid=valid,
                                      bias=bias, quantized=quantized)
        want = ref.fused_query_ref(lut, codes, ids, k, valid=valid,
                                   bias=bias, quantized=quantized)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    np.testing.assert_array_equal(
        np.asarray(ops.pq_scores(lut, codes)),
        np.asarray(ref.pq_score_seq_ref(lut, codes)))
    check(True, "fused_query (f32, int8) and pq_score: bitwise = oracle")

    flat = jnp.asarray(rng.integers(0, c, (n, m)), jnp.uint8)
    np.testing.assert_allclose(ops.pq_score(lut, flat),
                               ref.pq_score_ref(lut, flat),
                               rtol=1e-5, atol=1e-5)
    scores = jnp.asarray(rng.normal(size=(b, n)), jnp.float32)
    for got, want in zip(ops.topk_select(scores, k),
                         ref.topk_ref(scores, k)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def sparse(rows, nnz):
        idx = rng.integers(0, 64, (rows, nnz)).astype(np.uint32)
        idx[rng.random((rows, nnz)) < 0.3] = PAD_INDEX
        return jnp.asarray(idx), jnp.asarray(rng.random((rows, nnz)),
                                             jnp.float32)

    qi, qv = sparse(b, 16)
    di, dv = sparse(n, 16)
    np.testing.assert_allclose(ops.sparse_dot(qi, qv, di, dv),
                               ref.sparse_dot_ref(qi, qv, di, dv),
                               rtol=1e-5, atol=1e-6)
    bi, bv = di[:b * 50].reshape(b, 50, 16), dv[:b * 50].reshape(b, 50, 16)
    want = np.stack([ref.sparse_dot_ref(qi[i:i + 1], qv[i:i + 1], bi[i],
                                        bv[i])[0] for i in range(b)])
    np.testing.assert_allclose(ops.sparse_dot_batched(qi, qv, bi, bv), want,
                               rtol=1e-5, atol=1e-6)
    params = {"w0": rng.normal(size=(16, 10)), "b0": rng.normal(size=(10,)),
              "w1": rng.normal(size=(10, 10)), "b1": rng.normal(size=(10,)),
              "w2": rng.normal(size=(10, 1)), "b2": rng.normal(size=(1,))}
    params = {key: jnp.asarray(v, jnp.float32) for key, v in params.items()}
    feats = jnp.asarray(rng.normal(size=(300, 16)), jnp.float32)
    np.testing.assert_allclose(
        ops.scorer_mlp(feats, params),
        ref.scorer_mlp_ref(feats, *(params[key] for key in
                                    ("w0", "b0", "w1", "b1", "w2", "b2"))),
        rtol=1e-5, atol=1e-6)
    check(True, "pq_score, topk_select, sparse_dot(_batched), scorer_mlp "
                "agree with their oracles")


# ------------------------------------------------------------ references


def exact_check(q_emb, served_ids, served_d, corpus_ids, corpus_emb,
                k_dims: int, what: str) -> float:
    """Tie-tolerant recall@k of a served top-k against exact kNN, after
    checking that every served distance is the true one. Returns recall."""
    from repro.ann.brute import BruteIndex
    from repro.core.types import PAD_INDEX

    brute = BruteIndex(k_dims, capacity=len(corpus_ids))
    brute.upsert(corpus_ids, corpus_emb)
    _, exact_d = brute.search(q_emb, served_ids.shape[1])
    # the served distances are exact: recompute them from the corpus rows
    row_of = {int(p): r for r, p in enumerate(np.asarray(corpus_ids))}
    hit = served_ids >= 0
    rows = np.vectorize(lambda p: row_of.get(int(p), -1))(served_ids)
    check(bool(np.all(rows[hit] >= 0)), f"{what}: served ids are live")
    c_idx = np.asarray(corpus_emb.indices)[np.maximum(rows, 0)]
    c_val = np.asarray(corpus_emb.values)[np.maximum(rows, 0)]
    q_idx = np.asarray(q_emb.indices)[:, None, :, None]
    q_val = np.asarray(q_emb.values)[:, None, :, None]
    same = (q_idx == c_idx[:, :, None, :]) & (q_idx != PAD_INDEX)
    true_d = -np.sum(np.where(same, q_val * c_val[:, :, None, :], 0.0),
                     axis=(2, 3))
    check(bool(np.allclose(served_d[hit], true_d[hit], atol=1e-4)),
          f"{what}: served distances equal exact distances")
    kth = exact_d[:, -1:]
    good = hit & (served_d <= kth + 1e-4)
    return float(good.sum() / served_ids.size)


def exhaustive_vs_brute(ids, emb, k_dims: int, cfg, what: str) -> None:
    """An exhaustive-probe index returns brute's distances, before and
    after churn (the recipe of tests/test_dynamic_equivalence.py)."""
    from repro.ann.brute import BruteIndex
    from repro.ann.sharded_index import ShardedGusIndex

    idx = ShardedGusIndex(k_dims, cfg)
    idx.build(ids, emb)
    brute = BruteIndex(k_dims, capacity=len(ids))
    brute.upsert(ids, emb)
    q = emb[:24]
    for phase in ("built", "after churn"):
        _, b_d = brute.search(q, 6)
        _, s_d = idx.search(q, 6)
        check(bool(np.allclose(np.sort(b_d, -1), np.sort(s_d, -1),
                               atol=1e-4)),
              f"{what} ({phase}): distances = brute")
        for index in (idx, brute):
            index.delete(ids[100:300])
            index.upsert(ids[100:200], emb[100:200])
    check(len(idx) == len(brute), f"{what}: live count = brute")


def equivalence_corpus(n_points: int):
    from repro.core import BucketConfig
    from repro.core.embedding import EmbeddingGenerator
    from repro.data.synthetic import OGB_ARXIV_LIKE, make_dataset

    data = dataclasses.replace(OGB_ARXIV_LIKE, n_points=n_points,
                               n_clusters=12)
    ids, feats, _ = make_dataset(data)
    gen = EmbeddingGenerator.create(
        data.spec, BucketConfig(dense_tables=8, dense_bits=10,
                                scalar_widths=(2.0,)))
    return ids, gen(feats), gen.k_max, data, feats


# the serving widths (launch.serve.gus_config) with a shortlist that covers
# the whole small corpus, so the result must equal brute's exactly
EXACT = dict(d_proj=64, pq_m=8, n_partitions=8, nprobe_local=0,
             reorder=8192, kmeans_iters=4, pq_iters=2)


# ---------------------------------------------------------------- one chip


def served_traffic(engine, stream, n_batches: int, n_queries: int):
    """Mixed mutate + query traffic through the front-end, then queries on
    the quiesced corpus. Returns (query features, responses) of the
    quiesced round."""
    from repro.serve.frontend import Frontend

    fe = Frontend(engine)
    accepted, terminal = set(), []

    def admit(resp):
        if resp.status == "accepted":
            accepted.add(resp.rid)
        else:
            terminal.append(resp)

    t0 = time.perf_counter()
    for _ in range(n_batches):
        admit(fe.submit_mutation(next(stream)))
        for _ in range(4):
            admit(fe.submit_query(stream.query_features(1), k=K))
    terminal += fe.drain()
    mixed_s = time.perf_counter() - t0
    quiet = [stream.query_features(1) for _ in range(n_queries)]
    t0 = time.perf_counter()
    for feats in quiet:
        admit(fe.submit_query(feats, k=K))
    answers = fe.drain()
    quiet_s = time.perf_counter() - t0
    terminal += answers

    rids = [r.rid for r in terminal]
    check(len(rids) == len(set(rids)) and set(rids) == accepted,
          f"{len(accepted)} accepted requests got exactly one terminal "
          "response each")
    check(all(r.status == "ok" for r in terminal), "no request shed or "
          "failed")
    lat = fe.describe()
    log(f"smoke latencies (not a benchmark): mixed round "
        f"{n_batches} mutation batches + {4 * n_batches} queries in "
        f"{mixed_s:.3f} s; quiesced round {n_queries} queries in "
        f"{quiet_s:.3f} s; frontend query latency ms "
        f"{json.dumps(lat['query_latency'])}")
    by_rid = {r.rid: r for r in answers}
    ordered = [by_rid[rid] for rid in sorted(by_rid)]
    return quiet, ordered


def one_chip(n_points: int, seed: int) -> None:
    import jax
    from repro.graph import GraphConfig
    from repro.launch.serve import build_engine
    from repro.serve.engine import EngineConfig

    t0 = time.perf_counter()
    kernel_phase(seed)
    log(f"kernel phase {time.perf_counter() - t0:.1f} s (compile included)")

    log(f"size cut: {n_points:,} of ogbn-arxiv's {ARXIV_POINTS:,} points: "
        f"{SIZE_CUT}")
    t0 = time.perf_counter()
    engine, stream, _ = build_engine(
        "arxiv", n_points, backend="sharded", shards=1, seed=seed,
        graph=GraphConfig(k=K), engine_cfg=EngineConfig(pipeline=True))
    gus = engine.gus
    log(f"bootstrap {len(gus.index)} points in "
        f"{time.perf_counter() - t0:.1f} s (compile included); slab "
        f"{gus.index.slab}, partitions {gus.index.cfg.n_partitions}, "
        f"probe {gus.index.cfg.nprobe_local}")

    quiet, answers = served_traffic(engine, stream, n_batches=16,
                                    n_queries=128)

    idx = gus.index
    step = next(iter(idx._query_steps.values()))
    b = 64
    text = step.lower(
        np.zeros((b, idx.k_dims), np.uint32),
        np.zeros((b, idx.k_dims), np.float32),
        np.zeros((b, idx.cfg.d_proj), np.float32), idx.state,
    ).compile().as_text()
    check("tpu_custom_call" in text,
          "compiled query step contains tpu_custom_call (the fused kernel)")

    corpus_ids = gus.store.ids()
    q_feats = {key: np.concatenate([f[key] for f in quiet])
               for key in quiet[0]}
    served_ids = np.concatenate([r.result.ids for r in answers])
    served_d = np.concatenate([r.result.distances for r in answers])
    recall = exact_check(gus.embedder(q_feats), served_ids, served_d,
                         corpus_ids, gus.embedder(gus.store.gather(corpus_ids)),
                         idx.k_dims, "served top-k")
    check(recall >= MIN_RECALL,
          f"served recall@{K} {recall:.4f} >= {MIN_RECALL} vs exact kNN")

    ids, emb, k_dims, _, _ = equivalence_corpus(900)
    exhaustive_vs_brute(ids, emb, k_dims, _sharded_cfg(1), "1-shard index")

    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'n/a')}")


def _sharded_cfg(shards: int, **kw):
    from repro.ann.sharded_index import ShardedConfig
    return ShardedConfig(n_shards=shards, **{**EXACT, **kw})


# -------------------------------------------------------------- four chips


def pod_pass(seed: int, ids, feats, data, sharded, min_recall: float,
             what: str) -> None:
    """An engine whose primary and replica are 2-shard indexes on the two
    disjoint pod meshes of ``make_pod_meshes(2, 2)``, every query hedged;
    both pods' top-k checked against exact kNN."""
    import jax
    from repro.core import BucketConfig, DynamicGUS, GusConfig
    from repro.core.scorer import scorer_init
    from repro.launch.mesh import make_pod_meshes
    from repro.serve.engine import EngineConfig, GusEngine
    from repro.serve.frontend import Frontend

    slices = [{d.id for d in m.devices.flat} for m in make_pod_meshes(2, 2)]
    check(not slices[0] & slices[1] and len(slices[0] | slices[1]) == 4,
          f"pod meshes on disjoint devices {sorted(map(sorted, slices))}")
    scorer = scorer_init(jax.random.PRNGKey(seed), data.spec)
    buckets = BucketConfig(dense_tables=8, dense_bits=10,
                           scalar_widths=(2.0,))

    def member(pod: int):
        gus = DynamicGUS(data.spec, buckets, scorer, GusConfig(
            scann_nn=K, backend="sharded",
            sharded=dataclasses.replace(sharded, n_shards=2, pod=pod)))
        gus.bootstrap(ids, feats, build_graph=False)
        placed = {d.id for d in gus.index.state["codes"].sharding.device_set}
        check(placed == slices[pod],
              f"{what}: pod {pod} index state lives on devices "
              f"{sorted(placed)}")
        return gus

    t0 = time.perf_counter()
    engine = GusEngine(member(0), EngineConfig(hedge_ms=0.0),
                       replicas=[member(1)])
    log(f"{what}: both pods bootstrapped {len(ids)} points in "
        f"{time.perf_counter() - t0:.1f} s (compile included)")
    fe = Frontend(engine)
    q_feats = {key: v[:32] for key, v in feats.items()}
    for r in range(32):
        fe.submit_query({key: v[r:r + 1] for key, v in q_feats.items()}, k=K)
    answers = sorted(fe.drain(), key=lambda r: r.rid)
    check(all(r.status == "ok" for r in answers), f"{what}: 32 queries "
                                                  "answered")
    check(engine.hedged > 0, f"{what}: {engine.hedged} query batches hedged "
                             "to the replica pod")
    q_emb = engine.gus.embedder(q_feats)
    for name, gus in (("primary pod", engine.gus),
                      ("replica pod", engine.replicas[0])):
        res = gus.neighbors(q_feats, K)
        recall = exact_check(q_emb, res.ids, res.distances, ids,
                             gus.embedder(feats), gus.index.k_dims,
                             f"{what}: {name}")
        check(recall >= min_recall, f"{what}: {name} recall@{K} "
                                    f"{recall:.4f} >= {min_recall}")
    served = np.concatenate([r.result.distances for r in answers])
    res = engine.gus.neighbors(q_feats, K)
    check(bool(np.allclose(served, res.distances, atol=1e-4)),
          f"{what}: hedged answers = primary pod's answers")


def four_chip(seed: int) -> None:
    import jax
    from repro.data.synthetic import OGB_ARXIV_LIKE, make_dataset
    from repro.launch.serve import gus_config

    check(len(jax.devices()) == 4, "four chips visible")
    ids, emb, k_dims, data, feats = equivalence_corpus(900)
    for merge in ("flat", "hier"):
        exhaustive_vs_brute(ids, emb, k_dims, _sharded_cfg(4, merge=merge),
                            f"4-shard index, merge={merge}")
    pod_pass(seed, ids, feats, data, _sharded_cfg(2, n_partitions=16), 1.0,
             "exhaustive pods")
    # the served configuration on a corpus of POD_POINTS
    data = dataclasses.replace(OGB_ARXIV_LIKE, n_points=POD_POINTS)
    ids, feats, _ = make_dataset(data)
    pod_pass(seed, ids, feats, data,
             gus_config(POD_POINTS, scann_nn=K, backend="sharded",
                        shards=2).sharded, MIN_RECALL, "served pods")


# -------------------------------------------------------------------- main


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    dev = jax.devices()
    if dev[0].platform != "tpu":
        print(f"[chip_smoke] no TPU found (JAX platform is "
              f"{dev[0].platform!r}); this smoke runs only on a TPU",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.cache import configure_compile_cache
    log(f"compile cache: {configure_compile_cache()}")
    log(f"devices: {len(dev)} x {dev[0].device_kind}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chip(args.seed)
    else:
        one_chip(SMOKE_POINTS, args.seed)
    log(f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
