"""Dynamic GUS — the system of paper §3: Embedding Generator + ScaNN +
Similarity Scorer behind two RPC surfaces (mutations, neighborhoods).

``DynamicGUS`` is the serving engine: it owns the embedding generator
(with its hot-reloadable IDF/filter tables), an ANN backend, a feature
store (the scorer needs candidate features, paper §3.3.3 step "requests
the closest points ... and their features"), and the scorer parameters.
The backend is selected by ``GusConfig.backend``:

  "brute"   — exact ``BruteIndex`` (oracle / small corpora);
  "scann"   — quantized single-replica ``ScannIndex``;
  "sharded" — ``ShardedGusIndex``, the shard_map scatter/merge programs of
              ``ann.sharded`` on a multi-device mesh (the paper's index
              tower sharded across chips), with a maintained slab
              lifecycle: SOAR secondary copies, auto-compaction instead of
              ring-buffer age-out, and skew re-splits (ann/sharded_index).

Every backend speaks the same ``build / upsert / delete / search``
protocol, so the RPC surfaces below are backend-agnostic; ``serve.engine``
adds batching, hedging against replicas, and fault recovery on top.

Latency accounting mirrors the paper's Fig. 9/10: per-RPC wall-clock
timers for mutation and neighborhood paths. Inside a traced request the
neighborhood path opens ``embed`` and ``score`` spans (``score`` holding
``gather`` and the ``device_wait`` for the scorer's weights) on the
telemetry plane an engine binds (``bind_telemetry``).
"""
from __future__ import annotations

import dataclasses
from typing import Mapping

import numpy as np

from repro.ann.brute import BruteIndex
from repro.ann.scann import ScannConfig, ScannIndex
from repro.ann.sharded_index import ShardedConfig, ShardedGusIndex
from repro.core import idf as idf_mod
from repro.core.buckets import BucketConfig
from repro.core.embedding import EmbeddingGenerator
from repro.core.maintenance import MaintenanceConfig
from repro.core.scorer import pair_features, scorer_apply
from repro.core.types import (FeatureSpec, MutationBatch, NeighborResult,
                              MUTATION_DELETE)
from repro.graph.store import DynamicGraphStore, GraphConfig
from repro.multimodal import (MultiModalConfig, MultiModalStore,
                              two_stage_neighbors)
from repro.obs import Telemetry
from repro.utils.timing import Timer


@dataclasses.dataclass
class StagedMutation:
    """A mutation batch split at the encode/apply boundary (the unit the
    async pipeline double-buffers). ``encode_mutation`` fills everything
    but ``pending``; ``apply_mutation`` dispatches the device writes and
    parks their in-flight handle in ``pending`` for the barrier."""
    n: int                                  # points acknowledged
    dels: np.ndarray | None                 # ids to tombstone
    up_ids: np.ndarray | None               # ids to insert/update
    feats: dict | None                      # store-normalized features
    emb: object | None                      # SparseBatch embeddings
    index_staged: object | None             # backend encode artifacts
    buckets: tuple | None = None            # (bucket_ids, valid) np arrays,
                                            # staged when multimodal is on
    pending: object | None = None           # in-flight device handle


@dataclasses.dataclass(frozen=True)
class GusConfig:
    scann_nn: int = 10          # ScaNN-NN: neighbors retrieved from the index
    idf_size: int = 0           # IDF-S   : IDF table size (0 = unit weights)
    filter_percent: float = 0.0  # Filter-P: % of most popular buckets dropped
    backend: str = "scann"      # "scann" | "brute" | "sharded"
    scann: ScannConfig = ScannConfig()
    sharded: ShardedConfig = ShardedConfig()
    # maintained-graph layer (repro.graph): None disables maintenance
    graph: GraphConfig | None = None
    # canonical home of the maintenance knobs (core.maintenance): when
    # set, it overrides the per-subsystem configs' own `maintenance`;
    # `staleness_bound > 0` activates the concurrent maintenance plane
    maintenance: MaintenanceConfig | None = None
    # multi-modal scoring plane (repro.multimodal): None keeps the dense
    # embed -> search -> score path bitwise unchanged
    multimodal: MultiModalConfig | None = None


def make_index(k_dims: int, cfg: GusConfig):
    """ANN backend factory — every backend speaks build/upsert/delete/search."""
    if cfg.backend == "brute":
        return BruteIndex(k_dims)
    if cfg.backend == "sharded":
        return ShardedGusIndex(k_dims, cfg.sharded)
    if cfg.backend == "scann":
        return ScannIndex(k_dims, cfg.scann)
    raise ValueError(f"unknown GUS backend {cfg.backend!r}")


class FeatureStore:
    """Host-side feature store keyed by point id (numpy columns)."""

    def __init__(self, spec: FeatureSpec):
        self.spec = spec
        self._rows: dict[int, dict] = {}

    def put(self, ids: np.ndarray, features: Mapping[str, np.ndarray]) -> None:
        for i, pid in enumerate(np.asarray(ids).tolist()):
            self._rows[pid] = {k: np.asarray(v[i]) for k, v in features.items()}

    def drop(self, ids) -> None:
        for pid in np.asarray(ids).tolist():
            self._rows.pop(pid, None)

    def clear(self) -> None:
        """Drop every row (a stale replica re-bootstrapping from a
        snapshot must not keep features the snapshot already dropped)."""
        self._rows.clear()

    def ids(self) -> np.ndarray:
        """Live point ids, ascending (the public view of the corpus)."""
        return np.asarray(sorted(self._rows), np.int64)

    def gather(self, ids: np.ndarray) -> dict:
        """Batch features for ids (missing ids get zeros)."""
        ids = np.asarray(ids)
        proto = self.spec.feature_shapes(1)
        out = {k: np.zeros((ids.size,) + tuple(s.shape[1:]),
                           np.dtype(s.dtype.name)) for k, s in proto.items()}
        for j, pid in enumerate(ids.reshape(-1).tolist()):
            row = self._rows.get(pid)
            if row is not None:
                for k, v in row.items():
                    out[k][j] = v
        return {k: v.reshape(ids.shape + v.shape[1:]) for k, v in out.items()}

    def __len__(self):
        return len(self._rows)

    def __contains__(self, pid) -> bool:
        return int(pid) in self._rows

    # ------------------------------------------ persistence (SnapshotStateful)

    def snapshot_state(self) -> dict:
        ids = self.ids()
        return {"ids": ids, "features": self.gather(ids)}

    def restore_state(self, state: dict) -> None:
        self.clear()
        if len(state["ids"]):
            self.put(state["ids"], state["features"])


class DynamicGUS:
    """The Dynamic Grale Using ScaNN engine."""

    def __init__(self, spec: FeatureSpec, bucket_cfg: BucketConfig,
                 scorer_params: dict, cfg: GusConfig = GusConfig()):
        self.spec = spec
        # GusConfig.maintenance is canonical: push it down into the
        # per-subsystem configs so every layer sees one set of knobs
        if cfg.maintenance is not None:
            sub = {"sharded": dataclasses.replace(
                cfg.sharded, maintenance=cfg.maintenance)}
            if cfg.graph is not None:
                sub["graph"] = dataclasses.replace(
                    cfg.graph, maintenance=cfg.maintenance)
            cfg = dataclasses.replace(cfg, **sub)
        self.cfg = cfg
        self.maintenance = (
            cfg.maintenance
            or (cfg.graph.maintenance if cfg.graph is not None else None)
            or (cfg.sharded.maintenance if cfg.backend == "sharded" else None)
            or MaintenanceConfig())
        self.embedder = EmbeddingGenerator.create(spec, bucket_cfg)
        self.scorer_params = scorer_params
        self.store = FeatureStore(spec)
        self.index = make_index(self.embedder.k_max, cfg)
        self.graph = DynamicGraphStore(cfg.graph) if cfg.graph else None
        self.multimodal = (MultiModalStore(cfg.multimodal)
                           if cfg.multimodal is not None else None)
        # applied mutation batches — the staleness ledger the concurrent
        # maintenance plane stamps published snapshot versions against
        self.seq_applied = 0
        self.mutation_timer = Timer("mutation")
        self.query_timer = Timer("neighbors")
        self.graph_timer = Timer("graph")
        # standalone engines get a private telemetry plane that nothing
        # activates; a GusEngine binds its primary into the shared one
        self.obs = Telemetry()

    def bind_telemetry(self, telemetry: Telemetry) -> None:
        """Join a shared telemetry plane: the query path's spans attach to
        the traces its tracer activates."""
        self.obs = telemetry

    # ----------------------------------------------------- offline (§4.3)

    def bootstrap(self, ids: np.ndarray, features: Mapping[str, np.ndarray],
                  build_graph: bool = True) -> None:
        """Offline preprocessing: compute IDF/filter tables from the initial
        corpus, (re)build the index, and load all points. The maintained
        graph (if configured) is seeded from full-corpus neighborhoods;
        pass ``build_graph=False`` when restoring it from a snapshot."""
        bucket_ids, valid = self.embedder.buckets(features)
        bucket_ids, valid = np.asarray(bucket_ids), np.asarray(valid)
        n = len(ids)
        self.embedder = self.embedder.reload(
            idf=idf_mod.build_idf_table(bucket_ids, valid, n, self.cfg.idf_size),
            filter_table=idf_mod.build_filter_table(
                bucket_ids, valid, self.cfg.filter_percent))
        emb = self.embedder(features)
        self.index.build(ids, emb)
        self.store.put(ids, features)
        if self.multimodal is not None:
            # seed the multi-modal plane before the graph: its candidate
            # stage feeds the graph-seeding neighborhood probes below
            self.multimodal.rebuild(ids, emb, bucket_ids, valid)
        if self.graph is not None:
            self.graph = DynamicGraphStore(self.cfg.graph)   # fresh corpus
            if build_graph:
                with self.graph_timer:
                    self.graph.ensure_ids(np.asarray(ids))
                    for lo in range(0, len(ids), 256):
                        chunk = np.asarray(ids[lo:lo + 256])
                        self.graph.upsert(chunk, self._index_neighbors_of_ids(
                            chunk, self.graph.cfg.probe_k(), timed=False))
                    self.flush_graph_repair(limit=len(ids))
            if self.maintenance.staleness_bound > 0:
                self.graph.publish(seq=self.seq_applied)

    def periodic_reload(self) -> None:
        """Recompute IDF/filter from the live corpus and retrain the index
        (the paper's periodic consistency refresh)."""
        ids = self.store.ids()
        if ids.size == 0:
            return
        feats = self.store.gather(ids)
        bucket_ids, valid = self.embedder.buckets(feats)
        bucket_ids, valid = np.asarray(bucket_ids), np.asarray(valid)
        self.embedder = self.embedder.reload(
            idf=idf_mod.build_idf_table(bucket_ids, valid, ids.size,
                                        self.cfg.idf_size),
            filter_table=idf_mod.build_filter_table(
                bucket_ids, valid, self.cfg.filter_percent))
        # the reloaded tables change the embeddings, so every backend
        # retrains/reloads from the live corpus
        emb = self.embedder(feats)
        self.index.build(ids, emb)
        if self.multimodal is not None:
            self.multimodal.rebuild(ids, emb, bucket_ids, valid)

    # ------------------------------------------------------ mutation RPCs

    def mutate(self, batch: MutationBatch) -> int:
        """Insert / update / delete a batch of points (paper §3.3.1-.2).
        Returns the number of points acknowledged. When a maintained graph
        is configured, every mutation also updates it: deletes tombstone
        the row and purge back-edges; upserts re-query the point's scored
        neighborhood and apply two-sided edge updates.

        This is the synchronous path: encode, apply, and graph maintenance
        run back-to-back. ``serve.pipeline.MutationPipeline`` drives the
        same stages double-buffered (encode batch i+1 while batch i's
        device append is in flight) with identical final state."""
        with self.mutation_timer:
            staged = self.encode_mutation(batch)
            self.apply_mutation(staged)
            self.finish_mutation(staged)
        self.seq_applied += 1
        self.maybe_reload_multimodal()
        if self.graph is not None:
            with self.graph_timer:
                self.graph_apply(staged)
                self.flush_graph_repair()
            if self.maintenance.staleness_bound > 0:
                # the synchronous path keeps the published view fresh, so
                # mixed sync/plane serving still honors the bound
                self.graph.publish(seq=self.seq_applied)
        return staged.n

    # ---------------------------------------- staged mutation (write path)

    def encode_mutation(self, batch: MutationBatch) -> "StagedMutation":
        """Stage A (host routing + feature/embedding encoding, pure): parse
        the batch, normalize features to the store's dtypes, embed, and run
        the backend's pure encode (sketch/routing/PQ codes). Touches no
        engine state, so the pipeline can encode batch i+1 while batch i's
        device append is still in flight."""
        kinds = np.asarray(batch.kinds)
        ids = np.asarray(batch.ids)
        del_mask = kinds == MUTATION_DELETE
        dels = ids[del_mask] if del_mask.any() else None
        up_ids = feats = emb = index_staged = None
        up_mask = ~del_mask
        if up_mask.any():
            up_ids = ids[up_mask]
            proto = self.spec.feature_shapes(1)
            feats = {k: np.asarray(v)[up_mask].astype(
                np.dtype(proto[k].dtype.name), copy=False)
                for k, v in batch.features.items()}
            emb = self.embedder(feats)
            index_staged = self.index.encode_upsert(up_ids, emb)
        buckets = None
        if self.multimodal is not None and feats is not None:
            # buckets are a pure function of the features (IDF/filter
            # tables only re-weight *after* generation), so staging them
            # here keeps the encode stage side-effect-free
            b_ids, b_valid = self.embedder.buckets(feats)
            buckets = (np.asarray(b_ids), np.asarray(b_valid))
        return StagedMutation(n=int(ids.size), dels=dels, up_ids=up_ids,
                              feats=feats, emb=emb,
                              index_staged=index_staged, buckets=buckets)

    def apply_mutation(self, staged: "StagedMutation") -> None:
        """Stage B dispatch: tombstone deletes, ship the staged upserts
        through the backend's async append, update the feature store. Host
        maps that need device results are finalized by
        ``finish_mutation`` (the barrier)."""
        if staged.dels is not None:
            self.index.delete(staged.dels)
            self.store.drop(staged.dels)
            if self.multimodal is not None:
                self.multimodal.delete(staged.dels)
        if staged.up_ids is not None:
            staged.pending = self.index.begin_upsert(
                staged.up_ids, staged.emb, staged.index_staged)
            self.store.put(staged.up_ids, staged.feats)
            if self.multimodal is not None:
                self.multimodal.upsert(staged.up_ids, staged.emb,
                                       *staged.buckets)

    def finish_mutation(self, staged: "StagedMutation") -> None:
        """Barrier (hand-off): block on in-flight device appends and
        finalize host maps. After this, the batch is query-visible."""
        if staged.up_ids is not None:
            self.index.finish_upsert(staged.pending)

    def graph_apply(self, staged: "StagedMutation",
                    reuse_emb: bool = False) -> None:
        """Maintained-graph update for an applied batch. ``reuse_emb=True``
        (the pipelined path) feeds the staged embeddings straight into the
        probe query instead of re-gathering + re-embedding from the store —
        bit-identical results (the store holds the same feature values),
        one less embed per batch."""
        if self.graph is None:
            return
        if staged.dels is not None:
            self.graph.delete(staged.dels)
        if staged.up_ids is not None:
            probe_k = self.graph.cfg.probe_k()
            if reuse_emb:
                res = self._neighbors_impl(staged.feats, probe_k,
                                           exclude_ids=staged.up_ids,
                                           emb=staged.emb,
                                           buckets=staged.buckets)
            else:
                res = self._index_neighbors_of_ids(staged.up_ids, probe_k,
                                                   timed=False)
            self.graph.upsert(staged.up_ids, res)

    def flush_graph_repair(self, limit: int | None = None) -> int:
        """Drain the graph's repair queue: rows left under-full by deletes
        or evictions get a fresh neighborhood merged in (no purge — the
        repaired points' embeddings did not change). One batched
        ``_index_neighbors_of_ids`` call per drain, capped at ``limit``
        (default ``MaintenanceConfig.repair_per_tick``)."""
        if self.graph is None:
            return 0
        rep = self.graph.take_repair_ids(limit)
        if rep.size:
            self.graph.upsert(
                rep, self._index_neighbors_of_ids(
                    rep, self.graph.cfg.probe_k(), timed=False),
                purge=False)
        return int(rep.size)

    # --------------------------------------------------- neighborhood RPC

    def neighbors(self, features: Mapping[str, np.ndarray],
                  k: int | None = None,
                  exclude_ids: np.ndarray | None = None) -> NeighborResult:
        """Neighborhood of (possibly new) points given their features
        (paper §3.3.3): embed -> ANN search -> score -> respond."""
        with self.query_timer:
            return self._neighbors_impl(features, k, exclude_ids)

    def maybe_reload_multimodal(self) -> bool:
        """Reload the multi-modal routing tables when the configured
        cadence divides the applied-batch sequence. Both write paths call
        this right after bumping ``seq_applied`` (the pipeline pins its
        fuse window to 1 while a cadence is set, so the schedules — and
        therefore the tables any later batch embeds against — are
        identical; see serve/pipeline.py window-closing rules)."""
        mm = self.multimodal
        if mm is None or mm.cfg.reload_every <= 0:
            return False
        if self.seq_applied > 0 and \
                self.seq_applied % mm.cfg.reload_every == 0:
            mm.reload()
            return True
        return False

    def _neighbors_impl(self, features, k, exclude_ids,
                        emb=None, buckets=None) -> NeighborResult:
        k = k or self.cfg.scann_nn
        if self.multimodal is not None:
            return two_stage_neighbors(self, features, k, exclude_ids,
                                       emb=emb, buckets=buckets)
        tracer = self.obs.tracer
        if emb is None:
            with tracer.span("embed"):
                emb = self.embedder(features)
        ids, dists = self.index.search(emb, k + (exclude_ids is not None))
        if exclude_ids is not None:
            ids, dists = _drop_self(ids, dists, np.asarray(exclude_ids), k)
        with tracer.span("score"):
            with tracer.span("gather"):
                cand_feats = self.store.gather(ids)
            flat_q = {kk: np.repeat(np.asarray(v), ids.shape[1], axis=0)
                      for kk, v in features.items()}
            flat_c = {kk: v.reshape((-1,) + v.shape[2:])
                      for kk, v in cand_feats.items()}
            weights = scorer_apply(
                self.scorer_params, pair_features(flat_q, flat_c, self.spec))
            with tracer.span("device_wait"):
                weights = np.asarray(weights)
        weights = weights.reshape(ids.shape)
        weights = np.where(ids >= 0, weights, -np.inf)
        return NeighborResult(ids=ids, weights=weights.astype(np.float32),
                              distances=dists)

    def neighbors_of_ids(self, ids: np.ndarray, k: int | None = None
                         ) -> NeighborResult:
        """Neighborhood of existing points (self-match excluded).

        With a maintained graph, requests at k <= the maintenance k are
        served straight from the graph rows — no re-embedding, no ANN
        search (the paper's "graph building" product surface). With the
        concurrent maintenance plane active (``staleness_bound > 0``)
        the read goes through the *published* `GraphView` version, which
        may lag the applied stream by at most ``staleness_bound``
        batches; ids the view does not know yet fall back to the
        embed -> search -> score path."""
        ids = np.asarray(ids)
        k = k or self.cfg.scann_nn
        if self.graph is not None and k <= self.graph.cfg.k:
            if self.maintenance.staleness_bound > 0:
                view = self.graph.view()
                if view.has_ids(ids):
                    with self.query_timer:
                        return view.neighbors_of_ids(ids, k)
            elif self.graph.has_ids(ids):
                with self.query_timer:
                    return self.graph.neighbors_of_ids(ids, k)
        return self._index_neighbors_of_ids(ids, k)

    def _index_neighbors_of_ids(self, ids: np.ndarray, k: int | None = None,
                                timed: bool = True) -> NeighborResult:
        """The embed -> search -> score path, bypassing the graph (used by
        graph maintenance itself and as the fast path's fallback). Graph
        maintenance passes ``timed=False`` so its internal re-queries don't
        pollute the serving query-latency accounting (they are billed to
        ``graph_timer`` instead)."""
        feats = self.store.gather(np.asarray(ids))
        ids = np.asarray(ids)
        if timed:
            return self.neighbors(feats, k, exclude_ids=ids)
        return self._neighbors_impl(feats, k, exclude_ids=ids)

    # ------------------------------------------ persistence (SnapshotStateful)

    def snapshot_state(self) -> dict:
        """Composed snapshot: the feature store (corpus of record), the
        index's minimal routing state, and the full graph state. Each
        piece comes from the subsystem's own `SnapshotStateful`
        implementation — the engine just persists the dict."""
        return {
            "store": self.store.snapshot_state(),
            "index": self.index.snapshot_state(),
            "graph": (self.graph.snapshot_state()
                      if self.graph is not None else None),
            "multimodal": (self.multimodal.snapshot_state()
                           if self.multimodal is not None else None),
        }

    def restore_state(self, state: dict) -> None:
        """Inverse composition. Order matters: the index's routing state
        (owner-hash salt) must be installed before ``bootstrap`` rebuilds
        the slabs, and the graph restores after the corpus exists (a
        snapshotted graph skips the bootstrap re-seed entirely)."""
        self.store.clear()
        self.index.restore_state(state.get("index") or {})
        graph_state = state.get("graph")
        st = state["store"]
        self.bootstrap(st["ids"], st["features"],
                       build_graph=graph_state is None)
        if self.graph is not None and graph_state is not None:
            self.graph.restore_state(graph_state)
        mm_state = state.get("multimodal")
        if self.multimodal is not None and mm_state is not None:
            # overwrite bootstrap's re-seed: posting-list membership is
            # insertion-order-dependent (capped lists), so the restored
            # plane must be the snapshotted one, not a rebuild
            self.multimodal.restore_state(mm_state)


def _drop_self(ids, dists, self_ids, k):
    """Remove each query's own id from its result row, then trim to k."""
    out_ids = np.full((ids.shape[0], k), -1, ids.dtype)
    out_d = np.full((ids.shape[0], k), np.inf, dists.dtype)
    for r in range(ids.shape[0]):
        keep = ids[r] != self_ids[r]
        sel_ids, sel_d = ids[r][keep][:k], dists[r][keep][:k]
        out_ids[r, :sel_ids.size] = sel_ids
        out_d[r, :sel_d.size] = sel_d
    return out_ids, out_d
