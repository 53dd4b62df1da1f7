"""Live sharded GUS backend: the shard_map programs behind the index protocol.

``ShardedGusIndex`` takes the distributed query/mutate/delete/compact
programs of ``repro.ann.sharded`` — the exact programs the dry-run lowers
for the pod cells — and runs them on a small local mesh
(``launch.mesh.make_gus_mesh``) behind the same ``build / upsert / delete /
search`` protocol as ``BruteIndex`` and ``ScannIndex``, so ``DynamicGUS``
can serve from it unchanged (``GusConfig(backend="sharded")``).

Serving dataflow (paper §3.1 mapped onto shards, static shapes end-to-end):

  mutate  — batch replicated to every shard; rows hash-route to their owner
            shard (salted hash — see re-split below), append ring-buffer
            style into the nearest local partition's slab *and*, with SOAR
            enabled (the default), into a secondary local partition chosen
            for residual orthogonality (Sun et al. 2024 — the same
            effective redundancy ``ScannIndex`` spills). The device
            returns each row's landing sites (global partition, slot) per
            copy, which the host mirrors into an id -> rows map (needed
            for deletes and result translation).
  delete  — host looks up landing sites, the tombstone program clears the
            validity bits on the owning shard.
  search  — per-shard: centroid matmul -> local top-nprobe -> PQ LUT
            scoring -> exact sparse rescore -> SOAR dedup by point id ->
            local top-k; one all_gather + merge top-k across shards. The
            host translates global rows back to point ids.

Slab lifecycle (capacity is *maintained*, not silently recycled):

  compaction — ``compact()`` runs the per-shard compact program: dead
            slots (tombstones, superseded copies) are squeezed out, live
            rows slide forward in stable order, the ring cursor resets to
            the live count, and the host id -> rows map is remapped from
            the device-reported old-slot -> new-slot map. Stability makes
            search results **bit-identical** before/after compaction.
            With ``maintenance.compact`` (default), ``begin_upsert``
            compacts any
            slab an incoming chunk would wrap — and if live occupancy
            alone would still overflow, doubles the slab — so live rows
            never silently age out (``aged_out`` counts the rows the old
            wrap behavior would have dropped; it stays 0).
  re-split — ``resplit()`` fixes per-shard occupancy skew: when
            ``max/mean`` live rows per shard exceeds the threshold, the
            hottest shard's rows are read back, the owner-hash ``salt`` is
            bumped (a compile-time constant of the mutate program), and
            the rows re-insert through the ordinary route/mutate machinery
            — spreading them across the whole mesh. Queries never consult
            the owner hash, so mixed-salt placements stay exactly
            servable; ``GusEngine`` snapshots the salt so recovery
            re-routes the same way.

Fuse-window rule (the compaction boundary — see serve/pipeline.py): both
compaction and slab growth move or re-home slots, so they must never land
with another window's landing sites still un-materialized. They only ever
run inside ``begin_upsert`` — after the pending landing sites of the
current call are materialized — which is safe at any fuse width. What
``maintenance_pressure()`` buys depends on the maintenance plane
(``MaintenanceConfig.staleness_bound``):

  * bound == 0 (default): the pipeline closes its fuse window while
    pressure holds, so the pipelined schedule degenerates to exactly the
    synchronous per-batch schedule and stays bit-identical
    (tests/test_pipeline.py::test_pipeline_compaction_boundary).
  * bound > 0: windows stay fused under pressure; compaction triggers
    inside ``begin_upsert`` mid-stream (correct, but on a different —
    amortized — schedule than the sync path) and re-splits run off-path
    at worker-drain boundaries. Every lifecycle step builds its
    successor state fully before one atomic reference swap and bumps
    ``version``; ``publish()`` names the current state as an immutable
    `IndexVersion` so a holder never observes a half-built layout.
"""
from __future__ import annotations

import dataclasses
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.ann import partition as part_mod
from repro.ann import quantize as pq
from repro.ann.sharded import (GusCellConfig, index_specs, make_compact_step,
                               make_delete_step, make_mutate_step,
                               make_query_step)
from repro.ann.sparse import count_sketch
from repro.core import hashing
from repro.core.maintenance import MaintenanceConfig, resolve_legacy
from repro.core.types import PAD_INDEX, SparseBatch
from repro.kernels.topk_select import block_n_for
from repro.launch.mesh import make_gus_mesh
from repro.obs import Telemetry
from repro.utils import pow2_pad

_PAD_ID = 0xFFFFFFFF  # reserved: mutation-batch padding, never a point id


def blocks_under_cursor(cursor: np.ndarray, slab: int, n_shards: int,
                        block_n: int) -> float:
    """Share of the ``block_n``-slot blocks of each shard's slabs, laid end
    to end in partition order, that hold a slot under its partition's
    append cursor. Every live slot lies under its cursor, so with every
    partition probed, and slabs a whole number of blocks, this bounds the
    share of blocks the fused shortlist kernel scores
    (``kernels/ops.py::shortlist_block_live``)."""
    fill = np.minimum(cursor, slab).reshape(n_shards, -1)
    start = np.arange(fill.shape[1]) * slab
    n_blocks = -(-fill.shape[1] * slab // block_n)
    edges = np.zeros((n_shards, n_blocks + 1), np.int64)
    shard, part = np.nonzero(fill)
    np.add.at(edges, (shard, start[part] // block_n), 1)
    np.add.at(edges, (shard, (start[part] + fill[shard, part] - 1)
                      // block_n + 1), -1)
    return float((np.cumsum(edges, axis=1)[:, :n_blocks] > 0).mean())


@dataclasses.dataclass(frozen=True)
class IndexVersion:
    """An immutable published version of the slabs (the RCU read side).

    ``state`` is captured by reference (the jnp arrays are immutable and
    every lifecycle step rebinds a fresh dict rather than editing one);
    ``id_of_row`` is copied because ``_materialize`` writes it in place.
    A holder of an IndexVersion therefore keeps a self-consistent
    translated view across later compactions / grows / re-splits."""

    version: int
    seq: int                      # last applied mutation batch reflected
    state: dict
    id_of_row: np.ndarray
    salt: int
    slab: int
    points: int


@dataclasses.dataclass(frozen=True)
class ShardedConfig:
    n_shards: int = 1
    d_proj: int = 64            # CountSketch dimension
    n_partitions: int = 16      # global partition count (divisible by shards)
    slab: int = 512             # ring-buffer rows per partition (minimum;
    #                             build() grows it to fit the corpus)
    nprobe_local: int = 0       # partitions probed per shard (0 = all local)
    reorder: int = 256          # per-shard exact-rescore shortlist
    query_batch: int = 64       # max padded query batch per device call
    mutate_batch: int = 256     # padded mutation batch per device call
    pq_m: int = 8               # PQ subspaces
    pq_centers: int = 256
    kmeans_iters: int = 12
    pq_iters: int = 6
    eta: float = 1.0            # anisotropic weight for codebook training
    seed: int = 13
    merge: str = "flat"         # cross-shard candidate merge: "flat" | "hier"
    fused: bool = True          # fused shortlist op (False = composed ops,
    #                             bitwise-identical escape hatch)
    pq_int8: bool = False       # int8-quantised LUT scoring in the shortlist
    # ---- slab lifecycle -------------------------------------------------
    # Lifecycle knobs (SOAR weight, auto-compaction, slab headroom, skew
    # re-splits) live on MaintenanceConfig; the fields below are one-release
    # deprecation shims folded into ``maintenance`` by __post_init__.
    soar_lambda: float | None = None           # legacy-ok
    auto_compact: bool | None = None           # legacy-ok
    slab_headroom: float | None = None         # legacy-ok
    resplit_imbalance: float | None = None     # legacy-ok
    resplit_by: str | None = None              # legacy-ok
    # replica group this index belongs to: its mesh is carved from the
    # pod'th disjoint device slice (launch.mesh.make_gus_mesh)
    pod: int = 0
    maintenance: MaintenanceConfig | None = None

    def __post_init__(self):
        m = resolve_legacy(self.maintenance, {
            "soar": ("ShardedConfig.soar_lambda", self.soar_lambda),         # legacy-ok
            "compact": ("ShardedConfig.auto_compact", self.auto_compact),    # legacy-ok
            "headroom": ("ShardedConfig.slab_headroom", self.slab_headroom),  # legacy-ok
            "resplit":
                ("ShardedConfig.resplit_imbalance", self.resplit_imbalance),  # legacy-ok
            "resplit_metric": ("ShardedConfig.resplit_by", self.resplit_by),  # legacy-ok
        })
        object.__setattr__(self, "maintenance", m)
        for old in ("soar_lambda", "auto_compact", "slab_headroom",
                    "resplit_imbalance", "resplit_by"):
            object.__setattr__(self, old, None)

    @property
    def use_soar(self) -> bool:
        # SOAR disabled when a shard owns a single partition — no distinct
        # secondary exists
        return (self.maintenance.soar >= 0
                and self.n_partitions // max(self.n_shards, 1) > 1)

    @property
    def n_copies(self) -> int:
        return 2 if self.use_soar else 1


class ShardedGusIndex:
    """Dynamic sharded index over sparse embeddings (multi-device)."""

    def __init__(self, k_dims: int, cfg: ShardedConfig = ShardedConfig()):
        if cfg.n_partitions % cfg.n_shards:
            raise ValueError(
                f"n_partitions={cfg.n_partitions} must be divisible by "
                f"n_shards={cfg.n_shards}")
        if cfg.d_proj % cfg.pq_m:
            raise ValueError(
                f"d_proj={cfg.d_proj} must split into pq_m={cfg.pq_m} "
                "subspaces")
        self.k_dims = k_dims
        self.cfg = cfg
        self.mesh = make_gus_mesh(cfg.n_shards,
                                  two_level=cfg.merge == "hier",
                                  pod=cfg.pod)
        self.trained = False
        self.slab = cfg.slab
        self.salt = 3                        # owner-hash salt (resplit bumps)
        self.state: dict | None = None
        # id -> landing rows (part*S + pos), one per copy, primary first
        self.row_of: dict[int, tuple[int, ...]] = {}
        self.id_of_row: np.ndarray | None = None
        self._cursor = np.zeros((cfg.n_partitions,), np.int64)  # appends/part
        # queries served per partition since the last load-driven
        # re-split (the "load" skew metric; search() accumulates hits)
        self.query_load = np.zeros((cfg.n_partitions,), np.int64)
        self._query_steps: dict = {}         # (padded B, k) -> jitted step
        self._mutate = None
        self._tombstone = None
        self._compact_step = None
        self._in_maintenance = False
        # versioned publishing: every lifecycle step that re-homes slots
        # (compaction, slab grow, re-split) builds its successor state
        # fully before the atomic reference swap, then bumps `version`;
        # publish() names the current state as an immutable IndexVersion
        self.version = 0
        self._published: IndexVersion | None = None
        # lifecycle counters (occupancy()/stats() surface them)
        self.compactions = 0
        self.slab_grows = 0
        self.resplits = 0
        self.reclaimed = 0                   # dead slots squeezed out
        self.compacted_rows = 0              # live rows moved by compactions
        self.compact_s = 0.0                 # wall-clock spent compacting
        self.aged_out = 0                    # ids lost to ring wrap (0 when
        #                                      maintenance.compact is on)
        self.live_block_share = 0.0          # blocks_under_cursor, kept
        #                                      where cursors or slab move
        # standalone indexes get a private telemetry plane; an engine
        # rebinds its primary's index into the shared one (bind_telemetry)
        self.obs = Telemetry()
        self._bind_instruments()

    def _bind_instruments(self) -> None:
        reg = self.obs.registry
        self._c_compactions = reg.counter(
            "index_compactions_total", "slab compactions run")
        self._c_reclaimed = reg.counter(
            "index_reclaimed_slots_total", "dead slots squeezed out")
        self._c_compacted_rows = reg.counter(
            "index_compacted_rows_total", "live rows moved by compactions")
        self._c_slab_grows = reg.counter(
            "index_slab_grows_total", "slab doublings")
        self._c_resplits = reg.counter(
            "index_resplits_total", "skew re-splits")
        self._c_moved_points = reg.counter(
            "index_moved_points_total", "points re-hashed by re-splits")
        self._c_aged_out = reg.counter(
            "index_aged_out_total", "ids lost to ring wrap")
        self._h_compact = reg.histogram(
            "index_compact_ms", "wall-clock per compaction")
        self._h_search = reg.histogram(
            "index_search_ms", "device fan-out/merge time per search call")
        self._g_live_blocks = reg.gauge(
            "index_shortlist_live_block_share",
            "share of the shortlist kernel's slab blocks under a cursor")
        self._g_live_blocks.set(self.live_block_share)
        # carry lifetime counts already accumulated into the new registry
        self._c_compactions.inc(self.compactions)
        self._c_reclaimed.inc(self.reclaimed)
        self._c_compacted_rows.inc(self.compacted_rows)
        self._c_slab_grows.inc(self.slab_grows)
        self._c_resplits.inc(self.resplits)
        self._c_aged_out.inc(self.aged_out)

    def bind_telemetry(self, telemetry: Telemetry) -> None:
        """Join a shared telemetry plane (the engine binds its primary's
        index so slab-lifecycle instruments export through the plane's
        registry; lifetime counts so far transfer over)."""
        self.obs = telemetry
        self._bind_instruments()

    def __len__(self) -> int:
        return len(self.row_of)

    # ------------------------------------------------------------- plumbing

    def _cell(self, query_batch: int | None = None,
              top_k: int | None = None) -> GusCellConfig:
        cfg = self.cfg
        c_loc = cfg.n_partitions // cfg.n_shards
        npl = min(cfg.nprobe_local or c_loc, c_loc)
        return GusCellConfig(
            name="gus_live", n_rows=cfg.n_partitions * self.slab,
            k_dims=self.k_dims, d_proj=cfg.d_proj, pq_m=cfg.pq_m,
            pq_centers=cfg.pq_centers, n_partitions=cfg.n_partitions,
            slab=self.slab, nprobe_local=npl,
            query_batch=query_batch or cfg.query_batch,
            mutate_batch=cfg.mutate_batch, top_k=top_k or 10,
            reorder=cfg.reorder, merge=cfg.merge,
            soar_lambda=cfg.maintenance.soar if cfg.use_soar else -1.0,
            fused=cfg.fused, pq_int8=cfg.pq_int8)

    def _sketch(self, emb: SparseBatch) -> jax.Array:
        return count_sketch(emb, self.cfg.d_proj, self.cfg.seed)

    def _owners(self, ids: np.ndarray) -> np.ndarray:
        """Hash routing, identical to the device program (same salt)."""
        h = np.asarray(hashing.uhash(self.salt, jnp.asarray(ids, jnp.uint32)))
        return (h % np.uint32(self.cfg.n_shards)).astype(np.int64)

    def _route_partitions(self, sk: np.ndarray, owners: np.ndarray):
        """Mirror of the device assignment (primary + SOAR secondary inside
        the owner shard's local centroid block, via
        ``ann.partition.assign_partitions_local``) — used to encode PQ
        residuals before shipping the batch; placements themselves come
        back from the device as ground truth. Returns ``(p1, p2)``;
        ``p2`` is None with SOAR disabled."""
        cfg = self.cfg
        p1, p2 = part_mod.assign_partitions_local(
            jnp.asarray(sk, jnp.float32),
            jnp.asarray(self._centroids_np, jnp.float32),
            jnp.asarray(owners, jnp.int32),
            c_loc=cfg.n_partitions // cfg.n_shards,
            soar_lambda=cfg.maintenance.soar if cfg.use_soar else -1.0)
        return np.asarray(p1), (np.asarray(p2) if cfg.use_soar else None)

    def _query_step(self, padded: int, k: int):
        key = (padded, k)
        if key not in self._query_steps:
            self._query_steps[key] = jax.jit(make_query_step(
                self.mesh, self._cell(query_batch=padded, top_k=k)))
        return self._query_steps[key]

    # ------------------------------------------------------------- training

    def build(self, ids: np.ndarray, emb: SparseBatch) -> None:
        """(Re)train partitions + codebooks on the corpus, reset the slabs,
        then load every point through the mutation path (paper §4.3)."""
        cfg = self.cfg
        ids = np.asarray(ids)
        n = len(ids)
        sk = np.asarray(self._sketch(emb))
        centroids = part_mod.kmeans(jnp.asarray(sk), cfg.n_partitions,
                                    cfg.kmeans_iters, cfg.eta, cfg.seed)
        self._centroids_np = np.asarray(centroids)
        # residuals w.r.t. the *routed* assignment (owner-local nearest
        # partition) — the geometry the codes will actually live in
        if n:
            p1, _ = self._route_partitions(sk, self._owners(ids))
            residuals = jnp.asarray(sk - self._centroids_np[p1])
        else:
            residuals = jnp.zeros((1, cfg.d_proj), jnp.float32)
        books = pq.train_codebooks(residuals, cfg.pq_m, cfg.pq_centers,
                                   cfg.pq_iters, cfg.eta, cfg.seed)
        # size the ring buffers to the bootstrap corpus (every point lands
        # n_copies times) with slab_headroom slack for churn
        slab = 64
        while slab * cfg.n_partitions < \
                cfg.maintenance.headroom * cfg.n_copies * max(n, 1):
            slab *= 2
        self.slab = max(cfg.slab, slab)
        self._alloc(centroids, books)
        self.trained = True
        self.upsert(ids, emb)

    def _alloc(self, centroids, books) -> None:
        cfg = self.cfg
        c, s = cfg.n_partitions, self.slab
        cell = self._cell()
        specs = index_specs(cell, self.mesh)
        init = {
            "centroids": jnp.asarray(centroids, jnp.float32),
            "books": jnp.asarray(books, jnp.float32),
            "members_idx": jnp.full((c, s, self.k_dims), PAD_INDEX,
                                    jnp.uint32),
            "members_val": jnp.zeros((c, s, self.k_dims), jnp.float32),
            "codes": jnp.zeros((c, s, cfg.pq_m), jnp.uint8),
            "row_ids": jnp.full((c, s), _PAD_ID, jnp.uint32),
            "valid": jnp.zeros((c, s), bool),
            "counts": jnp.zeros((c,), jnp.int32),
        }
        with jax.set_mesh(self.mesh):
            self.state = {k: jax.device_put(
                v, NamedSharding(self.mesh, specs[k]))
                for k, v in init.items()}
        self.row_of = {}
        self.id_of_row = np.full((c * s,), -1, np.int64)
        self._cursor = np.zeros((c,), np.int64)
        self.query_load = np.zeros((c,), np.int64)
        self._query_steps = {}
        self._mutate = jax.jit(make_mutate_step(self.mesh, cell, self.salt))
        self._tombstone = jax.jit(make_delete_step(self.mesh, cell))
        self._compact_step = jax.jit(make_compact_step(self.mesh, cell))
        self._note_cursors()

    def _note_cursors(self) -> None:
        """Refresh ``live_block_share`` after the cursors or the slab
        moved, at the kernel's block width with every partition probed."""
        cfg = self.cfg
        cell = self._cell()
        n = cfg.n_partitions // cfg.n_shards * self.slab
        bn = block_n_for(n, min(cell.reorder or 2 * cell.top_k, n))
        self.live_block_share = blocks_under_cursor(
            self._cursor, self.slab, cfg.n_shards, bn)
        self._g_live_blocks.set(self.live_block_share)

    # ------------------------------------------------------------ mutations

    def upsert(self, ids: np.ndarray, emb: SparseBatch) -> None:
        self.auto_resplit()
        self.finish_upsert(
            self.begin_upsert(ids, emb, self.encode_upsert(ids, emb)))

    @property
    def auto_resplit_on(self) -> bool:
        """Whether the skew re-split policy is armed. The async pipeline
        pins its fuse window to 1 while this holds and calls
        ``auto_resplit`` on the synchronous per-batch schedule."""
        return self.cfg.maintenance.resplit > 0

    def auto_resplit(self) -> int:
        """Policy trigger: re-split when the configured per-shard
        imbalance is exceeded. Runs before a batch's encode — the salt it
        may bump is baked into staged routing, so it must never fire
        between a batch's encode and its append (``serve.pipeline`` calls
        it only at window boundaries, after the previous hand-off)."""
        if self.auto_resplit_on and self.trained:
            return self.resplit(self.cfg.maintenance.resplit)
        return 0

    # Two-phase mutate entry points (serve.pipeline double-buffers these).
    # ``encode_upsert`` reads only build-time structures (centroids, books)
    # so it can run for batch i+1 while batch i's shard_map append is in
    # flight; ``finish_upsert`` materializes the device-reported landing
    # sites into the host id -> rows map. ``upsert`` is the composition.

    def encode_upsert(self, ids: np.ndarray, emb: SparseBatch
                      ) -> dict | None:
        """Stage A: dedup, hash-route owners, sketch, partition routing
        (primary + SOAR secondary), residual PQ codes per copy, padded
        mutate-batch staging (all pure)."""
        assert self.trained, "build() the index before mutating it"
        cfg = self.cfg
        ids = np.asarray(ids, np.int64)
        if ids.size == 0:
            return None
        assert int(ids.max()) < _PAD_ID and int(ids.min()) >= 0, \
            "point ids must fit uint32 (hash routing)"
        # within-batch dedup: last write wins (matches ScannIndex semantics)
        last = {int(pid): i for i, pid in enumerate(ids.tolist())}
        if len(last) < len(ids):
            keep = np.asarray(sorted(last.values()), np.int64)
            ids, emb = ids[keep], emb[keep]

        sk = np.asarray(self._sketch(emb))    # host routing needs the sketch
        p1, p2 = self._route_partitions(sk, self._owners(ids))
        # the PQ codes stay device-side: begin_upsert materializes them
        # after the previous window's in-flight time has hidden the wait
        codes = pq.encode(jnp.asarray(sk - self._centroids_np[p1]),
                          self.state["books"])
        codes2 = None
        if p2 is not None:
            codes2 = pq.encode(jnp.asarray(sk - self._centroids_np[p2]),
                               self.state["books"])

        bm = cfg.mutate_batch
        chunks = []
        for lo in range(0, len(ids), bm):
            sel = slice(lo, min(lo + bm, len(ids)))
            n_c = sel.stop - sel.start
            ids_u = np.full((bm,), _PAD_ID, np.uint32)
            ids_u[:n_c] = ids[sel].astype(np.uint32)
            b_idx = np.full((bm, self.k_dims), PAD_INDEX, np.uint32)
            b_idx[:n_c] = np.asarray(emb.indices[sel])
            b_val = np.zeros((bm, self.k_dims), np.float32)
            b_val[:n_c] = np.asarray(emb.values[sel])
            b_sk = np.zeros((bm, cfg.d_proj), np.float32)
            b_sk[:n_c] = sk[sel]
            chunks.append((n_c, ids[sel].tolist(),
                           (ids_u, b_idx, b_val, b_sk, sel)))
        return {"ids": ids, "codes": codes, "codes2": codes2,
                "parts": p1, "parts2": p2, "chunks": chunks}

    def begin_upsert(self, ids: np.ndarray, emb: SparseBatch,
                     staged: dict | None = None):
        """Stage B dispatch: tombstone overwritten rows, ship the staged
        chunks through the shard_map append (async — landing sites are
        returned as in-flight device arrays).

        This is also where the slab lifecycle runs (the compaction
        boundary): before dispatching a chunk that would wrap a slab,
        already-dispatched landing sites are materialized, the slabs
        compact, and — only if live occupancy alone still would not fit —
        the slab doubles. Compaction never runs anywhere else, so a
        pipeline that closes its fuse window under ``maintenance_pressure``
        keeps the pipelined and synchronous schedules bit-identical."""
        assert self.trained, "build() the index before mutating it"
        if staged is None:
            staged = self.encode_upsert(ids, emb)
        if staged is None:
            return None
        self.delete([pid for pid in staged["ids"].tolist()
                     if pid in self.row_of])
        cfg = self.cfg
        codes = np.asarray(staged["codes"])
        codes2 = None if staged["codes2"] is None \
            else np.asarray(staged["codes2"])
        p1, p2 = staged["parts"], staged["parts2"]
        pending = []
        for n_c, chunk_ids, arrays in staged["chunks"]:
            ids_u, b_idx, b_val, b_sk, sel = arrays
            inc = np.bincount(p1[sel], minlength=cfg.n_partitions)
            if p2 is not None:
                inc += np.bincount(p2[sel], minlength=cfg.n_partitions)
            if cfg.maintenance.compact and np.any(self._cursor + inc > self.slab):
                self._materialize(pending)
                self.compact()
                while np.any(self._live_per_partition() + inc > self.slab):
                    self._grow_slab()
            b_codes = np.zeros((cfg.mutate_batch, cfg.pq_m), np.uint8)
            b_codes[:n_c] = codes[sel]
            b_codes2 = None
            if codes2 is not None:
                b_codes2 = np.zeros((cfg.mutate_batch, cfg.pq_m), np.uint8)
                b_codes2[:n_c] = codes2[sel]
                b_codes2 = jnp.asarray(b_codes2)
            with jax.set_mesh(self.mesh):
                self.state, (r_part, r_pos) = self._mutate(
                    jnp.asarray(ids_u), jnp.asarray(b_idx),
                    jnp.asarray(b_val), jnp.asarray(b_sk),
                    jnp.asarray(b_codes), self.state,
                    new_codes2=b_codes2)
            self._cursor += inc
            pending.append((n_c, chunk_ids, r_part, r_pos, inc))
        self._note_cursors()
        return pending

    def _materialize(self, pending) -> None:
        """Fold device-reported landing sites into the host id -> rows map,
        consuming ``pending`` in place. A ring overwrite (only possible
        with ``maintenance.compact`` off) ages the overwritten id out: its
        surviving copies are tombstoned so no stale slot can serve."""
        if not pending:
            return
        stale: list[int] = []
        while pending:
            n_c, chunk_ids, r_part, r_pos, host_inc = pending.pop(0)
            r_part = np.asarray(r_part)[:n_c]
            r_pos = np.asarray(r_pos)[:n_c]
            # the landing sites are the device truth: resync the cursor
            # mirror in case the host routing mirror disagreed by a float
            # ulp (placement stays exact either way; the mirror is only
            # the wrap-risk heuristic, but keep it in lockstep)
            dev_inc = np.bincount(r_part.reshape(-1),
                                  minlength=self.cfg.n_partitions)
            self._cursor += dev_inc - host_inc
            rows = r_part * self.slab + r_pos          # [n_c, n_copies]
            for pid, rowvec in zip(chunk_ids, rows.tolist()):
                for row in rowvec:
                    old = int(self.id_of_row[row])
                    if old >= 0 and old != pid:
                        self.aged_out += 1             # ring buffer wrapped
                        self._c_aged_out.inc()
                        for other in self.row_of.pop(old, ()):
                            if other != row:
                                self.id_of_row[other] = -1
                                stale.append(other)
                for row in rowvec:
                    self.id_of_row[row] = pid
                self.row_of[pid] = tuple(rowvec)
        # only slots that were not re-assigned by a later chunk need the
        # device-side tombstone
        stale = [r for r in set(stale) if self.id_of_row[r] < 0]
        if stale:
            self._tombstone_rows(stale)
        self._note_cursors()

    def finish_upsert(self, pending) -> None:
        """Barrier: materialize landing sites, mirror them into the host
        id -> rows map (needed by deletes and result translation)."""
        if pending is None:
            return
        self._materialize(pending)
        jax.block_until_ready(self.state)

    def _tombstone_rows(self, rows: list) -> None:
        """Clear validity at global rows (chunked tombstone dispatches)."""
        bm = self.cfg.mutate_batch
        for lo in range(0, len(rows), bm):
            chunk = rows[lo:lo + bm]
            parts = np.full((bm,), -1, np.int32)
            poss = np.zeros((bm,), np.int32)
            parts[:len(chunk)] = np.asarray(chunk, np.int64) // self.slab
            poss[:len(chunk)] = np.asarray(chunk, np.int64) % self.slab
            with jax.set_mesh(self.mesh):
                self.state = self._tombstone(
                    jnp.asarray(parts), jnp.asarray(poss), self.state)

    def delete(self, ids) -> int:
        assert self.trained, "build() the index before mutating it"
        rows = []
        n_del = 0
        for pid in list(ids):
            rowvec = self.row_of.pop(int(pid), None)
            if rowvec is None:
                continue
            n_del += 1
            for row in rowvec:
                rows.append(row)
                self.id_of_row[row] = -1
        if rows:
            self._tombstone_rows(rows)
        return n_del

    # ------------------------------------------------------ slab lifecycle

    def _live_per_partition(self) -> np.ndarray:
        """Live copies per partition, from the host id -> rows map."""
        c = self.cfg.n_partitions
        if not self.row_of:
            return np.zeros((c,), np.int64)
        rows = np.fromiter((r for t in self.row_of.values() for r in t),
                           np.int64)
        return np.bincount(rows // self.slab, minlength=c)

    def compact(self) -> dict:
        """Squeeze tombstoned / superseded slots out of every slab.

        Live rows keep their relative order (the compact program is
        stable), so unchanged queries return bit-identical results; the
        ring cursors restart at the live counts and the host id -> rows
        map is remapped from the device-reported slot map. Callers driving
        the async write path must flush it first — compaction moves slots,
        and in-flight landing sites name the old layout (``begin_upsert``'s
        auto-trigger materializes its own pending sites before compacting).
        """
        assert self.trained, "build() the index before compacting it"
        t0 = time.perf_counter()
        with jax.set_mesh(self.mesh):
            new_state, new_pos = self._compact_step(self.state)
        new_pos = np.asarray(new_pos)
        occupied = int(np.minimum(self._cursor, self.slab).sum())
        s = self.slab
        new_id_of_row = np.full_like(self.id_of_row, -1)
        if self.row_of:
            # vectorized remap: n_copies is uniform across the index, so
            # the id -> rows map flattens to one [points, copies] gather
            pids = np.fromiter(self.row_of.keys(), np.int64,
                               len(self.row_of))
            old_rows = np.asarray(list(self.row_of.values()), np.int64)
            parts, poss = np.divmod(old_rows, s)
            new_rows = parts * s + new_pos[parts, poss]
            new_row_of = {int(p): tuple(r) for p, r in
                          zip(pids.tolist(), new_rows.tolist())}
            new_id_of_row[new_rows.reshape(-1)] = np.repeat(
                pids, new_rows.shape[1])
            live = np.bincount(new_rows.reshape(-1) // s,
                               minlength=self.cfg.n_partitions)
        else:
            new_row_of = {}
            live = np.zeros((self.cfg.n_partitions,), np.int64)
        # the successor version is fully built — swap every piece at once
        # (a published IndexVersion captured before this point stays
        # self-consistent; nothing half-built is ever observable)
        self.state = new_state
        self.row_of = new_row_of
        self.id_of_row = new_id_of_row
        self._cursor = live.astype(np.int64)
        self.version += 1
        self._note_cursors()
        n_live = int(live.sum())
        reclaimed = max(occupied - n_live, 0)
        dt = time.perf_counter() - t0
        self.compactions += 1
        self.compacted_rows += n_live
        self.reclaimed += reclaimed
        self.compact_s += dt
        self._c_compactions.inc()
        self._c_compacted_rows.inc(n_live)
        self._c_reclaimed.inc(reclaimed)
        self._h_compact.observe(dt * 1e3)
        self.obs.events.emit("compaction", live_rows=n_live,
                             reclaimed=reclaimed)
        return {"live_rows": n_live, "reclaimed": reclaimed}

    def _grow_slab(self) -> None:
        """Double every partition's slab (device realloc + host row remap).

        Only reached from ``begin_upsert`` right after a compaction, when
        live occupancy alone would overflow a slab: positions within a
        partition are preserved, so cursors (== live counts) stay valid."""
        assert int(self._cursor.max()) <= self.slab
        cfg = self.cfg
        c, old_s = cfg.n_partitions, self.slab
        st = dict(self.state)
        pads = {
            "members_idx": np.full((c, old_s, self.k_dims), PAD_INDEX,
                                   np.uint32),
            "members_val": np.zeros((c, old_s, self.k_dims), np.float32),
            "codes": np.zeros((c, old_s, cfg.pq_m), np.uint8),
            "row_ids": np.full((c, old_s), _PAD_ID, np.uint32),
            "valid": np.zeros((c, old_s), bool),
        }
        self.slab = old_s * 2
        cell = self._cell()
        specs = index_specs(cell, self.mesh)
        with jax.set_mesh(self.mesh):
            for key, pad in pads.items():
                st[key] = jax.device_put(
                    np.concatenate([np.asarray(st[key]), pad], axis=1),
                    NamedSharding(self.mesh, specs[key]))
        new_id_of_row = np.full((c * self.slab,), -1, np.int64)
        new_row_of = {}
        for pid, rowvec in self.row_of.items():
            moved = tuple((r // old_s) * self.slab + (r % old_s)
                          for r in rowvec)
            new_row_of[pid] = moved
            for row in moved:
                new_id_of_row[row] = pid
        self.state = st
        self.row_of = new_row_of
        self.id_of_row = new_id_of_row
        self.version += 1
        self._query_steps = {}
        self._mutate = jax.jit(make_mutate_step(self.mesh, cell, self.salt))
        self._tombstone = jax.jit(make_delete_step(self.mesh, cell))
        self._compact_step = jax.jit(make_compact_step(self.mesh, cell))
        self._note_cursors()
        self.slab_grows += 1
        self._c_slab_grows.inc()
        self.obs.events.emit("slab_grow", slab=int(self.slab))

    def resplit(self, imbalance: float | None = None,
                by: str | None = None) -> int:
        """Skew re-split: re-hash the hottest shard's rows across the mesh.

        When per-shard skew (``max / mean``) exceeds ``imbalance``
        (default ``maintenance.resplit`` or 2.0), the hottest shard's
        rows are read back from the slabs, the owner-hash salt is bumped
        (re-jitting the mutate program — the salt is a compile-time
        constant), and the rows re-insert through the ordinary
        route/mutate machinery, spreading across every shard. Queries
        never consult the owner hash, so rows placed under old salts
        remain exactly servable. Returns the number of points moved.

        ``by`` picks the skew metric (default ``maintenance.resplit_metric``):
        ``"occupancy"`` watches live rows per shard; ``"load"`` watches
        queries served per shard since the last load-driven re-split —
        a shard can be occupancy-balanced yet serve most of the read
        traffic, and only the load metric moves its rows. A load-driven
        move resets the counters (a fresh observation window over the
        new placement). Like ``compact()``, callers on the async write
        path must flush it first (the engine does)."""
        assert self.trained, "build() the index before re-splitting it"
        cfg = self.cfg
        by = by if by is not None else cfg.maintenance.resplit_metric
        if by not in ("occupancy", "load"):
            raise ValueError(f"resplit by={by!r} must be 'occupancy' or "
                             "'load'")
        if self._in_maintenance:           # the re-insert upserts recurse
            return 0
        if cfg.n_shards < 2 or not self.row_of:
            return 0
        fac = imbalance if imbalance is not None \
            else (cfg.maintenance.resplit or 2.0)
        c_loc = cfg.n_partitions // cfg.n_shards
        metric = (self.query_load if by == "load"
                  else self._live_per_partition())
        shard_metric = np.asarray(metric).reshape(
            cfg.n_shards, c_loc).sum(axis=1)
        mean = float(shard_metric.mean())
        if mean <= 0 or shard_metric.max() <= fac * mean:
            return 0
        hot = int(shard_metric.argmax())
        move = [pid for pid, rowvec in self.row_of.items()
                if rowvec[0] // self.slab // c_loc == hot]
        if not move:
            return 0
        self._in_maintenance = True
        try:
            moved = self._resplit_move(move)
        finally:
            self._in_maintenance = False
        if by == "load" and moved:
            self.query_load[:] = 0
        return moved

    def _resplit_move(self, move: list) -> int:
        # the slabs hold the padded sparse rows — read the hot shard's
        # points back without any feature-store round trip
        rows0 = np.asarray([self.row_of[pid][0] for pid in move], np.int64)
        m_idx = np.asarray(self.state["members_idx"]) \
            .reshape(-1, self.k_dims)[rows0]
        m_val = np.asarray(self.state["members_val"]) \
            .reshape(-1, self.k_dims)[rows0]
        emb = SparseBatch(jnp.asarray(m_idx), jnp.asarray(m_val))
        self.salt += 1
        self._mutate = jax.jit(
            make_mutate_step(self.mesh, self._cell(), self.salt))
        self.delete(move)
        self.upsert(np.asarray(move, np.int64), emb)
        self.resplits += 1
        self.version += 1
        self._c_resplits.inc()
        self._c_moved_points.inc(len(move))
        self.obs.events.emit("resplit", moved=len(move), salt=self.salt)
        return len(move)

    def maintenance_pressure(self, n_rows: int) -> bool:
        """True when appending ``n_rows`` more points could wrap a slab,
        i.e. a compaction / slab grow may trigger inside the next
        ``begin_upsert``. ``serve.pipeline`` closes its fuse window while
        this holds, so the pipelined schedule degenerates to the
        synchronous per-batch schedule exactly when slot movement is
        possible (the compaction-boundary rule)."""
        if not self.trained or not self.cfg.maintenance.compact:
            return False
        return bool(int(self._cursor.max())
                    + n_rows * self.cfg.n_copies > self.slab)

    # ------------------------------------------------- versioned publishing

    def publish(self, seq: int = -1) -> IndexVersion:
        """Publish the current slabs as an immutable `IndexVersion`.

        Device arrays are captured by reference (free), the host
        ``id_of_row`` by copy; installing the version is one reference
        assignment, so it can never be observed half-built. The
        maintenance worker publishes after every off-path lifecycle step
        (``snapshot_swap`` events carry the version)."""
        self.version += 1
        self._published = IndexVersion(
            version=self.version, seq=seq, state=self.state,
            id_of_row=(self.id_of_row.copy()
                       if self.id_of_row is not None else None),
            salt=self.salt, slab=int(self.slab), points=len(self.row_of))
        return self._published

    def published(self) -> IndexVersion | None:
        """The latest published version (None before the first publish)."""
        return self._published

    # --------------------------------------------------------- persistence

    def snapshot_state(self) -> dict:
        """The host-side state the engine persists (`SnapshotStateful`).

        The slabs themselves rebuild from the feature store on recovery;
        what must survive is the owner-hash salt — mixed-salt placements
        re-route identically only if recovery bumps to the same salt."""
        return {"salt": self.salt}

    def restore_state(self, state: dict) -> None:
        salt = state.get("salt")
        if salt is not None and salt != self.salt:
            self.salt = int(salt)
            if self.trained:
                self._mutate = jax.jit(
                    make_mutate_step(self.mesh, self._cell(), self.salt))

    def occupancy(self) -> dict:
        """Slab / shard occupancy and lifecycle counters (engine stats)."""
        cfg = self.cfg
        live = self._live_per_partition()
        c_loc = cfg.n_partitions // cfg.n_shards
        shard_live = live.reshape(cfg.n_shards, c_loc).sum(axis=1)
        mean = float(shard_live.mean())
        shard_load = self.query_load.reshape(cfg.n_shards, c_loc).sum(axis=1)
        load_mean = float(shard_load.mean())
        return {
            "points": len(self.row_of),
            "live_rows": int(live.sum()),
            "slots": int(cfg.n_partitions * self.slab),
            "slab": int(self.slab),
            "cursor_max": int(self._cursor.max()),
            "partition_max": int(live.max()),
            "shard_live": shard_live.tolist(),
            "shard_imbalance": float(shard_live.max() / mean)
            if mean > 0 else 1.0,
            "shard_load": shard_load.tolist(),
            "load_imbalance": float(shard_load.max() / load_mean)
            if load_mean > 0 else 1.0,
            "soar": cfg.use_soar,
            "salt": self.salt,
            "compactions": self.compactions,
            "reclaimed_slots": self.reclaimed,
            "slab_grows": self.slab_grows,
            "resplits": self.resplits,
            "aged_out": self.aged_out,
            "live_block_share": self.live_block_share,
            "version": self.version,
        }

    describe = occupancy

    def stats(self) -> dict:  # legacy-ok
        """Deprecated alias of ``occupancy()`` / ``describe()``."""
        warnings.warn("ShardedGusIndex.stats() is deprecated; use "
                      "occupancy()/describe() or the Telemetry views",
                      DeprecationWarning, stacklevel=2)
        return self.occupancy()

    # ------------------------------------------------------------- queries

    def search(self, emb: SparseBatch, k: int):
        """Top-k (ids [B,k], dists [B,k]); padding id=-1, dist=+inf."""
        assert self.trained, "build() the index before searching it"
        t_search = time.perf_counter()
        with self.obs.tracer.span("shard_search", batch=emb.batch, k=k):
            out = self._search(emb, k)
        self._h_search.observe((time.perf_counter() - t_search) * 1e3)
        return out

    def _search(self, emb: SparseBatch, k: int):
        cfg = self.cfg
        b = emb.batch
        cell = self._cell()
        r = min(cell.reorder or 2 * k, cell.nprobe_local * self.slab)
        k_eff = min(k, r)
        out_ids = np.full((b, k), -1, np.int64)
        out_d = np.full((b, k), np.inf, np.float32)
        tracer = self.obs.tracer
        with tracer.span("sketch"):
            sk = self._sketch(emb)
            with tracer.span("device_wait"):
                sk = np.asarray(sk)
        step_b = pow2_pad(b, cfg.query_batch)
        for lo in range(0, b, step_b):
            sel = slice(lo, min(lo + step_b, b))
            n_c = sel.stop - sel.start
            padded = pow2_pad(n_c)
            q_idx = np.full((padded, self.k_dims), PAD_INDEX, np.uint32)
            q_idx[:n_c] = np.asarray(emb.indices[sel])
            q_val = np.zeros((padded, self.k_dims), np.float32)
            q_val[:n_c] = np.asarray(emb.values[sel])
            q_sk = np.zeros((padded, cfg.d_proj), np.float32)
            q_sk[:n_c] = sk[sel]
            step = self._query_step(padded, k_eff)
            with jax.set_mesh(self.mesh):
                rows, dists = step(jnp.asarray(q_idx), jnp.asarray(q_val),
                                   jnp.asarray(q_sk), self.state)
            with tracer.span("device_wait"):
                rows = np.asarray(rows)[:n_c]
                dists = np.asarray(dists)[:n_c]
            hit = np.isfinite(dists)
            if hit.any():
                # per-partition read-traffic counters: every returned
                # candidate charges the partition it was served from
                # (the "load" re-split metric)
                self.query_load += np.bincount(
                    (rows[hit] // self.slab).astype(np.int64),
                    minlength=cfg.n_partitions)
            ids_c = np.where(hit, self.id_of_row[np.where(hit, rows, 0)], -1)
            out_ids[sel, :k_eff] = ids_c
            out_d[sel, :k_eff] = np.where(hit, dists, np.inf)
        return out_ids, out_d
