"""Distributed GUS index: shard_map programs for the production mesh.

This is the paper's serving pattern mapped onto a TPU pod (DESIGN.md §5):
the index tower is sharded over every chip; queries are replicated in,
answered by a scatter/merge dataflow with static shapes end-to-end:

  query step   — each shard owns n_partitions/shards partitions (centroids
                 sharded too). Per shard: centroid matmul over local
                 partitions -> local top-nprobe -> fused shortlist
                 (``kernels.ops.pq_score_dedup_topk``: PQ LUT scores over
                 the probed slabs, SOAR dedup by point id in-register,
                 top-r — one pallas_call on TPU, its bitwise XLA twin on
                 CPU) -> exact sparse rescore of the local shortlist ->
                 local top-k. Then one all_gather of k-per-shard
                 candidates and a final merge top-k. No all-to-all, no
                 data-dependent gathers across chips. With SOAR enabled
                 the shortlist carries each slot's point id (``row_ids``)
                 and the lower-ranked duplicate copy is neutralised at the
                 shortlist cut (dedup-after-cut; see kernels/fused_query.py
                 for the tie-break contract) — the two-copy dedup
                 discipline of ``ann/scann.py``. ``fused=False`` composes
                 the same stages from individual ops, bitwise-identical.

  mutate step  — mutation batch replicated in; each shard keeps the rows it
                 owns (hash routing over a ``salt`` — bump the salt and
                 re-insert to re-balance owners, see ShardedGusIndex
                 ``resplit``), appends them ring-buffer style into its
                 slabs. With ``soar_lambda >= 0`` each row is appended to
                 its primary partition *and* a SOAR secondary (Sun et al.
                 2024) chosen inside the same shard — write amplification
                 stays local. Copies append in per-row interleaved order
                 (row0 primary, row0 secondary, row1 primary, ...) so the
                 slab layout is a pure function of the row sequence — the
                 invariant the fused-window write path relies on. The step
                 also returns each row's landing sites (global partition,
                 slot) per copy — replicated via psum — so a host-side
                 engine can maintain the id -> rows map that deletes and
                 result translation need.

  delete step  — tombstones: (global partition, slot) pairs replicated in;
                 each shard clears the validity bits of the slots it owns.

  compact step — per-shard slab squeeze: tombstoned / superseded slots are
                 dropped and live rows slide to the front of their slab in
                 stable order; the ring cursor resets to the live count.
                 Returns the old-slot -> new-slot map (sharded out, so the
                 reassembled global array is the device truth) with which
                 the host keeps its id -> rows map exact. Stability makes
                 post-compaction queries bit-identical: every top-k /
                 shortlist tie in the query step breaks by candidate
                 order, and compaction preserves the relative order of all
                 live slots.

These are the programs the dry-run lowers for the GUS cells, and the very
same functions serve live traffic on a small CPU mesh through
``repro.ann.sharded_index.ShardedGusIndex`` (tests/test_sharded.py,
tests/test_sharded_lifecycle.py, tests/test_dynamic_equivalence.py).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.ann.partition import soar_cost
from repro.core import hashing
from repro.core.types import PAD_INDEX
from repro.kernels import ops as kops


@dataclasses.dataclass(frozen=True)
class GusCellConfig:
    """Shapes of one sharded-GUS dry-run cell."""
    name: str = "gus_serve_100m"
    n_rows: int = 1 << 27          # 134M points globally
    k_dims: int = 16               # nnz per sparse embedding
    d_proj: int = 128              # sketch dim
    pq_m: int = 16                 # PQ subspaces
    pq_centers: int = 256
    n_partitions: int = 4096       # global partitions (sharded w/ slabs)
    slab: int = 8192               # rows per partition slab
    nprobe_local: int = 2          # partitions probed per shard
    query_batch: int = 4096
    mutate_batch: int = 65536
    top_k: int = 100
    reorder: int = 0               # per-shard exact-rescore shortlist
    #                                (0 = the historical default, 2*top_k)
    # candidate-merge schedule: "flat" (paper-faithful single all_gather of
    # k-per-shard over every chip) or "hier" (two-stage: intra-"model"
    # gather + top-k, then cross-"data"/"pod" — the §Perf C optimization)
    merge: str = "flat"
    # SOAR secondary-copy weight (Sun et al. 2024); < 0 = single copy.
    # When enabled the mutate step writes two copies per row and the query
    # step dedups shortlists by point id at the shortlist cut.
    soar_lambda: float = -1.0
    # fused shortlist op (PQ-score -> dedup -> top-r in one kernel); False
    # composes the same stages from individual ops, bitwise-identical
    fused: bool = True
    # score the shortlist from a symmetric int8-quantised LUT
    pq_int8: bool = False

    @property
    def use_soar(self) -> bool:
        return self.soar_lambda >= 0

    @property
    def n_copies(self) -> int:
        return 2 if self.use_soar else 1


# reserved id that no shard ever owns: mutation batches are padded with it
PAD_ID = jnp.uint32(0xFFFFFFFF)


def _flat_axes(mesh) -> tuple:
    return tuple(mesh.axis_names)


def _linear_shard_id(mesh) -> jax.Array:
    """This device's linearized position in the (possibly nD) mesh."""
    shard_id = jnp.int32(0)
    for name in mesh.axis_names:
        shard_id = shard_id * mesh.devices.shape[
            list(mesh.axis_names).index(name)] + jax.lax.axis_index(name)
    return shard_id


def index_specs(cell: GusCellConfig, mesh):
    """PartitionSpecs of the sharded index state."""
    ax = _flat_axes(mesh)
    return {
        "centroids": P(ax, None),           # [C, d_proj] partitions sharded
        "books": P(),                        # [M, 256, ds] replicated
        "members_idx": P(ax, None, None),    # [C, S, K] sparse rows by slab
        "members_val": P(ax, None, None),
        "codes": P(ax, None, None),          # [C, S, M] u8
        "row_ids": P(ax, None),              # [C, S] point id per slot
        "valid": P(ax, None),                # [C, S]
        "counts": P(ax),                     # [C] ring-buffer cursors
    }


def index_shapes(cell: GusCellConfig):
    c, s = cell.n_partitions, cell.slab
    return {
        "centroids": jax.ShapeDtypeStruct((c, cell.d_proj), jnp.float32),
        "books": jax.ShapeDtypeStruct(
            (cell.pq_m, cell.pq_centers, cell.d_proj // cell.pq_m),
            jnp.float32),
        "members_idx": jax.ShapeDtypeStruct((c, s, cell.k_dims), jnp.uint32),
        "members_val": jax.ShapeDtypeStruct((c, s, cell.k_dims), jnp.float32),
        "codes": jax.ShapeDtypeStruct((c, s, cell.pq_m), jnp.uint8),
        "row_ids": jax.ShapeDtypeStruct((c, s), jnp.uint32),
        "valid": jax.ShapeDtypeStruct((c, s), jnp.bool_),
        "counts": jax.ShapeDtypeStruct((c,), jnp.int32),
    }


def query_shapes(cell: GusCellConfig):
    b = cell.query_batch
    return (jax.ShapeDtypeStruct((b, cell.k_dims), jnp.uint32),
            jax.ShapeDtypeStruct((b, cell.k_dims), jnp.float32),
            jax.ShapeDtypeStruct((b, cell.d_proj), jnp.float32))


def make_query_step(mesh, cell: GusCellConfig):
    ax = _flat_axes(mesh)
    n_shards = 1
    for n in mesh.devices.shape:
        n_shards *= n
    ispec = index_specs(cell, mesh)

    def local_query(q_idx, q_val, q_sketch, centroids, books,
                    m_idx, m_val, codes, row_ids, valid, counts):
        # shapes here are per-shard: centroids [C/shards, d] etc.
        b = q_idx.shape[0]
        s = m_idx.shape[1]
        m = books.shape[0]
        # 1) local partition selection
        pscores = q_sketch @ centroids.T                       # [B, C_loc]
        top_ps, top_parts = jax.lax.top_k(pscores, cell.nprobe_local)
        # 2+3) fused shortlist: PQ LUT scores over the probed slabs, SOAR
        # dedup by point id (both copies of a point live on its owner
        # shard, so the in-register duplicate mask is complete), top-r —
        # one op; the lower-ranked duplicate copy comes back as -inf and
        # drops out of the rescore below
        q_sub = q_sketch.reshape(b, m, -1)
        lut = jnp.einsum("bmd,mcd->bmc", q_sub, books)         # [B, M, 256]
        cand_codes = codes[top_parts]                          # [B, np, S, M]
        cand_valid = valid[top_parts]
        cand_ids = row_ids[top_parts]                          # [B, np, S]

        flat_codes = cand_codes.reshape(b, -1, m)
        flat_valid = cand_valid.reshape(b, -1)
        flat_ids = cand_ids.reshape(b, -1)
        bias = jnp.repeat(top_ps, s, axis=-1)
        r = min(cell.reorder if cell.reorder > 0 else cell.top_k * 2,
                flat_valid.shape[-1])
        if cell.fused:
            short_vals, short = kops.pq_score_dedup_topk(
                lut, flat_codes, flat_ids, r, valid=flat_valid, bias=bias,
                quantized=cell.pq_int8)
        else:
            approx = kops.pq_scores(lut, flat_codes, quantized=cell.pq_int8)
            approx = jnp.where(flat_valid, approx + bias, -jnp.inf)
            short_vals, short = jax.lax.top_k(approx, r)       # [B, r]
            short_vals = kops.dedup_mask(short_vals, short,
                                         flat_ids.astype(jnp.int32),
                                         flat_valid)
        np_s = cell.nprobe_local
        part_of = jnp.take_along_axis(
            jnp.repeat(top_parts, s, axis=-1), short, axis=-1)
        pos_of = jnp.take_along_axis(
            jnp.tile(jnp.arange(s), (b, np_s)), short, axis=-1)
        # 4) exact sparse rescore of the deduped shortlist
        rows_idx = m_idx[part_of, pos_of]                      # [B, r, K]
        rows_val = m_val[part_of, pos_of]
        eq = (q_idx[:, None, :, None] == rows_idx[:, :, None, :]) \
            & (q_idx[:, None, :, None] != PAD_INDEX)
        prod = q_val[:, None, :, None] * rows_val[:, :, None, :]
        exact = jnp.sum(jnp.where(eq, prod, 0.0), axis=(2, 3))  # [B, r]
        exact = jnp.where(jnp.isfinite(short_vals), exact, -jnp.inf)
        k = min(cell.top_k, r)
        loc_scores, loc_pos = jax.lax.top_k(exact, k)
        # globalize candidate ids: (shard, partition, pos) -> flat row id
        shard_id = _linear_shard_id(mesh)
        loc_part = jnp.take_along_axis(part_of, loc_pos, axis=-1)
        loc_slot = jnp.take_along_axis(pos_of, loc_pos, axis=-1)
        c_loc = centroids.shape[0]
        global_row = ((shard_id * c_loc + loc_part) * s + loc_slot)
        # 4) merge each shard's local top-k into the global top-k
        if cell.merge == "hier" and len(ax) > 1:
            # stage 1: within the "model" row (16 shards) — gathers are
            # 16x smaller than the flat 256-shard gather, and the top-k
            # after stage 1 shrinks stage 2's operands by another 16x.
            s1 = jax.lax.all_gather(loc_scores, "model", axis=1, tiled=True)
            r1 = jax.lax.all_gather(global_row, "model", axis=1, tiled=True)
            v1, p1 = jax.lax.top_k(s1, cell.top_k)
            rows1 = jnp.take_along_axis(r1, p1, axis=-1)
            rest = tuple(a for a in ax if a != "model")
            s2 = jax.lax.all_gather(v1, rest, axis=1, tiled=True)
            r2 = jax.lax.all_gather(rows1, rest, axis=1, tiled=True)
            fin_scores, fin_pos = jax.lax.top_k(s2, cell.top_k)
            fin_rows = jnp.take_along_axis(r2, fin_pos, axis=-1)
        else:
            all_scores = jax.lax.all_gather(loc_scores, ax, axis=1,
                                            tiled=True)
            all_rows = jax.lax.all_gather(global_row, ax, axis=1, tiled=True)
            fin_scores, fin_pos = jax.lax.top_k(all_scores, cell.top_k)
            fin_rows = jnp.take_along_axis(all_rows, fin_pos, axis=-1)
        return fin_rows, -fin_scores                          # ids, distances

    fn = jax.shard_map(
        local_query, mesh=mesh,
        in_specs=(P(), P(), P(),
                  ispec["centroids"], ispec["books"], ispec["members_idx"],
                  ispec["members_val"], ispec["codes"], ispec["row_ids"],
                  ispec["valid"], ispec["counts"]),
        out_specs=(P(), P()),
        check_vma=False)

    def step(q_idx, q_val, q_sketch, state):
        return fn(q_idx, q_val, q_sketch, state["centroids"], state["books"],
                  state["members_idx"], state["members_val"], state["codes"],
                  state["row_ids"], state["valid"], state["counts"])

    return step


def make_mutate_step(mesh, cell: GusCellConfig, salt: int = 3):
    """Batched upsert: rows hash-route to one shard; each shard appends its
    rows into the nearest local partition's slab (ring-buffer cursor), and
    — with SOAR enabled — into a secondary local partition whose residual
    is as orthogonal as possible to the primary residual.

    Copies append in per-row interleaved order (primary then secondary per
    row, rows in batch order), which keeps the slab layout a pure function
    of the row sequence: fusing consecutive batches into one call lands
    every copy in exactly the slot per-batch calls would have used.

    Besides the updated index state, the step returns each row's landing
    sites ``(global partition, slot)`` per copy, shaped ``[B, n_copies]``
    (replicated across shards via psum; ``(-1, 0)`` for ``PAD_ID`` padding
    rows) so the serving engine can keep its host-side id -> rows map in
    lockstep with the device truth. ``salt`` seeds the owner hash and is a
    *compile-time* constant: bumping it (``ShardedGusIndex.resplit``)
    re-jits the step and re-routes subsequent inserts.
    """
    ax = _flat_axes(mesh)
    n_shards = 1
    for n in mesh.devices.shape:
        n_shards *= n
    ispec = index_specs(cell, mesh)

    def local_mutate(ids, new_idx, new_val, new_sketch, new_codes,
                     new_codes2, centroids, m_idx, m_val, codes, row_ids,
                     valid, counts):
        b = ids.shape[0]
        shard_id = _linear_shard_id(mesh)
        owner = (hashing.uhash(salt, ids)
                 % jnp.uint32(n_shards)).astype(jnp.int32)
        mine = (owner == shard_id) & (ids != PAD_ID)
        # nearest local partition for every row (masked rows write nowhere)
        d2 = (jnp.sum(new_sketch ** 2, -1)[:, None]
              - 2.0 * new_sketch @ centroids.T
              + jnp.sum(centroids ** 2, -1)[None, :])
        p1 = jnp.argmin(d2, axis=-1)                          # [Bm]
        if cell.use_soar:
            # SOAR secondary on the shard's local centroid block — the
            # cost formula is shared with the host mirror
            # (ann/partition.py::soar_cost) so the two can never drift
            cost2 = soar_cost(new_sketch, centroids, d2, p1,
                              cell.soar_lambda)
            cost2 = cost2.at[jnp.arange(b), p1].set(jnp.inf)
            p2 = jnp.argmin(cost2, axis=-1)
            part = jnp.stack([p1, p2], axis=1).reshape(-1)    # interleaved
            put_idx = jnp.repeat(new_idx, 2, axis=0)
            put_val = jnp.repeat(new_val, 2, axis=0)
            put_codes = jnp.stack([new_codes, new_codes2],
                                  axis=1).reshape(-1, new_codes.shape[1])
            put_ids = jnp.repeat(ids, 2)
            put_mine = jnp.repeat(mine, 2)
        else:
            part, put_idx, put_val, put_codes = p1, new_idx, new_val, \
                new_codes
            put_ids, put_mine = ids, mine
        # ring-buffer position: cursor[part] + my running count within part
        onehot = jax.nn.one_hot(part, centroids.shape[0],
                                dtype=jnp.int32) * put_mine[:, None]
        within = jnp.cumsum(onehot, axis=0) - onehot          # prior count
        pos = (counts[part] + jnp.sum(within * onehot, axis=-1)) \
            % m_idx.shape[1]
        row = jnp.where(put_mine, part, centroids.shape[0])   # OOB drops
        m_idx = m_idx.at[row, pos].set(put_idx, mode="drop")
        m_val = m_val.at[row, pos].set(put_val, mode="drop")
        codes = codes.at[row, pos].set(put_codes, mode="drop")
        row_ids = row_ids.at[row, pos].set(put_ids, mode="drop")
        valid = valid.at[row, pos].set(True, mode="drop")
        counts = counts + jnp.sum(onehot, axis=0)
        # landing sites, replicated out: exactly one shard owns each row,
        # so the psum reconstructs (part, pos) on every shard.
        part_global = shard_id * centroids.shape[0] + part
        route_part = jax.lax.psum(
            jnp.where(put_mine, part_global + 1, 0), ax) - 1
        route_pos = jax.lax.psum(
            jnp.where(put_mine, pos, 0).astype(jnp.int32), ax)
        nc = cell.n_copies
        return (m_idx, m_val, codes, row_ids, valid, counts,
                route_part.reshape(b, nc), route_pos.reshape(b, nc))

    fn = jax.shard_map(
        local_mutate, mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(), P(),
                  ispec["centroids"], ispec["members_idx"],
                  ispec["members_val"], ispec["codes"], ispec["row_ids"],
                  ispec["valid"], ispec["counts"]),
        out_specs=(ispec["members_idx"], ispec["members_val"], ispec["codes"],
                   ispec["row_ids"], ispec["valid"], ispec["counts"],
                   P(), P()),
        check_vma=False)

    def step(ids, new_idx, new_val, new_sketch, new_codes, state,
             new_codes2=None):
        if new_codes2 is None:
            new_codes2 = new_codes            # single-copy: slot unused
        m_idx, m_val, codes, row_ids, valid, counts, r_part, r_pos = fn(
            ids, new_idx, new_val, new_sketch, new_codes, new_codes2,
            state["centroids"], state["members_idx"], state["members_val"],
            state["codes"], state["row_ids"], state["valid"],
            state["counts"])
        return ({**state, "members_idx": m_idx, "members_val": m_val,
                 "codes": codes, "row_ids": row_ids, "valid": valid,
                 "counts": counts},
                (r_part, r_pos))

    return step


def make_delete_step(mesh, cell: GusCellConfig):
    """Tombstone step: clear validity at (global partition, slot) pairs.

    Deletes are host-routed — the engine knows each id's landing sites from
    the mutate step's returned routes — so the program is a pure masked
    scatter: each shard clears the slots that fall in its partition range,
    everything else drops. Pairs with ``part == -1`` (padding) are ignored.
    Tombstoned slots keep their stale payload until the compact step
    squeezes them out (the validity mask excludes them from every query).
    """
    ispec = index_specs(cell, mesh)

    def local_clear(parts, poss, valid):
        shard_id = _linear_shard_id(mesh)
        c_loc = valid.shape[0]
        local = parts - shard_id * c_loc
        ok = (parts >= 0) & (local >= 0) & (local < c_loc)
        row = jnp.where(ok, local, c_loc)                     # OOB drops
        return valid.at[row, poss].set(False, mode="drop")

    fn = jax.shard_map(
        local_clear, mesh=mesh,
        in_specs=(P(), P(), ispec["valid"]),
        out_specs=ispec["valid"],
        check_vma=False)

    def step(parts, poss, state):
        return {**state, "valid": fn(parts, poss, state["valid"])}

    return step


def make_compact_step(mesh, cell: GusCellConfig):
    """Slab compaction: squeeze tombstoned / superseded slots out, in place.

    Per shard, per local partition: live rows slide to the front of the
    slab in **stable order** (relative order of live slots is preserved —
    that is what keeps post-compaction queries bit-identical, every tie in
    the query step breaks by candidate order); dead tails are reset to
    padding; the ring cursor restarts at the live count, so subsequent
    appends land right after the compacted region.

    Returns, alongside the updated state, the old-slot -> new-slot map
    ``new_pos`` (i32 [C, S], −1 at dead slots; sharded out like ``valid``,
    so the reassembled global array is the device truth) — the host uses
    it to remap its id -> rows map without re-deriving anything.
    """
    ispec = index_specs(cell, mesh)

    def local_compact(m_idx, m_val, codes, row_ids, valid):
        s = valid.shape[1]
        live_rank = jnp.cumsum(valid, axis=1) - 1             # [C_loc, S]
        key = jnp.where(valid, live_rank, s + jnp.arange(s)[None, :])
        perm = jnp.argsort(key, axis=1)                       # stable
        n_live = jnp.sum(valid, axis=1).astype(jnp.int32)
        new_valid = jnp.arange(s)[None, :] < n_live[:, None]

        def g2(a, fill):
            return jnp.where(new_valid,
                             jnp.take_along_axis(a, perm, axis=1), fill)

        def g3(a, fill):
            return jnp.where(new_valid[:, :, None],
                             jnp.take_along_axis(a, perm[:, :, None],
                                                 axis=1), fill)

        new_pos = jnp.where(valid, live_rank, -1).astype(jnp.int32)
        return (g3(m_idx, PAD_INDEX), g3(m_val, 0.0),
                g3(codes, 0).astype(jnp.uint8), g2(row_ids, PAD_ID),
                new_valid, n_live, new_pos)

    fn = jax.shard_map(
        local_compact, mesh=mesh,
        in_specs=(ispec["members_idx"], ispec["members_val"], ispec["codes"],
                  ispec["row_ids"], ispec["valid"]),
        out_specs=(ispec["members_idx"], ispec["members_val"], ispec["codes"],
                   ispec["row_ids"], ispec["valid"], ispec["counts"],
                   ispec["valid"]),
        check_vma=False)

    def step(state):
        m_idx, m_val, codes, row_ids, valid, counts, new_pos = fn(
            state["members_idx"], state["members_val"], state["codes"],
            state["row_ids"], state["valid"])
        return ({**state, "members_idx": m_idx, "members_val": m_val,
                 "codes": codes, "row_ids": row_ids, "valid": valid,
                 "counts": counts}, new_pos)

    return step


def mutate_shapes(cell: GusCellConfig):
    b = cell.mutate_batch
    return (jax.ShapeDtypeStruct((b,), jnp.uint32),
            jax.ShapeDtypeStruct((b, cell.k_dims), jnp.uint32),
            jax.ShapeDtypeStruct((b, cell.k_dims), jnp.float32),
            jax.ShapeDtypeStruct((b, cell.d_proj), jnp.float32),
            jax.ShapeDtypeStruct((b, cell.pq_m), jnp.uint8))


def delete_shapes(cell: GusCellConfig):
    b = cell.mutate_batch
    return (jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32))
