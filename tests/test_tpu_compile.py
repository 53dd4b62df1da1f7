"""Compile the main path for a described v5e chip (nothing executes).

The TPU compiler is installed even where no chip is attached, so these
tests lower every Pallas kernel at real widths and the sharded index
steps at one chip's share of ``GusCellConfig`` through Mosaic/XLA:TPU,
and check that each compiled kernel program holds a ``tpu_custom_call``.
What the compiler refuses here (block shapes, layouts, VMEM) fails a test
instead of a chip run.

The topology is described inside a module fixture (never at import), and
all such compiles live in this one file: only one process at a time may
load the TPU library, and the file runs on one worker.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding

from repro.ann import sharded
from repro.kernels import fused_query, ops, pq_score, scorer_mlp, sparse_dot
from repro.kernels import topk_select

B, M, C, N, K = 64, 16, 256, 2048, 40      # query batch, PQ, candidates, k
# the products cells' query step: 156 probed slabs of 4,096 slots, PQ 8
CELL_B, CELL_M, CELL_N, CELL_K = 8, 8, 156 * 4096, 128
KD, F, H = 16, 16, 128                     # sparse nnz, scorer widths


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import compilation_cache, topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:        # no TPU compiler or library lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_cases():
    """name -> (fn, [(shape, dtype) per argument]) at real widths."""
    f32, i32, u8 = jnp.float32, jnp.int32, jnp.uint8
    row = (B, N)
    return {
        "pq_score": (lambda lut, c: pq_score.pq_score(lut, c),
                     [((B, M, C), f32), ((N, M), u8)]),
        "pq_score_batched": (lambda lut, c: pq_score.pq_score_batched(lut, c),
                             [((B, M, C), f32), ((B, N, M), u8)]),
        "fused_query": (
            lambda lut, c, i, v, b: fused_query.fused_query_kernel(
                lut, c, i, v, b, K),
            [((B, M, C), f32), ((B, N, M), u8), (row, i32), (row, i32),
             (row, f32)]),
        "fused_query_int8": (
            lambda q, s, c, i, v, b: fused_query.fused_query_kernel_int8(
                q, s, c, i, v, b, K),
            [((B, M, C), jnp.int8), ((B, M), f32), ((B, N, M), u8),
             (row, i32), (row, i32), (row, f32)]),
        "fused_query_cell": (
            lambda lut, c, i, v, b: fused_query.fused_query_kernel(
                lut, c, i, v, b, CELL_K,
                ops.shortlist_block_live(v != 0, CELL_K)),
            [((CELL_B, CELL_M, C), f32), ((CELL_B, CELL_N, CELL_M), u8),
             ((CELL_B, CELL_N), i32), ((CELL_B, CELL_N), i32),
             ((CELL_B, CELL_N), f32)]),
        "topk_select": (lambda s: topk_select.topk_select(s, K),
                        [(row, f32)]),
        "sparse_dot": (
            lambda qi, qv, di, dv: sparse_dot.sparse_dot(qi, qv, di, dv),
            [((B, KD), jnp.uint32), ((B, KD), f32), ((N, KD), jnp.uint32),
             ((N, KD), f32)]),
        "sparse_dot_batched": (
            lambda qi, qv, di, dv: sparse_dot.sparse_dot_batched(
                qi, qv, di, dv),
            [((B, KD), jnp.uint32), ((B, KD), f32),
             ((B, 4 * K, KD), jnp.uint32), ((B, 4 * K, KD), f32)]),
        "scorer_mlp": (
            lambda x, *p: scorer_mlp.scorer_mlp(x, *p),
            [((B * K, F), f32), ((F, H), f32), ((H,), f32), ((H, H), f32),
             ((H,), f32), ((H, 1), f32), ((1,), f32)]),
    }


@pytest.mark.parametrize("name", sorted(_kernel_cases()))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_cases()[name]
    args = [_sds(s, dt, one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# one chip's share of the production cell: 256 partitions x 2,048-row
# slabs (~75 MB of index state), the cell's own query/mutate batches
CHIP_CELL = dataclasses.replace(
    sharded.GusCellConfig(), name="gus_one_chip", n_rows=256 * 2048,
    n_partitions=256, slab=2048, query_batch=256, mutate_batch=4096,
    soar_lambda=1.0)


def _state_specs(mesh, cell):
    shapes, specs = sharded.index_shapes(cell), sharded.index_specs(cell, mesh)
    return {k: _sds(v.shape, v.dtype, NamedSharding(mesh, specs[k]))
            for k, v in shapes.items()}


def _mesh(topo, shape, axes):
    n = int(np.prod(shape))
    return Mesh(np.asarray(topo.devices[:n]).reshape(shape), axes,
                axis_types=(AxisType.Auto,) * len(axes))


@pytest.fixture(scope="module")
def chip_mesh(topo):
    return _mesh(topo, (1,), ("data",))


def _replicated(mesh, shapes):
    return [_sds(s.shape, s.dtype, NamedSharding(mesh, sharded.P()))
            for s in shapes]


# one chip; four chips, flat merge; a 2x2 grid, two-stage merge
@pytest.mark.parametrize("shape,axes,merge", [
    ((1,), ("data",), "flat"), ((4,), ("data",), "flat"),
    ((2, 2), ("data", "model"), "hier")])
def test_query_step_compiles_with_kernel(topo, monkeypatch, shape, axes,
                                         merge):
    # the platform rule reads the default backend, which is the CPU here:
    # steer it so the step is traced the way a chip traces it
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    mesh = _mesh(topo, shape, axes)
    n_chips = int(np.prod(shape))
    cell = dataclasses.replace(
        CHIP_CELL, n_partitions=CHIP_CELL.n_partitions * n_chips,
        merge=merge)
    step = sharded.make_query_step(mesh, cell)
    args = _replicated(mesh, sharded.query_shapes(cell))
    compiled = jax.jit(step).lower(*args, _state_specs(mesh, cell)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if n_chips > 1:
        assert "all-gather" in text


def test_mutate_step_compiles(chip_mesh):
    step = sharded.make_mutate_step(chip_mesh, CHIP_CELL)
    ids, idx, val, sk, codes = _replicated(
        chip_mesh, sharded.mutate_shapes(CHIP_CELL))
    jax.jit(step).lower(ids, idx, val, sk, codes,
                        _state_specs(chip_mesh, CHIP_CELL),
                        new_codes2=codes).compile()


def test_compact_step_compiles(chip_mesh):
    step = sharded.make_compact_step(chip_mesh, CHIP_CELL)
    jax.jit(step).lower(_state_specs(chip_mesh, CHIP_CELL)).compile()
