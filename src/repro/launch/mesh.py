"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — CPU runs must set XLA_FLAGS before any jax
initialization.

Axis semantics:
  pod   — outermost, maps to DCN (inter-pod) links; batch/index sharding
  data  — intra-pod DP/FSDP axis (and index-shard axis for GUS)
  model — TP/EP axis

Every mesh is built with ``Auto`` axis types, and callers activate one
with ``jax.set_mesh``, so the same GUS programs lower for the pod cells,
run on one or four TPU chips, and run on a 2-4 device CPU mesh.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for CPU tests (requires >= prod(shape) host devices)."""
    return _auto_mesh(shape, axes)


def make_gus_mesh(n_shards: int, *, two_level: bool = False, pod: int = 0):
    """Index-shard mesh over ``n_shards`` local devices — the CPU
    counterpart of the production GUS cells (ShardedGusIndex serves on
    it; the dry-run lowers the same programs for the pod meshes).

    ``pod`` selects the replica group: pod *p* owns the device slice
    ``devices[p*n_shards : (p+1)*n_shards]``, so a fleet of pods carves
    the host's devices into disjoint replica meshes — each pod serves a
    full copy of the index on its own devices, which is what
    ``serve.engine``'s hedging/fail-over replicates across
    (``make_pod_meshes`` builds the whole fleet at once).

    ``two_level=True`` factors the shards into a ("data", "model") grid so
    the hierarchical candidate-merge schedule (intra-"model" gather+top-k,
    then cross-"data") actually has a second stage to run — the 1-D mesh
    would silently degrade "hier" to the flat all_gather."""
    have = len(jax.devices())
    need = (pod + 1) * n_shards
    if need > have:
        platform = jax.devices()[0].platform
        hint = (f"set XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{need} before jax initializes" if platform == "cpu" else
                f"run on a host with at least {need} {platform} devices")
        raise ValueError(
            f"make_gus_mesh({n_shards}, pod={pod}): needs {need} device(s) "
            f"but only {have} {platform} device(s) are visible; {hint}")
    devices = jax.devices()[pod * n_shards:need]
    if two_level:
        # largest divisor <= sqrt becomes the outer "data" dim, so "model"
        # (the stage-1 gather) gets the bigger factor, as in production
        data = max(d for d in range(1, int(n_shards ** 0.5) + 1)
                   if n_shards % d == 0)
        return _auto_mesh((data, n_shards // data), ("data", "model"),
                          devices=devices)
    return _auto_mesh((n_shards,), ("data",), devices=devices)


def make_pod_meshes(n_pods: int, n_shards: int, *, two_level: bool = False):
    """The replica-group fleet: one index mesh per pod, over disjoint
    device slices (pod *p* gets ``devices[p*n_shards:(p+1)*n_shards]``).
    This is the serving plane's "pod" axis: every pod holds a complete
    replica of the sharded index, mutations fan out to all pods, and
    queries hedge/fail over between them (``serve.engine``)."""
    return [make_gus_mesh(n_shards, two_level=two_level, pod=p)
            for p in range(n_pods)]


def dp_axes(mesh) -> tuple:
    """The composite data-parallel axis names for this mesh."""
    names = mesh.axis_names
    return tuple(n for n in names if n in ("pod", "data"))


def model_axis(mesh) -> str:
    return "model"
