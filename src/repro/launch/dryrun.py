import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import: jax locks the device
# count at first init, and the dry-run needs 512 placeholder host devices to
# build the production meshes. Everything below is ordinary.

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import time              # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np       # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs.base import SHAPES, applicable          # noqa: E402
from repro.configs.registry import ARCHS, get_config        # noqa: E402
from repro.launch import sharding as shp                    # noqa: E402
from repro.launch.mesh import (make_gus_mesh,               # noqa: E402
                               make_production_mesh)
from repro.models.model import (cache_specs,                # noqa: E402
                                input_specs, params_specs)
from repro.serve.serve_step import make_decode_step, make_prefill_step  # noqa: E402
from repro.train.optimizer import AdamWConfig               # noqa: E402
from repro.train.train_step import make_train_step          # noqa: E402
from repro.utils.hlo import collective_stats                # noqa: E402

"""Multi-pod dry-run driver (deliverable e).

For every (architecture x input shape x mesh) cell:
  jit(step).lower(**ShapeDtypeStructs).compile()
must succeed on the 16x16 single-pod mesh AND the 2x16x16 multi-pod mesh.
The compiled artifact yields memory_analysis (fits-check), cost_analysis
(FLOPs/bytes) and the collective schedule (parsed from the partitioned
HLO); scan-under-counting is fixed up by per-layer probe programs
(unrolled 1-stack vs 2-stack, same width/sharding — see --probes).

Records land in results/dryrun/<arch>_<shape>_<mesh>.json; §Dry-run and
§Roofline of EXPERIMENTS.md are generated from them.
"""

PROBE_STACKS = {
    "dense": (1, 2), "moe": (1, 2), "vlm": (1, 2), "encdec": (1, 2),
    "ssm": (1, 2), "hybrid": (1, 2),   # in units of one scan *group*
}


def _group_size(cfg) -> int:
    if cfg.family == "ssm":
        return cfg.slstm_period
    if cfg.family == "hybrid":
        return cfg.attn_period
    return 1


def _probe_cfg(cfg, n_groups: int):
    g = _group_size(cfg)
    # microbatches=1: the grad-accumulation scan is ALSO counted once by
    # HLO cost analysis; probing at mb=1 over the same global batch keeps
    # per-step totals correct (caught by useful_frac > 1 in §Roofline).
    repl = {"n_layers": n_groups * g, "scan_layers": False,
            "microbatches": 1}
    if cfg.family == "encdec":
        repl["n_enc_layers"] = n_groups
    return dataclasses.replace(cfg, **repl)


def opt_config(cfg) -> AdamWConfig:
    return AdamWConfig(lr=1e-4, moment_dtype=jnp.dtype(cfg.moment_dtype))


def build_cell(cfg, shape, mesh):
    """Returns (lower_fn) -> lowered for one cell under the mesh context."""
    dp = tuple(n for n in ("pod", "data") if n in mesh.axis_names)
    dp_entry = dp if len(dp) > 1 else dp[0]
    p_shape = params_specs(cfg)
    p_specs = shp.param_specs(p_shape, cfg, mesh)
    batch_sds = input_specs(cfg, shape)
    b_specs = shp.batch_specs(cfg, shape, mesh, batch_sds)

    if shape.kind == "train":
        ocfg = opt_config(cfg)
        from repro.train.optimizer import adamw_init
        o_shape = jax.eval_shape(lambda p: adamw_init(p, ocfg), p_shape)
        o_specs = shp.opt_specs(o_shape, p_specs)
        step = make_train_step(cfg, ocfg)

        def lower():
            return jax.jit(
                step,
                in_shardings=(p_specs, o_specs, b_specs),
                out_shardings=(p_specs, o_specs, None),
            ).lower(p_shape, o_shape, batch_sds)
        return lower

    if shape.kind == "prefill":
        step = make_prefill_step(cfg)

        def lower():
            return jax.jit(
                step, in_shardings=(p_specs, b_specs), out_shardings=None,
            ).lower(p_shape, batch_sds)
        return lower

    # decode
    c_shape = cache_specs(cfg, shape)
    c_specs = shp.cache_specs_tree(cfg, shape, mesh, c_shape)
    tok_spec = P(dp_entry) if shape.global_batch % (
        int(np.prod([dict(zip(mesh.axis_names, mesh.devices.shape))[n]
                     for n in dp]))) == 0 else P(None)
    step = make_decode_step(cfg)
    tok_sds = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)

    def lower():
        return jax.jit(
            step,
            in_shardings=(p_specs, c_specs, tok_spec),
            out_shardings=(None, None, c_specs),
        ).lower(p_shape, c_shape, tok_sds)
    return lower


def analyze(compiled) -> dict:
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    coll = collective_stats(compiled.as_text())
    return {
        "memory": {
            "argument_bytes": getattr(ma, "argument_size_in_bytes", 0),
            "output_bytes": getattr(ma, "output_size_in_bytes", 0),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", 0),
            "code_bytes": getattr(ma, "generated_code_size_in_bytes", 0),
        },
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "collectives": coll.summary(),
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             probes: bool = True, out_dir: str = "results/dryrun",
             verbose: bool = True, probes_only: bool = False) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = applicable(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "kind": shape.kind}
    if not ok:
        rec["skipped"] = why
        _write(out_dir, rec)
        return rec

    if probes_only:  # merge probes into an existing record (single core:
        # the main compile already happened in an earlier pass)
        path = os.path.join(out_dir, f"{arch}_{shape_name}_{mesh_name}.json")
        if not os.path.exists(path):
            probes_only = False
        else:
            with open(path) as f:
                rec = json.load(f)
            if "corrected" in rec:
                print(f"[dryrun] {arch}_{shape_name}: probes already done")
                return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = dataclasses.replace(
        cfg, dp_axes=("pod", "data") if multi_pod else ("data",),
        sp_axis="model", model_axis_size=16)
    n_dev = int(np.prod(list(mesh.devices.shape)))
    rec["devices"] = n_dev
    with jax.set_mesh(mesh):
        if not probes_only:
            t0 = time.time()
            lowered = build_cell(cfg, shape, mesh)()
            rec["lower_s"] = round(time.time() - t0, 2)
            t0 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t0, 2)
            rec["main"] = analyze(compiled)
            if verbose:
                print(compiled.memory_analysis())
                ca = compiled.cost_analysis() or {}
                print({k: v for k, v in ca.items()
                       if k in ("flops", "bytes accessed")})

        if probes or probes_only:
            g = _group_size(cfg)
            lo, hi = PROBE_STACKS[cfg.family]
            probe_res = {}
            for tag, n in (("probe_lo", lo), ("probe_hi", hi)):
                pcfg = _probe_cfg(cfg, n)
                t0 = time.time()
                pl = build_cell(pcfg, shape, mesh)()
                pc = pl.compile()
                probe_res[tag] = analyze(pc)
                probe_res[tag]["layers"] = pcfg.n_layers
                probe_res[tag]["compile_s"] = round(time.time() - t0, 2)
            rec["probes"] = probe_res
            rec["corrected"] = extrapolate(cfg, probe_res, lo, hi, g)
    _write(out_dir, rec)
    return rec


def extrapolate(cfg, probes: dict, lo: int, hi: int, group: int) -> dict:
    """Linear extrapolation of per-device cost to the full layer count:
    total(L) = cost(lo) + (cost(hi) - cost(lo)) * (L/g - lo) / (hi - lo)."""
    n_groups = cfg.n_layers // group
    f = (n_groups - lo) / (hi - lo)
    out = {}
    for key in ("flops", "bytes_accessed"):
        a = probes["probe_lo"][key]
        b = probes["probe_hi"][key]
        out[key] = a + (b - a) * f
    a = probes["probe_lo"]["collectives"]["total_bytes"]
    b = probes["probe_hi"]["collectives"]["total_bytes"]
    out["collective_bytes"] = a + (b - a) * f
    # per-op collective extrapolation
    ops = set(probes["probe_lo"]["collectives"]["bytes_by_op"]) \
        | set(probes["probe_hi"]["collectives"]["bytes_by_op"])
    out["collective_by_op"] = {
        op: probes["probe_lo"]["collectives"]["bytes_by_op"].get(op, 0)
        + (probes["probe_hi"]["collectives"]["bytes_by_op"].get(op, 0)
           - probes["probe_lo"]["collectives"]["bytes_by_op"].get(op, 0)) * f
        for op in sorted(ops)}
    return out


def run_gus_cell(multi_pod: bool, out_dir: str = "results/dryrun",
                 op: str = "query", merge: str = "flat",
                 n_partitions: int = 4096, slab: int = 8192,
                 tag: str = "", shards: int = 0) -> dict:
    """The paper-technique cells: sharded GUS query / mutate / delete steps.

    ``shards > 0`` lowers the same programs for a small 1-D CPU mesh (the
    mesh ``ShardedGusIndex`` serves on) instead of the production pod mesh
    — the dry-run proof that one program covers both deployments.
    """
    from repro.ann.sharded import (GusCellConfig, delete_shapes, index_shapes,
                                   make_delete_step, make_mutate_step,
                                   make_query_step, mutate_shapes,
                                   query_shapes)
    cell = GusCellConfig(merge=merge, n_partitions=n_partitions, slab=slab)
    if shards:
        mesh = make_gus_mesh(shards)
        mesh_name = f"cpu{shards}"
        # shrink the cell so [C/shards, ...] blocks stay CPU-sized, and
        # round the partition count up to a multiple of the mesh size
        # (the sharded specs can't split a non-divisible partition axis)
        c = min(n_partitions, shards * 16)
        c = (c + shards - 1) // shards * shards
        cell = dataclasses.replace(
            cell, n_partitions=c,
            slab=min(slab, 1024), query_batch=64, mutate_batch=256)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
        mesh_name = "2x16x16" if multi_pod else "16x16"
    kind = f"gus_{op}"
    if merge != "flat":
        kind = f"{kind}_{merge}"
    if tag:
        kind = f"{kind}_{tag}"
    rec = {"arch": "dynamic-gus", "shape": cell.name, "mesh": mesh_name,
           "kind": kind}
    with jax.set_mesh(mesh):
        state_sds = index_shapes(cell)
        if op == "mutate":
            step = make_mutate_step(mesh, cell)
            args = mutate_shapes(cell) + (state_sds,)
        elif op == "delete":
            step = make_delete_step(mesh, cell)
            args = delete_shapes(cell) + (state_sds,)
        else:
            step = make_query_step(mesh, cell)
            args = query_shapes(cell) + (state_sds,)
        t0 = time.time()
        lowered = jax.jit(step).lower(*args)
        rec["lower_s"] = round(time.time() - t0, 2)
        t0 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t0, 2)
        rec["main"] = analyze(compiled)
        print(compiled.memory_analysis())
    _write(out_dir, rec)
    return rec


def _write(out_dir: str, rec: dict) -> None:
    os.makedirs(out_dir, exist_ok=True)
    name = f"{rec['arch']}_{rec['shape']}_{rec['mesh']}"
    if rec.get("kind", "").startswith("gus_"):
        name = f"{rec['kind']}_{rec['mesh']}"
    with open(os.path.join(out_dir, name + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    status = "SKIP" if "skipped" in rec else "OK"
    print(f"[dryrun] {name}: {status} "
          f"(compile {rec.get('compile_s', '-')}s)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id or 'all'")
    ap.add_argument("--shape", default=None, choices=[*SHAPES, None])
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--gus", action="store_true",
                    help="run the sharded-GUS paper cells")
    ap.add_argument("--gus-mutate", action="store_true")
    ap.add_argument("--gus-delete", action="store_true")
    ap.add_argument("--gus-merge", default="flat", choices=("flat", "hier"))
    ap.add_argument("--gus-partitions", type=int, default=4096)
    ap.add_argument("--gus-slab", type=int, default=8192)
    ap.add_argument("--gus-tag", default="")
    ap.add_argument("--gus-shards", type=int, default=0,
                    help="lower the GUS cells for an N-device 1-D CPU mesh "
                         "instead of the pod mesh")
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--probes-only", action="store_true",
                    help="add probe corrections to existing records")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    meshes = [False, True] if args.both_meshes else [args.multipod]
    if args.gus or args.gus_mutate or args.gus_delete:
        op = ("mutate" if args.gus_mutate
              else "delete" if args.gus_delete else "query")
        for mp in meshes:
            run_gus_cell(mp, args.out, op=op,
                         merge=args.gus_merge,
                         n_partitions=args.gus_partitions,
                         slab=args.gus_slab, tag=args.gus_tag,
                         shards=args.gus_shards)
        return
    archs = list(ARCHS) if args.arch in (None, "all") else [args.arch]
    shapes = list(SHAPES) if args.shape is None else [args.shape]
    for shape in shapes:          # shape-major: all train cells first
        for arch in archs:
            for mp in meshes:
                try:
                    run_cell(arch, shape, mp, probes=not args.no_probes,
                             out_dir=args.out,
                             probes_only=args.probes_only)
                except Exception as e:  # keep sweeping; record the failure
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "kind": SHAPES[shape].kind,
                           "error": f"{type(e).__name__}: {e}"[:500]}
                    _write(args.out, rec)


if __name__ == "__main__":
    main()
