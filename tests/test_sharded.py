"""Distributed programs on a real multi-device mesh.

These run in a subprocess with XLA_FLAGS forcing 8 host devices (the main
test process must keep the default single device — the dry-run brief), and
assert the sharded GUS query step agrees with a local oracle and that the
compressed-DP train step converges like plain DP.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_sharded_query_matches_local_oracle():
    res = _run(textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.ann.sharded import (GusCellConfig, index_shapes,
                                       make_query_step)
        from repro.core.types import PAD_INDEX
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh((2, 4), ("data", "model"))
        cell = GusCellConfig(n_rows=8*64, k_dims=4, d_proj=16, pq_m=4,
                             n_partitions=16, slab=32, nprobe_local=2,
                             query_batch=8, top_k=5)
        rng = np.random.default_rng(0)
        c, s = cell.n_partitions, cell.slab
        state = {
          "centroids": jnp.asarray(rng.normal(size=(c, cell.d_proj)), jnp.float32),
          "books": jnp.asarray(
              rng.normal(size=(cell.pq_m, 256,
                               cell.d_proj // cell.pq_m)) * 0.01,
              jnp.float32),
          "members_idx": jnp.asarray(rng.integers(0, 30, (c, s, cell.k_dims)), jnp.uint32),
          "members_val": jnp.asarray(rng.random((c, s, cell.k_dims)), jnp.float32),
          "codes": jnp.asarray(rng.integers(0, 256, (c, s, cell.pq_m)), jnp.uint8),
          "row_ids": jnp.asarray(rng.integers(0, 1 << 30, (c, s)), jnp.uint32),
          "valid": jnp.ones((c, s), bool),
          "counts": jnp.zeros((c,), jnp.int32),
        }
        q_idx = jnp.asarray(rng.integers(0, 30, (8, cell.k_dims)), jnp.uint32)
        q_val = jnp.asarray(rng.random((8, cell.k_dims)), jnp.float32)
        q_sk = jnp.asarray(rng.normal(size=(8, cell.d_proj)), jnp.float32)
        import dataclasses as dc
        with jax.set_mesh(mesh):
            step = make_query_step(mesh, cell)
            rows, dists = jax.jit(step)(q_idx, q_val, q_sk, state)
            hier = make_query_step(mesh, dc.replace(cell, merge="hier"))
            rows_h, dists_h = jax.jit(hier)(q_idx, q_val, q_sk, state)
        assert np.allclose(np.sort(np.asarray(dists), -1),
                           np.sort(np.asarray(dists_h), -1), atol=1e-5), \
            "hier merge must return the same top-k distances"
        rows, dists = np.asarray(rows), np.asarray(dists)
        # oracle: scores of returned rows must match exact sparse dots
        mi = np.asarray(state["members_idx"]).reshape(-1, cell.k_dims)
        mv = np.asarray(state["members_val"]).reshape(-1, cell.k_dims)
        ok = True
        for b in range(8):
            for r, d in zip(rows[b], dists[b]):
                if not np.isfinite(d):
                    continue
                qi, qv = np.asarray(q_idx[b]), np.asarray(q_val[b])
                exact = sum(float(qv[i]*mv[r][j]) for i in range(cell.k_dims)
                            for j in range(cell.k_dims)
                            if qi[i] == mi[r][j] and qi[i] != 0xFFFFFFFF)
                ok &= abs(-exact - d) < 1e-4
        print(json.dumps({"ok": bool(ok),
                          "n_finite": int(np.isfinite(dists).sum())}))
    """))
    assert res["ok"] and res["n_finite"] > 0


@pytest.mark.slow
def test_sharded_mutate_routes_and_tombstones():
    """The mutate step's returned landing sites must be the device truth:
    every (part, pos) it reports holds exactly the row that was appended,
    padding rows land nowhere, and the delete step clears exactly the
    reported sites — on a multi-axis (2x4) mesh."""
    res = _run(textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.ann.sharded import (GusCellConfig, make_delete_step,
                                       make_mutate_step, PAD_ID)
        from repro.core.types import PAD_INDEX
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh((2, 4), ("data", "model"))
        cell = GusCellConfig(k_dims=4, d_proj=16, pq_m=4, n_partitions=16,
                             slab=32, mutate_batch=64)
        rng = np.random.default_rng(1)
        c, s = cell.n_partitions, cell.slab
        state = {
          "centroids": jnp.asarray(rng.normal(size=(c, cell.d_proj)),
                                   jnp.float32),
          "books": jnp.asarray(
              rng.normal(size=(cell.pq_m, 256, cell.d_proj//cell.pq_m)),
              jnp.float32),
          "members_idx": jnp.full((c, s, cell.k_dims), PAD_INDEX,
                                  jnp.uint32),
          "members_val": jnp.zeros((c, s, cell.k_dims), jnp.float32),
          "codes": jnp.zeros((c, s, cell.pq_m), jnp.uint8),
          "row_ids": jnp.full((c, s), int(PAD_ID), jnp.uint32),
          "valid": jnp.zeros((c, s), bool),
          "counts": jnp.zeros((c,), jnp.int32),
        }
        n_real = 48
        ids = np.full((cell.mutate_batch,), int(PAD_ID), np.uint32)
        ids[:n_real] = np.arange(100, 100 + n_real, dtype=np.uint32)
        new_idx = jnp.asarray(
            rng.integers(0, 30, (cell.mutate_batch, cell.k_dims)),
            jnp.uint32)
        new_val = jnp.asarray(rng.random((cell.mutate_batch, cell.k_dims)),
                              jnp.float32)
        new_sk = jnp.asarray(
            rng.normal(size=(cell.mutate_batch, cell.d_proj)), jnp.float32)
        new_codes = jnp.asarray(
            rng.integers(0, 256, (cell.mutate_batch, cell.pq_m)), jnp.uint8)
        with jax.set_mesh(mesh):
            mutate = jax.jit(make_mutate_step(mesh, cell))
            state, (r_part, r_pos) = mutate(
                jnp.asarray(ids), new_idx, new_val, new_sk, new_codes, state)
            # single-copy cell: one (part, pos) per row
            r_part = np.asarray(r_part)[:, 0]
            r_pos = np.asarray(r_pos)[:, 0]
            m_idx = np.asarray(state["members_idx"])
            valid = np.asarray(state["valid"])
            ok_rows = bool((r_part[:n_real] >= 0).all())
            ok_pad = bool((r_part[n_real:] == -1).all())
            placed = all(
                (m_idx[r_part[i], r_pos[i]] == np.asarray(new_idx[i])).all()
                and valid[r_part[i], r_pos[i]]
                for i in range(n_real))
            ok_count = int(valid.sum()) == n_real
            # tombstone half of the batch
            dels = cell.mutate_batch
            parts = np.full((dels,), -1, np.int32)
            poss = np.zeros((dels,), np.int32)
            parts[:n_real//2] = r_part[:n_real//2]
            poss[:n_real//2] = r_pos[:n_real//2]
            delete = jax.jit(make_delete_step(mesh, cell))
            state = delete(jnp.asarray(parts), jnp.asarray(poss), state)
            valid2 = np.asarray(state["valid"])
            cleared = all(not valid2[r_part[i], r_pos[i]]
                          for i in range(n_real//2))
            kept = all(valid2[r_part[i], r_pos[i]]
                       for i in range(n_real//2, n_real))
        print(json.dumps({"ok_rows": ok_rows, "ok_pad": ok_pad,
                          "placed": placed, "ok_count": ok_count,
                          "cleared": cleared, "kept": kept}))
    """))
    assert all(res.values()), res


@pytest.mark.slow
def test_compressed_dp_step_trains():
    res = _run(textwrap.dedent("""
        import json, dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.registry import reduced_config
        from repro.launch.mesh import make_test_mesh
        from repro.train.optimizer import AdamWConfig
        from repro.train.train_step import (init_train_state,
                                            make_compressed_dp_train_step,
                                            init_ef_state, make_train_step)
        cfg = reduced_config("qwen3-8b")
        mesh = make_test_mesh((8,), ("data",))
        opt = AdamWConfig(lr=1e-3)
        params, opt_state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
        opt_state = init_ef_state(params, opt_state)
        step = make_compressed_dp_train_step(cfg, opt, mesh)
        rng = np.random.default_rng(0)
        losses = []
        with jax.set_mesh(mesh):
            jit_step = jax.jit(step)
            for i in range(8):
                batch = {"tokens": jnp.asarray(rng.integers(0, 64, (16, 16))),
                         "labels": jnp.asarray(rng.integers(0, 64, (16, 16)))}
                params, opt_state, m = jit_step(params, opt_state, batch)
                losses.append(float(m["loss"]))
        print(json.dumps({"first": losses[0], "last": losses[-1]}))
    """))
    assert res["last"] < res["first"]
