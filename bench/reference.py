"""The plain reference: the configuration's semantics written out directly.

It imports nothing of the program. From a point's features it computes
the bucket set the configuration defines (SimHash per dense mode, MinHash
per set mode, quantisation per scalar, hashed with murmur3 mixing), the
embedding distance of two points (minus the number of buckets they share)
and the edge weight of a pair (the scorer MLP over pair signals, in
float64). The corpus itself is replayed from the requests the harness
dispatched, in dispatch order.

SimHash precision: the configuration states how the projections round
their operands on each platform (``simhash_operands``: bfloat16 on a TPU,
float32 on a CPU, float32 accumulation); the reference rounds both
operands that way and sums in float64. A SimHash table with a projection
closer to zero than ``UNCERTAIN`` may come out either way on the chip,
where the program's own projections of one point differ by batch shape;
such a table may or may not be shared, every other table must match
exactly (``shared_range``).

The control (``mlp_lower``) is the scorer in float32 with every matmul
at the TPU's default pass, bfloat16 operands: the step a change that drops
the ``HIGHEST`` pass the configuration states would take. The ``high``
pass, three bfloat16 products, is kept as a reading: at the scorer's
width it moves a weight less than the chip's own float32 rounding does.
"""
from __future__ import annotations

import zlib

import jax
import ml_dtypes
import numpy as np

PAD_ITEM = -1
GOLDEN = np.uint32(0x9E3779B9)
# |projection| below which a SimHash bit may take either sign: on the chip
# one point's projection has been seen to change sign at 2.2e-3 between
# the batch shapes the program embeds it in (bfloat16 operands round to
# ~4e-3 of a product); ten times the bfloat16 step of a projection of ~1
UNCERTAIN = 2e-2


def _bf16_nearest(x):
    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


ROUNDINGS = {"float32": lambda x: x, "bfloat16": _bf16_nearest}


def _u32(x) -> np.ndarray:
    return np.atleast_1d(np.asarray(x)).astype(np.uint32)


def fmix32(x) -> np.ndarray:
    x = _u32(x).copy()
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x85EBCA6B)
    x ^= x >> np.uint32(13)
    x *= np.uint32(0xC2B2AE35)
    x ^= x >> np.uint32(16)
    return x


def combine(h, v) -> np.ndarray:
    h, v = _u32(h), _u32(v)
    return fmix32(h ^ (v + GOLDEN + (h << np.uint32(6)) + (h >> np.uint32(2))))


def hash_fields(*fields) -> np.ndarray:
    h = _u32(0x811C9DC5)
    for f in fields:
        h = combine(h, f)
    return h


def uhash(seed: int, x) -> np.ndarray:
    return fmix32((_u32(x) * GOLDEN) ^ fmix32(_u32(seed)))


def tag(kind: str, name: str) -> np.uint32:
    return np.uint32(zlib.crc32(f"{kind}:{name}".encode()))


def hyperplanes(cfg: dict) -> dict:
    """SimHash planes per dense mode: standard normals [T, D, bits] from
    the bucket seed, one key split per mode in name order."""
    b = cfg["buckets"]
    key = jax.random.PRNGKey(int(b["seed"]))
    out = {}
    for name in sorted(cfg["dense"]):
        key, sub = jax.random.split(key)
        out[name] = np.asarray(jax.random.normal(
            sub, (b["dense_tables"], cfg["dense"][name], b["dense_bits"]),
            np.float32))
    return out


def _round(x, rounding: str) -> np.ndarray:
    x = np.ascontiguousarray(x, np.float32)
    return ROUNDINGS[rounding](x).astype(np.float64)


def simhash_rounding(cfg: dict, platform: str | None = None) -> str:
    """The SimHash operand rounding the configuration states for
    ``platform`` (the default backend's when not given)."""
    return cfg["simhash_operands"][platform or jax.default_backend()]


def min_projection(cfg: dict, planes: dict, feats: dict,
                   rounding="float32") -> np.ndarray:
    """Each point's SimHash projection nearest zero, in absolute value."""
    out = np.full(len(next(iter(feats.values()))), np.inf)
    for name in sorted(cfg["dense"]):
        proj = np.einsum("bd,tdk->tbk", _round(feats[f"dense:{name}"],
                                               rounding),
                         _round(planes[name], rounding))
        out = np.minimum(out, np.abs(proj).min(axis=(0, 2)))
    return out


def buckets(cfg: dict, planes: dict, feats: dict, rounding="float32"):
    """Bucket ids uint32 [B, K], valid bool [B, K], uncertain bool
    [B, K]: SimHash operands rounded by ``rounding``, a SimHash table
    uncertain when one of its projections lies within ``UNCERTAIN`` of
    zero."""
    b = cfg["buckets"]
    ids, valid = [], []
    batch = len(next(iter(feats.values())))
    uncertain = []
    for name in sorted(cfg["dense"]):
        proj = np.einsum("bd,tdk->tbk", _round(feats[f"dense:{name}"],
                                               rounding),
                         _round(planes[name], rounding))
        near = (np.abs(proj) < UNCERTAIN).any(axis=2)          # [T, B]
        codes = ((proj > 0).astype(np.uint64)
                 << np.arange(b["dense_bits"], dtype=np.uint64)).sum(-1)
        for t in range(b["dense_tables"]):
            ids.append(hash_fields(tag("dense", name), t, codes[t]))
            valid.append(np.ones(batch, bool))
            uncertain.append(near[t])
    for name in sorted(cfg["sets"]):
        items = np.asarray(feats[f"set:{name}"])
        present = items != PAD_ITEM
        for t in range(b["set_tables"]):
            h = np.where(present, uhash(b["seed"] * 131 + t, items),
                         np.uint32(0xFFFFFFFF))
            ids.append(hash_fields(tag("set", name), t, h.min(axis=-1)))
            valid.append(present.any(axis=-1))
            uncertain.append(np.zeros(batch, bool))
    for name in sorted(cfg["scalars"]):
        x = np.asarray(feats[f"scalar:{name}"], np.float32)
        for wi, width in enumerate(b["scalar_widths"]):
            bins = np.floor(x / np.float32(width)).astype(np.int32)
            ids.append(hash_fields(tag("scalar", name), wi, bins))
            valid.append(np.ones(batch, bool))
            uncertain.append(np.zeros(batch, bool))
    return np.stack(ids, -1), np.stack(valid, -1), np.stack(uncertain, -1)


def shared_range(qb, qv, qu, cb, cv, cu) -> tuple[np.ndarray, np.ndarray]:
    """The fewest and the most buckets one query (``qb``, ``qv``, ``qu``
    of one row) can share with each candidate row: tables certain on both
    sides count as they match, tables uncertain on either side may or may
    not (bucket ids carry their table, so tables align by position)."""
    both = qv[None, :] & cv
    unsure = both & (qu[None, :] | cu)
    lo = (both & ~unsure & (qb[None, :] == cb)).sum(1)
    return lo, lo + unsure.sum(1)


def shared(qb, qv, cb, cv) -> np.ndarray:
    """Buckets shared by aligned rows (each bucket counted once)."""
    out = np.zeros(len(qb), np.int64)
    for r in range(len(qb)):
        out[r] = len(set(qb[r][qv[r]].tolist())
                     & set(cb[r][cv[r]].tolist()))
    return out


def pair_signals(cfg: dict, fa: dict, fb: dict) -> np.ndarray:
    """Per-pair similarity signals, float64 [B, F], in the configuration's
    order: per dense mode cosine and scaled L2, per set mode Jaccard and
    log-overlap, per scalar minus the absolute difference."""
    out = []
    for name in sorted(cfg["dense"]):
        a = np.asarray(fa[f"dense:{name}"], np.float64)
        b = np.asarray(fb[f"dense:{name}"], np.float64)
        na = np.linalg.norm(a, axis=-1) + 1e-9
        nb = np.linalg.norm(b, axis=-1) + 1e-9
        out.append((a * b).sum(-1) / (na * nb))
        out.append(-np.linalg.norm(a - b, axis=-1) / (na + nb))
    for name in sorted(cfg["sets"]):
        a, b = np.asarray(fa[f"set:{name}"]), np.asarray(fb[f"set:{name}"])
        va, vb = a != PAD_ITEM, b != PAD_ITEM
        inter = ((a[:, :, None] == b[:, None, :]) & va[:, :, None]
                 & vb[:, None, :]).sum((1, 2)).astype(np.float64)
        union = np.maximum(va.sum(-1) + vb.sum(-1) - inter, 1.0)
        out.append(inter / union)
        out.append(np.log1p(inter))
    for name in sorted(cfg["scalars"]):
        out.append(-np.abs(np.asarray(fa[f"scalar:{name}"], np.float64)
                           - np.asarray(fb[f"scalar:{name}"], np.float64)))
    return np.stack(out, -1)


def mlp(params: dict, x: np.ndarray) -> np.ndarray:
    """sigmoid of the tanh MLP, in float64."""
    h = np.asarray(x, np.float64)
    n = len(params) // 2
    for i in range(n):
        h = h @ np.asarray(params[f"w{i}"], np.float64) + np.asarray(
            params[f"b{i}"], np.float64)
        if i < n - 1:
            h = np.tanh(h)
    return 1.0 / (1.0 + np.exp(-h[..., 0]))


def _split_bf16(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    hi = _bf16_nearest(x)
    return hi, _bf16_nearest((x - hi).astype(np.float32))


def matmul_high(a, w) -> np.ndarray:
    """float32 matmul at the ``high`` pass: each operand split into a
    bfloat16 head and tail, the three largest products summed in float32
    (the tail-by-tail product is dropped)."""
    ah, al = _split_bf16(np.asarray(a, np.float32))
    wh, wl = _split_bf16(np.asarray(w, np.float32))
    out = (ah.astype(np.float64) @ wh + ah.astype(np.float64) @ wl
           + al.astype(np.float64) @ wh)
    return out.astype(np.float32)


def matmul_bf16(a, w) -> np.ndarray:
    """float32 matmul at the default pass of a TPU: both operands rounded
    to bfloat16, one product, summed in float32."""
    return (_bf16_nearest(np.asarray(a, np.float32)).astype(np.float64)
            @ _bf16_nearest(np.asarray(w, np.float32))).astype(np.float32)


MATMULS = {"high": matmul_high, "bfloat16": matmul_bf16}


def mlp_lower(params: dict, x: np.ndarray, matmul: str) -> np.ndarray:
    """A control: ``mlp`` in float32 with its matmuls at a lower pass
    (``MATMULS``)."""
    mm = MATMULS[matmul]
    h = np.asarray(x, np.float32)
    n = len(params) // 2
    for i in range(n):
        h = mm(h, params[f"w{i}"]) + np.asarray(params[f"b{i}"], np.float32)
        if i < n - 1:
            h = np.tanh(h)
    return (1.0 / (1.0 + np.exp(-h[..., 0]))).astype(np.float32)


class Corpus:
    """The live corpus, replayed batch by batch in dispatch order.

    Batch semantics: rows are independent (a request never names an id
    twice); insert and update store the row's features, delete drops the
    point."""

    def __init__(self, ids: np.ndarray, feats: dict):
        self.rows = {int(p): i for i, p in enumerate(np.asarray(ids))}
        self.store = [{k: np.asarray(v) for k, v in feats.items()}]
        self.applied = 0

    def apply(self, kinds, ids, feats) -> None:
        block = len(self.store)
        self.store.append({k: np.asarray(v) for k, v in feats.items()})
        for r, (kind, pid) in enumerate(zip(np.asarray(kinds).tolist(),
                                            np.asarray(ids).tolist())):
            if kind == 2:
                self.rows.pop(pid, None)
            else:
                self.rows[pid] = (block, r)
        self.applied += 1

    def live(self, pid: int) -> bool:
        return int(pid) in self.rows

    def features(self, pids) -> dict:
        """Current features of live points ``pids`` (in order)."""
        locs = [self.rows[int(p)] for p in pids]
        out = {}
        for k in self.store[0]:
            out[k] = np.stack([
                self.store[0][k][loc] if isinstance(loc, int)
                else self.store[loc[0]][k][loc[1]] for loc in locs]) \
                if locs else self.store[0][k][:0]
        return out

    def live_ids(self) -> np.ndarray:
        return np.asarray(sorted(self.rows), np.int64)
