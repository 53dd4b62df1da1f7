"""The deployment's corpus, made from ``--seed``.

Points carry the feature shapes of the configuration file: dense
embeddings, padded item sets and scalars, drawn around planted clusters
whose sizes follow a Zipf law. This is the benchmark's own generator, so
the data a cell serves cannot change when the program does.
"""
from __future__ import annotations

import numpy as np

PAD_ITEM = -1


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy stream ``stream`` of a run seed (any size)."""
    return np.random.default_rng([int(seed) % 2**63, stream])


def make_corpus(cfg: dict, seed: int):
    """Returns (ids int64 [N], features dict of numpy arrays).

    Feature keys follow the program's naming: ``dense:<name>`` f32
    [N, dim], ``set:<name>`` int32 [N, cap] padded with -1,
    ``scalar:<name>`` f32 [N]."""
    rng = rng_for(seed, 0)
    n, c = int(cfg["n_points"]), int(cfg["n_clusters"])
    probs = 1.0 / np.arange(1, c + 1) ** float(cfg["zipf_exponent"])
    cluster = rng.choice(c, n, p=probs / probs.sum())
    feats = {}
    for name, dim in sorted(cfg["dense"].items()):
        centers = rng.normal(size=(c, dim))
        centers /= np.linalg.norm(centers, axis=-1, keepdims=True)
        sigma = float(cfg["dense_noise"]) / np.sqrt(dim)
        x = centers[cluster] + sigma * rng.normal(size=(n, dim))
        feats[f"dense:{name}"] = x.astype(np.float32)
    for name, cap in sorted(cfg["sets"].items()):
        vocab = int(cfg["set_vocab_per_cluster"])
        count = np.maximum(rng.binomial(cap, float(cfg["set_fill"]), n), 1)
        pool = cluster[:, None] * vocab + rng.integers(0, vocab, (n, cap))
        noise = rng.random((n, cap)) < float(cfg["set_noise"])
        pool[noise] = rng.integers(0, c * vocab, int(noise.sum()))
        feats[f"set:{name}"] = np.where(np.arange(cap)[None, :] < count[:, None],
                                        pool, PAD_ITEM).astype(np.int32)
    for name in sorted(cfg["scalars"]):
        base = rng.uniform(0.0, 25.0, c)
        x = base[cluster] + float(cfg["scalar_spread"]) * rng.normal(size=n)
        feats[f"scalar:{name}"] = x.astype(np.float32)
    return np.arange(n, dtype=np.int64), feats


def take(feats: dict, rows) -> dict:
    return {k: v[rows] for k, v in feats.items()}
