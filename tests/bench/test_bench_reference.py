"""The plain reference against the program on the CPU, where the program's
SimHash runs in float32: same buckets, same hashes, same edge weights."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference as ref
from bench.corpus import make_corpus
from bench.spec import ROOT
from repro.core import hashing
from repro.core.buckets import BucketConfig, generate_buckets, \
    make_bucket_params
from repro.core.scorer import pair_features, scorer_apply
from repro.core.types import FeatureSpec

CONFIGS = ["arxiv-graph", "products"]


def config(name, n=300):
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                     .read_text())
    cfg["n_points"] = n
    return cfg


def program_spec(cfg):
    b = cfg["buckets"]
    return (FeatureSpec(dense=cfg["dense"], sets=cfg["sets"],
                        scalars=tuple(cfg["scalars"])),
            BucketConfig(dense_tables=b["dense_tables"],
                         dense_bits=b["dense_bits"],
                         set_tables=b["set_tables"],
                         scalar_widths=tuple(b["scalar_widths"]),
                         seed=b["seed"]))


def test_hashes_match_the_program():
    x = np.random.default_rng(0).integers(-2**31, 2**31, 1000).astype(
        np.int32)
    np.testing.assert_array_equal(ref.fmix32(x), np.asarray(
        hashing.fmix32(jnp.asarray(x))))
    np.testing.assert_array_equal(ref.uhash(131, x), np.asarray(
        hashing.uhash(131, jnp.asarray(x))))
    np.testing.assert_array_equal(
        ref.hash_fields(ref.tag("dense", "t"), 3, x), np.asarray(
            hashing.hash_fields(jnp.uint32(ref.tag("dense", "t")),
                                jnp.uint32(3), jnp.asarray(x))))


@pytest.mark.parametrize("name", CONFIGS)
def test_buckets_match_the_program(name):
    cfg = config(name)
    _, feats = make_corpus(cfg, 2**35 + 1)
    spec, bcfg = program_spec(cfg)
    want_b, want_v = generate_buckets(feats, spec, bcfg,
                                      make_bucket_params(spec, bcfg))
    rounding = ref.simhash_rounding(cfg)
    assert rounding == "float32"                        # the CPU's pass
    got_b, got_v, uncertain = ref.buckets(cfg, ref.hyperplanes(cfg), feats,
                                          rounding)
    np.testing.assert_array_equal(got_v, np.asarray(want_v))
    bad = got_b != np.asarray(want_b)
    assert not (bad & ~uncertain).any()
    assert uncertain.mean() < 0.2


@pytest.mark.parametrize("name", CONFIGS)
def test_edge_weights_match_the_program(name):
    cfg = config(name)
    _, feats = make_corpus(cfg, 11)
    spec, _ = program_spec(cfg)
    a = {k: v[:150] for k, v in feats.items()}
    b = {k: v[150:] for k, v in feats.items()}
    key = jax.random.PRNGKey(4)
    f = 2 * len(cfg["dense"]) + 2 * len(cfg["sets"]) + len(cfg["scalars"])
    params = {}
    for i, (n_in, n_out) in enumerate(((f, 10), (10, 10), (10, 1))):
        key, s1, s2 = jax.random.split(key, 3)
        params[f"w{i}"] = jax.random.normal(s1, (n_in, n_out))
        params[f"b{i}"] = jax.random.normal(s2, (n_out,))
    want = np.asarray(scorer_apply(params, pair_features(a, b, spec)))
    host = {k: np.asarray(v) for k, v in params.items()}
    got = ref.mlp(host, ref.pair_signals(cfg, a, b))
    np.testing.assert_allclose(got, want, atol=2e-6)
    # the control, with bfloat16 matmul operands, is off by far more than
    # the program; the high pass lies between the two
    x = ref.pair_signals(cfg, a, b)
    ctrl = ref.mlp_lower(host, x, "bfloat16")
    high = ref.mlp_lower(host, x, "high")
    assert np.abs(ctrl - got).max() > 30 * np.abs(want - got).max()
    assert np.abs(ctrl - got).max() > 10 * np.abs(high - got).max()


def test_corpus_replay_follows_dispatch_order():
    feats = {"dense:x": np.arange(8, dtype=np.float32).reshape(4, 2)}
    c = ref.Corpus(np.arange(4), feats)
    c.apply([1, 2, 0], [0, 1, 9],
            {"dense:x": np.full((3, 2), 7.0, np.float32)})
    assert not c.live(1) and c.live(9) and c.applied == 1
    got = c.features([0, 2, 9])["dense:x"]
    np.testing.assert_array_equal(got, [[7, 7], [4, 5], [7, 7]])
    np.testing.assert_array_equal(c.live_ids(), [0, 2, 3, 9])
