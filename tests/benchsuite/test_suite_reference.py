"""The plain reference against the program's model at a tiny size on the CPU
(float32 both; the StarCoder2 block, through ``archs.load``): the same
seed's weights, the same logits; and the comparison's arithmetic."""

from __future__ import annotations

import numpy as np
import pytest

from benchmarks.suite import archs, compare, loadgen, reference, weights
from tests.benchsuite.standin import TINY_CONFIG


def _logits(arch, w, tokens, config):
    import jax.numpy as jnp

    x = w["embedding"][tokens]
    for i in range(config["num_hidden_layers"]):
        lw = {n: w[f"layer_{i}.{n}"] for n in arch.LAYER_LEAVES}
        x = arch.layer(x, lw, config, jnp.float32)
    feats = reference.rms_norm(x, w["ln_final"], config["rms_norm_eps"],
                               jnp.float32)
    return feats @ w["lm_head"]


def test_reference_and_transformer_lm_agree_on_logits():
    import jax
    import jax.numpy as jnp

    from benchmarks.suite import program
    from covalent_tpu_plugin.parallel.sharding import unbox

    config, seed, seq = TINY_CONFIG, 2**31 + 3, 64
    arch = archs.load(config)
    lm, _ = arch.program(config, {
        "sequence": seq, "attention": "reference", "remat": False,
        "vocab_chunk": config["vocab_size"]}, None)
    template = unbox(jax.eval_shape(lambda: lm.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    params = program.place_weights(template, config, seed)
    tokens = jnp.asarray(loadgen.prompt(seed, 0, seq, config["vocab_size"]))
    got = lm.apply({"params": params}, tokens[None])[0]

    w = reference.all_leaves(config, seed, jnp.float32)
    want = _logits(arch, w, tokens, config)
    assert got.shape == want.shape == (seq, config["vocab_size"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    # The window binds at this size (48 < 64): it is exercised, not idle.
    assert config["sliding_window"] < seq


def test_serve_gaps_are_zero_for_the_references_own_greedy_tokens():
    config, seed = TINY_CONFIG, 11
    prompt = loadgen.prompt(seed, 0, 9, config["vocab_size"])
    # Served tokens that are simply wrong read a wide gap ...
    wrong = reference.serve_gaps(config, seed, [(prompt, [1, 2, 3])], 64, 16)[0]
    assert max(wrong) > 1e-2
    # ... and the reference's own argmax reads none.
    import jax.numpy as jnp

    arch = archs.load(config)
    w = reference.all_leaves(config, seed, jnp.float32)
    served = []
    for _ in range(3):
        tokens = jnp.asarray(prompt + served)
        served.append(int(jnp.argmax(_logits(arch, w, tokens, config)[-1])))
    gaps = reference.serve_gaps(config, seed, [(prompt, served)], 64, 16)[0]
    assert len(gaps) == 3 and max(gaps) < 1e-5


def test_norm_gaps_measure_against_the_median_leaf_and_skip_dead_leaves():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    prog = {"a": 1.1, "b": 2.0, "c": 2e-9}
    gaps = compare.norm_gaps(prog, ref)
    assert gaps["a"] == pytest.approx(0.1)
    assert gaps["c"] == pytest.approx(1e-9)  # against the median leaf, 1.0
    grads = {"a": 1.0, "b": 1.0, "c": 1e-6}
    kept = compare.norm_gaps(prog, ref, floor_of=grads, skip_below=1e-3)
    assert set(kept) == {"a", "b"}


def test_judge_holds_each_number_to_its_own_limit():
    ok, compared = compare.judge(
        {"x": 0.5, "y": 0, "free": 9}, {"x": 1.0, "y": 0, "_param": 3})
    assert ok and set(compared) == {"x", "y"}
    assert not compare.judge({"x": 2.0}, {"x": 1.0})[0]
    assert not compare.judge({}, {"x": 1.0})[0]
    assert not compare.judge({"x": float("nan")}, {"x": 1.0})[0]


def test_weights_are_the_seeds_and_the_names():
    import jax.numpy as jnp

    key = weights.seed_key(2**31 + 3)
    a = weights.leaf(key, "layer_0.q", (4, 4), 0.02, jnp.float32)
    b = weights.leaf(key, weights.name_hash("layer_0.q"), (4, 4), 0.02,
                     jnp.float32)
    c = weights.leaf(weights.seed_key(3), "layer_0.q", (4, 4), 0.02,
                     jnp.float32)
    assert (a == b).all() and not (a == c).all()
    assert (weights.leaf(key, "ln", (4,), None, jnp.float32) == 1).all()
