"""``laguna`` behind the ``model_type`` seam: its leaves against the
program's parameters, its cell at toy widths through the harness (sound,
under both kept faults, under the bfloat16 control and under one planted
fault for each mechanism that is new), the configuration file against the
catalog row it was cut from, its needed work against hand arithmetic, and
the guide's share test for the heads and for the experts."""

from __future__ import annotations

import dataclasses
import json
import os
import time

import pytest

from benchmarks.suite import archs, run, spec, weights, work
from benchmarks.suite.archs import laguna as arch
from tests.benchsuite import laguna_toy, standin
from tests.benchsuite.test_suite_run import _half_batch, _unchanged_state

REPO = standin.REPO
CELL = "laguna-toy.train"
REAL = "laguna-s.train-16k"
SEED = 2**31 + 1501
FULL, SLIDING, PER_HEAD = "full_attention", "sliding_attention", "per_head"
#: The catalog row's ``config`` (model-configs guide, ``Laguna-S-2.1``).
ROW = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
    "intermediate_size": 12288, "num_hidden_layers": 48,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 1048576, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
    "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head",
    "sliding_window": 512,
    "rope_parameters": {
        FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
               "original_max_position_embeddings": 8192, "beta_slow": 1,
               "beta_fast": 32, "attention_factor": 1.4852030263919618,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000,
                  "partial_rotary_factor": 1}},
    "layer_types": [FULL, SLIDING, SLIDING, SLIDING] * 12,
    "moe_apply_router_weight_on_input": False,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "gating_types": [PER_HEAD] * 48, "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
    "moe_router_logit_softcapping": 0,
}
REDUCED = {"num_hidden_layers", "layer_types", "mlp_layer_types",
           "gating_types", "num_experts", "num_attention_heads",
           "num_attention_heads_per_layer", "num_key_value_heads",
           "vocab_size"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The stand-in root and, added to it as new files and entries, the toy
    configuration, its traffic, its limits and its cell."""
    tmp = standin.make_root(str(tmp_path_factory.mktemp("laguna")))
    with open(os.path.join(tmp, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    home = bench["paths"][0]
    for sub, name, obj in [("configs", "laguna-toy", laguna_toy.CONFIG),
                           ("traffic", "laguna-toy-train", laguna_toy.JOB),
                           ("limits", CELL, laguna_toy.LIMITS)]:
        standin._write(os.path.join(tmp, home, sub, name + ".json"), obj)
    bench["configs"].append({
        "name": "laguna-toy", "source": laguna_toy.CONFIG["source"],
        "file": f"{home}/configs/laguna-toy.json", "reduced": [],
        "why": "stand-in"})
    bench["workloads"].append({
        "name": CELL, "config": "laguna-toy", "traffic": "laguna-toy-train",
        "chips": 1, "why": "stand-in"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if REAL in metric.get("workloads", []):
            metric["workloads"].append(CELL)
    standin._write(os.path.join(tmp, "BENCHMARK.json"), bench)
    return tmp


def _run(root, trace=0, **options):
    options.setdefault("kind_options", {"hooks": {"step": lambda f: f}})
    return run.run_cell(root, CELL, 2**31 + 97, 1, trace, require_tpu=False,
                        t_start=time.time(), **options)


def test_leaves_and_program_parameters_pair_one_to_one():
    import jax
    import jax.numpy as jnp

    config, job = laguna_toy.CONFIG, laguna_toy.JOB
    lm, _ = arch.program(config, job, None)
    params = jax.eval_shape(
        lambda: lm.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"])
    held = {arch.leaf_name(path): leaf.shape for path, leaf in
            jax.tree_util.tree_flatten_with_path(params)[0]}
    specs = {name: shape for name, shape, _ in arch.leaf_specs(config)}
    assert set(held) == set(specs)
    assert len(held) == len(jax.tree_util.tree_leaves(params))
    for name, shape in specs.items():
        assert int(jnp.prod(jnp.asarray(shape))) == int(
            jnp.prod(jnp.asarray(held[name]))), name
    # The heads differ by layer, the KV heads and a head's width do not.
    assert held["layer_0.q"] == (64, 4, 16) and held["layer_1.q"] == (64, 6, 16)
    assert held["layer_1.k"] == (64, 2, 16) and held["layer_1.o"] == (6, 16, 64)
    assert held["layer_4.head_gate"] == (64, 4)
    assert "layer_1.router_bias" not in held
    with pytest.raises(KeyError, match="no benchmark leaf"):
        arch.leaf_name((jax.tree_util.DictKey("layer_1"),
                        jax.tree_util.DictKey("moe"),
                        jax.tree_util.DictKey("router"),
                        jax.tree_util.DictKey("bias")))


def test_the_toy_cell_is_correct_and_counts_its_rows(root):
    from covalent_tpu_plugin.obs import modelstats

    before = modelstats.totals()
    result = _run(root)
    assert result["correct"] is True, result["compared"]
    assert set(result["metrics"]) == {"train_tok_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    json.dumps(result)
    # In this process (the hook) the step's counts land in this registry:
    # 128 tokens x 3 choices x 4 of 16 held = 96 rows in each of the four
    # sparse layers, under even routing.
    totals = modelstats.totals()
    steps = totals["steps"] - before.get("steps", 0)
    assert steps >= 3
    dropped = totals["rows"]["dropped"] - before.get("rows", {}).get(
        "dropped", 0)
    rows = totals["rows"]["held"] - before.get("rows", {}).get("held", 0)
    assert dropped == 0
    assert 0.5 * 4 * 96 < rows / steps < 2 * 4 * 96


def _other_model(**changes):
    """A ``loss_fn`` hook that runs the program's loss on the program's
    model built with one setting changed: what a fault in the block would
    compute.  ``changes`` maps an attention type's name (or ``routed``) to
    the fields replaced in it."""

    def hook(loss_fn):
        from covalent_tpu_plugin.models import TransformerLM

        sound = arch.model_config(
            laguna_toy.CONFIG, max_seq=laguna_toy.JOB["sequence"],
            attention=laguna_toy.JOB["attention"], remat=True,
            remat_prevent_cse=True)
        types = tuple(
            dataclasses.replace(t, **changes.get(t.name, {}))
            for t in sound.attention_types)
        routed = dataclasses.replace(sound.routed, **changes.get("routed", {}))
        other = TransformerLM(dataclasses.replace(
            sound, attention_types=types, routed=routed))

        def broken(params, apply_fn, batch):
            return loss_fn(params, other.apply, batch)

        return broken

    return hook


@pytest.mark.parametrize("options,fails", [
    ({"kind_options": {"hooks": {"step": _unchanged_state}}}, "delta_gap"),
    ({"kind_options": {"hooks": {"loss_fn": _half_batch}}}, "grad_gap"),
    ({"control": 1}, None),
    ({"kind_options": {"hooks": {"loss_fn": _other_model(
        attn_sliding={"sliding_window": None})}}}, None),
    ({"kind_options": {"hooks": {"loss_fn": _other_model(
        attn_full={"sliding_window": 16})}}}, None),
    ({"kind_options": {"hooks": {"loss_fn": _other_model(
        attn_full={"rope_share": 1.0})}}}, None),
    ({"kind_options": {"hooks": {"loss_fn": _other_model(
        attn_full={"gate": False}, attn_sliding={"gate": False})}}}, None),
    ({"kind_options": {"hooks": {"loss_fn": _other_model(
        routed={"score": "sigmoid"})}}}, None),
    ({"kind_options": {"hooks": {"loss_fn": _other_model(
        routed={"routed_scaling": 1.0})}}}, None),
], ids=["unchanged-state", "half-batch", "bfloat16-control",
        "window-ignored-in-a-window-layer", "window-applied-in-a-full-layer",
        "full-rotary-where-half-is-asked", "gate-left-out",
        "sigmoid-for-softmax", "routed-scaling-left-out"])
def test_faults_and_the_bfloat16_control_are_not_correct(root, options, fails):
    result = _run(root, **options)
    assert result["correct"] is False, result["compared"]
    if fails:
        pair = result["compared"][fails]
        assert pair["value"] is None or pair["value"] > pair["limit"]


def test_the_configuration_is_the_catalog_row_cut_as_it_says():
    import jax
    import jax.numpy as jnp

    cell = spec.load_cell(REPO, REAL)
    config = cell["config"]
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == "laguna-s-2.1-ep32-5l"][0]
    assert set(entry["reduced"]) == set(config["reduced"]) == REDUCED
    for key, value in ROW.items():
        if key in REDUCED:
            assert config[key] != value, key
            if not isinstance(value, list):
                assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    for key in ("layer_types", "mlp_layer_types", "gating_types"):
        assert config[key] == ROW[key][:5], key
    assert (config["num_hidden_layers"], config["num_experts"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["vocab_size"]) == (5, 8, 6, 1, 12544)
    # An eighth of the heads of each type, of the KV heads, of the rows.
    assert config["num_attention_heads_per_layer"] == [
        h // 8 for h in ROW["num_attention_heads_per_layer"][:5]]
    assert config["router_width"] == 256 and config["held_experts"] == [0, 8]
    assert len(config["assumed"]) >= 9 and "32 chips" in config["deployment"]
    # ``parameters`` is the leaves' count, and the program builds as many.
    assert "568.0 M" in config["parameters"]["together"]
    assert weights.parameter_count(config) == 567_957_504
    job = cell["traffic"]
    lm, _ = arch.program(config, job, None)
    params = jax.eval_shape(
        lambda: lm.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"])
    assert sum(leaf.size for leaf in jax.tree_util.tree_leaves(params)) == (
        567_957_504)
    assert (job["batch"], job["sequence"], job["vocab_chunk"],
            job["learning_rate"]) in ((1, 16384, 8192, 7.3e-6),
                                      (1, 16384, 8192, 1e-6))
    assert set(cell["limits"]) == {"loss_gap", "grad_gap", "delta_gap"}


def test_needed_work_against_hand_arithmetic():
    cell = spec.load_cell(REPO, REAL)
    config, job = cell["config"], cell["traffic"]
    full = 3072 * 768 * 2 + 3072 * 128 * 2 + 3072 * 6
    sliding = 3072 * 1152 * 2 + 3072 * 128 * 2 + 3072 * 9
    assert (full, sliding) == (5_523_456, 7_891_968)
    expert = 3 * 3072 * 1024
    touched = (3072 * 12544                              # the head
               + 2 * full + 3 * sliding
               + 3 * 3072 * 12288                        # the dense layer
               + 4 * (3072 * 256 + expert + expert * 10 * 8 / 256))
    assert arch.matmul_parameters(config) == pytest.approx(touched)
    assert touched == pytest.approx(239.0e6, rel=2e-3)
    pairs = 16384 * 16385 // 2
    band = 512 * 513 // 2 + (16384 - 512) * 512
    assert work.visible_pairs(16384, 512) == band
    assert arch.attention_forward_flops(config, 16384, FULL) == (
        2 * 4 * 128 * 6 * pairs)
    assert arch.attention_forward_flops(config, 16384, SLIDING) == (
        3 * 4 * 128 * 9 * band)
    per_token = 3 * (2 * touched + (
        2 * 4 * 128 * 6 * pairs + 3 * 4 * 128 * 9 * band) / 16384)
    assert arch.train_flops_per_token(config, job) == pytest.approx(per_token)
    assert per_token == pytest.approx(1.61e9, rel=5e-3)
    assert arch.kernel_work(config, job, "attn_full")["flops"] == (
        3 * 2 * 4 * 128 * 6 * pairs)
    sliding_work = arch.kernel_work(config, job, "attn_sliding")
    assert sliding_work["flops"] == 3 * 3 * 4 * 128 * 9 * band
    assert sliding_work["bytes"] == 6 * 3 * (9 + 1) * 16384 * 128 * 2
    assert arch.expected_held_rows(config, 16384) == 5120
    experts = arch.kernel_work(config, job, "experts")
    assert experts["flops"] == 4 * 3 * 2 * expert * 5120
    assert experts["flops"] == pytest.approx(1.16e12, rel=5e-3)
    peak = work.peaks("TPU v5 lite")
    for part in ("attn_full", "attn_sliding", "experts"):
        assert work.roofline_seconds(
            arch.kernel_work(config, job, part), peak)[1] == "compute"
    with pytest.raises(KeyError):
        arch.kernel_work(config, job, "flash_fwd")
    assert archs.load(config) is arch


def test_the_scopes_rooflines_read_the_traced_scopes_alone():
    from benchmarks.suite.readers import scope_ms, scope_roofline

    cell = spec.load_cell(REPO, REAL)
    by_name = {m["name"]: m for m in cell["per_layer"]}
    lead = "jit(step)/loss/jvp(LM)/layer_1/attention/"
    trace = {
        "scopes": {lead + "attn_sliding/flash_fwd": 0.004,
                   "jit(step)/loss/transpose(jvp(LM))/layer_1/attention/"
                   "attn_sliding/flash_bwd_dq": 0.008,
                   "jit(step)/loss/jvp(LM)/layer_0/attention/attn_full/"
                   "flash_fwd": 0.010,
                   lead + "attn_gate/mul": 0.001, lead + "q_proj/dot": 0.5},
        "ops": {},
    }
    context = {"cell": cell, "require_tpu": True, "chips": 1, "trace": trace,
               "trace_steps": 2, "device": {"kind": "TPU v5 lite"}}
    assert scope_ms.read(
        context, **by_name["attn_sliding_ms.train"]["args"]
    ) == pytest.approx(6.0)
    assert scope_ms.read(
        context, **by_name["attn_full_ms.train"]["args"]
    ) == pytest.approx(5.0)
    needed = arch.kernel_work(cell["config"], cell["traffic"], "attn_sliding")
    assert scope_roofline.read(
        context, **by_name["attn_sliding_roofline.train"]["args"]
    ) == pytest.approx(100 * needed["flops"] / 197e12 / 0.006)
    # A program without the scope (the parent of the PR that brought it):
    # nothing, and no error.
    trace["scopes"] = {lead + "q_proj/dot": 0.5}
    for name in ("attn_full_ms.train", "attn_sliding_ms.train",
                 "attn_full_roofline.train", "attn_sliding_roofline.train"):
        reader = {"scope_ms": scope_ms, "scope_roofline": scope_roofline}[
            by_name[name]["reader"]]
        assert reader.read(context, **by_name[name]["args"]) is None


def _reference(fn, *args):
    import jax

    with jax.default_matmul_precision("highest"):
        return fn(*args)


def test_the_shares_of_experts_and_of_heads_add_up_to_the_uncut_layer():
    """The guide's share test.  Sparse layer: the four shares' held parts,
    the shared expert counted once, are what the uncut reference gives.
    Attention: each KV head's share (its query heads, its rows of ``W_o``)
    computed alone by the program, summed, is the uncut attention."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.core import meta

    from covalent_tpu_plugin.models.moe import RoutedExperts
    from covalent_tpu_plugin.models.transformer import Attention

    x = jax.random.normal(jax.random.PRNGKey(11), (1, 64, 64))
    uncut = laguna_toy.with_sizes(held_experts=[0, 16])
    w = laguna_toy.layer_leaves(uncut, SEED, 1)
    want = _reference(arch.experts, x[0], w, uncut)
    shared = _reference(
        arch.gated, x[0], w["shared_wg"], w["shared_wu"], w["shared_wd"])
    total, rows = shared, 0.0
    for first in range(0, 16, 4):
        config = laguna_toy.with_sizes(held_experts=[first, 4])
        module = RoutedExperts(arch.model_config(config, max_seq=64))
        params = laguna_toy.fill(
            module.init(jax.random.PRNGKey(0), x)["params"],
            ("layer_1", "moe"), config, SEED)
        # The share's slice of the uncut layer's experts; the router and
        # the shared expert are every chip's alike (same names, same seed).
        for name in ("wg", "wu", "wd"):
            params["experts"][name] = w[f"experts_{name}"][first:first + 4]
        out, sown = module.apply(
            {"params": params}, x, mutable=["intermediates"])
        total = total + (out[0] - shared)
        rows += float(sown["intermediates"]["experts"]["moe_stats"][0][0])
    np.testing.assert_allclose(total, want, atol=3e-5)
    assert rows == 64 * 3  # every (token, choice) pair got a row somewhere

    for layer, kind in ((0, FULL), (1, SLIDING)):
        w = laguna_toy.layer_leaves(laguna_toy.CONFIG, SEED, layer)
        want = _reference(
            arch.gated_attention, x[0], w, laguna_toy.CONFIG, kind)
        heads = laguna_toy.CONFIG["num_attention_heads_per_layer"][layer]
        group, total = heads // 2, 0.0
        for share in range(2):  # one KV head with its query heads each
            config = dict(
                laguna_toy.CONFIG, num_key_value_heads=1,
                num_attention_heads=2,
                num_attention_heads_per_layer=[2, 3, 3, 3, 2])
            cfg = arch.model_config(config, max_seq=64, attention="flash")
            module = Attention(cfg, kind=cfg.attention_of(layer))
            params = meta.unbox(
                module.init(jax.random.PRNGKey(0), x)["params"])
            mine = slice(share * group, (share + 1) * group)
            cols = lambda a, n: a.reshape(64, n, -1)[:, mine]  # noqa: E731
            one = slice(share, share + 1)
            params = jax.tree.map(
                lambda held, mine: jnp.asarray(mine).reshape(held.shape),
                params, {
                    "q_proj": {"kernel": cols(w["q"], heads)},
                    "k_proj": {"kernel": w["k"].reshape(64, 2, 16)[:, one]},
                    "v_proj": {"kernel": w["v"].reshape(64, 2, 16)[:, one]},
                    "gate_proj": {"kernel": w["head_gate"][:, mine]},
                    "out_proj": {
                        "kernel": w["o"].reshape(heads, 16, 64)[mine]},
                })
            total = total + module.apply({"params": params}, x)[0]
        np.testing.assert_allclose(total, want, atol=3e-5)
