"""``xing4_0`` behind the ``model_type`` seam: its leaves against the
program's parameters, its cell at toy widths through the harness (sound,
under both kept faults and under the bfloat16 control), the configuration
file against the catalog row it was cut from, and its needed work against
hand arithmetic."""

from __future__ import annotations

import json
import os
import time

import pytest

from benchmarks.suite import archs, run, spec, weights, work
from benchmarks.suite.archs import xing4_0 as arch
from tests.benchsuite import standin, xing4_toy
from tests.benchsuite.test_suite_run import _half_batch, _unchanged_state

REPO = standin.REPO
CELL = "xing4-toy.train"
REAL = "xing4-29b.train-8k"
#: The catalog row's ``config`` (model-configs guide, ``Xing4.0-29B-A4B``).
ROW = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096, "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 131072,
}
REDUCED = {"num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "num_attention_heads", "num_key_value_heads", "vocab_size",
           "num_nextn_predict_layers"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The stand-in root and, added to it as new files and entries, the toy
    configuration, its traffic, its limits and its cell."""
    tmp = standin.make_root(str(tmp_path_factory.mktemp("xing4")))
    with open(os.path.join(tmp, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    home = bench["paths"][0]
    for sub, name, obj in [("configs", "xing4-toy", xing4_toy.CONFIG),
                           ("traffic", "xing4-toy-train", xing4_toy.JOB),
                           ("limits", CELL, xing4_toy.LIMITS)]:
        standin._write(os.path.join(tmp, home, sub, name + ".json"), obj)
    bench["configs"].append({
        "name": "xing4-toy", "source": xing4_toy.CONFIG["source"],
        "file": f"{home}/configs/xing4-toy.json", "reduced": [],
        "why": "stand-in"})
    bench["workloads"].append({
        "name": CELL, "config": "xing4-toy", "traffic": "xing4-toy-train",
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

    config, job = xing4_toy.CONFIG, xing4_toy.JOB
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
    with pytest.raises(KeyError, match="no benchmark leaf"):
        arch.leaf_name((jax.tree_util.DictKey("layer_0"),
                        jax.tree_util.DictKey("mlp"),
                        jax.tree_util.DictKey("bias")))


def test_a_matrix_of_constants_is_the_modules_own_rule():
    import jax.numpy as jnp

    key = weights.seed_key(2**31 + 5)
    specs = {name: (shape, init)
             for name, shape, init in weights.leaf_specs(xing4_toy.CONFIG)}
    shape, init = specs["layer_1.hc_mlp.b_res"]
    hash(init)  # a jitted generator takes it as a static argument
    got = weights.leaf(key, "layer_1.hc_mlp.b_res", shape, init, jnp.float32,
                       arch)
    assert (got == 2.0 * jnp.eye(4)).all()
    shape, init = specs["layer_1.hc_mlp.alpha"]
    assert (weights.leaf(key, "x", shape, init, jnp.float32, arch)
            == 0.01).all()
    shape, init = specs["layer_1.router_bias"]
    assert not weights.leaf(key, "x", shape, init, jnp.float32, arch).any()
    # A drawn leaf is the harness's own draw: same name, same seed.
    shape, init = specs["layer_1.q_a"]
    assert (weights.leaf(key, "layer_1.q_a", shape, init, jnp.float32, arch)
            == weights.leaf(key, "layer_1.q_a", shape, init, jnp.float32)
            ).all()


def test_the_toy_cell_is_correct_and_counts_its_rows(root):
    from covalent_tpu_plugin.obs import modelstats

    before = modelstats.totals().get("steps", 0)
    result = _run(root)
    assert result["correct"] is True, result["compared"]
    assert set(result["metrics"]) == {"train_tok_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    json.dumps(result)
    # In this process (the hook) the step's counts land in this registry:
    # 64 tokens x 2 choices x 2 of 8 held = 32 rows in the one expert layer.
    totals = modelstats.totals()
    steps = totals["steps"] - before
    assert steps >= 3
    assert totals["rows"]["dropped"] == 0
    assert 0.5 * 32 < totals["rows"]["held"] / totals["steps"] < 2 * 32


@pytest.mark.parametrize("options,fails", [
    ({"kind_options": {"hooks": {"step": _unchanged_state}}}, "delta_gap"),
    ({"kind_options": {"hooks": {"loss_fn": _half_batch}}}, "grad_gap"),
    ({"control": 1}, None),
])
def test_faults_and_the_bfloat16_control_are_not_correct(root, options, fails):
    result = _run(root, **options)
    assert result["correct"] is False
    if fails:
        pair = result["compared"][fails]
        assert pair["value"] is None or pair["value"] > pair["limit"]


def test_the_configuration_is_the_catalog_row_cut_as_it_says():
    cell = spec.load_cell(REPO, REAL)
    config = cell["config"]
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == "xing4-29b-a4b-ep8-5l"][0]
    assert set(entry["reduced"]) == set(config["reduced"]) == REDUCED
    for key, value in ROW.items():
        if key in REDUCED:
            assert config["published"][key] == value, key
            assert config[key] != value, key
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["n_routed_experts"], config["num_attention_heads"],
            config["vocab_size"], config["num_nextn_predict_layers"]) == (
                5, 1, 8, 4, 16384, 0)
    assert config["router_width"] == 64 and config["held_experts"] == [0, 8]
    assert config["assumed"] and "eight chips" in config["deployment"]
    assert abs(weights.parameter_count(config) - 656.1e6) < 1e6
    job = cell["traffic"]
    assert (job["batch"], job["sequence"], job["vocab_chunk"]) == (
        1, 8192, 8192)


def test_needed_work_against_hand_arithmetic():
    cell = spec.load_cell(REPO, REAL)
    config, job = cell["config"], cell["traffic"]
    attention = (3584 * 768 + 768 * 4 * 192 + 3584 * 576 + 512 * 4 * 256
                 + 4 * 128 * 3584)
    assert attention == 7_766_016
    mixing = 2 * 4 * 3584 * 24
    expert = 3 * 3584 * 1024
    weights_touched = (
        3584 * 16384                                   # the head
        + 5 * (attention + mixing)
        + 3 * 3584 * 9216                              # the dense layer
        + 4 * (3584 * 64 + expert + expert * 4 * 8 / 64))
    assert arch.matmul_parameters(config) == pytest.approx(weights_touched)
    assert weights_touched == pytest.approx(263.6e6 + 5 * mixing, rel=1e-3)
    pairs = 8192 * 8193 // 2
    per_layer = 2 * (192 + 128) * 4 * pairs
    assert arch.attention_forward_flops(config, 8192) == per_layer
    assert arch.train_flops_per_token(config, job) == pytest.approx(
        3 * (2 * weights_touched + 5 * per_layer / 8192))
    fwd = arch.kernel_work(config, job, "flash_fwd")
    dkdv = arch.kernel_work(config, job, "flash_bwd_dkdv")
    dq = arch.kernel_work(config, job, "flash_bwd_dq")
    assert fwd["flops"] == 5 * per_layer
    assert dkdv["flops"] == 5 * 2 * (192 + 2 * 128) * 4 * pairs
    assert dq["flops"] == 5 * 2 * 192 * 4 * pairs
    assert fwd["flops"] + dkdv["flops"] + dq["flops"] == 3 * fwd["flops"]
    assert fwd["bytes"] == 5 * 2 * 8192 * 4 * (192 + 128) * 2
    assert arch.expected_held_rows(config, 8192) == 4096
    experts = arch.kernel_work(config, job, "experts")
    assert experts["flops"] == 4 * 3 * 2 * expert * 4096
    streams = arch.kernel_work(config, job, "hc")
    assert streams["bytes"] == 10 * 2 * 2 * (4 * 3584 * 8192 * 2)
    peak = work.peaks("TPU v5 lite")
    assert work.roofline_seconds(experts, peak)[1] == "compute"
    assert work.roofline_seconds(streams, peak)[1] == "memory"
    with pytest.raises(KeyError):
        arch.kernel_work(config, job, "no_such_kernel")
    assert archs.load(config) is arch


def test_scope_roofline_adds_the_named_kernel_to_the_scopes_time():
    from benchmarks.suite.readers import scope_roofline

    cell = spec.load_cell(REPO, REAL)
    trace = {
        "scopes": {"jit(step)/loss/jvp(LM)/layer_1/moe/experts/gather": 0.030,
                   "jit(step)/loss/jvp(LM)/layer_1/hc_mlp.mix/hc/mul": 0.100,
                   "": 0.020},
        "ops": {"jit_step/ragged-dot-none(tpu_custom_call)": 0.020,
                "jit_step/fusion": 0.5},
    }
    context = {"cell": cell, "require_tpu": True, "chips": 1, "trace": trace,
               "trace_steps": 2, "device": {"kind": "TPU v5 lite"}}
    needed = arch.kernel_work(cell["config"], cell["traffic"], "experts")
    least = needed["flops"] / 197e12
    got = scope_roofline.read(
        context, "experts", under=["experts"],
        ops=["ragged-dot-none(tpu_custom_call)"])
    assert got == pytest.approx(100 * least / ((0.030 + 0.020) / 2))
    alone = scope_roofline.read(context, "experts", under=["experts"])
    assert alone == pytest.approx(100 * least / (0.030 / 2))
    streams = arch.kernel_work(cell["config"], cell["traffic"], "hc")
    assert scope_roofline.read(context, "hc", under=["hc"]) == pytest.approx(
        100 * (streams["bytes"] / 819e9) / (0.100 / 2))
    # No such scope, no such part, or no chip: nothing, and no error.
    assert scope_roofline.read(context, "experts", under=["nowhere"]) is None
    assert scope_roofline.read(context, "no_part", under=["hc"]) is None
    context["require_tpu"] = False
    assert scope_roofline.read(context, "hc", under=["hc"]) is None
