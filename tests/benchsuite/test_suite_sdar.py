"""``sdar_moe`` behind the ``model_type`` seam: its leaves against the
program's parameters, the program's block-diffusion loss and gradients
against the plain reference, its cell at toy widths through the harness
(sound, under both kept faults, under the bfloat16 control and under one
planted fault for each mechanism that is new), the configuration file
against the catalog row it was cut from, its needed work against hand
arithmetic, and the guide's share test for the experts."""

from __future__ import annotations

import dataclasses
import json
import os
import time

import pytest

from benchmarks.suite import archs, reference, run, spec, weights, work
from benchmarks.suite.archs import sdar_moe as arch
from tests.benchsuite import sdar_toy, standin

REPO = standin.REPO
CELL = "sdar-toy.train"
REAL = "sdar-30b.train-8k"
SEED = 2**31 + 4201
#: The catalog row's ``config`` (model-configs guide, ``SDAR-30B-A3B-Chat``).
ROW = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}
REDUCED = {"num_hidden_layers", "num_experts", "vocab_size"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The stand-in root and, added to it as new files and entries, the toy
    configuration, its traffic, its limits and its cell."""
    tmp = standin.make_root(str(tmp_path_factory.mktemp("sdar")))
    with open(os.path.join(tmp, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    home = bench["paths"][0]
    for sub, name, obj in [("configs", "sdar-toy", sdar_toy.CONFIG),
                           ("traffic", "sdar-toy-train", sdar_toy.JOB),
                           ("limits", CELL, sdar_toy.LIMITS)]:
        standin._write(os.path.join(tmp, home, sub, name + ".json"), obj)
    bench["configs"].append({
        "name": "sdar-toy", "source": sdar_toy.CONFIG["source"],
        "file": f"{home}/configs/sdar-toy.json", "reduced": [],
        "why": "stand-in"})
    bench["workloads"].append({
        "name": CELL, "config": "sdar-toy", "traffic": "sdar-toy-train",
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


def _other_model(**changes):
    """The program's model at the toy sizes with some settings changed:
    what a fault in the block would compute."""
    from covalent_tpu_plugin.models import TransformerLM

    return TransformerLM(dataclasses.replace(arch.model_config(
        sdar_toy.CONFIG, max_seq=2 * sdar_toy.JOB["sequence"],
        attention=sdar_toy.JOB["attention"], remat=True,
        remat_prevent_cse=True), **changes))


def test_leaves_and_program_parameters_pair_one_to_one():
    import jax
    import jax.numpy as jnp

    config = sdar_toy.CONFIG
    lm, _ = arch.program(config, sdar_toy.JOB, None)
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
    assert held["layer_0.q_norm"] == (16,) and held["layer_0.k_norm"] == (16,)
    assert held["layer_0.q"] == (64, 4, 16)
    assert held["layer_0.k"] == (64, 2, 16)
    assert not [n for n in held if "shared" in n or "bias" in n]
    with pytest.raises(KeyError, match="no benchmark leaf"):
        arch.leaf_name((jax.tree_util.DictKey("layer_1"),
                        jax.tree_util.DictKey("moe"),
                        jax.tree_util.DictKey("shared_expert")))


@pytest.fixture(scope="module")
def toy():
    """Rows of the toy traffic, the program's model and loss, its
    parameters filled with the seed's weights, and the reference's float32
    loss and gradient norms on those rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    config = sdar_toy.CONFIG
    tokens = jnp.asarray(np.random.default_rng(3).integers(
        0, config["vocab_size"], (2, sdar_toy.JOB["sequence"] + 1)), jnp.int32)
    lm, loss_fn = arch.program(config, sdar_toy.JOB, None)
    template = jax.eval_shape(
        lambda: lm.init(jax.random.PRNGKey(0), tokens[:, :-1])["params"])
    w = reference.all_leaves(config, SEED, jnp.float32)
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: w[arch.leaf_name(path)].reshape(leaf.shape),
        template)
    out = {"tokens": tokens, "lm": lm, "loss_fn": loss_fn, "params": params,
           "reference": _reference(w, tokens, jnp.float32)}
    out["program"] = _program(out)
    return out


def _reference(w, tokens, dtype):
    """The reference's loss and every leaf's gradient norm."""
    import jax
    import jax.numpy as jnp

    def loss(w):
        return reference.batch_loss(w, tokens, sdar_toy.CONFIG, dtype)

    with jax.default_matmul_precision("highest"):
        value, grads = jax.jit(jax.value_and_grad(loss))(w)
    return float(value), {n: float(jnp.linalg.norm(g.astype(jnp.float32)))
                          for n, g in grads.items()}


def _program(toy, model=None, loss_fn=None):
    """The program's loss, counts and every leaf's gradient norm."""
    import jax
    import jax.numpy as jnp

    apply = (model or toy["lm"]).apply
    loss_fn = loss_fn or toy["loss_fn"]
    (loss, counts), grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, apply, {"tokens": toy["tokens"]}),
        has_aux=True))(toy["params"])
    return float(loss), counts, {
        arch.leaf_name(path): float(jnp.linalg.norm(g))
        for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]}


def _gaps(program, reference_readings):
    (loss, _, norms), (value, ref) = program, reference_readings
    return abs(loss - value) / value, max(
        abs(norms[n] - ref[n]) / ref[n] for n in ref)


#: float32 on both sides: the loss agrees to its summation order over a
#: few hundred terms, every leaf's gradient norm to a few of the kernels'
#: and the fused loss's reassociations; bfloat16 rounds at 4e-3.
LOSS_GAP, GRAD_GAP = 1e-5, 1e-4


def test_program_loss_and_gradients_match_the_reference(toy):
    """At toy widths in float32 the program (flash kernels under the block
    mask, the fused loss with its rows' weights, the noise drawn in the
    step) and the reference agree within ``LOSS_GAP`` and ``GRAD_GAP``;
    the step counts every masked token, about half of them under
    U[1e-3, 1]."""
    program = toy["program"]
    loss_gap, grad_gap = _gaps(program, toy["reference"])
    assert loss_gap < LOSS_GAP and grad_gap < GRAD_GAP, (loss_gap, grad_gap)
    counts = program[1]
    masked = sum(int((arch.noised(sdar_toy.CONFIG, row)[1] > 0).sum())
                 for row in toy["tokens"][:, :-1])
    assert float(counts["masked"]) == masked
    assert 0.3 * 64 < masked < 0.7 * 64
    assert counts["moe"].shape == (1, 4)


def _unweighted(loss_fn):
    """The loss with every masked token weighing 1 in place of 1 / t."""

    def broken(params, apply_fn, batch):
        from covalent_tpu_plugin.ops import xent

        plain = xent.fused_cross_entropy

        def ones(x, w, labels, chunk=8192, weights=None):
            return plain(x, w, labels, chunk, (weights > 0) / weights.size)

        xent.fused_cross_entropy = ones
        try:
            return loss_fn(params, apply_fn, batch)
        finally:
            xent.fused_cross_entropy = plain

    return broken


@pytest.mark.parametrize("fault", [
    "bfloat16-reference", "another-block-length-in-the-mask",
    "qk-norm-left-out", "noise-weight-left-out"])
def test_a_lower_precision_or_a_fault_misses_the_tolerances(toy, fault):
    """What each tolerance must catch: the reference one precision down
    (the control), and a fault planted in each mechanism that is new."""
    import jax.numpy as jnp

    if fault == "bfloat16-reference":
        w = reference.all_leaves(sdar_toy.CONFIG, SEED, jnp.bfloat16)
        loss_gap, grad_gap = _gaps(toy["program"], _reference(
            w, toy["tokens"], jnp.bfloat16))
        assert loss_gap > LOSS_GAP and grad_gap > GRAD_GAP, (
            loss_gap, grad_gap)
        return
    if fault == "noise-weight-left-out":
        program = _program(toy, loss_fn=_unweighted(toy["loss_fn"]))
    else:
        changes = {"another-block-length-in-the-mask": {"diffusion_block": 8},
                   "qk-norm-left-out": {"qk_norm": False}}[fault]
        program = _program(toy, model=_other_model(**changes))
    loss_gap, grad_gap = _gaps(program, toy["reference"])
    assert loss_gap > LOSS_GAP or grad_gap > GRAD_GAP, (loss_gap, grad_gap)


def test_half_of_the_noisy_rows_is_a_planted_fault():
    """``positions`` keeps the first that many noisy rows (the
    ``half_batch`` fault on one row): the count and the sum are theirs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    config = sdar_toy.CONFIG
    row = jnp.asarray(np.random.default_rng(3).integers(
        0, config["vocab_size"], sdar_toy.JOB["sequence"] + 1), jnp.int32)
    w = reference.all_leaves(config, SEED, jnp.float32)

    @jax.jit
    def losses(w):
        with jax.default_matmul_precision("highest"):
            return (arch.sequence_loss(w, row, config, jnp.float32)[0],
                    arch.sequence_loss(w, row, config, jnp.float32, 16)[0])

    whole, half = (float(x) for x in losses(w))
    _, weight = arch.noised(config, row[:-1])
    assert 0 < half < whole and float(weight[16:].sum()) > 0


def test_the_toy_cell_is_correct_and_counts_its_tokens(root):
    from covalent_tpu_plugin.obs import modelstats

    before = modelstats.totals()
    result = _run(root)
    assert result["correct"] is True, result["compared"]
    assert set(result["metrics"]) == {"train_tok_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    json.dumps(result)
    # In this process (the hook) the step's counts land in this registry:
    # 2 rows x 32 tokens, about half masked; 128 positions x 3 choices x 4
    # of 16 held = 96 rows in the one layer, under even routing.
    totals = modelstats.totals()
    steps = totals["steps"] - before.get("steps", 0)
    assert steps >= 3
    masked = totals["tokens"]["masked"] - before.get("tokens", {}).get(
        "masked", 0)
    assert 0.3 * 64 < masked / steps < 0.7 * 64
    rows = totals["rows"]["held"] - before.get("rows", {}).get("held", 0)
    assert 0.5 * 96 < rows / steps < 2 * 96


def test_the_configuration_is_the_catalog_row_cut_as_it_says():
    import jax
    import jax.numpy as jnp

    cell = spec.load_cell(REPO, REAL)
    config = cell["config"]
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == "sdar-30b-a3b-ep8-6l"][0]
    assert set(entry["reduced"]) == set(config["reduced"]) == REDUCED
    for key, value in ROW.items():
        if key in REDUCED:
            assert config[key] != value, key
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (6, 16, 18992)
    assert config["router_width"] == 128 and config["held_experts"] == [0, 16]
    assert config["mask_token_id"] == 18991 and config["block_length"] == 4
    assert len(config["assumed"]) >= 6 and "8 chips" in config["deployment"]
    # ``parameters`` is the leaves' count, and the program builds as many.
    assert "645.6 M" in config["parameters"]["together"]
    assert weights.parameter_count(config) == 645_623_296
    job = cell["traffic"]
    lm, _ = arch.program(config, job, None)
    params = jax.eval_shape(
        lambda: lm.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"])
    assert sum(leaf.size for leaf in jax.tree_util.tree_leaves(params)) == (
        645_623_296)
    assert (job["batch"], job["sequence"], job["vocab_chunk"],
            job["learning_rate"]) == (1, 8192, 8192, 1e-6)
    assert lm.config.max_seq == 16384
    assert set(cell["limits"]) == {"loss_gap", "grad_gap", "delta_gap"}


def test_needed_work_against_hand_arithmetic():
    cell = spec.load_cell(REPO, REAL)
    config, job = cell["config"], cell["traffic"]
    # S x B noisy-to-noisy pairs, the clean keys before a noisy query's
    # block, up to a clean query's block's end: S^2 + S x B.
    assert arch.visible_pairs(config, 8192) == 8192 ** 2 + 8192 * 4
    assert arch.visible_pairs(config, 8192) == pytest.approx(67.1e6, rel=1e-3)
    per_layer = (2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * 128
                 + 3 * 2048 * 768 * 8 * 16 / 128)
    forward = (2 * (16384 * 6 * per_layer + 8192 * 2048 * 18992)
               + 6 * 4 * 128 * 32 * (8192 ** 2 + 8192 * 4))
    assert arch.train_flops_per_token(config, job) == pytest.approx(
        3 * forward / 8192)
    assert arch.train_flops_per_token(config, job) == pytest.approx(
        4.37e9, rel=2e-3)
    bd = arch.kernel_work(config, job, "attn_bd")
    assert bd["flops"] == 3 * 6 * 4 * 128 * 32 * (8192 ** 2 + 8192 * 4)
    assert bd["flops"] == pytest.approx(19.80e12, rel=1e-3)
    assert bd["bytes"] == 6 * 16384 * 6 * 128 * 2 * (32 + 4)
    assert arch.expected_held_rows(config, 16384) == 16384
    experts = arch.kernel_work(config, job, "experts")
    assert experts["flops"] == 6 * 3 * 2 * 3 * 2048 * 768 * 16384
    peak = work.peaks("TPU v5 lite")
    for part in ("attn_bd", "experts"):
        assert work.roofline_seconds(
            arch.kernel_work(config, job, part), peak)[1] == "compute"
    with pytest.raises(KeyError):
        arch.kernel_work(config, job, "attn_full")
    assert archs.load(config) is arch


#: The arguments the benchmark's existing readers take for the new scope and
#: series, ready for per-layer entries of their own.
ATTN_BD = {"under": [arch.SCOPE]}
MASKED = {"label": "kind", "values": ["masked"]}


def test_the_existing_readers_read_the_traced_scope_alone(monkeypatch):
    from benchmarks.suite.readers import registry_sum, scope_ms, scope_roofline
    from covalent_tpu_plugin import obs
    from covalent_tpu_plugin.obs import modelstats
    from covalent_tpu_plugin.obs.metrics import Registry

    cell = spec.load_cell(REPO, REAL)
    lead = "jit(step)/loss/jvp(LM)/layer_1/attention/"
    trace = {
        "scopes": {lead + "attn_bd/flash_fwd": 0.004,
                   "jit(step)/loss/transpose(jvp(LM))/layer_1/attention/"
                   "attn_bd/flash_bwd_dq": 0.008,
                   "jit(step)/loss/bd_noise/random_bits": 0.0001,
                   lead + "q_proj/dot": 0.5},
        "ops": {},
    }
    context = {"cell": cell, "require_tpu": True, "chips": 1, "trace": trace,
               "trace_steps": 2, "device": {"kind": "TPU v5 lite"}}
    assert scope_ms.read(context, **ATTN_BD) == pytest.approx(6.0)
    needed = arch.kernel_work(cell["config"], cell["traffic"], "attn_bd")
    assert scope_roofline.read(
        context, part=arch.SCOPE, **ATTN_BD
    ) == pytest.approx(100 * needed["flops"] / 197e12 / 0.006)
    # Not the flash metrics of the dense cell, which read every kernel.
    assert not [m["name"] for m in cell["per_layer"]
                if m["name"].startswith("flash_")]
    # A program without the scope (the parent of this configuration):
    # nothing, and no error.
    trace["scopes"] = {lead + "q_proj/dot": 0.5}
    assert scope_ms.read(context, **ATTN_BD) is None
    assert scope_roofline.read(context, part=arch.SCOPE, **ATTN_BD) is None
    # The masked tokens a step, on a registry of its own: nothing before a
    # worker sent its counts home, then their mean over its steps.
    registry = Registry()
    monkeypatch.setattr(obs, "REGISTRY", registry)
    masked = modelstats.WORKER_DIFFUSION_TOKENS_PER_STEP
    assert registry_sum.read(context, metric=masked, **MASKED) is None
    modelstats.absorb_worker({
        "steps": 2, "rows": {"held": 2.0}, "load_ratio": {"peak": 1.0},
        "tokens": {"masked": 8100.0}}, registry=registry)
    assert registry_sum.read(
        context, metric=masked, **MASKED) == pytest.approx(4050.0)


def test_the_shares_of_experts_add_up_to_the_uncut_layer():
    """The guide's share test: eight chips' shares of 16 of 128 experts,
    each computed alone by the program's routed layer, summed, are what
    the uncut reference gives for the whole layer (no shared expert to
    count once)."""
    import jax
    import numpy as np

    from covalent_tpu_plugin.models.moe import RoutedExperts

    x = jax.random.normal(jax.random.PRNGKey(11), (1, 64, 64))
    uncut = sdar_toy.with_sizes(router_width=128, num_experts_per_tok=8,
                                held_experts=[0, 128])
    w = {name.split(".", 1)[1]: weights.leaf(
        weights.seed_key(SEED), name, shape, init, np.float32, arch)
         for name, shape, init in arch.layer_specs(uncut, 1)}
    with jax.default_matmul_precision("highest"):
        want = arch.experts(x[0], w, uncut)
    total, rows = 0.0, 0.0
    for first in range(0, 128, 16):
        config = dict(uncut, held_experts=[first, 16], num_experts=16)
        module = RoutedExperts(arch.model_config(config, max_seq=64))
        params = {"router": {"gate": {"kernel": w["router"]}},
                  "experts": {name: w[f"experts_{name}"][first:first + 16]
                              for name in ("wg", "wu", "wd")}}
        out, sown = module.apply(
            {"params": params}, x, mutable=["intermediates"])
        total = total + out[0]
        rows += float(sown["intermediates"]["experts"]["moe_stats"][0][0])
    np.testing.assert_allclose(total, want, atol=3e-5)
    assert rows == 64 * 8  # every (token, choice) pair got a row somewhere
