"""``laguna`` at toy widths for the CPU tests: every mechanism of the
configuration (two attention types with their own head counts, a window in
one and half-turned YaRN heads in the other, a gate a head, a dense layer
then sparse layers, a softmax router wider than the experts held, a shared
expert) at sizes the interpreter runs in seconds, float32 throughout so
that the bfloat16 control stands apart."""

from __future__ import annotations

CONFIG = {
    "model_type": "laguna",
    "source": "a stand-in for tests, nobody's model",
    "hidden_size": 64, "head_dim": 16, "num_hidden_layers": 5,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
    "layer_types": ["full_attention", "sliding_attention",
                    "sliding_attention", "sliding_attention",
                    "full_attention"],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "mlp_only_layers": [0],
    "gating": "per-head", "gating_types": ["per_head"] * 5,
    "sliding_window": 16, "max_position_embeddings": 64,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 10000, "rope_type": "yarn", "factor": 4,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.1386294361119891,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 100,
                              "partial_rotary_factor": 1}},
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "num_experts": 4,
    "router_width": 16, "held_experts": [4, 4], "num_experts_per_tok": 3,
    "norm_topk_prob": True, "moe_routed_scaling_factor": 2.5,
    "moe_router_logit_softcapping": 0,
    "moe_apply_router_weight_on_input": False, "attention_bias": False,
    "rms_norm_eps": 1e-6, "vocab_size": 128, "initializer_range": 0.05,
    "weight_dtype": "float32", "activation_dtype": "float32",
}
JOB = {
    "kind": "train", "batch": 2, "sequence": 64, "attention": "flash",
    "remat": True, "vocab_chunk": 48, "learning_rate": 1e-3,
    "mesh": {"data": 1}, "feed_batches": 4, "check_steps": 2,
    "warm_steps": 1, "trace_after": 1, "trace_steps": 2,
}
LIMITS = {"loss_gap": 1e-3, "grad_gap": 1e-2, "delta_gap": 1e-2}


def with_sizes(**changes) -> dict:
    """The toy configuration with some sizes changed (``held_experts``
    carries ``num_experts`` with it)."""
    config = dict(CONFIG, **changes)
    config["num_experts"] = config["held_experts"][1]
    return config


def fill(template, prefix: tuple, config: dict, seed: int):
    """The seed's weights in a sub-tree of the program's parameters:
    ``prefix`` is the sub-tree's path in the whole tree (``("layer_1",
    "attention")``), so that ``leaf_name`` finds each leaf's name."""
    import jax
    from flax.core import meta

    from benchmarks.suite import weights
    from benchmarks.suite.archs import laguna as arch

    specs = {name: (shape, init)
             for name, shape, init in weights.leaf_specs(config)}
    key = weights.seed_key(seed)
    lead = tuple(jax.tree_util.DictKey(k) for k in prefix)

    def one(path, leaf):
        name = arch.leaf_name(lead + tuple(path))
        shape, init = specs[name]
        return weights.leaf(key, name, shape, init, leaf.dtype, arch).reshape(
            leaf.shape)

    return jax.tree_util.tree_map_with_path(one, meta.unbox(template))


def layer_leaves(config: dict, seed: int, i: int) -> dict:
    """Layer ``i``'s leaves by their short names, as the reference's
    ``layer`` takes them."""
    import jax.numpy as jnp

    from benchmarks.suite import weights
    from benchmarks.suite.archs import laguna as arch

    key = weights.seed_key(seed)
    prefix = f"layer_{i}."
    return {
        name[len(prefix):]: weights.leaf(
            key, name, shape, init, jnp.float32, arch)
        for name, shape, init in weights.leaf_specs(config)
        if name.startswith(prefix)
    }
