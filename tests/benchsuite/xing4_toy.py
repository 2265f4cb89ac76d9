"""``xing4_0`` at toy widths for the CPU tests: every mechanism of the
configuration (latent attention at unequal widths, a dense layer then
expert layers, a router wider than the experts held, a shared expert, four
residual streams) at sizes the interpreter runs in seconds, float32
throughout so that the bfloat16 control stands apart."""

from __future__ import annotations

CONFIG = {
    "model_type": "xing4_0",
    "source": "a stand-in for tests, nobody's model",
    "hidden_size": 64, "num_attention_heads": 2, "num_key_value_heads": 2,
    "num_hidden_layers": 2, "first_k_dense_replace": 1,
    "layer_types": ["dense", "moe"],
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_routed_experts": 2, "router_width": 8,
    "held_experts": [2, 2], "num_experts_per_tok": 2, "n_shared_experts": 1,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096, "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "norm_topk_prob": True,
    "hidden_act": "silu", "rms_norm_eps": 1e-6, "vocab_size": 128,
    "initializer_range": 0.05, "weight_dtype": "float32",
    "activation_dtype": "float32",
}
JOB = {
    "kind": "train", "batch": 2, "sequence": 32, "attention": "flash",
    "remat": True, "vocab_chunk": 64, "learning_rate": 1e-3,
    "mesh": {"data": 1}, "feed_batches": 4, "check_steps": 2,
    "warm_steps": 1, "trace_after": 1, "trace_steps": 2,
}
LIMITS = {"loss_gap": 1e-3, "grad_gap": 1e-2, "delta_gap": 1e-2}


def with_sizes(**changes) -> dict:
    """The toy configuration with some sizes changed (``held_experts``
    carries ``n_routed_experts`` with it)."""
    config = dict(CONFIG, **changes)
    config["n_routed_experts"] = config["held_experts"][1]
    return config


def fill(template, prefix: tuple, config: dict, seed: int):
    """The seed's weights in a sub-tree of the program's parameters:
    ``prefix`` is the sub-tree's path in the whole tree (``("layer_1",
    "moe")``), so that ``leaf_name`` finds each leaf's name."""
    import jax
    from flax.core import meta

    from benchmarks.suite import weights
    from benchmarks.suite.archs import xing4_0 as arch

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
    from benchmarks.suite.archs import xing4_0 as arch

    key = weights.seed_key(seed)
    prefix = f"layer_{i}."
    return {
        name[len(prefix):]: weights.leaf(
            key, name, shape, init, jnp.float32, arch)
        for name, shape, init in weights.leaf_specs(config)
        if name.startswith(prefix)
    }
