"""``sdar_moe`` at toy widths for the CPU tests: every mechanism of the
configuration (block-diffusion training over a noisy and a clean copy of
each row under the block mask, a norm over each query and key head, every
layer sparse, a softmax router wider than the experts held, no shared
expert) at sizes the interpreter runs in seconds, float32 throughout so
that the bfloat16 control stands apart."""

from __future__ import annotations

CONFIG = {
    "model_type": "sdar_moe",
    "source": "a stand-in for tests, nobody's model",
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 192,
    "max_position_embeddings": 256, "max_window_layers": 4,
    "mlp_only_layers": [], "moe_intermediate_size": 32,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 4,
    "num_experts_per_tok": 3, "num_hidden_layers": 1,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6, "rope_scaling": None,
    "rope_theta": 10000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 128, "router_width": 16, "held_experts": [4, 4],
    "block_length": 4, "noise_level_min": 0.001, "mask_token_id": 127,
    "noise_seed": 4242, "initializer_range": 0.05, "qk_norm_init": 3.0,
    "weight_dtype": "float32", "activation_dtype": "float32",
}
JOB = {
    "kind": "train", "batch": 2, "sequence": 32, "attention": "flash",
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
