"""A stand-in architecture for the seam's test, written the way a later PR
writes ``benchmarks/suite/archs/<model_type>.py``: an embedding, gated-SiLU
MLP blocks whose residual is scaled by a constant-initialised gate, a head.
Its parameter tree, its plain reference and its work count share nothing
with StarCoder2's; it has no attention, no kernel and no serving half.
``test_suite_archs.py`` registers it under the ``model_type`` ``gated_mlp``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.suite import reference

CONFIG = {
    "model_type": "gated_mlp",
    "source": "a stand-in for tests, nobody's model",
    "hidden_size": 32, "intermediate_size": 64, "num_blocks": 2,
    "vocab_size": 128, "norm_eps": 1e-06, "initializer_range": 0.05,
    "gate_init": 0.5, "weight_dtype": "float32", "activation_dtype": "float32",
}
JOB = {
    "kind": "train", "batch": 2, "sequence": 32, "learning_rate": 1e-3,
    "mesh": {"data": 1}, "feed_batches": 4, "check_steps": 3,
    "warm_steps": 1, "trace_after": 1, "trace_steps": 2,
}
LIMITS = {"loss_gap": 1e-3, "grad_gap": 1e-2, "delta_gap": 1e-2}


def sizes(config: dict) -> dict:
    return {"D": config["hidden_size"], "F": config["intermediate_size"],
            "B": config["num_blocks"], "V": config["vocab_size"]}


def leaf_specs(config: dict) -> list:
    s, std = sizes(config), float(config["initializer_range"])
    specs: list = [("tok", (s["V"], s["D"]), std)]
    for i in range(s["B"]):
        specs += [
            (f"block_{i}.up", (s["D"], s["F"]), std),
            (f"block_{i}.gate", (s["D"], s["F"]), std),
            (f"block_{i}.down", (s["F"], s["D"]), std),
            (f"block_{i}.mix", (s["D"],), {"const": config["gate_init"]}),
        ]
    return specs + [("out", (s["D"], s["V"]), std)]


def _norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


# -- the system under test: a flax module and a loss of its own --------------


def program(config: dict, job: dict, mesh):
    from flax import linen as nn

    s = sizes(config)
    init = nn.initializers.normal(0.02)

    class GatedMlpLM(nn.Module):
        @nn.compact
        def __call__(self, tokens):
            x = self.param("tok", init, (s["V"], s["D"]))[tokens]
            for i in range(s["B"]):
                up = self.param(f"up_{i}", init, (s["D"], s["F"]))
                gate = self.param(f"gate_{i}", init, (s["D"], s["F"]))
                down = self.param(f"down_{i}", init, (s["F"], s["D"]))
                mix = self.param(f"mix_{i}", nn.initializers.ones, (s["D"],))
                h = _norm(x, config["norm_eps"])
                x = x + mix * ((jax.nn.silu(h @ gate) * (h @ up)) @ down)
            return x @ self.param("out", init, (s["D"], s["V"]))

    def loss_fn(params, apply_fn, batch):
        tokens = batch["tokens"]
        logits = apply_fn({"params": params}, tokens[:, :-1])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        return -jnp.mean(picked)

    return GatedMlpLM(), loss_fn


def leaf_name(path) -> str:
    """``block_1.gate`` for ``params['gate_1']``."""
    (key,) = [k.key for k in path if isinstance(getattr(k, "key", None), str)]
    if key in ("tok", "out"):
        return key
    kind, _, index = key.rpartition("_")
    if kind not in ("up", "gate", "down", "mix") or not index.isdigit():
        raise KeyError(f"no benchmark leaf for the program's parameter {key}")
    return f"block_{index}.{kind}"


# -- the plain reference -----------------------------------------------------


def sequence_loss(w, tokens, config, dtype, positions=None):
    x = w["tok"].astype(dtype)[tokens[:-1]]
    for i in range(config["num_blocks"]):
        p = {n: w[f"block_{i}.{n}"].astype(dtype)
             for n in ("up", "gate", "down", "mix")}
        h = _norm(x, config["norm_eps"])
        x = x + p["mix"] * ((jax.nn.silu(h @ p["gate"]) * (h @ p["up"]))
                            @ p["down"])
    return reference.head_loss(
        x, tokens[1:], w["out"].astype(dtype), positions)


# -- the needed work ---------------------------------------------------------


def train_flops_per_token(config: dict, job: dict) -> float:
    """Three matmuls a block and the head, forward and twice that back."""
    s = sizes(config)
    return 3.0 * 2 * (s["B"] * 3 * s["D"] * s["F"] + s["D"] * s["V"])
