"""Weights from ``--seed``: one rule, read by the program's side and by the
plain reference alike, so neither takes anything the other has made.

A leaf is named (``layer_3.q``), shaped from the configuration's sizes, and
drawn ``normal(0, std)`` from a key folded from the seed and the name.  The
same name and seed give the same numbers whatever tree they are put in.
"""

from __future__ import annotations

import zlib


def sizes(config: dict) -> dict:
    """The widths the leaf shapes are built from, by the configuration's
    own (published) key names."""
    heads = config["num_attention_heads"]
    head_dim = config.get("head_dim") or config["hidden_size"] // heads
    return {
        "D": config["hidden_size"],
        "H": heads,
        "KV": config["num_key_value_heads"],
        "hd": head_dim,
        "F": config["intermediate_size"],
        "V": config["vocab_size"],
        "L": config["num_hidden_layers"],
    }


def leaf_specs(config: dict) -> list[tuple[str, tuple, float | None]]:
    """``(name, shape, std)`` of every leaf; ``std`` None is a norm scale
    (all ones).  Residual-output kernels take the depth-scaled std."""
    s = sizes(config)
    std = float(config["initializer_range"])
    specs: list = [("embedding", (s["V"], s["D"]), std)]
    for i in range(s["L"]):
        specs += layer_specs(config, i)
    specs += [("ln_final", (s["D"],), None), ("lm_head", (s["D"], s["V"]), std)]
    return specs


def layer_specs(config: dict, i: int) -> list[tuple[str, tuple, float | None]]:
    s = sizes(config)
    std = float(config["initializer_range"])
    res = std / (2 * s["L"]) ** 0.5
    p = f"layer_{i}."
    return [
        (p + "ln_attn", (s["D"],), None),
        (p + "q", (s["D"], s["H"] * s["hd"]), std),
        (p + "k", (s["D"], s["KV"] * s["hd"]), std),
        (p + "v", (s["D"], s["KV"] * s["hd"]), std),
        (p + "o", (s["H"] * s["hd"], s["D"]), res),
        (p + "ln_mlp", (s["D"],), None),
        (p + "wi", (s["D"], s["F"]), std),
        (p + "wo", (s["F"], s["D"]), res),
    ]


def parameter_count(config: dict) -> int:
    total = 0
    for _, shape, _ in leaf_specs(config):
        n = 1
        for d in shape:
            n *= d
        total += n
    return total


def seed_key(seed: int):
    """A key from any whole number up to 2**32 and beyond: the low 31 bits
    seed it, the rest is folded in."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def name_hash(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def leaf(key, name, shape, std, dtype):
    """One leaf.  ``name`` is the leaf's name or its ``name_hash``, which
    may be traced (one compiled generator then serves every layer)."""
    import jax
    import jax.numpy as jnp

    if std is None:
        return jnp.ones(shape, dtype)
    if isinstance(name, str):
        name = name_hash(name)
    k = jax.random.fold_in(key, name)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)
