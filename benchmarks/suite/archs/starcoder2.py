"""StarCoder2's block as the program runs it (``models/transformer.py``):
pre-norm, rotary, GQA, sliding window, non-gated tanh-GELU MLP of 4x, an
untied head.  Departures from the published model are the configuration
file's ``departures`` (RMSNorm for LayerNorm, no biases, untied head), which
the program's block forces.

Three parts, as ``archs/__init__.py`` asks: the leaves and how the program
names them; the plain reference's block in straightforward ``jax.numpy``
(float32 at matmul precision ``highest`` where ``reference.py`` calls it so,
no kernel, cache or batching; it imports nothing of the program); and the
work the algorithm needs, from the shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.suite import reference, work

LAYER_LEAVES = ("ln_attn", "q", "k", "v", "o", "ln_mlp", "wi", "wo")


# -- leaves ------------------------------------------------------------------


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


# -- the system under test ---------------------------------------------------

#: program leaf path -> the benchmark's leaf name.
_LEAF = {
    ("attention", "q_proj", "kernel"): "q",
    ("attention", "k_proj", "kernel"): "k",
    ("attention", "v_proj", "kernel"): "v",
    ("attention", "out_proj", "kernel"): "o",
    ("mlp", "wi", "kernel"): "wi",
    ("mlp", "wo", "kernel"): "wo",
    ("ln_attn", "scale"): "ln_attn",
    ("ln_mlp", "scale"): "ln_mlp",
}
_TOP = {("embedding",): "embedding", ("ln_final", "scale"): "ln_final",
        ("lm_head", "kernel"): "lm_head"}


def leaf_name(path) -> str:
    """``layer_3.q`` for ``params['layer_3']['attention']['q_proj']['kernel']``
    (a flax ``Partitioned`` box's ``.value`` step is skipped)."""
    keys = tuple(
        k.key for k in path if hasattr(k, "key") and isinstance(k.key, str)
    )
    if keys in _TOP:
        return _TOP[keys]
    if keys and keys[0].startswith("layer_") and keys[1:] in _LEAF:
        return f"{keys[0]}.{_LEAF[keys[1:]]}"
    raise KeyError(f"no benchmark leaf for the program's parameter {keys}")


def model_config(config: dict, **overrides):
    """The program's ``TransformerConfig`` at the configuration's sizes."""
    from covalent_tpu_plugin.models.transformer import TransformerConfig

    s = sizes(config)
    if s["D"] != s["H"] * s["hd"]:
        raise ValueError("the program's block needs head_dim = hidden / heads")
    return TransformerConfig(
        vocab_size=s["V"], d_model=s["D"], n_layers=s["L"], n_heads=s["H"],
        n_kv_heads=s["KV"], d_ff=s["F"],
        dtype=jnp.dtype(config["activation_dtype"]),
        param_dtype=jnp.dtype(config["weight_dtype"]),
        sliding_window=config["sliding_window"],
        rope_base=config["rope_theta"], scan_layers=False, **overrides,
    )


def program(config: dict, job: dict, mesh):
    """``(TransformerLM, lm_loss)`` as the train job runs them."""
    from covalent_tpu_plugin.models import TransformerLM, lm_loss

    lm = TransformerLM(model_config(
        config, max_seq=job["sequence"], attention=job["attention"],
        remat=job["remat"], mesh=mesh,
    ))
    return lm, functools.partial(lm_loss, vocab_chunk=job["vocab_chunk"])


def serve_model(config: dict, traffic: dict):
    from covalent_tpu_plugin.models import TransformerLM

    return TransformerLM(
        model_config(config, max_seq=traffic["engine"]["max_seq"]))


# -- the plain reference -----------------------------------------------------


def rope(x, theta):
    """Rotary embedding, half-split (rotate_half) form, over (S, H, hd)."""
    seq, _, head_dim = x.shape
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(angles)[:, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, window, block=512):
    """Causal sliding-window GQA over (S, H, hd) / (S, KV, hd), a block of
    query rows at a time so the (heads, block, S) scores fit."""
    seq, heads, head_dim = q.shape
    kv = k.shape[1]
    block = min(block, seq)
    if seq % block:
        raise ValueError(f"sequence {seq} is not a multiple of {block}")
    qg = q.reshape(seq // block, block, kv, heads // kv, head_dim)
    k_pos = jnp.arange(seq)

    @jax.checkpoint
    def rows(args):
        i, qb = args
        q_pos = i * block + jnp.arange(block)
        scores = jnp.einsum(
            "qkgd,skd->kgqs", qb, k, preferred_element_type=jnp.float32
        ) * (head_dim ** -0.5)
        seen = (k_pos[None, :] <= q_pos[:, None]) & (
            k_pos[None, :] > q_pos[:, None] - window
        )
        scores = jnp.where(seen[None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("kgqs,skd->qkgd", probs, v)

    out = jax.lax.map(rows, (jnp.arange(seq // block), qg))
    return out.reshape(seq, heads * head_dim)


def layer(x, w, config, dtype):
    """One block over (S, D): x + attn(norm(x)); x + mlp(norm(x))."""
    s = sizes(config)
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    h = reference.rms_norm(x, w["ln_attn"], eps, dtype)
    q = rope((h @ w["q"]).reshape(-1, s["H"], s["hd"]), theta)
    k = rope((h @ w["k"]).reshape(-1, s["KV"], s["hd"]), theta)
    v = (h @ w["v"]).reshape(-1, s["KV"], s["hd"])
    x = x + attention(q, k, v, config["sliding_window"]) @ w["o"]
    h = reference.rms_norm(x, w["ln_mlp"], eps, dtype)
    return x + jax.nn.gelu(h @ w["wi"], approximate=True) @ w["wo"]


def sequence_loss(w, tokens, config, dtype, positions=None):
    """Sum of next-token cross-entropies of one row of ``S + 1`` tokens
    (and the count).  ``positions`` keeps only the first that many (a
    planted fault)."""
    s = sizes(config)
    x = w["embedding"].astype(dtype)[tokens[:-1]]
    for i in range(s["L"]):
        lw = {n: w[f"layer_{i}.{n}"].astype(dtype) for n in LAYER_LEAVES}
        x = jax.checkpoint(
            functools.partial(layer, config=config, dtype=dtype)
        )(x, lw)
    feats = reference.rms_norm(x, w["ln_final"], config["rms_norm_eps"], dtype)
    return reference.head_loss(
        feats, tokens[1:], w["lm_head"].astype(dtype), positions)


# -- the needed work ---------------------------------------------------------

def matmul_parameters(config: dict) -> int:
    """Weights that multiply every token: the layers' six kernels and the
    output head.  The embedding is a lookup, the norms are vectors."""
    s = sizes(config)
    per_layer = (
        s["D"] * s["H"] * s["hd"] * 2          # q, o
        + s["D"] * s["KV"] * s["hd"] * 2       # k, v
        + s["D"] * s["F"] * 2                  # wi, wo
    )
    return s["L"] * per_layer + s["D"] * s["V"]


def attention_forward_flops(config: dict, seq: int) -> int:
    """QK^T and PV over the visible pairs, every head, one sequence, one
    layer: 2 matmuls x 2 FLOPs x head_dim each pair."""
    s = sizes(config)
    return 4 * s["hd"] * s["H"] * work.visible_pairs(
        seq, config["sliding_window"])


def train_flops_per_token(config: dict, job: dict) -> float:
    """Forward plus backward (twice the forward), no recompute: the matmul
    weights at 2 FLOPs each and attention inside the window."""
    s = sizes(config)
    seq = job["sequence"]
    forward = 2 * matmul_parameters(config) + (
        s["L"] * attention_forward_flops(config, seq) / seq
    )
    return 3.0 * forward


def kv_bytes_per_token(config: dict) -> int:
    s = sizes(config)
    return s["L"] * 2 * s["KV"] * s["hd"] * work._bytes(
        config["activation_dtype"])


def kernel_work(config: dict, job: dict, kernel: str) -> dict:
    """Needed FLOPs and bytes of one flash kernel in one train step, all
    layers.  ``flash_fwd``: the forward once a layer (QK^T and PV; reads Q,
    K, V, writes O).  ``flash_bwd_dkdv``: dV = P^T dO, dP = dO V^T and dK =
    dS^T Q, three of the backward's four matmuls, 1.5 x the forward's FLOPs,
    and the K/V side of its bytes (K, V in, dK, dV out).  ``flash_bwd_dq``:
    dQ = dS K, 0.5 x the forward's FLOPs, and the Q side (Q, O, dO in, dQ
    out).  Together: the forward once and the backward's four matmuls (twice
    the forward).  S and P recomputed by a kernel, and a forward run again
    by remat, count for nothing."""
    s = sizes(config)
    seq = job["sequence"]
    act = work._bytes(config["activation_dtype"])
    forward = attention_forward_flops(config, seq)
    q_bytes = seq * s["H"] * s["hd"] * act
    kv_bytes = seq * s["KV"] * s["hd"] * act
    flops, moved = {
        "flash_fwd": (forward, 2 * q_bytes + 2 * kv_bytes),
        "flash_bwd_dkdv": (1.5 * forward, 4 * kv_bytes),
        "flash_bwd_dq": (0.5 * forward, 4 * q_bytes),
    }[kernel]
    n = job["batch"] * s["L"]
    return {"flops": flops * n, "bytes": moved * n}
