"""Xing4.0's block as one of the chips that share each layer runs it: latent
attention (``num_attention_heads`` is the heads held here), a leading dense
SwiGLU layer then expert layers (sigmoid scores over ``router_width``
experts, top ``num_experts_per_tok`` by bias-corrected score, the
``held_experts`` computed here, a shared expert), ``hc_mult`` residual
streams mixed around every sublayer with a Sinkhorn-normalised matrix, an
untied head over the held slice of the vocabulary.  The configuration's
``layer_types`` says which layer is of which kind; ``assumed`` in the
configuration file lists what the published config does not give.

Three parts, as ``archs/__init__.py`` asks: the leaves and how the program
names them; the plain reference in straightforward ``jax.numpy`` (float32
at matmul precision ``highest`` where ``reference.py`` calls it so, no
kernel, every held expert over every token, attention a block of query
rows at a time; it imports nothing of the program); and the work the
algorithm needs, from the shapes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.suite import reference, work

#: The mixing's leaves; ``phi`` holds the pre and post columns (n each),
#: ``phi_res`` the n x n of H_res, whose gradient starts at zero.
HC_LEAVES = ("phi", "phi_res", "alpha", "b_pre", "b_post", "b_res")
#: Start values the published config does not give (``assumed``).
HC_ALPHA, HC_RES_DIAGONAL = 0.01, 2.0


# -- leaves ------------------------------------------------------------------


def sizes(config: dict) -> dict:
    """The widths the leaf shapes are built from, by the configuration's
    own (published) key names; ``heads``, ``held`` and ``V`` are this
    chip's share."""
    first, held = config["held_experts"]
    if held != config["n_routed_experts"]:
        raise ValueError("held_experts and n_routed_experts disagree")
    kinds = tuple(config["layer_types"])
    if len(kinds) != config["num_hidden_layers"] or kinds.count("dense") != (
            config["first_k_dense_replace"]):
        raise ValueError("layer_types disagrees with the layer counts")
    return {
        "D": config["hidden_size"], "H": config["num_attention_heads"],
        "L": config["num_hidden_layers"], "kinds": kinds,
        "q_rank": config["q_lora_rank"], "kv_rank": config["kv_lora_rank"],
        "nope": config["qk_nope_head_dim"], "rope": config["qk_rope_head_dim"],
        "v": config["v_head_dim"], "F": config["intermediate_size"],
        "Fe": config["moe_intermediate_size"],
        "E": config["router_width"], "k": config["num_experts_per_tok"],
        "first": first, "held": held, "shared": config["n_shared_experts"],
        "n": config["hc_mult"], "V": config["vocab_size"],
    }


def layer_specs(config: dict, i: int) -> list:
    s = sizes(config)
    std = float(config["initializer_range"])
    res = std / (2 * s["L"]) ** 0.5
    d, n, p = s["D"], s["n"], f"layer_{i}."
    specs = [
        ("ln_attn", (d,), None),
        ("q_a", (d, s["q_rank"]), std),
        ("q_a_norm", (s["q_rank"],), None),
        ("q_b", (s["q_rank"], s["H"] * (s["nope"] + s["rope"])), std),
        ("kv_a", (d, s["kv_rank"] + s["rope"]), std),
        ("kv_a_norm", (s["kv_rank"],), None),
        ("kv_b", (s["kv_rank"], s["H"] * (s["nope"] + s["v"])), std),
        ("o", (s["H"] * s["v"], d), res),
        ("ln_mlp", (d,), None),
    ]
    if s["kinds"][i] == "dense":
        specs += [("wg", (d, s["F"]), std), ("wu", (d, s["F"]), std),
                  ("wd", (s["F"], d), res)]
    else:
        fs = s["shared"] * s["Fe"]
        specs += [
            ("router", (d, s["E"]), std),
            ("router_bias", (s["E"],), {"const": 0.0}),
            ("experts_wg", (s["held"], d, s["Fe"]), std),
            ("experts_wu", (s["held"], d, s["Fe"]), std),
            ("experts_wd", (s["held"], s["Fe"], d), res),
            ("shared_wg", (d, fs), std), ("shared_wu", (d, fs), std),
            ("shared_wd", (fs, d), res),
        ]
    for tag in ("hc_attn", "hc_mlp"):
        specs += [
            (f"{tag}.phi", (n * d, 2 * n), std),
            (f"{tag}.phi_res", (n * d, n * n), std),
            (f"{tag}.alpha", (3,), {"const": HC_ALPHA}),
            (f"{tag}.b_pre", (n,), {"const": 0.0}),
            (f"{tag}.b_post", (n,), {"const": 0.0}),
            (f"{tag}.b_res", (n, n), ("diag", HC_RES_DIAGONAL)),
        ]
    return [(p + name, shape, init) for name, shape, init in specs]


def leaf_specs(config: dict) -> list:
    s = sizes(config)
    std = float(config["initializer_range"])
    specs: list = [("embedding", (s["V"], s["D"]), std)]
    for i in range(s["L"]):
        specs += layer_specs(config, i)
    return specs + [("ln_final", (s["D"],), None),
                    ("lm_head", (s["D"], s["V"]), std)]


def leaf_value(key, name, shape, init, dtype):
    """The harness's rule, and one more ``init``: ``("diag", x)``, a square
    matrix of constants, ``x`` on the diagonal and 0 off it (``b_res``)."""
    if isinstance(init, tuple) and init[0] == "diag":
        return init[1] * jnp.eye(shape[0], dtype=dtype)
    from benchmarks.suite import weights

    return weights.leaf(key, name, shape, init, dtype)


# -- the system under test ---------------------------------------------------

#: program leaf path inside a layer -> the benchmark's leaf name.
_LEAF = {
    ("ln_attn", "scale"): "ln_attn", ("ln_mlp", "scale"): "ln_mlp",
    ("attention", "latent_proj", "q_a", "kernel"): "q_a",
    ("attention", "latent_proj", "q_a_norm", "scale"): "q_a_norm",
    ("attention", "latent_proj", "q_b", "kernel"): "q_b",
    ("attention", "latent_proj", "kv_a", "kernel"): "kv_a",
    ("attention", "latent_proj", "kv_a_norm", "scale"): "kv_a_norm",
    ("attention", "latent_proj", "kv_b", "kernel"): "kv_b",
    ("attention", "out_proj", "kernel"): "o",
    ("mlp", "wg", "kernel"): "wg", ("mlp", "wi", "kernel"): "wu",
    ("mlp", "wo", "kernel"): "wd",
    ("moe", "router", "gate", "kernel"): "router",
    ("moe", "router", "bias"): "router_bias",
    ("moe", "experts", "wg"): "experts_wg",
    ("moe", "experts", "wu"): "experts_wu",
    ("moe", "experts", "wd"): "experts_wd",
    ("moe", "shared_expert", "wg", "kernel"): "shared_wg",
    ("moe", "shared_expert", "wi", "kernel"): "shared_wu",
    ("moe", "shared_expert", "wo", "kernel"): "shared_wd",
}
_LEAF.update({(tag, leaf): f"{tag}.{leaf}"
              for tag in ("hc_attn", "hc_mlp") for leaf in HC_LEAVES})
_TOP = {("embedding",): "embedding", ("ln_final", "scale"): "ln_final",
        ("lm_head", "kernel"): "lm_head"}


def leaf_name(path) -> str:
    """``layer_3.experts_wg`` for ``params['layer_3']['moe']['experts']
    ['wg']`` (a flax ``Partitioned`` box's ``.value`` step is skipped)."""
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
    from covalent_tpu_plugin.models.latent import LatentAttentionConfig
    from covalent_tpu_plugin.models.moe import RoutedExpertsConfig
    from covalent_tpu_plugin.models.streams import ResidualStreamsConfig
    from covalent_tpu_plugin.models.transformer import TransformerConfig

    s = sizes(config)
    yarn = config["rope_scaling"]
    if yarn["type"] != "yarn" or config["scoring_func"] != "sigmoid" or (
            config["topk_method"] != "noaux_tc" or config["n_group"] != 1):
        raise ValueError("the program's block runs yarn, sigmoid, noaux_tc")
    return TransformerConfig(
        vocab_size=s["V"], d_model=s["D"], n_layers=s["L"], n_heads=s["H"],
        d_ff=s["F"], dtype=jnp.dtype(config["activation_dtype"]),
        param_dtype=jnp.dtype(config["weight_dtype"]),
        rope_base=config["rope_theta"], scan_layers=False,
        mlp_gated=True, mlp_activation=config["hidden_act"],
        layer_kinds=s["kinds"],
        latent=LatentAttentionConfig(
            q_lora_rank=s["q_rank"], kv_lora_rank=s["kv_rank"],
            qk_nope_head_dim=s["nope"], qk_rope_head_dim=s["rope"],
            v_head_dim=s["v"], rope_factor=yarn["factor"],
            rope_original_max=yarn["original_max_position_embeddings"],
            rope_beta_fast=yarn["beta_fast"], rope_beta_slow=yarn["beta_slow"],
            rope_mscale=yarn["mscale"],
            rope_mscale_all_dim=yarn["mscale_all_dim"]),
        routed=RoutedExpertsConfig(
            n_experts=s["E"], top_k=s["k"], d_ff=s["Fe"],
            n_shared=s["shared"],
            routed_scaling=config["routed_scaling_factor"],
            norm_topk=config["norm_topk_prob"],
            held=(s["first"], s["held"])),
        streams=ResidualStreamsConfig(
            n=s["n"], sinkhorn_iters=config["hc_sinkhorn_iters"],
            eps=config["hc_eps"],
            clamp=(config["mhc_h_res_clamp_min"],
                   config["mhc_h_res_clamp_max"]),
            alpha_init=HC_ALPHA, res_diagonal_init=HC_RES_DIAGONAL),
        **overrides,
    )


def program(config: dict, job: dict, mesh):
    """``(TransformerLM, lm_loss)`` as the train job runs them; the loss
    also hands the routed layers' row counts to the train step.  Remat is
    fenced (``remat_prevent_cse``): left to merge the recompute with the
    first forward, the compiler keeps every layer's row buffers and the
    step does not fit the chip."""
    from covalent_tpu_plugin.models import TransformerLM, lm_loss

    lm = TransformerLM(model_config(
        config, max_seq=job["sequence"], attention=job["attention"],
        remat=job["remat"], remat_prevent_cse=True, mesh=mesh,
    ))
    return lm, functools.partial(lm_loss, vocab_chunk=job["vocab_chunk"])


# -- the plain reference -----------------------------------------------------


def yarn_frequencies(config: dict):
    """The rotary dims' inverse frequencies under YaRN: ``theta``'s run,
    divided by ``factor`` where a dim turns fewer than ``beta_slow`` times
    in the original context, kept where it turns more than ``beta_fast``
    times, a linear ramp between."""
    yarn, dim, theta = (config["rope_scaling"], config["qk_rope_head_dim"],
                        config["rope_theta"])
    plain = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)

    def dim_of(turns):
        return dim * math.log(yarn["original_max_position_embeddings"] / (
            turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(yarn["beta_fast"])), 0)
    high = min(math.ceil(dim_of(yarn["beta_slow"])), dim - 1)
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3),
        0.0, 1.0)
    return plain / yarn["factor"] * ramp + plain * (1.0 - ramp)


def yarn_factor(config: dict, mscale: float) -> float:
    return 0.1 * mscale * math.log(config["rope_scaling"]["factor"]) + 1.0


def rope(x, freqs, amplitude):
    """Rotary embedding, half-split (rotate_half) form, over (S, H, d)."""
    half = x.shape[-1] // 2
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs[None]
    cos = (jnp.cos(angles) * amplitude)[:, None, :].astype(x.dtype)
    sin = (jnp.sin(angles) * amplitude)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, scale, block=512):
    """Causal attention over (S, H, dk) / (S, H, dk) / (S, H, dv), a block
    of query rows at a time so the (heads, block, S) scores fit."""
    seq = q.shape[0]
    block = min(block, seq)
    if seq % block:
        raise ValueError(f"sequence {seq} is not a multiple of {block}")
    k_pos = jnp.arange(seq)

    @jax.checkpoint
    def rows(args):
        i, qb = args
        q_pos = i * block + jnp.arange(block)
        scores = jnp.einsum(
            "qhd,shd->hqs", qb, k, preferred_element_type=jnp.float32) * scale
        scores = jnp.where(
            (k_pos[None, :] <= q_pos[:, None])[None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("hqs,shd->qhd", probs, v)

    out = jax.lax.map(rows, (
        jnp.arange(seq // block), q.reshape(seq // block, block, *q.shape[1:])))
    return out.reshape(seq, -1)


def latent_attention(h, w, config):
    s = sizes(config)
    eps, dtype = config["rms_norm_eps"], h.dtype
    c_q = reference.rms_norm(h @ w["q_a"], w["q_a_norm"], eps, dtype)
    q = (c_q @ w["q_b"]).reshape(-1, s["H"], s["nope"] + s["rope"])
    kv = h @ w["kv_a"]
    c_kv = reference.rms_norm(kv[:, : s["kv_rank"]], w["kv_a_norm"], eps, dtype)
    kvb = (c_kv @ w["kv_b"]).reshape(-1, s["H"], s["nope"] + s["v"])
    yarn = config["rope_scaling"]
    freqs = yarn_frequencies(config)
    amplitude = yarn_factor(config, yarn["mscale"]) / yarn_factor(
        config, yarn["mscale_all_dim"])
    q_rope = rope(q[..., s["nope"]:], freqs, amplitude)
    k_rope = rope(kv[:, None, s["kv_rank"]:], freqs, amplitude)
    q = jnp.concatenate([q[..., : s["nope"]], q_rope], -1)
    k = jnp.concatenate([
        kvb[..., : s["nope"]],
        jnp.broadcast_to(k_rope, (k_rope.shape[0], s["H"], s["rope"]))], -1)
    scale = (s["nope"] + s["rope"]) ** -0.5 * yarn_factor(
        config, yarn["mscale_all_dim"]) ** 2
    return attention(q, k, kvb[..., s["nope"]:], scale) @ w["o"]


def gated(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def route(h, w, config):
    """``(S, E)`` gates: each token's top-k normalised, scaled weights at
    its chosen experts, 0 elsewhere.  Scores in float32, whatever ``h``'s
    dtype."""
    s = sizes(config)
    scores = jax.nn.sigmoid(
        h.astype(jnp.float32) @ w["router"].astype(jnp.float32))
    _, chosen = jax.lax.top_k(
        scores + w["router_bias"].astype(jnp.float32), s["k"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if config["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    picked = picked * config["routed_scaling_factor"]
    return jnp.sum(
        jax.nn.one_hot(chosen, s["E"], dtype=jnp.float32) * picked[..., None],
        axis=1)


@jax.checkpoint
def gated_side_by_side(h, gates, wg, wu, wd):
    """Experts ``e`` side by side as one gated MLP that many times as wide,
    each hidden unit carrying its expert's gate (``gates`` (S, e)): every
    expert over every token in three products.  A Python loop over the
    experts compiles three for each, and the float32 step's entry then does
    not fit the chip machine's compile cache beside the program's (PERF.md
    section 7); a ``lax.scan`` over them holds 3 GB more.  Checkpointed:
    the (S, e, F) hidden arrays are not kept."""
    hidden = jax.nn.silu(jnp.einsum("sd,edf->sef", h, wg)) * jnp.einsum(
        "sd,edf->sef", h, wu) * gates[:, :, None]
    return jnp.einsum("sef,efd->sd", hidden, wd)


def experts(h, w, config):
    """The held experts' part, each over every token and weighted by its
    gate (0 for a token that did not choose it), and the shared expert."""
    s = sizes(config)
    gates = route(h, w, config).astype(h.dtype)
    return gated_side_by_side(
        h, gates[:, s["first"]: s["first"] + s["held"]], w["experts_wg"],
        w["experts_wu"], w["experts_wd"],
    ) + gated(h, w["shared_wg"], w["shared_wu"], w["shared_wd"])


def sinkhorn(m, iters, eps):
    """Column then row normalisation of (S, n, n) matrices, ``iters``
    times, ``eps`` in each denominator."""

    def one(m, _):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=2, keepdims=True) + eps), None

    return jax.lax.scan(one, m, None, length=iters)[0]


def mixed(x, w, tag, config, sublayer):
    """One sublayer around the ``n`` streams ``x`` (n, S, D)."""
    n, seq, width = x.shape
    dtype = x.dtype
    # RMSNorm(vec(X)) phi, the norm (it has no learned scale) taken out of
    # the product: rsqrt(mean vec(X)^2) (vec(X) phi), vec(X) stream-major.
    mean_square = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=(0, 2))
    phi = jnp.concatenate([w[f"{tag}.phi"], w[f"{tag}.phi_res"]], axis=-1)
    h = jnp.einsum(
        "nsd,ndk->sk", x, phi.reshape(n, width, -1)
    ).astype(jnp.float32) * jax.lax.rsqrt(
        mean_square + config["rms_norm_eps"])[:, None]
    alpha = w[f"{tag}.alpha"].astype(jnp.float32)
    pre = jax.nn.sigmoid(alpha[0] * h[:, :n] + w[f"{tag}.b_pre"])
    post = 2.0 * jax.nn.sigmoid(
        alpha[1] * h[:, n:2 * n] + w[f"{tag}.b_post"])
    res = alpha[2] * h[:, 2 * n:].reshape(seq, n, n) + w[f"{tag}.b_res"]
    res = sinkhorn(
        jnp.exp(jnp.clip(res, config["mhc_h_res_clamp_min"],
                         config["mhc_h_res_clamp_max"])),
        config["hc_sinkhorn_iters"], config["hc_eps"])
    pre, post, res = pre.astype(dtype), post.astype(dtype), res.astype(dtype)
    u = sum(pre[:, j, None] * x[j] for j in range(n))
    y = sublayer(u)
    return jnp.stack([
        sum(res[:, i, j, None] * x[j] for j in range(n)) + post[:, i, None] * y
        for i in range(n)])


def layer(x, w, config, kind):
    """One block over the streams (n, S, D).  Each sublayer is checkpointed
    on its own inside the layer's checkpoint, so that the backward holds
    one sublayer's float32 intermediates at a time: the step has to fit
    the chip beside float32 weights, gradient and Adam's moments."""
    eps = config["rms_norm_eps"]

    def attention_sublayer(x, w):
        return mixed(x, w, "hc_attn", config, lambda u: latent_attention(
            reference.rms_norm(u, w["ln_attn"], eps, x.dtype), w, config))

    def mlp_sublayer(x, w):
        if kind == "dense":
            mlp = lambda h: gated(h, w["wg"], w["wu"], w["wd"])  # noqa: E731
        else:
            mlp = lambda h: experts(h, w, config)  # noqa: E731
        return mixed(x, w, "hc_mlp", config, lambda u: mlp(
            reference.rms_norm(u, w["ln_mlp"], eps, x.dtype)))

    x = jax.checkpoint(attention_sublayer)(x, w)
    return jax.checkpoint(mlp_sublayer)(x, w)


def sequence_loss(w, tokens, config, dtype, positions=None):
    """Sum of next-token cross-entropies of one row of ``S + 1`` tokens
    (and the count).  ``positions`` keeps only the first that many (a
    planted fault)."""
    s = sizes(config)
    x = w["embedding"].astype(dtype)[tokens[:-1]]
    x = jnp.broadcast_to(x[None], (s["n"],) + x.shape)
    for i, kind in enumerate(s["kinds"]):
        prefix = f"layer_{i}."
        lw = {n[len(prefix):]: a.astype(dtype) for n, a in w.items()
              if n.startswith(prefix)}
        x = jax.checkpoint(functools.partial(
            layer, config=config, kind=kind))(x, lw)
    feats = reference.rms_norm(
        jnp.sum(x, axis=0), w["ln_final"], config["rms_norm_eps"], dtype)
    return reference.head_loss(
        feats, tokens[1:], w["lm_head"].astype(dtype), positions)


# -- the needed work ---------------------------------------------------------


def expected_held_rows(config: dict, tokens: int) -> float:
    """(token, choice) pairs a routed layer sends to the experts held here
    under even routing: tokens x k x held / E."""
    s = sizes(config)
    return tokens * s["k"] * s["held"] / s["E"]


def matmul_parameters(config: dict) -> float:
    """Matmul weights that touch a token in the forward, all layers and the
    head: attention's five kernels, the mixing's ``phi``, the dense MLP or
    the shared expert, the router and the expected share of a token's
    chosen experts that are held here.  The embedding is a lookup, norms
    and biases are vectors."""
    s = sizes(config)
    d, n = s["D"], s["n"]
    attention_ = (d * s["q_rank"] + s["q_rank"] * s["H"] * (s["nope"] + s["rope"])
                  + d * (s["kv_rank"] + s["rope"])
                  + s["kv_rank"] * s["H"] * (s["nope"] + s["v"])
                  + s["H"] * s["v"] * d)
    mixing = 2 * n * d * n * (n + 2)
    expert = 3 * d * s["Fe"]
    moe = (d * s["E"] + s["shared"] * expert
           + expert * s["k"] * s["held"] / s["E"])
    total = d * s["V"]
    for kind in s["kinds"]:
        total += attention_ + mixing + (
            3 * d * s["F"] if kind == "dense" else moe)
    return total


def attention_forward_flops(config: dict, seq: int) -> int:
    """QK^T over the scores' width and PV over the values', the visible
    pairs, every held head, one sequence, one layer."""
    s = sizes(config)
    return 2 * (s["nope"] + s["rope"] + s["v"]) * s["H"] * work.visible_pairs(
        seq, None)


def train_flops_per_token(config: dict, job: dict) -> float:
    """Forward plus backward (twice the forward), no recompute: the matmul
    weights at 2 FLOPs each and causal attention.  The mixes' and
    Sinkhorn's elementwise work (under half a percent) is left out."""
    s = sizes(config)
    seq = job["sequence"]
    forward = 2 * matmul_parameters(config) + (
        s["L"] * attention_forward_flops(config, seq) / seq)
    return 3.0 * forward


def kernel_work(config: dict, job: dict, kernel: str) -> dict:
    """Needed FLOPs and bytes of one named part of one train step, all
    layers.  The flash kernels as ``archs/starcoder2.py`` counts them, at
    unequal widths: with ``F`` the forward (2 (dk + dv) a visible pair a
    head), dK/dV needs dV = P^T dO, dP = dO V^T (2 dv each) and dK = dS^T Q
    (2 dk); dQ needs dS K (2 dk); together 3 F.  ``experts``: the three
    grouped matmuls over the expected held rows, forward and twice that
    backward; its bytes the held weights read in both passes and their
    gradient written, the rows in and out.  ``hc``: the streams read once
    and written once a sublayer, forward and backward (remat not counted);
    its FLOPs the coefficients' matmul."""
    s = sizes(config)
    seq, batch = job["sequence"], job["batch"]
    act = work._bytes(config["activation_dtype"])
    dk, dv = s["nope"] + s["rope"], s["v"]
    pairs = s["H"] * work.visible_pairs(seq, None)
    q_bytes, v_bytes = seq * s["H"] * dk * act, seq * s["H"] * dv * act
    moe_layers = s["kinds"].count("moe")
    rows = expected_held_rows(config, batch * seq)
    expert = 3 * s["D"] * s["Fe"]
    streams = s["n"] * s["D"] * batch * seq * act
    flops, moved, n = {
        "flash_fwd": (2 * (dk + dv) * pairs, 2 * q_bytes + 2 * v_bytes,
                      batch * s["L"]),
        "flash_bwd_dkdv": (2 * (dk + 2 * dv) * pairs,
                           2 * q_bytes + 2 * v_bytes, batch * s["L"]),
        "flash_bwd_dq": (2 * dk * pairs, 2 * q_bytes + 2 * v_bytes,
                         batch * s["L"]),
        "experts": (3 * 2 * expert * rows,
                    3 * s["held"] * expert * act + 5 * rows * s["D"] * act,
                    moe_layers),
        "hc": (3 * 2 * batch * seq * s["n"] * s["D"] * s["n"] * (s["n"] + 2),
               2 * 2 * streams, 2 * s["L"]),
    }[kernel]
    return {"flops": flops * n, "bytes": moved * n}
