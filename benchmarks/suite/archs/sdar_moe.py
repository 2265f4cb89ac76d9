"""SDAR's block-diffusion training as one of the chips that share each
layer runs it: grouped-query attention with an RMSNorm over each query
and key head ahead of rotary, every layer sparse (softmax scores over
``router_width`` experts, the top ``num_experts_per_tok`` renormalised,
the ``held_experts`` computed here, no shared expert), an untied head over
the held slice of the vocabulary; trained as a block-diffusion model
(BD3-LM, arXiv:2503.09573): each row's ``S`` clean tokens in blocks of
``block_length``, each block masked at a level ``t ~ U[noise_level_min,
1]``, the model run once over ``[noisy ; clean]`` under the block-diffusion
mask, the loss each masked token's cross-entropy over ``t``, divided by S.
``assumed`` in the configuration file lists what the published config does
not give.

The noise is drawn from a key that :func:`noise_key` derives from the row's
clean ids, on both sides: the cells' batches carry ids alone.

Three parts, as ``archs/__init__.py`` asks: the leaves and how the program
names them; the plain reference in straightforward ``jax.numpy`` (float32
at matmul precision ``highest`` where ``reference.py`` calls it so, no
kernel, every held expert over every token, attention a block of query
rows at a time under a mask built here; it imports nothing of the
program); and the work the algorithm needs, from the shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.suite import reference, work

#: The named scope the program's kernel call stands under, and the part
#: whose needed work ``kernel_work`` gives for it.
SCOPE = "attn_bd"


# -- leaves ------------------------------------------------------------------


def sizes(config: dict) -> dict:
    """The widths the leaf shapes are built from, by the configuration's
    own (published) key names; ``held`` and ``V`` are this chip's share."""
    first, held = config["held_experts"]
    if held != config["num_experts"]:
        raise ValueError("held_experts and num_experts disagree")
    if config["mlp_only_layers"] or config["decoder_sparse_step"] != 1:
        raise ValueError("every layer of this block is sparse")
    if config["use_sliding_window"] or config["rope_scaling"] or config[
            "attention_bias"] or config["tie_word_embeddings"]:
        raise ValueError("a window, scaled rotary, a bias or a tied head: "
                         "not this block")
    return {
        "D": config["hidden_size"], "L": config["num_hidden_layers"],
        "H": config["num_attention_heads"],
        "KV": config["num_key_value_heads"], "hd": config["head_dim"],
        "Fe": config["moe_intermediate_size"], "E": config["router_width"],
        "k": config["num_experts_per_tok"], "first": first, "held": held,
        "V": config["vocab_size"], "B": config["block_length"],
    }


def layer_specs(config: dict, i: int) -> list:
    s = sizes(config)
    std = float(config["initializer_range"])
    res = std / (2 * s["L"]) ** 0.5
    qk = {"const": float(config["qk_norm_init"])}
    d, p = s["D"], f"layer_{i}."
    specs = [
        ("ln_attn", (d,), None),
        ("q", (d, s["H"] * s["hd"]), std),
        ("k", (d, s["KV"] * s["hd"]), std),
        ("v", (d, s["KV"] * s["hd"]), std),
        ("q_norm", (s["hd"],), qk),
        ("k_norm", (s["hd"],), qk),
        ("o", (s["H"] * s["hd"], d), res),
        ("ln_mlp", (d,), None),
        ("router", (d, s["E"]), std),
        ("experts_wg", (s["held"], d, s["Fe"]), std),
        ("experts_wu", (s["held"], d, s["Fe"]), std),
        ("experts_wd", (s["held"], s["Fe"], d), res),
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


# -- the system under test ---------------------------------------------------

#: program leaf path inside a layer -> the benchmark's leaf name.
_LEAF = {
    ("ln_attn", "scale"): "ln_attn", ("ln_mlp", "scale"): "ln_mlp",
    ("attention", "q_proj", "kernel"): "q",
    ("attention", "k_proj", "kernel"): "k",
    ("attention", "v_proj", "kernel"): "v",
    ("attention", "q_norm", "scale"): "q_norm",
    ("attention", "k_norm", "scale"): "k_norm",
    ("attention", "out_proj", "kernel"): "o",
    ("moe", "router", "gate", "kernel"): "router",
    ("moe", "experts", "wg"): "experts_wg",
    ("moe", "experts", "wu"): "experts_wu",
    ("moe", "experts", "wd"): "experts_wd",
}
_TOP = {("embedding",): "embedding", ("ln_final", "scale"): "ln_final",
        ("lm_head", "kernel"): "lm_head"}


def leaf_name(path) -> str:
    """``layer_3.q_norm`` for ``params['layer_3']['attention']['q_norm']
    ['scale']`` (a flax ``Partitioned`` box's ``.value`` step is skipped)."""
    keys = tuple(
        k.key for k in path if hasattr(k, "key") and isinstance(k.key, str)
    )
    if keys in _TOP:
        return _TOP[keys]
    if keys and keys[0].startswith("layer_") and keys[1:] in _LEAF:
        return f"{keys[0]}.{_LEAF[keys[1:]]}"
    raise KeyError(f"no benchmark leaf for the program's parameter {keys}")


def noise_key(config: dict, row):
    """The key a row's noise is drawn from: ``noise_seed``'s key folded
    with a hash of the row's clean ids (each id times an odd weight of its
    place, summed modulo 2**32).  A batch that recurs meets the same mask."""
    weights = 2 * jnp.arange(row.shape[0], dtype=jnp.uint32) + 1
    digest = jnp.sum(row.astype(jnp.uint32) * weights, dtype=jnp.uint32)
    return jax.random.fold_in(jax.random.PRNGKey(config["noise_seed"]),
                              digest)


def model_config(config: dict, **overrides):
    """The program's ``TransformerConfig`` at the configuration's sizes."""
    try:
        # A program without block-diffusion training (every parent of
        # this configuration) stops here, naming what it lacks.
        from covalent_tpu_plugin.models import diffusion  # noqa: F401
        from covalent_tpu_plugin.models.moe import RoutedExpertsConfig
        from covalent_tpu_plugin.models.transformer import (
            AttentionType,
            TransformerConfig,
        )
    except ImportError as err:
        raise LookupError(
            "this program cannot run model_type 'sdar_moe': it has no "
            f"block-diffusion training ({err})") from None

    s = sizes(config)
    return TransformerConfig(
        vocab_size=s["V"], d_model=s["D"], n_layers=s["L"],
        n_heads=s["H"], n_kv_heads=s["KV"], head_dim=s["hd"],
        d_ff=s["Fe"], dtype=jnp.dtype(config["activation_dtype"]),
        param_dtype=jnp.dtype(config["weight_dtype"]),
        scan_layers=False, mlp_gated=True, mlp_activation="silu",
        layer_kinds=("moe",) * s["L"], qk_norm=True,
        diffusion_block=s["B"],
        attention_types=(AttentionType(
            name=SCOPE, n_heads=s["H"], rope_base=config["rope_theta"]),),
        attention_kinds=(SCOPE,) * s["L"],
        routed=RoutedExpertsConfig(
            n_experts=s["E"], top_k=s["k"], d_ff=s["Fe"], n_shared=0,
            routed_scaling=1.0, norm_topk=config["norm_topk_prob"],
            score="softmax", correction_bias=False,
            held=(s["first"], s["held"])),
        **overrides,
    )


def program(config: dict, job: dict, mesh):
    """``(TransformerLM, diffusion_loss)`` as the train job runs them: the
    model takes the two copies of each row, so ``max_seq`` is twice the
    job's sequence; the loss hands the masked tokens and the routed layers'
    rows to the train step.  Remat is fenced (``remat_prevent_cse``), as
    the other routed cells build it."""
    lm_config = model_config(
        config, max_seq=2 * job["sequence"], attention=job["attention"],
        remat=job["remat"], remat_prevent_cse=True, mesh=mesh)
    from covalent_tpu_plugin.models import TransformerLM
    from covalent_tpu_plugin.models.diffusion import diffusion_loss

    return TransformerLM(lm_config), functools.partial(
        diffusion_loss, block=config["block_length"],
        mask_id=config["mask_token_id"],
        noise_key=functools.partial(noise_key, config),
        vocab_chunk=job["vocab_chunk"], t_min=config["noise_level_min"])


# -- the plain reference -----------------------------------------------------


def noised(config: dict, x0):
    """``(xt, weight)`` of one row of clean ids: a level a block drawn
    ``U[noise_level_min, 1]``, each token masked where its draw falls
    under its block's level (``mask_token_id`` in its place), a masked
    token weighing 1 / level and the others 0."""
    block, seq = config["block_length"], x0.shape[0]
    k_level, k_mask = jax.random.split(noise_key(config, x0))
    levels = jax.random.uniform(k_level, (seq // block,),
                                minval=config["noise_level_min"], maxval=1.0)
    level = levels[jnp.arange(seq) // block]
    masked = jax.random.uniform(k_mask, (seq,)) < level
    return (jnp.where(masked, config["mask_token_id"], x0),
            jnp.where(masked, 1.0 / level, 0.0))


def rope(x, positions, theta):
    """Rotary embedding over (N, H, d) at the given positions: all dims
    turn, as half-split (rotate_half) pairs, inverse frequencies
    ``theta ** (-2i / d)``."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, 2 * half, 2, dtype=jnp.float32)
                      / (2 * half))
    angles = positions.astype(jnp.float32)[:, None] * freqs[None]
    cos = jnp.cos(angles)[:, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, block_length, rows=128):
    """Grouped-query attention over the (2S, H, d) / (2S, KV, d) rows of
    ``[noisy ; clean]``, a block of ``rows`` query rows at a time so that
    the (heads, rows, 2S) scores fit.  Key ``j`` is visible to query ``i``
    where, with their places ``p`` in their halves and blocks ``p //
    block_length``: both noisy and in one block, or ``i`` noisy, ``j``
    clean and of an earlier block, or both clean and ``j``'s block not
    after ``i``'s.  Returns (2S, H, d)."""
    n, heads, head_dim = q.shape
    kv, half = k.shape[1], n // 2
    rows = min(rows, n)
    if n % rows:
        raise ValueError(f"{n} rows are not a multiple of {rows}")
    qg = q.reshape(n // rows, rows, kv, heads // kv, head_dim)
    k_clean = jnp.arange(n) >= half
    k_block = (jnp.arange(n) - half * k_clean) // block_length

    @jax.checkpoint
    def block_of_rows(args):
        i, qb = args
        index = i * rows + jnp.arange(rows)
        q_clean = index >= half
        q_block = (index - half * q_clean) // block_length
        earlier = k_block[None, :] < q_block[:, None]
        same = k_block[None, :] == q_block[:, None]
        seen = jnp.where(
            q_clean[:, None], k_clean[None, :] & (earlier | same),
            (k_clean[None, :] & earlier) | (~k_clean[None, :] & same))
        scores = jnp.einsum(
            "qkgd,skd->kgqs", qb, k, preferred_element_type=jnp.float32
        ) * (head_dim ** -0.5)
        scores = jnp.where(seen[None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("kgqs,skd->qkgd", probs, v)

    out = jax.lax.map(block_of_rows, (jnp.arange(n // rows), qg))
    return out.reshape(n, heads, head_dim)


def attention_sublayer(u, w, config):
    """Over the normed rows ``u`` (2S, D): projections, each query and key
    head normed, both halves turned from position 0, the masked attention,
    the out projection."""
    s = sizes(config)
    eps = config["rms_norm_eps"]
    n = u.shape[0]
    positions = jnp.arange(n) % (n // 2)
    q = (u @ w["q"]).reshape(n, s["H"], s["hd"])
    k = (u @ w["k"]).reshape(n, s["KV"], s["hd"])
    v = (u @ w["v"]).reshape(n, s["KV"], s["hd"])
    q = rope(reference.rms_norm(q, w["q_norm"], eps, u.dtype), positions,
             config["rope_theta"])
    k = rope(reference.rms_norm(k, w["k_norm"], eps, u.dtype), positions,
             config["rope_theta"])
    mixed = attention(q, k, v, s["B"])
    return mixed.reshape(n, -1) @ w["o"]


def by_rows(fn, *arrays, block=4096):
    """``fn`` over arrays of N rows a block of rows at a time, each block's
    hidden arrays recomputed on the way back and not kept."""
    n = arrays[0].shape[0]
    block = min(block, n)
    if n % block:
        raise ValueError(f"{n} rows are not a multiple of {block}")
    out = jax.lax.map(
        jax.checkpoint(lambda blocks: fn(*blocks)),
        tuple(a.reshape(-1, block, a.shape[-1]) for a in arrays))
    return out.reshape(n, -1)


def route(h, w, config):
    """``(N, E)`` gates: each token's top-k softmax probabilities (over all
    ``E``, in float32), renormalised over the chosen where
    ``norm_topk_prob``, at its chosen experts, 0 elsewhere."""
    s = sizes(config)
    probs = jax.nn.softmax(
        h.astype(jnp.float32) @ w["router"].astype(jnp.float32), axis=-1)
    picked, chosen = jax.lax.top_k(probs, s["k"])
    if config["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return jnp.sum(
        jax.nn.one_hot(chosen, s["E"], dtype=jnp.float32) * picked[..., None],
        axis=1)


def experts(h, w, config):
    """The held experts' part, each expert's SwiGLU weighted by its gate (0
    for a token that did not choose it), every held expert over every
    token side by side as one MLP ``held`` times as wide."""
    s = sizes(config)
    gates = route(h, w, config).astype(h.dtype)[
        :, s["first"]: s["first"] + s["held"]]

    def rows(hb, gb):
        hidden = jax.nn.silu(jnp.einsum(
            "sd,edf->sef", hb, w["experts_wg"])) * jnp.einsum(
            "sd,edf->sef", hb, w["experts_wu"]) * gb[:, :, None]
        return jnp.einsum("sef,efd->sd", hidden, w["experts_wd"])

    return by_rows(rows, h, gates)


def layer(x, w, config):
    """One block over the (2S, D) rows: ``x + attention(norm(x))``, then
    ``+ experts(norm(.))``, each sublayer checkpointed on its own."""
    eps = config["rms_norm_eps"]

    def attend(x, w):
        return x + attention_sublayer(
            reference.rms_norm(x, w["ln_attn"], eps, x.dtype), w, config)

    def route_and_mix(x, w):
        return x + experts(
            reference.rms_norm(x, w["ln_mlp"], eps, x.dtype), w, config)

    x = jax.checkpoint(attend)(x, w)
    return jax.checkpoint(route_and_mix)(x, w)


def weighted_head_loss(feats, labels, weight, kernel, chunk=2048):
    """Sum over rows of ``weight`` x the cross-entropy of ``labels`` under
    ``feats @ kernel``: logits a block of rows at a time, in float32."""
    chunk = min(chunk, feats.shape[0])

    @jax.checkpoint
    def rows(args):
        f, y, wt = args
        logits = (f @ kernel).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        nll = lse - jnp.take_along_axis(logits, y[:, None], -1)[:, 0]
        return jnp.sum(wt * nll)

    n = feats.shape[0] // chunk * chunk
    total = jnp.sum(jax.lax.map(rows, (
        feats[:n].reshape(-1, chunk, feats.shape[-1]),
        labels[:n].reshape(-1, chunk), weight[:n].reshape(-1, chunk))))
    if n < feats.shape[0]:
        total = total + rows((feats[n:], labels[n:], weight[n:]))
    return total


def sequence_loss(w, tokens, config, dtype, positions=None):
    """The block-diffusion loss's sum over one row of ``S + 1`` ids (the
    first S are the clean row) and the count it is divided by: S, or
    ``positions`` where only the first that many noisy rows are kept (a
    planted fault)."""
    s = sizes(config)
    x0 = tokens[:-1]
    seq = x0.shape[0]
    xt, weight = noised(config, x0)
    x = w["embedding"].astype(dtype)[jnp.concatenate([xt, x0])]
    for i in range(s["L"]):
        prefix = f"layer_{i}."
        lw = {n[len(prefix):]: a.astype(dtype) for n, a in w.items()
              if n.startswith(prefix)}
        x = layer(x, lw, config)
    kept = seq if positions is None else positions
    feats = reference.rms_norm(
        x[:kept], w["ln_final"], config["rms_norm_eps"], dtype)
    return weighted_head_loss(
        feats, x0[:kept], weight[:kept], w["lm_head"].astype(dtype)), kept


# -- the needed work ---------------------------------------------------------


def visible_pairs(config: dict, seq: int) -> int:
    """Query-key pairs the block-diffusion mask over ``[noisy ; clean]``
    of ``seq`` clean positions has to score: a noisy query its block's B
    noisy keys and the clean keys before its block, a clean query the
    clean keys up to its block's end; S x B + S^2 in all."""
    block = config["block_length"]
    if seq % block:
        raise ValueError(f"{seq} is not whole blocks of {block}")
    blocks = seq // block
    noisy_to_clean = block * block * blocks * (blocks - 1) // 2
    clean_to_clean = block * block * blocks * (blocks + 1) // 2
    return seq * block + noisy_to_clean + clean_to_clean


def expected_held_rows(config: dict, positions: int) -> float:
    """(position, choice) pairs a layer sends to the experts held here
    under even routing: positions x k x held / E."""
    s = sizes(config)
    return positions * s["k"] * s["held"] / s["E"]


def attention_forward_flops(config: dict, seq: int) -> int:
    """QK^T and PV over the visible pairs, every head of every layer, one
    row: 2 matmuls x 2 FLOPs x head_dim a pair."""
    s = sizes(config)
    return 4 * s["hd"] * s["H"] * s["L"] * visible_pairs(config, seq)


def train_flops_per_token(config: dict, job: dict) -> float:
    """Forward plus backward (twice the forward), no recompute, a clean
    token: every layer's matmul weights at 2 FLOPs each over the 2S
    positions (attention's four kernels, the router and the expected share
    of a position's chosen experts that are held here), the head over the
    S noisy positions, and attention over the visible pairs."""
    s = sizes(config)
    seq = job["sequence"]
    d = s["D"]
    per_layer = (2 * d * s["H"] * s["hd"] + 2 * d * s["KV"] * s["hd"]
                 + d * s["E"] + 3 * d * s["Fe"] * s["k"] * s["held"] / s["E"])
    forward = (2 * (2 * seq * s["L"] * per_layer + seq * d * s["V"])
               + attention_forward_flops(config, seq))
    return 3.0 * forward / seq


def kernel_work(config: dict, job: dict, kernel: str) -> dict:
    """Needed FLOPs and bytes of one named part of one train step, all
    layers.  ``attn_bd``: the three flash kernels, the forward over the
    visible pairs and the backward's four matmuls (twice the forward); S
    and P computed again by a kernel, and a forward run again by remat,
    count for nothing.  Its bytes: Q, K, V in and O out forward; those four
    and dO in, dQ, dK, dV out backward, over the 2S positions.
    ``experts``: the three grouped matmuls over the expected held rows,
    forward and twice that backward; its bytes the held weights read in
    both passes and their gradient written, the rows in and out."""
    s = sizes(config)
    seq, batch = job["sequence"], job["batch"]
    act = work._bytes(config["activation_dtype"])
    if kernel == SCOPE:
        rows = 2 * seq * s["L"] * s["hd"] * act
        return {
            "flops": 3 * attention_forward_flops(config, seq) * batch,
            "bytes": 6 * rows * (s["H"] + s["KV"]) * batch}
    if kernel != "experts":
        raise KeyError(kernel)
    rows = expected_held_rows(config, 2 * batch * seq)
    expert = 3 * s["D"] * s["Fe"]
    return {
        "flops": 3 * 2 * expert * rows * s["L"],
        "bytes": (3 * s["held"] * expert * act + 5 * rows * s["D"] * act)
        * s["L"]}
