"""Laguna's block as one of the chips that share each layer runs it:
grouped-query attention whose settings differ by layer type (``layer_types``:
``full_attention`` layers turn the first half of each head under YaRN,
``sliding_attention`` layers turn all of it plainly, see the last
``sliding_window`` positions and have their own head count), a sigmoid gate
a head on attention's output, a leading dense SwiGLU layer then sparse
layers (softmax scores over ``router_width`` experts, the top
``num_experts_per_tok`` renormalised and scaled, the ``held_experts``
computed here, a shared expert), an untied head over the held slice of the
vocabulary.  ``assumed`` in the configuration file lists what the published
config does not give.

Three parts, as ``archs/__init__.py`` asks: the leaves and how the program
names them; the plain reference in straightforward ``jax.numpy`` (float32
at matmul precision ``highest`` where ``reference.py`` calls it so, no
kernel, every held expert over every token, attention a block of query
rows at a time under the layer's own mask; it imports nothing of the
program); and the work the algorithm needs, from the shapes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.suite import reference, work

#: ``layer_types`` entry -> the named scope the program's kernel call
#: stands under (what ``attn_*_ms.train`` and the rooflines read).
SCOPES = {"full_attention": "attn_full", "sliding_attention": "attn_sliding"}


# -- leaves ------------------------------------------------------------------


def sizes(config: dict) -> dict:
    """The widths the leaf shapes are built from, by the configuration's
    own (published) key names; ``heads``, ``KV``, ``held`` and ``V`` are
    this chip's share."""
    first, held = config["held_experts"]
    if held != config["num_experts"]:
        raise ValueError("held_experts and num_experts disagree")
    layers = config["num_hidden_layers"]
    types, kinds, heads = (tuple(config[key]) for key in (
        "layer_types", "mlp_layer_types", "num_attention_heads_per_layer"))
    if not len(types) == len(kinds) == len(heads) == layers:
        raise ValueError("the lists by layer disagree with the layer count")
    if set(types) - set(SCOPES) or set(kinds) - {"dense", "sparse"} or set(
            config["gating_types"]) != {"per_head"}:
        raise ValueError("a layer of a type this block does not run")
    if [i for i, kind in enumerate(kinds) if kind == "dense"] != list(
            config["mlp_only_layers"]):
        raise ValueError("mlp_layer_types and mlp_only_layers disagree")
    if config["shared_expert_intermediate_size"] % config[
            "moe_intermediate_size"]:
        raise ValueError("the shared expert is whole experts wide")
    return {
        "D": config["hidden_size"], "L": layers, "types": types,
        "kinds": kinds, "heads": heads, "KV": config["num_key_value_heads"],
        "hd": config["head_dim"], "window": config["sliding_window"],
        "F": config["intermediate_size"],
        "Fe": config["moe_intermediate_size"],
        "Fs": config["shared_expert_intermediate_size"],
        "E": config["router_width"], "k": config["num_experts_per_tok"],
        "first": first, "held": held, "V": config["vocab_size"],
    }


def layer_specs(config: dict, i: int) -> list:
    s = sizes(config)
    std = float(config["initializer_range"])
    res = std / (2 * s["L"]) ** 0.5
    d, heads, p = s["D"], s["heads"][i], f"layer_{i}."
    specs = [
        ("ln_attn", (d,), None),
        ("q", (d, heads * s["hd"]), std),
        ("k", (d, s["KV"] * s["hd"]), std),
        ("v", (d, s["KV"] * s["hd"]), std),
        ("head_gate", (d, heads), std),
        ("o", (heads * s["hd"], d), res),
        ("ln_mlp", (d,), None),
    ]
    if s["kinds"][i] == "dense":
        specs += [("wg", (d, s["F"]), std), ("wu", (d, s["F"]), std),
                  ("wd", (s["F"], d), res)]
    else:
        specs += [
            ("router", (d, s["E"]), std),
            ("experts_wg", (s["held"], d, s["Fe"]), std),
            ("experts_wu", (s["held"], d, s["Fe"]), std),
            ("experts_wd", (s["held"], s["Fe"], d), res),
            ("shared_wg", (d, s["Fs"]), std), ("shared_wu", (d, s["Fs"]), std),
            ("shared_wd", (s["Fs"], d), res),
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
    ("attention", "gate_proj", "kernel"): "head_gate",
    ("attention", "out_proj", "kernel"): "o",
    ("mlp", "wg", "kernel"): "wg", ("mlp", "wi", "kernel"): "wu",
    ("mlp", "wo", "kernel"): "wd",
    ("moe", "router", "gate", "kernel"): "router",
    ("moe", "experts", "wg"): "experts_wg",
    ("moe", "experts", "wu"): "experts_wu",
    ("moe", "experts", "wd"): "experts_wd",
    ("moe", "shared_expert", "wg", "kernel"): "shared_wg",
    ("moe", "shared_expert", "wi", "kernel"): "shared_wu",
    ("moe", "shared_expert", "wo", "kernel"): "shared_wd",
}
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


def heads_of(config: dict, kind: str) -> int:
    """The query heads held here in the layers of type ``kind``."""
    s = sizes(config)
    held = {h for t, h in zip(s["types"], s["heads"]) if t == kind}
    if len(held) != 1:
        raise ValueError(f"{kind} layers of {sorted(held)} heads")
    return held.pop()


def model_config(config: dict, **overrides):
    """The program's ``TransformerConfig`` at the configuration's sizes."""
    try:
        from covalent_tpu_plugin.models.layers import YarnConfig
        from covalent_tpu_plugin.models.moe import RoutedExpertsConfig
        from covalent_tpu_plugin.models.transformer import (
            AttentionType,
            TransformerConfig,
        )
    except ImportError as err:
        raise LookupError(
            "this program cannot run model_type 'laguna': it has no "
            f"attention settings by layer ({err})") from None

    s = sizes(config)
    if config["moe_router_logit_softcapping"] or config[
            "moe_apply_router_weight_on_input"] or config["attention_bias"]:
        raise ValueError("the program's block caps no logit, weights the "
                         "experts' outputs and has no bias")
    types = []
    for kind in dict.fromkeys(s["types"]):
        rope = config["rope_parameters"][kind]
        yarn = None
        if rope["rope_type"] == "yarn":
            yarn = YarnConfig(
                factor=rope["factor"],
                original_max=rope["original_max_position_embeddings"],
                beta_fast=rope["beta_fast"], beta_slow=rope["beta_slow"],
                attention_factor=rope["attention_factor"])
        types.append(AttentionType(
            name=SCOPES[kind], n_heads=heads_of(config, kind),
            sliding_window=s["window"] if kind == "sliding_attention"
            else None,
            rope_base=rope["rope_theta"],
            rope_share=rope["partial_rotary_factor"], yarn=yarn, gate=True))
    return TransformerConfig(
        vocab_size=s["V"], d_model=s["D"], n_layers=s["L"],
        n_heads=config["num_attention_heads"], n_kv_heads=s["KV"],
        head_dim=s["hd"], d_ff=s["F"],
        dtype=jnp.dtype(config["activation_dtype"]),
        param_dtype=jnp.dtype(config["weight_dtype"]),
        scan_layers=False, mlp_gated=True, mlp_activation="silu",
        layer_kinds=tuple("dense" if kind == "dense" else "moe"
                          for kind in s["kinds"]),
        attention_types=tuple(types),
        attention_kinds=tuple(SCOPES[kind] for kind in s["types"]),
        routed=RoutedExpertsConfig(
            n_experts=s["E"], top_k=s["k"], d_ff=s["Fe"],
            n_shared=s["Fs"] // s["Fe"],
            routed_scaling=config["moe_routed_scaling_factor"],
            norm_topk=config["norm_topk_prob"], score="softmax",
            correction_bias=False, held=(s["first"], s["held"])),
        **overrides,
    )


def program(config: dict, job: dict, mesh):
    """``(TransformerLM, lm_loss)`` as the train job runs them; the loss
    also hands the routed layers' row counts to the train step.  Remat is
    fenced (``remat_prevent_cse``), as ``xing4_0`` builds it: merged with
    the first forward, every layer's row buffers would be kept."""
    from covalent_tpu_plugin.models import TransformerLM, lm_loss

    lm = TransformerLM(model_config(
        config, max_seq=job["sequence"], attention=job["attention"],
        remat=job["remat"], remat_prevent_cse=True, mesh=mesh,
    ))
    return lm, functools.partial(lm_loss, vocab_chunk=job["vocab_chunk"])


# -- the plain reference -----------------------------------------------------


def frequencies(rope: dict, dim: int):
    """The ``dim // 2`` inverse frequencies of one layer type's rotary
    dims: ``rope_theta``'s run and, under YaRN, each divided by ``factor``
    where a dim turns fewer than ``beta_slow`` times in the original
    context, kept where it turns more than ``beta_fast`` times, a linear
    ramp between (arXiv:2309.00071)."""
    theta = rope["rope_theta"]
    plain = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if rope["rope_type"] != "yarn":
        return plain

    def dim_of(turns):
        return dim * math.log(rope["original_max_position_embeddings"] / (
            turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(rope["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rope["beta_slow"])), dim - 1)
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3),
        0.0, 1.0)
    return plain / rope["factor"] * ramp + plain * (1.0 - ramp)


def rope(x, rope_parameters: dict):
    """Rotary embedding over (S, H, d): the first ``partial_rotary_factor``
    of the dims turn, as half-split (rotate_half) pairs, cos and sin times
    ``attention_factor`` where the type gives one; the rest pass."""
    turned = int(x.shape[-1] * rope_parameters["partial_rotary_factor"])
    half = turned // 2
    freqs = frequencies(rope_parameters, turned)
    amplitude = rope_parameters.get("attention_factor", 1.0)
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs[None]
    cos = (jnp.cos(angles) * amplitude)[:, None, :].astype(x.dtype)
    sin = (jnp.sin(angles) * amplitude)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:turned]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., turned:]], -1)


def attention(q, k, v, window, block=512):
    """Causal grouped-query attention over (S, H, d) / (S, KV, d), key
    ``j`` visible to query ``i`` iff ``0 <= i - j`` (``< window`` where
    there is one); a block of query rows at a time so that the (heads,
    block, S) scores fit.  Returns (S, H, d)."""
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
        behind = q_pos[:, None] - k_pos[None, :]
        seen = behind >= 0
        if window is not None:
            seen &= behind < window
        scores = jnp.where(seen[None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("kgqs,skd->qkgd", probs, v)

    out = jax.lax.map(rows, (jnp.arange(seq // block), qg))
    return out.reshape(seq, heads, head_dim)


def gated_attention(u, w, config, kind):
    """One attention sublayer over the normed input ``u`` (S, D): a sigmoid
    gate a head, from ``u``, on the kernel's output ahead of ``W_o``."""
    s = sizes(config)
    rope_parameters = config["rope_parameters"][kind]
    q = rope((u @ w["q"]).reshape(u.shape[0], -1, s["hd"]), rope_parameters)
    k = rope((u @ w["k"]).reshape(-1, s["KV"], s["hd"]), rope_parameters)
    v = (u @ w["v"]).reshape(-1, s["KV"], s["hd"])
    mixed = attention(
        q, k, v, s["window"] if kind == "sliding_attention" else None)
    gate = jax.nn.sigmoid(u @ w["head_gate"])
    return (mixed * gate[..., None]).reshape(u.shape[0], -1) @ w["o"]


def by_rows(fn, *arrays, block=4096):
    """``fn`` over arrays of S rows a block of rows at a time, each block's
    hidden arrays recomputed on the way back and not kept."""
    seq = arrays[0].shape[0]
    block = min(block, seq)
    if seq % block:
        raise ValueError(f"{seq} rows are not a multiple of {block}")
    out = jax.lax.map(
        jax.checkpoint(lambda blocks: fn(*blocks)),
        tuple(a.reshape(-1, block, a.shape[-1]) for a in arrays))
    return out.reshape(seq, -1)


def gated(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def route(h, w, config):
    """``(S, E)`` gates: each token's top-k probabilities, renormalised
    over the chosen and scaled, at its chosen experts, 0 elsewhere.  The
    softmax is over all ``E`` and in float32, whatever ``h``'s dtype."""
    s = sizes(config)
    probs = jax.nn.softmax(
        h.astype(jnp.float32) @ w["router"].astype(jnp.float32), axis=-1)
    picked, chosen = jax.lax.top_k(probs, s["k"])
    if config["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    picked = picked * config["moe_routed_scaling_factor"]
    return jnp.sum(
        jax.nn.one_hot(chosen, s["E"], dtype=jnp.float32) * picked[..., None],
        axis=1)


def gated_side_by_side(h, gates, wg, wu, wd):
    """Experts ``e`` side by side as one gated MLP that many times as wide,
    each hidden unit carrying its expert's gate (``gates`` (S, e)): every
    expert over every token in three products (a Python loop over the
    experts compiles three for each: PERF.md section 7)."""
    hidden = jax.nn.silu(jnp.einsum("sd,edf->sef", h, wg)) * jnp.einsum(
        "sd,edf->sef", h, wu) * gates[:, :, None]
    return jnp.einsum("sef,efd->sd", hidden, wd)


def experts(h, w, config):
    """The held experts' part, each weighted by its gate (0 for a token
    that did not choose it), and the shared expert, unweighted."""
    s = sizes(config)
    gates = route(h, w, config).astype(h.dtype)[
        :, s["first"]: s["first"] + s["held"]]

    def rows(hb, gb):
        return gated_side_by_side(
            hb, gb, w["experts_wg"], w["experts_wu"], w["experts_wd"]
        ) + gated(hb, w["shared_wg"], w["shared_wu"], w["shared_wd"])

    return by_rows(rows, h, gates)


def layer(x, w, config, kind, mlp_kind):
    """One block over (S, D): ``x + attention(norm(x))``, then ``+ mlp(
    norm(.))``.  Each sublayer is checkpointed on its own, so that the
    backward holds one sublayer's float32 intermediates at a time beside
    float32 weights, gradient and Adam's moments.  No checkpoint around the
    layer as well: it compiled every product once more, and the float32
    step's entry in the chip machine's compile cache was 81.5 MB for 71.1
    (PERF.md section 7), holding no less."""
    eps = config["rms_norm_eps"]

    def attention_sublayer(x, w):
        return x + gated_attention(
            reference.rms_norm(x, w["ln_attn"], eps, x.dtype), w, config, kind)

    def mlp_sublayer(x, w):
        m = reference.rms_norm(x, w["ln_mlp"], eps, x.dtype)
        if mlp_kind == "dense":
            return x + by_rows(
                lambda rows: gated(rows, w["wg"], w["wu"], w["wd"]), m)
        return x + experts(m, w, config)

    x = jax.checkpoint(attention_sublayer)(x, w)
    return jax.checkpoint(mlp_sublayer)(x, w)


def sequence_loss(w, tokens, config, dtype, positions=None):
    """Sum of next-token cross-entropies of one row of ``S + 1`` tokens
    (and the count).  ``positions`` keeps only the first that many (a
    planted fault)."""
    s = sizes(config)
    x = w["embedding"].astype(dtype)[tokens[:-1]]
    for i, (kind, mlp_kind) in enumerate(zip(s["types"], s["kinds"])):
        prefix = f"layer_{i}."
        lw = {n[len(prefix):]: a.astype(dtype) for n, a in w.items()
              if n.startswith(prefix)}
        x = layer(x, lw, config, kind, mlp_kind)
    feats = reference.rms_norm(x, w["ln_final"], config["rms_norm_eps"], dtype)
    return reference.head_loss(
        feats, tokens[1:], w["lm_head"].astype(dtype), positions)


# -- the needed work ---------------------------------------------------------


def expected_held_rows(config: dict, tokens: int) -> float:
    """(token, choice) pairs a sparse layer sends to the experts held here
    under even routing: tokens x k x held / E."""
    s = sizes(config)
    return tokens * s["k"] * s["held"] / s["E"]


def matmul_parameters(config: dict) -> float:
    """Matmul weights that touch a token in the forward, all layers and the
    head: attention's four kernels and the gate's, the dense MLP or the
    shared expert, the router and the expected share of a token's chosen
    experts that are held here.  The embedding is a lookup, the norms are
    vectors."""
    s = sizes(config)
    d = s["D"]
    expert = 3 * d * s["Fe"]
    total = d * s["V"]
    for heads, kind in zip(s["heads"], s["kinds"]):
        total += 2 * d * heads * s["hd"] + 2 * d * s["KV"] * s["hd"] + d * heads
        if kind == "dense":
            total += 3 * d * s["F"]
        else:
            total += d * s["E"] + 3 * d * s["Fs"] + (
                expert * s["k"] * s["held"] / s["E"])
    return total


def attention_forward_flops(config: dict, seq: int, kind: str) -> int:
    """QK^T and PV over the visible pairs, every held head of every layer
    of type ``kind``, one sequence: 2 matmuls x 2 FLOPs x head_dim a pair."""
    s = sizes(config)
    pairs = work.visible_pairs(
        seq, s["window"] if kind == "sliding_attention" else None)
    return sum(4 * s["hd"] * heads * pairs
               for t, heads in zip(s["types"], s["heads"]) if t == kind)


def train_flops_per_token(config: dict, job: dict) -> float:
    """Forward plus backward (twice the forward), no recompute: the matmul
    weights at 2 FLOPs each and attention over each layer's visible pairs."""
    seq = job["sequence"]
    forward = 2 * matmul_parameters(config) + sum(
        attention_forward_flops(config, seq, kind) for kind in SCOPES) / seq
    return 3.0 * forward


def kernel_work(config: dict, job: dict, kernel: str) -> dict:
    """Needed FLOPs and bytes of one named part of one train step, all
    layers.  ``attn_full`` / ``attn_sliding``: the three flash kernels of
    the layers of that type together, the forward over the visible pairs
    and the backward's four matmuls (twice the forward); S and P computed
    again by a kernel, and a forward run again by remat, count for nothing.
    Its bytes: Q, K, V in and O out forward; those four and dO in, dQ, dK,
    dV out backward.  ``experts``: the three grouped matmuls over the
    expected held rows, forward and twice that backward; its bytes the held
    weights read in both passes and their gradient written, the rows in
    and out."""
    s = sizes(config)
    seq, batch = job["sequence"], job["batch"]
    act = work._bytes(config["activation_dtype"])
    if kernel in SCOPES.values():
        kind = {scope: kind for kind, scope in SCOPES.items()}[kernel]
        heads = sum(h for t, h in zip(s["types"], s["heads"]) if t == kind)
        layers = s["types"].count(kind)
        q_bytes = seq * heads * s["hd"] * act
        kv_bytes = seq * layers * s["KV"] * s["hd"] * act
        return {
            "flops": 3 * attention_forward_flops(config, seq, kind) * batch,
            "bytes": 6 * (q_bytes + kv_bytes) * batch}
    if kernel != "experts":
        raise KeyError(kernel)
    rows = expected_held_rows(config, batch * seq)
    expert = 3 * s["D"] * s["Fe"]
    layers = s["kinds"].count("sparse")
    return {
        "flops": 3 * 2 * expert * rows * layers,
        "bytes": (3 * s["held"] * expert * act + 5 * rows * s["D"] * act)
        * layers}
