"""The plain reference, as far as it is nobody's in particular: the norm,
the row-chunked head, the batch's mean loss with its planted fault, AdamW
written out and the steps that follow a train job; the block itself is the
architecture's (``archs/<model_type>.py``: ``sequence_loss``, ``layer``).
Straightforward ``jax.numpy``, float32 at matmul precision ``highest``,
with no kernel, cache or batching.

It imports nothing of the program and takes nothing the program has made:
weights come from ``weights.leaf`` and the seed.

Two uses decide ``correct``:

* ``serve_gaps``: one forward pass over each sampled prompt with its served
  tokens, layer by layer so that float32 weights of one layer are all that
  is held; returns by how much each served token's logit lies below the
  reference's best.
* ``train_readings``: AdamW steps on the timed batches, as many as the job
  checks (``check_steps``); returns each step's loss, every leaf's
  first-gradient norm and the norm of its change over those steps.

``dtype`` below float32 is the control: the same mathematics one precision
down, put in the program's place.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.suite import archs, weights

#: AdamW as the train jobs state it (optax.adamw's defaults, written out).
ADAM_B1, ADAM_B2, ADAM_EPS, WEIGHT_DECAY = 0.9, 0.999, 1e-8, 1e-4


def _precision(dtype):
    if dtype == jnp.float32:
        return jax.default_matmul_precision("highest")
    return contextlib.nullcontext()


def rms_norm(x, scale, eps, dtype):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(dtype)


def _layer_leaves(arch, config, key, hashes, dtype):
    """One layer's leaves by their short names (``layer_0.q`` -> ``q``), the
    names' hashes traced: one compiled block serves every layer."""
    return {
        name.split(".", 1)[1]: weights.leaf(
            key, hashes[j], shape, init, dtype, arch)
        for j, (name, shape, init) in enumerate(arch.layer_specs(config, 0))
    }


def _layer_hashes(arch, config: dict, i: int):
    return jnp.asarray(
        [weights.name_hash(name) for name, _, _ in arch.layer_specs(config, i)],
        jnp.int32,
    )


# -- serving ---------------------------------------------------------------


def serve_gaps(config: dict, seed: int, samples: list, length: int,
               max_served: int) -> list:
    """``samples`` is ``[(prompt, served), ...]``; returns one list of gaps
    per sample: the reference's best logit minus the served token's, at each
    served position, the context being the prompt and the tokens served
    before it.  ``max_served`` is the most tokens one sample can hold (the
    mix's largest budget).  Weights are held as the configuration serves
    them (``weight_dtype``) and computed on in float32."""
    held = jnp.dtype(config["weight_dtype"])
    f32 = jnp.float32
    key = weights.seed_key(seed)
    arch = archs.load(config)
    s = arch.sizes(config)
    top = {name: (shape, init)
           for name, shape, init in weights.leaf_specs(config)}
    tokens = np.zeros((len(samples), length), np.int32)
    for r, (prompt, served) in enumerate(samples):
        row = list(prompt) + list(served[:-1])
        if len(row) > length:
            raise ValueError(f"sample of {len(row)} tokens exceeds {length}")
        tokens[r, : len(row)] = row

    @jax.jit
    def embed(key, tokens):
        table = weights.leaf(key, "embedding", *top["embedding"], held, arch)
        return table[tokens].astype(f32)

    @jax.jit
    def block(key, hashes, x):
        w = {
            n: a.astype(f32)
            for n, a in _layer_leaves(arch, config, key, hashes, held).items()
        }
        with _precision(f32):
            return jax.lax.map(lambda row: arch.layer(row, w, config, f32), x)

    @jax.jit
    def head(key, x, rows, at, served):
        # One call of one shape whatever the samples' lengths: ``rows`` and
        # ``at`` pick each served position's features out of ``x``.
        kernel = weights.leaf(
            key, "lm_head", *top["lm_head"], held, arch
        ).astype(f32)
        feats = rms_norm(x[rows, at], jnp.ones((s["D"],), f32),
                         config["rms_norm_eps"], f32)
        with _precision(f32):
            logits = feats @ kernel
        picked = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
        return logits.max(axis=-1) - picked

    x = embed(key, jnp.asarray(tokens))
    for i in range(s["L"]):
        x = block(key, _layer_hashes(arch, config, i), x)
    # Padded to the most that the samples could hold, so the shape is the
    # cell's and not the run's.
    most = len(samples) * int(max_served)
    rows, at, served, owner = [], [], [], []
    for r, (prompt, out) in enumerate(samples):
        rows += [r] * len(out)
        at += range(len(prompt) - 1, len(prompt) - 1 + len(out))
        served += list(out)
        owner += [r] * len(out)
    n = len(rows)
    if n > most:
        raise ValueError(f"{n} served tokens exceed the stated {most}")
    pad = [0] * (most - n)
    flat = np.asarray(head(
        key, x, jnp.asarray(rows + pad, jnp.int32),
        jnp.asarray(at + pad, jnp.int32), jnp.asarray(served + pad, jnp.int32),
    ))[:n]
    gaps = [[] for _ in samples]
    for r, g in zip(owner, flat.tolist()):
        gaps[r].append(g)
    return gaps


# -- training --------------------------------------------------------------


def all_leaves(config: dict, seed: int, dtype) -> dict:
    key = weights.seed_key(seed)
    arch = archs.load(config)
    make = jax.jit(weights.leaf, static_argnums=(2, 3, 4, 5))
    return {
        name: make(key, weights.name_hash(name), shape, init, dtype, arch)
        for name, shape, init in weights.leaf_specs(config)
    }


def head_loss(feats, labels, kernel, positions=None, chunk=2048):
    """Sum of the cross-entropies of ``labels`` under ``feats @ kernel`` (and
    the count): logits a block of rows at a time, in float32.  ``positions``
    keeps only the first that many (a planted fault)."""
    if positions is not None:
        feats, labels = feats[:positions], labels[:positions]
    chunk = min(chunk, feats.shape[0])

    @jax.checkpoint
    def rows(args):
        f, y = args
        logits = (f @ kernel).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(logits, y[:, None], -1)[:, 0])

    n = feats.shape[0] // chunk * chunk
    total = jnp.sum(jax.lax.map(rows, (
        feats[:n].reshape(-1, chunk, feats.shape[-1]),
        labels[:n].reshape(-1, chunk),
    )))
    if n < feats.shape[0]:
        total = total + rows((feats[n:], labels[n:]))
    return total, feats.shape[0]


def batch_loss(w, batch, config, dtype, fault=None):
    """Mean next-token loss over a (B, S + 1) batch.  ``fault`` plants what
    a broken program would compute: ``half_batch`` leaves out the second
    half of the rows (of the positions, where there is one row) and takes
    the mean over the rest."""
    rows, positions = batch, None
    if fault == "half_batch":
        if batch.shape[0] > 1:
            rows = batch[: batch.shape[0] // 2]
        else:
            positions = (batch.shape[1] - 1) // 2
    sequence_loss = archs.load(config).sequence_loss
    with _precision(dtype):
        sums, counts = zip(*(
            sequence_loss(w, rows[b], config, dtype, positions)
            for b in range(rows.shape[0])
        ))
    return sum(sums) / sum(counts)


def adamw_step(w, m, v, grads, count, lr):
    """optax.adamw(lr) written out: decay 1e-4 on every leaf, no mask."""
    new_w, new_m, new_v = {}, {}, {}
    for name, p in w.items():
        g = grads[name].astype(p.dtype)
        new_m[name] = ADAM_B1 * m[name] + (1 - ADAM_B1) * g
        new_v[name] = ADAM_B2 * v[name] + (1 - ADAM_B2) * g * g
        m_hat = new_m[name] / (1 - ADAM_B1 ** count).astype(p.dtype)
        v_hat = new_v[name] / (1 - ADAM_B2 ** count).astype(p.dtype)
        update = m_hat / (jnp.sqrt(v_hat) + ADAM_EPS) + WEIGHT_DECAY * p
        new_w[name] = (p - lr * update).astype(p.dtype)
    return new_w, new_m, new_v


def _norm(x):
    x = x.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(x * x))


def make_train_step(config: dict, job: dict, dtype, fault):
    """One jitted AdamW step: ``(w, m, v, batch, count) -> (w, m, v, loss,
    {leaf: gradient norm})``, the state donated."""
    lr = float(job["learning_rate"])

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(w, m, v, batch, count):
        loss, grads = jax.value_and_grad(batch_loss)(
            w, batch, config, dtype, fault
        )
        norms = {n: _norm(g) for n, g in grads.items()}
        w, m, v = adamw_step(w, m, v, grads, count, lr)
        return w, m, v, loss, norms

    return step


def train_readings(config: dict, job: dict, seed: int, batches,
                   dtype=jnp.float32, fault=None) -> dict:
    """Follow the job's first steps (one per batch in ``batches``).

    Returns ``{"losses": [...], "grad_norms": {leaf: norm of the first
    gradient}, "delta_norms": {leaf: norm of the change after the last
    step}}``.  ``dtype`` below float32 holds weights, optimizer state and
    activations in it: the control."""
    dtype = jnp.dtype(dtype)
    w = all_leaves(config, seed, dtype)
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    m, v = zeros(w), zeros(w)

    step = make_train_step(config, job, dtype, fault)

    losses, grad_norms = [], None
    for i, batch in enumerate(batches):
        w, m, v, loss, norms = step(
            w, m, v, jnp.asarray(batch), jnp.asarray(i + 1, jnp.float32)
        )
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = {n: float(x) for n, x in norms.items()}
    del m, v
    key = weights.seed_key(seed)
    arch = archs.load(config)
    change = jax.jit(
        lambda key, now, name, shape, init: _norm(
            now.astype(jnp.float32)
            - weights.leaf(key, name, shape, init, dtype, arch)
            .astype(jnp.float32)
        ),
        static_argnums=(3, 4),
    )
    delta_norms = {
        name: float(change(key, w[name], weights.name_hash(name), shape, init))
        for name, shape, init in weights.leaf_specs(config)
    }
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta_norms}
