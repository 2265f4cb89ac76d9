"""Sharded training: state construction and jitted train steps.

The flax scale-up recipe, packaged: ``jax.eval_shape`` the state, read the
logical axis names off the boxed params, translate them to NamedShardings
through the rules, then jit init and step with explicit in/out shardings and
donated state.  Everything under ``jit`` — no data-dependent Python control
flow; XLA sees one static graph per (mesh, shapes) pair and inserts all
collectives (gradient psum over data axes, all-gathers for fsdp, etc.).
"""

from __future__ import annotations

from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from flax.training import train_state
from jax.sharding import Mesh

from ..obs import modelstats, stepstats
from ..parallel.sharding import DEFAULT_RULES, replicated


#: The metrics key under which a loss's second return value (what the model
#: counted in this step) leaves the compiled step.
MODEL_STATS = "model_stats"


class TrainState(train_state.TrainState):
    """flax TrainState (params + optax state + step)."""


def cross_entropy_loss(
    logits: jax.Array, labels: jax.Array, mask: jax.Array | None = None
) -> jax.Array:
    """Mean softmax cross-entropy in float32."""
    logits = logits.astype(jnp.float32)
    losses = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    if mask is not None:
        return (losses * mask).sum() / jnp.maximum(mask.sum(), 1)
    return losses.mean()


def make_sharded_train_state(
    model: nn.Module,
    tx: optax.GradientTransformation,
    rng: jax.Array,
    sample_input: Any,
    mesh: Mesh,
    rules=DEFAULT_RULES,
) -> tuple[TrainState, Any]:
    """Initialise a TrainState with every leaf placed per the logical rules.

    Returns ``(state, state_shardings)``; the shardings pytree feeds the
    train step's in/out shardings.  Parameters are materialised *directly
    into their shards* (init under jit with out_shardings), so a model too
    big for one host's memory still initialises.
    """

    def init_fn(rng):
        variables = model.init(rng, sample_input)
        return TrainState.create(
            apply_fn=model.apply, params=variables["params"], tx=tx
        )

    abstract = jax.eval_shape(init_fn, rng)
    logical_specs = nn.get_partition_spec(abstract)
    shardings = nn.logical_to_mesh_sharding(logical_specs, mesh, list(rules))
    # Deliberately NOT under `with mesh:`: the params are boxed with
    # *logical* axis names via nn.with_partitioning, and flax's
    # Partitioned.unbox applies those names verbatim as a sharding
    # constraint whenever a global mesh is active — "vocab"/"embed" are not
    # physical mesh axes, so tracing init (or apply) under an ambient mesh
    # raises.  Placement comes entirely from the explicit out_shardings,
    # which logical_to_mesh_sharding already translated through the rules.
    state = jax.jit(init_fn, out_shardings=shardings)(rng)
    return state, shardings


def make_train_step(
    loss_fn: Callable[[Any, Any, Any], jax.Array],
    mesh: Mesh,
    state_shardings: Any,
    rules=DEFAULT_RULES,
    donate_state: bool = True,
    accumulate_steps: int = 1,
) -> Callable[[TrainState, Any], tuple[TrainState, dict]]:
    """Build the jitted sharded train step.

    ``loss_fn(params, apply_fn, batch) -> scalar loss``.  The batch arrives
    sharded over the data axes; gradients and metrics come out as the mesh
    demands (XLA inserts the psums).  The state is donated — its buffers are
    reused for the updated state, halving peak HBM.

    ``accumulate_steps > 1`` enables gradient accumulation: every batch
    leaf carries a leading microbatch axis of that length (dim 1 is then
    the data-sharded batch dim), a ``lax.scan`` accumulates mean gradients
    across the microbatches — activation memory stays one microbatch — and
    the optimizer applies once.  With mean-reducing losses and equal-size
    microbatches this equals the full-batch gradient up to f32
    reduction-order rounding (the accumulator is f32 regardless of param
    dtype).

    A loss may return ``(loss, stats)``: what the model counted in this
    pass (``lm_loss`` over routed layers) leaves the step beside the
    metrics and goes to ``obs.modelstats``; under accumulation the counts
    are not kept.
    """
    def grads_of(params, apply_fn, batch):
        # Scoped so a device trace can tell the loss's forward and backward
        # from the optimizer's update (flax already scopes the modules).
        def scalar_and_stats(p):
            out = loss_fn(p, apply_fn, batch)
            return out if isinstance(out, tuple) else (out, None)

        with jax.named_scope("loss"):
            (loss, stats), grads = jax.value_and_grad(
                scalar_and_stats, has_aux=True)(params)
        return loss, grads, stats

    def step(state: TrainState, batch: Any) -> tuple[TrainState, dict]:
        with nn.logical_axis_rules(list(rules)):
            stats = None
            if accumulate_steps == 1:
                loss, grads, stats = grads_of(
                    state.params, state.apply_fn, batch)
            else:
                lead = {
                    leaf.shape[0] for leaf in jax.tree_util.tree_leaves(batch)
                }
                if lead != {accumulate_steps}:
                    raise ValueError(
                        f"accumulate_steps={accumulate_steps} but batch "
                        f"leaves have leading axis {sorted(lead)}; every "
                        "leaf needs a leading microbatch axis of that length"
                    )

                def micro(carry, microbatch):
                    loss_acc, grads_acc = carry
                    loss, grads, _ = grads_of(
                        state.params, state.apply_fn, microbatch
                    )
                    return (
                        loss_acc + loss,
                        jax.tree_util.tree_map(jnp.add, grads_acc, grads),
                    ), None

                zeros = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), state.params
                )
                (loss, grads), _ = jax.lax.scan(
                    micro, (jnp.zeros((), jnp.float32), zeros), batch
                )
                scale = 1.0 / accumulate_steps
                loss = loss * scale
                grads = jax.tree_util.tree_map(
                    lambda g, p: (g * scale).astype(p.dtype),
                    grads, state.params,
                )
            with jax.named_scope("optimizer"):
                new_state = state.apply_gradients(grads=grads)
            metrics = {
                "loss": loss,
                "grad_norm": optax.global_norm(grads),
                "step": new_state.step,
            }
            if stats is not None:
                metrics[MODEL_STATS] = stats
            return new_state, metrics

    return _TrainStep(jax.jit(
        step,
        in_shardings=(state_shardings, None),
        # One sharding for the whole metrics dict, whatever a loss adds.
        out_shardings=(state_shardings, replicated(mesh)),
        donate_argnums=(0,) if donate_state else (),
    ))


class _TrainStep:
    """The jitted step, called like it and standing for it (``lower``,
    ``trace``, ...).  Where the loss returns ``(loss, stats)``, the stats
    leave the compiled step beside the metrics and go to ``obs.modelstats``
    (read a step late, so that no step waits for them) in place of the
    caller's dict.  Every call is recorded by ``obs.stepstats``."""

    def __init__(self, jitted):
        self._jitted = jitted

    def __call__(self, state, batch):
        with stepstats.step() as call:
            with call.dispatch():
                state, metrics = self._jitted(state, batch)
            stats = metrics.pop(MODEL_STATS, None)
            if stats is not None:
                with call.stats():
                    modelstats.defer(stats)
        return state, metrics

    def __getattr__(self, name):
        return getattr(self._jitted, name)


def classifier_loss(params, apply_fn, batch):
    logits = apply_fn({"params": params}, batch["image"])
    return cross_entropy_loss(logits, batch["label"])


def lm_loss(params, apply_fn, batch, vocab_chunk: int | None = None):
    """Next-token loss over a {"tokens": (B, S)} batch.

    Where the model's routed layers counted in this pass (``models/moe.py``
    ``moe_stats``) it returns ``(loss, counts)``, which ``make_train_step``
    sends to ``obs.modelstats``; the loss alone where no layer counted.

    ``vocab_chunk`` switches to the fused cross-entropy (``ops/xent.py``):
    the model returns final FEATURES and the loss walks them in tiles of
    rows, each tile's scores the whole vocabulary wide, forming the
    gradients in the same pass, so the (B, S, vocab) logits tensor is
    never materialised in HBM.  ``vocab_chunk`` bounds the scores live at
    once, at B·S x vocab_chunk elements.  Requires a plain float lm_head
    kernel (no lm_head LoRA, unquantized)."""
    from .moe import collect_moe_stats

    tokens = batch["tokens"]
    options = {} if vocab_chunk is None else {"return_features": True}
    out, sown = apply_fn({"params": params}, tokens[:, :-1],
                         mutable=["intermediates"], **options)
    counted = collect_moe_stats(sown.get("intermediates", {}))

    def result(loss):
        return loss if counted is None else (loss, counted)

    if vocab_chunk is None:
        return result(cross_entropy_loss(out, tokens[:, 1:]))
    from ..ops.xent import fused_cross_entropy

    feats = out
    flat = feats.reshape(-1, feats.shape[-1])
    labels = tokens[:, 1:].reshape(-1)
    return result(fused_cross_entropy(flat, head_kernel(params), labels,
                                      vocab_chunk))


def head_kernel(params):
    """The lm_head kernel the fused loss (``ops/xent.py``) takes in place
    of the model's head."""
    from flax.core import meta as flax_meta

    # The kernel may ride in a flax Partitioned box (sharded init path).
    head = params["lm_head"]
    kernel = flax_meta.unbox(head["kernel"])
    if "lora_a" in head or not jnp.issubdtype(
        jnp.asarray(kernel).dtype, jnp.floating
    ):
        # A LoRA head's adapters would be silently dropped (and get zero
        # grads); a quantized head's kernel is int8 + scales.  Both take
        # the standard logits path.
        raise ValueError(
            "vocab_chunk needs a plain float lm_head kernel "
            "(quantized/LoRA heads take the standard path)"
        )
    return kernel


def make_lm_train_step(mesh, state_shardings, rules=DEFAULT_RULES):
    return make_train_step(lm_loss, mesh, state_shardings, rules)


def make_classifier_train_step(mesh, state_shardings, rules=DEFAULT_RULES):
    return make_train_step(classifier_loss, mesh, state_shardings, rules)
