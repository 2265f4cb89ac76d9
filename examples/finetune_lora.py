"""LoRA on a float base and QLoRA on a frozen int8 base.

Adapters start at exact identity (B=0), train through either the masked
optimizer (float base) or the adapter-only split step (int8 base — plain
jax.grad refuses int8 inputs), and fold back into plain kernels.

Run:  JAX_PLATFORMS=cpu python examples/finetune_lora.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax


import jax.numpy as jnp
import numpy as np
import optax

from covalent_tpu_plugin.models import (
    TransformerConfig,
    TransformerLM,
    add_lora,
    lora_optimizer,
    lora_train_params,
    make_lora_train_state,
    make_lora_train_step,
    merge_lora,
    quantize_then_lora,
)
from covalent_tpu_plugin.models.train import lm_loss

CONFIG = TransformerConfig(
    vocab_size=256,
    d_model=64,
    n_layers=2,
    n_heads=4,
    d_ff=128,
    max_seq=32,
    dtype=jnp.float32,
    attention="reference",
    scan_layers=False,
)


def main() -> None:
    model = TransformerLM(CONFIG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 17), 0, CONFIG.vocab_size)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    batch = {"tokens": tokens}

    # ---- float-base LoRA: standard step + masked optimizer --------------
    lmodel, lparams = add_lora(model, params, rank=8)
    tx = lora_optimizer(optax.adam(1e-2), lparams)
    opt_state = tx.init(lparams)

    @jax.jit
    def step(p, o):
        loss, grads = jax.value_and_grad(
            lambda q: lm_loss(q, lmodel.apply, batch)
        )(p)
        updates, o = tx.update(grads, o, p)
        return optax.apply_updates(p, updates), o, loss

    for i in range(8):
        lparams, opt_state, loss = step(lparams, opt_state)
        if i % 2 == 0:
            print(f"lora step {i}: loss {float(loss):.4f}")

    plain_model, merged = merge_lora(lmodel, lparams)
    out = plain_model.apply({"params": merged}, tokens)
    print("merged adapters -> plain checkpoint, logits", out.shape)

    # ---- QLoRA: frozen int8 base, adapter-only split step ---------------
    qlmodel, qlparams = quantize_then_lora(model, params, rank=8)
    qtx = optax.adam(1e-2)
    state = make_lora_train_state(qlparams, qtx)
    qstep = make_lora_train_step(lm_loss, qlmodel.apply)
    for i in range(8):
        state, loss = qstep(state, batch)
        if i % 2 == 0:
            print(f"qlora step {i}: loss {float(loss):.4f}")
    final = qlmodel.apply({"params": lora_train_params(state)}, tokens)
    assert np.isfinite(np.asarray(final, np.float32)).all()
    print("qlora trained over a frozen int8 base, logits", final.shape)


if __name__ == "__main__":
    main()
