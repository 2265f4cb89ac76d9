"""Sharded LM training on a device mesh, with checkpoint save/resume.

Uses the same 4-axis mesh (data/fsdp/tensor/seq) and sharded train step
the multi-host path uses — on 8 virtual CPU devices here, on real chips
unchanged.  Scale `TransformerConfig` up and point `jax.distributed` at
a pod (the harness does this per worker) for the real thing.

Run:  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_lm.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax


import jax.numpy as jnp
import numpy as np
import optax

from covalent_tpu_plugin.models import TransformerConfig, TransformerLM
from covalent_tpu_plugin.models.data import synthetic_lm_batches
from covalent_tpu_plugin.models.train import (
    lm_loss,
    make_sharded_train_state,
    make_train_step,
)
from covalent_tpu_plugin.parallel import MeshPlan, make_mesh, shard_batch
from covalent_tpu_plugin.utils.checkpoint import (
    restore_checkpoint,
    save_checkpoint,
)


def main() -> None:
    mesh = make_mesh(MeshPlan(data=2, fsdp=2, tensor=2))
    config = TransformerConfig(
        vocab_size=512,
        d_model=64,
        n_layers=2,
        n_heads=4,
        d_ff=128,
        max_seq=64,
        dtype=jnp.float32,
        attention="reference",
    )
    model = TransformerLM(config)
    batches = synthetic_lm_batches(
        steps=6, batch_size=8, seq_len=33, vocab_size=config.vocab_size, seed=0
    )

    sample = next(batches)
    state, shardings = make_sharded_train_state(
        model, optax.adamw(1e-3), jax.random.PRNGKey(0),
        jnp.asarray(sample["tokens"][:, :-1]), mesh,
    )
    step = make_train_step(lm_loss, mesh, shardings)

    ckpt_dir = tempfile.mkdtemp(prefix="lm-ckpt-")
    for i in range(5):
        batch = shard_batch(next(batches), mesh)
        state, metrics = step(state, batch)
        print(f"step {int(metrics['step'])}: loss {float(metrics['loss']):.4f}")
    save_checkpoint(jax.device_get(state.params), int(metrics["step"]), ckpt_dir)

    # Resume: fresh state, parameters restored from the checkpoint.
    restored = restore_checkpoint(base=ckpt_dir)
    same = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(
            jax.tree_util.tree_leaves(jax.device_get(state.params)),
            jax.tree_util.tree_leaves(restored),
        )
    )
    print("checkpoint round-trip exact:", same)
    assert same


if __name__ == "__main__":
    main()
