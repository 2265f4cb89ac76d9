"""The train→serve loop: fine-tune a LoRA on a spot pool, promote it
into a LIVE serving session — zero dropped requests across the swap.

The ROADMAP item-1 arc end to end, runnable on any machine:

1. a tiny LoRA fine-tune runs as electrons on a "spot" pool — the first
   lease is preempted mid-run (it checkpoints and returns), the second
   lease restores the checkpoint and finishes (`utils.checkpoint`);
2. the trained adapter's portable wire form (`models/lora.adapter_leaves`)
   is promoted through the sha256-verified CAS registry into a serving
   session that is ALREADY streaming base-model traffic — a live
   `serve_attach` splices it into the running engine's adapter bank,
   no restart, no recompile;
3. requests routed with ``params={"adapter": ...}`` decode bit-equal to
   a dedicated single-adapter oracle engine, while every base request
   issued across the promotion completes untouched.

This process never touches jax: every model, param tree and engine —
the oracle included — is built in a worker, and the stages run one
after another, so on a one-chip host each finds the chip free.  On a
real deployment, swap the executors for `workers=[...]` /
`tpu_name=...` and drop the CPU pins.  Run:

  JAX_PLATFORMS=cpu python examples/multi_model_lattice.py
"""

import asyncio
import os
import sys
import tempfile
import time

repo_root = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, repo_root)

from covalent_tpu_plugin import TPUExecutor
from covalent_tpu_plugin.serving import open_session
from covalent_tpu_plugin.workflow import dispatch_sync, electron, lattice

CONFIG = dict(
    vocab_size=64,
    d_model=32,
    n_layers=2,
    n_heads=2,
    d_ff=64,
    max_seq=64,
    attention="reference",
    scan_layers=False,  # serving-optimal, and required by add_lora
)

RANK = 4
TRAIN_STEPS = 12
PREEMPT_AT = 6
BASE_REQUESTS = 8
MAX_NEW_TOKENS = 10

workdir = tempfile.mkdtemp(prefix="covalent-tpu-multimodel-")

#: The "spot" pool: on a real fleet this is a preemptible slice
#: (`tpu_name=...` + the preemption-notice machinery); here it rides the
#: local transport so the example runs green anywhere.
spot = TPUExecutor(
    transport="local",
    cache_dir=os.path.join(workdir, "cache_spot"),
    remote_cache=os.path.join(workdir, "remote_spot"),
    python_path=sys.executable,
    poll_freq=0.2,
    task_env={
        "PYTHONPATH": os.path.abspath(repo_root) + os.pathsep
        + os.environ.get("PYTHONPATH", ""),
        "JAX_PLATFORMS": "cpu",  # drop on a real TPU VM
    },
)

CKPT = os.path.join(workdir, "lora_ckpt")


def _train(config_dict, ckpt_dir, start_step, end_step):
    """One spot lease's worth of LoRA fine-tuning (runs IN the worker):
    restore the latest checkpoint if one exists, train to ``end_step``,
    checkpoint, and return the step reached + the adapter leaves."""
    import jax as jax_mod
    import jax.numpy as jnp_mod
    import numpy as np_mod
    import optax

    from covalent_tpu_plugin.models import (
        TransformerConfig as Config,
        TransformerLM as LM,
        add_lora as add_lora_fn,
        lora_optimizer,
    )
    from covalent_tpu_plugin.models import lora as lora_lib
    from covalent_tpu_plugin.models.train import lm_loss
    from covalent_tpu_plugin.utils import (
        latest_step,
        restore_checkpoint,
        save_checkpoint,
    )

    cfg = Config(**config_dict)
    model = LM(cfg)
    tokens = jax_mod.random.randint(
        jax_mod.random.PRNGKey(1), (4, 17), 0, cfg.vocab_size
    )
    params = model.init(jax_mod.random.PRNGKey(0), tokens)["params"]
    lmodel, lparams = add_lora_fn(model, params, rank=RANK)
    tx = lora_optimizer(optax.adam(1e-2), lparams)
    opt_state = tx.init(lparams)
    step0 = start_step
    have = latest_step(ckpt_dir)
    if have is not None:
        # The fresh (lparams, opt_state) is the restore template: orbax
        # needs it to rebuild optax's namedtuple states from the raw tree.
        lparams, opt_state = restore_checkpoint(
            have, ckpt_dir, template=(lparams, opt_state)
        )
        step0 = have

    @jax_mod.jit
    def train_step(p, o):
        loss, grads = jax_mod.value_and_grad(
            lambda q: lm_loss(q, lmodel.apply, {"tokens": tokens})
        )(p)
        updates, o = tx.update(grads, o, p)
        return optax.apply_updates(p, updates), o, loss

    loss = jnp_mod.float32(0)
    for _ in range(step0, end_step):
        lparams, opt_state, loss = train_step(lparams, opt_state)
    save_checkpoint((lparams, opt_state), end_step, ckpt_dir)
    leaves = [
        np_mod.asarray(leaf)
        for leaf in lora_lib.adapter_leaves(lparams)
    ]
    return {"step": end_step, "loss": float(loss), "leaves": leaves}


@electron(executor=spot)
def spot_lease_one(config_dict: dict, ckpt_dir: str) -> dict:
    # First lease: trains to PREEMPT_AT, checkpoints — then the "spot
    # reclaim" ends it.  (A real preemption interrupts the electron and
    # the retry restores; the checkpoint contract is identical.)
    return _train(config_dict, ckpt_dir, 0, PREEMPT_AT)


@electron(executor=spot)
def spot_lease_two(config_dict: dict, ckpt_dir: str, prior: dict) -> dict:
    # Second lease: restores the journaled step and finishes the run.
    assert prior["step"] == PREEMPT_AT
    return _train(config_dict, ckpt_dir, prior["step"], TRAIN_STEPS)


@lattice
def finetune(config_dict: dict, ckpt_dir: str) -> dict:
    return spot_lease_two(
        config_dict, ckpt_dir, spot_lease_one(config_dict, ckpt_dir)
    )


def base_model():
    """Worker side: the serving model and its seeded base params."""
    import jax
    import jax.numpy as jnp

    from covalent_tpu_plugin.models import TransformerConfig, TransformerLM

    model = TransformerLM(TransformerConfig(dtype=jnp.float32, **CONFIG))
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def engine_factory():
    """Runs ONCE, inside the resident serving worker — the process that
    holds the accelerator builds everything that lives on it."""
    from covalent_tpu_plugin.models.serve import ContinuousEngine

    model, params = base_model()
    # adapter_rank sizes the (empty) bank; attach fills it live.
    return ContinuousEngine(
        model, params, max_batch=4, sync_steps=4, adapter_rank=RANK
    )


@electron(executor=spot)
def oracle_stream(leaves: list) -> list:
    """A dedicated single-adapter engine, rebuilt from the portable leaf
    list (the registry wire form), decoding the promoted request."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from covalent_tpu_plugin.models import add_lora
    from covalent_tpu_plugin.models import lora as lora_mod
    from covalent_tpu_plugin.models.serve import ContinuousEngine

    model, params = base_model()
    lmodel, filled = add_lora(model, params, rank=RANK)
    mask = jax.tree_util.tree_leaves(lora_mod.lora_mask(filled))
    flat, treedef = jax.tree_util.tree_flatten(filled)
    it = iter(leaves)
    tuned = jax.tree_util.tree_unflatten(treedef, [
        jnp.asarray(next(it)) if m else leaf for leaf, m in zip(flat, mask)
    ])
    oracle = ContinuousEngine(
        lmodel, tuned, max_batch=2, sync_steps=4,
        max_new_tokens=MAX_NEW_TOKENS, length=48,
    )
    oracle.admit("r", np.asarray([7], np.int32))
    expected: list = []
    while oracle.busy:
        for event in oracle.step():
            expected.extend(event["tokens"])
    oracle.close()
    return [int(t) for t in expected]


@lattice
def oracle(leaves: list) -> list:
    return oracle_stream(leaves)


async def serve_and_promote(leaves) -> list:
    executor = TPUExecutor(
        transport="local",
        cache_dir=os.path.join(workdir, "cache_serve"),
        remote_cache=os.path.join(workdir, "remote_serve"),
        python_path=sys.executable,
        use_agent="pool",  # sessions live in the resident runtime
        prewarm=False,
        heartbeat_interval=0.0,
        task_env={
            "PYTHONPATH": os.path.abspath(repo_root) + os.pathsep
            + os.environ.get("PYTHONPATH", ""),
            "JAX_PLATFORMS": "cpu",  # drop on a real TPU VM
        },
    )
    t0 = time.perf_counter()
    handle = await open_session(
        executor, engine_factory, stats_interval_s=0.5,
    )
    print(f"session {handle.sid} open in {time.perf_counter() - t0:.1f}s "
          f"(adapter bank, rank {RANK})")
    try:
        # Base traffic first — and it KEEPS flowing while we promote.
        in_flight = [
            await handle.request(
                [i % CONFIG["vocab_size"]],
                params={"max_new_tokens": MAX_NEW_TOKENS},
            )
            for i in range(BASE_REQUESTS)
        ]

        # THE PROMOTION: the trained adapter's leaf list ships through
        # the CAS registry (sha256-verified bundle) and splices into the
        # running engine between decode waves.  No reopen, no recompile,
        # and none of the in-flight base streams notice.
        t1 = time.perf_counter()
        ack = await handle.attach_adapter("tuned", payload=leaves)
        print(f"promoted adapter 'tuned' "
              f"({ack['digest'][:12]}…) in {ack['attach_s']:.3f}s "
              f"worker-side, {time.perf_counter() - t1:.2f}s end to end; "
              f"book: {handle.adapters}")

        tuned_request = await handle.request(
            [7], params={"max_new_tokens": MAX_NEW_TOKENS,
                         "adapter": "tuned"},
        )
        results = await asyncio.gather(
            *(r.result(60.0) for r in in_flight),
            tuned_request.result(60.0),
        )
        base_streams, tuned_stream = results[:-1], results[-1]

        # Zero drops across the promotion: every base request issued
        # BEFORE the attach ran to completion.
        assert all(
            len(stream) == MAX_NEW_TOKENS for stream in base_streams
        ), "a base stream was dropped across the promotion"

        print(f"{BASE_REQUESTS} base requests completed across the "
              f"promotion (zero drops)")
        print("worker stats:", {
            k: v for k, v in (handle.stats or {}).items()
            if k.startswith("adapter_")
        })
    finally:
        closed = await handle.close()
        await executor.close()
        print("closed after", closed.get("served"), "requests served")
    return tuned_stream


if __name__ == "__main__":
    result = dispatch_sync(finetune)(CONFIG, CKPT)
    assert result.status == "COMPLETED", result.error
    trained = result.result
    print(f"fine-tune done at step {trained['step']} "
          f"(preempted at {PREEMPT_AT}, resumed from checkpoint), "
          f"loss {trained['loss']:.4f}, "
          f"{len(trained['leaves'])} adapter leaves")

    tuned_stream = asyncio.run(serve_and_promote(trained["leaves"]))

    # The promoted adapter decodes bit-equal to a dedicated single-adapter
    # oracle engine built from the same leaves — in a worker of its own,
    # after the session's runtime has gone and the accelerator is free.
    expected = dispatch_sync(oracle)(trained["leaves"])
    assert expected.status == "COMPLETED", expected.error
    assert tuned_stream == expected.result, "promoted adapter diverged"
    print(f"tuned stream bit-equal to the single-adapter oracle: "
          f"{tuned_stream}")
