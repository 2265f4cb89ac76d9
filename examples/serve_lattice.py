"""One resident serving session, many concurrent callers.

The ISSUE 9 acceptance shape, runnable on any machine: a tiny
TransformerLM is built and compiled ONCE inside a warm gang's resident
runtime (`serve_open` ships the engine factory by CAS digest), then 12
concurrent requests from two tenants share its fixed-slot continuous
batch — each a single `serve_request` write on the held-open agent
channel, tokens streamed back incrementally so time-to-first-token is
one decode chunk, not end-of-batch.  Shows:

* `serving.open_session` with a factory that builds the model, its
  params and the `ContinuousEngine` in the worker,
* the `request.stream()` chunk iterator (real TTFT) vs `result()`,
* per-session stats (queue depth, tokens/s) and the session status view.

This process never touches jax: an accelerator belongs to one process,
and that process is the resident worker.  On a real deployment, swap the
executor for `workers=[...]` / `tpu_name=...` and drop the CPU pin.  Run:

  JAX_PLATFORMS=cpu python examples/serve_lattice.py
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

CONFIG = dict(
    vocab_size=256,
    d_model=64,
    n_layers=2,
    n_heads=4,
    d_ff=128,
    max_seq=64,
    attention="reference",
    scan_layers=False,  # unrolled: the serving mode
)

REQUESTS = 12
MAX_NEW_TOKENS = 12


def engine_factory():
    """Runs ONCE, inside the resident worker: the model, its params and
    the engine are built by the process that holds the accelerator —
    nothing device-resident is pickled across from the dispatcher."""
    import jax
    import jax.numpy as jnp

    from covalent_tpu_plugin.models import TransformerConfig, TransformerLM
    from covalent_tpu_plugin.models.serve import ContinuousEngine

    model = TransformerLM(TransformerConfig(**CONFIG))
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return ContinuousEngine(model, params, max_batch=4, sync_steps=4)


async def main() -> None:
    workdir = tempfile.mkdtemp(prefix="covalent-tpu-serve-")
    executor = TPUExecutor(
        transport="local",
        cache_dir=os.path.join(workdir, "cache"),
        remote_cache=os.path.join(workdir, "remote"),
        python_path=sys.executable,
        use_agent="pool",  # sessions live in the resident runtime
        prewarm=False,
        heartbeat_interval=0.0,
        # The factory imports the package inside the resident worker.
        task_env={
            "PYTHONPATH": os.path.abspath(repo_root) + os.pathsep
            + os.environ.get("PYTHONPATH", ""),
            "JAX_PLATFORMS": "cpu",  # drop on a real TPU VM
        },
    )

    t0 = time.perf_counter()
    handle = await open_session(
        executor,
        # Model build + prefill/decode jit happen ONCE, in the worker:
        engine_factory,
        stats_interval_s=0.5,
    )
    print(f"session {handle.sid} open in {time.perf_counter() - t0:.1f}s "
          f"({handle.slots} slots)")

    try:
        # One streamed request: chunks arrive while the batch decodes.
        streamed = await handle.request(
            [1, 2, 3], params={"max_new_tokens": MAX_NEW_TOKENS},
            tenant="interactive",
        )
        async for chunk in streamed.stream():
            print(f"  stream chunk (+{streamed.ttft_s:.3f}s ttft): {chunk}")

        # A concurrent two-tenant fan-out through the SAME session: every
        # request shares the engine's fixed-slot batch; nobody re-loads
        # or re-compiles anything.
        t1 = time.perf_counter()
        requests = [
            await handle.request(
                [i % CONFIG["vocab_size"]],
                params={"max_new_tokens": MAX_NEW_TOKENS},
                tenant="interactive" if i % 2 else "batch",
            )
            for i in range(REQUESTS)
        ]
        results = await asyncio.gather(*(r.result(60.0) for r in requests))
        wall = time.perf_counter() - t1

        tokens = sum(len(r) for r in results)
        ttfts = sorted(r.ttft_s for r in requests)
        print(f"{REQUESTS} concurrent requests: {tokens} tokens "
              f"in {wall:.2f}s ({tokens / wall:.0f} tok/s aggregate), "
              f"ttft p50 {ttfts[len(ttfts) // 2] * 1000:.0f}ms")
        print("worker stats:", handle.stats)
        print("session view:", handle.status())
    finally:
        closed = await handle.close()
        print("closed after", closed.get("served"), "requests served")
        await executor.close()


if __name__ == "__main__":
    asyncio.run(main())
