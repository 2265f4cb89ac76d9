"""Windowed flash kernel sweep: banded-grid win vs full flash across
(S, window, block) configs, on whatever backend is present.

Run on the TPU VM:  python benchmarks/sweep_window.py
Prints one JSON line per config (resumable under a driver timeout).

Timing method: data-dependent chained iterations inside ONE jit (each
fwd+bwd's dq feeds the next iteration's q), so the measurement is pure
device time — per-dispatch host overhead appears in neither arm (a
two-batch delta method mis-ranked sub-10ms configs where chained timing
reproduced within a few percent across reruns).
"""

from __future__ import annotations

import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from covalent_tpu_plugin.ops.attention import flash_attention  # noqa: E402


def chain_ms(q, k, v, window, block_q=None, block_k=None, iters=16,
             trials=3):
    """Pure on-device ms per fwd+bwd: (iters-chain − 1-chain)/(iters−1)."""

    def one(q_in):
        dq = jax.grad(
            lambda q_: flash_attention(
                q_, k, v, causal=True, window=window,
                block_q=block_q, block_k=block_k,
            ).astype(jnp.float32).sum()
        )(q_in)
        # Data dependency serialises iterations on device; the axpy is
        # noise next to the attention FLOPs.
        return q_in + (1e-6 * dq).astype(q_in.dtype)

    @jax.jit
    def chain(q0, n):
        return jax.lax.fori_loop(0, n, lambda i, q_: one(q_), q0)

    jax.device_get(chain(q, iters)[0, 0, 0, 0])  # compile both shapes
    jax.device_get(chain(q, 1)[0, 0, 0, 0])
    samples = []
    for _ in range(trials):
        t0 = time.monotonic()
        jax.device_get(chain(q, 1)[0, 0, 0, 0])
        t1 = time.monotonic() - t0
        t0 = time.monotonic()
        jax.device_get(chain(q, iters)[0, 0, 0, 0])
        tn = time.monotonic() - t0
        if tn > t1:
            samples.append((tn - t1) / (iters - 1))
    return statistics.median(samples) * 1e3 if samples else float("nan")


def main() -> None:
    print(json.dumps({"devices": str(jax.devices())}), flush=True)
    b, h, d = 1, 8, 64
    for s in (4096, 8192, 16384):
        q, k, v = (
            jax.random.normal(jax.random.PRNGKey(i), (b, h, s, d), jnp.bfloat16)
            for i in range(3)
        )
        iters = max(8, 16384 * 16 // s)
        full = chain_ms(q, k, v, None, iters=iters)
        print(json.dumps({"s": s, "window": None,
                          "fwd_bwd_ms": round(full, 3)}), flush=True)
        for window in (512, 1024, 2048, 4096):
            if window >= s:
                continue
            # (256, *) rows: the interior-tile fast path cut per-tile VPU
            # overhead, which is what made tighter tiles lose before (512^2
            # has a 5.7x geometry ceiling at w=1k, 512x256 6.8x).
            for blocks in (None, (512, 512), (512, 1024), (1024, 1024),
                           (512, 256), (256, 256), (256, 512)):
                bq, bk = blocks if blocks else (None, None)
                unit = chain_ms(q, k, v, window, bq, bk, iters=iters)
                print(json.dumps({
                    "s": s, "window": window, "block_q": bq, "block_k": bk,
                    "fwd_bwd_ms": round(unit, 3),
                    "speedup_vs_full": round(full / unit, 2),
                }), flush=True)


if __name__ == "__main__":
    main()
