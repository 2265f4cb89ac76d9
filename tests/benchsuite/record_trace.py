"""Record the small trace the reducer's test reads (run once, on the chip):

    python3 tests/benchsuite/record_trace.py chiprun_out/small_trace

Two jitted programs of a few operations each, a host pause between them,
under ``jax.profiler``; prints what ``reduce.reduce_dir`` makes of it, which
is written beside the trace as the expected numbers.  The trace carries its
own map from instruction to ``op_name`` (the device plane's event metadata,
stat ``tf_op``), which ``reduce.read_scopes`` reads: ``"scopes"`` in what is
printed is each operation's own time laid to it.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    from benchmarks.suite import reduce

    @jax.jit
    def small_scan(x):
        def body(c, _):
            return jnp.tanh(c @ c), None
        return jax.lax.scan(body, x, None, length=3)[0]

    @jax.jit
    def small_sum(x):
        return jnp.sum(x * 2.0, axis=0)

    x = jnp.ones((256, 256), jnp.bfloat16)
    small_scan(x).block_until_ready()
    small_sum(x).block_until_ready()
    jax.profiler.start_trace(out_dir)
    for _ in range(2):
        y = small_scan(x)
        y.block_until_ready()
        time.sleep(0.01)
        small_sum(y).block_until_ready()
    jax.profiler.stop_trace()
    reduced = reduce.reduce_dir(out_dir)
    with open(os.path.join(out_dir, "expected.json"), "w") as f:
        json.dump(reduced, f, indent=1)
    print(json.dumps(reduced, indent=1))


if __name__ == "__main__":
    main(sys.argv[1])
