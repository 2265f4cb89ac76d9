"""Training cells: one launch-mode electron through ``TPUExecutor.run`` over
the local transport builds the sharded state and the compiled step, and
times the window inside the worker.  The harness's process stays off JAX
until the electron's worker is gone, then takes the chip for the reference.
"""

from __future__ import annotations

import asyncio
import math
import os
import time

from benchmarks.suite import compare, harness_util, loadgen, program
from benchmarks.suite.readers import slow_steps


async def _dispatch(cell: dict, args, trace_dir, workdir: str,
                    chips: int | None) -> dict:
    executor = harness_util.executor(cell, workdir, dispatch_mode="launch")
    try:
        report = await executor.run(
            program.train_electron,
            [cell["config"], cell["traffic"], args.seed, args.seconds,
             trace_dir, chips],
            {}, {"dispatch_id": "bench-suite", "node_id": 0},
        )
    finally:
        await executor.close()
    await harness_util.await_workers_gone()
    return report


def readings(cell: dict, args, dtype="float32", fault=None) -> dict:
    """The plain reference over the job's first steps (``dtype`` below
    float32, or a ``fault``: the control's and the faults' readings)."""
    from benchmarks.suite import reference

    job = cell["traffic"]
    batches = loadgen.train_batches(
        cell["config"], job, args.seed, job["feed_batches"]
    )[: int(job["check_steps"])]
    return reference.train_readings(
        cell["config"], job, args.seed, batches, dtype=dtype, fault=fault
    )


def run(cell: dict, args, t_start: float, require_tpu: bool = True,
        hooks: dict | None = None) -> dict:
    """One training run.  ``hooks`` (tests only) runs the electron in this
    process with the timed path broken underneath: ``hooks["step"]`` and
    ``hooks["loss_fn"]`` wrap the compiled step and the loss."""
    workdir = harness_util.workdir()
    trace_dir = os.path.join(workdir, "trace") if args.trace else None
    try:
        if args.control or args.fault:
            # The control (the reference one precision down) or a planted
            # fault, put in the program's place: readings only, no window.
            t0 = time.time()
            report = readings(
                cell, args,
                dtype="bfloat16" if args.control else "float32",
                fault=args.fault or None,
            )
            report.update(program.device_report(), steps=0, window_s=1.0,
                          step_times=[], tokens_per_step=0,
                          window_start=t0, compiles=[])
        elif hooks:
            report = program.train_electron(
                cell["config"], cell["traffic"], args.seed, args.seconds,
                trace_dir, None, hooks)
        else:
            report = asyncio.run(_dispatch(
                cell, args, trace_dir, workdir,
                cell["chips"] if require_tpu else None))
        harness_util.check_report(report)
        trace = None
        if trace_dir:
            from benchmarks.suite import reduce

            trace = reduce.reduce_dir(trace_dir)
            if trace is not None and report.get("trace_window_s"):
                # The electron's own clock around the traced steps: the
                # trace's span also holds the profiler's start and stop.
                trace["window_s"] = report["trace_window_s"]
        t_check = time.time()
        numbers = compare.train_numbers(report, readings(cell, args))
        numbers["check_s"] = time.time() - t_check
        # Not compared: the untraced line's notes tell a host's stall (a few
        # slow steps) from a slower program.
        numbers["slow_steps"] = slow_steps.count(report["step_times"])
    finally:
        harness_util.cleanup(workdir)
    tokens = report["steps"] * report["tokens_per_step"]
    end_to_end = {
        "train_tok_s": tokens / report["window_s"],
        "setup_s": report["window_start"] - t_start,
    }
    window = (report["window_start"],
              report["window_start"] + report["window_s"])
    context = {
        "cell": cell, "trace": trace, "window_s": report["window_s"],
        "end_to_end": end_to_end, "step_times": report["step_times"],
        "trace_steps": report.get("trace_steps"),
        "compiles_in_window": sum(
            1 for t, _ in report["compiles"] if window[0] <= t <= window[1]),
        "setup_parts": {k: report.get(k) for k in ("state_s", "check_s")},
    }
    return {
        "end_to_end": end_to_end,
        # A window that ends on a loss that is not finite has failed whole.
        "attempted": report["steps"],
        "failed": 0 if math.isfinite(report.get("last_loss", 0.0))
        else report["steps"],
        "device": {k: report[k] for k in
                   ("platform", "kind", "count", "memory_peak_bytes")},
        "numbers": numbers, "context": context,
    }
