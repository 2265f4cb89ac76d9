"""Run one cell of ``BENCHMARK.json`` once, in a new process.

    python3 benchmarks/suite/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Set-up (weights on the device from ``--seed``, warm-up of the cell's shapes)
is counted as ``setup_s``; then ``--seconds`` are measured; then, with the
program's workers gone, the plain reference decides ``correct``.  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced), the
numbers compared last.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics.  Without the chips the cell asks for
it exits with code 2 and prints no result.
"""

from __future__ import annotations

import argparse
import importlib
import json
import math
import os
import sys
import time

T_START = time.time()
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.suite import compare, harness_util, spec  # noqa: E402


def read_metrics(cell: dict, context: dict) -> dict:
    """Every per-layer metric of the cell whose reader finds something."""
    out = {}
    for metric in cell["per_layer"]:
        reader = importlib.import_module(
            f"benchmarks.suite.readers.{metric['reader']}")
        value = reader.read(context, **metric.get("args", {}))
        if value is not None and math.isfinite(value):
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: int, *, require_tpu: bool = True, control: int = 0,
             fault: str = "", t_start: float | None = None,
             kind_options: dict | None = None) -> dict:
    """One run; returns the result object.  ``require_tpu=False`` is for
    rehearsals and tests at stand-in sizes: the line then names the CPU it
    ran on, and no number in it is a device measurement."""
    cell = spec.load_cell(root, workload)
    args = argparse.Namespace(
        workload=workload, seed=int(seed), seconds=float(seconds),
        trace=int(trace), control=int(control), fault=fault,
    )
    kind = importlib.import_module(
        f"benchmarks.suite.kinds.{cell['traffic']['kind']}")
    outcome = kind.run(cell, args, T_START if t_start is None else t_start,
                       require_tpu=require_tpu, **(kind_options or {}))
    correct, compared = compare.judge(outcome["numbers"], cell["limits"])
    context = outcome["context"]
    context["device"] = outcome["device"]
    context["chips"] = cell["chips"]
    context["require_tpu"] = require_tpu
    device = dict(outcome["device"])
    if args.trace:
        metrics = read_metrics(cell, context)
        traced = context.get("trace")
        if traced is not None:
            device["busy_s"] = traced["busy_s"]
            device["window_s"] = traced["window_s"]
    else:
        metrics = {
            m["name"]: {"value": outcome["end_to_end"][m["name"]],
                        "unit": m["unit"]}
            for m in cell["end_to_end"]
        }
    result = {
        "correct": bool(correct),
        "attempted": int(outcome["attempted"]),
        "failed": int(outcome["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if args.trace and context.get("trace") is not None:
        from benchmarks.suite import reduce

        result["breakdown"] = reduce.breakdown(context["trace"])
    result["notes"] = {
        k: v for k, v in outcome["numbers"].items()
        if k not in compared and not k.startswith("_")
    }
    result["notes"]["setup_parts"] = context.get("setup_parts")
    result["compared"] = compared
    return result


def main(argv: list | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument(
        "--control", type=int, choices=(0, 1), default=0,
        help="not a benchmark run: put the control (one precision down) in "
             "the program's place and print what the comparison reads")
    parser.add_argument(
        "--fault", default="",
        help="not a benchmark run: plant this fault in the reference put in "
             "the program's place (train cells: half_batch)")
    parser.add_argument(
        "--root", default=REPO,
        help="the directory that holds BENCHMARK.json (default: the repo)")
    args = parser.parse_args(argv)
    try:
        result = run_cell(args.root, args.workload, args.seed, args.seconds,
                          args.trace, control=args.control, fault=args.fault)
    except harness_util.NoChip as err:
        print(f"no result: {err}", file=sys.stderr, flush=True)
        return 2
    for name, pair in result["compared"].items():
        print(f"compared {name}: value={pair['value']} limit={pair['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
