"""Serving cells: ``open_session(use_agent="pool")`` -> ``ServeHandle.request``
-> ``stream()``, under a closed or an open loop read from the traffic file.

This process is the load generator and the dispatcher and stays off JAX
while the worker lives: the worker holds the chip.  Once the session is
closed and the worker is gone, the same process takes the chip for the
plain reference.
"""

from __future__ import annotations

import asyncio
import json
import os
import random
import tarfile
import time

from benchmarks.suite import harness_util, loadgen, program

#: A traced run's capture: this many seconds of live traffic (a 4 s capture
#: of a busy worker outlasted the program's 120 s wait for ``stop_trace``),
#: begun at this share of the window.
TRACE_S = 1.0
TRACE_AT = 0.6
#: An answer due in the window is waited for this long past its close.
DRAIN_S = 60.0


def _now() -> float:
    return time.monotonic()


class _Load:
    """One run's requests: sends them, stamps them on the client's clock."""

    def __init__(self, handle, cell: dict, seed: int) -> None:
        self.handle = handle
        self.vocab = cell["config"]["vocab_size"]
        self.seed = seed
        self.records: list[dict] = []
        self.live: dict[int, object] = {}
        self.stopping = False

    async def one(self, index: int, due: float, n_prompt: int,
                  n_out: int) -> dict:
        rec = {"index": index, "due": due, "n_prompt": n_prompt,
               "budget": n_out, "chunks": [], "tokens": [], "error": ""}
        self.records.append(rec)
        tokens = loadgen.prompt(self.seed, index, n_prompt, self.vocab)
        rec["prompt"] = tokens
        try:
            rec["sent"] = _now()
            request = await self.handle.request(
                tokens, params={"max_new_tokens": n_out}
            )
            self.live[index] = request
            rec["request"] = request
            async for chunk in request.stream():
                rec["chunks"].append((_now(), len(chunk)))
                rec["tokens"].extend(chunk)
            rec["error"] = request.error or ""
        except Exception as err:  # noqa: BLE001 - a refused request is data
            rec["error"] = rec["error"] or repr(err)
        finally:
            self.live.pop(index, None)
            rec["end"] = _now()
        return rec

    async def closed_client(self, stream) -> None:
        while not self.stopping:
            index, n_prompt, n_out = next(stream)
            await self.one(index, _now(), n_prompt, n_out)

    async def open_arrivals(self, schedule, t0: float) -> list:
        tasks = []
        for index, due, n_prompt, n_out in schedule:
            delay = t0 + due - _now()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(asyncio.ensure_future(
                self.one(index, t0 + due, n_prompt, n_out)
            ))
        return tasks


def _finish(rec: dict) -> None:
    """Per-request readings from the chunk stamps."""
    chunks = rec["chunks"]
    request = rec.pop("request", None)
    rec["ok"] = bool(
        not rec["error"] and len(rec["tokens"]) == rec["budget"] and chunks
    )
    if not chunks:
        return
    first, last = chunks[0][0], chunks[-1][0]
    rec["first"], rec["last"] = first, last
    rec["ttft_s"] = first - rec["due"]
    rec["gen_lag_ms"] = (rec["sent"] - rec["due"]) * 1e3
    n = len(rec["tokens"])
    if n > 1 and len(chunks) > 1:
        rec["tpot_ms"] = (last - first) / (n - 1) * 1e3
        rec["stall_ms"] = max(
            b[0] - a[0] for a, b in zip(chunks, chunks[1:])
        ) * 1e3
    if request is not None and request.t_sent is not None:
        rec["dispatch_ms"] = (request.t_sent - request.t_submit) * 1e3


async def _capture(handle, seconds: float, delay: float, out_dir: str):
    """The worker's profiler trace of ``seconds`` of live traffic, unpacked
    under ``out_dir``; only the worker can trace the chip it holds.
    Starting and stopping the profiler stalls the engine for some tenths
    of a second, so the host-clock readings of a traced run leave out the
    requests it touched (``t_begin`` on)."""
    await asyncio.sleep(max(delay, 0.0))
    t_begin = _now()
    info = await handle.capture_profile(seconds)
    path = info.get("path") or info.get("local_path")
    os.makedirs(out_dir, exist_ok=True)
    with tarfile.open(path) as tar:
        tar.extractall(out_dir, filter="data")
    return {"dir": out_dir, "asked_s": seconds, "t_begin": t_begin}


async def _drive(cell: dict, args, workdir: str, t_start: float,
                 require_tpu: bool, hooks: dict) -> dict:
    from covalent_tpu_plugin.serving import open_session

    traffic, config = cell["traffic"], cell["config"]
    report_path = os.path.join(workdir, "worker_report.json")
    executor = harness_util.executor(cell, workdir, use_agent="pool")
    handle = None
    trace = None
    try:
        try:
            handle = await open_session(
                executor,
                program.engine_factory(
                    config, traffic, args.seed, report_path,
                    control=bool(args.control),
                    chips=cell["chips"] if require_tpu else None,
                    engine_class=hooks.get("engine_class"),
                ),
                open_timeout_s=1100.0,
                default_deadline_s=600.0,
                queue_max=max(64, 4 * int(traffic["engine"]["max_batch"])),
                retries=0,
            )
        except Exception:
            if os.path.exists(report_path):
                with open(report_path, encoding="utf-8") as f:
                    harness_util.check_report(json.load(f))
            raise
        load = _Load(handle, cell, args.seed)
        ramp = float(traffic["ramp_s"])
        if traffic["loop"] == "closed":
            stream = loadgen.closed_stream(traffic, args.seed)
            clients = [
                asyncio.ensure_future(load.closed_client(stream))
                for _ in range(int(traffic["clients"]))
            ]
            await asyncio.sleep(ramp)
            t0 = _now()
        else:
            t0 = _now() + ramp
            schedule = loadgen.open_schedule(traffic, args.seed, args.seconds)
            arrivals = asyncio.ensure_future(load.open_arrivals(schedule, t0))
            await asyncio.sleep(ramp)
        t0_wall = time.time()
        tracer = None
        if args.trace:
            tracer = asyncio.ensure_future(_capture(
                handle, TRACE_S, TRACE_AT * args.seconds,
                os.path.join(workdir, "trace"),
            ))
        await asyncio.sleep(max(t0 + args.seconds - _now(), 0.0))
        t1 = _now()
        load.stopping = True
        if traffic["loop"] == "closed":
            # What is still in flight is no part of any metric: free the
            # lanes instead of decoding to the end of each budget.
            for request in list(load.live.values()):
                handle.supervisor.abandon(request.rid)
            for client in clients:
                client.cancel()
            await asyncio.gather(*clients, return_exceptions=True)
        else:
            tasks = await arrivals
            if tasks:
                await asyncio.wait(tasks, timeout=DRAIN_S)
            for task in tasks:
                task.cancel()
        if tracer is not None:
            trace = await tracer
        closed = await handle.close(timeout=120.0)
        handle = None
    finally:
        if handle is not None:
            try:
                await handle.close(timeout=30.0)
            except Exception:  # noqa: BLE001 - teardown of a failed run
                pass
        await executor.close()
    await harness_util.await_workers_gone()
    with open(report_path, encoding="utf-8") as f:
        report = json.load(f)
    for rec in load.records:
        _finish(rec)
    return {
        "t0": t0, "t1": t1, "window_s": t1 - t0,
        "setup_s": t0_wall - t_start,
        "records": load.records, "report": report, "closed": closed,
        "trace": trace, "stats": report.get("stats", {}),
    }


def quantile(values: list, q: float) -> float | None:
    """Nearest-rank percentile (the q-th of 100), None of nothing."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(int(-(-q * len(ordered) // 100)), 1)
    return ordered[rank - 1]


def summarise(cell: dict, run: dict) -> dict:
    """The window's end-to-end numbers and the sets the readers read."""
    t0, t1 = run["t0"], run["t1"]
    loop = cell["traffic"]["loop"]
    records = run["records"]
    if loop == "closed":
        # Judged: every request that finished inside the window.
        judged = [r for r in records
                  if r.get("last") is not None and r["ok"]
                  and t0 <= r["last"] <= t1]
        attempted = [r for r in records
                     if r.get("end") is not None and t0 <= r["end"] <= t1
                     and (r["ok"] or r["error"])]
    else:
        # Judged: every request that was due inside the window.
        attempted = [r for r in records if t0 <= r["due"] <= t1]
        judged = [r for r in attempted if r["ok"]]
    failed = [r for r in attempted if not r["ok"]]
    out_tokens = sum(
        n for r in records for (t, n) in r["chunks"] if t0 <= t <= t1
    )
    e2e = {
        "out_tok_s": out_tokens / run["window_s"],
        "tpot_p90_ms": quantile(
            [r["tpot_ms"] for r in judged if "tpot_ms" in r], 90),
        "tpot_p50_ms": quantile(
            [r["tpot_ms"] for r in judged if "tpot_ms" in r], 50),
        # A request that failed never gave its first token: it stands at
        # the far end of the distribution.
        "ttft_p90_s": quantile(
            [r["ttft_s"] for r in judged] + [float("inf")] * len(failed), 90),
        "ttft_p50_s": quantile(
            [r["ttft_s"] for r in judged] + [float("inf")] * len(failed), 50),
        "setup_s": run["setup_s"],
    }
    prompt_tokens = sum(r["n_prompt"] for r in records if r["chunks"])
    served_tokens = sum(len(r["tokens"]) for r in records)
    return {
        "end_to_end": e2e, "judged": judged, "attempted": len(attempted),
        "failed": len(failed), "out_tokens_window": out_tokens,
        "prompt_tokens_run": prompt_tokens, "served_tokens_run": served_tokens,
    }


def check(cell: dict, run: dict, summary: dict, args) -> dict:
    """The numbers ``correct`` compares.  A sample of the requests the
    window finished, drawn from the seed with the longest in it, goes
    through the plain reference once; every finished request must have
    delivered exactly its budget of ids inside the vocabulary."""
    from benchmarks.suite import reference

    judged = summary["judged"]
    vocab = cell["config"]["vocab_size"]
    bad = sum(
        1 for r in judged
        if len(r["tokens"]) != r["budget"]
        or not all(0 <= t < vocab for t in r["tokens"])
    )
    numbers = {"bad_streams": bad + summary["failed"],
               "checked_tokens": 0, "token_gap": None}
    if judged:
        rng = random.Random(int(args.seed))
        longest = max(judged, key=lambda r: r["n_prompt"] + r["budget"])
        rest = [r for r in judged if r is not longest]
        rng.shuffle(rest)
        sample = [longest] + rest[: int(cell["traffic"]["check_requests"]) - 1]
        gaps = reference.serve_gaps(
            cell["config"], args.seed,
            [(r["prompt"], r["tokens"]) for r in sample],
            int(cell["traffic"]["engine"]["max_seq"]),
            int(cell["traffic"]["output_tokens"]["max"]),
        )
        flat = [g for row in gaps for g in row]
        numbers["token_gap"] = max(flat)
        numbers["checked_tokens"] = len(flat)
        numbers["checked_requests"] = len(sample)
        # Other views of the same gaps; the cell's limits file says which
        # of them are compared, the rest go into the line's notes.
        numbers["token_gap_mean"] = sum(flat) / len(flat)
        numbers["token_gap_p99"] = quantile(flat, 99)
        numbers["tokens_off_best"] = sum(1 for g in flat if g > 0)
    numbers["short_sample"] = int(
        numbers["checked_tokens"] < int(cell["limits"].get("_min_tokens", 1))
    )
    return numbers


def run(cell: dict, args, t_start: float, require_tpu: bool = True,
        hooks: dict | None = None) -> dict:
    """One serving run: drive, summarise, then (the worker gone) check.
    ``hooks["engine_class"]`` lets a test break the timed path."""
    workdir = harness_util.workdir()
    try:
        outcome = asyncio.run(_drive(cell, args, workdir, t_start,
                                     require_tpu, hooks or {}))
        summary = summarise(cell, outcome)
        trace = None
        if outcome["trace"] is not None:
            from benchmarks.suite import reduce

            trace = reduce.reduce_dir(outcome["trace"]["dir"], trim=True)
        t_check = time.time()
        numbers = check(cell, outcome, summary, args)
        numbers["check_s"] = time.time() - t_check
    finally:
        harness_util.cleanup(workdir)
    report = outcome["report"]
    window = (outcome["setup_s"] + t_start,
              outcome["setup_s"] + t_start + outcome["window_s"])
    judged = summary["judged"]
    if outcome["trace"] is not None:
        untouched = outcome["trace"]["t_begin"]
        judged = [r for r in judged if r["last"] < untouched]
    context = {
        "cell": cell, "records": outcome["records"], "judged": judged,
        "stats": outcome["stats"], "trace": trace,
        "window_s": outcome["window_s"], "end_to_end": summary["end_to_end"],
        "compiles_in_window": sum(
            1 for t, _ in report.get("compiles", [])
            if window[0] <= t <= window[1]),
        "prompt_tokens_run": summary["prompt_tokens_run"],
        "served_tokens_run": summary["served_tokens_run"],
        "out_tokens_window": summary["out_tokens_window"],
        "prompt_tokens_window": sum(
            r["n_prompt"] for r in outcome["records"]
            if r.get("first") is not None
            and outcome["t0"] <= r["first"] <= outcome["t1"]),
        "parameters": report.get("parameters"),
        "setup_parts": {
            "open_s": report["t_enter"] - t_start,
            **{k: report.get(k) for k in ("import_s", "weights_s", "warm_s")},
            "ramp_s": float(cell["traffic"]["ramp_s"]),
        },
    }
    return {
        "end_to_end": summary["end_to_end"],
        "attempted": summary["attempted"], "failed": summary["failed"],
        "device": {k: report[k] for k in
                   ("platform", "kind", "count", "memory_peak_bytes")},
        "numbers": numbers, "context": context,
    }
