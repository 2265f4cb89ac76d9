"""The worker's half of the trace: a launch-mode electron over the local
transport brings its five ``worker.*`` spans and its compile counters home
in the result file's trailer, the dispatcher re-emits them under
``executor.run`` with the worker's ids kept, ``wall_overhead`` is computed
from ``worker.execute``, and a profiler capture's host plane holds the
program's spans (``worker.*``, ``serve.loop.*``, ``serve.engine.*``)."""

from __future__ import annotations

import asyncio
import glob
import json
import os
import pickle
import sys
import tarfile

import pytest

from covalent_tpu_plugin import TPUExecutor
from covalent_tpu_plugin.obs import events as obs_events
from covalent_tpu_plugin.obs import jitstats
from covalent_tpu_plugin.obs.metrics import REGISTRY, Registry
from covalent_tpu_plugin.utils.serialize import load_result_and_trailer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER_SPANS = ["worker.boot", "worker.load", "worker.execute",
                "worker.to_host", "worker.store"]


def _executor(tmp_path, **options) -> TPUExecutor:
    """Launch mode over the local transport.  No ``JAX_PLATFORMS`` in
    ``task_env`` (the harness would import jax to pin it): the worker
    inherits this process's ``JAX_PLATFORMS=cpu``."""
    options.setdefault("task_env", {}).setdefault("PYTHONPATH", REPO)
    options.setdefault("use_agent", False)
    return TPUExecutor(
        transport="local", cache_dir=str(tmp_path / "cache"),
        remote_cache=str(tmp_path / "remote"), python_path=sys.executable,
        poll_freq=0.2, **options)


@pytest.fixture()
def events(tmp_path):
    path = tmp_path / "events.jsonl"
    obs_events.configure(str(path))
    yield lambda: [json.loads(line)
                   for line in path.read_text().splitlines()]
    obs_events.reset()


def _spans(events, **match) -> list[dict]:
    return [e for e in events() if e["type"] == "span"
            and all(e.get(k) == v for k, v in match.items())]


def _counted(name: str) -> dict:
    metric = REGISTRY.get(name)
    return {} if metric is None else {
        labels[0]: value for labels, value in metric.values().items()}


def plain(n):
    import sys as worker_sys

    return n + 1, "jax" in worker_sys.modules


def jitted(n):
    import jax
    import jax.numpy as jnp

    import covalent_tpu_plugin.models  # noqa: F401 - the program's import

    x = jnp.arange(n, dtype=jnp.float32)
    return float(jax.jit(lambda a: a @ a)(x))


def boom():
    raise ValueError("from the electron")


def test_launch_electron_brings_home_the_five_worker_spans(
        tmp_path, run_async, events):
    ex = _executor(tmp_path)

    async def go():
        try:
            return await ex.run(plain, [1], {},
                                {"dispatch_id": "wt", "node_id": 0})
        finally:
            await ex.close()

    result, had_jax = run_async(go())
    assert result == 2
    # A plain-Python electron's worker never imports jax for the tracing.
    assert had_jax is False
    (root,) = _spans(events, name="executor.run")
    mine = {e["name"]: e for e in _spans(events, trace_id=root["trace_id"])
            if e["name"].startswith("worker.")}
    assert sorted(mine) == sorted(WORKER_SPANS)
    for span in mine.values():
        assert span["parent_id"] == root["span_id"]
        assert span["status"] == "OK"
        assert span["span_id"] != root["span_id"]
    # On the worker's one clock, in order, inside the root's wall time.
    order = [mine[n] for n in WORKER_SPANS]
    assert all(a["start_ts"] <= b["start_ts"] + 1e-3
               for a, b in zip(order, order[1:]))
    assert sum(s["duration_s"] for s in order) <= root["duration_s"]
    # wall_overhead is the root less the user's function, each on one clock.
    assert ex.last_timings["wall_overhead"] == pytest.approx(
        root["duration_s"] - mine["worker.execute"]["duration_s"], abs=2e-3)
    assert ex.last_timings["wall_overhead"] > (
        ex.last_timings["total"] - ex.last_timings["execute"])


def test_rpc_invocation_sends_the_same_five_spans_over_the_side_band(
        tmp_path, run_async, events):
    """The resident runtime: one ``worker.trace`` record ahead of the
    result, re-emitted by the same road as a launch result's trailer."""
    ex = _executor(tmp_path, use_agent="pool", dispatch_mode="rpc",
                   heartbeat_interval=0.0, prewarm=False)

    def square(x):  # nested: pickled by value, as a user's electron is
        return x * x

    async def go():
        try:
            first = await ex.run(square, [7], {},
                                 {"dispatch_id": "wt", "node_id": 5})
            return first, ex.last_dispatch_mode
        finally:
            await ex.close()

    assert run_async(go()) == (49, "rpc")
    (root,) = _spans(events, name="executor.run")
    mine = {e["name"]: e for e in _spans(events, trace_id=root["trace_id"])
            if e["name"].startswith("worker.")}
    assert sorted(mine) == sorted(WORKER_SPANS)
    assert all(s["parent_id"] == root["span_id"] for s in mine.values())
    assert ex.last_timings["wall_overhead"] == pytest.approx(
        root["duration_s"] - mine["worker.execute"]["duration_s"], abs=2e-3)


def test_worker_execute_is_error_when_the_function_raises(
        tmp_path, run_async, events):
    ex = _executor(tmp_path)

    async def go():
        try:
            await ex.run(boom, [], {}, {"dispatch_id": "wt", "node_id": 1})
        finally:
            await ex.close()

    with pytest.raises(ValueError, match="from the electron"):
        run_async(go())
    (root,) = _spans(events, name="executor.run")
    mine = {e["name"]: e for e in _spans(events, trace_id=root["trace_id"])
            if e["name"].startswith("worker.")}
    assert mine["worker.execute"]["status"] == "ERROR"
    assert mine["worker.store"]["status"] == "OK"
    assert "worker.to_host" not in mine  # nothing to bring to the host
    assert ex.last_timings["wall_overhead"] == pytest.approx(
        root["duration_s"] - mine["worker.execute"]["duration_s"], abs=2e-3)


def test_jitting_electron_brings_home_compile_seconds_and_cache_counts(
        tmp_path, run_async):
    before_s = _counted(jitstats.WORKER_JIT_SECONDS)
    before_n = _counted(jitstats.WORKER_COMPILE_CACHE)
    ex = _executor(tmp_path, task_env={
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax-cache"),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0",
    })

    async def go():
        try:
            first = await ex.run(jitted, [64], {},
                                 {"dispatch_id": "wt", "node_id": 2})
            cold = _counted(jitstats.WORKER_COMPILE_CACHE)
            second = await ex.run(jitted, [64], {},
                                  {"dispatch_id": "wt", "node_id": 3})
            return first, second, cold
        finally:
            await ex.close()

    first, second, cold = run_async(go())
    assert first == second == float(sum(i * i for i in range(64)))
    seconds = _counted(jitstats.WORKER_JIT_SECONDS)
    for phase in ("jaxpr_trace", "jaxpr_to_mlir_module", "backend_compile"):
        assert seconds[phase] > before_s.get(phase, 0.0), phase
    # The first worker found an empty cache, the second one the first's.
    assert cold.get("miss", 0) > before_n.get("miss", 0)
    warm = _counted(jitstats.WORKER_COMPILE_CACHE)
    assert warm.get("miss", 0) == cold.get("miss", 0)
    assert warm.get("hit", 0) > cold.get("hit", 0)


def test_result_trailer_leaves_the_pickled_pair_readable(tmp_path):
    """Every reader of ``(result, exception)`` stops at the pickle's end; a
    file without a trailer, or with a torn one, still gives the pair."""
    from covalent_tpu_plugin import harness

    path = tmp_path / "result.pkl"
    spans: list = []
    harness._emit_span(spans.append, "worker.execute",
                       {"trace_id": "t" * 32, "span_id": "p" * 16}, 1.0, 1.5)
    for tail, held in ((harness._trace_trailer(spans), True),
                       (b"", False), (b"\n{torn", False)):
        path.write_bytes(pickle.dumps((41, None)) + tail)
        with open(path, "rb") as f:
            assert pickle.load(f) == (41, None)
        pair, trailer = load_result_and_trailer(path)
        assert pair == (41, None)
        assert (trailer is not None) is held
    assert trailer is None and spans[0]["duration_s"] == 0.5
    assert spans[0]["parent_id"] == "p" * 16
    # No trace context, no span: a worker never mints an orphan trace.
    harness._emit_span(spans.append, "worker.execute", None, 1.0, 1.5)
    assert len(spans) == 1


def test_absorb_worker_adds_only_the_growth_of_running_totals():
    registry = Registry()
    seen: dict = {}
    totals = {"seconds": {"backend_compile": 2.0}, "cache": {"hit": 3}}
    jitstats.absorb_worker(totals, seen, source=41, registry=registry)
    totals = {"seconds": {"backend_compile": 2.5}, "cache": {"hit": 3,
                                                            "miss": 1}}
    jitstats.absorb_worker(totals, seen, source=41, registry=registry)
    jitstats.absorb_worker("not a record", seen, source=41, registry=registry)
    assert registry.get(jitstats.WORKER_JIT_SECONDS).values() == {
        ("backend_compile",): 2.5}
    assert registry.get(jitstats.WORKER_COMPILE_CACHE).values() == {
        ("hit",): 3.0, ("miss",): 1.0}
    # Another process (a handoff, a restarted runtime) counts from zero, as
    # does a one-shot worker (no ``seen``): all of it is added.
    jitstats.absorb_worker({"cache": {"miss": 1}}, seen, source=42,
                           registry=registry)
    jitstats.absorb_worker({"cache": {"miss": 2}}, registry=registry)
    assert registry.get(jitstats.WORKER_COMPILE_CACHE).values()[
        ("miss",)] == 4.0


def _host_events(artifact: str, out: str) -> set:
    """Names of the events on the ``/host:CPU`` plane of the one trace in
    the packed profile ``artifact``, unpacked under ``out``."""
    from jax.profiler import ProfileData

    with tarfile.open(artifact) as tar:
        tar.extractall(out, filter="data")
    (path,) = glob.glob(
        os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    data = ProfileData.from_file(path)
    names = set()
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                names.update(event.name for event in line.events)
    return names


def test_profile_of_a_launch_electron_holds_worker_execute(
        tmp_path, run_async):
    ex = _executor(tmp_path, profile_dir=str(tmp_path / "profile"))

    async def go():
        try:
            await ex.run(jitted, [64], {},
                         {"dispatch_id": "wt", "node_id": 4})
            return ex.last_timings["profile_trace"]
        finally:
            await ex.close()

    names = _host_events(run_async(go()), str(tmp_path / "trace"))
    assert {"worker.execute", "worker.to_host"} <= names


def test_capture_of_a_serving_session_holds_loop_and_engine_phases(
        tmp_path, run_async):
    """A stand-in serve session under load, captured through the worker's
    own ``profile_start`` (Python's call tracer off): the host plane says
    what the loop and the engine did."""
    from benchmarks.suite import harness_util, loadgen, program
    from covalent_tpu_plugin.serving import open_session
    from tests.benchsuite import standin

    cell = {"root": str(tmp_path), "config": standin.TINY_CONFIG,
            "traffic": standin.TINY_SERVE}
    work = str(tmp_path / "work")

    async def go():
        executor = harness_util.executor(cell, work, use_agent="pool")
        handle = None
        try:
            handle = await open_session(
                executor,
                program.engine_factory(
                    cell["config"], cell["traffic"], 7,
                    str(tmp_path / "report.json")),
                open_timeout_s=600.0, default_deadline_s=120.0, retries=0)

            captured = asyncio.Event()

            async def client(i: int) -> None:
                j = 0
                while not captured.is_set():
                    j += 1
                    request = await handle.request(
                        loadgen.prompt(7, 1000 * i + j, 9 + j % 8, 512),
                        params={"max_new_tokens": 12})
                    async for _ in request.stream():
                        pass

            clients = [asyncio.ensure_future(client(i)) for i in range(3)]
            await asyncio.sleep(0.3)
            info = await handle.capture_profile(0.8)
            captured.set()
            await asyncio.gather(*clients)
            return info.get("path") or info.get("local_path")
        finally:
            if handle is not None:
                await handle.close(timeout=60.0)
            await executor.close()
            await harness_util.await_workers_gone()

    names = _host_events(run_async(go()), str(tmp_path / "trace"))
    assert {"serve.loop.drain", "serve.loop.admit", "serve.loop.step",
            "serve.loop.emit"} <= names
    assert {"serve.engine.admit_wave", "serve.engine.run_steps",
            "serve.engine.harvest", "serve.engine.insert_prefix"} <= names
    # Python's call tracer is off: no interpreter frames in the capture.
    assert not [n for n in names if n.startswith("$")]
