"""Persistent serving tier: session protocol, streaming, resilience.

Protocol-level coverage of the ``serve_open``/``serve_request``/
``serve_close`` verbs on the Python pool server (the native C++ agent's
analog lives in ``test_agent.py``), plus the dispatcher-side
:class:`ServeHandle` lifecycle: concurrent callers multiplexed onto one
session, incremental token streams with real TTFT, bounded-queue
backpressure classified PERMANENT, per-request deadlines, the kill-mid-
stream reconnect with exactly-once token delivery, fleet capacity
pinning, and the oversized streamed-result staging policy.

The engines here are closure-local stubs implementing the harness's
duck-typed serving surface (``slots``/``admit``/``step``/``cancel``) —
the real LM engine (``models/serve.ContinuousEngine``) is covered
against the decode oracle in ``test_continuous.py``.
"""

import asyncio
import os
import sys
import time

import cloudpickle
import pytest

from covalent_tpu_plugin import TPUExecutor
from covalent_tpu_plugin.agent import AgentError, start_pool_server
from covalent_tpu_plugin.cache import bytes_digest
from covalent_tpu_plugin.fleet.pools import Pool, PoolSpec
from covalent_tpu_plugin.obs import events as obs_events
from covalent_tpu_plugin.obs.metrics import REGISTRY
from covalent_tpu_plugin.resilience import FaultClass, classify_error
from covalent_tpu_plugin.serving import (
    ServeError,
    ServeRequestRejected,
    open_session,
)
from covalent_tpu_plugin.transport import LocalTransport

from .helpers import pin_cpu_task_env


def make_serve_executor(tmp_path, **kwargs):
    kwargs.setdefault("transport", "local")
    kwargs.setdefault("cache_dir", str(tmp_path / "cache"))
    kwargs.setdefault("remote_cache", str(tmp_path / "remote"))
    kwargs.setdefault("python_path", sys.executable)
    kwargs.setdefault("poll_freq", 0.2)
    kwargs.setdefault("use_agent", "pool")
    kwargs.setdefault("heartbeat_interval", 0.0)
    kwargs.setdefault("prewarm", False)
    return TPUExecutor(**pin_cpu_task_env(kwargs))


def make_factory(step_delay=0.0, slots=2, chunk=2, default_cap=6, gate=None):
    """A stub serving engine, cloudpickled BY VALUE (closure-local class:
    the resident worker cannot import the tests package).  Deterministic
    streams — prompt ``[..., base]`` yields ``base+1, base+2, ...`` — so
    replay splices are byte-checkable.  ``gate`` names a file holding the
    number of steps the engine may take in all (:func:`allow_steps`): a
    test that must find a stream mid-flight says how far it runs, where a
    ``step_delay`` would race the test under load."""

    may_step = step_gate(gate) if gate else None

    def factory():
        import time as time_mod

        class Engine:
            def __init__(self):
                self.slots = slots
                self.lanes = {}

            def admit(self, rid, prompt, params):
                cap = int((params or {}).get("max_new_tokens", default_cap))
                base = int(prompt[-1])
                self.lanes[rid] = [base + i + 1 for i in range(cap)]

            def step(self):
                if step_delay:
                    time_mod.sleep(step_delay)
                if may_step and self.lanes and not may_step():
                    return []
                events = []
                for rid in list(self.lanes):
                    taken = self.lanes[rid][:chunk]
                    self.lanes[rid] = self.lanes[rid][chunk:]
                    done = not self.lanes[rid]
                    if done:
                        del self.lanes[rid]
                    events.append(
                        {"rid": rid, "tokens": taken, "done": done}
                    )
                return events

            def cancel(self, rid):
                self.lanes.pop(rid, None)

        return Engine()

    return factory


def step_gate(gate):
    """``may_step()`` for a stub engine: true, and counted, while the
    number in the file ``gate`` exceeds the steps taken so far.  A closure,
    so that it is pickled by value with the stub that calls it."""
    taken = [0]

    def may_step():
        import time as time_mod

        try:
            with open(gate) as f:
                allowed = int(f.read())
        except (OSError, ValueError):
            allowed = 0
        if taken[0] >= allowed:
            time_mod.sleep(0.005)
            return False
        taken[0] += 1
        return True

    return may_step


def allow_steps(gate, n: int) -> None:
    """Let the engines gated on ``gate`` take ``n`` steps in all (the
    file is replaced whole: a worker reads it between steps)."""
    tmp = f"{gate}.tmp"
    with open(tmp, "w") as f:
        f.write(str(n))
    os.replace(tmp, gate)


#: More steps than any stub stream needs: the gate stands open.
GATE_OPEN = 1 << 30


def make_unsupported_factory():
    """A factory refusing its model shape with the duck-typed permanence
    tag — the shape ``models/serve.RollingCacheUnsupported`` carries."""

    def factory():
        class ModelUnsupported(ValueError):
            fault_label = "serve_model_unsupported"
            fault_transient = False

        raise ModelUnsupported("rolling_cache models are not servable")

    return factory


def stage_factory(tmp_path, factory):
    payload = cloudpickle.dumps(factory)
    digest = bytes_digest(payload)
    path = tmp_path / f"{digest}.pkl"
    path.write_bytes(payload)
    return digest, str(path)


def gauge_value(name: str, **labels) -> float:
    metric = REGISTRY.get(name)
    if metric is None:
        return 0.0
    for series_labels, gauge in metric._series():
        if all(series_labels.get(k) == v for k, v in labels.items()):
            return gauge.value
    return 0.0


async def drain_until(records, predicate, timeout=15.0):
    """Await the first side-band record satisfying ``predicate``."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for record in records:
            if predicate(record):
                return record
        await asyncio.sleep(0.02)
    raise AssertionError(f"no matching record in {records}")


# ---------------------------------------------------------------------------
# Protocol level: the pool server's session verbs over a real channel
# ---------------------------------------------------------------------------


def test_pool_serve_open_request_close_roundtrip(tmp_path, run_async):
    """The whole session protocol against the real forkserver: open by
    digest, stream one request's chunks (cumulative ``idx`` contract),
    drain-close with the served count."""

    async def flow():
        client = await start_pool_server(
            LocalTransport(), str(tmp_path / "remote"), sys.executable
        )
        records: list = []
        try:
            digest, path = stage_factory(tmp_path, make_factory())
            client.watch_serve("s1", lambda sid, data: records.append(data))
            opened = await client.serve_open(
                "s1", digest, path,
                options={"stats_interval_s": 0.1}, timeout=30.0,
            )
            await client.serve_request(
                "s1", "r1", [5], params={"max_new_tokens": 4}
            )
            final = await drain_until(
                records,
                lambda r: r.get("type") == "serve.token" and r.get("done"),
            )
            stats = await drain_until(
                records, lambda r: r.get("type") == "serve.stats"
            )
            closed = await client.serve_close("s1", timeout=15.0)
        finally:
            await client.close()
        return opened, records, final, stats, closed

    opened, records, final, stats, closed = run_async(flow())
    assert opened["slots"] == 2 and opened["pid"] > 0
    chunks = [r for r in records if r.get("type") == "serve.token"]
    streamed: list = []
    for chunk in chunks:
        assert chunk["rid"] == "r1"
        assert chunk["idx"] == len(streamed)  # cumulative-before-chunk
        streamed.extend(chunk["tokens"])
    assert streamed == [6, 7, 8, 9]
    assert final["done"] is True
    assert stats["slots"] == 2 and stats["served"] in (0, 1)
    assert closed["served"] == 1


def test_pool_serve_unknown_session_and_duplicate(tmp_path, run_async):
    """Requests against a sid that was never opened fail fast as streamed
    rejects; closing one errors; double-open is refused PERMANENT."""

    async def flow():
        client = await start_pool_server(
            LocalTransport(), str(tmp_path / "remote"), sys.executable
        )
        records: list = []
        try:
            client.watch_serve(
                "ghost", lambda sid, data: records.append(data)
            )
            await client.serve_request("ghost", "r0", [1])
            reject = await drain_until(
                records, lambda r: r.get("type") == "serve.reject"
            )
            with pytest.raises(AgentError, match="unknown_session"):
                await client.serve_close("ghost", timeout=10.0)
            digest, path = stage_factory(tmp_path, make_factory())
            await client.serve_open("dup", digest, path, timeout=30.0)
            with pytest.raises(AgentError, match="duplicate") as dup:
                await client.serve_open("dup", digest, path, timeout=30.0)
            await client.serve_close("dup", timeout=15.0)
        finally:
            await client.close()
        return reject, dup.value

    reject, dup_error = run_async(flow())
    assert reject["code"] == "unknown_session"
    assert reject["rid"] == "r0"
    fault, _ = classify_error(dup_error)
    assert fault is FaultClass.PERMANENT


def test_pool_serve_session_survives_unrelated_forget(tmp_path, run_async):
    """``forget()`` of an unrelated electron's state must not disturb an
    open session's sink, seq-dedup, or streams."""

    async def flow():
        client = await start_pool_server(
            LocalTransport(), str(tmp_path / "remote"), sys.executable
        )
        records: list = []
        try:
            digest, path = stage_factory(tmp_path, make_factory())
            client.watch_serve("s1", lambda sid, data: records.append(data))
            await client.serve_open("s1", digest, path, timeout=30.0)
            await client.serve_request(
                "s1", "r1", [10], params={"max_new_tokens": 2}
            )
            await drain_until(
                records,
                lambda r: r.get("type") == "serve.token" and r.get("done"),
            )
            # An unrelated electron leaving the executor's books.
            client.forget("some-finished-electron")
            client.unwatch_serve("some-other-session")
            await client.serve_request(
                "s1", "r2", [20], params={"max_new_tokens": 2}
            )
            await drain_until(
                records,
                lambda r: r.get("type") == "serve.token"
                and r.get("rid") == "r2" and r.get("done"),
            )
            closed = await client.serve_close("s1", timeout=15.0)
        finally:
            await client.close()
        return records, closed

    records, closed = run_async(flow())
    tokens = {
        r["rid"]: r for r in records
        if r.get("type") == "serve.token" and r.get("done")
    }
    assert set(tokens) == {"r1", "r2"}
    assert closed["served"] == 2


def test_pool_serve_open_digest_mismatch_permanent(tmp_path, run_async):
    """A factory artifact that fails its sha256 check is refused before
    unpickling and classifies PERMANENT — no gang retries."""

    async def flow():
        client = await start_pool_server(
            LocalTransport(), str(tmp_path / "remote"), sys.executable
        )
        try:
            _digest, path = stage_factory(tmp_path, make_factory())
            wrong = bytes_digest(b"entirely different bytes")
            with pytest.raises(AgentError, match="digest_mismatch") as info:
                await client.serve_open("bad", wrong, path, timeout=30.0)
        finally:
            await client.close()
        return info.value

    error = run_async(flow())
    fault, label = classify_error(error)
    assert fault is FaultClass.PERMANENT
    assert label == "serve_digest_mismatch"


def test_pool_serve_factory_fault_label_is_permanent(tmp_path, run_async):
    """A factory refusing its model shape (RollingCacheUnsupported's
    duck tag) surfaces through the RPC as a PERMANENT fault with the
    factory's own label — a misconfigured session is refused once."""

    async def flow():
        client = await start_pool_server(
            LocalTransport(), str(tmp_path / "remote"), sys.executable
        )
        try:
            digest, path = stage_factory(
                tmp_path, make_unsupported_factory()
            )
            with pytest.raises(AgentError, match="factory_failed") as info:
                await client.serve_open("unsup", digest, path, timeout=30.0)
        finally:
            await client.close()
        return info.value

    error = run_async(flow())
    fault, label = classify_error(error)
    assert fault is FaultClass.PERMANENT
    assert label == "serve_model_unsupported"


# ---------------------------------------------------------------------------
# Handle level: ServeHandle through the executor
# ---------------------------------------------------------------------------


def test_serve_handle_streams_concurrent_requests(tmp_path, run_async):
    """Five concurrent callers through one session: every stream lands
    deterministically, TTFT <= full latency, the live session shows on
    the executor's status view, close reports the served count."""

    async def flow():
        ex = make_serve_executor(tmp_path)
        try:
            handle = await open_session(
                ex, make_factory(), stats_interval_s=0.1
            )
            requests = [
                await handle.request([10 * i], params={"max_new_tokens": 4})
                for i in range(5)
            ]
            results = [await r.result(timeout=30) for r in requests]
            ttfts = [r.ttft_s for r in requests]
            latencies = [r.latency_s for r in requests]
            view = dict(ex.serve_sessions())
            state = handle.state
            closed = await handle.close()
            post_view = dict(ex.serve_sessions())
        finally:
            await ex.close()
        return (
            handle.sid, requests, results, ttfts, latencies, view, state,
            closed, post_view,
        )

    sid, requests, results, ttfts, latencies, view, state, closed, post = (
        run_async(flow())
    )
    for i, tokens in enumerate(results):
        assert tokens == [10 * i + j + 1 for j in range(4)]
    assert all(t is not None for t in ttfts)
    assert all(t <= lat for t, lat in zip(ttfts, latencies))
    assert state == "open"
    assert view[sid]["state"] == "open" and view[sid]["slots"] == 2
    assert closed["served"] == 5
    assert sid not in post


def test_serve_handle_stream_iterator_yields_chunks(tmp_path, run_async):
    """``stream()`` delivers the chunks incrementally, in order."""

    async def flow():
        ex = make_serve_executor(tmp_path)
        try:
            handle = await open_session(ex, make_factory(chunk=2))
            request = await handle.request(
                [100], params={"max_new_tokens": 6}
            )
            chunks = [chunk async for chunk in request.stream()]
            await handle.close()
        finally:
            await ex.close()
        return chunks

    chunks = run_async(flow())
    assert [t for chunk in chunks for t in chunk] == [
        101, 102, 103, 104, 105, 106
    ]
    assert all(len(chunk) <= 2 for chunk in chunks)
    assert len(chunks) >= 3


def test_serve_kill_mid_stream_reconnects_exactly_once(tmp_path, run_async):
    """The chaos contract: SIGKILL the resident server mid-stream; the
    supervisor classifies transient, re-opens on a fresh gang, replays
    in-flight requests, and the idx splice hands every caller each token
    EXACTLY once — no duplicates, none lost.  The handle stays usable."""

    async def flow():
        ex = make_serve_executor(
            tmp_path, retry_base_delay=0.05, retry_max_delay=0.2
        )
        try:
            handle = await open_session(
                ex,
                make_factory(step_delay=0.1, default_cap=12),
                retries=2,
            )
            requests = [await handle.request([100 * i]) for i in range(3)]
            for _ in range(200):
                if all(len(r.tokens) >= 4 for r in requests):
                    break
                await asyncio.sleep(0.05)
            assert all(len(r.tokens) >= 4 for r in requests), (
                [r.tokens for r in requests])
            ex._agents["localhost"]._process._proc.kill()
            results = [await r.result(timeout=60) for r in requests]
            reconnects = handle.reconnects
            state = handle.state
            late = await handle.request([7], params={"max_new_tokens": 3})
            late_result = await late.result(timeout=30)
            await handle.close()
        finally:
            await ex.close()
        return results, reconnects, state, late_result

    results, reconnects, state, late_result = run_async(flow())
    for i, tokens in enumerate(results):
        assert tokens == [100 * i + j + 1 for j in range(12)], tokens
    assert reconnects == 1
    assert state == "open"
    assert late_result == [8, 9, 10]


def test_serve_admission_shed_is_permanent(tmp_path, run_async):
    """A bounded queue refusing work sheds it immediately; the rejection
    classifies PERMANENT under ``serve_admission_shed`` (a gang retry
    would amplify exactly the overload that shed the work)."""

    async def flow():
        ex = make_serve_executor(tmp_path)
        try:
            handle = await open_session(
                ex,
                make_factory(step_delay=0.2, slots=1, default_cap=6),
                queue_max=1,
            )
            requests = [await handle.request([10 * i]) for i in range(6)]
            outcomes = await asyncio.gather(
                *(r.result(timeout=60) for r in requests),
                return_exceptions=True,
            )
            await handle.close()
        finally:
            await ex.close()
        return outcomes

    outcomes = run_async(flow())
    sheds = [o for o in outcomes if isinstance(o, ServeRequestRejected)]
    completions = [o for o in outcomes if isinstance(o, list)]
    assert sheds, outcomes
    assert completions, outcomes
    for shed in sheds:
        assert shed.code == "serve_admission_shed"
        fault, label = classify_error(shed)
        assert fault is FaultClass.PERMANENT
        assert label == "serve_admission_shed"


def test_serve_request_deadline_reclaims_lane(tmp_path, run_async):
    """A request past its deadline mid-generation completes with the
    partial stream and the ``deadline_exceeded`` marker — the lane is
    reclaimed, not wedged."""

    async def flow():
        ex = make_serve_executor(tmp_path)
        try:
            handle = await open_session(
                ex,
                make_factory(step_delay=0.15, slots=1, chunk=1,
                             default_cap=40),
            )
            request = await handle.request([0], deadline_s=0.5)
            tokens = await request.result(timeout=30)
            error = request.error
            # The freed lane must admit the next request.
            follow = await handle.request(
                [50], params={"max_new_tokens": 2}, deadline_s=30.0
            )
            follow_tokens = await follow.result(timeout=30)
            await handle.close()
        finally:
            await ex.close()
        return tokens, error, follow_tokens

    tokens, error, follow_tokens = run_async(flow())
    assert error == "deadline_exceeded"
    assert 0 < len(tokens) < 40
    assert tokens == [i + 1 for i in range(len(tokens))]
    assert follow_tokens == [51, 52]


def test_serve_session_pins_fleet_capacity(tmp_path, run_async):
    """Opened through a fleet pool, a session occupies one capacity slot
    for its lifetime (placement bin-packs around it) and its live view
    rides ``pool.status()``; close releases the slot."""

    async def flow():
        ex = make_serve_executor(tmp_path)
        pool = Pool(
            PoolSpec(name="srv", capacity=2, transport="local"),
            executor=ex,
        )
        try:
            handle = await pool.open_session(make_factory())
            in_use_open = pool.in_use
            status = pool.status()
            await handle.close()
            in_use_closed = pool.in_use
        finally:
            await ex.close()
        return handle.sid, in_use_open, status, in_use_closed

    sid, in_use_open, status, in_use_closed = run_async(flow())
    assert in_use_open == 1
    assert in_use_closed == 0
    assert status["in_use"] == 1
    assert status["serve_sessions"][sid]["state"] == "open"


def test_serve_failed_open_does_not_leak_capacity(tmp_path, run_async):
    """A refused open (permanent factory fault) must release nothing it
    never pinned: pool slots and the live-session gauge stay level."""

    async def flow():
        ex = make_serve_executor(tmp_path)
        pool = Pool(
            PoolSpec(name="srv", capacity=2, transport="local"),
            executor=ex,
        )
        sessions0 = gauge_value("covalent_tpu_serve_sessions")
        try:
            with pytest.raises(AgentError):
                await pool.open_session(make_unsupported_factory())
            in_use = pool.in_use
            sessions1 = gauge_value("covalent_tpu_serve_sessions")
            views = dict(ex.serve_sessions())
        finally:
            await ex.close()
        return in_use, sessions0, sessions1, views

    in_use, sessions0, sessions1, views = run_async(flow())
    assert in_use == 0
    assert sessions1 == sessions0
    assert views == {}


# ---------------------------------------------------------------------------
# Satellite: the inline-vs-CAS size policy applies to streamed results
# ---------------------------------------------------------------------------


def test_oversized_rpc_result_stages_instead_of_inlining(
    tmp_path, run_async
):
    """A result pickle over ``rpc_inline_args_max`` takes the staged road
    (remote file + sha256 announce) instead of one multi-MB base64 write
    on the channel — and still arrives intact."""

    staged_events: list = []

    def listener(event: dict) -> None:
        if event.get("type") == "task.rpc_result_staged":
            staged_events.append(event)

    def big_result(n):
        return bytes(range(256)) * n

    async def flow():
        ex = make_serve_executor(
            tmp_path, dispatch_mode="rpc", rpc_inline_args_max=1024
        )
        try:
            big = await ex.run(
                big_result, [2048], {},
                {"dispatch_id": "stage", "node_id": 0},
            )
            small = await ex.run(
                big_result, [1], {},
                {"dispatch_id": "inline", "node_id": 1},
            )
            mode = ex.last_dispatch_mode
        finally:
            await ex.close()
        return big, small, mode

    obs_events.add_listener(listener)
    try:
        big, small, mode = run_async(flow())
    finally:
        obs_events.remove_listener(listener)
    assert mode == "rpc"
    assert big == bytes(range(256)) * 2048
    assert small == bytes(range(256))
    # Exactly the oversized result staged; the small one rode inline.
    assert len(staged_events) == 1
    assert staged_events[0]["bytes"] > 1024


# ---------------------------------------------------------------------------
# Satellite: heartbeat backhaul carries serving slot occupancy
# ---------------------------------------------------------------------------


def test_serve_occupancy_rides_heartbeats():
    """Worker side: live sessions fold into every beat's ``serve`` block;
    dispatcher side: a fresh beat moves the per-worker occupancy gauges."""
    from covalent_tpu_plugin import harness

    class FakeQueue:
        def qsize(self):
            return 3

    class FakeSession:
        slots = 4
        running = {"r1": {}, "r2": {}}
        queue = FakeQueue()

    harness._SERVE_SESSIONS["fake"] = FakeSession()
    try:
        occupancy = harness._serve_occupancy()
    finally:
        harness._SERVE_SESSIONS.pop("fake", None)
    assert occupancy == {
        "sessions": 1, "slots": 4, "busy": 2, "queued": 3,
    }
    assert harness._serve_occupancy() == {}  # no sessions -> no block

    ex = TPUExecutor.__new__(TPUExecutor)  # gauge path needs no init
    ex._record_heartbeat(
        "op-serve", "worker9",
        {"type": "worker.heartbeat", "seq": 1, "pid": 1, "ts": 1.0,
         "serve": {"sessions": 1, "slots": 4, "busy": 2, "queued": 3}},
    )
    assert gauge_value(
        "covalent_tpu_serve_worker_slots", worker="worker9", state="busy"
    ) == 2.0
    assert gauge_value(
        "covalent_tpu_serve_worker_slots", worker="worker9", state="queued"
    ) == 3.0


def test_serve_metrics_move_with_traffic(tmp_path, run_async):
    """The obs registry's serving series move with real traffic: request
    outcomes, streamed tokens, TTFT observations, session gauge."""

    def counter_value(name: str, **labels) -> float:
        metric = REGISTRY.get(name)
        if metric is None:
            return 0.0
        total = 0.0
        for series_labels, counter in metric._series():
            if all(series_labels.get(k) == v for k, v in labels.items()):
                total += counter.value
        return total

    async def flow():
        ex = make_serve_executor(tmp_path)
        ok0 = counter_value(
            "covalent_tpu_serve_requests_total", outcome="ok"
        )
        tokens0 = counter_value("covalent_tpu_serve_tokens_total")
        try:
            handle = await open_session(
                ex, make_factory(), stats_interval_s=0.1
            )
            live_during = gauge_value("covalent_tpu_serve_sessions")
            requests = [
                await handle.request([0], params={"max_new_tokens": 4})
                for _ in range(2)
            ]
            for request in requests:
                await request.result(timeout=30)
            await asyncio.sleep(0.3)  # let a stats record land
            queue_depth = gauge_value(
                "covalent_tpu_serve_queue_depth", session=handle.sid
            )
            await handle.close()
        finally:
            await ex.close()
        return (
            counter_value(
                "covalent_tpu_serve_requests_total", outcome="ok"
            ) - ok0,
            counter_value("covalent_tpu_serve_tokens_total") - tokens0,
            live_during,
            queue_depth,
        )

    ok_delta, tokens_delta, live_during, queue_depth = run_async(flow())
    assert ok_delta == 2
    assert tokens_delta == 8
    assert live_during >= 1
    assert queue_depth == 0


def test_serve_error_when_agent_disabled(tmp_path, run_async):
    """Serving needs the resident runtime: a no-agent executor refuses
    the open with a clear error instead of wedging."""

    async def flow():
        ex = make_serve_executor(tmp_path, use_agent=False)
        try:
            with pytest.raises((AgentError, ServeError)):
                await open_session(ex, make_factory())
        finally:
            await ex.close()

    run_async(flow())


def test_pool_serve_failed_open_sid_is_reopenable(tmp_path, run_async):
    """A session whose factory failed leaves no tombstone: re-opening the
    SAME sid on the same live pool server must succeed — the reconnect
    path retries sid.gN verbatim, and a stale dead entry refusing it as
    'duplicate' (PERMANENT) would abort the whole retry loop."""

    async def flow():
        client = await start_pool_server(
            LocalTransport(), str(tmp_path / "remote"), sys.executable
        )
        records: list = []
        try:
            bad_digest, bad_path = stage_factory(
                tmp_path, make_unsupported_factory()
            )
            with pytest.raises(AgentError, match="factory_failed"):
                await client.serve_open("s1", bad_digest, bad_path,
                                        timeout=30.0)
            digest, path = stage_factory(tmp_path, make_factory())
            client.watch_serve("s1", lambda sid, data: records.append(data))
            opened = await client.serve_open("s1", digest, path, timeout=30.0)
            await client.serve_request(
                "s1", "r1", [3], params={"max_new_tokens": 2}
            )
            await drain_until(
                records,
                lambda r: r.get("type") == "serve.token" and r.get("done"),
            )
            closed = await client.serve_close("s1", timeout=15.0)
        finally:
            await client.close()
        return opened, closed

    opened, closed = run_async(flow())
    assert opened["slots"] == 2
    assert closed["served"] == 1


def test_serve_warm_handoff_zero_dropped_tokens(tmp_path, run_async):
    """Planned churn: handoff() opens the replacement session BEFORE
    retiring the old one and splices in-flight streams on the idx replay —
    byte-equal results, exactly-once, no reconnect event, handle usable
    on the new generation."""

    async def flow():
        ex = make_serve_executor(tmp_path)
        try:
            handle = await open_session(
                ex, make_factory(step_delay=0.1, default_cap=12)
            )
            requests = [await handle.request([100 * i]) for i in range(3)]
            for _ in range(200):
                if all(len(r.tokens) >= 4 for r in requests):
                    break
                await asyncio.sleep(0.05)
            assert all(len(r.tokens) >= 4 for r in requests)
            moved = await handle.handoff(reason="test")
            results = [await r.result(timeout=60) for r in requests]
            stats = (
                moved, handle.handoffs, handle.generation,
                handle.reconnects, handle.state,
            )
            late = await handle.request([7], params={"max_new_tokens": 3})
            late_result = await late.result(timeout=30)
            await handle.close()
        finally:
            await ex.close()
        return results, stats, late_result

    results, stats, late_result = run_async(flow())
    moved, handoffs, generation, reconnects, state = stats
    assert moved is True
    for i, tokens in enumerate(results):
        assert tokens == [100 * i + j + 1 for j in range(12)], tokens
    assert handoffs == 1
    assert generation == 2  # the replacement generation took over
    assert reconnects == 0  # warm path, not the death path
    assert state == "open"
    assert late_result == [8, 9, 10]


def test_serve_preempt_notice_triggers_auto_handoff(tmp_path, run_async):
    """SIGTERM on the serving runtime (the spot preemption notice): the
    worker announces ``serve.preempt`` on the side-band and KEEPS serving;
    the supervisor warm-hands the session off inside the grace window —
    streams stay byte-equal and exactly-once."""
    import os as os_mod
    import signal

    async def flow():
        ex = make_serve_executor(tmp_path)
        try:
            handle = await open_session(
                ex, make_factory(step_delay=0.1, default_cap=12)
            )
            requests = [await handle.request([100 * i]) for i in range(3)]
            for _ in range(200):
                if all(len(r.tokens) >= 4 for r in requests):
                    break
                await asyncio.sleep(0.05)
            server_pid = ex._agents["localhost"]._process._proc.pid
            os_mod.kill(server_pid, signal.SIGTERM)  # the preemption notice
            for _ in range(200):
                if handle.handoffs:
                    break
                await asyncio.sleep(0.05)
            results = [await r.result(timeout=60) for r in requests]
            stats = (handle.handoffs, handle.state)
            await handle.close()
        finally:
            await ex.close()
        return results, stats

    results, (handoffs, state) = run_async(flow())
    for i, tokens in enumerate(results):
        assert tokens == [100 * i + j + 1 for j in range(12)], tokens
    assert handoffs == 1
    assert state == "open"


def test_serve_session_spec_greedy_bit_equal_to_fp(tmp_path, run_async):
    """Greedy spec-decode through a REAL open_session: a tiny LM served
    with a self-draft (full acceptance) streams token-for-token what the
    same model's fp session streams, and the supervisor's stats records
    carry the spec accept-rate feed the metrics plane exports."""
    import os

    import jax
    import jax.numpy as jnp

    from covalent_tpu_plugin.models import TransformerConfig, TransformerLM
    from covalent_tpu_plugin.models.serve import lm_engine_factory

    cfg = TransformerConfig(
        vocab_size=32, d_model=16, n_layers=1, n_heads=2, d_ff=32,
        max_seq=32, dtype=jnp.float32, attention="reference",
    )
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )["params"]
    prompts = [[3, 9, 1], [7, 2], [5, 5, 5, 5]]

    async def flow():
        cloudpickle.register_pickle_by_value(
            sys.modules["covalent_tpu_plugin.models.serve"]
        )
        repo_root = os.path.dirname(os.path.dirname(__file__))
        ex = make_serve_executor(
            tmp_path,
            task_env={
                "PYTHONPATH": repo_root + os.pathsep
                + os.environ.get("PYTHONPATH", ""),
            },
        )
        results, spec_stats = {}, None
        try:
            for tag, extra in (
                ("fp", {}),
                ("spec", dict(
                    draft_model=model, draft_params=params, draft_len=2,
                )),
            ):
                factory = lm_engine_factory(
                    model, params, max_batch=2, sync_steps=3,
                    max_new_tokens=6, length=24, **extra,
                )
                handle = await open_session(
                    ex, factory, name=f"lm-{tag}",
                    stats_interval_s=0.1, open_timeout_s=180.0,
                )
                requests = [
                    await handle.request(p, params={"max_new_tokens": 5})
                    for p in prompts
                ]
                results[tag] = [
                    await r.result(timeout=120.0) for r in requests
                ]
                if tag == "spec":
                    # The 0.1s stats cadence must surface the engine's
                    # accept counters before close.
                    for _ in range(100):
                        if handle.supervisor.stats.get("spec_accepted"):
                            break
                        await asyncio.sleep(0.05)
                    spec_stats = dict(handle.supervisor.stats)
                await handle.close()
        finally:
            await ex.close()
        return results, spec_stats

    results, spec_stats = run_async(flow())
    assert len(results["fp"]) == len(prompts)
    assert all(len(t) == 5 for t in results["fp"])
    # The oracle: spec streams ARE the fp streams, bit for bit.
    assert results["spec"] == results["fp"]
    assert spec_stats is not None
    assert spec_stats.get("spec_proposed", 0) > 0
    # Self-draft: every proposal agrees, accept rate exactly 1.0.
    assert spec_stats["spec_accepted"] == spec_stats["spec_proposed"]
    assert float(spec_stats.get("spec_accept_rate") or 0.0) == 1.0
    assert spec_stats.get("spec_refusals", 0) == 0
