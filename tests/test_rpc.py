"""RPC dispatch: execute-by-digest on the warm resident runtime.

The PR-8 fast path end to end over the real local transport: function
registered once per connection via the CAS, invoked by digest with args
inline on the agent channel, results streamed back — plus the lifecycle
guarantees around it (re-registration after an agent restart, eviction on
discard, the oversized-args CAS road, digest-mismatch permanence, the
dead-resident-worker transient, launch-path fallbacks, scheduler digest
affinity, and the AgentClient leak audit).
"""

import asyncio
import base64
import sys

import cloudpickle
import pytest

from covalent_tpu_plugin import TPUExecutor
from covalent_tpu_plugin.agent import AgentError, start_pool_server
from covalent_tpu_plugin.cache import bytes_digest
from covalent_tpu_plugin.obs.metrics import REGISTRY
from covalent_tpu_plugin.resilience import FaultClass, classify_error
from covalent_tpu_plugin.transport import LocalTransport

from .helpers import pin_cpu_task_env


def make_rpc_executor(tmp_path, **kwargs):
    kwargs.setdefault("transport", "local")
    kwargs.setdefault("cache_dir", str(tmp_path / "cache"))
    kwargs.setdefault("remote_cache", str(tmp_path / "remote"))
    kwargs.setdefault("python_path", sys.executable)
    kwargs.setdefault("poll_freq", 0.2)
    kwargs.setdefault("use_agent", "pool")
    kwargs.setdefault("dispatch_mode", "rpc")
    kwargs.setdefault("heartbeat_interval", 0.0)
    kwargs.setdefault("prewarm", False)
    return TPUExecutor(**pin_cpu_task_env(kwargs))


def counter_value(name: str, **labels) -> float:
    metric = REGISTRY.get(name)
    if metric is None:
        return 0.0
    total = 0.0
    for series_labels, counter in metric._series():
        if all(series_labels.get(k) == v for k, v in labels.items()):
            total += counter.value
    return total


def _make_square():
    # Nested on purpose: cloudpickle serializes module-level functions BY
    # REFERENCE (module + qualname), and the resident server cannot import
    # the tests package — a closure-local function pickles by value, like
    # real user electrons defined in scripts/notebooks.
    def square(x):
        return x * x

    return square


square = _make_square()


# ---------------------------------------------------------------------------
# The happy path
# ---------------------------------------------------------------------------


def test_rpc_executes_by_digest_and_matches_launch(tmp_path, run_async):
    """Same electron through both modes: equal results, byte-equal pickles,
    and the fast path actually engaged (no silent launch fallback)."""

    async def flow():
        rpc = make_rpc_executor(tmp_path / "rpc")
        launch = make_rpc_executor(tmp_path / "launch", dispatch_mode="launch")
        try:
            rpc_result = await rpc.run(
                square, [7], {}, {"dispatch_id": "r", "node_id": 0}
            )
            rpc_mode = rpc.last_dispatch_mode
            launch_result = await launch.run(
                square, [7], {}, {"dispatch_id": "l", "node_id": 0}
            )
            launch_mode = launch.last_dispatch_mode
        finally:
            await rpc.close()
            await launch.close()
        return rpc_result, rpc_mode, launch_result, launch_mode

    rpc_result, rpc_mode, launch_result, launch_mode = run_async(flow())
    assert rpc_result == launch_result == 49
    assert cloudpickle.dumps(rpc_result) == cloudpickle.dumps(launch_result)
    assert rpc_mode == "rpc"
    assert launch_mode == "launch"


def test_rpc_registers_once_per_connection(tmp_path, run_async):
    """Repeat electrons with different args share one registration: the
    warm path is invoke-by-digest, not re-ship + re-register."""

    async def flow():
        ex = make_rpc_executor(tmp_path)
        misses0 = counter_value(
            "covalent_tpu_rpc_registrations_total", result="miss"
        )
        hits0 = counter_value(
            "covalent_tpu_rpc_registrations_total", result="hit"
        )
        try:
            results = [
                await ex.run(
                    square, [i], {}, {"dispatch_id": "warm", "node_id": i}
                )
                for i in range(3)
            ]
            counts = ex._fn_registry.counts()
            digest_count = ex.rpc_digest_count()
        finally:
            await ex.close()
        return (
            results, counts, digest_count,
            counter_value(
                "covalent_tpu_rpc_registrations_total", result="miss"
            ) - misses0,
            counter_value(
                "covalent_tpu_rpc_registrations_total", result="hit"
            ) - hits0,
        )

    results, counts, digest_count, misses, hits = run_async(flow())
    assert results == [0, 1, 4]
    assert digest_count == 1 and list(counts.values()) == [1]
    assert misses == 1  # one register_fn round trip total
    assert hits == 2    # electrons 2 and 3 rode the registry


def test_rpc_exception_transported(tmp_path, run_async):
    def boom():
        raise KeyError("rpc-boom")

    async def flow():
        ex = make_rpc_executor(tmp_path)
        try:
            with pytest.raises(KeyError, match="rpc-boom"):
                await ex.run(boom, [], {}, {"dispatch_id": "b", "node_id": 0})
            return ex.last_dispatch_mode
        finally:
            await ex.close()

    assert run_async(flow()) == "rpc"


def test_rpc_oversized_args_take_cas_path_with_equal_results(
    tmp_path, run_async
):
    """Args past the inline threshold stage through the CAS (digest
    verified remotely) and the invocation still returns identical bytes."""
    big = "x" * 50_000

    async def flow():
        inline = make_rpc_executor(tmp_path / "inline")
        staged = make_rpc_executor(
            tmp_path / "staged", rpc_inline_args_max=64
        )
        cas0 = counter_value("covalent_tpu_cas_uploads_total", result="miss")
        try:
            inline_result = await inline.run(
                len, [big], {}, {"dispatch_id": "i", "node_id": 0}
            )
            cas_inline = counter_value(
                "covalent_tpu_cas_uploads_total", result="miss"
            ) - cas0
            staged_result = await staged.run(
                len, [big], {}, {"dispatch_id": "s", "node_id": 0}
            )
            cas_staged = counter_value(
                "covalent_tpu_cas_uploads_total", result="miss"
            ) - cas0 - cas_inline
            modes = (inline.last_dispatch_mode, staged.last_dispatch_mode)
        finally:
            await inline.close()
            await staged.close()
        return inline_result, staged_result, cas_inline, cas_staged, modes

    inline_result, staged_result, cas_inline, cas_staged, modes = run_async(
        flow()
    )
    assert inline_result == staged_result == 50_000
    assert modes == ("rpc", "rpc")
    # Inline arm ships only the function payload; the staged arm ships the
    # args artifact too — proof the CAS road was actually taken.
    assert cas_staged == cas_inline + 1


# ---------------------------------------------------------------------------
# Registry lifecycle
# ---------------------------------------------------------------------------


def test_rpc_reregisters_after_agent_restart(tmp_path, run_async):
    """A restarted resident runtime lost its in-process registry: the
    per-connection registered-set is bound to the client object, so the
    next dispatch re-registers instead of invoking into a void."""

    async def flow():
        ex = make_rpc_executor(tmp_path)
        misses0 = counter_value(
            "covalent_tpu_rpc_registrations_total", result="miss"
        )
        try:
            assert await ex.run(
                square, [3], {}, {"dispatch_id": "a", "node_id": 0}
            ) == 9
            first_client = ex._agents.get("localhost")
            # Kill the pool server out from under the executor: the next
            # run's lease pings the cached client, fails, and rebuilds.
            first_client._process._proc.kill()
            assert await ex.run(
                square, [4], {}, {"dispatch_id": "a2", "node_id": 0}
            ) == 16
            second_client = ex._agents.get("localhost")
            misses = counter_value(
                "covalent_tpu_rpc_registrations_total", result="miss"
            ) - misses0
            counts = dict(ex._fn_registry.counts())
        finally:
            await ex.close()
        return first_client is not second_client, misses, counts

    restarted, misses, counts = run_async(flow())
    assert restarted
    assert misses == 2  # registered once per runtime generation
    assert list(counts.values()) == [1]  # no stale duplicates


def test_rpc_registry_evicted_when_connection_discarded(tmp_path, run_async):
    async def flow():
        ex = make_rpc_executor(tmp_path)
        try:
            await ex.run(square, [2], {}, {"dispatch_id": "d", "node_id": 0})
            before = ex.rpc_digest_count()
            await ex._discard_workers()
            after = ex.rpc_digest_count()
        finally:
            await ex.close()
        return before, after

    before, after = run_async(flow())
    assert before == 1
    assert after == 0


def test_rpc_digest_mismatch_is_permanent(tmp_path, run_async):
    """A CAS artifact whose bytes don't match the registered digest is a
    torn payload: the runtime refuses it and the classifier reads the
    refusal as PERMANENT — no gang retries on deterministic corruption."""

    async def flow():
        conn = LocalTransport()
        client = await start_pool_server(
            conn, str(tmp_path), sys.executable
        )
        try:
            artifact = tmp_path / "payload.pkl"
            artifact.write_bytes(cloudpickle.dumps(square))
            wrong_digest = bytes_digest(b"entirely different bytes")
            with pytest.raises(AgentError) as excinfo:
                await client.register_fn(wrong_digest, str(artifact))
        finally:
            await client.close()
            await conn.close()
        return excinfo.value

    error = run_async(flow())
    fault, label = classify_error(error)
    assert fault is FaultClass.PERMANENT
    assert label == "rpc_digest_mismatch"


def test_pool_server_invoke_roundtrip(tmp_path, run_async):
    """Protocol-level register + invoke against the real pool server."""

    async def flow():
        conn = LocalTransport()
        client = await start_pool_server(
            conn, str(tmp_path), sys.executable
        )
        try:
            payload = cloudpickle.dumps(square)
            digest = bytes_digest(payload)
            artifact = tmp_path / f"{digest}.pkl"
            artifact.write_bytes(payload)
            await client.register_fn(digest, str(artifact))
            args_b64 = base64.b64encode(
                cloudpickle.dumps(((6,), {}))
            ).decode("ascii")
            pid = await client.invoke(
                "op-1", digest, spec={"operation_id": "op-1"},
                args_b64=args_b64,
            )
            event = await client.wait_result("op-1", timeout=30.0)
            result, exception = TPUExecutor._decode_rpc_result(event)
        finally:
            await client.close()
            await conn.close()
        return pid, event.get("ok"), result, exception

    pid, ok, result, exception = run_async(flow())
    assert isinstance(pid, int)
    assert ok is True and exception is None
    assert result == 36


# ---------------------------------------------------------------------------
# Resilience
# ---------------------------------------------------------------------------


def test_rpc_dead_resident_worker_is_transient_and_retried(
    tmp_path, run_async
):
    """Kill the resident worker mid-invoke: classified transient
    (``rpc_channel``), the gang torn down, and the retry completes."""

    def slow(i):
        import time

        time.sleep(3.0)
        return i * 3

    async def flow():
        ex = make_rpc_executor(
            tmp_path, max_task_retries=2,
            retry_base_delay=0.05, retry_max_delay=0.2,
        )
        retries0 = counter_value(
            "covalent_tpu_task_retries_total", reason="rpc_channel"
        )
        fallbacks0 = counter_value(
            "covalent_tpu_tasks_total", outcome="fallback_local"
        )
        try:
            task = asyncio.ensure_future(ex.run(
                slow, [5], {}, {"dispatch_id": "kill", "node_id": 0}
            ))
            for _ in range(300):
                state = ex._op_status.get("kill_0", {})
                if state.get("stage") == "executing":
                    break
                await asyncio.sleep(0.05)
            assert state.get("mode") == "rpc", state
            ex._agents["localhost"]._process._proc.kill()
            result = await task
            attempts = ex.last_attempts
        finally:
            await ex.close()
        return (
            result, attempts,
            counter_value(
                "covalent_tpu_task_retries_total", reason="rpc_channel"
            ) - retries0,
            counter_value(
                "covalent_tpu_tasks_total", outcome="fallback_local"
            ) - fallbacks0,
        )

    result, attempts, retries, fallbacks = run_async(flow())
    assert result == 15
    assert attempts >= 2
    assert retries >= 1
    assert fallbacks == 0  # recovered remotely, never the local CPU re-run


def test_rpc_unavailable_runtime_falls_back_to_launch(tmp_path, run_async):
    """No resident pool runtime on the gang: the same attempt re-runs
    through the launch path (the ISSUE's missing-agent fallback)."""

    async def flow():
        from covalent_tpu_plugin import tpu as tpu_mod

        ex = make_rpc_executor(tmp_path)

        async def no_pool(*args, **kwargs):
            raise AgentError("scripted: no pool runtime")

        original = tpu_mod.start_pool_server
        tpu_mod.start_pool_server = no_pool
        try:
            result = await ex.run(
                square, [9], {}, {"dispatch_id": "fb", "node_id": 0}
            )
            mode = ex.last_dispatch_mode
        finally:
            tpu_mod.start_pool_server = original
            await ex.close()
        return result, mode

    result, mode = run_async(flow())
    assert result == 81
    assert mode == "launch"


def test_launch_from_a_runtime_holding_a_backend_fails_at_once(
    tmp_path, run_async
):
    """One runtime, both modes: after an RPC electron initialised jax inside
    the resident server, a launch-mode electron would fork a child that can
    only hang (and, on a TPU, could never have the chip).  It is refused at
    once, permanently, naming the holder — no retry, no nohup detour."""

    def touch_jax():
        import os

        import jax.numpy as jnp

        return os.getpid(), float(jnp.arange(4.0).sum())

    async def flow():
        ex = make_rpc_executor(tmp_path, max_task_retries=2)
        try:
            holder, value = await ex.run(
                touch_jax, [], {}, {"dispatch_id": "hold", "node_id": 0}
            )
            with pytest.raises(RuntimeError) as refused:
                await asyncio.wait_for(
                    ex.run(
                        square, [3], {},
                        {"dispatch_id": "hold", "node_id": 1,
                         "dispatch_mode": "launch"},
                    ),
                    timeout=60,
                )
            # The runtime itself is unharmed: RPC work keeps flowing.
            again = await ex.run(
                square, [4], {}, {"dispatch_id": "hold", "node_id": 2}
            )
        finally:
            await ex.close()
        return holder, value, str(refused.value), again

    holder, value, message, again = run_async(flow())
    assert value == 6.0 and again == 16
    assert f"pid {holder} holds an initialised cpu backend" in message


def test_rpc_preselect_static_fallbacks(tmp_path):
    """Shapes RPC mode cannot serve route to launch before any attempt."""
    ex = make_rpc_executor(tmp_path / "base", dispatch_mode="auto")
    assert ex._rpc_preselect({}) is True
    assert ex._rpc_preselect({"dispatch_mode": "launch"}) is False
    assert ex._rpc_preselect({"pip_deps": ["torch"]}) is False

    pod = make_rpc_executor(
        tmp_path / "pod", dispatch_mode="auto", workers=["w1", "w2"]
    )
    assert pod._rpc_preselect({}) is False  # multi-worker gangs launch

    no_agent = make_rpc_executor(
        tmp_path / "na", dispatch_mode="auto", use_agent=False
    )
    assert no_agent._rpc_preselect({}) is False

    from covalent_tpu_plugin.transport import ChaosPlan

    chaotic = make_rpc_executor(
        tmp_path / "ch", dispatch_mode="auto", chaos=ChaosPlan(delay=0.01)
    )
    assert chaotic._rpc_preselect({}) is False  # auto defers under chaos
    assert chaotic._rpc_preselect({"dispatch_mode": "rpc"}) is True  # pin wins


# ---------------------------------------------------------------------------
# Leak audit (satellite): per-task state drops on every exit path
# ---------------------------------------------------------------------------


def client_books(client) -> dict:
    return {
        "started": dict(client._started),
        "exits": dict(client._exits),
        "errors": dict(client._errors),
        "results": dict(client._results),
        "telemetry_seq": dict(client._telemetry_seq),
    }


def test_agent_client_state_dropped_on_every_exit_path(tmp_path, run_async):
    """Watch state, seq-dedup maps, and result buffers for finished tasks
    must be empty after success, remote exception, AND a mid-task kill —
    a resident client serves many electrons and must not accumulate."""

    def boom():
        raise ValueError("audit-boom")

    def sleeper():
        import time

        time.sleep(30)
        return "never"

    async def flow():
        ex = make_rpc_executor(
            tmp_path, heartbeat_interval=0.2, task_timeout=60.0
        )
        try:
            # Success path (heartbeats on: telemetry seq map exercised).
            await ex.run(square, [2], {}, {"dispatch_id": "ok", "node_id": 0})
            # Remote-exception path.
            with pytest.raises(ValueError):
                await ex.run(boom, [], {}, {"dispatch_id": "ex", "node_id": 0})
            # Cancel path: a task killed mid-flight.  Capture the client
            # BEFORE cancelling — cancel tears the resident runtime down
            # (the only way to stop an in-process invocation), so the
            # executor's agent map no longer holds it afterwards.
            task = asyncio.ensure_future(ex.run(
                sleeper, [], {}, {"dispatch_id": "cancel", "node_id": 0}
            ))
            for _ in range(300):
                if ex._op_status.get("cancel_0", {}).get("stage") == "executing":
                    break
                await asyncio.sleep(0.05)
            client = ex._agents.get("localhost")
            await ex.cancel("cancel_0")
            with pytest.raises(asyncio.CancelledError):
                await asyncio.wait_for(task, 30.0)
            # The cancelled invocation's runtime was actually dropped —
            # the user function must not keep burning the shared
            # interpreter after run() returned cancelled.
            assert ex._agents.get("localhost") is not client
            # Launch path through the same client (pool run + watch).
            launch_ex = make_rpc_executor(
                tmp_path / "launch2", dispatch_mode="launch",
                heartbeat_interval=0.2,
            )
            try:
                await launch_ex.run(
                    square, [3], {}, {"dispatch_id": "lw", "node_id": 0}
                )
                launch_client = launch_ex._agents.get("localhost")
                launch_books = client_books(launch_client)
            finally:
                await launch_ex.close()
            books = client_books(client)
        finally:
            await ex.close()
        return books, launch_books

    books, launch_books = run_async(flow())
    for name, mapping in {**books, **launch_books}.items():
        assert not mapping, f"leaked {name}: {mapping}"


def test_agent_client_forget_clears_rpc_state_after_channel_death(
    tmp_path, run_async
):
    """Channel death leaves stored per-task state; forget() must drop it
    even though no waiter consumed the events."""

    async def flow():
        conn = LocalTransport()
        client = await start_pool_server(
            conn, str(tmp_path), sys.executable
        )
        try:
            payload = cloudpickle.dumps(square)
            digest = bytes_digest(payload)
            artifact = tmp_path / f"{digest}.pkl"
            artifact.write_bytes(payload)
            await client.register_fn(digest, str(artifact))
            args_b64 = base64.b64encode(
                cloudpickle.dumps(((2,), {}))
            ).decode("ascii")
            await client.invoke("dead-op", digest, args_b64=args_b64)
            # Result arrives and is buffered; nobody waits for it.
            for _ in range(100):
                if "dead-op" in client._results:
                    break
                await asyncio.sleep(0.05)
            assert "dead-op" in client._results
            client._process._proc.kill()
            for _ in range(100):
                if not client.alive:
                    break
                await asyncio.sleep(0.05)
            client.forget("dead-op")
            books = client_books(client)
        finally:
            await client.close()
            await conn.close()
        return books

    books = run_async(flow())
    for name, mapping in books.items():
        assert not mapping, f"leaked {name} after channel death: {mapping}"


# ---------------------------------------------------------------------------
# Fleet placement affinity
# ---------------------------------------------------------------------------


def test_scheduler_prefers_pool_holding_fn_digest(run_async):
    """Digest affinity beats the bin-pack most-free tiebreak: the pool
    whose gang already registered the electron's function wins placement
    even against an emptier equally-warm pool."""
    from covalent_tpu_plugin.fleet.pools import PoolRegistry, PoolSpec
    from covalent_tpu_plugin.fleet.queue import WorkItem
    from covalent_tpu_plugin.fleet.scheduler import FleetScheduler

    fn_digest = bytes_digest(cloudpickle.dumps(square))

    class HoldingStub:
        def __init__(self, holds):
            self._holds = holds
            self.is_warm = True

        def gang_state(self):
            return {"warm": True, "breakers": {}}

        def rpc_digest_count(self):
            return 1 if self._holds else 0

        def holds_fn_digest(self, digest):
            return self._holds and digest == fn_digest

        async def run(self, fn, args, kwargs, task_metadata):
            return fn(*args, **kwargs)

        async def close(self):
            pass

    registry = PoolRegistry()
    # "empty" has MORE free slots; "holder" holds the digest.
    registry.register(
        PoolSpec(name="empty", capacity=4, transport="local"),
        executor=HoldingStub(holds=False),
    )
    registry.register(
        PoolSpec(name="holder", capacity=2, transport="local"),
        executor=HoldingStub(holds=True),
    )
    scheduler = FleetScheduler(registry)
    item = WorkItem(
        fn=square, args=(2,), kwargs={},
        task_metadata={"dispatch_id": "aff", "node_id": 0},
    )
    pool, rerouted = scheduler._select_pool(item)
    assert pool.name == "holder"
    assert rerouted is False

    # Without affinity the emptier pool wins, proving the key ordering.
    other = WorkItem(
        fn=len, args=("x",), kwargs={},
        task_metadata={"dispatch_id": "no", "node_id": 0},
    )
    pool, _ = scheduler._select_pool(other)
    assert pool.name == "empty"


def test_pool_status_reports_digests_and_dispatch_modes(tmp_path, run_async):
    """The fleet ``/status`` pool view carries the RPC dispatch state:
    how many function digests the gang's resident runtimes hold, and the
    dispatch mode of each in-flight electron."""
    from covalent_tpu_plugin.fleet.pools import PoolRegistry, PoolSpec

    async def flow():
        ex = make_rpc_executor(tmp_path)
        registry = PoolRegistry()
        registry.register(
            PoolSpec(name="p", capacity=2, transport="local"), executor=ex
        )
        pool = registry.get("p")
        try:
            cold = pool.status()
            await ex.run(square, [3], {}, {"dispatch_id": "st", "node_id": 0})
            # Freeze an in-flight view mid-run by reading the live books
            # right after seeding one op status entry ourselves.
            ex._op_status["st_9"] = {"stage": "executing", "mode": "rpc"}
            warm = pool.status()
            modes = dict(ex.in_flight_modes())
        finally:
            ex._op_status.pop("st_9", None)
            await ex.close()
        return cold, warm, modes

    cold, warm, modes = run_async(flow())
    assert cold["registered_digests"] == 0
    assert warm["registered_digests"] == 1
    assert warm["in_flight_modes"] == {"st_9": "rpc"}
    assert modes == {"st_9": "rpc"}
