"""Disaggregated prefill/decode serving: KV transfer plane + router.

Three tiers:

* **Protocol** — the ``serve_prefill`` verb and KV-attached
  ``serve_request`` against the real pool server: bundle round trip with
  worker-announced digest, unknown-session fast failure, engines without
  the surface, and the digest-mismatch degrade-to-full-prefill path
  (``kv_fallbacks`` counted, stream byte-equal).
* **Router units** — prefix-affinity ranked below sticky and above
  least-loaded with DRR fairness untouched, affinity sites forgotten
  with their replica.
* **Set integration** — a real :class:`DisaggregatedSet` (prefill tier +
  decode tier over pool-server processes): long prompts ride the KV
  road (transfer bytes/latency accounted), short prompts go direct,
  streams are byte-identical either way, and a SIGKILLed prefill
  replica mid-traffic degrades every request to a full prefill on the
  decode tier with byte-equal streams and zero user-visible errors.

The real-LM half of the contract (bit-equal greedy streams through
``prefill_only``/``admit_from_kv`` against the decode oracle) lives in
``tests/test_continuous.py``.
"""

import asyncio
import hashlib
import pickle
import sys
import time

import pytest

from covalent_tpu_plugin.agent import AgentError, start_pool_server
from covalent_tpu_plugin.fleet.pools import Pool, PoolSpec, parse_pool_specs
from covalent_tpu_plugin.fleet.queue import WorkItem
from covalent_tpu_plugin.resilience import FaultClass, classify_error
from covalent_tpu_plugin.serving import (
    ReplicaRouter,
    ReplicaView,
    open_disaggregated_set,
    open_session,
)
from covalent_tpu_plugin.transport import LocalTransport

from .test_serving import (
    drain_until,
    make_serve_executor,
    stage_factory,
)
from .test_serving_replicas import FakeClock, make_replica_executor


def make_kv_factory(
    slots=2, chunk=2, default_cap=6, step_delay=0.0, prefill_s_per_tok=0.0
):
    """A stub engine speaking the FULL disaggregated surface
    (``prefill_only``/``admit_from_kv`` on top of admit/step/cancel),
    cloudpickled by value.  Streams are deterministic per prompt —
    ``base+1, base+2, ...`` off the last prompt token — and IDENTICAL
    whichever admission road is taken, so byte-equality across the
    disagg/fallback/direct paths is checkable.  ``prefill_s_per_tok``
    models prefill compute occupying the engine loop (the cost
    disaggregation moves off the decode tier)."""

    def factory():
        import pickle as pickle_mod
        import time as time_mod

        class Engine:
            def __init__(self):
                self.slots = slots
                self.lanes = {}
                self.stats = {
                    "prefix_hits": 0, "prefix_misses": 0,
                    "prefill_positions": 0, "kv_exports": 0,
                }

            def _tokens(self, prompt, cap):
                base = int(prompt[-1])
                return [base + i + 1 for i in range(cap)]

            def admit(self, rid, prompt, params):
                cap = int((params or {}).get("max_new_tokens", default_cap))
                if prefill_s_per_tok:
                    time_mod.sleep(prefill_s_per_tok * len(prompt))
                self.stats["prefill_positions"] += len(prompt)
                self.lanes[rid] = self._tokens(prompt, cap)

            def prefill_only(self, prompt, params):
                if prefill_s_per_tok:
                    time_mod.sleep(prefill_s_per_tok * len(prompt))
                self.stats["prefill_positions"] += len(prompt)
                self.stats["kv_exports"] += 1
                return pickle_mod.dumps({
                    "prompt": [int(t) for t in prompt],
                    "first": int(prompt[-1]) + 1,
                })

            def admit_from_kv(self, rid, data, params):
                bundle = pickle_mod.loads(bytes(data))
                cap = int((params or {}).get("max_new_tokens", default_cap))
                # Zero prefill positions: the bundle carries the work.
                self.lanes[rid] = self._tokens(bundle["prompt"], cap)

            def step(self):
                if step_delay:
                    time_mod.sleep(step_delay)
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


def view(rid, load=0, capacity=4, open=True):
    return ReplicaView(rid, open=open, load=load, capacity=capacity)


def item(tenant="default", sticky="", prefix_key=""):
    return WorkItem(
        fn=None, args=(), kwargs={},
        task_metadata={
            "request": None, "sticky": sticky, "prefix_key": prefix_key,
        },
        tenant=tenant,
    )


# ---------------------------------------------------------------------------
# Protocol: serve_prefill + KV-attached serve_request on the pool server
# ---------------------------------------------------------------------------


def test_pool_serve_prefill_roundtrip_and_kv_admit(tmp_path, run_async):
    """serve_prefill streams a digest-announced bundle back; re-shipping
    it on a serve_request admits through admit_from_kv (kv_admits moves,
    the request never pays prefill) with a byte-equal stream."""

    async def flow():
        client = await start_pool_server(
            LocalTransport(), str(tmp_path / "remote"), sys.executable
        )
        records: list = []
        try:
            digest, path = stage_factory(tmp_path, make_kv_factory())
            client.watch_serve("s1", lambda sid, data: records.append(data))
            await client.serve_open(
                "s1", digest, path,
                options={"stats_interval_s": 0.1}, timeout=30.0,
            )
            event = await client.serve_prefill(
                "s1", "kv1", [3, 1, 7], params={"max_new_tokens": 4},
                timeout=20.0,
            )
            data = event["data_bytes"]
            assert hashlib.sha256(data).hexdigest() == event["digest"]
            await client.serve_request(
                "s1", "r1", [3, 1, 7], params={"max_new_tokens": 4},
                kv_bytes=data, kv_digest=event["digest"],
            )
            await drain_until(
                records,
                lambda r: r.get("type") == "serve.token" and r.get("done"),
            )
            stats = await drain_until(
                records,
                lambda r: r.get("type") == "serve.stats"
                and r.get("kv_admits"),
            )
            closed = await client.serve_close("s1", timeout=15.0)
        finally:
            await client.close()
        return event, records, stats, closed

    event, records, stats, closed = run_async(flow())
    bundle = pickle.loads(event["data_bytes"])
    assert bundle == {"prompt": [3, 1, 7], "first": 8}
    streamed: list = []
    for chunk in records:
        if chunk.get("type") == "serve.token":
            streamed.extend(chunk["tokens"])
    assert streamed == [8, 9, 10, 11]
    assert stats["kv_admits"] == 1
    assert stats.get("kv_fallbacks", 0) == 0
    # Engine-local counters surfaced in the stats record (satellite):
    assert stats["kv_exports"] == 1
    assert stats["prefill_positions"] == 3  # the prefill-only pass
    assert closed["served"] == 1


def test_pool_serve_prefill_unknown_session_and_unsupported(
    tmp_path, run_async
):
    """A prefill against a sid that was never opened fails fast with a
    serve_kv error; an engine without prefill_only answers
    ``unsupported`` — both raise AgentError for the caller to degrade."""
    from .test_serving import make_factory

    async def flow():
        client = await start_pool_server(
            LocalTransport(), str(tmp_path / "remote"), sys.executable
        )
        try:
            with pytest.raises(AgentError, match="unknown_session"):
                await client.serve_prefill("ghost", "k0", [1], timeout=15.0)
            digest, path = stage_factory(tmp_path, make_factory())
            await client.serve_open("plain", digest, path, timeout=30.0)
            with pytest.raises(AgentError, match="unsupported"):
                await client.serve_prefill(
                    "plain", "k1", [1, 2], timeout=15.0
                )
            await client.serve_close("plain", timeout=15.0)
        finally:
            await client.close()

    run_async(flow())


def test_pool_kv_digest_mismatch_degrades_to_full_prefill(
    tmp_path, run_async
):
    """A KV bundle whose bytes do not match the announced digest is
    NEVER unpickled: the worker counts a kv_fallback, runs the full
    prefill, and the stream is byte-identical to the clean road."""

    async def flow():
        client = await start_pool_server(
            LocalTransport(), str(tmp_path / "remote"), sys.executable
        )
        records: list = []
        try:
            digest, path = stage_factory(tmp_path, make_kv_factory())
            client.watch_serve("s1", lambda sid, data: records.append(data))
            await client.serve_open(
                "s1", digest, path,
                options={"stats_interval_s": 0.1}, timeout=30.0,
            )
            poison = pickle.dumps({"prompt": [99], "first": 1})
            await client.serve_request(
                "s1", "r1", [5], params={"max_new_tokens": 4},
                kv_bytes=poison,
                kv_digest="0" * 64,  # does not match the bytes
            )
            await drain_until(
                records,
                lambda r: r.get("type") == "serve.token" and r.get("done"),
            )
            stats = await drain_until(
                records,
                lambda r: r.get("type") == "serve.stats"
                and r.get("kv_fallbacks"),
            )
            await client.serve_close("s1", timeout=15.0)
        finally:
            await client.close()
        return records, stats

    records, stats = run_async(flow())
    streamed: list = []
    for chunk in records:
        if chunk.get("type") == "serve.token":
            streamed.extend(chunk["tokens"])
    # The FULL prefill road's stream (base 5), not the poison bundle's.
    assert streamed == [6, 7, 8, 9]
    assert stats["kv_fallbacks"] == 1
    assert stats.get("kv_admits", 0) == 0


# ---------------------------------------------------------------------------
# Router units: prefix affinity vs sticky vs DRR (no I/O)
# ---------------------------------------------------------------------------


def test_router_prefix_affinity_steers_and_sticky_wins():
    """A remembered prefix site attracts same-key requests
    (outcome=prefix_affinity); a sticky pin outranks it; and the site
    moves with the traffic (last placement wins)."""
    router = ReplicaRouter(clock=FakeClock())
    views = {
        "r0": view("r0", load=0), "r1": view("r1", load=0),
    }
    router.record_prefix_site("pfx", "r1")
    router.submit(item(prefix_key="pfx"))
    [(_, replica, outcome)] = router.pump(views)
    assert (replica, outcome) == ("r1", "prefix_affinity")
    # Sticky beats prefix affinity.
    router.pin("caller", "r0")
    router.submit(item(sticky="caller", prefix_key="pfx"))
    [(_, replica, outcome)] = router.pump(views)
    assert (replica, outcome) == ("r0", "sticky")
    # ... and that sticky placement re-recorded the site onto r0.
    assert router.prefix_site("pfx") == "r0"


def test_router_prefix_affinity_never_defers_and_respects_headroom():
    """A full (or dead) prefix site does NOT defer the request (unlike a
    sticky pin): placement falls through to least-loaded, and the site
    is forgotten with its replica."""
    router = ReplicaRouter(clock=FakeClock())
    router.record_prefix_site("pfx", "r1")
    views = {
        "r0": view("r0", load=0, capacity=4),
        "r1": view("r1", load=4, capacity=4),  # no headroom
    }
    router.submit(item(prefix_key="pfx"))
    [(_, replica, outcome)] = router.pump(views)
    assert (replica, outcome) == ("r0", "least_loaded")
    router.record_prefix_site("pfx2", "r1")
    router.forget_replica("r1")
    assert router.prefix_site("pfx2") is None


def test_router_drr_fairness_preserved_under_prefix_affinity():
    """With prefix-affinity ranking in play, per-tenant DRR still
    decides WHOSE request dispatches next: a 3:1 weighted tenant drains
    3x the other under a one-slot trickle, prefix keys or not."""
    clock = FakeClock()
    router = ReplicaRouter(weights={"gold": 3.0, "econ": 1.0}, clock=clock)
    for i in range(12):
        router.submit(item(tenant="gold", prefix_key="g"))
        router.submit(item(tenant="econ", prefix_key="e"))
    router.record_prefix_site("g", "r0")
    router.record_prefix_site("e", "r0")
    drained = {"gold": 0, "econ": 0}
    views = {"r0": view("r0", load=3, capacity=4)}
    for _ in range(8):  # 8 single-slot pumps
        assigned = router.pump(views)
        assert len(assigned) == 1
        drained[assigned[0][0].tenant] += 1
    assert drained["gold"] == 6 and drained["econ"] == 2, drained


# ---------------------------------------------------------------------------
# Set integration: real prefill/decode tiers over pool servers
# ---------------------------------------------------------------------------


def test_disaggregated_set_routes_long_prompts_through_kv(
    tmp_path, run_async
):
    """1 prefill + 2 decode replicas: long prompts ride the KV road
    (transfer bytes + latency accounted, decode tier pays zero prefill
    positions for them), short prompts go direct, and every stream is
    byte-exact.  Roles land on the role-declared pools."""

    from covalent_tpu_plugin.obs.tracestore import ensure_trace_store

    store = ensure_trace_store()
    store.sample = 1.0

    async def flow():
        pre = make_replica_executor(tmp_path, "pre")
        dec1 = make_replica_executor(tmp_path, "dec1")
        dec2 = make_replica_executor(tmp_path, "dec2")
        [pre_spec] = parse_pool_specs("prefill-pool=local@2!prefill")
        pre_spec.fallback = False
        pools = [
            Pool(pre_spec, executor=pre),
            Pool(PoolSpec(name="dec1", role="decode", capacity=2),
                 executor=dec1),
            Pool(PoolSpec(name="dec2", role="decode", capacity=2),
                 executor=dec2),
        ]
        try:
            dset = await open_disaggregated_set(
                pools,
                make_kv_factory(),
                decode_replicas=2,
                prefill_replicas=1,
                min_prompt_tokens=8,
                name="disagg",
                stats_interval_s=0.1,
            )
            long_prompts = [
                list(range(i, i + 11)) + [100 * (i + 1)] for i in range(4)
            ]
            short_prompts = [[7 * (i + 1)] for i in range(3)]
            requests = []
            for prompt in long_prompts + short_prompts:
                requests.append(await dset.request(
                    prompt, params={"max_new_tokens": 4}
                ))
            results = [await r.result(timeout=30) for r in requests]
            trace_ids = [r.span.trace_id for r in requests]
            status = dset.status()
            roles = dict(dset._role_of)
            placements = {
                rid: dset._placements[rid][1].name
                for rid in dset._placements
            }
            await dset.close()
        finally:
            await pre.close()
            await dec1.close()
            await dec2.close()
        return results, status, roles, placements, long_prompts, \
            short_prompts, trace_ids

    try:
        (results, status, roles, placements, long_prompts,
         short_prompts, trace_ids) = run_async(flow())
    finally:
        store._sample_override = None
    for prompt, tokens in zip(long_prompts + short_prompts, results):
        base = prompt[-1]
        assert tokens == [base + j + 1 for j in range(4)], (prompt, tokens)
    assert status["requests_by_path"].get("disagg") == len(long_prompts)
    assert status["requests_by_path"].get("direct") == len(short_prompts)
    assert status["kv_bytes_total"] > 0
    assert status["kv_transfer_p50_ms"] > 0
    assert roles == {"r0": "prefill", "r1": "decode", "r2": "decode"}
    # Role-aware placement: the prefill replica landed on the pool that
    # declared role=prefill.
    assert placements["r0"] == "prefill-pool"
    # One trace a request from the dispatcher through the prefill worker
    # to the decode worker: every one finalized, no span without its
    # parent, and the KV road's requests carry the prefill tile.
    for i, trace_id in enumerate(trace_ids):
        view = store.waterfall(trace_id)
        assert view is not None, f"trace {trace_id} never finalized"
        assert view["root"] == "serve.request"
        assert not any(s["orphan"] for s in view["spans"]), view["spans"]
        assert "ttft_wait" in view["segments"]
        names = {s["name"] for s in view["spans"]}
        assert "serve.worker.decode" in names
        on_kv_road = i < len(long_prompts)
        assert ("prefill" in view["segments"]) == on_kv_road
        assert ("serve.worker.prefill" in names) == on_kv_road


def test_disaggregated_prefill_kill_mid_traffic_degrades_byte_equal(
    tmp_path, run_async
):
    """SIGKILL the prefill replica's resident server mid-traffic: every
    in-flight and subsequent long-prompt request completes via the
    decode tier's full prefill — byte-equal streams, exactly-once, zero
    user-visible errors — and the fallback is visible in the path
    accounting."""

    async def flow():
        pre = make_replica_executor(
            tmp_path, "pre", retry_base_delay=0.05, retry_max_delay=0.2
        )
        dec = make_replica_executor(
            tmp_path, "dec", retry_base_delay=0.05, retry_max_delay=0.2
        )
        try:
            dset = await open_disaggregated_set(
                [pre, dec],
                make_kv_factory(step_delay=0.05),
                decode_replicas=1,
                prefill_replicas=1,
                min_prompt_tokens=4,
                kv_timeout_s=10.0,
                name="killpre",
                retries=1,
            )
            warm = await dset.request(
                list(range(6)) + [500], params={"max_new_tokens": 4}
            )
            warm_result = await warm.result(timeout=30)
            # Kill the prefill replica's resident server, then keep the
            # long-prompt traffic coming while it is down.
            pre._agents["localhost"]._process._proc.kill()
            requests = [
                await dset.request(
                    list(range(6)) + [1000 * (i + 1)],
                    params={"max_new_tokens": 4},
                )
                for i in range(3)
            ]
            results = [await r.result(timeout=30) for r in requests]
            status = dset.status()
            await dset.close()
        finally:
            await pre.close()
            await dec.close()
        return warm_result, results, status

    warm_result, results, status = run_async(flow())
    assert warm_result == [501, 502, 503, 504]
    for i, tokens in enumerate(results):
        base = 1000 * (i + 1)
        assert tokens == [base + j + 1 for j in range(4)], (i, tokens)
    paths = status["requests_by_path"]
    assert paths.get("disagg", 0) >= 1        # the pre-kill request
    assert paths.get("fallback", 0) >= 1      # the post-kill requests
    assert status["state"] in ("open", "reconnecting")


def test_disaggregated_sticky_rides_decode_tier(tmp_path, run_async):
    """Sticky sids pin to DECODE replicas only (the prefill tier is
    invisible to the router), and multi-turn callers stay put across
    short and long prompts alike."""

    async def flow():
        pre = make_replica_executor(tmp_path, "spre")
        dec1 = make_replica_executor(tmp_path, "sdec1")
        dec2 = make_replica_executor(tmp_path, "sdec2")
        try:
            dset = await open_disaggregated_set(
                [pre, dec1, dec2],
                make_kv_factory(slots=4),
                decode_replicas=2,
                prefill_replicas=1,
                min_prompt_tokens=6,
                name="sticky",
            )
            requests = []
            for i in range(6):
                prompt = (
                    list(range(8)) + [50 * (i + 1)]
                    if i % 2 else [50 * (i + 1)]
                )
                requests.append(await dset.request(
                    prompt, params={"max_new_tokens": 3},
                    sticky="caller-1",
                ))
            results = [await r.result(timeout=30) for r in requests]
            status = dset.status()
            served_by = {
                rid: v["served"] for rid, v in status["replicas"].items()
            }
            roles = dict(dset._role_of)
            await dset.close()
        finally:
            await pre.close()
            await dec1.close()
            await dec2.close()
        return results, served_by, roles

    results, served_by, roles = run_async(flow())
    for i, tokens in enumerate(results):
        base = 50 * (i + 1)
        assert tokens == [base + 1, base + 2, base + 3]
    decode_served = {
        rid: n for rid, n in served_by.items() if roles[rid] == "decode"
    }
    # One sticky caller -> exactly one decode replica took every stream.
    assert sorted(decode_served.values()) == [0, 6], decode_served
    assert served_by[next(
        rid for rid, role in roles.items() if role == "prefill"
    )] == 0


# ---------------------------------------------------------------------------
# Satellite: typed rolling_cache refusal through a REAL open_session
# ---------------------------------------------------------------------------


def test_rolling_cache_refusal_permanent_through_open_session(
    tmp_path, run_async
):
    """lm_engine_factory with a rolling_cache model surfaces
    RollingCacheUnsupported as serve_model_unsupported PERMANENT through
    a real open_session — one refusal, no gang-retry burn."""
    import dataclasses

    import jax.numpy as jnp

    from covalent_tpu_plugin.models import TransformerConfig, TransformerLM
    from covalent_tpu_plugin.models.serve import lm_engine_factory

    cfg = TransformerConfig(
        vocab_size=32, d_model=16, n_layers=1, n_heads=2, d_ff=32,
        max_seq=32, dtype=jnp.float32, attention="reference",
        sliding_window=8, rolling_cache=True,
    )
    model = TransformerLM(cfg)
    # Construction refuses before params are ever touched, so none are
    # needed — the worker only pays the jax import.
    factory = lm_engine_factory(model, None)

    async def flow():
        import os

        import cloudpickle

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
        try:
            t0 = time.monotonic()
            with pytest.raises(Exception) as err:
                await open_session(
                    ex, factory, name="rolling", open_timeout_s=120.0,
                )
            elapsed = time.monotonic() - t0
        finally:
            await ex.close()
        return err.value, elapsed

    failure, _elapsed = run_async(flow())
    fault, label = classify_error(failure)
    assert fault is FaultClass.PERMANENT
    assert label == "serve_model_unsupported"


def test_disaggregated_kv_rides_cas_road_without_frames(
    tmp_path, run_async
):
    """With binary frames off (JSONL channel), the KV bundle ships ONCE
    into the decode worker's remote CAS and the request references it
    by path — kv_admits still moves, streams stay byte-exact, and the
    digest-named artifact lands in the worker's CAS dir."""
    import os

    async def flow():
        pre = make_replica_executor(tmp_path, "cpre", agent_frames=False)
        dec = make_replica_executor(tmp_path, "cdec", agent_frames=False)
        try:
            dset = await open_disaggregated_set(
                [pre, dec],
                make_kv_factory(),
                decode_replicas=1,
                prefill_replicas=1,
                min_prompt_tokens=4,
                name="casroad",
                stats_interval_s=0.1,
            )
            requests = [
                await dset.request(
                    [1, 2, 3, 4, 5, 40 * (i + 1)],
                    params={"max_new_tokens": 3},
                )
                for i in range(2)
            ]
            results = [await r.result(timeout=30) for r in requests]
            # Wait for the stats record carrying the worker's final kv
            # counters: one taken between the two admissions counts 1.
            decode_sup = next(
                sup for rid, sup in dset._replicas.items()
                if dset._role_of[rid] == "decode"
            )
            for _ in range(100):
                if decode_sup.stats.get("kv_admits") == len(requests):
                    break
                await asyncio.sleep(0.05)
            kv_admits = decode_sup.stats.get("kv_admits")
            status = dset.status()
            cas_dir = os.path.join(str(tmp_path / "remote-cdec"), "cas")
            staged = [
                name for name in os.listdir(cas_dir)
                if name.endswith(".kv")
            ]
            await dset.close()
        finally:
            await pre.close()
            await dec.close()
        return results, kv_admits, status, staged

    results, kv_admits, status, staged = run_async(flow())
    for i, tokens in enumerate(results):
        base = 40 * (i + 1)
        assert tokens == [base + 1, base + 2, base + 3]
    assert kv_admits == 2
    assert status["requests_by_path"].get("disagg") == 2
    assert len(staged) == 2  # one digest-named artifact per bundle
