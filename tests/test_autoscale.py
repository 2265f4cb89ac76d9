"""Closed-loop predictive autoscaling (ISSUE 15).

Controller decisions run against stub pools/sets under a fake clock —
every cooldown, dwell, and TTL is reachable without sleeping — while the
end-to-end tier drives a REAL replica set over the local transport
through scale-to-zero and demand re-warm, asserting the streams stay
exactly-once across the suspension.
"""

from __future__ import annotations

import asyncio

import pytest

from covalent_tpu_plugin.fleet import (
    AutoscaleController,
    LocalPoolAutoscaler,
    PoolRegistry,
    PoolSpec,
    ReplicaSetPolicy,
)
from covalent_tpu_plugin.obs.history import MetricsHistory
from covalent_tpu_plugin.obs.metrics import Registry


# ---------------------------------------------------------------------------
# history: trend/slope queries (satellite)
# ---------------------------------------------------------------------------


def make_history(clock):
    registry = Registry()
    history = MetricsHistory(
        registry=registry, interval_s=1.0, capacity=64, clock=clock
    )
    return registry, history


def test_trend_gauge_slope_under_fake_clock():
    now = [1000.0]
    registry, history = make_history(lambda: now[0])
    depth = registry.gauge("queue_depth", "", ("tenant",))
    for value in (0, 2, 4, 6, 8):
        depth.labels(tenant="a").set(value)
        history.sample(force=True)
        now[0] += 1.0
    view = history.query("queue_depth", window_s=10.0, agg="trend")
    assert view["agg"] == "trend"
    series = view["series"]['{"tenant": "a"}']
    # 2 units per second, fit exactly by least squares.
    assert series["slope_per_s"] == pytest.approx(2.0)
    assert series["last"] == 8.0


def test_trend_counter_reports_rate_slope():
    now = [0.0]
    registry, history = make_history(lambda: now[0])
    total = registry.counter("reqs_total", "")
    # Rate accelerates 1/s -> 2/s -> 3/s -> 4/s: slope of the RATE is
    # +1 per second, even though the value slope is much larger.
    value = 0.0
    for rate in (0, 1, 2, 3, 4):
        value += rate
        total.inc(rate)
        history.sample(force=True)
        now[0] += 1.0
    view = history.query("reqs_total", window_s=10.0, agg="trend")
    series = view["series"][""]
    assert series["slope_per_s"] == pytest.approx(1.0)
    assert series["increase"] == pytest.approx(10.0)


def test_trend_flat_and_sparse_series_have_zero_slope():
    now = [0.0]
    registry, history = make_history(lambda: now[0])
    gauge = registry.gauge("flat", "")
    gauge.set(5.0)
    history.sample(force=True)
    view = history.query("flat", window_s=10.0, agg="trend")
    # One point has no trend; a constant series has slope 0.
    assert view["series"][""]["slope_per_s"] == 0.0
    now[0] += 1.0
    gauge.set(5.0)
    history.sample(force=True)
    view = history.query("flat", window_s=10.0, agg="trend")
    assert view["series"][""]["slope_per_s"] == 0.0


def test_trend_counter_reset_skips_torn_interval():
    now = [0.0]
    registry, history = make_history(lambda: now[0])
    total = registry.counter("resets_total", "")
    total.inc(10)
    history.sample(force=True)
    now[0] += 1.0
    # Simulate a registry reset: new child starts from zero.
    registry.unregister("resets_total")
    total = registry.counter("resets_total", "")
    total.inc(1)
    history.sample(force=True)
    now[0] += 1.0
    total.inc(1)
    history.sample(force=True)
    view = history.query("resets_total", window_s=10.0, agg="trend")
    series = view["series"][""]
    # The 10 -> 1 drop is a reset, not a negative burst.
    assert series["increase"] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# LocalPoolAutoscaler: anti-thrash cooldown (satellite)
# ---------------------------------------------------------------------------


def test_local_autoscaler_cooldown_suppresses_thrash():
    """Repeated high/low watermark crossings inside the dwell resize
    once, not once per crossing (the PR-7 hook thrashed on consecutive
    pump ticks)."""
    now = [0.0]
    registry = PoolRegistry()
    registry.register(
        PoolSpec(name="p", capacity=2, transport="local"), executor=object()
    )
    scaler = LocalPoolAutoscaler(
        "p", step=1, max_capacity=8, min_capacity=1,
        cooldown_s=10.0, clock=lambda: now[0],
    )
    scaler.on_high(10, registry)
    assert registry.get("p").capacity == 3
    # Flapping crossings 1s apart: all suppressed inside the dwell.
    for _ in range(3):
        now[0] += 1.0
        scaler.on_low(0, registry)
        now[0] += 1.0
        scaler.on_high(10, registry)
    assert registry.get("p").capacity == 3
    assert scaler.scale_ups == 1 and scaler.scale_downs == 0
    assert scaler.suppressed == 6
    # Past the dwell the next crossing acts again.
    now[0] += 10.0
    scaler.on_low(0, registry)
    assert registry.get("p").capacity == 2
    assert scaler.scale_downs == 1


# ---------------------------------------------------------------------------
# Controller stubs
# ---------------------------------------------------------------------------


class StubHistory:
    """query(agg='trend') answered from canned slopes.

    A plain float lands on the unlabelled series; a dict maps the JSON
    series key (as the real ring produces) to its slope, for tests of
    the controller's label filtering.
    """

    def __init__(self):
        self.slopes: dict = {}

    def query(self, metric, window_s=60.0, labels=None, agg=""):
        spec = self.slopes.get(metric, 0.0)
        if isinstance(spec, dict):
            return {
                "series": {
                    key: {"slope_per_s": value}
                    for key, value in spec.items()
                }
            }
        return {"series": {"": {"slope_per_s": spec}}}


class StubQueue:
    depth = 0


class StubScheduler:
    def __init__(self, registry):
        self.registry = registry
        self.queue = StubQueue()


class StubGang:
    """Pool-side executor stub with warmth + teardown/prewarm hooks."""

    def __init__(self, warm=True):
        self.warm = warm
        self.teardowns = 0
        self.prewarms = 0

    @property
    def is_warm(self):
        return self.warm

    def serve_sessions(self):
        return {}

    async def teardown_gang(self):
        self.warm = False
        self.teardowns += 1
        return True

    async def prewarm(self):
        self.warm = True
        self.prewarms += 1
        return True


class StubEngine:
    def __init__(self):
        self.hooks = []
        self.view = {"slos": {}}

    def add_alert_hook(self, hook):
        self.hooks.append(hook)

    def status(self):
        return self.view

    def burn(self, name, metric):
        self.view["slos"][name] = {"state": "burning", "metric": metric}

    def recover(self, name):
        self.view["slos"][name] = {"state": "ok", "metric": ""}


class StubSet:
    def __init__(self, name="s", replicas=1, slots_per=2):
        self.name = name
        self.slots_per = slots_per
        self._live = replicas
        self.in_flight = 0
        self.queued = 0
        self.state = "open"
        self.prefer_stable = False
        self._suspended = False
        self.scaled: list[int] = []

    @property
    def live_replicas(self):
        return self._live

    @property
    def suspended(self):
        return self._suspended and self._live == 0

    @property
    def decode_slots(self):
        return self._live * self.slots_per

    async def scale_to(self, n):
        self.scaled.append(n)
        self._suspended = n == 0
        self._live = n
        return n

    def rewarm(self, replicas=1):
        """What the request path does on first demand after suspension."""
        self._suspended = False
        self._live = replicas


def make_controller(clock, registry=None, engine=None, **kwargs):
    # Always an engine of the test's own: given none, the controller
    # attaches the process-wide one, whose burning SLOs are whatever the
    # tests before this one in the same process left on the real clock.
    engine = engine if engine is not None else StubEngine()
    history = StubHistory()
    scheduler = (
        StubScheduler(registry) if registry is not None else None
    )
    defaults = dict(
        interval_s=1.0,
        up_cooldown_s=3.0,
        down_cooldown_s=10.0,
        idle_ttl_s=20.0,
        lead_s=2.0,
        clock=clock,
    )
    defaults.update(kwargs)
    controller = AutoscaleController(
        scheduler=scheduler,
        registry=registry,
        history=history,
        slo_engine=engine,
        **defaults,
    )
    return controller, history


def spot_and_stable_registry():
    registry = PoolRegistry()
    gangs = {"spot": StubGang(warm=True), "stable": StubGang(warm=True)}
    registry.register(
        PoolSpec(name="spot", capacity=1, transport="local",
                 preemptible=True),
        executor=gangs["spot"],
    )
    registry.register(
        PoolSpec(name="stable", capacity=1, transport="local"),
        executor=gangs["stable"],
    )
    return registry, gangs


# ---------------------------------------------------------------------------
# Controller: predictive pool scaling
# ---------------------------------------------------------------------------


def test_pool_scale_up_is_predictive_from_queue_trend(run_async):
    """Zero backlog + a rising queue-depth trend scales capacity BEFORE
    demand arrives: predicted = depth + slope * measured lead."""
    now = [0.0]
    registry, _gangs = spot_and_stable_registry()
    controller, history = make_controller(lambda: now[0], registry)
    controller.manage_pool("spot", max_capacity=4)
    controller.manage_pool("stable", max_capacity=4)

    async def go():
        decisions = await controller.tick()
        assert decisions == []  # flat trend, no demand
        # Queue depth rising 2 items/s; lead 2s -> predicted backlog 4.
        history.slopes["covalent_tpu_queue_depth"] = 2.0
        return await controller.tick()

    decisions = run_async(go())
    ups = [d for d in decisions if d["action"] == "pool_up"]
    assert ups and ups[0]["reason"] == "queue_trend"
    # Batch overflow lands on the SPOT pool first (stable stays free for
    # SLO-critical serving).
    assert ups[0]["resource"] == "spot"
    assert registry.get("spot").capacity == 2


def test_pool_scale_up_and_down_hysteresis_no_flap(run_async):
    """Oscillating demand moves capacity at most once per dwell; the
    sustained-below requirement resets on every spike."""
    now = [0.0]
    registry, _gangs = spot_and_stable_registry()
    controller, history = make_controller(
        lambda: now[0], registry, down_cooldown_s=10.0
    )
    controller.manage_pool("spot", max_capacity=4)

    async def go():
        actions = []
        # 20 ticks of demand flapping high/low every second.
        for tick in range(20):
            history.slopes["covalent_tpu_queue_depth"] = (
                2.0 if tick % 2 == 0 else 0.0
            )
            for decision in await controller.tick():
                actions.append(decision["action"])
            now[0] += 1.0
        return actions

    actions = run_async(go())
    # Up-moves ratchet toward the peak, bounded by the up-cooldown (one
    # step per dwell, never one per spike), and the flapping never
    # produces a single scale-down: the sustained-below requirement
    # re-arms on every spike, so capacity cannot see-saw tick to tick.
    assert 1 <= actions.count("pool_up") <= 3
    assert actions.count("pool_down") == 0
    assert 2 <= registry.get("spot").capacity <= 4


def test_pool_scale_down_after_sustained_quiet(run_async):
    now = [0.0]
    registry, _gangs = spot_and_stable_registry()
    controller, history = make_controller(
        lambda: now[0], registry, down_cooldown_s=10.0, idle_ttl_s=0.0
    )
    controller.manage_pool("spot", min_capacity=1, max_capacity=4)

    async def go():
        history.slopes["covalent_tpu_queue_depth"] = 3.0
        await controller.tick()  # scale up to 2
        assert registry.get("spot").capacity == 2
        history.slopes["covalent_tpu_queue_depth"] = 0.0
        actions = []
        for _ in range(25):
            now[0] += 1.0
            for decision in await controller.tick():
                actions.append(decision["action"])
        return actions

    actions = run_async(go())
    assert "pool_down" in actions
    assert registry.get("spot").capacity == 1


def test_dispatch_burn_forces_pool_scale_up(run_async):
    now = [0.0]
    registry, _gangs = spot_and_stable_registry()
    engine = StubEngine()
    controller, _history = make_controller(
        lambda: now[0], registry, engine=engine
    )
    controller.manage_pool("stable", max_capacity=4)

    async def go():
        engine.burn("queue_wait", "covalent_tpu_wall_overhead_seconds")
        return await controller.tick()

    decisions = run_async(go())
    ups = [d for d in decisions if d["action"] == "pool_up"]
    assert ups and ups[0]["reason"] == "slo_burn"


# ---------------------------------------------------------------------------
# Controller: pool scale-to-zero + predictive re-warm
# ---------------------------------------------------------------------------


def test_idle_pool_gang_torn_down_after_ttl_and_prewarmed_on_trend(run_async):
    now = [0.0]
    registry, gangs = spot_and_stable_registry()
    controller, history = make_controller(
        lambda: now[0], registry, idle_ttl_s=20.0
    )
    controller.manage_pool("stable", max_capacity=4)

    async def go():
        await controller.tick()  # arms idle_since
        now[0] += 19.0
        assert not any(
            d["action"] == "gang_teardown" for d in await controller.tick()
        )
        now[0] += 2.0
        teardown = await controller.tick()
        assert any(d["action"] == "gang_teardown" for d in teardown)
        assert gangs["stable"].teardowns == 1
        assert not registry.get("stable").warm
        # Demand trends back in: the controller pays the cold start NOW
        # (predictive prewarm), not when placement already needs it.
        history.slopes["covalent_tpu_queue_depth"] = 1.0
        rewarm = await controller.tick()
        assert any(d["action"] == "prewarm" for d in rewarm)
        await asyncio.sleep(0)  # let the detached prewarm task run
        assert gangs["stable"].prewarms == 1

    run_async(go())


def test_busy_pool_never_torn_down(run_async):
    now = [0.0]
    registry, gangs = spot_and_stable_registry()
    controller, _history = make_controller(
        lambda: now[0], registry, idle_ttl_s=5.0
    )
    controller.manage_pool("stable")
    registry.get("stable").place()  # one slot in use

    async def go():
        for _ in range(10):
            now[0] += 5.0
            for decision in await controller.tick():
                assert decision["action"] != "gang_teardown"
        assert gangs["stable"].teardowns == 0

    run_async(go())


# ---------------------------------------------------------------------------
# Controller: replica sets
# ---------------------------------------------------------------------------


def test_set_scale_up_from_load_and_burn_override(run_async):
    now = [0.0]
    engine = StubEngine()
    controller, history = make_controller(lambda: now[0], engine=engine)
    rset = StubSet(replicas=1, slots_per=2)
    controller.manage_replica_set(rset, max_replicas=4)
    assert rset.prefer_stable is True  # SLO-critical pins to stable

    async def go():
        # Load within capacity: nothing happens.
        rset.in_flight = 1
        assert await controller.tick() == []
        # Load past the utilization target: proportional scale-up.
        rset.in_flight = 6
        decisions = await controller.tick()
        assert [d["action"] for d in decisions] == ["set_up"]
        assert decisions[0]["reason"] == "load_trend"
        assert rset.scaled[-1] == 4  # ceil(6 / (2 * 0.75)) = 4
        # A burning serving SLO forces growth even with load back down.
        rset2 = StubSet(name="s2", replicas=1)
        controller.manage_replica_set(rset2, max_replicas=3)
        engine.burn("serve_p95", "covalent_tpu_serve_request_seconds")
        now[0] += 5.0
        decisions = await controller.tick()
        burn_ups = [
            d for d in decisions
            if d["action"] == "set_up" and d["resource"] == "s2"
        ]
        assert burn_ups and burn_ups[0]["reason"] == "slo_burn"
        assert rset2.scaled[-1] == 2

    run_async(go())


def test_set_scale_up_is_predictive_from_in_flight_trend(run_async):
    now = [0.0]
    controller, history = make_controller(lambda: now[0])
    rset = StubSet(replicas=1, slots_per=2)
    controller.manage_replica_set(rset, max_replicas=4)

    async def go():
        rset.in_flight = 1  # half the slots: fine today
        history.slopes["covalent_tpu_serve_replica_in_flight"] = {
            '{"replica": "r0", "set": "s"}': 1.5,
            # A DIFFERENT set's rising trend must not leak in.
            '{"replica": "r0", "set": "other"}': 50.0,
        }
        decisions = await controller.tick()
        # predicted = 1 + 1.5 * 2s lead = 4 -> ceil(4 / 1.5) = 3
        assert [d["action"] for d in decisions] == ["set_up"]
        assert rset.scaled[-1] == 3

    run_async(go())


def test_set_scale_down_requires_sustained_low_and_no_burn(run_async):
    now = [0.0]
    engine = StubEngine()
    controller, _history = make_controller(
        lambda: now[0], engine=engine, down_cooldown_s=10.0,
        idle_ttl_s=0.0,
    )
    rset = StubSet(replicas=3, slots_per=2)
    # max_replicas == live: the burn override has no headroom to grow
    # into, isolating the scale-DOWN veto under test.
    controller.manage_replica_set(rset, min_replicas=1, max_replicas=3)

    async def go():
        rset.in_flight = 0
        # While a serving SLO burns, scale-down is vetoed outright.
        engine.burn("serve_p95", "covalent_tpu_serve_request_seconds")
        for _ in range(15):
            now[0] += 1.0
            assert await controller.tick() == []
        assert rset.scaled == []
        # Burn clears: the dwell starts NOW; one step down per dwell.
        engine.recover("serve_p95")
        actions = []
        for _ in range(12):
            now[0] += 1.0
            actions += [d["action"] for d in await controller.tick()]
        assert actions.count("set_down") == 1
        assert rset.scaled[-1] == 2

    run_async(go())


def test_set_scale_to_zero_after_idle_ttl_and_resume_decision(run_async):
    now = [0.0]
    controller, _history = make_controller(
        lambda: now[0], idle_ttl_s=20.0, down_cooldown_s=5.0
    )
    rset = StubSet(replicas=1, slots_per=2)
    controller.manage_replica_set(
        rset, min_replicas=0, max_replicas=3, slo_critical=False
    )

    async def go():
        rset.in_flight = 0
        await controller.tick()  # arms idle_since
        now[0] += 21.0
        decisions = await controller.tick()
        assert [d["action"] for d in decisions] == ["set_suspend"]
        assert rset.scaled[-1] == 0 and rset.suspended
        # Idle set stays suspended tick after tick.
        now[0] += 5.0
        assert await controller.tick() == []
        # First demand re-warms through the SET's request path; the
        # controller observes and records the resume.
        rset.rewarm(replicas=1)
        now[0] += 1.0
        decisions = await controller.tick()
        assert any(d["action"] == "set_resume" for d in decisions)

    run_async(go())


def test_controller_status_and_decision_counter(run_async):
    from covalent_tpu_plugin.fleet.autoscale import (
        AUTOSCALE_DECISIONS_TOTAL,
    )

    now = [0.0]
    registry, _gangs = spot_and_stable_registry()
    engine = StubEngine()
    controller, history = make_controller(
        lambda: now[0], registry, engine=engine
    )
    controller.manage_pool("spot", max_capacity=4)
    rset = StubSet(replicas=1)
    controller.manage_replica_set(rset, max_replicas=2)
    before = AUTOSCALE_DECISIONS_TOTAL.labels(action="pool_up").value

    async def go():
        history.slopes["covalent_tpu_queue_depth"] = 5.0
        await controller.tick()

    run_async(go())
    assert (
        AUTOSCALE_DECISIONS_TOTAL.labels(action="pool_up").value
        == before + 1
    )
    status = controller.status()
    assert status["pools"]["spot"]["capacity"] == 2
    assert status["pools"]["spot"]["lead_s"] == pytest.approx(2.0)
    assert "since_up_s" in status["pools"]["spot"]["cooldown"]
    assert status["sets"]["s"]["replicas"] == 1
    assert status["sets"]["s"]["slo_critical"] is True
    assert status["decision_counts"].get("pool_up", 0) >= 1
    assert any(
        d["action"] == "pool_up" for d in status["decisions"]
    )


def test_measured_prewarm_lead_time():
    """With no override, the lead comes from the per-pool prewarm
    histogram mean, clamped into [interval, max_lead]."""
    from covalent_tpu_plugin.tpu import _PREWARM_SECONDS

    now = [0.0]
    registry, _gangs = spot_and_stable_registry()
    controller, _history = make_controller(
        lambda: now[0], registry, lead_s=0.0
    )
    controller.lead_override_s = 0.0
    _PREWARM_SECONDS.labels(pool="stable").observe(4.0)
    _PREWARM_SECONDS.labels(pool="stable").observe(6.0)
    assert controller._lead_for("stable") == pytest.approx(5.0)
    # A pool with no measurements of its own rides the all-pools mean
    # (other tests may have observed pool="" in this process, so only
    # the clamp bounds are exact here).
    assert 1.0 <= controller._lead_for("spot") <= 30.0


def test_slo_alert_hook_wakes_controller(run_async):
    """The alert-hook path (engine thread) records the burn and the next
    tick acts on it without waiting for a status refresh."""
    now = [0.0]
    engine = StubEngine()
    controller, _history = make_controller(lambda: now[0], engine=engine)
    rset = StubSet(replicas=1)
    controller.manage_replica_set(rset, max_replicas=2)
    assert engine.hooks, "controller never subscribed an alert hook"

    async def go():
        engine.hooks[0](
            "serve_p95", "burning",
            {"metric": "covalent_tpu_serve_request_seconds"},
        )
        decisions = await controller.tick()
        assert any(d["action"] == "set_up" for d in decisions)
        # Recovery through the hook clears the veto state too.
        engine.hooks[0]("serve_p95", "ok", {"metric": ""})
        assert "serve_p95" not in controller._burning

    run_async(go())


# ---------------------------------------------------------------------------
# ReplicaSet: prefer_stable placement (SLO-driven pinning)
# ---------------------------------------------------------------------------


def test_replica_placement_prefers_stable_pools_when_pinned():
    from covalent_tpu_plugin.serving.replicas import ReplicaSet

    registry = PoolRegistry()
    spot = registry.register(
        PoolSpec(name="spot", capacity=4, transport="local",
                 preemptible=True),
        executor=StubGang(warm=True),
    )
    stable = registry.register(
        PoolSpec(name="stable", capacity=4, transport="local"),
        executor=StubGang(warm=False),  # colder AND stable must still win
    )
    rset = ReplicaSet([spot, stable], lambda: None, prefer_stable=True)
    ranked = rset._rank_targets()
    assert ranked[0][1] is stable
    rset_unpinned = ReplicaSet([spot, stable], lambda: None)
    # Without the pin, the warm spot pool ranks first (warmth wins).
    assert rset_unpinned._rank_targets()[0][1] is spot


# ---------------------------------------------------------------------------
# Scale-to-zero end to end: a REAL replica set over the local transport
# ---------------------------------------------------------------------------


def expected_stream(seed: int, cap: int = 6) -> list[int]:
    # test_serving.make_factory streams base+1..base+cap for prompt
    # [..., base].
    return [seed + j + 1 for j in range(cap)]


def test_scale_to_zero_rewarns_on_demand_exactly_once(run_async, tmp_path):
    """An idle set scaled to zero re-warms on the next request: the
    stream is byte-exact (no duplicate, no hole), the set reports
    suspended in between, and a SECOND round-trip proves the resumed
    set serves normally."""
    from covalent_tpu_plugin.serving import open_replica_set

    from .helpers import make_local_executor
    from .test_serving import make_factory

    async def go():
        ex = make_local_executor(
            tmp_path, use_agent="pool", heartbeat_interval=0.0,
            prewarm=False,
        )
        try:
            rset = await open_replica_set(
                [ex], make_factory(step_delay=0.01), name="s2z",
                stats_interval_s=0.1,
            )
            first = await rset.request(
                [1], params={"max_new_tokens": 6}
            )
            assert await first.result(timeout=30) == expected_stream(1)
            assert await rset.scale_to(0) == 0
            assert rset.suspended and rset.state == "suspended"
            assert rset.live_replicas == 0
            status = rset.status()
            assert status["suspended"] is True
            # First demand re-warms transparently; the stream is the
            # exact expected token sequence (exactly-once across the
            # suspension boundary).
            second = await rset.request(
                [2], params={"max_new_tokens": 6}
            )
            assert await second.result(timeout=60) == expected_stream(2)
            assert not rset.suspended and rset.live_replicas == 1
            third = await rset.request(
                [3], params={"max_new_tokens": 6}
            )
            assert await third.result(timeout=30) == expected_stream(3)
            assert rset.served >= 2  # post-resume replica's own count
            await rset.close()
        finally:
            await ex.close()

    run_async(go())


def test_request_racing_scale_to_zero_is_not_dropped(run_async, tmp_path):
    """A request arriving while scale_to(0) is mid-drain queues behind
    the scale lock, re-warms the set, and completes with its exact
    stream — never an error, never a drop."""
    from covalent_tpu_plugin.serving import open_replica_set

    from .helpers import make_local_executor
    from .test_serving import make_factory

    async def go():
        ex = make_local_executor(
            tmp_path, use_agent="pool", heartbeat_interval=0.0,
            prewarm=False,
        )
        try:
            rset = await open_replica_set(
                [ex], make_factory(step_delay=0.01), name="s2zrace",
                stats_interval_s=0.1,
            )
            warmup = await rset.request(
                [7], params={"max_new_tokens": 6}
            )
            assert await warmup.result(timeout=30) == expected_stream(7)
            teardown = asyncio.ensure_future(rset.scale_to(0))
            await asyncio.sleep(0)  # let the drain grab the scale lock
            racing = await rset.request(
                [9], params={"max_new_tokens": 6}
            )
            assert await racing.result(timeout=60) == expected_stream(9)
            await teardown
            # The race resolved by re-warming: the set is live again.
            assert rset.live_replicas == 1
            await rset.close()
        finally:
            await ex.close()

    run_async(go())


def test_scale_to_zero_with_router_backlog_rewarns_instead(
    run_async, tmp_path
):
    """scale_to(0) with a request still waiting in the router's DRR
    queue (admitted but never worker-assigned) must NOT suspend over
    it: queued requests are demand, so the drain re-warms immediately
    and the stream completes (the code-review hole: a suspended set
    never pumps its queue)."""
    from covalent_tpu_plugin.fleet.queue import WorkItem
    from covalent_tpu_plugin.serving import open_replica_set
    from covalent_tpu_plugin.serving.supervisor import ServeRequest

    from .helpers import make_local_executor
    from .test_serving import make_factory

    async def go():
        ex = make_local_executor(
            tmp_path, use_agent="pool", heartbeat_interval=0.0,
            prewarm=False,
        )
        try:
            rset = await open_replica_set(
                [ex], make_factory(step_delay=0.01), name="s2zq",
                stats_interval_s=0.1,
            )
            warmup = await rset.request([1], params={"max_new_tokens": 6})
            assert await warmup.result(timeout=30) == expected_stream(1)
            # Inject a router-queued request directly — the state a
            # request reaches when it races the drain while a replica
            # still looks alive but has no headroom.
            stranded = ServeRequest(
                "s2zq-stranded", [5], {"max_new_tokens": 6}, 0.0, ""
            )
            rset.router.submit(WorkItem(
                fn=None, args=(), kwargs={},
                task_metadata={
                    "request": stranded, "sticky": "", "prefix_key": "",
                },
            ))
            count = await rset.scale_to(0)
            # The drain saw the backlog and re-warmed instead of
            # suspending over it; the stranded stream completes.
            assert count >= 1 and not rset.suspended
            assert await stranded.result(timeout=60) == expected_stream(5)
            await rset.close()
        finally:
            await ex.close()

    run_async(go())


def test_controller_revives_dead_set_to_policy_floor(run_async):
    """A managed set whose replicas ALL died without a suspension (past
    retry budgets) cannot re-warm through its own request path — the
    controller must re-open it to the policy's replica floor, paced by
    the up-cooldown."""
    now = [0.0]
    controller, _history = make_controller(lambda: now[0])
    rset = StubSet(replicas=1)
    controller.manage_replica_set(rset, min_replicas=1, max_replicas=3)

    async def go():
        rset._live = 0  # dead, NOT suspended
        decisions = await controller.tick()
        revive = [d for d in decisions if d["action"] == "set_up"]
        assert revive and revive[0]["reason"] == "revive_dead"
        assert rset.scaled[-1] == 1 and rset.live_replicas == 1
        # A suspended set, by contrast, is left for its request path.
        rset2 = StubSet(name="s2", replicas=1)
        controller.manage_replica_set(
            rset2, min_replicas=0, max_replicas=3, slo_critical=False
        )
        rset2._live = 0
        rset2._suspended = True
        now[0] += 10.0
        assert all(
            d["resource"] != "s2" for d in await controller.tick()
        )
        assert rset2.scaled == []

    run_async(go())
