"""Elastic gangs: cooperative checkpoint-resume under preemption.

End-to-end over the local transport with real harness subprocesses: the
interval checkpointer publishes digest-named bundles + an atomic manifest
into the remote CAS; a chaos-injected spot preemption (SIGTERM notice,
grace window, channel drop) triggers the final cooperative snapshot; the
retry driver discovers/verifies the newest complete checkpoint and the
replacement gang resumes from it instead of recomputing — with the
``worker_preempted`` retry label, ``task.resumed`` lineage events and the
saves/restores counters moving.  A torn-bundle-on-disk test proves resume
skips incomplete checkpoints and falls back to the previous complete step.
"""

from __future__ import annotations

import asyncio
import json
import os
import pathlib
import time

from covalent_tpu_plugin import harness as harness_mod
from covalent_tpu_plugin.obs import events as obs_events
from covalent_tpu_plugin.obs.metrics import REGISTRY
from covalent_tpu_plugin.transport import ChaosPlan, LocalTransport

from .helpers import make_local_executor

REPO_ROOT = str(pathlib.Path(__file__).resolve().parents[1])


def counter_value(name: str, **labels) -> float:
    metric = REGISTRY.get(name)
    if metric is None:
        return 0.0
    child = metric.labels(**labels) if labels else metric
    return child.value


def make_elastic_executor(tmp_path, **kwargs):
    kwargs.setdefault("checkpoint_interval_s", 0.15)
    kwargs.setdefault("checkpoint_keep_n", 2)
    kwargs.setdefault("poll_freq", 0.1)
    # Heartbeats give the poll path a telemetry file: the preemption
    # notice lands there, and the failure handler's telemetry tail is how
    # the death gets its worker_preempted label without an agent channel.
    kwargs.setdefault("heartbeat_interval", 0.5)
    kwargs.setdefault("task_env", {
        "PYTHONPATH": REPO_ROOT + os.pathsep + os.environ.get(
            "PYTHONPATH", ""
        ),
    })
    return make_local_executor(tmp_path, **kwargs)


def elastic_train(
    steps: int, step_s: float, progress_path: str, hold_at: int = -1
):
    """A checkpoint-cooperative training electron.

    Appends every executed step to ``progress_path`` (so the test can
    count recomputation across attempts), registers a snapshot hook, and
    resumes from the dispatcher-shipped bundle when one exists.  An
    attempt that did not resume stands still before step ``hold_at``
    until the preemption takes it, so the fault finds it mid-run however
    long the worker took to boot.
    """
    import time

    from covalent_tpu_plugin.utils import checkpoint as ckpt

    state = {"acc": 0.0, "step": -1}
    start = 0
    resumed = ckpt.resume_state()
    if resumed is not None:
        step0, tree = resumed
        state.update(tree)
        start = int(step0) + 1

    def snap():
        # One read of the rebinding variable: the hook runs from the
        # checkpointer thread AND the SIGTERM handler, and each step
        # publishes a fresh dict instead of mutating in place, so a
        # snapshot is always internally consistent.
        current = state
        return dict(current), current["step"]

    ckpt.register_snapshot(snap)
    try:
        for step in range(start, steps):
            if resumed is None and step == hold_at:
                # Held for SIGTERM; bounded so a preemption that never
                # comes fails the test's assertions instead of hanging.
                held_until = time.monotonic() + 60.0
                while time.monotonic() < held_until:
                    time.sleep(0.01)
            with open(progress_path, "a") as f:
                f.write(f"{step}\n")
            time.sleep(step_s)
            state = {"acc": state["acc"] + step, "step": step}
    finally:
        ckpt.unregister_snapshot()
    return state["acc"], start


class EventLog:
    def __init__(self):
        self.events: list[dict] = []

    def __enter__(self):
        obs_events.add_listener(self.events.append)
        return self

    def __exit__(self, *exc):
        obs_events.remove_listener(self.events.append)

    def of(self, kind: str) -> list[dict]:
        return [e for e in self.events if e.get("type") == kind]


def test_interval_checkpoints_published_to_cas(tmp_path, run_async):
    """No faults: the interval checkpointer publishes sha256-named bundles
    plus a manifest whose history is bounded by keep_n, and the saves
    counter moves via the lifecycle event road."""
    ex = make_elastic_executor(tmp_path, checkpoint_interval_s=0.1)
    metadata = {"dispatch_id": "ckpt-pub", "node_id": 0}
    progress = tmp_path / "progress.txt"

    async def flow():
        try:
            return await ex.run(
                elastic_train, [10, 0.06, str(progress)], {}, metadata
            )
        finally:
            await ex.close()

    acc, start = run_async(flow())
    assert acc == sum(range(10)) and start == 0
    cas = tmp_path / "remote" / "cas"
    manifest_path = cas / "ckpt_ckpt-pub_0.json"
    assert manifest_path.exists(), list(cas.iterdir())
    # The result lands before the worker stops its checkpointer: a last
    # save may still be publishing (bundle, then manifest, then the
    # unlink of what fell off it).  Read once the directory is at rest.
    at_rest = time.monotonic() + 10.0
    while True:
        history = json.loads(manifest_path.read_text())["history"]
        if (
            len(list(cas.glob("*.ckpt"))) == len(history)
            or time.monotonic() > at_rest
        ):
            break
        time.sleep(0.05)
    assert 1 <= len(history) <= 2  # keep_n bounds the completed steps
    for entry in history:
        bundle = pathlib.Path(entry["file"])
        assert bundle.exists()
        from covalent_tpu_plugin.utils.checkpoint import verify_bundle_file

        assert verify_bundle_file(bundle, entry["digest"])
    # GC: bundles dropped off the manifest were unlinked.
    assert len(list(cas.glob("*.ckpt"))) == len(history)


def test_preemption_resume_not_recompute(tmp_path, run_async):
    """The tentpole contract: a preempted gang retries INTO a resume —
    correct result, recomputed steps bounded by the checkpoint interval
    (not the whole run), ``worker_preempted`` retry label, ``task.resumed``
    event, restores counter moving."""
    steps, step_s = 60, 0.05
    # Armed below, once a checkpoint exists: an op count alone may fire
    # while the worker is still importing, and then there is nothing to
    # resume from.
    plan = ChaosPlan(preempt_grace=1.0, max_faults=1)
    ex = make_elastic_executor(
        tmp_path,
        max_task_retries=2,
        retry_base_delay=0.05,
        retry_max_delay=0.1,
        chaos=plan,
    )
    metadata = {"dispatch_id": "ckpt-resume", "node_id": 0}
    progress = tmp_path / "progress.txt"
    saves_before = sum(
        child.value for _, child in
        (REGISTRY.get("covalent_tpu_checkpoint_saves_total")._series())
    ) if REGISTRY.get("covalent_tpu_checkpoint_saves_total") else 0.0
    restores_before = counter_value(
        "covalent_tpu_checkpoint_restores_total"
    )
    preempt_retries_before = counter_value(
        "covalent_tpu_task_retries_total", reason="worker_preempted"
    )

    manifest = tmp_path / "remote" / "cas" / "ckpt_ckpt-resume_0.json"

    async def preempt_after_first_checkpoint():
        while not (
            manifest.exists()
            and json.loads(manifest.read_text()).get("history")
        ):
            await asyncio.sleep(0.02)
        plan.preempt_after = 1  # the channel's next op delivers the notice

    async def flow():
        arm = asyncio.ensure_future(preempt_after_first_checkpoint())
        try:
            return await ex.run(
                elastic_train, [steps, step_s, str(progress), 10], {},
                metadata,
            )
        finally:
            arm.cancel()
            await ex.close()

    with EventLog() as log:
        acc, resumed_start = run_async(flow())
    assert acc == sum(range(steps))  # bit-equal train state
    assert plan.faults_injected == 1, "preemption never fired"
    assert resumed_start > 0, "final attempt did not resume"
    executed = [int(x) for x in progress.read_text().split()]
    recomputed = len(executed) - len(set(executed))
    assert recomputed < steps / 2, (recomputed, executed)
    assert counter_value(
        "covalent_tpu_task_retries_total", reason="worker_preempted"
    ) == preempt_retries_before + 1
    assert counter_value(
        "covalent_tpu_checkpoint_restores_total"
    ) == restores_before + 1
    resumed_events = log.of("task.resumed")
    assert resumed_events and resumed_events[0]["lineage"] == (
        "ckpt-resume_0"
    )
    assert int(resumed_events[0]["step"]) == resumed_start - 1
    # The preemption notice reached the dispatcher as an event too.
    assert log.of("task.resume_planned")
    # The flight recorder saw the lineage (task.resumed feeds it like any
    # other task event) — then the clean completion retired the ring.
    assert log.of("task.state")[-1]["state"] == "completed"


def test_torn_checkpoint_skipped_falls_back_to_previous(
    tmp_path, run_async
):
    """A bundle torn on disk (killed mid-write, truncated fs) fails its
    digest check during resume discovery: the previous complete step wins
    and a ``task.resume_skipped_torn`` event records the skip."""
    ex = make_elastic_executor(tmp_path)
    cas_dir = tmp_path / "remote" / "cas"
    cas_dir.mkdir(parents=True, exist_ok=True)
    lineage = "torn-lineage_0"
    harness_mod._write_checkpoint_bundle(
        str(cas_dir), lineage, 3, {"acc": 3.0, "step": 3}, keep_n=4
    )
    path, digest, _ = harness_mod._write_checkpoint_bundle(
        str(cas_dir), lineage, 7, {"acc": 21.0, "step": 7}, keep_n=4
    )
    # Tear the newest bundle ON DISK (its manifest entry still points
    # at it, exactly like a kill mid-fsync).
    data = pathlib.Path(path).read_bytes()
    pathlib.Path(path).write_bytes(data[: len(data) // 2])

    async def flow():
        conn = LocalTransport()
        try:
            with EventLog() as log:
                plan = await ex._discover_resume(lineage, [conn])
            return plan, log.of("task.resume_skipped_torn")
        finally:
            await conn.close()
            await ex.close()

    plan, torn_events = run_async(flow())
    assert plan is not None and plan["step"] == 3
    assert torn_events and torn_events[0]["step"] == 7
    assert torn_events[0]["digest"] == digest
    # The surviving plan's local mirror verifies.
    from covalent_tpu_plugin.utils.checkpoint import verify_bundle_file

    assert verify_bundle_file(plan["local"], plan["digest"])


def test_checkpoint_disabled_means_no_spec_block(tmp_path, run_async):
    """checkpoint_interval_s=0 (the default) ships no checkpoint config,
    installs no handler, and RPC preselect stays unaffected."""
    ex = make_local_executor(tmp_path)
    assert ex.checkpoint_interval_s == 0.0
    staged = ex._write_function_files(
        "nockpt", lambda: 1, (), {}, str(tmp_path / "wd"),
        lineage="nockpt",
    )
    spec = json.loads(
        pathlib.Path(staged.local_spec_files[0]).read_text()
    )
    assert "checkpoint" not in spec and "resume" not in spec

    ex2 = make_local_executor(
        tmp_path / "b", checkpoint_interval_s=5.0, dispatch_mode="auto",
        use_agent="pool",
    )
    assert ex2._rpc_preselect({}) is False  # checkpointing pins launch
    staged2 = ex2._write_function_files(
        "ckpt", lambda: 1, (), {}, str(tmp_path / "wd"), lineage="base",
    )
    spec2 = json.loads(
        pathlib.Path(staged2.local_spec_files[0]).read_text()
    )
    assert spec2["checkpoint"]["lineage"] == "base"
    assert spec2["checkpoint"]["interval_s"] == 5.0

    async def close():
        await ex.close()
        await ex2.close()

    run_async(close())
