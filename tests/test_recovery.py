"""Dispatcher crash recovery: journal replay → re-adoption, end to end.

The worker-side half (orphan mode, epoch fencing, ``serve_resume``,
inventories) is covered process-level in ``test_recovery_worker.py``;
the journal's framing/replay in ``test_journal.py``.  This file covers
the dispatcher side: a first executor incarnation journals its world
and "crashes" (channels torn down with no close handshake, supervision
tasks cancelled), a second incarnation replays the journal, re-dials,
adopts the orphaned pool server through the rendezvous + ``--attach``
splice, and resumes the in-flight stream from its journaled high-water
mark with exactly-once delivery.
"""

import asyncio
import time

import pytest

from covalent_tpu_plugin.fleet import journal as journal_mod
from covalent_tpu_plugin.fleet import recovery as recovery_mod
from covalent_tpu_plugin.obs.metrics import REGISTRY
from covalent_tpu_plugin.serving import open_session

from .test_serving import (
    GATE_OPEN,
    allow_steps,
    make_factory,
    make_serve_executor,
    step_gate,
)


def counter_value(name: str, **labels) -> float:
    metric = REGISTRY.get(name)
    if metric is None:
        return 0.0
    total = 0.0
    for series_labels, series in metric._series():
        if all(series_labels.get(k) == v for k, v in labels.items()):
            total += series.value
    return total


def crash_dispatcher(ex) -> None:
    """Tear the first incarnation down the way SIGKILL would.

    No ``serve_close``, no channel shutdown handshake: supervision tasks
    are cancelled and each agent channel's pipes are dropped cold, so
    the worker sees a bare stdin EOF — the orphan-mode trigger — while
    this process (standing in for the successor dispatcher) lives on.
    The writer is closed FIRST and the reader cancelled in the same
    synchronous block, so no supervision code can run a graceful
    teardown in between.
    """
    for handle in list(ex._serve_handles.values()):
        sup = getattr(handle, "supervisor", handle)
        task = getattr(sup, "_supervisor", None)
        if task is not None:
            task.cancel()
    for client in list(ex._agents.values()):
        client._process._writer.close()
        client._reader.cancel()
    ex._serve_handles.clear()
    ex._agents.clear()


async def eventually(done, what: str) -> None:
    deadline = time.monotonic() + 60
    while not done():
        if time.monotonic() > deadline:
            raise AssertionError(what)
        await asyncio.sleep(0.02)


async def stream_reaches(request, n: int) -> None:
    """Wait for a gated stream's first ``n`` tokens: the gate holds it
    there, so only a stream that never starts can outlast the wait."""
    await eventually(lambda: len(request.tokens) >= n, "stream never started")


async def orphan_published(tmp_path) -> None:
    """Wait for the crashed incarnation's worker to publish its
    rendezvous.  A real successor starts seconds after the crash; here it
    follows at once, and one that dials before the orphan has noticed its
    dead channel starts a fresh server and finds no session to adopt."""
    await eventually(
        (tmp_path / "remote" / "pool_orphan.json").exists,
        "the worker never went into orphan mode",
    )


@pytest.fixture()
def journal_dir(tmp_path, monkeypatch):
    path = tmp_path / "journal"
    monkeypatch.setenv("COVALENT_TPU_JOURNAL_DIR", str(path))
    monkeypatch.setenv("COVALENT_TPU_ORPHAN_TTL_S", "90")
    journal_mod.reset()
    yield str(path)
    journal_mod.reset()


def test_recover_is_noop_without_journal(tmp_path, run_async, monkeypatch):
    """With journaling off the recovery pass touches nothing — no dial,
    no subprocess, just a ``recovered=False`` report."""
    monkeypatch.delenv("COVALENT_TPU_JOURNAL_DIR", raising=False)
    journal_mod.reset()

    async def flow():
        ex = make_serve_executor(tmp_path)
        try:
            return await ex.recover()
        finally:
            await ex.close()

    report = run_async(flow())
    assert report["recovered"] is False
    assert report["adopted_sessions"] == []
    assert recovery_mod.last_report() is not None


def test_recover_adopts_orphan_and_resumes_stream_exactly_once(
    tmp_path, run_async, journal_dir
):
    """The full arc: journal → crash → replay → orphan adoption →
    stream resume.  The journaled prefix plus the resumed tail must be
    byte-equal to the uninterrupted stream, with no token repeated."""
    adopted0 = counter_value("covalent_tpu_recovery_adopted_total")
    orphaned0 = counter_value("covalent_tpu_recovery_orphaned_total")

    async def flow():
        # -- incarnation 1: open a session, get a stream mid-flight.
        journal_mod.configure(journal_dir)
        assert journal_mod.epoch() == 1
        ex_a = make_serve_executor(tmp_path)
        # The engine steps when the gate says: two steps reach this
        # incarnation, three more run while nobody listens, and the rest
        # once the successor has resumed the stream.
        gate = tmp_path / "gate"
        allow_steps(gate, 2)
        handle = await open_session(
            ex_a, make_factory(chunk=2, default_cap=30, gate=str(gate)),
            stats_interval_s=0.1,
        )
        sid = handle.sid
        req_a = await handle.request([100], params={"max_new_tokens": 30})
        await stream_reaches(req_a, 4)
        # A journaled session NO worker holds (its worker is long dead):
        # recovery must reap it, not hang on it.
        journal_mod.record(
            "session", sid="ghost", sid_g="serve-ghost.g0",
            address="ghost-host", digest="x", payload="", slots=1,
            sync=True,
        )
        crash_dispatcher(ex_a)
        await orphan_published(tmp_path)
        prefix = list(req_a.tokens)
        allow_steps(gate, 5)

        # -- incarnation 2: fresh journal handle over the same directory
        # replays the dead incarnation's world and bumps the epoch.
        journal_mod.reset()
        journal = journal_mod.configure(journal_dir)
        assert journal.epoch == 2
        assert sid in (journal.recovered.get("sessions") or {})
        ex_b = make_serve_executor(tmp_path)
        try:
            report = await ex_b.recover()
            allow_steps(gate, GATE_OPEN)
            rid = next(
                r for s, r in report.requests if s == sid
            )
            req_b = report.requests[(sid, rid)]
            resumed = await req_b.result(timeout=60)
        finally:
            await ex_b.close()
        return sid, prefix, report, resumed

    sid, prefix, report, resumed = run_async(flow())

    assert report["recovered"] is True
    assert report["epoch"] == 2
    assert sid in report["adopted_sessions"]
    assert "ghost" in report["orphaned_sessions"]
    entry = next(r for r in report["resumed_streams"] if r["sid"] == sid)
    assert entry["state"] == "streaming"
    assert prefix == [101, 102, 103, 104]
    # The journaled high-water mark is exactly what incarnation 1 had
    # delivered — the splice point.
    assert entry["from"] == len(prefix)
    # Exactly-once across the crash: prefix + resumed tail, no overlap,
    # no gap, byte-equal to the uninterrupted stream.
    assert prefix + resumed == [100 + i + 1 for i in range(30)]

    assert counter_value("covalent_tpu_recovery_adopted_total") == adopted0 + 1
    assert counter_value("covalent_tpu_recovery_orphaned_total") >= orphaned0 + 1
    last = recovery_mod.last_report()
    assert last is not None and last["recovered"] is True
    assert last["duration_s"] > 0


def test_recovered_session_serves_new_requests(
    tmp_path, run_async, journal_dir
):
    """A re-adopted session is a first-class citizen: new requests stream
    through it after recovery (the supervisor owns reconnects, stats and
    close exactly as if it had opened the session itself)."""

    async def flow():
        journal_mod.configure(journal_dir)
        ex_a = make_serve_executor(tmp_path)
        gate = tmp_path / "gate"
        allow_steps(gate, 1)  # the crash finds the stream mid-flight
        handle = await open_session(
            ex_a, make_factory(chunk=2, default_cap=6, gate=str(gate)),
            stats_interval_s=0.1,
        )
        sid = handle.sid
        req_a = await handle.request([100], params={"max_new_tokens": 20})
        await stream_reaches(req_a, 2)
        crash_dispatcher(ex_a)
        await orphan_published(tmp_path)

        journal_mod.reset()
        journal_mod.configure(journal_dir)
        ex_b = make_serve_executor(tmp_path)
        try:
            report = await ex_b.recover()
            allow_steps(gate, GATE_OPEN)
            sup = report.supervisors[sid]
            from covalent_tpu_plugin.serving.supervisor import ServeRequest

            fresh = ServeRequest(
                "r-fresh", [500], {"max_new_tokens": 3}, 0.0, ""
            )
            await sup.submit(fresh)
            fresh_tokens = await fresh.result(timeout=30)
            closed = await sup.close()
        finally:
            await ex_b.close()
        return fresh_tokens, closed

    fresh_tokens, closed = run_async(flow())
    assert fresh_tokens == [501, 502, 503]
    assert isinstance(closed, dict)


def make_adapter_factory(gate):
    """A stub engine with the duck-typed multi-adapter surface
    (attach/detach/adapter_digests), cloudpickled BY VALUE: adapter
    ``name`` offsets the deterministic stream by the first value of its
    bundle leaves, so a resumed adapter-routed splice is byte-checkable
    and a stream decoded WITHOUT the adapter is visibly different.  It
    steps as ``gate`` allows (``test_serving.allow_steps``)."""

    may_step = step_gate(gate)

    def factory():
        import numpy as np_mod

        class Engine:
            def __init__(self):
                self.slots = 2
                self.lanes = {}
                self.book = {}
                self.stats = {}

            def attach_adapter(self, name, payload):
                leaves = payload["leaves"]
                self.book[name] = (
                    str(payload["digest"]),
                    int(np_mod.asarray(leaves[0]).ravel()[0]),
                )
                self.stats.setdefault(f"adapter_tokens_{name}", 0)
                return payload["digest"]

            def detach_adapter(self, name):
                if name not in self.book:
                    raise ValueError(f"unknown adapter {name!r}")
                del self.book[name]

            @property
            def adapter_digests(self):
                return {n: d for n, (d, _) in self.book.items()}

            def admit(self, rid, prompt, params):
                name = str((params or {}).get("adapter") or "")
                offset = 0
                if name:
                    if name not in self.book:
                        err = ValueError(f"unknown adapter {name!r}")
                        err.fault_label = "serve_adapter_unknown"
                        err.fault_transient = False
                        raise err
                    offset = self.book[name][1]
                cap = int((params or {}).get("max_new_tokens", 6))
                base = int(prompt[-1]) + offset
                self.lanes[rid] = [base + i + 1 for i in range(cap)]
                if name:
                    self.stats[f"adapter_tokens_{name}"] += cap

            def step(self):
                if self.lanes and not may_step():
                    return []
                events = []
                for rid in list(self.lanes):
                    taken = self.lanes[rid][:2]
                    self.lanes[rid] = self.lanes[rid][2:]
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


def test_recover_reattaches_adapters_and_resumes_byte_equal(
    tmp_path, run_async, journal_dir
):
    """SIGKILL the dispatcher with TWO adapters attached and an
    adapter-routed stream mid-flight: ``recover()`` must restore both
    names into the successor supervisor's book from the journaled
    registry records (resident fast path — the surviving worker still
    holds them — or a full re-attach from the CAS path), the resumed
    stream must splice byte-equal on the ADAPTER's weights, and fresh
    adapter-routed requests must route through the recovered session."""
    import numpy as np

    async def flow():
        journal_mod.configure(journal_dir)
        ex_a = make_serve_executor(tmp_path)
        gate = tmp_path / "gate"
        allow_steps(gate, 2)
        handle = await open_session(
            ex_a, make_adapter_factory(str(gate)), stats_interval_s=0.1,
        )
        sid = handle.sid
        for name, offset in (("fr", 1000), ("de", 2000)):
            ack = await handle.attach_adapter(
                name, payload=[np.full((2, 2), offset, dtype=np.float32)]
            )
            assert ack.get("digest"), ack
        assert set(handle.adapters) == {"fr", "de"}
        req_a = await handle.request(
            [100], params={"max_new_tokens": 30, "adapter": "fr"}
        )
        await stream_reaches(req_a, 4)
        crash_dispatcher(ex_a)
        await orphan_published(tmp_path)
        prefix = list(req_a.tokens)
        allow_steps(gate, 5)

        journal_mod.reset()
        journal = journal_mod.configure(journal_dir)
        meta = (journal.recovered.get("sessions") or {}).get(sid) or {}
        journaled = set((meta.get("adapters") or {}))
        ex_b = make_serve_executor(tmp_path)
        try:
            report = await ex_b.recover()
            allow_steps(gate, GATE_OPEN)
            sup = report.supervisors[sid]
            recovered_book = dict(sup.adapters)
            rid = next(r for s, r in report.requests if s == sid)
            resumed = await report.requests[(sid, rid)].result(timeout=60)
            from covalent_tpu_plugin.serving.supervisor import (
                ServeRequest,
            )

            fresh = ServeRequest(
                "r-de", [5], {"max_new_tokens": 4, "adapter": "de"},
                0.0, "",
            )
            await sup.submit(fresh)
            fresh_tokens = await fresh.result(timeout=30)
            await sup.close()
        finally:
            await ex_b.close()
        return (sid, journaled, prefix, resumed, report,
                recovered_book, fresh_tokens)

    (sid, journaled, prefix, resumed, report, recovered_book,
     fresh_tokens) = run_async(flow())

    # Both attachments were journaled sync and survived the crash.
    assert journaled == {"fr", "de"}
    assert set(recovered_book) == {"fr", "de"}
    states = {
        entry["adapter"]: entry["state"]
        for entry in report["reattached_adapters"]
        if entry["sid"] == sid
    }
    assert set(states) == {"fr", "de"}
    assert set(states.values()) <= {"resident", "attached"}
    # Exactly-once across the crash ON THE ADAPTER'S WEIGHTS: prefix +
    # resumed tail equals the uninterrupted adapter-offset stream.
    assert prefix + resumed == [100 + 1000 + i + 1 for i in range(30)]
    # The re-attached book serves fresh adapter-routed traffic.
    assert fresh_tokens == [5 + 2000 + i + 1 for i in range(4)]
