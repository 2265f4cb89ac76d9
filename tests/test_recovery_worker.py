"""Worker-side crash-recovery protocol: epoch fencing, inventories,
stream resume, and orphan-mode re-adoption.

These tests drive a pool server as a raw subprocess over JSONL pipes —
no AgentClient — because the scenario under test IS the death of that
client: the dispatcher-side pipes are closed mid-stream and the worker
must hold its sessions, publish a rendezvous, and hand the protocol to
whoever adopts it over the unix socket.  The harness file is copied to a
tmp dir first, exactly as the dispatcher stages it into the remote
cache, so the rendezvous artifacts land next to the copy (the contract
`_orphan_dir()` implements), never inside the source tree.
"""

import json
import os
import shutil
import socket
import subprocess
import sys
import threading
import time

import cloudpickle

from covalent_tpu_plugin import harness as harness_mod
from covalent_tpu_plugin.cache import bytes_digest

from .test_serving import GATE_OPEN, allow_steps, make_factory


class Worker:
    """A pool server over raw pipes, with a background JSONL reader."""

    def __init__(self, tmp_path, env=None):
        self.dir = tmp_path / "pool"
        self.dir.mkdir(exist_ok=True)
        self.harness = self.dir / "harness.py"
        shutil.copyfile(harness_mod.__file__, self.harness)
        full_env = dict(os.environ)
        full_env.update({
            "COVALENT_TPU_AGENT_FRAMES": "0",  # JSONL only: asserted shapes
            "COVALENT_TPU_POOL_PRELOAD": "cloudpickle",
            "JAX_PLATFORMS": "cpu",
        })
        full_env.update(env or {})
        self.proc = subprocess.Popen(
            [sys.executable, str(self.harness), "--serve"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, env=full_env,
        )
        self.events: list = []
        self._cond = threading.Condition()
        self._reader = threading.Thread(
            target=self._read, args=(self.proc.stdout,), daemon=True
        )
        self._reader.start()

    def _read(self, stream) -> None:
        try:
            for raw in stream:
                try:
                    event = json.loads(raw)
                except ValueError:
                    continue
                with self._cond:
                    self.events.append(event)
                    self._cond.notify_all()
        except (OSError, ValueError):
            pass  # read end torn down by the test: the "crash"

    def stage(self, factory):
        payload = cloudpickle.dumps(factory)
        digest = bytes_digest(payload)
        path = self.dir / f"{digest}.pkl"
        path.write_bytes(payload)
        return digest, str(path)

    def send(self, **cmd) -> None:
        self.proc.stdin.write((json.dumps(cmd) + "\n").encode())
        self.proc.stdin.flush()

    def wait_for(self, pred, timeout=20.0):
        deadline = time.monotonic() + timeout
        with self._cond:
            while True:
                for event in self.events:
                    if pred(event):
                        return event
                left = deadline - time.monotonic()
                if left <= 0:
                    raise AssertionError(
                        f"no matching event within {timeout}s; saw "
                        f"{[e.get('event') for e in self.events]}"
                    )
                self._cond.wait(left)

    def tokens(self, rid):
        """Exactly-once splice of every serve.token chunk seen for rid."""
        out: list = []
        with self._cond:
            chunks = [
                e["data"] for e in self.events
                if e.get("event") == "telemetry"
                and (e.get("data") or {}).get("type") == "serve.token"
                and e["data"].get("rid") == rid
            ]
        for data in chunks:
            idx = int(data.get("idx") or 0)
            toks = list(data.get("tokens") or [])
            if idx > len(out):
                raise AssertionError(f"token gap for {rid}: idx {idx} > have {len(out)}")
            fresh = toks[len(out) - idx:]
            out.extend(fresh)
        return out

    def crash_dispatcher(self) -> None:
        """Sever both pipes without touching the child: stdout first so
        in-flight emits hit a dead pipe (tokens genuinely lost), then
        stdin EOF to trigger the worker's orphan path."""
        try:
            self.proc.stdout.close()
        except OSError:
            pass
        try:
            self.proc.stdin.close()
        except OSError:
            pass

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self.proc.stdin.close()
            except (OSError, ValueError):
                pass
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()


class SockChannel:
    """JSONL over the adoption unix socket — the successor dispatcher."""

    def __init__(self, path: str):
        self.sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self.sock.settimeout(20.0)
        self.sock.connect(path)
        self._file = self.sock.makefile("rb")
        self.events: list = []
        self._cond = threading.Condition()
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        try:
            for raw in self._file:
                try:
                    event = json.loads(raw)
                except ValueError:
                    continue
                with self._cond:
                    self.events.append(event)
                    self._cond.notify_all()
        except (OSError, ValueError):
            pass

    def send(self, **cmd) -> None:
        self.sock.sendall((json.dumps(cmd) + "\n").encode())

    def wait_for(self, pred, timeout=20.0):
        deadline = time.monotonic() + timeout
        with self._cond:
            while True:
                for event in self.events:
                    if pred(event):
                        return event
                left = deadline - time.monotonic()
                if left <= 0:
                    raise AssertionError(
                        f"no matching socket event within {timeout}s; saw "
                        f"{[e.get('event') for e in self.events]}"
                    )
                self._cond.wait(left)

    def tokens(self, rid, base=0):
        """Splice with absolute idx positions; ``base`` is the resumed
        stream's starting offset (the client-held high-water mark)."""
        out: list = []
        with self._cond:
            chunks = [
                e["data"] for e in self.events
                if e.get("event") == "telemetry"
                and (e.get("data") or {}).get("type") == "serve.token"
                and e["data"].get("rid") == rid
            ]
        for data in chunks:
            idx = int(data.get("idx") or 0) - base
            toks = list(data.get("tokens") or [])
            if idx > len(out):
                raise AssertionError(f"token gap for {rid}: idx {idx} > have {len(out)}")
            out.extend(toks[len(out) - idx:])
        return out

    def close(self) -> None:
        # makefile() dups the fd: both must close for the worker's read
        # end to see EOF.
        for closer in (self.sock.close, self._file.close):
            try:
                closer()
            except OSError:
                pass


def _open_session(worker, sid="s-rec", **factory_kw):
    digest, path = worker.stage(make_factory(**factory_kw))
    worker.send(cmd="serve_open", id=sid, digest=digest, path=path,
                options={"stats_interval_s": 30.0})
    worker.wait_for(
        lambda e: e.get("event") == "serve_opened" and e.get("id") == sid
    )
    return sid


# -- epoch fencing -----------------------------------------------------------


def test_epoch_fencing_refuses_stale_dispatcher(tmp_path):
    worker = Worker(tmp_path)
    try:
        worker.wait_for(lambda e: e.get("event") == "ready")
        worker.send(cmd="epoch", epoch=2)
        worker.wait_for(
            lambda e: e.get("event") == "epoch_ok" and e.get("epoch") == 2
        )

        # A stale dispatcher declares an older epoch: refused outright...
        worker.send(cmd="epoch", epoch=1)
        worker.wait_for(
            lambda e: e.get("event") == "error"
            and e.get("code") == "stale_epoch"
        )
        # ...and every mutating verb on that channel is fenced, each with
        # its caller-shaped refusal.
        worker.send(cmd="serve_open", id="s-x", digest="d", path="p")
        worker.wait_for(
            lambda e: e.get("event") == "serve_error" and e.get("id") == "s-x"
            and e.get("code") == "stale_epoch" and e.get("permanent")
        )
        worker.send(cmd="serve_request", id="s-x", rid="r-x", prompt=[1])
        worker.wait_for(
            lambda e: e.get("event") == "telemetry"
            and (e.get("data") or {}).get("type") == "serve.reject"
            and e["data"].get("code") == "stale_epoch"
        )
        worker.send(cmd="serve_resume", id="s-x", rid="r-x")
        worker.wait_for(
            lambda e: e.get("event") == "serve_resumed"
            and e.get("state") == "refused"
        )
        # Read-only verbs stay live: a stale dispatcher may look, not touch.
        worker.send(cmd="ping")
        worker.wait_for(lambda e: e.get("event") == "pong")
        worker.send(cmd="serve_inventory")
        worker.wait_for(
            lambda e: e.get("event") == "serve_inventory"
            and e.get("epoch") == 2
        )

        # The rightful successor re-declares and the fence lifts.
        worker.send(cmd="epoch", epoch=3)
        worker.wait_for(
            lambda e: e.get("event") == "epoch_ok" and e.get("epoch") == 3
        )
        _open_session(worker, "s-ok")
    finally:
        worker.close()


# -- inventories + resume ----------------------------------------------------


def test_inventory_reports_sessions_and_streams(tmp_path):
    worker = Worker(tmp_path)
    try:
        sid = _open_session(worker, "s-inv", default_cap=4)
        worker.send(cmd="serve_request", id=sid, rid="r-1", prompt=[100])
        worker.wait_for(
            lambda e: e.get("event") == "telemetry"
            and (e.get("data") or {}).get("type") == "serve.token"
            and e["data"].get("rid") == "r-1" and e["data"].get("done")
        )
        worker.send(cmd="serve_inventory")
        inv = worker.wait_for(lambda e: e.get("event") == "serve_inventory")
        assert [s["sid"] for s in inv["sessions"]] == [sid]
        entry = inv["sessions"][0]
        assert entry["finished"]["r-1"]["tokens"] == 4
        assert entry["finished"]["r-1"]["error"] == ""
        assert entry["served"] == 1

        worker.send(cmd="task_inventory")
        tasks = worker.wait_for(lambda e: e.get("event") == "task_inventory")
        assert tasks["tasks"] == []
    finally:
        worker.close()


def test_serve_resume_states(tmp_path):
    worker = Worker(tmp_path)
    try:
        # One step and no more until the gate opens: r-live stays
        # mid-decode and r-queued behind it however slow this test runs.
        gate = tmp_path / "gate"
        allow_steps(gate, 1)
        sid = _open_session(
            worker, "s-res", slots=1, chunk=2, default_cap=20,
            gate=str(gate),
        )
        worker.send(cmd="serve_request", id=sid, rid="r-live", prompt=[0])
        worker.send(cmd="serve_request", id=sid, rid="r-queued", prompt=[50])
        worker.wait_for(
            lambda e: e.get("event") == "telemetry"
            and (e.get("data") or {}).get("type") == "serve.token"
            and e["data"].get("rid") == "r-live"
        )

        # Mid-decode: full history re-emitted from the asked offset.
        worker.send(cmd="serve_resume", id=sid, rid="r-live", **{"from": 0})
        ack = worker.wait_for(
            lambda e: e.get("event") == "serve_resumed"
            and e.get("rid") == "r-live"
        )
        assert ack["state"] == "streaming"
        assert ack["from"] == 0 and ack["sent"] == 2

        # Queued behind the single slot: pending, nothing re-emitted.
        worker.send(cmd="serve_resume", id=sid, rid="r-queued", **{"from": 0})
        assert worker.wait_for(
            lambda e: e.get("event") == "serve_resumed"
            and e.get("rid") == "r-queued"
        )["state"] == "pending"

        # Never submitted here: unknown — the dispatcher re-sends in full.
        worker.send(cmd="serve_resume", id=sid, rid="r-ghost", **{"from": 0})
        assert worker.wait_for(
            lambda e: e.get("event") == "serve_resumed"
            and e.get("rid") == "r-ghost"
        )["state"] == "unknown"

        # Unknown session id entirely.
        worker.send(cmd="serve_resume", id="s-ghost", rid="r-1", **{"from": 0})
        assert worker.wait_for(
            lambda e: e.get("event") == "serve_resumed"
            and e.get("id") == "s-ghost"
        )["state"] == "unknown"

        # Drain both, then resume a FINISHED stream from an offset: the
        # bounded finished-ring re-emits the tail plus the done marker.
        allow_steps(gate, GATE_OPEN)
        worker.wait_for(
            lambda e: e.get("event") == "telemetry"
            and (e.get("data") or {}).get("type") == "serve.token"
            and e["data"].get("rid") == "r-queued" and e["data"].get("done"),
            timeout=40.0,
        )
        assert worker.tokens("r-live") == list(range(1, 21))
        worker.send(cmd="serve_resume", id=sid, rid="r-live", **{"from": 18})
        done_ack = worker.wait_for(
            lambda e: e.get("event") == "serve_resumed"
            and e.get("rid") == "r-live" and e.get("state") == "done"
        )
        assert done_ack["from"] == 18 and done_ack["sent"] == 2
    finally:
        worker.close()


# -- orphan mode + re-adoption ----------------------------------------------


def _wait_rendezvous(worker, timeout=20.0):
    path = worker.dir / "pool_orphan.json"
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if path.exists():
            try:
                return json.loads(path.read_text())
            except ValueError:
                pass  # mid-replace
        if worker.proc.poll() is not None:
            raise AssertionError("worker exited instead of orphaning")
        time.sleep(0.05)
    raise AssertionError("orphan rendezvous never published")


def test_orphan_adoption_resumes_streams_exactly_once(tmp_path):
    worker = Worker(tmp_path, env={"COVALENT_TPU_ORPHAN_TTL_S": "60"})
    try:
        worker.wait_for(lambda e: e.get("event") == "ready")
        worker.send(cmd="epoch", epoch=5)
        worker.wait_for(lambda e: e.get("event") == "epoch_ok")
        sid = _open_session(
            worker, "s-adopt", step_delay=0.1, chunk=2, default_cap=40
        )
        worker.send(cmd="serve_request", id=sid, rid="r-a", prompt=[1000])
        worker.wait_for(
            lambda e: e.get("event") == "telemetry"
            and (e.get("data") or {}).get("type") == "serve.token"
            and e["data"].get("rid") == "r-a"
        )
        hwm = len(worker.tokens("r-a"))
        assert hwm >= 2

        # The dispatcher dies mid-stream.  Tokens emitted from here land
        # in the dead pipe and are gone — only the worker's history and
        # our high-water mark survive.
        worker.crash_dispatcher()
        meta = _wait_rendezvous(worker)
        assert meta["pid"] == worker.proc.pid
        assert meta["epoch"] == 5
        assert meta["sessions"] == [sid]

        # A stale successor (older epoch) is refused and the worker keeps
        # waiting for the rightful one.
        stale = SockChannel(meta["sock"])
        stale.send(cmd="adopt", epoch=4)
        stale.wait_for(
            lambda e: e.get("event") == "error"
            and e.get("code") == "stale_epoch"
        )
        stale.close()

        # The real successor adopts: fresh banner, session roster intact.
        chan = SockChannel(meta["sock"])
        chan.send(cmd="adopt", epoch=6)
        banner = chan.wait_for(lambda e: e.get("event") == "ready")
        assert banner.get("reattach") is True
        assert banner.get("epoch") == 6
        assert banner.get("sessions") == [sid]
        # Rendezvous artifacts are cleaned up once adopted.
        deadline = time.monotonic() + 10
        while (worker.dir / "pool_orphan.json").exists():
            assert time.monotonic() < deadline
            time.sleep(0.05)

        # Resume from OUR high-water mark: worker re-emits history[hwm:]
        # and live chunks follow — splice must come out byte-equal.
        chan.send(cmd="serve_resume", id=sid, rid="r-a", **{"from": hwm})
        ack = chan.wait_for(
            lambda e: e.get("event") == "serve_resumed"
            and e.get("rid") == "r-a"
        )
        assert ack["state"] in ("streaming", "done")
        chan.wait_for(
            lambda e: e.get("event") == "telemetry"
            and (e.get("data") or {}).get("type") == "serve.token"
            and e["data"].get("rid") == "r-a" and e["data"].get("done"),
            timeout=40.0,
        )
        resumed = chan.tokens("r-a", base=hwm)
        assert [t for t in range(1001, 1001 + hwm)] + resumed == list(
            range(1001, 1041)
        )

        # New traffic flows on the adopted channel too.
        chan.send(cmd="serve_request", id=sid, rid="r-b", prompt=[2000],
                  params={"max_new_tokens": 4})
        chan.wait_for(
            lambda e: e.get("event") == "telemetry"
            and (e.get("data") or {}).get("type") == "serve.token"
            and e["data"].get("rid") == "r-b" and e["data"].get("done"),
            timeout=40.0,
        )
        assert chan.tokens("r-b") == [2001, 2002, 2003, 2004]

        chan.send(cmd="serve_close", id=sid)
        chan.wait_for(
            lambda e: e.get("event") == "serve_closed" and e.get("id") == sid
        )
        chan.close()
        worker.proc.wait(timeout=15)
    finally:
        worker.close()


def test_orphan_ttl_expiry_drains_and_exits(tmp_path):
    """Satellite: the never-returning dispatcher.  A worker must not leak
    forever — after the grace TTL it drains its sessions and exits."""
    worker = Worker(tmp_path, env={"COVALENT_TPU_ORPHAN_TTL_S": "1"})
    try:
        sid = _open_session(worker, "s-ttl", default_cap=2)
        worker.send(cmd="serve_request", id=sid, rid="r-1", prompt=[1])
        worker.wait_for(
            lambda e: e.get("event") == "telemetry"
            and (e.get("data") or {}).get("type") == "serve.token"
            and e["data"].get("done")
        )
        worker.crash_dispatcher()
        _wait_rendezvous(worker)
        worker.proc.wait(timeout=20)  # nobody adopts: drain + exit
        assert not (worker.dir / "pool_orphan.json").exists()
        assert not list(worker.dir.glob("pool_orphan.*.sock"))
    finally:
        worker.close()


def test_no_ttl_means_no_orphan_mode(tmp_path):
    """Without the knob the historical contract holds: sessions die with
    the channel and the server exits promptly."""
    worker = Worker(tmp_path)
    try:
        _open_session(worker, "s-plain", default_cap=2)
        worker.crash_dispatcher()
        worker.proc.wait(timeout=15)
        assert not (worker.dir / "pool_orphan.json").exists()
    finally:
        worker.close()
