"""Binary agent-channel frames: codec, negotiation, batching, fuzz.

The frame layer has three byte-compatible implementations — the
dispatcher (``transport/frames.py``), the standalone worker harness
(``harness.py``), and the native C++ agent (``native/agent.cc``, covered
in ``test_agent.py``).  This module cross-checks the first two against
each other, drives the negotiated fast path end to end (raw-pickle
invoke/result frames, multi-invoke batching, token coalescing), proves
the JSONL fallback is byte-equal in every direction the handshake can
degrade, and fuzzes the pool server's frame parser: malformed input must
fail loud as clean errors — permanent where torn — and never hang or
kill the resident runtime.
"""

import asyncio
import io
import json
import sys

import cloudpickle
import pytest

from covalent_tpu_plugin import TPUExecutor
from covalent_tpu_plugin import harness as harness_mod
from covalent_tpu_plugin.agent import start_pool_server
from covalent_tpu_plugin.cache import bytes_digest
from covalent_tpu_plugin.obs.metrics import REGISTRY
from covalent_tpu_plugin.resilience import FaultClass, classify_error
from covalent_tpu_plugin.transport import LocalTransport, frames

from .helpers import pin_cpu_task_env


def counter_value(name: str, **labels) -> float:
    metric = REGISTRY.get(name)
    if metric is None:
        return 0.0
    total = 0.0
    for series_labels, counter in metric._series():
        if all(series_labels.get(k) == v for k, v in labels.items()):
            total += counter.value
    return total


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


def _make_square():
    def square(x):
        return x * x

    return square


def stage_payload(tmp_path, obj):
    payload = cloudpickle.dumps(obj)
    digest = bytes_digest(payload)
    path = tmp_path / f"{digest}.pkl"
    path.write_bytes(payload)
    return payload, digest, str(path)


class _HarnessStdout:
    """Capture harness emissions (text lines AND binary frames) in one
    byte stream, the way the real channel sees them."""

    def __init__(self):
        self.buffer = io.BytesIO()

    def write(self, text):
        self.buffer.write(text.encode())

    def flush(self):
        pass


class _FakeSysModule:
    """``sys`` stand-in for the harness module: a private stdout, the real
    module for everything else (pytest's capture plugin re-swaps the real
    ``sys.stdout`` between fixture setup and the test call, so patching
    the interpreter-wide attribute is unreliable)."""

    def __init__(self, fake_stdout):
        self.stdout = fake_stdout

    def __getattr__(self, name):
        return getattr(sys, name)


@pytest.fixture()
def harness_stdout(monkeypatch):
    fake = _HarnessStdout()
    monkeypatch.setattr(harness_mod, "sys", _FakeSysModule(fake))
    return fake


# ---------------------------------------------------------------------------
# Codec cross-compatibility: dispatcher encoder <-> harness parser and back.
# ---------------------------------------------------------------------------


def test_dispatcher_frame_parses_on_harness_side(harness_stdout):
    body = b"\x00\x01raw pickle bytes\xff" * 10
    wire = frames.encode_frame(
        frames.VERB_INVOKE,
        {"cmd": "invoke", "id": "op1", "digest": "d" * 64,
         "_body": "args_bytes"},
        body,
    )
    buf = bytearray(wire)
    commands = harness_mod._extract_commands(buf)
    assert len(commands) == 1 and not buf
    assert commands[0]["cmd"] == "invoke"
    assert commands[0]["args_bytes"] == body
    assert harness_stdout.buffer.getvalue() == b""  # no error emitted


def test_dispatcher_compressed_frame_parses_on_harness_side(harness_stdout):
    body = b"compressible " * 4096
    wire = frames.encode_frame(
        frames.VERB_INVOKE,
        {"cmd": "invoke", "id": "op1", "_body": "args_bytes"},
        body,
        codec="zlib",
    )
    assert len(wire) < len(body)  # compression actually engaged
    flags = wire[4]
    assert flags & frames.FLAG_BODY_ZLIB
    commands = harness_mod._extract_commands(bytearray(wire))
    assert commands[0]["args_bytes"] == body


def test_harness_frame_parses_on_dispatcher_side(harness_stdout, monkeypatch):
    monkeypatch.setitem(harness_mod._FRAMES, "out", True)
    monkeypatch.setitem(harness_mod._FRAMES, "codec", "zlib")
    body = b"result pickle " * 2048
    harness_mod._emit_frame(
        harness_mod._VERB_RESULT,
        {"event": "result", "id": "op1", "ok": True, "_body": "data_bytes"},
        body,
    )
    wire = harness_stdout.buffer.getvalue()
    magic, version, verb, flags, hlen, blen = frames.HEADER.unpack(
        wire[:frames.HEADER_LEN]
    )
    assert magic == frames.MAGIC and version == frames.VERSION
    assert verb == frames.VERB_RESULT
    header = wire[frames.HEADER_LEN:frames.HEADER_LEN + hlen]
    payload = wire[frames.HEADER_LEN + hlen:frames.HEADER_LEN + hlen + blen]
    event = frames.decode_payload(flags, header, payload)
    assert event["event"] == "result" and event["ok"] is True
    assert event["data_bytes"] == body


def test_frames_and_lines_interleave(harness_stdout):
    wire = (
        json.dumps({"cmd": "ping"}).encode() + b"\n"
        + frames.encode_frame(
            frames.VERB_SERVE, {"cmd": "serve_request", "id": "s1"}
        )
        + json.dumps({"cmd": "shutdown"}).encode() + b"\n"
    )
    commands = harness_mod._extract_commands(bytearray(wire))
    assert [c.get("cmd") for c in commands] == [
        "ping", "serve_request", "shutdown"
    ]


def test_torn_compressed_body_fails_permanent():
    with pytest.raises(frames.FrameIntegrityError):
        frames.decode_payload(
            frames.FLAG_BODY_ZLIB, b'{"event":"result"}', b"not deflate"
        )
    fault, _ = classify_error(frames.FrameIntegrityError("torn"))
    assert fault is FaultClass.PERMANENT


def test_oversized_encode_refused():
    with pytest.raises(frames.FrameError):
        frames.encode_frame(
            frames.VERB_CMD, {"cmd": "x"},
            b"\x00" * (frames.MAX_BODY_BYTES + 1),
        )


# ---------------------------------------------------------------------------
# Harness parser fuzz (in process): clean errors, resync, no hangs.
# ---------------------------------------------------------------------------


def _emitted_errors(harness_stdout):
    return [
        json.loads(line)
        for line in harness_stdout.buffer.getvalue().decode().splitlines()
        if line.strip()
    ]


def test_parser_bad_magic_resyncs_at_newline(harness_stdout):
    buf = bytearray(
        bytes([frames.MAGIC[0], 0x00]) + b"garbage-without-meaning\n"
        + json.dumps({"cmd": "ping"}).encode() + b"\n"
    )
    commands = harness_mod._extract_commands(buf)
    assert [c.get("cmd") for c in commands] == ["ping"]
    errors = _emitted_errors(harness_stdout)
    assert errors and errors[0]["code"] == "bad_frame"


def test_parser_bad_version_resyncs(harness_stdout):
    frame = bytearray(frames.encode_frame(frames.VERB_CMD, {"cmd": "ping"}))
    frame[2] = 99  # corrupt the version byte
    buf = bytearray(bytes(frame) + b"\n" + b'{"cmd":"ping"}\n')
    commands = harness_mod._extract_commands(buf)
    assert [c.get("cmd") for c in commands] == ["ping"]
    assert _emitted_errors(harness_stdout)[0]["code"] == "bad_frame"


def test_parser_oversized_length_refused(harness_stdout):
    header = frames.HEADER.pack(
        frames.MAGIC, frames.VERSION, 0, 0, 5, frames.MAX_BODY_BYTES + 1
    )
    buf = bytearray(header + b"\n" + b'{"cmd":"ping"}\n')
    commands = harness_mod._extract_commands(buf)
    assert [c.get("cmd") for c in commands] == ["ping"]
    assert "oversized" in _emitted_errors(harness_stdout)[0]["message"]


def test_parser_non_json_header_consumes_frame_in_sync(harness_stdout):
    bad = frames.HEADER.pack(frames.MAGIC, frames.VERSION, 0, 0, 7, 3)
    buf = bytearray(
        bad + b"not-js!" + b"\x01\x02\x03"
        + frames.encode_frame(frames.VERB_CMD, {"cmd": "ping"})
    )
    commands = harness_mod._extract_commands(buf)
    # The bad-header frame is length-consumable, so the NEXT frame (no
    # newline between them) still parses — sync was never lost.
    assert [c.get("cmd") for c in commands] == ["ping"]
    assert _emitted_errors(harness_stdout)[0]["code"] == "bad_frame"


def test_parser_torn_zlib_body_is_permanent_error(harness_stdout):
    head = json.dumps(
        {"cmd": "invoke", "id": "tornop", "_body": "args_bytes"}
    ).encode()
    body = b"definitely not deflate data"
    wire = frames.HEADER.pack(
        frames.MAGIC, frames.VERSION, frames.VERB_INVOKE,
        frames.FLAG_BODY_ZLIB, len(head), len(body),
    ) + head + body
    commands = harness_mod._extract_commands(bytearray(wire))
    assert commands == []
    errors = _emitted_errors(harness_stdout)
    assert errors[0]["code"] == "bad_frame"
    assert errors[0]["permanent"] is True
    assert errors[0]["id"] == "tornop"


def test_parser_torn_multi_invoke_body_fans_error_to_every_op(
    harness_stdout,
):
    """A torn batched frame must refuse EVERY waiting op id permanently —
    the ids live in ops, not at the header top level, and an id-less
    error is log-only on the client (each op would sit out its started
    timeout and burn a transient retry on deterministic corruption)."""
    head = json.dumps({
        "cmd": "multi_invoke", "digest": "d" * 64,
        "ops": [{"id": "mop1"}, {"id": "mop2"}, {"id": "mop3"}],
        "args_lens": [3, 3, 3], "_body": "args_bytes",
    }).encode()
    body = b"definitely not deflate"
    wire = frames.HEADER.pack(
        frames.MAGIC, frames.VERSION, frames.VERB_MULTI_INVOKE,
        frames.FLAG_BODY_ZLIB, len(head), len(body),
    ) + head + body
    assert harness_mod._extract_commands(bytearray(wire)) == []
    errors = _emitted_errors(harness_stdout)
    assert [e["id"] for e in errors] == ["mop1", "mop2", "mop3"]
    assert all(
        e["code"] == "bad_frame" and e["permanent"] is True for e in errors
    )


def test_parser_partial_frame_waits_for_more_bytes(harness_stdout):
    wire = frames.encode_frame(
        frames.VERB_INVOKE, {"cmd": "invoke", "id": "op",
                             "_body": "args_bytes"}, b"x" * 100,
    )
    buf = bytearray(wire[:40])  # mid-frame: channel death leaves this
    assert harness_mod._extract_commands(buf) == []
    assert len(buf) == 40  # retained, not misparsed
    buf.extend(wire[40:])
    commands = harness_mod._extract_commands(buf)
    assert commands[0]["id"] == "op" and commands[0]["args_bytes"] == b"x" * 100


# ---------------------------------------------------------------------------
# Live pool-server fuzz over a real channel: the runtime must survive.
# ---------------------------------------------------------------------------


async def _pool_client(tmp_path, frames_enabled=None):
    conn = LocalTransport()
    return await start_pool_server(
        conn, str(tmp_path / "remote"), sys.executable,
        frames_enabled=frames_enabled,
    )


def test_pool_server_survives_frame_garbage(tmp_path, run_async):
    async def flow():
        client = await _pool_client(tmp_path)
        try:
            assert client.frames_active
            garbage = [
                bytes([frames.MAGIC[0], 0x11]) + b"junk\n",
                b"\xc5"  # lone magic byte then a newline-terminated mess
                + b"\x00" * 7 + b"\n",
                frames.HEADER.pack(
                    frames.MAGIC, 42, 0, 0, 1, 1
                ) + b"\n",  # bad version
                frames.HEADER.pack(
                    frames.MAGIC, frames.VERSION, 0, 0,
                    frames.MAX_HEADER_BYTES + 1, 0,
                ) + b"\n",  # oversized header length
                b"plain text that is not json\n",
            ]
            for chunk in garbage:
                await client._process.write_bytes(chunk)
                # The runtime must still answer commands after every
                # injection — fail loud, keep serving.
                await client.ping(10.0)
            return True
        finally:
            await client.close()

    assert run_async(flow()) is True


def test_pool_server_torn_invoke_body_rejected_permanent(
    tmp_path, run_async
):
    async def flow():
        client = await _pool_client(tmp_path)
        try:
            assert client.frames_active
            head = json.dumps({
                "cmd": "invoke", "id": "tornop", "digest": "d" * 64,
                "_body": "args_bytes",
            }).encode()
            body = b"garbage, not zlib"
            await client._process.write_bytes(
                frames.HEADER.pack(
                    frames.MAGIC, frames.VERSION, frames.VERB_INVOKE,
                    frames.FLAG_BODY_ZLIB, len(head), len(body),
                ) + head + body
            )
            await client._wait(
                lambda c: c._error_codes.get("tornop"), 15.0
            )
            rejection = client._pop_rejection("tornop", "invoke")
            fault, label = classify_error(rejection)
            await client.ping(10.0)  # runtime alive after the refusal
            return fault, label
        finally:
            await client.close()

    fault, label = run_async(flow())
    assert fault is FaultClass.PERMANENT
    assert label == "agent_bad_frame"


def test_pool_server_mid_frame_channel_death_exits_clean(
    tmp_path, run_async
):
    async def flow():
        client = await _pool_client(tmp_path)
        assert client.frames_active
        wire = frames.encode_frame(
            frames.VERB_INVOKE,
            {"cmd": "invoke", "id": "op", "_body": "args_bytes"},
            b"y" * 4096,
        )
        await client._process.write_bytes(wire[: len(wire) // 2])
        await client.close()  # EOF with half a frame buffered remotely
        return client._process.returncode

    assert run_async(flow()) == 0


# ---------------------------------------------------------------------------
# Negotiation and fallback: every degrade path is byte-equal.
# ---------------------------------------------------------------------------


def test_json_only_runtime_degrades_to_jsonl(tmp_path, run_async, monkeypatch):
    """Binary-capable client, frames-disabled runtime: silent banner, JSONL
    fallback, identical results."""
    monkeypatch.setenv("COVALENT_TPU_AGENT_FRAMES", "0")

    async def flow():
        client = await _pool_client(tmp_path, frames_enabled=True)
        try:
            assert not client.frames_active
            assert "frames" not in client._banner
            payload, digest, path = stage_payload(tmp_path, _make_square())
            await client.register_fn(digest, path)
            await client.invoke(
                "op1", digest, path=path,
                args_bytes=cloudpickle.dumps(((7,), {})),
            )
            event = await client.wait_result("op1", timeout=30.0)
            return cloudpickle.loads(
                __import__("base64").b64decode(event["data"])
            )
        finally:
            await client.close()

    result, exception = run_async(flow())
    assert exception is None and result == 49


def test_client_kill_switch_declines_capable_runtime(tmp_path, run_async):
    async def flow():
        client = await _pool_client(tmp_path, frames_enabled=False)
        try:
            assert not client.frames_active
            # The runtime DID advertise — the client declined.
            assert client._banner.get("frames") == 1
            payload, digest, path = stage_payload(tmp_path, _make_square())
            await client.register_fn(digest, path)
            await client.invoke(
                "op1", digest, path=path,
                args_bytes=cloudpickle.dumps(((8,), {})),
            )
            event = await client.wait_result("op1", timeout=30.0)
            return event.get("data_bytes"), event.get("data")
        finally:
            await client.close()

    data_bytes, data_b64 = run_async(flow())
    assert data_bytes is None  # result rode the JSONL fallback
    result, exception = cloudpickle.loads(
        __import__("base64").b64decode(data_b64)
    )
    assert exception is None and result == 64


def test_e2e_binary_and_jsonl_results_byte_equal(tmp_path, run_async):
    """The same electrons through a frames channel and a JSONL channel must
    produce byte-identical result pickles — and the binary arm must have
    actually used frames (no silent fallback can pass this), the JSONL
    arm none, and the frames must be the fewer bytes on the channel for
    the same inline arguments (base64 alone is a third more)."""
    text = "".join(chr(33 + i % 90) for i in range(16 * 1024))

    def framed_invokes():
        return sum(
            counter_value(
                "covalent_tpu_agent_frames_total",
                verb=verb, encoding="binary",
            )
            for verb in ("invoke", "multi_invoke")
        )

    def measure(value):
        return len(value), value[:8]

    async def run_arm(tag, agent_frames):
        ex = make_rpc_executor(tmp_path / tag, agent_frames=agent_frames)
        try:
            framed0 = framed_invokes()
            square = await ex.run(
                _make_square(), [123], {},
                {"dispatch_id": f"fr{tag}", "node_id": 0},
            )
            assert ex.last_dispatch_mode == "rpc"
            wire0 = counter_value("covalent_tpu_agent_wire_bytes_total")
            measured = await ex.run(
                measure, [text], {},
                {"dispatch_id": f"fr{tag}", "node_id": 1},
            )
            assert ex.last_dispatch_mode == "rpc"
            wire = counter_value("covalent_tpu_agent_wire_bytes_total") - wire0
            return square, measured, framed_invokes() - framed0, wire
        finally:
            await ex.close()

    async def flow():
        return await run_arm("bin", True), await run_arm("jsonl", False)

    binary, jsonl = run_async(flow())
    assert binary[0] == jsonl[0] == 123 * 123
    assert binary[1] == jsonl[1] == (len(text), text[:8])
    assert cloudpickle.dumps(binary[:2]) == cloudpickle.dumps(jsonl[:2])
    assert binary[2] >= 2 and jsonl[2] == 0
    assert len(text) < binary[3] < jsonl[3], (binary[3], jsonl[3])


def test_chaos_transport_faults_apply_to_framed_channel(tmp_path, run_async):
    """ChaosTransport's injected latency/faults gate the framed channel's
    start_process exactly like the JSONL one; results stay correct."""
    from covalent_tpu_plugin.transport import ChaosPlan

    async def flow():
        ex = make_rpc_executor(
            tmp_path, dispatch_mode="rpc", chaos=ChaosPlan(delay=0.01),
            agent_frames=True,
        )
        try:
            return await ex.run(
                _make_square(), [11], {},
                {"dispatch_id": "frchaos", "node_id": 0},
            )
        finally:
            await ex.close()

    assert run_async(flow()) == 121


# ---------------------------------------------------------------------------
# Batched invoke: same-turn invokes for one digest ship as ONE frame.
# ---------------------------------------------------------------------------


def test_concurrent_invokes_coalesce_into_multi_invoke(tmp_path, run_async):
    async def flow():
        client = await _pool_client(tmp_path)
        try:
            assert client.frames_active and client.mode == "pool"
            payload, digest, path = stage_payload(tmp_path, _make_square())
            await client.register_fn(digest, path)
            before = counter_value(
                "covalent_tpu_agent_frames_total",
                verb="multi_invoke", encoding="binary",
            )
            ids = [f"batch{i}" for i in range(4)]
            await asyncio.gather(*(
                client.invoke(
                    tid, digest, path=path,
                    args_bytes=cloudpickle.dumps(((i,), {})),
                )
                for i, tid in enumerate(ids)
            ))
            results = {}
            for tid in ids:
                event = await client.wait_result(tid, timeout=30.0)
                value, exception = cloudpickle.loads(event["data_bytes"])
                assert exception is None
                results[tid] = value
            after = counter_value(
                "covalent_tpu_agent_frames_total",
                verb="multi_invoke", encoding="binary",
            )
            return results, after - before
        finally:
            await client.close()

    results, multi_frames = run_async(flow())
    assert results == {f"batch{i}": i * i for i in range(4)}
    # All four invokes left in the same event-loop turn: one frame.
    assert multi_frames >= 1


def test_full_batch_flushes_without_waiting_out_the_window(
    tmp_path, run_async, monkeypatch
):
    """Hitting COVALENT_TPU_RPC_BATCH_MAX must ship the batch NOW — a
    wide window bounds how long a lone invoke may wait, never how fast a
    full batch goes out."""
    import time as time_mod

    from covalent_tpu_plugin import agent as agent_mod

    monkeypatch.setattr(agent_mod, "_BATCH_WINDOW_S", 0.8)
    monkeypatch.setattr(agent_mod, "_BATCH_MAX_OPS", 2)

    async def flow():
        client = await _pool_client(tmp_path)
        try:
            payload, digest, path = stage_payload(tmp_path, _make_square())
            await client.register_fn(digest, path)
            t0 = time_mod.perf_counter()
            await asyncio.gather(*(
                client.invoke(
                    f"full{i}", digest, path=path,
                    args_bytes=cloudpickle.dumps(((i,), {})),
                )
                for i in range(4)
            ))
            results = []
            for i in range(4):
                event = await client.wait_result(f"full{i}", timeout=30.0)
                value, exception = cloudpickle.loads(event["data_bytes"])
                assert exception is None
                results.append(value)
            return results, time_mod.perf_counter() - t0
        finally:
            await client.close()

    results, elapsed = run_async(flow())
    assert results == [0, 1, 4, 9]
    assert elapsed < 0.6, (
        f"full batch waited out the {0.8}s window ({elapsed:.2f}s)"
    )


def test_sequential_invokes_do_not_batch_or_stall(tmp_path, run_async):
    async def flow():
        client = await _pool_client(tmp_path)
        try:
            payload, digest, path = stage_payload(tmp_path, _make_square())
            await client.register_fn(digest, path)
            out = []
            for i in range(3):
                await client.invoke(
                    f"seq{i}", digest, path=path,
                    args_bytes=cloudpickle.dumps(((i,), {})),
                )
                event = await client.wait_result(f"seq{i}", timeout=30.0)
                value, exception = cloudpickle.loads(event["data_bytes"])
                assert exception is None
                out.append(value)
            return out
        finally:
            await client.close()

    assert run_async(flow()) == [0, 1, 4]


# ---------------------------------------------------------------------------
# Token coalescing: serve streams ride batch frames, byte-identically.
# ---------------------------------------------------------------------------


def _serve_factory(chunk=2, default_cap=8, slots=2):
    def factory():
        class Engine:
            def __init__(self):
                self.slots = slots
                self.lanes = {}

            def admit(self, rid, prompt, params):
                cap = int((params or {}).get("max_new_tokens", default_cap))
                base = int(prompt[-1])
                self.lanes[rid] = [base + i + 1 for i in range(cap)]

            def step(self):
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


async def _stream_requests(client, tmp_path, sid, n_requests):
    payload, digest, path = stage_payload(tmp_path, _serve_factory())
    records: list = []
    done_rids: set = set()
    got_all = asyncio.Event()

    def sink(_sid, data):
        records.append(data)
        if data.get("type") == "serve.token" and data.get("done"):
            done_rids.add(data.get("rid"))
            if len(done_rids) >= n_requests:
                got_all.set()

    client.watch_serve(sid, sink)
    await client.serve_open(sid, digest, path, timeout=60.0)
    for i in range(n_requests):
        await client.serve_request(sid, f"r{i}", [i * 10])
    await asyncio.wait_for(got_all.wait(), 60.0)
    await client.serve_close(sid)
    streams: dict = {}
    for record in records:
        if record.get("type") != "serve.token":
            continue
        rid = record["rid"]
        stream = streams.setdefault(rid, [])
        # idx is the cumulative count BEFORE the chunk: exactly-once
        # splice ordering must hold inside and across batch frames.
        assert record["idx"] == len(stream)
        stream.extend(record.get("tokens") or [])
    return streams


def test_serve_tokens_coalesce_and_match_jsonl_streams(
    tmp_path, run_async
):
    async def flow():
        before = counter_value(
            "covalent_tpu_agent_frames_total",
            verb="telemetry_batch", encoding="binary",
        )
        framed_client = await _pool_client(tmp_path / "framed")
        try:
            assert framed_client.frames_active
            framed = await _stream_requests(
                framed_client, tmp_path / "framed", "sid-framed", 3
            )
        finally:
            await framed_client.close()
        batches = counter_value(
            "covalent_tpu_agent_frames_total",
            verb="telemetry_batch", encoding="binary",
        ) - before
        plain_client = await _pool_client(
            tmp_path / "plain", frames_enabled=False
        )
        try:
            plain = await _stream_requests(
                plain_client, tmp_path / "plain", "sid-plain", 3
            )
        finally:
            await plain_client.close()
        return framed, plain, batches

    framed, plain, batches = run_async(flow())
    expected = {
        f"r{i}": [i * 10 + j + 1 for j in range(8)] for i in range(3)
    }
    assert framed == expected
    assert plain == expected
    assert batches >= 1  # coalescing actually engaged on the framed arm
