"""Dispatch fast-path tests: wire codecs, bundled staging, pipelined
dispatch, and DAG-driven connection prewarm (ISSUE 5).

The codec/bundle layer is exercised three ways: against the LocalTransport
override (direct-fs fast path), against the *generic* base-class
implementation (via a no-fault ChaosTransport wrapper, whose put/run ride
the real local shell — the same code path SSH/minissh use), and against a
truncating chaos wrapper to prove a torn bundle is a clean PERMANENT
integrity error, not a retry storm.
"""

import asyncio
import os
import sys

import pytest

from covalent_tpu_plugin.cache import CASIndex, file_digest
from covalent_tpu_plugin.obs.metrics import REGISTRY
from covalent_tpu_plugin.resilience import FaultClass, classify_error
from covalent_tpu_plugin.transport import (
    ChaosPlan,
    ChaosTransport,
    CodecIntegrityError,
    LocalTransport,
)
from covalent_tpu_plugin.transport import codec as codec_mod

from .helpers import (
    FakeTransport,
    make_local_executor,
    scripted_ok_responses,
)

#: ~8 KiB of structured, highly-compressible text (a realistic spec/manifest
#: payload shape) — comfortably above MIN_COMPRESS_BYTES.
COMPRESSIBLE = (
    '{"worker": 0, "env": {"JAX_PLATFORMS": "tpu"}, "path": '
    '"/workdir/covalent-tpu/artifacts/"}\n'
) * 80


def counter_value(counter, **labels) -> float:
    child = counter.labels(**labels) if labels else counter
    return child.value


def compressed_wire_bytes(direction: str) -> float:
    """Bytes that crossed the wire under any codec but raw: which codec a
    run negotiates depends on what is installed on both ends."""
    return sum(
        counter_value(codec_mod.WIRE_BYTES_TOTAL, direction=direction, codec=name)
        for name in codec_mod.available_codecs()
    )


def write(tmp_path, name: str, content: str) -> str:
    path = tmp_path / name
    path.write_text(content)
    return str(path)


# --------------------------------------------------------------------- #
# Codec primitives + negotiation
# --------------------------------------------------------------------- #


def test_codec_zlib_roundtrip():
    codec = codec_mod.get_codec("zlib")
    data = COMPRESSIBLE.encode()
    packed = codec.compress(data)
    assert len(packed) < len(data)
    assert codec.decompress(packed) == data


def test_pick_codec_intersects_with_raw_fallback():
    assert codec_mod.pick_codec(["zlib"]).name == "zlib"
    assert codec_mod.pick_codec([]) is None
    assert codec_mod.pick_codec(["lz4"]) is None  # unknown remote offer
    assert "zlib" in codec_mod.available_codecs()


def test_probe_clause_parse_and_garbled_fallback():
    clause = codec_mod.probe_clause(sys.executable)
    assert codec_mod.PROBE_PREFIX in clause
    assert clause.endswith("true)")  # can never fail the pre-flight chain
    assert codec_mod.probe_clause(sys.executable, compress="off") is None
    stdout = f"{codec_mod.PROBE_PREFIX}zlib\n3\n"
    assert codec_mod.parse_probe(stdout) == ["zlib"]
    # Garbled/absent probe output degrades to raw, never an error.
    assert codec_mod.parse_probe("3\n") == []
    assert codec_mod.parse_probe("") == []


def test_executor_negotiates_codec_from_preflight(tmp_path, run_async):
    """The pre-flight compound carries the probe; its output decides the
    per-connection codec, with raw as the fallback for silent workers."""
    from covalent_tpu_plugin.tpu import TPUExecutor

    ex = TPUExecutor(
        transport="local", cache_dir=str(tmp_path / "c"),
        remote_cache=str(tmp_path / "r"), use_agent=False,
    )
    from covalent_tpu_plugin.transport.base import CommandResult

    assert codec_mod.PROBE_PREFIX in ex._preflight_command()
    advertising = FakeTransport({
        "mkdir -p": CommandResult(
            0, f"{codec_mod.PROBE_PREFIX}zlib\n3\n", ""
        ),
    })
    silent = FakeTransport(scripted_ok_responses(), address="mute")
    run_async(ex._preflight(advertising, key="fake:w1"))
    run_async(ex._preflight(silent, key="fake:w2"))
    assert ex._codec_for("fake:w1", advertising).name == "zlib"
    assert ex._codec_for("fake:w2", silent) is None  # raw fallback
    # Zero-wire transports always ship raw, whatever was advertised.
    assert ex._codec_for("fake:w1", LocalTransport()) is None


# --------------------------------------------------------------------- #
# put_file: compressed single-artifact publish
# --------------------------------------------------------------------- #


def test_put_file_compressed_publish_verifies_decompressed_digest(
    tmp_path, run_async
):
    src = write(tmp_path, "artifact.json", COMPRESSIBLE)
    dst = str(tmp_path / "cas" / "artifact.json")
    os.makedirs(tmp_path / "cas")
    digest = file_digest(src)

    stats = run_async(codec_mod.put_file(
        LocalTransport(), src, dst,
        codec=codec_mod.get_codec("zlib"), python_path=sys.executable,
        digest=digest,
    ))
    # The digest the remote side verified is of the DECOMPRESSED bytes.
    assert open(dst).read() == COMPRESSIBLE
    assert file_digest(dst) == digest
    assert stats["codec"] == "zlib"
    assert stats["wire_bytes"] < os.path.getsize(src)


def test_put_file_skips_compression_when_unprofitable(tmp_path, run_async):
    incompressible = tmp_path / "noise.bin"
    incompressible.write_bytes(os.urandom(4096))
    small = write(tmp_path, "tiny.txt", "x")
    for src in (str(incompressible), small):
        dst = f"{src}.shipped"
        stats = run_async(codec_mod.put_file(
            LocalTransport(), src, dst,
            codec=codec_mod.get_codec("zlib"), python_path=sys.executable,
        ))
        assert stats["codec"] == "raw"
        assert open(dst, "rb").read() == open(src, "rb").read()


def test_put_file_digest_mismatch_is_permanent_integrity_error(
    tmp_path, run_async
):
    src = write(tmp_path, "artifact.json", COMPRESSIBLE)
    dst = str(tmp_path / "published")
    with pytest.raises(CodecIntegrityError, match="digest"):
        run_async(codec_mod.put_file(
            LocalTransport(), src, dst,
            codec=codec_mod.get_codec("zlib"), python_path=sys.executable,
            digest="0" * 64,
        ))
    fault, label = classify_error(CodecIntegrityError("x"))
    assert fault is FaultClass.PERMANENT
    assert not os.path.exists(dst)  # nothing published on failure


def test_get_file_compressed_roundtrip_and_raw_small(tmp_path, run_async):
    big = write(tmp_path, "result.pkl", COMPRESSIBLE)
    fetched = str(tmp_path / "fetched.pkl")
    stats = run_async(codec_mod.get_file(
        LocalTransport(), big, fetched,
        codec=codec_mod.get_codec("zlib"), python_path=sys.executable,
    ))
    assert open(fetched).read() == COMPRESSIBLE
    assert stats["codec"] == "zlib"
    assert stats["wire_bytes"] < os.path.getsize(big)
    small = write(tmp_path, "small.pkl", "tiny")
    stats = run_async(codec_mod.get_file(
        LocalTransport(), small, str(tmp_path / "small.out"),
        codec=codec_mod.get_codec("zlib"), python_path=sys.executable,
    ))
    assert stats["codec"] == "raw"  # remote side declined: too small


# --------------------------------------------------------------------- #
# put_bundle: one put + one exec for N artifacts
# --------------------------------------------------------------------- #


def bundle_items(tmp_path, n=3):
    # The executor's pre-flight mkdir -p creates the remote cas dir; these
    # transport-level tests stand in for it here.
    os.makedirs(tmp_path / "cas", exist_ok=True)
    items = []
    for i in range(n):
        local = write(tmp_path, f"art{i}.json", f"{COMPRESSIBLE}#{i}")
        remote = str(tmp_path / "cas" / f"art{i}.json")
        items.append((local, remote, file_digest(local)))
    return items


def test_put_bundle_generic_path_roundtrip(tmp_path, run_async):
    """The base-class tar+unpack path (what SSH/minissh ride), driven
    through a no-fault chaos wrapper over the real local shell."""
    conn = ChaosTransport(LocalTransport(), ChaosPlan())
    items = bundle_items(tmp_path)
    stats = run_async(conn.put_bundle(
        items, str(tmp_path / "cas" / "bundle.tar"),
        python_path=sys.executable, codec=codec_mod.get_codec("zlib"),
    ))
    for local, remote, digest in items:
        assert file_digest(remote) == digest
        assert open(remote).read() == open(local).read()
    assert stats["members"] == 3 and stats["ops"] == 2
    assert stats["codec"] == "zlib"
    raw_total = sum(os.path.getsize(l) for l, _, _ in items)
    assert stats["wire_bytes"] < raw_total  # compressed tar beat raw files
    # The bundle temp file was consumed by the unpack exec.
    assert not os.path.exists(tmp_path / "cas" / "bundle.tar")


def test_put_bundle_local_override_is_direct_copy(tmp_path, run_async):
    items = bundle_items(tmp_path)
    stats = run_async(LocalTransport().put_bundle(
        items, str(tmp_path / "cas" / "bundle.tar"),
        python_path=sys.executable, codec=codec_mod.get_codec("zlib"),
    ))
    for local, remote, digest in items:
        assert file_digest(remote) == digest
    assert stats["ops"] == 1 and stats["codec"] == "raw"  # zero wire


def test_truncated_bundle_is_permanent_integrity_error(tmp_path, run_async):
    """A bundle torn in flight fails the unpack's digest/decompress check
    loudly — classified PERMANENT so the retry driver never re-ships the
    same corrupt bytes (no retry storm)."""
    plan = ChaosPlan(truncate_uploads=1, max_faults=1)
    conn = ChaosTransport(LocalTransport(), plan)
    items = bundle_items(tmp_path)
    with pytest.raises(CodecIntegrityError, match="digest|decompress"):
        run_async(conn.put_bundle(
            items, str(tmp_path / "cas" / "bundle.tar"),
            python_path=sys.executable, codec=codec_mod.get_codec("zlib"),
        ))
    assert plan.faults_injected == 1
    for _, remote, _ in items:
        assert not os.path.exists(remote)  # nothing half-published
    fault, _ = classify_error(CodecIntegrityError("torn"))
    assert fault is FaultClass.PERMANENT


# --------------------------------------------------------------------- #
# CAS integration: ensure_bundle hits/misses/single-flight
# --------------------------------------------------------------------- #


def test_ensure_bundle_ships_once_then_hits(tmp_path, run_async):
    from covalent_tpu_plugin.cache import CAS_UPLOADS_TOTAL

    fake = FakeTransport()
    index = CASIndex()
    items = bundle_items(tmp_path)
    hits0 = counter_value(CAS_UPLOADS_TOTAL, result="hit")
    misses0 = counter_value(CAS_UPLOADS_TOTAL, result="miss")

    async def flow():
        await index.ensure_bundle("k", fake, items)
        await index.ensure_bundle("k", fake, items)

    run_async(flow())
    assert len(fake.puts) == 1  # one bundle, second call all-hit
    assert "/bundle-" in fake.puts[0][1]
    assert counter_value(CAS_UPLOADS_TOTAL, result="miss") - misses0 == 3
    assert counter_value(CAS_UPLOADS_TOTAL, result="hit") - hits0 == 3


def test_ensure_bundle_single_missing_degrades_to_per_file(
    tmp_path, run_async
):
    fake = FakeTransport()
    index = CASIndex()
    items = bundle_items(tmp_path)

    async def flow():
        # Pre-warm two of three digests; the bundle path must not pay tar
        # overhead to ship one file.
        index._present["k"] = {items[0][2], items[1][2]}
        await index.ensure_bundle("k", fake, items)

    run_async(flow())
    assert len(fake.puts) == 1
    assert ".tmp-" in fake.puts[0][1]  # per-file temp+rename, not a bundle


def test_ensure_bundle_dedupes_identical_payloads(tmp_path, run_async):
    """Two artifacts with the same digest (a map fan-out sharing one
    function pickle) bundle once."""
    fake = FakeTransport()
    index = CASIndex()
    local = write(tmp_path, "shared.pkl", COMPRESSIBLE)
    digest = file_digest(local)
    items = [
        (local, str(tmp_path / "cas" / "a.pkl"), digest),
        (local, str(tmp_path / "cas" / "b.pkl"), digest),
        (write(tmp_path, "other.pkl", "other"),
         str(tmp_path / "cas" / "c.pkl"), "d" * 64),
    ]
    run_async(index.ensure_bundle("k", fake, items))
    assert len(fake.puts) == 1  # one bundle: {shared, other}, not 3 members


# --------------------------------------------------------------------- #
# Executor end-to-end: bundled + compressed dispatch over a "wire"
# --------------------------------------------------------------------- #


def test_run_bundled_compressed_dispatch_end_to_end(tmp_path, run_async):
    """A full electron through the fast path: chaos wrapper (simulated
    wire) forces real codec negotiation + the generic bundle, the harness
    verifies the CAS digest of the decompressed function pickle, and the
    wire/staging metrics record the savings."""
    wire0 = compressed_wire_bytes("up")
    from covalent_tpu_plugin.cache import STAGING_OPS_TOTAL

    bundled0 = counter_value(STAGING_OPS_TOTAL, mode="bundled")
    ex = make_local_executor(
        tmp_path, chaos=ChaosPlan(), poll_freq=0.05,
    )
    payload = COMPRESSIBLE

    def electron(text):
        return len(text)

    async def flow():
        try:
            return await ex.run(
                electron, [payload], {},
                {"dispatch_id": "fastpath", "node_id": 0},
            )
        finally:
            await ex.close()

    assert run_async(flow()) == len(payload)
    # compressed bytes actually crossed the simulated wire
    assert compressed_wire_bytes("up") > wire0
    assert counter_value(STAGING_OPS_TOTAL, mode="bundled") - bundled0 == 2
    assert "wall_overhead" in ex.last_timings


def test_bundled_dispatch_fewer_round_trips_and_bytes_than_per_file(
    tmp_path, run_async
):
    """The same electrons through the per-file CAS path (``bundle=False``,
    ``compress="off"``) and the fast path (one compressed tar a worker):
    equal results, strictly fewer staging round trips and strictly fewer
    bytes on the simulated wire."""
    from covalent_tpu_plugin.cache import STAGING_OPS_TOTAL

    def electron(i, text):
        return i, len(text)

    def staging_ops():
        return sum(
            counter_value(STAGING_OPS_TOTAL, mode=mode)
            for mode in ("per_file", "bundled")
        )

    def wire_up():
        return compressed_wire_bytes("up") + counter_value(
            codec_mod.WIRE_BYTES_TOTAL, direction="up", codec="raw"
        )

    async def arm(tag, **kwargs):
        ex = make_local_executor(
            tmp_path / tag, chaos=ChaosPlan(), poll_freq=0.05, **kwargs
        )
        ops0, wire0 = staging_ops(), wire_up()
        try:
            results = [
                await ex.run(
                    electron, [i, COMPRESSIBLE + str(i)], {},
                    {"dispatch_id": f"fan-{tag}", "node_id": i},
                )
                for i in range(2)
            ]
        finally:
            await ex.close()
        return results, staging_ops() - ops0, wire_up() - wire0

    async def flow():
        per_file = await arm("perfile", bundle=False, compress="off")
        bundled = await arm("bundled", bundle=True, compress="auto")
        return per_file, bundled

    per_file, bundled = run_async(flow())
    assert bundled[0] == per_file[0] == [
        (i, len(COMPRESSIBLE) + 1) for i in range(2)
    ]
    assert 0 < bundled[1] < per_file[1], (bundled[1], per_file[1])
    assert 0 < bundled[2] < per_file[2], (bundled[2], per_file[2])


def test_run_pinned_codec_compresses_result_download(tmp_path, run_async):
    """compress="zlib" (pinned) engages the compressed result fetch, keyed
    by the worker's POOL key (the configured address — regression: keying
    by conn.address broke for user@host workers)."""
    down0 = counter_value(
        codec_mod.WIRE_BYTES_TOTAL, direction="down", codec="zlib"
    )
    ex = make_local_executor(
        tmp_path, chaos=ChaosPlan(), compress="zlib", poll_freq=0.05,
    )
    payload = COMPRESSIBLE * 4  # result pickle big enough to pack

    def electron(text):
        return text  # echo: the RESULT is the large compressible payload

    async def flow():
        try:
            return await ex.run(
                electron, [payload], {},
                {"dispatch_id": "pinned", "node_id": 0},
            )
        finally:
            await ex.close()

    assert run_async(flow()) == payload
    assert counter_value(
        codec_mod.WIRE_BYTES_TOTAL, direction="down", codec="zlib"
    ) > down0  # the fetch actually rode the wire compressed


def test_run_unpicklable_electron_still_fails_cleanly(tmp_path, run_async):
    """The pipelined stage leg (serialization on a thread, overlapping the
    dial) must surface staging errors exactly like the sequential path."""
    ex = make_local_executor(tmp_path)

    def gen():
        yield 1

    async def flow():
        try:
            return await ex.run(
                lambda g: next(g), [gen()], {},
                {"dispatch_id": "nopickle", "node_id": 0},
            )
        finally:
            await ex.close()

    with pytest.raises(TypeError, match="pickle|generator"):
        run_async(flow())


# --------------------------------------------------------------------- #
# DAG-driven prewarm
# --------------------------------------------------------------------- #


def test_prewarm_dials_pool_once_and_skips_when_warm(tmp_path, run_async):
    ex = make_local_executor(tmp_path)

    async def flow():
        first = await ex.prewarm()
        second = await ex.prewarm()
        warmed = ex._pool.has(ex._pool_key("localhost"))
        preflighted = ex._pool_key("localhost") in ex._preflighted
        await ex.close()
        return first, second, warmed, preflighted

    first, second, warmed, preflighted = run_async(flow())
    assert first is True and second is False  # idempotent fast path
    assert warmed and preflighted


def test_prewarm_disabled_under_chaos_and_by_config(tmp_path, run_async):
    chaotic = make_local_executor(tmp_path, chaos=ChaosPlan(drop_after=100))
    disabled = make_local_executor(tmp_path, prewarm=False)

    async def flow():
        a = await chaotic.prewarm()
        b = await disabled.prewarm()
        await chaotic.close()
        await disabled.close()
        return a, b

    assert run_async(flow()) == (False, False)
    assert chaotic._chaos.faults_injected == 0  # no budget spent on warmup


def test_workflow_runner_prewarms_blocked_node(tmp_path):
    """A node blocked on an upstream dependency gets its executor's
    control plane dialed WHILE the upstream runs, so its own connect
    stage lands on a warm pool."""
    import covalent_tpu_plugin.workflow as ct

    warmed = counter_value(
        REGISTRY.counter("covalent_tpu_prewarm_total", "", ("result",)),
        result="warmed",
    )
    ex = make_local_executor(tmp_path)

    @ct.electron
    def upstream():
        import time

        time.sleep(0.3)  # window for the prewarm to land
        return 2

    @ct.electron(executor=ex)
    def downstream(x):
        return x * 21

    @ct.lattice
    def flow():
        return downstream(upstream())

    result = ct.dispatch_sync(flow)()
    assert result.status.value == "COMPLETED", result.error
    assert result.result == 42
    assert counter_value(
        REGISTRY.counter("covalent_tpu_prewarm_total", "", ("result",)),
        result="warmed",
    ) == warmed + 1
