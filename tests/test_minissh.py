"""Protocol-level unit tests for the vendored SSH2 stack.

The functional tier (``tests/functional/test_real_ssh.py``) proves the
stack end to end; these tests pin the wire-level invariants an interop
partner would rely on: RFC 4251 mpint encoding, binary packet framing
with and without encryption, MAC tamper rejection, and the auth/hostkey
failure modes.
"""

from __future__ import annotations

import asyncio

import pytest

# The vendored stack is built on `cryptography` (its only dependency —
# minissh.py module docstring); images without it can't exercise any of
# these wire-level tests, and the functional SSH tier skips there too.
pytest.importorskip(
    "cryptography",
    reason="minissh needs the `cryptography` package (absent in this image)",
)

from covalent_tpu_plugin.transport import minissh
from covalent_tpu_plugin.transport.minissh import (
    MiniSSHError,
    _mpint,
    _PacketStream,
    _Reader,
    _string,
    _u32,
)


def run(coro):
    return asyncio.run(coro)


def test_mpint_rfc4251_vectors():
    # RFC 4251 §5 worked examples.
    assert _mpint(0) == bytes.fromhex("00000000")
    assert _mpint(0x9A378F9B2E332A7) == bytes.fromhex(
        "0000000809a378f9b2e332a7"
    )
    assert _mpint(0x80) == bytes.fromhex("000000020080")


def test_reader_roundtrip():
    payload = _u32(7) + _string(b"abc") + bytes([1])
    r = _Reader(payload)
    assert r.u32() == 7
    assert r.string() == b"abc"
    assert r.boolean() is True


class _FeedReader:
    """Minimal StreamReader stand-in backed by a byte buffer."""

    def __init__(self, data: bytes) -> None:
        self.data = data
        self.off = 0

    async def readexactly(self, n: int) -> bytes:
        if self.off + n > len(self.data):
            raise asyncio.IncompleteReadError(b"", n)
        self.off += n
        return self.data[self.off - n:self.off]


def test_packet_roundtrip_plaintext():
    out = _PacketStream()
    inp = _PacketStream()
    wire = out.wrap(b"\x14hello-kexinit")
    # multiple of 8, length field sane, payload recovered
    assert len(wire) % 8 == 0
    got = run(inp.read_packet(_FeedReader(wire)))
    assert got == b"\x14hello-kexinit"
    assert out.seq == 1 and inp.seq == 1


def test_packet_roundtrip_encrypted_and_mac_tamper():
    key, iv, mac = b"k" * 16, b"i" * 16, b"m" * 32
    out = _PacketStream()
    out.arm(key, iv, mac, encrypt=True)
    inp = _PacketStream()
    inp.arm(key, iv, mac, encrypt=False)
    wire1 = out.wrap(b"payload-one")
    wire2 = out.wrap(b"payload-two!")
    assert b"payload-one" not in wire1  # actually encrypted
    got1 = run(inp.read_packet(_FeedReader(wire1)))
    got2 = run(inp.read_packet(_FeedReader(wire2)))  # CTR state carries over
    assert (got1, got2) == (b"payload-one", b"payload-two!")

    # One flipped ciphertext bit must fail the MAC, not decode garbage.
    out2 = _PacketStream()
    out2.arm(key, iv, mac, encrypt=True)
    inp2 = _PacketStream()
    inp2.arm(key, iv, mac, encrypt=False)
    tampered = bytearray(out2.wrap(b"payload-one"))
    tampered[17] ^= 0x01  # inside ciphertext body, outside the length word
    with pytest.raises(MiniSSHError, match="MAC"):
        run(inp2.read_packet(_FeedReader(bytes(tampered))))


def test_wrong_mac_key_rejected():
    key, iv = b"k" * 16, b"i" * 16
    out = _PacketStream()
    out.arm(key, iv, b"m" * 32, encrypt=True)
    inp = _PacketStream()
    inp.arm(key, iv, b"X" * 32, encrypt=False)
    with pytest.raises(MiniSSHError, match="MAC"):
        run(inp.read_packet(_FeedReader(out.wrap(b"data"))))


def test_invalid_encrypted_packet_length_clean_error():
    """A garbled/hostile length that is below the cipher-block minimum or
    not block-aligned must raise a clean protocol error BEFORE readexactly
    (a negative count ValueError) or a CTR keystream desync."""
    key, iv, mac = b"k" * 16, b"i" * 16, b"m" * 32
    out = _PacketStream()
    out.arm(key, iv, mac, encrypt=True)
    inp = _PacketStream()
    inp.arm(key, iv, mac, encrypt=False)

    def forged_head(length: int) -> bytes:
        # Encrypt a head block whose decrypted length field is `length`
        # using the receiver's own keystream position (fresh streams, so
        # the first block's keystream matches).
        head_plain = _u32(length) + b"\x04" + b"\x00" * 11
        return out._cipher.update(head_plain)

    # length < block - 4: readexactly count would go negative.
    with pytest.raises(MiniSSHError, match="invalid packet length"):
        run(inp.read_packet(_FeedReader(forged_head(7) + b"\x00" * 64)))
    # misaligned length: (4 + length) not a multiple of the block size.
    out2 = _PacketStream()
    out2.arm(key, iv, mac, encrypt=True)
    inp2 = _PacketStream()
    inp2.arm(key, iv, mac, encrypt=False)
    head_plain = _u32(21) + b"\x04" + b"\x00" * 11
    forged = out2._cipher.update(head_plain)
    with pytest.raises(MiniSSHError, match="invalid packet length"):
        run(inp2.read_packet(_FeedReader(forged + b"\x00" * 64)))


def test_kexinit_guess_flag_parsed_and_mismatch_discarded():
    """RFC 4253 §7 first_kex_packet_follows: a wrongly guessed first kex
    packet is reported for discard; a right guess (or no guess) is not."""
    from covalent_tpu_plugin.transport.minissh import (
        _check_kexinit,
        _kexinit_payload,
    )

    # Our own KEXINIT: no guess, right algorithms.
    assert _check_kexinit(_kexinit_payload()) is False

    def kexinit(first_lists: dict, follows: bool) -> bytes:
        lists = [
            first_lists.get("kex", minissh._KEX_ALG),
            first_lists.get("hostkey", minissh._HOSTKEY_ALG),
            minissh._CIPHER_ALG, minissh._CIPHER_ALG,
            minissh._MAC_ALG, minissh._MAC_ALG,
            minissh._COMP_ALG, minissh._COMP_ALG,
            b"", b"",
        ]
        out = bytes([minissh.MSG_KEXINIT]) + b"\x00" * 16
        for item in lists:
            out += _string(item)
        return out + bytes([1 if follows else 0]) + _u32(0)

    # Guess flag set, but the peer's first-listed algorithms match ours:
    # the guessed packet IS the right one — nothing to discard.
    assert _check_kexinit(kexinit({}, follows=True)) is False
    # Peer guessed a kex algorithm we didn't negotiate: discard one packet.
    wrong = {"kex": b"diffie-hellman-group14-sha256," + minissh._KEX_ALG}
    assert _check_kexinit(kexinit(wrong, follows=True)) is True
    # Same first-list mismatch WITHOUT the flag: nothing was sent early.
    assert _check_kexinit(kexinit(wrong, follows=False)) is False


def test_password_auth_rejects_wrong_and_unknown_users():
    async def flow():
        server = await minissh.serve(users={"u": "pw"})
        try:
            for user, pw in (("u", "wrong"), ("ghost", "pw")):
                with pytest.raises(minissh.MiniSSHAuthError):
                    await minissh.connect(
                        "127.0.0.1", server.port, user, password=pw
                    )
            conn = await minissh.connect(
                "127.0.0.1", server.port, "u", password="pw"
            )
            conn.close()
            await conn.wait_closed()
        finally:
            server.close()
            await server.wait_closed()

    run(flow())


def test_authorized_keys_bound_to_username():
    """Dict-form authorized_keys authenticate only their own user; the
    legacy list form stays global (documented test-server behavior)."""
    from cryptography.hazmat.primitives.asymmetric import ed25519

    alice_key = ed25519.Ed25519PrivateKey.generate()

    async def flow():
        server = await minissh.serve(
            authorized_keys={"alice": [alice_key.public_key()]}
        )
        try:
            conn = await minissh.connect(
                "127.0.0.1", server.port, "alice", client_key=alice_key
            )
            res = await conn.run("echo ok")
            assert res.stdout.strip() == "ok"
            conn.close()
            await conn.wait_closed()
            # Same key under a different username must be rejected.
            with pytest.raises(minissh.MiniSSHAuthError):
                await minissh.connect(
                    "127.0.0.1", server.port, "mallory",
                    client_key=alice_key,
                )
        finally:
            server.close()
            await server.wait_closed()

        # Legacy global list: any username authenticates (test fixtures).
        server = await minissh.serve(
            authorized_keys=[alice_key.public_key()]
        )
        try:
            conn = await minissh.connect(
                "127.0.0.1", server.port, "anyone", client_key=alice_key
            )
            conn.close()
            await conn.wait_closed()
        finally:
            server.close()
            await server.wait_closed()

    run(flow())


def test_put_bundle_over_minissh_roundtrip(tmp_path):
    """The generic bundle path over a REAL encrypted channel: one cat
    upload + one unpack exec, members digest-verified on the far side."""
    import hashlib
    import os
    import sys

    from covalent_tpu_plugin.transport import SSHTransport
    from covalent_tpu_plugin.transport import codec as codec_mod

    os.makedirs(tmp_path / "cas", exist_ok=True)
    items = []
    for i in range(3):
        local = tmp_path / f"art{i}.json"
        local.write_text('{"spec": "payload", "idx": %d}\n' % i * 64)
        digest = hashlib.sha256(local.read_bytes()).hexdigest()
        items.append((str(local), str(tmp_path / "cas" / f"art{i}"), digest))

    async def flow():
        server = await minissh.serve(users={"u": "pw"})
        try:
            transport = SSHTransport(
                "127.0.0.1", username="u", port=server.port,
                strict_host_keys=False, backend="minissh", password="pw",
            )
            await transport._open()
            stats = await transport.put_bundle(
                items, str(tmp_path / "cas" / "bundle.tar"),
                python_path=sys.executable,
                codec=codec_mod.get_codec("zlib"),
            )
            assert stats["codec"] == "zlib" and stats["members"] == 3
            for local, remote, digest in items:
                assert hashlib.sha256(
                    open(remote, "rb").read()
                ).hexdigest() == digest
            await transport.close()
        finally:
            server.close()
            await server.wait_closed()

    run(flow())


def test_exec_exit_status_and_streams():
    async def flow():
        server = await minissh.serve(users={"u": "pw"})
        try:
            conn = await minissh.connect(
                "127.0.0.1", server.port, "u", password="pw"
            )
            res = await conn.run(
                "printf a-out; printf a-err >&2; exit 41"
            )
            assert (res.exit_status, res.stdout, res.stderr) == (
                41, "a-out", "a-err"
            )
            conn.close()
            await conn.wait_closed()
        finally:
            server.close()
            await server.wait_closed()

    run(flow())


def test_large_transfer_crosses_window_boundary():
    """> initial-window payloads force WINDOW_ADJUST traffic both ways."""

    async def flow():
        server = await minissh.serve(users={"u": "pw"})
        try:
            conn = await minissh.connect(
                "127.0.0.1", server.port, "u", password="pw"
            )
            n = (1 << 21) + 12345  # one byte past the 2 MiB window
            res = await conn.run(f"head -c {n} /dev/zero | wc -c")
            assert res.stdout.strip() == str(n)
            # and upstream: stdin bigger than the server's window
            res = await conn.run("wc -c", stdin=b"z" * n)
            assert res.stdout.strip() == str(n)
            conn.close()
            await conn.wait_closed()
        finally:
            server.close()
            await server.wait_closed()

    run(flow())


def test_concurrent_channels_one_connection():
    async def flow():
        server = await minissh.serve(users={"u": "pw"})
        try:
            conn = await minissh.connect(
                "127.0.0.1", server.port, "u", password="pw"
            )
            results = await asyncio.gather(*[
                conn.run(f"echo ch{i}") for i in range(8)
            ])
            assert [r.stdout for r in results] == [
                f"ch{i}\n" for i in range(8)
            ]
            conn.close()
            await conn.wait_closed()
        finally:
            server.close()
            await server.wait_closed()

    run(flow())


def test_unknown_channel_type_refused():
    async def flow():
        server = await minissh.serve(users={"u": "pw"})
        try:
            conn = await minissh.connect(
                "127.0.0.1", server.port, "u", password="pw"
            )
            ch = conn.new_channel()
            await conn.send(
                bytes([minissh.MSG_CHANNEL_OPEN]) + _string(b"x11")
                + _u32(ch.local_id) + _u32(1 << 20) + _u32(1 << 15)
            )
            with pytest.raises(MiniSSHError, match="channel open failed"):
                await asyncio.wait_for(ch.opened, 10)
            conn.close()
            await conn.wait_closed()
        finally:
            server.close()
            await server.wait_closed()

    run(flow())


def test_auth_and_hostkey_errors_not_retryable():
    """Deterministic verdicts must bypass the transport retry classifier
    (which retries ConnectionError/OSError)."""
    from covalent_tpu_plugin.transport.minissh import (
        MiniSSHAuthError,
        MiniSSHHostKeyError,
    )

    assert not issubclass(MiniSSHAuthError, OSError)
    assert not issubclass(MiniSSHHostKeyError, OSError)
    assert issubclass(MiniSSHError, ConnectionError)  # transport errors ARE


def test_non_ed25519_client_key_clear_error(tmp_path):
    from cryptography.hazmat.primitives import serialization
    from cryptography.hazmat.primitives.asymmetric import rsa

    key = rsa.generate_private_key(public_exponent=65537, key_size=2048)
    path = tmp_path / "id_rsa"
    path.write_bytes(key.private_bytes(
        serialization.Encoding.PEM,
        serialization.PrivateFormat.OpenSSH,
        serialization.NoEncryption(),
    ))

    async def flow():
        server = await minissh.serve(users={"u": "pw"})
        try:
            with pytest.raises(ValueError, match="only ed25519"):
                await minissh.connect(
                    "127.0.0.1", server.port, "u", client_key=str(path)
                )
        finally:
            server.close()
            await server.wait_closed()

    run(flow())


def test_server_kills_command_on_channel_close(tmp_path):
    """TransportProcess.close(kill=True) semantics: closing the exec
    channel must terminate the remote command, like the other backends."""
    import os
    import time

    pidfile = tmp_path / "pid"

    async def flow():
        server = await minissh.serve(users={"u": "pw"})
        try:
            conn = await minissh.connect(
                "127.0.0.1", server.port, "u", password="pw"
            )
            proc = await conn.open_exec(
                f"echo $$ > {pidfile}; exec sleep 600"
            )
            for _ in range(100):
                if pidfile.exists() and pidfile.read_text().strip():
                    break
                await asyncio.sleep(0.05)
            pid = int(pidfile.read_text())
            proc.terminate()
            for _ in range(100):
                try:
                    os.kill(pid, 0)
                except ProcessLookupError:
                    break
                await asyncio.sleep(0.05)
                time.sleep(0)
            else:
                raise AssertionError(f"remote pid {pid} survived close")
            conn.close()
            await conn.wait_closed()
        finally:
            server.close()
            await server.wait_closed()

    run(flow())
