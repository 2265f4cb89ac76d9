"""Binary frame layer for the agent channel (dispatcher side).

Every RPC arg/result and every streamed serve token used to travel as
pickle -> base64 -> JSON line over the agent channel: ~33% base64
inflation plus a per-line JSON parse on both ends, at thousands of
messages per second once the dispatch and serving tiers got fast (the
Gemma-on-TPU serving study in PAPERS.md grounds the tokens/s + p99
methodology those tiers assert against).  This module defines the
length-prefixed binary frame the hot path rides instead:

    offset 0   magic      2 bytes   0xC5 0xF7 (never begins a JSON line)
    offset 2   version    1 byte    currently 1
    offset 3   verb       1 byte    accounting/routing hint (VERB_*)
    offset 4   flags      1 byte    bit 0: body is zlib-compressed
    offset 5   header len 4 bytes   big-endian u32
    offset 9   body len   4 bytes   big-endian u32
    offset 13  header     UTF-8 JSON object (the command/event, small)
    ...        body       raw bytes (pickle payloads, token batches)

The JSON header is exactly the dict the JSONL protocol would have sent,
minus its bulky base64 field; the header's ``_body`` key names the field
the raw body bytes re-attach to on the receiving side (e.g. ``args_bytes``
for an invoke, ``data_bytes`` for a result, ``records`` for a coalesced
telemetry batch).  Frames and JSON lines interleave freely on one stream
after negotiation — a reader dispatches on the first byte.

Trace propagation rides the same header: a ``trace`` field (the
``obs.trace.context_of`` carrier — ``trace_id`` + parent ``span_id``)
on a ``serve``/``invoke`` command is opaque to this layer but lets the
worker's per-request spans join the dispatcher's trace, and worker-
recorded spans return as ``span`` records inside the coalesced
``telemetry_batch`` body — causal tracing costs zero new verbs, frames,
or round trips.

Negotiation rides the agent's existing ready-banner handshake (the same
one-round-trip pattern as the ``COVALENT_TPU_CODECS=`` pre-flight probe):
a frame-capable runtime advertises ``"frames": 1`` in its ready event, the
client (unless ``COVALENT_TPU_AGENT_FRAMES=0``) answers with a ``frames``
command, and both sides switch.  A silent banner — an old runtime, a
native-less worker, the kill switch — leaves the channel on JSONL with
byte-equal results, asserted in the test suite.

The worker-side mirror of this codec lives in ``harness.py`` (which must
stay stdlib-only and standalone) and ``native/agent.cc``; the three are
kept byte-compatible by the cross-implementation tests in
``tests/test_frames.py``.
"""

from __future__ import annotations

import json
import struct
import zlib

__all__ = [
    "MAGIC",
    "VERSION",
    "HEADER",
    "HEADER_LEN",
    "FLAG_BODY_ZLIB",
    "MAX_HEADER_BYTES",
    "MAX_BODY_BYTES",
    "MIN_COMPRESS_BYTES",
    "VERB_CMD",
    "VERB_INVOKE",
    "VERB_RESULT",
    "VERB_TELEMETRY",
    "VERB_MULTI_INVOKE",
    "VERB_SERVE",
    "VERB_NAMES",
    "FrameError",
    "FrameIntegrityError",
    "encode_frame",
    "decode_payload",
]

MAGIC = b"\xc5\xf7"
VERSION = 1

HEADER = struct.Struct(">2sBBBII")
HEADER_LEN = HEADER.size  # 13

#: Body compressed with zlib (stdlib on every worker — the frame codec
#: deliberately does not depend on the optional zstd the file-staging
#: codec can negotiate).
FLAG_BODY_ZLIB = 0x01

#: Header/body sanity ceilings: a corrupt length field must be refused as
#: a clean protocol error, never honoured as a multi-GB read that wedges
#: (or OOMs) the resident runtime.
MAX_HEADER_BYTES = 16 * 1024 * 1024
MAX_BODY_BYTES = 512 * 1024 * 1024

#: Bodies below this ship uncompressed (mirrors codec.MIN_COMPRESS_BYTES:
#: tiny payloads can't pay for the deflate header).
MIN_COMPRESS_BYTES = 512

VERB_CMD = 0
VERB_INVOKE = 1
VERB_RESULT = 2
VERB_TELEMETRY = 3
VERB_MULTI_INVOKE = 4
VERB_SERVE = 5

VERB_NAMES = {
    VERB_CMD: "cmd",
    VERB_INVOKE: "invoke",
    VERB_RESULT: "result",
    VERB_TELEMETRY: "telemetry_batch",
    VERB_MULTI_INVOKE: "multi_invoke",
    VERB_SERVE: "serve",
}


class FrameError(ValueError):
    """Malformed frame: bad magic/version, oversized or torn lengths.

    A ValueError (not TransportError) so a parser can distinguish protocol
    corruption from channel death; receivers surface it as a clean error
    event (server side) or a channel teardown (client side).
    """


class FrameIntegrityError(RuntimeError):
    """Frame body failed decompression after an intact transfer.

    RuntimeError on purpose — ``resilience.classify_error`` maps unknown
    non-transport errors PERMANENT, which is right for content corruption:
    re-sending the same torn bytes can never succeed (the same contract as
    ``codec.CodecIntegrityError`` for staged files).
    """


def encode_frame(
    verb: int,
    header: dict,
    body: bytes = b"",
    codec: str = "",
) -> bytes:
    """One wire-ready frame.  ``codec="zlib"`` compresses the body when it
    is large enough to win (>= MIN_COMPRESS_BYTES and shrinks >= 10%)."""
    flags = 0
    if body and codec == "zlib" and len(body) >= MIN_COMPRESS_BYTES:
        packed = zlib.compress(body, 6)
        if len(packed) < len(body) * 0.9:
            body = packed
            flags |= FLAG_BODY_ZLIB
    header_bytes = json.dumps(header, separators=(",", ":")).encode()
    if len(header_bytes) > MAX_HEADER_BYTES or len(body) > MAX_BODY_BYTES:
        raise FrameError(
            f"frame too large (header {len(header_bytes)}B, "
            f"body {len(body)}B)"
        )
    return (
        HEADER.pack(MAGIC, VERSION, verb, flags, len(header_bytes), len(body))
        + header_bytes
        + body
    )


def decode_payload(
    flags: int, header_bytes: bytes, body: bytes
) -> dict:
    """Reassemble the protocol dict from a received frame's parts.

    The header JSON parses back to the command/event dict; a compressed
    body is inflated (:class:`FrameIntegrityError` on torn bytes — the
    frame arrived length-intact, so garbage here is content corruption,
    not a channel problem); the body re-attaches under the field the
    header's ``_body`` key names.
    """
    try:
        event = json.loads(header_bytes.decode("utf-8"))
    except (UnicodeDecodeError, ValueError) as err:
        raise FrameError(f"frame header is not JSON: {err}") from err
    if not isinstance(event, dict):
        raise FrameError("frame header is not a JSON object")
    if flags & FLAG_BODY_ZLIB:
        try:
            body = zlib.decompress(body)
        except zlib.error as err:
            raise FrameIntegrityError(
                f"frame body failed decompression (torn payload): {err}"
            ) from err
    key = event.pop("_body", None)
    if key:
        event[str(key)] = body
    return event
