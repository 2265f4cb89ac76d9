"""Serving-tier metrics: request outcomes, stream latency, session load.

One module so the handle and the fleet status views move the same
series.  Label cardinality is deliberately low: ``outcome``
is a closed set, and per-session gauges key on the HANDLE sid (stable
across reconnect generations), not the per-generation remote session id.
"""

from __future__ import annotations

from ..obs.metrics import REGISTRY

#: Terminal accounting for every request submitted through a handle.
#: ``ok`` — full stream delivered; ``deadline`` — lane reclaimed at its
#: deadline (partial stream, ``error`` marker on the final chunk);
#: ``shed`` — refused at admission (bounded queue full); ``rejected`` —
#: refused for any other reason (unknown session, engine refusal);
#: ``error`` — stream failed (token gap, session death past its retry
#: budget, close with requests in flight).
SERVE_REQUESTS_TOTAL = REGISTRY.counter(
    "covalent_tpu_serve_requests_total",
    "Serving-session requests by terminal outcome",
    ("outcome",),
)

SERVE_TOKENS_TOTAL = REGISTRY.counter(
    "covalent_tpu_serve_tokens_total",
    "Tokens streamed back to serving-session callers",
)

SERVE_SESSIONS = REGISTRY.gauge(
    "covalent_tpu_serve_sessions",
    "Live serving sessions held open by this dispatcher",
)

SERVE_QUEUE_DEPTH = REGISTRY.gauge(
    "covalent_tpu_serve_queue_depth",
    "Worker-side admission queue depth per serving session",
    ("session",),
)

SERVE_TOKENS_PER_S = REGISTRY.gauge(
    "covalent_tpu_serve_tokens_per_s",
    "Worker-reported aggregate decode throughput per serving session",
    ("session",),
)

SERVE_RECONNECTS_TOTAL = REGISTRY.counter(
    "covalent_tpu_serve_reconnects_total",
    "Serving sessions re-opened after a channel/worker death",
)

SERVE_HANDOFFS_TOTAL = REGISTRY.counter(
    "covalent_tpu_serve_handoffs_total",
    "Warm session handoffs (replacement opened BEFORE the old gang died)",
    ("outcome",),
)

#: Time-to-first-token, submit -> first streamed chunk.  The streaming
#: side-band's whole point: TTFT must sit near one decode chunk, not at
#: end-of-response.
SERVE_TTFT_SECONDS = REGISTRY.histogram(
    "covalent_tpu_serve_ttft_seconds",
    "Serving-request time to first streamed token",
)

SERVE_REQUEST_SECONDS = REGISTRY.histogram(
    "covalent_tpu_serve_request_seconds",
    "Serving-request full-stream latency (submit -> final chunk)",
)

#: Dispatcher-side view of worker slot occupancy, fed by the heartbeat
#: backhaul (a serving worker's beats carry its ``serve`` block).
SERVE_WORKER_SLOTS = REGISTRY.gauge(
    "covalent_tpu_serve_worker_slots",
    "Serving slot occupancy reported by worker heartbeats",
    ("worker", "state"),
)

# -- replica sets -----------------------------------------------------------
# Per-replica series key on (set, replica) — the replica index is stable
# across reconnect generations, like the session sid — and are removed by
# the supervisor's ``_drop_live`` when the replica retires, so a scaled-
# down set leaves no stale series behind (the same reap contract the
# per-session gauges follow).  ``outcome`` on the router counter is a
# closed set: ``sticky`` (pinned sid honored), ``prefix_affinity``
# (steered to the replica whose engine prefix tree is warm for the
# prompt), ``least_loaded`` (fresh placement),
# ``queued`` (no open replica had headroom — DRR queue),
# ``shed`` (router admission bound hit), ``failover`` (re-routed off a
# dead replica).

SERVE_REPLICAS = REGISTRY.gauge(
    "covalent_tpu_serve_replicas",
    "Replica-set member sessions by state",
    ("set", "state"),
)

SERVE_REPLICA_REQUESTS_TOTAL = REGISTRY.counter(
    "covalent_tpu_serve_replica_requests_total",
    "Requests submitted to each replica of a serving replica set",
    ("set", "replica"),
)

SERVE_REPLICA_IN_FLIGHT = REGISTRY.gauge(
    "covalent_tpu_serve_replica_in_flight",
    "In-flight requests assigned to each replica of a serving replica set",
    ("set", "replica"),
)

SERVE_ROUTER_DECISIONS_TOTAL = REGISTRY.counter(
    "covalent_tpu_serve_router_decisions_total",
    "Replica-set router placement decisions by outcome",
    ("outcome",),
)

#: The router's own DRR queue depth.  Deliberately NOT the fleet
#: scheduler's covalent_tpu_queue_depth: the underlying FairWorkQueue is
#: shared code, and two queues writing one gauge would overwrite (and on
#: lane retirement, delete) each other's per-tenant series.
SERVE_ROUTER_QUEUE_DEPTH = REGISTRY.gauge(
    "covalent_tpu_serve_router_queue_depth",
    "Requests waiting in a replica-set router's per-tenant DRR queue",
    ("tenant",),
)

#: The router's whole per-request cost: scaling out must not move the
#: dispatch tax it removed back into the routing layer.
SERVE_ROUTER_DECISION_SECONDS = REGISTRY.histogram(
    "covalent_tpu_serve_router_decision_seconds",
    "Replica-set router per-request decision latency",
    buckets=(
        0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.05, 0.25,
    ),
)

# -- engine prefix counters (per-session, fed by serve.stats) ---------------
# ContinuousEngine.stats counters surfaced as serving metrics: set from
# every worker stats record and reaped by the supervisor's ``_drop_live``
# like serve_tokens_per_s — without these the prefix tree and the prefill
# accounting are engine-local and invisible to /metrics, /history, SLOs.

SERVE_PREFIX_HITS = REGISTRY.gauge(
    "covalent_tpu_serve_prefix_hits",
    "Engine prefix-tree admission hits per serving session",
    ("session",),
)

SERVE_PREFIX_MISSES = REGISTRY.gauge(
    "covalent_tpu_serve_prefix_misses",
    "Engine prefix-tree admission misses per serving session",
    ("session",),
)

SERVE_PREFILL_POSITIONS = REGISTRY.gauge(
    "covalent_tpu_serve_prefill_positions",
    "Prefill positions paid by a serving session's engine "
    "(suffix buckets on prefix hits, full-prompt buckets on misses)",
    ("session",),
)

# -- speculative + quantized decoding ---------------------------------------
# Per-session series fed by the engine's spec/mode counters through the
# worker stats backhaul, and reaped by the supervisor's ``_drop_live``
# with the other per-session gauges (the PR-10 stale-series contract —
# ``mode`` is a CLOSED set (models/quant.py SERVING_MODES), so the reap
# can enumerate it).  The accept rate is draft agreement
# (spec_accepted / spec_proposed), cumulative over the session.

SERVE_SPEC_ACCEPT_RATE = REGISTRY.gauge(
    "covalent_tpu_serve_spec_accept_rate",
    "Speculative-decode draft accept rate per serving session "
    "(accepted / proposed draft tokens, cumulative)",
    ("session",),
)

SERVE_MODE_TOKENS = REGISTRY.gauge(
    "covalent_tpu_serve_mode_tokens",
    "Output tokens per serving session by decode-mode lane group "
    "(fp / int8 / kv_quant / full_quant)",
    ("session", "mode"),
)

# -- multi-adapter serving ---------------------------------------------------
# One engine, N LoRA adapters (PR 20): per-adapter traffic series are
# fed by the engine's adapter_* stats counters through the worker stats
# backhaul.  Unlike the decode-mode set, the ``adapter`` label set is
# OPEN (operators name adapters) — the supervisor therefore tracks which
# (session, adapter) pairs it created and ``_drop_live`` reaps exactly
# those, never enumerating.  Attach latency is dispatcher-measured wall
# time: CAS stage + wire round trip + engine splice.

SERVE_ADAPTERS = REGISTRY.gauge(
    "covalent_tpu_serve_adapters",
    "LoRA adapters currently attached per serving session",
    ("session",),
)

SERVE_ADAPTER_TOKENS = REGISTRY.gauge(
    "covalent_tpu_serve_adapter_tokens",
    "Output tokens per serving session by adapter lane "
    "(cumulative; 'base' is the un-adapted lane)",
    ("session", "adapter"),
)

SERVE_ADAPTER_REQUESTS_TOTAL = REGISTRY.gauge(
    "covalent_tpu_serve_adapter_requests_total",
    "Requests admitted per serving session by adapter "
    "(cumulative engine counter, gauge-backed so the worker restates "
    "it on every stats tick)",
    ("session", "adapter"),
)

SERVE_ADAPTER_ATTACHES_TOTAL = REGISTRY.counter(
    "covalent_tpu_serve_adapter_attaches_total",
    "Adapter attach/detach operations by outcome",
    ("op", "outcome"),
)

SERVE_ADAPTER_ATTACH_SECONDS = REGISTRY.histogram(
    "covalent_tpu_serve_adapter_attach_seconds",
    "Live adapter attach wall time: CAS stage -> engine splice ack",
    buckets=(
        0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
        30.0,
    ),
)

# -- disaggregated prefill/decode -------------------------------------------
# The KV transfer plane: prefill replicas package admission prefill as
# content-addressed KV bundles; decode replicas import them and go
# straight to decode.  ``outcome`` is a closed set: ``ok`` (bundle
# fetched, digest-verified), ``digest_mismatch`` (torn/stale transfer —
# degraded to full prefill), ``error`` (prefill tier unreachable or
# refused — degraded), ``fallback`` (no prefill tier routable).  ``path``
# on the request counter: ``disagg`` (KV road taken), ``direct`` (short
# prompt, classic road), ``fallback`` (eligible but degraded).

SERVE_KV_TRANSFERS_TOTAL = REGISTRY.counter(
    "covalent_tpu_serve_kv_transfers_total",
    "KV bundle transfers between the prefill and decode tiers by outcome",
    ("outcome",),
)

SERVE_KV_TRANSFER_BYTES_TOTAL = REGISTRY.counter(
    "covalent_tpu_serve_kv_transfer_bytes_total",
    "Serialized KV bundle bytes shipped from the prefill tier",
)

SERVE_KV_TRANSFER_SECONDS = REGISTRY.histogram(
    "covalent_tpu_serve_kv_transfer_seconds",
    "Prefill-tier round trip: serve_prefill submit -> verified bundle",
    buckets=(
        0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
        2.5, 5.0,
    ),
)

SERVE_DISAGG_REQUESTS_TOTAL = REGISTRY.counter(
    "covalent_tpu_serve_disagg_requests_total",
    "Requests through a disaggregated set by road taken",
    ("path",),
)

# -- tail-latency hedging ----------------------------------------------------
# The gray-failure defense's request plane: an idempotent request whose
# TTFT exceeds the set's adaptive percentile is speculatively re-issued
# on the next-healthiest replica.  ``outcome`` is a closed set:
# ``launched`` (hedge sent), ``won`` (hedge arm fed the first token —
# the primary was cancelled), ``lost`` (primary answered first — the
# hedge was cancelled), ``budget`` (TTFT fired but the <5% budget was
# spent), ``no_target`` (no healthier routable replica to hedge onto).

SERVE_HEDGES_TOTAL = REGISTRY.counter(
    "covalent_tpu_serve_hedges_total",
    "Tail-latency hedge decisions by outcome",
    ("outcome",),
)
