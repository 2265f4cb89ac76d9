"""Benchmark: electron wall-clock + dispatch overhead (BASELINE.json metric).

Runs the north-star workloads end-to-end through the REAL framework path —
workflow dispatch -> TPUExecutor -> staged harness subprocess -> result
fetch — on whatever accelerator is present (the driver runs this on TPU).

Output protocol: one JSON line **per phase as it completes** (so a timeout
preserves partial progress in the driver's output tail), then ONE final
combined JSON line with ``{"metric", "value", "unit", "vs_baseline"}`` last.
``value`` is the median per-electron dispatch overhead in seconds; the
reference's own defaults bound its per-electron overhead at >= its 15 s poll
interval + ~10 sequential SSH round-trips (BASELINE.md; reference ssh.py:87
poll_freq=15, SURVEY §3.1), and the north star demands < 2 s, so
``vs_baseline`` is target/actual: 2.0 / value (> 1 beats the target).

Structure (fixes the round-1 rc-124 empty bench):
  * the bench parent process NEVER imports jax — only harness subprocesses
    touch the accelerator, so a hanging backend init can't take down the
    whole script;
  * all accelerator work runs in ONE combined electron, paying TPU backend
    init exactly once; the electron streams per-subphase JSON lines to a
    progress file which the parent tails and re-emits live;
  * every phase runs under its own wall-clock budget and is skipped (with
    an error line) on overrun, never aborting the phases after it.
"""

from __future__ import annotations

import asyncio
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chip_smoke import compile_cache_dir  # noqa: E402
from covalent_tpu_plugin import TPUExecutor  # noqa: E402

OVERHEAD_PROBES = 5
#: Phase selection (CI smoke runs pick a subset: the full TPU phase needs
#: an accelerator + minutes of budget, the dispatch phases need neither).
BENCH_PHASES = {
    phase.strip()
    for phase in os.environ.get(
        "BENCH_PHASES",
        "overhead,obs_tax,fanout,cached_fanout,bundled_fanout,"
        "rpc_overhead,serve_traffic,serve_scale,serve_disagg,serve_spec,"
        "serve_multilora,"
        "gray_failure,chaos_fanout,preemption_chaos,dispatcher_crash,"
        "sched_fanout,"
        "traffic_ramp,tpu",
    ).split(",")
    if phase.strip()
}
# Per-phase wall budgets (s).  The accelerator phase dominates: it absorbs
# one cold TPU backend init (minutes on some PJRT plugins) plus the compute
# sub-phases, each of which self-skips as the electron's deadline nears.
OVERHEAD_BUDGET_S = float(os.environ.get("BENCH_OVERHEAD_BUDGET_S", "60"))
FANOUT_BUDGET_S = float(os.environ.get("BENCH_FANOUT_BUDGET_S", "45"))
#: SLO asserted on the overhead phase: p95 of per-electron wall overhead
#: (elapsed minus execute) must stay under the north-star dispatch budget.
WALL_OVERHEAD_BUDGET_S = float(
    os.environ.get("BENCH_WALL_OVERHEAD_BUDGET_S", "2.0")
)
#: SLO asserted on the obs_tax phase: full telemetry (events stream +
#: heartbeats + ops endpoint) may cost at most this fraction of obs-off
#: wall time per electron (plus a small absolute floor for timer noise).
OBS_TAX_BUDGET_PCT = float(os.environ.get("BENCH_OBS_TAX_BUDGET_PCT", "3.0"))
#: SLO asserted on the rpc_overhead phase: median per-electron wall
#: overhead in RPC mode (warm resident runtime, execute-by-digest) must
#: stay under this many seconds — the ROADMAP item-3 sub-100ms target.
RPC_OVERHEAD_BUDGET_S = float(
    os.environ.get("BENCH_RPC_OVERHEAD_BUDGET_S", "0.1")
)
#: serve_traffic phase knobs: request count, the simulated model
#: load+compile each per-electron call pays (the cost a resident session
#: amortizes), per-decode-chunk latency, tokens per request, and the SLO —
#: the resident arm's p50 request latency must beat the per-electron arm's
#: by at least this factor (and its aggregate tokens/s must be higher).
SERVE_REQUESTS = int(os.environ.get("BENCH_SERVE_REQUESTS", "16"))
SERVE_LOAD_S = float(os.environ.get("BENCH_SERVE_LOAD_S", "0.25"))
SERVE_STEP_S = float(os.environ.get("BENCH_SERVE_STEP_S", "0.01"))
SERVE_TOKENS = int(os.environ.get("BENCH_SERVE_TOKENS", "8"))
SERVE_SPEEDUP_MIN = float(os.environ.get("BENCH_SERVE_SPEEDUP_MIN", "1.5"))
SERVE_BUDGET_S = float(os.environ.get("BENCH_SERVE_BUDGET_S", "90"))
#: serve_scale phase knobs: replica count for the scaled arm, offered
#: load (held constant across arms), per-decode-chunk step time, and the
#: SLOs — aggregate tokens/s must scale by >= SERVE_SCALE_MIN from 1 to
#: SERVE_SCALE_REPLICAS replicas, p99 at N must not regress vs N=1 under
#: the same offered load, and the router's median per-request decision
#: must stay under ROUTER_DECISION_BUDGET_S.
SERVE_SCALE_REPLICAS = int(os.environ.get("BENCH_SERVE_SCALE_REPLICAS", "4"))
SERVE_SCALE_REQUESTS = int(os.environ.get("BENCH_SERVE_SCALE_REQUESTS", "32"))
SERVE_SCALE_TOKENS = int(os.environ.get("BENCH_SERVE_SCALE_TOKENS", "12"))
SERVE_SCALE_STEP_S = float(
    os.environ.get("BENCH_SERVE_SCALE_STEP_S", "0.08")
)
SERVE_SCALE_MIN = float(os.environ.get("BENCH_SERVE_SCALE_MIN", "3.0"))
SERVE_SCALE_BUDGET_S = float(
    os.environ.get("BENCH_SERVE_SCALE_BUDGET_S", "150")
)
ROUTER_DECISION_BUDGET_S = float(
    os.environ.get("BENCH_ROUTER_DECISION_BUDGET_S", "0.001")
)
#: serve_disagg phase knobs: mixed short/long-prompt traffic through the
#: SAME decode tier with and without a prefill tier in front.  Long
#: prompts cost prefill_s_per_tok * len of ENGINE-LOOP time at admission
#: (the compute disaggregation moves off the decode tier); arrivals are
#: open-loop so prefill work genuinely overlaps decode.  SLOs: decode
#: tokens/s with the prefill tier must not be lower (no_slower, CI) —
#: and is expected to beat the fused arm — with every stream byte-equal
#: across arms and KV transfer bytes + p50 latency accounted.
SERVE_DISAGG_DECODE = int(os.environ.get("BENCH_SERVE_DISAGG_DECODE", "2"))
SERVE_DISAGG_REQUESTS = int(
    os.environ.get("BENCH_SERVE_DISAGG_REQUESTS", "18")
)
SERVE_DISAGG_TOKENS = int(os.environ.get("BENCH_SERVE_DISAGG_TOKENS", "16"))
SERVE_DISAGG_STEP_S = float(
    os.environ.get("BENCH_SERVE_DISAGG_STEP_S", "0.04")
)
SERVE_DISAGG_LONG_PROMPT = int(
    os.environ.get("BENCH_SERVE_DISAGG_LONG_PROMPT", "32")
)
SERVE_DISAGG_PREFILL_S_PER_TOK = float(
    os.environ.get("BENCH_SERVE_DISAGG_PREFILL_S_PER_TOK", "0.01")
)
SERVE_DISAGG_ARRIVAL_S = float(
    os.environ.get("BENCH_SERVE_DISAGG_ARRIVAL_S", "0.08")
)
SERVE_DISAGG_BUDGET_S = float(
    os.environ.get("BENCH_SERVE_DISAGG_BUDGET_S", "150")
)
#: serve_spec phase knobs: open-loop load through three REAL
#: ContinuousEngine arms inside one worker (the bench parent never
#: imports jax) — fp, fp+draft (speculative), and a kv_quant lane group
#: driven by the per-request ``quality`` knob, all greedy.  The draft is
#: a 1-layer model sharing the target's embed/unembed/layer-0 weights
#: while the target's upper layers have their residual contributions
#: zeroed, so draft and target argmax agree by construction (accept rate
#: 1.0) and the measured speedup isolates the verify-slab amortization
#: (draft_len+1 tokens per fused target pass vs 1 per plain step).
#: SLOs: the spec arm's greedy streams byte-equal the fp arm's, and its
#: aggregate tokens/s beats fp by >= SERVE_SPEC_SPEEDUP_MIN.
SERVE_SPEC_REQUESTS = int(os.environ.get("BENCH_SERVE_SPEC_REQUESTS", "8"))
SERVE_SPEC_TOKENS = int(os.environ.get("BENCH_SERVE_SPEC_TOKENS", "48"))
SERVE_SPEC_DRAFT_LEN = int(os.environ.get("BENCH_SERVE_SPEC_DRAFT_LEN", "6"))
SERVE_SPEC_LAYERS = int(os.environ.get("BENCH_SERVE_SPEC_LAYERS", "6"))
SERVE_SPEC_SPEEDUP_MIN = float(
    os.environ.get("BENCH_SERVE_SPEC_SPEEDUP_MIN", "1.5")
)
SERVE_SPEC_BUDGET_S = float(
    os.environ.get("BENCH_SERVE_SPEC_BUDGET_S", "240")
)
#: serve_multilora phase knobs: the SAME mixed multi-tenant load (a
#: round-robin of MULTILORA_ADAPTERS distinct LoRA adapters over one
#: shared base model) offered to ONE multiplexed engine (the adapter
#: bank: every wave gathers each lane's adapter inside the compiled
#: step, so all tenants co-batch) and to per-adapter single-tenant
#: engines time-sharing the same device (each sees only its adapter's
#: quarter of the traffic, so its batches run 1/N full and the device
#: serializes N engines' decode waves).  SLOs: every stream byte-equal
#: across arms (slot-0 identity / bank-gather exactness), aggregate
#: multiplexed tokens/s >= MULTILORA_SPEEDUP_MIN x the single-tenant
#: aggregate, and a mid-phase hot swap of one adapter finishes every
#: in-flight stream on the OLD generation while new admissions decode
#: the new one — zero drops, zero sheds.
MULTILORA_ADAPTERS = int(os.environ.get("BENCH_MULTILORA_ADAPTERS", "4"))
MULTILORA_REQUESTS = int(os.environ.get("BENCH_MULTILORA_REQUESTS", "32"))
MULTILORA_TOKENS = int(os.environ.get("BENCH_MULTILORA_TOKENS", "32"))
MULTILORA_RANK = int(os.environ.get("BENCH_MULTILORA_RANK", "4"))
MULTILORA_LAYERS = int(os.environ.get("BENCH_MULTILORA_LAYERS", "4"))
MULTILORA_SPEEDUP_MIN = float(
    os.environ.get("BENCH_MULTILORA_SPEEDUP_MIN", "1.3")
)
MULTILORA_BUDGET_S = float(
    os.environ.get("BENCH_MULTILORA_BUDGET_S", "240")
)
#: gray_failure phase knobs: three replica-set arms under the SAME
#: open-loop load — healthy (3 good replicas), brownout-unhedged (one
#: replica slowed GRAY_SLOW_S per engine step via worker-side chaos,
#: health scoring + hedging OFF: the pre-defense baseline), and
#: brownout-hedged (same brownout, full gray-failure defense ON).
#: SLOs: the hedged arm's measured p99 stays within GRAY_HEDGED_MAX of
#: the healthy arm's (floored at GRAY_P99_FLOOR_S against timer noise)
#: while the unhedged arm degrades by at least GRAY_UNHEDGED_MIN; every
#: stream byte-equal across all arms; zero requests shed; hedges fired.
GRAY_REQUESTS = int(os.environ.get("BENCH_GRAY_REQUESTS", "16"))
GRAY_WARMUP = int(os.environ.get("BENCH_GRAY_WARMUP", "12"))
GRAY_TOKENS = int(os.environ.get("BENCH_GRAY_TOKENS", "12"))
GRAY_STEP_S = float(os.environ.get("BENCH_GRAY_STEP_S", "0.04"))
GRAY_SLOW_S = float(os.environ.get("BENCH_GRAY_SLOW_S", "2.0"))
GRAY_ARRIVAL_S = float(os.environ.get("BENCH_GRAY_ARRIVAL_S", "0.03"))
GRAY_HEDGED_MAX = float(os.environ.get("BENCH_GRAY_HEDGED_MAX", "1.5"))
GRAY_UNHEDGED_MIN = float(os.environ.get("BENCH_GRAY_UNHEDGED_MIN", "2.0"))
GRAY_P99_FLOOR_S = float(os.environ.get("BENCH_GRAY_P99_FLOOR_S", "0.3"))
GRAY_BUDGET_S = float(os.environ.get("BENCH_GRAY_BUDGET_S", "240"))
#: traffic_ramp phase knobs: the SAME ramping open-loop load (a light
#: warm phase, a surge past one replica's throughput, a cool tail)
#: offered to a statically over-provisioned replica set and to a
#: 1-replica set under the closed-loop AutoscaleController with a
#: deliberately tight injected latency SLO.  Asserted: the injected burn
#: fires on the autoscaled arm and CLEARS after the controller's
#: scale-up, the autoscaled arm consumes measurably fewer warm
#: gang-seconds (live replicas integrated over the run) than the static
#: arm, and its p95 holds within RAMP_P95_MARGIN_S of the static arm's
#: (one decode chunk of queueing during the reaction window).
RAMP_REPLICAS_MAX = int(os.environ.get("BENCH_RAMP_REPLICAS_MAX", "3"))
RAMP_TOKENS = int(os.environ.get("BENCH_RAMP_TOKENS", "8"))
RAMP_STEP_S = float(os.environ.get("BENCH_RAMP_STEP_S", "0.05"))
RAMP_WARM_REQUESTS = int(os.environ.get("BENCH_RAMP_WARM_REQUESTS", "16"))
RAMP_WARM_INTERVAL_S = float(
    os.environ.get("BENCH_RAMP_WARM_INTERVAL_S", "0.4")
)
#: The surge is a STEP (start == end), not a gradual ramp: a gradual
#: acceleration gives the in-flight trend enough warning that the
#: controller scales before a single request queues (measured: max
#: latency 0.234s vs the 0.45s threshold — no burn to clear).  The step
#: is the injection: ~14 req/s against one replica's ~10 req/s ceiling
#: with zero trend warning, so the tight SLO below provably burns, the
#: burn hook drives the scale-up, and the cool tail clears it.
RAMP_SURGE_REQUESTS = int(os.environ.get("BENCH_RAMP_SURGE_REQUESTS", "24"))
RAMP_SURGE_START_S = float(
    os.environ.get("BENCH_RAMP_SURGE_START_S", "0.085")
)
RAMP_SURGE_END_S = float(os.environ.get("BENCH_RAMP_SURGE_END_S", "0.085"))
RAMP_COOL_REQUESTS = int(os.environ.get("BENCH_RAMP_COOL_REQUESTS", "14"))
RAMP_COOL_INTERVAL_S = float(
    os.environ.get("BENCH_RAMP_COOL_INTERVAL_S", "0.35")
)
#: Injected SLO: threshold 0.2 snaps to the 0.25s histogram bucket —
#: one queued decode chunk past the ~0.2s nominal service time is
#: already "bad" — and the 0.9 objective burns at >10% bad in-window.
RAMP_SLO_THRESHOLD_S = float(
    os.environ.get("BENCH_RAMP_SLO_THRESHOLD_S", "0.2")
)
RAMP_SLO_OBJECTIVE = float(os.environ.get("BENCH_RAMP_SLO_OBJECTIVE", "0.9"))
RAMP_LEAD_S = float(os.environ.get("BENCH_RAMP_LEAD_S", "1.5"))
RAMP_P95_MARGIN_S = float(os.environ.get("BENCH_RAMP_P95_MARGIN_S", "0.25"))
RAMP_GANG_RATIO_MAX = float(
    os.environ.get("BENCH_RAMP_GANG_RATIO_MAX", "0.85")
)
RAMP_BUDGET_S = float(os.environ.get("BENCH_RAMP_BUDGET_S", "150"))
# Sized on an earlier stack, where the phase list needed ~450 s cold; not
# re-measured on this installation.  The preflight means a machine without
# a chip exits in seconds regardless, so the budget only bounds the
# healthy path.
TPU_BUDGET_S = float(os.environ.get("BENCH_TPU_BUDGET_S", "570"))
#: Persistent XLA compilation cache shared across bench runs (and with
#: chip_smoke.py): JAX_COMPILATION_CACHE_DIR verbatim when set, else one
#: fixed path in the checkout.  Handed to workers through task_env, before
#: they import jax; no code sets jax_compilation_cache_dir.
JAX_CACHE_DIR = compile_cache_dir()


class _PhaseSkipped(Exception):
    """Raised inside a phase body when BENCH_PHASES deselects it."""


def emit(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def spread_stats(values, prefix: str) -> dict:
    """min/max/stdev fields for a list of seconds, ms-scaled.

    The r3 verdict asked for the TPU phases' honest-spread treatment on
    EVERY phase — the overhead/fanout phases previously reported point
    medians only.
    """
    out = {
        f"{prefix}_ms_min": round(min(values) * 1e3, 3),
        f"{prefix}_ms_max": round(max(values) * 1e3, 3),
    }
    if len(values) >= 2:
        out[f"{prefix}_ms_stdev"] = round(statistics.stdev(values) * 1e3, 3)
    return out


def introspection_view(metrics: list, window_s: float = 300.0) -> dict:
    """Phase-boundary introspection: windowed history timelines + SLO
    verdicts for a phase's emitted JSON.

    BENCH artifacts previously carried point summaries only; the history
    ring turns them into regression-comparable timelines (tokens/s and
    queue depth over the phase, windowed latency percentiles), and the
    SLO engine's verdicts say whether the phase burned any error budget
    while it ran.  Best-effort: introspection being disabled (env) or
    broken must never fail a bench phase.
    """
    view: dict = {"history": {}, "slo": {}}
    try:
        from covalent_tpu_plugin.obs import history as _history
        from covalent_tpu_plugin.obs import slo as _slo

        ring = _history.ensure_history()
        if ring is not None:
            ring.sample(force=True)  # pin the phase's final state
            for name in metrics:
                q = ring.query(name, window_s=window_s)
                view["history"][name] = {
                    "kind": q["kind"],
                    "samples": q["samples"],
                    "series": q["series"],
                }
        engine = _slo.ensure_slo_engine()
        if engine is not None:
            evaluated = engine.evaluate()
            view["slo"] = {
                name: {
                    "state": info["state"],
                    "burn_rate": info["burn_rate"],
                }
                for name, info in evaluated.get("slos", {}).items()
            }
    except Exception as error:  # noqa: BLE001 - observability never fatal
        view["error"] = repr(error)
    return view


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile of a small sample (q in [0, 1])."""
    ordered = sorted(values)
    if not ordered:
        return float("nan")
    if len(ordered) == 1:
        return ordered[0]
    rank = q * (len(ordered) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (rank - lo)


#: The tiling TTFT segments every traced serving request records (the
#: decode/flush tail is excluded — attribution answers "where did my
#: TTFT go", and TTFT ends at the first streamed token).
TTFT_SEGMENTS = ("prefill", "route", "dispatch", "ttft_wait")


def latency_attribution(trace_ids) -> dict:
    """Per-segment share-of-TTFT percentiles for one arm's requests.

    Pulls each request's waterfall from the in-process trace store and
    aggregates the tiling TTFT segments into p50/p95 shares — the
    artifact-level answer to "where did my TTFT go" across the arm, and
    the completeness evidence the CI smoke asserts on: every found
    trace must carry waterfall segments, contain no orphan spans, and
    its segment sum must cover the root's end-to-end duration.
    """
    out: dict = {
        "requests": len(trace_ids),
        "traces_found": 0,
        "traces_complete": 0,
        "traces_full_waterfall": 0,
        "orphan_spans": 0,
        "ttft_segments": {},
    }
    try:
        from covalent_tpu_plugin.obs.tracestore import TRACE_STORE

        shares: dict = {}
        coverages = []
        for trace_id in trace_ids:
            view = TRACE_STORE.waterfall(str(trace_id))
            if view is None:
                continue
            out["traces_found"] += 1
            out["orphan_spans"] += sum(
                1 for s in view.get("spans", ()) if s.get("orphan")
            )
            segments = view.get("segments") or {}
            ttft = sum(
                segments[name]["duration_s"]
                for name in TTFT_SEGMENTS
                if name in segments
            )
            if view.get("coverage") is not None:
                coverages.append(view["coverage"])
            if ttft <= 0:
                continue
            out["traces_complete"] += 1
            if all(name in segments for name in TTFT_SEGMENTS):
                # A short prompt legitimately skips the prefill tile;
                # the full four-segment waterfall only appears on the
                # KV-road (long-prompt) requests.
                out["traces_full_waterfall"] += 1
            for name in TTFT_SEGMENTS:
                if name in segments:
                    shares.setdefault(name, []).append(
                        segments[name]["duration_s"] / ttft
                    )
        for name, values in shares.items():
            out["ttft_segments"][name] = {
                "p50_share": round(percentile(values, 0.50), 4),
                "p95_share": round(percentile(values, 0.95), 4),
            }
        if coverages:
            out["coverage_p50"] = round(percentile(coverages, 0.50), 4)
            out["coverage_min"] = round(min(coverages), 4)
    except Exception as error:  # noqa: BLE001 - observability never fatal
        out["error"] = repr(error)
    return out


def tpu_host_signals() -> dict:
    """Host-level evidence of TPU hardware, gathered WITHOUT importing jax.

    On a host with no TPU device nodes libtpu's backend init can block
    instead of failing.  These signals are what a TPU VM actually exposes,
    so their absence turns a 45 s hang into an instant, actionable verdict.
    """
    import glob

    try:
        from importlib import metadata
        libtpus = sorted(
            d.metadata["Name"]
            for d in metadata.distributions()
            if (d.metadata["Name"] or "").lower().startswith("libtpu")
        )
    except Exception:  # noqa: BLE001 - diagnostics must not fail the probe
        libtpus = []
    return {
        "accel_devices": sorted(glob.glob("/dev/accel*")),
        "vfio": os.path.exists("/dev/vfio"),
        "tpu_env": bool(
            os.environ.get("TPU_NAME")
            or os.environ.get("TPU_WORKER_ID")
            or os.environ.get("TPU_WORKER_HOSTNAMES")
        ),
        "libtpu_dists": libtpus,
    }


def explicit_cpu() -> bool:
    """The one way to ask for the CPU validation tier: JAX_PLATFORMS=cpu."""
    return (os.environ.get("JAX_PLATFORMS") or "").strip().lower() == "cpu"


def tpu_preflight(timeout_s: float) -> tuple[bool, float, str]:
    """Device probe in a throwaway subprocess, before the big electron.

    The child inherits this process's environment and exits — releasing
    the chip — before the accelerator electron starts.

    * **Fail fast off-TPU** — when the env pins a TPU platform and the
      host shows none of a TPU VM's signals, refuse in milliseconds with
      the actionable reason (and the installed libtpu dists, since a
      ``libtpu`` + ``libtpu_nightly`` double-install is itself a known
      init-breaker).
    * **Stage markers** — the child prints a progress line per stage
      (import / backend / compile), and a timeout's partial stdout names
      the stage that hung instead of just the budget that died.
    * **No silent CPU pass** — a probe that settles on anything but a TPU
      is a FAILURE naming the settled platform, unless JAX_PLATFORMS=cpu
      asked for the CPU on purpose.
    """
    import subprocess

    t0 = time.monotonic()
    requested = (os.environ.get("JAX_PLATFORMS") or "").lower()
    if "tpu" in requested:
        signals = tpu_host_signals()
        if not (
            signals["accel_devices"] or signals["vfio"] or signals["tpu_env"]
        ):
            return False, time.monotonic() - t0, (
                f"not a TPU host: JAX_PLATFORMS={requested!r} but no "
                "/dev/accel* nodes, no /dev/vfio, no TPU_* env — libtpu "
                "backend init would hang, not fail "
                f"(libtpu dists installed: {signals['libtpu_dists'] or 'none'})"
            )
    code = (
        # Stage lines flush eagerly: they are the hang's attribution.
        "print('PREFLIGHT_STAGE import', flush=True)\n"
        "import jax, jax.numpy as jnp\n"
        "print('PREFLIGHT_STAGE backend', flush=True)\n"
        "devs = jax.devices()\n"
        "print('PREFLIGHT_STAGE compile', devs[0].platform, len(devs),"
        " flush=True)\n"
        "x = jnp.ones((256, 256), jnp.bfloat16)\n"
        "out = jax.jit(lambda a: a @ a)(x)\n"
        "print('PREFLIGHT_OK', float(out[0, 0]), devs[0].platform,"
        " flush=True)\n"
    )

    def last_stage(stdout: str | bytes | None) -> str:
        text = stdout or ""
        if isinstance(text, bytes):
            text = text.decode(errors="replace")
        stages = [
            line.split()[1]
            for line in text.splitlines()
            if line.startswith("PREFLIGHT_STAGE ") and len(line.split()) > 1
        ]
        return stages[-1] if stages else "interpreter-start"

    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            timeout=timeout_s, capture_output=True, text=True,
        )
        took = time.monotonic() - t0
        if proc.returncode == 0 and "PREFLIGHT_OK 256" in proc.stdout:
            settled = proc.stdout.rsplit("PREFLIGHT_OK 256", 1)[-1].split()
            platform = (settled[-1] if settled else "").lower()
            if platform != "tpu" and not explicit_cpu():
                return False, took, (
                    f"backend settled on {platform!r}, not a TPU (set "
                    "JAX_PLATFORMS=cpu to run the CPU validation tier on "
                    "purpose)"
                )
            return True, took, ""
        tail = (proc.stderr or proc.stdout or "")[-300:]
        return False, took, (
            f"rc={proc.returncode} in stage {last_stage(proc.stdout)!r}: "
            f"{tail}"
        )
    except subprocess.TimeoutExpired as error:
        stage = last_stage(error.stdout)
        hint = (
            " (TPU backend init blocked: check /dev/accel* visibility and "
            "for conflicting libtpu installs)"
            if stage == "backend"
            else ""
        )
        return False, time.monotonic() - t0, (
            f"timeout after {timeout_s}s, hung in stage {stage!r}{hint}"
        )
    except Exception as error:  # noqa: BLE001
        return False, time.monotonic() - t0, repr(error)


def trivial_electron(i: int) -> int:
    return i * i


# --------------------------------------------------------------------------
# dispatcher_crash drill: two processes play dispatcher incarnations.
#
# The phase cannot SIGKILL *itself*, so the drill runs the dispatcher in a
# child: `bench.py --dispatcher-drill serve <dir>` journals two serving
# sessions with one slow stream each and reports delivered-token progress
# on stdout until the phase kills it -9 mid-stream; `--dispatcher-drill
# recover <dir>` is the successor incarnation — journal replay, orphan
# adoption over the rendezvous socket, stream resume from the journaled
# high-water marks — and prints one summary line the phase asserts on.
# --------------------------------------------------------------------------

DRILL_SESSIONS = 2
DRILL_TOKENS = 40


def _drill_engine_factory(step_delay: float = 0.15):
    """Deterministic slow engine (closure-local: workers can't import
    bench).  Prompt ``[base]`` streams ``base+1 .. base+DRILL_TOKENS`` —
    byte-checkable across the crash."""

    def factory():
        import time as time_mod

        class Engine:
            def __init__(self):
                self.slots = 2
                self.lanes = {}

            def admit(self, rid, prompt, params):
                cap = int((params or {}).get("max_new_tokens", 8))
                base = int(prompt[-1])
                self.lanes[rid] = [base + i + 1 for i in range(cap)]

            def step(self):
                time_mod.sleep(step_delay)
                events = []
                for rid in list(self.lanes):
                    taken = self.lanes[rid][:2]
                    self.lanes[rid] = self.lanes[rid][2:]
                    done = not self.lanes[rid]
                    if done:
                        del self.lanes[rid]
                    events.append({"rid": rid, "tokens": taken, "done": done})
                return events

            def cancel(self, rid):
                self.lanes.pop(rid, None)

        return Engine()

    return factory


def _drill_executor(dwork: str):
    root = os.path.dirname(os.path.abspath(__file__))
    return TPUExecutor(
        transport="local",
        cache_dir=f"{dwork}/cache",
        remote_cache=f"{dwork}/remote",
        python_path=sys.executable,
        poll_freq=0.2,
        use_agent="pool",
        heartbeat_interval=0.0,
        prewarm=False,
        task_env={
            "PYTHONPATH": root + os.pathsep
            + os.environ.get("PYTHONPATH", ""),
            "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
        },
    )


async def _drill_serve(dwork: str) -> None:
    """Incarnation 1: journal, stream, report progress, die by SIGKILL."""
    from covalent_tpu_plugin.fleet import journal as journal_mod
    from covalent_tpu_plugin.serving import open_session

    journal_mod.configure(f"{dwork}/journal")
    ex = _drill_executor(dwork)
    # Both sessions warm BEFORE either request: stream 0 must not run to
    # completion while session 1 is still cold-starting its worker.
    handles = await asyncio.gather(*(
        open_session(
            ex, _drill_engine_factory(step_delay=0.2),
            name=f"dcrash-s{i}", stats_interval_s=0.2,
        )
        for i in range(DRILL_SESSIONS)
    ))
    streams = []
    for i, handle in enumerate(handles):
        base = 1000 * (i + 1)
        req = await handle.request(
            [base], params={"max_new_tokens": DRILL_TOKENS}
        )
        streams.append((handle.sid, base, req))
    deadline = time.monotonic() + 120  # safety: the kill should come first
    while time.monotonic() < deadline:
        for sid, base, req in streams:
            print(json.dumps({
                "drill": "progress", "sid": sid, "rid": req.rid,
                "base": base, "tokens": list(req.tokens),
            }), flush=True)
        await asyncio.sleep(0.1)


async def _drill_recover(dwork: str) -> None:
    """Incarnation 2: replay, re-adopt, resume, report, exit clean."""
    from covalent_tpu_plugin.fleet import journal as journal_mod
    from covalent_tpu_plugin.fleet import recovery as recovery_mod  # noqa: F401

    journal_mod.configure(f"{dwork}/journal")
    ex = _drill_executor(dwork)
    report = await ex.recover()
    streams = {}
    for (sid, rid), req in report.requests.items():
        tail = await req.result(timeout=90)
        streams[f"{sid}/{rid}"] = {
            "from": req.resumed_from, "tail": list(tail),
        }
    totals = metrics_totals()
    print(json.dumps({
        "drill": "recovered",
        "epoch": report["epoch"],
        "duration_s": report["duration_s"],
        "adopted": len(report["adopted_sessions"]),
        "orphaned": len(report["orphaned_sessions"]),
        "streams": streams,
        "metrics": {
            k: v for k, v in totals.items()
            if "recovery" in k or "journal" in k or "fallback_local" in k
        },
    }), flush=True)
    for sup in report.supervisors.values():
        await sup.close()
    await ex.close()


def run_dispatcher_crash_drill(dwork: str) -> dict:
    """Phase orchestrator (sync, called off-loop): serve → kill -9 →
    recover, returning the composed evidence."""
    import signal as signal_mod
    import subprocess

    os.makedirs(dwork, exist_ok=True)
    env = dict(os.environ)
    env["COVALENT_TPU_ORPHAN_TTL_S"] = "120"
    env.setdefault("JAX_PLATFORMS", "cpu")
    argv = [sys.executable, os.path.abspath(__file__), "--dispatcher-drill"]
    serve = subprocess.Popen(
        argv + ["serve", dwork],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=env,
    )
    prefixes: dict[str, dict] = {}
    try:
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            line = serve.stdout.readline()
            if not line:
                break
            try:
                msg = json.loads(line)
            except ValueError:
                continue
            if msg.get("drill") != "progress":
                continue
            prefixes[f"{msg['sid']}/{msg['rid']}"] = msg
            # Mid-stream on every session: tokens flowed, none finished.
            if len(prefixes) >= DRILL_SESSIONS and all(
                4 <= len(p["tokens"]) < DRILL_TOKENS
                for p in prefixes.values()
            ):
                break
        t_kill = time.monotonic()
        serve.send_signal(signal_mod.SIGKILL)
        serve.wait(timeout=30)
    finally:
        if serve.poll() is None:
            serve.kill()
    mid_flight = bool(prefixes) and all(
        0 < len(p["tokens"]) < DRILL_TOKENS for p in prefixes.values()
    )
    rec = subprocess.run(
        argv + ["recover", dwork],
        capture_output=True, text=True, env=env, timeout=240,
    )
    recovered = None
    for line in (rec.stdout or "").splitlines():
        try:
            msg = json.loads(line)
        except ValueError:
            continue
        if msg.get("drill") == "recovered":
            recovered = msg
    if recovered is None:
        raise AssertionError(
            f"recover drill produced no summary (rc={rec.returncode}): "
            f"{(rec.stderr or rec.stdout or '')[-400:]}"
        )
    # Exactly-once across the crash, per stream: the killed dispatcher's
    # last-reported prefix must be a prefix of the oracle, the journaled
    # splice point can exceed it only by the kill window (chunks delivered
    # between the last progress line and the SIGKILL), and the resumed
    # tail must complete the oracle byte-for-byte from that splice point.
    streams_exact = bool(recovered["streams"]) and mid_flight
    checks = []
    for key, got in recovered["streams"].items():
        progress = prefixes.get(key)
        base = progress["base"] if progress else 0
        oracle = [base + i + 1 for i in range(DRILL_TOKENS)]
        prefix = progress["tokens"] if progress else []
        splice = int(got["from"])
        ok = (
            progress is not None
            and prefix == oracle[:len(prefix)]
            and len(prefix) <= splice <= DRILL_TOKENS
            and got["tail"] == oracle[splice:]
        )
        streams_exact = streams_exact and ok
        checks.append({
            "stream": key, "prefix_tokens": len(prefix), "splice": splice,
            "tail_tokens": len(got["tail"]), "exact": ok,
        })
    return {
        "mid_flight_at_kill": mid_flight,
        "sessions_adopted": recovered["adopted"],
        "sessions_orphaned": recovered["orphaned"],
        "recovery_duration_s": recovered["duration_s"],
        "recovery_epoch": recovered["epoch"],
        "recovery_wall_s": round(time.monotonic() - t_kill, 3),
        "streams": checks,
        "streams_exact": streams_exact,
        "metrics": recovered["metrics"],
    }


def preemptible_train(steps: int, step_s: float, progress_path: str):
    """Checkpoint-cooperative training electron (preemption_chaos phase).

    Appends every executed step to ``progress_path`` so the phase can
    count recomputation across gang attempts; registers a snapshot hook
    for the harness's interval/SIGTERM checkpointer and resumes from the
    dispatcher-shipped bundle when one exists.
    """
    import time as time_mod

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
            with open(progress_path, "a") as f:
                f.write(f"{step}\n")
            time_mod.sleep(step_s)
            state = {"acc": state["acc"] + step, "step": step}
    finally:
        ckpt.unregister_snapshot()
    return state["acc"], start


#: ~36 KiB of structured, compressible text per electron — the realistic
#: spec/manifest payload shape the wire codec targets (random bytes would
#: dishonestly zero the codec's win; real staged payloads are pickles and
#: JSON, which compress well).
BUNDLE_PAYLOAD = (
    '{"field": "value", "worker_env": "JAX_PLATFORMS=tpu", '
    '"path": "/workdir/covalent-tpu/artifacts"}\n'
) * 400


def payload_electron(i: int, text: str) -> tuple:
    """Unique-per-electron args force a distinct function pickle each, so
    a cold fan-out stages real per-electron payload bytes."""
    return (i, len(text))


def wire_up_bytes() -> float:
    """Total upload bytes recorded by the codec layer so far."""
    return sum(
        v for k, v in metrics_totals().items()
        if k.startswith("covalent_tpu_wire_bytes_total{")
        and "direction=up" in k
    )


def staging_ops() -> float:
    """Total staging round trips (per-file + bundled) so far."""
    return sum(
        v for k, v in metrics_totals().items()
        if k.startswith("covalent_tpu_staging_ops_total{")
    )


def agent_wire_bytes(encoding: str = "") -> float:
    """Total agent-channel bytes so far (optionally one encoding)."""
    return sum(
        v for k, v in metrics_totals().items()
        if k.startswith("covalent_tpu_agent_wire_bytes_total{")
        and (not encoding or f"encoding={encoding}" in k)
    )


def agent_frames(verb: str, encoding: str = "binary") -> float:
    """Per-verb agent-channel message count from the frame accounting."""
    return sum(
        v for k, v in metrics_totals().items()
        if k.startswith("covalent_tpu_agent_frames_total{")
        and f"verb={verb}" in k and f"encoding={encoding}" in k
    )


def upload_span_sum() -> float:
    """Cumulative seconds spent inside executor.upload spans."""
    from covalent_tpu_plugin.obs.metrics import REGISTRY
    from covalent_tpu_plugin.obs.trace import SPAN_HISTOGRAM

    snap = REGISTRY.snapshot()["metrics"].get(SPAN_HISTOGRAM, {})
    return sum(
        series["sum"]
        for series in snap.get("series", [])
        if series["labels"].get("span") == "executor.upload"
    )


def busy_electron(i: int, seconds: float) -> int:
    """A task with real duration: shows fan-out concurrency honestly
    (trivial electrons are dispatcher-event-loop-bound, so their fan-out
    wall measures per-electron overhead, not parallelism)."""
    import time

    time.sleep(seconds)
    return i


def accelerator_electron(progress_path: str, budget_s: float) -> dict:
    """ALL accelerator phases in one harness process (one backend init).

    Streams one JSON line per subphase to ``progress_path`` so the
    dispatcher-side bench can surface partial results even if this electron
    is later killed on budget overrun.  Self-contained imports per the
    harness contract; requires the package on PYTHONPATH (task_env).
    """
    import json
    import time

    t_start = time.monotonic()
    results: dict = {}

    progress = open(progress_path, "a", buffering=1)

    def report(subphase: str, **data):
        data["at_s"] = round(time.monotonic() - t_start, 1)
        results[subphase] = data
        progress.write(json.dumps({"subphase": subphase, **data}) + "\n")

    def remaining() -> float:
        return budget_s - (time.monotonic() - t_start)

    # Filled by the lm_decode phase; consumed by the lm_serve tail phase
    # (reuses the decode model + measured static-batch wall so the serving
    # arm costs no extra baseline compiles).
    serve_ctx = None

    # -- backend init (the round-1 killer: measure it explicitly) ----------
    t0 = time.monotonic()
    import jax
    import jax.numpy as jnp

    # The persistent compile cache is whatever JAX_COMPILATION_CACHE_DIR
    # named when jax was imported (task_env carries it): report what jax
    # actually holds, so a variable that arrived too late shows as None.
    compile_cache = jax.config.jax_compilation_cache_dir

    devices = jax.devices()
    device_kind = devices[0].device_kind
    backend = devices[0].platform
    report(
        "init",
        init_s=round(time.monotonic() - t0, 2),
        backend=backend,
        device_kind=device_kind,
        n_devices=len(devices),
        compile_cache=compile_cache,
    )

    # Peak bf16 dense TFLOP/s per chip, for MFU (public spec sheets).
    peak_table = {
        "v6": 918.0,        # Trillium / v6e
        "v5p": 459.0,
        "v5": 197.0,        # v5e / v5 litepod
        "v4": 275.0,
        "v3": 123.0,
        "v2": 45.0,
    }
    peak_tflops = None
    kind_lower = device_kind.lower()
    for key in ("v6", "v5p", "v5", "v4", "v3", "v2"):
        if key in kind_lower:
            peak_tflops = peak_table[key]
            break

    def mfu(tflops):
        """Model FLOP utilisation, clamped at the physical ceiling.

        A computed MFU > 1.0 is a measurement error by definition (the
        chip cannot exceed its peak): report 1.0 with the raw value in a
        warning rather than an impossible number (an earlier run emitted 1.05
        once under min-of-2 delta timing; median-of-N makes this rare,
        the clamp makes it impossible).
        """
        if not peak_tflops:
            return None, None
        raw = tflops / peak_tflops
        if raw > 1.0:
            return 1.0, f"measured {raw:.4f} > physical peak; clamped"
        return round(raw, 4), None

    def unit_seconds(dispatch, fetch, target_s: float, cap: int,
                     trials: int = 5):
        """Seconds per dispatched unit, by median-of-N two-batch deltas.

        A large constant per-fetch round trip (~65 ms on the stack this
        was written against) would masquerade as low FLOP throughput.
        Timing a 1-unit batch and a
        k-unit batch and dividing by (k - 1) cancels that constant:
        dispatches are async (they only enqueue), the device queue
        serialises them, and ``fetch`` forces a drain.

        The per-trial delta jitters with the round-trip constant; the
        *median* of N trials is reported (a min would let one low-jitter
        outlier overstate throughput — the >100%-MFU failure
        mode), together with the spread so the artifact carries its own
        error bars.  Returns ``(unit_s, stats_dict)``.
        """
        import statistics as stats_mod

        dispatch()
        fetch()  # compiled + warm
        t0 = time.monotonic()
        dispatch()
        fetch()
        once = time.monotonic() - t0  # includes the round-trip constant
        k = max(2, min(cap, int(target_s / max(once, 1e-6)) + 1))
        deltas = []
        for _ in range(trials):
            t0 = time.monotonic()
            dispatch()
            fetch()
            e1 = time.monotonic() - t0
            t0 = time.monotonic()
            for _ in range(k):
                dispatch()
            fetch()
            ek = time.monotonic() - t0
            if ek > e1:  # jitter can invert tiny deltas; discard, don't clamp
                deltas.append((ek - e1) / (k - 1))
        if not deltas:
            # Every trial jitter-inverted: the single-batch time (round-trip
            # included) is the honest upper bound, never a fabricated rate.
            return once, {"n_deltas": 0, "note": "round-trip bound"}
        unit = stats_mod.median(deltas)
        spread = {
            "n_deltas": len(deltas),
            "unit_ms_median": round(unit * 1e3, 3),
            "unit_ms_min": round(min(deltas) * 1e3, 3),
            "unit_ms_max": round(max(deltas) * 1e3, 3),
        }
        if len(deltas) >= 2:
            spread["unit_ms_stdev"] = round(
                stats_mod.stdev(deltas) * 1e3, 3
            )
        return unit, spread

    # Non-TPU backends (the CPU validation tier) get scaled-down shapes so
    # every subphase still executes end to end within the budget.
    small = backend != "tpu"

    # -- matmul TFLOP/s + MFU (BASELINE config 2) --------------------------
    try:
        n = 1024 if small else 4096
        chain_len = 16
        inv_n = 1.0 / n
        x = jnp.ones((n, n), jnp.bfloat16)
        y = jnp.ones((n, n), jnp.bfloat16)

        @jax.jit
        def mm_chain(a, b):
            # Rescale by 1/n so the chained all-ones product stays exactly 1
            # (a raw chain overflows bf16 to inf after ~10 iterations) —
            # the fetched scalar doubles as a correctness check.
            return jax.lax.fori_loop(
                0,
                chain_len,
                lambda _, acc: jnp.einsum("ij,jk->ik", acc, b) * inv_n,
                a,
            )

        holder = {}

        def dispatch():
            holder["out"] = mm_chain(x, y)

        def fetch():
            # device_get, not block_until_ready: a fetched scalar can't lie.
            holder["check"] = float(jax.device_get(holder["out"][0, 0]))

        unit, spread = unit_seconds(dispatch, fetch, target_s=3.0, cap=40)
        tflops = (2 * n**3 * chain_len) / unit / 1e12
        mfu_val, mfu_warning = mfu(tflops)
        report(
            "matmul",
            n=n,
            chain_len=chain_len,
            tflops=round(tflops, 2),
            mfu=mfu_val,
            **({"mfu_warning": mfu_warning} if mfu_warning else {}),
            peak_tflops=peak_tflops,
            check=holder["check"],  # must be 1.0
            **spread,
        )
    except Exception as error:  # noqa: BLE001
        report("matmul", error=repr(error))

    # -- MNIST MLP training on a multi-batch stream (north-star electron) --
    # An epoch-style pass over DISTINCT batches with a falling loss curve —
    # "trains MNIST end-to-end" (BASELINE config 4) — not a memorize-one-
    # batch throughput proxy.
    if remaining() > 60:
        try:
            import numpy as onp
            import optax
            from flax.training import train_state

            from covalent_tpu_plugin.models.mlp import MLP, synthetic_mnist

            batch_size = 128 if small else 256
            n_batches = 24 if small else 64
            stream = [
                synthetic_mnist(batch_size, seed=i) for i in range(n_batches)
            ]
            images = jnp.asarray(onp.stack([b["image"] for b in stream]))
            labels = jnp.asarray(onp.stack([b["label"] for b in stream]))
            model = MLP()
            state = train_state.TrainState.create(
                apply_fn=model.apply,
                params=model.init(jax.random.PRNGKey(0), images[0])["params"],
                tx=optax.adam(1e-3),
            )

            @jax.jit
            def epoch(state):
                def step(state, batch):
                    def loss_fn(params):
                        logits = state.apply_fn(
                            {"params": params}, batch["image"]
                        )
                        return optax.softmax_cross_entropy_with_integer_labels(
                            logits.astype(jnp.float32), batch["label"]
                        ).mean()

                    loss, grads = jax.value_and_grad(loss_fn)(state.params)
                    return state.apply_gradients(grads=grads), loss

                return jax.lax.scan(
                    step, state, {"image": images, "label": labels}
                )

            state, losses = epoch(state)  # compile + epoch 1 (fresh params)
            curve = jax.device_get(losses).astype(float)
            holder = {"state": state}

            def dispatch():
                holder["state"], holder["losses"] = epoch(holder["state"])

            def fetch():
                holder["last"] = float(jax.device_get(holder["losses"][-1]))

            # Each unit is a full n_batches-step epoch, so the per-fetch
            # round-trip constant amortises n_batches-fold on top of the
            # delta cancellation.
            unit, spread = unit_seconds(
                dispatch, fetch, target_s=3.0, cap=40, trials=3
            )
            report(
                "mnist",
                n_batches=n_batches,
                steps_per_s=round(n_batches / unit, 2),
                loss_first=round(float(curve[:4].mean()), 4),
                loss_last=round(float(curve[-4:].mean()), 4),
                loss_final_epoch=round(holder["last"], 4),
                **spread,
            )
        except Exception as error:  # noqa: BLE001
            report("mnist", error=repr(error))
    else:
        report("mnist", skipped="budget")

    # -- flash attention forward vs dense (long-context hot op) ------------
    if remaining() > 50:
        try:
            from covalent_tpu_plugin.ops.attention import (
                flash_attention,
                mha_reference,
            )

            b, h, s, d = (1, 4, 512, 64) if small else (2, 16, 4096, 64)
            q = jax.random.normal(jax.random.PRNGKey(0), (b, h, s, d), jnp.bfloat16)
            k = jax.random.normal(jax.random.PRNGKey(1), (b, h, s, d), jnp.bfloat16)
            v = jax.random.normal(jax.random.PRNGKey(2), (b, h, s, d), jnp.bfloat16)

            def bench_fwd(fn, cap=24):
                f = jax.jit(fn)
                holder = {}

                def dispatch():
                    holder["out"] = f(q, k, v)

                def fetch():
                    jax.device_get(holder["out"][0, 0, 0, 0])

                return unit_seconds(
                    dispatch, fetch, target_s=2.0, cap=cap, trials=3
                )

            ref_s, _ = bench_fwd(lambda q, k, v: mha_reference(q, k, v, causal=True))
            flash_s, spread = bench_fwd(
                lambda q, k, v: flash_attention(q, k, v, causal=True)
            )
            report(
                "flash_fwd",
                seq_len=s,
                ref_ms=round(ref_s * 1e3, 2),
                flash_ms=round(flash_s * 1e3, 2),
                speedup=round(ref_s / flash_s, 2),
                **spread,
            )
        except Exception as error:  # noqa: BLE001
            report("flash_fwd", error=repr(error))
    else:
        report("flash_fwd", skipped="budget")

    # -- flash attention fwd+bwd (training path) ---------------------------
    if remaining() > 40:
        try:
            from covalent_tpu_plugin.ops.attention import (
                flash_attention,
                mha_reference,
            )

            b, h, s, d = (1, 4, 512, 64) if small else (2, 16, 4096, 64)
            q = jax.random.normal(jax.random.PRNGKey(0), (b, h, s, d), jnp.bfloat16)
            k = jax.random.normal(jax.random.PRNGKey(1), (b, h, s, d), jnp.bfloat16)
            v = jax.random.normal(jax.random.PRNGKey(2), (b, h, s, d), jnp.bfloat16)

            def bench_bwd(fn, cap=12):
                grad_fn = jax.jit(
                    jax.grad(
                        lambda q, k, v: fn(q, k, v).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2),
                    )
                )
                holder = {}

                def dispatch():
                    holder["grads"] = grad_fn(q, k, v)

                def fetch():
                    jax.device_get(holder["grads"][0][0, 0, 0, 0])

                return unit_seconds(
                    dispatch, fetch, target_s=2.0, cap=cap, trials=3
                )

            ref_s, _ = bench_bwd(lambda q, k, v: mha_reference(q, k, v, causal=True))
            flash_s, spread = bench_bwd(
                lambda q, k, v: flash_attention(q, k, v, causal=True)
            )
            report(
                "flash_bwd",
                seq_len=s,
                ref_ms=round(ref_s * 1e3, 2),
                flash_ms=round(flash_s * 1e3, 2),
                speedup=round(ref_s / flash_s, 2),
                **spread,
            )
        except Exception as error:  # noqa: BLE001
            report("flash_bwd", error=repr(error))
    else:
        report("flash_bwd", skipped="budget")

    # -- long context: flash fwd+bwd at S=16k (dense spills/OOMs there), --
    # -- then the same shape through the sliding-window band ---------------
    if remaining() > 40:
        try:
            from covalent_tpu_plugin.ops.attention import flash_attention

            b, h, s, d = (1, 2, 2048, 64) if small else (1, 8, 16384, 64)
            win = 256 if small else 1024
            q = jax.random.normal(jax.random.PRNGKey(0), (b, h, s, d), jnp.bfloat16)
            k = jax.random.normal(jax.random.PRNGKey(1), (b, h, s, d), jnp.bfloat16)
            v = jax.random.normal(jax.random.PRNGKey(2), (b, h, s, d), jnp.bfloat16)

            def bwd_unit(window, iters=16, trials=5):
                """Pure ON-DEVICE fwd+bwd seconds at this shape.

                Data-dependent chain inside one jit: dq feeds the next
                iteration's q, so per-dispatch host overhead appears in
                neither the 1-chain nor the N-chain wall and cancels
                exactly (the two-batch delta method inflated the slow
                arm of this very ratio on an earlier stack).
                """
                import statistics as stats_mod

                def one(q_in):
                    dq = jax.grad(
                        lambda q_: flash_attention(
                            q_, k, v, causal=True, window=window
                        ).astype(jnp.float32).sum()
                    )(q_in)
                    return q_in + (1e-6 * dq).astype(q_in.dtype)

                @jax.jit
                def chain(q0, n):
                    return jax.lax.fori_loop(0, n, lambda i, q_: one(q_), q0)

                jax.device_get(chain(q, iters)[0, 0, 0, 0])  # compile both
                jax.device_get(chain(q, 1)[0, 0, 0, 0])
                samples = []
                for _ in range(trials):
                    t0 = time.monotonic()
                    jax.device_get(chain(q, 1)[0, 0, 0, 0])
                    t1 = time.monotonic() - t0
                    t0 = time.monotonic()
                    jax.device_get(chain(q, iters)[0, 0, 0, 0])
                    tn = time.monotonic() - t0
                    if tn > t1:
                        samples.append((tn - t1) / (iters - 1))
                if not samples:
                    return tn / iters, {"n_deltas": 0,
                                        "note": "chain bound"}
                unit = stats_mod.median(samples)
                spread = {
                    "n_deltas": len(samples),
                    "unit_ms_median": round(unit * 1e3, 3),
                    "unit_ms_min": round(min(samples) * 1e3, 3),
                    "unit_ms_max": round(max(samples) * 1e3, 3),
                    "method": "on-device chain",
                }
                if len(samples) >= 2:
                    spread["unit_ms_stdev"] = round(
                        stats_mod.stdev(samples) * 1e3, 3
                    )
                return unit, spread

            # Exactness probe for the compiled (Mosaic) banded grid: the
            # CPU test tier runs the kernel in interpret mode only, so a
            # Mosaic-specific miscompile of the clamped index maps would
            # otherwise show up as silently wrong numbers here.
            from covalent_tpu_plugin.ops.attention import mha_reference

            pq, pk, pv = (
                jax.random.normal(
                    jax.random.PRNGKey(7 + i), (1, 2, 512, 64), jnp.bfloat16
                )
                for i in range(3)
            )
            probe_err = float(
                jax.device_get(
                    jnp.max(jnp.abs(
                        flash_attention(
                            pq, pk, pv, causal=True, window=96,
                            block_q=128, block_k=128,
                        ).astype(jnp.float32)
                        - mha_reference(
                            pq, pk, pv, causal=True, window=96
                        ).astype(jnp.float32)
                    ))
                )
            )

            unit, spread = bwd_unit(None)
            # attention flops: 4*S^2*D fwd + 10*S^2*D bwd, * 0.5 causal
            # (matches the kernels' own CostEstimates in ops/attention.py)
            att_tflops = 14 * b * h * s * s * d * 0.5 / unit / 1e12
            report(
                "flash_long",
                seq_len=s,
                fwd_bwd_ms=round(unit * 1e3, 2),
                attn_tflops=round(att_tflops, 2),
                note="dense S^2 path spills at this length",
                **spread,
            )
            if remaining() > 25:
                win_unit, win_spread = bwd_unit(win)
                report(
                    "flash_window",
                    seq_len=s,
                    window=win,
                    fwd_bwd_ms=round(win_unit * 1e3, 2),
                    speedup_vs_full=round(unit / win_unit, 2),
                    banded_max_err=round(probe_err, 5),
                    **win_spread,
                )
                # Second band width: w=512's tighter band has a higher
                # tile-geometry ceiling (the w=1k multiple saturates its
                # own ceiling).
                if not small and remaining() > 25:
                    w2 = 512
                    w2_unit, w2_spread = bwd_unit(w2)
                    report(
                        "flash_window_512",
                        seq_len=s,
                        window=w2,
                        fwd_bwd_ms=round(w2_unit * 1e3, 2),
                        speedup_vs_full=round(unit / w2_unit, 2),
                        **w2_spread,
                    )
            else:
                report("flash_window", skipped="budget")
        except Exception as error:  # noqa: BLE001
            report("flash_long", error=repr(error))
    else:
        report("flash_long", skipped="budget")
        report("flash_window", skipped="budget")

    # -- 125M-class LM train step + MFU (BASELINE config 5's model, 1 chip) -
    if remaining() > 75:
        try:
            import optax

            from covalent_tpu_plugin.models.train import (
                TrainState,
                lm_loss,
            )
            from covalent_tpu_plugin.models.transformer import (
                TransformerLM,
                lm_125m_config,
            )

            # Winner of an earlier v5e sweep (not re-measured here): unrolled
            # layers let XLA optimise across block boundaries (+33% over
            # lax.scan), dots-remat recomputes only the cheap elementwise
            # ops, and bsz 8 saturates the chip without b16's compile cost.
            if small:
                bsz, seq = 2, 256
                config = lm_125m_config(
                    max_seq=seq, n_layers=2, d_model=256, n_heads=4,
                    d_ff=1024, vocab_size=4096, remat=True,
                    remat_policy="dots", scan_layers=False,
                )
            else:
                bsz, seq = 8, 1024
                config = lm_125m_config(
                    max_seq=seq, remat=True, remat_policy="dots",
                    scan_layers=False,
                )
            model = TransformerLM(config=config)
            # seq+1 tokens: lm_loss shifts by one, so the model sees exactly
            # `seq` positions (a tileable multiple of 128 for flash).
            tokens = jax.random.randint(
                jax.random.PRNGKey(0), (bsz, seq + 1), 0, config.vocab_size
            )
            params = model.init(jax.random.PRNGKey(1), tokens[:, :-1])["params"]
            state = TrainState.create(
                apply_fn=model.apply, params=params, tx=optax.adamw(3e-4)
            )
            n_params = model.parameter_count(params)

            @jax.jit
            def step(state, tokens):
                loss, grads = jax.value_and_grad(
                    lambda p: lm_loss(p, state.apply_fn, {"tokens": tokens})
                )(state.params)
                return state.apply_gradients(grads=grads), loss

            holder = {"state": state}

            def dispatch():
                holder["state"], holder["loss"] = step(holder["state"], tokens)

            def fetch():
                holder["final"] = float(jax.device_get(holder["loss"]))

            step_s, spread = unit_seconds(dispatch, fetch, target_s=4.0, cap=10)
            final_loss = holder["final"]
            # 6ND for fwd+bwd (+ remat recompute ~ +1 fwd -> 8ND ceiling;
            # report the standard 6ND so MFU is comparable across frameworks)
            lm_tflops = 6 * n_params * bsz * seq / step_s / 1e12
            mfu_val, mfu_warning = mfu(lm_tflops)
            report(
                "lm_step",
                n_params=n_params,
                step_ms=round(step_s * 1e3, 1),
                tokens_per_s=round(bsz * seq / step_s),
                tflops_6nd=round(lm_tflops, 2),
                mfu=mfu_val,
                **({"mfu_warning": mfu_warning} if mfu_warning else {}),
                final_loss=round(final_loss, 4),
                **spread,
            )

            # Fused-xent arm: the same step with the
            # row-tiled loss (ops/xent.py) — the lm_head matmul runs
            # bf16-native and the (B,S,V) logits tensor never reaches
            # HBM.  A/B against the standard arm above; own try so a
            # fused failure can't void the standard number.  The gate is
            # deliberately conservative (150 s, not this phase's usual
            # 40): the serving wall (lm_serve, the round's #1 ask) runs
            # LAST and must not lose its budget to a new mid-order arm.
            if remaining() > 150:
                try:
                    v_chunk = min(8192, config.vocab_size)

                    @jax.jit
                    def step_fused(state, tokens):
                        loss, grads = jax.value_and_grad(
                            lambda p: lm_loss(
                                p, state.apply_fn, {"tokens": tokens},
                                vocab_chunk=v_chunk,
                            )
                        )(state.params)
                        return state.apply_gradients(grads=grads), loss

                    holder_f = {"state": holder["state"]}

                    def dispatch_f():
                        holder_f["state"], holder_f["loss"] = step_fused(
                            holder_f["state"], tokens
                        )

                    def fetch_f():
                        holder_f["final"] = float(
                            jax.device_get(holder_f["loss"])
                        )

                    fused_s, fspread = unit_seconds(
                        dispatch_f, fetch_f, target_s=4.0, cap=10
                    )
                    f_tflops = 6 * n_params * bsz * seq / fused_s / 1e12
                    f_mfu, f_warn = mfu(f_tflops)
                    report(
                        "lm_step_fused",
                        vocab_chunk=v_chunk,
                        step_ms=round(fused_s * 1e3, 1),
                        tokens_per_s=round(bsz * seq / fused_s),
                        tflops_6nd=round(f_tflops, 2),
                        mfu=f_mfu,
                        **({"mfu_warning": f_warn} if f_warn else {}),
                        speedup_vs_std_step=round(step_s / fused_s, 3),
                        final_loss=round(holder_f["final"], 4),
                        **fspread,
                    )
                except Exception as error:  # noqa: BLE001
                    report("lm_step_fused", error=repr(error))
            else:
                report("lm_step_fused", skipped="budget")
        except Exception as error:  # noqa: BLE001
            report("lm_step", error=repr(error))
    else:
        report("lm_step", skipped="budget")

    # -- 125M generation throughput (KV-cache decode) ----------------------
    if remaining() > 60:
        try:
            from covalent_tpu_plugin.models import (
                TransformerLM,
                generate,
                inference_params,
                lm_125m_config,
            )

            # Serving config (from an earlier v5e sweep): bf16 inference
            # weights halve the per-step HBM reads and unrolled layers
            # cut per-step overheads — +48% tokens/s over the scanned
            # f32-master baseline at batch 8.
            if small:
                gen_config = lm_125m_config(
                    max_seq=128, n_layers=2, d_model=256, n_heads=4,
                    d_ff=1024, vocab_size=4096, scan_layers=False,
                )
                bsz, prompt_len, new_tokens = 2, 16, 32
            else:
                gen_config = lm_125m_config(max_seq=512, scan_layers=False)
                bsz, prompt_len, new_tokens = 8, 128, 128
            model = TransformerLM(gen_config)
            prompt = jax.random.randint(
                jax.random.PRNGKey(0), (bsz, prompt_len), 0,
                gen_config.vocab_size,
            )
            params = inference_params(
                model.init(jax.random.PRNGKey(1), prompt)["params"]
            )
            import statistics as stats_mod

            gen = jax.jit(
                lambda p, t: generate(model, p, t, max_new_tokens=new_tokens)
            )
            jax.device_get(gen(params, prompt)[0, -1])  # compile + warm

            def time_gen(fn, p):
                t0 = time.monotonic()
                out = fn(p, prompt)
                jax.device_get(out[0, -1])
                return time.monotonic() - t0

            # Weight-only int8 serving (models/quant.py): halves the
            # per-step HBM reads again on top of the bf16 cast.  Own try
            # so a quant failure can't lose the bf16 line below.
            qgen = qparams = None
            if remaining() > 30:
                try:
                    from covalent_tpu_plugin.models import quantize_lm

                    qmodel, qparams = quantize_lm(model, params)
                    qparams = inference_params(qparams)
                    qgen = jax.jit(
                        lambda p, t: generate(
                            qmodel, p, t, max_new_tokens=new_tokens
                        )
                    )
                    jax.device_get(qgen(qparams, prompt)[0, -1])  # warm
                except Exception as error:  # noqa: BLE001
                    report("lm_decode_int8", error=repr(error))
                    qgen = None

            # int8 KV cache: halves the per-step CACHE reads (the other
            # bandwidth half); also its own try.
            kvq_gen = None
            if remaining() > 30:
                try:
                    import dataclasses as _dc

                    kvq_model = TransformerLM(
                        _dc.replace(gen_config, quantized_kv_cache=True)
                    )
                    kvq_gen = jax.jit(
                        lambda p, t: generate(
                            kvq_model, p, t, max_new_tokens=new_tokens
                        )
                    )
                    jax.device_get(kvq_gen(params, prompt)[0, -1])  # warm
                except Exception as error:  # noqa: BLE001
                    report("lm_decode_kvq", error=repr(error))
                    kvq_gen = None

            # The FULL quantized serving stack: int8 weights AND int8 KV
            # in one model — both bandwidth halves cut together.
            full_q_gen = None
            if qgen is not None and remaining() > 30:
                try:
                    import dataclasses as _dc

                    fullq_model = TransformerLM(
                        _dc.replace(
                            qmodel.config, quantized_kv_cache=True
                        )
                    )
                    full_q_gen = jax.jit(
                        lambda p, t: generate(
                            fullq_model, p, t, max_new_tokens=new_tokens
                        )
                    )
                    jax.device_get(full_q_gen(qparams, prompt)[0, -1])
                except Exception as error:  # noqa: BLE001
                    report("lm_decode_fullq", error=repr(error))
                    full_q_gen = None

            # Like-for-like A/B: alternate bf16/int8 measurements inside
            # one phase so drift hits both arms equally.  The int8 arm
            # keeps its own try at measurement time too — a quant-side
            # failure mid-loop must not void the bf16 numbers.
            bf16_times, int8_times, kvq_times = [], [], []
            fullq_times = []
            for _ in range(3):
                bf16_times.append(time_gen(gen, params))
                if qgen is not None:
                    try:
                        int8_times.append(time_gen(qgen, qparams))
                    except Exception as error:  # noqa: BLE001
                        report("lm_decode_int8", error=repr(error))
                        qgen, int8_times = None, []
                if kvq_gen is not None:
                    try:
                        kvq_times.append(time_gen(kvq_gen, params))
                    except Exception as error:  # noqa: BLE001
                        report("lm_decode_kvq", error=repr(error))
                        kvq_gen, kvq_times = None, []
                if full_q_gen is not None:
                    try:
                        fullq_times.append(time_gen(full_q_gen, qparams))
                    except Exception as error:  # noqa: BLE001
                        report("lm_decode_fullq", error=repr(error))
                        full_q_gen, fullq_times = None, []
            elapsed = stats_mod.median(bf16_times)
            # One batched prefill + (new_tokens - 1) decode steps share the
            # wall; metrics are labelled end-to-end, not per decode step.
            report(
                "lm_decode",
                prompt_len=prompt_len,
                new_tokens=new_tokens,
                batch=bsz,
                e2e_tokens_per_s=round(bsz * new_tokens / elapsed),
                e2e_ms_per_new_token=round(elapsed / new_tokens * 1e3, 2),
                e2e_s_spread=[round(t, 3) for t in sorted(bf16_times)],
            )
            serve_ctx = {
                "model": model, "params": params, "config": gen_config,
                "batch": bsz, "prompt_len": prompt_len,
                "new_tokens": new_tokens, "static_batch_s": elapsed,
            }
            if int8_times:
                q_elapsed = stats_mod.median(int8_times)
                report(
                    "lm_decode_int8",
                    batch=bsz,
                    tokens_per_s=round(bsz * new_tokens / q_elapsed),
                    ms_per_new_token=round(q_elapsed / new_tokens * 1e3, 2),
                    speedup_vs_bf16_same_phase=round(elapsed / q_elapsed, 3),
                    e2e_s_spread=[round(t, 3) for t in sorted(int8_times)],
                )
            elif qgen is None and remaining() <= 30:
                report("lm_decode_int8", skipped="budget")
            if kvq_times:
                kv_elapsed = stats_mod.median(kvq_times)
                report(
                    "lm_decode_kvq",
                    batch=bsz,
                    tokens_per_s=round(bsz * new_tokens / kv_elapsed),
                    speedup_vs_bf16_same_phase=round(
                        elapsed / kv_elapsed, 3
                    ),
                    e2e_s_spread=[round(t, 3) for t in sorted(kvq_times)],
                )
            if fullq_times:
                fq_elapsed = stats_mod.median(fullq_times)
                report(
                    "lm_decode_fullq",
                    batch=bsz,
                    tokens_per_s=round(bsz * new_tokens / fq_elapsed),
                    speedup_vs_bf16_same_phase=round(
                        elapsed / fq_elapsed, 3
                    ),
                    e2e_s_spread=[round(t, 3) for t in sorted(fullq_times)],
                )
        except Exception as error:  # noqa: BLE001
            report("lm_decode", error=repr(error))
    else:
        report("lm_decode", skipped="budget")

    # -- speculative decoding: trained draft/target pair -------------------
    # The serving stack's most advanced feature, previously proven exact
    # but never proven USEFUL: train a 2-layer draft + 6-layer target on
    # the learnable synthetic stream (models/data.py — the affine bigram
    # map drives both models to near-agreement in a few hundred steps),
    # then measure acceptance rate and end-to-end tokens/s vs plain decode
    # of the SAME target.
    if remaining() > 100:
        try:
            import statistics as stats_mod

            import optax

            from covalent_tpu_plugin.models import (
                TransformerLM,
                generate,
                inference_params,
                lm_125m_config,
                speculative_generate,
            )
            from covalent_tpu_plugin.models.data import synthetic_lm_batch
            from covalent_tpu_plugin.models.train import TrainState, lm_loss

            # The target must be MUCH heavier per decode step than the
            # draft or speculation cannot win (the r4 first run used a
            # 256-d toy target: accept 0.97, speedup 0.95 — every step
            # was launch-overhead-bound, so 4 draft steps + 1 verify cost
            # exactly 5 plain steps).  Production shape: the 125M-class
            # body (768×12) as target, a 128×2 draft — the setting the
            # feature exists for.
            if small:
                vocab, seq, sbsz = 512, 128, 16
                t_steps, d_steps = 30, 60
                spec_new, spec_prompt, spec_bsz = 48, 16, 2
                t_dims = dict(d_model=256, n_layers=6, n_heads=4, d_ff=1024)
            else:
                vocab, seq, sbsz = 512, 128, 32
                t_steps, d_steps = 120, 300
                spec_new, spec_prompt, spec_bsz = 192, 32, 8
                t_dims = {}  # 125M-class defaults (768 x 12)
            # draft_len 6 (not 4): acceptance on the trained pair runs
            # ~0.97, so a longer window amortises each verify slab
            # further — measured 1.14x at k=4.
            draft_len = 4 if small else 6
            cap = spec_prompt + spec_new + draft_len + 1
            t_cfg = lm_125m_config(
                vocab_size=vocab, max_seq=max(seq, cap),
                scan_layers=False, **t_dims,
            )
            d_cfg = lm_125m_config(
                vocab_size=vocab, d_model=128, n_layers=2, n_heads=4,
                d_ff=512, max_seq=max(seq, cap), scan_layers=False,
            )

            def train_lm(cfg, model_seed, train_steps):
                model = TransformerLM(cfg)
                tokens0 = jnp.asarray(
                    synthetic_lm_batch(sbsz, seq + 1, vocab, seed=0)["tokens"]
                )
                params = model.init(
                    jax.random.PRNGKey(model_seed), tokens0[:, :-1]
                )["params"]
                state = TrainState.create(
                    apply_fn=model.apply, params=params, tx=optax.adamw(1e-3)
                )

                @jax.jit
                def step(state, tokens):
                    loss, grads = jax.value_and_grad(
                        lambda p: lm_loss(
                            p, state.apply_fn, {"tokens": tokens}
                        )
                    )(state.params)
                    return state.apply_gradients(grads=grads), loss

                # Distinct batches each step (seed advances): honest
                # streaming, same rule the data module's stream uses.
                # Bail early when the phase budget runs low — a shorter
                # training run lowers acceptance but still completes the
                # phase (better than the parent killing the electron).
                # Always takes step 0 (compile can eat the margin BEFORE
                # the loop; a zero-step bail would leave loss undefined).
                loss = None
                for i in range(train_steps):
                    if i and i % 25 == 0 and remaining() < 60:
                        break
                    tokens = jnp.asarray(
                        synthetic_lm_batch(
                            sbsz, seq + 1, vocab, seed=1 + i
                        )["tokens"]
                    )
                    state, loss = step(state, tokens)
                return model, state.params, float(jax.device_get(loss))

            target_model, target_params, t_loss = train_lm(t_cfg, 1, t_steps)
            draft_model, draft_params, d_loss = train_lm(d_cfg, 2, d_steps)
            target_params = inference_params(target_params)
            draft_params = inference_params(draft_params)
            if remaining() < 45:
                # Training (or its compiles) ate the margin: the generate
                # compiles ahead are the expensive part — skip cleanly
                # rather than letting the parent kill the electron.
                raise TimeoutError("budget exhausted after draft training")

            prompt = jnp.asarray(
                synthetic_lm_batch(spec_bsz, spec_prompt, vocab, seed=999)[
                    "tokens"
                ]
            )
            plain = jax.jit(
                lambda p, t: generate(
                    target_model, p, t, max_new_tokens=spec_new
                )
            )
            spec = jax.jit(
                lambda tp, dp, t: speculative_generate(
                    target_model, tp, draft_model, dp, t, spec_new,
                    draft_len=draft_len, return_stats=True,
                )
            )
            out_plain = plain(target_params, prompt)
            out_spec, stats = spec(target_params, draft_params, prompt)
            jax.device_get(out_spec[0, -1])  # compile + warm both
            jax.device_get(out_plain[0, -1])
            exact = bool(
                jax.device_get((out_plain == out_spec).all())
            )  # bit-exactness contract, checked on-device
            rounds = int(jax.device_get(stats["rounds"]))
            # Each round commits (accepted drafts + 1): the +1 is the
            # correction or bonus token.  spec_new - 1 tokens came from
            # rounds rounds (token #1 is the prefill's), so accepted
            # drafts = spec_new - 1 - rounds of rounds * draft_len
            # proposals — the standard acceptance-rate definition.
            accept = (spec_new - 1 - rounds) / max(rounds * draft_len, 1)

            plain_t, spec_t = [], []
            for _ in range(3):  # alternating A/B, median
                t0 = time.monotonic()
                jax.device_get(plain(target_params, prompt)[0, -1])
                plain_t.append(time.monotonic() - t0)
                t0 = time.monotonic()
                out, _ = spec(target_params, draft_params, prompt)
                jax.device_get(out[0, -1])
                spec_t.append(time.monotonic() - t0)
            plain_s = stats_mod.median(plain_t)
            spec_s = stats_mod.median(spec_t)
            report(
                "lm_spec",
                target_loss=round(t_loss, 3),
                draft_loss=round(d_loss, 3),
                exact=exact,
                rounds=rounds,
                draft_len=draft_len,
                accept_rate=round(accept, 3),
                plain_tokens_per_s=round(spec_bsz * spec_new / plain_s),
                spec_tokens_per_s=round(spec_bsz * spec_new / spec_s),
                speedup=round(plain_s / spec_s, 3),
                plain_s_spread=[round(t, 3) for t in sorted(plain_t)],
                spec_s_spread=[round(t, 3) for t in sorted(spec_t)],
            )

            # Composed serving stack: the SAME spec machinery over an
            # int8-weight + int8-KV target (tests prove the composition
            # bit-exact vs the quantized target's own decode; this arm
            # measures it).  Own try: a quant failure must not void the
            # float lm_spec numbers above.
            if remaining() > 60:
                try:
                    import dataclasses as _dc

                    from covalent_tpu_plugin.models import quantize_lm

                    qt_model, qt_params = quantize_lm(
                        target_model, target_params
                    )
                    qt_model = TransformerLM(
                        _dc.replace(
                            qt_model.config, quantized_kv_cache=True
                        )
                    )
                    qplain = jax.jit(
                        lambda p, t: generate(
                            qt_model, p, t, max_new_tokens=spec_new
                        )
                    )
                    qspec = jax.jit(
                        lambda tp, dp, t: speculative_generate(
                            qt_model, tp, draft_model, dp, t, spec_new,
                            draft_len=draft_len, return_stats=True,
                        )
                    )
                    out_qp = qplain(qt_params, prompt)
                    out_qs, qstats = qspec(qt_params, draft_params, prompt)
                    jax.device_get(out_qp[0, -1])  # compile + warm
                    jax.device_get(out_qs[0, -1])
                    q_exact = bool(
                        jax.device_get((out_qp == out_qs).all())
                    )
                    q_rounds = int(jax.device_get(qstats["rounds"]))
                    q_accept = (spec_new - 1 - q_rounds) / max(
                        q_rounds * draft_len, 1
                    )
                    qp_t, qs_t = [], []
                    for _ in range(3):  # alternating A/B, median
                        t0 = time.monotonic()
                        jax.device_get(qplain(qt_params, prompt)[0, -1])
                        qp_t.append(time.monotonic() - t0)
                        t0 = time.monotonic()
                        out, _ = qspec(qt_params, draft_params, prompt)
                        jax.device_get(out[0, -1])
                        qs_t.append(time.monotonic() - t0)
                    qp_s = stats_mod.median(qp_t)
                    qs_s = stats_mod.median(qs_t)
                    report(
                        "lm_spec_quant",
                        exact=q_exact,
                        rounds=q_rounds,
                        accept_rate=round(q_accept, 3),
                        plain_tokens_per_s=round(
                            spec_bsz * spec_new / qp_s
                        ),
                        spec_tokens_per_s=round(
                            spec_bsz * spec_new / qs_s
                        ),
                        speedup=round(qp_s / qs_s, 3),
                        plain_s_spread=[round(t, 3) for t in sorted(qp_t)],
                        spec_s_spread=[round(t, 3) for t in sorted(qs_t)],
                    )
                except Exception as error:  # noqa: BLE001
                    report("lm_spec_quant", error=repr(error))
            else:
                report("lm_spec_quant", skipped="budget")
        except Exception as error:  # noqa: BLE001
            report("lm_spec", error=repr(error))
    else:
        report("lm_spec", skipped="budget")

    # -- continuous batching serving loop (beyond-parity; models/serve.py) -
    # A mixed-budget workload (half short, half long requests) through
    # fixed serving slots with rolling admission, vs static wave batching.
    # The static arm needs NO extra device work: a wave is exactly the
    # (batch, prompt_len) -> new_tokens generate() the lm_decode phase
    # already timed, so its wall is len(waves) * that measurement.  Step
    # accounting is structural (host arithmetic, sync-quantized the way
    # the real loop admits).  Runs last: it is the bonus phase that gets
    # skipped first when the budget is tight.
    if serve_ctx is not None and remaining() > 45:
        try:
            import numpy as np

            from covalent_tpu_plugin.models import (
                continuous_generate,
                step_accounting,
            )

            s_model = serve_ctx["model"]
            s_params = serve_ctx["params"]
            s_cfg = serve_ctx["config"]
            slots = serve_ctx["batch"]
            s_plen = serve_ctx["prompt_len"]
            long_cap = serve_ctx["new_tokens"]
            short_cap = max(2, long_cap // 4)
            n_req = 2 * slots
            # Admission granularity: the host only syncs every `sync`
            # decode steps, and each sync blocks on the device.  Matching
            # the short budget keeps quantization stranding negligible.
            sync = min(32, max(8, short_cap))
            keys = jax.random.split(jax.random.PRNGKey(7), n_req)
            s_prompts = [
                np.asarray(
                    jax.random.randint(
                        keys[i], (s_plen,), 0, s_cfg.vocab_size
                    ),
                    np.int32,
                )
                for i in range(n_req)
            ]
            caps = [short_cap if i % 2 else long_cap for i in range(n_req)]

            serve_stats: dict = {}

            def run_serve():
                return continuous_generate(
                    s_model, s_params, s_prompts, caps,
                    max_batch=slots, sync_steps=sync, stats=serve_stats,
                )

            t0 = time.monotonic()
            outs = run_serve()  # compile + warm
            compile_wall = time.monotonic() - t0
            complete = all(
                o is not None and o.size == s_plen + c
                for o, c in zip(outs, caps)
            )

            # Structural decode-step accounting, shared with
            # benchmarks/serve_bench.py so the model cannot drift from
            # the admission rule continuous_generate implements.
            steps = step_accounting(caps, slots, sync)
            static_steps = steps["static_wave_steps"]
            cont_steps = steps["continuous_steps_sync"]
            n_waves = -(-n_req // slots)
            static_wall = n_waves * serve_ctx["static_batch_s"]
            structural = {
                "n_requests": n_req,
                "max_batch": slots,
                "sync_steps": sync,
                # Counters measured by the host loop itself
                # (models/serve.py `stats`): fused admission waves and
                # blocking fetches.
                "prefill_passes": serve_stats.get("prefill_passes"),
                "sync_fetches": serve_stats.get("sync_fetches"),
                "device_chunks": serve_stats.get("device_chunks"),
                "caps_short_long": [short_cap, long_cap],
                "complete": complete,
                "compile_wall_s": round(compile_wall, 2),
                "step_reduction_vs_static": round(
                    static_steps / cont_steps, 2
                ),
            }
            if remaining() < 12:
                # Compile ate the tail of the budget: salvage the
                # structural line rather than dying mid-timing with no
                # lm_serve report at all.
                report("lm_serve", **structural, skipped_timing="budget")
            else:
                serve_walls = []
                for _ in range(2):
                    t0 = time.monotonic()
                    outs = run_serve()
                    serve_walls.append(time.monotonic() - t0)
                wall = min(serve_walls)
                report(
                    "lm_serve",
                    **structural,
                    tokens_per_s=round(sum(caps) / wall),
                    wall_s=round(wall, 3),
                    wall_speedup_vs_static_waves=round(
                        static_wall / wall, 2
                    ),
                    serve_s_spread=[round(t, 3) for t in sorted(serve_walls)],
                )
        except Exception as error:  # noqa: BLE001
            report("lm_serve", error=repr(error))
    elif serve_ctx is not None:
        report("lm_serve", skipped="budget")

    progress.close()
    return results


async def tail_progress(path: str, collected: dict, stop: asyncio.Event) -> None:
    """Re-emit the accelerator electron's subphase lines as they appear."""
    pos = 0
    while True:
        try:
            with open(path) as f:
                f.seek(pos)
                chunk = f.read()
            # Only consume complete lines; a partial line stays for later.
            if chunk:
                complete, _, _ = chunk.rpartition("\n")
                for line in complete.splitlines():
                    if not line.strip():
                        continue
                    try:
                        data = json.loads(line)
                    except ValueError:
                        continue
                    collected[data.get("subphase", "?")] = data
                    emit({"phase": f"tpu.{data.pop('subphase', '?')}", **data})
                pos += len(complete) + (1 if complete else 0)
        except FileNotFoundError:
            pass
        if stop.is_set():
            return
        await asyncio.sleep(0.5)


async def main() -> int:
    workdir = f"/tmp/covalent-tpu-bench-{os.getpid()}"
    repo_root = os.path.dirname(os.path.abspath(__file__))
    executor = TPUExecutor(
        transport="local",
        cache_dir=f"{workdir}/cache",
        remote_cache=f"{workdir}/remote",
        python_path=sys.executable,
        poll_freq=0.2,
        pool_preload="cloudpickle",
        defer_cleanup=True,
        task_env={
            "PYTHONPATH": repo_root + os.pathsep + os.environ.get("PYTHONPATH", ""),
            "JAX_COMPILATION_CACHE_DIR": JAX_CACHE_DIR,
        },
    )
    emit({"phase": "start", "pid": os.getpid(), "budgets_s": {
        "overhead": OVERHEAD_BUDGET_S, "fanout": FANOUT_BUDGET_S,
        "tpu": TPU_BUDGET_S,
    }})

    # Start the introspection plane before the first phase: the history
    # sampler needs to be recording WHILE phases run for their emitted
    # timelines to have points (0.25 s ticks — bench phases are seconds
    # long), and the SLO engine evaluates on every sample.
    try:
        from covalent_tpu_plugin.obs.history import ensure_history
        from covalent_tpu_plugin.obs.slo import ensure_slo_engine

        if os.environ.get("COVALENT_TPU_HISTORY_S"):
            ensure_history()  # env wins, incl. "0"/"off" to disable
        else:
            ensure_history(interval_s=0.25)
        ensure_slo_engine()
        from covalent_tpu_plugin.obs.tracestore import ensure_trace_store

        # Keep EVERY trace for the bench run (env still wins): the serve
        # phases' latency_attribution blocks and the CI completeness
        # assertions need each request's waterfall, not a 10% sample.
        ensure_trace_store().sample = float(
            os.environ.get("COVALENT_TPU_TRACE_SAMPLE", "") or 1.0
        )
    except Exception as error:  # noqa: BLE001 - observability never fatal
        emit({"phase": "introspection", "error": repr(error)})

    summary: dict = {}

    # ---- phase 1: dispatch overhead (the headline metric) ----------------
    overhead = None
    try:
        if "overhead" not in BENCH_PHASES:
            raise _PhaseSkipped

        async def overhead_phase():
            # Warm the pooled transport + agent; steady state is what an
            # N-electron lattice pays per electron.
            await executor.run(
                trivial_electron, [0], {}, {"dispatch_id": "warm", "node_id": 0}
            )
            overheads = []
            singles = []
            wall_overheads = []
            for i in range(OVERHEAD_PROBES):
                t0 = time.perf_counter()
                await executor.run(
                    trivial_electron, [i], {}, {"dispatch_id": "probe", "node_id": i}
                )
                singles.append(time.perf_counter() - t0)
                overheads.append(executor.last_timings["overhead"])
                wall_overheads.append(
                    executor.last_timings.get("wall_overhead", 0.0)
                )
            return overheads, singles, wall_overheads

        wire0 = wire_up_bytes()
        overheads, singles, wall_overheads = await asyncio.wait_for(
            overhead_phase(), OVERHEAD_BUDGET_S
        )
        overhead = statistics.median(overheads)
        summary["dispatch_overhead_s"] = round(overhead, 4)
        # Stage spans SUM pipelined work; the wall view is what the caller
        # actually waited with serialization overlapping the dial.
        summary["dispatch_wall_overhead_s"] = round(
            statistics.median(wall_overheads), 4
        )
        summary["electron_wall_s"] = round(statistics.median(singles), 4)
        summary["dispatch_overhead_ms_stdev"] = spread_stats(
            overheads, "overhead"
        ).get("overhead_ms_stdev")
        # SLO view: percentile summary of the wall overhead (what a caller
        # actually waited beyond the task), asserted against the dispatch
        # budget so CI turns red the day the control plane regresses.
        summary["wall_overhead_p50_s"] = round(
            percentile(wall_overheads, 0.50), 4
        )
        summary["wall_overhead_p95_s"] = round(
            percentile(wall_overheads, 0.95), 4
        )
        summary["wall_overhead_budget_s"] = WALL_OVERHEAD_BUDGET_S
        summary["wall_overhead_within_budget"] = (
            summary["wall_overhead_p95_s"] <= WALL_OVERHEAD_BUDGET_S
        )
        emit({"phase": "overhead", "dispatch_overhead_s": summary[
            "dispatch_overhead_s"], "per_probe": [round(o, 4) for o in overheads],
            "electron_wall_s": summary["electron_wall_s"],
            "wall_overhead_s": summary["dispatch_wall_overhead_s"],
            "wall_overhead_p50_s": summary["wall_overhead_p50_s"],
            "wall_overhead_p95_s": summary["wall_overhead_p95_s"],
            "wall_overhead_within_budget":
                summary["wall_overhead_within_budget"],
            # Per-stage latency breakdown of the final probe (same keys as
            # last_timings: connect/stage/upload/submit/execute/fetch/...).
            "breakdown": {
                k: round(v, 5) for k, v in executor.last_timings.items()
                if isinstance(v, (int, float))
            },
            "wire_bytes": round(wire_up_bytes() - wire0, 1),
            **spread_stats(overheads, "overhead"),
            **spread_stats(singles, "electron_wall")})
    except _PhaseSkipped:
        emit({"phase": "overhead", "skipped": "BENCH_PHASES"})
    except Exception as error:  # noqa: BLE001
        emit({"phase": "overhead", "error": repr(error)})

    # ---- phase 1b: telemetry tax (obs-on vs obs-off wall delta) ----------
    # The fleet observability plane (event stream + heartbeats + backhaul +
    # ops endpoint) must never become the new hot path: measure the same
    # trivial electron with everything on vs everything off
    # (COVALENT_TPU_METRICS=0 semantics: no events, no heartbeats) and
    # assert the per-electron delta stays under OBS_TAX_BUDGET_PCT.
    try:
        if "obs_tax" not in BENCH_PHASES:
            raise _PhaseSkipped
        from covalent_tpu_plugin.obs import events as obs_events
        from covalent_tpu_plugin.obs.opsserver import (
            ensure_ops_server,
            shutdown_ops_server,
        )

        OBS_TAX_PROBES = 7

        async def tax_arm(obs_on: bool) -> list:
            arm = "on" if obs_on else "off"
            # Agent (pool) mode on both arms: completion is PUSHED, so the
            # wall numbers measure real work, not poll-schedule alignment
            # (a poll-based arm quantizes to the probe boundary, which
            # dwarfs any telemetry delta with bimodal noise).
            arm_executor = TPUExecutor(
                transport="local",
                cache_dir=f"{workdir}/cache_obs_{arm}",
                remote_cache=f"{workdir}/remote_obs_{arm}",
                python_path=sys.executable,
                poll_freq=0.2,
                heartbeat_interval=0.5 if obs_on else 0.0,
                task_env={
                    "PYTHONPATH": repo_root + os.pathsep
                    + os.environ.get("PYTHONPATH", ""),
                },
            )
            if obs_on:
                obs_events.configure(f"{workdir}/obs_tax_events.jsonl")
                ensure_ops_server(port=0)
            else:
                obs_events.configure(None)
            walls = []
            try:
                await arm_executor.run(
                    trivial_electron, [0], {},
                    {"dispatch_id": f"taxwarm{arm}", "node_id": 0},
                )
                for i in range(OBS_TAX_PROBES):
                    t0 = time.perf_counter()
                    await arm_executor.run(
                        trivial_electron, [i], {},
                        {"dispatch_id": f"tax{arm}", "node_id": i},
                    )
                    walls.append(time.perf_counter() - t0)
            finally:
                await arm_executor.close()
                if obs_on:
                    shutdown_ops_server()
                obs_events.reset()
            return walls

        async def obs_tax_phase():
            # off first, then on: any residual warmup bias favors the OFF
            # arm, making the <budget assertion strictly harder to pass.
            off_walls = await tax_arm(False)
            on_walls = await tax_arm(True)
            return on_walls, off_walls

        on_walls, off_walls = await asyncio.wait_for(
            obs_tax_phase(), OVERHEAD_BUDGET_S
        )
        on_s = statistics.median(on_walls)
        off_s = statistics.median(off_walls)
        tax_pct = (on_s - off_s) / off_s * 100.0
        # 15 ms absolute floor keeps subprocess-spawn jitter from failing a
        # run whose relative delta is noise, not telemetry cost.
        tax_ok = on_s <= off_s * (1.0 + OBS_TAX_BUDGET_PCT / 100.0) + 0.015
        summary["obs_tax_on_wall_s"] = round(on_s, 4)
        summary["obs_tax_off_wall_s"] = round(off_s, 4)
        summary["obs_tax_pct"] = round(tax_pct, 2)
        summary["obs_tax_budget_pct"] = OBS_TAX_BUDGET_PCT
        summary["obs_tax_ok"] = tax_ok
        emit({
            "phase": "obs_tax",
            "on_wall_s": summary["obs_tax_on_wall_s"],
            "off_wall_s": summary["obs_tax_off_wall_s"],
            "tax_pct": summary["obs_tax_pct"],
            "budget_pct": OBS_TAX_BUDGET_PCT,
            "ok": tax_ok,
            **spread_stats(on_walls, "obs_on_wall"),
            **spread_stats(off_walls, "obs_off_wall"),
        })
    except _PhaseSkipped:
        emit({"phase": "obs_tax", "skipped": "BENCH_PHASES"})
    except Exception as error:  # noqa: BLE001
        emit({"phase": "obs_tax", "error": repr(error)})

    # ---- phase 2: 8-electron fan-out (BASELINE config 3) -----------------
    async def fanout8(fn, extra_args, dispatch_id):
        t0 = time.perf_counter()
        await asyncio.gather(
            *(
                executor.run(
                    fn, [i, *extra_args], {},
                    {"dispatch_id": dispatch_id, "node_id": i},
                )
                for i in range(8)
            )
        )
        return time.perf_counter() - t0

    try:
        if "fanout" not in BENCH_PHASES:
            raise _PhaseSkipped

        async def fanout_trials():
            # 3 trials -> median + spread (r3 verdict: honest statistics
            # on every phase, not just the TPU ones).
            return [await fanout8(trivial_electron, [], f"fan{t}")
                    for t in range(3)]

        wire0, ops0, upload0 = wire_up_bytes(), staging_ops(), upload_span_sum()
        fanout_walls = await asyncio.wait_for(fanout_trials(), FANOUT_BUDGET_S)
        fanout_wall = statistics.median(fanout_walls)
        single = summary.get("electron_wall_s") or fanout_wall / 8
        summary["fanout8_wall_s"] = round(fanout_wall, 3)
        summary["fanout8_per_electron_s"] = round(fanout_wall / 8, 4)
        summary["fanout8_speedup_vs_serial"] = round(8 * single / fanout_wall, 2)
        emit({"phase": "fanout8", **{k: summary[k] for k in (
            "fanout8_wall_s", "fanout8_per_electron_s",
            "fanout8_speedup_vs_serial")},
            # Dispatch-plane breakdown across the trials: staging round
            # trips, upload-stage seconds, and bytes shipped.
            "breakdown": {
                "staging_ops": round(staging_ops() - ops0, 1),
                "upload_s": round(upload_span_sum() - upload0, 4),
            },
            "wire_bytes": round(wire_up_bytes() - wire0, 1),
            **spread_stats(fanout_walls, "fanout8_wall")})
    except _PhaseSkipped:
        emit({"phase": "fanout8", "skipped": "BENCH_PHASES"})
    except Exception as error:  # noqa: BLE001
        emit({"phase": "fanout8", "error": repr(error)})

    # Same fan-out with 300 ms of real work per electron: serial would
    # take >= 2.4 s, so the wall directly exposes task concurrency.
    try:
        if "fanout" not in BENCH_PHASES:
            raise _PhaseSkipped
        task_s = 0.3

        async def busy_trials():
            return [await fanout8(busy_electron, [task_s], f"busy{t}")
                    for t in range(3)]

        busy_walls = await asyncio.wait_for(busy_trials(), FANOUT_BUDGET_S)
        busy_wall = statistics.median(busy_walls)
        summary["fanout8_busy_wall_s"] = round(busy_wall, 3)
        summary["fanout8_busy_speedup"] = round(8 * task_s / busy_wall, 2)
        emit({"phase": "fanout8_busy", "task_s": task_s, **{k: summary[k] for k in (
            "fanout8_busy_wall_s", "fanout8_busy_speedup")},
            **spread_stats(busy_walls, "fanout8_busy_wall")})
    except _PhaseSkipped:
        emit({"phase": "fanout8_busy", "skipped": "BENCH_PHASES"})
    except Exception as error:  # noqa: BLE001
        emit({"phase": "fanout8_busy", "error": repr(error)})

    # ---- phase 2b: two-level cache, same electron N times ----------------
    # Warm vs cold through a cache_results executor: the cold first run
    # pays connect + CAS-miss uploads + launch + execute; the warm repeats
    # memoize (level 2) and, where they do dispatch, skip repeat payloads
    # (level 1).  The trajectory JSON carries the measured speedup plus the
    # hit/miss counter deltas so the win is attributable, not inferred.
    try:
        if "cached_fanout" not in BENCH_PHASES:
            raise _PhaseSkipped

        def cache_counters() -> dict:
            # Same public snapshot path as the final line's metrics_totals.
            return {
                key: value
                for key, value in metrics_totals().items()
                if key.startswith(("covalent_tpu_result_cache_total",
                                   "covalent_tpu_cas_uploads_total"))
            }

        async def cached_phase():
            cache_ex = TPUExecutor(
                transport="local",
                cache_dir=f"{workdir}/cache_memo",
                remote_cache=f"{workdir}/remote_memo",
                python_path=sys.executable,
                poll_freq=0.2,
                pool_preload="cloudpickle",
                cache_results=True,
                task_env={
                    "PYTHONPATH": repo_root + os.pathsep
                    + os.environ.get("PYTHONPATH", ""),
                },
            )
            try:
                t0 = time.perf_counter()
                await cache_ex.run(
                    trivial_electron, [7], {},
                    {"dispatch_id": "cache_cold", "node_id": 0},
                )
                cold = time.perf_counter() - t0
                warm = []
                for i in range(4):
                    t0 = time.perf_counter()
                    await cache_ex.run(
                        trivial_electron, [7], {},
                        {"dispatch_id": "cache_warm", "node_id": i},
                    )
                    warm.append(time.perf_counter() - t0)
            finally:
                await cache_ex.close()
            return cold, warm

        counters_before = cache_counters()
        cold_s, warm_list = await asyncio.wait_for(
            cached_phase(), FANOUT_BUDGET_S
        )
        warm_s = statistics.median(warm_list)
        counters_delta = {
            key: round(value - counters_before.get(key, 0.0), 1)
            for key, value in cache_counters().items()
            if value != counters_before.get(key, 0.0)
        }
        summary["cached_fanout_cold_s"] = round(cold_s, 4)
        summary["cached_fanout_warm_s"] = round(warm_s, 4)
        summary["cached_fanout_speedup"] = round(cold_s / max(warm_s, 1e-9), 2)
        summary["cached_fanout_warm_below_cold"] = bool(warm_s < cold_s)
        emit({
            "phase": "cached_fanout",
            "cold_s": summary["cached_fanout_cold_s"],
            "warm_s_median": summary["cached_fanout_warm_s"],
            "warm_per_run_s": [round(w, 4) for w in warm_list],
            "speedup": summary["cached_fanout_speedup"],
            "warm_below_cold": summary["cached_fanout_warm_below_cold"],
            "cache_counters_delta": counters_delta,
            **spread_stats(warm_list, "warm"),
        })
    except _PhaseSkipped:
        emit({"phase": "cached_fanout", "skipped": "BENCH_PHASES"})
    except Exception as error:  # noqa: BLE001
        emit({"phase": "cached_fanout", "error": repr(error)})

    # ---- phase 2b': bundled+compressed staging vs the per-file path ------
    # Two cold 4-electron fan-outs with identical unique-payload electrons:
    # one through the PR-2 per-file CAS path (bundle=False, compress=off),
    # one through the fast path (one compressed tar per worker).  Both run
    # over a ChaosTransport that ONLY injects per-op latency (a simulated
    # network RTT, deterministic — a pure-local wire would hide the round
    # trips this phase exists to count).  The counters give exact round
    # trips + wire bytes; upload-span seconds give the staging latency.
    try:
        if "bundled_fanout" not in BENCH_PHASES:
            raise _PhaseSkipped
        from covalent_tpu_plugin.transport import ChaosPlan as _ChaosPlan

        def fastpath_executor(tag: str, bundle: bool, compress: str):
            return TPUExecutor(
                transport="local",
                cache_dir=f"{workdir}/cache_{tag}",
                remote_cache=f"{workdir}/remote_{tag}",
                python_path=sys.executable,
                poll_freq=0.2,
                use_agent=False,  # nohup path: identical launch RTs both ways
                prewarm=False,
                bundle=bundle,
                compress=compress,
                # 60 ms simulated RTT per op — a realistic cross-zone SSH
                # round trip.  The chaos wrapper also makes every publish
                # a real shell round trip (its rename/remove ride run, as
                # on a genuine wire), so the per-file path pays its honest
                # per-artifact exec cost.
                chaos=_ChaosPlan(delay=0.06),
                task_env={
                    "PYTHONPATH": repo_root + os.pathsep
                    + os.environ.get("PYTHONPATH", ""),
                },
            )

        async def measured_fanout(ex, dispatch_id):
            # SEQUENTIAL electrons: this phase measures per-electron
            # staging cost, and serial dispatch keeps the upload spans
            # free of single-flight waits and CPU contention between
            # concurrent unpack execs (fanout8 owns the concurrency
            # story).
            ops0, wire0, up0 = staging_ops(), wire_up_bytes(), upload_span_sum()
            t0 = time.perf_counter()
            results = []
            for i in range(4):
                results.append(await ex.run(
                    payload_electron, [i, BUNDLE_PAYLOAD + str(i)], {},
                    {"dispatch_id": dispatch_id, "node_id": i},
                ))
            return {
                "wall_s": time.perf_counter() - t0,
                "staging_ops": staging_ops() - ops0,
                "wire_bytes": wire_up_bytes() - wire0,
                "upload_s": upload_span_sum() - up0,
                "results": results,
            }

        async def bundled_phase():
            per = fastpath_executor("perfile", bundle=False, compress="off")
            try:
                perfile = await measured_fanout(per, "perfilefan")
            finally:
                await per.close()
            bun = fastpath_executor("bundled", bundle=True, compress="auto")
            try:
                bundled = await measured_fanout(bun, "bundledfan")
            finally:
                await bun.close()
            return perfile, bundled

        perfile, bundled = await asyncio.wait_for(
            bundled_phase(), FANOUT_BUDGET_S
        )
        # Equal results at fewer round trips / fewer bytes is the claim.
        assert bundled["results"] == perfile["results"], (
            bundled["results"], perfile["results"])
        summary["bundled_fanout_wall_s"] = round(bundled["wall_s"], 3)
        summary["bundled_fanout_perfile_wall_s"] = round(perfile["wall_s"], 3)
        summary["bundled_fanout_staging_ops"] = round(
            bundled["staging_ops"], 1)
        summary["bundled_fanout_perfile_staging_ops"] = round(
            perfile["staging_ops"], 1)
        summary["bundled_fanout_wire_bytes"] = round(bundled["wire_bytes"], 1)
        summary["bundled_fanout_perfile_wire_bytes"] = round(
            perfile["wire_bytes"], 1)
        summary["bundled_fanout_upload_s"] = round(bundled["upload_s"], 4)
        summary["bundled_fanout_perfile_upload_s"] = round(
            perfile["upload_s"], 4)
        summary["bundled_fanout_fewer_round_trips"] = bool(
            bundled["staging_ops"] < perfile["staging_ops"])
        summary["bundled_fanout_fewer_wire_bytes"] = bool(
            bundled["wire_bytes"] < perfile["wire_bytes"])
        # "No slower" is judged on the staging latency the feature owns
        # (upload spans): whole-electron wall also rides along, but its
        # poll-cadence noise under the injected RTT is not the feature's.
        summary["bundled_fanout_staging_no_slower"] = bool(
            bundled["upload_s"] <= perfile["upload_s"])
        emit({
            "phase": "bundled_fanout",
            "wall_s": summary["bundled_fanout_wall_s"],
            "perfile_wall_s": summary["bundled_fanout_perfile_wall_s"],
            "staging_ops": summary["bundled_fanout_staging_ops"],
            "perfile_staging_ops":
                summary["bundled_fanout_perfile_staging_ops"],
            "wire_bytes": summary["bundled_fanout_wire_bytes"],
            "perfile_wire_bytes":
                summary["bundled_fanout_perfile_wire_bytes"],
            "upload_s": summary["bundled_fanout_upload_s"],
            "perfile_upload_s": summary["bundled_fanout_perfile_upload_s"],
            "fewer_round_trips":
                summary["bundled_fanout_fewer_round_trips"],
            "fewer_wire_bytes": summary["bundled_fanout_fewer_wire_bytes"],
            "staging_no_slower":
                summary["bundled_fanout_staging_no_slower"],
        })
    except _PhaseSkipped:
        emit({"phase": "bundled_fanout", "skipped": "BENCH_PHASES"})
    except Exception as error:  # noqa: BLE001
        emit({"phase": "bundled_fanout", "error": repr(error)})

    # ---- phase 2b'': RPC dispatch vs process launch, same 8-fanout -------
    # The ROADMAP item-3 claim, measured: after the connection-scoped warm
    # -up (dial, pre-flight, pool server, register_fn), an RPC-mode
    # electron costs one invoke write + one pushed result on the agent
    # channel — no harness process, no pid file, no staging, no poll, no
    # result fetch — so its per-electron wall_overhead must sit in the
    # tens of milliseconds where launch mode sits in the hundreds (or
    # seconds on a real wire).  Both arms run the SAME 8 electrons
    # sequentially over a ChaosTransport injecting per-op latency (a
    # simulated cross-zone RTT: the round trips RPC mode eliminates must
    # cost something, as on a genuine wire), through the same pool-agent
    # runtime; results must be byte-equal across modes, and the RPC
    # median is asserted against BENCH_RPC_OVERHEAD_BUDGET_S in CI.
    try:
        if "rpc_overhead" not in BENCH_PHASES:
            raise _PhaseSkipped
        import cloudpickle as _cloudpickle

        from covalent_tpu_plugin.transport import ChaosPlan as _RpcChaosPlan

        RPC_ELECTRONS = 8

        def rpc_arm_executor(tag: str, mode: str, frames: bool = True):
            return TPUExecutor(
                transport="local",
                cache_dir=f"{workdir}/cache_rpc_{tag}",
                remote_cache=f"{workdir}/remote_rpc_{tag}",
                python_path=sys.executable,
                poll_freq=0.2,
                use_agent="pool",
                pool_preload="cloudpickle",
                dispatch_mode=mode,
                agent_frames=frames,
                prewarm=False,
                heartbeat_interval=0.0,
                # 30 ms simulated RTT per control-plane op; the agent
                # channel itself is a held-open stream, so RPC invokes
                # ride it untaxed — exactly the wire economics the mode
                # exists to exploit.  dispatch_mode="rpc" stays pinned
                # under the plan ("auto" would defer to launch).
                chaos=_RpcChaosPlan(delay=0.03),
                task_env={
                    "PYTHONPATH": repo_root + os.pathsep
                    + os.environ.get("PYTHONPATH", ""),
                },
            )

        async def rpc_arm(tag: str, mode: str, frames: bool = True) -> dict:
            ex = rpc_arm_executor(tag, mode, frames)
            overheads, results, modes = [], [], []
            wire0 = agent_wire_bytes()
            framed0 = agent_frames("invoke") + agent_frames("multi_invoke")
            try:
                # Warm-up electron pays the connection-scoped costs (pool
                # server start, harness/function staging, register_fn) so
                # the measured electrons show the steady state.  It runs
                # the MEASURED function so its digest registration (CAS
                # put + register round trips under the injected RTT) is
                # amortized too — otherwise the first measured electron
                # carries a ~100ms outlier into both wire arms' spreads.
                await ex.run(
                    payload_electron, [99, BUNDLE_PAYLOAD], {},
                    {"dispatch_id": f"rpcwarm{tag}", "node_id": 0},
                )
                wire0 = agent_wire_bytes()  # exclude warm-up traffic
                framed0 = (
                    agent_frames("invoke") + agent_frames("multi_invoke")
                )
                t0 = time.perf_counter()
                for i in range(RPC_ELECTRONS):
                    results.append(await ex.run(
                        payload_electron, [i, BUNDLE_PAYLOAD], {},
                        {"dispatch_id": f"rpcfan{tag}", "node_id": i},
                    ))
                    overheads.append(
                        ex.last_timings.get("wall_overhead", 0.0)
                    )
                    modes.append(ex.last_dispatch_mode)
                wall = time.perf_counter() - t0
            finally:
                await ex.close()
            return {
                "wall_s": wall,
                "overheads": overheads,
                "results": results,
                "modes": modes,
                "wire_bytes": agent_wire_bytes() - wire0,
                "framed_invokes": (
                    agent_frames("invoke") + agent_frames("multi_invoke")
                    - framed0
                ),
            }

        async def rpc_phase():
            launch = await rpc_arm("launch", "launch")
            # Both wire arms in the SAME run: the binary-frame claim is a
            # measured speedup over the JSONL fallback, not an assertion
            # against history.
            jsonl = await rpc_arm("jsonl", "rpc", frames=False)
            rpc = await rpc_arm("rpc", "rpc", frames=True)
            return launch, jsonl, rpc

        launch_arm, jsonl_arm, rpc_arm_run = await asyncio.wait_for(
            rpc_phase(), FANOUT_BUDGET_S * 3
        )
        # The fast path must have actually engaged — a silent fallback to
        # launch would "pass" the budget by measuring the wrong thing —
        # and the binary arm must have actually shipped frames (a silent
        # JSONL fallback would "pass" by measuring the wrong protocol).
        assert all(m == "rpc" for m in rpc_arm_run["modes"]), (
            rpc_arm_run["modes"])
        assert all(m == "rpc" for m in jsonl_arm["modes"]), (
            jsonl_arm["modes"])
        assert all(m == "launch" for m in launch_arm["modes"]), (
            launch_arm["modes"])
        assert rpc_arm_run["framed_invokes"] >= RPC_ELECTRONS, (
            rpc_arm_run["framed_invokes"])
        assert jsonl_arm["framed_invokes"] == 0, (
            jsonl_arm["framed_invokes"])
        # Byte-equal results across ALL arms: the streamed (result,
        # exception) pickle must carry exactly what the staged result file
        # does, whichever encoding the channel negotiated.
        byte_equal = (
            _cloudpickle.dumps(rpc_arm_run["results"])
            == _cloudpickle.dumps(launch_arm["results"])
            == _cloudpickle.dumps(jsonl_arm["results"])
        )
        assert rpc_arm_run["results"] == launch_arm["results"], (
            rpc_arm_run["results"], launch_arm["results"])
        assert rpc_arm_run["results"] == jsonl_arm["results"], (
            rpc_arm_run["results"], jsonl_arm["results"])
        rpc_median = statistics.median(rpc_arm_run["overheads"])
        jsonl_median = statistics.median(jsonl_arm["overheads"])
        launch_median = statistics.median(launch_arm["overheads"])
        summary["rpc_overhead_s"] = round(rpc_median, 4)
        summary["rpc_overhead_jsonl_s"] = round(jsonl_median, 4)
        summary["rpc_overhead_launch_s"] = round(launch_median, 4)
        summary["rpc_overhead_budget_s"] = RPC_OVERHEAD_BUDGET_S
        summary["rpc_overhead_within_budget"] = bool(
            rpc_median <= RPC_OVERHEAD_BUDGET_S
        )
        summary["rpc_results_byte_equal"] = bool(byte_equal)
        summary["rpc_overhead_speedup"] = round(
            launch_median / max(rpc_median, 1e-9), 2
        )
        # The binary-frame claims, asserted against the JSONL arm of the
        # SAME run: no slower on median wall overhead (timing — speedup
        # reported), strictly fewer bytes on the agent channel for the
        # same electrons (deterministic — base64 alone is a 33% tax).
        summary["rpc_frames_speedup"] = round(
            jsonl_median / max(rpc_median, 1e-9), 2
        )
        # 5% + 1ms noise floor: both arms' medians sit under 5ms, where
        # a fraction-of-a-millisecond scheduler hiccup on a loaded CI
        # machine flips a bare <= — the same timer-noise floor rationale
        # as obs_tax's absolute allowance.
        summary["rpc_frames_no_slower"] = bool(
            rpc_median <= jsonl_median * 1.05 + 0.001
        )
        summary["rpc_wire_bytes_per_electron"] = round(
            rpc_arm_run["wire_bytes"] / RPC_ELECTRONS, 1
        )
        summary["rpc_jsonl_wire_bytes_per_electron"] = round(
            jsonl_arm["wire_bytes"] / RPC_ELECTRONS, 1
        )
        summary["rpc_frames_fewer_wire_bytes"] = bool(
            rpc_arm_run["wire_bytes"] < jsonl_arm["wire_bytes"]
        )
        emit({
            "phase": "rpc_overhead",
            "electrons": RPC_ELECTRONS,
            "rpc_overhead_s": summary["rpc_overhead_s"],
            "jsonl_overhead_s": summary["rpc_overhead_jsonl_s"],
            "launch_overhead_s": summary["rpc_overhead_launch_s"],
            "rpc_wall_s": round(rpc_arm_run["wall_s"], 3),
            "jsonl_wall_s": round(jsonl_arm["wall_s"], 3),
            "launch_wall_s": round(launch_arm["wall_s"], 3),
            "frames_speedup": summary["rpc_frames_speedup"],
            "frames_no_slower": summary["rpc_frames_no_slower"],
            "wire_bytes_per_electron":
                summary["rpc_wire_bytes_per_electron"],
            "jsonl_wire_bytes_per_electron":
                summary["rpc_jsonl_wire_bytes_per_electron"],
            "frames_fewer_wire_bytes":
                summary["rpc_frames_fewer_wire_bytes"],
            "framed_invokes": rpc_arm_run["framed_invokes"],
            "per_electron_rpc_s": [
                round(o, 4) for o in rpc_arm_run["overheads"]
            ],
            "per_electron_launch_s": [
                round(o, 4) for o in launch_arm["overheads"]
            ],
            "budget_s": RPC_OVERHEAD_BUDGET_S,
            "within_budget": summary["rpc_overhead_within_budget"],
            "results_byte_equal": summary["rpc_results_byte_equal"],
            "speedup": summary["rpc_overhead_speedup"],
            # Regression-comparable timeline + budget verdicts, not just
            # the point medians above.
            "introspection": introspection_view([
                "covalent_tpu_wall_overhead_seconds",
                "covalent_tpu_tasks_total",
            ]),
            **spread_stats(rpc_arm_run["overheads"], "rpc_overhead"),
        })
    except _PhaseSkipped:
        emit({"phase": "rpc_overhead", "skipped": "BENCH_PHASES"})
    except Exception as error:  # noqa: BLE001
        emit({"phase": "rpc_overhead", "error": repr(error)})

    # ---- phase 2b2: resident serving session vs per-electron dispatch ----
    # The serving tier's whole argument in one phase: a generate "model"
    # that costs SERVE_LOAD_S to load+compile and SERVE_STEP_S per decode
    # chunk, driven two ways over the same pool-agent runtime.  The
    # per-electron arm pays the load on EVERY call (exactly what a generate
    # electron pays today, even via the millisecond RPC path); the resident
    # arm opens ONE session — the factory runs once — and fires every
    # request concurrently through the handle, sharing the engine's
    # fixed-slot batch.  Token streams must be identical across arms; the
    # resident arm must beat the per-electron arm on p50 request latency by
    # SERVE_SPEEDUP_MIN and on aggregate tokens/s, with streamed TTFT
    # strictly inside full-response latency — all asserted in CI.
    try:
        if "serve_traffic" not in BENCH_PHASES:
            raise _PhaseSkipped
        from covalent_tpu_plugin import serving as _serving

        serve_chunk = 4  # tokens per decode chunk (per busy lane per step)

        def _serve_tokens_for(seed: int) -> list:
            return [seed * 100 + j + 1 for j in range(SERVE_TOKENS)]

        def make_serve_factory(load_s: float, step_s: float, slots: int = 4):
            # Closure-local engine: cloudpickled BY VALUE into the CAS, so
            # the resident worker needs no bench import.  Same duck-typed
            # surface ContinuousEngine implements for real LMs.
            def factory():
                import time as _time

                _time.sleep(load_s)  # the amortized cost: load + compile

                class Engine:
                    def __init__(self):
                        self.slots = slots
                        self.lanes = {}

                    def admit(self, rid, prompt, params):
                        seed = int(prompt[-1])
                        cap = int((params or {}).get(
                            "max_new_tokens", SERVE_TOKENS
                        ))
                        self.lanes[rid] = [
                            seed * 100 + j + 1 for j in range(cap)
                        ]

                    def step(self):
                        _time.sleep(step_s)  # one decode chunk, all lanes
                        events = []
                        for rid in list(self.lanes):
                            chunk = self.lanes[rid][:serve_chunk]
                            self.lanes[rid] = self.lanes[rid][serve_chunk:]
                            done = not self.lanes[rid]
                            if done:
                                del self.lanes[rid]
                            events.append({
                                "rid": rid, "tokens": chunk, "done": done,
                            })
                        return events

                    def cancel(self, rid):
                        self.lanes.pop(rid, None)

                return Engine()

            return factory

        def generate_electron(seed, n_tokens, load_s, step_s):
            # The per-electron status quo: model load + compile, then the
            # same decode chunks — all paid inside ONE call.
            import math
            import time as _time

            _time.sleep(load_s)
            for _ in range(math.ceil(n_tokens / serve_chunk)):
                _time.sleep(step_s)
            return [seed * 100 + j + 1 for j in range(n_tokens)]

        def serve_arm_executor(tag: str):
            return TPUExecutor(
                transport="local",
                cache_dir=f"{workdir}/cache_serve_{tag}",
                remote_cache=f"{workdir}/remote_serve_{tag}",
                python_path=sys.executable,
                poll_freq=0.2,
                use_agent="pool",
                pool_preload="cloudpickle",
                dispatch_mode="rpc",
                prewarm=False,
                heartbeat_interval=0.0,
                task_env={
                    "PYTHONPATH": repo_root + os.pathsep
                    + os.environ.get("PYTHONPATH", ""),
                },
            )

        async def per_electron_arm() -> dict:
            ex = serve_arm_executor("electron")
            latencies, results = [], []
            try:
                # Warm-up pays connection-scoped costs (pool server, fn
                # registration) so the arm measures steady-state per-call
                # economics, exactly like the rpc_overhead phase.
                await ex.run(
                    generate_electron, [0, 1, 0.0, 0.0], {},
                    {"dispatch_id": "servewarm", "node_id": 0},
                )
                t0 = time.perf_counter()
                for i in range(SERVE_REQUESTS):
                    t_req = time.perf_counter()
                    results.append(await ex.run(
                        generate_electron,
                        [i, SERVE_TOKENS, SERVE_LOAD_S, SERVE_STEP_S], {},
                        {"dispatch_id": "servefan", "node_id": i},
                    ))
                    latencies.append(time.perf_counter() - t_req)
                wall = time.perf_counter() - t0
            finally:
                await ex.close()
            return {"wall_s": wall, "latencies": latencies,
                    "results": results}

        async def resident_arm() -> dict:
            ex = serve_arm_executor("resident")
            batches0 = agent_frames("telemetry_batch")
            wire_down0 = agent_wire_bytes()
            try:
                t_open0 = time.perf_counter()
                handle = await _serving.open_session(
                    ex,
                    make_serve_factory(SERVE_LOAD_S, SERVE_STEP_S),
                    stats_interval_s=0.2,
                )
                open_s = time.perf_counter() - t_open0
                t0 = time.perf_counter()
                requests = [
                    await handle.request(
                        [i], params={"max_new_tokens": SERVE_TOKENS},
                        tenant=f"t{i % 2}",
                    )
                    for i in range(SERVE_REQUESTS)
                ]
                results = await asyncio.gather(
                    *(r.result(timeout=SERVE_BUDGET_S) for r in requests)
                )
                wall = time.perf_counter() - t0
                latencies = [r.latency_s for r in requests]
                ttfts = [r.ttft_s for r in requests]
                stats = dict(handle.stats)
                await handle.close()
            finally:
                await ex.close()
            return {
                "wall_s": wall, "open_s": open_s, "latencies": latencies,
                "ttfts": ttfts, "results": list(results), "stats": stats,
                "coalesced_batches": (
                    agent_frames("telemetry_batch") - batches0
                ),
                "wire_bytes": agent_wire_bytes() - wire_down0,
            }

        async def serve_phase():
            electron = await per_electron_arm()
            resident = await resident_arm()
            return electron, resident

        electron_arm, resident_arm_run = await asyncio.wait_for(
            serve_phase(), SERVE_BUDGET_S
        )
        expected = [_serve_tokens_for(i) for i in range(SERVE_REQUESTS)]
        assert electron_arm["results"] == expected, electron_arm["results"]
        assert resident_arm_run["results"] == expected, (
            resident_arm_run["results"])
        assert all(t is not None for t in resident_arm_run["ttfts"])
        electron_p50 = percentile(electron_arm["latencies"], 0.50)
        electron_p99 = percentile(electron_arm["latencies"], 0.99)
        resident_p50 = percentile(resident_arm_run["latencies"], 0.50)
        resident_p99 = percentile(resident_arm_run["latencies"], 0.99)
        ttft_p50 = percentile(resident_arm_run["ttfts"], 0.50)
        total_tokens = SERVE_REQUESTS * SERVE_TOKENS
        electron_tps = total_tokens / max(electron_arm["wall_s"], 1e-9)
        resident_tps = total_tokens / max(resident_arm_run["wall_s"], 1e-9)
        speedup = electron_p50 / max(resident_p50, 1e-9)
        summary["serve_p50_s"] = round(resident_p50, 4)
        summary["serve_p99_s"] = round(resident_p99, 4)
        summary["serve_electron_p50_s"] = round(electron_p50, 4)
        summary["serve_ttft_p50_s"] = round(ttft_p50, 4)
        summary["serve_tokens_per_s"] = round(resident_tps, 1)
        summary["serve_electron_tokens_per_s"] = round(electron_tps, 1)
        summary["serve_speedup"] = round(speedup, 2)
        summary["serve_speedup_min"] = SERVE_SPEEDUP_MIN
        summary["serve_beats_per_electron"] = bool(
            speedup >= SERVE_SPEEDUP_MIN and resident_tps > electron_tps
        )
        # Streaming must be real: first tokens land while the stream is
        # still going, not at end-of-batch.
        summary["serve_ttft_streams_early"] = bool(ttft_p50 < resident_p50)
        # Token coalescing: the resident arm's streams — already asserted
        # token-identical above — must have ridden batched binary frames,
        # and the per-token wire cost is a first-class observable.
        summary["serve_coalesced_batches"] = round(
            resident_arm_run["coalesced_batches"], 1
        )
        summary["serve_coalescing_engaged"] = bool(
            resident_arm_run["coalesced_batches"] >= 1
        )
        summary["serve_wire_bytes_per_token"] = round(
            resident_arm_run["wire_bytes"] / max(total_tokens, 1), 1
        )
        emit({
            "phase": "serve_traffic",
            "requests": SERVE_REQUESTS,
            "tokens_per_request": SERVE_TOKENS,
            "model_load_s": SERVE_LOAD_S,
            "resident_p50_s": summary["serve_p50_s"],
            "resident_p99_s": summary["serve_p99_s"],
            "resident_ttft_p50_s": summary["serve_ttft_p50_s"],
            "resident_tokens_per_s": summary["serve_tokens_per_s"],
            "resident_wall_s": round(resident_arm_run["wall_s"], 3),
            "resident_open_s": round(resident_arm_run["open_s"], 3),
            "per_electron_p50_s": summary["serve_electron_p50_s"],
            "per_electron_p99_s": round(electron_p99, 4),
            "per_electron_tokens_per_s":
                summary["serve_electron_tokens_per_s"],
            "per_electron_wall_s": round(electron_arm["wall_s"], 3),
            "speedup": summary["serve_speedup"],
            "speedup_min": SERVE_SPEEDUP_MIN,
            "beats_per_electron": summary["serve_beats_per_electron"],
            "ttft_streams_early": summary["serve_ttft_streams_early"],
            "coalesced_batches": summary["serve_coalesced_batches"],
            "coalescing_engaged": summary["serve_coalescing_engaged"],
            "wire_bytes_per_token": summary["serve_wire_bytes_per_token"],
            "worker_stats": resident_arm_run["stats"],
            # The serving timeline (tokens/s + queue depth per session,
            # windowed latency/TTFT percentiles) + end-of-phase SLO
            # verdicts: BENCH artifacts carry the whole shape of the
            # phase, not just its point summary.
            "introspection": introspection_view([
                "covalent_tpu_serve_tokens_per_s",
                "covalent_tpu_serve_queue_depth",
                "covalent_tpu_serve_request_seconds",
                "covalent_tpu_serve_ttft_seconds",
            ]),
            **spread_stats(resident_arm_run["latencies"], "serve_latency"),
        })
    except _PhaseSkipped:
        emit({"phase": "serve_traffic", "skipped": "BENCH_PHASES"})
    except Exception as error:  # noqa: BLE001
        emit({"phase": "serve_traffic", "error": repr(error)})

    # ---- phase 2b3: horizontal serving scale (replica sets) --------------
    # ONE resident session's ceiling is one engine's slot count; this
    # phase offers the SAME concurrent load to a 1-replica set and an
    # N-replica set (each replica its own pool-server process, so the
    # step_s decode sleeps genuinely parallelize) and asserts the three
    # scaling SLOs: aggregate tokens/s grows >= SERVE_SCALE_MIN from
    # 1 -> N replicas, p99 request latency at N is no worse than at 1,
    # and the router's median per-request decision stays under
    # ROUTER_DECISION_BUDGET_S — scaling out must not re-tax the dispatch
    # path.  A final arm proves the engine-side half of the ISSUE:
    # shared-prefix prefill reuse on the REAL ContinuousEngine, bit-equal
    # greedy streams with measurably fewer prefill positions.
    try:
        if "serve_scale" not in BENCH_PHASES:
            raise _PhaseSkipped
        from covalent_tpu_plugin.serving import open_replica_set

        def make_scale_factory(step_s: float, slots: int = 4):
            # Same closure-local stub shape as serve_traffic: streams are
            # deterministic per prompt, one step_s sleep per decode chunk
            # across all busy lanes — the per-process serial resource a
            # replica adds a copy of.
            def factory():
                import time as _time

                class Engine:
                    def __init__(self):
                        self.slots = slots
                        self.lanes = {}

                    def admit(self, rid, prompt, params):
                        seed = int(prompt[-1])
                        cap = int((params or {}).get(
                            "max_new_tokens", SERVE_SCALE_TOKENS
                        ))
                        self.lanes[rid] = [
                            seed * 100 + j + 1 for j in range(cap)
                        ]

                    def step(self):
                        _time.sleep(step_s)
                        events = []
                        for rid in list(self.lanes):
                            chunk = self.lanes[rid][:4]
                            self.lanes[rid] = self.lanes[rid][4:]
                            done = not self.lanes[rid]
                            if done:
                                del self.lanes[rid]
                            events.append({
                                "rid": rid, "tokens": chunk, "done": done,
                            })
                        return events

                    def cancel(self, rid):
                        self.lanes.pop(rid, None)

                return Engine()

            return factory

        def scale_executor(tag: str):
            return TPUExecutor(
                transport="local",
                cache_dir=f"{workdir}/cache_scale_{tag}",
                remote_cache=f"{workdir}/remote_scale_{tag}",
                python_path=sys.executable,
                poll_freq=0.2,
                use_agent="pool",
                pool_preload="cloudpickle",
                prewarm=False,
                heartbeat_interval=0.0,
                task_env={
                    "PYTHONPATH": repo_root + os.pathsep
                    + os.environ.get("PYTHONPATH", ""),
                },
            )

        async def scale_arm(n_replicas: int) -> dict:
            executors = [
                scale_executor(f"n{n_replicas}_{i}")
                for i in range(n_replicas)
            ]
            try:
                rset = await open_replica_set(
                    executors,
                    make_scale_factory(SERVE_SCALE_STEP_S),
                    name=f"scale{n_replicas}",
                    stats_interval_s=0.2,
                )
                t0 = time.perf_counter()
                requests = [
                    await rset.request(
                        [i],
                        params={"max_new_tokens": SERVE_SCALE_TOKENS},
                        tenant=f"t{i % 2}",
                    )
                    for i in range(SERVE_SCALE_REQUESTS)
                ]
                results = await asyncio.gather(
                    *(
                        r.result(timeout=SERVE_SCALE_BUDGET_S)
                        for r in requests
                    )
                )
                wall = time.perf_counter() - t0
                latencies = [r.latency_s for r in requests]
                trace_ids = [r.span.trace_id for r in requests]
                decisions = sorted(rset.decision_s)
                status = rset.status()
                await rset.close()
            finally:
                for ex in executors:
                    await ex.close()
            return {
                "wall_s": wall,
                "latencies": latencies,
                "trace_ids": trace_ids,
                "results": list(results),
                "decisions": decisions,
                "per_replica_served": {
                    rid: view["served"]
                    for rid, view in status["replicas"].items()
                },
            }

        def prefix_probe(prefix_len, n_requests, cap):
            # Runs INSIDE a worker process (the bench parent never
            # imports jax): the real ContinuousEngine, driven with and
            # without shared-prefix reuse over identical prompts.
            import time as _time

            import jax
            import jax.numpy as jnp
            import numpy as np

            from covalent_tpu_plugin.models import (
                TransformerConfig,
                TransformerLM,
            )
            from covalent_tpu_plugin.models.serve import ContinuousEngine

            cfg = TransformerConfig(
                vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                d_ff=64, max_seq=64, dtype=jnp.float32,
                attention="reference",
            )
            model = TransformerLM(cfg)
            params = model.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
            )["params"]
            rng = np.random.default_rng(0)
            prefix = rng.integers(0, 64, prefix_len).astype(np.int32)
            prompts = [
                np.concatenate([
                    prefix,
                    rng.integers(0, 64, 2 + i % 3).astype(np.int32),
                ])
                for i in range(n_requests)
            ]

            def run(shared):
                engine = ContinuousEngine(
                    model, params, max_batch=2, sync_steps=4,
                    max_new_tokens=cap,
                    shared_prefix=prefix if shared else None,
                )
                streams = {}
                queue = [(f"r{i}", p) for i, p in enumerate(prompts)]
                done = set()
                t0 = _time.perf_counter()
                for _ in range(500):
                    while queue and engine.busy < engine.slots:
                        rid, p = queue.pop(0)
                        engine.admit(rid, p, {"max_new_tokens": cap})
                        streams[rid] = []
                    for event in engine.step():
                        streams[event["rid"]].extend(event["tokens"])
                        if event["done"]:
                            done.add(event["rid"])
                    if len(done) == len(prompts) and not queue:
                        break
                wall = _time.perf_counter() - t0
                stats = dict(engine.stats)
                engine.close()
                return streams, stats, wall

            reuse_streams, reuse_stats, reuse_wall = run(True)
            full_streams, full_stats, full_wall = run(False)
            return {
                "equal": reuse_streams == full_streams,
                "requests": n_requests,
                "prefix_hits": reuse_stats["prefix_hits"],
                "prefill_positions_reuse":
                    reuse_stats["prefill_positions"],
                "prefill_positions_full":
                    full_stats["prefill_positions"],
                "wall_reuse_s": round(reuse_wall, 4),
                "wall_full_s": round(full_wall, 4),
            }

        async def prefix_arm() -> dict:
            ex = scale_executor("prefix")
            try:
                return await ex.run(
                    prefix_probe, [12, 6, 6], {},
                    {"dispatch_id": "prefixprobe", "node_id": 0},
                )
            finally:
                await ex.close()

        async def scale_phase():
            one = await scale_arm(1)
            many = await scale_arm(SERVE_SCALE_REPLICAS)
            prefix = await prefix_arm()
            return one, many, prefix

        one_arm, many_arm, prefix_info = await asyncio.wait_for(
            scale_phase(), SERVE_SCALE_BUDGET_S
        )
        expected = [
            [i * 100 + j + 1 for j in range(SERVE_SCALE_TOKENS)]
            for i in range(SERVE_SCALE_REQUESTS)
        ]
        assert one_arm["results"] == expected, one_arm["results"]
        assert many_arm["results"] == expected, many_arm["results"]
        total_tokens = SERVE_SCALE_REQUESTS * SERVE_SCALE_TOKENS
        tps_one = total_tokens / max(one_arm["wall_s"], 1e-9)
        tps_many = total_tokens / max(many_arm["wall_s"], 1e-9)
        scale = tps_many / max(tps_one, 1e-9)
        p99_one = percentile(one_arm["latencies"], 0.99)
        p99_many = percentile(many_arm["latencies"], 0.99)
        decisions = sorted(one_arm["decisions"] + many_arm["decisions"])
        router_p50 = (
            decisions[len(decisions) // 2] if decisions else 0.0
        )
        assert prefix_info["equal"] is True, prefix_info
        prefix_reuse_ok = bool(
            prefix_info["prefill_positions_reuse"]
            < prefix_info["prefill_positions_full"]
        )
        summary["serve_scale_replicas"] = SERVE_SCALE_REPLICAS
        summary["serve_scale_tokens_per_s_1"] = round(tps_one, 1)
        summary["serve_scale_tokens_per_s_n"] = round(tps_many, 1)
        summary["serve_scale_speedup"] = round(scale, 2)
        summary["serve_scale_min"] = SERVE_SCALE_MIN
        summary["serve_scale_linear_ok"] = bool(scale >= SERVE_SCALE_MIN)
        summary["serve_scale_p99_1_s"] = round(p99_one, 4)
        summary["serve_scale_p99_n_s"] = round(p99_many, 4)
        summary["serve_scale_p99_ok"] = bool(p99_many <= p99_one)
        summary["serve_scale_router_p50_ms"] = round(router_p50 * 1e3, 4)
        summary["serve_scale_router_ok"] = bool(
            router_p50 < ROUTER_DECISION_BUDGET_S
        )
        summary["serve_prefix_reuse_ok"] = prefix_reuse_ok
        summary["serve_prefix_prefill_full"] = (
            prefix_info["prefill_positions_full"]
        )
        summary["serve_prefix_prefill_reuse"] = (
            prefix_info["prefill_positions_reuse"]
        )
        emit({
            "phase": "serve_scale",
            "replicas": SERVE_SCALE_REPLICAS,
            "requests": SERVE_SCALE_REQUESTS,
            "tokens_per_request": SERVE_SCALE_TOKENS,
            "step_s": SERVE_SCALE_STEP_S,
            "wall_1_s": round(one_arm["wall_s"], 3),
            "wall_n_s": round(many_arm["wall_s"], 3),
            "tokens_per_s_1": summary["serve_scale_tokens_per_s_1"],
            "tokens_per_s_n": summary["serve_scale_tokens_per_s_n"],
            "speedup": summary["serve_scale_speedup"],
            "speedup_min": SERVE_SCALE_MIN,
            "linear_ok": summary["serve_scale_linear_ok"],
            "p99_1_s": summary["serve_scale_p99_1_s"],
            "p99_n_s": summary["serve_scale_p99_n_s"],
            "p99_ok": summary["serve_scale_p99_ok"],
            "router_decision_p50_ms":
                summary["serve_scale_router_p50_ms"],
            "router_decision_budget_ms":
                round(ROUTER_DECISION_BUDGET_S * 1e3, 3),
            "router_ok": summary["serve_scale_router_ok"],
            "per_replica_served": many_arm["per_replica_served"],
            "latency_attribution": latency_attribution(
                many_arm["trace_ids"]
            ),
            "prefix_reuse": prefix_info,
            "prefix_reuse_ok": prefix_reuse_ok,
            "introspection": introspection_view([
                "covalent_tpu_serve_replicas",
                "covalent_tpu_serve_replica_in_flight",
                "covalent_tpu_serve_router_decision_seconds",
            ]),
            **spread_stats(many_arm["latencies"], "serve_scale_latency"),
        })
    except _PhaseSkipped:
        emit({"phase": "serve_scale", "skipped": "BENCH_PHASES"})
    except Exception as error:  # noqa: BLE001
        emit({"phase": "serve_scale", "error": repr(error)})

    # ---- phase 2b4: gray-failure defense (health + hedging) --------------
    # One replica of three is browned out (every engine step pays a
    # GRAY_SLOW_S chaos sleep — alive, heartbeating, just 50x slower:
    # the gray failure a crash-stop breaker never sees).  Three arms
    # under the SAME open-loop load: healthy baseline, brownout with the
    # defense OFF (pre-defense behavior: ~1/3 of requests eat the
    # brownout), and brownout with health scoring + tail hedging ON.
    # Asserted: hedged p99 recovers to within GRAY_HEDGED_MAX of
    # healthy, unhedged degrades >= GRAY_UNHEDGED_MIN, every stream
    # byte-equal across arms (the hedge's exactly-once splice), zero
    # shed, hedges actually fired, and health transitions are in the
    # archived metrics.
    try:
        if "gray_failure" not in BENCH_PHASES:
            raise _PhaseSkipped
        from covalent_tpu_plugin.fleet.health import HEALTH
        from covalent_tpu_plugin.serving import open_replica_set

        def make_gray_factory(step_s: float, slots: int = 4):
            def factory():
                import time as _time

                class Engine:
                    def __init__(self):
                        self.slots = slots
                        self.lanes = {}

                    def admit(self, rid, prompt, params):
                        seed = int(prompt[-1])
                        cap = int((params or {}).get(
                            "max_new_tokens", GRAY_TOKENS
                        ))
                        self.lanes[rid] = [
                            seed * 100 + j + 1 for j in range(cap)
                        ]

                    def step(self):
                        _time.sleep(step_s)
                        events = []
                        for rid in list(self.lanes):
                            chunk = self.lanes[rid][:4]
                            self.lanes[rid] = self.lanes[rid][4:]
                            done = not self.lanes[rid]
                            if done:
                                del self.lanes[rid]
                            events.append({
                                "rid": rid, "tokens": chunk, "done": done,
                            })
                        return events

                    def cancel(self, rid):
                        self.lanes.pop(rid, None)

                return Engine()

            return factory

        # The brownout rides the worker-side gray-chaos hook: the slow
        # replica's harness parses COVALENT_TPU_CHAOS from its process
        # env and pays a seeded slow-tail sleep per engine pump.
        # slow_s = slow_factor * max(jitter, 0.01).
        gray_chaos = (
            f"seed=11,jitter=0.02,p_slow=1.0,"
            f"slow_factor={GRAY_SLOW_S / 0.02:.0f}"
        )

        def gray_executor(tag: str, brownout: bool):
            env = {
                "PYTHONPATH": repo_root + os.pathsep
                + os.environ.get("PYTHONPATH", ""),
            }
            if brownout:
                env["COVALENT_TPU_CHAOS"] = gray_chaos
            return TPUExecutor(
                transport="local",
                cache_dir=f"{workdir}/cache_gray_{tag}",
                remote_cache=f"{workdir}/remote_gray_{tag}",
                python_path=sys.executable,
                poll_freq=0.2,
                use_agent="pool",
                pool_preload="cloudpickle",
                prewarm=False,
                heartbeat_interval=0.0,
                task_env=env,
            )

        async def gray_arm(tag: str, brownout: bool, defended: bool) -> dict:
            # Arm-scoped env: the defense toggles read os.environ at
            # ReplicaSet construction / per judge call.
            overrides = {
                "COVALENT_TPU_HEDGE": "on" if defended else "off",
                "COVALENT_TPU_HEALTH": "" if defended else "off",
                "COVALENT_TPU_HEDGE_BUDGET_PCT": "60",
                "COVALENT_TPU_HEDGE_PERCENTILE": "90",
            }
            saved = {k: os.environ.get(k) for k in overrides}
            saved_min_samples = HEALTH.min_samples
            HEALTH.reset()
            HEALTH.min_samples = 3
            for k, v in overrides.items():
                os.environ[k] = v
            executors = [
                gray_executor(f"{tag}_{i}", brownout and i == 2)
                for i in range(3)
            ]
            try:
                rset = await open_replica_set(
                    executors,
                    make_gray_factory(GRAY_STEP_S),
                    name=f"gray_{tag}",
                    stats_interval_s=0.2,
                )
                shed = 0

                async def offer(n: int, base: int) -> list:
                    nonlocal shed
                    out = []
                    for i in range(n):
                        try:
                            out.append(await rset.request(
                                [base + i],
                                params={"max_new_tokens": GRAY_TOKENS},
                                tenant=f"t{i % 2}",
                            ))
                        except Exception:  # noqa: BLE001 - shed counts
                            shed += 1
                        await asyncio.sleep(GRAY_ARRIVAL_S)
                    return out

                # Warm-up: trains the hedge TTFT ring and lets the
                # health monitor learn the brownout (lost hedges charge
                # the straggling primary); excluded from the measurement.
                warm = await offer(GRAY_WARMUP, 100)
                await asyncio.gather(
                    *(r.result(timeout=GRAY_BUDGET_S) for r in warm)
                )
                if brownout and defended:
                    # Measure the RECOVERED steady state, not the
                    # detection window: wait (bounded) until the health
                    # monitor has actually demoted the browned-out
                    # replica before offering the measured batch.
                    for _ in range(100):
                        states = {
                            HEALTH.state(sup.sid)
                            for sup in rset.supervisors.values()
                        }
                        if states & {"degraded", "quarantined"}:
                            break
                        await asyncio.sleep(0.1)
                measured = await offer(GRAY_REQUESTS, 200)
                results = await asyncio.gather(
                    *(r.result(timeout=GRAY_BUDGET_S) for r in measured)
                )
                latencies = [r.latency_s for r in measured]
                status = rset.status()
                await rset.close()
            finally:
                for k, v in saved.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v
                HEALTH.min_samples = saved_min_samples
                for ex in executors:
                    await ex.close()
            return {
                "results": list(results),
                "latencies": latencies,
                "p99_s": percentile(latencies, 0.99),
                "shed": shed,
                "hedge": status.get("hedge", {}),
                "health": {
                    rid: {
                        "score": view.get("health_score"),
                        "state": view.get("health_state"),
                    }
                    for rid, view in status["replicas"].items()
                },
            }

        async def gray_phase():
            healthy = await gray_arm("healthy", False, False)
            unhedged = await gray_arm("unhedged", True, False)
            hedged = await gray_arm("hedged", True, True)
            return healthy, unhedged, hedged

        healthy_arm, unhedged_arm, hedged_arm = await asyncio.wait_for(
            gray_phase(), GRAY_BUDGET_S
        )
        expected = [
            [(200 + i) * 100 + j + 1 for j in range(GRAY_TOKENS)]
            for i in range(GRAY_REQUESTS)
        ]
        byte_equal = (
            healthy_arm["results"] == expected
            and unhedged_arm["results"] == expected
            and hedged_arm["results"] == expected
        )
        total_shed = (
            healthy_arm["shed"] + unhedged_arm["shed"] + hedged_arm["shed"]
        )
        p99_floor = max(healthy_arm["p99_s"], GRAY_P99_FLOOR_S)
        hedge_recovered = bool(
            hedged_arm["p99_s"] <= GRAY_HEDGED_MAX * p99_floor
        )
        unhedged_degraded = bool(
            unhedged_arm["p99_s"] >= GRAY_UNHEDGED_MIN * p99_floor
        )
        hedges_issued = int(hedged_arm["hedge"].get("issued") or 0)
        summary["gray_failure_p99_healthy_s"] = round(
            healthy_arm["p99_s"], 4
        )
        summary["gray_failure_p99_unhedged_s"] = round(
            unhedged_arm["p99_s"], 4
        )
        summary["gray_failure_p99_hedged_s"] = round(hedged_arm["p99_s"], 4)
        summary["gray_failure_hedge_p99_recovered"] = hedge_recovered
        summary["gray_failure_unhedged_degraded"] = unhedged_degraded
        summary["gray_failure_streams_byte_equal"] = byte_equal
        summary["gray_failure_shed"] = total_shed
        summary["gray_failure_hedges_issued"] = hedges_issued
        summary["gray_failure_hedge_wins"] = int(
            hedged_arm["hedge"].get("wins") or 0
        )
        emit({
            "phase": "gray_failure",
            "requests": GRAY_REQUESTS,
            "warmup": GRAY_WARMUP,
            "slow_s": GRAY_SLOW_S,
            "p99_healthy_s": summary["gray_failure_p99_healthy_s"],
            "p99_unhedged_s": summary["gray_failure_p99_unhedged_s"],
            "p99_hedged_s": summary["gray_failure_p99_hedged_s"],
            "hedged_max": GRAY_HEDGED_MAX,
            "unhedged_min": GRAY_UNHEDGED_MIN,
            "hedge_p99_recovered": hedge_recovered,
            "unhedged_degraded": unhedged_degraded,
            "streams_byte_equal": byte_equal,
            "shed": total_shed,
            "hedge": hedged_arm["hedge"],
            "replica_health": hedged_arm["health"],
            "introspection": introspection_view([
                "covalent_tpu_health_score",
                "covalent_tpu_health_transitions_total",
                "covalent_tpu_serve_hedges_total",
            ]),
            **spread_stats(hedged_arm["latencies"], "gray_hedged_latency"),
        })
    except _PhaseSkipped:
        emit({"phase": "gray_failure", "skipped": "BENCH_PHASES"})
    except Exception as error:  # noqa: BLE001
        emit({"phase": "gray_failure", "error": repr(error)})

    # ---- phase 2b-ter: disaggregated prefill/decode serving --------------
    # The SAME open-loop mixed short/long-prompt traffic through the SAME
    # decode tier twice: fused (every replica prefills its own long
    # prompts inside its engine loop, stalling every stream it hosts) vs
    # disaggregated (a prefill tier runs prefill_only, ships the KV
    # bundle through the CAS/channel digest-verified, and decode replicas
    # admit_from_kv).  Asserted: byte-equal streams across arms (and vs
    # the deterministic single-engine expectation), decode tokens/s no
    # lower with the split (expected higher — that is the phase's point),
    # KV transfer bytes + p50 latency accounted in the artifact, and a
    # real-ContinuousEngine arm proving prefix-tree hits > 0 plus
    # bit-equal KV-disaggregated streams.
    try:
        if "serve_disagg" not in BENCH_PHASES:
            raise _PhaseSkipped
        from covalent_tpu_plugin.serving import (
            open_disaggregated_set,
            open_replica_set,
        )

        def make_disagg_factory():
            step_s = SERVE_DISAGG_STEP_S
            prefill_s = SERVE_DISAGG_PREFILL_S_PER_TOK

            def factory():
                import pickle as pickle_mod
                import time as _time

                class Engine:
                    def __init__(self):
                        self.slots = 2
                        self.lanes = {}
                        self.stats = {"prefill_positions": 0,
                                      "kv_exports": 0}

                    def _tokens(self, prompt, cap):
                        base = int(prompt[-1])
                        return [base + j + 1 for j in range(cap)]

                    def admit(self, rid, prompt, params):
                        cap = int((params or {}).get("max_new_tokens", 8))
                        _time.sleep(prefill_s * len(prompt))
                        self.stats["prefill_positions"] += len(prompt)
                        self.lanes[rid] = self._tokens(prompt, cap)

                    def prefill_only(self, prompt, params):
                        _time.sleep(prefill_s * len(prompt))
                        self.stats["prefill_positions"] += len(prompt)
                        self.stats["kv_exports"] += 1
                        return pickle_mod.dumps({
                            "prompt": [int(t) for t in prompt],
                        })

                    def admit_from_kv(self, rid, data, params):
                        bundle = pickle_mod.loads(bytes(data))
                        cap = int((params or {}).get("max_new_tokens", 8))
                        self.lanes[rid] = self._tokens(
                            bundle["prompt"], cap
                        )

                    def step(self):
                        _time.sleep(step_s)
                        events = []
                        for rid in list(self.lanes):
                            chunk = self.lanes[rid][:2]
                            self.lanes[rid] = self.lanes[rid][2:]
                            done = not self.lanes[rid]
                            if done:
                                del self.lanes[rid]
                            events.append({
                                "rid": rid, "tokens": chunk, "done": done,
                            })
                        return events

                    def cancel(self, rid):
                        self.lanes.pop(rid, None)

                return Engine()

            return factory

        def disagg_executor(tag: str):
            return TPUExecutor(
                transport="local",
                cache_dir=f"{workdir}/cache_disagg_{tag}",
                remote_cache=f"{workdir}/remote_disagg_{tag}",
                python_path=sys.executable,
                poll_freq=0.2,
                use_agent="pool",
                pool_preload="cloudpickle",
                prewarm=False,
                heartbeat_interval=0.0,
                task_env={
                    "PYTHONPATH": repo_root + os.pathsep
                    + os.environ.get("PYTHONPATH", ""),
                },
            )

        def disagg_prompts():
            prompts = []
            for i in range(SERVE_DISAGG_REQUESTS):
                if i % 4 == 0:  # every fourth request is a long prompt
                    prompts.append(
                        list(range(SERVE_DISAGG_LONG_PROMPT - 1))
                        + [1000 + i]
                    )
                else:
                    prompts.append([7, 1000 + i])
            return prompts

        async def disagg_arm(disaggregate: bool) -> dict:
            tags = [
                f"{'d' if disaggregate else 'f'}dec{i}"
                for i in range(SERVE_DISAGG_DECODE)
            ]
            executors = [disagg_executor(tag) for tag in tags]
            prefill_ex = None
            try:
                if disaggregate:
                    prefill_ex = disagg_executor("pre")
                    sset = await open_disaggregated_set(
                        [prefill_ex] + executors,
                        make_disagg_factory(),
                        decode_replicas=SERVE_DISAGG_DECODE,
                        prefill_replicas=1,
                        min_prompt_tokens=8,
                        name="disagg",
                        stats_interval_s=0.2,
                    )
                else:
                    sset = await open_replica_set(
                        executors,
                        make_disagg_factory(),
                        name="fused",
                        stats_interval_s=0.2,
                    )
                prompts = disagg_prompts()
                t0 = time.perf_counter()
                tasks = []
                for prompt in prompts:
                    tasks.append(asyncio.ensure_future(sset.request(
                        prompt,
                        params={"max_new_tokens": SERVE_DISAGG_TOKENS},
                    )))
                    await asyncio.sleep(SERVE_DISAGG_ARRIVAL_S)
                requests = await asyncio.gather(*tasks)
                results = await asyncio.gather(
                    *(
                        r.result(timeout=SERVE_DISAGG_BUDGET_S)
                        for r in requests
                    )
                )
                wall = time.perf_counter() - t0
                latencies = [r.latency_s for r in requests]
                trace_ids = [r.span.trace_id for r in requests]
                status = sset.status()
                await sset.close()
            finally:
                for ex in executors:
                    await ex.close()
                if prefill_ex is not None:
                    await prefill_ex.close()
            return {
                "wall_s": wall,
                "results": list(results),
                "latencies": latencies,
                "trace_ids": trace_ids,
                "status": status,
            }

        def kv_probe(prefix_len, n_requests, cap):
            # Runs INSIDE a worker process (the bench parent never
            # imports jax): the REAL ContinuousEngine split into a
            # prefill engine and a decode engine over serialized KV
            # bundles, driven with repeated-prefix prompts so the
            # prefix tree gets exercised on the prefill tier.
            import jax
            import jax.numpy as jnp
            import numpy as np

            from covalent_tpu_plugin.models import (
                TransformerConfig,
                TransformerLM,
            )
            from covalent_tpu_plugin.models.serve import ContinuousEngine

            cfg = TransformerConfig(
                vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                d_ff=64, max_seq=64, dtype=jnp.float32,
                attention="reference",
            )
            model = TransformerLM(cfg)
            params = model.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
            )["params"]
            rng = np.random.default_rng(0)
            prefix = rng.integers(0, 64, prefix_len).astype(np.int32)
            prompts = [
                np.concatenate([
                    prefix,
                    rng.integers(0, 64, 2 + i % 3).astype(np.int32),
                ])
                for i in range(n_requests)
            ]

            def drive(engine, admitter):
                streams = {}
                done = set()
                queue = list(enumerate(prompts))
                for _ in range(500):
                    while queue and engine.busy < engine.slots:
                        i, p = queue.pop(0)
                        admitter(f"r{i}", p)
                        streams[f"r{i}"] = []
                    for event in engine.step():
                        streams[event["rid"]].extend(event["tokens"])
                        if event["done"]:
                            done.add(event["rid"])
                    if len(done) == len(prompts) and not queue:
                        break
                return streams

            joint = ContinuousEngine(
                model, params, max_batch=2, sync_steps=4,
                max_new_tokens=cap,
            )
            joint_streams = drive(
                joint,
                lambda rid, p: joint.admit(
                    rid, p, {"max_new_tokens": cap}
                ),
            )
            joint.close()
            prefill_engine = ContinuousEngine(
                model, params, max_batch=2, sync_steps=4,
                max_new_tokens=cap,
            )
            decode_engine = ContinuousEngine(
                model, params, max_batch=2, sync_steps=4,
                max_new_tokens=cap,
            )
            bundles = {
                f"r{i}": prefill_engine.prefill_only(
                    p, {"max_new_tokens": cap}
                )
                for i, p in enumerate(prompts)
            }
            kv_bytes = sum(len(b) for b in bundles.values())
            disagg_streams = drive(
                decode_engine,
                lambda rid, p: decode_engine.admit_from_kv(
                    rid, bundles[rid], {"max_new_tokens": cap}
                ),
            )
            out = {
                "equal": disagg_streams == joint_streams,
                "requests": n_requests,
                "prefix_hits": prefill_engine.stats["prefix_hits"],
                "kv_exports": prefill_engine.stats["kv_exports"],
                "kv_admits": decode_engine.stats["kv_admits"],
                "decode_prefill_positions":
                    decode_engine.stats["prefill_positions"],
                "kv_bundle_bytes": kv_bytes,
            }
            prefill_engine.close()
            decode_engine.close()
            return out

        async def kv_probe_arm() -> dict:
            ex = disagg_executor("probe")
            try:
                return await ex.run(
                    kv_probe, [10, 6, 6], {},
                    {"dispatch_id": "kvprobe", "node_id": 0},
                )
            finally:
                await ex.close()

        async def disagg_phase():
            fused = await disagg_arm(False)
            split = await disagg_arm(True)
            probe = await kv_probe_arm()
            return fused, split, probe

        fused_arm, split_arm, probe_info = await asyncio.wait_for(
            disagg_phase(), SERVE_DISAGG_BUDGET_S * 3
        )
        expected = [
            [p[-1] + j + 1 for j in range(SERVE_DISAGG_TOKENS)]
            for p in disagg_prompts()
        ]
        streams_identical = (
            fused_arm["results"] == expected
            and split_arm["results"] == expected
        )
        assert streams_identical, (fused_arm["results"],
                                   split_arm["results"])
        total_tokens = SERVE_DISAGG_REQUESTS * SERVE_DISAGG_TOKENS
        tps_fused = total_tokens / max(fused_arm["wall_s"], 1e-9)
        tps_split = total_tokens / max(split_arm["wall_s"], 1e-9)
        split_status = split_arm["status"]
        n_long = len([
            p for p in disagg_prompts() if len(p) >= 8
        ])
        assert split_status["requests_by_path"].get("disagg") == n_long, (
            split_status["requests_by_path"]
        )
        kv_accounted = bool(
            split_status["kv_bytes_total"] > 0
            and split_status["kv_transfer_p50_ms"] > 0
        )
        assert probe_info["equal"] is True, probe_info
        assert probe_info["decode_prefill_positions"] == 0, probe_info
        prefix_hit_ok = probe_info["prefix_hits"] > 0
        summary["serve_disagg_tokens_per_s_fused"] = round(tps_fused, 1)
        summary["serve_disagg_tokens_per_s"] = round(tps_split, 1)
        summary["serve_disagg_speedup"] = round(
            tps_split / max(tps_fused, 1e-9), 3
        )
        summary["disagg_no_slower"] = bool(
            tps_split >= tps_fused * 0.98
        )
        summary["disagg_beats_fused"] = bool(tps_split > tps_fused)
        summary["disagg_streams_identical"] = streams_identical
        summary["kv_transfer_accounted"] = kv_accounted
        summary["serve_disagg_kv_bytes"] = split_status["kv_bytes_total"]
        summary["serve_disagg_kv_p50_ms"] = (
            split_status["kv_transfer_p50_ms"]
        )
        summary["serve_disagg_prefix_hits"] = probe_info["prefix_hits"]
        summary["serve_disagg_prefix_hit_ok"] = prefix_hit_ok
        # Trace completeness verdicts ride the final combined line: the
        # disagg arm is the acceptance target (dispatcher -> prefill
        # worker -> decode worker under ONE trace), so its long-prompt
        # requests must yield at least one full four-segment waterfall
        # with zero orphan spans.
        attribution = latency_attribution(split_arm["trace_ids"])
        summary["trace_traces_found"] = attribution["traces_found"]
        summary["trace_traces_complete"] = attribution["traces_complete"]
        summary["trace_full_waterfalls"] = attribution[
            "traces_full_waterfall"
        ]
        summary["trace_orphan_spans"] = attribution["orphan_spans"]
        summary["trace_coverage_min"] = attribution.get("coverage_min")
        summary["trace_completeness_ok"] = bool(
            attribution["traces_complete"] >= 1
            and attribution["traces_full_waterfall"] >= 1
            and attribution["orphan_spans"] == 0
            and "error" not in attribution
        )
        emit({
            "phase": "serve_disagg",
            "requests": SERVE_DISAGG_REQUESTS,
            "long_prompt_tokens": SERVE_DISAGG_LONG_PROMPT,
            "decode_replicas": SERVE_DISAGG_DECODE,
            "wall_fused_s": round(fused_arm["wall_s"], 3),
            "wall_disagg_s": round(split_arm["wall_s"], 3),
            "tokens_per_s_fused": summary["serve_disagg_tokens_per_s_fused"],
            "tokens_per_s_disagg": summary["serve_disagg_tokens_per_s"],
            "speedup": summary["serve_disagg_speedup"],
            "no_slower": summary["disagg_no_slower"],
            "beats_fused": summary["disagg_beats_fused"],
            "streams_identical": streams_identical,
            "requests_by_path": split_status["requests_by_path"],
            "kv_bytes_total": split_status["kv_bytes_total"],
            "kv_transfer_p50_ms": split_status["kv_transfer_p50_ms"],
            "kv_transfer_accounted": kv_accounted,
            "kv_probe": probe_info,
            "latency_attribution": attribution,
            "p95_fused_s": round(
                percentile(fused_arm["latencies"], 0.95), 4
            ),
            "p95_disagg_s": round(
                percentile(split_arm["latencies"], 0.95), 4
            ),
            "introspection": introspection_view([
                "covalent_tpu_serve_kv_transfers_total",
                "covalent_tpu_serve_kv_transfer_seconds",
                "covalent_tpu_serve_disagg_requests_total",
            ]),
            **spread_stats(split_arm["latencies"], "serve_disagg_latency"),
        })
    except _PhaseSkipped:
        emit({"phase": "serve_disagg", "skipped": "BENCH_PHASES"})
    except Exception as error:  # noqa: BLE001
        emit({"phase": "serve_disagg", "error": repr(error)})

    # ---- phase 2b'': speculative + quantized decoding in the engine ------
    # Open-loop greedy load through three REAL ContinuousEngine arms in one
    # worker: fp, fp+draft (speculative), and a kv_quant lane group reached
    # through the per-request ``quality`` knob.  Asserted: the spec arm's
    # streams are byte-equal to fp's (greedy/exact contract) and its
    # aggregate tokens/s beats fp by >= SERVE_SPEC_SPEEDUP_MIN; accept
    # rate, per-mode token counters, and prefix-tree composition ride the
    # artifact.  These numbers fill the final JSON's spec_* fields when
    # the TPU lm_spec subphase did not run.
    try:
        if "serve_spec" not in BENCH_PHASES:
            raise _PhaseSkipped

        def spec_probe(n_requests, cap, draft_len, n_layers):
            # Runs INSIDE a worker process (the bench parent never
            # imports jax).
            import dataclasses as dc
            import time as _time

            import jax
            import jax.numpy as jnp
            import numpy as np

            from covalent_tpu_plugin.models import (
                TransformerConfig,
                TransformerLM,
            )
            from covalent_tpu_plugin.models.serve import ContinuousEngine
            from covalent_tpu_plugin.parallel.sharding import unbox

            cfg = TransformerConfig(
                vocab_size=64, d_model=128, n_layers=n_layers, n_heads=4,
                d_ff=512, max_seq=96, dtype=jnp.float32,
                attention="reference",
            )
            model = TransformerLM(cfg)
            params = unbox(model.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
            )["params"])
            # Zero the upper layers' residual contributions (attention
            # out-proj + MLP down-proj): the residual stream after the
            # full stack equals the stream after layer 0, so a 1-layer
            # draft sharing layer 0 + embed/unembed/final-norm predicts
            # the target's greedy argmax exactly.  Accept rate is 1.0 by
            # construction, making the measured speedup the pure
            # verify-slab amortization rather than model luck — while the
            # draft still genuinely costs 1/n_layers of a target step.
            layers = params["layers"]
            o_kernel = layers["attention"]["out_proj"]["kernel"]
            w_kernel = layers["mlp"]["wo"]["kernel"]
            layers = {
                **layers,
                "attention": {
                    **layers["attention"],
                    "out_proj": {"kernel": o_kernel.at[1:].set(0.0)},
                },
                "mlp": {
                    **layers["mlp"],
                    "wo": {
                        **layers["mlp"]["wo"],
                        "kernel": w_kernel.at[1:].set(0.0),
                    },
                },
            }
            params = {**params, "layers": layers}
            draft = TransformerLM(dc.replace(cfg, n_layers=1))
            dparams = {
                **params,
                "layers": jax.tree_util.tree_map(
                    lambda leaf: leaf[:1], params["layers"]
                ),
            }
            rng = np.random.default_rng(0)
            prompts = [
                rng.integers(1, 64, 4 + i % 4).astype(np.int32)
                for i in range(n_requests)
            ]

            def drive(engine, quality=None):
                base = {"max_new_tokens": cap}
                if quality is not None:
                    base["quality"] = quality
                streams, done = {}, set()
                queue = list(enumerate(prompts))
                for _ in range(10000):
                    while queue and engine.busy < engine.slots:
                        i, p = queue.pop(0)
                        engine.admit(f"r{i}", p, dict(base))
                        streams[f"r{i}"] = []
                    for event in engine.step():
                        streams[event["rid"]].extend(event["tokens"])
                        if event["done"]:
                            done.add(event["rid"])
                    if len(done) == len(prompts) and not queue:
                        break
                return streams

            def arm(quality=None, **kw):
                engine = ContinuousEngine(
                    model, params, max_batch=4,
                    sync_steps=2 * (draft_len + 1), max_new_tokens=cap,
                    length=cfg.max_seq - draft_len - 2, **kw,
                )
                # TWO warmup drives before timing: the first compiles the
                # cold-tree admission waves + the decode loop; the second
                # compiles the warm-prefix-tree SUFFIX admission waves
                # (the timed pass re-admits the same prompts into a tree
                # the warmups left warm, a different wave shape).  A
                # single warmup leaves a multi-second recompile inside
                # the timed window.
                drive(engine, quality)
                repeat = drive(engine, quality)
                seen = dict(engine.stats)
                t0 = _time.perf_counter()
                streams = drive(engine, quality)
                wall = _time.perf_counter() - t0
                stats = dict(engine.stats)
                refusal = getattr(engine, "_spec_refusal", None)
                engine.close()
                proposed = (
                    stats.get("spec_proposed", 0)
                    - seen.get("spec_proposed", 0)
                )
                accepted = (
                    stats.get("spec_accepted", 0)
                    - seen.get("spec_accepted", 0)
                )
                return {
                    "streams": {
                        rid: [int(t) for t in toks]
                        for rid, toks in streams.items()
                    },
                    "deterministic": streams == repeat,
                    "tokens": sum(len(s) for s in streams.values()),
                    "wall_s": wall,
                    "accept_rate": (
                        round(accepted / proposed, 4) if proposed else None
                    ),
                    "prefix_hits": int(stats.get("prefix_hits", 0)),
                    "mode_tokens": {
                        key[len("mode_tokens_"):]: int(v)
                        for key, v in stats.items()
                        if key.startswith("mode_tokens_")
                    },
                    "spec_refusal": refusal,
                    "mode_refusals": int(stats.get("mode_refusals", 0)),
                }

            fp = arm()
            spec = arm(
                draft_model=draft, draft_params=dparams,
                draft_len=draft_len,
            )
            quant = arm(
                quality="kv_quant", decode_modes=("fp", "kv_quant"),
                draft_model=draft, draft_params=dparams,
                draft_len=draft_len,
            )
            return {
                "fp": fp, "spec": spec, "spec_quant": quant,
                "exact": fp["streams"] == spec["streams"],
            }

        spec_ex = TPUExecutor(
            transport="local",
            cache_dir=f"{workdir}/cache_spec",
            remote_cache=f"{workdir}/remote_spec",
            python_path=sys.executable,
            poll_freq=0.2,
            use_agent="pool",
            pool_preload="cloudpickle",
            prewarm=False,
            heartbeat_interval=0.0,
            task_env={
                "PYTHONPATH": repo_root + os.pathsep
                + os.environ.get("PYTHONPATH", ""),
            },
        )
        try:
            probe = await asyncio.wait_for(
                spec_ex.run(
                    spec_probe,
                    [SERVE_SPEC_REQUESTS, SERVE_SPEC_TOKENS,
                     SERVE_SPEC_DRAFT_LEN, SERVE_SPEC_LAYERS], {},
                    {"dispatch_id": "specprobe", "node_id": 0},
                ),
                SERVE_SPEC_BUDGET_S,
            )
        finally:
            await spec_ex.close()
        assert probe["spec"]["spec_refusal"] is None, (
            probe["spec"]["spec_refusal"]
        )
        assert probe["exact"] is True, "spec arm diverged from fp arm"
        tps_fp = probe["fp"]["tokens"] / max(probe["fp"]["wall_s"], 1e-9)
        tps_spec = (
            probe["spec"]["tokens"] / max(probe["spec"]["wall_s"], 1e-9)
        )
        tps_quant = (
            probe["spec_quant"]["tokens"]
            / max(probe["spec_quant"]["wall_s"], 1e-9)
        )
        speedup = tps_spec / max(tps_fp, 1e-9)
        summary["serve_spec_tokens_per_s_fp"] = round(tps_fp, 1)
        summary["serve_spec_tokens_per_s"] = round(tps_spec, 1)
        summary["serve_spec_quant_tokens_per_s"] = round(tps_quant, 1)
        summary["serve_spec_speedup"] = round(speedup, 3)
        summary["serve_spec_speedup_ok"] = bool(
            speedup >= SERVE_SPEC_SPEEDUP_MIN
        )
        summary["serve_spec_exact"] = bool(probe["exact"])
        summary["serve_spec_accept_rate"] = probe["spec"]["accept_rate"]
        summary["serve_spec_quant_accept_rate"] = (
            probe["spec_quant"]["accept_rate"]
        )
        summary["serve_spec_quant_speedup"] = round(
            tps_quant / max(tps_fp, 1e-9), 3
        )
        # The kv_quant lane is not bit-equal to fp by design (quantized
        # KV numerics); its exactness contract is determinism — repeat
        # greedy drives produce identical streams.
        summary["serve_spec_quant_deterministic"] = bool(
            probe["spec_quant"]["deterministic"]
        )
        summary["serve_spec_prefix_hits"] = probe["spec"]["prefix_hits"]
        emit({
            "phase": "serve_spec",
            "requests": SERVE_SPEC_REQUESTS,
            "tokens_per_request": SERVE_SPEC_TOKENS,
            "draft_len": SERVE_SPEC_DRAFT_LEN,
            "target_layers": SERVE_SPEC_LAYERS,
            "tokens_per_s_fp": summary["serve_spec_tokens_per_s_fp"],
            "tokens_per_s_spec": summary["serve_spec_tokens_per_s"],
            "tokens_per_s_spec_quant":
                summary["serve_spec_quant_tokens_per_s"],
            "speedup": summary["serve_spec_speedup"],
            "speedup_quant": summary["serve_spec_quant_speedup"],
            "speedup_min": SERVE_SPEC_SPEEDUP_MIN,
            "speedup_ok": summary["serve_spec_speedup_ok"],
            "exact": summary["serve_spec_exact"],
            "accept_rate": summary["serve_spec_accept_rate"],
            "accept_rate_quant": summary["serve_spec_quant_accept_rate"],
            "quant_deterministic":
                summary["serve_spec_quant_deterministic"],
            "prefix_hits": summary["serve_spec_prefix_hits"],
            "mode_tokens": probe["spec_quant"]["mode_tokens"],
            "mode_refusals": probe["spec_quant"]["mode_refusals"],
            "wall_fp_s": round(probe["fp"]["wall_s"], 3),
            "wall_spec_s": round(probe["spec"]["wall_s"], 3),
            "wall_spec_quant_s": round(
                probe["spec_quant"]["wall_s"], 3
            ),
        })
    except _PhaseSkipped:
        emit({"phase": "serve_spec", "skipped": "BENCH_PHASES"})
    except Exception as error:  # noqa: BLE001
        emit({"phase": "serve_spec", "error": repr(error)})

    # ---- phase 2b-iv: multi-adapter LoRA multiplexing inside the engine --
    # One REAL ContinuousEngine hosting an adapter bank serves a mixed
    # round-robin load over MULTILORA_ADAPTERS distinct LoRA adapters in
    # co-batched decode waves, against per-adapter single-tenant engines
    # time-sharing the same device.  Asserted: streams byte-equal across
    # arms per request, the multiplexed aggregate tokens/s beats the
    # single-tenant aggregate by >= MULTILORA_SPEEDUP_MIN, and a hot
    # swap mid-stream drops nothing (the in-flight lane finishes on the
    # old generation byte-equal; the next admission decodes the new).
    try:
        if "serve_multilora" not in BENCH_PHASES:
            raise _PhaseSkipped

        def multilora_probe(n_adapters, n_requests, cap, rank, n_layers):
            # Runs INSIDE a worker process (the bench parent never
            # imports jax).
            import time as _time

            import jax
            import numpy as np
            import jax.numpy as jnp

            from covalent_tpu_plugin.models import (
                TransformerConfig,
                TransformerLM,
            )
            from covalent_tpu_plugin.models import lora as lora_mod
            from covalent_tpu_plugin.models.serve import ContinuousEngine
            from covalent_tpu_plugin.parallel.sharding import unbox

            cfg = TransformerConfig(
                vocab_size=64, d_model=64, n_layers=n_layers, n_heads=4,
                d_ff=256, max_seq=96, dtype=jnp.float32,
                attention="reference", scan_layers=False,
            )
            model = TransformerLM(cfg)
            params = unbox(model.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
            )["params"])

            def make_adapter(seed):
                # A "fine-tuned" adapter: randomized nonzero lora_a AND
                # lora_b (add_lora's fresh B is zero — the identity),
                # so every adapter genuinely changes the argmax.
                lmodel, filled = lora_mod.add_lora(
                    model, params, rank=rank, alpha=16.0
                )
                mask = jax.tree_util.tree_leaves(
                    lora_mod.lora_mask(filled)
                )
                leaves, treedef = jax.tree_util.tree_flatten(filled)
                key = jax.random.PRNGKey(seed)
                out = []
                for leaf, m in zip(leaves, mask):
                    if m:
                        key, sub = jax.random.split(key)
                        out.append(
                            jax.random.normal(
                                sub, leaf.shape, leaf.dtype
                            ) * 0.05
                        )
                    else:
                        out.append(leaf)
                tuned = jax.tree_util.tree_unflatten(treedef, out)
                return lmodel, tuned

            lmodel = None
            tuned, banks = [], {}
            for i in range(n_adapters):
                lmodel, tree = make_adapter(i + 1)
                tuned.append(tree)
                banks[f"a{i}"] = lora_mod.adapter_leaves(tree)
            rng = np.random.default_rng(0)
            requests = [
                (
                    f"a{i % n_adapters}",
                    rng.integers(1, 64, 4 + i % 4).astype(np.int32),
                )
                for i in range(n_requests)
            ]
            slots = max(4, n_adapters * 2)

            def drive(engine, subset):
                streams, done = {}, set()
                queue = [
                    (f"r{i}", name, prompt)
                    for i, (name, prompt) in enumerate(requests)
                    if subset is None or name == subset
                ]
                pending = list(queue)
                for _ in range(10000):
                    while pending and engine.busy < engine.slots:
                        rid, name, prompt = pending.pop(0)
                        prm = {"max_new_tokens": cap}
                        if subset is None:
                            prm["adapter"] = name
                        engine.admit(rid, prompt, prm)
                        streams[rid] = []
                    for event in engine.step():
                        streams[event["rid"]].extend(event["tokens"])
                        if event["done"]:
                            done.add(event["rid"])
                    if len(done) == len(queue) and not pending:
                        break
                return streams

            def timed(engine, subset=None):
                drive(engine, subset)   # cold compiles
                drive(engine, subset)   # warm prefix-tree wave shapes
                # Best-of-3: the min wall is the least-noise estimate on
                # a shared CPU box (scheduler jitter only ever adds).
                streams, best = None, float("inf")
                for _ in range(3):
                    t0 = _time.perf_counter()
                    streams = drive(engine, subset)
                    best = min(best, _time.perf_counter() - t0)
                return streams, best

            # Arm 1: ONE multiplexed engine, all adapters co-batched.
            mux = ContinuousEngine(
                model, params, max_batch=slots, sync_steps=4,
                max_new_tokens=cap, length=cfg.max_seq - 4,
                adapters=banks,
            )
            mux_streams, mux_wall = timed(mux)

            # Arm 2: per-adapter single-tenant engines PARTITIONING the
            # same slot budget (slots/N lanes each — dedicating a
            # session per tenant statically splits the device's batch
            # capacity, which is exactly the cost the bank removes),
            # each timed on its own quarter of the load; the device
            # time-shares them, so the aggregate wall is the sum.
            single_streams, single_wall = {}, 0.0
            for i in range(n_adapters):
                engine = ContinuousEngine(
                    lmodel, tuned[i],
                    max_batch=max(1, slots // n_adapters), sync_steps=4,
                    max_new_tokens=cap, length=cfg.max_seq - 4,
                )
                streams, wall = timed(engine, subset=f"a{i}")
                single_streams.update(streams)
                single_wall += wall
                engine.close()
            exact = all(
                [int(t) for t in mux_streams[rid]]
                == [int(t) for t in single_streams[rid]]
                for rid in single_streams
            )

            # Hot swap mid-stream: admit on a0, swap a0's generation
            # while the lane is mid-decode, admit again.  The in-flight
            # stream finishes on the OLD weights; the new admission
            # decodes the new — zero drops either side.
            _, fresh = make_adapter(97)
            old_oracle = mux_streams["r0"]
            swap_prompt = requests[0][1]
            mux.admit("swap_old", swap_prompt,
                      {"max_new_tokens": cap, "adapter": "a0"})
            swapped = {"swap_old": [], "swap_new": []}
            for _ in range(2):      # a couple of waves in flight first
                for event in mux.step():
                    swapped[event["rid"]].extend(event["tokens"])
            mux.attach_adapter("a0", lora_mod.adapter_leaves(fresh))
            mux.admit("swap_new", swap_prompt,
                      {"max_new_tokens": cap, "adapter": "a0"})
            for _ in range(10000):
                for event in mux.step():
                    swapped[event["rid"]].extend(event["tokens"])
                if not mux.busy:
                    break
            new_engine = ContinuousEngine(
                lmodel, fresh, max_batch=slots, sync_steps=4,
                max_new_tokens=cap, length=cfg.max_seq - 4,
            )
            new_engine.admit("swap_new", swap_prompt,
                             {"max_new_tokens": cap})
            new_oracle = []
            for _ in range(10000):
                for event in new_engine.step():
                    new_oracle.extend(event["tokens"])
                if not new_engine.busy:
                    break
            new_engine.close()
            stats = dict(mux.stats)
            mux.close()
            total = sum(len(s) for s in mux_streams.values())
            return {
                "tokens": total,
                "mux_wall_s": mux_wall,
                "single_wall_s": single_wall,
                "exact": bool(exact),
                "swap_old_exact": swapped["swap_old"] == old_oracle,
                "swap_new_exact": swapped["swap_new"] == new_oracle,
                "swap_complete": (
                    len(swapped["swap_old"]) == cap
                    and len(swapped["swap_new"]) == cap
                ),
                "adapter_tokens": {
                    key[len("adapter_tokens_"):]: int(v)
                    for key, v in stats.items()
                    if key.startswith("adapter_tokens_")
                },
                "swaps": int(stats.get("adapter_swaps", 0)),
                "attaches": int(stats.get("adapter_attaches", 0)),
                "prefix_blocked": int(
                    stats.get("adapter_prefix_blocked", 0)
                ),
            }

        multilora_ex = TPUExecutor(
            transport="local",
            cache_dir=f"{workdir}/cache_multilora",
            remote_cache=f"{workdir}/remote_multilora",
            python_path=sys.executable,
            poll_freq=0.2,
            use_agent="pool",
            pool_preload="cloudpickle",
            prewarm=False,
            heartbeat_interval=0.0,
            task_env={
                "PYTHONPATH": repo_root + os.pathsep
                + os.environ.get("PYTHONPATH", ""),
            },
        )
        try:
            probe = await asyncio.wait_for(
                multilora_ex.run(
                    multilora_probe,
                    [MULTILORA_ADAPTERS, MULTILORA_REQUESTS,
                     MULTILORA_TOKENS, MULTILORA_RANK,
                     MULTILORA_LAYERS], {},
                    {"dispatch_id": "multiloraprobe", "node_id": 0},
                ),
                MULTILORA_BUDGET_S,
            )
        finally:
            await multilora_ex.close()
        assert probe["exact"] is True, (
            "multiplexed streams diverged from single-adapter oracles"
        )
        tps_mux = probe["tokens"] / max(probe["mux_wall_s"], 1e-9)
        tps_single = probe["tokens"] / max(probe["single_wall_s"], 1e-9)
        speedup = tps_mux / max(tps_single, 1e-9)
        # "Zero drops" at engine level IS stream completion: both the
        # in-flight lane (old generation) and the post-swap admission
        # ran to their full caps, byte-equal to their oracles — nothing
        # was cancelled, truncated, or re-decoded on the wrong weights.
        zero_drops = bool(
            probe["swap_old_exact"] and probe["swap_new_exact"]
            and probe["swap_complete"]
        )
        summary["serve_multilora_tokens_per_s"] = round(tps_mux, 1)
        summary["serve_multilora_tokens_per_s_single"] = round(
            tps_single, 1
        )
        summary["serve_multilora_speedup"] = round(speedup, 3)
        summary["serve_multilora_speedup_ok"] = bool(
            speedup >= MULTILORA_SPEEDUP_MIN
        )
        summary["serve_multilora_exact"] = bool(probe["exact"])
        summary["serve_multilora_swap_zero_drops"] = zero_drops
        emit({
            "phase": "serve_multilora",
            "adapters": MULTILORA_ADAPTERS,
            "requests": MULTILORA_REQUESTS,
            "tokens_per_request": MULTILORA_TOKENS,
            "rank": MULTILORA_RANK,
            "tokens_per_s_mux": summary["serve_multilora_tokens_per_s"],
            "tokens_per_s_single":
                summary["serve_multilora_tokens_per_s_single"],
            "speedup": summary["serve_multilora_speedup"],
            "speedup_min": MULTILORA_SPEEDUP_MIN,
            "speedup_ok": summary["serve_multilora_speedup_ok"],
            "exact": summary["serve_multilora_exact"],
            "swap_zero_drops": zero_drops,
            "swap_old_exact": probe["swap_old_exact"],
            "swap_new_exact": probe["swap_new_exact"],
            "hot_swaps": probe["swaps"],
            "attaches": probe["attaches"],
            "adapter_tokens": probe["adapter_tokens"],
            "prefix_blocked": probe["prefix_blocked"],
            "wall_mux_s": round(probe["mux_wall_s"], 3),
            "wall_single_s": round(probe["single_wall_s"], 3),
        })
    except _PhaseSkipped:
        emit({"phase": "serve_multilora", "skipped": "BENCH_PHASES"})
    except Exception as error:  # noqa: BLE001
        emit({"phase": "serve_multilora", "error": repr(error)})

    # ---- phase 2c: recovery overhead under one injected channel death ----
    # A 4-electron fan-out through a ChaosTransport that kills exactly ONE
    # control-plane channel mid-poll, with 2 gang retries budgeted: the
    # resilience layer must complete every electron with zero local
    # fallbacks, and the wall-clock delta vs the clean fanout8 phase IS the
    # measured recovery overhead (teardown + redial + CAS re-stage +
    # relaunch + backoff).
    try:
        if "chaos_fanout" not in BENCH_PHASES:
            raise _PhaseSkipped
        from covalent_tpu_plugin.transport import ChaosPlan

        def resilience_counters() -> dict:
            return {
                key: value
                for key, value in metrics_totals().items()
                if key.startswith(("covalent_tpu_task_retries_total",
                                   "covalent_tpu_chaos_faults_total"))
            }

        def chaos_executor(plan):
            return TPUExecutor(
                transport="local",
                cache_dir=f"{workdir}/cache_chaos",
                remote_cache=f"{workdir}/remote_chaos",
                python_path=sys.executable,
                poll_freq=0.2,
                pool_preload="cloudpickle",
                use_agent=False,  # poll path: where the drop_match bites
                max_task_retries=2,
                retry_base_delay=0.05,
                retry_max_delay=0.2,
                chaos=plan,
                task_env={
                    "PYTHONPATH": repo_root + os.pathsep
                    + os.environ.get("PYTHONPATH", ""),
                },
            )

        async def fanout4(ex, dispatch_id):
            t0 = time.perf_counter()
            results = await asyncio.gather(
                *(
                    ex.run(
                        trivial_electron, [i], {},
                        {"dispatch_id": dispatch_id, "node_id": i},
                    )
                    for i in range(4)
                )
            )
            return time.perf_counter() - t0, results

        async def chaos_phase():
            # Clean baseline FIRST, same shape and config (4 concurrent
            # electrons do NOT cost half an 8-fan-out's wall — dispatch is
            # parallel — so the overhead must be measured against an
            # actual clean 4-fan-out, not a scaled fanout8 number).
            clean_ex = chaos_executor(None)
            try:
                await fanout4(clean_ex, "chaoswarm")  # warm pool/CAS
                clean_wall, _ = await fanout4(clean_ex, "chaosclean")
            finally:
                await clean_ex.close()
            plan = ChaosPlan(drop_match="if test -f", max_faults=1)
            chaos_ex = chaos_executor(plan)
            try:
                wall, results = await fanout4(chaos_ex, "chaosfan")
            finally:
                await chaos_ex.close()
            return clean_wall, wall, results, plan.faults_injected

        counters_before = resilience_counters()
        clean_wall, chaos_wall, results, faults = await asyncio.wait_for(
            chaos_phase(), FANOUT_BUDGET_S
        )
        assert results == [trivial_electron(i) for i in range(4)], results
        counters_delta = {
            key: round(value - counters_before.get(key, 0.0), 1)
            for key, value in resilience_counters().items()
            if value != counters_before.get(key, 0.0)
        }
        summary["chaos_fanout4_wall_s"] = round(chaos_wall, 3)
        summary["chaos_fanout4_clean_wall_s"] = round(clean_wall, 3)
        summary["chaos_fanout_faults_injected"] = faults
        summary["chaos_fanout_recovery_overhead_s"] = round(
            chaos_wall - clean_wall, 3
        )
        emit({
            "phase": "chaos_fanout",
            "wall_s": summary["chaos_fanout4_wall_s"],
            "clean_wall_s": summary["chaos_fanout4_clean_wall_s"],
            "faults_injected": faults,
            "completed": len(results),
            "resilience_counters_delta": counters_delta,
            "recovery_overhead_s":
                summary["chaos_fanout_recovery_overhead_s"],
        })
    except _PhaseSkipped:
        emit({"phase": "chaos_fanout", "skipped": "BENCH_PHASES"})
    except Exception as error:  # noqa: BLE001
        emit({"phase": "chaos_fanout", "error": repr(error)})

    # ---- phase 2c': elastic gangs under spot preemption ------------------
    # The same checkpoint-cooperative training electron through three arms:
    # clean (no faults), full-retry (preempted, checkpointing OFF — the
    # pre-elastic behavior: the retry recomputes from step 0), and resume
    # (preempted, interval checkpointing ON — the retry restores the
    # newest complete checkpoint).  The artifact records recomputed steps
    # and recovered wall per arm; CI asserts the resume arm recomputes at
    # most HALF the full-retry arm's steps and recovers strictly faster.
    try:
        if "preemption_chaos" not in BENCH_PHASES:
            raise _PhaseSkipped
        from covalent_tpu_plugin.transport import ChaosPlan

        PREEMPT_STEPS = int(os.environ.get("BENCH_PREEMPT_STEPS", "80"))
        PREEMPT_STEP_S = float(os.environ.get("BENCH_PREEMPT_STEP_S", "0.05"))

        def preempt_executor(arm: str, plan, checkpoint_s: float):
            return TPUExecutor(
                transport="local",
                cache_dir=f"{workdir}/cache_preempt_{arm}",
                remote_cache=f"{workdir}/remote_preempt_{arm}",
                python_path=sys.executable,
                poll_freq=0.1,
                pool_preload="cloudpickle",
                use_agent=False,       # poll path: ops drive the preempt op count
                heartbeat_interval=0.5,  # telemetry carries the preempt notice
                max_task_retries=2,
                retry_base_delay=0.05,
                retry_max_delay=0.2,
                checkpoint_interval_s=checkpoint_s,
                chaos=plan,
                task_env={
                    "PYTHONPATH": repo_root + os.pathsep
                    + os.environ.get("PYTHONPATH", ""),
                },
            )

        async def preempt_arm(arm: str, chaos: bool, checkpoint_s: float):
            plan = (
                ChaosPlan(preempt_after=20, preempt_grace=1.0, max_faults=1)
                if chaos
                else None
            )
            ex = preempt_executor(arm, plan, checkpoint_s)
            progress = f"{workdir}/preempt_progress_{arm}.txt"
            t0 = time.perf_counter()
            try:
                result = await ex.run(
                    preemptible_train,
                    [PREEMPT_STEPS, PREEMPT_STEP_S, progress],
                    {},
                    {"dispatch_id": f"preempt-{arm}", "node_id": 0},
                )
            finally:
                await ex.close()
            wall = time.perf_counter() - t0
            with open(progress) as f:
                executed = [int(x) for x in f.read().split()]
            return {
                "arm": arm,
                "wall_s": round(wall, 3),
                "result_ok": result[0] == sum(range(PREEMPT_STEPS)),
                "resumed_start": int(result[1]),
                "steps_executed": len(executed),
                "steps_recomputed": len(executed) - len(set(executed)),
                "faults_injected": plan.faults_injected if plan else 0,
            }

        async def preemption_phase():
            clean = await preempt_arm("clean", chaos=False, checkpoint_s=0.0)
            retry = await preempt_arm("retry", chaos=True, checkpoint_s=0.0)
            resume = await preempt_arm(
                "resume", chaos=True, checkpoint_s=0.1
            )
            return clean, retry, resume

        clean, retry, resume = await asyncio.wait_for(
            preemption_phase(), FANOUT_BUDGET_S * 2
        )
        assert clean["result_ok"] and retry["result_ok"], (clean, retry)
        assert resume["result_ok"], resume
        retry_recovered = max(0.0, retry["wall_s"] - clean["wall_s"])
        resume_recovered = max(0.0, resume["wall_s"] - clean["wall_s"])
        summary["preemption_clean_wall_s"] = clean["wall_s"]
        summary["preemption_retry_wall_s"] = retry["wall_s"]
        summary["preemption_resume_wall_s"] = resume["wall_s"]
        summary["preemption_retry_recomputed_steps"] = (
            retry["steps_recomputed"]
        )
        summary["preemption_resume_recomputed_steps"] = (
            resume["steps_recomputed"]
        )
        summary["preemption_retry_recovered_wall_s"] = round(
            retry_recovered, 3
        )
        summary["preemption_resume_recovered_wall_s"] = round(
            resume_recovered, 3
        )
        # Both faulted arms must actually have been preempted for the
        # comparison to mean anything; the resume arm must have resumed.
        faulted = (
            retry["faults_injected"] == 1
            and resume["faults_injected"] == 1
            and retry["resumed_start"] == 0
            and resume["resumed_start"] > 0
        )
        summary["preemption_resume_recomputed_ok"] = bool(
            faulted
            and resume["steps_recomputed"]
            <= retry["steps_recomputed"] / 2
        )
        summary["preemption_resume_recovered_ok"] = bool(
            faulted and resume_recovered < retry_recovered
        )
        emit({
            "phase": "preemption_chaos",
            "steps": PREEMPT_STEPS,
            "arms": [clean, retry, resume],
            "retry_recovered_wall_s": round(retry_recovered, 3),
            "resume_recovered_wall_s": round(resume_recovered, 3),
            "resume_recomputed_ok":
                summary["preemption_resume_recomputed_ok"],
            "resume_recovered_ok":
                summary["preemption_resume_recovered_ok"],
        })
    except _PhaseSkipped:
        emit({"phase": "preemption_chaos", "skipped": "BENCH_PHASES"})
    except Exception as error:  # noqa: BLE001
        emit({"phase": "preemption_chaos", "error": repr(error)})

    # ---- phase 2c'': dispatcher crash recovery ---------------------------
    # SIGKILL the *dispatcher* (not a worker) mid-stream and prove the
    # successor incarnation replays the journal, re-adopts the surviving
    # pool servers and serving sessions, and resumes every in-flight
    # stream exactly once — the resumed tail splices byte-for-byte onto
    # the journaled high-water mark, no duplicate and no lost token.
    # Drill children carry the actual kill (a process cannot -9 itself
    # and keep benching); see run_dispatcher_crash_drill.
    try:
        if "dispatcher_crash" not in BENCH_PHASES:
            raise _PhaseSkipped
        # Overridable so CI can land the journal inside its artifact dir.
        drill_dir = (
            os.environ.get("BENCH_DISPATCHER_CRASH_DIR")
            or f"{workdir}/dispatcher_crash"
        )
        drill = await asyncio.get_running_loop().run_in_executor(
            None, run_dispatcher_crash_drill, drill_dir
        )
        summary["dispatcher_crash_recovery_s"] = drill["recovery_duration_s"]
        summary["dispatcher_crash_adopted"] = drill["sessions_adopted"]
        summary["dispatcher_crash_orphaned"] = drill["sessions_orphaned"]
        summary["dispatcher_crash_fallback_local"] = sum(
            value for key, value in drill["metrics"].items()
            if "fallback_local" in key
        )
        summary["recovery_streams_exact"] = drill["streams_exact"]
        emit({"phase": "dispatcher_crash", **drill})
    except _PhaseSkipped:
        emit({"phase": "dispatcher_crash", "skipped": "BENCH_PHASES"})
    except Exception as error:  # noqa: BLE001
        emit({"phase": "dispatcher_crash", "error": repr(error)})

    # ---- phase 2d: fleet scheduler fan-out vs naive 1:1 dispatch ---------
    # 16 electrons, 2 tenants, through the fleet work queue onto 2 warm
    # local pools (bin-packed onto pooled gangs, deficit-round-robin
    # fairness between the tenants) vs the pre-fleet shape: one FRESH
    # executor per electron, mapped 1:1 and dispatched sequentially.  The
    # scheduler arm's wall includes its own prewarm, so the comparison
    # charges the fleet for warming its gangs; warm-gang reuse must still
    # show as strictly fewer transport dials (connects < electrons) at
    # wall no worse than the naive arm's.
    try:
        if "sched_fanout" not in BENCH_PHASES:
            raise _PhaseSkipped
        from covalent_tpu_plugin.fleet import FleetExecutor

        SCHED_ELECTRONS = 16

        def pool_connect_misses() -> float:
            """Fresh transport dials (pool misses) recorded so far."""
            return sum(
                value for key, value in metrics_totals().items()
                if key.startswith("covalent_tpu_pool_acquires_total{")
                and "result=miss" in key
            )

        def sched_task_env() -> dict:
            return {
                "PYTHONPATH": repo_root + os.pathsep
                + os.environ.get("PYTHONPATH", ""),
            }

        def sched_pool(tag: str, capacity: int) -> dict:
            return {
                "name": tag,
                "transport": "local",
                "capacity": capacity,
                "executor": {
                    "cache_dir": f"{workdir}/cache_sched_{tag}",
                    "remote_cache": f"{workdir}/remote_sched_{tag}",
                    "python_path": sys.executable,
                    "poll_freq": 0.2,
                    "use_agent": False,
                    "prewarm": True,
                    "task_env": sched_task_env(),
                },
            }

        async def naive_arm() -> dict:
            connects0 = pool_connect_misses()
            t0 = time.perf_counter()
            results = []
            for i in range(SCHED_ELECTRONS):
                ex = TPUExecutor(
                    transport="local",
                    cache_dir=f"{workdir}/cache_sched_naive",
                    remote_cache=f"{workdir}/remote_sched_naive_{i}",
                    python_path=sys.executable,
                    poll_freq=0.2,
                    use_agent=False,
                    prewarm=False,
                    task_env=sched_task_env(),
                )
                try:
                    results.append(await ex.run(
                        trivial_electron, [i], {},
                        {"dispatch_id": "schednaive", "node_id": i},
                    ))
                finally:
                    await ex.close()
            return {
                "wall_s": time.perf_counter() - t0,
                "connects": pool_connect_misses() - connects0,
                "results": results,
            }

        async def fleet_arm() -> dict:
            fleet = FleetExecutor(
                pools=[sched_pool("sa", 4), sched_pool("sb", 4)],
                ensure_fallback=False,
            )
            try:
                connects0 = pool_connect_misses()
                t0 = time.perf_counter()
                # Warm both gangs THEN pack the whole backlog onto them:
                # the dial + pre-flight cost is inside the measured wall.
                await fleet.prewarm()
                results = await asyncio.gather(*(
                    fleet.run(
                        trivial_electron, [i], {},
                        {"dispatch_id": "schedfleet", "node_id": i,
                         "tenant": "heavy" if i % 2 else "light"},
                    )
                    for i in range(SCHED_ELECTRONS)
                ))
                wall = time.perf_counter() - t0
                connects = pool_connect_misses() - connects0
                status = fleet.scheduler.status()
                placements = {
                    name: view["placed_total"]
                    for name, view in status["pools"].items()
                }
                decisions = dict(fleet.scheduler.decisions)
            finally:
                await fleet.close()
            return {
                "wall_s": wall,
                "connects": connects,
                "results": list(results),
                "placements": placements,
                "decisions": decisions,
            }

        async def sched_phase():
            return await naive_arm(), await fleet_arm()

        naive, fleet_run = await asyncio.wait_for(
            sched_phase(), FANOUT_BUDGET_S * 2
        )
        assert fleet_run["results"] == naive["results"], (
            fleet_run["results"], naive["results"])
        summary["sched_fanout_wall_s"] = round(fleet_run["wall_s"], 3)
        summary["sched_fanout_naive_wall_s"] = round(naive["wall_s"], 3)
        summary["sched_fanout_connects"] = round(fleet_run["connects"], 1)
        summary["sched_fanout_naive_connects"] = round(naive["connects"], 1)
        summary["sched_fanout_placements"] = fleet_run["placements"]
        summary["sched_fanout_decisions"] = fleet_run["decisions"]
        # Warm-gang bin-packing: 16 electrons over 2 pooled gangs dial a
        # handful of channels, never one per electron.
        summary["sched_fanout_fewer_connects"] = bool(
            fleet_run["connects"] < SCHED_ELECTRONS
        )
        summary["sched_fanout_no_slower"] = bool(
            fleet_run["wall_s"] <= naive["wall_s"]
        )
        emit({
            "phase": "sched_fanout",
            "electrons": SCHED_ELECTRONS,
            "wall_s": summary["sched_fanout_wall_s"],
            "naive_wall_s": summary["sched_fanout_naive_wall_s"],
            "connects": summary["sched_fanout_connects"],
            "naive_connects": summary["sched_fanout_naive_connects"],
            "placements": fleet_run["placements"],
            "decisions": fleet_run["decisions"],
            "fewer_connects": summary["sched_fanout_fewer_connects"],
            "no_slower": summary["sched_fanout_no_slower"],
        })
    except _PhaseSkipped:
        emit({"phase": "sched_fanout", "skipped": "BENCH_PHASES"})
    except Exception as error:  # noqa: BLE001
        emit({"phase": "sched_fanout", "error": repr(error)})

    # ---- phase 2e: closed-loop autoscaling under a traffic ramp ----------
    # The SAME ramping open-loop load (light warm-up, a surge past one
    # replica's throughput ceiling, a cool tail) through two arms: a
    # statically over-provisioned RAMP_REPLICAS_MAX-replica set, and a
    # 1-replica set under the AutoscaleController with a deliberately
    # tight injected latency SLO.  The autoscaled arm must see the
    # injected burn fire, scale up (trend- and burn-driven), CLEAR the
    # burn while traffic still flows, hold p95 within a decode chunk of
    # the static arm, and consume measurably fewer warm gang-seconds
    # (live replicas integrated over the run) — right-sized capacity,
    # not over-provisioned capacity, is what holds the SLO.
    try:
        if "traffic_ramp" not in BENCH_PHASES:
            raise _PhaseSkipped
        from covalent_tpu_plugin.fleet import AutoscaleController
        from covalent_tpu_plugin.obs.history import HISTORY
        from covalent_tpu_plugin.obs.slo import SLOEngine, SLOSpec
        from covalent_tpu_plugin.serving import open_replica_set

        def make_ramp_factory():
            step_s, cap = RAMP_STEP_S, RAMP_TOKENS

            def factory():
                import time as _time

                class Engine:
                    def __init__(self):
                        self.slots = 2
                        self.lanes = {}

                    def admit(self, rid, prompt, params):
                        seed = int(prompt[-1])
                        n = int((params or {}).get("max_new_tokens", cap))
                        self.lanes[rid] = [
                            seed * 100 + j + 1 for j in range(n)
                        ]

                    def step(self):
                        _time.sleep(step_s)
                        events = []
                        for rid in list(self.lanes):
                            chunk = self.lanes[rid][:2]
                            self.lanes[rid] = self.lanes[rid][2:]
                            done = not self.lanes[rid]
                            if done:
                                del self.lanes[rid]
                            events.append({
                                "rid": rid, "tokens": chunk, "done": done,
                            })
                        return events

                    def cancel(self, rid):
                        self.lanes.pop(rid, None)

                return Engine()

            return factory

        def ramp_executor(tag: str):
            return TPUExecutor(
                transport="local",
                cache_dir=f"{workdir}/cache_ramp_{tag}",
                remote_cache=f"{workdir}/remote_ramp_{tag}",
                python_path=sys.executable,
                poll_freq=0.2,
                use_agent="pool",
                pool_preload="cloudpickle",
                prewarm=False,
                heartbeat_interval=0.0,
                task_env={
                    "PYTHONPATH": repo_root + os.pathsep
                    + os.environ.get("PYTHONPATH", ""),
                },
            )

        def ramp_schedule() -> list[float]:
            """Arrival intervals: warm, accelerating surge, cool."""
            intervals = [RAMP_WARM_INTERVAL_S] * RAMP_WARM_REQUESTS
            surge_n = max(1, RAMP_SURGE_REQUESTS)
            for i in range(surge_n):
                frac = i / max(1, surge_n - 1)
                intervals.append(
                    RAMP_SURGE_START_S
                    + (RAMP_SURGE_END_S - RAMP_SURGE_START_S) * frac
                )
            intervals += [RAMP_COOL_INTERVAL_S] * RAMP_COOL_REQUESTS
            return intervals

        async def ramp_arm(autoscaled: bool) -> dict:
            tag = "auto" if autoscaled else "static"
            executors = [
                ramp_executor(f"{tag}{i}")
                for i in range(RAMP_REPLICAS_MAX)
            ]
            controller = None
            listener = None
            rset = None
            meter = None
            stop = asyncio.Event()
            gang_samples: list = []
            burn_events: list = []
            try:
                rset = await open_replica_set(
                    executors,
                    make_ramp_factory(),
                    replicas=(1 if autoscaled else RAMP_REPLICAS_MAX),
                    name=f"ramp_{tag}",
                    stats_interval_s=0.2,
                )

                async def gang_meter():
                    while not stop.is_set():
                        gang_samples.append(
                            (time.perf_counter(), rset.live_replicas)
                        )
                        await asyncio.sleep(0.05)

                meter = asyncio.ensure_future(gang_meter())
                if autoscaled:
                    # A long bench run has downsampled the ring (stride
                    # doubling): a coarse-grained trend holds the set's
                    # own startup transient for seconds and can scale up
                    # during the warm phase.  Reset to fine-grained
                    # samples for the arm under measurement.
                    HISTORY.clear()
                    spec = SLOSpec(
                        name="ramp_injected_latency",
                        metric="covalent_tpu_serve_request_seconds",
                        kind="latency",
                        threshold_s=RAMP_SLO_THRESHOLD_S,
                        objective=RAMP_SLO_OBJECTIVE,
                        windows=[3.0, 8.0],
                    )
                    engine = SLOEngine(HISTORY, specs=[spec])
                    engine.add_alert_hook(
                        lambda _name, state, _info: burn_events.append(
                            (state, time.perf_counter())
                        )
                    )
                    listener = lambda _ts: engine.evaluate()  # noqa: E731
                    HISTORY.add_listener(listener)
                    controller = AutoscaleController(
                        history=HISTORY,
                        slo_engine=engine,
                        interval_s=0.15,
                        up_cooldown_s=0.4,
                        down_cooldown_s=6.0,
                        idle_ttl_s=0.0,
                        lead_s=RAMP_LEAD_S,
                        # 3s: long enough for a real trend, short enough
                        # that the set's own 0->1 startup transient has
                        # aged out before the surge (a 4s window plus a
                        # 0.6 utilization band flaked an early scale-up
                        # during the warm phase, erasing the burn AND the
                        # gang-second savings the phase asserts).
                        trend_window_s=3.0,
                    )
                    controller.manage_replica_set(
                        rset,
                        min_replicas=1,
                        max_replicas=RAMP_REPLICAS_MAX,
                        target_utilization=0.8,
                        # ~0.45s of sustained demand before a trend-
                        # driven scale-up: a single warm-phase overlap
                        # (one request's service time) is not the surge.
                        # The injected burn bypasses this entirely.
                        up_stabilization_ticks=3,
                    )
                    controller.start()
                t0 = time.perf_counter()
                tasks = []
                for seed, interval in enumerate(ramp_schedule()):
                    tasks.append(asyncio.ensure_future(rset.request(
                        [seed], params={"max_new_tokens": RAMP_TOKENS},
                    )))
                    await asyncio.sleep(interval)
                requests = await asyncio.gather(*tasks)
                results = await asyncio.gather(
                    *(r.result(timeout=RAMP_BUDGET_S) for r in requests)
                )
                wall = time.perf_counter() - t0
                latencies = [r.latency_s for r in requests]
                trace_ids = [r.span.trace_id for r in requests]
                scale_decisions = (
                    dict(controller.decision_counts)
                    if controller is not None else {}
                )
                controller_status = (
                    controller.status() if controller is not None else {}
                )
            finally:
                # Cleanup lives HERE, not in the try body: a failed arm
                # (stream timeout mid-gather) must not leak the 20 Hz
                # gang meter or an open replica set into the phases that
                # run after the phase-level except swallows the error.
                stop.set()
                if meter is not None:
                    try:
                        await meter
                    except Exception:  # noqa: BLE001
                        meter.cancel()
                if controller is not None:
                    await controller.close()
                if listener is not None:
                    HISTORY.remove_listener(listener)
                if rset is not None:
                    try:
                        await rset.close()
                    except Exception:  # noqa: BLE001 - teardown best-effort
                        pass
                for ex in executors:
                    await ex.close()
            gang_seconds = sum(
                max(0.0, t_b - t_a) * live_a
                for (t_a, live_a), (t_b, _live_b) in zip(
                    gang_samples, gang_samples[1:]
                )
            )
            return {
                "wall_s": wall,
                "results": list(results),
                "latencies": latencies,
                "trace_ids": trace_ids,
                "gang_seconds": gang_seconds,
                "max_live": max(
                    (live for _t, live in gang_samples), default=0
                ),
                "burn_events": [
                    (state, round(ts - t0, 3))
                    for state, ts in burn_events
                ],
                "decisions": scale_decisions,
                "controller": controller_status,
            }

        async def ramp_phase():
            static = await ramp_arm(False)
            # A short gap so the static arm's (all-good) latency samples
            # age out of the injected SLO's short window before the
            # autoscaled arm starts.
            await asyncio.sleep(2.0)
            auto = await ramp_arm(True)
            return static, auto

        static_arm, auto_arm = await asyncio.wait_for(
            ramp_phase(), RAMP_BUDGET_S * 2
        )
        n_requests = (
            RAMP_WARM_REQUESTS + RAMP_SURGE_REQUESTS + RAMP_COOL_REQUESTS
        )
        expected = [
            [i * 100 + j + 1 for j in range(RAMP_TOKENS)]
            for i in range(n_requests)
        ]
        assert static_arm["results"] == expected, "static streams diverged"
        assert auto_arm["results"] == expected, "autoscaled streams diverged"
        p95_static = percentile(static_arm["latencies"], 0.95)
        p95_auto = percentile(auto_arm["latencies"], 0.95)
        burn_states = [state for state, _ts in auto_arm["burn_events"]]
        burn_fired = "burning" in burn_states
        burn_cleared = bool(
            burn_fired and burn_states[-1] == "ok"
        )
        scaled_up = bool(
            auto_arm["decisions"].get("set_up", 0) >= 1
            and auto_arm["max_live"] > 1
        )
        gang_ratio = auto_arm["gang_seconds"] / max(
            static_arm["gang_seconds"], 1e-9
        )
        summary["ramp_requests"] = n_requests
        summary["ramp_p95_static_s"] = round(p95_static, 4)
        summary["ramp_p95_auto_s"] = round(p95_auto, 4)
        summary["ramp_p95_ok"] = bool(
            p95_auto <= p95_static + RAMP_P95_MARGIN_S
        )
        summary["ramp_gang_seconds_static"] = round(
            static_arm["gang_seconds"], 2
        )
        summary["ramp_gang_seconds_auto"] = round(
            auto_arm["gang_seconds"], 2
        )
        summary["ramp_gang_ratio"] = round(gang_ratio, 3)
        summary["ramp_fewer_gang_seconds_ok"] = bool(
            gang_ratio <= RAMP_GANG_RATIO_MAX
        )
        summary["ramp_burn_fired_ok"] = burn_fired
        summary["ramp_burn_cleared_ok"] = burn_cleared
        summary["ramp_scaled_up_ok"] = scaled_up
        emit({
            "phase": "traffic_ramp",
            "requests": n_requests,
            "tokens_per_request": RAMP_TOKENS,
            "step_s": RAMP_STEP_S,
            "replicas_static": RAMP_REPLICAS_MAX,
            "replicas_auto_max": auto_arm["max_live"],
            "wall_static_s": round(static_arm["wall_s"], 3),
            "wall_auto_s": round(auto_arm["wall_s"], 3),
            "p95_static_s": summary["ramp_p95_static_s"],
            "p95_auto_s": summary["ramp_p95_auto_s"],
            "p95_margin_s": RAMP_P95_MARGIN_S,
            "p95_ok": summary["ramp_p95_ok"],
            "gang_seconds_static": summary["ramp_gang_seconds_static"],
            "gang_seconds_auto": summary["ramp_gang_seconds_auto"],
            "gang_ratio": summary["ramp_gang_ratio"],
            "gang_ratio_max": RAMP_GANG_RATIO_MAX,
            "fewer_gang_seconds": summary["ramp_fewer_gang_seconds_ok"],
            "burn_events": auto_arm["burn_events"],
            "burn_fired": burn_fired,
            "burn_cleared": burn_cleared,
            "scaled_up": scaled_up,
            "autoscale_decisions": auto_arm["decisions"],
            "latency_attribution": latency_attribution(
                auto_arm["trace_ids"]
            ),
            "introspection": introspection_view([
                "covalent_tpu_serve_request_seconds",
                "covalent_tpu_serve_replicas",
                "covalent_tpu_slo_burn_rate",
                "covalent_tpu_autoscale_decisions_total",
            ]),
            **spread_stats(auto_arm["latencies"], "ramp_auto_latency"),
        })
    except _PhaseSkipped:
        emit({"phase": "traffic_ramp", "skipped": "BENCH_PHASES"})
    except Exception as error:  # noqa: BLE001
        emit({"phase": "traffic_ramp", "error": repr(error)})

    # ---- phase 3: all accelerator work, ONE electron, ONE backend init ---
    # The whole phase lives under ONE wall-clock deadline.  One preflight
    # gates the electron: the big budget is only committed once a
    # throwaway subprocess has reached the device this run asked for.
    collected: dict = {}
    progress_path = f"{workdir}/tpu_progress.jsonl"
    os.makedirs(workdir, exist_ok=True)
    stop = asyncio.Event()
    tailer = asyncio.create_task(tail_progress(progress_path, collected, stop))
    phase3_deadline = time.monotonic() + TPU_BUDGET_S + TPU_BUDGET_S / 3

    def phase3_left() -> float:
        return phase3_deadline - time.monotonic()

    try:
        healthy = False
        if "tpu" not in BENCH_PHASES:
            emit({"phase": "tpu", "skipped": "BENCH_PHASES"})
        else:
            healthy, took, reason = await asyncio.get_event_loop(
            ).run_in_executor(None, tpu_preflight, 45.0)
            emit({"phase": "tpu.preflight", "ok": healthy,
                  "probe_s": round(took, 1),
                  **({"error": reason} if reason else {})})
            if not healthy:
                # An artifact must say WHY its accelerator fields are
                # null, not just that they are.
                summary["tpu_preflight_failure_reason"] = reason
                emit({"phase": "tpu", "error": "preflight failed; electron "
                      "skipped", "preflight_error": reason})
                # CI log annotation (GitHub Actions picks these up from
                # any step output and surfaces them on the run summary
                # page).  stderr, NOT stdout: the stdout protocol is JSON
                # lines and the driver tails it.
                print(f"::warning title=TPU preflight failed::{reason}",
                      file=sys.stderr, flush=True)
        attempt = 0
        while healthy:
            # First electron gets the full remaining deadline; a retry only
            # makes sense when the attempt produced NOTHING (if init
            # succeeded, the budget is simply spent) and enough wall
            # remains for a meaningful rerun.
            budget = max(phase3_left() - 10, 30.0)
            try:
                await asyncio.wait_for(
                    executor.run(
                        accelerator_electron,
                        [progress_path, budget - 15.0],
                        {},
                        {"dispatch_id": f"accel{attempt}", "node_id": 0},
                    ),
                    budget,
                )
                break
            except Exception as error:  # noqa: BLE001
                emit({"phase": "tpu", "attempt": attempt, "error": repr(error)})
                try:
                    await asyncio.wait_for(executor.cancel(), 10)
                except Exception:  # noqa: BLE001
                    pass
                await asyncio.sleep(1)  # let the tailer drain partial lines
                if "init" in collected or phase3_left() < 60:
                    break  # backend came up (or no wall left): rerun can't help
                attempt += 1
    finally:
        stop.set()
        try:
            await asyncio.wait_for(tailer, 5)
        except Exception:  # noqa: BLE001
            tailer.cancel()

    try:
        await asyncio.wait_for(executor.close(), 15)
    except Exception:  # noqa: BLE001
        pass

    # Archive the whole trace store when asked (CI sets
    # COVALENT_TPU_TRACE_DUMP so the sampled waterfalls ride the build
    # artifact next to the metrics snapshots).
    dump_path = os.environ.get("COVALENT_TPU_TRACE_DUMP")
    if dump_path:
        try:
            from covalent_tpu_plugin.obs.tracestore import ensure_trace_store

            with open(dump_path, "w") as f:
                json.dump(ensure_trace_store().dump(), f, sort_keys=True)
        except Exception as error:  # noqa: BLE001 - artifact, not a gate
            emit({"phase": "trace_dump", "error": repr(error)})

    # ---- final combined line (must be LAST) ------------------------------
    def sub(phase, key):
        data = collected.get(phase) or {}
        return data.get(key)

    def pick(live, fallback):
        # Explicit None check, NOT ``or``: a legitimate 0.0 (or False)
        # from the TPU subphase must win over the CPU-phase fallback.
        return live if live is not None else fallback

    final = {
        "metric": "dispatch_overhead_s",
        "value": summary.get("dispatch_overhead_s"),
        "unit": "s",
        "vs_baseline": (
            round(2.0 / max(overhead, 1e-9), 2) if overhead else None
        ),
        **{k: v for k, v in summary.items() if k != "dispatch_overhead_s"},
        # fanout8_busy_speedup rides in via summary: 8 electrons x 300 ms
        # of real work — the honest concurrency figure.
        "backend": sub("init", "backend"),
        "device_kind": sub("init", "device_kind"),
        "backend_init_s": sub("init", "init_s"),
        "matmul4k_tflops": sub("matmul", "tflops"),
        "matmul4k_mfu": sub("matmul", "mfu"),
        "matmul4k_unit_ms_stdev": sub("matmul", "unit_ms_stdev"),
        "mnist_steps_per_s": sub("mnist", "steps_per_s"),
        "mnist_n_batches": sub("mnist", "n_batches"),
        "mnist_loss_first": sub("mnist", "loss_first"),
        "mnist_loss_last": sub("mnist", "loss_last"),
        "flash_fwd_4k_speedup": sub("flash_fwd", "speedup"),
        "flash_fwd_4k_ms": sub("flash_fwd", "flash_ms"),
        "flash_bwd_4k_speedup": sub("flash_bwd", "speedup"),
        "flash_16k_fwd_bwd_ms": sub("flash_long", "fwd_bwd_ms"),
        "flash_16k_attn_tflops": sub("flash_long", "attn_tflops"),
        "flash_16k_window1k_ms": sub("flash_window", "fwd_bwd_ms"),
        "flash_16k_window1k_speedup": sub("flash_window", "speedup_vs_full"),
        "flash_16k_window512_speedup": sub(
            "flash_window_512", "speedup_vs_full"
        ),
        "banded_max_err": sub("flash_window", "banded_max_err"),
        "lm125m_step_ms": sub("lm_step", "step_ms"),
        "lm125m_tokens_per_s": sub("lm_step", "tokens_per_s"),
        "lm125m_mfu": sub("lm_step", "mfu"),
        "lm125m_decode_tokens_per_s": sub("lm_decode", "e2e_tokens_per_s"),
        "lm125m_decode_ms_per_token": sub("lm_decode", "e2e_ms_per_new_token"),
        "lm125m_decode_int8_tokens_per_s": sub("lm_decode_int8", "tokens_per_s"),
        "lm125m_decode_int8_speedup_ab": sub(
            "lm_decode_int8", "speedup_vs_bf16_same_phase"
        ),
        "lm125m_decode_kvq_tokens_per_s": sub("lm_decode_kvq", "tokens_per_s"),
        "lm125m_decode_kvq_speedup_ab": sub(
            "lm_decode_kvq", "speedup_vs_bf16_same_phase"
        ),
        "lm125m_decode_fullq_tokens_per_s": sub(
            "lm_decode_fullq", "tokens_per_s"
        ),
        "lm125m_decode_fullq_speedup_ab": sub(
            "lm_decode_fullq", "speedup_vs_bf16_same_phase"
        ),
        # Speculative decoding: the TPU lm_spec subphase's numbers when
        # it ran, else the serve_spec engine phase's (real
        # ContinuousEngine arms on the local backend).
        "spec_accept_rate": pick(
            sub("lm_spec", "accept_rate"),
            summary.get("serve_spec_accept_rate"),
        ),
        "spec_tokens_per_s": pick(
            sub("lm_spec", "spec_tokens_per_s"),
            summary.get("serve_spec_tokens_per_s"),
        ),
        "spec_plain_tokens_per_s": pick(
            sub("lm_spec", "plain_tokens_per_s"),
            summary.get("serve_spec_tokens_per_s_fp"),
        ),
        "spec_speedup": pick(
            sub("lm_spec", "speedup"), summary.get("serve_spec_speedup")
        ),
        "spec_exact": pick(
            sub("lm_spec", "exact"), summary.get("serve_spec_exact")
        ),
        "spec_quant_speedup": pick(
            sub("lm_spec_quant", "speedup"),
            summary.get("serve_spec_quant_speedup"),
        ),
        "spec_quant_tokens_per_s": pick(
            sub("lm_spec_quant", "spec_tokens_per_s"),
            summary.get("serve_spec_quant_tokens_per_s"),
        ),
        "spec_quant_exact": pick(
            sub("lm_spec_quant", "exact"),
            summary.get("serve_spec_quant_deterministic"),
        ),
    }
    # The serving phase is a beyond-parity bonus that self-skips on tight
    # budgets; merge its fields only when it actually measured, so a
    # skipped run does not re-introduce null TPU fields.
    # Measured-only merges (no new nullable keys on skip paths).
    if sub("lm_step_fused", "step_ms") is not None:
        final.update({
            "lm125m_fused_step_ms": sub("lm_step_fused", "step_ms"),
            "lm125m_fused_mfu": sub("lm_step_fused", "mfu"),
            "lm125m_fused_speedup": sub(
                "lm_step_fused", "speedup_vs_std_step"
            ),
        })
    if sub("lm_serve", "tokens_per_s") is not None:
        final.update({
            "serve_tokens_per_s": sub("lm_serve", "tokens_per_s"),
            "serve_step_reduction_vs_static": sub(
                "lm_serve", "step_reduction_vs_static"
            ),
            "serve_wall_speedup_vs_static_waves": sub(
                "lm_serve", "wall_speedup_vs_static_waves"
            ),
            "serve_complete": sub("lm_serve", "complete"),
        })
    final["stage_histograms"] = stage_histogram_summary()
    final["metrics_totals"] = metrics_totals()
    emit(final)
    return tpu_phase_exit_code(BENCH_PHASES, sub("init", "backend"))


def tpu_phase_exit_code(phases, backend) -> int:
    """Non-zero when the ``tpu`` phase was selected and did not run on a
    TPU — unless JAX_PLATFORMS=cpu asked for the CPU validation tier on
    purpose.  A run that failed on the device must not read as a pass."""
    if "tpu" not in phases or backend == "tpu" or explicit_cpu():
        return 0
    print(
        "bench: the tpu phase was selected but "
        + (f"ran on {backend!r}" if backend else "did not run")
        + "; accelerator fields are not TPU measurements",
        file=sys.stderr, flush=True,
    )
    return 1


def metrics_totals() -> dict:
    """Flat counter/gauge snapshot (the registry's scalar series)."""
    from covalent_tpu_plugin.obs.metrics import REGISTRY

    out: dict = {}
    for name, metric in REGISTRY.snapshot()["metrics"].items():
        if metric["kind"] == "histogram":
            continue
        for series in metric["series"]:
            labels = ",".join(f"{k}={v}" for k, v in series["labels"].items())
            key = f"{name}{{{labels}}}" if labels else name
            out[key] = series["value"]
    return out


def stage_histogram_summary() -> dict:
    """Per-stage dispatch latency distributions from the obs registry.

    Every probe/fanout electron above ran through the instrumented
    TPUExecutor lifecycle, so the span histograms hold the full per-stage
    distribution — count/sum/p50/p95 per ``executor.<stage>`` plus the
    overhead histogram — where the pre-obs bench reported one overhead
    scalar.
    """
    from covalent_tpu_plugin.obs.metrics import REGISTRY
    from covalent_tpu_plugin.obs.trace import SPAN_HISTOGRAM

    out: dict = {}
    snap = REGISTRY.snapshot()["metrics"]
    spans = snap.get(SPAN_HISTOGRAM, {}).get("series", [])
    for series in spans:
        name = series["labels"].get("span", "")
        if not name.startswith(("executor.", "pool.", "agent.")):
            continue
        out[name] = {
            "count": series["count"],
            "sum_s": round(series["sum"], 4),
            "p50_s": series["p50"],
            "p95_s": series["p95"],
        }
    overhead = snap.get("covalent_tpu_dispatch_overhead_seconds", {})
    for series in overhead.get("series", []):
        out["dispatch_overhead"] = {
            "count": series["count"],
            "sum_s": round(series["sum"], 4),
            "p50_s": series["p50"],
            "p95_s": series["p95"],
        }
    return out


if __name__ == "__main__":
    if len(sys.argv) >= 4 and sys.argv[1] == "--dispatcher-drill":
        # Child modes of the dispatcher_crash phase, not a bench run.
        mode, dwork = sys.argv[2], sys.argv[3]
        if mode == "serve":
            asyncio.run(_drill_serve(dwork))
        else:
            asyncio.run(_drill_recover(dwork))
        sys.stdout.flush()
        os._exit(0)
    exit_code = asyncio.run(main())
    # Non-daemon helper threads from transport/agent internals must not keep
    # a finished bench alive into the driver's timeout.
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(exit_code)
