"""Unit tests for bench.py's dispatcher-side helpers.

The preflight decides whether the accelerator electron's budget is
committed at all, and the exit code decides whether a run that never
reached a TPU can read as a pass — so both are pinned here: a CPU counts
only when ``JAX_PLATFORMS=cpu`` asked for it.
"""

from __future__ import annotations

import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402


def test_spread_stats_fields():
    out = bench.spread_stats([0.001, 0.002, 0.004], "x")
    assert out["x_ms_min"] == 1.0
    assert out["x_ms_max"] == 4.0
    assert out["x_ms_stdev"] == pytest.approx(1.528, abs=1e-3)


def test_spread_stats_single_value_has_no_stdev():
    out = bench.spread_stats([0.003], "y")
    assert out == {"y_ms_min": 3.0, "y_ms_max": 3.0}


def test_tpu_preflight_passes_on_an_explicit_cpu():
    # conftest sets JAX_PLATFORMS=cpu for the whole test process; the
    # probe subprocess inherits it — the CPU validation tier, on purpose.
    assert bench.explicit_cpu()
    ok, took, err = bench.tpu_preflight(60.0)
    assert ok, f"preflight failed under JAX_PLATFORMS=cpu: {err}"
    assert took < 60.0


def test_tpu_preflight_refuses_a_cpu_nobody_asked_for(monkeypatch):
    # The child still settles on the CPU, but this run did not ask for
    # one: no silent pass, and the reason names what it found.
    monkeypatch.setattr(bench, "explicit_cpu", lambda: False)
    ok, _, err = bench.tpu_preflight(60.0)
    assert not ok
    assert "'cpu'" in err and "not a TPU" in err


def test_step_accounting_hand_computed():
    # Shared structural model consumed by bench.py's lm_serve phase and
    # benchmarks/serve_bench.py (one implementation, so the artifacts
    # cannot drift from the admission rule in models/serve.py).
    from covalent_tpu_plugin.models import step_accounting

    # One slot, sync=2: req(4) finishes at step 3, slot frees at the
    # NEXT boundary (4), req(2) adds 1 more step -> 5; unquantized
    # packing would chain them at 3 + 1 = 4; static waves pay 3 + 1.
    assert step_accounting([4, 2], 1, 2) == {
        "static_wave_steps": 4,
        "continuous_steps_ideal": 4,
        "continuous_steps_sync": 5,
    }
    # Two slots: the three short requests chain on slot 1 (1 step each,
    # quantized to 2-step boundaries) while the long one holds slot 0.
    assert step_accounting([8, 2, 2, 2], 2, 2) == {
        "static_wave_steps": 8,
        "continuous_steps_ideal": 7,
        "continuous_steps_sync": 7,
    }
    # sync=1 means no quantization: sync == ideal.
    acc = step_accounting([5, 3, 9, 2, 6], 2, 1)
    assert acc["continuous_steps_sync"] == acc["continuous_steps_ideal"]


def test_tpu_preflight_timeout_reports_false():
    # A zero-ish cap can't even start the interpreter: the probe must
    # report failure with the timeout reason, never hang or raise.
    ok, took, err = bench.tpu_preflight(0.01)
    assert not ok
    assert "timeout" in err
    # The staged probe attributes WHERE the budget died, not just that
    # it did.
    assert "stage" in err


def test_tpu_preflight_fails_fast_off_tpu_host(monkeypatch):
    # JAX_PLATFORMS=tpu on a host with no TPU device nodes can hang
    # inside libtpu backend init for the full budget.  The probe must
    # refuse in milliseconds with the actionable reason.
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.delenv("TPU_NAME", raising=False)
    monkeypatch.delenv("TPU_WORKER_ID", raising=False)
    monkeypatch.delenv("TPU_WORKER_HOSTNAMES", raising=False)
    if bench.tpu_host_signals()["accel_devices"]:
        pytest.skip("running on a real TPU host")
    t0 = time.monotonic()
    ok, took, err = bench.tpu_preflight(45.0)
    assert not ok
    assert time.monotonic() - t0 < 5.0  # no hang, no subprocess
    assert "not a TPU host" in err
    assert "libtpu" in err  # the double-install diagnostic rides along


@pytest.mark.parametrize(
    "phases, backend, asked_for_cpu, code",
    [
        ({"overhead"}, None, False, 0),       # tpu phase not selected
        ({"tpu"}, "tpu", False, 0),           # ran where it was meant to
        ({"tpu"}, "cpu", True, 0),            # the CPU tier, on purpose
        ({"tpu"}, "cpu", False, 1),           # slid onto a CPU
        ({"tpu", "overhead"}, None, False, 1),  # never ran at all
    ],
)
def test_exit_code_says_whether_the_tpu_phase_met_a_tpu(
    monkeypatch, capsys, phases, backend, asked_for_cpu, code
):
    monkeypatch.setattr(bench, "explicit_cpu", lambda: asked_for_cpu)
    assert bench.tpu_phase_exit_code(phases, backend) == code
    assert bool(capsys.readouterr().err) == bool(code)


def test_compile_cache_is_the_placeable_one():
    # One rule for bench.py and chip_smoke.py: the variable verbatim, else
    # one fixed path in the checkout — never a pid, temp name or time.
    import chip_smoke

    assert bench.JAX_CACHE_DIR == chip_smoke.compile_cache_dir()
    assert str(os.getpid()) not in bench.JAX_CACHE_DIR


def test_stage_histogram_summary_reads_span_registry():
    # The bench report embeds per-stage latency distributions from the obs
    # registry (ISSUE 1: real histograms instead of one overhead scalar).
    from covalent_tpu_plugin.obs.trace import Span

    with Span("executor.bench_probe_stage", emit=False):
        pass
    out = bench.stage_histogram_summary()
    entry = out["executor.bench_probe_stage"]
    assert entry["count"] >= 1
    assert {"count", "sum_s", "p50_s", "p95_s"} <= set(entry)
    # Unprefixed spans (models, workflow internals) stay out of the report.
    assert all(k.startswith(("executor.", "pool.", "agent.", "dispatch_"))
               for k in out)


def test_metrics_totals_flat_and_json_safe():
    import json

    from covalent_tpu_plugin.obs.metrics import REGISTRY

    REGISTRY.counter("bench_probe_total", "", ("kind",)).labels(
        kind="x"
    ).inc(2)
    totals = bench.metrics_totals()
    assert totals["bench_probe_total{kind=x}"] == 2
    json.dumps(totals)
