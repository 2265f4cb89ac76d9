"""The per-layer metrics read from inside the program: the per-kernel flash
rooflines and the forward's executions on synthetic traces, the span and
counter readers on a registry of their own, and the stand-in train cell run
through real dispatch, which has to report the dispatch overhead and the
worker's compile counters."""

from __future__ import annotations

import json
import os
import time

import pytest

from benchmarks.suite import archs, run, spec, work
from benchmarks.suite.readers import (
    flash_part_roofline,
    op_calls,
    registry_sum,
    span_overhead,
)
from tests.benchsuite import standin

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
KIND = "TPU v5 lite"
NEW = ["flash_fwd_roofline.train", "flash_dkdv_roofline.train",
       "flash_dq_roofline.train", "flash_fwd_calls.train",
       "dispatch_overhead_s.train", "jit_trace_lower_s.train",
       "jit_backend_s.train", "compile_cache_misses.train"]


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(REPO, "sc2-3b.train-16k")


def _context(cell, ops=None, op_events=None, steps=4, require_tpu=True):
    trace = None if ops is None else {
        "ops": ops, "op_events": op_events or {}, "devices": 1}
    return {"cell": cell, "trace": trace, "trace_steps": steps,
            "require_tpu": require_tpu, "chips": 1,
            "device": {"kind": KIND}}


def _part_work(cell, part):
    return archs.load(cell["config"]).kernel_work(
        cell["config"], cell["traffic"], flash_part_roofline.KERNELS[part])


def _least(cell, part):
    return work.roofline_seconds(_part_work(cell, part), work.peaks(KIND))[0]


def test_the_three_parts_sum_to_the_flash_kernels_needed_work(cell):
    from benchmarks.suite.readers import flash_roofline

    job, arch = cell["traffic"], archs.load(cell["config"])
    parts = [_part_work(cell, part) for part in flash_part_roofline.KERNELS]
    # The whole: the forward once and the backward's four matmuls (twice the
    # forward); Q, K, V, O once forward, Q, K, V, O, dO in and dQ, dK, dV out
    # backward.
    s = arch.sizes(cell["config"])
    n = job["batch"] * s["L"]
    forward = arch.attention_forward_flops(cell["config"], job["sequence"])
    q = job["sequence"] * s["H"] * s["hd"] * 2
    kv = job["sequence"] * s["KV"] * s["hd"] * 2
    assert sum(p["flops"] for p in parts) == 3 * forward * n
    assert sum(p["bytes"] for p in parts) == (6 * q + 6 * kv) * n
    # ... which is what the kernels' joint roofline reads 100% against.
    least = 3 * forward * n / 197e12
    name = "jit_step/flash_fwd(tpu_custom_call)"
    assert flash_roofline.read(
        _context(cell, {name: 4 * least}), r"\(tpu_custom_call\)$"
    ) == pytest.approx(100.0)
    fwd, dkdv, dq = parts
    assert dkdv["flops"] == 1.5 * fwd["flops"]
    assert dq["flops"] == 0.5 * fwd["flops"]


@pytest.mark.parametrize("part", list(flash_part_roofline.KERNELS))
def test_a_kernel_at_its_roofline_reads_100_and_never_more(cell, part):
    name = f"jit_step/{flash_part_roofline.KERNELS[part]}(tpu_custom_call)"
    least = _least(cell, part)
    # Four traced steps, the kernel at its roofline's own time: 100%.
    at = _context(cell, {name: 4 * least, "jit_step/fusion": 1.0})
    assert flash_part_roofline.read(at, part) == pytest.approx(100.0)
    # Remat's second forward, or dQ's recomputed S and P: the same needed
    # work over more device time, so a smaller share.
    slower = _context(cell, {name: 8 * least})
    assert flash_part_roofline.read(slower, part) == pytest.approx(50.0)
    # Nothing to read: a program whose kernels share one name (the parent
    # commit), no trace, a CPU rehearsal.
    lump = _context(cell, {"jit_step/attention(tpu_custom_call)": 1.0})
    assert flash_part_roofline.read(lump, part) is None
    assert flash_part_roofline.read(_context(cell), part) is None
    assert flash_part_roofline.read(
        _context(cell, {name: 1.0}, require_tpu=False), part) is None


def test_forward_executions_per_traced_step(cell):
    key = "jit_step/flash_fwd(tpu_custom_call)"
    context = _context(cell, {key: 1.0}, {
        key: 32, "jit_step/flash_bwd_dq(tpu_custom_call)": 16,
        "jit_step/fusion": 400})
    # 32 executions in four steps: twice a layer of four (4 needed).
    assert op_calls.read(context, "flash_fwd(tpu_custom_call)") == 8.0
    context["trace"]["devices"] = 2
    assert op_calls.read(context, "flash_fwd(tpu_custom_call)") == 4.0
    assert op_calls.read(context, "attention(tpu_custom_call)") is None
    assert op_calls.read(_context(cell), "flash_fwd(tpu_custom_call)") is None


def test_span_and_counter_readers_on_a_registry_of_their_own(monkeypatch):
    from covalent_tpu_plugin import obs
    from covalent_tpu_plugin.obs import jitstats
    from covalent_tpu_plugin.obs.metrics import Registry

    registry = Registry()
    monkeypatch.setattr(obs, "REGISTRY", registry)
    spans = {"span": "executor.run", "minus": "worker.execute"}
    misses = {"metric": jitstats.WORKER_COMPILE_CACHE, "label": "result",
              "values": ["miss"]}
    lowered = {"metric": jitstats.WORKER_JIT_SECONDS, "label": "phase",
               "values": ["jaxpr_trace", "jaxpr_to_mlir_module"]}
    # A program that records neither (the control, the parent commit).
    assert span_overhead.read({}, **spans) is None
    assert registry_sum.read({}, **misses) is None
    hist = registry.histogram(
        span_overhead.HISTOGRAM, "", label_names=("span",))
    hist.labels(span="executor.run").observe(12.5)
    assert span_overhead.read({}, **spans) is None  # no worker span came
    hist.labels(span="worker.execute").observe(11.0)
    assert span_overhead.read({}, **spans) == pytest.approx(1.5)
    jitstats.absorb_worker(
        {"seconds": {"jaxpr_trace": 2.0, "jaxpr_to_mlir_module": 3.0,
                     "backend_compile": 7.0},
         "cache": {"hit": 5}}, registry=registry)
    assert registry_sum.read({}, **lowered) == 5.0
    assert registry_sum.read({}, **misses) == 0.0  # a warm run: hits only


def test_standin_train_cell_through_real_dispatch_reports_the_new_metrics(
        tmp_path):
    root = standin.make_root(str(tmp_path))
    result = run.run_cell(root, "tiny.train", 2**31 + 29, 2, 1,
                          require_tpu=False, t_start=time.time())
    assert result["correct"] is True, result["compared"]
    got = {name: pair["value"] for name, pair in result["metrics"].items()}
    for name in ("dispatch_overhead_s.train", "jit_trace_lower_s.train",
                 "jit_backend_s.train"):
        assert got[name] > 0, name
    assert got["compile_cache_misses.train"] >= 0
    # No device plane in a CPU trace: the device's metrics are left out.
    assert not [n for n in got if "roofline" in n or "calls" in n]
    assert result["metrics"]["dispatch_overhead_s.train"]["unit"] == "s"
    json.dumps(result)


def test_the_new_entries_are_in_the_manifest_with_their_files():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NEW[0])
    assert names[first:first + len(NEW)] == NEW  # in the issue's order
    for metric in bench["per_layer"][first:first + len(NEW)]:
        assert metric["workloads"] == ["sc2-3b.train-16k"]
        assert os.path.exists(os.path.join(
            REPO, bench["paths"][0], "metrics", metric["name"] + ".json"))
    by = {m["name"]: m for m in bench["per_layer"]}
    assert by["dispatch_overhead_s.train"]["source"] == "program_span"
    assert by["dispatch_overhead_s.train"]["moves"] == "setup_s"
