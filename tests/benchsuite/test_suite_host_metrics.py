"""The train step's host layer: four per-layer metrics read from what the
worker's record of its steps (``obs.stepstats``) sends home.  Their entries
and files, their readers on a registry of their own (None where the program
keeps no record, as a program from before the record), and the stand-in
train cell run through real dispatch, whose traced line carries all four.
The record's run-queue wait has no metric: on the chip's machines the
record found no ``schedstat``, so no traced line there would carry it."""

from __future__ import annotations

import json
import os
import time

import pytest

from benchmarks.suite import run, spec
from tests.benchsuite import standin

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HOST = {
    "host_dispatch_ms_p50.train": "ms",
    "host_gc_s.train": "s",
    "host_major_faults.train": "count",
    "heartbeat_late_s.train": "s",
}
CELLS = ["sc2-3b.train-16k", "xing4-29b.train-8k", "laguna-s.train-16k"]


def test_the_four_entries_close_the_manifest_with_their_files():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    entries = bench["per_layer"][-len(HOST):]
    assert [m["name"] for m in entries] == list(HOST)
    for metric in entries:
        assert metric["unit"] == HOST[metric["name"]]
        assert metric["layer"] == "host"
        assert metric["source"] == "program_counter"
        assert metric["moves"] == "train_tok_s"
        assert metric["better"] == "lower"
        assert metric["workloads"] == CELLS
        assert os.path.exists(os.path.join(
            REPO, bench["paths"][0], "metrics", metric["name"] + ".json"))
    for cell in CELLS:
        names = [m["name"] for m in spec.load_cell(REPO, cell)["per_layer"]]
        assert set(HOST) <= set(names), cell


def _read(cell: dict, name: str):
    (metric,) = [m for m in cell["per_layer"] if m["name"] == name]
    reader = __import__(f"benchmarks.suite.readers.{metric['reader']}",
                        fromlist=["read"])
    return reader.read({"cell": cell}, **metric.get("args", {}))


def test_the_readers_on_a_registry_of_their_own(monkeypatch):
    from covalent_tpu_plugin import obs
    from covalent_tpu_plugin.obs import stepstats
    from covalent_tpu_plugin.obs.metrics import Registry

    cell = spec.load_cell(REPO, "laguna-s.train-16k")
    registry = Registry()
    monkeypatch.setattr(obs, "REGISTRY", registry)
    # A program that keeps no record of its steps: nothing to read.
    assert {name: _read(cell, name) for name in HOST} == dict.fromkeys(HOST)
    stepstats.absorb_worker({
        "calls": 120, "steady": 117,
        "seconds": {"interval": 42.4, "dispatch": 0.19, "stats": 0.01,
                    "runqueue": 0.003},
        "gc_seconds": {"0": 0.001, "1": 0.002, "2": 0.03},
        "events": {"gc_pause": 3, "major_fault": 0, "minor_fault": 210,
                   "involuntary_switch": 5},
        "dispatch_s": {"p50": 0.0016, "max": 0.004},
        "heartbeat_late_s": {"max": 0.012},
        "slowest": [], "stalls": [],
    }, registry=registry)
    got = {name: _read(cell, name) for name in HOST}
    assert got["host_dispatch_ms_p50.train"] == pytest.approx(1.6)
    assert got["host_gc_s.train"] == pytest.approx(0.033)
    assert got["host_major_faults.train"] == 0.0
    assert got["heartbeat_late_s.train"] == pytest.approx(0.012)


def test_standin_train_cell_reports_the_host_layer(tmp_path, monkeypatch):
    # Beats often enough to land inside a two-second window; the stall
    # watch far above anything a CPU compile holds the worker for.
    monkeypatch.setenv("COVALENT_TPU_HEARTBEAT_S", "0.25")
    monkeypatch.setenv("COVALENT_TPU_STALL_S", "300")
    root = standin.make_root(str(tmp_path))
    result = run.run_cell(root, "tiny.train", 2**31 + 41, 2, 1,
                          require_tpu=False, t_start=time.time())
    assert result["correct"] is True, result["compared"]
    got = {name: pair for name, pair in result["metrics"].items()
           if name in HOST}
    assert set(got) == set(HOST)
    for name, pair in got.items():
        assert pair["unit"] == HOST[name]
        assert pair["value"] >= 0, name
    assert got["host_dispatch_ms_p50.train"]["value"] > 0
