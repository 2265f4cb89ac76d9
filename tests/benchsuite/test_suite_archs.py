"""The ``model_type`` seam as the next ``model_config`` PR will use it: a
stand-in architecture (``gated_mlp.py``: nothing of StarCoder2's) registered
under its own ``model_type`` and run as a cell from a temporary root to
which only files and entries were added; the errors that name what is
missing; and a walk of ``benchmarks/suite/`` that keeps every architecture
word inside ``archs/``.
"""

from __future__ import annotations

import json
import os
import re
import sys
import time

import pytest

from benchmarks.suite import archs, run, weights, work
from benchmarks.suite.readers import train_mfu
from tests.benchsuite import gated_mlp, standin
from tests.benchsuite.test_suite_run import _half_batch

REPO = standin.REPO
SUITE = os.path.join(REPO, "benchmarks", "suite")
CELL = "gated.train"
#: What is one architecture's and may stand nowhere but under ``archs/``.
WORDS = re.compile(
    r"q_proj|TransformerLM|TransformerConfig|lm_loss|LAYER_LEAVES|"
    r"sliding_window|num_key_value_heads|model_type\s*==")


@pytest.fixture()
def registered(monkeypatch):
    """``gated_mlp`` where ``archs.load`` looks for it, as the file
    ``benchmarks/suite/archs/gated_mlp.py`` would be."""
    monkeypatch.setitem(
        sys.modules, "benchmarks.suite.archs.gated_mlp", gated_mlp)
    return gated_mlp


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The stand-in root, and one more configuration, traffic mix, limits
    file and cell added to it; the train metrics gain the cell's name."""
    tmp = standin.make_root(str(tmp_path_factory.mktemp("archs")))
    with open(os.path.join(tmp, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    home = bench["paths"][0]
    for sub, name, obj in [("configs", "gated", gated_mlp.CONFIG),
                           ("traffic", "gated-train", gated_mlp.JOB),
                           ("limits", CELL, gated_mlp.LIMITS)]:
        standin._write(os.path.join(tmp, home, sub, name + ".json"), obj)
    bench["configs"].append({
        "name": "gated", "source": gated_mlp.CONFIG["source"],
        "file": f"{home}/configs/gated.json", "reduced": [],
        "why": "stand-in"})
    bench["workloads"].append({
        "name": CELL, "config": "gated", "traffic": "gated-train",
        "chips": 1, "why": "stand-in"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "tiny.train" in metric.get("workloads", []):
            metric["workloads"].append(CELL)
    standin._write(os.path.join(tmp, "BENCHMARK.json"), bench)
    return tmp


def _run(root, trace=0, **options):
    """In this process (an identity hook): the worker of a real dispatch
    would look for the module's file under ``archs/``."""
    options.setdefault("kind_options", {"hooks": {"step": lambda f: f}})
    return run.run_cell(root, CELL, 2**31 + 41, 1, trace, require_tpu=False,
                        t_start=time.time(), **options)


def test_an_unknown_model_type_names_the_file_to_add():
    with pytest.raises(LookupError, match=r"benchmarks/suite/archs/mamba\.py"):
        archs.load({"model_type": "mamba"})
    for bad in ({}, {"model_type": "../run"}, {"model_type": 7}):
        with pytest.raises(LookupError, match="model_type"):
            archs.load(bad)
    assert archs.load({"model_type": "starcoder2"}).__name__.endswith(
        "archs.starcoder2")


def test_a_function_the_module_lacks_is_an_error_that_names_it(registered):
    """A train-only architecture asked for a serve cell's reading."""
    assert archs.load(gated_mlp.CONFIG) is gated_mlp
    with pytest.raises(AttributeError, match="matmul_parameters"):
        work.serve_flops(gated_mlp.CONFIG, 10)


def test_the_standin_architectures_cell_is_correct(registered, root):
    result = _run(root)
    assert result["correct"] is True, result["compared"]
    assert set(result["metrics"]) == {"train_tok_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["notes"]["slow_steps"] is not None
    json.dumps(result)


def test_its_traced_run_reports_the_train_metrics_it_joined(registered, root):
    result = _run(root, trace=1)
    assert result["correct"] is True, result["compared"]
    names = set(result["metrics"])
    assert {"step_ms_p50.train", "compiles_in_window.train",
            "slow_steps.train"} <= names
    # A CPU trace has no device plane: no share of a peak, no scope's time.
    assert not [n for n in names if "mfu" in n or "roofline" in n
                or n.endswith("_ms.train") and n != "step_ms_p50.train"]


@pytest.mark.parametrize("options", [
    {"fault": "half_batch"},  # planted in the reference put in its place
    {"kind_options": {"hooks": {"loss_fn": _half_batch}}},  # in the program
])
def test_half_of_the_batch_left_out_is_not_correct(registered, root, options):
    result = _run(root, **options)
    assert result["correct"] is False
    pair = result["compared"]["grad_gap"]
    assert pair["value"] > pair["limit"]


def test_step_mfu_reads_the_architectures_own_count(registered, root):
    from benchmarks.suite import spec

    def mfu(cell_name, rate):
        cell = spec.load_cell(root, cell_name)
        return train_mfu.read({
            "cell": cell, "require_tpu": True, "chips": 1,
            "device": {"kind": "TPU v5 lite"},
            "end_to_end": {"train_tok_s": rate}}), cell

    got, cell = mfu(CELL, 1e6)
    # Three D x F matmuls a block, the head; forward and twice that back.
    by_hand = 3 * 2 * (2 * 3 * 32 * 64 + 32 * 128)
    assert gated_mlp.train_flops_per_token(cell["config"], {}) == by_hand
    assert got == pytest.approx(100.0 * by_hand * 1e6 / 197e12)
    other, _ = mfu("tiny.train", 1e6)
    assert other != pytest.approx(got)  # StarCoder2's block counts its own


def test_a_constant_leaf_and_a_modules_own_rule(monkeypatch):
    import jax.numpy as jnp

    key = weights.seed_key(2**31 + 5)
    const = weights.leaf(key, "block_0.mix", (4,), {"const": 0.5}, jnp.float32)
    assert (const == 0.5).all()
    # Every leaf of the stand-in, its constant gate among them, is counted,
    # and its ``init`` is one a jit takes as a static argument.
    monkeypatch.setitem(
        sys.modules, "benchmarks.suite.archs.gated_mlp", gated_mlp)
    specs = {name: init for name, _, init in
             weights.leaf_specs(gated_mlp.CONFIG)}
    hash(specs["block_0.mix"])
    assert (weights.leaf(key, "x", (4,), specs["block_0.mix"], jnp.float32)
            == const).all()
    assert specs["tok"] == 0.05
    assert weights.parameter_count(gated_mlp.CONFIG) == (
        128 * 32 + 2 * (3 * 32 * 64 + 32) + 32 * 128)

    class Own:
        @staticmethod
        def leaf_value(key, name, shape, init, dtype):
            return jnp.full(shape, 7, dtype)

    assert (weights.leaf(key, "x", (2,), 0.02, jnp.float32, Own) == 7).all()


def test_no_architecture_word_stands_outside_archs():
    found = []
    for folder, _, files in os.walk(SUITE):
        if os.path.basename(folder) in ("archs", "__pycache__"):
            continue
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(folder, name)
            with open(path, encoding="utf-8") as f:
                for number, line in enumerate(f, 1):
                    if WORDS.search(line):
                        found.append(f"{os.path.relpath(path, REPO)}:{number}")
    assert not found, found
    # ... and the words are where they belong.
    with open(os.path.join(SUITE, "archs", "starcoder2.py"),
              encoding="utf-8") as f:
        assert len(set(WORDS.findall(f.read()))) >= 5
