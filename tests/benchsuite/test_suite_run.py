"""The harness end to end at stand-in sizes on the CPU: the result line's
keys, the refusal to call a CPU run a device run, a configuration, a
traffic mix and a per-layer metric added as new files, the control, and the
faults a cell can have planted under the timed path (``correct`` must come
out false for each).
"""

from __future__ import annotations

import json
import time

import pytest

from benchmarks.suite import run
from tests.benchsuite import standin

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return standin.make_root(str(tmp_path_factory.mktemp("standin")))


def _run(root, cell, trace=0, seconds=3, seed=2**31 + 11, **options):
    return run.run_cell(root, cell, seed, seconds, trace, require_tpu=False,
                        t_start=time.time(), **options)


@pytest.mark.parametrize("cell,metrics", [
    ("tiny.closed", {"out_tok_s", "tpot_p90_ms", "setup_s"}),
    ("tiny.open", {"ttft_p90_s", "setup_s"}),
    ("tiny.train", {"train_tok_s", "setup_s"}),
])
def test_standin_cell_prints_the_contracts_line(root, cell, metrics):
    """The stand-in cells are new files and new entries only (standin.py):
    a configuration, a traffic mix and a metric can be added without an
    edit to a file that is there."""
    result = _run(root, cell)
    assert list(result)[: len(CONTRACT_KEYS)] == CONTRACT_KEYS
    assert list(result)[-1] == "compared"
    assert result["correct"] is True, result["compared"]
    assert set(result["metrics"]) == metrics
    assert result["failed"] == 0 and result["attempted"] > 0
    # It names the device it ran on: a CPU, so no number is a chip's.
    assert result["device"]["platform"] == "cpu"
    assert all(v["value"] > 0 for v in result["metrics"].values())
    json.dumps(result)


def test_traced_run_reports_per_layer_metrics_and_the_added_one(root):
    result = _run(root, "tiny.closed", trace=1)
    names = set(result["metrics"])
    assert "ttft_p50_s.tiny" in names  # a metric standin.py added
    assert result["metrics"]["compiles_in_window.tiny"]["value"] == 0
    # No device plane in a CPU trace: shares of a roofline or of a peak are
    # left out, never reported as 0.
    assert not names & {"idle_pct.tiny", "decode_roofline.tiny",
                        "step_mfu.tiny"}
    assert "busy_s" not in result["device"]


def test_main_refuses_to_call_a_cpu_run_a_device_run(root, capsys):
    code = run.main(["--root", root, "--workload", "tiny.open",
                     "--seed", "5", "--seconds", "2", "--trace", "0"])
    captured = capsys.readouterr()
    assert code == 2
    assert captured.out.strip() == ""  # no result line
    assert "NO_CHIP" in captured.err


def test_train_electron_refuses_a_cpu_before_paying_set_up():
    from benchmarks.suite import program

    report = program.train_electron({}, {}, 0, 1.0, None, chips=1)
    assert "NO_CHIP" in report["no_chip"]


# -- the control: one precision down, put in the program's place -----------


def test_serve_control_int8_is_not_correct(root):
    result = _run(root, "tiny.closed", control=1)
    assert result["correct"] is False
    assert result["compared"]["token_gap"]["value"] > \
        result["compared"]["token_gap"]["limit"]


def test_train_control_bfloat16_is_not_correct(root):
    result = _run(root, "tiny.train", control=1)
    assert result["correct"] is False


# -- faults under the timed path --------------------------------------------


def alter_a_token(engine_class):
    """A token altered where it is produced: the first token of every
    finished request comes out one id higher."""

    class Altered(engine_class):
        def step(self):
            events = super().step()
            for event in events:
                if event["tokens"]:
                    event["tokens"][0] = (event["tokens"][0] + 1) % 512
            return events

    return Altered


def test_altered_token_is_not_correct(root):
    result = _run(root, "tiny.closed",
                  kind_options={"hooks": {"engine_class": alter_a_token}})
    assert result["correct"] is False
    assert result["compared"]["token_gap"]["value"] > 1e-3


def _unchanged_state(step):
    """A step that returns its state unchanged (it runs on a copy: the real
    step donates what it is given)."""

    def broken(state, batch):
        import jax

        copy = jax.tree_util.tree_map(lambda x: x.copy(), state)
        _, metrics = step(copy, batch)
        return state, metrics

    return broken


def _half_batch(loss_fn):
    def broken(params, apply_fn, batch):
        tokens = batch["tokens"]
        return loss_fn(params, apply_fn,
                       {"tokens": tokens[: tokens.shape[0] // 2]})

    return broken


@pytest.mark.parametrize("hooks,fails", [
    ({"step": _unchanged_state}, "delta_gap"),
    ({"loss_fn": _half_batch}, "grad_gap"),
])
def test_train_fault_is_not_correct(root, hooks, fails):
    result = _run(root, "tiny.train", kind_options={"hooks": hooks})
    assert result["correct"] is False
    pair = result["compared"][fails]
    assert pair["value"] is None or pair["value"] > pair["limit"]


def test_reference_fault_half_batch_reads_far_above_a_sound_run(root):
    """The fault as the chip readings take it: planted in the reference put
    in the program's place (``--fault half_batch``)."""
    result = _run(root, "tiny.train", fault="half_batch")
    assert result["correct"] is False
