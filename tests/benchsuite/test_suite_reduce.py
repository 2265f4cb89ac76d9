"""The trace reducer on a small trace recorded on the chip
(``record_trace.py``: two tiny jitted programs, twice, a host pause
between) and on events written by hand; the join of each operation's own
time to the ``op_name`` its instruction carries in the trace's own metadata,
and the readers that read it."""

from __future__ import annotations

import json
import os

import pytest

from benchmarks.suite import reduce
from benchmarks.suite.readers import scope_ms, slow_steps

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SCAN, SUM = 1742862024618725073, 11932480251785149672  # the program ids

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "small_trace.xplane.pb")


def test_recorded_trace_gives_the_numbers_written_beside_it():
    got = reduce.reduce_file(TRACE)
    assert got["devices"] == 1
    assert got["window_s"] == pytest.approx(0.312486361, rel=1e-9)
    assert got["busy_s"] == pytest.approx(5.805e-06, rel=1e-9)
    assert got["modules"] == pytest.approx(
        {"jit_small_scan": 4.006e-06, "jit_small_sum": 1.829e-06})
    assert got["module_events"] == {"jit_small_scan": 2.0,
                                    "jit_small_sum": 2.0}
    assert got["ops"] == pytest.approx({
        "jit_small_scan/copy-done": 2.041e-06,
        "jit_small_sum/multiply_reduce_fusion": 1.821e-06,
        "jit_small_scan/convolution_tanh_fusion": 1.737e-06,
        "jit_small_scan/copy": 1.37e-07,
        "jit_small_scan/while": 4.6e-08,
        "jit_small_scan/copy-start": 2.3e-08,
    })
    # Own times add up to the busy time: nothing nested is counted twice.
    assert sum(got["ops"].values()) == pytest.approx(got["busy_s"])
    assert got["op_events"]["jit_small_scan/convolution_tanh_fusion"] == 6
    # The host's 10 ms pause lies between the scan and the sum.
    assert got["idle_gaps"]["jit_small_scan>jit_small_sum"] > 0.02
    assert sum(got["idle_gaps"].values()) + got["busy_s"] == pytest.approx(
        got["window_s"])
    lines = reduce.breakdown(got)
    assert len(lines["device_ops"]) <= 10 and len(lines["idle_gaps"]) <= 10
    assert lines["device_ops"][0] == ["jit_small_scan/copy-done", 2.041e-06]


def test_events_by_hand_nesting_gaps_and_two_devices():
    dev = {"ops": [(0, 100, "%while.1 = () while()"), (10, 30, "%fusion.2"),
                   (40, 60, "%fusion.3"), (200, 250, "%copy.1")],
           "modules": [(0, 100, "jit_f(1)"), (200, 250, "jit_g(2)")]}
    idle = {"ops": [], "modules": [(0, 50, "jit_f(7)")]}
    got = reduce.reduce_events([dev, idle], (0, 400))
    assert got["busy_by_device_s"] == pytest.approx([150e-9, 50e-9])
    assert got["busy_s"] == pytest.approx(100e-9)  # the mean over devices
    assert got["ops"]["jit_f/while"] == pytest.approx(60e-9 / 2)
    assert got["ops"]["jit_f/fusion"] == pytest.approx(40e-9 / 2)
    assert got["idle_gaps"]["jit_f>jit_g"] == pytest.approx(100e-9 / 2)
    assert got["module_events"]["jit_f"] == 1.0


def test_a_mosaic_kernel_keeps_its_mark_and_names_lose_their_numbers():
    text = ('%attention.24 = (bf16[1,2,16384,128]) custom-call(bf16[] %x), '
            'custom_call_target="tpu_custom_call", frontend_attributes={}')
    assert reduce._plain(text) == "attention(tpu_custom_call)"
    assert reduce._plain("jit_run_steps(4711)") == "jit_run_steps"
    assert reduce._plain("%fusion.801 = f32[8] fusion(f32[8] %p)") == "fusion"


def test_a_trace_with_no_device_plane_reads_as_nothing(tmp_path):
    assert reduce.reduce_dir(str(tmp_path)) is None


def test_trim_takes_the_window_from_the_devices_first_event_to_its_last():
    whole = reduce.reduce_file(TRACE)
    trimmed = reduce.reduce_file(TRACE, trim=True)
    assert trimmed["busy_s"] == whole["busy_s"]
    assert trimmed["window_s"] < 0.03 < whole["window_s"]
    assert trimmed["idle_gaps"].get("jit_small_sum>no_module", 0.0) < 1e-6


# -- the join: an operation's own time laid to its instruction's op_name ----


def test_the_recorded_trace_holds_its_map():
    got = reduce.read_scopes(TRACE)
    assert got == {
        (SCAN, "convolution_tanh_fusion.2"):
            "jit(small_scan)/while/body/closed_call/dot_general",
        (SUM, "multiply_reduce_fusion"): "jit(small_sum)/reduce_sum",
    }


def test_a_fusion_is_named_for_its_root():
    """``multiply_reduce_fusion`` multiplies and then reduces: the compiler
    hands the fusion its root's ``op_name`` (``reduce_sum``, not ``mul``).
    In an output fusion, whose root is a convert after the ``tanh``, it is
    the matmul's."""
    got = reduce.read_scopes(TRACE)
    assert got[(SUM, "multiply_reduce_fusion")].endswith("/reduce_sum")
    assert got[(SCAN, "convolution_tanh_fusion.2")].endswith("/dot_general")


def test_own_time_laid_to_scopes_sums_to_the_busy_time():
    got = reduce.reduce_file(TRACE)
    assert got["scopes"] == pytest.approx({
        "": 2.247e-06,  # the copies and the ``while`` itself: no op_name
        "jit(small_sum)/reduce_sum": 1.821e-06,
        "jit(small_scan)/while/body/closed_call/dot_general": 1.737e-06,
    })
    assert sum(got["scopes"].values()) == pytest.approx(got["busy_s"])
    # Events inside the ``while`` carry their own: the six executions of
    # its body's fusion are under ``while/body``, the ``while`` keeps 46 ns.
    assert got["scopes"][""] == pytest.approx(
        got["busy_s"] - got["ops"]["jit_small_sum/multiply_reduce_fusion"]
        - got["ops"]["jit_small_scan/convolution_tanh_fusion"])
    # The line's breakdown keeps its names and gains nothing.
    assert set(reduce.breakdown(got)) == {"device_ops", "idle_gaps"}


def test_an_instruction_with_no_op_name_lands_in_unscoped():
    context = {"trace": reduce.reduce_file(TRACE), "trace_steps": 2}
    with open(os.path.join(REPO, "benchmarks", "suite", "metrics",
                           "unscoped_ms.train.json"), encoding="utf-8") as f:
        unscoped = json.load(f)["args"]
    # No ``op_name``, or none of the step's scopes: all of this trace.
    assert scope_ms.read(context, **unscoped) == pytest.approx(
        context["trace"]["busy_s"] / 2 * 1e3)
    # Under no ``jit(...)`` at all: the instructions that carry no name.
    assert scope_ms.read(context, not_under=["jit(*)"]) == pytest.approx(
        2.247e-06 / 2 * 1e3)
    assert scope_ms.read(context, under=["while/body"]) == pytest.approx(
        1.737e-06 / 2 * 1e3)
    # A scope the traced program does not have, no trace: nothing to read.
    assert scope_ms.read(context, under=["optimizer"]) is None
    assert scope_ms.read({"trace": None, "trace_steps": 2}) is None


def test_events_by_hand_are_joined_by_program_and_instruction():
    """Two programs each with a ``%fusion.2``: the module running at the
    event's start says whose it is."""
    dev = {"ops": [(0, 100, "%while.1 = () while()"),
                   (10, 30, "%fusion.2 = f32[8] fusion(f32[8] %p)"),
                   (200, 250, "%fusion.2 = f32[8] fusion(f32[8] %p)")],
           "modules": [(0, 100, "jit_f(1)"), (200, 250, "jit_g(2)")]}
    scopes = {(1, "fusion.2"): "jit(f)/loss/layer_0/mul",
              (2, "fusion.2"): "jit(g)/optimizer/add"}
    got = reduce.reduce_events([dev], (0, 400), scopes)
    assert got["scopes"] == pytest.approx({
        "": 80e-9, "jit(g)/optimizer/add": 50e-9,
        "jit(f)/loss/layer_0/mul": 20e-9})
    assert sum(got["scopes"].values()) == pytest.approx(got["busy_s"])
    assert reduce.reduce_events([dev], (0, 400))["scopes"] == {}
    assert reduce._program_id("jit_step(4711)") == 4711
    assert reduce._program_id("no_module") is None
    assert reduce._instruction("%copy-done.1 = bf16[2]{0} copy-done(%x)") == (
        "copy-done.1")


def test_patterns_hold_their_globs_in_order_not_side_by_side():
    back = ("jit(step)/loss/transpose(jvp(LM))/loss/jvp(LM)/checkpoint/"
            "layer_2/mlp/wo/dot_general")
    assert scope_ms.matches(back, "transpose(*)/layer_*")
    assert scope_ms.matches(back, "jvp(*)/layer_2/mlp")
    assert not scope_ms.matches(back, "layer_*/transpose(*)")
    assert not scope_ms.matches(back, "optimizer")
    assert not scope_ms.matches(back, "layer")  # a whole segment, by glob
    assert not scope_ms.matches("", "loss")


def test_overlapping_under_and_not_under_count_an_operation_once():
    context = {"trace_steps": 1, "trace": {"scopes": {
        "jit(step)/loss/jvp(LM)/layer_0/mlp/wi/dot_general": 4e-3,
        "jit(step)/loss/jvp()/while/body/dot_general": 2e-3,
        "jit(step)/optimizer/add": 1e-3, "": 0.5e-3}}}
    # Both patterns of ``under`` match the first operation: counted once.
    assert scope_ms.read(
        context, under=["loss", "jvp(*)/layer_*"]) == pytest.approx(6.0)
    # ``not_under`` takes out of what ``under`` let in, and only that.
    assert scope_ms.read(
        context, under=["loss"], not_under=["layer_*", "mlp"]
    ) == pytest.approx(2.0)
    assert scope_ms.read(context) == pytest.approx(7.5)  # everything


def test_the_five_scope_metrics_partition_any_steps_device_time():
    home = os.path.join(REPO, "benchmarks", "suite", "metrics")
    args = {}
    for name in ("layers_fwd_ms", "layers_bwd_ms", "loss_head_ms",
                 "optimizer_ms", "unscoped_ms"):
        with open(os.path.join(home, name + ".train.json"),
                  encoding="utf-8") as f:
            metric = json.load(f)
        assert metric["reader"] == "scope_ms"
        args[name] = metric["args"]
    names = {
        "jit(step)/loss/jvp(LM)/layer_0/attention/flash_fwd/pallas_call":
            "layers_fwd_ms",
        "jit(step)/loss/transpose(jvp(LM))/loss/jvp(LM)/checkpoint/layer_3/"
        "attention/flash_bwd_dq/pallas_call": "layers_bwd_ms",
        "jit(step)/loss/layer_1/transpose(jvp(LM))/mul": "layers_bwd_ms",
        "jit(step)/loss/jvp()/while/body/closed_call/dot_general":
            "loss_head_ms",
        "jit(step)/loss/transpose(jvp(LM))/loss/jvp(LM)/scatter-add":
            "loss_head_ms",
        "jit(step)/optimizer/add": "optimizer_ms",
        "jit(step)/optimizer/layer_0/add": "layers_fwd_ms",
        "jit(step)/convert_element_type": "unscoped_ms",
        "": "unscoped_ms",
    }
    for op_name, want in names.items():
        got = [m for m, a in args.items() if scope_ms.counts(op_name, **a)]
        assert got == [want], (op_name, got)


# -- slow steps --------------------------------------------------------------


def test_slow_steps_counts_the_steps_over_105_percent_of_the_median():
    steady = [0.502] * 98
    assert slow_steps.read({"step_times": steady}) == 0.0
    # PR 26's lost pair: a few steps of 0.54-1.17 s among 502 ms ones.
    stalled = steady + [0.54, 1.17, 0.5271, 0.5272]
    assert slow_steps.OVER == 1.05
    assert slow_steps.read({"step_times": stalled}) == 3.0  # 527.1 = limit
    # A slower program moves the median, and counts nothing.
    assert slow_steps.read({"step_times": [0.536] * 95}) == 0.0
    assert slow_steps.read({"step_times": []}) is None
    assert slow_steps.read({}) is None
