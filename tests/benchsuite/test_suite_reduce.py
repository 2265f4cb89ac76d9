"""The trace reducer on a small trace recorded on the chip
(``record_trace.py``: two tiny jitted programs, twice, a host pause
between) and on events written by hand."""

from __future__ import annotations

import os

import pytest

from benchmarks.suite import reduce

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
