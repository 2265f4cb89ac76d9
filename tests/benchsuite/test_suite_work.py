"""``work.py`` and the StarCoder2 block's needed work (``archs/starcoder2.py``,
reached through ``archs.load``) against operations and bytes counted by hand
for one tiny shape, and the table of peaks."""

from __future__ import annotations

import pytest

from benchmarks.suite import archs, weights, work
from benchmarks.suite.readers.flash_part_roofline import KERNELS

TINY = {
    "model_type": "starcoder2", "hidden_size": 8, "intermediate_size": 32, "num_attention_heads": 2,
    "num_key_value_heads": 1, "num_hidden_layers": 3, "head_dim": 4,
    "vocab_size": 10, "sliding_window": 3, "initializer_range": 0.02,
    "weight_dtype": "bfloat16", "activation_dtype": "bfloat16",
}
ARCH = archs.load(TINY)


def test_parameter_counts_by_hand():
    # layer: q 8x8, k 8x4, v 8x4, o 8x8, wi 8x32, wo 32x8, two norms of 8
    layer = 64 + 32 + 32 + 64 + 256 + 256
    assert ARCH.matmul_parameters(TINY) == 3 * layer + 8 * 10
    assert weights.parameter_count(TINY) == (
        3 * (layer + 16) + 10 * 8 + 8 + 8 * 10)


def test_visible_pairs_by_hand():
    # window 3 over 5 positions: 1 + 2 + 3 + 3 + 3
    assert work.visible_pairs(5, 3) == 12
    assert work.visible_pairs(5, None) == 15
    assert work.visible_pairs(2, 3) == 3


def test_train_and_serve_flops_by_hand():
    # attention forward, one layer, one sequence of 5: QK and PV are each
    # 2 FLOPs x head_dim 4 per visible pair per head (2 heads).
    attention = 2 * 2 * 4 * 2 * 12
    assert ARCH.attention_forward_flops(TINY, 5) == attention
    forward = 2 * ARCH.matmul_parameters(TINY) + 3 * attention / 5
    assert ARCH.train_flops_per_token(TINY, {"sequence": 5}) == pytest.approx(
        3 * forward)
    assert work.serve_flops(TINY, 7) == 2 * ARCH.matmul_parameters(TINY) * 7


def test_decode_bytes_by_hand():
    # K and V of one token: 3 layers x 2 x 1 kv head x 4 x 2 bytes
    assert ARCH.kv_bytes_per_token(TINY) == 48
    assert work.decode_step_bytes(TINY, 10) == (
        ARCH.matmul_parameters(TINY) * 2 + 10 * 48)


def test_flash_work_and_the_binding_bound():
    job = {"batch": 2, "sequence": 5}
    parts = [ARCH.kernel_work(TINY, job, k) for k in KERNELS.values()]
    got = {"flops": sum(p["flops"] for p in parts),
           "bytes": sum(p["bytes"] for p in parts)}
    forward = ARCH.attention_forward_flops(TINY, 5)
    assert got["flops"] == 3 * forward * 2 * 3
    q, kv = 5 * 2 * 4 * 2, 5 * 1 * 4 * 2
    assert got["bytes"] == (6 * q + 6 * kv) * 2 * 3
    peak = work.peaks("TPU v5 lite")
    seconds, bound = work.roofline_seconds(got, peak)
    assert bound == "memory"  # a toy: 15 visible pairs a head
    assert seconds == pytest.approx(got["bytes"] / 819e9)
    big = {"flops": 1e15, "bytes": 1e9}
    assert work.roofline_seconds(big, peak, chips=4) == (
        pytest.approx(1e15 / (4 * 197e12)), "compute")


def test_an_unknown_device_kind_is_an_error():
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")
