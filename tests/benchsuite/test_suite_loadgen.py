"""The traffic generator: the same seed gives the same requests, every seed
gets the same set of sizes and gaps in another order, and the sizes have the
medians and clips the traffic files state."""

from __future__ import annotations

import random
import statistics

import pytest

from benchmarks.suite import loadgen
from tests.benchsuite import standin

#: Serve mixes at the sizes PERF.md keeps for the serve cells to come.
MIXES = {
    "codegen": {
        "loop": "closed",
        "prompt_tokens": {"median": 384, "sigma": 0.6, "min": 128, "max": 1024},
        "output_tokens": {"median": 192, "sigma": 0.6, "min": 64, "max": 512},
        "engine": {"max_seq": 2048},
    },
    "complete": {
        "loop": "open", "rate": 2.4, "ramp_s": 5,
        "prompt_tokens": {"median": 1024, "sigma": 0.5, "min": 256, "max": 1984},
        "output_tokens": {"median": 24, "sigma": 0.5, "min": 8, "max": 64},
        "engine": {"max_seq": 2048},
    },
    "tiny": standin.TINY_SERVE,
}


@pytest.mark.parametrize("name", ["codegen", "complete", "tiny"])
def test_sizes_have_the_stated_median_and_clips(name):
    traffic = MIXES[name]
    for key in ("prompt_tokens", "output_tokens"):
        spec = traffic[key]
        sizes = loadgen.lognormal_quantiles(spec, 64)
        assert min(sizes) >= spec["min"] and max(sizes) <= spec["max"]
        assert abs(statistics.median(sizes) - spec["median"]) <= (
            0.02 * spec["median"] + 1)
    engine = traffic["engine"]
    assert (traffic["prompt_tokens"]["max"] + traffic["output_tokens"]["max"]
            <= engine["max_seq"])


def test_open_schedule_same_sizes_and_gaps_in_the_seeds_order():
    traffic = MIXES["complete"]
    a = loadgen.open_schedule(traffic, 2**31 + 3, 40)
    assert a == loadgen.open_schedule(traffic, 2**31 + 3, 40)
    b = loadgen.open_schedule(traffic, 7, 40)
    assert a != b
    in_window = lambda s: [r for r in s if r[1] >= 0]  # noqa: E731
    assert len(in_window(a)) == round(traffic["rate"] * 40)
    # Another seed: the same sets of sizes and of gaps, in another order.
    for column in (2, 3):
        assert sorted(r[column] for r in in_window(a)) == sorted(
            r[column] for r in in_window(b))

    def gaps(schedule):
        due = [0.0] + [r[1] for r in in_window(schedule)]
        return sorted(round(y - x, 9) for x, y in zip(due, due[1:]))

    assert gaps(a) == gaps(b)
    assert all(0 <= r[1] < 40 for r in in_window(a))
    assert all(r[1] < 0 for r in a if r not in in_window(a))
    assert [r[0] for r in a] == list(range(len(a)))


def test_closed_stream_cycles_one_pool_of_sizes():
    traffic = MIXES["codegen"]
    pool = loadgen.POOL
    stream = loadgen.closed_stream(traffic, 2**31 + 3)
    one = [next(stream) for _ in range(pool)]
    two = [next(stream) for _ in range(pool)]
    again = loadgen.closed_stream(traffic, 2**31 + 3)
    assert [next(again) for _ in range(pool)] == one
    other = loadgen.closed_stream(traffic, 5)
    three = [next(other) for _ in range(pool)]
    for cycle in (two, three):
        assert sorted(r[1] for r in cycle) == sorted(r[1] for r in one)
        assert sorted(r[2] for r in cycle) == sorted(r[2] for r in one)
    assert [r[1:] for r in one] != [r[1:] for r in three]
    assert [r[0] for r in one + two] == list(range(2 * pool))


def test_prompts_are_seeded_and_share_no_prefix():
    a = loadgen.prompt(2**31 + 5, 0, 64, 49152)
    assert a == loadgen.prompt(2**31 + 5, 0, 64, 49152)
    b = loadgen.prompt(2**31 + 5, 1, 64, 49152)
    assert a[:4] != b[:4] and all(0 <= t < 49152 for t in a)


def test_gaps_are_a_poisson_processes_quantiles():
    gaps = loadgen.exponential_quantiles(2.0, 80)
    assert abs(sum(gaps) / 80 - 0.5) < 0.02
    rng = random.Random(0)
    due = loadgen.arrivals(2.0, 80, rng)
    assert due == sorted(due) and abs(due[-1] - 40) < 1.5


def test_train_batches_rows_all_differ():
    config = {"vocab_size": 512}
    job = {"batch": 2, "sequence": 16}
    a = loadgen.train_batches(config, job, 2**31 + 9, 3)
    b = loadgen.train_batches(config, job, 2**31 + 9, 3)
    assert (a == b).all() and a.shape == (3, 2, 17)
    rows = {tuple(r) for batch in a for r in batch}
    assert len(rows) == 6
