"""The one general traffic generator: a traffic file's parameters and the
seed in, request sizes, token ids and due times out.

Sizes and gaps are the quantiles of the stated distributions, evenly spaced
in probability: every ``--seed`` gets the same set of sizes and of gaps, in
another order, and draws the token ids (and the weights) anew.
"""

from __future__ import annotations

import math
import random
import statistics

_NORMAL = statistics.NormalDist()
#: How many size pairs a closed loop cycles through.
POOL = 32


def lognormal_quantiles(spec: dict, n: int) -> list[int]:
    """``n`` whole sizes at the probabilities (i + 0.5)/n of a lognormal of
    the given ``median`` and ``sigma``, clipped to ``min``..``max``."""
    out = []
    for i in range(n):
        z = _NORMAL.inv_cdf((i + 0.5) / n)
        value = spec["median"] * math.exp(spec["sigma"] * z)
        out.append(int(min(max(round(value), spec["min"]), spec["max"])))
    return out


def exponential_quantiles(rate: float, n: int) -> list[float]:
    """``n`` gaps at the probabilities (i + 0.5)/n of an exponential of the
    given rate: a Poisson process's gaps, evenly represented."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def sizes(traffic: dict, n: int, rng: random.Random) -> list[tuple[int, int]]:
    """``n`` (prompt tokens, output tokens) pairs: both sets of quantiles,
    each shuffled on its own."""
    prompts = lognormal_quantiles(traffic["prompt_tokens"], n)
    outputs = lognormal_quantiles(traffic["output_tokens"], n)
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    return list(zip(prompts, outputs))


def arrivals(rate: float, n: int, rng: random.Random, start: float = 0.0):
    """Due times of ``n`` requests from ``start`` on, gaps shuffled."""
    gaps = exponential_quantiles(rate, n)
    rng.shuffle(gaps)
    out, t = [], start
    for gap in gaps:
        t += gap
        out.append(t)
    return out


def prompt(seed: int, index: int, length: int, vocab: int) -> list[int]:
    """Token ids of request ``index``: uniform, no shared prefixes."""
    rng = random.Random(f"{int(seed)}:{index}")
    return [rng.randrange(vocab) for _ in range(length)]


def closed_stream(traffic: dict, seed: int):
    """Endless (index, prompt tokens, output tokens) for a closed loop: the
    pool of sizes cycled, reshuffled each cycle."""
    rng = random.Random(int(seed))
    index = 0
    while True:
        for n_prompt, n_out in sizes(traffic, POOL, rng):
            yield index, n_prompt, n_out
            index += 1


def open_schedule(traffic: dict, seed: int, seconds: float):
    """``[(index, due, prompt tokens, output tokens), ...]`` for an open
    loop: ``due`` is relative to the window's start; the ramp's requests
    come before 0 and the window's (``rate * seconds`` of them, rescaled to
    end inside it) after."""
    rng = random.Random(int(seed))
    rate, ramp = float(traffic["rate"]), float(traffic["ramp_s"])
    n_ramp, n = max(int(round(rate * ramp)), 1), int(round(rate * seconds))
    out, index = [], 0
    ramp_due = arrivals(rate, n_ramp, rng)
    scale = max(ramp - 0.5 / rate, 0.0) / ramp_due[-1]
    for due, (a, b) in zip(ramp_due, sizes(traffic, n_ramp, rng)):
        out.append((index, due * scale - ramp, a, b))
        index += 1
    due_in = arrivals(rate, n, rng)
    scale = min(1.0, (seconds * (1 - 0.5 / n)) / due_in[-1])
    for due, (a, b) in zip(due_in, sizes(traffic, n, rng)):
        out.append((index, due * scale, a, b))
        index += 1
    return out


def train_batches(config: dict, job: dict, seed: int, n: int):
    """``n`` batches of (batch, sequence + 1) token ids from the seed, rows
    all different.  The loss feeds ``tokens[:, :-1]``, so ``sequence``
    positions go through the model."""
    import numpy as np

    rng = np.random.default_rng(int(seed))
    return rng.integers(
        0, config["vocab_size"],
        size=(n, job["batch"], job["sequence"] + 1), dtype=np.int32,
    )
