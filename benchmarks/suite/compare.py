"""The comparison that decides ``correct``: the program's readings against
the plain reference's, each number beside a limit of its own
(``limits/<cell>.json``; PERF.md gives the readings each was set from).
"""

from __future__ import annotations

import statistics


def norm_gaps(program: dict, reference: dict, floor_of: dict | None = None,
              skip_below: float = 0.0) -> dict:
    """Per leaf, the gap between the program's norm and the reference's (not
    the norm of a difference), against the reference's norm of that leaf or
    of the median leaf, whichever is larger.  Leaves whose ``floor_of``
    reading (the reference's first gradient) is under ``skip_below`` of the
    median leaf's are left out: they move by round-off alone."""
    median = statistics.median(reference.values())
    keep = set(reference)
    if floor_of is not None and skip_below > 0:
        floor = skip_below * statistics.median(floor_of.values())
        keep = {n for n in reference if floor_of[n] >= floor}
    return {
        n: abs(program[n] - reference[n]) / max(reference[n], median)
        for n in sorted(keep)
    }


def train_numbers(program: dict, reference: dict) -> dict:
    """``loss_gap``: the worst step's relative loss gap; ``grad_gap`` and
    ``delta_gap``: the worst leaf's gap of first-gradient norms and of the
    norms of the change over the checked steps."""
    loss_gap = max(
        abs(p - r) / abs(r)
        for p, r in zip(program["losses"], reference["losses"])
    )
    grad = norm_gaps(program["grad_norms"], reference["grad_norms"])
    delta = norm_gaps(program["delta_norms"], reference["delta_norms"],
                      floor_of=reference["grad_norms"], skip_below=1e-3)
    worst_grad = max(grad, key=grad.get)
    worst_delta = max(delta, key=delta.get)
    return {
        "loss_gap": loss_gap,
        "grad_gap": grad[worst_grad],
        "delta_gap": delta[worst_delta],
        "_worst": {"grad_gap": worst_grad, "delta_gap": worst_delta},
    }


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})`` over the numbers that have
    a limit; a limited number that is missing or not finite fails."""
    out, ok = {}, True
    for name, limit in limits.items():
        if name.startswith("_"):  # a parameter of the check, not a limit
            continue
        value = numbers.get(name)
        good = (
            isinstance(value, (int, float))
            and value == value
            and value <= limit
        )
        ok = ok and good
        out[name] = {"value": value, "limit": limit}
    return ok, out
