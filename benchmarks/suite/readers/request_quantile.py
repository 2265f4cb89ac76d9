"""A percentile of one per-request reading over the requests the window
judged (``scope="judged"``) or over every request of the run."""

from benchmarks.suite.kinds.serve import quantile


def read(context, field: str, q: float, scope: str = "judged",
         scale: float = 1.0):
    records = context.get(scope if scope == "judged" else "records") or []
    value = quantile([r[field] for r in records if field in r], q)
    return None if value is None else value * scale
