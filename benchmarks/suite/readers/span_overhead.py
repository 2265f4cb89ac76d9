"""Seconds of one program span that none of its named child spent: the sums
of ``covalent_tpu_span_duration_seconds`` for ``span`` less that for
``minus``, in the registry of the harness's own process (the dispatcher),
read after the electron has returned.  With ``executor.run`` and
``worker.execute``, each term on one clock, that is the dispatch overhead
of the run's one electron.  None where either span was never recorded (the
control, a fault, a program that sends no worker spans home)."""

HISTOGRAM = "covalent_tpu_span_duration_seconds"


def read(context, span: str, minus: str):
    from covalent_tpu_plugin.obs import REGISTRY

    family = REGISTRY.snapshot()["metrics"].get(HISTOGRAM)
    sums = {
        entry["labels"].get("span"): entry
        for entry in (family or {}).get("series", [])
    }
    whole, part = sums.get(span), sums.get(minus)
    if not whole or not part or not whole["count"] or not part["count"]:
        return None
    return whole["sum"] - part["sum"]
