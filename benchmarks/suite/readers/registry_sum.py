"""The sum of one labelled counter's series whose ``label`` is one of
``values``, in the registry of the harness's own process (the dispatcher),
read after the electron has returned: what the worker counted and sent
home with its result.  None where the program never made the counter (the
control, a fault, a program without it); 0 where it did and no such series
grew (no cache miss in a warm run)."""


def read(context, metric: str, label: str, values: list):
    from covalent_tpu_plugin.obs import REGISTRY

    family = REGISTRY.snapshot()["metrics"].get(metric)
    if family is None:
        return None
    return float(sum(
        entry["value"] for entry in family["series"]
        if entry["labels"].get(label) in values
    ))
