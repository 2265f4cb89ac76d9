"""``registry_sum``'s reading times ``scale``: a series the program keeps in
seconds, reported in milliseconds.  None where the program never made the
series."""

from benchmarks.suite.readers import registry_sum


def read(context, metric: str, label: str, values: list, scale: float):
    value = registry_sum.read(context, metric, label, values)
    return None if value is None else value * scale
