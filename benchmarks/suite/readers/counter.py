"""A count the run made itself, by its key in the context."""


def read(context, key: str):
    value = context.get(key)
    return None if value is None else float(value)
