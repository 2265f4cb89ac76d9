"""Device milliseconds a traced step spends in operations under a scope:
each operation's own device time (``reduce.py``) laid to the ``op_name`` the
compiler kept for its instruction (``jax.named_scope``s, flax modules and
transforms, ``/``-separated), summed over the operations that match, over
the traced steps.

A pattern is a ``/``-separated run of globs that the ``op_name``'s segments
have to hold in that order, not necessarily side by side: ``transpose(*)/
layer_*`` matches ``jit(step)/loss/transpose(jvp(LM))/checkpoint/layer_2/
mlp/wo/dot_general``.  An operation counts, once, when any pattern of
``under`` matches it (every operation, where ``under`` is empty) and none of
``not_under`` does.  An operation with no ``op_name`` has no segments.
"""

import fnmatch


def matches(op_name: str, pattern: str) -> bool:
    segments = iter(op_name.split("/") if op_name else [])
    return all(
        any(fnmatch.fnmatchcase(segment, glob) for segment in segments)
        for glob in pattern.split("/")
    )


def counts(op_name: str, under: list = (), not_under: list = ()) -> bool:
    return (not under or any(matches(op_name, p) for p in under)) and not any(
        matches(op_name, p) for p in not_under)


def read(context, under: list = (), not_under: list = ()):
    trace = context.get("trace")
    steps = context.get("trace_steps")
    if not trace or not steps:
        return None
    took = [seconds for op_name, seconds in (trace.get("scopes") or {}).items()
            if counts(op_name, under, not_under)]
    if not took:
        return None  # no such scope in the traced program
    return sum(took) / steps * 1e3
