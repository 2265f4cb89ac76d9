"""Task/result serialization.

The wire protocol matches the reference: the dispatcher cloudpickles the
``(fn, args, kwargs)`` triple into a function file
(``covalent_ssh_plugin/ssh.py:147-150``) and the remote harness writes a
``(result, exception)`` pickle back (``covalent_ssh_plugin/exec.py:45-46``).
The TPU additions are device-aware: results are materialised to host memory
(``block_until_ready`` + ``device_get``) before pickling, because
``jax.Array`` handles referencing TPU buffers do not survive a pickle
round-trip to another machine.
"""

from __future__ import annotations

import json
import pickle
from pathlib import Path
from typing import Any, Callable

import cloudpickle


def dump_task(
    fn: Callable, args: tuple, kwargs: dict, path: str | Path
) -> None:
    """Stage ``(fn, args, kwargs)`` to ``path`` (reference: ssh.py:147-150)."""
    with open(path, "wb") as f:
        cloudpickle.dump((fn, args, kwargs), f)


def load_result(path: str | Path) -> tuple[Any, BaseException | None]:
    """Unpickle a fetched result file (reference: ssh.py:455-458)."""
    return load_result_and_trailer(path)[0]


def load_result_and_trailer(
    path: str | Path,
) -> tuple[tuple[Any, BaseException | None], dict | None]:
    """The ``(result, exception)`` pair and, where the harness wrote one,
    the JSON trailer that follows the pickle in the same file: the worker's
    spans and compile counters (``harness._trace_trailer``), which so come
    home in the fetch the dispatcher makes anyway.  A missing or torn
    trailer reads as ``None``: observability never fails a result."""
    with open(path, "rb") as f:
        pair = pickle.load(f)
        rest = f.read()
    try:
        trailer = json.loads(rest) if rest.strip() else None
    except ValueError:
        trailer = None
    return pair, trailer if isinstance(trailer, dict) else None
