"""What both kinds of cell need around the program: an executor over the
local transport with the compile cache placed, the look for the chip, and
the wait for the workers to be gone (a chip belongs to one process).
"""

from __future__ import annotations

import asyncio
import os
import shutil
import signal
import sys
import tempfile
import time

from benchmarks.suite import spec

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: Every process a run starts inherits this variable; found again by it.
RUN_MARK = "BENCH_SUITE_RUN"


class NoChip(Exception):
    """The run did not get the accelerator its cell asks for."""


def workdir() -> str:
    """A scratch directory under ``TMPDIR`` for this run's staging files."""
    path = tempfile.mkdtemp(prefix="bench-suite-")
    os.environ[RUN_MARK] = path
    return path


def cleanup(path: str) -> None:
    for pid in started_here():
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            pass
    shutil.rmtree(path, ignore_errors=True)


def executor(cell: dict, work: str, **options):
    from covalent_tpu_plugin import TPUExecutor

    cache = spec.compile_cache_dir(cell["root"])
    os.makedirs(cache, exist_ok=True)
    return TPUExecutor(
        transport="local",
        cache_dir=os.path.join(work, "cache"),
        remote_cache=os.path.join(work, "remote"),
        python_path=sys.executable,
        poll_freq=0.2,
        prewarm=False,
        task_env={
            "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
            "JAX_COMPILATION_CACHE_DIR": cache,
            # Every program, not only those over jax's default second: the
            # second run of a cell has to find all of them.
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
            "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0",
        },
        **options,
    )


def check_report(report: dict) -> None:
    """A worker that did not find the cell's chips says so in its report."""
    if report.get("no_chip"):
        raise NoChip(report["no_chip"])


def started_here() -> list[int]:
    """Live pids this run started (they inherited ``RUN_MARK``)."""
    value = os.environ.get(RUN_MARK)
    if not value:
        return []
    mark = f"{RUN_MARK}={value}".encode()
    pids = []
    for entry in os.listdir("/proc"):
        if not entry.isdigit() or int(entry) == os.getpid():
            continue
        try:
            with open(f"/proc/{entry}/environ", "rb") as f:
                marked = mark in f.read().split(b"\0")
            with open(f"/proc/{entry}/stat", encoding="utf-8") as f:
                state = f.read().rsplit(")", 1)[1].split()[0]
        except (OSError, IndexError):
            continue
        if marked and state != "Z":
            pids.append(int(entry))
    return pids


async def await_workers_gone(limit_s: float = 30.0) -> None:
    """The workers must have exited before this process takes the chip."""
    deadline = time.monotonic() + limit_s
    while started_here():
        if time.monotonic() > deadline:
            for pid in started_here():
                try:
                    os.kill(pid, signal.SIGKILL)
                except OSError:
                    pass
            await asyncio.sleep(0.5)
            return
        await asyncio.sleep(0.1)
