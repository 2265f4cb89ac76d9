"""Two-pool fleet dispatch: one work queue, two tenants, bin-packed gangs.

A 16-electron, 2-tenant lattice routed through the fleet scheduler onto
two pools plus a CPU fallback — the ISSUE 7 acceptance shape, runnable on
any machine (pools ride the local transport here; swap the specs for
`workers=[...]` / `tpu_name=...` entries to drive real slices).  Shows:

* pool specs (capacity = electrons sharing one warm gang),
* tenant tags in electron metadata feeding deficit-round-robin fairness,
* per-pool placement breakdown + scheduler decisions after the run.

Run:  python examples/fleet_lattice.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from covalent_tpu_plugin.fleet import FleetExecutor
from covalent_tpu_plugin.workflow import dispatch_sync, electron, lattice

workdir = tempfile.mkdtemp(prefix="covalent-tpu-fleet-")


def pool_spec(name: str, capacity: int, fallback: bool = False) -> dict:
    # On a real deployment: {"name": "v5e", "workers": ["10.0.0.1", ...],
    # "capacity": 4} or {"name": "spare", "tpu_name": "my-v5e-8"}.
    return {
        "name": name,
        "transport": "local",
        "capacity": capacity,
        "fallback": fallback,
        "executor": {
            "cache_dir": os.path.join(workdir, f"cache_{name}"),
            "remote_cache": os.path.join(workdir, f"remote_{name}"),
            "python_path": sys.executable,
            "poll_freq": 0.2,
            "use_agent": False,
            # Drop on a real TPU VM — where a pool's capacity must not
            # exceed its chips once electrons use them: one process a chip.
            "task_env": {"JAX_PLATFORMS": "cpu"},
        },
    }


fleet = FleetExecutor(pools=[
    pool_spec("pool-a", capacity=2),
    pool_spec("pool-b", capacity=2),
    pool_spec("cpu", capacity=2, fallback=True),
])


@electron(executor=fleet, metadata={"tenant": "interactive"})
def infer(i: int) -> int:
    return i * i


@electron(executor=fleet, metadata={"tenant": "batch"})
def crunch(i: int) -> int:
    return i * i


@lattice
def fan(n: int):
    # Mixed-tenant fan-out: the queue interleaves the two tenants under
    # deficit round-robin, and the scheduler bin-packs onto warm gangs.
    return [(crunch(i) if i % 2 else infer(i)) for i in range(n)]


if __name__ == "__main__":
    result = dispatch_sync(fan)(16)
    print("status: ", result.status.value)
    print("results:", result.result)
    status = fleet.scheduler.status()
    print("decisions:", status["decisions"])
    print("placements:", {
        name: view["placed_total"]
        for name, view in status["pools"].items()
    })

    # Tear the fleet down on the loop that owns its pooled transports.
    import asyncio

    from covalent_tpu_plugin.workflow import runner

    asyncio.run_coroutine_threadsafe(
        fleet.close(), runner._dispatcher_loop()
    ).result(30)
