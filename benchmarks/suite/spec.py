"""``BENCHMARK.json`` and the data files it names, read into one cell.

Nothing about a cell lives in code: a configuration is its file of sizes, a
traffic mix its file of parameters, a per-layer metric its file naming a
reader, a cell's limits the file of the numbers ``correct`` compares.  All
are found under ``paths[0]`` by the name the manifest gives.
"""

from __future__ import annotations

import json
import os


def _read(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_cell(root: str, workload: str) -> dict:
    """Everything one run needs to know about ``workload``; ``root`` is the
    directory that holds ``BENCHMARK.json``."""
    bench = _read(os.path.join(root, "BENCHMARK.json"))
    home = os.path.join(root, bench["paths"][0])
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(has: {', '.join(cells)})")
    entry = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read(os.path.join(root, configs[entry["config"]]["file"]))
    traffic = _read(os.path.join(home, "traffic", entry["traffic"] + ".json"))

    def reported(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    per_layer = []
    for metric in bench["per_layer"]:
        if reported(metric):
            per_layer.append({**metric, **_read(
                os.path.join(home, "metrics", metric["name"] + ".json"))})
    return {
        "name": workload,
        "root": root,
        "home": home,
        "chips": int(entry["chips"]),
        "config": config,
        "traffic": traffic,
        "limits": _read(os.path.join(home, "limits", workload + ".json")),
        "end_to_end": [m for m in bench["end_to_end"] if reported(m)],
        "per_layer": per_layer,
    }


def compile_cache_dir(root: str) -> str:
    """``JAX_COMPILATION_CACHE_DIR`` verbatim when set, else one fixed path
    inside the checkout (the path is part of the cache's key).  Workers get
    it through their environment before they import jax; no code sets it."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".cache", "jax"
    )
