"""A stand-in benchmark at toy sizes in a temporary root: the real manifest
and data files copied, then one tiny configuration, one tiny traffic mix of
each kind (closed loop, open loop, train), the serve cells' end-to-end
metrics and a few per-layer metrics ADDED as new files with one new entry
each, the way a later PR adds a cell.  Nothing that is there is edited.
"""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_CONFIG = {
    "model_type": "starcoder2",
    "source": "a stand-in for tests, nobody's model",
    "hidden_size": 64, "intermediate_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "head_dim": 16,
    "vocab_size": 512, "sliding_window": 48, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-06, "initializer_range": 0.05,
    "weight_dtype": "float32", "activation_dtype": "float32",
}
TINY_SERVE = {
    "kind": "serve", "loop": "closed", "clients": 3, "ramp_s": 1,
    "prompt_tokens": {"median": 12, "sigma": 0.5, "min": 5, "max": 30},
    "output_tokens": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
    "engine": {"max_batch": 4, "sync_steps": 4, "max_seq": 64,
               "prefix_cache_size": 2},
    "check_requests": 16,
}
TINY_OPEN = dict(TINY_SERVE, loop="open", rate=4.0)
TINY_TRAIN = {
    "kind": "train", "batch": 2, "sequence": 64, "attention": "flash",
    "remat": True, "vocab_chunk": 128, "learning_rate": 1e-3,
    "mesh": {"data": 1}, "feed_batches": 4, "check_steps": 3,
    "warm_steps": 1, "trace_after": 1, "trace_steps": 2,
}


def _write(path: str, obj: dict) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(obj, f, indent=1)


def make_root(tmp: str) -> str:
    """Copy the manifest and the data files into ``tmp``; add the stand-ins."""
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    home = bench["paths"][0]
    for sub in ("configs", "traffic", "metrics", "limits"):
        shutil.copytree(os.path.join(REPO, home, sub),
                        os.path.join(tmp, home, sub))
    _write(os.path.join(tmp, home, "configs", "tiny.json"), TINY_CONFIG)
    bench["configs"].append({
        "name": "tiny", "source": TINY_CONFIG["source"],
        "file": f"{home}/configs/tiny.json", "reduced": [],
        "why": "stand-in"})
    cells = {"tiny.closed": TINY_SERVE, "tiny.open": TINY_OPEN,
             "tiny.train": TINY_TRAIN}
    for cell, traffic in cells.items():
        mix = cell.split(".")[1]
        _write(os.path.join(tmp, home, "traffic", mix + ".json"), traffic)
        bench["workloads"].append({
            "name": cell, "config": "tiny", "traffic": mix, "chips": 1,
            "why": "stand-in"})
        limits = (
            {"loss_gap": 1e-3, "grad_gap": 1e-2, "delta_gap": 1e-2}
            if mix == "train" else
            {"token_gap": 1e-3, "bad_streams": 0, "short_sample": 0,
             "_min_tokens": 8}
        )
        _write(os.path.join(tmp, home, "limits", cell + ".json"), limits)
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if metric.get("workloads") == ["sc2-3b.train-16k"]:
            metric["workloads"] = metric["workloads"] + ["tiny.train"]
    # The serve cells' metrics, as the PR that brings such cells adds them:
    # new entries, and a new file for each per-layer one.
    for name, unit, better, cell in [
        ("out_tok_s", "tokens/s", "higher", "tiny.closed"),
        ("tpot_p90_ms", "ms", "lower", "tiny.closed"),
        ("ttft_p90_s", "s", "lower", "tiny.open"),
    ]:
        bench["end_to_end"].append({
            "name": name, "unit": unit, "better": better, "bound": 0.1,
            "source": "host_clock", "workloads": [cell]})
    for name, unit, better, source, layer, moves, cell, reader in [
        ("ttft_p50_s.tiny", "s", "lower", "host_clock", "engine",
         "out_tok_s", "tiny.closed",
         {"reader": "request_quantile", "args": {"field": "ttft_s", "q": 50}}),
        ("compiles_in_window.tiny", "count", "lower", "program_counter",
         "engine", "out_tok_s", "tiny.closed",
         {"reader": "counter", "args": {"key": "compiles_in_window"}}),
        ("decode_roofline.tiny", "%", "higher", "device_trace", "kernels",
         "out_tok_s", "tiny.closed",
         {"reader": "decode_roofline",
          "args": {"module": "jit_run_steps", "steps_key": "sync_steps"}}),
        ("step_mfu.tiny", "%", "higher", "host_clock", "model step, serving",
         "out_tok_s", "tiny.closed", {"reader": "serve_mfu"}),
        ("idle_pct.tiny", "%", "lower", "device_trace", "device",
         "out_tok_s", "tiny.closed", {"reader": "idle_pct"}),
        ("prefill_pad_pct.tiny", "%", "lower", "program_counter", "engine",
         "ttft_p90_s", "tiny.open", {"reader": "prefill_pad"}),
    ]:
        _write(os.path.join(tmp, home, "metrics", name + ".json"), reader)
        bench["per_layer"].append({
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": moves, "workloads": [cell]})
    _write(os.path.join(tmp, "BENCHMARK.json"), bench)
    return tmp
