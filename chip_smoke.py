"""Chip smoke: the dispatch, serving and training paths, once, on the chip.

The quickest proof that the system still starts on the accelerator.  Three
stages run in order over ``transport="local"``, through the entry points a
user calls, at the full width of the one preset the repo has
(``models.lm_125m_config()``), weights random from a seed:

* ``dispatch`` — ``TPUExecutor.run()`` (launch mode) of an electron that
  reports its device and checks a bf16 matmul against a float32 reference.
* ``serve`` — ``serving.open_session`` with ``use_agent="pool"``; the factory
  builds the model AND its params inside the worker and returns a
  ``ContinuousEngine``; concurrent ragged requests, one consumed as a stream
  and compared with ``generate()`` run in the same worker.
* ``train`` — a launch-mode electron: ``attention="flash"`` named explicitly,
  ``make_sharded_train_state`` + ``make_train_step`` over a mesh of every local
  device, a falling finite loss, and the Mosaic custom call in the lowered step.

Each stage has its own worker runtime, gone before the next starts: a chip
belongs to one process.  The parent never initialises a JAX backend.  Any stage
that fails, times out, or finds a device other than a TPU fails the run —
``--tiny`` alone accepts a CPU, so the command can be rehearsed end to end with
``JAX_PLATFORMS=cpu`` before chip time is spent on it.

The last stdout line of a passing run is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import os
import random
import shutil
import signal
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

from covalent_tpu_plugin import TPUExecutor, harness  # noqa: E402
from covalent_tpu_plugin.serving import open_session  # noqa: E402

#: One row per mode.  ``model`` overrides ``lm_125m_config()`` — empty for
#: ``full``, which is the preset as the repo defines it (vocab 32768, d_model
#: 768, 12 layers, 12 heads, d_ff 3072, bf16 activations).  ``prompt_lens``
#: are ragged on purpose: several prefill buckets compile, and more requests
#: than ``max_batch`` means admission takes more than one wave.
SIZES = {
    "full": {
        "model": {},
        "matmul_n": 4096,
        "serve": {
            "max_seq": 1024, "max_batch": 8, "sync_steps": 8,
            "prompt_lens": (16, 700, 40, 300, 130, 520, 64, 260, 24, 100),
            "new_tokens": (32, 48, 64),
        },
        "train": {"batch": 8, "seq": 1024, "steps": 4},
        "timeouts_s": {"dispatch": 240, "serve": 540, "train": 360},
    },
    "tiny": {
        "model": {
            "vocab_size": 256, "d_model": 64, "n_layers": 2, "n_heads": 4,
            "d_ff": 128,
        },
        "matmul_n": 256,
        "serve": {
            "max_seq": 128, "max_batch": 4, "sync_steps": 4,
            "prompt_lens": (4, 70, 9, 33, 17, 40, 5, 20),
            "new_tokens": (6, 8, 10),
        },
        "train": {"batch": 4, "seq": 128, "steps": 3},
        "timeouts_s": {"dispatch": 120, "serve": 240, "train": 240},
    },
}

SEED = 0
#: Environment variable every process this run starts inherits.
RUN_MARK = "CHIP_SMOKE_RUN"


class SmokeFailure(Exception):
    """One stage did not meet its contract."""


def compile_cache_dir() -> str:
    """Where every worker keeps jax's persistent compile cache.

    ``JAX_COMPILATION_CACHE_DIR`` verbatim when set; otherwise one fixed path
    inside the checkout.  The path is part of each cache key's provenance and
    must be found again by the next run, so it is never built from a pid, a
    temp name or the time — and no code sets ``jax_compilation_cache_dir``:
    workers get the variable through ``task_env`` before they import jax.
    """
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        REPO, ".cache", "jax"
    )


# --------------------------------------------------------------------------
# Worker side.  Everything below runs INSIDE a worker process; the parent
# only pickles these functions (by value: this file is ``__main__``).
# --------------------------------------------------------------------------


def _compile_clock():
    """Start summing this process's XLA compiles (or persistent-cache
    fetches, when warm); returns the reader.  Tracing and lowering are not
    counted: their events nest, and they cost the same warm or cold."""
    import jax

    total = [0.0]

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            total[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return lambda: total[0]


def _device_report() -> dict:
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "devices": len(devices),
        "cache_dir": jax.config.jax_compilation_cache_dir,
    }


def _dispatch_electron(n: int) -> dict:
    """BASELINE config 2: one bf16 matmul, checked against float32 rows."""
    clock = _compile_clock()
    import jax
    import jax.numpy as jnp
    import numpy as np

    report = _device_report()
    ka, kb = jax.random.split(jax.random.PRNGKey(SEED))
    a = jax.random.normal(ka, (n, n), jnp.bfloat16)
    b = jax.random.normal(kb, (n, n), jnp.bfloat16)
    matmul = jax.jit(
        lambda x, y: jnp.matmul(x, y, preferred_element_type=jnp.float32)
    )
    out = matmul(a, b).block_until_ready()
    t0 = time.perf_counter()
    out = matmul(a, b).block_until_ready()
    report["run_s"] = time.perf_counter() - t0
    rows = 8
    want = np.asarray(a[:rows], np.float32) @ np.asarray(b, np.float32)
    got = np.asarray(out[:rows])
    # bf16 inputs are exact in float32 and both sides accumulate in float32:
    # what is left is summation order over n terms of magnitude ~1.
    report["max_abs_err"] = float(np.max(np.abs(got - want)))
    report["checked"] = bool(
        got.shape == want.shape
        and np.all(np.isfinite(got))
        and np.allclose(got, want, rtol=1e-3, atol=1e-2 * n**0.5)
    )
    report["compile_s"] = clock()
    return report


def _engine_factory(model: dict, serve: dict, report_path: str,
                    ref_prompt: list, ref_new: int):
    """The zero-arg factory ``open_session`` ships: model, params and engine
    are all built here, in the worker that holds the chip."""

    def factory():
        clock = _compile_clock()
        import jax
        import jax.numpy as jnp
        import numpy as np

        from covalent_tpu_plugin.models import (
            TransformerLM,
            generate,
            inference_params,
            lm_125m_config,
        )
        from covalent_tpu_plugin.models.serve import ContinuousEngine

        report = _device_report()
        lm = TransformerLM(lm_125m_config(
            **model, max_seq=serve["max_seq"], scan_layers=False,
        ))
        params = inference_params(jax.jit(
            lambda key: lm.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
        )(jax.random.PRNGKey(SEED)))
        report["params"] = lm.parameter_count(params)
        reference = generate(
            lm, params, jnp.asarray([ref_prompt], jnp.int32), ref_new
        )
        report["reference"] = np.asarray(reference)[0, len(ref_prompt):].tolist()

        def publish() -> None:
            report["compile_s"] = clock()
            tmp = report_path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(report, f)
            os.replace(tmp, report_path)

        class ReportingEngine(ContinuousEngine):
            def close(self) -> None:  # the session's teardown hook
                super().close()
                publish()

        engine = ReportingEngine(
            lm, params, max_batch=serve["max_batch"],
            sync_steps=serve["sync_steps"],
            max_new_tokens=max(serve["new_tokens"]),
        )
        report["compile_open_s"] = clock()
        publish()
        return engine

    return factory


def _mesh_plan(n_devices: int) -> dict:
    """One chip: the trivial mesh.  Four: fsdp 2 x tensor 2, so the
    shard_map'd flash kernel and real collectives run."""
    if n_devices % 4 == 0:
        return {"data": n_devices // 4, "fsdp": 2, "tensor": 2}
    return {"data": n_devices}


def _train_electron(model: dict, train: dict) -> dict:
    clock = _compile_clock()
    import jax
    import numpy as np
    import optax

    from covalent_tpu_plugin.models import (
        TransformerLM,
        lm_125m_config,
        lm_loss,
        make_sharded_train_state,
        make_train_step,
    )
    from covalent_tpu_plugin.parallel import MeshPlan, make_mesh, shard_batch

    report = _device_report()
    devices = jax.local_devices()
    plan = _mesh_plan(len(devices))
    mesh = make_mesh(MeshPlan(**plan), devices)
    config = lm_125m_config(
        **model, max_seq=train["seq"], attention="flash", mesh=mesh,
    )
    lm = TransformerLM(config)
    # lm_loss feeds tokens[:, :-1], so seq + 1 tokens put `seq` positions —
    # a whole number of flash tiles — through the model.
    tokens = np.random.default_rng(SEED).integers(
        0, config.vocab_size, size=(train["batch"], train["seq"] + 1)
    ).astype(np.int32)
    batch = shard_batch({"tokens": tokens}, mesh)
    # 1e-4: Adam's first updates move every weight by about the learning
    # rate, and at 125M width 1e-3 overshoots by the fourth step.
    state, shardings = make_sharded_train_state(
        lm, optax.adamw(1e-4), jax.random.PRNGKey(SEED),
        batch["tokens"][:, :-1], mesh,
    )
    step = make_train_step(lm_loss, mesh, shardings)
    # A kernel that slid into interpret mode or into mha_reference lowers
    # to plain HLO; only the compiled Pallas kernel leaves this call.
    report["mosaic"] = "tpu_custom_call" in step.lower(state, batch).as_text()

    losses = []
    for i in range(train["steps"]):
        if i == 1:
            t0 = time.perf_counter()
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))  # blocks on the step
    report["run_s"] = (time.perf_counter() - t0) / (train["steps"] - 1)
    report["losses"] = losses

    # Where the state actually lives: bytes of params + optimizer state on
    # each device, read off the arrays the last step returned.
    held = {d.id: 0 for d in devices}
    total = 0
    for leaf in jax.tree_util.tree_leaves((state.params, state.opt_state)):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    report["mesh"] = plan
    report["state_bytes"] = total
    report["state_bytes_by_device"] = [held[d.id] for d in devices]
    report["compile_s"] = clock()
    return report


# --------------------------------------------------------------------------
# Parent side.
# --------------------------------------------------------------------------


def _executor(workdir: str, stage: str, cache_dir: str, **options) -> TPUExecutor:
    return TPUExecutor(
        transport="local",
        cache_dir=os.path.join(workdir, stage, "cache"),
        remote_cache=os.path.join(workdir, stage, "remote"),
        python_path=sys.executable,
        poll_freq=0.5,
        prewarm=False,
        # run_local_on_dispatch_fail stays off: a failed dispatch must fail.
        task_env={
            # Workers import the package; nothing else reaches them by env
            # — the platform in particular is whatever this process was
            # started under.
            "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
            "JAX_COMPILATION_CACHE_DIR": cache_dir,
            # Cache every program, not only those over jax's default 1 s:
            # a warm run should find all of them, at any model size.
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        },
        **options,
    )


def _check_device(stage: str, report: dict, accept_cpu: bool,
                  cache_dir: str) -> None:
    platform = report["platform"]
    if platform != "tpu" and not (accept_cpu and platform == "cpu"):
        raise SmokeFailure(
            f"{stage}: ran on platform={platform} "
            f"device_kind={report['device_kind']!r} x{report['devices']}, "
            "not a TPU (only --tiny accepts a CPU)"
        )
    if report["cache_dir"] != cache_dir:
        raise SmokeFailure(
            f"{stage}: worker's jax compile cache is {report['cache_dir']!r}, "
            f"not {cache_dir!r} — the variable reached it after jax's import"
        )


def _stage_line(stage: str, report: dict, **extra) -> None:
    fields = {
        "platform": report["platform"],
        "device_kind": json.dumps(report["device_kind"]),
        "devices": report["devices"],
        "compile_s": f"{report['compile_s']:.2f}",
        "run_s": f"{report['run_s']:.4f}",
        **extra,
    }
    print(f"stage={stage} " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def _started_here() -> list:
    """Live pids this run started: they inherited ``RUN_MARK``.  Found by
    environment, not ancestry — pool-forked tasks ``setsid`` away."""
    mark = f"{RUN_MARK}={os.environ[RUN_MARK]}".encode()
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
        if marked and state != "Z":  # nothing reaps zombies in a container
            pids.append(int(entry))
    return pids


async def _await_gone(stage: str) -> None:
    """The stage's runtime must have exited before the next one starts: a
    chip belongs to one process."""
    deadline = time.monotonic() + 30.0
    while _started_here():
        if time.monotonic() > deadline:
            raise SmokeFailure(
                f"{stage}: worker processes {_started_here()} still alive "
                "30 s after executor.close()"
            )
        await asyncio.sleep(0.2)


async def _stage_dispatch(size: dict, workdir: str, cache_dir: str,
                          accept_cpu: bool) -> dict:
    executor = _executor(workdir, "dispatch", cache_dir, dispatch_mode="launch")
    try:
        report = await executor.run(
            _dispatch_electron, [size["matmul_n"]], {},
            {"dispatch_id": "chip-smoke", "node_id": 0},
        )
        if executor.last_dispatch_mode != "launch":
            raise SmokeFailure(
                f"dispatch: ran in {executor.last_dispatch_mode!r} mode"
            )
    finally:
        await executor.close()
    _check_device("dispatch", report, accept_cpu, cache_dir)
    if not report["checked"]:
        raise SmokeFailure(
            f"dispatch: {size['matmul_n']}^2 bf16 matmul disagrees with the "
            f"float32 reference (max abs err {report['max_abs_err']})"
        )
    await _await_gone("dispatch")
    _stage_line("dispatch", report, matmul_n=size["matmul_n"],
                max_abs_err=f"{report['max_abs_err']:.3g}")
    return report


async def _stage_serve(size: dict, workdir: str, cache_dir: str,
                       accept_cpu: bool) -> dict:
    serve = size["serve"]
    vocab = size["model"].get("vocab_size", 32768)
    rng = random.Random(SEED)
    prompts = [
        [rng.randrange(vocab) for _ in range(n)] for n in serve["prompt_lens"]
    ]
    budgets = [
        serve["new_tokens"][i % len(serve["new_tokens"])]
        for i in range(len(prompts))
    ]
    report_path = os.path.join(workdir, "serve_report.json")
    budget_s = size["timeouts_s"]["serve"]
    executor = _executor(
        workdir, "serve", cache_dir, use_agent="pool",
    )
    handle = None
    try:
        # Nothing pre-compiles: the open pays init + the generate() oracle,
        # then the first request of each prefill bucket pays its compile.
        handle = await open_session(
            executor,
            _engine_factory(size["model"], serve, report_path,
                            prompts[0], budgets[0]),
            open_timeout_s=budget_s,
            default_deadline_s=budget_s,
        )
        t0 = time.perf_counter()
        requests = [
            await handle.request(p, params={"max_new_tokens": n})
            for p, n in zip(prompts, budgets)
        ]
        streamed = [
            token async for chunk in requests[0].stream() for token in chunk
        ]
        results = await asyncio.gather(
            *(r.result(budget_s) for r in requests)
        )
        wall_s = time.perf_counter() - t0
        closed = await handle.close()
        handle = None
    finally:
        if handle is not None:
            await handle.close()
        await executor.close()

    with open(report_path, encoding="utf-8") as f:
        report = json.load(f)
    _check_device("serve", report, accept_cpu, cache_dir)
    for request, tokens, budget in zip(requests, results, budgets):
        if request.error or len(tokens) != budget:
            raise SmokeFailure(
                f"serve: {request.rid} returned {len(tokens)} of {budget} "
                f"tokens (error={request.error!r})"
            )
        if not all(0 <= t < vocab for t in tokens):
            raise SmokeFailure(f"serve: {request.rid} has ids outside the vocab")
        if request.ttft_s is None:
            raise SmokeFailure(f"serve: {request.rid} recorded no TTFT")
    if streamed != results[0]:
        raise SmokeFailure("serve: stream() chunks differ from result()")
    if closed.get("served") != len(requests):
        raise SmokeFailure(
            f"serve: session served {closed.get('served')} of {len(requests)}"
        )
    await _await_gone("serve")

    # Not gated: on the chip a batched lane and the batch-1 oracle may round
    # bf16 near-ties differently (models/serve.py, module docstring), and
    # greedy streams never re-converge after their first split.
    agree = next(
        (i for i, (a, b) in enumerate(zip(results[0], report["reference"]))
         if a != b),
        len(results[0]),
    )
    serve_compile_s = report["compile_s"] - report["compile_open_s"]
    report["run_s"] = max(wall_s - serve_compile_s, 0.0)
    ttfts = sorted(r.ttft_s for r in requests)
    _stage_line(
        "serve", report, requests=len(requests),
        tokens=sum(len(r) for r in results),
        params=report["params"], wall_s=f"{wall_s:.2f}",
        compile_open_s=f"{report['compile_open_s']:.2f}",
        ttft_p50_s=f"{ttfts[len(ttfts) // 2]:.3f}",
        ttft_max_s=f"{ttfts[-1]:.3f}",
        generate_common_prefix=f"{agree}/{len(results[0])}",
    )
    return report


async def _stage_train(size: dict, workdir: str, cache_dir: str,
                       accept_cpu: bool) -> dict:
    executor = _executor(workdir, "train", cache_dir, dispatch_mode="launch")
    try:
        report = await executor.run(
            _train_electron, [size["model"], size["train"]], {},
            {"dispatch_id": "chip-smoke", "node_id": 1},
        )
    finally:
        await executor.close()
    _check_device("train", report, accept_cpu, cache_dir)
    losses = report["losses"]
    if not all(math.isfinite(x) for x in losses):
        raise SmokeFailure(f"train: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise SmokeFailure(f"train: loss did not fall over {losses}")
    if report["platform"] == "tpu" and not report["mosaic"]:
        raise SmokeFailure(
            "train: no tpu_custom_call in the lowered step — the flash "
            "kernel did not compile through Mosaic"
        )
    by_device = report["state_bytes_by_device"]
    if min(by_device) == 0 or (
        len(by_device) > 1 and max(by_device) >= report["state_bytes"]
    ):
        raise SmokeFailure(
            f"train: state not spread over the mesh {report['mesh']}: "
            f"{by_device} of {report['state_bytes']} bytes per device"
        )
    await _await_gone("train")
    _stage_line(
        "train", report, steps=len(losses),
        losses="/".join(f"{x:.3f}" for x in losses),
        mosaic=str(report["mosaic"]).lower(),
        mesh=json.dumps(report["mesh"], separators=(",", ":")),
        state_mb=f"{report['state_bytes'] / 2**20:.1f}",
        state_share_by_device="/".join(
            f"{b / report['state_bytes']:.2f}" for b in by_device
        ),
    )
    return report


STAGES = (
    ("dispatch", _stage_dispatch),
    ("serve", _stage_serve),
    ("train", _stage_train),
)


def _cache_entries(cache_dir: str) -> int:
    try:
        return len(os.listdir(cache_dir))
    except OSError:
        return 0


async def run(mode: str) -> dict:
    """All stages in order; returns the device every stage agreed on."""
    size = SIZES[mode]
    accept_cpu = mode == "tiny"
    cache_dir = compile_cache_dir()
    os.makedirs(cache_dir, exist_ok=True)
    entries_before = _cache_entries(cache_dir)
    workdir = tempfile.mkdtemp(prefix="chip-smoke-")
    os.environ[RUN_MARK] = workdir
    reports = {}
    try:
        for stage, body in STAGES:
            reports[stage] = await asyncio.wait_for(
                body(size, workdir, cache_dir, accept_cpu),
                timeout=size["timeouts_s"][stage],
            )
    finally:
        # Whatever a failed or timed-out stage left behind dies here.
        for pid in _started_here():
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
        shutil.rmtree(workdir, ignore_errors=True)

    devices = {
        (r["platform"], r["device_kind"], r["devices"])
        for r in reports.values()
    }
    if len(devices) != 1:
        raise SmokeFailure(f"stages disagree on the device: {sorted(devices)}")
    entries = _cache_entries(cache_dir)
    if not entries:
        raise SmokeFailure(f"compile cache {cache_dir} is empty after the run")
    if harness.live_backend():
        raise SmokeFailure(
            "the parent initialised a jax backend; it must leave the chip "
            "to its workers"
        )

    compile_s = sum(r["compile_s"] for r in reports.values())
    last_path = os.path.join(REPO, ".cache", f"chip_smoke_{mode}_last.json")
    previous = ""
    try:
        with open(last_path, encoding="utf-8") as f:
            last = json.load(f)
        if last.get("cache_dir") == cache_dir:
            previous = (
                f" previous_run_compile_s={last['compile_s']:.2f}"
                f" previous_run_cache={last['cache']}"
            )
    except (OSError, ValueError, KeyError):
        pass
    cache_state = "warm" if entries_before else "cold"
    print(
        f"compile_cache dir={cache_dir} cache={cache_state} "
        f"entries_before={entries_before} entries_after={entries} "
        f"compile_s={compile_s:.2f}{previous}",
        flush=True,
    )
    os.makedirs(os.path.dirname(last_path), exist_ok=True)
    with open(last_path, "w", encoding="utf-8") as f:
        json.dump({"cache_dir": cache_dir, "compile_s": compile_s,
                   "cache": cache_state}, f)
    platform, kind, count = devices.pop()
    return {"platform": platform, "kind": kind, "count": count}


def main(argv: list | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--tiny", action="store_true",
        help="toy sizes, and the only mode that accepts a CPU device "
             "(rehearsal: JAX_PLATFORMS=cpu python chip_smoke.py --tiny)",
    )
    args = parser.parse_args(argv)
    t0 = time.perf_counter()
    try:
        device = asyncio.run(run("tiny" if args.tiny else "full"))
    except SmokeFailure as failure:
        print(f"chip_smoke FAILED: {failure}", file=sys.stderr, flush=True)
        return 1
    except asyncio.TimeoutError:
        print("chip_smoke FAILED: a stage exceeded its time limit",
              file=sys.stderr, flush=True)
        return 1
    print(f"chip_smoke passed in {time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
