"""The system under test, as the cells drive it: everything here runs INSIDE
a worker that holds the chip.  The harness's own process only pickles these
functions (by reference: workers get the repo on ``PYTHONPATH``).

From the program the benchmark takes the entry points users call
(``open_session`` -> ``ContinuousEngine``; ``TPUExecutor.run`` ->
``make_sharded_train_state`` / ``make_train_step``), its counters, and the
names of its jitted steps.  Weights come from ``weights.leaf`` and the seed,
placed into the program's own parameter tree.  Which model that is, what its
loss is and how its parameters are named is the architecture's
(``archs.load(config)``).
"""

from __future__ import annotations

import json
import math
import os
import time

from benchmarks.suite import archs, loadgen, weights


def compile_log():
    """Record this process's backend compiles (or cache fetches) as
    ``[wall time at end, seconds]``; returns the live list."""
    import jax

    events: list = []

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            events.append([time.time(), duration])

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return events


def device_report() -> dict:
    import jax

    devices = jax.local_devices()
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(peaks),
        "cache_dir": jax.config.jax_compilation_cache_dir,
    }


class NoChipHere(RuntimeError):
    """The worker did not find the accelerator the cell asks for."""


def require_chips(chips: int | None) -> dict:
    """The device report; raises ``NoChipHere`` (before any set-up is paid)
    unless this worker holds ``chips`` TPU chips.  ``None`` asks nothing."""
    report = device_report()
    if chips is not None and (
        report["platform"] != "tpu" or report["count"] < chips
    ):
        raise NoChipHere(
            f"NO_CHIP platform={report['platform']} kind={report['kind']!r} "
            f"count={report['count']}; the cell asks for {chips} TPU chip(s)")
    return report


def place_weights(template, config: dict, seed: int, dtype=None):
    """Fill the program's parameter tree ``template`` (arrays, shapes or
    boxed either) with the seed's weights in ONE jitted call, each leaf made
    where the template's sharding puts it."""
    import jax

    arch = archs.load(config)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
    specs = {name: (shape, init) for name, shape, init in
             weights.leaf_specs(config)}
    plan, shardings = [], []
    for path, leaf in leaves:
        name = arch.leaf_name(path)
        shape, std = specs[name]
        size = 1
        for d in leaf.shape:
            size *= d
        flat = 1
        for d in shape:
            flat *= d
        if size != flat:
            raise ValueError(f"{name}: program holds {leaf.shape}, "
                             f"configuration gives {shape}")
        plan.append((name, shape, std, tuple(leaf.shape),
                     dtype or leaf.dtype))
        shardings.append(getattr(leaf, "sharding", None))

    def build(key):
        return [
            weights.leaf(key, name, shape, std, dt, arch).reshape(held)
            for name, shape, std, held, dt in plan
        ]

    placed = all(s is not None for s in shardings)
    made = jax.jit(build, out_shardings=shardings if placed else None)(
        weights.seed_key(seed)
    )
    return jax.tree_util.tree_unflatten(treedef, made)


# -- serving ---------------------------------------------------------------


def prompt_buckets(low: int, high: int, max_seq: int) -> list[int]:
    """The prefill buckets (powers of two, capped at ``max_seq``) that
    prompts of ``low..high`` tokens can land in."""
    out, b = [], 1 << (low - 1).bit_length()
    while True:
        out.append(min(b, max_seq))
        if b >= high:
            return out
        b *= 2


def engine_factory(config: dict, traffic: dict, seed: int, report_path: str,
                   control: bool = False, chips: int | None = None,
                   engine_class=None):
    """The zero-argument factory ``open_session`` ships.  In the worker it
    builds the model, the seed's weights on the device, the engine, and
    drives every program the cell's traffic can reach once (warm-up), then
    publishes a report; the session's close publishes it again with the
    peak memory and the compile log."""

    def factory():
        t_enter = time.time()
        compiles = compile_log()
        import jax
        import jax.numpy as jnp
        import numpy as np

        from covalent_tpu_plugin.models.serve import ContinuousEngine
        from covalent_tpu_plugin.parallel.sharding import unbox

        try:
            require_chips(chips)
        except NoChipHere as err:
            with open(report_path, "w", encoding="utf-8") as f:
                json.dump({"no_chip": str(err)}, f)
            raise
        t_device = time.time()
        engine_args = traffic["engine"]
        lm = archs.load(config).serve_model(config, traffic)
        template = unbox(jax.eval_shape(
            lambda: lm.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
        ))
        params = place_weights(template, config, seed,
                               jnp.dtype(config["weight_dtype"]))
        jax.block_until_ready(params)
        t_weights = time.time()
        if control:
            # The control: the program's own path one precision down (int8
            # weights and int8 K/V cache for the bfloat16 the configuration
            # states), everything else as the cell runs it.
            from covalent_tpu_plugin.models.quant import mode_variant

            lm, params = mode_variant(lm, params, "full_quant")
        report = {"t_enter": t_enter, "import_s": t_device - t_enter,
                  "weights_s": t_weights - t_device,
                  "parameters": weights.parameter_count(config)}

        def publish(final: bool) -> None:
            report.update(device_report())
            report["compiles"] = list(compiles)
            report["final"] = final
            tmp = report_path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(report, f)
            os.replace(tmp, report_path)

        # ``engine_class`` lets a test plant a fault under the timed path.
        base = (engine_class or (lambda cls: cls))(ContinuousEngine)

        class ReportingEngine(base):
            def close(self) -> None:  # the session's teardown hook
                report["stats"] = dict(self.stats)
                super().close()
                publish(final=True)

        out = traffic["output_tokens"]
        engine = ReportingEngine(
            lm, params, max_batch=engine_args["max_batch"],
            sync_steps=engine_args["sync_steps"],
            max_new_tokens=out["max"],
            prefix_cache_size=engine_args["prefix_cache_size"],
        )
        # Warm-up: one admission wave of every (bucket, wave size) the
        # traffic can produce (waves grow to ``max_batch``: nothing holds
        # requests back), each followed by one decode chunk.  Budget 1
        # finishes a request at its admission, so lanes free at once.
        prompts = traffic["prompt_tokens"]
        rng = np.random.default_rng(0)
        waves = []
        g = 1
        while g <= engine_args["max_batch"]:
            waves.append(g)
            g *= 2
        n = 0
        for bucket in prompt_buckets(prompts["min"], prompts["max"],
                                     engine_args["max_seq"]):
            size = min(bucket, prompts["max"])
            for g in waves:
                for _ in range(g):
                    n += 1
                    engine.admit(
                        f"warm-{n}",
                        rng.integers(0, config["vocab_size"], size),
                        {"max_new_tokens": 1})
                while engine.busy:
                    engine.step()
        for key in engine.stats:
            if isinstance(engine.stats[key], int):
                engine.stats[key] = 0
        report["warm_s"] = time.time() - t_weights
        report["warm_programs"] = n
        publish(final=False)
        return engine

    return factory


# -- training --------------------------------------------------------------


def _norms_by_leaf(tree, leaf_name, scale: float = 1.0) -> dict:
    import jax
    import jax.numpy as jnp

    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    norm = jax.jit(lambda x: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) * scale)
    return {leaf_name(path): float(norm(x)) for path, x in leaves}


def _delta_norms(params, config: dict, seed: int) -> dict:
    """Norm of (leaf now - the seed's leaf) for every leaf, a leaf at a
    time so that no second copy of the weights is held."""
    import jax
    import jax.numpy as jnp

    key = weights.seed_key(seed)
    arch = archs.load(config)
    specs = {name: (shape, init) for name, shape, init in
             weights.leaf_specs(config)}

    def change(key, now, name, shape, std):
        first = weights.leaf(key, name, shape, std, now.dtype, arch)
        return jnp.sqrt(jnp.sum(jnp.square(
            now.astype(jnp.float32) - first.reshape(now.shape)
            .astype(jnp.float32))))

    fn = jax.jit(change, static_argnums=(3, 4))
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = arch.leaf_name(path)
        shape, std = specs[name]
        out[name] = float(fn(key, leaf, weights.name_hash(name), shape, std))
    return out


def train_electron(config: dict, job: dict, seed: int, seconds: float,
                   trace_dir: str | None, chips: int | None = None,
                   hooks: dict | None = None) -> dict:
    """One train cell, whole, in the worker: state from the seed, the first
    ``check_steps`` steps (read for ``correct``), the rest of the warm-up,
    then the measured window on the same compiled step and state."""
    t_enter = time.time()
    compiles = compile_log()
    import jax
    import optax

    from covalent_tpu_plugin.models import (
        make_sharded_train_state,
        make_train_step,
    )
    from covalent_tpu_plugin.parallel import MeshPlan, make_mesh, shard_batch

    hooks = hooks or {}
    try:
        require_chips(chips)
    except NoChipHere as err:
        return {"no_chip": str(err)}
    plan = MeshPlan(**job["mesh"])
    # The mesh takes as many devices as the job's plan names: all of the
    # cell's chips on the chip, the first few of a CPU rehearsal's.
    mesh = make_mesh(plan, jax.local_devices()[: math.prod(job["mesh"].values())])
    arch = archs.load(config)
    lm, loss_fn = arch.program(config, job, mesh)
    pool = loadgen.train_batches(config, job, seed, job["feed_batches"])
    batches = [shard_batch({"tokens": b}, mesh) for b in pool]
    state, shardings = make_sharded_train_state(
        lm, optax.adamw(job["learning_rate"]), jax.random.PRNGKey(0),
        batches[0]["tokens"][:, :-1], mesh,
    )
    state = state.replace(params=place_weights(state.params, config, seed))
    loss_fn = hooks.get("loss_fn", lambda f: f)(loss_fn)
    step = make_train_step(loss_fn, mesh, shardings)
    step = hooks.get("step", lambda f: f)(step)
    report = {"t_enter": t_enter, "state_s": time.time() - t_enter}

    # The first steps, through the window's own call and feed.
    checks = int(job["check_steps"])
    losses = []
    for i in range(checks):
        state, metrics = step(state, batches[i % len(batches)])
        losses.append(float(metrics["loss"]))
        if i == 0:
            mu = state.opt_state[0].mu
            report["grad_norms"] = _norms_by_leaf(
                mu, arch.leaf_name, 1.0 / (1.0 - 0.9))
    report["losses"] = losses
    report["delta_norms"] = _delta_norms(state.params, config, seed)
    n = checks
    for _ in range(int(job["warm_steps"])):
        state, metrics = step(state, batches[n % len(batches)])
        n += 1
    float(metrics["loss"])
    report["check_s"] = time.time() - t_enter - report["state_s"]

    # The window.  A traced run starts the profiler after ``trace_after``
    # steps and stops it ``trace_steps`` later; the stop's own seconds
    # (it writes the trace) are taken out of the window's length.
    trace_on, traced = False, 0
    excluded = 0.0
    step_times = []
    t0_wall, t0 = time.time(), time.perf_counter()
    t_prev = t0
    while True:
        if trace_dir and not traced and not trace_on and (
            len(step_times) == job["trace_after"]
        ):
            # The device's planes are what is read: keep Python's call
            # tracer off, which would be most of the trace and of its cost.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            trace_on, t_trace = True, time.perf_counter()
            t_prev = t_trace
        state, metrics = step(state, batches[n % len(batches)])
        n += 1
        metrics["loss"].block_until_ready()
        now = time.perf_counter()
        step_times.append(now - t_prev)
        t_prev = now
        if trace_on:
            traced += 1
        done = now - t0 - excluded >= seconds
        if trace_on and (traced == job["trace_steps"] or done):
            report["trace_window_s"] = now - t_trace
            report["trace_steps"] = traced
            jax.profiler.stop_trace()
            trace_on = False
            t_prev = time.perf_counter()
            excluded += t_prev - now
        if done:
            break
    report.update({
        "window_start": t0_wall,
        "window_s": t_prev - t0 - excluded,
        "steps": len(step_times),
        "step_times": step_times,
        "tokens_per_step": int(job["batch"]) * int(job["sequence"]),
        "last_loss": float(metrics["loss"]),
        "parameters": weights.parameter_count(config),
        "compiles": list(compiles),
    })
    report.update(device_report())
    return report
