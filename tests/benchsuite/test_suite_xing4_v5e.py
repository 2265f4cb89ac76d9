"""Compile-only rehearsal of ``xing4-29b.train-8k`` at its own size for a
described (not attached) v5e chip: the bytes the step holds, the kernels'
and the scopes' names the per-layer metrics read, and the plain reference's
float32 step on the same chip.  Costs no chip time and guards every later
PR.

The topology is described inside a fixture, never at import (see
``test_suite_compile_v5e.py``, whose pattern this follows; that file is not
this PR's to edit, so these tests live beside it).
"""

from __future__ import annotations

import json
import os
import re

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "xing4-29b.train-8k"
#: ISSUE 28: over this at 8,192 tokens the cell would fall back to 4,096.
LIMIT = 15.0e9
KERNELS = ("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq")
SCOPES = ("router", "experts", "shared_expert", "hc", "latent_proj")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_cache():
    """A described-device compile is written to the persistent cache but
    cannot be read back without a chip: keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _cell() -> dict:
    from benchmarks.suite import spec

    return spec.load_cell(REPO, CELL)


def _held(compiled) -> float:
    """Bytes the program holds on the device while it runs."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_train_8k_step_fits_and_names_its_kernels_and_scopes(
        topo, no_cache, monkeypatch):
    import jax
    import jax.numpy as jnp
    import optax
    from flax import linen as nn
    from jax.sharding import NamedSharding, PartitionSpec

    from benchmarks.suite import archs, reduce
    from covalent_tpu_plugin.models.train import TrainState, make_train_step
    from covalent_tpu_plugin.ops import attention
    from covalent_tpu_plugin.parallel import MeshPlan, make_mesh
    from covalent_tpu_plugin.parallel.sharding import DEFAULT_RULES

    # The kernels ask the default backend (the CPU, here) whether to run
    # interpreted; the chip this compiles for runs them through Mosaic.
    monkeypatch.setattr(attention, "default_interpret", lambda: False)
    cell = _cell()
    config, job = cell["config"], cell["traffic"]
    mesh = make_mesh(MeshPlan(**job["mesh"]), [topo.devices[0]])
    lm, loss_fn = archs.load(config).program(config, job, mesh)
    tokens = jax.ShapeDtypeStruct(
        (job["batch"], job["sequence"] + 1), jnp.int32,
        sharding=NamedSharding(mesh, PartitionSpec()))

    def init(rng):
        variables = lm.init(
            rng, jnp.zeros((job["batch"], job["sequence"]), jnp.int32))
        return TrainState.create(
            apply_fn=lm.apply, params=variables["params"],
            tx=optax.adamw(job["learning_rate"]))

    abstract = jax.eval_shape(init, jax.random.PRNGKey(0))
    shardings = nn.logical_to_mesh_sharding(
        nn.get_partition_spec(abstract), mesh, list(DEFAULT_RULES))
    leaves, treedef = jax.tree_util.tree_flatten(abstract)
    state = jax.tree_util.tree_unflatten(treedef, [
        jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s)
        for x, s in zip(leaves, jax.tree_util.tree_leaves(shardings))])
    parameters = sum(
        x.size for x in jax.tree_util.tree_leaves(abstract.params))
    assert abs(parameters - 656.1e6) < 1e6, parameters
    step = make_train_step(loss_fn, mesh, shardings)
    compiled = step.lower(state, {"tokens": tokens}).compile()
    held = _held(compiled)
    print(json.dumps({CELL: {"held": held, "parameters": parameters}}))
    assert held < LIMIT
    text = compiled.as_text()
    calls = [reduce._plain(line.strip()) for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for kernel in KERNELS:
        assert f"{kernel}(tpu_custom_call)" in calls, sorted(set(calls))
    layers = config["num_hidden_layers"]
    assert calls.count("flash_bwd_dkdv(tpu_custom_call)") == layers
    for scope in SCOPES:
        assert re.search(rf'op_name="[^"]*/loss/[^"]*/{scope}/', text), scope
    assert re.search(r'op_name="[^"]*/loss/[^"]*layer_4/', text)
    assert re.search(r'op_name="[^"]*/optimizer/', text)


def test_reference_train_8k_step_fits_one_chip(one_chip, no_cache):
    """The plain reference follows the cell's first steps on the same chip
    once the program is gone: its float32 step has to fit too."""
    import jax
    import jax.numpy as jnp

    from benchmarks.suite import archs, reference

    cell = _cell()
    config, job = cell["config"], cell["traffic"]
    f32 = jnp.dtype("float32")
    w = {name: jax.ShapeDtypeStruct(shape, f32, sharding=one_chip)
         for name, shape, _ in archs.load(config).leaf_specs(config)}
    batch = jax.ShapeDtypeStruct(
        (job["batch"], job["sequence"] + 1), jnp.int32, sharding=one_chip)
    count = jax.ShapeDtypeStruct((), f32, sharding=one_chip)
    step = reference.make_train_step(config, job, f32, None)
    compiled = step.lower(w, w, w, batch, count).compile()
    print(json.dumps({"reference": {"held": _held(compiled)}}))
    assert _held(compiled) < 0.95 * 16e9
