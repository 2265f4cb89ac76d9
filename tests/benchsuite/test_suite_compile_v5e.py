"""Compile-only rehearsal: the programs the cells time, at their real sizes,
compiled for a described (not attached) v5e chip, and held against its
memory.  Costs no chip time and guards every later PR.

The topology is described inside a fixture, never at import: only one
process may load the TPU's library, and every xdist worker imports this
file.  All such tests live in this one file for the same reason.
"""

from __future__ import annotations

import json
import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HBM = 16e9  # benchmarks/suite/peaks.json, "TPU v5 lite"


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


def _cell(name: str) -> dict:
    from benchmarks.suite import spec

    return spec.load_cell(REPO, name)


def _held(compiled) -> float:
    """Bytes the program holds on the device while it runs."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_train_16k_step_compiles_with_mosaic_and_fits(topo, one_chip, no_cache,
                                                      monkeypatch):
    import jax
    import jax.numpy as jnp
    import optax
    from flax import linen as nn
    from jax.sharding import NamedSharding, PartitionSpec

    from benchmarks.suite import archs
    from covalent_tpu_plugin.models.train import TrainState, make_train_step
    from covalent_tpu_plugin.ops import attention
    from covalent_tpu_plugin.parallel import MeshPlan, make_mesh
    from covalent_tpu_plugin.parallel.sharding import DEFAULT_RULES

    # The kernels ask the default backend (the CPU, here) whether to run
    # interpreted; the chip this compiles for runs them through Mosaic.
    monkeypatch.setattr(attention, "default_interpret", lambda: False)
    cell = _cell("sc2-3b.train-16k")
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
    # The state's params ride in flax Partitioned boxes; the shardings tree
    # has one NamedSharding where each box is: pair them leaf by leaf.
    leaves, treedef = jax.tree_util.tree_flatten(abstract)
    state = jax.tree_util.tree_unflatten(treedef, [
        jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s)
        for x, s in zip(leaves, jax.tree_util.tree_leaves(shardings))])
    step = make_train_step(loss_fn, mesh, shardings)
    lowered = step.lower(state, {"tokens": tokens})
    assert "tpu_custom_call" in lowered.as_text()
    compiled = lowered.compile()
    print(json.dumps({"train-16k": {"held": _held(compiled)}}))
    assert _held(compiled) < 0.95 * HBM


def test_reference_train_step_fits_one_chip(one_chip, no_cache):
    """The plain reference follows the train cell's first steps on the same
    chip once the program is gone: its float32 step has to fit too."""
    import jax
    import jax.numpy as jnp

    from benchmarks.suite import archs, reference

    cell = _cell("sc2-3b.train-16k")
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
    assert _held(compiled) < 0.95 * HBM
