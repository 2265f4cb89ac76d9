"""Compile-only rehearsal of ``laguna-s.train-16k`` at its own size for a
described (not attached) v5e chip: the bytes the step holds with the whole
row buffer (163,840 rows) behind every sparse layer's conditional, the flash
kernels under both attention scopes, the parameters the program builds, and
the plain reference's float32 step on the same chip.  Costs no chip time and
guards every later PR.

The fixtures are ``test_suite_xing4_v5e.py``'s, by import: the topology is
described inside a fixture, never at import.
"""

from __future__ import annotations

import json
import os
import re

from tests.benchsuite.test_suite_xing4_v5e import (  # noqa: F401 - fixtures
    _held,
    no_cache,
    one_chip,
    topo,
)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "laguna-s.train-16k"
#: ISSUE 34: ``sc2-3b.train-16k``'s step holds 14.56 GB and runs.
LIMIT = 14.5e9
#: The layers of each attention scope, of five.
SCOPES = {"attn_full": 2, "attn_sliding": 3}


def _cell() -> dict:
    from benchmarks.suite import spec

    return spec.load_cell(REPO, CELL)


def test_train_16k_step_fits_and_names_its_kernels_under_both_scopes(
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
    assert abs(parameters - 568.0e6) < 0.1e6, parameters
    step = make_train_step(loss_fn, mesh, shardings)
    compiled = step.lower(state, {"tokens": tokens}).compile()
    held = _held(compiled)
    print(json.dumps({CELL: {"held": held, "parameters": parameters}}))
    assert held <= LIMIT
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for scope, layers in SCOPES.items():
        mine = [reduce._plain(line.strip()) for line in calls
                if re.search(rf'op_name="[^"]*/attention/{scope}/', line)]
        # Forward, remat's forward, and the two backward kernels a layer.
        assert mine.count("flash_fwd(tpu_custom_call)") == 2 * layers, mine
        assert mine.count("flash_bwd_dkdv(tpu_custom_call)") == layers
        assert mine.count("flash_bwd_dq(tpu_custom_call)") == layers
    # The kernel calls alone stand under the scopes: no projection does.
    assert not re.search(r'op_name="[^"]*/attn_(full|sliding)/[^"]*_proj', text)
    assert re.search(r'op_name="[^"]*/attention/attn_gate/', text)
    # Both buffers of every sparse layer are in the step: the whole one's
    # branch is what the limit above has to hold.
    assert re.search(r'op_name="[^"]*/experts/cond/branch_1_fun/', text)
    assert re.search(r'op_name="[^"]*/loss/[^"]*layer_4/', text)
    assert re.search(r'op_name="[^"]*/optimizer/', text)


def test_reference_train_16k_step_fits_one_chip(one_chip, no_cache):
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
