"""Compile-only rehearsal of ``sdar-30b.train-8k`` at its own size for a
described (not attached) v5e chip: the bytes the step holds (16,384
positions through six layers, the whole row buffer behind every layer's
conditional), the block-diffusion kernels and the noise under their scopes,
and the parameters the program builds.  Costs no chip time and guards every
later PR that changes the program.  The plain reference's float32 step is
not compiled here: no such PR can change it, every run on the chip runs it
at this size, and its compile for the described chip takes minutes of
several cores (it holds 13.19 GB there).

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
    topo,
)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "sdar-30b.train-8k"
#: ``sc2-3b.train-16k``'s step holds 14.56 GB and runs on the chip.
LIMIT = 14.5e9
LAYERS = 6


def _cell() -> dict:
    from benchmarks.suite import spec

    return spec.load_cell(REPO, CELL)


def test_train_8k_step_fits_and_names_its_kernels_and_the_noise(
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
    assert parameters == 645_623_296, parameters
    step = make_train_step(loss_fn, mesh, shardings)
    compiled = step.lower(state, {"tokens": tokens}).compile()
    held = _held(compiled)
    print(json.dumps({CELL: {"held": held, "parameters": parameters}}))
    assert held <= LIMIT
    text = compiled.as_text()
    calls = [reduce._plain(line.strip()) for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and re.search(r'op_name="[^"]*/attention/attn_bd/', line)]
    # The three kernels stand under the scope, a layer's backward pair
    # once; the forward runs once or, where remat runs it again, twice.
    assert calls.count("flash_bwd_dkdv(tpu_custom_call)") == LAYERS, calls
    assert calls.count("flash_bwd_dq(tpu_custom_call)") == LAYERS, calls
    assert calls.count("flash_fwd(tpu_custom_call)") >= LAYERS, calls
    # The kernel calls alone stand under the scope: no projection does.
    assert not re.search(r'op_name="[^"]*/attn_bd/[^"]*_proj', text)
    # The noise is drawn inside the step, under its scope (at the loss's
    # top level, so a transform's name wraps it: ``jvp(bd_noise)``).
    assert re.search(r'op_name="[^"]*/loss/jvp\(bd_noise\)/', text)
    assert re.search(r'op_name="[^"]*/experts/cond/branch_1_fun/', text)
    assert re.search(r'op_name="[^"]*/optimizer/', text)

