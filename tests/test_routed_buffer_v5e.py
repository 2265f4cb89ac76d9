"""Compile-only rehearsal of the bounded row buffer (``models/moe.py``
``HeldExperts``) in ``xing4-29b.train-8k``'s step at its own size, for a
described (not attached) v5e chip: the bytes the step holds with a second
branch in every expert layer, one conditional a pass under the ``experts``
scope, both branches' products inside them, and the read-back's kernel
(``moe_readback``) three times a layer in the bounded branch alone, where no
array of one row a pair is left, and the branch's row gathers reading the
tokens from VMEM.  Costs no chip time.

The fixtures are ``tests/benchsuite/test_suite_xing4_v5e.py``'s, by import
(that file is the benchmark's; this one is the program's): the topology is
described inside a fixture, never at import.
"""

from __future__ import annotations

import json
import re

import pytest

from tests.benchsuite.test_suite_xing4_v5e import (  # noqa: F401 - a fixture
    CELL,
    _cell,
    _held,
    topo,
)

#: The step held 12,211,270,656 bytes with the gather form's read-back in
#: both branches (PERF.md §4's 12.21 GB) and 12,213,365,248 with the kernel
#: in the bounded one.  The fenced checkpoint's kept set
#: (``FENCE_KEEPS``: the gated MLPs' products, the router's choice and
#: weights, the held experts' order; 0.39 GB) takes it to 12,602,117,120.
HELD_BEFORE = 12.605e9


@pytest.fixture(scope="module")
def compiled_step(topo):
    import jax
    import jax.numpy as jnp
    import optax
    from flax import linen as nn
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import NamedSharding, PartitionSpec

    from benchmarks.suite import archs
    from covalent_tpu_plugin.models.train import TrainState, make_train_step
    from covalent_tpu_plugin.ops import attention
    from covalent_tpu_plugin.parallel import MeshPlan, make_mesh
    from covalent_tpu_plugin.parallel.sharding import DEFAULT_RULES

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
    # The kernels ask the default backend (the CPU, here) whether to run
    # interpreted; the chip this compiles for runs them through Mosaic.
    # And a described-device compile cannot be read back from the cache.
    patch = pytest.MonkeyPatch()
    patch.setattr(attention, "default_interpret", lambda: False)
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        step = make_train_step(loss_fn, mesh, shardings)
        yield config, step.lower(state, {"tokens": tokens}).compile()
    finally:
        patch.undo()
        jax.config.update("jax_enable_compilation_cache", cache)
        compilation_cache.reset_cache()


def test_the_step_with_two_branches_a_layer_holds_no_more(compiled_step):
    _, compiled = compiled_step
    held = _held(compiled)
    print(json.dumps({CELL: {"held": held}}))
    assert held <= HELD_BEFORE, held


def test_one_conditional_a_pass_under_the_experts_scope(compiled_step):
    config, compiled = compiled_step
    text = compiled.as_text()
    names = [re.search(r'op_name="([^"]*)"', line)
             for line in text.splitlines() if " conditional(" in line]
    names = [found.group(1) if found else "" for found in names]
    assert all("/experts/" in name for name in names), names
    layers = [i for i, kind in enumerate(config["layer_types"])
              if kind == "moe"]
    for i in layers:
        mine = [name for name in names if f"/layer_{i}/moe/experts/" in name]
        back = [name for name in mine if "transpose(" in name]
        # Forward; then, transposed, remat's forward and the backward rule.
        assert len(mine) - len(back) == 1, mine
        assert sum("rematted_computation" in name for name in back) == 1, mine
        assert sum("rematted_computation" not in name for name in back) == 1
    assert len(names) == 3 * len(layers)
    # The scope the per-layer metrics read is still there, inside the
    # branches too (their time is the scope's, not the unscoped rest's).
    assert re.search(r'op_name="[^"]*/loss/[^"]*/experts/', text)
    assert re.search(r'op_name="[^"]*/experts/cond/branch_1_fun/', text)


def _sparse_layers(config):
    return [i for i, kind in enumerate(config["layer_types"])
            if kind == "moe"]


def test_the_kernel_reads_back_three_times_a_layer_in_the_bounded_branch(
        compiled_step):
    from benchmarks.suite import reduce

    config, compiled = compiled_step
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and reduce._plain(line.strip()) == "moe_readback(tpu_custom_call)"]
    names = [re.search(r'op_name="([^"]*)"', line).group(1) for line in calls]
    # ``lax.cond(fits, bounded, whole)``: the bounded buffer's is branch 1.
    assert all("/experts/cond/branch_1_fun/" in name for name in names), names
    for i in _sparse_layers(config):
        mine = [name for name in names if f"/layer_{i}/moe/experts/" in name]
        back = [name for name in mine if "transpose(" in name]
        # Forward; remat's forward; and the rows' cotangents read back.
        assert len(mine) == 3 and len(back) == 2, mine
        assert sum("rematted_computation" in name for name in back) == 1, mine
    assert len(names) == 3 * len(_sparse_layers(config))


def test_no_array_of_one_row_a_pair_is_left_in_the_bounded_branch(
        compiled_step):
    config, compiled = compiled_step
    cell = _cell()
    pairs = (cell["traffic"]["batch"] * cell["traffic"]["sequence"]
             * config["num_experts_per_tok"])
    a_row_a_pair = f"[{pairs},{config['hidden_size']}]"
    inside = {0: [], 1: []}
    for line in compiled.as_text().splitlines():
        found = re.search(r'/experts/cond/branch_([01])_fun/', line)
        if found and a_row_a_pair in line.split(" metadata=")[0]:
            inside[int(found.group(1))].append(line.strip()[:200])
    assert not inside[1], inside[1][:3]
    assert inside[0]  # the whole buffer's own rows: the pattern finds them


def test_the_bounded_branch_gathers_its_rows_from_vmem(compiled_step):
    """The bounded branch gathers its buffer's rows out of the layer's
    tokens, in the backward's rule and in remat's forward: from VMEM
    (memory space 1) 0.34 ms a gather on the chip, 1.08 from HBM, where a
    fenced checkpoint that keeps the router's scores beside the MLPs'
    products puts the tokens."""
    config, compiled = compiled_step
    text = compiled.as_text().splitlines()
    shapes = {}
    for line in text:
        found = re.match(r"\s+(?:ROOT )?%([\w.\-]+) = (\S+) ", line)
        if found:
            shapes[found.group(1)] = found.group(2)
    a_row = rf"bf16\[\d+,{config['hidden_size']}\]"
    gathers = []
    for line in text:
        found = re.match(rf"\s+(?:ROOT )?%[\w.\-]+ = {a_row}\S* "
                         r"fusion\(%([\w.\-]+), ", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if (found and name and "kind=kCustom" in line and re.search(
                r"/experts/cond/branch_1_fun/(jvp\(\)/)?gather$",
                name.group(1))):
            gathers.append((name.group(1), shapes[found.group(1)]))
    # Forward, backward and remat's forward, a sparse layer.
    assert len(gathers) >= 3 * len(_sparse_layers(config)), gathers
    assert all("S(1)" in shape for _, shape in gathers), [
        name for name, shape in gathers if "S(1)" not in shape]
