"""The three flash kernels keep their names into the compiled train step:
the stand-in's step (remat, flash attention, fused loss), compiled for a
described (not attached) v5e chip, holds custom calls whose HLO instruction
names are ``flash_fwd``, ``flash_bwd_dkdv`` and ``flash_bwd_dq``, which is
what the trace reducer's ``_plain`` keys a kernel's device time by.

The topology is described inside a fixture, never at import (see
``test_suite_compile_v5e.py``, whose pattern this follows).
"""

from __future__ import annotations

import os
import re

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

KERNELS = ("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq")
#: Mosaic wants lane-sized heads: the stand-in's block with head_dim 128.
CONFIG = {
    "model_type": "starcoder2", "hidden_size": 256, "intermediate_size": 512, "num_attention_heads": 2,
    "num_key_value_heads": 1, "num_hidden_layers": 2, "head_dim": 128,
    "vocab_size": 512, "sliding_window": 256, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-06, "initializer_range": 0.05,
    "weight_dtype": "float32", "activation_dtype": "bfloat16",
}
JOB = {"batch": 1, "sequence": 1024, "attention": "flash", "remat": True,
       "vocab_chunk": 128, "learning_rate": 1e-3, "mesh": {"data": 1}}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


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


def test_compiled_step_names_the_three_flash_kernels(topo, no_cache,
                                                     monkeypatch):
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
    mesh = make_mesh(MeshPlan(**JOB["mesh"]), [topo.devices[0]])
    lm, loss_fn = archs.load(CONFIG).program(CONFIG, JOB, mesh)
    tokens = jax.ShapeDtypeStruct(
        (JOB["batch"], JOB["sequence"] + 1), jnp.int32,
        sharding=NamedSharding(mesh, PartitionSpec()))

    def init(rng):
        variables = lm.init(
            rng, jnp.zeros((JOB["batch"], JOB["sequence"]), jnp.int32))
        return TrainState.create(
            apply_fn=lm.apply, params=variables["params"],
            tx=optax.adamw(JOB["learning_rate"]))

    abstract = jax.eval_shape(init, jax.random.PRNGKey(0))
    shardings = nn.logical_to_mesh_sharding(
        nn.get_partition_spec(abstract), mesh, list(DEFAULT_RULES))
    leaves, treedef = jax.tree_util.tree_flatten(abstract)
    state = jax.tree_util.tree_unflatten(treedef, [
        jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s)
        for x, s in zip(leaves, jax.tree_util.tree_leaves(shardings))])
    step = make_train_step(loss_fn, mesh, shardings)
    text = step.lower(state, {"tokens": tokens}).compile().as_text()
    calls = [
        line.strip() for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]
    # The reducer's own rule turns an instruction's line into its key.
    names = [reduce._plain(line) for line in calls]
    layers = CONFIG["num_hidden_layers"]
    assert sorted(set(names)) == sorted(
        f"{k}(tpu_custom_call)" for k in KERNELS)
    # Each backward kernel once a layer; the forward once, or twice where
    # the compiler keeps remat's second run (it may merge the two).
    assert names.count("flash_fwd(tpu_custom_call)") in (layers, 2 * layers)
    assert names.count("flash_bwd_dkdv(tpu_custom_call)") == layers
    assert names.count("flash_bwd_dq(tpu_custom_call)") == layers
    # The loss and the optimizer update are scoped for the trace too.
    assert re.search(r'op_name="[^"]*/loss/', text)
    assert re.search(r'op_name="[^"]*/optimizer/', text)
