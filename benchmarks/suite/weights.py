"""Weights from ``--seed``: one rule, read by the program's side and by the
plain reference alike, so neither takes anything the other has made.

A leaf is named (``layer_3.q``), shaped by the architecture's ``leaf_specs``
from the configuration's sizes, and drawn ``normal(0, std)`` from a key
folded from the seed and the name (or all ones, or a constant).  The same
name and seed give the same numbers whatever tree they are put in.
"""

from __future__ import annotations

import zlib

from benchmarks.suite import archs


def _frozen(init):
    """``init`` in a form a jit takes as a static argument: a standard
    deviation and None as they are, ``{"const": x}`` as a tuple."""
    return ("const", float(init["const"])) if isinstance(init, dict) else init


def leaf_specs(config: dict) -> list[tuple]:
    """The architecture's ``(name, shape, init)`` of every leaf, each
    ``init`` hashable (a jitted generator takes it as a static argument)."""
    return [(name, shape, _frozen(init))
            for name, shape, init in archs.load(config).leaf_specs(config)]


def parameter_count(config: dict) -> int:
    total = 0
    for _, shape, _ in leaf_specs(config):
        n = 1
        for d in shape:
            n *= d
        total += n
    return total


def seed_key(seed: int):
    """A key from any whole number up to 2**32 and beyond: the low 31 bits
    seed it, the rest is folded in."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def name_hash(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def leaf(key, name, shape, init, dtype, arch=None):
    """One leaf.  ``name`` is the leaf's name or its ``name_hash``, which
    may be traced (one compiled generator then serves every layer).
    ``init`` is a standard deviation, None (all ones) or a constant
    (``{"const": x}``, or the tuple ``leaf_specs`` makes of it).  An
    architecture (``arch``)
    that gives its own ``leaf_value`` makes the leaf itself."""
    import jax
    import jax.numpy as jnp

    own = getattr(arch, "leaf_value", None)
    if own is not None:
        return own(key, name, shape, init, dtype)
    if init is None:
        return jnp.ones(shape, dtype)
    if isinstance(init, (dict, tuple)):
        return jnp.full(shape, _frozen(init)[1], dtype)
    if isinstance(name, str):
        name = name_hash(name)
    k = jax.random.fold_in(key, name)
    return (jax.random.normal(k, shape, jnp.float32) * init).astype(dtype)
