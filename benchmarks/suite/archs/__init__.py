"""One module per architecture, found by the configuration's ``model_type``:
``load(config)`` imports ``benchmarks/suite/archs/<model_type>.py``.  Whatever
is one architecture's (its leaves, the program's model and loss, its plain
reference, the work it needs) lives there and nowhere else; the harness, the
kinds and the readers ask the module and never name an architecture.

A PR that brings a configuration of a new architecture adds that one module
beside its data files (``configs/``, ``traffic/``, ``limits/``, ``metrics/``)
and its entries in ``BENCHMARK.json``, and edits no file that is there.

**What a module gives.**  Every function takes the configuration's dict
(the file of sizes as it is run); ``job`` is the cell's traffic file.

Weights (read by the program's side and by the reference alike):

* ``sizes(config) -> dict``: the widths the shapes are built from.
* ``leaf_specs(config) -> [(name, shape, init), ...]``: every leaf.  ``init``
  is a standard deviation (drawn ``normal(0, init)`` from the seed and the
  name), ``None`` (all ones: a norm's scale) or ``{"const": x}`` (a router's
  correction bias, a mixing gate, a decay).
* optional ``leaf_value(key, name, shape, init, dtype)``: the module's own
  rule for a leaf; ``weights.leaf`` uses it when it is there.

The system under test:

* ``program(config, job, mesh) -> (module, loss_fn)``: the program's model
  at the configuration's sizes and the loss ``make_train_step`` is handed.
  A loss with further terms (a router's balance) is the architecture's on
  both sides.
* ``leaf_name(path) -> str``: a parameter's path in the program's tree ->
  the benchmark's leaf name; ``KeyError`` on a parameter it does not know.
* serving only: ``serve_model(config, traffic) -> module``.

The plain reference (``jax.numpy``, nothing of the program):

* ``sequence_loss(w, tokens, config, dtype, positions=None) -> (sum, n)``:
  the summed next-token cross-entropy of one row of ``S + 1`` tokens and the
  count; ``positions`` keeps the first that many (the ``half_batch`` fault).
  ``reference.head_loss`` is the row-chunked head, ``reference.rms_norm``
  the norm, for a module that wants them.
* serving only: ``layer_specs(config, i)`` (one layer's leaves, in the
  order ``leaf_specs`` gives them) and ``layer(x, w, config, dtype)`` over
  ``(S, D)`` with ``w`` keyed by the leaves' short names;
  ``reference.serve_gaps`` walks embedding, the layers, ``ln_final`` and
  ``lm_head``.

The needed work (what the algorithm needs, whatever implements it):

* ``train_flops_per_token(config, job) -> float``: forward and backward, no
  recompute; ``step_mfu`` reads it.
* ``kernel_work(config, job, kernel) -> {"flops", "bytes"}``: one named
  kernel's needed work in one train step, all layers (``KeyError`` on a
  kernel the architecture does not run).
* serving only: ``matmul_parameters(config)``, ``kv_bytes_per_token(config)``.

A function a module lacks (a train-only architecture asked for a serve
cell) fails as Python's own ``AttributeError``, which names the module and
the function.
"""

from __future__ import annotations

import importlib
import re

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_\-]*$")


def load(config: dict):
    """The architecture's module for ``config["model_type"]``."""
    model_type = config.get("model_type")
    if not isinstance(model_type, str) or not _NAME.match(model_type):
        raise LookupError(
            f"the configuration's model_type is {model_type!r}: it has to "
            "name a module under benchmarks/suite/archs/")
    name = f"{__name__}.{model_type}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as err:
        if err.name != name:
            raise
        raise LookupError(
            f"no architecture {model_type!r}: add benchmarks/suite/archs/"
            f"{model_type}.py (the contract is the docstring of "
            "benchmarks/suite/archs/__init__.py)") from None
