"""What a model counts about its own steps (``obs/modelstats.py``): read a
step late, sent home in the result file's trailer, absorbed into the
dispatcher's ``covalent_tpu_worker_moe_*`` series; and the serving engine's
refusal of the block forms it has no cache for."""

from __future__ import annotations

import json

import numpy as np
import pytest

from covalent_tpu_plugin import harness
from covalent_tpu_plugin.obs import modelstats
from covalent_tpu_plugin.obs.metrics import Registry


def test_no_routed_layer_no_totals():
    assert modelstats.totals(Registry()) == {}


def test_totals_flush_the_pending_step_and_absorb_makes_the_worker_series():
    worker, dispatcher = Registry(), Registry()
    # The second layer outgrew its bounded buffer in the first step.
    steps = [np.array([[100.0, 1.2, 0.0, 0.0], [120.0, 1.5, 0.0, 1.0]]),
             np.array([[90.0, 1.1, 0.0, 0.0], [110.0, 1.3, 0.0, 0.0]])]
    modelstats._record(steps, worker)
    totals = modelstats.totals(worker)
    assert totals["steps"] == 2
    assert totals["rows"] == {
        "held": 420.0, "dropped": 0.0, "whole_buffer": 1.0}
    assert totals["load_ratio"] == {"last": 1.3, "peak": 1.5}
    json.dumps(totals)
    modelstats.absorb_worker(totals, dispatcher)
    snap = dispatcher.snapshot()["metrics"]
    per_step = {e["labels"]["kind"]: e["value"] for e in
                snap["covalent_tpu_worker_moe_rows_per_step"]["series"]}
    assert per_step == {"held": 210.0, "dropped": 0.0, "whole_buffer": 0.5}
    ratio = {e["labels"]["stat"]: e["value"] for e in
             snap["covalent_tpu_worker_moe_load_ratio"]["series"]}
    assert ratio["peak"] == 1.5
    assert snap["covalent_tpu_worker_moe_steps_total"]["series"][0][
        "value"] == 2
    # What crossed a process boundary never raises.
    for bad in (None, {}, {"steps": "x"}, {"steps": 0, "rows": {},
                                           "load_ratio": {}}, []):
        modelstats.absorb_worker(bad, dispatcher)


def test_three_numbers_from_an_older_program_still_record_and_absorb():
    worker, dispatcher = Registry(), Registry()
    modelstats._record([np.array([[100.0, 1.2, 0.0], [120.0, 1.5, 0.0]])],
                       worker)
    totals = modelstats.totals(worker)
    assert totals["rows"] == {
        "held": 220.0, "dropped": 0.0, "whole_buffer": 0.0}
    # And what an older worker sends home has no such kind at all.
    del totals["rows"]["whole_buffer"]
    modelstats.absorb_worker(totals, dispatcher)
    per_step = {e["labels"]["kind"]: e["value"] for e in
                dispatcher.snapshot()["metrics"][
                    "covalent_tpu_worker_moe_rows_per_step"]["series"]}
    assert per_step == {"held": 220.0, "dropped": 0.0}


def test_deferred_counts_reach_the_registry_a_step_late():
    before = modelstats.totals().get("steps", 0)
    whole = modelstats.totals().get("rows", {}).get("whole_buffer", 0)
    modelstats.defer(np.array([[10.0, 1.0, 0.0, 1.0]]))
    modelstats.defer(np.array([[12.0, 1.0, 0.0, 1.0]]))
    from covalent_tpu_plugin.obs import REGISTRY

    # One step recorded, one pending; asking for the totals reads it too.
    assert REGISTRY.get(modelstats.MOE_STEPS).value == before + 1
    totals = modelstats.totals()
    assert totals["steps"] == before + 2
    assert totals["rows"]["whole_buffer"] == whole + 2


def test_the_result_trailer_carries_the_models_counts():
    modelstats.defer(np.array([[5.0, 1.0, 0.0, 0.0]]))
    trailer = json.loads(harness._trace_trailer([]).decode())
    assert trailer["model"]["steps"] >= 1
    assert set(trailer["model"]["rows"]) == {
        "held", "dropped", "whole_buffer"}


def test_masked_tokens_record_absorb_and_leave_the_routed_series_alone():
    """A block-diffusion step's counts: the tokens it masked, with the
    routed layers' table where it has routed layers, or alone."""
    worker, dispatcher = Registry(), Registry()
    modelstats._record([
        {"masked": np.float32(4100.0),
         "moe": np.array([[100.0, 1.2, 0.0, 0.0]])},
        {"masked": np.float32(4000.0)},
    ], worker)
    totals = modelstats.totals(worker)
    assert totals["steps"] == 2 and totals["tokens"] == {"masked": 8100.0}
    assert totals["rows"]["held"] == 100.0
    json.dumps(totals)
    modelstats.absorb_worker(totals, dispatcher)
    snap = dispatcher.snapshot()["metrics"]
    per_step = snap[modelstats.WORKER_DIFFUSION_TOKENS_PER_STEP]["series"]
    assert [(e["labels"], e["value"]) for e in per_step] == [
        ({"kind": "masked"}, 4050.0)]
    # A program without routed layers: the tokens alone, no rows.
    alone = Registry()
    modelstats._record([{"masked": 7.0}], alone)
    assert modelstats.totals(alone) == {
        "rows": {}, "steps": 1, "load_ratio": {}, "tokens": {"masked": 7.0}}
    # A routed program's totals carry no tokens, and absorb makes none.
    routed, home = Registry(), Registry()
    modelstats._record([np.array([[5.0, 1.0, 0.0, 0.0]])], routed)
    assert "tokens" not in modelstats.totals(routed)
    modelstats.absorb_worker(modelstats.totals(routed), home)
    assert modelstats.WORKER_DIFFUSION_TOKENS_PER_STEP not in (
        home.snapshot()["metrics"])


@pytest.mark.parametrize("field", ["latent", "routed", "streams",
                                   "diffusion_block"])
def test_the_serving_engine_refuses_the_block_forms_by_name(field):
    import jax.numpy as jnp

    from covalent_tpu_plugin.models import TransformerConfig
    from covalent_tpu_plugin.models.latent import LatentAttentionConfig
    from covalent_tpu_plugin.models.moe import RoutedExpertsConfig
    from covalent_tpu_plugin.models.serve import (
        BlockUnsupported,
        _require_plain_cache,
    )
    from covalent_tpu_plugin.models.streams import ResidualStreamsConfig

    value = {"latent": LatentAttentionConfig(8, 8, 8, 4, 8),
             "routed": RoutedExpertsConfig(8, 2, 16),
             "streams": ResidualStreamsConfig(),
             "diffusion_block": 4}[field]
    config = TransformerConfig(
        vocab_size=32, d_model=16, n_layers=1, n_heads=2, d_ff=32,
        dtype=jnp.float32, scan_layers=False, **{field: value})
    with pytest.raises(BlockUnsupported, match=field) as refused:
        _require_plain_cache(config, "ContinuousEngine")
    assert refused.value.fault_label == "serve_model_unsupported"
    assert refused.value.fault_transient is False
    _require_plain_cache(TransformerConfig(), "ContinuousEngine")
