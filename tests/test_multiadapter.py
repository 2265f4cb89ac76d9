"""Multi-adapter LoRA serving: bank exactness, CAS registry, live attach.

The engine-level contract is EXACTNESS: a lane decoding under adapter X
inside the multiplexed bank must be bit-equal to a dedicated
single-adapter engine serving (base + X) alone — the bank gather is an
implementation detail, never a numeric one.  On top of that ride the
registry's wire form (pack/unpack + the content digest both sides of
the wire must agree on), the adapter-scoped prefix tree, the
quantize_then_lora refusal through a REAL ``open_session`` (PERMANENT,
one factory invocation — never a retry storm), and the live
``serve_attach`` path's fault classification.  The full control plane
(supervisor journal/replay, recovery re-attach) is covered in
``test_recovery.py``.
"""

import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from covalent_tpu_plugin.models import TransformerConfig, TransformerLM
from covalent_tpu_plugin.models import lora as lora_mod
from covalent_tpu_plugin.models.serve import (
    AdapterUnsupported,
    ContinuousEngine,
)
from covalent_tpu_plugin.resilience import FaultClass, classify_error
from covalent_tpu_plugin.serving import open_session
from covalent_tpu_plugin.serving.registry import (
    AdapterRegistry,
    adapter_content_digest,
    pack_adapter,
    unpack_adapter,
)
from covalent_tpu_plugin.serving.supervisor import ServeError

from .test_serving import make_serve_executor

CFG = TransformerConfig(
    vocab_size=64,
    d_model=32,
    n_layers=2,
    n_heads=2,
    d_ff=64,
    max_seq=64,
    dtype=jnp.float32,
    attention="reference",
    scan_layers=False,
)

#: One shared base model/params and LoRA template for the module (the
#: per-test init + trace dominates CPU wall otherwise).
_SHARED: dict = {}


def shared():
    if not _SHARED:
        model = TransformerLM(CFG)
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
        )["params"]
        _SHARED["model"], _SHARED["params"] = model, params
    return _SHARED["model"], _SHARED["params"]


def make_adapter(seed, rank=2):
    """A "fine-tuned" adapter: randomized nonzero lora_a AND lora_b
    (``add_lora``'s fresh B is zero — the identity), so the adapter
    genuinely changes the argmax."""
    model, params = shared()
    lmodel, filled = lora_mod.add_lora(model, params, rank=rank, alpha=16.0)
    mask = jax.tree_util.tree_leaves(lora_mod.lora_mask(filled))
    leaves, treedef = jax.tree_util.tree_flatten(filled)
    key = jax.random.PRNGKey(seed)
    out = []
    for leaf, m in zip(leaves, mask):
        if m:
            key, sub = jax.random.split(key)
            out.append(jax.random.normal(sub, leaf.shape, leaf.dtype) * 0.05)
        else:
            out.append(leaf)
    return lmodel, jax.tree_util.tree_unflatten(treedef, out)


def run_single(model, params, prompt, cap=8, **kw):
    engine = ContinuousEngine(
        model, params, max_batch=2, sync_steps=4,
        max_new_tokens=cap, length=48, **kw,
    )
    engine.admit("r", prompt)
    tokens: list = []
    for _ in range(200):
        for event in engine.step():
            tokens += event["tokens"]
            if event["done"]:
                engine.close()
                return tokens
    engine.close()
    return tokens


def drain(engine, streams):
    for _ in range(400):
        for event in engine.step():
            streams[event["rid"]] += event["tokens"]
        if not engine.busy:
            return streams
    raise AssertionError("engine never drained")


PROMPTS = [
    np.arange(1, 6, dtype=np.int32),
    np.arange(3, 10, dtype=np.int32),
    np.arange(2, 7, dtype=np.int32),
]


# ---------------------------------------------------------------------------
# Registry: the wire form both sides of serve_attach must agree on
# ---------------------------------------------------------------------------


def test_registry_pack_unpack_roundtrip(tmp_path):
    leaves = [
        np.arange(8, dtype=np.float32).reshape(2, 4),
        np.ones((4, 2), dtype=np.float32),
    ]
    data = pack_adapter(leaves, name="fr", rank=4, alpha=8.0)
    bundle = unpack_adapter(data)
    assert bundle["name"] == "fr"
    assert bundle["rank"] == 4 and bundle["alpha"] == 8.0
    assert bundle["digest"] == adapter_content_digest(leaves)
    for got, want in zip(bundle["leaves"], leaves):
        np.testing.assert_array_equal(got, want)


def test_registry_digest_matches_jax_side():
    """The numpy-side content digest (registry, journal, scheduler
    affinity) must be bit-identical to the jax-side one the engine
    computes (``models.lora.adapter_digest``) — a drift here would make
    every recovered adapter look stale."""
    _, tuned = make_adapter(3)
    leaves = lora_mod.adapter_leaves(tuned)
    assert adapter_content_digest(leaves) == lora_mod.adapter_digest(leaves)


def test_registry_put_get_remove(tmp_path):
    registry = AdapterRegistry(str(tmp_path))
    leaves = [np.ones((2, 3), dtype=np.float32)]
    record = registry.put("fr", leaves)
    assert record["name"] == "fr" and record["digest"]
    assert record["content"] == adapter_content_digest(leaves)
    assert "fr" in registry and registry.get("fr")["path"] == record["path"]
    # Re-registering the same leaves keeps the same CONTENT identity
    # (the file digest may differ — bundle metadata like the embedded
    # name is part of the pickled bytes, not of the semantic identity).
    again = registry.put("fr", pack_adapter(leaves))
    assert again["content"] == record["content"]
    registry.remove("fr")
    assert "fr" not in registry
    with pytest.raises(ValueError):
        registry.put("bad", object())


# ---------------------------------------------------------------------------
# Engine: multiplexed lanes bit-equal to single-adapter oracles
# ---------------------------------------------------------------------------


def test_bank_lanes_bit_equal_single_adapter_engines():
    """Base lane + two adapter lanes co-batched in ONE bank engine must
    each match the dedicated engine for that (base|adapter) alone, and
    an unknown adapter name must refuse at admission — PERMANENT, no
    lane consumed."""
    model, params = shared()
    lmodel, tuned_a = make_adapter(1)
    _, tuned_b = make_adapter(2)
    oracle_base = run_single(model, params, PROMPTS[0])
    oracle_a = run_single(lmodel, tuned_a, PROMPTS[1])
    oracle_b = run_single(lmodel, tuned_b, PROMPTS[2])

    mux = ContinuousEngine(
        model, params, max_batch=4, sync_steps=4, max_new_tokens=8,
        length=48,
        adapters={
            "a": lora_mod.adapter_leaves(tuned_a),
            "b": lora_mod.adapter_leaves(tuned_b),
        },
    )
    assert mux.adapters == ("a", "b")
    mux.admit("base", PROMPTS[0], {})
    mux.admit("a", PROMPTS[1], {"adapter": "a"})
    mux.admit("b", PROMPTS[2], {"adapter": "b"})
    streams = drain(mux, {"base": [], "a": [], "b": []})
    assert streams["base"] == oracle_base
    assert streams["a"] == oracle_a
    assert streams["b"] == oracle_b

    with pytest.raises(ValueError) as info:
        mux.admit("x", PROMPTS[0], {"adapter": "ghost"})
    fault, _ = classify_error(info.value)
    assert fault is FaultClass.PERMANENT
    assert mux.busy == 0
    mux.close()


def test_hot_swap_in_flight_old_generation_new_admissions_new():
    """Re-attaching a live name mid-decode is the zero-drop hot swap:
    the in-flight lane finishes on the OLD generation byte-equal, the
    next admission decodes the NEW one."""
    model, params = shared()
    lmodel, tuned_a = make_adapter(1)
    _, tuned_a2 = make_adapter(7)
    oracle_old = run_single(lmodel, tuned_a, PROMPTS[1])
    oracle_new = run_single(lmodel, tuned_a2, PROMPTS[1])

    mux = ContinuousEngine(
        model, params, max_batch=4, sync_steps=4, max_new_tokens=8,
        length=48, adapters={"a": lora_mod.adapter_leaves(tuned_a)},
    )
    mux.admit("old", PROMPTS[1], {"adapter": "a"})
    streams = {"old": [], "new": []}
    for _ in range(2):
        for event in mux.step():
            streams[event["rid"]] += event["tokens"]
    mux.attach_adapter("a", lora_mod.adapter_leaves(tuned_a2))
    mux.admit("new", PROMPTS[1], {"adapter": "a"})
    drain(mux, streams)
    assert streams["old"] == oracle_old
    assert streams["new"] == oracle_new
    assert mux.stats["adapter_swaps"] == 1
    mux.close()


def test_prefix_tree_scoped_by_adapter():
    """The SAME prompt under two adapters must never share a KV lane:
    the cross-adapter reuse is blocked (counted), and the blocked
    admission full-prefills byte-equal."""
    model, params = shared()
    lmodel, tuned_a = make_adapter(1)
    _, tuned_b = make_adapter(2)
    long_prompt = np.arange(1, 12, dtype=np.int32)
    oracle_b = run_single(lmodel, tuned_b, long_prompt, cap=6)

    mux = ContinuousEngine(
        model, params, max_batch=2, sync_steps=4, max_new_tokens=6,
        length=48, prefix_min_tokens=3,
        adapters={
            "a": lora_mod.adapter_leaves(tuned_a),
            "b": lora_mod.adapter_leaves(tuned_b),
        },
    )
    mux.admit("pa", long_prompt, {"adapter": "a"})
    drain(mux, {"pa": []})
    mux.admit("pb", long_prompt, {"adapter": "b"})
    streams = drain(mux, {"pb": []})
    assert mux.stats["adapter_prefix_blocked"] >= 1
    assert streams["pb"] == oracle_b
    mux.close()


def test_kv_bundle_carries_adapter_identity():
    """A disagg KV bundle prefilled under adapter X admits only into an
    engine whose X generation matches; the decoded stream equals the
    single-adapter oracle."""
    model, params = shared()
    lmodel, tuned_a = make_adapter(1)
    prompt = np.arange(4, 11, dtype=np.int32)
    oracle = run_single(lmodel, tuned_a, prompt, cap=6)

    mux = ContinuousEngine(
        model, params, max_batch=2, sync_steps=4, max_new_tokens=6,
        length=48, adapters={"a": lora_mod.adapter_leaves(tuned_a)},
    )
    bundle = mux.prefill_only(prompt, {"adapter": "a"})
    mux.admit_from_kv("kv1", bundle, {"adapter": "a"})
    streams = drain(mux, {"kv1": []})
    assert streams["kv1"] == oracle
    mux.close()


# ---------------------------------------------------------------------------
# The base engine's arms: a cheaper road streams what the plain one does
# ---------------------------------------------------------------------------


def drive(engine, prompts, cap=6, params=None):
    """Admit ``prompts`` as lanes free up and run the engine dry."""
    queue = list(enumerate(prompts))
    streams: dict = {}
    for _ in range(400):
        while queue and engine.busy < engine.slots:
            i, prompt = queue.pop(0)
            streams[f"r{i}"] = []
            engine.admit(
                f"r{i}", prompt, {"max_new_tokens": cap, **(params or {})}
            )
        for event in engine.step():
            streams[event["rid"]] += event["tokens"]
        if not queue and not engine.busy:
            return streams
    raise AssertionError("engine never drained")


def plain_engine(**kw):
    model, params = shared()
    return ContinuousEngine(
        model, params, max_batch=2, sync_steps=3, max_new_tokens=6,
        length=40, **kw,
    )


def arm_prefix_reuse():
    """A shared prefix is prefilled once: equal streams for strictly
    fewer prefill positions."""
    prefix = np.asarray([5, 9, 2, 7, 11, 3, 8, 1, 4, 6], np.int32)
    prompts = [
        np.concatenate([prefix, np.asarray(tail, np.int32)])
        for tail in ([12, 13], [20], [31, 32, 33])
    ]
    plain, reuse = plain_engine(), plain_engine(shared_prefix=prefix)
    want, got = drive(plain, prompts), drive(reuse, prompts)
    assert reuse.stats["prefix_hits"] == len(prompts)
    assert 0 < reuse.stats["prefill_positions"] < (
        plain.stats["prefill_positions"]
    )
    plain.close()
    reuse.close()
    return want, got


def arm_kv_split():
    """Prefill on one engine, decode on another from the bundle: the
    decode side pays no prefill position, and a prompt seen before rides
    the prefill side's prefix tree."""
    prompts = [np.arange(2 + i, 12 + i, dtype=np.int32) for i in range(3)]
    prompts.append(prompts[0])
    joint, prefill, decode = plain_engine(), plain_engine(), plain_engine()
    want = drive(joint, prompts)
    bundles = [
        prefill.prefill_only(p, {"max_new_tokens": 6}) for p in prompts
    ]
    got: dict = {}
    for i, bundle in enumerate(bundles):
        decode.admit_from_kv(f"r{i}", bundle, {"max_new_tokens": 6})
        got.update(drain(decode, {f"r{i}": []}))
    assert decode.stats["prefill_positions"] == 0
    assert decode.stats["kv_admits"] == len(prompts)
    assert prefill.stats["prefix_hits"] > 0
    for engine in (joint, prefill, decode):
        engine.close()
    return want, got


def arm_spec_kv_quant():
    """Speculative decode on the quantized-cache lane is not bit-equal
    to full precision by design; its contract is that a repeated greedy
    drive streams the same tokens."""
    model, params = shared()
    engine = plain_engine(
        decode_modes=("fp", "kv_quant"), draft_model=model,
        draft_params=params, draft_len=2,
    )
    assert engine._spec_refusal is None
    first = drive(engine, PROMPTS, params={"quality": "kv_quant"})
    again = drive(engine, PROMPTS, params={"quality": "kv_quant"})
    assert engine.stats["spec_proposed"] > 0
    assert engine.stats["mode_tokens_kv_quant"] > 0
    assert engine.stats["mode_refusals"] == 0
    engine.close()
    return first, again


@pytest.mark.parametrize(
    "arm", [arm_prefix_reuse, arm_kv_split, arm_spec_kv_quant],
    ids=["prefix_reuse", "kv_split", "spec_kv_quant"],
)
def test_engine_arms_stream_equal(arm):
    want, got = arm()
    assert want and got == want
    assert all(len(tokens) == 6 for tokens in got.values())


# ---------------------------------------------------------------------------
# The quantize_then_lora refusal through a REAL open_session
# ---------------------------------------------------------------------------


def make_uncomposable_factory(marker_path):
    """A factory violating the quant.py:229 composition order — the
    model already carries baked-in adapters (lora_rank on the config),
    and an adapter bank on top is refused by the REAL engine
    (``AdapterUnsupported``).  Appends to ``marker_path`` per
    invocation so the test can prove the refusal never retry-storms."""

    def factory():
        with open(marker_path, "a") as f:
            f.write("invoked\n")
        import jax as jax_mod
        import jax.numpy as jnp_mod

        from covalent_tpu_plugin.models import (
            TransformerConfig as Config,
            TransformerLM as LM,
        )
        from covalent_tpu_plugin.models.serve import (
            ContinuousEngine as Engine,
        )

        cfg = Config(
            vocab_size=32, d_model=16, n_layers=1, n_heads=2, d_ff=32,
            max_seq=32, dtype=jnp_mod.float32, attention="reference",
            scan_layers=False, lora_rank=2,
        )
        model = LM(cfg)
        params = model.init(
            jax_mod.random.PRNGKey(0), jnp_mod.zeros((1, 4), jnp_mod.int32)
        )["params"]
        return Engine(
            model, params, max_batch=2, max_new_tokens=4, length=16,
            adapter_rank=2,
        )

    return factory


@pytest.mark.slow
def test_open_session_refuses_uncomposable_adapter_stack(
    tmp_path, run_async
):
    """An engine construction that violates quantize_then_lora order
    refuses through a real ``open_session`` as PERMANENT
    (``serve_model_unsupported``) after exactly ONE factory invocation
    — a deterministic misconfiguration must never burn gang retries."""
    marker = tmp_path / "invocations.log"
    marker.write_text("")
    repo_root = str(pathlib.Path(__file__).parents[1])

    async def flow():
        # The factory imports the real package in the worker (stub
        # factories deliberately avoid this), so the worker needs the
        # repo on its path.
        ex = make_serve_executor(
            tmp_path,
            task_env={
                "PYTHONPATH": repo_root + os.pathsep
                + os.environ.get("PYTHONPATH", "")
            },
        )
        try:
            with pytest.raises(Exception) as info:
                await open_session(
                    ex, make_uncomposable_factory(str(marker))
                )
        finally:
            await ex.close()
        return info.value

    error = run_async(flow())
    fault, label = classify_error(error)
    assert fault is FaultClass.PERMANENT
    assert label == "serve_model_unsupported"
    assert marker.read_text().count("invoked") == 1


# ---------------------------------------------------------------------------
# Live serve_attach fault classification through a real session
# ---------------------------------------------------------------------------


def make_bank_stub_factory():
    """Closure-local stub with the duck-typed adapter surface: attach
    refuses geometry mismatches exactly the way the real bank does
    (``fault_label``/``fault_transient`` PERMANENT duck tags)."""

    def factory():
        class Refused(ValueError):
            fault_label = "serve_model_unsupported"
            fault_transient = False

        class Engine:
            def __init__(self):
                self.slots = 2
                self.lanes = {}
                self.book = {}

            def attach_adapter(self, name, payload):
                rank = int(payload.get("rank") or 0)
                if rank != 2:
                    raise Refused(
                        f"adapter {name!r} rank {rank} does not match "
                        "the bank template rank 2"
                    )
                self.book[name] = str(payload["digest"])
                return payload["digest"]

            def detach_adapter(self, name):
                if name not in self.book:
                    raise ValueError(f"unknown adapter {name!r}")
                del self.book[name]

            @property
            def adapter_digests(self):
                return dict(self.book)

            def admit(self, rid, prompt, params):
                cap = int((params or {}).get("max_new_tokens", 4))
                base = int(prompt[-1])
                self.lanes[rid] = [base + i + 1 for i in range(cap)]

            def step(self):
                events = []
                for rid in list(self.lanes):
                    taken, self.lanes[rid] = (
                        self.lanes[rid][:2], self.lanes[rid][2:]
                    )
                    done = not self.lanes[rid]
                    if done:
                        del self.lanes[rid]
                    events.append(
                        {"rid": rid, "tokens": taken, "done": done}
                    )
                return events

            def cancel(self, rid):
                self.lanes.pop(rid, None)

        return Engine()

    return factory


def test_live_attach_geometry_refusal_is_permanent(tmp_path, run_async):
    """A rank-mismatched bundle through the live ``serve_attach`` verb
    refuses as PERMANENT with the engine's own label; a well-formed one
    lands, shows in the handle's book, and detaches cleanly."""

    async def flow():
        ex = make_serve_executor(tmp_path)
        try:
            handle = await open_session(ex, make_bank_stub_factory())
            good = [np.zeros((4, 2), dtype=np.float32)]
            ack = await handle.attach_adapter("ok", payload=good)
            assert "ok" in handle.adapters
            with pytest.raises(ServeError) as info:
                await handle.attach_adapter(
                    "bad", payload=[np.zeros((4, 3), dtype=np.float32)]
                )
            assert "bad" not in handle.adapters
            await handle.detach_adapter("ok")
            assert "ok" not in handle.adapters
            await handle.close()
        finally:
            await ex.close()
        return ack, info.value

    ack, error = run_async(flow())
    assert ack.get("digest")
    fault, label = classify_error(error)
    assert fault is FaultClass.PERMANENT
    assert label == "serve_model_unsupported"
