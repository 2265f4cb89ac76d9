"""Checkpoint/resume helpers (SURVEY §5 checkpoint subsystem).

The reference persists nothing mid-task; these tests pin down the new
capability: atomic saves, latest-step discovery, restore round-trips (with
jax arrays materialised to host), and the workdir contract — an electron
re-dispatched into the same unique workdir resumes from its own state.
"""

import numpy as np
import pytest

from covalent_tpu_plugin.utils import (
    checkpoint_dir,
    latest_step,
    prune_checkpoints,
    register_snapshot,
    reshard_tree,
    restore_checkpoint,
    resume_state,
    save_checkpoint,
    unregister_snapshot,
)


def test_save_restore_roundtrip(tmp_path):
    tree = {"w": np.arange(6.0).reshape(2, 3), "step": 7, "name": "mlp"}
    save_checkpoint(tree, step=7, base=tmp_path)
    restored = restore_checkpoint(step=7, base=tmp_path)
    np.testing.assert_array_equal(restored["w"], tree["w"])
    assert restored["step"] == 7
    assert restored["name"] == "mlp"


def test_latest_step_and_default_restore(tmp_path):
    assert latest_step(tmp_path) is None
    for step in (1, 5, 3):
        save_checkpoint({"s": step}, step=step, base=tmp_path)
    assert latest_step(tmp_path) == 5
    assert restore_checkpoint(base=tmp_path)["s"] == 5


def test_restore_missing_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        restore_checkpoint(base=tmp_path)
    with pytest.raises(FileNotFoundError):
        restore_checkpoint(step=9, base=tmp_path)


def test_jax_arrays_materialise_to_host(tmp_path):
    import jax.numpy as jnp

    tree = {"p": jnp.ones((4, 4))}
    save_checkpoint(tree, step=0, base=tmp_path)
    restored = restore_checkpoint(step=0, base=tmp_path)
    np.testing.assert_array_equal(np.asarray(restored["p"]), np.ones((4, 4)))


def test_checkpoint_dir_honors_cwd_workdir_contract(tmp_path, monkeypatch):
    """Default base is <cwd>/checkpoints — the harness chdirs into the
    per-task workdir (reference exec.py:33-35), so resume is automatic."""
    monkeypatch.chdir(tmp_path)
    save_checkpoint({"x": 1}, step=2)
    assert (tmp_path / "checkpoints" / "step_2").exists()
    assert restore_checkpoint()["x"] == 1
    assert checkpoint_dir() == tmp_path / "checkpoints"


def test_format_mismatch_raises_descriptive_error(tmp_path, monkeypatch):
    """Orbax availability can differ between save and restore environments;
    a dir-vs-file mismatch must be a clear error, not IsADirectoryError
    (ADVICE r1)."""
    from covalent_tpu_plugin.utils import checkpoint as ckpt_mod

    # Simulate an orbax-written step (directory), then an orbax-less stack.
    (tmp_path / "step_1").mkdir()
    monkeypatch.setattr(ckpt_mod, "_ORBAX", False)
    with pytest.raises(RuntimeError, match="orbax"):
        restore_checkpoint(step=1, base=tmp_path)
    with pytest.raises(RuntimeError, match="orbax"):
        save_checkpoint({"x": 1}, step=1, base=tmp_path)


def test_nonzero_process_skips_write(tmp_path, monkeypatch):
    """Replicated electrons: process 0 is the single writer."""
    from covalent_tpu_plugin.utils import checkpoint as ckpt_mod

    monkeypatch.setattr(ckpt_mod, "_process_index", lambda: 1)
    target = save_checkpoint({"x": 1}, step=3, base=tmp_path)
    assert not target.exists()
    # The documented escape hatch: per-process state writes from any rank.
    target = save_checkpoint({"x": 1}, step=3, base=tmp_path / "proc1",
                             per_process=True)
    assert target.exists()


def test_keep_n_prunes_old_steps(tmp_path):
    """keep_n garbage collection: only the newest N complete steps
    survive, and interrupted saves (tmp files) are invisible to
    latest_step by construction."""
    for step in range(6):
        save_checkpoint({"s": step}, step=step, base=tmp_path, keep_n=3)
    steps = sorted(
        int(p.name.split("_")[1])
        for p in tmp_path.iterdir()
        if p.name.startswith("step_")
    )
    assert steps == [3, 4, 5]
    # A torn tmp file (killed mid-save) is never selected nor counted.
    (tmp_path / ".tmp_step_9.123.deadbeef").write_bytes(b"torn")
    assert latest_step(tmp_path) == 5
    assert prune_checkpoints(tmp_path, keep_n=1) == [4, 3]
    assert latest_step(tmp_path) == 5


def test_snapshot_registry_roundtrip():
    from covalent_tpu_plugin.utils import checkpoint as ckpt_mod

    assert ckpt_mod.take_snapshot() is None  # no hook registered
    state = {"acc": 1.5}
    register_snapshot(lambda: (dict(state), 4))
    try:
        tree, step = ckpt_mod.take_snapshot()
        assert tree == {"acc": 1.5} and step == 4
        with pytest.raises(TypeError):
            register_snapshot("not-callable")
    finally:
        unregister_snapshot()
    assert ckpt_mod.take_snapshot() is None


def test_resume_state_env_contract(tmp_path, monkeypatch):
    """resume_state: digest-verified bundle -> (step, tree); a torn
    artifact (wrong digest) returns None so the electron recomputes."""
    import hashlib

    import cloudpickle

    from covalent_tpu_plugin.utils import checkpoint as ckpt_mod

    payload = cloudpickle.dumps(
        {"v": 1, "step": 11, "tree": {"w": np.ones(3)}, "meta": {}}
    )
    bundle = tmp_path / "bundle.ckpt"
    bundle.write_bytes(payload)
    monkeypatch.delenv(ckpt_mod.RESUME_PATH_ENV, raising=False)
    assert resume_state() is None  # cold start: nothing shipped
    monkeypatch.setenv(ckpt_mod.RESUME_PATH_ENV, str(bundle))
    monkeypatch.setenv(
        ckpt_mod.RESUME_DIGEST_ENV, hashlib.sha256(payload).hexdigest()
    )
    step, tree = resume_state()
    assert step == 11
    np.testing.assert_array_equal(tree["w"], np.ones(3))
    # Torn bundle: digest mismatch -> None, never garbage state.
    bundle.write_bytes(payload[: len(payload) // 2])
    assert resume_state() is None


def test_reshard_tree_across_mesh_sizes():
    """Elastic re-meshing: state saved under a 2-device mesh restores
    bit-equal onto 1- and 4-device replacement meshes (CPU virtual mesh),
    sharded leaves included."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    devices = jax.devices()
    assert len(devices) >= 4  # conftest forces an 8-device CPU mesh
    w = np.arange(32.0).reshape(8, 4)
    mesh2 = Mesh(np.array(devices[:2]), ("data",))
    saved = {
        "w": jax.device_put(w, NamedSharding(mesh2, PartitionSpec("data"))),
        "step": 7,
    }
    from covalent_tpu_plugin.utils.checkpoint import host_tree

    host = host_tree(saved)  # what a checkpoint bundle holds
    np.testing.assert_array_equal(np.asarray(host["w"]), w)
    for n in (1, 4):
        mesh_n = Mesh(np.array(devices[:n]), ("data",))
        restored = reshard_tree(
            host, mesh_n,
            shardings={"w": PartitionSpec("data"), "step": PartitionSpec()},
        )
        assert restored["step"] == 7
        assert len(restored["w"].sharding.mesh.devices) == n
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(restored["w"])), w
        )
        # Replicated default (no shardings): same bytes, full copy per
        # device — the train-state restore path.
        replicated = reshard_tree(host, mesh_n)
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(replicated["w"])), w
        )


def test_resume_across_electron_dispatches(tmp_path, run_async):
    """End-to-end: electron 1 checkpoints, electron 2 (same unique workdir)
    resumes — the framework-level resume story."""
    import os
    import pathlib

    from .helpers import make_local_executor

    repo_root = str(pathlib.Path(__file__).resolve().parents[1])
    ex = make_local_executor(
        tmp_path,
        create_unique_workdir=True,
        remote_workdir=str(tmp_path / "wd"),
        # Workers normally have the package installed; the subprocess in this
        # test gets it via PYTHONPATH.
        task_env={
            "PYTHONPATH": repo_root + os.pathsep + os.environ.get("PYTHONPATH", "")
        },
    )

    def train_until(stop):
        from covalent_tpu_plugin.utils import (
            latest_step as latest,
            restore_checkpoint as restore,
            save_checkpoint as save,
        )

        start = (latest() + 1) if latest() is not None else 0
        state = restore()["acc"] if start else 0
        for step in range(start, stop):
            state += step
            save({"acc": state}, step=step)
        return state

    metadata = {"dispatch_id": "resume", "node_id": 0}

    async def flow():
        first = await ex.run(train_until, [3], {}, metadata)
        second = await ex.run(train_until, [6], {}, metadata)  # same workdir
        await ex.close()
        return first, second

    first, second = run_async(flow())
    assert first == 0 + 1 + 2
    assert second == first + 3 + 4 + 5  # resumed, not recomputed
