"""Harness tests — the reference never executes ``exec.py`` in its test
suite (excluded from coverage, ``codecov.yml:1-3``); here the harness is a
real importable module, so the file protocol (``exec.py:29-46``) is tested
directly *and* via a true subprocess round-trip.
"""

import json
import os
import pickle
import subprocess
import sys

import cloudpickle
import pytest

from covalent_tpu_plugin import harness
from covalent_tpu_plugin.utils.serialize import dump_task, load_result


def _stage(tmp_path, fn, args=(), kwargs=None, **spec_extra):
    function_file = tmp_path / "function.pkl"
    result_file = tmp_path / "result.pkl"
    dump_task(fn, args, kwargs or {}, function_file)
    spec = {
        "function_file": str(function_file),
        "result_file": str(result_file),
        "workdir": str(tmp_path / "workdir"),
        **spec_extra,
    }
    return spec, result_file


def test_run_task_success(tmp_path):
    spec, result_file = _stage(tmp_path, lambda a, b: a + b, (2, 3))
    assert harness.run_task(spec) == 0
    result, exception = load_result(result_file)
    assert result == 5 and exception is None


def test_run_task_transports_user_exception(tmp_path):
    def boom():
        raise ValueError("user error")

    spec, result_file = _stage(tmp_path, boom)
    assert harness.run_task(spec) == 0  # harness itself succeeds (exec.py:45-46)
    result, exception = load_result(result_file)
    assert result is None
    assert isinstance(exception, ValueError) and "user error" in str(exception)


def test_run_task_chdirs_into_workdir_and_restores(tmp_path):
    spec, result_file = _stage(tmp_path, lambda: os.getcwd())
    before = os.getcwd()
    harness.run_task(spec)
    assert os.getcwd() == before  # cwd restored (exec.py:41-42)
    result, _ = load_result(result_file)
    assert result == str(tmp_path / "workdir")
    assert (tmp_path / "workdir").is_dir()  # created on demand (exec.py:33-35)


def test_run_task_applies_env(tmp_path, monkeypatch):
    monkeypatch.delenv("CTPU_TEST_VAR", raising=False)
    spec, result_file = _stage(
        tmp_path, lambda: os.environ.get("CTPU_TEST_VAR"), env={"CTPU_TEST_VAR": "42"}
    )
    harness.run_task(spec)
    result, _ = load_result(result_file)
    assert result == "42"


def test_run_task_nonzero_process_writes_done_marker(tmp_path, monkeypatch):
    import jax

    calls = {}

    def fake_init(**kwargs):
        calls.update(kwargs)

    monkeypatch.setattr(jax.distributed, "initialize", fake_init)
    spec, result_file = _stage(
        tmp_path,
        lambda: "replicated",
        distributed={
            "coordinator_address": "w0:8476",
            "num_processes": 2,
            "process_id": 1,
        },
    )
    assert harness.run_task(spec) == 0
    # Only process 0 writes the result pickle; others drop a done marker.
    assert not result_file.exists()
    assert (tmp_path / "result.pkl.done.1").exists()
    assert calls == {
        "coordinator_address": "w0:8476",
        "num_processes": 2,
        "process_id": 1,
    }


def test_result_write_is_atomic_no_tmp_left(tmp_path):
    spec, result_file = _stage(tmp_path, lambda: 1)
    harness.run_task(spec)
    assert result_file.exists()
    assert not (tmp_path / "result.pkl.tmp").exists()


def test_to_host_materialises_jax_arrays(tmp_path):
    import jax.numpy as jnp
    import numpy as np

    out = harness._to_host({"x": jnp.ones((4,)), "y": 3})
    assert isinstance(out["x"], np.ndarray)
    assert out["y"] == 3


@pytest.mark.functional_tests
def test_harness_subprocess_roundtrip(tmp_path):
    """Full machine-boundary simulation: fresh python process runs the staged
    harness file exactly as a worker would (reference flow ssh.py:377-383)."""
    spec, result_file = _stage(tmp_path, lambda x: x * 10, (7,))
    spec_file = tmp_path / "spec.json"
    spec_file.write_text(json.dumps(spec))
    proc = subprocess.run(
        [sys.executable, harness.__file__, str(spec_file)],
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    result, exception = pickle.loads(result_file.read_bytes())
    assert result == 70 and exception is None


def test_run_task_pythonpath_env_reaches_sys_path(tmp_path):
    """task_env PYTHONPATH must affect imports inside the electron, not just
    child processes (the interpreter is already running when env applies)."""
    pkg = tmp_path / "extra_pkg"
    pkg.mkdir()
    (pkg / "task_env_probe_mod.py").write_text("VALUE = 'found-me'\n")

    def electron():
        import task_env_probe_mod

        return task_env_probe_mod.VALUE

    spec, result_file = _stage(tmp_path, electron, env={"PYTHONPATH": str(pkg)})
    assert harness.run_task(spec) == 0
    result, exception = load_result(result_file)
    assert exception is None
    assert result == "found-me"


def test_run_task_writes_profiler_trace(tmp_path):
    """profile_dir in the spec turns on jax.profiler around the electron."""

    def electron():
        import jax.numpy as jnp

        return float(jnp.ones((8, 8)).sum())

    profile_dir = tmp_path / "traces"
    spec, result_file = _stage(tmp_path, electron, profile_dir=str(profile_dir))
    assert harness.run_task(spec) == 0
    result, exception = load_result(result_file)
    assert exception is None and result == 64.0
    # jax writes plugins/profile/<ts>/*.xplane.pb under the trace dir
    assert any(profile_dir.rglob("*.xplane.pb"))


def test_platform_pin_that_cannot_take_is_the_tasks_error(tmp_path, monkeypatch):
    """This process already runs on the CPU: a spec pinning another platform
    must fail in the task's result, not run the task somewhere else."""
    import jax

    jax.devices()  # backends initialised under JAX_PLATFORMS=cpu
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")  # restored after the env write
    ran = tmp_path / "ran"
    spec, result_file = _stage(
        tmp_path, lambda: ran.write_text("x"), env={"JAX_PLATFORMS": "tpu"}
    )
    assert harness.run_task(spec) == 1
    result, exception = load_result(result_file)
    assert result is None and not ran.exists()
    assert isinstance(exception, RuntimeError)
    assert "JAX_PLATFORMS='tpu'" in str(exception)
    assert str(os.getpid()) in str(exception)
    # The same pin the process started under keeps working.
    harness._apply_spec_env({"env": {"JAX_PLATFORMS": "cpu"}})


def test_the_staged_directory_stays_out_of_what_jax_lowers(monkeypatch):
    """A worker's compile cache keys carry the file names of the frames that
    traced a kernel; the harness's own lies in a directory picked per run,
    so the worker has jax strip that directory (unless the task's env says
    what to strip)."""
    import re

    name = "JAX_HLO_SOURCE_FILE_CANONICALIZATION_REGEX"
    monkeypatch.setenv(name, "")  # so that the writes below are undone
    monkeypatch.delenv(name)
    harness._apply_spec_env({})
    staged = os.path.abspath(harness.__file__)
    assert re.sub(os.environ[name], "", staged) == os.path.basename(staged)
    assert re.sub(os.environ[name], "", "/elsewhere/model.py") == (
        "/elsewhere/model.py")
    harness._apply_spec_env({"env": {name: "^/checkout/"}})
    assert os.environ[name] == "^/checkout/"
    harness._apply_spec_env({})  # a value that is there is not replaced
    assert os.environ[name] == "^/checkout/"


def test_pool_server_refuses_to_fork_once_it_holds_a_backend(capsys):
    """A forked child of a jax-initialised process deadlocks at its first
    computation (and on a TPU the chip has one owner): ``run`` must answer
    with a permanent, classified refusal naming the holder — and not fork."""
    import jax

    jax.devices()
    assert harness.live_backend() == "cpu"
    children: dict = {}
    harness._spawn_task({"id": "op-1", "spec": "/nonexistent.json"}, children)
    event = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert children == {}
    assert event["event"] == "error" and event["id"] == "op-1"
    assert event["code"] == "backend_held" and event["permanent"] is True
    assert f"pid {os.getpid()}" in event["message"]
