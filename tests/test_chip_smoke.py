"""``chip_smoke.py``: the command the driver runs on the chip, rehearsed here.

On the CPU only ``--tiny`` may pass; the full mode must refuse a CPU and say
what it found.  Also the two rules the smoke rests on: one placeable compile
cache, and an ``on_tpu()`` that lets a failed backend init be seen.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def run_smoke(*args, cache_dir=None, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # One CPU device: the virtual 8-device mesh conftest sets up for the
    # in-process tests only slows the rehearsal down.
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    return subprocess.run(
        [sys.executable, SMOKE, *args], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=timeout,
    )


def test_tiny_runs_every_stage_on_a_cpu(tmp_path):
    cache = tmp_path / "placed-by-the-caller"
    proc = run_smoke("--tiny", cache_dir=cache)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    stages = [ln for ln in lines if ln.startswith("stage=")]
    assert [ln.split()[0] for ln in stages] == [
        "stage=dispatch", "stage=serve", "stage=train",
    ]
    for line in stages:
        assert "platform=cpu" in line and 'device_kind="cpu"' in line
        assert "compile_s=" in line and "run_s=" in line
    assert "requests=8" in stages[1]
    # The variable was set: that directory, verbatim, holds the cache.
    assert f"dir={cache} " in proc.stdout
    assert os.listdir(cache)


def test_full_mode_refuses_a_cpu_and_names_it():
    proc = run_smoke()
    assert proc.returncode != 0
    assert "platform=cpu" in proc.stderr and "device_kind='cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_compile_cache_dir_is_placeable_and_fixed(monkeypatch):
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert chip_smoke.compile_cache_dir() == "/somewhere/else"

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(REPO, ".cache", "jax")
    assert chip_smoke.compile_cache_dir() == fixed
    assert chip_smoke.compile_cache_dir() == fixed
    other_pid = subprocess.run(
        [sys.executable, "-c",
         "import chip_smoke; print(chip_smoke.compile_cache_dir())"],
        env={k: v for k, v in os.environ.items()
             if k != "JAX_COMPILATION_CACHE_DIR"},
        cwd=REPO, capture_output=True, text=True, timeout=60,
    )
    assert other_pid.stdout.strip() == fixed, other_pid.stderr[-500:]


def test_on_tpu_lets_a_failed_backend_init_be_seen(monkeypatch):
    import jax

    from covalent_tpu_plugin.ops import attention

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        attention.on_tpu()
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        attention.default_interpret()


def test_interpret_mode_only_for_an_explicit_cpu(monkeypatch):
    import jax

    from covalent_tpu_plugin.ops import attention

    class Device:
        def __init__(self, platform):
            self.platform = platform

    assert attention.default_interpret() is True  # the test tier's CPU
    assert attention.on_tpu() is False
    monkeypatch.setattr(jax, "devices", lambda: [Device("tpu")])
    assert attention.default_interpret() is False
    assert attention.on_tpu() is True
    monkeypatch.setattr(jax, "devices", lambda: [Device("gpu")])
    with pytest.raises(RuntimeError, match="'gpu'"):
        attention.default_interpret()
