"""Shared test fakes.

``FakeTransport`` is the analog of the reference's hand-rolled ``_FakeConn``
(``tests/ssh_test.py:120-132``): an in-memory Transport with scripted
responses keyed by command substring, recording every call so orchestration
tests can assert the control-plane conversation.
"""

from __future__ import annotations

import sys

import cloudpickle

from covalent_tpu_plugin.transport.base import CommandResult, Transport


def pin_cpu_task_env(kwargs: dict) -> dict:
    """Merge ``JAX_PLATFORMS=cpu`` under a kwargs dict's ``task_env``.

    Harness subprocesses must run on CPU in tests whatever platform the
    invoking shell names; the spec env (and the harness's jax.config pin
    for interpreters that imported jax earlier) carries it.
    Caller-provided task_env keys win.
    """
    kwargs["task_env"] = {"JAX_PLATFORMS": "cpu", **kwargs.get("task_env", {})}
    return kwargs


def make_local_executor(tmp_path, **kwargs):
    """A TPUExecutor over the local transport, staged under tmp_path."""
    from covalent_tpu_plugin import TPUExecutor

    kwargs.setdefault("transport", "local")
    kwargs.setdefault("cache_dir", str(tmp_path / "cache"))
    kwargs.setdefault("remote_cache", str(tmp_path / "remote"))
    kwargs.setdefault("python_path", sys.executable)
    kwargs.setdefault("poll_freq", 0.2)
    kwargs.setdefault("use_agent", False)  # dedicated agent tests opt in
    return TPUExecutor(**pin_cpu_task_env(kwargs))


class FakeTransport(Transport):
    def __init__(self, responses: dict | None = None, address: str = "fake-worker"):
        self.address = address
        self.commands: list[str] = []
        self.puts: list[tuple[str, str]] = []
        self.gets: list[tuple[str, str]] = []
        self.closed = False
        #: substring -> CommandResult | callable(command) -> CommandResult
        self.responses = responses or {}
        #: what query_result's download materialises locally
        self.result_payload: tuple = (None, None)

    async def run(self, command: str, timeout: float | None = None) -> CommandResult:
        self.commands.append(command)
        for pattern, response in self.responses.items():
            if pattern in command:
                return response(command) if callable(response) else response
        return CommandResult(0, "", "")

    async def put(self, local_path: str, remote_path: str) -> None:
        self.puts.append((local_path, remote_path))

    async def get(self, remote_path: str, local_path: str) -> None:
        self.gets.append((remote_path, local_path))
        with open(local_path, "wb") as f:
            cloudpickle.dump(self.result_payload, f)

    async def close(self) -> None:
        self.closed = True


def scripted_ok_responses(
    pid: int = 12345, status: str = "READY"
) -> dict:
    """Happy-path responses for a full run(): preflight, submit, status."""
    return {
        "mkdir -p": CommandResult(0, "3\n", ""),
        "nohup": CommandResult(0, f"{pid}\n", ""),
        "if test -f": CommandResult(0, f"{status}\n", ""),
        "tail -n": CommandResult(0, "log tail\n", ""),
        "rm -f": CommandResult(0, "", ""),
    }
