"""Cross-cutting utilities: config, logging, serialization, checkpoints.

The reference keeps these as loose globals inside ``ssh.py`` (config at
``covalent_ssh_plugin/ssh.py:31,39-50``, logging at ``ssh.py:36-37``,
serialization at ``ssh.py:28``).  Here they are a proper subpackage so the
transport, executor, and harness layers share one implementation.
"""

from .checkpoint import (
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
from .config import get_config, set_config, update_config
from .log import app_log
from .serialize import dump_task, load_result

__all__ = [
    "checkpoint_dir",
    "latest_step",
    "prune_checkpoints",
    "register_snapshot",
    "reshard_tree",
    "restore_checkpoint",
    "resume_state",
    "save_checkpoint",
    "unregister_snapshot",
    "get_config",
    "set_config",
    "update_config",
    "app_log",
    "dump_task",
    "load_result",
]
