"""Launcher↔child environment-variable protocol + launch command helpers.

Counterpart of ``/root/reference/src/accelerate/utils/launch.py`` (env
serialization :98-325).  The env layer IS the IPC mechanism between the
launcher and child processes: ``accelerate-tpu launch`` serializes the
resolved config into ``ACCELERATE_*`` / ``*_SIZE`` variables, and
``PartialState``/``AcceleratorState``/plugin ``__post_init__`` re-read them in
the children (state.py / utils/dataclasses.py in this repo).

TPU inversion vs the reference: there is no per-GPU process fan-out on one
machine — SPMD means ONE process per host drives all local chips, so
``num_processes`` counts hosts, rendezvous is ``jax.distributed.initialize``
(coordinator address ≈ MASTER_ADDR), and the only multi-process-per-machine
mode is the CPU simulation used for development/testing.
"""

from __future__ import annotations

import os
import subprocess
import sys
from typing import Any, Optional

__all__ = [
    "prepare_launch_environment",
    "prepare_simple_launcher_cmd_env",
    "prepare_multihost_worker_env",
    "launch_command_to_argv",
]


def _set(env: dict, key: str, value) -> None:
    if value is None:
        return
    env[key] = str(value)


def prepare_launch_environment(args: Any) -> dict[str, str]:
    """Serialize resolved launch args into the child-process env protocol.

    Reference: prepare_multi_gpu_env utils/launch.py:195-325.  Reads
    attributes defensively (``getattr`` with None default) so both the CLI
    namespace and programmatic callers (notebook_launcher) can use it.
    """
    env: dict[str, str] = {}
    g = lambda k, d=None: getattr(args, k, d)  # noqa: E731

    _set(env, "ACCELERATE_MIXED_PRECISION", g("mixed_precision"))
    _set(env, "ACCELERATE_GRADIENT_ACCUMULATION_STEPS", g("gradient_accumulation_steps"))
    if g("cpu"):
        env["ACCELERATE_USE_CPU"] = "true"
        env["JAX_PLATFORMS"] = "cpu"
    if g("debug"):
        env["ACCELERATE_DEBUG_MODE"] = "true"
    if g("seed") is not None:
        env["ACCELERATE_SEED"] = str(g("seed"))

    # multi-host rendezvous (jax.distributed.initialize in the child)
    num_processes = g("num_processes")
    if num_processes and int(num_processes) > 1:
        env["ACCELERATE_NUM_PROCESSES"] = str(num_processes)
        ip, port = g("main_process_ip") or "127.0.0.1", g("main_process_port") or 29500
        env["ACCELERATE_COORDINATOR_ADDRESS"] = f"{ip}:{port}"
        _set(env, "ACCELERATE_PROCESS_INDEX", g("machine_rank"))

    # mesh layout — read back by ParallelismConfig.from_env / plugin
    # __post_init__ (utils/dataclasses.py)
    _set(env, "DP_SIZE", g("dp_size"))
    for axis in ("fsdp", "tp", "sp", "ep", "pp"):
        value = g(f"{axis}_size")
        if value and int(value) > 1:
            env[f"{axis.upper()}_SIZE"] = str(value)
    if g("use_fsdp"):
        env["ACCELERATE_USE_FSDP"] = "true"
        _set(env, "FSDP_SHARDING_STRATEGY", g("fsdp_sharding_strategy"))
        _set(env, "FSDP_STATE_DICT_TYPE", g("fsdp_state_dict_type"))
        _set(env, "FSDP_TRANSFORMER_CLS_TO_WRAP", g("fsdp_transformer_layer_cls_to_wrap"))
        if g("fsdp_activation_checkpointing"):
            env["FSDP_ACTIVATION_CHECKPOINTING"] = "true"
        if g("fsdp_offload_params"):
            env["FSDP_OFFLOAD_PARAMS"] = "true"
        if g("fsdp_offload_optimizer"):
            env["FSDP_OFFLOAD_OPTIMIZER"] = "true"

    # make this accelerate_tpu importable in the child even when running from
    # a source checkout (not pip-installed)
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    existing = os.environ.get("PYTHONPATH", "")
    if pkg_root not in existing.split(os.pathsep):
        env["PYTHONPATH"] = f"{pkg_root}{os.pathsep}{existing}" if existing else pkg_root

    # CPU-simulation: N virtual XLA host devices inside each process
    nvd = g("num_virtual_devices")
    if nvd and int(nvd) > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={nvd}"
            ).strip()
        env.setdefault("JAX_PLATFORMS", "cpu")
    return env


def prepare_simple_launcher_cmd_env(args: Any) -> tuple[list[str], dict[str, str]]:
    """(argv, env) for the single-process-per-host launcher.

    Reference: prepare_simple_launcher_cmd_env utils/launch.py:106-123.
    """
    cmd = []
    if getattr(args, "module", False):
        cmd.extend([sys.executable, "-m"])
    elif not getattr(args, "no_python", False):
        cmd.append(sys.executable)
    cmd.append(args.training_script)
    cmd.extend(getattr(args, "training_script_args", []) or [])

    env = os.environ.copy()
    env.update(prepare_launch_environment(args))
    return cmd, env


def prepare_multihost_worker_env(
    args: Any, process_index: int, num_processes: int, coordinator: str
) -> dict[str, str]:
    """Per-worker env for the local multi-process (CPU simulation) launcher."""
    env = os.environ.copy()
    env.update(prepare_launch_environment(args))
    env["ACCELERATE_NUM_PROCESSES"] = str(num_processes)
    env["ACCELERATE_PROCESS_INDEX"] = str(process_index)
    env["ACCELERATE_LOCAL_PROCESS_INDEX"] = str(process_index)
    env["ACCELERATE_COORDINATOR_ADDRESS"] = coordinator
    return env


def launch_command_to_argv(
    script: str,
    script_args: Optional[list[str]] = None,
    num_processes: Optional[int] = None,
    num_virtual_devices: Optional[int] = None,
    extra: Optional[list[str]] = None,
) -> list[str]:
    """Build an ``accelerate-tpu launch`` argv (test-harness helper;
    reference DEFAULT_LAUNCH_COMMAND test_utils/testing.py:105-125)."""
    argv = [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli", "launch"]
    if num_processes:
        argv += ["--num_processes", str(num_processes)]
    if num_virtual_devices:
        argv += ["--num_virtual_devices", str(num_virtual_devices)]
    if extra:
        argv += list(extra)
    argv.append(script)
    argv += list(script_args or [])
    return argv


def run_subprocess(cmd: list[str], env: Optional[dict] = None) -> int:
    """Run a child to completion, streaming output (simple_launcher body)."""
    process = subprocess.Popen(cmd, env=env)
    process.wait()
    return process.returncode
