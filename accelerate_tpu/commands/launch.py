"""``accelerate-tpu launch`` — validate args, pick a launcher, spawn.

Counterpart of ``/root/reference/src/accelerate/commands/launch.py``
(launch_command :1169, launcher selection :1169-1194, config-default merge
:988-1166).  The reference multiplexes over 7 launchers (torchrun elastic,
deepspeed pdsh, xmp.spawn, SSH pod fan-out, SageMaker, ...); the TPU-native
set is three:

* ``simple_launcher``    — one process on this host driving all local chips
  (the common case: SPMD replaces per-GPU process fan-out);
* ``multihost_launcher`` — N processes rendezvousing through
  ``jax.distributed`` (on one dev box this doubles as the CPU-simulation
  distributed mode, reference debug/notebook Pattern-3 analog);
* ``tpu_pod_launcher``   — ``gcloud compute tpus tpu-vm ssh --worker=all``
  fan-out that re-runs the command on every pod worker (reference
  tpu_pod_launcher launch.py:909).
"""

from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys
import time
from typing import Optional

from ..utils.launch import (
    prepare_multihost_worker_env,
    prepare_simple_launcher_cmd_env,
)

__all__ = ["launch_command", "launch_command_parser", "main"]


def launch_command_parser(subparsers: Optional[argparse._SubParsersAction] = None):
    description = "Launch a training script on TPU (or the CPU simulator)"
    if subparsers is not None:
        parser = subparsers.add_parser(
            "launch", help=description, allow_abbrev=False
        )
    else:
        parser = argparse.ArgumentParser(
            "accelerate-tpu launch", description=description, allow_abbrev=False
        )

    parser.add_argument("--config_file", default=None, help="Config YAML/JSON to use")
    # hardware / processes
    hw = parser.add_argument_group("Hardware and process topology")
    hw.add_argument("--cpu", action="store_true", help="Force the CPU backend")
    hw.add_argument(
        "--num_processes",
        type=int,
        default=None,
        help="Number of host processes (one per TPU VM worker)",
    )
    hw.add_argument(
        "--machine_rank", type=int, default=None, help="This host's process index"
    )
    hw.add_argument("--main_process_ip", default=None, help="Coordinator IP (worker 0)")
    hw.add_argument(
        "--main_process_port", type=int, default=None, help="Coordinator port"
    )
    hw.add_argument(
        "--num_virtual_devices",
        type=int,
        default=None,
        help="CPU simulation: per-process virtual XLA device count",
    )
    hw.add_argument(
        "--local_ranks",
        action="store_true",
        help="Multihost on ONE machine (CPU simulation): spawn all ranks locally",
    )
    hw.add_argument(
        "--max_restarts",
        type=int,
        default=0,
        help="Gang restarts after a worker failure (torchrun elastic-agent "
        "parity; SPMD restarts the WHOLE gang — partial restarts cannot "
        "rejoin a compiled collective program)",
    )
    hw.add_argument(
        "--monitor_interval",
        type=float,
        default=0.2,
        help="Seconds between worker liveness polls (torchrun parity)",
    )
    # mesh layout
    mesh = parser.add_argument_group("Mesh layout (SPMD parallelism axes)")
    for axis, doc in (
        ("dp", "data-parallel"),
        ("fsdp", "parameter-sharding (ZeRO/FSDP)"),
        ("tp", "tensor-parallel"),
        ("sp", "sequence-parallel (ring attention)"),
        ("ep", "expert-parallel (MoE)"),
        ("pp", "pipeline-parallel"),
    ):
        mesh.add_argument(
            f"--{axis}_size",
            type=int,
            default=None,
            help=f"{doc} mesh-axis size",
        )
    mesh.add_argument("--use_fsdp", action="store_true")
    mesh.add_argument("--fsdp_sharding_strategy", default=None)
    mesh.add_argument("--fsdp_state_dict_type", default=None)
    mesh.add_argument("--fsdp_transformer_layer_cls_to_wrap", default=None)
    mesh.add_argument("--fsdp_activation_checkpointing", action="store_true")
    mesh.add_argument("--fsdp_offload_params", action="store_true")
    # training knobs carried by env
    tr = parser.add_argument_group("Training")
    tr.add_argument(
        "--mixed_precision", default=None, choices=["no", "bf16", "fp16", "fp8"]
    )
    tr.add_argument("--gradient_accumulation_steps", type=int, default=None)
    tr.add_argument("--seed", type=int, default=None)
    tr.add_argument("--debug", action="store_true")
    # pod fan-out
    pod = parser.add_argument_group("TPU pod")
    pod.add_argument("--tpu_use_cluster", action="store_true")
    pod.add_argument("--tpu_name", default=None)
    pod.add_argument("--tpu_zone", default=None)
    # script
    parser.add_argument(
        "-m",
        "--module",
        action="store_true",
        help="Interpret training_script as a python module (python -m)",
    )
    parser.add_argument(
        "--no_python",
        action="store_true",
        help="Run training_script directly (it is not a python file)",
    )
    parser.add_argument("training_script", help="Script (or module) to launch")
    parser.add_argument(
        "training_script_args", nargs=argparse.REMAINDER, help="Script arguments"
    )
    if subparsers is not None:
        parser.set_defaults(func=launch_command)
    return parser


def _merge_config_defaults(args) -> None:
    """Fill unset CLI args from the config file (reference
    _validate_launch_command launch.py:988-1166: CLI > config > default)."""
    from .config import load_config_from_file
    from .config.config_args import default_config_file

    config_file = args.config_file
    if config_file is None:
        candidate = os.environ.get("ACCELERATE_CONFIG_FILE", default_config_file)
        if not os.path.isfile(candidate):
            return
        config_file = candidate
    config = load_config_from_file(config_file)
    mapping = {
        "num_processes": config.num_processes,
        "machine_rank": config.machine_rank,
        "main_process_ip": config.main_process_ip,
        "main_process_port": config.main_process_port,
        "mixed_precision": config.mixed_precision,
        "gradient_accumulation_steps": config.gradient_accumulation_steps,
        "dp_size": config.dp_size or None,
        "fsdp_size": config.fsdp_size,
        "tp_size": config.tp_size,
        "sp_size": config.sp_size,
        "ep_size": config.ep_size,
        "pp_size": config.pp_size,
        "num_virtual_devices": config.num_virtual_devices or None,
        "tpu_name": config.tpu_name,
        "tpu_zone": config.tpu_zone,
    }
    for key, value in mapping.items():
        # value-typed keys: only None means "unset" — 0 is a legitimate
        # explicit value (e.g. --machine_rank 0 must beat the config file)
        if getattr(args, key, None) is None:
            setattr(args, key, value)
    if config.use_cpu:
        args.cpu = True
    if config.debug:
        args.debug = True
    if config.tpu_use_cluster:
        args.tpu_use_cluster = True
    if config.fsdp_config:
        args.use_fsdp = True
        for k, v in config.fsdp_config.items():
            attr = k if k.startswith("fsdp_") else f"fsdp_{k}"
            cur = getattr(args, attr, None)
            # store_true flags default to False; value-typed args default None
            if cur is None or (cur is False and isinstance(v, bool)):
                setattr(args, attr, v)


def _supervise(run_once, max_restarts: int, cmd, what: str) -> None:
    """Elastic gang supervision (torchrun-agent parity): re-run ``run_once``
    after failures, up to ``max_restarts`` times, with exponential backoff so
    an import-time crash cannot burn every restart in milliseconds.
    Startup-time RuntimeErrors (e.g. a coordinator port still draining from
    the killed gang) count as retryable failures, not aborts."""
    restarts_left = max(0, max_restarts or 0)
    attempt = 0
    while True:
        failure: object
        try:
            rc = run_once()
            if rc == 0:
                return
            failure = rc
        except RuntimeError as exc:
            failure = exc
        if restarts_left <= 0:
            if isinstance(failure, BaseException):
                raise failure
            raise subprocess.CalledProcessError(failure, cmd)
        restarts_left -= 1
        attempt += 1
        delay = min(5.0, 0.5 * (2 ** (attempt - 1)))
        print(
            f"[accelerate-tpu launch] {what} failed ({failure}); restarting "
            f"in {delay:.1f}s ({restarts_left} restart(s) left)",
            file=sys.stderr,
        )
        time.sleep(delay)


def _is_multi_machine(args) -> bool:
    return bool(
        (getattr(args, "num_machines", None) or 1) > 1
        or getattr(args, "main_process_ip", None)
        not in (None, "", "127.0.0.1", "localhost")
    )


def simple_launcher(args) -> None:
    """Single process on this host (reference simple_launcher launch.py:773),
    re-launched up to ``--max_restarts`` times on failure.

    Restarts apply only to SINGLE-machine jobs: one member of a multi-host
    ``jax.distributed`` gang cannot rejoin a coordinator that still holds
    its dead slot, so a host-local restart would hang — pod-level gang
    restarts live in tpu_pod_launcher (the whole SSH fan-out reruns)."""
    cmd, env = prepare_simple_launcher_cmd_env(args)
    max_restarts = getattr(args, "max_restarts", 0) or 0
    if max_restarts and _is_multi_machine(args):
        print(
            "[accelerate-tpu launch] --max_restarts ignored for a multi-host "
            "member: a lone restarted worker cannot rejoin the gang (use the "
            "pod launcher's gang restart)",
            file=sys.stderr,
        )
        max_restarts = 0

    def run_once() -> int:
        process = subprocess.Popen(cmd, env=env)
        process.wait()
        return process.returncode

    _supervise(run_once, max_restarts, cmd, "worker")


def _wait_port_free(port: int, host: str = "127.0.0.1") -> None:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        try:
            s.bind((host, port))
        except OSError as e:
            raise RuntimeError(
                f"coordinator port {port} is busy; pass --main_process_port"
            ) from e


def multihost_launcher(args) -> None:
    """Spawn all ranks on THIS machine, rendezvoused via jax.distributed.

    This is the CPU-simulation distributed mode (reference debug_launcher
    Pattern 3, launchers.py:268): genuine multi-process collectives with no
    accelerator attached.  On a real pod each worker runs its own single
    process instead (see tpu_pod_launcher).
    """
    num_processes = args.num_processes
    port = args.main_process_port or 29500
    coordinator = f"127.0.0.1:{port}"
    platforms = os.environ.get("JAX_PLATFORMS", "cpu")
    if platforms != "cpu" and not args.cpu:
        raise ValueError(
            f"--num_processes {num_processes} on one machine is the CPU "
            f"simulation, but JAX_PLATFORMS={platforms!r} sends the workers "
            "to an accelerator.  A chip belongs to one process at a time: on "
            "a TPU host ONE process drives all local chips (launch without "
            "--num_processes; shard with --dp_size/--fsdp_size/...).  For "
            "the simulation pass --cpu or set JAX_PLATFORMS=cpu."
        )

    cmd = []
    if args.module:
        cmd.extend([sys.executable, "-m"])
    elif not args.no_python:
        cmd.append(sys.executable)
    cmd.append(args.training_script)
    cmd.extend(args.training_script_args or [])

    interval = getattr(args, "monitor_interval", None)
    # 0 is a legitimate explicit value (tightest poll) — clamp, don't default
    interval = 0.2 if interval is None else max(0.01, interval)
    restarts_left = max(0, getattr(args, "max_restarts", 0) or 0)

    def run_gang() -> int:
        """Spawn the full rank gang; 0 on success, else the first bad rc.
        Any failure kills the remaining ranks — a compiled SPMD program
        cannot make progress (or be rejoined) with a member missing, so
        gang-restart is the only sound elastic unit."""
        _wait_port_free(port)
        processes = []
        for rank in range(num_processes):
            env = prepare_multihost_worker_env(args, rank, num_processes, coordinator)
            env["JAX_PLATFORMS"] = "cpu"
            processes.append(subprocess.Popen(cmd, env=env))
        try:
            while processes:
                time.sleep(interval)
                for p in list(processes):
                    rc = p.poll()
                    if rc is None:
                        continue
                    processes.remove(p)
                    if rc != 0:
                        return rc
            return 0
        finally:
            for p in processes:
                p.terminate()
            for p in processes:
                p.wait()

    _supervise(run_gang, restarts_left, cmd, "gang")


def tpu_pod_launcher(args) -> None:
    """SSH fan-out over all pod workers (reference tpu_pod_launcher
    launch.py:909): each worker re-runs ``accelerate-tpu launch`` locally with
    its own machine_rank discovered from TPU metadata."""
    if not args.tpu_name:
        raise ValueError("--tpu_use_cluster requires --tpu_name (and --tpu_zone)")
    inner = ["accelerate-tpu", "launch"]
    for flag in ("mixed_precision", "gradient_accumulation_steps", "seed"):
        value = getattr(args, flag, None)
        if value is not None:
            inner += [f"--{flag}", str(value)]
    for axis in ("dp", "fsdp", "tp", "sp", "ep", "pp"):
        value = getattr(args, f"{axis}_size", None)
        if value and value > 1:
            inner += [f"--{axis}_size", str(value)]
    inner.append(args.training_script)
    inner += args.training_script_args or []
    command = " ".join(inner)
    gcloud_cmd = [
        "gcloud",
        "compute",
        "tpus",
        "tpu-vm",
        "ssh",
        args.tpu_name,
        "--worker=all",
        f"--command={command}",
    ]
    if args.tpu_zone:
        gcloud_cmd.insert(5, f"--zone={args.tpu_zone}")
    print(f"Running: {' '.join(gcloud_cmd)}")
    # gang restart = rerun the WHOLE fan-out: every worker restarts together
    # so the jax.distributed coordinator comes up fresh.  --max_restarts is
    # deliberately NOT forwarded to the inner per-worker launches — a lone
    # worker restarting inside a live gang could never rejoin (see
    # simple_launcher).

    def run_once() -> int:
        return subprocess.run(gcloud_cmd).returncode

    _supervise(
        run_once, getattr(args, "max_restarts", 0) or 0, gcloud_cmd, "pod gang"
    )


def launch_command(args) -> None:
    _merge_config_defaults(args)
    if getattr(args, "tpu_use_cluster", False):
        tpu_pod_launcher(args)
    elif (
        args.num_processes
        and args.num_processes > 1
        and (args.local_ranks or args.cpu or not args.main_process_ip)
    ):
        multihost_launcher(args)
    else:
        simple_launcher(args)


def main():
    args = launch_command_parser().parse_args()
    launch_command(args)


if __name__ == "__main__":
    main()
