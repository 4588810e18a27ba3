"""Programmatic launchers: ``notebook_launcher`` + ``debug_launcher``.

Counterpart of ``/root/reference/src/accelerate/launchers.py`` (:40 notebook,
:268 debug).  The reference forks N torch.multiprocessing workers per GPU; on
TPU one SPMD process already drives every local chip, so ``notebook_launcher``
is mostly a guard-railed direct call — multi-worker spawning only exists for
(a) multi-host pods (where each host runs its own notebook anyway) and (b)
the CPU-simulation debug mode, which spawns real OS processes rendezvousing
through ``jax.distributed`` so collective semantics are genuinely exercised
(reference Pattern 3, SURVEY.md §4).
"""

from __future__ import annotations

import os
import pickle
import subprocess
import sys
import tempfile
import textwrap
from typing import Any, Callable, Optional

from .state import PartialState
from .utils.environment import patch_environment

__all__ = ["notebook_launcher", "debug_launcher"]


def notebook_launcher(
    function: Callable,
    args: tuple = (),
    num_processes: Optional[int] = None,
    mixed_precision: str = "no",
    use_port: str = "29500",
    node_rank: int = 0,
    num_nodes: int = 1,
) -> Any:
    """Launch ``function(*args)`` for (notebook) training.

    Reference: notebook_launcher launchers.py:40.  TPU inversion: no per-chip
    fan-out is needed — ``function`` runs once in this process and pjit drives
    all chips.  ``num_processes`` > 1 without TPU hardware falls back to the
    debug (CPU multi-process) path.
    """
    if PartialState._shared_state:
        raise ValueError(
            "An Accelerator/PartialState was already created in this notebook. "
            "Restart the kernel and create it only inside the launched function."
        )
    with patch_environment(ACCELERATE_MIXED_PRECISION=mixed_precision):
        try:
            import jax

            local = jax.local_devices()
            backend = local[0].platform
            n_chips = len(local)
        except Exception:
            backend, n_chips = "cpu", 0
        if backend == "cpu" and num_processes and num_processes > 1:
            return debug_launcher(function, args, num_processes, use_port=use_port)
        print(f"Launching training on {backend} ({n_chips} chips).")
        return function(*args)


_WORKER_TEMPLATE = """\
import os, pickle, sys
os.environ.update({env!r})
with open({payload!r}, "rb") as f:
    function, args = pickle.load(f)
function(*args)
"""


def _free_port() -> str:
    import socket

    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return str(s.getsockname()[1])


def debug_launcher(
    function: Callable,
    args: tuple = (),
    num_processes: int = 2,
    use_port: Optional[str] = None,
    timeout: int = 300,
) -> None:
    """Run ``function`` on N CPU processes with real collective rendezvous.

    Reference: debug_launcher launchers.py:268 (gloo CPU fork).  Spawns fresh
    interpreters (never forks — the JAX backend may already be initialised
    here) that join a jax.distributed coordinator on localhost.  ``function``
    and ``args`` must be picklable (module-level function, as in the
    reference).
    """
    if use_port is None:
        use_port = _free_port()  # fixed ports collide across test runs
    with tempfile.TemporaryDirectory() as td:
        payload = os.path.join(td, "fn.pkl")
        with open(payload, "wb") as f:
            pickle.dump((function, args), f)
        workers = []
        # the worker must be able to unpickle `function`, whose module may
        # only be importable through the parent's sys.path (e.g. a test file)
        pythonpath = os.pathsep.join(
            [p for p in sys.path if p] + [os.environ.get("PYTHONPATH", "")]
        ).strip(os.pathsep)
        for rank in range(num_processes):
            env = {
                "PYTHONPATH": pythonpath,
                "JAX_PLATFORMS": "cpu",
                "ACCELERATE_NUM_PROCESSES": str(num_processes),
                "ACCELERATE_PROCESS_INDEX": str(rank),
                "ACCELERATE_LOCAL_PROCESS_INDEX": str(rank),
                "ACCELERATE_COORDINATOR_ADDRESS": f"127.0.0.1:{use_port}",
            }
            code = _WORKER_TEMPLATE.format(env=env, payload=payload)
            # JAX_PLATFORMS=cpu above is what keeps every worker off the
            # chip (it belongs to one process at a time): these are CPU
            # processes by construction
            full_env = os.environ.copy()
            full_env.update(env)
            workers.append(
                subprocess.Popen([sys.executable, "-c", code], env=full_env)
            )
        try:
            rcs = [w.wait(timeout=timeout) for w in workers]
        except subprocess.TimeoutExpired:
            for w in workers:
                w.kill()
            raise RuntimeError(
                f"debug_launcher workers did not finish within {timeout}s "
                "(rendezvous deadlock?)"
            )
        for rank, rc in enumerate(rcs):
            if rc != 0:
                raise RuntimeError(
                    f"debug_launcher worker {rank} exited with code {rc}"
                )
