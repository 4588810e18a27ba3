"""Environment parsing helpers.

Behavioural counterpart of ``/root/reference/src/accelerate/utils/environment.py``
(str_to_bool :41, parse_flag_from_env :69, patch_environment :326) rebuilt for a
PJRT/libtpu world: instead of CUDA_VISIBLE_DEVICES / NUMA affinity, the helpers
here surface TPU topology hints (TPU_WORKER_ID, MEGASCALE_*, JAX coordination
env vars).
"""

from __future__ import annotations

import contextlib
import os
from contextlib import contextmanager
from typing import Any


def str_to_bool(value: str) -> int:
    """Convert a truthy/falsy env string to 1/0. Raises on garbage."""
    value = value.lower().strip()
    if value in ("y", "yes", "t", "true", "on", "1"):
        return 1
    if value in ("n", "no", "f", "false", "off", "0", ""):
        return 0
    raise ValueError(f"invalid truth value {value!r}")


def get_int_from_env(env_keys, default: int) -> int:
    """Return the first env var in ``env_keys`` that is set, as an int."""
    for key in env_keys:
        val = int(os.environ.get(key, -1))
        if val >= 0:
            return val
    return default


def parse_flag_from_env(key: str, default: bool = False) -> bool:
    value = os.environ.get(key, None)
    if value is None:
        return default
    return bool(str_to_bool(value))


def parse_choice_from_env(key: str, default: str = "no") -> str:
    return os.environ.get(key, default)


def are_libraries_initialized(*library_names: str) -> list[str]:
    """Return the subset of ``library_names`` already imported in this process."""
    import sys

    return [name for name in library_names if name in sys.modules]


@contextmanager
def patch_environment(**kwargs: Any):
    """Temporarily set env vars (upper-cased keys), restoring previous values.

    Reference behaviour: /root/reference/src/accelerate/utils/environment.py:326.
    """
    existing = {}
    for key, value in kwargs.items():
        key = key.upper()
        if key in os.environ:
            existing[key] = os.environ[key]
        os.environ[key] = str(value)
    try:
        yield
    finally:
        for key in kwargs:
            key = key.upper()
            if key in existing:
                os.environ[key] = existing[key]
            else:
                os.environ.pop(key, None)


# the source checkout (or install prefix) this package lives in
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def compilation_cache_dir(preferred: str | None = None) -> str:
    """Where JAX's persistent compilation cache lives — the ONE placement
    rule (docs/aot_cache.md §compile cache placement).

    ``$JAX_COMPILATION_CACHE_DIR`` wins whenever it is set, so whoever runs
    the program (a chip tool, a CI driver, a fleet launcher) places the
    cache from outside and nothing in code moves it.  Unset, a caller's
    ``preferred`` directory (``CompilationCacheKwargs.jax_cache_dir``) is
    used, else the fixed ``<checkout>/.jax_cache`` — never a temp, pid or
    timestamp path: the directory is part of what a later process must find
    again, so a directory that moves never hits.
    """
    return (
        os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or preferred
        or os.path.join(_CHECKOUT, ".jax_cache")
    )


def enable_compilation_cache(preferred: str | None = None) -> str:
    """Arm JAX's persistent compilation cache at :func:`compilation_cache_dir`
    and return the directory.  The choice is exported to the environment so
    child processes (launch workers, smoke subprocesses) share the cache."""
    import jax

    path = compilation_cache_dir(preferred)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def get_tpu_worker_id() -> int:
    """Host/worker index within a TPU pod slice (0 on single host)."""
    return get_int_from_env(
        ["TPU_WORKER_ID", "CLOUD_TPU_TASK_ID", "JAX_PROCESS_INDEX"], 0
    )


def get_coordinator_address() -> str | None:
    """Coordinator address for jax.distributed.initialize (MASTER_ADDR analog)."""
    addr = os.environ.get("JAX_COORDINATOR_ADDRESS") or os.environ.get(
        "ACCELERATE_COORDINATOR_ADDRESS"
    )
    if addr:
        return addr
    master_addr = os.environ.get("MASTER_ADDR")
    if master_addr:
        port = os.environ.get("MASTER_PORT", "8476")
        return f"{master_addr}:{port}"
    return None


def get_num_processes_env() -> int | None:
    """Global process (host) count from the launch env protocol, if set."""
    for key in ("ACCELERATE_NUM_PROCESSES", "JAX_NUM_PROCESSES", "WORLD_SIZE"):
        if key in os.environ:
            return int(os.environ[key])
    return None


def get_process_index_env() -> int | None:
    for key in ("ACCELERATE_PROCESS_INDEX", "JAX_PROCESS_INDEX", "RANK"):
        if key in os.environ:
            return int(os.environ[key])
    return None


def get_cpu_affinity(local_process_index: int) -> None:
    """Best-effort CPU affinity pinning for the host process.

    TPU hosts do not need NUMA/GPU affinity mapping (reference:
    utils/environment.py:273); we simply leave scheduling to the OS. Kept as an
    API no-op for drop-in compatibility.
    """
    return None


@contextlib.contextmanager
def clear_environment():
    """Temporarily clear os.environ; restored on exit (reference
    environment.py:291) — even mutations made inside the block are
    discarded."""
    old = os.environ.copy()
    os.environ.clear()
    try:
        yield
    finally:
        os.environ.clear()
        os.environ.update(old)


def convert_dict_to_env_variables(current_env: dict) -> list:
    """Render an env dict as KEY=value lines, skipping entries with
    characters that would break an env file (reference environment.py:34)."""
    forbidden = [";", "\n", "<", ">", " "]
    valid = []
    for key, value in current_env.items():
        if all(c not in (key + value) for c in forbidden) and key and value:
            valid.append(f"{key}={value}\n")
    return valid
