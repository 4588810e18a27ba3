"""Test harness: skip decorators + subprocess runner + launch helpers.

Counterpart of ``/root/reference/src/accelerate/test_utils/testing.py``
(require_* decorators :146-560, subprocess exec :652-754,
DEFAULT_LAUNCH_COMMAND :105-125).  Importable by downstream libraries, like
the reference's.
"""

from __future__ import annotations

import functools
import inspect
import os
import subprocess
import sys
import unittest
from functools import partial
from typing import Callable, Optional

from ..utils.launch import launch_command_to_argv

__all__ = [
    "slow",
    "require_tpu",
    "require_non_cpu",
    "require_cpu",
    "require_multi_device",
    "require_single_device",
    "require_transformers",
    "require_torch",
    "require_multi_host",
    "require_pallas",
    "require_fp8",
    "require_datasets",
    "skip",
    "execute_subprocess",
    "run_command",
    "default_launch_command",
    "TempDirTestCase",
    "device_count",
]


def _parse_flag_from_env(key: str, default: bool = False) -> bool:
    value = os.environ.get(key, str(default))
    return value.lower() in ("1", "true", "yes", "on")


_run_slow_tests = _parse_flag_from_env("RUN_SLOW", default=False)


def are_slow_tests_enabled() -> bool:
    """True when RUN_SLOW=1 — for module-level ``pytestmark`` gates."""
    return _run_slow_tests


def slow(test_case):
    """Skip unless RUN_SLOW=1 (reference testing.py:245).

    Also tags the pytest ``slow`` marker so ``pytest -m "not slow"`` /
    ``-m slow`` select the same split the env flag gates."""
    try:
        import pytest

        test_case = pytest.mark.slow(test_case)
    except ImportError:  # harness is importable without pytest
        pass
    return unittest.skipUnless(_run_slow_tests, "test is slow")(test_case)


def skip(test_case):
    return unittest.skip("test was skipped")(test_case)


def device_count() -> int:
    import jax

    return len(jax.devices())


def _backend() -> str:
    import jax

    return jax.devices()[0].platform


def _skip_unless(predicate: Callable[[], bool], reason: str):
    """Decorator: skip the test unless ``predicate()`` holds — asked when
    the test RUNS, never when it is decorated.  Decoration happens while a
    test module is imported, and a question like ``jax.devices()`` there
    would initialise a backend (and take a chip) in every process that
    merely collects the file.  A predicate that raises counts as unmet."""

    def met() -> bool:
        try:
            return bool(predicate())
        except Exception:  # no backend attachable: the requirement is unmet
            return False

    def decorator(test_case):
        if isinstance(test_case, type):
            original = test_case.setUp

            @functools.wraps(original)
            def setUp(self, *args, **kwargs):
                if not met():
                    raise unittest.SkipTest(reason)
                return original(self, *args, **kwargs)

            test_case.setUp = setUp
            return test_case

        @functools.wraps(test_case)
        def wrapper(*args, **kwargs):
            if not met():
                raise unittest.SkipTest(reason)
            return test_case(*args, **kwargs)

        return wrapper

    return decorator


def require_tpu(test_case):
    """Skip unless a real TPU backend is attached."""
    return _skip_unless(lambda: _backend() == "tpu", "test requires TPU")(test_case)


def require_non_cpu(test_case):
    return _skip_unless(
        lambda: _backend() != "cpu", "test requires an accelerator"
    )(test_case)


def require_cpu(test_case):
    return _skip_unless(
        lambda: _backend() == "cpu", "test requires the CPU backend"
    )(test_case)


def require_multi_device(test_case):
    """Skip unless >1 device (real chips or virtual CPU devices)."""
    return _skip_unless(
        lambda: device_count() > 1, "test requires multiple devices"
    )(test_case)


def require_single_device(test_case):
    return _skip_unless(
        lambda: device_count() == 1, "test requires a single device"
    )(test_case)


def require_multi_host(test_case):
    """Skip unless the job spans >1 host process (TPU pod slice)."""

    def multi_host() -> bool:
        import jax

        return jax.process_count() > 1

    return _skip_unless(multi_host, "test requires a multi-host job")(test_case)


def require_pallas(test_case):
    """Kept for harness parity: the Pallas TPU (Mosaic) backend ships with
    the one jax this package supports, so there is nothing to skip on."""
    return test_case


def require_fp8(test_case):
    """Skip unless jnp exposes fp8 dtypes (float8_e4m3fn/e5m2)."""
    try:
        import jax.numpy as jnp

        ok = hasattr(jnp, "float8_e4m3fn") and hasattr(jnp, "float8_e5m2")
    except Exception:
        ok = False
    return unittest.skipUnless(ok, "test requires fp8 dtypes")(test_case)


def _require_importable(module_name: str):
    def decorator(test_case):
        try:
            __import__(module_name)
            ok = True
        except ImportError:
            ok = False
        return unittest.skipUnless(ok, f"test requires {module_name}")(test_case)

    return decorator


require_transformers = _require_importable("transformers")
require_torch = _require_importable("torch")
require_datasets = _require_importable("datasets")


def default_launch_command(
    num_processes: Optional[int] = None, num_virtual_devices: Optional[int] = None
) -> list[str]:
    """Reference DEFAULT_LAUNCH_COMMAND testing.py:105."""
    return [
        sys.executable,
        "-m",
        "accelerate_tpu.commands.accelerate_cli",
        "launch",
    ] + (
        ["--num_processes", str(num_processes)] if num_processes else []
    ) + (
        ["--num_virtual_devices", str(num_virtual_devices)] if num_virtual_devices else []
    )


class SubprocessCallException(Exception):
    pass


def run_command(command: list[str], return_stdout: bool = False, env=None):
    """Run a command, raising with captured output on failure
    (reference run_command testing.py:652)."""
    if env is None:
        env = os.environ.copy()
    try:
        output = subprocess.check_output(
            command, stderr=subprocess.STDOUT, env=env
        )
        if return_stdout:
            return output.decode("utf-8")
    except subprocess.CalledProcessError as e:
        raise SubprocessCallException(
            f"Command `{' '.join(str(c) for c in command)}` failed with code "
            f"{e.returncode}:\n{e.output.decode()}"
        ) from e


def execute_subprocess(cmd: list[str], env=None, timeout: int = 600) -> str:
    """Run to completion with live-captured output (reference
    execute_subprocess_async testing.py:709 — sync here: no asyncio needed
    for a blocking test step)."""
    if env is None:
        env = os.environ.copy()
    result = subprocess.run(
        cmd, env=env, timeout=timeout, capture_output=True, text=True
    )
    if result.returncode != 0:
        raise SubprocessCallException(
            f"Command `{' '.join(str(c) for c in cmd)}` failed with code "
            f"{result.returncode}\nstdout:\n{result.stdout}\nstderr:\n{result.stderr}"
        )
    return result.stdout + result.stderr


def launch_scoped_tmpdir(prefix: str) -> str:
    """A tmp path every process of THIS launch resolves identically.

    Derived from the coordinator address (set by debug_launcher/the env
    protocol, unique per launch and shared across its processes); a
    single-process run has no coordinator, so the pid keeps concurrent runs
    on one machine from racing on the same directory.
    """
    import tempfile

    tag = os.environ.get("ACCELERATE_COORDINATOR_ADDRESS") or f"pid{os.getpid()}"
    tag = tag.replace(":", "_").replace(".", "_")
    return os.path.join(tempfile.gettempdir(), f"{prefix}_{tag}")


def launch_test_script(
    script_path: str,
    script_args: Optional[list[str]] = None,
    num_virtual_devices: Optional[int] = None,
    env=None,
) -> str:
    """Launch an in-package distributed test script through the real CLI
    (reference Pattern 2, SURVEY.md §4)."""
    argv = launch_command_to_argv(
        script_path, script_args, num_virtual_devices=num_virtual_devices
    )
    return execute_subprocess(argv, env=env)


class TempDirTestCase(unittest.TestCase):
    """unittest base with a fresh temp dir per test (reference
    TempDirTestCase testing.py:578)."""

    clear_on_setup = True

    @classmethod
    def setUpClass(cls):
        import tempfile

        cls.tmpdir = tempfile.mkdtemp()

    @classmethod
    def tearDownClass(cls):
        import shutil

        shutil.rmtree(cls.tmpdir, ignore_errors=True)

    def setUp(self):
        if self.clear_on_setup:
            import pathlib
            import shutil

            for path in pathlib.Path(self.tmpdir).glob("**/*"):
                if path.is_file():
                    path.unlink()
                elif path.is_dir():
                    shutil.rmtree(path, ignore_errors=True)
