"""Test config: force an 8-device virtual CPU mesh before jax imports.

This is the TPU-native analog of the reference's Pattern-3 CPU multi-"device"
simulation (SURVEY.md §4): instead of spawning gloo processes, XLA itself
exposes N host devices via --xla_force_host_platform_device_count, so every
sharding/collective path runs exactly the SPMD code it would on a pod.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persistent XLA compilation cache: the suite is compile-bound (hundreds of
# jit programs over identical tiny shapes), and the cache works on the CPU
# backend too.  Thresholds are env vars set before jax is imported; the
# directory comes from the package's one placement rule
# ($JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache), which
# also exports it so subprocess-launched scripts share the cache.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from accelerate_tpu import enable_compilation_cache  # noqa: E402

enable_compilation_cache()

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavyweight test (subprocess launches, big compiles); "
        "skipped unless RUN_SLOW=1, selectable via -m slow / -m 'not slow'",
    )
    config.addinivalue_line(
        "markers",
        "graftlint: static-analyzer tests (pure AST, no tracing); "
        "selectable via -m graftlint",
    )


@pytest.fixture(autouse=True)
def _reset_singletons():
    """Each test gets fresh Borg state (mirrors reference test hygiene)."""
    yield
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
