"""Test config: force an 8-device virtual CPU mesh before jax imports.

This is the TPU-native analog of the reference's Pattern-3 CPU multi-"device"
simulation (SURVEY.md §4): instead of spawning gloo processes, XLA itself
exposes N host devices via --xla_force_host_platform_device_count, so every
sharding/collective path runs exactly the SPMD code it would on a pod.
"""

import contextlib
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


@contextlib.contextmanager
def fresh_executables():
    """Within it, every executable is compiled by this process: the suite's
    persistent XLA cache (above) is off, and JAX's in-memory caches are
    cleared on entry, since an earlier test in the same worker may have
    loaded the same programs from a warm cache and ``lower().compile()``
    would hand that executable back.  They are cleared again on exit, so no
    later test is handed what was compiled here with the cache off.

    An XLA:CPU executable that came out of the persistent cache is not the
    one the compiler produced, in two ways the tests meet:

    * it serializes into an AOT-store entry that loads and then dies at its
      first dispatch ("Function iota_compare_fusion not found"), past
      verify-on-store;
    * it does not keep the order of two collectives that have no data
      dependence (gpipe's token-count all-reduce over dp beside the ring's
      collective-permute): the devices enter them in different orders and
      the run deadlocks, where the freshly compiled program never does.

    On a TPU the layers compose; here a compile is a compile."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    try:
        yield
    finally:
        jax.clear_caches()
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


@pytest.fixture
def compiled_in_this_process():
    """The test runs inside :func:`fresh_executables`."""
    with fresh_executables():
        yield
