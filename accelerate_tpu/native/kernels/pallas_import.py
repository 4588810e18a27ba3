"""``jax.experimental.pallas`` and its ``tpu`` half, imported once for every
serving kernel (``paged_attention.py``, ``ssm_step.py``): a serving process
imports them when its first decode program is traced — set-up time, at every
start."""

from __future__ import annotations

import sys

import jax

_GPU_INTERPRETER = "jax._src.pallas.mosaic_gpu.interpret.interpret_pallas_call"


def import_pallas():
    """``(pl, pltpu)``.

    ``jax._src.pallas.pallas_call`` imports jax's Mosaic-GPU interpreter
    whatever the backend — 0.7 of the import's 1.0-1.1 s, the LLVM and NVVM
    dialects behind it — inside a ``try … except ImportError`` of its own,
    because some builds lack it.  On a TPU nothing can ask for a GPU kernel to
    be interpreted, so there that one import is made to fail and jax takes its
    own fallback (``sys.modules[name] = None`` is Python's way to say "not
    here").  Only where Pallas has not been imported yet (a process that
    trained first keeps what it has), and the name is free again afterwards.
    PERF.md §6: ``setup_s`` by phase."""
    block = (
        jax.default_backend() == "tpu"
        and "jax._src.pallas.pallas_call" not in sys.modules
        and _GPU_INTERPRETER not in sys.modules
    )
    if block:
        sys.modules[_GPU_INTERPRETER] = None
    try:
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
    finally:
        if block:
            del sys.modules[_GPU_INTERPRETER]
    return pl, pltpu
