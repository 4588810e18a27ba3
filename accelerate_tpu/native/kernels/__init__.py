"""Pallas hot-path kernels (docs/kernels.md).

Two training paths leave device time on the table because XLA will not fuse
across a collective on its own; they sit behind one :class:`KernelPolicy`:

* **collective-matmul** (``collective_matmul.py``) — the ZeRO-1 all-gather
  expressed as a chunked ring (``shard_map`` + per-hop transport: RDMA
  semaphores on TPU, ``ppermute`` off-TPU) so partial matmuls consume
  shards as they arrive instead of waiting on one monolithic all-gather;
* **fused quantize+reduce-scatter** (``quantize_rs.py``) —
  ``parallel/compress.py``'s per-block scale compute, rounding and widening
  collapsed into ONE kernel region so scale+round ride the shard boundary
  instead of round-tripping HBM between separate XLA ops; also carries the
  stochastic-rounding wire that reopens the ZeRO-2 first scatter.

**Paged-attention decode** (``paged_attention.py``) is not behind the policy:
it is how the serving decode program of an all-attention layer plan attends,
each slot's live pages read where they lie, and the plan alone decides it
(docs/serving.md §decode attention).

Policy discipline (same as telemetry/resilience/aot-cache/fleet): the
policy is resolved from ``KernelKwargs`` / ``$ACCELERATE_KERNELS`` and is
**default-off with the off path byte-identical** — no kernel module is even
imported on the hot path until a kernel is armed.  Off-TPU the kernels run
under the Pallas CPU interpreter (``interpret=True``), which lowers to
plain partitionable StableHLO, so numerics verify **bitwise** against the
reference paths in tier-1 (tests/test_kernels.py) and every fusion claim is
checkable from ``lower().compiler_ir()`` (``inspect.py``).

The AOT executable cache keys its topology fingerprint on
``KernelPolicy.describe()`` — flipping a kernel on is a LOUD cache miss
naming the ``kernels`` field, never a silently-stale executable.
"""

from __future__ import annotations

import os
from typing import Optional

__all__ = [
    "KernelPolicy",
    "KERNEL_NAMES",
    "TPU_REFUSED",
    "resolve_kernel_policy",
    "current_kernel_policy",
    "_set_active_kernels",
    "_reset_active_kernels",
]

# the policy's hot-path fusions, in the order ROADMAP names them
KERNEL_NAMES = ("collective_matmul", "quantized_rs")

# Kernels the TPU compiler refuses today, with its own words (asked of it
# for a described v5e:2x2 — tests/test_tpu_compile.py keeps each message
# true; docs/kernels.md has the shapes; ROADMAP D1 the follow-up).  Arming
# one on a TPU backend raises this instead of ever interpreting it there.
# ``collective_matmul`` is not here: the policy routes only the ring
# all-gather (shard_map + ppermute, no Pallas body), which compiles.
TPU_REFUSED = {
    "quantized_rs": (
        "inside the captured step the kernel is called on a dp-sharded "
        "array, and the lowering refuses: 'Mosaic kernels cannot be "
        "automatically partitioned. Please wrap the call in a shard_map.'"
    ),
}


class KernelPolicy:
    """Which Pallas kernels are armed, and how they lower.

    ``interpret=None`` resolves lazily to "not on TPU": tier-1 (and any CPU
    mesh) runs every kernel under the Pallas interpreter — bitwise-testable,
    partitionable StableHLO — while a TPU backend compiles the real Mosaic
    kernel.  The resolution is cached on first use so a policy's lowering
    mode cannot drift between captured variants of one run (which would be
    a recompile hazard: ``interpret`` is a static argument everywhere).
    """

    def __init__(
        self,
        collective_matmul: bool = False,
        quantized_rs: bool = False,
        interpret: Optional[bool] = None,
    ):
        self.collective_matmul = bool(collective_matmul)
        self.quantized_rs = bool(quantized_rs)
        self._interpret = interpret

    @property
    def enabled(self) -> bool:
        return self.collective_matmul or self.quantized_rs

    @property
    def interpret(self) -> bool:
        """The lowering mode — and the gate every armed kernel passes, since
        each call site asks it: on a TPU backend a kernel compiles as Mosaic
        or arming raises; it never interprets there."""
        import jax

        # a backend that cannot be asked raises here: falling to the
        # interpreter would run a TPU's kernels interpreted, unnoticed
        on_tpu = jax.default_backend() == "tpu"
        if on_tpu:
            if self._interpret:
                raise ValueError(
                    "KernelKwargs(interpret=True) on a TPU backend: the "
                    "Pallas interpreter is for CPU verification only"
                )
            refused = [n for n in self.armed() if n in TPU_REFUSED]
            if refused:
                raise NotImplementedError(
                    "; ".join(
                        f"kernel {n!r} does not compile for TPU: {TPU_REFUSED[n]}"
                        for n in refused
                    )
                    + " (docs/kernels.md; ROADMAP D1)"
                )
        if self._interpret is None:
            self._interpret = not on_tpu
        return self._interpret

    def armed(self) -> tuple:
        """The armed kernel names, in canonical order (telemetry/bench)."""
        return tuple(n for n in KERNEL_NAMES if getattr(self, n))

    def describe(self) -> str:
        """Canonical armed-set string for telemetry and human output
        (order-independent spellings collapse)."""
        return "+".join(self.armed()) or "none"

    def cache_tag(self) -> str:
        """What executable caches key on: the armed set PLUS the lowering
        mode.  `interpret` usually follows the backend (which fingerprints
        already hash), but ``KernelKwargs(interpret=...)`` can force it —
        an interpreter-mode executable replayed by a Mosaic-mode run (or
        vice versa) would be exactly the silently-stale entry the
        fingerprint exists to prevent.  ``none`` when nothing is armed
        (mode is meaningless, and resolving it would touch the backend)."""
        if not self.enabled:
            return "none"
        return self.describe() + (":interpret" if self.interpret else ":mosaic")

    def __repr__(self):
        return f"KernelPolicy({self.describe()!r})"


def resolve_kernel_policy(handler=None) -> KernelPolicy:
    """Resolve the active policy from a ``KernelKwargs`` handler (or the
    ``$ACCELERATE_KERNELS`` env var it reads).

    Grammar: a comma/plus-separated subset of ``collective_matmul``,
    ``quantized_rs``; ``all`` (or ``1``) arms both; empty / ``none`` / ``0``
    (the default) arms nothing.
    """
    if handler is None:
        from ...utils.dataclasses import KernelKwargs

        handler = KernelKwargs()
    spec = str(handler.kernels or "").strip().lower()
    flags = dict.fromkeys(KERNEL_NAMES, False)
    if spec in ("all", "1", "true", "yes", "on"):
        flags = dict.fromkeys(KERNEL_NAMES, True)
    elif spec not in ("", "0", "none", "false", "no", "off"):
        for name in spec.replace("+", ",").split(","):
            name = name.strip().replace("-", "_")
            if not name:
                continue
            if name not in flags:
                raise ValueError(
                    f"unknown kernel {name!r} in ACCELERATE_KERNELS/"
                    f"KernelKwargs; use a subset of {KERNEL_NAMES} or 'all'"
                )
            flags[name] = True
    return KernelPolicy(interpret=handler.interpret, **flags)


# process-active policy (the Accelerator publishes its resolution here,
# mirroring native/aot_cache's _set_active) — what a bare Optimizer
# relayout picks up without a handle.
# The _UNSET sentinel distinguishes "no Accelerator resolved anything yet"
# (fall back to the env) from "an Accelerator explicitly disarmed kernels"
# (None — the env must NOT re-arm a policy the user opted out of).
_UNSET = object()
_ACTIVE = _UNSET


def _set_active_kernels(policy: Optional[KernelPolicy]) -> None:
    global _ACTIVE
    _ACTIVE = policy


def _reset_active_kernels() -> None:
    """Back to the never-resolved state (test hygiene)."""
    global _ACTIVE
    _ACTIVE = _UNSET


def current_kernel_policy() -> Optional[KernelPolicy]:
    """The process-active policy (which may be an explicit None — a
    constructed Accelerator's disarm wins over the env), else an
    env-resolved one if the env arms anything, else None — the single
    lookup every default-off call site performs once at construction,
    never per step."""
    if _ACTIVE is not _UNSET:
        return _ACTIVE
    if os.environ.get("ACCELERATE_KERNELS"):
        policy = resolve_kernel_policy()
        return policy if policy.enabled else None
    return None
