"""Pillar 3 — resource accounting: live HBM bytes and per-program costs.

Two sources, both best-effort (every backend exposes a different subset —
missing analyses degrade to absent keys, never to an exception on the hot
path):

* ``live_bytes_by_device()`` walks ``jax.live_arrays()`` and sums per-shard
  ``nbytes`` by device — the "what is resident *right now*" view, sampled at
  capture time and on demand (``Telemetry.sample_resources``).
* ``program_stats(compiled)`` reads the compiled executable's
  ``memory_analysis()`` (argument/output/temp/alias bytes — the *static*
  footprint XLA reserved for one launch) and ``cost_analysis()`` (FLOPs,
  bytes accessed, and any collective bytes the backend reports) — the
  EQuARX-style comms/FLOP denominator per captured program.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field


def live_bytes_by_device() -> dict[str, int]:
    """Bytes of live jax.Arrays per addressable device (host view)."""
    import jax

    per_device: dict[str, int] = {}
    try:
        arrays = jax.live_arrays()
    except Exception:
        return per_device
    for x in arrays:
        try:
            for shard in x.addressable_shards:
                data = shard.data
                if data is None:
                    continue
                dev = str(shard.device)
                per_device[dev] = per_device.get(dev, 0) + int(data.nbytes)
        except Exception:
            continue
    return per_device


def _memory_analysis_dict(compiled) -> dict:
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return {}
    if mem is None:
        return {}
    out = {}
    for name in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        value = getattr(mem, name, None)
        if isinstance(value, (int, float)):
            out[name.replace("_in_bytes", "_bytes")] = int(value)
    return out


def _cost_analysis_dict(compiled) -> dict:
    try:
        cost = compiled.cost_analysis()
    except Exception:
        return {}
    if cost is None:
        return {}
    # jax returns either a per-device list of dicts or a single dict
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    if not isinstance(cost, dict):
        return {}
    out = {}
    for key, value in cost.items():
        if not isinstance(value, (int, float)):
            continue
        if key == "flops":
            out["flops"] = float(value)
        elif key in ("bytes accessed", "bytes_accessed"):
            out["bytes_accessed"] = float(value)
        elif "utilization" in key:
            continue  # per-operand noise; the totals above are the signal
        elif any(tag in key.lower() for tag in ("collective", "all-reduce", "rendezvous", "bytes accessed output")):
            out[key.replace(" ", "_")] = float(value)
    return out


def program_stats(compiled) -> dict:
    """memory_analysis + cost_analysis of one compiled executable."""
    stats = {}
    stats.update(_memory_analysis_dict(compiled))
    stats.update(_cost_analysis_dict(compiled))
    return stats


@dataclass
class ProgramRecord:
    key: str  # cache-key id of the captured variant
    label: str  # e.g. "capture:0"
    stats: dict = field(default_factory=dict)

    def to_dict(self) -> dict:
        return {"kind": "program", "key": self.key, "label": self.label, **self.stats}


@dataclass
class CollectiveRecord:
    """dp-axis collective-bytes attribution under a compression policy
    (``parallel.compress.collective_bytes``): the analytic per-step wire
    bytes of the ZeRO-1 reduce-scatter/all-gather pair, recorded once per
    ``prepare()``.  Complements ``cost_analysis`` — the backend reports
    collective bytes only on some platforms (the keys ``program_stats``
    scrapes), while this figure exists on every backend, CPU mesh included,
    so ``none`` vs ``int8`` vs ``fp8`` can be A/B'd anywhere."""

    policy: str
    stats: dict = field(default_factory=dict)

    def to_dict(self) -> dict:
        return {"kind": "collectives", "policy": self.policy, **self.stats}


@dataclass
class KernelRecord:
    """One armed Pallas hot-path kernel (docs/kernels.md), recorded at
    ``prepare()`` like :class:`CollectiveRecord`: which reference path the
    kernel replaces and how it lowers (compiled Mosaic vs interpreter) —
    the join key for a kernel on/off A/B and the per-phase device-time
    split."""

    kernel: str
    stats: dict = field(default_factory=dict)

    def to_dict(self) -> dict:
        return {"kind": "kernel", "kernel": self.kernel, **self.stats}


@dataclass
class ResourceSample:
    tag: str
    time: float = field(default_factory=time.time)
    devices: dict = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        return int(sum(self.devices.values()))

    def to_dict(self) -> dict:
        return {
            "kind": "resources",
            "tag": self.tag,
            "time": self.time,
            "devices": dict(self.devices),
            "total_bytes": self.total_bytes,
        }


def sample_live(tag: str) -> ResourceSample:
    return ResourceSample(tag=tag, devices=live_bytes_by_device())
