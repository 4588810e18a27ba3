"""Pillar 2 — recompile forensics.

``CapturedStep`` keys its compiled variants on
``(args_treedef, per-leaf (shape, dtype), sync_gradients, training_modes)``
and silently builds a new program whenever a component moves.  A count
says only *that* a recompile happened; this module says *what
changed*: each new cache key is diffed against the previously used one and the
differences become human-readable cause strings on a structured
:class:`RecompileEvent`.

State-structure invalidations (the carried pytree grew/shrank, or the
donation split between device and host-offloaded leaves moved) don't change
the cache key at all — the capture path detects them separately and passes a
pre-built cause string in, so they surface through the same event stream.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from typing import Optional


def key_id(key) -> str:
    """Short stable id for a CapturedStep cache key (``repr`` is stable for
    the tuple-of-hashables keys the capture path builds)."""
    return "k" + hashlib.sha1(repr(key).encode("utf-8")).hexdigest()[:10]


def _clip(text, limit: int = 200) -> str:
    text = str(text)
    return text if len(text) <= limit else text[: limit - 1] + "…"


def diff_keys(prev, new) -> list[str]:
    """Name every component that moved between two cache keys."""
    causes: list[str] = []
    p_tree, p_shapes, p_sync, p_train = prev
    n_tree, n_shapes, n_sync, n_train = new
    if p_tree != n_tree:
        # treedef reprs of nested batches run to kilobytes, and cause
        # strings flow verbatim into every tracker backend — cap them like
        # the layout path caps exception text
        causes.append(
            f"argument pytree structure changed: {_clip(p_tree)} -> {_clip(n_tree)}"
        )
    if p_shapes != n_shapes:
        if len(p_shapes) != len(n_shapes):
            causes.append(
                f"argument count changed: {len(p_shapes)} -> {len(n_shapes)} leaves"
            )
        else:
            for i, ((ps, pd), (ns, nd)) in enumerate(zip(p_shapes, n_shapes)):
                if ps != ns:
                    causes.append(
                        f"arg[{i}] shape changed: {tuple(ps)} -> {tuple(ns)}"
                    )
                if pd != nd:
                    causes.append(f"arg[{i}] dtype changed: {pd} -> {nd}")
    if p_sync != n_sync:
        causes.append(
            f"sync_gradients flipped {p_sync} -> {n_sync} "
            "(gradient-accumulation boundary variant)"
        )
    if p_train != n_train:
        for i, (pt, nt) in enumerate(zip(p_train, n_train)):
            if pt != nt:
                causes.append(
                    f"model[{i}].training changed {pt} -> {nt} (train/eval switch)"
                )
        if len(p_train) != len(n_train):
            causes.append(
                f"model count changed: {len(p_train)} -> {len(n_train)}"
            )
    return causes


@dataclass
class RecompileEvent:
    step: int  # global captured-call index at which the rebuild happened
    key: str  # key_id of the newly built variant
    prev_key: Optional[str]  # key_id of the variant used just before
    causes: list[str] = field(default_factory=list)
    # "key" (cache-key component moved), "state" (carried pytree structure /
    # donation split changed), "layout" (AOT executable rejected drifted
    # input shardings — the case plain jit re-traces silently)
    kind: str = "key"

    @property
    def cause(self) -> str:
        return self.causes[0] if self.causes else "unknown"

    def to_dict(self) -> dict:
        return {
            "kind": "recompile",
            "step": self.step,
            "key": self.key,
            "prev_key": self.prev_key,
            "cause": self.cause,
            "causes": list(self.causes),
            "recompile_kind": self.kind,
        }
