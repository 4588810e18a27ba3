"""Persistent AOT executable cache — the zero-cold-start subsystem
(``accelerator.aot_cache``, docs/aot_cache.md).

Every fresh process — a preempted-and-rescheduled worker, an autoscaled
serving replica, a bench rerun — pays full trace+compile before its first
useful step.  The capture path already builds through ``jit.lower().
compile()`` (the AOT split telemetry measures); this module persists that
compiled object across processes: ``jax.experimental.serialize_executable``
pickles the underlying PJRT executable (donation, shardings and out-tree
included), and a later process with a matching topology fingerprint
deserializes it and dispatches — **zero trace, zero XLA compile**, bit-for-
bit the same program.

Layout (one directory, ``CompilationCacheKwargs.cache_dir`` /
``$ACCELERATE_AOT_CACHE``):

* ``{variant}-{fp}.pkl`` — pickled ``{payload, in_tree, out_tree, side}``
  where ``payload`` is the serialized executable, the trees are the pickled
  pytree defs ``serialize`` hands back, and ``side`` carries the trace-time
  metadata a skipped trace can no longer discover (``uses_accumulate``,
  deferred scheduler replays by registry index).
* ``{variant}-{fp}.json`` — metadata: the full fingerprint dict, byte size,
  the compile_ms the entry cost (reported as ``avoided_compile_ms`` on
  every later hit), created/used stamps for LRU, and a human key
  description.  Listing/eviction/mismatch diagnosis never unpickles.
* ``profile-{step}.json`` — per-captured-step sidecar (``uses_accumulate``)
  consulted *before* the first call computes its cache key, so an
  accumulate-using body advances its schedule host-side exactly like a warm
  step and lands on the key the cold process stored under.

Key anatomy: the **variant digest** hashes the existing capture cache key
(arg treedef/shapes/dtypes, ``sync_gradients``, training modes) extended
with the carried state's structure (treedef, per-leaf shape/dtype/sharding/
memory-kind), the donation split (host mask) and a digest of the step
body's source.  The **fingerprint digest** hashes the topology/compiler
environment: jax+jaxlib versions, platform, device kind+count, process
count, mesh shape, compression policy, the compiler-mode flags
(``FINGERPRINT_FLAGS`` — ``jax_default_matmul_precision`` et al., whose
flip would otherwise deserialize a program compiled under the other
numerics silently) and the cache format version.  A
lookup globs ``{variant}-*``: an exact fingerprint match is a hit; a
variant match under a DIFFERENT fingerprint is the stale-entry case — the
mismatching fields are named in a loud ``kind="aot_cache"`` miss record and
the caller falls through to a normal compile.  Never a crash, never a
wrong-program dispatch.

Multi-host atomicity: entries are written to a per-pid temp file in the
cache dir and ``os.replace``d into place, so concurrent writers (every
host of a fleet warming the same NFS/GCS-fuse dir) can race freely — a
reader sees either the old complete entry or the new complete entry,
never a torn one.  All IO is fail-soft: a corrupt/truncated/unpicklable
entry is a miss with a cause, not an exception on the step path.
"""

from __future__ import annotations

import glob
import hashlib
import json
import os
import pickle
import tempfile
import time
from typing import Any, Optional

from ..logging import get_logger

logger = get_logger(__name__)

# Process-wide latch for the jax-compilation-cache second layer: once a
# scope-dependent (profiler-armed) run disarms it, NO later-constructed
# cache may silently re-arm it — jax's config is global, the sampler stays
# live for the process, and cache-served executables carry no HLO scope
# metadata (docs/telemetry.md §phases).  A latch, not an instance field:
# the hazard is exactly that a DIFFERENT instance re-arms the layer.
_JAX_CACHE_LAYER_DISARMED = False


def _jax_cache_layer_disarmed() -> bool:
    return _JAX_CACHE_LAYER_DISARMED


def _set_jax_cache_layer_disarmed(value: bool) -> None:
    global _JAX_CACHE_LAYER_DISARMED
    _JAX_CACHE_LAYER_DISARMED = value

# bump when the entry layout / side-metadata schema changes: old entries
# then report a format mismatch and fall through to a normal compile
# (2: compiler flags joined the fingerprint as flat flag:* fields)
# (3: the resolved ParallelPlan digest joined as the `plan` field — a
#  schedule/virtual-stage/ZeRO/compression flip is a loud miss naming it)
AOT_CACHE_FORMAT = 3

# compiler-mode flags that change the COMPILED PROGRAM without moving any
# shape/dtype/topology field the fingerprint already hashes: a flip between
# the storing and loading process would deserialize a program compiled
# under the other mode and silently dispatch the wrong numerics.  Flat
# ``flag:<name>`` fields (not one nested dict) so a stale-flag miss names
# the exact flag that moved.
FINGERPRINT_FLAGS = (
    "jax_default_matmul_precision",
    "jax_enable_x64",
    "jax_numpy_dtype_promotion",
    "jax_numpy_rank_promotion",
    "jax_default_prng_impl",
)

# the active enabled cache — serving constructs (DecodeService) resolve it
# here when no explicit cache is passed, mirroring telemetry's module slot
_ACTIVE: Optional["AOTCompilationCache"] = None


def current_aot_cache() -> Optional["AOTCompilationCache"]:
    return _ACTIVE


def _set_active(cache: Optional["AOTCompilationCache"]) -> None:
    global _ACTIVE
    _ACTIVE = cache


def _digest(obj: Any) -> str:
    """Stable content digest of a JSON-able description."""
    blob = json.dumps(obj, sort_keys=True, default=repr).encode("utf-8")
    return hashlib.sha256(blob).hexdigest()[:16]


def _leaf_aval(x) -> list:
    """(shape, dtype, sharding, memory_kind) description of one state/arg
    leaf — what must match for a stored executable to accept it."""
    import numpy as _np

    shape = list(_np.shape(x))
    dtype = getattr(x, "dtype", None)  # typed PRNG keys stringify as key<fry>
    if dtype is None and x is not None:
        try:
            dtype = _np.result_type(x)
        except TypeError:
            dtype = type(x).__name__
    dtype = str(dtype)
    s = getattr(x, "sharding", None)
    return [shape, dtype, repr(s) if s is not None else None,
            getattr(s, "memory_kind", None)]


def topology_fingerprint(mesh=None, compression: Optional[str] = None,
                         kernels: Optional[str] = None,
                         plan: Optional[dict] = None) -> dict:
    """The invalidation matrix (docs/aot_cache.md): any field moving between
    the storing and the loading process makes the entry stale.  ``kernels``
    is the armed Pallas-kernel set (``KernelPolicy.describe()``,
    docs/kernels.md): a kernel-armed program computes through different IR
    than the reference path, so flipping a kernel must be a loud miss
    NAMING the ``kernels`` field — never a silently-stale executable.
    ``plan`` is the resolved ``ParallelPlan.describe()`` digest
    (docs/parallel_plan.md): the pipeline schedule / virtual-stage factor /
    ZeRO modes shape the compiled program beyond the raw mesh dict, so a
    plan flip must likewise be a loud miss NAMING the ``plan`` field."""
    import jax
    import jaxlib

    devices = jax.devices()
    fingerprint = {
        "format": AOT_CACHE_FORMAT,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "process_count": jax.process_count(),
        "mesh": dict(mesh.shape) if mesh is not None else None,
        "compression": compression,
        "kernels": kernels or "none",
        "plan": plan,
    }
    for flag in FINGERPRINT_FLAGS:
        # repr, not str: distinguishes unset (None) from the string "None",
        # and keeps every value JSON-stable
        fingerprint[f"flag:{flag}"] = repr(getattr(jax.config, flag, None))
    return fingerprint


def fingerprint_mismatch(stored: Optional[dict], live: dict) -> str:
    """Human cause naming exactly which fingerprint fields moved.  When
    nothing moved the entry itself is broken (an orphaned metadata file, a
    torn write) — say that instead of the self-contradictory 'match'."""
    if not isinstance(stored, dict):
        return "entry metadata carries no fingerprint"
    moved = []
    for field in sorted(set(stored) | set(live)):
        if stored.get(field) != live.get(field):
            moved.append(f"{field} {stored.get(field)!r} -> {live.get(field)!r}")
    if not moved:
        return (
            "entry unreadable despite matching fingerprint "
            "(missing or torn payload)"
        )
    return "fingerprint mismatch: " + "; ".join(moved)


def _deserialize(entry: dict, devices):
    """A stored executable, loaded onto ``devices`` — the live mesh's, in
    mesh order.  Left to itself jax loads onto every device of the backend,
    which gives a program compiled for a sub-mesh (a shrunk fleet's dp=4 of
    8 devices) the wrong shard count: the dispatch then dies with "Expected
    args to execute_sharded_on_local_devices to have 8 shards, got 4".
    ``None`` (no mesh pinned) keeps jax's default.

    ``entry`` must come from a fingerprint-matched lookup (``lookup`` /
    ``warm``): loading skips trace AND compile, so nothing below the caller
    re-validates the program against this process's topology."""
    from jax.experimental import serialize_executable

    return serialize_executable.deserialize_and_load(
        entry["payload"], entry["in_tree"], entry["out_tree"],
        execution_devices=devices,
    )


def _atomic_write_bytes(path: str, data: bytes) -> None:
    """Write-then-rename so concurrent multi-host writers never tear an
    entry; the temp file lives in the same dir (rename must not cross
    filesystems)."""
    fd, tmp = tempfile.mkstemp(
        prefix=os.path.basename(path) + ".", suffix=f".{os.getpid()}.tmp",
        dir=os.path.dirname(path),
    )
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(data)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def _atomic_write_json(path: str, payload: dict) -> None:
    _atomic_write_bytes(path, json.dumps(payload, sort_keys=True).encode("utf-8"))


class AOTCompilationCache:
    """The on-disk store plus hit/miss accounting; inert when disabled."""

    def __init__(self, handler=None):
        if handler is None:
            from ..utils.dataclasses import CompilationCacheKwargs

            handler = CompilationCacheKwargs()
        self.handler = handler
        self.enabled = bool(handler.enabled) and handler.cache_dir is not None
        self.cache_dir = handler.cache_dir
        self.max_bytes = int(handler.max_bytes)
        self.warm_on_restore = bool(handler.warm_on_restore)
        self.hits = 0
        self.misses = 0
        self.stores = 0
        self.evictions = 0
        self.last_prefetch_count = 0
        self._metrics_memo = None  # (monotonic, entries, bytes) scrape memo
        self._prefetched: dict[str, bytes] = {}
        self._telemetry = None
        self._fingerprint: Optional[dict] = None
        self._execution_devices = None  # the pinned mesh's devices (set_context)
        if not self.enabled:
            return
        try:
            os.makedirs(self.cache_dir, exist_ok=True)
        except OSError as exc:
            logger.warning(
                "AOT cache dir %r is unusable (%s); cache disabled", self.cache_dir, exc
            )
            self.enabled = False
            return
        if handler.jax_cache_dir:
            if _jax_cache_layer_disarmed():
                # a profiler-armed hub already disarmed the layer for this
                # PROCESS (attach_telemetry below): the config is global,
                # and a later-constructed cache silently re-arming it would
                # reintroduce metadata-less cache-served executables while
                # the sampler is still live
                logger.info(
                    "jax compilation cache layer (%s) NOT armed: disarmed "
                    "process-wide for a scope-dependent run",
                    handler.jax_cache_dir,
                )
            else:
                # second layer (SNIPPETS.md [2]): jax's own persistent XLA
                # compilation cache catches programs outside the capture
                # path.  Placed by the one rule (docs/aot_cache.md §compile
                # cache placement): $JAX_COMPILATION_CACHE_DIR wins over
                # this knob, so a cache placed from outside stays put
                from ..utils.environment import enable_compilation_cache

                enable_compilation_cache(handler.jax_cache_dir)

    # -- telemetry -----------------------------------------------------------
    def attach_telemetry(self, hub) -> None:
        """Pin the enabled telemetry hub so every hit/miss/store lands as a
        ``kind="aot_cache"`` record, and expose the live counters on the
        hub's Prometheus endpoint (``atpu_aot_cache_hits_total`` /
        ``_misses_total``).

        Scope-fidelity guard (ROADMAP carried item, docs/telemetry.md
        §phases): when the hub samples device time (``profile_every_n``),
        the per-phase split joins trace events to the op→scope map parsed
        from the compiled program's HLO metadata — but an executable served
        by jax's own XLA compilation cache (the ``jax_cache_dir`` second
        layer) carries NO metadata, and unlike the first-layer AOT store it
        has no side payload to persist the storing process's map in.  A
        pre-compile parse can't substitute either: the lowered module's
        scope paths hang off UNOPTIMIZED instruction names, which never
        match the post-fusion names trace events carry.  So a
        scope-dependent run disarms that layer — every program it compiles
        is a real compile whose metadata is parseable, and the per-phase
        device split stays populated regardless of warm/cold.  The
        first-layer AOT store keeps serving (its entries carry the
        persisted map)."""
        if hub is None or not getattr(hub, "enabled", False) or not self.enabled:
            return
        self._telemetry = hub
        hub.register_metrics_provider("aot_cache", self.metrics)
        if getattr(hub, "profiler", None) is not None:
            # the hazard is the PROCESS-GLOBAL config, not this instance's
            # own knob: another cache may have armed the layer already (or
            # may try later), so a dir-less cache attaching the sampler
            # must still disarm whatever is set and latch the process
            armed_dir = None
            try:
                import jax

                armed_dir = jax.config.jax_compilation_cache_dir
                if armed_dir:
                    jax.config.update("jax_compilation_cache_dir", None)
            except Exception as exc:
                logger.warning(
                    "could not disarm the jax compilation cache for the "
                    "scope-dependent run: %s", exc,
                )
                return
            # latch it process-wide: any cache constructed AFTER this point
            # must not re-arm the layer (the __init__ arm checks the latch)
            _set_jax_cache_layer_disarmed(True)
            if armed_dir or self.handler.jax_cache_dir:
                logger.info(
                    "jax compilation cache layer (%s) disarmed: device-time "
                    "sampling is on, and cache-served executables carry no "
                    "HLO scope metadata (phases would sample empty)",
                    armed_dir or self.handler.jax_cache_dir,
                )
                self._record(
                    "jax_cache_layer_disarmed", scope="train",
                    key="jax_cache_dir",
                    cause="device-time sampling armed: executables served "
                    "from the XLA compilation cache carry no HLO metadata "
                    "and would sample empty phases",
                )

    _METRICS_TTL_S = 15.0  # dir-stat memo: scrapes must not stat a shared
    # NFS/GCS cache dir per entry every 15 s — counters below are live ints

    def metrics(self) -> dict:
        now = time.monotonic()
        memo = self._metrics_memo
        if memo is None or now - memo[0] > self._METRICS_TTL_S:
            entries, total = self._entries()
            memo = self._metrics_memo = (now, len(entries), total)
        return {
            "hits_total": self.hits,
            "misses_total": self.misses,
            "stores_total": self.stores,
            "evictions_total": self.evictions,
            "entries": memo[1],
            "bytes": memo[2],
        }

    def _record(self, event: str, **fields) -> None:
        if self._telemetry is not None:
            self._telemetry.record_aot_cache({"event": event, **fields})
        # scalar mirror into the flight ring (docs/telemetry.md §flight
        # recorder): AOT-store I/O — hit / miss / store / store_failed —
        # is postmortem-relevant (a hang inside deserialize_and_load shows
        # as a hit with no following step_begin)
        from ..telemetry import flightrec

        flightrec.record(
            "aot_cache",
            event=event,
            **{k: v for k, v in fields.items()
               if v is None or isinstance(v, (bool, int, float, str))},
        )

    # -- fingerprint ---------------------------------------------------------
    def set_context(self, mesh=None, compression: Optional[str] = None,
                    kernels: Optional[str] = None,
                    plan: Optional[dict] = None) -> None:
        """Pin the owning run's mesh/compression/kernel-policy/plan digest
        into the cache's ONE canonical fingerprint (the Accelerator calls
        this at construction; a fleet resize re-pins it).  Every consumer —
        captured-step digests, serving warm, restore prefetch — must hash
        the same fingerprint, or a prefetch that runs before the first step
        (the preemption-resume flow) would pin a mesh-less fingerprint and
        every later lookup would miss."""
        if self.enabled:
            self._fingerprint = topology_fingerprint(
                mesh=mesh, compression=compression, kernels=kernels, plan=plan
            )
            self._execution_devices = (
                list(mesh.devices.flat) if mesh is not None else None
            )

    def fingerprint(self) -> dict:
        if self._fingerprint is None:
            # no pinned context (a standalone cache, e.g. direct API use):
            # mesh-less, but consistently so for both store and load
            self._fingerprint = topology_fingerprint()
        return self._fingerprint

    # -- entry IO ------------------------------------------------------------
    def _paths(self, variant_digest: str, fp_digest: str) -> tuple[str, str]:
        stem = os.path.join(self.cache_dir, f"{variant_digest}-{fp_digest}")
        return stem + ".pkl", stem + ".json"

    def _entries(self) -> tuple[list[str], int]:
        """Metadata paths + total payload bytes (LRU bookkeeping input).
        Profile sidecars are not entries — they carry no executable."""
        if not self.enabled:
            return [], 0
        metas = [
            p
            for p in glob.glob(os.path.join(self.cache_dir, "*-*.json"))
            if not os.path.basename(p).startswith("profile-")
        ]
        total = 0
        for meta_path in metas:
            try:
                total += os.path.getsize(meta_path[: -len(".json")] + ".pkl")
            except OSError:
                continue
        return metas, total

    def lookup(self, variant_digest: str, fingerprint: dict,
               scope: str, key_desc: str, defer_hit: bool = False) -> Optional[dict]:
        """Load one entry.  Exact fingerprint match → the unpickled entry
        dict (``payload``/``in_tree``/``out_tree``/``side``/``meta``);
        a variant twin under a different fingerprint → a LOUD miss naming
        the moved fields; anything broken → a miss with its cause.

        ``defer_hit``: return the entry WITHOUT counting/recording the hit —
        the caller still has to validate side metadata and deserialize, and
        a hit record for a lookup that ends up unusable would make the event
        stream disagree with the counters; the caller settles the outcome
        via ``commit_hit`` or ``record_miss``."""
        if not self.enabled:
            return None
        fp_digest = _digest(fingerprint)
        pkl_path, meta_path = self._paths(variant_digest, fp_digest)
        t0 = time.perf_counter()
        raw = self._prefetched.get(pkl_path)
        if raw is None:
            try:
                with open(pkl_path, "rb") as f:
                    raw = f.read()
            except OSError:
                raw = None
        meta: dict = {}
        cause = None
        if raw is None:
            # stale-fingerprint diagnosis: a same-variant entry stored under
            # a different topology exists — name what moved (the acceptance
            # contract: loud miss, normal compile, never a wrong dispatch)
            twins = glob.glob(
                os.path.join(self.cache_dir, f"{variant_digest}-*.json")
            )
            if twins:
                try:
                    with open(twins[0], encoding="utf-8") as f:
                        stale = json.load(f)
                except (OSError, ValueError):
                    stale = {}
                cause = fingerprint_mismatch(stale.get("fingerprint"), fingerprint)
            else:
                cause = "no entry for this program variant"
        else:
            try:
                with open(meta_path, encoding="utf-8") as f:
                    meta = json.load(f)
            except (OSError, ValueError):
                meta = {}
            stored_fp = meta.get("fingerprint")
            if stored_fp != fingerprint:
                # defense in depth: the digest already keyed the fingerprint,
                # but a hand-edited/corrupt metadata file must not smuggle a
                # foreign-topology executable into a dispatch
                cause = fingerprint_mismatch(stored_fp, fingerprint)
            else:
                try:
                    entry = pickle.loads(raw)
                except Exception as exc:
                    cause = f"entry unpicklable ({type(exc).__name__}: {exc})"[:200]
                else:
                    entry["meta"] = meta
                    entry["_pending_hit"] = {
                        "meta_path": meta_path,
                        "bytes": len(raw),
                        "load_ms": round((time.perf_counter() - t0) * 1e3, 3),
                    }
                    if not defer_hit:
                        self.commit_hit(entry, scope, key_desc)
                    return entry
        self.record_miss(scope, key_desc, cause)
        return None

    def commit_hit(self, entry: dict, scope: str, key_desc: str) -> None:
        """Settle a (possibly deferred) lookup as a hit: count it, refresh
        the LRU stamp, and emit the hit record."""
        pending = entry.pop("_pending_hit", None)
        if pending is None:
            return
        meta = entry.get("meta") or {}
        self.hits += 1
        self._touch(pending["meta_path"], meta)
        self._record(
            "hit", scope=scope, key=key_desc,
            bytes=pending["bytes"],
            load_ms=pending["load_ms"],
            avoided_compile_ms=meta.get("compile_ms"),
            avoided_trace_ms=meta.get("trace_ms"),
        )

    def record_miss(self, scope: str, key_desc: str, cause: Optional[str]) -> None:
        self.misses += 1
        self._record("miss", scope=scope, key=key_desc, cause=cause)
        if cause and "mismatch" in cause:
            logger.warning("AOT cache miss for %s: %s", key_desc, cause)

    def store(self, variant_digest: str, fingerprint: dict, compiled,
              side: Optional[dict], scope: str, key_desc: str,
              trace_ms: float = 0.0, compile_ms: float = 0.0) -> bool:
        """Serialize one compiled executable.  Fail-soft: a backend that
        refuses serialization (or an unpicklable side payload) records a
        ``store_failed`` event and the run continues uncached."""
        if not self.enabled:
            return False
        from jax.experimental import serialize_executable

        fp_digest = _digest(fingerprint)
        pkl_path, meta_path = self._paths(variant_digest, fp_digest)
        try:
            payload, in_tree, out_tree = serialize_executable.serialize(compiled)
            blob = pickle.dumps(
                {
                    "payload": payload,
                    "in_tree": in_tree,
                    "out_tree": out_tree,
                    "side": side or {},
                },
                protocol=pickle.HIGHEST_PROTOCOL,
            )
            # verify-on-store: round-trip the entry BEFORE it reaches disk.
            # XLA:CPU's executable serialization can emit an incomplete
            # object when the process already JIT-compiled other programs
            # (function symbols deduplicated against process state — the
            # load then dies with "Symbols not found" in EVERY process);
            # a serialized program that cannot deserialize here would only
            # ever produce downstream loud misses, so refuse it now and
            # keep the run on its in-memory compiled object
            _deserialize(pickle.loads(blob), self._execution_devices)
            _atomic_write_bytes(pkl_path, blob)
            _atomic_write_json(
                meta_path,
                {
                    "fingerprint": fingerprint,
                    "scope": scope,
                    "key": key_desc,
                    "bytes": len(blob),
                    "trace_ms": round(trace_ms, 3),
                    "compile_ms": round(compile_ms, 3),
                    "created_at": time.time(),
                    "used_at": time.time(),
                    "side": {
                        k: v
                        for k, v in (side or {}).items()
                        # bulky payloads stay in the pickle only: the JSON
                        # metadata is the listing/diagnosis surface and must
                        # stay cheap to read per entry
                        if k not in ("scheduler_replays", "scope_map")
                    },
                    "sig": (side or {}).get("sig"),
                    "service": (side or {}).get("service"),
                },
            )
        except Exception as exc:
            # a payload written before the metadata write failed (ENOSPC et
            # al.) would be invisible to LRU accounting and unloadable
            # forever — drop both halves so the entry is absent, not torn
            for path in (pkl_path, meta_path):
                try:
                    os.unlink(path)
                except OSError:
                    pass
            self._record(
                "store_failed", scope=scope, key=key_desc,
                cause=f"{type(exc).__name__}: {exc}"[:200],
            )
            logger.warning("AOT cache store failed for %s: %s", key_desc, exc)
            return False
        self.stores += 1
        self._record("store", scope=scope, key=key_desc, bytes=len(blob),
                     compile_ms=round(compile_ms, 3))
        self._evict_over_budget(keep=meta_path)
        return True

    def _touch(self, meta_path: str, meta: dict) -> None:
        """Refresh the LRU stamp (best-effort — a read-only shared cache
        still serves hits, it just ages uniformly)."""
        try:
            meta = dict(meta)
            meta["used_at"] = time.time()
            _atomic_write_json(meta_path, meta)
        except OSError:
            pass

    def _evict_over_budget(self, keep: Optional[str] = None) -> None:
        """Drop least-recently-used entries until the payload total fits
        ``max_bytes``.  The entry just written is exempt — evicting it would
        make a store a no-op whenever one program exceeds the budget."""
        metas, total = self._entries()
        if total <= self.max_bytes:
            return
        aged = []
        for meta_path in metas:
            if meta_path == keep:
                continue
            try:
                with open(meta_path, encoding="utf-8") as f:
                    used_at = json.load(f).get("used_at", 0.0)
            except (OSError, ValueError):
                used_at = 0.0
            aged.append((used_at, meta_path))
        for _, meta_path in sorted(aged):
            if total <= self.max_bytes:
                break
            pkl_path = meta_path[: -len(".json")] + ".pkl"
            try:
                size = os.path.getsize(pkl_path)
                os.unlink(pkl_path)
                os.unlink(meta_path)
            except OSError:
                continue
            self._prefetched.pop(pkl_path, None)
            total -= size
            self.evictions += 1

    # -- warm/prefetch -------------------------------------------------------
    def prefetch(self) -> int:
        """Read every entry matching the live fingerprint into memory so the
        next captured-call build is a dict lookup, not a disk read — the
        resilience coupling: ``load_state`` (rollback-restore and the
        ``latest_checkpoint`` resume path) calls this first, so
        restore-after-fault replays the serialized executable off the hot
        path (docs/aot_cache.md §resilience)."""
        if not self.enabled:
            return 0
        live = self.fingerprint()
        fp_digest = _digest(live)
        # entries staged for a PREVIOUS fingerprint are dead weight now: an
        # elastic fleet that resizes repeatedly (shrink → grow → shrink…)
        # re-pins the context each time, and without this sweep every past
        # topology's executables would stay resident for the process's life
        suffix = f"-{fp_digest}.pkl"
        for stale in [p for p in self._prefetched if not p.endswith(suffix)]:
            del self._prefetched[stale]
        count = 0
        for pkl_path in glob.glob(
            os.path.join(self.cache_dir, f"*-{fp_digest}.pkl")
        ):
            try:
                with open(pkl_path, "rb") as f:
                    self._prefetched[pkl_path] = f.read()
                count += 1
            except OSError:
                continue
        self.last_prefetch_count = count
        self._record("warm", scope="restore", entries=count)
        return count

    # -- captured-step integration -------------------------------------------
    def _fn_digest(self, fn) -> str:
        import inspect

        try:
            return _digest(inspect.getsource(fn))
        except (OSError, TypeError):
            return _digest(f"{getattr(fn, '__module__', '?')}."
                           f"{getattr(fn, '__qualname__', repr(fn))}")

    def captured_digests(self, step, key, state_template, host_mask):
        """(variant_digest, fingerprint, fn_digest) for one CapturedStep
        variant — the on-disk identity of one compiled program."""
        import jax

        flat_state, state_treedef = jax.tree_util.tree_flatten(state_template)
        variant = {
            "key": repr(key),
            "state_treedef": repr(state_treedef),
            "state_avals": [_leaf_aval(x) for x in flat_state],
            "host_mask": list(host_mask),
            "fn": self._fn_digest(step.fn),
        }
        # mesh/compression ride the ONE pinned fingerprint (set_context)
        return _digest(variant), self.fingerprint(), variant["fn"]

    def load_captured(self, step, key, state_template, host_mask):
        """(compiled, side) for a stored captured-step variant, or
        (None, None) — a miss (already recorded) or a side payload that no
        longer maps onto this process's scheduler registry."""
        variant_digest, fingerprint, _ = self.captured_digests(
            step, key, state_template, host_mask
        )
        from ..telemetry.recompile import key_id

        # defer the hit: side-metadata validation and the deserialize below
        # can still turn this lookup into a miss, and the event stream must
        # agree with the counters
        entry = self.lookup(
            variant_digest, fingerprint, "train", key_id(key), defer_hit=True
        )
        if entry is None:
            return None, None
        side = entry.get("side") or {}
        if side.get("uses_accumulate") and step._uses_accumulate is None:
            # the profile sidecar is missing (partial dir copy): without it
            # the first call did not advance the accumulation schedule
            # host-side, so dispatching this entry would skip an advance —
            # fall through to a real trace, which advances it
            self.record_miss(
                "train", key_id(key),
                "accumulate-using entry without a step profile sidecar; "
                "tracing to rediscover the schedule",
            )
            return None, None
        schedulers = step.accelerator._schedulers
        for replay in side.get("scheduler_replays", []):
            if not 0 <= replay.get("index", -1) < len(schedulers):
                self.record_miss(
                    "train", key_id(key),
                    "stored scheduler replay index not in this process's "
                    "scheduler registry",
                )
                return None, None
        try:
            compiled = _deserialize(entry, self._execution_devices)
        except Exception as exc:
            self.record_miss(
                "train", key_id(key),
                f"deserialize failed ({type(exc).__name__}: {exc})"[:200],
            )
            return None, None
        self.commit_hit(entry, "train", key_id(key))
        return compiled, side

    def store_captured(self, step, key, compiled, ctx, state_template,
                       host_mask, trace_ms: float, compile_ms: float) -> bool:
        """Persist one freshly compiled captured-step variant plus the
        trace-time side metadata a skipped trace cannot rediscover."""
        variant_digest, fingerprint, fn_digest = self.captured_digests(
            step, key, state_template, host_mask
        )
        schedulers = step.accelerator._schedulers
        replays = []
        for scheduler, args, kwargs in ctx.deferred_scheduler_steps:
            if scheduler not in schedulers:
                self._record(
                    "store_failed", scope="train", key=str(variant_digest),
                    cause="deferred scheduler not registered on the "
                    "accelerator; entry not serializable",
                )
                return False
            try:
                json.dumps([list(args), dict(kwargs)])
            except (TypeError, ValueError):
                self._record(
                    "store_failed", scope="train", key=str(variant_digest),
                    cause="deferred scheduler args not JSON-serializable",
                )
                return False
            replays.append(
                {"index": schedulers.index(scheduler), "args": list(args),
                 "kwargs": dict(kwargs)}
            )
        side = {
            "uses_accumulate": bool(ctx.used_accumulate),
            "scheduler_replays": replays,
        }
        # per-phase device attribution survives the warm start (ROADMAP
        # carried item, docs/telemetry.md §phases): a deserialized
        # executable carries NO HLO metadata, so the op→scope map must be
        # parsed NOW — while the freshly compiled object still has it — and
        # persisted beside the executable; the loading process restores it
        # into its telemetry hub (capture.py) so warm samples keep the
        # split instead of reading empty phases.  Gated on the storing
        # step's telemetry: as_text() stringifies the whole HLO module
        # (can be tens of MB on big programs), and a telemetry-off run has
        # no atpu scopes in its trace to map anyway (the named_scope spans
        # only exist when telemetry instrumented the capture).
        if step._telemetry is not None:
            from ..telemetry.profiler import scope_map_from_compiled

            scope_map = scope_map_from_compiled(compiled)
            if scope_map:
                side["scope_map"] = scope_map
        from ..telemetry.recompile import key_id

        ok = self.store(
            variant_digest, fingerprint, compiled, side, "train",
            key_id(key), trace_ms=trace_ms, compile_ms=compile_ms,
        )
        if ok:
            self._store_profile(fn_digest, {"uses_accumulate": side["uses_accumulate"]})
        return ok

    # -- step profile sidecar ------------------------------------------------
    def _profile_path(self, fn_digest: str) -> str:
        return os.path.join(self.cache_dir, f"profile-{fn_digest}.json")

    def _store_profile(self, fn_digest: str, profile: dict) -> None:
        try:
            _atomic_write_json(self._profile_path(fn_digest), profile)
        except OSError:
            pass

    def step_profile_uses_accumulate(self, step) -> Optional[bool]:
        """The stored ``uses_accumulate`` flag for this step body, or None
        when no profile exists.  Consulted before the FIRST call computes
        its cache key: an accumulate-using body must advance its schedule
        host-side (like every warm call does) so the key it computes is the
        post-advance key the cold process stored under."""
        if not self.enabled:
            return None
        try:
            with open(self._profile_path(self._fn_digest(step.fn)),
                      encoding="utf-8") as f:
                profile = json.load(f)
        except (OSError, ValueError):
            return None
        flag = profile.get("uses_accumulate")
        return bool(flag) if flag is not None else None


class AOTServingPrograms:
    """Per-DecodeService view of the cache: one deserialized executable per
    bucket signature, warmed from disk at service construction so a fresh
    replica's first prefill/decode dispatches without compiling.

    ``call`` replaces the plain-jit dispatch in ``serving/engine.py`` when a
    cache is armed: signature hit → dispatch the pinned executable; miss →
    ``jit_fn.lower(...).compile()`` explicitly (so the object is
    serializable), store, dispatch.  CompileWatcher bookkeeping is kept
    equivalent: cold builds count as compiles, disk/memory hits never do,
    and a build on an already-seen signature still raises the steady-state
    recompile event the tests' and ``chip_smoke.py``'s assertions read.
    """

    def __init__(self, cache: AOTCompilationCache, service_fingerprint: dict):
        self.cache = cache
        self.service_digest = _digest(service_fingerprint)
        self.programs: dict[str, Any] = {}
        self.warmed = 0

    def _variant_digest(self, sig) -> str:
        return _digest({"service": self.service_digest, "sig": repr(sig)})

    def warm(self) -> int:
        """Deserialize every stored bucket program of this service's
        geometry+topology — replica spin-up collapses to disk reads."""
        if not self.cache.enabled:
            return 0
        live = self.cache.fingerprint()
        fp_digest = _digest(live)
        for meta_path in glob.glob(
            os.path.join(self.cache.cache_dir, f"*-{fp_digest}.json")
        ):
            try:
                with open(meta_path, encoding="utf-8") as f:
                    meta = json.load(f)
            except (OSError, ValueError):
                continue
            if meta.get("scope") != "serving" or meta.get("service") != self.service_digest:
                continue
            if meta.get("fingerprint") != live:
                # digest collision or hand-edited metadata: the fingerprint
                # check is the contract — never load a foreign-topology entry
                continue
            pkl_path = meta_path[: -len(".json")] + ".pkl"
            try:
                with open(pkl_path, "rb") as f:
                    entry = pickle.loads(f.read())
                compiled = _deserialize(entry, self.cache._execution_devices)
            except Exception as exc:
                self.cache.record_miss(
                    "serving", str(meta.get("sig")),
                    f"warm deserialize failed "
                    f"({type(exc).__name__}: {exc})"[:200],
                )
                continue
            sig_key = (entry.get("side") or {}).get("sig") or meta.get("sig")
            if sig_key:
                self.programs[sig_key] = compiled
                self.warmed += 1
                self.cache.hits += 1
                # refresh the LRU stamp: a warm-only replica fleet never
                # goes through lookup(), and un-touched entries would age
                # as never-used — evicted before genuinely stale ones
                self.cache._touch(meta_path, meta)
                self.cache._record(
                    "hit", scope="serving", key=sig_key,
                    bytes=meta.get("bytes"),
                    avoided_compile_ms=meta.get("compile_ms"),
                    avoided_trace_ms=meta.get("trace_ms"),
                )
        return self.warmed

    def call(self, label: str, sig, jit_fn, args, statics, watcher=None):
        sig_key = repr(sig)
        if watcher is not None:
            watcher._calls += 1
        compiled = self.programs.get(sig_key)
        stale_drop = False
        if compiled is not None:
            try:
                return compiled(*args)
            except (TypeError, ValueError) as exc:
                # argument validation rejected the live avals — a stale
                # executable (validation precedes donation, so the pools are
                # intact).  Drop it, rebuild below, loud miss.
                stale_drop = True
                self.programs.pop(sig_key, None)
                self.cache.record_miss(
                    "serving", sig_key,
                    f"stale executable rejected inputs "
                    f"({type(exc).__name__}: {exc})"[:200],
                )
                compiled = None
        t0 = time.perf_counter()
        lowered = jit_fn.lower(*args, **statics)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        self.programs[sig_key] = compiled
        if watcher is not None:
            # one contract for both dispatch routes (CompileWatcher.
            # note_build): a rebuild of a program that was live — whether
            # the watcher saw its cold build or it was warmed from disk
            # (stale_drop) — is a steady-state recompile
            watcher.note_build(
                label, sig, seen=stale_drop or (sig in watcher._seen)
            )
        self.cache.store(
            self._variant_digest(sig), self.cache.fingerprint(), compiled,
            {"sig": sig_key, "service": self.service_digest}, "serving",
            sig_key, trace_ms=(t1 - t0) * 1e3, compile_ms=(t2 - t1) * 1e3,
        )
        return compiled(*args)


__all__ = [
    "AOT_CACHE_FORMAT",
    "FINGERPRINT_FLAGS",
    "AOTCompilationCache",
    "AOTServingPrograms",
    "current_aot_cache",
    "fingerprint_mismatch",
    "topology_fingerprint",
]
