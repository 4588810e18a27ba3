"""Continuous-batching request scheduler over the paged decode engine.

``DecodeService`` is the serving front end (docs/serving.md): construct it
from any model exposing ``_decoder_spec()``, ``submit()`` requests with
arbitrary prompt lengths and token budgets, and drive ``step()`` (or
``run()``).  One ``step()`` is one engine iteration:

1. **Admit** — pop the queue FIFO while a batch slot AND enough pool blocks
   are free: bucket-pad the prompt (``kv_blocks.bucket_length``), reserve
   the request's blocks up front, run the captured prefill (which writes
   the prompt's k/v into the reserved blocks and samples the first token —
   that token's latency is the request's TTFT).
2. **Decode** — one captured call steps EVERY occupied slot
   ``decode_steps`` tokens (default 1): the sampled token feeds the next
   embed and positions advance IN-PROGRAM, so the host pays one dispatch
   and one blocking sync per *n* tokens instead of per token.  Admission
   happens only at these block boundaries, so a joining prompt never
   stalls streaming for in-flight sequences beyond one block.
3. **Evict** — finish detection is host-side post-processing of the
   returned ``(slots, n)`` token block: tokens past a slot's budget/eos
   are discarded (the ≤ n-1 micro-step overrun wrote only into the slot's
   own reservation — ``kv_blocks.blocks_for_request``), and finished
   sequences free their slot and blocks at the block boundary (the freed
   slot is re-admissible next step), instead of riding out the batch.

The host keeps small int mirrors (block tables, positions, last tokens)
for admission math.  On the multi-token path (``decode_steps > 1``) the
arrays the decode program consumes are COMMITTED DEVICE STATE owned by
the service: each call's outputs feed the next call's inputs, and the
mirrors are re-uploaded only when admission or eviction actually changed
them — a steady-state step performs ZERO host→device transfers.  The
default ``decode_steps=1`` path keeps the classic per-step mirror
uploads on purpose: the program must see the exact (uncommitted) avals
it always has, or it lowers to a different HLO module whose
independently-compiled binary can drift a near-tie argmax off
``generate()``'s — see ``step()``.  The pools live on device and are
donated through every call.
Telemetry: when a hub is attached, every step emits a ``kind="serving"``
occupancy record and every completion a per-request TTFT/TPOT record
(docs/telemetry.md).
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Optional

import numpy as np

from ..logging import get_logger
from ..telemetry import flightrec
from .kv_blocks import BlockPool, blocks_for_request, bucket_length, make_pools, make_state_pool

logger = get_logger(__name__)

# metrics() snapshot-retry bound: the scrape thread races the stepping
# thread's deque appends; four attempts at most, then the scrape proceeds
# without percentiles (and says so — see metrics())
_METRICS_SNAPSHOT_RETRIES = 4


@dataclasses.dataclass
class ServingConfig:
    """Service geometry — every field is baked into the captured programs'
    shapes at construction, which is the zero-recompile contract: nothing a
    request carries (length, budget, arrival time) reaches a shape.

    ``prompt_bucket`` must be a multiple of ``block_size`` so a bucketed
    prefill writes whole blocks.  ``max_request_len`` caps prompt+new per
    request (defaults to the model's positional capacity); ``num_blocks``
    sizes the shared pool (default: full reservation — every slot can hold
    a max-length request; set it lower to oversubscribe and exercise
    queue back-pressure).

    ``decode_steps`` is the device-resident hot-loop knob
    (docs/serving.md §device-resident decode): each engine iteration runs
    *n* decode micro-steps inside ONE captured program, feeding sampled
    tokens back on-device, and the host syncs once per n-token block.
    Default 1 (``$ACCELERATE_SERVING_DECODE_STEPS``) is the classic
    one-token-per-step path, byte-identical to the pre-knob service.
    Greedy per-sequence outputs are identical at every n; latency trades
    granularity for dispatch overhead — a request's tokens arrive in
    blocks of n, so small-batch TPOT drops ~n× while per-token streaming
    granularity coarsens to the block."""

    max_slots: int = 8
    block_size: int = 16
    prompt_bucket: int = 32
    num_blocks: Optional[int] = None
    max_request_len: Optional[int] = None
    decode_steps: Optional[int] = None  # None → $ACCELERATE_SERVING_DECODE_STEPS, default 1
    temperature: float = 0.0
    eos_token_id: Optional[int] = None
    pad_token_id: int = 0
    quantize_weights: Optional[int] = None
    rng_seed: int = 0
    # retained completed Requests in service.results (oldest evicted past
    # the bound): a long-running service must not grow host memory with its
    # request history — streaming consumers take step()'s return value or
    # pop_result() and the bound never bites
    max_retained_results: int = 4096
    # completions retained for the metrics() sliding window (TTFT/TPOT
    # p50/p99 on the live endpoint, docs/telemetry.md §metrics endpoint)
    metrics_window: int = 512
    # fault tolerance (docs/serving.md §fault tolerance): journal_dir arms
    # the request WAL + deterministic recovery + preemption drain; off
    # (the default) the hot path is byte-identical.  None of these reach a
    # program shape, so none ride the AOT service fingerprint — a warm
    # store serves journaled and journal-less replicas alike.
    journal_dir: Optional[str] = None  # None → $ACCELERATE_SERVING_JOURNAL
    # bounded queueing: submits past this depth raise QueueFullError with
    # a retry-after hint instead of growing host memory without bound
    max_queue_depth: Optional[int] = None
    # transient decode-dispatch faults are retried this many times against
    # the SAME compiled program before the batch is evicted-and-requeued
    max_decode_retries: Optional[int] = None  # None → $ACCELERATE_SERVING_MAX_RETRIES
    retry_backoff_s: float = 0.05

    def __post_init__(self):
        from ..utils.dataclasses import env_int

        if self.decode_steps is None:
            # malformed values warn and keep the single-token default —
            # the one shared env-int parser (utils/dataclasses.env_int)
            self.decode_steps = env_int("ACCELERATE_SERVING_DECODE_STEPS", 1)
        if self.journal_dir is None:
            import os

            self.journal_dir = os.environ.get("ACCELERATE_SERVING_JOURNAL") or None
        if self.max_decode_retries is None:
            self.max_decode_retries = env_int("ACCELERATE_SERVING_MAX_RETRIES", 2)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (prompt_len,) int32
    max_new_tokens: int
    eos_token_id: Optional[int]
    bucket_len: int
    blocks_needed: int
    state: str = "queued"  # queued -> running -> done (or -> shed)
    tokens: list = dataclasses.field(default_factory=list)
    submitted_t: float = 0.0
    # when the request left the queue with a slot and its blocks, before its
    # prefill (perf_counter, like its neighbours); a requeued request keeps
    # its first admission
    admitted_t: Optional[float] = None
    first_token_t: Optional[float] = None
    done_t: Optional[float] = None
    # per-request latency budget: a queued request whose age exceeds this
    # is SHED at admission time (state="shed", never prefilled) — an
    # expired request must not burn a slot its caller stopped waiting for
    deadline_ms: Optional[float] = None

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])

    @property
    def output_ids(self) -> np.ndarray:
        """prompt + generated tokens (truncated at the stop token, which is
        itself emitted — matching ``generate()``'s convention)."""
        return np.concatenate(
            [self.prompt, np.asarray(self.tokens, np.int32)]
        )

    @property
    def ttft_ms(self) -> Optional[float]:
        if self.first_token_t is None:
            return None
        return (self.first_token_t - self.submitted_t) * 1e3

    @property
    def tpot_ms(self) -> Optional[float]:
        """Mean per-output-token latency after the first token."""
        if self.done_t is None or self.first_token_t is None or len(self.tokens) < 2:
            return None
        return (self.done_t - self.first_token_t) / (len(self.tokens) - 1) * 1e3


class DecodeService:
    """Continuous-batching decode front end for one model (docs/serving.md).

    Composes with everything the single-request engine composes with: the
    stacked per-mode param cache is SHARED with ``generate()`` (alternating
    serving and one-shot decode never restacks), int8/int4 weight modes ride
    ``quantize_weights``, and params prepared through ``shard_for_inference``
    keep their GSPMD layouts — pools and activations inherit them.
    """

    @flightrec.spanned("atpu/serve/init")
    def __init__(self, model, config: Optional[ServingConfig] = None, telemetry=None,
                 aot_cache=None, preemption_guard=None):
        from ..models.generation import ATTENTION, RECURRENT, layer_plan, stacked_params_for_mode

        self.config = cfg = config or ServingConfig()
        if cfg.block_size < 1 or cfg.max_slots < 1:
            raise ValueError("block_size and max_slots must be >= 1")
        if cfg.decode_steps < 1:
            raise ValueError(
                f"decode_steps must be >= 1, got {cfg.decode_steps}"
            )
        if cfg.prompt_bucket % cfg.block_size:
            raise ValueError(
                f"prompt_bucket ({cfg.prompt_bucket}) must be a multiple of "
                f"block_size ({cfg.block_size}) so bucketed prefills write "
                "whole blocks"
            )
        if cfg.quantize_weights not in (None, 4, 8):
            raise ValueError(
                f"quantize_weights={cfg.quantize_weights!r}: use None, 8 or 4"
            )
        self.spec = spec = model._decoder_spec()
        self._qbits = cfg.quantize_weights or 0
        # the layer plan (docs/serving.md §layer plan): None where every
        # layer is attention, else the kinds in order
        kinds = layer_plan(spec.family, spec.cfg)
        if kinds is not None and cfg.decode_steps != 1:
            raise NotImplementedError(
                "a mixed layer plan is served one token a dispatch: "
                "decode_steps > 1 does not carry its state pool yet"
            )
        self._g, self._layers = stacked_params_for_mode(
            model, self._qbits, spec.stack
        )
        cap = min(cfg.max_request_len or spec.max_len, spec.max_len)
        self.capacity = (cap // cfg.block_size) * cfg.block_size
        if self.capacity < cfg.prompt_bucket:
            raise ValueError(
                f"usable capacity ({self.capacity}) < prompt_bucket "
                f"({cfg.prompt_bucket}): shrink the bucket or the block size"
            )
        blocks_per_slot = self.capacity // cfg.block_size
        num_blocks = cfg.num_blocks or (cfg.max_slots * blocks_per_slot + 1)
        n_state_layers = kinds.count(RECURRENT) if kinds is not None else 0
        self._state_layers = n_state_layers
        self.pool = BlockPool(
            num_blocks, cfg.block_size, cfg.max_slots, blocks_per_slot,
            has_state=n_state_layers > 0,
        )

        import jax
        import jax.numpy as jnp

        from .engine import CompileWatcher

        dcfg = spec.cfg
        # the paged pool is as deep as the plan has attention layers
        n_layers = (
            next(iter(self._layers[0].values())).shape[0] if kinds is None
            else kinds.count(ATTENTION)
        )
        # activation dtype drives the pool dtype: one tiny eager embed
        # (params may be bf16 under a mixed-precision prepare)
        probe = spec.family.embed(
            self._g, jnp.zeros((1, 1), jnp.int32), jnp.zeros((1,), jnp.int32), dcfg
        )
        act_dtype = probe.dtype
        self._k_pool, self._v_pool = make_pools(
            n_layers, num_blocks, dcfg.n_kv_head, cfg.block_size,
            dcfg.head_dim, act_dtype,
        )
        # GSPMD-stable pools: when the params carry a NamedSharding (a
        # prepared / shard_for_inference model), commit the pools replicated
        # on the SAME mesh up front.  Fresh jnp.zeros are uncommitted
        # single-device arrays, and the first captured call would return
        # them re-committed onto the params' mesh — flipping the input
        # sharding for call 2 of the same bucket and silently recompiling
        # the one program the service exists to pin (caught by the
        # CompileWatcher; regression-pinned in test_serving)
        from jax.sharding import NamedSharding, PartitionSpec

        param_sharding = next(
            (
                leaf.sharding
                for leaf in jax.tree_util.tree_leaves((self._g, self._layers))
                if isinstance(getattr(leaf, "sharding", None), NamedSharding)
            ),
            None,
        )
        if param_sharding is not None:
            replicated = NamedSharding(param_sharding.mesh, PartitionSpec())
            self._k_pool = jax.device_put(self._k_pool, replicated)
            self._v_pool = jax.device_put(self._v_pool, replicated)
        self._pool_sharding = (
            replicated if param_sharding is not None else None
        )

        # the second kind of cache: per slot and recurrent layer a state and
        # a convolution tail, not paged (kv_blocks.make_state_pool).  Their
        # shapes are what the family's own prefill returns for one layer
        def _rebuild_state():
            if not n_state_layers:
                return None
            # the first recurrent layer's weights: a dict of its own, or one row
            # of its position's stack where the plan is held by its period
            held = self._layers[0]
            first = held[kinds.index(RECURRENT)]
            if len(held) < len(kinds):
                first = jax.eval_shape(lambda l: jax.tree_util.tree_map(lambda a: a[0], l), first)
            _, state, tail = jax.eval_shape(
                lambda l: spec.family.recurrent_prefill(
                    l, jnp.zeros((1, cfg.prompt_bucket, probe.shape[-1]), act_dtype),
                    jnp.int32(1), dcfg,
                ), first,
            )
            pool = make_state_pool(n_state_layers, cfg.max_slots, state.shape, tail.shape, act_dtype)
            if self._pool_sharding is not None:
                pool = jax.device_put(pool, self._pool_sharding)
            return pool

        self._state_factory = _rebuild_state
        self._state = _rebuild_state()

        # pool rebuild hook for the retry-exhaustion recovery path: a fault
        # that fires MID-EXECUTION may have consumed the donated pools; the
        # requeue re-prefills every sequence anyway, so fresh zeroed pools
        # (same shape, dtype and sharding) are a complete replacement
        def _rebuild_pools():
            kp, vp = make_pools(
                n_layers, num_blocks, dcfg.n_kv_head, cfg.block_size,
                dcfg.head_dim, act_dtype,
            )
            if self._pool_sharding is not None:
                kp = jax.device_put(kp, self._pool_sharding)
                vp = jax.device_put(vp, self._pool_sharding)
            return kp, vp

        self._pool_factory = _rebuild_pools
        slots = cfg.max_slots
        self._tables = np.zeros((slots, blocks_per_slot), np.int32)
        self._positions = np.zeros(slots, np.int32)
        self._tokens = np.full(slots, cfg.pad_token_id, np.int32)
        # device-resident decode state (docs/serving.md §device-resident
        # decode): the arrays the multi-token (decode_steps > 1) captured
        # decode consumes.  The numpy mirrors above stay the source of
        # truth for admission math; _flush_device_state re-commits them
        # ONLY when the dirty flag says admission/eviction changed a slot —
        # a steady-state step feeds the previous call's outputs straight
        # back, uploading nothing.  (The n=1 path deliberately keeps the
        # legacy per-step uploads — see step().)
        self._dev_tables = None
        self._dev_positions = None
        self._dev_tokens = None
        self._state_dirty = True
        self._slot_req: list[Optional[Request]] = [None] * slots
        self._base_rng = jax.random.PRNGKey(cfg.rng_seed)
        self._rngs = jnp.stack(
            [jax.random.fold_in(self._base_rng, i) for i in range(slots)]
        )
        if self._pool_sharding is not None:
            # same stability argument as the pools: the sampled-decode
            # program returns the per-slot streams re-committed
            self._rngs = jax.device_put(self._rngs, self._pool_sharding)
        self._queue: deque[Request] = deque()
        self._next_rid = 0
        self.results: dict[int, Request] = {}
        if telemetry is None:
            from ..telemetry import current_telemetry

            telemetry = current_telemetry()
        self._hub = telemetry if (telemetry is not None and telemetry.enabled) else None
        self.watcher = CompileWatcher(hub=self._hub)
        # persistent AOT executable cache (docs/aot_cache.md): when armed
        # (explicit handle or the process-active cache), every bucket
        # program this service compiles is serialized, and a FRESH replica
        # of the same geometry+topology warms them all from disk right here
        # — spin-up collapses from per-bucket XLA compiles to disk reads.
        # Off (the default): both run_* calls below dispatch the plain jit
        # path byte-identically to the pre-cache service.
        if aot_cache is None:
            from ..native.aot_cache import current_aot_cache

            aot_cache = current_aot_cache()
        self._aot = None
        # /healthz readiness input: True once the bucket programs exist in
        # this process (warmed from the AOT store, or built by the first
        # admission) — a scrape-ready replica is one that can serve its
        # first token without a cold compile stall
        self._programs_warmed = False
        if aot_cache is not None and aot_cache.enabled:
            import jax as _jax

            from ..native.aot_cache import AOTServingPrograms, _leaf_aval

            service_fingerprint = {
                "family": type(self.spec.family).__name__,
                "cfg": repr(dcfg),
                "qbits": self._qbits,
                "temperature": float(cfg.temperature),
                "block_size": cfg.block_size,
                "max_slots": cfg.max_slots,
                "prompt_bucket": cfg.prompt_bucket,
                "capacity": self.capacity,
                "pools": [_leaf_aval(self._k_pool), _leaf_aval(self._v_pool)],
                "params": [
                    _leaf_aval(leaf)
                    for leaf in _jax.tree_util.tree_leaves((self._g, self._layers))
                ],
            }
            if cfg.decode_steps != 1:
                # the n-token decode block is a different program with a
                # different OUTPUT ARITY (token block + advanced state):
                # entries stored by a service of another n must miss
                # loudly, never deserialize into a shape the caller can't
                # unpack.  Keyed CONDITIONALLY so default (n=1) services
                # keep the fingerprint — and the warm entries — they have
                # always had.
                service_fingerprint["decode_steps"] = int(cfg.decode_steps)
            self._aot = AOTServingPrograms(aot_cache, service_fingerprint)
            self._programs_warmed = self._aot.warm() > 0
        # fault tolerance (docs/serving.md §fault tolerance): everything
        # below is None / False when the journal is off — the hot path
        # pays one None-check per site, byte-identical to the pre-recovery
        # service (pinned by tests/test_serving_recovery.py)
        self._draining = False
        self._journal = None
        self._guard = preemption_guard
        if cfg.journal_dir:
            from .recovery import RequestJournal

            self._journal = RequestJournal(cfg.journal_dir, meta={
                # sampling determinism rides these: resume validates them
                # so a mismatched replica fails loudly instead of emitting
                # a silently different continuation
                "temperature": float(cfg.temperature),
                "rng_seed": int(cfg.rng_seed),
                "quantize_weights": cfg.quantize_weights,
                "decode_steps": int(cfg.decode_steps),
            })
            if self._guard is None:
                from ..resilience.preemption import PreemptionGuard

                # sticky-flag SIGTERM/SIGINT guard (resilience pillar 2):
                # step() polls it and drains at its own safe point.
                # install() is a no-op off the main thread — a journaled
                # service on a worker thread still journals, it just
                # relies on an explicit drain() call
                self._guard = PreemptionGuard()
            if not self._guard.installed:
                self._guard.install()
        # deterministic fault injection (resilience pillar 4): armed only
        # when $ACCELERATE_FAULT_PLAN names serving verbs — production
        # runs carry a None here
        from ..resilience.inject import FaultInjector

        self._injector = FaultInjector.from_spec(None)
        self.stats = {
            "steps": 0,
            "admitted": 0,
            "completed": 0,
            "occupancy_sum": 0.0,
            "queue_peak": 0,
            # dispatch-overhead accounting (docs/telemetry.md §serving):
            # host_syncs counts EVERY blocking device→host read (prefill
            # first tokens + decode blocks); decode_syncs counts PER-SLOT
            # sync exposures (each decode sync, once per active slot) so
            # host_syncs_per_token = decode_syncs/decode_tokens reads 1.0
            # on the classic path and ~1/n on n-token blocks independent
            # of batch size; h2d_uploads counts host→device state
            # re-commits (0 in steady state)
            "host_syncs": 0,
            "decode_syncs": 0,
            "decode_tokens": 0,
            "h2d_uploads": 0,
            # how far decode attention's mechanism engages (docs/telemetry.md
            # §serving): pages a scanned plan's kernel walks (each decoding
            # slot's own length, positions // block_size + 1 a token) against
            # pages those slots' table rows span (blocks_per_slot each: what
            # the gather path attends over) — host arithmetic on the mirrors,
            # no device read
            "kv_pages_walked": 0,
            "kv_pages_tabled": 0,
            # a mixed plan: (recurrent layer, slot) states the decode step
            # takes, the decoding slots' alone a layer and micro-step — what
            # a live-slot step walks (Mamba-2's kernel) of the max_slots a
            # layer a step over every slot reads (docs/telemetry.md §serving)
            "state_slots_walked": 0,
            # fault-tolerance accounting (docs/serving.md §fault
            # tolerance): shed completions, recovered (re-prefilled)
            # admissions, retry attempts, exhaustion requeues, pool
            # rebuilds after a consumed-donation fault, and metrics-scrape
            # snapshot retries that ran the cap dry
            "shed": 0,
            "recovered": 0,
            "decode_retries": 0,
            "requeued": 0,
            "pool_rebuilds": 0,
            # a mixed layer plan: (token, held expert) products the expert
            # layers did, from the load vector each program returns
            "expert_tokens": 0,
            "metrics_snapshot_retry_exhausted": 0,
        }
        # sliding (ttft_ms, tpot_ms) window behind metrics() — the live
        # endpoint's SLO percentiles must reflect *recent* traffic, not the
        # whole run
        self._latency_window: deque = deque(maxlen=max(1, cfg.metrics_window))
        # native Prometheus histograms alongside the window percentiles:
        # cumulative _bucket series a server-side histogram_quantile() can
        # rate() over any range and merge across replicas — the window
        # gauges cannot be aggregated (docs/telemetry.md §endpoint)
        from ..telemetry.metrics import LatencyHistogram

        self._ttft_hist = LatencyHistogram()
        self._tpot_hist = LatencyHistogram()
        if self._hub is not None:
            # the hub's metrics endpoint (telemetry/metrics.py) scrapes any
            # provider registered here; latest-constructed service wins the
            # "serving" name (a MetricsServer.add_service call attaches
            # additional services explicitly).  Registered through a
            # weakref: the hub is process-lived, and a strong ref from it
            # would pin this service's params + KV pools after the caller
            # drops it — a dropped service renders as no gauges, silently
            import weakref

            service_ref = weakref.ref(self)

            def _serving_metrics():
                service = service_ref()
                return service.metrics() if service is not None else {}

            self._hub.register_metrics_provider("serving", _serving_metrics)

            def _serving_health():
                service = service_ref()
                return service.health() if service is not None else {}

            # /healthz rides the same endpoint (telemetry/metrics.py): a
            # dropped service renders as an absent section, never a stale
            # "ready"
            self._hub.register_health_provider("serving", _serving_health)

    # -- request intake ------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int,
               eos_token_id: Optional[int] = None,
               arrival_t: Optional[float] = None,
               deadline_ms: Optional[float] = None) -> int:
        """Queue one request; returns its id.  Validation happens here so a
        request that can NEVER be admitted fails loudly at submit time
        instead of deadlocking the queue.

        ``arrival_t`` (a ``time.perf_counter()`` timestamp) backdates the
        TTFT clock to when the request actually ARRIVED rather than when
        the driver got around to calling submit — an open-loop load
        generator must pass it or its p99 TTFT silently excludes the
        queueing delay it exists to measure (coordinated omission).

        ``deadline_ms`` bounds the request's queueing age: a request still
        queued past it is SHED at admission time (a ``state="shed"``
        completion record, never prefilled).  With
        ``ServingConfig(max_queue_depth=...)`` set, a submit against a full
        queue raises :class:`~.recovery.QueueFullError` carrying a
        TPOT-derived ``retry_after_ms`` — bounded host memory under
        overload instead of unbounded queue growth."""
        prompt = np.asarray(
            prompt.data if hasattr(prompt, "data") else prompt, np.int32
        ).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        p_len = int(prompt.size)
        if p_len + max_new_tokens > self.capacity:
            raise ValueError(
                f"prompt ({p_len}) + max_new_tokens ({max_new_tokens}) exceeds "
                f"the service's per-request capacity ({self.capacity})"
            )
        blen = bucket_length(p_len, self.config.prompt_bucket, cap=self.capacity)
        needed = blocks_for_request(
            p_len, max_new_tokens, blen, self.config.block_size,
            decode_steps=self.config.decode_steps,
            blocks_per_slot=self.pool.blocks_per_slot,
        )
        if needed > self.pool.usable_blocks:
            raise ValueError(
                f"request needs {needed} blocks but the pool only has "
                f"{self.pool.usable_blocks}: raise num_blocks"
            )
        if self._draining or (
            self.config.max_queue_depth is not None
            and len(self._queue) >= self.config.max_queue_depth
        ):
            # bounded queueing / drain back-pressure: reject with a
            # retry-after hint — the caller's load balancer re-routes or
            # re-submits, and host memory stays bounded under overload
            from .recovery import QueueFullError

            reason = "draining" if self._draining else "queue_full"
            retry_after = self._retry_after_ms()
            self.stats["shed"] += 1
            flightrec.record(
                "serving_shed", reason=reason, queue_depth=len(self._queue),
            )
            if self._hub is not None:
                self._hub.record_serving({
                    "event": "shed", "reason": reason,
                    "queue_depth": len(self._queue),
                    "retry_after_ms": retry_after,
                })
            raise QueueFullError(
                f"submit rejected ({reason}): queue depth "
                f"{len(self._queue)}; retry in ~{retry_after:.0f} ms",
                retry_after_ms=retry_after,
            )
        rid = self._next_rid
        self._next_rid += 1
        req = Request(
            rid=rid, prompt=prompt, max_new_tokens=max_new_tokens,
            eos_token_id=(
                eos_token_id if eos_token_id is not None
                else self.config.eos_token_id
            ),
            bucket_len=blen, blocks_needed=needed,
            submitted_t=arrival_t if arrival_t is not None else time.perf_counter(),
            deadline_ms=deadline_ms,
        )
        if self._journal is not None:
            self._journal.log_submit(
                rid, prompt, max_new_tokens, req.eos_token_id,
                deadline_ms=deadline_ms,
            )
        self._queue.append(req)
        self.stats["queue_peak"] = max(self.stats["queue_peak"], len(self._queue))
        rec = flightrec.recorder()
        rec.record(
            "serve/submit", rid=rid, prompt_len=p_len,
            submitted=rec.from_perf_counter(req.submitted_t),
        )
        return rid

    # -- scheduling ----------------------------------------------------------
    @property
    def active_slots(self) -> int:
        return sum(r is not None for r in self._slot_req)

    @property
    def pool_free_frac(self) -> float:
        """Free fraction of the usable KV block pool — the back-pressure
        gauge the step records, the fleet signal and the metrics endpoint
        all report (one definition, three consumers)."""
        return self.pool.free_blocks / max(1, self.pool.usable_blocks)

    @property
    def has_work(self) -> bool:
        return bool(self._queue) or self.active_slots > 0

    def _free_slot(self) -> Optional[int]:
        for i, r in enumerate(self._slot_req):
            if r is None:
                return i
        return None

    def _admit(self) -> list[Request]:
        """FIFO head-of-line admission: the oldest queued request is always
        next (no shorter request overtakes it — predictable tail latency),
        gated on a free slot AND its block reservation fitting the pool."""
        import jax

        admitted = []
        while self._queue:
            req = self._queue[0]
            if req.deadline_ms is not None and (
                (time.perf_counter() - req.submitted_t) * 1e3 > req.deadline_ms
            ):
                # expired while queued: shed BEFORE the slot gate — an
                # abandoned request must neither burn a prefill nor block
                # the head of the line
                self._queue.popleft()
                self._shed(req, "deadline")
                continue
            slot = self._free_slot()
            if slot is None or not self.pool.can_alloc(req.blocks_needed):
                break
            self._queue.popleft()
            if req.admitted_t is None:
                req.admitted_t = time.perf_counter()
            if req.tokens:
                # journal-recovered (or retry-requeued) request: rebuild
                # its KV by teacher-forced re-prefill over the emitted
                # prefix (docs/serving.md §fault tolerance)
                self._admit_recovering(req, slot)
                admitted.append(req)
                continue
            row = self.pool.alloc(slot, req.blocks_needed)
            table_row = np.zeros(self.pool.blocks_per_slot, np.int32)
            table_row[: len(row)] = row
            about = dict(rid=req.rid, bucket_len=req.bucket_len, prompt_len=req.prompt_len)
            with flightrec.span("atpu/serve/prefill_launch", **about):
                tok, rng_out, load = self._launch_prefill(
                    req, slot, req.prompt, table_row,
                    jax.random.fold_in(self._base_rng, 2 * req.rid + 1),
                )
            self.stats["host_syncs"] += 1
            self._programs_warmed = True
            with flightrec.span("atpu/serve/prefill_sync", **about):
                first = int(tok)
            self._record_load("prefill", load, tokens=req.prompt_len)
            req.first_token_t = time.perf_counter()
            req.tokens.append(first)
            req.state = "running"
            self.stats["admitted"] += 1
            if self._journal is not None:
                self._journal.log_tokens(req.rid, [first])
            admitted.append(req)
            if req.max_new_tokens == 1 or (
                req.eos_token_id is not None and first == req.eos_token_id
            ):
                # one-token request (or instant stop): never occupies the
                # decode batch — blocks go straight back
                self.pool.free_slot(slot)
                self._finish(req)
                continue
            self._slot_req[slot] = req
            self._tables[slot] = table_row
            self._positions[slot] = req.prompt_len
            self._tokens[slot] = first
            self._state_dirty = True  # new slot row: re-commit before decode
            self._rngs = self._rngs.at[slot].set(rng_out)
        return admitted

    def _launch_prefill(self, req: Request, slot: int, seq, table_row, rng):
        """Dispatch one bucketed prefill of ``seq`` (the prompt, or on
        recovery the prompt and the tokens so far) into ``slot``: its pages
        of the KV pool and, under a mixed layer plan, the slot's row of the
        state pool — written whole from a zero state, which is the slot's
        reset.  Returns ``(first token, rng, load)``, all still on the device."""
        import jax.numpy as jnp

        from .engine import run_prefill

        padded_ids = np.full((1, req.bucket_len), self.config.pad_token_id, np.int32)
        padded_ids[0, : len(seq)] = seq
        out = run_prefill(
            self._k_pool, self._v_pool, self._g, self._layers,
            jnp.asarray(padded_ids), jnp.asarray(table_row),
            jnp.asarray(len(seq), jnp.int32), rng,
            family=self.spec.family, cfg=self.spec.cfg, qbits=self._qbits,
            temperature=float(self.config.temperature),
            watcher=self.watcher, aot=self._aot, slot=slot, state=self._state,
        )
        self._k_pool, self._v_pool, tok, rng_out = out[:4]
        load = None
        if self._state is not None:
            self._state, load = out[4:]
        return tok, rng_out, load

    def _record_load(self, phase: str, load, **about) -> None:
        """The expert layers' load of one program execution onto the ring
        (docs/telemetry.md §moe_load): ``load`` is the small int vector the
        program returned beside its tokens — the tokens each held expert got,
        summed over the expert layers, then how many (layer, expert) pairs
        got any.  Read after the step's one blocking read, so it waits for
        nothing."""
        if load is None:
            return
        load = np.asarray(load)
        self.stats["expert_tokens"] += int(load[:-1].sum())
        flightrec.record(
            "atpu/serve/moe_load", phase=phase, step=self.stats["steps"],
            per_expert=load[:-1].tolist(), touched=int(load[-1]), **about,
        )

    def _evict(self, slot: int) -> None:
        """Free the slot the moment its request finishes: table back to the
        trash block, blocks back to the pool — next step's admission can
        hand them to a queued request."""
        self.pool.free_slot(slot)
        self._slot_req[slot] = None
        self._tables[slot] = 0
        self._positions[slot] = 0
        self._tokens[slot] = self.config.pad_token_id
        # the device copy of this slot now points at freed blocks (and, at
        # decode_steps>1, overran positions) — re-commit before next decode
        self._state_dirty = True

    def pop_result(self, rid: int) -> Optional[Request]:
        """Take (and drop) one finished request — the streaming-consumer
        API; ``step()``'s return value is the push-style equivalent."""
        return self.results.pop(rid, None)

    def _finish(self, req: Request) -> None:
        req.done_t = time.perf_counter()
        req.state = "done"
        self.results[req.rid] = req
        rec = flightrec.recorder()
        rec.record(
            "serve/request", rid=req.rid, prompt_len=req.prompt_len, tokens=len(req.tokens),
            **{name: rec.from_perf_counter(t) for name, t in (
                ("submitted", req.submitted_t), ("admitted", req.admitted_t),
                ("first_token", req.first_token_t), ("done", req.done_t),
            )},
        )
        while len(self.results) > self.config.max_retained_results:
            self.results.pop(next(iter(self.results)))
        if self._journal is not None:
            self._journal.log_complete(req.rid)
        self.stats["completed"] += 1
        self._latency_window.append((req.ttft_ms, req.tpot_ms))
        if req.ttft_ms is not None:
            self._ttft_hist.observe(req.ttft_ms)
        if req.tpot_ms is not None:
            self._tpot_hist.observe(req.tpot_ms)
        if self._hub is not None:
            self._hub.record_serving({
                "event": "complete", "rid": req.rid,
                "prompt_len": req.prompt_len,
                "new_tokens": len(req.tokens),
                "ttft_ms": req.ttft_ms,
                "tpot_ms": req.tpot_ms,
            })

    # -- fault tolerance -----------------------------------------------------
    def _shed(self, req: Request, reason: str) -> None:
        """Complete a request WITHOUT serving it: ``state="shed"``, a
        completion record the caller can poll, a journal entry so a
        recovering replica never resurrects it — and nothing in the
        latency window, which describes served traffic only."""
        req.done_t = time.perf_counter()
        req.state = "shed"
        self.results[req.rid] = req
        while len(self.results) > self.config.max_retained_results:
            self.results.pop(next(iter(self.results)))
        self.stats["shed"] += 1
        if self._journal is not None:
            self._journal.log_shed(req.rid, reason)
        flightrec.record("serving_shed", rid=req.rid, reason=reason)
        if self._hub is not None:
            self._hub.record_serving({
                "event": "shed", "rid": req.rid, "reason": reason,
                "queued_ms": (req.done_t - req.submitted_t) * 1e3,
            })

    def _retry_after_ms(self) -> float:
        """Back-pressure hint for rejected submits: roughly one decode
        block at the service's recent median TPOT — when capacity next
        frees up, not a magic constant.  Falls back to 100 ms before any
        completion has been observed."""
        tpots = sorted(
            p for _, p in list(self._latency_window) if p is not None
        )
        if not tpots:
            return 100.0
        return max(1.0, tpots[len(tpots) // 2] * self.config.decode_steps)

    def _queue_recovery(self, reqs: list, front: bool = False) -> None:
        """(Re)queue requests carrying an emitted prefix: recompute each
        one's bucket and block reservation for the RECOVERY sequence
        (prompt + prefix-minus-last re-prefilled, the last journaled token
        re-fed as the next decode input) and restore FIFO order."""
        reqs = sorted(reqs, key=lambda r: r.rid)
        for req in reqs:
            k = len(req.tokens)
            seq_len = req.prompt_len + max(0, k - 1)
            remaining = req.max_new_tokens - k + 1 if k else req.max_new_tokens
            req.bucket_len = bucket_length(
                seq_len, self.config.prompt_bucket, cap=self.capacity
            )
            req.blocks_needed = blocks_for_request(
                seq_len, remaining, req.bucket_len, self.config.block_size,
                decode_steps=self.config.decode_steps,
                blocks_per_slot=self.pool.blocks_per_slot,
            )
            req.state = "queued"
        if front:
            self._queue.extendleft(reversed(reqs))
        else:
            self._queue.extend(reqs)
        self.stats["queue_peak"] = max(self.stats["queue_peak"], len(self._queue))

    def _admit_recovering(self, req: Request, slot: int) -> None:
        """Teacher-forced re-prefill: rebuild the slot's KV by running the
        ordinary bucketed prefill over ``prompt + tokens[:-1]`` — the same
        captured program family the service pins, so a warm-AOT replica
        recovers with zero compiles — then feed the LAST journaled token
        as the next decode input at its true position.  The prefill's own
        sampled token is discarded (the journal is the source of truth),
        and the per-request RNG stream is re-advanced so a sampled
        continuation is bitwise-identical to the uninterrupted run: the
        stream consumes one split per sampled token, so handing prefill
        the stream at position ``k-1`` lands its internal split exactly at
        ``k`` (recovery.advance_rng)."""
        import jax

        from .recovery import advance_rng

        k = len(req.tokens)
        seq = np.concatenate(
            [req.prompt, np.asarray(req.tokens[:-1], np.int32)]
        )
        seq_len = int(seq.size)
        row = self.pool.alloc(slot, req.blocks_needed)
        table_row = np.zeros(self.pool.blocks_per_slot, np.int32)
        table_row[: len(row)] = row
        about = dict(rid=req.rid, bucket_len=req.bucket_len, prompt_len=seq_len)
        with flightrec.span("atpu/serve/prefill_launch", **about):
            rng = jax.random.fold_in(self._base_rng, 2 * req.rid + 1)
            if float(self.config.temperature) > 0.0:
                rng = advance_rng(rng, k - 1)
            tok, rng_out, load = self._launch_prefill(req, slot, seq, table_row, rng)
        self.stats["host_syncs"] += 1
        with flightrec.span("atpu/serve/prefill_sync", **about):
            int(tok)  # block for the prefill; the sample itself is teacher-forced away
        self._record_load("prefill", load, tokens=seq_len)
        self._programs_warmed = True
        req.state = "running"
        if req.first_token_t is None:
            # resumed from a dead replica's journal: the recovered TTFT
            # clock starts at resubmission (perf_counter doesn't survive
            # a process boundary)
            req.first_token_t = time.perf_counter()
        self.stats["admitted"] += 1
        self.stats["recovered"] += 1
        flightrec.record(
            "serving_recovered", rid=req.rid, prefix_tokens=k,
        )
        if self._hub is not None:
            self._hub.record_serving_recovery({
                "event": "recovered_admit", "rid": req.rid,
                "prefix_tokens": k, "seq_len": seq_len,
            })
        last = int(req.tokens[-1])
        if len(req.tokens) >= req.max_new_tokens or (
            req.eos_token_id is not None and last == req.eos_token_id
        ):
            # the journaled prefix already satisfied the budget/stop: the
            # request is complete — nothing left to decode
            self.pool.free_slot(slot)
            self._finish(req)
            return
        self._slot_req[slot] = req
        self._tables[slot] = table_row
        self._positions[slot] = seq_len
        self._tokens[slot] = last
        self._state_dirty = True
        self._rngs = self._rngs.at[slot].set(rng_out)

    def _requeue_active(self, reason: str, error=None) -> None:
        """Decode-retry exhaustion path: evict every active slot and send
        its request back through journal-style recovery (the emitted
        prefixes live in the host Request objects) instead of crashing the
        service.  A mid-execution fault may have consumed the donated
        pools — rebuild them; the re-prefills repopulate everything."""
        reqs = [r for r in self._slot_req if r is not None]
        for slot, r in enumerate(self._slot_req):
            if r is not None:
                self._evict(slot)
        if self._k_pool.is_deleted():
            self._k_pool, self._v_pool = self._pool_factory()
            # the state pool was donated beside them; the re-prefills write
            # every slot's state anew
            self._state = self._state_factory()
            self.stats["pool_rebuilds"] += 1
        self._queue_recovery(reqs, front=True)
        self.stats["requeued"] += len(reqs)
        flightrec.record(
            "serving_requeue", count=len(reqs), reason=reason,
        )
        if self._hub is not None:
            self._hub.record_serving_recovery({
                "event": "requeue", "reason": reason,
                "rids": [r.rid for r in reqs],
                "error": None if error is None else f"{type(error).__name__}: {error}"[:300],
            })

    def drain(self, reason: Optional[str] = None) -> list[int]:
        """Preemption drain: stop admission, finalize the journal, emit
        ``kind="serving_recovery"`` records.  In-flight and queued
        requests stay OPEN in the journal — a fresh replica pointed at the
        same ``journal_dir`` (``resume_from_journal``) completes every one
        of them from its emitted prefix, with warm AOT programs.
        Idempotent; returns the open rids."""
        open_rids = sorted(
            [r.rid for r in self._queue]
            + [r.rid for r in self._slot_req if r is not None]
        )
        if self._draining:
            return open_rids
        self._draining = True
        if reason is None:
            reason = (
                self._guard.signal_name if self._guard is not None else None
            ) or "drain"
        flightrec.record(
            "serving_drain", reason=reason, open=len(open_rids),
        )
        if self._hub is not None:
            self._hub.record_serving_recovery({
                "event": "drain", "reason": reason, "open_rids": open_rids,
            })
        if self._journal is not None:
            self._journal.log_drain(open_rids)
            self._journal.close()
        return open_rids

    @property
    def draining(self) -> bool:
        return self._draining

    def resume_from_journal(self, journal_dir: Optional[str] = None) -> list[int]:
        """Resubmit every open request from a journal (default: this
        service's own ``journal_dir``) under its ORIGINAL rid — the rid
        seeds the per-request RNG stream (``fold_in(base, 2*rid+1)``), so
        pinning it is what makes the recovered continuation deterministic.
        Sampling-config mismatches against the journal's metadata fail
        loudly.  Returns the resumed rids (FIFO order preserved)."""
        from .recovery import replay_journal

        path = journal_dir or self.config.journal_dir
        if not path:
            raise ValueError(
                "resume_from_journal needs a journal_dir (argument, "
                "ServingConfig, or $ACCELERATE_SERVING_JOURNAL)"
            )
        state = replay_journal(path)
        for key, ours in (
            ("temperature", float(self.config.temperature)),
            ("rng_seed", int(self.config.rng_seed)),
            ("quantize_weights", self.config.quantize_weights),
        ):
            theirs = state.meta.get(key, ours)
            if theirs != ours:
                raise ValueError(
                    f"journal was written by a service with {key}={theirs!r} "
                    f"but this replica has {key}={ours!r}: recovered "
                    "continuations would silently diverge"
                )
        reqs = []
        for entry in state.open_requests:
            req = Request(
                rid=entry.rid, prompt=entry.prompt,
                max_new_tokens=entry.max_new_tokens,
                eos_token_id=entry.eos_token_id,
                bucket_len=0, blocks_needed=0,  # recomputed by _queue_recovery
                tokens=list(entry.tokens),
                submitted_t=time.perf_counter(),
            )
            reqs.append(req)
        rids = [r.rid for r in reqs]
        if rids:
            self._next_rid = max(self._next_rid, max(rids) + 1)
            own_path = self._journal.path if self._journal is not None else None
            from .recovery import _journal_path

            if self._journal is not None and own_path != _journal_path(path):
                # resuming from ANOTHER journal: re-log into ours so this
                # replica's log is self-contained (same-dir resume skips —
                # the records are already in the file we append to)
                for req in reqs:
                    self._journal.log_submit(
                        req.rid, req.prompt, req.max_new_tokens,
                        req.eos_token_id, tokens=req.tokens,
                    )
        self._queue_recovery(reqs, front=False)
        flightrec.record("serving_resume", count=len(rids))
        if self._hub is not None and rids:
            self._hub.record_serving_recovery({
                "event": "resume", "count": len(rids), "rids": rids,
            })
        return rids

    def health(self) -> dict:
        """Readiness + liveness snapshot for the ``/healthz`` probe
        (telemetry/metrics.py): ready = programs warmed ∧ pool allocated ∧
        not draining.  Pure host reads — safe from the endpoint thread."""
        pool_allocated = self.pool.usable_blocks > 0
        return {
            "ready": bool(
                self._programs_warmed and pool_allocated and not self._draining
            ),
            "live": True,
            "programs_warmed": self._programs_warmed,
            "pool_allocated": pool_allocated,
            "draining": self._draining,
            "slots_active": self.active_slots,
            "queue_depth": len(self._queue),
        }

    def _flush_device_state(self) -> None:
        """Re-commit the host mirrors to the device (the ``decode_steps >
        1`` path) — ONLY when admission or eviction changed a slot since
        the last decode.  Steady state (every slot mid-sequence) feeds the
        previous call's outputs straight back: zero host→device transfers
        per step, pinned by the ``jax.transfer_guard`` regression test in
        tests/test_serving.py."""
        if not self._state_dirty and self._dev_tables is not None:
            return
        import jax
        import jax.numpy as jnp

        arrays = (
            jnp.asarray(self._tables),
            jnp.asarray(self._positions),
            jnp.asarray(self._tokens),
        )
        if self._pool_sharding is not None:
            # same stability argument as the pools/rng streams: the decode
            # program returns this state re-committed on the params' mesh,
            # and an uncommitted re-upload would flip the input sharding
            arrays = tuple(
                jax.device_put(a, self._pool_sharding) for a in arrays
            )
        self._dev_tables, self._dev_positions, self._dev_tokens = arrays
        self._state_dirty = False
        self.stats["h2d_uploads"] += 1

    def step(self) -> list[Request]:
        """One engine iteration (admit → decode a ``decode_steps`` token
        block → evict); returns the requests that completed during it.

        Its host phases are spans on the flight recorder's ring
        (docs/telemetry.md §spans and scopes): ``atpu/serve/step`` around
        all of it, inside it ``admit`` (with a ``prefill_launch`` and a
        ``prefill_sync`` per admitted request), ``decode_launch``,
        ``decode_sync`` and ``emit`` — none inside a per-token or per-slot
        loop."""
        with flightrec.span("atpu/serve/step", step=self.stats["steps"]) as whole:
            return self._step(whole.fields)

    def _step(self, about: dict) -> list[Request]:
        from .engine import run_decode, run_decode_n

        n = self.config.decode_steps
        if self._injector is not None:
            # deterministic preemption rehearsal (resilience pillar 4):
            # serving_sigterm:step=N delivers a real SIGTERM before engine
            # step N — the guard's sticky flag is then read right below
            self._injector.maybe_serving_sigterm(self.stats["steps"])
        if not self._draining and self._guard is not None and (
            self._guard.triggered or self._guard.deadline_reached()
        ):
            self.drain()
        if self._draining:
            # admission stopped; in-flight requests stay open in the
            # journal for the successor replica
            return []
        admitted = []
        if self._queue:
            with flightrec.span("atpu/serve/admit") as admitting:
                admitted = self._admit()
                admitting.fields["admitted"] = len(admitted)
        if admitted:
            # flight event: admissions (docs/telemetry.md §flight recorder)
            # — in a hang postmortem the last admit/decode_window pair shows
            # whether the engine died admitting or mid-block
            flightrec.record(
                "serving_admit",
                count=len(admitted), queue_depth=len(self._queue),
            )
        completed = [r for r in admitted if r.state == "done"]
        slot_evictions = 0
        emitted = 0
        active = [i for i, r in enumerate(self._slot_req) if r is not None]
        about.update(active=len(active), queue_depth=len(self._queue))
        emitting = None
        load = None  # a mixed plan's expert load, beside the tokens
        uploads_before = self.stats["h2d_uploads"]
        if active:
            flightrec.record(
                "decode_window",
                step=self.stats["steps"], active=len(active), decode_steps=n,
            )
            with flightrec.span("atpu/serve/decode_launch", active=len(active)):
                if n > 1:
                    self._flush_device_state()
                common = dict(
                    family=self.spec.family, cfg=self.spec.cfg,
                    qbits=self._qbits,
                    temperature=float(self.config.temperature),
                    watcher=self.watcher, aot=self._aot,
                )
                # transient-fault retry (docs/serving.md §fault tolerance):
                # the injected/classified-transient fault fires BEFORE the
                # dispatch consumes the donated pools, so a retry re-dispatches
                # the SAME compiled program (zero extra compiles).  A real
                # mid-execution fault that consumed the pools skips straight
                # to eviction-and-requeue, whose re-prefills rebuild all KV.
                dispatched = False
                attempt = 0
                while True:
                    try:
                        if self._injector is not None:
                            self._injector.maybe_decode_fault(self.stats["steps"])
                        if n == 1:
                            # legacy single-token dispatch, byte-identical to the
                            # pre-multi-token service INCLUDING the per-step mirror
                            # uploads: the program must see the exact avals it always
                            # has (fresh uncommitted int arrays), because inputs
                            # committed with a NamedSharding lower to a DIFFERENT HLO
                            # module — an independently compiled binary whose near-tie
                            # argmaxes can drift 1 ulp from generate()'s programs and
                            # break the bitwise parity contract (caught live on a
                            # prepared single-device run; see engine._decode_jit for
                            # the same argument against a length-1 loop variant).  The
                            # uploads are three tiny int arrays; the per-token cost
                            # that matters — the blocking host sync — is unchanged
                            # here and amortized n-fold on the n>1 path below.
                            import jax.numpy as jnp

                            out = run_decode(
                                self._k_pool, self._v_pool, self._g, self._layers,
                                jnp.asarray(self._tables), jnp.asarray(self._positions),
                                jnp.asarray(self._tokens), self._rngs, state=self._state,
                                **common,
                            )
                            self._k_pool, self._v_pool, nxt, self._rngs = out[:4]
                            if self._state is not None:
                                self._state, load = out[4:]
                            self.stats["h2d_uploads"] += 1
                            self._state_dirty = True  # mirrors stay the source of truth
                            tok_block = nxt  # reshaped host-side below
                        else:
                            (self._k_pool, self._v_pool, tok_block, self._dev_positions,
                             self._dev_tokens, self._rngs) = run_decode_n(
                                self._k_pool, self._v_pool, self._g, self._layers,
                                self._dev_tables, self._dev_positions, self._dev_tokens,
                                self._rngs, decode_steps=n, **common,
                            )
                        dispatched = True
                        break
                    except Exception as exc:
                        from ..resilience.backend import backoff_delay
                        from ..resilience.retry import classify_failure

                        if classify_failure(exc) != "transient":
                            raise  # user/program errors propagate unchanged
                        pools_ok = not self._k_pool.is_deleted()
                        if attempt < self.config.max_decode_retries and pools_ok:
                            attempt += 1
                            self.stats["decode_retries"] += 1
                            delay = backoff_delay(
                                attempt, self.config.retry_backoff_s, cap_s=5.0
                            )
                            flightrec.record(
                                "serving_retry", step=self.stats["steps"],
                                attempt=attempt,
                            )
                            if self._hub is not None:
                                self._hub.record_serving_recovery({
                                    "event": "retry", "step": self.stats["steps"],
                                    "attempt": attempt, "wait_ms": delay * 1e3,
                                    "error": f"{type(exc).__name__}: {exc}"[:300],
                                })
                            time.sleep(delay)
                            continue
                        self._requeue_active(
                            "retry_exhausted" if pools_ok else "pools_consumed",
                            error=exc,
                        )
                        break
            if dispatched:
                # THE host sync of the hot loop: one blocking read per
                # n-token block, weighted per active slot for the
                # per-token ratio
                self.stats["host_syncs"] += 1
                self.stats["decode_syncs"] += len(active)
                fed = self._positions[active][:, None] + np.arange(n)  # (active, n)
                walked = int((fed // self.config.block_size + 1).sum())
                tabled = n * len(active) * self._tables.shape[1]
                self.stats["kv_pages_walked"] += walked
                self.stats["kv_pages_tabled"] += tabled
                about.update(kv_pages_walked=walked, kv_pages_tabled=tabled)
                if self._state_layers:
                    states = n * len(active) * self._state_layers
                    self.stats["state_slots_walked"] += states
                    about.update(state_slots_walked=states)
                with flightrec.span("atpu/serve/decode_sync"):
                    block_host = np.asarray(tok_block).reshape(
                        self.config.max_slots, n
                    )
                self._record_load("decode", load, active=len(active))
                # closed at the end of the step: the slot loop and the
                # step's bookkeeping after it
                emitting = flightrec.span("atpu/serve/emit").__enter__()
                for slot in active:
                    req = self._slot_req[slot]
                    emitted_before = len(req.tokens)
                    for j in range(n):
                        tok = int(block_host[slot, j])
                        req.tokens.append(tok)
                        self._positions[slot] += 1
                        self._tokens[slot] = tok
                        emitted += 1
                        if len(req.tokens) >= req.max_new_tokens or (
                            req.eos_token_id is not None
                            and tok == req.eos_token_id
                        ):
                            # tokens past the stop are DISCARDED (never appended
                            # — the block's tail is pad as far as any consumer
                            # can see), and eviction lands at the block
                            # boundary; greedy output stays identical to
                            # generate() at every n
                            if self._journal is not None:
                                self._journal.log_tokens(
                                    req.rid, req.tokens[emitted_before:]
                                )
                            self._evict(slot)
                            self._finish(req)
                            completed.append(req)
                            slot_evictions += 1
                            break
                    else:
                        if self._journal is not None:
                            self._journal.log_tokens(
                                req.rid, req.tokens[emitted_before:]
                            )
        self.stats["decode_tokens"] += emitted
        self.stats["steps"] += 1
        occupancy = len(active) / self.config.max_slots
        self.stats["occupancy_sum"] += occupancy
        if self._hub is not None:
            self._hub.record_serving({
                "event": "step", "step": self.stats["steps"],
                "occupancy": occupancy, "active": len(active),
                "queue_depth": len(self._queue),
                # pool back-pressure rides the step record too: the fleet
                # autopilot's serving signal (docs/elastic.md §autopilot)
                # reads queue depth/occupancy from here, and a full pool is
                # the "queue deep because blocks, not slots" disambiguator
                "pool_free_frac": self.pool_free_frac,
                "admitted": len(admitted),
                # true slot evictions only — a one-token request completing
                # inside _admit never held a decode slot and is visible in
                # "completed", not here (slot-churn consumers cross-check
                # evicted against occupancy)
                "evicted": slot_evictions,
                "completed": len(completed),
                # device-resident hot-loop accounting (docs/telemetry.md):
                # block size, tokens actually emitted to requests this step
                # (overrun tokens past a stop are discarded, not emitted),
                # and whether this step re-committed host state
                "decode_steps": n,
                "emitted": emitted,
                "h2d_upload": self.stats["h2d_uploads"] > uploads_before,
            })
        if emitting is not None:
            emitting.fields.update(emitted=emitted, completed=len(completed))
            emitting.__exit__(None, None, None)
        return completed

    def run(self, max_steps: Optional[int] = None) -> dict[int, Request]:
        """Drive ``step()`` until the queue and every slot drain (or
        ``max_steps``); returns ``{rid: Request}`` for everything finished."""
        steps = 0
        while self.has_work and not self._draining:
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return dict(self.results)

    # -- accounting ----------------------------------------------------------
    def fleet_signal(self) -> dict:
        """The serving half of the fleet autopilot's input (docs/elastic.md
        §autopilot): instantaneous queue depth, occupancy and pool
        back-pressure — pure host reads, safe from any thread.  The same
        numbers ride every ``kind="serving"`` step record, which is where a
        training-colocated autopilot actually samples them (the records are
        rank-retained; this accessor is the direct/standalone form)."""
        return {
            "queue_depth": len(self._queue),
            "occupancy": self.active_slots / self.config.max_slots,
            "pool_free_frac": self.pool_free_frac,
        }

    def metrics(self) -> dict:
        """Live scrape snapshot (the metrics endpoint and tests share it):
        instantaneous occupancy/queue/pool gauges plus TTFT/TPOT p50/p99
        over the sliding completion window.  Pure host reads — safe to call
        from the endpoint's thread while the service is stepping."""
        # the stepping thread appends completions concurrently, and a deque
        # raises on mutation-during-iteration — retry the snapshot (capped:
        # a scrape must never spin against a hot completion stream), and
        # surface cap exhaustion as a flight event + counter so a
        # percentile-less scrape is diagnosable, not silent
        window: list = []
        for _ in range(_METRICS_SNAPSHOT_RETRIES):
            try:
                window = list(self._latency_window)
                break
            except RuntimeError:
                continue
        else:
            self.stats["metrics_snapshot_retry_exhausted"] += 1
            flightrec.record(
                "metrics_snapshot_retry_exhausted",
                retries=_METRICS_SNAPSHOT_RETRIES,
            )
        out = {
            "occupancy": self.active_slots / self.config.max_slots,
            "slots_active": self.active_slots,
            "slots_total": self.config.max_slots,
            "queue_depth": len(self._queue),
            "queue_peak": self.stats["queue_peak"],
            "block_pool_free_frac": self.pool_free_frac,
            "steps_total": self.stats["steps"],
            "admitted_total": self.stats["admitted"],
            "completed_total": self.stats["completed"],
            "recompile_events_total": self.recompile_events,
            # device-resident decode counters (docs/telemetry.md §serving):
            # syncs/token is the dispatch-overhead gauge — 1.0 on the
            # classic path, ~1/n with an n-token block; h2d uploads stay
            # flat while the batch is steady
            "decode_steps": self.config.decode_steps,
            "decode_tokens_total": self.stats["decode_tokens"],
            "host_syncs_total": self.stats["host_syncs"],
            "h2d_uploads_total": self.stats["h2d_uploads"],
            "host_syncs_per_token": round(self.host_syncs_per_token, 4),
            "latency_window": len(window),
            # fault-tolerance counters (docs/serving.md §fault tolerance)
            "shed_total": self.stats["shed"],
            "recovered_total": self.stats["recovered"],
            "decode_retries_total": self.stats["decode_retries"],
            "requeued_total": self.stats["requeued"],
            "metrics_snapshot_retry_exhausted_total": self.stats[
                "metrics_snapshot_retry_exhausted"
            ],
            "draining": self._draining,
            # native histograms (cumulative over the service lifetime);
            # the p50/p99 gauges below stay for human eyeballs — dashboards
            # should quantile() the _bucket series instead
            "ttft_ms": self._ttft_hist,
            "tpot_ms": self._tpot_hist,
        }
        ttfts = sorted(t for t, _ in window if t is not None)
        tpots = sorted(p for _, p in window if p is not None)
        for name, values in (("ttft_ms", ttfts), ("tpot_ms", tpots)):
            if values:
                out[f"{name}_p50"] = values[int(0.50 * (len(values) - 1))]
                out[f"{name}_p99"] = values[int(0.99 * (len(values) - 1))]
        return out

    @property
    def mean_batch_occupancy(self) -> float:
        return self.stats["occupancy_sum"] / max(1, self.stats["steps"])

    @property
    def host_syncs_per_token(self) -> float:
        """Blocking device→host syncs a sequence experiences per emitted
        DECODE token — the dispatch-overhead gauge ``tests/test_serving.py``
        asserts on: exactly 1.0 on the classic per-token path,
        ~1/n with an n-token device-resident block (slightly above 1/n
        when stops discard overrun tokens).  Each decode sync counts once
        per active slot, so the ratio is batch-size independent; prefill's
        per-request first-token sync is per-request, not per-token, so it
        rides ``stats["host_syncs"]`` but not this ratio."""
        return self.stats["decode_syncs"] / max(1, self.stats["decode_tokens"])

    @property
    def recompile_events(self) -> int:
        """Post-warmup program builds — 0 is the steady-state contract."""
        return self.watcher.recompile_events
