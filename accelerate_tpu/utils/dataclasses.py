"""Config & plugin dataclasses (the L5 layer).

Behavioural counterpart of ``/root/reference/src/accelerate/utils/dataclasses.py``
(2620 LoC).  The big inversion versus the reference: torch's ten
``DistributedType`` backends collapse on TPU into *mesh-axis layouts of one SPMD
program*, so plugins here resolve to mesh axis sizes + sharding rules instead of
wrapper-module configs.  Env-var fallbacks in ``__post_init__`` keep the
launcher↔child env protocol (reference dataclasses.py:1635-1727).
"""

from __future__ import annotations

import enum
import os
import warnings
from dataclasses import dataclass, field
from datetime import timedelta
from typing import Any, Callable, Iterable, Optional

from .environment import parse_flag_from_env, str_to_bool


class BaseEnum(str, enum.Enum):
    def __str__(self) -> str:  # YAML/env round-trip friendly
        return self.value

    @classmethod
    def list(cls) -> list[str]:
        return [e.value for e in cls]


class DistributedType(BaseEnum):
    """How this process participates in distributed execution.

    Reference enum: dataclasses.py:552.  The torch backends (MULTI_GPU,
    DEEPSPEED, MEGATRON_LM, ...) have no meaning on a PJRT stack; what remains
    is NO (single process, possibly many local devices under SPMD) vs
    MULTI_HOST (jax.distributed across hosts), with the parallelism *strategy*
    expressed by `ParallelismConfig` rather than by backend.
    """

    NO = "NO"
    TPU = "TPU"  # single-host SPMD over local TPU devices
    MULTI_HOST = "MULTI_HOST"  # jax.distributed over DCN, SPMD within/across slices


class PrecisionType(BaseEnum):
    NO = "no"
    FP8 = "fp8"
    FP16 = "fp16"
    BF16 = "bf16"


class RNGType(BaseEnum):
    JAX = "jax"
    NUMPY = "numpy"
    PYTHON = "python"
    TORCH = "torch"
    GENERATOR = "generator"


class LoggerType(BaseEnum):
    ALL = "all"
    TENSORBOARD = "tensorboard"
    WANDB = "wandb"
    COMETML = "comet_ml"
    AIM = "aim"
    MLFLOW = "mlflow"
    CLEARML = "clearml"
    DVCLIVE = "dvclive"
    SWANLAB = "swanlab"
    JSONL = "jsonl"  # native dependency-free tracker


class SaveFormat(BaseEnum):
    SAFETENSORS = "safetensors"
    MSGPACK = "msgpack"
    ORBAX = "orbax"


class ComputeBackend(BaseEnum):
    """Where a jitted step should be lowered."""

    AUTO = "auto"
    TPU = "tpu"
    CPU = "cpu"


def env_int(name, default):
    """Integer env knob with the observability-grade failure mode: unset or
    empty reads as the default, and a malformed value WARNS and falls back
    instead of raising mid-``__init__`` — one parser shared by every
    integer env knob (telemetry cadence/ports, serving decode_steps, bench
    A/B legs) so empty-string and typo semantics can never drift apart."""
    value = os.environ.get(name)
    if value is None or value == "":
        return default
    try:
        return int(value)
    except ValueError:
        warnings.warn(f"{name}={value!r} is not an integer; ignoring")
        return default


def env_float(name, default):
    """Float env knob with the same failure mode as :func:`env_int` (the
    watchdog deadline is fractional-seconds-valued in tests)."""
    value = os.environ.get(name)
    if value is None or value == "":
        return default
    try:
        return float(value)
    except ValueError:
        warnings.warn(f"{name}={value!r} is not a number; ignoring")
        return default


# ---------------------------------------------------------------------------
# Kwargs handlers (typed pass-throughs; reference dataclasses.py:62-551)
# ---------------------------------------------------------------------------
class KwargsHandler:
    def to_dict(self) -> dict[str, Any]:
        return {k: v for k, v in self.__dict__.items()}

    def to_kwargs(self) -> dict[str, Any]:
        default = self.__class__()
        return {
            k: v for k, v in self.__dict__.items() if getattr(default, k) != v
        }


@dataclass
class AutocastKwargs(KwargsHandler):
    """Controls the mixed-precision policy applied to jitted computation.

    Reference: AutocastKwargs dataclasses.py:107 (torch.autocast args).  On
    TPU the policy is a dtype trio (param/compute/output) applied at trace
    time — there is no context-manager autocast in XLA.
    """

    enabled: bool = True
    cache_enabled: bool = True  # accepted for API parity; no-op under XLA


@dataclass
class GradScalerKwargs(KwargsHandler):
    """Dynamic loss-scaling config for fp16 (reference dataclasses.py:226).

    bf16 — the TPU default — needs no scaling; these values feed
    ``DynamicLossScale`` only when ``mixed_precision='fp16'`` is requested.
    """

    init_scale: float = 65536.0
    growth_factor: float = 2.0
    backoff_factor: float = 0.5
    growth_interval: int = 2000
    enabled: bool = True


@dataclass
class InitProcessGroupKwargs(KwargsHandler):
    """jax.distributed.initialize knobs (reference dataclasses.py:257)."""

    backend: Optional[str] = "pjrt"
    init_method: Optional[str] = None
    timeout: Optional[timedelta] = None
    coordinator_address: Optional[str] = None
    num_processes: Optional[int] = None
    process_id: Optional[int] = None


@dataclass
class ProfileKwargs(KwargsHandler):
    """jax.profiler trace options (reference ProfileKwargs dataclasses.py:436).

    ``output_trace_dir`` receives a TensorBoard-loadable trace; `on_trace_ready`
    is invoked with the dir after collection.
    """

    output_trace_dir: Optional[str] = None
    with_flops: bool = False
    record_shapes: bool = False
    profile_memory: bool = False
    python_tracer_level: int = 1
    host_tracer_level: int = 2
    device_tracer_level: int = 1
    on_trace_ready: Optional[Callable] = None


@dataclass
class TelemetryKwargs(KwargsHandler):
    """Runtime-telemetry knobs (``accelerator.telemetry``, docs/telemetry.md).

    No reference counterpart — the observability layer is TPU-native.  When
    ``enabled`` is left ``None`` it resolves from ``$ACCELERATE_TELEMETRY``
    (default off); off means the capture path runs its pre-telemetry code
    byte-for-byte (no timers, no ring-buffer writes).

    ``timeline_size`` bounds the per-step ring buffer; ``max_events`` bounds
    each event stream (recompiles / program stats / resource samples);
    ``sample_resources`` additionally snapshots per-device live bytes at
    every capture (the capture phases' xprof spans are the flight
    recorder's, always on: docs/telemetry.md §spans and scopes);
    ``jsonl_path`` (or ``$ACCELERATE_TELEMETRY_JSONL``) auto-dumps
    the full history at ``end_training``/tracker ``finish``.

    ``profile_every_n`` (or ``$ACCELERATE_TELEMETRY_PROFILE_N``; 0 = off)
    samples device-time attribution: every Nth captured call runs inside a
    ``jax.profiler`` trace session and blocks until the device drains, so
    the sampled step's per-device busy/idle + compute/collective/transfer
    split lands as a ``DeviceStepRecord`` (docs/telemetry.md §device time —
    the sampled call pays the sync, every other call keeps the async
    pipeline).  ``profile_dir`` (``$ACCELERATE_TELEMETRY_PROFILE_DIR``)
    keeps the raw xprof dumps on disk instead of deleting them after
    parsing.  ``metrics_port`` (``$ACCELERATE_METRICS_PORT``; 0 = ephemeral
    port) serves live Prometheus text on ``/metrics``.

    ``watchdog_s`` (``$ACCELERATE_WATCHDOG_S``; default off) arms the hang
    watchdog (telemetry/watchdog.py): a background thread with that many
    seconds of budget around every blocking collective/device sync, dumping
    faulthandler stacks plus the flight-recorder ring to a per-rank JSON
    under ``blackbox_dir`` (``$ACCELERATE_BLACKBOX_DIR``, default
    ``blackbox/``) on stall, fatal signal, or exit.  The watchdog arms even
    when ``enabled`` is off — hang forensics must not require the full
    telemetry pipeline.  ``trace_export_path``
    (``$ACCELERATE_TRACE_EXPORT``; default off) writes the joined
    Chrome/Perfetto timeline (telemetry/trace_export.py) at
    ``end_training``.  The flight recorder itself has no knob here: it is
    on by default process-wide (``$ACCELERATE_FLIGHTREC=0`` kills it).
    """

    enabled: Optional[bool] = None  # None → $ACCELERATE_TELEMETRY, default off
    timeline_size: int = 256
    max_events: int = 256
    sample_resources: bool = True
    jsonl_path: Optional[str] = None
    profile_every_n: Optional[int] = None  # None → env, default 0 (off)
    profile_dir: Optional[str] = None
    metrics_port: Optional[int] = None  # None → env, default no endpoint
    watchdog_s: Optional[float] = None  # None → env, default off
    blackbox_dir: Optional[str] = None  # None → env, default "blackbox"
    trace_export_path: Optional[str] = None  # None → env, default off

    def __post_init__(self):
        if self.enabled is None:
            value = os.environ.get("ACCELERATE_TELEMETRY")
            self.enabled = bool(str_to_bool(value)) if value is not None else False
        if self.jsonl_path is None:
            self.jsonl_path = os.environ.get("ACCELERATE_TELEMETRY_JSONL")
        # observability knobs must not kill the job: a malformed env value
        # warns and leaves the feature off instead of raising mid-__init__
        if self.profile_every_n is None:
            self.profile_every_n = self._env_int("ACCELERATE_TELEMETRY_PROFILE_N", 0)
        if self.profile_dir is None:
            self.profile_dir = os.environ.get("ACCELERATE_TELEMETRY_PROFILE_DIR")
        if self.metrics_port is None:
            self.metrics_port = self._env_int("ACCELERATE_METRICS_PORT", None)
        if self.watchdog_s is None:
            self.watchdog_s = env_float("ACCELERATE_WATCHDOG_S", None)
        if self.blackbox_dir is None:
            self.blackbox_dir = os.environ.get("ACCELERATE_BLACKBOX_DIR", "blackbox")
        if self.trace_export_path is None:
            self.trace_export_path = os.environ.get("ACCELERATE_TRACE_EXPORT")

    @staticmethod
    def _env_int(name, default):
        return env_int(name, default)


@dataclass
class ResilienceKwargs(KwargsHandler):
    """Resilience-subsystem knobs (``accelerator.resilience``,
    docs/resilience.md).

    No reference counterpart — preemption handling lives in PyTorch/XLA and
    torchelastic externally; here it is library behavior.  When ``enabled``
    is left ``None`` it resolves from ``$ACCELERATE_RESILIENCE`` (default
    off); off means the capture hot path runs its pre-resilience code
    byte-for-byte (one ``None``-check, matching the telemetry precedent).

    ``preemption`` installs SIGTERM/SIGINT sticky-flag handlers read via
    ``resilience.should_save``/``should_exit``; ``deadline_s`` additionally
    trips those flags N seconds after construction (maintenance windows).
    ``retry``/``max_retries``/``retry_backoff_s`` bound the transient-fault
    retry around captured-step dispatch; ``rollback`` restores the last good
    checkpoint on exhaustion and replays.  ``checkpoint_dir`` is the default
    ``resilience.drain()`` target.  ``fault_plan`` wires the test-only
    deterministic injector (``$ACCELERATE_FAULT_PLAN``).  Backend-init
    hardening is its own entry point (``resilience.backend.init_backend`` +
    ``$ACCELERATE_RESILIENCE_INIT`` at state construction) because it must
    run before any jax device call.
    """

    enabled: Optional[bool] = None  # None → $ACCELERATE_RESILIENCE, default off
    preemption: bool = True
    deadline_s: Optional[float] = None  # $ACCELERATE_RESILIENCE_DEADLINE_S
    retry: bool = True
    max_retries: int = 2  # $ACCELERATE_RESILIENCE_MAX_RETRIES
    retry_backoff_s: float = 0.5  # $ACCELERATE_RESILIENCE_RETRY_BACKOFF_S
    rollback: bool = True
    checkpoint_dir: Optional[str] = None  # $ACCELERATE_RESILIENCE_CHECKPOINT_DIR
    fault_plan: Optional[str] = None  # $ACCELERATE_FAULT_PLAN (test-only)

    def __post_init__(self):
        env = os.environ
        if self.enabled is None:
            value = env.get("ACCELERATE_RESILIENCE")
            self.enabled = bool(str_to_bool(value)) if value is not None else False
        if self.deadline_s is None and "ACCELERATE_RESILIENCE_DEADLINE_S" in env:
            self.deadline_s = float(env["ACCELERATE_RESILIENCE_DEADLINE_S"])
        if "ACCELERATE_RESILIENCE_MAX_RETRIES" in env:
            self.max_retries = int(env["ACCELERATE_RESILIENCE_MAX_RETRIES"])
        if "ACCELERATE_RESILIENCE_RETRY_BACKOFF_S" in env:
            self.retry_backoff_s = float(env["ACCELERATE_RESILIENCE_RETRY_BACKOFF_S"])
        if self.checkpoint_dir is None:
            self.checkpoint_dir = env.get("ACCELERATE_RESILIENCE_CHECKPOINT_DIR")
        if self.fault_plan is None:
            self.fault_plan = env.get("ACCELERATE_FAULT_PLAN")


@dataclass
class FleetKwargs(KwargsHandler):
    """Elastic-fleet-runtime knobs (``accelerator.fleet``, docs/elastic.md).

    No reference counterpart — this is the torchelastic-style "survive and
    resize" composition over the resilience/checkpoint/AOT-cache subsystems.
    When ``enabled`` is left ``None`` it resolves from ``$ACCELERATE_FLEET``
    (default off); off means the capture hot path runs its pre-fleet code
    byte-for-byte (one ``None``-check, matching the telemetry/resilience/
    aot-cache precedent).

    ``coordinate_rollback`` arms the multi-host restore protocol: on retry
    exhaustion every rank offers its visible complete checkpoints to a
    gather/vote barrier and all ranks issue the collective ``load_state``
    against the agreed restore point — replacing the resilience layer's
    single-process-only rollback refusal.  ``elastic`` arms dp resize: a
    lost host (``host_lost`` fault-plan verb, or a real reclamation notice)
    trips ``fleet.should_resize`` and ``fleet.resize()`` drains → re-meshes
    at the surviving topology → reshards ZeRO-1 masters/moments (and
    compression residuals) from the spec-carrying checkpoint → prewarms the
    new-topology programs from the AOT cache; a returned host
    (``host_gained``) trips ``fleet.should_grow`` and ``fleet.grow()``
    re-meshes dp *up* through the grow rendezvous.  ``min_dp`` refuses
    resizes below that dp extent.  ``aggregate_every_n`` (dispatches;
    0 = off) graduates ``telemetry.aggregate_fleet()`` to periodic mid-run
    skew/straggler records — the autoscaler/resize signal.  ``autopilot``
    arms the signal-driven autoscaler (docs/elastic.md §autopilot):
    ``True``/``"on"`` for the default policy, a ``"key=value,..."`` spec
    string (``"skew_pct=150,window=4,hysteresis=0.2,cooldown=8"``), a dict
    of the same knobs, or a ready ``fleet.AutopilotPolicy``; resolves from
    ``$ACCELERATE_FLEET_AUTOPILOT`` when left ``None`` (default off) —
    explicit kwargs beat the env, and BAD VALUES RAISE HERE, at
    construction, never at the first fire.  ``checkpoint_dir`` is the
    default drain target for resize; ``fault_plan`` wires the test-only
    injector (``$ACCELERATE_FAULT_PLAN``; the ``host_lost`` /
    ``host_gained`` / ``signal_storm`` verbs are consumed here — the rest
    belong to resilience).
    """

    enabled: Optional[bool] = None  # None → $ACCELERATE_FLEET, default off
    coordinate_rollback: bool = True
    elastic: bool = True
    min_dp: int = 1  # $ACCELERATE_FLEET_MIN_DP
    aggregate_every_n: int = 0  # $ACCELERATE_FLEET_AGGREGATE_N
    autopilot: Optional[object] = None  # None → $ACCELERATE_FLEET_AUTOPILOT, off
    checkpoint_dir: Optional[str] = None  # $ACCELERATE_FLEET_CHECKPOINT_DIR
    fault_plan: Optional[str] = None  # $ACCELERATE_FAULT_PLAN (test-only)

    def __post_init__(self):
        env = os.environ
        if self.enabled is None:
            value = env.get("ACCELERATE_FLEET")
            self.enabled = bool(str_to_bool(value)) if value is not None else False
        if "ACCELERATE_FLEET_MIN_DP" in env:
            self.min_dp = int(env["ACCELERATE_FLEET_MIN_DP"])
        if "ACCELERATE_FLEET_AGGREGATE_N" in env:
            self.aggregate_every_n = int(env["ACCELERATE_FLEET_AGGREGATE_N"])
        if self.autopilot is None:
            self.autopilot = env.get("ACCELERATE_FLEET_AUTOPILOT")
        # resolve (and VALIDATE) the policy now: a bad threshold must raise
        # at Accelerator construction, not at the autopilot's first fire
        from ..fleet.autopilot import AutopilotPolicy

        self.autopilot_policy = AutopilotPolicy.resolve(self.autopilot)
        if self.checkpoint_dir is None:
            self.checkpoint_dir = env.get("ACCELERATE_FLEET_CHECKPOINT_DIR")
        if self.fault_plan is None:
            self.fault_plan = env.get("ACCELERATE_FAULT_PLAN")


@dataclass
class CompressionKwargs(KwargsHandler):
    """dp-axis collective compression knobs (docs/compression.md).

    One surface for BOTH compression stories: ``policy`` selects a
    ``parallel.compress.CompressionPolicy`` —

    * ``"none"`` (default) — every path byte-identical to the
      pre-compression library;
    * ``"int8"`` / ``"fp8"`` — quantize the ZeRO-1 reduce-scatter /
      all-gather pair inside the captured step (per-block scales, dp-sharded
      error-feedback residuals threaded like optax moments);
    * ``"powersgd"`` / ``"batched_powersgd"`` — rank-k + error-feedback
      compression at the backward sync boundary (the reference comm hook,
      now policy-selected; legacy ``DistributedDataParallelKwargs(
      comm_hook=...)`` resolves to the same policy object).

    ``min_size``/``min_block`` are the eligibility gates (tensors below
    them pass through uncompressed); ``error_feedback`` toggles the
    residual; the ``powersgd_*`` knobs mirror torch's ``PowerSGDState``
    options.  When ``policy`` is left ``None`` it resolves from
    ``$ACCELERATE_COMPRESSION`` (default ``"none"``).
    """

    policy: Optional[str] = None  # None → $ACCELERATE_COMPRESSION, default none
    min_size: int = 2048
    min_block: int = 8
    error_feedback: bool = True
    powersgd_rank: int = 1
    powersgd_warm_start: bool = True
    powersgd_wrapper: Optional[str] = None  # "fp16" | "bf16"

    def __post_init__(self):
        if self.policy is None:
            self.policy = os.environ.get("ACCELERATE_COMPRESSION", "none")
        self.policy = str(self.policy).lower()


@dataclass
class CompilationCacheKwargs(KwargsHandler):
    """Persistent AOT executable cache knobs (``accelerator.aot_cache``,
    docs/aot_cache.md).

    No reference counterpart — compiled-program persistence is an XLA-native
    concern.  ``cache_dir`` names the on-disk store; when left ``None`` it
    resolves from ``$ACCELERATE_AOT_CACHE`` (unset = cache off).  Off means
    the capture/serving hot paths run their pre-cache code byte-for-byte
    (one ``None``-check, matching the telemetry/resilience precedent).

    Every compiled captured program (and every serving prefill/decode bucket
    program) is serialized via ``jax.experimental.serialize_executable`` into
    a content-addressed entry keyed on the capture cache key extended with a
    topology/compiler fingerprint (jax/jaxlib version, platform, device
    kind+count, process count, mesh shape, donation split, compression
    policy).  A later process with a matching fingerprint deserializes the
    executable and skips trace+compile entirely; ANY mismatch falls through
    to a normal compile with a loud ``kind="aot_cache"`` miss record.

    ``max_bytes`` bounds the store (LRU eviction, ``$ACCELERATE_AOT_CACHE_
    MAX_BYTES``); ``warm_on_restore`` prefetches matching entries into
    memory during ``load_state`` (the resilience rollback / preemption-resume
    path) so restore-after-fault replays the serialized executable without a
    step-path disk read.  ``jax_cache_dir`` additionally arms jax's own
    persistent XLA compilation cache (``$ACCELERATE_AOT_CACHE_JAX_DIR``) as
    a second layer for programs outside the capture path — at that
    directory unless ``$JAX_COMPILATION_CACHE_DIR`` is set, which wins
    (``utils.environment.compilation_cache_dir``, the one placement rule).
    """

    cache_dir: Optional[str] = None  # None → $ACCELERATE_AOT_CACHE, unset = off
    enabled: Optional[bool] = None  # None → on iff cache_dir resolves
    max_bytes: int = 2 << 30  # $ACCELERATE_AOT_CACHE_MAX_BYTES
    warm_on_restore: bool = True
    jax_cache_dir: Optional[str] = None  # $ACCELERATE_AOT_CACHE_JAX_DIR

    def __post_init__(self):
        env = os.environ
        if self.cache_dir is None:
            value = env.get("ACCELERATE_AOT_CACHE")
            # "0"/"false" must read as "off", not as a relative cache dir
            if value and value.lower() not in ("0", "false", "no", "off"):
                self.cache_dir = value
        if self.enabled is None:
            self.enabled = self.cache_dir is not None
        if "ACCELERATE_AOT_CACHE_MAX_BYTES" in env:
            try:
                self.max_bytes = int(env["ACCELERATE_AOT_CACHE_MAX_BYTES"])
            except ValueError:
                warnings.warn(
                    "ACCELERATE_AOT_CACHE_MAX_BYTES="
                    f"{env['ACCELERATE_AOT_CACHE_MAX_BYTES']!r} is not an "
                    "integer; keeping the default"
                )
        if self.jax_cache_dir is None:
            self.jax_cache_dir = env.get("ACCELERATE_AOT_CACHE_JAX_DIR")


@dataclass
class KernelKwargs(KwargsHandler):
    """Pallas hot-path kernel knobs (``accelerator.kernels``,
    docs/kernels.md).

    No reference counterpart — custom-kernel fusion is an XLA/Mosaic-native
    concern.  ``kernels`` names the armed set: a comma/plus-separated
    subset of ``collective_matmul`` (the ZeRO-1 all-gather as a chunked
    ring feeding partial matmuls) and ``quantized_rs`` (compress.py's
    per-block scale+round fused into one kernel region at the shard
    boundary, plus the stochastic-rounding ZeRO-2 wire); ``all`` arms
    both.  When left ``None`` it resolves from ``$ACCELERATE_KERNELS``
    (default off) — off means every hot path runs its pre-kernel code
    byte-for-byte, matching the telemetry/resilience/aot-cache/fleet
    precedent.

    ``interpret`` forces the Pallas lowering mode; ``None`` (default)
    resolves to interpreter mode off-TPU (bitwise-testable StableHLO, the
    tier-1 surface) and compiled Mosaic on TPU.  The AOT cache fingerprint
    keys on the armed set, so flipping a kernel is a loud miss, never a
    stale executable.
    """

    kernels: Optional[str] = None  # None → $ACCELERATE_KERNELS, default off
    interpret: Optional[bool] = None  # None → auto (off-TPU: interpreter)

    def __post_init__(self):
        if self.kernels is None:
            self.kernels = os.environ.get("ACCELERATE_KERNELS", "")
        self.kernels = str(self.kernels).lower()
        if self.interpret is None and "ACCELERATE_KERNELS_INTERPRET" in os.environ:
            self.interpret = bool(
                str_to_bool(os.environ["ACCELERATE_KERNELS_INTERPRET"])
            )


@dataclass
class DistributedDataParallelKwargs(KwargsHandler):
    """Accepted for API parity with the reference (dataclasses.py:149).

    Under SPMD there is no DDP wrapper; gradient bucketing/overlap is the XLA
    scheduler's job.  ``gradient_as_bucket_view`` etc. are accepted and
    ignored; ``comm_hook`` ("fp16"/"bf16") compresses synced gradients at
    the backward boundary — half-width grad buffers and downstream
    consumers — and "powersgd"/"batched_powersgd" run rank-k compression
    with error feedback there (utils/powersgd.py); see
    Accelerator._apply_comm_hook for exactly what this does and does not
    change about XLA's collective dtypes.

    ``comm_wrapper`` ("fp16"/"bf16") composes with the PowerSGD hooks the
    way the reference's fp16/bf16 wrappers compose with powerSGD_hook: the
    transported low-rank factors are rounded through that dtype.
    ``comm_state_option`` carries the PowerSGDState options
    (``matrix_approximation_rank``, ``use_error_feedback``, ``warm_start``;
    ``start_powerSGD_iter`` is accepted and ignored — compression runs from
    step 0, see utils/powersgd.py).  Reference: dataclasses.py:137-215.
    """

    bucket_cap_mb: int = 25
    find_unused_parameters: bool = False
    gradient_as_bucket_view: bool = False
    static_graph: bool = False
    comm_hook: Optional[str] = None  # "fp16"|"bf16"|"powersgd"|"batched_powersgd"
    comm_wrapper: Optional[str] = None  # "fp16" | "bf16" wrapper for powersgd
    comm_state_option: dict = field(default_factory=dict)


# ---------------------------------------------------------------------------
# Plugins
# ---------------------------------------------------------------------------
@dataclass
class GradientAccumulationPlugin(KwargsHandler):
    """Reference: dataclasses.py:779."""

    num_steps: Optional[int] = None
    adjust_scheduler: bool = True
    sync_with_dataloader: bool = True
    sync_each_batch: bool = False


@dataclass
class ProjectConfiguration:
    """Checkpoint/logging directory layout (reference dataclasses.py:857)."""

    project_dir: Optional[str] = None
    logging_dir: Optional[str] = None
    automatic_checkpoint_naming: bool = False
    total_limit: Optional[int] = None
    iteration: int = 0
    save_on_each_node: bool = False

    def __post_init__(self):
        if self.logging_dir is None:
            self.logging_dir = self.project_dir

    def set_directories(self, project_dir: Optional[str] = None):
        self.project_dir = project_dir
        if self.logging_dir is None:
            self.logging_dir = project_dir


@dataclass
class DataLoaderConfiguration:
    """Reference: dataclasses.py:789 (DataLoaderConfiguration)."""

    split_batches: bool = False
    dispatch_batches: Optional[bool] = None
    even_batches: bool = True
    use_seedable_sampler: bool = False
    data_seed: Optional[int] = None
    non_blocking: bool = False  # parity; device feed is always async on TPU
    use_stateful_dataloader: bool = False
    prefetch_size: int = 2  # device prefetch depth (MpDeviceLoader analog)


@dataclass
class FullyShardedDataParallelPlugin:
    """ZeRO/FSDP expressed as GSPMD sharding on the ``fsdp`` mesh axis.

    User-facing surface mirrors the reference plugin
    (dataclasses.py:1449-1863); the lowering is a NamedSharding rule-set, not a
    wrapper module.  ``sharding_strategy``:
      FULL_SHARD      → params+grads+optimizer sharded (ZeRO-3)
      SHARD_GRAD_OP   → grads+optimizer sharded, params replicated (ZeRO-2)
      NO_SHARD        → pure DP
      HYBRID_SHARD    → shard within a slice, replicate across slices
    """

    sharding_strategy: str = "FULL_SHARD"
    reshard_after_forward: bool = True
    fsdp_size: Optional[int] = None  # mesh axis size; None → all devices
    auto_wrap_policy: Optional[str] = "transformer_based_wrap"
    transformer_cls_names_to_wrap: Optional[list[str]] = None
    min_num_params: int = 0
    # training-time parameter offload (torch FSDP CPUOffload(offload_params)
    # / DeepSpeed ZeRO-Infinity offload_param, reference
    # dataclasses.py:1082-1090): fsdp-sharded params live in pinned host
    # memory between steps and are staged back by a forward hook traced into
    # the captured step (hooks.ParamOffloadHook).  Env: FSDP_OFFLOAD_PARAMS.
    cpu_offload: bool = False
    state_dict_type: str = "SHARDED_STATE_DICT"  # or FULL_STATE_DICT
    use_orig_params: bool = True  # parity; always true functionally
    # MixedPrecisionPolicy analog (reference dataclasses.py:1449):
    # param_dtype = per-plugin compute dtype for sharded params ("bf16"/
    # "fp16"/"fp32"); reduce_dtype = synced-gradient dtype, applied through
    # the same boundary as DistributedDataParallelKwargs.comm_hook
    param_dtype: Optional[str] = None
    reduce_dtype: Optional[str] = None
    activation_checkpointing: bool = False
    # host-offloaded optimizer state (reference dataclasses.py:1019
    # offload_optimizer via DeepSpeed; torch FSDP CPUOffload): Adam moments
    # and fp32 masters live in pinned host memory, streamed to the chip only
    # for the update — HBM then holds params+grads+activations only.  Pays a
    # host<->device round-trip per sync step; for models whose optimizer
    # state doesn't fit even fsdp-sharded.
    offload_optimizer: bool = False

    _DTYPES = {"bf16": "bfloat16", "fp16": "float16", "fp32": "float32",
               "bfloat16": "bfloat16", "float16": "float16", "float32": "float32"}

    def resolved_dtype(self, field_name: str):
        """jnp dtype for param_dtype/reduce_dtype, or None when unset."""
        value = getattr(self, field_name)
        if value is None:
            return None
        import jax.numpy as jnp

        key = self._DTYPES.get(str(value).lower())
        if key is None:
            raise ValueError(
                f"{field_name}={value!r}: use one of bf16/fp16/fp32"
            )
        return jnp.dtype(key)

    def __post_init__(self):
        env = os.environ
        self.sharding_strategy = env.get(
            "FSDP_SHARDING_STRATEGY", self.sharding_strategy
        ).upper()
        if "FSDP_OFFLOAD_PARAMS" in env:
            self.cpu_offload = bool(str_to_bool(env["FSDP_OFFLOAD_PARAMS"]))
        if "FSDP_OFFLOAD_OPTIMIZER" in env:
            self.offload_optimizer = bool(str_to_bool(env["FSDP_OFFLOAD_OPTIMIZER"]))
        self.state_dict_type = env.get(
            "FSDP_STATE_DICT_TYPE", self.state_dict_type
        ).upper()
        if self.transformer_cls_names_to_wrap is None:
            names = env.get("FSDP_TRANSFORMER_CLS_TO_WRAP", "")
            self.transformer_cls_names_to_wrap = (
                [n.strip() for n in names.split(",") if n.strip()] or None
            )
        if self.fsdp_size is None and "FSDP_SIZE" in env:
            self.fsdp_size = int(env["FSDP_SIZE"])
        if "FSDP_ACTIVATION_CHECKPOINTING" in env:
            self.activation_checkpointing = bool(
                str_to_bool(env["FSDP_ACTIVATION_CHECKPOINTING"])
            )
        # fail on dtype typos at construction, not at the first sync backward
        self.resolved_dtype("param_dtype")
        self.resolved_dtype("reduce_dtype")


@dataclass
class DataParallelPlugin:
    """Knobs for the plain ``dp`` mesh axis.

    ``zero1`` shards the *weight update* cross-replica (ZeRO-1,
    arXiv:2004.13336): fp32 masters and optax moments get a NamedSharding
    over the dp axis, so GSPMD lowers the captured step to reduce-scatter →
    shard-local update → all-gather inside the one XLA program.  Per-replica
    optimizer-state HBM drops to ~1/dp and the update math is deduplicated;
    params, grads and the user-visible API are untouched.

    ``None`` (default) = automatic: on whenever dp > 1 and no ``fsdp`` axis
    already owns the params (FULL_SHARD/HYBRID_SHARD state follows the
    params, making ZeRO-1 redundant there).  Env: ACCELERATE_ZERO1.

    ``zero2`` additionally keeps the *accumulated gradients* reduce-
    scattered between micro-steps under gradient accumulation, so the
    accumulation buffer is also ~1/dp per replica (docs/compression.md).
    Opt-in (default off) because it changes the ``.grad`` layout contract:
    between micro-steps ``param.grad`` is a dp-sharded global array (same
    values, 1/dp resident bytes) rather than a replicated one.  Requires
    ZeRO-1 to be active (sharded grads feed the sharded update directly).
    Env: ACCELERATE_ZERO2.
    """

    zero1: Optional[bool] = None
    zero2: Optional[bool] = None

    def __post_init__(self):
        if self.zero1 is None and "ACCELERATE_ZERO1" in os.environ:
            self.zero1 = bool(str_to_bool(os.environ["ACCELERATE_ZERO1"]))
        if self.zero2 is None and "ACCELERATE_ZERO2" in os.environ:
            self.zero2 = bool(str_to_bool(os.environ["ACCELERATE_ZERO2"]))


@dataclass
class TensorParallelPlugin:
    """Tensor parallelism on the ``tp`` mesh axis.

    Reference: TorchTensorParallelPlugin dataclasses.py:1863-1895 (reads
    TP_SIZE from env, utils/launch.py:303-305).  ``tp_plan`` maps parameter
    path regexes to partition specs; None uses the model's built-in plan
    (`Module.tp_plan`).
    """

    tp_size: int = 1
    tp_plan: Optional[dict[str, Any]] = None

    def __post_init__(self):
        if self.tp_size == 1 and "TP_SIZE" in os.environ:
            self.tp_size = int(os.environ["TP_SIZE"])


@dataclass
class SequenceParallelPlugin:
    """Long-context sequence/context parallelism on the ``sp`` mesh axis.

    New TPU-native capability (absent from the reference natively — see
    SURVEY.md §2.2 SP row): ring attention via shard_map + lax.ppermute over
    ICI, with blockwise-softmax renormalisation.
    """

    sp_size: int = 1
    mode: str = "ring"  # "ring" | "all_to_all" (Ulysses-style)
    chunk_size: Optional[int] = None

    def __post_init__(self):
        if self.sp_size == 1 and "SP_SIZE" in os.environ:
            self.sp_size = int(os.environ["SP_SIZE"])
        if self.mode not in ("ring", "all_to_all"):
            raise ValueError(f"unknown sequence-parallel mode {self.mode!r}")


@dataclass
class PipelineParallelPlugin:
    """Microbatch pipelining over the ``pp`` mesh axis.

    ``schedule``:
      * ``"gpipe"`` — fill-drain: all forwards, then all backwards (JAX AD
        transposes the forward loop).  Peak activation state grows with
        ``num_microbatches``.
      * ``"1f1b"`` — fused one-forward-one-backward: loss and backward run
        INSIDE the pipeline loop, so each stage holds at most ``2·S−1``
        in-flight stage inputs regardless of microbatch count (the
        Megatron-style memory profile; reference delegates to
        megatron.core's get_forward_backward_func, utils/megatron_lm.py:40).
        Requires the loss to be computed by the pipelined program — models
        opt in via their pipelined loss path (PipelinedGPTLMHeadModel).
      * ``"interleaved"`` — interleaved 1F1B (MPMD pipeline-parallelism,
        PAPERS.md #4): each pp device hosts ``virtual_stages`` NON-contiguous
        layer spans and microbatches hop V× around the ring, shrinking the
        fill/drain bubble by the virtual factor while keeping the
        ``2·S−1``-order residual window.  Needs ``num_microbatches``
        divisible by ``pp_size`` and layers divisible by
        ``pp_size × virtual_stages``.

    The resolved values land in the run's ``ParallelPlan``
    (``accelerator.plan.stage`` — docs/parallel_plan.md); consumers read
    the plan, never this plugin directly.
    """

    pp_size: int = 1
    num_microbatches: int = 1
    # None/0 = unset: resolves to $PP_SCHEDULE / $PP_VIRTUAL, then the
    # default.  Sentinels (not concrete defaults) so an EXPLICIT
    # schedule="gpipe" / virtual_stages=1 beats the env var.
    schedule: Optional[str] = None  # "gpipe" | "1f1b" | "interleaved"
    virtual_stages: int = 0  # interleave factor V; 0 = unset
    # stacked-layer-axis layout of record (docs/parallel_plan.md §layout
    # contract).  None = unset: resolves to $PP_LAYOUT, then the plan's
    # default ("plain" at V=1, "committed" at V>1 — prepare() permutes the
    # layer stack once and the step moves zero permutation bytes).
    # "gather" keeps the legacy per-step in-program permutation (A/B arm).
    layout: Optional[str] = None  # "committed" | "gather"

    def __post_init__(self):
        if self.pp_size == 1 and "PP_SIZE" in os.environ:
            self.pp_size = int(os.environ["PP_SIZE"])
        explicit_layout = self.layout is not None
        if self.layout is None:
            self.layout = os.environ.get("PP_LAYOUT", None) or None
        if self.layout is not None and self.layout not in ("committed", "gather"):
            raise ValueError(
                f"unknown pipeline layer layout {self.layout!r}; use "
                "'committed' (prepare-time permute, default) or 'gather' "
                "(legacy per-step in-program permutation)"
            )
        explicit_schedule = self.schedule is not None
        explicit_virtual = self.virtual_stages != 0
        env_schedule = None
        if self.schedule is None:
            env_schedule = os.environ.get("PP_SCHEDULE", None)
            self.schedule = env_schedule
        if self.virtual_stages == 0 and "PP_VIRTUAL" in os.environ:
            self.virtual_stages = int(os.environ["PP_VIRTUAL"])
            if explicit_schedule and (
                (self.schedule in ("gpipe", "1f1b") and self.virtual_stages > 1)
                or (self.schedule == "interleaved" and self.virtual_stages < 2)
            ):
                # kwargs beat env: an env-sourced virtual factor that is
                # incompatible with the EXPLICIT schedule yields back to
                # unset instead of raising or silently changing the
                # schedule — gpipe/fused 1f1b cannot interleave (a
                # different compiled program, fingerprint and M%S
                # constraint), and an explicit interleaved keeps its
                # default factor under an ambient PP_VIRTUAL=1
                self.virtual_stages = 0
        if explicit_virtual and env_schedule is not None:
            # and symmetrically: an env-sourced schedule incompatible with
            # the EXPLICIT virtual factor yields to the factor's canonical
            # schedule (V=1 IS the fused 1f1b, V>1 IS interleaved)
            if env_schedule == "interleaved" and self.virtual_stages == 1:
                self.schedule = "1f1b"
            elif env_schedule == "gpipe" and self.virtual_stages > 1:
                self.schedule = "interleaved"
        if self.virtual_stages == 0:
            # interleaved without an explicit factor means "interleave at
            # all": the smallest real factor
            self.virtual_stages = 2 if self.schedule == "interleaved" else 1
        if self.schedule is None:
            self.schedule = "interleaved" if self.virtual_stages > 1 else "gpipe"
        if self.schedule == "1f1b" and self.virtual_stages > 1:
            # V>1 IS the interleaved schedule; normalize so the plan and the
            # AOT fingerprint carry one canonical name
            self.schedule = "interleaved"
        if self.schedule not in ("gpipe", "1f1b", "interleaved"):
            raise ValueError(
                f"unknown pipeline schedule {self.schedule!r}; use 'gpipe', "
                "'1f1b' or 'interleaved'"
            )
        if self.virtual_stages < 1:
            raise ValueError(
                f"virtual_stages must be >= 1, got {self.virtual_stages}"
            )
        if self.schedule == "gpipe" and self.virtual_stages > 1:
            raise ValueError(
                "virtual_stages > 1 interleaves the fused 1F1B schedule; it "
                "cannot combine with schedule='gpipe'"
            )
        if self.schedule == "interleaved" and self.virtual_stages < 2:
            raise ValueError(
                "schedule='interleaved' needs virtual_stages >= 2 "
                "(virtual_stages=1 is exactly the fused '1f1b' schedule)"
            )
        if self.virtual_stages == 1 and self.layout is not None:
            if explicit_layout:
                raise ValueError(
                    f"layout={self.layout!r} needs virtual_stages >= 2: at "
                    "V=1 the interleave order is the identity and the only "
                    "layer layout is 'plain'"
                )
            # kwargs beat env: an ambient PP_LAYOUT cannot apply to a run
            # whose (explicit or resolved) factor is V=1 — yield to unset
            # instead of raising on an unrelated fused/gpipe run
            self.layout = None


@dataclass
class ExpertParallelPlugin:
    """MoE expert parallelism on the ``ep`` mesh axis (reference exposes only
    DeepSpeed MoE leaf hints, accelerator.py:1881 — this is first-class here)."""

    ep_size: int = 1

    def __post_init__(self):
        if self.ep_size == 1 and "EP_SIZE" in os.environ:
            self.ep_size = int(os.environ["EP_SIZE"])


@dataclass
class ParallelismConfig:
    """The resolved mesh layout: one SPMD program, many axes.

    dp is inferred as ``num_devices // (fsdp*tp*sp*ep*pp)`` when left at 0.
    """

    dp_size: int = 0
    fsdp_size: int = 1
    tp_size: int = 1
    sp_size: int = 1
    ep_size: int = 1
    pp_size: int = 1

    def axis_sizes(self, num_devices: int) -> dict[str, int]:
        fixed = self.fsdp_size * self.tp_size * self.sp_size * self.ep_size * self.pp_size
        if fixed <= 0 or num_devices % fixed != 0:
            raise ValueError(
                f"mesh axes {self!r} do not divide device count {num_devices}"
            )
        dp = self.dp_size or num_devices // fixed
        if dp * fixed != num_devices:
            raise ValueError(
                f"dp({dp})×fsdp({self.fsdp_size})×tp({self.tp_size})×sp({self.sp_size})"
                f"×ep({self.ep_size})×pp({self.pp_size}) != {num_devices} devices"
            )
        return {
            "dp": dp,
            "fsdp": self.fsdp_size,
            "tp": self.tp_size,
            "sp": self.sp_size,
            "ep": self.ep_size,
            "pp": self.pp_size,
        }

    @classmethod
    def from_env(cls) -> "ParallelismConfig":
        env = os.environ
        return cls(
            dp_size=int(env.get("DP_SIZE", 0)),
            fsdp_size=int(env.get("FSDP_SIZE", 1)),
            tp_size=int(env.get("TP_SIZE", 1)),
            sp_size=int(env.get("SP_SIZE", 1)),
            ep_size=int(env.get("EP_SIZE", 1)),
            pp_size=int(env.get("PP_SIZE", 1)),
        )


# ---------------------------------------------------------------------------
# FP8 recipes (reference dataclasses.py:295-435): on TPU fp8 is native XLA
# dtypes (e8m4/e5m2) rather than TransformerEngine/MSAMP module swaps.
# ---------------------------------------------------------------------------
@dataclass
class FP8RecipeKwargs(KwargsHandler):
    backend: str = "xla"  # only native XLA fp8 on TPU
    use_autocast_during_eval: bool = False
    margin: int = 0
    fp8_format: str = "HYBRID"  # E4M3 fwd / E5M2 bwd
    amax_history_len: int = 1024
    amax_compute_algo: str = "max"


def add_model_config_to_megatron_parser(*args, **kwargs):  # pragma: no cover
    raise NotImplementedError(
        "Megatron-LM delegation does not exist on the TPU stack; its "
        "capabilities (tp/pp/sp degrees, distributed optimizer) are expressed "
        "through ParallelismConfig mesh axes instead."
    )
