"""Accelerator — the user-facing orchestration core (L4).

Counterpart of ``/root/reference/src/accelerate/accelerator.py`` (3769 LoC).
The API shape survives — ``prepare`` / ``backward`` / ``accumulate`` /
``clip_grad_norm_`` / ``gather_for_metrics`` / ``save_state`` — but the
execution model inverts (SURVEY.md §7): instead of multiplexing over ten
process backends and wrapping mutable torch objects, there is one SPMD
program on a mesh.  ``prepare`` lays parameters and batches onto the mesh;
the imperative loop runs either

* **eagerly** (tape autodiff, op-by-op dispatch) — debugging, parity with the
  reference's "unmodified loop" promise; or
* **captured** (``accelerator.compile_step``): the loop body traces once into
  a single jitted, donated, fully-fused XLA program — forward, backward,
  optimizer update and (sharded) collectives in one launch.  This is the
  performance path that makes TPU throughput competitive.
"""

from __future__ import annotations

import contextlib
import math
import os
from functools import partial
from typing import Any, Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from .data_loader import DataLoaderShard, prepare_data_loader, skip_first_batches
from .logging import get_logger
from .nn import random as nn_random
from .nn.module import Module
from .nn.tape import Tensor
from .optim import LRScheduler, Optimizer
from .optimizer import AcceleratedOptimizer, DynamicLossScaler
from .scheduler import AcceleratedScheduler
from .state import AcceleratorState, GradientState, PartialState
from .telemetry import flightrec as _flightrec
from .utils import operations as ops
from .utils.dataclasses import (
    DataLoaderConfiguration,
    DataParallelPlugin,
    FullyShardedDataParallelPlugin,
    GradientAccumulationPlugin,
    GradScalerKwargs,
    InitProcessGroupKwargs,
    LoggerType,
    ParallelismConfig,
    PrecisionType,
    ProfileKwargs,
    ProjectConfiguration,
    SequenceParallelPlugin,
    TensorParallelPlugin,
)

logger = get_logger(__name__)


class Accelerator:
    def __init__(
        self,
        device_placement: bool = True,
        split_batches: bool = False,
        mixed_precision: Optional[str] = None,
        gradient_accumulation_steps: int = 1,
        cpu: bool = False,
        dataloader_config: Optional[DataLoaderConfiguration] = None,
        fsdp_plugin: Optional[FullyShardedDataParallelPlugin] = None,
        tp_plugin: Optional[TensorParallelPlugin] = None,
        sp_plugin: Optional[SequenceParallelPlugin] = None,
        dp_plugin: Optional[DataParallelPlugin] = None,
        pp_plugin=None,
        parallelism_config: Optional[ParallelismConfig] = None,
        rng_types: Optional[list] = None,
        log_with: Optional[Union[str, list]] = None,
        project_dir: Optional[str] = None,
        project_config: Optional[ProjectConfiguration] = None,
        gradient_accumulation_plugin: Optional[GradientAccumulationPlugin] = None,
        step_scheduler_with_optimizer: bool = True,
        kwargs_handlers: Optional[list] = None,
        dynamo_backend: Optional[str] = None,  # parity; XLA is the only compiler here
    ):
        self.project_configuration = project_config or ProjectConfiguration(
            project_dir=project_dir
        )
        if project_dir is not None and self.project_configuration.project_dir is None:
            self.project_configuration.set_directories(project_dir)

        # kwargs handlers
        self.scaler_handler = None
        self.init_handler = None
        self.profile_handler = None
        self.autocast_handler = None
        self.fp8_recipe_handler = None
        self.ddp_handler = None
        # normalized "fp16"/"bf16"/"powersgd"/"batched_powersgd"/None, set below
        self._comm_hook = None
        self._comm_wrapper = None  # "fp16"/"bf16" factor rounding for powersgd
        self._powersgd_state = None  # per-model {q, err} arrays, capture-threaded
        self.telemetry_handler = None
        self.resilience_handler = None
        self.compression_handler = None
        self.aot_cache_handler = None
        self.fleet_handler = None
        self.kernels_handler = None
        from .utils.dataclasses import FP8RecipeKwargs

        from .utils.dataclasses import (
            AutocastKwargs,
            CompilationCacheKwargs,
            CompressionKwargs,
            DistributedDataParallelKwargs,
            FleetKwargs,
            KernelKwargs,
            ResilienceKwargs,
            TelemetryKwargs,
        )

        for handler in kwargs_handlers or []:
            if isinstance(handler, TelemetryKwargs):
                self.telemetry_handler = handler
            elif isinstance(handler, CompressionKwargs):
                self.compression_handler = handler
            elif isinstance(handler, CompilationCacheKwargs):
                self.aot_cache_handler = handler
            elif isinstance(handler, FleetKwargs):
                self.fleet_handler = handler
            elif isinstance(handler, KernelKwargs):
                self.kernels_handler = handler
            elif isinstance(handler, ResilienceKwargs):
                self.resilience_handler = handler
            elif isinstance(handler, AutocastKwargs):
                self.autocast_handler = handler
            elif isinstance(handler, GradScalerKwargs):
                self.scaler_handler = handler
            elif isinstance(handler, InitProcessGroupKwargs):
                self.init_handler = handler
            elif isinstance(handler, ProfileKwargs):
                self.profile_handler = handler
            elif isinstance(handler, FP8RecipeKwargs):
                self.fp8_recipe_handler = handler
            elif isinstance(handler, DistributedDataParallelKwargs):
                self.ddp_handler = handler
                if handler.comm_hook is not None:
                    hook = str(handler.comm_hook).lower()
                    # accept both the bare value and its enum stringification
                    # (DDPCommunicationHookType.NO prints as "ddpcommunicationhooktype.no")
                    hook = hook.rsplit(".", 1)[-1]
                    if hook in ("no", "none"):
                        # the reference's NO hook is a valid no-op default —
                        # run uncompressed rather than failing construction
                        hook = None
                    elif hook in ("power_sgd", "batched_power_sgd"):
                        hook = hook.replace("_sgd", "sgd")  # normalize spelling
                    elif hook not in ("fp16", "bf16", "powersgd", "batched_powersgd"):
                        # fail at configuration time, not mid-first-train-step
                        raise ValueError(
                            f"unsupported comm_hook {handler.comm_hook!r}; use "
                            "'fp16', 'bf16', 'powersgd' or 'batched_powersgd'"
                        )
                    # normalized copy — the caller-owned handler stays untouched
                    self._comm_hook = hook
                if getattr(handler, "comm_wrapper", None) is not None:
                    wrapper = str(handler.comm_wrapper).lower().rsplit(".", 1)[-1]
                    if wrapper in ("no", "none"):
                        wrapper = None
                    elif wrapper not in ("fp16", "bf16"):
                        raise ValueError(
                            f"unsupported comm_wrapper {handler.comm_wrapper!r}; "
                            "use 'fp16' or 'bf16'"
                        )
                    self._comm_wrapper = wrapper

        # dp-axis collective compression (docs/compression.md): ONE policy
        # surface for the quantized ZeRO-1 collectives (int8/fp8) and the
        # PowerSGD comm hook — CompressionKwargs/$ACCELERATE_COMPRESSION
        # selects it, and the legacy ddp comm_hook="powersgd" spelling
        # resolves to the same PowerSGDCompression object
        from .parallel.compress import powersgd_from_ddp, resolve_policy

        self._compression = resolve_policy(
            self.compression_handler, ddp_handler=self.ddp_handler
        )
        # Pallas hot-path kernels (docs/kernels.md): one default-off policy
        # for the collective-matmul ZeRO-1 gather and the fused quantize+RS
        # wire — resolved here so the optimizer relayout and the AOT-cache
        # fingerprint read ONE armed set
        from .native.kernels import _set_active_kernels, resolve_kernel_policy

        self.kernels = resolve_kernel_policy(self.kernels_handler)
        _set_active_kernels(self.kernels if self.kernels.enabled else None)
        # the sync-boundary hook policy: the compression policy itself when
        # it IS a hook (powersgd), else the legacy ddp spelling (which also
        # lets powersgd compose with an int8/fp8 collective policy)
        self._hook_policy = (
            self._compression
            if self._compression.hook_name is not None
            else powersgd_from_ddp(self.ddp_handler)
        )
        if self._hook_policy is not None:
            if self._comm_hook in ("fp16", "bf16"):
                raise ValueError(
                    f"comm_hook={self._comm_hook!r} and compression policy "
                    f"{self._hook_policy.name!r} both claim the gradient sync "
                    "boundary; pick one (the fp16/bf16 cast is the PowerSGD "
                    "comm_wrapper option, not a separate hook)"
                )
            self._comm_hook = self._hook_policy.hook_name
            if self._hook_policy.wrapper_dtype is None and self._comm_wrapper:
                # powersgd selected via CompressionKwargs alongside a legacy
                # ddp comm_wrapper: honor the wrapper rather than silently
                # dropping the requested factor rounding
                from .parallel.compress import _wrapper_dtype

                self._hook_policy.wrapper_dtype = _wrapper_dtype(self._comm_wrapper)

        if fsdp_plugin is None and os.environ.get("ACCELERATE_USE_FSDP", "false").lower() in ("1", "true"):
            fsdp_plugin = FullyShardedDataParallelPlugin()

        self.state = AcceleratorState(
            mixed_precision=mixed_precision,
            cpu=cpu,
            parallelism_config=parallelism_config,
            fsdp_plugin=fsdp_plugin,
            tp_plugin=tp_plugin,
            sp_plugin=sp_plugin,
            dp_plugin=dp_plugin,
            pp_plugin=pp_plugin,
            _from_accelerator=True,
            **(
                {"init_process_group_kwargs": self.init_handler}
                if self.init_handler
                else {}
            ),
        )

        if gradient_accumulation_plugin is None:
            ga_steps = int(
                os.environ.get(
                    "ACCELERATE_GRADIENT_ACCUMULATION_STEPS", gradient_accumulation_steps
                )
            )
            gradient_accumulation_plugin = GradientAccumulationPlugin(num_steps=ga_steps)
        self.gradient_state = GradientState(gradient_accumulation_plugin)

        self.device_placement = device_placement
        self.dataloader_config = dataloader_config or DataLoaderConfiguration(
            split_batches=split_batches
        )
        self.step_scheduler_with_optimizer = step_scheduler_with_optimizer
        self.rng_types = rng_types or ["jax"]

        # fp16 needs dynamic loss scaling; bf16 (the TPU default) does not
        self.scaler = None
        if self.state.mixed_precision == "fp16":
            self.scaler = DynamicLossScaler(self.scaler_handler)

        self._models: list[Module] = []
        self._converted_models: list[Module] = []  # torch→native conversions
        self._converted_optimizers: list[tuple] = []  # (torch_opt, native_opt)
        self._optimizers: list[AcceleratedOptimizer] = []
        self._schedulers: list[AcceleratedScheduler] = []
        self._dataloaders: list[DataLoaderShard] = []
        self._custom_objects: list[Any] = []
        from collections import OrderedDict

        self._save_state_pre_hooks: "OrderedDict[int, Callable]" = OrderedDict()
        self._load_state_pre_hooks: "OrderedDict[int, Callable]" = OrderedDict()

        self.step = 0
        self.flag_tensor = None
        self._capture_cache: dict = {}
        self._capture_ctx: Optional[dict] = None
        # (param, sharding, dp-axis) triples for the ZeRO-2 accumulated-grad
        # layout; empty (one falsy check in backward) unless prepare() armed
        # it.  _zero2_stochastic arms the kernel policy's narrow wire on top
        self._zero2_grads: list = []
        self._zero2_stochastic = False

        # trackers
        from .tracking import filter_trackers

        self.log_with = filter_trackers(log_with, self.logging_dir)
        self.trackers: list = []

        # runtime telemetry (docs/telemetry.md): always constructed (a few
        # empty deques), OFF unless TelemetryKwargs/$ACCELERATE_TELEMETRY
        # turns it on — compile_step pins the enabled instance so the
        # captured path pays one None-check when off
        from .telemetry import Telemetry

        self.telemetry = Telemetry(self.telemetry_handler)

        # resilience (docs/resilience.md): always constructed, OFF unless
        # ResilienceKwargs/$ACCELERATE_RESILIENCE turns it on — compile_step
        # pins the enabled instance so the captured path pays one None-check
        # when off; enabled, it installs the preemption guard and arms the
        # dispatch retrier
        from .resilience import Resilience

        self.resilience = Resilience(self.resilience_handler, telemetry=self.telemetry)

        # ONE resolved ParallelPlan (docs/parallel_plan.md): mesh axes, ZeRO
        # modes, compression, pipeline stage layout — resolved here, once,
        # from ParallelismConfig/plugins/env, published on the Borg state
        # (parallel.plan.current_plan) and re-resolved only by a fleet
        # resize.  Every consumer below (optimizer relayout, compression,
        # capture, AOT fingerprint, fleet, the pipelined models) reads THIS
        # object instead of rediscovering its own axis.
        self._resolve_plan()

        # persistent AOT executable cache (docs/aot_cache.md): always
        # constructed, OFF unless CompilationCacheKwargs/$ACCELERATE_AOT_CACHE
        # names a cache dir — compile_step pins the enabled instance so the
        # captured build path pays one None-check when off; enabled, builds
        # deserialize stored executables instead of tracing+compiling, the
        # hit/miss stream lands as kind="aot_cache" telemetry, and the live
        # counters serve as atpu_aot_cache_* on the metrics endpoint
        from .native.aot_cache import AOTCompilationCache, _set_active

        self.aot_cache = AOTCompilationCache(self.aot_cache_handler)
        # pin the run's topology into the ONE canonical fingerprint now —
        # a restore-path prefetch() can run before the first captured build,
        # and both must hash the same mesh/compression or the prefetch pins
        # a fingerprint no stored entry was keyed under
        self.aot_cache.set_context(
            mesh=self.state.mesh,
            compression=self._compression.name,
            # armed set + lowering mode: a forced interpret flip must be a
            # loud miss too, not a replay of the other mode's executable
            kernels=self.kernels.cache_tag(),
            # the resolved plan digest: a schedule/virtual-stage/ZeRO flip
            # compiles a different program, so it must be a loud miss
            # NAMING the plan field (docs/parallel_plan.md §AOT coupling)
            plan=self.plan.describe(),
        )
        self.aot_cache.attach_telemetry(self.telemetry)
        _set_active(self.aot_cache if self.aot_cache.enabled else None)

        # elastic fleet runtime (docs/elastic.md): always constructed, OFF
        # unless FleetKwargs/$ACCELERATE_FLEET turns it on — compile_step
        # pins the enabled instance so the captured path pays one None-check
        # when off; enabled, it composes the subsystems above into
        # coordinated multi-host rollback (the resilience retrier consults
        # it), host-loss-driven dp resize, and the periodic mid-run fleet
        # aggregation signal
        from .fleet import Fleet

        self.fleet = Fleet(
            self.fleet_handler, telemetry=self.telemetry, resilience=self.resilience
        )
        self.resilience.fleet = self.fleet if self.fleet.enabled else None
        # bumped by fleet.resize() when the mesh changes; fleet-armed
        # CapturedSteps drop their compiled variants when it moves
        self._mesh_generation = 0

        # seed the nn RNG only when explicitly requested or still unseeded —
        # never clobber a user's earlier manual_seed
        if "ACCELERATE_SEED" in os.environ:
            nn_random.manual_seed(int(os.environ["ACCELERATE_SEED"]))
        elif nn_random.default_rng._base_key is None:
            nn_random.manual_seed(nn_random.default_rng._seed)

    # ----------------------------------------------------------------- plan
    def _resolve_plan(self, bump: bool = False):
        """Resolve (or, after a fleet resize, RE-resolve) the run's ONE
        :class:`~accelerate_tpu.parallel.plan.ParallelPlan` from the live
        state and publish it on the Borg state for :func:`current_plan`.

        ``bump=True`` (the resize path) advances the plan generation and
        the mesh generation together, so fleet-armed CapturedSteps drop
        every compiled variant bound to the old layout before their next
        lookup, and syncs the parallelism config's dp entry from the live
        mesh — the single place it may move — so later mesh rebuilds and
        ``zero1_enabled`` reads agree with what the plan says.  At
        construction the config is left untouched: it just BUILT the mesh,
        and pinning the auto-resolved dp onto it would make a second
        Accelerator with an equivalent auto config a conflicting re-init
        on the Borg state.
        """
        from .parallel.plan import DP_AXIS, ParallelPlan

        if bump:
            self.state.parallelism_config.dp_size = dict(
                self.state.mesh.shape
            ).get(DP_AXIS, 1)
        generation = (self.plan.generation + 1) if (bump and hasattr(self, "plan")) else 0
        self.plan = ParallelPlan.resolve(
            self.state, compression=self._compression.name, generation=generation
        )
        self.state.plan = self.plan
        if bump:
            self._mesh_generation = getattr(self, "_mesh_generation", 0) + 1
        return self.plan

    # ------------------------------------------------------------------ props
    @property
    def distributed_type(self):
        return self.state.distributed_type

    @property
    def mesh(self):
        return self.state.mesh

    @property
    def num_processes(self) -> int:
        return self.state.num_processes

    @property
    def process_index(self) -> int:
        return self.state.process_index

    @property
    def local_process_index(self) -> int:
        return self.state.local_process_index

    @property
    def device(self):
        return self.state.device

    @property
    def is_main_process(self) -> bool:
        return self.state.is_main_process

    @property
    def is_local_main_process(self) -> bool:
        return self.state.is_local_main_process

    @property
    def is_last_process(self) -> bool:
        return self.state.is_last_process

    @property
    def mixed_precision(self) -> str:
        return self.state.mixed_precision

    @property
    def num_devices(self) -> int:
        return self.state.num_devices

    @property
    def sync_gradients(self) -> bool:
        return self.gradient_state.sync_gradients

    @property
    def gradient_accumulation_steps(self) -> int:
        return self.gradient_state.num_steps

    @gradient_accumulation_steps.setter
    def gradient_accumulation_steps(self, value: int):
        self.gradient_state.plugin_kwargs.update({"num_steps": value})

    @property
    def project_dir(self):
        return self.project_configuration.project_dir

    @property
    def logging_dir(self):
        return self.project_configuration.logging_dir

    @property
    def use_distributed(self) -> bool:
        return self.state.use_distributed

    @property
    def compute_dtype(self):
        # fp8 keeps bf16 activations/params; only the matmuls drop to fp8
        return jnp.bfloat16 if self.state.mixed_precision in ("bf16", "fp8") else (
            jnp.float16 if self.state.mixed_precision == "fp16" else jnp.float32
        )

    @property
    def save_iteration(self) -> int:
        """Next automatic checkpoint index (reference accelerator.py:680)."""
        return self.project_configuration.iteration

    @property
    def optimizer_step_was_skipped(self) -> bool:
        """True when the last update was dropped (fp16 overflow) — the LR
        should then not advance (reference accelerator.py:3674)."""
        return any(opt.step_was_skipped for opt in self._optimizers)

    @property
    def deepspeed_plugin(self):
        """Always ``None``: there is no DeepSpeed engine on TPU.  DeepSpeed
        configs are INGESTED instead — ``utils/deepspeed_compat.py`` maps
        ZeRO stages/offload onto fsdp mesh layouts (reference
        accelerator.py:603 returns the active plugin)."""
        return None

    # deprecated-in-reference dataloader passthroughs, kept for drop-in
    # parity (reference reads them off dataloader_config the same way)
    @property
    def split_batches(self) -> bool:
        return self.dataloader_config.split_batches

    @property
    def dispatch_batches(self):
        return self.dataloader_config.dispatch_batches

    @property
    def even_batches(self) -> bool:
        return self.dataloader_config.even_batches

    @property
    def use_seedable_sampler(self) -> bool:
        return self.dataloader_config.use_seedable_sampler

    @property
    def non_blocking(self) -> bool:
        return self.dataloader_config.non_blocking

    @property
    def use_stateful_dataloader(self) -> bool:
        return self.dataloader_config.use_stateful_dataloader

    # ------------------------------------------------------------- process ctl
    def wait_for_everyone(self) -> None:
        PartialState().wait_for_everyone()

    def print(self, *args, **kwargs) -> None:
        PartialState().print(*args, **kwargs)

    def on_main_process(self, function):
        return PartialState().on_main_process(function)

    def on_local_main_process(self, function):
        return PartialState().on_local_main_process(function)

    def on_process(self, function=None, process_index=None):
        return PartialState().on_process(function, process_index=process_index)

    def on_last_process(self, function):
        return PartialState().on_last_process(function)

    def on_local_process(self, function=None, local_process_index=None):
        """Run only on the given LOCAL process index (reference
        accelerator.py:908)."""
        return PartialState().on_local_process(
            function, local_process_index=local_process_index
        )

    def trigger_sync_in_backward(self, model=None) -> None:
        """Force the NEXT backward/step to be a sync step after forwards ran
        under ``no_sync`` (reference accelerator.py:1043).  Under SPMD this
        flips the accumulation gate: ``optimizer.step`` will apply."""
        self.gradient_state._set_sync_gradients(True)

    @contextlib.contextmanager
    def main_process_first(self):
        with PartialState().main_process_first():
            yield

    @contextlib.contextmanager
    def local_main_process_first(self):
        with PartialState().local_main_process_first():
            yield

    def split_between_processes(self, inputs, apply_padding: bool = False):
        return PartialState().split_between_processes(inputs, apply_padding)

    # --------------------------------------------------------------- prepare
    @_flightrec.spanned("atpu/setup/prepare")
    def prepare(self, *args, device_placement=None):
        """Re-bind user objects onto the mesh (reference accelerator.py:1283).

        Models: params sharded per plugin rules (replicated for pure DP, fsdp
        axis for ZeRO, tp axis per plan) + precision policy. Optimizers:
        wrapped with accumulation/scaler semantics. DataLoaders: rebuilt as
        SPMD global-batch loaders. Schedulers: wrapped to step per real
        optimizer step.
        """
        result = []
        for obj in args:
            result.append(self._prepare_one(obj))
        # commit the plan's interleaved layer layout BEFORE the optimizer
        # relayout below: masters/moments snapshot the params, so ZeRO-1
        # state is born permuted and updates stay permuted-in-place — the
        # captured step never sees a permutation (docs/parallel_plan.md
        # §layout contract)
        self._commit_layer_layout()
        # re-lay-out optimizer state (Adam moments, fp32 masters) onto the
        # params' mesh shardings: tx.init ran before prepare() sharded the
        # params, so without this the opt state stays on the old layout and
        # ZeRO saves no memory (reference FSDP shards optimizer state too,
        # accelerator.py:1555-1679)
        offload_opt = bool(
            self.state.fsdp_plugin is not None
            and getattr(self.state.fsdp_plugin, "offload_optimizer", False)
        )
        # training-time parameter offload (reference FSDP CPUOffload /
        # DeepSpeed offload_param): params pinned to host between steps,
        # staged back by a forward hook (traced h2d under compile_step)
        offload_params = bool(
            self.state.fsdp_plugin is not None
            and getattr(self.state.fsdp_plugin, "cpu_offload", False)
        )
        # ZeRO-1 (arXiv:2004.13336): with a dp axis and no fsdp owner the
        # state relayout below additionally shards masters + moments over dp,
        # turning the captured update into reduce-scatter → 1/dp-shard-local
        # AdamW → all-gather with no eager-mode change for users.  The armed
        # modes come off the resolved ParallelPlan (docs/parallel_plan.md) —
        # the optimizer never re-derives its own dp axis.
        zero1_mesh = self.state.mesh if self.plan.zero1 else None
        for opt in self._optimizers:
            opt.optimizer.relayout_for_sharded_params(
                offload_to_host=offload_opt,
                offload_params=offload_params,
                zero1_mesh=zero1_mesh,
                # quantized dp collectives + ZeRO-2 grad-accumulation layout
                # (docs/compression.md); both no-ops unless armed
                compression=self._compression,
                zero2=self.plan.zero2,
                # Pallas hot-path kernels (docs/kernels.md): routes the
                # ZeRO-1 writeback gather through the chunked ring and the
                # quantized RS through the fused kernel; None-check off-path
                kernels=self.kernels,
                # per-param state shardings are the plan's to decide
                # (ParallelPlan.state_spec delegates the ZeRO-1 layout rule)
                plan=self.plan,
            )
        if offload_params:
            from .hooks import ParamOffloadHook, add_hook_to_module

            for model in self._models:
                if not getattr(model, "_atpu_param_offload", False):
                    add_hook_to_module(model, ParamOffloadHook(), append=True)
                    model._atpu_param_offload = True
        self._ensure_powersgd_state()
        self._refresh_zero2_grads()
        self._record_collectives()
        self._record_kernels()
        return result[0] if len(result) == 1 else tuple(result)

    # ------------------------------------------------- layer layout contract
    def _stacked_layer_params(self, model):
        """``(name, param)`` pairs whose leading axis is the plan's stacked
        layer axis — identified by the pp-sharded leading dim (the tp_plan
        rule that makes a stack a stack) or by an existing commit marker."""
        from .parallel.plan import PP_AXIS

        out = []
        seen = set()
        for name, p in model.named_parameters():
            if id(p) in seen:
                continue  # tied params appear once
            seen.add(id(p))
            if getattr(p, "_layer_layout_committed", False):
                out.append((name, p))
                continue
            data = getattr(p, "data", None)
            s = getattr(data, "sharding", None)
            spec = getattr(s, "spec", None)
            if not spec:
                continue
            first = spec[0] if len(spec) else None
            names = first if isinstance(first, tuple) else (first,)
            if PP_AXIS in names:
                out.append((name, p))
        return out

    def _commit_layer_layout(self) -> None:
        """Physically reorder every stacked layer param into the plan's
        ``StagePlan.layer_order`` ONCE — the layout of record under
        ``layer_layout == "committed"`` (docs/parallel_plan.md §layout
        contract).  After this the captured 1F1B step consumes the stack in
        place and moves zero permutation bytes; each param carries a
        ``_layer_layout_committed`` marker (the runtime source of truth the
        model's forward keys on, and the idempotency guard a re-prepare or
        fleet resize relies on)."""
        stage = getattr(self.plan, "stage", None)
        if (
            stage is None
            or stage.virtual <= 1
            or self.plan.layer_layout != "committed"
        ):
            return
        from .parallel.pipeline import apply_layer_order

        for model in self._models:
            for _, p in self._stacked_layer_params(model):
                if getattr(p, "_layer_layout_committed", False):
                    continue
                data = p.data
                order = stage.layer_order(int(data.shape[0]))
                p.data = jax.device_put(
                    apply_layer_order(data, order), data.sharding
                )
                p._layer_layout_committed = True

    def _layer_layout_record(self) -> Optional[dict]:
        """Checkpoint meta descriptor of the live stacked-layer layout —
        ``None`` when plain (saved checkpoints then match every pre-layout
        reader bitwise)."""
        stage = getattr(self.plan, "stage", None)
        if (
            stage is None
            or stage.virtual <= 1
            or self.plan.layer_layout != "committed"
        ):
            return None
        if not any(
            getattr(p, "_layer_layout_committed", False)
            for m in self._models
            for _, p in self._stacked_layer_params(m)
        ):
            return None
        return {
            "layer_layout": {
                "layout": "committed",
                "num_stages": stage.num_stages,
                "virtual": stage.virtual,
            }
        }

    def _retarget_layer_layout(self, ckpt_rec: Optional[dict]) -> None:
        """Transpose just-restored stacked arrays from the CHECKPOINT's
        layer layout into the LIVE one (either direction; no-op when they
        match — including the pre-layout-checkpoint → plain-run case, which
        stays bitwise).  Covers model params and, through
        ``Optimizer.relayout_layer_axis``, the fp32 masters and moments —
        bitwise after transposition."""
        stage = getattr(self.plan, "stage", None)
        live_committed = any(
            getattr(p, "_layer_layout_committed", False)
            for m in self._models
            for _, p in self._stacked_layer_params(m)
        )
        ckpt_committed = bool(ckpt_rec) and ckpt_rec.get("layout") == "committed"
        if not live_committed and not ckpt_committed:
            return
        from .parallel.pipeline import apply_layer_order
        from .parallel.plan import _layer_orders

        def composed(num_layers: int):
            # committed array C satisfies C[i] = plain[order[i]]; the ckpt→
            # live transposition is one take by inv_ckpt ∘ order_live
            ident = tuple(range(num_layers))
            inv0 = (
                _layer_orders(
                    int(ckpt_rec["num_stages"]), int(ckpt_rec["virtual"]),
                    num_layers,
                )[1]
                if ckpt_committed
                else ident
            )
            order1 = (
                stage.layer_order(num_layers)
                if live_committed and stage is not None
                else ident
            )
            perm = tuple(inv0[j] for j in order1)
            return None if perm == ident else perm

        transposed: set[int] = set()
        for model in self._models:
            for _, p in self._stacked_layer_params(model):
                data = p.data
                perm = composed(int(data.shape[0]))
                transposed.add(id(p))
                if perm is None:
                    continue
                p.data = jax.device_put(
                    apply_layer_order(data, perm), data.sharding
                )
        for opt in self._optimizers:
            inner = getattr(opt, "optimizer", opt)
            indices = [
                i
                for i, p in enumerate(getattr(inner, "param_list", []))
                if id(p) in transposed
            ]
            if indices:
                inner.relayout_layer_axis(indices, composed)

    def _refresh_zero2_grads(self) -> None:
        """Collect the (param, accumulation-sharding) pairs ZeRO-2 armed at
        relayout time, so ``backward`` pays one cheap loop (empty when off)."""
        # (param, sharding, dp-axis, stochastic-wire-eligible): axis and
        # eligibility come from the optimizer's own relayout bookkeeping —
        # _dp_state_axis is the dp entry the state spec actually gained,
        # and _comp_axis is non-None exactly for the tensors the
        # compression policy's min_size/min_block/dtype gates admit, so the
        # narrow wire below can never quantize a tensor the reference
        # reduce-scatter path would deliberately pass through uncompressed
        self._zero2_grads = [
            (p, p._grad_sharding, opt.optimizer._dp_state_axis[i],
             opt.optimizer._comp_axis[i] is not None)
            for opt in self._optimizers
            for i, p in enumerate(opt.optimizer.param_list)
            if getattr(p, "_grad_sharding", None) is not None
        ]
        # stochastic-rounding ZeRO-2 wire (docs/kernels.md §stochastic
        # wire): the mid-accumulation scatter crosses dp narrow only when
        # the kernel policy AND an int8 collective policy AND ZeRO-2 are
        # all armed — the unbiased floor(y+u) round is what PR 6's
        # deterministic rounding could not offer
        import jax.numpy as jnp

        self._zero2_stochastic = bool(
            self._zero2_grads
            and self.kernels.quantized_rs
            and getattr(self._compression, "wire_dtype", None) is not None
            and jnp.dtype(self._compression.wire_dtype) == jnp.int8
        )

    def _record_collectives(self) -> None:
        """dp-axis collective-bytes attribution (telemetry
        ``kind="collectives"``): the analytic per-step wire bytes of the
        ZeRO-1 reduce-scatter/all-gather pair under the active compression
        policy — the denominator an A/B across policies compares."""
        if not self.telemetry.enabled:
            return
        for opt in self._optimizers:
            summary = opt.optimizer.compression_summary()
            if summary is not None:
                self.telemetry.record_collectives(summary)

    def _record_kernels(self) -> None:
        """One ``kind="kernel"`` record per armed Pallas kernel
        (docs/kernels.md): which hot path it replaces and how it lowers —
        the attribution a kernel on/off A/B and the per-phase device
        split join against."""
        if not self.telemetry.enabled or not self.kernels.enabled:
            return
        targets = {
            "collective_matmul": "zero1 all-gather → chunked ring + partial matmuls",
            "quantized_rs": "compress reduce-scatter → fused scale+round region",
        }
        for name in self.kernels.armed():
            self.telemetry.record_kernel(
                {
                    "kernel": name,
                    "target": targets[name],
                    "interpret": self.kernels.interpret,
                    "policy": self.kernels.describe(),
                }
            )

    def _prepare_one(self, obj):
        from .utils.torch_bridge import (
            convert_torch_module,
            convert_torch_optimizer,
            convert_torch_scheduler,
            is_torch_lr_scheduler,
            is_torch_module,
            is_torch_optimizer,
        )

        if is_torch_module(obj):
            # reference prepare_model takes any torch.nn.Module
            # (accelerator.py:1421); convert supported architectures to the
            # native nn with weights copied, then prepare as usual
            obj = convert_torch_module(obj)
            self._converted_models.append(obj)
        elif is_torch_optimizer(obj):
            # param identity can't cross the torch→JAX boundary: rebuild over
            # the converted models' params (reference's XLA param remap,
            # accelerator.py:1376-1410, same problem one framework harder)
            torch_opt = obj
            obj = convert_torch_optimizer(
                torch_opt, self._converted_models or self._models
            )
            self._converted_optimizers.append((torch_opt, obj))
        elif is_torch_lr_scheduler(obj):
            # the scheduler must drive the CONVERTED optimizer, not the
            # discarded torch one (silent frozen-LR bug otherwise)
            obj = convert_torch_scheduler(obj, self._converted_optimizers)
        if isinstance(obj, Module):
            return self.prepare_model(obj)
        if isinstance(obj, AcceleratedOptimizer):
            return obj
        if isinstance(obj, Optimizer):
            return self.prepare_optimizer(obj)
        if isinstance(obj, AcceleratedScheduler):
            return obj
        if isinstance(obj, (LRScheduler,)) or (
            hasattr(obj, "step") and hasattr(obj, "get_last_lr")
        ):
            return self.prepare_scheduler(obj)
        if isinstance(obj, DataLoaderShard) or hasattr(obj, "dataset") or hasattr(obj, "__iter__"):
            if isinstance(obj, (list, tuple, dict)):
                return obj
            return self.prepare_data_loader(obj)
        return obj

    def prepare_model(self, model: Module, device_placement: Optional[bool] = None, evaluation_mode: bool = False) -> Module:
        from .parallel.sharding import shard_module_params

        if self.num_devices > 1 and self.verify_device_map(model):
            # reference accelerator.py:1338-1349: an offload-dispatched model
            # carries align/offload hooks that fight mesh sharding at forward
            # time — refuse loudly instead of silently producing both
            raise ValueError(
                "you can't prepare a model dispatched with a multi-device "
                "device_map for distributed training; load it without "
                "device_map (shard_for_inference / ParallelismConfig handles "
                "multi-chip placement) or train on one device"
            )
        if device_placement is None:
            device_placement = self.device_placement
        # precision policy: params in compute dtype, master fp32 kept by optim
        fsdp = self.state.fsdp_plugin
        param_dtype = fsdp.resolved_dtype("param_dtype") if fsdp is not None else None
        if self.state.mixed_precision == "fp8":
            # swap Linears for fp8-matmul layers FIRST — an fsdp param_dtype
            # must tune the residual dtype, not silently disable fp8
            # (reference fp8 backends convert + autocast, SURVEY.md §2.4)
            from .utils.fp8 import convert_to_float8_training

            convert_to_float8_training(model, self.fp8_recipe_handler)
            model.to(param_dtype or jnp.bfloat16)
        elif param_dtype is not None:
            # FSDP MixedPrecisionPolicy.param_dtype (reference
            # dataclasses.py:1449): explicit per-plugin compute dtype wins
            # over the global mixed_precision default
            model.to(param_dtype)
        elif self.state.mixed_precision in ("bf16", "fp16"):
            model.to(self.compute_dtype)
        if device_placement:
            shard_module_params(
                model,
                self.state.mesh,
                fsdp_plugin=self.state.fsdp_plugin,
                tp_plugin=self.state.tp_plugin,
            )
        if model not in self._models:
            self._models.append(model)
        return model

    def prepare_optimizer(self, optimizer: Optimizer, device_placement: Optional[bool] = None) -> AcceleratedOptimizer:
        if isinstance(optimizer, AcceleratedOptimizer):
            return optimizer
        wrapped = AcceleratedOptimizer(
            optimizer,
            device_placement=device_placement if device_placement is not None else self.device_placement,
            scaler=self.scaler,
        )
        self._optimizers.append(wrapped)
        return wrapped

    def prepare_scheduler(self, scheduler) -> AcceleratedScheduler:
        if isinstance(scheduler, AcceleratedScheduler):
            return scheduler
        optimizers = self._optimizers or [
            getattr(scheduler, "optimizer", None)
        ]
        wrapped = AcceleratedScheduler(
            scheduler,
            optimizers,
            step_with_optimizer=self.step_scheduler_with_optimizer,
            split_batches=self.dataloader_config.split_batches,
        )
        self._schedulers.append(wrapped)
        return wrapped

    def prepare_data_loader(self, data_loader, device_placement: Optional[bool] = None, slice_fn_for_dispatch=None):
        if isinstance(data_loader, DataLoaderShard):
            if data_loader not in self._dataloaders:
                self._dataloaders.append(data_loader)
            data_loader._telemetry = self.telemetry if self.telemetry.enabled else None
            return data_loader
        prepared = prepare_data_loader(
            data_loader,
            split_batches=self.dataloader_config.split_batches,
            put_on_device=device_placement if device_placement is not None else self.device_placement,
            dispatch_batches=self.dataloader_config.dispatch_batches,
            even_batches=self.dataloader_config.even_batches,
            use_seedable_sampler=self.dataloader_config.use_seedable_sampler,
            data_seed=self.dataloader_config.data_seed,
            mesh=self.state.mesh,
            prefetch_size=self.dataloader_config.prefetch_size,
        )
        # pin this accelerator's telemetry hub: the loader's wait accounting
        # must survive (and never be rerouted by) later Accelerator
        # constructions flipping the module-global active slot
        prepared._telemetry = self.telemetry if self.telemetry.enabled else None
        self._dataloaders.append(prepared)
        return prepared

    # -------------------------------------------------------------- training
    def backward(self, loss: Tensor, **kwargs) -> None:
        """Reference accelerator.py:2357: scale for accumulation (+fp16) and
        run the tape backward; grads accumulate in ``param.grad``."""
        if self.gradient_state.num_steps > 1:
            loss = loss / self.gradient_state.num_steps
        if self.scaler is not None:
            loss = loss * self.scaler.scale
        import jax

        with jax.named_scope("atpu_backward"):
            # the scope is HLO metadata only (numerics untouched): the
            # sampled device timeline splits per phase on it
            # (docs/telemetry.md §per-phase attribution)
            loss.backward(**kwargs)
        if self._zero2_grads:
            # ZeRO-2 (docs/compression.md): keep the accumulated grads
            # reduce-scattered between micro-steps so the accumulation
            # buffer is ~1/dp per replica.  Layout-only — the value is the
            # same global array, and deterministically compressing a running
            # fp32 sum every micro-step would round it num_steps times (same
            # reason the comm hook below runs only at the sync boundary).
            # With the kernel policy's stochastic wire armed the scatter
            # crosses dp narrow anyway: floor(y+u) is unbiased per re-round
            # (docs/kernels.md §stochastic wire).
            from .parallel.compress import shard_accumulation

            if self._zero2_stochastic and not self.gradient_state.sync_gradients:
                from .native.kernels.quantize_rs import zero2_stochastic_wire

                for p, s, axis, sr_ok in self._zero2_grads:
                    if p.grad is None:
                        continue
                    if sr_ok and axis is not None:
                        p.grad = zero2_stochastic_wire(
                            p.grad, s, axis, nn_random.next_key(),
                            interpret=self.kernels.interpret,
                        )
                    else:
                        # the policy's eligibility gates exempt this tensor
                        # (too small to amortize the scale granularity):
                        # layout-only, exactly like the reference RS path
                        p.grad = shard_accumulation(p.grad, s)
            else:
                # the sync-boundary micro-step feeds the update directly —
                # its trip is the (exactly-quantized, error-fed) ZeRO-1
                # reduce-scatter, so it stays layout-only here
                for p, s, _axis, _sr_ok in self._zero2_grads:
                    if p.grad is not None:
                        p.grad = shard_accumulation(p.grad, s)
        if self.gradient_state.sync_gradients:
            # only at the sync boundary: re-quantizing the running fp32
            # accumulation every micro-step would pass the sum through
            # half-precision rounding num_steps times (reference DDP hooks
            # likewise compress only the sync-step all-reduce)
            self._apply_comm_hook()

    def _apply_comm_hook(self) -> None:
        """Gradient compression knob (reference DistributedDataParallelKwargs
        comm_hook / register_comm_hook, dataclasses.py:149-225): cast synced
        grads to fp16/bf16 at the backward boundary.

        What this buys under GSPMD: half-width grad buffers in HBM and
        half-width downstream consumers (clipping, any cross-host DCN grad
        traffic issued after this point).  What it does NOT change: the dtype
        of the dp gradient all-reduce XLA inserts *inside* the backward —
        that follows the compute dtype (bf16 mixed precision already reduces
        in bf16), and a cast placed after the reduce cannot legally be hoisted
        above it.  The optimizer upcasts to fp32 masters at apply time.

        The powersgd hooks run the full rank-k + error-feedback recurrence
        (utils/powersgd.py) on the synced gradients instead of a cast; the
        (Q, error) state rides the captured-step pytree like optimizer
        state, so the hook works identically under compile_step."""
        if self._comm_hook in ("powersgd", "batched_powersgd"):
            self._apply_powersgd_hook()
            return
        dtype = None
        if self._comm_hook is not None:
            dtype = jnp.float16 if self._comm_hook == "fp16" else jnp.bfloat16
        elif self.state.fsdp_plugin is not None:
            # FSDP MixedPrecisionPolicy.reduce_dtype rides the same boundary
            dtype = self.state.fsdp_plugin.resolved_dtype("reduce_dtype")
        if dtype is None:
            return
        for model in self._models:
            for p in model.parameters():
                if p.grad is not None and p.grad.dtype != dtype:
                    p.grad = p.grad.astype(dtype)

    # -- PowerSGD machinery (delegates to the CompressionPolicy) -------------
    def _ensure_powersgd_state(self) -> None:
        """Build (Q, error) hook buffers for every prepared model that lacks
        them, through the active :class:`PowerSGDCompression` policy — hook
        selection, eligibility and error-feedback state are one code path
        with the quantized-collective policies (parallel/compress.py).

        Runs eagerly at ``prepare()`` so the captured-step state pytree is
        structurally complete before the first trace (a mid-trace
        structure change would force a second compile)."""
        policy = self._hook_policy
        if policy is None:
            return
        from .nn import random as nn_random

        if self._powersgd_state is None:
            self._powersgd_state = []
        if self.scaler is not None and not getattr(self, "_powersgd_fp16_warned", False):
            self._powersgd_fp16_warned = True
            logger.warning(
                "comm_hook=powersgd with fp16 dynamic loss scaling: the error-"
                "feedback residual is carried at the loss scale it was produced "
                "under, so a scale change mis-scales one step's residual "
                "injection. Prefer mixed_precision='bf16' (no scaler) with "
                "PowerSGD, or accept the transient after each scale update."
            )
        while len(self._powersgd_state) < len(self._models):
            model = self._models[len(self._powersgd_state)]
            named = dict(model.named_parameters())
            shapes = {n: tuple(p.shape) for n, p in named.items()}
            state = policy.init_hook_state(shapes, nn_random.next_key())
            # shard each error buffer like its parameter: it is grad-shaped
            # and grad-sized, and an unsharded fp32 copy would undo ZeRO's
            # memory savings (per-tensor mode; the batched buffer has no
            # per-param layout to inherit)
            if not policy.batched:
                for n, err in state["err"].items():
                    s = getattr(named[n].data, "sharding", None)
                    if isinstance(s, jax.sharding.NamedSharding):
                        state["err"][n] = jax.device_put(
                            err, jax.sharding.NamedSharding(s.mesh, s.spec)
                        )
            self._powersgd_state.append(state)

    def _apply_powersgd_hook(self) -> None:
        from .nn import random as nn_random

        policy = self._hook_policy
        self._ensure_powersgd_state()
        for i, model in enumerate(self._models):
            named = dict(model.named_parameters())
            if policy.batched:
                # the batched error buffer is a FLAT layout over the whole
                # param set — the name set must be identical every call, so
                # zero-fill params without grads and only write back to the
                # ones that had one (parallel/compress.py contract)
                had_grad = {n for n, p in named.items() if p.grad is not None}
                grads = {
                    n: (p.grad if p.grad is not None else jnp.zeros_like(p.data))
                    for n, p in named.items()
                }
            else:
                had_grad = None
                grads = {n: p.grad for n, p in named.items() if p.grad is not None}
            new_grads, new_state = policy.apply_hook(
                grads,
                self._powersgd_state[i],
                rng_key=None if policy.warm_start else nn_random.next_key(),
            )
            for n, g in new_grads.items():
                if had_grad is None or n in had_grad:
                    named[n].grad = g
            self._powersgd_state[i] = new_state

    def _comm_hook_capture_state(self):
        """Arrays the captured step must thread (None when no powersgd)."""
        return self._powersgd_state

    def _bind_comm_hook_state(self, state) -> None:
        if state is not None:
            self._powersgd_state = state

    @contextlib.contextmanager
    def accumulate(self, *models):
        """Reference accelerator.py:1116: flip sync_gradients on schedule.

        Works both eagerly and *inside* a ``compile_step`` body: under
        capture, the owning CapturedStep advances the schedule host-side
        before every replay (one compiled variant per sync_gradients value —
        the micro-step program skips optimizer/scheduler work at trace time
        exactly as the eager path skips it at run time), so the reference's
        canonical ``with accelerator.accumulate(model):`` loop captures
        without restructuring."""
        if self._capture_ctx is not None:
            self._capture_ctx.on_accumulate(self)
            yield
            return
        self._do_sync()
        yield

    def _do_sync(self) -> None:
        if self.gradient_state.sync_with_dataloader and self.gradient_state.end_of_dataloader:
            self.step = 0
            self.gradient_state._set_sync_gradients(True)
        else:
            self.step += 1
            self.gradient_state._set_sync_gradients(
                (self.step % self.gradient_state.num_steps) == 0
                or self.gradient_state.sync_each_batch
            )

    @contextlib.contextmanager
    def no_sync(self, model=None):
        """Reference accelerator.py:1001: suppress the update this micro-step."""
        prev = self.gradient_state.sync_gradients
        self.gradient_state._set_sync_gradients(False)
        try:
            yield
        finally:
            self.gradient_state._set_sync_gradients(prev)

    def verify_device_map(self, model: Module) -> bool:
        """True when ``model`` was dispatched with a multi-device device_map
        (reference accelerator.py:3720 checks ``hf_device_map``; our
        dispatch path records ``atpu_device_map``, big_modeling.py).  Used
        to refuse distributed prepare() of an offload-dispatched model."""
        for m in model.modules():
            dmap = getattr(m, "atpu_device_map", None) or getattr(m, "hf_device_map", None)
            if dmap and len(set(map(str, dict(dmap).values()))) > 1:
                return True
        return False

    def lomo_backward(self, loss, learning_rate: float) -> None:
        """Reference API for LOMO's fused backward (accelerator.py:3731).

        Unsupported here: LOMO fuses the parameter update into torch's
        backward hooks, which has no counterpart in the traced-step model —
        under capture the optimizer update is already fused into the same
        XLA program as the backward, so LOMO's memory win is the default.
        """
        raise NotImplementedError(
            "lomo_backward is torch-hook-specific; under accelerate_tpu the "
            "captured step already fuses backward+update into one XLA program "
            "(use compile_step with any optim.* optimizer)."
        )

    @contextlib.contextmanager
    def join_uneven_inputs(self, joinables, even_batches=None):
        """SPMD requires shape-uniform programs; the loader already evens
        batches (reference join is a torch.distributed.algorithms concept),
        so this is a compatibility no-op context."""
        if even_batches is not None:
            logger.warning(
                "join_uneven_inputs(even_batches=...) has no effect: the SPMD "
                "data loader always produces even batches and tracks the "
                "remainder for gather_for_metrics."
            )
        yield

    def unscale_gradients(self, optimizer=None) -> None:
        """Divide the fp16 loss scale out of the gradients now (reference
        accelerator.py:2450); a no-op in every other precision mode.  The
        following ``optimizer.step`` will not divide again.  Normally called
        for you by ``clip_grad_norm_`` / ``clip_grad_value_``."""
        if optimizer is None:
            optimizers = self._optimizers
        elif isinstance(optimizer, (list, tuple)):
            optimizers = optimizer
        else:
            optimizers = [optimizer]
        for opt in optimizers:
            if hasattr(opt, "unscale_grads"):
                opt.unscale_grads()

    def clip_grad_norm_(self, parameters, max_norm: float, norm_type: float = 2.0):
        """Global-norm clip over ``param.grad`` (reference accelerator.py:2485).

        Works eagerly and under capture (pure jnp ops on the grads).
        Under fp16 the loss scale is divided out first — clipping must see
        true gradient magnitudes (reference clips after unscale_gradients).
        """
        self.unscale_gradients()
        params = list(parameters)
        grads = [p.grad for p in params if p.grad is not None]
        if not grads:
            return jnp.asarray(0.0)
        if norm_type == 2.0:
            total = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in grads))
        else:
            total = sum(jnp.sum(jnp.abs(g.astype(jnp.float32)) ** norm_type) for g in grads) ** (
                1.0 / norm_type
            )
        clip_coef = jnp.minimum(max_norm / (total + 1e-6), 1.0)
        for p in params:
            if p.grad is not None:
                p.grad = (p.grad.astype(jnp.float32) * clip_coef).astype(p.grad.dtype)
        return total

    def clip_grad_value_(self, parameters, clip_value: float) -> None:
        self.unscale_gradients()
        for p in parameters:
            if p.grad is not None:
                p.grad = jnp.clip(p.grad, -clip_value, clip_value)

    # ------------------------------------------------------------ collectives
    def gather(self, tensor):
        data = tensor.data if isinstance(tensor, Tensor) else tensor
        return ops.gather(data)

    def gather_for_metrics(self, input_data, use_gather_object: bool = False):
        """Gather + drop the duplicated tail samples the loader added
        (reference accelerator.py:2601; remainder from GradientState)."""
        try:
            ops.recursively_apply(lambda x: x, input_data, error_on_other_type=True)
            all_tensors = True
        except TypeError:
            all_tensors = False
        used_object_path = use_gather_object or not all_tensors
        if used_object_path:
            data = ops.gather_object(input_data)
        else:
            data = self.gather(
                input_data.data if isinstance(input_data, Tensor) else input_data
            )
        if self.gradient_state.end_of_dataloader and self.gradient_state.remainder > 0:
            remainder = self.gradient_state.remainder
            if used_object_path:
                # the flattened object list carries the sample count in its
                # own length (reference accelerator.py:2659 slices the list
                # itself when use_gather_object)
                return data[: len(data) - remainder]

            def _truncate(t):
                if getattr(t, "ndim", 0) == 0:
                    return t  # scalars carry no batch dim to truncate
                return t[: t.shape[0] - remainder]

            return ops.recursively_apply(_truncate, data)
        return data

    def reduce(self, tensor, reduction: str = "sum", scale: float = 1.0):
        data = tensor.data if isinstance(tensor, Tensor) else tensor
        return ops.reduce(data, reduction, scale)

    def pad_across_processes(self, tensor, dim: int = 0, pad_index: int = 0, pad_first: bool = False):
        data = tensor.data if isinstance(tensor, Tensor) else tensor
        return ops.pad_across_processes(data, dim, pad_index, pad_first)

    # -------------------------------------------------------------- triggers
    def set_trigger(self) -> None:
        """Any process can raise the flag; all see it at check (reference
        accelerator.py:2391 breakpoint trigger for early stopping)."""
        self.flag_tensor = 1

    def check_trigger(self) -> bool:
        flags = ops.gather_object([self.flag_tensor or 0])
        if any(bool(f) for f in flags):
            self.flag_tensor = None
            return True
        return False

    # ------------------------------------------------------------- unwrap/save
    def unwrap_model(self, model: Module, keep_fp32_wrapper: bool = True) -> Module:
        return model  # no wrapper modules exist under SPMD

    def get_state_dict(self, model: Module, unwrap: bool = True):
        sd = model.state_dict()
        # fully gather sharded params for a portable state dict
        return {
            k: np.asarray(jax.device_get(v)) for k, v in sd.items()
        }

    def save_model(
        self,
        model: Module,
        save_directory: str,
        max_shard_size: str = "10GB",
        safe_serialization: bool = True,
    ) -> None:
        from .checkpointing import save_model_weights

        os.makedirs(save_directory, exist_ok=True)
        save_model_weights(
            self.get_state_dict(model), save_directory, safe_serialization=safe_serialization
        )

    def save(self, obj, f, safe_serialization: bool = False) -> None:
        from .checkpointing import save_object

        if self.is_main_process:
            save_object(obj, f, safe_serialization=safe_serialization)

    def register_for_checkpointing(self, *objects) -> None:
        invalid = [o for o in objects if not (hasattr(o, "state_dict") and hasattr(o, "load_state_dict"))]
        if invalid:
            raise ValueError(
                "register_for_checkpointing requires state_dict/load_state_dict "
                f"on every object; invalid: {invalid}"
            )
        self._custom_objects.extend(objects)

    def save_state(
        self,
        output_dir: Optional[str] = None,
        safe_serialization: bool = True,
        sharded_state: Optional[bool] = None,
        async_save: bool = False,
        **kwargs,
    ) -> str:
        """Checkpoint everything registered with the Accelerator.

        ``async_save=True`` overlaps checkpoint serialization and file
        writes with continued training, on any process count.  The save's
        prepare phase runs at call time on the main thread of every
        process: all collectives (unsharded multi-host gathers) plus every
        device→host transfer, materializing the state into host numpy the
        training loop can never invalidate (donation in a captured step
        deletes live buffers regardless of held references; sharded saves
        pull only this host's unique GSPMD shards — O(shard) host memory,
        no extra HBM copy).  The writer thread then only serializes and
        writes files, so it cannot race the training loop's collectives.
        Steps taken after the call never leak into the checkpoint.  One
        save may be in flight at a time; ``wait_for_checkpoint()`` joins
        the writer and runs the collective finalize (barrier +
        stale-artifact cleanup) — ``load_state``/``end_training``/the next
        ``save_state`` call it automatically on every rank, and the writer
        is non-daemon so interpreter exit joins it.
        """
        self.wait_for_checkpoint()
        if self.project_configuration.automatic_checkpoint_naming:
            output_dir = os.path.join(self.project_dir or ".", "checkpoints")
            folders = []
            if os.path.isdir(output_dir):
                folders = [f for f in os.listdir(output_dir) if f.startswith("checkpoint_")]
            iteration = self.project_configuration.iteration
            # rotation (reference accelerator.py:3148-3163)
            limit = self.project_configuration.total_limit
            if limit is not None and len(folders) + 1 > limit and self.is_main_process:
                import shutil

                folders.sort(key=lambda f: int(f.split("_")[-1]))
                for f in folders[: len(folders) + 1 - limit]:
                    shutil.rmtree(os.path.join(output_dir, f), ignore_errors=True)
            output_dir = os.path.join(output_dir, f"checkpoint_{iteration}")
            self.project_configuration.iteration += 1
        if output_dir is None:
            raise ValueError("save_state needs output_dir (or automatic_checkpoint_naming)")
        os.makedirs(output_dir, exist_ok=True)
        if sharded_state is None:
            # default: shard the checkpoint exactly when the state is sharded
            # (fsdp axis populated) and the plugin doesn't demand FULL —
            # reference FSDP state_dict_type semantics (fsdp_utils.py:66)
            plugin = getattr(self.state, "fsdp_plugin", None)
            fsdp_axis = dict(self.mesh.shape).get("fsdp", 1) if self.mesh else 1
            sharded_state = fsdp_axis > 1 and (
                plugin is None or plugin.state_dict_type == "SHARDED_STATE_DICT"
            )
        # pre-hooks see (models, weights, output_dir) and may mutate the
        # weights list — removing/replacing entries takes over saving for
        # those models (reference accelerator.py:3221); whatever is left is
        # exactly what gets written below (both sync and async paths)
        from .checkpointing import FrozenState

        weights = [dict(m.state_dict()) for m in self._models]
        for hook in self._save_state_pre_hooks.values():
            hook(self._models, weights, output_dir)
        model_states = [FrozenState(w) for w in weights]

        # Three-phase save (checkpointing.py): prepare runs EVERY collective
        # (unsharded multi-host gathers) and every device→host transfer here
        # on the main thread of every process, so the write phase is pure
        # file IO.  That is what makes async safe multi-process: the writer
        # thread never issues a collective that could race the training
        # loop's own (the dispatch-loader producer hazard).  snapshot=True
        # additionally deep-copies Python-side state; device arrays are
        # materialized to host numpy either way (donation in a later
        # captured step invalidates live buffers regardless of references).
        from .checkpointing import (
            finalize_accelerator_save,
            prepare_accelerator_save,
            write_accelerator_save,
        )

        plan = prepare_accelerator_save(
            output_dir,
            models=model_states,
            optimizers=self._optimizers,
            schedulers=self._schedulers,
            dataloaders=self._dataloaders,
            custom_objects=self._custom_objects,
            step=self.step,
            scaler=self.scaler,
            safe_serialization=safe_serialization,
            sharded_state=sharded_state,
            snapshot=async_save,
            # spec-carrying layout descriptor: stacked layer arrays are
            # written AS-IS (committed order); the record lets a restore
            # into a different layout transpose them (docs/parallel_plan.md)
            extra_meta=self._layer_layout_record(),
        )
        if not async_save:
            write_accelerator_save(plan)
            finalize_accelerator_save(plan)
            if self.resilience.enabled:
                self.resilience.note_checkpoint(output_dir)
            return output_dir

        import threading as _threading

        def _runner():
            try:
                write_accelerator_save(plan)
            except BaseException as exc:  # noqa: BLE001 — surfaced on wait
                self._async_save_error = exc

        self._async_save_error = None
        self._async_save_plan = plan
        self._async_save_dir = output_dir
        # non-daemon: a normal interpreter exit joins this thread, so a
        # script that ends right after save_state still gets a complete
        # checkpoint instead of a silently truncated one.  The collective
        # finalize (barrier + stale-artifact cleanup) runs on the main
        # thread in wait_for_checkpoint.
        self._async_save_thread = _threading.Thread(
            target=_runner, name="accelerate-tpu-async-save", daemon=False
        )
        self._async_save_thread.start()
        # Exit-without-wait safety net: CPython joins non-daemon threads
        # BEFORE atexit callbacks run, so a handler registered here sees the
        # write finished and can run the (deferred) finalize cleanup.
        # Single-process only — finalize's barriers are no-ops there; with
        # multiple processes an atexit-time collective against ranks that
        # may already be gone could hang, so those must call
        # wait_for_checkpoint (load_state/end_training do) or stale-file
        # cleanup is skipped.
        if self.num_processes == 1 and not getattr(self, "_async_atexit_armed", False):
            import atexit

            def _finalize_at_exit():
                try:
                    self.wait_for_checkpoint()
                except Exception as exc:  # noqa: BLE001 — exit path, log only
                    logger.warning(f"async checkpoint failed at interpreter exit: {exc}")

            atexit.register(_finalize_at_exit)
            self._async_atexit_armed = True
        return output_dir

    def register_save_state_pre_hook(self, hook):
        """Run ``hook(models, weights, output_dir)`` before every
        ``save_state`` write (reference accelerator.py:3074).  ``weights``
        is the list of state dicts about to be saved; mutating it (removing
        or replacing entries) customizes what gets written.  Returns a
        handle whose ``remove()`` detaches the hook."""
        from .hooks import RemovableHandle

        handle = RemovableHandle(self._save_state_pre_hooks)
        self._save_state_pre_hooks[handle.id] = hook
        return handle

    def register_load_state_pre_hook(self, hook):
        """Run ``hook(models, input_dir)`` before every ``load_state``
        restore (reference accelerator.py:3241).  Removing models from the
        list takes over loading for them.  Returns a removable handle."""
        from .hooks import RemovableHandle

        handle = RemovableHandle(self._load_state_pre_hooks)
        self._load_state_pre_hooks[handle.id] = hook
        return handle

    def wait_for_checkpoint(self) -> None:
        """Block until an in-flight ``save_state(async_save=True)`` is
        durable on disk; re-raise any error it hit.

        Collective on multi-process: after joining the local writer thread
        this runs the save's finalize phase (cross-process barrier +
        stale-artifact cleanup), so every process must call it — which the
        automatic call sites (``load_state``/``end_training``/the next
        ``save_state``) already do on every rank.  If the writer failed,
        cleanup is skipped (older checkpoint files stay loadable) and the
        error re-raises after the barrier."""
        thread = getattr(self, "_async_save_thread", None)
        if thread is None:
            return
        thread.join()
        self._async_save_thread = None
        error = getattr(self, "_async_save_error", None)
        self._async_save_error = None
        plan = getattr(self, "_async_save_plan", None)
        self._async_save_plan = None
        saved_dir = getattr(self, "_async_save_dir", None)
        self._async_save_dir = None
        failed = error is not None
        if plan is not None:
            from .checkpointing import finalize_accelerator_save

            if self.num_processes > 1:
                # cleanup must be all-or-nothing: a writer failure on ANY
                # rank means some new artifact is missing/truncated there,
                # and deleting the previous checkpoint's files elsewhere
                # would leave no loadable checkpoint at all
                from .utils.operations import gather_object

                failed = any(gather_object([failed]))
            finalize_accelerator_save(plan, cleanup=not failed)
        if error is not None:
            raise error
        if self.resilience.enabled and saved_dir is not None and not failed:
            # only a save that landed error-free ON EVERY RANK is a valid
            # rollback target
            self.resilience.note_checkpoint(saved_dir)

    def load_state(self, input_dir: Optional[str] = None, **kwargs) -> None:
        from .checkpointing import load_accelerator_state

        self.wait_for_checkpoint()
        if input_dir is None and self.project_configuration.automatic_checkpoint_naming:
            base = os.path.join(self.project_dir or ".", "checkpoints")
            # prefer the newest COMPLETE checkpoint (meta sentinel present):
            # a preempted run killed mid-write leaves a truncated newest
            # folder, and resuming must not load half a state
            from .checkpointing import latest_checkpoint

            input_dir = latest_checkpoint(base)
            if input_dir is None:
                folders = sorted(
                    (f for f in os.listdir(base) if f.startswith("checkpoint_")),
                    key=lambda f: int(f.split("_")[-1]),
                )
                if not folders:
                    raise FileNotFoundError(f"no checkpoints in {base}")
                input_dir = os.path.join(base, folders[-1])
        # pre-hooks see (models, input_dir) and may remove entries from the
        # list to take over loading for those models (reference
        # accelerator.py:3365); the loader restores whatever remains
        models = list(self._models)
        for hook in self._load_state_pre_hooks.values():
            hook(models, input_dir)
        # zero-cold-start coupling (docs/aot_cache.md): a restore — the
        # resilience rollback path and the latest_checkpoint preemption
        # resume both land here — warms the executable cache FIRST, so the
        # replayed step deserializes the same compiled program from memory
        # instead of recompiling (or even touching disk on the step path)
        if self.aot_cache.enabled and self.aot_cache.warm_on_restore:
            self.aot_cache.prefetch()
        override = load_accelerator_state(
            input_dir,
            models=models,
            optimizers=self._optimizers,
            schedulers=self._schedulers,
            dataloaders=self._dataloaders,
            custom_objects=self._custom_objects,
            scaler=self.scaler,
        )
        # cross-layout restore: transpose stacked layer arrays (params +
        # masters/moments) from the checkpoint's layer layout into the live
        # one; bitwise no-op when they match (incl. pre-layout checkpoints
        # into plain runs)
        self._retarget_layer_layout(override.pop("layer_layout", None))
        if "step" in override:
            self.step = override["step"]

    def free_memory(self, *objects):
        """Release references + device buffers (reference accelerator.py:3412)."""
        # the captured steps' compiled handles are about to go: let the
        # scope registry read their HLO text first (telemetry/profiler.py)
        from .telemetry.profiler import settle_programs

        settle_programs()
        self._models.clear()
        self._optimizers.clear()
        self._schedulers.clear()
        self._dataloaders.clear()
        self._custom_objects.clear()
        self._capture_cache.clear()
        # the ZeRO-2 pairs hold (param, sharding) references — leaving them
        # would keep every released param's device buffers reachable AND
        # re-layout stale grads on the next backward
        self._zero2_grads.clear()
        self.step = 0
        import gc

        gc.collect()
        return objects

    def clear(self, *objects):
        return self.free_memory(*objects)

    # -------------------------------------------------------------- tracking
    def init_trackers(self, project_name: str, config: Optional[dict] = None, init_kwargs: dict = {}) -> None:
        from .tracking import resolve_trackers

        self.trackers = resolve_trackers(
            self.log_with, project_name, self.logging_dir, init_kwargs
        )
        if config is not None:
            for tracker in self.trackers:
                tracker.store_init_configuration(config)
        if self.telemetry.enabled and self.trackers:
            # bridge: every accelerator.log() drains pending telemetry
            # events (step phases, recompile causes, HBM samples) into the
            # same backends as the user's metrics (telemetry/export.py).
            # First in the list: end_training finishes trackers in order,
            # and the bridge's finish() must flush into delegates that are
            # still open (a finished WandB run rejects further log calls).
            from .telemetry.export import TelemetryTracker

            self.trackers.insert(
                0, TelemetryTracker(self.telemetry, delegates=list(self.trackers))
            )

    def get_tracker(self, name: str, unwrap: bool = False):
        for tracker in self.trackers:
            if tracker.name == name:
                return tracker.tracker if unwrap else tracker
        raise ValueError(f"tracker {name} not initialized")

    def log(self, values: dict, step: Optional[int] = None, log_kwargs: dict = {}) -> None:
        if not self.is_main_process:
            return
        def _clean(v):
            if hasattr(v, "item") and getattr(v, "ndim", 1) == 0:
                return float(v.item())
            if hasattr(v, "tolist"):
                return v.tolist()
            return v

        clean = {k: _clean(v) for k, v in values.items()}
        for tracker in self.trackers:
            tracker.log(clean, step=step, **log_kwargs.get(tracker.name, {}))

    def end_training(self) -> None:
        self.wait_for_checkpoint()  # an in-flight async save must land
        self.resilience.close()  # restore default signal handling
        if self.telemetry.enabled and self.num_processes > 1:
            # fleet merge BEFORE any tracker finishes: the gather is
            # collective (every rank participates), and the main rank's
            # JSONL dump below — whether written here or by the bridge's
            # finish() — must already hold the rank-tagged records plus the
            # kind="fleet" skew record (docs/telemetry.md §aggregation)
            self.telemetry.aggregate_fleet()
        for tracker in self.trackers:
            tracker.finish()
        if self.telemetry.enabled and not any(
            t.name == "telemetry" for t in self.trackers
        ):
            # no-op unless a JSONL dump path was configured; the tracker
            # bridge, when present, already wrote it in finish()
            self.telemetry.write_jsonl()
        # black-box forensics teardown: the joined Chrome/Perfetto timeline
        # (no-op without a configured path), then the watchdog thread — the
        # flight ring itself stays live for any later manual dump
        self.telemetry.export_trace()
        self.telemetry.close_watchdog()
        self.telemetry.close_metrics()  # stop serving /metrics for this run
        self.wait_for_everyone()

    # --------------------------------------------------------------- contexts
    @contextlib.contextmanager
    def autocast(self, autocast_handler=None):
        """Local precision override (reference accelerator.py:3587).

        The *ambient* policy lands at prepare() time (params cast to bf16 and
        compute follows), so with ``enabled=True`` this yields unchanged.
        ``AutocastKwargs(enabled=False)`` opens a locally-fp32 region: the
        numerically-sensitive ``F.*`` ops traced inside (matmuls, norms,
        softmaxes, losses, attention) compute in fp32 regardless of param
        dtype — the reference's "disable autocast around the loss" idiom.
        Pure element-wise activations keep their operand dtype.  The region
        is a trace-time property: under ``compile_step`` the policy active at
        capture time is baked into the replayed program.
        """
        from .nn.amp import autocast_region

        handler = autocast_handler or self.autocast_handler
        if handler is not None and not handler.enabled:
            with autocast_region(jnp.float32):
                yield
            return
        yield

    @contextlib.contextmanager
    def profile(self, profile_handler: Optional[ProfileKwargs] = None):
        """jax.profiler trace (reference accelerator.py:3614 torch.profiler).

        Handler fields map onto ``jax.profiler.ProfileOptions``:
        ``host_tracer_level``/``python_tracer_level`` pass through directly;
        ``with_flops`` turns on HLO-proto capture (FLOPs are derivable from
        the HLO in TensorBoard's op profile); ``profile_memory`` additionally
        writes a device-memory profile next to the trace.
        ``device_tracer_level`` and ``record_shapes`` have no jax.profiler
        equivalent (device tracing is always on for TPU; shapes live in the
        HLO) and are accepted for reference API parity.
        """
        handler = profile_handler or self.profile_handler or ProfileKwargs()
        trace_dir = handler.output_trace_dir
        if trace_dir is None:
            yield None
            return
        os.makedirs(trace_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = handler.host_tracer_level
        options.python_tracer_level = handler.python_tracer_level
        if handler.with_flops:
            options.enable_hlo_proto = True
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            yield None
        finally:
            jax.profiler.stop_trace()
            if handler.profile_memory:
                jax.profiler.save_device_memory_profile(
                    os.path.join(trace_dir, "memory.prof")
                )
            if handler.on_trace_ready is not None:
                handler.on_trace_ready(trace_dir)

    @contextlib.contextmanager
    def local_sgd(self, *args, **kwargs):
        from .local_sgd import LocalSGD

        with LocalSGD(self, *args, **kwargs) as ctx:
            yield ctx

    # ---------------------------------------------------------- step capture
    def compile_step(self, fn: Callable) -> Callable:
        """Trace the imperative loop body once; replay as one XLA program.

        ``fn(*array_pytrees)`` may use prepared models/optimizers/schedulers
        imperatively (forward, ``accelerator.backward``, ``optimizer.step()``,
        ``scheduler.step()``...).  State (params, grads, optimizer state, RNG)
        is threaded as donated jit arguments; scheduler steps are deferred to
        python after each replay (their LR lands in the optimizer's
        hyperparams, which are part of the traced state).

        Returns a wrapper with the same signature; the return value of ``fn``
        must be a pytree of arrays/Tensors (e.g. the loss).
        """
        from .capture import CapturedStep

        return CapturedStep(self, fn)

    def __repr__(self):
        return (
            f"Accelerator(mesh={dict(self.state.mesh.shape)}, "
            f"mixed_precision={self.mixed_precision!r}, "
            f"grad_accum={self.gradient_accumulation_steps})"
        )

    # convenience parity helpers
    def skip_first_batches(self, dataloader, num_batches: int = 0):
        return skip_first_batches(dataloader, num_batches)

    @staticmethod
    def _reset_state(reset_partial_state: bool = True):
        AcceleratorState._reset_state(reset_partial_state)
        GradientState._reset_state()
