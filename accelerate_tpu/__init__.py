"""accelerate_tpu — a TPU-native training & inference framework.

A from-scratch rebuild of the capability surface of HuggingFace Accelerate
(reference snapshot surveyed in SURVEY.md) designed for JAX/XLA/Pallas on
Cloud TPU: one SPMD program over a ``jax.sharding.Mesh`` replaces the
reference's ten process backends; FSDP/TP/SP/PP are mesh-axis layouts, not
wrapper modules; collectives are compiled into the step by XLA and ride ICI.
"""

__version__ = "0.1.0"

from .accelerator import Accelerator
from .big_modeling import (
    cpu_offload,
    cpu_offload_with_hook,
    disk_offload,
    dispatch_model,
    init_empty_weights,
    init_on_device,
    load_checkpoint_and_dispatch,
    materialize_meta_module,
    shard_for_inference,
)
from .serving import DecodeService, ServingConfig
from .state import AcceleratorState, GradientState, PartialState
from .logging import get_logger
from .data_loader import PaddingCollate, prepare_data_loader, skip_first_batches
from .utils.environment import compilation_cache_dir, enable_compilation_cache
from .utils.memory import find_executable_batch_size
from .utils.modeling import (
    find_tied_parameters,
    get_balanced_memory,
    get_max_memory,
    infer_auto_device_map,
    load_checkpoint_in_model,
    set_module_tensor_to_device,
)
from .utils.random import set_seed, synchronize_rng_states
from .utils.dataclasses import (
    CompilationCacheKwargs,
    CompressionKwargs,
    DataLoaderConfiguration,
    DataParallelPlugin,
    DistributedType,
    FleetKwargs,
    FullyShardedDataParallelPlugin,
    GradientAccumulationPlugin,
    InitProcessGroupKwargs,
    KernelKwargs,
    ParallelismConfig,
    ProfileKwargs,
    ProjectConfiguration,
    ResilienceKwargs,
    SequenceParallelPlugin,
    TelemetryKwargs,
    TensorParallelPlugin,
)
