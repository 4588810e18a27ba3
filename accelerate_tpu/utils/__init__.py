from .constants import (
    ALL_MESH_AXES,
    CUSTOM_STATES_NAME,
    MODEL_NAME,
    OPTIMIZER_NAME,
    RNG_STATE_NAME,
    SAMPLER_NAME,
    SCHEDULER_NAME,
    TPU_PAD_MULTIPLE,
    WEIGHTS_NAME,
)
from .dataclasses import (
    AutocastKwargs,
    BaseEnum,
    CompressionKwargs,
    ComputeBackend,
    DataLoaderConfiguration,
    DataParallelPlugin,
    DistributedDataParallelKwargs,
    DistributedType,
    ExpertParallelPlugin,
    FleetKwargs,
    FP8RecipeKwargs,
    FullyShardedDataParallelPlugin,
    GradientAccumulationPlugin,
    GradScalerKwargs,
    InitProcessGroupKwargs,
    KwargsHandler,
    KernelKwargs,
    LoggerType,
    ParallelismConfig,
    PipelineParallelPlugin,
    PrecisionType,
    ProfileKwargs,
    ProjectConfiguration,
    ResilienceKwargs,
    RNGType,
    SaveFormat,
    SequenceParallelPlugin,
    TelemetryKwargs,
    TensorParallelPlugin,
)
from .fp8 import FP8Linear, convert_to_float8_training
from .quantization import (
    QuantizationConfig,
    QuantizedLinear,
    load_and_quantize_model,
    replace_with_quantized_layers,
)
from .fsdp_utils import (
    load_sharded_model_state,
    merge_sharded_weights,
    save_sharded_model_state,
)
from .environment import (
    are_libraries_initialized,
    clear_environment,
    compilation_cache_dir,
    convert_dict_to_env_variables,
    enable_compilation_cache,
    get_int_from_env,
    parse_choice_from_env,
    parse_flag_from_env,
    patch_environment,
    str_to_bool,
)
from .imports import (
    is_aim_available,
    is_clearml_available,
    is_comet_ml_available,
    is_datasets_available,
    is_dvclive_available,
    is_flax_available,
    is_jax_available,
    is_mlflow_available,
    is_optax_available,
    is_orbax_available,
    is_pallas_available,
    is_rich_available,
    is_safetensors_available,
    is_tensorboard_available,
    is_torch_available,
    is_tpu_available,
    is_tqdm_available,
    is_transformers_available,
    is_wandb_available,
)
from .memory import (
    clear_device_cache,
    find_executable_batch_size,
    get_device_memory_stats,
    opt_state_bytes_per_replica,
    release_memory,
    should_reduce_batch_size,
)
from .other import (
    clean_state_dict_for_safetensors,
    convert_bytes,
    extract_model_from_parallel,
    get_pretty_name,
    load,
    merge_dicts,
    recursive_getattr,
    save,
    wait_for_everyone,
)
from .random import set_seed, synchronize_rng_state, synchronize_rng_states
from .tqdm import tqdm
from .versions import compare_versions, is_jax_version

# flat re-exports matching the reference's `accelerate.utils` namespace
# (utils/__init__.py there) — migrating code does
# `from accelerate.utils import gather_object, send_to_device, ...` and the
# same names must resolve here
from .operations import (
    broadcast,
    broadcast_object_list,
    concatenate,
    convert_to_fp32,
    find_batch_size,
    find_device,
    gather,
    gather_object,
    get_data_structure,
    honor_type,
    initialize_tensors,
    listify,
    pad_across_processes,
    pad_input_tensors,
    recursively_apply,
    reduce,
    send_to_device,
    slice_tensors,
)
from .modeling import (
    calculate_maximum_sizes,
    check_device_map,
    compute_module_sizes,
    convert_file_size_to_int,
    dtype_byte_size,
    find_tied_parameters,
    get_balanced_memory,
    get_max_memory,
    has_offloaded_params,
    infer_auto_device_map,
    load_checkpoint_in_model,
    named_module_tensors,
    retie_parameters,
    set_module_tensor_to_device,
)
from .offload import (
    load_offloaded_weight,
    offload_state_dict,
    offload_weight,
    save_offload_index,
)
