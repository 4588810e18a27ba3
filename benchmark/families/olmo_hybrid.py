"""The Olmo-Hybrid family as the program runs it: ``accelerate_tpu.models.olmo_hybrid``
built from a configuration file's keys, holding the weights the benchmark made
from the seed.  The only module of the family that imports the program.

The reference's tree and the program's module have the same leaves under the
same names (three globals and one dict per position in the period, its leaves
stacked over the repeats), so every parameter is set to the reference's own
array: the weights are held once.
"""

from __future__ import annotations

# at import, not at first use: a checkout whose program lacks the family (the
# parent of the PR that adds it) fails here, before a cell draws 8 GB of weights
from accelerate_tpu.models import olmo_hybrid as _program  # noqa: F401

REFERENCE = "olmo_hybrid"  # benchmark/reference/olmo_hybrid.py

_SAME_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads",
    "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim",
    "linear_conv_kernel_dim", "linear_allow_neg_eigval", "rms_norm_eps", "max_position_embeddings",
)


def program_config(cfg: dict):
    from accelerate_tpu.models.olmo_hybrid import OlmoHybridConfig

    return OlmoHybridConfig(
        layer_types=tuple(cfg["layer_types"]), chunk_size=cfg["assumed_sizes"]["chunk_size"],
        **{k: cfg[k] for k in _SAME_KEYS},
    )


def build_model(cfg: dict, params: dict):
    """The program's ``OlmoHybridForCausalLM`` at the file's sizes, its
    parameters set to ``params`` (the reference's tree).  Built empty, so
    nothing is initialised twice."""
    from accelerate_tpu import init_empty_weights
    from accelerate_tpu.models.olmo_hybrid import OlmoHybridForCausalLM

    with init_empty_weights():
        model = OlmoHybridForCausalLM(program_config(cfg))
    for name, p in model.named_parameters():
        *where, leaf = name.split(".")  # globals_.embed, layers.3.up_w
        p.data = params[leaf] if where == ["globals_"] else params["layers"][int(where[1])][leaf]
    return model
