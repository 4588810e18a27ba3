"""The Nemotron-H family as the program runs it: ``accelerate_tpu.models.nemotron_h``
built from a configuration file's keys, holding the weights the benchmark made
from the seed.  The only module of the family that imports the program.

The reference's tree and the program's module have the same leaves under the
same names (three globals and one dict a layer), so every parameter is set to
the reference's own array: the weights are held once.
"""

from __future__ import annotations

# at import, not at first use: a checkout whose program lacks the family (the
# parent of the PR that adds it) fails here, before a cell draws 9 GB of weights
from accelerate_tpu.models import nemotron_h as _program  # noqa: F401

REFERENCE = "nemotron_h"  # benchmark/reference/nemotron_h.py


def program_config(cfg: dict):
    from accelerate_tpu.models.nemotron_h import NemotronHConfig

    return NemotronHConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        pattern=cfg["hybrid_override_pattern"],
        mamba_num_heads=cfg["mamba_num_heads"], mamba_head_dim=cfg["mamba_head_dim"],
        n_groups=cfg["n_groups"], ssm_state_size=cfg["ssm_state_size"],
        conv_kernel=cfg["conv_kernel"], chunk_size=cfg["chunk_size"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        n_routed_experts=cfg.get("router_width", cfg["n_routed_experts"]),
        experts_held=cfg["n_routed_experts"], expert_offset=cfg.get("expert_offset", 0),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=cfg["moe_shared_expert_intermediate_size"],
        routed_scaling_factor=cfg["routed_scaling_factor"], norm_eps=cfg["norm_eps"],
        max_position_embeddings=cfg["max_position_embeddings"],
    )


def build_model(cfg: dict, params: dict):
    """The program's ``NemotronHForCausalLM`` at the file's sizes, its
    parameters set to ``params`` (the reference's tree).  Built empty, so
    nothing is initialised twice."""
    from accelerate_tpu import init_empty_weights
    from accelerate_tpu.models.nemotron_h import NemotronHForCausalLM

    with init_empty_weights():
        model = NemotronHForCausalLM(program_config(cfg))
    for name, p in model.named_parameters():
        *where, leaf = name.split(".")  # globals_.embed, layers.3.up_w
        p.data = params[leaf] if where == ["globals_"] else params["layers"][int(where[1])][leaf]
    return model
