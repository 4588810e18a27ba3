"""The GPT-2 family as the program runs it: ``accelerate_tpu.models.gpt``
built from a configuration file's keys, holding the weights the benchmark
made from the seed.  The only module of the family that imports the program.
"""

from __future__ import annotations

REFERENCE = "gpt2"  # benchmark/reference/gpt2.py

# program parameter name inside a block -> the reference's stacked key
_BLOCK_NAMES = {
    "ln_1.weight": "ln1_w", "ln_1.bias": "ln1_b",
    "attn.c_attn.weight": "qkv_w", "attn.c_attn.bias": "qkv_b",
    "attn.c_proj.weight": "proj_w", "attn.c_proj.bias": "proj_b",
    "ln_2.weight": "ln2_w", "ln_2.bias": "ln2_b",
    "mlp.c_fc.weight": "fc_w", "mlp.c_fc.bias": "fc_b",
    "mlp.c_proj.weight": "fcproj_w", "mlp.c_proj.bias": "fcproj_b",
}
_GLOBAL_NAMES = {
    "wte.weight": "wte", "wpe.weight": "wpe",
    "ln_f.weight": "ln_f_w", "ln_f.bias": "ln_f_b",
}


def canonical_name(program_name: str) -> str:
    """``h.3.attn.c_attn.weight`` -> ``h.3.qkv_w``; ``wte.weight`` -> ``wte``."""
    if program_name in _GLOBAL_NAMES:
        return _GLOBAL_NAMES[program_name]
    _, layer, rest = program_name.split(".", 2)
    return f"h.{layer}.{_BLOCK_NAMES[rest]}"


def program_config(cfg: dict):
    from accelerate_tpu.models import GPTConfig

    return GPTConfig(
        vocab_size=cfg["vocab_rows"], n_positions=cfg["n_positions"],
        n_embd=cfg["n_embd"], n_layer=cfg["n_layer"], n_head=cfg["n_head"],
        layer_norm_eps=cfg["layer_norm_epsilon"],
    )


def build_model(cfg: dict, params: dict):
    """The program's ``GPTLMHeadModel`` at the file's sizes, its parameters
    set to ``params`` (the reference's tree, stacked by layer).  The model is
    built empty, so nothing is initialised twice."""
    from accelerate_tpu import init_empty_weights
    from accelerate_tpu.models import GPTLMHeadModel

    with init_empty_weights():
        model = GPTLMHeadModel(program_config(cfg))
    for name, p in named_parameters(model):
        p.data = leaf_of(params, canonical_name(name))
    return model


def named_parameters(model) -> list:
    """Each parameter once (the tied head shares the token table's)."""
    seen, out = set(), []
    for name, p in model.named_parameters():
        if id(p) not in seen and not name.startswith("lm_head"):
            seen.add(id(p))
            out.append((name, p))
    return out


def leaf_of(tree: dict, canonical: str):
    if not canonical.startswith("h."):
        return tree[canonical]
    _, layer, key = canonical.split(".")
    return tree["blocks"][key][int(layer)]
