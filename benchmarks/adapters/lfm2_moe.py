"""Where each reference tensor of LFM2-MoE sits in the program's parameter tree.

The only place in the benchmark that knows the program's tree for this
family.  ``leaf_map`` rows are (reference name, layer index or None, program
path, transpose).  The reference names its tensors per layer and holds them as
the program does (``reference/lfm2_moe.py``), so no row stacks or transposes:
the one jitted call that makes the tree makes one copy of each tensor.
"""

from __future__ import annotations

REGISTRY_TABLE = "LFM2_CONFIGS"  # the dict of models/registry.py that names this family's configs

_ATTN = {"q_proj": ("q_proj", "kernel"), "k_proj": ("k_proj", "kernel"), "v_proj": ("v_proj", "kernel"),
         "out_proj": ("o_proj", "kernel"), "q_layernorm": ("q_norm", "scale"), "k_layernorm": ("k_norm", "scale")}
_CONV = {"in_proj": ("in_proj", "kernel"), "conv": ("conv_weight",), "out_proj": ("out_proj", "kernel")}
_DENSE = {"w1": "gate_proj", "w3": "up_proj", "w2": "down_proj"}


def leaf_map(cfg: dict) -> list[tuple]:
    rows = [
        ("embed_tokens.weight", None, ("embed_tokens", "embedding"), False),
        ("final_norm.weight", None, ("final_norm", "scale"), False),
    ]
    for i, kind in enumerate(cfg["layer_types"]):
        blk, pre = f"block_{i}", f"layers.{i}"
        rows.append((f"{pre}.operator_norm.weight", None, (blk, "operator_norm", "scale"), False))
        rows.append((f"{pre}.ffn_norm.weight", None, (blk, "ffn_norm", "scale"), False))
        if kind == "conv":
            rows += [(f"{pre}.conv.{n}.weight", None, (blk, "conv", *path), False) for n, path in _CONV.items()]
        else:
            rows += [(f"{pre}.self_attn.{n}.weight", None, (blk, "self_attn", *path), False) for n, path in _ATTN.items()]
        ff = f"{pre}.feed_forward"
        if i < cfg["num_dense_layers"]:
            rows += [(f"{ff}.{n}.weight", None, (blk, "mlp", ours, "kernel"), False) for n, ours in _DENSE.items()]
        else:
            rows.append((f"{ff}.gate.weight", None, (blk, "mlp", "router", "kernel"), False))
            rows.append((f"{ff}.expert_bias", None, (blk, "mlp", "expert_bias"), False))
            rows += [(f"{ff}.experts.{n}.weight", None, (blk, "mlp", ours), False) for n, ours in _DENSE.items()]
    return rows


def program_config_checks(cfg: dict) -> dict:
    """Fields of the program's model config that must equal the file's: every
    width, head count, expert count, top-k and the vocabulary."""
    keys = ("vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads",
            "num_key_value_heads", "conv_L_cache", "num_experts", "num_experts_per_tok", "norm_topk_prob",
            "use_expert_bias", "routed_scaling_factor", "norm_eps", "rope_theta", "max_position_embeddings",
            "pad_token_id", "bos_token_id")
    return {k: cfg[k] for k in keys}


def program_config_overrides(cfg: dict) -> dict:
    """Fields set from the file on the program's model config: what ``reduced``
    lists (the depth), the parameter dtype, the end-of-sequence id (null in
    the cells' files: requests run to their budget, see ``assumed``) and the
    residual dropout a trainer would apply (serving applies none)."""
    return {
        "num_hidden_layers": cfg["num_hidden_layers"], "layer_types": tuple(cfg["layer_types"]),
        "num_dense_layers": cfg["num_dense_layers"], "param_dtype": cfg["dtypes"]["params"],
        "eos_token_id": cfg["eos_token_id"], "dropout_rate": cfg["dropout"],
    }
