"""Where each reference tensor of Brumby sits in the program's parameter tree.

The only place in the benchmark that knows the program's tree for this
family.  ``leaf_map`` rows are (reference name, layer index or None, program
path, transpose).  The reference names its tensors per layer and holds them as
the program does (``reference/brumby.py``), so no row stacks or transposes.
"""

from __future__ import annotations

REGISTRY_TABLE = "BRUMBY_CONFIGS"  # the dict of models/registry.py that names this family's configs

_RET = {"q_proj.weight": ("q_proj", "kernel"), "k_proj.weight": ("k_proj", "kernel"),
        "v_proj.weight": ("v_proj", "kernel"), "o_proj.weight": ("o_proj", "kernel"),
        "q_norm.weight": ("q_norm", "scale"), "k_norm.weight": ("k_norm", "scale"),
        "g_proj.weight": ("g_proj", "kernel"), "g_proj.bias": ("g_proj", "bias")}
_NORM = {"input_layernorm": "input_norm", "post_attention_layernorm": "post_norm"}


def leaf_map(cfg: dict) -> list[tuple]:
    rows = [
        ("embed_tokens.weight", None, ("embed_tokens", "embedding"), False),
        ("norm.weight", None, ("final_norm", "scale"), False),
        ("lm_head.weight", None, ("lm_head", "kernel"), False),
    ]
    for i in range(cfg["num_hidden_layers"]):
        blk, pre = f"block_{i}", f"layers.{i}"
        rows += [(f"{pre}.{n}.weight", None, (blk, ours, "scale"), False) for n, ours in _NORM.items()]
        rows += [(f"{pre}.self_attn.{n}", None, (blk, "retention", *path), False) for n, path in _RET.items()]
        rows += [(f"{pre}.mlp.{n}.weight", None, (blk, "mlp", n, "kernel"), False)
                 for n in ("gate_proj", "up_proj", "down_proj")]
    return rows


def program_config_checks(cfg: dict) -> dict:
    """Fields of the program's model config that must equal the file's: every width and head count."""
    keys = ("hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta", "max_position_embeddings", "retention_degree", "retention_eps",
            "pad_token_id", "bos_token_id")
    return {k: cfg[k] for k in keys}


def program_config_overrides(cfg: dict) -> dict:
    """Fields set from the file on the program's model config: what ``reduced``
    lists (the depth, the vocabulary's slice), the parameter dtype, the
    end-of-sequence id (null in the cell's file: requests run to their budget)
    and the residual dropout a trainer would apply (serving applies none)."""
    return {
        "num_hidden_layers": cfg["num_hidden_layers"], "vocab_size": cfg["vocab_size"],
        "param_dtype": cfg["dtypes"]["params"] if cfg["dtypes"]["params"] != "float32" else None,
        "eos_token_id": cfg["eos_token_id"], "dropout_rate": cfg["dropout"],
    }
