"""Where each reference tensor of Falcon-H1 sits in the program's parameter tree.

The only place in the benchmark that knows the program's tree for this
family.  ``leaf_map`` rows are (reference name, layer index or None, program
path, transpose).  The reference names its tensors per layer and holds them as
the program does (``reference/falcon_h1.py``), so no row stacks or transposes.
"""

from __future__ import annotations

REGISTRY_TABLE = "FALCON_H1_CONFIGS"  # the dict of models/registry.py that names this family's configs

_ATTN = ("q_proj", "k_proj", "v_proj", "o_proj")
_MIXER = {"in_proj.weight": ("in_proj", "kernel"), "conv1d.weight": ("conv_weight",), "conv1d.bias": ("conv_bias",),
          "dt_bias": ("dt_bias",), "A_log": ("A_log",), "D": ("D",), "norm.weight": ("norm_scale",),
          "out_proj.weight": ("out_proj", "kernel")}
_NORM = {"input_layernorm": "input_norm", "pre_ff_layernorm": "ffn_norm"}
MULTIPLIERS = ("embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier", "attention_out_multiplier",
               "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier")


def leaf_map(cfg: dict) -> list[tuple]:
    rows = [
        ("embed_tokens.weight", None, ("embed_tokens", "embedding"), False),
        ("final_layernorm.weight", None, ("final_norm", "scale"), False),
        ("lm_head.weight", None, ("lm_head", "kernel"), False),
    ]
    for i in range(cfg["num_hidden_layers"]):
        blk, pre = f"block_{i}", f"layers.{i}"
        rows += [(f"{pre}.{n}.weight", None, (blk, ours, "scale"), False) for n, ours in _NORM.items()]
        rows += [(f"{pre}.self_attn.{n}.weight", None, (blk, "self_attn", n, "kernel"), False) for n in _ATTN]
        rows += [(f"{pre}.mamba.{n}", None, (blk, "mixer", *path), False) for n, path in _MIXER.items()]
        rows += [(f"{pre}.feed_forward.{n}.weight", None, (blk, "mlp", n, "kernel"), False)
                 for n in ("gate_proj", "up_proj", "down_proj")]
    return rows


def program_config_checks(cfg: dict) -> dict:
    """Fields of the program's model config that must equal the file's: every
    width, head count and state size, and each of the fourteen multipliers."""
    keys = ("hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads", "head_dim",
            "mamba_d_ssm", "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
            "mamba_chunk_size", "rms_norm_eps", "rope_theta", "max_position_embeddings", "pad_token_id",
            "bos_token_id") + MULTIPLIERS
    checks = {k: cfg[k] for k in keys}
    checks.update(ssm_multipliers=tuple(cfg["ssm_multipliers"]), mlp_multipliers=tuple(cfg["mlp_multipliers"]))
    return checks


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
