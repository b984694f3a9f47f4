"""Where each reference tensor of Mellum sits in the program's parameter tree.

The only place in the benchmark that knows the program's tree for this family.
``leaf_map`` rows are (reference name, layer index or None, program path,
transpose).  The reference names its tensors per layer and holds them as the
program does (``reference/mellum.py``), so no row stacks or transposes: the one
jitted call that makes the tree makes one copy of each tensor.
"""

from __future__ import annotations

REGISTRY_TABLE = "MELLUM_CONFIGS"  # the dict of models/registry.py that names this family's configs

_NORM = {"input_layernorm": "input_norm", "post_attention_layernorm": "post_norm"}
_ATTN = {"q_proj": ("q_proj", "kernel"), "k_proj": ("k_proj", "kernel"), "v_proj": ("v_proj", "kernel"),
         "o_proj": ("o_proj", "kernel"), "q_norm": ("q_norm", "scale"), "k_norm": ("k_norm", "scale")}


def leaf_map(cfg: dict) -> list[tuple]:
    rows = [
        ("embed_tokens.weight", None, ("embed_tokens", "embedding"), False),
        ("norm.weight", None, ("final_norm", "scale"), False),
        ("lm_head.weight", None, ("lm_head", "kernel"), False),
    ]
    for i in range(cfg["num_hidden_layers"]):
        blk, pre = f"block_{i}", f"layers.{i}"
        rows += [(f"{pre}.{n}.weight", None, (blk, ours, "scale"), False) for n, ours in _NORM.items()]
        rows += [(f"{pre}.self_attn.{n}.weight", None, (blk, "self_attn", *path), False) for n, path in _ATTN.items()]
        rows.append((f"{pre}.mlp.gate.weight", None, (blk, "mlp", "router", "kernel"), False))
        rows += [(f"{pre}.mlp.experts.{n}.weight", None, (blk, "mlp", n), False)
                 for n in ("gate_proj", "up_proj", "down_proj")]
    return rows


def program_config_checks(cfg: dict) -> dict:
    """Fields of the program's model config that must equal the file's: every
    width, head count, the window, the experts and their top-k, both rotations."""
    keys = ("hidden_size", "moe_intermediate_size", "num_attention_heads", "num_key_value_heads", "head_dim",
            "sliding_window", "num_experts", "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps",
            "max_position_embeddings", "pad_token_id", "bos_token_id")
    checks = {k: cfg[k] for k in keys}
    full, window = cfg["rope_parameters"]["full_attention"], cfg["rope_parameters"]["sliding_attention"]
    if window["rope_type"] != "default" or window["rope_theta"] != full["rope_theta"]:
        raise SystemExit("the program rotates its window layers plainly, at the full layers' theta")
    checks["rope_theta"] = float(full["rope_theta"])
    return checks


def program_config_overrides(cfg: dict) -> dict:
    """Fields set from the file on the program's model config: what ``reduced``
    lists (the depth with its layer kinds, the vocabulary's slice), the full
    layers' YaRN numbers as the file's ``rope_parameters`` gives them, the
    parameter dtype, the end-of-sequence id (null in the cell's file: requests
    run to their budget) and the residual dropout a trainer would apply."""
    from distributed_llms_example_tpu.ops.mha import YarnRope

    full = cfg["rope_parameters"]["full_attention"]
    yarn = None
    if full["rope_type"] == "yarn":
        yarn = YarnRope(factor=float(full["factor"]),
                        original_max_position_embeddings=int(full["original_max_position_embeddings"]),
                        beta_fast=float(full["beta_fast"]), beta_slow=float(full["beta_slow"]),
                        attention_factor=float(full["attention_factor"]))
    return {
        "num_hidden_layers": cfg["num_hidden_layers"], "layer_types": tuple(cfg["layer_types"]),
        "vocab_size": cfg["vocab_size"], "rope_yarn": yarn,
        "param_dtype": cfg["dtypes"]["params"] if cfg["dtypes"]["params"] != "float32" else None,
        "eos_token_id": cfg["eos_token_id"], "dropout_rate": cfg["dropout"],
    }
