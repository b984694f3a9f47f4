"""Where each reference tensor of T5 sits in the program's parameter tree
(see ``adapters/bart.py`` for the row format)."""

from __future__ import annotations

REGISTRY_TABLE = "T5_CONFIGS"  # the dict of models/registry.py that names this family's configs

_ATTN = {"SelfAttention": ("self_attn", "self_attn_norm"), "EncDecAttention": ("cross_attn", "cross_attn_norm")}
_PROJ = {"q": "q_proj", "k": "k_proj", "v": "v_proj", "o": "o_proj"}


def leaf_map(cfg: dict) -> list[tuple]:
    rows = [("shared.weight", None, ("shared", "embedding"), False)]
    for side, n in (("encoder", cfg["num_layers"]), ("decoder", cfg.get("num_decoder_layers") or cfg["num_layers"])):
        rows.append((f"{side}.relative_attention_bias.weight", None,
                     (side, "relative_attention_bias", "embedding"), False))
        rows.append((f"{side}.final_layer_norm.weight", None, (side, "final_norm", "scale"), False))
        attns = ["SelfAttention"] + (["EncDecAttention"] if side == "decoder" else [])
        for i in range(n):
            blk, pre = f"block_{i}", f"{side}.block.*"
            for a in attns:
                ours, norm = _ATTN[a]
                for proj, name in _PROJ.items():
                    rows.append((f"{pre}.{a}.{proj}.weight", i, (side, blk, ours, name, "kernel"), True))
                rows.append((f"{pre}.{a}.layer_norm.weight", i, (side, blk, norm, "scale"), False))
            for w in ("wi", "wo"):
                rows.append((f"{pre}.DenseReluDense.{w}.weight", i, (side, blk, "mlp", w, "kernel"), True))
            rows.append((f"{pre}.DenseReluDense.layer_norm.weight", i, (side, blk, "mlp_norm", "scale"), False))
    return rows


def program_config_checks(cfg: dict) -> dict:
    return {
        "vocab_size": cfg["vocab_size"], "d_model": cfg["d_model"], "d_kv": cfg["d_kv"], "d_ff": cfg["d_ff"],
        "num_layers": cfg["num_layers"], "num_heads": cfg["num_heads"],
        "relative_attention_num_buckets": cfg["relative_attention_num_buckets"],
        "feed_forward_proj": cfg.get("feed_forward_proj", "relu"),
        "tie_word_embeddings": cfg.get("tie_word_embeddings", True),
        "pad_token_id": cfg.get("pad_token_id", 0), "eos_token_id": cfg.get("eos_token_id", 1),
        "decoder_start_token_id": cfg.get("decoder_start_token_id", 0),
    }


def program_config_overrides(cfg: dict) -> dict:
    return {"dropout_rate": cfg["dropout_rate"]}
