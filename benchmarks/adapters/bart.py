"""Where each reference tensor of BART sits in the program's parameter tree.

The only place in the benchmark that knows the program's tree for this
family.  ``leaf_map`` rows are (reference name, layer index or None, program
path, transpose): a projection is (out, in) in the published layout and
(in, out) as a flax kernel.
"""

from __future__ import annotations

REGISTRY_TABLE = "BART_CONFIGS"  # the dict of models/registry.py that names this family's configs

_ATTN = {"self_attn": "self_attn", "encoder_attn": "cross_attn"}
_PROJ = {"q_proj": "q_proj", "k_proj": "k_proj", "v_proj": "v_proj", "out_proj": "o_proj"}
_NORM = {
    "self_attn_layer_norm": "self_attn_layer_norm",
    "encoder_attn_layer_norm": "cross_attn_layer_norm",
    "final_layer_norm": "final_layer_norm",
}


def leaf_map(cfg: dict) -> list[tuple]:
    rows = [
        ("shared.weight", None, ("shared", "embedding"), False),
        ("final_logits_bias", None, ("final_logits_bias",), False),
    ]
    for side, n in (("encoder", cfg["encoder_layers"]), ("decoder", cfg["decoder_layers"])):
        rows.append((f"{side}.embed_positions.weight", None, (f"{side}_embed_positions", "embedding"), False))
        rows.append((f"{side}.layernorm_embedding.weight", None, (f"{side}_layernorm_embedding", "scale"), False))
        rows.append((f"{side}.layernorm_embedding.bias", None, (f"{side}_layernorm_embedding", "bias"), False))
        attns = ["self_attn"] + (["encoder_attn"] if side == "decoder" else [])
        norms = [a + "_layer_norm" for a in attns] + ["final_layer_norm"]
        for i in range(n):
            blk, pre = f"{side}_block_{i}", f"{side}.layers.*"
            for a in attns:
                for proj, ours in _PROJ.items():
                    rows.append((f"{pre}.{a}.{proj}.weight", i, (blk, _ATTN[a], ours, "kernel"), True))
                    rows.append((f"{pre}.{a}.{proj}.bias", i, (blk, _ATTN[a], ours, "bias"), False))
            for fc in ("fc1", "fc2"):
                rows.append((f"{pre}.{fc}.weight", i, (blk, "mlp", fc, "kernel"), True))
                rows.append((f"{pre}.{fc}.bias", i, (blk, "mlp", fc, "bias"), False))
            for norm in norms:
                rows.append((f"{pre}.{norm}.weight", i, (blk, _NORM[norm], "scale"), False))
                rows.append((f"{pre}.{norm}.bias", i, (blk, _NORM[norm], "bias"), False))
    return rows


def program_config_checks(cfg: dict) -> dict:
    """Fields of the program's model config that must equal the file's."""
    return {
        "vocab_size": cfg["vocab_size"], "d_model": cfg["d_model"],
        "encoder_layers": cfg["encoder_layers"], "decoder_layers": cfg["decoder_layers"],
        "encoder_attention_heads": cfg["encoder_attention_heads"],
        "decoder_attention_heads": cfg["decoder_attention_heads"],
        "encoder_ffn_dim": cfg["encoder_ffn_dim"], "decoder_ffn_dim": cfg["decoder_ffn_dim"],
        "max_position_embeddings": cfg["max_position_embeddings"],
        "scale_embedding": cfg.get("scale_embedding", False),
        "decoder_start_token_id": cfg["decoder_start_token_id"], "pad_token_id": cfg["pad_token_id"],
        "eos_token_id": cfg["eos_token_id"], "forced_bos_token_id": cfg.get("forced_bos_token_id"),
        "forced_eos_token_id": cfg.get("forced_eos_token_id"),
    }


def program_config_overrides(cfg: dict) -> dict:
    """Fields set from the file on the program's model config."""
    return {"dropout_rate": cfg["dropout"], "attn_dropout_rate": cfg["attention_dropout"]}
