"""What LFM2-MoE's serving programs must read and compute, from the shapes.

Bytes a decode round must read (the weights of every layer once, of each
expert layer only the experts that received a row; the K/V of the attention
layers; the conv states) and the operations of a prefill wave (2 per weight
and token, the attention products causal).  What the arithmetic requires,
not what a program happens to execute.
"""

from __future__ import annotations


def expert_params(cfg: dict) -> int:
    """Parameters of one expert: its three SwiGLU projections."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_bytes_read(cfg: dict, experts_hit: float, itemsize: int = 2) -> float:
    """Bytes of expert weights a round must read when ``experts_hit`` experts,
    summed over the expert layers, received at least one row."""
    return float(experts_hit) * expert_params(cfg) * itemsize


def layer_params(cfg: dict, i: int, experts_hit: float | None = None) -> float:
    """Parameters layer ``i`` reads in a step; its experts all (None) or ``experts_hit``."""
    d, heads, kv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // heads
    if cfg["layer_types"][i] == "conv":
        op = 4 * d * d + d * cfg["conv_L_cache"]
    else:
        op = 2 * d * heads * hd + 2 * d * kv * hd + 2 * hd
    if i < cfg["num_dense_layers"]:
        ffn = 3 * d * cfg["intermediate_size"]
    else:
        hit = cfg["num_experts"] if experts_hit is None else experts_hit
        ffn = d * cfg["num_experts"] + hit * expert_params(cfg)
    return op + ffn + 2 * d


def decode_round_bytes(cfg: dict, slots: int, cache_len: int, experts_hit_per_layer: float | None = None,
                       itemsize: int = 2) -> dict:
    """Bytes one decode round of ``slots`` slots must read, by kind."""
    d, kv, hd = cfg["hidden_size"], cfg["num_key_value_heads"], cfg["hidden_size"] // cfg["num_attention_heads"]
    n = len(cfg["layer_types"])
    weights = sum(layer_params(cfg, i, experts_hit_per_layer) for i in range(n)) + cfg["vocab_size"] * d + d
    attn = sum(1 for k in cfg["layer_types"] if k == "full_attention")
    conv = n - attn
    return {
        "weights": weights * itemsize,
        "experts": sum(1 for i in range(n) if i >= cfg["num_dense_layers"])
        * (cfg["num_experts"] if experts_hit_per_layer is None else experts_hit_per_layer)
        * expert_params(cfg) * itemsize,
        "kv": attn * slots * cache_len * kv * hd * 2 * itemsize,
        "conv_state": conv * slots * d * (cfg["conv_L_cache"] - 1) * itemsize,
    }


def prefill_wave_flops(cfg: dict, batch: int, prompt: int) -> float:
    """Operations of one prefill wave of ``batch`` prompts of ``prompt`` tokens:
    2 per weight a token passes (top-k experts of each expert layer, the head
    for the last position only), the causal attention products."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    hd, k = d // heads, cfg["num_experts_per_tok"]
    per_token = sum(layer_params(cfg, i, k) - 2 * d for i in range(len(cfg["layer_types"])))
    attn = sum(1 for kind in cfg["layer_types"] if kind == "full_attention")
    return (2.0 * batch * prompt * per_token + 2.0 * batch * cfg["vocab_size"] * d
            + attn * 4.0 * batch * heads * prompt * prompt * hd / 2)
