"""What Mellum's serving programs must read and compute, from the shapes.

Bytes a decode round must read (the experts that received a row; the K/V
positions the round's attention needs: a full layer's whole context, a window
layer's last ``sliding_window``) and the operations of a prefill wave (2 per
weight and token, the top-k experts of every layer, the head for the last
position only, the attention products causal on a full layer and the BAND on a
window layer).  What the arithmetic requires, not what a program happens to
execute: a kernel that visits key tiles outside the band, or streams K/V
entries no query needs, reads low against these.
"""

from __future__ import annotations


def expert_params(cfg: dict) -> int:
    """Parameters of one expert: its three SwiGLU projections."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_bytes_read(cfg: dict, experts_hit: float, itemsize: int = 2) -> float:
    """Bytes of expert weights a round must read when ``experts_hit`` experts,
    summed over the layers, received at least one row."""
    return float(experts_hit) * expert_params(cfg) * itemsize


def attention_params(cfg: dict) -> int:
    d, heads, kv, hd = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    return 2 * d * heads * hd + 2 * d * kv * hd + 2 * hd


def layer_params(cfg: dict, experts_hit: float | None = None) -> float:
    """Parameters a layer reads in a step; its experts all (None) or ``experts_hit``."""
    d = cfg["hidden_size"]
    hit = cfg["num_experts"] if experts_hit is None else experts_hit
    return attention_params(cfg) + d * cfg["num_experts"] + hit * expert_params(cfg) + 2 * d


def band_pairs(prompt: int, window: int) -> float:
    """(query, key) pairs of a sliding-window layer over ``prompt`` tokens:
    query i reads ``min(i + 1, window)`` keys."""
    w = min(window, prompt)
    return w * (w + 1) / 2 + (prompt - w) * w


def prefill_attn_flops(cfg: dict, batch: int, prompt: int) -> float:
    """The attention products (scores and weighted values, 2 operations a
    multiply-add each) of one wave: the causal half on a full layer, the band
    on a window layer."""
    heads, hd = cfg["num_attention_heads"], cfg["head_dim"]
    pairs = sum(
        prompt * (prompt + 1) / 2 if kind == "full_attention" else band_pairs(prompt, cfg["sliding_window"])
        for kind in cfg["layer_types"])
    return 4.0 * batch * heads * hd * pairs


def prefill_wave_flops(cfg: dict, batch: int, prompt: int) -> float:
    """Operations of one prefill wave of ``batch`` prompts of ``prompt`` tokens."""
    d, k = cfg["hidden_size"], cfg["num_experts_per_tok"]
    per_token = len(cfg["layer_types"]) * (layer_params(cfg, k) - 2 * d - 2 * cfg["head_dim"])
    return 2.0 * batch * prompt * per_token + 2.0 * batch * cfg["vocab_size"] * d + prefill_attn_flops(cfg, batch, prompt)


def decode_attn_bytes(cfg: dict, kv_positions: float, itemsize: int = 2) -> float:
    """Bytes of K/V a decode round's attention must read for ``kv_positions``
    positions summed over the attention layers and the live slots (the engine's
    ``kv_positions_live``): a key and a value of every KV head each."""
    return float(kv_positions) * 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize
