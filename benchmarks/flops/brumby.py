"""What Brumby's serving programs must compute and move, from the shapes.

Operations of a prefill wave (2 per weight and token, the head for the last
position only, the retention products of the attention form and of the state
a prompt leaves) and bytes of a decode round (every weight once; each
streamed slot's retention state read AND written; q, k, v).  What the
arithmetic requires, not what a program happens to execute: the state has the
``d (d + 1) / 2`` distinct second powers a KV head (8,256 rows at d = 128),
whatever rows a layout pads them to.
"""

from __future__ import annotations

STATE_ITEMSIZE = 4  # the state is float32 (configuration file, dtypes.state)


def state_rows(cfg: dict) -> int:
    """Rows of a KV head's state: the distinct products of two of a key's components."""
    d = cfg["head_dim"]
    return d * (d + 1) // 2


def layer_params(cfg: dict) -> int:
    d, heads, kv, hd = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    ret = 2 * d * heads * hd + 2 * d * kv * hd + d * kv + kv + 2 * hd
    return ret + 3 * d * cfg["intermediate_size"] + 2 * d


def state_bytes(cfg: dict, slots: float) -> float:
    """Bytes of ``slots`` sequences' retention state (matrix and normaliser), all layers."""
    per_head = state_rows(cfg) * (cfg["head_dim"] + 1)
    return float(slots) * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * per_head * STATE_ITEMSIZE


def retention_step_bytes(cfg: dict, slots_streamed: float) -> float:
    """Bytes the decode round's retention steps must move: the state of every
    slot streamed, read once and written once."""
    return 2.0 * state_bytes(cfg, slots_streamed)


def decode_round_bytes(cfg: dict, slots_streamed: int, itemsize: int = 2) -> dict:
    """Bytes one decode round must move, by kind."""
    d, heads, kv, hd = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    n = cfg["num_hidden_layers"]
    state = state_bytes(cfg, slots_streamed)
    return {
        "weights": (n * layer_params(cfg) + cfg["vocab_size"] * d + d) * itemsize,  # one embedding row a slot is noise
        "state_read": state,
        "state_written": state,
        "qkv": n * slots_streamed * (heads + 2 * kv) * hd * itemsize,
    }


def retention_prefill_flops(cfg: dict, batch: int, prompt: int) -> float:
    """Products the retention layers of one wave require: causal scores and
    weighted values of the attention form, the state and normaliser a prompt leaves."""
    heads, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    attention = 2 * (2.0 * heads * prompt * prompt * hd / 2)
    state = 2.0 * kv * prompt * state_rows(cfg) * (hd + 1)
    return cfg["num_hidden_layers"] * batch * (attention + state)


def prefill_wave_flops(cfg: dict, batch: int, prompt: int) -> float:
    """Operations of one prefill wave of ``batch`` prompts of ``prompt`` tokens."""
    d = cfg["hidden_size"]
    per_token = cfg["num_hidden_layers"] * (layer_params(cfg) - 2 * d - 2 * cfg["head_dim"])
    return (2.0 * batch * prompt * per_token + 2.0 * batch * cfg["vocab_size"] * d
            + retention_prefill_flops(cfg, batch, prompt))
