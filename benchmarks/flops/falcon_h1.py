"""What Falcon-H1's serving programs must compute and move, from the shapes.

Operations of a prefill wave (2 per weight and token, the head for the last
position only, the causal half of attention, the products of the state-space
scan in its chunked form) and bytes of a decode round (every weight once; each
attention layer's K/V up to a slot's position; each streamed slot's state read
AND written, with its convolution columns).  What the arithmetic requires, not
what a program happens to execute.
"""

from __future__ import annotations

STATE_ITEMSIZE = 4  # the state is float32 (configuration file, dtypes.state)


def conv_dim(cfg: dict) -> int:
    return cfg["mamba_d_ssm"] + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]


def attention_params(cfg: dict) -> int:
    d, heads, kv, hd = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    return 2 * d * heads * hd + 2 * d * kv * hd


def mixer_matrix_params(cfg: dict) -> int:
    """``W_in`` and ``W_out``: what a token multiplies."""
    d, inner = cfg["hidden_size"], cfg["mamba_d_ssm"]
    return d * (inner + conv_dim(cfg) + cfg["mamba_n_heads"]) + inner * d


def mixer_params(cfg: dict) -> int:
    """... with the convolution's taps and bias, dt_bias / A_log / D a head and the norm's gain."""
    return (mixer_matrix_params(cfg) + conv_dim(cfg) * (cfg["mamba_d_conv"] + 1) + 3 * cfg["mamba_n_heads"]
            + cfg["mamba_d_ssm"])


def layer_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    return attention_params(cfg) + mixer_params(cfg) + 3 * d * cfg["intermediate_size"] + 2 * d


def model_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    return cfg["num_hidden_layers"] * layer_params(cfg) + 2 * cfg["vocab_size"] * d + d


def state_bytes(cfg: dict, slots: float) -> float:
    """Bytes of ``slots`` sequences' state-space state, all layers."""
    per_layer = cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"] * STATE_ITEMSIZE
    return float(slots) * cfg["num_hidden_layers"] * per_layer


def ssm_step_bytes(cfg: dict, slots_streamed: float, itemsize: int = 2) -> float:
    """Bytes the decode round's state-space steps must move: the state of every
    slot streamed, read once and written once, and its ``taps - 1`` convolution
    columns likewise."""
    conv = float(slots_streamed) * cfg["num_hidden_layers"] * conv_dim(cfg) * (cfg["mamba_d_conv"] - 1) * itemsize
    return 2.0 * (state_bytes(cfg, slots_streamed) + conv)


def kv_bytes_per_position(cfg: dict, itemsize: int = 2) -> int:
    """K and V of one position of one attention layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def decode_attn_bytes(cfg: dict, positions: float, itemsize: int = 2) -> float:
    """Bytes the decode round's attention must read: ``positions`` K/V positions
    (``kv_positions_live``: summed over the attention layers by the engine)."""
    return float(positions) * kv_bytes_per_position(cfg, itemsize)


def decode_round_bytes(cfg: dict, slots_streamed: int, kv_positions: float, itemsize: int = 2) -> dict:
    """Bytes one decode round must move, by kind."""
    return {
        "weights": (cfg["num_hidden_layers"] * layer_params(cfg) + cfg["vocab_size"] * cfg["hidden_size"]
                    + cfg["hidden_size"]) * itemsize,  # one embedding row a slot is noise
        "state_and_taps": ssm_step_bytes(cfg, slots_streamed, itemsize),
        "kv": decode_attn_bytes(cfg, kv_positions, itemsize),
    }


def prefill_attn_flops(cfg: dict, batch: int, prompt: int) -> float:
    """Causal scores and weighted values of the attention layers of one wave."""
    per_layer = 2 * (2.0 * cfg["num_attention_heads"] * prompt * prompt * cfg["head_dim"] / 2)
    return cfg["num_hidden_layers"] * batch * per_layer


def ssm_prefill_flops(cfg: dict, batch: int, prompt: int) -> float:
    """Products of the chunked scan of one wave: inside a chunk of ``L`` tokens
    the causal half of ``C . B`` a group and of the weights times ``dt x`` a
    head; the state a chunk leaves (``B (dt x)^T``) and the read of the state a
    chunk starts from (``C . S``), a head a token."""
    heads, p, n, g, chunk = (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"], cfg["mamba_n_groups"],
                             cfg["mamba_chunk_size"])
    inside = 2.0 * g * chunk * n / 2 + 2.0 * heads * chunk * p / 2
    across = 2 * (2.0 * heads * n * p)
    return cfg["num_hidden_layers"] * batch * prompt * (inside + across)


def prefill_wave_flops(cfg: dict, batch: int, prompt: int) -> float:
    """Operations of one prefill wave of ``batch`` prompts of ``prompt`` tokens."""
    d = cfg["hidden_size"]
    per_token = cfg["num_hidden_layers"] * (attention_params(cfg) + mixer_matrix_params(cfg)
                                             + 3 * d * cfg["intermediate_size"])
    return (2.0 * batch * prompt * per_token + 2.0 * batch * cfg["vocab_size"] * d
            + prefill_attn_flops(cfg, batch, prompt) + ssm_prefill_flops(cfg, batch, prompt))
