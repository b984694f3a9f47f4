"""Operations one BART train step requires, from the computed shapes.

Matrix products forward and backward (2 + 4 = 6 per weight and token), the
attention score and value products (4*B*H*Sq*Sk*D forward, 8 backward; a
causal site needs half), the tied output head.  No recompute, no dropout, no
optimizer, no embedding gather: what the arithmetic requires, not what a
program happens to execute.
"""

from __future__ import annotations

from benchmarks.harness.flops import attention_flops  # noqa: F401 — read through this module by the metric files


def attention_sites(cfg: dict, batch: int, src: int, tgt: int) -> list[dict]:
    h, d = cfg["encoder_attention_heads"], cfg["d_model"] // cfg["encoder_attention_heads"]
    return [
        {"site": "encoder_self", "count": cfg["encoder_layers"], "b": batch, "h": h, "sq": src, "sk": src, "d": d, "causal": False},
        {"site": "decoder_self", "count": cfg["decoder_layers"], "b": batch, "h": h, "sq": tgt, "sk": tgt, "d": d, "causal": True},
        {"site": "decoder_cross", "count": cfg["decoder_layers"], "b": batch, "h": h, "sq": tgt, "sk": src, "d": d, "causal": False},
    ]


def train_step_flops(cfg: dict, batch: int, src: int, tgt: int) -> float:
    d, v = cfg["d_model"], cfg["vocab_size"]
    enc = cfg["encoder_layers"] * (4 * d * d + 2 * d * cfg["encoder_ffn_dim"])
    dec_tgt = cfg["decoder_layers"] * (4 * d * d + 2 * d * d + 2 * d * cfg["decoder_ffn_dim"])
    dec_src = cfg["decoder_layers"] * (2 * d * d)  # cross-attention keys and values, from the source
    matmul = 6.0 * (batch * src * (enc + dec_src) + batch * tgt * (dec_tgt + d * v))
    return matmul + attention_flops(attention_sites(cfg, batch, src, tgt))
