"""Operations one T5 train step requires, from the computed shapes (see
``flops/bart.py`` for what counts).  The learned relative bias adds no
product; its gradient (the dbias pass) is a reduction, not counted."""

from __future__ import annotations

from benchmarks.harness.flops import attention_flops  # noqa: F401 — read through this module by the metric files


def attention_sites(cfg: dict, batch: int, src: int, tgt: int) -> list[dict]:
    h, d = cfg["num_heads"], cfg["d_kv"]
    n_dec = cfg.get("num_decoder_layers") or cfg["num_layers"]
    return [
        {"site": "encoder_self", "count": cfg["num_layers"], "b": batch, "h": h, "sq": src, "sk": src, "d": d, "causal": False},
        {"site": "decoder_self", "count": n_dec, "b": batch, "h": h, "sq": tgt, "sk": tgt, "d": d, "causal": True},
        {"site": "decoder_cross", "count": n_dec, "b": batch, "h": h, "sq": tgt, "sk": src, "d": d, "causal": False},
    ]


def train_step_flops(cfg: dict, batch: int, src: int, tgt: int) -> float:
    d, v, inner, ff = cfg["d_model"], cfg["vocab_size"], cfg["num_heads"] * cfg["d_kv"], cfg["d_ff"]
    n_dec = cfg.get("num_decoder_layers") or cfg["num_layers"]
    enc = cfg["num_layers"] * (4 * d * inner + 2 * d * ff)
    dec_tgt = n_dec * (4 * d * inner + 2 * d * inner + 2 * d * ff)
    dec_src = n_dec * (2 * d * inner)
    matmul = 6.0 * (batch * src * (enc + dec_src) + batch * tgt * (dec_tgt + d * v))
    return matmul + attention_flops(attention_sites(cfg, batch, src, tgt))
