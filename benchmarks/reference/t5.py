"""Plain T5 v1.0 (Raffel et al. 2020; ``T5ForConditionalGeneration``), float32.

Written from the published equations, with no kernel, no cache and nothing
of the program: pre-norm residual blocks with RMS normalisation (no mean, no
bias), bias-free projections, attention scores NOT scaled by 1/sqrt(d_kv),
one learned relative-position bias table per stack (bucketed log-distance;
bidirectional in the encoder, one-sided in the decoder; none on
cross-attention), ReLU feed-forward, final stack norm, output head tied to
the embedding with the hidden state scaled by d_model**-0.5.  Per-layer
tensors are stacked and walked with ``lax.scan`` (see ``bart.py``).

Only the relu, tied flavour is written; a configuration with another
``feed_forward_proj`` or an untied head is refused (flan-t5-xl needs that
branch added here: PERF.md, Open questions).

Departure of the program's model (``models/t5.py``): it applies no dropout
to the attention probabilities where the published model does at
``dropout_rate`` — no matter here, the cells run dropout as the identity.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG = -1e9


def param_spec(cfg: dict) -> dict:
    """name -> (shape, mean, std): the published initialisation (factor 1)."""
    if cfg.get("feed_forward_proj", "relu") != "relu" or not cfg.get("tie_word_embeddings", True):
        raise NotImplementedError("reference/t5.py holds the relu, tied T5 v1.0 only")
    d, kv, h, ff = cfg["d_model"], cfg["d_kv"], cfg["num_heads"], cfg["d_ff"]
    inner = h * kv
    spec = {"shared.weight": ((cfg["vocab_size"], d), 0.0, 1.0)}
    for side, n in (("encoder", cfg["num_layers"]), ("decoder", cfg.get("num_decoder_layers") or cfg["num_layers"])):
        spec[f"{side}.relative_attention_bias.weight"] = (
            (cfg["relative_attention_num_buckets"], h), 0.0, d**-0.5)
        spec[f"{side}.final_layer_norm.weight"] = ((d,), 1.0, 0.02)
        attns = ["SelfAttention"] + (["EncDecAttention"] if side == "decoder" else [])
        for a in attns:
            spec[f"{side}.block.*.{a}.q.weight"] = ((n, inner, d), 0.0, (d * kv) ** -0.5)
            spec[f"{side}.block.*.{a}.k.weight"] = ((n, inner, d), 0.0, d**-0.5)
            spec[f"{side}.block.*.{a}.v.weight"] = ((n, inner, d), 0.0, d**-0.5)
            spec[f"{side}.block.*.{a}.o.weight"] = ((n, d, inner), 0.0, inner**-0.5)
            spec[f"{side}.block.*.{a}.layer_norm.weight"] = ((n, d), 1.0, 0.02)
        spec[f"{side}.block.*.DenseReluDense.wi.weight"] = ((n, ff, d), 0.0, d**-0.5)
        spec[f"{side}.block.*.DenseReluDense.wo.weight"] = ((n, d, ff), 0.0, ff**-0.5)
        spec[f"{side}.block.*.DenseReluDense.layer_norm.weight"] = ((n, d), 1.0, 0.02)
    return spec


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _bucket(rel, bidirectional: bool, num_buckets: int, max_distance: int):
    """Relative position (key - query) -> bucket: half the buckets exact, the
    rest log-spaced up to ``max_distance``."""
    ret = jnp.zeros_like(rel)
    if bidirectional:
        num_buckets //= 2
        ret = ret + (rel > 0).astype(jnp.int32) * num_buckets
        rel = jnp.abs(rel)
    else:
        rel = -jnp.minimum(rel, 0)
    max_exact = num_buckets // 2
    large = max_exact + (
        jnp.log(jnp.maximum(rel, 1).astype(jnp.float32) / max_exact)
        / math.log(max_distance / max_exact) * (num_buckets - max_exact)
    ).astype(jnp.int32)
    return ret + jnp.where(rel < max_exact, rel, jnp.minimum(large, num_buckets - 1))


def _position_bias(table, q_len, k_len, bidirectional, cfg):
    rel = jnp.arange(k_len)[None, :] - jnp.arange(q_len)[:, None]
    buckets = _bucket(rel, bidirectional, cfg["relative_attention_num_buckets"],
                      cfg.get("relative_attention_max_distance", 128))
    return table[buckets].transpose(2, 0, 1)[None]  # (1, H, q, k)


def _attention(dot, p, pre, x, kv, bias, heads):
    b, sq, _ = x.shape
    split = lambda t: t.reshape(t.shape[0], t.shape[1], heads, -1).transpose(0, 2, 1, 3)  # noqa: E731
    q = split(dot("bsi,oi->bso", x, p[f"{pre}.q.weight"]))
    k = split(dot("bsi,oi->bso", kv, p[f"{pre}.k.weight"]))
    v = split(dot("bsi,oi->bso", kv, p[f"{pre}.v.weight"]))
    probs = jax.nn.softmax(dot("bhqd,bhkd->bhqk", q, k) + bias, axis=-1)
    out = dot("bhqk,bhkd->bhqd", probs, v).transpose(0, 2, 1, 3).reshape(b, sq, -1)
    return dot("bsi,oi->bso", out, p[f"{pre}.o.weight"])


def _ffn(dot, p, x):
    h = jax.nn.relu(dot("bsi,oi->bso", x, p["L.DenseReluDense.wi.weight"]))
    return dot("bsi,oi->bso", h, p["L.DenseReluDense.wo.weight"])


def _stack(params: dict, prefix: str) -> dict:
    return {k.replace(prefix + ".*", "L"): v for k, v in params.items() if k.startswith(prefix + ".*")}


def forward(params: dict, cfg: dict, input_ids, attention_mask, decoder_input_ids, dot):
    """Teacher-forced logits (B, T, vocab), float32."""
    heads, eps = cfg["num_heads"], cfg.get("layer_norm_epsilon", 1e-6)
    emb = params["shared.weight"]
    s, t = input_ids.shape[1], decoder_input_ids.shape[1]
    pad_bias = jnp.where(attention_mask[:, None, None, :] > 0, 0.0, NEG)
    enc_bias = _position_bias(params["encoder.relative_attention_bias.weight"], s, s, True, cfg) + pad_bias

    @jax.checkpoint
    def enc_layer(x, lp):
        x = x + _self(dot, lp, x, enc_bias, heads, eps)
        x = x + _ffn(dot, lp, _rms(x, lp["L.DenseReluDense.layer_norm.weight"], eps))
        return x, None

    enc, _ = jax.lax.scan(enc_layer, emb[input_ids], _stack(params, "encoder.block"))
    enc = _rms(enc, params["encoder.final_layer_norm.weight"], eps)

    causal = jnp.where(jnp.arange(t)[None, :] <= jnp.arange(t)[:, None], 0.0, NEG)[None, None]
    dec_bias = _position_bias(params["decoder.relative_attention_bias.weight"], t, t, False, cfg) + causal

    @jax.checkpoint
    def dec_layer(x, lp):
        x = x + _self(dot, lp, x, dec_bias, heads, eps)
        h = _rms(x, lp["L.EncDecAttention.layer_norm.weight"], eps)
        x = x + _attention(dot, lp, "L.EncDecAttention", h, enc, pad_bias, heads)
        x = x + _ffn(dot, lp, _rms(x, lp["L.DenseReluDense.layer_norm.weight"], eps))
        return x, None

    dec, _ = jax.lax.scan(dec_layer, emb[decoder_input_ids], _stack(params, "decoder.block"))
    dec = _rms(dec, params["decoder.final_layer_norm.weight"], eps) * cfg["d_model"] ** -0.5
    return dot("btd,vd->btv", dec, emb)


def _self(dot, lp, x, bias, heads, eps):
    h = _rms(x, lp["L.SelfAttention.layer_norm.weight"], eps)
    return _attention(dot, lp, "L.SelfAttention", h, h, bias, heads)


def decoder_start(cfg: dict) -> tuple[int, int]:
    """(decoder start token, the id a masked label becomes when shifted)."""
    return cfg.get("decoder_start_token_id", 0), cfg.get("pad_token_id", 0)


def forced_tokens(cfg: dict, max_new_tokens: int) -> dict[int, int]:
    """T5 forces no output token."""
    return {}
