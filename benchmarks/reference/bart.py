"""Plain BART (Lewis et al. 2019; ``BartForConditionalGeneration``), float32.

Written from the published equations, with no kernel, no cache and nothing
of the program: post-layer-norm residual blocks, learned positions with the
offset of 2, biased projections, queries scaled by head_dim**-0.5, exact
(erf) GELU, the output head tied to the token embedding plus
``final_logits_bias``.  Per-layer tensors are stacked on a leading axis and
the stacks are walked with ``lax.scan`` (the same arithmetic as a loop over
layers; it keeps the compile short and, with ``jax.checkpoint`` on the layer,
the backward pass's memory at one layer's activations).

Departures of the program's model (``models/bart.py``) from the published
one, for the reader of a mismatch:
* it trains ``final_logits_bias`` (a buffer in the published model); the
  training reference follows it there, so that three steps stay comparable;
* it applies hidden dropout to the MLP activation, where bart-large-cnn
  publishes ``activation_dropout`` 0.0 — no matter here, the cells run with
  dropout as the identity (see the configuration file).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

POSITION_OFFSET = 2
NEG = -1e9


def _layer_spec(prefix: str, n: int, d: int, ffn: int, std: float, cross: bool) -> dict:
    spec = {}
    attns = ["self_attn"] + (["encoder_attn"] if cross else [])
    for a in attns:
        for proj in ("q_proj", "k_proj", "v_proj", "out_proj"):
            spec[f"{prefix}.*.{a}.{proj}.weight"] = ((n, d, d), 0.0, std)
            spec[f"{prefix}.*.{a}.{proj}.bias"] = ((n, d), 0.0, std)
        spec[f"{prefix}.*.{a}_layer_norm.weight"] = ((n, d), 1.0, std)
        spec[f"{prefix}.*.{a}_layer_norm.bias"] = ((n, d), 0.0, std)
    spec[f"{prefix}.*.fc1.weight"] = ((n, ffn, d), 0.0, std)
    spec[f"{prefix}.*.fc1.bias"] = ((n, ffn), 0.0, std)
    spec[f"{prefix}.*.fc2.weight"] = ((n, d, ffn), 0.0, std)
    spec[f"{prefix}.*.fc2.bias"] = ((n, d), 0.0, std)
    spec[f"{prefix}.*.final_layer_norm.weight"] = ((n, d), 1.0, std)
    spec[f"{prefix}.*.final_layer_norm.bias"] = ((n, d), 0.0, std)
    return spec


def param_spec(cfg: dict) -> dict:
    """name -> (shape, mean, std), published naming, layers stacked (``*``).
    Weights N(0, init_std); biases and norm offsets too (zeros would hide a
    dropped bias), norm scales around 1.  A configuration may draw either
    side's position embeddings wider (``<side>_position_init_std``) and says
    why under ``assumed``."""
    d, v, std = cfg["d_model"], cfg["vocab_size"], cfg.get("init_std", 0.02)
    pos = cfg["max_position_embeddings"] + POSITION_OFFSET
    spec = {
        "shared.weight": ((v, d), 0.0, std),
        "final_logits_bias": ((v,), 0.0, 0.0),
    }
    for side, n, ffn in (
        ("encoder", cfg["encoder_layers"], cfg["encoder_ffn_dim"]),
        ("decoder", cfg["decoder_layers"], cfg["decoder_ffn_dim"]),
    ):
        spec[f"{side}.embed_positions.weight"] = ((pos, d), 0.0, cfg.get(f"{side}_position_init_std", std))
        spec[f"{side}.layernorm_embedding.weight"] = ((d,), 1.0, std)
        spec[f"{side}.layernorm_embedding.bias"] = ((d,), 0.0, std)
        spec.update(_layer_spec(f"{side}.layers", n, d, ffn, std, cross=side == "decoder"))
    return spec


def _ln(x, w, b, eps=1e-5):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def _linear(dot, x, w, b):
    return dot("bsi,oi->bso", x, w) + b


def _attention(dot, p, pre, x, kv, bias, heads):
    """Multi-head attention of queries from ``x`` over keys/values from ``kv``;
    ``bias`` (B or 1, 1, Sq or 1, Sk) is added to the scores."""
    b, sq, d = x.shape
    hd = d // heads
    split = lambda t: t.reshape(t.shape[0], t.shape[1], heads, hd).transpose(0, 2, 1, 3)  # noqa: E731
    q = split(_linear(dot, x, p[f"{pre}.q_proj.weight"], p[f"{pre}.q_proj.bias"]) * hd**-0.5)
    k = split(_linear(dot, kv, p[f"{pre}.k_proj.weight"], p[f"{pre}.k_proj.bias"]))
    v = split(_linear(dot, kv, p[f"{pre}.v_proj.weight"], p[f"{pre}.v_proj.bias"]))
    scores = dot("bhqd,bhkd->bhqk", q, k) + bias
    probs = jax.nn.softmax(scores, axis=-1)
    out = dot("bhqk,bhkd->bhqd", probs, v).transpose(0, 2, 1, 3).reshape(b, sq, d)
    return _linear(dot, out, p[f"{pre}.out_proj.weight"], p[f"{pre}.out_proj.bias"])


def _ffn(dot, p, pre, x):
    h = jax.nn.gelu(_linear(dot, x, p[f"{pre}.fc1.weight"], p[f"{pre}.fc1.bias"]), approximate=False)
    return _linear(dot, h, p[f"{pre}.fc2.weight"], p[f"{pre}.fc2.bias"])


def _stack(params: dict, prefix: str) -> dict:
    """The stacked tensors of one side, keyed by their per-layer name."""
    return {k.replace(prefix + ".*", "L"): v for k, v in params.items() if k.startswith(prefix + ".*")}


def forward(params: dict, cfg: dict, input_ids, attention_mask, decoder_input_ids, dot):
    """Teacher-forced logits (B, T, vocab), float32."""
    heads_e, heads_d = cfg["encoder_attention_heads"], cfg["decoder_attention_heads"]
    scale = cfg["d_model"] ** 0.5 if cfg.get("scale_embedding", False) else 1.0
    emb = params["shared.weight"]

    def embed(side, ids):
        pos = jnp.arange(ids.shape[1]) + POSITION_OFFSET
        x = emb[ids] * scale + params[f"{side}.embed_positions.weight"][pos][None]
        return _ln(x, params[f"{side}.layernorm_embedding.weight"], params[f"{side}.layernorm_embedding.bias"])

    pad_bias = jnp.where(attention_mask[:, None, None, :] > 0, 0.0, NEG)

    @jax.checkpoint
    def enc_layer(x, lp):
        x = _ln(x + _attention(dot, lp, "L.self_attn", x, x, pad_bias, heads_e),
                lp["L.self_attn_layer_norm.weight"], lp["L.self_attn_layer_norm.bias"])
        x = _ln(x + _ffn(dot, lp, "L", x), lp["L.final_layer_norm.weight"], lp["L.final_layer_norm.bias"])
        return x, None

    enc, _ = jax.lax.scan(enc_layer, embed("encoder", input_ids), _stack(params, "encoder.layers"))

    t = decoder_input_ids.shape[1]
    causal = jnp.where(jnp.arange(t)[None, :] <= jnp.arange(t)[:, None], 0.0, NEG)[None, None]

    @jax.checkpoint
    def dec_layer(x, lp):
        x = _ln(x + _attention(dot, lp, "L.self_attn", x, x, causal, heads_d),
                lp["L.self_attn_layer_norm.weight"], lp["L.self_attn_layer_norm.bias"])
        x = _ln(x + _attention(dot, lp, "L.encoder_attn", x, enc, pad_bias, heads_d),
                lp["L.encoder_attn_layer_norm.weight"], lp["L.encoder_attn_layer_norm.bias"])
        x = _ln(x + _ffn(dot, lp, "L", x), lp["L.final_layer_norm.weight"], lp["L.final_layer_norm.bias"])
        return x, None

    dec, _ = jax.lax.scan(dec_layer, embed("decoder", decoder_input_ids), _stack(params, "decoder.layers"))
    return dot("btd,vd->btv", dec, emb) + params["final_logits_bias"]


def decoder_start(cfg: dict) -> tuple[int, int]:
    """(decoder start token, the id a masked label becomes when shifted)."""
    return cfg["decoder_start_token_id"], cfg["pad_token_id"]


def forced_tokens(cfg: dict, max_new_tokens: int) -> dict[int, int]:
    """Output positions whose token generation forces whatever the logits say
    (``forced_bos_token_id`` first, ``forced_eos_token_id`` at the length cap)."""
    forced = {}
    if cfg.get("forced_bos_token_id") is not None:
        forced[0] = cfg["forced_bos_token_id"]
    if cfg.get("forced_eos_token_id") is not None:
        forced[max_new_tokens - 1] = cfg["forced_eos_token_id"]
    return forced
