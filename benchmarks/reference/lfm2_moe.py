"""Plain LFM2-MoE (LiquidAI ``lfm2_moe``: LFM2-8B-A1B), float32.

Written from the published config (``config.json``: ``layer_types``,
``conv_L_cache``, ``num_dense_layers``, ``num_experts``, ``norm_topk_prob``,
``use_expert_bias``, ``routed_scaling_factor``) and the family's equations,
with no kernel, no cache and nothing of the program.  One request at a time
(``lax.map`` over the batch), the whole sequence at once:

* embedding ``E`` (vocab, d); the head is ``E`` again (tied);
* layer ``i``: ``h = x + op_i(rms(x))``, ``y = h + ffn_i(rms(h))``, with
  ``rms(x) = x / sqrt(mean(x^2) + norm_eps) * g``; a final ``rms`` before
  the head;
* ``op_i`` for ``layer_types[i] == "conv"``: ``[B, C, X] = split3(x W_in)``
  (d -> 3d, no bias), ``z = B * X``, the depthwise causal convolution as an
  explicit sum over its ``L = conv_L_cache`` taps,
  ``c_t = sum_{k<L} w[:, k] * z_{t-(L-1)+k}`` (``z`` of a negative index is
  0), ``op = (C * c) W_out``;
* ``op_i`` for ``"full_attention"``: ``q = x W_q`` as 32 heads of 64,
  ``k, v`` as 8 heads of 64 (query head ``h`` reads KV head ``h // 4``);
  ``rms`` over the 64 of every q and k head (their own gains), then RoPE
  (theta ``rope_theta``, half-rotation layout, unscaled); scores
  ``q.k / sqrt(64)``, causal mask, a full softmax; ``op = concat(heads) W_o``;
* ``ffn_i`` for ``i < num_dense_layers``: SwiGLU ``(silu(x W_g) * x W_u) W_d``
  at ``intermediate_size``;
* ``ffn_i`` otherwise: ``s = sigmoid(x W_r)``; the experts of a token are the
  top ``num_experts_per_tok`` of ``s + expert_bias``; their weights are ``s``
  there, divided by their sum + 1e-6 (``norm_topk_prob``), times
  ``routed_scaling_factor``; each expert a SwiGLU at
  ``moe_intermediate_size``; computed as a loop over the experts, each over
  every token under a mask.  No shared expert, no dropped token.

Tensors are named per layer and held as the program holds them (a projection
is (in, out), the experts of a layer one (experts, in, out) tensor), so that
the one jitted call that makes the program's tree makes one copy of each.

Departures from the published model, for the reader of a mismatch:
* ``tie_word_embeddings`` is not in the config row: the family ties, and so
  does this file (the configuration lists it under ``assumed``);
* ``expert_bias`` is a buffer the published model's load balancer moves and
  no gradient reaches; here it is a seeded tensor like the others;
* the weights are seeded, not trained: see ``param_spec`` for what is drawn
  how, and the configuration's ``assumed.weights`` for why.

Every call of ``forward`` prints one line: the share of the returned
positions at which, in some expert layer, the 4th and 5th selection scores lie
within ``NEAR_TIE`` of each other (where a bfloat16 program may route
otherwise than float32 does).
"""

from __future__ import annotations

import json
import sys

import jax
import jax.numpy as jnp

NEG = -1e9
NEAR_TIE = 0.02  # in units of the selection score (a sigmoid plus its bias)


def param_spec(cfg: dict) -> dict:
    """name -> (shape, mean, std).  Matrices, the embedding and the router
    N(0, ``init_std``); norm gains N(1, ``init_std``).  Three kinds are drawn
    otherwise, each an ``assumed`` of the configuration: the convolution's
    taps N(0, ``conv_init_std``), the q/k head norms' gains around
    ``qk_norm_init_mean``, ``expert_bias`` N(0, ``expert_bias_init_std``)."""
    d, v, std = cfg["hidden_size"], cfg["vocab_size"], cfg.get("init_std", 0.02)
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // heads
    e, ff, mff = cfg["num_experts"], cfg["intermediate_size"], cfg["moe_intermediate_size"]
    spec = {"embed_tokens.weight": ((v, d), 0.0, std), "final_norm.weight": ((d,), 1.0, std)}
    for i, kind in enumerate(cfg["layer_types"]):
        pre = f"layers.{i}"
        spec[f"{pre}.operator_norm.weight"] = ((d,), 1.0, std)
        spec[f"{pre}.ffn_norm.weight"] = ((d,), 1.0, std)
        if kind == "conv":
            spec[f"{pre}.conv.in_proj.weight"] = ((d, 3 * d), 0.0, std)
            spec[f"{pre}.conv.conv.weight"] = ((d, cfg["conv_L_cache"]), 0.0, cfg.get("conv_init_std", std))
            spec[f"{pre}.conv.out_proj.weight"] = ((d, d), 0.0, std)
        else:
            spec[f"{pre}.self_attn.q_proj.weight"] = ((d, heads * hd), 0.0, std)
            spec[f"{pre}.self_attn.k_proj.weight"] = ((d, kv * hd), 0.0, std)
            spec[f"{pre}.self_attn.v_proj.weight"] = ((d, kv * hd), 0.0, std)
            spec[f"{pre}.self_attn.out_proj.weight"] = ((heads * hd, d), 0.0, std)
            for n in ("q_layernorm", "k_layernorm"):
                spec[f"{pre}.self_attn.{n}.weight"] = ((hd,), cfg.get("qk_norm_init_mean", 1.0), std)
        if i < cfg["num_dense_layers"]:
            spec[f"{pre}.feed_forward.w1.weight"] = ((d, ff), 0.0, std)  # gate
            spec[f"{pre}.feed_forward.w3.weight"] = ((d, ff), 0.0, std)  # up
            spec[f"{pre}.feed_forward.w2.weight"] = ((ff, d), 0.0, std)  # down
        else:
            spec[f"{pre}.feed_forward.gate.weight"] = ((d, e), 0.0, std)
            spec[f"{pre}.feed_forward.expert_bias"] = ((e,), 0.0, cfg.get("expert_bias_init_std", 0.0))
            spec[f"{pre}.feed_forward.experts.w1.weight"] = ((e, d, mff), 0.0, std)
            spec[f"{pre}.feed_forward.experts.w3.weight"] = ((e, d, mff), 0.0, std)
            spec[f"{pre}.feed_forward.experts.w2.weight"] = ((e, mff, d), 0.0, std)
    return spec


def rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def short_conv(z, w):
    """Depthwise causal convolution of ``z`` (T, d) with taps ``w`` (d, L):
    ``c_t = sum_k w[:, k] * z_{t-(L-1)+k}``, an explicit sum over the taps."""
    taps = w.shape[1]
    t = z.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, z.shape[1]), z.dtype), z], axis=0)
    out = jnp.zeros_like(z)
    for k in range(taps):
        out = out + w[:, k][None, :] * padded[k : k + t]
    return out


def conv_operator(dot, p, pre, x):
    d = x.shape[-1]
    bcx = dot("ti,io->to", x, p[f"{pre}.conv.in_proj.weight"])
    gate_b, gate_c, xs = bcx[:, :d], bcx[:, d : 2 * d], bcx[:, 2 * d :]
    c = short_conv(gate_b * xs, p[f"{pre}.conv.conv.weight"])
    return dot("ti,io->to", gate_c * c, p[f"{pre}.conv.out_proj.weight"])


def rope(x, theta):
    """x (heads, T, hd); position t rotates pair (j, j + hd/2) by t * theta^(-2j/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.concatenate([jnp.cos(ang)] * 2, -1), jnp.concatenate([jnp.sin(ang)] * 2, -1)
    rot = jnp.concatenate([-x[..., hd // 2 :], x[..., : hd // 2]], -1)
    return x * cos + rot * sin


def attention_operator(dot, p, pre, x, cfg):
    t, d = x.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = d // heads, cfg["norm_eps"]
    split = lambda y, n: y.reshape(t, n, hd).transpose(1, 0, 2)  # noqa: E731 — (n, T, hd)
    q = split(dot("ti,io->to", x, p[f"{pre}.self_attn.q_proj.weight"]), heads)
    k = split(dot("ti,io->to", x, p[f"{pre}.self_attn.k_proj.weight"]), kv)
    v = split(dot("ti,io->to", x, p[f"{pre}.self_attn.v_proj.weight"]), kv)
    q = rope(rms(q, p[f"{pre}.self_attn.q_layernorm.weight"], eps), cfg["rope_theta"])
    k = rope(rms(k, p[f"{pre}.self_attn.k_layernorm.weight"], eps), cfg["rope_theta"])
    k, v = jnp.repeat(k, heads // kv, axis=0), jnp.repeat(v, heads // kv, axis=0)
    scores = dot("hqd,hkd->hqk", q, k) / jnp.sqrt(float(hd))
    scores = scores + jnp.where(jnp.arange(t)[None, :] <= jnp.arange(t)[:, None], 0.0, NEG)[None]
    out = dot("hqk,hkd->hqd", jax.nn.softmax(scores, axis=-1), v)
    return dot("ti,io->to", out.transpose(1, 0, 2).reshape(t, d), p[f"{pre}.self_attn.out_proj.weight"])


def route(dot, p, pre, x, cfg):
    """(weights (T, E), zero off the chosen experts; margin (T,) between the
    last chosen and the first refused selection score)."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(dot("ti,ie->te", x, p[f"{pre}.feed_forward.gate.weight"]))
    select = s + p[f"{pre}.feed_forward.expert_bias"] if cfg.get("use_expert_bias") else s
    ranked = jnp.sort(select, axis=-1)[:, ::-1]
    chosen = select >= ranked[:, k - 1 : k]
    w = jnp.where(chosen, s, 0.0)
    if cfg.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    return w * cfg.get("routed_scaling_factor", 1.0), ranked[:, k - 1] - ranked[:, k]


def swiglu(dot, x, w_gate, w_up, w_down):
    return dot("tf,fo->to", jax.nn.silu(dot("ti,if->tf", x, w_gate)) * dot("ti,if->tf", x, w_up), w_down)


def expert_layer(dot, p, pre, x, cfg):
    w, margin = route(dot, p, pre, x, cfg)

    def one(acc, ew):
        w_e, w1, w3, w2 = ew  # this expert's weight per token (0 where not chosen) and its SwiGLU
        return acc + w_e[:, None] * swiglu(dot, x, w1, w3, w2), None

    ff = f"{pre}.feed_forward.experts"
    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (w.T, p[f"{ff}.w1.weight"], p[f"{ff}.w3.weight"], p[f"{ff}.w2.weight"]))
    return out, margin


def sequence_logits(params: dict, cfg: dict, tokens, first: int, dot):
    """Logits (T - first, vocab) of one sequence ``tokens`` (T,) from position
    ``first`` on, and the smallest routing margin of each of those positions."""
    eps = cfg["norm_eps"]
    x = params["embed_tokens.weight"][tokens]
    margins = []
    for i, kind in enumerate(cfg["layer_types"]):
        pre = f"layers.{i}"
        h = rms(x, params[f"{pre}.operator_norm.weight"], eps)
        op = conv_operator(dot, params, pre, h) if kind == "conv" else attention_operator(dot, params, pre, h, cfg)
        x = x + op
        h = rms(x, params[f"{pre}.ffn_norm.weight"], eps)
        if i < cfg["num_dense_layers"]:
            ff = f"{pre}.feed_forward"
            x = x + swiglu(dot, h, params[f"{ff}.w1.weight"], params[f"{ff}.w3.weight"], params[f"{ff}.w2.weight"])
        else:
            out, margin = expert_layer(dot, params, pre, h, cfg)
            x = x + out
            margins.append(margin[first:])
    x = rms(x[first:], params["final_norm.weight"], eps)
    least = jnp.min(jnp.stack(margins), axis=0) if margins else jnp.full((x.shape[0],), jnp.inf)
    return dot("td,vd->tv", x, params["embed_tokens.weight"]), least


def _print_near_ties(near, total):
    sys.stdout.write(json.dumps({
        "reference": "lfm2_moe", "positions": int(total), "near_tie_positions": int(near),
        "near_tie_share": float(near) / max(int(total), 1), "near_tie_margin": NEAR_TIE}) + "\n")
    sys.stdout.flush()


def forward(params: dict, cfg: dict, input_ids, attention_mask, decoder_input_ids, dot):
    """Teacher-forced logits (B, T, vocab), float32, for the serve driver: the
    model runs over ``concat(input_ids, decoder_input_ids[:, 1:])`` and the
    logits of positions P-1 .. P-1+T-1 come back: position P-1, the prompt's
    last, gives the first served token, so ``decoder_input_ids[:, 0]`` (the
    seq2seq layout's start token) is not read.  Every prompt fills its row
    (``attention_mask`` all ones): a causal model without padding needs no mask."""
    p = input_ids.shape[1]
    tokens = jnp.concatenate([input_ids, decoder_input_ids[:, 1:]], axis=1)
    logits, least = jax.lax.map(lambda row: sequence_logits(params, cfg, row, p - 1, dot), tokens)
    jax.debug.callback(_print_near_ties, jnp.sum(least < NEAR_TIE), least.size)
    return logits


def decoder_start(cfg: dict) -> tuple[int, int]:
    """(the id that fills ``decoder_input_ids[:, 0]``, the pad id)."""
    return cfg["bos_token_id"], cfg["pad_token_id"]


def forced_tokens(cfg: dict, max_new_tokens: int) -> dict[int, int]:
    """No output position is forced: every served token is compared."""
    return {}
