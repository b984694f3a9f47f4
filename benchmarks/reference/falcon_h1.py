"""Plain Falcon-H1 (tiiuae ``falcon_h1``: Falcon-H1-34B-Instruct), float32, the
mixer as the TOKEN-BY-TOKEN recurrence and attention as a full masked softmax.

Written from the published ``config.json`` and the layer's equations, with no
kernel, no chunked form, no cache and nothing of the program.  One request at a
time (``lax.map`` over the batch), every matrix product through ``dot``
(``harness/precision.py``: float32 at ``highest``, or the int8 control):

* ``h_0 = embedding_multiplier x E[ids]``; blocks; a final ``rms``; ``logits =
  lm_head_multiplier x h W_head``; untied; no biases but the convolution's;
  ``rms(x) = x / sqrt(mean(x^2) + rms_norm_eps) * g``;
* block: ``u = rms(x)``; ``x' = x + attention_out_multiplier x
  Attn(attention_in_multiplier x u) + ssm_out_multiplier x Mix(ssm_in_multiplier
  x u)``; ``y = x' + MLP(rms(x'))``: both mixers read the same normed input;
* ``MLP(s) = mlp_multipliers[1] x (silu(mlp_multipliers[0] x s W_gate) * s
  W_up) W_down``;
* ``Attn(s)``: ``q = s W_q`` as 20 heads of 128, ``k = key_multiplier x s W_k``
  and ``v = s W_v`` as 4 heads of 128 (query head ``h`` reads KV head ``h //
  5``); RoPE (theta ``rope_theta``, half-rotation layout, unscaled) on q and k;
  softmax over ``j <= i`` of ``q_i . k_j / sqrt(128)``; ``W_o``;
* ``Mix(s)``: ``p = (s W_in) * m``, ``m`` holding ``ssm_multipliers[0..4]`` over
  the sections ``[z: I | x: I | B: G N | C: G N | dt: heads]``; ``xBC_t <-
  silu(sum_k w[:, k] xBC_{t - (K-1) + k} + bias)`` (depthwise, causal, ``K`` =
  ``mamba_d_conv`` taps, zeros before the sequence); ``dt_t = softplus(dt_t +
  dt_bias)``, ``A = -exp(A_log)`` a head; a head's state ``S`` (``P x N``) goes
  token by token: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t
  C_t + D x_t`` (head ``h`` reads group ``h // (heads / G)``); then ``y <- y *
  silu(z)``, ``rms`` over each of the ``G`` groups of ``I / G`` channels with a
  gain of ``I``; ``W_out``.

Tensors are named per layer and held as the program holds them (a projection
is (in, out)), so that the one jitted call that makes the program's tree makes
one copy of each.

Departures from the published model, for the reader of a mismatch (each is an
``assumed`` of the configuration file, with its reason): the order of
``W_in``'s sections and of ``ssm_multipliers`` over them, and the multipliers
on ``W_in``'s output; ``dt_bias``, ``A_log``, ``D`` a head, no clamp on ``dt``;
the norm's grouping; the weights are seeded, not trained: see ``param_spec``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def param_spec(cfg: dict) -> dict:
    """name -> (shape, mean, std).  Every matrix N(0, its own ``init_std``
    entry): the configuration file says each scale and what it gives after the
    family's multipliers.  Norm gains N(1, ``norm``); ``dt_bias``, ``A_log``,
    ``D`` N(their ``*_mean``, their std)."""
    d, v, ff, std = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"], cfg["init_std"]
    heads, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    inner, mh, taps = cfg["mamba_d_ssm"], cfg["mamba_n_heads"], cfg["mamba_d_conv"]
    conv_dim = inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    spec = {"embed_tokens.weight": ((v, d), 0.0, std["embed"]), "final_layernorm.weight": ((d,), 1.0, std["norm"]),
            "lm_head.weight": ((d, v), 0.0, std["lm_head"])}
    for i in range(cfg["num_hidden_layers"]):
        pre = f"layers.{i}"
        spec[f"{pre}.input_layernorm.weight"] = ((d,), 1.0, std["norm"])
        spec[f"{pre}.pre_ff_layernorm.weight"] = ((d,), 1.0, std["norm"])
        spec[f"{pre}.self_attn.q_proj.weight"] = ((d, heads * hd), 0.0, std["q_proj"])
        spec[f"{pre}.self_attn.k_proj.weight"] = ((d, kv * hd), 0.0, std["k_proj"])
        spec[f"{pre}.self_attn.v_proj.weight"] = ((d, kv * hd), 0.0, std["v_proj"])
        spec[f"{pre}.self_attn.o_proj.weight"] = ((heads * hd, d), 0.0, std["o_proj"])
        spec[f"{pre}.mamba.in_proj.weight"] = ((d, inner + conv_dim + mh), 0.0, std["in_proj"])
        spec[f"{pre}.mamba.conv1d.weight"] = ((conv_dim, taps), 0.0, std["conv"])
        spec[f"{pre}.mamba.conv1d.bias"] = ((conv_dim,), 0.0, std["conv_bias"])
        spec[f"{pre}.mamba.dt_bias"] = ((mh,), cfg["dt_bias_init_mean"], std["dt_bias"])
        spec[f"{pre}.mamba.A_log"] = ((mh,), cfg["a_log_init_mean"], std["a_log"])
        spec[f"{pre}.mamba.D"] = ((mh,), 1.0, std["d_skip"])
        spec[f"{pre}.mamba.norm.weight"] = ((inner,), 1.0, std["norm"])
        spec[f"{pre}.mamba.out_proj.weight"] = ((inner, d), 0.0, std["out_proj"])
        spec[f"{pre}.feed_forward.gate_proj.weight"] = ((d, ff), 0.0, std["gate_proj"])
        spec[f"{pre}.feed_forward.up_proj.weight"] = ((d, ff), 0.0, std["up_proj"])
        spec[f"{pre}.feed_forward.down_proj.weight"] = ((ff, d), 0.0, std["down_proj"])
    return spec


def rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def rope(x, theta):
    """x (heads, T, hd); position t rotates pair (j, j + hd/2) by t * theta^(-2j/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / (float(theta) ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.concatenate([jnp.cos(ang)] * 2, -1), jnp.concatenate([jnp.sin(ang)] * 2, -1)
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., : hd // 2]], -1)
    return x * cos + rot * sin


def attention(dot, p, pre, s, cfg):
    """``Attn``: s (T, d) -> (T, d), the (T, T) scores built whole."""
    t = s.shape[0]
    heads, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    split = lambda y, n: y.reshape(t, n, hd).transpose(1, 0, 2)  # noqa: E731 — (n, T, hd)
    q = rope(split(dot("ti,io->to", s, p[f"{pre}.self_attn.q_proj.weight"]), heads), cfg["rope_theta"])
    k = rope(split(cfg["key_multiplier"] * dot("ti,io->to", s, p[f"{pre}.self_attn.k_proj.weight"]), kv), cfg["rope_theta"])
    v = split(dot("ti,io->to", s, p[f"{pre}.self_attn.v_proj.weight"]), kv)
    k, v = jnp.repeat(k, heads // kv, axis=0), jnp.repeat(v, heads // kv, axis=0)
    scores = dot("hqd,hkd->hqk", q, k) / jnp.sqrt(float(hd))
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    y = dot("hqk,hkd->hqd", jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1), v)
    return dot("ti,io->to", y.transpose(1, 0, 2).reshape(t, heads * hd), p[f"{pre}.self_attn.o_proj.weight"])


def recurrence(dot, x, dt, a, b, c, d_skip):
    """The state-space recurrence, one token after another from an empty state:
    x (T, H, P), ``dt`` (T, H), ``a`` (H,) < 0, b and c (T, H, N) (a head's
    group's), ``d_skip`` (H,) -> y (T, H, P)."""

    def one(state, xs):  # state (H, P, N)
        x_t, dt_t, b_t, c_t = xs
        state = jnp.exp(dt_t * a)[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, dot("hpn,hn->hp", state, c_t) + d_skip[:, None] * x_t

    zero = jnp.zeros((x.shape[1], x.shape[2], b.shape[2]), jnp.float32)
    return jax.lax.scan(one, zero, (x, dt, b, c))[1]


def mixer(dot, p, pre, s, cfg):
    """``Mix``: s (T, d) -> (T, d)."""
    t = s.shape[0]
    inner, heads, hd = cfg["mamba_d_ssm"], cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n, taps = cfg["mamba_n_groups"], cfg["mamba_d_state"], cfg["mamba_d_conv"]
    sections = (inner, inner, g * n, g * n, heads)
    m = jnp.concatenate([jnp.full((w,), v, jnp.float32) for w, v in zip(sections, cfg["ssm_multipliers"])])
    proj = dot("ti,io->to", s, p[f"{pre}.mamba.in_proj.weight"]) * m
    z, xbc, dt = proj[:, :inner], proj[:, inner: 2 * inner + 2 * g * n], proj[:, 2 * inner + 2 * g * n:]
    w, bias = p[f"{pre}.mamba.conv1d.weight"], p[f"{pre}.mamba.conv1d.bias"]
    past = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1]), jnp.float32), xbc], axis=0)
    xbc = jax.nn.silu(sum(w[:, k] * past[k: k + t] for k in range(taps)) + bias)
    x = xbc[:, :inner].reshape(t, heads, hd)
    of_heads = lambda u: jnp.repeat(u.reshape(t, g, n), heads // g, axis=1)  # noqa: E731 — a head reads its group's
    b, c = of_heads(xbc[:, inner: inner + g * n]), of_heads(xbc[:, inner + g * n:])
    dt = jax.nn.softplus(dt + p[f"{pre}.mamba.dt_bias"])
    y = recurrence(dot, x, dt, -jnp.exp(p[f"{pre}.mamba.A_log"]), b, c, p[f"{pre}.mamba.D"])
    y = (y.reshape(t, inner) * jax.nn.silu(z)).reshape(t, g, inner // g)
    y = y / jnp.sqrt(jnp.mean(jnp.square(y), -1, keepdims=True) + cfg["rms_norm_eps"])
    return dot("ti,io->to", y.reshape(t, inner) * p[f"{pre}.mamba.norm.weight"], p[f"{pre}.mamba.out_proj.weight"])


def mlp(dot, p, pre, s, cfg):
    gate_m, down_m = cfg["mlp_multipliers"]
    gate = jax.nn.silu(gate_m * dot("ti,if->tf", s, p[f"{pre}.feed_forward.gate_proj.weight"]))
    return down_m * dot("tf,fo->to", gate * dot("ti,if->tf", s, p[f"{pre}.feed_forward.up_proj.weight"]),
                        p[f"{pre}.feed_forward.down_proj.weight"])


def sequence_logits(params: dict, cfg: dict, tokens, first: int, dot):
    """Logits (T - first, vocab) of one sequence ``tokens`` (T,) from position ``first`` on."""
    eps = cfg["rms_norm_eps"]
    x = cfg["embedding_multiplier"] * params["embed_tokens.weight"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        pre = f"layers.{i}"
        u = rms(x, params[f"{pre}.input_layernorm.weight"], eps)
        x = (x + cfg["attention_out_multiplier"] * attention(dot, params, pre, cfg["attention_in_multiplier"] * u, cfg)
             + cfg["ssm_out_multiplier"] * mixer(dot, params, pre, cfg["ssm_in_multiplier"] * u, cfg))
        x = x + mlp(dot, params, pre, rms(x, params[f"{pre}.pre_ff_layernorm.weight"], eps), cfg)
    h = rms(x[first:], params["final_layernorm.weight"], eps)
    return cfg["lm_head_multiplier"] * dot("td,dv->tv", h, params["lm_head.weight"])


def forward(params: dict, cfg: dict, input_ids, attention_mask, decoder_input_ids, dot):
    """Teacher-forced logits (B, T, vocab), float32, for the serve driver: the
    model runs over ``concat(input_ids, decoder_input_ids[:, 1:])`` and the
    logits of positions P-1 .. P-1+T-1 come back: position P-1, the prompt's
    last, gives the first served token, so ``decoder_input_ids[:, 0]`` (the
    seq2seq layout's start token) is not read.  Every prompt fills its row
    (``attention_mask`` all ones): a causal model without padding needs no mask.
    A request at a time, so that ten of 512 positions fit beside the open session."""
    p = input_ids.shape[1]
    tokens = jnp.concatenate([input_ids, decoder_input_ids[:, 1:]], axis=1)
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(lambda row: sequence_logits(params, cfg, row, p - 1, dot), tokens)


def decoder_start(cfg: dict) -> tuple[int, int]:
    """(the id that fills ``decoder_input_ids[:, 0]``, the pad id)."""
    return cfg["bos_token_id"], cfg["pad_token_id"]


def forced_tokens(cfg: dict, max_new_tokens: int) -> dict[int, int]:
    """No output position is forced: every served token is compared."""
    return {}
