"""Plain Brumby (manifestai ``brumby``: Brumby-14B-Base), float32, in the
ATTENTION FORM only.

Written from the published config (``config.json``: the Qwen3 lineage's keys)
and the power-retention layer's equations, with no kernel, no state, no
recurrence and nothing of the program.  One request at a time (``lax.map``
over the batch), the whole sequence at once, every product through ``dot``
(``harness/precision.py``: float32 at ``highest``, or the int8 control):

* embedding ``E`` (vocab, d); an untied head ``W_head`` (d, vocab); no biases
  but the gate's;
* layer ``i``: ``h = x + Ret(rms(x))``, ``y = h + SwiGLU(rms(h))``, with
  ``rms(x) = x / sqrt(mean(x^2) + rms_norm_eps) * g``; a final ``rms`` before
  the head; SwiGLU ``(silu(x W_g) * x W_u) W_d`` at ``intermediate_size``;
* ``Ret(x)``: ``q = x W_q`` as 40 heads of 128, ``k, v`` as 8 heads of 128
  (query head ``h`` reads KV head ``h // 5``); ``rms`` over the 128 of every q
  and k head (their own gains), then RoPE (theta ``rope_theta``, half-rotation
  layout, unscaled); a gate a KV head a token, ``log g_t = logsigmoid(x_t W_gate
  + b_gate)``; ``G_i = sum_{l<=i} log g_l``;
  ``a_ij = (q_i . k_j / sqrt(128))^2 * exp(G_i - G_j)`` for ``j <= i``, else 0;
  ``y_i = sum_j a_ij v_j / (sum_j a_ij + eps)``; ``Ret = concat_h(y) W_o``.
  The (T, T) weights are built whole: no softmax (the power is even, every
  weight is >= 0), no state matrix, no feature map.

Tensors are named per layer and held as the program holds them (a projection
is (in, out)), so that the one jitted call that makes the program's tree makes
one copy of each.

Departures from the published model, for the reader of a mismatch (each is an
``assumed`` of the configuration file, with its reason):
* the degree 2, the gate a KV head with ``logsigmoid`` and a bias, ``eps``, the
  ``1/sqrt(d)`` scale, q/k head norms and RoPE kept from the lineage: the
  published config has no key for any of them;
* the weights are seeded, not trained: see ``param_spec``; the gate's
  pre-activation is drawn so that g lies in about 0.98-0.999.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-6


def param_spec(cfg: dict) -> dict:
    """name -> (shape, mean, std).  Matrices and the embedding N(0,
    ``init_std``); norm gains N(1, ``init_std``), the q/k head norms' around
    ``qk_norm_init_mean``; the gate's matrix N(0, ``gate_init_std``) and its
    bias N(``gate_bias_init_mean``, ``gate_bias_init_std``)."""
    d, v, std = cfg["hidden_size"], cfg["vocab_size"], cfg.get("init_std", 0.02)
    heads, kv, hd, ff = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"], cfg["intermediate_size"]
    spec = {"embed_tokens.weight": ((v, d), 0.0, std), "norm.weight": ((d,), 1.0, std),
            "lm_head.weight": ((d, v), 0.0, std)}
    for i in range(cfg["num_hidden_layers"]):
        pre = f"layers.{i}"
        spec[f"{pre}.input_layernorm.weight"] = ((d,), 1.0, std)
        spec[f"{pre}.post_attention_layernorm.weight"] = ((d,), 1.0, std)
        spec[f"{pre}.self_attn.q_proj.weight"] = ((d, heads * hd), 0.0, std)
        spec[f"{pre}.self_attn.k_proj.weight"] = ((d, kv * hd), 0.0, std)
        spec[f"{pre}.self_attn.v_proj.weight"] = ((d, kv * hd), 0.0, std)
        spec[f"{pre}.self_attn.o_proj.weight"] = ((heads * hd, d), 0.0, std)
        for n in ("q_norm", "k_norm"):
            spec[f"{pre}.self_attn.{n}.weight"] = ((hd,), cfg.get("qk_norm_init_mean", 1.0), std)
        spec[f"{pre}.self_attn.g_proj.weight"] = ((d, kv), 0.0, cfg.get("gate_init_std", std))
        spec[f"{pre}.self_attn.g_proj.bias"] = ((kv,), cfg.get("gate_bias_init_mean", 0.0), cfg.get("gate_bias_init_std", 0.0))
        spec[f"{pre}.mlp.gate_proj.weight"] = ((d, ff), 0.0, std)
        spec[f"{pre}.mlp.up_proj.weight"] = ((d, ff), 0.0, std)
        spec[f"{pre}.mlp.down_proj.weight"] = ((ff, d), 0.0, std)
    return spec


def rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def rope(x, theta):
    """x (heads, T, hd); position t rotates pair (j, j + hd/2) by t * theta^(-2j/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.concatenate([jnp.cos(ang)] * 2, -1), jnp.concatenate([jnp.sin(ang)] * 2, -1)
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., : hd // 2]], -1)
    return x * cos + rot * sin


def retention(dot, p, pre, x, cfg):
    """``Ret`` in the attention form: x (T, d) -> (T, d)."""
    t = x.shape[0]
    heads, kv, hd, eps = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"], cfg["rms_norm_eps"]
    split = lambda y, n: y.reshape(t, n, hd).transpose(1, 0, 2)  # noqa: E731 — (n, T, hd)
    q = split(dot("ti,io->to", x, p[f"{pre}.self_attn.q_proj.weight"]), heads)
    k = split(dot("ti,io->to", x, p[f"{pre}.self_attn.k_proj.weight"]), kv)
    v = split(dot("ti,io->to", x, p[f"{pre}.self_attn.v_proj.weight"]), kv)
    q = rope(rms(q, p[f"{pre}.self_attn.q_norm.weight"], eps), cfg["rope_theta"])
    k = rope(rms(k, p[f"{pre}.self_attn.k_norm.weight"], eps), cfg["rope_theta"])
    gate = dot("ti,ig->tg", x, p[f"{pre}.self_attn.g_proj.weight"]) + p[f"{pre}.self_attn.g_proj.bias"]
    g_cum = jnp.cumsum(jax.nn.log_sigmoid(gate), axis=0).T  # (kv, T): G
    k, v, g_cum = (jnp.repeat(a, heads // kv, axis=0) for a in (k, v, g_cum))
    scores = dot("hqd,hkd->hqk", q, k) / jnp.sqrt(float(hd))
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    decay = jnp.exp(jnp.where(causal[None], g_cum[:, :, None] - g_cum[:, None, :], -jnp.inf))
    a = scores ** cfg.get("retention_degree", 2) * decay
    y = dot("hqk,hkd->hqd", a, v) / (jnp.sum(a, axis=-1, keepdims=True) + cfg.get("retention_eps", EPS))
    return dot("ti,io->to", y.transpose(1, 0, 2).reshape(t, heads * hd), p[f"{pre}.self_attn.o_proj.weight"])


def swiglu(dot, x, w_gate, w_up, w_down):
    return dot("tf,fo->to", jax.nn.silu(dot("ti,if->tf", x, w_gate)) * dot("ti,if->tf", x, w_up), w_down)


def sequence_logits(params: dict, cfg: dict, tokens, first: int, dot):
    """Logits (T - first, vocab) of one sequence ``tokens`` (T,) from position ``first`` on."""
    eps = cfg["rms_norm_eps"]
    x = params["embed_tokens.weight"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        pre = f"layers.{i}"
        x = x + retention(dot, params, pre, rms(x, params[f"{pre}.input_layernorm.weight"], eps), cfg)
        h = rms(x, params[f"{pre}.post_attention_layernorm.weight"], eps)
        x = x + swiglu(dot, h, params[f"{pre}.mlp.gate_proj.weight"], params[f"{pre}.mlp.up_proj.weight"],
                       params[f"{pre}.mlp.down_proj.weight"])
    return dot("td,dv->tv", rms(x[first:], params["norm.weight"], eps), params["lm_head.weight"])


def forward(params: dict, cfg: dict, input_ids, attention_mask, decoder_input_ids, dot):
    """Teacher-forced logits (B, T, vocab), float32, for the serve driver: the
    model runs over ``concat(input_ids, decoder_input_ids[:, 1:])`` and the
    logits of positions P-1 .. P-1+T-1 come back: position P-1, the prompt's
    last, gives the first served token, so ``decoder_input_ids[:, 0]`` (the
    seq2seq layout's start token) is not read.  Every prompt fills its row
    (``attention_mask`` all ones): a causal model without padding needs no mask.
    A request at a time, so that ten of 1,152 positions fit beside the open session."""
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
