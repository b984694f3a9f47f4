"""Plain Mellum (JetBrains ``mellum``: Mellum2-12B-A2.5B-Instruct), float32.

Written from the published config (``config.json``: ``layer_types``,
``sliding_window``, ``rope_parameters`` by kind of layer, ``num_experts``,
``num_experts_per_tok``, ``norm_topk_prob``, ``moe_intermediate_size``) and the
layers' equations, with no kernel, no cache and nothing of the program.  One
request at a time (``lax.map`` over the batch), the whole sequence at once:

* embedding ``E`` (vocab, d); an untied head ``W_head`` (d, vocab);
* layer ``l``: ``h = x + Attn_l(rms(x))``, ``y = h + MoE(rms(h))``, with
  ``rms(x) = x / sqrt(mean(x^2) + rms_norm_eps) * g``; a final ``rms`` before
  the head; no biases;
* ``Attn_l``: ``q = x W_q`` as 32 heads of 128, ``k, v`` as 4 heads of 128
  (query head ``h`` reads KV head ``h // 8``); ``rms`` over the 128 of every q
  and k head (their own gains); RoPE by ``rope_parameters[layer_types[l]]``;
  scores ``q . k / sqrt(128)`` plus the layer's mask out of the full ``(T,
  T)`` one, ``0`` where ``j <= i`` (``full_attention``) or ``0 <= i - j <
  sliding_window`` (``sliding_attention``: the window counts the query), else
  ``-1e9``; a full softmax; ``concat(heads) W_o``.  The queries go through in
  blocks of ``QUERY_BLOCK`` rows (each block against every key under its rows
  of the mask), so that 8,448 positions fit: the same sums, fewer at a time;
* RoPE, half-rotation layout (pair ``(n, n + 64)``): ``"default"`` rotates by
  ``t * theta^(-2n/128)``; ``"yarn"`` by YaRN's frequencies: with ``d = 128``,
  ``L0 = original_max_position_embeddings``, ``s = factor``, ``dim(b) = d
  ln(L0 / (2 pi b)) / (2 ln theta)``, ``lo = max(floor(dim(beta_fast)), 0)``,
  ``hi = min(ceil(dim(beta_slow)), d - 1)``, ``r_n = clip((n - lo) / (hi -
  lo), 0, 1)``: ``inv_freq'_n = (inv_freq_n / s) r_n + inv_freq_n (1 -
  r_n)``, and cos and sin both times ``attention_factor``;
* ``MoE``: ``p = softmax(x W_r)`` over the experts; the experts of a token are
  its ``num_experts_per_tok`` largest ``p``; their weights are ``p`` there,
  divided by their sum (``norm_topk_prob``); each expert a SwiGLU at
  ``moe_intermediate_size``; computed as a loop over the experts, each over
  every token under a mask.  No shared expert, no selection bias, no dropped
  token.

Tensors are named per layer and held as the program holds them (a projection
is (in, out), the experts of a layer one (experts, in, out) tensor), so that
the one jitted call that makes the program's tree makes one copy of each.

Departures and assumptions, for the reader of a mismatch (each with its reason
in the configuration's ``assumed``): ``model_type`` ``mellum`` has no public
modelling file here, so the q/k head norms, softmax-then-top-k and the
half-rotation layout are the Qwen3-MoE lineage's, whose keys the config
carries; YaRN's formulas are the usual ones (the config gives the numbers);
the card's "MTP head" has no key in the config and is not built; the weights
are seeded, not trained (``param_spec``).

Every call of ``forward`` prints one line: the share of the returned positions
at which, in some layer, the last chosen and the first refused expert's
probabilities lie within ``NEAR_TIE`` of each other, relative to the last
chosen (where a bfloat16 program may route otherwise than float32 does).
"""

from __future__ import annotations

import json
import math
import sys

import jax
import jax.numpy as jnp

NEG = -1e9
NEAR_TIE = 0.02  # of the last chosen expert's probability
QUERY_BLOCK = 256


def param_spec(cfg: dict) -> dict:
    """name -> (shape, mean, std).  Matrices, the embedding, the head and the
    router N(0, ``init_std``); norm gains N(1, ``init_std``); the q/k head
    norms' gains around ``qk_norm_init_mean`` (an ``assumed`` of the
    configuration: peaked attention)."""
    d, v, std = cfg["hidden_size"], cfg["vocab_size"], cfg.get("init_std", 0.02)
    heads, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    e, mff = cfg["num_experts"], cfg["moe_intermediate_size"]
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
        spec[f"{pre}.mlp.gate.weight"] = ((d, e), 0.0, std)
        spec[f"{pre}.mlp.experts.gate_proj.weight"] = ((e, d, mff), 0.0, std)
        spec[f"{pre}.mlp.experts.up_proj.weight"] = ((e, d, mff), 0.0, std)
        spec[f"{pre}.mlp.experts.down_proj.weight"] = ((e, mff, d), 0.0, std)
    return spec


def rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def inv_freq(hd: int, rope: dict):
    """(hd / 2,) rotation frequencies of one ``rope_parameters`` section."""
    base = rope["rope_theta"] ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    if rope["rope_type"] == "default":
        return base
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    turns = lambda b: hd * math.log(rope["original_max_position_embeddings"] / (2 * math.pi * b)) / (  # noqa: E731
        2 * math.log(rope["rope_theta"]))
    lo = max(math.floor(turns(rope["beta_fast"])), 0)
    hi = min(math.ceil(turns(rope["beta_slow"])), hd - 1)
    ramp = jnp.clip((jnp.arange(hd // 2, dtype=jnp.float32) - lo) / (hi - lo), 0.0, 1.0)
    return base / rope["factor"] * ramp + base * (1.0 - ramp)


def rope(x, section: dict):
    """x (heads, T, hd); position t rotates pair (n, n + hd/2) by t * inv_freq_n,
    times the section's attention factor (1 for the plain rotation)."""
    hd = x.shape[-1]
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq(hd, section)[None, :]
    scale = section.get("attention_factor", 1.0) if section["rope_type"] == "yarn" else 1.0
    cos, sin = jnp.concatenate([jnp.cos(ang)] * 2, -1) * scale, jnp.concatenate([jnp.sin(ang)] * 2, -1) * scale
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., : hd // 2]], -1)
    return x * cos + rot * sin


def layer_mask(kind: str, t: int, window: int):
    """The layer's full (T, T) additive mask."""
    delta = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    seen = delta >= 0 if kind == "full_attention" else (delta >= 0) & (delta < window)
    return jnp.where(seen, 0.0, NEG)


def attention_operator(dot, p, pre, x, cfg, kind):
    t = x.shape[0]
    heads, kv, hd, eps = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"], cfg["rms_norm_eps"]
    split = lambda y, n: y.reshape(t, n, hd).transpose(1, 0, 2)  # noqa: E731 — (n, T, hd)
    q = split(dot("ti,io->to", x, p[f"{pre}.self_attn.q_proj.weight"]), heads)
    k = split(dot("ti,io->to", x, p[f"{pre}.self_attn.k_proj.weight"]), kv)
    v = split(dot("ti,io->to", x, p[f"{pre}.self_attn.v_proj.weight"]), kv)
    section = cfg["rope_parameters"][kind]
    q = rope(rms(q, p[f"{pre}.self_attn.q_norm.weight"], eps), section)
    k = rope(rms(k, p[f"{pre}.self_attn.k_norm.weight"], eps), section)
    k, v = jnp.repeat(k, heads // kv, axis=0), jnp.repeat(v, heads // kv, axis=0)
    mask = layer_mask(kind, t, cfg["sliding_window"])

    def rows(q_rows, mask_rows):  # a block of queries against every key, under its rows of the mask
        scores = dot("hqd,hkd->hqk", q_rows, k) / jnp.sqrt(float(hd)) + mask_rows[None]
        return dot("hqk,hkd->hqd", jax.nn.softmax(scores, axis=-1), v)

    if t <= QUERY_BLOCK:
        out = rows(q, mask)
    else:
        pad = -t % QUERY_BLOCK  # rows added to fill the last block attend every key and are cut off
        qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0))).reshape(heads, -1, QUERY_BLOCK, hd).transpose(1, 0, 2, 3)
        mb = jnp.pad(mask, ((0, pad), (0, 0))).reshape(-1, QUERY_BLOCK, t)
        out = jax.lax.map(lambda qm: rows(*qm), (qb, mb))  # (blocks, heads, QUERY_BLOCK, hd)
        out = out.transpose(1, 0, 2, 3).reshape(heads, -1, hd)[:, :t]
    return dot("ti,io->to", out.transpose(1, 0, 2).reshape(t, heads * hd), p[f"{pre}.self_attn.o_proj.weight"])


def route(dot, p, pre, x, cfg):
    """(weights (T, E), zero off the chosen experts; margin (T,) between the
    last chosen and the first refused expert's probability, relative to the former)."""
    k = cfg["num_experts_per_tok"]
    prob = jax.nn.softmax(dot("ti,ie->te", x, p[f"{pre}.mlp.gate.weight"]), axis=-1)
    ranked = jnp.sort(prob, axis=-1)[:, ::-1]
    w = jnp.where(prob >= ranked[:, k - 1: k], prob, 0.0)
    if cfg.get("norm_topk_prob", True):
        w = w / jnp.sum(w, -1, keepdims=True)
    return w, (ranked[:, k - 1] - ranked[:, k]) / ranked[:, k - 1]


def swiglu(dot, x, w_gate, w_up, w_down):
    return dot("tf,fo->to", jax.nn.silu(dot("ti,if->tf", x, w_gate)) * dot("ti,if->tf", x, w_up), w_down)


def expert_layer(dot, p, pre, x, cfg):
    w, margin = route(dot, p, pre, x, cfg)

    def one(acc, ew):
        w_e, w1, w3, w2 = ew  # this expert's weight per token (0 where not chosen) and its SwiGLU
        return acc + w_e[:, None] * swiglu(dot, x, w1, w3, w2), None

    ff = f"{pre}.mlp.experts"
    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        w.T, p[f"{ff}.gate_proj.weight"], p[f"{ff}.up_proj.weight"], p[f"{ff}.down_proj.weight"]))
    return out, margin


def sequence_logits(params: dict, cfg: dict, tokens, first: int, dot):
    """Logits (T - first, vocab) of one sequence ``tokens`` (T,) from position
    ``first`` on, and the smallest routing margin of each of those positions."""
    eps = cfg["rms_norm_eps"]
    x = params["embed_tokens.weight"][tokens]
    margins = []
    for i, kind in enumerate(cfg["layer_types"]):
        pre = f"layers.{i}"
        x = x + attention_operator(dot, params, pre, rms(x, params[f"{pre}.input_layernorm.weight"], eps), cfg, kind)
        out, margin = expert_layer(dot, params, pre, rms(x, params[f"{pre}.post_attention_layernorm.weight"], eps), cfg)
        x = x + out
        margins.append(margin[first:])
    x = rms(x[first:], params["norm.weight"], eps)
    return dot("td,dv->tv", x, params["lm_head.weight"]), jnp.min(jnp.stack(margins), axis=0)


def _print_near_ties(near, total):
    sys.stdout.write(json.dumps({
        "reference": "mellum", "positions": int(total), "near_tie_positions": int(near),
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
