"""Mellum causal LM in flax.linen (JetBrains ``mellum``: Mellum2-12B-A2.5B-Instruct).

A sparse decoder of the Qwen3-MoE lineage in which three layers of four read
only a **sliding window** of the context and every fourth reads all of it,
each kind with its own rotation, and every feed-forward is 64 small
softmax-routed experts.  Written from the published ``config.json`` and the
layers' equations (the plain twin, with each assumption noted, is
``benchmarks/reference/mellum.py``):

* block: ``h = x + Attn_l(rms(x))``, ``y = h + MoE(rms(h))``; RMSNorm eps
  ``rms_norm_eps``; a final RMSNorm; an untied head; no biases
  (``attention_bias`` false).  Layer ``l`` is ``sliding_attention`` or
  ``full_attention`` by ``layer_types[l]``;
* ``Attn_l(x)``: ``q = W_q x`` (32 heads x 128), ``k = W_k x``, ``v = W_v x``
  (4 heads x 128); RMSNorm over head_dim of every q and k head (their own
  gains); RoPE by the layer's kind; query head ``h`` reads KV head ``h //
  8``; ``a_ij = softmax_j(q_i . k_j / sqrt(128) + m_ij)``, ``m_ij = 0`` where
  ``j <= i`` (full) or ``0 <= i - j < sliding_window`` (window: the window
  counts the query itself), else ``-inf``; ``W_o concat_h(sum_j a_ij v_j)``;
* RoPE, half-rotation layout.  Window layers: the plain rotation,
  ``inv_freq_n = rope_theta^(-2n / 128)``.  Full layers: YaRN
  (``ops/mha.py`` ``rope_inv_freq``: the published factor 16 over the original
  8,192 positions, ``beta_fast`` 32, ``beta_slow`` 1) and cos and sin times
  ``attention_factor`` (so the scores by its square);
* ``MoE(x)``: ``p = softmax(W_r x)`` over the 64 experts in float32, the 8
  largest, their ``p`` renormalised to sum 1 (``norm_topk_prob``), ``y =
  sum_e p_e W_down,e (silu(W_gate,e x) * W_up,e x)`` at width 896
  (``ops/moe.py``: the sorted no-drop path, on every path); no shared expert,
  no selection bias, no dropped token.

**Assumed** (none of it a key of the published config; each with its reason in
``benchmarks/configs/mellum2-12b-a2.5b.json``): the q/k head norms,
softmax-then-top-k and the half-rotation layout are the lineage's; the window
counts the query; YaRN's formulas (the config gives the numbers); the "MTP
head" the model's card mentions has no key in the config, and none is built.

What a decoder carries from step to step is, a ``full_attention`` layer, the
K/V of the whole context (``cached_key`` / ``cached_value``, ``(batch, length,
kv_heads x head_dim)``) and, a ``sliding_attention`` layer, the K/V of the
last ``sliding_window`` positions and no more (``window_key`` /
``window_value``, ``(batch, sliding_window, ...)`` at any context, written at
``position mod sliding_window``: ``ops/mha.py`` ``cache_window_kv``).  Both
lie in one ``cache`` collection, and everything that handles it goes by the
leaf.  Continuing a ring with several tokens at once (a prefix hit, a
speculative verify) is not implemented, and a block pool would have to free a
window layer's blocks as they slide out: the serving engine refuses those
modes by ``has_window_cache``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from distributed_llms_example_tpu.ops.attention import mask_to_bias
from distributed_llms_example_tpu.ops.fused_dropout import Dropout
from distributed_llms_example_tpu.ops.mha import MultiHeadAttention, YarnRope
from distributed_llms_example_tpu.ops.moe import MoEMLP
from distributed_llms_example_tpu.ops.norms import RMSNorm
from distributed_llms_example_tpu.parallel.activation import constrain_hidden, constrain_logits
from distributed_llms_example_tpu.utils.remat import remat_block

LAYER_TYPES = ("sliding_attention", "full_attention")
_PUBLISHED_LAYER_TYPES = tuple("full_attention" if i % 4 == 3 else "sliding_attention" for i in range(28))
# ``rope_parameters.full_attention`` as published
PUBLISHED_YARN = YarnRope(factor=16.0, original_max_position_embeddings=8192, beta_fast=32.0, beta_slow=1.0,
                          attention_factor=1.2772588722239782)


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    moe_intermediate_size: int = 896  # each expert's SwiGLU (every layer is sparse)
    num_hidden_layers: int = 28
    layer_types: tuple[str, ...] = _PUBLISHED_LAYER_TYPES
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 1024
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 500000.0  # both kinds of layer
    rope_yarn: Optional[YarnRope] = PUBLISHED_YARN  # the full layers'; the window layers rotate plainly
    max_position_embeddings: int = 131072
    pad_token_id: int = 0
    bos_token_id: int = 1
    # None: no token ends a request (it runs to its budget)
    eos_token_id: Optional[int] = 1
    attention_impl: str = "auto"  # see ops/mha.py
    dropout_rate: float = 0.0  # the published model has none; a fine-tuning recipe's
    # the dtype the published weights are stored in, and the one a serving
    # engine keeps them resident in (``ServingEngine.open``); None = as loaded
    param_dtype: Optional[str] = "bfloat16"

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, num_hidden_layers is {self.num_hidden_layers}"
            )
        bad = sorted(set(self.layer_types) - set(LAYER_TYPES))
        if bad:
            raise ValueError(f"layer_types {bad}: each must be one of {LAYER_TYPES}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of num_key_value_heads")

    @property
    def decoder_start_token_id(self) -> int:
        return self.bos_token_id

    @property
    def has_window_cache(self) -> bool:
        """True where the ``cache`` collection holds a window leaf: K/V of the
        last ``sliding_window`` positions, written as a ring."""
        return "sliding_attention" in self.layer_types


class MellumBlock(nn.Module):
    config: MellumConfig
    layer: int
    dtype: jnp.dtype = jnp.float32

    def setup(self) -> None:
        cfg = self.config
        window = cfg.layer_types[self.layer] == "sliding_attention"
        self.input_norm = RMSNorm(cfg.rms_norm_eps, self.dtype, name="input_norm")
        # one name for both kinds: a full layer's decode kernel is found by its
        # call site, a window layer's by the kernel's own name (``window_decode``)
        self.self_attn = MultiHeadAttention(
            num_heads=cfg.num_attention_heads,
            head_dim=cfg.head_dim,
            model_dim=cfg.hidden_size,
            num_kv_heads=cfg.num_key_value_heads,
            use_bias=False,
            causal=True,
            use_rope=True,
            rope_theta=cfg.rope_theta,
            rope_yarn=None if window else cfg.rope_yarn,
            window=cfg.sliding_window if window else None,
            dtype=self.dtype,
            attention_impl=cfg.attention_impl,
            qk_norm_eps=cfg.rms_norm_eps,
            name="self_attn",
        )
        self.post_norm = RMSNorm(cfg.rms_norm_eps, self.dtype, name="post_norm")
        self.mlp = MoEMLP(
            num_experts=cfg.num_experts,
            intermediate_size=cfg.moe_intermediate_size,
            top_k=cfg.num_experts_per_tok,
            capacity_factor=-1.0,  # the model never drops a token, on any path
            dtype=self.dtype,
            scorer="softmax",
            norm_topk_prob=cfg.norm_topk_prob,
            aux_loss=False,  # the published config names no auxiliary loss
            name="mlp",
        )
        self.dropout = Dropout(cfg.dropout_rate)

    def __call__(self, hidden, mask=None, bias=None, deterministic: bool = True, use_cache: bool = False,
                 positions=None, cache_positions=None):
        h = self.self_attn(
            self.input_norm(hidden), bias=bias, use_cache=use_cache, positions=positions,
            deterministic=deterministic, cache_positions=cache_positions, mask=mask if use_cache else None,
        )
        hidden = self.dropout(h, deterministic, residual=hidden)
        return self.dropout(self.mlp(self.post_norm(hidden)), deterministic, residual=hidden)


class MellumForCausalLM(nn.Module):
    config: MellumConfig
    dtype: jnp.dtype = jnp.float32
    remat: bool = False
    remat_policy: str = "full"  # "full" | "dots" (utils/remat.py)

    def setup(self) -> None:
        cfg = self.config
        self.embed_tokens = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=self.dtype, name="embed_tokens")
        # static args: deterministic (4), use_cache (5) — counting self at 0
        block = remat_block(MellumBlock, (4, 5), self.remat_policy) if self.remat else MellumBlock
        self.blocks = [block(cfg, i, dtype=self.dtype, name=f"block_{i}") for i in range(cfg.num_hidden_layers)]
        self.final_norm = RMSNorm(cfg.rms_norm_eps, self.dtype, name="final_norm")
        self.lm_head = nn.Dense(cfg.vocab_size, use_bias=False, dtype=self.dtype, name="lm_head")

    def hidden_states(self, input_ids, attention_mask=None, *, deterministic: bool = True,
                      use_cache: bool = False, positions: jnp.ndarray | None = None,
                      cache_positions: jnp.ndarray | None = None):
        """Final-norm output without the head."""
        hidden = constrain_hidden(self.embed_tokens(input_ids))
        # causal and window masking live inside MultiHeadAttention; only padding is a bias
        bias = mask_to_bias(attention_mask) if attention_mask is not None else None
        for blk in self.blocks:
            hidden = constrain_hidden(
                blk(hidden, attention_mask, bias, deterministic, use_cache, positions, cache_positions)
            )
        return self.final_norm(hidden)

    def __call__(self, input_ids, attention_mask=None, *, deterministic: bool = True, use_cache: bool = False,
                 cache_offset: int | jnp.ndarray = 0, max_kv_len: int | None = None,
                 positions: jnp.ndarray | None = None, cache_positions: jnp.ndarray | None = None):
        hidden = self.hidden_states(
            input_ids, attention_mask, deterministic=deterministic, use_cache=use_cache,
            positions=positions, cache_positions=cache_positions,
        )
        return constrain_logits(self.lm_head(hidden))
