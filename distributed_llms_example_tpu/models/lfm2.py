"""LFM2-MoE causal LM in flax.linen (LiquidAI ``lfm2_moe``: LFM2-8B-A1B).

A hybrid decoder: each layer's operator is either a **gated short
convolution** or **grouped-query attention**, chosen per layer by
``layer_types``; its feed-forward is a dense SwiGLU in the first
``num_dense_layers`` layers and a sigmoid-routed expert layer after them.
Written from the published config and the family's equations (the plain
twin, with each departure noted, is ``benchmarks/reference/lfm2_moe.py``):

* block: ``h = x + op(rms(x))``, ``y = h + ffn(rms(h))``; RMSNorm eps
  ``norm_eps``; a final RMSNorm; the head tied to the embedding;
* ``conv``: ``[B, C, X] = split3(W_in x)``, ``z = B * X``,
  ``c_t = sum_k w[:, k] * z_{t-(L-1)+k}`` (depthwise, causal, ``L`` =
  ``conv_L_cache`` taps), ``out = W_out (C * c)``.  What a decoder carries
  from step to step is the last ``L - 1`` columns of ``z``: the
  ``conv_state`` leaf of the ``cache`` collection, beside the K/V of the
  attention layers;
* ``full_attention``: GQA with an RMSNorm over head_dim of every q and k
  head before RoPE (``ops/mha.py`` ``qk_norm_eps``), causal, no biases;
* experts: ``ops/moe.py`` with the sigmoid scorer, a selection bias, top-k
  weights renormalised; never a dropped token, on any path.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_llms_example_tpu.models.llama import LlamaMLP
from distributed_llms_example_tpu.ops.attention import mask_to_bias
from distributed_llms_example_tpu.ops.fused_dropout import Dropout
from distributed_llms_example_tpu.ops.mha import MultiHeadAttention
from distributed_llms_example_tpu.ops.moe import MoEMLP
from distributed_llms_example_tpu.ops.norms import RMSNorm
from distributed_llms_example_tpu.parallel.activation import constrain_hidden, constrain_logits
from distributed_llms_example_tpu.utils.remat import remat_block

LAYER_TYPES = ("conv", "full_attention")
_PUBLISHED_LAYER_TYPES = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv" for i in range(24)
)


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168  # the dense layers' SwiGLU
    moe_intermediate_size: int = 1792  # each expert's SwiGLU
    num_hidden_layers: int = 24
    layer_types: tuple[str, ...] = _PUBLISHED_LAYER_TYPES
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    num_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_position_embeddings: int = 128000
    pad_token_id: int = 0
    bos_token_id: int = 1
    # None: the model has no end-of-sequence token to stop on (a request runs
    # to its budget)
    eos_token_id: Optional[int] = 1
    attention_impl: str = "auto"  # see ops/mha.py
    # the published model has no dropout; as in models/llama.py the residual
    # adds go through the shared fused helper, so a fine-tuning recipe can turn
    # it on (0 = a plain add)
    dropout_rate: float = 0.0
    # the dtype the published weights are stored in, and the one a serving
    # engine keeps them resident in (``ServingEngine.open``); None = as loaded
    param_dtype: Optional[str] = "bfloat16"

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}"
            )
        bad = sorted(set(self.layer_types) - set(LAYER_TYPES))
        if bad:
            raise ValueError(f"layer_types {bad}: each must be one of {LAYER_TYPES}")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def decoder_start_token_id(self) -> int:
        return self.bos_token_id

    @property
    def has_recurrent_state(self) -> bool:
        """True where the ``cache`` collection holds state other than K/V."""
        return "conv" in self.layer_types


class ShortConv(nn.Module):
    """The gated short-convolution operator, with its decode state."""

    config: Lfm2Config
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, mask=None, use_cache: bool = False, cache_positions=None):
        """``mask`` (batch, cache width) and ``cache_positions`` (batch,) as the
        attention layers take them: on a cached call they say which of the
        new columns are real tokens (a right-padded prompt's tail is not), so
        that the state kept is the last ``L - 1`` VALID columns of ``z``."""
        cfg = self.config
        d, taps = cfg.hidden_size, cfg.conv_L_cache
        b, t, _ = x.shape
        bcx = nn.Dense(3 * d, use_bias=False, dtype=self.dtype, name="in_proj")(x)
        weight = self.param(
            "conv_weight", nn.initializers.lecun_normal(), (d, taps), jnp.float32
        ).astype(self.dtype)
        with jax.named_scope("short_conv"):
            gate_b, gate_c, xs = jnp.split(bcx, 3, axis=-1)
            z = gate_b * xs  # (B, T, d)
            if use_cache:
                seen = self.has_variable("cache", "conv_state")
                state = self.variable(
                    "cache", "conv_state", jnp.zeros, (b, d, taps - 1), self.dtype
                )
                index = self.variable(
                    "cache", "cache_index", lambda: jnp.array(0, dtype=jnp.int32)
                )
                past = jnp.swapaxes(state.value, 1, 2) if seen else jnp.zeros((b, taps - 1, d), z.dtype)
                zs = jnp.concatenate([past.astype(z.dtype), z], axis=1)  # (B, L-1+T, d)
                if seen:
                    start = (
                        cache_positions if cache_positions is not None
                        else jnp.full((b,), index.value, jnp.int32)
                    )
                    if mask is None:
                        n_valid = jnp.full((b,), t, jnp.int32)
                    else:
                        pos = start[:, None] + jnp.arange(t)[None, :]
                        real = jnp.take_along_axis(
                            mask, jnp.clip(pos, 0, mask.shape[1] - 1), axis=1
                        ) * (pos < mask.shape[1])
                        n_valid = jnp.sum(real, axis=1).astype(jnp.int32)
                    # columns n_valid .. n_valid + L - 2 of ``zs`` are the last
                    # L - 1 the row really holds (its old state where it got none)
                    keep = n_valid[:, None] + jnp.arange(taps - 1)[None, :]
                    state.value = jnp.swapaxes(
                        jnp.take_along_axis(zs, keep[:, :, None], axis=1), 1, 2
                    ).astype(self.dtype)
                    if cache_positions is None:
                        index.value = index.value + t
            else:
                zs = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
            conv = sum(weight[:, k] * zs[:, k : k + t] for k in range(taps))
            y = gate_c * conv
        return nn.Dense(d, use_bias=False, dtype=self.dtype, name="out_proj")(y)


class Lfm2Block(nn.Module):
    config: Lfm2Config
    layer: int
    dtype: jnp.dtype = jnp.float32

    def setup(self) -> None:
        cfg = self.config
        self.operator_norm = RMSNorm(cfg.norm_eps, self.dtype, name="operator_norm")
        self.is_attention = cfg.layer_types[self.layer] == "full_attention"
        if self.is_attention:
            self.self_attn = MultiHeadAttention(
                num_heads=cfg.num_attention_heads,
                head_dim=cfg.head_dim,
                model_dim=cfg.hidden_size,
                num_kv_heads=cfg.num_key_value_heads,
                use_bias=False,
                causal=True,
                use_rope=True,
                rope_theta=cfg.rope_theta,
                dtype=self.dtype,
                attention_impl=cfg.attention_impl,
                qk_norm_eps=cfg.norm_eps,
                name="self_attn",
            )
        else:
            self.conv = ShortConv(cfg, dtype=self.dtype, name="conv")
        self.ffn_norm = RMSNorm(cfg.norm_eps, self.dtype, name="ffn_norm")
        self.is_moe = self.layer >= cfg.num_dense_layers
        if self.is_moe:
            self.mlp = MoEMLP(
                num_experts=cfg.num_experts,
                intermediate_size=cfg.moe_intermediate_size,
                top_k=cfg.num_experts_per_tok,
                capacity_factor=-1.0,  # the model never drops a token, on any path
                dtype=self.dtype,
                scorer="sigmoid",
                use_expert_bias=cfg.use_expert_bias,
                norm_topk_prob=cfg.norm_topk_prob,
                routed_scaling_factor=cfg.routed_scaling_factor,
                aux_loss=False,  # balanced through the selection bias, not a loss
                name="mlp",
            )
        else:
            self.mlp = LlamaMLP(cfg, dtype=self.dtype, name="mlp")
        self.dropout = Dropout(cfg.dropout_rate)

    def __call__(
        self, hidden, mask=None, bias=None, deterministic: bool = True, use_cache: bool = False,
        positions=None, cache_positions=None,
    ):
        x = self.operator_norm(hidden)
        if self.is_attention:
            h = self.self_attn(
                x, bias=bias, use_cache=use_cache, positions=positions,
                deterministic=deterministic, cache_positions=cache_positions,
            )
        else:
            h = self.conv(x, mask, use_cache, cache_positions)
        hidden = self.dropout(h, deterministic, residual=hidden)
        return self.dropout(self.mlp(self.ffn_norm(hidden)), deterministic, residual=hidden)


class Lfm2ForCausalLM(nn.Module):
    config: Lfm2Config
    dtype: jnp.dtype = jnp.float32
    remat: bool = False
    remat_policy: str = "full"  # "full" | "dots" (utils/remat.py)

    def setup(self) -> None:
        cfg = self.config
        self.embed_tokens = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=self.dtype, name="embed_tokens")
        # static args: deterministic (4), use_cache (5) — counting self at 0
        block = remat_block(Lfm2Block, (4, 5), self.remat_policy) if self.remat else Lfm2Block
        self.blocks = [
            block(cfg, i, dtype=self.dtype, name=f"block_{i}") for i in range(cfg.num_hidden_layers)
        ]
        self.final_norm = RMSNorm(cfg.norm_eps, self.dtype, name="final_norm")

    def hidden_states(
        self,
        input_ids,
        attention_mask=None,
        *,
        deterministic: bool = True,
        use_cache: bool = False,
        positions: jnp.ndarray | None = None,
        cache_positions: jnp.ndarray | None = None,
    ):
        """Final-norm output without the head (the fused-CE path's input)."""
        hidden = constrain_hidden(self.embed_tokens(input_ids))
        # causal masking lives inside MultiHeadAttention; only padding is a bias
        bias = mask_to_bias(attention_mask) if attention_mask is not None else None
        for blk in self.blocks:
            hidden = constrain_hidden(
                blk(hidden, attention_mask, bias, deterministic, use_cache, positions, cache_positions)
            )
        return self.final_norm(hidden)

    def __call__(
        self,
        input_ids,
        attention_mask=None,
        *,
        deterministic: bool = True,
        use_cache: bool = False,
        cache_offset: int | jnp.ndarray = 0,
        max_kv_len: int | None = None,
        positions: jnp.ndarray | None = None,
        cache_positions: jnp.ndarray | None = None,
    ):
        hidden = self.hidden_states(
            input_ids, attention_mask, deterministic=deterministic, use_cache=use_cache,
            positions=positions, cache_positions=cache_positions,
        )
        return constrain_logits(self.embed_tokens.attend(hidden))
