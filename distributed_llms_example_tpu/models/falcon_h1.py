"""Falcon-H1 causal LM in flax.linen (tiiuae ``falcon_h1``: Falcon-H1-34B-Instruct).

A dense decoder in which EVERY block runs a Mamba-2 mixer and grouped-query
attention side by side over one normed input and sums them into one residual;
the family's fixed µP multipliers are config keys.  Written from the published
``config.json`` and the layer's equations (the plain twin, token by token, is
``benchmarks/reference/falcon_h1.py``; the mixer's mathematics and the state's
layout are ``ops/ssm.py``):

* model: ``h_0 = embedding_multiplier x E[ids]``; the blocks; a final RMSNorm
  (eps ``rms_norm_eps``); ``logits = lm_head_multiplier x W_head h``; untied;
  no biases but the convolution's;
* block: ``u = rms(x)``; ``x' = x + attention_out_multiplier x
  Attn(attention_in_multiplier x u) + ssm_out_multiplier x Mix(ssm_in_multiplier
  x u)``; ``y = x' + MLP(rms(x'))``;
* ``MLP(s) = mlp_multipliers[1] x W_down(silu(mlp_multipliers[0] x W_gate s) *
  W_up s)``;
* ``Attn(s)``: ``q = W_q s`` (20 x 128), ``k = key_multiplier x W_k s``, ``v =
  W_v s`` (4 x 128); RoPE (``rope_theta``, half rotation, unscaled); causal
  softmax of ``q . k / sqrt(128)``; ``W_o``; no q/k head norms;
* ``Mix(s)`` (``I = mamba_d_ssm`` = heads x head size, ``G`` groups, ``N``
  state, ``conv_dim = I + 2 G N``): ``p = (W_in s) * m`` with ``W_in: hidden ->
  I + conv_dim + heads`` and ``m`` the vector that holds ``ssm_multipliers[0..4]``
  over the sections ``[z: I | x: I | B: G N | C: G N | dt: heads]``; ``xBC <-
  silu(causal depthwise conv of mamba_d_conv taps (xBC) + bias)``; ``dt =
  softplus(dt + dt_bias)`` a head; ``A = -exp(A_log)`` a head; the recurrence
  of ``ops/ssm.py`` (head ``h`` reads group ``h // (heads / G)``) with the skip
  ``D x``; then the gated norm (``mamba_rms_norm`` true,
  ``mamba_norm_before_gate`` false): ``y <- y * silu(z)``, RMSNorm over each of
  the ``G`` groups of ``I / G`` channels with a gain of ``I``; ``W_out: I ->
  hidden``.

**Assumed** (none of it a key of the published config; each is listed with its
reason in ``benchmarks/configs/falcon-h1-34b.json``): the order of ``W_in``'s
sections and of ``ssm_multipliers`` over them; the multipliers applied to
``W_in``'s OUTPUT; ``dt_bias``, ``A_log`` and ``D`` a head; no clamp on ``dt``;
the norm's grouping; ``mamba_use_mlp`` true = the block has its MLP;
``mamba_expand``, ``mlp_expansion_factor``, ``attn_layer_indices`` (null) and
``num_logits_to_keep`` are not read.

What a decoder carries from step to step is, a layer, THREE kinds of leaf in
the ``cache`` collection: the attention's ``cached_key`` / ``cached_value``
(batch, length, kv_heads x head_dim), the mixer's ``ssm_state`` (batch, heads,
state, head size) float32 with no length axis, and its ``conv_state`` (batch,
conv_dim, taps - 1), the last pre-activation columns of ``xBC``.  A cached
call of ONE token is a recurrent step (the Pallas kernel ``ssm_step`` on a TPU
at lane-aligned sizes, which streams the state of the rows that hold a
sequence and no other; plain ``jnp`` elsewhere) beside a decode attention; a
cached call of MORE tokens is a prompt and starts the sequence: the state and
taps it finds are not read, those it leaves are of its valid tokens alone
(``mask``: a right-padded prompt's tail is kept out).  Continuing a stored
state with several tokens at once (a further turn, a prefix cache, a
speculative verify) is not implemented and refused by ``has_recurrent_state``
in the serving engine; a row whose ``cache_positions`` lie outside the mask (an
idle serving slot) leaves its state and taps as they were.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_llms_example_tpu.ops import ssm
from distributed_llms_example_tpu.ops.attention import mask_to_bias
from distributed_llms_example_tpu.ops.fused_dropout import Dropout
from distributed_llms_example_tpu.ops.mha import MultiHeadAttention
from distributed_llms_example_tpu.ops.norms import RMSNorm
from distributed_llms_example_tpu.parallel.activation import constrain_hidden, constrain_logits
from distributed_llms_example_tpu.utils.remat import remat_block


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    max_position_embeddings: int = 262144
    # the family's fixed µP multipliers, config keys all of them
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    ssm_multipliers: tuple[float, ...] = (0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738)
    mlp_multipliers: tuple[float, ...] = (0.1767766952966369, 0.011160714285714284)
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
        if self.mamba_d_ssm != self.mamba_n_heads * self.mamba_d_head:
            raise ValueError("mamba_d_ssm must be mamba_n_heads x mamba_d_head")
        if self.mamba_n_heads % self.mamba_n_groups or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads must be a multiple of their groups (mamba_n_groups, num_key_value_heads)")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers holds 5 values (z, x, B, C, dt) and mlp_multipliers 2 (gate, down)")

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: ``x | B | C``."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def decoder_start_token_id(self) -> int:
        return self.bos_token_id

    @property
    def has_recurrent_state(self) -> bool:
        """True: the ``cache`` collection holds a state and taps beside its K/V."""
        return True

    @property
    def decode_streams_live_slots(self) -> bool:
        """Whether a decode round moves the state of the live slots alone (the
        step kernel's list) or of every slot (the plain step): what chooses
        the mixer's step, and what the serving engine's counter asks."""
        return ssm.step_kernel_runs(self.mamba_d_head, self.mamba_d_state)


# what the mixer's own parameters start from (a model file's to choose: the
# published config has no key for them): softplus(-2.0) = 0.127 and A = -exp(-1.2)
# = -0.30 give a decay of exp(-0.038) = 0.96 a token, a memory of ~25 tokens
DT_BIAS_INIT, A_LOG_INIT = -2.0, -1.2


class Mamba2Mixer(nn.Module):
    """``Mix`` of the module docstring, with its decode state and taps."""

    config: FalconH1Config
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, s, mask=None, use_cache: bool = False, cache_positions=None):
        """``mask`` (batch, tokens) uncached, (batch, cache width) cached, and
        ``cache_positions`` (batch,) as the attention layers take them: on a
        cached call they say which of the new tokens are real."""
        cfg = self.config
        inner, heads, p = cfg.mamba_d_ssm, cfg.mamba_n_heads, cfg.mamba_d_head
        g, n, taps = cfg.mamba_n_groups, cfg.mamba_d_state, cfg.mamba_d_conv
        b, t, _ = s.shape
        sections = (inner, inner, g * n, g * n, heads)  # z | x | B | C | dt
        m = jnp.concatenate([jnp.full((w,), v, jnp.float32) for w, v in zip(sections, cfg.ssm_multipliers)])
        proj = nn.Dense(sum(sections), use_bias=False, dtype=self.dtype, name="in_proj")(s) * m.astype(self.dtype)
        z, xbc, dt = jnp.split(proj, (inner, inner + cfg.conv_dim), axis=-1)
        weight = self.param("conv_weight", nn.initializers.lecun_normal(), (cfg.conv_dim, taps), jnp.float32).astype(self.dtype)
        bias = self.param("conv_bias", nn.initializers.zeros, (cfg.conv_dim,), jnp.float32).astype(self.dtype)
        dt_bias = self.param("dt_bias", nn.initializers.constant(DT_BIAS_INIT), (heads,), jnp.float32)
        a_neg = -jnp.exp(self.param("A_log", nn.initializers.constant(A_LOG_INIT), (heads,), jnp.float32).astype(jnp.float32))
        d_skip = self.param("D", nn.initializers.ones, (heads,), jnp.float32).astype(jnp.float32)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))  # (B, T, H), no clamp

        def split(u):  # activated conv channels -> x (.., H, P), B and C (.., G, N)
            x, bm, cm = jnp.split(nn.silu(u), (inner, inner + g * n), axis=-1)
            lead = u.shape[:-1]
            return x.reshape(*lead, heads, p), bm.reshape(*lead, g, n), cm.reshape(*lead, g, n)

        with jax.named_scope("mixer"):
            if not use_cache:
                x, bm, cm = split(ssm.causal_conv(xbc, weight, bias, mask)[0])
                y = ssm.ssm_prefill(x, dt, a_neg, bm, cm, d_skip, mask, cfg.mamba_chunk_size)[0]
            else:
                state = self.variable("cache", "ssm_state", jnp.zeros, ssm.state_shape(b, heads, p, n), jnp.float32)
                taps_kept = self.variable("cache", "conv_state", jnp.zeros, (b, cfg.conv_dim, taps - 1), self.dtype)
                index = self.variable("cache", "cache_index", lambda: jnp.array(0, dtype=jnp.int32))
                start = cache_positions if cache_positions is not None else jnp.full((b,), index.value, jnp.int32)
                if mask is None:
                    real = jnp.ones((b, t), jnp.int32)
                else:  # which of the new tokens the (cache-width) mask calls real
                    pos = start[:, None] + jnp.arange(t)[None, :]
                    real = jnp.take_along_axis(mask, jnp.clip(pos, 0, mask.shape[1] - 1), axis=1) * (pos < mask.shape[1])
                if t == 1:
                    # a row the mask leaves out (an idle serving slot) is not live: its state and taps stay
                    live = real[:, 0]
                    conv, taps_kept.value = ssm.causal_conv_step(xbc[:, 0], weight, bias, taps_kept.value, live)
                    x, bm, cm = split(conv)
                    step = ssm.ssm_step if cfg.decode_streams_live_slots else ssm.ssm_step_reference
                    y, state.value = step(x, dt[:, 0], a_neg, bm, cm, d_skip, state.value, live=live)
                    y = y[:, None]
                else:
                    if cache_positions is not None:
                        raise NotImplementedError(
                            "a cached state-space call of several tokens starts a sequence; continuing a stored "
                            "state and taps at per-row positions (warm admission, speculative verify) is not "
                            "implemented"
                        )
                    conv, kept = ssm.causal_conv(xbc, weight, bias, real)
                    taps_kept.value = kept.astype(self.dtype)
                    x, bm, cm = split(conv)
                    y, state.value = ssm.ssm_prefill(x, dt, a_neg, bm, cm, d_skip, real, cfg.mamba_chunk_size)
                if cache_positions is None:
                    index.value = index.value + t
            # the gated norm: statistics in float32 over each group's channels
            y = y.reshape(b, t, inner) * nn.silu(z.astype(jnp.float32))
            grouped = y.reshape(b, t, g, inner // g)
            grouped = grouped * jax.lax.rsqrt(jnp.mean(jnp.square(grouped), axis=-1, keepdims=True) + cfg.rms_norm_eps)
            gain = self.param("norm_scale", nn.initializers.ones, (inner,), jnp.float32)
            y = (grouped.reshape(b, t, inner) * gain).astype(self.dtype)
        return nn.Dense(cfg.hidden_size, use_bias=False, dtype=self.dtype, name="out_proj")(y)


class FalconH1MLP(nn.Module):
    config: FalconH1Config
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        gate_m, down_m = cfg.mlp_multipliers
        gate = nn.Dense(cfg.intermediate_size, use_bias=False, dtype=self.dtype, name="gate_proj")(x)
        up = nn.Dense(cfg.intermediate_size, use_bias=False, dtype=self.dtype, name="up_proj")(x)
        return nn.Dense(cfg.hidden_size, use_bias=False, dtype=self.dtype, name="down_proj")(
            nn.silu(gate * gate_m) * up
        ) * down_m


class FalconH1Block(nn.Module):
    config: FalconH1Config
    dtype: jnp.dtype = jnp.float32

    def setup(self) -> None:
        cfg = self.config
        self.input_norm = RMSNorm(cfg.rms_norm_eps, self.dtype, name="input_norm")
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
            key_multiplier=cfg.key_multiplier,
            name="self_attn",
        )
        self.mixer = Mamba2Mixer(cfg, dtype=self.dtype, name="mixer")
        self.ffn_norm = RMSNorm(cfg.rms_norm_eps, self.dtype, name="ffn_norm")
        self.mlp = FalconH1MLP(cfg, dtype=self.dtype, name="mlp")
        self.dropout = Dropout(cfg.dropout_rate)

    def __call__(self, hidden, mask=None, bias=None, deterministic: bool = True, use_cache: bool = False,
                 positions=None, cache_positions=None):
        cfg = self.config
        u = self.input_norm(hidden)  # both mixers read the SAME normed input
        attn = self.self_attn(
            u * cfg.attention_in_multiplier if cfg.attention_in_multiplier != 1.0 else u, bias=bias,
            use_cache=use_cache, positions=positions, deterministic=deterministic, cache_positions=cache_positions,
        )
        mix = self.mixer(u * cfg.ssm_in_multiplier, mask, use_cache, cache_positions)
        both = attn * cfg.attention_out_multiplier + mix * cfg.ssm_out_multiplier
        hidden = self.dropout(both.astype(hidden.dtype), deterministic, residual=hidden)
        return self.dropout(self.mlp(self.ffn_norm(hidden)), deterministic, residual=hidden)


class FalconH1ForCausalLM(nn.Module):
    config: FalconH1Config
    dtype: jnp.dtype = jnp.float32
    remat: bool = False
    remat_policy: str = "full"  # "full" | "dots" (utils/remat.py)

    def setup(self) -> None:
        cfg = self.config
        self.embed_tokens = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=self.dtype, name="embed_tokens")
        # static args: deterministic (4), use_cache (5) — counting self at 0
        block = remat_block(FalconH1Block, (4, 5), self.remat_policy) if self.remat else FalconH1Block
        self.blocks = [block(cfg, dtype=self.dtype, name=f"block_{i}") for i in range(cfg.num_hidden_layers)]
        self.final_norm = RMSNorm(cfg.rms_norm_eps, self.dtype, name="final_norm")
        self.lm_head = nn.Dense(cfg.vocab_size, use_bias=False, dtype=self.dtype, name="lm_head")

    def hidden_states(self, input_ids, attention_mask=None, *, deterministic: bool = True,
                      use_cache: bool = False, positions: jnp.ndarray | None = None,
                      cache_positions: jnp.ndarray | None = None):
        """Final-norm output without the head."""
        hidden = constrain_hidden(self.embed_tokens(input_ids) * self.config.embedding_multiplier)
        # causal masking lives inside MultiHeadAttention; only padding is a bias
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
        return constrain_logits(self.lm_head(hidden) * self.config.lm_head_multiplier)
