"""Brumby causal LM in flax.linen (manifestai ``brumby``: Brumby-14B-Base).

A dense decoder of the Qwen3 lineage in which every layer's attention is a
**power-retention** layer: a sequence's whole memory in a layer is one
fixed-size matrix a KV head, the same at position 1 as at position 32,768; no
K/V is kept at all.  Written from the published ``config.json`` and the
layer's equations (the plain twin, in the attention form only, is
``benchmarks/reference/brumby.py``; the mathematics and the state's layout are
``ops/retention.py``):

* block: ``h = x + Ret(rms(x))``, ``y = h + SwiGLU(rms(h))``; RMSNorm eps
  ``rms_norm_eps``; a final RMSNorm; an untied head; no biases
  (``attention_bias`` false);
* ``Ret(x)``: ``q = W_q x`` (40 heads x 128), ``k = W_k x``, ``v = W_v x`` (8
  heads x 128); RMSNorm over head_dim of every q and k head (their own
  gains), then RoPE (``rope_theta``); a gate a KV head a token, ``log g_t =
  logsigmoid(W_g x_t + b_g)``, ``W_g: hidden -> kv_heads``; with ``p = 2``,
  ``d = head_dim``, ``G_i = sum_{l<=i} log g_l``, for a query head ``h`` of KV
  head ``kv(h) = h // (heads / kv_heads)``:
  ``a_ij = (q_i . k_j / sqrt(d))^p exp(G_i - G_j)`` for ``j <= i``, else 0;
  ``y_i = sum_j a_ij v_j / (sum_j a_ij + eps)``; equally the recurrence ``S_i =
  g_i S_{i-1} + phi(k_i) v_i^T``, ``z_i = g_i z_{i-1} + phi(k_i)``, ``y_i =
  phi(q_i)^T S_i / (phi(q_i)^T z_i + eps)`` with ``phi(a) . phi(b) = (a . b /
  sqrt(d))^p``; ``Ret`` returns ``W_o concat_h(y)``.

**Assumed** (none of it a key of the published config; each is listed with its
reason in ``benchmarks/configs/brumby-14b.json``): the degree ``p = 2`` (the
publisher's description); the gate a KV head, so that a group of query heads
shares one state as grouped heads share K/V; ``logsigmoid`` and the gate's
bias; ``eps`` = 1e-6; the ``1/sqrt(d)`` scale; q/k head norms and RoPE kept
from the lineage.

What a decoder carries from step to step is, a layer, the ``retention_state``
leaf ``(batch, kv_heads, rotations, head_dim, head_dim)`` and its normaliser
``retention_norm`` ``(batch, kv_heads, rotations, head_dim)``, float32, in
the ``cache`` collection (``ops/retention.py`` says why that layout and
dtype).  A cached call of ONE token is a recurrent step (the Pallas kernel
``retention_step`` on a TPU at lane-aligned head sizes, which streams the
state of the rows that hold a sequence and no other; plain ``jnp``
elsewhere); a cached call of MORE tokens is a prompt and starts the sequence:
the state it finds is not read, the state it leaves is that of its valid
tokens alone (``mask``: a right-padded prompt's tail is kept out).  Continuing
a stored state with several tokens at once (a further turn, a prefix cache, a
speculative verify) is not implemented and refused by
``has_recurrent_state`` in the serving engine; a row whose ``cache_positions``
lie outside the mask (an idle serving slot) leaves its state as it was.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_llms_example_tpu.models.llama import LlamaMLP
from distributed_llms_example_tpu.ops import retention
from distributed_llms_example_tpu.ops.fused_dropout import Dropout
from distributed_llms_example_tpu.ops.mha import apply_rope, rope_cos_sin
from distributed_llms_example_tpu.ops.norms import RMSNorm
from distributed_llms_example_tpu.parallel.activation import constrain_hidden, constrain_logits
from distributed_llms_example_tpu.utils.remat import remat_block


@dataclasses.dataclass(frozen=True)
class BrumbyConfig:
    vocab_size: int = 151936
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    max_position_embeddings: int = 32768
    # assumed (module docstring): the degree is not a config key; only the
    # second power is implemented (its feature map is ops/retention.py's)
    retention_degree: int = 2
    retention_eps: float = retention.EPS
    pad_token_id: int = 0
    bos_token_id: int = 1
    # None: no token ends a request (it runs to its budget)
    eos_token_id: Optional[int] = 1
    dropout_rate: float = 0.0  # the published model has none; a fine-tuning recipe's
    # the dtype the published weights are stored in, and the one a serving
    # engine keeps them resident in (``ServingEngine.open``); None = as loaded
    param_dtype: Optional[str] = "bfloat16"

    def __post_init__(self):
        if self.retention_degree != 2:
            raise ValueError(f"retention_degree {self.retention_degree}: only degree 2 is implemented")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of num_key_value_heads")

    @property
    def decoder_start_token_id(self) -> int:
        return self.bos_token_id

    @property
    def has_recurrent_state(self) -> bool:
        """True: the ``cache`` collection holds a retention state and no K/V."""
        return True

    @property
    def decode_streams_live_slots(self) -> bool:
        """Whether a decode round moves the state of the live slots alone (the
        step kernel's list) or of every slot (the plain step): what chooses
        the layer's step, and what the serving engine's counter asks."""
        return retention.step_kernel_runs(self.head_dim, self.head_dim)


# the gate's bias at init: sigmoid(5.4) = 0.9955, a memory of ~220 tokens (a
# zero bias would forget in two)
GATE_BIAS_INIT = 5.4


class PowerRetention(nn.Module):
    """``Ret`` of the module docstring, with its decode state."""

    config: BrumbyConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, mask=None, use_cache: bool = False, positions=None, cache_positions=None):
        """``mask`` (batch, tokens) uncached, (batch, cache width) cached, and
        ``cache_positions`` (batch,) as the attention layers of the other
        models take them: on a cached call they say which of the new tokens
        are real."""
        cfg = self.config
        heads, kv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        b, t, _ = x.shape
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=self.dtype, name=name)  # noqa: E731
        split = lambda y, n: y.reshape(b, t, n, d).transpose(0, 2, 1, 3)  # noqa: E731 — (B, n, T, d)
        q = RMSNorm(cfg.rms_norm_eps, self.dtype, name="q_norm")(split(dense(heads * d, "q_proj")(x), heads))
        k = RMSNorm(cfg.rms_norm_eps, self.dtype, name="k_norm")(split(dense(kv * d, "k_proj")(x), kv))
        v = split(dense(kv * d, "v_proj")(x), kv)
        gate = nn.Dense(kv, use_bias=True, dtype=jnp.float32, name="g_proj",
                        bias_init=nn.initializers.constant(GATE_BIAS_INIT))(x)
        log_g = jax.nn.log_sigmoid(gate.astype(jnp.float32)).transpose(0, 2, 1)  # (B, KV, T)

        start = None
        if use_cache:
            s_shape, z_shape = retention.state_shapes(b, kv, d, d)
            state = self.variable("cache", "retention_state", jnp.zeros, s_shape, jnp.float32)
            norm = self.variable("cache", "retention_norm", jnp.zeros, z_shape, jnp.float32)
            index = self.variable("cache", "cache_index", lambda: jnp.array(0, dtype=jnp.int32))
            start = cache_positions if cache_positions is not None else jnp.full((b,), index.value, jnp.int32)
        if positions is None:
            positions = jnp.arange(t)[None, :] + (0 if start is None else start[:, None])
        cos, sin = rope_cos_sin(positions, d, cfg.rope_theta)
        q, k = apply_rope(q, cos[:, None], sin[:, None]), apply_rope(k, cos[:, None], sin[:, None])

        with jax.named_scope("retention"):
            if not use_cache:
                y = retention.retention_prefill(q, k, v, log_g, mask, cfg.retention_eps)[0]
            else:
                if mask is None:
                    real = jnp.ones((b, t), jnp.int32)
                else:  # which of the new tokens the (cache-width) mask calls real
                    pos = start[:, None] + jnp.arange(t)[None, :]
                    real = jnp.take_along_axis(mask, jnp.clip(pos, 0, mask.shape[1] - 1), axis=1) * (pos < mask.shape[1])
                if t == 1:
                    # a row the mask leaves out (an idle serving slot) is not live: its state stays as it was
                    step = retention.retention_step if cfg.decode_streams_live_slots else retention.retention_step_reference
                    y, state.value, norm.value = step(
                        q[:, :, 0], k[:, :, 0], v[:, :, 0], log_g[:, :, 0],
                        state.value, norm.value, live=real[:, 0], eps=cfg.retention_eps,
                    )
                    y = y[:, :, None]
                else:
                    if cache_positions is not None:
                        raise NotImplementedError(
                            "a cached power-retention call of several tokens starts a sequence; continuing a "
                            "stored state at per-row positions (warm admission, speculative verify) is not "
                            "implemented"
                        )
                    y, state.value, norm.value = retention.retention_prefill(q, k, v, log_g, real, cfg.retention_eps)
                if cache_positions is None:
                    index.value = index.value + t
        y = y.astype(self.dtype).transpose(0, 2, 1, 3).reshape(b, t, heads * d)
        return dense(cfg.hidden_size, "o_proj")(y)


class BrumbyBlock(nn.Module):
    config: BrumbyConfig
    dtype: jnp.dtype = jnp.float32

    def setup(self) -> None:
        cfg = self.config
        self.input_norm = RMSNorm(cfg.rms_norm_eps, self.dtype, name="input_norm")
        self.retention = PowerRetention(cfg, dtype=self.dtype, name="retention")
        self.post_norm = RMSNorm(cfg.rms_norm_eps, self.dtype, name="post_norm")
        self.mlp = LlamaMLP(cfg, dtype=self.dtype, name="mlp")
        self.dropout = Dropout(cfg.dropout_rate)

    def __call__(self, hidden, mask=None, deterministic: bool = True, use_cache: bool = False,
                 positions=None, cache_positions=None):
        h = self.retention(self.input_norm(hidden), mask, use_cache, positions, cache_positions)
        hidden = self.dropout(h, deterministic, residual=hidden)
        return self.dropout(self.mlp(self.post_norm(hidden)), deterministic, residual=hidden)


class BrumbyForCausalLM(nn.Module):
    config: BrumbyConfig
    dtype: jnp.dtype = jnp.float32
    remat: bool = False
    remat_policy: str = "full"  # "full" | "dots" (utils/remat.py)

    def setup(self) -> None:
        cfg = self.config
        self.embed_tokens = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=self.dtype, name="embed_tokens")
        # static args: deterministic (3), use_cache (4) — counting self at 0
        block = remat_block(BrumbyBlock, (3, 4), self.remat_policy) if self.remat else BrumbyBlock
        self.blocks = [block(cfg, dtype=self.dtype, name=f"block_{i}") for i in range(cfg.num_hidden_layers)]
        self.final_norm = RMSNorm(cfg.rms_norm_eps, self.dtype, name="final_norm")
        self.lm_head = nn.Dense(cfg.vocab_size, use_bias=False, dtype=self.dtype, name="lm_head")

    def hidden_states(self, input_ids, attention_mask=None, *, deterministic: bool = True,
                      use_cache: bool = False, positions: jnp.ndarray | None = None,
                      cache_positions: jnp.ndarray | None = None):
        """Final-norm output without the head."""
        hidden = constrain_hidden(self.embed_tokens(input_ids))
        for blk in self.blocks:
            hidden = constrain_hidden(
                blk(hidden, attention_mask, deterministic, use_cache, positions, cache_positions)
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
