"""T5 encoder-decoder, written TPU-first in flax.linen.

Replaces the reference's opaque ``AutoModelForSeq2SeqLM.from_pretrained``
(reference train-accelerator.py:40-41) with an in-repo model definition the
sharding rules and Pallas kernels can see into.  Numerical semantics match
HF T5 so converted checkpoints are drop-in (verified by parity tests):

- RMSNorm (no mean subtraction, no bias), fp32 statistics
- relative position bias added to attention scores, bias table shared
  across layers (held once per stack, not per block 0 as HF stores it)
- attention scores are NOT scaled by 1/sqrt(d_kv) — T5 folds that into init
- pre-norm residual blocks; final stack norm
- tied embeddings scale decoder output by d_model**-0.5 before the logits
  projection; T5 v1.1 ("gated-gelu") unties and adds a separate lm_head

Supports both T5 v1.0 (relu FFN, tied) and v1.1/flan (gated-gelu, untied).
"""

from __future__ import annotations

import collections
import dataclasses
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_llms_example_tpu.ops.attention import (
    NEG_INF,
    beam_grouped_attention,
    dot_product_attention,
    make_causal_bias,
    mask_to_bias,
)
from distributed_llms_example_tpu.ops.flash_attention import flash_attention, relative_bias_matrix
from distributed_llms_example_tpu.ops.fused_dropout import Dropout
from distributed_llms_example_tpu.ops.norms import RMSNorm
from distributed_llms_example_tpu.utils.remat import remat_block
from distributed_llms_example_tpu.parallel.activation import constrain_hidden, constrain_logits


@dataclasses.dataclass(frozen=True)
class T5Config:
    vocab_size: int = 32128
    d_model: int = 512
    d_kv: int = 64
    d_ff: int = 2048
    num_layers: int = 6
    num_decoder_layers: Optional[int] = None
    num_heads: int = 8
    relative_attention_num_buckets: int = 32
    relative_attention_max_distance: int = 128
    dropout_rate: float = 0.1
    # attention-PROBS dropout.  HF T5 trains with this equal to
    # dropout_rate; this port has historically run it at 0 (activations
    # only) and keeps that default so trajectories stay comparable — set
    # it explicitly to recover the HF recipe.  On the flash path the mask
    # is drawn in-kernel (never materialized); the XLA path uses the
    # bernoulli reference (ops/attention.py).
    attn_dropout_rate: float = 0.0
    layer_norm_epsilon: float = 1e-6
    feed_forward_proj: str = "relu"  # or "gated-gelu"
    tie_word_embeddings: bool = True
    pad_token_id: int = 0
    eos_token_id: int = 1
    decoder_start_token_id: int = 0
    # "auto": Pallas flash attention where eligible — the learned
    # relative-position bias rides the kernel's differentiable
    # ``relative_bias`` input as a per-diagonal vector (multi-device meshes
    # use the sharded path whose hand-written vjp psums the diagonal sums
    # across batch shards,
    # ops/flash_attention.flash_attention_lbias_sharded), and mask-only
    # cross-attention takes the same paths as BART/LLaMA.
    attention_impl: str = "auto"

    @property
    def decoder_layers(self) -> int:
        return self.num_decoder_layers if self.num_decoder_layers is not None else self.num_layers

    @property
    def is_gated(self) -> bool:
        return self.feed_forward_proj.startswith("gated")


def relative_position_bucket(
    relative_position: jnp.ndarray,
    *,
    bidirectional: bool,
    num_buckets: int,
    max_distance: int,
) -> jnp.ndarray:
    """T5's log-bucketed relative position (kv_pos - q_pos) → bucket id."""
    ret = jnp.zeros_like(relative_position)
    if bidirectional:
        num_buckets //= 2
        ret += (relative_position > 0).astype(jnp.int32) * num_buckets
        rel = jnp.abs(relative_position)
    else:
        rel = -jnp.minimum(relative_position, 0)
    max_exact = num_buckets // 2
    is_small = rel < max_exact
    rel_f = jnp.maximum(rel.astype(jnp.float32), 1.0)
    if_large = max_exact + (
        jnp.log(rel_f / max_exact) / jnp.log(max_distance / max_exact) * (num_buckets - max_exact)
    ).astype(jnp.int32)
    if_large = jnp.minimum(if_large, num_buckets - 1)
    return ret + jnp.where(is_small, rel, if_large)


def relative_bias_vector(table: jnp.ndarray, config: T5Config, q_len: int, kv_len: int,
                         *, bidirectional: bool) -> jnp.ndarray:
    """(heads, q_len + kv_len - 1) fp32 from a stack's (buckets, heads) table:
    entry ``(k - q) + q_len - 1`` is ``table[bucket(k - q)]``."""
    buckets = relative_position_bucket(
        jnp.arange(-(q_len - 1), kv_len),
        bidirectional=bidirectional,
        num_buckets=config.relative_attention_num_buckets,
        max_distance=config.relative_attention_max_distance,
    )
    return jnp.take(table.astype(jnp.float32), buckets, axis=0).T


# attention sites with a relative bias traced since the last flush, by how the
# bias's gradient is made: "diagonal" = the flash kernel's diagonal sums,
# "matrix" = XLA's path through the (1, H, Q, K) matrix
_RELATIVE_BIAS_SITES: collections.Counter = collections.Counter()


def flush_relative_bias_sites() -> dict[str, int]:
    """Write the tally of one traced forward to the run's log (once a distinct
    tally, like every ``attention_impl`` record) and start it again."""
    from distributed_llms_example_tpu.ops.mha import _log_impl_once

    sites = {k: _RELATIVE_BIAS_SITES.pop(k, 0) for k in ("diagonal", "matrix")}
    if any(sites.values()):
        _log_impl_once(
            "t5:relative_bias",
            f"bias gradient by diagonal sums at {sites['diagonal']} sites, "
            f"through the full matrix at {sites['matrix']}",
            **sites,
        )
    return sites


class T5Attention(nn.Module):
    """Multi-head attention with optional causal masking and KV cache.

    Cache protocol (flax "cache" collection): initialize zero-filled
    full-length buffers with ``init_cache``, then each call with a
    single-query-step writes k/v at ``cache_index`` and attends over the
    prefix — the standard fixed-shape autoregressive decode under jit.
    """

    config: T5Config
    causal: bool = False
    dtype: jnp.dtype = jnp.float32

    def setup(self) -> None:
        cfg = self.config
        inner = cfg.num_heads * cfg.d_kv
        dense = lambda name: nn.Dense(inner, use_bias=False, dtype=self.dtype, name=name)  # noqa: E731
        self.q_proj, self.k_proj, self.v_proj = dense("q_proj"), dense("k_proj"), dense("v_proj")
        self.o_proj = nn.Dense(cfg.d_model, use_bias=False, dtype=self.dtype, name="o_proj")

    def _split(self, x: jnp.ndarray) -> jnp.ndarray:
        b, s, _ = x.shape
        return x.reshape(b, s, self.config.num_heads, self.config.d_kv).transpose(0, 2, 1, 3)

    def project_kv(self, kv_hidden: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
        """K/V projections alone — precomputed once per sequence for
        cross-attention decode (see MultiHeadAttention.project_kv)."""
        return self._split(self.k_proj(kv_hidden)), self._split(self.v_proj(kv_hidden))

    def _merge(self, x: jnp.ndarray) -> jnp.ndarray:
        b, h, s, d = x.shape
        return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)

    @nn.compact
    def _cache_kv(self, key: jnp.ndarray, value: jnp.ndarray,
                  cache_positions: jnp.ndarray | None = None) -> tuple:
        """Append this step's k/v ((B, heads, T, d_kv)) into the cache:
        ``ops.mha.cache_kv``, the package's one cache layout (a leaf is
        ``(batch, length, heads x d_kv)``, written and read where it rests).
        Returns the full-length leaves, the int8-KV scales (None on the f32
        path) and the (pre-update) cache index.  Per-row ``cache_positions``
        (continuous-batching slots at distinct offsets) take one q row here:
        the decode-step relative bias is built for one."""
        from distributed_llms_example_tpu.ops.mha import cache_kv

        if cache_positions is not None and key.shape[2] != 1:
            raise ValueError(
                f"per-row cache_positions requires q_len == 1, got {key.shape[2]}"
            )
        return cache_kv(self, key, value, cache_positions)

    def __call__(
        self,
        hidden: jnp.ndarray,
        kv_hidden: jnp.ndarray | None = None,
        bias: jnp.ndarray | None = None,
        *,
        use_cache: bool = False,
        relative_bias: jnp.ndarray | None = None,
        cross_kv: tuple[jnp.ndarray, jnp.ndarray] | None = None,
        deterministic: bool = True,
        cache_positions: jnp.ndarray | None = None,
    ) -> jnp.ndarray:
        """``bias``: constant (mask-like) additive bias.  ``relative_bias``:
        the relative-position bias as its (H, Q + K - 1) per-diagonal vector
        (``T5Stack.relative_bias``), kept SEPARATE so the flash kernel can
        treat the mask as constant while its dbias kernel hands the vector
        its gradient as diagonal sums.  When the caller pre-combines
        everything into ``bias`` (cache decode), the
        XLA path reproduces round-2 behavior exactly.  ``cross_kv``:
        precomputed ``project_kv`` output — skips the k/v projections.
        ``deterministic`` gates ``config.attn_dropout_rate`` (probs
        dropout; in-kernel mask on the flash path)."""
        q = self._split(self.q_proj(hidden))
        if cross_kv is not None:
            k, v = cross_kv
            if k.shape[0] != hidden.shape[0]:
                # beam decode: beams share the row's cross K/V — one
                # shared fold/unfold convention (ops/attention.py); T5
                # attention is unscaled
                out = beam_grouped_attention(q, k, v, bias, scale=1.0, dtype=self.dtype)
                return self.o_proj(self._merge(out))
        else:
            kv_src = hidden if kv_hidden is None else kv_hidden
            k = self._split(self.k_proj(kv_src))
            v = self._split(self.v_proj(kv_src))
        causal_in_bias = False
        if use_cache and self.causal:
            from distributed_llms_example_tpu.ops.flash_attention import (
                flash_decode_run,
            )
            from distributed_llms_example_tpu.ops.mha import (
                _log_impl_once,
                cache_heads_view,
                decode_step_bias,
                select_decode_impl,
            )
            from distributed_llms_example_tpu.parallel.activation import current_mesh

            k, v, k_scale, v_scale, idx = self._cache_kv(k, v, cache_positions)
            kv_len = k.shape[1]
            q_len = q.shape[2]
            offsets = (
                cache_positions
                if cache_positions is not None
                else jnp.full((q.shape[0],), idx, jnp.int32)
            )
            mesh = current_mesh()
            impl, reason = select_decode_impl(
                self.config.attention_impl,
                batch=q.shape[0],
                heads=self.config.num_heads,
                head_dim=self.config.d_kv,
                q_len=q_len,
                kv_len=kv_len,
                mesh=mesh,
                backend=jax.default_backend(),
                device_count=jax.device_count(),
                kv_dtype=k.dtype,
            )
            if (
                impl == "flash_decode"
                and not deterministic
                and float(self.config.attn_dropout_rate) > 0.0
            ):
                # no in-kernel mask stream in the decode kernel: keep the
                # XLA probs-dropout semantics via _attend below
                impl, reason = "xla", "probs dropout requested on cached decode"
            _log_impl_once(f"t5:{impl}", reason)
            if impl == "flash_decode":
                # the decode-step relative-position bias rides ``bias`` as a
                # constant (no gradients in decode); validity/causality ride
                # the kernel's per-row length mask.  T5 scores are unscaled;
                # int8 KV scales dequantize per kv tile inside the kernel.
                out = flash_decode_run(
                    q, k, v, bias, offsets=offsets, mesh=mesh, scale=1.0,
                    k_scale=k_scale, v_scale=v_scale,
                    dtype=self.dtype,
                )
                return self.o_proj(self._merge(out))
            # XLA's path views the leaf as (B, heads, L, d_kv) inside the program
            k, v = cache_heads_view(k, v, k_scale, v_scale, self.config.num_heads)
            # XLA path: per-row validity+causality mask merged into the bias
            step_bias = decode_step_bias(offsets, q_len, kv_len)
            bias = step_bias if bias is None else bias + step_bias
            causal_in_bias = True
        out = self._attend(
            q, k, v, bias, relative_bias, use_cache, causal_in_bias,
            deterministic,
        )
        return self.o_proj(self._merge(out))

    def _attend(self, q, k, v, bias, relative_bias, use_cache, causal_in_bias,
                deterministic=True):
        """T5 attention is UNSCALED (scale=1.0).  Selection mirrors
        MultiHeadAttention: ring on sequence meshes (cross-attention /
        mask-only biases), Pallas flash on TPU where tileable, XLA
        otherwise.  With a relative bias, multi-device meshes use the
        dedicated sharded path whose hand-written vjp psums the bias's
        diagonal sums across batch shards (flash_attention_lbias_sharded);
        XLA's path lays the vector out as the (1, H, Q, K) matrix it stands
        for and differentiates through that."""
        from distributed_llms_example_tpu.ops.flash_attention import (
            flash_attention_lbias_sharded,
        )
        from distributed_llms_example_tpu.ops.mha import (
            _log_impl_once,
            flash_run,
            select_attention_impl,
        )
        from distributed_llms_example_tpu.ops.ring_attention import ring_attention_sharded
        from distributed_llms_example_tpu.parallel.activation import BATCH_AXES, current_mesh

        causal_here = self.causal and not use_cache and not causal_in_bias
        mesh = current_mesh()
        impl, reason = select_attention_impl(
            self.config.attention_impl,
            batch=q.shape[0],
            heads=self.config.num_heads,
            head_dim=self.config.d_kv,
            q_len=q.shape[2],
            kv_len=k.shape[2],
            use_cache=use_cache,
            mesh=mesh,
            backend=jax.default_backend(),
            device_count=jax.device_count(),
            causal=causal_here,
            bias_kv_only=(
                False
                if relative_bias is not None
                else None if bias is None else (bias.shape[1] == 1 and bias.shape[2] == 1)
            ),
            has_learned_bias=relative_bias is not None,
        )
        _log_impl_once(f"t5:{impl}", reason)
        if relative_bias is not None:
            # trace-time tally of how this site's bias gradient is made
            # (``flush_relative_bias_sites`` writes it to the run's log)
            _RELATIVE_BIAS_SITES["diagonal" if impl == "flash" else "matrix"] += 1
        probs_dropout = (
            float(self.config.attn_dropout_rate) if not deterministic else 0.0
        )
        if impl == "ring":
            if probs_dropout > 0.0:
                raise ValueError(
                    "attn_dropout_rate > 0 is not supported on the ring "
                    "attention path; use attention_impl 'flash'/'xla'"
                )
            return ring_attention_sharded(
                q, k, v, bias, mesh=mesh, causal=causal_here, scale=1.0, dtype=self.dtype
            )
        if impl == "flash":
            seed = None
            if probs_dropout > 0.0:
                from distributed_llms_example_tpu.ops.fused_dropout import (
                    seed_from_key,
                )

                seed = seed_from_key(self.make_rng("dropout"))
            if relative_bias is not None:
                if mesh is not None and math.prod(mesh.devices.shape) > 1:
                    return flash_attention_lbias_sharded(
                        q, k, v, bias, relative_bias, mesh=mesh,
                        batch_axes=tuple(a for a in BATCH_AXES if a in mesh.shape),
                        head_axis="tensor" if "tensor" in mesh.shape else None,
                        causal=causal_here, scale=1.0, dtype=self.dtype,
                        dropout_rate=probs_dropout, dropout_seed=seed,
                    )
                return flash_attention(
                    q, k, v, bias, relative_bias=relative_bias,
                    causal=causal_here, scale=1.0, dtype=self.dtype,
                    dropout_rate=probs_dropout, dropout_seed=seed,
                )
            return flash_run(
                q, k, v, bias, causal=causal_here, mesh=mesh, dtype=self.dtype,
                scale=1.0, dropout_rate=probs_dropout, dropout_seed=seed,
            )
        if causal_here:
            step = make_causal_bias(q.shape[2], k.shape[2])
            bias = step if bias is None else bias + step
        if relative_bias is not None:
            learned = relative_bias_matrix(relative_bias, q.shape[2], k.shape[2]).astype(self.dtype)
            bias = learned if bias is None else bias + learned
        return dot_product_attention(
            q, k, v, bias, scale=1.0, dtype=self.dtype,
            dropout_rate=probs_dropout,
            dropout_rng=self.make_rng("dropout") if probs_dropout > 0.0 else None,
        )


class T5MLP(nn.Module):
    config: T5Config
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        cfg = self.config
        if cfg.is_gated:
            gate = nn.Dense(cfg.d_ff, use_bias=False, dtype=self.dtype, name="wi_0")(x)
            lin = nn.Dense(cfg.d_ff, use_bias=False, dtype=self.dtype, name="wi_1")(x)
            h = nn.gelu(gate, approximate=True) * lin
        else:
            h = nn.relu(nn.Dense(cfg.d_ff, use_bias=False, dtype=self.dtype, name="wi")(x))
        h = Dropout(cfg.dropout_rate)(h, deterministic)
        return nn.Dense(cfg.d_model, use_bias=False, dtype=self.dtype, name="wo")(h)


class T5Block(nn.Module):
    config: T5Config
    causal: bool = False
    has_cross: bool = False
    dtype: jnp.dtype = jnp.float32

    def setup(self) -> None:
        cfg = self.config
        eps = cfg.layer_norm_epsilon
        self.self_attn_norm = RMSNorm(epsilon=eps, dtype=self.dtype, name="self_attn_norm")
        self.self_attn = T5Attention(cfg, causal=self.causal, dtype=self.dtype, name="self_attn")
        if self.has_cross:
            self.cross_attn_norm = RMSNorm(epsilon=eps, dtype=self.dtype, name="cross_attn_norm")
            self.cross_attn = T5Attention(cfg, causal=False, dtype=self.dtype, name="cross_attn")
        self.mlp_norm = RMSNorm(epsilon=eps, dtype=self.dtype, name="mlp_norm")
        self.mlp = T5MLP(cfg, dtype=self.dtype, name="mlp")
        self.dropout = Dropout(cfg.dropout_rate)

    def __call__(
        self,
        hidden: jnp.ndarray,
        self_bias: jnp.ndarray | None,
        encoder_hidden: jnp.ndarray | None = None,
        cross_bias: jnp.ndarray | None = None,
        deterministic: bool = True,
        use_cache: bool = False,
        pos_bias: jnp.ndarray | None = None,
        cross_kv=None,
        cache_positions: jnp.ndarray | None = None,
    ) -> jnp.ndarray:
        # deterministic/use_cache are positional so nn.remat can mark them
        # static (argnums 5, 6 counting self at 0); pos_bias is the learned
        # relative-position bias (its per-diagonal vector) kept separate from
        # the (constant) mask in self_bias so the flash kernel can compute
        # its gradient
        h = self.self_attn(
            self.self_attn_norm(hidden), bias=self_bias, use_cache=use_cache,
            relative_bias=pos_bias, deterministic=deterministic,
            cache_positions=cache_positions,
        )
        # residual rides the dropout kernel (one fused pass on TPU)
        hidden = self.dropout(h, deterministic, residual=hidden)
        if self.has_cross:
            h = self.cross_attn(
                self.cross_attn_norm(hidden), kv_hidden=encoder_hidden,
                bias=cross_bias, cross_kv=cross_kv, deterministic=deterministic,
            )
            hidden = self.dropout(h, deterministic, residual=hidden)
        h = self.mlp(self.mlp_norm(hidden), deterministic=deterministic)
        return self.dropout(h, deterministic, residual=hidden)


class T5Stack(nn.Module):
    config: T5Config
    causal: bool = False  # True → decoder (causal self-attn + cross-attn)
    dtype: jnp.dtype = jnp.float32
    remat: bool = False
    remat_policy: str = "full"  # "full" | "dots" (utils/remat.py)

    def setup(self) -> None:
        cfg = self.config
        n = cfg.decoder_layers if self.causal else cfg.num_layers
        self.relative_attention_bias = nn.Embed(
            cfg.relative_attention_num_buckets,
            cfg.num_heads,
            dtype=jnp.float32,
            name="relative_attention_bias",
        )
        block = T5Block
        if self.remat:
            block = remat_block(T5Block, (5, 6), self.remat_policy)
        self.blocks = [
            block(cfg, causal=self.causal, has_cross=self.causal, dtype=self.dtype, name=f"block_{i}")
            for i in range(n)
        ]
        self.final_norm = RMSNorm(epsilon=cfg.layer_norm_epsilon, dtype=self.dtype, name="final_norm")
        self.dropout = Dropout(cfg.dropout_rate)

    def relative_bias(self, q_len: int, kv_len: int) -> jnp.ndarray:
        """The relative-position bias of an uncached call as what it is:
        (heads, q_len + kv_len - 1) fp32, one entry a diagonal — index
        ``(k - q) + q_len - 1`` holds ``table[bucket(k - q)]``, the bias of
        every (q, k) pair at that offset
        (``ops.flash_attention.relative_bias_matrix`` is the matrix).  A
        ``take`` over 2,047 offsets where the matrix's was one over 1,048,576
        pairs, and the table's gradient is that small take's transpose."""
        return relative_bias_vector(
            self.relative_attention_bias.embedding, self.config, q_len, kv_len,
            bidirectional=not self.causal,
        )

    def position_bias(self, q_len: int, kv_len: int, offset: int | jnp.ndarray = 0) -> jnp.ndarray:
        """(1, heads, q_len, kv_len) additive relative-position bias of a
        cached decode step (a constant there: no gradient).

        ``offset`` may be a (B,) array — per-ROW decode offsets for
        continuous-batching slots, yielding a (B, heads, q_len, kv_len)
        bias (each slot's relative positions computed against its own
        cache offset)."""
        cfg = self.config
        off = jnp.asarray(offset)
        if off.ndim == 1:
            q_pos = off[:, None, None] + jnp.arange(q_len)[None, :, None]  # (B, q, 1)
            rel = jnp.arange(kv_len)[None, None, :] - q_pos  # (B, q, kv)
        else:
            q_pos = jnp.arange(q_len)[:, None] + off
            rel = jnp.arange(kv_len)[None, :] - q_pos  # (q, kv)
        buckets = relative_position_bucket(
            rel,
            bidirectional=not self.causal,
            num_buckets=cfg.relative_attention_num_buckets,
            max_distance=cfg.relative_attention_max_distance,
        )
        bias = self.relative_attention_bias(buckets)  # (..., q, kv, heads)
        if off.ndim == 1:
            return bias.transpose(0, 3, 1, 2).astype(self.dtype)
        return bias.transpose(2, 0, 1)[None].astype(self.dtype)

    def __call__(
        self,
        hidden: jnp.ndarray,
        attention_mask: jnp.ndarray | None = None,
        encoder_hidden: jnp.ndarray | None = None,
        encoder_mask: jnp.ndarray | None = None,
        *,
        deterministic: bool = True,
        use_cache: bool = False,
        cache_offset: int | jnp.ndarray = 0,
        max_kv_len: int | None = None,
        cross_kv=None,
    ) -> jnp.ndarray:
        q_len = hidden.shape[1]
        pos_bias = None
        cache_positions = None
        if use_cache and self.causal:
            # Incremental decoding: relative bias of the current step(s)
            # against the full cache buffer (max_kv_len); masking of not-yet-
            # written cache slots + causality is added inside T5Attention.
            # The learned bias rides the combined constant-treated bias on
            # both decode impls (XLA merged mask, flash_decode additive
            # input) — no gradients in decode.  A (B,) ``cache_offset``
            # is the continuous-batching form: per-SLOT offsets, per-row
            # position bias and per-row cache writes.
            if max_kv_len is None:
                raise ValueError("max_kv_len is required when decoding with a cache")
            if getattr(jnp.asarray(cache_offset), "ndim", 0) == 1:
                cache_positions = jnp.asarray(cache_offset, jnp.int32)
            self_bias = self.position_bias(q_len, max_kv_len, offset=cache_offset)
        else:
            # keep the LEARNED bias separate from the constant mask:
            # T5Attention routes it through the flash kernel's
            # differentiable relative_bias input (causality is the
            # attention impl's job — flash applies it natively)
            pos_bias = self.relative_bias(q_len, q_len)
            self_bias = mask_to_bias(attention_mask) if attention_mask is not None else None
        cross_bias = mask_to_bias(encoder_mask) if encoder_mask is not None else None
        hidden = self.dropout(hidden, deterministic=deterministic)
        for i, blk in enumerate(self.blocks):
            # re-anchor the residual stream every layer so GSPMD never
            # propagates a param sharding (d_model over fsdp/tensor) into it
            hidden = constrain_hidden(
                blk(hidden, self_bias, encoder_hidden, cross_bias, deterministic, use_cache, pos_bias,
                    cross_kv=None if cross_kv is None else cross_kv[i],
                    cache_positions=cache_positions)
            )
        return self.dropout(self.final_norm(hidden), deterministic=deterministic)


class T5ForConditionalGeneration(nn.Module):
    """Full seq2seq model: encode + decode + LM head."""

    config: T5Config
    dtype: jnp.dtype = jnp.float32
    remat: bool = False
    remat_policy: str = "full"  # "full" | "dots" (utils/remat.py)

    def setup(self) -> None:
        cfg = self.config
        self.shared = nn.Embed(
            cfg.vocab_size,
            cfg.d_model,
            embedding_init=nn.initializers.normal(1.0),
            dtype=self.dtype,
            name="shared",
        )
        self.encoder = T5Stack(cfg, causal=False, dtype=self.dtype, remat=self.remat,
                               remat_policy=self.remat_policy, name="encoder")
        self.decoder = T5Stack(cfg, causal=True, dtype=self.dtype, remat=self.remat,
                               remat_policy=self.remat_policy, name="decoder")
        if not cfg.tie_word_embeddings:
            self.lm_head = nn.Dense(cfg.vocab_size, use_bias=False, dtype=self.dtype, name="lm_head")

    def encode(
        self, input_ids: jnp.ndarray, attention_mask: jnp.ndarray | None = None, *, deterministic: bool = True
    ) -> jnp.ndarray:
        return self.encoder(
            constrain_hidden(self.shared(input_ids)),
            attention_mask=attention_mask,
            deterministic=deterministic,
        )

    def _logits(self, hidden: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        if cfg.tie_word_embeddings:
            hidden = hidden * (cfg.d_model**-0.5)
            return constrain_logits(hidden @ self.shared.embedding.astype(self.dtype).T)
        return constrain_logits(self.lm_head(hidden))

    def cross_kv(self, encoder_hidden: jnp.ndarray):
        """Per-decoder-layer cross-attention K/V, projected ONCE from the
        encoder output (see BartForConditionalGeneration.cross_kv)."""
        return tuple(
            blk.cross_attn.project_kv(encoder_hidden) for blk in self.decoder.blocks
        )

    def decode(
        self,
        decoder_input_ids: jnp.ndarray,
        encoder_hidden: jnp.ndarray,
        encoder_mask: jnp.ndarray | None = None,
        decoder_attention_mask: jnp.ndarray | None = None,
        *,
        deterministic: bool = True,
        use_cache: bool = False,
        cache_offset: int | jnp.ndarray = 0,
        max_kv_len: int | None = None,
        cross_kv=None,
    ) -> jnp.ndarray:
        hidden = constrain_hidden(self.shared(decoder_input_ids))
        if use_cache:
            hidden = self.decoder(
                hidden,
                encoder_hidden=encoder_hidden,
                encoder_mask=encoder_mask,
                deterministic=deterministic,
                use_cache=True,
                cache_offset=cache_offset,
                max_kv_len=max_kv_len,
                cross_kv=cross_kv,
            )
        else:
            hidden = self.decoder(
                hidden,
                attention_mask=decoder_attention_mask,
                encoder_hidden=encoder_hidden,
                encoder_mask=encoder_mask,
                deterministic=deterministic,
            )
        return self._logits(hidden)

    def __call__(
        self,
        input_ids: jnp.ndarray,
        attention_mask: jnp.ndarray | None = None,
        decoder_input_ids: jnp.ndarray | None = None,
        decoder_attention_mask: jnp.ndarray | None = None,
        *,
        deterministic: bool = True,
    ) -> jnp.ndarray:
        _RELATIVE_BIAS_SITES.clear()  # sites of a stack traced alone before (encode, the pipelined adapter)
        enc = self.encode(input_ids, attention_mask, deterministic=deterministic)
        logits = self.decode(
            decoder_input_ids,
            enc,
            encoder_mask=attention_mask,
            decoder_attention_mask=decoder_attention_mask,
            deterministic=deterministic,
        )
        flush_relative_bias_sites()  # the whole forward is traced: say how its bias gradients are made
        return logits


def shift_right(labels: jnp.ndarray, decoder_start_token_id: int, pad_token_id: int) -> jnp.ndarray:
    """Teacher-forcing decoder inputs from labels (HF shift_tokens_right
    semantics: -100 label positions become pad)."""
    shifted = jnp.roll(labels, 1, axis=-1).at[:, 0].set(decoder_start_token_id)
    return jnp.where(shifted == -100, pad_token_id, shifted)


class PipelinedT5:
    """Train-time ``apply()`` adapter running both T5 stacks as GPipe
    pipelines over ``stage`` (parallel/pipeline.py; see ``PipelinedBart``
    for the twin-pipeline shape).  The learned relative-position bias is
    computed OUTSIDE the pipelines directly from each stack's bucket table
    — one (1, heads, q, kv) tensor per stack, entering the stage loop as a
    replicated per-call extra, so the bias table itself still receives
    gradient through the bucket lookup.  Param tree:
    ``stack_for_family("t5", ...)`` (each stack's blocks stacked under
    ``{encoder,decoder}/stacked_blocks``).  Dropout supported (key folded
    per microbatch/stage/layer, see PipelinedBart); training +
    teacher-forced scoring only.
    """

    def __init__(self, config: T5Config, mesh, dtype=jnp.float32,
                 num_microbatches: int = 0, remat: bool = True,
                 schedule: str = "gpipe"):
        if schedule not in ("gpipe", "1f1b", "interleaved"):
            raise ValueError(
                f"unknown pipeline schedule {schedule!r}: must be gpipe, "
                "1f1b, or interleaved"
            )
        # known-bad combos are rows in the composition matrix
        # (analysis/composition.py): 1f1b×fsdp partitioner crash,
        # interleaved (decoder-only), sequence parallelism
        from distributed_llms_example_tpu.analysis.composition import (
            validate_composition,
        )

        validate_composition(
            family="t5", schedule=schedule, mesh_axes=dict(mesh.shape),
            flags=("pipelined",),
        )
        stages = mesh.shape.get("stage", 1)
        for n, what in ((config.num_layers, "encoder"), (config.decoder_layers, "decoder")):
            if n % max(stages, 1):
                raise ValueError(f"{n} {what} layers not divisible into {stages} stages")
        self.config = config
        self.mesh = mesh
        self.dtype = dtype
        self.num_microbatches = num_microbatches or max(stages, 1)
        self.remat = remat
        self.pipeline_schedule = schedule
        cfg = config
        self._shared = nn.Embed(
            cfg.vocab_size, cfg.d_model, embedding_init=nn.initializers.normal(1.0), dtype=dtype
        )
        self._enc_block = T5Block(cfg, causal=False, has_cross=False, dtype=dtype)
        self._dec_block = T5Block(cfg, causal=True, has_cross=True, dtype=dtype)
        self._norm = RMSNorm(epsilon=cfg.layer_norm_epsilon, dtype=dtype)
        if not cfg.tie_word_embeddings:
            self._head = nn.Dense(cfg.vocab_size, use_bias=False, dtype=dtype)

    def _relative_bias(self, table: jnp.ndarray, q_len: int, causal: bool) -> jnp.ndarray:
        """(heads, 2 q - 1) per-diagonal bias from a stack's bucket table —
        the functional twin of T5Stack.relative_bias."""
        return relative_bias_vector(table, self.config, q_len, q_len, bidirectional=not causal)

    def _dropout(self, x, key):
        from distributed_llms_example_tpu.parallel.pipeline import dropout

        return dropout(x, key, self.config.dropout_rate)

    def make_value_and_grad(self, label_smoothing: float = 0.0,
                            is_seq2seq: bool = True):
        """Twin-pipeline 1F1B training path (see ``PipelinedBart`` for the
        shape).  T5's extra structure maps onto the fused executor's hooks:
        the encoder's final-norm + dropout become the SEAM transform
        (applied once per microbatch where the encoder output enters the
        decoder pipeline, differentiated for the norm scale's gradient);
        the learned relative-position biases ride ``diff_extras`` as their
        per-diagonal vectors — the executor accumulates their cotangents
        across every (chunk, microbatch) vjp, and the bucket tables get
        their gradients through an outer ``jax.vjp`` of the vectors'
        construction."""
        from distributed_llms_example_tpu.parallel.activation import activation_mesh
        from distributed_llms_example_tpu.parallel.pipeline_seq2seq import (
            pipeline_value_and_grad_seq2seq,
        )
        from distributed_llms_example_tpu.train.step import cross_entropy_sums

        assert is_seq2seq
        cfg = self.config

        def post_loss(pp, y, mb, key):
            # decoder tail: final_norm + dropout (T5Stack's trailing
            # dropout) + (tied-scaled) logits projection
            h = self._norm.apply({"params": pp["final_norm"]}, y["dec"])
            if key is not None:
                # post_loss runs INSIDE the pipeline shard_map: clear the
                # ambient mesh (like the block fns) so the shared dropout
                # helper takes its no-mesh XLA path instead of nesting a
                # shard_map in the manual region
                with activation_mesh(None):
                    h = self._dropout(h, jax.random.fold_in(key, 555))
            if cfg.tie_word_embeddings:
                h = h * (cfg.d_model**-0.5)
                logits = h @ pp["shared"]["embedding"].astype(self.dtype).T
            else:
                logits = self._head.apply({"params": pp["lm_head"]}, h)
            return cross_entropy_sums(logits, mb["labels"], label_smoothing)

        def seam(sp, h, key):
            # encoder tail between the pipelines: final_norm + dropout
            # (runs inside the pipeline shard_map — same ambient-mesh
            # reset as post_loss/the block fns)
            h = self._norm.apply({"params": sp["final_norm"]}, h)
            if key is not None:
                with activation_mesh(None):
                    h = self._dropout(h, key)
            return h

        def enc_fn(lp, h, ex, key=None):
            with activation_mesh(None):
                if key is None:
                    return self._enc_block.apply(
                        {"params": lp}, h, ex.get("src_bias"), None, None,
                        True, False, ex.get("enc_pos"),
                    )
                return self._enc_block.apply(
                    {"params": lp}, h, ex.get("src_bias"), None, None,
                    False, False, ex.get("enc_pos"), rngs={"dropout": key},
                )

        def dec_fn(lp, h, ex, key=None):
            with activation_mesh(None):
                if key is None:
                    return self._dec_block.apply(
                        {"params": lp}, h, None, ex["enc"], ex.get("src_bias"),
                        True, False, ex.get("dec_pos"),
                    )
                return self._dec_block.apply(
                    {"params": lp}, h, None, ex["enc"], ex.get("src_bias"),
                    False, False, ex.get("dec_pos"), rngs={"dropout": key},
                )

        def value_and_grad_sums(params, batch, rng=None):
            labels = batch["labels"]
            dec_ids = shift_right(labels, cfg.decoder_start_token_id, cfg.pad_token_id)

            def embed_all(shared_p):
                eh = constrain_hidden(
                    self._shared.apply({"params": shared_p}, batch["input_ids"])
                )
                dh = constrain_hidden(self._shared.apply({"params": shared_p}, dec_ids))
                # T5Stack applies dropout to the embedded input of each stack
                if rng is not None:
                    eh = self._dropout(eh, jax.random.fold_in(rng, 201))
                    dh = self._dropout(dh, jax.random.fold_in(rng, 202))
                return eh, dh

            (enc_h, dec_h), embed_vjp = jax.vjp(embed_all, params["shared"])

            def pos_biases(tables):
                et, dt = tables
                return (
                    self._relative_bias(et, batch["input_ids"].shape[1], causal=False),
                    self._relative_bias(dt, dec_ids.shape[1], causal=True),
                )

            (enc_pos, dec_pos), pos_vjp = jax.vjp(
                pos_biases,
                (
                    params["encoder"]["relative_attention_bias"]["embedding"],
                    params["decoder"]["relative_attention_bias"]["embedding"],
                ),
            )
            src_bias = (
                mask_to_bias(batch["attention_mask"])
                if batch.get("attention_mask") is not None else None
            )
            extras = {} if src_bias is None else {"src_bias": src_bias}
            post_params = {"final_norm": params["decoder"]["final_norm"]}
            if cfg.tie_word_embeddings:
                post_params["shared"] = params["shared"]
            else:
                post_params["lm_head"] = params["lm_head"]
            seam_params = {"final_norm": params["encoder"]["final_norm"]}
            (lsum, tokens, d_se, d_sd, d_pp, d_seam, d_dex, d_eh, d_dh) = (
                pipeline_value_and_grad_seq2seq(
                    enc_fn, dec_fn, post_loss,
                    params["encoder"]["stacked_blocks"],
                    params["decoder"]["stacked_blocks"],
                    post_params, enc_h, dec_h, extras, {"labels": labels},
                    mesh=self.mesh, num_microbatches=self.num_microbatches,
                    seam_fn=seam, seam_params=seam_params,
                    diff_extras={"enc_pos": enc_pos, "dec_pos": dec_pos},
                    checkpoint=self.remat,
                    rng=None if rng is None else jax.random.fold_in(rng, 7),
                )
            )
            (d_embed,) = embed_vjp((d_eh.astype(enc_h.dtype), d_dh.astype(dec_h.dtype)))
            ((d_enc_table, d_dec_table),) = pos_vjp(
                (d_dex["enc_pos"].astype(enc_pos.dtype), d_dex["dec_pos"].astype(dec_pos.dtype))
            )
            d_shared = d_embed
            if cfg.tie_word_embeddings:
                d_shared = jax.tree.map(jnp.add, d_shared, d_pp["shared"])
            grads = {
                "shared": d_shared,
                "encoder": {
                    "stacked_blocks": d_se,
                    "final_norm": d_seam["final_norm"],
                    "relative_attention_bias": {"embedding": d_enc_table},
                },
                "decoder": {
                    "stacked_blocks": d_sd,
                    "final_norm": d_pp["final_norm"],
                    "relative_attention_bias": {"embedding": d_dec_table},
                },
            }
            if not cfg.tie_word_embeddings:
                grads["lm_head"] = d_pp["lm_head"]
            return lsum, tokens, grads

        return value_and_grad_sums

    def _run_stack(self, stack_params, block, hidden, self_bias, pos_bias, extras,
                   rng=None):
        from distributed_llms_example_tpu.parallel.activation import activation_mesh
        from distributed_llms_example_tpu.parallel.pipeline import pipeline_apply

        ex = {k: v for k, v in extras.items() if v is not None}
        if self_bias is not None:
            ex["self_bias"] = self_bias
        # the LEARNED bias rides its own slot all the way into
        # T5Attention.relative_bias — pre-combining it into the constant
        # mask would zero its gradient on any flash-selected path.  A
        # leading 1: the executor splits an extra whose first axis is
        # the batch's size into microbatches, and heads can equal it
        ex["pos_bias"] = pos_bias[None]

        # T5Stack applies dropout on the embedded input and after the
        # final norm; mirror that around the pipeline
        if rng is not None:
            hidden = self._dropout(hidden, jax.random.fold_in(rng, 101))

        def layer_fn(lp, h, e, key=None):
            with activation_mesh(None):
                if key is None:
                    return block.apply(
                        {"params": lp}, h, e.get("self_bias"), e.get("enc"),
                        e.get("cross_bias"), True, False, e["pos_bias"][0],
                    )
                return block.apply(
                    {"params": lp}, h, e.get("self_bias"), e.get("enc"),
                    e.get("cross_bias"), False, False, e["pos_bias"][0],
                    rngs={"dropout": key},
                )

        hidden = pipeline_apply(
            layer_fn, stack_params["stacked_blocks"], hidden, ex,
            mesh=self.mesh, num_microbatches=self.num_microbatches, checkpoint=self.remat,
            rng=rng,
        )
        hidden = self._norm.apply({"params": stack_params["final_norm"]}, hidden)
        if rng is not None:
            hidden = self._dropout(hidden, jax.random.fold_in(rng, 102))
        return hidden

    def apply(self, variables, input_ids, attention_mask=None, decoder_input_ids=None,
              decoder_attention_mask=None, *, deterministic: bool = True, rngs=None):
        p = variables["params"]
        cfg = self.config
        rng = None
        if not deterministic and rngs and "dropout" in rngs and cfg.dropout_rate > 0:
            rng = rngs["dropout"]
        shared = lambda ids: constrain_hidden(  # noqa: E731
            self._shared.apply({"params": p["shared"]}, ids)
        )

        q_len = input_ids.shape[1]
        enc_table = p["encoder"]["relative_attention_bias"]["embedding"]
        enc_pos = self._relative_bias(enc_table, q_len, causal=False)
        enc_mask = mask_to_bias(attention_mask) if attention_mask is not None else None
        enc = self._run_stack(
            p["encoder"], self._enc_block, shared(input_ids), enc_mask, enc_pos, {},
            rng=None if rng is None else jax.random.fold_in(rng, 0),
        )

        d_len = decoder_input_ids.shape[1]
        dec_table = p["decoder"]["relative_attention_bias"]["embedding"]
        dec_pos = self._relative_bias(dec_table, d_len, causal=True)
        # causality is the attention impl's job (T5Block's decoder
        # self-attention has causal=True); only the padding mask goes in
        dec_mask = (
            mask_to_bias(decoder_attention_mask) if decoder_attention_mask is not None else None
        )
        cross_bias = mask_to_bias(attention_mask) if attention_mask is not None else None
        hidden = self._run_stack(
            p["decoder"], self._dec_block, shared(decoder_input_ids), dec_mask, dec_pos,
            {"enc": enc, "cross_bias": cross_bias},
            rng=None if rng is None else jax.random.fold_in(rng, 1),
        )
        if cfg.tie_word_embeddings:
            hidden = hidden * (cfg.d_model**-0.5)
            return constrain_logits(hidden @ p["shared"]["embedding"].astype(self.dtype).T)
        return constrain_logits(self._head.apply({"params": p["lm_head"]}, hidden))
