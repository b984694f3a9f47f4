"""Attention core shared by all model families.

Replaces what the reference consumes as opaque CUDA/cuDNN kernels inside
``model(**batch)`` (reference train-accelerator.py:220) with an explicit,
TPU-shaped computation: one batched einsum onto the MXU for QK^T, fp32
softmax, one einsum for the value contraction.  XLA fuses mask/bias/softmax
into the surrounding matmuls; a Pallas flash-attention kernel
(``ops/flash_attention.py``) is used for long sequences where materializing
the (S, S) score matrix would be HBM-bound.

Conventions: q/k/v are (batch, heads, q_len/kv_len, head_dim); ``bias`` is
additive, broadcastable to (batch, heads, q_len, kv_len) and already
encodes masking as large negative values.
"""

from __future__ import annotations

import jax.numpy as jnp

NEG_INF = -1e9  # large-negative mask value; safe in both fp32 and bf16


def dot_product_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: jnp.ndarray | None = None,
    *,
    scale: float | None = None,
    dtype: jnp.dtype | None = None,
    dropout_rate: float = 0.0,
    dropout_rng=None,
) -> jnp.ndarray:
    """Plain softmax attention.

    ``scale=None`` means 1/sqrt(head_dim); pass ``scale=1.0`` for T5, which
    folds the scale into initialization and does NOT scale scores.
    Softmax runs in float32 regardless of compute dtype.

    ``dropout_rate`` > 0 (with a ``dropout_rng`` key) applies inverted
    dropout to the attention probs — the XLA reference semantics for the
    flash kernel's in-kernel probs dropout.  This path DOES materialize
    the (B, H, Q, K) mask (that is exactly the cost the fused kernel
    removes); it exists for parity and for shapes the kernel rejects.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    dtype = dtype or q.dtype
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    scores = scores * scale
    if bias is not None:
        scores = scores + bias.astype(jnp.float32)
    probs = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    if dropout_rate > 0.0 and dropout_rng is not None:
        import jax

        keep_prob = 1.0 - dropout_rate
        keep = jax.random.bernoulli(dropout_rng, keep_prob, probs.shape)
        probs = jnp.where(keep, probs / keep_prob, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(dtype), v)


def grouped_dot_product_attention(
    q5: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: jnp.ndarray | None = None,
    *,
    scale: float | None = None,
    dtype: jnp.dtype | None = None,
) -> jnp.ndarray:
    """``dot_product_attention`` with a beam/group dim folded next to heads.

    ``q5``: (B, G, H, Q, d) attends SHARED ``k``/``v``: (B, H, K, d) —
    the einsum contracts without materializing the (B·G, H, K, d) repeat,
    so K/V stream from HBM once per row instead of once per beam copy
    (the dominant decode-step traffic for seq2seq generation, where every
    beam of a row shares the encoder's cross K/V).  Same math per element
    as ``dot_product_attention`` on repeated K/V: fp32 scores/softmax,
    identical scale/bias conventions; ``bias`` is (B|1, 1|H, Q, K) —
    per-row, like K/V, never per-beam (beams of a row share the mask)."""
    if scale is None:
        scale = q5.shape[-1] ** -0.5
    dtype = dtype or q5.dtype
    scores = jnp.einsum("bghqd,bhkd->bghqk", q5, k, preferred_element_type=jnp.float32)
    scores = scores * scale
    if bias is not None:
        scores = scores + bias.astype(jnp.float32)[:, None]
    probs = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    return jnp.einsum("bghqk,bhkd->bghqd", probs.astype(dtype), v)


def beam_grouped_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: jnp.ndarray | None = None,
    *,
    scale: float | None = None,
    dtype: jnp.dtype | None = None,
) -> jnp.ndarray:
    """Beam-decode front end for ``grouped_dot_product_attention``: the ONE
    home for the fold/slice/unfold convention both attention modules use.

    ``q``: (B·G, H, Q, d) flattened beam batch; ``k``/``v``: (B, H, K, d)
    shared per row.  A per-beam ``bias`` (leading dim B·G) is stride-
    sliced to one row per group (beams of a row share their mask).
    Returns (B·G, H, Q, d)."""
    B = k.shape[0]
    G = q.shape[0] // B
    H, Q, d = q.shape[1], q.shape[2], q.shape[3]
    bb = None
    if bias is not None:
        bb = bias if bias.shape[0] in (1, B) else bias[::G]
    out = grouped_dot_product_attention(
        q.reshape(B, G, H, Q, d), k, v, bb, scale=scale, dtype=dtype
    )
    return out.reshape(B * G, H, Q, d)


def make_causal_bias(q_len: int, kv_len: int, offset: int = 0) -> jnp.ndarray:
    """(1, 1, q_len, kv_len) additive causal mask; ``offset`` is the absolute
    position of query 0 (for incremental decoding with a KV cache)."""
    q_pos = jnp.arange(q_len)[:, None] + offset
    kv_pos = jnp.arange(kv_len)[None, :]
    mask = q_pos >= kv_pos
    return jnp.where(mask, 0.0, NEG_INF)[None, None, :, :]


def make_band_bias(q_len: int, kv_len: int, window: int) -> jnp.ndarray:
    """(1, 1, q_len, kv_len) additive sliding-window mask: query i reads keys j
    with ``0 <= i - j < window`` (causal, and the window counts the query)."""
    delta = jnp.arange(q_len)[:, None] - jnp.arange(kv_len)[None, :]
    return jnp.where((delta >= 0) & (delta < window), 0.0, NEG_INF)[None, None, :, :]


def mask_to_bias(attention_mask: jnp.ndarray) -> jnp.ndarray:
    """(batch, kv_len) {0,1} padding mask → (batch, 1, 1, kv_len) additive bias."""
    return jnp.where(attention_mask[:, None, None, :] > 0, 0.0, NEG_INF)
