"""Ring attention: sequence/context parallelism over the ``sequence`` axis.

The reference has no long-context support at all — sequence length is a
fixed 1024/128 pad/truncate (reference train-accelerator.py:114-127) and
its parallelism is data-only (SURVEY.md §5 "Long-context/sequence
parallelism: absent").  This module goes past parity: it makes the
``sequence`` mesh axis a real execution path, so a sequence too long for
one chip's HBM can be sharded across chips and attention still computes
exact (non-approximate) softmax over the full length.

Design (TPU-first, not a port of any CUDA kernel):

- Q stays put; K/V (and any K-aligned padding bias) rotate around the ring
  of ``sequence``-axis neighbors via ``jax.lax.ppermute`` — ICI
  neighbor-to-neighbor traffic, the cheapest collective on a TPU torus.
- Each device folds one (q_block × kv_block) tile per step into a running
  online-softmax state (max ``m``, denominator ``l``, accumulator ``acc``
  — the same streaming-softmax algebra as the Pallas flash kernel in
  ``flash_attention.py``, here expressed in jnp so XLA fuses it and
  autodiff provides the backward pass).
- The next rotation is issued *before* the current tile's compute, so
  XLA's async scheduler overlaps collective-permute with the matmuls.
- With ``causal=True``, tiles strictly above the diagonal are skipped with
  a ``lax.cond`` (no MXU work, the rotation still proceeds), and the
  per-step state update is wrapped in ``jax.checkpoint`` so the backward
  pass recomputes score tiles instead of storing all of them: peak memory
  per device stays O(S_local · d + S_local · S_local) regardless of ring
  size.

Conventions match ``ops.attention``: q/k/v are (batch, heads, seq,
head_dim) — *local shards* inside ``shard_map`` for ``ring_attention``,
global arrays for ``ring_attention_sharded``.  ``bias`` must be K-only:
shape (batch|1, 1, 1, kv_len) additive (a ``mask_to_bias`` padding mask);
it is sharded and rotated along its last axis with K/V.  Learned biases
with a query dimension (T5's relative-position table) are not supported —
T5 keeps its own attention path.  Like the flash kernel, a K-only bias is
treated as a *mask*: it rides the ring as data, and its gradient is zero
by construction of the callers (padding masks are constants).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from distributed_llms_example_tpu.ops.attention import NEG_INF
from distributed_llms_example_tpu.parallel.activation import pvary_to


def _block_update(carry, q, k, v, bias_blk, q_pos, k_pos, *, scale: float, causal: bool,
                  compute_dtype=None):
    """Fold one (q_blk, kv_blk) attention tile into the running softmax state.

    ``q_pos``/``k_pos`` are *global* positions of the local rows / the
    currently-held (rotated) K block, so the causal mask is exact across
    shard boundaries.  fp32 throughout; the P·V matmul runs in the value
    dtype (bf16 on TPU) on the MXU, like the flash kernel.
    """
    m, l, acc = carry
    # q/k/v may ride the ring (and the causal lax.cond) in fp32
    # (plumb_fp32 below); the matmuls run in the compute dtype so the MXU
    # path is unchanged
    cd = compute_dtype or q.dtype
    q, k, v = q.astype(cd), k.astype(cd), v.astype(cd)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    if bias_blk is not None:
        s = s + bias_blk.astype(jnp.float32)
    if causal:
        s = jnp.where(q_pos[None, None, :, None] >= k_pos[None, None, None, :], s, NEG_INF)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_next = jnp.maximum(m, m_cur)
    alpha = jnp.exp(m - m_next)  # m starts at -inf, all masks are finite → no NaN
    p = jnp.exp(s - m_next)
    l_next = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v).astype(jnp.float32)
    acc_next = acc * alpha + pv
    return m_next, l_next, acc_next


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: jnp.ndarray | None = None,
    *,
    axis_name: str = "sequence",
    axis_size: int,
    causal: bool = False,
    scale: float | None = None,
    dtype: jnp.dtype | None = None,
    plumb_fp32: bool = False,
) -> jnp.ndarray:
    """Exact attention over a sequence sharded across ``axis_name``.

    Must run inside ``shard_map`` with the seq dim of q/k/v sharded over
    ``axis_name`` (``axis_size`` shards, equal blocks).  ``causal=True``
    requires equal global q/kv lengths (top-left alignment, as in
    ``flash_attention``).  ``bias`` is a K-only local block (batch|1, 1, 1,
    kv_blk).  Masking uses a finite NEG_INF, so a row whose keys are ALL
    masked yields a near-uniform average of V, not zeros — such rows are
    padding queries and the caller must loss-mask them (the train step's
    label mask does).

    ``plumb_fp32``: rotate K/V/bias around the ring in fp32 even when the
    compute dtype is bf16.  Needed inside PARTIAL-manual regions (the
    stage×sequence pipeline): the XLA SPMD partitioner miscompiles bf16
    copy chains there ("Invalid binary instruction opcode copy" — the same
    bug the pipeline plumbing works around, parallel/pipeline.py), and the
    transpose of a bf16 ``ppermute`` hits it in the backward pass.  The
    matmuls still run in the compute dtype (``_block_update`` casts back),
    so only ring-hop bandwidth is affected.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    b, h, q_blk, d = q.shape
    kv_blk = k.shape[2]
    n = axis_size
    idx = jax.lax.axis_index(axis_name)
    q_pos = idx * q_blk + jnp.arange(q_blk)
    m = jnp.full((b, h, q_blk, 1), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, h, q_blk, 1), jnp.float32)
    acc = jnp.zeros((b, h, q_blk, d), jnp.float32)
    # fresh zeros carry no varying-manual-axes provenance; inside a
    # check_vma region (the stage×sequence pipeline) the running state must
    # match q's vma or the causal lax.cond's branches disagree on types
    want = tuple(getattr(jax.typeof(q), "vma", frozenset()))
    m, l, acc = pvary_to((m, l, acc), want)

    compute_dtype = q.dtype
    update = jax.checkpoint(
        functools.partial(_block_update, scale=scale, causal=causal, compute_dtype=compute_dtype)
    )
    # each step sends the held K/V block to the left neighbor; after t steps
    # device i holds the block that started on device (i + t) mod n
    perm = [(i, (i - 1) % n) for i in range(n)]
    if plumb_fp32 and compute_dtype == jnp.bfloat16:
        q = q.astype(jnp.float32)
        k = k.astype(jnp.float32)
        v = v.astype(jnp.float32)
        bias = None if bias is None else bias.astype(jnp.float32)
    kv: Any = (k, v, bias)
    for t in range(n):
        # issue next rotation before this tile's compute → XLA overlaps the
        # collective-permute with the matmuls
        nxt = jax.tree.map(lambda x: jax.lax.ppermute(x, axis_name, perm), kv) if t < n - 1 else None
        cur_k, cur_v, cur_bias = kv
        src = jax.lax.rem(idx + t, n)
        k_pos = src * kv_blk + jnp.arange(kv_blk)
        if causal:
            # equal blocks ⇒ the tile is all-masked iff src > idx; skip its MXU work
            m, l, acc = jax.lax.cond(
                src <= idx,
                lambda ops: update(ops[:3], *ops[3:]),
                lambda ops: ops[:3],
                (m, l, acc, q, cur_k, cur_v, cur_bias, q_pos, k_pos),
            )
        else:
            m, l, acc = update((m, l, acc), q, cur_k, cur_v, cur_bias, q_pos, k_pos)
        if nxt is not None:
            kv = nxt
    # l >= 1 always: every device applies at least one update (causal skip
    # never drops the diagonal tile) and the running max makes the max
    # element contribute exp(0) = 1, so no division guard is needed
    out = acc / l
    return out.astype(dtype or compute_dtype)


def ring_attention_sharded(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: jnp.ndarray | None = None,
    *,
    mesh: Mesh,
    causal: bool = False,
    scale: float | None = None,
    dtype: jnp.dtype | None = None,
    batch_axes: tuple[str, ...] = ("data", "fsdp", "expert"),
    head_axis: str = "tensor",
    seq_axis: str = "sequence",
) -> jnp.ndarray:
    """Global-array front door: shard (batch over data×fsdp, heads over
    tensor, seq over sequence) and run the ring per-shard.

    Requires: seq dims divisible by the ``sequence`` axis size, batch by
    the batch shards, heads by ``tensor`` — callers gate on
    ``select_attention_impl`` (ops/mha.py), which falls back to XLA
    attention when any of these fail.
    """
    n = mesh.shape.get(seq_axis, 1)
    batch_axes = tuple(a for a in batch_axes if a in mesh.shape)
    head = head_axis if head_axis in mesh.shape else None
    qspec = P(batch_axes or None, head, seq_axis, None)
    args: list = [q, k, v]
    in_specs: list = [qspec, qspec, qspec]
    if bias is not None:
        if bias.shape[1] != 1 or bias.shape[2] != 1:
            raise ValueError(
                f"ring attention needs a K-only bias (b|1, 1, 1, K); got {bias.shape}"
            )
        in_specs.append(P((batch_axes or None) if bias.shape[0] != 1 else None, None, None, seq_axis))
        args.append(bias)

    def run(q, k, v, *rest):
        return ring_attention(
            q, k, v, rest[0] if rest else None,
            axis_name=seq_axis, axis_size=n, causal=causal, scale=scale, dtype=dtype,
        )

    return jax.shard_map(
        run, mesh=mesh, in_specs=tuple(in_specs), out_specs=qspec, check_vma=False
    )(*args)
