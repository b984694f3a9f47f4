"""Pallas TPU flash attention (blockwise online-softmax), fwd + bwd.

The reference consumes attention as opaque CUDA/cuDNN kernels inside every
``model(**batch)`` call (reference train-accelerator.py:220); on TPU the
analogous hot op is this kernel: the (S, S) score matrix is never
materialized in HBM — Q/K/V tiles stream HBM→VMEM, QK^T and PV run on the
MXU per (block_q, block_k) tile, and the softmax is computed online with
running max/denominator carried in VMEM scratch across the kv grid axis.

Layout/conventions
  - q, k, v: (batch, heads, seq, head_dim); output matches q.
  - ``bias`` is additive, fp32-convertible, with every dim either 1 or the
    full size — e.g. a (B, 1, 1, K) padding mask from
    ``ops.attention.mask_to_bias``.  Size-1 dims are handled in the
    BlockSpec index maps, so the bias is never broadcast in HBM.
  - ``relative_bias`` is a second additive bias that DOES receive a
    gradient — T5's relative-position bias — handed over as what it is: a
    (H, Q + K - 1) vector of per-DIAGONAL values, entry ``(k - q) + Q - 1``
    being the bias of every (q, k) pair at that offset.  The forward, dq
    and dkv kernels read the (1, H, Q, K) matrix XLA lays out from it
    (``relative_bias_matrix``); a third backward kernel accumulates
    dbias = p·(dp − δ) tile-by-tile with batch as the innermost
    (sequential) grid axis and writes each tile's sums ALONG ITS DIAGONALS
    in fp32, so neither the (B, H, Q, K) un-reduced gradient nor the
    (1, H, Q, K) reduced one is ever materialized in HBM: the vector's
    cotangent is an overlap-add of a few hundred KB.
  - ``causal=True`` applies the triangular mask inside the kernel (and
    skips fully-masked kv tiles); don't also encode causality in ``bias``.
  - The backward pass treats ``bias`` as a constant (zero gradient) —
    padding/causal masks only; the learned relative bias goes through
    ``relative_bias``.
  - Softmax statistics (running max ``m``, denominator ``l``) live in
    (block_q, 128) fp32 scratch — TPU vector layout wants a full 128-lane
    last dim — and the logsumexp residual is saved as (B, H, S, 128) with
    the value replicated across lanes (same layout the backward kernels
    read it in).

Grid semantics: the kv axis is the innermost ("arbitrary") grid dimension,
so scratch accumulators persist across kv steps for a fixed (b, h, q-tile);
batch/heads/q-tiles are "parallel".

On CPU (tests, the 8-device virtual mesh) the kernel runs in Pallas
interpret mode; numerics are checked against ``dot_product_attention``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_llms_example_tpu.ops.fused_dropout import tile_keep

LANES = 128  # TPU vector lane count: last-dim unit for scratch/statistics
MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)

# --------------------------------------------------------- probs dropout
#
# Attention-probs dropout rides INSIDE the kernels: the keep-mask for a
# (block_q, block_k) tile is drawn in-kernel from (seed, b, h, tile
# offsets) via ops.fused_dropout.tile_keep (TPU hardware PRNG compiled,
# counter hash in interpret mode), so the (B, H, S, S) mask never exists
# in HBM and the backward kernels recompute the identical mask from the
# same seed instead of saving it.  Math: with p-tilde the unnormalized
# softmax numerator and l its row sum, the forward accumulates
# pv from m·p-tilde/keep while l stays un-dropped — o = acc/l is then
# exactly dropout(softmax(s)) @ v.  Backward: with dp = do·vT,
# ds = p · (m·dp/keep − delta) and dv sums (m·p/keep)T·do, where
# delta = rowsum(do∘o) already equals Σ_j pd_j dp_j.
#
# Dropout seeding is per-(b, h, q-tile, k-tile), so forward and all three
# backward kernels agree as long as they tile identically — they share
# block_q/block_k by construction.


def _tile_dropout_keep(seed_ref, b, h, qi, ki, shape, *, rate: float,
                       block_q: int, block_k: int, hw_rng: bool):
    return tile_keep(
        seed_ref[0], b, h, qi * block_q, ki * block_k, shape, rate, hw_rng
    )


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _bias_spec(bias_shape, block_q: int, block_k: int, kv_tile=None):
    """BlockSpec for an additive bias whose dims are each 1 or full-size.
    ``kv_tile(qi, ki)``: the kv tile a grid step reads where that is not
    ``ki`` itself (the window flavour walks a band)."""
    b1, h1, q1, k1 = (d == 1 for d in bias_shape)
    block = (1, 1, 1 if q1 else block_q, bias_shape[3] if k1 else block_k)

    def index_map(b, h, qi, ki):
        kt = ki if kv_tile is None else kv_tile(qi, ki)
        return (0 if b1 else b, 0 if h1 else h, 0 if q1 else qi, 0 if k1 else kt)

    return pl.BlockSpec(block, index_map)


def _causal_mask(s, qi, ki, block_q: int, block_k: int):
    # -inf, not a large finite value: a finite mask score would dominate
    # m_next for rows whose every VALID key is -inf-bias-masked, making the
    # forward average v over causally-forbidden positions.  The online
    # softmax handles -inf via safe_m (fwd) and the lse sentinel (bwd).
    # NOTE the exact-zero/zero-grad guarantee for fully-masked rows holds
    # only for true -inf biases; a finite large-negative padding bias
    # (ops/attention.py NEG_INF = -1e9, chosen because the XLA softmax path
    # NaNs on all--inf rows) leaves an all-padded row as a garbage-but-
    # finite uniform average — identical to the XLA path's behavior, and
    # unreachable from the data pipeline (every example carries ≥1 real
    # token, so no all-masked rows exist in training).
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(q_pos >= k_pos, s, -jnp.inf)


def _band_tiles(q_len: int, block_q: int, block_k: int, window: int) -> int:
    """kv tiles a q tile's band can touch: query i reads keys j with
    ``0 <= i - j < window``, so q tile ``qi`` reads keys ``qi * block_q -
    (window - 1) .. (qi + 1) * block_q - 1``; the most tiles any q tile's
    range meets (its first q tiles meet fewer)."""
    most = 1
    for qi in range(q_len // block_q):
        first = max(qi * block_q - (window - 1), 0) // block_k
        last = ((qi + 1) * block_q - 1) // block_k
        most = max(most, last - first + 1)
    return most


def _band_tile(qi, ki, block_q: int, block_k: int, nkb: int):
    """The kv tile step ``ki`` of ``nkb`` reads for q tile ``qi`` in the window
    flavour: the band's tiles end at the diagonal's, so they are counted back
    from it; below zero there is none (the step is skipped, its block index
    clamped to the first tile so that no other block is fetched for it)."""
    return ((qi + 1) * block_q - 1) // block_k - (nkb - 1) + ki


def _window_mask(s, qi, kt, block_q: int, block_k: int, window: int):
    """``_causal_mask`` with the band's far edge: key j of query i survives
    where ``0 <= i - j < window`` (the window counts the query itself)."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = kt * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where((q_pos >= k_pos) & (q_pos - k_pos < window), s, -jnp.inf)


# ---------------------------------------------------------------- forward


def _fwd_kernel(
    *refs, scale: float, causal: bool, block_q: int, block_k: int, nk: int,
    has_bias: bool, has_lbias: bool, dropout_rate: float = 0.0,
    hw_rng: bool = False, window: int = 0,
):
    """``window`` > 0 is the sliding-window flavour: the kv grid axis walks
    the ``nk`` tiles of the q tile's band (``_band_tile``), not the whole
    sequence, so a key tile wholly outside the band is never visited."""
    it = iter(refs)
    seed_ref = next(it) if dropout_rate > 0.0 else None
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    bias_ref = next(it) if has_bias else None
    lbias_ref = next(it) if has_lbias else None
    o_ref, lse_ref, m_scr, l_scr, acc_scr = it
    # grid ids at kernel TOP LEVEL: the interpret-mode lowering only
    # rewrites program_id in the outer kernel jaxpr, not inside pl.when
    bi, hi = pl.program_id(0), pl.program_id(1)
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    # with causal masking, tiles strictly above the diagonal contribute nothing
    diag_ok = (qi + 1) * block_q > ki * block_k if causal else True
    kt = ki
    if window:
        # the band's tiles, counted back from the diagonal's: one before the
        # sequence's first, or wholly before the band's far edge, has nothing
        kt = _band_tile(qi, ki, block_q, block_k, nk)
        diag_ok = (kt >= 0) & ((kt + 1) * block_k > qi * block_q - (window - 1))

    @pl.when(diag_ok)
    def _compute():
        q = q_ref[0, 0]  # (block_q, d)
        k = k_ref[0, 0]  # (block_k, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s *= scale
        if bias_ref is not None:
            s += bias_ref[0, 0].astype(jnp.float32)
        if lbias_ref is not None:
            s += lbias_ref[0, 0].astype(jnp.float32)
        if window:
            s = _window_mask(s, qi, kt, block_q, block_k, window)
        elif causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)

        m_prev = m_scr[:, :1]  # (block_q, 1)
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_cur)
        # a row can still be all -inf here (every key masked by a -inf
        # bias): -inf - -inf = NaN would poison alpha/p, so substitute a
        # finite max — exp(-inf - 0) = 0 then zeroes those entries, l
        # stays 0, and _finish's sentinel takes over
        safe_m = jnp.where(m_next == -jnp.inf, 0.0, m_next)
        alpha = jnp.exp(m_prev - safe_m)
        p = jnp.exp(s - safe_m)  # (block_q, block_k)
        l_next = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[:] = jax.lax.broadcast_in_dim(m_next[:, 0], m_scr.shape, (0,))
        l_scr[:] = jax.lax.broadcast_in_dim(l_next[:, 0], l_scr.shape, (0,))
        if seed_ref is not None:
            # drop AFTER l accumulates: l normalizes the un-dropped
            # softmax, the dropped numerator rides only the value product
            keep = _tile_dropout_keep(
                seed_ref, bi, hi, qi, ki,
                p.shape, rate=dropout_rate, block_q=block_q,
                block_k=block_k, hw_rng=hw_rng,
            )
            p = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[:] = acc_scr[:] * alpha + pv

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows → zeros, not NaN
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse = m_scr[:] + jnp.log(jnp.where(l_scr[:] == 0.0, 1.0, l_scr[:]))
        lse_ref[0, 0] = jnp.where(l_scr[:] == 0.0, MASK_VALUE, lse)


def _seed_arg(dropout_seed):
    """(args, specs) prefix carrying the dropout seed into a kernel."""
    if dropout_seed is None:
        return [], []
    seed = jnp.asarray(dropout_seed, jnp.int32).reshape(1)
    return [seed], [pl.BlockSpec(memory_space=pltpu.SMEM)]


def _fwd(q, k, v, bias, lbias, *, scale, causal, block_q, block_k, interpret,
         dropout_rate=0.0, dropout_seed=None, hw_rng=False, window=0, name=None):
    batch, heads, q_len, d = q.shape
    kv_len = k.shape[2]
    nq, nk = q_len // block_q, kv_len // block_k
    kv_tile = None
    if window:
        # the kv axis of the grid is the band, not the sequence
        nk = _band_tiles(q_len, block_q, block_k, window)
        kv_tile = lambda qi, ki: jnp.maximum(_band_tile(qi, ki, block_q, block_k, nk), 0)  # noqa: E731
    grid = (batch, heads, nq, nk)

    def q_map(b, h, qi, ki):
        return (b, h, qi, 0)

    def kv_map(b, h, qi, ki):
        return (b, h, ki if kv_tile is None else kv_tile(qi, ki), 0)

    seed_args, in_specs = _seed_arg(dropout_seed if dropout_rate > 0.0 else None)
    in_specs += [
        pl.BlockSpec((1, 1, block_q, d), q_map),
        pl.BlockSpec((1, 1, block_k, d), kv_map),
        pl.BlockSpec((1, 1, block_k, d), kv_map),
    ]
    if bias is not None:
        in_specs.append(_bias_spec(bias.shape, block_q, block_k, kv_tile))
    if lbias is not None:
        in_specs.append(_bias_spec(lbias.shape, block_q, block_k))
    out_shape = [
        jax.ShapeDtypeStruct(q.shape, q.dtype),
        jax.ShapeDtypeStruct((batch, heads, q_len, LANES), jnp.float32),
    ]
    out_specs = [
        pl.BlockSpec((1, 1, block_q, d), q_map),
        pl.BlockSpec((1, 1, block_q, LANES), q_map),
    ]
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, nk=nk,
        has_bias=bias is not None, has_lbias=lbias is not None,
        dropout_rate=dropout_rate, hw_rng=hw_rng, window=window,
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=name,
    )(*seed_args, *[x for x in (q, k, v, bias, lbias) if x is not None])
    return o, lse


# --------------------------------------------------------------- backward


def _bwd_dq_kernel(
    *refs, scale: float, causal: bool, block_q: int, block_k: int, nk: int,
    has_bias: bool, has_lbias: bool, dropout_rate: float = 0.0,
    hw_rng: bool = False,
):
    it = iter(refs)
    seed_ref = next(it) if dropout_rate > 0.0 else None
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    bias_ref = next(it) if has_bias else None
    lbias_ref = next(it) if has_lbias else None
    do_ref, lse_ref, delta_ref, dq_ref, dq_scr = it
    bi, hi = pl.program_id(0), pl.program_id(1)
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros(dq_scr.shape, jnp.float32)

    diag_ok = (qi + 1) * block_q > ki * block_k if causal else True

    @pl.when(diag_ok)
    def _compute():
        q, kk, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        do = do_ref[0, 0]
        s = jax.lax.dot_general(
            q, kk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s *= scale
        if bias_ref is not None:
            s += bias_ref[0, 0].astype(jnp.float32)
        if lbias_ref is not None:
            s += lbias_ref[0, 0].astype(jnp.float32)
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)
        lse = lse_ref[0, 0][:, :1]
        p = jnp.exp(s - lse)  # (block_q, block_k)
        # fully-masked rows save lse = MASK_VALUE (sentinel, fwd kernel):
        # exp(s - sentinel) is garbage there (overflows to inf when any s
        # is finite), and inf·0 = NaN would poison the gradient — zero
        # those rows explicitly
        p = jnp.where(lse <= MASK_VALUE / 2, 0.0, p)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if seed_ref is not None:
            # recompute the forward's keep-mask from the seed: the dropped
            # entries' dp never reaches ds (d(dropout)/d(p) = m/keep)
            keep = _tile_dropout_keep(
                seed_ref, bi, hi, qi, ki,
                p.shape, rate=dropout_rate, block_q=block_q,
                block_k=block_k, hw_rng=hw_rng,
            )
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_rate)), 0.0)
        ds = p * (dp - delta_ref[0, 0][:, :1]) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(kk.dtype), kk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    *refs, scale: float, causal: bool, block_q: int, block_k: int, nq: int,
    has_bias: bool, has_lbias: bool, dropout_rate: float = 0.0,
    hw_rng: bool = False,
):
    it = iter(refs)
    seed_ref = next(it) if dropout_rate > 0.0 else None
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    bias_ref = next(it) if has_bias else None
    lbias_ref = next(it) if has_lbias else None
    do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr = it
    bi, hi = pl.program_id(0), pl.program_id(1)
    ki, qi = pl.program_id(2), pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[:] = jnp.zeros(dv_scr.shape, jnp.float32)

    diag_ok = (qi + 1) * block_q > ki * block_k if causal else True

    @pl.when(diag_ok)
    def _compute():
        q, kk, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        do = do_ref[0, 0]
        s = jax.lax.dot_general(
            q, kk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s *= scale
        if bias_ref is not None:
            s += bias_ref[0, 0].astype(jnp.float32)
        if lbias_ref is not None:
            s += lbias_ref[0, 0].astype(jnp.float32)
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)
        lse = lse_ref[0, 0][:, :1]
        p = jnp.exp(s - lse)
        # zero fully-masked rows (lse == MASK_VALUE sentinel) — see dq kernel
        p = jnp.where(lse <= MASK_VALUE / 2, 0.0, p)
        keep = None
        if seed_ref is not None:
            keep = _tile_dropout_keep(
                seed_ref, bi, hi, qi, ki,
                p.shape, rate=dropout_rate, block_q=block_q,
                block_k=block_k, hw_rng=hw_rng,
            )
        # dv sums the DROPPED probs (only kept entries fed the forward pv)
        pd = p if keep is None else jnp.where(
            keep, p * (1.0 / (1.0 - dropout_rate)), 0.0
        )
        dv_scr[:] += jax.lax.dot_general(
            pd.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if keep is not None:
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_rate)), 0.0)
        ds = p * (dp - delta_ref[0, 0][:, :1]) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def relative_bias_matrix(rel: jnp.ndarray, q_len: int, kv_len: int) -> jnp.ndarray:
    """The (1, H, Q, K) bias a (H, Q + K - 1) per-diagonal vector stands for:
    ``out[0, h, q, k] = rel[h, (k - q) + q_len - 1]``.  Built from static
    slices alone, never a gather: a strip of ``rows`` query rows is ``rows``
    shifted views of the vector, and a strip ``f`` times as tall is ``f``
    shifted views of that strip, so 1,024 rows take 8 + 16 + 8 slices, the
    last level's at whole 128-lane offsets."""
    strip, rows = rel[:, None, :], 1
    for factor in _row_factors(q_len):
        width = strip.shape[-1] - rows * (factor - 1)
        strip = jnp.concatenate(
            [strip[:, :, rows * (factor - 1 - i):rows * (factor - 1 - i) + width] for i in range(factor)],
            axis=1,
        )
        rows *= factor
    assert strip.shape[1:] == (q_len, kv_len), (strip.shape, q_len, kv_len)
    return strip[None]


def _row_factors(n: int) -> list[int]:
    """``n`` as a product of strip heights: the sublane tile, then 128-row
    blocks, then whatever is left (1,024 = 8 x 16 x 8)."""
    factors = []
    for f in (8, 16):
        if n % f == 0 and n > f:
            factors.append(f)
            n //= f
    return factors + [n]


def _diagonal_width(block_q: int, block_k: int) -> int:
    """Lanes a tile's diagonal sums are written in: whole ``block_k``-wide
    segments that hold the tile's ``block_q + block_k - 1`` diagonals, eight
    sublanes still apart (``_tile_diagonal_sums``); at least ``block_q +
    block_k``."""
    return ((block_q - 8) // block_k + 2) * block_k


def _tile_diagonal_sums(tile_ref, block_q: int, block_k: int):
    """A (block_q, block_k) fp32 tile summed along its diagonals, as far as
    whole sublane groups go: (8, ``_diagonal_width``) with
    ``out[r, c] = Σ_g tile[8g + r, c - (block_q - 8 - 8g)]``, so tile
    diagonal ``d = (k - q) + block_q - 1`` lies at ``out[r, d - 7 + r]`` and
    the last eight-way sum, a different shift a sublane, is XLA's
    (``_overlap_add``: the output is a few KB).  Row group ``g`` wants a lane
    shift of ``block_q - 8 - 8g``.  The tile is taken a slab of 128 rows at a
    time, (16 groups, 8, block_k): a slab's shift is whole vregs, so the slab
    is an address in the (16, 8, width) sum of slabs, and group ``p`` of every
    slab wants the same ``8 (15 - p)`` lanes more — sixteen rotates of
    (8, width) in all, no mask (nothing wraps: width >= block_q + block_k),
    and a few dozen operations to lower where one a vreg was 2,000 a call
    site and 37 s of every run's set-up.  A tile that is no whole slab, or
    narrower than a vreg's lanes (interpret mode, toy shapes), is taken in
    slabs as tall as divide it and placed by as many lanes."""
    rows = math.gcd(block_q, LANES) if block_k % LANES == 0 else 8
    groups, slabs = rows // 8, block_q // rows
    width = _diagonal_width(block_q, block_k)
    acc = None
    for s in range(slabs):
        slab = tile_ref[rows * s:rows * (s + 1), :].reshape(groups, 8, block_k)
        left = rows * (slabs - 1 - s)
        pads = [jnp.zeros((groups, 8, n), jnp.float32) for n in (left, width - block_k - left)]
        placed = jnp.concatenate([x for x in (pads[0], slab, pads[1]) if x.shape[2]], axis=2)
        acc = placed if acc is None else acc + placed
    total = None
    for p in range(groups):
        part, shift = acc[p], 8 * (groups - 1 - p)
        if shift:
            part = pltpu.roll(part, shift, 1)
        total = part if total is None else total + part
    return total


def _bwd_drel_kernel(
    *refs, scale: float, causal: bool, block_q: int, block_k: int, nb: int,
    has_bias: bool, dropout_rate: float = 0.0, hw_rng: bool = False,
):
    """Gradient of the relative bias: dbias = Σ_batch p·(dp−δ), handed back
    as sums along diagonals (every (q, k) pair of one offset reads the same
    entry of the vector, so that is its whole gradient).

    Grid is (heads, q-tiles, k-tiles, batch) with batch innermost and
    "arbitrary", so the (block_q, block_k) scratch accumulates the batch
    reduction across grid steps and neither the un-reduced (B, H, Q, K)
    gradient nor the reduced (1, H, Q, K) one ever exists in HBM: the last
    batch step reduces the tile where it lies (``_tile_diagonal_sums``).
    Recomputes s/p per tile from the residuals (same trade the dq/dkv
    kernels make)."""
    it = iter(refs)
    seed_ref = next(it) if dropout_rate > 0.0 else None
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    bias_ref = next(it) if has_bias else None
    lbias_ref, do_ref, lse_ref, delta_ref, drel_ref, dlb_scr = it
    hi = pl.program_id(0)
    qi, ki, bi = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(bi == 0)
    def _init():
        dlb_scr[:] = jnp.zeros(dlb_scr.shape, jnp.float32)

    diag_ok = (qi + 1) * block_q > ki * block_k if causal else True

    @pl.when(diag_ok)
    def _compute():
        q, kk, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        do = do_ref[0, 0]
        s = jax.lax.dot_general(
            q, kk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s *= scale
        if bias_ref is not None:
            s += bias_ref[0, 0].astype(jnp.float32)
        s += lbias_ref[0, 0].astype(jnp.float32)
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)
        # masked entries in a live row have s = -inf → p is exactly 0;
        # FULLY-masked rows save lse = MASK_VALUE (sentinel), so exp(s -
        # lse) is garbage there — zero those rows explicitly
        lse = lse_ref[0, 0][:, :1]
        p = jnp.exp(s - lse)
        p = jnp.where(lse <= MASK_VALUE / 2, 0.0, p)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if seed_ref is not None:
            # grid here is (heads, q, k, batch): tags stay (b, h)
            keep = _tile_dropout_keep(
                seed_ref, bi, hi, qi, ki,
                p.shape, rate=dropout_rate, block_q=block_q,
                block_k=block_k, hw_rng=hw_rng,
            )
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_rate)), 0.0)
        # ∂s/∂lbias = 1 (no scale factor — scale multiplies only q·k)
        dlb_scr[:] += p * (dp - delta_ref[0, 0][:, :1])

    @pl.when(bi == nb - 1)
    def _finish():
        # a causal tile above the diagonal never computed: its zeros sum to zeros
        drel_ref[0, 0, 0] = _tile_diagonal_sums(dlb_scr, block_q, block_k)


def _overlap_add(sums, q_len: int, kv_len: int, block_q: int, block_k: int, causal: bool):
    """(H, nq, nk, 8, ``_diagonal_width``) tile sums -> the (H, Q + K - 1)
    per-diagonal gradient: the eight sublanes' last shift-and-add
    (``_tile_diagonal_sums``), then every tile's ``block_q + block_k - 1``
    diagonals added in at the tile's place (tiles whose offsets coincide,
    as those of one block diagonal do where the tile is square, first)."""
    nq, nk = sums.shape[1:3]
    span = block_q + block_k - 1
    flat = jnp.pad(sums, [(0, 0)] * 4 + [(7, 0)])
    tiles = sum(flat[..., r, r:r + span] for r in range(8))  # (H, nq, nk, span)
    by_start: dict[int, list] = {}
    for qi in range(nq):
        for ki in range(nk):
            if causal and (qi + 1) * block_q <= ki * block_k:
                continue  # never computed: zeros
            start = ki * block_k - qi * block_q + q_len - block_q
            by_start.setdefault(start, []).append(tiles[:, qi, ki])
    # pads and adds (one fusion), not scatters into a zero vector
    end = q_len + kv_len - 1 - span
    return sum(jnp.pad(sum(parts), ((0, 0), (start, end - start))) for start, parts in by_start.items())


def _bwd_drel(q, k, v, bias, lbias, lse, delta, do, *, scale, causal,
              block_q, block_k, interpret,
              dropout_rate=0.0, dropout_seed=None, hw_rng=False):
    batch, heads, q_len, d = q.shape
    kv_len = k.shape[2]
    nq, nk = q_len // block_q, kv_len // block_k
    grid = (heads, nq, nk, batch)
    width = _diagonal_width(block_q, block_k)

    def q_map(h, qi, ki, b):
        return (b, h, qi, 0)

    def kv_map(h, qi, ki, b):
        return (b, h, ki, 0)

    def lb_map(h, qi, ki, b):
        return (0, h, qi, ki)

    bias_spec = None
    if bias is not None:
        inner = _bias_spec(bias.shape, block_q, block_k)

        def reordered(h, qi, ki, b):
            return inner.index_map(b, h, qi, ki)

        bias_spec = pl.BlockSpec(inner.block_shape, reordered)
    seed_args, in_specs = _seed_arg(dropout_seed if dropout_rate > 0.0 else None)
    in_specs += [
        spec
        for spec in (
            pl.BlockSpec((1, 1, block_q, d), q_map),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
            bias_spec,
            pl.BlockSpec((1, 1, block_q, block_k), lb_map),
            pl.BlockSpec((1, 1, block_q, d), q_map),
            pl.BlockSpec((1, 1, block_q, LANES), q_map),
            pl.BlockSpec((1, 1, block_q, LANES), q_map),
        )
        if spec is not None
    ]
    args = seed_args + [
        x for x in (q, k, v, bias, lbias, do, lse, delta) if x is not None
    ]
    sums = pl.pallas_call(
        functools.partial(
            _bwd_drel_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, nb=batch, has_bias=bias is not None,
            dropout_rate=dropout_rate, hw_rng=hw_rng,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, 1, 8, width), lambda h, qi, ki, b: (h, qi, ki, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((heads, nq, nk, 8, width), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_q, block_k), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*args)
    return _overlap_add(sums, q_len, kv_len, block_q, block_k, causal)


def _bwd(q, k, v, bias, lbias, o, lse, do, *, scale, causal, block_q, block_k,
         interpret, dropout_rate=0.0, dropout_seed=None, hw_rng=False):
    """(dq, dk, dv, drel): ``lbias`` is the relative bias as the kernels read
    it, the (1, H, Q, K) matrix of its per-diagonal vector (or None);
    ``drel`` is that VECTOR's gradient, fp32."""
    batch, heads, q_len, d = q.shape
    kv_len = k.shape[2]
    nq, nk = q_len // block_q, kv_len // block_k

    # delta_i = rowsum(dO ∘ O): tiny elementwise reduce, leave it to XLA
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = jax.lax.broadcast_in_dim(
        delta, (batch, heads, q_len, LANES), (0, 1, 2)
    )

    def q_map(b, h, qi, ki):
        return (b, h, qi, 0)

    def kv_map_q(b, h, qi, ki):
        return (b, h, ki, 0)

    bias_spec = _bias_spec(bias.shape, block_q, block_k) if bias is not None else None
    lbias_spec = _bias_spec(lbias.shape, block_q, block_k) if lbias is not None else None
    seed_args, seed_specs = _seed_arg(dropout_seed if dropout_rate > 0.0 else None)
    common_in = seed_specs + [
        spec
        for spec in (
            pl.BlockSpec((1, 1, block_q, d), q_map),
            pl.BlockSpec((1, 1, block_k, d), kv_map_q),
            pl.BlockSpec((1, 1, block_k, d), kv_map_q),
            bias_spec,
            lbias_spec,
            pl.BlockSpec((1, 1, block_q, d), q_map),
            pl.BlockSpec((1, 1, block_q, LANES), q_map),
            pl.BlockSpec((1, 1, block_q, LANES), q_map),
        )
        if spec is not None
    ]
    args = seed_args + [
        x for x in (q, k, v, bias, lbias, do, lse, delta) if x is not None
    ]

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, nk=nk,
            has_bias=bias is not None, has_lbias=lbias is not None,
            dropout_rate=dropout_rate, hw_rng=hw_rng,
        ),
        grid=(batch, heads, nq, nk),
        in_specs=common_in,
        out_specs=pl.BlockSpec((1, 1, block_q, d), q_map),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*args)

    # dk/dv: kv tiles are the outer (parallel) axis, q tiles the inner
    def q_map_kv(b, h, ki, qi):
        return (b, h, qi, 0)

    def kv_map_kv(b, h, ki, qi):
        return (b, h, ki, 0)

    def _swap_spec(x):
        if x is None:
            return None
        inner = _bias_spec(x.shape, block_q, block_k)

        def swapped(b, h, ki, qi):
            return inner.index_map(b, h, qi, ki)

        return pl.BlockSpec(inner.block_shape, swapped)

    dkv_in = seed_specs + [
        spec
        for spec in (
            pl.BlockSpec((1, 1, block_q, d), q_map_kv),
            pl.BlockSpec((1, 1, block_k, d), kv_map_kv),
            pl.BlockSpec((1, 1, block_k, d), kv_map_kv),
            _swap_spec(bias),
            _swap_spec(lbias),
            pl.BlockSpec((1, 1, block_q, d), q_map_kv),
            pl.BlockSpec((1, 1, block_q, LANES), q_map_kv),
            pl.BlockSpec((1, 1, block_q, LANES), q_map_kv),
        )
        if spec is not None
    ]
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, nq=nq,
            has_bias=bias is not None, has_lbias=lbias is not None,
            dropout_rate=dropout_rate, hw_rng=hw_rng,
        ),
        grid=(batch, heads, nk, nq),
        in_specs=dkv_in,
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), kv_map_kv),
            pl.BlockSpec((1, 1, block_k, d), kv_map_kv),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*args)
    drel = None
    if lbias is not None:
        drel = _bwd_drel(
            q, k, v, bias, lbias, lse, delta, do,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            interpret=interpret, dropout_rate=dropout_rate,
            dropout_seed=dropout_seed, hw_rng=hw_rng,
        )
    return dq, dk, dv, drel


# ------------------------------------------------------------- public API


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11, 12)
)
def _flash(q, k, v, bias, rel, dropout_seed,
           scale, causal, block_q, block_k, interpret, dropout_rate, hw_rng):
    return _flash_fwd(q, k, v, bias, rel, dropout_seed, scale, causal,
                      block_q, block_k, interpret, dropout_rate, hw_rng)[0]


def _flash_fwd(q, k, v, bias, rel, dropout_seed,
               scale, causal, block_q, block_k, interpret, dropout_rate, hw_rng):
    # the kernels read the relative bias as the matrix XLA lays out from its
    # vector; every attention site of a stack lays out the same one from the
    # same operand, which XLA merges into one (t5-large's compiled step
    # holds one layout a microbatch and stack, not 24), so the residual each
    # site keeps for its backward is that one buffer
    lbias = None if rel is None else relative_bias_matrix(rel.astype(q.dtype), q.shape[2], k.shape[2])
    o, lse = _fwd(
        q, k, v, bias, lbias, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
        dropout_rate=dropout_rate, dropout_seed=dropout_seed, hw_rng=hw_rng,
    )
    # the kernel replicates lse across all 128 lanes — keep one lane as the
    # residual so HBM between fwd and bwd holds (B,H,S,1), not (B,H,S,128).
    # The dropout mask is NOT a residual: the backward kernels redraw it
    # from the seed — zero extra bytes for probs dropout.
    return o, (q, k, v, bias, lbias, dropout_seed, o, lse[..., :1])


def _flash_bwd(scale, causal, block_q, block_k, interpret, dropout_rate,
               hw_rng, res, do):
    q, k, v, bias, lbias, dropout_seed, o, lse_lane = res
    lse = jax.lax.broadcast_in_dim(
        lse_lane[..., 0], (*lse_lane.shape[:-1], LANES), (0, 1, 2)
    )
    dq, dk, dv, drel = _bwd(
        q, k, v, bias, lbias, o, lse, do, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
        dropout_rate=dropout_rate, dropout_seed=dropout_seed, hw_rng=hw_rng,
    )
    dbias = None if bias is None else jnp.zeros_like(bias)  # bias is a mask
    return dq, dk, dv, dbias, drel, None  # seed: int, no cotangent


_flash.defvjp(_flash_fwd, _flash_bwd)


MAX_BLOCK = 512  # measured on v5e: 512-tiles run the fwd+bwd ~2.5x faster
#                  than 128-tiles at (16, 16, 1024, 64) — bigger tiles
#                  amortize grid overhead and keep the MXU busier, and a
#                  512x512 fp32 score tile + operands is still ~1.5 MB VMEM

MAX_BLOCK_NONCAUSAL = 1024  # v5e sweep at (16, 16, 1024, 64) fwd+bwd:
#                  non-causal 1024x1024 = 70.0 ms vs 512x512 = 74.6 ms
#                  (~6% — fewer grid steps, same VMEM class: 4 MB score
#                  tile).  CAUSAL at head_dim 64 stays at 512: the
#                  tile-skip guard works per-block, so 1024-tiles waste
#                  half of each diagonal block on masked work (74.5 ms vs
#                  71.0 at 512).  The relative-bias path caps block_q at
#                  512 but block_k at 1024 (71.1 ms vs 73.9 at 512x512):
#                  its backward carries the (1, H, Q, K) bias tile +
#                  dbias accumulator on top of the plain path's scratch,
#                  and 1024x1024 overflows the 16 MB VMEM stack (measured
#                  18.07 MB on v5e).

MAX_BLOCK_CAUSAL_WIDE = 1024  # v5e sweep at the 7B regime (4/8, 32,
#                  1024, 128) fwd+bwd: causal 1024x1024 = 3.48/4.97 ms vs
#                  512x512 = 4.16/6.58 ms (batch 4/8) — at head_dim 128
#                  the wider tiles' extra MXU occupancy beats the diagonal
#                  blocks' masked-work waste that dominates at d=64, so
#                  the causal cap is head_dim-dependent.


MAX_BLOCK_WINDOW = 1024  # v5e at (1, 32, 8192, 128), window 1,024 (PR 37): tiles of 1,024
#                  2.61 ms (2 tiles a q tile: 2,048 keys visited for the ~1,536 of
#                  its band), 512 3.73 ms (3 tiles), 256 6.73 ms, 1024 x 512 4.33 ms:
#                  as on the causal flavour at head_dim 128 (6.44 ms at 1,024, 12.03 at
#                  512), the wider tile's MXU occupancy beats its masked work


def _block_caps(causal: bool, has_learned_bias: bool,
                head_dim: int = 64, window: int = 0) -> tuple[int, int]:
    """(cap_q, cap_k) for the given attention flavor — see the constants'
    comments for the v5e measurements behind each choice.  The relative-
    bias cap applies even when causal: its backward's bias tile + dbias
    accumulator overflow VMEM at 1024×1024 regardless of masking (and
    tiles only grow with head_dim).  The window flavour's tiles are capped
    at ``MAX_BLOCK_WINDOW`` and at the window itself."""
    if window:
        cap = min(MAX_BLOCK_WINDOW, max(window, 128))
        return cap, cap
    if has_learned_bias:
        return MAX_BLOCK, MAX_BLOCK_NONCAUSAL
    if causal:
        cap = MAX_BLOCK_CAUSAL_WIDE if head_dim >= 128 else MAX_BLOCK
        return cap, cap
    return MAX_BLOCK_NONCAUSAL, MAX_BLOCK_NONCAUSAL


def auto_block(seq_len: int, cap: int = MAX_BLOCK) -> int:
    """Default tile size when the caller doesn't pin one (0 = not tileable,
    callers fall back to XLA attention).

    Largest 16-aligned block in [128, cap] dividing ``seq_len`` — 16 is the
    bf16 sublane tiling (8 would satisfy fp32 only), and below 128 the
    kv×q grid overhead beats the XLA path the kernel replaces.  Sequences
    shorter than 128 use one seq-sized tile when 16-aligned."""
    if seq_len < 128:
        return seq_len if seq_len >= 16 and seq_len % 16 == 0 else 0
    start = min(cap, seq_len) // 16 * 16
    for b in range(start, 127, -16):
        if seq_len % b == 0:
            return b
    return 0


def decode_block(kv_len: int) -> int:
    """The kv tile of a cached decode step.  A bias or length-mask block ends
    on the tile (its lane axis), and Mosaic takes a lane block only in
    multiples of 128 or whole: so the largest 128-aligned tile dividing the
    cache where there is one (1280 = 1024 + 256 gives 256, where
    ``auto_block``'s 16-aligned 320 does not compile), else ``auto_block``'s.
    Every power-of-two cache gets the tile it had."""
    for b in range(min(MAX_BLOCK, kv_len) // 128 * 128, 127, -128):
        if kv_len % b == 0:
            return b
    return auto_block(kv_len)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: jnp.ndarray | None = None,
    *,
    relative_bias: jnp.ndarray | None = None,
    causal: bool = False,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    dtype: jnp.dtype | None = None,
    dropout_rate: float = 0.0,
    dropout_seed: jax.Array | None = None,
    hw_rng: bool | None = None,
) -> jnp.ndarray:
    """Blockwise-softmax attention; drop-in for ``dot_product_attention``.

    ``block_q``/``block_k`` default to ``auto_block``: the largest
    16-aligned tile dividing each sequence length, capped per attention
    flavor (512 causal, 512/1024 relative-bias, 1024 otherwise — see
    ``_block_caps``; one seq-sized tile for short sequences).  Each seq
    len must divide by its (auto-clamped) block size — the framework's
    bucketed batching guarantees this for training shapes; call
    ``flash_supported`` first for arbitrary shapes.

    Contract notes (both enforced or documented because this is a public
    drop-in API, not just an internal kernel):

    - ``bias`` is treated as a CONSTANT mask: its gradient is zero.  Do not
      route a *learned* additive bias through it — that bias would silently
      stop training.  T5's learned bias goes through ``relative_bias``.
    - ``relative_bias`` must be exactly (heads, q_len + kv_len - 1): the
      bias of every (q, k) pair at offset ``k - q``, at index ``(k - q) +
      q_len - 1`` (``relative_bias_matrix`` is the (1, H, Q, K) matrix it
      stands for).  It is differentiable: the backward pass runs a third
      kernel that accumulates the bias gradient over the batch grid axis
      and reduces each tile along its diagonals in VMEM, so the vector's
      cotangent comes back in fp32 and no (1, H, Q, K) gradient is
      written, let alone a (B, H, Q, K) one.
    - ``causal=True`` requires ``q_len == kv_len``.  The mask is top-left
      aligned (q_pos >= k_pos with no kv offset), which is only meaningful
      for square self-attention; decode-style bottom-right alignment with
      cached keys is the KV-cache path's job, not this kernel's.
    - ``dropout_rate`` > 0 applies attention-PROBS dropout inside the
      kernel: the keep-mask is drawn in-kernel from ``dropout_seed`` (an
      int32 scalar, e.g. ``ops.fused_dropout.seed_from_key``) — the
      (B, H, Q, K) mask never materializes in HBM and the backward
      recomputes it from the same seed instead of saving it.  ``hw_rng``
      picks the TPU hardware PRNG (default on compiled TPU) vs the
      portable counter hash (interpret mode / tests).
    """
    if causal and q.shape[2] != k.shape[2]:
        raise ValueError(
            f"causal=True requires square self-attention, got q_len={q.shape[2]} "
            f"!= kv_len={k.shape[2]} (the mask is top-left aligned; a causal "
            "prefix over cached keys needs the KV-cache path instead)"
        )
    if scale is None:
        scale = q.shape[-1] ** -0.5
    cap_q, cap_k = _block_caps(causal, relative_bias is not None, q.shape[-1])
    block_q = auto_block(q.shape[2], cap_q) if block_q is None else min(block_q, q.shape[2])
    block_k = auto_block(k.shape[2], cap_k) if block_k is None else min(block_k, k.shape[2])
    if (
        not block_q
        or not block_k
        or q.shape[2] % block_q
        or k.shape[2] % block_k
        or block_q % 8
        or block_k % 8
    ):
        raise ValueError(
            f"seq lens {q.shape[2]}/{k.shape[2]} not divisible into 8-aligned "
            f"blocks {block_q}/{block_k}"
        )
    if bias is not None:
        for i, (bd, full) in enumerate(
            zip(bias.shape, (q.shape[0], q.shape[1], q.shape[2], k.shape[2]))
        ):
            if bd not in (1, full):
                raise ValueError(f"bias dim {i} is {bd}, must be 1 or {full}")
    if relative_bias is not None:
        want = (q.shape[1], q.shape[2] + k.shape[2] - 1)
        if tuple(relative_bias.shape) != want:
            raise ValueError(
                f"relative_bias shape {tuple(relative_bias.shape)} must be exactly "
                f"{want}: (heads, q_len + kv_len - 1), one entry a diagonal"
            )
        relative_bias = relative_bias.astype(jnp.float32)  # its cotangent comes back in fp32
    if interpret is None:
        interpret = _default_interpret()
    if hw_rng is None:
        hw_rng = not interpret
    dropout_rate = float(dropout_rate)
    if dropout_rate > 0.0:
        if not dropout_rate < 1.0:
            raise ValueError(f"dropout_rate must be in [0, 1), got {dropout_rate}")
        if dropout_seed is None:
            raise ValueError("dropout_rate > 0 requires a dropout_seed scalar")
        dropout_seed = jnp.asarray(dropout_seed, jnp.int32).reshape(())
    else:
        dropout_seed = None
    out = _flash(q, k, v, bias, relative_bias, dropout_seed,
                 float(scale), bool(causal), int(block_q), int(block_k),
                 bool(interpret), dropout_rate, bool(hw_rng))
    return out if dtype is None else out.astype(dtype)


def flash_supported(q_len: int, kv_len: int, head_dim: int,
                    block_q: int | None = None, block_k: int | None = None,
                    *, causal: bool = False,
                    has_learned_bias: bool = False, window: int = 0) -> bool:
    """True when shapes are flash-eligible (divisible seqs, sane head_dim).
    ``None`` blocks mirror ``flash_attention``'s ``auto_block`` defaults,
    including its per-path block caps (``_block_caps``) — pass ``causal``/
    ``has_learned_bias`` as the eventual kernel call will, or a length only
    tileable above 512 (e.g. 592 = 16*37) would be reported eligible for a
    path whose cap rejects it."""
    cap_q, cap_k = _block_caps(causal, has_learned_bias, head_dim, window)
    bq = auto_block(q_len, cap_q) if block_q is None else min(block_q, q_len)
    bk = auto_block(kv_len, cap_k) if block_k is None else min(block_k, kv_len)
    return (
        bq > 0
        and bk > 0
        and q_len % bq == 0
        and kv_len % bk == 0
        and bq % 8 == 0  # TPU sublane alignment
        and bk % 8 == 0
        and head_dim % 8 == 0
    )


PROMPT_ATTN = "prompt_attn"  # a cached prompt's attention in a device trace


def flash_prompt_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: jnp.ndarray | None = None,
    *,
    window: int | None = None,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    dtype: jnp.dtype | None = None,
) -> jnp.ndarray:
    """A cached PROMPT's attention (``ops/mha.py``: the prefill of a causal
    model): the forward kernel alone, causal, square, its ``T`` new tokens
    against each other while the cache is written beside.  ``bias``: the keys'
    padding mask, every dim 1 or full.  FORWARD ONLY: no vjp is defined.

    ``window``: sliding-window attention, query i reads keys j with ``0 <= i -
    j < window`` (the window counts the query).  The kv axis of the grid is
    then the BAND of the q tile (``_band_tile``), so a key tile wholly outside
    it is neither fetched nor computed: at 8,192 tokens and a window of 1,024
    two tiles of 1,024 a q tile, not eight.

    The custom call is ``prompt_attn`` in a device trace, whatever its call
    site (``_prompt_call``: not inlined, the kernel named)."""
    if q.shape[2] != k.shape[2] or k.shape != v.shape:
        raise ValueError(f"a prompt attends itself: q {q.shape}, k {k.shape}, v {v.shape}")
    if window is not None and window < 1:
        raise ValueError(f"window={window}: at least the query itself")
    cap_q, cap_k = _block_caps(True, False, q.shape[-1], window or 0)
    block_q = auto_block(q.shape[2], cap_q) if block_q is None else min(block_q, q.shape[2])
    block_k = auto_block(k.shape[2], cap_k) if block_k is None else min(block_k, k.shape[2])
    if not block_q or not block_k or q.shape[2] % block_q or k.shape[2] % block_k or block_q % 8 or block_k % 8:
        raise ValueError(f"prompt of {q.shape[2]} tokens not divisible into 8-aligned blocks {block_q}/{block_k}")
    if bias is not None:
        _check_decode_bias(bias, q.shape[0], q.shape[1], q.shape[2], k.shape[2])
    out = _prompt_call(
        q, k, v, bias, scale=float(q.shape[-1] ** -0.5 if scale is None else scale), window=int(window or 0),
        block_q=int(block_q), block_k=int(block_k),
        interpret=bool(_default_interpret() if interpret is None else interpret),
    )
    return out if dtype is None else out.astype(dtype)


@functools.partial(jax.jit, static_argnames=("scale", "window", "block_q", "block_k", "interpret"))
def _prompt_call(q, k, v, bias, *, scale: float, window: int, block_q: int, block_k: int, interpret: bool):
    return _fwd(q, k, v, bias, None, scale=scale, causal=True, block_q=block_q, block_k=block_k,
                interpret=interpret, window=window, name=PROMPT_ATTN)[0]


# ------------------------------------------------------- decode variant
#
# The four kernels above are the TRAINING shapes: square (or prefill-
# rectangular) attention where q tiles stream against kv tiles and a
# backward pass exists.  Serving's hot op is different: ONE query row per
# sequence (the token being decoded) against a full-length cached K/V
# buffer of which only the first ``offset+1`` slots are live.  The decode
# kernel reuses the same online-softmax block machinery with three
# changes: the whole (tiny) q block rides every grid step, validity is a
# per-ROW length mask (k_pos <= offset[b] + q_row, bottom-right aligned —
# exactly the alignment the training kernel's top-left causal mask cannot
# express), and kv tiles entirely beyond the longest live row are SKIPPED
# via a dynamic pl.when, so a step early in the decode reads ~offset/L of
# the cache instead of all of it.  Inference only: no vjp.
#
# int8 KV (--kv-cache-dtype int8): the cache buffers arrive as s8 with
# per-head per-position f32 scales (quantize_kv below — THE owning
# quantize/dequantize implementation, guarded by repo_lint rule 10).
# The kernel dequantizes each (block_k, heads x d) tile in VMEM right after the
# DMA, so HBM traffic and cache footprint are both s8 while the MXU math
# stays f32 — the XLA fallback path dequantizes through the identical
# dequantize_kv expression, which is what keeps the two paths
# token-comparable.


def quantize_kv(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-head per-position int8 quantization of a K/V tensor.

    ``x``: (..., len, head_dim) — one scale per (..., position): the
    head_dim row written at one cache slot shares one scale, so a cache
    write (one row per slot per step) quantizes independently of every
    other position and nothing ever needs requantizing.  Deterministic
    round-to-nearest (decode parity wants bit-stable values, not the
    unbiased stochastic rounding gradients need).  Returns ``(q, scale)``
    with ``q`` int8 shaped like ``x`` and ``scale`` f32 with the head_dim
    axis dropped."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.where(amax > 0.0, amax / 127.0, 1.0)
    q = jnp.round(x.astype(jnp.float32) / scale[..., None])
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Inverse of ``quantize_kv`` — the ONE dequantize expression both the
    Pallas decode kernel (per tile, in VMEM) and the XLA fallback path
    (whole buffer) evaluate, so their reconstructed K/V are identical."""
    return q.astype(jnp.float32) * scale[..., None]


# K and V blocks of one grid step, double-buffered by the pipeline, and the q
# block, the output and the fp32 accumulator beside them: half of the 16 MB a
# kernel may hold in VMEM by default; the rest is bias, statistics and the
# step's own temporaries
DECODE_STEP_VMEM_BUDGET = 8 * 2**20


def _vmem_tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """Bytes a (rows, cols) block takes in VMEM: lanes pad to 128, sublanes
    to 8 words of 32 bits (16 bf16 rows, 32 int8 rows)."""
    sub = 8 * (4 // itemsize)
    return -(-rows // sub) * sub * -(-cols // LANES) * LANES * itemsize


def decode_step_heads(
    heads: int, block_k: int, head_dim: int, itemsize: int,
    *, q_len: int = 1, int8_scales: bool = False,
) -> int:
    """Heads of a cache slot that one grid step of ``flash_decode`` covers,
    from what the call's shapes say.

    A K/V tile is (block_k, heads x head_dim): the heads lie side by side on
    the lanes, as the cache leaf keeps them.  All of them when K and V tiles
    (double-buffered) and the block-diagonal q block with its fp32
    accumulator fit ``DECODE_STEP_VMEM_BUDGET``, else the largest divisor of
    ``heads`` that does and whose lanes are whole vregs (a multiple of 128:
    the chip takes a lane block only so, or whole).  Under ``int8_scales``
    the tiles count as the f32 the step dequantizes them to, and the (B, L,
    H) scales ride every step whole, padded to 128 lanes (their heads lie on
    the lanes, where a part of them is no block: the step picks its group's
    out of all, ``_head_lanes``).  Nothing fitting is an error here, not a
    Mosaic failure far from its cause; ``decode_step_fits`` asks first."""
    def fits(h: int) -> bool:
        lanes, rows = h * head_dim, h * q_len
        wide = 4 if int8_scales else itemsize  # q is never s8: count it as the f32 it may be
        kv = 2 * 2 * _vmem_tile_bytes(block_k, lanes, wide)
        scales = 2 * 2 * _vmem_tile_bytes(block_k, heads, 4) if int8_scales else 0
        q_acc = 2 * _vmem_tile_bytes(rows, lanes, wide) + _vmem_tile_bytes(rows, lanes, 4)
        return kv + scales + q_acc <= DECODE_STEP_VMEM_BUDGET

    groups = [
        h for h in range(heads, 0, -1)
        if heads % h == 0 and (h == heads or (h * head_dim) % LANES == 0) and fits(h)
    ]
    if not groups:
        raise ValueError(
            f"flash_decode: {heads} heads of {head_dim}, kv tile {block_k}, "
            f"{q_len} q rows{', int8 K/V (tiles counted as f32)' if int8_scales else ''}: "
            f"no group of heads with whole 128-lane tiles fits the step's VMEM "
            f"budget ({DECODE_STEP_VMEM_BUDGET} bytes); pass a smaller block_k "
            "or take the XLA path"
        )
    return groups[0]


def decode_step_fits(
    heads: int, kv_len: int, head_dim: int, itemsize: int,
    *, q_len: int = 1, int8_scales: bool = False,
) -> bool:
    """Whether ``flash_decode`` finds a group of heads for this cache at its
    own kv tile (``decode_block``): what ``select_decode_impl`` asks before
    it sends a step to the kernel, so that a shape no group fits takes XLA's
    path and does not raise while the program is traced."""
    try:
        decode_step_heads(
            heads, decode_block(kv_len), head_dim, itemsize,
            q_len=q_len, int8_scales=int8_scales,
        )
    except ValueError:
        return False
    return True


def decode_q_rows(q: jnp.ndarray, step_heads: int) -> jnp.ndarray:
    """The decode kernel's q operand: (B, H, Q, d) query rows laid
    block-diagonally over the cache leaf's merged (heads x head_dim) axis,
    a group of ``step_heads`` heads at a time: (B, H / step_heads,
    step_heads x Q, step_heads x d), row (h, r) holding q[h, r] on head h's
    lanes and zeros on the others'.  One product of it against a (block_k,
    step_heads x d) K tile is then every head's scores, each from its own
    lanes, with no tile relaid per head; the zeros cost multiply-adds the
    MXU has to spare at a handful of rows (a K tile is its stationary
    operand either way)."""
    b, heads, q_len, d = q.shape
    g = heads // step_heads
    eye = jnp.eye(step_heads, dtype=q.dtype)[None, None, :, None, :, None]
    rows = q.reshape(b, g, step_heads, q_len, 1, d) * eye
    return rows.reshape(b, g, step_heads * q_len, step_heads * d)


def _decode_bias_rows(bias, heads: int, q_len: int, step_heads: int):
    """A (b|1, h|1, q|1, k|1) additive bias as the kernel's rows see it:
    (b|1, H / step_heads | 1, step_heads x Q | 1, k|1), row (h, r) of a head
    group the bias of head h, q row r."""
    b, h, q, k = bias.shape
    if h == 1 and q == 1:
        return bias
    full = jnp.broadcast_to(bias, (b, heads, q_len, k))
    return full.reshape(b, heads // step_heads, step_heads * q_len, k)


def _head_lanes(x, head_dim: int, heads: int, first_head):
    """(n, H) per-head values of which a step holds ``heads``, from
    ``first_head`` on, spread over the step's merged axis: (n, heads x
    head_dim), each repeated over its head's lanes.  A product with a 0/1
    selection at the highest precision, which is exact (one term a result)
    and is the lane broadcast the chip's compiler takes at any head_dim;
    the selection is also how a group of heads is taken out of all (the
    scales' lanes are no block of their own)."""
    shape = (x.shape[-1], heads * head_dim)
    sel = (
        jax.lax.broadcasted_iota(jnp.int32, shape, 1) // head_dim + first_head
        == jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    ).astype(x.dtype)
    return jax.lax.dot_general(
        x, sel, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )


def _decode_tile(q, k, v, k_scale, v_scale, bias, valid, m_prev, l_prev, acc,
                 *, scale: float, head_dim: int, first_head=0):
    """One kv tile's online-softmax update for a group of heads — the
    arithmetic ``_decode_kernel`` and ``_decode_paged_kernel`` share, which
    is what keeps them bit-identical at equal tiling.

    ``q``: (h x q_len, h x d), block-diagonal (``decode_q_rows``);
    ``k``/``v``: (block_k, h x d), the tile as the cache leaf holds it, s8
    with ``k_scale``/``v_scale`` (block_k, H) under int8 KV, the scales of
    all H heads of the slot, of which this group starts at ``first_head``;
    ``bias``: None
    or broadcastable to (h x q_len, block_k); ``valid``: (h x q_len,
    block_k) bool, the per-row length mask; ``m_prev``/``l_prev``: (h x
    q_len, 1) running max and denominator, ``acc``: (h x q_len, h x d), of
    which row (h, r) means its own head's lanes (the others hold p of head h
    times V of another head, finite and never read).  The heads are one
    product: sixteen one-row products a slot are latency, not work (v5e,
    PR 26: 0.90 ms batched against 1.91 ms as a loop of 2-D products).
    Returns the new (m, l, acc)."""
    if k_scale is not None:
        # dequantize the tile in VMEM: HBM moved 1 byte/elem, the MXU sees
        # f32 — ``dequantize_kv``'s expression with the scale on its lanes
        heads = k.shape[-1] // head_dim
        k = k.astype(jnp.float32) * _head_lanes(k_scale, head_dim, heads, first_head)
        v = v.astype(jnp.float32) * _head_lanes(v_scale, head_dim, heads, first_head)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    s *= scale
    if bias is not None:
        s += bias.astype(jnp.float32)
    s = jnp.where(valid, s, -jnp.inf)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_next = jnp.maximum(m_prev, m_cur)
    safe_m = jnp.where(m_next == -jnp.inf, 0.0, m_next)
    alpha = jnp.exp(m_prev - safe_m)
    p = jnp.exp(s - safe_m)
    l_next = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return m_next, l_next, acc * alpha + pv


def _decode_valid(offset, ki, rows: int, q_len: int, block_k: int, q_group: int = 1):
    """(rows, block_k) bottom-right aligned length mask of kv tile ``ki``:
    row (h, r) of the step's heads (``rows`` = heads x ``q_len``) sits at
    absolute position offset + r // q_group (``q_group`` rows a position:
    the query heads that share a KV head) and may attend cache slots <= its
    own."""
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 0) % q_len
    q_pos = offset + (r if q_group == 1 else r // q_group)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 1)
    return q_pos >= k_pos


def _decode_bias_spec(bias_shape, rows: int, block_k: int, walk=None):
    """BlockSpec of a decode step's additive bias (``_decode_bias_rows``),
    every dim 1 or full: the step's slot x head group x rows x kv tile where
    the bias has them.  The grid is (slot, head group, kv tile), whatever
    scalar-prefetch refs follow; ``walk`` turns a grid step (and those refs)
    into the (slot, head group, kv tile) whose blocks it holds, None = its own."""
    b1, g1, r1, k1 = (n == 1 for n in bias_shape)

    def index_map(*at):
        b, g, ki = at[:3] if walk is None else walk(*at)
        return (0 if b1 else b, 0 if g1 else g, 0, 0 if k1 else ki)

    return pl.BlockSpec(
        (1, 1, 1 if r1 else rows, 1 if k1 else block_k), index_map,
    )


def _decode_update(group, ki, offset, allocated, refs, *, scale, block_k, nk, q_group):
    """What both decode kernels do with one grid step's blocks: start the
    statistics on the first kv tile, fold the tile in where it is live
    (at or before the tile of the slot's last position; ``allocated``: None,
    or the paged kernel's "this tile has a block"), and on the last tile hand
    each head its own lanes of the accumulator.  A dead tile's step computes
    nothing; whether it also fetches nothing is up to the caller's index maps.
    ``group`` is the step's head group, which only the int8 scales need: they
    come whole, and a group that is not all heads picks its own."""
    q_ref, k_ref, v_ref, ks_ref, vs_ref, bias_ref, o_ref, m_scr, l_scr, acc_scr = refs
    _, hb, q_len, d = o_ref.shape  # the step's output block: slot x heads x q rows x d
    first_head = 0 if ks_ref is None or ks_ref.shape[-1] == hb else group * hb
    # every live position of this slot is <= the last q row's, offset + (q_len
    # - 1) // q_group: tiles past that contribute nothing, and their arithmetic
    # is skipped here.  What such a step HOLDS is the index maps' business: the
    # flat kernel's repeat the slot's last live tile, so nothing is fetched for
    # it either (``_decode_walk``); the paged kernel's fetch the (clamped) block
    live = ki * block_k <= offset + (q_len - 1) // q_group
    if allocated is not None:
        live = jnp.logical_and(live, allocated)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(live)
    def _compute():
        m, l, acc = _decode_tile(
            q_ref[0, 0], k_ref[0], v_ref[0],
            None if ks_ref is None else ks_ref[0],
            None if vs_ref is None else vs_ref[0],
            None if bias_ref is None else bias_ref[0, 0],
            _decode_valid(offset, ki, hb * q_len, q_len, block_k, q_group),
            m_scr[:, :1], l_scr[:, :1], acc_scr[:],
            scale=scale, head_dim=d, first_head=first_head,
        )
        m_scr[:] = jnp.broadcast_to(m, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l, l_scr.shape)
        acc_scr[:] = acc

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[:, :1]
        out = (acc_scr[:] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)
        for h in range(hb):  # a head's rows, its own lanes (the loop is what a trace of the kernel pays for most)
            o_ref[0, h] = jax.lax.slice(out, (h * q_len, h * d), ((h + 1) * q_len, (h + 1) * d))


def _decode_kernel(
    *refs, scale: float, block_k: int, nk: int, has_bias: bool,
    has_scales: bool = False, q_group: int = 1,
):
    it = iter(refs)
    # scalar prefetch: (batch,) absolute position of q row 0; the slot indices,
    # live ones first; (1,) how many are live
    off_ref, order_ref, n_ref = next(it), next(it), next(it)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    ks_ref = next(it) if has_scales else None
    vs_ref = next(it) if has_scales else None
    bias_ref = next(it) if has_bias else None
    rest = tuple(it)
    b, g, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)  # read outside the branch: interpret mode needs it

    @pl.when(b < n_ref[0])  # past the last live slot a step holds that slot's last blocks and does nothing
    def _():
        _decode_update(
            g, ki, off_ref[order_ref[b]], None,
            (q_ref, k_ref, v_ref, ks_ref, vs_ref, bias_ref, *rest),
            scale=scale, block_k=block_k, nk=nk, q_group=q_group,
        )


def _check_decode_bias(bias, batch, heads, q_len, kv_len):
    for i, (bd, full) in enumerate(zip(bias.shape, (batch, heads, q_len, kv_len))):
        if bd not in (1, full):
            raise ValueError(f"bias dim {i} is {bd}, must be 1 or {full}")


def _decode_scratch(hb: int, q_len: int, d: int):
    return [
        pltpu.VMEM((hb * q_len, LANES), jnp.float32),
        pltpu.VMEM((hb * q_len, LANES), jnp.float32),
        pltpu.VMEM((hb * q_len, hb * d), jnp.float32),
    ]


def flash_decode(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: jnp.ndarray | None = None,
    *,
    offsets: jnp.ndarray,
    live: jnp.ndarray | None = None,
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
    scale: float | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    dtype: jnp.dtype | None = None,
    q_group: int = 1,
    ring: bool = False,
) -> jnp.ndarray:
    """Decode-step attention: a short q block against a cached K/V buffer.

    ``q``: (B, H, Q, d) with Q the decode step width (1 for token-by-token
    decode; beam batches flatten beams into B).  ``k``/``v``: (B, L, H x d)
    full-length cache buffers AS THE CACHE KEEPS THEM (``ops/mha.py``
    ``_cache_kv``): a position's heads side by side, head_dim minor, which
    is what ``k_proj`` returns and what XLA's row write fills, so the
    buffer rests row-major between the two and no step relays it (PR 32;
    before, (B, H, L, d) was copied three times a leaf a round).
    ``offsets``: (B,) int32 — the absolute cache position of each row's
    FIRST query; row r of the q block attends cache slots <= offsets[b] +
    r, so not-yet-written slots never contribute regardless of their
    (stale, reused) contents.  ``bias`` is a constant additive mask
    (b|1, h|1, q|1, L|1), every dim 1 or full — the padding mask / T5's
    decode-step relative-position bias.  ``k_scale``/``v_scale``
    ((B, L, H) f32, both or neither): the int8 KV cache's per-head
    per-position scales — ``k``/``v`` are then s8 and each kv tile is
    dequantized in VMEM after the DMA, so decode HBM traffic drops ~4×
    vs f32 buffers.  Returns (B, H, Q, d).  Inference only (no vjp);
    numerically identical to masked ``dot_product_attention`` on the same
    (dequantized) inputs (the parity tests pin greedy and beam decode
    against it).

    ``q_group`` > 1 is grouped-query attention without a repeated cache:
    H counts the KV heads, and the q block holds, position by position, the
    ``q_group`` query heads that read one KV head (row r is position
    ``offsets[b] + r // q_group``), so each K/V tile is streamed once for
    all of them (``ops/mha.py`` folds the heads in and out).

    One grid step streams one kv tile of ALL heads of a cache slot (of
    fewer when they do not fit VMEM: ``decode_step_heads``, from the shapes
    alone), so the grid is (B, H / heads, L / block_k) — (64, 1, 1) at
    bart-large-cnn's serving shape, 64 slots x cache 128 x 16 heads of 64.
    The q rows are laid block-diagonally over the merged axis
    (``decode_q_rows``), so one product a tile serves every head from its
    own lanes.  Per head the online softmax, its order over kv tiles, the
    per-row mask, the dead-tile skip and the fp32 accumulation are what
    they were.

    **What is fetched** (PR 46): only what a row needs.  ``live`` ((B,) bool;
    None = every row, which is every caller but the serving engine's step)
    says which rows hold a sequence.  The grid's slot axis walks a list of
    the slots, live ones first, as far as their number (both by scalar
    prefetch beside ``offsets``, as ``retention_step``'s); a step past the
    last live slot holds the blocks of the last live step, so the pipeline
    copies nothing in or out for an idle row, whose output is ZERO and whose
    leaf may hold anything.  Inside a live row the kv tile index stops at the
    row's last live tile, that of its last q row's position: the steps after
    it hold that tile again (no copy) and compute nothing, so a tile past a
    row's last position is never read either.  The kernel moves whole tiles,
    never fewer bytes than the row's positions; a skipped step costs what an
    empty grid step costs (~0.35 us on v5e).

    ``ring``: ``k``/``v`` are a window layer's leaves, (B, window, H x d),
    written at ``position mod window`` (``ops/mha.py`` ``cache_window_kv``),
    and ``offsets`` are the rows' absolute POSITIONS: an entry is valid by the
    row's position, not by its index (index r holds the newest position
    congruent to r, there from position r on), so the step reads entries ``<=
    min(position, window - 1)``; the order of a ring's entries does not
    matter to a softmax over keys cached after RoPE.  One q position a row.
    A ring takes the live list (an idle row's ring is not read) and no tile
    stop: a live row's ring is fetched whole.
    The same kernel under its own name in a device trace, ``window_decode``
    (``_window_decode_call``: not inlined), whatever its call site.
    """
    batch, heads, q_len, d = q.shape
    if scale is None:
        scale = d ** -0.5
    kv_len = k.shape[1]
    if ring:
        if q_len != q_group:
            raise ValueError(f"a ring step is one position a row; got {q_len} q rows at q_group {q_group}")
        offsets = jnp.clip(jnp.asarray(offsets, jnp.int32), 0, kv_len - 1)
    if k.shape != (batch, kv_len, heads * d) or v.shape != k.shape:
        raise ValueError(
            f"k/v {k.shape}/{v.shape}: the cache leaf is (batch, length, heads x "
            f"head_dim) = ({batch}, L, {heads * d}) for q {q.shape}"
        )
    block_k = decode_block(kv_len) if block_k is None else min(block_k, kv_len)
    if not block_k or kv_len % block_k or block_k % 8:
        raise ValueError(
            f"kv_len {kv_len} not divisible into 8-aligned blocks ({block_k})"
        )
    if bias is not None:
        _check_decode_bias(bias, batch, heads, q_len, kv_len)
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    has_scales = k_scale is not None
    if has_scales:
        want = (batch, kv_len, heads)
        for name, s in (("k_scale", k_scale), ("v_scale", v_scale)):
            if tuple(s.shape) != want:
                raise ValueError(f"{name} shape {tuple(s.shape)} != {want}")
    if interpret is None:
        interpret = _default_interpret()
    hb = decode_step_heads(
        heads, block_k, d, k.dtype.itemsize, q_len=q_len, int8_scales=has_scales
    )
    live = jnp.ones((batch,), bool) if live is None else jnp.asarray(live, bool).reshape(batch)
    return (_window_decode_call if ring else _decode_call)(
        jnp.asarray(offsets, jnp.int32).reshape(batch), live, q, k, v, k_scale, v_scale, bias,
        scale=float(scale), block_k=block_k, step_heads=hb, q_group=q_group,
        interpret=bool(interpret), dtype=dtype,
    )


_DECODE_STATICS = ("scale", "block_k", "step_heads", "q_group", "interpret", "dtype")
WINDOW_DECODE = "window_decode"  # a ring step's custom call in a device trace


@functools.partial(jax.jit, inline=True, static_argnames=_DECODE_STATICS)
def _decode_call(offsets, live, q, k, v, k_scale, v_scale, bias, **statics):
    """``flash_decode``'s program on checked operands, every choice made from
    the shapes passed in as a static.  Jitted so that a decode program's
    call sites of one shape (twelve layers) trace the kernel once, and
    inlined so that each stays an operation of its own call site (the
    custom call keeps the site's name)."""
    return _decode_program(offsets, live, q, k, v, k_scale, v_scale, bias, **statics)


@functools.partial(jax.jit, static_argnames=_DECODE_STATICS)
def _window_decode_call(offsets, live, q, k, v, k_scale, v_scale, bias, **statics):
    """The same program for a ring leaf, NOT inlined and the kernel named, so
    that the custom call is ``window_decode`` in a device trace and no metric
    has to tell a window layer's step from a full layer's by its shape."""
    return _decode_program(offsets, live, q, k, v, k_scale, v_scale, bias, ring=True, **statics)


def _decode_walk(b, g, ki, offsets, order, n_live, *, nk: int, block_k: int, last_pos: int,
                 last_group: int, ring: bool):
    """Whose blocks grid step (b, g, ki) of the decode kernel holds: (slot, head
    group, kv tile).  ``offsets`` (slots,), ``order`` (the slot indices, live
    ones first) and ``n_live`` (1,) are the call's scalar-prefetch refs (any
    indexable does: the tests walk a grid with numpy arrays).  While ``b <
    n_live``: slot ``order[b]``, its own group, and tile ``min(ki, the slot's
    last live tile)``, that of position ``offsets[slot] + last_pos`` (the last
    q row's); a ``ring`` has no tile stop (its entries are valid by position).
    Past the last live slot: the last live step's blocks, whatever (g, ki)."""
    on = b < n_live[0]
    slot = order[jnp.where(on, b, jnp.maximum(n_live[0] - 1, 0))]
    last = nk - 1 if ring else jnp.clip((offsets[slot] + last_pos) // block_k, 0, nk - 1)
    return slot, jnp.where(on, g, last_group), jnp.where(on, jnp.minimum(ki, last), last)


def _decode_program(offsets, live, q, k, v, k_scale, v_scale, bias, *, scale: float, block_k: int,
                    step_heads: int, q_group: int, interpret: bool, dtype, ring: bool = False):
    """The pallas call.  Its grid is (slot, head group, kv tile) at full size,
    but what a step HOLDS follows the live list (``_decode_walk``), so
    consecutive steps that need nothing new name the block already resident
    and the pipeline moves nothing.  Every operand and the output go by the
    same walk.  A slot the grid never visits has no output written: it is
    set to zero after the call.  That also makes every grid axis sequential,
    as ``retention._step_call``'s."""
    batch, heads, q_len, d = q.shape
    hb, nk = step_heads, k.shape[1] // block_k
    has_scales = k_scale is not None
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    n_live = jnp.sum(live, dtype=jnp.int32).reshape(1)
    walk = functools.partial(
        _decode_walk, nk=nk, block_k=block_k, last_pos=(q_len - 1) // q_group,
        last_group=heads // hb - 1, ring=ring,
    )

    def q_map(*at):
        slot, g, _ = walk(*at)
        return (slot, g, 0, 0)

    def kv_map(*at):
        slot, g, ki = walk(*at)
        return (slot, ki, g)

    def scale_map(*at):
        slot, _, ki = walk(*at)
        return (slot, ki, 0)

    in_specs = [
        pl.BlockSpec((1, 1, hb * q_len, hb * d), q_map),
        pl.BlockSpec((1, block_k, hb * d), kv_map),
        pl.BlockSpec((1, block_k, hb * d), kv_map),
    ]
    if has_scales:  # all heads' scales a step: a part of their lanes is no block
        in_specs += [pl.BlockSpec((1, block_k, heads), scale_map)] * 2
    if bias is not None:
        bias = _decode_bias_rows(bias, heads, q_len, hb)
        in_specs.append(_decode_bias_spec(bias.shape, hb * q_len, block_k, walk))
    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, scale=scale, block_k=block_k, nk=nk,
            has_bias=bias is not None, has_scales=has_scales, q_group=q_group,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # offsets, order, n_live
            grid=(batch, heads // hb, nk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, hb, q_len, d), q_map),
            scratch_shapes=_decode_scratch(hb, q_len, d),
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name=WINDOW_DECODE if ring else None,  # a full leaf's call keeps its call site's name
    )(offsets, order, n_live, *[
        x for x in (decode_q_rows(q, hb), k, v, k_scale, v_scale, bias) if x is not None
    ])
    # a slot the grid did not visit has no output written: zero, not what the buffer held
    out = jnp.where(live[:, None, None, None], out, jnp.zeros((), out.dtype))
    return out if dtype is None else out.astype(dtype)


# The decode kernel's q-block ceiling: plain decode steps are 1 row, and
# speculative verify (serving/spec.py) rides the SAME entry with a q block
# of spec_tokens + 1 rows — the per-row length masks already express the
# staggered offsets, so k drafts verify for about the price of one step.
# core.config.SPEC_MAX_DRAFT_TOKENS = this - 1 (the bonus row).
MAX_DECODE_Q_ROWS = 8


def flash_decode_supported(
    q_len: int, kv_len: int, head_dim: int, block_k: int | None = None
) -> bool:
    """True when a cached decode step is kernel-eligible: the cache length
    tiles into 8-aligned blocks, the head dim is lane-aligned, and the q
    block is small enough to live in scratch (plain decode steps are 1
    row, speculative verify up to ``MAX_DECODE_Q_ROWS`` — the cap keeps
    prefill-sized calls out)."""
    bk = decode_block(kv_len) if block_k is None else min(block_k, kv_len)
    return (
        0 < q_len <= MAX_DECODE_Q_ROWS
        and bk > 0
        and kv_len % bk == 0
        and bk % 8 == 0
        and head_dim % 8 == 0
    )


# ------------------------------------------------- paged decode variant
#
# The paged-cache twin of flash_decode (serving/cache_pool.py owns the
# pool/allocator; this kernel is the device half): K/V live in a SHARED
# block pool of (num_blocks, block_size, H x d) and each slot maps its
# logical kv tiles onto pool blocks through a per-slot block table.  The
# block size IS the kv tile size, so the kernel's tile loop indexes pool
# blocks directly — the block table rides scalar prefetch and the
# BlockSpec index maps read it, meaning the DMA fetches exactly the
# slot's blocks and a flat (slots, L, H x d) view never exists anywhere.
# A sentinel entry (>= num_blocks: an unallocated logical tile) clamps to
# a valid block for the DMA and is masked to -inf in-kernel, so whatever
# the clamped block holds contributes exactly nothing.


def _decode_paged_kernel(
    *refs, scale: float, block_k: int, nk: int, num_blocks: int,
    has_bias: bool, has_scales: bool,
):
    it = iter(refs)
    bt_ref, off_ref = next(it), next(it)  # scalar-prefetch: (B, nk), (B,)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    ks_ref = next(it) if has_scales else None
    vs_ref = next(it) if has_scales else None
    bias_ref = next(it) if has_bias else None
    bi = pl.program_id(0)
    ki = pl.program_id(2)
    # dead-tile skip as in _decode_kernel, plus: a sentinel block-table
    # entry is an unallocated tile — nothing of it may contribute.  One pool
    # block's heads go through the flat kernel's own tile arithmetic on the
    # same group of heads: bit-identity rests on it
    _decode_update(
        pl.program_id(1), ki, off_ref[bi], bt_ref[bi, ki] < num_blocks,
        (q_ref, k_ref, v_ref, ks_ref, vs_ref, bias_ref, *it),
        scale=scale, block_k=block_k, nk=nk, q_group=1,
    )


def flash_decode_paged(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    bias: jnp.ndarray | None = None,
    *,
    block_tables: jnp.ndarray,
    offsets: jnp.ndarray,
    k_scale_pool: jnp.ndarray | None = None,
    v_scale_pool: jnp.ndarray | None = None,
    scale: float | None = None,
    interpret: bool | None = None,
    dtype: jnp.dtype | None = None,
) -> jnp.ndarray:
    """Decode attention straight off a shared block pool.

    ``q``: (B, H, Q≤8, d).  ``k_pool``/``v_pool``: (num_blocks,
    block_size, H x d) — the pool, a block laid like a tile of the flat
    cache leaf; ``block_tables``: (B, n_tiles) int32 mapping each row's
    logical tile to its pool block (entries >= num_blocks are unallocated
    tiles and contribute nothing); ``offsets``: (B,) as in
    ``flash_decode``.  The logical cache length is ``n_tiles ×
    block_size`` and ``bias`` (1-or-full dims) is indexed in LOGICAL tile
    order.  ``k_scale_pool``/``v_scale_pool`` ((num_blocks, block_size, H)
    f32) compose the int8 KV cache with paging.  Numerically identical to
    ``flash_decode`` over the flattened view of the same blocks."""
    batch, heads, q_len, d = q.shape
    if scale is None:
        scale = d ** -0.5
    num_blocks, block_k, pool_lanes = k_pool.shape
    if pool_lanes != heads * d:
        raise ValueError(
            f"pool shape {k_pool.shape} does not match q heads x dim "
            f"({heads} x {d})"
        )
    n_tiles = block_tables.shape[1]
    kv_len = n_tiles * block_k
    if block_k % 8:
        raise ValueError(f"block_size {block_k} must be 8-aligned")
    if bias is not None:
        _check_decode_bias(bias, batch, heads, q_len, kv_len)
    if (k_scale_pool is None) != (v_scale_pool is None):
        raise ValueError("k_scale_pool and v_scale_pool go together")
    has_scales = k_scale_pool is not None
    if interpret is None:
        interpret = _default_interpret()
    block_tables = jnp.asarray(block_tables, jnp.int32).reshape(batch, n_tiles)
    offsets = jnp.asarray(offsets, jnp.int32).reshape(batch)
    # the flat kernel's head group (a pool block is one slot's tile, so a
    # step holds one slot): same blocks, same arithmetic, same bits
    hb = decode_step_heads(
        heads, block_k, d, k_pool.dtype.itemsize, q_len=q_len, int8_scales=has_scales
    )
    grid = (batch, heads // hb, n_tiles)
    clamp = num_blocks - 1

    def q_map(b, g, ki, bt_ref, off_ref):
        return (b, g, 0, 0)

    def pool_map(b, g, ki, bt_ref, off_ref):
        # sentinel tiles clamp to a real block for the DMA; the kernel
        # masks them to -inf so the clamped contents never contribute
        return (jnp.minimum(bt_ref[b, ki], clamp), 0, g)

    in_specs = [
        pl.BlockSpec((1, 1, hb * q_len, hb * d), q_map),
        pl.BlockSpec((1, block_k, hb * d), pool_map),
        pl.BlockSpec((1, block_k, hb * d), pool_map),
    ]
    if has_scales:  # all heads' scales a step, as in the flat kernel
        in_specs += [pl.BlockSpec(
            (1, block_k, heads),
            lambda b, g, ki, bt_ref, off_ref: (jnp.minimum(bt_ref[b, ki], clamp), 0, 0),
        )] * 2
    if bias is not None:
        bias = _decode_bias_rows(bias, heads, q_len, hb)
        in_specs.append(_decode_bias_spec(bias.shape, hb * q_len, block_k))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hb, q_len, d), q_map),
        scratch_shapes=_decode_scratch(hb, q_len, d),
    )
    out = pl.pallas_call(
        functools.partial(
            _decode_paged_kernel, scale=float(scale), block_k=block_k,
            nk=n_tiles, num_blocks=num_blocks,
            has_bias=bias is not None, has_scales=has_scales,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        block_tables, offsets,
        *[
            x
            for x in (decode_q_rows(q, hb), k_pool, v_pool, k_scale_pool, v_scale_pool, bias)
            if x is not None
        ],
    )
    return out if dtype is None else out.astype(dtype)


def flash_decode_run(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: jnp.ndarray | None,
    *,
    offsets: jnp.ndarray,
    mesh,
    live: jnp.ndarray | None = None,
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
    scale: float | None = None,
    dtype: jnp.dtype | None = None,
    interpret: bool | None = None,
    q_group: int = 1,
    ring: bool = False,
) -> jnp.ndarray:
    """Run the decode kernel — directly on one device, per-shard under
    ``shard_map`` on a mesh (batch over data×fsdp×expert, heads over
    ``tensor``, mirroring ``ops.mha.flash_run``: q's head axis, and the
    merged last axis of the (B, L, H x d) buffers, in which the heads are
    contiguous).  ``offsets`` and ``live`` shard with the batch rows (each
    batch shard walks its own live list); the int8 KV scales
    (``k_scale``/``v_scale``, (B, L, H)) shard exactly like the buffers
    they dequantize; the kernel body needs no collectives (decode never
    mixes rows or heads).  A bias carrying a HEAD dim must be full-size (it
    shards with the heads); batch dim 1-or-full as usual."""
    import math as _math

    from jax.sharding import PartitionSpec as P

    from distributed_llms_example_tpu.parallel.activation import BATCH_AXES

    if mesh is None or _math.prod(mesh.devices.shape) == 1:
        return flash_decode(
            q, k, v, bias, offsets=offsets, live=live, k_scale=k_scale, v_scale=v_scale,
            scale=scale, dtype=dtype, interpret=interpret, q_group=q_group, ring=ring,
        )
    batch_axes = tuple(a for a in BATCH_AXES if a in mesh.shape)
    head_axis = "tensor" if "tensor" in mesh.shape else None
    q_spec = P(batch_axes or None, head_axis, None, None)
    kv_spec = P(batch_axes or None, None, head_axis)
    off_spec = P(batch_axes or None)
    has_scales = k_scale is not None

    def run(q, k, v, off, on, *rest):
        rest = list(rest)
        ks = vs = None
        if has_scales:
            ks, vs = rest.pop(0), rest.pop(0)
        return flash_decode(
            q, k, v, rest[0] if rest else None, offsets=off, live=on,
            k_scale=ks, v_scale=vs, scale=scale,
            dtype=dtype, interpret=interpret, q_group=q_group, ring=ring,
        )

    rows = q.shape[0]
    args = (q, k, v, jnp.asarray(offsets, jnp.int32).reshape(rows),
            jnp.ones((rows,), bool) if live is None else jnp.asarray(live, bool).reshape(rows))
    in_specs = (q_spec, kv_spec, kv_spec, off_spec, off_spec)
    if has_scales:
        args = (*args, k_scale, v_scale)
        in_specs = (*in_specs, kv_spec, kv_spec)
    if bias is not None:
        bias_spec = P(
            (batch_axes or None) if bias.shape[0] != 1 else None,
            head_axis if bias.shape[1] != 1 else None,
            None,
            None,
        )
        args = (*args, bias)
        in_specs = (*in_specs, bias_spec)
    return jax.shard_map(
        run, mesh=mesh, in_specs=in_specs, out_specs=q_spec, check_vma=False
    )(*args)


# -------------------------------------------- multi-device relative bias


def make_flash_lbias_sharded(
    mesh,
    *,
    batch_axes: tuple[str, ...],
    head_axis: str | None,
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    interpret: bool,
    has_bias: bool,
    out_dtype,
    dropout_rate: float = 0.0,
    hw_rng: bool = False,
):
    """Multi-device flash attention WITH a differentiable relative bias
    (the (H, Q + K - 1) per-diagonal vector of ``flash_attention``):
    per-shard Pallas kernels under ``shard_map`` (batch over
    ``batch_axes``, heads over ``head_axis``) and a HAND-WRITTEN vjp whose
    backward psums the per-batch-shard diagonal sums inside the manual
    region.  The generic ``flash_run`` path can't do this: its shard_map
    runs ``check_vma=False``, under which autodiff would silently drop the
    cross-shard reduction a replicated input's cotangent needs — here the
    reduction is explicit, so T5's relative-position bias trains correctly
    on any mesh, not just a single chip.

    Returns ``f(q, k, v[, bias], rel[, seed]) -> o``.  ``bias`` (present
    iff ``has_bias``) is a constant (b|1, 1, 1, K) mask; ``rel`` is
    heads-sharded over ``head_axis`` and replicated across the batch
    shards.  ``seed`` (present iff ``dropout_rate > 0``) is the replicated
    int32 probs-dropout seed — each shard folds its axis indices in, so
    batch/head shards draw independent masks, and the per-shard backward
    redraws the identical mask from the same folded seed.
    """
    from jax.sharding import PartitionSpec as P

    from distributed_llms_example_tpu.ops.fused_dropout import _shard_seed

    has_dropout = dropout_rate > 0.0
    fold_axes = batch_axes + ((head_axis,) if head_axis else ())

    qkv_spec = P(batch_axes or None, head_axis, None, None)
    rel_spec = P(head_axis, None)
    lse_spec = P(batch_axes or None, head_axis, None, None)

    def mask_spec(b):
        return P(
            (batch_axes or None) if b.shape[0] != 1 else None,
            None, None, None,
        )

    kw = dict(scale=scale, causal=causal, block_q=block_q, block_k=block_k,
              interpret=interpret)

    def split(args):
        """(q, k, v[, bias], rel[, seed]) → (q, k, v, bias|None, rel,
        seed|None)."""
        args, seed = (args[:-1], args[-1]) if has_dropout else (args, None)
        if has_bias:
            q, k, v, bias, rel = args
        else:
            (q, k, v, rel), bias = args, None
        return q, k, v, bias, rel, seed

    def drop_kw(seed):
        if seed is None:
            return {}
        return dict(
            dropout_rate=dropout_rate, hw_rng=hw_rng,
            dropout_seed=_shard_seed(seed, fold_axes) if fold_axes else seed,
        )

    def fwd_in_specs(bias):
        return tuple(
            s for s in (
                qkv_spec, qkv_spec, qkv_spec,
                mask_spec(bias) if has_bias else None, rel_spec,
                P() if has_dropout else None,
            ) if s is not None
        )

    def fwd_shard(*sargs):
        sq, sk, sv, sbias, srel, sseed = split(sargs)
        slb = relative_bias_matrix(srel.astype(sq.dtype), sq.shape[2], sk.shape[2])
        o, lse = _fwd(sq, sk, sv, sbias, slb, **kw, **drop_kw(sseed))
        return o, lse[..., :1]

    def run_fwd(args, bias):
        return jax.shard_map(
            fwd_shard, mesh=mesh, in_specs=fwd_in_specs(bias),
            out_specs=(qkv_spec, lse_spec), check_vma=False,
        )(*args)

    @jax.custom_vjp
    def f(*args):
        _, _, _, bias, _, _ = split(args)
        return run_fwd(args, bias)[0]

    def f_fwd(*args):
        q, k, v, bias, rel, seed = split(args)
        o, lse1 = run_fwd(args, bias)
        return o, (q, k, v, bias, rel, seed, o, lse1)

    def f_bwd(res, do):
        q, k, v, bias, rel, seed, o, lse1 = res

        def bwd_shard(*sargs):
            sargs, sseed = (sargs[:-1], sargs[-1]) if has_dropout else (sargs, None)
            if has_bias:
                sq, sk, sv, sbias, srel, so, slse1, sdo = sargs
            else:
                (sq, sk, sv, srel, so, slse1, sdo), sbias = sargs, None
            lse = jax.lax.broadcast_in_dim(
                slse1[..., 0], (*slse1.shape[:-1], LANES), (0, 1, 2)
            )
            slb = relative_bias_matrix(srel.astype(sq.dtype), sq.shape[2], sk.shape[2])
            dq, dk, dv, drel = _bwd(
                sq, sk, sv, sbias, slb, so, lse, sdo, **kw, **drop_kw(sseed)
            )
            # each batch shard summed the diagonals of ITS rows only: the
            # explicit cross-shard reduction autodiff can't insert here
            # (a (H, Q + K - 1) vector, where it was a (1, H, Q, K) matrix)
            if batch_axes:
                drel = jax.lax.psum(drel, batch_axes)
            return dq, dk, dv, drel

        base = fwd_in_specs(bias)
        if has_dropout:
            base = base[:-1]  # seed spec moves to the end (matches args)
        in_specs = (*base, qkv_spec, lse_spec, qkv_spec) + (
            (P(),) if has_dropout else ()
        )
        args = tuple(
            x for x in (q, k, v, bias, rel, o, lse1, do) if x is not None
        ) + ((seed,) if has_dropout else ())
        dq, dk, dv, drel = jax.shard_map(
            bwd_shard, mesh=mesh, in_specs=in_specs,
            out_specs=(qkv_spec, qkv_spec, qkv_spec, rel_spec), check_vma=False,
        )(*args)
        out = (dq, dk, dv)
        if has_bias:
            out = (*out, jnp.zeros_like(bias))
        out = (*out, drel)
        if has_dropout:
            out = (*out, None)  # seed: int, no cotangent
        return out

    f.defvjp(f_fwd, f_bwd)
    return lambda *args: f(*args).astype(out_dtype)


def flash_attention_lbias_sharded(
    q, k, v, bias, relative_bias, *, mesh,
    batch_axes: tuple[str, ...], head_axis: str | None,
    causal: bool = False, scale: float | None = None,
    block_q: int | None = None, block_k: int | None = None,
    interpret: bool | None = None, dtype=None,
    dropout_rate: float = 0.0, dropout_seed=None, hw_rng: bool | None = None,
):
    """Front door for the multi-device relative-bias path (see
    ``make_flash_lbias_sharded``).  Same shape/validation contract as
    ``flash_attention``; block sizes are the per-shard auto defaults
    (q and the bias's diagonals are full-length per shard — only batch
    and heads split).  The mask additionally must not carry a HEAD dim
    (the per-shard BlockSpec would index the wrong heads on non-first
    tensor shards); a full query dim — a (B, 1, Q, K) mask — is fine, since
    Q/K are unsharded here."""
    if causal and q.shape[2] != k.shape[2]:
        raise ValueError(
            f"causal=True requires square self-attention, got q_len={q.shape[2]} "
            f"!= kv_len={k.shape[2]}"
        )
    if scale is None:
        scale = q.shape[-1] ** -0.5
    cap_q, cap_k = _block_caps(bool(causal), True, q.shape[-1])
    block_q = auto_block(q.shape[2], cap_q) if block_q is None else min(block_q, q.shape[2])
    block_k = auto_block(k.shape[2], cap_k) if block_k is None else min(block_k, k.shape[2])
    if (
        not block_q or not block_k
        or q.shape[2] % block_q or k.shape[2] % block_k
        or block_q % 8 or block_k % 8
    ):
        raise ValueError(
            f"seq lens {q.shape[2]}/{k.shape[2]} not divisible into 8-aligned "
            f"blocks {block_q}/{block_k}"
        )
    if bias is not None:
        for i, (bd, full) in enumerate(
            zip(bias.shape, (q.shape[0], 1, q.shape[2], k.shape[2]))
        ):
            if bd not in (1, full):
                raise ValueError(
                    f"bias dim {i} is {bd}, must be 1 or {full} (the head dim "
                    "must be 1 on the sharded relative-bias path)"
                )
    want = (q.shape[1], q.shape[2] + k.shape[2] - 1)
    if tuple(relative_bias.shape) != want:
        raise ValueError(f"relative_bias shape {tuple(relative_bias.shape)} != {want}")
    relative_bias = relative_bias.astype(jnp.float32)  # its cotangent comes back in fp32
    if interpret is None:
        interpret = _default_interpret()
    if hw_rng is None:
        hw_rng = not interpret
    dropout_rate = float(dropout_rate)
    if dropout_rate > 0.0:
        if not dropout_rate < 1.0:
            raise ValueError(f"dropout_rate must be in [0, 1), got {dropout_rate}")
        if dropout_seed is None:
            raise ValueError("dropout_rate > 0 requires a dropout_seed scalar")
    f = make_flash_lbias_sharded(
        mesh, batch_axes=batch_axes, head_axis=head_axis, causal=bool(causal),
        scale=float(scale), block_q=int(block_q), block_k=int(block_k),
        interpret=bool(interpret), has_bias=bias is not None,
        out_dtype=dtype or q.dtype,
        dropout_rate=dropout_rate, hw_rng=bool(hw_rng),
    )
    args = (q, k, v, bias, relative_bias) if bias is not None else (q, k, v, relative_bias)
    if dropout_rate > 0.0:
        args = (*args, jnp.asarray(dropout_seed, jnp.int32).reshape(()))
    return f(*args)
