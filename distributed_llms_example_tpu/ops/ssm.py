"""Selective state-space mixer (Mamba-2, "SSD"): one layer's mathematics in its
three equal forms, and the 4-tap causal convolution in front of it.

A head ``h`` (``H`` heads of ``P`` channels) remembers a sequence in a matrix
``S`` of ``N x P`` whose size does not depend on the context.  With
``dt_t > 0`` a head a token (after its softplus), ``A_h < 0`` a head, ``B_t``
and ``C_t`` of ``N`` a GROUP a token (head ``h`` reads group ``h // (H / G)``)
and ``x_t`` of ``P`` a head a token:

* recurrence: ``S_t = exp(dt_t A_h) S_{t-1} + B_t (dt_t x_t)^T``,
  ``y_t = S_t^T C_t + D_h x_t``: a decay a HEAD a token that depends on the
  input, a rank-one update, a read by ``C``, a skip;
* chunked form (chunks of ``L`` tokens): with ``a_i = sum_{l<=i} dt_l A`` inside
  a chunk, ``y_i = sum_{j<=i} (C_i . B_j) exp(a_i - a_j) dt_j x_j + exp(a_i)
  S_start^T C_i``, and the chunk leaves ``exp(a_last) S_start + sum_j
  exp(a_last - a_j) B_j (dt_j x_j)^T``: matrix products inside a chunk, a
  scan over the chunks' states;
* decode step: the recurrence's one step on the state a slot holds.

**The state's layout and dtype** (design decisions).  A slot's state in a
layer is ``(H, N, P)``: the state's ``N`` on the sublanes and a head's ``P`` =
128 channels on the lanes.  ``B`` and ``C`` are shared by the 16 heads of a
group, so the step kernel broadcasts each across the lanes ONCE a grid step
and every head of the step reuses them; ``dt x`` is a lane-dense row that
broadcasts down the sublanes for free, and the read ``sum_n S[n, :] C[n]`` is a
sum of whole vector registers with one 8-to-1 sublane reduction at its end and
a lane-dense row as its result.  With ``P`` on the sublanes instead every head
would pay its own lane broadcast of ``x`` and a lane reduction a row of ``y``.
The state is **float32**: it is a running sum of up to 262,144 decayed terms
with decays of 0.9-0.999 a token, so the oldest terms that still matter are
``1e-3`` of the newest and bfloat16's 8 bits would drop them
(``tests/test_ssm.py``: the update in bfloat16 fails the comparison with the
recurrence).  ``dt``, ``exp(dt A)`` and the running sums ``a`` are float32 for
the same reason; the products that build and read the state run at
``Precision.HIGH`` (three bfloat16 passes; ``C . B`` of two bfloat16 operands
needs one).

**The convolution** is depthwise, causal, ``K`` taps over the channels
``x | B | C`` before their activation; what a decoder carries is the last
``K - 1`` pre-activation columns.  Its step stays in XLA: it moves 30 KB a
slot a layer where the state moves 8.4 MB, and XLA fuses it with the
projection's slices around it.

Every function here takes the mixer's tensors and knows nothing of a model;
``models/falcon_h1.py`` is the caller.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128
_HIGH = jax.lax.Precision.HIGH
_HIGHEST = jax.lax.Precision.HIGHEST


def state_shape(batch: int, heads: int, head_dim: int, state: int) -> tuple:
    """The ``ssm_state`` leaf of ``batch`` sequences of one layer (module docstring)."""
    return (batch, heads, state, head_dim)


# ------------------------------------------------------------ convolution


def causal_conv(xbc, weight, bias, valid=None):
    """A prompt's depthwise causal convolution from an empty past: ``xbc`` (B,
    T, C) pre-activation, ``weight`` (C, K), ``bias`` (C,), ``valid`` (B, T) 0
    where a position is right-padding.  Returns (``conv + bias`` (B, T, C), the
    last ``K - 1`` REAL columns of ``xbc`` as (B, C, K - 1): zeros where the
    prompt holds fewer)."""
    b, t, _ = xbc.shape
    taps = weight.shape[1]
    zs = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    out = sum(weight[:, k] * zs[:, k: k + t] for k in range(taps)) + bias
    n_valid = jnp.full((b,), t, jnp.int32) if valid is None else jnp.sum(valid, axis=1).astype(jnp.int32)
    # columns n_valid .. n_valid + K - 2 of ``zs`` are the last K - 1 the row really holds
    keep = n_valid[:, None] + jnp.arange(taps - 1)[None, :]
    return out, jnp.swapaxes(jnp.take_along_axis(zs, keep[:, :, None], axis=1), 1, 2)


def causal_conv_step(xbc, weight, bias, state, live=None):
    """One token's convolution: ``xbc`` (B, C), ``state`` (B, C, K - 1) the
    columns before it.  Returns (``conv + bias`` (B, C), the new state); a row
    that is not ``live`` keeps its columns."""
    zs = jnp.concatenate([state.astype(xbc.dtype), xbc[:, :, None]], axis=2)  # (B, C, K)
    out = jnp.sum(zs * weight[None], axis=2) + bias
    new = zs[:, :, 1:].astype(state.dtype)
    if live is not None:
        new = jnp.where(live.astype(bool)[:, None, None], new, state)
    return out, new


# ------------------------------------------------------------ the three forms


def _masked(x, dt, valid):
    """float32 ``dt`` with padding's set to 0 (a decay of 1, no update) and ``x`` zeroed there."""
    dt = dt.astype(jnp.float32)
    if valid is None:
        return x, dt
    return x * valid[:, :, None, None].astype(x.dtype), dt * valid[:, :, None].astype(jnp.float32)


def ssm_recurrence(x, dt, a_neg, b, c, d_skip, valid=None, state=None):
    """The plain recurrence: a ``lax.scan`` of ``ssm_step_reference`` over the
    tokens (the tests' oracle for the chunked form and the kernel; the
    benchmark's reference has its own).  x (B, T, H, P); ``dt`` (B, T, H) after
    its softplus; ``a_neg`` (H,) = A < 0; b, c (B, T, G, N); ``d_skip`` (H,);
    ``valid`` (B, T); ``state`` (B, H, N, P) to start from (None: zeros).
    Returns (y (B, T, H, P) float32, the state left)."""
    bsz, _, h, p = x.shape
    x, dt = _masked(x, dt, valid)

    def one(s, xs):
        y, s = ssm_step_reference(*xs[:2], a_neg, *xs[2:], d_skip, s)
        return s, y

    zero = jnp.zeros(state_shape(bsz, h, p, b.shape[3]), jnp.float32) if state is None else state
    state, y = jax.lax.scan(one, zero, tuple(jnp.moveaxis(u, 1, 0) for u in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1), state


def ssm_prefill(x, dt, a_neg, b, c, d_skip, valid=None, chunk: int = CHUNK):
    """A prompt from an empty state in the chunked form (XLA products under the
    scope ``ssm_prefill``; a device trace names no scope, so the benchmark tells
    them by the shapes only this form has: ``layer_metrics/serve_ssm_prefill_ms.py``).
    Arguments as ``ssm_recurrence``; T is padded to whole chunks with ``dt =
    0``, which neither moves the state nor is returned.  Returns (y (B, T, H,
    P) float32, state (B, H, N, P) float32)."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    rep = h // g
    x, dt = _masked(x, dt, valid)
    pad = -t % chunk
    if pad:
        x, dt, b, c = (jnp.pad(u, ((0, 0), (0, pad)) + ((0, 0),) * (u.ndim - 2)) for u in (x, dt, b, c))
    nc = (t + pad) // chunk
    chunks = lambda u: u.reshape(bsz, nc, chunk, *u.shape[2:])  # noqa: E731
    with jax.named_scope("ssm_prefill"):
        a = jnp.cumsum(chunks(dt * a_neg), axis=2)  # (B, nc, L, H): the decay's running sum inside a chunk
        dtx = chunks(dt[..., None] * x.astype(jnp.float32)).reshape(bsz, nc, chunk, g, rep, p)
        bc, cc = chunks(b), chunks(c)  # (B, nc, L, G, N)
        a_g = a.reshape(bsz, nc, chunk, g, rep)
        # inside a chunk: (C_i . B_j) exp(a_i - a_j) for j <= i, times dt_j x_j
        cb = jnp.einsum("zcign,zcjgn->zcijg", cc, bc, preferred_element_type=jnp.float32)
        seen = jnp.tril(jnp.ones((chunk, chunk), bool))[:, :, None, None]
        decay = jnp.exp(jnp.where(seen, a_g[:, :, :, None] - a_g[:, :, None, :], -jnp.inf))  # (B, nc, i, j, G, rep)
        y = jnp.einsum("zcijgr,zcjgrp->zcigrp", cb[..., None] * decay, dtx, precision=_HIGH)
        # what each chunk adds to the state, and the state each chunk starts from
        to_end = jnp.exp(a_g[:, :, -1:] - a_g)  # (B, nc, L, G, rep)
        added = jnp.einsum("zcjgn,zcjgrp->zcgrnp", bc.astype(jnp.float32), dtx * to_end[..., None], precision=_HIGH)
        whole = jnp.exp(a_g[:, :, -1])  # (B, nc, G, rep): a chunk's whole decay

        def carry(s, xs):
            add, keep = xs
            return keep[..., None, None] * s + add, s

        zero = jnp.zeros((bsz, g, rep, n, p), jnp.float32)
        state, starts = jax.lax.scan(carry, zero, (jnp.moveaxis(added, 1, 0), jnp.moveaxis(whole, 1, 0)))
        starts = jnp.moveaxis(starts, 0, 1)  # (B, nc, G, rep, N, P)
        y = y + jnp.einsum("zcign,zcgrnp->zcigrp", cc.astype(jnp.float32), starts, precision=_HIGH) * jnp.exp(a_g)[..., None]
        y = y.reshape(bsz, nc * chunk, h, p)[:, :t] + d_skip[None, None, :, None] * x[:, :t].astype(jnp.float32)
    return y, state.reshape(bsz, h, n, p)


def _live_rows(live, like):
    return live.astype(bool).reshape(live.shape + (1,) * (like.ndim - 1))


def ssm_step_reference(x, dt, a_neg, b, c, d_skip, state, *, live=None):
    """One recurrent step in plain ``jnp`` (what the kernel computes; the path
    off the chip).  x (B, H, P); ``dt`` (B, H) after its softplus; b, c (B, G,
    N); ``state`` (B, H, N, P) float32; ``live`` (B,) says which rows hold a
    sequence (None: all): an idle row's state stays as it was and its ``y`` is
    zero.  Returns (y (B, H, P) float32, state)."""
    h, g = x.shape[1], b.shape[1]
    dt, xf = dt.astype(jnp.float32), x.astype(jnp.float32)
    heads = lambda u: jnp.repeat(u.astype(jnp.float32), h // g, axis=1)  # noqa: E731
    new = jnp.exp(dt * a_neg)[..., None, None] * state + heads(b)[..., :, None] * (dt[..., None] * xf)[..., None, :]
    y = jnp.einsum("bhn,bhnp->bhp", heads(c), new, precision=_HIGHEST) + d_skip[None, :, None] * xf
    if live is None:
        return y, new
    return jnp.where(_live_rows(live, y), y, 0.0), jnp.where(_live_rows(live, state), new, state)


# ------------------------------------------------------------ decode kernel

STEP_HEADS = 8  # heads a grid step streams: (8, 256, 128) float32 is 1 MB in and 1 MB out


def _step_kernel(order_ref, n_ref, decay_ref, dtx_ref, b_ref, c_ref, s_ref, y_ref, so_ref, bb_ref, cb_ref,
                 *, heads: int, block: int):
    """One (live slot, block of heads of one group): broadcast the group's B
    and C across the lanes once, then for each head scale its state by its
    decay, add ``B (dt x)^T``, write it back in place and read it by C.  The
    grid's first axis walks ``order_ref`` (slot indices, the live ones first)
    as far as ``n_ref[0]``; past it a step does nothing and holds the block of
    the last live step (``_step_call``'s index maps), so nothing is copied."""
    bi, hi = pl.program_id(0), pl.program_id(1)
    n_live = n_ref[0]
    n, p = s_ref.shape[-2:]

    @pl.when((n_live == 0) & (bi == 0) & (hi == 0))
    def _():
        # no live slot: every step holds one block, which is written back
        # when the grid ends, so it must hold what was read
        so_ref[...] = s_ref[...]

    @pl.when(bi < n_live)
    def _():
        # (1, N) down the sublanes, then turned: B and C across every lane
        bb_ref[...] = jnp.broadcast_to(b_ref[0, 0], (p, n)).T
        cb_ref[...] = jnp.broadcast_to(c_ref[0, 0], (p, n)).T
        base = order_ref[bi] * heads + hi * block
        for j in range(block):
            s_new = decay_ref[base + j] * s_ref[0, j] + bb_ref[...] * dtx_ref[0, j]  # (N, P) + (N, P) x (1, P)
            so_ref[0, j] = s_new
            y_ref[0, j] = jnp.sum(s_new * cb_ref[...], axis=0, keepdims=True)


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def step_block(heads: int, groups: int) -> int:
    """Heads a grid step streams: the divisor of a group's heads nearest under ``STEP_HEADS``."""
    rep = heads // groups
    return max(k for k in range(1, min(rep, STEP_HEADS) + 1) if rep % k == 0)


def ssm_step(x, dt, a_neg, b, c, d_skip, state, *, live=None, interpret: bool | None = None):
    """One recurrent step as a Pallas kernel, one call a layer: the state of
    every LIVE slot (``live`` (slots,), None: all of them) is read once and
    written once, in place (``state`` is aliased to the result); an idle
    slot's state is neither read nor written and its ``y`` is zero.  Arguments
    and results as ``ssm_step_reference``."""
    return _step_call(
        x, dt, a_neg, b, c, d_skip, state, jnp.ones((x.shape[0],), bool) if live is None else live,
        block=step_block(x.shape[1], b.shape[1]),
        interpret=_default_interpret() if interpret is None else bool(interpret),
    )


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _step_call(x, dt, a_neg, b, c, d_skip, state, live, *, block: int, interpret: bool):
    """``ssm_step``'s program.  Jitted (not inlined) so that the custom call
    takes this kernel's name in a device trace, ``ssm_step``, whatever its
    call site.

    The live slots reach the kernel by scalar prefetch: ``order`` (the slot
    indices, live ones first) and their number.  A grid step past the last
    live slot maps every operand to the block of the last live step, so the
    pipeline sees an unchanged block, copies nothing in and writes nothing
    back; a slot the grid never visits keeps its bytes where they lie, the
    result being aliased to ``state``.  That also makes both grid axes
    sequential: a second core given the idle tail of an axis would write back
    a block it never computed."""
    bsz, h, p = x.shape
    g, n = b.shape[1:]
    rep = h // g
    live = live.astype(bool)
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    n_live = jnp.sum(live, dtype=jnp.int32).reshape(1)
    dt, xf = dt.astype(jnp.float32), x.astype(jnp.float32)
    decay = jnp.exp(dt * a_neg).reshape(bsz * h)
    dtx = (dt[..., None] * xf)[:, :, None, :]  # (B, H, 1, P)
    last = h // block - 1

    def walk(bi, hi, order_ref, n_ref):
        on = bi < n_ref[0]
        return order_ref[jnp.where(on, bi, jnp.maximum(n_ref[0] - 1, 0))], jnp.where(on, hi, last)

    def head(*at):
        return (*walk(*at), 0, 0)

    def group(*at):
        slot, hi = walk(*at)
        return slot, hi * block // rep, 0, 0

    y, state = pl.pallas_call(
        functools.partial(_step_kernel, heads=h, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bsz, h // block),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),  # the decays, whole
                pl.BlockSpec((1, block, 1, p), head),
                pl.BlockSpec((1, 1, 1, n), group),
                pl.BlockSpec((1, 1, 1, n), group),
                pl.BlockSpec((1, block, n, p), head),
            ],
            out_specs=[
                pl.BlockSpec((1, block, 1, p), head),
                pl.BlockSpec((1, block, n, p), head),
            ],
            scratch_shapes=[pltpu.VMEM((n, p), jnp.float32), pltpu.VMEM((n, p), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, 1, p), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, jnp.float32),
        ],
        input_output_aliases={6: 1},  # the state, counted from the scalar operands
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssm_step",
    )(order, n_live, decay, dtx, b.astype(jnp.float32)[:, :, None, :], c.astype(jnp.float32)[:, :, None, :], state)
    # a slot the grid did not visit has no row of y written: zero, not what the buffer held
    y = jnp.where(_live_rows(live, y), y, 0.0)[:, :, 0, :] + d_skip[None, :, None] * xf * _live_rows(live, xf)
    return y, state


def step_kernel_supported(head_dim: int, state: int) -> bool:
    """The kernel's tiles are whole (8, 128) float32 tiles, and its turn of B
    and C a whole (128, 128) one, at these sizes."""
    return head_dim % 128 == 0 and state % 128 == 0


def step_kernel_runs(head_dim: int, state: int) -> bool:
    """Whether a decode step at these sizes is the kernel, which streams the
    state of the live slots alone (a TPU, whole tiles), or the plain ``jnp``
    step, where XLA reads and writes every slot's.  The model's config asks
    (``decode_streams_live_slots``): the layer chooses its step by the answer
    and the serving engine counts ``slots_streamed`` by it."""
    return jax.default_backend() == "tpu" and step_kernel_supported(head_dim, state)
