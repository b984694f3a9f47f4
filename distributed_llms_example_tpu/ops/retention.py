"""Power retention (degree 2): one layer's mathematics in its three equal forms.

A power-retention layer remembers a sequence in a matrix whose size does not
depend on the context.  With head size ``d``, a gate ``g_t`` in (0, 1] a KV
head a token, ``G_i = sum_{l<=i} log g_l`` and a query head ``h`` of KV head
``kv(h)`` (grouped heads share the keys, the values, the gate and so the
state):

* attention form: ``a_ij = (q_i . k_j / sqrt(d))^2 * exp(G_i - G_j)`` for
  ``j <= i``, else 0; ``y_i = sum_j a_ij v_j / (sum_j a_ij + eps)``.  The
  degree is even, so every weight is >= 0 and no softmax is needed;
* recurrent form: ``S_i = g_i S_{i-1} + phi(k_i) v_i^T``,
  ``z_i = g_i z_{i-1} + phi(k_i)``,
  ``y_i = phi(q_i)^T S_i / (phi(q_i)^T z_i + eps)``, where
  ``phi(a) . phi(b) = (a . b / sqrt(d))^2``;
* chunked form: the attention form inside a chunk plus ``phi(Q) S`` from the
  state the chunks before left.

**The feature map and the state's layout** (a design decision).  ``(a . b)^2
= sum_{m,n} a_m a_n b_m b_n`` is symmetric in (m, n), so the distinct products
``a_m a_n`` (``d (d + 1) / 2`` = 8,256 at d = 128) carry all of it at half the
bytes of the full ``d^2`` = 16,384; a decode round is bound by reading and
writing the state, so the symmetric half is taken.  The pairs are laid out by
ROTATION so that a row of features is a whole 128-lane vector: row ``r`` of
``phi(a)`` is ``c_r * a * roll(a, -r)`` (entry ``m`` = ``a_m a_{(m + r) mod
d}``), ``r = 0 .. d/2``.  Every unordered pair {m, n} lies at exactly one
circular distance ``r <= d/2``; at ``0 < r < d/2`` it appears once (``c_r =
sqrt(2/d)``), at ``r = 0`` it is the square (``c_0 = 1/sqrt(d)``), and at ``r =
d/2`` the rotation meets itself and each pair appears twice (``c = 1/sqrt(d)``
each, the same sum).  So the state holds ``d/2 + 1`` = 65 rotations x 128 lanes
= 8,320 feature rows for the 8,256 the mathematics needs (0.8 % more), and no
tile of it is ragged.  A KV head's state is ``(rotations, d_v, d)``: feature
row ``r``, then the value's component on the sublanes, then the feature's lane
— what the decode kernel updates with one column (v) times one row (phi(k)_r)
and reads with one row (phi(q)_r) a query head, never a lane broadcast of a
feature.  The normaliser ``z`` is ``(rotations, d)``.

**The state's dtype is float32**: it is a running sum of up to 32,768 decayed
terms that is read against a normaliser of the same kind, and the comparison
with the float32 reference (``tests/test_retention.py``: the state products in
bfloat16 fail it) does not allow less.  ``phi``'s entries are products of two
bfloat16 numbers, which float32 holds exactly; the products that build and
read the state run at ``Precision.HIGH`` (three bfloat16 passes).

Every function here takes q, k (after the head norms and RoPE), v and
``log_g`` and knows nothing of a model; ``models/brumby.py`` is the caller.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

EPS = 1e-6
CHUNK = 1024
# from this many tokens on a prompt takes the chunked form, whose work is
# linear in T where the attention form's is quadratic.  By the products alone
# the two break even at rotations x d + CHUNK = 9,344 tokens; on a v5e a row's
# layer took 11.6 ms against 19.5 at 4,096 tokens and 45.9 against 38.5 at
# 8,192 (attention form and state against the chunked scan; PERF.md, PR 35)
CHUNKED_FROM = 8192
_HIGH = jax.lax.Precision.HIGH


def rotations(d: int) -> int:
    """Feature rows of ``d`` lanes each in ``phi`` (module docstring)."""
    if d % 2:
        raise ValueError(f"the rotation layout needs an even head size, got {d}")
    return d // 2 + 1


def state_shapes(batch: int, kv_heads: int, d: int, d_v: int) -> tuple[tuple, tuple]:
    """((state), (normaliser)) shapes of ``batch`` sequences of one layer."""
    r = rotations(d)
    return (batch, kv_heads, r, d_v, d), (batch, kv_heads, r, d)


@functools.lru_cache(maxsize=None)
def _rotation_matrix(d: int) -> np.ndarray:
    """(d, rotations x d) of 0 and 1: ``a @ P`` lays ``a``'s rotations side by side."""
    r = rotations(d)
    p = np.zeros((d, r * d), np.float32)
    for i in range(r):
        p[(np.arange(d) + i) % d, i * d + np.arange(d)] = 1.0
    return p


def phi(a: jnp.ndarray) -> jnp.ndarray:
    """(..., d) -> (..., d/2 + 1, d) float32 with ``phi(a) . phi(b) = (a . b)^2 / d``.
    The rotations are one product with a 0/1 matrix (exact: every sum has one
    term), which the chip does in 11 us a head where 65 slices of a (T, 65, d)
    buffer are 65 passes over it."""
    d = a.shape[-1]
    r = rotations(d)
    c = np.full((r,), math.sqrt(2.0 / d), np.float32)
    c[[0, r - 1]] = 1.0 / math.sqrt(d)
    exact = a.dtype == jnp.bfloat16  # a bfloat16 operand times 1 needs one pass
    rolled = jnp.dot(
        a, jnp.asarray(_rotation_matrix(d), a.dtype), preferred_element_type=jnp.float32,
        precision=None if exact else jax.lax.Precision.HIGHEST,
    ).reshape(*a.shape[:-1], r, d)
    return a.astype(jnp.float32)[..., None, :] * rolled * c[:, None]


def _over_heads(fn, per_head: tuple, valid):
    """``fn(*per_head, valid)`` over every (sequence, KV head) in turn
    (``lax.map``): ``per_head`` arrays are (B, KV, ...), ``valid`` (B, T) is
    what a sequence's heads share.  A prompt's temporaries (a group's (T, T)
    weights, ``phi(K)``: 34 MB a head at 1,024 tokens) are then one head's
    whatever the wave's rows; whole, a four-row wave at the published widths
    asks 13 GB of them (the program compiled for a described v5e)."""
    b, kv = per_head[0].shape[:2]
    flat = tuple(a.reshape(b * kv, *a.shape[2:]) for a in per_head) + (jnp.repeat(valid, kv, axis=0),)
    out = jax.lax.map(lambda xs: fn(*xs), flat)
    return jax.tree.map(lambda y: y.reshape(b, kv, *y.shape[1:]), out)


def _head_attention(q, k, v, g_cum, valid):
    """One KV head's attention form: q (rep, T, d), k (T, d), v (T, d_v),
    ``g_cum`` (T,) = G, ``valid`` (T,) -> numerator (rep, T, d_v) and
    denominator (rep, T), float32."""
    d, t = q.shape[-1], q.shape[-2]
    s = jnp.einsum("rid,jd->rij", q, k, preferred_element_type=jnp.float32) / math.sqrt(d)
    seen = jnp.tril(jnp.ones((t, t), bool)) & valid[None, :].astype(bool)
    decay = jnp.exp(jnp.where(seen, g_cum[:, None] - g_cum[None, :], -jnp.inf))
    a = jnp.square(s) * decay
    return jnp.einsum("rij,jv->riv", a, v.astype(jnp.float32), precision=_HIGH), jnp.sum(a, axis=-1)


def _head_state(k, v, log_g, valid):
    """One KV head's state after its valid tokens: k (T, d), v (T, d_v),
    ``log_g`` (T,) (0 at a padded position) -> ((R, d_v, d), (R, d)), and the
    whole decay ``exp(G_T)``."""
    g_cum = jnp.cumsum(log_g)
    w = jnp.exp(g_cum[-1] - g_cum) * valid.astype(jnp.float32)
    pk = phi(k)  # (T, R, d)
    state = jnp.einsum("jv,jrm->rvm", w[:, None] * v.astype(jnp.float32), pk, precision=_HIGH)
    return state, jnp.einsum("j,jrm->rm", w, pk, precision=_HIGH), jnp.exp(g_cum[-1])


def _grouped(q, kv_heads: int):
    """(B, H, T, d) -> (B, KV, H/KV, T, d): a KV head's query heads side by side."""
    b, h, t, d = q.shape
    return q.reshape(b, kv_heads, h // kv_heads, t, d)


def _masked(log_g, valid):
    """(float32 gates with padding's set to 1 (log 0), ``valid`` (B, T), all ones where none was given)."""
    if valid is None:
        valid = jnp.ones((log_g.shape[0], log_g.shape[-1]), jnp.int32)
    return log_g.astype(jnp.float32) * valid[:, None, :].astype(jnp.float32), valid


def retention_attention(q, k, v, log_g, valid=None, eps: float = EPS):
    """The attention form.  q (B, H, T, d); k, v (B, KV, T, d); ``log_g`` (B,
    KV, T) float32; ``valid`` (B, T), 0 where a position is padding (its key
    reaches nobody and its gate is 1).  Returns (B, H, T, d_v) float32."""
    qg, (log_g, valid) = _grouped(q, k.shape[1]), _masked(log_g, valid)

    def head(qh, kh, vh, gh, ok):
        num, den = _head_attention(qh, kh, vh, jnp.cumsum(gh), ok)
        return num / (den[..., None] + eps)

    y = _over_heads(head, (qg, k, v, log_g), valid)
    return y.reshape(q.shape[0], q.shape[1], q.shape[2], v.shape[-1])


def retention_state(k, v, log_g, valid=None):
    """What a prompt leaves behind: ``S = phi(K)^T diag(exp(G_T - G_j)) V`` and
    ``z = phi(K)^T exp(G_T - G_j)``, one product a KV head, of the valid
    positions only.  Returns (state (B, KV, R, d_v, d), normaliser (B, KV, R,
    d)), float32."""
    log_g, valid = _masked(log_g, valid)
    state, norm, _ = _over_heads(_head_state, (k, v, log_g), valid)
    return state, norm


def _head_chunked(q, k, v, log_g, valid, eps: float, chunk: int):
    """One KV head's chunked form: a scan over chunks of ``chunk`` tokens."""
    rep, t, d = q.shape
    n = t // chunk
    split = lambda x, axis: jnp.moveaxis(  # noqa: E731 — the chunk axis first, for scan
        x.reshape(x.shape[:axis] + (n, chunk) + x.shape[axis + 1:]), axis, 0)

    def one(carry, xs):
        state, norm = carry
        qc, kc, vc, gc, ok = xs
        g_cum = jnp.cumsum(gc)
        num, den = _head_attention(qc, kc, vc, g_cum, ok)
        pq = phi(qc) * jnp.exp(g_cum)[None, :, None, None]  # (rep, c, R, d)
        num = num + jnp.einsum("rcfm,fvm->rcv", pq, state, precision=_HIGH)
        den = den + jnp.einsum("rcfm,fm->rc", pq, norm, precision=_HIGH)
        s_c, z_c, total = _head_state(kc, vc, gc, ok)
        return (total * state + s_c, total * norm + z_c), num / (den[..., None] + eps)

    r = rotations(d)
    zero = (jnp.zeros((r, v.shape[-1], d), jnp.float32), jnp.zeros((r, d), jnp.float32))
    (state, norm), y = jax.lax.scan(one, zero, (split(q, 1), split(k, 0), split(v, 0), split(log_g, 0), split(valid, 0)))
    return jnp.moveaxis(y, 0, 1).reshape(rep, t, v.shape[-1]), state, norm  # (n, rep, c, d_v) -> (rep, T, d_v)


def retention_chunked(q, k, v, log_g, valid=None, eps: float = EPS, chunk: int = CHUNK):
    """The chunked form as a ``lax.scan`` over chunks of ``chunk`` tokens in
    plain ``jnp`` (no kernel yet: ROADMAP B-I): inside a chunk the attention
    form, across chunks ``phi(Q) S`` of the state carried.  T must divide into
    chunks.  Returns (y (B, H, T, d_v), state, normaliser) float32."""
    if q.shape[2] % chunk:
        raise ValueError(f"retention_chunked: {q.shape[2]} tokens do not divide into chunks of {chunk}")
    qg, (log_g, valid) = _grouped(q, k.shape[1]), _masked(log_g, valid)
    y, state, norm = _over_heads(functools.partial(_head_chunked, eps=eps, chunk=chunk), (qg, k, v, log_g), valid)
    return y.reshape(q.shape[0], q.shape[1], q.shape[2], v.shape[-1]), state, norm


def retention_prefill(q, k, v, log_g, valid=None, eps: float = EPS):
    """A prompt from an empty state: (y, state, normaliser).  The form is
    chosen from the shape: the attention form and one state product below
    ``CHUNKED_FROM`` tokens, the chunked scan from there on where the length
    divides into chunks."""
    t = q.shape[2]
    if t >= CHUNKED_FROM and t % CHUNK == 0:
        return retention_chunked(q, k, v, log_g, valid, eps, CHUNK)
    qg, (log_g, valid) = _grouped(q, k.shape[1]), _masked(log_g, valid)

    def head(qh, kh, vh, gh, ok):
        num, den = _head_attention(qh, kh, vh, jnp.cumsum(gh), ok)
        state, norm, _ = _head_state(kh, vh, gh, ok)
        return num / (den[..., None] + eps), state, norm

    y, state, norm = _over_heads(head, (qg, k, v, log_g), valid)
    return y.reshape(q.shape[0], q.shape[1], q.shape[2], v.shape[-1]), state, norm


# ------------------------------------------------------------ decode step


def _live_rows(live, like):
    """``live`` (slots,) as a boolean that broadcasts over ``like``'s trailing axes."""
    return live.astype(bool).reshape(live.shape + (1,) * (like.ndim - 1))


def retention_step_reference(q, k, v, log_g, state, norm, *, live=None, eps: float = EPS):
    """One recurrent step in plain ``jnp`` (what the kernel computes; the
    path off the chip).  q (B, H, d); k, v (B, KV, d); ``log_g`` (B, KV);
    ``live`` (B,) says which rows hold a sequence (None: all): an idle row's
    state and normaliser stay as they were and its ``y`` is zero.  Returns
    (y (B, H, d_v) float32, state, normaliser)."""
    b, h, d = q.shape
    kv = k.shape[1]
    g = jnp.exp(log_g.astype(jnp.float32))
    pk = phi(k)  # (B, KV, R, d)
    new_state = g[..., None, None, None] * state + v.astype(jnp.float32)[:, :, None, :, None] * pk[:, :, :, None, :]
    new_norm = g[..., None, None] * norm + pk
    pq = phi(q.reshape(b, kv, h // kv, d))  # (B, KV, rep, R, d)
    num = jnp.einsum("bgrfm,bgfvm->bgrv", pq, new_state, precision=jax.lax.Precision.HIGHEST)
    den = jnp.einsum("bgrfm,bgfm->bgr", pq, new_norm, precision=jax.lax.Precision.HIGHEST)
    y = (num / (den[..., None] + eps)).reshape(b, h, v.shape[-1])
    if live is None:
        return y, new_state, new_norm
    return (jnp.where(_live_rows(live, y), y, 0.0), jnp.where(_live_rows(live, state), new_state, state),
            jnp.where(_live_rows(live, norm), new_norm, norm))


STEP_ROTATIONS = 13  # feature rows a grid step streams: a (13, 128, 128) float32 tile is 852 KB
STEP_ROWS = 32  # value rows (sublanes) held in registers while a tile's rotations pass


def _step_kernel(order_ref, n_ref, g_ref, v_ref, pk_ref, pq_ref, s_ref, z_ref, y_ref, so_ref, zo_ref,
                 acc_ref, den_ref, vb_ref, *, kv_heads: int, tile: int, rows: int, eps: float):
    """One (live slot, KV head, tile of rotations): scale the tile by the gate,
    add the rank-one update ``v phi(k)_r^T``, write it back in place, and add
    its part of every query head's read ``phi(q)_r . S_r`` to the accumulators;
    the last tile reduces them over the lanes and normalises.  The grid's first
    axis walks ``order_ref`` (slot indices, the live ones first) as far as
    ``n_ref[0]``; past it a step does nothing and holds the block of the last
    live step (``_step_call``'s index maps), so nothing is copied either way."""
    b, h, ri = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    rep, d_v, d = acc_ref.shape
    n_live = n_ref[0]

    @pl.when((n_live == 0) & (b == 0) & (h == 0) & (ri == 0))
    def _():
        # no live slot: every step holds one block (slot 0's last), which is
        # written back when the grid ends, so it must hold what was read
        so_ref[...] = s_ref[...]
        zo_ref[...] = z_ref[...]

    @pl.when(b < n_live)
    def _():
        g = g_ref[order_ref[b] * kv_heads + h]

        @pl.when(ri == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            den_ref[...] = jnp.zeros_like(den_ref)
            vb_ref[...] = jnp.broadcast_to(v_ref[0, 0], (d_v, d))  # v down the sublanes, across every lane

        den = den_ref[...]
        for r in range(tile):
            pk = pk_ref[0, 0, r]  # (1, d)
            z_new = g * z_ref[0, 0, r] + pk
            zo_ref[0, 0, r] = z_new
            den = den + pq_ref[0, 0, r] * z_new  # (rep, d)
        den_ref[...] = den

        def chunk(c, carry):
            sl = pl.ds(pl.multiple_of(c * rows, rows), rows)
            vb = vb_ref[sl, :]
            accs = [acc_ref[i, sl, :] for i in range(rep)]
            for r in range(tile):
                s_new = g * s_ref[0, 0, r, sl, :] + vb * pk_ref[0, 0, r]
                so_ref[0, 0, r, sl, :] = s_new
                for i in range(rep):
                    accs[i] = accs[i] + s_new * pq_ref[0, 0, r, pl.ds(i, 1), :]
            for i in range(rep):
                acc_ref[i, sl, :] = accs[i]
            return carry

        jax.lax.fori_loop(0, d_v // rows, chunk, 0)

        @pl.when(ri == pl.num_programs(2) - 1)
        def _():
            num = jnp.sum(acc_ref[...], axis=-1)  # (rep, d_v)
            total = jnp.sum(den_ref[...], axis=-1, keepdims=True)  # (rep, 1)
            y_ref[0, 0] = num / (total + eps)


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def step_tile(r: int) -> int:
    """Rotations a grid step streams: the divisor of ``r`` nearest under ``STEP_ROTATIONS``."""
    return max(t for t in range(1, min(r, STEP_ROTATIONS) + 1) if r % t == 0)


def retention_step(q, k, v, log_g, state, norm, *, live=None, eps: float = EPS, interpret: bool | None = None):
    """One recurrent step as a Pallas kernel, one call a layer: the state of
    every LIVE slot (``live`` (slots,), None: all of them) is read once and
    written once, in place (``state`` and ``norm`` are aliased to the results),
    all the query heads of a KV head inside the call; an idle slot's state is
    neither read nor written and its ``y`` is zero.  Arguments and results as
    ``retention_step_reference``."""
    d, d_v = q.shape[-1], v.shape[-1]
    return _step_call(
        q, k, v, log_g, state, norm, jnp.ones((q.shape[0],), bool) if live is None else live,
        eps=float(eps), tile=step_tile(rotations(d)), rows=STEP_ROWS if d_v % STEP_ROWS == 0 else d_v,
        interpret=_default_interpret() if interpret is None else bool(interpret),
    )


@functools.partial(jax.jit, static_argnames=("eps", "tile", "rows", "interpret"))
def _step_call(q, k, v, log_g, state, norm, live, *, eps: float, tile: int, rows: int, interpret: bool):
    """``retention_step``'s program, every choice made from the shapes passed
    in as a static.  Jitted (not inlined) so that the custom call takes this
    kernel's name in a device trace, ``retention_step``, whatever its call site.

    The live slots reach the kernel by scalar prefetch: ``order`` (the slot
    indices, live ones first) and their number.  A grid step past the last live
    slot maps every operand to the block of the last live step (last live slot,
    last KV head, last tile), so the pipeline sees an unchanged block, copies
    nothing in and writes nothing back; a slot the grid never visits keeps its
    bytes where they lie, the results being aliased to ``state`` and ``norm``.
    That also makes every grid axis sequential: a second core given the idle
    tail of an axis would write back a block it never computed."""
    b, h, d = q.shape
    kv, d_v = k.shape[1], v.shape[-1]
    rep, r = h // kv, rotations(d)
    live = live.astype(bool)
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    n_live = jnp.sum(live, dtype=jnp.int32).reshape(1)
    g = jnp.exp(log_g.astype(jnp.float32)).reshape(b * kv)
    pk = phi(k)[:, :, :, None, :]  # (B, KV, R, 1, d)
    pq = jnp.swapaxes(phi(q.reshape(b, kv, rep, d)), 2, 3)  # (B, KV, R, rep, d)
    z = norm[:, :, :, None, :]
    last_h, last_r = kv - 1, r // tile - 1

    def walk(bi, hi, ri, order_ref, n_ref):
        on = bi < n_ref[0]
        slot = order_ref[jnp.where(on, bi, jnp.maximum(n_ref[0] - 1, 0))]
        return slot, jnp.where(on, hi, last_h), jnp.where(on, ri, last_r)

    def head(*at):
        slot, hi, _ = walk(*at)
        return slot, hi, 0, 0

    def rot4(*at):
        return (*walk(*at), 0, 0)

    y, state, z = pl.pallas_call(
        functools.partial(_step_kernel, kv_heads=kv, tile=tile, rows=rows, eps=eps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, kv, r // tile),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),  # the gates, whole
                pl.BlockSpec((1, 1, d_v, 1), head),
                pl.BlockSpec((1, 1, tile, 1, d), rot4),
                pl.BlockSpec((1, 1, tile, rep, d), rot4),
                pl.BlockSpec((1, 1, tile, d_v, d), rot4),
                pl.BlockSpec((1, 1, tile, 1, d), rot4),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, rep, d_v), head),
                pl.BlockSpec((1, 1, tile, d_v, d), rot4),
                pl.BlockSpec((1, 1, tile, 1, d), rot4),
            ],
            scratch_shapes=[
                pltpu.VMEM((rep, d_v, d), jnp.float32),
                pltpu.VMEM((rep, d), jnp.float32),
                pltpu.VMEM((d_v, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, kv, rep, d_v), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, jnp.float32),
            jax.ShapeDtypeStruct(z.shape, jnp.float32),
        ],
        input_output_aliases={6: 1, 7: 2},  # state and normaliser, counted from the scalar operands
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="retention_step",
    )(order, n_live, g, v.astype(jnp.float32)[..., None], pk, pq, state, z)
    # a slot the grid did not visit has no row of y written: zero, not what the buffer held
    y = jnp.where(_live_rows(live, y), y, 0.0)
    return y.reshape(b, h, d_v), state, z[:, :, :, 0, :]


def step_kernel_supported(d: int, d_v: int) -> bool:
    """The kernel's tiles are whole (8, 128) float32 tiles at these head sizes."""
    return d % 128 == 0 and d_v % 8 == 0


def step_kernel_runs(d: int, d_v: int) -> bool:
    """Whether a decode step at these head sizes is the kernel, which streams
    the state of the live slots alone (a TPU, whole tiles), or the plain
    ``jnp`` step, where XLA reads and writes every slot's.  The model's config
    asks (``decode_streams_live_slots``): the layer chooses its step by the
    answer and the serving engine counts ``slots_streamed`` by it."""
    return jax.default_backend() == "tpu" and step_kernel_supported(d, d_v)
