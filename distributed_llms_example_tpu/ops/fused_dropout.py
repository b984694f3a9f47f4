"""Fused Pallas TPU dropout — in-kernel RNG, seed-recompute backward.

Why this exists: the trainer fine-tunes with the model's real dropout, and
the bench A/B (BENCH_r05) shows dropout is the single largest measured gap
in the hot path — the dropout-free synthetic step runs ~24% faster than
the with-dropout step.  ``--prng-impl rbg`` proves most of that is mask
*generation* (threefry counter math); the rest is the mask tensor itself:
XLA materializes the random bits to HBM, reads them back in the backward
pass (the mask is a saved residual), and does not fuse the
generate→compare→select→add chain into one pass over the activation.

This module removes the whole tax:

- **In-kernel RNG**: random bits are generated INSIDE the Pallas kernel —
  ``pltpu.prng_seed`` / ``pltpu.prng_random_bits`` (the TPU hardware RNG)
  on real TPUs, seeded deterministically per (seed, tile); a murmur3-style
  counter hash of absolute element positions everywhere else (pure uint32
  VPU ops, identical in interpret and compiled mode, so the fused path is
  testable in the CPU tier-1 suite).  No mask tensor is ever produced by
  threefry or written to HBM.
- **Fused residual add**: the transformer call sites are all
  ``residual + dropout(h)`` — the add rides the same kernel, so the
  activation makes one HBM round-trip instead of three.
- **Seed-recompute backward**: the ``jax.custom_vjp`` saves ONLY the int32
  seed and recomputes the keep-mask in the backward kernel from the same
  (seed, tile) stream — zero residual bytes for dropout, which also makes
  the op remat-transparent (recomputing the forward draws the identical
  mask).

Determinism contract: masks are a pure function of (seed, absolute element
position) for the hash stream, and of (seed, tile index, tile shape) for
the hardware stream — equal seeds give equal masks across calls, forward
and backward always agree.  The bit stream differs from
``jax.random.bernoulli`` (and between the hash/hw streams): selecting the
fused impl trades bit-for-bit reproducibility with the XLA path for speed,
exactly like ``--prng-impl rbg`` already does (README "Dropout & RNG
performance").

Impl selection (``--dropout-impl``): ``auto`` (default) resolves to
``fused`` on TPU backends and ``xla`` elsewhere; the ``xla`` path is
bit-identical to ``flax.linen.Dropout``.  Model code routes every dropout
through the :class:`Dropout` module / :func:`dropout` functional below —
``scripts/repo_lint.py`` forbids raw ``nn.Dropout`` / ``bernoulli`` in
``models/`` and ``train/`` so call sites cannot silently bypass the fused
path.  Attention-probs dropout is folded into the flash-attention kernels
(``ops/flash_attention.py``) using :func:`tile_keep` from here, so the
(B, H, S, S) probs mask never materializes either.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128  # TPU vector lane count; last dim must divide into it

# VMEM budget per tile: block_rows * cols elements.  512K fp32 elements is
# ~2 MB — three buffers (x, residual, out) stay far under the 16 MB stack.
_MAX_TILE_ELEMS = 512 * 1024

# ---------------------------------------------------------------- impl knob

_VALID_IMPLS = ("auto", "fused", "xla")
_DEFAULT_IMPL = "auto"


def set_default_impl(impl: str) -> None:
    """Process-wide default for :class:`Dropout` / :func:`dropout` when the
    caller does not pin one — the trainer sets it from ``--dropout-impl``
    at startup, bench flips it for the fused-vs-xla A/B."""
    global _DEFAULT_IMPL
    if impl not in _VALID_IMPLS:
        raise ValueError(f"dropout impl {impl!r}: must be one of {_VALID_IMPLS}")
    _DEFAULT_IMPL = impl


def default_impl() -> str:
    return _DEFAULT_IMPL


def resolve_impl(impl: str | None = None, backend: str | None = None) -> str:
    """``auto`` → ``fused`` on TPU, ``xla`` elsewhere (the interpreted
    kernel is pure overhead in a real training run; tests pin
    ``impl="fused"`` explicitly to exercise it on CPU)."""
    impl = impl or _DEFAULT_IMPL
    if impl not in _VALID_IMPLS:
        raise ValueError(f"dropout impl {impl!r}: must be one of {_VALID_IMPLS}")
    if impl != "auto":
        return impl
    backend = backend or jax.default_backend()
    return "fused" if backend == "tpu" else "xla"


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


# ------------------------------------------------------------------ the RNG


def keep_threshold(rate: float) -> int:
    """uint32 threshold T such that ``(bits >> 8) < T`` keeps with
    probability ``1 - rate`` (24-bit uniform compare — integer-only keep
    decision, no float conversion of the bits)."""
    return int(round((1.0 - float(rate)) * (1 << 24)))


def _mix32(x):
    """murmur3 finalizer: full-avalanche 32-bit mix (uint32 in/out)."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def _hash_bits(seed, tag_a, tag_b, rows, cols):
    """Counter-based uint32 bit stream: a pure function of (seed, tag pair,
    absolute row, absolute col).  ``rows``/``cols`` are uint32 arrays of the
    tile's absolute element coordinates; scalars are int32-convertible.
    Block-size independent by construction, so forward/backward (and remat
    replays) agree no matter how each pass tiles the array."""
    s = _mix32(
        jnp.uint32(seed).astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
        + jnp.uint32(tag_a).astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
        + jnp.uint32(tag_b).astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D)
    )
    x = (
        rows * jnp.uint32(0x27D4EB2F)
        + cols * jnp.uint32(0x165667B1)
        + s
    )
    return _mix32(x)


def hw_seed_words(seed, tag_a, tag_b, row0, col0):
    """Fold the five values that identify a tile into the TWO int32 words
    the TPU PRNG accepts (Mosaic refuses ``prng_seed`` with more): two
    ``_mix32`` chains over the same values from different constants, so a
    pair collides only where both 32-bit chains do.  A pure function of its
    arguments — scalar uint32 ops only, the same in a kernel and on the
    host — which is what keeps the hardware stream's contract: equal
    (seed, tags, offsets) seed equal bits, and any differing tile or
    ``_shard_seed`` shard seeds an unrelated stream."""
    words = []
    for init in (0x9E3779B9, 0x85EBCA77):
        h = jnp.uint32(init)
        for v in (seed, tag_a, tag_b, row0, col0):
            h = _mix32(h ^ jnp.asarray(v).astype(jnp.uint32))
        words.append(h.astype(jnp.int32))
    return tuple(words)


def tile_keep(seed, tag_a, tag_b, row0, col0, shape, rate: float,
              hw_rng: bool):
    """Keep-mask for one (rows, cols) tile whose top-left element sits at
    absolute (row0, col0) of the (tag_a, tag_b)-indexed plane.

    Called INSIDE Pallas kernels (here and in the flash-attention probs
    dropout).  ``hw_rng=True`` seeds the TPU hardware PRNG per tile —
    deterministic for equal (seed, tags, offsets, shape), compiled-TPU
    only; ``False`` uses the portable counter hash, which is additionally
    tile-independent (same bits for an element no matter the blocking).
    """
    if not hw_rng:
        # the counter-hash stream: the SAME function tests use as the
        # reference, so the in-kernel mask cannot drift from it
        return hash_keep_mask(
            seed, shape, rate, tag_a=tag_a, tag_b=tag_b, row0=row0, col0=col0
        )
    pltpu.prng_seed(*hw_seed_words(seed, tag_a, tag_b, row0, col0))
    bits = pltpu.prng_random_bits(shape)
    if bits.dtype != jnp.uint32:
        bits = pltpu.bitcast(bits, jnp.uint32)
    return (bits >> 8) < jnp.uint32(keep_threshold(rate))


def hash_keep_mask(seed, shape, rate: float, *, tag_a=0, tag_b=0,
                   row0=0, col0=0) -> jnp.ndarray:
    """The hash stream's keep-mask as a plain jnp array — the REFERENCE the
    kernels reproduce tile-by-tile (tests reconstruct the exact in-kernel
    mask with this; it is also what the backward recomputes)."""
    r = jnp.uint32(row0) + jax.lax.broadcasted_iota(jnp.int32, shape, 0).astype(jnp.uint32)
    c = jnp.uint32(col0) + jax.lax.broadcasted_iota(jnp.int32, shape, 1).astype(jnp.uint32)
    bits = _hash_bits(seed, tag_a, tag_b, r, c)
    return (bits >> 8) < jnp.uint32(keep_threshold(rate))


def seed_from_key(key: jax.Array) -> jax.Array:
    """Fold a JAX PRNG key (typed — threefry/rbg — or legacy uint32 vector)
    into the ONE int32 scalar the kernels consume.  Cheap by design: the
    whole point is that per-element randomness comes from the in-kernel
    stream, so the host-side PRNG only ever produces this scalar."""
    data = key
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        data = jax.random.key_data(key)
    data = data.astype(jnp.uint32).ravel()
    seed = jnp.uint32(0x9E3779B9)
    for i in range(int(data.shape[0])):  # static, 2-4 words
        seed = _mix32(seed ^ data[i])
    return seed.astype(jnp.int32)


# ------------------------------------------------------------------ kernels


def _dropout_kernel(*refs, rate: float, hw_rng: bool, block_rows: int,
                    has_res: bool):
    """out = residual + where(keep, x * 1/(1-rate), 0) over one row tile."""
    it = iter(refs)
    seed_ref = next(it)
    x_ref = next(it)
    res_ref = next(it) if has_res else None
    o_ref = next(it)
    i = pl.program_id(0)
    keep = tile_keep(
        seed_ref[0], 0, 0, i * block_rows, 0, x_ref.shape, rate, hw_rng
    )
    inv_keep = jnp.float32(1.0 / (1.0 - rate))
    y = jnp.where(keep, x_ref[...].astype(jnp.float32) * inv_keep, 0.0)
    if res_ref is not None:
        y = res_ref[...].astype(jnp.float32) + y
    o_ref[...] = y.astype(o_ref.dtype)


def _run_dropout(x2, res2, seed, *, rate: float, block_rows: int,
                 hw_rng: bool, interpret: bool):
    rows, cols = x2.shape
    grid = (rows // block_rows,)
    spec = pl.BlockSpec((block_rows, cols), lambda i: (i, 0))
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM), spec]
    args = [seed.reshape(1), x2]
    if res2 is not None:
        in_specs.append(spec)
        args.append(res2)
    return pl.pallas_call(
        functools.partial(
            _dropout_kernel, rate=rate, hw_rng=hw_rng,
            block_rows=block_rows, has_res=res2 is not None,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype),
        interpret=interpret,
    )(*args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _fused(x2, seed, rate, block_rows, hw_rng, interpret):
    return _run_dropout(
        x2, None, seed, rate=rate, block_rows=block_rows,
        hw_rng=hw_rng, interpret=interpret,
    )


def _fused_fwd(x2, seed, rate, block_rows, hw_rng, interpret):
    y = _fused(x2, seed, rate, block_rows, hw_rng, interpret)
    return y, seed  # the ENTIRE residual: one int32 scalar


def _fused_bwd(rate, block_rows, hw_rng, interpret, seed, g):
    # recompute the keep-mask from the seed: dx = where(keep, g/(1-rate), 0)
    # is the same masked-scale as the forward (without residual), so the
    # forward kernel IS the backward kernel
    dx = _run_dropout(
        g, None, seed, rate=rate, block_rows=block_rows,
        hw_rng=hw_rng, interpret=interpret,
    )
    return dx, None


_fused.defvjp(_fused_fwd, _fused_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _fused_res(x2, res2, seed, rate, block_rows, hw_rng, interpret):
    return _run_dropout(
        x2, res2, seed, rate=rate, block_rows=block_rows,
        hw_rng=hw_rng, interpret=interpret,
    )


def _fused_res_fwd(x2, res2, seed, rate, block_rows, hw_rng, interpret):
    y = _fused_res(x2, res2, seed, rate, block_rows, hw_rng, interpret)
    return y, seed


def _fused_res_bwd(rate, block_rows, hw_rng, interpret, seed, g):
    dx = _run_dropout(
        g, None, seed, rate=rate, block_rows=block_rows,
        hw_rng=hw_rng, interpret=interpret,
    )
    return dx, g, None  # d(residual) = g: the add saves nothing either


_fused_res.defvjp(_fused_res_fwd, _fused_res_bwd)


# ----------------------------------------------------------------- plumbing


def _pick_block_rows(rows: int, cols: int) -> int:
    """Largest 8-aligned divisor of ``rows`` whose tile fits the VMEM
    budget; 0 = shape not tileable (caller falls back to XLA)."""
    cap = max(8, (_MAX_TILE_ELEMS // max(cols, 1)) // 8 * 8)
    start = min(rows, cap) // 8 * 8
    for b in range(start, 7, -8):
        if rows % b == 0:
            return b
    return 0


def fused_dropout_supported(shape, *, rate: float | None = None) -> bool:
    """True when the fused kernel can run this activation shape: last dim a
    multiple of the 128-lane vector width, leading dims tiling into
    8-aligned row blocks.  The helper silently uses the XLA path otherwise
    (correctness first; training activation shapes all qualify)."""
    if rate is not None and not 0.0 < float(rate) < 1.0:
        return False
    if len(shape) < 2:
        return False
    cols = int(shape[-1])
    rows = int(math.prod(shape[:-1]))
    if cols % LANES or rows < 8:
        return False
    return _pick_block_rows(rows, cols) > 0


def fused_dropout(
    x: jnp.ndarray,
    seed: jax.Array,
    rate: float,
    *,
    residual: jnp.ndarray | None = None,
    interpret: bool | None = None,
    hw_rng: bool | None = None,
) -> jnp.ndarray:
    """The raw fused op: ``residual + where(keep, x/(1-rate), 0)`` in one
    Pallas pass, mask drawn in-kernel from ``seed``, backward recomputed
    from the same seed (no saved mask).  ``x`` is any >=2-D activation;
    ``residual`` (optional) must match its shape.  Callers wanting
    automatic impl/mesh dispatch use :func:`dropout` / :class:`Dropout`.
    """
    if not 0.0 < float(rate) < 1.0:
        raise ValueError(f"fused_dropout needs 0 < rate < 1, got {rate}")
    if residual is not None and residual.shape != x.shape:
        raise ValueError(
            f"residual shape {residual.shape} != activation shape {x.shape}"
        )
    if interpret is None:
        interpret = _default_interpret()
    if hw_rng is None:
        hw_rng = not interpret
    cols = x.shape[-1]
    rows = int(math.prod(x.shape[:-1]))
    block_rows = _pick_block_rows(rows, cols)
    if cols % LANES or not block_rows:
        raise ValueError(
            f"shape {x.shape} not fused-dropout tileable (cols % {LANES} == 0 "
            "and 8-aligned row blocks required); gate on fused_dropout_supported"
        )
    seed = jnp.asarray(seed, jnp.int32).reshape(())
    x2 = x.reshape(rows, cols)
    if residual is None:
        y2 = _fused(x2, seed, float(rate), block_rows, bool(hw_rng), bool(interpret))
    else:
        y2 = _fused_res(
            x2, residual.reshape(rows, cols).astype(x.dtype), seed,
            float(rate), block_rows, bool(hw_rng), bool(interpret),
        )
    return y2.reshape(x.shape)


def _xla_dropout(x, key, rate, residual=None):
    """Bit-identical to ``flax.linen.Dropout``: threefry/rbg bernoulli mask,
    divide-by-keep scaling — the reproducible reference path."""
    keep_prob = 1.0 - rate
    mask = jax.random.bernoulli(key, keep_prob, x.shape)
    y = jnp.where(mask, x / keep_prob, jnp.zeros_like(x))
    return y if residual is None else residual + y


def _shard_seed(seed, axes):
    """Fold the shard's position on every mesh axis into the seed so shards
    draw independent masks (program ids restart at 0 per shard)."""
    for ax in axes:
        seed = seed * jnp.int32(1000003) + jax.lax.axis_index(ax).astype(jnp.int32)
    return seed


def _fused_run(x, seed, rate, residual, mesh):
    """Run the kernel directly on one device, or per-shard under
    ``shard_map`` on a mesh — the same dispatch shape as
    ``ops.mha.flash_run`` (an opaque pallas call cannot be partitioned by
    GSPMD itself).  Activations are (batch, ..., features): batch over the
    (data, fsdp, expert) axes, lengths over ``sequence`` when it divides,
    features replicated.  Each shard folds its axis indices into the seed.
    Returns None when the mesh splits the shape unevenly (caller falls
    back to XLA)."""
    from jax.sharding import PartitionSpec as P

    from distributed_llms_example_tpu.parallel.activation import BATCH_AXES

    if mesh is None or math.prod(mesh.devices.shape) == 1:
        return fused_dropout(x, seed, rate, residual=residual)
    if mesh.shape.get("tensor", 1) > 1:
        # megatron meshes shard some dropout inputs over ``tensor`` on the
        # FEATURE dim (the fc1/wi MLP intermediates) while others are
        # feature-replicated (the residual stream) — one spec cannot serve
        # both, and declaring features replicated would make GSPMD
        # all-gather the ffn-wide intermediates around every kernel call,
        # costing far more than the dropout tax saved.  Fall back to XLA
        # (elementwise, sharding-preserving) until the helper can see the
        # operand's actual sharding.
        return None
    batch_axes = tuple(a for a in BATCH_AXES if a in mesh.shape)
    batch_shards = math.prod(mesh.shape[a] for a in batch_axes)
    seq_shards = mesh.shape.get("sequence", 1)
    if x.shape[0] % max(batch_shards, 1):
        return None
    seq_axis = None
    if seq_shards > 1 and x.ndim >= 3 and x.shape[1] % seq_shards == 0:
        seq_axis = "sequence"
    spec = P(
        batch_axes or None,
        *([seq_axis] + [None] * (x.ndim - 2) if x.ndim >= 2 else []),
    )
    # per-shard supportability: the kernel sees LOCAL shapes
    local_rows = (
        x.shape[0] // max(batch_shards, 1)
        * int(math.prod(x.shape[1:-1]))
        // (seq_shards if seq_axis else 1)
    )
    if not fused_dropout_supported((local_rows, x.shape[-1]), rate=rate):
        return None
    fold_axes = batch_axes + (("sequence",) if seq_axis else ())

    def run(seed, x, *rest):
        s = _shard_seed(seed, fold_axes)
        return fused_dropout(x, s, rate, residual=rest[0] if rest else None)

    args = (seed, x)
    in_specs = (P(), spec)
    if residual is not None:
        args = (*args, residual)
        in_specs = (*in_specs, spec)
    return jax.shard_map(
        run, mesh=mesh, in_specs=in_specs, out_specs=spec, check_vma=False
    )(*args)


def dropout(
    x: jnp.ndarray,
    key: jax.Array,
    rate: float,
    *,
    residual: jnp.ndarray | None = None,
    impl: str | None = None,
) -> jnp.ndarray:
    """THE shared dropout entry point (functional form) — every dropout in
    ``models/`` and ``train/`` routes through here or :class:`Dropout`
    (enforced by scripts/repo_lint.py rule 5).

    Resolves the impl (``--dropout-impl``; ``auto`` = fused on TPU), then:
    ``fused`` runs the Pallas kernel — directly, or per-shard under the
    ambient mesh — with the key folded to the in-kernel seed; shapes or
    contexts the kernel cannot serve (uneven shard splits, sub-lane
    feature dims, the pipeline's partial-manual regions where no mesh
    context exists) silently use the XLA path, mirroring how attention
    falls back from flash.  ``rate<=0`` or ``rate>=1`` edge cases match
    ``nn.Dropout`` semantics."""
    if rate <= 0.0:
        return x if residual is None else residual + x
    if rate >= 1.0:
        z = jnp.zeros_like(x)
        return z if residual is None else residual + z
    if resolve_impl(impl) == "fused" and fused_dropout_supported(x.shape, rate=rate):
        from distributed_llms_example_tpu.parallel.activation import current_mesh

        mesh = current_mesh()
        if mesh is None and jax.device_count() > 1:
            # multi-device jit without a mesh context (e.g. inside the
            # pipeline's partial-manual shard_map): an opaque pallas call
            # would force GSPMD to gather — same rule as flash attention
            return _xla_dropout(x, key, rate, residual)
        out = _fused_run(x, seed_from_key(key), rate, residual, mesh)
        if out is not None:
            return out
    return _xla_dropout(x, key, rate, residual)


import flax.linen as nn  # noqa: E402  (after the kernel section on purpose)


class Dropout(nn.Module):
    """Drop-in for ``flax.linen.Dropout`` routed through the shared helper:
    same ``"dropout"`` rng collection, same no-param tree, same
    ``deterministic`` contract — plus ``residual`` for the fused
    residual-add (``dropout(h, residual=r)`` == ``r + dropout(h)``, in ONE
    kernel pass on the fused path).  ``impl=None`` follows the process
    default (``--dropout-impl``)."""

    rate: float
    impl: str | None = None

    @nn.compact
    def __call__(self, x, deterministic: bool = True, *, residual=None):
        if deterministic or self.rate <= 0.0:
            return x if residual is None else residual + x
        return dropout(
            x, self.make_rng("dropout"), self.rate,
            residual=residual, impl=self.impl,
        )
