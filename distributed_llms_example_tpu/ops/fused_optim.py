"""Fused Pallas clip+AdamW(+weight-decay+health) optimizer apply.

Why this exists: BENCH_7B_r05 pins 99.3 ms/step of non-layer overhead on
the 7B recipe, and a slice of it is the optimizer tail — the optax chain
(`clip_by_global_norm` → `scale_by_adam` → `add_decayed_weights` →
`scale_by_learning_rate` → `apply_updates`) lowers to MANY small HLO ops
per parameter leaf, each reading and writing param-sized fp32 buffers:
mu/nu EMA updates, bias-corrected division, sqrt, weight decay, lr scale,
and the final add each make their own pass unless XLA happens to fuse
them.  This module collapses the whole per-leaf update into ONE Pallas
kernel pass: each tile reads (param, mu, nu, grad) once, applies
clip-scale → AdamW → weight decay → lr in registers, and writes (param,
mu, nu) back IN PLACE (``input_output_aliases`` — no fp32 param copy),
emitting the health partial sums (param/update sum-of-squares, non-finite
grad count) from the same pass so ``--health`` costs no extra reduction
pass either.

Bit-equivalence contract: the kernel replicates the optax 0.2.x op
sequence EXACTLY, elementwise —

    gc  = select(gnorm < max_norm, g, (g / gnorm) * max_norm)
    mu' = (1-b1)*gc + b1*mu            nu' = (1-b2)*gc^2 + b2*nu
    u   = (mu'/bc1) / (sqrt(nu'/bc2) + eps)
    u   = u + wd*p        (decay-masked leaves only)
    u   = (-lr) * u       p' = p + u

with the scalars (global grad-norm, clip trigger, bias corrections,
-lr) computed OUTSIDE the kernel by the very same jnp expressions optax
uses.  Elementwise IEEE ops are deterministic, so the fused apply equals
the optax chain's output up to XLA's per-compilation FLOAT CONTRACTION —
the backend may fuse a multiply-add into an FMA in one program and not
the other, measured at ≤1 element per few thousand and a few ulp after
cancellation (pinned by tests/test_fused_optim.py; the opt-state pytree
structure and integer counts are exact, and the per-leaf health SUMS may
differ in reduction order — they are metrics, not state).  The global
grad-norm itself is the standard two-stage reduction: per-shard partial
sum-of-squares, then the cross-shard psum GSPMD inserts — the
weight-update-sharding recipe of arXiv:2004.13336, same as the optax
path.

Sharding: the apply is purely elementwise per leaf, so each leaf runs
per-shard under ``jax.shard_map`` with the leaf's OWN param
PartitionSpec (params, mu, nu and the grad accumulators share it by the
PR 5 mirror contract — ``analysis/spec_lint.py`` lints both mirrors).
Health partial sums psum over exactly the leaf's sharded axes.  Leaves
the kernel cannot tile (element count not a multiple of 8·128, non-f32
dtypes) take :func:`adamw_leaf_reference` — the same formulas in plain
jnp under the same contract, partitioned by GSPMD like any
elementwise op.

Impl selection mirrors ``ops/fused_dropout.py``: ``--optim-impl auto``
resolves to ``fused`` on TPU backends and ``xla`` (the optax chain)
elsewhere; tests force ``fused`` to exercise the interpret-mode kernel
on CPU.  The opt-state layout is UNTOUCHED — ``train/optim.py`` parses
and rebuilds the standard optax pytree, so checkpoints round-trip
freely between impls (test-pinned).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128  # TPU vector lane count
SUBLANES = 8  # fp32 sublane alignment

# VMEM tile budget: 7 live buffers (4 in / 3 out) per tile; 128K fp32
# elements each keeps the working set ~3.5 MB, far under the 16 MB stack.
_MAX_TILE_ELEMS = 128 * 1024

# scalar-vector layout (SMEM input): the traced per-step scalars the
# kernel consumes.  Indices are shared with the reference path.
_S_GNORM, _S_TRIGGER, _S_BC1, _S_BC2, _S_NEG_LR = 0, 1, 2, 3, 4
SCALARS = 8  # padded so the SMEM vector stays one sublane

# per-leaf stats-vector layout (SMEM output): health partial sums
# produced in the same kernel pass.
STAT_P_SUMSQ, STAT_U_SUMSQ, STAT_NONFINITE = 0, 1, 2
STATS = 4

# ---------------------------------------------------------------- impl knob

_VALID_IMPLS = ("auto", "fused", "xla")
_DEFAULT_IMPL = "auto"


def set_default_impl(impl: str) -> None:
    """Process-wide default for the optimizer apply when the caller does
    not pin one — the trainer sets it from ``--optim-impl`` at startup,
    bench flips it for the fused-vs-xla A/B."""
    global _DEFAULT_IMPL
    if impl not in _VALID_IMPLS:
        raise ValueError(f"optim impl {impl!r}: must be one of {_VALID_IMPLS}")
    _DEFAULT_IMPL = impl


def default_impl() -> str:
    return _DEFAULT_IMPL


def resolve_impl(impl: str | None = None, backend: str | None = None) -> str:
    """``auto`` → ``fused`` on TPU, ``xla`` elsewhere (the interpreted
    kernel is pure overhead in a real CPU run; tests pin ``fused``
    explicitly to exercise it)."""
    impl = impl or _DEFAULT_IMPL
    if impl not in _VALID_IMPLS:
        raise ValueError(f"optim impl {impl!r}: must be one of {_VALID_IMPLS}")
    if impl != "auto":
        return impl
    backend = backend or jax.default_backend()
    return "fused" if backend == "tpu" else "xla"


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


# ------------------------------------------------------------------- tiling


def _pick_cols(total: int) -> int:
    """Widest 128-multiple divisor of ``total`` ≤ 2048 whose row count
    stays 8-aligned — the apply is elementwise, so ANY (rows, cols)
    factorization of the flattened leaf is valid."""
    for cols in range(2048, 0, -LANES):
        if total % cols == 0 and (total // cols) % SUBLANES == 0:
            return cols
    return 0


def _pick_block_rows(rows: int, cols: int) -> int:
    cap = max(SUBLANES, (_MAX_TILE_ELEMS // max(cols, 1)) // SUBLANES * SUBLANES)
    start = min(rows, cap) // SUBLANES * SUBLANES
    for b in range(start, SUBLANES - 1, -SUBLANES):
        if rows % b == 0:
            return b
    return 0


def fused_adamw_supported(n_elems: int, dtype=jnp.float32) -> bool:
    """True when the kernel can serve a leaf (or leaf-shard) of this
    size: fp32, flattenable into 8-aligned rows of 128-aligned lanes.
    Unsupported leaves take the jnp reference path (same op
    sequence, same contract)."""
    if jnp.dtype(dtype) != jnp.float32:
        return False
    n = int(n_elems)
    if n <= 0 or n % (SUBLANES * LANES):
        return False
    cols = _pick_cols(n)
    return cols > 0 and _pick_block_rows(n // cols, cols) > 0


# ------------------------------------------------------------------- kernel


def _adamw_kernel(
    scal_ref, p_ref, mu_ref, nu_ref, g_ref, po_ref, muo_ref, nuo_ref,
    stats_ref, *, b1: float, b2: float, eps: float, max_norm: float,
    wd: float, clip: bool,
):
    """One row-tile of the fused apply.  All elementwise ops follow the
    optax op sequence exactly (module docstring) so the tile's output
    bits match the optax chain's; the stats accumulate across the
    sequential grid into the SMEM vector."""
    i = pl.program_id(0)
    g = g_ref[...]
    if clip:
        gnorm = scal_ref[_S_GNORM]
        trigger = scal_ref[_S_TRIGGER]
        # optax clip_by_global_norm: select(trigger, t, (t/g_norm)*max_norm)
        g = jnp.where(trigger != 0.0, g, (g / gnorm) * max_norm)
    p = p_ref[...]
    mu = (1 - b1) * g + b1 * mu_ref[...]
    nu = (1 - b2) * (g * g) + b2 * nu_ref[...]
    mu_hat = mu / scal_ref[_S_BC1]
    nu_hat = nu / scal_ref[_S_BC2]
    u = mu_hat / (jnp.sqrt(nu_hat) + eps)
    if wd:
        u = u + wd * p
    u = scal_ref[_S_NEG_LR] * u
    po_ref[...] = p + u
    muo_ref[...] = mu
    nuo_ref[...] = nu
    # health partial sums, same pass: param/update sum-of-squares and the
    # non-finite count of the (pre-clip) normalized gradient
    p_ss = jnp.sum(p * p)
    u_ss = jnp.sum(u * u)
    nf = jnp.sum((~jnp.isfinite(g_ref[...])).astype(jnp.float32))

    @pl.when(i == 0)
    def _():
        stats_ref[STAT_P_SUMSQ] = 0.0
        stats_ref[STAT_U_SUMSQ] = 0.0
        stats_ref[STAT_NONFINITE] = 0.0
        stats_ref[STATS - 1] = 0.0

    stats_ref[STAT_P_SUMSQ] = stats_ref[STAT_P_SUMSQ] + p_ss
    stats_ref[STAT_U_SUMSQ] = stats_ref[STAT_U_SUMSQ] + u_ss
    stats_ref[STAT_NONFINITE] = stats_ref[STAT_NONFINITE] + nf


def fused_adamw_leaf(
    p: jnp.ndarray, mu: jnp.ndarray, nu: jnp.ndarray, g: jnp.ndarray,
    scal: jnp.ndarray, *, b1: float, b2: float, eps: float,
    max_norm: float, wd: float, interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The fused per-leaf apply: (p', mu', nu', stats[4]) in one Pallas
    pass, param/mu/nu buffers aliased in place.  ``g`` is the
    token-NORMALIZED fp32 gradient (the ``optimizer_apply_block``
    contract); ``scal`` the ``SCALARS``-vector of traced step scalars.
    Gate on :func:`fused_adamw_supported` — this raises on untileable
    shapes."""
    if interpret is None:
        interpret = _default_interpret()
    shape = p.shape
    total = int(math.prod(shape))
    cols = _pick_cols(total)
    if not cols:
        raise ValueError(
            f"leaf of {total} elements is not fused-adamw tileable; gate on "
            "fused_adamw_supported"
        )
    rows = total // cols
    block_rows = _pick_block_rows(rows, cols)
    spec = pl.BlockSpec((block_rows, cols), lambda i: (i, 0))
    args = [
        scal,
        p.reshape(rows, cols),
        mu.reshape(rows, cols),
        nu.reshape(rows, cols),
        g.reshape(rows, cols),
    ]
    out = pl.pallas_call(
        functools.partial(
            _adamw_kernel, b1=b1, b2=b2, eps=eps, max_norm=max_norm,
            wd=wd, clip=max_norm > 0,
        ),
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), spec, spec, spec, spec],
        out_specs=[spec, spec, spec, pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[
            jax.ShapeDtypeStruct((rows, cols), p.dtype),
            jax.ShapeDtypeStruct((rows, cols), mu.dtype),
            jax.ShapeDtypeStruct((rows, cols), nu.dtype),
            jax.ShapeDtypeStruct((STATS,), jnp.float32),
        ],
        # the in-place contract: param/mu/nu write back over their own
        # buffers — no fp32 param copy in the compiled apply (the IR
        # census extension in analysis/ir_lint.py checks the program)
        input_output_aliases={1: 0, 2: 1, 3: 2},
        interpret=interpret,
    )(*args)
    p2, mu2, nu2, stats = out
    return p2.reshape(shape), mu2.reshape(shape), nu2.reshape(shape), stats


def adamw_leaf_reference(
    p: jnp.ndarray, mu: jnp.ndarray, nu: jnp.ndarray, g: jnp.ndarray,
    scal: jnp.ndarray, *, b1: float, b2: float, eps: float,
    max_norm: float, wd: float,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The identical update in plain jnp — the fallback for leaves the
    kernel cannot tile AND the oracle the kernel is tested against.
    Same op sequence, so (compiled) outputs match the kernel and the
    optax chain up to XLA float contraction (module docstring)."""
    g_raw = g  # the PRE-clip gradient: a NaN anywhere makes the global
    # norm NaN and the clip branch then NaN-floods the whole leaf — the
    # non-finite COUNT must see the raw stream (like the kernel's
    # g_ref read and health_metrics), or one bad element reports as
    # leaf-size and the tripwire loses the only signal it exists for
    if max_norm > 0:
        gnorm = scal[_S_GNORM]
        trigger = scal[_S_TRIGGER]
        g = jnp.where(trigger != 0.0, g, (g / gnorm) * max_norm)
    mu2 = (1 - b1) * g + b1 * mu
    nu2 = (1 - b2) * (g * g) + b2 * nu
    u = (mu2 / scal[_S_BC1]) / (jnp.sqrt(nu2 / scal[_S_BC2]) + eps)
    if wd:
        u = u + wd * p
    u = scal[_S_NEG_LR] * u
    stats = jnp.stack([
        jnp.sum(p.astype(jnp.float32) ** 2),
        jnp.sum(u.astype(jnp.float32) ** 2),
        jnp.sum(~jnp.isfinite(g_raw)).astype(jnp.float32),
        jnp.zeros((), jnp.float32),
    ])
    return p + u, mu2, nu2, stats


# ----------------------------------------------------------- tree dispatch


def _spec_axes(spec) -> tuple[str, ...]:
    axes: list[str] = []
    for entry in spec or ():
        if entry is None:
            continue
        axes.extend(entry if isinstance(entry, tuple) else (entry,))
    return tuple(axes)


def _spec_divides(shape: tuple, spec, mesh) -> bool:
    """Every spec'd dim must divide evenly over its axes: shard_map has
    no padded shards, so a ragged leaf must stay on the (GSPMD-padded)
    reference path even when its TOTAL element count happens to tile."""
    for i, entry in enumerate(spec or ()):
        if entry is None or i >= len(shape):
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for a in axes:
            n *= max(1, mesh.shape.get(a, 1))
        if shape[i] % n:
            return False
    return True


def _shard_elems(shape: tuple, spec, mesh) -> int:
    n = int(math.prod(shape))
    for a in _spec_axes(spec):
        n //= max(1, mesh.shape.get(a, 1))
    return n


def _sharded_leaf(
    p, mu, nu, g, scal, spec, mesh, *, hyper: dict, interpret: bool | None
):
    """Per-shard kernel run under ``jax.shard_map`` with the leaf's
    own param spec (params/mu/nu/grads share it by the mirror
    contracts); the health partial sums psum over exactly the leaf's
    sharded axes — the second stage of the two-stage reduction."""
    from jax.sharding import PartitionSpec as P


    axes = _spec_axes(spec)

    def run(scal, p, mu, nu, g):
        p2, mu2, nu2, stats = fused_adamw_leaf(
            p, mu, nu, g, scal, interpret=interpret, **hyper
        )
        if axes:
            stats = jax.lax.psum(stats, axes)
        return p2, mu2, nu2, stats

    return jax.shard_map(
        run, mesh=mesh,
        in_specs=(P(), spec, spec, spec, spec),
        out_specs=(spec, spec, spec, P()),
        check_vma=False,
    )(scal, p, mu, nu, g)


def adamw_tree_apply(
    params, mu, nu, grads, scal, *, b1: float, b2: float, eps: float,
    max_norm: float, weight_decay: float, decay_tree,
    mesh=None, param_specs=None, interpret: bool | None = None,
):
    """Map the fused apply over a whole (params, mu, nu, grads) tree.

    Per leaf: the Pallas kernel when the leaf (or its per-device shard,
    under a >1-device mesh with known ``param_specs``) tiles, the jnp
    reference otherwise — both matching the optax chain up to XLA float
    contraction.  Returns
    ``(new_params, new_mu, new_nu, stats_tree)`` with ``stats_tree``
    holding one ``(STATS,)`` fp32 vector per leaf (health partial sums,
    already cross-shard reduced)."""
    hyper = dict(b1=b1, b2=b2, eps=eps, max_norm=max_norm)
    multi = mesh is not None and int(mesh.devices.size) > 1

    def leaf(p, m, v, g, decay, spec):
        h = dict(hyper, wd=weight_decay if decay else 0.0)
        if not multi:
            if fused_adamw_supported(p.size, p.dtype) and p.dtype == m.dtype == v.dtype:
                return fused_adamw_leaf(p, m, v, g, scal, interpret=interpret, **h)
            return adamw_leaf_reference(p, m, v, g, scal, **h)
        if (
            spec is not None
            and p.dtype == m.dtype == v.dtype
            and _spec_divides(p.shape, spec, mesh)
            and fused_adamw_supported(_shard_elems(p.shape, spec, mesh), p.dtype)
        ):
            return _sharded_leaf(
                p, m, v, g, scal, spec, mesh, hyper=h, interpret=interpret
            )
        # GSPMD partitions the elementwise reference natively
        return adamw_leaf_reference(p, m, v, g, scal, **h)

    # manual flatten: PartitionSpec / bool auxiliary leaves must not be
    # re-interpreted as pytree structure by a multi-tree map
    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_mu = treedef.flatten_up_to(mu)
    flat_nu = treedef.flatten_up_to(nu)
    flat_g = treedef.flatten_up_to(grads)
    flat_decay = treedef.flatten_up_to(decay_tree)
    flat_spec = (
        treedef.flatten_up_to(param_specs)
        if param_specs is not None
        else [None] * len(flat_p)
    )
    outs = [
        leaf(p, m, v, g, d, s)
        for p, m, v, g, d, s in zip(
            flat_p, flat_mu, flat_nu, flat_g, flat_decay, flat_spec
        )
    ]
    new_p = treedef.unflatten([o[0] for o in outs])
    new_mu = treedef.unflatten([o[1] for o in outs])
    new_nu = treedef.unflatten([o[2] for o in outs])
    stats = treedef.unflatten([o[3] for o in outs])
    return new_p, new_mu, new_nu, stats
