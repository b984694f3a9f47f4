"""Activation sharding constraints for the SPMD train/eval graphs.

The path-regex rules in ``sharding.py`` pin down *parameter* layouts, but
GSPMD still has to propagate shardings through activations — and with a
vocab/d_model-sharded embedding feeding a batch-sharded residual stream it
can end up with conflicting choices it reconciles by "involuntary full
rematerialization" (replicate, then re-partition: the round-1 dryrun
emitted exactly that warning on the tensor-parallel path).  Explicit
``with_sharding_constraint`` calls at the model's seams give the
partitioner one consistent answer:

- residual stream / hidden states: batch over ``(data, fsdp)``, d_model
  replicated (megatron-style: tensor parallelism lives *inside* the
  attention/MLP blocks, the residual stream is replicated over ``tensor``);
- logits: batch over ``(data, fsdp)``, vocab over ``tensor`` (matches the
  vocab-sharded embedding/lm_head so the loss's logsumexp reduces over a
  sharded axis with a psum instead of materializing replicated logits).

Model code calls the ``constrain_*`` helpers unconditionally; they are
no-ops unless a mesh has been installed with ``activation_mesh`` — the
train step and evaluator install it around tracing, so pure single-device
uses (unit tests, conversion scripts) see unchanged graphs.
"""

from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()

BATCH_AXES = ("data", "fsdp", "expert")


def current_mesh() -> Mesh | None:
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def activation_mesh(mesh: Mesh | None):
    """Install ``mesh`` as the ambient mesh for ``constrain_*`` during
    tracing.  Constraints bake into the jitted program, so this only needs
    to wrap the *first* (tracing) call — wrapping every call is harmless."""
    prev = current_mesh()
    _state.mesh = mesh
    try:
        yield
    finally:
        _state.mesh = prev


def pvary_to(tree, axes):
    """Mark every array in ``tree`` varying over ``axes`` (a name or tuple
    of names) for shard_map's vma checking (check_vma=True), skipping axes
    an array is ALREADY varying over — so values that enter a manual region
    sharded (hence varying) over some axis can be upcast to the full set
    without double-marking.  The single home for this logic: the pipeline
    body and the ring-attention carry init both need it."""
    if isinstance(axes, str):
        axes = (axes,)

    def mark(x):
        have = getattr(jax.typeof(x), "vma", frozenset())
        missing = tuple(a for a in axes if a not in have)
        return jax.lax.pcast(x, missing, to="varying") if missing else x

    return jax.tree.map(mark, tree)


def current_kv_cache_dtype() -> str:
    """The serving KV-cache storage dtype for programs traced under
    ``kv_cache_context`` — ``"f32"`` (store K/V at compute dtype, the
    default) or ``"int8"`` (quantize on cache write with per-head
    per-position symmetric scales; ``ops/flash_attention.py`` owns the
    quantize/dequantize math).  A trace-time knob exactly like the
    ambient mesh: the attention modules' ``_cache_kv`` reads it when
    creating/writing cache variables, so the flag never threads through
    every model signature."""
    return getattr(_state, "kv_cache_dtype", "f32")


@contextlib.contextmanager
def kv_cache_context(dtype: str):
    """Install the KV-cache storage dtype for tracing (see
    ``current_kv_cache_dtype``).  Must wrap BOTH the cache-allocating
    program (prefill / init) and every program that reads or writes the
    cache — the serving engine and the static runners wrap all their
    jitted calls, so one engine is internally consistent by construction."""
    if dtype not in ("f32", "int8"):
        raise ValueError(
            f"kv_cache_dtype={dtype!r}: must be 'f32' or 'int8'"
        )
    prev = current_kv_cache_dtype()
    _state.kv_cache_dtype = dtype
    try:
        yield
    finally:
        _state.kv_cache_dtype = prev


def current_manual_seq() -> tuple[str, int] | None:
    """(axis_name, axis_size) when tracing inside a manual region that owns
    the sequence axis (the stage×sequence pipeline), else None."""
    return getattr(_state, "manual_seq", None)


@contextlib.contextmanager
def manual_sequence(axis_name: str, axis_size: int):
    """Declare that the enclosing ``shard_map`` is manual over the sequence
    axis: activations carry LOCAL sequence shards and collectives over
    ``axis_name`` are legal.  Attention modules switch to the in-region
    ring-attention body (``ops.ring_attention.ring_attention``) instead of
    opening their own ``shard_map`` — nesting manual regions is not
    supported, which is why the pipeline installs this context rather than
    relying on the modules' normal global-shape dispatch."""
    prev = current_manual_seq()
    _state.manual_seq = (axis_name, axis_size)
    try:
        yield
    finally:
        _state.manual_seq = prev


def constrain(x: jax.Array, spec: P) -> jax.Array:
    """Constrain ``x`` to ``spec`` on the ambient mesh (no-op without one).

    The spec is truncated to ``x.ndim`` so one call site can serve ranks
    that differ by a leading/trailing axis."""
    mesh = current_mesh()
    if mesh is None:
        return x
    if len(spec) > x.ndim:
        spec = P(*spec[: x.ndim])
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _seq_axis(x: jax.Array, dim: int = 1) -> str | None:
    """``"sequence"`` when the ambient mesh runs sequence parallelism and
    the seq dim splits evenly (decode-time length-1 slices stay unsharded),
    else None — so non-SP meshes compile to exactly the old graphs."""
    mesh = current_mesh()
    if mesh is None:
        return None
    n = mesh.shape.get("sequence", 1)
    size = x.shape[dim]
    return "sequence" if n > 1 and size and size % n == 0 else None


def constrain_hidden(x: jax.Array) -> jax.Array:
    """(batch, seq, d_model) residual-stream activations; seq over
    ``sequence`` under context parallelism."""
    return constrain(x, P(BATCH_AXES, _seq_axis(x), None))


def constrain_logits(x: jax.Array) -> jax.Array:
    """(batch, seq, vocab) logits — vocab sharded over ``tensor``, seq over
    ``sequence`` under context parallelism."""
    return constrain(x, P(BATCH_AXES, _seq_axis(x), "tensor"))


def constrain_kv(x: jax.Array) -> jax.Array:
    """(batch, heads, len, head_dim) precomputed cross-K/V: batch rows over
    the batch axes, heads over ``tensor`` — the serving twin of
    ``constrain_hidden``.  The layout (and its divisibility fallbacks) is
    ``parallel/sharding.py kv_leaf_spec`` — the ONE definition this
    constraint and the engine's host-side placement share."""
    mesh = current_mesh()
    if mesh is None or x.ndim != 4:
        return x
    from distributed_llms_example_tpu.parallel.sharding import kv_leaf_spec

    return constrain(x, kv_leaf_spec(x.shape, dict(mesh.shape)))


def constrain_cache(tree, kv_heads: int | None = None):
    """Pin a whole flax "cache" collection (or cross-KV tuple tree) to the
    serving layout, leaf by leaf and by the leaf's name
    (``sharding.cache_leaf_spec``, CACHE_RULES' one definition): K/V
    buffers (batch, len, heads x head_dim), the int8 cache's (batch, len,
    heads) scales, a ``conv_state``; the unnamed 4-D leaves of a cross-KV
    tuple via ``constrain_kv`` (its 3-D leaves, (batch, len, heads x
    head_dim), as a K/V buffer); scalars (the ``cache_index`` counters)
    replicated by GSPMD default.  ``kv_heads`` (``sharding.cache_kv_heads``
    of the model's config) is what a K/V buffer's merged axis is told apart
    by, so a tree that holds one needs it; a cross-KV tree does not.  No-op
    without an ambient mesh — the decode/prefill programs call it
    unconditionally, exactly like the models call ``constrain_hidden``."""
    import jax.tree_util as jtu

    mesh = current_mesh()
    if mesh is None:
        return tree
    from distributed_llms_example_tpu.parallel.sharding import KV_LEAVES, cache_leaf_spec

    def pin(path, x):
        name = str(path[-1].key) if path and hasattr(path[-1], "key") else ""
        if name in KV_LEAVES and kv_heads is None:
            raise ValueError(f"constrain_cache: the K/V leaf {name} needs the model's kv_heads")
        nd = getattr(x, "ndim", 0)
        if not name and nd == 3 and kv_heads is not None:
            name = "cached_key"  # a cross-KV pair kept as a cache keeps K/V: that leaf's spec
        spec = cache_leaf_spec(name, getattr(x, "shape", ()), dict(mesh.shape), kv_heads)
        if spec is not None:
            return constrain(x, spec)
        return constrain_kv(x) if nd == 4 else x

    return jtu.tree_map_with_path(pin, tree)
