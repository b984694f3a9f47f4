"""GPipe-style pipeline parallelism over the ``stage`` mesh axis.

The reference has no model parallelism of any kind (SURVEY.md §2: tensor/
pipeline parallel "No"); this module goes past parity so decoder stacks
too deep for one chip's HBM can be split *by layer* across chips — the
complement of FSDP (which shards within each tensor) and the standard way
to scale across slices, since stage hops are point-to-point and tolerate
DCN latency (the ``stage`` axis is outermost in the mesh for exactly that
reason, core/mesh.py).

Design — a spatial pipeline expressed as one SPMD program, TPU-first:

- Layer parameters are *stacked*: every transformer block's param tree
  gets a leading layer dim (L, ...) sharded over ``stage``, so each device
  group holds L/S contiguous layers and total param memory scales 1/S.
- ``shard_map`` over the mesh runs the scheduling loop per-shard: a
  ``lax.scan`` over M + S - 1 ticks.  Each tick, stage 0 feeds the next
  microbatch in, every stage applies its layers (an inner ``lax.scan``
  over the local layer stack, optionally ``jax.checkpoint``-ed), and
  activations hop to the next stage with a single ``lax.ppermute`` —
  neighbor-to-neighbor traffic XLA can overlap with the next tick's
  compute.  The last stage collects finished microbatches.
- The backward pass is pure autodiff: ``scan`` reverses the schedule and
  the ``ppermute`` transpose carries activation-gradients backwards
  through the ring — the 1F1B-shaped reverse traffic for free.
- Bubble: (S-1)/(M+S-1) of ticks compute garbage that is discarded (and
  contributes zero gradient).  Raise ``num_microbatches`` to amortize.

Composition (v2): the ``shard_map`` is manual over ``stage`` ONLY
(``axis_names={"stage"}``) — every other mesh axis stays *automatic*, so
GSPMD keeps partitioning the per-stage compute over ``data``/``fsdp``
(batch) and ``tensor`` (megatron splits on the stacked kernels, the
standard stage×tensor 7B+ topology) inside the pipeline body, inserting
the collectives itself.  MoE composes too (stage × expert): sown aux
losses can't cross the shard_map, so ``with_aux`` layer_fns return the
load-balance loss as an explicit output the schedule accumulates (bubble
ticks masked) and psums.  ``sequence`` composes on both schedules via
``seq_axis``: the region goes manual over {stage, sequence} — ONE combined
manual region instead of (unsupported) nested ones — hidden shards its
sequence dim, and attention runs the in-region ring body under a
``manual_sequence`` context (see ``pipeline_apply``); long-context models
can then ALSO split their layer stack across stages.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from distributed_llms_example_tpu.analysis.composition import reason_for
from distributed_llms_example_tpu.parallel.activation import (
    manual_sequence,
    pvary_to,
)


def stack_blocks(params: dict, prefix: str = "block_", out_key: str = "stacked_blocks") -> dict:
    """Standard per-layer tree ({block_0: t, block_1: t, ...}) → pipelined
    tree ({stacked_blocks: tree-of-(L, ...) arrays, ...rest}).  The inverse
    of ``unstack_blocks``; checkpoints and HF conversion stay in the
    per-layer layout, this transform is applied at training-setup time."""
    names = sorted(
        (k for k in params if k.startswith(prefix) and k[len(prefix):].isdigit()),
        key=lambda k: int(k[len(prefix):]),
    )
    if not names:
        raise ValueError(f"no {prefix}* subtrees in params")
    if names != [f"{prefix}{i}" for i in range(len(names))]:
        raise ValueError(f"layer indices not contiguous from 0: {names}")
    rest = {k: v for k, v in params.items() if k not in names}
    # host-side stack: jnp.stack would commit the whole stacked tree to the
    # default device before the P('stage') sharding is ever applied — OOM
    # for exactly the too-big-for-one-chip models this module exists for
    import numpy as np

    stacked = jax.tree.map(
        lambda *xs: np.stack([np.asarray(x) for x in xs]), *(params[n] for n in names)
    )
    return {**rest, out_key: stacked}


def unstack_blocks(params: dict, prefix: str = "block_", key: str = "stacked_blocks",
                   layer_transform=None, row_order=None) -> dict:
    """Pipelined tree → standard per-layer tree (for checkpoints/eval).
    ``layer_transform`` (if given) is applied to each layer tree AS it is
    unstacked — the hook the memory-aware reshard path uses so only one
    untransformed (replicated) layer is ever live.  ``row_order`` (if
    given) maps TRUE layer index → storage row — the interleaved pipeline
    schedule's permuted layout resolves here one row at a time, instead of
    materializing a whole un-permuted copy of the stack first."""
    stacked = params[key]
    rest = {k: v for k, v in params.items() if k != key}
    n = jax.tree.leaves(stacked)[0].shape[0]
    out = dict(rest)
    for i in range(n):
        row = i if row_order is None else int(row_order[i])
        layer = jax.tree.map(lambda x: x[row], stacked)
        out[f"{prefix}{i}"] = layer if layer_transform is None else layer_transform(layer)
    return out


def _unstack_dispatch(family: str, params: dict, unstack_one) -> dict:
    """Shared family layout dispatch: LLaMA's single stack, BART's twin
    top-level stacks, T5's nested encoder/decoder stacks."""
    if family == "llama":
        return unstack_one(params, "block_", "stacked_blocks")
    if family == "bart":
        params = unstack_one(params, "encoder_block_", "stacked_encoder_blocks")
        return unstack_one(params, "decoder_block_", "stacked_decoder_blocks")
    if family == "t5":
        return {
            **params,
            "encoder": unstack_one(params["encoder"], "block_", "stacked_blocks"),
            "decoder": unstack_one(params["decoder"], "block_", "stacked_blocks"),
        }
    raise ValueError(f"no pipeline unstacking for family {family!r}")


def stack_for_family(family: str, params: dict) -> dict:
    """Family-aware stacking: LLaMA stacks its single decoder stack; BART
    stacks encoder+decoder at the top level; T5 stacks inside its nested
    encoder/decoder subtrees."""
    if family == "llama":
        return stack_blocks(params)
    if family == "bart":
        params = stack_blocks(params, "encoder_block_", "stacked_encoder_blocks")
        return stack_blocks(params, "decoder_block_", "stacked_decoder_blocks")
    if family == "t5":
        return {
            **params,
            "encoder": stack_blocks(params["encoder"]),
            "decoder": stack_blocks(params["decoder"]),
        }
    raise ValueError(f"no pipeline stacking for family {family!r}")


def unstack_for_family(family: str, params: dict) -> dict:
    return _unstack_dispatch(family, params, unstack_blocks)


def unstack_for_family_resharded(family: str, params: dict, mesh, rules=None,
                                 row_order=None) -> dict:
    """``unstack_for_family`` that device_puts each layer onto its
    (default FSDP/TP) rule sharding AS it is unstacked.  Indexing a
    stage-sharded stack yields a replicated layer; doing all layers before
    resharding would transiently hold a full replicated copy of the model
    on every device — exactly the cliff pipelined eval/export exists to
    avoid.  Here at most ONE replicated layer is live at a time; the
    resulting tree holds params/(fsdp·tensor) per device."""
    from distributed_llms_example_tpu.parallel.sharding import resolve_shardings

    def unstack_one(tree, prefix="block_", key="stacked_blocks"):
        holder = {}  # all layers of one stack share a structure: resolve once

        def transform(layer):
            if not holder:
                holder["sh"] = resolve_shardings(layer, mesh, rules)
            return jax.tree.map(jax.device_put, layer, holder["sh"])

        return unstack_blocks(
            tree, prefix, key, layer_transform=transform, row_order=row_order
        )

    out = _unstack_dispatch(family, params, unstack_one)
    # non-stacked leaves (embeddings/norms/head) get their rule shardings
    # too; the per-layer trees above are already placed, so this final
    # tree-wide device_put no-ops on them
    return jax.tree.map(jax.device_put, out, resolve_shardings(out, mesh, rules))


def gather_tree_to_host(tree, *, writer_only: bool = False):
    """Copy a (possibly multi-host-sharded) pytree to host numpy, one leaf
    at a time.  Non-fully-addressable leaves are allgathered — every
    process enters every collective in the same (tree) order, so this is
    collectively safe.  With ``writer_only``, non-writing processes free
    each gathered leaf immediately and get a tree of None leaves back:
    peak extra host memory on them is ONE leaf, while process 0 (where the
    checkpoint/safetensors writer runs) accumulates the full tree it needs
    anyway.  Shared by the pipelined (per-layer) and non-pipelined export
    paths so the gather semantics cannot drift between them."""
    import numpy as np

    drop = writer_only and jax.process_count() > 1 and jax.process_index() != 0

    def to_host(x):
        if (  # pod-agreed: process_count() is pod-uniform; the per-leaf allgather below runs on every rank
            jax.process_count() > 1 and hasattr(x, "is_fully_addressable") and not x.is_fully_addressable
        ):
            from jax.experimental import multihost_utils

            g = np.asarray(multihost_utils.process_allgather(x, tiled=True))
            return None if drop else g
        return None if drop else np.asarray(jax.device_get(x))

    return jax.tree.map(to_host, tree)


def unstack_for_family_to_host(family: str, params: dict, *, writer_only: bool = False,
                               row_order=None) -> dict:
    """Unstack a pipelined tree layer-by-layer STRAIGHT TO HOST numpy —
    the export path.  Device-side resharded unstacking still replicates
    everything on a pure-pipeline mesh (stage>1 with fsdp=tensor=1, the
    canonical too-big-for-one-chip config), so the HF export gathers each
    layer to host RAM as it is unstacked: HBM peak is the training
    footprint plus ONE gathered layer; the full fp32 tree only ever exists
    host-side, where the checkpoint writer needs it anyway.  Multi-host:
    see ``gather_tree_to_host`` (with ``writer_only`` the full host copy
    exists only on process 0)."""

    def unstack_one(tree, prefix="block_", key="stacked_blocks"):
        return unstack_blocks(
            tree, prefix, key,
            layer_transform=lambda layer: gather_tree_to_host(layer, writer_only=writer_only),
            row_order=row_order,
        )

    out = _unstack_dispatch(family, params, unstack_one)
    return gather_tree_to_host(out, writer_only=writer_only)


def _full_spec(leading, ndim: int) -> P:
    return P(leading, *([None] * (ndim - 1)))


def _seq_specs(seq_axis: str, hidden_ndim: int, *dim_trees) -> tuple:
    """Shard_map specs for the sequence-parallel boundary, shared by the
    gpipe and 1f1b paths so the convention cannot drift: hidden shards dim
    1 over ``seq_axis``; each ``(tree, dims)`` pair in ``dim_trees`` maps
    per-leaf dims (int, <0 or None = replicated) to PartitionSpecs,
    defaulting every leaf to replicated when ``dims`` is None."""
    hidden_spec = P(None, seq_axis, *([None] * (hidden_ndim - 2)))

    def dim_spec(m, d):
        return P() if d is None or d < 0 else P(*([None] * d), seq_axis)

    out = [hidden_spec]
    for tree, dims in dim_trees:
        out.append(jax.tree.map(
            dim_spec, tree,
            jax.tree.map(lambda _: -1, tree) if dims is None else dims,
        ))
    return tuple(out)


def dropout(x: jnp.ndarray, key: jnp.ndarray, rate: float) -> jnp.ndarray:
    """Inverted dropout for the pipeline adapters' out-of-loop layers
    (embeddings, final norms) — in-loop dropout goes through each block's
    own ``ops.fused_dropout.Dropout`` with a per-layer folded key.  Routed
    through the shared helper so the fused Pallas path applies here too
    (these calls run OUTSIDE the pipeline's manual region, under plain
    GSPMD, where the helper's shard_map dispatch is legal)."""
    from distributed_llms_example_tpu.ops.fused_dropout import (
        dropout as shared_dropout,
    )

    return shared_dropout(x, key, rate).astype(x.dtype)


def _vary(tree, axes):
    """Mark every array varying over ``axes``: the body branches on
    axis_index, and shard_map's vma checking (check_vma=True) requires the
    provenance to be explicit rather than inferred.  See ``pvary_to``."""
    return pvary_to(tree, axes)


def _make_run_stage(layer_fn: Callable, checkpoint: bool,
                    with_aux: bool = False) -> Callable:
    """One stage's work: an inner ``lax.scan`` over its local layer stack,
    each layer optionally ``jax.checkpoint``-ed.  With a key, ``layer_fn``
    takes a fourth argument folded to be unique per local layer (callers
    fold stage and microbatch in first).  ``with_aux``: ``layer_fn``
    returns ``(h, aux_scalar)`` (e.g. an MoE load-balance loss) and
    ``run_stage`` returns ``(y, aux_sum_over_local_layers)``."""
    one_layer = jax.checkpoint(layer_fn) if checkpoint else layer_fn

    def call(p, x, ex, k):
        out = one_layer(p, x, ex) if k is None else one_layer(p, x, ex, k)
        return out if with_aux else (out, jnp.zeros((), jnp.float32))

    def run_stage(local_params: Any, x: jnp.ndarray, ex: Any,
                  key: jnp.ndarray | None = None):
        local_l = jax.tree.leaves(local_params)[0].shape[0]
        # derive the zero from x so its vma type (stage-varying inside the
        # pipeline body, plain outside) matches the aux the scan carries
        aux0 = (x.ravel()[0] * 0).astype(jnp.float32)
        if key is None:
            def step(carry, p):
                y, aux = call(p, carry[0], ex, None)
                return (y, carry[1] + aux), None

            (y, aux), _ = jax.lax.scan(step, (x, aux0), local_params)
        else:
            def step(carry, xs):
                p, i = xs
                y, aux = call(p, carry[0], ex, jax.random.fold_in(key, i))
                return (y, carry[1] + aux), None

            (y, aux), _ = jax.lax.scan(
                step, (x, aux0), (local_params, jnp.arange(local_l))
            )
        return (y, aux) if with_aux else y

    return run_stage


def pipeline_apply(
    layer_fn: Callable[[Any, jnp.ndarray, Any], jnp.ndarray],
    stacked_params: Any,
    hidden: jnp.ndarray,
    extras: Any = None,
    *,
    mesh: Mesh,
    num_microbatches: int,
    axis_name: str = "stage",
    batch_axes: tuple[str, ...] = ("data", "fsdp", "expert"),
    checkpoint: bool = True,
    rng: jnp.ndarray | None = None,
    with_aux: bool = False,
    seq_axis: str | None = None,
    extras_seq_dims: Any = None,
) -> jnp.ndarray:
    """Run ``hidden`` through the stacked layers as a pipelined schedule.

    ``layer_fn(layer_params, h, extras_microbatch) -> h`` applies ONE
    layer.  ``with_aux``: ``layer_fn`` instead returns ``(h, aux_scalar)``
    (an MoE load-balance loss term); the call then returns
    ``(out, aux_mean)`` where ``aux_mean`` averages the per-(layer,
    microbatch) scalars over all L layers and M microbatches, bubble
    ticks excluded.  The mean is UNWEIGHTED over microbatches: it equals
    the grad-accumulation objective (which token-weights each
    microbatch's aux) exactly when microbatch token counts are uniform,
    and is otherwise an equal-weight estimator of the same batch-level
    statistic.  ``hidden``: (B, ...) global batch; ``extras``: optional pytree
    of per-example arrays (leading dim B, e.g. an attention padding bias)
    or per-call constants (leading dim != B, replicated to every stage).
    Requires L % stages == 0 and (local batch) % num_microbatches == 0.
    Output is bit-identical to applying the layers sequentially (the
    schedule only reorders microbatches, never the math within one).

    ``rng``: optional PRNG key enabling stochastic layers (dropout).  When
    given, ``layer_fn`` must take a fourth argument — a key folded to be
    unique per (microbatch, stage, local layer), so every layer of every
    microbatch draws an independent mask while the whole schedule stays a
    deterministic function of ``rng``.

    ``seq_axis``: compose with sequence/context parallelism by making the
    shard_map manual over {stage, seq_axis} — ONE combined manual region
    instead of (unsupported) nested ones.  ``hidden`` dim 1 is then sharded
    over ``seq_axis``; inside the body every activation holds a local
    sequence shard and ``layer_fn`` is traced under a ``manual_sequence``
    context, which switches attention modules onto the in-region ring body
    (ops/ring_attention.py) with collectives over the manual axis.
    ``extras_seq_dims``: pytree matching ``extras`` giving, per leaf, the
    dim sharded over ``seq_axis`` (None = replicated along sequence) — e.g.
    a K-aligned padding bias (B, 1, 1, K) shards dim 3 and then rotates
    around the ring with K/V.
    """
    S = mesh.shape.get(axis_name, 1)
    L = jax.tree.leaves(stacked_params)[0].shape[0]
    M = num_microbatches
    if L % S:
        raise ValueError(f"{L} layers not divisible into {S} pipeline stages")
    B = hidden.shape[0]
    batch_axes = tuple(a for a in batch_axes if a in mesh.shape)
    batch_shards = 1
    for a in batch_axes:
        batch_shards *= mesh.shape[a]
    if B % (batch_shards * M):
        raise ValueError(
            f"global batch {B} not divisible by {batch_shards} batch shards "
            f"× {M} microbatches"
        )

    run_stage = _make_run_stage(layer_fn, checkpoint, with_aux)

    if S == 1:
        # no pipeline: plain scan over the full stack under GSPMD (a
        # sequence axis, if any, is handled by the modules' own global-shape
        # ring dispatch — no manual region to compose with)
        if with_aux:
            y, aux = run_stage(stacked_params, hidden, extras, rng)
            return y, aux / L
        return run_stage(stacked_params, hidden, extras, rng)

    # seq-axis resolution, divisibility, and the bf16→fp32 boundary
    # conversion are shared with the fused executors (_pvg_common) so the
    # partitioner-workaround conventions cannot drift between the paths.
    # The pipeline PLUMBING (microbatch selects, hop buffers, the output
    # accumulator) runs in fp32 when the compute dtype is bf16: the XLA
    # SPMD partitioner miscompiles bf16 select/copy chains under
    # partial-manual shard_map ("Invalid binary instruction opcode copy",
    # observed on jax 0.9/XLA CPU), and the converts fuse into the layer
    # matmuls anyway.  Layer compute still happens in the caller's dtype.
    (seq_axis, n_seq, axes_all, is_batched, ex_dtypes, compute_dtype,
     plumb_dtype, hidden, extras) = _pvg_common(
        hidden, extras, mesh=mesh, axis_name=axis_name, seq_axis=seq_axis,
    )
    if seq_axis is not None and with_aux:
        # deep twin of the adapter-construction check: the message comes
        # from the composition table so it cannot drift
        raise ValueError(reason_for("pipeline-sequence-moe"))

    def body(local_params: Any, h: jnp.ndarray, ex: Any, key: Any) -> jnp.ndarray:
        # Manual over ``stage`` only: shapes here are GLOBAL in every other
        # dim and every array must be made stage-varying (each stage
        # branches on s_idx), hence the pcasts.  GSPMD still auto-shards
        # the per-stage compute over data/fsdp/tensor.
        s_idx = jax.lax.axis_index(axis_name)
        if seq_axis is not None:
            # Params enter stage-varying but sequence-UNvarying; the first
            # op mixing them with sequence-varying activations would insert
            # an implicit pvary whose TRANSPOSE is a psum of the (bf16)
            # parameter cotangent over the sequence axis — and a bf16 psum
            # over a manual axis is exactly the partitioner copy-chain
            # crash.  Pre-vary every bf16 param through an fp32 bridge so
            # the transpose psum runs in fp32 (the converts fuse).
            def seq_vary_param(p):
                if p.dtype == jnp.bfloat16:
                    return _vary(p.astype(jnp.float32), axes_all).astype(p.dtype)
                return _vary(p, axes_all)

            local_params = jax.tree.map(seq_vary_param, local_params)
        ex = jax.tree.map(
            lambda m: m.astype(plumb_dtype) if m.dtype == jnp.bfloat16 else m, ex
        )
        h, ex = _vary(h.astype(plumb_dtype), axes_all), _vary(ex, axes_all)
        if key is not None:
            # unique stream per stage (and per sequence shard, so local
            # dropout masks are independent); tick folds in the microbatch
            key = jax.random.fold_in(_vary(key, axes_all), s_idx)
            if seq_axis is not None:
                key = jax.random.fold_in(key, jax.lax.axis_index(seq_axis))
        mb = h.shape[0] // M
        micro = h.reshape(M, mb, *h.shape[1:])
        micro_ex = jax.tree.map(
            lambda m, batched: m.reshape(M, m.shape[0] // M, *m.shape[1:]) if batched else m,
            ex,
            is_batched,
        )
        buf = _vary(jnp.zeros((mb, *h.shape[1:]), h.dtype), axes_all)
        outputs = _vary(jnp.zeros((M, mb, *h.shape[1:]), h.dtype), axes_all)
        aux_acc = _vary(jnp.zeros((), jnp.float32), axes_all)
        perm = [(i, i + 1) for i in range(S - 1)]

        def tick(carry, t):
            buf, outputs, aux_acc = carry
            # stage s processes microbatch (t - s); clamp covers bubble ticks
            m_idx = jnp.clip(t - s_idx, 0, M - 1)
            x0 = jax.lax.dynamic_index_in_dim(micro, m_idx, 0, keepdims=False)
            ex_t = jax.tree.map(
                lambda m, batched, dt: (
                    jax.lax.dynamic_index_in_dim(m, m_idx, 0, keepdims=False)
                    if batched else m
                ).astype(dt),
                micro_ex,
                is_batched,
                ex_dtypes,
            )
            inp = jnp.where(s_idx == 0, x0, buf)
            key_m = None if key is None else jax.random.fold_in(key, m_idx)
            y = run_stage(local_params, inp.astype(compute_dtype), ex_t, key_m)
            if with_aux:
                y, aux_t = y
                # bubble ticks run the layers on clamped garbage; only
                # ticks where this stage holds a real microbatch count
                active = (t >= s_idx) & (t - s_idx < M)
                aux_acc = aux_acc + jnp.where(active, aux_t, 0.0)
            y = y.astype(plumb_dtype)
            nxt = jax.lax.ppermute(y, axis_name, perm)
            write = (s_idx == S - 1) & (t >= S - 1)
            upd = jax.lax.dynamic_update_index_in_dim(outputs, y, m_idx, 0)
            outputs = jnp.where(write, upd, outputs)
            return (nxt, outputs, aux_acc), None

        (_, outputs, aux_acc), _ = jax.lax.scan(
            tick, (buf, outputs, aux_acc), jnp.arange(M + S - 1)
        )
        # only the last stage holds real results; replicate them to every
        # stage so downstream (final norm / head / loss) is stage-uniform
        outputs = jax.lax.psum(
            jnp.where(s_idx == S - 1, outputs, jnp.zeros_like(outputs)), axis_name
        )
        # on the sequence-sharded path the output boundary stays fp32 too
        # (cast back outside the region, same bug as the input boundary)
        out = outputs.reshape(h.shape)
        if seq_axis is None:
            out = out.astype(compute_dtype)
        if with_aux:
            # every (layer, microbatch) contributed once across all stages
            return out, jax.lax.psum(aux_acc, axis_name) / (L * M)
        return out

    # in/out specs name ONLY the manual axes; shardings over the automatic
    # axes (fsdp/tensor splits on the stacked kernels, data/fsdp on the
    # batch) ride through untouched
    param_specs = jax.tree.map(lambda x: _full_spec(axis_name, x.ndim), stacked_params)
    if seq_axis is None:
        hidden_spec = P()
        extras_specs = jax.tree.map(lambda m: P(), extras)
    else:
        hidden_spec, extras_specs = _seq_specs(
            seq_axis, hidden.ndim, (extras, extras_seq_dims)
        )
    # rng enters as a pytree ({} when absent) so in_specs structure-matches
    rng_tree = {} if rng is None else {"key": rng}
    rng_specs = jax.tree.map(lambda _: P(), rng_tree)

    def outer(sp, h, ex, rt):
        if seq_axis is None:
            return body(sp, h, ex, rt.get("key"))
        with manual_sequence(seq_axis, n_seq):
            return body(sp, h, ex, rt.get("key"))

    out_specs = (hidden_spec, P()) if with_aux else hidden_spec

    result = jax.shard_map(
        outer,
        mesh=mesh,
        axis_names=set(axes_all),
        in_specs=(param_specs, hidden_spec, extras_specs, rng_specs),
        out_specs=out_specs,
        check_vma=True,
    )(stacked_params, hidden, extras, rng_tree)
    if seq_axis is None:
        return result
    # with_aux cannot reach here (seq_axis + with_aux raises above)
    return result.astype(compute_dtype)


def _pvg_single_stage(run_stage, post_loss_fn, stacked_params, post_params,
                      hidden, extras, loss_batch, rng):
    """S == 1 fallback shared by the fused-schedule executors: one vjp over
    (blocks ∘ tail) under plain GSPMD — no pipeline."""

    def whole(sp, pp, h):
        return post_loss_fn(pp, run_stage(sp, h, extras, rng), loss_batch)

    (lsum, tokens), vjp = jax.vjp(whole, stacked_params, post_params, hidden)
    d_sp, d_pp, d_h = vjp((jnp.ones((), lsum.dtype), jnp.zeros((), tokens.dtype)))
    return lsum, tokens, d_sp, d_pp, d_h


def _pvg_single_stage_aux(run_stage, post_loss_fn, stacked_params, post_params,
                          hidden, extras, loss_batch, rng, aux_cotangent, M):
    """S == 1 fallback for the fused executors when ``with_aux``: one vjp
    under plain GSPMD, with the aux output's cotangent folded in.

    Contract note: aux_sum spans L layers × M microbatches; the single-
    stage path runs ONE full-batch pass (aux over L only), so aux scales
    by M — the caller's /(L·M) normalization and the /(L·M) cotangent
    then stay exact, and the value equals the gpipe S==1 aux/L mean."""

    def whole(sp, pp, h):
        y, aux = run_stage(sp, h, extras, rng)
        ls, tk = post_loss_fn(pp, y, loss_batch)
        return ls, tk, aux * M

    (lsum, tokens, aux_sum), vjp = jax.vjp(
        whole, stacked_params, post_params, hidden
    )
    # the aux output's cotangent IS the constant d(objective)/d(aux) —
    # one vjp covers CE and load-balance gradients together
    d_sp, d_pp, d_h = vjp((
        jnp.ones((), lsum.dtype),
        jnp.zeros((), tokens.dtype),
        jnp.asarray(aux_cotangent, aux_sum.dtype),
    ))
    return lsum, tokens, d_sp, d_pp, d_h, aux_sum


def _pvg_check_batch(B: int, mesh: Mesh, M: int, batch_axes) -> None:
    """Fail fast on a batch that doesn't divide into (batch shards ×
    microbatches) — run BEFORE the S==1 early return too, so a stage=1
    misconfiguration surfaces immediately instead of when scaled up."""
    batch_shards = 1
    for a in batch_axes:
        if a in mesh.shape:
            batch_shards *= mesh.shape[a]
    if B % (batch_shards * M):
        raise ValueError(
            f"global batch {B} not divisible by {batch_shards} batch shards "
            f"× {M} microbatches"
        )


def _pvg_common(hidden, extras, *, mesh, axis_name, seq_axis):
    """Shared setup for the fused-schedule executors (plain 1F1B and
    interleaved): sequence axis resolution and the bf16→fp32 boundary
    conversion (sharded-boundary bf16 crossings feed the partitioner
    copy-chain bug — convert OUTSIDE the manual region, see
    ``pipeline_apply``).  Returns ``(seq_axis, n_seq, axes_all,
    is_batched, ex_dtypes, compute_dtype, plumb_dtype, hidden, extras)``.
    Batch divisibility is validated by the executors themselves
    (``_pvg_check_batch``, BEFORE their S==1 early return) — not here."""
    B = hidden.shape[0]
    n_seq = mesh.shape.get(seq_axis, 1) if seq_axis else 1
    if n_seq <= 1:
        seq_axis = None
    if seq_axis is not None and hidden.ndim >= 2 and hidden.shape[1] % n_seq:
        raise ValueError(
            f"sequence length {hidden.shape[1]} not divisible by "
            f"{seq_axis}={n_seq}"
        )
    axes_all = (axis_name,) if seq_axis is None else (axis_name, seq_axis)
    is_batched = jax.tree.map(lambda m: m.ndim > 0 and m.shape[0] == B, extras)
    ex_dtypes = jax.tree.map(lambda m: m.dtype, extras)
    compute_dtype = hidden.dtype
    plumb_dtype = jnp.float32 if compute_dtype == jnp.bfloat16 else compute_dtype
    if seq_axis is not None:
        hidden = hidden.astype(plumb_dtype)
        extras = jax.tree.map(
            lambda m: m.astype(plumb_dtype) if m.dtype == jnp.bfloat16 else m, extras
        )
    return (seq_axis, n_seq, axes_all, is_batched, ex_dtypes,
            compute_dtype, plumb_dtype, hidden, extras)


def _pvg_body_prologue(sp_local, pp, h, ex, lb, rt, *, S, M, axis_name,
                       axes_all, seq_axis, plumb_dtype, is_batched, ex_dtypes):
    """Shared in-body setup for the fused-schedule executors.  Everything
    entering a ``jax.vjp`` is pre-varied: differentiating w.r.t. an
    unvarying input under a varying cotangent transposes the implicit
    broadcast into a hidden psum over the manual axes — the per-stage
    grads would then already contain every OTHER stage's (garbage)
    contribution, leaking through the schedule masks (and over ``seq``
    that implicit psum would be bf16, the partitioner crash).  Explicit
    fp32 psums in the epilogue do the real cross-shard reductions.

    Returns ``(s_idx, is_last, sp_local, pp, key, mb, micro, micro_ex,
    micro_lb, ex_at)`` with the batch already split into M microbatches."""
    s_idx = jax.lax.axis_index(axis_name)
    is_last = s_idx == S - 1
    ex = jax.tree.map(
        lambda m: m.astype(plumb_dtype) if m.dtype == jnp.bfloat16 else m, ex
    )
    h, ex, lb = _vary(h.astype(plumb_dtype), axes_all), _vary(ex, axes_all), _vary(lb, axes_all)
    pp = _vary(pp, axes_all)
    sp_local = _vary(sp_local, axes_all)
    key = rt.get("key")
    if key is not None:
        key = jax.random.fold_in(_vary(key, axes_all), s_idx)
        if seq_axis is not None:
            key = jax.random.fold_in(key, jax.lax.axis_index(seq_axis))
    mb = h.shape[0] // M
    micro = h.reshape(M, mb, *h.shape[1:])
    micro_ex = jax.tree.map(
        lambda m, batched: m.reshape(M, m.shape[0] // M, *m.shape[1:]) if batched else m,
        ex, is_batched,
    )
    micro_lb = jax.tree.map(lambda m: m.reshape(M, m.shape[0] // M, *m.shape[1:]), lb)

    def ex_at(m_idx):
        return jax.tree.map(
            lambda m, batched, dt: (
                jax.lax.dynamic_index_in_dim(m, m_idx, 0, keepdims=False)
                if batched else m
            ).astype(dt),
            micro_ex, is_batched, ex_dtypes,
        )

    return s_idx, is_last, sp_local, pp, key, mb, micro, micro_ex, micro_lb, ex_at


def _pvg_loss_vjp(loss_f, pp, y, do_loss):
    """Loss-head forward+vjp, gated on ``do_loss`` — a tick-level predicate
    that is UNVARYING across devices (derived from the tick index / a
    schedule table, never from ``axis_index``), so ``lax.cond`` runs ONE
    branch and all devices agree (collectives inside ``loss_f``, e.g. the
    seq-sharded label-shift ppermute, stay consistent).  Without the gate
    every tick of every device would pay a full loss-head fwd+bwd
    (final-norm + lm_head over a microbatch + CE) that only the last
    stage's real loss ticks need — for large-vocab models that fixed cost
    rivals a layer chunk's.  Returns ``(ls_m, tk_m, d_pp_m, dy_loss)``;
    the skip branch returns zeros of the same shapes/dtypes (vma types
    derived from the varying operands, so ``check_vma`` stays happy).
    ``y`` may be any pytree (a single activation array here; the twin
    seq2seq executor carries an {enc, dec} pair through the same gate)."""

    def with_loss(ops):
        pp_, y_ = ops
        (ls_m, tk_m), loss_vjp = jax.vjp(loss_f, pp_, y_)
        # cotangents must carry exactly the outputs' vma type (varying or
        # not, depending on what loss_f computes) — derive from the outputs
        d_pp_m, dy_loss = loss_vjp((ls_m * 0 + 1, tk_m * 0))
        return ls_m, tk_m, d_pp_m, dy_loss

    def skip_loss(ops):
        pp_, y_ = ops
        out_sh = jax.eval_shape(loss_f, pp_, y_)
        zscal = jax.tree.leaves(y_)[0].ravel()[0] * 0
        ls_m = zscal.astype(out_sh[0].dtype)
        tk_m = zscal.astype(out_sh[1].dtype)
        d_pp_m = jax.tree.map(lambda p: p * 0, pp_)
        dy_loss = jax.tree.map(lambda a: a * 0, y_)
        return ls_m, tk_m, d_pp_m, dy_loss

    return jax.lax.cond(do_loss, with_loss, skip_loss, (pp, y))


def _pvg_body_epilogue(lsum, toks, d_sp, d_pp, d_h, h_shape, *, axis_name,
                       axes_all, seq_axis):
    """Shared reduction epilogue: loss/tail grads live on the last stage,
    d_hidden on stage 0 (updates already masked to those stages); psum
    replicates.  Under sequence parallelism the scalars and param/tail
    grads additionally reduce over the seq shards (all fp32 — bf16 psums
    over manual axes crash the partitioner); d_h stays seq-sharded (it IS
    the local positions' gradient)."""
    lsum = jax.lax.psum(lsum, axes_all)
    toks = jax.lax.psum(toks, axes_all)
    d_pp = jax.tree.map(lambda g: jax.lax.psum(g, axes_all), d_pp)
    d_h = jax.lax.psum(d_h, axis_name)
    if seq_axis is not None:
        d_sp = jax.tree.map(lambda g: jax.lax.psum(g, seq_axis), d_sp)
    return lsum, toks, d_sp, d_pp, d_h.reshape(h_shape)


def _pvg_shard_map(body, *, mesh, axis_name, axes_all, seq_axis, n_seq,
                   stacked_params, post_params, hidden, extras, loss_batch,
                   rng, extras_seq_dims, loss_seq_dims, with_aux=False):
    """Shared spec construction + ``shard_map`` epilogue for the fused-
    schedule executors.  ``body(sp, pp, h, ex, lb, rt)`` returns
    ``(lsum, tokens, d_sp, d_pp, d_h)`` (plus an aux-sum scalar when
    ``with_aux``); it is wrapped in the ``manual_sequence`` context when a
    sequence axis is live."""
    param_specs = jax.tree.map(lambda x: _full_spec(axis_name, x.ndim), stacked_params)
    rng_tree = {} if rng is None else {"key": rng}
    if seq_axis is None:
        hidden_spec = P()
        extras_specs = jax.tree.map(lambda m: P(), extras)
        loss_specs = jax.tree.map(lambda m: P(), loss_batch)
    else:
        hidden_spec, extras_specs, loss_specs = _seq_specs(
            seq_axis, hidden.ndim, (extras, extras_seq_dims), (loss_batch, loss_seq_dims)
        )

    def outer(sp, pp, h, ex, lb, rt):
        if seq_axis is None:
            return body(sp, pp, h, ex, lb, rt)
        with manual_sequence(seq_axis, n_seq):
            return body(sp, pp, h, ex, lb, rt)

    return jax.shard_map(
        outer,
        mesh=mesh,
        axis_names=set(axes_all),
        in_specs=(
            param_specs,
            jax.tree.map(lambda _: P(), post_params),
            hidden_spec,
            extras_specs,
            loss_specs,
            jax.tree.map(lambda _: P(), rng_tree),
        ),
        out_specs=(
            P(), P(), param_specs,
            jax.tree.map(lambda _: P(), post_params),
            hidden_spec,
            *((P(),) if with_aux else ()),
        ),
        check_vma=True,
    )(stacked_params, post_params, hidden, extras, loss_batch, rng_tree)


def pipeline_value_and_grad(
    layer_fn: Callable,
    post_loss_fn: Callable,
    stacked_params: Any,
    post_params: Any,
    hidden: jnp.ndarray,
    extras: Any,
    loss_batch: Any,
    *,
    mesh: Mesh,
    num_microbatches: int,
    axis_name: str = "stage",
    batch_axes: tuple[str, ...] = ("data", "fsdp", "expert"),
    checkpoint: bool = True,
    rng: jnp.ndarray | None = None,
    seq_axis: str | None = None,
    extras_seq_dims: Any = None,
    loss_seq_dims: Any = None,
    with_aux: bool = False,
    aux_cotangent: jnp.ndarray | float = 0.0,
):
    """1F1B pipeline schedule: loss AND parameter gradients in ONE fused
    scan, backward microbatches interleaved with forward.

    The GPipe path (``pipeline_apply`` + autodiff) must keep every
    microbatch's stage activations alive between the forward scan and the
    reversed backward scan — O(M) activations per stage.  Differentiating
    through the scan cannot reorder that; interleaving requires owning the
    backward, so this function computes gradients itself:

    - tick ``t``, stage ``s`` FORWARDS microbatch ``t - s`` (saving only
      the CHUNK INPUT in a ring buffer of ``2S - 1`` slots) and BACKWARDS
      microbatch ``t - (2(S-1) - s)`` via ``jax.vjp`` of the stage chunk —
      which recomputes the chunk forward, so per-stage activation memory is
      O(S) ring slots + one chunk's transient, independent of M;
    - the last stage folds the loss in-tick: its just-finished forward
      microbatch immediately drives ``post_loss_fn``'s vjp, and the
      resulting activation-gradient starts hopping backwards on the SAME
      tick (the 1F1B signature — microbatch 0's backward begins while
      microbatch S's forward is still entering the pipe);
    - activation-gradients ride a second ``ppermute`` ring in the opposite
      direction; total ticks = M + 2(S-1).

    The trade, honestly: under SPMD every stage executes both the F and B
    slots of every tick (masked when inactive), so wall-clock is
    ~(M + 2(S-1)) fused ticks vs GPipe's (M+S-1) forward + (M+S-1)
    backward ticks — about (S-1) extra tick-equivalents of compute — in
    exchange for activation memory dropping from O(M) to O(S) microbatches
    per stage.  That is the trade that makes LARGE microbatch counts (the
    bubble amortizer) affordable at stage>2.

    ``layer_fn(p, h, ex[, key]) -> h`` as in ``pipeline_apply``.
    ``post_loss_fn(post_params, h, loss_microbatch) -> (loss_sum, tokens)``
    runs the model tail + loss for ONE microbatch (token-SUM semantics so
    microbatch results add exactly).  ``loss_batch``: pytree of per-example
    arrays (leading dim B) consumed by the loss.  Returns
    ``(loss_sum, tokens, d_stacked, d_post, d_hidden)`` — unnormalized
    sums, gradients of loss_sum w.r.t. the three differentiable inputs.

    Schedule-only reordering: the math per microbatch is identical to the
    sequential computation, so results match GPipe and the single-device
    step exactly (tests/test_pipeline.py::test_1f1b_*).

    ``seq_axis``/``extras_seq_dims``: sequence-parallel composition, same
    contract as ``pipeline_apply`` — ONE manual region over {stage,
    seq_axis}, ``layer_fn``/``post_loss_fn`` traced under a
    ``manual_sequence`` context with LOCAL sequence shards.
    ``loss_seq_dims``: like ``extras_seq_dims`` but for ``loss_batch``
    (e.g. next-token labels shard dim 1; the loss fn must handle the
    cross-shard target shift itself — see models/llama.py).  All manual-
    axis gradient reductions run in fp32 (bf16 psums over manual axes
    crash the partitioner, see ``pipeline_apply``).

    ``with_aux``: ``layer_fn`` returns ``(h, aux_scalar)`` (the MoE
    load-balance loss).  The call then additionally returns ``aux_sum``
    (the raw sum over all L layers × M microbatches — the caller
    normalizes), and every chunk vjp receives ``aux_cotangent`` as the
    aux output's cotangent so its gradient lands in d_stacked/d_hidden
    with everything else.  ``aux_cotangent`` must be the CONSTANT
    d(objective)/d(aux_sum) — for the ``moe_weight·aux_mean·tokens``
    objective that is ``moe_weight·tokens/(L·M)``, computable from the
    labels alone BEFORE the schedule runs (token counts don't depend on
    params).  Does not compose with ``seq_axis`` (per-shard router
    statistics would need their own reduction — same restriction as
    ``pipeline_apply``).
    """
    S = mesh.shape.get(axis_name, 1)
    M = num_microbatches
    L = jax.tree.leaves(stacked_params)[0].shape[0]
    if L % max(S, 1):
        raise ValueError(f"{L} layers not divisible into {S} pipeline stages")
    if with_aux and seq_axis is not None and mesh.shape.get(seq_axis, 1) > 1:
        raise ValueError(reason_for("pipeline-sequence-moe"))
    run_stage = _make_run_stage(layer_fn, checkpoint, with_aux)
    _pvg_check_batch(hidden.shape[0], mesh, M, batch_axes)
    if S == 1:
        if with_aux:
            return _pvg_single_stage_aux(
                run_stage, post_loss_fn, stacked_params, post_params,
                hidden, extras, loss_batch, rng, aux_cotangent, M,
            )
        return _pvg_single_stage(
            run_stage, post_loss_fn, stacked_params, post_params,
            hidden, extras, loss_batch, rng,
        )
    (seq_axis, n_seq, axes_all, is_batched, ex_dtypes, compute_dtype,
     plumb_dtype, hidden, extras) = _pvg_common(
        hidden, extras, mesh=mesh, axis_name=axis_name, seq_axis=seq_axis,
    )
    K = 2 * S - 1  # ring depth ≥ max activation lifetime in ticks (stage 0)
    T = M + 2 * (S - 1)

    def body(sp_local, pp, h, ex, lb, rt):
        h_shape = h.shape
        (s_idx, is_last, sp_local, pp, key, mb, micro, micro_ex, micro_lb,
         ex_at) = _pvg_body_prologue(
            sp_local, pp, h, ex, lb, rt, S=S, M=M, axis_name=axis_name,
            axes_all=axes_all, seq_axis=seq_axis, plumb_dtype=plumb_dtype,
            is_batched=is_batched, ex_dtypes=ex_dtypes,
        )

        zeros_like_f32 = lambda t: jax.tree.map(  # noqa: E731
            lambda x: _vary(jnp.zeros(x.shape, jnp.float32), axes_all), t
        )
        fwd_buf = _vary(jnp.zeros((mb, *h.shape[1:]), plumb_dtype), axes_all)
        bwd_buf = _vary(jnp.zeros((mb, *h.shape[1:]), plumb_dtype), axes_all)
        act = _vary(jnp.zeros((K, mb, *h.shape[1:]), plumb_dtype), axes_all)
        d_sp = zeros_like_f32(sp_local)
        d_pp = zeros_like_f32(pp)
        d_h = _vary(jnp.zeros((M, mb, *h.shape[1:]), jnp.float32), axes_all)
        scal0 = _vary(jnp.zeros((), jnp.float32), axes_all)
        aux_ct = _vary(jnp.asarray(aux_cotangent, jnp.float32), axes_all)
        perm_fwd = [(i, i + 1) for i in range(S - 1)]
        perm_bwd = [(i + 1, i) for i in range(S - 1)]

        def tick(carry, t):
            fwd_buf, bwd_buf, act, d_sp, d_pp, d_h, lsum, toks, aux_acc = carry
            mf = t - s_idx
            mb_i = t - (2 * (S - 1) - s_idx)
            act_f = (mf >= 0) & (mf < M)
            act_b = (mb_i >= 0) & (mb_i < M)
            mf_c = jnp.clip(mf, 0, M - 1)
            mb_c = jnp.clip(mb_i, 0, M - 1)

            # ---- forward: one microbatch through this stage's chunk
            x0 = jax.lax.dynamic_index_in_dim(micro, mf_c, 0, keepdims=False)
            x_in = jnp.where(s_idx == 0, x0, fwd_buf)
            ex_f = ex_at(mf_c)
            key_f = None if key is None else jax.random.fold_in(key, mf_c)

            def chunk_f(p_, x_):
                out = run_stage(p_, x_.astype(compute_dtype), ex_f, key_f)
                if with_aux:
                    return out[0].astype(plumb_dtype), out[1]
                return out.astype(plumb_dtype)

            y = chunk_f(sp_local, x_in)
            if with_aux:
                y, aux_f = y
                aux_acc = aux_acc + jnp.where(act_f, aux_f.astype(jnp.float32), 0.0)
            act = jax.lax.dynamic_update_index_in_dim(act, x_in, mf_c % K, 0)

            # ---- last stage: loss fwd+vjp for the microbatch it just
            # finished (1F then immediately 1B of the same microbatch).
            # The gate is TICK-level (the last stage's F is active exactly
            # on ticks S-1 .. S-1+M-1) and unvarying across devices, so
            # the loss head runs on M ticks instead of all T.
            lb_f = jax.tree.map(
                lambda m: jax.lax.dynamic_index_in_dim(m, mf_c, 0, keepdims=False),
                micro_lb,
            )

            def loss_f(pp_, y_):
                return post_loss_fn(pp_, y_.astype(compute_dtype), lb_f)

            do_loss = (t >= S - 1) & (t < S - 1 + M)
            ls_m, tk_m, d_pp_m, dy_loss = _pvg_loss_vjp(loss_f, pp, y, do_loss)
            take_loss = is_last & act_f
            lsum = lsum + jnp.where(take_loss, ls_m.astype(jnp.float32), 0.0)
            toks = toks + jnp.where(take_loss, tk_m.astype(jnp.float32), 0.0)
            d_pp = jax.tree.map(
                lambda a, g: a + jnp.where(take_loss, g.astype(jnp.float32), 0.0),
                d_pp, d_pp_m,
            )

            # ---- backward: vjp of this stage's chunk for an EARLIER
            # microbatch (recomputes the chunk forward — remat)
            x_b = jax.lax.dynamic_index_in_dim(act, mb_c % K, 0, keepdims=False)
            ex_b = ex_at(mb_c)
            key_b = None if key is None else jax.random.fold_in(key, mb_c)

            def chunk_b(p_, x_):
                out = run_stage(p_, x_.astype(compute_dtype), ex_b, key_b)
                if with_aux:
                    return out[0].astype(plumb_dtype), out[1]
                return out.astype(plumb_dtype)

            _, chunk_vjp = jax.vjp(chunk_b, sp_local, x_b)
            dy_in = jnp.where(is_last, dy_loss.astype(plumb_dtype), bwd_buf)
            if with_aux:
                # the aux output's cotangent: the constant objective
                # coefficient, masked to active backward ticks (bubble
                # ticks' dx is never consumed, but bounding it costs one
                # where and keeps the invariant obvious)
                aux_dy = jnp.where(act_b, aux_ct, 0.0)
                d_sp_m, dx = chunk_vjp((dy_in, aux_dy))
            else:
                d_sp_m, dx = chunk_vjp(dy_in)
            d_sp = jax.tree.map(
                lambda a, g: a + jnp.where(act_b, g.astype(jnp.float32), 0.0),
                d_sp, d_sp_m,
            )
            d_h_upd = jax.lax.dynamic_update_index_in_dim(
                d_h, dx.astype(jnp.float32), mb_c, 0
            )
            d_h = jnp.where(act_b & (s_idx == 0), d_h_upd, d_h)

            # ---- hops: activations forward, activation-grads backward
            fwd_buf = jax.lax.ppermute(y, axis_name, perm_fwd)
            bwd_buf = jax.lax.ppermute(dx.astype(plumb_dtype), axis_name, perm_bwd)
            return (fwd_buf, bwd_buf, act, d_sp, d_pp, d_h, lsum, toks, aux_acc), None

        carry = (fwd_buf, bwd_buf, act, d_sp, d_pp, d_h, scal0, scal0, scal0)
        (fwd_buf, bwd_buf, act, d_sp, d_pp, d_h, lsum, toks, aux_acc), _ = jax.lax.scan(
            tick, carry, jnp.arange(T)
        )
        out = _pvg_body_epilogue(
            lsum, toks, d_sp, d_pp, d_h, h_shape,
            axis_name=axis_name, axes_all=axes_all, seq_axis=seq_axis,
        )
        if with_aux:
            # every (stage-chunk, microbatch) contributed its layer-sum once
            return (*out, jax.lax.psum(aux_acc, axis_name))
        return out

    return _pvg_shard_map(
        body, mesh=mesh, axis_name=axis_name, axes_all=axes_all,
        seq_axis=seq_axis, n_seq=n_seq, stacked_params=stacked_params,
        post_params=post_params, hidden=hidden, extras=extras,
        loss_batch=loss_batch, rng=rng, extras_seq_dims=extras_seq_dims,
        loss_seq_dims=loss_seq_dims, with_aux=with_aux,
    )


def pipeline_value_and_grad_interleaved(
    layer_fn: Callable,
    post_loss_fn: Callable,
    stacked_params: Any,
    post_params: Any,
    hidden: jnp.ndarray,
    extras: Any,
    loss_batch: Any,
    *,
    mesh: Mesh,
    num_microbatches: int,
    virtual_stages: int,
    axis_name: str = "stage",
    batch_axes: tuple[str, ...] = ("data", "fsdp", "expert"),
    checkpoint: bool = True,
    rng: jnp.ndarray | None = None,
    seq_axis: str | None = None,
    extras_seq_dims: Any = None,
    loss_seq_dims: Any = None,
    with_aux: bool = False,
    aux_cotangent: jnp.ndarray | float = 0.0,
):
    """Interleaved (virtual-stage) 1F1B: each device runs ``virtual_stages``
    NON-CONTIGUOUS layer chunks, table-driven by a precomputed schedule
    (``parallel/interleave.py`` — see its docstring for the model and the
    honest cost accounting: in this fused-tick SPMD executor the win over
    plain 1F1B is the shorter tick count T(v)/v < T(1), ~7-10% of pipeline
    wall at stage >= 4, growing with depth; the price is ~v× more buffered
    chunk inputs.  The loss-head vjp is gated to its M real ticks on BOTH
    schedules — ``_pvg_loss_vjp`` — so it does not scale with T(v)).
    ``stacked_params`` rows must already be in INTERLEAVED
    storage order (``interleave.interleave_tree``): device ``s``'s shard
    holds its v chunks contiguously, chunk ``c`` covering true layers
    ``(c*S + s) * Lc .. + Lc``.  Same contract as
    ``pipeline_value_and_grad`` otherwise; ``virtual_stages=1`` is plain
    1F1B through the table machinery (the equivalence tests pin both
    against the single-device step).  ``with_aux``/``aux_cotangent``:
    same MoE contract as ``pipeline_value_and_grad`` — chunks emit their
    aux sums and every chunk vjp takes the constant objective
    coefficient as the aux output's cotangent.
    """
    from distributed_llms_example_tpu.parallel.interleave import (
        make_interleaved_schedule,
    )

    S = mesh.shape.get(axis_name, 1)
    M = num_microbatches
    v = int(virtual_stages)
    L = jax.tree.leaves(stacked_params)[0].shape[0]
    if with_aux and seq_axis is not None and mesh.shape.get(seq_axis, 1) > 1:
        raise ValueError(reason_for("pipeline-sequence-moe"))
    run_stage = _make_run_stage(layer_fn, checkpoint, with_aux)
    _pvg_check_batch(hidden.shape[0], mesh, M, batch_axes)
    if S == 1:
        if with_aux:
            return _pvg_single_stage_aux(
                run_stage, post_loss_fn, stacked_params, post_params,
                hidden, extras, loss_batch, rng, aux_cotangent, M,
            )
        return _pvg_single_stage(
            run_stage, post_loss_fn, stacked_params, post_params,
            hidden, extras, loss_batch, rng,
        )
    if L % (S * v):
        raise ValueError(
            f"{L} layers not divisible into {S} stages x {v} virtual chunks"
        )
    sc = make_interleaved_schedule(S, v, M)
    (seq_axis, n_seq, axes_all, is_batched, ex_dtypes, compute_dtype,
     plumb_dtype, hidden, extras) = _pvg_common(
        hidden, extras, mesh=mesh, axis_name=axis_name, seq_axis=seq_axis,
    )

    # schedule tables as device constants; each tick reads its own row
    tbl = {
        name: jnp.asarray(getattr(sc, name))
        for name in (
            "f_active", "f_micro", "f_chunk", "f_src_q", "f_save", "arr_f",
            "b_active", "b_micro", "b_chunk", "b_act", "b_src_q", "arr_b",
            "b_emit_dh",
        )
    }
    # tick-level (device-independent) gate for the loss-head vjp: the
    # ticks where device S-1 forwards the loss chunk — exactly M of them
    _t_loss_np = (sc.f_active[:, S - 1] == 1) & (sc.f_chunk[:, S - 1] == v - 1)
    if int(_t_loss_np.sum()) != M:  # not assert: must survive python -O
        raise ValueError(
            f"interleaved schedule runs the loss chunk {int(_t_loss_np.sum())} "
            f"times, expected {M}"
        )
    t_loss = jnp.asarray(_t_loss_np)

    def body(sp_local, pp, h, ex, lb, rt):
        h_shape = h.shape
        (s_idx, is_last, sp_local, pp, key, mb, micro, micro_ex, micro_lb,
         ex_at) = _pvg_body_prologue(
            sp_local, pp, h, ex, lb, rt, S=S, M=M, axis_name=axis_name,
            axes_all=axes_all, seq_axis=seq_axis, plumb_dtype=plumb_dtype,
            is_batched=is_batched, ex_dtypes=ex_dtypes,
        )
        # local rows -> (v, Lc, ...): chunk c of device s = global chunk
        # c*S + s (the interleaved storage order)
        sp_v = jax.tree.map(
            lambda a: a.reshape(v, a.shape[0] // v, *a.shape[1:]), sp_local
        )

        def chunk_key(c_idx, m_idx):
            if key is None:
                return None
            return jax.random.fold_in(jax.random.fold_in(key, c_idx), m_idx)

        def chunk_run(p_all, c_idx, x, ex_c, k):
            p_c = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, c_idx, 0, keepdims=False),
                p_all,
            )
            out = run_stage(p_c, x.astype(compute_dtype), ex_c, k)
            if with_aux:
                return out[0].astype(plumb_dtype), out[1]
            return out.astype(plumb_dtype)

        zeros_like_f32 = lambda t: jax.tree.map(  # noqa: E731
            lambda x: _vary(jnp.zeros(x.shape, jnp.float32), axes_all), t
        )
        zbuf = lambda n: _vary(jnp.zeros((n, mb, *h.shape[1:]), plumb_dtype), axes_all)  # noqa: E731
        fwd_in = zbuf(1)[0]
        bwd_in = zbuf(1)[0]
        fqbuf = zbuf(sc.fq_depth)
        bqbuf = zbuf(sc.bq_depth)
        act = zbuf(sc.act_depth)
        d_sp = zeros_like_f32(sp_v)
        d_pp = zeros_like_f32(pp)
        d_h = _vary(jnp.zeros((M, mb, *h.shape[1:]), jnp.float32), axes_all)
        scal0 = _vary(jnp.zeros((), jnp.float32), axes_all)
        aux_ct = _vary(jnp.asarray(aux_cotangent, jnp.float32), axes_all)
        perm_fwd = [(i, (i + 1) % S) for i in range(S)]
        perm_bwd = [(i, (i - 1) % S) for i in range(S)]

        def at(name, t):
            return tbl[name][t, s_idx]

        def tick(carry, t):
            (fwd_in, bwd_in, fqbuf, bqbuf, act, d_sp, d_pp, d_h, lsum, toks,
             aux_acc) = carry

            # ---- queue arrivals (values sent on the rings last tick)
            af = at("arr_f", t)
            fq_upd = jax.lax.dynamic_update_index_in_dim(
                fqbuf, fwd_in, jnp.clip(af, 0, sc.fq_depth - 1), 0
            )
            fqbuf = jnp.where(af >= 0, fq_upd, fqbuf)
            ab = at("arr_b", t)
            bq_upd = jax.lax.dynamic_update_index_in_dim(
                bqbuf, bwd_in, jnp.clip(ab, 0, sc.bq_depth - 1), 0
            )
            bqbuf = jnp.where(ab >= 0, bq_upd, bqbuf)

            # ---- forward slot
            f_on = at("f_active", t) == 1
            fm = at("f_micro", t)
            fc = at("f_chunk", t)
            fsrc = at("f_src_q", t)
            x0 = jax.lax.dynamic_index_in_dim(micro, fm, 0, keepdims=False)
            xq = jax.lax.dynamic_index_in_dim(
                fqbuf, jnp.clip(fsrc, 0, sc.fq_depth - 1), 0, keepdims=False
            )
            x_in = jnp.where(fsrc < 0, x0, xq)
            ex_f = ex_at(fm)
            y = chunk_run(sp_v, fc, x_in, ex_f, chunk_key(fc, fm))
            if with_aux:
                y, aux_f = y
                aux_acc = aux_acc + jnp.where(f_on, aux_f.astype(jnp.float32), 0.0)
            a_save = jnp.clip(at("f_save", t), 0, sc.act_depth - 1)
            act_upd = jax.lax.dynamic_update_index_in_dim(act, x_in, a_save, 0)
            act = jnp.where(f_on, act_upd, act)

            # ---- loss vjp on the in-tick forward output; tick-gated by
            # the schedule table (unvarying across devices → lax.cond),
            # folded only where this slot IS the loss chunk
            lb_f = jax.tree.map(
                lambda m: jax.lax.dynamic_index_in_dim(m, fm, 0, keepdims=False),
                micro_lb,
            )

            def loss_f(pp_, y_):
                return post_loss_fn(pp_, y_.astype(compute_dtype), lb_f)

            ls_m, tk_m, d_pp_m, dy_loss = _pvg_loss_vjp(loss_f, pp, y, t_loss[t])
            take_loss = f_on & is_last & (fc == v - 1)
            lsum = lsum + jnp.where(take_loss, ls_m.astype(jnp.float32), 0.0)
            toks = toks + jnp.where(take_loss, tk_m.astype(jnp.float32), 0.0)
            d_pp = jax.tree.map(
                lambda a_, g: a_ + jnp.where(take_loss, g.astype(jnp.float32), 0.0),
                d_pp, d_pp_m,
            )

            # ---- backward slot (recomputes its chunk forward under vjp)
            b_on = at("b_active", t) == 1
            bm = at("b_micro", t)
            bc = at("b_chunk", t)
            bsrc = at("b_src_q", t)
            x_b = jax.lax.dynamic_index_in_dim(
                act, jnp.clip(at("b_act", t), 0, sc.act_depth - 1), 0, keepdims=False
            )
            ex_b = ex_at(bm)
            k_b = chunk_key(bc, bm)

            def chunk_b(p_, x_):
                return chunk_run(p_, bc, x_, ex_b, k_b)

            _, chunk_vjp = jax.vjp(chunk_b, sp_v, x_b)
            dy_q = jax.lax.dynamic_index_in_dim(
                bqbuf, jnp.clip(bsrc, 0, sc.bq_depth - 1), 0, keepdims=False
            )
            dy_in = jnp.where(bsrc < 0, dy_loss.astype(plumb_dtype), dy_q)
            if with_aux:
                # constant objective coefficient on active backward ticks
                # (see pipeline_value_and_grad)
                d_sp_m, dx = chunk_vjp((dy_in, jnp.where(b_on, aux_ct, 0.0)))
            else:
                d_sp_m, dx = chunk_vjp(dy_in)
            d_sp = jax.tree.map(
                lambda a_, g: a_ + jnp.where(b_on, g.astype(jnp.float32), 0.0),
                d_sp, d_sp_m,
            )
            emit = (at("b_emit_dh", t) == 1) & b_on
            d_h_upd = jax.lax.dynamic_update_index_in_dim(
                d_h, dx.astype(jnp.float32), bm, 0
            )
            d_h = jnp.where(emit, d_h_upd, d_h)

            # ---- ring hops
            fwd_in = jax.lax.ppermute(y, axis_name, perm_fwd)
            bwd_in = jax.lax.ppermute(dx.astype(plumb_dtype), axis_name, perm_bwd)
            return (fwd_in, bwd_in, fqbuf, bqbuf, act, d_sp, d_pp, d_h, lsum, toks,
                    aux_acc), None

        carry = (fwd_in, bwd_in, fqbuf, bqbuf, act, d_sp, d_pp, d_h, scal0, scal0,
                 scal0)
        carry, _ = jax.lax.scan(tick, carry, jnp.arange(sc.T))
        (d_sp, d_pp, d_h, lsum, toks, aux_acc) = (
            carry[5], carry[6], carry[7], carry[8], carry[9], carry[10]
        )
        # (v, Lc, ...) grads back to the sharded row layout first
        d_sp = jax.tree.map(
            lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]), d_sp
        )
        out = _pvg_body_epilogue(
            lsum, toks, d_sp, d_pp, d_h, h_shape,
            axis_name=axis_name, axes_all=axes_all, seq_axis=seq_axis,
        )
        if with_aux:
            return (*out, jax.lax.psum(aux_acc, axis_name))
        return out

    return _pvg_shard_map(
        body, mesh=mesh, axis_name=axis_name, axes_all=axes_all,
        seq_axis=seq_axis, n_seq=n_seq, stacked_params=stacked_params,
        post_params=post_params, hidden=hidden, extras=extras,
        loss_batch=loss_batch, rng=rng, extras_seq_dims=extras_seq_dims,
        loss_seq_dims=loss_seq_dims, with_aux=with_aux,
    )
