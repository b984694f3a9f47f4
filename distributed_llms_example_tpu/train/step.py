"""The SPMD train step — the heart of the framework.

One jitted function replaces all three of the reference's distribution
mechanisms (torchrun-DDP, Accelerate, hand-rolled NCCL loops):

- the global batch arrives sharded over the ``("data","fsdp")`` mesh axes;
- parameters and optimizer state are sharded by the path-regex rules
  (FSDP over ``fsdp``, megatron-style splits over ``tensor``);
- ``jax.value_and_grad`` of a *global-mean* loss makes the XLA SPMD
  partitioner insert the gradient all-reduce — the five hand-written lines
  of ``average_gradients`` (reference train-task.py:65-69, one NCCL call
  per tensor, no bucketing, no overlap) become zero lines here, and XLA
  overlaps the collectives with the backward pass;
- gradient accumulation is a ``lax.scan`` over microbatches (the
  TPU-native form of ``gradient_accumulation_steps=16``,
  reference train-torchrun.py:126), accumulating token-weighted loss and
  gradient sums so the result is exactly the full-batch gradient.

Gradient accumulation invariants (the in-step microbatching contract):

- the fp32 accumulators are sharded EXACTLY like the parameters
  (``accumulator_shardings`` is the one mirror; an explicit
  ``with_sharding_constraint`` pins the scan carry so FSDP keeps its
  reduce-scatter gradient shape and the accumulators never replicate —
  per the weight-update-sharding recipe of arXiv:2004.13336);
- microbatches are cut SHARD-LOCALLY when the microbatch divides the
  batch shards: each device scans over slices of rows it already holds,
  so the (B,) → (N, B/N) regrouping costs zero collectives.  Loss and
  gradient sums are additive over rows, so any partition of the batch
  into microbatches yields the identical optimizer step;
- clip + AdamW + the health numerics run ONCE per optimizer step, after
  the scan (``optimizer_apply_block`` — a named function so the IR lint
  can prove from compiled-HLO metadata that none of it slid into the
  scan body), amortizing the non-layer overhead over N microbatches;
- a global batch is ONE optimizer step regardless of ``accum_steps``:
  the data iterator, the step counter, checkpoints, and the health
  watchdog all count optimizer steps, so O(1) resume lands on an
  optimizer-step boundary by construction.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import flax.struct
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_llms_example_tpu.data.batching import LABEL_PAD
from distributed_llms_example_tpu.models.t5 import shift_right
from distributed_llms_example_tpu.parallel.activation import activation_mesh
from distributed_llms_example_tpu.parallel.sharding import (
    ShardingRules,
    batch_sharding,
    default_rules,
    resolve_shardings,
)


@flax.struct.dataclass
class TrainState:
    """step / params / opt_state, plus ``ef`` — the error-feedback tree of
    ``--grad-compression int8`` (``ops/quant_collectives.py``): per-leaf
    ``(W, *shape)`` fp32 quantization residuals, worker dim over the
    replica axes, inner dims sharded exactly like the params.  ``None``
    whenever compression is off (the default), which keeps the off path's
    compiled program bit-identical to the pre-compression step.  Carried
    in the state so checkpoints resume it; a checkpoint written without
    it (older run, or compression off) resumes with a zero-filled tree —
    step 0 semantics, no error to feed back yet."""

    step: jnp.ndarray
    params: Any
    opt_state: Any
    ef: Any = None


# ---------------------------------------------------------------------------
# In-graph training-health telemetry (the obs/health.py numerics source).
#
# Everything here is computed INSIDE the pjit'd step — a handful of
# elementwise reductions riding the same program as the loss, so the
# values are device scalars like ``loss``/``grad_norm`` and cost zero
# extra device syncs: the watchdog converts them to host floats only at
# the logging cadence (the same fetch the MetricLogger already pays).
# ---------------------------------------------------------------------------

# Coarse parameter buckets for the per-bucket update ratio.  A uniform
# whole-tree ratio hides the classic failure signatures (an embedding
# whose updates dwarf its weights while the MLPs are healthy, a head
# diverging under a bad label stream), and a per-leaf report would be
# thousands of scalars; four buckets is the resolution operators act on.
HEALTH_BUCKETS = ("embed", "attn", "mlp", "head")

# The per-step scalars a health-enabled step adds to its metrics dict.
HEALTH_METRIC_KEYS: tuple[str, ...] = (
    "param_norm",
    "nonfinite_count",
) + tuple(f"update_ratio_{b}" for b in HEALTH_BUCKETS)


def bucket_of_path(path: tuple) -> str:
    """Coarse bucket for one parameter path (a jax key-path tuple).

    Name matching covers every family in models/: llama (embed_tokens /
    self_attn / mlp / lm_head), t5 (shared / self_attn / cross_attn /
    mlp / lm_head), bart (shared / *_embed_positions / self_attn / mlp),
    and the pipelined stacked trees (same leaf names under
    ``stacked_blocks``).  The matching table itself lives in
    analysis/ir_lint.py (``MODULE_BUCKET_PATTERNS``) and is shared with
    the device-time attribution of HLO ``op_name`` scopes
    (obs/devprof.py) — one definition of what "attn" means.  Unmatched
    leaves (norms, biases) fall to ``mlp`` — a param bucket must be
    total, and misfiling a layernorm scale costs nothing the per-bucket
    ratio is watching for.
    """
    from distributed_llms_example_tpu.analysis.ir_lint import module_bucket_of

    p = "/".join(
        str(getattr(k, "key", getattr(k, "name", getattr(k, "idx", k)))) for k in path
    )
    return module_bucket_of(p) or "mlp"


def _bucket_sumsq(tree: Any) -> dict[str, jnp.ndarray]:
    sums = {b: jnp.zeros((), jnp.float32) for b in HEALTH_BUCKETS}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        b = bucket_of_path(path)
        sums[b] = sums[b] + jnp.sum(jnp.square(leaf.astype(jnp.float32)))
    return sums


def health_metrics(params: Any, grads: Any, updates: Any) -> dict[str, jnp.ndarray]:
    """The in-graph numerics bundle: global param norm, non-finite grad
    element count, and per-bucket update ratios ||Δw|| / ||w|| (the
    step-size-relative-to-weights signal; healthy AdamW fine-tuning sits
    around 1e-3, a bucket at 1e-1 is about to diverge)."""
    p_sq = _bucket_sumsq(params)
    u_sq = _bucket_sumsq(updates)
    # integer accumulation per leaf: a float32 ``size - finite_count``
    # rounds 1-4 NaNs in a 1e8-element leaf to exactly 0 (spacing 8 at
    # that magnitude) — the one signal the tripwire must never lose
    nonfinite = jnp.zeros((), jnp.float32)
    for g in jax.tree.leaves(grads):
        nonfinite = nonfinite + jnp.sum(~jnp.isfinite(g)).astype(jnp.float32)
    out: dict[str, jnp.ndarray] = {
        "param_norm": jnp.sqrt(sum(p_sq.values())),
        "nonfinite_count": nonfinite,
    }
    for b in HEALTH_BUCKETS:
        out[f"update_ratio_{b}"] = jnp.sqrt(u_sq[b]) / jnp.maximum(
            jnp.sqrt(p_sq[b]), 1e-12
        )
    return out


def create_train_state(
    params: Any,
    tx: optax.GradientTransformation,
    *,
    grad_compression: str = "off",
    workers: int = 1,
) -> TrainState:
    """``grad_compression="int8"`` additionally allocates the zero
    error-feedback tree (``workers`` = the replica-axis product — see
    ``ops/quant_collectives.py worker_count``)."""
    ef = None
    if grad_compression == "int8":
        from distributed_llms_example_tpu.ops.quant_collectives import (
            zero_error_feedback,
        )

        ef = zero_error_feedback(params, workers)
    return TrainState(
        step=jnp.zeros((), jnp.int32), params=params,
        opt_state=tx.init(params), ef=ef,
    )


def accumulator_shardings(param_shardings: Any) -> Any:
    """Shardings for the in-step fp32 gradient accumulators: EXACTLY the
    param shardings, leaf for leaf.

    This identity is THE accumulator layout contract — the scan carry is
    constrained with it, ``analysis/spec_lint.py`` lints against it, and
    the compiled-carry test pins it — so the three cannot drift.  Anything
    else either replicates a param-sized fp32 tree per device (the memory
    cliff accumulation exists to avoid) or forces GSPMD to reshard every
    microbatch's gradients against the carry."""
    return jax.tree.map(lambda s: s, param_shardings)


def health_metrics_from_stats(stats: Any) -> dict[str, jnp.ndarray]:
    """The health bundle assembled from the fused optimizer kernel's
    per-leaf partial sums (``ops/fused_optim.py`` — param/update
    sum-of-squares and non-finite grad counts produced in the SAME
    kernel pass as the update) instead of a separate reduction pass.
    Same keys and semantics as :func:`health_metrics`; per-bucket sums
    may differ from it in fp reduction order only."""
    from distributed_llms_example_tpu.ops.fused_optim import (
        STAT_NONFINITE,
        STAT_P_SUMSQ,
        STAT_U_SUMSQ,
    )

    p_sq = {b: jnp.zeros((), jnp.float32) for b in HEALTH_BUCKETS}
    u_sq = {b: jnp.zeros((), jnp.float32) for b in HEALTH_BUCKETS}
    nonfinite = jnp.zeros((), jnp.float32)
    for path, leaf in jax.tree_util.tree_leaves_with_path(stats):
        b = bucket_of_path(path)
        p_sq[b] = p_sq[b] + leaf[STAT_P_SUMSQ]
        u_sq[b] = u_sq[b] + leaf[STAT_U_SUMSQ]
        nonfinite = nonfinite + leaf[STAT_NONFINITE]
    out: dict[str, jnp.ndarray] = {
        "param_norm": jnp.sqrt(sum(p_sq.values())),
        "nonfinite_count": nonfinite,
    }
    for b in HEALTH_BUCKETS:
        out[f"update_ratio_{b}"] = jnp.sqrt(u_sq[b]) / jnp.maximum(
            jnp.sqrt(p_sq[b]), 1e-12
        )
    return out


def optimizer_apply_block(
    state: TrainState,
    tx: optax.GradientTransformation,
    schedule: optax.Schedule,
    lsum: jnp.ndarray,
    tokens: jnp.ndarray,
    grads: Any,
    *,
    health: bool,
    fused: Any = None,
    ef: Any = None,
) -> tuple[TrainState, dict]:
    """The once-per-optimizer-step tail: normalize the token-weighted
    sums, clip + AdamW, and the health numerics.

    ``fused`` (a ``train.optim.FusedOptimPlan``, or None) selects the
    impl: None runs the optax chain through ``train.optim
    .optimizer_update`` (the ``xla`` impl — the one owner of the raw
    apply, repo-lint rule 8); a plan runs the Pallas fused
    clip+AdamW(+health) apply in place (``--optim-impl fused``), with
    the health numerics sourced from the kernel's partial sums.  The
    impls run the identical op sequence — equal up to XLA float
    contraction (test-pinned), same opt-state pytree.

    A NAMED function on purpose: jax stamps each HLO instruction with the
    first non-library source frame, so everything traced here (including
    optax's clip/adamw internals, attributed to the call lines below)
    carries this function's source span — ``once_per_step_source_spans``
    hands that span to ``analysis/ir_lint.py``, which proves on the
    compiled program that none of it was scheduled inside the
    grad-accumulation scan body, i.e. the optimizer genuinely runs once
    per step regardless of ``accum_steps``."""
    from distributed_llms_example_tpu.train.optim import (
        fused_optimizer_apply,
        optimizer_update,
    )

    tokens = jnp.maximum(tokens, 1.0)
    loss = lsum / tokens
    grads = jax.tree.map(lambda g: (g / tokens).astype(jnp.float32), grads)
    if fused is not None:
        new_params, new_opt, grad_norm, stats = fused_optimizer_apply(
            fused, schedule, state.params, state.opt_state, grads
        )
        health_vals = health_metrics_from_stats(stats) if health else None
    else:
        new_params, new_opt, updates = optimizer_update(
            tx, grads, state.opt_state, state.params
        )
        grad_norm = optax.global_norm(grads)
        health_vals = (
            health_metrics(state.params, grads, updates) if health else None
        )
    new_state = TrainState(
        step=state.step + 1, params=new_params, opt_state=new_opt, ef=ef,
    )
    metrics = {
        "loss": loss,
        "learning_rate": schedule(state.step),
        "grad_norm": grad_norm,
        "target_tokens": tokens,
    }
    if health_vals is not None:
        metrics.update(health_vals)
    return new_state, metrics


def once_per_step_source_spans() -> list[tuple[str, int, int]]:
    """``(source_file, first_line, last_line)`` spans of the code that
    must execute exactly once per optimizer step — ``optimizer_apply_block``
    plus the health-numerics helpers it calls (their bodies are user code,
    so jax attributes their instructions to these lines, not to the apply
    block's call site), plus the fused-apply implementation layer
    (``train/optim.py`` orchestration and the ``ops/fused_optim.py``
    kernel dispatch — under ``--optim-impl fused`` the apply's
    instructions carry THOSE frames).  Computed from the live source so
    the spans track edits; consumed by
    ``ir_lint.once_per_step_placement``."""
    import inspect

    from distributed_llms_example_tpu.ops import fused_optim, quant_collectives
    from distributed_llms_example_tpu.train import optim as optim_mod

    spans = []
    fns = (
        optimizer_apply_block,
        health_metrics,
        _bucket_sumsq,
        health_metrics_from_stats,
        optim_mod.optimizer_update,
        optim_mod.fused_optimizer_apply,
        fused_optim.adamw_tree_apply,
        fused_optim.fused_adamw_leaf,
        fused_optim.adamw_leaf_reference,
        fused_optim._adamw_kernel,
        fused_optim._sharded_leaf,
        # the quantized gradient reduction (--grad-compression int8) runs
        # once per optimizer step, at the boundary AFTER the microbatch
        # scan — covering its frames lets the placement census prove it
        # never slid into the accumulation loop (the grad-compression-accum
        # composition contract)
        quant_collectives.quantized_tree_reduce,
        quant_collectives._reduce_one_leaf,
        quant_collectives.quantize_blocks,
        quant_collectives.dequantize_blocks,
        quant_collectives.stochastic_round,
    )
    for fn in fns:
        lines, first = inspect.getsourcelines(fn)
        spans.append((inspect.getsourcefile(fn), first, first + len(lines) - 1))
    return spans


def cross_entropy_sums(
    logits: jnp.ndarray, labels: jnp.ndarray, label_smoothing: float = 0.0
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(sum of token losses, number of unmasked tokens); fp32 accumulation."""
    mask = (labels != LABEL_PAD).astype(jnp.float32)
    targets = jnp.where(labels == LABEL_PAD, 0, labels)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    true_logit = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    loss = logz - true_logit
    if label_smoothing > 0.0:
        smooth = -jnp.mean(jax.nn.log_softmax(logits, axis=-1), axis=-1)
        loss = (1.0 - label_smoothing) * loss + label_smoothing * smooth
    return jnp.sum(loss * mask), jnp.sum(mask)


def make_loss_fn(
    model: Any, config: Any, label_smoothing: float = 0.0, is_seq2seq: bool = True
) -> Callable:
    """Loss over a batch dict (input_ids, attention_mask, labels).

    Seq2seq: teacher-forced decoder on shift-right labels.  Causal LM:
    ``labels`` is input-length-aligned with -100 over prompt/pad positions;
    position t's logits predict ``labels[t+1]`` (next-token convention).
    """

    # MoE models sow a load-balance loss into the "losses" collection; it
    # is token-weighted into the CE sum so the normalized loss comes out
    # as mean-CE + weight·aux (exact under scan-based grad accumulation).
    moe_weight = float(getattr(config, "moe_aux_weight", 0.0) or 0.0)

    # fused (vocab-chunked) CE: consume the pre-head hidden and apply the
    # LM head inside blockwise_cross_entropy_sums' scan, so (tokens, vocab)
    # fp32 logits never materialize.  Causal flax modules only (the
    # pipelined adapters own their loss paths).
    fused_ce = (
        not is_seq2seq
        and bool(getattr(config, "fused_ce", False))
        and hasattr(model, "hidden_states")
    )

    def apply_model(params: Any, *args, **kw):
        if moe_weight > 0.0:
            logits, mutated = model.apply({"params": params}, *args, mutable=["losses"], **kw)
            leaves = jax.tree.leaves(mutated.get("losses", {}))
            # mean over layers (each MoE layer sows one scalar): keeps the
            # configured coefficient comparable to HF Mixtral's single
            # all-layer loss instead of scaling with depth
            aux = sum(leaves, jnp.zeros((), jnp.float32)) / max(len(leaves), 1)
            return logits, aux
        return model.apply({"params": params}, *args, **kw), jnp.zeros((), jnp.float32)

    def loss_sums(params: Any, batch: dict, dropout_rng: jax.Array | None = None) -> tuple:
        labels = batch["labels"]
        rngs = {"dropout": dropout_rng} if dropout_rng is not None else {}
        if is_seq2seq:
            decoder_input_ids = shift_right(labels, config.decoder_start_token_id, config.pad_token_id)
            logits, aux = apply_model(
                params,
                batch["input_ids"],
                batch["attention_mask"],
                decoder_input_ids,
                deterministic=dropout_rng is None,
                rngs=rngs,
            )
            lsum, tokens = cross_entropy_sums(logits, labels, label_smoothing)
        elif fused_ce:
            h, aux = apply_model(
                params,
                batch["input_ids"],
                batch["attention_mask"],
                deterministic=dropout_rng is None,
                rngs=rngs,
                method="hidden_states",
            )
            from distributed_llms_example_tpu.ops.blockwise_ce import (
                blockwise_cross_entropy_sums,
            )

            h2 = h[:, :-1].reshape(-1, h.shape[-1])
            # cast the master-fp32 kernel to the compute dtype first — the
            # unfused lm_head does the same (nn.Dense dtype), and a raw
            # fp32×fp32 chunk matmul would forfeit MXU bf16 throughput
            w = params["lm_head"]["kernel"].astype(h.dtype)
            lsum, tokens = blockwise_cross_entropy_sums(
                h2, w, labels[:, 1:].reshape(-1), label_smoothing
            )
        else:
            logits, aux = apply_model(
                params,
                batch["input_ids"],
                batch["attention_mask"],
                deterministic=dropout_rng is None,
                rngs=rngs,
            )
            lsum, tokens = cross_entropy_sums(logits[:, :-1], labels[:, 1:], label_smoothing)
        return lsum + moe_weight * aux * tokens, tokens

    return loss_sums


def state_shardings(state: Any, mesh: Mesh, rules: ShardingRules | None = None) -> Any:
    """Shardings for a TrainState (or any pytree): param-rule regexes applied
    to every leaf path — optimizer moments mirror the param tree (their
    paths end with the param path, which the regex rules match), scalars
    fall through to replicated.

    The error-feedback tree (``--grad-compression int8``) is the one
    subtree the path rules CANNOT resolve: its leaves carry a leading
    worker dim, so a param rule's spec would land on the wrong ranks.  It
    gets the tiled layout instead — worker dim over the replica axes,
    inner dims exactly the param shardings
    (``ops/quant_collectives.py error_feedback_shardings``)."""
    ef = getattr(state, "ef", None)
    if ef is None or not hasattr(state, "replace"):
        return resolve_shardings(state, mesh, rules)
    # resolve WITHOUT the ef subtree (a param rule matching "ef/<path>"
    # at the tiled rank would log spurious ragged-dim fallbacks), then
    # attach the tiled layout
    from distributed_llms_example_tpu.ops.quant_collectives import (
        error_feedback_shardings,
    )

    sh = resolve_shardings(state.replace(ef=None), mesh, rules)
    return sh.replace(ef=error_feedback_shardings(sh.params, mesh))


def make_train_step(
    model: Any,
    config: Any,
    tx: optax.GradientTransformation,
    schedule: optax.Schedule,
    mesh: Mesh,
    *,
    rules: ShardingRules | None = None,
    grad_accum_steps: int = 1,
    label_smoothing: float = 0.0,
    with_dropout: bool = False,
    donate: bool = True,
    is_seq2seq: bool = True,
    sequence_sharded: bool | None = None,
    health: bool = False,
    optim_spec: Any = None,
    optim_impl: str | None = None,
    grad_compression: str = "off",
) -> Callable[[TrainState, dict], tuple[TrainState, dict]]:
    """Build the jitted train step: (state, batch[, rng]) → (state, metrics).

    ``grad_compression`` (``--grad-compression``): ``"off"`` (default —
    the code path is untouched, the compiled program bit-identical to the
    pre-compression step) or ``"int8"`` — the gradient tree's
    cross-replica reduction runs through ``ops/quant_collectives.py``:
    per-worker partial grads (``value_and_grad`` vmapped over shard-local
    batch groups along the ``data`` axis, the fsdp/tensor legs inside
    each group staying GSPMD's in fp32), block-int8 quantization with
    stochastic rounding off the step RNG, int-safe integer partial sums
    on an s8 wire, and the per-worker error-feedback tree carried in
    ``TrainState.ef`` (callers allocate it via
    ``create_train_state(..., grad_compression="int8", workers=W)``).
    Composes with in-step grad accumulation — the scan accumulates fp32
    TILED partial sums and the quantized reduction runs once at the
    optimizer-step boundary; stage>1 pipelines and sequence parallelism
    are composition-matrix errors.

    ``optim_spec`` (a ``train.optim.OptimizerSpec`` describing ``tx``)
    plus ``optim_impl`` (``--optim-impl``; None follows the process
    default, ``auto`` = fused on TPU) select the optimizer apply: the
    fused Pallas clip+AdamW kernel (in place on the param/accumulator
    shardings, health sourced from its partial sums) or the optax chain.
    Without a spec the step always runs the optax (``xla``) impl.
    Pipelined adapters always run xla (composition row
    ``fused-optim-pipelined`` guards the explicit flag).

    ``health=True`` additionally computes the in-graph numerics bundle
    (``HEALTH_METRIC_KEYS``: param norm, non-finite grad count, per-bucket
    update ratios) inside the compiled step — extra metrics entries, no
    extra device syncs; the obs health watchdog reads them at the logging
    cadence.

    The global batch (leading dim = global batch size) must be divisible by
    ``grad_accum_steps``; each microbatch stays sharded over (data, fsdp).
    ``sequence_sharded``: also split batch lengths over the ``sequence``
    axis (context parallelism).  None = on whenever the mesh has a
    sequence axis > 1; callers whose batch lengths may not divide that
    axis (Trainer checks its bucket widths) must pass False explicitly —
    a sharding over a non-divisible length is a dispatch-time error, not
    a graceful fallback.
    """
    if grad_accum_steps < 1:
        raise ValueError(f"grad_accum_steps must be >= 1, got {grad_accum_steps}")
    if grad_accum_steps > 1 and hasattr(model, "num_microbatches"):
        # stage>1 pipeline adapters own their microbatching; the table row
        # owns the message (analysis/composition.py — the Trainer checks
        # the same row at startup, this deep guard catches direct callers)
        from distributed_llms_example_tpu.analysis.composition import reason_for

        raise ValueError(reason_for("grad-accum-pipelined"))
    if grad_compression not in ("off", "int8"):
        raise ValueError(
            f"grad_compression must be 'off' or 'int8', got {grad_compression!r}"
        )
    compress = grad_compression == "int8"
    if compress and hasattr(model, "num_microbatches"):
        from distributed_llms_example_tpu.analysis.composition import reason_for

        raise ValueError(reason_for("grad-compression-pipelined"))
    loss_sums = make_loss_fn(model, config, label_smoothing, is_seq2seq=is_seq2seq)
    seq_sharded = (
        sequence_sharded
        if sequence_sharded is not None
        else mesh.shape.get("sequence", 1) > 1
    )
    if compress and seq_sharded:
        from distributed_llms_example_tpu.analysis.composition import reason_for

        raise ValueError(reason_for("grad-compression-sequence"))
    micro_sharding = NamedSharding(
        mesh, P(None, ("data", "fsdp", "expert"), "sequence" if seq_sharded else None)
    )

    if getattr(model, "pipeline_schedule", "gpipe") in ("1f1b", "interleaved"):
        # these pipelines own their backward pass (forward/backward
        # microbatches interleave inside one fused schedule — autodiff
        # cannot reorder its backward, so the adapter computes gradients
        # itself); same (loss_sum, tokens, grads) contract as the
        # jax.value_and_grad path below
        value_and_grad_sums = model.make_value_and_grad(
            label_smoothing, is_seq2seq=is_seq2seq
        )
    else:
        def value_and_grad_sums(params: Any, batch: dict, rng: jax.Array | None) -> tuple:
            def wrapped(p):
                lsum, tokens = loss_sums(p, batch, rng)
                return lsum, tokens

            (lsum, tokens), grads = jax.value_and_grad(wrapped, has_aux=True)(params)
            return lsum, tokens, grads

    workers = 1
    if compress:
        from distributed_llms_example_tpu.ops.quant_collectives import (
            GRAD_WORKER_AXES,
            worker_count,
        )

        base_value_and_grad_sums = value_and_grad_sums
        workers = worker_count(dict(mesh.shape))
        if workers <= 1:
            raise ValueError(
                f"grad_compression='int8' needs a replica axis > 1 (mesh "
                f"axes {GRAD_WORKER_AXES} on {dict(mesh.shape)} give 1 "
                "worker group): with no cross-replica leg there is "
                "nothing to compress — every step would pay quantization "
                "noise and a params-sized fp32 residual for zero wire "
                "savings"
            )
        # each worker group's batch rows keep their (fsdp, expert) spread;
        # the worker dim rides the replica axis.  The (B,) -> (W, B/W)
        # reshape is a zero-collective relabeling: the combined batch
        # sharding orders data-major, so every device's rows stay local.
        tiled_batch_sharding = NamedSharding(
            mesh, P("data", ("fsdp", "expert"), None)
        )

        def tiled_value_and_grad_sums(
            params: Any, batch: dict, rng: jax.Array | None
        ) -> tuple:
            """Per-worker partial gradients: (loss sum, token sum, grads
            tiled ``(W, *shape)``).  The model runs inside ``vmap`` with
            the ambient mesh CLEARED — its internal activation
            constraints name the combined batch axes at the un-tiled
            rank, which would fight the tiled layout; sharding is steered
            by the explicit input/output pins instead (the same
            discipline the pipeline adapters use for nested regions)."""

            def regroup(x):
                if x.shape[0] % workers:
                    raise ValueError(
                        f"microbatch {x.shape[0]} is not divisible by the "
                        f"{workers} grad-compression worker group(s) "
                        f"(mesh axes {GRAD_WORKER_AXES})"
                    )
                return x.reshape(workers, x.shape[0] // workers, *x.shape[1:])

            grouped = jax.tree.map(regroup, batch)
            grouped = jax.lax.with_sharding_constraint(
                grouped, jax.tree.map(lambda _: tiled_batch_sharding, batch)
            )
            if rng is not None:
                keys = jax.vmap(lambda i: jax.random.fold_in(rng, i))(
                    jnp.arange(workers)
                )

                def one(mb, k):
                    with activation_mesh(None):
                        return base_value_and_grad_sums(params, mb, k)

                ls, toks, gt = jax.vmap(one)(grouped, keys)
            else:

                def one(mb):
                    with activation_mesh(None):
                        return base_value_and_grad_sums(params, mb, None)

                ls, toks, gt = jax.vmap(one)(grouped)
            return jnp.sum(ls), jnp.sum(toks), gt

        value_and_grad_sums = tiled_value_and_grad_sums

    def make_step_fn(accum_sh: Any, fused_plan: Any = None, comp_specs: Any = None) -> Callable:
        """The step body, closed over the accumulator shardings (the
        mirror of the param shardings — ``accumulator_shardings``) so the
        scan carry is PINNED to the param layout: under FSDP each
        device's accumulator holds exactly its gradient shard, gradients
        reduce-scatter straight into it, and the fp32 tree never
        replicates.  ``accum_sh=None`` (abstract callers without resolved
        shardings) leaves the layout to GSPMD.  ``fused_plan`` routes the
        optimizer tail to the fused Pallas apply (None = optax chain)."""

        def step_fn(state: TrainState, batch: dict, rng: jax.Array | None = None) -> tuple[TrainState, dict]:
            if compress and state.ef is None:
                raise ValueError(
                    "grad_compression='int8' needs the error-feedback tree: "
                    "build the state with create_train_state(..., "
                    "grad_compression='int8', workers=N)"
                )
            if grad_accum_steps > 1:
                b = jax.tree.leaves(batch)[0].shape[0]
                if b % grad_accum_steps:
                    raise ValueError(
                        f"global batch {b} is not divisible by "
                        f"grad_accum_steps={grad_accum_steps}"
                    )
                # Shard-local microbatch grouping: row r joins microbatch
                # r mod N (reshape to (B/N, N, ...) then swap), NOT the
                # contiguous slab r // (B/N).  With the batch sharded
                # contiguously over devices on dim 0, each device's rows
                # land wholly inside its own shard of every microbatch —
                # the slab grouping would instead scatter each microbatch
                # across device boundaries and GSPMD would pay an
                # all-to-all per step.  Loss and gradient sums are
                # additive over rows, so any grouping yields the same
                # optimizer step.
                micro = jax.tree.map(
                    lambda x: jnp.swapaxes(
                        x.reshape(x.shape[0] // grad_accum_steps, grad_accum_steps, *x.shape[1:]),
                        0,
                        1,
                    ),
                    batch,
                )
                micro = jax.lax.with_sharding_constraint(
                    micro, jax.tree.map(lambda _: micro_sharding, batch)
                )

                def pin(g_acc: Any) -> Any:
                    if accum_sh is None:
                        return g_acc
                    return jax.lax.with_sharding_constraint(g_acc, accum_sh)

                def body(carry, mb):
                    lsum_acc, tok_acc, g_acc, i = carry
                    r = jax.random.fold_in(rng, i) if rng is not None else None
                    lsum, tokens, grads = value_and_grad_sums(state.params, mb, r)
                    g_acc = pin(
                        jax.tree.map(
                            lambda a, g: a + g.astype(jnp.float32), g_acc, grads
                        )
                    )
                    return (lsum_acc + lsum, tok_acc + tokens, g_acc, i + 1), None

                zero_g = pin(
                    jax.tree.map(
                        lambda p: jnp.zeros(
                            ((workers,) + p.shape) if compress else p.shape,
                            jnp.float32,
                        ),
                        state.params,
                    )
                )
                (lsum, tokens, grads, _), _ = jax.lax.scan(
                    body,
                    (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32), zero_g, 0),
                    micro,
                )
            else:
                lsum, tokens, grads = value_and_grad_sums(state.params, batch, rng)
            if compress:
                # the quantized cross-replica reduction, ONCE per optimizer
                # step (under accumulation the scan above summed fp32 TILED
                # partials — EF and the s8 wire apply at the step boundary);
                # stochastic rounding keys off the step RNG, folded with the
                # step counter so rng-less runs still draw fresh bits
                from distributed_llms_example_tpu.ops.quant_collectives import (
                    quantized_tree_reduce,
                )

                sr_base = rng if rng is not None else jax.random.PRNGKey(0x6e7)
                sr_key = jax.random.fold_in(
                    jax.random.fold_in(sr_base, 0x51ab), state.step
                )
                grads, new_ef = quantized_tree_reduce(
                    grads, state.ef, sr_key, mesh=mesh, param_specs=comp_specs,
                )
            else:
                new_ef = state.ef
            return optimizer_apply_block(
                state, tx, schedule, lsum, tokens, grads, health=health,
                fused=fused_plan, ef=new_ef,
            )

        return step_fn

    # shardings: state per rules; batch over (data, fsdp) with lengths over
    # sequence under context parallelism; rng replicated
    rules = rules or default_rules()
    bsh = batch_sharding(mesh, sequence_sharded=seq_sharded)
    repl = NamedSharding(mesh, P())

    metric_keys = ("loss", "learning_rate", "grad_norm", "target_tokens") + (
        HEALTH_METRIC_KEYS if health else ()
    )

    def jit_it(state_sh: Any, abstract_params: Any = None) -> Callable:
        from distributed_llms_example_tpu.train.optim import resolve_fused_plan

        metrics_sh = {k: repl for k in metric_keys}
        # the fp32 gradient accumulators mirror the param shardings leaf
        # for leaf — the weight-update-sharding contract the spec lint
        # checks and the compiled-carry test pins; the fused-plan
        # resolution (the --optim-impl dispatch) is the SHARED
        # train/optim.py resolver so the step and the budget probe can
        # never pick different impls
        comp_specs = None
        accum_pin_sh = None
        if grad_accum_steps > 1:
            accum_pin_sh = accumulator_shardings(state_sh.params)
        if compress:
            from distributed_llms_example_tpu.ops.quant_collectives import (
                error_feedback_shardings,
            )

            comp_specs = jax.tree.map(
                lambda sh: getattr(sh, "spec", None), state_sh.params
            )
            if grad_accum_steps > 1:
                # the scan carry holds TILED partial sums: worker dim over
                # the replica axes, inner dims still the param mirror
                accum_pin_sh = error_feedback_shardings(state_sh.params, mesh)
        step_fn = make_step_fn(
            accum_pin_sh,
            resolve_fused_plan(
                optim_spec, optim_impl, tx, state_sh, mesh,
                abstract_params=abstract_params,
                pipelined=hasattr(model, "num_microbatches"),
            ),
            comp_specs,
        )
        in_shardings = (state_sh, {"input_ids": bsh, "attention_mask": bsh, "labels": bsh})
        if with_dropout:
            jitted = jax.jit(
                step_fn,
                in_shardings=(*in_shardings, repl),
                out_shardings=(state_sh, metrics_sh),
                donate_argnums=(0,) if donate else (),
            )
        else:
            jitted = jax.jit(
                lambda s, b: step_fn(s, b, None),
                in_shardings=in_shardings,
                out_shardings=(state_sh, metrics_sh),
                donate_argnums=(0,) if donate else (),
            )

        # tracing must see the mesh so the models' activation constraints
        # (parallel/activation.py) bake into the compiled program
        def run(*args):
            with activation_mesh(mesh):
                return jitted(*args)

        run.jitted = jitted  # AOT access (utils/memory_audit.py)
        run.mesh = mesh
        return run

    def build(state: TrainState) -> tuple[Callable, Any]:
        sh = state_shardings(state, mesh, rules)
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state.params
        )
        return jit_it(sh, abstract), sh

    return build


def make_optimizer_probe(
    tx: optax.GradientTransformation,
    schedule: optax.Schedule,
    state_sh: Any,
    mesh: Mesh,
    *,
    optim_spec: Any = None,
    optim_impl: str | None = None,
    health: bool = False,
    abstract_params: Any = None,
) -> Callable[[TrainState], Any]:
    """A jitted stand-alone run of ``optimizer_apply_block`` for the
    budget layer's cadenced optimizer-apply timing (obs/budget.py
    ``probe_optimizer``): the SAME impl dispatch as the train step
    (``train.optim.resolve_fused_plan`` — one resolver, so the probe can
    never stamp a fused sample for a step that actually ran xla; pass
    ``abstract_params`` so an unparseable chain falls back with the same
    logged ``fused_optim_fallback`` instead of raising at the first
    cadence), fed a zeros gradient tree built in-program, with the
    outputs reduced to one replicated scalar so XLA must execute the
    full elementwise update (returning the new state would allocate a
    second full state per probe).  The output writes fuse into the
    reductions, so the sample reads as the apply's arithmetic + operand
    traffic — a slightly write-light but componentwise-faithful wall
    sample.  The caller times it at the LOG CADENCE only; nothing here
    runs on non-cadence steps."""
    from distributed_llms_example_tpu.train.optim import resolve_fused_plan

    plan = resolve_fused_plan(
        optim_spec, optim_impl, tx, state_sh, mesh,
        abstract_params=abstract_params,
    )

    def probe(state: TrainState):
        grads = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), state.params
        )
        new_state, _metrics = optimizer_apply_block(
            state, tx, schedule, jnp.zeros((), jnp.float32),
            jnp.ones((), jnp.float32), grads, health=health, fused=plan,
            ef=state.ef,
        )
        total = jnp.zeros((), jnp.float32)
        # the EF tree only passes THROUGH the apply — folding its W x
        # params fp32 leaves into the reduction would bill the probe for
        # reads the real apply never does, inflating optimizer_apply_ms
        # on compressed runs
        for leaf in jax.tree.leaves(new_state.replace(ef=None)):
            total = total + jnp.sum(leaf).astype(jnp.float32)
        return total

    jitted = jax.jit(
        probe,
        in_shardings=(state_sh,),
        out_shardings=NamedSharding(mesh, P()),
    )

    def run(state: TrainState):
        with activation_mesh(mesh):
            return jitted(state)

    return run


def put_batch(batch: dict, mesh: Mesh, *, sequence_sharded: bool = False) -> dict:
    """Host-local numpy batch → global sharded arrays.

    Single-process: a plain device_put onto the (data, fsdp) sharding.
    Multi-host: ``make_array_from_process_local_data`` assembles the global
    array from each host's slice (the analog of DDP's per-rank loaders).
    ``sequence_sharded``: also split lengths over the ``sequence`` axis
    (train batches under context parallelism; generation keeps lengths
    whole because decode steps are length-1).
    """
    sh = batch_sharding(mesh, sequence_sharded=sequence_sharded)
    if jax.process_count() == 1:  # pod-agreed: process_count() is pod-uniform; single-host fast path
        return {k: jax.device_put(v, sh) for k, v in batch.items()}
    return {k: jax.make_array_from_process_local_data(sh, v) for k, v in batch.items()}
