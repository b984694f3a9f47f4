"""Compile-only per-device memory audit for the large BASELINE configs.

BASELINE.md configs 4-5 (flan-t5-xl FSDP, llama-2-7b bf16 + grad
checkpointing) must fit a v5e chip's 16 GB HBM.  Rather than hoping, this
audits the ACTUAL compiled train step: the full sharded program is lowered
and compiled ahead-of-time from abstract (ShapeDtypeStruct) arguments — no
parameters are ever materialized — and XLA's ``memory_analysis()`` reports
per-device argument/output/temp sizes, from which the peak is

    peak ≈ arguments + temps + (outputs - aliased)

(donated state aliases its output buffers, so steady-state outputs are
nearly free).  Run as a module for the audit JSON line:

    python -m distributed_llms_example_tpu.utils.memory_audit \
        --model llama-2-7b --mesh fsdp=8 --batch 8 --remat

Two views are reported:

- ``compiled_*``: XLA's own buffer accounting for the current backend.
  Authoritative when that backend is TPU; on the CPU test mesh XLA's
  buffer assignment is far more conservative (measured: remat does not
  reduce CPU temp bytes at all), so the compiled figures OVERSTATE TPU
  usage there and are reported for reference only.
- ``analytic_*``: exact sharding-aware byte counts for state/grads (from
  ``NamedSharding.shard_shape``, no estimation) plus a structural model of
  the remat activation footprint (per-block boundary saves + one block's
  recompute working set + fp32 logits/loss buffers).  Backend-independent;
  this is what the fit assertion uses off-TPU.
"""

from __future__ import annotations

import argparse
from typing import Any

HBM_BYTES_V5E = 16 * 1024**3


def _shard_bytes(tree: Any, shardings: Any) -> int:
    """Exact per-device bytes of a sharded pytree (max shard per leaf)."""
    import jax
    import numpy as np

    total = 0
    for leaf, sh in zip(jax.tree.leaves(tree), jax.tree.leaves(shardings)):
        shard_shape = sh.shard_shape(leaf.shape)
        total += int(np.prod(shard_shape)) * leaf.dtype.itemsize
    return total


def compiled_byte_view(ma: Any) -> dict:
    """XLA's ``memory_analysis()`` as per-device byte counts, with the ONE
    peak formula (donation credited: the state argument aliases its output
    buffers, so steady-state outputs cost only the non-aliased slack)

        peak = arguments + temps + max(0, outputs - aliased)

    Both the audit's ``compiled_*`` view and ``obs/memprof.py``'s bucketed
    account read XLA through this function — single owner, no forked
    arithmetic."""
    args_b = int(ma.argument_size_in_bytes)
    out_b = int(ma.output_size_in_bytes)
    alias_b = int(ma.alias_size_in_bytes)
    temp_b = int(ma.temp_size_in_bytes)
    return {
        "arguments_bytes": args_b,
        "output_bytes": out_b,
        "aliased_bytes": alias_b,
        "temp_bytes": temp_b,
        "peak_bytes": args_b + temp_b + max(0, out_b - alias_b),
    }


# TrainState field → shared memory-bucket scheme (obs/memprof.py BUCKETS).
# ``ef`` is the per-worker fp32 error-feedback carry from --grad-compression,
# i.e. gradient-accumulation state that persists across steps.
_STATE_FIELD_BUCKETS = {
    "params": "params",
    "opt_state": "optimizer_state",
    "ef": "grad_accum",
}


def state_bucket_bytes(a_state: Any, sh: Any) -> dict[str, int]:
    """Per-device shard bytes of the train state, split by top-level
    TrainState field into the shared bucket scheme.  Per-leaf additive,
    so ``sum(values)`` EQUALS ``_shard_bytes(a_state, sh)`` — the audit's
    ``analytic_state_bytes`` and memprof's params/optimizer buckets are
    the same numbers from this one function."""
    import dataclasses

    buckets: dict[str, int] = {}
    if dataclasses.is_dataclass(a_state):
        for f in dataclasses.fields(a_state):
            bucket = _STATE_FIELD_BUCKETS.get(f.name, "other")
            buckets[bucket] = buckets.get(bucket, 0) + _shard_bytes(
                getattr(a_state, f.name), getattr(sh, f.name)
            )
    else:
        buckets["other"] = _shard_bytes(a_state, sh)
    return buckets


def _activation_bytes(
    config: Any, b_loc: int, src: int, tgt: int, dtype_bytes: int, remat: bool,
) -> dict:
    """Structural activation model, per device.

    Under block-level remat the backward holds: every block's boundary
    activation (batch, seq, d_model), ONE block's recomputed internals
    (attention scores in fp32 — assume the XLA path, which is conservative
    vs the flash kernel — plus MLP inner), and the fp32 logits/loss
    buffers.  Without remat EVERY block's internals are saved residuals, so
    the working-set term multiplies by the layer count.  Batch is sharded
    over (data, fsdp) so ``b_loc`` is the per-device batch."""
    name = type(config).__name__
    if name == "LlamaConfig":
        h, inter, layers = config.hidden_size, config.intermediate_size, config.num_hidden_layers
        heads, vocab = config.num_attention_heads, config.vocab_size
        boundaries = layers * b_loc * src * h * dtype_bytes
        scores = b_loc * heads * src * src * 4
        mlp_inner = 3 * b_loc * src * inter * dtype_bytes  # gate, up, silu*up
        if remat:
            block_ws = 2 * max(scores, mlp_inner)  # recomputed fwd + its bwd temps
        else:
            block_ws = layers * (scores + mlp_inner)  # all residuals saved
        logits = 2 * b_loc * src * vocab * 4  # fp32 logits + softmax-grad temp
    else:  # T5/BART seq2seq: encoder + decoder with cross attention
        h = getattr(config, "d_model", None)
        layers_e = getattr(config, "num_layers", None) or config.encoder_layers
        layers_d = getattr(config, "decoder_layers", layers_e)
        inter = getattr(config, "d_ff", None) or config.encoder_ffn_dim
        heads = getattr(config, "num_heads", None) or config.encoder_attention_heads
        vocab = config.vocab_size
        boundaries = (layers_e * b_loc * src * h + layers_d * b_loc * tgt * h) * dtype_bytes
        boundaries += b_loc * src * h * dtype_bytes  # encoder output, live all decode
        scores = max(
            b_loc * heads * src * src * 4,  # encoder self
            b_loc * heads * tgt * src * 4,  # cross
        )
        mlp_inner = 2 * b_loc * max(src, tgt) * inter * dtype_bytes
        if remat:
            block_ws = 2 * max(scores, mlp_inner)
        else:
            block_ws = (layers_e + layers_d) * (scores + mlp_inner)
        logits = 2 * b_loc * tgt * vocab * 4
    return {
        "boundaries_bytes": int(boundaries),
        "block_working_set_bytes": int(block_ws),
        "logits_bytes": int(logits),
    }


def abstract_train_setup(
    model_name: str,
    mesh: Any,
    *,
    dtype: str = "bfloat16",
    remat: bool = True,
    remat_policy: str = "full",
    grad_compression: str = "",
):
    """Model + train state as pure ShapeDtypeStructs with shardings — no
    weights, no devices touched.  Returns ``(lm, tx, schedule, a_params,
    a_state, sh)``.  Shared by the memory audit and the analysis/ IR lint
    so the two always reason about the SAME abstract program."""
    import jax

    from distributed_llms_example_tpu.core.precision import parse_dtype
    from distributed_llms_example_tpu.models.registry import load_model
    from distributed_llms_example_tpu.train.optim import make_optimizer
    from distributed_llms_example_tpu.train.step import (
        create_train_state,
        state_shardings,
    )

    lm = load_model(
        model_name, dtype=parse_dtype(dtype), remat=remat, load_weights=False,
        remat_policy=remat_policy,
    )
    tx, schedule = make_optimizer(total_steps=1000)
    a_params = jax.eval_shape(lambda: lm.init_params(0))
    workers = 1
    if grad_compression and grad_compression != "off":
        from distributed_llms_example_tpu.ops.quant_collectives import (
            worker_count,
        )

        workers = worker_count(dict(mesh.shape))
    a_state = jax.eval_shape(
        lambda p: create_train_state(
            p, tx,
            grad_compression=grad_compression or "off", workers=workers,
        ),
        a_params,
    )
    sh = state_shardings(a_state, mesh)
    a_state = jax.tree.map(
        lambda s, shd: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=shd),
        a_state, sh,
    )
    return lm, tx, schedule, a_params, a_state, sh


def aot_compile_train_step(
    model_name: str,
    mesh: Any,
    *,
    global_batch: int = 8,
    src_len: int = 1024,
    tgt_len: int = 128,
    dtype: str = "bfloat16",
    remat: bool = True,
    remat_policy: str = "full",
    grad_accum_steps: int = 1,
    optim_impl: str = "",
    grad_compression: str = "",
):
    """AOT-lower and compile the sharded train step from abstract args
    (no parameter is ever materialized).  Returns ``(compiled, lm,
    a_params, a_state, sh)`` — the compiled object serves both XLA's
    ``memory_analysis()`` (the audit) and ``as_text()`` (the IR lint)."""
    import jax
    import jax.numpy as jnp

    from distributed_llms_example_tpu.parallel.activation import activation_mesh
    from distributed_llms_example_tpu.parallel.sharding import batch_sharding
    from distributed_llms_example_tpu.train.step import make_train_step

    lm, tx, schedule, a_params, a_state, sh = abstract_train_setup(
        model_name, mesh, dtype=dtype, remat=remat, remat_policy=remat_policy,
        grad_compression=grad_compression,
    )
    bsh = batch_sharding(mesh)
    shapes = {
        "input_ids": (global_batch, src_len),
        "attention_mask": (global_batch, src_len),
        "labels": (global_batch, tgt_len if lm.is_seq2seq else src_len),
    }
    a_batch = {
        k: jax.ShapeDtypeStruct(v, jnp.int32, sharding=bsh) for k, v in shapes.items()
    }
    optim_spec = None
    if optim_impl:
        # rebuild the SAME chain with its spec so the compiled program
        # runs the requested --optim-impl apply (the IR lint proves the
        # fused in-place/once-per-step contracts on this program)
        from distributed_llms_example_tpu.train.optim import make_optimizer_bundle

        tx, schedule, optim_spec = make_optimizer_bundle(total_steps=1000)
    build = make_train_step(
        lm.module,
        lm.config,
        tx,
        schedule,
        mesh,
        grad_accum_steps=grad_accum_steps,
        is_seq2seq=lm.is_seq2seq,
        optim_spec=optim_spec,
        optim_impl=optim_impl or None,
        grad_compression=grad_compression or "off",
    )
    step_fn, _ = build(a_state)
    with activation_mesh(mesh):
        compiled = step_fn.jitted.lower(a_state, a_batch).compile()
    return compiled, lm, a_params, a_state, sh


def audit_train_step_memory(
    model_name: str,
    *,
    mesh_config: Any = None,
    global_batch: int = 8,
    src_len: int = 1024,
    tgt_len: int = 128,
    dtype: str = "bfloat16",
    remat: bool = True,
    remat_policy: str = "full",
    grad_accum_steps: int = 1,
    compile: bool = True,
) -> dict:
    """Compile the sharded train step AOT and return per-device byte counts.

    Returns a dict with ``arguments_bytes``, ``temp_bytes``,
    ``output_bytes``, ``aliased_bytes``, ``peak_bytes`` (all per device),
    plus ``params`` and ``fits_v5e_hbm``.
    """
    import jax
    import jax.numpy as jnp

    from distributed_llms_example_tpu.core.config import MeshConfig
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.core.precision import parse_dtype
    from distributed_llms_example_tpu.train.step import state_shardings

    cfg = mesh_config or MeshConfig(data=1, fsdp=-1, sequence=1, tensor=1)
    if compile:
        mesh = build_mesh(cfg)
    else:
        # analytic-only audits never place data on devices, so the mesh can
        # be abstract — this also allows auditing shapes LARGER than the
        # attached device count (e.g. a 16-way multi-host mesh from one dev
        # box with 8 virtual devices)
        sizes = dict(cfg.axis_sizes())
        if -1 in sizes.values():
            known = 1
            for v in sizes.values():
                if v != -1:
                    known *= v
            # floor at 1: with an abstract mesh the wildcard may not be
            # satisfiable from local devices (e.g. --mesh fsdp=16 on 8)
            sizes = {
                k: (max(1, jax.device_count() // known) if v == -1 else v)
                for k, v in sizes.items()
            }
        try:
            mesh = jax.sharding.AbstractMesh(tuple(sizes.values()), tuple(sizes.keys()))
        except TypeError:  # pre-0.5 signature: one ((name, size), ...) tuple
            mesh = jax.sharding.AbstractMesh(tuple(sizes.items()))
    ma = None
    if compile:
        compiled, lm, a_params, a_state, sh = aot_compile_train_step(
            model_name, mesh,
            global_batch=global_batch, src_len=src_len, tgt_len=tgt_len,
            dtype=dtype, remat=remat, remat_policy=remat_policy,
            grad_accum_steps=grad_accum_steps,
        )
        ma = compiled.memory_analysis()
    else:
        lm, _, _, a_params, a_state, sh = abstract_train_setup(
            model_name, mesh, dtype=dtype, remat=remat, remat_policy=remat_policy,
        )

    # ---- analytic per-device accounting (backend-independent) ----
    state_buckets = state_bucket_bytes(a_state, sh)
    state_b = sum(state_buckets.values())
    # gradients: fp32, sharded like the params (one full tree live at the
    # optimizer update, alongside a comparable fused-update temporary)
    params_sh = state_shardings(a_params, mesh)
    grads_b = _shard_bytes(
        jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), a_params), params_sh,
    )
    micro_batch = global_batch // max(1, grad_accum_steps)
    batch_shards = 1
    for ax in ("data", "fsdp", "expert"):
        batch_shards *= mesh.shape.get(ax, 1)
    b_loc = max(1, micro_batch // batch_shards)
    dtype_bytes = jnp.dtype(parse_dtype(dtype)).itemsize
    act = _activation_bytes(
        lm.config, b_loc, src_len, tgt_len if lm.is_seq2seq else src_len, dtype_bytes, remat,
    )
    # Gradient liveness bounds the verdict from both sides:
    # - optimistic (1.25x): XLA fuses each layer's gradient into the scan
    #   accumulator / update as it is produced, so only one full tree plus
    #   fused-op slack is ever live (donation reuses grad buffers for the
    #   updates tree at the optimizer step);
    # - conservative (2x under grad accumulation): the scan carry g_acc and
    #   a fully materialized fresh microbatch tree coexist at the
    #   tree-map add (train/step.py scan body) if XLA does not fuse.
    grad_factor_conservative = 2.0 if grad_accum_steps > 1 else 1.25
    analytic_peak = state_b + int(1.25 * grads_b) + sum(act.values())
    analytic_peak_conservative = (
        state_b + int(grad_factor_conservative * grads_b) + sum(act.values())
    )

    backend = jax.default_backend()
    if ma is not None:
        view = compiled_byte_view(ma)
        args_b = view["arguments_bytes"]
        out_b = view["output_bytes"]
        alias_b = view["aliased_bytes"]
        temp_b = view["temp_bytes"]
        compiled_peak = view["peak_bytes"]
    else:
        args_b = out_b = alias_b = temp_b = compiled_peak = 0
    # the fit verdict: compiled stats when compiled for TPU, analytic model
    # otherwise (CPU buffer assignment ignores remat — measured)
    peak = compiled_peak if (backend == "tpu" and ma is not None) else analytic_peak
    n_params = int(sum(x.size for x in jax.tree.leaves(a_params)))
    return {
        "model": model_name,
        "mesh": dict(mesh.shape),
        "global_batch": global_batch,
        "src_len": src_len,
        "tgt_len": tgt_len,
        "dtype": dtype,
        "remat": remat,
        "remat_policy": remat_policy,
        # the analytic activation model assumes policy="full" (block-boundary
        # saves only); "dots" additionally saves matmul outputs, so analytic
        # figures UNDER-estimate it — trust the compiled stats for dots
        "analytic_assumes_full_remat": remat_policy != "full",
        "params": n_params,
        "backend": backend,
        "analytic_state_bytes": state_b,
        "analytic_state_bucket_bytes": state_buckets,
        "analytic_grad_bytes": grads_b,
        "analytic_activation_bytes": act,
        "analytic_peak_bytes": analytic_peak,
        "analytic_peak_conservative_bytes": analytic_peak_conservative,
        "compiled_arguments_bytes": args_b,
        "compiled_temp_bytes": temp_b,
        "compiled_output_bytes": out_b,
        "compiled_aliased_bytes": alias_b,
        "compiled_peak_bytes": compiled_peak,
        "peak_bytes": peak,
        "peak_gib": round(peak / 1024**3, 3),
        "hbm_bytes": HBM_BYTES_V5E,
        "fits_v5e_hbm": peak < HBM_BYTES_V5E,
        # safety verdict: true only if even the conservative bound fits
        # (compiled TPU stats override the analytic bounds when available)
        "fits_v5e_hbm_conservative": (
            compiled_peak < HBM_BYTES_V5E
            if (backend == "tpu" and ma is not None)
            else analytic_peak_conservative < HBM_BYTES_V5E
        ),
    }


def main(argv: list[str] | None = None) -> int:
    from distributed_llms_example_tpu.core.config import parse_mesh_arg

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", required=True)
    p.add_argument("--mesh", type=str, default="fsdp=-1")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--src-len", type=int, default=1024)
    p.add_argument("--tgt-len", type=int, default=128)
    p.add_argument("--dtype", type=str, default="bfloat16")
    p.add_argument("--remat", action="store_true")
    p.add_argument("--remat-policy", type=str, default="full")
    p.add_argument("--grad-accum-steps", type=int, default=1)
    p.add_argument(
        "--analytic",
        action="store_true",
        help="skip the AOT compile: seconds instead of minutes, and allows "
        "meshes larger than the attached device count",
    )
    p.add_argument(
        "--strict",
        action="store_true",
        help="CI mode: also exit nonzero unless the CONSERVATIVE "
        "gradient-liveness bound fits the chip HBM budget (the default "
        "verdict uses the optimistic fused-accumulation bound)",
    )
    args = p.parse_args(argv)
    report = audit_train_step_memory(
        args.model,
        mesh_config=parse_mesh_arg(args.mesh),
        global_batch=args.batch,
        src_len=args.src_len,
        tgt_len=args.tgt_len,
        dtype=args.dtype,
        remat=args.remat,
        remat_policy=args.remat_policy,
        grad_accum_steps=args.grad_accum_steps,
        compile=not args.analytic,
    )
    # the audit JSON line rides the metric sink (scripts/repo_lint.py
    # forbids direct print(json.dumps(...)) emission outside obs/)
    from distributed_llms_example_tpu.utils.jsonlog import log_json

    log_json(report)
    fits = report["fits_v5e_hbm"] and (
        not args.strict or report["fits_v5e_hbm_conservative"]
    )
    return 0 if fits else 1


if __name__ == "__main__":
    raise SystemExit(main())
