"""The unified Trainer — one SPMD core, three launch modes.

The reference maintains three near-duplicate ~120-line ModelTrainer classes
(reference train-torchrun.py:24, train-accelerator.py:29, train-task.py:72)
because each distribution mechanism (torchrun-DDP / Accelerate / raw
torch.distributed) imposes its own ceremony.  Under SPMD they are the same
program at different mesh shapes, so this Trainer covers all three:

- single process, many chips  (≈ torchrun / accelerate single host)
- multi-host                  (≈ train-task; ``initialize_distributed``
                                consumes the same Valohai triple).
                                ``output_dir`` must be one SHARED
                                filesystem path (GCS / NFS / Valohai
                                outputs): checkpoints are written
                                collaboratively — every process commits
                                its own shards and orbax's finalize
                                barrier waits for all of them
- single chip / CPU           (local dev)

Capabilities the reference has that live here: epoch training loop with
JSON-line loss logging (train-accelerator.py:217-232), periodic +
end-of-epoch ROUGE eval (train-accelerator.py:237-268 — plus the
``--evaluation-steps`` cadence the reference only honors in variant A),
final save with Valohai sidecars (helpers.py).  Capabilities it lacks that
live here too: periodic checkpointing with resume, bf16 policy, gradient
accumulation everywhere, deterministic multi-host data sharding.
"""

from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Any, Sequence

import jax
import numpy as np

from distributed_llms_example_tpu.core.config import TrainConfig
from distributed_llms_example_tpu.core.mesh import build_mesh, device_report
from distributed_llms_example_tpu.core.precision import parse_dtype
from distributed_llms_example_tpu.data.batching import LABEL_PAD, BatchIterator
from distributed_llms_example_tpu.data.dataset import (
    CausalLMDataset,
    SummarizationDataset,
    host_batch_slices,
)
from distributed_llms_example_tpu.data.prefetch import Prefetcher
from distributed_llms_example_tpu.data.tokenizer import get_tokenizer
from distributed_llms_example_tpu.evaluation.evaluate import Evaluator
from distributed_llms_example_tpu.io.checkpoint import (
    Checkpointer,
    ReshardError,
    abstract_like,
    describe_factorization,
    mesh_layout_array,
    parse_mesh_layout,
)
from distributed_llms_example_tpu.io.valohai_meta import save_valohai_metadata
from distributed_llms_example_tpu.models.registry import load_model
from distributed_llms_example_tpu.obs import setup
from distributed_llms_example_tpu.parallel.sharding import shard_params
from distributed_llms_example_tpu.train.optim import make_optimizer_bundle
from distributed_llms_example_tpu.train.step import (
    create_train_state,
    make_train_step,
    put_batch,
    state_shardings,
)
from distributed_llms_example_tpu.utils.backoff import sleep_backoff
from distributed_llms_example_tpu.utils.jsonlog import MetricLogger, log_json


_NOT_FIRST = contextlib.nullcontext()  # what wraps every call of the step program but the first


class Trainer:
    @setup.phase("trainer_init", awaits="train")
    def __init__(
        self,
        cfg: TrainConfig,
        *,
        train_records: Sequence[dict],
        val_records: Sequence[dict] | None = None,
        mesh: Any | None = None,
    ):
        self.cfg = cfg
        # sink first: every log_json below (device_report included) must
        # already flow through the --obs channel
        from distributed_llms_example_tpu.obs.sink import build_sink, install_sink

        install_sink(build_sink(getattr(cfg, "obs", "stdout"), cfg.output_dir))
        self.mesh = mesh if mesh is not None else build_mesh(cfg.mesh)
        log_json({"event": "device_report", **device_report()})

        compute_dtype = parse_dtype(cfg.compute_dtype)
        self.loaded = load_model(
            cfg.model_ckpt, dtype=compute_dtype, remat=cfg.remat, remat_policy=cfg.remat_policy,
            moe_capacity_factor=cfg.moe_capacity_factor,
            attention_impl=cfg.attention_impl or None,
            fused_ce=cfg.fused_ce or None,
        )
        self.model, self.config = self.loaded.module, self.loaded.config

        with setup.span("data_open"):  # dataset, tokenizer, the batch plan
            self.tokenizer = get_tokenizer(cfg.tokenizer, cfg.model_ckpt)
            if self.loaded.is_seq2seq:
                mk_ds = lambda recs: SummarizationDataset(  # noqa: E731
                    recs,
                    self.tokenizer,
                    max_source_length=cfg.max_source_length,
                    max_target_length=cfg.max_target_length,
                    source_column=cfg.source_column,
                    target_column=cfg.target_column,
                )
            else:
                # decoder-only: prompt+target concatenated, loss masked on prompt
                mk_ds = lambda recs: CausalLMDataset(  # noqa: E731
                    recs,
                    self.tokenizer,
                    max_length=cfg.max_source_length,
                    max_target_length=cfg.max_target_length,
                    source_column=cfg.source_column,
                    target_column=cfg.target_column,
                )
            self.train_ds = mk_ds(train_records)
            self.val_ds = mk_ds(val_records) if val_records else None

            # For causal LM, input and labels share one width: cap both at
            # max_source_length so the bucket widths agree.
            tgt_cap = cfg.max_target_length if self.loaded.is_seq2seq else cfg.max_source_length
            self._tgt_cap = tgt_cap  # the topology-change rebuild re-derives the plan
            self.batches = BatchIterator(
                self.train_ds,
                global_batch=cfg.batch_size,
                process_count=jax.process_count(),
                process_index=jax.process_index(),
                seed=cfg.shuffle_seed,
                bucket_multiple=cfg.pad_to_multiple,
                max_source_length=cfg.max_source_length,
                max_target_length=tgt_cap,
            )
            steps_per_epoch = self.batches.steps_per_epoch()
            if steps_per_epoch == 0:
                raise ValueError(
                    f"dataset of {len(self.train_ds)} examples is smaller than one "
                    f"global batch ({cfg.batch_size})"
                )
            self.total_steps = steps_per_epoch * cfg.num_epochs

        self.tx, self.schedule, self.optim_spec = make_optimizer_bundle(
            learning_rate=cfg.learning_rate,
            weight_decay=cfg.weight_decay,
            warmup_steps=cfg.warmup_steps,
            total_steps=self.total_steps,
            max_grad_norm=cfg.max_grad_norm,
        )

        params = self.loaded.params
        if params is None:
            with setup.span("model_init"):  # flax's init: its trace, its compile or load, its run
                params = self.loaded.init_params(cfg.shuffle_seed)
            with setup.span("params_to_host"):
                params = jax.device_get(params)

        # Pipeline parallelism: stage>1 swaps in the family's GPipe adapter
        # — blocks stacked (leading layer dim sharded over ``stage``),
        # training + teacher-forced scoring only.
        self.pipelined = self.mesh.shape.get("stage", 1) > 1
        self._rules = None  # None → default FSDP/TP rules everywhere below
        if self.pipelined:
            from distributed_llms_example_tpu.parallel.pipeline import stack_for_family
            from distributed_llms_example_tpu.parallel.sharding import pipeline_rules

            adapter_kw = dict(
                dtype=compute_dtype,
                num_microbatches=cfg.pipeline_microbatches,
                remat=cfg.remat,
            )
            if cfg.pipeline_schedule in ("1f1b", "interleaved"):
                # the adapters re-validate at construction; checking the
                # composition table here too fails before the stacking work
                from distributed_llms_example_tpu.analysis.composition import (
                    validate_composition,
                )

                validate_composition(
                    family=self.loaded.family,
                    schedule=cfg.pipeline_schedule,
                    mesh_axes=dict(self.mesh.shape),
                    flags=("pipelined",),
                )
                adapter_kw["schedule"] = cfg.pipeline_schedule
                if cfg.pipeline_schedule == "interleaved":
                    adapter_kw["virtual_stages"] = cfg.pipeline_virtual_stages
            if self.loaded.family == "llama":
                from distributed_llms_example_tpu.models.llama import PipelinedLlama as Adapter
            elif self.loaded.family == "bart":
                from distributed_llms_example_tpu.models.bart import PipelinedBart as Adapter
            elif self.loaded.family == "t5":
                from distributed_llms_example_tpu.models.t5 import PipelinedT5 as Adapter
            else:
                raise ValueError(
                    f"pipeline parallelism (stage>1) does not support family "
                    f"{self.loaded.family!r}"
                )
            params = stack_for_family(self.loaded.family, params)
            if cfg.pipeline_schedule == "interleaved" and cfg.pipeline_virtual_stages > 1:
                # interleaved storage order: device s's stage shard holds
                # its v non-contiguous chunks contiguously (host-side
                # permutation, before sharding; checkpoints store this
                # layout — resume with the same schedule flags.  v == 1 is
                # the identity: standard layout, no permutation)
                from distributed_llms_example_tpu.parallel.interleave import (
                    interleave_tree,
                )

                params["stacked_blocks"] = interleave_tree(
                    params["stacked_blocks"],
                    self.mesh.shape["stage"],
                    cfg.pipeline_virtual_stages,
                )
            self.model = Adapter(self.config, self.mesh, **adapter_kw)
            self._rules = pipeline_rules()
            log_json({
                "event": "pipeline_enabled",
                "family": self.loaded.family,
                "stages": self.mesh.shape["stage"],
                "num_microbatches": self.model.num_microbatches,
                "schedule": getattr(self.model, "pipeline_schedule", "gpipe"),
            })

        with setup.span("shard_params"):
            params = shard_params(params, self.mesh, self._rules)
        # gradient-collective compression (--grad-compression int8,
        # ops/quant_collectives.py): per-worker partial grads tiled over
        # the replica axes, s8 wire, error-feedback tree in TrainState —
        # validate the batch regrouping divisibility against the actual
        # mesh before any compile, like the grad-accum check below
        self._grad_workers = 1
        if cfg.grad_compression == "int8":
            from distributed_llms_example_tpu.ops.quant_collectives import (
                GRAD_WORKER_AXES,
                worker_count,
            )

            self._grad_workers = worker_count(dict(self.mesh.shape))
            if self._grad_workers <= 1:
                raise ValueError(
                    f"--grad-compression int8 needs a replica axis > 1 "
                    f"(mesh axes {GRAD_WORKER_AXES} on "
                    f"{dict(self.mesh.shape)} give 1 worker group): with "
                    "no cross-replica leg there is nothing to compress — "
                    "every step would pay quantization noise and a "
                    "params-sized fp32 residual for zero wire savings"
                )
            # the stochastic-rounding bits are drawn over the worker-tiled
            # gradient shapes; without partitionable threefry the lowering
            # computes them through cross-device u32 collectives as large
            # as the gradient traffic the compression removes (measured)
            jax.config.update("jax_threefry_partitionable", True)
            denom = cfg.grad_accum_steps * self._grad_workers
            if cfg.batch_size % denom:
                raise ValueError(
                    f"--grad-compression int8 cuts each microbatch into "
                    f"{self._grad_workers} worker group(s) (mesh axes "
                    f"{GRAD_WORKER_AXES}): --batch-size {cfg.batch_size} "
                    f"must be divisible by grad-accum-steps x workers = "
                    f"{denom}"
                )
            log_json({
                "event": "grad_compression",
                "mode": cfg.grad_compression,
                "workers": self._grad_workers,
                "worker_axes": list(GRAD_WORKER_AXES),
            })
        with setup.span("optimizer_init"):  # the moments beside the parameters, as laid out
            self.state = create_train_state(params, self.tx)
            self.state_sh = state_shardings(self.state, self.mesh, self._rules)
        if cfg.grad_compression == "int8":
            # EF allocated DIRECTLY into the tiled layout (sharded at
            # birth): a default-device zeros tree before the device_put
            # would sit W x params x 4B whole on chip 0 at 7B scale
            from distributed_llms_example_tpu.ops.quant_collectives import (
                attach_error_feedback,
            )

            self.state, self.state_sh = attach_error_feedback(
                self.state, self.state_sh, self.mesh, self._grad_workers,
            )
        with setup.span("state_to_device"):
            self.state = jax.tree.map(lambda x, s: jax.device_put(x, s), self.state, self.state_sh)

        # Sequence (context) parallelism needs every bucket width divisible
        # by the axis: widths are multiples of pad_to_multiple capped at the
        # max lengths, so checking those three covers all batch shapes.  A
        # non-divisible setup falls back to unsharded lengths (the model
        # then picks XLA attention per shape) instead of crashing in
        # device_put/jit dispatch.
        seq_axis = self.mesh.shape.get("sequence", 1)
        self.sequence_sharded = seq_axis > 1 and all(
            dim % seq_axis == 0
            for dim in (cfg.pad_to_multiple, cfg.max_source_length, tgt_cap)
        )
        if seq_axis > 1 and not self.sequence_sharded:
            if self.pipelined:
                # the stage×sequence pipeline hard-shards hidden over the
                # sequence axis (shard_map in_specs) — there is no graceful
                # unsharded fallback, so a non-divisible setup must fail at
                # startup, not at first dispatch
                raise ValueError(
                    f"pipeline stage×sequence needs pad_to_multiple="
                    f"{cfg.pad_to_multiple}, max_source_length="
                    f"{cfg.max_source_length} and target cap {tgt_cap} all "
                    f"divisible by the sequence axis ({seq_axis})"
                )
            log_json({
                "event": "sequence_sharding_disabled",
                "reason": f"pad_to_multiple={cfg.pad_to_multiple}/"
                          f"max_source_length={cfg.max_source_length}/"
                          f"target_cap={tgt_cap} not all divisible by sequence={seq_axis}",
            })

        # --fused-ce / forced-attention misconfigurations must fail HERE,
        # loudly, before any compile: the known-bad combos are rows in the
        # composition matrix (analysis/composition.py) — fused-ce on
        # seq2seq or tensor/stage/sequence meshes, ring on pipelined
        # seq2seq, forced xla/flash on a stage×sequence llama mesh.
        if cfg.attention_impl == "ring" and self.mesh.shape.get("sequence", 1) <= 1:
            # not a combo — ring simply has nothing to shard over
            raise ValueError(
                "--attention-impl ring requires a mesh with a sequence axis > 1 "
                f"(got {dict(self.mesh.shape)})"
            )
        from distributed_llms_example_tpu.analysis.composition import (
            config_flags,
            validate_composition,
        )

        validate_composition(
            family=self.loaded.family,
            schedule=cfg.pipeline_schedule if self.pipelined else None,
            mesh_axes=dict(self.mesh.shape),
            flags=config_flags(
                pipelined=self.pipelined,
                fused_ce=cfg.fused_ce,
                attention_impl=cfg.attention_impl,
                num_experts=int(getattr(self.config, "num_experts", 0) or 0),
                grad_accum_steps=cfg.grad_accum_steps,
                optim_impl=cfg.optim_impl,
                grad_compression=cfg.grad_compression,
            ),
        )

        # In-step gradient accumulation: batch_size stays the EFFECTIVE
        # optimizer batch (one iterator batch = one optimizer step, so the
        # epoch/resume contract is untouched); the compiled step cuts it
        # into N shard-local microbatches.  Validate the divisibility the
        # regrouping needs against the actual mesh, before any compile.
        if cfg.grad_accum_steps > 1:
            from distributed_llms_example_tpu.data.batching import microbatch_size

            batch_shards = 1
            for ax in ("data", "fsdp", "expert"):
                batch_shards *= self.mesh.shape.get(ax, 1)
            micro = microbatch_size(
                cfg.batch_size,
                cfg.grad_accum_steps,
                batch_shards=batch_shards,
                process_count=jax.process_count(),
            )
            log_json({
                "event": "grad_accum",
                "grad_accum_steps": cfg.grad_accum_steps,
                "effective_batch": cfg.batch_size,
                "microbatch": micro,
            })

        # attn_dropout_rate alone (e.g. an HF checkpoint with
        # attention_dropout > 0 but dropout 0, or a llama recipe enabling
        # probs dropout on the dropout-free architecture) must also thread
        # the rng — otherwise the configured dropout silently never fires
        self.use_dropout = (
            self.config.dropout_rate > 0.0
            or float(getattr(self.config, "attn_dropout_rate", 0.0) or 0.0) > 0.0
        )
        # dropout path (--dropout-impl): the process default the shared
        # helper (ops/fused_dropout.py) reads at trace time — "auto" =
        # fused Pallas kernel on TPU, XLA bernoulli elsewhere
        from distributed_llms_example_tpu.ops.fused_dropout import (
            set_default_impl,
        )

        set_default_impl(cfg.dropout_impl)
        # optimizer-apply path (--optim-impl): process default for the
        # fused Pallas clip+AdamW kernel (ops/fused_optim.py) — "auto" =
        # fused on TPU, optax chain elsewhere; the resolved value is
        # logged below so post-hoc analysis knows which path ran
        from distributed_llms_example_tpu.ops.fused_optim import (
            resolve_impl as resolve_optim_impl,
            set_default_impl as set_optim_impl,
        )

        set_optim_impl(cfg.optim_impl)
        # pipelined runs stay on the optax chain (make_train_step gates
        # the fused plan on the adapter; log the EFFECTIVE impl)
        self.optim_impl = (
            "xla" if self.pipelined else resolve_optim_impl(cfg.optim_impl)
        )
        log_json({"event": "optim_config", "optim_impl": self.optim_impl})
        # training health: the in-graph numerics ride the compiled step
        # itself (extra metrics entries, no extra syncs) when the
        # watchdog will consume them
        from distributed_llms_example_tpu.obs.health import health_enabled

        self.health_on = health_enabled(cfg)
        with setup.span("build_step"):
            self._build_train_step()
        # deterministic fault injection (obs/chaos.py --chaos): the ONE
        # injection point for faulted numerics, checkpoint corruption,
        # transient data errors and signals; the legacy
        # ``_poison_nan_at_step`` test hook is a thin alias that arms a
        # nan_grad injection here
        from distributed_llms_example_tpu.obs.chaos import parse_chaos

        self.chaos = parse_chaos(cfg.chaos)

        ckpt_dir = os.path.join(cfg.output_dir, "checkpoints")
        self.checkpointer = Checkpointer(
            ckpt_dir,
            save_every_steps=cfg.checkpoint.save_every_steps,
            keep=cfg.checkpoint.keep,
            async_save=cfg.checkpoint.async_save,
        )
        # in-run rewind-and-retry recovery (train/recovery.py): the state
        # machine is always constructed (its quarantine check is a dict
        # lookup per batch); only --on-anomaly rewind ever drives it
        from distributed_llms_example_tpu.train.recovery import RecoveryController

        self.recovery = RecoveryController(max_rewinds=cfg.max_rewinds)
        self._save_ordinal = 0  # chaos ckpt_corrupt ticks on save ordinals
        # Stacked-block STORAGE ORDER is schedule-dependent (interleaved
        # packs each device's v non-contiguous chunks contiguously) but
        # invisible to array shapes — resuming a checkpoint under a
        # different layout would silently train a layer-permuted model.
        # Record the layout next to the checkpoints and hard-fail on
        # mismatch instead.
        # v == 1 is the IDENTITY permutation (interleave_order(L, S, 1) is
        # ascending), so only v > 1 is a distinct storage layout — and the
        # permutation is f(L, stages, v): the STAGE COUNT matters too (the
        # same v on a resized stage axis packs different chunks per shard),
        # so it is part of the guarded identity
        permuted = (
            self.pipelined
            and cfg.pipeline_schedule == "interleaved"
            and cfg.pipeline_virtual_stages > 1
        )
        self._ckpt_layout = {
            "interleaved": permuted,
            "virtual_stages": cfg.pipeline_virtual_stages if permuted else 1,
            "stages": self.mesh.shape.get("stage", 1) if permuted else 1,
        }
        # the same identity ALSO rides inside the checkpoint payload as an
        # array leaf (ADVICE r4: the sidecar can be separated from the
        # arrays — a copy that drops the small JSON silently yields a
        # layer-permuted model, which nothing else can catch since shapes
        # are permutation-invariant).  Saved with the state, checked on
        # restore; the sidecar stays for pre-restore refusal + humans.
        self._layout_leaf = np.asarray(
            [
                int(permuted),
                self._ckpt_layout["virtual_stages"],
                self._ckpt_layout["stages"],
            ],
            np.int32,
        )
        # the TOPOLOGY identity rides the payload the same way: mesh axis
        # sizes + process count + EF worker count (io/checkpoint.py
        # mesh_layout_array) — what the resharding restore's fail-fast
        # check and the spec-lint reshard pass judge a live mesh against
        self._mesh_layout_leaf = mesh_layout_array(
            dict(self.mesh.shape),
            jax.process_count(),
            self._grad_workers if cfg.grad_compression == "int8" else 0,
        )
        # THE single storage→true-order map (None: storage is already in
        # layer order).  Every consumer — eval unstack, HF export, the
        # val-loss un-permute — reads this one attribute, so the layout
        # identity cannot drift between them.
        self._storage_row_order = None
        if permuted:
            from distributed_llms_example_tpu.parallel.interleave import (
                uninterleave_order,
            )

            self._storage_row_order = uninterleave_order(
                self.config.num_hidden_layers,
                self.mesh.shape["stage"],
                cfg.pipeline_virtual_stages,
            )
        self._ckpt_layout_path = os.path.join(ckpt_dir, "stacked_layout.json")
        self.start_step = 0
        if self.checkpointer.latest_step() is not None:
            stored = {"interleaved": False, "virtual_stages": 1, "stages": 1}
            if os.path.exists(self._ckpt_layout_path):
                with open(self._ckpt_layout_path) as f:
                    stored = json.load(f)
            if stored != self._ckpt_layout:
                # refuse MIXED-layout dirs even with resume=False: this
                # run's saves would not erase the old run's higher steps,
                # and rewriting the sidecar would mislabel them for a
                # later resume (restore_latest takes the HIGHEST step)
                raise ValueError(
                    f"checkpoint dir {ckpt_dir} stores stacked blocks in "
                    f"layout {stored}, but this run uses "
                    f"{self._ckpt_layout} — resume with the same "
                    "--pipeline-schedule/--pipeline-virtual-stages flags "
                    "AND stage-axis size, or point --output-dir at a fresh "
                    "directory (array shapes match under any row "
                    "permutation, so restoring across layouts would "
                    "silently permute the model's layers)"
                )
        # per-step resharding plans, populated by _restore_target_for as
        # restore_latest's walk consults it (cleared before every walk)
        self._reshard_plan: dict[int, dict] = {}
        # test hook: the topology-change path's next mesh (a MeshSpec /
        # MeshConfig); None = re-resolve the configured shape against the
        # surviving device count (core/mesh.py elastic_mesh_spec)
        self._next_mesh_override = None
        if cfg.checkpoint.resume and self.checkpointer.latest_step() is not None:
            # THE RESHARDING RESTORE (ISSUE 14): the abstract target is
            # built PER CANDIDATE STEP from the saved payload's orbax
            # metadata — its STRUCTURE (legacy bare-TrainState vs layout
            # payload, error-feedback tree present or not, the EF worker
            # dim as saved) matches the disk, its SHARDINGS come from the
            # LIVE mesh — so a checkpoint written under a different
            # data×fsdp factorization or process count restores directly
            # onto this mesh.  A mixed flag-flip dir needs no candidate
            # ladder anymore: every step gets the target its own payload
            # shape requires, so the newest verified step always wins.
            t0 = time.perf_counter()
            self._reshard_plan = {}
            with setup.span("restore"):
                restored = self.checkpointer.restore_latest(
                    None, target_for=self._restore_target_for
                )
            if restored is None:
                # checkpoints EXIST but none passed verification:
                # training silently from step 0 would let this run's
                # retention garbage-collect the (possibly salvageable)
                # corrupt steps — refuse loudly instead
                self._refuse_unverifiable_resume(ckpt_dir)
            payload, self.start_step = restored
            self.state, plan = self._finish_restore(payload, self.start_step)
            log_json({
                "event": "resumed", "step": self.start_step,
                **({"legacy_payload": True} if plan["legacy"] else {}),
            })
            if plan["resharded"]:
                self._emit_reshard_restore(
                    plan, self.start_step,
                    reshard_wall_s=round(time.perf_counter() - t0, 4),
                )
        # cross-run recovery state: the (epoch, pos) cursor and the
        # quarantine set ride a sidecar next to the restored step —
        # after a quarantine skip the cursor drifts from step %
        # steps_per_epoch, so the arithmetic fallback would re-train one
        # batch and shift the rest of the epoch
        self._resume_cursor: tuple[int, int] | None = None
        if self.start_step:
            side = self._load_recovery_sidecar(self.start_step)
            if side is not None:
                self._resume_cursor = (int(side["epoch"]), int(side["pos"]))
                for e, s, rec in side.get("quarantined", []):
                    self.recovery.quarantined[(int(e), int(s))] = rec
                log_json({
                    "event": "recovery_cursor_restored",
                    "step": self.start_step,
                    "epoch": self._resume_cursor[0],
                    "pos": self._resume_cursor[1],
                    "quarantined": len(self.recovery.quarantined),
                })
        # Written at init, AFTER the mismatch guard: a mixed dir has
        # already been refused above, and deferring to the first save
        # would leave a crash window (preemption save lands, SIGKILL
        # before the sidecar write → interleaved checkpoints unlabeled,
        # and a later same-flags resume would be refused as a "mismatch").
        # Only written when storage is actually permuted — the guard's
        # missing-sidecar default IS the standard layout, so a sidecar for
        # it would add nothing (and litter every plain run's output dir)
        if permuted and jax.process_index() == 0:  # pod-agreed: p0-only LOCAL sidecar write; no collectives in branch
            os.makedirs(ckpt_dir, exist_ok=True)
            with open(self._ckpt_layout_path, "w") as f:
                json.dump(self._ckpt_layout, f)

        # Generation-based ROUGE under stage>1 unstacks each layer onto the
        # FSDP/TP rule shardings — but on a PURE-stage mesh (fsdp×tensor==1,
        # the canonical too-big-for-one-chip config) those rules resolve to
        # fully replicated, i.e. a whole-model copy per device: exactly the
        # cliff the pipeline exists to avoid.  Auto-skip ROUGE there (the
        # stage-sharded teacher-forced val_loss is always reported); an
        # explicit --no-pipeline-eval-rouge skips it on any mesh.
        self._pipeline_rouge_ok = self.cfg.pipeline_eval_rouge and (
            self.mesh.shape.get("fsdp", 1) * self.mesh.shape.get("tensor", 1) > 1
        )
        if self.pipelined and self.cfg.pipeline_eval_rouge and not self._pipeline_rouge_ok:
            log_json({
                "event": "pipeline_rouge_disabled",
                "reason": "fsdp*tensor == 1: unstacked eval params would be "
                          "fully replicated (one whole-model copy per device); "
                          "reporting stage-sharded val_loss only",
            })
        # Eval always uses the STANDARD (per-layer) module: under pipeline
        # parallelism evaluate() unstacks the stacked blocks first (layer
        # params then live replicated across stage groups for the eval pass
        # — generation needs the KV-cache path the pipeline adapter lacks).
        self.evaluator = (
            Evaluator(
                self.loaded.module,
                self.config,
                self.tokenizer,
                self.mesh,
                num_beams=cfg.num_beams,
                max_new_tokens=cfg.eval_max_new_tokens,
                is_seq2seq=self.loaded.is_seq2seq,
            )
            if self.val_ds
            else None
        )
        # dropout stream: --prng-impl auto resolves to the TPU hardware
        # RNG on TPU backends (threefry's counter math can cost ~20% of a
        # dropout-on step) and bit-reproducible threefry elsewhere
        self.set_prng_impl(cfg.prng_impl)
        if self.use_dropout:
            from distributed_llms_example_tpu.ops.fused_dropout import (
                resolve_impl,
            )

            log_json({
                "event": "rng_config",
                "prng_impl": self.prng_impl,
                # RESOLVED value ("fused"/"xla", never "auto") — the whole
                # point of the event is telling post-hoc which path ran
                "dropout_impl": resolve_impl(cfg.dropout_impl),
            })
        # telemetry bundle (obs/): span recorder, profiler controller,
        # heartbeat, and — under --obs jsonl / --obs-gauges on — the
        # startup AOT gauge compile (MFU FLOPs numerator + the static
        # collective-traffic account).  stage>1 skips the gauge compile:
        # the shared recipe, like the IR lint, does not cover pipelined
        # shard_map programs yet (ROADMAP open item).
        from distributed_llms_example_tpu.obs import TrainerObs

        with setup.span("obs_open"):  # holds the existing train/obs_gauge_compile where gauges are on
            self.obs = TrainerObs(cfg, start_step=self.start_step, manage_sink=False)
            if not self.pipelined:
                self.obs.startup_gauges(self.mesh, tgt_cap=tgt_cap)
        self._stepped = False  # the step program has been called (set-up's first_step is over)

    # ------------------------------------------------------------------

    def _build_train_step(self) -> None:
        """(Re)build the jitted train step against ``self.mesh`` — the
        step closes over the mesh, so the topology-change path calls
        this again after swapping it.  Also resets the lazily-built
        optimizer-apply probe (same closure problem)."""
        cfg = self.cfg
        build = make_train_step(
            self.model,
            self.config,
            self.tx,
            self.schedule,
            self.mesh,
            grad_accum_steps=cfg.grad_accum_steps,
            label_smoothing=cfg.label_smoothing,
            with_dropout=self.use_dropout,
            is_seq2seq=self.loaded.is_seq2seq,
            sequence_sharded=self.sequence_sharded,
            rules=self._rules,
            health=self.health_on,
            optim_spec=self.optim_spec,
            optim_impl=cfg.optim_impl,
            grad_compression=cfg.grad_compression,
        )
        self.train_step, _ = build(self.state)
        # lazily-built jitted optimizer-apply probe (budget layer): the
        # cadenced optimizer_apply_ms sample — see _optimizer_probe_output
        self._opt_probe = None

    def set_prng_impl(self, impl: str) -> None:
        """(Re)seed the dropout stream with the given PRNG implementation
        ("auto" / "threefry" / "rbg") — the ONE home for the key wiring
        AND the auto resolution (rbg on TPU backends, threefry elsewhere),
        used by __init__ and by bench A/B passes, so the two cannot drift.
        The resolved impl lands in ``self.prng_impl`` so bench/obs can
        stamp it into their records."""
        if impl == "auto":
            impl = "rbg" if jax.default_backend() == "tpu" else "threefry"
        self.prng_impl = impl
        self._rng = (
            jax.random.PRNGKey(self.cfg.shuffle_seed)
            if impl == "threefry"
            else jax.random.key(self.cfg.shuffle_seed, impl=impl)
        )

    def _refuse_unverifiable_resume(self, ckpt_dir: str) -> None:
        raise ValueError(
            f"resume: checkpoints exist under {ckpt_dir} "
            f"(steps {self.checkpointer.all_steps()}) but none passed "
            "integrity verification — see the ckpt_verify_failed events "
            "for per-file detail; inspect/restore the step dirs against "
            "their integrity-<step>.json manifests, or pass --no-resume "
            "to train from scratch (which will eventually retention-"
            "delete the corrupt steps)"
        )

    @property
    def _poison_nan_at_step(self) -> int | None:
        """Legacy test hook, kept as a thin alias over the chaos harness:
        reading returns the first armed-but-unfired nan_grad step (None =
        never), assigning arms a ``nan_grad@step`` injection."""
        armed = self.chaos.armed_at("nan_grad")
        return armed[0] if armed else None

    @_poison_nan_at_step.setter
    def _poison_nan_at_step(self, step: int | None) -> None:
        # assignment REPLACES the armed injection, exactly like the plain
        # attribute it used to be: None disarms, a step re-arms
        self.chaos.disarm("nan_grad")
        if step is not None:
            self.chaos.arm("nan_grad", int(step))

    def _save_checkpoint(
        self,
        step: int,
        *,
        epoch: int | None = None,
        pos: int | None = None,
        force: bool = False,
    ) -> bool:
        """THE checkpoint save path — every save (cadence, rewind anchor,
        anomaly, preemption, final) goes through here so the recovery
        snapshot (RNG + data cursor, needed for a bit-exact in-process
        rewind) and the chaos ``ckpt_corrupt`` ordinal counter cannot
        miss one."""
        saved = self.checkpointer.save(step, self._with_layout(self.state), force=force)
        if not saved:
            return False
        self._save_ordinal += 1
        if epoch is not None and pos is not None:
            self.recovery.note_save(step, rng=self._rng, epoch=epoch, pos=pos)
            self._write_recovery_sidecar(step, epoch, pos)
        if self.chaos.take("ckpt_corrupt", self._save_ordinal):
            # finalize the data AND its checksum manifest first: the
            # corruption must be caught by integrity verification, not by
            # an unluckily torn write orbax happens to notice
            self.checkpointer.wait()
            if jax.process_index() == 0:  # pod-agreed: chaos injection corrupts p0's local file only; no collectives in branch
                from distributed_llms_example_tpu.obs.chaos import corrupt_checkpoint

                corrupt_checkpoint(self.checkpointer.step_dir(step))
        return True

    def _recovery_sidecar_path(self, step: int) -> str:
        from distributed_llms_example_tpu.io.checkpoint import RECOVERY_PREFIX

        return os.path.join(
            self.checkpointer.directory, f"{RECOVERY_PREFIX}{int(step)}.json"
        )

    def _write_recovery_sidecar(self, step: int, epoch: int, pos: int) -> None:
        """Persist the host-side recovery state orbax's payload cannot
        hold — the (epoch, pos) data cursor and the quarantine set — next
        to the checkpoint (atomic, p0).  Quarantine skips make the cursor
        drift from ``step % steps_per_epoch``, so a CROSS-RUN resume that
        reconstructed it arithmetically would re-train one batch and
        shift the rest of the epoch; with the sidecar, resume is exact
        and the quarantine survives the restart (the dropout-RNG snapshot
        stays in-memory only: bit-exact replay is a same-process
        property).  GC'd with the step by io/checkpoint.py."""
        if jax.process_index() != 0:  # pod-agreed: p0-only LOCAL sidecar write; no collectives after the early return
            return
        payload = {
            "step": int(step),
            "epoch": int(epoch),
            "pos": int(pos),
            "quarantined": [
                [e, s, rec] for (e, s), rec in self.recovery.quarantined.items()
            ],
            # the saving topology, readable WITHOUT a restore: the
            # resharding path's fail-fast pre-check and obs.report's
            # old→new mesh rows both read it from here
            "mesh_layout": self._live_mesh_layout(),
        }
        path = self._recovery_sidecar_path(step)
        tmp = path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(payload, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except OSError as e:
            # best-effort, like the manifest write: resume falls back to
            # the arithmetic cursor when the sidecar is missing
            log_json({
                "event": "recovery_sidecar_write_failed",
                "step": int(step),
                "error": str(e)[:200],
            })

    def _load_recovery_sidecar(self, step: int) -> dict | None:
        try:
            with open(self._recovery_sidecar_path(step)) as f:
                return json.load(f)
        except (OSError, json.JSONDecodeError):
            return None

    def _with_data_retries(self, batches: Any):
        """Wrap the epoch's batch stream with the chaos ``data_error``
        injection point and its retry (capped backoff, ``data_retry``
        events).  The injected error is raised BEFORE touching the
        iterator, so the retry cleanly re-fetches.  A real error from
        the iterator propagates immediately: a generator or Prefetcher
        that raised is dead (the producer latches the error), so
        retrying could only emit phantom ``data_retry`` events and sleep
        before failing with the same exception — transient FILE errors
        are retried where the read is actually restartable, inside
        ``data/dataset.py``."""
        class _Injected(OSError):
            pass  # raised BEFORE next(it): the iterator is untouched

        it = iter(batches)
        while True:
            attempt, delay = 0, 0.05
            while True:
                try:
                    if self.chaos.take("data_error", self._last_step + 1):
                        raise _Injected("chaos: injected transient data-read error")
                    batch = next(it)
                    break
                except StopIteration:
                    return
                except _Injected as e:
                    attempt += 1
                    log_json({
                        "event": "data_retry",
                        "step": self._last_step + 1,
                        "attempt": attempt,
                        "backoff_s": round(delay, 3),
                        "error": str(e)[:200],
                    })
                    delay = sleep_backoff(delay, cap_s=2.0)
            yield batch

    def _saved_ef_workers(self, meta: Any) -> int:
        """The error-feedback worker count a payload was SAVED with, read
        from its orbax metadata (0 = no EF tree in the payload).  The
        worker dim is a function of the saving mesh's replica axes, so
        this is the one state shape a topology change moves."""
        state_meta = meta.get("state", meta) if isinstance(meta, dict) else meta
        ef_meta = (
            state_meta.get("ef") if isinstance(state_meta, dict)
            else getattr(state_meta, "ef", None)
        )
        shapes = [
            tuple(x.shape)
            for x in jax.tree.leaves(ef_meta)
            if hasattr(x, "shape") and len(tuple(x.shape))
        ]
        return int(shapes[0][0]) if shapes else 0

    def _ef_restore_target(self, abstract, saved_workers: int):
        """The EF half of the per-step restore target — PR 12's flag-flip
        ladder generalized to ARBITRARY saved worker counts (ISSUE 14),
        shared by resume, anomaly-rewind and the topology path so none
        can drift.  Returns ``(target, ef_mode)``:

        - saved 0, live on   → ef-less target, then ZERO-FILL ("fill")
        - saved W, live off  → restore at W, then DROP ("drop")
        - saved W == live W  → unchanged ("")
        - saved W != live W  → restore at the SAVED W (worker dim laid
          over the live replica axes when divisible, replicated
          otherwise), then RE-TILE when the live count divides the saved
          one ("retile": merged groups' residuals sum, preserving the
          total deferred error) or ZERO-FILL otherwise ("zero")."""
        live_ef = getattr(self.state, "ef", None) is not None
        live_workers = self._grad_workers if live_ef else 0
        if saved_workers == 0:
            return (abstract.replace(ef=None), "fill") if live_ef else (abstract, "")
        from distributed_llms_example_tpu.parallel.sharding import divisible_spec
        from distributed_llms_example_tpu.ops.quant_collectives import tiled_spec
        from jax.sharding import NamedSharding

        def one(p, sh):
            shape = (int(saved_workers),) + tuple(p.shape)
            spec = divisible_spec(tiled_spec(sh.spec), shape, self.mesh)
            return jax.ShapeDtypeStruct(
                shape, np.float32, sharding=NamedSharding(self.mesh, spec)
            )

        param_sh = (
            self.state_sh.params if hasattr(self.state_sh, "params") else self.state_sh
        )
        target = abstract.replace(
            ef=jax.tree.map(one, abstract.params, param_sh)
        )
        if not live_ef:
            return target, "drop"
        if saved_workers == live_workers:
            # same worker count: the payload's EF tree restores directly
            # (the target must still CARRY it — `abstract` is ef-less)
            return target, ""
        return target, ("retile" if saved_workers % live_workers == 0 else "zero")

    def _apply_ef_mode(self, state, ef_mode: str, step: int, saved_workers: int = 0):
        """Finish a flag-flip or reshard restore: zero-fill the EF tree
        (sharded at birth), drop the restored residual, or re-tile it
        onto the new worker count — with the event log."""
        if ef_mode == "fill":
            from distributed_llms_example_tpu.ops.quant_collectives import (
                sharded_zero_error_feedback,
            )

            state = state.replace(ef=sharded_zero_error_feedback(
                state.params, self._grad_workers, self.state_sh.ef,
            ))
            log_json({
                "event": "grad_compression_ef_zero_filled",
                "step": int(step),
                "reason": "checkpoint carries no error-feedback tree "
                          "(written before --grad-compression, or with "
                          "it off); resuming with a zero residual",
            })
        elif ef_mode == "drop":
            state = state.replace(ef=None)
            log_json({
                "event": "grad_compression_ef_dropped",
                "step": int(step),
                "reason": "checkpoint was written under --grad-compression "
                          "int8 but this run has it off; the error-feedback "
                          "residual is dropped (its deferred quantization "
                          "error is lost once — the uncompressed run does "
                          "not need it)",
            })
        elif ef_mode == "retile":
            from distributed_llms_example_tpu.ops.quant_collectives import (
                retile_error_feedback,
            )

            state = state.replace(ef=retile_error_feedback(
                state.ef, self._grad_workers, self.state_sh.ef,
            ))
            log_json({
                "event": "grad_compression_ef_reshaped",
                "step": int(step),
                "mode": "retile",
                "from_workers": int(saved_workers),
                "to_workers": int(self._grad_workers),
                "reason": "topology change: the new worker count divides "
                          "the saved one, so each new worker group absorbs "
                          "the summed residuals of the groups it merges "
                          "(total deferred quantization error preserved)",
            })
        elif ef_mode == "zero":
            from distributed_llms_example_tpu.ops.quant_collectives import (
                sharded_zero_error_feedback,
            )

            state = state.replace(ef=sharded_zero_error_feedback(
                state.params, self._grad_workers, self.state_sh.ef,
            ))
            log_json({
                "event": "grad_compression_ef_reshaped",
                "step": int(step),
                "mode": "zero_fill",
                "from_workers": int(saved_workers),
                "to_workers": int(self._grad_workers),
                "reason": "topology change: the new worker count does not "
                          "divide the saved one — no residual regrouping "
                          "preserves the per-worker error, so it restarts "
                          "from zero (step-0 semantics, one residual's "
                          "worth of deferred error dropped)",
            })
        return state

    def _live_mesh_layout(self) -> dict:
        return {
            "axes": {a: int(s) for a, s in self.mesh.shape.items()},
            "processes": int(jax.process_count()),
            "ef_workers": (
                int(self._grad_workers)
                if getattr(self.state, "ef", None) is not None else 0
            ),
        }

    def _check_reshardable(self, saved_layout: dict, step: int) -> None:
        """Fail FAST, with both factorizations named, when a recorded
        topology cannot map onto the live mesh (``analysis/spec_lint.py
        lint_reshard_layout`` is the shared judge) — instead of the
        opaque orbax structure error the walk-back used to surface."""
        live = self._live_mesh_layout()
        axes = saved_layout.get("axes", {})
        if axes == live["axes"] and saved_layout.get("processes") == live["processes"]:
            return  # same topology: nothing to judge
        from distributed_llms_example_tpu.analysis.spec_lint import (
            lint_reshard_layout,
        )

        abstract_params = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.state.params
        )
        errors = [
            f for f in lint_reshard_layout(
                saved_layout, dict(self.mesh.shape), abstract_params,
                rules=self._rules,
            )
            if f.severity == "error"
        ]
        if errors:
            raise ReshardError(
                f"checkpoint step {step} was saved under "
                f"{describe_factorization(saved_layout)} and cannot restore "
                f"onto the live {describe_factorization(live)}: "
                + "; ".join(f.message for f in errors[:3])
            )

    def _restore_target_for(self, step: int):
        """Per-step abstract restore target for the resharding path:
        structure from the SAVED payload's orbax metadata, shardings from
        the LIVE mesh.  Records the step's plan (legacy?, ef mode, saved
        layout) in ``self._reshard_plan`` for ``_finish_restore``."""
        step = int(step)
        abstract = abstract_like(
            self.state.replace(ef=None), self.state_sh.replace(ef=None)
        )
        meta = self.checkpointer.payload_metadata(step)
        side = self._load_recovery_sidecar(step)
        saved_layout = (side or {}).get("mesh_layout")
        if saved_layout:
            # the sidecar names the saving topology WITHOUT a restore —
            # the fail-fast seam (sidecar-less dirs are judged after the
            # restore lands, from the payload's own mesh_layout leaf)
            self._check_reshardable(saved_layout, step)
        legacy = False
        structure_unknown = False
        has_mesh_leaf = False
        if isinstance(meta, dict) and "state" in meta:
            has_mesh_leaf = "mesh_layout" in meta
            saved_workers = self._saved_ef_workers(meta)
        elif meta is not None:
            # bare-TrainState payload (pre-layout-leaf checkpoints)
            legacy = True
            saved_workers = self._saved_ef_workers(meta)
        else:
            # no metadata (foreign/ancient dir): the structure cannot be
            # classified — assume the live EF shape and try BOTH payload
            # structures (layout payload first, legacy bare state as the
            # fallback, exactly the pre-reshard candidate ladder's order)
            structure_unknown = True
            saved_workers = (
                self._grad_workers
                if getattr(self.state, "ef", None) is not None else 0
            )
        target, ef_mode = self._ef_restore_target(abstract, saved_workers)
        resharded = bool(saved_layout) and (
            saved_layout.get("axes") != self._live_mesh_layout()["axes"]
            or saved_layout.get("processes") != jax.process_count()
        )
        self._reshard_plan[step] = {
            "legacy": legacy,
            "structure_unknown": structure_unknown,
            "ef_mode": ef_mode,
            "saved_workers": int(saved_workers),
            "saved_layout": saved_layout,
            "resharded": resharded or ef_mode in ("retile", "zero"),
        }
        if legacy:
            return target
        payload: dict[str, Any] = {
            "state": target,
            "stacked_layout": jax.ShapeDtypeStruct(
                self._layout_leaf.shape, self._layout_leaf.dtype
            ),
        }
        if has_mesh_leaf:
            payload["mesh_layout"] = jax.ShapeDtypeStruct(
                self._mesh_layout_leaf.shape, self._mesh_layout_leaf.dtype
            )
        if not structure_unknown:
            return payload
        # the pre-reshard candidate ladder's order for an unclassifiable
        # step: layout payload first (mesh-leaf-carrying — the modern
        # save format — then the pre-mesh-leaf shape, live EF structure
        # then the --grad-compression flag-flip shape), legacy bare
        # state last — _finish_restore classifies structure AND EF
        # transition from what actually landed
        from distributed_llms_example_tpu.ops.quant_collectives import (
            worker_count,
        )

        live_ef = getattr(self.state, "ef", None) is not None
        flip, _ = self._ef_restore_target(
            abstract, 0 if live_ef else worker_count(dict(self.mesh.shape))
        )
        flip_payload = dict(payload)
        flip_payload["state"] = flip

        def with_mesh_leaf(p: dict) -> dict:
            q = dict(p)
            q["mesh_layout"] = jax.ShapeDtypeStruct(
                self._mesh_layout_leaf.shape, self._mesh_layout_leaf.dtype
            )
            return q

        return [
            with_mesh_leaf(payload), payload,
            with_mesh_leaf(flip_payload), flip_payload,
            target, flip,
        ]

    def _finish_restore(self, payload: Any, step: int) -> tuple[Any, dict]:
        """Unwrap a restored payload per its recorded plan: layout-leaf
        guard, mesh-layout cross-check (the sidecar-less fail path), EF
        fill/drop/retile/zero-fill.  Returns ``(state, plan)``."""
        plan = self._reshard_plan.pop(int(step), None) or {
            "legacy": not isinstance(payload, dict),
            "ef_mode": "", "saved_workers": 0,
            "saved_layout": None, "resharded": False,
        }
        if plan.get("structure_unknown"):
            # a metadata-less step offered several candidate structures
            # — classify the payload shape AND the EF transition by what
            # actually restored
            plan["legacy"] = not isinstance(payload, dict)
            inner = payload if plan["legacy"] else payload["state"]
            restored_ef = getattr(inner, "ef", None)
            live_ef = self.cfg.grad_compression == "int8"
            if live_ef and restored_ef is None:
                plan["ef_mode"] = "fill"
            elif not live_ef and restored_ef is not None:
                plan["ef_mode"] = "drop"
                plan["saved_workers"] = int(
                    jax.tree.leaves(restored_ef)[0].shape[0]
                )
            else:
                plan["ef_mode"] = ""
        if plan["legacy"]:
            state = payload
        else:
            stored_leaf = np.asarray(jax.device_get(payload["stacked_layout"]))
            if not np.array_equal(stored_leaf, self._layout_leaf):
                raise ValueError(
                    f"checkpoint payload records stacked-block layout "
                    f"[interleaved, virtual_stages, stages] = "
                    f"{stored_leaf.tolist()}, but this run uses "
                    f"{self._layout_leaf.tolist()} — resume with the same "
                    "--pipeline-schedule/--pipeline-virtual-stages flags "
                    "and stage-axis size (restoring across layouts would "
                    "silently permute the model's layers)"
                )
            if "mesh_layout" in payload and plan["saved_layout"] is None:
                # no sidecar named the topology pre-restore: the payload
                # leaf is authoritative — judge it now (still a NAMED
                # error, just after the arrays landed)
                saved = parse_mesh_layout(jax.device_get(payload["mesh_layout"]))
                self._check_reshardable(saved, step)
                plan["saved_layout"] = saved
                plan["resharded"] = plan["resharded"] or (
                    saved["axes"] != self._live_mesh_layout()["axes"]
                    or saved["processes"] != jax.process_count()
                )
            state = payload["state"]
        state = self._apply_ef_mode(
            state, plan["ef_mode"], step, saved_workers=plan["saved_workers"]
        )
        return state, plan

    def _emit_reshard_restore(self, plan: dict, step: int, **extra: Any) -> None:
        """The ``reshard_restore`` obs event: a checkpoint crossed a
        topology boundary on its way back in (old → new factorization,
        EF handling, wall clock) — what ``obs.report``'s recovery
        timeline and the MTTR account consume."""
        from distributed_llms_example_tpu.obs import sink as sink_mod

        saved = plan.get("saved_layout") or {}
        sink_mod.emit({
            "event": "reshard_restore",
            "step": int(step),
            "old_mesh": saved.get("axes"),
            "old_processes": saved.get("processes"),
            "new_mesh": {a: int(s) for a, s in self.mesh.shape.items()},
            "new_processes": int(jax.process_count()),
            "ef_mode": plan.get("ef_mode") or "none",
            **extra,
        }, local=True)

    def _with_layout(self, state: Any, abstract: bool = False) -> dict:
        """Checkpoint payload: the TrainState plus the stacked-block
        layout identity AND the mesh topology (axis sizes, process
        count, EF workers) as ARRAY leaves, so neither identity can be
        separated from the arrays it describes (a sidecar JSON can)."""
        if abstract:
            return {
                "state": state,
                "stacked_layout": jax.ShapeDtypeStruct(
                    self._layout_leaf.shape, self._layout_leaf.dtype
                ),
                "mesh_layout": jax.ShapeDtypeStruct(
                    self._mesh_layout_leaf.shape, self._mesh_layout_leaf.dtype
                ),
            }
        return {
            "state": state,
            "stacked_layout": self._layout_leaf,
            "mesh_layout": self._mesh_layout_leaf,
        }

    def evaluate(
        self, epoch: int | None = None, step: int | None = None
    ) -> dict[str, float]:
        if self.val_ds is None:
            return {}
        scores: dict[str, float] = {}
        if self.pipelined:
            # teacher-forced val loss through the PIPELINED module: params
            # stay stage-sharded, nothing is unstacked — the eval path that
            # works for models too big to replicate (VERDICT r2 weak #4)
            scores["val_loss"] = self._pipelined_val_loss()
        run_rouge = self.evaluator is not None and (
            not self.pipelined or self._pipeline_rouge_ok
        )
        if run_rouge:
            eval_params = self.state.params
            if self.pipelined:
                from distributed_llms_example_tpu.parallel.pipeline import (
                    unstack_for_family_resharded,
                )

                # unstack to the standard per-layer layout with each layer
                # device_put onto the default FSDP/TP shardings AS it is
                # unstacked (at most one replicated layer live at a time) —
                # generation then needs params/(fsdp·tensor) per device,
                # the normal FSDP story instead of a whole-model cliff
                eval_params = unstack_for_family_resharded(
                    self.loaded.family, eval_params, self.mesh,
                    row_order=self._storage_row_order,
                )
            eval_batch = self.cfg.eval_batch_size or self.cfg.batch_size
            pc = jax.process_count()
            eval_batch = min(eval_batch, max(pc, len(self.val_ds)))
            # host_batch_slices requires divisibility by process count; a
            # tiny val set (e.g. 3 examples, 2 processes) would otherwise
            # crash mid-eval after the clamp above
            eval_batch = max(pc, eval_batch - eval_batch % pc)
            scores.update(self.evaluator.run(
                eval_params,
                self.val_ds,
                global_batch=eval_batch,
                bucket_multiple=self.cfg.pad_to_multiple,
                max_source_length=self.cfg.max_source_length,
            ))
        if epoch is not None:
            scores["epoch"] = float(epoch)
        # eval events carry the global step under the SAME field name as
        # the train cadence lines, so report-side timeline joins need no
        # special-casing (val_loss lands at the step that produced it)
        event = {"event": "eval", **({"step": step} if step is not None else {})}
        log_json({**event, **scores})
        return scores

    def _pipelined_val_loss(self) -> float:
        """Mean teacher-forced CE over the val set, computed with the
        stage-sharded pipelined module (no unstacking; peak memory is the
        training footprint, not a replicated copy of the model)."""
        from distributed_llms_example_tpu.train.step import make_loss_fn

        interleaved_storage = self._storage_row_order is not None
        if not hasattr(self, "_val_loss_fn"):
            from distributed_llms_example_tpu.parallel.activation import activation_mesh
            from distributed_llms_example_tpu.parallel.sharding import batch_sharding

            # same objective as training (incl. label smoothing) so the
            # train-vs-val gap measures generalization, not a formula skew.
            # Under interleaved STORAGE, score through a gpipe-VIEW adapter
            # fed a true-order tree instead (built once per evaluate below)
            # — the interleaved adapter's apply() would re-gather the whole
            # stacked tree on every batch
            model_for_val = self.model
            if interleaved_storage:
                from distributed_llms_example_tpu.models.llama import PipelinedLlama

                model_for_val = PipelinedLlama(
                    self.config, self.mesh, dtype=self.model.dtype,
                    num_microbatches=self.model.num_microbatches,
                    remat=self.cfg.remat, schedule="gpipe",
                )
            loss_sums = make_loss_fn(
                model_for_val, self.config, self.cfg.label_smoothing,
                is_seq2seq=self.loaded.is_seq2seq,
            )
            bsh = batch_sharding(self.mesh)
            jitted = jax.jit(
                lambda p, b: loss_sums(p, b),
                in_shardings=(
                    self.state_sh.params,
                    {"input_ids": bsh, "attention_mask": bsh, "labels": bsh},
                ),
            )

            def run(p, b):
                with activation_mesh(self.mesh):
                    return jitted(p, b)

            self._val_loss_fn = run
        val_params = self.state.params
        if interleaved_storage:
            # ONE stacked-tree un-permute per evaluate, not per batch —
            # and JITTED with sharded outputs, so the partitioner emits a
            # cross-shard row permutation instead of an eager per-leaf
            # take() that would gather the whole stack replicated (the
            # memory cliff this stage-sharded val path exists to avoid)
            if not hasattr(self, "_val_unpermute"):
                import jax.numpy as _jnp

                inv = self._storage_row_order  # THE storage→true-order map
                self._val_unpermute = jax.jit(
                    lambda t: jax.tree.map(lambda a: _jnp.take(a, inv, axis=0), t),
                    out_shardings=self.state_sh.params["stacked_blocks"],
                )
            val_params = dict(val_params)
            val_params["stacked_blocks"] = self._val_unpermute(
                val_params["stacked_blocks"]
            )

        # eval batch rounded to the pipeline quantum: batch shards ×
        # microbatches (and the host slice divisibility)
        shards = 1
        for ax in ("data", "fsdp", "expert"):
            shards *= self.mesh.shape.get(ax, 1)
        quantum = shards * getattr(self.model, "num_microbatches", 1)
        if quantum % jax.process_count():  # pod-agreed: arithmetic on the pod-uniform process count
            quantum *= jax.process_count()
        eval_batch = max(self.cfg.eval_batch_size or self.cfg.batch_size, quantum)
        eval_batch -= eval_batch % quantum
        val_batches = BatchIterator(
            self.val_ds,
            global_batch=eval_batch,
            process_count=jax.process_count(),
            process_index=jax.process_index(),
            seed=0,
            shuffle=False,
            drop_last=False,
            bucket_multiple=self.cfg.pad_to_multiple,
            max_source_length=self.cfg.max_source_length,
            max_target_length=(
                self.cfg.max_target_length if self.loaded.is_seq2seq else self.cfg.max_source_length
            ),
        )
        # the final batch wraps around to the epoch start to keep shapes
        # fixed (iter_global_batches drop_last=False); loss-mask those
        # duplicate rows so each example is counted exactly once — the
        # same trim the ROUGE evaluator applies to its generations
        n_batches = val_batches.steps_per_epoch()
        rem = len(self.val_ds) % eval_batch
        sl = host_batch_slices(eval_batch, jax.process_count(), jax.process_index())
        total_loss, total_tokens = 0.0, 0.0
        for i, batch in enumerate(val_batches.epoch(0)):
            if rem and i == n_batches - 1:
                local_pos = np.arange(sl.start, sl.stop)
                batch = dict(batch)
                batch["labels"] = np.where(
                    (local_pos >= rem)[:, None], LABEL_PAD, batch["labels"]
                )
            gb = put_batch(batch, self.mesh, sequence_sharded=False)
            lsum, tokens = self._val_loss_fn(val_params, gb)
            total_loss += float(lsum)
            total_tokens += float(tokens)
        return total_loss / max(total_tokens, 1.0)

    def _batch_tokens(self, batch: dict) -> int:
        """Non-pad tokens processed in one host-local batch — source plus
        target for seq2seq; for causal LM the attention mask already covers
        prompt+target, so counting labels again would double-count.  The
        benchmark keeps its own copy of this rule
        (benchmarks/harness/text.py); tests/test_benchmark_copies.py
        pins the two equal so "tokens/sec" means one thing."""
        tokens = int(np.sum(batch["attention_mask"]))
        if self.loaded.is_seq2seq:
            tokens += int(np.sum(batch["labels"] != LABEL_PAD))
        return tokens

    def _optimizer_probe_output(self):
        """The budget layer's cadenced optimizer-apply sample: run a
        stand-alone jitted ``optimizer_apply_block`` (same impl dispatch
        as the train step, zeros gradients built in-program) on the live
        state and return its reduction scalar for the caller to block
        on.  Built LAZILY at the first log cadence so runs that never
        reach a cadence pay no extra compile; only ever invoked by
        ``TrainerObs.optimizer_probe`` at the log cadence — zero new
        off-cadence syncs."""
        if self._opt_probe is None:
            from distributed_llms_example_tpu.train.step import (
                make_optimizer_probe,
            )

            self._opt_probe = make_optimizer_probe(
                self.tx, self.schedule, self.state_sh, self.mesh,
                optim_spec=self.optim_spec,
                optim_impl="xla" if self.pipelined else self.cfg.optim_impl,
                health=self.health_on,
                abstract_params=jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                    self.state.params,
                ),
            )
        return self._opt_probe(self.state)

    def _install_preemption_handler(self) -> None:
        """SIGTERM/SIGINT → finish the in-flight step, checkpoint, exit
        cleanly.  TPU pods get preempted; the reference's answer is losing
        the run (its only save is end-of-training).  With this handler plus
        resume, a preempted execution restarts where it stopped.  No-op
        outside the main thread (signal module restriction)."""
        import signal

        self._preempted = False
        self._prev_handlers = {}

        def on_signal(signum, frame):
            self._preempted = True
            log_json({"event": "preemption_signal", "signal": int(signum)})
            # one graceful chance: restore the previous handler so a SECOND
            # signal terminates (a hung collective can't be flag-broken)
            prev = self._prev_handlers.get(signum)
            if prev is not None:
                try:
                    signal.signal(signum, prev)
                except (ValueError, TypeError):
                    pass

        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                self._prev_handlers[sig] = signal.signal(sig, on_signal)
            except ValueError:  # not the main thread
                return

    def _restore_signal_handlers(self) -> None:
        import signal

        for sig, handler in getattr(self, "_prev_handlers", {}).items():
            try:
                signal.signal(sig, handler)
            except ValueError:
                pass

    def _preemption_agreed(self) -> bool:
        """Multi-host: every process must take the same branch at the same
        step — a host-local flag would leave host A saving while host B
        issues the next step's collectives (pod-wide deadlock).  All hosts
        agree via an allgather of the local flag (any host signaled →
        everyone stops).  Single-process: just the flag."""
        if jax.process_count() == 1:  # pod-agreed: process_count() is pod-uniform; single-host fast path
            return self._preempted
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(np.asarray([self._preempted]))
        return bool(np.asarray(flags).any())

    def _check_preemption(self, step: int) -> bool:
        """Preemption check for the step loop.  Single-process: the local
        flag, every step (free).  Multi-host: the allgather only at a
        bounded cadence (every ``log_every_steps``) — a per-step blocking
        host collective would serialize JAX's async dispatch and put a DCN
        round-trip on every step's critical path.  The step counter is
        identical on all hosts, so they always enter the allgather
        together; a SIGTERM is acted on at most ``log_every_steps`` steps
        late, well inside any preemption grace period (tens of seconds)."""
        if jax.process_count() == 1:  # pod-agreed: process_count() is pod-uniform; single-host fast path
            return self._preempted
        if step % self._preempt_sync_every != 0:
            return False
        return self._preemption_agreed()

    def _handle_rewind(
        self, step: int, epoch: int, pos: int
    ) -> tuple[int, int, int] | None:
        """The agreed ``rewind`` anomaly action: run the escalation
        (rewind / skip-batch / halt) through the recovery controller and
        execute it.  Returns the (epoch, pos, step) cursor the loop
        resumes at, or None to stop (``self._anomaly_action`` set).

        Every input here is pod-agreed — the anomaly record's step/code,
        the deterministic fingerprint plan position, the shared
        checkpoint dir — so all processes execute the same branch and
        enter the (collective) orbax restore together."""
        from distributed_llms_example_tpu.obs import sink as sink_mod

        t0 = time.perf_counter()
        anomaly = self.obs.last_anomaly or {"step": step, "code": "unknown"}
        a_step = int(anomaly.get("step", step))
        fingerprint = (
            self.obs.recorder.fingerprint_for(a_step)
            if self.obs.recorder is not None
            else None
        )
        decision = self.recovery.decide(anomaly, fingerprint=fingerprint)
        action, reason = decision.action, decision.reason
        if action != "halt" and fingerprint is not None:
            # quarantine FIRST (for rewind and skip_batch alike): even if
            # the restore below fails and we halt, the quarantine record
            # is evidence for the post-mortem
            self.recovery.quarantine(
                fingerprint["epoch"],
                fingerprint["epoch_step"],
                fingerprint,
                reason=f"anomaly:{anomaly.get('code')}@{a_step}",
            )
        if action == "skip_batch":
            sink_mod.emit({
                "event": "recovery", "action": "skip_batch",
                "step": a_step, "detected_at_step": int(step),
                "code": anomaly.get("code"), "reason": reason,
            }, local=True)
            sink_mod.flush(fsync=True)
            return epoch, pos, step
        if action == "rewind":
            # the rewind target can sit on the far side of a
            # --grad-compression flip OR a topology change (a run that
            # resharded can rewind past its own reshard boundary): the
            # per-step metadata-driven target builder — the SAME one the
            # resume and topology paths use — matches each candidate
            # step's saved shapes, so the walk never skips a newer step
            # over a shape mismatch
            self._reshard_plan = {}
            restored, rewind_err = None, None
            try:
                restored = self.checkpointer.restore_before(
                    a_step, None, target_for=self._restore_target_for
                )
            except Exception as e:
                rewind_err = e
            if restored is None:
                action = "halt"
                reason = (
                    f"no verified checkpoint older than anomaly step {a_step}"
                    + (f" ({str(rewind_err)[:160]})" if rewind_err else "")
                )
            else:
                payload, rstep = restored
                self.state, rplan = self._finish_restore(payload, rstep)
                if rplan["resharded"]:
                    self._emit_reshard_restore(rplan, rstep)
                # checkpoints newer than the restore target may hold the
                # poisoned state (saved between anomaly and detection)
                # with CLEAN checksums — drop them so the replay re-saves
                # from recovered state and no later rewind/resume can
                # pick them (collective, like the restore above)
                self.checkpointer.delete_after(rstep)
                snap = self.recovery.snapshot_for(rstep)
                if snap is not None:
                    # bit-exact replay: the dropout key and the data
                    # cursor exactly as they stood when this checkpoint
                    # was saved
                    self._rng = snap["rng"]
                    r_epoch, r_pos = snap["epoch"], snap["pos"]
                else:
                    # checkpoint predates this process (resume-then-
                    # rewind): its recovery sidecar carries the exact
                    # cursor even across prior-run quarantine skips; the
                    # arithmetic cursor is the last resort.  The dropout
                    # stream continues from the current key (bit-replay
                    # is a same-process property)
                    side = self._load_recovery_sidecar(rstep)
                    if side is not None:
                        r_epoch, r_pos = int(side["epoch"]), int(side["pos"])
                    else:
                        spe = self.batches.steps_per_epoch()
                        r_epoch, r_pos = rstep // spe, rstep % spe
                sink_mod.emit({
                    "event": "recovery", "action": "rewind",
                    "step": a_step, "detected_at_step": int(step),
                    "code": anomaly.get("code"),
                    "restored_step": int(rstep),
                    "steps_lost": int(step - rstep),
                    "rewind_index": self.recovery.rewinds_done,
                    "max_rewinds": self.recovery.max_rewinds,
                    "quarantined": fingerprint is not None,
                    "recovery_wall_s": round(time.perf_counter() - t0, 4),
                    "reason": reason,
                }, local=True)
                sink_mod.flush(fsync=True)
                return r_epoch, r_pos, int(rstep)
        # halt (decided, or a rewind that found nothing to restore)
        self._anomaly_action = "halt"
        sink_mod.emit({
            "event": "recovery", "action": "halt",
            "step": a_step, "detected_at_step": int(step),
            "code": anomaly.get("code"), "reason": reason,
        }, local=True)
        sink_mod.flush(fsync=True)
        return None

    def _check_topology(self, step: int) -> bool:
        """Topology-change (host-loss) check for the step loop — the
        same cadence/agreement discipline as ``_check_preemption``:
        single-process reads the local flag every step; multi-host
        agrees over an allgather at the bounded cadence so every rank
        takes the teardown branch at the same step.  (The injected
        ``host_loss@K`` schedule is deterministic across ranks, so the
        allgather is the same belt the preemption flag wears, not the
        mechanism.)"""
        if jax.process_count() == 1:  # pod-agreed: process_count() is pod-uniform; single-host fast path
            return self._host_lost
        if step % self._preempt_sync_every != 0:
            return False
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(np.asarray([self._host_lost]))
        return bool(np.asarray(flags).any())

    def _rebuild_for_mesh(self, mesh: Any) -> None:
        """Swap in a NEW mesh and rebuild everything derived from it —
        the trainer half of topology-change recovery.  Validates first
        (named errors, nothing torn down on failure), then replaces:
        shardings, the abstract state template (EF worker dim follows
        the new replica axes), the batch iterator (global batch
        PRESERVED — only the per-host slice and the shard layout move),
        the jitted train step, the evaluator, the topology payload leaf.
        ``self.state`` becomes an ABSTRACT template: the caller MUST
        follow with the resharding restore (a lost host's shards are
        gone — topology recovery is a restore, not a migration)."""
        cfg = self.cfg
        new_shape = {a: int(s) for a, s in mesh.shape.items()}
        workers = 1
        if cfg.grad_compression == "int8":
            from distributed_llms_example_tpu.ops.quant_collectives import (
                GRAD_WORKER_AXES,
                worker_count,
            )

            workers = worker_count(new_shape)
            if workers <= 1:
                raise ValueError(
                    f"--grad-compression int8 cannot continue on the new "
                    f"mesh {new_shape}: the replica axes "
                    f"{GRAD_WORKER_AXES} give 1 worker group — resume on "
                    "the new slice with compression off instead"
                )
        from distributed_llms_example_tpu.data.batching import validate_batch_mesh

        validate_batch_mesh(
            cfg.batch_size, new_shape,
            process_count=jax.process_count(),
            grad_accum_steps=cfg.grad_accum_steps,
        )
        seq_axis = new_shape.get("sequence", 1)
        sequence_sharded = seq_axis > 1 and all(
            dim % seq_axis == 0
            for dim in (cfg.pad_to_multiple, cfg.max_source_length, self._tgt_cap)
        )
        self.mesh = mesh
        self._grad_workers = workers
        self.sequence_sharded = sequence_sharded
        # abstract state template at the NEW topology: params/opt-state
        # shapes are mesh-invariant, only the EF worker dim moves
        template = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            self.state.replace(ef=None),
        )
        if cfg.grad_compression == "int8":
            template = template.replace(ef=jax.tree.map(
                lambda p: jax.ShapeDtypeStruct(
                    (workers,) + tuple(p.shape), np.float32
                ),
                template.params,
            ))
        self.state = template
        self.state_sh = state_shardings(template, mesh, self._rules)
        self._mesh_layout_leaf = mesh_layout_array(
            new_shape, jax.process_count(),
            workers if cfg.grad_compression == "int8" else 0,
        )
        # the batch PLAN is a deterministic function of (seed, epoch,
        # global batch) — all preserved — so the loss trajectory stays
        # comparable across the change; only this host's slice moves
        self.batches = BatchIterator(
            self.train_ds,
            global_batch=cfg.batch_size,
            process_count=jax.process_count(),
            process_index=jax.process_index(),
            seed=cfg.shuffle_seed,
            bucket_multiple=cfg.pad_to_multiple,
            max_source_length=cfg.max_source_length,
            max_target_length=self._tgt_cap,
        )
        self._build_train_step()
        # the startup obs gauges (MFU FLOPs numerator, the static
        # collective-traffic account, devprof's instruction→bucket index)
        # were compiled against the OLD mesh — recompute them from the
        # rebuilt step so post-reshard windows stop reporting a stale MFU
        # and the byte account matches the live program (the PR 14
        # caveat).  Same gating/failure-isolation as startup: an
        # obs_gauges_skipped event, never a failed recovery.
        if not self.pipelined:
            self.obs.startup_gauges(mesh, tgt_cap=self._tgt_cap)
        for attr in ("_val_loss_fn", "_val_unpermute"):
            if hasattr(self, attr):
                delattr(self, attr)
        if self.val_ds:
            self.evaluator = Evaluator(
                self.loaded.module,
                self.config,
                self.tokenizer,
                mesh,
                num_beams=cfg.num_beams,
                max_new_tokens=cfg.eval_max_new_tokens,
                is_seq2seq=self.loaded.is_seq2seq,
            )

    def _handle_topology_change(
        self, step: int, epoch: int, pos: int
    ) -> tuple[int, int, int] | None:
        """The agreed host-loss action (ISSUE 14), on top of PR 6's
        escalation: tear down collectives, re-run the ``jax.distributed``
        bootstrap on the surviving slice, rebuild mesh / shardings /
        train step / batch plan, restore the newest verified checkpoint
        through the RESHARDING path, and resume from the recovery
        sidecar's (epoch, pos) cursor with the quarantine set intact.
        Returns the cursor the loop resumes at, or None to stop
        (``self._anomaly_action`` set — the evidence-preserving
        checkpoint policy, like a final-window rewind)."""
        from distributed_llms_example_tpu.obs import sink as sink_mod

        t0 = time.perf_counter()
        self._host_lost = False
        old_layout = self._live_mesh_layout()
        halt_reason: str | None = None
        if self.cfg.on_host_loss != "reshard":
            halt_reason = "--on-host-loss halt: leaving recovery to a resumed run"
        elif self.pipelined:
            # the composition table's row IS the message (deep-guard
            # discipline: the text cannot drift from the table)
            from distributed_llms_example_tpu.analysis.composition import (
                reason_for,
            )

            halt_reason = reason_for("reshard-pipelined")
        sink_mod.emit({
            "event": "topology_change",
            "step": int(step),
            "old_mesh": old_layout["axes"],
            "old_processes": old_layout["processes"],
            "policy": "halt" if halt_reason else "reshard",
            **({"reason": halt_reason} if halt_reason else {}),
        }, local=True)
        sink_mod.flush(fsync=True)
        if halt_reason:
            self._anomaly_action = "checkpoint"
            return None
        # nothing in flight may straddle the teardown
        self.checkpointer.wait()
        if old_layout["processes"] > 1:
            # the ONE owner of the re-init path (core/mesh.py): shutdown
            # + fresh bootstrap from the re-read rendezvous facts of the
            # surviving slice
            from distributed_llms_example_tpu.core.mesh import (
                reinitialize_distributed,
            )

            reinitialize_distributed()
        try:
            if self._next_mesh_override is not None:
                new_mesh = build_mesh(self._next_mesh_override)
                self._next_mesh_override = None
            else:
                from distributed_llms_example_tpu.core.mesh import elastic_mesh_spec

                new_mesh = build_mesh(
                    elastic_mesh_spec(self.cfg.mesh, jax.device_count())
                )
            self._rebuild_for_mesh(new_mesh)
            self._reshard_plan = {}
            restored = self.checkpointer.restore_latest(
                None, target_for=self._restore_target_for
            )
        except Exception as e:
            sink_mod.emit({
                "event": "recovery", "action": "halt", "step": int(step),
                "code": "host_loss",
                "reason": f"topology rebuild/restore failed: {str(e)[:240]}",
            }, local=True)
            sink_mod.flush(fsync=True)
            self._anomaly_action = "halt"
            return None
        if restored is None:
            sink_mod.emit({
                "event": "recovery", "action": "halt", "step": int(step),
                "code": "host_loss",
                "reason": "no verified checkpoint to reshard from",
            }, local=True)
            sink_mod.flush(fsync=True)
            self._anomaly_action = "halt"
            return None
        payload, rstep = restored
        self.state, plan = self._finish_restore(payload, rstep)
        # exact cursor + quarantine, same ladder as rewind: the in-memory
        # save snapshot (restores the dropout key too, so an in-process
        # reshard replays the surviving steps on the same RNG stream),
        # then the recovery sidecar (cross-run: pos can drift from
        # step % steps_per_epoch after a quarantine skip), then arithmetic
        snap = self.recovery.snapshot_for(rstep)
        side = self._load_recovery_sidecar(rstep)
        if side is not None:
            for e, s, rec in side.get("quarantined", []):
                self.recovery.quarantined.setdefault((int(e), int(s)), rec)
        if snap is not None:
            self._rng = snap["rng"]
            r_epoch, r_pos = snap["epoch"], snap["pos"]
        elif side is not None:
            r_epoch, r_pos = int(side["epoch"]), int(side["pos"])
        else:
            spe = self.batches.steps_per_epoch()
            r_epoch, r_pos = rstep // spe, rstep % spe
        self._emit_reshard_restore(
            plan, rstep,
            detected_at_step=int(step),
            steps_lost=int(step - rstep),
            reshard_wall_s=round(time.perf_counter() - t0, 4),
        )
        sink_mod.flush(fsync=True)
        return r_epoch, r_pos, int(rstep)

    def train(self) -> dict[str, Any]:
        # handlers restored in a finally: a raising train step must not
        # leave the flag-setting handler installed process-wide (it would
        # swallow Ctrl-C forever after); on the preempted path the finally
        # runs AFTER the graceful checkpoint, so a second SIGTERM during
        # the save terminates instead of being silently re-flagged
        self._install_preemption_handler()
        try:
            return self._train_loop()
        except Exception as e:
            # a crashing step must still leave the post-mortem evidence:
            # dump the flight recorder (ring → atomic bundle) — and, when
            # the crash is a RESOURCE_EXHAUSTED, the memory postmortem
            # (last static account + watermark history + live-buffer
            # top-N) — then push the JSONL channel to disk before the
            # traceback propagates
            crash_step = int(getattr(self, "_last_step", self.start_step))
            if self.obs.recorder is not None:
                self.obs.recorder.dump(
                    self.cfg.output_dir,
                    reason="exception",
                    step=crash_step,
                )
            if self.obs.memory is not None:
                self.obs.memory.maybe_dump_postmortem(
                    self.cfg.output_dir, step=crash_step, error=e
                )
            from distributed_llms_example_tpu.obs import sink as sink_mod

            sink_mod.flush(fsync=True)
            raise
        finally:
            self._restore_signal_handlers()

    def _train_loop(self) -> dict[str, Any]:
        from distributed_llms_example_tpu.obs.recorder import batch_fingerprint

        cfg = self.cfg
        obs = self.obs
        obs.set_start_step(self.start_step)
        logger = MetricLogger(every=cfg.log_every_steps)
        self._preempt_sync_every = max(1, cfg.log_every_steps)
        step = self.start_step
        self._last_step = step
        self._anomaly_action: str | None = None
        self._host_lost = False
        t0 = time.perf_counter()
        last_eval: dict[str, float] = {}
        last_metrics: dict[str, Any] | None = None
        steps_per_epoch = self.batches.steps_per_epoch()
        # (epoch, pos) is the DATA cursor: ``pos`` counts iterator items
        # consumed this epoch INCLUDING quarantine-skipped batches, so it
        # can drift ahead of ``step % steps_per_epoch`` after a recovery
        # skip.  The global ``step`` stays the optimizer-step counter
        # (checkpoints, LR schedule, resume contract); only the cursor
        # knows about skips, and rewinds restore both together.
        if self._resume_cursor is not None:
            # exact cursor from the recovery sidecar (survives quarantine
            # skips); arithmetic otherwise
            epoch, pos = self._resume_cursor
        else:
            epoch = step // steps_per_epoch
            pos = step - epoch * steps_per_epoch
        report_epoch = epoch
        if cfg.on_anomaly == "rewind" and self.checkpointer.latest_step() is None:
            # the rewind anchor: an anomaly before the first periodic save
            # must still find a verified step to restore to — without it
            # the very first recovery attempt could only halt
            self._save_checkpoint(step, epoch=epoch, pos=pos, force=True)
            self.checkpointer.wait()
        while epoch < cfg.num_epochs:
            report_epoch = epoch
            # assemble host batches (tokenize/pad/bucket) on a background
            # thread, prefetch_batches ahead, so input work overlaps the
            # device step instead of sitting on the critical path.  A
            # resumed (or rewound) epoch fast-forwards at the INDEX level
            # (the batch plan is deterministic per (seed, epoch)): no
            # skipped batch is ever tokenized or padded.
            epoch_batches = self.batches.epoch(epoch, start_step=pos)
            if cfg.prefetch_batches > 0:
                epoch_batches = Prefetcher(epoch_batches, depth=cfg.prefetch_batches)
            rewind_cursor: tuple[int, int, int] | None = None
            topology_cursor: tuple[int, int, int] | None = None
            try:
                for batch in obs.wrap_batches(self._with_data_retries(epoch_batches)):
                    pos += 1
                    if self.recovery.should_skip(epoch, pos - 1, batch):
                        continue  # quarantined batch: the retry skips it
                    obs.profiler.before_step(step + 1)
                    if self.chaos.take("oom", step + 1):
                        # RESOURCE_EXHAUSTED-shaped so the memprof
                        # tripwire (train()'s except hook) fires exactly
                        # like a real XLA OOM: postmortem bundle, then
                        # the raise propagates
                        raise RuntimeError(
                            "RESOURCE_EXHAUSTED: chaos-injected out of "
                            f"memory before step {step + 1}"
                        )
                    if self.chaos.take("nan_grad", step + 1):
                        # chaos (or the legacy test hook): corrupt one
                        # param element (lazy device op — the NaN surfaces
                        # in this step's in-graph numerics, nowhere on the
                        # host)
                        flat, treedef = jax.tree.flatten(self.state.params)
                        flat[0] = flat[0].at[(0,) * flat[0].ndim].set(float("nan"))
                        self.state = self.state.replace(
                            params=jax.tree.unflatten(treedef, flat)
                        )
                    with obs.host_span():
                        # host bookkeeping charged to the budget account's
                        # host_overhead component (the fingerprint's crc32
                        # is the loop's main non-span host cost)
                        fingerprint = (
                            batch_fingerprint(
                                batch,
                                epoch=epoch,
                                epoch_step=pos - 1,
                            )
                            if obs.recorder is not None
                            else None
                        )
                    with obs.step_span():
                        gb = put_batch(batch, self.mesh, sequence_sharded=self.sequence_sharded)
                        # set-up's last span: trace, lowering and compile or load are
                        # synchronous inside the first call; nothing waits on the device
                        with _NOT_FIRST if self._stepped else setup.span("first_step"):
                            if self.use_dropout:
                                self._rng, sub = jax.random.split(self._rng)
                                self.state, metrics = self.train_step(self.state, gb, sub)
                            else:
                                self.state, metrics = self.train_step(self.state, gb)
                    if not self._stepped:
                        self._stepped = True
                        setup.ready("train")
                    step += 1
                    self._last_step = step
                    last_metrics = metrics
                    tokens = self._batch_tokens(batch) * jax.process_count()
                    # budget layer: at the log cadence ONLY, time the
                    # device-queue drain before the logger's fetch — the
                    # measured block is the un-overlapped device tail
                    # (step_budget's device_busy); off-cadence this is two
                    # comparisons and returns
                    obs.budget_probe(step, metrics["loss"])
                    # pass DEVICE scalars: converting here (float(...)) would
                    # block on the step every iteration and serialize JAX's
                    # async dispatch — the logger converts only on emit (the
                    # device_sync span times exactly that cadenced readback)
                    with obs.sync_span():
                        logger.step(
                            step,
                            metrics["loss"],
                            lr=metrics["learning_rate"],
                            tokens=tokens,
                            epoch=epoch,
                        )
                    # per-step obs bookkeeping: step-time ring, profiler
                    # stop, flight-recorder append, cadenced heartbeat +
                    # health check + window summary — before
                    # checkpoint/eval so their wall time rides their own
                    # spans, not this step's duration
                    action = obs.on_step(step, epoch, metrics, fingerprint)
                    if action in ("halt", "checkpoint"):
                        # agreed across hosts inside the health cadence
                        # (same allgather discipline as preemption) — every
                        # process takes this branch at the same step
                        self._anomaly_action = action
                        break
                    if action == "rewind":
                        # agreed like halt/checkpoint; the escalation
                        # (rewind / skip-batch / halt) derives only from
                        # pod-agreed inputs, so every process computes the
                        # same cursor (or the same halt)
                        rewind_cursor = self._handle_rewind(step, epoch, pos)
                        break
                    # cadenced optimizer-apply wall sample (budget layer:
                    # optimizer_apply_ms in the step_budget account) —
                    # runs AFTER the window closed, alongside ckpt/eval,
                    # so mark_step_start below excludes its wall from the
                    # next step's duration like theirs
                    obs.optimizer_probe(step, self._optimizer_probe_output)
                    if self.checkpointer.should_save(step):
                        with obs.checkpoint_span():
                            self._save_checkpoint(step, epoch=epoch, pos=pos)
                    if cfg.evaluation_steps > 0 and step % cfg.evaluation_steps == 0:
                        with obs.eval_span():
                            last_eval = self.evaluate(epoch, step=step)
                    # re-anchor the step clock: checkpoint/eval time is on
                    # their own spans and must not inflate the NEXT step's
                    # ring-buffer duration (false straggler flags)
                    obs.spans.mark_step_start()
                    if self.chaos.take("sigterm", step):
                        # chaos: a real signal through the real handler —
                        # the graceful-preemption path, not a shortcut
                        import signal as _signal

                        os.kill(os.getpid(), _signal.SIGTERM)
                    if self.chaos.take("host_loss", step):
                        # chaos: the agreed topology-change signal — the
                        # deterministic schedule raises it on every rank
                        # at the same step; _check_topology's allgather
                        # is the same belt the preemption flag wears
                        self._host_lost = True
                    if self._check_topology(step):
                        topology_cursor = self._handle_topology_change(
                            step, epoch, pos
                        )
                        break
                    if self._check_preemption(step):
                        self._preempted = True  # agreed across hosts
                        break
            finally:
                # stop the producer thread even when the loop body raises
                if isinstance(epoch_batches, Prefetcher):
                    epoch_batches.close()
                    # the per-run "is the input pipeline on the critical
                    # path?" answer (host counters, once per epoch): a
                    # consumer_wait_s near the first batch's assembly time
                    # means the thread hid everything (device-bound loop —
                    # BENCH_r05's prefetch2 ≈ prefetch0); wait growing with
                    # items means the producer cannot keep up
                    s = epoch_batches.stats()
                    log_json({
                        "event": "prefetch_stats",
                        "epoch": epoch,
                        "depth": cfg.prefetch_batches,
                        "items": s["items"],
                        "consumer_wait_s": round(s["consumer_wait_s"], 4),
                    })
            if rewind_cursor is not None:
                # resume the loop at the restored (epoch, pos, step) —
                # same-process, no recompilation, no weight reload; the
                # replay re-runs the surviving steps bit-identically and
                # skips the quarantined batch
                epoch, pos, step = rewind_cursor
                self._last_step = step
                obs.spans.mark_step_start()
                continue
            if topology_cursor is not None:
                # resume on the NEW mesh at the resharded checkpoint's
                # cursor: the epoch re-enters at the top of this loop, so
                # the batch plan is re-derived from the rebuilt iterator
                # (same global batch, new per-host slice) and the next
                # step dispatch compiles the rebuilt program
                epoch, pos, step = topology_cursor
                self._last_step = step
                obs.spans.mark_step_start()
                continue
            # Epoch boundary: a SIGTERM that landed between sync steps may
            # have set only the LOCAL flag (the cadence check above skipped
            # it) — acting on it here un-agreed would desynchronize the
            # pod (this host saves/exits while peers enter eval's
            # collectives).  Every host reaches this point at the same
            # step, so an unconditional agreement round is collectively
            # safe; mid-epoch agreed breaks re-agree here (still true).
            if jax.process_count() > 1:  # pod-agreed: pod-uniform guard; the branch body IS the agreement (_preemption_agreed)
                self._preempted = self._preemption_agreed()
            if self._preempted or self._anomaly_action is not None:
                break
            # epoch boundary: emit the partial metric window (the fix for
            # the lost-final-window cadence bug) before the eval resets
            # the wall clocks
            logger.flush(step, epoch=epoch)
            with obs.eval_span():
                # per-epoch eval, reference parity
                last_eval = self.evaluate(epoch, step=step)
            epoch += 1
            pos = 0
        logger.flush(step, epoch=report_epoch)
        # close any open trace window (flushed, not lost) and emit the
        # final obs window (plus the final partial-window health check)
        final_action = obs.finalize(
            step, report_epoch, sync_leaf=last_metrics["loss"] if last_metrics else None
        )
        if self._anomaly_action is None and final_action in (
            "halt", "checkpoint", "rewind"
        ):
            # a rewind agreed in the FINAL partial window has no loop left
            # to replay: degrade to the checkpoint policy (preserve the
            # evidence, stop with the anomaly marker) — never fall through
            # to save_final() exporting possibly-poisoned params as a
            # successful run
            self._anomaly_action = (
                "checkpoint" if final_action == "rewind" else final_action
            )
        if self._anomaly_action is not None:
            wall = time.perf_counter() - t0
            if self._anomaly_action == "checkpoint":
                # a RESUMABLE checkpoint of the (possibly already
                # poisoned) state: post-mortem work restores it next to
                # the flight-recorder bundle — resuming a diverged run
                # from here is the operator's explicit call
                self._save_checkpoint(step, epoch=epoch, pos=pos, force=True)
                self.checkpointer.wait()
            log_json({
                "event": "anomaly_stop", "step": step,
                "policy": self._anomaly_action, "wall_seconds": wall,
            })
            return {
                "steps": step, "wall_seconds": wall, "final_eval": last_eval,
                "anomaly": self._anomaly_action,
            }
        if self._preempted:
            # the last steps' evidence first (the bundle is what a
            # post-mortem of the preempted run reads)...
            if obs.recorder is not None:
                obs.recorder.dump(
                    self.cfg.output_dir, reason="preemption", step=step
                )
            # ...then save where we stopped and get out; resume restarts
            # from here (cursor + quarantine ride the recovery sidecar)
            self._save_checkpoint(step, epoch=epoch, pos=pos, force=True)
            self.checkpointer.wait()
            wall = time.perf_counter() - t0
            log_json({"event": "preempted", "step": step, "wall_seconds": wall})
            return {
                "steps": step, "wall_seconds": wall, "final_eval": last_eval,
                "preempted": True,
            }
        self._save_checkpoint(self.total_steps, epoch=epoch, pos=pos, force=True)
        self.checkpointer.wait()
        self.save_final()
        wall = time.perf_counter() - t0
        log_json({"event": "done", "steps": step, "wall_seconds": wall,
                  "late_compiles": setup.late_compiles()})
        return {"steps": step, "wall_seconds": wall, "final_eval": last_eval}

    def save_final(self) -> None:
        """Final artifact: an HF-format checkpoint (``config.json`` +
        ``model.safetensors``) — parity with the reference's
        ``model.save_pretrained(output_dir)`` (reference helpers.py:13), so
        the trained model loads in transformers, back into this framework
        (``load_model(out_dir)``), or any downstream HF consumer — plus the
        TrainConfig (``train_config.json``) and Valohai sidecars."""
        from distributed_llms_example_tpu.models.export import save_hf_checkpoint

        out = os.path.join(self.cfg.output_dir, "model")
        final_params = self.state.params
        if self.pipelined:
            # export in the standard per-layer layout so the artifact loads
            # anywhere (eval, conversion, non-pipelined resume), gathering
            # each layer STRAIGHT to host as it is unstacked — on a
            # pure-pipeline mesh (fsdp=tensor=1) any device-side unstack
            # would replicate the whole model; this caps HBM at the
            # training footprint plus one layer
            from distributed_llms_example_tpu.parallel.pipeline import (
                unstack_for_family_to_host,
            )

            final_params = unstack_for_family_to_host(
                self.loaded.family, final_params, writer_only=True,
                row_order=self._storage_row_order,
            )
        else:
            # multi-host shards live on other hosts' devices; gather each
            # leaf to host, kept only on the writing process — a whole-tree
            # allgather would materialize the full fp32 model in EVERY
            # host's RAM simultaneously (~27 GB/host for llama-2-7b) when
            # only process 0 writes
            from distributed_llms_example_tpu.parallel.pipeline import gather_tree_to_host

            final_params = gather_tree_to_host(final_params, writer_only=True)
        if jax.process_index() == 0:  # pod-agreed: p0-only LOCAL export; gather_tree_to_host above ran on every rank
            os.makedirs(out, exist_ok=True)
            save_hf_checkpoint(out, self.loaded.family, self.config, final_params)
            with open(os.path.join(out, "train_config.json"), "w") as f:
                f.write(self.cfg.to_json())
            save_valohai_metadata(out)
