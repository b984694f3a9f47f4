"""Configuration for the framework.

The reference exposes exactly six CLI parameters, identical across its three
entry points (reference train-torchrun.py:182-188, train-accelerator.py:319-325,
train-task.py:410-416): ``model-ckpt``, ``output-dir``, ``batch-size``,
``num-epochs``, ``warmup-steps``, ``evaluation-steps``.  Two of them are dead
in the reference (``batch-size`` is hardcoded away in train-accelerator.py:169
and train-task.py:180; ``warmup-steps`` is overridden to 1 in
train-accelerator.py:204) — here every flag is honored for real.

On top of those six we add the knobs a TPU SPMD framework actually needs:
mesh shape, precision policy, gradient accumulation, checkpointing cadence,
and sequence lengths (the reference hardcodes 1024/128,
train-accelerator.py:115-127).
"""

from __future__ import annotations

import argparse
import dataclasses
import difflib
import json
from typing import Any

# The canonical mesh axis names, in physical-locality order (tensor is the
# innermost / fastest-varying axis; stage is outermost so pipeline hops can
# cross DCN).  Lives here — not in core/mesh.py — so axis-name validation
# (parse_mesh_arg, the sharding lint) never needs jax importable; mesh.py
# re-exports it for the device-mesh construction itself.
AXES: tuple[str, ...] = ("stage", "data", "fsdp", "expert", "sequence", "tensor")


def unknown_axis_error(name: str) -> ValueError:
    """A typo'd mesh axis must name itself and its likely intent — the
    alternative today is an opaque KeyError deep inside jax once the bad
    name reaches a PartitionSpec."""
    hint = difflib.get_close_matches(name, AXES, n=1)
    did_you_mean = f" (did you mean {hint[0]!r}?)" if hint else ""
    return ValueError(
        f"unknown mesh axis {name!r}{did_you_mean}; valid axes: {', '.join(AXES)}"
    )


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Logical device mesh shape.

    Axis semantics (order is physical-locality order; ``tensor`` is the
    innermost / fastest-varying axis so tensor-parallel collectives ride the
    shortest ICI links, and ``stage`` is outermost so pipeline hops — the
    least latency-sensitive traffic — can cross DCN between slices):

    - ``stage``:    pipeline (GPipe-style) model parallelism — decoder
                    layers split into stages, microbatches streamed through
                    (parallel/pipeline.py)
    - ``data``:     pure data parallelism (batch sharding, params replicated)
    - ``fsdp``:     data parallelism with parameters/optimizer sharded
                    (ZeRO-3 equivalent; batch is also sharded over this axis)
    - ``expert``:   MoE expert parallelism (stacked expert weights shard
                    their leading E dim here; batch is also sharded over
                    this axis, and GSPMD lowers dispatch/combine to the
                    expert all-to-all) — independent of ``tensor`` so
                    expert count and megatron splits scale separately
    - ``sequence``: sequence/context parallelism (activations sharded over
                    the length dimension; ring attention)
    - ``tensor``:   tensor (megatron-style) model parallelism

    A value of -1 means "absorb all remaining devices" (at most one axis).
    """

    data: int = -1
    fsdp: int = 1
    sequence: int = 1
    tensor: int = 1
    stage: int = 1
    expert: int = 1

    def axis_sizes(self) -> dict[str, int]:
        return {
            "stage": self.stage,
            "data": self.data,
            "fsdp": self.fsdp,
            "expert": self.expert,
            "sequence": self.sequence,
            "tensor": self.tensor,
        }


@dataclasses.dataclass(frozen=True)
class CheckpointConfig:
    """Checkpoint/resume policy.

    The reference saves exactly once, at the end of training
    (train-accelerator.py:277-280) and has no resume path (SURVEY.md §5);
    periodic save + resume is an intentional capability add.
    """

    save_every_steps: int = 0  # 0 = only at end of training
    keep: int = 3
    resume: bool = True  # resume from latest checkpoint in output_dir if present
    async_save: bool = True


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    # --- the reference's six parameters (names + defaults from valohai.yaml:8-20) ---
    model_ckpt: str = "t5-small"
    output_dir: str = "/tmp/dllm-tpu-out"
    batch_size: int = 8  # GLOBAL batch size (split across data×fsdp×sequence hosts)
    num_epochs: int = 1
    warmup_steps: int = 500
    evaluation_steps: int = 500

    # --- optimizer (reference: AdamW lr 5e-5, linear schedule, weight_decay
    #     nominally 0.01 in variant A, train-torchrun.py:120) ---
    learning_rate: float = 5e-5
    weight_decay: float = 0.01
    max_grad_norm: float = 1.0
    grad_accum_steps: int = 1  # reference variant A uses 16 (train-torchrun.py:126)
    label_smoothing: float = 0.0

    # --- data (reference hardcodes src 1024 / tgt 128, train-accelerator.py:115-127) ---
    max_source_length: int = 1024
    max_target_length: int = 128
    source_column: str = "dialogue"  # with "article" fallback, per reference dual schema
    target_column: str = "summary"  # with "highlights" fallback
    shuffle_seed: int = 1234  # reference DataPartitioner seed (train-task.py:46)
    pad_to_multiple: int = 128  # TPU-idiomatic version of pad_to_multiple_of=8
    prefetch_batches: int = 2  # host batches assembled ahead of the device; 0 = off

    # --- precision / memory ---
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    # "" = model default; else "auto" | "flash" | "ring" | "xla" (ops/mha.py)
    attention_impl: str = ""
    # fuse LM-head + CE into a vocab-chunked scan (causal families; no
    # (tokens, vocab) fp32 logits in HBM — ops/blockwise_ce.py).  Meant
    # for data/fsdp meshes; under tensor parallelism keep it off.
    fused_ce: bool = False
    # PRNG implementation for the in-step dropout stream: "auto" (default
    # — resolves to "rbg" on TPU backends and "threefry" elsewhere at
    # trainer startup; trainer.set_prng_impl owns the resolution and the
    # resolved value is logged + stamped into BENCH json so runs stay
    # comparable), "threefry" (counter-based, bit-reproducible across
    # backends) or "rbg" (TPU hardware RNG; much cheaper mask generation
    # when dropout sits on the critical path, different — still
    # deterministic — bit stream)
    prng_impl: str = "auto"
    # dropout implementation (ops/fused_dropout.py): "auto" (default —
    # fused Pallas kernel with in-kernel RNG + seed-recompute backward on
    # TPU, XLA bernoulli elsewhere), "fused" or "xla" to force.  "fused"
    # trades bit-reproducibility with the XLA mask stream for the removal
    # of threefry mask generation AND the mask's HBM round-trips
    dropout_impl: str = "auto"
    # optimizer-apply implementation (ops/fused_optim.py): "auto" (default
    # — fused Pallas clip+AdamW kernel on TPU: one in-place pass per
    # leaf-shard with the health partial sums riding the same pass; the
    # optax chain elsewhere), "fused" or "xla" to force.  The impls run
    # the identical op sequence — equal up to XLA float contraction (a
    # few ulp on rare elements; test-pinned) — and the opt-state pytree
    # layout never changes, so checkpoints roam freely between impls.
    # Pipelined (stage>1) runs always use xla; --optim-impl fused there
    # is a composition-matrix error.
    optim_impl: str = "auto"
    # gradient-collective compression (ops/quant_collectives.py): "off"
    # (default — the compiled step is bit-identical to the uncompressed
    # path) or "int8" — the cross-replica (data-axis) gradient reduction
    # runs as block-int8 with stochastic rounding, int-safe integer
    # partial sums on an s8 wire (~4x fewer gradient wire bytes, per
    # EQuARX arXiv:2506.17615), and a per-worker fp32 error-feedback
    # tree carried in TrainState (checkpointed; resume from an
    # uncompressed checkpoint zero-fills it).  Composes with grad
    # accumulation; stage>1 pipelines and sequence parallelism are
    # composition-matrix errors.
    grad_compression: str = "off"
    remat: bool = False  # jax.checkpoint the transformer blocks
    remat_policy: str = "full"  # "full" | "dots" (utils/remat.py)
    # microbatches per pipeline tick when mesh stage>1 (0 → stage count);
    # bubble fraction is (stages-1)/(microbatches+stages-1)
    pipeline_microbatches: int = 0
    # "gpipe": forward scan + autodiff backward, O(microbatches) activation
    # memory per stage.  "1f1b": fused schedule interleaving backward with
    # forward microbatches, O(stages) activation memory — the schedule that
    # makes large microbatch counts affordable (decoder-only families).
    # "interleaved": 1f1b with pipeline_virtual_stages non-contiguous layer
    # chunks per device (parallel/interleave.py) — shorter schedule at
    # stage >= 4, ~v× more buffered chunk inputs (decoder-only families).
    # NOTE: checkpoints store the stacked blocks in the schedule's storage
    # order; resume with the same schedule/virtual-stages flags.
    pipeline_schedule: str = "gpipe"
    pipeline_virtual_stages: int = 2  # chunks per device (interleaved only)
    # MoE expert capacity override for fine-tuning (None = keep the model's
    # own setting; HF-converted Mixtral defaults to no-drop, which is exact
    # but memory-hungry — 1.25 restores the capacity trade for training)
    moe_capacity_factor: float | None = None
    # Under stage>1, generation-based ROUGE eval unstacks the blocks onto
    # the FSDP/TP shardings (params/(fsdp·tensor) per device).  On a
    # pure-stage mesh (fsdp×tensor == 1) that would mean a fully replicated
    # whole-model copy per device, so the Trainer auto-skips ROUGE there
    # regardless of this flag; the stage-sharded teacher-forced val_loss
    # (computed through the pipeline itself, no unstacking) is always
    # reported.  False skips pipelined ROUGE on every mesh.
    pipeline_eval_rouge: bool = True

    # --- eval/generation (reference live path: beams=2, max_length=128,
    #     train-accelerator.py:239-242) ---
    num_beams: int = 2
    eval_max_new_tokens: int = 128
    eval_batch_size: int = 0  # 0 = use batch_size

    # --- logging (reference cadences: 10/300/100 steps; we default to 100) ---
    log_every_steps: int = 100

    # --- observability (obs/): the layered telemetry stack ---
    # "stdout": spans/heartbeat events ride the Valohai stdout channel;
    # "jsonl": additionally tee schema-versioned records into
    # <output_dir>/obs/metrics-p{process}.jsonl and turn the gauge compile
    # on (obs_gauges=auto); "off": no obs instrumentation (the stdout
    # metric channel itself never turns off — it is the platform contract)
    obs: str = "stdout"
    # static-gauge AOT compile (MFU FLOPs + collective-traffic account):
    # "auto" = only under --obs jsonl; "on"/"off" force it
    obs_gauges: str = "auto"
    # heartbeat cadence in steps (0 = off).  Multi-host: every process
    # probes at the same global step, process 0 reports skew/laggards
    obs_heartbeat_steps: int = 0
    # persistent-laggard classification (obs/health.py LaggardStreaks):
    # a rank named laggard this many CONSECUTIVE heartbeats becomes a
    # pod-agreed host_loss_suspect event — organic host-loss DETECTION
    # only (report row; the --on-host-loss policy is unchanged).  0 =
    # classification off, same convention as the heartbeat cadence
    obs_heartbeat_suspect_beats: int = 3
    # step-time budget accounting (obs/budget.py): each logging window's
    # wall time decomposed into data_wait / dispatch / device_busy /
    # sync_block / host_overhead (additive — the unattributed remainder
    # is test-pinned under 5%) with a dispatch_efficiency gauge and the
    # off-cadence host-transfer tripwire, emitted as step_budget events.
    # "auto" = on whenever --obs is not off; under --obs jsonl the span
    # instances are also captured for the Perfetto trace export
    # (obs.report --trace).  Host-clock arithmetic only; the single
    # device interaction is one timed block at the log cadence.
    obs_budget: str = "auto"
    # per-chip HBM ceiling in GiB for the bucketed memory account
    # (obs/memprof.py): the static account's fit verdict, the report's
    # --max-peak-hbm-frac / --min-hbm-headroom-gib denominators, and the
    # serving capacity gauges all divide by this one number (v5e = 16)
    hbm_budget_gib: float = 16.0

    # --- training health (obs/health.py + in-graph numerics in train/step.py) ---
    # "on": the compiled step also returns param norm, per-bucket update
    # ratios and non-finite grad counts (computed in-graph, zero extra
    # device syncs) and the anomaly watchdog consumes them at the log
    # cadence; "auto" = on under --obs jsonl; "off" = neither
    health: str = "auto"
    # what the run does when an anomaly is agreed across hosts:
    # "warn" logs obs_anomaly and continues; "halt" stops the run (no
    # extra save); "checkpoint" force-saves a resumable checkpoint, dumps
    # the flight recorder, and stops; "rewind" recovers IN-PROCESS —
    # restore the last verified checkpoint, quarantine the anomaly
    # step's batch by fingerprint so the retry skips it, escalation
    # rewind → skip-batch → halt (train/recovery.py).  Requires periodic
    # checkpointing (--save-every-steps) and the flight recorder.
    on_anomaly: str = "warn"
    # bounded in-process rewind budget for --on-anomaly rewind; once
    # exhausted the escalation continues skip-batch → halt
    max_rewinds: int = 2
    # topology-change policy (ISSUE 14): on an agreed host-loss signal
    # ("--chaos host_loss@K", or a pod-size change at resume), "reshard"
    # tears down collectives, re-initializes jax.distributed on the
    # surviving slice, rebuilds mesh/shardings/train-step, and restores
    # the newest verified checkpoint through the resharding path;
    # "halt" checkpoints the evidence and stops (restart-based recovery)
    on_host_loss: str = "reshard"
    # flight-recorder ring capacity in steps (0 = off): the last N steps'
    # metrics + batch fingerprints, dumped on anomaly/SIGTERM/crash
    recorder_steps: int = 256
    # loss-spike threshold: loss above the EWMA by this many mean
    # absolute deviations trips "loss_spike"
    health_loss_spike_factor: float = 4.0
    # grad-norm explosion threshold: grad_norm above this multiple of its
    # EWMA trips "grad_explosion"
    health_grad_norm_factor: float = 10.0
    # finite steps the EWMAs absorb before spike/explosion detection arms
    # (the NaN/Inf tripwire is always armed)
    health_warmup_steps: int = 20

    # --- chaos (obs/chaos.py): deterministic fault injection, e.g.
    #     "nan_grad@120,ckpt_corrupt@2,data_error@300,sigterm@240" —
    #     every firing is logged as a chaos_injection event so obs.report
    #     separates injected faults from organic ones ("" = off) ---
    chaos: str = ""

    # --- profiling (SURVEY.md §7 step 8: jax.profiler hooks; the reference's
    #     only "profiling" is an nvidia-smi report at startup) ---
    profile_dir: str = ""  # "" = profiling off; else write a trace here
    # legacy count ("3": trace 3 steps after the first compiled one; needs
    # profile_dir) or an absolute inclusive step window ("100:105", trace
    # dir defaults under output_dir) — obs/profile.py parses both
    profile_steps: int | str = 3
    # trigger file polled at step cadence for on-demand capture;
    # "" = <output_dir>/obs/profile.trigger when obs is enabled
    profile_trigger: str = ""
    # arm the trigger automatically when the health watchdog agrees an
    # anomaly: the next steps are profiled, so the post-mortem carries a
    # device timeline (device_account) next to the flight recorder
    profile_on_anomaly: bool = False

    # --- nested ---
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    checkpoint: CheckpointConfig = dataclasses.field(default_factory=CheckpointConfig)

    # --- tokenizer: path to HF tokenizer files, or "byte" for the built-in
    #     network-free byte-level tokenizer ---
    tokenizer: str = ""  # "" = try model_ckpt as a local path, else byte

    def replace(self, **kw: Any) -> "TrainConfig":
        return dataclasses.replace(self, **kw)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=2, sort_keys=True)


# Single source of defaults for the CLI layer: the dataclass itself.
# remat policy names; utils/remat.py asserts its POLICIES registry matches
# (kept here so config stays importable without jax/flax)
REMAT_POLICIES = ("full", "dots")

# Speculative-decode draft cap: the verify step scores spec_tokens + 1
# positions in ONE flash_decode call, and the kernel's q block holds at
# most 8 rows (ops/flash_attention.py MAX_DECODE_Q_ROWS) — so at most 7
# drafts ride each round.  Kept here (jax-free) so the CLI layer can
# validate --spec-tokens without importing the ops stack.
SPEC_MAX_DRAFT_TOKENS = 7

_D = TrainConfig()


def add_reference_args(p: argparse.ArgumentParser) -> None:
    """The six flags of the reference CLIs (train-torchrun.py:182-188), with
    the same names surfaced by valohai.yaml:8-20."""
    p.add_argument("--model-ckpt", type=str, default=_D.model_ckpt)
    p.add_argument("--output-dir", type=str, default=_D.output_dir)
    p.add_argument("--batch-size", type=int, default=_D.batch_size)
    p.add_argument("--num-epochs", type=int, default=_D.num_epochs)
    p.add_argument("--warmup-steps", type=int, default=_D.warmup_steps)
    p.add_argument("--evaluation-steps", type=int, default=_D.evaluation_steps)


def add_tpu_args(p: argparse.ArgumentParser) -> None:
    p.add_argument("--learning-rate", type=float, default=_D.learning_rate)
    p.add_argument("--weight-decay", type=float, default=_D.weight_decay)
    p.add_argument("--max-grad-norm", type=float, default=_D.max_grad_norm)
    p.add_argument("--label-smoothing", type=float, default=_D.label_smoothing)
    p.add_argument(
        "--grad-accum-steps",
        # the reference's parameter name (train-torchrun.py:126), as
        # valohai.yaml passes it — both spellings land on grad_accum_steps
        "--gradient-accumulation-steps",
        "--gradient_accumulation_steps",
        dest="grad_accum_steps",
        type=int, default=_D.grad_accum_steps,
        help="microbatches accumulated INSIDE each compiled step (a "
             "lax.scan with fp32 accumulators sharded like the params): "
             "--batch-size stays the effective optimizer batch and must "
             "divide evenly; one optimizer apply per step regardless of N. "
             "The reference's gradient_accumulation_steps "
             "(train-torchrun.py:126). Composes with data/fsdp/tensor "
             "meshes; stage>1 pipelines microbatch via "
             "--pipeline-microbatches instead",
    )
    p.add_argument("--shuffle-seed", type=int, default=_D.shuffle_seed)
    p.add_argument("--pad-to-multiple", type=int, default=_D.pad_to_multiple)
    p.add_argument("--max-source-length", type=int, default=_D.max_source_length)
    p.add_argument("--max-target-length", type=int, default=_D.max_target_length)
    p.add_argument("--param-dtype", type=str, default=_D.param_dtype)
    p.add_argument("--compute-dtype", type=str, default=_D.compute_dtype)
    p.add_argument("--remat", action="store_true")
    p.add_argument(
        "--attention-impl", type=str, default=_D.attention_impl,
        choices=("", "auto", "flash", "ring", "xla"),
        help="attention path override; empty = model default (auto)",
    )
    p.add_argument(
        "--fused-ce", action="store_true",
        help="vocab-chunked fused LM-head + cross-entropy (causal families, "
             "data/fsdp meshes; logits never materialize)",
    )
    p.add_argument(
        "--prng-impl", type=str, default=_D.prng_impl,
        choices=("auto", "threefry", "rbg"),
        help="dropout PRNG: auto (rbg on TPU, threefry elsewhere — the "
             "resolved impl is logged), threefry (bit-reproducible) or rbg "
             "(TPU hardware RNG, faster)",
    )
    p.add_argument(
        "--dropout-impl", type=str, default=_D.dropout_impl,
        choices=("auto", "fused", "xla"),
        help="dropout path: auto (fused Pallas kernel on TPU — in-kernel "
             "RNG, no mask in HBM, seed-recompute backward; XLA elsewhere), "
             "fused or xla to force",
    )
    p.add_argument(
        "--optim-impl", type=str, default=_D.optim_impl,
        choices=("auto", "fused", "xla"),
        help="optimizer apply: auto (fused Pallas clip+AdamW kernel on TPU "
             "— one in-place pass per leaf-shard, health stats from the "
             "same pass; optax chain elsewhere), fused or xla to force. "
             "Same op sequence either way (equal up to XLA float "
             "contraction); checkpoints roam between impls",
    )
    p.add_argument(
        "--grad-compression", type=str, default=_D.grad_compression,
        choices=("off", "int8"),
        help="gradient-collective compression: off (bit-identical to the "
             "uncompressed step) or int8 — the cross-replica gradient "
             "reduction rides an s8 wire (block quantization, stochastic "
             "rounding, integer partial sums) with a checkpointed "
             "error-feedback tree; ~4x fewer gradient wire bytes "
             "(ops/quant_collectives.py)",
    )
    p.add_argument("--remat-policy", type=str, default=_D.remat_policy, choices=REMAT_POLICIES)
    p.add_argument("--pipeline-microbatches", type=int, default=_D.pipeline_microbatches)
    p.add_argument(
        "--pipeline-schedule", type=str, default=_D.pipeline_schedule,
        choices=("gpipe", "1f1b", "interleaved"),
        help="stage>1 schedule: gpipe (O(M) activation memory), 1f1b (O(S)), "
             "or interleaved (1f1b with virtual layer chunks per device)",
    )
    p.add_argument(
        "--pipeline-virtual-stages", type=int, default=_D.pipeline_virtual_stages,
        help="layer chunks per device for --pipeline-schedule interleaved",
    )
    p.add_argument("--moe-capacity-factor", type=float, default=_D.moe_capacity_factor)
    p.add_argument(
        "--no-pipeline-eval-rouge", action="store_true",
        help="under stage>1, skip the unstacked generation eval (use for models too big to replicate)",
    )
    p.add_argument("--num-beams", type=int, default=_D.num_beams)
    p.add_argument("--eval-max-new-tokens", type=int, default=_D.eval_max_new_tokens)
    p.add_argument("--eval-batch-size", type=int, default=_D.eval_batch_size)
    p.add_argument("--log-every-steps", type=int, default=_D.log_every_steps)
    p.add_argument("--tokenizer", type=str, default=_D.tokenizer)
    p.add_argument("--prefetch-batches", type=int, default=_D.prefetch_batches)
    p.add_argument("--profile-dir", type=str, default=_D.profile_dir)
    p.add_argument(
        "--profile-steps", type=str, default=str(_D.profile_steps),
        help="jax.profiler capture: step count ('3', needs --profile-dir) "
             "or absolute inclusive window ('100:105')",
    )
    p.add_argument(
        "--profile-trigger", type=str, default=_D.profile_trigger,
        help="trigger-file path polled every step for on-demand capture "
             "(default: <output-dir>/obs/profile.trigger when --obs is on)",
    )
    p.add_argument(
        "--profile-on-anomaly", action="store_true",
        default=_D.profile_on_anomaly,
        help="arm the profile trigger automatically when the health "
             "watchdog agrees an anomaly: the following steps are "
             "captured and parsed into a device_account, so the "
             "post-mortem carries a device timeline",
    )
    p.add_argument(
        "--obs", type=str, default=_D.obs, choices=("off", "stdout", "jsonl"),
        help="telemetry (obs/): stdout-only events, + JSONL file under the "
             "output dir, or off (metric stdout always stays on)",
    )
    p.add_argument(
        "--obs-gauges", type=str, default=_D.obs_gauges,
        choices=("auto", "on", "off"),
        help="AOT-compile the train step at startup for MFU FLOPs + the "
             "collective-traffic account (auto = only under --obs jsonl)",
    )
    p.add_argument("--obs-heartbeat-steps", type=int, default=_D.obs_heartbeat_steps)
    p.add_argument(
        "--obs-heartbeat-suspect-beats", type=int,
        default=_D.obs_heartbeat_suspect_beats,
        help="consecutive heartbeats a rank must be named laggard before "
             "the pod-agreed host_loss_suspect event fires (detection + "
             "report row only; --on-host-loss policy unchanged; 0 = off)",
    )
    p.add_argument(
        "--obs-budget", type=str, default=_D.obs_budget,
        choices=("auto", "on", "off"),
        help="step-time budget accounting: per-window wall time decomposed "
             "into data_wait/dispatch/device_busy/sync_block/host_overhead "
             "with a dispatch_efficiency gauge and the off-cadence "
             "host-transfer tripwire (step_budget events; under --obs jsonl "
             "also span capture for obs.report --trace).  auto = on "
             "whenever --obs is not off",
    )
    p.add_argument(
        "--hbm-budget-gib", type=float, default=_D.hbm_budget_gib,
        help="per-chip HBM ceiling in GiB for the bucketed memory account "
             "(obs/memprof.py fit verdict + report memory gates; v5e = 16)",
    )
    p.add_argument(
        "--health", type=str, default=_D.health, choices=("auto", "on", "off"),
        help="in-graph numerics (param norm, per-bucket update ratios, "
             "non-finite counts) + the anomaly watchdog at the log cadence "
             "(auto = on under --obs jsonl)",
    )
    p.add_argument(
        "--on-anomaly", type=str, default=_D.on_anomaly,
        choices=("warn", "halt", "checkpoint", "rewind"),
        help="agreed-anomaly policy: warn and continue, halt the run, "
             "force-save a resumable checkpoint + flight-recorder bundle "
             "and stop, or rewind — restore the last verified checkpoint "
             "in-process, quarantine the poison batch, and retry "
             "(escalation rewind -> skip-batch -> halt; needs "
             "--save-every-steps and the flight recorder)",
    )
    p.add_argument(
        "--max-rewinds", type=int, default=_D.max_rewinds,
        help="in-process rewind budget for --on-anomaly rewind; exhausted "
             "budget escalates skip-batch -> halt",
    )
    p.add_argument(
        "--on-host-loss", type=str, default=_D.on_host_loss,
        choices=("reshard", "halt"),
        help="agreed topology-change policy: reshard — tear down "
             "collectives, re-init jax.distributed on the surviving "
             "slice, rebuild mesh/shardings/train-step and restore the "
             "newest verified checkpoint through the resharding path "
             "(needs --save-every-steps); halt — checkpoint the evidence "
             "and stop, leaving recovery to a resumed run on the new "
             "slice (the resume path reshards either way)",
    )
    p.add_argument(
        "--chaos", type=str, default=_D.chaos,
        help="deterministic fault injection: comma list of kind@tick with "
             "kind in nan_grad/ckpt_corrupt/data_error/sigterm/host_loss/"
             "oom (tick = global step; for ckpt_corrupt the Nth checkpoint "
             "save), e.g. 'nan_grad@120,ckpt_corrupt@2'; every firing is "
             "logged as a chaos_injection event",
    )
    p.add_argument(
        "--recorder-steps", type=int, default=_D.recorder_steps,
        help="flight-recorder ring capacity in steps (0 = off); dumped to "
             "<output-dir>/obs/flight-recorder-p*.json on anomaly/SIGTERM/crash",
    )
    p.add_argument(
        "--health-loss-spike-factor", type=float,
        default=_D.health_loss_spike_factor,
    )
    p.add_argument(
        "--health-grad-norm-factor", type=float,
        default=_D.health_grad_norm_factor,
    )
    p.add_argument(
        "--health-warmup-steps", type=int, default=_D.health_warmup_steps,
    )
    p.add_argument("--save-every-steps", type=int, default=_D.checkpoint.save_every_steps)
    p.add_argument("--no-resume", action="store_true")
    p.add_argument("--mesh", type=str, default="data=-1", help="comma list axis=size, e.g. data=2,fsdp=4,tensor=1")
    # multi-host rendezvous (the triple consumed at reference train-task.py:421-425)
    p.add_argument("--coordinator-address", type=str, default="")
    p.add_argument("--num-processes", type=int, default=0)
    p.add_argument("--process-id", type=int, default=-1)


def parse_mesh_arg(spec: str) -> MeshConfig:
    """Parse ``"data=2,fsdp=4"`` into a MeshConfig."""
    kw: dict[str, int] = {}
    if spec.strip():
        for part in spec.split(","):
            k, _, v = part.partition("=")
            k = k.strip()
            if k not in AXES:
                raise unknown_axis_error(k)
            kw[k] = int(v)
    # MeshConfig defaults data to -1 (wildcard); if the user put the wildcard
    # on a different axis, pin data to 1 so there is exactly one wildcard.
    if "data" not in kw:
        kw["data"] = 1 if -1 in kw.values() else -1
    return MeshConfig(**kw)


def config_from_args(args: argparse.Namespace) -> TrainConfig:
    """Build a TrainConfig from an argparse namespace.

    Only attributes actually present on the namespace are applied, so the
    dataclass remains the single source of defaults (argparse defaults are
    themselves read from the dataclass above).
    """
    present = vars(args)
    fields = {f.name for f in dataclasses.fields(TrainConfig)}
    kw = {k: v for k, v in present.items() if k in fields and k not in ("mesh", "checkpoint")}
    if "mesh" in present:
        kw["mesh"] = parse_mesh_arg(present["mesh"])
    if present.get("no_pipeline_eval_rouge"):
        kw["pipeline_eval_rouge"] = False
    ckpt_kw = {}
    if "save_every_steps" in present:
        ckpt_kw["save_every_steps"] = present["save_every_steps"]
    if "no_resume" in present:
        ckpt_kw["resume"] = not present["no_resume"]
    if ckpt_kw:
        kw["checkpoint"] = CheckpointConfig(**ckpt_kw)
    cfg = TrainConfig(**kw)
    # fail at parse time, not at first compile: the batch/accumulation
    # divisibility is knowable here (the mesh-aware microbatch-vs-shards
    # check runs at Trainer startup, where the device mesh exists)
    if cfg.grad_accum_steps < 1:
        raise ValueError(
            f"--grad-accum-steps must be >= 1, got {cfg.grad_accum_steps}"
        )
    if cfg.batch_size % cfg.grad_accum_steps:
        raise ValueError(
            f"--batch-size {cfg.batch_size} is not divisible by "
            f"--grad-accum-steps {cfg.grad_accum_steps}: batch-size is the "
            "EFFECTIVE optimizer batch; the step cuts it into "
            "grad-accum-steps equal microbatches"
        )
    # rewind recovery has hard prerequisites — surface them at parse time
    # with a fix-it, not as a mid-run halt the first time an anomaly fires
    if cfg.max_rewinds < 0:
        raise ValueError(f"--max-rewinds must be >= 0, got {cfg.max_rewinds}")
    if cfg.on_anomaly == "rewind":
        if cfg.checkpoint.save_every_steps <= 0:
            raise ValueError(
                "--on-anomaly rewind needs periodic checkpointing to rewind "
                "TO: set --save-every-steps N (N bounds the optimizer steps "
                "one recovery can lose)"
            )
        if cfg.recorder_steps <= 0:
            raise ValueError(
                "--on-anomaly rewind quarantines the poison batch via the "
                "flight recorder's fingerprints: set --recorder-steps N "
                "(default 256) instead of 0"
            )
    if cfg.chaos:
        # grammar errors fail here, not at injection time mid-run
        from distributed_llms_example_tpu.obs.chaos import parse_chaos

        schedule = parse_chaos(cfg.chaos)
        if (
            schedule.armed_at("host_loss")
            and cfg.on_host_loss == "reshard"
            and cfg.checkpoint.save_every_steps <= 0
        ):
            raise ValueError(
                "--chaos host_loss@K with --on-host-loss reshard needs a "
                "checkpoint to reshard FROM: set --save-every-steps N "
                "(a lost host's state is gone — topology recovery is a "
                "restore, not a migration)"
            )
    return cfg
