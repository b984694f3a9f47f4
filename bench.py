"""Framework benchmark: seq2seq fine-tune train-step throughput on TPU.

``python bench.py`` runs in the one process that holds the chip (a second
process cannot reach it) and exits non-zero when it produced no result.  On
a device with no entry in the peak table (``obs/gauges.py``) — the CPU — it
refuses to run: a number from there is not a device number.

Output contract: the LAST result line on stdout is the benchmark record —
  {"metric": ..., "value": N, "unit": "tokens/sec/chip", "vs_baseline": N}
The record is re-printed as each add-on measurement lands (headline first,
then enriched with grad-accum/dropout/rbg/trainer fields), so a kill at any
point loses only the not-yet-measured fields — always take the last line.
Add-ons that ``BENCH_CHILD_BUDGET`` skips are named in ``skipped_passes``.

Workload: the reference's headline recipe — bart-large-cnn-class seq2seq
fine-tuning, source 1024 / target 128 (reference train-accelerator.py:115-127),
AdamW + linear schedule — as our SPMD train step (bf16 compute, fp32
params/optimizer, remat) on all locally available chips.  Throughput
counts non-pad source+target tokens per optimizer step.

Baseline: the reference publishes no numbers (BASELINE.md), so
``vs_baseline`` is measured against a documented estimate of its strongest
variant (A: HF Trainer fp32 DDP on modern data-center GPUs):
~6 * n_params FLOPs/token training compute at ~35% utilization of a
312 TFLOP/s bf16 A100 ≈ 4000 tokens/sec/GPU for a 406M-param model.
We report per-chip so the comparison is per-accelerator.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time
from typing import Callable


def _device_peak_flops() -> float:
    """Peak bf16 FLOP/s of the device this process holds, from the one table
    keyed by ``device_kind``; a device that is not in it is an error."""
    import jax

    from distributed_llms_example_tpu.obs.gauges import PEAK_BF16_FLOPS

    dev = jax.devices()[0]
    peak = PEAK_BF16_FLOPS.get(dev.device_kind)
    if peak is None:
        raise SystemExit(
            f"bench.py measures on a chip: this process holds {dev.platform} / "
            f"{dev.device_kind!r}, which has no entry in the peak table "
            f"({sorted(PEAK_BF16_FLOPS)}); a number from here is not a device number"
        )
    return peak


# The reference's documented estimate for its strongest variant
# (BASELINE.md: ~4,000 tok/s per A100-class GPU on bart-large-cnn,
# src 1024 / tgt 128).  NOTE: bench defaults have evolved across rounds
# (round 1: batch 8/chip, remat on; round 2+: batch 16/chip, remat off for
# <1B-param models) — the baseline constant describes the REFERENCE and is
# config-independent, but vs_baseline values in BENCH_r{N}.json files are
# only comparable across rounds when the metric string reports the same
# bench config (it always names batch/remat/attention).
BASELINE_TOKENS_PER_SEC_PER_CHIP = 4000.0


def _flagship():
    import jax

    from distributed_llms_example_tpu.models.registry import load_model

    attention = os.environ.get("BENCH_ATTENTION", "") or None
    if attention not in (None, "auto", "flash", "ring", "xla"):
        # validate up front: a typo'd env var must not read as a model error
        raise SystemExit(f"BENCH_ATTENTION={attention!r}: must be auto/flash/ring/xla")
    name = os.environ.get("BENCH_MODEL", "") or "bart-large-cnn"
    # a model that fails to load is an error, never another model's number
    lm = load_model(name, dtype=jax.numpy.bfloat16, attention_impl=attention)
    # remat trades throughput for activation memory — only worth it when
    # the model might not fit (7B-class).  The 406M flagship at batch 16
    # fits a v5e without it only while dropout is off (PERF.md, PR 22)
    shapes = jax.eval_shape(lambda: lm.init_params(0))
    n_params = sum(int(math.prod(x.shape)) for x in jax.tree.leaves(shapes))
    remat_env = os.environ.get("BENCH_REMAT", "")
    remat = (n_params > 1_000_000_000) if remat_env == "" else remat_env != "0"
    if remat:
        # rebuild just the module with remat on — the already-loaded
        # weights (if any) don't depend on the flag, so no second
        # checkpoint read/convert for the 7B-class models
        import dataclasses

        lm = dataclasses.replace(
            lm,
            module=type(lm.module)(
                lm.config, dtype=jax.numpy.bfloat16, remat=True,
                remat_policy=os.environ.get("BENCH_REMAT_POLICY", "full"),
            ),
        )
    return name, lm, remat


def _trainer_loop_bench(model_name: str, n_chips: int, *, remat: bool,
                        attention: str | None,
                        rbg_ok: Callable[[float], bool] = lambda est: True) -> dict:
    """Measure the REAL Trainer loop (bucketed batching + prefetch +
    logging cadence + put_batch on the critical path), not just the jitted
    step — the round-2 bench only timed synthetic fixed batches, so input-
    pipeline regressions were invisible.  Returns tok/s/chip with the
    prefetcher on (depth 2) and off (0): their gap quantifies how much
    host input work the background thread actually hides.

    Checkpoint/export IO is stubbed out (this measures the training loop,
    not artifact writes), and each timed pass re-runs the SAME Trainer so
    compilation stays out of the window."""
    import tempfile

    import jax
    import numpy as np

    from distributed_llms_example_tpu.core.config import (
        CheckpointConfig,
        MeshConfig,
        TrainConfig,
    )
    from distributed_llms_example_tpu.train.trainer import Trainer

    steps = max(2, int(os.environ.get("BENCH_TRAINER_STEPS", "6")))
    batch = int(os.environ.get("BENCH_BATCH", "16")) * n_chips
    rng = np.random.RandomState(7)

    def text(n_chars: int) -> str:
        # byte tokenizer ≈ 1 token/char: sources fill the 1024 bucket,
        # targets the 128 bucket, mirroring the synthetic workload
        words = []
        total = 0
        while total < n_chars:
            w = "".join(chr(97 + rng.randint(26)) for _ in range(3 + rng.randint(6)))
            words.append(w)
            total += len(w) + 1
        return " ".join(words)[:n_chars]

    records = [{"dialogue": text(1016), "summary": text(120)} for _ in range(batch * steps)]
    # device-time attribution: one PROFILED (untimed) pass captures a
    # 1-step jax.profiler window, parsed into the device_account that is
    # stamped below — the gauges compile supplies the instruction→bucket
    # index and the byte account the bandwidth join needs.  "auto" =
    # accelerators only: the CPU thunk-runtime profiler multiplies a
    # bench-sized (src 1024) step's wall ~20× and overflows the session
    # into an EMPTY trace (measured on this container); the CPU parse
    # path is pinned by tests/test_devprof.py on CLI-sized windows
    # instead.  BENCH_DEVICE_PROFILE=1 forces it anywhere, 0 disables.
    dev_profile_env = os.environ.get("BENCH_DEVICE_PROFILE", "auto")
    dev_profile = dev_profile_env != "0" and (
        dev_profile_env == "1" or jax.default_backend() != "cpu"
    )
    with tempfile.TemporaryDirectory() as tmp:
        cfg = TrainConfig(
            model_ckpt=model_name,
            output_dir=tmp,
            obs_gauges="on" if dev_profile else "auto",
            batch_size=batch,
            num_epochs=1,
            warmup_steps=0,
            evaluation_steps=0,
            learning_rate=5e-5,
            max_source_length=1024,
            max_target_length=128,
            pad_to_multiple=128,
            prefetch_batches=2,
            log_every_steps=steps,
            tokenizer="byte",
            # mirror the synthetic step's BENCH_REMAT / BENCH_ATTENTION
            # overrides so vs_synthetic compares identically-built programs
            remat=remat,
            attention_impl=attention or "",
            # pin the baseline stream: with the new "auto" defaults a TPU
            # trainer would silently start on rbg+fused and the rbg A/B
            # pass below would compare like against like
            prng_impl="threefry",
            dropout_impl="xla",
            mesh=MeshConfig(data=-1),
            checkpoint=CheckpointConfig(save_every_steps=0, resume=False, async_save=False),
        )
        trainer = Trainer(cfg, train_records=records)
        trainer.checkpointer.save = lambda *a, **k: None
        trainer.checkpointer.wait = lambda: None
        trainer.save_final = lambda: None
        tokens = sum(trainer._batch_tokens(b) for b in trainer.batches.epoch(0))

        # capture the span windows each pass emits (data_wait /
        # step_dispatch / device_sync) — BENCH_r05 showed prefetch2 ≈
        # prefetch0 with no way to tell WHY from the artifact; the span
        # totals are the answer (device-bound loop: data_wait ≪
        # step_dispatch at depth 0 already)
        captured_windows: list[dict] = []
        orig_summary = trainer.obs.spans.summary

        def capturing_summary():
            s = orig_summary()
            if s is not None:
                captured_windows.append(s)
            return s

        trainer.obs.spans.summary = capturing_summary

        def pass_budget() -> dict | None:
            """Drain the pass's step_budget accounts (obs/budget.py) into
            one aggregate: the additive component breakdown plus the
            wall-weighted dispatch_efficiency — the same-session A/B
            artifact the ROADMAP's vs_synthetic_step >= 0.95 attack needs
            (which component to shrink, not just that a gap exists)."""
            from distributed_llms_example_tpu.obs.budget import aggregate_accounts

            bud = getattr(trainer.obs, "budget", None)
            if bud is None or not bud.history:
                return None
            accounts = bud.history[:]
            bud.history.clear()
            return aggregate_accounts(accounts)

        def timed_pass() -> float:
            t0 = time.perf_counter()
            trainer.train()
            # force completion: train() can return with steps still in
            # flight (async dispatch), so read a param element back
            _ = jax.device_get(jax.tree.leaves(trainer.state.params)[0].ravel()[0])
            return time.perf_counter() - t0

        def pass_spans() -> dict:
            """Aggregate this pass's captured windows into per-span totals."""
            agg: dict[str, float] = {}
            n_steps = 0
            for w in captured_windows:
                n_steps += int(w.get("window_steps", 0))
                for name, slot in w.get("spans", {}).items():
                    agg[name] = agg.get(name, 0.0) + float(slot["total_ms"])
            captured_windows.clear()
            return {"steps": n_steps, **{f"{k}_ms": round(v, 1) for k, v in sorted(agg.items())}}

        dt_first = timed_pass()  # compile + warmup
        captured_windows.clear()
        pass_budget()  # drop the warmup pass's accounts
        out = {}
        for prefetch in (2, 0):
            trainer.cfg = cfg.replace(prefetch_batches=prefetch)
            # COLD tokenizer cache each pass: the dataset memoizes encoded
            # examples, and a warm cache would exclude tokenization from
            # the timed window entirely — the prefetch 2-vs-0 gap is
            # precisely "does the background thread hide tokenize+pad"
            trainer.train_ds.clear_cache()
            dt = timed_pass()
            out[f"tokens_per_sec_chip_prefetch{prefetch}"] = round(tokens / dt / n_chips, 1)
            out[f"spans_prefetch{prefetch}"] = pass_spans()
            budget = pass_budget()
            if budget is not None:
                out[f"budget_prefetch{prefetch}"] = budget
        if "budget_prefetch2" in out:
            # the headline gauge: the fraction of trainer-loop wall the
            # device was fed or drained (vs host-side stalls) on the
            # default prefetch config
            out["dispatch_efficiency"] = out["budget_prefetch2"][
                "dispatch_efficiency"
            ]
        if dev_profile and rbg_ok(dt + 25.0):
            # one profiled (UNTIMED — the profiler start/stop syncs would
            # pollute a timed window) pass: touch the trainer's own
            # profile trigger, let the capture land mid-pass, and read
            # back the parsed device account (per-bucket device time,
            # achieved collective bandwidth, overlap) the capture emitted
            try:
                trainer.cfg = cfg.replace(prefetch_batches=2)
                trigger = trainer.obs._trigger
                os.makedirs(os.path.dirname(trigger), exist_ok=True)
                with open(trigger, "w") as f:
                    f.write("1")  # one profiled step bounds the overhead
                trainer.train_ds.clear_cache()
                trainer.train()
                acct = (
                    trainer.obs.budget.last_device_account
                    if trainer.obs.budget is not None
                    else None
                )
                if acct is not None:
                    out["device_account"] = {
                        k: v for k, v in acct.items()
                        if k not in ("lanes", "lane_slices_dropped", "event")
                    }
                else:
                    out["device_account"] = {"error": "no capture landed"}
            except Exception as e:
                out["device_account"] = {"error": str(e)[:300]}
            captured_windows.clear()
            pass_budget()  # drop the profiled pass's accounts
        # adaptive cost estimate for the rbg pass: one warm pass (includes
        # the typed-key retrace — bounded by the compile-inclusive first
        # pass) plus one timed pass
        rbg_est = dt_first + dt + 30.0
        if trainer.use_dropout and os.environ.get("BENCH_TRAINER_RBG", "1") != "0" and rbg_ok(rbg_est):
            # the --prng-impl rbg trainer path: hardware-RNG dropout masks.
            # Swap the key impl via the Trainer's own knob and warm once
            # (the step retraces for the typed-key argument) before timing.
            trainer.cfg = cfg.replace(prefetch_batches=2)
            trainer.set_prng_impl("rbg")
            timed_pass()
            trainer.train_ds.clear_cache()
            dt = timed_pass()
            out["tokens_per_sec_chip_rbg"] = round(tokens / dt / n_chips, 1)
        out["steps"] = steps
        out["prng_impl"] = trainer.prng_impl  # resolved (not the "auto" alias)
        out["dropout_impl"] = trainer.cfg.dropout_impl
        # resolved optimizer path; the budget_prefetch* aggregates above
        # carry its per-window optimizer_apply_ms gauge (the cadenced
        # stand-alone apply sample) when budget accounting ran
        out["optim_impl"] = trainer.optim_impl
        return out


def _llama_depth_main() -> None:
    """BENCH_MODE=llama-depth: measured 7B-class remat step time by depth
    extrapolation.  One v5e chip cannot hold llama-2-7b's optimizer state,
    so this measures the full-width model (hidden 4096 / inter 11008, GQA,
    bf16, remat ON — the BASELINE.json config-5 recipe) truncated to
    2 and 4 layers, fits time = overhead + per_layer · L, and extrapolates
    to the real 32-layer depth.  Transformer step time is linear in depth
    (identical layers, remat recompute included per layer), so the fit has
    exactly the two degrees of freedom the two measurements pin down."""
    import dataclasses

    import jax
    import numpy as np

    from distributed_llms_example_tpu.core.config import MeshConfig
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.data.batching import LABEL_PAD
    from distributed_llms_example_tpu.models.llama import LlamaForCausalLM
    from distributed_llms_example_tpu.models.registry import LLAMA_CONFIGS
    from distributed_llms_example_tpu.ops.fused_optim import (
        resolve_impl as resolve_optim_impl,
    )
    from distributed_llms_example_tpu.train.optim import make_optimizer_bundle
    from distributed_llms_example_tpu.train.step import (
        create_train_state,
        make_train_step,
        put_batch,
        state_shardings,
    )

    policy = os.environ.get("BENCH_REMAT_POLICY", "full")
    batch = int(os.environ.get("BENCH_BATCH_7B", "4"))
    seq = int(os.environ.get("BENCH_SEQ_7B", "1024"))
    depths = [int(x) for x in os.environ.get("BENCH_DEPTHS", "2,4").split(",")]
    steps = max(2, int(os.environ.get("BENCH_STEPS", "4")))
    base = LLAMA_CONFIGS["llama-2-7b"]
    mesh = build_mesh(MeshConfig(data=-1))
    n_chips = jax.device_count()
    # the HEADLINE runs the production default optimizer path (--optim-impl
    # auto = the fused Pallas clip+AdamW apply on TPU, optax elsewhere);
    # same-session variants below re-measure the OTHER impl and the fused
    # blockwise CE so the non-layer-overhead delta is attributed per
    # component in one session (the ROADMAP acceptance shape)
    optim_impl = os.environ.get("BENCH_OPTIM_IMPL", "auto")
    resolved_optim = resolve_optim_impl(optim_impl)
    # this mode measures depth scaling only and always runs uncompressed;
    # a silently-ignored BENCH_GRAD_COMPRESSION here would be the exact
    # config-loss failure obs_gate exists to catch — say so loudly
    if os.environ.get("BENCH_GRAD_COMPRESSION", "off") != "off":
        print(
            "bench: BENCH_GRAD_COMPRESSION is ignored in llama-depth mode "
            "(record stamps grad_compression=off); the compression A/B "
            "lives in the main bench",
            file=sys.stderr,
        )
    variant_names = [
        v for v in os.environ.get(
            "BENCH_7B_VARIANTS", "optim_xla,fused_ce"
        ).split(",") if v
    ]

    rng = np.random.RandomState(0)
    ids = rng.randint(2, base.vocab_size, (batch * n_chips, seq)).astype(np.int32)
    labels = ids.copy()
    labels[:, : seq // 4] = LABEL_PAD
    b = {"input_ids": ids, "attention_mask": np.ones_like(ids), "labels": labels}
    tokens_per_step = int(np.sum(b["attention_mask"]))

    from distributed_llms_example_tpu.parallel.sharding import infer_param_shardings

    fused_ce = os.environ.get("BENCH_FUSED_CE", "0") == "1"
    step_ms = {}
    variant_ms: dict = {v: {} for v in variant_names}
    optim_probe_ms: dict = {}
    accum_report = None
    for L in depths:
        cfg = dataclasses.replace(base, num_hidden_layers=L, fused_ce=fused_ce)
        module = LlamaForCausalLM(cfg, dtype=jax.numpy.bfloat16, remat=True, remat_policy=policy)

        # init ON-DEVICE with output shardings: no host round-trip of
        # these multi-GB trees
        def init_params():
            return module.init(
                jax.random.PRNGKey(0), jax.numpy.ones((1, 8), jax.numpy.int32)
            )["params"]

        shapes = jax.eval_shape(init_params)
        params = jax.jit(
            init_params, out_shardings=infer_param_shardings(shapes, mesh)
        )()
        tx, schedule, optim_spec = make_optimizer_bundle(
            learning_rate=5e-5, warmup_steps=0, total_steps=1000
        )
        state = create_train_state(params, tx)
        sh = state_shardings(state, mesh)
        state = jax.tree.map(lambda x, s: jax.device_put(x, s), state, sh)
        build = make_train_step(
            module, cfg, tx, schedule, mesh, is_seq2seq=False,
            optim_spec=optim_spec, optim_impl=optim_impl,
        )
        step_fn, _ = build(state)
        gb = put_batch(b, mesh)

        def timed_median(fn, state):
            """warm twice, then per-step sync-inclusive times, MEDIAN over
            the window: one host stall inside a single aggregate window
            once turned a 2-layer measurement slower than the 4-layer one
            (negative per-layer fit).  Returns (median_ms, state)."""
            for _ in range(2):
                state, metrics = fn(state, gb)
            _ = float(jax.device_get(metrics["loss"]))
            times = []
            for _ in range(steps):
                t0 = time.perf_counter()
                state, metrics = fn(state, gb)
                _ = float(jax.device_get(metrics["loss"]))
                times.append(time.perf_counter() - t0)
            return sorted(times)[len(times) // 2] * 1e3, state

        step_ms[L], state = timed_median(step_fn, state)

        # same-session component A/Bs at every depth (both depths feed the
        # per-variant intercept fit, so the non-layer-overhead delta is
        # attributed to the optimizer / CE component it came from):
        # "optim_xla" re-measures the step on the optax chain;
        # "fused_ce" measures the vocab-chunked LM-head+CE path.
        for v in variant_names:
            try:
                if v == "optim_xla":
                    if resolved_optim == "xla":
                        variant_ms[v][L] = {"skipped": "headline already xla"}
                        continue
                    build_v = make_train_step(
                        module, cfg, tx, schedule, mesh, is_seq2seq=False,
                        optim_spec=optim_spec, optim_impl="xla",
                    )
                elif v == "fused_ce":
                    if fused_ce:
                        # BENCH_FUSED_CE=1: the headline already runs the
                        # fused CE — re-measuring would stamp run-to-run
                        # jitter as a component delta
                        variant_ms[v][L] = {"skipped": "headline already fused_ce"}
                        continue
                    ce_cfg = dataclasses.replace(cfg, fused_ce=True)
                    ce_module = LlamaForCausalLM(
                        ce_cfg, dtype=jax.numpy.bfloat16, remat=True,
                        remat_policy=policy,
                    )
                    build_v = make_train_step(
                        ce_module, ce_cfg, tx, schedule, mesh,
                        is_seq2seq=False,
                        optim_spec=optim_spec, optim_impl=optim_impl,
                    )
                else:
                    variant_ms[v][L] = {"skipped": f"unknown variant {v!r}"}
                    continue
                sv, _ = build_v(state)
                ms, state = timed_median(sv, state)
                variant_ms[v][L] = ms
            except Exception as e:
                variant_ms[v][L] = {"error": str(e)[:300]}

        # direct optimizer-apply wall sample per impl (the step-budget
        # layer's optimizer_apply_ms, stand-alone): the component-level
        # evidence for WHICH slice of the intercept the fused apply moved
        if L == max(depths) and os.environ.get("BENCH_OPTIM_PROBE_7B", "1") != "0":
            from distributed_llms_example_tpu.train.step import (
                make_optimizer_probe,
            )

            probe_impls = ["xla"] + (
                [resolved_optim] if resolved_optim != "xla" else []
            )
            for impl_name in probe_impls:
                try:
                    probe = make_optimizer_probe(
                        tx, schedule, sh, mesh,
                        optim_spec=optim_spec, optim_impl=impl_name,
                    )
                    _ = float(jax.device_get(probe(state)))  # compile+warm
                    pts = []
                    for _ in range(steps):
                        t0 = time.perf_counter()
                        _ = float(jax.device_get(probe(state)))
                        pts.append(time.perf_counter() - t0)
                    optim_probe_ms[impl_name] = round(
                        sorted(pts)[len(pts) // 2] * 1e3, 2
                    )
                except Exception as e:
                    optim_probe_ms[impl_name] = f"error: {str(e)[:200]}"

        # In-step grad-accumulation sweep at the deepest measured config:
        # effective batch = microbatch(=BENCH_BATCH_7B) × N at the SAME
        # peak activation memory as the batch-4 step (the scan holds one
        # microbatch's activations + the param-sharded fp32 accumulators)
        # — this is how batch 8+ becomes reachable on one v5e chip after
        # the round-5 batch8_oom.  Ideal linear scaling is N × the accum=1
        # step time; the per-microbatch overhead fraction is the cost of
        # the scan + the (amortized-away) once-per-step tail.
        if L == max(depths) and os.environ.get("BENCH_ACCUM_7B", "1") != "0":
            from distributed_llms_example_tpu.obs import memprof

            # peak_bytes_in_use is the allocator's PROCESS-LIFETIME
            # high-water mark (never reset), so every field derived from
            # it is named *_cumulative and each accumN entry also reports
            # the watermark delta vs its own pre-pass mark(): delta 0
            # proves the pass stayed under the historical peak (the
            # memory-flatness claim), delta > 0 is the new high water
            # this pass alone set
            watermark = memprof.Watermark()

            def peak_gib():
                p = watermark.peak_bytes()
                return round(p / memprof.GIB, 2) if p else None

            accum_list = [
                int(x)
                for x in os.environ.get("BENCH_ACCUM_7B_STEPS", "4,16").split(",")
            ]
            accum_report = {
                "note": (
                    f"measured at depth {L} (full-width layers, the same "
                    "truncated-depth methodology as the headline): in-step "
                    "scan accumulation, microbatch "
                    f"{batch * n_chips}, one optimizer apply per step"
                ),
                "microbatch": batch * n_chips,
                "accum1_step_ms": round(step_ms[L], 1),
            }
            p = peak_gib()
            if p is not None:
                accum_report["accum1_peak_hbm_gib_cumulative"] = p
            for N in accum_list:
                watermark.mark()
                rows = batch * n_chips * N
                idsN = rng.randint(2, base.vocab_size, (rows, seq)).astype(np.int32)
                labelsN = idsN.copy()
                labelsN[:, : seq // 4] = LABEL_PAD
                bN = {
                    "input_ids": idsN,
                    "attention_mask": np.ones_like(idsN),
                    "labels": labelsN,
                }
                try:
                    buildN = make_train_step(
                        module, cfg, tx, schedule, mesh,
                        is_seq2seq=False, grad_accum_steps=N,
                        optim_spec=optim_spec, optim_impl=optim_impl,
                    )
                    stepN, _ = buildN(state)
                    gbN = put_batch(bN, mesh)
                    state, mN = stepN(state, gbN)  # compile + warmup
                    _ = float(jax.device_get(mN["loss"]))
                    tN = []
                    for _ in range(steps):
                        t0 = time.perf_counter()
                        state, mN = stepN(state, gbN)
                        _ = float(jax.device_get(mN["loss"]))
                        tN.append(time.perf_counter() - t0)
                    tN_ms = sorted(tN)[len(tN) // 2] * 1e3
                    ideal = N * step_ms[L]
                    entry = {
                        "effective_batch": rows,
                        "step_ms": round(tN_ms, 1),
                        "per_microbatch_ms": round(tN_ms / N, 2),
                        # tokens/sec/chip ratio vs accum=1 at equal token
                        # throughput accounting == ideal/actual; the
                        # acceptance bar is >= 0.95 at accum=4
                        "tokens_per_sec_vs_accum1": round(ideal / tN_ms, 3),
                        "overhead_frac_vs_ideal_linear": round(tN_ms / ideal - 1.0, 4),
                    }
                    if N == 4:
                        entry["ok_95pct"] = bool(ideal / tN_ms >= 0.95)
                    p = peak_gib()
                    if p is not None:
                        entry["peak_hbm_gib_cumulative"] = p
                        delta = watermark.delta_bytes()
                        if delta is not None:
                            # 0.0 == this pass stayed under the lifetime
                            # peak: the constant-memory acceptance signal
                            entry["peak_hbm_new_high_water_gib"] = round(
                                delta / memprof.GIB, 2
                            )
                    accum_report[f"accum{N}"] = entry
                    del gbN, mN
                except Exception as e:
                    accum_report[f"accum{N}"] = {"error": str(e)[:300]}
                    # a failure mid-step may have consumed the donated
                    # state; rebuild it so the next N measures (or OOMs)
                    # on its own terms instead of 'Array has been deleted'.
                    # Drop the dead tree and this N's batch FIRST — on an
                    # OOM before donation, old + replacement living at
                    # once would OOM the rebuild too
                    state = None
                    gbN = None
                    state = jax.tree.map(
                        lambda x, s: jax.device_put(x, s),
                        create_train_state(
                            jax.jit(
                                init_params,
                                out_shardings=infer_param_shardings(shapes, mesh),
                            )(),
                            tx,
                        ),
                        sh,
                    )
        del state, params, gb  # free ~11 GB before the next depth

    l_lo, l_hi = min(depths), max(depths)
    per_layer = (step_ms[l_hi] - step_ms[l_lo]) / (l_hi - l_lo)
    overhead = step_ms[l_lo] - l_lo * per_layer
    if per_layer <= 0:
        # a non-positive slope means a polluted measurement, not physics —
        # refuse to extrapolate garbage into the artifact
        print(json.dumps({
            "metric": "llama-2-7b depth-extrapolated throughput",
            "value": None,
            "unit": "tokens/sec/chip (extrapolated)",
            "vs_baseline": None,
            "error": "non-positive per-layer slope: measurement polluted, re-run",
            "measured_step_ms": {str(k): round(v, 1) for k, v in step_ms.items()},
        }))
        return
    t_full_ms = overhead + base.num_hidden_layers * per_layer
    tps_chip = tokens_per_step / (t_full_ms / 1e3) / n_chips
    # same analytic method as the 406M baseline constant: 6·N FLOPs/token at
    # 35% utilization of a 312 TFLOP/s bf16 A100 → ~2,700 tok/s/GPU at 6.74B
    baseline_7b = 312e12 * 0.35 / (6.0 * 6.74e9)
    # per-variant intercept fits: the same two-point depth fit as the
    # headline, so each variant's non_layer_overhead_ms delta attributes
    # the headline's intercept move to its component (optimizer impl / CE)
    variants_out: dict = {}
    for v, per_depth in variant_ms.items():
        ok = {k: x for k, x in per_depth.items() if isinstance(x, (int, float))}
        if l_lo in ok and l_hi in ok:
            vl = (ok[l_hi] - ok[l_lo]) / (l_hi - l_lo)
            vo = ok[l_lo] - l_lo * vl
            variants_out[v] = {
                "measured_step_ms": {str(k): round(x, 1) for k, x in ok.items()},
                "per_layer_ms": round(vl, 2),
                "non_layer_overhead_ms": round(vo, 2),
                "overhead_delta_ms_vs_headline": round(vo - overhead, 2),
            }
        elif per_depth:
            variants_out[v] = {
                "measured": {str(k): x for k, x in per_depth.items()}
            }
    print(
        json.dumps(
            {
                "grad_compression": "off",
                "metric": f"llama-2-7b causal-LM fine-tune throughput, depth-extrapolated "
                          f"from measured {depths}-layer full-width steps "
                          f"(seq {seq}, bf16+remat[{policy}]"
                          f"{'+fused_ce' if fused_ce else ''}, batch {batch}, "
                          f"optim {resolved_optim})",
                "value": round(tps_chip, 1),
                "unit": "tokens/sec/chip (extrapolated)",
                "vs_baseline": round(tps_chip / baseline_7b, 3),
                "extrapolated_step_ms": round(t_full_ms, 1),
                "per_layer_ms": round(per_layer, 2),
                "non_layer_overhead_ms": round(overhead, 2),
                "measured_step_ms": {str(k): round(v, 1) for k, v in step_ms.items()},
                "chips": n_chips,
                "backend": jax.default_backend(),
                # the headline's optimizer impl (--optim-impl auto resolves
                # to the fused Pallas apply on TPU) + the same-session
                # component A/Bs: per-variant intercept fits and the
                # stand-alone optimizer-apply wall per impl
                "optim_impl": resolved_optim,
                **({"optimizer_apply_ms": optim_probe_ms} if optim_probe_ms else {}),
                **({"variants": variants_out} if variants_out else {}),
                # stamped even when the sweep is disabled/failed, so the
                # record always says which accumulation config it measured
                "grad_accum_steps": 1,
                **({"grad_accum": accum_report} if accum_report else {}),
            }
        )
    )


def _host_input_main() -> None:
    """BENCH_MODE=host-input: batch-assembly throughput, host only.

    A v5e-8 host must feed 8 chips at the measured per-chip rate
    (~60k tok/s each ⇒ ~483k tok/s of assembled batches) through ONE
    prefetch thread running tokenize + pad + bucket.  This measures that
    assembly path in isolation — no devices touched — for both the
    dependency-free byte tokenizer and a real HF fast (byte-level BPE)
    tokenizer trained in-process (no egress), at the headline shape
    (src 1024 / tgt 128 buckets, host batch = 8 chips × 16/chip).
    Token counting matches Trainer._batch_tokens (non-pad source +
    target), so the margin vs the device rate is apples-to-apples."""
    import tempfile

    import numpy as np

    from distributed_llms_example_tpu.data.batching import LABEL_PAD, BatchIterator
    from distributed_llms_example_tpu.data.dataset import SummarizationDataset
    from distributed_llms_example_tpu.data.tokenizer import ByteTokenizer, HFTokenizer

    steps = max(4, int(os.environ.get("BENCH_HOST_STEPS", "12")))
    batch = int(os.environ.get("BENCH_HOST_BATCH", str(16 * 8)))
    chip_rate = float(os.environ.get("BENCH_HOST_CHIP_RATE", "60343"))  # BENCH_r04
    n_chips = int(os.environ.get("BENCH_HOST_CHIPS", "8"))
    target = chip_rate * n_chips
    rng = np.random.RandomState(11)

    def text(n_chars: int) -> str:
        words = []
        total = 0
        while total < n_chars:
            w = "".join(chr(97 + rng.randint(26)) for _ in range(3 + rng.randint(6)))
            words.append(w)
            total += len(w) + 1
        return " ".join(words)[:n_chars]

    records = [{"dialogue": text(1016), "summary": text(120)} for _ in range(batch * steps)]

    def build_bpe(tmp: str):
        # a real transformers fast tokenizer (rust BPE), trained on the
        # fixture corpus so no assets are needed — same construction as
        # tests/test_tokenizer_parity.py
        from tokenizers import Tokenizer as TK, models, pre_tokenizers, processors
        from tokenizers.trainers import BpeTrainer
        from transformers import PreTrainedTokenizerFast

        tok = TK(models.BPE(unk_token="<unk>"))
        tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
        trainer = BpeTrainer(
            special_tokens=["<s>", "<pad>", "</s>", "<unk>"],
            vocab_size=int(os.environ.get("BENCH_HOST_BPE_VOCAB", "8000")),
        )
        corpus = (r["dialogue"] + " " + r["summary"] for r in records)
        tok.train_from_iterator(corpus, trainer)
        bos, eos = tok.token_to_id("<s>"), tok.token_to_id("</s>")
        tok.post_processor = processors.TemplateProcessing(
            single="<s> $A </s>", pair="<s> $A </s> $B </s>",
            special_tokens=[("<s>", bos), ("</s>", eos)],
        )
        fast = PreTrainedTokenizerFast(
            tokenizer_object=tok, bos_token="<s>", eos_token="</s>",
            pad_token="<pad>", unk_token="<unk>",
        )
        fast.save_pretrained(tmp)
        return HFTokenizer(tmp)

    result = {
        "grad_compression": "off",
        "metric": f"host batch-assembly throughput (tokenize+pad+bucket, no devices; "
                  f"host batch {batch}, src1024/tgt128) vs the ~{target / 1e3:.0f}k tok/s "
                  f"a v5e-{n_chips} host must feed at {chip_rate / 1e3:.1f}k tok/s/chip",
        "unit": "host tokens/sec",
        "vs_baseline": None,
        "target_tokens_per_sec": round(target),
        "chips_assumed": n_chips,
        # the HF number scales with cores: encode_batch fans across them
        # (rayon), and this machine is the FLOOR — a real v5e-8 host has
        # ~100 vCPUs where one batch call parallelizes
        "host_cpus": os.cpu_count(),
    }
    with tempfile.TemporaryDirectory() as tmp:
        for label, tokzr in (("byte", ByteTokenizer()), ("hf_bpe", build_bpe(tmp))):
            ds = SummarizationDataset(
                records, tokzr, max_source_length=1024, max_target_length=128
            )
            it = BatchIterator(
                ds, global_batch=batch, seed=0,
                bucket_multiple=128, max_source_length=1024, max_target_length=128,
            )
            for warm in range(2):
                ds._cache = [None] * len(ds)  # cold tokenizer cache each pass
                t0 = time.perf_counter()
                tokens = 0
                for b in it.epoch(0):
                    tokens += int(np.sum(b["attention_mask"]))
                    tokens += int(np.sum(b["labels"] != LABEL_PAD))
                dt = time.perf_counter() - t0
            rate = tokens / dt
            result[f"{label}_tokens_per_sec"] = round(rate)
            result[f"{label}_margin_vs_target"] = round(rate / target, 2)
    # headline value = the slower (realistic HF) tokenizer's rate
    result["value"] = result["hf_bpe_tokens_per_sec"]
    print(json.dumps(result))


def _generate_main() -> None:
    """BENCH_MODE=generate: jitted eval-generation throughput on the
    flagship seq2seq model.  The reference's live eval loop spends roughly
    half its wall clock inside beam-2 ``generate()`` (reference
    train-accelerator.py:245-249); this measures that exact contract
    on-chip — beam-2, src 1024 / max_new 128 — reporting generated
    tokens/sec/chip plus the prefill(encode)/decode split.  Weights are
    randomly initialized (no egress): the decode loop is a fixed-trip-count
    ``fori_loop``, so throughput is content-independent."""
    import jax
    import numpy as np

    from distributed_llms_example_tpu.core.config import MeshConfig
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.evaluation.generation import (
        make_beam_search,
        make_greedy_generate,
    )
    from distributed_llms_example_tpu.parallel.activation import activation_mesh
    from distributed_llms_example_tpu.parallel.sharding import shard_params

    name, lm, _ = _flagship()
    n_chips = jax.device_count()
    mesh = build_mesh(MeshConfig(data=-1))
    src_len = int(os.environ.get("BENCH_GEN_SRC", "1024"))
    new_tokens = int(os.environ.get("BENCH_GEN_NEW", "128"))
    beams = int(os.environ.get("BENCH_GEN_BEAMS", "2"))
    batch = int(os.environ.get("BENCH_GEN_BATCH", "16")) * n_chips
    reps = max(1, int(os.environ.get("BENCH_STEPS", "3")))

    params = lm.params if lm.params is not None else jax.device_get(lm.init_params(0))
    params = shard_params(params, mesh)
    if beams > 1:
        gen = make_beam_search(lm.module, lm.config, new_tokens, beams)
    else:
        gen = make_greedy_generate(lm.module, lm.config, new_tokens)
    jgen = jax.jit(gen)
    jenc = jax.jit(
        lambda p, ids, m: lm.module.apply({"params": p}, ids, m, method="encode")
    )

    rng = np.random.RandomState(0)
    ids = jax.numpy.asarray(
        rng.randint(2, min(lm.config.vocab_size, 30000), (batch, src_len)).astype(np.int32)
    )
    mask = jax.numpy.ones((batch, src_len), jax.numpy.int32)

    with activation_mesh(mesh):
        out = jgen(params, ids, mask)  # compile + warmup
        _ = np.asarray(out)
        t0 = time.perf_counter()
        for _ in range(reps):
            out = jgen(params, ids, mask)
        _ = np.asarray(out)
        dt_total = (time.perf_counter() - t0) / reps

        enc = jenc(params, ids, mask)  # compile + warmup
        _ = np.asarray(jax.device_get(enc.ravel()[0]))
        t0 = time.perf_counter()
        for _ in range(reps):
            enc = jenc(params, ids, mask)
        _ = np.asarray(jax.device_get(enc.ravel()[0]))
        dt_prefill = (time.perf_counter() - t0) / reps

    dt_decode = max(dt_total - dt_prefill, 1e-9)
    gen_tokens = batch * new_tokens  # fixed trip count: every row decodes L steps
    tps_chip = gen_tokens / dt_total / n_chips
    print(json.dumps({
        "grad_compression": "off",
        "metric": f"{name} eval generation throughput (beam {beams}, src {src_len} "
                  f"/ max_new {new_tokens}, bf16, batch {batch}) — the reference's "
                  "live eval contract (train-accelerator.py:245-249); no reference "
                  "number exists to compare against (BASELINE.md: none published)",
        "value": round(tps_chip, 1),
        "unit": "generated tokens/sec/chip",
        "vs_baseline": None,
        "examples_per_sec_chip": round(batch / dt_total / n_chips, 2),
        "prefill_ms": round(dt_prefill * 1e3, 1),
        "decode_ms": round(dt_decode * 1e3, 1),
        "decode_ms_per_token": round(dt_decode * 1e3 / new_tokens, 3),
        "decode_tokens_per_sec_chip": round(gen_tokens / dt_decode / n_chips, 1),
        "chips": n_chips,
        "backend": jax.default_backend(),
    }))


def _serve_measure(
    lm, mesh, sharded, *,
    slots: int, src: int, new_tokens: int, n_req: int, eval_beams: int,
) -> dict:
    """The serving measurements, shared by BENCH_MODE=serve and the main
    bench's ``serve`` add-on: continuous-batching decode tokens/sec/chip
    and TTFT (serving/engine.py), the continuous-vs-static utilization A/B
    at per-request token budgets, the ROUGE-eval-path A/B (OLD contract:
    params replicated onto one device, whole-batch generate — vs the
    sharded prefill/decode split the Evaluator now rides), and the decode
    composition-matrix rows for fsdp/tensor/stage/sequence mesh shapes.
    Same session, same requests; weights are randomly initialized —
    greedy/beam decode is deterministic and throughput content-independent."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llms_example_tpu.analysis.composition import failing_combos
    from distributed_llms_example_tpu.evaluation.generation import (
        CausalGenerator,
        Seq2SeqGenerator,
    )
    from distributed_llms_example_tpu.parallel.activation import activation_mesh
    from distributed_llms_example_tpu.serving.engine import (
        ServeConfig,
        ServingEngine,
        make_static_runner,
    )

    n_chips = jax.device_count()
    rng = np.random.RandomState(0)
    vocab_hi = min(lm.config.vocab_size, 30000)
    requests = [
        list(rng.randint(4, vocab_hi, rng.randint(max(src // 2, 8), src + 1)))
        for _ in range(n_req)
    ]
    # per-request token budgets (the serving max_tokens knob): varied, so
    # continuous batching's slot refill has something to exploit and the
    # static path's pay-max-L-per-row cost is visible
    budgets = [int(b) for b in rng.randint(max(new_tokens // 4, 1), new_tokens + 1, n_req)]

    # the goodput SLO the router tier dispatches on: useful tokens/sec +
    # attainment at this first-token threshold ride the serve block (and
    # the serve_summary event) — BENCH_TTFT_SLO_MS overrides per round
    ttft_slo_ms = float(os.environ.get("BENCH_TTFT_SLO_MS", "500"))
    engine = ServingEngine(
        lm.module, lm.config, mesh,
        ServeConfig(
            max_slots=slots, prefill_batch=slots,
            max_new_tokens=new_tokens, max_source_length=src,
            log_every_steps=0, ttft_slo_ms=ttft_slo_ms,
        ),
        is_seq2seq=lm.is_seq2seq,
    )
    engine.generate(sharded, requests[: slots], max_new=budgets[: slots])  # compile+warm
    t0 = time.perf_counter()
    headline_outs = engine.generate(sharded, requests, max_new=budgets)
    serve_s = time.perf_counter() - t0
    stats = engine.last_stats

    # static contract on the SAME workload: every chunk row decodes the
    # full max_new_tokens no matter when its budget is met — timed through
    # the very runner the determinism test pins (serving/engine.py)
    static_all = make_static_runner(
        lm.module, lm.config, mesh,
        max_new_tokens=new_tokens, width=src, batch=slots,
        is_seq2seq=lm.is_seq2seq,
    )

    def run_static() -> float:
        t0 = time.perf_counter()
        static_all(sharded, requests)
        return time.perf_counter() - t0

    run_static()  # compile+warm
    static_s = run_static()
    useful_tokens = sum(budgets)
    static_rows = slots * math.ceil(n_req / slots)
    serve_tps_chip = stats.tokens_per_sec() / n_chips
    ttft_p50, ttft_p95 = stats.ttft_percentiles()

    # ROUGE-eval-path A/B (the Evaluator's generation cost): OLD = params
    # replicated onto ONE device (host copy → default placement), the
    # whole-batch program traced with no mesh — the seed's single-device
    # decode; NEW = the sharded prefill/decode split the Evaluator uses.
    eval_batch = slots
    ids = np.full((eval_batch, src), lm.config.pad_token_id, np.int32)
    mask = np.zeros((eval_batch, src), np.int32)
    for r in range(eval_batch):
        req = requests[r % n_req][:src]
        ids[r, : len(req)] = req
        mask[r, : len(req)] = 1
    gen_cls = Seq2SeqGenerator if lm.is_seq2seq else CausalGenerator
    gen = gen_cls(lm.module, lm.config, new_tokens, num_beams=eval_beams)
    rouge_ab = {}
    try:
        # the whole tree RESIDENT on device 0 before timing — numpy args
        # would re-transfer every param on each call and bill the H2D copy
        # to the "single-device" leg
        old_params = jax.device_put(jax.device_get(sharded), jax.devices()[0])
        old_run = jax.jit(gen.run)
        with activation_mesh(None):
            np.asarray(old_run(old_params, jnp.asarray(ids), jnp.asarray(mask)))
            t0 = time.perf_counter()
            np.asarray(old_run(old_params, jnp.asarray(ids), jnp.asarray(mask)))
            old_s = time.perf_counter() - t0
        del old_params
        prefill = jax.jit(gen.prefill)
        decode = jax.jit(gen.decode_loop)
        finalize = jax.jit(gen.finalize)

        def run_new() -> float:
            with activation_mesh(mesh):
                carry = prefill(sharded, jnp.asarray(ids), jnp.asarray(mask))
                out = finalize(decode(sharded, carry))
            np.asarray(out)
            return 0.0

        run_new()
        t0 = time.perf_counter()
        run_new()
        new_s = time.perf_counter() - t0
        rouge_ab = {
            "beams": eval_beams,
            "batch": eval_batch,
            "old_single_device_s": round(old_s, 3),
            "sharded_split_s": round(new_s, 3),
            "speedup": round(old_s / max(new_s, 1e-9), 2),
        }
        if jax.default_backend() == "cpu":
            # forced host devices share ONE machine's cores: the
            # "single-device" leg still uses every thread via XLA intra-op
            # parallelism, so this A/B only separates on real accelerators
            rouge_ab["note"] = (
                "cpu backend: virtual devices share one host's cores — the "
                "single-device leg is not resource-constrained here"
            )
    except Exception as e:
        print(f"bench: rouge-eval A/B failed ({e})", file=sys.stderr)
        rouge_ab = {"error": str(e)[:300]}

    # decode × mesh composition rows — pure table evaluation, every shape
    # stamped whether or not this host can build the mesh
    flags = ("decode", "seq2seq" if lm.is_seq2seq else "causal")
    compo = {}
    for label, axes in (
        ("data", {"data": n_chips}),
        ("fsdp", {"fsdp": n_chips}),
        ("fsdp_tensor", {"fsdp": max(n_chips // 2, 1), "tensor": 2}),
        ("tensor", {"tensor": n_chips}),
        ("stage", {"stage": 2, "data": max(n_chips // 2, 1)}),
        ("sequence", {"sequence": 2, "data": max(n_chips // 2, 1)}),
    ):
        bad = failing_combos(flags=flags, mesh_axes=axes)
        compo[label] = "ok" if not bad else [row.id for row in bad]

    # decode-capacity block (ISSUE 13): int8 KV A/B on this model (token
    # parity at a tolerance + static footprint ratio), paged A/B when the
    # family is causal, capacity headline fields — all at the same mixed
    # prompt lengths as the headline run
    capacity = {}
    try:
        capacity = _serve_capacity(
            lm, mesh, sharded, requests, budgets,
            slots=slots, src=src, new_tokens=new_tokens,
            f32_stats=stats, f32_outs=headline_outs,
        )
    except Exception as e:
        print(f"bench: serve capacity block failed ({e})", file=sys.stderr)
        capacity = {"error": str(e)[:300]}

    return {
        "decode_tokens_per_sec_chip": round(serve_tps_chip, 1),
        "ttft_p50_ms": round(ttft_p50 * 1e3, 1),
        "ttft_p95_ms": round(ttft_p95 * 1e3, 1),
        # queue-wait vs prefill share of TTFT (serving request spans):
        # the explainable-p95 fields the serve_summary event also carries
        **stats.ttft_decomposition(),
        # goodput at the TTFT SLO (useful tokens/sec + attainment) — the
        # serve_summary fields the router open item dispatches on
        **stats.goodput,
        "slot_occupancy": round(stats.slot_occupancy, 4),
        "decode_steps": stats.decode_steps,
        "wall_s": round(serve_s, 2),
        "static_wall_s": round(static_s, 2),
        # useful tokens (the budget sum) per second, both paths — the
        # utilization A/B: static decodes max_new for EVERY padded row
        "continuous_useful_tokens_per_sec_chip": round(useful_tokens / serve_s / n_chips, 1),
        "static_useful_tokens_per_sec_chip": round(useful_tokens / static_s / n_chips, 1),
        "continuous_vs_static": round(static_s / max(serve_s, 1e-9), 2),
        "static_row_utilization": round(useful_tokens / (static_rows * new_tokens), 4),
        "rouge_eval_ab": rouge_ab,
        "decode_composition": compo,
        "capacity": capacity,
        "slots": slots,
        "src_len": src,
        "max_new_tokens": new_tokens,
        "requests": n_req,
    }


def _token_match_rate(a_rows, b_rows, eos, pad) -> float:
    """Greedy prefix agreement between two decode paths: positionwise
    match over the eos-trimmed common prefix length.  A single near-tie
    argmax flip cascades (every later token conditions on it), so this is
    the CONSERVATIVE tolerance metric — per-step teacher-forced agreement
    is strictly higher."""
    from distributed_llms_example_tpu.serving.engine import trim_eos

    match = total = 0
    for a, b in zip(a_rows, b_rows):
        ta, tb = trim_eos(a, eos, pad), trim_eos(b, eos, pad)
        n = min(len(ta), len(tb))
        total += max(len(ta), len(tb))
        match += sum(x == y for x, y in zip(ta[:n], tb[:n]))
    return match / max(total, 1)


def _serve_capacity(
    lm, mesh, sharded, requests, budgets, *,
    slots: int, src: int, new_tokens: int, f32_stats, f32_outs,
) -> dict:
    """The decode-capacity A/Bs: int8 KV vs the f32 headline engine
    (token-parity at a tolerance + >= 3.5x static footprint reduction),
    and — causal families — paged vs flat (BIT-identical tokens,
    bytes-per-token scaling with actual prompt length).  Static byte
    accounting throughout (serving/cache_pool.py tree_bytes): capacity
    claims are measured off the state trees, not inferred; HBM/bandwidth
    wall-clock verdicts land on the TPU round."""
    import jax

    from distributed_llms_example_tpu.serving import cache_pool
    from distributed_llms_example_tpu.serving.engine import (
        ServeConfig,
        ServingEngine,
    )

    eos, pad = lm.config.eos_token_id, lm.config.pad_token_id
    base_kw = dict(
        max_slots=slots, prefill_batch=slots, max_new_tokens=new_tokens,
        max_source_length=src, log_every_steps=0, request_spans=False,
    )

    def run(**kw):
        eng = ServingEngine(
            lm.module, lm.config, mesh, ServeConfig(**base_kw, **kw),
            is_seq2seq=lm.is_seq2seq,
        )
        outs = eng.generate(sharded, requests, max_new=budgets)
        return eng, outs

    out = {
        # the f32 flat baseline's capacity headline: a full-width slot set
        "max_sustained_slots": slots,
        "cache_bytes_per_token": round(f32_stats.bytes_per_live_token, 1),
        "cache_bytes_resident": f32_stats.cache_bytes_resident,
    }

    i8_eng, i8_outs = run(kv_cache_dtype="int8")
    out["int8_vs_f32_kv"] = {
        "token_match_rate": round(
            _token_match_rate(f32_outs, i8_outs, eos, pad), 4
        ),
        "cache_bytes_ratio": round(
            f32_stats.cache_bytes_resident
            / max(i8_eng.last_stats.cache_bytes_resident, 1),
            3,
        ),
        "cache_bytes_per_token_f32": round(
            f32_stats.bytes_per_live_token, 1
        ),
        "cache_bytes_per_token_int8": round(
            i8_eng.last_stats.bytes_per_live_token, 1
        ),
        "decode_tokens_per_sec_chip_int8": round(
            i8_eng.last_stats.tokens_per_sec() / max(jax.device_count(), 1), 1
        ),
    }
    if lm.is_seq2seq:
        out["paged_vs_flat"] = {
            "note": (
                "paged_kv applies to the causal KV cache; the seq2seq "
                "slot state is encoder output + cross-KV — see the "
                "standalone causal paged record"
            )
        }
        return out

    # kv_block_size=0: the engine picks the largest valid block — it must
    # tile the cache width AND the admission bucket, a constraint the
    # engine owns (gcd-based auto default)
    pg_eng, pg_outs = run(paged_kv=True)
    bs = pg_eng.block_size
    mean_blocks = sum(
        cache_pool.blocks_needed(min(len(r), src), b, bs)
        for r, b in zip(requests, budgets)
    ) / max(len(requests), 1)
    out["paged_vs_flat"] = {
        # the acceptance pin: paged tokens are BIT-identical to flat
        "bit_identical": list(pg_outs) == list(f32_outs),
        "kv_block_size": bs,
        "pool_blocks": pg_eng.pool.num_blocks,
        "cache_bytes_per_token_flat": round(
            f32_stats.bytes_per_live_token, 1
        ),
        # scales with ACTUAL prompt length: live blocks / live tokens
        "cache_bytes_per_token_paged": round(
            pg_eng.last_stats.bytes_per_live_token, 1
        ),
        "admit_deferrals": pg_eng.last_stats.admit_deferrals,
        # what the SAME pool memory sustains at this workload's mix —
        # the concurrency headroom paging converts padding into
        "max_sustained_slots": int(pg_eng.pool.num_blocks // max(mean_blocks, 1)),
    }
    out["max_sustained_slots"] = max(
        out["max_sustained_slots"], out["paged_vs_flat"]["max_sustained_slots"]
    )
    return out


def _serve_main() -> None:
    """BENCH_MODE=serve: the full-size standalone serving record on the
    flagship seq2seq model (see ``_serve_measure``)."""
    import jax

    from distributed_llms_example_tpu.core.config import MeshConfig, parse_mesh_arg
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.parallel.sharding import shard_params

    name, lm, _ = _flagship()
    n_chips = jax.device_count()
    mesh_spec = os.environ.get("BENCH_SERVE_MESH", "")
    mesh = build_mesh(parse_mesh_arg(mesh_spec) if mesh_spec else MeshConfig(data=-1))
    batch_shards = 1
    for a in ("data", "fsdp", "expert"):
        batch_shards *= mesh.shape.get(a, 1)
    src = int(os.environ.get("BENCH_SERVE_SRC", "1024"))
    new_tokens = int(os.environ.get("BENCH_SERVE_NEW", "64"))
    slots = int(os.environ.get("BENCH_SERVE_SLOTS_PER_SHARD", "4")) * batch_shards
    n_req = int(os.environ.get("BENCH_SERVE_REQUESTS", str(3 * slots)))
    eval_beams = int(os.environ.get("BENCH_SERVE_EVAL_BEAMS", "2"))
    params = lm.params if lm.params is not None else jax.device_get(lm.init_params(0))
    sharded = shard_params(params, mesh)
    serve = _serve_measure(
        lm, mesh, sharded,
        slots=slots, src=src, new_tokens=new_tokens, n_req=n_req,
        eval_beams=eval_beams,
    )
    # the flagship is seq2seq, whose slot state has no causal cache to
    # page — run the paged_vs_flat acceptance A/B on a causal model at the
    # same mixed prompt lengths (random init: greedy decode is
    # deterministic and the bit-identity/footprint claims are
    # weight-independent)
    if lm.is_seq2seq and os.environ.get("BENCH_SERVE_PAGED_AB", "1") != "0":
        try:
            causal_name = os.environ.get("BENCH_SERVE_CAUSAL", "llama-test")
            from distributed_llms_example_tpu.models.registry import load_model

            clm = load_model(causal_name)
            cparams = shard_params(
                clm.params if clm.params is not None else clm.init_params(0),
                mesh,
            )
            crng = __import__("numpy").random.RandomState(1)
            c_src, c_new = 64, 16
            c_slots = max(2, batch_shards)
            c_reqs = [
                list(crng.randint(4, min(clm.config.vocab_size, 1000),
                                  crng.randint(max(c_src // 4, 4), c_src + 1)))
                for _ in range(3 * c_slots)
            ]
            c_budgets = [int(b) for b in crng.randint(c_new // 2, c_new + 1, len(c_reqs))]
            from distributed_llms_example_tpu.serving.engine import (
                ServeConfig as _SC,
                ServingEngine as _SE,
            )

            base = dict(max_slots=c_slots, prefill_batch=c_slots,
                        max_new_tokens=c_new, max_source_length=c_src,
                        log_every_steps=0, request_spans=False)
            flat_eng = _SE(clm.module, clm.config, mesh, _SC(**base),
                           is_seq2seq=False)
            flat_outs = flat_eng.generate(cparams, c_reqs, max_new=c_budgets)
            serve["paged_vs_flat_causal"] = {
                "model": causal_name,
                **_serve_capacity(
                    clm, mesh, cparams, c_reqs, c_budgets,
                    slots=c_slots, src=c_src, new_tokens=c_new,
                    f32_stats=flat_eng.last_stats, f32_outs=flat_outs,
                ),
            }
        except Exception as e:
            print(f"bench: causal paged A/B failed ({e})", file=sys.stderr)
            serve["paged_vs_flat_causal"] = {"error": str(e)[:300]}
    print(json.dumps({
        "grad_compression": "off",
        "metric": f"{name} continuous-batching serving decode (slots {slots}, "
                  f"src {src} / max_new {new_tokens}, {n_req} requests with "
                  "varied per-request budgets) — serving/engine.py on mesh "
                  f"{mesh_spec or 'data=-1'}; no reference number exists "
                  "(BASELINE.md: none published)",
        "value": serve["decode_tokens_per_sec_chip"],
        "unit": "decode tokens/sec/chip",
        "vs_baseline": None,
        **{k: v for k, v in serve.items() if k != "decode_tokens_per_sec_chip"},
        "chips": n_chips,
        "backend": jax.default_backend(),
    }))


def _router_measure(
    lm, mesh, sharded, *,
    replicas: int, slots: int, src: int, new_tokens: int, n_req: int,
) -> dict:
    """Degraded-mode serving throughput (ISSUE 15): the same workload
    through the replica router twice — an unfailed ORACLE pass, then a
    pass with ``replica_crash`` injected at the oracle's halfway tick —
    stamping p99 TTFT and goodput BEFORE / DURING / AFTER the kill
    (phases cut at the router's failure / recovered instants), the
    request-level MTTR and retry counts, and the bit-identity verdict
    (greedy tokens of the failed run == the unfailed oracle's).  Engines
    are built once and reused across both passes (compiled programs are
    per-engine; a router 'crash' discards only session state)."""
    import numpy as np

    from distributed_llms_example_tpu.obs.chaos import parse_chaos
    from distributed_llms_example_tpu.serving.engine import (
        ServeConfig,
        ServingEngine,
    )
    from distributed_llms_example_tpu.serving.router import (
        ReplicaRouter,
        RouterConfig,
    )

    rng = np.random.RandomState(0)
    vocab_hi = min(lm.config.vocab_size, 30000)
    requests = [
        list(rng.randint(4, vocab_hi, rng.randint(max(src // 2, 8), src + 1)))
        for _ in range(n_req)
    ]
    budgets = [
        int(b)
        for b in rng.randint(max(new_tokens // 4, 1), new_tokens + 1, n_req)
    ]
    engines = [
        ServingEngine(
            lm.module, lm.config, mesh,
            ServeConfig(
                max_slots=slots, prefill_batch=slots,
                max_new_tokens=new_tokens, max_source_length=src,
                log_every_steps=0, request_spans=False,
            ),
            is_seq2seq=lm.is_seq2seq,
        )
        for _ in range(replicas)
    ]
    # oracle pass: unfailed run — the bit-identity reference AND the
    # compile/warm pass (both routers share the engines' programs)
    oracle = ReplicaRouter(engines, sharded, RouterConfig(log_every_ticks=0))
    oracle_outs = oracle.serve(requests, max_new=budgets)
    kill_tick = max(2, oracle.ticks // 2)
    for r in oracle.replicas:
        # only ticks + outputs are needed past this point: drop the
        # oracle sessions' serving state so the injected pass doesn't
        # hold 2x replicas worth of KV cache resident
        r.session = None
    injected = ReplicaRouter(
        engines, sharded,
        RouterConfig(
            log_every_ticks=0,
            chaos=parse_chaos(f"replica_crash@{kill_tick}"),
        ),
    )
    t0 = time.perf_counter()
    outs = injected.serve(requests, max_new=budgets)
    wall = time.perf_counter() - t0
    summary = injected.last_stats or {}
    rows = [r for r in injected.request_rows() if not r["synthetic"]]
    t_fail = summary.get("t_fail_s")
    t_rec = summary.get("t_recovered_s", t_fail)

    def phase_stats(lo: float, hi: float) -> dict:
        from distributed_llms_example_tpu.obs.spans import percentiles

        done = [
            r for r in rows
            if r["done_s"] is not None and lo <= r["done_s"] < hi
        ]
        ttfts = [r["ttft_s"] for r in done if r["ttft_s"] is not None]
        dur = max(hi - lo, 1e-9)
        (p99,) = percentiles(ttfts, (0.99,))
        return {
            "requests": len(done),
            "ttft_p99_ms": round(p99 * 1e3, 1) if ttfts else None,
            "goodput_tokens_per_sec": round(
                sum(r["tokens"] for r in done) / dur, 1
            ),
        }

    out: dict = {
        "replicas": replicas,
        "kill_tick": kill_tick,
        "retries": summary.get("retries"),
        "request_retry_rate": summary.get("request_retry_rate"),
        "request_mttr_s": summary.get("request_mttr_s"),
        "goodput_frac": summary.get("goodput_frac"),
        "completed": summary.get("completed"),
        "shed": summary.get("shed"),
        # the acceptance verdict: a mid-decode replica kill loses nothing
        # and changes no tokens (greedy re-prefill == unfailed oracle)
        "tokens_identical": outs == oracle_outs,
        "requests_lost": sum(
            1 for r in rows if r["done_s"] is None and not r["shed"]
        ),
        "wall_s": round(wall, 3),
    }
    if t_fail is not None:
        out["degraded"] = {
            "t_fail_s": t_fail,
            "t_recovered_s": t_rec,
            "before": phase_stats(0.0, t_fail),
            "during": phase_stats(t_fail, t_rec if t_rec > t_fail else t_fail),
            "after": phase_stats(t_rec, wall + 1e-9),
        }
    return out


def _router_main() -> None:
    """BENCH_MODE=serve-router: the standalone degraded-mode serving
    record — replica router over the flagship model, p99 TTFT + goodput
    before/during/after an injected replica kill."""
    import jax

    from distributed_llms_example_tpu.core.config import MeshConfig, parse_mesh_arg
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.parallel.sharding import shard_params

    name, lm, _ = _flagship()
    n_chips = jax.device_count()
    mesh_spec = os.environ.get("BENCH_SERVE_MESH", "")
    mesh = build_mesh(parse_mesh_arg(mesh_spec) if mesh_spec else MeshConfig(data=-1))
    batch_shards = 1
    for a in ("data", "fsdp", "expert"):
        batch_shards *= mesh.shape.get(a, 1)
    src = int(os.environ.get("BENCH_ROUTER_SRC", "256"))
    new_tokens = int(os.environ.get("BENCH_ROUTER_NEW", "32"))
    slots = int(os.environ.get("BENCH_ROUTER_SLOTS_PER_SHARD", "2")) * batch_shards
    replicas = int(os.environ.get("BENCH_ROUTER_REPLICAS", "2"))
    n_req = int(os.environ.get("BENCH_ROUTER_REQUESTS", str(4 * replicas * slots)))
    params = lm.params if lm.params is not None else jax.device_get(lm.init_params(0))
    sharded = shard_params(params, mesh)
    record = _router_measure(
        lm, mesh, sharded,
        replicas=replicas, slots=slots, src=src, new_tokens=new_tokens,
        n_req=n_req,
    )
    print(json.dumps({
        "grad_compression": "off",
        "metric": f"{name} serve-router degraded-mode serving "
                  f"({replicas} replicas x {slots} slots, src {src} / "
                  f"max_new {new_tokens}, {n_req} requests, one replica "
                  "killed mid-decode) — serving/router.py on mesh "
                  f"{mesh_spec or 'data=-1'}; no reference number exists",
        "value": (record.get("degraded") or {}).get("after", {}).get(
            "goodput_tokens_per_sec"
        ),
        "unit": "goodput tokens/sec after recovery",
        "vs_baseline": None,
        **record,
        "chips": n_chips,
        "backend": jax.default_backend(),
    }))


def _loadgen_measure(
    lm, mesh, sharded, *,
    slots: int, src: int, new_tokens: int, n_req: int,
    process: str, seed: int, qps_grid: tuple, slo_ms: float,
    max_wall_s: float, replicas: int, chaos_spec: str,
) -> dict:
    """Open-loop QPS sweep (ISSUE 17) vs the closed-loop measurement of
    the SAME engine config.  The closed-loop pass (generate: submit all,
    drain) is what every previous serving bench reported — its offered
    rate is capped by the service rate, so it reads healthy even when
    the config would collapse under real traffic.  The open-loop sweep
    offers seeded arrivals that never wait for completions, so the same
    config gains a saturation knee, per-rate goodput/SLO-attainment, and
    TTFT-from-arrival percentiles.  Two extra stamps: the determinism
    pin (open-loop tokens at the top of the grid == the closed-loop
    oracle's — arrival timing moves latency, never tokens) and, when
    ``replicas >= 1``, a second sweep through the replica router with
    ``chaos_spec`` injected per point (degraded-mode numbers AT a
    stated offered load)."""
    import numpy as np

    from distributed_llms_example_tpu.serving.engine import (
        ServeConfig,
        ServingEngine,
    )
    from distributed_llms_example_tpu.serving.loadgen import (
        EngineTarget,
        LoadgenConfig,
        RouterTarget,
        arrival_schedule,
        drive_open_loop,
        sweep_qps,
    )

    rng = np.random.RandomState(0)
    vocab_hi = min(lm.config.vocab_size, 30000)
    requests = [
        list(rng.randint(4, vocab_hi, rng.randint(max(src // 2, 8), src + 1)))
        for _ in range(n_req)
    ]
    budgets = [
        int(b)
        for b in rng.randint(max(new_tokens // 4, 1), new_tokens + 1, n_req)
    ]
    serve_cfg = ServeConfig(
        max_slots=slots, prefill_batch=slots,
        max_new_tokens=new_tokens, max_source_length=src,
        log_every_steps=0, request_spans=False, ttft_slo_ms=slo_ms,
    )
    engine = ServingEngine(
        lm.module, lm.config, mesh, serve_cfg, is_seq2seq=lm.is_seq2seq
    )
    # closed-loop measurement of the same config — the number that can
    # NEVER expose queueing collapse (and the determinism oracle)
    t0 = time.perf_counter()
    oracle_outs = engine.generate(sharded, requests, max_new=budgets)
    closed_wall = max(time.perf_counter() - t0, 1e-9)
    closed_stats = engine.last_stats
    cfg = LoadgenConfig(
        process=process, seed=seed, qps_grid=qps_grid,
        ttft_slo_ms=slo_ms, max_wall_s=max_wall_s,
    )
    summary = sweep_qps(
        lambda: EngineTarget(engine.open(sharded)),
        requests, cfg, budgets=budgets,
    )
    # determinism pin: an uncapped open-loop run at the top of the grid
    # must produce the oracle's tokens bit-for-bit
    sess = engine.open(sharded)
    sched = arrival_schedule(
        process, qps=float(qps_grid[-1]), n=n_req, seed=seed,
    )
    drive_open_loop(EngineTarget(sess), requests, sched, budgets=budgets)
    open_outs = [sess.output(r) for r in range(n_req)]
    out: dict = {
        "closed_loop": {
            "wall_s": round(closed_wall, 3),
            "decode_tokens_per_sec": round(
                sum(len(o) for o in oracle_outs) / closed_wall, 1
            ),
            "slo_attainment": (
                (closed_stats.goodput or {}).get("slo_attainment")
                if closed_stats else None
            ),
        },
        "loadgen": summary,
        "tokens_identical_to_closed_loop": open_outs == oracle_outs,
    }
    if replicas >= 1:
        from distributed_llms_example_tpu.obs.chaos import parse_chaos
        from distributed_llms_example_tpu.serving.router import (
            ReplicaRouter,
            RouterConfig,
        )

        engines = [
            ServingEngine(
                lm.module, lm.config, mesh, serve_cfg,
                is_seq2seq=lm.is_seq2seq,
            )
            for _ in range(replicas)
        ]
        router_cfg = RouterConfig(
            log_every_ticks=0,
            chaos=parse_chaos(chaos_spec) if chaos_spec else None,
        )
        chaos_summary = sweep_qps(
            lambda: RouterTarget(ReplicaRouter(engines, sharded, router_cfg)),
            requests, cfg, budgets=budgets,
        )
        out["router_sweep"] = {
            "replicas": replicas,
            "chaos": chaos_spec or None,
            **chaos_summary,
        }
    return out


def _loadgen_main() -> None:
    """BENCH_MODE=serve-loadgen: the standalone open-loop load record —
    offered-QPS sweep over the flagship model with the closed-loop
    measurement of the same config stamped beside it."""
    import jax

    from distributed_llms_example_tpu.core.config import MeshConfig, parse_mesh_arg
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.parallel.sharding import shard_params

    name, lm, _ = _flagship()
    n_chips = jax.device_count()
    mesh_spec = os.environ.get("BENCH_SERVE_MESH", "")
    mesh = build_mesh(parse_mesh_arg(mesh_spec) if mesh_spec else MeshConfig(data=-1))
    batch_shards = 1
    for a in ("data", "fsdp", "expert"):
        batch_shards *= mesh.shape.get(a, 1)
    src = int(os.environ.get("BENCH_LOADGEN_SRC", "256"))
    new_tokens = int(os.environ.get("BENCH_LOADGEN_NEW", "32"))
    slots = int(os.environ.get("BENCH_LOADGEN_SLOTS_PER_SHARD", "2")) * batch_shards
    n_req = int(os.environ.get("BENCH_LOADGEN_REQUESTS", str(4 * slots)))
    process = os.environ.get("BENCH_LOADGEN_PROCESS", "poisson")
    seed = int(os.environ.get("BENCH_LOADGEN_SEED", "0"))
    qps_grid = tuple(
        float(q)
        for q in os.environ.get("BENCH_LOADGEN_QPS_GRID", "0.5,1,2,4,8").split(",")
        if q.strip()
    )
    slo_ms = float(os.environ.get("BENCH_LOADGEN_SLO_MS", "500"))
    max_wall_s = float(os.environ.get("BENCH_LOADGEN_MAX_WALL_S", "120"))
    replicas = int(os.environ.get("BENCH_LOADGEN_REPLICAS", "0"))
    chaos_spec = os.environ.get("BENCH_LOADGEN_CHAOS", "")
    params = lm.params if lm.params is not None else jax.device_get(lm.init_params(0))
    sharded = shard_params(params, mesh)
    record = _loadgen_measure(
        lm, mesh, sharded,
        slots=slots, src=src, new_tokens=new_tokens, n_req=n_req,
        process=process, seed=seed, qps_grid=qps_grid, slo_ms=slo_ms,
        max_wall_s=max_wall_s, replicas=replicas, chaos_spec=chaos_spec,
    )
    print(json.dumps({
        "grad_compression": "off",
        "metric": f"{name} open-loop load sweep ({process} arrivals, "
                  f"QPS grid {list(qps_grid)}, {n_req} requests/point, "
                  f"slots {slots}, src {src} / max_new {new_tokens}, "
                  f"TTFT SLO {slo_ms:.0f} ms) — serving/loadgen.py on "
                  f"mesh {mesh_spec or 'data=-1'}; no reference number "
                  "exists",
        "value": record["loadgen"].get("knee_qps"),
        "unit": "offered QPS at the saturation knee",
        "vs_baseline": None,
        **record,
        "chips": n_chips,
        "backend": jax.default_backend(),
    }))


def _prefix_measure(
    clm, mesh, cparams, *,
    slots: int, src: int, new_tokens: int,
    sessions: int, turns: int, seed: int, budget_gib: float,
) -> dict:
    """The prefix-cache A/B (ISSUE 19): the seeded chatbot shared-prefix
    mix (serving/loadgen.py ``chatbot_requests`` — shared system prompt,
    multi-turn growing histories, turn-major arrival) through the SAME
    paged engine config twice — cold (prefix cache off, the baseline
    every previous serving bench measured) and warm (``--prefix-cache``
    with an LRU warm-retention budget).  Stamps the acceptance pins:
    tokens bit-identical to cold, hit_rate, prefill_tokens_saved_frac,
    tokens/sec/chip and p95 TTFT for both legs."""
    import jax

    from distributed_llms_example_tpu.serving.engine import (
        ServeConfig,
        ServingEngine,
    )
    from distributed_llms_example_tpu.serving.loadgen import chatbot_requests

    requests, _keys = chatbot_requests(
        sessions=sessions, turns=turns, seed=seed,
        vocab=min(clm.config.vocab_size, 1000),
        system_len=max(src * 3 // 4, 8), user_len=(2, 4), reply_len=(2, 4),
        max_len=src,
    )
    base = dict(
        max_slots=slots, prefill_batch=slots, max_new_tokens=new_tokens,
        max_source_length=src, log_every_steps=0, request_spans=False,
        # block size 8, not the auto (largest-valid) default: match
        # granularity IS the block size — a turn's uncached delta is a
        # handful of tokens, and coarse blocks round every chain down.
        # Pool at 4x the slots' worst case: warm retention lives in the
        # pool's free headroom (evicted strictly at refcount 0 under
        # allocation pressure), and a worst-case-exact pool has none
        paged_kv=True, kv_block_size=8,
        pool_blocks=4 * slots * ((src + new_tokens) // 8),
    )
    n_chips = max(jax.device_count(), 1)

    def run(**kw):
        eng = ServingEngine(
            clm.module, clm.config, mesh, ServeConfig(**base, **kw),
            is_seq2seq=False,
        )
        t0 = time.perf_counter()
        outs = eng.generate(cparams, requests)
        return eng, outs, max(time.perf_counter() - t0, 1e-9)

    cold_eng, cold_outs, cold_wall = run()
    cs = cold_eng.last_stats
    warm_eng, warm_outs, warm_wall = run(
        prefix_cache=True, prefix_cache_budget_gib=budget_gib,
    )
    ws = warm_eng.last_stats
    _, c95 = cs.ttft_percentiles()
    _, w95 = ws.ttft_percentiles()
    return {
        "requests": len(requests),
        "chat_sessions": sessions,
        "chat_turns": turns,
        "kv_block_size": warm_eng.block_size,
        "prefix_cache_budget": budget_gib,
        # the acceptance pin: warm-path tokens == cold-start tokens
        "bit_identical": list(warm_outs) == list(cold_outs),
        "hit_rate": round(ws.prefix_hits / max(ws.prefix_lookups, 1), 4),
        "prefill_tokens_total": ws.prefill_tokens_total,
        "prefill_tokens_saved": ws.prefill_tokens_saved,
        "prefill_tokens_saved_frac": round(
            ws.prefill_tokens_saved / max(ws.prefill_tokens_total, 1), 4
        ),
        "decode_tokens_per_sec_chip": round(ws.tokens_per_sec() / n_chips, 1),
        "decode_tokens_per_sec_chip_cold": round(
            cs.tokens_per_sec() / n_chips, 1
        ),
        "ttft_p95_ms": round(w95 * 1e3, 1),
        "ttft_p95_ms_cold": round(c95 * 1e3, 1),
        "prefill_seconds": round(ws.prefill_seconds, 3),
        "prefill_seconds_cold": round(cs.prefill_seconds, 3),
        "wall_s": round(warm_wall, 3),
        "wall_s_cold": round(cold_wall, 3),
    }


def _prefix_main() -> None:
    """BENCH_MODE=serve-prefix: the standalone prefix-caching record —
    chatbot shared-prefix mix, warm vs cold, on a causal paged engine
    (the flagship is seq2seq; prefix caching shares the causal paged
    pool, so the record runs on BENCH_PREFIX_MODEL, default the
    registry's causal test model — random init is fine: greedy decode is
    deterministic and every claim here is weight-independent)."""
    import jax

    from distributed_llms_example_tpu.core.config import MeshConfig, parse_mesh_arg
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.models.registry import load_model
    from distributed_llms_example_tpu.parallel.sharding import shard_params

    name = os.environ.get("BENCH_PREFIX_MODEL", "llama-test")
    clm = load_model(name)
    if clm.is_seq2seq:
        raise SystemExit(
            f"BENCH_PREFIX_MODEL={name!r} is seq2seq; the prefix cache "
            "shares the causal paged pool — pick a causal model"
        )
    n_chips = jax.device_count()
    mesh_spec = os.environ.get("BENCH_SERVE_MESH", "")
    mesh = build_mesh(parse_mesh_arg(mesh_spec) if mesh_spec else MeshConfig(data=-1))
    batch_shards = 1
    for a in ("data", "fsdp", "expert"):
        batch_shards *= mesh.shape.get(a, 1)
    src = int(os.environ.get("BENCH_PREFIX_SRC", "64"))
    new_tokens = int(os.environ.get("BENCH_PREFIX_NEW", "16"))
    slots = int(os.environ.get("BENCH_PREFIX_SLOTS_PER_SHARD", "2")) * batch_shards
    sessions = int(os.environ.get("BENCH_PREFIX_SESSIONS", "6"))
    turns = int(os.environ.get("BENCH_PREFIX_TURNS", "5"))
    seed = int(os.environ.get("BENCH_PREFIX_SEED", "0"))
    budget_gib = float(os.environ.get("BENCH_PREFIX_BUDGET_GIB", "0.5"))
    params = clm.params if clm.params is not None else jax.device_get(clm.init_params(0))
    sharded = shard_params(params, mesh)
    record = _prefix_measure(
        clm, mesh, sharded,
        slots=slots, src=src, new_tokens=new_tokens,
        sessions=sessions, turns=turns, seed=seed, budget_gib=budget_gib,
    )
    print(json.dumps({
        "grad_compression": "off",
        "metric": f"{name} prefix-cache warm vs cold serving "
                  f"(chatbot mix: {sessions} sessions x {turns} turns, "
                  f"slots {slots}, src {src} / max_new {new_tokens}, "
                  f"warm budget {budget_gib} GiB) — serving/cache_pool.py "
                  f"content-hash block dedup on mesh {mesh_spec or 'data=-1'}; "
                  "no reference number exists",
        "value": record["prefill_tokens_saved_frac"],
        "unit": "fraction of prefill tokens served from cache",
        "vs_baseline": None,
        **{k: v for k, v in record.items() if k != "prefill_tokens_saved_frac"},
        "chips": n_chips,
        "backend": jax.default_backend(),
    }))


def _spec_measure(
    clm, mesh, cparams, *,
    slots: int, src: int, new_tokens: int,
    sessions: int, turns: int, seed: int,
    spec_tokens: int, draft_model: str,
) -> dict:
    """The speculative-decode A/B (ISSUE 20): the seeded chatbot mix
    through the SAME paged engine config twice — plain greedy (the
    baseline) and draft-then-verify (``--spec-tokens k``, n-gram
    self-drafting by default or ``draft_model`` through the registry).
    Both legs decode the mix's scripted per-turn reply lengths
    (``chatbot_requests(with_budgets=True)``) as per-request budgets, so
    the token counts are identical by construction — apples-to-apples.
    Stamps the acceptance pins: tokens bit-identical to plain,
    accepted_tokens_per_step (per-slot; plain decode ≡ 1.0),
    acceptance_rate, decode tok/s both legs and ``vs_plain`` (relative
    decode-throughput delta, positive = speculation won), p95 TTFT both
    legs (speculation must not touch prefill)."""
    import jax

    from distributed_llms_example_tpu.serving.engine import (
        ServeConfig,
        ServingEngine,
    )
    from distributed_llms_example_tpu.serving.loadgen import chatbot_requests

    requests, _keys, budgets = chatbot_requests(
        sessions=sessions, turns=turns, seed=seed,
        vocab=min(clm.config.vocab_size, 1000),
        system_len=max(src * 3 // 4, 8), user_len=(2, 4),
        # scripted replies span up to the decode cap: speculation needs
        # room (acceptance is clamped to budget - emitted - 1), and a
        # 2..4-token reply would pin every round to partial acceptance
        reply_len=(4, max(new_tokens, 5)),
        max_len=src, with_budgets=True,
    )
    base = dict(
        max_slots=slots, prefill_batch=slots, max_new_tokens=new_tokens,
        max_source_length=src, log_every_steps=0, request_spans=False,
        # same pool shape as the prefix A/B: block size 8 keeps rollback
        # granularity honest, 4x-worst-case headroom keeps admission off
        # the critical path
        paged_kv=True, kv_block_size=8,
        pool_blocks=4 * slots * ((src + new_tokens) // 8),
    )
    n_chips = max(jax.device_count(), 1)

    def run(**kw):
        eng = ServingEngine(
            clm.module, clm.config, mesh, ServeConfig(**base, **kw),
            is_seq2seq=False,
        )
        t0 = time.perf_counter()
        outs = eng.generate(cparams, requests, max_new=budgets)
        return eng, outs, max(time.perf_counter() - t0, 1e-9)

    plain_eng, plain_outs, plain_wall = run()
    ps = plain_eng.last_stats
    spec_eng, spec_outs, spec_wall = run(
        spec_tokens=spec_tokens, spec_draft_model=draft_model,
    )
    ss = spec_eng.last_stats
    _, p95_plain = ps.ttft_percentiles()
    _, p95_spec = ss.ttft_percentiles()
    plain_tps = ps.tokens_per_sec()
    spec_tps = ss.tokens_per_sec()
    return {
        "requests": len(requests),
        "chat_sessions": sessions,
        "chat_turns": turns,
        "decode_budget_tokens": int(sum(budgets)),
        "spec_tokens": spec_tokens,
        "spec_draft_model": draft_model or "ngram",
        # the acceptance pin: speculative tokens == plain greedy tokens
        "bit_identical": list(spec_outs) == list(plain_outs),
        "accepted_tokens_per_step": round(
            ss.spec_emitted / max(ss.spec_slot_rounds, 1), 4
        ),
        "acceptance_rate": round(
            ss.spec_accepted / max(ss.spec_drafted, 1), 4
        ),
        "spec_drafted_tokens": ss.spec_drafted,
        "spec_accepted_tokens": ss.spec_accepted,
        "decode_tokens_per_sec_chip": round(spec_tps / n_chips, 1),
        "decode_tokens_per_sec_chip_plain": round(plain_tps / n_chips, 1),
        "vs_plain": round(spec_tps / max(plain_tps, 1e-9) - 1.0, 4),
        "ttft_p95_ms": round(p95_spec * 1e3, 1),
        "ttft_p95_ms_plain": round(p95_plain * 1e3, 1),
        "wall_s": round(spec_wall, 3),
        "wall_s_plain": round(plain_wall, 3),
    }


def _spec_main() -> None:
    """BENCH_MODE=serve-spec: the standalone speculative-decode record —
    chatbot mix, spec vs plain, on a causal paged engine
    (BENCH_SPEC_MODEL, default the registry's causal test model — random
    init is fine: greedy decode is deterministic, the acceptance rule is
    argmax-exact, and every claim here is weight-independent; the tok/s
    delta is a TPU verdict, CPU pins correctness and the acceptance
    ledger)."""
    import jax

    from distributed_llms_example_tpu.core.config import MeshConfig, parse_mesh_arg
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.models.registry import load_model
    from distributed_llms_example_tpu.parallel.sharding import shard_params

    name = os.environ.get("BENCH_SPEC_MODEL", "llama-test")
    clm = load_model(name)
    if clm.is_seq2seq:
        raise SystemExit(
            f"BENCH_SPEC_MODEL={name!r} is seq2seq; speculation verifies "
            "through the causal decode path — pick a causal model"
        )
    n_chips = jax.device_count()
    mesh_spec = os.environ.get("BENCH_SERVE_MESH", "")
    mesh = build_mesh(parse_mesh_arg(mesh_spec) if mesh_spec else MeshConfig(data=-1))
    batch_shards = 1
    for a in ("data", "fsdp", "expert"):
        batch_shards *= mesh.shape.get(a, 1)
    src = int(os.environ.get("BENCH_SPEC_SRC", "64"))
    new_tokens = int(os.environ.get("BENCH_SPEC_NEW", "16"))
    slots = int(os.environ.get("BENCH_SPEC_SLOTS_PER_SHARD", "2")) * batch_shards
    sessions = int(os.environ.get("BENCH_SPEC_SESSIONS", "6"))
    turns = int(os.environ.get("BENCH_SPEC_TURNS", "5"))
    seed = int(os.environ.get("BENCH_SPEC_SEED", "0"))
    spec_tokens = int(os.environ.get("BENCH_SPEC_TOKENS", "3"))
    draft = os.environ.get("BENCH_SPEC_DRAFT", "")
    params = clm.params if clm.params is not None else jax.device_get(clm.init_params(0))
    sharded = shard_params(params, mesh)
    record = _spec_measure(
        clm, mesh, sharded,
        slots=slots, src=src, new_tokens=new_tokens,
        sessions=sessions, turns=turns, seed=seed,
        spec_tokens=spec_tokens, draft_model=draft,
    )
    print(json.dumps({
        "grad_compression": "off",
        "metric": f"{name} speculative vs plain greedy decode "
                  f"(chatbot mix: {sessions} sessions x {turns} turns, "
                  f"slots {slots}, src {src} / max_new {new_tokens}, "
                  f"k={spec_tokens}, draft {draft or 'ngram'}) — "
                  f"serving/spec.py draft-then-verify on mesh "
                  f"{mesh_spec or 'data=-1'}; no reference number exists",
        "value": record["accepted_tokens_per_step"],
        "unit": "accepted tokens per verify step per slot (plain = 1.0)",
        "vs_baseline": None,
        **{k: v for k, v in record.items() if k != "accepted_tokens_per_step"},
        "chips": n_chips,
        "backend": jax.default_backend(),
    }))


def main() -> None:
    # Wall-clock budget for the add-on measurements (grad-accum, dropout,
    # rbg-dropout, trainer loop, trainer-rbg): each compiles its own
    # program, and on a cold cache the full menu runs ~25 min.  With
    # BENCH_CHILD_BUDGET set the gate is ADAPTIVE: each add-on states its
    # estimated cost (scaled from the measured cost of the comparable pass
    # — compile time and measure window are both known after the headline)
    # and runs iff the estimate fits the time left.  Every skip is logged to
    # stderr AND stamped into the result JSON (``skipped_passes``) — a
    # silently missing field reads as "measured, nothing to report", which
    # is exactly wrong.  Unset, the full menu is measured.
    _t0 = time.monotonic()
    _child_budget = float(os.environ.get("BENCH_CHILD_BUDGET") or "inf")
    skipped_passes: list[str] = []

    def over_budget(what: str, est: float = 0.0) -> bool:
        elapsed = time.monotonic() - _t0
        if elapsed + est > _child_budget:
            msg = (
                f"{what} skipped (elapsed {elapsed:.0f}s + estimated "
                f"{est:.0f}s > BENCH_CHILD_BUDGET {_child_budget:.0f}s)"
            )
            print(f"bench: {msg}", file=sys.stderr)
            skipped_passes.append(msg)
            return True
        return False

    import jax
    import numpy as np

    from distributed_llms_example_tpu.core.config import MeshConfig
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.data.batching import LABEL_PAD
    from distributed_llms_example_tpu.ops.fused_optim import (
        resolve_impl as resolve_optim_impl,
    )
    from distributed_llms_example_tpu.parallel.sharding import shard_params
    from distributed_llms_example_tpu.train.optim import make_optimizer_bundle
    from distributed_llms_example_tpu.train.step import (
        create_train_state,
        make_train_step,
        put_batch,
        state_shardings,
    )

    name, lm, remat = _flagship()
    n_chips = jax.device_count()
    mesh = build_mesh(MeshConfig(data=-1))

    src_len, tgt_len = 1024, 128
    batch = int(os.environ.get("BENCH_BATCH", "16")) * n_chips
    steps = max(1, int(os.environ.get("BENCH_STEPS", "5")))
    # the production-default optimizer path for every synthetic pass
    # (--optim-impl auto = fused Pallas clip+AdamW on TPU, optax
    # elsewhere); the optim A/B add-on below re-measures the other impl
    optim_impl = os.environ.get("BENCH_OPTIM_IMPL", "auto")
    resolved_optim = resolve_optim_impl(optim_impl)
    # gradient-collective compression for the headline step (default off —
    # the A/B add-on below measures int8 against it in-session; a TPU
    # round can flip the headline itself with BENCH_GRAD_COMPRESSION=int8)
    grad_compression = os.environ.get("BENCH_GRAD_COMPRESSION", "off")
    if grad_compression == "int8":
        # same guard the trainer applies: without partitionable threefry
        # the stochastic-rounding bits lower through u32 collectives as
        # large as the gradient traffic the compression removes, skewing
        # every number this session stamps
        jax.config.update("jax_threefry_partitionable", True)

    rng = np.random.RandomState(0)
    vocab = lm.config.vocab_size
    b = {
        "input_ids": rng.randint(2, min(vocab, 30000), (batch, src_len)).astype(np.int32),
        "attention_mask": np.ones((batch, src_len), np.int32),
        "labels": rng.randint(2, min(vocab, 30000), (batch, tgt_len)).astype(np.int32),
    }
    b["labels"][:, -8:] = LABEL_PAD

    tx, schedule, optim_spec = make_optimizer_bundle(
        learning_rate=5e-5, warmup_steps=0, total_steps=1000
    )
    from distributed_llms_example_tpu.ops.quant_collectives import (
        attach_error_feedback,
        worker_count,
    )

    grad_workers = worker_count(dict(mesh.shape))

    def _fresh_state(mode: str):
        """A FRESH state from re-sharded initial params (the A/B arms
        need identical re-inits; the evolving headline state's buffers
        are donated).  Under int8 the EF tree is allocated
        sharded-at-birth (attach_error_feedback) — a default-device
        zeros tree would sit W x params x 4B whole on chip 0."""
        p0 = lm.params if lm.params is not None else jax.device_get(lm.init_params(0))
        st = create_train_state(shard_params(p0, mesh), tx)
        shm = state_shardings(st, mesh)
        if mode == "int8":
            st, shm = attach_error_feedback(st, shm, mesh, grad_workers)
        return jax.tree.map(lambda x, s: jax.device_put(x, s), st, shm), shm

    # the headline state ALIASES the one sharded param tree (`params` is
    # only read for sizes below) — a second resident copy here would
    # double param memory for the whole bench
    params = lm.params if lm.params is not None else jax.device_get(lm.init_params(0))
    params = shard_params(params, mesh)
    state = create_train_state(params, tx)
    sh = state_shardings(state, mesh)
    if grad_compression == "int8":
        state, sh = attach_error_feedback(state, sh, mesh, grad_workers)
    state = jax.tree.map(lambda x, s: jax.device_put(x, s), state, sh)
    build = make_train_step(
        lm.module, lm.config, tx, schedule, mesh,
        optim_spec=optim_spec, optim_impl=optim_impl,
        grad_compression=grad_compression,
    )
    step_fn, _ = build(state)
    gb = put_batch(b, mesh)

    # Sync via host readbacks: a scalar device_get of the loss plus one
    # updated parameter element forces the full step chain.
    def sync(state, metrics) -> float:
        leaf = jax.tree.leaves(state.params)[0]
        _ = jax.device_get(leaf.ravel()[0])
        return float(jax.device_get(metrics["loss"]))

    tokens_per_step = int(np.sum(b["attention_mask"])) + int(np.sum(b["labels"] != LABEL_PAD))
    n_params = int(sum(x.size for x in jax.tree.leaves(params)))

    # Per-step FLOPs: compiler cost analysis of the actual program when the
    # backend reports it, else the standard 6*N*tokens training estimate
    # (fwd 2N + bwd 4N matmul FLOPs per token; attention excluded, so MFU
    # is slightly conservative relative to true utilization).
    from distributed_llms_example_tpu.parallel.activation import activation_mesh

    flops_per_step = 0.0
    lowered = None
    try:
        # HLO-level analysis on the Lowered stage: no second backend compile.
        # Must lower under the mesh context — jit caches the traced jaxpr,
        # and a trace made without the ambient mesh would bake constraint
        # no-ops into the very program the benchmark then measures.
        with activation_mesh(step_fn.mesh):
            lowered = step_fn.jitted.lower(state, gb)
        ca = lowered.cost_analysis()
        if isinstance(ca, list):  # some backends return one dict per device
            ca = ca[0] if ca else {}
        flops_per_step = float((ca or {}).get("flops", 0.0))
    except Exception as e:
        print(f"bench: cost_analysis unavailable ({e}); using 6*N*tokens", file=sys.stderr)
    if flops_per_step <= 0.0:
        flops_per_step = 6.0 * n_params * tokens_per_step

    # Per-step collective-traffic account (obs/gauges.py) from the compiled
    # step's HLO — gradient vs activation bytes per collective op.  The AOT
    # compile shares the persistent compilation cache with the first jit
    # call, so this costs one disk hit, not a second real compile.
    comm_bytes = None
    if lowered is not None and os.environ.get("BENCH_COMM_BYTES", "1") != "0":
        try:
            from distributed_llms_example_tpu.obs.gauges import collective_traffic

            comm_bytes = collective_traffic(
                lowered.compile().as_text(),
                [int(x.size) for x in jax.tree.leaves(params)],
                n_chips,
            )
        except Exception as e:
            print(f"bench: collective-traffic account unavailable ({e})", file=sys.stderr)

    # warmup/compile — timed: the compile cost is the dominant unknown in
    # every add-on's budget estimate below (cache hits make it small,
    # cold compiles make it the whole story)
    t0 = time.perf_counter()
    for _ in range(2):
        state, metrics = step_fn(state, gb)
    sync(state, metrics)
    compile_s = time.perf_counter() - t0

    # throughput: one sync at the end so async dispatch can overlap steps —
    # the same pipelining the trainer gets (a per-step readback here would
    # deflate tokens/sec by the host round-trip)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step_fn(state, gb)
    loss = sync(state, metrics)
    dt = time.perf_counter() - t0
    assert loss == loss, "non-finite loss"

    # one compile + warm + timed window, the shape of every synthetic
    # add-on pass below — the adaptive budget gate scales from it
    est_step_pass = compile_s + 2.5 * dt

    # step-time distribution: a separate pass with a readback per step
    # (sync-inclusive — upper bounds on single-step latency, not 1/throughput)
    times = []
    for _ in range(steps):
        t1 = time.perf_counter()
        state, metrics = step_fn(state, gb)
        sync(state, metrics)
        times.append(time.perf_counter() - t1)

    peak_flops = _device_peak_flops()
    from distributed_llms_example_tpu.obs.spans import percentiles

    order = sorted(times)
    p50, p95 = percentiles(times, (0.50, 0.95))
    tps = tokens_per_step * steps / dt
    tps_chip = tps / n_chips
    mfu = flops_per_step * steps / dt / (n_chips * peak_flops)

    result = {
        "metric": f"{name} seq2seq fine-tune train-step throughput "
                  f"(src1024/tgt128, bf16{'+remat' if remat else ''}, batch {batch})",
        "value": round(tps_chip, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(tps_chip / BASELINE_TOKENS_PER_SEC_PER_CHIP, 3),
        "mfu": round(mfu, 4),
        "model_flops_per_token": round(flops_per_step / tokens_per_step),
        "params": n_params,
        "chips": n_chips,
        "backend": jax.default_backend(),
        "step_time_ms_sync_inclusive": {
            "p50": round(p50 * 1e3, 1),
            "p90": round(order[min(len(order) - 1, int(0.9 * len(order)))] * 1e3, 1),
            "p95": round(p95 * 1e3, 1),
            "min": round(order[0] * 1e3, 1),
            "max": round(order[-1] * 1e3, 1),
        },
    }
    if comm_bytes is not None:
        result["comm_bytes_per_step"] = comm_bytes
    # the synthetic passes below drive their own keys: headline has no
    # dropout; the with-dropout pass feeds threefry keys, the rbg add-on
    # hardware-RNG keys, and the fused add-on flips --dropout-impl —
    # stamp both knobs so BENCH_*.json rows stay comparable across rounds
    result["dropout_impl"] = "xla"
    result["prng_impl"] = "threefry"
    result["optim_impl"] = resolved_optim  # headline optimizer path (auto-resolved)
    result["grad_accum_steps"] = 1  # the headline step; the A/B below adds accum>1
    result["grad_compression"] = grad_compression  # headline wire mode

    # Emit the record NOW and again after each add-on lands, so every
    # field measured before a kill survives on stdout.
    # Consumers take the LAST result line (module docstring contract).
    # Every emit carries the skip log (the no-silent-caps rule: a missing
    # field must say WHY it is missing).
    def emit_result() -> None:
        if skipped_passes:
            result["skipped_passes"] = list(skipped_passes)
        print(json.dumps(result), flush=True)

    emit_result()

    # grad-accumulation A/B: the SAME effective batch cut into 4 in-step
    # microbatches (lax.scan, fp32 accumulators sharded like the params,
    # one optimizer apply per step).  tokens/sec at the same effective
    # batch compares directly; the ratio is the accumulation overhead vs
    # ideal linear scaling (acceptance bar: >= 0.95 at accum=4).
    accum_n = int(os.environ.get("BENCH_ACCUM", "4"))
    if accum_n > 1 and batch % accum_n:
        # a config skip is still a skip (no-silent-caps): a missing
        # grad_accum field must not read as "measured, nothing to report"
        msg = (
            f"grad-accum step skipped (batch {batch} not divisible by "
            f"BENCH_ACCUM={accum_n})"
        )
        print(f"bench: {msg}", file=sys.stderr)
        skipped_passes.append(msg)
    elif accum_n > 1 and not over_budget("grad-accum step", est_step_pass):
        try:
            build_a = make_train_step(
                lm.module, lm.config, tx, schedule, mesh, grad_accum_steps=accum_n,
                optim_spec=optim_spec, optim_impl=optim_impl,
            )
            step_a, _ = build_a(state)
            for _ in range(2):
                state, metrics = step_a(state, gb)
            sync(state, metrics)
            t0 = time.perf_counter()
            for _ in range(steps):
                state, metrics = step_a(state, gb)
            sync(state, metrics)
            dta = time.perf_counter() - t0
            tps_chip_accum = round(tokens_per_step * steps / dta / n_chips, 1)
            result["grad_accum"] = {
                "steps": accum_n,
                "tokens_per_sec_chip": tps_chip_accum,
                # tokens/sec ratio at equal effective batch == ideal-linear-
                # scaling fraction; 1 - ratio is the per-step scan overhead
                "vs_accum1": round(tps_chip_accum / tps_chip, 3),
                "overhead_frac": round(1.0 - tps_chip_accum / tps_chip, 4),
                "overhead_ok": bool(tps_chip_accum / tps_chip >= 0.95),
            }
            emit_result()
        except Exception as e:
            print(f"bench: grad-accum bench failed ({e})", file=sys.stderr)
            # a failed accum step may have consumed (donated) the state
            # buffers mid-execution — rebuild so the health/dropout/rbg
            # add-ons below don't all die on 'Array has been deleted'.
            # Drop the dead tree FIRST: if the failure was an OOM before
            # donation, old + replacement living at once would OOM the
            # rebuild itself and lose every already-measured field
            state = None
            state, _ = _fresh_state(grad_compression)

    # health-telemetry overhead: the SAME step compiled with the in-graph
    # numerics (param norm, per-bucket update ratios, non-finite counts —
    # train/step.py health_metrics).  The contract is <2% vs the plain
    # step: a handful of elementwise reductions must stay invisible next
    # to the matmuls, or --health on costs real throughput at scale.
    max_overhead = float(os.environ.get("BENCH_HEALTH_MAX_OVERHEAD", "0.02"))
    if os.environ.get("BENCH_HEALTH", "1") != "0" and not over_budget("health step", est_step_pass):
        try:
            build_h = make_train_step(
                lm.module, lm.config, tx, schedule, mesh, health=True,
                optim_spec=optim_spec, optim_impl=optim_impl,
            )
            step_h, _ = build_h(state)
            for _ in range(2):
                state, metrics = step_h(state, gb)
            sync(state, metrics)
            t0 = time.perf_counter()
            for _ in range(steps):
                state, metrics = step_h(state, gb)
            sync(state, metrics)
            dth = time.perf_counter() - t0
            tps_chip_health = tokens_per_step * steps / dth / n_chips
            overhead = 1.0 - tps_chip_health / tps_chip
            result["health_tokens_per_sec_chip"] = round(tps_chip_health, 1)
            result["health_overhead_frac"] = round(overhead, 4)
            result["health_overhead_ok"] = bool(overhead <= max_overhead)
            if overhead > max_overhead:
                print(
                    f"bench: HEALTH OVERHEAD {overhead:.1%} exceeds the "
                    f"{max_overhead:.0%} budget — the in-graph numerics are "
                    "on the critical path",
                    file=sys.stderr,
                )
            emit_result()
        except Exception as e:
            print(f"bench: health-step bench failed ({e})", file=sys.stderr)

    # fused-optim A/B: the SAME step rebuilt on the optax chain
    # (--optim-impl xla) when the headline resolved to the fused Pallas
    # apply — same session, same shapes, so the tokens/sec delta IS the
    # optimizer-apply component the budget account's optimizer_apply_ms
    # gauge tracks per-window in the trainer loop below.
    if resolved_optim == "fused" and os.environ.get("BENCH_OPTIM_AB", "1") != "0":
        if not over_budget("optim xla A/B step", est_step_pass):
            try:
                build_o = make_train_step(
                    lm.module, lm.config, tx, schedule, mesh,
                    optim_spec=optim_spec, optim_impl="xla",
                )
                step_o, _ = build_o(state)
                for _ in range(2):
                    state, metrics = step_o(state, gb)
                sync(state, metrics)
                t0 = time.perf_counter()
                for _ in range(steps):
                    state, metrics = step_o(state, gb)
                sync(state, metrics)
                dto = time.perf_counter() - t0
                tps_chip_xla_optim = round(tokens_per_step * steps / dto / n_chips, 1)
                result["optim_ab"] = {
                    "xla_tokens_per_sec_chip": tps_chip_xla_optim,
                    # headline(fused) over xla: >1.0 = the fused apply won
                    "fused_vs_xla_optim": round(tps_chip / tps_chip_xla_optim, 3),
                }
                emit_result()
            except Exception as e:
                print(f"bench: optim A/B bench failed ({e})", file=sys.stderr)
    elif resolved_optim != "fused":
        # a config skip is still a skip (no-silent-caps)
        msg = f"optim A/B skipped (headline already {resolved_optim}; fused needs TPU or --optim-impl fused)"
        print(f"bench: {msg}", file=sys.stderr)
        skipped_passes.append(msg)

    # grad-compression A/B: the step rebuilt with --grad-compression int8
    # (ops/quant_collectives.py: per-worker partial grads, s8 wire, error
    # feedback) vs off, SAME session/shapes/seed.  Both arms restart from
    # an identical fresh init so the loss trajectories are comparable;
    # the byte delta comes from the compiled programs' collective
    # accounts (the same classifier the obs gauges use).  Measured
    # per-collective ms + achieved bytes/sec ride the trainer-loop
    # bench's profiled device account (BENCH_DEVICE_PROFILE) — on CPU
    # rounds that capture is auto-skipped, so the A/B stamps the static
    # byte verdict and the TPU round upgrades it to measured bandwidth.
    ab_steps = max(2, int(os.environ.get("BENCH_GRAD_COMPRESSION_STEPS", "4")))
    comp_modes = ("off", "int8")
    if os.environ.get("BENCH_GRAD_COMPRESSION_AB", "1") == "0":
        msg = "grad-compression A/B skipped (BENCH_GRAD_COMPRESSION_AB=0)"
        print(f"bench: {msg}", file=sys.stderr)
        skipped_passes.append(msg)
    elif batch % max(1, grad_workers):
        msg = (
            f"grad-compression A/B skipped (batch {batch} not divisible "
            f"by {grad_workers} worker groups)"
        )
        print(f"bench: {msg}", file=sys.stderr)
        skipped_passes.append(msg)
    elif not over_budget("grad-compression A/B", 3 * est_step_pass):
        try:
            from distributed_llms_example_tpu.analysis.ir_lint import (
                quantized_gradient_census,
            )
            from distributed_llms_example_tpu.obs.gauges import (
                collective_traffic as _ctraffic,
            )

            # counts need SHAPES only — never materialize params for them
            a_params = jax.eval_shape(lambda: lm.init_params(0))
            leaf_counts = [
                int(np.prod(x.shape)) for x in jax.tree.leaves(a_params)
            ]
            # the int8 arm needs partitionable threefry (see the headline
            # guard above); restore the process default afterwards so the
            # dropout add-ons below keep their established bit streams
            _tf_prev = jax.config.jax_threefry_partitionable

            def _comp_arm(mode: str) -> dict:
                st, _shm = _fresh_state(mode)
                build_c = make_train_step(
                    lm.module, lm.config, tx, schedule, mesh,
                    optim_spec=optim_spec, optim_impl=optim_impl,
                    grad_compression=mode,
                )
                step_c, _ = build_c(st)
                losses = []
                for _ in range(ab_steps):
                    st, m = step_c(st, gb)
                    losses.append(sync(st, m))
                t0 = time.perf_counter()
                for _ in range(steps):
                    st, m = step_c(st, gb)
                sync(st, m)
                dtc = time.perf_counter() - t0
                from distributed_llms_example_tpu.parallel.activation import (
                    activation_mesh as _amesh,
                )

                with _amesh(step_c.mesh):
                    text = step_c.jitted.lower(st, gb).compile().as_text()
                from distributed_llms_example_tpu.analysis.ir_lint import (
                    parse_hlo_instructions as _parse,
                )

                instrs = _parse(text)
                comm_c = _ctraffic(instrs, leaf_counts, n_chips)
                census = quantized_gradient_census(
                    instrs, leaf_counts, dict(mesh.shape)
                )
                del st
                return {
                    "losses": losses,
                    "tokens_per_sec_chip": round(tokens_per_step * steps / dtc / n_chips, 1),
                    "gradient_bytes_per_step": int(comm_c["gradient_bytes"]),
                    "gradient_wire_bytes": int(census["gradient_wire_bytes"]),
                    "s8_gradient_collectives": len(census["s8_gradient_collectives"]),
                }

            try:
                jax.config.update("jax_threefry_partitionable", True)
                arms = {m: _comp_arm(m) for m in comp_modes}
            finally:
                jax.config.update("jax_threefry_partitionable", _tf_prev)
            delta = max(
                abs(a - b)
                for a, b in zip(arms["off"]["losses"], arms["int8"]["losses"])
            )
            off_b = max(1, arms["off"]["gradient_bytes_per_step"])
            int8_b = max(1, arms["int8"]["gradient_bytes_per_step"])
            off_w = max(1, arms["off"]["gradient_wire_bytes"])
            int8_w = max(1, arms["int8"]["gradient_wire_bytes"])
            result["grad_compression_ab"] = {
                "steps": ab_steps,
                "workers": grad_workers,
                "off_tokens_per_sec_chip": arms["off"]["tokens_per_sec_chip"],
                "int8_tokens_per_sec_chip": arms["int8"]["tokens_per_sec_chip"],
                # >1.0 = compression won wall-clock (expect <1 on CPU: the
                # wire it saves is free there and the quantize math is not)
                "int8_vs_off": round(
                    arms["int8"]["tokens_per_sec_chip"]
                    / max(arms["off"]["tokens_per_sec_chip"], 1e-9), 3,
                ),
                "loss_max_abs_delta": round(delta, 6),
                "loss_final_off": round(arms["off"]["losses"][-1], 6),
                "loss_final_int8": round(arms["int8"]["losses"][-1], 6),
                "gradient_bytes_per_step": {"off": off_b, "int8": int8_b},
                "gradient_bytes_ratio": round(off_b / int8_b, 2),
                "gradient_wire_bytes": {"off": off_w, "int8": int8_w},
                "gradient_wire_ratio": round(off_w / int8_w, 2),
                "s8_gradient_collectives": arms["int8"]["s8_gradient_collectives"],
                # on profiled rounds the measured per-collective ms +
                # achieved bytes/sec live in trainer_loop.device_account
                # (PR 11); CPU rounds auto-skip that capture, so this A/B
                # carries the static byte verdict only
                "measured_bandwidth": "see trainer_loop.device_account "
                                      "(profiled rounds)",
            }
            emit_result()
        except Exception as e:
            print(f"bench: grad-compression A/B failed ({e})", file=sys.stderr)
            skipped_passes.append(f"grad-compression A/B failed ({str(e)[:200]})")

    # The Trainer trains with the model's real dropout (bart-large-cnn:
    # 0.1, the reference's recipe) while the headline synthetic step runs
    # dropout-free — measured on v5e, dropout alone costs ~20%.  Measure a
    # with-dropout synthetic pass so the trainer-loop comparison below is
    # apples-to-apples (trainer ≈ this number ⇒ the input pipeline is off
    # the critical path; trainer ≈ headline would be impossible).
    tps_chip_dropout = None
    if os.environ.get("BENCH_DROPOUT", "1") != "0" and not over_budget("dropout step", est_step_pass):
        try:
            # pin the BASELINE to the xla impl: on TPU the process default
            # ("auto") resolves to fused, and the fused-vs-xla A/B below
            # would silently compare fused against fused (the rbg add-on
            # retraces this step for the typed key, so the pin must hold
            # through it — restored by the fused A/B block / the reset
            # before the trainer loop)
            from distributed_llms_example_tpu.ops.fused_dropout import (
                set_default_impl as _set_dropout_impl,
            )

            _set_dropout_impl("xla")
            build_d = make_train_step(
                lm.module, lm.config, tx, schedule, mesh, with_dropout=True,
                optim_spec=optim_spec, optim_impl=optim_impl,
            )
            step_d, _ = build_d(state)
            key = jax.random.PRNGKey(0)
            for _ in range(2):
                key, sub = jax.random.split(key)
                state, metrics = step_d(state, gb, sub)
            sync(state, metrics)
            t0 = time.perf_counter()
            for _ in range(steps):
                key, sub = jax.random.split(key)
                state, metrics = step_d(state, gb, sub)
            sync(state, metrics)
            dtd = time.perf_counter() - t0
            tps_chip_dropout = round(tokens_per_step * steps / dtd / n_chips, 1)
            result["with_dropout_tokens_per_sec_chip"] = tps_chip_dropout
            emit_result()
        except Exception as e:
            print(f"bench: dropout-step bench failed ({e})", file=sys.stderr)

    # same with-dropout step fed an RBG (TPU hardware RNG) key — the
    # --prng-impl rbg trainer path.  Threefry mask generation is counter
    # math on the VPU and costs ~20% of the step; this measures what the
    # hardware stream buys back (the jit recompiles for the typed-key
    # argument, a cache hit on every later run).
    tps_chip_dropout_rbg = None
    if (
        tps_chip_dropout is not None
        and os.environ.get("BENCH_DROPOUT_RBG", "1") != "0"
        and not over_budget("rbg dropout step", est_step_pass)
    ):
        try:
            key = jax.random.key(0, impl="rbg")
            for _ in range(2):
                key, sub = jax.random.split(key)
                state, metrics = step_d(state, gb, sub)
            sync(state, metrics)
            t0 = time.perf_counter()
            for _ in range(steps):
                key, sub = jax.random.split(key)
                state, metrics = step_d(state, gb, sub)
            sync(state, metrics)
            dtr = time.perf_counter() - t0
            tps_chip_dropout_rbg = round(tokens_per_step * steps / dtr / n_chips, 1)
            result["with_dropout_rbg_tokens_per_sec_chip"] = tps_chip_dropout_rbg
            emit_result()
        except Exception as e:
            print(f"bench: rbg dropout-step bench failed ({e})", file=sys.stderr)

    # fused-dropout A/B: the SAME with-dropout step rebuilt with
    # --dropout-impl fused (ops/fused_dropout.py — in-kernel RNG, no mask
    # in HBM, seed-recompute backward), same session, same shapes, same
    # threefry key stream (the fused path folds the key to ONE scalar, so
    # host-PRNG choice no longer matters — that is the point).  The
    # acceptance bar is fused ≥ 1.10× the xla with-dropout number.
    if (
        tps_chip_dropout is not None
        and os.environ.get("BENCH_DROPOUT_FUSED", "1") != "0"
        and not over_budget("fused dropout step", est_step_pass)
    ):
        from distributed_llms_example_tpu.ops.fused_dropout import (
            set_default_impl,
        )

        try:
            set_default_impl("fused")
            build_f = make_train_step(
                lm.module, lm.config, tx, schedule, mesh, with_dropout=True,
                optim_spec=optim_spec, optim_impl=optim_impl,
            )
            step_f, _ = build_f(state)
            key = jax.random.PRNGKey(0)
            for _ in range(2):
                key, sub = jax.random.split(key)
                state, metrics = step_f(state, gb, sub)
            sync(state, metrics)
            t0 = time.perf_counter()
            for _ in range(steps):
                key, sub = jax.random.split(key)
                state, metrics = step_f(state, gb, sub)
            sync(state, metrics)
            dtf = time.perf_counter() - t0
            tps_chip_dropout_fused = round(tokens_per_step * steps / dtf / n_chips, 1)
            result["with_dropout_fused_tokens_per_sec_chip"] = tps_chip_dropout_fused
            result["fused_vs_xla_dropout"] = round(tps_chip_dropout_fused / tps_chip_dropout, 3)
            # mask-absence assertion: scan the compiled fused step for any
            # operand shaped like a (B_local·H·S·S) attention-probs mask —
            # the fused path must never materialize one (the headline
            # families run attn_dropout_rate 0, so any hit is a bug)
            try:
                from distributed_llms_example_tpu.analysis.ir_lint import (
                    parse_hlo_instructions,
                )

                with activation_mesh(step_f.mesh):
                    txt = step_f.jitted.lower(state, gb, sub).compile().as_text()
                heads = int(getattr(
                    lm.config, "encoder_attention_heads",
                    getattr(lm.config, "num_heads",
                            getattr(lm.config, "num_attention_heads", 0)),
                ) or 0)
                b_local = max(1, batch // n_chips)
                probs_elems = {
                    b_local * heads * ql * kl
                    for ql in (src_len, tgt_len) for kl in (src_len, tgt_len)
                } if heads else set()
                hits = [
                    i.name for i in parse_hlo_instructions(txt).values()
                    if i.elems in probs_elems
                ]
                result["attn_probs_mask_operands"] = len(hits)
                if hits:
                    print(
                        f"bench: {len(hits)} (B·H·S·S)-sized operand(s) in the "
                        f"fused step (e.g. %{hits[0]}) — probs-mask smell",
                        file=sys.stderr,
                    )
            except Exception as e:
                print(f"bench: fused-step HLO scan unavailable ({e})", file=sys.stderr)
            emit_result()
        except Exception as e:
            print(f"bench: fused dropout-step bench failed ({e})", file=sys.stderr)

    # restore the process default ("auto") after the pinned A/B passes —
    # the trainer-loop bench pins its own cfg, but a leaked pin would
    # still surprise anything imported after us
    try:
        from distributed_llms_example_tpu.ops.fused_dropout import set_default_impl

        set_default_impl("auto")
    except Exception:
        pass

    # serving block: continuous-batching decode tokens/sec/chip + TTFT +
    # the continuous-vs-static and ROUGE-eval-path A/Bs (serving/engine.py)
    # on the same sharded params the train step just used.  Cost is a
    # prefill+decode sweep per path, plus the capacity A/B's int8 engine
    # rebuild — budget it like four step passes.
    if os.environ.get("BENCH_SERVE", "1") != "0" and not over_budget(
        "serve block", 4 * est_step_pass
    ):
        try:
            batch_shards = 1
            for a in ("data", "fsdp", "expert"):
                batch_shards *= mesh.shape.get(a, 1)
            serve_slots = int(os.environ.get("BENCH_SERVE_SLOTS_PER_SHARD", "2")) * batch_shards
            result["serve"] = _serve_measure(
                lm, mesh, state.params,
                slots=serve_slots,
                src=int(os.environ.get("BENCH_SERVE_SRC", str(src_len))),
                new_tokens=int(os.environ.get("BENCH_SERVE_NEW", "32")),
                n_req=int(os.environ.get("BENCH_SERVE_REQUESTS", str(2 * serve_slots))),
                eval_beams=int(os.environ.get("BENCH_SERVE_EVAL_BEAMS", "2")),
            )
            emit_result()
        except Exception as e:
            print(f"bench: serve block failed ({e})", file=sys.stderr)
            skipped_passes.append(f"serve block failed ({str(e)[:200]})")

    # speculative-decode block: spec vs plain greedy on the chatbot mix
    # (serving/spec.py), riding the flagship's params when the flagship
    # is causal.  A seq2seq flagship is a CONFIG skip, stamped like a
    # budget skip — speculation verifies through the causal decode path,
    # and a silently missing spec field would read as "measured, no win".
    if os.environ.get("BENCH_SPEC", "1") != "0":
        if lm.is_seq2seq:
            msg = (
                "serve-spec A/B skipped (flagship model is seq2seq; "
                "speculation verifies through the causal decode path — "
                "run BENCH_MODE=serve-spec on a causal model instead)"
            )
            print(f"bench: {msg}", file=sys.stderr)
            skipped_passes.append(msg)
        elif not over_budget("serve-spec A/B", 4 * est_step_pass):
            try:
                batch_shards = 1
                for a in ("data", "fsdp", "expert"):
                    batch_shards *= mesh.shape.get(a, 1)
                spec_slots = int(os.environ.get("BENCH_SPEC_SLOTS_PER_SHARD", "2")) * batch_shards
                result["serve_spec"] = _spec_measure(
                    lm, mesh, state.params,
                    slots=spec_slots,
                    src=int(os.environ.get("BENCH_SPEC_SRC", "64")),
                    new_tokens=int(os.environ.get("BENCH_SPEC_NEW", "16")),
                    sessions=int(os.environ.get("BENCH_SPEC_SESSIONS", "6")),
                    turns=int(os.environ.get("BENCH_SPEC_TURNS", "5")),
                    seed=int(os.environ.get("BENCH_SPEC_SEED", "0")),
                    spec_tokens=int(os.environ.get("BENCH_SPEC_TOKENS", "3")),
                    draft_model=os.environ.get("BENCH_SPEC_DRAFT", ""),
                )
                emit_result()
            except Exception as e:
                print(f"bench: serve-spec A/B failed ({e})", file=sys.stderr)
                skipped_passes.append(f"serve-spec A/B failed ({str(e)[:200]})")

    # memory stamp: the static bucketed HBM account (obs/memprof.py) at
    # the measured shape plus the allocator watermark this process set —
    # the "where did the bytes go" record for the headline pass.  The
    # account is an abstract AOT compile (no device buffers), so it is
    # safe to run while the synthetic state is still resident.
    if os.environ.get("BENCH_MEMORY", "1") != "0":
        from distributed_llms_example_tpu.obs import memprof

        try:
            acct = memprof.static_memory_account(
                name, mesh,
                global_batch=batch, src_len=src_len, tgt_len=tgt_len,
                remat=remat,
                hbm_budget_gib=float(
                    os.environ.get("BENCH_HBM_BUDGET_GIB", "16")
                ),
            )
            result["memory_account"] = {
                k: acct[k]
                for k in (
                    "buckets_bytes", "peak_bytes", "peak_gib",
                    "additivity_gap_bytes", "hbm_budget_gib",
                    "hbm_headroom_gib", "peak_frac_of_budget", "fits_budget",
                )
            }
        except Exception as e:
            print(f"bench: static memory account failed ({e})", file=sys.stderr)
        wm = memprof.Watermark().read()
        if wm is not None:
            result["memory_watermark"] = wm
        emit_result()

    # the full Trainer loop (bucketed batching + prefetch + logging on the
    # critical path): validating within ~5% of the with-dropout synthetic
    # number proves the input pipeline stays off the device's back
    trainer_loop = None
    if os.environ.get("BENCH_TRAINER", "1") != "0" and not over_budget(
        "trainer loop", 2 * est_step_pass + 2 * dt
    ):
        # free the synthetic run's device state first: params + AdamW
        # moments are ~5 GB for the 406M flagship, and the Trainer builds
        # its own copy — both living at once exhausts a 16 GB chip
        del state, metrics, gb, params
        try:
            trainer_loop = _trainer_loop_bench(
                name, n_chips, remat=remat,
                attention=os.environ.get("BENCH_ATTENTION", "") or None,
                rbg_ok=lambda est: not over_budget("trainer rbg pass", est),
            )
            tl = trainer_loop.get("tokens_per_sec_chip_prefetch2")
            if tl:
                trainer_loop["vs_synthetic_step"] = round(tl / tps_chip, 3)
                if tps_chip_dropout:
                    trainer_loop["vs_synthetic_step_with_dropout"] = round(
                        tl / tps_chip_dropout, 3
                    )
        except Exception as e:  # never lose the headline number to an add-on
            print(f"bench: trainer-loop bench failed ({e})", file=sys.stderr)
            trainer_loop = {"error": str(e)[:300]}

    if trainer_loop is not None:
        result["trainer_loop"] = trainer_loop
    emit_result()


if __name__ == "__main__":
    from distributed_llms_example_tpu.core.compile_cache import place_compile_cache

    place_compile_cache()
    _mode = os.environ.get("BENCH_MODE", "")
    if _mode != "host-input":  # the one mode that measures the host, not a chip
        _device_peak_flops()
    {
        "llama-depth": _llama_depth_main,
        "generate": _generate_main,
        "serve": _serve_main,
        "serve-router": _router_main,
        "serve-loadgen": _loadgen_main,
        "serve-prefix": _prefix_main,
        "serve-spec": _spec_main,
        "host-input": _host_input_main,
    }.get(_mode, main)()
