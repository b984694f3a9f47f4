"""Train driver: the program's ``Trainer`` in-process, fed from its own input
pipeline, timed over whole passes of a fixed number of steps.

Set-up builds ONE trainer (one compiled step program), replaces its seeded
parameters by the benchmark's (same seed as the reference's), and drives it
through its first pass with the calls the window makes.  The first three
steps of that pass are the ones the plain reference follows once the window
has closed: each step's loss, the per-leaf norm of the first gradient as the
optimizer got it (first moment after one step / (1 - b1)), and the per-leaf
norm of the parameters' change after the three.  The window then runs the
same object: whole passes until ``--seconds`` have gone by.  Nothing of size
runs after it but the reference (and, traced, the few traced steps): every
second there is paid by every run of every later check.
"""

from __future__ import annotations

import gc
import itertools
import os
import shutil
import time

import jax
import numpy as np

from benchmarks.harness import check, program, spec as spec_mod, stats, text, train_ref, weights
from benchmarks.harness.runtime import CompileCounter, Run, profiled

ANNOTATIONS = ("train_pass", "train_step_call")


class StepRecorder:
    """Wraps the trainer's compiled step during set-up to keep what the
    reference will be held against.  Taken off before the window."""

    def __init__(self, inner, rows, fresh_weights, keep: int):
        self.inner, self.rows, self.fresh_weights, self.keep = inner, rows, fresh_weights, keep
        self.calls = 0
        self.batches, self.all_losses = [], []
        self.first_moment = self.delta = None

    def __call__(self, state, batch, *rng):
        i = self.calls
        self.calls += 1
        if i < self.keep:
            self.batches.append({k: np.asarray(jax.device_get(v)) for k, v in batch.items()})
        state, metrics = self.inner(state, batch, *rng)
        self.all_losses.append(metrics["loss"])
        if i == 0:
            adam = [s for s in jax.tree.leaves(state.opt_state, is_leaf=lambda x: hasattr(x, "mu"))
                    if hasattr(s, "mu")]
            self.first_moment = program.leaf_norms_by_row(self.rows, adam[0].mu)
            self.first_moment_sample = program.leaf_samples_by_row(self.rows, adam[0].mu)
        if i == self.keep - 1:
            self.delta = program.leaf_diff_norms_by_row(self.rows, state.params, self.fresh_weights())
        return state, metrics


class LossTap:
    """Around the compiled step from the second warm-up pass on: keeps each
    step's loss handle (no copy, no wait), so that the window's own last pass
    says whether the run learned and no further pass has to be run for it."""

    def __init__(self, inner):
        self.inner, self.losses = inner, []

    def __call__(self, state, batch, *rng):
        out = self.inner(state, batch, *rng)
        self.losses.append(out[1]["loss"])
        return out


class FirstBatches:
    """The trainer's batch plan cut to an epoch's first ``n`` batches: the
    traced pass ends with the steps it traces."""

    def __init__(self, inner, n: int):
        self.inner, self.n = inner, n

    def epoch(self, *args, **kwargs):
        return itertools.islice(self.inner.epoch(*args, **kwargs), self.n)

    def __getattr__(self, name):
        return getattr(self.inner, name)


def run(run: Run) -> dict:
    from distributed_llms_example_tpu.core.config import CheckpointConfig, MeshConfig, TrainConfig
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.train.trainer import Trainer

    cell, cfg = run.cell, run.cell.config
    ref = spec_mod.load_module("reference", cell.family)
    adapter = spec_mod.load_module("adapters", cell.family)
    rows = adapter.leaf_map(cfg)
    spec = ref.param_spec(cfg)
    counter = CompileCounter()
    t = {"driver_start": time.perf_counter()}

    batch, steps_per_pass = int(cell.recipe("batch_size")), int(cell.recipe("steps_per_pass"))
    src_len, tgt_len = int(cell.recipe("max_source_length")), int(cell.recipe("max_target_length"))
    lo, hi = cell.recipe("target_tokens")
    records = text.summarize_records(
        run.seed, batch * steps_per_pass, source_chars=int(cell.recipe("source_chars")),
        target_tokens=stats.stratified(int(lo), int(hi), batch * steps_per_pass),
    )
    pass_tokens = sum(
        len(text.encode(r["dialogue"], src_len)) + len(text.encode(r["summary"], tgt_len)) for r in records
    )

    model_name = program.register_bench_model(cfg, adapter)

    out_dir = os.path.join(spec_mod.CACHE_DIR, "run", cell.name)
    shutil.rmtree(out_dir, ignore_errors=True)
    opt = dict(cell.recipe("optimizer"))
    mesh = build_mesh(MeshConfig(**cell.recipe("mesh", {"data": -1})), devices=jax.devices()[: cell.chips])
    tcfg = TrainConfig(
        model_ckpt=model_name, output_dir=out_dir, batch_size=batch,
        # the schedule's length is baked into the step program: fixed, and
        # long enough that the rate is nowhere near zero inside any window
        num_epochs=int(opt["total_steps"]) // steps_per_pass,
        warmup_steps=int(opt["warmup_steps"]), evaluation_steps=0,
        learning_rate=float(opt["learning_rate"]), weight_decay=float(opt["weight_decay"]),
        max_grad_norm=float(opt["max_grad_norm"]), grad_accum_steps=int(cell.recipe("grad_accum_steps")),
        max_source_length=src_len, max_target_length=tgt_len,
        pad_to_multiple=int(cell.recipe("pad_to_multiple")), prefetch_batches=int(cell.recipe("prefetch_batches")),
        log_every_steps=int(cell.recipe("log_every_steps")), tokenizer="byte",
        shuffle_seed=run.seed % 2147483647, compute_dtype=cfg["dtypes"]["compute"],
        param_dtype=cfg["dtypes"]["params"], obs="stdout", obs_gauges="off",
        checkpoint=CheckpointConfig(save_every_steps=0, resume=False, async_save=False),
    )
    trainer = Trainer(tcfg, train_records=records, mesh=mesh)
    opt["total_steps"] = trainer.total_steps  # whole passes: what the program's schedule was built with
    trainer.cfg = tcfg.replace(num_epochs=1)  # one pass = one epoch over the records
    trainer.checkpointer.save = lambda *a, **k: None  # this measures training, not artifact writes
    trainer.checkpointer.wait = lambda: None
    trainer.save_final = lambda: None
    t["trainer_built"] = time.perf_counter()

    def fresh_weights():
        tree = weights.make_program_weights(spec, run.seed, program.to_program_tree(rows))
        return jax.tree.map(jax.device_put, tree, trainer.state_sh.params)

    trainer.state = trainer.state.replace(params=fresh_weights())
    t["weights_made"] = time.perf_counter()

    def one_pass():
        trainer.train_ds.clear_cache()  # the input pipeline really tokenizes every pass
        with jax.profiler.TraceAnnotation("train_pass"):
            trainer.train()

    keep = int(cfg["check"]["steps"])
    compiled_step = trainer.train_step
    rec = StepRecorder(compiled_step, rows, fresh_weights, keep)
    trainer.train_step = rec
    one_pass()  # compiles (or loads) the step program; steps 1..keep are the checked ones
    trainer.train_step = tap = LossTap(compiled_step)
    jax.block_until_ready(trainer.state.params)
    t["first_pass"] = time.perf_counter()
    for _ in range(int(cell.recipe("warmup_passes")) - 1):
        one_pass()
    jax.block_until_ready(trainer.state.params)
    first_loss = float(jax.device_get(rec.all_losses[0]))
    program_losses = [float(jax.device_get(x)) for x in rec.all_losses[:keep]]
    trainer.obs.budget.history.clear()
    gc.collect()

    # ---- the window: whole passes, the last may overrun --seconds
    passes = 0
    with counter.window():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < run.seconds:
            one_pass()
            passes += 1
        jax.block_until_ready(trainer.state.params)
        wall = time.perf_counter() - t0
    # an account runs from the one before it: the window's first began at warm-up's end and holds
    # what lies between (the collection above, ~1 s), so the trainer-loop metrics leave it out
    accounts = list(trainer.obs.budget.history)[1:]
    steps = passes * steps_per_pass
    tokens_per_s_chip = passes * pass_tokens / wall / cell.chips
    run.emit({"phase": "window", "passes": passes, "steps": steps, "wall_s": wall,
              "tokens": passes * pass_tokens, "step_s": wall / steps,
              "setup_pieces_s": {
                  "imports_and_device": t["driver_start"] - run.t_start,
                  "trainer_built": t["trainer_built"] - t["driver_start"],
                  "weights_made": t["weights_made"] - t["trainer_built"],
                  "first_pass_with_compile": t["first_pass"] - t["weights_made"],
                  "warmup_passes": t0 - t["first_pass"]},
              "step_budget_accounts": len(accounts)})
    memory_peak = program.memory_peak_bytes(cell.chips)

    layers = {"cell": cell, "config": cfg, "peaks": run.peaks, "accounts": accounts,
              "steps_per_s": steps / wall, "batch": batch, "src_len": src_len, "tgt_len": tgt_len,
              "trace": None}
    if run.trace:
        traced_steps = int(cell.recipe("traced_steps"))

        with profiled(run, ANNOTATIONS, "train_pass", layers) as profile:
            calls = [0]

            def traced_step(state, batch, *rng):
                with jax.profiler.TraceAnnotation("train_step_call"):
                    out = compiled_step(state, batch, *rng)
                calls[0] += 1
                if calls[0] == traced_steps:  # the window is these steps, first device event to last
                    jax.block_until_ready(out[1]["loss"])
                    profile.stop()
                return out

            trainer.train_step, plan = traced_step, trainer.batches
            trainer.batches = FirstBatches(plan, traced_steps)
            one_pass()
            trainer.train_step, trainer.batches = tap, plan
            jax.block_until_ready(trainer.state.params)

    # loss over the window's last pass against the first step's
    window_losses = [float(x) for x in jax.device_get(tap.losses[-steps_per_pass:])]
    last_pass_loss = float(np.mean(window_losses))

    # ---- free the program's state, then let the reference follow the first steps
    del trainer.state, compiled_step
    trainer.train_step = tap.inner = None
    gc.collect()
    expected = text.expected_rows(records, src_len, tgt_len, rec.batches[0]["labels"].shape[1])
    seen = set()
    for b in rec.batches:
        for ids, mask, labels in zip(b["input_ids"], b["attention_mask"], b["labels"]):
            want = expected.get(ids.tobytes())
            if want is None or not (np.array_equal(want[0], mask) and np.array_equal(want[1], labels)):
                raise SystemExit("a row fed to the step is not the benchmark's own encoding of a record")
            seen.add(ids.tobytes())
    if len(seen) != keep * batch:
        raise SystemExit(f"the first {keep} steps repeated rows: {len(seen)} distinct of {keep * batch}")
    followed = train_ref.follow(
        ref, cfg, run.seed, rec.batches, opt, precision="fp32",
        rows_per_block=int(cfg["check"]["rows_per_block"]),
    )
    b1 = float(opt["b1"])
    first_grad = {k: v / (1.0 - b1) for k, v in rec.first_moment.items()}
    grad_gap, grad_at = check.worst_leaf_gap(first_grad, followed["first_grad_norms"])
    noise = check.rounding_only_leaves(followed["first_grad_norms"])
    delta_gap, delta_at = check.worst_leaf_gap(rec.delta, followed["delta_norms"], skip=noise)
    grad_sample = {k: v / (1.0 - b1) for k, v in rec.first_moment_sample.items()}
    grad_diff = check.median_leaf_rel_diff(grad_sample, followed["first_grad_samples"], skip=noise)
    loss_gap = max(abs(p - r) / abs(r) for p, r in zip(program_losses, followed["losses"]))
    finite = all(np.isfinite(window_losses)) and np.isfinite(first_loss)
    run.emit({"phase": "check", "program_losses": program_losses, "reference_losses": followed["losses"],
              "reference_grad_global_norms": followed["grad_global_norms"],
              "first_step_loss": first_loss, "last_pass_mean_loss": last_pass_loss,
              "reference_s": followed["seconds"],
              "leaves": len(rows), "rounding_only_leaves_left_out_of_param_change": len(noise)})
    numbers = {
        "loss_rel_gap_max_over_steps": loss_gap,
        "first_grad_norm_gap_worst_leaf": grad_gap,
        "first_grad_rel_diff_median_leaf": grad_diff,
        "param_change_norm_gap_worst_leaf": delta_gap,
        "last_pass_loss_over_first_step_loss": last_pass_loss / first_loss if finite else float("inf"),
    }
    correct = check.judge(numbers, cfg["check"]["limits"]["train_loop"],
                          {"first_grad_norm_gap_worst_leaf": grad_at, "param_change_norm_gap_worst_leaf": delta_at})
    if run.control:
        ctl = train_ref.follow(ref, cfg, run.seed, rec.batches, opt, precision=cfg["check"]["control"],
                               rows_per_block=int(cfg["check"]["rows_per_block"]))
        check.control_caught({
            "loss_rel_gap_max_over_steps": max(
                abs(p - r) / abs(r) for p, r in zip(ctl["losses"], followed["losses"])),
            "first_grad_norm_gap_worst_leaf": check.worst_leaf_gap(
                ctl["first_grad_norms"], followed["first_grad_norms"])[0],
            "first_grad_rel_diff_median_leaf": check.median_leaf_rel_diff(
                ctl["first_grad_samples"], followed["first_grad_samples"], skip=noise),
            "param_change_norm_gap_worst_leaf": check.worst_leaf_gap(
                ctl["delta_norms"], followed["delta_norms"], skip=noise)[0],
        }, cfg["check"]["limits"]["train_loop"])
    return {
        "correct": correct, "attempted": steps, "failed": 0,
        "end_to_end": {"train_tokens_per_s_chip": tokens_per_s_chip, "setup_s": t0 - run.t_start},
        "layers": layers, "compiles_in_window": counter.count, "memory_peak_bytes": memory_peak,
        "reference_s": followed["seconds"],
    }
