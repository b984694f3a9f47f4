"""Serve driver for a model that routes tokens to experts: ``serve_open_loop``
with another judgement of the same replay.

The session, the warm-up, the measured window, the reference's replay and the
gaps are ``drivers/serve_open_loop.py``'s own functions, loaded from that file
and not changed.  What differs is which numbers of the gaps are held to limits.
Routing is discontinuous: a token whose last chosen and first refused expert
scores nearly tie routes otherwise in a bfloat16 program than in the float32
reference, and its logits then move by an expert's share of a layer, far more
than a bfloat16 step.  The LARGEST gap over ~1,800 served tokens is therefore
set by the few tokens that flipped, in the sound program and in the int8
control alike, and does not separate them with room (PERF.md, section 2: the
readings).  What the precision moves is how OFTEN and how far the served token
falls below the reference's best, so this driver judges

* ``served_logit_gap_mean``, ``served_logit_gap_p95``: the mean and the 95th
  percentile of the gaps over every compared token (none is left out);
* ``served_logit_gap_max``: kept, as the guard against a gross error (a wrong
  cache position, a dropped expert), with a limit a flip cannot reach;

whichever of these the configuration's ``check.limits.serve_open_loop_routed``
names, each beside its limit.  The reference never takes the program's routing.
It prints the share of compared positions with a near-tie at some expert layer
(``reference/<family>.py``) in every run, beside the numbers.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.harness import check, program, spec as spec_mod, stats, text
from benchmarks.harness.runtime import CompileCounter, Run, profiled

base = spec_mod.load_module("drivers", "serve_open_loop")
ANNOTATIONS = base.ANNOTATIONS
build_session, warm_up, measure = base.build_session, base.warm_up, base.measure  # tools/sweep.py drives these

STATISTICS = {
    "served_logit_gap_max": max,
    "served_logit_gap_mean": lambda g: float(np.mean(g)),
    "served_logit_gap_p95": lambda g: stats.percentile(g, 0.95),
}


def judged(gaps: list[float], limits: dict) -> dict[str, float]:
    """The statistics of the gaps that the configuration holds to a limit."""
    unknown = sorted(set(limits) - set(STATISTICS))
    if unknown:
        raise SystemExit(f"check.limits names {unknown}; this driver computes {sorted(STATISTICS)}")
    return {name: float(STATISTICS[name](gaps)) for name in limits}


def ended_before_budget(session, rows: list) -> int:
    """Requests the engine finished short of their budget: neither waiting nor in a slot any more."""
    waiting = set(session.pending) | {int(r) for r in session.slot_req[session.active]}
    return sum(1 for r in rows if r["rid"] is not None and not r["done"] and r["rid"] not in waiting)


def run(run: Run) -> dict:
    cell, cfg = run.cell, base.weights_config(run.cell)
    limits = cfg["check"]["limits"]["serve_open_loop_routed"]
    counter = CompileCounter()
    t_driver = time.perf_counter()
    session, ref = base.build_session(run)
    t_open = time.perf_counter()
    base.warm_up(session, run)
    t_warm = time.perf_counter()
    rate = float(cell.recipe("rate_rps"))

    with counter.window():
        t0 = time.perf_counter()
        m = base.measure(session, run, rate, run.seconds)
    memory_peak = program.memory_peak_bytes(cell.chips)
    failed = m["offered"] - m["completed"]
    ms = lambda xs: {k: v * (1e3 if k != "n" else 1) for k, v in stats.summary(xs).items()}  # noqa: E731
    run.emit({"phase": "window", "rate_rps": rate, "offered": m["offered"], "completed": m["completed"],
              "failed": failed, "wall_s": m["wall_s"], "queue_growing": m["queue_growing"],
              # ended on an end-of-sequence token before its budget: counted among the failed
              "ended_before_budget": ended_before_budget(session, m["rows"]),
              "tokens_offered": m["tokens_offered"], "tokens_in_window": m["tokens_in_window"],
              "tokens_in_window_per_s": m["tokens_per_s"],
              "slots": int(cell.recipe("max_slots")), "slots_live_mean": m["slots_live_mean"],
              "slots_live_peak": m["slots_live_peak"], "by_5s": m["by_5s"],
              "ttft_ms": ms(m["ttft_s"]), "gap_ms": ms(m["gaps_s"]), "late_ms": ms(m["late_s"]),
              "rounds": len(m["rounds"]), "share_of_rounds_with_a_wave": m["share_of_rounds_with_a_wave"],
              "share_of_gaps_with_a_wave": m["share_of_gaps_with_a_wave"],
              "share_of_gaps_with_a_full_wave": m["share_of_gaps_with_a_full_wave"], "wave_ms_p50": m["wave_ms_p50"],
              "setup_pieces_s": {"imports_and_device": t_driver - run.t_start,
                                 "engine_open_with_compile": t_open - t_driver, "warm_up_requests": t_warm - t_open}})

    layers = {"cell": cell, "config": cfg, "peaks": run.peaks, "step_times": m["rounds"],
              "late_s": m["late_s"], "share_of_gaps_with_a_wave": m["share_of_gaps_with_a_wave"],
              "share_of_gaps_with_a_full_wave": m["share_of_gaps_with_a_full_wave"], "trace": None}
    if run.trace:
        with profiled(run, ANNOTATIONS, "serve_step", layers):
            base.measure(session, run, rate, float(cell.recipe("trace_seconds", 2.0)))

    # ---- the sample the reference replays: seeded, the longest among it (as the base driver picks it)
    done = m["done"]
    k = min(int(cell.recipe("check_requests")), len(done))
    correct, ref_s = False, 0.0
    if k:
        pick = set(int(j) for j in text.rng_for(run.seed, 4).choice(len(done), size=k, replace=False))
        longest = max(range(len(done)), key=lambda j: len(done[j]["tokens_at"]))
        if longest not in pick:
            pick.pop()
            pick.add(longest)
        sample = [done[j] for j in sorted(pick)]
        t_ref = time.perf_counter()
        served = [list(session.outputs[r["rid"]]) for r in sample]
        prompts = [m["prompts"][r["index"]] for r in sample]
        forced = ref.forced_tokens(cfg, int(cell.recipe("max_new_tokens")))
        logits = base.reference_logits(ref, cfg, run.seed, prompts, served)
        gaps = base.gaps_below_best(logits, served, forced)
        ref_s = time.perf_counter() - t_ref
        everything = {name: float(fn(gaps)) for name, fn in STATISTICS.items()}
        run.emit({"phase": "check", "requests": k, "served_tokens_compared": len(gaps), "gap": stats.summary(gaps),
                  **everything, "share_not_the_references_best": sum(1 for x in gaps if x > 0) / len(gaps),
                  "reference_s": ref_s})
        correct = check.judge(judged(gaps, limits), limits)
        if run.control:
            # the token the lower precision puts first at each position of the
            # same prompts and tokens, judged by the float32 reference
            low = base.reference_logits(ref, cfg, run.seed, prompts, served, cfg["check"]["control"])
            first = [list(np.argmax(low[i, : len(s)], axis=-1)) for i, s in enumerate(served)]
            control_gaps = base.gaps_below_best(logits, first, forced)
            run.emit({"phase": "control", "gap": stats.summary(control_gaps),
                      **{name: float(fn(control_gaps)) for name, fn in STATISTICS.items()},
                      "share_not_the_references_best": sum(1 for x in control_gaps if x > 0) / len(control_gaps)})
            check.control_caught(judged(control_gaps, limits), limits)
    session.finalize()
    return {
        "correct": correct, "attempted": m["offered"], "failed": failed,
        "end_to_end": {
            "serve_tokens_per_s": m["tokens_per_s"],
            "ttft_p95_ms": stats.percentile(m["ttft_s"], 0.95) * 1e3,
            "gap_p95_ms": stats.percentile(m["gaps_s"], 0.95) * 1e3 if m["gaps_s"] else None,
            "setup_s": t0 - run.t_start,
        },
        "layers": layers, "compiles_in_window": counter.count, "memory_peak_bytes": memory_peak,
        "reference_s": ref_s,
    }
