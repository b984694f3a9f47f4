#!/usr/bin/env python3
"""The rate sweep that places a serve cell's fixed rate, once, on the chip.

    python3 benchmarks/tools/sweep.py --workload <cell> --seed 5 --seconds 25 --rates 4,8,12,16,20,24

One process, one engine, the cell's own sizes: for each rate an open-loop
window, then the table and the knee (the highest rate whose completions keep
up, 0.95 of the offered, with no growing backlog).  The result goes to
``chiprun_out/sweep.<cell>.json`` and into the traffic file's ``why`` by hand.
Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=5)
    p.add_argument("--seconds", type=float, default=25.0)
    p.add_argument("--rates", required=True)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()

    import jax

    from benchmarks.harness import loadgen, peaks, program, spec as spec_mod, stats
    from benchmarks.harness.runtime import Run

    cell = spec_mod.Cell(spec_mod.load_benchmark(), args.workload)
    if args.rehearse:
        cell.rehearse()
    jax.config.update("jax_compilation_cache_dir", os.path.join(spec_mod.CACHE_DIR, "jax"))
    device = program.device_facts(cell.chips)
    if not args.rehearse:
        peaks.peaks_for(device["kind"])
    run = Run(cell=cell, seed=args.seed, seconds=args.seconds, trace=False, rehearse=args.rehearse,
              t_start=time.perf_counter(), peaks=None, emit=lambda r: None)
    driver = spec_mod.load_module("drivers", cell.driver)
    session, _ = driver.build_session(run)
    driver.warm_up(session, run)
    points = []
    for rate in [float(r) for r in args.rates.split(",")]:
        m = driver.measure(session, run, rate, args.seconds)
        plain = [dt for dt, adm in m["rounds"] if not adm]
        point = {
            "rate_rps": rate, "offered": m["offered"], "completed": m["completed"],
            "queue_growing": m["queue_growing"], "tokens_per_s": m["tokens_per_s"], "wall_s": m["wall_s"],
            "ttft_p50_ms": stats.percentile(m["ttft_s"], 0.5) * 1e3, "ttft_p95_ms": stats.percentile(m["ttft_s"], 0.95) * 1e3,
            "gap_p50_ms": stats.percentile(m["gaps_s"], 0.5) * 1e3 if m["gaps_s"] else None,
            "gap_p95_ms": stats.percentile(m["gaps_s"], 0.95) * 1e3 if m["gaps_s"] else None,
            "gap_p99_ms": stats.percentile(m["gaps_s"], 0.99) * 1e3 if m["gaps_s"] else None,
            "plain_round_ms_p50": stats.percentile(plain, 0.5) * 1e3 if plain else None,
            "wave_round_ms_p50": m["wave_ms_p50"], "share_of_rounds_with_a_wave": m["share_of_rounds_with_a_wave"],
            "late_p95_ms": stats.percentile(m["late_s"], 0.95) * 1e3,
        }
        points.append(point)
        print(json.dumps(point), flush=True)
        while session.has_work():  # a saturated point leaves a backlog: clear it before the next
            session.step()
    first_bad = loadgen.detect_knee(points)
    good = [p["rate_rps"] for p in points if first_bad is None or p["rate_rps"] < first_bad]
    summary = {"device": device, "seed": args.seed, "seconds": args.seconds,
               "first_rate_not_sustained": first_bad, "knee_rps": max(good) if good else None, "points": points}
    print(json.dumps({"knee_rps": summary["knee_rps"], "first_rate_not_sustained": first_bad}), flush=True)
    out = os.path.join(ROOT, "chiprun_out", f"sweep.{args.workload}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(summary, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
