#!/usr/bin/env python3
"""The rate sweep that places a serve cell's fixed rate, once, on the chip.

    python3 benchmarks/tools/sweep.py --workload <cell> --seed 5 --seconds 25 --rates 4,8,12,16,20,24
    python3 benchmarks/tools/sweep.py --workload <cell> --seeds 11,12,13,14,15,16 --seconds 30 --rates 9.0,9.6 --tag try

One process, one engine, the cell's own sizes: for each rate an open-loop
window, then the table and the knee (the highest rate whose completions keep
up, 0.95 of the offered, with no growing backlog).  With ``--seeds`` every rate
gets one window a seed (the traffic's seed; the weights stay ``--seed``'s) and
the spread of ``gap_p95_ms`` and ``ttft_p95_ms`` over each six of them: what a
candidate rate's sets would read, for a third of the chip time of whole runs.
The result goes to ``chiprun_out/sweep.<cell>[.<tag>].json`` and into the
traffic file's ``why`` by hand.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import dataclasses
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
    p.add_argument("--seeds", default="", help="traffic seeds: one window a rate for each")
    p.add_argument("--tag", default="")
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
    seeds = [int(s) for s in args.seeds.split(",")] if args.seeds else [args.seed]
    for rate, seed in [(float(r), s) for r in args.rates.split(",") for s in seeds]:
        m = driver.measure(session, dataclasses.replace(run, seed=seed), rate, args.seconds)
        plain = [dt for dt, adm in m["rounds"] if not adm]
        point = {
            "rate_rps": rate, "seed": seed, "offered": m["offered"], "completed": m["completed"],
            "queue_growing": m["queue_growing"], "tokens_per_s": m["tokens_per_s"], "wall_s": m["wall_s"],
            "ttft_p50_ms": stats.percentile(m["ttft_s"], 0.5) * 1e3, "ttft_p95_ms": stats.percentile(m["ttft_s"], 0.95) * 1e3,
            "gap_mean_ms": sum(m["gaps_s"]) / len(m["gaps_s"]) * 1e3 if m["gaps_s"] else None,
            "gap_p50_ms": stats.percentile(m["gaps_s"], 0.5) * 1e3 if m["gaps_s"] else None,
            "gap_p95_ms": stats.percentile(m["gaps_s"], 0.95) * 1e3 if m["gaps_s"] else None,
            "gap_p99_ms": stats.percentile(m["gaps_s"], 0.99) * 1e3 if m["gaps_s"] else None,
            "plain_round_ms_p50": stats.percentile(plain, 0.5) * 1e3 if plain else None,
            "wave_round_ms_p50": m["wave_ms_p50"], "share_of_rounds_with_a_wave": m["share_of_rounds_with_a_wave"],
            "share_of_gaps_with_a_wave": m["share_of_gaps_with_a_wave"],
            "share_of_gaps_with_a_full_wave": m["share_of_gaps_with_a_full_wave"],
            "slots_live_mean": m["slots_live_mean"], "slots_live_peak": m["slots_live_peak"],
            "late_p95_ms": stats.percentile(m["late_s"], 0.95) * 1e3,
        }
        points.append(point)
        print(json.dumps(point), flush=True)
        while session.has_work():  # a saturated point leaves a backlog: clear it before the next
            session.step()
    summary = {"device": device, "seed": args.seed, "seconds": args.seconds, "points": points}
    if args.seeds:  # a candidate rate's sets: the spread over each six windows, as the bounds are read
        summary["spreads"] = [
            {"rate_rps": rate, "metric": name, "values": values,
             "spread_of_each_six": [stats.iqr_share(values[k:k + 6]) for k in range(0, len(values), 6) if len(values[k:k + 6]) > 1]}
            for rate in dict.fromkeys(p["rate_rps"] for p in points) for name in ("gap_p95_ms", "ttft_p95_ms")
            for values in [[p[name] for p in points if p["rate_rps"] == rate]]]
        for row in summary["spreads"]:
            print(json.dumps(row), flush=True)
    else:
        first_bad = loadgen.detect_knee(points)
        good = [p["rate_rps"] for p in points if first_bad is None or p["rate_rps"] < first_bad]
        summary.update(first_rate_not_sustained=first_bad, knee_rps=max(good) if good else None)
        print(json.dumps({"knee_rps": summary["knee_rps"], "first_rate_not_sustained": first_bad}), flush=True)
    out = os.path.join(ROOT, "chiprun_out", f"sweep.{args.workload}{'.' + args.tag if args.tag else ''}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(summary, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
