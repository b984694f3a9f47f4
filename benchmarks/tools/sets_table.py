#!/usr/bin/env python3
"""The table of a serve cell's sets of runs, and whether its rate stands by the rule.

    python3 benchmarks/tools/sets_table.py <tag>

Reads the logs ``tools/sets.sh`` left (``chiprun_out/<tag>_<set>_<seed>.log``), no jax.
A run counts when it is ``correct`` with ``failed`` 0, no compilation in the window,
no growing backlog, ``slots_live_peak`` under the slots, and at least ``FLOOR_PCT``
of its gaps hold a wave (half again the 5 % at which ``gap_p95_ms`` flips to a plain
round) and at most ``CEILING_PCT`` a wave of several requests (as far under the 5 %
at which it flips to a full wave round); a set when (Q3 - Q1) / median of each
latency the cell reports is at most half that metric's ``bound`` in
``BENCHMARK.json``, the driver's own test of a bound that is too tight.  The rule
is PERF.md section 2's and has no options.  Not part of a benchmark run.
"""

from __future__ import annotations

import collections
import glob
import json
import os
import statistics
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)

from benchmarks.harness import spec as spec_mod, stats  # noqa: E402

FLOOR_PCT = 7.5    # of the gaps hold a wave, at least: 1.5 x the line at which the tail leaves the wave round
CEILING_PCT = 3.3  # of the gaps hold a wave of several requests, at most: the line of the full wave round / 1.5


def read_run(path: str) -> dict:
    lines = [json.loads(x) for x in open(path) if x.startswith("{")]
    phase = {x["phase"]: x for x in lines if "phase" in x}
    last, w = lines[-1] if lines and "correct" in lines[-1] else {}, phase.get("window", {})
    metrics = {k: v["value"] for k, v in last.get("metrics", {}).items()}
    return {"correct": last.get("correct", False), "failed": last.get("failed"), "attempted": last.get("attempted"),
            **metrics, "gaps_pct": 100 * (w.get("share_of_gaps_with_a_wave") or 0.0),
            "full_pct": 100 * (w.get("share_of_gaps_with_a_full_wave") or 0.0),
            "rounds_pct": 100 * w.get("share_of_rounds_with_a_wave", 0.0), "rounds": w.get("rounds"),
            "live": w.get("slots_live_mean"), "peak": w.get("slots_live_peak"), "slots": w.get("slots"),
            "growing": w.get("queue_growing"), "compiles": phase.get("end", {}).get("compiles_in_window"),
            "gap_p50": w.get("gap_ms", {}).get("p50"), "gap_p99": w.get("gap_ms", {}).get("p99"),
            "ttft_p95": w.get("ttft_ms", {}).get("p95"), "wave_ms": w.get("wave_ms_p50"),
            "memory_peak_bytes": last.get("device", {}).get("memory_peak_bytes"), "rate": w.get("rate_rps")}


def main() -> int:
    if len(sys.argv) != 2:
        sys.exit(__doc__)
    tag = sys.argv[1]
    half_bound = {m["name"]: 50.0 * m["bound"] for m in spec_mod.load_benchmark()["end_to_end"]}  # percent
    sets = collections.defaultdict(dict)
    for path in sorted(glob.glob(os.path.join(ROOT, "chiprun_out", f"{tag}_*_*.log"))):
        setn, seed = os.path.basename(path)[len(tag) + 1: -4].rsplit("_", 1)
        sets[setn][seed] = read_run(path)
    stands = bool(sets)
    for setn, runs in sets.items():
        for seed, r in runs.items():
            sound = (r["correct"] and r["failed"] == 0 and r["compiles"] == 0 and not r["growing"]
                     and r["peak"] < r["slots"] and r["gaps_pct"] >= FLOOR_PCT and r["full_pct"] <= CEILING_PCT)
            stands &= sound
            f = lambda k, d=2: "-" if r.get(k) is None else f"{r[k]:.{d}f}"  # noqa: E731
            print(f"{setn} {seed} rate {r['rate']} {'ok ' if sound else 'NOT'} correct={r['correct']} failed={r['failed']}/{r['attempted']} "
                  f"compiles={r['compiles']} growing={r['growing']} gap_p95 {f('gap_p95_ms', 3)} p50 {f('gap_p50', 3)} p99 {f('gap_p99')} "
                  f"ttft_p95 {f('ttft_p95')} setup_s {f('setup_s')} gaps% {f('gaps_pct')} full% {f('full_pct')} rounds% {f('rounds_pct')} of {r['rounds']} "
                  f"live {f('live')} peak {r['peak']}/{r['slots']} wave {f('wave_ms')} mem {r['memory_peak_bytes']}")
        for name in ("gap_p95_ms", "ttft_p95_ms"):
            values = [r[name] for r in runs.values() if r.get(name) is not None]  # none where the cell does not report it
            if len(values) >= 2:
                spread, limit = 100 * stats.iqr_share(values), half_bound[name]
                stands &= spread <= limit
                print(f"  set {setn} {name}: median {statistics.median(values):.4f} min {min(values):.4f} max {max(values):.4f} "
                      f"spread {spread:.3f} % (at most {limit})")
    print("the rate stands" if stands else "the rate does NOT stand")
    return 0


if __name__ == "__main__":
    sys.exit(main())
