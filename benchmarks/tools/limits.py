#!/usr/bin/env python3
"""Read, in one process, the numbers the limits of ``correct`` are set from.

    python3 benchmarks/tools/limits.py --workload <cell> --seeds 11,12,13 [--control-seeds 11,12,13] --seconds 2

For each seed: one whole run of the cell's driver (a new program object, the
compiled programs from the persistent cache after the first), with a short
window at the cell's own sizes and load; for the control seeds also the
control.  Every number compared, per seed, and at the end the largest the
sound runs gave and the smallest the control gave, go to
``chiprun_out/limits.<cell>.json``.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import contextlib
import io
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
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--seconds", type=float, default=2.0)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}

    from benchmarks import run as run_mod

    tag = ".rehearsal" if args.rehearse else ""
    out_path = os.path.join(ROOT, "chiprun_out", f"limits.{args.workload}{tag}.json")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    per_seed = []
    for seed in seeds:
        argv = ["--workload", args.workload, "--seed", str(seed), "--seconds", str(args.seconds), "--trace", "0"]
        if seed in control_seeds:
            argv.append("--control")
        if args.rehearse:
            argv.append("--rehearse")
        buf = io.StringIO()
        t0 = time.perf_counter()
        with contextlib.redirect_stdout(buf):
            rc = run_mod.main(argv)
        lines = [json.loads(x) for x in buf.getvalue().splitlines() if x.startswith("{")]
        rec = {"seed": seed, "rc": rc, "seconds": round(time.perf_counter() - t0, 1),
               "checks": {x["check"]: x["value"] for x in lines if "check" in x},
               "controls": {x["control"]: x["value"] for x in lines if "control" in x},
               "result": lines[-1] if lines and "correct" in lines[-1] else None,
               "phases": [x for x in lines if x.get("phase") in ("window", "check")]}
        per_seed.append(rec)
        print(json.dumps({k: rec[k] for k in ("seed", "rc", "seconds", "checks", "controls")}), flush=True)
        with open(out_path, "w") as f:
            json.dump(per_seed, f, indent=1)
    names = sorted({n for r in per_seed for n in r["checks"]})
    summary = {
        n: {"sound_max": max(r["checks"][n] for r in per_seed if n in r["checks"]),
            "control_min": min((r["controls"][n] for r in per_seed if n in r["controls"]), default=None)}
        for n in names
    }
    print(json.dumps({"summary": summary}), flush=True)
    with open(out_path, "w") as f:
        json.dump({"per_seed": per_seed, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
