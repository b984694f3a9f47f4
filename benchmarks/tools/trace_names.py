#!/usr/bin/env python3
"""Look at a trace by hand: the planes and lines of the last traced run of a
cell, the operations and programs that took most device time, and a small
excerpt in the ``extract`` form for the reduction's test.

    python3 benchmarks/tools/trace_names.py --workload <cell> [--excerpt-events 3000]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--annotations", default="train_pass,serve_step,serve_submit")
    p.add_argument("--excerpt-events", type=int, default=12000)
    args = p.parse_args()
    from jax.profiler import ProfileData

    from benchmarks.harness import spec as spec_mod, trace

    path = os.path.join(spec_mod.CACHE_DIR, "trace", args.workload, trace.XPLANE_FILE)
    data = ProfileData.from_file(path)
    layout = [{"plane": pl.name, "lines": [{"line": ln.name, "events": len(list(ln.events))} for ln in pl.lines]}
              for pl in data.planes]
    events = trace.extract(path, args.annotations.split(","))
    reduced = trace.reduce(events, chips=1, window_annotation=args.annotations.split(",")[0])
    ops = sorted(reduced["op_totals_ns"].items(), key=lambda kv: -kv[1])
    mods: dict = {}
    for n, _, d in reduced["modules"]:
        mods[n] = mods.get(n, [0, 0])
        mods[n][0] += 1
        mods[n][1] += d
    plane = max(events["devices"], key=lambda k: len(events["devices"][k].get(trace.OPS_LINE, [])))
    main = trace.main_module(reduced)
    runs = sorted(e[1] for e in reduced["modules"] if e[0] == main)
    lo = runs[min(1, len(runs) - 1)]  # from the second run of the main program on
    keep_ops = sorted(events["devices"][plane][trace.OPS_LINE], key=lambda e: e[1])
    keep_ops = [e for e in keep_ops if e[1] >= lo][: args.excerpt_events]
    hi = keep_ops[-1][1] + keep_ops[-1][2] if keep_ops else lo
    labels = sorted({e[0] for e in keep_ops})
    index = {n: i for i, n in enumerate(labels)}
    excerpt = {  # operations as [label index, start - lo, duration]: a step is ~47,000 of them
        "t0_ns": lo, "labels": labels, "plane": plane,
        "ops": [[index[n], s - lo, d] for n, s, d in keep_ops],
        "modules": [[n, s - lo, d] for n, s, d in events["devices"][plane].get(trace.MODULES_LINE, []) if lo <= s <= hi],
        "host": [[n, s - lo, d] for n, s, d in events["host"] if s <= hi and s + d >= lo],
    }
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"trace_names.{args.workload}.json"), "w") as f:
        json.dump({"xplane_bytes": os.path.getsize(path), "layout": layout,
                   "busy_s": reduced["busy_s"], "window_s": reduced["window_s"],
                   "top_ops": [[n, t / 1e9] for n, t in ops[:80]], "n_op_names": len(ops),
                   "modules": {n: {"runs": c, "seconds": t / 1e9} for n, (c, t) in mods.items()},
                   "idle_gaps": reduced["idle_gaps"]}, f, indent=1)
    with open(os.path.join(out_dir, f"trace_excerpt.{args.workload}.events.json"), "w") as f:
        json.dump(excerpt, f)
    print(json.dumps({"planes": [x["plane"] for x in layout], "top_ops": [[n, round(t / 1e9, 4)] for n, t in ops[:25]],
                      "modules": {n: {"runs": c, "seconds": round(t / 1e9, 4)} for n, (c, t) in mods.items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
