#!/usr/bin/env python3
"""Look at a traced run's device operations under their FULL names: the whole
HLO instruction with its operands and layouts, which ``harness/trace.py``'s
short labels drop (an event carries no ``op_name`` metadata and no stats but
its timing: a ``jax.named_scope`` does not show; a Pallas call is named after
its call site).  For each program (``XLA Modules`` name) the operations of
its median run, longest first, go to ``chiprun_out/op_names.<cell>.txt``.

    python3 benchmarks/tools/op_names.py --workload <cell> [--program jit_serve_decode_step]
"""

from __future__ import annotations

import argparse
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--program", default="")
    p.add_argument("--chars", type=int, default=700)
    p.add_argument("--top", type=int, default=40)
    args = p.parse_args()
    from jax.profiler import ProfileData

    from benchmarks.harness import program_spans, trace

    data = ProfileData.from_file(program_spans.trace_path(args.workload))
    out = os.path.join(ROOT, "chiprun_out", f"op_names.{args.workload}.txt")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        for plane in data.planes:
            if not plane.name.startswith(trace.DEVICE_PLANE):
                continue
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            ops = sorted(lines.get(trace.OPS_LINE, []), key=lambda e: e.start_ns)
            by_program: dict[str, list] = {}
            for ev in lines.get(trace.MODULES_LINE, []):
                by_program.setdefault(ev.name.split("(")[0], []).append(ev)
            for name, runs in sorted(by_program.items()):
                if args.program and name != args.program:
                    continue
                run = sorted(runs, key=lambda e: e.duration_ns)[len(runs) // 2]
                inside = [e for e in ops if run.start_ns <= e.start_ns < run.start_ns + run.duration_ns]
                f.write(f"\n==== {plane.name} {name}: {len(runs)} runs, median {run.duration_ns / 1e6:.3f} ms, "
                        f"{len(inside)} operations\n")
                for e in sorted(inside, key=lambda e: -e.duration_ns)[: args.top]:
                    f.write(f"{e.duration_ns / 1e3:9.1f} us  {e.name[: args.chars]}\n")
                f.write(f"---- every operation of that run in order, as harness/trace.py labels it\n")
                for e in inside:
                    short, opcode = trace.label(e.name)
                    if opcode not in trace.CONTAINERS:
                        f.write(f"{e.duration_ns / 1e3:9.1f} us  {short}\n")
            break
    print(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
