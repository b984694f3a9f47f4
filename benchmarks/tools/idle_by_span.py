#!/usr/bin/env python3
"""Where the device idles, by what the host was doing: for the last traced run
of a cell, the device-idle time of the traced window by innermost span (the
program's ``serve/...`` / ``train/...`` spans and the benchmark's own
annotations together), the share that falls in no program span, the ten
longest gaps with their span, and each span's count and median duration (the
traced window's own round or step, for the cost of tracing).  By hand, not
part of a run.

    python3 benchmarks/tools/idle_by_span.py --workload <cell> [--excerpt-rounds 4]

``--excerpt-rounds N`` also writes ``chiprun_out/span_excerpt.<cell>.spans.json``:
the spans and the device's busy intervals of N consecutive rounds around the
window's middle wave, in the form ``tests/test_program_spans.py`` reads.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)


def table(reduced: dict, spans: list, window_annotation: str) -> dict:
    """The numbers the tool prints, from a reduced trace and every span;
    ``window_annotation`` names the benchmark's spans that make the window."""
    from benchmarks.harness import program_spans as ps

    gaps = ps.idle_gaps(reduced)
    spans = ps.in_window(spans, reduced["window_ns"])
    program = [s for s in spans if s.name.startswith(ps.PREFIXES)]
    by_span = ps.idle_by_innermost_span(gaps, spans)
    by_program_span = ps.idle_by_innermost_span(gaps, program)
    idle_ns = sum(b - a for a, b in gaps)
    window_idle_ns = round((reduced["window_s"] - reduced["busy_s"]) * 1e9)
    # inside the benchmark's window spans (serve_step, train_pass): how much of the idle time a LEAF program span holds
    # (one with no program span inside it: serve/round's own time is what its children leave)
    parents = {p.name for p in program if ps.children(program, p)}
    steps = [s for s in spans if s.name == window_annotation]
    in_steps = sum(ps.idle_within(gaps, s.start, s.end) for s in steps)
    segments = [(lo, hi, n) for lo, hi, n in ps.innermost_segments(program) if n not in parents]
    in_leaves = sum(ps.idle_within(gaps, max(lo, s.start), min(hi, s.end))
                    for s in steps for lo, hi, n in segments if hi > s.start and lo < s.end)
    segs = ps.innermost_segments(spans)

    def doing(mid: int) -> str:
        return next((n for lo, hi, n in segs if lo <= mid < hi), "(no span)")

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    durations: dict[str, list[int]] = {}
    for s in spans:
        durations.setdefault(s.name, []).append(s.dur)
    return {
        "window_s": reduced["window_s"], "busy_s": reduced["busy_s"],
        "idle_s": idle_ns / 1e9, "window_idle_s": window_idle_ns / 1e9,
        "idle_s_by_innermost_span": {str(k or "(no span)"): v / 1e9 for k, v in
                                     sorted(by_span.items(), key=lambda kv: -kv[1])},
        "idle_s_in_no_program_span": by_program_span[None] / 1e9,
        "share_in_no_program_span": by_program_span[None] / idle_ns if idle_ns else 0.0,
        "idle_s_inside_" + window_annotation: in_steps / 1e9,
        "share_of_that_in_a_leaf_program_span": in_leaves / in_steps if in_steps else None,
        "longest_gaps": [[doing((a + b) // 2), (b - a) / 1e9] for a, b in longest],
        "span_count_and_median_ms": {n: [len(d), statistics.median(d) / 1e6] for n, d in sorted(durations.items())},
    }


def excerpt(reduced: dict, spans: list, n_rounds: int) -> dict:
    """Spans and busy intervals of ``n_rounds`` consecutive rounds, the window's
    middle wave round second among them; times from 1 ms before the first."""
    from benchmarks.harness import program_spans as ps
    from benchmarks.harness import trace

    rounds = ps.named(spans, "serve/round")
    _, wave = ps.rounds(spans)
    first = max(0, rounds.index(wave[len(wave) // 2][0]) - 1) if wave else 0
    kept = rounds[first:first + n_rounds]
    lo, hi = kept[0].start - 1_000_000, kept[-1].end + 1_000_000
    busy = trace.union([(max(s, lo), min(s + d, hi)) for _, s, d in reduced["ops"] if s + d > lo and s < hi])
    return {
        "window_ns": [0, hi - lo],
        "busy": [[a - lo, b - a] for a, b in busy],
        "modules": [[n, s - lo, d] for n, s, d in reduced["modules"] if s >= lo and s + d <= hi],
        "spans": [[s.name, s.start - lo, s.dur, s.stats] for s in spans if s.start >= lo and s.end <= hi],
    }


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--excerpt-rounds", type=int, default=0)
    args = p.parse_args()

    from benchmarks.harness import program_spans as ps
    from benchmarks.harness import spec as spec_mod, trace

    cell = spec_mod.Cell(spec_mod.load_benchmark(), args.workload)
    path = ps.trace_path(cell.name)
    # the benchmark's own annotations are the driver's; the first is the one it gives as the window's
    annotations = spec_mod.load_module("drivers", cell.driver).ANNOTATIONS
    window = annotations[0]
    reduced = trace.reduce(trace.extract(path, annotations), chips=cell.chips, window_annotation=window)
    spans = ps.read_spans(path, keep=lambda name: name.startswith(ps.PREFIXES) or name in annotations)
    out = table(reduced, spans, window)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"idle_by_span.{cell.name}.json"), "w") as f:
        json.dump(out, f, indent=1)
    in_window = ps.in_window(spans, reduced["window_ns"])
    if args.excerpt_rounds and ps.named(in_window, "serve/round"):  # an older program has no round to excerpt
        with open(os.path.join(out_dir, f"span_excerpt.{cell.name}.spans.json"), "w") as f:
            json.dump(excerpt(reduced, in_window, args.excerpt_rounds), f)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
