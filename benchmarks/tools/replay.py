#!/usr/bin/env python3
"""A serve cell's open-loop window replayed as arithmetic, on the harness's own schedule: no jax, no chip.

    python3 benchmarks/tools/replay.py --workload <cell> --rates 6,7,8.4 [--seeds 48] [--seconds 30]
    python3 benchmarks/tools/replay.py --workload <cell> --rates 9 --plain-ms 5.17,0.385 --wave-ms 20.1,80

A round costs ``a + b n`` ms at n live slots, and a wave round one wave more: the
one-row program for one waiting request, the ``prefill_batch``-row program for
several (``round_costs.json`` beside this file: each cell's costs as the chip read
them, with where from; ``--plain-ms`` / ``--wave-ms`` for a cell or a program it does
not have).  Arrivals and budgets are the harness's (``loadgen.schedule``, the budgets
of ``loadgen.requests``), slots and wave rows the cell's traffic file's.  For each
rate, over ``--seeds`` seeds: ``gap_p95_ms`` and ``ttft_p95_ms`` with the spread of
each six (as a bound is read), both gap shares, the live slots.  It places a rate
before the chip is asked (PR 44's predictions were made with it: PERF.md section 6)
and says which way a shorter round moves a cell; the chip decides.  It knows nothing
of the host, of incidents, or of a wave's hand-over: read its percentiles to a
percent, its shares to a few tenths of a point.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import statistics
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)

from benchmarks.harness import loadgen, spec as spec_mod, stats, text  # noqa: E402


def budgets_for(seed: int, n: int, output_tokens) -> list:
    """The budgets ``loadgen.requests`` draws, without its prompts (``benchmarks/tests/test_arithmetic.py`` holds the two together)."""
    rng = text.rng_for(seed, 3)
    budgets = stats.stratified(int(output_tokens[0]), int(output_tokens[1]), n)
    return [budgets[int(j)] for j in rng.permutation(n)]


def replay(seed: int, rate_rps: float, seconds: float, *, output_tokens, prefill_batch: int, max_slots: int,
           plain_ms, wave_ms, jitter: float = 0.003) -> dict:
    """One window: every round admits what waits (at most ``prefill_batch`` and the free slots), then every live slot gets a token."""
    arrivals = [float(t) for t in loadgen.schedule(seed, rate_rps, seconds)]
    budgets = budgets_for(seed, len(arrivals), output_tokens)
    noise = random.Random(seed)
    t, nxt, queue, left, tokens_at, rounds_at, rounds = 0.0, 0, [], {}, {}, {}, []
    while t <= seconds + 15.0:
        while nxt < len(arrivals) and arrivals[nxt] <= t:
            queue.append(nxt)
            nxt += 1
        if not queue and not left:
            if nxt >= len(arrivals):
                break
            t = arrivals[nxt]
            continue
        rows = min(prefill_batch, len(queue), max_slots - len(left))
        for r in queue[:rows]:
            left[r], tokens_at[r], rounds_at[r] = budgets[r], [], []
        queue = queue[rows:]
        live = len(left)
        ms = plain_ms[0] + plain_ms[1] * live + (wave_ms[0] if rows == 1 else wave_ms[1] if rows else 0.0)
        t += ms * 1e-3 * (1 + jitter * noise.gauss(0, 1))
        rounds.append((live, rows, ms))
        for r in list(left):
            tokens_at[r].append(t)
            rounds_at[r].append(len(rounds) - 1)
            left[r] -= 1
            if not left[r]:
                del left[r]
    done = [r for r in tokens_at if r not in left]
    gaps = [b - a for r in done for a, b in zip(tokens_at[r], tokens_at[r][1:])]
    seen, admitted = [rounds_at[r] for r in done], [rows for _, rows, _ in rounds]
    return {"gap_p95_ms": stats.percentile(gaps, 0.95) * 1e3, "gap_p99_ms": stats.percentile(gaps, 0.99) * 1e3,
            "ttft_p95_ms": stats.percentile([tokens_at[r][0] - arrivals[r] for r in tokens_at], 0.95) * 1e3,
            "gaps_pct": 100 * loadgen.share_of_gaps_with_a_wave(seen, admitted),
            "full_pct": 100 * loadgen.share_of_gaps_with_a_wave(seen, admitted, of_at_least=2),
            "live": sum(live * ms for live, _, ms in rounds) / sum(ms for _, _, ms in rounds), "peak": max(live for live, _, _ in rounds),
            "unfinished": len(queue) + len(left)}


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seeds", type=int, default=48, help="how many seeds a rate")
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--plain-ms", default="", help="a,b: a plain round is a + b n ms at n live slots")
    p.add_argument("--wave-ms", default="", help="w1,wN: the one-row wave and the wave of prefill_batch rows")
    args = p.parse_args()
    cell = spec_mod.Cell(spec_mod.load_benchmark(), args.workload)
    costs = spec_mod.load_json(os.path.join(BENCH_DIR, "tools", "round_costs.json")).get(args.workload, {})
    plain = [float(x) for x in args.plain_ms.split(",")] if args.plain_ms else costs["plain_ms"]
    wave = [float(x) for x in args.wave_ms.split(",")] if args.wave_ms else costs["wave_ms"]
    sizes = dict(output_tokens=cell.recipe("output_tokens"), prefill_batch=int(cell.recipe("prefill_batch")),
                 max_slots=int(cell.recipe("max_slots")), plain_ms=plain, wave_ms=wave)
    seeds = [2100000021 + 100000012 * k for k in range(args.seeds)]
    for rate in [float(r) for r in args.rates.split(",")]:
        runs = [replay(s, rate, args.seconds, **sizes) for s in seeds]
        row = {"workload": args.workload, "rate_rps": rate, "seeds": len(runs)}
        for name in ("gap_p95_ms", "ttft_p95_ms"):
            values = [r[name] for r in runs]
            six = [100 * stats.iqr_share(values[k:k + 6]) for k in range(0, len(values) - 5, 6)]
            row[name] = {"min": round(min(values), 3), "median": round(statistics.median(values), 3), "max": round(max(values), 3),
                         "spread_pct_of_each_six": {"median": round(statistics.median(six), 2), "max": round(max(six), 2)} if six else None}
        for name in ("gaps_pct", "full_pct", "live", "peak", "gap_p99_ms", "unfinished"):
            values = [r[name] for r in runs]
            row[name] = [round(min(values), 2), round(max(values), 2)]
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
