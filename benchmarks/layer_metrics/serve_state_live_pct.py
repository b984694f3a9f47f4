"""Share of the state traffic of a decode round that serves a request:
100 x ``slots_live`` / ``slots_streamed``, the counters the engine sets on
each round's ``serve/decode_dispatch`` span, median over the traced window's
rounds.  100 where the step walks the live slots alone (the retention kernel's
live list, since PR 36); where a program streams every slot of the flat cache,
the idle ones' share is what every live slot waits for."""

import statistics

from benchmarks.harness import program_spans


def live_pct(spans) -> float | None:
    got = [100.0 * float(s.stats["slots_live"]) / float(s.stats["slots_streamed"])
           for s in program_spans.named(spans, "serve/decode_dispatch") if s.stats.get("slots_streamed")]
    return statistics.median(got) if got else None


def read(ctx):
    spans = program_spans.load(ctx)
    return None if spans is None else live_pct(spans)
