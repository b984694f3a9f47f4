"""Share of the K/V a decode round's program reads that its attention needs:
100 x ``kv_positions_live`` / ``kv_positions_streamed``, the counters the
engine sets on each round's ``serve/decode_dispatch`` span (over the attention
layers: the live slots' own positions, on a window layer at most the window,
against every slot's whole leaf), median over the traced window's rounds.  The
flat cache reserves every slot at its full length and the decode kernel's grid
walks all of it; what the dead entries cost, every live slot waits for."""

import statistics

from benchmarks.harness import program_spans


def live_pct(spans) -> float | None:
    got = [100.0 * float(s.stats["kv_positions_live"]) / float(s.stats["kv_positions_streamed"])
           for s in program_spans.named(spans, "serve/decode_dispatch") if s.stats.get("kv_positions_streamed")]
    return statistics.median(got) if got else None


def read(ctx):
    spans = program_spans.load(ctx)
    return None if spans is None else live_pct(spans)
