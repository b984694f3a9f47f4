"""Share of the rows the prefill waves computed that a request filled:
100 x sum of ``rows`` over sum of ``rows_computed``, the counters the engine
sets on each wave's ``serve/prefill_dispatch`` span, over the traced window's
waves.  Below the knee nearly every wave admits one request; what the rest of
its rows cost, every live slot waits for.

A program from before the counters ran every wave at the cell's
``prefill_batch`` rows and said on ``serve/admit_prep`` how many it admitted
(``n``): its share is read from those."""

from benchmarks.harness import program_spans


def fill_pct(spans, prefill_batch: int) -> float | None:
    waves = program_spans.named(spans, "serve/prefill_dispatch")
    if any("rows_computed" in s.stats for s in waves):
        rows = sum(int(s.stats["rows"]) for s in waves)
        computed = sum(int(s.stats["rows_computed"]) for s in waves)
    else:
        rows = sum(int(s.stats.get("n", 0)) for s in program_spans.named(spans, "serve/admit_prep"))
        computed = prefill_batch * len(waves)
    return 100.0 * rows / computed if computed else None


def read(ctx):
    spans = program_spans.load(ctx)
    if spans is None:
        return None
    return fill_pct(spans, int(ctx["cell"].recipe("prefill_batch")))
