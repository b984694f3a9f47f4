"""Milliseconds of a plain round in which no operation ran on the device:
the traced window's idle gaps (complement of the busy union) inside each plain
``serve/round``, median per round.  Both clocks: the span is the program's,
the gaps are the device's."""

from benchmarks.harness import program_spans


def read(ctx):
    spans = program_spans.load(ctx)
    if spans is None:
        return None
    gaps = program_spans.idle_gaps(ctx["trace"])
    plain, _ = program_spans.rounds(spans)
    return program_spans.median_ms([program_spans.idle_within(gaps, rd.start, rd.end) for rd, _ in plain])
