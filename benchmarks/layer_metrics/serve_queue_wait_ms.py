"""Mean milliseconds from a request's arrival to the start of the round that
admits it: ``queue_wait_us_sum`` over ``n`` of the traced window's admitting
``serve/admit_prep`` spans.  A 2 s window admits some 26 requests: a mean, not
a tail."""

from benchmarks.harness import program_spans


def read(ctx):
    spans = program_spans.load(ctx)
    if spans is None:
        return None
    admits = [s.stats for s in program_spans.named(spans, "serve/admit_prep") if s.stats.get("n")]
    n = sum(int(st["n"]) for st in admits)
    if not n:
        return None
    return sum(float(st["queue_wait_us_sum"]) for st in admits) / n / 1e3
