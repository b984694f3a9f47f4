"""Device-busy milliseconds inside one run of the step program: union of the
operation intervals within each ``XLA Modules`` event of the step, median
over the traced steps."""

from benchmarks.harness import trace


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    runs = trace.per_module_run(tr, trace.main_module(tr))
    m = trace.median_or_none(runs)
    return None if m is None else m * 1e3
