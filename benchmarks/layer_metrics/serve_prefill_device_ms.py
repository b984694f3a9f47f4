"""Device milliseconds of one prefill wave: the busy union inside the runs of
the programs ``jit_serve_prefill`` and ``jit_serve_admit`` (the names
``ServingEngine._wrap`` gives them on the ``XLA Modules`` line), summed per
wave (a prefill run and the admit runs up to the next prefill), median over
the traced window's waves."""

from benchmarks.harness import trace

PREFILL, ADMIT = "jit_serve_prefill", "jit_serve_admit"


def runs_of(reduced, program):
    """[(start_ns, busy seconds)] of every run of the program, whatever its fingerprint."""
    out = []
    for name in {n for n, _, _ in reduced["modules"] if n.split("(")[0] == program}:
        starts = sorted(s for n, s, _ in reduced["modules"] if n == name)
        out += zip(starts, trace.per_module_run(reduced, name))
    return sorted(out)


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    prefills, admits = runs_of(tr, PREFILL), runs_of(tr, ADMIT)
    waves = []
    for i, (start, busy) in enumerate(prefills):
        until = prefills[i + 1][0] if i + 1 < len(prefills) else float("inf")
        waves.append(busy + sum(b for s, b in admits if start <= s < until))
    m = trace.median_or_none(waves)
    return None if m is None else m * 1e3
