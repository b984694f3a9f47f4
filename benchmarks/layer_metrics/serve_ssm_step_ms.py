"""Device milliseconds of the state-space steps in one decode round: summed
durations of the ``ssm_step`` custom calls (``ops/ssm.py``: the Pallas kernel
that decays, updates and reads each live slot's state and steps over an idle
one's, one call a layer) inside one run of the program
``jit_serve_decode_step``, median over the traced window's runs.

How the trace shows them: a custom call named after the jitted function that
issues it, ``ssm_step`` / ``ssm_step.<n>`` (read from the program compiled for
a described v5e, ``tests/test_chip_compile.py``).  A program without the kernel
has no such call, and the metric is not reported."""

from benchmarks.harness import spec as spec_mod, trace

per_decode_run = spec_mod.load_module("layer_metrics", "serve_moe_experts_ms").per_decode_run  # by op filter, a run of the decode program
KERNEL = "ssm_step"


def is_ssm_step(name: str) -> bool:
    head, _, rest = trace.family(name).partition(" ")
    return head == KERNEL and rest.startswith("custom-call")


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    m = trace.median_or_none(per_decode_run(tr, is_ssm_step))
    return None if not m else m * 1e3
