"""Device milliseconds of the expert products in one decode round: summed
durations of the grouped products over the experts inside one run of the
program ``jit_serve_decode_step`` (three an expert layer: gate, up, down),
median over the traced window's runs.

How the chip's trace shows them (found by looking at one trace by hand,
PERF.md, Findings PR 28): the Pallas grouped matmul ``ops/moe.py``
``grouped_dot`` issues is a custom call named ``gmm`` / ``gmm.<n>`` after the
kernel; where it does not tile, XLA's own ``jax.lax.ragged_dot`` shows as the
custom call ``ragged-dot-none`` / ``ragged-dot-none.<n>`` (its
``ragged-dot-metadata`` twin only lays out the group offsets and is routing).
A ``jax.named_scope`` does not reach an event's name."""

from benchmarks.harness import trace

PROGRAM = "jit_serve_decode_step"
EXPERT_PRODUCTS = ("gmm", "ragged-dot-none")


def is_expert_product(name: str) -> bool:
    head, _, rest = trace.family(name).partition(" ")
    return head in EXPERT_PRODUCTS and rest.startswith("custom-call")


def per_decode_run(reduced: dict, op_filter) -> list[float]:
    """Seconds of the accepted operations inside each run of the decode program."""
    runs = []
    for module in {n for n, _, _ in reduced["modules"] if n.split("(")[0] == PROGRAM}:
        runs += trace.per_module_run(reduced, module, op_filter=op_filter)
    return runs


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    m = trace.median_or_none(per_decode_run(tr, is_expert_product))
    return None if not m else m * 1e3
