"""Device milliseconds of the sliding-window layers' decode attention in one
decode round: summed durations of the ``window_decode`` custom calls
(``ops/flash_attention.py`` ``flash_decode`` with ``ring``: one short q block
a slot against the layer's ring leaf, ``(slots, window, kv_heads x
head_dim)``; one call a window layer) inside one run of the program
``jit_serve_decode_step``, median over the traced window's runs.

How the trace shows them: a custom call named after the jitted function that
issues it and the kernel's own name, ``window_decode`` / ``window_decode.<n>``
(read from the program compiled for a described v5e,
``tests/test_chip_compile.py``), whatever its call site, so that no metric
tells a window layer's step from a full layer's (``serve_decode_attn_ms``:
``self_attn.<n>``) by its shape.  A program without the kernel has no such
call, and the metric is not reported."""

from benchmarks.harness import spec as spec_mod, trace

per_decode_run = spec_mod.load_module("layer_metrics", "serve_moe_experts_ms").per_decode_run  # by op filter, a run of the decode program
KERNEL = "window_decode"


def is_window_decode(name: str) -> bool:
    head, _, rest = trace.family(name).partition(" ")
    return head == KERNEL and rest.startswith("custom-call")


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    m = trace.median_or_none(per_decode_run(tr, is_window_decode))
    return None if not m else m * 1e3
