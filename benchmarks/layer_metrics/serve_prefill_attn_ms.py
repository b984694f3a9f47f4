"""Device milliseconds of a prefill wave's attention: summed durations of the
``prompt_attn`` custom calls (``ops/flash_attention.py``
``flash_prompt_attention``: the flash forward kernel a cached prompt takes, its
window flavour on a sliding-window layer; one call an attention layer) inside
one run of the program ``jit_serve_prefill``, median over the traced window's
runs.

How the trace shows them: a custom call named after the jitted function that
issues it and the kernel's own name, ``prompt_attn`` / ``prompt_attn.<n>``,
whatever its call site.  A wave whose prompts stay on XLA's path
(``ops/mha.py`` ``select_attention_impl``: scores that fit) has no such call,
and the metric is not reported."""

from benchmarks.harness import trace

PROGRAM = "jit_serve_prefill"
KERNEL = "prompt_attn"


def is_prompt_attn(name: str) -> bool:
    head, _, rest = trace.family(name).partition(" ")
    return head == KERNEL and rest.startswith("custom-call")


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    runs = []
    for module in {n for n, _, _ in tr["modules"] if n.split("(")[0] == PROGRAM}:
        runs += trace.per_module_run(tr, module, op_filter=is_prompt_attn)
    m = trace.median_or_none(runs)
    return None if not m else m * 1e3
