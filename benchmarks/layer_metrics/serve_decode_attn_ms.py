"""Device milliseconds of decode self-attention in one decode round: summed
durations of the self-attention kernels inside one run of the program
``jit_serve_decode_step`` (twelve calls for bart-large-cnn, one a decoder
layer), median over the traced window's runs.

The operation is how the chip's trace shows ``ops/flash_attention.py``
``flash_decode`` when ``ops/mha.py`` issues it for a cached step: a custom call
named after its call site alone, ``self_attn.<n>``, whose result is the q
block, ``bf16[slots, heads, q rows, head_dim]`` with at most
``MAX_DECODE_Q_ROWS`` = 8 rows (one for a plain step, up to eight for a
speculative verify) — found by looking at one trace by hand (PERF.md,
Findings PR 26).  The prefill's flash kernel in the other programs is
``self_attn._flash_run.<n>`` and is not counted.  A decode attention under
another name adds its pattern through a new metric file, not by editing this
one."""

import re

from benchmarks.harness import trace

PROGRAM = "jit_serve_decode_step"
SITE = "self_attn"
MAX_Q_ROWS = 8
_RESULT = re.compile(r"custom-call [a-z0-9]+\[\d+,\d+,(\d+),\d+\]$")


def is_decode_attn(name: str) -> bool:
    head, _, rest = trace.family(name).partition(" ")
    m = _RESULT.match(rest)
    return head == SITE and m is not None and int(m.group(1)) <= MAX_Q_ROWS


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    runs = []
    for module in {n for n, _, _ in tr["modules"] if n.split("(")[0] == PROGRAM}:
        runs += trace.per_module_run(tr, module, op_filter=is_decode_attn)
    m = trace.median_or_none(runs)
    return None if not m else m * 1e3
