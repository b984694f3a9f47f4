"""Device milliseconds of decode CROSS-attention in one decode round: summed
durations of the cross-attention kernels inside one run of the program
``jit_serve_decode_step`` (twelve calls for bart-large-cnn, one a decoder
layer), median over the traced window's runs.

The operation is ``ops/flash_attention.py`` ``flash_decode`` as ``ops/mha.py``
issues it for a one-token step over a slot's cross K/V kept as a cache keeps
K/V, (slots, source length, heads x head_dim) (PR 46): a custom call named
after its call site alone, ``cross_attn.<n>``, whose result is the q block,
``bf16[slots, heads, q rows, head_dim]`` with at most ``MAX_DECODE_Q_ROWS`` = 8
rows: the shape of ``serve_decode_attn_ms.py``, whose site is ``self_attn``
and which does not see this call.  A program whose cross attention is XLA's
(the fusions ``fusion bf16[64,16,1,64]`` and ``multiply_reduce_fusion
f32[64,16,1024]`` of every tree before PR 46, T5's own attention class) has no
such custom call, and the metric is left out of its line."""

import re

from benchmarks.harness import trace

PROGRAM = "jit_serve_decode_step"
SITE = "cross_attn"
MAX_Q_ROWS = 8
_RESULT = re.compile(r"custom-call [a-z0-9]+\[\d+,\d+,(\d+),\d+\]$")


def is_cross_attn(name: str) -> bool:
    head, _, rest = trace.family(name).partition(" ")
    m = _RESULT.match(rest)
    return head == SITE and m is not None and int(m.group(1)) <= MAX_Q_ROWS


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    runs = []
    for module in {n for n, _, _ in tr["modules"] if n.split("(")[0] == PROGRAM}:
        runs += trace.per_module_run(tr, module, op_filter=is_cross_attn)
    m = trace.median_or_none(runs)
    return None if not m else m * 1e3
