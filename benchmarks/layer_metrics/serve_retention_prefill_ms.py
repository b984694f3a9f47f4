"""Device milliseconds of the power-retention layers in one prefill wave: the
attention form's scores, weights and weighted values, and the state and
normaliser a prompt leaves (``ops/retention.py`` ``retention_prefill``: XLA
operations inside a loop over the wave's KV heads, no kernel yet), summed inside
one run of the program ``jit_serve_prefill``, median over the traced window's
runs.

A ``jax.named_scope`` does not reach an event's name and XLA names a fusion
after its operations, so these are told by the SHAPE of what they produce,
which only the retention has in a prefill wave (read from the program
compiled for a described v5e, ``tests/test_chip_compile.py``, and from one
trace by hand, PERF.md, Findings PR 35): anything with the state's
``(rotations, head_dim)`` pair of axes (the features of every key
``f32[T,65,128]``, a head's state ``f32[1,65,128,128]`` and normaliser
``f32[65,128]``, the wave's ``f32[rows x 8,65,128,128]``), the ``(T, T)``
decays and a group's ``(5, T, T)`` weights with their ``(5, T)`` sums, the
transposed keys ``f32[128,T]`` and the heads' outputs ``bf16[rows x 8,5,T,128]``.
The cumulative sums of the gates (a few vectors of T) are not counted."""

import re

from benchmarks.harness import trace

PROGRAM = "jit_serve_prefill"
_SHAPE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def retention_shapes(prompt: int, heads: int, kv_heads: int, head_dim: int):
    """A predicate on an operation's result dims."""
    rep, rot = heads // kv_heads, head_dim // 2 + 1

    def is_retention(dims: tuple[int, ...]) -> bool:
        if (rot, head_dim) in zip(dims, dims[1:]):
            return True
        if dims[-2:] == (prompt, prompt) or dims == (rep, prompt) or dims == (head_dim, prompt):
            return True
        return len(dims) == 4 and dims[1:] == (rep, prompt, head_dim) and dims[0] % kv_heads == 0

    return is_retention


def make_filter(prompt: int, heads: int, kv_heads: int, head_dim: int):
    is_retention = retention_shapes(prompt, heads, kv_heads, head_dim)

    def accept(name: str) -> bool:
        m = _SHAPE.search(trace.family(name).partition(" ")[2])
        return m is not None and is_retention(tuple(int(x) for x in m.group(2).split(",") if x))

    return accept


def read(ctx):
    tr, cfg = ctx.get("trace"), ctx["config"]
    if tr is None or "retention_degree" not in cfg:
        return None
    accept = make_filter(int(ctx["cell"].recipe("prompt_tokens")), cfg["num_attention_heads"],
                         cfg["num_key_value_heads"], cfg["head_dim"])
    runs = []
    for module in {n for n, _, _ in tr["modules"] if n.split("(")[0] == PROGRAM}:
        runs += trace.per_module_run(tr, module, op_filter=accept)
    m = trace.median_or_none(runs)
    return None if not m else m * 1e3
