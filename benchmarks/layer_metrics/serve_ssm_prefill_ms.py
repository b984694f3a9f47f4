"""Device milliseconds of the state-space scan in one prefill wave: the chunked
form's products and the scan over the chunks' states (``ops/ssm.py``
``ssm_prefill``: XLA operations, no kernel), summed inside one run of the
program ``jit_serve_prefill``, median over the traced window's runs.

A ``jax.named_scope`` does not reach an event's name and XLA names a fusion
after its operations, so these are told by the SHAPE of what they produce,
which only the scan has in a prefill wave (``tests/test_chip_compile.py`` holds
the predicate to the cell's wave compiled for a described v5e: every result it
accepts carries the scan's scope in its metadata): a state ``(.., heads, N,
P)`` or ``(.., G, H/G, N, P)``, a chunk's ``(L, L)`` weights anywhere, and its
running sums, whose last axes are ``(G, H/G)`` or ``(L, heads)``.  The heads in
front keep an attention result of (prompt, head size) out where the prompt is
as long as the state.  The convolution and the gated norm, a few elementwise
passes over (T, 5120), are not counted."""

import re

from benchmarks.harness import trace

PROGRAM = "jit_serve_prefill"
_SHAPE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def ssm_shapes(heads: int, groups: int, head_dim: int, state: int, chunk: int):
    """A predicate on an operation's result dims."""
    rep = heads // groups

    def is_ssm(dims: tuple) -> bool:
        if dims[-2:] == (state, head_dim) and (dims[-3:-2] == (heads,) or dims[-4:-2] == (groups, rep)):
            return True
        return (chunk, chunk) in zip(dims, dims[1:]) or dims[-2:] in ((groups, rep), (chunk, heads))

    return is_ssm


def make_filter(cfg: dict):
    is_ssm = ssm_shapes(cfg["mamba_n_heads"], cfg["mamba_n_groups"], cfg["mamba_d_head"], cfg["mamba_d_state"],
                        cfg["mamba_chunk_size"])

    def accept(name: str) -> bool:
        m = _SHAPE.search(trace.family(name).partition(" ")[2])
        return m is not None and is_ssm(tuple(int(x) for x in m.group(2).split(",") if x))

    return accept


def read(ctx):
    tr, cfg = ctx.get("trace"), ctx["config"]
    if tr is None or "mamba_d_state" not in cfg:
        return None
    accept = make_filter(cfg)
    runs = []
    for module in {n for n, _, _ in tr["modules"] if n.split("(")[0] == PROGRAM}:
        runs += trace.per_module_run(tr, module, op_filter=accept)
    m = trace.median_or_none(runs)
    return None if not m else m * 1e3
