"""Device milliseconds of the routing around the expert products in one decode
round: the router's product and scores, the top-k, the sort of the
assignments, the gather of the rows into expert order, the unsort and the
gate-weighted sum, summed inside one run of ``jit_serve_decode_step``, median
over the traced window's runs.

A ``jax.named_scope`` does not reach an event's name and XLA names a fusion
after its operations, so these are told by the SHAPE of what they produce,
which only the routing has in a decode round (looked at in one trace by hand,
PERF.md, Findings PR 28): a leading axis of slots x top_k rows (the sorted
batch: ``bf16[512,2048]``, ``s32[512]``), the router's (slots, experts) scores
(``f32[128,32]``), the (slots, top_k) choices and weights, the
(slots, top_k, hidden) unsorted outputs, and the short int vectors of the
per-expert counts, offsets and the grouped products' work lists (``s32[32]``,
``s32[33]``, ``s32[35]``, ``s32[70]``).  The last gate-weighted sum produces
(slots, hidden), as a dozen other operations do, and is not counted (13 us a
layer).  The expert products themselves
(``serve_moe_experts_ms``) are left out; ``ragged-dot-metadata`` is in."""

import re

from benchmarks.harness import spec as spec_mod, trace

experts_ms = spec_mod.load_module("layer_metrics", "serve_moe_experts_ms")
_SHAPE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
ROW_TILE = 128  # the smallest row tile a grouped product's metadata is laid out for


def routing_shapes(slots: int, experts: int, top_k: int):
    """A predicate on an operation's result (dtype, dims)."""
    rows = slots * top_k
    tiles = experts + rows // ROW_TILE  # work items of a grouped product: its offsets, ids and tile ids

    def is_routing(dtype: str, dims: tuple[int, ...]) -> bool:
        if not dims:
            return False
        if dims[0] == rows or dims == (slots, experts) or dims[:2] == (slots, top_k):
            return True
        # the per-expert counts and offsets, and the grouped products' work lists: short int vectors
        return dtype == "s32" and experts - 1 <= dims[0] <= 2 * (tiles + 1) and dims[0] != slots

    return is_routing


def make_filter(slots: int, experts: int, top_k: int):
    is_routing = routing_shapes(slots, experts, top_k)

    def accept(name: str) -> bool:
        if experts_ms.is_expert_product(name):
            return False
        m = _SHAPE.search(trace.family(name).partition(" ")[2])
        return m is not None and is_routing(m.group(1), tuple(int(x) for x in m.group(2).split(",") if x))

    return accept


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or "num_experts" not in ctx["config"]:
        return None
    cfg = ctx["config"]
    accept = make_filter(int(ctx["cell"].recipe("max_slots")), cfg["num_experts"], cfg["num_experts_per_tok"])
    runs = experts_ms.per_decode_run(tr, accept)
    if not runs or not experts_ms.per_decode_run(tr, experts_ms.is_expert_product):
        return None
    m = trace.median_or_none(runs)
    return None if not m else m * 1e3
