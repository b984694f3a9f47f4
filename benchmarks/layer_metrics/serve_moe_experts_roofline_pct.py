"""The expert products' share of their bandwidth roofline in a decode round:
the bytes of the experts that received a row (the round's ``moe_experts_hit``
counter on ``serve/token_fetch``, summed over the expert layers, times an
expert's three matrices in the dtype they are resident in:
``flops/<family>.py`` ``expert_bytes_read``) over the chip's HBM bandwidth,
over ``serve_moe_experts_ms``.  It counts the experts HIT, not the experts
held: a quiet round that reaches few experts reads few, and counting all of
them would read over 100 %.  Medians over the traced window's decode rounds
(the counter) and decode program runs (the time).  A decode round is bound by
reading the weights, not by the MXU (16 rows an expert)."""

import statistics

from benchmarks.harness import program_spans, spec as spec_mod

experts_ms = spec_mod.load_module("layer_metrics", "serve_moe_experts_ms")


def experts_hit_median(spans) -> float | None:
    hits = [float(s.stats["moe_experts_hit"]) for s in program_spans.named(spans, "serve/token_fetch")
            if "moe_experts_hit" in s.stats]
    return statistics.median(hits) if hits else None


def read(ctx):
    ms = experts_ms.read(ctx)
    spans = program_spans.load(ctx) if ms else None
    hit = experts_hit_median(spans) if spans else None
    if not hit:
        return None
    flops = spec_mod.load_module("flops", ctx["cell"].family)
    itemsize = 2 if ctx["config"]["dtypes"]["params"] == "bfloat16" else 4
    floor_s = flops.expert_bytes_read(ctx["config"], hit, itemsize) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (ms / 1e3)
