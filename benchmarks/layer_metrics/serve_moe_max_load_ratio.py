"""How uneven a decode round's routing is: the largest expert's rows
(``moe_max_load``, the maximum over the expert layers) over the mean expert's
(``moe_assignments`` over expert layers x experts), from the counters the
engine sets on each round's ``serve/token_fetch`` span; median over the traced
window's decode rounds.  1 = every expert of every layer got the same; the
slowest expert's tile sets what a grouped product waits for."""

import statistics

from benchmarks.harness import program_spans


def ratios(spans, expert_layers: int, experts: int) -> list[float]:
    out = []
    for s in program_spans.named(spans, "serve/token_fetch"):
        if s.stats.get("moe_assignments"):
            mean = float(s.stats["moe_assignments"]) / (expert_layers * experts)
            out.append(float(s.stats["moe_max_load"]) / mean)
    return out


def read(ctx):
    cfg = ctx["config"]
    spans = program_spans.load(ctx) if "num_experts" in cfg else None
    if spans is None:
        return None
    layers = len(cfg["layer_types"]) - cfg["num_dense_layers"]
    got = ratios(spans, layers, cfg["num_experts"])
    return statistics.median(got) if got else None
