"""The retention steps' share of their bandwidth roofline in a decode round:
the bytes the state of every slot STREAMED must move (``slots_streamed``, the
counter on ``serve/decode_dispatch``: the round's LIVE slots where the kernel
walks its live list, as it has since PR 36, and every slot of the flat cache
where it does not; each such slot's state is read once and written once a layer,
``flops/<family>.py`` ``retention_step_bytes``: the 8,256 rows a KV head the
mathematics needs, not the rows a layout pads them to) over the chip's HBM
bandwidth, over ``serve_retention_step_ms``.  Bandwidth-bound: a step does
~13 operations a state element and moves 8 bytes of it.  Medians over the
traced window's decode rounds (the counter) and decode program runs (the time)."""

import statistics

from benchmarks.harness import program_spans, spec as spec_mod

step_ms = spec_mod.load_module("layer_metrics", "serve_retention_step_ms")


def slots_streamed_median(spans) -> float | None:
    got = [float(s.stats["slots_streamed"]) for s in program_spans.named(spans, "serve/decode_dispatch")
           if "slots_streamed" in s.stats]
    return statistics.median(got) if got else None


def read(ctx):
    ms = step_ms.read(ctx)
    spans = program_spans.load(ctx) if ms else None
    streamed = slots_streamed_median(spans) if spans else None
    if not streamed:
        return None
    flops = spec_mod.load_module("flops", ctx["cell"].family)
    floor_s = flops.retention_step_bytes(ctx["config"], streamed) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (ms / 1e3)
