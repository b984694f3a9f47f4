"""A prefill wave's share of the chip's peak: the operations a wave of the
median size requires (``flops/<family>.py`` ``prefill_wave_flops`` at the
median ``rows_computed`` of the traced window's ``serve/prefill_dispatch``
spans and the cell's prompt length: 2 per weight and token, the head for the
last position only, the retention products) over ``serve_prefill_device_ms``
x the peak bf16 rate.  The share of the WHOLE wave, so that a later claim on
the prefill is bounded.  A family whose ``flops`` module counts no wave, or a
program without the counter, is not reported."""

import statistics

from benchmarks.harness import program_spans, spec as spec_mod

device_ms = spec_mod.load_module("layer_metrics", "serve_prefill_device_ms")


def rows_computed_median(spans) -> float | None:
    got = [float(s.stats["rows_computed"]) for s in program_spans.named(spans, "serve/prefill_dispatch")
           if "rows_computed" in s.stats]
    return statistics.median(got) if got else None


def read(ctx):
    flops = spec_mod.load_module("flops", ctx["cell"].family)
    ms = device_ms.read(ctx) if hasattr(flops, "prefill_wave_flops") else None
    spans = program_spans.load(ctx) if ms else None
    rows = rows_computed_median(spans) if spans else None
    if not rows:
        return None
    need = flops.prefill_wave_flops(ctx["config"], rows, int(ctx["cell"].recipe("prompt_tokens")))
    return 100.0 * need / (ms / 1e3) / ctx["peaks"]["bf16_flops"]
