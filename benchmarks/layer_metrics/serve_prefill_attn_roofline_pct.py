"""A prefill wave's attention as a share of its compute roofline: the
attention products a wave of the median size requires (``flops/<family>.py``
``prefill_attn_flops`` at the median ``rows_computed`` of the traced window's
``serve/prefill_dispatch`` spans and the cell's prompt length: the causal half
on a full layer, the BAND on a sliding-window layer) over the chip's peak
bf16 rate, over ``serve_prefill_attn_ms``.  Compute-bound (a key is read once
a q tile and used for ~512 rows).  It counts the band, so a kernel that visits
key tiles outside it, or whole tiles where the band covers part of one, reads
low."""

from benchmarks.harness import program_spans, spec as spec_mod

attn_ms = spec_mod.load_module("layer_metrics", "serve_prefill_attn_ms")
rows_computed_median = spec_mod.load_module("layer_metrics", "serve_prefill_mfu_pct").rows_computed_median


def read(ctx):
    flops = spec_mod.load_module("flops", ctx["cell"].family)
    ms = attn_ms.read(ctx) if hasattr(flops, "prefill_attn_flops") else None
    spans = program_spans.load(ctx) if ms else None
    rows = rows_computed_median(spans) if spans else None
    if not rows:
        return None
    need = flops.prefill_attn_flops(ctx["config"], rows, int(ctx["cell"].recipe("prompt_tokens")))
    return 100.0 * need / (ms / 1e3) / ctx["peaks"]["bf16_flops"]
