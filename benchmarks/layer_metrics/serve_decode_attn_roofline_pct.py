"""The decode attention's share of its bandwidth roofline in a decode round:
the bytes of K/V the round's attention NEEDS (``kv_positions_live``, the
counter on ``serve/decode_dispatch``: over the attention layers and the live
slots, a slot's own positions, on a window layer at most the window; a key and
a value of every KV head each, ``flops/<family>.py`` ``decode_attn_bytes``)
over the chip's HBM bandwidth, over ``serve_decode_attn_ms`` +
``serve_window_attn_ms`` (the full layers' and the window layers' kernels).
Bandwidth-bound: a step does one multiply-add a byte or two.  It counts the
positions needed, so a kernel that streams the tiles of idle slots or of
positions past a slot's own reads low.  Medians over the traced window's
decode rounds (the counter) and decode program runs (the times)."""

import statistics

from benchmarks.harness import program_spans, spec as spec_mod

full_ms = spec_mod.load_module("layer_metrics", "serve_decode_attn_ms")
window_ms = spec_mod.load_module("layer_metrics", "serve_window_attn_ms")


def kv_positions_live_median(spans) -> float | None:
    got = [float(s.stats["kv_positions_live"]) for s in program_spans.named(spans, "serve/decode_dispatch")
           if "kv_positions_live" in s.stats]
    return statistics.median(got) if got else None


def read(ctx):
    flops = spec_mod.load_module("flops", ctx["cell"].family)
    if ctx.get("trace") is None or not hasattr(flops, "decode_attn_bytes"):
        return None
    ms = (full_ms.read(ctx) or 0.0) + (window_ms.read(ctx) or 0.0)
    spans = program_spans.load(ctx) if ms else None
    live = kv_positions_live_median(spans) if spans else None
    if not live:
        return None
    itemsize = 2 if ctx["config"]["dtypes"]["compute"] == "bfloat16" else 4
    floor_s = flops.decode_attn_bytes(ctx["config"], live, itemsize) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (ms / 1e3)
