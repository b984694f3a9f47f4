"""The state-space steps' share of their bandwidth roofline in a decode round:
the bytes the state of every slot STREAMED must move (``slots_streamed``, the
counter on ``serve/decode_dispatch``: the round's LIVE slots where the kernel
walks its live list; each such slot's state is read once and written once a
layer, with its three convolution columns, ``flops/<family>.py``
``ssm_step_bytes``) over the chip's HBM bandwidth, over ``serve_ssm_step_ms``.
Bandwidth-bound: a step does ~5 operations a state element and moves 8 bytes
of it.  The convolution's columns are counted in the bytes though their step
runs in XLA beside the kernel (0.7 % of them).  Medians over the traced
window's decode rounds (the counter) and decode program runs (the time)."""

from benchmarks.harness import program_spans, spec as spec_mod

step_ms = spec_mod.load_module("layer_metrics", "serve_ssm_step_ms")
slots_streamed_median = spec_mod.load_module("layer_metrics", "serve_retention_step_roofline_pct").slots_streamed_median


def read(ctx):
    flops = spec_mod.load_module("flops", ctx["cell"].family)
    ms = step_ms.read(ctx) if hasattr(flops, "ssm_step_bytes") else None
    spans = program_spans.load(ctx) if ms else None
    streamed = slots_streamed_median(spans) if spans else None
    if not streamed:
        return None
    itemsize = 2 if ctx["config"]["dtypes"]["compute"] == "bfloat16" else 4
    floor_s = flops.ssm_step_bytes(ctx["config"], streamed, itemsize) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (ms / 1e3)
