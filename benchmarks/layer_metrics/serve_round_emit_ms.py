"""Host milliseconds a plain round spends after the tokens are back:
``serve/emit`` (stats, the per-slot append / evict / finish loop) +
``serve/window_log`` (the cadenced event), median over the traced window's
plain rounds."""

from benchmarks.harness import program_spans


def read(ctx):
    return program_spans.plain_round_ms(ctx, "serve/emit", "serve/window_log")
