"""Milliseconds a plain round's host waits for the device: ``serve/token_fetch``
(the ``device_get`` of the token vector), median over the traced window's
plain rounds."""

from benchmarks.harness import program_spans


def read(ctx):
    return program_spans.plain_round_ms(ctx, "serve/token_fetch")
