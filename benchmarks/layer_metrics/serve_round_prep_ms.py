"""Host milliseconds a plain round spends before the device can start:
``serve/admit_prep`` (free-slot scan; nothing to admit) + ``serve/decode_dispatch``
(the transfers and the step program's call until it returns), median over the
traced window's plain rounds (a ``serve/round`` with no ``serve/prefill_dispatch``)."""

from benchmarks.harness import program_spans


def read(ctx):
    return program_spans.plain_round_ms(ctx, "serve/admit_prep", "serve/decode_dispatch")
