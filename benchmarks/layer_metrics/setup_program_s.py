"""Seconds of ``setup_s`` that the program owns: the sum of its outermost
``setup/*`` spans up to ready (``trainer_init`` + ``first_step``, or
``engine_init`` + ``session_open``).  Not in it: the interpreter's imports, the
machine's bring-up, the harness's weights from the seed, its warm-up passes or
requests."""

from benchmarks.harness import setup_account


def read(ctx):
    return setup_account.program_s(setup_account.load())
