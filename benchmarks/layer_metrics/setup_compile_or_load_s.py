"""Seconds of backend compilation inside the program's set-up: jax's
``backend_compile_duration`` events inside a ``setup/*`` span, all programs.  In a
warm run the stage is the persistent cache's load, in a cold one XLA's compile."""

from benchmarks.harness import setup_account


def read(ctx):
    return setup_account.total(setup_account.load(), "compile_or_load_s")
