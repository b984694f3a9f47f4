"""Seconds of tracing inside the program's set-up: jax's outermost
``jaxpr_trace_duration`` events inside a ``setup/*`` span, all programs (a nested
``jit``'s trace lies inside its caller's and is counted once)."""

from benchmarks.harness import setup_account


def read(ctx):
    return setup_account.total(setup_account.load(), "trace_s")
