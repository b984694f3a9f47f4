"""Seconds of lowering inside the program's set-up: jax's
``jaxpr_to_mlir_module_duration`` events inside a ``setup/*`` span, all programs.
Where a Pallas kernel's body and an unrolled layer stack are paid, by every run,
warm or cold."""

from benchmarks.harness import setup_account


def read(ctx):
    return setup_account.total(setup_account.load(), "lower_s")
