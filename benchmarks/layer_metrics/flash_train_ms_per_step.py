"""Device milliseconds of the flash-attention kernels in one step: summed
durations of the operations whose trace name matches ``FLASH_OPS``, per run
of the step program, median over the traced steps.

The names are how the chip's trace shows the Pallas calls of
``ops/flash_attention.py`` (forward, dq, dkv and, for a learned bias, dbias):
custom calls named after the function that issued them, ``<site>._flash_run``
from ``ops/mha.py`` and ``<site>._attend`` from ``models/t5.py`` — found by
looking at one trace of each family by hand (PERF.md, Findings PR 24).  A later
kernel under another name adds its pattern here through a new metric file,
not by editing this one."""

from benchmarks.harness import trace

FLASH_OPS = ("._flash_run", "._attend")  # bart / llama call sites, t5's call site


def is_flash(name: str) -> bool:
    head, _, rest = name.partition(" ")
    return rest.startswith("custom-call") and any(p in head for p in FLASH_OPS)


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    runs = trace.per_module_run(tr, trace.main_module(tr), op_filter=is_flash)
    m = trace.median_or_none(runs)
    return None if not m else m * 1e3
