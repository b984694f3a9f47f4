"""The program's own account of its set-up (``obs/setup.py``: the
``setup_summary`` it logged when it was ready to train or serve), as the
``setup_*`` metrics read it.

The readers run in the run's own process once the driver has returned, so the
account is read from the program's module as it stood at ready; nothing comes
from the profile.  A program without one (a commit from before PR 41, or one
that never became ready) gives ``None`` from ``load``: every reader then returns
None and the result line leaves its metric out.
"""

from __future__ import annotations


def load() -> dict | None:
    try:
        from distributed_llms_example_tpu.obs import setup
    except ImportError:
        return None
    return setup.snapshot()


def program_s(account: dict | None) -> float | None:
    """Seconds of the outermost ``setup/*`` spans up to ready (a path with no
    ``/``: ``trainer_init``, ``first_step``, ``engine_init``, ``session_open``)."""
    if account is None:
        return None
    return sum(float(v["s"]) for path, v in account["phases"].items() if "/" not in path)


def total(account: dict | None, field: str) -> float | None:
    """A total over every program, inside the set-up's spans: ``trace_s``,
    ``lower_s``, ``compile_or_load_s``, ``cache_hits``, ``cache_misses``."""
    return None if account is None else float(account["totals"][field])
