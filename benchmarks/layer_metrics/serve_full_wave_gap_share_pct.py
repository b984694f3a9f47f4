"""Share of the window's inter-token gaps that hold a prefill wave of several
requests, in percent: ``share_of_gaps_with_a_full_wave`` of the measured
window's summary (``harness/loadgen.py``).  Two requests or more that wait
together are admitted by one wave, which runs the program of ``prefill_batch``
rows: while this reads under 5, ``gap_p95_ms`` is a one-row wave round's gap,
above 5 a full wave round's, and at 5 either by the seed.  ``BENCHMARK.json``
lists the cells whose traffic has such a program (``prefill_batch`` 2 or more)."""


def read(ctx):
    share = ctx.get("share_of_gaps_with_a_full_wave")
    return None if share is None else 100.0 * share
