"""Share of the window's inter-token gaps that hold a prefill wave, in percent:
``share_of_gaps_with_a_wave`` of the measured window's summary
(``harness/loadgen.py``: of the completed requests' gaps, those between whose
two tokens a round admitted).  ``gap_p95_ms`` is a wave round's gap while this
reads above 5 and a plain round's below it, and at 5 it is either by the seed:
the distance from 5 is the room a cell has left before its tail flips."""


def read(ctx):
    share = ctx.get("share_of_gaps_with_a_wave")
    return None if share is None else 100.0 * share
