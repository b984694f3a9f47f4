"""Milliseconds of one plain decode round: the benchmark's clock around
``session.step()`` for the window's rounds that admitted nothing, median."""

import statistics


def read(ctx):
    plain = [dt for dt, admitted in ctx.get("step_times", []) if not admitted]
    return statistics.median(plain) * 1e3 if plain else None
