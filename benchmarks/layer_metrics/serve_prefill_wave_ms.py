"""Milliseconds a prefill wave adds to a round: median of the rounds that
admitted requests, minus the median plain round."""

import statistics


def read(ctx):
    times = ctx.get("step_times", [])
    plain = [dt for dt, admitted in times if not admitted]
    waves = [dt for dt, admitted in times if admitted]
    if not plain or not waves:
        return None
    return (statistics.median(waves) - statistics.median(plain)) * 1e3
