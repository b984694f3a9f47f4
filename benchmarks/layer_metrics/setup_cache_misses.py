"""Programs the persistent cache did not hold inside the program's set-up
(jax's ``cache_misses`` events: an executable compiled and written).  0 in a warm
run; more says the cache did not hold (a stale directory, an evicted entry)."""

from benchmarks.harness import setup_account


def read(ctx):
    return setup_account.total(setup_account.load(), "cache_misses")
