"""How late the load generator ran: 95th percentile of submit instant minus
scheduled arrival, over the window's requests.  A starved generator must not
read as a fast server."""

from benchmarks.harness import stats


def read(ctx):
    late = ctx.get("late_s") or []
    return stats.percentile(late, 0.95) * 1e3 if late else None
