"""Host milliseconds a step spends outside dispatch and the wait for the
device: data_wait + host_overhead + unattributed of the window's
``step_budget`` accounts (obs/budget.py) but the first, per step.  (The issue's "wall minus
device_busy" only means this when every step syncs; at a log cadence of 10
the account's device_busy is the drain at the cadence alone.)"""


def read(ctx):
    accounts = ctx.get("accounts") or []
    steps = sum(a["window_steps"] for a in accounts)
    if not steps:
        return None
    host = sum(a["data_wait_ms"] + a["host_overhead_ms"] + a["unattributed_ms"] for a in accounts)
    return host / steps
