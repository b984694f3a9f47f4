"""Milliseconds a step waited for its batch: ``step_budget.data_wait_ms`` of
the window's accounts, per step."""


def read(ctx):
    accounts = ctx.get("accounts") or []
    steps = sum(a["window_steps"] for a in accounts)
    if not steps:
        return None
    return sum(a["data_wait_ms"] for a in accounts) / steps
