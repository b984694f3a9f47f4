"""Model FLOP/s utilization: the operations one step requires at the cell's
computed shapes (``flops/<family>.py``) times the steps per second of this
run's own window (host clock, profiler off), over chips x the bf16 peak."""

from benchmarks.harness import spec


def read(ctx):
    if ctx.get("peaks") is None or not ctx.get("steps_per_s"):
        return None
    cell = ctx["cell"]
    flops = spec.load_module("flops", cell.family).train_step_flops(
        ctx["config"], ctx["batch"], ctx["src_len"], ctx["tgt_len"])
    return 100.0 * flops * ctx["steps_per_s"] / (cell.chips * ctx["peaks"]["bf16_flops"])
