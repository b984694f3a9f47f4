"""The flash kernels' share of their roofline: the least time the chip could
take for the attention products the step requires (``attention_flops`` of
``flops/<family>.py`` over the bf16 peak; these kernels are compute-bound at
these shapes: 4*Sq*Sk*D operations against (Sq+Sk)*D*2 bytes a head) over
the time the kernels took (``flash_train_ms_per_step``)."""

from benchmarks.harness import spec


def read(ctx):
    if ctx.get("trace") is None or ctx.get("peaks") is None:
        return None
    ms = spec.load_module("layer_metrics", "flash_train_ms_per_step").read(ctx)
    if not ms:
        return None
    cell = ctx["cell"]
    f = spec.load_module("flops", cell.family)
    flops = f.attention_flops(f.attention_sites(ctx["config"], ctx["batch"], ctx["src_len"], ctx["tgt_len"]))
    least_ms = flops / (cell.chips * ctx["peaks"]["bf16_flops"]) * 1e3
    return 100.0 * least_ms / ms
