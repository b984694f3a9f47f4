"""``serve_decode_attn_ms``: which operations of a decode round it counts."""

import pytest

from benchmarks.harness import spec as spec_mod, trace

reader = spec_mod.load_module("layer_metrics", "serve_decode_attn_ms")


@pytest.mark.parametrize("label,counted", [
    ("self_attn.12 custom-call bf16[64,16,1,64]", True),  # a plain step's q block
    ("self_attn.3 custom-call bf16[64,16,8,64]", True),  # a speculative verify's
    ("self_attn custom-call bf16[64,16,1,64]", True),
    ("self_attn._flash_run.12 custom-call bf16[8,16,1024,64]", False),  # prefill flash
    ("self_attn.12 custom-call bf16[8,16,1024,64]", False),  # no decode q block
    ("custom-call.53 custom-call bf16[64,16,128,64]", False),  # the cache's ConcatBitcast
    ("copy.7 copy bf16[64,16,128,64]", False),
    ("fusion.9 fusion bf16[64,16,1,64]", False),  # cross attention's p.V on the XLA path
])
def test_which_operations_count(label, counted):
    assert reader.is_decode_attn(label) is counted


def test_median_over_the_decode_program_runs():
    step, other = "jit_serve_decode_step(1)", "jit_serve_prefill(2)"
    attn, flash = "self_attn.1 custom-call bf16[64,16,1,64]", "self_attn._flash_run.1 custom-call bf16[8,16,1024,64]"
    ops, modules = [], []
    for i, dur in enumerate((500_000, 700_000, 600_000)):  # three rounds, twelve calls each
        lo = i * 10_000_000
        modules.append((step, lo, 9_000_000))
        ops += [(attn, lo + j * 700_000, dur) for j in range(12)] + [("copy.1 copy bf16[64,16,128,64]", lo + 8_500_000, 100_000)]
    modules.append((other, 40_000_000, 5_000_000))
    ops.append((flash, 40_000_000, 4_000_000))
    reduced = {"modules": modules, "ops": sorted(ops, key=lambda e: e[1])}
    assert reader.read({"trace": reduced}) == pytest.approx(12 * 0.6)
    assert reader.read({"trace": {"modules": [modules[-1]], "ops": [ops[-1]]}}) is None  # no decode program in the window
    assert reader.read({}) is None  # an untraced run
    assert trace.label("%self_attn.12 = bf16[64,16,1,64]{3,2,1,0:T(2,128)(2,1)} custom-call(%a, %b)")[0] == "self_attn.12 custom-call bf16[64,16,1,64]"
