"""``serve_cross_attn_ms``: which operations of a decode round it counts."""

import pytest

from benchmarks.harness import spec as spec_mod

reader = spec_mod.load_module("layer_metrics", "serve_cross_attn_ms")
self_reader = spec_mod.load_module("layer_metrics", "serve_decode_attn_ms")


@pytest.mark.parametrize("label,counted", [
    ("cross_attn.12 custom-call bf16[64,16,1,64]", True),  # a plain step's q block
    ("cross_attn custom-call bf16[64,16,1,64]", True),
    ("cross_attn.3 custom-call bf16[64,16,8,64]", True),  # up to the decode kernel's eight q rows
    ("self_attn.12 custom-call bf16[64,16,1,64]", False),  # the self step: serve_decode_attn_ms
    ("cross_attn._flash_run.12 custom-call bf16[8,16,128,64]", False),  # an uncached cross attention's flash kernel
    ("cross_attn.12 custom-call bf16[8,16,1024,64]", False),  # no decode q block
    ("fusion.9 fusion bf16[64,16,1,64]", False),  # cross attention's p.V on XLA's path (every tree before PR 46)
    ("multiply_reduce_fusion.4 fusion f32[64,16,1024]", False),  # its scores
])
def test_which_operations_count(label, counted):
    assert reader.is_cross_attn(label) is counted
    assert not (counted and self_reader.is_decode_attn(label))  # no call is counted twice


def test_median_over_the_decode_program_runs_and_nothing_where_xla_runs_the_step():
    step, other = "jit_serve_decode_step(1)", "jit_serve_prefill(2)"
    cross, own = "cross_attn.1 custom-call bf16[64,16,1,64]", "self_attn.1 custom-call bf16[64,16,1,64]"
    ops, modules = [], []
    for i, dur in enumerate((90_000, 110_000, 100_000)):  # three rounds, twelve calls of each kind
        lo = i * 10_000_000
        modules.append((step, lo, 9_000_000))
        ops += [(cross, lo + j * 700_000, dur) for j in range(12)] + [(own, lo + j * 700_000 + 300_000, 20_000) for j in range(12)]
    modules.append((other, 40_000_000, 5_000_000))
    reduced = {"modules": modules, "ops": sorted(ops, key=lambda e: e[1])}
    assert reader.read({"trace": reduced}) == pytest.approx(12 * 0.1)
    assert self_reader.read({"trace": reduced}) == pytest.approx(12 * 0.02)
    # the parent's program: XLA's fusions and the self kernel, no cross custom call: the line leaves the metric out
    parent_ops = [(own if "custom" in n else n, t, d) for n, t, d in (
        (o if o[0] == own else ("fusion.9 fusion bf16[64,16,1,64]", o[1], o[2])) for o in reduced["ops"])]
    assert reader.read({"trace": {"modules": modules, "ops": parent_ops}}) is None
    assert reader.read({}) is None  # an untraced run
