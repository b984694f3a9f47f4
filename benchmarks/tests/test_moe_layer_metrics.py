"""The four per-layer metrics of the expert layers: which operations of a
decode round each counts (labels as one chip trace showed them, PERF.md,
Findings PR 28), and their arithmetic on hand-built rounds."""

import os

import pytest

from benchmarks.harness import spec as spec_mod
from benchmarks.harness.program_spans import Span

experts_ms = spec_mod.load_module("layer_metrics", "serve_moe_experts_ms")
route_ms = spec_mod.load_module("layer_metrics", "serve_moe_route_ms")
roofline = spec_mod.load_module("layer_metrics", "serve_moe_experts_roofline_pct")
load_ratio = spec_mod.load_module("layer_metrics", "serve_moe_max_load_ratio")
flops = spec_mod.load_module("flops", "lfm2_moe")

CFG = spec_mod.load_json(os.path.join(spec_mod.BENCH_DIR, "configs", "lfm2-8b-a1b.json"))


@pytest.mark.parametrize("label,product,routing", [
    ("gmm.4 custom-call bf16[512,1792]", True, False),  # the Pallas grouped product: gate / up
    ("gmm custom-call bf16[512,2048]", True, False),  # down
    ("ragged-dot-none.5 custom-call bf16[512,1792]", True, False),  # XLA's own, where gmm does not tile
    ("ragged-dot-metadata custom-call s32[33]", False, True),  # the group offsets
    ("broadcast_select_fusion.7 fusion bf16[512,2048]", False, True),  # rows gathered into expert order
    ("fusion.39 fusion bf16[512,2048]", False, True),  # the unsort
    ("sort.3 sort s32[512]", False, True),
    ("fusion.9 fusion f32[128,32]", False, True),  # the router's scores
    ("fusion.7 fusion bf16[128,4,2048]", False, True),  # (slots, top_k, hidden)
    ("gmm.1 custom-call bf16[16384,1792]", True, False),  # a prefill wave's: a product, outside the decode program
    ("self_attn.1 custom-call bf16[128,8,4,64]", False, False),
    ("copy.133 copy bf16[128,8,1280,64]", False, False),
    ("iota_reduce_fusion fusion bf16[128]", False, False),  # the head and its argmax
    ("convolution_bitcast_fusion.3 fusion bf16[128,1,6144]", False, False),  # the conv operator's in-projection
])
def test_which_operations_count(label, product, routing):
    assert experts_ms.is_expert_product(label) is product
    assert route_ms.make_filter(128, 32, 4)(label) is routing


class Cell:
    name, family = "lfm2-8b-a1b.serve-steady", "lfm2_moe"

    def recipe(self, key, default=None):
        return {"max_slots": 128}.get(key, default)


def rounds(product_ns=(375_000, 380_000, 370_000)):
    """Three decode rounds of twelve products each (and some routing), one prefill."""
    step, wave = "jit_serve_decode_step(1)", "jit_serve_prefill(2)"
    ops, modules = [], []
    for i, dur in enumerate(product_ns):
        lo = i * 20_000_000
        modules.append((step, lo, 12_000_000))
        ops += [("gmm.%d custom-call bf16[512,1792]" % j, lo + j * 900_000, dur) for j in range(12)]
        ops += [("fusion.39 fusion bf16[512,2048]", lo + 11_000_000, 8_000), ("sort.3 sort s32[512]", lo + 11_100_000, 5_000),
                ("copy.133 copy bf16[128,8,1280,64]", lo + 11_200_000, 700_000)]
    modules.append((wave, 70_000_000, 5_000_000))
    ops.append(("gmm.1 custom-call bf16[16384,1792]", 70_000_000, 1_000_000))
    return {"modules": modules, "ops": sorted(ops, key=lambda e: e[1])}


def test_experts_and_route_ms_are_medians_over_the_decode_runs():
    ctx = {"trace": rounds(), "config": CFG, "cell": Cell()}
    assert experts_ms.read(ctx) == pytest.approx(12 * 0.375)
    assert route_ms.read(ctx) == pytest.approx(0.013)
    assert experts_ms.read({}) is None and route_ms.read({"config": CFG}) is None  # an untraced run
    dense = {"trace": {"modules": rounds()["modules"], "ops": [("copy.1 copy bf16[64,16,128,64]", 5, 5)]}, "config": CFG, "cell": Cell()}
    assert experts_ms.read(dense) is None and route_ms.read(dense) is None  # a program with no expert product


def fetches(hit, max_load, assignments):
    return [Span("serve/token_fetch", i * 100, 50, {"moe_experts_hit": h, "moe_max_load": m, "moe_assignments": a})
            for i, (h, m, a) in enumerate(zip(hit, max_load, assignments))]


def test_roofline_counts_the_experts_hit_and_max_load_is_over_the_mean(monkeypatch):
    spans = fetches([128, 96, 120], [24, 40, 32], [2048, 2048, 2048]) + [Span("serve/token_fetch", 900, 50, {})]
    assert roofline.experts_hit_median(spans) == 120
    # mean load = 2048 assignments / (4 expert layers x 32 experts) = 16 rows an expert
    assert load_ratio.ratios(spans, 4, 32) == [1.5, 2.5, 2.0]
    ctx = {"trace": rounds(), "config": CFG, "cell": Cell(), "peaks": {"hbm_bytes_per_s": 819e9}}
    monkeypatch.setattr(roofline.program_spans, "load", lambda c: spans)
    monkeypatch.setattr(load_ratio.program_spans, "load", lambda c: spans)
    bytes_hit = 120 * 3 * 2048 * 1792 * 2
    assert flops.expert_bytes_read(CFG, 120) == bytes_hit
    assert roofline.read(ctx) == pytest.approx(100 * (bytes_hit / 819e9) / (12 * 0.375e-3))
    assert roofline.read(ctx) < 100
    assert load_ratio.read(ctx) == 2.0
    monkeypatch.setattr(roofline.program_spans, "load", lambda c: [Span("serve/token_fetch", 0, 5, {})])
    monkeypatch.setattr(load_ratio.program_spans, "load", lambda c: [Span("serve/token_fetch", 0, 5, {})])
    assert roofline.read(ctx) is None and load_ratio.read(ctx) is None  # a program without the counters (the parent)


def test_decode_round_bytes_by_kind():
    b = flops.decode_round_bytes(CFG, slots=128, cache_len=1280)
    assert b["experts"] == 4 * 32 * 3 * 2048 * 1792 * 2  # 2.82 GB
    assert b["kv"] == 128 * 1280 * 8 * 64 * 2 * 2  # one attention layer: 0.34 GB
    assert b["conv_state"] == 4 * 128 * 2048 * 2 * 2
    assert 3.32e9 < b["weights"] < 3.34e9
    assert flops.decode_round_bytes(CFG, 128, 1280, experts_hit_per_layer=16)["experts"] == b["experts"] / 2
    assert 2.4e12 < flops.prefill_wave_flops(CFG, 4, 1024) < 2.5e12  # ~298M parameters a token pass, 4,096 tokens
