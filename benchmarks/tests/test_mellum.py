"""The Mellum configuration's benchmark parts: the reference's attention
against a hand-written evaluation (the band, both rotations) and in blocks
against itself whole, the configuration file against the catalog row, the
flops module against counts by hand, each new per-layer reader on a synthetic
trace (it finds its calls; it returns None where there are none), and the
cell's rehearsal."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import precision, spec as spec_mod, weights
from benchmarks.harness.program_spans import Span

from conftest import run_cell

REF = spec_mod.load_module("reference", "mellum")
flops = spec_mod.load_module("flops", "mellum")
window_ms = spec_mod.load_module("layer_metrics", "serve_window_attn_ms")
full_ms = spec_mod.load_module("layer_metrics", "serve_decode_attn_ms")
decode_roofline = spec_mod.load_module("layer_metrics", "serve_decode_attn_roofline_pct")
prefill_attn_ms = spec_mod.load_module("layer_metrics", "serve_prefill_attn_ms")
prefill_roofline = spec_mod.load_module("layer_metrics", "serve_prefill_attn_roofline_pct")
kv_live = spec_mod.load_module("layer_metrics", "serve_kv_live_pct")

CFG = spec_mod.load_json(os.path.join(spec_mod.BENCH_DIR, "configs", "mellum2-12b-a2.5b.json"))
TOY = spec_mod.load_json(os.path.join(spec_mod.BENCH_DIR, "configs", "mellum-test.json"))
CELL = "mellum2-12b-a2.5b.serve-steady"
FP32 = precision.make_dot("fp32")


def attention_by_hand(p, pre, x, cfg, kind):
    """``Attn_l`` of one sequence in numpy float64, query by query over the keys
    it may read (a loop, no mask), the rotation by its angle a pair: shares
    nothing with the reference but the weights."""
    heads, kv, hd, eps = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"], cfg["rms_norm_eps"]
    w = {k: np.asarray(v, np.float64) for k, v in p.items() if k.startswith(pre + ".self_attn")}
    rope = cfg["rope_parameters"][kind]
    n = np.arange(hd // 2)
    freq = float(rope["rope_theta"]) ** (-2.0 * n / hd)
    factor = 1.0
    if rope["rope_type"] == "yarn":
        dim = lambda b: hd * np.log(rope["original_max_position_embeddings"] / (2 * np.pi * b)) / (2 * np.log(rope["rope_theta"]))  # noqa: E731
        lo, hi = max(np.floor(dim(rope["beta_fast"])), 0), min(np.ceil(dim(rope["beta_slow"])), hd - 1)
        ramp = np.clip((n - lo) / (hi - lo), 0, 1)
        freq, factor = freq / rope["factor"] * ramp + freq * (1 - ramp), rope["attention_factor"]
    rms = lambda a, g: a / np.sqrt(np.mean(a * a, -1, keepdims=True) + eps) * g  # noqa: E731

    def rotate(a, pos):  # pair (n, n + hd/2) turned by pos * freq_n
        c, s = np.cos(pos * freq) * factor, np.sin(pos * freq) * factor
        return np.concatenate([a[: hd // 2] * c - a[hd // 2:] * s, a[hd // 2:] * c + a[: hd // 2] * s])

    t = x.shape[0]
    q = (x @ w[f"{pre}.self_attn.q_proj.weight"]).reshape(t, heads, hd)
    k = (x @ w[f"{pre}.self_attn.k_proj.weight"]).reshape(t, kv, hd)
    v = (x @ w[f"{pre}.self_attn.v_proj.weight"]).reshape(t, kv, hd)
    q = np.stack([[rotate(rms(q[i, h], w[f"{pre}.self_attn.q_norm.weight"]), i) for h in range(heads)] for i in range(t)])
    k = np.stack([[rotate(rms(k[i, j], w[f"{pre}.self_attn.k_norm.weight"]), i) for j in range(kv)] for i in range(t)])
    out = np.zeros((t, heads, hd))
    for i in range(t):
        first = 0 if kind == "full_attention" else max(i - cfg["sliding_window"] + 1, 0)
        for h in range(heads):
            j = h // (heads // kv)
            s = k[first: i + 1, j] @ q[i, h] / np.sqrt(hd)
            a = np.exp(s - s.max())
            out[i, h] = (a / a.sum()) @ v[first: i + 1, j]
    return out.reshape(t, heads * hd) @ w[f"{pre}.self_attn.o_proj.weight"]


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_reference_attention_equals_a_hand_written_loop(kind, monkeypatch):
    params = weights.make_reference_weights(REF.param_spec(TOY), 11)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (44, TOY["hidden_size"])), np.float64)
    want = attention_by_hand(params, "layers.2", x, TOY, kind)
    for block in (256, 16):  # whole, and in query blocks of 16 with a last block of 12
        monkeypatch.setattr(REF, "QUERY_BLOCK", block)
        with jax.default_matmul_precision("highest"):
            got = np.asarray(REF.attention_operator(FP32, params, "layers.2", jnp.asarray(x, jnp.float32), TOY, kind))
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


def test_reference_forward_returns_the_served_positions():
    params = weights.make_reference_weights(REF.param_spec(TOY), 12)
    ids = jnp.asarray(np.random.default_rng(0).integers(2, 250, size=(2, 20)), jnp.int32)
    dec = jnp.asarray([[1, 5, 6, 7], [1, 9, 8, 0]], jnp.int32)
    logits = REF.forward(params, TOY, ids, jnp.ones_like(ids), dec, FP32)
    assert logits.shape == (2, 4, TOY["vocab_size"])
    whole, _ = REF.sequence_logits(params, TOY, jnp.concatenate([ids[0], dec[0, 1:]]), 0, FP32)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(whole[19:]), atol=1e-5)
    assert REF.decoder_start(TOY) == (1, 0) and REF.forced_tokens(TOY, 8) == {}


def test_configuration_file_states_the_published_widths_and_the_cut():
    period = ["sliding_attention"] * 3 + ["full_attention"]
    row = {"attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2304, "intermediate_size": 7168,
           "layer_types": period * 7, "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072,
           "max_window_layers": 0, "model_type": "mellum", "moe_intermediate_size": 896, "norm_topk_prob": True,
           "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 8, "num_hidden_layers": 28,
           "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
           "rope_parameters": {
               "full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                                  "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
                                  "attention_factor": 1.2772588722239782},
               "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
           "sliding_window": 1024, "tie_word_embeddings": False, "vocab_size": 98304,
           "use_sliding_window": True}  # the catalog row's `config`
    differs = sorted(k for k, v in row.items() if CFG.get(k, "absent") != v)
    assert differs == sorted(CFG["reduced"]) == ["layer_types", "num_hidden_layers", "vocab_size"]
    assert CFG["layer_types"] == period and CFG["num_hidden_layers"] == 4 and CFG["vocab_size"] * 4 == 98304
    assert set(CFG["reduced_why"]) == set(CFG["reduced"])
    for key in ("qk_norms", "router", "rope_layout", "yarn", "window", "mtp_head", "weights", "qk_norm_init_mean",
                "tokenizer", "eos_token_id"):
        assert len(CFG["assumed"][key]) > 40, key
    assert set(CFG["check"]["limits"]["serve_open_loop_routed"]) == {
        "served_logit_gap_max", "served_logit_gap_mean", "served_logit_gap_p95"}


def test_flops_and_bytes_against_counts_by_hand():
    attn = 2 * 2304 * 32 * 128 + 2 * 2304 * 4 * 128 + 2 * 128
    expert = 3 * 2304 * 896
    layer = attn + 2304 * 64 + 64 * expert + 2 * 2304
    assert flops.attention_params(CFG) == attn and flops.expert_params(CFG) == expert == 6_193_152
    assert flops.layer_params(CFG) == layer and 417.6e6 < layer < 417.8e6
    assert 4 * layer + 2 * 24576 * 2304 + 2304 == sum(int(np.prod(s)) for s, _, _ in REF.param_spec(CFG).values())
    assert 1.783e9 < 4 * layer + 2 * 24576 * 2304 < 1.785e9  # 3.57 GB resident in bfloat16
    assert flops.expert_bytes_read(CFG, 256) == 256 * expert * 2 and 3.16e9 < flops.expert_bytes_read(CFG, 256) < 3.18e9
    # the cache at 48 slots, 8,192 + 256 positions: one full layer's K/V and three rings, as decode_attn_bytes counts a position
    assert flops.decode_attn_bytes(CFG, 48 * 8448) == 830_472_192 and flops.decode_attn_bytes(CFG, 3 * 48 * 1024) == 301_989_888
    assert flops.decode_attn_bytes(CFG, 1000) == 1000 * 2 * 4 * 128 * 2
    # the band: query i reads min(i + 1, W) keys
    for t, w in ((5, 3), (3, 5), (16, 16), (8192, 1024)):
        assert flops.band_pairs(t, w) == sum(min(i + 1, w) for i in range(t))
    toy_attn = 4.0 * 8 * 16 * (40 * 41 / 2 + 3 * sum(min(i + 1, 16) for i in range(40)))
    assert flops.prefill_attn_flops(TOY, 1, 40) == toy_attn
    toy_token = 4 * (2 * 64 * 8 * 16 + 2 * 64 * 2 * 16 + 64 * 8 + 2 * 3 * 64 * 32)
    assert flops.prefill_wave_flops(TOY, 2, 40) == 2.0 * 2 * 40 * toy_token + 2.0 * 2 * 256 * 64 + 2 * toy_attn
    # one 8,192-token row: 0.550 TFLOP for the full layer's causal half, 0.129 a window layer's band
    full = 4.0 * 32 * 128 * 8192 * 8193 / 2
    band = 4.0 * 32 * 128 * flops.band_pairs(8192, 1024)
    assert 0.549e12 < full < 0.551e12 and 0.128e12 < band < 0.130e12
    assert flops.prefill_attn_flops(CFG, 1, 8192) == full + 3 * band
    per_token = 4 * (attn - 2 * 128 + 2304 * 64 + 8 * expert)
    assert flops.prefill_wave_flops(CFG, 1, 8192) == 2.0 * 8192 * per_token + 2.0 * 24576 * 2304 + full + 3 * band
    assert 5.5e12 < flops.prefill_wave_flops(CFG, 1, 8192) < 5.7e12


@pytest.mark.parametrize("label,window,full,prompt", [
    ("window_decode.3 custom-call bf16[48,4,8,128]", True, False, False),  # a window layer's ring step
    ("window_decode custom-call bf16[48,4,8,128]", True, False, False),
    ("self_attn.1 custom-call bf16[48,4,8,128]", False, True, False),  # the full layer's step, by its call site
    ("prompt_attn.2 custom-call bf16[1,32,8192,128]", False, False, True),  # a prompt's attention, either flavour
    ("prompt_attn custom-call bf16[1,32,8192,128]", False, False, True),
    ("gmm.5 custom-call bf16[384,896]", False, False, False),
    ("retention_step.8 custom-call f32[24,8,5,128]", False, False, False),
    ("fusion.12 fusion bf16[48,1024,512]", False, False, False),  # the ring's row write
])
def test_which_operations_count(label, window, full, prompt):
    assert window_ms.is_window_decode(label) is window
    assert full_ms.is_decode_attn(label) is full
    assert prefill_attn_ms.is_prompt_attn(label) is prompt


class Cell:
    name, family = CELL, "mellum"

    def recipe(self, key, default=None):
        return {"max_slots": 48, "prompt_tokens": 8192, "prefill_batch": 1}.get(key, default)


def window(ring_ns=(50_000, 52_000, 48_000), full_ns=(400_000, 420_000, 380_000)):
    """Three decode rounds of three ring steps and one full-layer step each, one one-row wave of four prompt kernels."""
    step, wave, admit = "jit_serve_decode_step(1)", "jit_serve_prefill(2)", "jit_serve_admit(3)"
    ops, modules = [], []
    for i, (ring, full) in enumerate(zip(ring_ns, full_ns)):
        lo = i * 20_000_000
        modules.append((step, lo, 9_000_000))
        ops += [("window_decode.%d custom-call bf16[48,4,8,128]" % j, lo + j * 2_000_000, ring) for j in range(3)]
        ops.append(("self_attn.1 custom-call bf16[48,4,8,128]", lo + 6_500_000, full))
        ops.append(("gmm.2 custom-call bf16[384,896]", lo + 7_500_000, 300_000))
    modules += [(wave, 70_000_000, 60_000_000), (admit, 130_500_000, 1_000_000)]
    ops += [("prompt_attn.%d custom-call bf16[1,32,8192,128]" % j, 70_000_000 + j * 12_000_000, 2_000_000) for j in range(3)]
    ops += [("prompt_attn.3 custom-call bf16[1,32,8192,128]", 110_000_000, 6_000_000),
            ("gmm.9 custom-call bf16[65536,896]", 120_000_000, 4_000_000)]
    return {"modules": modules, "ops": sorted(ops, key=lambda e: e[1])}


def spans(live=(60_000, 70_000, 80_000), streamed=48 * (3 * 1024 + 8448), rows=(1,)):
    out = [Span("serve/decode_dispatch", i * 100, 50, {"slots_live": 7, "slots_streamed": 48, "kv_positions_live": n,
                                                         "kv_positions_streamed": streamed}) for i, n in enumerate(live)]
    out += [Span("serve/prefill_dispatch", 1000 + i, 5, {"rows": 1, "rows_computed": r}) for i, r in enumerate(rows)]
    return out


def test_readers_find_their_calls_and_none_where_there_are_none(monkeypatch):
    ctx = {"trace": window(), "config": CFG, "cell": Cell(), "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}
    assert window_ms.read(ctx) == pytest.approx(3 * 0.050) and full_ms.read(ctx) == pytest.approx(0.400)
    assert prefill_attn_ms.read(ctx) == pytest.approx(3 * 2.0 + 6.0)
    for mod in (decode_roofline, prefill_roofline, kv_live):
        monkeypatch.setattr(mod.program_spans, "load", lambda c: spans())
    assert kv_live.read(ctx) == pytest.approx(100 * 70_000 / (48 * (3 * 1024 + 8448)))
    floor_ms = flops.decode_attn_bytes(CFG, 70_000) / 819e9 * 1e3
    assert decode_roofline.read(ctx) == pytest.approx(100 * floor_ms / 0.55) and 30 < decode_roofline.read(ctx) < 35
    assert prefill_roofline.read(ctx) == pytest.approx(100 * flops.prefill_attn_flops(CFG, 1, 8192) / 12e-3 / 197e12)
    assert 35 < prefill_roofline.read(ctx) < 45

    # an untraced run, another model's trace, the parent's spans (no counters): nothing to read, no raise
    assert window_ms.read({}) is None and prefill_attn_ms.read({}) is None and decode_roofline.read({"cell": Cell()}) is None
    other = {"trace": {"modules": window()["modules"], "ops": [("retention_step.1 custom-call f32[24,8,5,128]", 5, 5)]},
             "config": CFG, "cell": Cell(), "peaks": ctx["peaks"]}
    assert window_ms.read(other) is None and prefill_attn_ms.read(other) is None
    assert decode_roofline.read(other) is None and prefill_roofline.read(other) is None

    class Lfm2(Cell):
        family = "lfm2_moe"  # a family whose flops module counts neither

    assert decode_roofline.read({**ctx, "cell": Lfm2()}) is None and prefill_roofline.read({**ctx, "cell": Lfm2()}) is None
    for mod in (decode_roofline, prefill_roofline, kv_live):
        monkeypatch.setattr(mod.program_spans, "load", lambda c: [Span("serve/decode_dispatch", 0, 5, {"slots_live": 3}),
                                                                  Span("serve/prefill_dispatch", 9, 5, {})])
    assert decode_roofline.read(ctx) is None and prefill_roofline.read(ctx) is None and kv_live.read(ctx) is None
    for mod in (decode_roofline, prefill_roofline, kv_live):
        monkeypatch.setattr(mod.program_spans, "load", lambda c: None)
    assert decode_roofline.read(ctx) is None and prefill_roofline.read(ctx) is None and kv_live.read(ctx) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_the_new_cell_rehearses_to_its_last_line(bench, trace):
    rc, lines, err = run_cell(["--workload", CELL, "--seed", str(2**31 + 37), "--seconds", "2", "--trace", str(trace),
                               "--rehearse", "--control"])
    assert rc == 1, err[-2000:]
    last = lines[-1]
    assert last["rehearsal"] is True and last["correct"] is False and last["failed"] == 0 and last["attempted"] > 0
    allowed = {m["name"] for m in bench["per_layer" if trace else "end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert last["metrics"] and set(last["metrics"]) <= allowed
    if not trace:
        assert set(last["metrics"]) == {"gap_p95_ms", "setup_s"}  # no ttft_p95_ms: PERF.md, section 6 (PR 34, PR 35)
    summary = [x for x in lines if x.get("event") == "serve_summary"][-1]
    assert summary["kv_window_bytes"] == 3 * 2 * 4 * 16 * 32 * 2 and summary["kv_full_bytes"] == 2 * 4 * 104 * 32 * 2
    checks = [x for x in lines if "check" in x]
    assert checks and all(x["ok"] for x in checks), checks  # the toy limits hold on the sound program
    control = [x for x in lines if "control" in x]
    assert control and any(x["caught"] for x in control), control  # and catch the int8 reference
