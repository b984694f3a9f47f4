"""The Brumby configuration's benchmark parts: the attention-form reference
against a hand-written recurrent evaluation, the flops module against counts
by hand, each new per-layer reader on a synthetic trace (it finds its calls;
it returns None where there are none), and the cell's rehearsal."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import precision, spec as spec_mod, weights
from benchmarks.harness.program_spans import Span

from conftest import run_cell

REF = spec_mod.load_module("reference", "brumby")
flops = spec_mod.load_module("flops", "brumby")
step_ms = spec_mod.load_module("layer_metrics", "serve_retention_step_ms")
step_roofline = spec_mod.load_module("layer_metrics", "serve_retention_step_roofline_pct")
prefill_ms = spec_mod.load_module("layer_metrics", "serve_retention_prefill_ms")
live_pct = spec_mod.load_module("layer_metrics", "serve_state_live_pct")
prefill_mfu = spec_mod.load_module("layer_metrics", "serve_prefill_mfu_pct")

CFG = spec_mod.load_json(os.path.join(spec_mod.BENCH_DIR, "configs", "brumby-14b.json"))
TOY = spec_mod.load_json(os.path.join(spec_mod.BENCH_DIR, "configs", "brumby-test.json"))
CELL = "brumby-14b.serve-steady"


def recurrent_retention_by_hand(p, pre, x, cfg):
    """``Ret`` of one sequence in numpy float64 as the RECURRENCE, with the full
    (d x d) outer-product feature map: S_i = g_i S_{i-1} + (k_i k_i^T / sqrt(d)) (x) v_i,
    y_i = <q_i q_i^T / sqrt(d), S_i> / (<q_i q_i^T / sqrt(d), z_i> + eps).  Shares
    nothing with the reference but the weights and the RoPE table."""
    heads, kv, hd, eps = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"], cfg["rms_norm_eps"]
    w = {k: np.asarray(v, np.float64) for k, v in p.items() if k.startswith(pre + ".self_attn")}
    t = x.shape[0]
    rms = lambda a, g: a / np.sqrt(np.mean(a * a, -1, keepdims=True) + eps) * g  # noqa: E731
    inv = 1.0 / (cfg["rope_theta"] ** (np.arange(0, hd, 2) / hd))

    def rope(a, pos):
        ang = pos * inv
        cos, sin = np.concatenate([np.cos(ang)] * 2), np.concatenate([np.sin(ang)] * 2)
        return a * cos + np.concatenate([-a[hd // 2:], a[: hd // 2]]) * sin

    q = (x @ w[f"{pre}.self_attn.q_proj.weight"]).reshape(t, heads, hd)
    k = (x @ w[f"{pre}.self_attn.k_proj.weight"]).reshape(t, kv, hd)
    v = (x @ w[f"{pre}.self_attn.v_proj.weight"]).reshape(t, kv, hd)
    gate = x @ w[f"{pre}.self_attn.g_proj.weight"] + w[f"{pre}.self_attn.g_proj.bias"]
    g = 1.0 / (1.0 + np.exp(-gate))  # (t, kv)
    out = np.zeros((t, heads, hd))
    state = np.zeros((kv, hd, hd, hd))
    norm = np.zeros((kv, hd, hd))
    for i in range(t):
        for j in range(kv):
            kj = rope(rms(k[i, j], w[f"{pre}.self_attn.k_norm.weight"]), i)
            feat = np.outer(kj, kj) / np.sqrt(hd)
            state[j] = g[i, j] * state[j] + feat[:, :, None] * v[i, j][None, None, :]
            norm[j] = g[i, j] * norm[j] + feat
        for h in range(heads):
            j = h // (heads // kv)
            qh = rope(rms(q[i, h], w[f"{pre}.self_attn.q_norm.weight"]), i)
            feat = np.outer(qh, qh) / np.sqrt(hd)
            out[i, h] = np.einsum("mn,mnv->v", feat, state[j]) / (np.sum(feat * norm[j]) + cfg["retention_eps"])
    return out.reshape(t, heads * hd) @ w[f"{pre}.self_attn.o_proj.weight"]


def test_reference_attention_form_equals_a_hand_written_recurrence():
    params = weights.make_reference_weights(REF.param_spec(TOY), 11)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (20, TOY["hidden_size"])), np.float64)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(REF.retention(precision.make_dot("fp32"), params, "layers.1", jnp.asarray(x, jnp.float32), TOY))
    want = recurrent_retention_by_hand(params, "layers.1", x, TOY)
    # float32 attention form against a float64 recurrence: rounding of a dozen products (|Ret| ~ 1)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)
    g = 1 / (1 + np.exp(-(x @ np.asarray(params["layers.1.self_attn.g_proj.weight"]) + np.asarray(params["layers.1.self_attn.g_proj.bias"]))))
    assert 0.85 < g.min() and g.max() < 0.9995  # the toy's gates remember (assumed.gate_range)


def test_reference_forward_returns_the_served_positions():
    params = weights.make_reference_weights(REF.param_spec(TOY), 12)
    ids = jnp.asarray(np.random.default_rng(0).integers(2, 250, size=(2, 12)), jnp.int32)
    dec = jnp.asarray([[1, 5, 6, 7], [1, 9, 8, 0]], jnp.int32)
    logits = REF.forward(params, TOY, ids, jnp.ones_like(ids), dec, precision.make_dot("fp32"))
    assert logits.shape == (2, 4, TOY["vocab_size"])
    whole = REF.sequence_logits(params, TOY, jnp.concatenate([ids[0], dec[0, 1:]]), 0, precision.make_dot("fp32"))
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(whole[11:]), atol=1e-5)
    assert REF.decoder_start(TOY) == (1, 0) and REF.forced_tokens(TOY, 8) == {}


def test_configuration_file_states_the_published_widths_and_the_cut():
    row = {"attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 17408,
           "max_position_embeddings": 32768, "max_window_layers": 40, "model_type": "brumby", "num_attention_heads": 40,
           "num_hidden_layers": 40, "num_key_value_heads": 8, "rms_norm_eps": 1e-06, "rope_scaling": None,
           "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
           "vocab_size": 151936}  # the catalog row's `config`
    differs = sorted(k for k, v in row.items() if CFG.get(k, "absent") != v)
    assert differs == sorted(CFG["reduced"]) == ["num_hidden_layers", "vocab_size"]
    assert CFG["num_hidden_layers"] == 4 and CFG["vocab_size"] * 8 == 151936
    assert set(CFG["reduced_why"]) == set(CFG["reduced"])
    for key in ("retention_degree", "gate", "norms_and_rope", "retention_eps", "weights", "gate_range", "tokenizer", "eos_token_id"):
        assert len(CFG["assumed"][key]) > 40, key
    assert CFG["retention_state_rows"] == flops.state_rows(CFG) == 128 * 129 // 2


def test_flops_and_bytes_against_counts_by_hand():
    layer = (2 * 5120 * 40 * 128 + 2 * 5120 * 8 * 128 + 5120 * 8 + 8 + 2 * 128) + 3 * 5120 * 17408 + 2 * 5120
    assert flops.layer_params(CFG) == layer and 330.2e6 < layer < 330.4e6
    assert 4 * layer + 2 * 18992 * 5120 + 5120 == sum(int(np.prod(s)) for s, _, _ in REF.param_spec(CFG).values())
    # a slot's state: 8 heads x 8,256 rows x (128 values + the normaliser) x 4 bytes, 4 layers
    assert flops.state_bytes(CFG, 1) == 4 * 8 * 8256 * 129 * 4
    assert 3.26e9 < flops.state_bytes(CFG, 24) < 3.28e9
    assert flops.retention_step_bytes(CFG, 24) == 2 * flops.state_bytes(CFG, 24)
    b = flops.decode_round_bytes(CFG, 24)
    assert b["state_read"] == b["state_written"] == flops.state_bytes(CFG, 24)
    assert 2.83e9 < b["weights"] < 2.85e9  # four layers and the head; one embedding row a slot is noise
    assert b["qkv"] == 4 * 24 * 56 * 128 * 2
    share = (b["state_read"] + b["state_written"]) / sum(b.values())
    assert 0.67 < share < 0.70  # state traffic is two thirds of a round's bytes
    # a one-row wave of 1,024 tokens: 2 per weight and token, the head once, the retention products
    ret = 4 * (2 * (2 * 40 * 1024 * 1024 * 128 / 2) + 2 * 8 * 1024 * 8256 * 129)
    assert flops.retention_prefill_flops(CFG, 1, 1024) == ret and 1.1e11 < ret < 1.2e11
    per_token = 4 * (layer - 2 * 5120 - 2 * 128)
    assert flops.prefill_wave_flops(CFG, 1, 1024) == 2.0 * 1024 * per_token + 2.0 * 18992 * 5120 + ret
    assert 2.8e12 < flops.prefill_wave_flops(CFG, 1, 1024) < 2.9e12
    assert flops.prefill_wave_flops(CFG, 4, 1024) == pytest.approx(4 * flops.prefill_wave_flops(CFG, 1, 1024))


@pytest.mark.parametrize("label,step,prefill", [
    ("retention_step.8 custom-call f32[24,8,5,128]", True, False),  # the decode kernel, one a layer
    ("retention_step custom-call f32[24,8,5,128]", True, False),
    ("self_attn.1 custom-call bf16[128,8,4,64]", False, False),  # another model's decode attention
    ("fusion.394 fusion f32[1,65,128,128]", False, True),  # a head's state product
    ("fusion.393 fusion f32[65,128]", False, True),  # its normaliser (and the features of every key)
    ("constant_dynamic-update-slice_fusion.8 fusion f32[8,65,128,128]", False, True),  # into the wave's states
    ("select_exponential_fusion.8 fusion f32[1024,1024]", False, True),  # the decays
    ("fusion.391 fusion f32[5,1024]", False, True),  # a group's weights and their sums
    ("bitcast_dynamic-update-slice_fusion.8 fusion bf16[32,5,1024,128]", False, True),  # the heads' outputs, a 4-row wave
    ("fusion.392 fusion f32[128,1024]", False, True),  # the keys, transposed for the rotations' product
    ("fusion.128 fusion bf16[1024,17408]", False, False),  # the SwiGLU
    ("fusion.7 fusion bf16[4,1024,5120]", False, False),
    ("exponential_multiply_fusion.8 fusion f32[1024]", False, False),  # a gate vector: not counted
])
def test_which_operations_count(label, step, prefill):
    assert step_ms.is_retention_step(label) is step
    assert prefill_ms.make_filter(1024, 40, 8, 128)(label) is prefill


class Cell:
    name, family = CELL, "brumby"

    def recipe(self, key, default=None):
        return {"max_slots": 24, "prompt_tokens": 1024, "prefill_batch": 4}.get(key, default)


def window(step_ns=(2_500_000, 2_600_000, 2_400_000)):
    """Three decode rounds of four retention steps each, one one-row prefill wave with its admit."""
    step, wave, admit = "jit_serve_decode_step(1)", "jit_serve_prefill(2)", "jit_serve_admit(3)"
    ops, modules = [], []
    for i, dur in enumerate(step_ns):
        lo = i * 20_000_000
        modules.append((step, lo, 15_000_000))
        ops += [("retention_step.%d custom-call f32[24,8,5,128]" % j, lo + j * 3_000_000, dur) for j in range(4)]
        ops.append(("fusion.128 fusion bf16[24,17408]", lo + 12_500_000, 400_000))
    modules += [(wave, 70_000_000, 30_000_000), (admit, 100_500_000, 1_000_000)]
    ops += [("fusion.394 fusion f32[1,65,128,128]", 70_000_000 + j * 500_000, 60_000) for j in range(32)]
    ops += [("fusion.391 fusion f32[5,1024]", 90_000_000 + j * 100_000, 40_000) for j in range(32)]
    ops += [("fusion.128 fusion bf16[1024,17408]", 95_000_000, 4_000_000), ("fusion.5 fusion f32[24,8,65,128,128]", 100_500_000, 900_000)]
    return {"modules": modules, "ops": sorted(ops, key=lambda e: e[1])}


def spans(live=(9, 11, 13), streamed=24, rows=(1,)):
    out = [Span("serve/decode_dispatch", i * 100, 50, {"slots_live": n, "slots_streamed": streamed}) for i, n in enumerate(live)]
    out += [Span("serve/prefill_dispatch", 1000 + i, 5, {"rows": 1, "rows_computed": r}) for i, r in enumerate(rows)]
    return out


def test_readers_find_their_calls_and_none_where_there_are_none(monkeypatch):
    ctx = {"trace": window(), "config": CFG, "cell": Cell(), "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}
    assert step_ms.read(ctx) == pytest.approx(4 * 2.5)
    assert prefill_ms.read(ctx) == pytest.approx(32 * 0.060 + 32 * 0.040)  # the admit program's state write is not the prefill's
    for mod in (step_roofline, live_pct, prefill_mfu):
        monkeypatch.setattr(mod.program_spans, "load", lambda c: spans())
    assert live_pct.read(ctx) == pytest.approx(100 * 11 / 24)
    floor_ms = flops.retention_step_bytes(CFG, 24) / 819e9 * 1e3
    assert step_roofline.read(ctx) == pytest.approx(100 * floor_ms / 10.0) and 75 < step_roofline.read(ctx) < 85
    wave_ms = 32 * 0.06 + 32 * 0.04 + 4.0 + 0.9  # busy union of the prefill run and its admit
    assert prefill_mfu.read(ctx) == pytest.approx(100 * flops.prefill_wave_flops(CFG, 1, 1024) / (wave_ms / 1e3) / 197e12)

    # an untraced run, another model's trace, the parent's spans (no counters): nothing to read, no raise
    assert step_ms.read({}) is None and prefill_ms.read({"config": CFG}) is None
    other = {"trace": {"modules": window()["modules"], "ops": [("self_attn.1 custom-call bf16[128,8,4,64]", 5, 5)]},
             "config": CFG, "cell": Cell(), "peaks": ctx["peaks"]}
    assert step_ms.read(other) is None and step_roofline.read(other) is None and prefill_ms.read(other) is None
    lfm2 = {**ctx, "config": {"hidden_size": 2048}}
    assert prefill_ms.read(lfm2) is None  # a configuration without retention layers
    for mod in (step_roofline, live_pct, prefill_mfu):
        monkeypatch.setattr(mod.program_spans, "load", lambda c: [Span("serve/decode_dispatch", 0, 5, {}), Span("serve/prefill_dispatch", 9, 5, {})])
    assert step_roofline.read(ctx) is None and live_pct.read(ctx) is None and prefill_mfu.read(ctx) is None
    for mod in (step_roofline, live_pct, prefill_mfu):
        monkeypatch.setattr(mod.program_spans, "load", lambda c: None)
    assert step_roofline.read(ctx) is None and live_pct.read(ctx) is None and prefill_mfu.read(ctx) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_the_new_cell_rehearses_to_its_last_line(bench, trace):
    rc, lines, err = run_cell(["--workload", CELL, "--seed", str(2**31 + 35), "--seconds", "2", "--trace", str(trace),
                               "--rehearse", "--control"])
    assert rc == 1, err[-2000:]
    last = lines[-1]
    assert last["rehearsal"] is True and last["correct"] is False and last["failed"] == 0 and last["attempted"] > 0
    allowed = {m["name"] for m in bench["per_layer" if trace else "end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert last["metrics"] and set(last["metrics"]) <= allowed
    if not trace:
        assert set(last["metrics"]) == {"gap_p95_ms", "setup_s"}  # TTFT p95 of ~160 requests a window is sampling noise: PERF.md
    checks = [x for x in lines if "check" in x]
    assert checks and all(x["ok"] for x in checks), checks  # the toy limit holds on the sound program
    control = [x for x in lines if "control" in x]
    assert control and any(x["caught"] for x in control), control  # and catches the int8 reference
