"""The Falcon-H1 configuration's benchmark parts: the reference against a
hand-written evaluation of one block, the flops module against counts by hand,
each new per-layer reader on a synthetic trace (it finds its calls; it returns
None where there are none), and the cell's rehearsal."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import precision, spec as spec_mod, weights
from benchmarks.harness.program_spans import Span

from conftest import run_cell

REF = spec_mod.load_module("reference", "falcon_h1")
flops = spec_mod.load_module("flops", "falcon_h1")
step_ms = spec_mod.load_module("layer_metrics", "serve_ssm_step_ms")
step_roofline = spec_mod.load_module("layer_metrics", "serve_ssm_step_roofline_pct")
prefill_ms = spec_mod.load_module("layer_metrics", "serve_ssm_prefill_ms")
live_pct = spec_mod.load_module("layer_metrics", "serve_state_live_pct")
kv_live_pct = spec_mod.load_module("layer_metrics", "serve_kv_live_pct")
attn_roofline = spec_mod.load_module("layer_metrics", "serve_decode_attn_roofline_pct")

CFG = spec_mod.load_json(os.path.join(spec_mod.BENCH_DIR, "configs", "falcon-h1-34b.json"))
TOY = spec_mod.load_json(os.path.join(spec_mod.BENCH_DIR, "configs", "falcon-h1-test.json"))
CELL = "falcon-h1-34b.serve-steady"


def block_by_hand(p, pre, x, cfg):
    """One block of one sequence in numpy float64, token after token with plain
    loops: the attention a softmax a query, the mixer a state a head updated a
    token at a time (state (N, P), the other way round from the reference's),
    the convolution a sum over its taps a position.  Shares nothing with the
    reference but the weights."""
    w = {k[len(pre) + 1:]: np.asarray(v, np.float64) for k, v in p.items() if k.startswith(pre + ".")}
    t, d = x.shape
    eps = cfg["rms_norm_eps"]
    silu = lambda a: a / (1.0 + np.exp(-a))  # noqa: E731
    rms = lambda a, g: a / np.sqrt(np.mean(a * a, -1, keepdims=True) + eps) * g  # noqa: E731
    u = rms(x, w["input_layernorm.weight"])

    # attention
    heads, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    inv = 1.0 / (float(cfg["rope_theta"]) ** (np.arange(0, hd, 2) / hd))

    def rope(a, pos):
        ang = pos * inv
        cos, sin = np.concatenate([np.cos(ang)] * 2), np.concatenate([np.sin(ang)] * 2)
        return a * cos + np.concatenate([-a[hd // 2:], a[: hd // 2]]) * sin

    s = cfg["attention_in_multiplier"] * u
    q = (s @ w["self_attn.q_proj.weight"]).reshape(t, heads, hd)
    k = (cfg["key_multiplier"] * (s @ w["self_attn.k_proj.weight"])).reshape(t, kv, hd)
    v = (s @ w["self_attn.v_proj.weight"]).reshape(t, kv, hd)
    attn = np.zeros((t, heads, hd))
    for i in range(t):
        for h in range(heads):
            j = h // (heads // kv)
            scores = np.asarray([rope(q[i, h], i) @ rope(k[m, j], m) for m in range(i + 1)]) / np.sqrt(hd)
            weights_ = np.exp(scores - scores.max())
            attn[i, h] = (weights_ / weights_.sum()) @ v[: i + 1, j]
    attn = attn.reshape(t, heads * hd) @ w["self_attn.o_proj.weight"]

    # mixer
    inner, mh, p_, g, n, taps = (cfg["mamba_d_ssm"], cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_n_groups"],
                                 cfg["mamba_d_state"], cfg["mamba_d_conv"])
    m = cfg["ssm_multipliers"]
    proj = (cfg["ssm_in_multiplier"] * u) @ w["mamba.in_proj.weight"]
    z = proj[:, :inner] * m[0]
    xbc = np.concatenate([proj[:, inner:2 * inner] * m[1], proj[:, 2 * inner:2 * inner + g * n] * m[2],
                          proj[:, 2 * inner + g * n:2 * inner + 2 * g * n] * m[3]], axis=1)
    dt = np.log1p(np.exp(proj[:, 2 * inner + 2 * g * n:] * m[4] + w["mamba.dt_bias"]))
    conv = np.zeros_like(xbc)
    for i in range(t):
        for back in range(taps):  # the tap that multiplies the column ``back`` tokens ago
            if i - back >= 0:
                conv[i] += w["mamba.conv1d.weight"][:, taps - 1 - back] * xbc[i - back]
    act = silu(conv + w["mamba.conv1d.bias"])
    a_neg = -np.exp(w["mamba.A_log"])
    y = np.zeros((t, mh, p_))
    state = np.zeros((mh, n, p_))
    for i in range(t):
        for h in range(mh):
            grp = h // (mh // g)
            x_h = act[i, h * p_:(h + 1) * p_]
            b_g = act[i, inner + grp * n: inner + (grp + 1) * n]
            c_g = act[i, inner + g * n + grp * n: inner + g * n + (grp + 1) * n]
            state[h] = np.exp(dt[i, h] * a_neg[h]) * state[h] + np.outer(b_g, dt[i, h] * x_h)
            y[i, h] = c_g @ state[h] + w["mamba.D"][h] * x_h
    gated = (y.reshape(t, inner) * silu(z)).reshape(t, g, inner // g)
    gated = gated / np.sqrt(np.mean(gated * gated, -1, keepdims=True) + eps)
    mix = (gated.reshape(t, inner) * w["mamba.norm.weight"]) @ w["mamba.out_proj.weight"]

    h1 = x + cfg["attention_out_multiplier"] * attn + cfg["ssm_out_multiplier"] * mix
    f = rms(h1, w["pre_ff_layernorm.weight"])
    gate = silu(cfg["mlp_multipliers"][0] * (f @ w["feed_forward.gate_proj.weight"]))
    return h1 + cfg["mlp_multipliers"][1] * ((gate * (f @ w["feed_forward.up_proj.weight"])) @ w["feed_forward.down_proj.weight"])


def test_reference_block_equals_a_hand_written_evaluation():
    params = weights.make_reference_weights(REF.param_spec(TOY), 11)
    x = 0.3 * np.asarray(jax.random.normal(jax.random.PRNGKey(1), (14, TOY["hidden_size"])), np.float64)
    dot, pre, eps = precision.make_dot("fp32"), "layers.1", TOY["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        xj = jnp.asarray(x, jnp.float32)
        u = REF.rms(xj, params[f"{pre}.input_layernorm.weight"], eps)
        h1 = (xj + TOY["attention_out_multiplier"] * REF.attention(dot, params, pre, TOY["attention_in_multiplier"] * u, TOY)
              + TOY["ssm_out_multiplier"] * REF.mixer(dot, params, pre, TOY["ssm_in_multiplier"] * u, TOY))
        got = np.asarray(h1 + REF.mlp(dot, params, pre, REF.rms(h1, params[f"{pre}.pre_ff_layernorm.weight"], eps), TOY))
    want = block_by_hand(params, pre, x, TOY)
    # float32 against float64, a dozen products deep (the block writes ~0.1 a channel into |x| ~ 0.3)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)
    assert np.abs(want - x).std() > 0.05  # the block does write


def test_reference_forward_returns_the_served_positions():
    params = weights.make_reference_weights(REF.param_spec(TOY), 12)
    ids = jnp.asarray(np.random.default_rng(0).integers(2, 250, size=(2, 12)), jnp.int32)
    dec = jnp.asarray([[1, 5, 6, 7], [1, 9, 8, 0]], jnp.int32)
    logits = REF.forward(params, TOY, ids, jnp.ones_like(ids), dec, precision.make_dot("fp32"))
    assert logits.shape == (2, 4, TOY["vocab_size"])
    whole = REF.sequence_logits(params, TOY, jnp.concatenate([ids[0], dec[0, 1:]]), 0, precision.make_dot("fp32"))
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(whole[11:]), atol=1e-5)
    assert REF.decoder_start(TOY) == (1, 0) and REF.forced_tokens(TOY, 8) == {}


def test_configuration_file_states_the_published_widths_multipliers_and_the_cut():
    row = {"attention_bias": False, "attention_in_multiplier": 1, "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
           "embedding_multiplier": 5.656854249492381, "head_dim": 128, "hidden_act": "silu", "hidden_size": 5120,
           "intermediate_size": 21504, "key_multiplier": 0.011048543456039804, "lm_head_multiplier": 0.0078125,
           "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128, "mamba_d_ssm": 4096,
           "mamba_d_state": 256, "mamba_expand": 2, "mamba_n_groups": 2, "mamba_n_heads": 32, "mamba_norm_before_gate": False,
           "mamba_proj_bias": False, "mamba_rms_norm": True, "mamba_use_mlp": True, "max_position_embeddings": 262144,
           "mlp_bias": False, "mlp_expansion_factor": 8, "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
           "model_type": "falcon_h1", "num_attention_heads": 20, "num_hidden_layers": 72, "num_key_value_heads": 4,
           "num_logits_to_keep": 1, "projectors_bias": False, "rms_norm_eps": 1e-05, "rope_scaling": None,
           "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
           "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738],
           "ssm_out_multiplier": 0.08838834764831845, "tie_word_embeddings": False, "vocab_size": 261120}  # the catalog row's `config`
    differs = sorted(k for k, v in row.items() if CFG.get(k, "absent") != v)
    assert differs == sorted(CFG["reduced"]) == ["num_hidden_layers", "vocab_size"]
    assert CFG["num_hidden_layers"] == 4 and CFG["vocab_size"] * 8 == 261120
    assert set(CFG["reduced_why"]) == set(CFG["reduced"])
    for key in ("section_order", "multipliers_placement", "dt_bias, A_log, D", "norm_grouping", "conv", "weights",
                "decay_range", "tokenizer", "eos_token_id", "dtypes"):
        assert len(CFG["assumed"][key]) > 40, key
    assert set(CFG["init_std"]) == set(TOY["init_std"]) and len(CFG["init_std"]) == 17
    adapter = spec_mod.load_module("adapters", "falcon_h1")
    assert len(adapter.MULTIPLIERS) + 5 + 2 == 14  # the fourteen: seven scalars, five over W_in's sections, two in the MLP


def test_flops_and_bytes_against_counts_by_hand():
    attention = 2 * 5120 * 20 * 128 + 2 * 5120 * 4 * 128
    w_in, w_out = 5120 * (4096 + 5120 + 32), 4096 * 5120
    mixer = w_in + w_out + 5120 * 4 + 5120 + 3 * 32 + 4096  # taps, bias, dt_bias / A_log / D, the norm's gain
    layer = attention + mixer + 3 * 5120 * 21504 + 2 * 5120
    assert attention == 31_457_280 and w_in == 47_349_760 and w_out == 20_971_520 and mixer == 68_351_072
    assert flops.layer_params(CFG) == layer == 430_120_032  # the issue's 430.1M
    assert flops.model_params(CFG) == 4 * layer + 2 * 32640 * 5120 + 5120 == sum(
        int(np.prod(s)) for s, _, _ in REF.param_spec(CFG).values())
    assert 2.054e9 < flops.model_params(CFG) < 2.056e9  # 4.11 GB in bfloat16, 12.33 GB at the harness's 6 bytes
    # a slot's state: 32 heads x 128 x 256 float32 = 4,194,304 bytes a layer; its taps 5120 x 3 bfloat16
    assert flops.state_bytes(CFG, 1) == 4 * 4_194_304 and flops.state_bytes(CFG, 64) == 1_073_741_824
    assert flops.ssm_step_bytes(CFG, 1) == 2 * (4 * 4_194_304 + 4 * 5120 * 3 * 2)
    assert flops.kv_bytes_per_position(CFG) == 2048 and flops.decode_attn_bytes(CFG, 1000) == 2_048_000
    b = flops.decode_round_bytes(CFG, 30, 4 * 30 * 300)
    assert 3.77e9 < b["weights"] < 3.78e9 and b["state_and_taps"] == flops.ssm_step_bytes(CFG, 30)
    assert 0.20 < b["state_and_taps"] / sum(b.values()) < 0.22  # a fifth of a round's bytes at 30 live slots
    # a one-row wave of 256 tokens: 2 per weight and token, the head once, causal attention, the scan's products
    attn = 4 * 2 * (2 * 20 * 256 * 256 * 128 / 2)
    scan = 4 * 256 * ((2 * 2 * 128 * 256 / 2 + 2 * 32 * 128 * 128 / 2) + 2 * (2 * 32 * 256 * 128))
    assert flops.prefill_attn_flops(CFG, 1, 256) == attn and flops.ssm_prefill_flops(CFG, 1, 256) == scan
    per_token = 4 * (attention + w_in + w_out + 3 * 5120 * 21504)
    assert flops.prefill_wave_flops(CFG, 1, 256) == 2.0 * 256 * per_token + 2.0 * 32640 * 5120 + attn + scan
    assert 0.88e12 < flops.prefill_wave_flops(CFG, 1, 256) < 0.89e12 and scan / flops.prefill_wave_flops(CFG, 1, 256) < 0.006
    assert flops.prefill_wave_flops(CFG, 4, 256) == pytest.approx(4 * flops.prefill_wave_flops(CFG, 1, 256))


@pytest.mark.parametrize("label,step,prefill", [
    ("ssm_step.8 custom-call f32[64,32,1,128]", True, False),  # the decode kernel, one a layer
    ("ssm_step custom-call f32[64,32,1,128]", True, False),
    ("retention_step.2 custom-call f32[24,8,5,128]", False, False),  # another model's step
    ("self_attn.1 custom-call bf16[64,4,5,128]", False, False),  # this model's decode attention
    ("fusion.394 fusion f32[1,2,2,16,256,128]", False, True),  # the states the chunks leave
    ("fusion.12 fusion f32[1,32,256,128]", False, True),  # the state a prompt leaves
    ("fusion.393 fusion f32[1,2,128,128,2,16]", False, True),  # a chunk's weights
    ("convolution.3 convolution f32[1,2,2,128,128]", False, True),  # C . B a group
    ("fusion.391 fusion f32[1,2,128,2,16]", False, True),  # the running sums of dt A
    ("fusion.390 fusion f32[1,2,128,32]", False, True),
    ("prompt_attn.2 custom-call bf16[1,20,256,128]", False, False),  # attention over a 256-token prompt: (T, d) = (N, P)
    ("fusion.7 fusion bf16[1,4,256,128]", False, False),  # its keys
    ("fusion.128 fusion bf16[256,21504]", False, False),  # the SwiGLU
    ("fusion.9 fusion bf16[1,256,9248]", False, False),  # W_in's output
    ("fusion.10 fusion bf16[4,256,5120]", False, False),
])
def test_which_operations_count(label, step, prefill):
    assert step_ms.is_ssm_step(label) is step
    assert prefill_ms.make_filter(CFG)(label) is prefill


class Cell:
    name, family = CELL, "falcon_h1"

    def recipe(self, key, default=None):
        return {"max_slots": 64, "prompt_tokens": 256, "prefill_batch": 4}.get(key, default)


def window(step_ns=(450_000, 460_000, 440_000)):
    """Three decode rounds of four state-space steps and four decode attentions each, one one-row prefill wave."""
    step, wave, admit = "jit_serve_decode_step(1)", "jit_serve_prefill(2)", "jit_serve_admit(3)"
    ops, modules = [], []
    for i, dur in enumerate(step_ns):
        lo = i * 20_000_000
        modules.append((step, lo, 9_000_000))
        ops += [("ssm_step.%d custom-call f32[64,32,1,128]" % j, lo + j * 2_000_000, dur) for j in range(4)]
        ops += [("self_attn.%d custom-call bf16[64,4,5,128]" % j, lo + j * 2_000_000 + 1_000_000, 100_000) for j in range(4)]
        ops.append(("fusion.128 fusion bf16[64,21504]", lo + 8_200_000, 400_000))
    modules += [(wave, 70_000_000, 9_000_000), (admit, 79_500_000, 300_000)]
    ops += [("fusion.394 fusion f32[1,2,2,16,256,128]", 70_000_000 + j * 500_000, 60_000) for j in range(8)]
    ops += [("fusion.393 fusion f32[1,2,128,128,2,16]", 75_000_000 + j * 100_000, 40_000) for j in range(8)]
    ops += [("fusion.128 fusion bf16[256,21504]", 76_000_000, 2_000_000), ("fusion.5 fusion f32[64,32,256,128]", 79_500_000, 250_000)]
    return {"modules": modules, "ops": sorted(ops, key=lambda e: e[1])}


def spans(live=(25, 30, 35)):
    return [Span("serve/decode_dispatch", i * 100, 50, {
        "slots_live": n, "slots_streamed": n, "kv_positions_live": 4 * n * 300, "kv_positions_streamed": 4 * 64 * 512})
        for i, n in enumerate(live)]


def test_readers_find_their_calls_and_none_where_there_are_none(monkeypatch):
    ctx = {"trace": window(), "config": CFG, "cell": Cell(), "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}
    assert step_ms.read(ctx) == pytest.approx(4 * 0.45)
    assert prefill_ms.read(ctx) == pytest.approx(8 * 0.060 + 8 * 0.040)  # the admit program's state write is not the prefill's
    for mod in (step_roofline, live_pct, kv_live_pct, attn_roofline):
        monkeypatch.setattr(mod.program_spans, "load", lambda c: spans())
    # both families of counters on one span, one model
    assert live_pct.read(ctx) == pytest.approx(100.0) and kv_live_pct.read(ctx) == pytest.approx(100 * 30 * 300 / (64 * 512))
    floor_ms = flops.ssm_step_bytes(CFG, 30) / 819e9 * 1e3
    assert step_roofline.read(ctx) == pytest.approx(100 * floor_ms / 1.8) and 65 < step_roofline.read(ctx) < 75
    assert attn_roofline.read(ctx) == pytest.approx(100 * (4 * 30 * 300 * 2048 / 819e9 * 1e3) / 0.4)

    # an untraced run, another model's trace, the parent's spans (no counters): nothing to read, no raise
    assert step_ms.read({}) is None and prefill_ms.read({"config": CFG}) is None and step_roofline.read({**ctx, "trace": None}) is None
    other = {"trace": {"modules": window()["modules"], "ops": [("retention_step.1 custom-call f32[24,8,5,128]", 5, 5)]},
             "config": CFG, "cell": Cell(), "peaks": ctx["peaks"]}
    assert step_ms.read(other) is None and step_roofline.read(other) is None and prefill_ms.read(other) is None
    assert prefill_ms.read({**ctx, "config": {"hidden_size": 2048}}) is None  # a configuration without a state-space mixer

    class Brumby(Cell):
        family = "brumby"  # a family whose flops module counts no such bytes

    assert step_roofline.read({**ctx, "cell": Brumby()}) is None
    monkeypatch.setattr(step_roofline.program_spans, "load", lambda c: [Span("serve/decode_dispatch", 0, 5, {})])
    assert step_roofline.read(ctx) is None
    monkeypatch.setattr(step_roofline.program_spans, "load", lambda c: None)
    assert step_roofline.read(ctx) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_the_new_cell_rehearses_to_its_last_line(bench, trace):
    rc, lines, err = run_cell(["--workload", CELL, "--seed", str(2**31 + 45), "--seconds", "2", "--trace", str(trace),
                               "--rehearse", "--control"])
    assert rc == 1, err[-2000:]
    last = lines[-1]
    assert last["rehearsal"] is True and last["correct"] is False and last["failed"] == 0 and last["attempted"] > 0
    allowed = {m["name"] for m in bench["per_layer" if trace else "end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert last["metrics"] and set(last["metrics"]) <= allowed
    if not trace:
        assert {"gap_p95_ms", "setup_s"} <= set(last["metrics"])
    checks = [x for x in lines if "check" in x]
    assert checks and all(x["ok"] for x in checks), checks  # the toy limit holds on the sound program
    control = [x for x in lines if "control" in x]
    assert control and any(x["caught"] for x in control), control  # and catches the int8 reference
