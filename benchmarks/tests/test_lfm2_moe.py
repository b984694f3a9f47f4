"""``reference/lfm2_moe.py`` held to hand-worked arithmetic, and the new cell's
control: the plain reference one precision down comes out as not correct under
the toy configuration's limit while the sound program stays inside it."""

import contextlib
import io
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as run_mod
from benchmarks.harness import precision, spec as spec_mod, weights

CELL = "lfm2-8b-a1b.serve-steady"
FP32 = precision.make_dot("fp32")


@pytest.fixture(scope="module")
def ref():
    return spec_mod.load_module("reference", "lfm2_moe")


@pytest.fixture(scope="module")
def toy():
    return spec_mod.load_json(os.path.join(spec_mod.BENCH_DIR, "configs", "lfm2-moe-test.json"))


def test_short_conv_is_numpys_causal_convolution(ref):
    rng = np.random.default_rng(0)
    z, w = rng.normal(size=(11, 5)).astype(np.float32), rng.normal(size=(5, 3)).astype(np.float32)
    got = np.asarray(ref.short_conv(jnp.asarray(z), jnp.asarray(w)))
    for c in range(5):
        # c_t = w0 z_{t-2} + w1 z_{t-1} + w2 z_t: a convolution with the taps reversed, cut to the first T
        np.testing.assert_allclose(got[:, c], np.convolve(z[:, c], w[c, ::-1])[:11], atol=1e-6)


def test_router_against_a_hand_worked_token(ref):
    """Four experts, top 2, logits (0, ln 3, -ln 3, 0): scores 0.5, 0.75, 0.25,
    0.5.  Without a bias experts 1 and 0 win (0 before 3 on the tie's order is
    not relied on: the bias below breaks it); with bias +0.3 on expert 2 the
    selection scores are 0.5, 0.75, 0.55, 0.5, so 1 and 2 win, and their weights
    are still the SCORES 0.75 and 0.25 over their sum: 0.75, 0.25."""
    x = jnp.asarray([[1.0, 0.0]])
    gate = jnp.asarray([[0.0, np.log(3.0), -np.log(3.0), -1e-3], [0.0, 0.0, 0.0, 0.0]], jnp.float32)
    cfg = {"num_experts_per_tok": 2, "use_expert_bias": True, "norm_topk_prob": True, "routed_scaling_factor": 1.0}
    p = {"L.feed_forward.gate.weight": gate, "L.feed_forward.expert_bias": jnp.zeros(4)}
    w, margin = ref.route(FP32, p, "L", x, cfg)
    np.testing.assert_allclose(np.asarray(w[0]), [0.4, 0.6, 0.0, 0.0], atol=1e-5)  # 0.5 and 0.75 over 1.25
    assert abs(float(margin[0]) - (0.5 - 0.49975)) < 1e-4  # the 2nd score over the 3rd
    p["L.feed_forward.expert_bias"] = jnp.asarray([0.0, 0.0, 0.3, 0.0])
    w, margin = ref.route(FP32, p, "L", x, cfg)
    np.testing.assert_allclose(np.asarray(w[0]), [0.0, 0.75, 0.25, 0.0], atol=1e-5)
    assert abs(float(margin[0]) - 0.05) < 1e-5  # 0.55 over 0.5
    cfg["norm_topk_prob"], cfg["routed_scaling_factor"] = False, 2.0
    np.testing.assert_allclose(np.asarray(ref.route(FP32, p, "L", x, cfg)[0][0]), [0.0, 1.5, 0.5, 0.0], atol=1e-5)


def test_forward_returns_the_positions_that_predict_the_served_tokens(ref, toy, capsys):
    """``forward(ids, dec)`` = the logits of positions P-1 .. P-1+T-1 of the model
    run over ``concat(ids, dec[:, 1:])``; ``dec[:, 0]`` is not read; a later token
    does not reach an earlier position (causal), an earlier one does."""
    params = weights.make_reference_weights(ref.param_spec(toy), 7)
    rng = np.random.default_rng(1)
    ids, dec = rng.integers(2, 250, size=(2, 9)), rng.integers(2, 250, size=(2, 5))
    fwd = jax.jit(lambda a, d: ref.forward(params, toy, a, jnp.ones_like(a), d, FP32))
    out = np.asarray(fwd(jnp.asarray(ids), jnp.asarray(dec)))
    assert out.shape == (2, 5, toy["vocab_size"])
    whole = np.concatenate([ids, dec[:, 1:]], axis=1)
    for b in range(2):
        logits, _ = ref.sequence_logits(params, toy, jnp.asarray(whole[b]), 0, FP32)
        np.testing.assert_allclose(out[b], np.asarray(logits)[8:], atol=1e-5)
    other = dec.copy()
    other[:, 0] += 1  # the seq2seq layout's start token: ignored
    np.testing.assert_array_equal(np.asarray(fwd(jnp.asarray(ids), jnp.asarray(other))), out)
    other = dec.copy()
    other[:, 3] += 1  # enters at position P+2: positions P-1..P+1 (outputs 0..2) must not move
    moved = np.asarray(fwd(jnp.asarray(ids), jnp.asarray(other)))
    np.testing.assert_array_equal(moved[:, :3], out[:, :3])
    assert np.abs(moved[:, 3:] - out[:, 3:]).max() > 1e-3
    # the reference's own lines: once an earlier test of the process has built an engine, the program's
    # set-up account says on the same stream what compiles after it (``late_compile``)
    said = [x for x in map(json.loads, (x for x in capsys.readouterr().out.splitlines() if x.startswith("{")))
            if "near_tie_share" in x]
    assert said and all(x["positions"] == 10 and 0 <= x["near_tie_share"] <= 1 for x in said)  # every call says it


def test_param_spec_is_the_programs_layout_leaf_for_leaf(ref, toy):
    """No stacked axis, no transpose: each reference tensor IS a program leaf."""
    from benchmarks.harness import program
    from distributed_llms_example_tpu.models import registry

    adapter = spec_mod.load_module("adapters", "lfm2_moe")
    rows = adapter.leaf_map(toy)
    assert all(layer is None and not transpose for _, layer, _, transpose in rows)
    spec = ref.param_spec(toy)
    assert sorted(spec) == sorted(name for name, *_ in rows)
    init = registry.load_model("lfm2-moe-test").init_params(0)
    for name, _, path, _ in rows:
        assert tuple(program.tree_get(init, path).shape) == tuple(spec[name][0]), name


def _run(argv):
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run_mod.main(argv)
    return rc, [json.loads(x) for x in buf.getvalue().splitlines() if x.startswith("{")]


def test_the_new_cells_control_is_not_correct_and_its_rehearsal_cannot_pass():
    rc, lines = _run(["--workload", CELL, "--seed", "21", "--seconds", "1", "--trace", "0", "--rehearse", "--control"])
    assert rc == 1 and lines[-1]["rehearsal"] is True and lines[-1]["correct"] is False
    assert all(x["ok"] for x in lines if "check" in x), [x for x in lines if "check" in x]
    controls = [x for x in lines if "control" in x]
    assert controls and any(x["caught"] for x in controls), controls
    assert any(x.get("reference") == "lfm2_moe" for x in lines)  # the near-tie share is printed by every run
