"""LFM2-MoE (models/lfm2.py) against its plain reference
(benchmarks/reference/lfm2_moe.py) at toy size on the CPU, seeded weights:
the full forward, prefill + cached decode through the serving engine with
ragged prompts and reused slots, the sorted no-drop expert layer under a
skewed router, the selection bias, and two trainer steps."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import precision, program, spec as spec_mod, weights
from distributed_llms_example_tpu.models import registry
from distributed_llms_example_tpu.ops.moe import MoEMLP
from distributed_llms_example_tpu.parallel.sharding import cache_leaf_name
from distributed_llms_example_tpu.serving.engine import ServeConfig, ServingEngine, UnsupportedServeMode

CFG = spec_mod.load_json(os.path.join(spec_mod.BENCH_DIR, "configs", "lfm2-moe-test.json"))
REF = spec_mod.load_module("reference", "lfm2_moe")
ADAPTER = spec_mod.load_module("adapters", "lfm2_moe")
FP32 = precision.make_dot("fp32")


def seeded(seed, dtype=jnp.float32):
    """(loaded model, program params, reference params) from one seed."""
    lm = registry.load_model("lfm2-moe-test", dtype=dtype)
    for k, want in ADAPTER.program_config_checks(CFG).items():
        assert getattr(lm.config, k) == want, k
    spec = REF.param_spec(CFG)
    params = weights.make_program_weights(spec, seed, program.to_program_tree(ADAPTER.leaf_map(CFG)))
    init = lm.init_params(0)
    assert jax.tree.map(lambda x: x.shape, params) == jax.tree.map(lambda x: x.shape, init)  # every leaf mapped
    return lm, params, weights.make_reference_weights(spec, seed)


def reference_logits(ref_params, tokens):
    """Float32 reference logits of every position of ``tokens`` (T,)."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(REF.sequence_logits(ref_params, CFG, jnp.asarray(tokens, jnp.int32), 0, FP32)[0])


def test_full_forward_matches_the_reference_in_float32():
    lm, params, ref_params = seeded(3)
    ids = np.random.default_rng(0).integers(2, 250, size=(2, 40))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(lm.module.apply({"params": params}, jnp.asarray(ids, jnp.int32)))
    for row, logits in zip(ids, got):
        # float32 on both sides: what is left is the order of the sums (the
        # program's sorted grouped products, the reference's loop over experts)
        np.testing.assert_allclose(logits, reference_logits(ref_params, row), atol=2e-4, rtol=0)


def test_full_forward_in_bfloat16_stays_within_bfloat16_of_the_reference():
    lm, params, ref_params = seeded(4, dtype=jnp.bfloat16)
    ids = np.random.default_rng(1).integers(2, 250, size=(24,))
    got = np.asarray(lm.module.apply({"params": params}, jnp.asarray(ids[None], jnp.int32))[0], np.float32)
    want = reference_logits(ref_params, ids)
    rel = np.sqrt(np.mean(np.square(got - want), axis=-1)) / want.std(axis=-1)  # per position
    # bfloat16 keeps 8 bits: 0.4 % a rounding, and a logit is some thirty
    # roundings deep (five layers of operator and feed-forward), so a few per
    # cent of the logits' own spread at the median position (read: 0.028-0.058
    # on seeds 4 to 9).  A position whose 4th and 5th expert scores nearly tie
    # may route otherwise than float32 does and move by one expert's share of
    # a layer (read: 0.11-0.36 at the worst position): that one only has to
    # stay under half the spread, which a wrong layer would not
    assert np.median(rel) < 0.09, np.median(rel)
    assert rel.max() < 0.5, rel.max()


def _engine(lm, **kw):
    # no end-of-sequence id, as the benchmark's cell runs it: a toy head over 256
    # rows would end one request in a dozen on id 1
    lm = dataclasses.replace(lm, config=dataclasses.replace(lm.config, eos_token_id=None))
    serve = ServeConfig(max_slots=3, prefill_batch=2, max_new_tokens=10, max_source_length=24,
                        log_every_steps=0, request_spans=False, **kw)
    return ServingEngine(lm.module, lm.config, None, serve, is_seq2seq=False)


def test_engine_prefill_and_cached_decode_follow_the_reference():
    """Ragged right-padded prompts (lengths 1 to the full width, so a state of
    fewer than two valid columns is there too) and more requests than slots:
    every served token must be the reference's best at its position, or lie
    within float32 rounding of it, with the reference teacher-forced on the
    served tokens — logits, not tokens, are what is compared."""
    lm, params, ref_params = seeded(5)
    rng = np.random.default_rng(2)
    prompts = [list(rng.integers(2, 250, size=n)) for n in (24, 1, 7, 2, 24, 13, 3, 19)]
    budgets = [10, 6, 10, 3, 8, 10, 5, 9]
    with jax.default_matmul_precision("highest"):
        outs = _engine(lm).generate(params, prompts, max_new=budgets)
    assert [len(o) for o in outs] == budgets
    for prompt, served in zip(prompts, outs):
        logits = reference_logits(ref_params, prompt + served[:-1])[len(prompt) - 1:]
        below = logits.max(axis=-1) - logits[np.arange(len(served)), served]
        assert below.max() < 1e-4, (len(prompt), below)


def test_a_reused_slot_starts_from_a_clean_conv_state():
    """One slot, three requests one after another: the second and third land in
    the slot the first used; each must decode as it does alone."""
    lm, params, _ = seeded(6)
    rng = np.random.default_rng(3)
    prompts = [list(rng.integers(2, 250, size=n)) for n in (20, 5, 11)]
    serve = ServeConfig(max_slots=1, prefill_batch=1, max_new_tokens=8, max_source_length=24,
                        log_every_steps=0, request_spans=False)
    config = dataclasses.replace(lm.config, eos_token_id=None)
    eng = ServingEngine(lm.module, config, None, serve, is_seq2seq=False)
    together = eng.generate(params, prompts)
    alone = [eng.generate(params, [p])[0] for p in prompts]
    assert together == alone


def test_the_cache_holds_conv_state_beside_kv_and_the_summary_counts_both():
    lm, params, _ = seeded(6)
    sess = _engine(lm).open(params)
    leaves = {cache_leaf_name(p): x.shape for p, x in jax.tree_util.tree_leaves_with_path(sess.state["cache"])}
    assert leaves["conv_state"] == (3, 64, 2) and leaves["cached_key"] == (3, 34, 2 * 16)
    assert sess._cache_bytes_by_kind == {"kv_bytes": 2 * 2 * 3 * 2 * 34 * 16 * 4, "conv_state_bytes": 3 * 3 * 64 * 2 * 4}
    sess.finalize()


@pytest.mark.parametrize("mode", [{"paged_kv": True}, {"paged_kv": True, "prefix_cache": True}, {"spec_tokens": 2}])
def test_modes_that_cannot_hold_a_conv_state_are_refused_by_name(mode):
    lm = registry.load_model("lfm2-moe-test")
    with pytest.raises(UnsupportedServeMode, match="convolution state"):
        _engine(lm, **mode)


def test_open_keeps_weights_in_the_dtype_the_config_states():
    lm, params, _ = seeded(7)
    assert lm.config.param_dtype is None
    sess = _engine(lm).open(params)
    assert {x.dtype for x in jax.tree.leaves(sess.params)} == {jnp.dtype("float32")}
    sess.finalize()
    stated = dataclasses.replace(lm.config, param_dtype="bfloat16")
    eng = ServingEngine(lm.module, stated, None, _engine(lm).serve, is_seq2seq=False)
    sess = eng.open(params)
    assert {x.dtype for x in jax.tree.leaves(sess.params)} == {jnp.dtype("bfloat16")}
    sess.finalize()


def test_a_decode_round_reports_its_expert_load_on_the_fetch_span():
    from distributed_llms_example_tpu.obs.spans import SpanRecorder

    seen = []

    class Annotation:
        def __init__(self, name, **kw):
            self.name = name

        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

        def set_metadata(self, **kw):
            seen.append((self.name, kw))

    lm, params, _ = seeded(8)
    sess = _engine(lm).open(params, spans=SpanRecorder(scope="serve", annotate=Annotation))
    for n in (9, 4):
        sess.submit(list(range(2, 2 + n)), max_new=4)
    while sess.has_work():
        sess.step()
    sess.finalize()
    rounds = [kw for name, kw in seen if name == "serve/token_fetch"]
    assert rounds and all(set(kw) == {"moe_experts_hit", "moe_max_load", "moe_assignments"} for kw in rounds)
    for kw in rounds:  # three slots (idle ones too) x top-4, in each of the four expert layers
        assert kw["moe_assignments"] == 3 * 4 * 4
        assert 4 * 4 <= kw["moe_experts_hit"] <= 4 * 8 and 2 <= kw["moe_max_load"] <= 3
    # K/V and a conv state, no step that walks a live list: the round streams every slot (PR 36)
    streamed = [kw["slots_streamed"] for name, kw in seen if name == "serve/decode_dispatch"]
    assert not sess.eng.streams_live_slots and streamed and set(streamed) == {sess.eng.S} == {3}


def _skewed_moe():
    """An expert layer whose router sends most tokens to expert 0 and none to
    experts 5, 6, 7, with a per-token loop to hold it to."""
    moe = MoEMLP(num_experts=8, intermediate_size=16, top_k=2, capacity_factor=-1.0,
                 scorer="sigmoid", use_expert_bias=True, aux_loss=False)
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 20, 12))
    params = moe.init(jax.random.PRNGKey(1), x)["params"]
    router = np.asarray(params["router"]["kernel"]).copy() * 0.3
    bias = np.zeros(8, np.float32)
    bias[0], bias[5:] = 2.0, -5.0
    params = {**params, "router": {"kernel": jnp.asarray(router)}, "expert_bias": jnp.asarray(bias)}
    return moe, params, x


def _per_token_loop(params, x, bias):
    out = np.zeros(x.shape, np.float32)
    chosen_all = []
    for idx in np.ndindex(x.shape[:-1]):
        t = np.asarray(x[idx], np.float32)
        s = 1.0 / (1.0 + np.exp(-(t @ np.asarray(params["router"]["kernel"]))))
        chosen = np.argsort(-(s + bias), kind="stable")[:2]
        w = s[chosen] / (s[chosen].sum() + 1e-6)
        for e, we in zip(chosen, w):
            g = t @ np.asarray(params["gate_proj"][e])
            h = g / (1.0 + np.exp(-g)) * (t @ np.asarray(params["up_proj"][e]))
            out[idx] += we * (h @ np.asarray(params["down_proj"][e]))
        chosen_all.append(tuple(sorted(int(e) for e in chosen)))
    return out, chosen_all


def test_sorted_no_drop_layer_matches_a_per_token_loop_under_a_skewed_router():
    moe, params, x = _skewed_moe()
    with jax.default_matmul_precision("highest"):
        got, stats = moe.apply({"params": params}, x, mutable=["moe_stats"])
    want, chosen = _per_token_loop(params, x, np.asarray(params["expert_bias"]))
    load = np.asarray(jax.tree.leaves(stats["moe_stats"])[0])
    # assignments in = assignments out: every token's two choices are computed
    assert load.sum() == 60 * 2 and load[0] == 60 and (load[5:] == 0).all()
    assert load.tolist() == [sum(e in c for c in chosen) for e in range(8)]
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5, rtol=1e-5)


def test_no_tokens_by_experts_by_capacity_tensor_on_the_no_drop_path():
    """The dense dispatch would build (1, n, E, n): nothing of n*E*n elements
    may appear in the no-drop path's program, at any expert count."""
    moe = MoEMLP(num_experts=8, intermediate_size=16, top_k=2, capacity_factor=-1.0)
    x = jnp.zeros((1, 96, 12))
    params = moe.init(jax.random.PRNGKey(1), x)["params"]
    jaxpr = jax.make_jaxpr(lambda p, v: moe.apply({"params": p}, v))(params, x)
    biggest = max(int(np.prod(v.aval.shape)) for eqn in jaxpr.eqns for v in eqn.outvars)
    assert biggest <= 96 * 2 * 16 * 2, biggest  # rows x the widest projection, with room; 96*8*96 is 73,728


def test_expert_bias_changes_which_experts_are_chosen_and_not_their_weights():
    moe, params, x = _skewed_moe()
    flat = {**params, "expert_bias": jnp.zeros(8)}
    with jax.default_matmul_precision("highest"):
        biased = np.asarray(moe.apply({"params": params}, x))
        plain = np.asarray(moe.apply({"params": flat}, x))
    want_biased, chosen_biased = _per_token_loop(params, x, np.asarray(params["expert_bias"]))
    want_plain, chosen_plain = _per_token_loop(flat, x, np.zeros(8))
    assert chosen_biased != chosen_plain  # the bias moved the selection
    np.testing.assert_allclose(biased, want_biased, atol=1e-5, rtol=1e-5)  # weights from the scores alone
    np.testing.assert_allclose(plain, want_plain, atol=1e-5, rtol=1e-5)
    # no gradient reaches the buffer
    grad = jax.grad(lambda p: jnp.sum(moe.apply({"params": p}, x) ** 2))(params)
    assert float(jnp.abs(grad["expert_bias"]).max()) == 0.0


def test_two_trainer_steps_run_and_the_loss_falls(tmp_path):
    from distributed_llms_example_tpu.core.config import CheckpointConfig, MeshConfig, TrainConfig
    from distributed_llms_example_tpu.train.trainer import Trainer

    rng = np.random.RandomState(0)
    recs = [{"dialogue": " ".join(f"w{rng.randint(30)}" for _ in range(8)), "summary": "w1 w2"} for _ in range(16)]
    cfg = TrainConfig(
        model_ckpt="lfm2-moe-test", output_dir=str(tmp_path), batch_size=8, num_epochs=2, warmup_steps=0,
        evaluation_steps=0, learning_rate=3e-3, max_source_length=64, max_target_length=16, pad_to_multiple=32,
        eval_max_new_tokens=4, num_beams=1, log_every_steps=1, mesh=MeshConfig(data=-1),
        checkpoint=CheckpointConfig(save_every_steps=0, resume=False, async_save=False), tokenizer="byte",
    )
    losses = []
    tr = Trainer(cfg, train_records=recs, val_records=recs[:8])
    real = tr.train_step

    def step(*a):
        state, metrics = real(*a)
        losses.append(float(metrics["loss"]))
        return state, metrics

    tr.train_step = step
    tr.train()
    assert len(losses) >= 2 and all(np.isfinite(losses)) and losses[-1] < losses[0], losses


def test_grouped_dot_pallas_path_equals_ragged_dot():
    """The Pallas grouped product ``grouped_dot`` takes on a TPU chip (megablox
    ``gmm``, here interpreted) against ``jax.lax.ragged_dot``, its path
    everywhere else: an uneven load with an empty expert, rows past the last
    group left alone.  And which shapes tile."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from distributed_llms_example_tpu.ops.moe import gmm_tiling

    assert gmm_tiling(512, 2048, 1792, 2) == (128, 2048, 896)  # a decode round: bound by reading the experts
    assert gmm_tiling(16384, 1792, 2048, 2) == (256, 1792, 1024)  # a prefill wave: bound by the MXU
    assert gmm_tiling(24, 64, 32, 4) is None  # a toy width: ragged_dot
    rows = jax.random.normal(jax.random.PRNGKey(0), (256, 128), jnp.float32)
    weights = jax.random.normal(jax.random.PRNGKey(1), (4, 128, 256), jnp.float32)
    load = jnp.asarray([130, 0, 25, 90], jnp.int32)  # 245 of 256 rows belong to an expert
    with jax.default_matmul_precision("highest"):
        want = jax.lax.ragged_dot(rows, weights, load)
        got = gmm(rows, weights, load, jnp.float32, (128, 128, 128), interpret=True)
    np.testing.assert_allclose(np.asarray(got)[:245], np.asarray(want)[:245], atol=1e-4, rtol=1e-4)
