"""Falcon-H1 (models/falcon_h1.py) against its plain reference
(benchmarks/reference/falcon_h1.py: the token-by-token recurrence and a full
softmax) at toy size on the CPU, seeded weights, every multiplier different
from 1: the full forward, prefill + cached decode through the serving engine's
slot cache with ragged prompts and reused slots, the three kinds of cache leaf
and their byte account, the refused serve modes, and the state's sharding.

One seed, one engine a decode step (two slots, waves of one or two rows) and
jitted calls padded to one length: the file's compiles are few, so that it
stays well under a minute of the suite's time."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import precision, program, spec as spec_mod, weights
from distributed_llms_example_tpu.models import registry
from distributed_llms_example_tpu.ops import ssm
from distributed_llms_example_tpu.parallel.sharding import cache_leaf_name
from distributed_llms_example_tpu.serving.engine import ServeConfig, ServingEngine, UnsupportedServeMode

CFG = spec_mod.load_json(os.path.join(spec_mod.BENCH_DIR, "configs", "falcon-h1-test.json"))
REF = spec_mod.load_module("reference", "falcon_h1")
ADAPTER = spec_mod.load_module("adapters", "falcon_h1")
FP32 = precision.make_dot("fp32")
STATE_LEAVES = ("ssm_state", "conv_state")
WIDTH, NEW = 24, 16  # the engine's prompt bucket and decode tail; a sequence is at most 40 tokens
SLOTS = 2


@pytest.fixture(autouse=True)
def float32_products():
    with jax.default_matmul_precision("highest"):
        yield


@functools.lru_cache(maxsize=None)
def seeded(dtype=jnp.float32):
    """(loaded model, program params, reference params) from one seed."""
    lm = registry.load_model("falcon-h1-test", dtype=dtype)
    for k, want in ADAPTER.program_config_checks(CFG).items():
        assert getattr(lm.config, k) == want, k
    spec = REF.param_spec(CFG)
    params = weights.make_program_weights(spec, 5, program.to_program_tree(ADAPTER.leaf_map(CFG)))
    init = jax.eval_shape(lambda: lm.module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    assert jax.tree.map(lambda x: x.shape, params) == jax.tree.map(lambda x: x.shape, init)  # every leaf mapped
    return lm, params, weights.make_reference_weights(spec, 5)


@functools.lru_cache(maxsize=None)
def _reference():
    return jax.jit(lambda p, tokens: REF.sequence_logits(p, CFG, tokens, 0, FP32))


def reference_logits(ref_params, tokens):
    """Float32 reference logits of every position of ``tokens`` (T,): one program for every length (the
    model is causal, so zeros after the sequence change nothing before them)."""
    tokens = list(map(int, tokens))
    padded = jnp.asarray(tokens + [0] * (WIDTH + NEW - len(tokens)), jnp.int32)
    return np.asarray(_reference()(ref_params, padded))[: len(tokens)]


@functools.lru_cache(maxsize=None)
def _forward(dtype=jnp.float32):
    lm = seeded(dtype)[0]
    return jax.jit(lambda p, ids: lm.module.apply({"params": p}, ids))


def program_logits(params, tokens, dtype=jnp.float32):
    """The program's uncached logits of ``tokens`` (T,), padded to the one length as above."""
    tokens = list(map(int, tokens))
    padded = jnp.asarray([tokens + [0] * (WIDTH + NEW - len(tokens))], jnp.int32)
    return np.asarray(_forward(dtype)(params, padded)[0], np.float32)[: len(tokens)]


_ENGINES = {}


def _engine(kernel=False, mesh=None, **kw):
    """The file's engine: two slots, waves of one or two rows, no end-of-sequence id (as the benchmark's
    cell runs it).  One a decode step (its programs are traced once, under the step's predicate)."""
    key = (kernel, mesh is not None, tuple(sorted(kw.items())))
    if key not in _ENGINES:
        lm = seeded()[0]
        config = dataclasses.replace(lm.config, eos_token_id=None)
        serve = ServeConfig(max_slots=SLOTS, prefill_batch=2, max_new_tokens=NEW, max_source_length=WIDTH,
                            log_every_steps=0, request_spans=False, **kw)
        _ENGINES[key] = ServingEngine(lm.module, config, mesh, serve, is_seq2seq=False)
    return _ENGINES[key]


def _empty_cache(lm, rows, width):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(
        lambda: lm.module.init(jax.random.PRNGKey(0), jnp.zeros((rows, width), jnp.int32), use_cache=True))["cache"])


def _leaves(cache):
    out = {}
    for p, x in jax.tree_util.tree_leaves_with_path(cache):
        out.setdefault(cache_leaf_name(p), []).append(x)
    return out


@pytest.fixture(params=["plain-step", "kernel-step"])
def kernel_step(request, monkeypatch):
    """Both decode steps: the plain ``jnp`` step a CPU run takes, and the Pallas
    kernel (interpreted here), chosen by handing the model and the engine
    another answer from the one predicate both ask.  True on the kernel's path."""
    if request.param == "kernel-step":
        monkeypatch.setattr(ssm, "step_kernel_runs", lambda p, n: True)
    return request.param == "kernel-step"


def test_every_multiplier_differs_from_one_and_the_decays_remember():
    """The toy keeps the published multipliers (attention_in_multiplier, published
    as 1, is 0.8 here), so a multiplier left out or misplaced shows in every
    comparison below; its decays lie in ~0.84-0.997 (the cell's file): a state
    that forgot in two tokens could hide a wrong slot or position."""
    lm, params, _ = seeded()
    c = lm.config
    ms = [c.embedding_multiplier, c.lm_head_multiplier, c.attention_in_multiplier, c.attention_out_multiplier,
          c.key_multiplier, c.ssm_in_multiplier, c.ssm_out_multiplier, *c.ssm_multipliers, *c.mlp_multipliers]
    assert len(ms) == 14 and all(abs(m - 1.0) > 0.1 for m in ms)
    mixer = params["block_0"]["mixer"]
    u = jax.random.normal(jax.random.PRNGKey(0), (512, 64)) * c.ssm_in_multiplier
    dt = jax.nn.softplus((u @ mixer["in_proj"]["kernel"])[:, -4:] * c.ssm_multipliers[4] + mixer["dt_bias"])
    decay = jnp.exp(-dt * jnp.exp(mixer["A_log"]))
    assert 0.75 < float(jnp.quantile(decay, 0.02)) and float(jnp.quantile(decay, 0.98)) < 0.9995
    assert 0.9 < float(jnp.median(decay)) < 0.995 and float(decay.std()) > 0.005


def test_full_forward_matches_the_reference_in_float32():
    _, params, ref_params = seeded()
    for row in np.random.default_rng(0).integers(2, 250, size=(2, 37)):
        want = reference_logits(ref_params, row)
        assert want.std() > 0.3  # logits of standard deviation ~1: the seeded head undoes lm_head_multiplier
        # float32 on both sides; the chunked form (chunks of 16 over 40 positions: not a multiple) against
        # the recurrence: what is left is the order of the sums (read: 3e-6 on logits whose spread is ~1)
        np.testing.assert_allclose(program_logits(params, row), want, atol=1e-4, rtol=0)


def test_full_forward_in_bfloat16_stays_within_bfloat16_of_the_reference():
    _, params, ref_params = seeded()
    ids = np.random.default_rng(1).integers(2, 250, size=(24,))
    got, want = program_logits(params, ids, jnp.bfloat16), reference_logits(ref_params, ids)
    rel = np.sqrt(np.mean(np.square(got - want), axis=-1)) / want.std(axis=-1)  # per position
    # bfloat16 keeps 8 bits, 0.4 % a rounding, and a logit is some twenty roundings deep (two
    # layers of three branches); nothing here is discontinuous, so the worst position stays near the median
    assert np.median(rel) < 0.06 and rel.max() < 0.15, (np.median(rel), rel.max())


def _served_tokens_are_the_references_best(ref_params, prompts, outs):
    for prompt, served in zip(prompts, outs):
        logits = reference_logits(ref_params, list(prompt) + list(served[:-1]))[len(prompt) - 1:]
        below = logits.max(axis=-1) - logits[np.arange(len(served)), served]
        # K/V, state and taps through the slot cache against the whole sequence at once in float32: rounding alone
        assert below.max() < 1e-4, (len(prompt), below)


def test_engine_prefill_then_decode_follow_the_references_full_forward(kernel_step):
    """Ragged right-padded prompts (lengths 1 to the full width) and more
    requests than slots, 16 decode steps through the slot cache: every served
    token must be the reference's best at its position, or lie within float32
    rounding of it, with the reference teacher-forced on the served tokens in
    its token-by-token form: logits, not tokens, are what is compared."""
    _, params, ref_params = seeded()
    rng = np.random.default_rng(2)
    prompts = [list(rng.integers(2, 250, size=n)) for n in (24, 1, 7, 2, 24, 13, 3, 19)]
    budgets = [16, 6, 16, 3, 8, 16, 5, 9]
    outs = _engine(kernel_step).generate(params, prompts, max_new=budgets)
    assert [len(o) for o in outs] == budgets
    _served_tokens_are_the_references_best(ref_params, prompts, outs)


def test_decode_logits_through_the_cache_equal_the_full_forward():
    """The model's own two paths, logits against logits: prefill of a padded
    prompt into a cache, then 16 cached steps, against one uncached pass."""
    lm, params, _ = seeded()
    tokens = np.random.default_rng(3).integers(2, 250, size=(2, 36))
    lengths = np.asarray([20, 13])  # row 1's prompt is shorter than the bucket of 20
    prompt = np.where(np.arange(20)[None, :] < lengths[:, None], tokens[:, :20], 0)
    mask = (np.arange(20)[None, :] < lengths[:, None]).astype(np.int32)
    whole = [program_logits(params, tokens[i, :n + 16]) for i, n in enumerate(lengths)]
    full_mask = jnp.concatenate([jnp.asarray(mask), jnp.zeros((2, 16), jnp.int32)], axis=1)
    pos = jnp.clip(jnp.cumsum(jnp.asarray(mask), axis=1) - 1, 0, None)
    logits, mut = jax.jit(lambda p, c, ids, m, at: lm.module.apply(
        {"params": p, "cache": c}, ids, m, use_cache=True, positions=at, mutable=["cache"]))(
            params, _empty_cache(lm, 2, 36), jnp.asarray(prompt), full_mask, pos)
    for i, n in enumerate(lengths):
        np.testing.assert_allclose(np.asarray(logits[i, :n]), whole[i][:n], atol=1e-4, rtol=0)
    step = jax.jit(lambda p, c, ids, m, rope, at: lm.module.apply(
        {"params": p, "cache": c}, ids, m, use_cache=True, positions=rope, cache_positions=at, mutable=["cache"]))
    cache = mut["cache"]
    for k in range(16):
        at = jnp.asarray(20 + k + 0 * lengths, jnp.int32)  # every row writes its mask at the cache's column
        full_mask = full_mask.at[jnp.arange(2), at].set(1)
        nxt = jnp.asarray([tokens[i, n + k] for i, n in enumerate(lengths)], jnp.int32)[:, None]
        logits, mut = step(params, cache, nxt, full_mask, jnp.asarray(lengths + k)[:, None], at)
        cache = mut["cache"]
        for i, n in enumerate(lengths):
            np.testing.assert_allclose(np.asarray(logits[i, 0]), whole[i][n + k], atol=1e-4, rtol=0)


def test_a_prompt_shorter_than_its_bucket_leaves_the_state_and_taps_of_its_real_tokens_only():
    lm, params, _ = seeded()
    toks = np.random.default_rng(4).integers(2, 250, size=(1, 24))
    prefill = jax.jit(lambda ids, mask: lm.module.apply(
        {"params": params, "cache": _empty_cache(lm, 1, ids.shape[1])}, ids, mask, use_cache=True,
        positions=jnp.clip(jnp.cumsum(mask, 1) - 1, 0, None), mutable=["cache"])[1]["cache"]["block_1"])
    mask = jnp.asarray((np.arange(24) < 9).astype(np.int32)[None])
    short = _leaves(prefill(jnp.asarray(np.where(np.arange(24) < 9, toks, 0)), mask))
    garbage = _leaves(prefill(jnp.asarray(np.where(np.arange(24) < 9, toks, 77)), mask))  # other tokens under the padding
    exact = _leaves(prefill(jnp.asarray(toks[:, :9]), jnp.ones((1, 9), jnp.int32)))  # the nine tokens alone
    for name in STATE_LEAVES:
        np.testing.assert_array_equal(np.asarray(short[name][0]), np.asarray(garbage[name][0]))
        # one chunk of 16 either way: float32 rounding (read: 1.4e-6 on entries of ~1)
        np.testing.assert_allclose(np.asarray(short[name][0]), np.asarray(exact[name][0]), atol=1e-5, rtol=0)
        assert float(jnp.abs(short[name][0]).max()) > 0


def test_a_reused_slot_starts_from_a_zero_state_and_fresh_taps():
    """Five requests on two slots: the later ones land in slots an earlier one
    used, whose state and taps are full of its memory (and whose K/V hides
    behind the positions); each must decode as it does alone in a fresh session."""
    _, params, _ = seeded()
    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(2, 250, size=n)) for n in (20, 5, 2, 24, 1)]  # two hold fewer tokens than the taps
    eng = _engine()
    together = eng.generate(params, prompts, max_new=[8] * 5)
    assert together == [eng.generate(params, [p], max_new=[8])[0] for p in prompts]


def test_a_slot_idle_beside_a_live_one_and_then_reused_serves_the_references_tokens(kernel_step):
    """Two slots.  A short request leaves slot 1 while a long one goes on in
    slot 0; slot 1 then sits idle for several rounds (not on the kernel's list:
    never streamed; on the plain step: selected back) before a third request is
    admitted into it.  The long request's memory was not disturbed by its idle
    neighbour and the reused slot is clean: every served token is the float32
    reference's best at its position."""
    _, params, ref_params = seeded()
    rng = np.random.default_rng(6)
    prompts = [list(rng.integers(2, 250, size=n)) for n in (17, 6, 11)]
    sess = _engine(kernel_step).open(params)
    long_, short = sess.submit(prompts[0], max_new=16), sess.submit(prompts[1], max_new=3)
    idle_rounds = 0
    while len(sess.outputs[long_]) < 9:
        sess.step()
        idle_rounds += int(sess.active.sum() == 1)
    assert idle_rounds >= 4 and len(sess.outputs[short]) == 3
    third = sess.submit(prompts[2], max_new=6)
    while sess.has_work():
        sess.step()
    sess.finalize()
    assert [len(sess.outputs[r]) for r in (long_, short, third)] == [16, 3, 6]
    _served_tokens_are_the_references_best(ref_params, prompts, sess.outputs)


def test_an_idle_slots_state_and_taps_are_left_as_they_are(kernel_step):
    """A slot that holds no request (its cache position lies outside the mask)
    keeps its state and taps bit for bit through a decode round, whether the
    round's steps walk the live slots alone (the kernel) or every slot."""
    _, params, _ = seeded()
    sess = _engine(kernel_step).open(params)
    sess.submit(list(range(2, 12)), max_new=6)
    sess.step()
    before = jax.tree.map(np.asarray, sess.state["cache"])
    sess.step()
    after = jax.tree.map(np.asarray, sess.state["cache"])
    live = int(np.flatnonzero(sess.active)[0])
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(before), jax.tree.leaves(after)):
        if cache_leaf_name(path) in STATE_LEAVES:
            np.testing.assert_array_equal(a[1 - live], b[1 - live])
            assert not np.array_equal(a[live], b[live])
    sess.finalize()


def test_a_layer_holds_three_kinds_of_leaf_and_the_summary_counts_each(capsys):
    _, params, _ = seeded()
    sess = _engine().open(params)
    layer = _leaves(sess.state["cache"]["block_0"])
    assert set(layer) == {"cached_key", "cached_value", "ssm_state", "conv_state", "cache_index"}
    assert layer["cached_key"][0].shape == (2, 40, 2 * 16) and layer["conv_state"][0].shape == (2, 64 + 2 * 2 * 32, 3)
    assert layer["ssm_state"][0].shape == (2, 4, 32, 16) and layer["ssm_state"][0].dtype == jnp.float32
    assert sess._cache_bytes_by_kind == {
        "kv_bytes": 2 * 2 * 2 * 40 * 32 * 4, "conv_state_bytes": 2 * 2 * 192 * 3 * 4, "ssm_state_bytes": 2 * 2 * 4 * 32 * 16 * 4}
    capsys.readouterr()
    sess.finalize()
    summary = next(e for e in map(json.loads, filter(lambda ln: ln.startswith("{"), capsys.readouterr().out.splitlines()))
                   if e.get("event") == "serve_summary")
    assert {k: summary[k] for k in sess._cache_bytes_by_kind} == sess._cache_bytes_by_kind


@pytest.mark.parametrize("mode", [{"paged_kv": True}, {"paged_kv": True, "prefix_cache": True}, {"spec_tokens": 2}])
def test_modes_that_cannot_hold_a_state_beside_kv_are_refused_by_name(mode):
    assert seeded()[0].config.has_recurrent_state
    with pytest.raises(UnsupportedServeMode, match="recurrent state .*state-space state beside K/V"):
        _engine(**mode)


def test_continuing_a_stored_state_with_several_tokens_is_refused():
    lm, params, _ = seeded()
    with pytest.raises(NotImplementedError, match="starts a sequence"):
        jax.eval_shape(lambda: lm.module.apply(
            {"params": params, "cache": _empty_cache(lm, 1, 8)}, jnp.ones((1, 4), jnp.int32), jnp.ones((1, 8), jnp.int32),
            use_cache=True, cache_positions=jnp.zeros((1,), jnp.int32), mutable=["cache"]))


def test_a_decode_round_reports_both_families_of_counters_on_the_dispatch_span(kernel_step):
    """One model, both counter families on one ``serve/decode_dispatch`` span:
    ``slots_live`` / ``slots_streamed`` for the state (streamed = live where the
    kernel walks its list, every slot on the plain step) and ``kv_positions_live``
    / ``kv_positions_streamed`` for the K/V (two attention layers here)."""
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

    lm, params, _ = seeded()
    sess = _engine(kernel_step).open(params, spans=SpanRecorder(scope="serve", annotate=Annotation))
    sess.submit(list(range(2, 12)), max_new=8)
    sess.submit(list(range(2, 7)), max_new=3)
    while sess.has_work():
        sess.step()
    sess.finalize()
    assert sess.eng.streams_live_slots is kernel_step
    rounds = [kw for name, kw in seen if name == "serve/decode_dispatch"]
    assert rounds and all({"slots_live", "slots_streamed", "kv_positions_live", "kv_positions_streamed"} <= set(r) for r in rounds)
    for r in rounds:
        assert r["slots_streamed"] == (r["slots_live"] if kernel_step else SLOTS)
        assert r["kv_positions_streamed"] == SLOTS * 2 * 40 and 0 < r["kv_positions_live"] <= r["slots_live"] * 2 * 40
    assert {r["slots_live"] for r in rounds} == {1, 2}


def test_state_shards_by_heads_on_tensor_and_no_collective_touches_it():
    """``cache_leaf_spec`` puts the slots over the batch axes and the mixer's
    heads over ``tensor``; the compiled decode step (tensor=2 on two virtual
    devices) then updates and reads each head's state where it lies: no
    collective carries a state leaf (the projections' own all-reduces, and the
    small resharding of x | B | C after the convolution, whose channels split by
    position and not by group, are not the state's)."""
    from jax.sharding import PartitionSpec as P

    from distributed_llms_example_tpu.core.config import MeshConfig
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.parallel.sharding import cache_leaf_spec, shard_params

    axes = {"data": 2, "tensor": 2}
    assert cache_leaf_spec("ssm_state", (4, 4, 32, 16), axes, 2) == P(("data", "fsdp", "expert"), "tensor", None, None)
    assert cache_leaf_spec("ssm_state", (4, 3, 32, 16), axes, 2)[1] is None  # 3 heads do not split in two
    assert cache_leaf_spec("ssm_state", (4, 4, 32, 16), axes, 2, pool=True) is None  # a pool pages no state
    assert cache_leaf_spec("conv_state", (4, 192, 3), axes, 2) == P(("data", "fsdp", "expert"), "tensor", None)
    assert cache_leaf_spec("cached_key", (4, 40, 32), axes, 2) == P(("data", "fsdp", "expert"), None, "tensor")

    _, params, _ = seeded()
    mesh = build_mesh(MeshConfig(data=1, tensor=2), devices=jax.devices()[:2])
    eng = _engine(mesh=mesh)
    sess = eng.open(shard_params(params, mesh))
    state = _leaves(sess.state["cache"])["ssm_state"][0]
    assert state.sharding.spec[1] == "tensor" and state.addressable_shards[0].data.shape == (2, 2, 32, 16)
    pos = jnp.zeros((2,), jnp.int32)
    text = eng._step.lower(sess.params, sess.state, pos, pos, jnp.ones((2,), bool)).compile().as_text()
    collectives = [ln for ln in text.splitlines()
                   if any(op in ln for op in (" all-gather(", " all-to-all(", " collective-permute(", " all-reduce("))]
    assert len(collectives) >= 4
    assert not any("32,16]" in ln.split(" metadata=")[0] for ln in collectives), collectives
    sess.finalize()
