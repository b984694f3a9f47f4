"""Brumby (models/brumby.py) against its plain reference
(benchmarks/reference/brumby.py, attention form only) at toy size on the CPU,
seeded weights: the full forward, prefill + cached decode through the serving
engine's slot cache with ragged prompts and reused slots, the state leaves and
their byte account, the refused serve modes, and the state's sharding."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import precision, program, spec as spec_mod, weights
from distributed_llms_example_tpu.models import registry
from distributed_llms_example_tpu.ops import retention
from distributed_llms_example_tpu.parallel.sharding import cache_leaf_name
from distributed_llms_example_tpu.serving.engine import ServeConfig, ServingEngine, UnsupportedServeMode

CFG = spec_mod.load_json(os.path.join(spec_mod.BENCH_DIR, "configs", "brumby-test.json"))
REF = spec_mod.load_module("reference", "brumby")
ADAPTER = spec_mod.load_module("adapters", "brumby")
FP32 = precision.make_dot("fp32")


def seeded(seed, dtype=jnp.float32):
    """(loaded model, program params, reference params) from one seed."""
    lm = registry.load_model("brumby-test", dtype=dtype)
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
        return np.asarray(REF.sequence_logits(ref_params, CFG, jnp.asarray(tokens, jnp.int32), 0, FP32))


def _engine(lm, slots=3, wave=2, new=16, **kw):
    # no end-of-sequence id, as the benchmark's cell runs it
    config = dataclasses.replace(lm.config, eos_token_id=None)
    serve = ServeConfig(max_slots=slots, prefill_batch=wave, max_new_tokens=new, max_source_length=24,
                        log_every_steps=0, request_spans=False, **kw)
    return ServingEngine(lm.module, config, None, serve, is_seq2seq=False)


@pytest.fixture(params=["plain-step", "kernel-step"])
def kernel_step(request, monkeypatch):
    """Both decode steps: the plain ``jnp`` step a CPU run takes, and the Pallas
    kernel (interpreted here), chosen by handing the model and the engine
    another answer from the one predicate both ask.  True on the kernel's path."""
    if request.param == "kernel-step":
        monkeypatch.setattr(retention, "step_kernel_runs", lambda d, d_v: True)
    return request.param == "kernel-step"


def test_gates_are_drawn_to_remember():
    """The toy's gate weights put g in ~0.92-0.996 (the cell's file: 0.98-0.999):
    a state that forgot in two tokens could hide a wrong slot or position."""
    lm, params, _ = seeded(2)
    x = jax.random.normal(jax.random.PRNGKey(0), (512, 64))
    g = jax.nn.sigmoid(x @ params["block_0"]["retention"]["g_proj"]["kernel"] + params["block_0"]["retention"]["g_proj"]["bias"])
    assert 0.88 < float(jnp.quantile(g, 0.02)) and float(jnp.quantile(g, 0.98)) < 0.9995 and float(g.std()) > 0.005


def test_full_forward_matches_the_reference_in_float32():
    lm, params, ref_params = seeded(3)
    ids = np.random.default_rng(0).integers(2, 250, size=(2, 40))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(lm.module.apply({"params": params}, jnp.asarray(ids, jnp.int32)))
    for row, logits in zip(ids, got):
        # float32 on both sides, attention form on both: what is left is the order of the sums
        # (read: 4e-6 to 6e-6 on logits whose spread is ~0.9)
        np.testing.assert_allclose(logits, reference_logits(ref_params, row), atol=1e-4, rtol=0)


def test_full_forward_in_bfloat16_stays_within_bfloat16_of_the_reference():
    lm, params, ref_params = seeded(4, dtype=jnp.bfloat16)
    ids = np.random.default_rng(1).integers(2, 250, size=(24,))
    got = np.asarray(lm.module.apply({"params": params}, jnp.asarray(ids[None], jnp.int32))[0], np.float32)
    want = reference_logits(ref_params, ids)
    rel = np.sqrt(np.mean(np.square(got - want), axis=-1)) / want.std(axis=-1)  # per position
    # bfloat16 keeps 8 bits, 0.4 % a rounding, and a logit is some fifteen roundings deep (two
    # layers); nothing here is discontinuous, so the worst position stays near the median
    assert np.median(rel) < 0.06 and rel.max() < 0.15, (np.median(rel), rel.max())


def test_engine_prefill_then_decode_follow_the_references_full_forward(kernel_step):
    """Ragged right-padded prompts (lengths 1 to the full width) and more
    requests than slots, 16 decode steps through the slot cache: every served
    token must be the reference's best at its position, or lie within float32
    rounding of it, with the reference teacher-forced on the served tokens in
    its ATTENTION form: logits, not tokens, are what is compared."""
    lm, params, ref_params = seeded(5)
    rng = np.random.default_rng(2)
    prompts = [list(rng.integers(2, 250, size=n)) for n in (24, 1, 7, 2, 24, 13, 3, 19)]
    budgets = [16, 6, 16, 3, 8, 16, 5, 9]
    with jax.default_matmul_precision("highest"):
        outs = _engine(lm).generate(params, prompts, max_new=budgets)
    assert [len(o) for o in outs] == budgets
    for prompt, served in zip(prompts, outs):
        logits = reference_logits(ref_params, prompt + served[:-1])[len(prompt) - 1:]
        below = logits.max(axis=-1) - logits[np.arange(len(served)), served]
        # the recurrent state against the (T, T) weights in float32: rounding alone
        assert below.max() < 1e-4, (len(prompt), below)


def test_decode_logits_through_the_cache_equal_the_full_forward():
    """The model's own two paths, logits against logits: prefill of a padded
    prompt into a cache, then 16 cached steps, against one uncached pass."""
    lm, params, _ = seeded(6)
    rng = np.random.default_rng(3)
    tokens = rng.integers(2, 250, size=(2, 36))
    lengths = np.asarray([20, 13])  # row 1's prompt is shorter than the bucket of 20
    prompt = np.where(np.arange(20)[None, :] < lengths[:, None], tokens[:, :20], 0)
    mask = (np.arange(20)[None, :] < lengths[:, None]).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        whole = [np.asarray(lm.module.apply({"params": params}, jnp.asarray(tokens[i:i + 1, :n + 16])))[0]
                 for i, n in enumerate(lengths)]
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(
            lambda: lm.module.init(jax.random.PRNGKey(0), jnp.zeros((2, 36), jnp.int32), use_cache=True))["cache"])
        full_mask = jnp.concatenate([jnp.asarray(mask), jnp.zeros((2, 16), jnp.int32)], axis=1)
        pos = jnp.clip(jnp.cumsum(jnp.asarray(mask), axis=1) - 1, 0, None)
        logits, mut = lm.module.apply({"params": params, "cache": cache}, jnp.asarray(prompt), full_mask,
                                      use_cache=True, positions=pos, mutable=["cache"])
        for i, n in enumerate(lengths):
            np.testing.assert_allclose(np.asarray(logits[i, :n]), whole[i][:n], atol=1e-4, rtol=0)
        cache = mut["cache"]
        for step in range(16):
            at = jnp.asarray(20 + step + 0 * lengths, jnp.int32)  # every row writes its mask at the cache's column
            full_mask = full_mask.at[jnp.arange(2), at].set(1)
            nxt = jnp.asarray([tokens[i, n + step] for i, n in enumerate(lengths)], jnp.int32)[:, None]
            logits, mut = lm.module.apply(
                {"params": params, "cache": cache}, nxt, full_mask, use_cache=True,
                positions=jnp.asarray(lengths + step)[:, None], cache_positions=at, mutable=["cache"])
            cache = mut["cache"]
            for i, n in enumerate(lengths):
                np.testing.assert_allclose(np.asarray(logits[i, 0]), whole[i][n + step], atol=1e-4, rtol=0)


def test_a_prompt_shorter_than_its_bucket_leaves_the_state_of_its_real_tokens_only():
    lm, params, _ = seeded(7)
    rng = np.random.default_rng(4)
    toks = rng.integers(2, 250, size=(1, 24))
    state_of = lambda name, cache: {cache_leaf_name(p): x for p, x in jax.tree_util.tree_leaves_with_path(  # noqa: E731
        cache["block_1"])}[name]

    def prefill(ids, mask):
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(
            lambda: lm.module.init(jax.random.PRNGKey(0), jnp.zeros((1, 24), jnp.int32), use_cache=True))["cache"])
        return lm.module.apply({"params": params, "cache": cache}, jnp.asarray(ids), jnp.asarray(mask), use_cache=True,
                               positions=jnp.clip(jnp.cumsum(jnp.asarray(mask), 1) - 1, 0, None), mutable=["cache"])[1]["cache"]

    padded = np.where(np.arange(24) < 9, toks, 0)
    mask = (np.arange(24) < 9).astype(np.int32)[None]
    with jax.default_matmul_precision("highest"):
        short = prefill(padded, mask)
        garbage = prefill(np.where(np.arange(24) < 9, toks, 77), mask)  # other tokens under the padding
        exact = prefill(np.concatenate([toks[:, :9], np.zeros((1, 15), np.int64)], 1)[:, :24], mask)
    for name in ("retention_state", "retention_norm"):
        np.testing.assert_array_equal(np.asarray(state_of(name, short)), np.asarray(state_of(name, garbage)))
        np.testing.assert_array_equal(np.asarray(state_of(name, short)), np.asarray(state_of(name, exact)))
        assert float(jnp.abs(state_of(name, short)).max()) > 0


def test_a_reused_slot_starts_from_a_zero_state():
    """One slot, three requests one after another: the second and third land in
    the slot the first used, whose state is full of the first's memory; each
    must decode as it does through a fresh engine."""
    lm, params, _ = seeded(8)
    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(2, 250, size=n)) for n in (20, 5, 11)]
    together = _engine(lm, slots=1, wave=1, new=8).generate(params, prompts)
    alone = [_engine(lm, slots=1, wave=1, new=8).generate(params, [p])[0] for p in prompts]
    assert together == alone


def test_a_slot_idle_beside_a_live_one_and_then_reused_serves_the_references_tokens(kernel_step):
    """Two slots.  A short request leaves slot 1 while a long one goes on in
    slot 0; slot 1 then sits idle for several rounds (not on the kernel's list:
    never streamed; on the plain step: selected back) before a third request is
    admitted into it.  The long request's state was not disturbed by its idle
    neighbour and the reused slot is clean: every served token is the float32
    reference's best at its position."""
    lm, params, ref_params = seeded(10)
    rng = np.random.default_rng(6)
    prompts = [list(rng.integers(2, 250, size=n)) for n in (17, 6, 11)]
    with jax.default_matmul_precision("highest"):
        sess = _engine(lm, slots=2, wave=1, new=16).open(params)
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
    for prompt, served in zip(prompts, sess.outputs):
        logits = reference_logits(ref_params, prompt + served[:-1])[len(prompt) - 1:]
        below = logits.max(axis=-1) - logits[np.arange(len(served)), served]
        assert below.max() < 1e-4, (len(prompt), below)


def test_an_idle_slots_state_is_left_as_it_is(kernel_step):
    """A slot that holds no request (its cache position lies outside the mask)
    keeps its state bit for bit through a decode round, whether the round's
    steps walk the live slots alone (the kernel) or every slot (the plain step)."""
    lm, params, _ = seeded(9)
    sess = _engine(lm).open(params)
    sess.submit(list(range(2, 12)), max_new=6)
    sess.step()
    before = jax.tree.map(np.asarray, sess.state["cache"])
    sess.step()
    after = jax.tree.map(np.asarray, sess.state["cache"])
    live = int(np.flatnonzero(sess.active)[0])
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(before), jax.tree.leaves(after)):
        if cache_leaf_name(path) != "cache_index":
            idle = [s for s in range(3) if s != live]
            np.testing.assert_array_equal(a[idle], b[idle])
            assert not np.array_equal(a[live], b[live])
    sess.finalize()


def test_the_cache_holds_a_retention_state_and_no_kv_and_the_summary_counts_it():
    lm, params, _ = seeded(6)
    sess = _engine(lm).open(params)
    leaves = {cache_leaf_name(p): x for p, x in jax.tree_util.tree_leaves_with_path(sess.state["cache"])}
    assert set(leaves) == {"retention_state", "retention_norm", "cache_index"}
    assert leaves["retention_state"].shape == (3, 2, 9, 16, 16) and leaves["retention_norm"].shape == (3, 2, 9, 16)
    assert leaves["retention_state"].dtype == jnp.float32
    assert sess._cache_bytes_by_kind == {
        "kv_bytes": 0, "conv_state_bytes": 0, "retention_state_bytes": 2 * 3 * 2 * 9 * (16 * 16 + 16) * 4}
    sess.finalize()


@pytest.mark.parametrize("mode", [{"paged_kv": True}, {"paged_kv": True, "prefix_cache": True}, {"spec_tokens": 2}])
def test_modes_that_cannot_hold_a_retention_state_are_refused_by_name(mode):
    lm = registry.load_model("brumby-test")
    assert lm.config.has_recurrent_state
    with pytest.raises(UnsupportedServeMode, match="recurrent state .* retention state"):
        _engine(lm, **mode)


def test_continuing_a_stored_state_with_several_tokens_is_refused():
    lm, params, _ = seeded(6)
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(
        lambda: lm.module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), use_cache=True))["cache"])
    with pytest.raises(NotImplementedError, match="starts a sequence"):
        lm.module.apply({"params": params, "cache": cache}, jnp.ones((1, 4), jnp.int32), jnp.ones((1, 8), jnp.int32),
                        use_cache=True, cache_positions=jnp.zeros((1,), jnp.int32), mutable=["cache"])


def test_a_decode_round_reports_its_live_and_streamed_slots_on_the_dispatch_span(kernel_step):
    """``slots_streamed`` says what the decode program moves: the round's live
    slots where its retention steps walk the live list (the kernel), every slot
    where the plain step runs and XLA touches them all."""
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
    for n, new in ((9, 5), (4, 3)):
        sess.submit(list(range(2, 2 + n)), max_new=new)
    while sess.has_work():
        sess.step()
    sess.finalize()
    rounds = [kw for name, kw in seen if name == "serve/decode_dispatch"]
    assert sess.eng.streams_live_slots is kernel_step and lm.config.decode_streams_live_slots is kernel_step
    assert rounds and all(1 <= kw["slots_live"] <= 2 for kw in rounds)
    assert all(kw["slots_streamed"] == (kw["slots_live"] if kernel_step else 3) for kw in rounds)
    assert rounds[0]["slots_live"] == 2 and {kw["slots_live"] for kw in rounds} == {1, 2}


def test_state_shards_by_kv_heads_on_tensor_and_the_decode_step_needs_no_collective():
    """``cache_leaf_spec`` puts the slots over the batch axes and the KV heads
    over ``tensor``; the compiled decode step (tensor=2 on two virtual devices)
    then updates and reads each head's state where it lies: no collective
    touches a state leaf (the projections' own all-reduces are not the state's)."""
    from jax.sharding import PartitionSpec as P

    from distributed_llms_example_tpu.core.config import MeshConfig
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.parallel.sharding import cache_leaf_spec, shard_params

    axes = {"data": 2, "tensor": 2}
    assert cache_leaf_spec("retention_state", (4, 2, 9, 16, 16), axes, 2) == P(("data", "fsdp", "expert"), "tensor", None, None, None)
    assert cache_leaf_spec("retention_norm", (4, 2, 9, 16), axes, 2) == P(("data", "fsdp", "expert"), "tensor", None, None)
    assert cache_leaf_spec("retention_state", (4, 3, 9, 16, 16), axes, 3)[1] is None  # 3 heads do not split in two
    assert cache_leaf_spec("retention_state", (4, 2, 9, 16, 16), axes, 2, pool=True) is None  # a pool pages no state

    lm, params, _ = seeded(6)
    mesh = build_mesh(MeshConfig(data=1, tensor=2), devices=jax.devices()[:2])
    config = dataclasses.replace(lm.config, eos_token_id=None)
    serve = ServeConfig(max_slots=2, prefill_batch=1, max_new_tokens=8, max_source_length=24,
                        log_every_steps=0, request_spans=False)
    eng = ServingEngine(lm.module, config, mesh, serve, is_seq2seq=False)
    sess = eng.open(shard_params(params, mesh))
    state = {cache_leaf_name(p): x for p, x in jax.tree_util.tree_leaves_with_path(sess.state["cache"])}["retention_state"]
    assert state.sharding.spec[1] == "tensor" and state.addressable_shards[0].data.shape == (2, 1, 9, 16, 16)
    pos = jnp.zeros((2,), jnp.int32)
    text = eng._step.lower(sess.params, sess.state, pos, pos, jnp.ones((2,), bool)).compile().as_text()
    collectives = [ln for ln in text.splitlines()
                   if any(op in ln for op in (" all-gather(", " all-to-all(", " collective-permute(", " all-reduce("))]
    # what a tensor-parallel step pays anyway: the embedding's and the row-parallel o_proj's and
    # down_proj's all-reduces, the argmax over a sharded vocabulary.  Nothing between a layer's
    # q/k/v/gate and its o_proj: the state is updated and read where it lies
    inside = [ln for ln in collectives if "/retention/" in ln and "/retention/o_proj/" not in ln]
    assert len(collectives) >= 4 and not inside, inside
    assert not any("9,16,16" in ln.split(" metadata=")[0] for ln in collectives), collectives
    sess.finalize()
