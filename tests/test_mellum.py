"""Mellum (models/mellum.py) against its plain reference
(benchmarks/reference/mellum.py) at toy size on the CPU, seeded weights: the
full forward, prefill + cached decode through a slot cache whose window layers
hold a ring of the last ``sliding_window`` positions, the window flavour of the
flash forward kernel and the ring decode step (interpreted) with the window's
edge pinned, YaRN's frequencies, the softmax top-k router, the serving engine
against the static greedy loop, the byte account by kind of leaf, the refused
serve modes, the round's K/V counters and the leaves' sharding."""

import dataclasses
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import precision, program, spec as spec_mod, weights
from distributed_llms_example_tpu.evaluation.generation import make_causal_greedy
from distributed_llms_example_tpu.models import registry
from distributed_llms_example_tpu.ops import mha
from distributed_llms_example_tpu.ops.attention import NEG_INF, dot_product_attention, make_band_bias
from distributed_llms_example_tpu.ops.flash_attention import flash_decode, flash_prompt_attention
from distributed_llms_example_tpu.parallel.sharding import cache_leaf_name
from distributed_llms_example_tpu.serving.engine import ServeConfig, ServingEngine, UnsupportedServeMode

CFG = spec_mod.load_json(os.path.join(spec_mod.BENCH_DIR, "configs", "mellum-test.json"))
PUBLISHED = spec_mod.load_json(os.path.join(spec_mod.BENCH_DIR, "configs", "mellum2-12b-a2.5b.json"))
REF = spec_mod.load_module("reference", "mellum")
ADAPTER = spec_mod.load_module("adapters", "mellum")
FP32 = precision.make_dot("fp32")
W = CFG["sliding_window"]  # 16


def seeded(seed, dtype=jnp.float32, **config):
    """(loaded model, program params, reference params) from one seed."""
    lm = registry.load_model("mellum-test", dtype=dtype)
    for k, want in ADAPTER.program_config_checks(CFG).items():
        assert getattr(lm.config, k) == want, k
    assert dataclasses.replace(lm.config, **{
        k: v for k, v in ADAPTER.program_config_overrides(CFG).items() if k in ("layer_types", "rope_yarn", "vocab_size")
    }) == lm.config  # the toy's file and the registry's toy are one model
    if config:
        lm = dataclasses.replace(lm, config=dataclasses.replace(lm.config, **config),
                                 module=lm.module.clone(config=dataclasses.replace(lm.config, **config)))
    spec = REF.param_spec(CFG)
    params = weights.make_program_weights(spec, seed, program.to_program_tree(ADAPTER.leaf_map(CFG)))
    init = lm.init_params(0)
    assert jax.tree.map(lambda x: x.shape, params) == jax.tree.map(lambda x: x.shape, init)  # every leaf mapped
    return lm, params, weights.make_reference_weights(spec, seed)


def reference_logits(ref_params, tokens, cfg=CFG):
    """Float32 reference logits of every position of ``tokens`` (T,)."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(REF.sequence_logits(ref_params, cfg, jnp.asarray(tokens, jnp.int32), 0, FP32)[0])


def _engine(lm, slots=3, wave=2, new=24, source=48, mesh=None, **kw):
    # no end-of-sequence id, as the benchmark's cell runs it
    config = dataclasses.replace(lm.config, eos_token_id=None)
    serve = ServeConfig(max_slots=slots, prefill_batch=wave, max_new_tokens=new, max_source_length=source,
                        log_every_steps=0, request_spans=False, **kw)
    return ServingEngine(lm.module, config, mesh, serve, is_seq2seq=False)


def _fresh_cache(lm, rows, width):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(
        lambda: lm.module.init(jax.random.PRNGKey(0), jnp.zeros((rows, width), jnp.int32), use_cache=True))["cache"])


# ------------------------------------------------------------------ the model


def test_full_forward_matches_the_reference_in_float32():
    lm, params, ref_params = seeded(3)
    ids = np.random.default_rng(0).integers(2, 250, size=(2, 56))  # 3.5 windows
    with jax.default_matmul_precision("highest"):
        got = np.asarray(lm.module.apply({"params": params}, jnp.asarray(ids, jnp.int32)))
    for row, logits in zip(ids, got):
        # float32 on both sides: what is left is the order of the sums (logits spread ~1)
        np.testing.assert_allclose(logits, reference_logits(ref_params, row), atol=2e-4, rtol=0)


PLAIN = CFG["rope_parameters"]["sliding_attention"]
ALTERED = {
    "window layers made full": {**CFG, "sliding_window": 1 << 30},
    "plain rotation on the full layer": {**CFG, "rope_parameters": {"full_attention": PLAIN, "sliding_attention": PLAIN}},
    "window one longer": {**CFG, "sliding_window": W + 1},
}


@pytest.mark.parametrize("altered", sorted(ALTERED))
def test_the_reference_sees_the_window_and_the_rotation(altered):
    """`correct` can see the mechanism: a reference whose window layers read
    everything, whose full layer rotates plainly, or whose window is one
    position longer, gives other logits than the model's (far past rounding)."""
    lm, params, ref_params = seeded(3)
    ids = np.random.default_rng(0).integers(2, 250, size=(56,))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(lm.module.apply({"params": params}, jnp.asarray(ids[None], jnp.int32)))[0]
    want = reference_logits(ref_params, ids, ALTERED[altered])
    if "window" in altered:  # inside the first window the two masks are one
        np.testing.assert_allclose(got[:W], want[:W], atol=2e-4)
    assert np.abs(got - want).max() > 0.01


def test_full_forward_in_bfloat16_stays_within_bfloat16_of_the_reference():
    lm, params, ref_params = seeded(4, dtype=jnp.bfloat16)
    ids = np.random.default_rng(1).integers(2, 250, size=(40,))
    got = np.asarray(lm.module.apply({"params": params}, jnp.asarray(ids[None], jnp.int32))[0], np.float32)
    want = reference_logits(ref_params, ids)
    rel = np.sqrt(np.mean(np.square(got - want), axis=-1)) / want.std(axis=-1)  # per position
    # routing is discontinuous (a near-tie routes otherwise in bfloat16), so the worst position
    # may sit an expert's share away; the median stays at bfloat16's rounding
    assert np.median(rel) < 0.08 and rel.max() < 0.6, (np.median(rel), rel.max())


def test_decode_logits_through_the_cache_equal_the_full_forward():
    """The model's own two paths, logits against logits: prefill of a prompt of
    three windows (row 1 padded: 37 real tokens) into a cache whose window
    leaves hold 16 positions, then 24 cached steps that wrap the ring again,
    against one uncached pass."""
    lm, params, _ = seeded(6)
    rng = np.random.default_rng(3)
    p, steps = 3 * W, 24
    tokens = rng.integers(2, 250, size=(2, p + steps))
    lengths = np.asarray([p, 37])
    prompt = np.where(np.arange(p)[None, :] < lengths[:, None], tokens[:, :p], 0)
    mask = (np.arange(p)[None, :] < lengths[:, None]).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        whole = [np.asarray(lm.module.apply({"params": params}, jnp.asarray(tokens[i:i + 1, :n + steps])))[0]
                 for i, n in enumerate(lengths)]
        cache = _fresh_cache(lm, 2, p + steps)
        shapes = {cache_leaf_name(path): x.shape for path, x in jax.tree_util.tree_leaves_with_path(cache)}
        assert shapes["window_key"] == (2, W, 2 * 16) and shapes["cached_key"] == (2, p + steps, 2 * 16)
        full_mask = jnp.concatenate([jnp.asarray(mask), jnp.zeros((2, steps), jnp.int32)], axis=1)
        pos = jnp.clip(jnp.cumsum(jnp.asarray(mask), axis=1) - 1, 0, None)
        logits, mut = lm.module.apply({"params": params, "cache": cache}, jnp.asarray(prompt), full_mask,
                                      use_cache=True, positions=pos, mutable=["cache"])
        for i, n in enumerate(lengths):
            np.testing.assert_allclose(np.asarray(logits[i, :n]), whole[i][:n], atol=2e-4, rtol=0)
        cache = mut["cache"]
        for step in range(steps):
            at = jnp.asarray(p + step + 0 * lengths, jnp.int32)  # every row writes its mask at the cache's column
            full_mask = full_mask.at[jnp.arange(2), at].set(1)
            nxt = jnp.asarray([tokens[i, n + step] for i, n in enumerate(lengths)], jnp.int32)[:, None]
            logits, mut = lm.module.apply(
                {"params": params, "cache": cache}, nxt, full_mask, use_cache=True,
                positions=jnp.asarray(lengths + step)[:, None], cache_positions=at, mutable=["cache"])
            cache = mut["cache"]
            for i, n in enumerate(lengths):
                np.testing.assert_allclose(np.asarray(logits[i, 0]), whole[i][n + step], atol=2e-4, rtol=0)


def test_a_prompts_ring_holds_its_last_real_positions_whatever_the_padding():
    """Admission's window leaf: entry r is the key of the newest real position
    congruent to r; what lies under the padding never gets in."""
    lm, params, _ = seeded(7)
    toks = np.random.default_rng(4).integers(2, 250, size=(1, 48))
    ring = lambda cache: np.asarray({cache_leaf_name(p): x for p, x in jax.tree_util.tree_leaves_with_path(  # noqa: E731
        cache["block_0"])}["window_key"])[0]

    def prefill(ids, mask):
        return lm.module.apply(
            {"params": params, "cache": _fresh_cache(lm, 1, 48)}, jnp.asarray(ids), jnp.asarray(mask), use_cache=True,
            positions=jnp.clip(jnp.cumsum(jnp.asarray(mask), 1) - 1, 0, None), mutable=["cache"])[1]["cache"]

    n = 37  # real tokens: positions 21..36 are its last 16; entry r holds position 32 + r for r < 5, 16 + r above
    mask = (np.arange(48) < n).astype(np.int32)[None]
    with jax.default_matmul_precision("highest"):
        padded = ring(prefill(np.where(np.arange(48) < n, toks, 0), mask))
        garbage = ring(prefill(np.where(np.arange(48) < n, toks, 77), mask))  # other tokens under the padding
        whole = ring(prefill(toks, np.ones((1, 48), np.int32)))  # the same 37 tokens and 11 more: positions 32..47
    np.testing.assert_array_equal(padded, garbage)
    # layer 0's key is a function of its token and position alone: entries 0..4 hold positions 32..36 in both
    np.testing.assert_allclose(padded[:5], whole[:5], atol=1e-6)
    assert not np.allclose(padded[5:], whole[5:], atol=1e-3)  # positions 21..31 against 37..47


def test_continuing_a_ring_with_several_tokens_is_refused():
    lm, params, _ = seeded(6)
    with pytest.raises(NotImplementedError, match="starts a sequence"):
        lm.module.apply({"params": params, "cache": _fresh_cache(lm, 1, 24)}, jnp.ones((1, 4), jnp.int32),
                        jnp.ones((1, 24), jnp.int32), use_cache=True, cache_positions=jnp.zeros((1,), jnp.int32),
                        mutable=["cache"])


# ---------------------------------------------------------------- the kernels


def _qkv(t, heads=2, d=32, batch=2, seed=0):
    return tuple(jax.random.normal(k, (batch, heads, t, d)) for k in jax.random.split(jax.random.PRNGKey(seed), 3))


@pytest.mark.parametrize("window,block_q,block_k", [(64, 32, 32), (64, 64, 32), (100, 32, 64), (1, 32, 32),
                                                    (300, 64, 64), (64, 128, 128), (256, 64, 64)])
def test_window_flash_forward_equals_the_band_masked_attention(window, block_q, block_k):
    q, k, v = _qkv(256)
    pad = jnp.where(jnp.arange(256)[None, None, None, :] < jnp.asarray([256, 200])[:, None, None, None], 0.0, NEG_INF)
    got = flash_prompt_attention(q, k, v, pad, window=window, block_q=block_q, block_k=block_k, interpret=True)
    want = dot_product_attention(q, k, v, make_band_bias(256, 256, window) + pad)
    np.testing.assert_allclose(np.asarray(got)[0], np.asarray(want)[0], atol=2e-6, rtol=0)
    # row 1's queries past its 200 real tokens are padding themselves: what they read is nobody's
    np.testing.assert_allclose(np.asarray(got)[1, :, :200], np.asarray(want)[1, :, :200], atol=2e-6, rtol=0)


def test_the_window_counts_the_query_and_ends_at_its_edge():
    """Keys that score alike and values that say where they sit: query i's
    output is 1 / min(i + 1, W) on positions i - (W - 1) .. i and exactly 0 at
    i - W, on the forward kernel and on the ring step alike."""
    t, window, d = 128, 48, 128
    q = jnp.zeros((1, 1, t, d))
    v = jnp.eye(t, d)[None, None]  # value j is the unit vector j
    out = np.asarray(flash_prompt_attention(q, q, v, window=window, block_q=32, block_k=32, interpret=True))[0, 0]
    for i in (0, 5, window - 1, window, 100, t - 1):
        seen = np.flatnonzero(out[i])
        assert seen.min() == max(i - (window - 1), 0) and seen.max() == i, (i, seen)
        np.testing.assert_allclose(out[i, seen], 1.0 / min(i + 1, window), rtol=1e-6)
    # the ring: position p rests at entry p mod W; a step at position i reads min(i + 1, W) entries
    for i in (5, window - 1, window, 100):
        at = np.arange(max(i - (window - 1), 0), i + 1)
        ring = jnp.zeros((1, window, d)).at[0, at % window].set(jnp.eye(t, d)[at])
        stale = ring.at[0, (i + 1) % window].add(0.0 if i >= window - 1 else 7.0)  # an entry not yet written holds garbage
        got = np.asarray(flash_decode(jnp.zeros((1, 1, 1, d)), jnp.zeros((1, window, d)), stale,
                                      offsets=jnp.asarray([i]), ring=True, interpret=True))[0, 0, 0]
        seen = np.flatnonzero(got)
        assert seen.min() == at[0] and seen.max() == i and len(seen) == len(at), (i, seen)
        np.testing.assert_allclose(got[seen], 1.0 / len(at), rtol=1e-6)


def test_ring_decode_equals_masked_attention_over_the_ring_grouped_heads_too():
    rng = jax.random.split(jax.random.PRNGKey(2), 3)
    b, kv, rep, d, window = 3, 2, 4, 32, 64
    q = jax.random.normal(rng[0], (b, kv * rep, 1, d))
    k, v = (jax.random.normal(r, (b, window, kv * d)) for r in rng[1:])
    positions = jnp.asarray([3, window - 1, 5 * window + 7])
    valid = jnp.arange(window)[None, :] <= jnp.minimum(positions, window - 1)[:, None]
    heads = lambda x: jnp.repeat(x.reshape(b, window, kv, d).transpose(0, 2, 1, 3), rep, axis=1)  # noqa: E731
    want = dot_product_attention(q, heads(k), heads(v), jnp.where(valid, 0.0, NEG_INF)[:, None, None, :])
    rows = q.reshape(b, kv, rep, 1, d).swapaxes(2, 3).reshape(b, kv, rep, d)
    got = flash_decode(rows, k, v, offsets=positions, q_group=rep, ring=True, interpret=True)
    got = got.reshape(b, kv, 1, rep, d).swapaxes(2, 3).reshape(b, kv * rep, 1, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6, rtol=0)
    with pytest.raises(ValueError, match="one position a row"):
        flash_decode(jnp.zeros((b, kv, 2, d)), k, v, offsets=positions, ring=True, interpret=True)


def test_yarn_frequencies_are_the_formulas_at_the_published_numbers():
    rope = PUBLISHED["rope_parameters"]["full_attention"]
    d, theta, s, l0 = PUBLISHED["head_dim"], rope["rope_theta"], rope["factor"], rope["original_max_position_embeddings"]
    dim = lambda b: d * math.log(l0 / (2 * math.pi * b)) / (2 * math.log(theta))  # noqa: E731
    lo, hi = max(math.floor(dim(rope["beta_fast"])), 0), min(math.ceil(dim(rope["beta_slow"])), d - 1)
    assert (lo, hi) == (18, 35)
    n = np.arange(d // 2, dtype=np.float64)
    base = theta ** (-2 * n / d)
    ramp = np.clip((n - lo) / (hi - lo), 0, 1)
    want = base / s * ramp + base * (1 - ramp)
    yarn = ADAPTER.program_config_overrides(PUBLISHED)["rope_yarn"]
    assert yarn == registry.MELLUM_CONFIGS["mellum2-12b-a2.5b"].rope_yarn
    np.testing.assert_allclose(np.asarray(mha.rope_inv_freq(d, theta, yarn)), want, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(REF.inv_freq(d, rope)), want, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(mha.rope_inv_freq(d, theta)), base, rtol=2e-6)  # the window layers'
    assert math.isclose(rope["attention_factor"], 0.1 * math.log(s) + 1, rel_tol=1e-12)
    pos = jnp.asarray([[0, 1, 8191, 100000]])
    cos, sin = mha.rope_cos_sin(pos, d, theta, yarn)
    np.testing.assert_allclose(np.asarray(cos ** 2 + sin ** 2), rope["attention_factor"] ** 2, rtol=1e-5)


def test_softmax_top_k_router_and_experts_equal_the_reference_layer():
    lm, params, ref_params = seeded(5)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 40, CFG["hidden_size"]))
    from distributed_llms_example_tpu.ops.moe import MoEMLP

    layer = MoEMLP(num_experts=CFG["num_experts"], intermediate_size=CFG["moe_intermediate_size"],
                   top_k=CFG["num_experts_per_tok"], capacity_factor=-1.0, scorer="softmax", aux_loss=False)
    with jax.default_matmul_precision("highest"):
        got = layer.apply({"params": params["block_1"]["mlp"]}, x)[0]
        want, _ = REF.expert_layer(FP32, ref_params, "layers.1", x[0], CFG)
        weights_, _ = REF.route(FP32, ref_params, "layers.1", x[0], CFG)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=0)
    assert np.all(np.count_nonzero(np.asarray(weights_), axis=-1) == CFG["num_experts_per_tok"])
    np.testing.assert_allclose(np.asarray(weights_).sum(-1), 1.0, rtol=1e-6)


# ------------------------------------------------------------------ the engine


PROMPT_LENGTHS = (48, 1, 7, W, W + 1, 37, 3, 48, 2 * W, 19)  # shorter than, equal to and longer than the window
BUDGETS = (24, 6, 24, 20, 24, 24, 5, 9, 24, 18)


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_engine_serves_the_static_greedy_loops_tokens_and_the_references_best(impl):
    """Ragged right-padded prompts in two buckets, more requests than slots
    (every slot is reused), decodes that wrap the ring: every served token is
    the static greedy loop's, and the float32 reference's best at its position.
    ``flash``: the prompt's window kernel and the ring decode step, interpreted."""
    lm, params, ref_params = seeded(5, attention_impl=impl)
    rng = np.random.default_rng(2)
    prompts = [list(rng.integers(2, 250, size=n)) for n in PROMPT_LENGTHS]
    with jax.default_matmul_precision("highest"):
        outs = _engine(lm, prefill_buckets=(16,)).generate(params, prompts, max_new=list(BUDGETS))
        # the static loop on the same prompts, right-padded into one batch
        ids = np.zeros((len(prompts), 48), np.int32)
        for i, prompt in enumerate(prompts):
            ids[i, : len(prompt)] = prompt
        greedy = jax.jit(make_causal_greedy(lm.module, dataclasses.replace(lm.config, eos_token_id=None), 24))
        static = np.asarray(greedy(params, jnp.asarray(ids), jnp.asarray(ids > 0, jnp.int32)))
    for prompt, served, loop in zip(prompts, outs, static):
        assert list(loop[: len(served)]) == served, len(prompt)
    assert [len(o) for o in outs] == list(BUDGETS)
    for prompt, served in zip(prompts, outs):
        logits = reference_logits(ref_params, prompt + served[:-1])[len(prompt) - 1:]
        below = logits.max(axis=-1) - logits[np.arange(len(served)), served]
        assert below.max() < 2e-4, (len(prompt), below)


def test_a_reused_slots_ring_cannot_leak():
    """One slot, a long request and then a short one in the same slot: the ring
    is full of the first's keys when the second (3 tokens) is admitted; it must
    decode as it does through a fresh engine."""
    lm, params, _ = seeded(8)
    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(2, 250, size=n)) for n in (48, 3, 20)]
    together = _engine(lm, slots=1, wave=1, new=12).generate(params, prompts)
    alone = [_engine(lm, slots=1, wave=1, new=12).generate(params, [p])[0] for p in prompts]
    assert together == alone


def test_the_cache_holds_two_lengths_of_kv_leaf_and_the_summary_counts_them_by_kind():
    lm, params, _ = seeded(6)
    sess = _engine(lm).open(params)
    shapes = {}
    for path, x in jax.tree_util.tree_leaves_with_path(sess.state["cache"]):
        shapes.setdefault(cache_leaf_name(path), set()).add(x.shape)
    assert shapes == {"window_key": {(3, W, 32)}, "window_value": {(3, W, 32)}, "cached_key": {(3, 72, 32)},
                      "cached_value": {(3, 72, 32)}, "cache_index": {()}}
    window, full = 3 * 2 * 3 * W * 32 * 4, 1 * 2 * 3 * 72 * 32 * 4  # layers x (K, V) x slots x length x lanes x float32
    assert sess._cache_bytes_by_kind == {
        "kv_bytes": window + full, "conv_state_bytes": 0, "kv_window_bytes": window, "kv_full_bytes": full}
    sess.finalize()


@pytest.mark.parametrize("mode", [{"paged_kv": True}, {"paged_kv": True, "prefix_cache": True}, {"spec_tokens": 2}])
def test_modes_that_cannot_hold_a_window_leaf_are_refused_by_name(mode):
    lm = registry.load_model("mellum-test")
    assert lm.config.has_window_cache
    with pytest.raises(UnsupportedServeMode, match="window leaf .*window_key"):
        _engine(lm, **mode)


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_a_decode_round_reports_the_kv_positions_it_needs_and_streams(impl):
    from distributed_llms_example_tpu.core.config import MeshConfig
    from distributed_llms_example_tpu.core.mesh import build_mesh
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

    lm, params, _ = seeded(8, attention_impl=impl)
    # a forced kernel runs (interpreted) only under a mesh context: eight virtual devices without one select XLA
    mesh = build_mesh(MeshConfig(data=-1), devices=jax.devices()[:1])
    sess = _engine(lm, new=16, mesh=mesh).open(params, spans=SpanRecorder(scope="serve", annotate=Annotation))
    sess.submit(list(range(2, 42)), max_new=4)  # 40 tokens: past the window
    sess.submit(list(range(2, 7)), max_new=2)  # 5 tokens: inside it
    while sess.has_work():
        sess.step()
    sess.finalize()
    rounds = [kw for name, kw in seen if name == "serve/decode_dispatch"]
    if impl == "xla":
        assert sess._kv_reads == [((W, 0), 3), ((64, 0), 1)]
        assert all(kw["kv_positions_streamed"] == 3 * (3 * W + 64) for kw in rounds)  # slots x (window leaves + the full leaf)
    else:  # the decode kernel (PR 46): of a live slot its rings whole and the full leaf's one tile (64 positions: 56 do not tile, and XLA would read them), of an idle slot nothing
        assert sess._kv_reads == [((W, W), 3), ((64, 64), 1)]
        assert [kw["kv_positions_streamed"] for kw in rounds] == [kw["slots_live"] * (3 * W + 64) for kw in rounds]
        assert {kw["slots_live"] for kw in rounds} == {1, 2}
    # the round that emits a request's 2nd token holds its prompt and first token: 41 and 6 positions
    assert rounds[0]["kv_positions_live"] == (41 + 3 * W) + (6 + 3 * 6)
    assert rounds[1]["kv_positions_live"] == 42 + 3 * W  # the short request is done
    assert rounds[-1]["kv_positions_live"] == 43 + 3 * W


def test_leaf_specs_go_by_the_leaf_on_one_device_and_on_eight():
    from jax.sharding import PartitionSpec as P

    from distributed_llms_example_tpu.core.config import MeshConfig
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.parallel.sharding import cache_leaf_spec, shard_params

    batch = ("data", "fsdp", "expert")
    for axes in ({"data": 1}, {"data": 2, "fsdp": 2, "tensor": 2}):
        heads = "tensor" if axes.get("tensor", 1) > 1 else None
        for name, length in (("cached_key", 72), ("window_key", W), ("window_value", W)):
            assert cache_leaf_spec(name, (4, length, 2 * 16), axes, 2) in (P(batch, None, heads), P(batch, None, "tensor"))
    # 4 KV heads against a wider tensor axis replicate; a pool pages no ring
    assert cache_leaf_spec("window_key", (8, 1024, 512), {"tensor": 8}, 4) == P(batch, None, None)
    assert cache_leaf_spec("window_key", (8, 1024, 512), {"tensor": 4}, 4) == P(batch, None, "tensor")
    assert cache_leaf_spec("window_key", (8, 1024, 512), {"tensor": 4}, 4, pool=True) is None

    lm, params, _ = seeded(6)
    rng = np.random.default_rng(7)
    prompts = [list(rng.integers(2, 250, size=n)) for n in (40, 9, 48, 17)]
    with jax.default_matmul_precision("highest"):
        want = _engine(lm, slots=4, wave=4, new=20).generate(params, prompts)
        for shape in ({"data": 1}, {"data": 2, "fsdp": 2, "tensor": 2}):
            n = math.prod(shape.values())
            mesh = build_mesh(MeshConfig(**shape), devices=jax.devices()[:n])
            eng = _engine(lm, slots=4, wave=4, new=20, mesh=mesh)
            sess = eng.open(shard_params(params, mesh))
            ring = {cache_leaf_name(p): x for p, x in jax.tree_util.tree_leaves_with_path(sess.state["cache"])}["window_key"]
            assert ring.addressable_shards[0].data.shape == (4 // (n // shape.get("tensor", 1)), W, 32 // shape.get("tensor", 1))
            sess.finalize()
            assert eng.generate(shard_params(params, mesh), prompts) == want, shape


# --------------------------------------------------------- the prompt's route


def test_a_cached_prompt_takes_the_flash_kernel_wherever_an_uncached_causal_call_would():
    pick = lambda **kw: mha.select_attention_impl(  # noqa: E731
        **{"attention_impl": "auto", "batch": 1, "heads": 32, "head_dim": 128, "q_len": 8192, "kv_len": 8192,
           "use_cache": True, "prompt": True, "mesh": None, "backend": "tpu", "device_count": 1, "causal": True, **kw})[0]
    assert pick() == "flash" and pick(window=1024) == "flash"  # one 8,192-token row: 8.6 GB of scores a layer on XLA's path
    assert pick(backend="cpu") == "xla" and pick(attention_impl="xla") == "xla"
    assert pick(prompt=False) == "xla"  # a step that continues a cache is select_decode_impl's
    # one rule, no threshold: lfm2's 1,024-token waves of 1 and 4 rows take the kernel too (no slower there: PERF.md, PR 37)
    for rows in (1, 4):
        lfm2 = {"batch": rows, "heads": 32, "head_dim": 64, "q_len": 1024, "kv_len": 1024}
        assert pick(**lfm2) == "flash" == pick(**lfm2, use_cache=False, prompt=False)
    assert pick(q_len=8200, kv_len=8200) == "xla"  # does not tile
    assert pick(q_len=64, kv_len=64) == "xla"  # too small to tile
