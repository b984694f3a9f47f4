"""Serving subsystem: decode flash kernel parity, sharded KV-cache lint,
prefill/decode split, continuous batching determinism, prefill-in-decode IR
smell."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_example_tpu.models.registry import load_model
from distributed_llms_example_tpu.ops.attention import NEG_INF, dot_product_attention
from distributed_llms_example_tpu.ops.flash_attention import (
    flash_decode,
    flash_decode_supported,
)
from distributed_llms_example_tpu.ops.mha import decode_step_bias, select_decode_impl


# ------------------------------------------------------ kernel unit parity


def _dense_decode_ref(q, k, v, bias, offsets, scale=None, q_group=1):
    """Masked dot_product_attention with the kernel's per-row length mask
    (``q_group`` q rows a position: the query heads that share a KV head)."""
    L = k.shape[2]
    Q = q.shape[2]
    k_pos = jnp.arange(L)[None, None, None, :]
    q_pos = offsets[:, None, None, None] + (jnp.arange(Q) // q_group)[None, None, :, None]
    step = jnp.where(k_pos <= q_pos, 0.0, NEG_INF)
    return dot_product_attention(q, k, v, step if bias is None else bias + step, scale=scale)


def _leaf(x):
    """(B, H, L, d) as the cache keeps it: (B, L, H x d), heads side by side."""
    b, h, length, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, length, h * d)


def _scale_leaf(s):
    """(B, H, L) int8 scales as the cache keeps them: (B, L, H)."""
    return s.transpose(0, 2, 1)


# name: (B, H, L, d, q_len, bias, int8 K/V, block_k, step's VMEM budget, q rows a
# position) — bias is None, "pad" (B, 1, 1, L) or "full" (B, H, q, L: T5's
# decode-step bias)
DECODE_CASES = {
    "tiny-q1": (3, 4, 64, 16, 1, "pad", False, None, None, 1),
    "tiny-q4": (3, 4, 64, 16, 4, "pad", False, None, None, 1),
    # the serve cell's head shape (bart-large-cnn: 16 heads x 64, cache 128):
    # a grid step holds every head of a slot
    "cell-q1": (4, 16, 128, 64, 1, None, False, None, None, 1),
    "cell-q8": (4, 16, 128, 64, 8, None, False, None, None, 1),
    "cell-q1-pad-bias": (4, 16, 128, 64, 1, "pad", False, None, None, 1),
    "cell-q8-pad-bias": (4, 16, 128, 64, 8, "pad", False, None, None, 1),
    "cell-q1-full-bias": (4, 16, 128, 64, 1, "full", False, None, None, 1),
    "cell-q8-full-bias": (4, 16, 128, 64, 8, "full", False, None, None, 1),
    "cell-q1-int8": (4, 16, 128, 64, 1, None, True, None, None, 1),
    "cell-q1-int8-pad-bias": (4, 16, 128, 64, 1, "pad", True, None, None, 1),
    "cell-q8-int8-full-bias": (4, 16, 128, 64, 8, "full", True, None, None, 1),
    # lfm2's head shape (8 KV heads x 64, four query heads a KV head as q rows)
    "grouped-q4": (4, 8, 256, 64, 4, None, False, None, None, 4),
    "grouped-q4-pad-bias": (4, 8, 256, 64, 4, "pad", False, None, None, 4),
    "grouped-q8-two-positions-pad-bias": (4, 8, 256, 64, 8, "pad", False, None, None, 4),
    "grouped-q4-int8-pad-bias": (4, 8, 256, 64, 4, "pad", True, None, None, 4),
    # four kv tiles, offsets in the first, a middle and the last one: the
    # first two rows leave dead tiles behind them
    "tiles-q1": (4, 4, 256, 16, 1, "pad", False, 64, None, 1),
    "tiles-q4-full-bias": (4, 4, 256, 16, 4, "full", False, 64, None, 1),
    "tiles-q8": (4, 4, 256, 16, 8, None, False, 64, None, 1),
    "tiles-grouped-q4-pad-bias": (4, 8, 256, 64, 4, "pad", False, 64, None, 4),
    "tiles-q1-int8": (4, 16, 256, 64, 1, None, True, 64, None, 1),
    # a budget that holds two of eight heads (128 lanes): the merged axis splits
    "split-heads-q1": (3, 8, 64, 64, 1, "full", False, None, 200_000, 1),
    "split-heads-grouped-q4": (3, 8, 64, 64, 4, "pad", False, None, 220_000, 4),
    # int8 K/V in groups of heads: the (B, L, H) scales come whole every step
    # and a group picks its own (a 7B cache's 32 heads of 128 need this at
    # every kv tile the cache has)
    "split-heads-q4-int8": (3, 8, 64, 64, 4, "pad", True, None, 400_000, 1),
    "split-heads-tiles-q1-int8-full-bias": (3, 8, 128, 64, 1, "full", True, 64, 400_000, 1),
}


@pytest.mark.parametrize("case", sorted(DECODE_CASES))
def test_flash_decode_matches_dense(case, monkeypatch):
    from distributed_llms_example_tpu.ops import flash_attention as fa

    B, H, L, d, q_len, bias_kind, int8, block_k, budget, q_group = DECODE_CASES[case]
    if budget is not None:
        monkeypatch.setattr(fa, "DECODE_STEP_VMEM_BUDGET", budget)
        hb = fa.decode_step_heads(H, block_k or L, d, 1 if int8 else 4, q_len=q_len, int8_scales=int8)
        assert 1 < hb < H  # the case is what its name says
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, q_len, d).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, L, d).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, L, d).astype(np.float32))
    bias = None
    if bias_kind == "pad":
        bias = np.where(rng.rand(B, 1, 1, L) > 0.2, 0.0, NEG_INF)
        if not case.startswith("tiny"):  # the two tiny cases keep the draw they always had
            # key 0 stays live: it is all the fresh slot's first row sees, and
            # the dense reference spreads a row with no live key over masked ones
            bias[..., 0] = 0.0
    elif bias_kind == "full":
        bias = rng.randn(B, H, q_len, L)
    if bias is not None:
        bias = jnp.asarray(bias.astype(np.float32))
    # ragged per-row offsets: fresh slot (0), mid-decode, cache-full
    offsets = jnp.array([0, 17, L - q_len, L // 2 + 3][:B], jnp.int32)
    if int8:
        qk, ks = fa.quantize_kv(k)
        qv, vs = fa.quantize_kv(v)
        out = flash_decode(
            q, _leaf(qk), _leaf(qv), bias, offsets=offsets, k_scale=_scale_leaf(ks),
            v_scale=_scale_leaf(vs), block_k=block_k, q_group=q_group,
        )
        k, v = fa.dequantize_kv(qk, ks), fa.dequantize_kv(qv, vs)
    else:
        out = flash_decode(q, _leaf(k), _leaf(v), bias, offsets=offsets, block_k=block_k, q_group=q_group)
    ref = _dense_decode_ref(q, k, v, bias, offsets, q_group=q_group)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


def test_flash_decode_grid_is_slots_by_tiles():
    """At the serve cell's shape one call takes at most batch x kv-tiles grid
    steps: a step per (slot, head) cost 0.51 us for 2 x 16 KB on the chip
    (PERF.md, PR 26), and no CPU test would notice its return.  Its K/V blocks
    are tiles of the cache leaf as it rests, (1, block_k, heads x head_dim)."""
    B, H, L, d = 64, 16, 128, 64
    q = jax.ShapeDtypeStruct((B, H, 1, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((B, L, H * d), jnp.bfloat16)
    off = jax.ShapeDtypeStruct((B,), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda q, k, v, o: flash_decode(q, k, v, offsets=o))(q, kv, kv, off)
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    mapping = calls[0].params["grid_mapping"]
    assert int(np.prod(mapping.grid)) <= B * (L // 128), mapping.grid
    blocks = [tuple(getattr(n, "block_size", n) for n in bm.block_shape) for bm in mapping.block_mappings]
    assert blocks.count((1, 128, H * d)) == 2, blocks
    assert calls[0].outvars[0].aval.shape == (B, H, 1, d)  # what serve_decode_attn_ms looks for


def test_flash_decode_refuses_the_old_leaf():
    q = jnp.zeros((2, 4, 1, 16))
    kv = jnp.zeros((2, 4, 64, 16))
    with pytest.raises(ValueError, match=r"\(batch, length, heads x head_dim\)"):
        flash_decode(q, kv, kv, offsets=jnp.zeros((2,), jnp.int32))


def test_decode_step_heads_rule():
    from distributed_llms_example_tpu.ops.flash_attention import decode_step_heads

    # bart-large-cnn's serve cell: all 16 heads of a slot side by side, 1 MB of VMEM a step
    assert decode_step_heads(16, 128, 64, 2) == 16
    # lfm2's: 8 KV heads, four q rows each, a 256-row tile
    assert decode_step_heads(8, 256, 64, 2, q_len=4) == 8
    # a 7B cache tile (512 x 128 bf16) is 256 KB a head for K and V: 16 of 32
    # heads' tiles fit alone, 8 with their q block and accumulator beside them
    assert decode_step_heads(32, 512, 128, 2) == 8
    # a group's lanes are whole vregs: heads of 64 split in pairs, not singly
    assert decode_step_heads(32, 512, 64, 4, q_len=8) % 2 == 0
    # five heads of 2 MB do not fit and 5 has no smaller group: one head a step
    assert decode_step_heads(5, 512, 1024, 2) == 1
    # int8 K/V: tiles count as the f32 they dequantize to, and all heads'
    # scales ride every step (padded to 128 lanes)
    assert decode_step_heads(16, 128, 64, 1, int8_scales=True) == 16
    assert decode_step_heads(12, 512, 64, 1, int8_scales=True) == 12
    # llama-2-7b / 13b under --kv-cache-dtype int8 (32 / 40 heads of 128, kv
    # tile 512): 8 heads' tiles alone are the budget, so 4 and 5 a step
    assert decode_step_heads(32, 512, 128, 1, int8_scales=True) == 4
    assert decode_step_heads(40, 512, 128, 1, int8_scales=True) == 5
    assert decode_step_heads(8, 512, 128, 1, int8_scales=True) == 4  # mixtral-8x7b's 8 KV heads
    # five heads of 1024 as f32 fit not even singly: named here, not a Mosaic
    # failure far from its cause, and asked first by select_decode_impl
    with pytest.raises(ValueError, match="5 heads of 1024.*int8 K/V"):
        decode_step_heads(5, 512, 1024, 1, int8_scales=True)


def test_flash_decode_stale_cache_unreachable():
    """Slot-reuse contract: whatever sits beyond a row's offset (a previous
    occupant's K/V) must not influence the output."""
    rng = np.random.RandomState(1)
    B, H, L, d = 2, 2, 32, 8
    q = jnp.asarray(rng.randn(B, H, 1, d).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, L, d).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, L, d).astype(np.float32))
    offsets = jnp.array([3, 9], jnp.int32)
    out = flash_decode(q, _leaf(k), _leaf(v), offsets=offsets)
    # poison everything beyond each row's offset with huge garbage
    k_pos = jnp.arange(L)[None, None, :, None]
    beyond = k_pos > offsets[:, None, None, None]
    out_poisoned = flash_decode(
        q,
        _leaf(jnp.where(beyond, 1e6, k)),
        _leaf(jnp.where(beyond, -1e6, v)),
        offsets=offsets,
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_poisoned))


@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
def test_flash_decode_run_shards_the_leaf_like_its_heads(mesh8, int8):
    """Per shard under ``shard_map`` the kernel sees its batch rows and its
    heads: q's head axis and the leaf's merged last axis split over ``tensor``
    together (the heads are contiguous in it), the (B, L, H) scales with them."""
    from distributed_llms_example_tpu.ops import flash_attention as fa

    rng = np.random.RandomState(5)
    B, H, L, d, Q = 8, 4, 64, 16, 2
    q = jnp.asarray(rng.randn(B, H, Q, d).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, L, d).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, L, d).astype(np.float32))
    bias = jnp.asarray(rng.randn(1, H, Q, L).astype(np.float32))  # a head axis: it shards with the heads
    offsets = jnp.asarray(rng.randint(0, L - Q, (B,)), jnp.int32)
    scales = {}
    if int8:
        (k, ks), (v, vs) = fa.quantize_kv(k), fa.quantize_kv(v)
        scales = {"k_scale": _scale_leaf(ks), "v_scale": _scale_leaf(vs)}
    one = flash_decode(q, _leaf(k), _leaf(v), bias, offsets=offsets, **scales)
    sharded = jax.jit(lambda q, k, v, bias, off, sc: fa.flash_decode_run(
        q, k, v, bias, offsets=off, mesh=mesh8, **sc))(q, _leaf(k), _leaf(v), bias, offsets, scales)
    np.testing.assert_array_equal(np.asarray(sharded), np.asarray(one))


def test_flash_decode_run_walks_each_batch_shards_own_live_list(mesh8):
    """Under ``shard_map`` ``live`` shards with the batch rows and each shard
    sorts its own: the result is the one-device call's, idle rows zero, whatever
    the idle rows' leaves hold."""
    from distributed_llms_example_tpu.ops import flash_attention as fa

    rng = np.random.RandomState(6)
    B, H, L, d = 8, 4, 64, 16
    q = jnp.asarray(rng.randn(B, H, 1, d).astype(np.float32))
    k, v = (_leaf(jnp.asarray(rng.randn(B, H, L, d).astype(np.float32))) for _ in range(2))
    live = np.array([True, False, False, True, False, True, True, False])  # a shard with none, one with both
    offsets = jnp.asarray(np.where(live, rng.randint(0, L, B), L), jnp.int32)
    one = np.asarray(flash_decode(q, k, v, offsets=offsets, live=live))
    nan = jnp.where(jnp.asarray(live)[:, None, None], 0.0, jnp.nan)
    sharded = jax.jit(lambda q, k, v, off, on: fa.flash_decode_run(q, k, v, None, offsets=off, live=on, mesh=mesh8))(
        q, k + nan, v + nan, offsets, jnp.asarray(live))
    np.testing.assert_array_equal(np.asarray(sharded), one)
    assert (one[~live] == 0).all() and np.abs(one[live]).min() > 0


def test_flash_decode_supported_gating():
    assert flash_decode_supported(1, 128, 64)
    assert flash_decode_supported(8, 64, 16)
    assert not flash_decode_supported(9, 128, 64)  # q block too tall
    assert not flash_decode_supported(1, 12, 64)  # 12 not 8-tileable
    assert not flash_decode_supported(1, 128, 12)  # head_dim not lane-aligned


def test_select_decode_impl_pure():
    kw = dict(batch=8, heads=8, head_dim=64, q_len=1, kv_len=128, mesh=None,
              backend="tpu", device_count=1)
    assert select_decode_impl("auto", **kw)[0] == "flash_decode"
    assert select_decode_impl("xla", **kw) == ("xla", "forced")
    assert select_decode_impl("ring", **kw)[0] == "xla"
    impl, reason = select_decode_impl("auto", **{**kw, "backend": "cpu"})
    assert impl == "xla" and "cpu" in reason
    # forced flash wins on any backend when the shape tiles
    assert select_decode_impl("flash", **{**kw, "backend": "cpu"})[0] == "flash_decode"
    # untileable cache falls back even when forced
    assert select_decode_impl("flash", **{**kw, "kv_len": 12})[0] == "xla"
    # the registered 7B / 13B shapes take the kernel under the int8 cache too
    for heads in (32, 40):
        big = {**kw, "heads": heads, "head_dim": 128, "kv_len": 4096}
        assert select_decode_impl("auto", **big, kv_dtype=jnp.int8)[0] == "flash_decode"
        assert select_decode_impl("auto", **big)[0] == "flash_decode"
    # a step of which no group of heads fits VMEM takes XLA, forced or not,
    # and does not raise while the program is traced
    huge = {**kw, "heads": 5, "head_dim": 1024, "kv_len": 512}
    for impl in ("auto", "flash"):
        got, why = select_decode_impl(impl, **huge, kv_dtype=jnp.int8)
        assert got == "xla" and "VMEM" in why
    assert select_decode_impl("auto", **huge)[0] == "flash_decode"  # bf16: one head a step


def test_decode_step_bias_per_row():
    offsets = jnp.array([0, 5], jnp.int32)
    bias = decode_step_bias(offsets, 1, 8)
    assert bias.shape == (2, 1, 1, 8)
    row0 = np.asarray(bias)[0, 0, 0]
    row1 = np.asarray(bias)[1, 0, 0]
    assert (row0[:1] == 0).all() and (row0[1:] < -1e8).all()
    assert (row1[:6] == 0).all() and (row1[6:] < -1e8).all()


def test_cached_decode_keeps_probs_dropout():
    """A cached decode step that WANTS attention-probs dropout (MC-dropout
    eval: deterministic=False + a dropout rng) must keep applying it —
    the decode kernel has no mask stream, so the dispatch falls back to
    the XLA path instead of silently going deterministic."""
    from distributed_llms_example_tpu.ops.mha import MultiHeadAttention

    mha = MultiHeadAttention(
        num_heads=2, head_dim=8, model_dim=16, causal=True,
        attention_impl="flash", probs_dropout_rate=0.5,
    )
    rng = np.random.RandomState(0)
    x_full = jnp.asarray(rng.randn(2, 16, 16).astype(np.float32))
    variables = mha.init(jax.random.PRNGKey(0), x_full, use_cache=True)
    x = x_full[:, :1]
    kw = dict(use_cache=True, mutable=["cache"])
    det, _ = mha.apply(variables, x, deterministic=True, **kw)
    drop1, _ = mha.apply(
        variables, x, deterministic=False,
        rngs={"dropout": jax.random.PRNGKey(1)}, **kw,
    )
    drop2, _ = mha.apply(
        variables, x, deterministic=False,
        rngs={"dropout": jax.random.PRNGKey(2)}, **kw,
    )
    assert not np.allclose(np.asarray(det), np.asarray(drop1))
    assert not np.allclose(np.asarray(drop1), np.asarray(drop2))


# ------------------------------------------ kernel parity through decoding


def _with_impl(lm, impl):
    cfg = dataclasses.replace(lm.config, attention_impl=impl)
    return type(lm.module)(cfg), cfg


def test_seq2seq_decode_kernel_parity_greedy_and_beam():
    """Forced-flash cached decode (the Pallas decode kernel, interpret mode
    on CPU) is token-identical to the XLA reference on greedy AND beam
    paths — the bit-parity acceptance gate."""
    from distributed_llms_example_tpu.evaluation.generation import (
        make_beam_search,
        make_greedy_generate,
    )

    lm = load_model("t5-test")
    params = lm.init_params(0)
    rng = np.random.RandomState(2)
    ids = rng.randint(2, 200, (2, 16)).astype(np.int32)
    mask = np.ones((2, 16), np.int32)
    mask[1, -5:] = 0
    for factory, kw in (
        (make_greedy_generate, {}),
        (make_beam_search, {"num_beams": 2}),
    ):
        outs = {}
        for impl in ("xla", "flash"):
            mod, cfg = _with_impl(lm, impl)
            outs[impl] = np.asarray(factory(mod, cfg, 16, **kw)(params, ids, mask))
        np.testing.assert_array_equal(outs["xla"], outs["flash"])


def test_causal_decode_kernel_parity_ragged_prompts():
    """LLaMA cached decode through the kernel: ragged (right-padded)
    prompts exercise per-row causal offsets; greedy + beam vs XLA."""
    from distributed_llms_example_tpu.evaluation.generation import (
        make_causal_beam_search,
        make_causal_greedy,
    )

    lm = load_model("llama-test")
    params = lm.init_params(0)
    rng = np.random.RandomState(3)
    ids = rng.randint(3, 120, (3, 8)).astype(np.int32)
    mask = np.ones((3, 8), np.int32)
    mask[1, -3:] = 0
    mask[2, -1:] = 0
    for factory, kw in (
        (make_causal_greedy, {}),
        (make_causal_beam_search, {"num_beams": 2}),
    ):
        outs = {}
        for impl in ("xla", "flash"):
            mod, cfg = _with_impl(lm, impl)
            outs[impl] = np.asarray(factory(mod, cfg, 8, **kw)(params, ids, mask))
        np.testing.assert_array_equal(outs["xla"], outs["flash"])


# --------------------------------------------------- cache sharding lint


def test_cache_rules_lint_green_on_abstract_cache():
    from distributed_llms_example_tpu.analysis.spec_lint import lint_cache_sharding
    from distributed_llms_example_tpu.evaluation.generation import abstract_cache

    axes = {"data": 2, "fsdp": 2, "tensor": 2}
    for name, seq2seq in (("t5-test", True), ("bart-test", True), ("llama-test", False)):
        lm = load_model(name, load_weights=False)
        a_params = jax.eval_shape(lambda lm=lm: lm.init_params(0))
        cache = abstract_cache(
            lm.module, a_params, batch=8, max_new_tokens=16, src_len=32,
            is_seq2seq=seq2seq,
        )
        findings = lint_cache_sharding(cache, axes)
        errors = [f for f in findings if f.severity == "error"]
        assert not errors, errors


def test_cache_rules_lint_catches_unmatched_leaf():
    from jax.sharding import PartitionSpec as P

    from distributed_llms_example_tpu.analysis.spec_lint import lint_cache_sharding
    from distributed_llms_example_tpu.evaluation.generation import abstract_cache
    from distributed_llms_example_tpu.parallel.sharding import ShardingRules

    lm = load_model("t5-test", load_weights=False)
    a_params = jax.eval_shape(lambda: lm.init_params(0))
    cache = abstract_cache(lm.module, a_params, batch=8, max_new_tokens=16, src_len=32)
    # a typo'd rule set: cached_value leaves match nothing → they decode
    # fully replicated
    bad = ShardingRules(rules=[
        (r"cached_key$", P(("data", "fsdp"), None, "tensor")),
        (r"cache_index$", P()),
    ])
    findings = lint_cache_sharding(cache, {"data": 2, "fsdp": 2, "tensor": 2}, rules=bad)
    assert any(f.code == "unmatched-cache-leaf" for f in findings)


def test_cache_resolves_on_mesh8(mesh8):
    """The cache rule set drives real NamedSharding resolution for the
    serving state — cached K/V shards batch over data×fsdp and the merged
    heads x head_dim axis over tensor on the 8-device mesh."""
    from distributed_llms_example_tpu.evaluation.generation import abstract_cache
    from distributed_llms_example_tpu.parallel.sharding import (
        cache_rules,
        resolve_shardings,
    )

    lm = load_model("t5-test", load_weights=False)
    a_params = jax.eval_shape(lambda: lm.init_params(0))
    cache = abstract_cache(lm.module, a_params, batch=8, max_new_tokens=16, src_len=32)
    sh = resolve_shardings(cache, mesh8, cache_rules())
    leaves = jax.tree_util.tree_leaves_with_path(sh)
    kv = [
        (path, s) for path, s in leaves
        if "cached_key" in str(path) or "cached_value" in str(path)
    ]
    assert kv
    for path, s in kv:
        spec = s.spec
        assert spec[0] == ("data", "fsdp", "expert"), (path, spec)
        assert spec[1] is None and spec[2] == "tensor", (path, spec)  # (slots, length, heads x d)


def test_aot_decode_program_carries_cache_rules_sharding(mesh8):
    """The cache spec lint's claim, proven on the COMPILED program: the
    AOT-compiled prefill emits its cache carry (the decode step's input)
    sharded exactly per CACHE_RULES — batch rows over (data, fsdp), heads
    over tensor — not whatever GSPMD would guess for an unconstrained
    zeros-init."""
    import jax.tree_util as jtu

    from distributed_llms_example_tpu.evaluation.generation import Seq2SeqGenerator
    from distributed_llms_example_tpu.parallel.activation import activation_mesh

    lm = load_model("t5-test", load_weights=False)
    a_params = jax.eval_shape(lambda: lm.init_params(0))
    gen = Seq2SeqGenerator(lm.module, lm.config, 16, num_beams=1)
    ids = jax.ShapeDtypeStruct((8, 32), jnp.int32)
    with activation_mesh(mesh8):
        compiled = jax.jit(gen.prefill).lower(a_params, ids, ids).compile()
    kv = [
        (jtu.keystr(path), s.spec)
        for path, s in jtu.tree_leaves_with_path(compiled.output_shardings["cache"])
        if "cached_key" in jtu.keystr(path) or "cached_value" in jtu.keystr(path)
    ]
    assert kv
    for path, spec in kv:
        batch_axes = spec[0] if len(spec) > 0 else None
        batch_axes = batch_axes if isinstance(batch_axes, tuple) else (batch_axes,)
        assert {"data", "fsdp"} <= set(batch_axes), (path, spec)
        assert len(spec) > 2 and spec[1] is None and spec[2] == "tensor", (path, spec)


def test_ragged_kv_heads_replicate_in_compiled_decode_step():
    """llama-test's 2 KV heads on tensor=4: the cache's merged axis would
    divide by its lanes, and a shard of half a head costs every layer of
    every step collectives inside a head (RoPE's halves permuted, scores
    all-reduced).  ``cache_leaf_spec`` goes by the heads, so the compiled
    step keeps K/V whole over ``tensor`` and permutes nothing."""
    from distributed_llms_example_tpu.analysis.ir_lint import lint_decode_step
    from distributed_llms_example_tpu.core.config import MeshConfig

    collect: dict = {}
    lint_decode_step(
        "llama-test", mesh_config=MeshConfig(data=2, fsdp=1, sequence=1, tensor=4),
        slots=8, src_len=32, max_new_tokens=16, collect=collect,
    )
    text = collect["decode"]
    assert "collective-permute" not in text
    # per device: 8 slots over data=2, the cache's 48 positions, both heads' 32 lanes
    assert "f32[4,48,32]" in text and "f32[4,48,8]" not in text


# ---------------------------------------- AOT decode step: spec + IR lint


@pytest.mark.parametrize("name", ["t5-test", "llama-test"])
def test_decode_step_compiles_green(name):
    """The acceptance gate: the compiled per-token decode step carries no
    encoder recompute and no per-step cross-KV re-projection
    (prefill_in_decode_smell green), on the multi-axis mesh."""
    from distributed_llms_example_tpu.analysis.ir_lint import lint_decode_step
    from distributed_llms_example_tpu.core.config import MeshConfig

    findings = lint_decode_step(
        name,
        mesh_config=MeshConfig(data=2, fsdp=2, sequence=1, tensor=2),
        slots=8, src_len=32, max_new_tokens=16,
    )
    errors = [f for f in findings if f.severity == "error"]
    assert not errors, errors


def test_prefill_in_decode_smell_fixture():
    """Pure-predicate check on seeded HLO: a decode-legit cross-attention
    score dot stays quiet; a re-projected cross-KV-sized dot errors."""
    from distributed_llms_example_tpu.analysis.ir_lint import (
        parse_hlo_instructions,
        prefill_in_decode_smell,
        scan_hlo_text,
    )

    enc_len, B, H, dh = 128, 8, 4, 64
    ok_text = f"""
  %scores = f32[{B},{H},1,{enc_len}]{{3,2,1,0}} dot(%q, %k)
  %ctx = f32[{B},{H},1,{dh}]{{3,2,1,0}} dot(%p, %v)
"""
    bad_text = ok_text + f"""
  %reproj = f32[{B},{enc_len},{H * dh}]{{2,1,0}} dot(%enc, %w)
"""
    contract = dict(enc_len=enc_len, batch=B, heads=H, q_len=1)
    assert prefill_in_decode_smell(parse_hlo_instructions(ok_text), **contract) is None
    finding = prefill_in_decode_smell(parse_hlo_instructions(bad_text), **contract)
    assert finding is not None and finding.code == "prefill-in-decode"
    assert "reproj" in str(finding.context["instructions"])
    # wired through scan_hlo_text via decode_contract
    codes = [f.code for f in scan_hlo_text(bad_text, mesh_axes={}, decode_contract=contract)]
    assert "prefill-in-decode" in codes


# ----------------------------------------------- continuous batching


def _requests(rng, n, lo=3, hi=20, vocab=200):
    return [list(rng.randint(4, vocab, rng.randint(lo, hi))) for _ in range(n)]


def test_engine_matches_static_batching_seq2seq(mesh8, capsys):
    """Determinism acceptance: an admit/evict schedule over reused slots
    produces EXACTLY the tokens static batching produces, per request —
    with per-request budgets (the continuous-batching lever) exercised.
    The per-request lifecycle spans (ISSUE 9) ride the same run: one
    serve_request event per request with the queue-wait/prefill/decode
    decomposition, and serve_summary's TTFT split accounts for them."""
    import json as _json

    from distributed_llms_example_tpu.serving.engine import (
        ServeConfig,
        ServingEngine,
        static_batch_generate,
        trim_eos,
    )

    lm = load_model("bart-test")
    from distributed_llms_example_tpu.parallel.sharding import shard_params

    params = shard_params(lm.init_params(0), mesh8)
    rng = np.random.RandomState(7)
    reqs = _requests(rng, 10)
    L, W = 12, 32
    budgets = [int(b) for b in rng.randint(4, L + 1, len(reqs))]
    eng = ServingEngine(
        lm.module, lm.config, mesh8,
        ServeConfig(max_slots=4, prefill_batch=4, max_new_tokens=L,
                    max_source_length=W, log_every_steps=0),
        is_seq2seq=True,
    )
    capsys.readouterr()
    outs = eng.generate(params, reqs, max_new=budgets)
    assert eng.last_stats is not None and eng.last_stats.decode_steps > 0
    assert eng.last_stats.ttft_s and len(eng.last_stats.ttft_s) == len(reqs)
    # slot reuse genuinely happened: 10 requests through 4 slots
    assert eng.last_stats.sequences > eng.S
    # per-request lifecycle spans: one serve_request per request, each
    # decomposed (queue-wait + prefill <= ttft; decode + evict step), and
    # the summary's TTFT split covers every finished request
    events = [
        _json.loads(line)
        for line in capsys.readouterr().out.splitlines()
        if line.startswith("{")
    ]
    spans = [e for e in events if e.get("event") == "serve_request"]
    assert sorted(e["request"] for e in spans) == list(range(len(reqs)))
    for e in spans:
        assert {"slot", "queue_wait_ms", "prefill_ms", "ttft_ms",
                "decode_ms", "tokens", "t_admit_s", "t_done_s",
                "finished_at_step"} <= set(e)
        assert e["tokens"] == len(outs[e["request"]])
        # TTFT covers at least the queue-wait and this chunk's prefill
        assert e["ttft_ms"] >= e["queue_wait_ms"] + e["prefill_ms"] - 0.5
    # late-admitted requests (slot reuse) genuinely waited in queue
    assert max(e["queue_wait_ms"] for e in spans) > 0
    summary = next(e for e in events if e.get("event") == "serve_summary")
    assert {"ttft_queue_p50_ms", "ttft_queue_p95_ms", "ttft_prefill_p50_ms",
            "ttft_prefill_p95_ms", "ttft_queue_share",
            "ttft_prefill_share"} <= set(summary)
    assert 0.0 <= summary["ttft_queue_share"] <= 1.0
    assert len(eng.last_stats.queue_wait_s) == len(reqs)
    # goodput (ISSUE 11 satellite): useful tokens/sec rides the summary;
    # with no SLO configured every finished token is useful and the SLO
    # fields stay absent (0 = off, not "everything attained")
    assert summary["goodput_tokens_per_sec"] > 0
    assert summary["goodput_tokens_per_sec_chip"] > 0
    assert "slo_attainment" not in summary and "ttft_slo_ms" not in summary
    assert eng.last_stats.goodput["goodput_tokens_per_sec"] == summary[
        "goodput_tokens_per_sec"
    ]
    ref = static_batch_generate(
        lm.module, lm.config, mesh8, params, reqs, max_new_tokens=L, width=W, batch=4
    )
    eos, pad = lm.config.eos_token_id, lm.config.pad_token_id
    for got, want, budget in zip(outs, ref, budgets):
        g = trim_eos(got, eos, pad)
        w = trim_eos(want, eos, pad)[: len(g)]
        # engine stops at the per-request budget; static decodes to L —
        # the engine's tokens must be the static prefix (eos-trimmed)
        assert g == w, (g, w)
        assert len(g) <= budget


# ------------------------------------- host spans inside the engine's round


class _Annotations:
    """Annotation factory double (obs/spans.py's ``annotate``): every
    annotation's name, stats and extent on the given clock."""

    def __init__(self, clock):
        self.clock, self.events = clock, []

    def __call__(self, name, **stats):
        owner, event = self, {"name": name, "stats": dict(stats)}

        class _One:
            def __enter__(self):
                event["start"] = owner.clock()
                owner.events.append(event)
                return self

            def __exit__(self, *exc):
                event["end"] = owner.clock()
                return False

            def set_metadata(self, **more):
                event["stats"].update(more)

        return _One()

    def children(self, parent):
        return [e for e in self.events if e is not parent
                and parent["start"] <= e["start"] and e["end"] <= parent["end"]]


class _Clock:
    t = 0.0

    def __call__(self):
        return self.t


@pytest.fixture(scope="module")
def serve_rig(mesh8):
    """A small seq2seq engine shared by the span tests: 8 slots, waves of 4."""
    from distributed_llms_example_tpu.parallel.sharding import shard_params
    from distributed_llms_example_tpu.serving.engine import ServeConfig, ServingEngine

    lm = load_model("bart-test")
    params = shard_params(lm.init_params(0), mesh8)
    eng = ServingEngine(
        lm.module, lm.config, mesh8,
        ServeConfig(max_slots=8, prefill_batch=4, max_new_tokens=8,
                    max_source_length=32, log_every_steps=3),
        is_seq2seq=True,
    )
    return eng, params


def _traced_session(rig, clock, **recorder):
    from distributed_llms_example_tpu.obs.spans import SpanRecorder

    eng, params = rig
    notes = _Annotations(clock)
    return eng.open(params, spans=SpanRecorder(clock=clock, scope="serve", annotate=notes, **recorder)), notes


def test_round_spans_partition_the_round(serve_rig, capsys):
    """On the real clock: a plain round is admit_prep + decode_dispatch +
    token_fetch + emit (+ window_log at its cadence) with under 5 % of self
    time; a round that admits also has prefill_dispatch.  Since PR 42 the
    round dispatches program n+1 and then fetches program n: the first round
    fetches nothing, the last dispatches nothing, and ``token_fetch`` names
    the round dispatched one call earlier."""
    import time

    sess, notes = _traced_session(serve_rig, time.perf_counter)
    rng = np.random.RandomState(3)
    for r in _requests(rng, 10):
        sess.submit(r, max_new=8)
    while sess.has_work():
        sess.step()
    sess.finalize()
    rounds = [e for e in notes.events if e["name"] == "serve/round"]
    # N decode programs take N + 1 rounds: one more dispatch than fetch in the first, one fewer in the last
    assert len(rounds) == sess.stats.decode_steps + 1 and len(rounds) >= 9
    assert sess.stats.rounds_ahead == sess.stats.decode_steps - 1 and sess.stats.tokens_discarded == 0
    plain_self, kinds = [], set()
    for i, rd in enumerate(rounds):
        kids = notes.children(rd)
        names = [k["name"] for k in kids]
        for a, b in zip(kids, kids[1:]):
            assert a["end"] <= b["start"]  # siblings, in order, none nested in another
        wave = "serve/prefill_dispatch" in names
        kinds.add(wave)
        first, last = i == 0, i == len(rounds) - 1
        tail = ([] if last else ["serve/decode_dispatch"]) + ([] if first else ["serve/token_fetch", "serve/emit"])
        logs = ["serve/window_log"] if names[-1] == "serve/window_log" else []
        if wave:
            assert names == ["serve/admit_prep", "serve/prefill_dispatch"] + tail + logs
            assert set(kids[0]["stats"]) == {"n", "queue_wait_us_sum"}
            # the wave's dispatch says what it admitted and what its programs computed
            assert kids[1]["stats"] == {"rows": kids[0]["stats"]["n"], "rows_computed": 4}
        else:
            assert names == ["serve/admit_prep"] + tail + logs
            assert kids[0]["stats"] == {}
            if not last:
                covered = sum(k["end"] - k["start"] for k in kids)
                plain_self.append(1.0 - covered / (rd["end"] - rd["start"]))
        # the admitting admit_prep and its prefill_dispatch carry counters, and every round's
        # decode_dispatch says how many slots held a request and how many the cache streamed (PR 35):
        # every one here, K/V caches having no step that walks the live slots alone (PR 36);
        # and whether the round before it was still unfetched (PR 42): every round but the first
        for e in [rd] + kids[2 if wave else 1:]:
            if e["name"] == "serve/decode_dispatch":
                assert set(e["stats"]) == {"slots_live", "slots_streamed", "ahead",
                                           "cross_positions_live", "cross_positions_streamed"}
                assert 1 <= e["stats"]["slots_live"] <= e["stats"]["slots_streamed"] == sess.eng.S
                # a seq2seq round's cross attention (PR 46): the live slots' source positions over the
                # decoder layers, against every slot's whole source width where XLA runs the step (here)
                layers, st = len(sess.state["ckv"]), e["stats"]
                assert 0 < st["cross_positions_live"] <= st["slots_live"] * sess.eng.W * layers
                assert st["cross_positions_streamed"] == sess.eng.S * sess.eng.W * layers
                assert e["stats"]["ahead"] == (0 if first else 1)
            else:
                assert e["stats"] == {}
    assert kinds == {True, False} and not sess.eng.streams_live_slots
    assert sorted(plain_self)[len(plain_self) // 2] < 0.05
    logged = sum("serve/window_log" in [k["name"] for k in notes.children(rd)] for rd in rounds)
    assert logged == sess.stats.decode_steps // 3  # log_every_steps=3, counted in fetched rounds
    capsys.readouterr()


def test_queue_wait_counters_on_a_virtual_clock(serve_rig, capsys):
    clock = _Clock()
    sess, notes = _traced_session(serve_rig, clock)
    rng = np.random.RandomState(4)
    reqs = _requests(rng, 5)
    clock.t = 10.0
    rids = [sess.submit(reqs[0], max_new=4, arrival=9.25)] + [sess.submit(r, max_new=4) for r in reqs[1:4]]
    clock.t = 10.5
    rids.append(sess.submit(reqs[4], max_new=4, arrival=10.125))
    assert rids == [0, 1, 2, 3, 4] and notes.events == []  # submit opens no span: the driver has its own
    clock.t = 11.0
    sess.step()  # admits the first wave of 4 at t = 11
    clock.t = 12.0
    sess.step()  # and the fifth request at t = 12
    preps = [e for e in notes.events if e["name"] == "serve/admit_prep" and e["stats"]]
    # queue wait = the admit_prep span's start minus the request's arrival (submit instant when none was given)
    assert [e["stats"] for e in preps] == [
        {"n": 4, "queue_wait_us_sum": 1_750_000 + 3 * 1_000_000}, {"n": 1, "queue_wait_us_sum": 1_875_000}]
    # the ring keeps the round under its bare name, and no counter
    assert sess.spans.window_step_records() == [{"dur": 11.0, "spans": {"round": 0.0}}, {"dur": 1.0, "spans": {"round": 0.0}}]
    # the engine's own stamps are the spans' clock reads
    assert sess.submit_t == [10.0] * 4 + [10.5] and sess.admit_t == [11.0] * 4 + [12.0]
    while sess.has_work():
        sess.step()
    sess.finalize()
    capsys.readouterr()


def test_serve_span_ring_is_bounded(serve_rig, capsys):
    import time

    sess, notes = _traced_session(serve_rig, time.perf_counter, ring_size=4)
    rng = np.random.RandomState(5)
    for r in _requests(rng, 10):
        sess.submit(r, max_new=8)
    while sess.has_work():
        sess.step()
    assert sess.stats.decode_steps > 4
    assert len(sess.spans._ring) == len(sess.spans._step_records) == 4
    sess.finalize()
    capsys.readouterr()


def test_round_syncs_and_event_fields_are_what_they_were(serve_rig, monkeypatch, capsys):
    """The counting pin (PR 3's technique): a seq2seq round fetches exactly
    once (the token vector), wave or not, and never blocks another way; the
    serve events keep every field they had, serve_summary gains host_spans
    (PR 25), the cache's bytes by kind of leaf (PR 28) and the waves by the
    rows their programs computed (PR 30)."""
    import inspect
    import json as _json

    from distributed_llms_example_tpu.serving import engine as engine_mod

    calls = {"device_get": 0, "block_until_ready": 0}
    real_get, real_block = jax.device_get, jax.block_until_ready
    monkeypatch.setattr(jax, "device_get", lambda x: (calls.__setitem__("device_get", calls["device_get"] + 1), real_get(x))[1])
    monkeypatch.setattr(jax, "block_until_ready", lambda x: (calls.__setitem__("block_until_ready", calls["block_until_ready"] + 1), real_block(x))[1])
    assert ".block_until_ready(" not in inspect.getsource(engine_mod.ServeSession)
    eng, params = serve_rig
    sess = eng.open(params)
    rng = np.random.RandomState(6)
    for r in _requests(rng, 10):
        sess.submit(r, max_new=8)
    capsys.readouterr()
    rounds = 0
    while sess.has_work():
        before = calls["device_get"]
        sess.step()
        rounds += 1
        # the first round dispatches and fetches nothing; every later one fetches the round before it
        assert calls["device_get"] - before == (0 if rounds == 1 else 1)
    assert calls == {"device_get": rounds - 1, "block_until_ready": 0}
    assert sess.stats.decode_steps == rounds - 1
    sess.finalize()
    events = [_json.loads(x) for x in capsys.readouterr().out.splitlines() if x.startswith("{")]
    window = next(e for e in events if e.get("event") == "serve_window")
    assert set(window) == {
        "event", "step", "decode_tokens_per_sec", "decode_tokens_per_sec_chip", "slot_occupancy", "queue_depth",
        "arrival_rate_per_sec", "service_rate_per_sec", "queue_growth", "prefill_ms", "decode_ms",
        "cache_bytes_in_use", "cache_bytes_per_token"}
    assert window["decode_ms"] > 0 and window["prefill_ms"] > 0
    summary = next(e for e in events if e.get("event") == "serve_summary")
    was = {
        "event", "sequences", "decode_steps", "decode_tokens", "decode_tokens_per_sec", "decode_tokens_per_sec_chip",
        "ttft_p50_ms", "ttft_p95_ms", "queue_delay_p50_ms", "queue_delay_p95_ms", "queue_delay_p99_ms",
        "ttft_queue_p50_ms", "ttft_queue_p95_ms", "ttft_prefill_p50_ms", "ttft_prefill_p95_ms", "ttft_queue_share",
        "ttft_prefill_share", "goodput_tokens_per_sec", "goodput_tokens_per_sec_chip", "slot_occupancy",
        "prefill_seconds", "slots", "chips", "kv_cache_dtype", "paged_kv", "prefill_buckets", "cache_bytes_resident",
        "peak_cache_bytes_in_use", "cache_bytes_per_token", "memory_account", "hbm_headroom_gib"}
    # PR 28 adds the static cache bytes by kind of leaf, beside cache_bytes_resident
    # PR 41: the count of compilations after set-up was over (obs/setup.py), process-wide
    # PR 42: the round's order in two counts (rounds dispatched ahead of the last fetch, tokens dropped after an EOS)
    assert set(summary) - {"peak_hbm_bytes"} == was | {
        "host_spans", "kv_bytes", "conv_state_bytes", "prefill_waves_by_rows", "late_compiles",
        "rounds_ahead", "tokens_discarded"}
    assert summary["rounds_ahead"] == summary["decode_steps"] - 1 and summary["tokens_discarded"] == 0
    assert summary["prefill_waves_by_rows"] == {"4": 3}  # 10 requests as 4 + 4 + 2; the mesh shards 4 rows
    assert summary["kv_bytes"] > 0 and summary["conv_state_bytes"] == 0
    host = summary["host_spans"]
    assert host["window_steps"] == rounds and host["spans"]["round"]["count"] == rounds
    assert set(host["spans"]) == {"round", "admit_prep", "prefill_dispatch", "decode_dispatch",
                                  "token_fetch", "emit", "window_log"}
    # a seq2seq wave's prefill seconds ARE its dispatch span's; the decode seconds run from fetch end to
    # fetch end (the cadence a client sees), so they hold what lies between two fetches (dispatches,
    # waves, emit) and, but for the first round's dispatch, add up to the span from the first dispatch
    # to the last fetch
    assert summary["prefill_seconds"] == pytest.approx(host["spans"]["prefill_dispatch"]["total_ms"] / 1e3, abs=2e-3)
    fetched = host["spans"]["decode_dispatch"]["total_ms"] + host["spans"]["token_fetch"]["total_ms"]
    assert fetched * 0.98 <= sess.stats.decode_seconds * 1e3 <= host["spans"]["round"]["total_ms"] * 1.02


def test_serving_programs_are_named(mesh8, caplog, capsys):
    """``_wrap`` gives each jitted program its own name (``jit_serve_<name>`` on
    the profiler's XLA Modules line), and ``trace_counts`` keeps its keys."""
    import logging

    from distributed_llms_example_tpu.parallel.sharding import shard_params
    from distributed_llms_example_tpu.serving.engine import ServeConfig, ServingEngine

    lm = load_model("bart-test")
    eng = ServingEngine(
        lm.module, lm.config, mesh8,
        ServeConfig(max_slots=4, prefill_batch=4, max_new_tokens=4, max_source_length=16, log_every_steps=0),
        is_seq2seq=True,
    )
    with caplog.at_level(logging.DEBUG, logger="jax._src.dispatch"):
        eng.open(shard_params(lm.init_params(0), mesh8)).finalize()
    compiled = {m.split("compilation of ")[1].split(" in ")[0]
                for m in (r.getMessage() for r in caplog.records) if "Finished XLA compilation of " in m}
    assert {"jit(serve_prefill)", "jit(serve_admit)", "jit(serve_decode_step)"} <= compiled
    assert not any("counted" in name for name in compiled)
    assert set(eng.trace_counts) == {"prefill", "admit", "decode_step"}
    capsys.readouterr()


def test_compute_goodput_slo_arithmetic():
    """The goodput fields pinned on hand numbers: useful tokens are the
    tokens of requests whose TTFT met the SLO; attainment counts finished
    requests only; no SLO → every finished token is useful and the SLO
    fields are absent."""
    from distributed_llms_example_tpu.serving.engine import compute_goodput

    ttft = [0.1, 0.4, None, 0.2]  # request 2 never finished
    tokens = [10, 20, 99, 30]
    g = compute_goodput(
        ttft, tokens, wall_s=2.0, ttft_slo_ms=250.0, n_chips=2
    )
    # met: requests 0 and 3 → 40 useful tokens over 2 s
    assert g["goodput_tokens_per_sec"] == 20.0
    assert g["goodput_tokens_per_sec_chip"] == 10.0
    assert g["ttft_slo_ms"] == 250.0
    assert g["slo_attainment"] == pytest.approx(2 / 3, abs=1e-4)
    # SLO off: all finished tokens are useful, no attainment claim
    g0 = compute_goodput(ttft, tokens, wall_s=2.0, ttft_slo_ms=0.0, n_chips=2)
    assert g0["goodput_tokens_per_sec"] == 30.0
    assert "slo_attainment" not in g0
    # nothing finished at all: zero goodput, zero attainment
    g_none = compute_goodput(
        [None, None], [5, 5], wall_s=1.0, ttft_slo_ms=100.0, n_chips=1
    )
    assert g_none["goodput_tokens_per_sec"] == 0.0
    assert g_none["slo_attainment"] == 0.0


def test_engine_matches_static_batching_causal(mesh8):
    from distributed_llms_example_tpu.evaluation.generation import CausalGenerator
    from distributed_llms_example_tpu.parallel.activation import activation_mesh
    from distributed_llms_example_tpu.parallel.sharding import shard_params
    from distributed_llms_example_tpu.serving.engine import (
        ServeConfig,
        ServingEngine,
        trim_eos,
    )

    lm = load_model("llama-test")
    params = shard_params(lm.init_params(0), mesh8)
    rng = np.random.RandomState(9)
    reqs = _requests(rng, 6, lo=3, hi=14, vocab=120)
    W, L = 16, 8
    eng = ServingEngine(
        lm.module, lm.config, mesh8,
        ServeConfig(max_slots=4, prefill_batch=4, max_new_tokens=L,
                    max_source_length=W, log_every_steps=0),
        is_seq2seq=False,
    )
    outs = eng.generate(params, reqs)
    gen = CausalGenerator(lm.module, lm.config, L, num_beams=1)
    run = jax.jit(gen.run)
    ref = []
    for lo in range(0, len(reqs), 2):
        chunk = reqs[lo : lo + 2]
        ids = np.full((2, W), lm.config.pad_token_id, np.int32)
        mask = np.zeros((2, W), np.int32)
        for r, req in enumerate(chunk):
            ids[r, : len(req)] = req
            mask[r, : len(req)] = 1
        with activation_mesh(None):
            got = np.asarray(run(params, jnp.asarray(ids), jnp.asarray(mask)))
        ref.extend(got[r].tolist() for r in range(len(chunk)))
    eos, pad = lm.config.eos_token_id, lm.config.pad_token_id
    for got, want in zip(outs, ref):
        assert trim_eos(got, eos, pad) == trim_eos(want, eos, pad)


# ------------------- the decode kernel over live slots alone; bart's cross step on it (PR 46)


@pytest.fixture
def one_device_mesh():
    """A forced ``attention_impl="flash"`` runs the kernels (interpreted) only
    under a mesh context: pytest's eight virtual devices without one select XLA."""
    from distributed_llms_example_tpu.core.config import MeshConfig
    from distributed_llms_example_tpu.core.mesh import build_mesh

    return build_mesh(MeshConfig(data=-1), devices=jax.devices()[:1])


def _serve_all(sess, reqs, budgets):
    """Serve ``reqs`` through the session to the end; -> each request's tokens."""
    rids = [sess.submit(r, max_new=b) for r, b in zip(reqs, budgets)]
    while sess.has_work():
        sess.step()
    sess.finalize()
    return [list(sess.outputs[r]) for r in rids]


@pytest.mark.parametrize("impl", ["flash", "auto"], ids=["kernel", "xla"])
def test_bart_cross_step_over_mostly_idle_slots_serves_the_unbatched_tokens(impl, one_device_mesh, monkeypatch, capsys):
    """bart-test, eight slots of which at most three hold a request, sources of
    unequal length (one tile of the cross leaf and two): greedy tokens equal the
    unbatched reference's, with the cross step (and the self step) on the decode
    kernel, interpreted, and on XLA's path.  The slots keep the cross K/V as a
    cache keeps K/V, and ``serve/decode_dispatch`` says what the cross step
    reads: whole tiles of the live slots where the kernel runs, every slot's
    whole width elsewhere."""
    import time

    from distributed_llms_example_tpu.obs.spans import SpanRecorder
    from distributed_llms_example_tpu.ops import flash_attention as fa
    from distributed_llms_example_tpu.serving.engine import ServeConfig, ServingEngine, static_batch_generate, trim_eos

    monkeypatch.setattr(fa, "MAX_BLOCK", 128)  # so that a 256-wide source is two kv tiles
    kernel_calls, flash_decode = [], fa.flash_decode
    monkeypatch.setattr(fa, "flash_decode", lambda q, k, v, bias=None, **kw: (
        kernel_calls.append((k.shape, kw.get("live") is not None)), flash_decode(q, k, v, bias, **kw))[1])
    lm = load_model("bart-test")
    module, config = _with_impl(lm, impl)
    params = lm.init_params(0)
    W, L, S = 256, 16, 8
    rng = np.random.RandomState(13)
    reqs = [list(rng.randint(4, 200, n)) for n in (5, 200, 90)]
    budgets = [16, 9, 12]
    eng = ServingEngine(module, config, one_device_mesh,
                        ServeConfig(max_slots=S, prefill_batch=2, max_new_tokens=L, max_source_length=W,
                                    log_every_steps=0, request_spans=False), is_seq2seq=True)
    notes = _Annotations(time.perf_counter)
    sess = eng.open(params, spans=SpanRecorder(clock=time.perf_counter, scope="serve", annotate=notes))
    layers = len(sess.state["ckv"])
    assert {x.shape for x in jax.tree.leaves(sess.state["ckv"])} == {(S, W, config.d_model)}  # (slots, length, heads x d)
    outs = _serve_all(sess, reqs, budgets)
    xla_module, xla_config = _with_impl(lm, "xla")
    ref = static_batch_generate(xla_module, xla_config, one_device_mesh, params, reqs, max_new_tokens=L, width=W, batch=1)
    eos, pad = config.eos_token_id, config.pad_token_id
    for got, want, budget in zip(outs, ref, budgets):
        g = trim_eos(got, eos, pad)
        assert g == trim_eos(want, eos, pad)[: len(g)] and len(g) <= budget
    rounds = [e["stats"] for e in notes.events if e["name"] == "serve/decode_dispatch"]
    assert rounds and all("kv_positions_streamed" not in st for st in rounds)  # the causal cache's counters
    kernel = impl == "flash"
    # the step's traces: the kernel for the self leaves and the cross leaves, told who is live, or not at all
    assert set(kernel_calls) == ({((S, L, config.d_model), True), ((S, W, config.d_model), True)} if kernel else set())
    assert sess._cross_reads == [((W, 128 if kernel else 0), layers)]
    tiles = {5: 128, 200: 256, 90: 128}  # a source's positions as whole tiles of 128
    seen = set()
    for st in rounds:
        assert 0 < st["cross_positions_live"] <= st["cross_positions_streamed"]
        assert st["slots_live"] <= 3 < S
        if kernel:
            assert st["cross_positions_streamed"] <= st["slots_live"] * W * layers < S * W * layers
            seen.add((st["cross_positions_live"] // layers, st["cross_positions_streamed"] // layers))
        else:
            assert st["cross_positions_streamed"] == S * W * layers
    if kernel:  # each mix of live sources reads its own whole tiles: all three, then as the budgets end
        assert {(5 + 200 + 90, 128 + 256 + 128), (5 + 90, 128 + 128), (5, 128)} <= seen
        for live, streamed in seen:
            assert streamed == sum(tiles[n] for n in _subset(live, (5, 200, 90)))
    capsys.readouterr()


def _subset(total, parts):
    """The subset of ``parts`` that sums to ``total`` (the live sources of a round)."""
    import itertools

    return next(c for r in range(len(parts) + 1) for c in itertools.combinations(parts, r) if sum(c) == total)


def test_causal_round_counts_the_whole_tiles_of_its_live_slots(one_device_mesh, monkeypatch, capsys):
    """``kv_positions_streamed`` on the kernel's path: of each live slot the
    whole kv tiles up to its write position, of an idle slot nothing; on XLA's
    path every slot's whole leaf."""
    import time

    from distributed_llms_example_tpu.obs.spans import SpanRecorder
    from distributed_llms_example_tpu.ops import flash_attention as fa
    from distributed_llms_example_tpu.serving.engine import ServeConfig, ServingEngine

    monkeypatch.setattr(fa, "MAX_BLOCK", 128)
    lm = load_model("llama-test")
    W, L, S = 240, 16, 4  # a K/V leaf of 256 positions: two kv tiles
    rng = np.random.RandomState(5)
    reqs = [list(rng.randint(4, 120, n)) for n in (7, 130)]
    tokens = {}
    for impl in ("flash", "xla"):
        module, config = _with_impl(lm, impl)
        config = dataclasses.replace(config, eos_token_id=None)
        eng = ServingEngine(module, config, one_device_mesh,
                            ServeConfig(max_slots=S, prefill_batch=2, max_new_tokens=L, max_source_length=W,
                                        prefill_buckets=(W,), log_every_steps=0, request_spans=False), is_seq2seq=False)
        notes = _Annotations(time.perf_counter)
        sess = eng.open(lm.init_params(0), spans=SpanRecorder(clock=time.perf_counter, scope="serve", annotate=notes))
        tokens[impl] = _serve_all(sess, reqs, [6, 6])
        layers = len(sess._kv_lengths)
        rounds = [e["stats"] for e in notes.events if e["name"] == "serve/decode_dispatch"]
        assert rounds and layers == lm.config.num_hidden_layers
        for st in rounds:
            assert st["slots_live"] == 2 and st["kv_positions_live"] <= st["kv_positions_streamed"]
            # every prompt sits in the one bucket of 240, so a slot writes at 240 + its tokens so far: the second tile
            assert st["kv_positions_streamed"] == (2 * 256 if impl == "flash" else S * 256) * layers
    assert tokens["flash"] == tokens["xla"]
    capsys.readouterr()


def test_t5_serving_and_beam_evaluation_keep_their_cross_layout(one_device_mesh, capsys):
    """What PR 46 leaves alone: T5's engine slots hold the cross K/V as (slots,
    heads, length, head_dim) (its own attention class reads it) and count it as
    read whole; ``generation.py`` hands bart's decode the 4-D pair, whose beam
    path folds beams beside heads; both serve and search the tokens they did."""
    from distributed_llms_example_tpu.evaluation.generation import make_beam_search
    from distributed_llms_example_tpu.serving.engine import ServeConfig, ServingEngine, static_batch_generate, trim_eos

    t5 = load_model("t5-test")
    params = t5.init_params(0)
    rng = np.random.RandomState(3)
    reqs = _requests(rng, 3)
    eng = ServingEngine(t5.module, t5.config, one_device_mesh,
                        ServeConfig(max_slots=4, prefill_batch=2, max_new_tokens=8, max_source_length=32,
                                    log_every_steps=0, request_spans=False), is_seq2seq=True)
    assert not eng.cross_kv_rows
    sess = eng.open(params)
    heads, d_kv = t5.config.num_heads, t5.config.d_kv
    assert {x.shape for x in jax.tree.leaves(sess.state["ckv"])} == {(4, heads, 32, d_kv)}
    assert sess._cross_reads == [((32, 0), len(sess.state["ckv"]))]
    outs = _serve_all(sess, reqs, [8] * 3)
    ref = static_batch_generate(t5.module, t5.config, one_device_mesh, params, reqs, max_new_tokens=8, width=32, batch=1)
    eos, pad = t5.config.eos_token_id, t5.config.pad_token_id
    assert [trim_eos(o, eos, pad) for o in outs] == [trim_eos(r, eos, pad) for r in ref]
    # beam evaluation of bart: the rows layout is the engine's to ask for, never generation's
    bart = load_model("bart-test")
    bp = bart.init_params(0)
    ids = rng.randint(4, 200, (2, 16)).astype(np.int32)
    mask = np.ones((2, 16), np.int32)
    mask[1, -6:] = 0
    enc = bart.module.apply({"params": bp}, ids, mask, method="encode")
    pairs = bart.module.apply({"params": bp}, enc, method="cross_kv")
    assert {x.ndim for x in jax.tree.leaves(pairs)} == {4}
    rows = bart.module.apply({"params": bp}, enc, True, method="cross_kv")
    for (k4, v4), (k3, v3) in zip(pairs, rows):  # the same numbers, laid as a cache keeps them
        np.testing.assert_array_equal(np.asarray(_leaf(k4)), np.asarray(k3))
        np.testing.assert_array_equal(np.asarray(_leaf(v4)), np.asarray(v3))
    beams = {}
    for impl in ("xla", "flash"):
        mod, cfg = _with_impl(bart, impl)
        beams[impl] = np.asarray(make_beam_search(mod, cfg, 8, num_beams=2)(bp, ids, mask))
    np.testing.assert_array_equal(beams["xla"], beams["flash"])
    capsys.readouterr()


# ---------------------------- the round's order: dispatch ahead, fetch behind (PR 42)

# one engine a kind of slot state, at toy sizes: more requests than slots, waves
# smaller than the slot count, so admissions and evictions fall mid-stream
RUN_AHEAD_KINDS = {
    "flat-seq2seq": ("bart-test", True, {}),
    "flat-causal": ("llama-test", False, {}),
    "paged": ("llama-test", False, {"paged_kv": True, "kv_block_size": 8}),
    "paged-prefix": ("llama-test", False, {"paged_kv": True, "kv_block_size": 8, "prefix_cache": True,
                                           "prefix_cache_budget_gib": 0.01}),
    "retention-state": ("brumby-test", False, {}),
    "conv-state-and-experts": ("lfm2-moe-test", False, {}),
}


@pytest.fixture(scope="module")
def ahead_rigs():
    """``rig(kind)`` -> (engine, params, requests, budgets), built once a kind."""
    import dataclasses

    from distributed_llms_example_tpu.serving.engine import ServeConfig, ServingEngine

    built = {}

    def rig(kind):
        if kind not in built:
            name, seq2seq, modes = RUN_AHEAD_KINDS[kind]
            lm = load_model(name)
            config = lm.config
            if not seq2seq:
                # the tests below choose the end-of-sequence id themselves (a host-side
                # comparison: no program reads it)
                config = dataclasses.replace(config, eos_token_id=None)
            eng = ServingEngine(
                lm.module, config, None,
                ServeConfig(max_slots=3, prefill_batch=2, max_new_tokens=8, max_source_length=16,
                            log_every_steps=0, request_spans=False, **modes),
                is_seq2seq=seq2seq,
            )
            rng = np.random.RandomState(11)
            reqs = _requests(rng, 8, lo=3, hi=14, vocab=120)
            if "prefix_cache" in modes:
                # two requests that share a whole block with an earlier one, so a warm admission runs
                reqs[0], reqs[1] = (list(rng.randint(4, 120, 12)) for _ in range(2))
                reqs[5] = reqs[0][:8] + reqs[5][:4]
                reqs[6] = reqs[1][:8] + reqs[6][:3]
            budgets = [int(b) for b in rng.randint(2, 9, len(reqs))]
            built[kind] = (eng, lm.init_params(0), reqs, budgets)
        return built[kind]

    return rig


def _serve(eng, params, reqs, budgets, *, lockstep=False, late=3, eos=None):
    """Serve ``reqs`` through a session, the last ``late`` submitted mid-stream;
    ``lockstep`` pins the round to depth 0 (dispatch, fetch, emit: the oracle)."""
    from distributed_llms_example_tpu.serving.engine import ServeSession

    old_eos = eng.eos
    eng.eos = eos if eos is not None else old_eos
    try:
        sess = eng.open(params)
        if lockstep:
            assert isinstance(ServeSession._depth, property)  # what the oracle overrides
            sess.__class__ = type("LockstepSession", (ServeSession,), {"_depth": property(lambda self: 0)})
        rids = [sess.submit(r, max_new=b) for r, b in zip(reqs[:-late], budgets[:-late])]
        finished = []
        for _ in range(4):
            finished += sess.step()
        rids += [sess.submit(r, max_new=b) for r, b in zip(reqs[-late:], budgets[-late:])]
        while sess.has_work():
            finished += sess.step()
        stats = sess.finalize()
        assert sorted(finished) == rids  # every request is reported finished, once
        return [list(sess.outputs[r]) for r in rids], stats
    finally:
        eng.eos = old_eos


@pytest.mark.parametrize("kind", sorted(RUN_AHEAD_KINDS))
def test_running_ahead_serves_the_lockstep_tokens(ahead_rigs, kind, capsys):
    """(a) + (c): a session that dispatches round n+1 before it fetches round n
    serves, token for token, what the lockstep round serves (and, for the flat
    seq2seq engine, what static batching does), through admissions and evictions
    mid-stream; requests that end by budget discard nothing, and every round but
    one a dispatch streak's first is dispatched ahead."""
    eng, params, reqs, budgets = ahead_rigs(kind)
    want, lock = _serve(eng, params, reqs, budgets, lockstep=True)
    got, stats = _serve(eng, params, reqs, budgets)
    assert got == want and [len(g) for g in got] == budgets
    assert lock.rounds_ahead == 0 and lock.tokens_discarded == 0
    assert stats.tokens_discarded == 0 and stats.decode_tokens == lock.decode_tokens
    # a slot is free when its request's last token is EMITTED, a round after it was computed: with
    # three slots for eight requests an admission may wait a round longer, never a token more
    assert lock.decode_steps <= stats.decode_steps <= lock.decode_steps + len(reqs)
    # a steady run: the device is never without a queued program after the first dispatch
    assert stats.rounds_ahead == stats.decode_steps - 1
    if kind == "flat-seq2seq":
        from distributed_llms_example_tpu.serving.engine import static_batch_generate, trim_eos

        lm = load_model("bart-test")
        ref = static_batch_generate(lm.module, lm.config, None, params, reqs, max_new_tokens=8, width=16, batch=4)
        eos, pad = lm.config.eos_token_id, lm.config.pad_token_id
        for g, w in zip(got, ref):
            assert trim_eos(g, eos, pad) == trim_eos(w, eos, pad)[: len(g)]
    if kind == "paged-prefix":
        assert stats.prefix_hits >= 2
    capsys.readouterr()


@pytest.mark.parametrize("kind", sorted(RUN_AHEAD_KINDS))
@pytest.mark.parametrize("where", ["mid-stream", "first-token"])
def test_a_request_that_ends_by_eos_is_found_one_round_late(ahead_rigs, kind, where, capsys):
    """(b): the token the round in flight computed for a slot whose request had
    ended by EOS is in no output and is counted; the slot is admitted again and
    the next request there is served right."""
    eng, params, reqs, budgets = ahead_rigs(kind)
    budgets = [max(b, 4) for b in budgets]
    free, _ = _serve(eng, params, reqs, budgets, lockstep=True)
    # an id that ends an early request where the case wants it: its first token (the wave's), or a later one
    if where == "first-token":
        eos = free[0][0]
    else:
        eos = next(o[t] for o in free[:3] for t in range(1, len(o) - 1) if o[t] not in o[:t])
    cut = [o[: o.index(eos) + 1] if eos in o else o for o in free]
    if where == "first-token":
        assert len(cut[0]) == 1
    else:
        assert any(1 < len(c) < len(o) for c, o in zip(cut[:3], free))
    want, lock = _serve(eng, params, reqs, budgets, lockstep=True, eos=eos)
    got, stats = _serve(eng, params, reqs, budgets, eos=eos)
    # the oracle with that id is the free run cut at it (greedy: a request's tokens do not depend on its neighbours)
    assert want == cut and got == cut
    ended_early = sum(1 for o, b in zip(cut, budgets) if o[-1] == eos and len(o) < b)
    assert ended_early >= 1 and stats.tokens_discarded == ended_early and lock.tokens_discarded == 0
    assert stats.decode_tokens == lock.decode_tokens
    capsys.readouterr()


def test_dispatch_opens_before_the_fetch_of_the_round_before_closes(serve_rig, capsys):
    """(d): the recorder's order.  ``decode_dispatch`` of round n+1 opens before
    ``token_fetch`` of round n closes; at every fetch one more round has been
    dispatched than fetched, until the last round fetches with nothing behind it."""
    clock = _Clock()
    sess, notes = _traced_session(serve_rig, clock)
    rng = np.random.RandomState(12)
    for r in _requests(rng, 3):
        sess.submit(r, max_new=5)
    order = []
    real_span = sess.spans.span

    def ticking(name):
        clock.t += 1.0  # every span opens at an instant of its own
        return real_span(name)

    sess.spans.span = ticking
    while sess.has_work():
        sess.step()
        clock.t += 1.0
    for e in notes.events:
        if e["name"] in ("serve/decode_dispatch", "serve/token_fetch"):
            order.append(e)
    names = [e["name"].split("/")[1] for e in order]
    assert names == ["decode_dispatch"] + ["decode_dispatch", "token_fetch"] * 4 + ["token_fetch"]
    dispatches = [e for e in order if e["name"].endswith("decode_dispatch")]
    fetches = [e for e in order if e["name"].endswith("token_fetch")]
    for n, fetch in enumerate(fetches[:-1]):
        # round n's fetch closes after round n+1's dispatch opened (and closed: one thread)
        assert dispatches[n + 1]["start"] < fetch["end"] and dispatches[n]["end"] < dispatches[n + 1]["start"]
    assert [d["stats"]["ahead"] for d in dispatches] == [0, 1, 1, 1, 1]
    assert sess.finalize().decode_steps == 5
    capsys.readouterr()


@pytest.mark.parametrize("how", ["has_work", "finalize"])
def test_no_in_flight_token_is_lost(ahead_rigs, how, capsys):
    """(d): a round in flight keeps the session at work, and closing the books
    with one in flight fetches and serves it first."""
    eng, params, reqs, budgets = ahead_rigs("flat-causal")
    want, _ = _serve(eng, params, reqs[:2], [5, 5], lockstep=True, late=1)
    if how == "finalize":
        sess = eng.open(params)
        rids = [sess.submit(r, max_new=5) for r in reqs[:2]]
        sess.step()  # the wave (token 1) and round 1 dispatched
        sess.step()  # round 2 dispatched, round 1 fetched: two tokens held, a third in flight
        assert [len(sess.outputs[r]) for r in rids] == [2, 2] and sess._inflight
        sess.finalize()
        assert [list(sess.outputs[r]) for r in rids] == [w[:3] for w in want] and not sess._inflight
    else:
        # an id that ends the request mid-stream: the round dispatched before it was seen is then in flight for nobody
        at = next(j for j in range(1, 4) if want[0][j] not in want[0][:j])
        old, eng.eos = eng.eos, want[0][at]
        try:
            sess = eng.open(params)
            rid = sess.submit(reqs[0], max_new=5)
            for _ in range(at):  # the wave (token 1) and round 1 dispatched; then a round dispatched and one fetched
                assert sess.step() == []
            assert sess.step() == [rid]  # round at + 1 dispatched, round at fetched: the EOS
            assert not sess.active.any() and not sess.pending
            assert sess.has_work()  # the round dispatched before the EOS was seen is still to fetch
            assert sess.step() == [] and not sess.has_work()
            stats = sess.finalize()
            assert list(sess.outputs[rid]) == want[0][: at + 1] and stats.tokens_discarded == 1
        finally:
            eng.eos = old
    capsys.readouterr()


def test_a_speculative_session_stays_in_lockstep(capsys):
    """(e): a verify round is built from the fetched tokens (``outputs[rid][-1]``,
    the n-gram history), so it is dispatched and fetched in the same round."""
    from distributed_llms_example_tpu.serving.engine import ServeConfig, ServingEngine

    lm = load_model("llama-test")
    params = lm.init_params(0)
    rng = np.random.RandomState(13)
    reqs = _requests(rng, 5, lo=3, hi=14, vocab=120)
    serve = dict(max_slots=2, prefill_batch=2, max_new_tokens=8, max_source_length=16, log_every_steps=0,
                 request_spans=False)
    plain = ServingEngine(lm.module, lm.config, None, ServeConfig(**serve), is_seq2seq=False)
    spec = ServingEngine(lm.module, lm.config, None, ServeConfig(spec_tokens=2, **serve), is_seq2seq=False)
    want = plain.generate(params, reqs)
    # (two slots that fill and end together: a round with every last token in flight dispatches nothing)
    assert 0 < plain.last_stats.rounds_ahead < plain.last_stats.decode_steps
    got = spec.generate(params, reqs)
    assert got == want
    assert spec.last_stats.rounds_ahead == 0 and spec.last_stats.tokens_discarded == 0
    assert spec.last_stats.spec_steps == spec.last_stats.decode_steps > 0
    capsys.readouterr()


def test_engine_validates_composition_and_shards():
    from distributed_llms_example_tpu.core.config import MeshConfig
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.serving.engine import ServeConfig, ServingEngine

    lm = load_model("t5-test", load_weights=False)
    mesh = build_mesh(MeshConfig(data=2, fsdp=2, sequence=1, tensor=2))
    with pytest.raises(ValueError, match="batch shards"):
        ServingEngine(
            lm.module, lm.config, mesh,
            ServeConfig(max_slots=4, prefill_batch=2), is_seq2seq=True,
        )
    seq_mesh = build_mesh(MeshConfig(data=4, fsdp=1, sequence=2, tensor=1))
    with pytest.raises(ValueError, match="sequence"):
        ServingEngine(
            lm.module, lm.config, seq_mesh,
            ServeConfig(max_slots=4, prefill_batch=4), is_seq2seq=True,
        )


def test_decode_composition_rows():
    from distributed_llms_example_tpu.analysis.composition import failing_combos

    assert not failing_combos(flags=("decode", "seq2seq"), mesh_axes={"data": 4, "fsdp": 2})
    assert not failing_combos(flags=("decode", "causal"), mesh_axes={"fsdp": 4, "tensor": 2})
    bad = failing_combos(flags=("decode", "seq2seq"), mesh_axes={"stage": 2, "data": 4})
    assert [row.id for row in bad] == ["decode-pipelined"]
    bad = failing_combos(flags=("decode", "causal"), mesh_axes={"sequence": 2, "data": 4})
    assert [row.id for row in bad] == ["decode-sequence"]


# -------------------------------------------------------------- serve CLI


@pytest.mark.slow
def test_serve_cli_end_to_end(tmp_path):
    import json

    from distributed_llms_example_tpu.launch.cli import serve_main

    prompts = tmp_path / "prompts.json"
    prompts.write_text(json.dumps([
        {"dialogue": f"prompt number {i} with some words", "summary": "x"}
        for i in range(5)
    ]))
    out = tmp_path / "out.jsonl"
    rc = serve_main([
        "--model-ckpt", "t5-test",
        "--prompts-file", str(prompts),
        "--output-file", str(out),
        "--max-slots", "8", "--prefill-batch", "8",
        "--max-new-tokens", "8", "--max-source-length", "32",
        "--compute-dtype", "float32", "--log-every-steps", "0",
    ])
    assert rc == 0
    recs = [json.loads(line) for line in out.read_text().splitlines()]
    assert len(recs) == 5
    assert all({"prompt", "output", "tokens"} <= set(r) for r in recs)
