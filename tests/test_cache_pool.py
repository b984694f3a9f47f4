"""Decode capacity: int8 KV cache, paged block-pool allocation, bucketed
prefill (ISSUE 13) — allocator properties, kernel parity, engine token
parity (paged+bucketed bit-identical to flat; int8 at a stated tolerance),
zero-recompile churn, capacity gauges, pool spec lint."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_example_tpu.models.registry import load_model
from distributed_llms_example_tpu.ops.attention import NEG_INF, dot_product_attention
from distributed_llms_example_tpu.ops.flash_attention import (
    dequantize_kv,
    flash_decode,
    flash_decode_paged,
    quantize_kv,
)
from distributed_llms_example_tpu.serving import cache_pool
from distributed_llms_example_tpu.serving.engine import (
    ServeConfig,
    ServingEngine,
    static_batch_generate,
    trim_eos,
)


# ------------------------------------------------------------- allocator


def test_pool_alloc_free_properties():
    """Property sweep: random interleaved alloc/free keeps the invariants —
    no block handed out twice, free+used == total, and (blocks being
    identityless) any request within the free count succeeds no matter how
    fragmented the history (fragmentation cannot strand capacity)."""
    rng = np.random.RandomState(0)
    pool = cache_pool.CachePool(num_blocks=37, block_size=8)
    held: list[list[int]] = []
    seen_concurrent: set[int] = set()
    for _ in range(500):
        if held and rng.rand() < 0.45:
            grant = held.pop(rng.randint(len(held)))
            pool.free(grant)
            seen_concurrent.difference_update(grant)
        else:
            n = int(rng.randint(1, 9))
            grant = pool.alloc(n)
            if n <= 37 - len(seen_concurrent):
                assert grant is not None and len(grant) == n
            if grant is None:
                continue
            assert not (set(grant) & seen_concurrent), "block double-granted"
            seen_concurrent.update(grant)
            held.append(grant)
        assert pool.blocks_free + pool.blocks_in_use == 37
        assert pool.blocks_in_use == len(seen_concurrent)
    for grant in held:
        pool.free(grant)
    assert pool.blocks_in_use == 0 and pool.blocks_free == 37
    # after arbitrary churn, a full-pool request still succeeds whole
    assert pool.alloc(37) is not None


def test_pool_refusal_and_free_errors():
    pool = cache_pool.CachePool(num_blocks=4, block_size=8)
    got = pool.alloc(3)
    assert got is not None
    # refusal is total, never a partial grant
    assert pool.alloc(2) is None
    assert pool.blocks_free == 1
    pool.free(got)
    with pytest.raises(ValueError, match="double-free|not allocated"):
        pool.free(got)
    with pytest.raises(ValueError, match="not allocated"):
        pool.free([99])


def test_blocks_needed_and_block_row():
    # 5-token prompt at block 8 → 1 block; 9 → 2; budget 8 → 1
    assert cache_pool.blocks_needed(5, 8, 8) == 2
    assert cache_pool.blocks_needed(9, 8, 8) == 3
    row = cache_pool.build_block_row(
        6, [10, 11, 12], prompt_len=9, bucket_width=32, budget=8,
        block_size=8, sentinel=99,
    )
    # prompt tiles [0,2) allocated, gap [2,4) sentinel, decode tile at
    # 32//8 = 4 allocated, tail sentinel
    assert row.tolist() == [10, 11, 99, 99, 12, 99]
    with pytest.raises(ValueError, match="multiple of the block size"):
        cache_pool.build_block_row(
            6, [1, 2], prompt_len=3, bucket_width=20, budget=4,
            block_size=8, sentinel=99,
        )


def test_gather_scatter_round_trip():
    """Pool plumbing unit: admit-scatter then gather reconstructs the
    chunk view exactly (zeros at sentinel tiles); step-scatter lands one
    row in the owning block; sentinel/parked writes drop.  Leaves are
    (slots, length, heads x head_dim), pool blocks (blocks, block, heads x
    head_dim)."""
    S, HD, bs, nt = 2, 6, 4, 3
    N = 5
    rng = np.random.RandomState(1)
    chunk = jnp.asarray(rng.randn(S, nt * bs, HD).astype(np.float32))
    pool_tree = {"cached_key": jnp.zeros((N, bs, HD), jnp.float32)}
    # row 0: tiles 0,1 → blocks 0,1; row 1: tile 0 → block 2; rest sentinel
    admit = jnp.asarray(np.array([0, 1, N, 2, N, N], np.int32))
    pool_tree = cache_pool.scatter_admit(
        pool_tree, {"cached_key": chunk}, admit, bs
    )
    bt = jnp.asarray(np.array([[0, 1, N], [2, N, N]], np.int32))
    view = cache_pool.gather_cache(pool_tree, bt)["cached_key"]
    want = np.asarray(chunk).copy()
    want[0, 2 * bs :, :] = 0.0
    want[1, bs:, :] = 0.0
    np.testing.assert_array_equal(np.asarray(view), want)
    # step write at position 5 of row 0 (tile 1, in-block 1) and a PARKED
    # row 1 (offset = width → must drop)
    new_cache = {"cached_key": jnp.asarray(rng.randn(S, nt * bs, HD).astype(np.float32))}
    offs = jnp.asarray(np.array([5, nt * bs], np.int32))
    before = np.asarray(pool_tree["cached_key"]).copy()
    pool_tree = cache_pool.scatter_step(
        pool_tree, new_cache, bt, offs, num_blocks=N, block_size=bs
    )
    after = np.asarray(pool_tree["cached_key"])
    # row 0's position 5 = tile 1, in-block slot 1 → exactly block 1
    # changed, at exactly that slot
    np.testing.assert_array_equal(
        after[1, 1, :], np.asarray(new_cache["cached_key"])[0, 5, :]
    )
    untouched = np.ones((bs,), bool)
    untouched[1] = False
    np.testing.assert_array_equal(
        after[1][untouched, :], before[1][untouched, :]
    )
    # every other block untouched — including row 1's (PARKED: offset =
    # width → the write dropped) and the never-allocated spares
    for blk in (0, 2, 3, 4):
        np.testing.assert_array_equal(after[blk], before[blk])


def test_tree_bytes_and_block_bytes():
    tree = {
        "k": jax.ShapeDtypeStruct((4, 2, 8, 4), jnp.int8),
        "s": jax.ShapeDtypeStruct((4, 2, 8), jnp.float32),
        "i": jax.ShapeDtypeStruct((), jnp.int32),
    }
    assert cache_pool.tree_bytes(tree) == 4 * 2 * 8 * 4 + 4 * 2 * 8 * 4 + 4
    assert cache_pool.block_bytes(tree, 4) == 2 * 8 * 4 + 2 * 8 * 4


# ----------------------------------------------------- int8 quantization


def test_quantize_kv_round_trip_bound():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(2, 3, 5, 16).astype(np.float32) * 3.0)
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == (2, 3, 5)
    err = np.abs(np.asarray(dequantize_kv(q, s)) - np.asarray(x))
    # symmetric round-to-nearest: |err| <= scale/2 per element
    assert (err <= np.asarray(s)[..., None] / 2 + 1e-7).all()
    # all-zero rows stay exactly zero (scale guard, no NaN)
    q0, s0 = quantize_kv(jnp.zeros((1, 1, 2, 8)))
    assert np.asarray(dequantize_kv(q0, s0)).sum() == 0.0


def _leaf(x):
    """(B, H, L, d) as the cache (or, with B blocks, the pool) keeps it: (B, L, H x d)."""
    x = jnp.asarray(x)
    b, h, length, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, length, h * d)


def _scale_leaf(s):
    """(B, H, L) int8 scales as the cache keeps them: (B, L, H)."""
    return jnp.asarray(s).transpose(0, 2, 1)


def test_flash_decode_int8_scales_parity():
    """Kernel in-VMEM dequant == XLA dequantize_kv + dense attention —
    the identical-expression contract the dispatches rely on."""
    rng = np.random.RandomState(3)
    B, H, L, d = 3, 4, 64, 16
    q = jnp.asarray(rng.randn(B, H, 1, d).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, L, d).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, L, d).astype(np.float32))
    bias = jnp.asarray(
        np.where(rng.rand(B, 1, 1, L) > 0.2, 0.0, NEG_INF).astype(np.float32)
    )
    offsets = jnp.array([0, 17, L - 1], jnp.int32)
    qk, ks = quantize_kv(k)
    qv, vs = quantize_kv(v)
    out = flash_decode(
        q, _leaf(qk), _leaf(qv), bias, offsets=offsets,
        k_scale=_scale_leaf(ks), v_scale=_scale_leaf(vs),
    )
    k_pos = jnp.arange(L)[None, None, None, :]
    step = jnp.where(k_pos <= offsets[:, None, None, None], 0.0, NEG_INF)
    ref = dot_product_attention(
        q, dequantize_kv(qk, ks), dequantize_kv(qv, vs), bias + step
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


# ------------------------------------------------------- paged kernel


def _paged_fixture(rng, B, H, L, d, bs, extra_blocks=2):
    """K/V (B, H, L, d) and the same tiles scattered over a pool, a pool
    block held as (H, bs, d) here (``_leaf`` lays it as the pool keeps it)."""
    nt = L // bs
    N = B * nt + extra_blocks
    k = rng.randn(B, H, L, d).astype(np.float32)
    v = rng.randn(B, H, L, d).astype(np.float32)
    perm = rng.permutation(B * nt)
    bt = np.zeros((B, nt), np.int32)
    k_pool = np.zeros((N, H, bs, d), np.float32)
    v_pool = np.zeros((N, H, bs, d), np.float32)
    for b in range(B):
        for t in range(nt):
            blk = int(perm[b * nt + t])
            bt[b, t] = blk
            k_pool[blk] = k[b, :, t * bs : (t + 1) * bs, :]
            v_pool[blk] = v[b, :, t * bs : (t + 1) * bs, :]
    return k, v, k_pool, v_pool, bt, N


@pytest.mark.parametrize("q_len,H,d", [(1, 4, 16), (4, 4, 16), (8, 16, 64)], ids=["q1", "q4", "q8-cell-heads"])
def test_flash_decode_paged_matches_flat(q_len, H, d):
    """The block-table kernel (scalar-prefetch indexed pool blocks) is
    bit-identical to flash_decode over the flattened view of the same
    blocks — scrambled block order and all."""
    rng = np.random.RandomState(4)
    B, L, bs = 3, 64, 16
    k, v, k_pool, v_pool, bt, N = _paged_fixture(rng, B, H, L, d, bs)
    q = jnp.asarray(rng.randn(B, H, q_len, d).astype(np.float32))
    bias = jnp.asarray(
        np.where(rng.rand(B, 1, 1, L) > 0.2, 0.0, NEG_INF).astype(np.float32)
    )
    offsets = jnp.array([0, 30, L - q_len], jnp.int32)
    # same tile size on both sides: the online softmax accumulates in
    # tile order, so bit-identity is a same-tiling property
    flat = flash_decode(
        q, _leaf(k), _leaf(v), bias, offsets=offsets, block_k=bs
    )
    paged = flash_decode_paged(
        q, _leaf(k_pool), _leaf(v_pool), bias,
        block_tables=jnp.asarray(bt), offsets=offsets,
    )
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(flat))
    # sentinel (unallocated) tiles beyond each row's last position change nothing
    bt2 = bt.copy()
    for b in range(B):
        for t in range(L // bs):
            if t * bs > int(offsets[b]) + q_len - 1:
                bt2[b, t] = N
    paged2 = flash_decode_paged(
        q, _leaf(k_pool), _leaf(v_pool), bias,
        block_tables=jnp.asarray(bt2), offsets=offsets,
    )
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(paged2))


def test_flash_decode_paged_int8_compose():
    """int8 scales compose with paging: pool-resident s8 blocks + scale
    blocks reproduce the flat int8 kernel exactly."""
    rng = np.random.RandomState(5)
    B, H, L, d, bs = 2, 2, 32, 16, 8
    k, v, k_pool, v_pool, bt, N = _paged_fixture(rng, B, H, L, d, bs)
    qk, ks = quantize_kv(jnp.asarray(k))
    qv, vs = quantize_kv(jnp.asarray(v))
    nt = L // bs
    kqp = np.zeros((N, H, bs, d), np.int8)
    vqp = np.zeros((N, H, bs, d), np.int8)
    ksp = np.zeros((N, H, bs), np.float32)
    vsp = np.zeros((N, H, bs), np.float32)
    for b in range(B):
        for t in range(nt):
            blk = int(bt[b, t])
            kqp[blk] = np.asarray(qk)[b, :, t * bs : (t + 1) * bs, :]
            vqp[blk] = np.asarray(qv)[b, :, t * bs : (t + 1) * bs, :]
            ksp[blk] = np.asarray(ks)[b, :, t * bs : (t + 1) * bs]
            vsp[blk] = np.asarray(vs)[b, :, t * bs : (t + 1) * bs]
    q = jnp.asarray(rng.randn(B, H, 1, d).astype(np.float32))
    offsets = jnp.array([7, L - 1], jnp.int32)
    flat = flash_decode(
        q, _leaf(qk), _leaf(qv), offsets=offsets,
        k_scale=_scale_leaf(ks), v_scale=_scale_leaf(vs), block_k=bs,
    )
    paged = flash_decode_paged(
        q, _leaf(kqp), _leaf(vqp),
        block_tables=jnp.asarray(bt), offsets=offsets,
        k_scale_pool=_scale_leaf(ksp), v_scale_pool=_scale_leaf(vsp),
    )
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(flat))


# ------------------------------------------------------- engine parity


def _llama_requests(rng, n=8, lo=3, hi=14):
    return [list(rng.randint(4, 120, rng.randint(lo, hi))) for _ in range(n)]


def _engine(lm, *, is_seq2seq, W, L, slots=2, **kw):
    return ServingEngine(
        lm.module, lm.config, None,
        ServeConfig(
            max_slots=slots, prefill_batch=slots, max_new_tokens=L,
            max_source_length=W, log_every_steps=0, request_spans=False, **kw,
        ),
        is_seq2seq=is_seq2seq,
    )


@pytest.fixture(scope="module")
def llama_runs():
    """One flat-f32 llama serving run shared by the parity tests."""
    lm = load_model("llama-test")
    params = lm.init_params(0)
    rng = np.random.RandomState(7)
    reqs = _llama_requests(rng)
    W, L = 16, 8
    eng = _engine(lm, is_seq2seq=False, W=W, L=L)
    outs = eng.generate(params, reqs)
    return lm, params, reqs, W, L, eng, outs


def test_engine_paged_bucketed_bit_identical(llama_runs):
    """THE acceptance pin: paged + bucketed admission produces tokens
    BIT-identical to the flat full-width f32 engine, while the pool
    drains to zero at the end (evict returned every block) and bytes per
    live token drop (blocks track actual prompt length, not max)."""
    lm, params, reqs, W, L, flat_eng, flat = llama_runs
    eng = _engine(
        lm, is_seq2seq=False, W=W, L=L,
        paged_kv=True, kv_block_size=8, prefill_buckets=(8,),
    )
    outs = eng.generate(params, reqs)
    assert outs == flat
    assert eng.pool.blocks_in_use == 0
    assert (
        eng.last_stats.bytes_per_live_token
        < flat_eng.last_stats.bytes_per_live_token
    )


def test_engine_paged_default_block_size(llama_runs):
    """kv_block_size=0 (the CLI default) must construct: the auto block
    divides gcd(cache width, every admission bucket) — auto_block(W+L)
    alone is wrong whenever it doesn't divide W (here auto_block(24)=0
    and 24 itself doesn't tile the W=16 bucket).  Still bit-identical."""
    lm, params, reqs, W, L, _, flat = llama_runs
    eng = _engine(lm, is_seq2seq=False, W=W, L=L, paged_kv=True)
    assert (W + L) % eng.block_size == 0
    for b in eng.buckets:
        assert b % eng.block_size == 0
    assert eng.generate(params, reqs) == flat


def test_engine_paged_admit_refusal_small_pool(llama_runs):
    """A pool sized below the workload's concurrency DEFERS admissions
    (free list short) instead of over-committing — every request still
    completes with identical tokens once evictions free blocks."""
    lm, params, reqs, W, L, _, flat = llama_runs
    worst = cache_pool.blocks_needed(W, L, 8)
    eng = _engine(
        lm, is_seq2seq=False, W=W, L=L,
        paged_kv=True, kv_block_size=8, pool_blocks=worst,
    )
    outs = eng.generate(params, reqs)
    assert outs == flat
    assert eng.last_stats.admit_deferrals > 0
    assert eng.pool.blocks_in_use == 0
    # an unservable pool is rejected at construction, not livelocked
    with pytest.raises(ValueError, match="worst-case request"):
        _engine(
            lm, is_seq2seq=False, W=W, L=L,
            paged_kv=True, kv_block_size=8, pool_blocks=worst - 1,
        )


def test_engine_pool_garbage_invariant(llama_runs):
    """Stale-block-unreachable, restated per block (the PR 7 slot-reuse
    argument): poison the ENTIRE pool at init — every block then behaves
    like a freed block full of a previous owner's data — and the engine
    still produces the flat engine's exact tokens, because every read is
    masked to the owner's written region."""
    lm, params, reqs, W, L, _, flat = llama_runs
    eng = _engine(lm, is_seq2seq=False, W=W, L=L, paged_kv=True, kv_block_size=8)
    orig = eng._init_state

    def poisoned(p):
        st = orig(p)
        st["pool"] = jax.tree.map(
            lambda x: jnp.full(x.shape, 1e3, x.dtype) if x.ndim >= 3 else x,
            st["pool"],
        )
        return st

    eng._init_state = poisoned
    assert eng.generate(params, reqs) == flat


def test_engine_int8_all_flags_vs_static(llama_runs):
    """Determinism under ALL THREE flags combined: the int8+paged+bucketed
    engine is token-identical to the static int8 runner (same quantized
    cache on both sides), and zero programs retrace across a second full
    admit/evict/bucket churn (AOT-warmed, compile-count pinned)."""
    lm, params, reqs, W, L, _, _ = llama_runs
    eos, pad = lm.config.eos_token_id, lm.config.pad_token_id
    static8 = static_batch_generate(
        lm.module, lm.config, None, params, reqs,
        max_new_tokens=L, width=W, batch=2, is_seq2seq=False,
        kv_cache_dtype="int8",
    )
    eng = _engine(
        lm, is_seq2seq=False, W=W, L=L,
        kv_cache_dtype="int8", paged_kv=True, kv_block_size=8,
        prefill_buckets=(8,),
    )
    outs = eng.generate(params, reqs)
    for got, want in zip(outs, static8):
        assert trim_eos(got, eos, pad) == trim_eos(want, eos, pad)
    # one trace per bucket and wave size (1 row, 2 rows) for prefill/admit,
    # ONE decode step — and no retrace on a second serve over the same engine
    assert eng.trace_counts == {"prefill": 4, "admit": 4, "decode_step": 1}
    eng.generate(params, reqs)
    assert eng.trace_counts == {"prefill": 4, "admit": 4, "decode_step": 1}


def test_engine_int8_token_match_rates(llama_runs):
    """The int8 tolerance contract: engine-int8 vs engine-f32 greedy
    token match.  t5-test holds the >= 0.99 bar; llama-test's random-init
    logits are near-uniform (the argmax-stability worst case — one
    near-tie flip cascades through the greedy prefix), so it pins the
    measured-with-margin rate plus the BIT-exact engine==static-int8
    determinism above.  Real checkpoints with confident logits sit at the
    >= 0.99 contract (README 'Serving capacity')."""
    lm, params, reqs, W, L, _, flat = llama_runs
    eos, pad = lm.config.eos_token_id, lm.config.pad_token_id

    def match_rate(a_rows, b_rows):
        match = total = 0
        for a, b in zip(a_rows, b_rows):
            ta, tb = trim_eos(a, eos, pad), trim_eos(b, eos, pad)
            n = min(len(ta), len(tb))
            total += max(len(ta), len(tb))
            match += sum(x == y for x, y in zip(ta[:n], tb[:n]))
        return match / max(total, 1)

    i8 = _engine(lm, is_seq2seq=False, W=W, L=L, kv_cache_dtype="int8")
    assert match_rate(flat, i8.generate(params, reqs)) >= 0.85
    # int8 footprint: the static account matches the closed form
    # 4D/(D+4) exactly (s8 buffers + one f32 scale per D-row); >= 3.5x
    # needs head_dim >= 64 — the production shapes — while the D=16 test
    # models land at exactly 3.2x
    d = lm.config.hidden_size // lm.config.num_attention_heads
    flat_eng = llama_runs[5]
    ratio = (
        flat_eng.last_stats.cache_bytes_resident
        / i8.last_stats.cache_bytes_resident
    )
    want = 4 * d / (d + 4)
    assert ratio == pytest.approx(want, rel=0.02)
    assert 4 * 64 / (64 + 4) >= 3.5  # the production head-dim claim

    # the seq2seq test model carries the >= 0.99 pin
    lm2 = load_model("t5-test")
    p2 = lm2.init_params(0)
    rng = np.random.RandomState(11)
    reqs2 = [list(rng.randint(4, 200, rng.randint(4, 28))) for _ in range(6)]
    e_f32 = _engine(lm2, is_seq2seq=True, W=32, L=8)
    e_i8 = _engine(lm2, is_seq2seq=True, W=32, L=8, kv_cache_dtype="int8")
    eos, pad = lm2.config.eos_token_id, lm2.config.pad_token_id
    assert match_rate(e_f32.generate(p2, reqs2), e_i8.generate(p2, reqs2)) >= 0.99


def test_engine_sustained_pool_pressure_no_starvation(llama_runs):
    """ISSUE 15 satellite: admit-deferral under SUSTAINED pool pressure —
    3x the fixture's load through a minimal pool (one worst-case request)
    — defers continually but eventually completes EVERY request with the
    flat engine's exact tokens (no starvation: FIFO admission means a
    deferred request admits as soon as evictions fund it), and the pool
    drains to empty."""
    lm, params, _, W, L, flat_eng, _ = llama_runs
    rng = np.random.RandomState(3)
    reqs = _llama_requests(rng, n=24)
    # the flat fixture engine's programs are already compiled: its run is
    # the completeness+correctness oracle at zero extra trace cost
    flat = flat_eng.generate(params, reqs)
    worst = cache_pool.blocks_needed(W, L, 8)
    eng = _engine(
        lm, is_seq2seq=False, W=W, L=L,
        paged_kv=True, kv_block_size=8, pool_blocks=worst,
    )
    outs = eng.generate(params, reqs)
    assert outs == flat
    assert all(len(o) >= 1 for o in outs)  # every request produced output
    # pressure was genuinely sustained, not a one-off dip
    assert eng.last_stats.admit_deferrals >= 5
    assert eng.pool.blocks_in_use == 0


def test_engine_pool_blocks_all_returned_random_churn(llama_runs):
    """ISSUE 15 satellite: evict-on-done returns EVERY pool block under
    randomized admit/evict churn — random prompt lengths and budgets
    over several waves on one engine; after each wave the free list
    holds exactly the full block set (leak AND double-free would both
    break the set equality)."""
    lm, params, _, W, L, _, _ = llama_runs
    eng = _engine(lm, is_seq2seq=False, W=W, L=L, paged_kv=True, kv_block_size=8)
    all_blocks = set(range(eng.pool.num_blocks))
    rng = np.random.RandomState(11)
    for wave in range(3):
        reqs = _llama_requests(rng, n=10, lo=3, hi=14)
        budgets = [int(b) for b in rng.randint(1, L + 1, len(reqs))]
        eng.generate(params, reqs, max_new=budgets)
        assert eng.pool.blocks_in_use == 0, f"wave {wave} leaked blocks"
        assert set(eng.pool._free) == all_blocks, f"wave {wave} corrupted free list"


def test_engine_seq2seq_buckets_identical_and_warm():
    """Bucketed admission on the seq2seq engine: identical tokens to the
    single-width engine, one compiled prefill/admit per bucket and wave
    size (all AOT-warmed at first generate), capacity gauges in the summary."""
    lm = load_model("t5-test")
    params = lm.init_params(0)
    rng = np.random.RandomState(13)
    reqs = [list(rng.randint(4, 200, rng.randint(4, 28))) for _ in range(6)]
    flat = _engine(lm, is_seq2seq=True, W=32, L=8).generate(params, reqs)
    eng = _engine(lm, is_seq2seq=True, W=32, L=8, prefill_buckets=(8, 16))
    outs = eng.generate(params, reqs)
    assert outs == flat
    assert eng.trace_counts == {"prefill": 6, "admit": 6, "decode_step": 1}
    assert eng.last_stats.cache_bytes_resident > 0
    assert eng.last_stats.bytes_per_live_token > 0


def test_engine_rejects_bad_capacity_configs():
    lm = load_model("t5-test", load_weights=False)
    with pytest.raises(ValueError, match="f32.*int8|'f32' or 'int8'"):
        _engine(lm, is_seq2seq=True, W=32, L=8, kv_cache_dtype="fp8")
    with pytest.raises(ValueError, match="paged_kv applies to the causal"):
        _engine(lm, is_seq2seq=True, W=32, L=8, paged_kv=True)
    clm = load_model("llama-test", load_weights=False)
    with pytest.raises(ValueError, match="does not tile"):
        _engine(clm, is_seq2seq=False, W=16, L=8, paged_kv=True, kv_block_size=7)
    with pytest.raises(ValueError, match="not a multiple of the kv block"):
        _engine(
            clm, is_seq2seq=False, W=16, L=8,
            paged_kv=True, kv_block_size=8, prefill_buckets=(12,),
        )


# ------------------------------------------------------- spec lint / rules


def test_int8_cache_scale_leaves_lint_green():
    """CACHE_RULES covers the int8 cache's scale leaves: the lint is green
    on the quantized abstract cache, and a rule set WITHOUT the scale rule
    errors on every scale leaf (unmatched-cache-leaf — the strengthened
    3-D check)."""
    from jax.sharding import PartitionSpec as P

    from distributed_llms_example_tpu.analysis.spec_lint import lint_cache_sharding
    from distributed_llms_example_tpu.evaluation.generation import abstract_cache
    from distributed_llms_example_tpu.parallel.sharding import ShardingRules

    axes = {"data": 2, "fsdp": 2, "tensor": 2}
    for name, seq2seq in (("t5-test", True), ("llama-test", False)):
        lm = load_model(name, load_weights=False)
        a_params = jax.eval_shape(lambda lm=lm: lm.init_params(0))
        cache = abstract_cache(
            lm.module, a_params, batch=8, max_new_tokens=16, src_len=32,
            is_seq2seq=seq2seq, kv_cache_dtype="int8",
        )
        leaves = jax.tree.leaves(cache)
        assert any(getattr(x, "dtype", None) == jnp.int8 for x in leaves)
        assert any(
            getattr(x, "ndim", 0) == 3 for x in leaves
        ), "int8 cache should carry (B, H, L) scale leaves"
        findings = lint_cache_sharding(cache, axes)
        errors = [f for f in findings if f.severity == "error"]
        assert not errors, errors
    # drop the scale rule → every scale leaf is an unmatched error
    lm = load_model("t5-test", load_weights=False)
    a_params = jax.eval_shape(lambda: lm.init_params(0))
    cache = abstract_cache(
        lm.module, a_params, batch=8, max_new_tokens=16, src_len=32,
        kv_cache_dtype="int8",
    )
    bad = ShardingRules(rules=[
        (r"(cached_key|cached_value)$", P(("data", "fsdp"), "tensor", None, None)),
        (r"cache_index$", P()),
    ])
    findings = lint_cache_sharding(cache, axes, rules=bad)
    assert any(
        f.code == "unmatched-cache-leaf" and "_scale" in f.message
        for f in findings
    )


def test_pool_rules_lint_and_scale_spec(mesh8):
    """POOL_RULES validates the pool tree like CACHE_RULES validates the
    flat cache (blocks never shard over batch axes, heads over tensor) —
    and cache_leaf_spec resolves the scale layout on the real mesh."""
    from jax.sharding import PartitionSpec as P

    from distributed_llms_example_tpu.analysis.spec_lint import lint_cache_sharding
    from distributed_llms_example_tpu.evaluation.generation import abstract_cache
    from distributed_llms_example_tpu.parallel.sharding import (
        cache_leaf_spec,
        cache_rules,
        pool_rules,
        resolve_shardings,
    )

    lm = load_model("llama-test", load_weights=False)
    a_params = jax.eval_shape(lambda: lm.init_params(0))
    cache = abstract_cache(
        lm.module, a_params, batch=8, max_new_tokens=16, src_len=32,
        is_seq2seq=False, kv_cache_dtype="int8",
    )
    pool_tree = jax.eval_shape(lambda: cache_pool.pool_cache_tree(cache, 12, 8))
    findings = lint_cache_sharding(
        pool_tree, {"data": 2, "fsdp": 2, "tensor": 2}, rules=pool_rules()
    )
    errors = [f for f in findings if f.severity == "error"]
    assert not errors, errors
    # scale leaves resolve on the 8-device mesh per CACHE_RULES
    sh = resolve_shardings(cache, mesh8, cache_rules())
    scales = [
        (jax.tree_util.keystr(p), s.spec)
        for p, s in jax.tree_util.tree_leaves_with_path(sh)
        if "_scale" in jax.tree_util.keystr(p)
    ]
    assert scales
    for path, spec in scales:
        assert spec[0] == ("data", "fsdp", "expert"), (path, spec)
        assert spec[1] is None and spec[2] == "tensor", (path, spec)  # (slots, length, heads)
    # the one definition both sides derive from, by the leaf's name
    axes = dict(mesh8.shape)
    assert cache_leaf_spec("key_scale", (8, 24, 4), axes, 4)[2] == "tensor"
    assert cache_leaf_spec("cached_value", (8, 24, 64), axes, 4)[1:] == (None, "tensor")
    assert cache_leaf_spec("conv_state", (8, 64, 2), axes, 4)[1:] == ("tensor", None)
    assert cache_leaf_spec("cached_key", (8, 24, 3), axes, 3)[2] is None  # a ragged axis replicates
    assert cache_leaf_spec("cache_index", (), axes, 4) is None
    # ragged HEADS: llama-test's 2 KV heads of 16 on tensor=4.  The merged
    # axis' 32 lanes would divide, the heads do not: K/V replicate over
    # tensor as their scales do (and as the kernel's eligibility reads it),
    # so no shard holds half a head
    axes4 = {"data": 2, "fsdp": 1, "tensor": 4}
    for name, width in (("cached_key", 32), ("cached_value", 32), ("key_scale", 2)):
        assert cache_leaf_spec(name, (8, 24, width), axes4, 2)[2] is None, name
        assert cache_leaf_spec(name, (40, 16, width), axes4, 2, pool=True) == P(None, None, None), name
    assert cache_leaf_spec("cached_key", (8, 24, 64), axes4, 4)[2] == "tensor"
    # the block pool: the K/V layout with the block dim never sharded
    assert cache_leaf_spec("cached_key", (40, 16, 64), axes4, 4, pool=True) == P(None, None, "tensor")
    assert cache_leaf_spec("value_scale", (40, 16, 4), axes4, 4, pool=True) == P(None, None, "tensor")


# ----------------------------------------------- prefix cache: pool unit


def test_chain_hash_collision_discipline():
    """Chained identity: a block's hash commits to its WHOLE prefix, so
    equal hash at position k implies blocks 0..k-1 matched too; token
    boundaries are part of the identity (no concatenation ambiguity);
    the partial tail block has no identity at all."""
    bs = 4
    a = cache_pool.chain_hashes([1, 2, 3, 4, 5, 6, 7, 8], bs)
    b = cache_pool.chain_hashes([9, 9, 9, 9, 5, 6, 7, 8], bs)
    assert len(a) == len(b) == 2
    # identical second-block TOKENS, different predecessor → different hash
    assert a[0] != b[0] and a[1] != b[1]
    # extending past a full block never perturbs the existing chain
    c = cache_pool.chain_hashes([1, 2, 3, 4, 5, 6, 7, 8, 9], bs)
    assert c == a  # the 1-token tail is unhashed (no stable identity)
    # boundary discipline: [1, 23] vs [12, 3] must not collide
    assert cache_pool.block_hash(None, [1, 23]) != cache_pool.block_hash(None, [12, 3])


def test_pool_register_first_writer_wins_and_acquire_errors():
    pool = cache_pool.CachePool(num_blocks=8, block_size=4)
    pool.warm_capacity = 8
    h = cache_pool.chain_hashes([1, 2, 3, 4], 4)
    b1 = pool.alloc(1)
    b2 = pool.alloc(1)
    pool.register(b1, h)
    pool.register(b2, h)  # duplicate content: first writer keeps the hash
    assert pool.lookup(h[0]) == b1[0]
    assert pool.match_chain(h) == b1
    # the anonymous duplicate reclaims to the FREE list, not the warm LRU
    pool.free(b2)
    assert pool.blocks_warm == 0
    # the registered block parks warm at refcount 0...
    pool.free(b1)
    assert pool.blocks_warm == 1 and pool.match_chain(h) == b1
    # ...and revives via acquire
    pool.acquire(b1)
    assert pool.blocks_in_use == 1 and pool.blocks_warm == 0
    pool.free(b1)
    # a chain match gone stale (block neither live nor warm) raises
    pool.drop_warm()
    with pytest.raises(ValueError, match="neither live nor warm"):
        pool.acquire(b1)


def test_pool_warm_lru_eviction_order():
    """Warm retention evicts strictly oldest-first; re-acquire refreshes
    recency; alloc pressure reclaims warm blocks before refusing; and
    drop_warm clears the whole set (the replica-death path)."""
    pool = cache_pool.CachePool(num_blocks=4, block_size=4)
    pool.warm_capacity = 2
    chains = [cache_pool.chain_hashes([i, i, i, i], 4) for i in (1, 2, 3)]
    blocks = []
    for h in chains:
        (b,) = pool.alloc(1)
        pool.register([b], h)
        blocks.append(b)
    for b in blocks:
        pool.free([b])  # park in order 0, 1, 2 — capacity 2 evicts 0
    assert pool.blocks_warm == 2
    assert pool.match_chain(chains[0]) == []
    assert pool.match_chain(chains[1]) == [blocks[1]]
    # revive 1 then re-park: now 1 is NEWEST, so pressure evicts 2 first
    pool.acquire([blocks[1]])
    pool.free([blocks[1]])
    grant = pool.alloc(3)  # 2 free + 1 evicted warm (block 2, the oldest)
    assert grant is not None
    assert pool.match_chain(chains[2]) == []
    assert pool.match_chain(chains[1]) == [blocks[1]]
    pool.free(grant)
    assert pool.drop_warm() == 1
    assert pool.blocks_warm == 0 and pool.match_chain(chains[1]) == []
    assert pool.blocks_free == pool.num_blocks


def test_pool_prefix_refcount_churn_invariant():
    """Property sweep over admit/share/free churn with warm retention:
    random sessions match-acquire-alloc-register like the engine's
    admission, free in random order — after EVERY operation the walked
    refcount invariant holds and the free/used/warm partition is exact."""
    rng = np.random.RandomState(17)
    pool = cache_pool.CachePool(num_blocks=30, block_size=4)
    pool.warm_capacity = 8
    live: list[list[int]] = []  # per-request block lists (the block tables)
    for _ in range(300):
        if live and rng.rand() < 0.45:
            chain = live.pop(rng.randint(len(live)))
            pool.free(list(reversed(chain)))
        else:
            # small alphabet → real prefix collisions across requests
            toks = [int(t) for t in rng.randint(0, 3, int(rng.randint(4, 17)))]
            hashes = cache_pool.chain_hashes(toks, 4)
            p = len(toks)
            chain = pool.match_chain(hashes[: (p - 1) // 4])
            k = len(chain)
            need = max(1, -(-p // 4)) - k + 1  # + one decode block
            if k:
                pool.acquire(chain)
            fresh = pool.alloc(need)
            if fresh is None:
                if k:
                    pool.free(list(reversed(chain)))  # transactional rollback
                continue
            blocks = chain + fresh
            full = p // 4
            if full:
                pool.register(blocks[:full], hashes[:full])
            live.append(blocks)
        assert pool.ref_invariant_violations(live) == []
        assert pool.blocks_free + pool.blocks_in_use == 30
    for chain in live:
        pool.free(list(reversed(chain)))
    assert pool.ref_invariant_violations([]) == []
    assert pool.blocks_in_use == 0


# ----------------------------------------------- prefix cache: engine


def _prefix_engine(lm, W, L, **kw):
    kw.setdefault("pool_blocks", 24)  # headroom: warm retention lives in it
    return _engine(
        lm, is_seq2seq=False, W=W, L=L,
        paged_kv=True, kv_block_size=8,
        prefix_cache=True, prefix_cache_budget_gib=0.25, **kw,
    )


def _chat_requests(rng, sys_len=8, n=8, lo=2, hi=8):
    sys_toks = [int(t) for t in rng.randint(4, 120, sys_len)]
    return [
        sys_toks + [int(t) for t in rng.randint(4, 120, rng.randint(lo, hi))]
        for _ in range(n)
    ]


def test_engine_prefix_warm_vs_cold_bit_identical(llama_runs):
    """THE warm-path acceptance pin (greedy): shared-prefix requests
    through the prefix cache produce tokens BIT-identical to the flat
    cold engine, with real hits (the shared system-prompt block prefills
    once), an exact reuse ledger, and a drained pool whose warm set
    holds exactly the one registered chain block.  A SECOND session on
    the same engine drops the stale warm set (its device pool was
    re-zeroed) and is bit-identical again — no cross-session splice."""
    lm, params, _, W, L, flat_eng, _ = llama_runs
    rng = np.random.RandomState(23)
    reqs = _chat_requests(rng)
    flat = flat_eng.generate(params, reqs)
    eng = _prefix_engine(lm, W, L)
    outs = eng.generate(params, reqs)
    assert outs == flat
    st = eng.last_stats
    # every request was eligible; all but the first matched the shared
    # 8-token system block (pool headroom keeps it warm/live throughout)
    assert st.prefix_lookups == len(reqs)
    assert st.prefix_hits == len(reqs) - 1
    assert st.prefill_tokens_saved == (len(reqs) - 1) * 8
    assert st.prefill_tokens_total == sum(len(r) for r in reqs)
    assert eng.pool.blocks_in_use == 0
    # all requests share ONE full block (the system prompt): first writer
    # wins, so exactly one block is registered and retained warm
    assert eng.pool.blocks_warm == 1
    # compiled-program budget: one warm_admit per bucket and wave size
    # (1 row, 2 rows), nothing retraced
    assert eng.trace_counts == {
        "prefill": 2, "admit": 2, "warm_admit": 2, "decode_step": 1,
    }
    outs2 = eng.generate(params, reqs)
    assert outs2 == flat
    assert eng.last_stats.prefix_hits == len(reqs) - 1
    assert eng.trace_counts == {
        "prefill": 2, "admit": 2, "warm_admit": 2, "decode_step": 1,
    }


def test_engine_prefix_cow_divergence_and_slot_reuse(llama_runs):
    """COW discipline through divergence and slot reuse, stepwise: A and
    B share one system block then diverge (B admits warm, holding the
    SHARED block and allocating only its own tail — never writing the
    shared block); C repeats A exactly and re-acquires A's chain from
    the warm LRU through a REUSED slot.  Tokens bit-identical to cold
    throughout, and the walked refcount invariant holds after every
    step."""
    lm, params, _, W, L, flat_eng, _ = llama_runs
    rng = np.random.RandomState(29)
    sys_toks = [int(t) for t in rng.randint(4, 120, 8)]
    a = sys_toks + [int(t) for t in rng.randint(4, 120, 5)]
    b = sys_toks + [int(t) for t in rng.randint(4, 120, 5)]
    reqs = [a, b, list(a)]
    flat = flat_eng.generate(params, reqs)
    eng = _prefix_engine(lm, W, L)
    sess = eng.open(params)
    for r in reqs:
        sess.submit(r)
    shared_in_use = None
    while sess.has_work():
        sess.step()
        assert sess.prefix_ref_violations() == []
        if shared_in_use is None and sess.active.all():
            # A and B live together: 3 blocks each (2 prompt + 1 decode)
            # MINUS the one shared system block
            shared_in_use = eng.pool.blocks_in_use
    sess.finalize()
    assert shared_in_use == 5
    assert list(sess.outputs) == flat
    st = eng.last_stats
    # B matched the system block; C matched its full chain (1 block —
    # the last prompt block always re-prefills for first-token logits)
    assert st.prefix_hits == 2
    assert st.prefix_lookups == 3
    assert eng.pool.blocks_in_use == 0
    assert eng.pool.ref_invariant_violations([]) == []


def test_engine_prefix_custom_mask_ineligible(llama_runs):
    """A request with a custom attention mask has no token-only identity:
    it neither matches nor registers (zero lookups), and tokens stay
    bit-identical to the flat engine under the same masks."""
    lm, params, _, W, L, flat_eng, _ = llama_runs
    rng = np.random.RandomState(31)
    reqs = _chat_requests(rng, n=4)
    masks = [[1] * len(r) for r in reqs]
    flat = flat_eng.generate(params, reqs, attention_masks=masks)
    eng = _prefix_engine(lm, W, L)
    outs = eng.generate(params, reqs, attention_masks=masks)
    assert outs == flat
    st = eng.last_stats
    assert st.prefix_lookups == 0 and st.prefix_hits == 0
    assert eng.pool.blocks_warm == 0  # nothing registered, nothing retained
    assert eng.pool.blocks_in_use == 0


def test_engine_prefix_warm_beam_bit_identical(llama_runs):
    """Beam-search leg of the bit-identity contract: a KV prefix
    reconstructed from WARM pool blocks matches a cold prefill's cache
    region (to the cross-program ulp — the engine's compiled prefill
    and the generator's eager one fuse differently, the same class of
    difference the engine-vs-static token pins absorb), and a
    num_beams=2 decode over the spliced carry emits exactly the cold
    run's beam tokens — the warm path changes where prefix KV comes
    from, never what it holds."""
    from distributed_llms_example_tpu.evaluation.generation import CausalGenerator

    lm, params, _, W, L, _, _ = llama_runs
    rng = np.random.RandomState(37)
    prompt = [int(t) for t in rng.randint(4, 120, 12)]
    eng = _prefix_engine(lm, W, L)
    sess = eng.open(params)
    sess.submit(list(prompt))
    while sess.has_work():
        sess.step()
    sess.finalize()
    # the finished request's full-block chain is warm and matchable
    hashes = cache_pool.chain_hashes(prompt, eng.block_size)
    chain = eng.pool.match_chain(hashes[: (len(prompt) - 1) // eng.block_size])
    assert len(chain) == 1
    bt = np.full((1, eng.n_tiles), eng.pool.num_blocks, np.int32)
    bt[0, : len(chain)] = chain
    warm_view = cache_pool.gather_cache(sess.state["pool"], jnp.asarray(bt))
    # cold reference: the generator's own prefill at the same width
    gen = CausalGenerator(lm.module, lm.config, L, num_beams=2)
    ids = np.full((1, W), lm.config.pad_token_id, np.int32)
    mask = np.zeros((1, W), np.int32)
    ids[0, : len(prompt)] = prompt
    mask[0, : len(prompt)] = 1
    carry_cold = gen.prefill(params, jnp.asarray(ids), jnp.asarray(mask))
    kbs = len(chain) * eng.block_size
    for cold, warm in zip(
        jax.tree.leaves(carry_cold["cache"]), jax.tree.leaves(warm_view)
    ):
        if getattr(cold, "ndim", 0) == 3:  # a K/V leaf, (rows, length, heads x head_dim)
            # warm pool bytes ≈ cold prefill bytes over the cached prefix
            # (exact within one program; here across two compilations)
            np.testing.assert_allclose(
                np.asarray(warm)[0, :kbs, :],
                np.asarray(cold)[0, :kbs, :], atol=1e-5,
            )

    def splice(c, w):
        if getattr(c, "ndim", 0) == 3 and c.shape[-1] == w.shape[-1]:
            rep = jnp.repeat(w[:, :kbs, :], 2, axis=0)  # K beams share it
            return c.at[:, :kbs, :].set(rep)
        return c

    carry_warm = dict(carry_cold)
    carry_warm["cache"] = jax.tree.map(splice, carry_cold["cache"], warm_view)
    out_cold = np.asarray(gen.finalize(gen.decode_loop(params, carry_cold)))
    out_warm = np.asarray(gen.finalize(gen.decode_loop(params, carry_warm)))
    np.testing.assert_array_equal(out_warm, out_cold)
