"""Flash attention (Pallas, interpret mode on CPU) vs XLA attention.

Checks forward numerics and gradients of the blockwise online-softmax
kernel against ``dot_product_attention`` — the property the reference never
tests for its cuDNN attention (SURVEY.md §4: no tests at all).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_example_tpu.ops.attention import (
    dot_product_attention,
    make_causal_bias,
    mask_to_bias,
)
from distributed_llms_example_tpu.ops.flash_attention import (
    flash_attention,
    flash_supported,
)

B, H, D = 2, 3, 32


def _qkv(q_len=256, kv_len=256, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32), dtype)  # noqa: E731
    return mk(B, H, q_len, D), mk(B, H, kv_len, D), mk(B, H, kv_len, D)


def test_forward_matches_xla():
    q, k, v = _qkv()
    out = flash_attention(q, k, v, block_q=128, block_k=128)
    ref = dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_forward_causal():
    q, k, v = _qkv(256, 256)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = dot_product_attention(q, k, v, bias=make_causal_bias(256, 256))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_forward_padding_bias():
    q, k, v = _qkv(128, 256)
    mask = np.ones((B, 256), np.int32)
    mask[0, 100:] = 0
    mask[1, 37:] = 0
    bias = mask_to_bias(jnp.asarray(mask))  # (B, 1, 1, K)
    out = flash_attention(q, k, v, bias, block_q=64, block_k=64)
    ref = dot_product_attention(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_forward_full_bias_bf16():
    q, k, v = _qkv(128, 128, dtype=jnp.bfloat16)
    rng = np.random.RandomState(1)
    bias = jnp.asarray(rng.randn(1, H, 128, 128).astype(np.float32))
    out = flash_attention(q, k, v, bias, block_q=64, block_k=64)
    ref = dot_product_attention(q, k, v, bias)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2
    )


@pytest.mark.parametrize(
    "causal,q_len,kv_len",
    [
        (False, 128, 128),
        (True, 128, 128),
        # the rectangular case exercises the bwd kernels with nq != nk (BART
        # cross-attention shape); causal+rectangular is rejected by contract
        (False, 64, 128),
    ],
)
def test_gradients_match(causal, q_len, kv_len):
    q, k, v = _qkv(q_len, kv_len)
    mask = np.ones((B, kv_len), np.int32)
    mask[0, kv_len - 38 :] = 0
    bias = mask_to_bias(jnp.asarray(mask))

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, bias, causal=causal, block_q=32, block_k=64) ** 2
        )

    def loss_ref(q, k, v):
        full = bias + (make_causal_bias(q_len, kv_len) if causal else 0.0)
        return jnp.sum(dot_product_attention(q, k, v, full) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, rtol=1e-3)


def test_causal_requires_square():
    """causal=True with q_len != kv_len is ambiguous (top-left vs decode
    bottom-right alignment) and must be rejected, not silently mis-masked."""
    q, k, v = _qkv(64, 128)
    with pytest.raises(ValueError, match="square self-attention"):
        flash_attention(q, k, v, causal=True, block_q=32, block_k=64)


def test_grad_under_jit_and_vmap_free_shapes():
    q, k, v = _qkv(128, 128)

    @jax.jit
    def f(q, k, v):
        return jnp.mean(flash_attention(q, k, v, causal=True))

    g = jax.grad(f)(q, k, v)
    assert np.isfinite(np.asarray(g)).all()


def test_flash_supported():
    assert flash_supported(1024, 1024, 64)
    assert flash_supported(128, 256, 64)
    assert not flash_supported(100, 128, 64)  # not divisible
    assert not flash_supported(4, 4, 64)  # too small
    assert not flash_supported(128, 128, 65)  # odd head dim


def test_rejects_bad_shapes():
    q, k, v = _qkv(100, 100)
    with pytest.raises(ValueError):
        flash_attention(q, k, v)


def test_auto_block_selection():
    """Largest 16-aligned divisor in [128, 512] — big tiles for the bench shapes,
    graceful degradation for odd-but-divisible lengths."""
    from distributed_llms_example_tpu.ops.flash_attention import auto_block, flash_supported

    assert auto_block(1024) == 512
    assert auto_block(512) == 512
    assert auto_block(128) == 128
    assert auto_block(640) == 320  # not divisible by 512; previously 128-tiled
    assert auto_block(64) == 64  # short sequence: one seq-sized tile
    assert auto_block(136) == 0  # no 16-aligned divisor ≥ 128 → XLA fallback
    assert auto_block(1048) == 0  # 8*131: tiny tiles would drown in grid overhead
    assert auto_block(100) == 0
    assert auto_block(7) == 0
    assert flash_supported(640, 640, 64)
    assert not flash_supported(7, 7, 64)
    assert not flash_supported(1048, 1048, 64)


def test_noncausal_block_cap():
    """Non-causal attention without a learned bias tiles up to 1024 (measured
    faster on v5e); causal stays at 512, and learned-bias caps block_q at
    512 (dlbias VMEM) while its block_k may reach 1024."""
    from distributed_llms_example_tpu.ops.flash_attention import (
        MAX_BLOCK,
        MAX_BLOCK_NONCAUSAL,
        auto_block,
    )

    assert MAX_BLOCK == 512 and MAX_BLOCK_NONCAUSAL == 1024
    assert auto_block(1024, MAX_BLOCK_NONCAUSAL) == 1024
    assert auto_block(2048, MAX_BLOCK_NONCAUSAL) == 1024
    assert auto_block(512, MAX_BLOCK_NONCAUSAL) == 512
    # flash_supported mirrors the per-path caps: 592 = 16*37 tiles only
    # above 512, so it is eligible non-causal but NOT causal; learned-bias
    # caps block_q at 512 (dlbias VMEM) while block_k may reach 1024
    assert flash_supported(592, 592, 64)
    assert not flash_supported(592, 592, 64, causal=True)
    assert not flash_supported(592, 592, 64, has_learned_bias=True)
    assert flash_supported(512, 592, 64, has_learned_bias=True)
    # correctness at the 1024 tile, interpret-mode (CPU): square + cross
    rng = np.random.RandomState(3)
    for q_len in (1024, 128):
        q = jnp.asarray(rng.randn(1, 2, q_len, 32), jnp.float32)
        k = jnp.asarray(rng.randn(1, 2, 1024, 32), jnp.float32)
        v = jnp.asarray(rng.randn(1, 2, 1024, 32), jnp.float32)
        got = flash_attention(q, k, v, causal=False)
        want = dot_product_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # gradients through the 1024-tile bwd kernels (dq/dkv grids run ONE
    # k/q block each at this size — the production bart encoder shape)
    q = jnp.asarray(rng.randn(1, 2, 1024, 32), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 1024, 32), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 1024, 32), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=False) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v) ** 2)

    got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-4)


def test_lbias_asymmetric_tiles_grad_parity():
    """The learned-bias default tiling is now ASYMMETRIC (block_q capped at
    512, block_k at 1024) — run its backward (dq/dkv/dlbias kernels) with
    block_k > block_q and check gradients against plain attention."""
    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(1, 2, 64, 32), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 128, 32), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 128, 32), jnp.float32)
    lb = jnp.asarray(rng.randn(1, 2, 64, 128).astype(np.float32) * 0.1)

    def loss_flash(q, k, v, lb):
        out = flash_attention(q, k, v, learned_bias=lb, block_q=64, block_k=128)
        return jnp.sum(out ** 2)

    def loss_ref(q, k, v, lb):
        return jnp.sum(dot_product_attention(q, k, v, lb) ** 2)

    got = jax.grad(loss_flash, argnums=(0, 1, 2, 3))(q, k, v, lb)
    want = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(q, k, v, lb)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-4)


def test_parity_non_pow2_length():
    """Auto-blocked parity at a length divisible by neither 128 nor 512."""
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(2, 2, 320, 16).astype(np.float32)) for _ in range(3))
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = dot_product_attention(q, k, v, make_causal_bias(320, 320))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_fully_masked_rows_give_finite_zero_grads():
    """The advisor's edge: causal attention plus an additive -inf padding
    bias that masks EVERY key of example 0.  Its rows' only finite scores
    are the causally-masked MASK_VALUE entries, so the saved lse lands at
    ~MASK_VALUE; the backward kernels must zero p for such rows (lse at the
    sentinel scale) or they contribute garbage — potentially inf/NaN once a
    learned bias shifts s — to the batch-summed learned-bias gradient.
    Dead-example grads must be exactly zero and the live example's grads
    (and the summed dlbias) must equal a run without the dead example."""
    q_len = kv_len = 64
    q, k, v = _qkv(q_len, kv_len)
    mask = np.ones((B, kv_len), np.float32)
    mask[0, :] = 0  # example 0: every key masked
    bias = jnp.where(jnp.asarray(mask)[:, None, None, :] > 0, 0.0, -jnp.inf)
    rng = np.random.RandomState(2)
    lbias = jnp.asarray(rng.randn(1, H, q_len, kv_len).astype(np.float32) * 0.1)

    def loss(q, k, v, lbias, bias):
        return jnp.sum(
            flash_attention(
                q, k, v, bias, learned_bias=lbias, causal=True, block_q=32, block_k=32
            )
            ** 2
        )

    # the dead example's FORWARD output must be exact zeros (not an
    # average of v over causally-forbidden positions)
    out = flash_attention(
        q, k, v, bias, learned_bias=lbias, causal=True, block_q=32, block_k=32
    )
    np.testing.assert_array_equal(np.asarray(out[0]), 0.0)
    assert np.isfinite(np.asarray(out)).all()

    g_full = jax.grad(loss, argnums=(0, 1, 2, 3))(q, k, v, lbias, bias)
    for g in g_full:
        assert np.isfinite(np.asarray(g)).all(), "NaN/inf gradient from fully-masked rows"
    # the dead example contributes nothing to its own q/k/v grads...
    for g in g_full[:3]:
        np.testing.assert_array_equal(np.asarray(g[0]), 0.0)
    # ...and nothing to the batch-summed learned-bias grad: grads must
    # match a run over the live examples only
    g_live = jax.grad(loss, argnums=(0, 1, 2, 3))(
        q[1:], k[1:], v[1:], lbias, bias[1:]
    )
    for a, b in zip(g_full[:3], g_live[:3]):
        np.testing.assert_allclose(np.asarray(a[1:]), np.asarray(b), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(g_full[3]), np.asarray(g_live[3]), atol=1e-5
    )


def test_causal_cap_is_head_dim_dependent():
    """Causal tiles cap at 512 for narrow heads (d=64: diagonal masked work
    dominates wider tiles) but 1024 at d>=128 (7B regime: -17%/-24% fwd+bwd
    at batch 4/8, measured before this round on other code; not measured on
    today's)."""
    from distributed_llms_example_tpu.ops.flash_attention import _block_caps

    assert _block_caps(True, False, 64) == (512, 512)
    assert _block_caps(True, False, 128) == (1024, 1024)
    # 592 = 16*37 tiles only above 512: causal+wide heads becomes eligible
    assert flash_supported(592, 592, 128, causal=True)
    assert not flash_supported(592, 592, 64, causal=True)


def test_beam_grouped_attention_matches_replicated_kv():
    """The beam-decode grouped path (K/V shared per row) must reproduce
    plain attention on per-beam-replicated K/V exactly — same fp32
    softmax, scale, bias conventions (ops/attention.py)."""
    import jax.numpy as jnp

    from distributed_llms_example_tpu.ops.attention import (
        beam_grouped_attention,
        dot_product_attention,
    )

    rng = np.random.RandomState(9)
    B, G, H, Q, K, d = 3, 2, 4, 1, 16, 8
    q = jnp.asarray(rng.randn(B * G, H, Q, d).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, K, d).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, K, d).astype(np.float32))
    bias = jnp.asarray(
        np.where(rng.rand(B, 1, 1, K) < 0.2, -1e9, 0.0).astype(np.float32)
    )
    # per-beam bias: each row's mask repeated per beam (the generation layout)
    bias_rep = jnp.repeat(bias, G, axis=0)
    k_rep = jnp.repeat(k, G, axis=0)
    v_rep = jnp.repeat(v, G, axis=0)

    ref = dot_product_attention(q, k_rep, v_rep, bias_rep)
    got = beam_grouped_attention(q, k, v, bias_rep)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-6, rtol=1e-6)
    # unscaled + learned-bias variant (the T5 cross path)
    lb = jnp.asarray(rng.randn(1, H, Q, K).astype(np.float32) * 0.1)
    ref2 = dot_product_attention(q, k_rep, v_rep, bias_rep + lb, scale=1.0)
    got2 = beam_grouped_attention(q, k, v, bias_rep, scale=1.0, learned_bias=lb)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(ref2), atol=1e-6, rtol=1e-6)
