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
    relative_bias_matrix,
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
    faster on v5e); causal stays at 512, and relative-bias caps block_q at
    512 (dbias tile VMEM) while its block_k may reach 1024."""
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
    # above 512, so it is eligible non-causal but NOT causal; relative-bias
    # caps block_q at 512 (dbias tile VMEM) while block_k may reach 1024
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
    """The relative-bias default tiling is ASYMMETRIC (block_q capped at
    512, block_k at 1024) — run its backward (dq/dkv/dbias kernels) with
    block_k > block_q and check gradients against plain attention."""
    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(1, 2, 64, 32), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 128, 32), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 128, 32), jnp.float32)
    lb = jnp.asarray(rng.randn(2, 64 + 128 - 1).astype(np.float32) * 0.1)

    def loss_flash(q, k, v, lb):
        out = flash_attention(q, k, v, relative_bias=lb, block_q=64, block_k=128)
        return jnp.sum(out ** 2)

    def loss_ref(q, k, v, lb):
        return jnp.sum(dot_product_attention(q, k, v, relative_bias_matrix(lb, 64, 128)) ** 2)

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
    (and the summed bias gradient) must equal a run without the dead example."""
    q_len = kv_len = 64
    q, k, v = _qkv(q_len, kv_len)
    mask = np.ones((B, kv_len), np.float32)
    mask[0, :] = 0  # example 0: every key masked
    bias = jnp.where(jnp.asarray(mask)[:, None, None, :] > 0, 0.0, -jnp.inf)
    rng = np.random.RandomState(2)
    lbias = jnp.asarray(rng.randn(H, q_len + kv_len - 1).astype(np.float32) * 0.1)

    def loss(q, k, v, lbias, bias):
        return jnp.sum(
            flash_attention(
                q, k, v, bias, relative_bias=lbias, causal=True, block_q=32, block_k=32
            )
            ** 2
        )

    # the dead example's FORWARD output must be exact zeros (not an
    # average of v over causally-forbidden positions)
    out = flash_attention(
        q, k, v, bias, relative_bias=lbias, causal=True, block_q=32, block_k=32
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
    # unscaled variant (the T5 cross path)
    ref2 = dot_product_attention(q, k_rep, v_rep, bias_rep, scale=1.0)
    got2 = beam_grouped_attention(q, k, v, bias_rep, scale=1.0)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(ref2), atol=1e-6, rtol=1e-6)


# ------------------------------------------- the relative bias, by diagonals


@pytest.mark.parametrize("q_len,kv_len", [(1, 1), (8, 8), (16, 24), (24, 16), (128, 128), (96, 40), (7, 13)])
def test_relative_bias_matrix_is_the_toeplitz_matrix_of_its_vector(q_len, kv_len):
    """``out[0, h, q, k] = rel[h, (k - q) + q_len - 1]``, at lengths whose
    strip heights factor as 8 x 16 x n and at ones that do not."""
    rel = np.random.RandomState(q_len).randn(3, q_len + kv_len - 1).astype(np.float32)
    want = np.stack([[rel[h, np.arange(kv_len) - i + q_len - 1] for i in range(q_len)] for h in range(3)])[None]
    np.testing.assert_array_equal(np.asarray(relative_bias_matrix(jnp.asarray(rel), q_len, kv_len)), want)


# name: (batch, q_len, kv_len, block_q, block_k, causal, extra)
RELATIVE_BIAS_CASES = {
    "square_2x2_tiles": (2, 128, 128, 64, 64, False, None),
    "causal_2x2_tiles": (2, 128, 128, 64, 64, True, None),
    "one_tile": (1, 64, 64, 64, 64, False, None),
    "wide_kv_lane_tiles": (1, 64, 256, 32, 128, False, None),  # block_k a vreg's lanes: the rotates
    "lane_tiles_4x2": (1, 256, 256, 64, 128, False, None),
    "causal_lane_tiles": (1, 256, 256, 128, 128, True, None),
    "tall_q_tile": (1, 256, 64, 128, 32, False, None),  # block_q > block_k: five segments
    "batch_3": (3, 64, 64, 32, 32, False, None),
    "mask_bias": (2, 64, 128, 32, 64, False, "mask"),
    "dropout_seed": (2, 64, 64, 32, 32, False, "dropout"),
    "causal_dropout_seed": (1, 128, 128, 64, 128, True, "dropout"),
    "fully_masked_row": (2, 64, 64, 32, 32, True, "dead"),
}


@pytest.mark.parametrize("case", sorted(RELATIVE_BIAS_CASES))
def test_relative_bias_gradient_is_the_matrix_gradient_summed_along_diagonals(case):
    """dq, dk, dv and the (H, Q + K - 1) vector's gradient from the kernels
    (the dbias tile reduced along its diagonals in VMEM, the tiles' sums
    overlap-added outside) against ``jax.grad`` through the materialised
    matrix and ``dot_product_attention``."""
    from distributed_llms_example_tpu.ops.fused_dropout import hash_keep_mask

    batch, q_len, kv_len, block_q, block_k, causal, extra = RELATIVE_BIAS_CASES[case]
    heads, d, rate, seed = 2, 16, 0.15, 1234
    rng = np.random.RandomState(len(case))
    mk = lambda *shape: jnp.asarray(rng.randn(*shape).astype(np.float32))  # noqa: E731
    q, k, v, w = mk(batch, heads, q_len, d), mk(batch, heads, kv_len, d), mk(batch, heads, kv_len, d), mk(batch, heads, q_len, d)
    rel = mk(heads, q_len + kv_len - 1) * 0.3
    bias, live = None, slice(None)
    if extra == "mask":
        mask = np.ones((batch, kv_len), np.int32)
        mask[0, kv_len - 38:] = 0
        bias = mask_to_bias(jnp.asarray(mask))
    if extra == "dead":  # every key of example 0 masked: its rows are dead, and XLA's softmax cannot follow
        mask = np.ones((batch, kv_len), np.float32)
        mask[0] = 0
        bias, live = jnp.where(jnp.asarray(mask)[:, None, None, :] > 0, 0.0, -jnp.inf), slice(1, None)
    drop = dict(dropout_rate=rate, dropout_seed=seed) if extra == "dropout" else {}

    def loss_flash(q, k, v, rel):
        out = flash_attention(q, k, v, bias, relative_bias=rel, causal=causal, scale=1.0,
                              block_q=block_q, block_k=block_k, interpret=True, **drop)
        return (out * w).sum()

    def loss_ref(q, k, v, rel):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) + relative_bias_matrix(rel, q_len, kv_len)
        if bias is not None:
            s = s + bias[live]
        if causal:
            s = s + make_causal_bias(q_len, kv_len)
        p = jax.nn.softmax(s, axis=-1)
        if extra == "dropout":
            keep = jnp.stack([jnp.stack([hash_keep_mask(seed, (q_len, kv_len), rate, tag_a=b, tag_b=h)
                                         for h in range(heads)]) for b in range(batch)])
            p = jnp.where(keep, p / (1 - rate), 0.0)
        return (jnp.einsum("bhqk,bhkd->bhqd", p, v) * w[live]).sum()

    got = jax.grad(loss_flash, argnums=(0, 1, 2, 3))(q, k, v, rel)
    want = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(q[live], k[live], v[live], rel)
    assert got[3].shape == rel.shape and got[3].dtype == jnp.float32
    for name, g, r in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(g[live]), np.asarray(r), atol=2e-5, rtol=1e-4, err_msg=name)
        if extra == "dead":
            np.testing.assert_array_equal(np.asarray(g[0]), 0.0, err_msg=name)
    np.testing.assert_allclose(np.asarray(got[3]), np.asarray(want[3]), atol=5e-5, rtol=1e-4, err_msg="drel")


@pytest.mark.parametrize("bidirectional", [True, False], ids=["encoder", "decoder"])
def test_table_gradient_through_the_buckets_matches_the_embedding_path(bidirectional):
    """The (buckets, heads) table's gradient through ``relative_bias_vector``
    (a take over Q + K - 1 offsets) and the kernels, against the path that
    gathers the table into the (1, H, Q, K) matrix with ``nn.Embed`` over
    all Q x K pairs and attends in XLA."""
    import flax.linen as nn

    from distributed_llms_example_tpu.models.t5 import T5Config, relative_bias_vector, relative_position_bucket

    cfg = T5Config(num_heads=2, relative_attention_num_buckets=8, relative_attention_max_distance=20)
    q_len = kv_len = 64
    rng = np.random.RandomState(5)
    mk = lambda *shape: jnp.asarray(rng.randn(*shape).astype(np.float32))  # noqa: E731
    q, k, v, w = (mk(2, 2, q_len, 16) for _ in range(4))
    table = mk(8, 2)
    embed = nn.Embed(8, 2)

    def loss_flash(table):
        rel = relative_bias_vector(table, cfg, q_len, kv_len, bidirectional=bidirectional)
        out = flash_attention(q, k, v, relative_bias=rel, causal=not bidirectional, scale=1.0,
                              block_q=32, block_k=32, interpret=True)
        return (out * w).sum()

    def loss_ref(table):
        offsets = jnp.arange(kv_len)[None, :] - jnp.arange(q_len)[:, None]
        buckets = relative_position_bucket(offsets, bidirectional=bidirectional, num_buckets=8, max_distance=20)
        bias = embed.apply({"params": {"embedding": table}}, buckets).transpose(2, 0, 1)[None]
        if not bidirectional:
            bias = bias + make_causal_bias(q_len, kv_len)
        return (dot_product_attention(q, k, v, bias, scale=1.0) * w).sum()

    got, want = jax.grad(loss_flash)(table), jax.grad(loss_ref)(table)
    # 64 positions reach every bucket a stack can use (the encoder's "ahead by zero" does not exist)
    assert (np.abs(np.asarray(want)).max(axis=1) > 0).sum() >= 7
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4)


def test_relative_bias_shape_is_checked():
    q, k, v = _qkv(64, 64)
    with pytest.raises(ValueError, match="relative_bias shape"):
        flash_attention(q, k, v, relative_bias=jnp.zeros((1, H, 64, 64)), interpret=True)


# ------------------------------------------------ flash_decode's live list (PR 46)
#
# The decode kernel fetches what is live: the slots ``live`` names, and of each
# the kv tiles up to its last position.  (The kernel against the dense reference
# at every shape, with every row live: tests/test_serving.py.)


def _leaf(x):
    """(B, H, L, d) as the cache keeps it: (B, L, H x d), heads side by side."""
    b, h, length, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, length, h * d)


# name: (B, H, L, d, q rows, q rows a position, bias, int8 K/V, block_k)
LIVE_CASES = {
    "plain": (6, 4, 128, 16, 1, 1, None, False, None),
    "grouped": (6, 2, 256, 64, 4, 4, None, False, None),
    "int8": (6, 4, 128, 64, 1, 1, None, True, None),
    "pad-bias": (6, 4, 128, 16, 1, 1, "pad", False, None),
    "full-bias-q4": (6, 4, 128, 16, 4, 1, "full", False, None),
    "tiles": (6, 4, 256, 16, 1, 1, None, False, 64),
    "tiles-grouped-int8-pad-bias": (6, 2, 256, 64, 4, 4, "pad", True, 64),
}


def _live_case(case):
    from distributed_llms_example_tpu.ops import flash_attention as fa

    B, H, L, d, q_len, q_group, bias_kind, int8, block_k = LIVE_CASES[case]
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(B, H, q_len, d).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, L, d).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, L, d).astype(np.float32))
    live = np.array([True, False, True, True, False, False])
    # live rows in the first tile, a middle one and the last; idle rows parked at L, as the engine parks them
    offsets = np.where(live, [3, 0, L // 2 + 5, L - q_len, 0, 0], L).astype(np.int32)
    bias = None
    if bias_kind == "pad":
        bias = np.where(rng.rand(B, 1, 1, L) > 0.2, 0.0, -1e9).astype(np.float32)
        bias[..., 0] = 0.0  # key 0 stays live: it is all a fresh row sees
    elif bias_kind == "full":
        bias = rng.randn(B, H, q_len, L).astype(np.float32)
    scales = {}
    leaves = [_leaf(k), _leaf(v)]
    dense_k, dense_v = k, v
    if int8:
        (qk, ks), (qv, vs) = fa.quantize_kv(k), fa.quantize_kv(v)
        leaves = [_leaf(qk), _leaf(qv)]
        scales = {"k_scale": ks.transpose(0, 2, 1), "v_scale": vs.transpose(0, 2, 1)}
        dense_k, dense_v = fa.dequantize_kv(qk, ks), fa.dequantize_kv(qv, vs)
    kw = dict(block_k=block_k, q_group=q_group)
    return fa, q, leaves, scales, bias, offsets, live, (dense_k, dense_v), kw


@pytest.mark.parametrize("case", sorted(LIVE_CASES))
def test_flash_decode_live_rows_are_the_all_rows_kernels_and_idle_rows_zero(case):
    """With ``live`` given, a live row is bit for bit what the kernel returns
    for it when every row is live (``live=None``: today's call), and within
    tolerance the masked dense attention; an idle row is zero."""
    fa, q, (k, v), scales, bias, offsets, live, (dk, dv), kw = _live_case(case)
    b = None if bias is None else jnp.asarray(bias)
    every = fa.flash_decode(q, k, v, b, offsets=jnp.minimum(offsets, k.shape[1] - 1), **scales, **kw)
    out = np.asarray(fa.flash_decode(q, k, v, b, offsets=offsets, live=live, **scales, **kw))
    np.testing.assert_array_equal(out[live], np.asarray(every)[live])
    assert (out[~live] == 0).all()
    L, Q = dk.shape[2], q.shape[2]
    q_pos = offsets[:, None, None, None] + (np.arange(Q) // kw["q_group"])[None, None, :, None]
    step = jnp.where(jnp.arange(L)[None, None, None, :] <= q_pos, 0.0, -1e9)
    ref = dot_product_attention(q, dk, dv, step if b is None else b + step)  # row r sits at position r // q_group
    np.testing.assert_allclose(out[live], np.asarray(ref)[live], atol=2e-6)
    # every row named live is the call without the word
    ones = fa.flash_decode(q, k, v, b, offsets=jnp.minimum(offsets, L - 1), live=np.ones(len(live), bool), **scales, **kw)
    np.testing.assert_array_equal(np.asarray(ones), np.asarray(every))


@pytest.mark.parametrize("case", sorted(LIVE_CASES))
def test_flash_decode_reads_nothing_of_idle_slots_and_dead_tiles(case):
    """Idle slots' leaves (K, V, int8 scales, bias rows) and every whole tile
    past a live slot's last position filled with NaN: the output is finite and
    the same, so nothing of them is used (a NaN times a zero weight would be a
    NaN: the tiles are not merely masked)."""
    fa, q, (k, v), scales, bias, offsets, live, _, kw = _live_case(case)
    L = k.shape[1]
    block = kw["block_k"] or fa.decode_block(L)
    last_tile = np.minimum(offsets + (q.shape[2] - 1) // kw["q_group"], L - 1) // block
    dead = (np.arange(L)[None, :] // block > last_tile[:, None]) | ~live[:, None]  # (B, L)
    assert dead[live].any() or block == L  # the tiled cases leave dead tiles behind live rows
    poison = lambda x: jnp.where(jnp.asarray(dead)[:, :, None], jnp.nan, x.astype(jnp.float32)).astype(x.dtype)  # noqa: E731
    if k.dtype == jnp.int8:  # an s8 cannot hold a NaN: its scales do
        pk, pv, pscales = k, v, {n: poison(s) for n, s in scales.items()}
    else:
        pk, pv, pscales = poison(k), poison(v), scales
    b = pb = None
    if bias is not None:
        b = jnp.asarray(bias)
        pb = jnp.where(jnp.asarray(~live)[:, None, None, None], jnp.nan, b)  # idle rows' bias is not fetched either
    out = np.asarray(fa.flash_decode(q, k, v, b, offsets=offsets, live=live, **scales, **kw))
    poisoned = np.asarray(fa.flash_decode(q, pk, pv, pb, offsets=offsets, live=live, **pscales, **kw))
    assert np.isfinite(poisoned).all()
    np.testing.assert_array_equal(poisoned, out)


@pytest.mark.parametrize("case", ["plain", "tiles-grouped-int8-pad-bias"])
def test_flash_decode_with_no_live_row_returns_zeros(case):
    fa, q, (k, v), scales, bias, offsets, live, _, kw = _live_case(case)
    b = None if bias is None else jnp.asarray(bias)
    nan = lambda x: x if x.dtype == jnp.int8 else jnp.full_like(x, jnp.nan)  # noqa: E731
    out = fa.flash_decode(
        q, nan(k), nan(v), b, offsets=np.full(len(live), k.shape[1], np.int32), live=np.zeros(len(live), bool),
        **{n: nan(s) for n, s in scales.items()}, **kw,
    )
    assert out.shape == q.shape and (np.asarray(out) == 0).all()


@pytest.mark.parametrize("ring", [False, True], ids=["leaf", "ring"])
def test_flash_decode_walk_names_a_new_block_only_for_a_live_tile(ring):
    """The grid's walk (``_decode_walk``, what every operand's index map is):
    over the whole grid in the pipeline's order, the (slot, tile) a step holds
    changes exactly once a live tile of a live slot (a ring: every tile of a
    live slot) and never for an idle slot or a dead tile, whose steps hold the
    block already there; so the K/V fetched are the live tiles' and no more."""
    from distributed_llms_example_tpu.ops import flash_attention as fa

    nk, block, groups = 4, 64, 2
    live = np.array([False, True, False, True, True, False, False, False])
    offsets = np.array([256, 70, 256, 0, 255, 256, 256, 256], np.int32)  # live rows end in tiles 1, 0 and 3
    order = np.argsort(~live, kind="stable").astype(np.int32)
    n_live = np.array([live.sum()], np.int32)
    held, fetches = None, []
    for b in range(len(live)):
        for g in range(groups):
            for ki in range(nk):
                at = tuple(int(x) for x in fa._decode_walk(
                    b, g, ki, offsets, order, n_live, nk=nk, block_k=block, last_pos=0, last_group=groups - 1, ring=ring))
                if at != held:
                    fetches.append(at)
                    held = at
    tiles = {1: 2, 3: 1, 4: 4}  # live slot -> its live tiles
    want = [(s, g, t) for s in (1, 3, 4) for g in range(groups) for t in range(nk if ring else tiles[s])]
    assert fetches == want
    # nobody live: one block for the whole grid (slot order[0], its last tile), which the kernel never touches
    idle = {tuple(int(x) for x in fa._decode_walk(
        b, g, ki, offsets, np.arange(8, dtype=np.int32), np.array([0], np.int32),
        nk=nk, block_k=block, last_pos=0, last_group=groups - 1, ring=ring))
        for b in range(8) for g in range(groups) for ki in range(nk)}
    assert idle == {(0, groups - 1, nk - 1)}
