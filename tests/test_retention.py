"""Power retention (ops/retention.py): the three forms of one layer agree on
seeded inputs (gates in 0.9-0.999, degree 2, 5 query heads a KV head), the
feature map is the second power, padding stays out of a state, the decode
kernel (interpreted) is the plain step on the slots its live list names and
leaves every other slot's state alone, and bfloat16 state products are not
good enough.  Tolerances are written with their reasons."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_example_tpu.ops import retention as R

B, H, KV, T, D = 2, 10, 2, 48, 16


def inputs(seed, t=T, d=D, b=B, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, H, t, d), dtype)
    k = jax.random.normal(ks[1], (b, KV, t, d), dtype)
    v = jax.random.normal(ks[2], (b, KV, t, d), dtype)
    g = jax.random.uniform(ks[3], (b, KV, t), minval=0.9, maxval=0.999)
    return q, k, v, jnp.log(g)


def recurrent(q, k, v, log_g, step=R.retention_step_reference, **kw):
    """The recurrent form, a token at a time from an empty state."""
    b, _, t, d = q.shape
    s_shape, z_shape = R.state_shapes(b, k.shape[1], d, v.shape[-1])
    state, norm = jnp.zeros(s_shape), jnp.zeros(z_shape)
    ys = []
    for i in range(t):
        y, state, norm = step(q[:, :, i], k[:, :, i], v[:, :, i], log_g[:, :, i], state, norm, **kw)
        ys.append(y)
    return jnp.stack(ys, axis=2), state, norm


def test_phi_is_the_symmetric_second_power():
    a, b = (jax.random.normal(jax.random.PRNGKey(s), (7, D)) for s in (1, 2))
    got = jnp.sum(R.phi(a) * R.phi(b), axis=(-2, -1))
    want = jnp.sum(a * b, axis=-1) ** 2 / D
    # float32 on both sides, a sum of (D/2 + 1) x D products against one of D: rounding alone
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=1e-6)
    assert R.phi(a).shape == (7, D // 2 + 1, D) and R.rotations(128) * 128 == 8320
    with pytest.raises(ValueError, match="even head size"):
        R.rotations(15)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_three_forms_agree(seed):
    q, k, v, log_g = inputs(seed)
    with jax.default_matmul_precision("highest"):
        y_attn = R.retention_attention(q, k, v, log_g)
        state, norm = R.retention_state(k, v, log_g)
        y_rec, s_rec, z_rec = recurrent(q, k, v, log_g)
        y_chunk, s_chunk, z_chunk = R.retention_chunked(q, k, v, log_g, chunk=16)
    # float32 everywhere: what differs is the order of the sums (one (T, T) product, 48 rank-one
    # updates, three chunks).  Outputs are weighted means of unit-normal values: |y| <~ 3
    for other in (y_rec, y_chunk):
        np.testing.assert_allclose(np.asarray(y_attn), np.asarray(other), atol=2e-4, rtol=0)
    for got in ((s_rec, z_rec), (s_chunk, z_chunk)):
        np.testing.assert_allclose(np.asarray(state), np.asarray(got[0]), atol=1e-4, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(norm), np.asarray(got[1]), atol=1e-4, rtol=1e-5)


def test_the_state_decays_and_a_wrong_gate_shows():
    """With g near 0.9 a token 48 back weighs 0.6 % of the newest: the forms
    must disagree visibly when one of them is handed other gates."""
    q, k, v, log_g = inputs(3)
    y = R.retention_attention(q, k, v, log_g)
    y_wrong = R.retention_attention(q, k, v, log_g * 0.5)
    assert float(jnp.max(jnp.abs(y - y_wrong))) > 0.05


def test_padding_stays_out_of_the_state_and_of_the_outputs():
    """A right-padded prompt leaves the state of its real tokens alone, and its
    real positions' outputs are those of the unpadded prompt."""
    q, k, v, log_g = inputs(4)
    n = 29
    valid = jnp.asarray([[1] * n + [0] * (T - n), [1] * T], jnp.int32)
    with jax.default_matmul_precision("highest"):
        y_pad, s_pad, z_pad = R.retention_prefill(q, k, v, log_g, valid)
        y_cut, s_cut, z_cut = R.retention_prefill(q[:1, :, :n], k[:1, :, :n], v[:1, :, :n], log_g[:1, :, :n])
        y_chunk, s_chunk, _ = R.retention_chunked(q, k, v, log_g, valid, chunk=16)
    np.testing.assert_allclose(np.asarray(y_pad[0, :, :n]), np.asarray(y_cut[0]), atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(s_pad[0]), np.asarray(s_cut[0]), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(z_pad[0]), np.asarray(z_cut[0]), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(s_chunk[0]), np.asarray(s_cut[0]), atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(y_chunk[0, :, :n]), np.asarray(y_cut[0]), atol=2e-4, rtol=0)


def test_the_form_is_chosen_from_the_shape(monkeypatch):
    """From ``CHUNKED_FROM`` tokens on a prompt that divides into chunks takes the scan."""
    called = []
    real = R.retention_chunked
    monkeypatch.setattr(R, "retention_chunked", lambda *a, **kw: called.append(a[0].shape) or real(*a, **kw))
    monkeypatch.setattr(R, "CHUNK", 16)
    monkeypatch.setattr(R, "CHUNKED_FROM", 176)
    for t, chunked in ((160, False), (176, True), (186, False), (192, True)):
        called.clear()
        q, k, v, log_g = inputs(5, t=t, b=1)
        y, state, norm = R.retention_prefill(q, k, v, log_g)
        assert bool(called) is chunked, t
        assert y.shape == (1, H, t, D) and state.shape == (1, KV, 9, D, D) and norm.shape == (1, KV, 9, D)


def step_inputs(b, d=128, seed=6):
    """One decode step's operands at head size ``d``, 5 query heads a KV head, and a state that is not empty."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v = (jax.random.normal(ks[i], (b, n, d)) for i, n in ((0, H), (1, KV), (2, KV)))
    log_g = jnp.log(jax.random.uniform(ks[3], (b, KV), minval=0.9, maxval=0.999))
    s_shape, z_shape = R.state_shapes(b, KV, d, d)
    return q, k, v, log_g, jax.random.normal(ks[4], s_shape), jnp.abs(jax.random.normal(ks[5], z_shape)) + 1.0


def test_decode_kernel_is_the_plain_step_at_lane_aligned_heads():
    """The Pallas kernel, interpreted, at head size 128 with 5 query heads a KV
    head: one call updates the state in place and reads it for every head."""
    args = step_inputs(2)
    assert R.step_kernel_supported(128, 128) and not R.step_kernel_supported(16, 16) and R.step_tile(65) == 13
    want = R.retention_step_reference(*args)
    got = R.retention_step(*args, interpret=True)
    # the same float32 products in another order (a lane-wise accumulator reduced at the end)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), atol=1e-3, rtol=1e-4)  # |y| up to ~300 here
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[2]), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("live", [
    pytest.param([1, 1, 1, 1, 1], id="all-live"),
    pytest.param([0, 0, 1, 0, 0], id="one-live"),
    pytest.param([0, 0, 0, 0, 0], id="none-live"),
    pytest.param([1, 0, 1, 0, 0], id="live-slots-not-contiguous"),
    pytest.param([0, 0, 0, 0, 1], id="the-last-slot-alone"),
])
def test_decode_kernel_walks_the_live_slots_and_leaves_the_idle_ones_alone(live):
    """The kernel's grid walks the live slots handed to it by scalar prefetch:
    a live slot is the plain step's, to the tolerances above; an idle slot's
    state and normaliser are the input's bit for bit and its ``y`` is exactly
    zero (the interpreter hands the kernel a NaN-filled ``y``: a row the grid
    never writes shows), in the kernel and in the reference alike."""
    args = step_inputs(len(live))
    state, norm = args[4], args[5]
    on = np.asarray(live, bool)
    want = R.retention_step_reference(*args, live=jnp.asarray(on))
    got = R.retention_step(*args, live=jnp.asarray(live, jnp.int32), interpret=True)  # a 0/1 vector reads as a boolean one
    for out in (want, got):
        y, s, z = (np.asarray(x) for x in out)
        np.testing.assert_array_equal(s[~on], np.asarray(state)[~on])
        np.testing.assert_array_equal(z[~on], np.asarray(norm)[~on])
        np.testing.assert_array_equal(y[~on], np.zeros_like(y[~on]))
        assert np.isfinite(y).all() and (not on.any() or np.abs(y[on]).max() > 0)
        assert not on.any() or not np.array_equal(s[on], np.asarray(state)[on])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), atol=1e-3, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[2]), atol=1e-5, rtol=1e-5)
    if on.all():  # no list given is every slot: the same kernel on the identity list, the same bits
        for step in (R.retention_step_reference, functools.partial(R.retention_step, interpret=True)):
            for a, b in zip(step(*args), step(*args, live=jnp.asarray(on))):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_state_products_in_bfloat16_fail_the_tolerance(monkeypatch):
    """ops/retention.py says the state and its products are float32.  Built
    from bfloat16 features and values (one bfloat16 pass, what a narrower state
    would be read through), the state misses the float32 one by ~2^-9 of its
    entries: hundreds of times the tolerance the forms are held to above."""
    q, k, v, log_g = inputs(7)
    with jax.default_matmul_precision("highest"):
        state, _ = R.retention_state(k, v, log_g)
        real_phi = R.phi
        monkeypatch.setattr(R, "phi", lambda a: real_phi(a).astype(jnp.bfloat16).astype(jnp.float32))
        low, _ = R.retention_state(k.astype(jnp.bfloat16), v.astype(jnp.bfloat16), log_g)
    err = float(jnp.max(jnp.abs(low - state)))
    assert err > 100 * 1e-4, err
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(np.asarray(state), np.asarray(low), atol=1e-4, rtol=1e-5)
