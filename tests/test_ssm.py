"""The state-space mixer's mathematics (ops/ssm.py): the token-by-token
recurrence, the chunked form and the decode step agree on seeded inputs; the
convolution and its taps; padding stays out; the Pallas step (interpreted) is
the plain step and walks its live list; the state is float32 for a reason."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_example_tpu.ops import ssm

B, T, H, G, P, N = 2, 45, 4, 2, 16, 32  # two groups of two heads; 45 tokens are not a multiple of the chunk
CHUNK = 16


@pytest.fixture(autouse=True)
def float32_products():
    with jax.default_matmul_precision("highest"):
        yield


def inputs(seed, b=B, t=T, h=H, g=G, p=P, n=N):
    """x, dt (after its softplus), A, B, C, D with decays exp(dt A) in 0.9-0.999."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    decay = jax.random.uniform(ks[0], (b, t, h), minval=0.9, maxval=0.999)
    a_neg = -jnp.exp(0.3 * jax.random.normal(ks[1], (h,)) - 1.0)
    dt = jnp.log(decay) / a_neg  # so that exp(dt A) is the decay drawn
    return (jax.random.normal(ks[2], (b, t, h, p)), dt, a_neg, 0.4 * jax.random.normal(ks[3], (b, t, g, n)),
            0.4 * jax.random.normal(ks[4], (b, t, g, n)), 1.0 + 0.1 * jax.random.normal(ks[5], (h,)))


def by_steps(x, dt, a_neg, b, c, d_skip, step=ssm.ssm_step_reference, valid=None):
    """The decode step, a token at a time from an empty state (a padded token: the row is not live).  The
    recurrence is a scan of the same plain step, so with the default step this checks the ``live`` route
    against the ``valid`` one; the kernel's step has its own test below."""
    state = jnp.zeros(ssm.state_shape(x.shape[0], x.shape[2], x.shape[3], b.shape[3]), jnp.float32)
    ys = []
    for i in range(x.shape[1]):
        live = None if valid is None else valid[:, i]
        y, state = step(x[:, i], dt[:, i], a_neg, b[:, i], c[:, i], d_skip, state, live=live)
        ys.append(y)
    return jnp.stack(ys, axis=1), state


@pytest.mark.parametrize("seed", [0, 1])
def test_the_three_forms_agree(seed):
    args = inputs(seed)
    decay = jnp.exp(args[1] * args[2])
    assert 0.9 <= float(decay.min()) and float(decay.max()) <= 0.999
    y, state = ssm.ssm_recurrence(*args)
    y_c, state_c = jax.jit(functools.partial(ssm.ssm_prefill, chunk=CHUNK))(*args)
    y_s, state_s = jax.jit(by_steps)(*args)
    # float32 everywhere; the forms differ in the order of their sums (read: 2e-6 on |y| up to ~8)
    for got_y, got_s in ((y_c, state_c), (y_s, state_s)):
        np.testing.assert_allclose(np.asarray(got_y), np.asarray(y), atol=1e-4, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(got_s), np.asarray(state), atol=1e-4, rtol=1e-5)
    assert float(jnp.abs(state).max()) > 0.5


def test_a_wrong_decay_or_group_shows():
    """The comparison above can fail: a decay of 1 (a state that never forgets)
    or the other group's B and C move y by far more than the tolerance."""
    x, dt, a_neg, b, c, d_skip = inputs(2)
    y, _ = ssm.ssm_recurrence(x, dt, a_neg, b, c, d_skip)
    no_decay, _ = ssm.ssm_recurrence(x, dt, 0.0 * a_neg, b, c, d_skip)
    swapped, _ = ssm.ssm_recurrence(x, dt, a_neg, b[:, :, ::-1], c[:, :, ::-1], d_skip)
    assert float(jnp.abs(no_decay - y).max()) > 0.1 and float(jnp.abs(swapped - y).max()) > 0.1


def test_padding_stays_out_of_the_state_and_of_the_outputs():
    """A right-padded row: whatever lies under the padding, the real positions'
    outputs and the state left are those of the real tokens alone, in every form."""
    x, dt, a_neg, b, c, d_skip = inputs(3)
    valid = jnp.asarray(np.arange(T)[None, :] < np.asarray([T, 21])[:, None], jnp.int32)
    want_y, want_s = ssm.ssm_recurrence(x[1:, :21], dt[1:, :21], a_neg, b[1:, :21], c[1:, :21], d_skip)
    for form in (ssm.ssm_recurrence, functools.partial(ssm.ssm_prefill, chunk=CHUNK),
                 lambda *a: by_steps(*a[:6], valid=a[6])):
        y, state = form(x, dt, a_neg, b, c, d_skip, valid)
        np.testing.assert_allclose(np.asarray(y[1, :21]), np.asarray(want_y[0]), atol=1e-4, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(state[1]), np.asarray(want_s[0]), atol=1e-4, rtol=1e-5)
        garbage = form(x.at[1, 21:].set(7.0), dt.at[1, 21:].set(0.9), a_neg, b, c, d_skip, valid)[1]
        np.testing.assert_array_equal(np.asarray(garbage), np.asarray(state))


def test_the_convolution_and_its_taps():
    """``causal_conv`` is the depthwise causal sum from an empty past and keeps
    the last three REAL pre-activation columns (zeros where a prompt holds
    fewer); ``causal_conv_step`` continues it token by token, and a row that is
    not live keeps its columns."""
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    xbc, w, bias = jax.random.normal(ks[0], (3, 10, 6)), jax.random.normal(ks[1], (6, 4)), jax.random.normal(ks[2], (6,))
    lengths = np.asarray([10, 7, 2])
    valid = jnp.asarray(np.arange(10)[None, :] < lengths[:, None], jnp.int32)
    out, kept = ssm.causal_conv(xbc, w, bias, valid)
    padded = np.concatenate([np.zeros((3, 3, 6), np.float32), np.asarray(xbc)], axis=1)
    for t in range(10):  # tap k multiplies the column 3 - k tokens back
        want = sum(np.asarray(w)[:, k] * padded[:, t + k] for k in range(4)) + np.asarray(bias)
        np.testing.assert_allclose(np.asarray(out[:, t]), want, atol=1e-5)
    for row, n in enumerate(lengths):
        np.testing.assert_array_equal(np.asarray(kept[row]).T, padded[row, n: n + 3])
    assert float(jnp.abs(kept[2, :, 0]).max()) == 0.0  # two tokens: the oldest tap is the empty past
    # a further token through the step, on rows 0 and 2; row 1 is idle
    new = jax.random.normal(jax.random.PRNGKey(5), (3, 6))
    live = jnp.asarray([1, 0, 1])
    step_out, step_kept = ssm.causal_conv_step(new, w, bias, kept, live)
    for row, n in ((0, 10), (2, 2)):
        seq = jnp.concatenate([xbc[row, :n], new[row][None]], axis=0)[None]
        whole, whole_kept = ssm.causal_conv(seq, w, bias)
        np.testing.assert_allclose(np.asarray(step_out[row]), np.asarray(whole[0, -1]), atol=1e-5)
        np.testing.assert_array_equal(np.asarray(step_kept[row]), np.asarray(whole_kept[0]))
    np.testing.assert_array_equal(np.asarray(step_kept[1]), np.asarray(kept[1]))


def step_inputs(b, seed=6, h=4, g=2, p=128, n=128):
    """One decode step's operands at lane-aligned sizes and a state that is not empty."""
    x, dt, a_neg, bm, cm, d_skip = inputs(seed, b=b, t=1, h=h, g=g, p=p, n=n)
    state = jax.random.normal(jax.random.PRNGKey(seed + 1), ssm.state_shape(b, h, p, n))
    return x[:, 0], dt[:, 0], a_neg, bm[:, 0], cm[:, 0], d_skip, state


@pytest.mark.parametrize("live", [
    pytest.param(None, id="no-list"),
    pytest.param([1, 1, 1, 1, 1], id="all-live"),
    pytest.param([0, 0, 1, 0, 0], id="one-live"),
    pytest.param([0, 0, 0, 0, 0], id="none-live"),
    pytest.param([1, 0, 1, 0, 0], id="live-slots-not-contiguous"),
    pytest.param([0, 0, 0, 0, 1], id="the-last-slot-alone"),
])
def test_decode_kernel_is_the_plain_step_and_walks_the_live_slots(live):
    """The Pallas kernel, interpreted, at a head size and state of 128 with two
    heads a group (one grid step a slot and group): a live slot is the plain
    step's; an idle slot's state is the input's bit for bit and its ``y`` is
    exactly zero (the interpreter hands the kernel a NaN-filled ``y``: a row the
    grid never writes shows), in the kernel and in the reference alike."""
    assert ssm.step_kernel_supported(128, 256) and not ssm.step_kernel_supported(16, 32)
    assert ssm.step_block(32, 2) == 8 and ssm.step_block(4, 2) == 2
    args = step_inputs(5)
    on = np.ones(5, bool) if live is None else np.asarray(live, bool)
    given = None if live is None else jnp.asarray(live, jnp.int32)  # a 0/1 vector reads as a boolean one
    want = ssm.ssm_step_reference(*args, live=given)
    got = ssm.ssm_step(*args, live=given, interpret=True)
    for y, s in (want, got):
        y, s = np.asarray(y), np.asarray(s)
        np.testing.assert_array_equal(s[~on], np.asarray(args[6])[~on])
        np.testing.assert_array_equal(y[~on], np.zeros_like(y[~on]))
        assert np.isfinite(y).all() and (not on.any() or np.abs(y[on]).max() > 0)
        assert not on.any() or not np.array_equal(s[on], np.asarray(args[6])[on])
    # the same float32 products in another order (a sum of rows reduced at the end)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]), atol=1e-6, rtol=1e-6)


def test_a_state_update_in_bfloat16_fails_the_tolerance():
    """ops/ssm.py says the state is float32.  Kept in bfloat16 between tokens
    (what a narrower leaf would be), the state after 45 tokens misses the
    float32 one by ~2^-9 of its entries a step (read: 7e-3 on entries of ~1,
    seventy times the tolerance the forms are held to above)."""
    args = inputs(7)

    def narrow_step(*a, state, live=None):
        y, s = ssm.ssm_step_reference(*a, state.astype(jnp.bfloat16).astype(jnp.float32), live=live)
        return y, s.astype(jnp.bfloat16).astype(jnp.float32)

    y, state = ssm.ssm_recurrence(*args)
    low_y, low = jax.jit(functools.partial(by_steps, step=lambda *a, live=None: narrow_step(*a[:6], state=a[6], live=live)))(*args)
    assert float(jnp.max(jnp.abs(low - state))) > 30 * 1e-4 and float(jnp.max(jnp.abs(low_y - y))) > 30 * 1e-4
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(np.asarray(low), np.asarray(state), atol=1e-4, rtol=1e-5)
