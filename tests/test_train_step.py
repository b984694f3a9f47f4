"""Train-step tests on the 8-device mesh: loss decreases, grad accumulation
is exact, schedules and decay masks behave."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_example_tpu.data.batching import LABEL_PAD
from distributed_llms_example_tpu.models.registry import load_model
from distributed_llms_example_tpu.parallel.sharding import shard_params
from distributed_llms_example_tpu.train.optim import (
    decay_mask,
    linear_schedule_with_warmup,
    make_optimizer,
)
from distributed_llms_example_tpu.train.step import (
    create_train_state,
    make_train_step,
    put_batch,
    state_shardings,
)


def _toy_batch(b=8, src=16, tgt=8, vocab=256, seed=0):
    rng = np.random.RandomState(seed)
    input_ids = rng.randint(2, vocab, (b, src)).astype(np.int32)
    attn = np.ones((b, src), np.int32)
    labels = rng.randint(2, vocab, (b, tgt)).astype(np.int32)
    labels[:, -2:] = LABEL_PAD
    return {"input_ids": input_ids, "attention_mask": attn, "labels": labels}


@pytest.fixture(scope="module")
def setup(request):
    lm = load_model("t5-test")
    # keep fixture params on host: device_put can alias CPU buffers, and a
    # donating train step would delete them out from under later tests
    params = jax.device_get(lm.init_params(0))
    return lm, params


def test_loss_decreases(mesh8, setup):
    lm, params = setup
    tx, schedule = make_optimizer(learning_rate=1e-3, warmup_steps=0, total_steps=1000)
    build = make_train_step(lm.module, lm.config, tx, schedule, mesh8)
    params = shard_params(params, mesh8)
    state = create_train_state(params, tx)
    sh = state_shardings(state, mesh8)
    state = jax.tree.map(lambda x, s: jax.device_put(x, s), state, sh)
    step, _ = build(state)
    batch = put_batch(_toy_batch(), mesh8)
    losses = []
    for _ in range(12):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.9, losses
    assert int(jax.device_get(state.step)) == 12
    assert float(metrics["target_tokens"]) == 8 * 6  # 2 label cols masked


def test_grad_accum_matches_full_batch(mesh8, setup):
    """grad_accum=4 over a batch must produce the same updated params as a
    single full-batch step (token-weighted accumulation is exact).

    Uses SGD so the param delta IS the accumulated gradient — Adam's
    g/(|g|+eps) at step 1 amplifies fp summation-order noise for
    near-zero gradient entries and would hide real errors behind a loose
    tolerance.
    """
    import optax

    lm, params = setup
    tx = optax.sgd(1e-2)
    schedule = lambda step: 1e-2  # noqa: E731
    batch = _toy_batch(b=8)
    # vary the mask so microbatches have different token counts
    batch["labels"][0:2, 3:] = LABEL_PAD

    outs = []
    for accum in (1, 4):
        build = make_train_step(
            lm.module, lm.config, tx, schedule, mesh8, grad_accum_steps=accum, donate=False
        )
        state = create_train_state(shard_params(params, mesh8), tx)
        sh = state_shardings(state, mesh8)
        state = jax.tree.map(lambda x, s: jax.device_put(x, s), state, sh)
        step, _ = build(state)
        new_state, metrics = step(state, put_batch(batch, mesh8))
        outs.append((jax.device_get(new_state.params), float(metrics["loss"])))
    p1, l1 = outs[0]
    p4, l4 = outs[1]
    assert abs(l1 - l4) < 1e-5
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5)


def test_sharded_step_equals_single_device(mesh8, setup):
    """A tensor=2/fsdp=2/data=2 train step must produce the same loss,
    grad-norm, and updated params as the identical step on a 1-device mesh
    — the test that catches wrong sharding rules (a bad spec changes
    numerics through mis-reduced collectives, not just performance)."""
    import optax

    from distributed_llms_example_tpu.core.config import MeshConfig
    from distributed_llms_example_tpu.core.mesh import build_mesh

    lm, params = setup
    tx = optax.sgd(1e-2)
    schedule = lambda step: 1e-2  # noqa: E731
    batch = _toy_batch(b=8)
    batch["labels"][0:2, 3:] = LABEL_PAD  # uneven token counts across shards

    mesh1 = build_mesh(MeshConfig(data=1, fsdp=1, sequence=1, tensor=1), devices=jax.devices()[:1])
    outs = {}
    for name, mesh in (("sharded", mesh8), ("single", mesh1)):
        build = make_train_step(lm.module, lm.config, tx, schedule, mesh, donate=False)
        state = create_train_state(shard_params(params, mesh), tx)
        sh = state_shardings(state, mesh)
        state = jax.tree.map(lambda x, s: jax.device_put(x, s), state, sh)
        step, _ = build(state)
        new_state, metrics = step(state, put_batch(batch, mesh))
        outs[name] = (
            jax.device_get(new_state.params),
            float(metrics["loss"]),
            float(metrics["grad_norm"]),
        )
    p_sh, loss_sh, gn_sh = outs["sharded"]
    p_1, loss_1, gn_1 = outs["single"]
    assert loss_sh == pytest.approx(loss_1, rel=1e-5)
    assert gn_sh == pytest.approx(gn_1, rel=1e-4)
    for a, b in zip(jax.tree.leaves(p_sh), jax.tree.leaves(p_1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5)


def test_schedule_shape():
    s = linear_schedule_with_warmup(1e-4, warmup_steps=10, total_steps=110)
    assert float(s(0)) == 0.0
    assert float(s(10)) == pytest.approx(1e-4, rel=1e-6)  # fp32 schedule values
    assert float(s(60)) == pytest.approx(5e-5, rel=1e-3)
    assert float(s(110)) == pytest.approx(0.0, abs=1e-10)


def test_decay_mask(setup):
    lm, params = setup
    mask = decay_mask(params)
    assert mask["shared"]["embedding"] is True
    blk = mask["encoder"]["block_0"]
    assert blk["self_attn"]["q_proj"]["kernel"] is True
    assert blk["self_attn_norm"]["scale"] is False


def test_state_shardings_cover_opt_state(mesh8, setup):
    lm, params = setup
    tx, _ = make_optimizer()
    state = create_train_state(params, tx)
    sh = state_shardings(state, mesh8)
    # adam moments of q_proj kernels must be sharded like the kernel itself
    flat = jax.tree_util.tree_leaves_with_path(sh)
    qproj = [s for path, s in flat if "q_proj" in str(path)]
    assert len(qproj) >= 3  # param + mu + nu
    assert len({str(s) for s in qproj}) == 1


def test_dropout_step_runs(mesh8, setup):
    lm, params = setup
    tx, schedule = make_optimizer(learning_rate=1e-3, warmup_steps=0, total_steps=100)
    build = make_train_step(lm.module, lm.config, tx, schedule, mesh8, with_dropout=True)
    state = create_train_state(shard_params(params, mesh8), tx)
    sh = state_shardings(state, mesh8)
    state = jax.tree.map(lambda x, s: jax.device_put(x, s), state, sh)
    step, _ = build(state)
    state, metrics = step(state, put_batch(_toy_batch(), mesh8), jax.random.PRNGKey(3))
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.slow  # ~15s extra step compile for the rbg key type: slow
# tier (test_dropout_step_runs pins the threefry path fast)
def test_dropout_step_accepts_rbg_key(mesh8, setup):
    """--prng-impl rbg hands the step a TYPED key array (TPU hardware RNG
    stream); the jitted step's replicated rng sharding must accept it and
    grad accumulation's fold_in must work on it."""
    lm, params = setup
    tx, schedule = make_optimizer(learning_rate=1e-3, warmup_steps=0, total_steps=100)
    build = make_train_step(
        lm.module, lm.config, tx, schedule, mesh8, with_dropout=True, grad_accum_steps=2
    )
    state = create_train_state(shard_params(params, mesh8), tx)
    sh = state_shardings(state, mesh8)
    state = jax.tree.map(lambda x, s: jax.device_put(x, s), state, sh)
    step, _ = build(state)
    key = jax.random.key(3, impl="rbg")
    state, metrics = step(state, put_batch(_toy_batch(), mesh8), key)
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.slow  # ~12s per-policy recompiles: slow tier
def test_remat_policies_match_no_remat(mesh8):
    """Remat never changes math — 'full' and 'dots' policies must produce
    the identical loss as no remat at all."""
    import optax

    losses = {}
    batch = _toy_batch(b=8)
    for policy in (None, "full", "dots"):
        lm = load_model(
            "llama-test",
            remat=policy is not None,
            remat_policy=policy or "full",
        )
        tx = optax.sgd(1e-2)
        build = make_train_step(
            lm.module, lm.config, tx, lambda s: 1e-2, mesh8, donate=False, is_seq2seq=False
        )
        params = jax.device_get(lm.init_params(0))
        state = create_train_state(shard_params(params, mesh8), tx)
        sh = state_shardings(state, mesh8)
        state = jax.tree.map(lambda x, s: jax.device_put(x, s), state, sh)
        step, _ = build(state)
        cb = {
            "input_ids": batch["input_ids"],
            "attention_mask": batch["attention_mask"],
            "labels": batch["input_ids"],
        }
        _, metrics = step(state, put_batch(cb, mesh8))
        losses[policy] = float(metrics["loss"])
    assert losses["full"] == pytest.approx(losses[None], rel=1e-6)
    assert losses["dots"] == pytest.approx(losses[None], rel=1e-6)


# ---------------------------------------------------------------------------
# In-step gradient accumulation (ISSUE 5): the single-apply contract vs the
# optax.MultiSteps oracle, donation safety, the compiled carry's sharded
# fp32 accumulators, and once-per-optimizer-step health/counting.
# ---------------------------------------------------------------------------


@pytest.mark.slow  # MultiSteps' lax.cond inner-apply compiles (~18s CPU): slow tier
def test_single_apply_bit_equal_vs_multisteps(setup):
    """The accumulation window's optimizer apply is bit-equal to a single
    apply on the full gradient: MultiSteps with use_grad_mean=False sums
    its inputs (g/2 + g/2 == g exactly in binary fp) and runs the inner
    tx exactly once on the window's last microbatch — the cross-check
    oracle for the scan's single-apply contract (train/optim.py
    multisteps_reference).  Both sides go through multisteps_reference
    (k=1 vs k=2) so they share the lax.cond-compiled inner apply — an
    eager op-by-op tx.update sees different XLA fusion (FMA) and differs
    at the ulp level, which is execution mode, not accumulation."""
    import optax

    from distributed_llms_example_tpu.train.optim import multisteps_reference

    lm, params = setup
    tx, _ = make_optimizer(learning_rate=1e-3, warmup_steps=0, total_steps=100)
    g = jax.tree.map(lambda p: (p * 0.1 + 0.01).astype(jnp.float32), params)

    ms1 = multisteps_reference(tx, 1)
    updates, _ = ms1.update(g, ms1.init(params), params)
    p_once = optax.apply_updates(params, updates)

    ms = multisteps_reference(tx, 2)
    s = ms.init(params)
    half = jax.tree.map(lambda x: x * 0.5, g)  # exact halving in binary fp
    u1, s = ms.update(half, s, params)
    # mid-window: MultiSteps emits zero updates, no apply happened
    assert all(not np.any(np.asarray(u)) for u in jax.tree.leaves(u1))
    # and the accumulated gradient is the exact sum of the halves
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(s.acc_grads)[0]),
        np.asarray(jax.tree.leaves(half)[0]),
    )
    u2, s = ms.update(half, s, params)
    p_ms = optax.apply_updates(params, u2)
    for a, b in zip(jax.tree.leaves(p_once), jax.tree.leaves(p_ms)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow  # compiles an accum step + eager grads (~30s CPU): slow tier
def test_grad_accum_step_matches_multisteps_trajectory(mesh8, setup):
    """End-to-end cross-check: the compiled accum=2 AdamW step lands on
    the same params as optax.MultiSteps driven with the per-microbatch
    token-normalized gradients computed eagerly (shard-local grouping:
    microbatch n takes rows n::N).  The scan normalizes the SUM once,
    MultiSteps sums pre-normalized terms — ulp-level gradient
    differences, but AdamW's g/(sqrt(nu)+eps) acts like sign(g) where
    |g| is tiny, so a single ulp flip there can move an update by up to
    2·lr on that element.  Hence two bounds: elementwise 2.5·lr (sign
    flips on isolated near-zero-gradient elements are execution noise),
    and mean |diff| under 5% of lr (a real accumulation bug — a second
    optimizer apply, wrong normalization, a dropped microbatch — moves
    the whole tree by O(lr))."""
    import optax

    from distributed_llms_example_tpu.train.optim import multisteps_reference
    from distributed_llms_example_tpu.train.step import make_loss_fn

    lm, params = setup
    N = 2
    tx, schedule = make_optimizer(learning_rate=1e-3, warmup_steps=0, total_steps=100)
    batch = _toy_batch(b=8)
    batch["labels"][0:2, 3:] = LABEL_PAD  # uneven tokens across microbatches

    build = make_train_step(
        lm.module, lm.config, tx, schedule, mesh8, grad_accum_steps=N, donate=False
    )
    state = create_train_state(shard_params(params, mesh8), tx)
    sh = state_shardings(state, mesh8)
    state = jax.tree.map(lambda x, s: jax.device_put(x, s), state, sh)
    step, _ = build(state)
    new_state, metrics = step(state, put_batch(batch, mesh8))
    p_step = jax.device_get(new_state.params)

    loss_sums = make_loss_fn(lm.module, lm.config, 0.0, is_seq2seq=True)
    grad_fn = jax.jit(jax.value_and_grad(lambda p, b: loss_sums(p, b), has_aux=True))
    mbs = [{k: v[n::N] for k, v in batch.items()} for n in range(N)]
    sums = [grad_fn(params, mb) for mb in mbs]
    total_tokens = sum(float(tok) for (_, tok), _ in sums)
    assert float(metrics["target_tokens"]) == total_tokens
    lsum_total = sum(float(ls) for (ls, _), _ in sums)
    assert float(metrics["loss"]) == pytest.approx(lsum_total / total_tokens, rel=1e-6)

    ms = multisteps_reference(tx, N)
    s = ms.init(params)
    p_ms = params
    for (_, _), grads in sums:
        gnorm = jax.tree.map(lambda g: (g / total_tokens).astype(jnp.float32), grads)
        u, s = ms.update(gnorm, s, p_ms)
        p_ms = optax.apply_updates(p_ms, u)
    lr = 1e-3
    diffs = [
        np.abs(np.asarray(a) - np.asarray(b))
        for a, b in zip(jax.tree.leaves(p_step), jax.tree.leaves(p_ms))
    ]
    assert max(d.max() for d in diffs) < 2.5 * lr
    total = sum(d.sum() for d in diffs)
    count = sum(d.size for d in diffs)
    assert total / count < 0.05 * lr


@pytest.mark.slow  # two full compiles (donate on/off): slow tier
def test_grad_accum_donation_safe(mesh8, setup):
    """donate=True under accumulation must not reuse a stale buffer: a
    3-step donated trajectory equals the non-donated one exactly (the
    accumulators and carry are donation-internal; the input state is the
    only donated argument, and it is consumed exactly once per step)."""
    import optax

    lm, params = setup
    batch = _toy_batch(b=8)
    trajectories = {}
    for donate in (False, True):
        tx = optax.sgd(1e-2)
        build = make_train_step(
            lm.module, lm.config, tx, lambda s: 1e-2, mesh8,
            grad_accum_steps=2, donate=donate,
        )
        state = create_train_state(shard_params(params, mesh8), tx)
        sh = state_shardings(state, mesh8)
        state = jax.tree.map(lambda x, s: jax.device_put(x, s), state, sh)
        step, _ = build(state)
        gb = put_batch(batch, mesh8)
        losses = []
        for _ in range(3):
            state, metrics = step(state, gb)
            losses.append(float(metrics["loss"]))
        trajectories[donate] = (losses, jax.device_get(state.params))
    l_no, p_no = trajectories[False]
    l_yes, p_yes = trajectories[True]
    assert l_yes == pytest.approx(l_no, rel=1e-6)
    for a, b in zip(jax.tree.leaves(p_no), jax.tree.leaves(p_yes)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6, rtol=1e-6)


def test_grad_accum_validation_and_pipeline_guard(mesh8, setup):
    """Config validation fails loudly: accum < 1 at build, indivisible
    batch at trace, and a stage>1 pipeline adapter (which owns its own
    microbatching) at build with the composition table's message."""
    import optax

    from distributed_llms_example_tpu.analysis.composition import reason_for

    lm, params = setup
    tx = optax.sgd(1e-2)
    sched = lambda s: 1e-2  # noqa: E731
    with pytest.raises(ValueError, match="grad_accum_steps"):
        make_train_step(lm.module, lm.config, tx, sched, mesh8, grad_accum_steps=0)

    class _FakePipe:
        num_microbatches = 4

    with pytest.raises(ValueError) as ei:
        make_train_step(_FakePipe(), lm.config, tx, sched, mesh8, grad_accum_steps=2)
    assert str(ei.value) == reason_for("grad-accum-pipelined")

    build = make_train_step(
        lm.module, lm.config, tx, sched, mesh8, grad_accum_steps=3, donate=False
    )
    state = create_train_state(shard_params(params, mesh8), tx)
    sh = state_shardings(state, mesh8)
    state = jax.tree.map(lambda x, s: jax.device_put(x, s), state, sh)
    step, _ = build(state)
    with pytest.raises(ValueError, match="not divisible"):
        step(state, put_batch(_toy_batch(b=8), mesh8))


@pytest.mark.slow  # its own health-step compile: slow tier
def test_grad_accum_health_once_per_optimizer_step(mesh8, setup):
    """health=True at accum>1 emits ONE metrics bundle per optimizer step
    (the watchdog's cadence unit): every health key present exactly once,
    the step counter advances by one per global batch, and the schedule is
    read at the optimizer step — microbatches are invisible."""
    from distributed_llms_example_tpu.train.step import HEALTH_METRIC_KEYS

    lm, params = setup
    tx, schedule = make_optimizer(learning_rate=1e-3, warmup_steps=0, total_steps=100)
    build = make_train_step(
        lm.module, lm.config, tx, schedule, mesh8,
        grad_accum_steps=4, health=True, donate=False,
    )
    state = create_train_state(shard_params(params, mesh8), tx)
    sh = state_shardings(state, mesh8)
    state = jax.tree.map(lambda x, s: jax.device_put(x, s), state, sh)
    step, _ = build(state)
    gb = put_batch(_toy_batch(), mesh8)
    state, metrics = step(state, gb)
    for k in HEALTH_METRIC_KEYS:
        assert k in metrics, k
    assert float(metrics["learning_rate"]) == pytest.approx(float(schedule(0)))
    assert int(jax.device_get(state.step)) == 1  # one optimizer step, not 4
    state, metrics = step(state, gb)
    assert int(jax.device_get(state.step)) == 2
    assert float(metrics["nonfinite_count"]) == 0.0


@pytest.mark.slow  # an AOT fsdp=8 compile + HLO text scan: slow tier
def test_grad_accum_carry_sharded_and_optimizer_outside_scan(setup):
    """The two compiled-program contracts, pinned on a pure-FSDP step:

    1. the scan carry's fp32 accumulators keep the param sharding — no
       while-loop carry element has the FULL global shape of any sharded
       param (a replicated accumulator would put a param-sized fp32 leaf
       in the carry on every device);
    2. the optimizer/clip/health block appears in the program (census
       total > 0) and NO instruction of it sits inside a loop body —
       clip + AdamW run once per optimizer step, after the scan
       (analysis/ir_lint.py once_per_step_placement over the source-span
       metadata of train/step.py optimizer_apply_block).
    """
    import re

    from distributed_llms_example_tpu.analysis.ir_lint import (
        once_per_step_finding,
        once_per_step_placement,
    )
    from distributed_llms_example_tpu.core.config import MeshConfig
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.train.step import once_per_step_source_spans

    lm, params = setup
    mesh = build_mesh(MeshConfig(data=1, fsdp=8, sequence=1, tensor=1))
    tx, schedule = make_optimizer(learning_rate=1e-3, warmup_steps=0, total_steps=100)
    build = make_train_step(
        lm.module, lm.config, tx, schedule, mesh, grad_accum_steps=2, donate=False
    )
    state = create_train_state(shard_params(params, mesh), tx)
    sh = state_shardings(state, mesh)
    state = jax.tree.map(lambda x, s: jax.device_put(x, s), state, sh)
    step, _ = build(state)
    batch = _toy_batch(b=16)  # microbatch 8 rows over 8 fsdp shards
    compiled = step.jitted.lower(state, put_batch(batch, mesh)).compile()
    text = compiled.as_text()

    # -- contract 2: the once-per-step census --------------------------------
    spans = once_per_step_source_spans()
    census = once_per_step_placement(text, spans)
    assert census["total"] > 0, "optimizer block's source spans missing from HLO"
    assert census["in_loop"] == 0, census
    assert once_per_step_finding(text, spans) is None

    # -- contract 1: the scan carry never holds a full-size f32 leaf ---------
    # The program has OTHER while loops on CPU (XLA lowers the embedding
    # backward's scatter-add to a while, and those legitimately carry
    # full-size operands) — the accumulation scan is the one whose carry
    # holds the two f32[] scalars (loss sum, token sum) next to the fp32
    # gradient accumulators.
    carries = re.findall(r"=\s*\(([^)]*)\)\s+while\(", text)
    assert carries, "no while loop found — the accumulation scan is gone"
    scan_carries = [c for c in carries if len(re.findall(r"f32\[\]", c)) >= 2]
    assert len(scan_carries) == 1, (
        f"expected exactly one accumulation-scan while (2 f32[] scalars in "
        f"the carry), found {len(scan_carries)} of {len(carries)}"
    )
    # The carry also legitimately holds FULL-size f32 weights: XLA hoists
    # the all-gathered fsdp params through the while as loop invariants
    # (gather once, use N times).  So "no full shape present" is the wrong
    # predicate — instead count: every shard shape must appear at least as
    # many times as there are param leaves with that shard shape.  A
    # replicated accumulator swaps its shard-shaped carry slot for a
    # full-shaped one and the count drops below the param count.
    from collections import Counter

    carry_counts = Counter(re.findall(r"f32\[[0-9,]*\]", scan_carries[0]))
    shard_counts = Counter()
    n_sharded = 0
    for p_leaf, s_leaf in zip(jax.tree.leaves(state.params), jax.tree.leaves(sh.params)):
        global_shape = tuple(p_leaf.shape)
        shard_shape = s_leaf.shard_shape(global_shape)
        shard_counts["f32[" + ",".join(str(d) for d in shard_shape) + "]"] += 1
        if shard_shape != global_shape:
            n_sharded += 1
    assert n_sharded, "no param is sharded — the fixture mesh is broken"
    for shape, need in shard_counts.items():
        assert carry_counts[shape] >= need, (
            f"scan carry holds {carry_counts[shape]} x {shape} but the param "
            f"tree has {need} leaves with that shard shape — an accumulator "
            f"lost its param sharding (replicated into the carry full-size)"
        )


# sha256 (16 hex) of the StableHLO of the gradient of each family's UNCACHED
# forward, dropout on, matmul precision pinned, locations stripped — read at
# c993dc2, the commit before the K/V cache moved to (slots, length, kv_heads
# x head_dim) (PR 32).
# That PR pulled the cached step out of ``MultiHeadAttention.__call__`` and
# ``T5Attention.__call__`` and promised the train step the parent's program:
# this holds it to that.  A change that means to move the train forward
# replaces these with its own reading (`python -m pytest -k uncached_forward`
# prints the one it got).
UNCACHED_FORWARD_STABLEHLO = {
    "bart-test": "5e92d8ee0010ac50",
    # re-read in PR 40: T5's uncached forward lays its relative bias out from a
    # (heads, 2 q - 1) per-diagonal vector (e8058bedc3fd5580 before); the other
    # three families' programs are the parent's
    "t5-test": "4a1feb71a05810f0",
    "llama-test": "eb36f48211af733e",
    "lfm2-moe-test": "af0a81de6fffcc8e",
}


@pytest.mark.parametrize("name", sorted(UNCACHED_FORWARD_STABLEHLO))
def test_uncached_forward_lowers_to_the_same_program(name):
    import hashlib
    import re

    lm = load_model(name, load_weights=False)
    a_params = jax.eval_shape(lambda: lm.init_params(0))
    ids = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)

    def loss(params, key, *args):
        out = lm.module.apply({"params": params}, *args, deterministic=False, rngs={"dropout": key})
        return (out[0] if isinstance(out, tuple) else out).astype(jnp.float32).sum()

    args = (ids, ids, ids) if lm.is_seq2seq else (ids, ids)
    # pinned, because two test modules set the process's default at import
    with jax.default_matmul_precision("highest"):
        text = jax.jit(jax.grad(loss)).lower(a_params, key, *args).as_text()
    got = hashlib.sha256(re.sub(r"loc\(.*?\)", "", text).encode()).hexdigest()[:16]
    assert got == UNCACHED_FORWARD_STABLEHLO[name], f"{name}: {got} ({len(text)} chars)"


# sha256 (16 hex) of the StableHLO of each serving family's one-row prefill wave
# and decode step at toy sizes (4 slots, prompt 16, 8 new tokens), matmul
# precision pinned, locations stripped — read at c3364de, the parent of PR 40,
# which changed the flash backward and ``models/t5.py`` and promised the serve
# cells the parent's programs (a refused PR 38 had been judged on a serve
# cell's ``setup_s``).  A change that means to move a serve program replaces
# its pair with its own reading (`python -m pytest -k serve_programs` prints it).
SERVE_PROGRAM_STABLEHLO = {
    # PR 46 meant to move both: the wave lays the cross K/V as a cache keeps K/V, the step's cross attention reads it so
    "bart-test": ("56ec9f68a05b4ced", "8aa8e0a431527c60"),
    "lfm2-moe-test": ("528fb781646eb336", "bb94970b379ada29"),
    "brumby-test": ("749984b88dc6fc07", "8ce8b68afc9ea8f6"),
    "mellum-test": ("7f4fd4c1ec83912a", "6c0bec6e7e781b29"),
}


@pytest.mark.parametrize("name", sorted(SERVE_PROGRAM_STABLEHLO))
def test_serve_programs_lower_to_the_same_programs(name):
    import hashlib
    import re

    from distributed_llms_example_tpu.core.config import MeshConfig
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.evaluation.generation import _init_cache
    from distributed_llms_example_tpu.serving.engine import ServeConfig, ServingEngine

    slots_n, prompt, new = 4, 16, 8
    lm = load_model(name, load_weights=False)
    serve = ServeConfig(max_slots=slots_n, prefill_batch=2, max_new_tokens=new, max_source_length=prompt)
    eng = ServingEngine(lm.module, lm.config, build_mesh(MeshConfig(data=-1), devices=jax.devices()[:1]), serve,
                        is_seq2seq=lm.is_seq2seq)
    params = jax.eval_shape(lambda: lm.init_params(0))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    active = jax.ShapeDtypeStruct((slots_n,), jnp.bool_)
    ids = i32(1, prompt)
    with jax.default_matmul_precision("highest"):
        prefill = eng._prefill.lower(params, ids, ids).as_text()
        if lm.is_seq2seq:
            enc, mask, ckv = jax.tree.map(  # a wave's rows -> the slots
                lambda x: jax.ShapeDtypeStruct((slots_n, *x.shape[1:]), x.dtype), eng._prefill.eval_shape(params, ids, ids))
            cache = jax.eval_shape(lambda p: _init_cache(
                eng.model, p, slots_n, new, jnp.zeros(enc.shape, enc.dtype), jnp.zeros(mask.shape, mask.dtype)), params)
            state = {"cache": cache, "enc": enc, "enc_mask": mask, "ckv": ckv, "last": i32(slots_n, 1)}
            step = eng._step.lower(params, state, i32(slots_n), active).as_text()
        else:
            zeros = jnp.zeros((slots_n, prompt), jnp.int32)
            cache, mask, _, _ = jax.eval_shape(lambda p: eng._prefill_core(p, zeros, zeros), params)
            state = {"cache": cache, "mask": mask, "last": i32(slots_n)}
            step = eng._step.lower(params, state, i32(slots_n), i32(slots_n), active).as_text()
    got = tuple(hashlib.sha256(re.sub(r"loc\(.*?\)", "", t).encode()).hexdigest()[:16] for t in (prefill, step))
    assert got == SERVE_PROGRAM_STABLEHLO[name], f"{name}: (prefill, decode step) = {got}"
