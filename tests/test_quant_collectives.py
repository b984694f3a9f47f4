"""Quantized gradient collectives (--grad-compression int8): wire math,
error-feedback contracts, composition, and the compiled-program proofs.

Pure-function tests run the reduction with ``mesh=None`` (identical math,
no sharding pins); compiled tests ride the 8-device mesh fixtures — the
data=2 x fsdp=2 x tensor=2 mesh exercises the worker tiling against both
model-sharding axes, and the data=8 mesh is where the census A/B reads
cleanest (the replica leg IS the whole gradient reduction there)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_llms_example_tpu.data.batching import LABEL_PAD
from distributed_llms_example_tpu.models.registry import load_model
from distributed_llms_example_tpu.ops.quant_collectives import (
    GRAD_WORKER_AXES,
    block_size_for,
    dequantize_blocks,
    error_feedback_shardings,
    error_feedback_specs,
    quantize_blocks,
    quantized_tree_reduce,
    stochastic_round,
    tiled_spec,
    worker_count,
    zero_error_feedback,
)
from distributed_llms_example_tpu.parallel.sharding import shard_params
from distributed_llms_example_tpu.train.optim import make_optimizer
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


# ---------------------------------------------------------------------------
# pure wire math (mesh=None: same code path, no sharding pins)
# ---------------------------------------------------------------------------


def test_quantize_roundtrip_error_bound():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (4, 8, 256)) * 3.0
    q, scale = quantize_blocks(x, key, block=64)
    assert q.dtype == jnp.int8
    deq = dequantize_blocks(q, scale[None], block=64)
    # stochastic rounding error is strictly under one quantization step
    step = np.repeat(np.asarray(scale), 64, axis=-1)[None]
    assert np.all(np.abs(np.asarray(deq - x)) <= step + 1e-7)


def test_stochastic_rounding_unbiased():
    v = jnp.asarray([0.25, -1.75, 3.5, -0.01])
    keys = jax.random.split(jax.random.PRNGKey(1), 4000)
    samples = jax.vmap(lambda k: stochastic_round(v, k))(keys)
    mean = np.asarray(jnp.mean(samples, axis=0))
    np.testing.assert_allclose(mean, np.asarray(v), atol=0.05)


def test_integer_sum_order_free():
    """Shared scales + int32 tile sums: permuting the worker order changes
    nothing, bit for bit — the determinism float reductions cannot give."""
    key = jax.random.PRNGKey(2)
    g = jax.random.normal(key, (4, 2, 256))
    q, scale = quantize_blocks(g, key, block=256)
    s1 = jnp.sum(q.astype(jnp.int32), axis=0)
    s2 = jnp.sum(q[::-1].astype(jnp.int32), axis=0)
    assert np.array_equal(np.asarray(s1), np.asarray(s2))


def test_reduce_matches_true_sum_within_bound():
    key = jax.random.PRNGKey(3)
    g = {"w": jax.random.normal(key, (4, 16, 512))}
    ef = zero_error_feedback({"w": jnp.zeros((16, 512))}, 4)
    out, new_ef = quantized_tree_reduce(g, ef, key)
    true = np.asarray(jnp.sum(g["w"], axis=0))
    got = np.asarray(out["w"])
    # worst case: W per-worker quantization steps of error per element
    q, scale = quantize_blocks(g["w"], key, block=256)
    bound = 4 * np.repeat(np.asarray(scale), 256, axis=-1) + 1e-6
    assert np.all(np.abs(got - true) <= bound)
    assert float(jnp.max(jnp.abs(new_ef["w"]))) > 0.0


def test_error_feedback_telescopes():
    """Sum of applied (reduced) gradients over K steps == sum of true
    gradient sums, up to the FINAL residual — the EF contract: no error
    is ever lost, only deferred one step."""
    key = jax.random.PRNGKey(4)
    W, shape = 4, (8, 256)
    ef = zero_error_feedback({"w": jnp.zeros(shape)}, W)
    applied = np.zeros(shape, np.float64)
    true = np.zeros(shape, np.float64)
    for k in range(5):
        g = {"w": jax.random.normal(jax.random.fold_in(key, k), (W,) + shape)}
        out, ef = quantized_tree_reduce(
            g, ef, jax.random.fold_in(key, 100 + k)
        )
        applied += np.asarray(out["w"], np.float64)
        true += np.asarray(jnp.sum(g["w"], axis=0), np.float64)
    residual = np.asarray(jnp.sum(ef["w"], axis=0), np.float64)
    np.testing.assert_allclose(applied + residual, true, atol=2e-4)


def test_small_leaves_take_fp32_fallback():
    key = jax.random.PRNGKey(5)
    g = {"scale": jax.random.normal(key, (4, 64))}  # 64 elems << floor
    ef = zero_error_feedback({"scale": jnp.zeros((64,))}, 4)
    out, new_ef = quantized_tree_reduce(g, ef, key)
    np.testing.assert_allclose(
        np.asarray(out["scale"]), np.asarray(jnp.sum(g["scale"], axis=0)),
        rtol=1e-6,
    )
    assert float(jnp.max(jnp.abs(new_ef["scale"]))) == 0.0


def test_block_size_respects_shards():
    assert block_size_for(512, 1) == 256
    assert block_size_for(512, 2) == 256
    assert block_size_for(512, 4) == 128  # per-shard extent caps the block
    assert block_size_for(12, 1) == 12
    assert block_size_for(7, 1) == 7


# ---------------------------------------------------------------------------
# layout contracts: tiled specs, EF mirror lint
# ---------------------------------------------------------------------------


def test_tiled_spec_prefixes_workers():
    assert tiled_spec(P("fsdp", "tensor")) == P("data", "fsdp", "tensor")
    assert tiled_spec(P()) == P("data")
    tree = error_feedback_specs({"a": P(("tensor", "fsdp"), None)})
    assert tree["a"] == P("data", ("tensor", "fsdp"), None)


def test_ef_mirror_lint_green_and_seeded_violation(monkeypatch):
    from distributed_llms_example_tpu.analysis import spec_lint
    from distributed_llms_example_tpu.ops import quant_collectives

    lm = load_model("t5-test", load_weights=False)
    a_params = jax.eval_shape(lambda: lm.init_params(0))
    assert spec_lint.lint_error_feedback_mirror(a_params) == []

    # seed a drift: an EF layout that re-shards the residual against the
    # tiled gradients (drops the param spec's first entry)
    def drifted(spec_tree):
        return jax.tree.map(
            lambda s: P("data", *([None] + list(s[1:]) if len(s) else [])),
            spec_tree,
            is_leaf=lambda x: isinstance(x, P),
        )

    monkeypatch.setattr(quant_collectives, "error_feedback_specs", drifted)
    findings = spec_lint.lint_error_feedback_mirror(a_params)
    assert any(f.code == "error-feedback-spec-mismatch" for f in findings)


def test_composition_rows():
    from distributed_llms_example_tpu.analysis.composition import (
        check_composition,
        config_flags,
        failing_combos,
    )

    flags = config_flags(pipelined=False, grad_compression="int8")
    assert "grad_compression" in flags
    assert config_flags(pipelined=False, grad_compression="off") == set()
    # pipelined: bad
    bad = failing_combos(
        family="llama", schedule="gpipe",
        mesh_axes={"stage": 2, "data": 2},
        flags=("grad_compression", "pipelined"),
    )
    assert any(r.id == "grad-compression-pipelined" for r in bad)
    # sequence: bad
    bad = failing_combos(
        family="llama", mesh_axes={"sequence": 2, "data": 2},
        flags=("grad_compression",),
    )
    assert any(r.id == "grad-compression-sequence" for r in bad)
    # gspmd data x fsdp: no failing row
    assert not failing_combos(
        family="t5", mesh_axes={"data": 2, "fsdp": 4},
        flags=("grad_compression",),
    )
    assert not check_composition(
        family="t5", mesh_axes={"data": 2, "fsdp": 4},
        flags=("grad_compression", "grad_accum"),
    )


def test_make_train_step_guards():
    lm = load_model("t5-test", load_weights=False)
    tx, schedule = make_optimizer(total_steps=10)
    with pytest.raises(ValueError, match="grad_compression"):
        make_train_step(
            lm.module, lm.config, tx, schedule, None, grad_compression="int4"
        )


# ---------------------------------------------------------------------------
# the compiled step (mesh8 = data2 x fsdp2 x tensor2)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def t5():
    lm = load_model("t5-test")
    params = jax.device_get(lm.init_params(0))
    return lm, params


def _build(lm, params, mesh, mode, accum=1, lr=1e-3):
    tx, schedule = make_optimizer(
        learning_rate=lr, warmup_steps=0, total_steps=1000
    )
    state = create_train_state(
        shard_params(params, mesh), tx,
        grad_compression=mode, workers=worker_count(dict(mesh.shape)),
    )
    sh = state_shardings(state, mesh)
    state = jax.tree.map(lambda x, s: jax.device_put(x, s), state, sh)
    build = make_train_step(
        lm.module, lm.config, tx, schedule, mesh,
        grad_accum_steps=accum, grad_compression=mode, donate=False,
    )
    step, _ = build(state)
    return step, state, sh


@pytest.fixture(scope="module")
def int8_step(mesh8, t5):
    lm, params = t5
    return _build(lm, params, mesh8, "int8")


@pytest.fixture(scope="module")
def off_step(mesh8, t5):
    lm, params = t5
    return _build(lm, params, mesh8, "off")


def test_int8_step_trains_and_ef_sharded(mesh8, t5, int8_step, off_step):
    _, params = t5
    step_i, state_i, sh = int8_step
    step_o, state_o, _ = off_step
    batch = put_batch(_toy_batch(), mesh8)
    s1, m1 = step_i(state_i, batch)
    s0, m0 = step_o(state_o, batch)
    # loss is computed BEFORE the reduction — identical; grad_norm sees
    # only quantization noise
    assert float(m1["loss"]) == pytest.approx(float(m0["loss"]), abs=1e-6)
    g0, g1 = float(m0["grad_norm"]), float(m1["grad_norm"])
    assert abs(g0 - g1) / g0 < 5e-3
    # EF populated and laid out per the contract: worker dim over the
    # replica axes, inner dims exactly the param specs
    ef_sh = error_feedback_shardings(sh.params, mesh8)
    for (path, leaf), (_, want) in zip(
        jax.tree_util.tree_leaves_with_path(s1.ef),
        jax.tree_util.tree_leaves_with_path(ef_sh),
    ):
        assert leaf.sharding.spec == want.spec, path
    assert max(
        float(jnp.max(jnp.abs(e))) for e in jax.tree.leaves(s1.ef)
    ) > 0.0


def test_off_program_bit_identical(mesh8, t5):
    """--grad-compression off must be byte-for-byte the pre-compression
    program: the default build and an explicit off build lower to the
    SAME text (no code motion on the default path)."""
    lm, params = t5
    from distributed_llms_example_tpu.parallel.activation import (
        activation_mesh,
    )

    tx, schedule = make_optimizer(
        learning_rate=1e-3, warmup_steps=0, total_steps=1000
    )
    state = create_train_state(shard_params(params, mesh8), tx)
    sh = state_shardings(state, mesh8)
    state = jax.tree.map(lambda x, s: jax.device_put(x, s), state, sh)
    batch = put_batch(_toy_batch(), mesh8)
    texts = []
    for kw in ({}, {"grad_compression": "off"}):
        build = make_train_step(
            lm.module, lm.config, tx, schedule, mesh8, donate=False, **kw
        )
        step, _ = build(state)
        with activation_mesh(mesh8):
            texts.append(step.jitted.lower(state, batch).as_text())
    assert texts[0] == texts[1]


@pytest.mark.slow
def test_int8_accum_matches_single_shot(mesh8, t5, int8_step):
    """int8 at accum=2 accumulates TILED partials and reduces once — the
    same quantizer input as accum=1, so losses and grad norms match to
    scan-reassociation noise."""
    lm, params = t5
    step1, state1, _ = int8_step
    step2, state2, _ = _build(lm, params, mesh8, "int8", accum=2)
    batch = put_batch(_toy_batch(), mesh8)
    _, m1 = step1(state1, batch)
    _, m2 = step2(state2, batch)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), abs=1e-5)
    assert float(m1["grad_norm"]) == pytest.approx(
        float(m2["grad_norm"]), rel=1e-3
    )


def test_int8_convergence_matches_fp32(mesh8, t5, int8_step, off_step):
    """Short convergence A/B on the t5-test recipe: the int8 trajectory
    tracks fp32 within tolerance (stochastic rounding is unbiased and EF
    carries what it misses)."""
    step_i, state_i, _ = int8_step
    step_o, state_o, _ = off_step
    batch = put_batch(_toy_batch(), mesh8)
    li = lo = None
    si, so = state_i, state_o
    for _ in range(8):
        si, mi = step_i(si, batch)
        so, mo = step_o(so, batch)
        li, lo = float(mi["loss"]), float(mo["loss"])
    assert lo < 6.0  # it actually trained
    assert abs(li - lo) / lo < 0.02, (li, lo)


def test_int8_census_and_comm_account(dp_mesh, t5):
    """The compiled-program verdict on the pure-replica mesh (data=8):
    the int8 program's gradient collectives ride s8, the off program's
    ride f32, and the byte accounts drop accordingly — the ir-lint
    census and the obs comm account pinned EQUAL on the same parse."""
    import math

    from distributed_llms_example_tpu.analysis.ir_lint import (
        int8_compression_missing_finding,
        parse_hlo_instructions,
        quantized_gradient_census,
        scan_hlo_text,
    )
    from distributed_llms_example_tpu.obs.gauges import collective_traffic
    from distributed_llms_example_tpu.parallel.activation import (
        activation_mesh,
    )

    lm, params = t5
    batch = put_batch(_toy_batch(), dp_mesh)
    texts = {}
    for mode in ("off", "int8"):
        step, state, _ = _build(lm, params, dp_mesh, mode)
        with activation_mesh(dp_mesh):
            texts[mode] = step.jitted.lower(state, batch).compile().as_text()
    counts = [int(math.prod(x.shape)) for x in jax.tree.leaves(params)]
    axes = dict(dp_mesh.shape)
    census = {
        m: quantized_gradient_census(parse_hlo_instructions(t), counts, axes)
        for m, t in texts.items()
    }
    # int8 program: s8 gradient collectives present; off program: none
    assert census["int8"]["s8_gradient_collectives"]
    assert not census["off"]["s8_gradient_collectives"]
    assert int8_compression_missing_finding(census["off"], "int8") is not None
    assert int8_compression_missing_finding(census["int8"], "int8") is None
    # wire estimate: ~4x fewer gradient bytes moved (f32 all-reduce ->
    # s8 all-to-all + s8 all-gather); the quantized program keeps only
    # small f32 scale traffic on the gradient account
    wire_ratio = census["off"]["gradient_wire_bytes"] / max(
        1, census["int8"]["gradient_wire_bytes"]
    )
    assert wire_ratio > 3.0, census
    s8_bytes = census["int8"]["gradient_bytes_by_dtype"].get("s8", 0)
    f32_bytes = census["int8"]["gradient_bytes_by_dtype"].get("f32", 0)
    assert s8_bytes > f32_bytes, census["int8"]
    # the obs comm account classifies the SAME bytes (shared parser +
    # candidate set): total gradient bytes equal, per parse
    for mode in ("off", "int8"):
        instrs = parse_hlo_instructions(texts[mode])
        acct = collective_traffic(instrs, counts, 8)
        assert acct["gradient_bytes"] == sum(
            census[mode]["gradient_bytes_by_dtype"].values()
        ), mode
    # and scan_hlo_text carries the census in its collective-census info
    findings = scan_hlo_text(
        texts["int8"], mesh_axes=axes, param_element_counts=counts,
        grad_compression="int8",
    )
    info = [f for f in findings if f.code == "collective-census"][0]
    assert info.context["s8_gradient_collectives"]
    assert not any(f.code == "int8-compression-missing" for f in findings)


@pytest.mark.slow
def test_checkpoint_roundtrip_and_zero_fill(tmp_path, mesh8, t5, int8_step):
    """EF rides checkpoints: an int8 state restores bit-equal (including
    the residual); a checkpoint written WITHOUT compression restores into
    an int8 run with the EF tree zero-filled (restore-less resume)."""
    from distributed_llms_example_tpu.io.checkpoint import (
        Checkpointer,
        abstract_like,
    )

    step_i, state_i, sh = int8_step
    batch = put_batch(_toy_batch(), mesh8)
    trained, _ = step_i(state_i, batch)  # non-zero EF

    ck = Checkpointer(str(tmp_path / "int8"), save_every_steps=1, keep=2,
                      async_save=False)
    assert ck.save(1, trained, force=True)
    restored, step_no = ck.restore_latest(abstract_like(trained, sh))
    assert step_no == 1
    for a, b in zip(jax.tree.leaves(trained.ef), jax.tree.leaves(restored.ef)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ck.close()

    # off-written checkpoint -> int8 resume: restore the ef-less shapes,
    # then zero-fill (the trainer's fallback path does exactly this)
    lm, params = t5
    off = create_train_state(shard_params(params, mesh8), make_optimizer(total_steps=10)[0])
    off_sh = state_shardings(off, mesh8)
    ck2 = Checkpointer(str(tmp_path / "off"), save_every_steps=1, keep=2,
                       async_save=False)
    assert ck2.save(1, off, force=True)
    with pytest.raises(Exception):
        ck2.restore_latest(abstract_like(trained, sh))
    bare = abstract_like(trained, sh).replace(ef=None)
    restored, _ = ck2.restore_latest(bare)
    filled = restored.replace(ef=jax.tree.map(
        lambda s, z: jax.device_put(z, s),
        sh.ef,
        zero_error_feedback(restored.params, worker_count(dict(mesh8.shape))),
    ))
    assert all(
        float(jnp.max(jnp.abs(e))) == 0.0 for e in jax.tree.leaves(filled.ef)
    )
    for (p, a), (_, b) in zip(
        jax.tree_util.tree_leaves_with_path(filled.ef),
        jax.tree_util.tree_leaves_with_path(sh.ef),
    ):
        assert a.sharding.spec == b.spec, p
    ck2.close()


def test_cli_flag_and_config():
    from distributed_llms_example_tpu.core.config import TrainConfig
    from distributed_llms_example_tpu.launch.cli import build_parser

    args = build_parser().parse_args(
        ["--grad-compression", "int8", "--train-file", "x.json"]
    )
    from distributed_llms_example_tpu.core.config import config_from_args

    cfg = config_from_args(args)
    assert cfg.grad_compression == "int8"
    assert TrainConfig().grad_compression == "off"


def test_strict_gradient_bytes_ceiling(tmp_path, capsys):
    """obs.report --strict --max-gradient-bytes-per-step: fails a run
    whose startup byte account exceeds the ceiling OR that emitted no
    account at all (silently lost compression must not pass); green
    under the ceiling."""
    from distributed_llms_example_tpu.obs.report import main as report_main

    def write(dirname, recs):
        d = tmp_path / dirname / "obs"
        os.makedirs(d, exist_ok=True)
        with open(d / "metrics-p000.jsonl", "w") as f:
            for r in recs:
                f.write(json.dumps({"schema_version": 1, **r}) + "\n")
        return tmp_path / dirname

    def gate(run, ceiling):
        return report_main([
            str(run), "--strict", "--json",
            "--max-gradient-bytes-per-step", ceiling,
        ])

    gauges = {
        "event": "obs_gauges", "mesh": {"data": 8}, "flops_per_step": 1.0,
        "grad_compression": "int8",
        "comm": {
            "all-to-all": {"count": 2, "gradient_bytes": 1000,
                           "activation_bytes": 0},
            "total_bytes": 1000, "gradient_bytes": 1000,
            "activation_bytes": 0,
        },
    }
    good = write("good", [gauges])
    assert gate(good, "2000") == 0
    assert gate(good, "500") == 1
    # no obs_gauges record at all: the gate must fail, not pass silently
    empty = write("empty", [{"step": 1, "loss": 1.0}])
    assert gate(empty, "2000") == 1
    capsys.readouterr()
