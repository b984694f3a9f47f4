"""Static sharding analysis: spec lint, IR lint, composition matrix, CLI.

Acceptance pins (ISSUE 1): the lint CLI flags three seeded violations —
unknown mesh axis, oversized replicated-by-default param, fsdp×1f1b
seq2seq composition — as ``error``, and reports zero error-level findings
on every BASELINE.md config.  Plus the repo AST lint and the analysis-CLI
smoke run (satellite: CI / tooling).
"""

import json

import jax
import pytest
from jax.sharding import PartitionSpec as P

from distributed_llms_example_tpu.analysis import composition
from distributed_llms_example_tpu.analysis.findings import Finding, has_errors
from distributed_llms_example_tpu.analysis.ir_lint import scan_hlo_text
from distributed_llms_example_tpu.analysis.lint import main as lint_main
from distributed_llms_example_tpu.analysis.spec_lint import lint_sharding_rules
from distributed_llms_example_tpu.core.config import MeshConfig, parse_mesh_arg
from distributed_llms_example_tpu.core.mesh import build_mesh
from distributed_llms_example_tpu.parallel.sharding import (
    ShardingRules,
    default_rules,
    find_dead_rules,
    shard_params,
)


def _codes(findings, severity=None):
    return [
        f.code for f in findings if severity is None or f.severity == severity
    ]


def _abstract_llama_params():
    from distributed_llms_example_tpu.models.registry import load_model

    lm = load_model("llama-test", load_weights=False)
    return jax.eval_shape(lambda: lm.init_params(0))


# ---------------------------------------------------------------------------
# pass 1 — spec lint
# ---------------------------------------------------------------------------

def test_spec_lint_unknown_axis_names_the_typo():
    rules = ShardingRules(rules=[(r"mlp/.*proj/kernel", P("fsdp", "tensro"))])
    findings = lint_sharding_rules(
        rules, {"fsdp": 2, "tensor": 2}, _abstract_llama_params()
    )
    errs = [f for f in findings if f.code == "unknown-mesh-axis"]
    assert errs and errs[0].severity == "error"
    assert "tensro" in errs[0].message and "tensor" in errs[0].message  # suggestion


def test_spec_lint_duplicate_axis():
    rules = ShardingRules(rules=[(r"kernel", P("tensor", "tensor"))])
    findings = lint_sharding_rules(rules, {"tensor": 2}, _abstract_llama_params())
    assert "duplicate-spec-axis" in _codes(findings, "error")


def test_spec_lint_dead_rule_is_warning():
    rules = ShardingRules(
        rules=[
            (r"no_such_param/anywhere", P("fsdp")),
            (r"kernel", P("fsdp", "tensor")),
        ]
    )
    findings = lint_sharding_rules(
        rules, {"fsdp": 2, "tensor": 2}, _abstract_llama_params()
    )
    dead = [f for f in findings if f.code == "dead-rule"]
    assert len(dead) == 1 and dead[0].severity == "warning"
    assert "no_such_param" in dead[0].message


def test_spec_lint_oversized_replicated_default():
    # no rules at all: every matmul weight falls through to replicated
    findings = lint_sharding_rules(
        ShardingRules(rules=[]),
        {"fsdp": 8},
        _abstract_llama_params(),
        replicated_bytes_threshold=1024,  # tiny model needs a tiny bar
    )
    over = [f for f in findings if f.code == "oversized-replicated-param"]
    assert over and all(f.severity == "error" for f in over)


def test_spec_lint_oversized_silent_on_pure_data_mesh():
    # pure DP replicates params BY DESIGN — never an error
    findings = lint_sharding_rules(
        ShardingRules(rules=[]),
        {"data": 8},
        _abstract_llama_params(),
        replicated_bytes_threshold=1024,
    )
    assert "oversized-replicated-param" not in _codes(findings)


def test_spec_lint_ragged_dim_warns():
    import numpy as np

    params = {"embed": jax.ShapeDtypeStruct((50265, 64), np.dtype("float32"))}
    rules = ShardingRules(rules=[(r"embed", P(("tensor", "fsdp"), None))])
    findings = lint_sharding_rules(rules, {"tensor": 2, "fsdp": 2}, params)
    ragged = [f for f in findings if f.code == "ragged-dim-replicated"]
    assert ragged and ragged[0].severity == "warning"


def test_default_rules_clean_on_llama_fsdp():
    findings = lint_sharding_rules(
        default_rules(), {"fsdp": 8}, _abstract_llama_params()
    )
    assert not has_errors(findings)


# ---------------------------------------------------------------------------
# pass 3 — composition matrix
# ---------------------------------------------------------------------------

BAD_CASES = [
    # (row id, family, schedule, mesh axes, flags)
    ("grad-accum-pipelined", "llama", "gpipe", {"stage": 2, "data": 2},
     ("pipelined", "grad_accum")),
    ("seq2seq-1f1b-fsdp", "bart", "1f1b", {"stage": 2, "fsdp": 2}, ("pipelined",)),
    ("seq2seq-1f1b-fsdp", "t5", "1f1b", {"stage": 4, "fsdp": 2}, ("pipelined",)),
    ("seq2seq-interleaved", "bart", "interleaved", {"stage": 2}, ("pipelined",)),
    ("seq2seq-pipeline-sequence", "t5", "gpipe", {"stage": 2, "sequence": 2}, ("pipelined",)),
    ("pipeline-sequence-moe", "llama", "gpipe", {"stage": 2, "sequence": 2}, ("pipelined", "moe")),
    ("fused-ce-seq2seq", "bart", None, {"data": 8}, ("fused_ce",)),
    ("fused-ce-model-axes", "llama", None, {"tensor": 2}, ("fused_ce",)),
    ("ring-seq2seq-pipeline", "t5", "gpipe", {"stage": 2, "sequence": 2}, ("pipelined", "ring")),
    ("dense-attention-stage-sequence", "llama", "1f1b", {"stage": 2, "sequence": 2},
     ("pipelined", "forced_dense_attention")),
]


@pytest.mark.parametrize("row_id,family,schedule,axes,flags", BAD_CASES)
def test_every_known_bad_combo_fires(row_id, family, schedule, axes, flags):
    bad = composition.failing_combos(
        family=family, schedule=schedule, mesh_axes=axes, flags=flags
    )
    assert row_id in [r.id for r in bad]
    # validate raises the FIRST failing row's reason (overlapping combos —
    # e.g. ring × seq2seq × pipeline also trips the sequence row — report
    # the most specific/earliest table entry)
    with pytest.raises(ValueError) as ei:
        composition.validate_composition(
            family=family, schedule=schedule, mesh_axes=axes, flags=flags
        )
    assert str(ei.value) == bad[0].reason


def test_good_combos_do_not_fire():
    for family, schedule, axes, flags in [
        ("llama", "1f1b", {"stage": 2, "fsdp": 2, "data": 2}, ("pipelined",)),
        ("bart", "gpipe", {"stage": 2, "fsdp": 2, "data": 2}, ("pipelined",)),
        ("bart", "1f1b", {"stage": 2, "data": 2, "tensor": 2}, ("pipelined",)),
        ("llama", None, {"data": 4, "fsdp": 2}, ("fused_ce",)),
        ("t5", None, {"data": 4, "sequence": 2}, ()),
        # in-step accumulation composes with every GSPMD mesh; only
        # stage>1 (the pipeline's own microbatching) is condemned
        ("llama", None, {"data": 4, "fsdp": 2}, ("grad_accum",)),
        ("bart", None, {"data": 2, "fsdp": 2, "tensor": 2}, ("grad_accum",)),
    ]:
        composition.validate_composition(
            family=family, schedule=schedule, mesh_axes=axes, flags=flags
        )


def test_executor_guard_uses_table_message():
    """The deep guard in the seq2seq executor raises the table row's text
    (it cannot drift from the adapter-construction message)."""
    import jax.numpy as jnp

    from distributed_llms_example_tpu.parallel.pipeline_seq2seq import (
        pipeline_value_and_grad_seq2seq,
    )

    mesh = build_mesh(MeshConfig(stage=2, data=2, fsdp=2, sequence=1, tensor=1))
    with pytest.raises(ValueError, match="fsdp"):
        pipeline_value_and_grad_seq2seq(
            None, None, None, {"w": jnp.zeros((2, 1))}, {"w": jnp.zeros((2, 1))},
            {}, jnp.zeros((4, 4, 8)), jnp.zeros((4, 2, 8)), {}, {},
            mesh=mesh, num_microbatches=2,
        )


def test_adapters_reject_known_bad_at_construction():
    """Satellite: every known-bad combo reachable through an adapter ctor
    is rejected at construction with the table-driven message."""
    from distributed_llms_example_tpu.models.bart import PipelinedBart
    from distributed_llms_example_tpu.models.llama import PipelinedLlama
    from distributed_llms_example_tpu.models.registry import (
        BART_CONFIGS,
        LLAMA_CONFIGS,
        T5_CONFIGS,
    )
    from distributed_llms_example_tpu.models.t5 import PipelinedT5

    fsdp_mesh = build_mesh(MeshConfig(stage=2, data=2, fsdp=2, sequence=1, tensor=1))
    seq_mesh = build_mesh(MeshConfig(stage=2, data=2, fsdp=1, sequence=2, tensor=1))

    # seq2seq 1f1b × fsdp at stage > 1 — both families
    with pytest.raises(ValueError, match="fsdp"):
        PipelinedBart(BART_CONFIGS["bart-test"], fsdp_mesh, schedule="1f1b")
    with pytest.raises(ValueError, match="fsdp"):
        PipelinedT5(T5_CONFIGS["t5-test"], fsdp_mesh, schedule="1f1b")
    # interleaved is decoder-only
    with pytest.raises(ValueError, match="interleaved"):
        PipelinedBart(BART_CONFIGS["bart-test"], fsdp_mesh, schedule="interleaved")
    # seq2seq pipeline × sequence parallelism
    with pytest.raises(ValueError, match="sequence"):
        PipelinedT5(T5_CONFIGS["t5-test"], seq_mesh, schedule="gpipe")
    # MoE × sequence under the pipeline
    with pytest.raises(ValueError, match="MoE"):
        PipelinedLlama(LLAMA_CONFIGS["mixtral-test"], seq_mesh, schedule="gpipe")
    # same meshes construct fine on allowed schedules/families
    PipelinedBart(BART_CONFIGS["bart-test"], fsdp_mesh, schedule="gpipe")
    PipelinedLlama(LLAMA_CONFIGS["llama-test"], seq_mesh, schedule="gpipe")


# ---------------------------------------------------------------------------
# pass 2 — IR scanner (pure text)
# ---------------------------------------------------------------------------

_SYNTH_HLO = """\
HloModule synth

ENTRY %main {
  %p0 = bf16[64,64]{1,0} parameter(0)
  %c1 = f32[64,64]{1,0} convert(bf16[64,64]{1,0} %p0)
  %p1 = f32[64,64]{1,0} parameter(1)
  %dot.1 = f32[64,64]{1,0} dot(f32[64,64]{1,0} %c1, f32[64,64]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ag.1 = f32[4096,4096]{1,0} all-gather(f32[512,4096]{1,0} %p1), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %ar.1 = f32[64]{0} all-reduce(f32[64]{0} %p1), replica_groups={{0},{1},{2},{3}}, to_apply=%add
  %ar.2 = f32[64]{0} all-reduce(f32[64]{0} %p1), replica_groups={{0,1},{2,3}}, to_apply=%add
  ROOT %t.1 = f32[64,64]{1,0} tuple(%dot.1)
}
"""


def test_ir_scanner_flags_gather_on_unsharded_mesh():
    findings = scan_hlo_text(_SYNTH_HLO, mesh_axes={"data": 8})
    gather = [f for f in findings if f.code == "full-param-all-gather"]
    assert gather and gather[0].severity == "error"
    assert gather[0].context["max_bytes"] == 4096 * 4096 * 4


def test_ir_scanner_mega_gather_on_fsdp_mesh():
    findings = scan_hlo_text(
        _SYNTH_HLO, mesh_axes={"fsdp": 8}, largest_param_bytes=1024 * 1024
    )
    assert "full-param-all-gather" not in _codes(findings)  # fsdp gathers are the design
    mega = [f for f in findings if f.code == "fused-mega-all-gather"]
    assert mega and mega[0].severity == "warning"


def test_ir_scanner_precision_promotion():
    findings = scan_hlo_text(
        _SYNTH_HLO, mesh_axes={"fsdp": 8}, promotion_smell=("bf16", "f32")
    )
    promo = [f for f in findings if f.code == "matmul-precision-promotion"]
    assert promo and "dot.1" in promo[0].context["instructions"]
    # fp32 policy has nothing to violate
    clean = scan_hlo_text(_SYNTH_HLO, mesh_axes={"fsdp": 8}, promotion_smell=None)
    assert "matmul-precision-promotion" not in _codes(clean)


def test_ir_scanner_degenerate_collective():
    findings = scan_hlo_text(_SYNTH_HLO, mesh_axes={"fsdp": 8})
    degen = [f for f in findings if f.code == "degenerate-collective"]
    assert degen and degen[0].context["instructions"] == ["ar.1"]  # ar.2 is real
    census = [f for f in findings if f.code == "collective-census"][0]
    assert census.context["census"] == {"all-gather": 1, "all-reduce": 2}


_ASYNC_HLO = """\
HloModule async

ENTRY %main {
  %p1 = f32[512,4096]{1,0} parameter(0)
  %ags.1 = (f32[512,4096]{1,0}, f32[4096,4096]{1,0}) all-gather-start(f32[512,4096]{1,0} %p1), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %agd.1 = f32[4096,4096]{1,0} all-gather-done((f32[512,4096]{1,0}, f32[4096,4096]{1,0}) %ags.1)
  %ars.1 = f32[64]{0} all-reduce-start(f32[64]{0} %p1), replica_groups={{0},{1},{2},{3}}, to_apply=%add
  ROOT %t.1 = f32[4096,4096]{1,0} tuple(%agd.1)
}
"""


def test_ir_scanner_parses_async_tuple_collectives():
    """TPU HLO emits async pairs with tuple-shaped -start defs; the
    scanner must size them (max tuple element = the gathered result) and
    see their replica groups."""
    findings = scan_hlo_text(_ASYNC_HLO, mesh_axes={"data": 8})
    gather = [f for f in findings if f.code == "full-param-all-gather"]
    assert gather and gather[0].context["max_bytes"] == 4096 * 4096 * 4
    degen = [f for f in findings if f.code == "degenerate-collective"]
    assert degen and degen[0].context["instructions"] == ["ars.1"]
    census = [f for f in findings if f.code == "collective-census"][0]
    assert census.context["census"] == {
        "all-gather-start": 1, "all-reduce-start": 1,
    }


_HOST_XFER_HLO = """\
HloModule leaky

ENTRY %main {
  %p1 = f32[64,64]{1,0} parameter(0)
  %send.1 = (f32[64,64]{1,0}, u32[], token[]) send(f32[64,64]{1,0} %p1, token[] %tok), channel_id=1, is_host_transfer=true
  %send.2 = (f32[64,64]{1,0}, u32[], token[]) send(f32[64,64]{1,0} %p1, token[] %tok), channel_id=2
  %out.1 = token[] outfeed(f32[64,64]{1,0} %p1, token[] %tok)
  %cc.1 = f32[64,64]{1,0} custom-call(f32[64,64]{1,0} %p1), custom_call_target="MoveToHost"
  ROOT %t.1 = f32[64,64]{1,0} tuple(%p1)
}
"""


def test_ir_scanner_host_transfer_in_step():
    """The ROADMAP 'host-transfer ops inside the step body' smell: outfeed,
    is_host_transfer-attributed send, and MoveToHost custom-calls are
    errors; an UN-attributed send (device-to-device channel traffic) is
    not flagged."""
    findings = scan_hlo_text(_HOST_XFER_HLO, mesh_axes={"data": 8})
    host = [f for f in findings if f.code == "host-transfer-in-step"]
    assert host and host[0].severity == "error"
    flagged = host[0].context["instructions"]
    assert "send.1" in flagged and "out.1" in flagged and "cc.1" in flagged
    assert "send.2" not in flagged


def test_ir_scanner_host_transfer_clean_on_synth_and_real_step():
    # the synthetic collective program carries no host traffic
    assert "host-transfer-in-step" not in _codes(
        scan_hlo_text(_SYNTH_HLO, mesh_axes={"data": 8})
    )


def test_policy_promotion_smell():
    from distributed_llms_example_tpu.core.precision import Policy, parse_dtype

    assert Policy(compute_dtype=parse_dtype("bfloat16")).matmul_promotion_smell() == ("bf16", "f32")
    assert Policy(compute_dtype=parse_dtype("float32")).matmul_promotion_smell() is None


# ---------------------------------------------------------------------------
# the CLI — seeded violations + BASELINE configs
# ---------------------------------------------------------------------------

def _run_cli(capsys, *argv):
    rc = lint_main(["--json", *argv])
    out = capsys.readouterr().out
    findings = [
        json.loads(line) for line in out.splitlines()
        if line.startswith("{") and json.loads(line).get("event") == "lint_finding"
    ]
    return rc, findings


def test_cli_seeded_unknown_mesh_axis(capsys):
    rc, findings = _run_cli(capsys, "--model", "t5-small", "--mesh", "datta=8")
    assert rc == 1
    f = [x for x in findings if x["code"] == "unknown-mesh-axis"]
    assert f and f[0]["severity"] == "error" and "data" in f[0]["message"]


def test_cli_seeded_oversized_replicated(capsys):
    rc, findings = _run_cli(
        capsys, "--model", "llama-2-7b", "--mesh", "fsdp=8",
        "--rules-json", "[]", "--no-ir",
    )
    assert rc == 1
    assert any(
        f["code"] == "oversized-replicated-param" and f["severity"] == "error"
        for f in findings
    )


def test_cli_seeded_seq2seq_1f1b_fsdp(capsys):
    rc, findings = _run_cli(
        capsys, "--model", "bart-large-cnn", "--mesh", "stage=2,fsdp=2,data=2",
        "--pipeline-schedule", "1f1b", "--no-ir",
    )
    assert rc == 1
    assert any(
        f["code"] == "seq2seq-1f1b-fsdp" and f["severity"] == "error"
        for f in findings
    )


# every BASELINE.md config must come out clean (error-free)
BASELINE_CONFIGS = [
    ("t5-small", "data=1"),
    ("t5-base", "data=-1"),
    ("bart-large-cnn", "data=8"),
    ("flan-t5-xl", "fsdp=8"),
    ("llama-2-7b", "fsdp=8"),
]


@pytest.mark.parametrize("model,mesh", BASELINE_CONFIGS)
def test_cli_baseline_configs_error_free(capsys, model, mesh):
    rc, findings = _run_cli(capsys, "--model", model, "--mesh", mesh, "--no-ir")
    assert rc == 0
    assert [f for f in findings if f["severity"] == "error"] == []


def test_cli_ir_pass_smoke(capsys):
    """The full three-pass run, AOT compile included, on the tiny config."""
    rc, findings = _run_cli(
        capsys, "--model", "t5-test", "--mesh", "data=2,fsdp=2,tensor=2",
        "--batch", "8", "--src-len", "64", "--tgt-len", "16",
    )
    assert rc == 0
    census = [f for f in findings if f["code"] == "collective-census"]
    assert census, "IR pass should have run and reported its census"
    assert [f for f in findings if f["severity"] == "error"] == []


def test_cli_strict_promotes_warnings(capsys):
    # the stock multi-family rule set's dead entries are info (by design),
    # so --strict stays green on a clean default config...
    rc, findings = _run_cli(
        capsys, "--model", "t5-small", "--mesh", "data=1", "--no-ir", "--strict"
    )
    assert rc == 0
    assert all(f["severity"] == "info" for f in findings if f["code"] == "dead-rule")
    # ...but a CUSTOM rule set's dead rule is a warning, and --strict
    # fails on it
    custom = '[["encoder/.*/kernel", ["fsdp", "tensor"]], ["typo/never", ["fsdp"]]]'
    rc, findings = _run_cli(
        capsys, "--model", "t5-small", "--mesh", "data=1",
        "--rules-json", custom, "--no-ir",
    )
    assert rc == 0  # dead rule is only a warning
    assert any(
        f["code"] == "dead-rule" and f["severity"] == "warning" for f in findings
    )
    rc, _ = _run_cli(
        capsys, "--model", "t5-small", "--mesh", "data=1",
        "--rules-json", custom, "--no-ir", "--strict",
    )
    assert rc == 1


def test_startup_lint_runs_from_train_config():
    from distributed_llms_example_tpu.analysis.lint import startup_lint
    from distributed_llms_example_tpu.core.config import TrainConfig

    cfg = TrainConfig(model_ckpt="t5-test", mesh=MeshConfig(data=2, fsdp=1))
    findings = startup_lint(cfg)
    assert findings and not has_errors(findings)
    # a known-bad combo surfaces as an error finding, not a crash
    bad = TrainConfig(
        model_ckpt="bart-test",
        pipeline_schedule="1f1b",
        mesh=MeshConfig(stage=2, fsdp=2, data=2),
    )
    assert has_errors(startup_lint(bad))


# ---------------------------------------------------------------------------
# satellites: mesh-axis typo, dead-rule warning, memory-audit --strict,
# repo AST lint
# ---------------------------------------------------------------------------

def test_parse_mesh_arg_names_typo_with_suggestion():
    with pytest.raises(ValueError, match="did you mean 'data'"):
        parse_mesh_arg("datta=2")
    with pytest.raises(ValueError, match="valid axes"):
        parse_mesh_arg("bogus=2")


def test_shard_params_warns_on_dead_rules(capsys, dp_mesh):
    import numpy as np

    params = {"layer": {"kernel": np.zeros((8, 8), np.float32)}}
    rules = ShardingRules(rules=[
        (r"kernel", P()),
        (r"no_such/param", P("fsdp")),
    ])
    assert find_dead_rules(rules, params) == [r"no_such/param"]
    shard_params(params, dp_mesh, rules)
    events = [
        json.loads(line) for line in capsys.readouterr().out.splitlines()
        if line.startswith("{")
    ]
    dead = [e for e in events if e.get("event") == "dead_sharding_rules"]
    assert dead and dead[0]["patterns"] == [r"no_such/param"]


def test_memory_audit_strict_flag():
    from distributed_llms_example_tpu.utils.memory_audit import main as audit_main

    args = [
        "--model", "llama-2-7b", "--mesh", "fsdp=8", "--batch", "8",
        "--remat", "--grad-accum-steps", "8", "--analytic",
    ]
    # optimistic bound fits on one v5e-8 host...
    assert audit_main(args) == 0
    # ...but the conservative gradient-liveness bound does not: --strict
    # makes that CI-visible
    assert audit_main(args + ["--strict"]) == 1


def test_repo_lint_clean_and_catches_violations(tmp_path):
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "repo_lint",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "repo_lint.py"),
    )
    repo_lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(repo_lint)

    # the repo itself is clean (this IS the CI check)
    assert repo_lint.main([]) == 0

    # a hot-path sync is caught — device_get by BOTH rule 1 (hot-path
    # sync) and rule 4 (step-cadence conversion; train/step.py is in
    # STEP_CADENCE_FILES), block_until_ready by rule 1
    bad_step = tmp_path / "step.py"
    bad_step.write_text("import jax\nx = jax.device_get(y)\nz = y.block_until_ready()\n")
    rel = os.path.join("distributed_llms_example_tpu", "train", "step.py")
    assert len(repo_lint.lint_file(str(bad_step), rel)) == 3

    # a bare axis-name spec outside parallel/ is caught, tuples included
    bad_spec = tmp_path / "rogue.py"
    bad_spec.write_text(
        "from jax.sharding import PartitionSpec as P\ns = P(('data', 'fsdp'), None)\n"
    )
    rel = os.path.join("distributed_llms_example_tpu", "models", "rogue.py")
    assert len(repo_lint.lint_file(str(bad_spec), rel)) == 1
    # ...but the same spec inside parallel/ is the sharding layer's job
    rel = os.path.join("distributed_llms_example_tpu", "parallel", "rogue.py")
    assert repo_lint.lint_file(str(bad_spec), rel) == []

    # rule 5: raw dropout primitives in models//train/ bypass the shared
    # fused helper (ops/fused_dropout.py) — aliased spellings included
    bad_drop = tmp_path / "dropmodel.py"
    bad_drop.write_text(
        "import flax.linen as nn\nimport jax\n"
        "from flax import linen\nfrom jax import random\n"
        "d = nn.Dropout(0.1)\n"
        "d2 = linen.Dropout(0.1)\n"
        "d3 = Dropout(0.1)\n"  # bare name NOT from the helper
        "m = jax.random.bernoulli(key, 0.9, (4, 4))\n"
        "m2 = random.bernoulli(key, 0.9, (4, 4))\n"
    )
    rel = os.path.join("distributed_llms_example_tpu", "models", "dropmodel.py")
    assert len(repo_lint.lint_file(str(bad_drop), rel)) == 5
    rel = os.path.join("distributed_llms_example_tpu", "train", "dropmodel.py")
    assert len(repo_lint.lint_file(str(bad_drop), rel)) == 5
    # ...the ops/ layer IS the implementation (helper + attention reference)
    rel = os.path.join("distributed_llms_example_tpu", "ops", "dropmodel.py")
    assert repo_lint.lint_file(str(bad_drop), rel) == []
    # the helper's OWN class, imported from ops.fused_dropout, is the
    # sanctioned spelling
    ok_drop = tmp_path / "okmodel.py"
    ok_drop.write_text(
        "from distributed_llms_example_tpu.ops.fused_dropout import Dropout\n"
        "d = Dropout(0.1)\n"
    )
    rel = os.path.join("distributed_llms_example_tpu", "models", "okmodel.py")
    assert repo_lint.lint_file(str(ok_drop), rel) == []

    # rule 12: time.sleep inside an except handler is an ad-hoc retry
    # loop — any spelling (time.sleep, aliased sleep, bare sleep)
    bad_retry = tmp_path / "retry.py"
    bad_retry.write_text(
        "import time\nfrom time import sleep\n"
        "try:\n    f()\nexcept OSError:\n"
        "    time.sleep(1)\n    sleep(2)\n"
    )
    rel = os.path.join("distributed_llms_example_tpu", "io", "retry.py")
    assert len(repo_lint.lint_file(str(bad_retry), rel)) == 2
    # ...the designated backoff helper is the owner; and a sleep OUTSIDE
    # an except handler (a poll cadence, not a retry) stays legal
    rel = os.path.join("distributed_llms_example_tpu", "utils", "backoff.py")
    assert repo_lint.lint_file(str(bad_retry), rel) == []
    ok_poll = tmp_path / "poll.py"
    ok_poll.write_text("import time\nwhile x:\n    time.sleep(0.1)\n")
    rel = os.path.join("distributed_llms_example_tpu", "obs", "poll.py")
    assert repo_lint.lint_file(str(ok_poll), rel) == []
    # the sanctioned call site: sleep_backoff in an except handler
    ok_retry = tmp_path / "okretry.py"
    ok_retry.write_text(
        "from distributed_llms_example_tpu.utils.backoff import sleep_backoff\n"
        "try:\n    f()\nexcept OSError:\n    d = sleep_backoff(d, cap_s=2.0)\n"
    )
    rel = os.path.join("distributed_llms_example_tpu", "io", "okretry.py")
    assert repo_lint.lint_file(str(ok_retry), rel) == []

    # rule 14: inline percentile/quantile computation outside the one
    # owner — numpy spellings and the sorted-index rank idiom both fork
    # the quantile definition the tail-latency gates compare against
    bad_pct = tmp_path / "pct.py"
    bad_pct.write_text(
        "import numpy as np\n"
        "p = np.percentile(xs, 99)\n"
        "q = np.quantile(xs, 0.99)\n"
        "r = sorted(xs)[int(0.99 * (len(xs) - 1))]\n"
    )
    rel = os.path.join("distributed_llms_example_tpu", "serving", "pct.py")
    assert len(repo_lint.lint_file(str(bad_pct), rel)) == 3
    # ...the owner holds the one definition
    rel = os.path.join("distributed_llms_example_tpu", "obs", "spans.py")
    assert repo_lint.lint_file(str(bad_pct), rel) == []
    # the sanctioned spelling, and a plain sorted()[0] (min, not a
    # quantile), stay legal everywhere
    ok_pct = tmp_path / "okpct.py"
    ok_pct.write_text(
        "from distributed_llms_example_tpu.obs.spans import percentiles\n"
        "(p99,) = percentiles(xs, (0.99,))\n"
        "first = sorted(xs)[0]\n"
    )
    rel = os.path.join("distributed_llms_example_tpu", "serving", "okpct.py")
    assert repo_lint.lint_file(str(ok_pct), rel) == []

    # rule 15: raw memory_stats()/live_buffers() reads outside the memory
    # owners fork the HBM account (no absent-beats-zero, no watermark
    # delta semantics) — any qualifier spelling
    bad_mem = tmp_path / "mem.py"
    bad_mem.write_text(
        "import jax\n"
        "for d in jax.local_devices():\n"
        "    s = d.memory_stats()\n"
        "b = jax.local_devices()[0].live_buffers()\n"
    )
    rel = os.path.join("distributed_llms_example_tpu", "serving", "mem.py")
    assert len(repo_lint.lint_file(str(bad_mem), rel)) == 2
    # ...both owners hold the raw reads
    rel = os.path.join("distributed_llms_example_tpu", "obs", "memprof.py")
    assert repo_lint.lint_file(str(bad_mem), rel) == []
    rel = os.path.join("distributed_llms_example_tpu", "utils", "memory_audit.py")
    assert repo_lint.lint_file(str(bad_mem), rel) == []
    # the sanctioned read path stays legal everywhere
    ok_mem = tmp_path / "okmem.py"
    ok_mem.write_text(
        "from distributed_llms_example_tpu.obs import memprof\n"
        "stats = memprof.hbm_stats()\n"
        "wm = memprof.Watermark()\n"
    )
    rel = os.path.join("distributed_llms_example_tpu", "serving", "okmem.py")
    assert repo_lint.lint_file(str(ok_mem), rel) == []

    # rule 16: the block-identity ledger is cache_pool.py's alone — a
    # refcount poked from outside the owner breaks the refcount ==
    # live-references invariant, and a second hashlib-based block hash
    # in serving/ forks the chained content identity
    bad_px = tmp_path / "px.py"
    bad_px.write_text(
        "import hashlib\n"
        "from hashlib import sha256\n"
        "pool._ref[b] -= 1\n"
        "h = pool._hash_of.get(b)\n"
        "blk = pool._index[h]\n"
        "pool._lru.pop(b, None)\n"
    )
    rel = os.path.join("distributed_llms_example_tpu", "serving", "px.py")
    assert len(repo_lint.lint_file(str(bad_px), rel)) == 6
    # ...the owner holds the ledger and the hash
    rel = os.path.join("distributed_llms_example_tpu", "serving", "cache_pool.py")
    assert repo_lint.lint_file(str(bad_px), rel) == []
    # hashlib outside serving/ is fine (checkpoint digests etc.); the
    # ledger attrs stay forbidden repo-wide
    bad_ref = tmp_path / "ref.py"
    bad_ref.write_text("import hashlib\npool._ref[b] += 1\n")
    rel = os.path.join("distributed_llms_example_tpu", "io", "ref.py")
    assert len(repo_lint.lint_file(str(bad_ref), rel)) == 1
    # the sanctioned API stays legal everywhere in serving/
    ok_px = tmp_path / "okpx.py"
    ok_px.write_text(
        "from distributed_llms_example_tpu.serving import cache_pool\n"
        "hashes = cache_pool.chain_hashes(toks, 8)\n"
        "chain = pool.match_chain(hashes)\n"
        "pool.acquire(chain)\n"
        "pool.free(chain)\n"
    )
    rel = os.path.join("distributed_llms_example_tpu", "serving", "okpx.py")
    assert repo_lint.lint_file(str(ok_px), rel) == []


# ---------------------------------------------------------------------------
# grad accumulation (ISSUE 5): accumulator-mirror spec lint, the
# once-per-step placement census, the ppermute-chain smell, rule 5a
# ---------------------------------------------------------------------------


def test_spec_lint_accumulator_mirror_clean_and_catches_drift(monkeypatch):
    """The fp32 accumulators must mirror the param specs leaf for leaf:
    the live accumulator_shardings is the identity (clean), and an edit
    that replicates the accumulators is an error naming the leaf."""
    import distributed_llms_example_tpu.train.step as step_mod
    from distributed_llms_example_tpu.analysis.spec_lint import lint_accumulator_mirror

    a_params = _abstract_llama_params()
    assert lint_accumulator_mirror(a_params) == []

    # a drifted implementation: replicate every accumulator leaf
    monkeypatch.setattr(
        step_mod, "accumulator_shardings",
        lambda tree: jax.tree.map(lambda s: P(), tree),
    )
    findings = lint_accumulator_mirror(a_params)
    assert findings and all(f.severity == "error" for f in findings)
    assert {f.code for f in findings} == {"accumulator-spec-mismatch"}
    # only the genuinely sharded leaves drifted (replicated ones still match)
    assert any("kernel" in f.message for f in findings)


def test_ir_once_per_step_placement_fixture():
    """Hand-built HLO: the census attributes span-stamped instructions to
    their computation, and the finding fires iff optimizer code sits in a
    while-body (or warns when the metadata is missing entirely)."""
    from distributed_llms_example_tpu.analysis.ir_lint import (
        once_per_step_finding,
        once_per_step_placement,
    )
    from distributed_llms_example_tpu.train.step import once_per_step_source_spans

    spans = once_per_step_source_spans()
    f, first, _last = spans[0]
    meta = f'metadata={{op_name="adamw" source_file="{f}" source_line={first}}}'

    def prog(opt_in_body: bool) -> str:
        body_extra = f"\n  %opt.b = f32[] add(f32[] %g.1, f32[] %g.1), {meta}" if opt_in_body else ""
        entry_extra = "" if opt_in_body else f"\n  %opt.e = f32[] add(f32[] %c.1, f32[] %c.1), {meta}"
        return f"""HloModule fixture

%body.1 (p.1: (s32[], f32[])) -> (s32[], f32[]) {{
  %p.1 = (s32[], f32[]) parameter(0)
  %i.1 = s32[] get-tuple-element((s32[], f32[]) %p.1), index=0
  %g.1 = f32[] get-tuple-element((s32[], f32[]) %p.1), index=1{body_extra}
  ROOT %t.1 = (s32[], f32[]) tuple(%i.1, %g.1)
}}

%cond.1 (q.1: (s32[], f32[])) -> pred[] {{
  %q.1 = (s32[], f32[]) parameter(0)
  ROOT %lt.1 = pred[] compare(s32[] %j.1, s32[] %n.1), direction=LT
}}

ENTRY %main.1 (a.1: f32[]) -> f32[] {{
  %c.1 = f32[] parameter(0)
  %init.1 = (s32[], f32[]) tuple(s32[] %z.1, f32[] %c.1)
  %w.1 = (s32[], f32[]) while((s32[], f32[]) %init.1), condition=%cond.1, body=%body.1{entry_extra}
  ROOT %r.1 = f32[] get-tuple-element((s32[], f32[]) %w.1), index=1
}}
"""

    good = prog(opt_in_body=False)
    census = once_per_step_placement(good, spans)
    assert census == {"total": 1, "in_loop": 0, "in_loop_examples": []}
    assert once_per_step_finding(good, spans) is None

    bad = prog(opt_in_body=True)
    census = once_per_step_placement(bad, spans)
    assert census["total"] == 1 and census["in_loop"] == 1
    finding = once_per_step_finding(bad, spans)
    assert finding is not None and finding.severity == "error"
    assert finding.code == "optimizer-in-scan-body"

    # no span-stamped instruction at all: the census proves nothing → warning
    empty = prog(opt_in_body=False).replace(meta, "")
    finding = once_per_step_finding(empty, spans)
    assert finding is not None and finding.severity == "warning"
    assert finding.code == "optimizer-census-empty"


_PPERMUTE_CHAIN_HLO = """\
HloModule rings

ENTRY %main {
  %p0 = f32[64]{0} parameter(0)
  %cp.1 = f32[64]{0} collective-permute(f32[64]{0} %p0), source_target_pairs={{0,1},{1,0}}
  %cp.2 = f32[64]{0} collective-permute(f32[64]{0} %cp.1), source_target_pairs={{0,1},{1,0}}
  %cp.3 = f32[64]{0} collective-permute(f32[64]{0} %cp.2), source_target_pairs={{0,1},{1,0}}
  ROOT %t.1 = f32[64]{0} tuple(%cp.3)
}
"""


def test_ir_ppermute_chain_smell_fixture():
    """The ROADMAP smell, pinned on a hand-built 3-permute dependency
    chain: longer than the stage ring → warning with the chain length;
    within the ring, or no stage axis → silent."""
    from distributed_llms_example_tpu.analysis.ir_lint import (
        parse_hlo_instructions,
        ppermute_chain_smell,
    )

    instrs = parse_hlo_instructions(_PPERMUTE_CHAIN_HLO)
    smell = ppermute_chain_smell(instrs, {"stage": 2})
    assert smell is not None and smell.severity == "warning"
    assert smell.code == "ppermute-chain-exceeds-stage-ring"
    assert smell.context == {"chain_length": 3, "stage": 2}
    # a 3-hop chain fits a 4-stage ring; stage=1 has no ring at all
    assert ppermute_chain_smell(instrs, {"stage": 4}) is None
    assert ppermute_chain_smell(instrs, {"stage": 1, "data": 8}) is None
    # mixed stage x sequence: ring/context-parallel permutes chain once
    # per layer and are textually indistinguishable — the smell stands down
    assert ppermute_chain_smell(instrs, {"stage": 2, "sequence": 2}) is None
    # wired into the scanner (stage>1 meshes only)
    findings = scan_hlo_text(_PPERMUTE_CHAIN_HLO, mesh_axes={"stage": 2, "data": 2})
    assert "ppermute-chain-exceeds-stage-ring" in _codes(findings)
    findings = scan_hlo_text(_PPERMUTE_CHAIN_HLO, mesh_axes={"data": 8})
    assert "ppermute-chain-exceeds-stage-ring" not in _codes(findings)


def test_cli_grad_accum_pipelined_composition(capsys):
    """--grad-accum-steps > 1 on a stage>1 mesh is condemned by the
    composition table before any compile."""
    rc, findings = _run_cli(
        capsys, "--model", "llama-test", "--mesh", "stage=2,data=2",
        "--grad-accum-steps", "2", "--no-ir",
    )
    assert rc == 1
    assert any(f.get("code") == "grad-accum-pipelined" for f in findings)


def test_repo_lint_grad_accum_rule(tmp_path):
    """Rule 5a: a manual gradient accumulator outside train/step.py is a
    rogue second accumulation layer — flagged in models/ and train/,
    exempt in the owning file and in parallel/ (the pipeline executors'
    schedule-internal microbatching)."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "repo_lint",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "repo_lint.py"),
    )
    repo_lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(repo_lint)

    bad = tmp_path / "acc.py"
    bad.write_text(
        "import jax\n"
        "from jax.tree_util import tree_map\n"
        "def f(acc, grads, loss, x):\n"
        "    acc += grads\n"
        "    acc = jax.tree.map(lambda a, g: a + g, acc, grads)\n"
        "    acc = tree_map(lambda a, g: a + g, acc, grads)\n"  # bare-name import must not evade
        "    loss += x\n"  # non-gradient accumulator stays legal
        "    return acc, loss\n"
    )
    rel = os.path.join("distributed_llms_example_tpu", "models", "acc.py")
    assert len(repo_lint.lint_file(str(bad), rel)) == 3
    rel = os.path.join("distributed_llms_example_tpu", "train", "acc.py")
    assert len(repo_lint.lint_file(str(bad), rel)) == 3
    # the owner is exempt — train/step.py IS the accumulation layer
    rel = os.path.join("distributed_llms_example_tpu", "train", "step.py")
    assert repo_lint.lint_file(str(bad), rel) == []
    # parallel/ owns the pipeline executors' microbatching
    rel = os.path.join("distributed_llms_example_tpu", "parallel", "acc.py")
    assert repo_lint.lint_file(str(bad), rel) == []


def test_repo_lint_grad_collective_rule(tmp_path):
    """Rule 9 (ISSUE 12): a raw lax.psum / psum_scatter / all_to_all over
    a gradient tree — or a manual int8 cast of gradients — outside
    train/step.py bypasses the --grad-compression dispatch
    (ops/quant_collectives.py: error feedback, shared-scale int-safe
    wire, off-path bit-identity pin)."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "repo_lint",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "repo_lint.py"),
    )
    repo_lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(repo_lint)

    bad = tmp_path / "qc.py"
    bad.write_text(
        "import jax\n"
        "import jax.numpy as jnp\n"
        "from jax import lax\n"
        "def f(grads, x, axis):\n"
        "    g = lax.psum(grads, axis)\n"
        "    g2 = lax.psum_scatter(grads, axis)\n"
        "    g3 = jax.lax.all_to_all(grads, axis, 0, 0)\n"
        "    q = grads.astype(jnp.int8)\n"
        "    q2 = grads.astype(dtype=jnp.int8)\n"  # kwarg form must not evade
        "    ok = lax.psum(x, axis)\n"  # non-gradient collectives stay legal
        "    ok2 = x.astype(jnp.int8)\n"  # non-gradient int8 casts too
        "    return g, g2, g3, q, ok, ok2\n"
    )
    # Under models/ rule 10 (KV-cast ownership, ISSUE 13) also fires on
    # every astype(int8) — including the non-gradient one — on top of
    # rule 9's five hits; under train/ only rule 9 applies.
    for d, expected in (("models", 8), ("train", 5)):
        rel = os.path.join("distributed_llms_example_tpu", d, "qc.py")
        violations = repo_lint.lint_file(str(bad), rel)
        assert len(violations) == expected, violations
        assert any("quant_collectives" in v for v in violations)
    # the owners are exempt: train/step.py calls the compression layer,
    # ops/ and parallel/ ARE implementation layers
    rel = os.path.join("distributed_llms_example_tpu", "train", "step.py")
    assert repo_lint.lint_file(str(bad), rel) == []
    rel = os.path.join("distributed_llms_example_tpu", "ops", "qc.py")
    assert repo_lint.lint_file(str(bad), rel) == []


def test_repo_lint_kv_cast_rule(tmp_path):
    """Rule 10 (ISSUE 13): a raw ``.astype(int8/uint8)`` in models/,
    serving/, evaluation/ or ops/mha.py forks the KV-cache number format
    away from the quantize_kv/dequantize_kv scale contract; the owners
    (ops/flash_attention.py, serving/cache_pool.py) stay exempt, and
    int8 *allocation* (jnp.zeros) stays legal everywhere."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "repo_lint",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "repo_lint.py"),
    )
    repo_lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(repo_lint)

    bad = tmp_path / "kv.py"
    bad.write_text(
        "import jax.numpy as jnp\n"
        "def f(k, v):\n"
        "    qk = k.astype(jnp.int8)\n"
        "    qv = v.astype(dtype=jnp.uint8)\n"  # kwarg + uint8 must not evade
        "    pool = jnp.zeros((4, 8), jnp.int8)\n"  # allocation stays legal
        "    wide = k.astype(jnp.float32)\n"  # non-int8 casts stay legal
        "    return qk, qv, pool, wide\n"
    )
    for d in ("models", "serving", "evaluation"):
        rel = os.path.join("distributed_llms_example_tpu", d, "kv.py")
        violations = repo_lint.lint_file(str(bad), rel)
        assert len(violations) == 2, violations
        assert all("quantize_kv" in v for v in violations)
    # the cache-write site is covered by file, not dir
    rel = os.path.join("distributed_llms_example_tpu", "ops", "mha.py")
    assert len(repo_lint.lint_file(str(bad), rel)) == 2
    # the owners are exempt; so is everything outside the covered dirs
    for rel in (
        os.path.join("distributed_llms_example_tpu", "ops", "flash_attention.py"),
        os.path.join("distributed_llms_example_tpu", "serving", "cache_pool.py"),
        os.path.join("distributed_llms_example_tpu", "train", "kv.py"),
    ):
        assert repo_lint.lint_file(str(bad), rel) == []


def test_repo_lint_mesh_ownership_rule(tmp_path):
    """Rule 11 (ISSUE 14): raw ``Mesh(...)`` construction and any
    ``jax.distributed.*`` call outside core/mesh.py fork the distributed
    lifecycle the topology-change path owns (teardown ordering, the
    topology-aware device order, the gloo-on-CPU flag); ``AbstractMesh``
    (shape-only, no devices) stays legal, and the owner is exempt."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "repo_lint",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "repo_lint.py"),
    )
    repo_lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(repo_lint)

    bad = tmp_path / "m.py"
    bad.write_text(
        "import jax\n"
        "import numpy as np\n"
        "from jax.sharding import Mesh, AbstractMesh\n"
        "def f(devs):\n"
        "    m = Mesh(np.array(devs).reshape(2, 4), ('data', 'fsdp'))\n"
        "    m2 = jax.sharding.Mesh(devs, ('data',))\n"
        "    jax.distributed.initialize('c:1', 2, 0)\n"
        "    jax.distributed.shutdown()\n"
        "    ok = AbstractMesh((2,), ('data',))\n"  # shape-only: legal
        "    return m, m2, ok\n"
    )
    rel = os.path.join("distributed_llms_example_tpu", "train", "m.py")
    violations = repo_lint.lint_file(str(bad), rel)
    assert len(violations) == 4, violations
    assert any("build_mesh" in v for v in violations)
    assert any("reinitialize_distributed" in v for v in violations)
    # the owner is exempt
    rel = os.path.join("distributed_llms_example_tpu", "core", "mesh.py")
    assert repo_lint.lint_file(str(bad), rel) == []


def test_repo_lint_ckpt_manager_rule(tmp_path):
    """Rule 6 (ISSUE 6): bare orbax ``manager.save``/``manager.restore``
    outside io/checkpoint.py bypasses the integrity wrappers (save
    retry/backoff, checksum manifest, verify-before-restore with
    fallback) — flagged everywhere except the owning module."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "repo_lint",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "repo_lint.py"),
    )
    repo_lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(repo_lint)

    bad = tmp_path / "rogue_ckpt.py"
    bad.write_text(
        "def f(self, manager, ckpt_manager, state, step):\n"
        "    manager.save(step, state)\n"
        "    manager.restore(step)\n"
        "    self.manager.save(step, state)\n"       # attribute base too
        "    ckpt_manager.restore(step)\n"           # aliased spelling
        "    self.checkpointer.save(step, state)\n"  # the WRAPPER is legal
        "    manager.wait_until_finished()\n"  # non-save/restore call is ok
    )
    rel = os.path.join("distributed_llms_example_tpu", "train", "rogue_ckpt.py")
    violations = repo_lint.lint_file(str(bad), rel)
    assert len(violations) == 4
    assert all("verified checkpoint wrappers" in v for v in violations)
    # the owning module holds the one sanctioned call site
    rel = os.path.join("distributed_llms_example_tpu", "io", "checkpoint.py")
    assert repo_lint.lint_file(str(bad), rel) == []


def test_spec_lint_optimizer_moment_mirror_clean_and_catches_anchor():
    """The adam moments resolve to the param specs under the stock rules
    (their paths END with the param path and the regexes are unanchored);
    an anchored rule that matches the param but not its moment path is
    exactly the drift this pass exists to catch."""
    from distributed_llms_example_tpu.analysis.spec_lint import (
        lint_optimizer_moment_mirror,
    )
    from distributed_llms_example_tpu.parallel.sharding import ShardingRules

    a_params = _abstract_llama_params()
    assert lint_optimizer_moment_mirror(a_params) == []

    anchored = ShardingRules(rules=[(r"^block_0/self_attn", P("fsdp", "tensor"))])
    findings = lint_optimizer_moment_mirror(a_params, anchored)
    assert findings and all(f.severity == "error" for f in findings)
    assert {f.code for f in findings} == {"optimizer-moment-spec-mismatch"}
    assert any("mu" in f.message for f in findings)


def test_ir_census_counts_fp32_param_copies():
    """The in-place contract extension: span-attributed f32 copy
    instructions whose element count matches a param leaf are counted
    (and the finding fires) only when param_elems is supplied — the
    legacy census dict shape is untouched otherwise."""
    from distributed_llms_example_tpu.analysis.ir_lint import (
        in_place_apply_finding,
        once_per_step_placement,
    )
    from distributed_llms_example_tpu.train.step import once_per_step_source_spans

    spans = once_per_step_source_spans()
    f, first, _last = spans[0]
    meta = f'metadata={{op_name="adamw" source_file="{f}" source_line={first}}}'
    text = f"""HloModule fixture

ENTRY %main.1 (a.1: f32[128]) -> f32[128] {{
  %c.1 = f32[128]{{0}} parameter(0)
  %cp.1 = f32[128]{{0}} copy(f32[128]{{0}} %c.1), {meta}
  %cp.2 = f32[64]{{0}} copy(f32[64]{{0}} %c.1), {meta}
  %cp.3 = s32[128]{{0}} copy(s32[128]{{0}} %c.1), {meta}
  %cp.4 = f32[128]{{0}} copy(f32[128]{{0}} %c.1)
  %cp.5 = (f32[128]{{0}}, f32[128]{{0}}, u32[]) copy-start(f32[128]{{0}} %c.1), {meta}
  ROOT %r.1 = f32[128]{{0}} add(f32[128]{{0}} %cp.1, f32[128]{{0}} %cp.1), {meta}
}}
"""
    # legacy shape: no param_elems, no copy keys
    census = once_per_step_placement(text, spans)
    assert census == {"total": 5, "in_loop": 0, "in_loop_examples": []}
    # with param elems: the f32[128] span-attributed copies count — incl.
    # the ASYNC copy-start tuple form (its largest tuple element is the
    # copied buffer); the wrong-size (64), wrong-dtype (s32), and
    # unattributed copies do not
    census = once_per_step_placement(
        text, spans, param_elems=[128], min_copy_elems=0
    )
    assert census["fp32_param_copies"] == 2
    assert census["fp32_copy_examples"] == ["main.1:%cp.1", "main.1:%cp.5"]
    finding = in_place_apply_finding(text, spans, [128], min_copy_elems=0)
    assert finding is not None and finding.severity == "warning"
    assert finding.code == "optimizer-param-copy"
    # no matching copies → no finding
    assert in_place_apply_finding(text, spans, [999], min_copy_elems=0) is None
    # the default floor excludes small layout-normalization relayouts:
    # the same program is clean without the explicit floor override
    assert in_place_apply_finding(text, spans, [128]) is None


def test_repo_lint_optim_apply_rule(tmp_path):
    """Rule 8: raw apply_updates / manual p - lr*u tree-maps are
    forbidden in models/ and train/ outside train/optim.py (the
    --optim-impl dispatch owner)."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "repo_lint",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "repo_lint.py"),
    )
    repo_lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(repo_lint)

    bad = tmp_path / "rogue_optim.py"
    bad.write_text(
        "import jax, optax\n"
        "def apply(params, updates, lr, learning_rate):\n"
        "    p1 = optax.apply_updates(params, updates)\n"          # 1
        "    p2 = apply_updates(params, updates)\n"                # 2
        "    p3 = jax.tree.map(lambda p, u: p - lr * u, params, updates)\n"  # 3
        "    p4 = jax.tree_util.tree_map(\n"                       # 4
        "        lambda p, u: p + (-learning_rate) * u, params, updates)\n"
        "    ok = jax.tree.map(lambda a, b: a + b, params, updates)\n"
        "    return p1, p2, p3, p4, ok\n"
    )
    for layer in ("models", "train"):
        rel = os.path.join("distributed_llms_example_tpu", layer, "rogue_optim.py")
        violations = repo_lint.lint_file(str(bad), rel)
        assert len(violations) == 4, (layer, violations)
        assert sum("apply_updates" in v for v in violations) == 2
        assert sum("p - lr*u" in v for v in violations) == 2
    # train/optim.py owns the apply; other layers are out of scope
    rel = os.path.join("distributed_llms_example_tpu", "train", "optim.py")
    assert repo_lint.lint_file(str(bad), rel) == []
    rel = os.path.join("distributed_llms_example_tpu", "serving", "rogue_optim.py")
    assert repo_lint.lint_file(str(bad), rel) == []
    # and the live tree stays clean under the new rule
    assert repo_lint.main([]) == 0


def test_repo_lint_rank_conditional_rule(tmp_path):
    """Rule 13 (ISSUE 16): a bare ``process_index()``/``process_count()``
    conditional outside the rank-branching owners is forbidden — raw rank
    identity feeding a branch is the pod-deadlock seed the divergence
    pass hunts semantically; this is the cheap lexical backstop."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "repo_lint",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "repo_lint.py"),
    )
    repo_lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(repo_lint)

    # (the annotated repo being clean is already pinned by
    # test_repo_lint_clean_and_catches_violations's main([]) run — rule 13
    # rides the same driver, so a whole-tree re-lint here is pure wall)

    bad = tmp_path / "rogue.py"
    bad.write_text(
        "import jax\n"
        "if jax.process_index() == 0:\n"
        "    save()\n"
        "while jax.process_count() > 1:\n"
        "    sync()\n"
        "x = 1 if jax.process_index() else 0\n"
        "assert jax.process_count() == 8\n"
    )
    rel = os.path.join("distributed_llms_example_tpu", "train", "rogue.py")
    violations = repo_lint.lint_file(str(bad), rel)
    assert len(violations) == 4
    assert all("pod-agreed" in v for v in violations)

    # ...every whitelisted owner keeps its rank-branching license
    for owner in sorted(repo_lint.RANK_CONDITIONAL_OWNERS):
        assert repo_lint.lint_file(str(bad), owner) == []

    # a NON-conditional use (gating nothing) is not rule 13's business
    ok_use = tmp_path / "use.py"
    ok_use.write_text("import jax\npid = jax.process_index()\n")
    assert repo_lint.lint_file(str(ok_use), rel) == []

    # the pragma waives, on either the statement or the call line
    waived = tmp_path / "waived.py"
    waived.write_text(
        "import jax\n"
        "if jax.process_count() == 1:  # pod-agreed: pod-uniform fast path\n"
        "    save()\n"
        "if (  # pod-agreed: pod-uniform guard\n"
        "    jax.process_count() > 1\n"
        "):\n"
        "    sync()\n"
    )
    assert repo_lint.lint_file(str(waived), rel) == []
