"""The obs/ telemetry stack (ISSUE 2).

Acceptance pins: span nesting/percentiles on a fake clock; MFU math
against a hand-computed FLOP count; collective-traffic accounting against
a known FSDP HLO (reduce-scatter vs all-reduce split); the Valohai stdout
byte-parity contract; the MetricLogger cadence fix; and the end-to-end
``--obs jsonl`` stream whose gradient all-gather/reduce-scatter byte
totals match the IR lint's independent accounting of the same compiled
step.  The heartbeat's real multi-process leg rides the slow tier next to
tests/test_multiprocess.py; its skew math is unit-tested here.

This module is tier-1 (not slow) and budgeted: the instrumentation it
tests must itself be cheap (test_span_recording_time_budget).
"""

from __future__ import annotations

import json
import os
import time

import jax
import numpy as np
import pytest

from distributed_llms_example_tpu.core.config import (
    CheckpointConfig,
    MeshConfig,
    TrainConfig,
)
from distributed_llms_example_tpu.core.mesh import build_mesh
from distributed_llms_example_tpu.obs import sink as sink_mod
from distributed_llms_example_tpu.obs.gauges import (
    collective_traffic,
    mfu,
    training_flops_estimate,
)
from distributed_llms_example_tpu.obs.heartbeat import Heartbeat, detect_laggards
from distributed_llms_example_tpu.obs.profile import ProfileController, parse_profile_steps
from distributed_llms_example_tpu.obs.spans import SpanRecorder, percentiles
from distributed_llms_example_tpu.utils.jsonlog import MetricLogger, log_json


@pytest.fixture(autouse=True)
def _default_sink():
    """Every test starts and ends on the plain stdout sink, whatever a
    previous test (or a Trainer construction) installed."""
    sink_mod.install_sink(sink_mod.build_sink("stdout", ""))
    yield
    sink_mod.install_sink(sink_mod.build_sink("stdout", ""))


def _json_lines(text: str) -> list[dict]:
    out = []
    for line in text.splitlines():
        if line.startswith("{"):
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                pass
    return out


# ---------------------------------------------------------------------------
# spans: fake clock, nesting, percentiles, straggler flag
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def test_span_nesting_and_window_summary_on_fake_clock():
    clock = FakeClock()
    rec = SpanRecorder(clock=clock)
    for step_time in (0.1, 0.1, 0.1, 0.5):  # one fat straggler step
        with rec.span("step_dispatch"):
            with rec.span("data_wait"):  # nested span
                clock.advance(step_time / 2)
            clock.advance(step_time / 2)
        rec.step_complete()
    s = rec.summary()
    assert s["window_steps"] == 4
    assert s["step_ms_p50"] == pytest.approx(100.0)
    assert s["step_ms_max"] == pytest.approx(500.0)
    assert s["straggler"] is True  # 500 > 2 × 100
    assert s["spans"]["step_dispatch"]["count"] == 4
    # nested data_wait time is counted inside BOTH spans (nesting, not
    # exclusive attribution)
    assert s["spans"]["data_wait"]["total_ms"] == pytest.approx(400.0)
    assert s["spans"]["step_dispatch"]["total_ms"] == pytest.approx(800.0)
    # summary resets the window
    assert rec.summary() is None
    with rec.span("eval"):
        clock.advance(1.0)
    rec.step_complete()
    s2 = rec.summary()
    assert s2["window_steps"] == 1 and "step_dispatch" not in s2["spans"]
    assert s2["straggler"] is False


def test_mark_step_start_excludes_eval_time():
    """Checkpoint/eval wall time between steps rides its own span, not
    the next step's ring-buffer duration (which would flag every healthy
    eval cadence as a straggler)."""
    clock = FakeClock()
    rec = SpanRecorder(clock=clock)
    clock.advance(0.1)
    rec.step_complete()
    with rec.span("eval"):
        clock.advance(5.0)  # a fat eval after the step
    rec.mark_step_start()
    clock.advance(0.1)
    rec.step_complete()
    s = rec.summary()
    assert s["step_ms_max"] == pytest.approx(100.0)  # eval's 5 s excluded
    assert s["straggler"] is False
    assert s["spans"]["eval"]["total_ms"] == pytest.approx(5000.0)


def test_percentiles_nearest_rank():
    vals = [1.0, 2.0, 3.0, 4.0, 5.0]
    p50, p95, p0 = percentiles(vals, (0.5, 0.95, 0.0))
    assert (p50, p95, p0) == (3.0, 5.0, 1.0)
    assert percentiles([], (0.5,)) == [0.0]


def test_span_recording_time_budget():
    """The instrumentation must be hot-path cheap: 20k span enter/exits
    plus step bookkeeping in well under a second (it measures host clock
    reads and dict updates, nothing else)."""
    rec = SpanRecorder()
    t0 = time.perf_counter()
    for _ in range(20_000):
        with rec.span("step_dispatch"):
            pass
        rec.step_complete()
    assert rec.summary()["window_steps"] == 20_000
    assert time.perf_counter() - t0 < 1.0


# ---------------------------------------------------------------------------
# one primitive, two clocks: every span also opens a profiler annotation
# ---------------------------------------------------------------------------

class FakeAnnotations:
    """Annotation factory double: records each annotation's name, stats and
    extent on the recorder's (fake) clock, in opening order."""

    def __init__(self, clock):
        self.clock = clock
        self.events: list[dict] = []

    def __call__(self, name, **stats):
        return _FakeAnnotation(self, name, stats)

    def named(self, name):
        return [e for e in self.events if e["name"] == name]


class _FakeAnnotation:
    def __init__(self, owner, name, stats):
        self.owner = owner
        self.event = {"name": name, "stats": dict(stats)}

    def __enter__(self):
        self.event["start"] = self.owner.clock()
        self.owner.events.append(self.event)
        return self

    def __exit__(self, *exc):
        self.event["end"] = self.owner.clock()
        return False

    def set_metadata(self, **stats):
        self.event["stats"].update(stats)


@pytest.mark.parametrize("scope", ["train", "serve"])
def test_every_span_opens_an_annotation_named_by_scope(scope):
    clock = FakeClock()
    notes = FakeAnnotations(clock)
    rec = SpanRecorder(clock=clock, scope=scope, annotate=notes)
    with rec.span("step_dispatch"):
        clock.advance(0.25)
        with rec.span("data_wait") as inner:  # nested spans annotate too
            clock.advance(0.5)
            inner.set(rows=8, batch=3)  # counters go straight to the annotation
    assert [e["name"] for e in notes.events] == [f"{scope}/step_dispatch", f"{scope}/data_wait"]
    outer, nested = notes.events
    assert (outer["start"], outer["end"]) == (0.0, 0.75) and outer["stats"] == {}
    assert (nested["start"], nested["end"]) == (0.25, 0.75)
    assert nested["stats"] == {"batch": 3, "rows": 8}
    # the handle gives the caller the span's own clock reads
    assert (inner.t0, inner.dur, inner.end) == (0.25, 0.5, 0.75)


def test_ring_keeps_bare_names_and_the_outermost_partition():
    """The annotation changes nothing the budget reads: the step's record
    holds the OUTERMOST spans under their bare names and nothing else
    (counters live on the annotation alone)."""
    clock = FakeClock()
    notes = FakeAnnotations(clock)
    rec = SpanRecorder(clock=clock, scope="serve", annotate=notes)
    with rec.span("round"):
        with rec.span("admit_prep") as prep:
            clock.advance(0.01)
            prep.set(n=1, queue_wait_us_sum=7)
        with rec.span("emit"):
            clock.advance(0.02)
    rec.step_complete()
    with rec.span("round"):
        clock.advance(0.03)
    rec.step_complete()
    first, second = rec.window_step_records()
    assert first == second == {"dur": pytest.approx(0.03), "spans": {"round": pytest.approx(0.03)}}
    assert notes.named("serve/admit_prep")[0]["stats"] == {"n": 1, "queue_wait_us_sum": 7}
    s = rec.summary()
    assert set(s["spans"]) == {"round", "admit_prep", "emit"}  # bare names in the window aggregates too


def test_span_closes_its_annotation_and_depth_when_the_body_raises():
    clock = FakeClock()
    notes = FakeAnnotations(clock)
    rec = SpanRecorder(clock=clock, annotate=notes)
    with pytest.raises(ValueError):
        with rec.span("step_dispatch"):
            clock.advance(0.1)
            raise ValueError("boom")
    assert notes.events[0]["end"] == pytest.approx(0.1)
    with rec.span("data_wait"):
        clock.advance(0.2)
    rec.step_complete()
    # both are outermost: the failed span did not leave the depth raised
    assert rec.window_step_records()[0]["spans"] == {
        "step_dispatch": pytest.approx(0.1), "data_wait": pytest.approx(0.2)}


@pytest.mark.parametrize("annotate", ["fake", "profiler"])
def test_step_budget_numbers_do_not_depend_on_the_annotation(annotate):
    """The trainer's account on a hand-driven window reads the same numbers
    with the fake factory and with jax's own (inactive) TraceAnnotation."""
    from distributed_llms_example_tpu.obs.budget import BudgetAccountant

    clock = FakeClock()
    rec = SpanRecorder(clock=clock, scope="train",
                       annotate=FakeAnnotations(clock) if annotate == "fake" else None)
    bud = BudgetAccountant(rec)
    for last in (False, False, True):
        with rec.span("data_wait"):
            clock.advance(0.02)
        with rec.span("host_overhead"):
            clock.advance(0.01)
            with rec.span("obs_gauge_compile"):  # nested: annotated, not in the partition
                clock.advance(0.002)
        with rec.span("step_dispatch"):
            clock.advance(0.005)
        if last:
            with rec.span("device_busy"):
                clock.advance(0.05)
            with rec.span("device_sync"):
                clock.advance(0.01)
        clock.advance(0.004)
        rec.step_complete()
    acct = bud.close_window(step=3, epoch=0, emit=False)
    assert acct["window_steps"] == 3
    assert acct["data_wait_ms"] == pytest.approx(60.0)
    assert acct["host_overhead_ms"] == pytest.approx(36.0)
    assert acct["dispatch_ms"] == pytest.approx(15.0)
    assert acct["device_busy_ms"] == pytest.approx(50.0)
    assert acct["sync_block_ms"] == pytest.approx(10.0)
    assert acct["unattributed_ms"] == pytest.approx(12.0)
    assert acct["wall_ms"] == pytest.approx(183.0)


def test_ring_is_bounded():
    clock = FakeClock()
    rec = SpanRecorder(ring_size=8, clock=clock, scope="serve", annotate=FakeAnnotations(clock))
    for i in range(50):
        with rec.span("round") as rd:
            clock.advance(0.01)
            rd.set(n=i)  # a counter leaves nothing behind in the recorder
        rec.step_complete()
    assert len(rec._ring) == 8 and len(rec._step_records) == 8
    assert rec._step_records[-1] == {"dur": pytest.approx(0.01), "spans": {"round": pytest.approx(0.01)}}
    assert rec.summary()["window_steps"] == 50


def test_trainer_obs_spans_are_train_scoped(tmp_path):
    """TrainerObs adds no ring span: its existing spans become visible as
    ``train/<name>`` and the ring keeps the names the budget reads."""
    from distributed_llms_example_tpu.core.config import TrainConfig
    from distributed_llms_example_tpu.obs import TrainerObs

    obs = TrainerObs(TrainConfig(output_dir=str(tmp_path), obs="off", health="off"), start_step=0)
    assert obs.spans.scope == "train"
    notes = FakeAnnotations(obs.spans.clock)
    obs.spans._annotate = notes
    list(obs.wrap_batches([{"x": 1}]))
    with obs.step_span():
        pass
    with obs.host_span():
        pass
    with obs.sync_span():
        pass
    assert [e["name"] for e in notes.events] == [
        "train/data_wait", "train/data_wait", "train/step_dispatch", "train/host_overhead", "train/device_sync"]
    obs.spans.step_complete()
    assert set(obs.spans.window_step_records()[0]["spans"]) == {
        "data_wait", "step_dispatch", "host_overhead", "device_sync"}


# ---------------------------------------------------------------------------
# gauges: MFU math, HBM gating, collective accounting on a known FSDP HLO
# ---------------------------------------------------------------------------

def test_mfu_math_hand_computed():
    # tiny model by hand: N=1000 params, 64 tokens/step → 6·N·T FLOPs
    assert training_flops_estimate(1000, 64) == 6.0 * 1000 * 64
    # 384k FLOPs over 0.5 s on 4 chips of 1 MFLOP/s peak:
    # 384e3 / (0.5 · 4 · 1e6) = 0.192
    assert mfu(384_000.0, 0.5, 4, 1e6) == pytest.approx(0.192)
    assert mfu(1.0, 0.0, 4, 1e6) == 0.0  # degenerate window


def test_hbm_stats_gated_on_cpu():
    from distributed_llms_example_tpu.obs.gauges import hbm_stats

    # CPU PJRT reports no memory_stats: the gauge must say nothing, not 0
    assert hbm_stats() is None


# A hand-written FSDP-style step: params sharded 8-way.  The gradient
# reduce-scatter (full 2048×512 f32 tree leaf in, 1/8 shard out) and the
# forward param all-gather match the model tree; the small all-reduce is
# the loss scalar (activation traffic); the big all-reduce is the SAME
# gradient leaf all-reduced — the 2× traffic anti-pattern the account
# exists to expose next to its reduce-scattered twin.
_FSDP_HLO = """\
HloModule fsdp_step

ENTRY %main {
  %pshard = bf16[256,512]{1,0} parameter(0)
  %gfull = f32[2048,512]{1,0} parameter(1)
  %act = f32[8,128]{1,0} parameter(2)
  %ag.params = bf16[2048,512]{1,0} all-gather(bf16[256,512]{1,0} %pshard), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %rs.grads = f32[256,512]{1,0} reduce-scatter(f32[2048,512]{1,0} %gfull), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}, to_apply=%add
  %ar.grads = f32[2048,512]{1,0} all-reduce(f32[2048,512]{1,0} %gfull), replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add
  %ar.loss = f32[] all-reduce(f32[] %act), replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add
  ROOT %t = (f32[256,512]{1,0}) tuple(%rs.grads)
}
"""


def test_collective_traffic_fsdp_split():
    acct = collective_traffic(_FSDP_HLO, [2048 * 512], mesh_size=8)
    # reduce-scatter: gradient traffic, sized by its per-device RESULT
    assert acct["reduce-scatter"]["gradient_bytes"] == 256 * 512 * 4
    assert acct["reduce-scatter"]["activation_bytes"] == 0
    # the all-reduce twin of the same gradient leaf is gradient traffic
    # (2048·512 f32) — vs the loss-scalar all-reduce on the activation side
    assert acct["all-reduce"]["gradient_bytes"] == 2048 * 512 * 4
    assert acct["all-reduce"]["activation_bytes"] == 4
    # the forward param gather moves the model tree too
    assert acct["all-gather"]["gradient_bytes"] == 2048 * 512 * 2
    assert acct["gradient_bytes"] == (
        256 * 512 * 4 + 2048 * 512 * 4 + 2048 * 512 * 2
    )
    assert acct["activation_bytes"] == 4
    assert acct["total_bytes"] == acct["gradient_bytes"] + acct["activation_bytes"]
    # and the reduce-scatter vs all-reduce split is visible: the same
    # gradient bytes cost 8× less scattered than replicated
    assert acct["all-reduce"]["gradient_bytes"] == 8 * acct["reduce-scatter"]["gradient_bytes"]


# ---------------------------------------------------------------------------
# acceptance: obs account == IR lint accounting on the SAME compiled step
# ---------------------------------------------------------------------------

_STEP_ARGS = dict(
    global_batch=8, src_len=32, tgt_len=16, dtype="bfloat16",
    remat=False, remat_policy="full", grad_accum_steps=1,
)


@pytest.fixture(scope="module")
def compiled_t5_fsdp():
    """One AOT compile (the shared recipe) serving every test below —
    and byte-identical to what the Trainer's gauge pass compiles for the
    same config, since both call the same recipe with the same args."""
    from distributed_llms_example_tpu.utils.memory_audit import (
        aot_compile_train_step,
    )

    mesh = build_mesh(MeshConfig(data=2, fsdp=2, sequence=1, tensor=2))
    compiled, lm, a_params, _, _ = aot_compile_train_step(
        "t5-test", mesh, **_STEP_ARGS
    )
    elems = [int(np.prod(x.shape)) for x in jax.tree.leaves(a_params)]
    return compiled.as_text(), elems, mesh


def _merge_async(by_op: dict) -> dict:
    out: dict[str, int] = {}
    for op, b in by_op.items():
        base = op[: -len("-start")] if op.endswith("-start") else op
        out[base] = out.get(base, 0) + b
    return out


def test_comm_account_matches_ir_lint_census(compiled_t5_fsdp):
    from distributed_llms_example_tpu.analysis.ir_lint import scan_hlo_text

    text, elems, mesh = compiled_t5_fsdp
    acct = collective_traffic(text, elems, mesh.size)
    findings = scan_hlo_text(
        text, mesh_axes=dict(mesh.shape), param_element_counts=elems
    )
    census = next(f for f in findings if f.code == "collective-census")
    total_by_op = _merge_async(census.context["bytes_by_op"])
    grad_by_op = _merge_async(census.context["gradient_bytes_by_op"])
    assert total_by_op, "compiled fsdp step must contain collectives"
    for op, totals in total_by_op.items():
        slot = acct[op]
        assert slot["gradient_bytes"] + slot["activation_bytes"] == totals
        # the acceptance pin: gradient all-gather / reduce-scatter byte
        # totals agree between the runtime account and the IR lint
        assert slot["gradient_bytes"] == grad_by_op.get(op, 0)
    assert acct["gradient_bytes"] > 0  # an fsdp step moves the model tree


def test_obs_jsonl_stream_without_trainer(tmp_path):
    """Fast-tier wiring check: a TrainerObs driven by hand produces the
    same JSONL stream shape the Trainer does — window summaries with
    spans + MFU, heartbeat, schema stamps — without paying a train-step
    compile (the full end-to-end run is the slow-tier test below)."""
    from distributed_llms_example_tpu.obs import TrainerObs

    cfg = TrainConfig(
        output_dir=str(tmp_path), log_every_steps=2, obs="jsonl",
        obs_heartbeat_steps=2,
    )
    obs = TrainerObs(cfg, start_step=0)
    obs.flops_per_step = 1e9  # as the gauge compile would have set
    # the CPU has no published peak, so by default there is no MFU here
    assert obs.peak_flops_per_chip is None and obs.window_mfu(
        {"window_seconds": 1.0, "window_steps": 1}
    ) is None
    obs.peak_flops_per_chip = 197e12  # as the device_kind lookup gives on a v5e
    for step in (1, 2):
        with obs.step_span():
            pass
        obs.on_step(step, epoch=0, metrics={})
    log_json({"step": 2, "loss": 0.5})
    sink_mod.current_sink().close()
    path = os.path.join(str(tmp_path), "obs", "metrics-p000.jsonl")
    records = [json.loads(line) for line in open(path)]
    assert all(r["schema_version"] == 1 for r in records)
    window = next(r for r in records if r.get("event") == "obs_window")
    assert {"step_ms_p50", "step_ms_p95", "step_ms_max", "straggler"} <= set(window)
    assert "step_dispatch" in window["spans"] and window["mfu"] > 0
    assert any(r.get("event") == "heartbeat" for r in records)
    assert any(r.get("step") == 2 and "loss" in r for r in records)


@pytest.mark.slow  # one full Trainer construction + two compiles (~35s):
# the fast tier keeps the same acceptance equality via the module fixture
# (test_comm_account_matches_ir_lint_census) and the stream-shape check
# above; this leg proves the real --obs jsonl loop end to end
def test_trainer_obs_jsonl_stream(tmp_path, compiled_t5_fsdp):
    """The end-to-end acceptance run: --obs jsonl on the CPU demo config
    produces a JSONL stream with per-step span windows, an MFU gauge, and
    a collective-traffic account equal to the IR lint's accounting of the
    same compiled step (the module fixture: same recipe, same args)."""
    from distributed_llms_example_tpu.analysis.ir_lint import scan_hlo_text
    from distributed_llms_example_tpu.train.trainer import Trainer

    text, elems, mesh = compiled_t5_fsdp
    rng = np.random.RandomState(0)
    recs = [
        {
            "dialogue": " ".join(f"w{rng.randint(40)}" for _ in range(12)),
            "summary": f"w{rng.randint(40)}",
        }
        for _ in range(16)
    ]
    cfg = TrainConfig(
        model_ckpt="t5-test",
        output_dir=str(tmp_path),
        batch_size=8,
        num_epochs=1,
        warmup_steps=1,
        evaluation_steps=0,
        max_source_length=32,
        max_target_length=16,
        pad_to_multiple=32,
        log_every_steps=2,
        num_beams=1,
        tokenizer="byte",
        mesh=MeshConfig(data=2, fsdp=2, sequence=1, tensor=2),
        checkpoint=CheckpointConfig(save_every_steps=0, resume=False, async_save=False),
        obs="jsonl",
        obs_heartbeat_steps=2,
    )
    trainer = Trainer(cfg, train_records=recs)
    trainer.save_final = lambda: None  # the stream, not the artifact
    result = trainer.train()
    assert result["steps"] == 2

    path = os.path.join(str(tmp_path), "obs", "metrics-p000.jsonl")
    records = [json.loads(line) for line in open(path)]
    assert all(r["schema_version"] == 1 for r in records)
    by_event: dict[str, list[dict]] = {}
    for r in records:
        by_event.setdefault(r.get("event", "metric"), []).append(r)

    # per-step spans + percentiles + MFU ride the window summaries
    window = by_event["obs_window"][0]
    assert {"step_ms_p50", "step_ms_p95", "step_ms_max", "straggler"} <= set(window)
    assert {"data_wait", "step_dispatch", "device_sync"} <= set(window["spans"])
    # no MFU on a device without a published peak — omitted, and named once
    assert "mfu" not in window
    assert "'cpu'" in by_event["obs_gauges"][0]["mfu_skipped"]
    # the step-time budget account (ISSUE 9 acceptance, on the REAL
    # trainer loop): components sum to the measured wall within 5% —
    # i.e. the unattributed remainder stays under tolerance — and the
    # budget layer's own probe charged device_busy at the cadence
    budgets = by_event["step_budget"]
    assert budgets, "budget layer must close every logging window"
    from distributed_llms_example_tpu.obs.budget import COMPONENTS

    for acct in budgets:
        total = sum(acct[f"{c}_ms"] for c in COMPONENTS)
        assert total == pytest.approx(acct["wall_ms"], rel=0.01)
        assert acct["additivity_ok"], acct
        assert acct["accounted_frac"] >= 0.95
        assert 0.0 <= acct["dispatch_efficiency"] <= 1.0
        # a healthy async loop must not trip the host-blocking tripwire
        assert acct["offcadence_sync_suspect"] is False
    assert any(a["device_busy_ms"] > 0 for a in budgets)
    # the step-cadence metric lines tee into the same stream
    assert any("loss" in r and "step" in r for r in by_event["metric"])
    # heartbeat (single process: trivially zero skew, but alive)
    hb = by_event["heartbeat"][0]
    assert hb["process_count"] == 1 and hb["skew_steps"] == 0

    # the acceptance equality: the emitted account vs the IR lint's
    # independent scan of the same compiled step
    gauges = by_event["obs_gauges"][0]
    assert gauges["flops_per_step"] > 0
    emitted = gauges["comm"]
    census = next(
        f
        for f in scan_hlo_text(
            text, mesh_axes=dict(mesh.shape), param_element_counts=elems
        )
        if f.code == "collective-census"
    )
    grad_by_op = _merge_async(census.context["gradient_bytes_by_op"])
    total_by_op = _merge_async(census.context["bytes_by_op"])
    for op in ("all-gather", "reduce-scatter"):
        slot = emitted.get(op)
        if slot is None:
            assert grad_by_op.get(op, 0) == 0
            continue
        assert slot["gradient_bytes"] == grad_by_op.get(op, 0)
        assert slot["gradient_bytes"] + slot["activation_bytes"] == total_by_op[op]
    assert emitted["gradient_bytes"] > 0


# ---------------------------------------------------------------------------
# satellite (ISSUE 3): the ROADMAP reduce-scatter smell as a pure predicate
# over the gradient-byte account, pinned on a real compiled FSDP step
# ---------------------------------------------------------------------------

def test_reduce_scatter_smell_pure_predicate():
    from distributed_llms_example_tpu.analysis.ir_lint import (
        account_gradient_bytes_by_op,
        reduce_scatter_smell,
    )

    fsdp = {"fsdp": 8, "data": 1}
    # healthy: gradients ride reduce-scatter → no finding
    assert reduce_scatter_smell(
        {"reduce-scatter": 64 << 20, "all-reduce": 4}, fsdp
    ) is None
    # the 2× smell: the same bytes all-REDUCED instead
    f = reduce_scatter_smell({"all-reduce": 64 << 20, "reduce-scatter": 0}, fsdp)
    assert f is not None and f.code == "gradient-all-reduce-not-reduce-scatter"
    assert f.context["all_reduce_gradient_bytes"] == 64 << 20
    # async -start forms fold into their base op
    assert reduce_scatter_smell({"all-reduce-start": 64 << 20}, fsdp) is not None
    # not an fsdp mesh → gradients are SUPPOSED to all-reduce (pure DP)
    assert reduce_scatter_smell({"all-reduce": 64 << 20}, {"data": 8}) is None
    # below the noise floor → quiet
    assert reduce_scatter_smell({"all-reduce": 1024}, fsdp) is None
    # the obs runtime account (per-op dicts) feeds the SAME predicate
    acct = {
        "all-reduce": {"count": 2, "gradient_bytes": 64 << 20, "activation_bytes": 4},
        "reduce-scatter": {"count": 0, "gradient_bytes": 0, "activation_bytes": 0},
        "total_bytes": (64 << 20) + 4,
        "gradient_bytes": 64 << 20,
        "activation_bytes": 4,
    }
    by_op = account_gradient_bytes_by_op(acct)
    assert by_op == {"all-reduce": 64 << 20, "reduce-scatter": 0}
    assert reduce_scatter_smell(by_op, fsdp) is not None


def test_reduce_scatter_smell_pinned_on_compiled_fsdp_step(compiled_t5_fsdp):
    """The predicate over the REAL compiled FSDP step.  Pinned behavior on
    this backend: the CPU SPMD partitioner lowers the fsdp gradient
    reduction as all-reduce (+ dynamic-slice), NOT reduce-scatter — i.e.
    the compiled step genuinely exhibits the 2× gradient-traffic pattern
    the smell hunts, so with the noise floor dropped the predicate MUST
    fire, and it must fire identically over the IR census and the obs
    runtime account (same parser, same classification)."""
    from distributed_llms_example_tpu.analysis.ir_lint import (
        account_gradient_bytes_by_op,
        reduce_scatter_smell,
        scan_hlo_text,
    )

    text, elems, mesh = compiled_t5_fsdp
    census = next(
        f
        for f in scan_hlo_text(
            text, mesh_axes=dict(mesh.shape), param_element_counts=elems
        )
        if f.code == "collective-census"
    )
    grad_by_op = census.context["gradient_bytes_by_op"]
    assert grad_by_op.get("all-reduce", 0) > 0  # the pattern is really there
    f = reduce_scatter_smell(grad_by_op, dict(mesh.shape), min_bytes=0)
    assert f is not None and f.code == "gradient-all-reduce-not-reduce-scatter"
    # the same verdict from the runtime account of the same program
    acct = collective_traffic(text, elems, mesh.size)
    f2 = reduce_scatter_smell(
        account_gradient_bytes_by_op(acct), dict(mesh.shape), min_bytes=0
    )
    assert f2 is not None
    assert f2.context == f.context


# ---------------------------------------------------------------------------
# satellite: MetricLogger cadence fix + flush
# ---------------------------------------------------------------------------

def test_metric_logger_no_step0_fire_and_flush(capsys):
    logger = MetricLogger(every=3)
    logger.step(0, 1.0, tokens=10)  # the old bug: fired here, empty window
    assert capsys.readouterr().out == ""
    for s in (1, 2, 3):
        logger.step(s, 0.5, lr=0.1, tokens=10)
    lines = _json_lines(capsys.readouterr().out)
    assert len(lines) == 1 and lines[0]["step"] == 3
    assert lines[0]["steps_per_sec"] > 0
    # partial final window: two more steps, then flush
    logger.step(4, 0.4, lr=0.1, tokens=10)
    logger.step(5, 0.3, lr=0.1, tokens=10)
    assert _json_lines(capsys.readouterr().out) == []
    logger.flush(5, epoch=0)
    lines = _json_lines(capsys.readouterr().out)
    assert len(lines) == 1
    assert lines[0]["step"] == 5 and lines[0]["loss"] == 0.3 and lines[0]["epoch"] == 0
    # flush is idempotent: the window is already drained
    logger.flush(5)
    assert _json_lines(capsys.readouterr().out) == []


# ---------------------------------------------------------------------------
# satellite: log_json sink routing, schema_version, stdout byte parity
# ---------------------------------------------------------------------------

def _legacy_line(metrics: dict) -> str:
    """The pre-obs log_json serialization, verbatim (the Valohai metadata
    contract this PR must not move a byte)."""
    def conv(v):
        if hasattr(v, "item") and getattr(v, "ndim", 1) == 0:
            v = v.item()
        if isinstance(v, float):
            return round(v, 6)
        return v

    return json.dumps({k: conv(v) for k, v in metrics.items()})


def test_log_json_stdout_byte_parity(capsys):
    import jax.numpy as jnp

    metrics = {
        "step": 7,
        "loss": jnp.float32(0.123456789),  # 0-d device array, like the trainer
        "learning_rate": 5e-5,
        "tokens_per_sec": 12345.678901234,
        "event": "parity",
    }
    log_json(metrics)
    out = capsys.readouterr().out
    assert out == _legacy_line(metrics) + "\n"


def test_jsonl_file_sink_schema_version(tmp_path, capsys):
    path = str(tmp_path / "obs" / "m.jsonl")
    sink_mod.install_sink(
        sink_mod.TeeSink([sink_mod.StdoutSink(), sink_mod.JsonlFileSink(path)])
    )
    log_json({"event": "x", "v": 1})
    # stdout unchanged (no schema_version: the platform contract)...
    assert _json_lines(capsys.readouterr().out) == [{"event": "x", "v": 1}]
    # ...the file record is stamped
    sink_mod.current_sink().close()
    rec = json.loads(open(path).read())
    assert rec == {"schema_version": 1, "event": "x", "v": 1}


def test_build_sink_modes(tmp_path):
    assert isinstance(sink_mod.build_sink("stdout", str(tmp_path)), sink_mod.StdoutSink)
    assert isinstance(sink_mod.build_sink("off", str(tmp_path)), sink_mod.StdoutSink)
    tee = sink_mod.build_sink("jsonl", str(tmp_path))
    assert isinstance(tee, sink_mod.TeeSink)
    tee.close()


# ---------------------------------------------------------------------------
# heartbeat: skew math (pure) + single-process beat; the 2-process leg is
# slow-tier (the same multiprocess CPU rendezvous as test_multiprocess.py)
# ---------------------------------------------------------------------------

def test_detect_laggards_pure():
    out = detect_laggards(
        np.array([10, 10, 8]),
        np.array([100.0, 100.2, 103.0]),
        laggard_threshold_s=1.0,
    )
    assert out["skew_steps"] == 2
    assert out["min_step"] == 8 and out["max_step"] == 10
    assert out["arrival_spread_s"] == pytest.approx(3.0)
    assert out["laggards"] == [2]
    clean = detect_laggards(np.array([5]), np.array([10.0]))
    assert clean["skew_steps"] == 0 and clean["laggards"] == []


def test_heartbeat_single_process_beat(capsys):
    rec = Heartbeat(every_steps=4).beat(12)
    assert rec["process_count"] == 1 and rec["skew_steps"] == 0
    lines = _json_lines(capsys.readouterr().out)
    assert any(r.get("event") == "heartbeat" and r["step"] == 12 for r in lines)


@pytest.mark.slow
def test_heartbeat_two_process_skew(tmp_path):
    """Two real OS processes rendezvous (the test_multiprocess.py CPU
    mesh) and probe with different step counters and a delayed rank 1:
    process 0 must report the skew and the laggard."""
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = """
import json, os, sys, time
import jax
from distributed_llms_example_tpu.core.mesh import initialize_distributed
initialize_distributed(
    os.environ["HB_COORD"], 2, int(os.environ["HB_RANK"])
)
from distributed_llms_example_tpu.obs.heartbeat import Heartbeat
rank = jax.process_index()
if rank == 1:
    time.sleep(1.5)  # the straggler
rec = Heartbeat(every_steps=1, laggard_threshold_s=1.0).beat(7 + 2 * rank)
if rank == 0:
    print("HBREC " + json.dumps(rec))
"""
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", ""),
            "HB_COORD": f"127.0.0.1:{port}",
            "HB_RANK": str(rank),
        })
        for k in ("VH_MASTER_IP", "VH_WORLD_SIZE", "VH_RANK"):
            env.pop(k, None)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", code], env=env, cwd=repo,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        ))
    outs = [p.communicate(timeout=300) for p in procs]
    assert all(p.returncode == 0 for p in procs), outs[0][1][-2000:] + outs[1][1][-2000:]
    line = next(ln for ln in outs[0][0].splitlines() if ln.startswith("HBREC "))
    rec = json.loads(line[len("HBREC "):])
    assert rec["process_count"] == 2
    assert rec["skew_steps"] == 2  # ranks probed at steps 7 and 9
    assert rec["arrival_spread_s"] >= 1.0  # rank 1 slept 1.5 s
    assert rec["laggards"] == [1]


# ---------------------------------------------------------------------------
# profiler: window spec parsing + trigger-file capture
# ---------------------------------------------------------------------------

def test_parse_profile_steps_forms():
    assert parse_profile_steps(3) == 3
    assert parse_profile_steps("3") == 3
    assert parse_profile_steps("100:105") == (100, 105)
    assert parse_profile_steps(0) is None
    assert parse_profile_steps("") is None
    assert parse_profile_steps(None) is None
    with pytest.raises(ValueError):
        parse_profile_steps("105:100")


def test_profile_window_anchoring(tmp_path):
    # absolute window: starts exactly at the named step, any start_step
    ctl = ProfileController(
        steps_spec="100:105", output_dir=str(tmp_path), start_step=90
    )
    assert ctl.window == (100, 105)
    assert ctl.profile_dir == os.path.join(str(tmp_path), "obs", "profile")
    # legacy count: relative to the run's start, skipping the compile step
    ctl = ProfileController(
        steps_spec=3, profile_dir=str(tmp_path / "d"), start_step=10,
        output_dir=str(tmp_path),
    )
    assert ctl.window == (12, 14)


@pytest.mark.slow  # ~13s: jax's profiler session init dominates; the
# cheap window/spec logic above keeps fast-tier coverage of the controller
def test_profile_trigger_capture(tmp_path, capsys):
    trigger = str(tmp_path / "profile.trigger")
    ctl = ProfileController(
        steps_spec=0,
        trigger_path=trigger,
        output_dir=str(tmp_path),
        start_step=0,
    )
    ctl.before_step(5)
    assert not ctl.active  # no trigger yet
    with open(trigger, "w") as f:
        f.write("2")
    ctl.before_step(5)
    assert ctl.active
    assert not os.path.exists(trigger)  # consumed
    ctl.after_step(5)
    assert ctl.active  # window is 2 steps
    ctl.after_step(6)
    assert not ctl.active
    # the capture dir is self-describing: proc index + step window +
    # wall clock, so report/devprof locate THIS capture without globbing
    base = os.path.join(str(tmp_path), "obs", "profile")
    dirs = [d for d in os.listdir(base) if d.startswith("proc000-s000005-000006-")]
    assert dirs, f"no step-stamped capture dir under {base}: {os.listdir(base)}"
    trace_dir = os.path.join(base, dirs[0])
    files = [os.path.join(dp, f) for dp, _, fs in os.walk(trace_dir) for f in fs]
    assert files, f"no trace files under {trace_dir}"
    lines = _json_lines(capsys.readouterr().out)
    assert any(r.get("event") == "profile_trace" for r in lines)
    captured = next(r for r in lines if r.get("event") == "profile_captured")
    assert captured["path"] == trace_dir
    assert captured["window"] == [5, 6] and captured["steps"] == 2


# ---------------------------------------------------------------------------
# CI/tooling: the repo AST lint's json-emission rule
# ---------------------------------------------------------------------------

def test_repo_lint_forbids_rogue_json_print(tmp_path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "repo_lint",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "repo_lint.py"),
    )
    repo_lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(repo_lint)

    rogue = tmp_path / "rogue.py"
    rogue.write_text(
        "import json\n"
        "print(json.dumps({'step': 1, 'loss': 0.5}))\n"
        "print('plain text is fine')\n"
    )
    rel = os.path.join("distributed_llms_example_tpu", "train", "rogue.py")
    violations = repo_lint.lint_file(str(rogue), rel)
    assert len(violations) == 1 and "sink" in violations[0]
    # the sink layer itself is allowed
    rel = os.path.join("distributed_llms_example_tpu", "obs", "sink.py")
    assert repo_lint.lint_file(str(rogue), rel) == []
    rel = os.path.join("distributed_llms_example_tpu", "utils", "jsonlog.py")
    assert repo_lint.lint_file(str(rogue), rel) == []
    # and the repo itself stays clean under the new rule
    assert repo_lint.main([]) == 0


@pytest.mark.slow  # two AOT gauge compiles: slow tier
def test_mfu_flops_invariant_under_grad_accum():
    """The MFU numerator is ×N-corrected under grad accumulation: XLA's
    cost analysis counts the scan's while body exactly ONCE (measured on
    jax 0.4.37 — without the correction MFU would underreport by ~N), so
    gauges.py scales by grad_accum_steps.  At the same effective batch
    the corrected flops match accum=1 from below (equal model flops) and
    exceed it only by N-1 extra optimizer tails + loop bookkeeping —
    ~10% at this toy width, ~0 at real widths.  grad_accum_steps is
    stamped into the gauge report."""
    from distributed_llms_example_tpu.obs.gauges import train_step_static_gauges

    mesh = build_mesh(MeshConfig(data=2, fsdp=2, sequence=1, tensor=2))
    reports = {}
    for n in (1, 4):
        reports[n] = train_step_static_gauges(
            "t5-test", mesh, global_batch=16, src_len=32, tgt_len=16,
            dtype="bfloat16", grad_accum_steps=n,
        )
    assert reports[1]["grad_accum_steps"] == 1
    assert reports[4]["grad_accum_steps"] == 4
    assert reports[1]["flops_source"] == reports[4]["flops_source"] == "hlo_cost_analysis"
    f1, f4 = reports[1]["flops_per_step"], reports[4]["flops_per_step"]
    assert f1 > 0
    # same effective batch → same model flops, so the ×N-corrected accum
    # count brackets accum=1: at least f1 (nothing lost — an uncorrected
    # body-counted-once number would sit at ~f1/4), at most f1 + the
    # (N-1) duplicated optimizer tails (~10% at this toy width)
    assert f1 * 0.98 <= f4 <= f1 * 1.2
