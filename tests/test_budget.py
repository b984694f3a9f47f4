"""Step-time budget accounting (ISSUE 9).

Pins: the additive budget account on a fake-clock span recorder
(components sum to wall, the unattributed remainder is the measured
residue); the off-cadence host-blocking-dispatch tripwire; the
zero-new-syncs-off-cadence property of the budget probe (counting-leaf,
same technique as PR 3's health pin); schema round-trip through
obs/report.py's loader for every new event type (``step_budget``,
``serve_request``); and the report's "Where did the time go" section +
the --strict dispatch-efficiency floor.
"""

from __future__ import annotations

import json
import os

import pytest

from distributed_llms_example_tpu.core.config import TrainConfig
from distributed_llms_example_tpu.obs import TrainerObs, sink as sink_mod
from distributed_llms_example_tpu.obs.budget import (
    COMPONENTS,
    BudgetAccountant,
    aggregate_accounts,
    budget_enabled,
)
from distributed_llms_example_tpu.obs.report import (
    build_report,
    load_jsonl,
    render_markdown,
)
from distributed_llms_example_tpu.obs.spans import SpanRecorder


@pytest.fixture(autouse=True)
def _default_sink():
    sink_mod.install_sink(sink_mod.build_sink("stdout", ""))
    yield
    sink_mod.install_sink(sink_mod.build_sink("stdout", ""))


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


# ---------------------------------------------------------------------------
# the additive account on a fake clock
# ---------------------------------------------------------------------------


def _drive_step(rec, clock, *, data_wait=0.0, host=0.0, dispatch=0.0,
                busy=0.0, sync=0.0, untracked=0.0):
    if data_wait:
        with rec.span("data_wait"):
            clock.advance(data_wait)
    if host:
        with rec.span("host_overhead"):
            clock.advance(host)
    if dispatch:
        with rec.span("step_dispatch"):
            clock.advance(dispatch)
    if busy:
        with rec.span("device_busy"):
            clock.advance(busy)
    if sync:
        with rec.span("device_sync"):
            clock.advance(sync)
    clock.advance(untracked)
    rec.step_complete()


def test_budget_additivity_on_fake_clock():
    """Hand-driven window: every component lands in its slot, the named
    components plus the unattributed remainder sum EXACTLY to the
    measured wall, and dispatch_efficiency is the documented formula."""
    clock = FakeClock()
    rec = SpanRecorder(clock=clock)
    bud = BudgetAccountant(rec)
    # three steps: 0.02 data_wait + 0.01 host + 0.005 dispatch + 0.06
    # untracked-free device overlap... last step carries the probe + sync
    for _ in range(2):
        _drive_step(rec, clock, data_wait=0.02, host=0.01, dispatch=0.005,
                    untracked=0.065)
    _drive_step(rec, clock, data_wait=0.02, host=0.01, dispatch=0.005,
                busy=0.05, sync=0.01, untracked=0.005)
    acct = bud.close_window(step=3, epoch=0, emit=False)
    assert acct["event"] == "step_budget" and acct["window_steps"] == 3
    assert acct["data_wait_ms"] == pytest.approx(60.0)
    assert acct["host_overhead_ms"] == pytest.approx(30.0)
    assert acct["dispatch_ms"] == pytest.approx(15.0)
    assert acct["device_busy_ms"] == pytest.approx(50.0)
    assert acct["sync_block_ms"] == pytest.approx(10.0)
    assert acct["unattributed_ms"] == pytest.approx(135.0)
    # additivity: named components + remainder == wall, exactly
    total = sum(acct[f"{c}_ms"] for c in COMPONENTS)
    assert total == pytest.approx(acct["wall_ms"])
    assert acct["wall_ms"] == pytest.approx(300.0)
    assert acct["accounted_frac"] == pytest.approx(165.0 / 300.0, abs=1e-3)
    assert acct["additivity_ok"] is False  # 45% unattributed > 5%
    # efficiency = 1 - (data_wait + host + unattributed)/wall
    assert acct["dispatch_efficiency"] == pytest.approx(
        1 - (60 + 30 + 135) / 300.0, abs=1e-3
    )
    # the window is consumed with summary(), like the cadence does
    rec.summary()
    assert bud.close_window(step=3, emit=False) is None


def test_budget_nested_spans_do_not_double_count():
    """Only OUTERMOST spans enter the per-step partition — a nested span
    would charge the same wall twice and break additivity."""
    clock = FakeClock()
    rec = SpanRecorder(clock=clock)
    bud = BudgetAccountant(rec)
    with rec.span("step_dispatch"):
        with rec.span("data_wait"):  # nested: window aggregate only
            clock.advance(0.1)
        clock.advance(0.1)
    rec.step_complete()
    acct = bud.close_window(step=1, emit=False)
    assert acct["dispatch_ms"] == pytest.approx(200.0)
    assert acct["data_wait_ms"] == 0.0
    assert acct["unattributed_ms"] == pytest.approx(0.0, abs=1e-6)
    # ...while the span SUMMARY still reports the nesting (existing contract)
    assert rec.summary()["spans"]["data_wait"]["total_ms"] == pytest.approx(100.0)


def test_budget_mark_step_start_excludes_between_step_work():
    """Checkpoint/eval time between steps is excluded from the next
    step's duration (mark_step_start) — the budget partition must drop
    those spans too, or components would exceed wall."""
    clock = FakeClock()
    rec = SpanRecorder(clock=clock)
    bud = BudgetAccountant(rec)
    _drive_step(rec, clock, dispatch=0.1)
    with rec.span("checkpoint"):
        clock.advance(5.0)
    rec.mark_step_start()
    _drive_step(rec, clock, dispatch=0.1)
    acct = bud.close_window(step=2, emit=False)
    assert acct["wall_ms"] == pytest.approx(200.0)
    assert acct["host_overhead_ms"] == 0.0  # the 5 s checkpoint dropped
    assert acct["dispatch_ms"] == pytest.approx(200.0)


def test_budget_offcadence_tripwire():
    """A NON-cadence step whose dispatch eats a device-step's worth of
    wall is a host-blocked transfer (the runtime twin of repo-lint rule
    4): counted and flagged.  A healthy async window — millisecond
    dispatches, the cadence step carrying the block — stays quiet."""
    clock = FakeClock()
    rec = SpanRecorder(clock=clock)
    # warmup_windows=0: evaluate the detector on every window (the
    # default 1 stands down for the compile window — tested below)
    bud = BudgetAccountant(rec, warmup_windows=0)
    # healthy: 3 fast dispatches, the cadence (last) step drains 0.27s
    for _ in range(3):
        _drive_step(rec, clock, dispatch=0.002, untracked=0.002)
    _drive_step(rec, clock, dispatch=0.002, busy=0.27, sync=0.01)
    acct = bud.close_window(step=4, emit=False)
    assert acct["offcadence_sync_steps"] == 0
    assert acct["offcadence_sync_suspect"] is False
    rec.summary()
    # lock-stepped: every dispatch blocks ~a full device step
    for _ in range(3):
        _drive_step(rec, clock, dispatch=0.07, untracked=0.001)
    _drive_step(rec, clock, dispatch=0.07, sync=0.001)  # nothing to drain
    acct = bud.close_window(step=8, emit=False)
    assert acct["offcadence_sync_steps"] == 3  # every non-cadence step
    assert acct["offcadence_sync_suspect"] is True


def test_budget_tripwire_warmup_window_stands_down():
    """The FIRST window holds the JIT compile — a legitimate dispatch
    block the tripwire cannot tell from a host-blocking transfer, so the
    default warmup suppresses it (stamped, not silent) and the detector
    arms from window 2."""
    clock = FakeClock()
    rec = SpanRecorder(clock=clock)
    bud = BudgetAccountant(rec)  # default warmup_windows=1
    _drive_step(rec, clock, dispatch=15.0)  # the compile step
    _drive_step(rec, clock, dispatch=0.002, busy=0.1)
    acct = bud.close_window(step=2, emit=False)
    assert acct["warmup"] is True
    assert acct["offcadence_sync_suspect"] is False
    rec.summary()
    # window 2: the same fat dispatch now IS a finding
    _drive_step(rec, clock, dispatch=0.08, untracked=0.001)
    _drive_step(rec, clock, dispatch=0.002, sync=0.001)
    acct = bud.close_window(step=4, emit=False)
    assert "warmup" not in acct
    assert acct["offcadence_sync_suspect"] is True


def test_budget_probe_zero_syncs_off_cadence(tmp_path):
    """The counting-leaf pin (PR 3's technique): the budget layer's only
    device interaction is the cadenced probe — off-cadence steps cost
    zero blocks, the cadence step exactly one."""

    class CountingLeaf:
        blocks = 0

        def block_until_ready(self):
            CountingLeaf.blocks += 1
            return self

    cfg = TrainConfig(
        output_dir=str(tmp_path), obs="jsonl", log_every_steps=4,
        health="off",
    )
    obs = TrainerObs(cfg, start_step=0)
    assert obs.budget is not None
    CountingLeaf.blocks = 0
    for step in (1, 2, 3):
        with obs.step_span():
            pass
        obs.budget_probe(step, CountingLeaf())
        obs.on_step(step, 0, {})
        assert CountingLeaf.blocks == 0  # the invariant
    with obs.step_span():
        pass
    obs.budget_probe(4, CountingLeaf())
    obs.on_step(4, 0, {})
    assert CountingLeaf.blocks == 1  # exactly the cadence probe
    assert obs.budget.history, "cadence must close a step_budget account"
    acct = obs.budget.history[-1]
    assert acct["window_steps"] == 4
    assert acct["device_busy_ms"] >= 0.0
    sink_mod.current_sink().close()


def test_budget_window_resets_without_obs_window(tmp_path):
    """--obs off --obs-budget on: emit_window (which resets the span
    window) never runs, so the cadence must consume the window itself —
    otherwise every account re-counts all prior steps (regression)."""
    cfg = TrainConfig(
        output_dir=str(tmp_path), obs="off", obs_budget="on",
        log_every_steps=2, health="off",
    )
    obs = TrainerObs(cfg, start_step=0)
    assert obs.budget is not None and not obs.enabled
    for step in range(1, 7):
        with obs.step_span():
            pass
        obs.on_step(step, 0, {})
    assert [a["window_steps"] for a in obs.budget.history] == [2, 2, 2]


def test_budget_enabled_tristate():
    assert budget_enabled(TrainConfig(obs_budget="on", obs="off"))
    assert not budget_enabled(TrainConfig(obs_budget="off", obs="jsonl"))
    assert budget_enabled(TrainConfig(obs_budget="auto", obs="stdout"))
    assert budget_enabled(TrainConfig(obs_budget="auto", obs="jsonl"))
    assert not budget_enabled(TrainConfig(obs_budget="auto", obs="off"))


def test_aggregate_accounts_weighted():
    a = {
        "wall_ms": 100.0, "window_steps": 2, "dispatch_efficiency": 1.0,
        **{f"{c}_ms": 0.0 for c in COMPONENTS},
    }
    b = {
        "wall_ms": 300.0, "window_steps": 6, "dispatch_efficiency": 0.5,
        **{f"{c}_ms": 10.0 for c in COMPONENTS},
        "offcadence_sync_steps": 2,
    }
    agg = aggregate_accounts([a, b])
    assert agg["windows"] == 2 and agg["steps"] == 8
    assert agg["wall_ms"] == pytest.approx(400.0)
    # wall-weighted: (1.0·100 + 0.5·300) / 400
    assert agg["dispatch_efficiency"] == pytest.approx(0.625)
    assert agg["unattributed_ms"] == pytest.approx(10.0)
    assert agg["offcadence_sync_steps"] == 2
    assert aggregate_accounts([]) is None


# ---------------------------------------------------------------------------
# schema round-trip: every new event type through the report loader
# ---------------------------------------------------------------------------


def test_schema_round_trip_new_event_types(tmp_path):
    """step_budget and serve_request both parse back through
    obs/report.py's loader schema-checked, feed build_report, and the
    markdown renders the budget section."""
    from distributed_llms_example_tpu.utils.jsonlog import log_json

    cfg = TrainConfig(
        output_dir=str(tmp_path), obs="jsonl", log_every_steps=2,
        health="off",
    )
    obs = TrainerObs(cfg, start_step=0)
    assert obs.budget is not None
    for step in (1, 2):
        with obs.host_span():
            pass
        with obs.step_span():
            pass
        with obs.sync_span():
            pass
        obs.on_step(step, 0, {})
    # a serving request span, the shape the engine emits
    log_json({
        "event": "serve_request", "request": 0, "slot": 1,
        "queue_wait_ms": 1.5, "prefill_ms": 20.0, "ttft_ms": 30.0,
        "decode_ms": 55.0, "tokens": 12, "t_admit_s": 0.0015,
        "t_done_s": 0.085, "finished_at_step": 12,
    })
    obs.finalize(2, 0)
    sink_mod.current_sink().close()
    path = os.path.join(str(tmp_path), "obs", "metrics-p000.jsonl")
    records, errors = load_jsonl(path)
    assert errors == []
    events = {r.get("event", "metric") for r in records}
    assert {"step_budget", "serve_request"} <= events
    budget = next(r for r in records if r.get("event") == "step_budget")
    for c in COMPONENTS:
        assert f"{c}_ms" in budget
    assert {"dispatch_efficiency", "accounted_frac", "additivity_ok",
            "offcadence_sync_steps"} <= set(budget)
    report = build_report(str(tmp_path))
    assert report["schema_errors"] == []
    assert report["budget"] is not None
    assert report["budget"]["ranks"]["0"]["windows"] >= 1
    md = render_markdown(report)
    assert "Where did the time go" in md
    assert "dispatch efficiency" in md


# ---------------------------------------------------------------------------
# report: budget section, offenders, incidents, the strict floor
# ---------------------------------------------------------------------------


def _stamp(rec: dict) -> dict:
    return {"schema_version": 1, **rec}


def _budget_event(step, *, wall=1000.0, data_wait=300.0, dispatch=50.0,
                  busy=500.0, sync=50.0, host=50.0, unattr=50.0,
                  eff=None, offcadence=0):
    eff = eff if eff is not None else round(
        1 - (data_wait + host + unattr) / wall, 4
    )
    return _stamp({
        "event": "step_budget", "step": step, "window_steps": 4,
        "wall_ms": wall, "data_wait_ms": data_wait, "dispatch_ms": dispatch,
        "device_busy_ms": busy, "sync_block_ms": sync,
        "host_overhead_ms": host, "unattributed_ms": unattr,
        "accounted_frac": round((wall - unattr) / wall, 4),
        "additivity_ok": unattr <= 0.05 * wall,
        "dispatch_efficiency": eff,
        "offcadence_sync_steps": offcadence,
        "offcadence_sync_suspect": offcadence > 0,
    })


def _write_rank(tmp_path, rank: int, recs: list[dict]) -> None:
    obs_dir = tmp_path / "obs"
    os.makedirs(obs_dir, exist_ok=True)
    with open(obs_dir / f"metrics-p{rank:03d}.jsonl", "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")


def test_report_budget_section_and_strict_floor(tmp_path, capsys):
    from distributed_llms_example_tpu.obs.report import main as report_main

    _write_rank(tmp_path, 0, [
        _budget_event(2),
        _budget_event(4, data_wait=600.0, unattr=100.0, busy=200.0,
                      offcadence=3),
    ])
    _write_rank(tmp_path, 1, [_budget_event(2), _budget_event(4)])
    report = build_report(str(tmp_path))
    budget = report["budget"]
    assert set(budget["ranks"]) == {"0", "1"}
    # rank 0: (0.6·1000 + 0.25·1000)/2000 wall-weighted
    assert budget["ranks"]["0"]["dispatch_efficiency"] == pytest.approx(
        0.425, abs=1e-3
    )
    assert budget["dispatch_efficiency"] == pytest.approx(
        (0.425 * 2000 + 0.6 * 2000) / 4000, abs=1e-3
    )
    # worst offender: data_wait dominates the stall components
    assert budget["offenders"][0]["component"] == "data_wait"
    assert budget["incidents"] == [{
        "rank": 0, "step": 4, "blocked_steps": 3, "window_steps": 4,
        "dispatch_ms": 50.0,
    }]
    md = render_markdown(report)
    assert "off-cadence host-blocking dispatch incidents" in md
    assert "rank 0 window@step 4: 3/4 step(s)" in md
    # the strict floor: 0.52 mean efficiency fails a 0.9 floor...
    rc = report_main([
        str(tmp_path), "--strict", "--min-dispatch-efficiency", "0.9",
    ])
    assert rc == 1
    assert "below the 0.9 floor" in capsys.readouterr().err
    # ...passes a 0.4 floor, and no floor means no budget gate at all
    assert report_main([
        str(tmp_path), "--strict", "--min-dispatch-efficiency", "0.4",
    ]) == 0
    assert report_main([str(tmp_path), "--strict"]) == 0


@pytest.mark.parametrize(
    "effs, floor, rc",
    [
        ((0.97, 0.95), "0.9", 0),
        ((0.5,), "0.9", 1),
        ((0.5,), "0.4", 0),
    ],
    ids=["above-floor", "below-floor", "below-a-lower-floor"],
)
def test_strict_dispatch_floor_by_run(tmp_path, capsys, effs, floor, rc):
    """The trainer-loop-gap gate is one command: a run whose wall-weighted
    dispatch_efficiency sits under the floor fails, one above it passes."""
    from distributed_llms_example_tpu.obs.report import main as report_main

    _write_rank(tmp_path, 0, [
        _budget_event(2 * (i + 1), eff=e) for i, e in enumerate(effs)
    ])
    assert report_main([
        str(tmp_path), "--strict", "--min-dispatch-efficiency", floor, "--json",
    ]) == rc
    capsys.readouterr()


def test_report_strict_floor_without_budget_records(tmp_path, capsys):
    from distributed_llms_example_tpu.obs.report import main as report_main

    _write_rank(tmp_path, 0, [_stamp({"step": 1, "loss": 1.0})])
    assert report_main([str(tmp_path)]) == 0
    rc = report_main([
        str(tmp_path), "--strict", "--min-dispatch-efficiency", "0.5",
    ])
    assert rc == 1  # a floor with no data is a failed gate, not a pass
    assert "no step_budget records" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# the cadenced optimizer-apply gauge (ISSUE 10 satellite)
# ---------------------------------------------------------------------------


def test_probe_optimizer_gauge_lands_on_account():
    """probe_optimizer: the first call warms (a lazily-built probe
    jit-compiles inside fn — a compile is not an apply), subsequent calls
    time fn and the newest sample rides the next account as
    optimizer_apply_ms + optimizer_share_of_step."""
    import jax.numpy as jnp

    clock = FakeClock()
    rec = SpanRecorder(clock=clock)
    bud = BudgetAccountant(rec)
    calls = []

    def fn():
        # "compile" costs 1.0s, later applies 0.007s — the fake clock
        # advances inside the timed region exactly like a real block
        calls.append(1)
        clock.advance(1.0 if len(calls) == 1 else 0.007)
        return jnp.zeros(())

    _drive_step(rec, clock, dispatch=0.05, untracked=0.05)
    bud.probe_optimizer(fn)
    # warm + timed: two calls, and the SAMPLE is the second (7 ms)
    assert len(calls) == 2
    acct = bud.close_window(step=1, emit=False)
    assert acct["optimizer_apply_ms"] == pytest.approx(7.0)
    # share: 7 ms of a 100 ms mean step wall
    assert acct["optimizer_share_of_step"] == pytest.approx(0.07, abs=1e-3)
    # next window: one timed call only, sample refreshed
    _drive_step(rec, clock, dispatch=0.05, untracked=0.05)
    bud.probe_optimizer(fn)
    assert len(calls) == 3
    acct = bud.close_window(step=2, emit=False)
    assert acct["optimizer_apply_ms"] == pytest.approx(7.0)


def test_trainer_obs_optimizer_probe_cadence_gated(tmp_path):
    """TrainerObs.optimizer_probe runs the factory at the log cadence
    only — off-cadence steps never touch it (zero new syncs)."""
    import jax.numpy as jnp

    cfg = TrainConfig(output_dir=str(tmp_path), obs="off", obs_budget="on",
                      log_every_steps=3, health="off")
    obs = TrainerObs(cfg, start_step=0)
    calls = []

    def fn():
        calls.append(1)
        return jnp.zeros(())

    for step in range(1, 7):
        with obs.step_span():
            pass
        obs.optimizer_probe(step, fn)
        obs.on_step(step, 0, {})
    # cadence steps 3 and 6: warm+timed at 3, timed at 6
    assert len(calls) == 3
    assert obs.budget.history[-1].get("optimizer_apply_ms") is not None
    sink_mod.current_sink().close()


def test_aggregate_accounts_carries_optimizer_gauge():
    base = {
        "wall_ms": 100.0, "window_steps": 2, "dispatch_efficiency": 1.0,
        **{f"{c}_ms": 0.0 for c in COMPONENTS},
    }
    a = dict(base, optimizer_apply_ms=10.0, optimizer_share_of_step=0.2)
    b = dict(base, optimizer_apply_ms=20.0, optimizer_share_of_step=0.4)
    c = dict(base)  # a window without a sample must not poison the mean
    agg = aggregate_accounts([a, b, c])
    assert agg["optimizer_apply_ms"] == pytest.approx(15.0)
    assert agg["optimizer_share_of_step"] == pytest.approx(0.3)
    assert "optimizer_apply_ms" not in (aggregate_accounts([c]) or {})


def test_report_renders_optimizer_gauge(tmp_path):
    ev = _budget_event(2)
    ev["optimizer_apply_ms"] = 12.5
    ev["optimizer_share_of_step"] = 0.05
    _write_rank(tmp_path, 0, [ev])
    report = build_report(str(tmp_path))
    assert report["budget"]["ranks"]["0"]["optimizer_apply_ms"] == pytest.approx(12.5)
    md = render_markdown(report)
    assert "optimizer apply (cadenced stand-alone sample)" in md
    # absent gauge → no line (and no crash)
    plain = tmp_path / "plain"
    _write_rank(plain, 0, [_budget_event(2)])
    assert "optimizer apply (cadenced" not in render_markdown(build_report(str(plain)))


def test_probe_optimizer_failure_disables_gauge_not_run(capsys):
    """A failing probe (OOM compiling the stand-alone apply, transient
    backend error) must disable the gauge with one logged event — never
    propagate into the training loop — and a failed WARM call must not
    leave a later compile mislabeled as the timed sample."""
    import jax.numpy as jnp

    clock = FakeClock()
    rec = SpanRecorder(clock=clock)
    bud = BudgetAccountant(rec)
    calls = []

    def failing_then_fine():
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("RESOURCE_EXHAUSTED: probe compile OOM")
        clock.advance(0.007)
        return jnp.zeros(())

    _drive_step(rec, clock, dispatch=0.05, untracked=0.05)
    bud.probe_optimizer(failing_then_fine)  # swallowed, probe disabled
    assert len(calls) == 1
    bud.probe_optimizer(failing_then_fine)  # dead: never calls fn again
    assert len(calls) == 1
    acct = bud.close_window(step=1, emit=False)
    assert "optimizer_apply_ms" not in acct
    assert "optimizer_probe_disabled" in capsys.readouterr().out
