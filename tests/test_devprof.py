"""Device-time attribution (ISSUE 11): profiler traces → device account.

Pins: the fixture-pinned trace parse (hand-written trace-viewer JSON with
known durations/op_names → EXACT per-bucket times, overlap and idle);
the achieved-bandwidth join against a hand byte account (exact numbers);
the shared op_name→bucket mapping (analysis/ir_lint.py) between param
paths and HLO scopes; the fake-capture end-to-end (fixture trace →
TrainerObs parse → device_account in the JSONL → report tables FROM THE
JSONL ALONE); the
``--profile-on-anomaly`` trigger arming; the schema round-trip for
``device_account``/``profile_captured``; and the strict
``--min-overlap-frac`` gate (including captures that produced no
account).  The REAL CPU profile round-trip on the 8-device mesh rides
the slow tier (jax's profiler session init dominates).
"""

from __future__ import annotations

import gzip
import json
import os
import shutil

import pytest

from distributed_llms_example_tpu.analysis.ir_lint import (
    base_collective_op,
    classify_op_scope,
    module_bucket_of,
    op_bucket_index,
)
from distributed_llms_example_tpu.core.config import (
    CheckpointConfig,
    MeshConfig,
    TrainConfig,
)
from distributed_llms_example_tpu.obs import TrainerObs, sink as sink_mod
from distributed_llms_example_tpu.obs.devprof import (
    DEVICE_BUCKETS,
    build_account,
    classify_event,
    device_account_from_dir,
    device_op_events,
    find_trace_files,
    join_collective_bandwidth,
)
from distributed_llms_example_tpu.obs.report import (
    build_report,
    load_jsonl,
    render_markdown,
)


@pytest.fixture(autouse=True)
def _default_sink():
    sink_mod.install_sink(sink_mod.build_sink("stdout", ""))
    yield
    sink_mod.install_sink(sink_mod.build_sink("stdout", ""))


# ---------------------------------------------------------------------------
# the shared op_name→bucket mapping (analysis/ir_lint.py)
# ---------------------------------------------------------------------------


def test_module_bucket_table_matches_param_buckets():
    # the same table serves param paths (train/step.py bucket_of_path)
    # and device op scopes — spot-check both spellings
    assert module_bucket_of("encoder/block_0/self_attn/q_proj") == "attn"
    assert module_bucket_of("model/decoder/layers/3/mlp/wi") == "mlp"
    assert module_bucket_of("shared/embedding") == "embed"
    assert module_bucket_of("lm_head/kernel") == "head"
    assert module_bucket_of("final_norm/scale") is None  # caller decides


def test_classify_op_scope_optimizer_and_modules():
    assert classify_op_scope(
        "jit(train_step)/jit(main)/Model/encoder/block_0/self_attn/dot_general"
    ) == "attn"
    assert classify_op_scope("jit(train_step)/jit(main)/adamw/mul") == "optimizer"
    assert classify_op_scope("jit(f)/jit(main)/clip_by_global_norm/div") == "optimizer"
    assert classify_op_scope("jit(f)/jit(main)/reduce_sum") is None


def test_base_collective_op_forms():
    assert base_collective_op("all-reduce") == "all-reduce"
    assert base_collective_op("all-reduce-start.3") == "all-reduce"
    assert base_collective_op("reduce-scatter.12") == "reduce-scatter"
    assert base_collective_op("collective-permute-done.1") == "collective-permute"
    assert base_collective_op("dot.1") is None
    assert base_collective_op("fusion.clone") is None


def test_op_bucket_index_from_hlo_metadata():
    text = "\n".join([
        "HloModule jit_train_step",
        "ENTRY %main () -> f32[] {",
        '  %dot.1 = f32[8,8]{1,0} dot(%a, %b), metadata={op_name="jit(f)/jit(main)/M/encoder/block_0/self_attn/q_proj/dot_general" source_file="m.py" source_line=10}',
        '  %fusion.2 = f32[8]{0} fusion(%dot.1), kind=kLoop, metadata={op_name="jit(f)/jit(main)/M/encoder/block_0/mlp/wi/dot_general"}',
        "  %all-reduce.3 = f32[8]{0} all-reduce(%fusion.2), replica_groups={{0,1}}, to_apply=%add",
        '  %copy.4 = f32[8]{0} copy(%all-reduce.3), metadata={op_name="jit(f)/jit(main)/adamw/update"}',
        '  %embed.5 = f32[16]{0} gather(%c, %d), metadata={op_name="jit(f)/jit(main)/M/shared/take"}',
        '  %slice.6 = f32[4]{0} slice(%embed.5), metadata={op_name="jit(f)/jit(main)/reduce_sum"}',
        "  %rs.7 = f32[4]{0} reduce-scatter(%slice.6), replica_groups={{0,1}}, to_apply=%add",
        "}",
    ])
    idx = op_bucket_index(text)
    assert idx["dot.1"] == "attn"
    assert idx["fusion.2"] == "mlp"
    assert idx["all-reduce.3"] == "collective"
    assert idx["copy.4"] == "optimizer"
    assert idx["embed.5"] == "embed"
    assert idx["slice.6"] == "other"  # scope with no module signal
    assert idx["rs.7"] == "collective"


def test_classify_event_precedence():
    idx = {"fusion.1": "attn"}
    # collective opcode beats everything, with or without an index
    assert classify_event("all-reduce.9", "all-reduce.9", idx) == "collective"
    assert classify_event("all-gather-start.2", "", None) == "collective"
    assert classify_event("outfeed.1", "outfeed.1", idx) == "infeed"
    # instruction-name join (CPU traces)
    assert classify_event("fusion.1", "fusion.1", idx) == "attn"
    # scope-named events (TPU device lanes) classify directly
    assert classify_event("M/decoder/layers/0/mlp/wo/dot", "", None) == "mlp"
    # nothing known → other
    assert classify_event("dot.7", "dot.7", idx) == "other"
    assert classify_event("dot.7", "dot.7", None) == "other"


# ---------------------------------------------------------------------------
# fixture-pinned parse: known durations → exact account
# ---------------------------------------------------------------------------

# one hand-written trace-viewer session: timings in µs, chosen so every
# derived number below is exact decimal arithmetic
_FIXTURE_OP_BUCKETS = {"fusion.1": "attn", "fusion.2": "mlp"}


def _fixture_events() -> list[dict]:
    def x(name, ts, dur, tid):
        return {
            "ph": "X", "pid": 1, "tid": tid, "ts": float(ts),
            "dur": float(dur), "name": name,
            "args": {"hlo_module": "jit_train_step", "hlo_op": name},
        }

    return [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": 1, "tid": 7, "name": "thread_name",
         "args": {"name": "tf_XLAEigen/7"}},
        # host-side python noise: no hlo_op, no /device: pid → excluded
        {"ph": "X", "pid": 1, "tid": 99, "ts": 0.0, "dur": 9500.0,
         "name": "PjitFunction(train_step)"},
        x("fusion.1", 0, 4000, 7),        # attn   [0, 4000)
        x("fusion.2", 4000, 2000, 7),     # mlp    [4000, 6000)
        x("all-reduce.3", 5000, 2000, 8),  # comm  [5000, 7000) — 1 ms under compute
        x("dot.4", 8000, 1000, 7),        # other  [8000, 9000) after 1 ms idle
    ]


def _write_fixture_trace(dir_path: str, events: list[dict]) -> str:
    session = os.path.join(dir_path, "plugins", "profile", "2026_08_04_00_00_00")
    os.makedirs(session, exist_ok=True)
    path = os.path.join(session, "host.trace.json.gz")
    with gzip.open(path, "wt") as f:
        json.dump({"displayTimeUnit": "ns", "traceEvents": events}, f)
    return path


def test_fixture_trace_exact_account(tmp_path):
    _write_fixture_trace(str(tmp_path), _fixture_events())
    acct = device_account_from_dir(
        str(tmp_path), op_buckets=_FIXTURE_OP_BUCKETS
    )
    assert acct is not None and acct["events"] == 4
    assert acct["span_ms"] == 9.0
    # busy union [0,7000)∪[8000,9000) = 8 ms; exposed idle = 1 ms
    assert acct["busy_ms"] == 8.0
    assert acct["exposed_idle_ms"] == 1.0
    b = acct["buckets_ms"]
    assert b["attn"] == 4.0 and b["mlp"] == 2.0
    assert b["collective"] == 2.0 and b["other"] == 1.0
    assert b["embed"] == b["head"] == b["optimizer"] == b["infeed"] == 0.0
    # per-bucket sums cover the measured device span entirely (the
    # acceptance bar is ≥ 90%; an attributed-total parse hits 100%+)
    assert sum(b.values()) >= 0.9 * acct["busy_ms"]
    assert acct["bucket_frac"]["attn"] == pytest.approx(4.0 / 9.0, abs=1e-4)
    assert acct["collectives"] == {
        "all-reduce": {"count": 1, "time_ms": 2.0, "wall_ms": 2.0}
    }
    ov = acct["overlap"]
    # compute [0,6000)∪[8000,9000) = 7 ms; comm [5000,7000) = 2 ms;
    # intersection [5000,6000) = 1 ms → half the comm hid under compute
    assert ov["compute_ms"] == 7.0 and ov["collective_ms"] == 2.0
    assert ov["overlapped_ms"] == 1.0 and ov["exposed_collective_ms"] == 1.0
    assert ov["overlap_frac"] == 0.5
    # lanes: one merged slice per bucket, start-ordered, ms-relative
    assert acct["lanes"] == [
        ["attn", 0.0, 4.0], ["mlp", 4.0, 2.0],
        ["collective", 5.0, 2.0], ["other", 8.0, 1.0],
    ]


def test_fixture_bandwidth_join_exact(tmp_path):
    """Known collective durations + the static byte account reproduce
    hand-computed achieved-bandwidth numbers exactly."""
    _write_fixture_trace(str(tmp_path), _fixture_events())
    acct = device_account_from_dir(str(tmp_path), op_buckets=_FIXTURE_OP_BUCKETS)
    comm = {
        "all-reduce": {"count": 1, "gradient_bytes": 600, "activation_bytes": 400},
        "total_bytes": 1000,  # rollup keys must be ignored by the join
    }
    join_collective_bandwidth(acct, comm, window_steps=2)
    slot = acct["collectives"]["all-reduce"]
    assert slot["bytes_per_step"] == 1000
    # 1000 B/step × 2 steps over 2 ms of device time = 1,000,000 B/s
    assert slot["achieved_bytes_per_sec"] == 1_000_000.0
    # no byte row for the op → time stays, no bandwidth claim
    acct2 = device_account_from_dir(str(tmp_path), op_buckets=_FIXTURE_OP_BUCKETS)
    join_collective_bandwidth(acct2, {"reduce-scatter": {"gradient_bytes": 8}}, 2)
    assert "achieved_bytes_per_sec" not in acct2["collectives"]["all-reduce"]


def test_bandwidth_uses_cross_lane_wall_not_summed_time(tmp_path):
    """On a multi-device host every participant emits its own collective
    event; the bandwidth denominator must be the cross-lane WALL (union),
    not the lane-summed device·time — else achieved bytes/sec reads N×
    too low on an N-device host."""
    events = [
        # 4 participants run the same 2 ms all-reduce concurrently
        {"ph": "X", "pid": 1, "tid": 10 + i, "ts": 1000.0, "dur": 2000.0,
         "name": "all-reduce.1", "args": {"hlo_op": "all-reduce.1"}}
        for i in range(4)
    ]
    _write_fixture_trace(str(tmp_path), events)
    acct = device_account_from_dir(str(tmp_path))
    slot = acct["collectives"]["all-reduce"]
    assert slot["count"] == 4
    assert slot["time_ms"] == 8.0   # summed device·time (4 lanes × 2 ms)
    assert slot["wall_ms"] == 2.0   # the wire was busy for 2 ms of wall
    join_collective_bandwidth(
        acct, {"all-reduce": {"gradient_bytes": 1000, "activation_bytes": 0}}, 2
    )
    # 1000 B/step × 2 steps over 2 ms WALL = 1,000,000 B/s — the
    # lane-summed time would have claimed a quarter of that
    assert slot["achieved_bytes_per_sec"] == 1_000_000.0


def test_device_pid_aggregate_lanes_excluded():
    """TPU-style traces stack 'XLA Modules'/'Steps' lanes under each
    device pid — whole-step slices enclosing every op.  Counting them
    would balloon 'other' and pin overlap_frac at 1.0, so only the
    per-op lanes survive normalization."""
    events = [
        {"ph": "M", "pid": 7, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 7, "tid": 1, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 7, "tid": 2, "name": "thread_name",
         "args": {"name": "XLA Modules"}},
        {"ph": "M", "pid": 7, "tid": 3, "name": "thread_name",
         "args": {"name": "Steps"}},
        {"ph": "X", "pid": 7, "tid": 1, "ts": 0.0, "dur": 100.0,
         "name": "model/encoder/block_0/mlp/wi/dot"},
        {"ph": "X", "pid": 7, "tid": 2, "ts": 0.0, "dur": 1000.0,
         "name": "jit_train_step"},
        {"ph": "X", "pid": 7, "tid": 3, "ts": 0.0, "dur": 1000.0,
         "name": "step 5"},
    ]
    ops = device_op_events(events)
    assert [e["name"] for e in ops] == ["model/encoder/block_0/mlp/wi/dot"]
    acct = build_account(ops)
    assert acct["buckets_ms"]["mlp"] == 0.1
    assert acct["buckets_ms"]["other"] == 0.0


def test_truncated_capture_clamps_window(tmp_path, capsys):
    """A run that dies inside the profile window reports the steps it
    actually captured — the scheduled stop would inflate every per-step
    consumer (the bandwidth join multiplies bytes/step by window steps)."""
    from distributed_llms_example_tpu.obs.profile import ProfileController

    ctl = ProfileController(
        steps_spec="5:10", output_dir=str(tmp_path), start_step=0
    )
    seen = []
    ctl.on_capture = lambda d, w, t: seen.append((w, t))
    ctl.before_step(5)
    assert ctl.active
    # the run ends after step 6 — four scheduled steps never happen
    ctl.finalize(None, last_step=6)
    assert seen == [((5, 6), True)]
    lines = _json_lines(capsys.readouterr().out)
    cap = next(r for r in lines if r.get("event") == "profile_captured")
    assert cap["window"] == [5, 6] and cap["steps"] == 2
    assert cap["truncated"] is True


def _json_lines(text):
    out = []
    for line in text.splitlines():
        if line.startswith("{"):
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                pass
    return out


def test_find_trace_files_newest_session_and_empty(tmp_path):
    assert device_account_from_dir(str(tmp_path / "nothing")) is None
    old = _write_fixture_trace(str(tmp_path), _fixture_events())
    # a newer session with one tiny event must win the session pick
    newer = os.path.join(
        str(tmp_path), "plugins", "profile", "2026_08_04_11_11_11"
    )
    os.makedirs(newer)
    with open(os.path.join(newer, "host.trace.json"), "w") as f:
        json.dump({"traceEvents": [{
            "ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "dur": 500.0,
            "name": "dot.1", "args": {"hlo_op": "dot.1"},
        }]}, f)
    os.utime(old, (1, 1))  # the gz is the OLD session now
    acct = device_account_from_dir(str(tmp_path))
    assert acct is not None and acct["events"] == 1
    assert acct["span_ms"] == 0.5
    # an empty-events trace parses to None, not a zero account
    shutil.rmtree(os.path.join(str(tmp_path), "plugins"))
    _write_fixture_trace(str(tmp_path), [
        {"ph": "M", "pid": 1, "name": "process_name", "args": {"name": "x"}},
    ])
    assert device_account_from_dir(str(tmp_path)) is None


def test_account_lane_cap_counts_drops(tmp_path):
    events = [
        {"ph": "X", "pid": 1, "tid": 1, "ts": float(i * 10), "dur": 4.0,
         "name": f"dot.{i}", "args": {"hlo_op": f"dot.{i}"}}
        for i in range(50)
    ]
    _write_fixture_trace(str(tmp_path), events)
    normalized = device_op_events(
        json.load(gzip.open(find_trace_files(str(tmp_path))[0], "rt"))["traceEvents"]
    )
    acct = build_account(normalized, max_lane_slices=8)
    assert len(acct["lanes"]) == 8
    assert acct["lane_slices_dropped"] == 42  # counted, never silent


# ---------------------------------------------------------------------------
# fake-capture end-to-end: TrainerObs parse → JSONL → report
# ---------------------------------------------------------------------------


def _obs_with_fixture_capture(tmp_path) -> TrainerObs:
    cfg = TrainConfig(
        output_dir=str(tmp_path), obs="jsonl", log_every_steps=1,
        health="off",
    )
    obs = TrainerObs(cfg, start_step=0)
    assert obs.budget is not None
    # what startup_gauges would have supplied (gauges are off here: no
    # AOT compile in a fast test)
    obs._op_buckets = dict(_FIXTURE_OP_BUCKETS)
    obs._comm_account = {
        "all-reduce": {"count": 1, "gradient_bytes": 600, "activation_bytes": 400},
    }
    capture_dir = os.path.join(str(tmp_path), "capture")
    _write_fixture_trace(capture_dir, _fixture_events())
    # drive three steps around the capture window [2, 3]
    for step in (1, 2):
        with obs.step_span():
            pass
        obs.on_step(step, 0, {})
    # the capture "lands" after step 3's work, before its cadence close
    obs._on_profile_captured(capture_dir, (2, 3))
    with obs.step_span():
        pass
    obs.on_step(3, 0, {})
    sink_mod.emit({
        "event": "profile_captured", "path": capture_dir,
        "window": [2, 3], "steps": 2,
    }, all_processes=True)
    obs.finalize(3, 0)
    sink_mod.current_sink().close()
    return obs


def test_fake_capture_roundtrip_jsonl_report(tmp_path):
    _obs_with_fixture_capture(tmp_path)
    # schema round-trip: device_account + profile_captured parse back
    # through the report loader schema-checked
    path = os.path.join(str(tmp_path), "obs", "metrics-p000.jsonl")
    records, errors = load_jsonl(path)
    assert errors == []
    events = {r.get("event", "metric") for r in records}
    assert {"device_account", "profile_captured", "step_budget"} <= events
    acct = next(r for r in records if r.get("event") == "device_account")
    assert acct["window"] == [2, 3] and acct["window_steps"] == 2
    assert acct["buckets_ms"]["attn"] == 4.0
    # the runtime join already stamped achieved bandwidth (gauges' comm)
    assert acct["collectives"]["all-reduce"]["achieved_bytes_per_sec"] == 1_000_000.0

    # the report renders bucket + bandwidth + overlap from JSONL ALONE:
    # remove the trace files first to prove it
    shutil.rmtree(os.path.join(str(tmp_path), "capture"))
    report = build_report(str(tmp_path))
    assert report["schema_errors"] == []
    device = report["device"]
    assert device["accounts"] == 1 and set(device["ranks"]) == {"0"}
    assert device["captures"][0]["window"] == [2, 3]
    md = render_markdown(report)
    assert "Device account (profiled windows)" in md
    assert "all-reduce" in md and "1.0 MB/s achieved" in md
    assert "overlap_frac 0.5" in md


@pytest.mark.parametrize(
    "floor, rc", [("0.9", 1), ("0.3", 0), (None, 0)],
    ids=["floor-above", "floor-below", "no-floor"],
)
def test_strict_min_overlap_frac_gate(tmp_path, capsys, floor, rc):
    from distributed_llms_example_tpu.obs.report import main as report_main

    _obs_with_fixture_capture(tmp_path)
    # overlap_frac 0.5: a 0.9 floor fails, a 0.3 floor passes, and without
    # the floor the same run is strict-green
    gate = ["--min-overlap-frac", floor] if floor else []
    assert report_main([str(tmp_path), "--strict", *gate, "--json"]) == rc
    err = capsys.readouterr().err
    assert ("overlap_frac 0.5 below" in err) == (rc == 1)


def test_strict_fails_on_capture_without_account(tmp_path, capsys):
    from distributed_llms_example_tpu.obs.report import main as report_main

    obs_dir = os.path.join(str(tmp_path), "obs")
    os.makedirs(obs_dir)
    with open(os.path.join(obs_dir, "metrics-p000.jsonl"), "w") as f:
        f.write(json.dumps({
            "schema_version": 1, "event": "profile_captured",
            "path": "/tmp/x", "window": [2, 3], "steps": 2,
        }) + "\n")
    # a capture landed but no device_account: the gate must not pass
    rc = report_main([
        str(tmp_path), "--strict", "--min-overlap-frac", "0.1", "--json",
    ])
    assert rc == 1
    assert "no device_account" in capsys.readouterr().err
    # without the device floor this is not gated (budget-only runs)
    assert report_main([str(tmp_path), "--strict", "--json"]) == 0


# ---------------------------------------------------------------------------
# --profile-on-anomaly: an agreed anomaly arms the trigger machinery
# ---------------------------------------------------------------------------


def test_profile_on_anomaly_arms_trigger(tmp_path):
    cfg = TrainConfig(
        output_dir=str(tmp_path), obs="jsonl", health="on",
        log_every_steps=2, recorder_steps=8, profile_on_anomaly=True,
    )
    obs = TrainerObs(cfg, start_step=0)
    trigger = os.path.join(str(tmp_path), "obs", "profile.trigger")
    assert obs._trigger == trigger
    with obs.step_span():
        pass
    assert obs.on_step(
        1, 0, {"loss": 2.0, "grad_norm": 1.0, "nonfinite_count": 0.0}
    ) == "ok"
    assert not os.path.exists(trigger)  # healthy window: not armed
    with obs.step_span():
        pass
    action = obs.on_step(
        2, 0, {"loss": float("nan"), "grad_norm": 1.0, "nonfinite_count": 1.0}
    )
    assert action == "warn"
    # the anomaly armed the profiler's OWN trigger file (the same file an
    # operator would touch), so the NEXT before_step opens a capture
    assert os.path.exists(trigger)
    with open(trigger) as f:
        assert int(f.read()) >= 1
    sink_mod.current_sink().close()
    path = os.path.join(str(tmp_path), "obs", "metrics-p000.jsonl")
    records, errors = load_jsonl(path)
    assert errors == []
    armed = next(r for r in records if r.get("event") == "profile_trigger_armed")
    assert armed["reason"] == "anomaly:nonfinite" and armed["step"] == 2


def test_profile_on_anomaly_off_by_default(tmp_path):
    cfg = TrainConfig(
        output_dir=str(tmp_path), obs="jsonl", health="on",
        log_every_steps=1, recorder_steps=8,
    )
    obs = TrainerObs(cfg, start_step=0)
    with obs.step_span():
        pass
    obs.on_step(1, 0, {"loss": float("nan"), "grad_norm": 1.0,
                       "nonfinite_count": 1.0})
    assert not os.path.exists(
        os.path.join(str(tmp_path), "obs", "profile.trigger")
    )
    sink_mod.current_sink().close()


# ---------------------------------------------------------------------------
# the real thing: CPU-captured profile round-trip on the 8-device mesh
# ---------------------------------------------------------------------------


@pytest.mark.slow  # ~45s: jax profiler session init + a real t5-test train
def test_e2e_profiled_window_device_account(tmp_path):
    """The acceptance run: an 8-device CPU-mesh trainer with a profiled
    window emits device_account events whose bucket sums cover ≥ 90% of
    the measured device span, obs.report renders the tables from the
    JSONL alone (trace files deleted first), and the Perfetto export
    carries device lanes.  --profile-on-anomaly rides the same run
    through the poison-step hook and arms a SECOND capture."""
    import numpy as np

    from distributed_llms_example_tpu.train.trainer import Trainer

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
        num_epochs=4,  # 2 steps/epoch → 8 steps
        warmup_steps=1,
        evaluation_steps=0,
        max_source_length=32,
        max_target_length=16,
        pad_to_multiple=32,
        log_every_steps=1,
        num_beams=1,
        tokenizer="byte",
        mesh=MeshConfig(data=-1),
        checkpoint=CheckpointConfig(save_every_steps=0, resume=False, async_save=False),
        obs="jsonl",
        obs_gauges="on",  # the op_name index + byte account for the join
        health="on",
        on_anomaly="warn",
        recorder_steps=8,
        profile_steps="2:3",  # the profiled window
        profile_on_anomaly=True,
    )
    trainer = Trainer(cfg, train_records=recs)
    trainer.save_final = lambda: None
    trainer._poison_nan_at_step = 5  # detected at 5 → arms capture of 6-8
    result = trainer.train()
    assert result["steps"] == 8

    path = os.path.join(str(tmp_path), "obs", "metrics-p000.jsonl")
    records, errors = load_jsonl(path)
    assert errors == []
    captured = [r for r in records if r.get("event") == "profile_captured"]
    assert len(captured) >= 2  # the window capture AND the anomaly capture
    assert captured[0]["window"] == [2, 3]
    accounts = [r for r in records if r.get("event") == "device_account"]
    assert accounts, "no device_account emitted for the profiled window"
    acct = accounts[0]
    assert acct["window"] == [2, 3] and acct["window_steps"] == 2
    # the acceptance bar: per-bucket device times sum to ≥ 90% of the
    # window's measured device span (busy union) — nothing unattributed
    total = sum(acct["buckets_ms"].values())
    assert total >= 0.9 * acct["busy_ms"] > 0
    assert set(acct["buckets_ms"]) == set(DEVICE_BUCKETS)
    # the 8-way data-parallel step all-reduces its grads: collective
    # device time must be measured and the byte join must land
    assert "all-reduce" in acct["collectives"]
    ar = acct["collectives"]["all-reduce"]
    assert ar["time_ms"] > 0
    assert ar.get("bytes_per_step", 0) > 0
    assert ar.get("achieved_bytes_per_sec", 0) > 0
    assert "overlap" in acct and acct["overlap"]["collective_ms"] > 0

    # report renders the tables from the JSONL alone — trace dirs gone
    shutil.rmtree(os.path.join(str(tmp_path), "obs", "profile"))
    report = build_report(str(tmp_path))
    assert report["schema_errors"] == []
    assert report["device"] is not None and report["device"]["ranks"]
    md = render_markdown(report)
    assert "Device account (profiled windows)" in md
    assert "all-reduce" in md and "achieved" in md
