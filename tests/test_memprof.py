"""HBM memory attribution (ISSUE 18): the bucketed byte account, the
watermark telemetry, and the OOM forensics path.

Acceptance pins held here:

- on the REAL AOT-compiled fsdp=8 t5-test train step, the static
  account's bucket bytes sum to the XLA-reported peak within 5% (with
  donation/aliasing credited), and the params/optimizer buckets equal
  ``utils/memory_audit.py``'s analytic shard-byte counts EXACTLY — both
  derive from the same shared accounting functions, so forked arithmetic
  would fail here first;
- an injected RESOURCE_EXHAUSTED produces a parseable
  ``memory-postmortem-p*.json`` bundle (atomic: tmp + fsync + rename)
  and the ``obs.report`` "Where did the bytes go" section renders from
  the JSONL/bundle files alone;
- ``--max-peak-hbm-frac`` / ``--min-hbm-headroom-gib`` gate both ways
  under ``--strict`` and FAIL a run carrying no memory measurement — a
  missing measurement must never read as a pass;
- ``Watermark`` owns the reset-or-delta semantics over the
  process-lifetime PJRT peak, and degrades by NAME (never to zeros) on
  backends without ``memory_stats``.
"""

from __future__ import annotations

import glob
import json
import os

import pytest

from distributed_llms_example_tpu.core.config import MeshConfig
from distributed_llms_example_tpu.core.mesh import build_mesh
from distributed_llms_example_tpu.obs import memprof
from distributed_llms_example_tpu.obs import sink as sink_mod
from distributed_llms_example_tpu.obs.report import (
    build_report,
    main as report_main,
    render_markdown,
)


@pytest.fixture(autouse=True)
def _default_sink():
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
# Watermark: reset-or-delta semantics over the process-lifetime peak
# ---------------------------------------------------------------------------


def test_watermark_delta_semantics(monkeypatch):
    readings = [
        # two devices, asymmetric peaks: the reading maxes over devices
        [{"device": 0, "bytes_in_use": 100, "peak_bytes_in_use": 500,
          "bytes_limit": 1000},
         {"device": 1, "bytes_in_use": 90, "peak_bytes_in_use": 400,
          "bytes_limit": 1000}],
        [{"device": 0, "bytes_in_use": 200, "peak_bytes_in_use": 800,
          "bytes_limit": 1000},
         {"device": 1, "bytes_in_use": 250, "peak_bytes_in_use": 900,
          "bytes_limit": 1000}],
    ]
    monkeypatch.setattr(memprof, "hbm_stats", lambda: readings.pop(0))
    wm = memprof.Watermark()
    wm.mark()  # consumes the first reading: peaks {0: 500, 1: 400}
    r = wm.read()
    assert r["peak_bytes_in_use"] == 900
    assert r["bytes_in_use"] == 250
    # per-device deltas 300 and 500, maxed — NOT max-peak minus max-mark
    assert r["watermark_delta_bytes"] == 500
    assert r["devices"] == 2


def test_watermark_unmarked_reads_absolute_peak(monkeypatch):
    monkeypatch.setattr(memprof, "hbm_stats", lambda: [
        {"device": 0, "bytes_in_use": 10, "peak_bytes_in_use": 700,
         "bytes_limit": 1000},
    ])
    wm = memprof.Watermark()
    assert wm.read()["watermark_delta_bytes"] == 700
    assert wm.peak_bytes() == 700
    assert wm.delta_bytes() == 700


def test_watermark_absent_backend_degrades_by_name(monkeypatch):
    """No memory_stats (CPU PJRT): None/0, never fabricated zeros-as-data."""
    monkeypatch.setattr(memprof, "hbm_stats", lambda: None)
    wm = memprof.Watermark()
    wm.mark()  # no-op, must not raise
    assert wm.read() is None
    assert wm.peak_bytes() == 0
    assert wm.delta_bytes() is None


def test_hbm_stats_on_cpu_is_absent_not_zero():
    # the real backend in CI is CPU PJRT: the contract is None, not a
    # list of zero rows some gauge would happily average
    assert memprof.hbm_stats() is None


# ---------------------------------------------------------------------------
# OOM detection
# ---------------------------------------------------------------------------


def test_is_resource_exhausted_matches_the_oom_shapes():
    assert memprof.is_resource_exhausted(
        RuntimeError("RESOURCE_EXHAUSTED: chaos-injected out of memory")
    )
    assert memprof.is_resource_exhausted(
        RuntimeError("Resource exhausted: Out of memory allocating "
                     "16106127360 bytes")
    )
    assert memprof.is_resource_exhausted(
        RuntimeError("Allocation failure: hbm allocator ran dry")
    )
    assert memprof.is_resource_exhausted(MemoryError())
    assert not memprof.is_resource_exhausted(ValueError("bad shape"))
    assert not memprof.is_resource_exhausted(RuntimeError("nan loss"))


# ---------------------------------------------------------------------------
# MemoryMonitor: log-cadence windows + the named CPU skip
# ---------------------------------------------------------------------------


def test_memory_monitor_emits_windows_with_per_window_deltas(
    monkeypatch, capsys
):
    seq = [
        [{"device": 0, "bytes_in_use": 100, "peak_bytes_in_use": 500,
          "bytes_limit": 1000}],
        [{"device": 0, "bytes_in_use": 150, "peak_bytes_in_use": 800,
          "bytes_limit": 1000}],
        [{"device": 0, "bytes_in_use": 150, "peak_bytes_in_use": 800,
          "bytes_limit": 1000}],  # re-mark read inside sample 1
        [{"device": 0, "bytes_in_use": 120, "peak_bytes_in_use": 800,
          "bytes_limit": 1000}],
        [{"device": 0, "bytes_in_use": 120, "peak_bytes_in_use": 800,
          "bytes_limit": 1000}],
    ]
    monkeypatch.setattr(memprof, "hbm_stats", lambda: seq.pop(0))
    mon = memprof.MemoryMonitor()
    mon.watermark.mark()
    r1 = mon.sample(2)
    r2 = mon.sample(4)
    assert r1["event"] == "memory_window" and r1["step"] == 2
    assert r1["watermark_delta_bytes"] == 300
    # the monitor re-marks after each window: a flat second window reads 0
    assert r2["watermark_delta_bytes"] == 0
    assert [h["step"] for h in mon.history] == [2, 4]
    events = _json_lines(capsys.readouterr().out)
    kinds = [e["event"] for e in events if "event" in e]
    assert kinds.count("memory_window") == 2


def test_memory_monitor_cpu_skip_is_named_and_once_only(capsys):
    mon = memprof.MemoryMonitor()
    assert mon.sample(2) is None
    assert mon.sample(4) is None
    events = _json_lines(capsys.readouterr().out)
    skips = [e for e in events if e.get("event") == "memory_window_skipped"]
    assert len(skips) == 1
    assert "static-only" in skips[0]["reason"]
    assert list(mon.history) == []


# ---------------------------------------------------------------------------
# the serving account: same scheme, same fit fields
# ---------------------------------------------------------------------------


def test_serving_account_buckets_and_fit_verdict():
    acct = memprof.serving_account(
        params_bytes=4 * memprof.GIB, kv_cache_bytes=2 * memprof.GIB,
        hbm_budget_gib=8.0,
    )
    assert set(acct["buckets_bytes"]) == set(memprof.BUCKETS)
    assert acct["buckets_bytes"]["params"] == 4 * memprof.GIB
    assert acct["buckets_bytes"]["kv_cache"] == 2 * memprof.GIB
    assert acct["fits_budget"] and acct["hbm_headroom_gib"] == 2.0
    over = memprof.serving_account(
        params_bytes=7 * memprof.GIB, kv_cache_bytes=2 * memprof.GIB,
        hbm_budget_gib=8.0,
    )
    assert not over["fits_budget"] and over["hbm_headroom_gib"] < 0


# ---------------------------------------------------------------------------
# THE tentpole pin: the compiled fsdp=8 account is additive and exactly
# shares the audit's analytic state-byte arithmetic
# ---------------------------------------------------------------------------


def test_static_account_is_additive_and_matches_audit_exactly():
    from distributed_llms_example_tpu.utils.memory_audit import (
        audit_train_step_memory,
    )

    mesh = build_mesh(MeshConfig(fsdp=8))
    acct = memprof.static_memory_account(
        "t5-test", mesh, global_batch=8, src_len=64, tgt_len=16,
    )
    # additivity: buckets sum to the XLA peak within 5% (donation
    # credited — outputs enter only net of aliased bytes)
    peak = acct["peak_bytes"]
    assert peak > 0
    assert abs(acct["bucket_total_bytes"] - peak) <= 0.05 * peak
    assert abs(acct["additivity_gap_bytes"]) <= 0.05 * peak
    # donation really was credited: the raw output bytes alone exceed
    # what the 'other' bucket absorbed
    view = acct["compiled"]
    assert view["aliased_bytes"] > 0
    assert acct["buckets_bytes"]["other"] < view["output_bytes"]
    # EXACT equality with the audit's analytic per-bucket state bytes:
    # same function, same numbers — not approximately, not rounded
    audit = audit_train_step_memory(
        "t5-test", mesh_config=MeshConfig(fsdp=8),
        global_batch=8, src_len=64, tgt_len=16,
    )
    sb = audit["analytic_state_bucket_bytes"]
    assert acct["buckets_bytes"]["params"] == sb["params"]
    assert acct["buckets_bytes"]["optimizer_state"] == sb["optimizer_state"]
    assert acct["buckets_bytes"]["grad_accum"] == sb.get("grad_accum", 0)
    assert audit["analytic_state_bytes"] == sum(sb.values())
    # the largest-buffers listing names real sharded state leaves
    top = acct["largest_buffers"]
    assert top and all(r["bytes"] > 0 for r in top)
    assert any("embedding" in r["name"] for r in top)
    # fsdp=8 shards the big leaves: shard bytes < replicated bytes
    import numpy as np

    biggest = top[0]
    assert (
        int(np.prod(biggest["shard_shape"]))
        < int(np.prod(biggest["shape"]))
        or biggest["shape"] == biggest["shard_shape"]  # tiny leaves stay whole
    )
    # the grad_accum bucket (TrainState.ef error-feedback) exists even
    # when EF is absent — 0, not missing (absent beats zero is for
    # MEASUREMENTS; the scheme itself is total)
    assert acct["buckets_bytes"]["grad_accum"] == 0  # no EF without int8


# ---------------------------------------------------------------------------
# postmortem bundles: atomic, parseable, schema-stamped
# ---------------------------------------------------------------------------


def test_dump_postmortem_atomic_and_parseable(tmp_path, capsys):
    acct = memprof.serving_account(
        params_bytes=123, kv_cache_bytes=456, hbm_budget_gib=1.0,
    )
    path = memprof.dump_postmortem(
        str(tmp_path),
        reason="RuntimeError: RESOURCE_EXHAUSTED: injected",
        step=7,
        account=acct,
        watermark_history=[{"step": 5, "bytes_in_use": 9}],
    )
    assert path == os.path.join(str(tmp_path), "obs",
                                "memory-postmortem-p000.json")
    # atomic discipline: the tmp staging file is gone, the bundle parses
    assert not os.path.exists(path + ".tmp")
    bundle = json.load(open(path))
    assert bundle["schema_version"] == sink_mod.SCHEMA_VERSION
    assert bundle["event"] == "memory_postmortem"
    assert bundle["step"] == 7 and "RESOURCE_EXHAUSTED" in bundle["reason"]
    assert bundle["account"]["buckets_bytes"]["params"] == 123
    assert bundle["watermark_history"] == [{"step": 5, "bytes_in_use": 9}]
    events = _json_lines(capsys.readouterr().out)
    ann = [e for e in events if e.get("event") == "memory_postmortem"]
    assert len(ann) == 1 and ann[0]["path"] == path


def test_maybe_dump_postmortem_fires_only_on_oom(tmp_path):
    mon = memprof.MemoryMonitor()
    assert mon.maybe_dump_postmortem(
        str(tmp_path), step=3, error=ValueError("not an oom"),
    ) is None
    assert glob.glob(str(tmp_path / "obs" / "memory-postmortem-*")) == []
    path = mon.maybe_dump_postmortem(
        str(tmp_path), step=3,
        error=RuntimeError("RESOURCE_EXHAUSTED: out of memory"),
    )
    assert path is not None and os.path.exists(path)


def test_dump_postmortem_io_failure_never_raises(tmp_path, capsys):
    """Telemetry never takes down the run: an unwritable output dir is a
    named failure event, not an exception on the crash path."""
    blocker = tmp_path / "obs"
    blocker.write_text("a file where the obs dir should be")
    path = memprof.dump_postmortem(
        str(tmp_path), reason="RESOURCE_EXHAUSTED", step=1,
    )
    assert path is None
    events = _json_lines(capsys.readouterr().out)
    assert any(e.get("event") == "memory_postmortem_failed" for e in events)


# ---------------------------------------------------------------------------
# report: "Where did the bytes go" from the JSONL/bundle files alone
# ---------------------------------------------------------------------------


def _write_jsonl(tmp_path, records):
    obs_dir = tmp_path / "obs"
    os.makedirs(obs_dir, exist_ok=True)
    with open(obs_dir / "metrics-p000.jsonl", "w") as f:
        for r in records:
            f.write(json.dumps({"schema_version": 1, **r}) + "\n")
    return str(tmp_path)


def _account_event(**over):
    acct = memprof.serving_account(
        params_bytes=4 * memprof.GIB, kv_cache_bytes=0, hbm_budget_gib=16.0,
    )
    acct["buckets_bytes"]["activations"] = memprof.GIB
    acct.update(
        event="memory_account", model="t5-test", mesh={"fsdp": 8},
        backend="tpu", additivity_gap_bytes=0, largest_buffers=[
            {"name": ".params['shared']['embedding']", "shape": [256, 64],
             "shard_shape": [32, 64], "dtype": "float32", "bytes": 8192,
             "module": "embed"},
        ],
    )
    acct.update(over)
    return acct


def test_report_memory_section_round_trips_from_jsonl(tmp_path):
    d = _write_jsonl(tmp_path, [
        {"step": 1, "loss": 2.0},
        _account_event(),
        {"event": "memory_window", "step": 2, "bytes_in_use": 5 * memprof.GIB,
         "peak_bytes_in_use": 6 * memprof.GIB, "watermark_delta_bytes": 0,
         "bytes_limit": 16 * memprof.GIB, "devices": 8},
        {"event": "memory_window", "step": 4, "bytes_in_use": 5 * memprof.GIB,
         "peak_bytes_in_use": 7 * memprof.GIB,
         "watermark_delta_bytes": memprof.GIB,
         "bytes_limit": 16 * memprof.GIB, "devices": 8},
    ])
    rep = build_report(d)
    mem = rep["memory"]
    assert mem["account"]["peak_bytes"] == 4 * memprof.GIB
    assert mem["runtime"]["windows"] == 2
    assert mem["runtime"]["peak_bytes_in_use"] == 7 * memprof.GIB
    assert mem["runtime"]["max_watermark_delta_bytes"] == memprof.GIB
    # a runtime sample outranks the static account as THE measured peak
    assert mem["measured_peak_bytes"] == 7 * memprof.GIB
    assert mem["measured_peak_source"] == "memory_window"
    assert not mem["static_only"]
    md = render_markdown(rep)
    assert "## Where did the bytes go" in md
    assert "| params |" in md and "share of peak" in md
    assert ".params['shared']['embedding']" in md


def test_report_memory_static_only_names_the_skip(tmp_path):
    d = _write_jsonl(tmp_path, [
        _account_event(),
        {"event": "memory_window_skipped", "step": 2,
         "reason": "backend reports no memory_stats (CPU PJRT) — memory "
                   "account degrades to static-only"},
    ])
    rep = build_report(d)
    mem = rep["memory"]
    assert mem["static_only"] and mem["runtime"] is None
    assert mem["measured_peak_source"] == "static_account"
    assert "static-only" in render_markdown(rep)


def test_report_renders_over_budget_account(tmp_path):
    acct = _account_event()
    acct.update(
        peak_bytes=20 * memprof.GIB, peak_gib=20.0, fits_budget=False,
        hbm_headroom_gib=-4.0, peak_frac_of_budget=1.25,
    )
    d = _write_jsonl(tmp_path, [acct])
    md = render_markdown(build_report(d))
    assert "OVER BUDGET" in md


def test_report_memory_section_absent_without_events(tmp_path):
    d = _write_jsonl(tmp_path, [{"step": 1, "loss": 1.0}])
    rep = build_report(d)
    assert rep["memory"] is None
    assert "Where did the bytes go" not in render_markdown(rep)


def test_report_surfaces_postmortem_bundles(tmp_path):
    d = _write_jsonl(tmp_path, [_account_event()])
    memprof.dump_postmortem(
        d, reason="RuntimeError: RESOURCE_EXHAUSTED: injected", step=9,
        account=_account_event(),
        watermark_history=[{"step": 8, "bytes_in_use": 1}],
    )
    rep = build_report(d)
    pm = rep["memory"]["postmortems"]
    assert pm["0"]["step"] == 9 and pm["0"]["has_account"]
    assert pm["0"]["watermark_samples"] == 1
    assert "OOM postmortem" in render_markdown(rep)


def test_report_rejects_torn_postmortem_as_error(tmp_path):
    d = _write_jsonl(tmp_path, [{"step": 1, "loss": 1.0}])
    obs_dir = os.path.join(d, "obs")
    with open(os.path.join(obs_dir, "memory-postmortem-p000.json"), "w") as f:
        f.write('{"schema_version": 1, "truncated')
    rep = build_report(d)
    assert any("memory-postmortem" in e for e in rep["schema_errors"])


# ---------------------------------------------------------------------------
# strict gates: both directions, and missing-measurement fails
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "gates, rc, says",
    [
        # peak_frac_of_budget = 5/16 GiB ≈ 0.3125 (params 4 GiB + act 1 GiB)
        (("--max-peak-hbm-frac", "0.9", "--min-hbm-headroom-gib", "1.0"), 0, ""),
        (("--max-peak-hbm-frac", "0.2"), 1, "exceeds"),
        (("--min-hbm-headroom-gib", "14.0"), 1, "below the"),
        # both gates in the one command, one of them missed
        (("--max-peak-hbm-frac", "0.85", "--min-hbm-headroom-gib", "14.0"), 1,
         "below the"),
    ],
    ids=["both-pass", "peak-over", "headroom-under", "both-one-fails"],
)
def test_strict_memory_gates_pass_and_fail(tmp_path, capsys, gates, rc, says):
    d = _write_jsonl(tmp_path, [{"step": 1, "loss": 1.0}, _account_event()])
    assert report_main([d, "--strict", *gates, "--json"]) == rc
    assert says in capsys.readouterr().err


def test_strict_memory_gates_fail_without_measurement(tmp_path, capsys):
    """THE acceptance pin: --max-peak-hbm-frac on a run with no memory
    measurement fails — a missing measurement must never read as a
    pass."""
    d = _write_jsonl(tmp_path, [{"step": 1, "loss": 1.0}])
    assert report_main([d, "--strict", "--json"]) == 0  # clean sans gate
    assert report_main(
        [d, "--strict", "--max-peak-hbm-frac", "0.9", "--json"]
    ) == 1
    assert "no memory measurement" in capsys.readouterr().err
    assert report_main(
        [d, "--strict", "--min-hbm-headroom-gib", "1.0", "--json"]
    ) == 1
    assert "no memory account" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# lint --memory: the account as findings, skips by name
# ---------------------------------------------------------------------------


def test_lint_memory_pass_emits_account_and_over_budget():
    """ONE compile exercises both faces: the info ``memory-account``
    finding always lands, and a budget the step cannot fit turns into
    an error ``memory-over-budget`` (the fits_budget=True face is
    pinned on the account itself in the additivity test above)."""
    from distributed_llms_example_tpu.analysis.lint import run_passes

    findings = run_passes(
        model="t5-test", mesh_cfg=MeshConfig(fsdp=8),
        global_batch=8, src_len=64, tgt_len=16,
        memory=True, hbm_budget_gib=0.001,  # ~1 MiB: anything overflows
    )
    acct = [f for f in findings if f.code == "memory-account"]
    assert len(acct) == 1 and acct[0].severity == "info"
    assert not acct[0].context["fits_budget"]
    assert set(acct[0].context["buckets_bytes"]) == set(memprof.BUCKETS)
    over = [f for f in findings if f.code == "memory-over-budget"]
    assert len(over) == 1 and over[0].severity == "error"
    assert "exceeds" in over[0].message


def test_lint_memory_skip_is_named_when_ir_cannot_compile():
    from distributed_llms_example_tpu.analysis.lint import run_passes

    findings = run_passes(
        model="t5-test", mesh_cfg=MeshConfig(fsdp=8),
        run_ir=False, memory=True,
    )
    skips = [f for f in findings if f.code == "memory-account-skipped"]
    assert len(skips) == 1
    assert not [f for f in findings if f.code == "memory-account"]


# ---------------------------------------------------------------------------
# the e2e kill path: chaos oom@K through the real Trainer
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_chaos_oom_e2e_dumps_postmortem_and_reraises(tmp_path):
    import numpy as np

    from distributed_llms_example_tpu.core.config import (
        CheckpointConfig,
        TrainConfig,
    )
    from distributed_llms_example_tpu.train.trainer import Trainer

    rng = np.random.RandomState(0)
    recs = [
        {"dialogue": " ".join(f"w{rng.randint(40)}" for _ in range(12)),
         "summary": f"w{rng.randint(40)}"}
        for _ in range(16)
    ]
    cfg = TrainConfig(
        model_ckpt="t5-test", output_dir=str(tmp_path), batch_size=8,
        num_epochs=1, warmup_steps=1, evaluation_steps=0,
        max_source_length=32, max_target_length=16, pad_to_multiple=32,
        log_every_steps=1, num_beams=1, tokenizer="byte",
        mesh=MeshConfig(data=-1),
        checkpoint=CheckpointConfig(save_every_steps=0, resume=False,
                                    async_save=False),
        obs="jsonl", obs_gauges="on", health="on", chaos="oom@2",
    )
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        Trainer(cfg, train_records=recs).train()
    # the bundle landed atomically and parses
    paths = glob.glob(str(tmp_path / "obs" / "memory-postmortem-p*.json"))
    assert len(paths) == 1
    bundle = json.load(open(paths[0]))
    assert bundle["event"] == "memory_postmortem"
    assert "RESOURCE_EXHAUSTED" in bundle["reason"]
    # the startup account was attached to the bundle (obs_gauges on)
    assert bundle["account"] is not None
    assert bundle["account"]["buckets_bytes"]["params"] > 0
    # the report renders the whole story from the files alone
    rep = build_report(str(tmp_path))
    mem = rep["memory"]
    assert mem["account"]["additivity_gap_bytes"] == 0
    assert mem["postmortems"]["0"]["has_account"]
    md = render_markdown(rep)
    assert "Where did the bytes go" in md and "OOM postmortem" in md
    # and the gates run off it
    assert report_main(
        [str(tmp_path), "--strict", "--max-peak-hbm-frac", "0.9", "--json"]
    ) == 0
