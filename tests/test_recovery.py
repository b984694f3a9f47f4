"""Fault-tolerant training (ISSUE 6).

Acceptance pins: the checkpoint-integrity layer (checksum-manifest
sidecars, save retry-with-backoff, verify-before-restore with fallback
to the previous retained step); the chaos grammar and its one-shot
injection semantics; the recovery controller's escalation (rewind →
skip-batch → halt, quarantine by batch-plan position); parse-time config
validation of the rewind prerequisites; the data loader's
transient-retry + malformed-record skip; the chaos e2e runs on the CPU
mesh (``nan_grad@3 --on-anomaly rewind`` finishes with exactly one
rewind + one quarantine and a bit-exact post-rewind trajectory vs a
clean run that skipped the quarantined batch; ``ckpt_corrupt@2`` resumes
from the previous verified step instead of crashing); and the
``obs.report`` recovery timeline with the injected/organic split
``--strict`` gates on.

The 2-process pod-agreed-rewind leg rides the slow tier next to
tests/test_multiprocess.py.
"""

from __future__ import annotations

import argparse
import json
import os

import jax
import numpy as np
import pytest

from distributed_llms_example_tpu.core.config import (
    CheckpointConfig,
    MeshConfig,
    TrainConfig,
    add_tpu_args,
    config_from_args,
)
from distributed_llms_example_tpu.io.checkpoint import Checkpointer, abstract_like
from distributed_llms_example_tpu.obs import sink as sink_mod
from distributed_llms_example_tpu.obs.chaos import (
    ChaosSchedule,
    corrupt_checkpoint,
    parse_chaos,
)
from distributed_llms_example_tpu.obs.report import build_report, render_markdown
from distributed_llms_example_tpu.train.recovery import RecoveryController


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
# chaos grammar + one-shot injection semantics
# ---------------------------------------------------------------------------

def test_parse_chaos_grammar(capsys):
    s = parse_chaos("nan_grad@120,ckpt_corrupt@2,data_error@300,sigterm@240")
    assert s.armed_at("nan_grad") == [120]
    assert s.armed_at("ckpt_corrupt") == [2]
    assert s.armed_at("data_error") == [300]
    assert s.armed_at("sigterm") == [240]
    assert not parse_chaos("")  # empty = off
    assert not parse_chaos("   ")
    for bad in ("nan_grad", "nan_grad@", "nan_grad@0", "nan_grad@-3",
                "nan_grad@x", "bogus@5", "@5", "nan_grad@5,"):
        with pytest.raises(ValueError, match="kind@tick"):
            parse_chaos(bad)


def test_chaos_take_is_one_shot(capsys):
    s = parse_chaos("nan_grad@3,nan_grad@7")
    assert not s.take("nan_grad", 2)      # wrong tick
    assert not s.take("ckpt_corrupt", 3)  # wrong kind
    assert s.take("nan_grad", 3)          # fires exactly once...
    assert not s.take("nan_grad", 3)      # ...a rewind replay cannot re-fire
    assert s.armed_at("nan_grad") == [7]  # the other injection stays armed
    # disarm drops UNFIRED injections only (fired ones stay for the record)
    s.disarm("nan_grad")
    assert s.armed_at("nan_grad") == []
    assert not s.take("nan_grad", 7)
    s.arm("nan_grad", 7)
    assert s.take("nan_grad", 7)
    events = _json_lines(capsys.readouterr().out)
    fired = [e for e in events if e.get("event") == "chaos_injection"]
    assert [(e["kind"], e["step"]) for e in fired] == [("nan_grad", 3), ("nan_grad", 7)]


def test_corrupt_checkpoint_flips_the_largest_file(tmp_path, capsys):
    d = tmp_path / "step"
    os.makedirs(d)
    (d / "small.bin").write_bytes(b"x" * 64)
    (d / "large.bin").write_bytes(b"y" * 4096)
    before = (d / "large.bin").read_bytes()
    path = corrupt_checkpoint(str(d))
    assert path == str(d / "large.bin")
    assert (d / "large.bin").read_bytes() != before
    assert (d / "small.bin").read_bytes() == b"x" * 64
    assert os.path.getsize(path) == 4096  # flipped in place, not truncated
    events = _json_lines(capsys.readouterr().out)
    assert any(e.get("event") == "chaos_ckpt_corrupted" for e in events)
    # an empty/missing step dir corrupts nothing and does not raise
    assert corrupt_checkpoint(str(tmp_path / "empty")) is None


# ---------------------------------------------------------------------------
# parse-time config validation of the rewind prerequisites
# ---------------------------------------------------------------------------

def _cfg_from_cli(*argv: str) -> TrainConfig:
    p = argparse.ArgumentParser()
    add_tpu_args(p)
    return config_from_args(p.parse_args(list(argv)))


def test_config_rewind_requires_periodic_checkpointing():
    with pytest.raises(ValueError, match="--save-every-steps"):
        _cfg_from_cli("--on-anomaly", "rewind")
    with pytest.raises(ValueError, match="--recorder-steps"):
        _cfg_from_cli("--on-anomaly", "rewind", "--save-every-steps", "50",
                      "--recorder-steps", "0")
    cfg = _cfg_from_cli("--on-anomaly", "rewind", "--save-every-steps", "50",
                        "--max-rewinds", "3", "--chaos", "nan_grad@120")
    assert cfg.on_anomaly == "rewind" and cfg.max_rewinds == 3
    assert cfg.chaos == "nan_grad@120"
    with pytest.raises(ValueError, match="--max-rewinds"):
        _cfg_from_cli("--max-rewinds", "-1")
    # chaos grammar errors surface at parse time, not mid-run
    with pytest.raises(ValueError, match="kind@tick"):
        _cfg_from_cli("--chaos", "nan_grad@oops")


# ---------------------------------------------------------------------------
# checkpoint integrity: manifest sidecar, verify, fallback, save retry
# ---------------------------------------------------------------------------

def _tiny_state() -> dict:
    return {
        "w": np.arange(64, dtype=np.float32).reshape(8, 8),
        "b": np.ones((8,), np.float32),
        "step": np.zeros((), np.int32),
    }


def test_manifest_written_and_verifies(tmp_path):
    ck = Checkpointer(str(tmp_path), save_every_steps=1, async_save=False)
    ck.save(1, _tiny_state())
    ck.wait()
    assert os.path.exists(ck.manifest_path(1))
    manifest = json.load(open(ck.manifest_path(1)))
    assert manifest["step"] == 1 and manifest["files"]
    assert all(
        set(meta) == {"crc32", "size"} for meta in manifest["files"].values()
    )
    assert ck.verify(1) is None  # clean
    # corruption is caught by the manifest, named to the file
    corrupt_checkpoint(ck.step_dir(1))
    problem = ck.verify(1)
    assert problem is not None and "crc32" in problem
    ck.close()


def test_restore_falls_back_to_previous_verified_step(tmp_path, capsys):
    ck = Checkpointer(str(tmp_path), save_every_steps=1, keep=3, async_save=False)
    state = _tiny_state()
    for step in (1, 2):
        state = {**state, "step": np.asarray(step, np.int32)}
        ck.save(step, state)
    ck.wait()
    corrupt_checkpoint(ck.step_dir(2))  # the NEWEST step is torn
    restored = ck.restore_latest(abstract_like(_tiny_state()))
    assert restored is not None
    got, step = restored
    assert step == 1  # fell back instead of crashing
    assert int(got["step"]) == 1
    np.testing.assert_array_equal(got["w"], _tiny_state()["w"])
    events = _json_lines(capsys.readouterr().out)
    bad = [e for e in events if e.get("event") == "ckpt_verify_failed"]
    assert bad and bad[0]["step"] == 2
    # restore_before excludes the anomaly step itself even when clean
    assert ck.restore_before(2, abstract_like(_tiny_state()))[1] == 1
    # every retained step corrupt → None, not an exception
    corrupt_checkpoint(ck.step_dir(1))
    assert ck.restore_latest(abstract_like(_tiny_state())) is None
    ck.close()


def test_delete_after_drops_newer_steps_and_manifests(tmp_path, capsys):
    """The rewind path deletes checkpoints newer than the restore target:
    a checkpoint saved between anomaly and detection holds semantically
    poisoned state that CHECKSUMS CLEAN, and save() refuses existing
    steps, so without deletion the replay could never refresh it."""
    ck = Checkpointer(str(tmp_path), save_every_steps=1, keep=5, async_save=False)
    for step in (1, 2, 3):
        ck.save(step, _tiny_state())
    ck.wait()
    assert ck.delete_after(1) == [2, 3]
    assert ck.all_steps() == [1]
    assert not os.path.exists(ck.manifest_path(2))
    assert not os.path.exists(ck.manifest_path(3))
    assert os.path.exists(ck.manifest_path(1))
    events = _json_lines(capsys.readouterr().out)
    assert any(
        e.get("event") == "ckpt_deleted_after_rewind" and e["steps"] == [2, 3]
        for e in events
    )
    # the replay can now RE-SAVE the dropped steps from recovered state
    assert ck.save(2, _tiny_state())
    ck.wait()
    assert ck.verify(2) is None
    # nothing newer than the target → no-op
    assert ck.delete_after(10) == []
    ck.close()


def test_manifest_never_authored_for_foreign_steps(tmp_path):
    """Only the instance that SAVED a step may write its manifest: a
    restore-time instance checksumming pre-existing (possibly corrupt)
    files would baptize the corruption as verified."""
    ck1 = Checkpointer(str(tmp_path), save_every_steps=1, async_save=False)
    ck1.save(1, _tiny_state())
    ck1.close()
    os.remove(ck1.manifest_path(1))  # simulate a legacy pre-manifest step
    ck2 = Checkpointer(str(tmp_path), save_every_steps=1, async_save=False)
    restored = ck2.restore_latest(abstract_like(_tiny_state()))
    assert restored is not None and restored[1] == 1  # legacy: accepted...
    assert not os.path.exists(ck2.manifest_path(1))   # ...but never baptized
    assert ck2.verify(1) is None  # missing sidecar = legacy, not corruption
    ck2.close()


def test_save_retries_with_backoff_on_transient_io(tmp_path, capsys, monkeypatch):
    ck = Checkpointer(
        str(tmp_path), save_every_steps=1, async_save=False,
        save_retries=3, retry_backoff_s=0.01,
    )
    real_save = ck.manager.save
    calls = {"n": 0}

    def flaky(step, **kw):
        calls["n"] += 1
        if calls["n"] <= 2:
            raise OSError("transient: storage mount flapped")
        return real_save(step, **kw)

    monkeypatch.setattr(ck.manager, "save", flaky)
    assert ck.save(1, _tiny_state())
    assert calls["n"] == 3
    retries = [
        e for e in _json_lines(capsys.readouterr().out)
        if e.get("event") == "ckpt_save_retry"
    ]
    assert [r["attempt"] for r in retries] == [1, 2]
    assert retries[1]["backoff_s"] > retries[0]["backoff_s"]  # exponential
    # a PERSISTENT failure still propagates once the budget is spent
    calls["n"] = -100
    monkeypatch.setattr(
        ck.manager, "save",
        lambda step, **kw: (_ for _ in ()).throw(OSError("dead mount")),
    )
    with pytest.raises(OSError, match="dead mount"):
        ck.save(2, _tiny_state())
    ck.close()


# ---------------------------------------------------------------------------
# recovery controller: escalation order, quarantine, pod-determinism
# ---------------------------------------------------------------------------

def _fp(epoch=1, epoch_step=0, crc=1234):
    return {"epoch": epoch, "epoch_step": epoch_step, "input_ids_crc32": crc}


def test_escalation_rewind_then_skip_then_halt(capsys):
    rc = RecoveryController(max_rewinds=2)
    spike = {"step": 10, "code": "loss_spike"}
    d1 = rc.decide(spike, fingerprint=_fp(epoch_step=0))
    d2 = rc.decide(spike, fingerprint=_fp(epoch_step=1))
    assert (d1.action, d2.action) == ("rewind", "rewind")
    # budget exhausted + finite state → ONE degraded skip-batch try
    d3 = rc.decide(spike, fingerprint=_fp(epoch_step=2))
    assert d3.action == "skip_batch"
    d4 = rc.decide(spike, fingerprint=_fp(epoch_step=3))
    assert d4.action == "halt"


def test_escalation_nonfinite_never_skips():
    """NaN state cannot 'continue without restore': skip-batch is only
    for finite anomalies (spike/explosion)."""
    rc = RecoveryController(max_rewinds=0)
    d = rc.decide({"step": 5, "code": "nonfinite"}, fingerprint=_fp())
    assert d.action == "halt"


def test_escalation_halts_on_requarantined_batch(capsys):
    """An anomaly recurring at an already-quarantined plan position
    refutes the poison-batch hypothesis: halt, don't loop."""
    rc = RecoveryController(max_rewinds=5)
    rc.quarantine(1, 0, _fp(), reason="anomaly:loss_spike@10")
    d = rc.decide({"step": 10, "code": "loss_spike"}, fingerprint=_fp())
    assert d.action == "halt" and "quarantined" in d.reason
    assert rc.rewinds_done == 0  # the budget was not spent on a halt


def test_quarantine_skip_checks_crc(capsys):
    rc = RecoveryController()
    batch = {"input_ids": np.arange(8, dtype=np.int32)}
    import zlib

    crc = zlib.crc32(np.ascontiguousarray(batch["input_ids"]).tobytes()) & 0xFFFFFFFF
    rc.quarantine(0, 3, _fp(epoch=0, epoch_step=3, crc=crc), reason="test")
    assert not rc.should_skip(0, 2, batch)   # un-quarantined position
    assert rc.should_skip(0, 3, batch)       # quarantined, crc matches
    events = _json_lines(capsys.readouterr().out)
    assert any(e.get("event") == "quarantine" for e in events)
    assert any(e.get("event") == "quarantine_skip" for e in events)
    assert not any(e.get("event") == "quarantine_crc_mismatch" for e in events)
    # a drifted batch at the same position still skips — but loudly
    drifted = {"input_ids": np.arange(8, dtype=np.int32) + 1}
    assert rc.should_skip(0, 3, drifted)
    events = _json_lines(capsys.readouterr().out)
    assert any(e.get("event") == "quarantine_crc_mismatch" for e in events)


# ---------------------------------------------------------------------------
# data loader robustness: transient retry + malformed-record skip
# ---------------------------------------------------------------------------

def test_load_json_records_retries_transient_errors(tmp_path, capsys, monkeypatch):
    import distributed_llms_example_tpu.data.dataset as ds

    path = str(tmp_path / "train.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"dialogue": "a", "summary": "b"}) + "\n")
    real = ds._read_json_records
    calls = {"n": 0}

    def flaky(p):
        calls["n"] += 1
        if calls["n"] == 1:
            raise OSError("transient: NFS timed out")
        return real(p)

    monkeypatch.setattr(ds, "_read_json_records", flaky)
    recs = ds.load_json_records(path, backoff_s=0.01)
    assert len(recs) == 1 and calls["n"] == 2
    # PERMANENT errors fail fast — a typo'd path must not "retry"
    with pytest.raises(FileNotFoundError):
        ds.load_json_records(str(tmp_path / "nope.jsonl"))
    events = _json_lines(capsys.readouterr().out)
    retry = next(e for e in events if e.get("event") == "data_retry")
    assert retry["attempt"] == 1 and "NFS" in retry["error"]
    # persistent failure propagates after the budget
    monkeypatch.setattr(
        ds, "_read_json_records",
        lambda p: (_ for _ in ()).throw(OSError("gone")),
    )
    with pytest.raises(OSError, match="gone"):
        ds.load_json_records(path, retries=1, backoff_s=0.01)


def test_load_json_records_skips_malformed_lines(tmp_path, capsys):
    from distributed_llms_example_tpu.data.dataset import load_json_records

    path = str(tmp_path / "corrupt.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"dialogue": "a", "summary": "b"}) + "\n")
        f.write('{"dialogue": "torn mid-wri\n')        # torn line
        f.write("[1, 2, 3]\n")                          # not a record
        f.write(json.dumps({"dialogue": "c", "summary": "d"}) + "\n")
    recs = list(load_json_records(path))
    assert [r["dialogue"] for r in recs] == ["a", "c"]
    events = _json_lines(capsys.readouterr().out)
    skip = next(e for e in events if e.get("event") == "data_skipped_records")
    assert skip["skipped"] == 2 and skip["kept"] == 2
    # a file with NO parseable record is an error, not an empty epoch
    bad = str(tmp_path / "all_bad.jsonl")
    with open(bad, "w") as f:
        f.write('{"torn": \n{"also": \n')
    with pytest.raises(ValueError, match="no parseable"):
        load_json_records(bad)
    # pretty-printed single-document JSON still takes the whole-file path
    doc = str(tmp_path / "wrapper.json")
    with open(doc, "w") as f:
        f.write('{\n  "data": [\n    {"dialogue": "x", "summary": "y"}\n  ]\n}\n')
    assert list(load_json_records(doc)) == [{"dialogue": "x", "summary": "y"}]


def test_recovery_sidecar_round_trip(tmp_path, capsys):
    """The recovery sidecar persists the (epoch, pos) cursor and the
    quarantine set next to the checkpoint: after a quarantine skip the
    cursor drifts from ``step % steps_per_epoch``, so a cross-run resume
    without it would re-train one batch and shift the rest of the
    epoch."""
    from distributed_llms_example_tpu.train.trainer import Trainer

    from distributed_llms_example_tpu.core.mesh import build_mesh

    t = object.__new__(Trainer)
    t.checkpointer = Checkpointer(str(tmp_path), save_every_steps=1, async_save=False)
    t.recovery = RecoveryController()
    t.mesh = build_mesh(MeshConfig(data=-1))
    t.state = argparse.Namespace(ef=None)  # no error-feedback tree
    t._grad_workers = 1
    t.recovery.quarantine(1, 0, _fp(), reason="anomaly:nonfinite@3")
    Trainer._write_recovery_sidecar(t, 4, 2, 1)
    side = Trainer._load_recovery_sidecar(t, 4)
    assert (side["epoch"], side["pos"]) == (2, 1)
    assert side["quarantined"] == [[1, 0, t.recovery.quarantined[(1, 0)]]]
    # the sidecar names the saving topology (ISSUE 14): the resharding
    # restore's fail-fast pre-check reads it without touching orbax
    assert side["mesh_layout"]["axes"]["data"] == 8
    assert side["mesh_layout"]["processes"] == 1
    assert side["mesh_layout"]["ef_workers"] == 0
    assert Trainer._load_recovery_sidecar(t, 99) is None  # missing = None
    # GC'd with the step: deleting past step 0 drops step 4's sidecar
    t.checkpointer.save(4, _tiny_state())
    t.checkpointer.wait()
    t.checkpointer.delete_after(0)
    assert Trainer._load_recovery_sidecar(t, 4) is None
    t.checkpointer.close()


def test_trainer_data_retry_wrapper_and_chaos_injection(capsys):
    """The in-loop batch-fetch retry: a chaos ``data_error`` injection
    (one transient OSError) is retried away without losing a batch; a
    PERSISTENT error still propagates once the budget is spent."""
    from distributed_llms_example_tpu.train.trainer import Trainer

    t = object.__new__(Trainer)  # _with_data_retries touches chaos/_last_step
    t.chaos = parse_chaos("data_error@2")
    t._last_step = 1  # the next step is 2 → the injection fires on fetch
    batches = [{"a": 1}, {"a": 2}, {"a": 3}]
    assert list(Trainer._with_data_retries(t, batches)) == batches
    assert t.chaos.armed_at("data_error") == []  # fired exactly once
    events = _json_lines(capsys.readouterr().out)
    assert any(e.get("event") == "chaos_injection" for e in events)
    retry = next(e for e in events if e.get("event") == "data_retry")
    assert retry["attempt"] == 1 and "chaos" in retry["error"]

    class Dead:
        def __iter__(self):
            return self

        def __next__(self):
            raise OSError("mount gone")

    t2 = object.__new__(Trainer)
    t2.chaos = ChaosSchedule()
    t2._last_step = 0
    with pytest.raises(OSError, match="mount gone"):
        list(Trainer._with_data_retries(t2, Dead()))


# ---------------------------------------------------------------------------
# the chaos e2e acceptance runs (CPU mesh, in-process Trainer)
# ---------------------------------------------------------------------------

def _records(n=16, seed=0):
    rng = np.random.RandomState(seed)
    return [
        {
            "dialogue": " ".join(f"w{rng.randint(40)}" for _ in range(12)),
            "summary": f"w{rng.randint(40)}",
        }
        for _ in range(n)
    ]


def _run_cfg(out, **over) -> TrainConfig:
    kw = dict(
        model_ckpt="t5-test",
        output_dir=str(out),
        batch_size=8,
        num_epochs=3,
        warmup_steps=1,
        evaluation_steps=0,
        max_source_length=32,
        max_target_length=16,
        pad_to_multiple=32,
        log_every_steps=2,
        num_beams=1,
        tokenizer="byte",
        mesh=MeshConfig(data=-1),
        checkpoint=CheckpointConfig(save_every_steps=2, resume=False, async_save=False),
        obs="jsonl",
        obs_gauges="off",
        health="on",
        recorder_steps=8,
    )
    kw.update(over)
    return TrainConfig(**kw)


def _leaves(params):
    return [np.asarray(x) for x in jax.tree.leaves(jax.device_get(params))]


@pytest.mark.slow
def test_rewind_e2e_and_bit_exact_replay(tmp_path):
    """The acceptance run: ``--chaos nan_grad@3 --on-anomaly rewind``
    FINISHES training (does not halt), emits exactly one ``recovery``
    rewind + one ``quarantine`` event, loses ≤ save_every_steps optimizer
    steps, the final loss is finite — and the post-rewind trajectory
    bit-matches a clean run that skipped the quarantined batch.  Then
    ``obs.report`` renders the recovery timeline with a finite MTTR and
    ``--strict`` passes (the only faults are injected ones)."""
    from distributed_llms_example_tpu.obs import report as report_mod
    from distributed_llms_example_tpu.train.trainer import Trainer

    recs = _records()
    cfg = _run_cfg(tmp_path / "chaos", on_anomaly="rewind", chaos="nan_grad@3",
                   max_rewinds=2)
    trainer = Trainer(cfg, train_records=recs)
    trainer.save_final = lambda: None
    result = trainer.train()

    # the run FINISHED: no anomaly stop, one optimizer step lost to the
    # quarantined batch (6 planned − 1 skipped), final loss finite
    assert "anomaly" not in result
    assert result["steps"] == 5
    assert trainer.recovery.rewinds_done == 1
    # the poison batch was quarantined by plan position with its crc
    assert list(trainer.recovery.quarantined) == [(1, 0)]
    q = trainer.recovery.quarantined[(1, 0)]
    assert q["reason"] == "anomaly:nonfinite@3"
    assert q["input_ids_crc32"] is not None

    path = os.path.join(cfg.output_dir, "obs", "metrics-p000.jsonl")
    events = [json.loads(line) for line in open(path)]
    by = {}
    for e in events:
        by.setdefault(e.get("event"), []).append(e)
    # exactly one injection, one agreed anomaly, ONE rewind, ONE quarantine
    assert [(e["kind"], e["step"]) for e in by["chaos_injection"]] == [("nan_grad", 3)]
    assert len(by["obs_anomaly"]) == 1
    anomaly = by["obs_anomaly"][0]
    assert anomaly["step"] == 3 and anomaly["policy"] == "rewind"
    recovery = by["recovery"]
    assert len(recovery) == 1 and recovery[0]["action"] == "rewind"
    assert recovery[0]["restored_step"] == 2
    # detection at cadence step 4, restore to the step-2 checkpoint:
    # 2 steps lost ≤ save_every_steps
    assert recovery[0]["steps_lost"] == 2 <= cfg.checkpoint.save_every_steps
    assert recovery[0]["recovery_wall_s"] > 0
    assert len(by["quarantine"]) == 1
    assert (by["quarantine"][0]["epoch"], by["quarantine"][0]["epoch_step"]) == (1, 0)
    assert len(by["quarantine_skip"]) == 1  # the replay skipped it, once
    # final loss finite on the metric stream
    losses = [e["loss"] for e in events if "loss" in e and "step" in e]
    assert losses and np.isfinite(losses[-1])

    # obs.report: recovery timeline with a finite MTTR; --strict passes
    # because the one fault is injected
    report = build_report(cfg.output_dir)
    rec = report["recovery"]
    assert rec["rewinds"] == 1 and rec["steps_lost_total"] == 2
    assert rec["mttr_s"] is not None and rec["mttr_s"] > 0
    assert [i["kind"] for i in rec["injections"]] == ["nan_grad"]
    assert rec["organic_faults"] == []
    assert [f["injected"] for f in rec["faults"]] == [True]
    md = render_markdown(report)
    assert "Recovery timeline" in md and "rewind" in md
    assert "1 injected, 0 organic" in md
    assert report_mod.main([cfg.output_dir, "--strict"]) == 0

    # ---- the bit-exactness oracle: a clean run over the same data that
    # skips the quarantined batch from the start must land on the SAME
    # final parameters (same steps, same batches, same dropout stream)
    cfg2 = _run_cfg(tmp_path / "clean", on_anomaly="warn")
    clean = Trainer(cfg2, train_records=recs)
    clean.save_final = lambda: None
    clean.recovery.quarantine(1, 0, {}, reason="oracle")
    result2 = clean.train()
    assert result2["steps"] == 5
    for a, b in zip(_leaves(trainer.state.params), _leaves(clean.state.params)):
        np.testing.assert_array_equal(a, b)

    # ---- cross-run recovery state: a resumed Trainer over the chaos
    # run's dir restores the exact cursor AND the quarantine set from the
    # recovery sidecar (after the skip, pos drifted ahead of step % spe)
    cfg3 = _run_cfg(
        tmp_path / "chaos",
        on_anomaly="rewind", max_rewinds=2,
        checkpoint=CheckpointConfig(save_every_steps=2, resume=True, async_save=False),
    )
    resumed = Trainer(cfg3, train_records=recs)
    assert resumed.start_step == 6  # the final save
    assert resumed._resume_cursor == (3, 0)  # end-of-run cursor, exact
    assert (1, 0) in resumed.recovery.quarantined  # quarantine survived


@pytest.mark.slow
def test_ckpt_corrupt_chaos_resumes_from_previous_step(tmp_path):
    """``--chaos ckpt_corrupt@2``: the second checkpoint save is
    bit-flipped AFTER its manifest is finalized.  The next run's resume
    must fall back to the previous verified step instead of crashing —
    the exact failure mode that used to kill the resume."""
    from distributed_llms_example_tpu.train.trainer import Trainer

    recs = _records()
    out = tmp_path / "run"
    cfg = _run_cfg(out, num_epochs=2, chaos="ckpt_corrupt@2")
    trainer = Trainer(cfg, train_records=recs)
    trainer.save_final = lambda: None
    result = trainer.train()
    assert result["steps"] == 4  # 2 epochs × 2 steps, run unaffected
    # saves landed at steps 2 and 4; the SECOND (step 4, the newest) is
    # corrupt but carries a pre-corruption manifest
    assert trainer.checkpointer.all_steps() == [2, 4]
    assert trainer.checkpointer.verify(2) is None
    assert trainer.checkpointer.verify(4) is not None

    cfg2 = _run_cfg(
        out, num_epochs=2,
        checkpoint=CheckpointConfig(save_every_steps=2, resume=True, async_save=False),
    )
    resumed = Trainer(cfg2, train_records=recs)
    resumed.save_final = lambda: None
    assert resumed.start_step == 2  # fell back past the corrupt step 4
    result2 = resumed.train()
    assert result2["steps"] == 4  # ...and finished the remaining steps
    events = [
        json.loads(line)
        for line in open(os.path.join(str(out), "obs", "metrics-p000.jsonl"))
    ]
    verify_failed = [e for e in events if e.get("event") == "ckpt_verify_failed"]
    assert verify_failed and verify_failed[0]["step"] == 4
    assert any(
        e.get("event") == "resumed" and e["step"] == 2 for e in events
    )
    # the report classifies the integrity fault as INJECTED (the
    # chaos_ckpt_corrupted event from run 1 names step 4 on the same
    # stream) → strict-green
    report = build_report(str(out))
    assert [f for f in report["recovery"]["organic_faults"]] == []
    assert any(f["kind"] == "ckpt_integrity" for f in report["recovery"]["faults"])

    # EVERY retained step corrupt → resume refuses loudly instead of
    # silently training from step 0 (which would retention-delete the
    # possibly salvageable checkpoints)
    corrupt_checkpoint(resumed.checkpointer.step_dir(2))
    with pytest.raises(ValueError, match="integrity verification"):
        Trainer(cfg2, train_records=recs)


@pytest.mark.slow
def test_final_window_rewind_degrades_to_checkpoint(tmp_path):
    """An anomaly agreed only in the FINAL partial health window has no
    loop left to replay: --on-anomaly rewind must degrade to the
    checkpoint policy (resumable save + anomaly marker), never fall
    through to save_final() exporting poisoned params as a success."""
    from distributed_llms_example_tpu.train.trainer import Trainer

    exported = []
    cfg = _run_cfg(
        tmp_path, num_epochs=1, on_anomaly="rewind", chaos="nan_grad@2",
        log_every_steps=8,  # cadence never fires in-loop: finalize detects
    )
    trainer = Trainer(cfg, train_records=_records())
    trainer.save_final = lambda: exported.append(True)
    result = trainer.train()
    assert result.get("anomaly") == "checkpoint"
    assert exported == []  # no HF export of poisoned params
    events = [
        json.loads(line)
        for line in open(os.path.join(cfg.output_dir, "obs", "metrics-p000.jsonl"))
    ]
    assert any(e.get("event") == "obs_anomaly" and e["step"] == 2 for e in events)


# ---------------------------------------------------------------------------
# report: injected/organic split on hand-built streams
# ---------------------------------------------------------------------------

def _stamp(rec: dict) -> dict:
    return {"schema_version": 1, **rec}


def test_report_separates_injected_from_organic(tmp_path):
    from distributed_llms_example_tpu.obs import report as report_mod

    obs_dir = tmp_path / "obs"
    os.makedirs(obs_dir)
    recs = [
        _stamp({"event": "chaos_injection", "kind": "nan_grad", "step": 7}),
        _stamp({"event": "obs_anomaly", "step": 7, "detected_at_step": 8,
                "code": "nonfinite", "ranks": [0], "policy": "rewind"}),
        _stamp({"event": "recovery", "action": "rewind", "step": 7,
                "detected_at_step": 8, "code": "nonfinite",
                "restored_step": 4, "steps_lost": 4, "rewind_index": 1,
                "recovery_wall_s": 1.5, "reason": "rewind 1/2"}),
        _stamp({"event": "quarantine", "epoch": 0, "epoch_step": 6,
                "reason": "anomaly:nonfinite@7"}),
        # a SECOND rewind with the same (step, restored_step) but its own
        # rewind_index is a distinct recovery, not a per-rank copy
        _stamp({"event": "recovery", "action": "rewind", "step": 7,
                "detected_at_step": 8, "code": "nonfinite",
                "restored_step": 4, "steps_lost": 4, "rewind_index": 2,
                "recovery_wall_s": 2.5, "reason": "rewind 2/2"}),
        # ckpt integrity: step 12 was chaos-corrupted (injected), step 20
        # failed verification organically
        _stamp({"event": "chaos_injection", "kind": "ckpt_corrupt", "step": 2}),
        _stamp({"event": "chaos_ckpt_corrupted", "path": "/ck/12/d/x",
                "bytes_flipped": 64, "step": 12}),
        _stamp({"event": "ckpt_verify_failed", "step": 12, "detail": "crc32"}),
        _stamp({"event": "ckpt_verify_failed", "step": 20, "detail": "crc32"}),
        # ORGANIC: an anomaly at a step no injection explains
        _stamp({"event": "obs_anomaly", "step": 30, "detected_at_step": 30,
                "code": "loss_spike", "ranks": [1], "policy": "rewind"}),
    ]
    with open(obs_dir / "metrics-p000.jsonl", "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    # rank 1 carries duplicate copies of the local events — dedup to one row
    with open(obs_dir / "metrics-p001.jsonl", "w") as f:
        for r in recs[:4]:
            f.write(json.dumps(r) + "\n")
    report = build_report(str(tmp_path))
    rec = report["recovery"]
    assert len(rec["injections"]) == 2 and len(rec["actions"]) == 2
    assert len(rec["quarantines"]) == 1
    # the rank-1 duplicates collapsed; the rewind_index=2 row did not
    assert rec["rewinds"] == 2 and rec["steps_lost_total"] == 8
    assert rec["mttr_s"] == 2.0  # mean of 1.5 and 2.5
    kinds = {(f["kind"], f["step"], f["injected"]) for f in rec["faults"]}
    assert kinds == {
        ("anomaly:nonfinite", 7, True),
        ("anomaly:loss_spike", 30, False),
        # per-STEP match: only the chaos-corrupted step 12 is injected
        ("ckpt_integrity", 12, True),
        ("ckpt_integrity", 20, False),
    }
    assert len(rec["organic_faults"]) == 2
    md = render_markdown(report)
    assert "2 injected, 2 organic" in md and "**organic** anomaly:loss_spike" in md
    # --strict fails on the organic faults...
    assert report_mod.main([str(tmp_path), "--strict"]) == 1
    # ...and passes once only injected ones remain (incl. the injected
    # ckpt_integrity failure at the chaos-corrupted step)
    with open(obs_dir / "metrics-p000.jsonl", "w") as f:
        for r in recs[:8]:
            f.write(json.dumps(r) + "\n")
    os.remove(obs_dir / "metrics-p001.jsonl")
    assert report_mod.main([str(tmp_path), "--strict"]) == 0


# ---------------------------------------------------------------------------
# 2-process leg: pod-agreed rewind (both ranks restore the same step)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_two_process_pod_agreed_rewind(tmp_path):
    """Two real OS processes run the full CLI with ``nan_grad@3
    --on-anomaly rewind``: the anomaly is agreed over the heartbeat
    channel, BOTH ranks restore the same checkpoint step through orbax's
    collective restore, and both finish training."""
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    recs = _records(32, seed=1)
    train = str(tmp_path / "train.json")
    with open(train, "w") as f:
        json.dump(recs, f)
    out = str(tmp_path / "out")
    args = [
        sys.executable, "-m", "distributed_llms_example_tpu.launch.cli",
        "--model-ckpt", "t5-test", "--output-dir", out,
        "--train-file", train, "--batch-size", "8", "--num-epochs", "2",
        "--mesh", "data=2,fsdp=2,tensor=2", "--tokenizer", "byte",
        "--max-source-length", "32", "--max-target-length", "16",
        "--pad-to-multiple", "32", "--log-every-steps", "2",
        "--num-beams", "1", "--warmup-steps", "1",
        "--obs", "jsonl", "--health", "on", "--recorder-steps", "8",
        "--on-anomaly", "rewind", "--max-rewinds", "2",
        "--save-every-steps", "2", "--chaos", "nan_grad@3",
    ]
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", ""),
            "VH_MASTER_IP": f"127.0.0.1:{port}",
            "VH_WORLD_SIZE": "2",
            "VH_RANK": str(rank),
        })
        for k in ("MASTER_ADDR", "WORLD_SIZE", "RANK"):
            env.pop(k, None)
        procs.append(subprocess.Popen(
            args, env=env, cwd=repo,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        ))
    outs = [p.communicate(timeout=600) for p in procs]
    assert all(p.returncode == 0 for p in procs), (
        outs[0][1][-3000:] + outs[1][1][-3000:]
    )
    # BOTH ranks' streams carry the rewind, restored to the SAME step
    restored = []
    for rank in range(2):
        path = os.path.join(out, "obs", f"metrics-p{rank:03d}.jsonl")
        events = [json.loads(line) for line in open(path)]
        rew = [e for e in events if e.get("event") == "recovery"]
        assert len(rew) == 1 and rew[0]["action"] == "rewind", rew
        restored.append(rew[0]["restored_step"])
        assert any(e.get("event") == "quarantine" for e in events)
        assert any(e.get("event") == "chaos_injection" for e in events)
    assert restored[0] == restored[1] == 2
    # both ranks finished training after the rewind ("done", not
    # "anomaly_stop", on the p0 stdout channel)
    ev0 = _json_lines(outs[0][0])
    assert any(e.get("event") == "done" for e in ev0)
    assert not any(e.get("event") == "anomaly_stop" for e in ev0)
