"""The set-up account (``obs/setup.py``): phases as spans of the one primitive,
compile stages by program from jax's own events, ``setup_summary`` once at
ready, ``late_compile`` after it."""

from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from distributed_llms_example_tpu.obs import setup
from distributed_llms_example_tpu.obs.spans import SpanRecorder, open_spans
from tests.test_obs import FakeAnnotations, FakeClock

TRACE, LOWER, COMPILE = setup.STAGES


@pytest.fixture
def account(monkeypatch):
    """A fresh process-wide account whose events land in ``account.events``."""
    events = []
    acc = setup.SetupAccount(log=events.append)
    acc.events = events
    monkeypatch.setattr(setup, "ACCOUNT", acc)
    return acc


def fake_account():
    clock = FakeClock()
    notes = FakeAnnotations(clock)
    events = []
    acc = setup.SetupAccount(SpanRecorder(clock=clock, scope="setup", annotate=notes, totals=True), log=events.append)
    return acc, clock, notes, events


def stage(acc, event, name, seconds, inside=()):
    """One stage as jax reports it: a scalar on entry, the duration on exit."""
    acc.on_enter(event, 0.0, fun_name=name)
    for nested in inside:
        stage(acc, *nested)
    acc.on_exit(event, seconds, fun_name=name)


# ---- (a) phases nest; outermost + unattributed is the wall to ready


def test_phases_nest_and_sum_to_the_wall_to_ready():
    acc, clock, notes, events = fake_account()
    acc.awaited.add("train")
    with acc.spans.span("trainer_init"):
        clock.advance(0.5)  # nobody's
        with acc.spans.span("model_init"):
            clock.advance(2.0)
        with acc.spans.span("build_step"):
            clock.advance(0.25)
            with acc.spans.span("lint"):
                clock.advance(0.25)
    with acc.spans.span("first_step"):
        stage(acc, TRACE, "step_fn", 3.0)
        clock.advance(4.0)
    acc.ready("train")
    assert [e["name"] for e in notes.events] == [
        "setup/trainer_init", "setup/model_init", "setup/build_step", "setup/lint", "setup/first_step"]
    (summary,) = events
    assert summary["event"] == "setup_summary" and setup.SetupAccount.summarize(acc)["phases"] == summary["phases"]
    seconds = {p: v["s"] for p, v in summary["phases"].items()}
    assert seconds == {"trainer_init/model_init": 2.0, "trainer_init/build_step/lint": 0.25,
                       "trainer_init/build_step": 0.5, "trainer_init": 3.0, "first_step": 4.0}
    # of an outermost span: what neither a child nor a stage charged to the span itself covers
    assert summary["unattributed_s"] == {"trainer_init": 0.5, "first_step": 1.0}
    assert summary["phases"]["first_step"] == {"s": 4.0, "trace_s": 3.0, "lower_s": 0.0, "compile_or_load_s": 0.0}
    outermost = sum(s for p, s in seconds.items() if "/" not in p)
    assert outermost == clock() == 7.0  # the spans were back to back: their sum is the wall to ready
    for p, left in summary["unattributed_s"].items():
        children = sum(s for q, s in seconds.items() if q.rpartition("/")[0] == p)
        assert children + sum(summary["phases"][p].values()) - seconds[p] + left == pytest.approx(seconds[p])


def test_totals_need_no_step_complete_and_the_ring_is_what_it_was():
    clock = FakeClock()
    rec = SpanRecorder(clock=clock, scope="serve", annotate=FakeAnnotations(clock), totals=True)
    with rec.span("round"):
        assert open_spans()[-1] == "serve/round"
        with rec.span("emit"):
            assert open_spans()[-2:] == ["serve/round", "serve/emit"]
            clock.advance(0.5)
    with rec.span("round"):
        clock.advance(0.25)
    assert not open_spans()
    assert rec.totals() == {"round/emit": 0.5, "round": 0.75}
    rec.step_complete()
    assert rec.window_step_records()[0]["spans"] == {"round": 0.75}  # outermost, bare names, as before
    assert rec.totals() == {"round/emit": 0.5, "round": 0.75}


def test_ready_waits_for_everything_awaited_and_is_logged_once():
    acc, clock, _, events = fake_account()
    acc.awaited.update({"train", "serve"})
    acc.ready("train")
    assert events == [] and acc.summary is None
    acc.ready("serve")
    acc.ready("serve")  # a second session on the engine
    assert [e["event"] for e in events] == ["setup_summary"]


# ---- (b) synthetic listener calls


def test_a_nested_stage_is_not_added_to_its_caller():
    acc, clock, _, _ = fake_account()
    with acc.spans.span("session_open"):
        with acc.spans.span("warm"):
            stage(acc, TRACE, "serve_decode_step", 1.0,
                  inside=[(TRACE, "matmul", 0.25), (TRACE, "inner", 0.5, [(TRACE, "tanh", 0.125)]),
                          # an eager operation inside the trace: its little program is part of the trace
                          (LOWER, "jit(multiply)", 0.0625), (COMPILE, "jit(multiply)", 0.0625)])
            stage(acc, LOWER, "jit(serve_decode_step)", 2.0)
            stage(acc, COMPILE, "jit(serve_decode_step)", 4.0)
    assert set(acc.programs) == {"serve_decode_step"}  # ``jit(...)`` stripped: one row for the three stages
    row = acc.programs["serve_decode_step"]
    assert (row["n"], row["trace_s"], row["lower_s"], row["compile_or_load_s"]) == (1, 1.0, 2.0, 4.0)
    assert acc.stages == {"session_open/warm": {"trace_s": 1.0, "lower_s": 2.0, "compile_or_load_s": 4.0}}


def test_the_caches_events_land_on_the_program_whose_compile_holds_them():
    acc, clock, _, _ = fake_account()
    hits, misses, load_s, saved_s = setup.CACHE
    with acc.spans.span("first_step"):
        acc.on_exit(hits)  # outside any compile: nobody's
        acc.on_enter(COMPILE, 0.0, fun_name="jit(step_fn)")
        acc.on_exit(hits)
        acc.on_exit(saved_s, 200.0)
        acc.on_exit(load_s, 0.5)
        acc.on_exit(COMPILE, 0.75, fun_name="jit(step_fn)")
        acc.on_enter(COMPILE, 0.0, fun_name="jit(probe)")
        acc.on_exit(misses)
        acc.on_exit(COMPILE, 9.0, fun_name="jit(probe)")
        acc.on_exit(misses)  # after it closed: nobody's
        acc.on_exit("/jax/compilation_cache/compile_requests_use_cache")  # not kept
    step, probe = acc.programs["step_fn"], acc.programs["probe"]
    assert (step["cache_hits"], step["cache_misses"], step["cache_load_s"], step["compile_saved_s"]) == (1, 0, 0.5, 200.0)
    assert (probe["cache_hits"], probe["cache_misses"], probe["compile_or_load_s"]) == (0, 1, 9.0)
    totals = acc.summarize()["totals"]
    assert totals == {"trace_s": 0.0, "lower_s": 0.0, "compile_or_load_s": 9.75, "cache_hits": 1, "cache_misses": 1}


def test_a_stage_outside_every_setup_span_is_the_callers_before_ready_and_late_after():
    acc, clock, _, events = fake_account()
    stage(acc, TRACE, "make_weights", 1.0)  # the harness's, between two outermost spans
    assert acc.programs == {} and events == []
    acc.ready("serve")
    train = SpanRecorder(clock=clock, scope="train", annotate=FakeAnnotations(clock))
    with train.span("eval"):
        stage(acc, TRACE, "generate", 1.0)
        stage(acc, LOWER, "jit(generate)", 0.5)
        acc.on_enter(COMPILE, 0.0, fun_name="jit(generate)")
        acc.on_exit("/jax/compilation_cache/cache_hits")
        acc.on_exit(COMPILE, 0.25, fun_name="jit(generate)")
    stage(acc, TRACE, "serve_admit", 0.0001)  # a trace jax had kept: no lowering, no compile, no incident
    stage(acc, COMPILE, "jit(replay)", 2.0)
    assert events[1:] == [
        {"event": "late_compile", "program": "generate", "trace_s": 1.0, "lower_s": 0.5,
         "compile_or_load_s": 0.25, "cache_hit": True, "span": "train/eval"},
        {"event": "late_compile", "program": "replay", "trace_s": 0.0, "lower_s": 0.0,
         "compile_or_load_s": 2.0, "cache_hit": False, "span": None}]
    assert acc.late_compiles == 2 and acc.programs == {}
    with acc.spans.span("session_open"):  # a second engine adds to the account; it is not late
        stage(acc, COMPILE, "jit(serve_prefill)", 1.0)
    assert acc.late_compiles == 2 and set(acc.programs) == {"serve_prefill"}


def test_summary_names_the_ten_dearest_programs_and_sums_the_rest():
    acc, clock, _, _ = fake_account()
    with acc.spans.span("first_step"):
        for i in range(13):
            stage(acc, LOWER, f"jit(p{i})", float(i + 1))
    s = acc.summarize()
    assert list(s["programs"]) == [f"p{i}" for i in range(12, 2, -1)]
    assert s["others"]["n"] == 3 and s["others"]["lower_s"] == 1.0 + 2.0 + 3.0
    assert s["totals"]["lower_s"] == sum(range(1, 14))


def test_registration_twice_counts_once(account):
    setup.install()
    setup.install()
    with setup.span("first_step"):
        jax.jit(lambda x: x * 3 + 1)(jnp.ones((3, 5)))
    assert account.programs["<lambda>"]["n"] == 1


# ---- (c) a real jit inside a setup span


def test_a_real_jit_lands_in_the_table_under_its_name(account):
    def named_program(x):
        return jnp.tanh(x @ x).sum()  # matmul, tanh, sum: nested jits of their own

    f = jax.jit(named_program)
    x = jnp.ones((7, 7))  # its own little programs, outside every span: not the account's
    assert account.programs == {}
    with setup.span("first_step"):
        f(x)
    row = dict(account.programs["named_program"])
    assert set(account.programs) == {"named_program"}
    assert row["n"] == 1 and min(row["trace_s"], row["lower_s"], row["compile_or_load_s"]) > 0
    assert account.stages["first_step"]["trace_s"] == row["trace_s"]
    with setup.span("first_step"):
        f(x)
    assert account.programs["named_program"] == row  # a second call adds nothing


# ---- (d) a toy engine


def toy_engine(name="bart-test", **serve):
    from distributed_llms_example_tpu.models import registry
    from distributed_llms_example_tpu.serving.engine import ServeConfig, ServingEngine

    lm = registry.load_model(name)
    params = lm.init_params(0)
    cfg = ServeConfig(max_slots=4, prefill_batch=2, max_new_tokens=4, max_source_length=32, **serve)
    return ServingEngine(lm.module, lm.config, None, cfg, is_seq2seq=lm.is_seq2seq), params


@pytest.mark.parametrize("name", ["bart-test", "lfm2-moe-test"])
def test_engine_account_agrees_with_trace_counts_and_a_cold_bucket_is_a_late_compile(account, capsys, name):
    eng, params = toy_engine(name, prefill_buckets=(16,))
    if name == "lfm2-moe-test":  # as the published config does: it states the dtype its weights rest in
        eng.config = dataclasses.replace(eng.config, param_dtype="float32")
    assert account.awaited == {"serve"} and account.summary is None
    sess = eng.open(params)
    summary = account.summary
    assert [e["event"] for e in account.events] == ["setup_summary"]
    assert eng.trace_counts == {"prefill": 4, "admit": 4, "decode_step": 1}  # 2 wave sizes x 2 buckets
    assert {p: r["n"] for p, r in account.programs.items() if p.startswith("serve_")} == {
        f"serve_{k}": n for k, n in eng.trace_counts.items()}
    paths = set(summary["phases"])
    assert {"engine_init", "engine_init/engine_build", "session_open", "session_open/init_cache",
            "session_open/warm", "session_open/warm/warm_prefill", "session_open/warm/warm_admit",
            "session_open/warm/warm_decode_step"} <= paths
    assert ("session_open/weights_resident" in paths) == (name == "lfm2-moe-test")
    for p, left in summary["unattributed_s"].items():
        assert 0 <= left <= 0.05 * summary["phases"][p]["s"] + 0.01
    assert sum(summary["totals"][f] for f in setup.STAGES.values()) <= sum(
        summary["phases"][p]["s"] for p in ("engine_init", "session_open"))
    # a warmed bucket compiles nothing: no event
    sess.submit(list(range(5, 15)), max_new=2)
    while sess.has_work():
        sess.step()
    assert [e["event"] for e in account.events] == ["setup_summary"]
    # a second session on the warm engine: no second summary, nothing awaited again
    eng.open(params)
    assert [e["event"] for e in account.events] == ["setup_summary"] and account.summary is summary
    # a bucket ``warm`` was not given: the incident has a name and a span
    eng.buckets = (8,) + eng.buckets
    sess.submit([5, 6, 7], max_new=2)
    while sess.has_work():
        sess.step()
    late = [e for e in account.events if e["event"] == "late_compile"]
    assert [(e["program"], e["span"]) for e in late if e["program"] == "serve_prefill"] == [
        ("serve_prefill", "serve/prefill_dispatch")]
    (prefill,) = [e for e in late if e["program"] == "serve_prefill"]
    assert prefill["trace_s"] > 0 and prefill["lower_s"] > 0 and prefill["compile_or_load_s"] > 0
    assert eng.trace_counts["prefill"] == 5
    sess.finalize()
    served = [json.loads(x) for x in capsys.readouterr().out.splitlines() if x.startswith('{"event": "serve_summary"')]
    assert served[-1]["late_compiles"] == account.late_compiles == len(late)


# ---- (e) a toy trainer


def test_trainer_first_step_holds_the_step_programs_stages_and_a_second_epoch_compiles_nothing(account, tmp_path, capsys):
    from distributed_llms_example_tpu.core.config import CheckpointConfig, TrainConfig
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.train.trainer import Trainer

    records = [{"dialogue": "hello world " * (i % 5 + 1), "summary": "hi " * (i % 3 + 1)} for i in range(16)]
    cfg = TrainConfig(
        model_ckpt="t5-test", output_dir=str(tmp_path), batch_size=8, num_epochs=2, tokenizer="byte",
        max_source_length=32, max_target_length=8, pad_to_multiple=32, evaluation_steps=0, log_every_steps=100,
        obs="stdout", obs_gauges="off", obs_budget="off",
        checkpoint=CheckpointConfig(save_every_steps=0, resume=False, async_save=False))
    mesh = build_mesh(cfg.mesh, devices=jax.devices()[:1])
    trainer = Trainer(cfg, train_records=records, mesh=mesh)
    assert account.awaited == {"train"} and account.summary is None
    trainer.save_final = lambda: None
    trainer.checkpointer.save = lambda *a, **k: None
    trainer.train()
    summary = account.summary
    assert [e["event"] for e in account.events].count("setup_summary") == 1
    paths = set(summary["phases"])
    assert {"trainer_init", "trainer_init/data_open", "trainer_init/model_init", "trainer_init/params_to_host",
            "trainer_init/shard_params", "trainer_init/optimizer_init", "trainer_init/build_step",
            "first_step"} <= paths
    first = summary["phases"]["first_step"]
    step = summary["programs"]["step_fn"]
    assert step["n"] == 1 and min(step["trace_s"], step["lower_s"], step["compile_or_load_s"]) > 0
    for f in setup.STAGES.values():  # the step program's stages are first_step's (with the batch's little programs)
        assert step[f] <= first[f] <= first["s"]
    assert 0 <= summary["unattributed_s"]["first_step"] < first["s"] - step["compile_or_load_s"]
    assert summary["unattributed_s"]["trainer_init"] <= 0.05 * summary["phases"]["trainer_init"]["s"] + 0.01
    # two epochs of two steps: only the first call of the step program was set-up, and nothing compiled after it
    done = [json.loads(x) for x in capsys.readouterr().out.splitlines() if x.startswith('{"event": "done"')]
    assert done[-1]["late_compiles"] == account.late_compiles == 0
    assert [e["event"] for e in account.events] == ["setup_summary"]
