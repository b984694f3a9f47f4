"""The program's spans beside the device's busy time (harness/program_spans.py):
hand-built spans, a host plane written by the profiler on the CPU, and an
excerpt of a real chip trace with the numbers the readers gave when it was
recorded."""

import glob
import json
import os

import pytest

from conftest import ROOT

from benchmarks.harness import program_spans as ps
from benchmarks.harness import spec as spec_mod
from benchmarks.harness.program_spans import Span

SERVE_READERS = ("serve_round_prep_ms", "serve_round_fetch_ms", "serve_round_emit_ms",
                 "serve_round_device_idle_ms", "serve_queue_wait_ms", "serve_prefill_device_ms")


def reader(name):
    return spec_mod.load_module("layer_metrics", name).read


def hand_built():
    """Two rounds: a plain one (0..100) and a wave (100..300); the device is
    busy 10..70 and 80..90 in the first, 120..170 (prefill), 175..200 (admit)
    and 210..280 in the second."""
    spans = [
        Span("serve/round", 0, 100, {}),
        Span("serve/admit_prep", 1, 4, {}),
        Span("serve/decode_dispatch", 6, 10, {}),
        Span("serve/token_fetch", 17, 60, {}),
        Span("serve/emit", 78, 20, {}),
        Span("serve/round", 100, 200, {}),
        Span("serve/admit_prep", 101, 9, {"n": 2, "queue_wait_us_sum": 30000}),
        Span("serve/prefill_dispatch", 111, 20, {}),
        Span("serve/emit", 132, 5, {}),
        Span("serve/decode_dispatch", 140, 10, {}),
        Span("serve/token_fetch", 151, 130, {}),
        Span("serve/emit", 282, 15, {}),
    ]
    reduced = {
        "window_ns": (0, 300), "window_s": 300e-9, "busy_s": 215e-9,
        "ops": [("fusion.1", 10, 60), ("fusion.2", 80, 10), ("flash", 120, 50), ("scatter", 175, 25), ("fusion.1", 210, 70)],
        "modules": [("jit_serve_decode_step(1)", 10, 80), ("jit_serve_prefill(2)", 120, 50),
                    ("jit_serve_admit(3)", 175, 25), ("jit_serve_decode_step(1)", 210, 70)],
    }
    return spans, reduced


def test_rounds_children_and_idle_on_hand_built_spans():
    spans, reduced = hand_built()
    plain, wave = ps.rounds(spans)
    assert len(plain) == len(wave) == 1
    assert [k.name for k in plain[0][1]] == ["serve/admit_prep", "serve/decode_dispatch", "serve/token_fetch", "serve/emit"]
    assert "serve/prefill_dispatch" in [k.name for k in wave[0][1]] and len(wave[0][1]) == 6
    gaps = ps.idle_gaps(reduced)
    assert gaps == [(0, 10), (70, 80), (90, 120), (170, 175), (200, 210), (280, 300)]
    assert ps.idle_within(gaps, 0, 100) == 30 and ps.idle_within(gaps, 75, 95) == 10 and ps.idle_within(gaps, 300, 400) == 0
    # innermost attribution: the round's own time is what its children leave
    by = ps.idle_by_innermost_span(gaps, spans)
    assert sum(by.values()) == 85 and by[None] == 0
    assert by == {None: 0, "serve/round": 11, "serve/admit_prep": 4 + 9, "serve/decode_dispatch": 4,
                  "serve/token_fetch": 7 + 5 + 10 + 1, "serve/emit": 2 + 8 + 15, "serve/prefill_dispatch": 9}
    # two threads: spans that overlap without nesting go to the later start
    segs = ps.innermost_segments([Span("train/a", 0, 10, {}), Span("train/b", 5, 10, {}), Span("train/c", 30, 5, {})])
    assert segs == [(0, 5, "train/a"), (5, 15, "train/b"), (30, 35, "train/c")]
    assert ps.idle_by_innermost_span([(0, 40)], [Span("train/c", 30, 5, {})]) == {None: 35, "train/c": 5}
    assert [s.name for s in ps.in_window([Span("train/a", 0, 10, {}), Span("train/b", 20, 5, {})], (8, 20))] == ["train/a"]


def context(tmp_path, monkeypatch, spans, reduced):
    """A reader's ``ctx`` whose trace file "holds" these spans."""
    cell = type("Cell", (), {"name": "a-cell"})()
    monkeypatch.setattr(spec_mod, "CACHE_DIR", str(tmp_path))
    path = ps.trace_path(cell.name)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    open(path, "wb").close()
    monkeypatch.setattr(ps, "_program_spans", lambda p: spans if p == path else [])
    return {"cell": cell, "trace": reduced}


def test_readers_on_hand_built_spans(tmp_path, monkeypatch):
    spans, reduced = hand_built()
    ctx = context(tmp_path, monkeypatch, spans, reduced)
    got = {name: reader(name)(ctx) for name in SERVE_READERS}
    assert got == {
        "serve_round_prep_ms": pytest.approx(14e-6),  # plain round only: admit_prep 4 + decode_dispatch 10
        "serve_round_fetch_ms": pytest.approx(60e-6),
        "serve_round_emit_ms": pytest.approx(20e-6),
        "serve_round_device_idle_ms": pytest.approx(30e-6),
        "serve_queue_wait_ms": pytest.approx(15.0),  # 30000 us over 2 requests
        "serve_prefill_device_ms": pytest.approx((50 + 25) * 1e-6),  # prefill run + admit run, busy union inside
    }


def test_no_program_span_gives_none(tmp_path, monkeypatch):
    _, reduced = hand_built()
    older = {**reduced, "modules": [("jit_counted(1)", 10, 80), ("jit_counted(2)", 120, 50)]}
    for ctx in (context(tmp_path, monkeypatch, [], older),  # an older program: no span, no named program
                {"cell": type("Cell", (), {"name": "no-trace-file"})(), "trace": older},
                {"cell": None, "trace": None}):  # a CPU rehearsal: no device plane
        for name in SERVE_READERS:
            assert reader(name)(ctx) is None, name


def test_spans_and_stats_come_back_from_a_profile(tmp_path):
    """The real round trip on the CPU: obs/spans.py's annotations through the
    profiler into an .xplane.pb, read back with names, nesting and stats."""
    import jax

    from distributed_llms_example_tpu.obs.spans import SpanRecorder

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        rec = SpanRecorder(scope="serve")
        with jax.profiler.TraceAnnotation("serve_step"):
            with rec.span("round"):
                with rec.span("admit_prep") as prep:
                    prep.set(n=2, queue_wait_us_sum=1500)
                with rec.span("emit"):
                    pass
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    spans = ps.read_spans(path)
    assert [s.name for s in spans] == ["serve/round", "serve/admit_prep", "serve/emit"]  # serve_step is not the program's
    rd, prep, emit = spans
    assert rd.stats == {} == emit.stats and prep.stats == {"n": 2, "queue_wait_us_sum": 1500}
    assert rd.start <= prep.start and prep.end <= emit.start and emit.end <= rd.end
    plain, wave = ps.rounds(spans)
    assert len(plain) == 1 and not wave and [k.name for k in plain[0][1]] == ["serve/admit_prep", "serve/emit"]


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(ROOT, "benchmarks", "tests", "data", "*.spans.json"))))
def test_readers_on_a_recorded_chip_excerpt(path, tmp_path, monkeypatch):
    """An excerpt of a real TPU v5e trace (tools/idle_by_span.py --excerpt-rounds):
    the readers keep giving what they gave when it was recorded, idle time is
    conserved, and the round's children cover it."""
    rec = json.load(open(path))
    spans = [Span(n, s, d, st) for n, s, d, st in rec["spans"]]
    reduced = {"window_ns": tuple(rec["window_ns"]), "ops": [("busy", s, d) for s, d in rec["busy"]],
               "modules": [tuple(m) for m in rec["modules"]]}
    expected = json.load(open(path.replace(".spans.json", ".expected.json")))
    ctx = context(tmp_path, monkeypatch, spans, reduced)
    for metric, want in expected["metrics"].items():
        assert reader(metric)(ctx) == pytest.approx(want, rel=1e-9), metric
    gaps = ps.idle_gaps(reduced)
    by = ps.idle_by_innermost_span(gaps, spans)
    assert sum(by.values()) == sum(b - a for a, b in gaps)
    plain, wave = ps.rounds(spans)
    assert len(plain) == expected["plain_rounds"] and len(wave) == expected["wave_rounds"]
    for rd, kids in plain + wave:
        assert sum(k.dur for k in kids) >= 0.95 * rd.dur


def test_idle_by_span_table_and_excerpt_on_hand_built_spans():
    import importlib.util

    spec = importlib.util.spec_from_file_location("idle_by_span", os.path.join(ROOT, "benchmarks", "tools", "idle_by_span.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    spans, reduced = hand_built()
    steps = [Span("serve_step", -1, 101, {}), Span("serve_step", 100, 201, {})]
    out = tool.table(reduced, sorted(spans + steps, key=lambda s: (s.start, -s.dur)), "serve_step")
    assert out["idle_s"] == pytest.approx(85e-9) == out["window_idle_s"]
    assert sum(out["idle_s_by_innermost_span"].values()) == pytest.approx(85e-9)
    assert out["idle_s_in_no_program_span"] == 0.0 and out["idle_s_by_innermost_span"]["serve/round"] == pytest.approx(11e-9)
    assert out["idle_s_inside_serve_step"] == pytest.approx(85e-9)
    assert out["share_of_that_in_a_leaf_program_span"] == pytest.approx(74 / 85)  # all but the rounds' own 11
    assert out["longest_gaps"][0] == ["serve/admit_prep", pytest.approx(30e-9)]  # 90..120, its middle in admit_prep
    cut = tool.excerpt(reduced, spans, 2)
    assert [s[0] for s in cut["spans"]].count("serve/round") == 2 and len(cut["busy"]) == 5
    assert cut["modules"][1][0] == "jit_serve_prefill(2)" and cut["spans"][0][1] == 1_000_000


@pytest.mark.parametrize("driver", ["serve_open_loop", "train_loop"])
def test_a_drivers_first_annotation_is_its_window(driver):
    """tools/idle_by_span.py takes the benchmark's span names from the cell's
    driver and its first as the window's: what the driver hands ``profiled``."""
    import inspect

    module = spec_mod.load_module("drivers", driver)
    assert f'profiled(run, ANNOTATIONS, "{module.ANNOTATIONS[0]}"' in inspect.getsource(module)
