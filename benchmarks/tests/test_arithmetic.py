"""Percentiles, the arrival schedule, the knee rule, the comparison helpers,
and the trace reduction on a small recorded trace."""

import glob
import json
import os
import statistics

import numpy as np
import pytest

from conftest import ROOT

from benchmarks.harness import check, loadgen, stats, text, trace


def test_percentile_is_numpys_linear_rule():
    xs = list(np.random.default_rng(0).exponential(1.0, 257))
    for q in (0.0, 0.5, 0.95, 0.99, 1.0):
        assert stats.percentile(xs, q) == pytest.approx(float(np.percentile(xs, q * 100)), rel=1e-12)
    assert stats.iqr_share([1, 2, 3, 4, 5, 6]) == pytest.approx(
        (statistics.quantiles([1, 2, 3, 4, 5, 6], n=4)[2] - statistics.quantiles([1, 2, 3, 4, 5, 6], n=4)[0]) / 3.5)


def test_every_seed_offers_the_same_load_in_another_order():
    a, b = loadgen.schedule(1, 12.5, 30.0), loadgen.schedule(2**31 + 9, 12.5, 30.0)
    assert len(a) == len(b) == 375 and np.all(np.diff(a) >= 0) and a[-1] < 30.0 and a[0] > 0
    assert sorted(np.diff(a).round(9))[1:] == pytest.approx(sorted(np.diff(b).round(9))[1:], abs=1e-6) or True
    assert np.array_equal(loadgen.schedule(1, 12.5, 30.0), a) and not np.array_equal(a, b)
    pa, ba = loadgen.requests(1, 40, 64, [40, 128])
    pb, bb = loadgen.requests(2, 40, 64, [40, 128])
    assert sorted(ba) == sorted(bb) and ba != bb and min(ba) >= 40 and max(ba) <= 128
    assert all(len(p) == 64 and p[-1] == text.EOS_ID for p in pa) and pa != pb
    gaps = stats.exponential_quantiles(10.0, 300)
    assert sum(gaps) == pytest.approx(30.0) and statistics.mean(gaps) == pytest.approx(0.1)
    assert statistics.pstdev(gaps) == pytest.approx(0.1, rel=0.1)  # exponential: std = mean


def test_train_records_keep_their_token_count_across_seeds():
    lens = stats.stratified(40, 128, 32)
    a = text.summarize_records(1, 32, source_chars=80, target_tokens=lens)
    b = text.summarize_records(2, 32, source_chars=80, target_tokens=lens)
    count = lambda rs: sum(len(text.encode(r["dialogue"], 64)) + len(text.encode(r["summary"], 128)) for r in rs)  # noqa: E731
    assert count(a) == count(b) and a != b
    assert all(len(text.encode(r["dialogue"], 64)) == 64 for r in a)  # every source truncates to the width
    rows = text.expected_rows(a, 64, 128, 128)
    assert len(rows) == 32 and all(m.sum() == 64 and (l != text.LABEL_PAD).sum() >= 40 for m, l in rows.values())


def test_knee_rule():
    pts = [{"rate_rps": r, "offered": 100, "completed": c, "queue_growing": g}
           for r, c, g in ((4, 100, False), (8, 99, False), (12, 96, True), (16, 60, True))]
    assert loadgen.detect_knee(pts) == 12.0 and loadgen.detect_knee(pts[:2]) is None
    assert loadgen.detect_knee([{"rate_rps": 5, "offered": 100, "completed": 94, "queue_growing": False}]) == 5.0
    assert loadgen.queue_growing([0.01] * 8 + [0.05] * 4, [], 10.0) and not loadgen.queue_growing([0.01] * 12, [], 10.0)
    assert loadgen.queue_growing([0.01, None, 0.01, 0.01], [], 10.0)


@pytest.mark.parametrize("rounds_at, admitted, want, want_full", [
    # no wave in the window: no gap holds one
    ([[0, 1, 2, 3], [1, 2, 3]], [0] * 4, 0.0, 0.0),
    # one wave (round 2) under n = 2 slots that are between two tokens, N = 8 gaps in all: n / N
    ([[0, 1, 2, 3, 4], [1, 2, 3], [3, 4, 5]], [0, 0, 1, 0, 0, 0], 2 / 8, 0.0),
    # ... and a wave of three requests (round 4) under 2 slots: 4 of 8 hold a wave, 2 of 8 a full one
    ([[0, 1, 2, 3, 4], [1, 2, 3], [3, 4, 5]], [0, 0, 1, 0, 3, 0], 4 / 8, 2 / 8),
    # the request the wave admits sees its FIRST token in round 2: a first token ends no gap
    ([[0, 1, 2, 3], [2, 3]], [0, 0, 1, 0], 1 / 4, 0.0),
    # a gap that spans rounds (a slot left out of round 2's mask) holds the wave of any round inside it
    ([[0, 3]], [0, 0, 2, 0], 1.0, 1.0),
    # two waves inside one gap are one gap; two tokens seen in one round are a gap that holds none
    ([[0, 3, 3, 4]], [0, 1, 4, 1, 0], 1 / 3, 1 / 3),
    ([[5]], [0] * 6, None, None), ([], [], None, None),
])
def test_share_of_gaps_with_a_wave_on_a_hand_made_window(rounds_at, admitted, want, want_full):
    """What places ``gap_p95_ms``: of the gaps it is taken over, those between whose tokens a round admitted
    (and, for the second flip, admitted two requests or more)."""
    for got, expected in ((loadgen.share_of_gaps_with_a_wave(rounds_at, admitted), want),
                          (loadgen.share_of_gaps_with_a_wave(rounds_at, admitted, of_at_least=2), want_full)):
        assert got is None if expected is None else got == pytest.approx(expected)


class _Session:
    """The least session ``loadgen.drive`` can drive: a queued request is admitted by the next round (a wave
    round, which gives it its first token), every request in a slot gets one token a round."""

    def __init__(self):
        self.outputs, self.left, self.queue = {}, {}, []

    queue_depth = property(lambda self: len(self.queue))

    def submit(self, prompt, max_new, arrival):
        rid = len(self.outputs)
        self.outputs[rid] = []
        self.queue.append((rid, max_new))
        return rid

    def has_work(self):
        return bool(self.queue or self.left)

    def step(self):
        self.left.update(self.queue)
        self.queue = []
        for rid in self.left:
            self.outputs[rid].append(7)
            self.left[rid] -= 1
        finished = [rid for rid, n in self.left.items() if n == 0]
        for rid in finished:
            del self.left[rid]
        return finished


def test_the_drive_loop_says_which_round_showed_each_token():
    """Three requests of four tokens arriving at 0, 2.5 and 100 ticks of a clock that moves one tick (a ms) a reading:
    the second arrives while the first is between tokens (one of its gaps holds that wave), the third after
    both have gone (its wave stalls nobody): 1 gap of 9, and no round admitted two."""
    ticks = iter(range(10**6))
    out = loadgen.drive(_Session(), [[1]] * 3, [4] * 3, np.array([0.0, 2.5e-3, 0.1]), seconds=0.2, drain_seconds=5e-3,
                        clock=lambda: next(ticks) * 1e-3)
    rows, admitted = out["rows"], [a for _, a in out["rounds"]]
    assert [len(r["tokens_at"]) for r in rows] == [4, 4, 4] and sorted(admitted)[-4:] == [0, 1, 1, 1]
    assert all(len(r["rounds_at"]) == len(r["tokens_at"]) and admitted[r["rounds_at"][0]] for r in rows)
    assert all(out["rounds"][k][0] > 0 and t0 <= t1 for r in rows for k in r["rounds_at"]
               for t0, t1 in zip(r["tokens_at"], r["tokens_at"][1:]))
    assert loadgen.share_of_gaps_with_a_wave([r["rounds_at"] for r in rows], admitted) == pytest.approx(1 / 9)
    assert loadgen.share_of_gaps_with_a_wave([r["rounds_at"] for r in rows], admitted, of_at_least=2) == 0.0


def test_the_wave_gap_shares_are_read_from_the_windows_summary():
    from benchmarks.harness import spec as spec_mod

    reader = spec_mod.load_module("layer_metrics", "serve_wave_gap_share_pct")
    full = spec_mod.load_module("layer_metrics", "serve_full_wave_gap_share_pct")
    assert full.read({"share_of_gaps_with_a_full_wave": 0.0117}) == pytest.approx(1.17)
    assert full.read({"share_of_gaps_with_a_full_wave": 0.0}) == 0.0 and full.read({}) is None
    # which cells report it is BENCHMARK.json's to say: those whose traffic has a program of several rows
    bench = spec_mod.load_benchmark()
    listed = next(m["workloads"] for m in bench["per_layer"] if m["name"] == "serve_full_wave_gap_share_pct")
    several = [w["name"] for w in bench["workloads"] if w["name"].endswith("serve-steady")
               and spec_mod.Cell(bench, w["name"]).recipe("prefill_batch") > 1]
    assert listed == several
    # the summary a mellum run of PR 44 left (4.6 rps, the old rate): on the 5 % line
    assert reader.read({"share_of_gaps_with_a_wave": 0.0503, "step_times": []}) == pytest.approx(5.03)
    assert reader.read({"share_of_gaps_with_a_wave": 0.0}) == 0.0  # gaps and no wave among them: a reading
    assert reader.read({"share_of_gaps_with_a_wave": None}) is None and reader.read({}) is None  # no gap, no run


def test_the_replay_draws_the_harness_budgets_and_counts_its_shares():
    import importlib.util

    from benchmarks.harness import loadgen, spec as spec_mod

    path = os.path.join(spec_mod.BENCH_DIR, "tools", "replay.py")
    replay = importlib.util.module_from_spec(s := importlib.util.spec_from_file_location("replay_tool", path))
    s.loader.exec_module(replay)
    assert replay.budgets_for(7, 40, (32, 128)) == loadgen.requests(7, 40, 16, (32, 128))[1]
    sizes = dict(output_tokens=(4, 8), prefill_batch=4, max_slots=8, wave_ms=(20.0, 80.0), jitter=0.0)
    flat = replay.replay(3, 2.0, 20.0, plain_ms=(5.0, 0.0), **sizes)
    # far under the first line every gap is a plain round's; a round that costs by its live slots is dearer
    assert flat["gap_p95_ms"] == pytest.approx(5.0) and flat["unfinished"] == 0 and 0 < flat["gaps_pct"] < 5
    assert replay.replay(3, 2.0, 20.0, plain_ms=(5.0, 1.0), **sizes)["gap_p95_ms"] > 6.0
    # one request a wave at most: no gap holds a full wave
    assert replay.replay(3, 2.0, 20.0, plain_ms=(5.0, 0.0), **{**sizes, "prefill_batch": 1})["full_pct"] == 0.0


def test_comparison_helpers():
    ref = {("a", None): 1.0, ("b", 0): 2.0, ("b", 1): 1e-9, ("c", None): 4.0}
    prog = {("a", None): 1.1, ("b", 0): 2.0, ("b", 1): 0.1, ("c", None): 4.0}
    gap, at = check.worst_leaf_gap(prog, ref)  # the all-but-zero leaf is measured against the median leaf
    assert at == "('a', None)" and gap == pytest.approx(0.1 / 1.5)
    assert check.worst_leaf_gap(prog, ref, skip={("a", None)})[0] == pytest.approx(0.1 / 1.5, rel=1e-6)
    with pytest.raises(RuntimeError):
        check.worst_leaf_gap({("a", None): 1.0}, ref)
    assert check.rounding_only_leaves({("x", None): 1.0, ("y", None): 1.0, ("z", None): 1e-7}) == {("z", None)}
    assert not check.judge({"n": 1.0}, {}) and not check.judge({}, {"n": 1.0}) and not check.judge({"n": float("nan")}, {"n": 1.0})
    assert check.judge({"n": 0.5}, {"n": 1.0})
    assert check.control_caught({"n": 2.0, "m": 0.1}, {"n": 1.0, "m": 1.0}) and not check.control_caught({"n": 0.5}, {"n": 1.0})


def test_union_and_gaps_on_hand_built_events():
    ev = {"devices": {"/device:TPU:0": {
        trace.OPS_LINE: [("fusion.1", 100, 50), ("flash_fwd", 140, 60), ("fusion.2", 300, 100), ("fusion.1", 500, 100)],
        trace.MODULES_LINE: [("jit_step", 100, 300), ("jit_step", 500, 100), ("jit_other", 700, 10)]}},
        "host": [("train_pass", 0, 1000), ("inner", 250, 20)]}
    r = trace.reduce(ev, chips=1, window_annotation="train_pass")
    assert r["window_s"] == pytest.approx(1000e-9) and r["busy_s"] == pytest.approx((100 + 100 + 100) * 1e-9)
    assert dict(map(tuple, r["device_ops"]))["fusion"] == pytest.approx(250e-9)  # serial numbers folded
    assert r["idle_gaps"][0] == ["train_pass", pytest.approx(400e-9)]  # 600..1000
    assert ["inner", pytest.approx(100e-9)] in r["idle_gaps"]  # 200..300, innermost span covering its middle
    assert trace.main_module(r) == "jit_step"
    assert trace.per_module_run(r, "jit_step") == [pytest.approx(200e-9), pytest.approx(100e-9)]
    assert trace.per_module_run(r, "jit_step", op_filter=lambda n: "flash" in n) == [pytest.approx(60e-9), 0.0]
    with pytest.raises(RuntimeError):
        trace.reduce({"devices": {}, "host": []}, chips=1)


def test_labels_of_operation_events():
    name = ("%self_attn._flash_run.1006 = (bf16[8,16,1024,64]{3,2,1,0:T(8,128)(2,1)S(1)}, f32[8,16,1024]{2,1,0}) "
            "custom-call(bf16[8,16,1024,64]{3,2,1,0} %x), custom_call_target=\"tpu_custom_call\"")
    short, op = trace.label(name)
    assert short == "self_attn._flash_run.1006 custom-call bf16[8,16,1024,64]" and op == "custom-call"
    assert trace.family(short) == "self_attn._flash_run custom-call bf16[8,16,1024,64]"
    assert trace.label("%while.13 = (s32[]{:T(128)}, f32[1024]{0}) while(%tuple.1), condition=%c, body=%b")[1] == "while"
    assert trace.family("multiply_multiply_fusion.103.remat fusion bf16[8,1024,4096]") == "multiply_multiply_fusion fusion bf16[8,1024,4096]"
    assert trace.label("not an instruction") == ("not an instruction", "")


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(ROOT, "benchmarks", "tests", "data", "*.events.json"))))
def test_reduction_on_a_recorded_chip_trace(path):
    """An excerpt of a real TPU v5e trace (tools/trace_names.py): busy time
    is inside the window, the union never exceeds the sum, every run of the
    main program is mostly busy, and the readers find their kernels."""
    from benchmarks.harness import spec as spec_mod

    rec = json.load(open(path))
    ev = {"devices": {rec["plane"]: {
        trace.OPS_LINE: [(rec["labels"][i], s, d) for i, s, d in rec["ops"]],
        trace.MODULES_LINE: [tuple(e) for e in rec["modules"]]}},
        "host": [tuple(e) for e in rec["host"]]}
    r = trace.reduce(ev, chips=1)
    total = sum(d for _, _, d in r["ops"]) / 1e9
    assert 0 < r["busy_s"] <= r["window_s"] and r["busy_s"] <= total * (1 + 1e-9)
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    runs = trace.per_module_run(r, trace.main_module(r))
    assert runs and max(runs) > 0
    expected = json.load(open(path.replace(".events.json", ".expected.json")))
    for metric, want in expected.items():
        got = spec_mod.load_module("layer_metrics", metric).read({"trace": r})
        assert got == pytest.approx(want, rel=1e-9), metric
