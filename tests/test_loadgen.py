"""Open-loop load observability (ISSUE 17): seeded arrival schedules,
the virtual-clock QPS sweep, knee detection, the closed-loop-vs-open-loop
disagreement pin, the report's sweep section + strict gates, and the
serve_request arrival/queue-delay schema growth.

The deterministic tier runs on a session-shaped fake whose clock is a
``VirtualClock`` shared with the driver — schedule, queueing, and
verdicts replay bit-for-bit with no wall clock anywhere.  The slow tier
drives a real tiny engine and pins the determinism contract (open-loop
tokens == the closed-loop oracle's) plus genuine queueing collapse."""

import json
import os

import numpy as np
import pytest

from distributed_llms_example_tpu.obs import sink as sink_mod
from distributed_llms_example_tpu.obs.report import (
    build_report,
    render_markdown,
)
from distributed_llms_example_tpu.serving.loadgen import (
    EngineTarget,
    LoadgenConfig,
    RouterTarget,
    VirtualClock,
    arrival_schedule,
    detect_knee,
    drive_open_loop,
    queue_growing,
    summarize_point,
    sweep_qps,
)


@pytest.fixture(autouse=True)
def _default_sink():
    sink_mod.install_sink(sink_mod.build_sink("stdout", ""))
    yield
    sink_mod.install_sink(sink_mod.build_sink("stdout", ""))


# ---------------------------------------------------------------------------
# pure logic: config validation, arrival schedules, knee detection
# ---------------------------------------------------------------------------


def test_loadgen_config_validation():
    with pytest.raises(ValueError, match="process"):
        LoadgenConfig(process="uniform")
    with pytest.raises(ValueError, match="burst_size"):
        LoadgenConfig(burst_size=0)
    with pytest.raises(ValueError, match="ramp_start_frac"):
        LoadgenConfig(ramp_start_frac=0.0)
    with pytest.raises(ValueError, match="at least one"):
        LoadgenConfig(qps_grid=())
    with pytest.raises(ValueError, match="positive"):
        LoadgenConfig(qps_grid=(1.0, -2.0))
    with pytest.raises(ValueError, match="ascend"):
        LoadgenConfig(qps_grid=(4.0, 2.0))


@pytest.mark.parametrize("process", ["poisson", "bursty", "ramp"])
def test_arrival_schedule_deterministic(process):
    """The determinism acceptance pin: same seed + config → bit-identical
    float64 schedule; a different seed or rate → a different one."""
    a = arrival_schedule(process, qps=4.0, n=64, seed=3)
    b = arrival_schedule(process, qps=4.0, n=64, seed=3)
    assert a.dtype == np.float64 and len(a) == 64
    assert (a == b).all()
    assert (np.diff(a) >= 0).all() and (a > 0).all()
    assert not (a == arrival_schedule(process, qps=4.0, n=64, seed=4)).all()
    assert not (a == arrival_schedule(process, qps=8.0, n=64, seed=3)).all()
    # the average rate is the offered rate (law of large numbers at n=64:
    # a loose band is enough to catch a rate-off-by-k bug)
    assert 2.0 < 64 / a[-1] < 8.0


def test_arrival_schedule_shapes_and_errors():
    # bursty: burst_size arrivals share each instant
    s = arrival_schedule("bursty", qps=8.0, n=12, seed=0, burst_size=4)
    assert len(set(s[:4])) == 1 and len(set(s[4:8])) == 1
    assert s[0] < s[4] < s[8]
    # ramp: the early arrivals come at a slower instantaneous rate, so
    # the first half spans more time than the second half
    r = arrival_schedule("ramp", qps=8.0, n=200, seed=0, ramp_start_frac=0.2)
    assert (r[99] - r[0]) > (r[199] - r[100])
    with pytest.raises(ValueError, match="n must be"):
        arrival_schedule("poisson", qps=1.0, n=0, seed=0)
    with pytest.raises(ValueError, match="qps must be"):
        arrival_schedule("poisson", qps=0.0, n=4, seed=0)
    with pytest.raises(ValueError, match="process"):
        arrival_schedule("uniform", qps=1.0, n=4, seed=0)


def _point(offered, *, achieved=None, growing=False, shed=0):
    return {
        "offered_qps": offered,
        "achieved_qps": offered if achieved is None else achieved,
        "queue_growing": growing,
        "shed": shed,
    }


def test_detect_knee_pinned_curves():
    """The knee is the FIRST saturated offered rate, by any of the three
    saturation signals, in grid order."""
    # throughput stops tracking the offer
    assert detect_knee([
        _point(1.0), _point(2.0), _point(4.0, achieved=3.0), _point(8.0, achieved=3.1),
    ]) == 4.0
    # unbounded queue growth fires first
    assert detect_knee([
        _point(1.0), _point(2.0, growing=True), _point(4.0, achieved=1.0),
    ]) == 2.0
    # shed requests saturate even when achieved tracks
    assert detect_knee([_point(1.0), _point(2.0, shed=3)]) == 2.0
    # every point tracks: the grid never reached saturation
    assert detect_knee([_point(1.0), _point(2.0), _point(4.0)]) is None
    # track_tol moves the tracking bar
    curve = [_point(2.0, achieved=1.9)]
    assert detect_knee(curve, track_tol=0.9) is None
    assert detect_knee(curve, track_tol=0.99) == 2.0


def test_queue_growing_verdicts():
    def row(arrival, ttft, finished=True):
        return {"arrival_s": arrival, "ttft_s": ttft, "finished": finished,
                "shed": False}

    # stationary waits: not growing
    flat = [row(i * 1.0, 0.05) for i in range(8)]
    assert not queue_growing(flat, 8.0)
    # the last quarter waits 10x the first: growing
    ramp = [row(i * 1.0, 0.01 if i < 6 else 0.5) for i in range(8)]
    assert queue_growing(ramp, 8.0)
    # an unfinished tail IS unbounded growth
    tail = flat[:-1] + [row(7.0, None, finished=False)]
    assert queue_growing(tail, 8.0)
    # under 4 rows there's no head/tail to compare
    assert not queue_growing(flat[:3], 3.0)


def test_summarize_point_missing_measurement_is_none():
    """A fully-collapsed point (nothing finished) must report its TTFT
    percentiles as None — 0.0 would PASS a --max-p99-ttft-ms gate."""
    rows = [
        {"arrival_s": float(i), "queue_delay_s": None, "ttft_s": None,
         "finished": False, "shed": False}
        for i in range(4)
    ]
    p = summarize_point(rows, offered_qps=2.0, ttft_slo_ms=100.0, wall_s=10.0)
    assert p["completed"] == 0 and p["unfinished"] == 4
    assert p["ttft_p99_ms"] is None and p["ttft_p50_ms"] is None
    assert p["slo_attainment"] == 0.0 and p["goodput_qps"] == 0.0
    assert p["queue_growing"] is True


def test_summarize_point_slo_over_offered_denominator():
    """SLO attainment is judged over every OFFERED request: unfinished
    and shed requests are misses, never dropped from the denominator."""
    rows = [
        {"arrival_s": 0.0, "queue_delay_s": 0.0, "ttft_s": 0.01,
         "finished": True, "shed": False},
        {"arrival_s": 1.0, "queue_delay_s": 0.0, "ttft_s": 5.0,
         "finished": True, "shed": False},  # finished but missed the SLO
        {"arrival_s": 2.0, "queue_delay_s": 0.0, "ttft_s": None,
         "finished": False, "shed": True},  # shed = a miss
        {"arrival_s": 3.0, "queue_delay_s": None, "ttft_s": None,
         "finished": False, "shed": False},  # unfinished = a miss
    ]
    p = summarize_point(rows, offered_qps=1.0, ttft_slo_ms=100.0, wall_s=4.0)
    assert p["offered"] == 4 and p["completed"] == 2 and p["shed"] == 1
    assert p["slo_attainment"] == 0.25
    assert p["goodput_qps"] == 0.25


# ---------------------------------------------------------------------------
# the deterministic fake tier: a session-shaped fake over a VirtualClock
# ---------------------------------------------------------------------------


class ClockedFakeSession:
    """ServeSession surface with a deterministic service model: ``slots``
    concurrent, one token per request per step, each step ``step_s`` of
    virtual time.  Capacity = slots / (step_s × budget) requests/sec."""

    def __init__(self, clock, slots=2, step_s=0.05, default_budget=4):
        self.clock = clock
        self.slots = slots
        self.step_s = step_s
        self.default_budget = default_budget
        self.submit_t: list[float] = []
        self.arrival_t: list[float] = []
        self.budgets: list[int] = []
        self.outputs: list[list[int]] = []
        self._first: list[float | None] = []
        self.pending: list[int] = []
        self.active: list[int] = []

    def submit(self, tokens, *, max_new=None, attention_mask=None,
               label=None, arrival=None):
        rid = len(self.submit_t)
        now = self.clock.now()
        self.submit_t.append(now)
        self.arrival_t.append(arrival if arrival is not None else now)
        self.budgets.append(max_new or self.default_budget)
        self.outputs.append([])
        self._first.append(None)
        self.pending.append(rid)
        return rid

    def has_work(self):
        return bool(self.pending or self.active)

    def step(self):
        self.clock.advance(self.step_s)
        while self.pending and len(self.active) < self.slots:
            self.active.append(self.pending.pop(0))
        finished = []
        for rid in list(self.active):
            self.outputs[rid].append(100 + len(self.outputs[rid]))
            if self._first[rid] is None:
                self._first[rid] = self.clock.now()
            if len(self.outputs[rid]) >= self.budgets[rid]:
                self.active.remove(rid)
                finished.append(rid)
        return finished

    def finalize(self):
        return {}

    def first_token_wall(self, rid):
        return self._first[rid]

    def output(self, rid):
        return self.outputs[rid]


def _fake_sweep(cfg, n_req=24, slots=2, step_s=0.05):
    """One whole sweep on the fake, virtual time only."""
    vc = VirtualClock()
    return sweep_qps(
        lambda: EngineTarget(ClockedFakeSession(vc, slots=slots, step_s=step_s)),
        [[1, 2, 3]] * n_req, cfg,
        clock=vc.now, wait=vc.advance, emit=False,
    )


def test_open_loop_drive_builds_queues():
    """Arrivals never wait for completions: offering 10× the fake's
    capacity piles requests into the queue, and TTFT measured from
    ARRIVAL grows with arrival index."""
    vc = VirtualClock()
    sess = ClockedFakeSession(vc, slots=1, step_s=0.1, default_budget=1)
    # capacity 10 tokens/s => 10 req/s at budget 1; offer 100/s
    sched = [i * 0.01 for i in range(12)]
    rows, wall_s = drive_open_loop(
        EngineTarget(sess), [[1]] * 12, sched, clock=vc.now, wait=vc.advance,
    )
    assert all(r["finished"] for r in rows)
    ttfts = [r["ttft_s"] for r in rows]
    assert ttfts[-1] > ttfts[0] * 5  # the queue genuinely built
    assert queue_growing(rows, wall_s)


def test_sweep_deterministic_and_knee_on_fake():
    """Same seed + config → identical sweep summaries (verdicts, curves,
    knee), twice over; the knee lands where offered rate crosses the
    fake's capacity."""
    cfg = LoadgenConfig(qps_grid=(1.0, 4.0, 40.0), ttft_slo_ms=400.0)
    s1 = _fake_sweep(cfg)
    s2 = _fake_sweep(cfg)
    assert s1 == s2
    # capacity is 2 slots / (0.05 s × 4 tokens) = 10 req/s: 1 and 4 QPS
    # track, 40 QPS has saturated
    assert [p["queue_growing"] for p in s1["points"]] == [False, False, True]
    assert s1["knee_qps"] == 40.0
    assert s1["points"][0]["slo_attainment"] == 1.0
    assert s1["points"][2]["slo_attainment"] < 0.5
    # a different seed moves the schedule (the curve numbers shift)
    s3 = _fake_sweep(LoadgenConfig(qps_grid=(1.0, 4.0, 40.0),
                                   ttft_slo_ms=400.0, seed=9))
    assert s3["points"] != s1["points"]


def test_open_loop_sees_collapse_closed_loop_cannot():
    """THE acceptance disagreement: the same config measured closed-loop
    (submit all, drain — offered rate capped by service rate) reads
    healthy, while the open-loop sweep at an offered rate above capacity
    reports unbounded queue growth.  Two verdicts, pinned to disagree."""
    # closed-loop pass: all 16 requests at t=0, drain to completion
    vc = VirtualClock()
    sess = ClockedFakeSession(vc, slots=2, step_s=0.05)
    for _ in range(16):
        sess.submit([1, 2, 3])
    while sess.has_work():
        sess.step()
    closed_wall = vc.now()
    closed_qps = 16 / closed_wall
    assert closed_qps > 9.0  # ~capacity: the closed-loop number is healthy
    # open-loop pass: offer 4× capacity — the same config collapses
    cfg = LoadgenConfig(qps_grid=(40.0,), ttft_slo_ms=400.0)
    point = _fake_sweep(cfg, n_req=16)["points"][0]
    assert point["queue_growing"] is True
    assert point["slo_attainment"] < 1.0
    # the open-loop driver still pushed tokens at device rate — it is the
    # LATENCY verdict that collapses, which closed-loop cannot see
    assert point["achieved_qps"] > 9.0


def test_open_loop_matches_closed_loop_tokens_on_fake():
    """Determinism contract at the fake tier: arrival timing moves
    latency, never tokens — open-loop outputs equal the closed-loop
    drain's."""
    vc = VirtualClock()
    oracle = ClockedFakeSession(vc, slots=2, step_s=0.05)
    budgets = [2, 4, 3, 5, 1, 4, 2, 3]
    for b in budgets:
        oracle.submit([1], max_new=b)
    while oracle.has_work():
        oracle.step()
    vc2 = VirtualClock()
    sess = ClockedFakeSession(vc2, slots=2, step_s=0.05)
    sched = arrival_schedule("bursty", qps=30.0, n=8, seed=1)
    drive_open_loop(
        EngineTarget(sess), [[1]] * 8, sched, budgets=budgets,
        clock=vc2.now, wait=vc2.advance,
    )
    assert [sess.output(r) for r in range(8)] == [
        oracle.output(r) for r in range(8)
    ]


def test_drive_open_loop_wall_cap_reports_unsubmitted_tail():
    """A capped run reports what never got submitted as data (submitted=
    False rows), not an error — and the length validation still bites."""
    vc = VirtualClock()
    sess = ClockedFakeSession(vc, slots=1, step_s=0.5, default_budget=8)
    sched = [0.0, 0.1, 50.0]
    rows, wall_s = drive_open_loop(
        EngineTarget(sess), [[1]] * 3, sched, clock=vc.now, wait=vc.advance,
        max_wall_s=2.0,
    )
    assert rows[2]["submitted"] is False and rows[2]["finished"] is False
    assert wall_s <= 3.0
    with pytest.raises(ValueError, match="arrivals for"):
        drive_open_loop(EngineTarget(sess), [[1]] * 2, [0.0])


# ---------------------------------------------------------------------------
# the router target: shed accounting + arrival threading (fake replicas)
# ---------------------------------------------------------------------------


def test_router_target_threads_arrival_and_counts_shed():
    from distributed_llms_example_tpu.serving.router import (
        ReplicaRouter,
        RouterConfig,
    )
    from tests.test_router import FakeEngine

    router = ReplicaRouter(
        [FakeEngine(), FakeEngine()], None,
        RouterConfig(log_every_ticks=0, max_queue=4, shed_policy="shed"),
    )
    target = RouterTarget(router)
    # one burst: every arrival due before the first tick, so the queue
    # bound (4) trips before dispatch can drain it
    sched = [1e-4] * 10
    rows, wall_s = drive_open_loop(target, [[1, 2]] * 10, sched)
    assert len(rows) == 10
    assert sum(r["shed"] for r in rows) > 0  # the queue bound shed some
    done = [r for r in rows if r["finished"]]
    assert done and all(r["ttft_s"] is not None for r in done)
    # arrival threading: the router rows carry the arrival→submit stage
    rrows = [r for r in router.request_rows() if not r["synthetic"]]
    assert all("arrival_s" in r and "queue_delay_ms" in r for r in rrows)
    assert all(r["queue_delay_ms"] >= 0 for r in rrows)
    point = summarize_point(
        rows, offered_qps=1000.0, ttft_slo_ms=500.0, wall_s=wall_s,
    )
    assert point["shed"] == sum(r["shed"] for r in rows)
    # shed requests saturate the point
    assert detect_knee([point]) == 1000.0


# ---------------------------------------------------------------------------
# schema round-trip: sweep events → JSONL → report section + strict gates
# ---------------------------------------------------------------------------


def _emit_fake_sweep_to(tmp_path, cfg, **kw):
    path = os.path.join(str(tmp_path), "obs", "metrics-p000.jsonl")
    sink_mod.install_sink(sink_mod.JsonlFileSink(path))
    try:
        vc = VirtualClock()
        summary = sweep_qps(
            lambda: EngineTarget(ClockedFakeSession(vc, **kw)),
            [[1, 2, 3]] * 16, cfg, clock=vc.now, wait=vc.advance,
        )
    finally:
        sink_mod.current_sink().close()
        sink_mod.install_sink(sink_mod.build_sink("stdout", ""))
    return summary


def test_report_renders_sweep_from_jsonl_alone(tmp_path):
    """The acceptance pin: obs.report renders the QPS-sweep table and
    SLO attainment from the JSONL stream alone — no in-process state."""
    cfg = LoadgenConfig(qps_grid=(1.0, 4.0, 40.0), ttft_slo_ms=400.0)
    summary = _emit_fake_sweep_to(tmp_path, cfg)
    # every event round-trips the schema loader (schema_version stamped)
    path = os.path.join(str(tmp_path), "obs", "metrics-p000.jsonl")
    records = [json.loads(line) for line in open(path)]
    assert all(r["schema_version"] == 1 for r in records)
    assert sum(r.get("event") == "loadgen_point" for r in records) == 3
    assert sum(r.get("event") == "loadgen_summary" for r in records) == 1
    report = build_report(str(tmp_path))
    lg = report["loadgen"]
    assert lg["knee_qps"] == summary["knee_qps"] == 40.0
    assert [p["offered_qps"] for p in lg["points"]] == [1.0, 4.0, 40.0]
    assert lg["best_slo_attainment"] == 1.0
    assert lg["best_ttft_p99_ms"] is not None
    md = render_markdown(report)
    assert "## Open-loop load sweep" in md
    assert "**40 QPS** (first saturated offered rate)" in md
    assert "| offered QPS |" in md and "| 40 |" in md


def test_report_bare_points_without_summary_still_render(tmp_path):
    """A run killed mid-sweep leaves loadgen_point events but no
    summary — the curve still renders (knee unknown)."""
    obs_dir = tmp_path / "obs"
    os.makedirs(obs_dir, exist_ok=True)
    p = summarize_point(
        [{"arrival_s": 0.0, "queue_delay_s": 0.0, "ttft_s": 0.02,
          "finished": True, "shed": False}],
        offered_qps=2.0, ttft_slo_ms=100.0, wall_s=1.0,
    )
    with open(obs_dir / "metrics-p000.jsonl", "w") as f:
        f.write(json.dumps({
            "schema_version": 1, "event": "loadgen_point",
            "process": "poisson", "seed": 0, **p,
        }) + "\n")
    lg = build_report(str(tmp_path))["loadgen"]
    assert lg["knee_qps"] is None
    assert len(lg["points"]) == 1
    assert "not reached on this grid" in render_markdown(
        build_report(str(tmp_path))
    )


@pytest.mark.parametrize(
    "gates, rc, says",
    [
        # attainment: the best point reaches 1.0 → a 0.99 floor passes
        (("--min-slo-attainment", "0.99"), 0, ""),
        # p99: the best measured point is well under a generous ceiling
        (("--max-p99-ttft-ms", "5000"), 0, ""),
        # ...and over a 1 ms ceiling fails with the measured value named
        (("--max-p99-ttft-ms", "1"), 1, "exceeds"),
        # both gates in the one command
        (("--min-slo-attainment", "0.8", "--max-p99-ttft-ms", "5000"), 0, ""),
        (("--min-slo-attainment", "0.8", "--max-p99-ttft-ms", "1"), 1, "exceeds"),
    ],
    ids=["attainment", "p99-under", "p99-over", "both-pass", "both-one-fails"],
)
def test_strict_gates_cut_both_ways(tmp_path, capsys, gates, rc, says):
    from distributed_llms_example_tpu.obs.report import main as report_main

    cfg = LoadgenConfig(qps_grid=(1.0, 4.0, 40.0), ttft_slo_ms=400.0)
    _emit_fake_sweep_to(tmp_path, cfg)
    assert report_main([str(tmp_path), "--strict", *gates, "--json"]) == rc
    assert says in capsys.readouterr().err


def test_strict_gate_fails_without_loadgen_measurement(tmp_path, capsys):
    """THE acceptance pin: --strict --min-slo-attainment on a run with no
    loadgen measurement fails — missing must never read as a pass."""
    from distributed_llms_example_tpu.obs.report import main as report_main

    obs_dir = tmp_path / "obs"
    os.makedirs(obs_dir, exist_ok=True)
    with open(obs_dir / "metrics-p000.jsonl", "w") as f:
        f.write(json.dumps({"schema_version": 1, "step": 1, "loss": 1.0}) + "\n")
    d = str(tmp_path)
    assert report_main([d, "--strict", "--json"]) == 0  # clean without the gate
    assert report_main(
        [d, "--strict", "--min-slo-attainment", "0.5", "--json"]
    ) == 1
    assert "no loadgen measurement" in capsys.readouterr().err
    assert report_main(
        [d, "--strict", "--max-p99-ttft-ms", "500", "--json"]
    ) == 1
    assert "no measured p99" in capsys.readouterr().err


def test_strict_p99_gate_fails_on_fully_collapsed_run(tmp_path, capsys):
    """Every point collapsed (nothing finished anywhere): the p99 gate
    fails as a MISSING measurement — None percentiles never compare."""
    from distributed_llms_example_tpu.obs.report import main as report_main

    obs_dir = tmp_path / "obs"
    os.makedirs(obs_dir, exist_ok=True)
    p = summarize_point(
        [{"arrival_s": 0.0, "queue_delay_s": None, "ttft_s": None,
          "finished": False, "shed": False}],
        offered_qps=8.0, ttft_slo_ms=100.0, wall_s=1.0,
    )
    with open(obs_dir / "metrics-p000.jsonl", "w") as f:
        f.write(json.dumps({
            "schema_version": 1, "event": "loadgen_point",
            "process": "poisson", "seed": 0, **p,
        }) + "\n")
    rc = report_main(
        [str(tmp_path), "--strict", "--max-p99-ttft-ms", "500", "--json"]
    )
    assert rc == 1
    assert "no measured p99" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# the real engine: closed-loop arrival stamps (fast) + open-loop
# collapse and token determinism (slow tier)
# ---------------------------------------------------------------------------


def _engine(lm, mesh, *, slots=4, max_new=6, src=16, slo_ms=0.0,
            log_every=0):
    from distributed_llms_example_tpu.serving.engine import (
        ServeConfig,
        ServingEngine,
    )

    return ServingEngine(
        lm.module, lm.config, mesh,
        ServeConfig(max_slots=slots, prefill_batch=slots,
                    max_new_tokens=max_new, max_source_length=src,
                    log_every_steps=log_every, ttft_slo_ms=slo_ms),
        is_seq2seq=lm.is_seq2seq,
    )


def test_closed_loop_serve_request_arrival_fields(mesh8, capsys):
    """Satellite 1: serve_request gains t_arrival_s + queue_delay_ms and
    serve_summary the queue-delay percentiles; closed-loop submits stamp
    arrival == submit, so the new stage reads 0 and every existing
    consumer stays green."""
    from distributed_llms_example_tpu.models.registry import load_model
    from distributed_llms_example_tpu.parallel.sharding import shard_params

    lm = load_model("t5-test", load_weights=False)
    params = shard_params(lm.init_params(0), mesh8)
    eng = _engine(lm, mesh8, log_every=2)
    rng = np.random.RandomState(0)
    reqs = [list(rng.randint(3, 100, rng.randint(3, 10))) for _ in range(4)]
    capsys.readouterr()
    eng.generate(params, reqs)
    events = [
        json.loads(line)
        for line in capsys.readouterr().out.splitlines()
        if line.startswith("{")
    ]
    spans = [e for e in events if e.get("event") == "serve_request"]
    assert len(spans) == len(reqs)
    for e in spans:
        assert "t_arrival_s" in e and "queue_delay_ms" in e
        assert e["queue_delay_ms"] == 0.0  # closed-loop: arrival == submit
        # the two queueing stages decompose: arrival→submit + submit→admit
        assert e["t_arrival_s"] <= e["t_admit_s"]
    summary = next(e for e in events if e.get("event") == "serve_summary")
    assert summary["queue_delay_p50_ms"] == 0.0
    assert summary["queue_delay_p99_ms"] == 0.0
    window = next(e for e in events if e.get("event") == "serve_window")
    assert {"arrival_rate_per_sec", "service_rate_per_sec",
            "queue_growth"} <= set(window)


@pytest.mark.slow  # real compiled engine: one prefill+decode program, a
# closed-loop oracle pass and a 2-point open-loop sweep (~1 min on CPU)
def test_real_engine_open_loop_collapse_and_token_determinism(mesh8, capsys):
    """The acceptance criteria on a REAL tiny engine: (1) open-loop
    tokens are bit-identical to the closed-loop oracle at every offered
    rate (arrival timing moves latency, never tokens); (2) an offered
    rate far above the engine's measured capacity reports queueing
    collapse while the closed-loop measurement of the same config
    reports healthy throughput."""
    from distributed_llms_example_tpu.models.registry import load_model
    from distributed_llms_example_tpu.parallel.sharding import shard_params

    lm = load_model("t5-test", load_weights=False)
    params = shard_params(lm.init_params(0), mesh8)
    rng = np.random.RandomState(3)
    reqs = [list(rng.randint(3, 100, rng.randint(3, 12))) for _ in range(8)]
    budgets = [int(b) for b in rng.randint(2, 7, len(reqs))]
    eng = _engine(lm, mesh8, slo_ms=10_000.0)
    # closed-loop oracle: healthy verdict + the token reference
    import time as _time

    t0 = _time.perf_counter()
    oracle = eng.generate(params, reqs, max_new=budgets)
    closed_wall = max(_time.perf_counter() - t0, 1e-9)
    closed_qps = len(reqs) / closed_wall
    # open-loop sweep: one rate the engine can absorb, one far past it
    cfg = LoadgenConfig(
        qps_grid=(max(closed_qps / 4, 0.1), closed_qps * 50),
        ttft_slo_ms=10_000.0, max_wall_s=max(closed_wall * 6, 5.0),
    )
    sessions = []

    def factory():
        sess = eng.open(params)
        sessions.append(sess)
        return EngineTarget(sess)

    summary = sweep_qps(factory, reqs, cfg, budgets=budgets)
    low, high = summary["points"]
    # (2) the disagreement: closed-loop reads healthy, the over-offered
    # open-loop point saturates (growing delay / unfinished tail)
    assert low["completed"] == len(reqs)
    assert high["queue_growing"] or high["unfinished"] > 0
    assert summary["knee_qps"] is not None
    # (1) determinism: both sweep points produced the oracle's tokens
    # for everything that ran to completion
    for sess in sessions:
        for rid in range(len(reqs)):
            out = sess.output(rid)
            if len(out) == budgets[rid]:  # ran to completion
                assert out == oracle[rid]


# ------------------------------------------------------- chatbot workload


def test_chatbot_workload_replayable_and_multi_turn():
    """The chatbot mix (prefix-cache bench workload): bit-replayable from
    its seed; turn t+1's prompt EXTENDS turn t's exactly (history grows,
    never rewrites — the property prefix matching feeds on); the shared
    fraction of sessions opens with one identical system prompt; and
    session keys group turns."""
    from distributed_llms_example_tpu.serving.loadgen import chatbot_requests

    reqs, keys = chatbot_requests(sessions=10, turns=4, seed=3)
    again, keys2 = chatbot_requests(sessions=10, turns=4, seed=3)
    assert reqs == again and keys == keys2
    other, _ = chatbot_requests(sessions=10, turns=4, seed=4)
    assert reqs != other
    assert len(reqs) == 40 and len(set(keys)) == 10
    # group by session, in turn order (the interleave is turn-major)
    by_session: dict = {}
    for req, key in zip(reqs, keys):
        by_session.setdefault(key, []).append(req)
    for turns in by_session.values():
        assert len(turns) == 4
        for a, b in zip(turns, turns[1:]):
            assert b[: len(a)] == a and len(b) > len(a)
    # 90% of sessions open with the SAME system prompt, the rest diverge
    openers = [tuple(t[0][:12]) for t in by_session.values()]
    top = max(set(openers), key=openers.count)
    assert openers.count(top) == 9
    # max_len caps the submitted prompt while history keeps growing
    capped, _ = chatbot_requests(sessions=2, turns=6, seed=5, max_len=20)
    assert max(len(r) for r in capped) == 20
    with pytest.raises(ValueError):
        chatbot_requests(sessions=0, turns=4)
    with pytest.raises(ValueError):
        chatbot_requests(sessions=2, turns=4, shared_frac=1.5)
