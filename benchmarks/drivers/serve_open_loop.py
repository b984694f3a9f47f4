"""Serve driver: the program's ``ServingEngine`` / ``ServeSession`` in-process
under open-loop load at the rate fixed in the traffic file.

Latencies are taken on this driver's clock from the SCHEDULED arrival; a
request not finished when the bounded drain ends has failed, and its time to
first token counts as the whole run.  Once the window has closed a seeded
sample of the finished requests (the longest among them) is replayed through
the plain float32 reference, prompt and served tokens teacher-forced, and the
widest gap by which a served token's logit lies below the reference's best is
held to its limit.  Greedy decoding only (the engine's).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import check, loadgen, precision, program, spec as spec_mod, stats, text, weights
from benchmarks.harness.runtime import CompileCounter, Run, profiled

ANNOTATIONS = ("serve_step", "serve_submit")


def weights_config(cell) -> dict:
    """The configuration as the reference's ``param_spec`` reads it, with the
    serve recipe's ``weights`` values (an ``assumed`` of the configuration:
    why the served weights are drawn wider) laid over it."""
    return {**cell.config, **cell.recipe("weights", {})}


def build_session(run: Run):
    """(open session with every program warm, reference module)."""
    from distributed_llms_example_tpu.core.config import MeshConfig
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.core.precision import parse_dtype
    from distributed_llms_example_tpu.models import registry
    from distributed_llms_example_tpu.parallel.sharding import shard_params
    from distributed_llms_example_tpu.serving.engine import ServeConfig, ServingEngine

    cell, cfg = run.cell, run.cell.config
    ref = spec_mod.load_module("reference", cell.family)
    adapter = spec_mod.load_module("adapters", cell.family)
    lm = registry.load_model(program.register_bench_model(cfg, adapter), dtype=parse_dtype(cfg["dtypes"]["compute"]))
    mesh = build_mesh(MeshConfig(**cell.recipe("mesh", {"data": -1})), devices=jax.devices()[: cell.chips])
    params = weights.make_program_weights(
        ref.param_spec(weights_config(cell)), run.seed, program.to_program_tree(adapter.leaf_map(cfg)))
    params = shard_params(params, mesh)
    engine = ServingEngine(
        lm.module, lm.config, mesh,
        ServeConfig(max_slots=int(cell.recipe("max_slots")), prefill_batch=int(cell.recipe("prefill_batch")),
                    max_new_tokens=int(cell.recipe("max_new_tokens")),
                    max_source_length=int(cell.recipe("prompt_tokens"))),
        is_seq2seq=lm.is_seq2seq,
    )
    return engine.open(params), ref


def warm_up(session, run: Run) -> None:
    """Real requests through every path the window takes: a full prefill
    wave, decode rounds at several occupancies, eviction."""
    n = int(run.cell.recipe("warmup_requests"))
    prompts, _ = loadgen.requests(run.seed + 1, n, int(run.cell.recipe("prompt_tokens")), [4, 8])
    for i, p in enumerate(prompts):
        session.submit(p, max_new=4 + i % 5)
    while session.has_work():
        session.step()


def measure(session, run: Run, rate_rps: float, seconds: float) -> dict:
    """One open-loop window at ``rate_rps``; the numbers of every request."""
    cell = run.cell
    arrivals = loadgen.schedule(run.seed, rate_rps, seconds)
    prompts, budgets = loadgen.requests(
        run.seed, len(arrivals), int(cell.recipe("prompt_tokens")), cell.recipe("output_tokens"))
    out = loadgen.drive(session, prompts, budgets, arrivals, seconds=seconds,
                        drain_seconds=float(cell.recipe("drain_seconds")))
    rows = out["rows"]
    for r in rows:
        r["done"] = r["rid"] is not None and len(r["tokens_at"]) >= r["budget"]
    done = [r for r in rows if r["done"]]
    worst = seconds + float(cell.recipe("drain_seconds"))  # a failed request waited the whole run
    ttft = [(r["tokens_at"][0] - r["arrival"]) if r["done"] else worst for r in rows]
    gaps = [b - a for r in done for a, b in zip(r["tokens_at"], r["tokens_at"][1:])]
    late = [r["submit"] - r["arrival"] for r in rows if r["submit"] is not None]
    # every token the driver saw inside [t0, t0 + seconds], of whichever request: all the
    # work of the window over all its time (what the drain emits afterwards is not the window's)
    close = out["t0"] + seconds
    tokens_in_window = sum(1 for r in rows for t in r["tokens_at"] if t <= close)
    waves = [dt for dt, admitted in out["rounds"] if admitted]
    seen, admitted = [r["rounds_at"] for r in done], [n for _, n in out["rounds"]]
    busy = sum(dt for dt, _ in out["rounds"])
    return {
        "rows": rows, "prompts": prompts, "done": done, "ttft_s": ttft, "gaps_s": gaps, "late_s": late,
        "rounds": out["rounds"], "wall_s": out["wall_s"], "offered": len(rows), "completed": len(done),
        "tokens_per_s": tokens_in_window / seconds, "rate_rps": rate_rps,
        "tokens_offered": sum(r["budget"] for r in rows), "tokens_in_window": tokens_in_window,
        "slots_live_mean": sum(dt * n for (dt, _), n in zip(out["rounds"], out["slots_live"])) / max(busy, 1e-9),
        "slots_live_peak": max(out["slots_live"], default=0),
        # every 5 s: the plain round's median, the longest round and this process's own CPU seconds (the
        # chip machine's /proc/stat reads zeros).  A one-chip machine shares its host: this line says
        # when in a run that reads far off the rounds grew, and whether the process was kept off the CPU
        "by_5s": [
            {"from_s": round(ta, 1),
             "plain_round_ms_p50": round(stats.percentile(
                 [dt for dt, adm in out["rounds"][ia:ib] if not adm] or [0.0], 0.5) * 1e3, 2),
             "longest_round_ms": round(max((dt for dt, _ in out["rounds"][ia:ib]), default=0.0) * 1e3, 1),
             "own_cpu_s": round(hb - ha, 3)}
            for (ta, ia, ha), (_tb, ib, hb) in zip(out["probes"], out["probes"][1:])],
        "queue_growing": loadgen.queue_growing(
            [(r["tokens_at"][0] - r["arrival"]) if r["tokens_at"] else None for r in rows],
            list(arrivals), out["wall_s"]),
        "share_of_rounds_with_a_wave": len(waves) / max(1, len(out["rounds"])),
        # of the gaps ``gap_p95_ms`` is taken over (the completed requests'), those that waited out a wave
        "share_of_gaps_with_a_wave": loadgen.share_of_gaps_with_a_wave(seen, admitted),
        # ... and a wave of several requests, which runs the program of ``prefill_batch`` rows
        "share_of_gaps_with_a_full_wave": loadgen.share_of_gaps_with_a_wave(seen, admitted, of_at_least=2),
        "wave_ms_p50": stats.percentile(waves, 0.5) * 1e3 if waves else None,
    }


def reference_logits(ref, cfg: dict, seed: int, prompts: list, served: list, dot_name: str = "fp32") -> np.ndarray:
    """Logits (request, position, vocab) of the plain reference, teacher-forced
    on each prompt and its served tokens, at the named precision."""
    start, _ = ref.decoder_start(cfg)
    params = weights.make_reference_weights(ref.param_spec(cfg), seed)
    ids = jnp.asarray(np.asarray(prompts, np.int32))
    dec = np.zeros((len(served), max(len(s) for s in served)), np.int32)
    for i, s in enumerate(served):
        dec[i, 0] = start
        dec[i, 1: len(s)] = s[:-1]
    fwd = jax.jit(lambda p, a, m, d: ref.forward(p, cfg, a, m, d, precision.make_dot(dot_name)))
    return np.asarray(jax.device_get(fwd(params, ids, jnp.ones_like(ids), jnp.asarray(dec))), np.float64)


def gaps_below_best(logits: np.ndarray, tokens: list, forced: dict) -> list[float]:
    """For each token not forced by the generation config: how far its logit
    lies below the best logit of its position."""
    return [
        float(logits[i, t].max() - logits[i, t, int(tok)])
        for i, row in enumerate(tokens) for t, tok in enumerate(row) if t not in forced
    ]


def run(run: Run) -> dict:
    cell, cfg = run.cell, weights_config(run.cell)
    counter = CompileCounter()
    t_driver = time.perf_counter()
    session, ref = build_session(run)
    t_open = time.perf_counter()
    warm_up(session, run)
    t_warm = time.perf_counter()
    rate = float(cell.recipe("rate_rps"))

    with counter.window():
        t0 = time.perf_counter()
        m = measure(session, run, rate, run.seconds)
    memory_peak = program.memory_peak_bytes(cell.chips)
    failed = m["offered"] - m["completed"]
    run.emit({"phase": "window", "rate_rps": rate, "offered": m["offered"], "completed": m["completed"],
              "failed": failed, "wall_s": m["wall_s"], "queue_growing": m["queue_growing"],
              "tokens_offered": m["tokens_offered"], "tokens_in_window": m["tokens_in_window"],
              "tokens_in_window_per_s": m["tokens_per_s"],
              "slots": int(cell.recipe("max_slots")), "slots_live_mean": m["slots_live_mean"],
              "slots_live_peak": m["slots_live_peak"], "by_5s": m["by_5s"],
              "ttft_ms": {k: v * (1e3 if k != "n" else 1) for k, v in stats.summary(m["ttft_s"]).items()},
              "gap_ms": {k: v * (1e3 if k != "n" else 1) for k, v in stats.summary(m["gaps_s"]).items()},
              "late_ms": {k: v * (1e3 if k != "n" else 1) for k, v in stats.summary(m["late_s"]).items()},
              "rounds": len(m["rounds"]), "share_of_rounds_with_a_wave": m["share_of_rounds_with_a_wave"],
              "share_of_gaps_with_a_wave": m["share_of_gaps_with_a_wave"],
              "share_of_gaps_with_a_full_wave": m["share_of_gaps_with_a_full_wave"], "wave_ms_p50": m["wave_ms_p50"],
              "setup_pieces_s": {"imports_and_device": t_driver - run.t_start,
                                 "engine_open_with_compile": t_open - t_driver, "warm_up_requests": t_warm - t_open}})

    layers = {"cell": cell, "config": cfg, "peaks": run.peaks, "step_times": m["rounds"],
              "late_s": m["late_s"], "share_of_gaps_with_a_wave": m["share_of_gaps_with_a_wave"],
              "share_of_gaps_with_a_full_wave": m["share_of_gaps_with_a_full_wave"], "trace": None}
    if run.trace:
        with profiled(run, ANNOTATIONS, "serve_step", layers):
            measure(session, run, rate, float(cell.recipe("trace_seconds", 2.0)))

    # ---- the sample the reference replays: seeded, the longest among it
    done = m["done"]
    k = min(int(cell.recipe("check_requests")), len(done))
    correct, ref_s = False, 0.0
    if k:
        pick = set(int(j) for j in text.rng_for(run.seed, 4).choice(len(done), size=k, replace=False))
        longest = max(range(len(done)), key=lambda j: len(done[j]["tokens_at"]))
        if longest not in pick:
            pick.pop()
            pick.add(longest)
        sample = [done[j] for j in sorted(pick)]
        t_ref = time.perf_counter()
        served = [list(session.outputs[r["rid"]]) for r in sample]
        logits = reference_logits(ref, cfg, run.seed, [m["prompts"][r["index"]] for r in sample], served)
        gaps = gaps_below_best(logits, served, ref.forced_tokens(cfg, int(cell.recipe("max_new_tokens"))))
        ref_s = time.perf_counter() - t_ref
        run.emit({"phase": "check", "requests": k, "served_tokens_compared": len(gaps),
                  "gap": stats.summary(gaps), "share_not_the_references_best": sum(1 for x in gaps if x > 0) / len(gaps),
                  "reference_s": ref_s})
        numbers = lambda g: {"served_logit_gap_max": max(g)}  # noqa: E731
        correct = check.judge(numbers(gaps), cfg["check"]["limits"]["serve_open_loop"])
        if run.control:
            # the token the lower precision puts first at each position of the
            # same prompts and tokens, judged by the float32 reference
            low = reference_logits(ref, cfg, run.seed, [m["prompts"][r["index"]] for r in sample],
                                   served, cfg["check"]["control"])
            first = [list(np.argmax(low[i, : len(s)], axis=-1)) for i, s in enumerate(served)]
            check.control_caught(
                numbers(gaps_below_best(logits, first, ref.forced_tokens(cfg, int(cell.recipe("max_new_tokens"))))),
                cfg["check"]["limits"]["serve_open_loop"])
    session.finalize()
    return {
        "correct": correct, "attempted": m["offered"], "failed": failed,
        "end_to_end": {
            "serve_tokens_per_s": m["tokens_per_s"],
            "ttft_p95_ms": stats.percentile(m["ttft_s"], 0.95) * 1e3,
            "gap_p95_ms": stats.percentile(m["gaps_s"], 0.95) * 1e3 if m["gaps_s"] else None,
            "setup_s": t0 - run.t_start,
        },
        "layers": layers, "compiles_in_window": counter.count, "memory_peak_bytes": memory_peak,
        "reference_s": ref_s,
    }
