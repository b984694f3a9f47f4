"""Open-loop load: the schedule, the loop that drives a session, the knee rule.

Copied in substance from the program's ``serving/loadgen.py`` (schedule,
open-loop driver, ``detect_knee``, ``queue_growing``) so that later PRs may
change the program and not the yardstick; PERF.md lists the originals.  Two
things differ on purpose: the arrivals of one rate are ONE multiset of
exponential gaps that the seed only permutes (so every seed offers the same
number of requests over the same span: Poisson-like, and steady from seed to
seed), and every token is stamped on this clock as the driver sees it.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Sequence

import jax
import numpy as np

from benchmarks.harness import stats, text


PROBE_EVERY_S = 5.0


def schedule(seed: int, rate_rps: float, seconds: float) -> np.ndarray:
    """Arrival offsets in [0, seconds): ``round(rate * seconds)`` arrivals
    whose gaps are the exponential quantiles at ``rate``, in the seed's order."""
    n = max(1, int(round(rate_rps * seconds)))
    gaps = np.asarray(stats.exponential_quantiles(rate_rps, n))
    order = text.rng_for(seed, 2).permutation(n)
    arrivals = np.cumsum(gaps[order])
    return arrivals - gaps[order][0] * 0.5  # first arrival half a gap in, none at or past `seconds`


def requests(seed: int, n: int, prompt_tokens: int, output_tokens: Sequence[int]) -> tuple[list, list]:
    """``n`` prompts of exactly ``prompt_tokens`` byte tokens (distinct seeded
    text, closing EOS) and their output budgets: the same multiset of budgets
    for every seed, in the seed's order."""
    rng = text.rng_for(seed, 3)
    budgets = stats.stratified(int(output_tokens[0]), int(output_tokens[1]), n)
    budgets = [budgets[int(j)] for j in rng.permutation(n)]
    prompts = [text.encode(text.words_text(rng, prompt_tokens), prompt_tokens) for _ in range(n)]
    return prompts, budgets


def drive(session: Any, prompts: list, budgets: list, arrivals: np.ndarray, *,
          seconds: float, drain_seconds: float, clock: Callable[[], float] = time.perf_counter) -> dict:
    """Submit request ``i`` when ``arrivals[i]`` has passed, never waiting on a
    completion; otherwise step the session.  Arrivals stop at ``seconds``; the
    loop then drains for at most ``drain_seconds``.  Returns per-request rows
    (scheduled arrival, submit instant, the instant each token was seen and
    the round that showed it),
    per-round (duration, requests admitted: 0 in a plain round) pairs and, beside them, the slots that
    held a request in each round, and every ``PROBE_EVERY_S`` a probe
    (offset, rounds so far, this process's CPU seconds) for the log."""
    n = len(prompts)
    t0 = clock()
    due = [t0 + float(a) for a in arrivals]
    rows = [{"index": i, "arrival": due[i], "submit": None, "tokens_at": [], "rounds_at": [],
             "budget": budgets[i], "rid": None}
            for i in range(n)]
    by_rid: dict[int, int] = {}
    live: set[int] = set()
    rounds: list[tuple[float, int]] = []
    slots_live: list[int] = []
    probes: list[tuple[float, int, float]] = []
    i = 0
    while True:
        now = clock()
        if now - t0 >= len(probes) * PROBE_EVERY_S:
            probes.append((now - t0, len(rounds), time.process_time()))
        while i < n and due[i] <= now:
            with jax.profiler.TraceAnnotation("serve_submit"):
                rid = session.submit(prompts[i], max_new=budgets[i], arrival=due[i])
            rows[i]["submit"], rows[i]["rid"] = clock(), rid
            by_rid[rid] = i
            live.add(rid)
            i += 1
        if i >= n and not session.has_work():
            break
        if now - t0 > seconds + drain_seconds:
            break
        if session.has_work():
            waiting = session.queue_depth
            ts = clock()
            with jax.profiler.TraceAnnotation("serve_step"):
                finished = session.step()
            te = clock()
            rounds.append((te - ts, waiting - session.queue_depth))
            slots_live.append(len(live) - session.queue_depth)  # submitted, not queued: in a slot this round
            for rid in list(live):
                row = rows[by_rid[rid]]
                new = len(session.outputs[rid]) - len(row["tokens_at"])
                row["tokens_at"].extend([te] * new)
                row["rounds_at"].extend([len(rounds) - 1] * new)
            live.difference_update(finished)
        else:
            wait = due[i] - clock()
            if wait > 0.002:
                time.sleep(wait - 0.001)  # then spin the last millisecond
    probes.append((clock() - t0, len(rounds), time.process_time()))
    return {"t0": t0, "wall_s": clock() - t0, "rows": rows, "rounds": rounds, "slots_live": slots_live,
            "probes": probes}


def share_of_gaps_with_a_wave(rounds_at: Sequence[Sequence[int]], admitted: Sequence[int], *,
                              of_at_least: int = 1) -> float | None:
    """Of the inter-token gaps of the given requests, the share that hold a
    prefill wave of at least ``of_at_least`` requests.  ``rounds_at[r]`` is,
    token by token, the round that showed request ``r`` the token;
    ``admitted[k]`` is how many requests round ``k`` admitted.  The gap between
    two tokens seen in rounds ``a`` and ``b`` holds a wave when one of the rounds
    ``a + 1 .. b`` admitted: every live slot waits out every wave, so one wave
    under ``n`` slots that are between two tokens is ``n`` such gaps.  This,
    not the share of ROUNDS with a wave, is what places the 95th percentile of
    the gaps: above 5 % it is a wave round's gap.  A wave of two requests or
    more runs the program of ``prefill_batch`` rows: above 5 % of gaps under
    THOSE, the 95th percentile is a full wave round's.  (A seq2seq wave's
    device time lands in the round AFTER the one that admitted; that round's
    gaps are as many, to a slot.)  None without a gap."""
    waves_up_to = [0]
    for n in admitted:
        waves_up_to.append(waves_up_to[-1] + (n >= of_at_least))
    gaps = held = 0
    for seen in rounds_at:
        for a, b in zip(seen, seen[1:]):
            gaps += 1
            held += waves_up_to[b + 1] > waves_up_to[a + 1]
    return held / gaps if gaps else None


def queue_growing(ttft_s: Sequence[float | None], arrivals_s: Sequence[float], wall_s: float, *,
                  growth_x: float = 2.0, min_wait_s: float = 5e-3) -> bool:
    """Unbounded-queue verdict for one run (the program's rule): an unfinished
    tail, or the last quarter of arrivals waiting ``growth_x`` times the first
    quarter's (and at least ``min_wait_s``)."""
    if any(t is None for t in ttft_s):
        return True
    n = len(ttft_s)
    if n < 4:
        return False
    k = max(n // 4, 1)
    head = sum(ttft_s[:k]) / k
    tail = sum(ttft_s[-k:]) / k
    return tail > growth_x * max(head, 1e-9) and tail > min_wait_s


def detect_knee(points: Sequence[dict], *, track_tol: float = 0.95) -> float | None:
    """The first offered rate (ascending) that stopped tracking the offer:
    a growing queue, or completions under ``track_tol`` of the offered
    requests.  None when every point tracks."""
    for p in points:
        if p["queue_growing"] or p["completed"] < track_tol * p["offered"]:
            return float(p["rate_rps"])
    return None
