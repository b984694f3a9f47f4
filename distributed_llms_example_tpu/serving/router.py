"""Fault-tolerant serving tier: a replica router over N engine replicas.

The PR 7 engine is a single controller: one crash silently loses every
in-flight request.  This module is the tier above it — a host-side
router spreading requests over N ``ServingEngine`` replicas (in-process
on this container; each replica owns its own compiled programs and slot
state, so the replica boundary is exactly the seam a multi-host router
needs later), with the robustness core the training stack already has
for checkpoints (PRs 6/14) applied to serving:

- **dispatch**: session→replica affinity (a ``session`` key maps to one
  replica while that replica lives, so a conversation's KV locality is
  preservable later) with queue-depth-aware placement otherwise — the
  same ``queue_depth``/occupancy numbers the engine's ``serve_window``
  stream stamps, read live off each replica's session;
- **health machine** per replica: ``live → suspect → dead`` driven by
  heartbeat-miss / step-stall detection (a replica with work whose
  session ``progress`` counter stops moving misses beats), plus
  ``draining → drained`` for graceful retirement.  A step that RAISES is
  an immediate crash → dead;
- **retry / re-prefill**: every request a dead replica held (queued or
  mid-decode) is re-dispatched to a surviving replica with its original
  prompt, budget, and sampling state (greedy — the sampling state IS the
  prompt), bounded by ``max_retries`` with deterministic tick-unit
  exponential backoff (utils/backoff.py ``backoff_ticks``).  Serving is
  stateless by construction, and greedy decode is schedule-independent
  (the PR 7 engine-vs-static pins), so the re-prefilled output is
  BIT-IDENTICAL to an unfailed run — partial tokens from the dead
  replica are discarded, never surfaced;
- **admission control / backpressure**: a bounded router queue
  (``max_queue``); over-pressure submissions are SHED (counted,
  reported) or DEFERRED to a client-side buffer per ``shed_policy``
  instead of queueing unboundedly — the router-level twin of PR 13's
  pool-pressure admit-deferral, which keeps operating underneath (a
  replica whose paged pool is short defers its own admissions);
- **deadlines**: per-request wall/tick deadlines checked while a request
  waits (queued, deferred, or backing off) — a request that can no
  longer be served in time is shed with a reason, not silently late;
- **graceful drain**: ``drain_replica(i)`` stops admitting to a replica,
  re-dispatches its queued requests, lets live slots finish, then
  retires it — zero requests lost, nothing checkpointed, because there
  is nothing to checkpoint.

Chaos (obs/chaos.py serving kinds, ticks = router scheduler ticks):
``replica_crash@K`` raises from the busiest replica's step at tick K;
``replica_stall@K`` wedges it (no progress, no exception — only the
heartbeat-miss detector can catch it); ``request_storm@K`` injects a
synthetic burst through admission control.  Every failure path in this
module is reachable from the grammar, and ``obs.report --strict`` stays
green exactly when every observed serving fault is one the harness
injected.

Honest scope notes: replicas here are in-process, so an ORGANIC wedged
step would block the single scheduler thread — the stall detector's
organic trigger is a replica that stops progressing across ticks (e.g.
a paged pool livelock), while a truly hung device call needs the
multi-host router this seam is built for.  Organic crashes (any
exception out of a replica's step) take the full detect→retry path.

Obs events: ``router_window`` (cadence), ``replica_health``
(transitions, ``local``), ``serve_retry`` / ``serve_shed`` per
occurrence, and a final ``router_summary`` carrying request-level MTTR,
retry rate, shed counts and the goodput fields — what
``obs.report --strict --max-request-retry-rate /
--min-serve-goodput-frac`` gates on.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Sequence

from distributed_llms_example_tpu.obs import sink as sink_mod
from distributed_llms_example_tpu.obs.chaos import ChaosSchedule
from distributed_llms_example_tpu.serving.engine import (
    ServingEngine,
    compute_goodput,
)
from distributed_llms_example_tpu.utils.backoff import backoff_ticks
from distributed_llms_example_tpu.utils.jsonlog import log_json

HEALTH_STATES = ("live", "suspect", "dead", "draining", "drained")


@dataclasses.dataclass(frozen=True)
class RouterConfig:
    """Router policy knobs.  Tick-unit fields are deterministic by
    design: the failure tests replay bit-for-bit.

    ``max_retries``: re-dispatch budget per request after replica
    failures (exceeding it sheds the request — bounded retry, never a
    hot loop).  ``retry_backoff_ticks``/``retry_backoff_cap_ticks``: the
    capped exponential re-dispatch delay (utils/backoff.py).
    ``suspect_after_ticks``/``dead_after_ticks``: missed heartbeats
    (ticks without session progress while holding work) before live →
    suspect → dead.  ``max_queue``: router queue bound (0 = unbounded —
    admission control off).  ``shed_policy``: what happens to a
    submission over ``max_queue`` — "shed" rejects it now, "defer" parks
    it client-side and admits when the queue drains.
    ``replica_queue_depth``: per-replica dispatch cap (0 = the engine's
    prefill chunk).  ``deadline_s``: default per-request wall deadline
    (0 = none).  ``storm_size``/``storm_deadline_ticks``: the
    ``request_storm`` chaos burst's size (0 = auto) and the synthetic
    requests' tick deadline (storms must shed, not starve real work).
    """

    max_retries: int = 2
    retry_backoff_ticks: int = 2
    retry_backoff_cap_ticks: int = 16
    suspect_after_ticks: int = 3
    dead_after_ticks: int = 6
    max_queue: int = 0
    shed_policy: str = "defer"  # "defer" | "shed"
    replica_queue_depth: int = 0
    deadline_s: float = 0.0
    log_every_ticks: int = 50
    storm_size: int = 0
    storm_deadline_ticks: int = 64
    chaos: ChaosSchedule | None = None

    def __post_init__(self):
        if self.shed_policy not in ("defer", "shed"):
            raise ValueError(
                f"shed_policy={self.shed_policy!r}: must be 'defer' or 'shed'"
            )
        if self.max_retries < 0:
            raise ValueError("max_retries must be >= 0")
        if self.dead_after_ticks <= self.suspect_after_ticks:
            raise ValueError(
                "dead_after_ticks must exceed suspect_after_ticks "
                "(suspect is the earlier rung of the same detector)"
            )


@dataclasses.dataclass
class _Request:
    rid: int
    tokens: list
    mask: Any
    budget: int | None
    session_key: Any
    synthetic: bool
    arrival_wall: float  # scheduled arrival (== submit_wall closed-loop)
    submit_wall: float
    submit_tick: int
    deadline_wall: float | None  # absolute perf_counter instant
    deadline_tick: int | None
    retries: int = 0
    ready_tick: int = 0
    replica: int | None = None  # current assignment
    local: int | None = None  # session-local rid on that replica
    done: bool = False
    shed: bool = False
    shed_reason: str = ""
    out: list = dataclasses.field(default_factory=list)
    ttft_s: float | None = None
    done_wall: float | None = None
    first_fail_wall: float | None = None


@dataclasses.dataclass
class _Replica:
    idx: int
    engine: ServingEngine
    session: Any
    state: str = "live"
    last_beat: int = 0
    crashes: int = 0


class ReplicaRouter:
    """The scheduler: one ``tick()`` = chaos → deadlines → dispatch →
    step every serving replica → health update → cadence window.
    ``serve()`` is the batch driver (submit everything, tick until every
    request is done or shed, finalize)."""

    def __init__(
        self,
        engines: Sequence[ServingEngine],
        params: Any,
        cfg: RouterConfig | None = None,
    ):
        if not engines:
            raise ValueError("the replica pool needs at least one engine")
        self.cfg = cfg or RouterConfig()
        self.params = params
        self.replicas = [
            _Replica(idx=i, engine=e, session=e.open(params, replica=i))
            for i, e in enumerate(engines)
        ]
        self._depth_cap = self.cfg.replica_queue_depth or max(
            e.prefill_batch for e in engines
        )
        self.requests: list[_Request] = []
        self.queue: "collections.deque[_Request]" = collections.deque()
        self.deferred: "collections.deque[_Request]" = collections.deque()
        self.affinity: dict[Any, int] = {}
        self.ticks = 0
        self.t_open = time.perf_counter()
        self.admitting = True  # drain() flips it
        # counters / degraded-phase stamps
        self.retries_total = 0
        self.shed_by_reason: dict[str, int] = {}
        self._chaos_stalled: set[int] = set()
        self._requeued_outstanding: set[int] = set()
        self.t_fail: float | None = None  # first replica failure (wall)
        self.t_recovered: float | None = None  # last failure-requeue re-dispatched
        self.last_stats: dict | None = None
        self._finalized = False

    # ------------------------------------------------------------- intake
    def submit(
        self,
        tokens: Sequence[int],
        *,
        max_new: int | None = None,
        attention_mask: Sequence[int] | None = None,
        session: Any = None,
        deadline_s: float | None = None,
        deadline_ticks: int | None = None,
        synthetic: bool = False,
        arrival: float | None = None,
    ) -> int:
        """Offer one request to the router.  Admission control applies
        HERE: a full queue sheds (policy "shed") or defers (policy
        "defer" — parked client-side, admitted as the queue drains)
        instead of growing without bound.  Returns the router-global
        request id either way; a shed request's output stays empty and
        its reason rides the summary.  ``arrival`` (absolute
        perf_counter instant, default: now) is the open-loop scheduled
        arrival — it threads through dispatch to the replica session so
        the ``serve_request`` stream's arrival→submit queue-delay stage
        covers router-held time too."""
        now = time.perf_counter()
        ddl_s = self.cfg.deadline_s if deadline_s is None else deadline_s
        req = _Request(
            rid=len(self.requests),
            tokens=list(tokens),
            mask=list(attention_mask) if attention_mask is not None else None,
            budget=max_new,
            session_key=session,
            synthetic=synthetic,
            arrival_wall=float(arrival) if arrival is not None else now,
            submit_wall=now,
            submit_tick=self.ticks,
            deadline_wall=(now + ddl_s) if ddl_s and ddl_s > 0 else None,
            deadline_tick=(
                self.ticks + int(deadline_ticks)
                if deadline_ticks is not None
                else None
            ),
        )
        self.requests.append(req)
        if not self.admitting:
            self._shed(req, "draining")
            return req.rid
        if self.cfg.max_queue and len(self.queue) >= self.cfg.max_queue:
            if self.cfg.shed_policy == "shed":
                self._shed(req, "queue_full")
            else:
                self.deferred.append(req)
        else:
            self.queue.append(req)
        return req.rid

    # ------------------------------------------------------------ helpers
    def _shed(self, req: _Request, reason: str) -> None:
        req.shed, req.shed_reason = True, reason
        self._requeued_outstanding.discard(req.rid)
        self.shed_by_reason[reason] = self.shed_by_reason.get(reason, 0) + 1
        log_json({
            "event": "serve_shed",
            "request": req.rid,
            "reason": reason,
            "tick": self.ticks,
            "synthetic": req.synthetic,
        })

    def _emit_health(self, r: _Replica, old: str, new: str, *,
                     reason: str, **extra: Any) -> None:
        r.state = new
        # local: single-process today, but the event is per-replica
        # telemetry by nature — the multi-host router will fan it out
        sink_mod.emit({
            "event": "replica_health",
            "replica": r.idx,
            "from": old,
            "to": new,
            "tick": self.ticks,
            "reason": reason,
            **extra,
        }, local=True)

    def _live(self) -> list[_Replica]:
        return [r for r in self.replicas if r.state == "live"]

    def _serving(self) -> list[_Replica]:
        # replicas still worth stepping: live, suspect (maybe just slow),
        # draining (finishing their slots)
        return [
            r for r in self.replicas
            if r.state in ("live", "suspect", "draining")
        ]

    def _pick_victim(self) -> _Replica | None:
        """The chaos target: the busiest steppable replica (most active
        decode slots, ties to the lowest id) — deterministic, and the
        most impactful kill."""
        cands = [r for r in self.replicas if r.state in ("live", "suspect")]
        if not cands:
            return None
        return max(cands, key=lambda r: (r.session.active_count, -r.idx))

    # ------------------------------------------------------------ failure
    def _fail_replica(self, r: _Replica, *, cause: str, reason: str) -> None:
        """A replica is gone (crash raised, or the stall detector gave
        up): mark it dead and re-dispatch every request it held — queued
        OR mid-decode — onto the surviving pool.  Partial tokens are
        discarded; the re-prefill regenerates from the original prompt,
        so greedy output stays bit-identical to an unfailed run."""
        now = time.perf_counter()
        if self.t_fail is None:
            self.t_fail = now
        r.crashes += 1
        self._emit_health(
            r, r.state, "dead", reason=reason, cause=cause,
            since_tick=r.last_beat,
        )
        self._chaos_stalled.discard(r.idx)
        held = [
            q for q in self.requests
            if q.replica == r.idx and not q.done and not q.shed
        ]
        for q in held:
            had_tokens = bool(q.local is not None
                              and r.session.outputs[q.local])
            q.replica, q.local = None, None
            q.retries += 1
            self.retries_total += 1
            if q.first_fail_wall is None:
                q.first_fail_wall = now
            if q.retries > self.cfg.max_retries:
                self._shed(q, "retries_exhausted")
                continue
            q.ready_tick = self.ticks + backoff_ticks(
                q.retries,
                base=self.cfg.retry_backoff_ticks,
                cap=self.cfg.retry_backoff_cap_ticks,
            )
            self._requeued_outstanding.add(q.rid)
            self.queue.appendleft(q)  # failed work re-queues at the front
            log_json({
                "event": "serve_retry",
                "request": q.rid,
                "replica": r.idx,
                "retries": q.retries,
                "ready_tick": q.ready_tick,
                "tick": self.ticks,
                "had_tokens": had_tokens,
                "synthetic": q.synthetic,
                "reason": cause,
            })
        # the session (and its device state) is gone with the replica —
        # but the paged pool's free list is HOST state on the engine: if
        # the engine object is ever reused (tests, bench reruns), the
        # dead session's blocks must return or they leak forever
        if r.engine.paged and r.session is not None:
            for blocks in r.session.slot_blocks:
                if blocks:
                    r.engine.pool.free(blocks)
            if r.engine.prefix:
                # the dead replica's warm set dies with it: its pool
                # content is device state that no re-prefilled survivor
                # may match against — a follow-up turn re-prefills cold
                # on whichever replica inherits the session
                r.engine.pool.drop_warm()
        r.session = None

    # ------------------------------------------------------------- drain
    def drain_replica(self, idx: int) -> None:
        """Graceful retirement: stop admitting to the replica, re-route
        its queued requests, let its live slots decode to completion —
        then it parks as ``drained``.  Nothing is checkpointed: serving
        state is derived entirely from the request stream."""
        r = self.replicas[idx]
        if r.state not in ("live", "suspect"):
            return
        self._emit_health(r, r.state, "draining", reason="operator drain")
        taken = set(r.session.take_pending())
        for q in self.requests:
            if q.rid in taken:
                q.replica, q.local = None, None
                q.ready_tick = self.ticks  # no lost work: no backoff
                self.queue.appendleft(q)
                log_json({
                    "event": "serve_retry",
                    "request": q.rid,
                    "replica": r.idx,
                    "retries": q.retries,  # drain re-dispatch is not a retry
                    "ready_tick": q.ready_tick,
                    "tick": self.ticks,
                    "had_tokens": False,
                    "synthetic": q.synthetic,
                    "reason": "drain",
                })

    def drain(self) -> None:
        """Router-wide graceful drain: stop admitting NEW submissions
        (they shed with reason "draining"); everything already accepted
        finishes."""
        self.admitting = False

    # ------------------------------------------------------------ routing
    def _route(self, req: _Request) -> _Replica | None:
        """Pick the replica for a request: session affinity while the
        mapped replica is live and has room, else the live replica with
        the smallest (queued + active) load — the dispatch signal the
        engine's serve_window stamps as queue_depth/occupancy, read live
        off each session."""
        def depth(r: _Replica) -> int:
            return r.session.queue_depth + r.session.active_count

        live = self._live()
        if not live:
            return None
        if req.session_key is not None:
            mapped = self.affinity.get(req.session_key)
            if mapped is not None:
                r = self.replicas[mapped]
                if r.state == "live" and depth(r) < self._depth_cap:
                    return r
        best = min(live, key=lambda r: (depth(r), r.idx))
        if depth(best) >= self._depth_cap:
            return None
        if req.session_key is not None:
            self.affinity[req.session_key] = best.idx
        return best

    def _dispatch(self) -> None:
        # FIFO over READY requests (backoff holds a request out without
        # blocking the ones behind it)
        held: list[_Request] = []
        while self.queue:
            req = self.queue.popleft()
            if req.shed or req.done:
                continue
            if req.ready_tick > self.ticks:
                held.append(req)
                continue
            target = self._route(req)
            if target is None:
                held.append(req)
                break  # no capacity anywhere this tick
            req.local = target.session.submit(
                req.tokens,
                max_new=req.budget,
                attention_mask=req.mask,
                label=req.rid,
                arrival=req.arrival_wall,
            )
            req.replica = target.idx
            if req.rid in self._requeued_outstanding:
                self._requeued_outstanding.discard(req.rid)
                if not self._requeued_outstanding and self.t_fail is not None:
                    # every failure-displaced request is re-admitted: the
                    # degraded phase ends here (bench's before/during/after)
                    self.t_recovered = time.perf_counter()
        for req in reversed(held):
            self.queue.appendleft(req)

    # ----------------------------------------------------------- deadline
    def _sweep_deadlines(self) -> None:
        now = time.perf_counter()

        def expired(q: _Request) -> bool:
            if q.deadline_wall is not None and now > q.deadline_wall:
                return True
            return q.deadline_tick is not None and self.ticks > q.deadline_tick

        for buf in (self.queue, self.deferred):
            for q in list(buf):
                if expired(q):
                    buf.remove(q)
                    self._shed(q, "deadline")

    def _promote_deferred(self) -> None:
        while self.deferred and (
            not self.cfg.max_queue or len(self.queue) < self.cfg.max_queue
        ):
            self.queue.append(self.deferred.popleft())

    # -------------------------------------------------------------- chaos
    def _take_chaos(self) -> None:
        chaos = self.cfg.chaos
        if not chaos:
            return
        if chaos.take("replica_crash", self.ticks):
            victim = self._pick_victim()
            if victim is not None:
                # the injected crash IS an exception out of the replica's
                # step path: route it through the one failure handler
                self._fail_replica(
                    victim, cause="crash",
                    reason="chaos: injected replica crash",
                )
        if chaos.take("replica_stall", self.ticks):
            victim = self._pick_victim()
            if victim is not None:
                # wedge, don't kill: the replica stops progressing and
                # only the heartbeat-miss detector can notice
                self._chaos_stalled.add(victim.idx)
        if chaos.take("request_storm", self.ticks):
            real = [q for q in self.requests if not q.synthetic]
            if real:
                size = self.cfg.storm_size or 2 * (
                    self.cfg.max_queue or 2 * self._depth_cap
                )
                for i in range(size):
                    src = real[i % len(real)]
                    self.submit(
                        src.tokens,
                        max_new=src.budget,
                        attention_mask=src.mask,
                        deadline_ticks=self.cfg.storm_deadline_ticks,
                        synthetic=True,
                    )

    # ------------------------------------------------------------ the tick
    def tick(self) -> None:
        self.ticks += 1
        self._take_chaos()
        self._sweep_deadlines()
        self._promote_deferred()
        self._dispatch()
        now = time.perf_counter()
        for r in self._serving():
            if not r.session.has_work():
                if r.state == "draining":
                    self._emit_health(
                        r, "draining", "drained", reason="slots empty"
                    )
                else:
                    r.last_beat = self.ticks  # idle is not a missed beat
                continue
            if r.idx in self._chaos_stalled:
                continue  # wedged: no step, no progress, no beat
            before = r.session.progress
            try:
                finished = r.session.step()
            except Exception as e:  # noqa: BLE001 — a replica crash is any escape
                self._fail_replica(
                    r, cause="crash", reason=f"step raised: {str(e)[:200]}"
                )
                continue
            if r.session.progress > before:
                r.last_beat = self.ticks
                if r.state == "suspect":
                    self._emit_health(
                        r, "suspect", "live", reason="progress resumed"
                    )
            for local in finished:
                self._complete(r, local, now)
        self._update_health()
        if (
            self.cfg.log_every_ticks
            and self.ticks % self.cfg.log_every_ticks == 0
        ):
            self._emit_window()

    def _complete(self, r: _Replica, local: int, now: float) -> None:
        rid = r.session.labels[local]
        req = self.requests[rid]
        req.done = True
        req.out = list(r.session.output(local))
        req.done_wall = now
        ft = r.session.first_token_wall(local)
        if ft is not None:
            # TTFT from the ORIGINAL submit: a retried request's first
            # token is the one the client actually received — failure +
            # re-prefill time lands in the tail, where the degraded-mode
            # bench must see it
            req.ttft_s = ft - req.submit_wall
        if req.session_key is not None:
            self.affinity[req.session_key] = r.idx

    def _update_health(self) -> None:
        # draining replicas stay under the stall detector too: a wedged
        # replica mid-drain must still be declared dead (and its slot
        # work re-prefilled) or the drain would hang forever
        for r in self.replicas:
            if r.state not in ("live", "suspect", "draining"):
                continue
            if not (r.session.has_work() or r.idx in self._chaos_stalled):
                continue
            missed = self.ticks - r.last_beat
            if missed > self.cfg.dead_after_ticks:
                self._fail_replica(
                    r, cause="stall",
                    reason=(
                        f"no progress for {missed} ticks with work queued "
                        "(heartbeat-miss / step-stall detector)"
                    ),
                )
            elif missed > self.cfg.suspect_after_ticks and r.state == "live":
                self._emit_health(
                    r, "live", "suspect",
                    reason=f"no progress for {missed} ticks",
                )

    def _emit_window(self) -> None:
        log_json({
            "event": "router_window",
            "tick": self.ticks,
            "queue_depth": len(self.queue),
            "deferred": len(self.deferred),
            "retries": self.retries_total,
            "shed": sum(self.shed_by_reason.values()),
            "completed": sum(1 for q in self.requests if q.done),
            "replicas": [
                {
                    "replica": r.idx,
                    "state": r.state,
                    "queue_depth": (
                        r.session.queue_depth if r.session is not None else 0
                    ),
                    "active": (
                        r.session.active_count if r.session is not None else 0
                    ),
                }
                for r in self.replicas
            ],
        })

    # ------------------------------------------------------------- driver
    def _outstanding(self) -> bool:
        return any(not (q.done or q.shed) for q in self.requests)

    def run_until_drained(self) -> None:
        """Tick until every accepted request is done or shed.  If the
        pool empties (every replica dead), the remainder sheds loudly —
        a router with no replicas is an outage, not a hang."""
        while self._outstanding():
            if not self._serving():
                for q in self.requests:
                    if not (q.done or q.shed):
                        self._shed(q, "no_replicas")
                break
            self.tick()

    def serve(
        self,
        requests: Sequence[Sequence[int]],
        *,
        max_new: Sequence[int] | None = None,
        attention_masks: Sequence[Sequence[int]] | None = None,
        sessions: Sequence[Any] | None = None,
    ) -> list[list[int]]:
        """The batch entry point (the serve-router CLI's driver): submit
        everything, run to drained, finalize.  Returns per-request
        generated ids in request order (shed requests: empty list)."""
        if max_new is not None and len(max_new) != len(requests):
            raise ValueError(
                f"max_new has {len(max_new)} entries for {len(requests)} requests"
            )
        rids = [
            self.submit(
                req,
                max_new=(max_new[i] if max_new is not None else None),
                attention_mask=(
                    attention_masks[i] if attention_masks is not None else None
                ),
                session=(sessions[i] if sessions is not None else None),
            )
            for i, req in enumerate(requests)
        ]
        self.run_until_drained()
        self.finalize()
        return [list(self.requests[rid].out) for rid in rids]

    # ------------------------------------------------------------ summary
    def finalize(self) -> dict:
        """Close every surviving session (their serve_summary events) and
        emit the ``router_summary`` the report/gates consume.  Idempotent."""
        if self._finalized:
            return self.last_stats
        self._finalized = True
        for r in self.replicas:
            if r.session is not None:
                r.session.finalize()
        now = time.perf_counter()
        wall = max(now - self.t_open, 1e-9)
        real = [q for q in self.requests if not q.synthetic]
        completed = [q for q in real if q.done]
        mttr_vals = [
            q.done_wall - q.first_fail_wall
            for q in real
            if q.done and q.first_fail_wall is not None
        ]
        from distributed_llms_example_tpu.obs.spans import percentiles

        ttfts = [q.ttft_s for q in completed if q.ttft_s is not None]
        p50, p95, p99 = percentiles(ttfts, (0.50, 0.95, 0.99))
        slo_ms = max(
            (e.serve.ttft_slo_ms for e in (r.engine for r in self.replicas)),
            default=0.0,
        )
        slo_s = slo_ms / 1e3
        useful = [
            q for q in completed
            if q.ttft_s is not None and (slo_s <= 0 or q.ttft_s <= slo_s)
        ]
        import jax

        goodput = compute_goodput(
            [q.ttft_s for q in real],
            [len(q.out) for q in real],
            wall_s=wall,
            ttft_slo_ms=slo_ms,
            n_chips=max(jax.device_count(), 1),
        )
        # the gated rate is REAL traffic's failure retries: synthetic
        # storm requests are injected load, and counting their retries
        # against a real-request denominator would inflate the rate past
        # 1.0 under storm+crash chaos
        real_retries = sum(q.retries for q in real)
        summary = {
            "event": "router_summary",
            "replicas": len(self.replicas),
            "replica_states": {
                str(r.idx): r.state for r in self.replicas
            },
            "ticks": self.ticks,
            "wall_s": round(wall, 3),
            "requests": len(real),
            "synthetic_requests": len(self.requests) - len(real),
            "completed": len(completed),
            "shed": sum(
                1 for q in real if q.shed
            ),
            "shed_by_reason": dict(sorted(self.shed_by_reason.items())),
            "retries": real_retries,
            "retries_total": self.retries_total,  # synthetic included
            # the gate inputs: bounded-retry health and request-level
            # usefulness of the whole tier, not one replica
            "request_retry_rate": round(
                real_retries / max(len(real), 1), 4
            ),
            "goodput_frac": round(len(useful) / max(len(real), 1), 4),
            "request_mttr_s": (
                round(sum(mttr_vals) / len(mttr_vals), 4) if mttr_vals else None
            ),
            "ttft_p50_ms": round(p50 * 1e3, 1),
            "ttft_p95_ms": round(p95 * 1e3, 1),
            "ttft_p99_ms": round(p99 * 1e3, 1),
            **goodput,
        }
        if any(r.engine.paged and r.engine.prefix for r in self.replicas):
            # tier-wide prefix-cache ledger, summed over surviving
            # replicas' closed sessions (a dead replica's stats die with
            # its session — the drop is part of the failure's cost)
            lookups = hits = saved = total = 0
            for r in self.replicas:
                st = r.engine.last_stats
                if st is None:
                    continue
                lookups += st.prefix_lookups
                hits += st.prefix_hits
                saved += st.prefill_tokens_saved
                total += st.prefill_tokens_total
            summary["prefix_lookups"] = lookups
            summary["prefix_hits"] = hits
            summary["prefix_hit_rate"] = round(hits / max(lookups, 1), 4)
            summary["prefill_tokens_saved"] = saved
            summary["prefill_tokens_total"] = total
            summary["prefill_tokens_saved_frac"] = round(
                saved / max(total, 1), 4
            )
        if any(getattr(r.engine, "spec", 0) for r in self.replicas):
            # tier-wide speculative-decode ledger over surviving replicas
            # — the degraded-mode leg reads its multi-token yield off the
            # SAME fields, so "the speedup survives a replica kill" is a
            # router_summary claim, not a per-replica one
            drafted = accepted = emitted = rounds = 0
            for r in self.replicas:
                st = r.engine.last_stats
                if st is None:
                    continue
                drafted += st.spec_drafted
                accepted += st.spec_accepted
                emitted += st.spec_emitted
                rounds += st.spec_slot_rounds
            summary["spec_tokens"] = max(
                getattr(r.engine, "spec", 0) for r in self.replicas
            )
            summary["spec_drafted_tokens"] = drafted
            summary["spec_accepted_tokens"] = accepted
            summary["acceptance_rate"] = round(
                accepted / max(drafted, 1), 4
            )
            summary["accepted_tokens_per_step"] = round(
                emitted / max(rounds, 1), 4
            )
        if self.t_fail is not None:
            summary["t_fail_s"] = round(self.t_fail - self.t_open, 4)
            if self.t_recovered is not None:
                summary["t_recovered_s"] = round(
                    self.t_recovered - self.t_open, 4
                )
        log_json(summary)
        self.last_stats = summary
        return summary

    def request_rows(self) -> list[dict]:
        """Per-request completion rows (bench's degraded-phase input):
        submit/done instants relative to router open, TTFT, tokens,
        retries, shed."""
        return [
            {
                "rid": q.rid,
                "synthetic": q.synthetic,
                "arrival_s": round(q.arrival_wall - self.t_open, 6),
                "queue_delay_ms": round(
                    (q.submit_wall - q.arrival_wall) * 1e3, 3
                ),
                "submit_s": round(q.submit_wall - self.t_open, 6),
                "done_s": (
                    round(q.done_wall - self.t_open, 6)
                    if q.done_wall is not None
                    else None
                ),
                "ttft_s": q.ttft_s,
                "tokens": len(q.out),
                "retries": q.retries,
                "shed": q.shed,
                "shed_reason": q.shed_reason,
            }
            for q in self.requests
        ]
