"""Step-time budget accounting: where every step's milliseconds go.

BENCH_r05 measured the trainer loop at 0.751× synthetic-step throughput
with dropout off and BENCH_7B_r05 pinned 99.3 ms/step of non-layer
overhead — both host-side, neither explainable from the existing span
*aggregates* (total data_wait per window says nothing about whether the
missing quarter of wall time is input stall, dispatch serialization, or
untracked host bookkeeping).  This module closes each logging window into
an **additive account** of the window's step wall time:

    wall = data_wait + dispatch + device_busy + sync_block
         + host_overhead + unattributed

- ``data_wait``       blocked on the input pipeline (tokenize/pad/prefetch)
- ``dispatch``        host time issuing the compiled step (put_batch +
                      the jitted call's enqueue) — milliseconds when async
                      dispatch is healthy, a whole device step when a
                      hidden host sync serializes it
- ``device_busy``     the cadenced queue-drain probe: at the log cadence
                      (and ONLY there) the budget times a
                      ``block_until_ready`` on the step output *before*
                      the metric logger's fetch — the un-overlapped device
                      tail the host genuinely waits on
- ``sync_block``      the ``device_sync`` spans (the logger's cadenced
                      device→host conversion + emit)
- ``host_overhead``   every other recorded span landing inside a step's
                      duration: batch fingerprinting, flight-recorder/
                      metrics bookkeeping.  Cadenced checkpoint/eval time
                      BETWEEN steps is excluded from the partition (the
                      trainer re-anchors the step clock after it — see
                      ``SpanRecorder.mark_step_start``); read those costs
                      from the ``obs_window`` span aggregates instead
- ``unattributed``    the remainder — loop bookkeeping in no span.  The
                      additivity contract (test-pinned, and the e2e
                      acceptance bar) is that this stays under
                      ``tolerance`` of wall: the named components explain
                      ≥ 95% of where the time went.

Two derived signals ride each ``step_budget`` event:

- ``dispatch_efficiency`` = 1 − (data_wait + host_overhead +
  unattributed) / wall: the fraction of wall during which the device was
  being fed or drained rather than idling behind a host-side stall.  The
  ROADMAP's ``vs_synthetic_step ≥ 0.95`` attack is exactly "drive this
  toward 1.0"; bench stamps it per trainer-loop pass so the A/B is
  same-session.
- the **off-cadence host-transfer tripwire**: a host-blocking transfer
  inside the step body (a stray ``float()``/``device_get`` — the pattern
  repo-lint rule 4 bans *statically*) shows up at runtime as a dispatch
  span that consumes a device-step's worth of wall on a NON-cadence step.
  Any non-cadence step whose dispatch exceeds half the window's mean step
  wall (and an absolute floor) is counted in ``offcadence_sync_steps``
  and flags ``offcadence_sync_suspect`` — the runtime complement of the
  static rule, catching the transfers that hide behind attribute lookups
  or third-party code the AST lint cannot see.  The first window stands
  down (``"warmup": true``): it holds the JIT compile, a legitimate
  dispatch block wall time alone cannot tell from a transfer.

Everything here is host-clock arithmetic over the span recorder's
per-step records; the ONLY device interaction is the cadenced probe.  The
zero-new-syncs-off-cadence property is pinned by a counting-leaf test the
same way PR 3 pinned the health telemetry.
"""

from __future__ import annotations

from typing import Any

from distributed_llms_example_tpu.obs import sink as sink_mod
from distributed_llms_example_tpu.obs.spans import SpanRecorder

# the additive components, in emission order; "<name>_ms" fields on every
# step_budget event.  obs/report.py iterates this list too — one
# definition, two consumers.
COMPONENTS: tuple[str, ...] = (
    "data_wait",
    "dispatch",
    "device_busy",
    "sync_block",
    "host_overhead",
    "unattributed",
)

# span name → component.  Spans not named here (checkpoint, eval,
# host_overhead itself, obs_gauge_compile, future additions) fold into
# host_overhead: they are host work riding a step's wall time.
_SPAN_COMPONENT = {
    "data_wait": "data_wait",
    "step_dispatch": "dispatch",
    "device_busy": "device_busy",
    "device_sync": "sync_block",
}

# a dispatch must eat at least this much wall before the tripwire will
# consider it a blocked transfer — keeps clock jitter on sub-ms steps out
MIN_BLOCK_S = 0.005


def _ms(seconds: float) -> float:
    return round(seconds * 1e3, 3)


class BudgetAccountant:
    """Closes the span recorder's window into one ``step_budget`` event.

    ``probe(sync_leaf)`` is the cadenced device timing (call it at the
    log cadence, BEFORE the metric logger's own fetch, so the measured
    block is the genuine queue drain and the logger's fetch lands on an
    already-idle device); ``close_window(step)`` computes the account
    from the per-step span records and emits it.  ``history`` keeps the
    last ``history_size`` accounts for in-process consumers (bench)."""

    def __init__(
        self,
        spans: SpanRecorder,
        *,
        tolerance: float = 0.05,
        suspect_frac: float = 0.5,
        min_block_s: float = MIN_BLOCK_S,
        warmup_windows: int = 1,
        async_dispatch: bool = True,
        history_size: int = 64,
    ):
        self.spans = spans
        self.tolerance = float(tolerance)
        self.suspect_frac = float(suspect_frac)
        self.min_block_s = float(min_block_s)
        # multi-device CPU executes the program inline in the dispatching
        # thread — EVERY dispatch legitimately spans the device step, so
        # a blocked dispatch carries no signal there.  The count is still
        # reported (it is a measurement); only the SUSPECT verdict stands
        # down, stamped "sync_dispatch_backend" so the report can say why.
        self.async_dispatch = bool(async_dispatch)
        # the first window contains the JIT compile — a legitimate
        # dispatch block indistinguishable from a host-blocking transfer
        # by wall time alone, so the tripwire stands down for it (the
        # account itself still closes; the event carries "warmup": true)
        self.warmup_windows = int(warmup_windows)
        self.history_size = int(history_size)
        self.history: list[dict] = []
        self._closed = 0
        # cadenced gauges riding the account (not partition components):
        # currently the optimizer-apply wall sample (probe_optimizer)
        self._gauges: dict[str, float] = {}

    # -- the one device interaction (log cadence only) -------------------

    def probe(self, sync_leaf: Any) -> None:
        """Time the device-queue drain as a ``device_busy`` span: blocks
        until ``sync_leaf`` (the step's loss scalar) is ready.  The
        caller gates this to the log cadence — at that boundary the host
        would block for the same drain one line later inside the metric
        logger anyway, so the probe adds measurement, not a sync."""
        import jax

        with self.spans.span("device_busy"):
            jax.block_until_ready(sync_leaf)

    def probe_optimizer(self, fn: Any) -> None:
        """Time one stand-alone optimizer apply (``fn`` runs the jitted
        apply and returns its output to block on) — the satellite gauge
        that lets the fused-vs-xla A/B read optimizer milliseconds
        DIRECTLY from the ``step_budget`` account instead of inferring
        them from step-time deltas.  Cadence-gated by the caller
        (``TrainerObs.optimizer_probe``), and run AFTER the window
        closes, alongside checkpoint/eval, so its wall is EXCLUDED from
        the additive step-time partition (it is measurement, not step
        work); the sample lands on the NEXT window's account as
        ``optimizer_apply_ms``.  The FIRST invocation runs one untimed
        warm call: the lazily-built probe program jit-compiles inside
        ``fn`` and a compile is not an apply (the warm flag is set only
        AFTER that call succeeds, so a transient failure cannot leave a
        later compile mislabeled as the timed sample).

        The probe is a GAUGE, never load-bearing: any failure (an OOM
        compiling the stand-alone apply on a memory-tight config, a
        transient backend error inside the blocking call) disables
        further probes for this run with one logged event instead of
        propagating into the training loop."""
        import jax

        if getattr(self, "_opt_probe_dead", False):
            return
        try:
            if not getattr(self, "_opt_probe_warm", False):
                jax.block_until_ready(fn())
                self._opt_probe_warm = True
            t0 = self.spans.clock()
            jax.block_until_ready(fn())
            self._gauges["optimizer_apply_ms"] = _ms(self.spans.clock() - t0)
        except Exception as e:  # noqa: BLE001 — telemetry must not kill the run
            self._opt_probe_dead = True
            self._gauges.pop("optimizer_apply_ms", None)
            sink_mod.emit({
                "event": "optimizer_probe_disabled",
                "reason": str(e)[:300],
            }, local=True)

    # -- the device-side decomposition (profile windows only) ------------

    def attach_device_account(self, account: dict) -> dict:
        """Emit one parsed profile capture (obs/devprof.py) as a
        ``device_account`` event — the device-side decomposition of the
        host account's ``device_busy`` blob: per-module-bucket device
        time, per-collective time (+ achieved bandwidth when the byte
        account joined), and the overlap/exposed-idle metrics.  Bulk
        (file channel only — the lanes payload has no place on the
        Valohai stdout contract) and local (every capturing rank's file
        carries its own account)."""
        record = {"event": "device_account", **{
            k: v for k, v in account.items() if k != "event"
        }}
        sink_mod.emit(record, local=True, bulk=True)
        return record

    # -- window close (log cadence only) ---------------------------------

    def close_window(
        self, step: int, epoch: int | None = None, *, emit: bool = True
    ) -> dict | None:
        """Fold the window's per-step records into the additive account.
        Call BEFORE ``spans.summary()`` (which resets the window).  Emits
        a ``step_budget`` event (``local``: every rank's file carries its
        own account) and returns it; None when no step completed."""
        recs = self.spans.window_step_records()
        if not recs:
            return None
        wall = sum(r["dur"] for r in recs)
        if wall <= 0:
            return None
        comp = {c: 0.0 for c in COMPONENTS[:-1]}
        for r in recs:
            for name, s in r["spans"].items():
                comp[_SPAN_COMPONENT.get(name, "host_overhead")] += s
        # the remainder: host time in no span (loop bookkeeping).  Clock
        # rounding can push the sum a hair past wall — clamp at zero so
        # the account never reports negative time.
        unattributed = max(0.0, wall - sum(comp.values()))
        # the off-cadence tripwire: the window's LAST record is the
        # cadence step (probe + logger fetch legitimately block there);
        # any earlier step whose dispatch ate half a mean step-wall was
        # host-blocked inside the step body
        mean_step = wall / len(recs)
        threshold = max(self.suspect_frac * mean_step, self.min_block_s)
        self._closed += 1
        warmup = self._closed <= self.warmup_windows
        offcadence = 0 if warmup else sum(
            1
            for r in recs[:-1]
            if r["spans"].get("step_dispatch", 0.0) > threshold
        )
        stalled = comp["data_wait"] + comp["host_overhead"] + unattributed
        acct: dict[str, Any] = {
            "event": "step_budget",
            "step": int(step),
            "window_steps": len(recs),
            "wall_ms": _ms(wall),
        }
        if epoch is not None:
            acct["epoch"] = int(epoch)
        for c in COMPONENTS[:-1]:
            acct[f"{c}_ms"] = _ms(comp[c])
        acct["unattributed_ms"] = _ms(unattributed)
        acct["accounted_frac"] = round((wall - unattributed) / wall, 4)
        acct["additivity_ok"] = bool(unattributed <= self.tolerance * wall)
        acct["dispatch_efficiency"] = round(max(0.0, 1.0 - stalled / wall), 4)
        acct["offcadence_sync_steps"] = int(offcadence)
        acct["offcadence_sync_suspect"] = bool(
            offcadence > 0 and self.async_dispatch
        )
        opt_ms = self._gauges.get("optimizer_apply_ms")
        if opt_ms is not None:
            # the newest cadenced optimizer-apply sample (probe_optimizer)
            # + its share of the window's mean step wall — the direct
            # "how much of each step is the optimizer" read the fused
            # optimizer A/B consumes
            acct["optimizer_apply_ms"] = opt_ms
            acct["optimizer_share_of_step"] = round(
                opt_ms / max(_ms(mean_step), 1e-9), 4
            )
        if not self.async_dispatch:
            acct["sync_dispatch_backend"] = True
        if warmup:
            acct["warmup"] = True
        self.history.append(acct)
        if len(self.history) > self.history_size:
            del self.history[: len(self.history) - self.history_size]
        if emit:
            sink_mod.emit(acct, local=True)
        return acct


def aggregate_accounts(accounts: list[dict]) -> dict | None:
    """Fold ``step_budget`` accounts (one run) into per-component
    totals plus the wall-weighted dispatch efficiency — obs/report.py's
    per-rank rollup."""
    accounts = [a for a in accounts if a.get("wall_ms")]
    if not accounts:
        return None
    wall = sum(float(a["wall_ms"]) for a in accounts)
    out: dict[str, Any] = {
        "windows": len(accounts),
        "steps": sum(int(a.get("window_steps", 0)) for a in accounts),
        "wall_ms": round(wall, 3),
    }
    for c in COMPONENTS:
        out[f"{c}_ms"] = round(
            sum(float(a.get(f"{c}_ms", 0.0) or 0.0) for a in accounts), 3
        )
    out["dispatch_efficiency"] = round(
        sum(
            float(a.get("dispatch_efficiency", 0.0) or 0.0) * float(a["wall_ms"])
            for a in accounts
        )
        / wall,
        4,
    )
    out["accounted_frac"] = round(
        (wall - out["unattributed_ms"]) / wall, 4
    ) if wall else None
    out["offcadence_sync_steps"] = sum(
        int(a.get("offcadence_sync_steps", 0) or 0) for a in accounts
    )
    opt_samples = [
        float(a["optimizer_apply_ms"])
        for a in accounts
        if a.get("optimizer_apply_ms") is not None
    ]
    if opt_samples:
        out["optimizer_apply_ms"] = round(
            sum(opt_samples) / len(opt_samples), 3
        )
        share_samples = [
            float(a["optimizer_share_of_step"])
            for a in accounts
            if a.get("optimizer_share_of_step") is not None
        ]
        if share_samples:
            out["optimizer_share_of_step"] = round(
                sum(share_samples) / len(share_samples), 4
            )
    return out


def budget_enabled(cfg: Any) -> bool:
    """``--obs-budget`` tristate: "on" forces, "off" disables, "auto"
    follows the obs instrumentation gate (any mode but "off")."""
    mode = getattr(cfg, "obs_budget", "auto")
    if mode == "on":
        return True
    if mode == "off":
        return False
    return getattr(cfg, "obs", "stdout") != "off"


__all__ = [
    "COMPONENTS",
    "BudgetAccountant",
    "aggregate_accounts",
    "budget_enabled",
]
# NOTE: the device-side decomposition of device_busy is emitted through
# BudgetAccountant.attach_device_account (device_account events) — parsed
# by obs/devprof.py from profile captures, rendered by obs/report.py.
