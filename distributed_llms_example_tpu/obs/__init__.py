"""Layered telemetry for the training system.

The reference's observability contract is ONE channel: a JSON line per
metric window printed to stdout, parsed by Valohai as execution metadata
(utils/jsonlog.py).  That is enough to watch a loss curve and nothing
else — pjit-at-scale training reports (PAPERS.md: arxiv 2204.06514) treat
MFU and per-step comm/compute breakdowns as the primary tuning signal,
and weight-update-sharding work (arxiv 2004.13336) shows gradient-traffic
accounting is what separates a correctly sharded step from a 2× overweight
one.  This package supplies those signals in four layers:

- ``spans``     host-side monotonic-clock span tracing (data_wait /
                step_dispatch / device_sync / eval / checkpoint) with a
                ring buffer and per-window step-time percentiles; zero
                device syncs off the logging cadence
- ``gauges``    derived device gauges: MFU from the AOT-compiled train
                step's HLO cost analysis (the shared compile recipe in
                utils/memory_audit.py), live HBM via ``memory_stats()``,
                and a static per-step collective-traffic account scanned
                from the same HLO the IR lint parses
- ``profile``   on-demand ``jax.profiler`` capture for a step window
                (``--profile-steps 100:105``), a trigger file polled at
                step cadence, or an agreed anomaly
                (``--profile-on-anomaly``); captures land in
                step-window-stamped dirs and announce themselves with
                ``profile_captured`` events
- ``devprof``   device-time attribution: the jax-free trace parser that
                reduces a landed capture into the ``device_account`` —
                per-module-bucket device time (op_name scopes through
                the same table as the health param buckets), achieved
                bytes/sec per collective, compute↔comm overlap
- ``heartbeat`` multi-host liveness/step-skew probe so process 0 reports
                laggards before a collective hangs silently
- ``health``    the training-signal watchdog: consumes the in-graph
                numerics (train/step.py ``health_metrics``) at the log
                cadence — NaN/Inf tripwire, EWMA loss-spike, grad-norm
                explosion — with multi-host agreement over the heartbeat
                allgather channel and a ``warn``/``halt``/``checkpoint``
                policy
- ``recorder``  the flight recorder: a bounded ring of the last N steps'
                metrics + batch fingerprints, dumped as a schema-stamped
                bundle on anomaly / SIGTERM / crash
- ``budget``    step-time budget accounting: each window's wall time
                decomposed into data_wait / dispatch / device_busy /
                sync_block / host_overhead (additive, test-pinned), a
                ``dispatch_efficiency`` gauge, and the runtime tripwire
                for host-blocking transfers off the log cadence
- ``report``    the offline consumer: merges the per-process JSONL into
                a cross-host step timeline (``python -m
                distributed_llms_example_tpu.obs.report <output_dir>``)

Everything funnels through ``sink`` (stdout Valohai channel + optional
JSONL file, same schema).  ``TrainerObs`` below is the one object the
Trainer holds — it owns the wiring so the train loop stays readable.
"""

from __future__ import annotations

import math
import os
from typing import Any, Iterable, Iterator

from distributed_llms_example_tpu.obs import health as health_mod
from distributed_llms_example_tpu.obs import profile as profile_mod
from distributed_llms_example_tpu.obs import sink as sink_mod
from distributed_llms_example_tpu.obs.memprof import MemoryMonitor
from distributed_llms_example_tpu.obs.budget import BudgetAccountant, budget_enabled
from distributed_llms_example_tpu.obs.health import HealthWatchdog, health_enabled
from distributed_llms_example_tpu.obs.heartbeat import Heartbeat
from distributed_llms_example_tpu.obs.profile import ProfileController
from distributed_llms_example_tpu.obs.recorder import FlightRecorder, batch_fingerprint
from distributed_llms_example_tpu.obs.sink import build_sink, install_sink
from distributed_llms_example_tpu.obs.spans import SpanRecorder

__all__ = [
    "TrainerObs",
    "HealthWatchdog",
    "FlightRecorder",
    "batch_fingerprint",
    "health_enabled",
    "budget_enabled",
]


class TrainerObs:
    """The Trainer's telemetry bundle.

    Owns the sink, the span recorder, the (optional) static gauges, the
    heartbeat, and the profiler controller.  Everything here is host-side
    bookkeeping except: the startup gauge compile (one AOT compile of the
    train step, gated by ``obs_gauges``), the heartbeat's cadenced
    cross-process gather, and the profiler's start/stop syncs — none of
    which ever lands on a non-cadence step.
    """

    def __init__(self, cfg: Any, *, start_step: int = 0, manage_sink: bool = True):
        self.cfg = cfg
        self.enabled = getattr(cfg, "obs", "stdout") != "off"
        if manage_sink:
            # standalone use (tests, tools); the Trainer installs its sink
            # itself — before its first device_report line — and passes
            # manage_sink=False so the file channel is opened exactly once
            install_sink(build_sink(getattr(cfg, "obs", "stdout"), cfg.output_dir))
        self.spans = SpanRecorder(scope="train")
        self.every = max(1, int(cfg.log_every_steps))
        self.flops_per_step: float | None = None
        # MFU denominator: looked up by device kind (obs/gauges.py); None
        # for a device with no published peak — the gauge is then omitted
        import jax

        from distributed_llms_example_tpu.obs.gauges import PEAK_BF16_FLOPS

        self.device_kind = jax.devices()[0].device_kind
        self.peak_flops_per_chip = PEAK_BF16_FLOPS.get(self.device_kind)
        hb_every = int(getattr(cfg, "obs_heartbeat_steps", 0) or 0)
        self.heartbeat = Heartbeat(
            every_steps=hb_every,
            # 0 = classification off (the knob's own convention); only a
            # MISSING config field falls back to the default of 3
            suspect_beats=int(
                getattr(cfg, "obs_heartbeat_suspect_beats", 3)
            ),
        ) if (
            self.enabled and hb_every > 0
        ) else None
        # training-health layer: the watchdog consumes the in-graph
        # numerics at the log cadence; the recorder rings every step
        self.health_on = health_enabled(cfg)
        self.on_anomaly = getattr(cfg, "on_anomaly", "warn")
        self.watchdog = (
            HealthWatchdog(
                loss_spike_factor=float(getattr(cfg, "health_loss_spike_factor", 4.0)),
                grad_norm_factor=float(getattr(cfg, "health_grad_norm_factor", 10.0)),
                warmup_steps=int(getattr(cfg, "health_warmup_steps", 20)),
            )
            if self.health_on
            else None
        )
        # gated on obs OR health: --obs off --health on --on-anomaly
        # checkpoint still promises a bundle with the checkpoint
        rec_steps = int(getattr(cfg, "recorder_steps", 0) or 0)
        self.recorder = (
            FlightRecorder(rec_steps)
            if (rec_steps > 0 and (self.enabled or self.health_on))
            else None
        )
        self._pending_health: list[tuple[int, dict]] = []
        self._last_health: dict[str, Any] | None = None
        # the last agreed obs_anomaly record (pod-consistent fields:
        # step/code/policy) — what the rewind recovery path consumes when
        # on_step returns its action
        self.last_anomaly: dict[str, Any] | None = None
        self._trigger = getattr(cfg, "profile_trigger", "") or (
            os.path.join(cfg.output_dir, "obs", "profile.trigger")
            if self.enabled
            else ""
        )
        # device-time attribution (obs/devprof.py) inputs, filled by
        # startup_gauges: the instruction→bucket index of the compiled
        # step and the static per-step collective byte account
        self._op_buckets: dict[str, str] | None = None
        self._comm_account: dict | None = None
        # the HBM account + watermark telemetry (obs/memprof.py): samples
        # memory_window events at the log cadence and holds the last
        # static account for the OOM postmortem bundle
        self.memory = MemoryMonitor() if self.enabled else None
        # --profile-on-anomaly: an agreed anomaly arms the profiler's own
        # trigger file, so the NEXT steps are captured and the post-mortem
        # carries a device timeline next to the flight recorder
        self.profile_on_anomaly = bool(getattr(cfg, "profile_on_anomaly", False))
        self.profiler = self._build_profiler(start_step)
        # step-time budget layer (obs/budget.py): host-clock arithmetic
        # over the span recorder's per-step records, closed at the log
        # cadence into a step_budget event; its ONE device interaction is
        # the cadenced queue-drain probe (budget_probe below)
        self.budget = None
        if budget_enabled(cfg):
            self.budget = BudgetAccountant(
                self.spans,
                # multi-device CPU dispatch runs the program inline: a
                # blocked dispatch is that backend's normal mode, not a
                # stray transfer — the tripwire verdict stands down there
                async_dispatch=jax.default_backend() != "cpu",
            )

    def _build_profiler(self, start_step: int) -> ProfileController:
        ctl = ProfileController(
            profile_dir=self.cfg.profile_dir,
            steps_spec=self.cfg.profile_steps,
            trigger_path=self._trigger,
            start_step=start_step,
            output_dir=self.cfg.output_dir,
        )
        ctl.on_capture = self._on_profile_captured
        return ctl

    def set_start_step(self, start_step: int) -> None:
        """Re-anchor the legacy relative profile window once the Trainer
        knows its resume step (checkpoint restore happens after obs
        construction)."""
        self.profiler = self._build_profiler(start_step)

    # -- startup ---------------------------------------------------------

    def startup_gauges(self, mesh: Any, *, tgt_cap: int) -> None:
        """AOT-compile the train step via the shared recipe
        (utils/memory_audit.py) and emit the static gauges: per-step HLO
        FLOPs (the MFU numerator) and the collective-traffic account.
        One extra compile at startup — on TPU with the persistent
        compilation cache it is a disk hit for any program the run will
        compile anyway."""
        cfg = self.cfg
        mode = getattr(cfg, "obs_gauges", "auto")
        want = mode == "on" or (mode == "auto" and getattr(cfg, "obs", "") == "jsonl")
        if not (self.enabled and want):
            return
        from distributed_llms_example_tpu.obs import gauges

        try:
            with self.spans.span("obs_gauge_compile"):
                report = gauges.train_step_static_gauges(
                    cfg.model_ckpt,
                    mesh,
                    global_batch=cfg.batch_size,
                    src_len=cfg.max_source_length,
                    tgt_len=tgt_cap,
                    dtype=cfg.compute_dtype,
                    remat=cfg.remat,
                    remat_policy=cfg.remat_policy,
                    grad_accum_steps=cfg.grad_accum_steps,
                    grad_compression=getattr(cfg, "grad_compression", ""),
                    hbm_budget_gib=float(getattr(cfg, "hbm_budget_gib", 16.0)),
                )
        except Exception as e:  # never fail training for telemetry
            sink_mod.emit({
                "event": "obs_gauges_skipped",
                "reason": str(e)[:300],
            })
            return
        self.flops_per_step = report["flops_per_step"]
        # devprof inputs stay in-process: the instruction→bucket index is
        # thousands of entries (no place on a metric line) and the byte
        # account is re-read from the emitted record at report time
        self._op_buckets = report.pop("op_bucket_index", None)
        self._comm_account = report.get("comm")
        # the bucketed HBM account gets its OWN event (the report's
        # "Where did the bytes go" table reads it from the JSONL alone)
        # and seeds the monitor so an OOM postmortem carries it
        account = report.pop("memory_account", None)
        if account is not None:
            if self.memory is not None:
                self.memory.attach_account(account)
            sink_mod.emit({"event": "memory_account", **account})
        sink_mod.emit({
            "event": "obs_gauges",
            **(
                {"peak_flops_per_chip": self.peak_flops_per_chip}
                if self.peak_flops_per_chip
                else {"mfu_skipped": f"no published peak FLOP/s for device_kind {self.device_kind!r}"}
            ),
            **report,
        })

    # -- the step loop ---------------------------------------------------

    def wrap_batches(self, batches: Iterable[dict]) -> Iterator[dict]:
        """Time host-batch availability as ``data_wait`` spans — the time
        the device loop spends blocked on tokenize/pad/bucket (or on the
        prefetcher when it cannot keep up)."""
        it = iter(batches)
        while True:
            with self.spans.span("data_wait"):
                try:
                    batch = next(it)
                except StopIteration:
                    return
            yield batch

    def step_span(self):
        return self.spans.span("step_dispatch")

    def sync_span(self):
        return self.spans.span("device_sync")

    def host_span(self):
        """Host bookkeeping riding the step's wall (batch fingerprinting,
        metric/recorder prep) — the budget account's ``host_overhead``."""
        return self.spans.span("host_overhead")

    def budget_probe(self, step: int, sync_leaf: Any) -> None:
        """The budget layer's cadenced device timing: at the log cadence
        ONLY, time the queue drain on the step output BEFORE the metric
        logger's own fetch (so the logger's conversion lands on an idle
        device and the measured block is the genuine un-overlapped device
        tail).  Off-cadence steps return after two comparisons — zero
        device syncs, the invariant the counting-leaf test pins."""
        if self.budget is None or sync_leaf is None or step % self.every != 0:
            return
        self.budget.probe(sync_leaf)

    def optimizer_probe(self, step: int, fn_factory: Any) -> None:
        """The budget layer's cadenced optimizer-apply wall sample: at
        the log cadence ONLY (after the window closed — the trainer's
        ``mark_step_start`` excludes the probe's wall from the step-time
        partition like checkpoint/eval), run one stand-alone jitted
        optimizer apply and time it (``optimizer_apply_ms`` on the next
        ``step_budget`` account).  Off-cadence this is two comparisons
        and returns — zero device syncs."""
        if self.budget is None or step % self.every != 0:
            return
        self.budget.probe_optimizer(fn_factory)

    def _on_profile_captured(
        self, trace_dir: str, window: tuple[int, int], truncated: bool = False
    ) -> None:
        """A profile window landed: parse the capture into the device
        account (obs/devprof.py — host-side file IO on the capture's
        closing step only) and emit it through the budget layer.  A GAUGE,
        never load-bearing: any parse failure logs one event and the run
        continues.  Truncated captures carry the clamped (honest) window
        and a ``truncated`` stamp."""
        if self.budget is None:
            return
        try:
            from distributed_llms_example_tpu.obs.devprof import (
                device_account_from_dir,
                join_collective_bandwidth,
            )

            acct = device_account_from_dir(trace_dir, op_buckets=self._op_buckets)
            if acct is None:
                sink_mod.emit({
                    "event": "device_account_skipped",
                    "reason": f"no device op events under {trace_dir}",
                }, local=True)
                return
            steps = int(window[1] - window[0] + 1)
            acct["step"] = int(window[1])
            acct["window"] = [int(window[0]), int(window[1])]
            acct["window_steps"] = steps
            if truncated:
                acct["truncated"] = True
            join_collective_bandwidth(acct, self._comm_account, steps)
            self.budget.attach_device_account(acct)
        except Exception as e:  # noqa: BLE001 — telemetry must not kill the run
            sink_mod.emit({
                "event": "device_account_skipped",
                "reason": str(e)[:300],
            }, local=True)

    def eval_span(self):
        return self.spans.span("eval")

    def checkpoint_span(self):
        return self.spans.span("checkpoint")

    def on_step(
        self,
        step: int,
        epoch: int,
        metrics: dict,
        fingerprint: dict | None = None,
    ) -> str:
        """Per-step bookkeeping: host clocks only (pointer appends for the
        recorder/health pending list), except the profiler's stop sync
        (cadenced), the heartbeat gather (cadenced), and the health
        window's one device_get (cadenced).  Returns the anomaly policy
        action for the train loop: "ok" / "warn" / "halt" / "checkpoint".
        """
        self.profiler.after_step(step, metrics.get("loss"))
        self.spans.step_complete()
        if self.recorder is not None:
            self.recorder.record(step, epoch, metrics, fingerprint)
        if self.watchdog is not None:
            self._pending_health.append((step, dict(metrics)))
        if self.heartbeat is not None and step % self.heartbeat.every == 0:
            self.heartbeat.beat(step)
        action = "ok"
        if step % self.every == 0:
            # budget first: it reads the window's per-step records, which
            # emit_window's summary() resets
            if self.budget is not None:
                self.budget.close_window(step, epoch)
            if self.watchdog is not None:
                action = self._health_cadence(step)
            if self.enabled:
                self.emit_window(step, epoch)
            elif self.budget is not None:
                # --obs off --obs-budget on: emit_window won't run, so
                # consume the window here — otherwise every later account
                # re-reads (and re-counts) the same ever-growing records
                self.spans.summary()
        return action

    def _health_cadence(self, step: int) -> str:
        """The log-cadence health check: resolve the window's device
        scalars to host floats (ONE transfer — the same fetch the metric
        logger pays), run the detectors, agree across hosts, apply the
        policy.  Every process runs this at the same step, so the
        returned action is pod-consistent."""
        if not self._pending_health:
            return "ok"
        entries = health_mod.to_host(self._pending_health)
        self._pending_health = []
        if self.recorder is not None:
            for s, vals in entries:
                self.recorder.annotate(s, vals)
        last_step, last_vals = entries[-1]
        # non-finite values become strings: an anomalous window is exactly
        # when these are NaN, and a bare NaN literal is invalid JSON on
        # the stdout/JSONL channels (same convention as the recorder)
        self._last_health = {
            k: (float(f"{v:.6g}") if math.isfinite(v) else repr(v))
            for k, v in last_vals.items()
            if k in ("param_norm", "grad_norm", "nonfinite_count")
            or k.startswith("update_ratio_")
        }
        anomalies = self.watchdog.check(entries)
        event = health_mod.agree_and_emit(
            anomalies, step=step, policy=self.on_anomaly
        )
        if event is None:
            return "ok"
        self.last_anomaly = event
        if (
            self.profile_on_anomaly
            and self._trigger
            and not self.profiler.active
        ):
            # arm the profiler's OWN trigger-file machinery: the next
            # step opens a capture, so the post-mortem carries a device
            # timeline next to the flight-recorder bundle.  Every rank
            # writes the same path (the schedule is pod-agreed); the
            # controller consumes it exactly like an operator touch.
            try:
                os.makedirs(os.path.dirname(self._trigger), exist_ok=True)
                with open(self._trigger, "w") as f:
                    f.write(str(profile_mod.DEFAULT_TRIGGER_STEPS))
                sink_mod.emit({
                    "event": "profile_trigger_armed",
                    "step": step,
                    "reason": f"anomaly:{event['code']}",
                }, local=True)
            except OSError:
                pass  # a failed arm must not change the policy action
        if self.recorder is not None:
            self.recorder.dump(
                self.cfg.output_dir,
                reason=f"anomaly:{event['code']}",
                step=step,
                anomalies=anomalies,
            )
        # the last window must survive whatever the policy does next
        sink_mod.flush(fsync=True)
        return self.on_anomaly

    def emit_window(self, step: int, epoch: int | None = None) -> None:
        summary = self.spans.summary()
        if summary is None:
            return
        record: dict[str, Any] = {"event": "obs_window", "step": step}
        if epoch is not None:
            record["epoch"] = epoch
        record.update(summary)
        mfu = self.window_mfu(summary)
        if mfu is not None:
            # significant digits, not decimal places: a CPU-mesh MFU of
            # 2e-9 must not round to a flat 0.0
            record["mfu"] = float(f"{mfu:.4g}")
        if self._last_health is not None:
            record["health"] = self._last_health
        if self.memory is not None:
            # one cadenced memory_stats read: a memory_window event with
            # watermark-delta-since-last-window (or a single named skip on
            # backends that report nothing), plus the live summary inline
            hbm = self.memory.sample(step)
            if hbm is not None:
                record["hbm"] = {
                    k: hbm[k]
                    for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
                }
        # local: every process's window lands in its OWN jsonl file (the
        # cross-host timeline obs/report.py merges); stdout stays p0-only
        sink_mod.emit(record, local=True)

    def window_mfu(self, summary: dict) -> float | None:
        """MFU over the just-closed window: compiled-step FLOPs × steps
        over wall seconds and aggregate peak FLOPs.  None until the
        startup gauge compile has supplied the numerator, and on a device
        whose peak is not in the table (named once, in ``obs_gauges``)."""
        if (
            not self.flops_per_step
            or not self.peak_flops_per_chip
            or not summary.get("window_seconds")
        ):
            return None
        import jax

        from distributed_llms_example_tpu.obs.gauges import mfu

        return mfu(
            self.flops_per_step,
            summary["window_seconds"] / max(1, summary["window_steps"]),
            jax.device_count(),
            self.peak_flops_per_chip,
        )

    # -- shutdown --------------------------------------------------------

    def finalize(self, step: int, epoch: int | None = None, sync_leaf: Any = None) -> str:
        """End of run: close the profiler, run the health check over the
        final partial window (a NaN in the last steps must still fire),
        emit the final span window, and push the file channel to disk.
        Returns the final health action (informational — the loop is
        already over)."""
        self.profiler.finalize(sync_leaf, last_step=step)
        action = "ok"
        if self.budget is not None:
            # the final partial window's account (before summary resets it)
            self.budget.close_window(step, epoch)
        if self.watchdog is not None and self._pending_health:
            action = self._health_cadence(step)
        if self.enabled:
            self.emit_window(step, epoch)
        elif self.budget is not None:
            self.spans.summary()  # consume the window the budget read
        sink_mod.flush(fsync=True)
        return action
