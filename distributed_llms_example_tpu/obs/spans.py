"""Host-side span tracing for the train loop and the serving engine's round.

Monotonic-clock spans (``data_wait``, ``step_dispatch``, ``device_sync``,
``eval``, ``checkpoint``, nested freely) plus a per-step ring buffer from
which each logging window reports step-time percentiles (p50/p95/max) and
a straggler flag.  Everything is ``time.perf_counter`` arithmetic on the
host — recording a span costs two clock reads and a dict update, and
NOTHING here touches a device, so instrumented non-logging steps keep the
zero-sync async-dispatch property MetricLogger already guarantees.

Every span, nested ones too, also opens a profiler annotation named
``<scope>/<name>`` (``jax.profiler.TraceAnnotation``): under a profiler
session it lands on a ``/host:CPU`` line on the device trace's clock (what
``Span.set`` is given becomes its stats); with no session it is one inactive
TraceMe.  The ring keeps the bare names.

The clock and the annotation factory are injectable so tests drive the
recorder deterministically (and this module imports jax only when a
recorder is built without a factory).
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, Sequence

# step-time max > STRAGGLER_FACTOR × p50 within a window flags the window:
# on a healthy synchronous-SPMD step the distribution is tight, and a fat
# max means some host stalled (GC, page cache, a slow storage read) — the
# local precursor of the cross-host skew the heartbeat watches for.
STRAGGLER_FACTOR = 2.0


_thread = threading.local()  # .recorders: those with a span open on this thread, outermost first


def open_spans() -> list[str]:
    """This thread's open spans as ``<scope>/<name>``, outermost first, of
    every recorder: what a listener that fires inside one is charged to
    (``obs/setup.py``: a compilation's phase, a late compilation's span).
    Put together when asked (a compilation asks); a span pays nothing for it."""
    return [f"{r.scope}/{name}" for r in getattr(_thread, "recorders", ()) for name in r._open]


def percentiles(values: Sequence[float], qs: Sequence[float]) -> list[float]:
    """Nearest-rank percentiles of ``values`` (no numpy: callers live on
    the trainer hot path's cadence and in bench post-processing)."""
    if not values:
        return [0.0 for _ in qs]
    s = sorted(values)
    out = []
    for q in qs:
        idx = min(len(s) - 1, max(0, int(round(q * (len(s) - 1)))))
        out.append(s[idx])
    return out


class Span:
    """One open span: ``t0`` from its start, ``dur`` / ``end`` once closed
    (the caller's own arithmetic reads these instead of the clock again);
    ``set`` hands counters to the span's annotation as its stats."""

    __slots__ = ("t0", "dur", "_annotation")

    def __init__(self, t0: float, annotation):
        self.t0 = t0
        self.dur = 0.0
        self._annotation = annotation

    @property
    def end(self) -> float:
        return self.t0 + self.dur

    def set(self, **counters) -> None:
        self._annotation.set_metadata(**counters)


class SpanRecorder:
    """Ring-buffered span/step-time recorder with window summaries.

    ``span(name)`` times a (possibly nested) region; ``step_complete()``
    closes one loop iteration and records its wall duration in the ring.
    ``summary()`` reports the window since the previous summary —
    per-step percentiles plus per-span aggregates — and resets the window
    (the ring keeps ``ring_size`` steps for end-of-run retrospectives).
    """

    def __init__(
        self,
        ring_size: int = 512,
        clock: Callable[[], float] = time.perf_counter,
        straggler_factor: float = STRAGGLER_FACTOR,
        scope: str = "train",
        annotate: Callable | None = None,
        totals: bool = False,
    ):
        self.ring_size = int(ring_size)
        self.clock = clock
        self.scope = scope
        if annotate is None:
            from jax.profiler import TraceAnnotation as annotate
        # name -> context manager with set_metadata(**counters)
        self._annotate = annotate
        self.straggler_factor = float(straggler_factor)
        self._ring: list[float] = []  # per-step wall seconds, newest last
        self._open: list[str] = []  # this recorder's open spans, outermost first
        # path ("outer/inner") -> seconds of every span closed since the
        # recorder was built: never reset, needs no step_complete.  Kept
        # where asked for (the set-up account's recorder): a round's or a
        # step's spans pay nothing for it
        self._totals: dict[str, float] | None = {} if totals else None
        self._window_spans: dict[str, list[float]] = {}  # name → [total_s, count, max_s]
        self._window_steps = 0
        self._window_t0 = clock()
        self._step_t0: float | None = None
        # per-step breakdown for the budget layer (obs/budget.py): the
        # OUTERMOST spans closed since the step's anchor, keyed by name —
        # a partition of the step's ring duration (nested spans would
        # double-count, so only depth-0 exits land here)
        self._step_spans: dict[str, float] = {}
        self._step_records: list[dict] = []  # rings with _ring

    # -- recording -------------------------------------------------------

    @contextlib.contextmanager
    def span(self, name: str):
        with self._annotate(f"{self.scope}/{name}") as annotation:
            outermost = not self._open
            if outermost:  # open_spans() finds this recorder's open spans through the thread's list
                try:
                    recorders = _thread.recorders
                except AttributeError:
                    recorders = _thread.recorders = []
                recorders.append(self)
            self._open.append(name)
            sp = Span(self.clock(), annotation)
            try:
                yield sp
            finally:
                dt = sp.dur = self.clock() - sp.t0
                if self._totals is not None:
                    path = "/".join(self._open)
                    self._totals[path] = self._totals.get(path, 0.0) + dt
                self._open.pop()
                if outermost:
                    recorders.pop()
                agg = self._window_spans.get(name)
                if agg is None:
                    self._window_spans[name] = [dt, 1, dt]
                else:
                    agg[0] += dt
                    agg[1] += 1
                    if dt > agg[2]:
                        agg[2] = dt
                if outermost:
                    self._step_spans[name] = self._step_spans.get(name, 0.0) + dt

    def step_complete(self) -> None:
        """One train-loop iteration finished: record its wall duration
        (time since the previous ``step_complete`` / window start)."""
        now = self.clock()
        t0 = self._step_t0 if self._step_t0 is not None else self._window_t0
        dur = now - t0
        self._ring.append(dur)
        self._step_records.append({"dur": dur, "spans": self._step_spans})
        self._step_spans = {}
        if len(self._ring) > self.ring_size:
            del self._ring[: len(self._ring) - self.ring_size]
            del self._step_records[: len(self._step_records) - self.ring_size]
        self._step_t0 = now
        self._window_steps += 1

    def mark_step_start(self) -> None:
        """Re-anchor the per-step clock.  The trainer calls this after
        cadenced non-step work (checkpoint save, eval) so that wall time
        — already tracked under its own span — is not also charged to
        the NEXT step's ring-buffer duration (which would fire the
        straggler flag on every healthy eval cadence).  The per-step span
        breakdown is re-anchored with it: a span recorded between the
        boundary and here (checkpoint/eval) is excluded from the next
        step's duration, so charging it to that step's budget would break
        the partition the budget account sums over."""
        self._step_t0 = self.clock()
        self._step_spans = {}

    # -- reporting -------------------------------------------------------

    def totals(self) -> dict[str, float]:
        """Seconds by path (``outer/inner``: a span under the spans of this
        recorder that were open around it) of every span closed so far, of
        a recorder built with ``totals=True``."""
        return dict(self._totals or {})

    def window_step_times(self) -> list[float]:
        if self._window_steps == 0:
            return []
        return self._ring[-min(self._window_steps, len(self._ring)):]

    def window_step_records(self) -> list[dict]:
        """The current window's per-step ``{"dur": s, "spans": {name: s}}``
        records (the budget account's raw material).  Read BEFORE
        ``summary()`` — which resets the window counter this slices by."""
        if self._window_steps == 0:
            return []
        n = min(self._window_steps, len(self._step_records))
        return self._step_records[-n:]

    def summary(self) -> dict | None:
        """Close the window: step-time percentiles + span aggregates.
        None when no step completed since the last summary (telemetry
        cadence fired before any work — nothing to report)."""
        times = self.window_step_times()
        if not times:
            return None
        now = self.clock()
        p50, p95 = percentiles(times, (0.50, 0.95))
        mx = max(times)
        out = {
            "window_steps": self._window_steps,
            "window_seconds": round(now - self._window_t0, 6),
            "step_ms_p50": round(p50 * 1e3, 3),
            "step_ms_p95": round(p95 * 1e3, 3),
            "step_ms_max": round(mx * 1e3, 3),
            "straggler": bool(p50 > 0 and mx > self.straggler_factor * p50),
            "spans": {
                name: {
                    "total_ms": round(total * 1e3, 3),
                    "count": count,
                    "max_ms": round(peak * 1e3, 3),
                }
                for name, (total, count, peak) in sorted(self._window_spans.items())
            },
        }
        self._window_spans = {}
        self._window_steps = 0
        self._window_t0 = now
        return out
