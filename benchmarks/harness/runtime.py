"""What one run hands its driver, and the two watchers every driver uses: a
count of compilations (none may fall inside the window) and a profiler window
of its own (traced runs only)."""

from __future__ import annotations

import contextlib
import dataclasses
import os
import shutil
import time
from typing import Any, Callable

import jax

from benchmarks.harness import spec as spec_mod
from benchmarks.harness import trace as trace_mod


@dataclasses.dataclass
class Run:
    cell: Any
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    t_start: float
    peaks: dict | None
    emit: Callable[[dict], None]
    control: bool = False  # also put the lower-precision reference in the program's place


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache while
    ``watching`` — either one inside the measured window is a shape that
    set-up failed to warm."""

    _EVENTS = ("/jax/core/compile/backend_compile_duration",
               "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self) -> None:
        self.count = 0
        self.watching = False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw: Any) -> None:
        if self.watching and event in self._EVENTS:
            self.count += 1

    @contextlib.contextmanager
    def window(self):
        self.watching = True
        try:
            yield self
        finally:
            self.watching = False


class Profile:
    """One profiler window into a fixed directory under the cache.  ``stop``
    may be called early from inside the traced block (a train pass is traced
    for its first steps only: a step is 46,000 to 390,000 device operations).

    The Python tracer is off and the session is stopped without jax's export
    (``stop_trace`` also converts every event for the trace viewer): neither
    is read, a whole run has 360 s, and handing the events over already takes
    ~29 us for each device operation (PERF.md, PR 24's second round)."""

    def __init__(self, run: Run):
        self.dir = os.path.join(spec_mod.CACHE_DIR, "trace", run.cell.name)
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        self.path = os.path.join(self.dir, trace_mod.XPLANE_FILE)
        self.on = False
        self.stop_s = 0.0

    def start(self) -> None:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the benchmark's spans are TraceAnnotations, not Python frames
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.on = True

    def stop(self) -> None:
        if not self.on:
            return
        self.on = False
        from jax._src import profiler as jax_profiler  # the session, to stop it without the export

        t0 = time.perf_counter()
        state = jax_profiler._profile_state
        with state.lock:
            xspace = state.profile_session.stop()
            state.reset()
        with open(self.path, "wb") as f:
            f.write(xspace)
        self.stop_s = time.perf_counter() - t0


@contextlib.contextmanager
def profiled(run: Run, annotations: tuple[str, ...], window_annotation: str, out: dict):
    """Trace the block and leave the reduced trace in ``out["trace"]`` (None
    where the trace holds no device plane: a CPU rehearsal).  Yields the
    ``Profile`` so that the block can stop it early."""
    profile = Profile(run)
    profile.start()
    try:
        yield profile
    finally:
        profile.stop()
    t0 = time.perf_counter()
    events = trace_mod.extract(profile.path, annotations)
    t1 = time.perf_counter()
    if run.rehearse and not events["devices"]:
        out["trace"] = None
    else:
        out["trace"] = trace_mod.reduce(events, chips=run.cell.chips, window_annotation=window_annotation)
    run.emit({"phase": "trace", "xplane_bytes": os.path.getsize(profile.path),
              "device_op_events": sum(len(lines.get(trace_mod.OPS_LINE, [])) for lines in events["devices"].values()),
              "stop_s": profile.stop_s, "extract_s": t1 - t0, "reduce_s": time.perf_counter() - t1})
