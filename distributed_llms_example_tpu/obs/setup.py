"""The set-up account: what a process spends before it is ready to train or serve.

Phases are spans of ONE process-wide ``SpanRecorder(scope="setup")``, each a
``TraceAnnotation`` ``setup/<name>``: ``trainer_init``, ``first_step``,
``engine_init``, ``session_open`` outermost, their pieces nested.  Compile
stages are sums by program from jax's own ``jax.monitoring`` events.  jax
records a scalar when a stage (trace, lowering, backend compile or cache load)
is entered and its duration when it is left, so a stage that opens inside
another (a nested ``jit``'s trace inside its caller's) is known to be part of it
and is not added to it.  An outermost stage inside an open ``setup/*`` span is
charged to its program and to that span; outside every such span it is the
caller's before ready, and a ``late_compile`` event after it.

Ready is when everything awaited (a trainer's first call of its step program,
an engine's ``warm``) has happened: ``setup_summary`` is logged once and kept
(``snapshot``) for the benchmark's ``setup_*`` metrics.  Always on; nothing is
stored per event, and no listener fires in a loop that compiles nothing.
"""

from __future__ import annotations

import functools
import threading

import jax

from distributed_llms_example_tpu.obs.spans import SpanRecorder, open_spans
from distributed_llms_example_tpu.utils.jsonlog import log_json

_COMPILE = "/jax/core/compile/backend_compile_duration"
STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    _COMPILE: "compile_or_load_s",
}
# the persistent cache's events name no program: they fire inside the backend
# compile of the one they belong to (a plain event counts 1, a duration its seconds)
CACHE = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load_s",
    "/jax/compilation_cache/compile_time_saved_sec": "compile_saved_s",
}
STAGE_FIELDS = tuple(STAGES.values())
DEAREST = 10  # programs setup_summary lists by name; the rest are summed


def program_name(fun_name: str) -> str:
    """``jit(serve_prefill)`` (lowering, compile) and ``serve_prefill`` (trace) are one program."""
    return fun_name[4:-1] if fun_name.startswith("jit(") and fun_name.endswith(")") else fun_name


class SetupAccount:
    def __init__(self, recorder: SpanRecorder | None = None, log=log_json):
        self.spans = recorder if recorder is not None else SpanRecorder(scope="setup", totals=True)
        self.log = log
        self.programs: dict[str, dict] = {}  # program -> n lowerings and the sums of STAGES and CACHE
        self.stages: dict[str, dict] = {}  # span path -> the stage seconds charged to it, innermost
        self.awaited: set[str] = set()
        self.summary: dict | None = None  # setup_summary as logged at ready
        self.late_compiles = 0
        self._thread = threading.local()  # .depth of open stages, .compiling (a program), .late (an event)

    # ---- jax.monitoring listeners
    def on_enter(self, event: str, _start=None, fun_name: str = "", **_) -> None:
        if event in STAGES:
            t = self._thread
            t.depth = getattr(t, "depth", 0) + 1
            if event == _COMPILE:  # the cache's events are this program's, if the compile is outermost
                t.compiling = program_name(fun_name) if t.depth == 1 else None

    def on_exit(self, event: str, amount: float = 1, fun_name: str = "", **_) -> None:
        t = self._thread
        if event in CACHE:
            if getattr(t, "compiling", None) is not None:
                self._charge(t.compiling, CACHE[event], amount)
        elif event in STAGES:
            t.depth = max(getattr(t, "depth", 1) - 1, 0)
            if t.depth == 0:  # outermost: nothing it is part of is still open
                self._charge(program_name(fun_name), STAGES[event], amount)
                if event == _COMPILE:
                    t.compiling = None
                    late, t.late = getattr(t, "late", None), None
                    if late is not None:  # the program's one event, with the stages before its compile
                        self.late_compiles += 1
                        self.log(late)

    def _charge(self, program: str, field: str, amount: float) -> None:
        scope = self.spans.scope + "/"
        path = "/".join(s[len(scope):] for s in open_spans() if s.startswith(scope))
        if path:
            row = self.programs.setdefault(program, dict.fromkeys(("n", *STAGE_FIELDS, *CACHE.values()), 0))
            row[field] += amount
            row["n"] += field == "lower_s"  # specializations: a trace event also fires for a trace jax had kept
            if field in STAGE_FIELDS:
                at = self.stages.setdefault(path, dict.fromkeys(STAGE_FIELDS, 0.0))
                at[field] += amount
        elif self.summary is not None:  # after ready, outside every setup span: an incident with a name
            t = self._thread
            if getattr(t, "late", None) is None or t.late["program"] != program:
                # (one begun and never compiled is dropped here: a kept trace is reported as a trace)
                spans = open_spans()
                t.late = {"event": "late_compile", "program": program, **dict.fromkeys(STAGE_FIELDS, 0.0),
                          "cache_hit": False, "span": spans[-1] if spans else None}
            if field in STAGE_FIELDS:
                t.late[field] += amount
            t.late["cache_hit"] |= field == "cache_hits"

    # ---- ready
    def ready(self, what: str) -> None:
        self.awaited.discard(what)
        if self.summary is None and not self.awaited:
            self.summary = self.summarize()
            self.log({"event": "setup_summary", **self.summary})

    def summarize(self) -> dict:
        seconds = self.spans.totals()
        rows = sorted(self.programs.items(), key=lambda kv: -sum(kv[1][f] for f in STAGE_FIELDS))
        rest = [row for _, row in rows[DEAREST:]]
        summed = lambda table, fields: {f: round(sum(r[f] for r in table), 6) for f in fields}  # noqa: E731
        return {
            # path (outer/inner) -> its seconds and the stage seconds charged to it, innermost
            "phases": {p: {"s": round(s, 6), **summed([self.stages.get(p, {})], self.stages.get(p, ()))}
                       for p, s in seconds.items()},
            # of each outermost span, the wall that neither a child nor a stage charged to it covers
            "unattributed_s": {
                p: round(s - sum(c for q, c in seconds.items() if q.rpartition("/")[0] == p)
                         - sum(self.stages.get(p, {}).values()), 6)
                for p, s in seconds.items() if "/" not in p},
            "programs": {name: summed([row], row) for name, row in rows[:DEAREST]},
            "others": summed(rest, rest[0]) if rest else {},
            "totals": {**summed(self.stages.values(), STAGE_FIELDS),
                       **summed(self.programs.values(), ("cache_hits", "cache_misses"))},
            "cache_dir": jax.config.jax_compilation_cache_dir,
        }


ACCOUNT = SetupAccount()
_installed = False


def install() -> None:
    """Register the listeners, once a process (jax cannot take one back).
    They hand each event to whatever ``ACCOUNT`` is then (tests swap it)."""
    global _installed
    if not _installed:
        _installed = True
        jax.monitoring.register_scalar_listener(lambda *a, **k: ACCOUNT.on_enter(*a, **k))
        jax.monitoring.register_event_duration_secs_listener(lambda *a, **k: ACCOUNT.on_exit(*a, **k))
        jax.monitoring.register_event_listener(lambda *a, **k: ACCOUNT.on_exit(*a, **k))


install()


def span(name: str):
    return ACCOUNT.spans.span(name)


def ready(what: str) -> None:
    ACCOUNT.ready(what)


def snapshot() -> dict | None:
    """``setup_summary`` as it stood at ready; None before."""
    return ACCOUNT.summary


def late_compiles() -> int:
    return ACCOUNT.late_compiles


def phase(name: str, *, awaits: str | None = None, ready: str | None = None):
    """Decorator: the call is the span ``setup/<name>``.  ``awaits``: set-up is
    not over until ``ready(<awaits>)``; ``ready``: it is, when this call returns."""

    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            if awaits is not None and ACCOUNT.summary is None:
                ACCOUNT.awaited.add(awaits)
            with span(name):
                out = fn(*args, **kwargs)
            if ready is not None:
                ACCOUNT.ready(ready)
            return out

        return call

    return wrap
