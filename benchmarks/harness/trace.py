"""From a profiler trace (``.xplane.pb``) to device busy time, top operations,
idle gaps and per-step times.  Reads with ``jax.profiler.ProfileData`` only.

Two stages, so that the arithmetic can be checked on a small recorded trace
(``tests/data/*.events.json``) without the profiler: ``extract`` turns the
file into plain lists of (name, start_ns, duration_ns) per plane and line;
everything else works on those lists.

What the planes look like on a TPU v5e (looked at by hand, PERF.md): each
chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per
executed operation and ``XLA Modules`` one per executed program; host threads
are lines of the plane ``/host:CPU`` and hold the ``TraceAnnotation`` spans.
An operation's event name is the whole text of its HLO instruction
(``%self_attn._flash_run.1006 = (bf16[8,16,1024,64]{...}, ...) custom-call(...)``):
``label`` cuts it to ``<instruction> <opcode> <first result shape>``.  A
``while``, ``conditional`` or ``call`` is a container whose interval covers the
operations inside it (the microbatch loop is one ``while`` as long as the
step); containers are left out, so that busy time is the time some real
operation ran.
"""

from __future__ import annotations

import functools
import os
import re
import statistics
from typing import Iterable

XPLANE_FILE = "run.xplane.pb"  # where a traced run leaves its trace, under the cache's trace/<cell>/
DEVICE_PLANE = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


CONTAINERS = ("while", "conditional", "call")
_INSTR = re.compile(r"^%?(?P<head>\S+) = (?P<shape>\(?[a-z0-9]+\[[^\]]*\])?.*?\)?\s(?P<op>[a-z][a-z\-]*)\(", re.S)


@functools.lru_cache(maxsize=None)  # a step repeats its few thousand instructions for every microbatch
def label(event_name: str) -> tuple[str, str]:
    """(short label, opcode) of an operation event's name."""
    m = _INSTR.match(event_name)
    if not m:
        return event_name[:80], ""
    shape = (m.group("shape") or "").lstrip("(")
    return f"{m.group('head')} {m.group('op')} {shape}".strip(), m.group("op")


@functools.lru_cache(maxsize=None)
def family(short: str) -> str:
    """The label without the instruction's serial number and remat suffix, so
    that the thirty-six runs of one kernel in a step count as one row."""
    head, _, rest = short.partition(" ")
    return (re.sub(r"(\.remat\d*|\.clone|\.\d+)+$", "", head) + " " + rest).strip()


def extract(xplane_path: str, annotations: Iterable[str]) -> dict:
    """{"devices": {plane: {line: [(name, start_ns, dur_ns)]}},
        "host": [(name, start_ns, dur_ns)] for the named annotations}."""
    from jax.profiler import ProfileData

    wanted = set(annotations)
    data = ProfileData.from_file(xplane_path)
    devices: dict = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    lines[line.name] = [(ev.name, int(ev.start_ns), int(ev.duration_ns)) for ev in line.events]
                elif line.name == OPS_LINE:
                    ops = []
                    for ev in line.events:
                        short, opcode = label(ev.name)
                        if opcode not in CONTAINERS:
                            ops.append((short, int(ev.start_ns), int(ev.duration_ns)))
                    lines[line.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        host.append((ev.name, int(ev.start_ns), int(ev.duration_ns)))
    host.sort(key=lambda e: e[1])
    return {"devices": devices, "host": host}


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Merge (start, end) intervals into disjoint ascending ones."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals: list[tuple[int, int]], lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _total(intervals: list[tuple[int, int]]) -> int:
    return sum(e - s for s, e in intervals)


def reduce(events: dict, *, chips: int, window_annotation: str | None = None) -> dict:
    """The numbers every traced run reports.

    The window is the span of ``window_annotation`` on the host (first start
    to last end) when the trace holds it, else first to last device event.
    ``busy_s`` is the union of operation intervals inside the window,
    averaged over the ``chips`` busiest device planes.
    """
    planes = sorted(
        events["devices"].items(),
        key=lambda kv: -sum(d for _, _, d in kv[1].get(OPS_LINE, [])),
    )[:chips]
    if not planes or not any(p[1].get(OPS_LINE) for p in planes):
        raise RuntimeError("the trace holds no device operation")
    spans = [e for e in events["host"] if e[0] == window_annotation]
    if spans:
        lo, hi = spans[0][1], max(s + d for _, s, d in spans)
    else:
        all_ops = [ev for _, lines in planes for ev in lines.get(OPS_LINE, [])]
        lo, hi = min(s for _, s, _ in all_ops), max(s + d for _, s, d in all_ops)
    busy_ns, per_plane = 0, []
    for name, lines in planes:
        merged = _clip(union([(s, s + d) for _, s, d in lines.get(OPS_LINE, [])]), lo, hi)
        busy_ns += _total(merged)
        per_plane.append((name, lines, merged))
    busy_s, window_s = busy_ns / len(planes) / 1e9, (hi - lo) / 1e9

    # per-operation totals and idle gaps on the busiest plane
    _, lines, merged = per_plane[0]
    totals: dict[str, int] = {}
    for name, s, d in lines.get(OPS_LINE, []):
        if s + d > lo and s < hi:
            name = family(name)
            totals[name] = totals.get(name, 0) + d
    gaps = []
    edges = [lo] + [t for iv in merged for t in iv] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            gaps.append((a, b))
    host = events["host"]

    def doing(mid: int) -> str:
        inner = [(d, n) for n, s, d in host if s <= mid < s + d]
        return min(inner)[1] if inner else "(no benchmark span)"

    top_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "window_ns": (lo, hi),
        "op_totals_ns": totals,
        "device_ops": [[n, t / 1e9] for n, t in sorted(totals.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[doing((a + b) // 2), (b - a) / 1e9] for a, b in top_gaps],
        "modules": [ev for ev in lines.get(MODULES_LINE, []) if ev[1] + ev[2] > lo and ev[1] < hi],
        "ops": sorted((ev for ev in lines.get(OPS_LINE, []) if ev[1] + ev[2] > lo and ev[1] < hi),
                      key=lambda e: e[1]),  # by start: per_module_run walks them once
    }


def main_module(reduced: dict) -> str:
    """The program that took most device time in the window (a train cell's
    step, a serve cell's decode step)."""
    totals: dict[str, int] = {}
    for name, _, d in reduced["modules"]:
        totals[name] = totals.get(name, 0) + d
    if not totals:
        raise RuntimeError("the trace holds no program (XLA Modules) event")
    return max(totals, key=totals.get)


def per_module_run(reduced: dict, module: str, op_filter=None) -> list[float]:
    """For each run of ``module``: seconds of device-busy union inside it (or,
    with ``op_filter``, the summed durations of the operations it accepts)."""
    runs = sorted((s, s + d) for n, s, d in reduced["modules"] if n == module)
    ops = reduced["ops"]
    out, i = [], 0
    for lo, hi in runs:
        inside = []
        while i < len(ops) and ops[i][1] < lo:
            i += 1
        j = i
        while j < len(ops) and ops[j][1] < hi:
            inside.append(ops[j])
            j += 1
        if op_filter is None:
            out.append(_total(_clip(union([(s, s + d) for _, s, d in inside]), lo, hi)) / 1e9)
        else:
            out.append(sum(d for n, _, d in inside if op_filter(n)) / 1e9)
    return out


def median_or_none(values: list[float]) -> float | None:
    return statistics.median(values) if values else None
