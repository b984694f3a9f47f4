"""The program's own spans (``obs/spans.py``: ``serve/...``, ``train/...``) read
from the host planes of a traced run, laid beside the device's busy time.

The spans are ``TraceAnnotation`` events on the profiler's clock, so a span's
interval and a device operation's interval can be compared directly.  Two
stages, as in ``trace.py``: ``read_spans`` turns the ``.xplane.pb`` into plain
``Span`` tuples (parsed once per path, however many readers ask); everything
else works on those and on the reduced trace (``ctx["trace"]``), and is checked
on a small recorded excerpt (``tests/data/*.spans.json``).

A program without such spans (an older commit, a CPU rehearsal whose trace has
no device plane) gives ``None`` from ``load``: the reader then returns None and
the result line leaves its metric out.
"""

from __future__ import annotations

import bisect
import functools
import os
import statistics
from typing import Iterable, NamedTuple

from benchmarks.harness import spec as spec_mod
from benchmarks.harness import trace as trace_mod

PREFIXES = ("serve/", "train/")


class Span(NamedTuple):
    name: str
    start: int  # ns, the profiler's clock
    dur: int
    stats: dict

    @property
    def end(self) -> int:
        return self.start + self.dur


def read_spans(xplane_path: str, keep=lambda name: name.startswith(PREFIXES)) -> list[Span]:
    """Every host-plane event whose name ``keep`` accepts, with its stats, by start."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if keep(ev.name):
                    out.append(Span(ev.name, int(ev.start_ns), int(ev.duration_ns), dict(ev.stats)))
    out.sort(key=lambda s: (s.start, -s.dur))
    return out


_program_spans = functools.lru_cache(maxsize=None)(read_spans)  # one pass for all the readers of a run


def trace_path(cell_name: str) -> str:
    return os.path.join(spec_mod.CACHE_DIR, "trace", cell_name, trace_mod.XPLANE_FILE)


def in_window(spans: Iterable[Span], window_ns: tuple[int, int]) -> list[Span]:
    """The spans that overlap the window.  (A train trace's window runs from
    the first device operation to the last: the span that dispatched the first
    step began before it.)"""
    lo, hi = window_ns
    return [s for s in spans if s.end > lo and s.start < hi]


def load(ctx: dict) -> list[Span] | None:
    """The program spans that overlap this run's traced window, or None."""
    tr = ctx.get("trace")
    if tr is None:
        return None
    path = trace_path(ctx["cell"].name)
    if not os.path.exists(path):
        return None
    return in_window(_program_spans(path), tr["window_ns"]) or None


def named(spans: Iterable[Span], *names: str) -> list[Span]:
    return [s for s in spans if s.name in names]


def children(spans: list[Span], parent: Span) -> list[Span]:
    """Spans that lie inside ``parent`` (any depth), ``parent`` itself left out."""
    return [s for s in spans if s is not parent and s.start >= parent.start and s.end <= parent.end]


def rounds(spans: list[Span]) -> tuple[list[tuple[Span, list[Span]]], list[tuple[Span, list[Span]]]]:
    """(plain, wave): each ``serve/round`` with its children; a wave round
    has a ``serve/prefill_dispatch`` child, a plain one has none."""
    plain, wave = [], []
    starts = [s.start for s in spans]
    for rd in named(spans, "serve/round"):
        i = bisect.bisect_left(starts, rd.start)
        j = bisect.bisect_right(starts, rd.end)
        kids = children(spans[i:j], rd)
        (wave if any(k.name == "serve/prefill_dispatch" for k in kids) else plain).append((rd, kids))
    return plain, wave


def median_ms(per_round_ns: list[int]) -> float | None:
    return statistics.median(per_round_ns) / 1e6 if per_round_ns else None


def plain_round_ms(ctx: dict, *names: str) -> float | None:
    """Median over the window's plain rounds of the summed durations of the
    children called ``names``."""
    spans = load(ctx)
    if spans is None:
        return None
    plain, _ = rounds(spans)
    return median_ms([sum(k.dur for k in kids if k.name in names) for _, kids in plain])


# ---- the device's idle time, laid over the spans

def idle_gaps(reduced: dict) -> list[tuple[int, int]]:
    """The intervals of the traced window in which no operation ran on the
    device: the complement of the busy union of ``reduced["ops"]``."""
    lo, hi = reduced["window_ns"]
    busy = trace_mod.union([(max(s, lo), min(s + d, hi)) for _, s, d in reduced["ops"] if s + d > lo and s < hi])
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def idle_within(gaps: list[tuple[int, int]], lo: int, hi: int) -> int:
    """Idle nanoseconds inside [lo, hi)."""
    i = bisect.bisect_left(gaps, (lo, lo))
    if i and gaps[i - 1][1] > lo:
        i -= 1
    total = 0
    while i < len(gaps) and gaps[i][0] < hi:
        total += max(0, min(gaps[i][1], hi) - max(gaps[i][0], lo))
        i += 1
    return total


def innermost_segments(spans: list[Span]) -> list[tuple[int, int, str]]:
    """Disjoint ascending (start, end, name): each instant some span covers,
    under the name of the innermost one (the latest to start, then the first
    to end: spans of one thread nest, those of two threads may only overlap)."""
    spans = sorted(spans, key=lambda s: (s.start, -s.dur))
    bounds = sorted({s.start for s in spans} | {s.end for s in spans})
    out: list[tuple[int, int, str]] = []
    active: list[Span] = []
    i = 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(spans) and spans[i].start <= a:
            active.append(spans[i])
            i += 1
        active = [s for s in active if s.end > a]
        if active:
            name = max(active, key=lambda s: (s.start, -s.end)).name
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))
    return out


def idle_by_innermost_span(gaps: list[tuple[int, int]], spans: list[Span]) -> dict[str | None, int]:
    """Idle nanoseconds by the innermost span that covers them; what no span
    covers is under the key None.  The values sum to the gaps' total."""
    out: dict[str | None, int] = {None: sum(b - a for a, b in gaps)}
    for lo, hi, name in innermost_segments(spans):
        ns = idle_within(gaps, lo, hi)
        if ns:
            out[name] = out.get(name, 0) + ns
            out[None] -= ns
    return out
