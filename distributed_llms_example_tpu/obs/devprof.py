"""Device-time attribution: profiler traces → a per-module device account.

The budget layer (obs/budget.py) closes every logging window into an
additive HOST account, but its largest component — ``device_busy`` — is
one opaque measured blob.  This module opens it: a **jax-free** parser
for the trace-viewer JSON ``jax.profiler`` leaves under a capture dir
(obs/profile.py), reducing the raw device events into a **device
account**:

- **per-bucket device time** — every device op event is attributed to a
  module bucket via the HLO ``op_name`` scope metadata, through the SAME
  matching table the health telemetry's param buckets use
  (analysis/ir_lint.py ``MODULE_BUCKET_PATTERNS``: embed / attn / mlp /
  head) plus the device-only classes ``optimizer`` (the clip/AdamW tail),
  ``collective`` (comm), ``infeed`` (host transfers) and ``other``
  (loss arithmetic, layout ops, scan plumbing);
- **per-collective-op time** — counts and total device time per base
  collective opcode, joined against obs/gauges.py's static byte account
  (``join_collective_bandwidth``) to yield **achieved bytes/sec** per
  collective — the measured half of every queued comms PR's verdict;
- **overlap / exposed idle** — interval arithmetic over the merged
  collective vs compute timelines: how much comm hid under compute
  (``overlap_frac``), how much was exposed, and how much of the window's
  span no device op covered at all (``exposed_idle``).

Backend notes: TPU/GPU traces carry per-device processes (``/device:…``
pids) whose event names are op_name scopes; the CPU thunk runtime names
device events by HLO *instruction* (``args.hlo_op = "fusion.3"``) on the
host process's executor threads.  Both shapes parse here — instruction
names are joined to buckets through an ``op_bucket_index`` built from
the SAME compiled HLO text the startup gauges already hold (the AOT
compile in utils/memory_audit.py), with opcode-class fallbacks for
events the index misses.  Bucket sums are per-op durations, so on a
multi-device (or multi-thread) timeline they can legitimately exceed
the busy UNION — they are device·time, the union is wall coverage.

Offline: ``python -m distributed_llms_example_tpu.obs.devprof
<trace_dir>`` prints the account; at runtime TrainerObs parses each
landed capture and emits it as a ``device_account`` event through
obs/budget.py (bulk/local), so obs/report.py
renders the tables from the JSONL alone — no trace files needed at
report time.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import sys
from typing import Any, Iterable, Mapping

from distributed_llms_example_tpu.analysis.ir_lint import (
    base_collective_op,
    classify_op_scope,
    op_bucket_index,  # noqa: F401  (re-exported: the runtime's index builder)
)

# the device-account buckets, in emission order: the four module buckets
# (shared with train/step.py HEALTH_BUCKETS via MODULE_BUCKET_PATTERNS)
# plus the device-only classes
DEVICE_BUCKETS: tuple[str, ...] = (
    "embed", "attn", "mlp", "head", "optimizer", "collective", "infeed",
    "other",
)

_INFEED_NAMES = (
    "infeed", "outfeed", "send", "recv", "send-done", "recv-done",
)

# cap on the per-bucket lane slices a device_account event carries for
# the Perfetto export — bounded like the trace collector's span buffer;
# overflow is counted (lane_slices_dropped), never silent
MAX_LANE_SLICES = 512


# ---------------------------------------------------------------------------
# trace loading
# ---------------------------------------------------------------------------


def find_trace_files(trace_dir: str) -> list[str]:
    """Every ``*.trace.json(.gz)`` under ``trace_dir`` (jax writes them at
    ``plugins/profile/<date>/<host>.trace.json.gz``), newest session
    first."""
    hits = [
        p
        for pattern in ("*.trace.json.gz", "*.trace.json")
        for p in glob.glob(
            os.path.join(trace_dir, "**", pattern), recursive=True
        )
    ]
    return sorted(hits, key=os.path.getmtime, reverse=True)


def load_trace_events(path: str) -> list[dict]:
    """One trace-viewer JSON file → its ``traceEvents`` list."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        doc = json.load(f)
    events = doc.get("traceEvents", []) if isinstance(doc, dict) else []
    return [e for e in events if isinstance(e, dict)]


def device_op_events(events: Iterable[dict]) -> list[dict]:
    """Normalize the raw event stream to the DEVICE OP events only:
    ``{"name", "hlo_op", "ts", "dur", "pid", "tid"}`` (times in µs).

    Two backend shapes: accelerator traces put ops on ``/device:…``
    processes (every complete event there counts); the CPU thunk runtime
    has no device pids — there the op events are exactly the ones stamped
    with ``args.hlo_op``."""
    meta_pid_names: dict[Any, str] = {}
    thread_names: dict[tuple, str] = {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            meta_pid_names[e.get("pid")] = str(
                (e.get("args") or {}).get("name", "")
            )
        elif e.get("name") == "thread_name":
            thread_names[(e.get("pid"), e.get("tid"))] = str(
                (e.get("args") or {}).get("name", "")
            ).lower()
    device_pids = {
        pid for pid, name in meta_pid_names.items()
        if name.startswith("/device:")
    }
    out: list[dict] = []
    for e in events:
        if e.get("ph") != "X":
            continue
        args = e.get("args") or {}
        hlo_op = args.get("hlo_op")
        if e.get("pid") in device_pids and not hlo_op:
            # accelerator traces stack several lanes under each device
            # pid; only the per-op lanes are device ops.  Aggregate lanes
            # ("XLA Modules", "Steps" — one whole-step slice enclosing
            # every op) would double-count the entire span into "other"
            # and pin overlap_frac at 1.0, so they are excluded.
            lane = thread_names.get((e.get("pid"), e.get("tid")), "")
            if "module" in lane or "step" in lane:
                continue
        if e.get("pid") in device_pids or hlo_op:
            dur = float(e.get("dur", 0.0) or 0.0)
            if dur <= 0:
                continue
            out.append({
                "name": str(e.get("name", "")),
                "hlo_op": str(hlo_op) if hlo_op else "",
                "ts": float(e.get("ts", 0.0) or 0.0),
                "dur": dur,
                "pid": e.get("pid"),
                "tid": e.get("tid"),
            })
    return out


# ---------------------------------------------------------------------------
# attribution
# ---------------------------------------------------------------------------


def classify_event(
    name: str, hlo_op: str, op_buckets: Mapping[str, str] | None
) -> str:
    """One device op event → its account bucket.

    Order: collective/infeed by opcode shape (works with or without an
    index); the instruction-name join through ``op_buckets`` (CPU traces
    name events by HLO instruction); a scope classification of the event
    name itself (TPU device lanes name events by op_name scope); then
    ``other``."""
    instr = hlo_op or name
    if base_collective_op(instr) is not None:
        return "collective"
    base = instr.split(".", 1)[0]
    if base in _INFEED_NAMES:
        return "infeed"
    if op_buckets:
        bucket = op_buckets.get(instr)
        if bucket:
            return bucket
    if "/" in name:  # an op_name scope path, classifiable directly
        return classify_op_scope(name) or "other"
    return "other"


def _merged_intervals(spans: Iterable[tuple[float, float]]) -> list[list[float]]:
    """Sorted (start, end) µs intervals → merged disjoint cover."""
    merged: list[list[float]] = []
    for t0, t1 in sorted(spans):
        if merged and t0 <= merged[-1][1]:
            if t1 > merged[-1][1]:
                merged[-1][1] = t1
        else:
            merged.append([t0, t1])
    return merged


def _union_us(merged: list[list[float]]) -> float:
    return sum(t1 - t0 for t0, t1 in merged)


def _intersect_us(a: list[list[float]], b: list[list[float]]) -> float:
    """Total overlap between two merged interval lists."""
    out = 0.0
    i = j = 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _ms(us: float) -> float:
    return round(us / 1e3, 3)


def build_account(
    events: list[dict],
    *,
    op_buckets: Mapping[str, str] | None = None,
    max_lane_slices: int = MAX_LANE_SLICES,
) -> dict[str, Any] | None:
    """Reduce normalized device op events into the device account.

    Returns None when the trace holds no device op events (a capture
    that caught no step).  All times in ms (3 decimals — trace input is
    µs, so the rounding is exact representation, not loss)."""
    if not events:
        return None
    span_lo = min(e["ts"] for e in events)
    span_hi = max(e["ts"] + e["dur"] for e in events)
    buckets = {b: 0.0 for b in DEVICE_BUCKETS}
    collectives: dict[str, dict[str, Any]] = {}
    op_spans: dict[str, list[tuple[float, float]]] = {}
    all_spans: list[tuple[float, float]] = []
    comm_spans: list[tuple[float, float]] = []
    compute_spans: list[tuple[float, float]] = []
    # per-bucket lane slices for the Perfetto export, relative to span_lo
    lane_raw: dict[str, list[tuple[float, float]]] = {}
    for e in events:
        bucket = classify_event(e["name"], e["hlo_op"], op_buckets)
        buckets[bucket] += e["dur"]
        t0, t1 = e["ts"], e["ts"] + e["dur"]
        all_spans.append((t0, t1))
        if bucket == "collective":
            comm_spans.append((t0, t1))
            op = base_collective_op(e["hlo_op"] or e["name"]) or "collective"
            slot = collectives.setdefault(op, {"count": 0, "time_us": 0.0})
            slot["count"] += 1
            slot["time_us"] += e["dur"]
            op_spans.setdefault(op, []).append((t0, t1))
        else:
            compute_spans.append((t0, t1))
        lane_raw.setdefault(bucket, []).append((t0 - span_lo, t1 - span_lo))
    busy = _merged_intervals(all_spans)
    comm = _merged_intervals(comm_spans)
    compute = _merged_intervals(compute_spans)
    busy_us = _union_us(busy)
    comm_us = _union_us(comm)
    compute_us = _union_us(compute)
    overlapped_us = _intersect_us(comm, compute)
    span_us = span_hi - span_lo
    total_op_us = sum(buckets.values())
    acct: dict[str, Any] = {
        "event": "device_account",
        "events": len(events),
        "span_ms": _ms(span_us),
        "busy_ms": _ms(busy_us),
        "exposed_idle_ms": _ms(max(0.0, span_us - busy_us)),
        "buckets_ms": {b: _ms(buckets[b]) for b in DEVICE_BUCKETS},
        "bucket_frac": {
            b: round(buckets[b] / total_op_us, 4) if total_op_us else 0.0
            for b in DEVICE_BUCKETS
        },
        # per op: time_ms is summed device·time across every lane that
        # ran the op (N participants ≈ N× one device's time); wall_ms is
        # the interval UNION — the wall during which the op ran on ANY
        # lane, the lane-count-independent denominator the bandwidth
        # join divides by
        "collectives": {
            op: {
                "count": s["count"],
                "time_ms": _ms(s["time_us"]),
                "wall_ms": _ms(_union_us(_merged_intervals(op_spans[op]))),
            }
            for op, s in sorted(collectives.items())
        },
        "overlap": {
            "collective_ms": _ms(comm_us),
            "compute_ms": _ms(compute_us),
            "overlapped_ms": _ms(overlapped_us),
            "exposed_collective_ms": _ms(comm_us - overlapped_us),
            **(
                {"overlap_frac": round(overlapped_us / comm_us, 4)}
                if comm_us > 0
                else {}
            ),
        },
    }
    # bounded per-bucket lanes (merged, largest-first) for the trace
    # exporter's device tracks — enough to DRAW the account, not a full
    # op dump (that is what the raw capture is for)
    lanes: list[list[Any]] = []
    dropped = 0
    for b in DEVICE_BUCKETS:
        if b not in lane_raw:
            continue
        merged = _merged_intervals(lane_raw[b])
        merged.sort(key=lambda iv: iv[0] - iv[1])  # longest first
        budget_n = max_lane_slices - len(lanes)
        dropped += max(0, len(merged) - budget_n)
        lanes.extend(
            [b, _ms(t0), _ms(t1 - t0)] for t0, t1 in merged[:budget_n]
        )
    lanes.sort(key=lambda s: s[1])
    acct["lanes"] = lanes
    if dropped:
        acct["lane_slices_dropped"] = dropped
    return acct


def device_account_from_dir(
    trace_dir: str,
    *,
    op_buckets: Mapping[str, str] | None = None,
) -> dict[str, Any] | None:
    """Parse the newest trace session under ``trace_dir`` into a device
    account.  None when no trace file or no device op events exist."""
    files = find_trace_files(trace_dir)
    if not files:
        return None
    # one capture session can write several host files; take every file
    # sharing the newest session directory
    session_dir = os.path.dirname(files[0])
    events: list[dict] = []
    for path in files:
        if os.path.dirname(path) == session_dir:
            events.extend(device_op_events(load_trace_events(path)))
    acct = build_account(events, op_buckets=op_buckets)
    if acct is not None:
        acct["trace_dir"] = trace_dir
    return acct


# ---------------------------------------------------------------------------
# the byte-account join
# ---------------------------------------------------------------------------


def join_collective_bandwidth(
    account: dict[str, Any],
    comm: Mapping[str, Any] | None,
    window_steps: int,
) -> dict[str, Any]:
    """Stamp achieved bytes/sec onto the account's per-collective rows.

    ``comm`` is obs/gauges.py's static per-step byte account
    (``collective_traffic``: per-op dicts with gradient/activation
    bytes).  bytes moved = per-step bytes × window steps; achieved
    bandwidth = bytes moved / the op's WALL time (``wall_ms``, the
    cross-lane interval union) — dividing by the lane-summed ``time_ms``
    would understate bandwidth by the local-device count on any
    multi-device host.  The byte account is already per-device tensor
    bytes, so the quotient is the per-device achieved rate.  Mutates and
    returns ``account`` — shared by the runtime emission (TrainerObs)
    and the offline report, so the two cannot disagree on the
    arithmetic."""
    if not comm or window_steps <= 0:
        return account
    for op, slot in account.get("collectives", {}).items():
        per_step = comm.get(op)
        if not isinstance(per_step, Mapping):
            continue
        step_bytes = int(per_step.get("gradient_bytes", 0)) + int(
            per_step.get("activation_bytes", 0)
        )
        slot["bytes_per_step"] = step_bytes
        wall_s = float(slot.get("wall_ms", slot.get("time_ms", 0.0)) or 0.0) / 1e3
        if step_bytes > 0 and wall_s > 0:
            slot["achieved_bytes_per_sec"] = round(
                step_bytes * window_steps / wall_s, 1
            )
    return account


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m distributed_llms_example_tpu.obs.devprof",
        description=__doc__,
    )
    p.add_argument("trace_dir", help="a profile capture dir (obs/profile.py)")
    p.add_argument(
        "--hlo-text", default="",
        help="compiled HLO text file: builds the instruction→bucket index "
             "so CPU-trace events attribute to module buckets",
    )
    args = p.parse_args(argv)
    op_buckets = None
    if args.hlo_text:
        with open(args.hlo_text) as f:
            op_buckets = op_bucket_index(f.read())
    acct = device_account_from_dir(args.trace_dir, op_buckets=op_buckets)
    if acct is None:
        print(f"no device op events under {args.trace_dir}", file=sys.stderr)
        return 2
    print(json.dumps(acct))
    return 0


if __name__ == "__main__":
    sys.exit(main())
