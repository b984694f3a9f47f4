"""Offline consumer for the per-process ``--obs jsonl`` telemetry.

``python -m distributed_llms_example_tpu.obs.report <output_dir>`` reads
every ``obs/metrics-p*.jsonl`` (and any ``obs/flight-recorder-p*.json``
bundle) a run left behind, validates ``schema_version`` on every line,
and reconstructs the run:

- a **merged per-step timeline** joining, on the global ``step`` field,
  process 0's metric lines (loss / lr / tokens-per-sec), every process's
  ``obs_window`` span summaries, eval events (``val_loss`` — same
  ``step`` field as train events), heartbeat skew, and anomalies;
- a **"Where did the time go" budget section** from the ``step_budget``
  events (obs/budget.py): per-window additive component tables per rank,
  the worst-offender ranking over the host-stall components, the
  wall-weighted ``dispatch_efficiency``, and every off-cadence
  host-blocking-dispatch incident the runtime tripwire flagged.
  ``--min-dispatch-efficiency X`` + ``--strict`` turn a regressed
  efficiency into a nonzero exit (the trainer-loop-gap CI gate);
- a **device account section** from the ``device_account`` events
  (obs/devprof.py — profile captures parsed at runtime): per-module-
  bucket device time, per-collective achieved bandwidth (measured device
  time joined with the gauges' static byte account), and the compute↔comm
  overlap / exposed-idle metrics, all from the JSONL alone (no trace
  files needed at report time).  ``--min-overlap-frac X`` + ``--strict``
  gate on exposed collectives and on captures that produced no account;
- **window trends**: p50/p95 step time per process across the run (is it
  getting slower? did one host drift?);
- **straggler attribution**: which ranks the heartbeat named laggards
  and how often, next to each rank's own window p95 — the "go look at
  host N" answer;
- the **comm-bytes account** from the startup gauges, with the
  reduce-scatter smell predicate (analysis/ir_lint.py) evaluated over it
  — an fsdp run whose gradient bytes ride all-reduce is flagged right in
  the report;
- an **"Open-loop load sweep" section** from the ``loadgen_point`` /
  ``loadgen_summary`` events (serving/loadgen.py): the offered-vs-
  achieved/goodput and TTFT-percentile curves per offered-QPS grid
  point, per-point SLO attainment, and the detected saturation knee —
  rendered from the JSONL alone.  ``--min-slo-attainment X`` /
  ``--max-p99-ttft-ms Y`` + ``--strict`` gate on the curve (missing
  loadgen measurement = fail);
- the **anomaly log** (``obs_anomaly`` events + flight-recorder
  bundles).

Output: human markdown (default) or ``--json``.  Schema drift is
reported per line; ``--strict`` turns any invalid line into a nonzero
exit.  Pure file reader — jax is imported by nothing on this path, so
the report runs anywhere the output dir is mounted.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Any

from distributed_llms_example_tpu.obs.sink import SCHEMA_VERSION

_PROC_RE = re.compile(r"-p(\d+)\.jsonl?$")


def load_jsonl(path: str) -> tuple[list[dict], list[str]]:
    """Parse one JSONL file, checking ``schema_version`` on every line.
    Returns (valid records, per-line error strings).  A trailing torn
    line (kill mid-write) is an error entry, not an exception."""
    records: list[dict] = []
    errors: list[str] = []
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                errors.append(f"{path}:{lineno}: unparseable line ({e})")
                continue
            if not isinstance(rec, dict):
                errors.append(f"{path}:{lineno}: not a JSON object")
                continue
            v = rec.get("schema_version")
            if v != SCHEMA_VERSION:
                errors.append(
                    f"{path}:{lineno}: schema_version {v!r} != {SCHEMA_VERSION}"
                )
                continue
            records.append(rec)
    return records, errors


def load_run(output_dir: str) -> dict[str, Any]:
    """Read every per-process stream + recorder bundle under
    ``<output_dir>/obs/``."""
    obs_dir = os.path.join(output_dir, "obs")
    processes: dict[int, list[dict]] = {}
    errors: list[str] = []
    for path in sorted(glob.glob(os.path.join(obs_dir, "metrics-p*.jsonl"))):
        m = _PROC_RE.search(path)
        if not m:
            continue
        recs, errs = load_jsonl(path)
        processes[int(m.group(1))] = recs
        errors.extend(errs)
    recorders: dict[int, dict] = {}
    for path in sorted(glob.glob(os.path.join(obs_dir, "flight-recorder-p*.json"))):
        m = re.search(r"-p(\d+)\.json$", path)
        if not m:
            continue
        try:
            with open(path) as f:
                bundle = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            errors.append(f"{path}: unreadable bundle ({e})")
            continue
        if bundle.get("schema_version") != SCHEMA_VERSION:
            errors.append(
                f"{path}: schema_version {bundle.get('schema_version')!r} "
                f"!= {SCHEMA_VERSION}"
            )
            continue
        recorders[int(m.group(1))] = bundle
    postmortems: dict[int, dict] = {}
    for path in sorted(
        glob.glob(os.path.join(obs_dir, "memory-postmortem-p*.json"))
    ):
        m = re.search(r"-p(\d+)\.json$", path)
        if not m:
            continue
        try:
            with open(path) as f:
                bundle = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            errors.append(f"{path}: unreadable bundle ({e})")
            continue
        if bundle.get("schema_version") != SCHEMA_VERSION:
            errors.append(
                f"{path}: schema_version {bundle.get('schema_version')!r} "
                f"!= {SCHEMA_VERSION}"
            )
            continue
        postmortems[int(m.group(1))] = bundle
    return {
        "processes": processes,
        "recorders": recorders,
        "postmortems": postmortems,
        "errors": errors,
    }


def _by_event(records: list[dict]) -> dict[str, list[dict]]:
    out: dict[str, list[dict]] = {}
    for r in records:
        out.setdefault(r.get("event", "metric"), []).append(r)
    return out


def merge_timeline(processes: dict[int, list[dict]]) -> list[dict]:
    """Join every process's records on the global ``step`` field into one
    chronological per-step timeline."""
    steps: dict[int, dict[str, Any]] = {}

    def at(step: Any) -> dict | None:
        if not isinstance(step, (int, float)):
            return None
        return steps.setdefault(int(step), {"step": int(step)})

    for proc, records in sorted(processes.items()):
        ev = _by_event(records)  # bucket once per process
        for r in ev.get("metric", []):
            row = at(r.get("step"))
            if row is None or "loss" not in r:
                continue
            for k in ("loss", "learning_rate", "tokens_per_sec", "steps_per_sec", "epoch"):
                if k in r:
                    row[k] = r[k]
        for r in ev.get("obs_window", []):
            row = at(r.get("step"))
            if row is None:
                continue
            row.setdefault("windows", {})[proc] = {
                "p50": r.get("step_ms_p50"),
                "p95": r.get("step_ms_p95"),
                "max": r.get("step_ms_max"),
                "straggler": r.get("straggler"),
            }
            if "health" in r:
                row.setdefault("health", {})[proc] = r["health"]
        for r in ev.get("eval", []):
            row = at(r.get("step"))
            if row is None:
                continue
            for k, v in r.items():
                if k not in ("event", "step", "schema_version"):
                    row.setdefault("eval", {})[k] = v
        for r in ev.get("heartbeat", []):
            row = at(r.get("step"))
            if row is None:
                continue
            row["heartbeat"] = {
                k: r.get(k)
                for k in ("skew_steps", "arrival_spread_s", "laggards", "process_count")
            }
        for r in ev.get("obs_anomaly", []):
            row = at(r.get("step"))
            if row is None:
                continue
            row.setdefault("anomalies", []).append(
                {
                    k: r.get(k)
                    for k in ("code", "ranks", "policy", "value", "detail", "detected_at_step")
                    if k in r
                }
            )
    return [steps[s] for s in sorted(steps)]


def straggler_attribution(processes: dict[int, list[dict]]) -> dict[str, Any]:
    """Who was slow: heartbeat laggard counts per rank (the gather is a
    barrier, so a laggard there really did keep everyone waiting) next to
    each rank's own mean window p95."""
    laggard_counts: dict[int, int] = {}
    max_skew = 0
    max_spread = 0.0
    per_rank_p95: dict[int, float] = {}
    straggler_windows: dict[int, int] = {}
    for proc, records in sorted(processes.items()):
        ev = _by_event(records)  # bucket once per process
        for r in ev.get("heartbeat", []):
            for lag in r.get("laggards", []) or []:
                laggard_counts[int(lag)] = laggard_counts.get(int(lag), 0) + 1
            max_skew = max(max_skew, int(r.get("skew_steps", 0) or 0))
            max_spread = max(max_spread, float(r.get("arrival_spread_s", 0.0) or 0.0))
        windows = ev.get("obs_window", [])
        p95s = [
            r["step_ms_p95"]
            for r in windows
            if isinstance(r.get("step_ms_p95"), (int, float))
        ]
        if p95s:
            per_rank_p95[proc] = round(sum(p95s) / len(p95s), 3)
        straggler_windows[proc] = sum(1 for r in windows if r.get("straggler"))
    return {
        "heartbeat_laggard_counts": {str(k): v for k, v in sorted(laggard_counts.items())},
        "max_skew_steps": max_skew,
        "max_arrival_spread_s": max_spread,
        "mean_step_ms_p95_by_rank": {str(k): v for k, v in sorted(per_rank_p95.items())},
        "straggler_windows_by_rank": {str(k): v for k, v in sorted(straggler_windows.items())},
    }


def window_trends(processes: dict[int, list[dict]]) -> dict[str, list[dict]]:
    out: dict[str, list[dict]] = {}
    for proc, records in sorted(processes.items()):
        out[str(proc)] = [
            {
                "step": r.get("step"),
                "p50": r.get("step_ms_p50"),
                "p95": r.get("step_ms_p95"),
                "mfu": r.get("mfu"),
            }
            for r in _by_event(records).get("obs_window", [])
        ]
    return out


def comm_report(processes: dict[int, list[dict]]) -> dict[str, Any] | None:
    """The startup gauges' collective-traffic account, with the
    reduce-scatter smell predicate evaluated over it."""
    for records in processes.values():
        for r in _by_event(records).get("obs_gauges", []):
            comm = r.get("comm")
            if not isinstance(comm, dict):
                continue
            out: dict[str, Any] = {
                "mesh": r.get("mesh"),
                "flops_per_step": r.get("flops_per_step"),
                "flops_source": r.get("flops_source"),
                "grad_compression": r.get("grad_compression"),
                "comm": comm,
            }
            from distributed_llms_example_tpu.analysis.ir_lint import (
                account_gradient_bytes_by_op,
                reduce_scatter_smell,
            )

            smell = reduce_scatter_smell(
                account_gradient_bytes_by_op(comm), r.get("mesh") or {}
            )
            if smell is not None:
                out["reduce_scatter_smell"] = smell.to_json()
            return out
    return None


def budget_report(processes: dict[int, list[dict]]) -> dict[str, Any] | None:
    """The "Where did the time go" rollup over every rank's
    ``step_budget`` events: per-rank component totals + efficiency (via
    obs/budget.py's shared aggregation, so bench and the report cannot
    disagree), the worst-offender ranking over host-stall components, and
    the off-cadence host-blocking-dispatch incident list."""
    from distributed_llms_example_tpu.obs.budget import (
        COMPONENTS,
        aggregate_accounts,
    )

    ranks: dict[str, Any] = {}
    windows: dict[str, list[dict]] = {}
    incidents: list[dict] = []
    eff_wall: list[tuple[float, float]] = []
    for proc, records in sorted(processes.items()):
        accts = _by_event(records).get("step_budget", [])
        if not accts:
            continue
        agg = aggregate_accounts(accts)
        ranks[str(proc)] = agg
        windows[str(proc)] = [
            {
                "step": a.get("step"),
                "wall_ms": a.get("wall_ms"),
                **{f"{c}_ms": a.get(f"{c}_ms") for c in COMPONENTS},
                "dispatch_efficiency": a.get("dispatch_efficiency"),
                "accounted_frac": a.get("accounted_frac"),
                "offcadence_sync_steps": a.get("offcadence_sync_steps", 0),
            }
            for a in accts
        ]
        for a in accts:
            # SUSPECT windows only: on a synchronous-dispatch backend
            # (multi-device CPU) the raw count is that backend's normal
            # mode, stamped sync_dispatch_backend — not an incident
            if a.get("offcadence_sync_suspect"):
                incidents.append({
                    "rank": proc,
                    "step": a.get("step"),
                    "blocked_steps": int(a.get("offcadence_sync_steps", 0) or 0),
                    "window_steps": a.get("window_steps"),
                    "dispatch_ms": a.get("dispatch_ms"),
                })
        if agg and agg.get("wall_ms"):
            eff_wall.append((agg["dispatch_efficiency"], agg["wall_ms"]))
    if not ranks:
        return None
    total_wall = sum(w for _, w in eff_wall)
    overall_eff = (
        round(sum(e * w for e, w in eff_wall) / total_wall, 4)
        if total_wall
        else None
    )
    # worst offenders: the host-stall components (the time the device was
    # NOT being fed), ranked by share of total wall across ranks
    stall_components = ("data_wait", "host_overhead", "sync_block", "unattributed")
    totals = {
        c: sum(r.get(f"{c}_ms", 0.0) or 0.0 for r in ranks.values())
        for c in stall_components
    }
    all_wall = sum(r.get("wall_ms", 0.0) or 0.0 for r in ranks.values())
    offenders = sorted(
        (
            {"component": c, "total_ms": round(v, 3),
             "share": round(v / all_wall, 4) if all_wall else 0.0}
            for c, v in totals.items()
        ),
        key=lambda o: -o["total_ms"],
    )
    return {
        "ranks": ranks,
        "windows": windows,
        "offenders": offenders,
        "incidents": incidents,
        "dispatch_efficiency": overall_eff,
    }


def device_report(processes: dict[int, list[dict]]) -> dict[str, Any] | None:
    """The device-time attribution rollup: each rank's NEWEST
    ``device_account`` (a parsed profile capture — obs/devprof.py), the
    ``profile_captured`` inventory, and the achieved-bandwidth join
    against the startup gauges' byte account for any account the runtime
    emitted without one (e.g. gauges landed after the capture).  Renders
    from the JSONL alone — no trace files are read here."""
    from distributed_llms_example_tpu.obs.devprof import (
        join_collective_bandwidth,
    )

    comm = None
    for records in processes.values():
        for r in _by_event(records).get("obs_gauges", []):
            if isinstance(r.get("comm"), dict):
                comm = r["comm"]
                break
        if comm:
            break
    ranks: dict[str, dict] = {}
    captures: list[dict] = []
    n_accounts = 0
    for proc, records in sorted(processes.items()):
        ev = _by_event(records)
        for r in ev.get("profile_captured", []):
            captures.append({
                "rank": proc,
                "path": r.get("path"),
                "window": r.get("window"),
                "steps": r.get("steps"),
                **({"truncated": True} if r.get("truncated") else {}),
            })
        accts = ev.get("device_account", [])
        n_accounts += len(accts)
        if not accts:
            continue
        acct = dict(accts[-1])  # newest capture is the rank's account
        acct.pop("lanes", None)  # exporter payload, not report material
        needs_join = any(
            "achieved_bytes_per_sec" not in slot
            for slot in (acct.get("collectives") or {}).values()
        )
        if needs_join and comm:
            join_collective_bandwidth(
                acct, comm, int(acct.get("window_steps", 0) or 0)
            )
        ranks[str(proc)] = acct
    if not ranks and not captures:
        return None
    return {"ranks": ranks, "captures": captures, "accounts": n_accounts}


def recovery_report(processes: dict[int, list[dict]]) -> dict[str, Any]:
    """The fault-tolerance timeline: chaos injections, recovery actions
    (rewinds / skip-batch / halts), quarantines, checkpoint-integrity
    failures, data retries — with the injected/organic split.

    A fault is **injected** when a ``chaos_injection`` event explains it
    (``nan_grad`` at the anomaly's step; any ``ckpt_corrupt`` firing for
    an integrity failure; ``data_error`` at a retry's step); everything
    else is **organic** — the distinction ``--strict`` gates on (a chaos
    run is green only when every fault it saw is one it caused)."""
    injections: list[dict] = []
    corrupted: list[dict] = []
    recoveries: list[dict] = []
    quarantines: list[dict] = []
    verify_failures: list[dict] = []
    data_events: list[dict] = []
    anomalies: list[dict] = []
    topo_changes: list[dict] = []
    reshards: list[dict] = []
    replica_events: list[dict] = []
    serve_retries: list[dict] = []
    serve_sheds: list[dict] = []
    router_summaries: list[dict] = []
    suspects: list[dict] = []
    # injections/recoveries/quarantines are ``local`` events (every
    # rank's file carries its own copy — the schedule and the escalation
    # are deterministic across the pod): dedup to per-run rows
    seen: set = set()

    def dedup(into: list[dict], rec: dict, *keys: str) -> None:
        k = (rec.get("event"),) + tuple(rec.get(x) for x in keys)
        if k not in seen:
            seen.add(k)
            into.append(rec)

    for _, records in sorted(processes.items()):
        ev = _by_event(records)
        for r in ev.get("chaos_injection", []):
            dedup(injections, r, "kind", "step")
        for r in ev.get("chaos_ckpt_corrupted", []):
            dedup(corrupted, r, "step", "path")
        for r in ev.get("recovery", []):
            # rewind_index is in the key: two rewinds with the same
            # (step, restored_step) — a second poison batch on the replay
            # — are distinct recoveries, not per-rank copies
            dedup(
                recoveries, r,
                "action", "step", "detected_at_step", "restored_step",
                "rewind_index",
            )
        for r in ev.get("quarantine", []):
            dedup(quarantines, r, "epoch", "epoch_step")
        for r in ev.get("topology_change", []):
            dedup(topo_changes, r, "step", "policy")
        for r in ev.get("reshard_restore", []):
            # (step, detected_at_step) identifies one reshard across the
            # ranks' local copies; wall clock differs per rank, so it
            # must stay OUT of the key
            dedup(reshards, r, "step", "detected_at_step", "new_processes")
        for r in ev.get("replica_health", []):
            # local events: every rank's file may carry a copy (single-
            # process today, per-host tomorrow) — one row per transition
            dedup(replica_events, r, "replica", "from", "to", "tick")
        for r in ev.get("serve_retry", []):
            dedup(serve_retries, r, "request", "retries", "tick", "reason")
        for r in ev.get("serve_shed", []):
            dedup(serve_sheds, r, "request", "tick")
        for r in ev.get("host_loss_suspect", []):
            dedup(suspects, r, "rank", "step")
        router_summaries.extend(ev.get("router_summary", []))
        for kind in ("ckpt_verify_failed", "ckpt_restore_failed"):
            verify_failures.extend(ev.get(kind, []))
        for kind in ("data_retry", "data_skipped_records"):
            data_events.extend(ev.get(kind, []))
        anomalies.extend(ev.get("obs_anomaly", []))
    injected_at: dict[str, set] = {}
    for i in injections:
        injected_at.setdefault(i.get("kind", "?"), set()).add(i.get("step"))

    def fault_row(kind: str, step: Any, injected: bool, detail: str) -> dict:
        return {"kind": kind, "step": step, "injected": injected, "detail": detail}

    faults: list[dict] = []
    seen_anomaly_steps = set()
    for a in anomalies:
        key = (a.get("step"), a.get("code"))
        if key in seen_anomaly_steps:
            continue  # one fault per (step, code), however many ranks logged it
        seen_anomaly_steps.add(key)
        injected = a.get("step") in injected_at.get("nan_grad", set())
        faults.append(fault_row(
            f"anomaly:{a.get('code')}", a.get("step"), injected,
            str(a.get("detail", ""))[:120],
        ))
    # per-step match: a verify failure is injected only when the chaos
    # harness corrupted THAT step (chaos_ckpt_corrupted carries the step
    # dir's number) — an organic corruption elsewhere in the same chaos
    # run must stay organic
    corrupted_steps = {c.get("step") for c in corrupted if "step" in c}
    seen_ckpt_steps = set()
    for v in verify_failures:
        if v.get("step") in seen_ckpt_steps:
            continue
        seen_ckpt_steps.add(v.get("step"))
        faults.append(fault_row(
            "ckpt_integrity", v.get("step"), v.get("step") in corrupted_steps,
            str(v.get("detail", v.get("error", "")))[:120],
        ))
    seen_data_steps = set()
    for d in data_events:
        if d.get("event") == "data_retry" and d.get("step") not in seen_data_steps:
            seen_data_steps.add(d.get("step"))
            injected = d.get("step") in injected_at.get("data_error", set())
            faults.append(fault_row(
                "data_retry", d.get("step"), injected, str(d.get("error", ""))[:120]
            ))
    # a topology change is a FAULT (a host left) even when the recovery
    # succeeds: injected when a host_loss chaos firing explains its step,
    # organic otherwise — exactly the split --strict gates on
    for t in topo_changes:
        injected = t.get("step") in injected_at.get("host_loss", set())
        faults.append(fault_row(
            "topology_change", t.get("step"), injected,
            f"policy {t.get('policy')}: "
            f"{t.get('old_mesh')} → {t.get('reason', 'reshard')}"[:120],
        ))
    # serving tier (ISSUE 15): a replica DYING is a fault even when every
    # request re-prefilled cleanly — the crash kind matches the injection
    # at its tick exactly; a stall's death tick trails its injection (the
    # heartbeat-miss detector needs dead_after ticks), so the match
    # window is [since_tick, tick] (since_tick = the replica's last
    # progress, stamped on the transition event)
    for r in replica_events:
        if r.get("to") != "dead":
            continue
        cause = r.get("cause", "crash")
        tick = r.get("tick")
        if cause == "stall":
            lo = r.get("since_tick", tick)
            injected = any(
                s is not None and lo is not None and tick is not None
                and lo <= s <= tick
                for s in injected_at.get("replica_stall", set())
            )
        else:
            injected = tick in injected_at.get("replica_crash", set())
        faults.append(fault_row(
            f"replica_{cause}", tick, injected,
            f"replica {r.get('replica')}: {str(r.get('reason', ''))}"[:120],
        ))
    organic = [f for f in faults if not f["injected"]]
    rewinds = [r for r in recoveries if r.get("action") == "rewind"]
    # reshard wall-clock counts toward MTTR: a topology recovery is a
    # recovery, and its restore is the dominant cost
    mttr_vals = [
        r["recovery_wall_s"]
        for r in rewinds
        if isinstance(r.get("recovery_wall_s"), (int, float))
    ] + [
        r["reshard_wall_s"]
        for r in reshards
        if isinstance(r.get("reshard_wall_s"), (int, float))
    ]
    serving = None
    if replica_events or serve_retries or serve_sheds or router_summaries:
        rs = router_summaries[-1] if router_summaries else {}
        serving = {
            "replica_transitions": [
                {
                    k: r.get(k)
                    for k in ("replica", "from", "to", "tick", "reason", "cause")
                    if k in r
                }
                for r in replica_events
            ],
            "replicas_lost": sum(
                1 for r in replica_events if r.get("to") == "dead"
            ),
            # failure retries of REAL traffic only: a drain re-dispatch
            # lost no work (the router doesn't count it either), and a
            # synthetic storm request's retries are injected load — both
            # would overstate failures next to the summary's rate
            "retries": sum(
                1 for r in serve_retries
                if r.get("reason") != "drain" and not r.get("synthetic")
            ),
            "redispatches": len(serve_retries),
            "shed": sum(
                1 for r in serve_sheds if not r.get("synthetic")
            ),
            "shed_total": len(serve_sheds),  # synthetic storm included
            "shed_by_reason": rs.get("shed_by_reason"),
            # the request-level recovery numbers the acceptance pins:
            # finite MTTR for re-prefilled requests + the gate inputs
            "request_mttr_s": rs.get("request_mttr_s"),
            "request_retry_rate": rs.get("request_retry_rate"),
            "goodput_frac": rs.get("goodput_frac"),
            "requests": rs.get("requests"),
            "completed": rs.get("completed"),
        }
    return {
        "injections": [
            {"kind": i.get("kind"), "step": i.get("step")} for i in injections
        ],
        "actions": [
            {
                k: r.get(k)
                for k in (
                    "action", "step", "code", "restored_step", "steps_lost",
                    "rewind_index", "recovery_wall_s", "reason",
                )
                if k in r
            }
            for r in recoveries
        ],
        "quarantines": [
            {k: q.get(k) for k in ("epoch", "epoch_step", "reason") if k in q}
            for q in quarantines
        ],
        "topology": [
            {
                k: t.get(k)
                for k in (
                    "step", "policy", "old_mesh", "old_processes", "reason",
                )
                if k in t
            }
            for t in topo_changes
        ],
        "reshards": [
            {
                k: r.get(k)
                for k in (
                    "step", "detected_at_step", "old_mesh", "new_mesh",
                    "old_processes", "new_processes", "ef_mode",
                    "steps_lost", "reshard_wall_s",
                )
                if k in r
            }
            for r in reshards
        ],
        "rewinds": len(rewinds),
        "steps_lost_total": sum(
            int(r.get("steps_lost", 0) or 0) for r in rewinds
        ) + sum(int(r.get("steps_lost", 0) or 0) for r in reshards),
        "mttr_s": (
            round(sum(mttr_vals) / len(mttr_vals), 4) if mttr_vals else None
        ),
        "serving": serving,
        "host_loss_suspects": [
            {
                k: s.get(k)
                for k in ("rank", "step", "consecutive_beats")
                if k in s
            }
            for s in suspects
        ],
        "faults": faults,
        "organic_faults": organic,
    }


def loadgen_report(processes: dict[int, list[dict]]) -> dict[str, Any] | None:
    """The open-loop load sweep rollup: the curve (one row per offered-
    QPS grid point) + the detected knee, from ``loadgen_point`` /
    ``loadgen_summary`` events alone.  The newest ``loadgen_summary``
    is authoritative for the curve and knee (it embeds its points);
    bare points (a run killed mid-sweep) still render.

    ``best_slo_attainment`` / ``best_ttft_p99_ms`` are the gate inputs:
    the best attainment any measured point reached, and the lowest
    MEASURED p99 TTFT (points where nothing finished measure None and
    are excluded — so a run whose every point collapsed has no p99 at
    all, and a p99 gate on it fails as a missing measurement)."""
    points: list[dict] = []
    summaries: list[dict] = []
    for _, records in sorted(processes.items()):
        ev = _by_event(records)
        points.extend(ev.get("loadgen_point", []))
        summaries.extend(ev.get("loadgen_summary", []))
    if not points and not summaries:
        return None
    summary = summaries[-1] if summaries else None
    curve = list((summary or {}).get("points") or points)
    attains = [
        p["slo_attainment"] for p in curve
        if isinstance(p.get("slo_attainment"), (int, float))
    ]
    p99s = [
        p["ttft_p99_ms"] for p in curve
        if isinstance(p.get("ttft_p99_ms"), (int, float))
    ]
    meta = summary or (points[-1] if points else {})
    return {
        "process": meta.get("process"),
        "seed": meta.get("seed"),
        "ttft_slo_ms": meta.get("ttft_slo_ms"),
        "requests_per_point": (summary or {}).get("requests_per_point"),
        "qps_grid": (summary or {}).get("qps_grid"),
        "knee_qps": (summary or {}).get("knee_qps"),
        "sweeps": len(summaries),
        "points": curve,
        "best_slo_attainment": max(attains) if attains else None,
        "best_ttft_p99_ms": min(p99s) if p99s else None,
    }


def prefix_report(processes: dict[int, list[dict]]) -> dict[str, Any] | None:
    """The prefix-cache rollup: reuse ledger from ``serve_summary``
    events whose engine ran with the cache on (``prefix_cache: true``)
    plus the router's cross-replica aggregate when one exists (a
    ``router_summary`` carrying ``prefix_hit_rate``).  The router
    aggregate is authoritative when present — per-replica summaries
    double-count nothing but see only their own traffic.

    ``hit_rate`` is the gate input: None when no prefix-enabled engine
    ever summarized, and the strict ``--min-prefix-hit-rate`` gate
    treats that as a failure, never a pass."""
    serve: list[dict] = []
    router: list[dict] = []
    windows = 0
    for _, records in sorted(processes.items()):
        ev = _by_event(records)
        serve.extend(
            r for r in ev.get("serve_summary", []) if r.get("prefix_cache")
        )
        router.extend(
            r for r in ev.get("router_summary", []) if "prefix_hit_rate" in r
        )
        windows += sum(
            1 for r in ev.get("serve_window", []) if "prefix_hit_rate" in r
        )
    if not (serve or router):
        return None
    src = router[-1] if router else serve[-1]
    latest = serve[-1] if serve else {}
    return {
        "scope": "router" if router else "engine",
        "hit_rate": src.get("prefix_hit_rate"),
        "lookups": src.get("prefix_lookups"),
        "hits": src.get("prefix_hits"),
        "prefill_tokens_total": src.get("prefill_tokens_total"),
        "prefill_tokens_saved": src.get("prefill_tokens_saved"),
        "prefill_tokens_saved_frac": src.get("prefill_tokens_saved_frac"),
        "budget_gib": latest.get("prefix_cache_budget_gib"),
        "pool_blocks_warm": latest.get("pool_blocks_warm"),
        "warm_bytes": latest.get("warm_bytes"),
        "windows": windows,
        "engines": len(serve),
    }


def spec_report(processes: dict[int, list[dict]]) -> dict[str, Any] | None:
    """The speculative-decode rollup: the acceptance ledger from
    ``serve_summary`` events whose engine ran with speculation on
    (``spec_decode: true``) plus the router's cross-replica aggregate
    when one exists (a ``router_summary`` carrying ``acceptance_rate``).
    The router aggregate is authoritative when present — same precedence
    as the prefix-cache rollup.

    ``acceptance_rate`` is the gate input: None when no spec-enabled
    engine ever summarized, and the strict ``--min-acceptance-rate``
    gate treats that as a failure, never a pass."""
    serve: list[dict] = []
    router: list[dict] = []
    windows = 0
    for _, records in sorted(processes.items()):
        ev = _by_event(records)
        serve.extend(
            r for r in ev.get("serve_summary", []) if r.get("spec_decode")
        )
        router.extend(
            r for r in ev.get("router_summary", []) if "acceptance_rate" in r
        )
        windows += sum(
            1 for r in ev.get("serve_window", []) if "acceptance_rate" in r
        )
    if not (serve or router):
        return None
    src = router[-1] if router else serve[-1]
    latest = serve[-1] if serve else {}
    return {
        "scope": "router" if router else "engine",
        "acceptance_rate": src.get("acceptance_rate"),
        "accepted_tokens_per_step": src.get("accepted_tokens_per_step"),
        "drafted_tokens": src.get("spec_drafted_tokens"),
        "accepted_tokens": src.get("spec_accepted_tokens"),
        "spec_tokens": latest.get("spec_tokens", src.get("spec_tokens")),
        "draft_model": latest.get("spec_draft_model"),
        "spec_steps": latest.get("spec_steps"),
        "windows": windows,
        "engines": len(serve),
    }


def memory_report(
    processes: dict[int, list[dict]],
    postmortems: dict[int, dict] | None = None,
) -> dict[str, Any] | None:
    """"Where did the bytes go" — the HBM rollup from the JSONL (and
    postmortem bundles) alone: the last static ``memory_account`` (the
    bucketed peak composition of the compiled step), the runtime
    ``memory_window`` envelope (max bytes-in-use / peak / per-window
    watermark delta over every rank's samples), the serving tier's
    account off its ``serve_summary``, and any ``memory-postmortem-p*``
    bundles.  ``measured_peak_bytes`` is the gate input: the runtime peak
    when any window was sampled, else the static account's compiled peak
    — a run with NEITHER has no measurement, and the strict gates treat
    that as a failure, never a pass."""
    accounts: list[dict] = []
    windows: list[dict] = []
    skips: list[dict] = []
    serve_accounts: list[dict] = []
    for _, records in sorted(processes.items()):
        ev = _by_event(records)
        accounts.extend(ev.get("memory_account", []))
        windows.extend(ev.get("memory_window", []))
        skips.extend(ev.get("memory_window_skipped", []))
        for r in ev.get("serve_summary", []):
            if isinstance(r.get("memory_account"), dict):
                serve_accounts.append(r["memory_account"])
    postmortems = postmortems or {}
    if not (accounts or windows or skips or serve_accounts or postmortems):
        return None
    account = accounts[-1] if accounts else None
    serve_account = serve_accounts[-1] if serve_accounts else None
    runtime = None
    if windows:
        runtime = {
            "windows": len(windows),
            "max_bytes_in_use": max(int(w.get("bytes_in_use", 0)) for w in windows),
            "peak_bytes_in_use": max(
                int(w.get("peak_bytes_in_use", 0)) for w in windows
            ),
            "max_watermark_delta_bytes": max(
                int(w.get("watermark_delta_bytes", 0)) for w in windows
            ),
            "bytes_limit": max(int(w.get("bytes_limit", 0)) for w in windows),
        }
    measured_peak = None
    peak_source = None
    if runtime is not None:
        measured_peak = runtime["peak_bytes_in_use"]
        peak_source = "memory_window"
    elif account is not None and isinstance(
        account.get("peak_bytes"), (int, float)
    ):
        measured_peak = int(account["peak_bytes"])
        peak_source = "static_account"
    budget_bytes = None
    for src in (account, serve_account):
        if src is not None and isinstance(
            src.get("hbm_budget_bytes"), (int, float)
        ):
            budget_bytes = int(src["hbm_budget_bytes"])
            break
    headrooms = [
        a["hbm_headroom_gib"]
        for a in (account, serve_account)
        if a is not None and isinstance(a.get("hbm_headroom_gib"), (int, float))
    ]
    return {
        "account": account,
        "serve_account": serve_account,
        "runtime": runtime,
        "static_only": bool(not windows and (account or serve_account)),
        "skips": [s.get("reason") for s in skips[:1]],
        "measured_peak_bytes": measured_peak,
        "measured_peak_source": peak_source,
        "hbm_budget_bytes": budget_bytes,
        "peak_frac_of_budget": (
            round(measured_peak / budget_bytes, 4)
            if (measured_peak is not None and budget_bytes)
            else None
        ),
        "min_headroom_gib": min(headrooms) if headrooms else None,
        "postmortems": {
            str(p): {
                "reason": b.get("reason"),
                "step": b.get("step"),
                "has_account": b.get("account") is not None,
                "watermark_samples": len(b.get("watermark_history") or []),
                "live_buffers_top": len(b.get("live_buffers_top") or []),
            }
            for p, b in sorted(postmortems.items())
        },
    }


def build_report(output_dir: str) -> dict[str, Any]:
    run = load_run(output_dir)
    processes = run["processes"]
    anomalies = [
        r
        for records in processes.values()
        for r in _by_event(records).get("obs_anomaly", [])
    ]
    report: dict[str, Any] = {
        "output_dir": output_dir,
        "schema_version": SCHEMA_VERSION,
        "processes": sorted(processes),
        "records": sum(len(r) for r in processes.values()),
        "schema_errors": run["errors"],
        "timeline": merge_timeline(processes),
        "trends": window_trends(processes),
        "stragglers": straggler_attribution(processes),
        "comm": comm_report(processes),
        "budget": budget_report(processes),
        "device": device_report(processes),
        "memory": memory_report(processes, run["postmortems"]),
        "loadgen": loadgen_report(processes),
        "prefix": prefix_report(processes),
        "spec": spec_report(processes),
        "recovery": recovery_report(processes),
        "anomalies": anomalies,
        "recorders": {
            str(p): {
                "reason": b.get("reason"),
                "step": b.get("step"),
                "steps_recorded": len(b.get("entries", [])),
                "anomalies": b.get("anomalies", []),
            }
            for p, b in run["recorders"].items()
        },
    }
    return report


def _fmt(v: Any) -> str:
    if isinstance(v, float):
        return f"{v:.4g}"
    return "" if v is None else str(v)


def render_markdown(report: dict[str, Any], *, last: int = 20) -> str:
    lines: list[str] = []
    add = lines.append
    add(f"# obs report — {report['output_dir']}")
    add("")
    add(
        f"processes: {report['processes'] or 'none'} · records: "
        f"{report['records']} · schema errors: {len(report['schema_errors'])}"
    )
    for e in report["schema_errors"][:10]:
        add(f"- schema error: {e}")
    timeline = report["timeline"]
    add("")
    add(f"## Step timeline ({len(timeline)} steps with events; last {last} shown)")
    add("")
    add("| step | loss | val_loss | p50/p95 ms by rank | skew | anomalies |")
    add("|---|---|---|---|---|---|")
    for row in timeline[-last:]:
        win = row.get("windows", {})
        winfmt = " ".join(
            f"r{p}:{_fmt(w['p50'])}/{_fmt(w['p95'])}"
            + ("!" if w.get("straggler") else "")
            for p, w in sorted(win.items())
        )
        hb = row.get("heartbeat") or {}
        anom = "; ".join(
            f"{a.get('code')}@ranks{a.get('ranks')}" for a in row.get("anomalies", [])
        )
        add(
            f"| {row['step']} | {_fmt(row.get('loss'))} | "
            f"{_fmt((row.get('eval') or {}).get('val_loss'))} | {winfmt} | "
            f"{_fmt(hb.get('skew_steps'))} | {anom} |"
        )
    add("")
    add("## Trends (window p50/p95 ms)")
    for proc, ws in report["trends"].items():
        if not ws:
            continue
        first, final = ws[0], ws[-1]
        add(
            f"- rank {proc}: p50 {_fmt(first['p50'])} → {_fmt(final['p50'])}, "
            f"p95 {_fmt(first['p95'])} → {_fmt(final['p95'])} over {len(ws)} windows"
            + (f", last mfu {_fmt(final['mfu'])}" if final.get("mfu") is not None else "")
        )
    s = report["stragglers"]
    add("")
    add("## Straggler attribution")
    add(
        f"- max heartbeat skew: {s['max_skew_steps']} steps; max arrival "
        f"spread: {_fmt(s['max_arrival_spread_s'])} s"
    )
    if s["heartbeat_laggard_counts"]:
        for rank, n in s["heartbeat_laggard_counts"].items():
            add(f"- rank {rank}: named laggard in {n} heartbeat(s)")
    else:
        add("- no laggards named by any heartbeat")
    if s["mean_step_ms_p95_by_rank"]:
        add(
            "- mean window p95 by rank: "
            + ", ".join(
                f"r{k}={_fmt(v)}ms" for k, v in s["mean_step_ms_p95_by_rank"].items()
            )
        )
    budget = report.get("budget")
    add("")
    add("## Where did the time go")
    if budget is None:
        add("- no step_budget records (run without --obs-budget?)")
    else:
        from distributed_llms_example_tpu.obs.budget import COMPONENTS

        add(
            f"- dispatch efficiency (wall-weighted, all ranks): "
            f"{_fmt(budget['dispatch_efficiency'])}"
        )
        add("")
        header = " | ".join(c for c in COMPONENTS)
        add(f"| rank | windows | wall ms | {header} | efficiency |")
        add("|---" * (len(COMPONENTS) + 4) + "|")
        for rank, agg in sorted(budget["ranks"].items()):
            comps = " | ".join(_fmt(agg.get(f"{c}_ms")) for c in COMPONENTS)
            add(
                f"| {rank} | {agg['windows']} | {_fmt(agg['wall_ms'])} | "
                f"{comps} | {_fmt(agg['dispatch_efficiency'])} |"
            )
        opt_rows = [
            (rank, agg)
            for rank, agg in sorted(budget["ranks"].items())
            if agg.get("optimizer_apply_ms") is not None
        ]
        if opt_rows:
            # the cadenced stand-alone apply sample (obs/budget.py
            # probe_optimizer) — the direct optimizer-ms read the
            # fused-vs-xla --optim-impl A/B consumes
            add(
                "optimizer apply (cadenced stand-alone sample): "
                + ", ".join(
                    f"r{rank}={_fmt(agg['optimizer_apply_ms'])}ms"
                    + (
                        f" ({_fmt(agg['optimizer_share_of_step'] * 100)}% of step)"
                        if agg.get("optimizer_share_of_step") is not None
                        else ""
                    )
                    for rank, agg in opt_rows
                )
            )
        add("")
        add("worst offenders (host-stall components, share of total wall):")
        for o in budget["offenders"]:
            add(
                f"- {o['component']}: {_fmt(o['total_ms'])} ms "
                f"({_fmt(o['share'] * 100)}% of wall)"
            )
        if budget["incidents"]:
            add("")
            add("**off-cadence host-blocking dispatch incidents** (the "
                "runtime rule-4 tripwire — a transfer blocked the step "
                "body outside the logging window):")
            for inc in budget["incidents"]:
                add(
                    f"- rank {inc['rank']} window@step {inc['step']}: "
                    f"{inc['blocked_steps']}/{inc['window_steps']} step(s) "
                    f"blocked in dispatch ({_fmt(inc['dispatch_ms'])} ms total)"
                )
        else:
            add("- no off-cadence host-blocking dispatch detected")
        # per-window trend, most recent windows per rank
        for rank, ws in sorted(budget["windows"].items()):
            shown = ws[-last:]
            if not shown:
                continue
            first, final = shown[0], shown[-1]
            add(
                f"- rank {rank} windows: efficiency "
                f"{_fmt(first['dispatch_efficiency'])} → "
                f"{_fmt(final['dispatch_efficiency'])}, accounted "
                f"{_fmt(final['accounted_frac'])} of wall over {len(ws)} window(s)"
            )
    device = report.get("device")
    add("")
    add("## Device account (profiled windows)")
    if device is None:
        add("- no device_account records (no profile window landed — "
            "touch the profile trigger or pass --profile-steps)")
    else:
        from distributed_llms_example_tpu.obs.devprof import DEVICE_BUCKETS

        for cap in device["captures"]:
            add(
                f"- capture r{cap['rank']}: steps {cap.get('window')} → "
                f"`{cap.get('path')}`"
                + (" (truncated)" if cap.get("truncated") else "")
            )
        if not device["ranks"]:
            add("- captures exist but no device_account parsed — run with "
                "--obs-budget on, or parse offline: python -m "
                "distributed_llms_example_tpu.obs.devprof <capture_dir>")
        else:
            add("")
            add("| rank | window | span ms | busy ms | idle ms | "
                + " | ".join(DEVICE_BUCKETS) + " |")
            add("|---" * (len(DEVICE_BUCKETS) + 5) + "|")
            for rank, acct in sorted(device["ranks"].items()):
                b = acct.get("buckets_ms", {})
                cells = " | ".join(_fmt(b.get(k)) for k in DEVICE_BUCKETS)
                add(
                    f"| {rank} | {acct.get('window')} | "
                    f"{_fmt(acct.get('span_ms'))} | {_fmt(acct.get('busy_ms'))} | "
                    f"{_fmt(acct.get('exposed_idle_ms'))} | {cells} |"
                )
            add("")
            add("collective bandwidth (measured device time × static "
                "byte account):")
            any_coll = False
            for rank, acct in sorted(device["ranks"].items()):
                for op, slot in sorted((acct.get("collectives") or {}).items()):
                    any_coll = True
                    bw = slot.get("achieved_bytes_per_sec")
                    add(
                        f"- r{rank} {op}: ×{slot.get('count')} — "
                        f"{_fmt(slot.get('time_ms'))} ms"
                        + (
                            f", {slot.get('bytes_per_step', 0):,} B/step → "
                            f"{bw / 1e6:.1f} MB/s achieved"
                            if isinstance(bw, (int, float))
                            else ""
                        )
                    )
            if not any_coll:
                add("- no collective device time in the captured window")
            for rank, acct in sorted(device["ranks"].items()):
                ov = acct.get("overlap") or {}
                if not ov:
                    continue
                frac = ov.get("overlap_frac")
                add(
                    f"- r{rank} overlap: collective {_fmt(ov.get('collective_ms'))} ms, "
                    f"compute {_fmt(ov.get('compute_ms'))} ms, "
                    f"overlapped {_fmt(ov.get('overlapped_ms'))} ms"
                    + (
                        f" (overlap_frac {_fmt(frac)})"
                        if frac is not None
                        else ""
                    )
                    + f", exposed collective {_fmt(ov.get('exposed_collective_ms'))} ms, "
                    f"exposed idle {_fmt(acct.get('exposed_idle_ms'))} ms"
                )
    comm = report["comm"]
    add("")
    add("## Comm account")
    if comm is None:
        add("- no obs_gauges record (run without --obs-gauges?)")
    else:
        acct = comm["comm"]
        add(
            f"- total {acct.get('total_bytes', 0):,} B/step — gradient "
            f"{acct.get('gradient_bytes', 0):,} B, activation "
            f"{acct.get('activation_bytes', 0):,} B (mesh {comm.get('mesh')})"
        )
        for op, slot in sorted(acct.items()):
            if isinstance(slot, dict):
                add(
                    f"  - {op}: ×{slot.get('count')} — grad "
                    f"{slot.get('gradient_bytes', 0):,} B, act "
                    f"{slot.get('activation_bytes', 0):,} B"
                )
        if "reduce_scatter_smell" in comm:
            add(f"- **smell**: {comm['reduce_scatter_smell'].get('message')}")
    mem = report.get("memory")
    if mem is not None:
        add("")
        add("## Where did the bytes go")
        acct = mem.get("account")
        if acct is not None:
            add(
                f"- static account (model {acct.get('model')}, mesh "
                f"{acct.get('mesh')}): compiled peak "
                f"{int(acct.get('peak_bytes', 0)):,} B "
                f"({_fmt(acct.get('peak_gib'))} GiB) vs budget "
                f"{_fmt(acct.get('hbm_budget_gib'))} GiB — "
                + ("fits" if acct.get("fits_budget") else "**OVER BUDGET**")
                + f" (headroom {_fmt(acct.get('hbm_headroom_gib'))} GiB, "
                f"additivity gap {int(acct.get('additivity_gap_bytes', 0)):,} B)"
            )
            add("")
            add("| bucket | bytes | GiB | share of peak |")
            add("|---|---|---|---|")
            peak = max(1, int(acct.get("peak_bytes", 0)))
            for bucket, b in sorted(
                (acct.get("buckets_bytes") or {}).items(),
                key=lambda kv: -kv[1],
            ):
                add(
                    f"| {bucket} | {int(b):,} | {b / 1024**3:.3f} | "
                    f"{b / peak:.1%} |"
                )
            add("")
            for row in (acct.get("largest_buffers") or [])[:8]:
                add(
                    f"- {row.get('name')}: {int(row.get('bytes', 0)):,} B "
                    f"(shard {row.get('shard_shape')} {row.get('dtype')}"
                    + (
                        f", module {row['module']}"
                        if row.get("module")
                        else ""
                    )
                    + ")"
                )
        sa = mem.get("serve_account")
        if sa is not None:
            buckets = sa.get("buckets_bytes") or {}
            add(
                f"- serving account: params {int(buckets.get('params', 0)):,} B"
                f" + kv_cache {int(buckets.get('kv_cache', 0)):,} B = "
                f"{int(sa.get('peak_bytes', 0)):,} B vs budget "
                f"{_fmt(sa.get('hbm_budget_gib'))} GiB — "
                + ("fits" if sa.get("fits_budget") else "**OVER BUDGET**")
            )
        rt = mem.get("runtime")
        if rt is not None:
            add(
                f"- runtime ({rt.get('windows')} memory_window samples): "
                f"bytes in use ≤ {rt.get('max_bytes_in_use', 0):,} B, "
                f"process peak {rt.get('peak_bytes_in_use', 0):,} B, "
                f"largest per-window watermark delta "
                f"{rt.get('max_watermark_delta_bytes', 0):,} B"
            )
        elif mem.get("static_only"):
            reason = (mem.get("skips") or [None])[0]
            add(
                "- runtime: static-only"
                + (f" — {reason}" if reason else "")
            )
        for p, b in sorted((mem.get("postmortems") or {}).items()):
            add(
                f"- **OOM postmortem** p{p} at step {b.get('step')}: "
                f"{b.get('reason')} ({b.get('watermark_samples')} watermark "
                f"samples, account "
                + ("attached" if b.get("has_account") else "absent")
                + ")"
            )
    lg = report.get("loadgen")
    if lg is not None:
        add("")
        add("## Open-loop load sweep")
        knee = lg.get("knee_qps")
        add(
            f"- process={lg.get('process')} seed={lg.get('seed')} "
            f"slo={_fmt(lg.get('ttft_slo_ms'))}ms "
            f"requests/point={lg.get('requests_per_point')} — knee: "
            + (
                f"**{_fmt(knee)} QPS** (first saturated offered rate)"
                if knee is not None
                else "not reached on this grid"
            )
        )
        add("")
        add("| offered QPS | achieved | goodput | SLO attain | ttft p50 ms "
            "| p95 | p99 | qdelay p99 ms | growing | shed | unfinished |")
        add("|---" * 11 + "|")
        for pt in lg.get("points", []):
            add(
                f"| {_fmt(pt.get('offered_qps'))} | "
                f"{_fmt(pt.get('achieved_qps'))} | "
                f"{_fmt(pt.get('goodput_qps'))} | "
                f"{_fmt(pt.get('slo_attainment'))} | "
                f"{_fmt(pt.get('ttft_p50_ms'))} | "
                f"{_fmt(pt.get('ttft_p95_ms'))} | "
                f"{_fmt(pt.get('ttft_p99_ms'))} | "
                f"{_fmt(pt.get('queue_delay_p99_ms'))} | "
                f"{'yes' if pt.get('queue_growing') else ''} | "
                f"{_fmt(pt.get('shed'))} | {_fmt(pt.get('unfinished'))} |"
            )
    px = report.get("prefix")
    if px is not None:
        add("")
        add("## Prefix cache")
        add(
            f"- scope={px.get('scope')} engines={px.get('engines')} "
            f"budget={_fmt(px.get('budget_gib'))} GiB — hit rate: "
            f"**{_fmt(px.get('hit_rate'))}** "
            f"({_fmt(px.get('hits'))}/{_fmt(px.get('lookups'))} lookups)"
        )
        add(
            f"- prefill tokens saved: {_fmt(px.get('prefill_tokens_saved'))}"
            f"/{_fmt(px.get('prefill_tokens_total'))} "
            f"({_fmt(px.get('prefill_tokens_saved_frac'))} of all prefill) — "
            f"warm set {_fmt(px.get('pool_blocks_warm'))} blocks / "
            f"{_fmt(px.get('warm_bytes'))} bytes at last summary"
        )
    sp = report.get("spec")
    if sp is not None:
        add("")
        add("## Speculative decode")
        add(
            f"- scope={sp.get('scope')} engines={sp.get('engines')} "
            f"k={_fmt(sp.get('spec_tokens'))} "
            f"draft={_fmt(sp.get('draft_model'))} — accepted tokens per "
            f"step: **{_fmt(sp.get('accepted_tokens_per_step'))}** "
            "(plain decode = 1.0)"
        )
        add(
            f"- draft acceptance: {_fmt(sp.get('accepted_tokens'))}"
            f"/{_fmt(sp.get('drafted_tokens'))} proposals "
            f"(rate {_fmt(sp.get('acceptance_rate'))}) over "
            f"{_fmt(sp.get('spec_steps'))} verify rounds"
        )
    rec = report.get("recovery") or {}
    add("")
    add("## Recovery timeline")
    if rec.get("injections"):
        add(
            "- chaos injections: "
            + ", ".join(f"{i['kind']}@{i['step']}" for i in rec["injections"])
        )
    for a in rec.get("actions", []):
        if a.get("action") == "rewind":
            add(
                f"- **rewind** {a.get('rewind_index')}: anomaly "
                f"[{a.get('code')}] at step {a.get('step')} → restored step "
                f"{a.get('restored_step')} ({a.get('steps_lost')} steps lost, "
                f"{_fmt(a.get('recovery_wall_s'))} s)"
            )
        else:
            add(
                f"- **{a.get('action')}**: anomaly [{a.get('code')}] at step "
                f"{a.get('step')} — {a.get('reason', '')}"
            )
    for t in rec.get("topology", []):
        add(
            f"- **topology change** at step {t.get('step')} "
            f"(policy {t.get('policy')}): mesh was {t.get('old_mesh')} over "
            f"{t.get('old_processes')} process(es)"
            + (f" — {t['reason']}" if t.get("reason") else "")
        )
    for r in rec.get("reshards", []):
        add(
            f"- **reshard restore**: step {r.get('step')} re-laid "
            f"{r.get('old_mesh')}×{r.get('old_processes')}p → "
            f"{r.get('new_mesh')}×{r.get('new_processes')}p "
            f"(ef {r.get('ef_mode')}, {r.get('steps_lost', 0)} steps lost, "
            f"{_fmt(r.get('reshard_wall_s'))} s)"
        )
    for q in rec.get("quarantines", []):
        add(
            f"- quarantined batch (epoch {q.get('epoch')}, epoch_step "
            f"{q.get('epoch_step')}): {q.get('reason', '')}"
        )
    if rec.get("rewinds"):
        add(
            f"- {rec['rewinds']} rewind(s), {rec['steps_lost_total']} optimizer "
            f"steps lost, MTTR {_fmt(rec.get('mttr_s'))} s"
        )
    serving = rec.get("serving")
    if serving:
        for t in serving.get("replica_transitions", []):
            add(
                f"- **replica {t.get('replica')}** {t.get('from')} → "
                f"{t.get('to')} at tick {t.get('tick')}"
                + (f" [{t['cause']}]" if t.get("cause") else "")
                + f": {t.get('reason', '')}"
            )
        add(
            f"- serving tier: {serving.get('replicas_lost', 0)} replica(s) "
            f"lost, {serving.get('retries', 0)} request retr"
            f"{'y' if serving.get('retries', 0) == 1 else 'ies'}, "
            f"{serving.get('shed', 0)} shed "
            f"({serving.get('shed_by_reason') or {}}), request MTTR "
            f"{_fmt(serving.get('request_mttr_s'))} s, retry rate "
            f"{_fmt(serving.get('request_retry_rate'))}, goodput frac "
            f"{_fmt(serving.get('goodput_frac'))}"
        )
    for s in rec.get("host_loss_suspects", []):
        add(
            f"- **host_loss_suspect**: rank {s.get('rank')} named laggard "
            f"{s.get('consecutive_beats')} consecutive heartbeat(s) by "
            f"step {s.get('step')} (detection only — go look at that host)"
        )
    injected = [f for f in rec.get("faults", []) if f["injected"]]
    organic = rec.get("organic_faults", [])
    if not rec.get("faults"):
        add("- no faults observed")
    else:
        add(f"- faults: {len(injected)} injected, {len(organic)} organic")
        for f in organic:
            add(
                f"  - **organic** {f['kind']} at step {f['step']}: {f['detail']}"
            )
    add("")
    add(f"## Anomalies ({len(report['anomalies'])})")
    for a in report["anomalies"]:
        add(
            f"- step {a.get('step')} [{a.get('code')}] ranks {a.get('ranks')} "
            f"policy {a.get('policy')}: {a.get('detail', '')}"
        )
    for proc, rec in report["recorders"].items():
        add(
            f"- flight recorder p{proc}: reason {rec['reason']!r} at step "
            f"{rec['step']}, {rec['steps_recorded']} steps recorded"
        )
    return "\n".join(lines) + "\n"


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m distributed_llms_example_tpu.obs.report",
        description=__doc__,
    )
    p.add_argument("output_dir", help="a run's --output-dir (containing obs/)")
    p.add_argument("--json", action="store_true", help="emit the full report as JSON")
    p.add_argument("--last", type=int, default=20, help="timeline rows to render")
    p.add_argument(
        "--strict", action="store_true",
        help="nonzero exit on any schema-invalid line OR any ORGANIC fault "
             "(one no chaos_injection event explains) — a chaos run is "
             "green only when every fault it saw is one it caused — OR a "
             "wall-weighted dispatch_efficiency below "
             "--min-dispatch-efficiency",
    )
    p.add_argument(
        "--min-dispatch-efficiency", type=float, default=0.0,
        help="with --strict: fail when the run's wall-weighted "
             "dispatch_efficiency (step_budget events) falls below this "
             "floor (0 = no floor) — the trainer-loop-gap CI gate",
    )
    p.add_argument(
        "--min-overlap-frac", type=float, default=0.0,
        help="with --strict: fail when any rank's device_account shows "
             "collective device time with overlap_frac below this floor "
             "(0 = no floor), and fail when a profile was captured but NO "
             "device_account was emitted — a missing device measurement "
             "must never read as a pass",
    )
    p.add_argument(
        "--max-gradient-bytes-per-step", type=float, default=0.0,
        help="with --strict: fail when the startup gauges' collective "
             "byte account (obs_gauges.comm.gradient_bytes) exceeds this "
             "ceiling, or when NO obs_gauges record exists (0 = no "
             "ceiling) — the compression gate: a run that silently loses "
             "--grad-compression (flag ignored, partitioner folded the "
             "wire back to fp32) fails here instead of passing on "
             "wall-clock luck",
    )
    p.add_argument(
        "--max-request-retry-rate", type=float, default=-1.0,
        help="with --strict: fail when the serving router's "
             "request_retry_rate (router_summary) exceeds this ceiling, "
             "or when NO router_summary exists (-1 = the gate is off; 0 "
             "is a valid ceiling: any retry fails) — the serve-router "
             "retry-storm gate",
    )
    p.add_argument(
        "--min-serve-goodput-frac", type=float, default=0.0,
        help="with --strict: fail when the serving router's goodput_frac "
             "(requests completed within the TTFT SLO over requests "
             "submitted, router_summary) falls below this floor, or when "
             "NO router_summary exists (0 = the gate is off) — a missing "
             "serving measurement must never read as a pass",
    )
    p.add_argument(
        "--min-slo-attainment", type=float, default=0.0,
        help="with --strict: fail when the open-loop load sweep's BEST "
             "per-point slo_attainment (loadgen_point/loadgen_summary "
             "events) falls below this floor — if even the best offered "
             "rate cannot meet it, the deployment cannot — or when NO "
             "loadgen measurement exists (0 = the gate is off); a "
             "missing measurement must never read as a pass",
    )
    p.add_argument(
        "--max-p99-ttft-ms", type=float, default=0.0,
        help="with --strict: fail when the open-loop load sweep's lowest "
             "MEASURED per-point p99 TTFT (from arrival) exceeds this "
             "ceiling, or when no point measured one (nothing finished, "
             "or no loadgen run at all) (0 = the gate is off); a missing "
             "measurement must never read as a pass",
    )
    p.add_argument(
        "--min-prefix-hit-rate", type=float, default=0.0,
        help="with --strict: fail when the prefix cache's hit rate "
             "(prefix_hit_rate — the router aggregate when one exists, "
             "else the last prefix-enabled serve_summary) falls below "
             "this floor, or when NO prefix-enabled summary exists at "
             "all (0 = the gate is off); a run that silently loses "
             "--prefix-cache must fail here, never pass unmeasured",
    )
    p.add_argument(
        "--min-acceptance-rate", type=float, default=0.0,
        help="with --strict: fail when speculative decode's draft "
             "acceptance rate (acceptance_rate — the router aggregate "
             "when one exists, else the last spec-enabled serve_summary) "
             "falls below this floor, or when NO spec-enabled summary "
             "exists at all (0 = the gate is off); a run that silently "
             "loses --spec-tokens must fail here, never pass unmeasured",
    )
    p.add_argument(
        "--max-peak-hbm-frac", type=float, default=0.0,
        help="with --strict: fail when the measured HBM peak (the runtime "
             "memory_window peak where sampled, else the static account's "
             "compiled peak) exceeds this fraction of the account's "
             "--hbm-budget-gib ceiling, or when NO memory measurement "
             "exists at all (0 = the gate is off); a missing measurement "
             "must never read as a pass",
    )
    p.add_argument(
        "--min-hbm-headroom-gib", type=float, default=0.0,
        help="with --strict: fail when any memory account's "
             "hbm_headroom_gib (budget minus peak) falls below this floor, "
             "or when NO memory account exists (0 = the gate is off); a "
             "missing measurement must never read as a pass",
    )
    args = p.parse_args(argv)
    if not os.path.isdir(os.path.join(args.output_dir, "obs")):
        print(f"no obs/ directory under {args.output_dir}", file=sys.stderr)
        return 2
    report = build_report(args.output_dir)
    if args.json:
        print(json.dumps(report))
    else:
        print(render_markdown(report, last=args.last), end="")
    rc = 0
    if args.strict:
        if report["schema_errors"] or report["recovery"]["organic_faults"]:
            rc = 1
        floor = args.min_dispatch_efficiency
        budget = report.get("budget")
        if floor > 0:
            eff = budget["dispatch_efficiency"] if budget else None
            if eff is None:
                print(
                    "strict: --min-dispatch-efficiency set but no "
                    "step_budget records found", file=sys.stderr,
                )
                rc = 1
            elif eff < floor:
                print(
                    f"strict: dispatch_efficiency {eff} below the "
                    f"{floor} floor", file=sys.stderr,
                )
                rc = 1
        grad_ceiling = args.max_gradient_bytes_per_step
        if grad_ceiling > 0:
            comm = report.get("comm")
            worst = None
            if comm is not None and isinstance(comm.get("comm"), dict):
                worst = float(comm["comm"].get("gradient_bytes", 0))
            if worst is None:
                print(
                    "strict: --max-gradient-bytes-per-step set but no "
                    "obs_gauges byte account found (run with --obs-gauges "
                    "on) — a missing measurement must never read as a pass",
                    file=sys.stderr,
                )
                rc = 1
            elif worst > grad_ceiling:
                print(
                    f"strict: gradient_bytes per step {worst:.0f} exceeds "
                    f"the {grad_ceiling:.0f} ceiling — compression lost or "
                    "never engaged (check grad_compression in the "
                    "obs_gauges record)",
                    file=sys.stderr,
                )
                rc = 1
        serving = report["recovery"].get("serving")
        if args.max_request_retry_rate >= 0:
            rate = (serving or {}).get("request_retry_rate")
            if rate is None:
                print(
                    "strict: --max-request-retry-rate set but no "
                    "router_summary record found (serve-router run "
                    "required) — a missing measurement must never read "
                    "as a pass", file=sys.stderr,
                )
                rc = 1
            elif rate > args.max_request_retry_rate:
                print(
                    f"strict: request_retry_rate {rate} exceeds the "
                    f"{args.max_request_retry_rate} ceiling — the pool is "
                    "retry-storming (dying replicas or a too-tight "
                    "deadline/backoff config)", file=sys.stderr,
                )
                rc = 1
        if args.min_serve_goodput_frac > 0:
            frac = (serving or {}).get("goodput_frac")
            if frac is None:
                print(
                    "strict: --min-serve-goodput-frac set but no "
                    "router_summary record found (serve-router run "
                    "required) — a missing measurement must never read "
                    "as a pass", file=sys.stderr,
                )
                rc = 1
            elif frac < args.min_serve_goodput_frac:
                print(
                    f"strict: goodput_frac {frac} below the "
                    f"{args.min_serve_goodput_frac} floor — requests are "
                    "being shed or missing the TTFT SLO", file=sys.stderr,
                )
                rc = 1
        lg = report.get("loadgen")
        if args.min_slo_attainment > 0:
            best = (lg or {}).get("best_slo_attainment")
            if best is None:
                print(
                    "strict: --min-slo-attainment set but no loadgen "
                    "measurement found (run the open-loop load sweep — "
                    "serving/loadgen.py) — a missing measurement must "
                    "never read as a pass", file=sys.stderr,
                )
                rc = 1
            elif best < args.min_slo_attainment:
                print(
                    f"strict: best per-point slo_attainment {best} below "
                    f"the {args.min_slo_attainment} floor — no offered "
                    "rate on the sweep grid meets the SLO",
                    file=sys.stderr,
                )
                rc = 1
        if args.max_p99_ttft_ms > 0:
            best = (lg or {}).get("best_ttft_p99_ms")
            if best is None:
                print(
                    "strict: --max-p99-ttft-ms set but no measured p99 "
                    "TTFT found (no loadgen run, or nothing finished at "
                    "any offered rate) — a missing measurement must "
                    "never read as a pass", file=sys.stderr,
                )
                rc = 1
            elif best > args.max_p99_ttft_ms:
                print(
                    f"strict: best per-point p99 TTFT {best} ms exceeds "
                    f"the {args.max_p99_ttft_ms} ms ceiling at every "
                    "offered rate on the sweep grid", file=sys.stderr,
                )
                rc = 1
        if args.min_prefix_hit_rate > 0:
            rate = (report.get("prefix") or {}).get("hit_rate")
            if rate is None:
                print(
                    "strict: --min-prefix-hit-rate set but no "
                    "prefix-enabled serve_summary found (run with "
                    "--prefix-cache on a paged engine) — a missing "
                    "measurement must never read as a pass",
                    file=sys.stderr,
                )
                rc = 1
            elif rate < args.min_prefix_hit_rate:
                print(
                    f"strict: prefix_hit_rate {rate} below the "
                    f"{args.min_prefix_hit_rate} floor — the workload is "
                    "not sharing prefixes, the warm budget is too small, "
                    "or custom attention masks made requests ineligible",
                    file=sys.stderr,
                )
                rc = 1
        if args.min_acceptance_rate > 0:
            rate = (report.get("spec") or {}).get("acceptance_rate")
            if rate is None:
                print(
                    "strict: --min-acceptance-rate set but no "
                    "spec-enabled serve_summary found (run with "
                    "--spec-tokens > 0) — a missing measurement must "
                    "never read as a pass",
                    file=sys.stderr,
                )
                rc = 1
            elif rate < args.min_acceptance_rate:
                print(
                    f"strict: acceptance_rate {rate} below the "
                    f"{args.min_acceptance_rate} floor — the drafter is "
                    "mispredicting this workload (try a draft model, "
                    "fewer --spec-tokens, or a more repetitive mix)",
                    file=sys.stderr,
                )
                rc = 1
        mem = report.get("memory")
        if args.max_peak_hbm_frac > 0:
            frac = (mem or {}).get("peak_frac_of_budget")
            if frac is None:
                print(
                    "strict: --max-peak-hbm-frac set but no memory "
                    "measurement found (no memory_window samples and no "
                    "memory_account — run with --obs jsonl so the startup "
                    "gauges emit the static account) — a missing "
                    "measurement must never read as a pass", file=sys.stderr,
                )
                rc = 1
            elif frac > args.max_peak_hbm_frac:
                src = (mem or {}).get("measured_peak_source")
                print(
                    f"strict: HBM peak at {frac} of the budget "
                    f"(source: {src}) exceeds the {args.max_peak_hbm_frac} "
                    "ceiling — where the bytes went is in the report's "
                    "memory section", file=sys.stderr,
                )
                rc = 1
        if args.min_hbm_headroom_gib > 0:
            headroom = (mem or {}).get("min_headroom_gib")
            if headroom is None:
                print(
                    "strict: --min-hbm-headroom-gib set but no memory "
                    "account found (run with --obs jsonl so the startup "
                    "gauges emit the static account) — a missing "
                    "measurement must never read as a pass", file=sys.stderr,
                )
                rc = 1
            elif headroom < args.min_hbm_headroom_gib:
                print(
                    f"strict: hbm_headroom_gib {headroom} below the "
                    f"{args.min_hbm_headroom_gib} GiB floor — the config "
                    "is one allocation spike from an OOM", file=sys.stderr,
                )
                rc = 1
        ov_floor = args.min_overlap_frac
        if ov_floor > 0:
            device = report.get("device")
            if device is None or not device["ranks"]:
                # a capture with no parsed account is a broken pipeline;
                # no capture at all is a missing measurement — both fail
                # a gate that was explicitly asked to look at overlap
                print(
                    "strict: --min-overlap-frac set but no device_account "
                    "records found"
                    + (
                        f" ({len(device['captures'])} profile capture(s) "
                        "landed without one)"
                        if device is not None
                        else ""
                    ),
                    file=sys.stderr,
                )
                rc = 1
            else:
                for rank, acct in sorted(device["ranks"].items()):
                    frac = (acct.get("overlap") or {}).get("overlap_frac")
                    if frac is not None and frac < ov_floor:
                        print(
                            f"strict: rank {rank} overlap_frac {frac} below "
                            f"the {ov_floor} floor (exposed collective time)",
                            file=sys.stderr,
                        )
                        rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
