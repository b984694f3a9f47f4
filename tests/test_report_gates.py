"""The CI gates are one command: ``python -m
distributed_llms_example_tpu.obs.report <dir> --strict --<gate> X``.

One case per gate flag.  Each reads three hand-written JSONL runs: one
that meets the floor (or stays under the ceiling), one that misses it,
and one that holds NO such measurement — which must fail too: a gate
that was asked to look at a number and found none has not passed.
"""

from __future__ import annotations

import json
import os

import pytest

from distributed_llms_example_tpu.obs.report import main as report_main


def _run(root, name: str, records: list[dict]) -> str:
    obs_dir = os.path.join(str(root), name, "obs")
    os.makedirs(obs_dir)
    with open(os.path.join(obs_dir, "metrics-p000.jsonl"), "w") as f:
        for r in records:
            f.write(json.dumps({"schema_version": 1, **r}) + "\n")
    return os.path.join(str(root), name)


def _budget(eff: float) -> dict:
    return {
        "event": "step_budget", "step": 2, "window_steps": 2,
        "wall_ms": 1000.0, "data_wait_ms": 1000.0 * (1 - eff),
        "dispatch_ms": 0.0, "device_busy_ms": 1000.0 * eff,
        "sync_block_ms": 0.0, "host_overhead_ms": 0.0,
        "unattributed_ms": 0.0, "dispatch_efficiency": eff,
    }


def _gauges(gradient_bytes: int) -> dict:
    return {
        "event": "obs_gauges", "mesh": {"data": 8}, "flops_per_step": 1.0,
        "comm": {
            "all-reduce": {"count": 1, "gradient_bytes": gradient_bytes,
                           "activation_bytes": 0},
            "total_bytes": gradient_bytes, "gradient_bytes": gradient_bytes,
            "activation_bytes": 0,
        },
    }


def _device(overlap_frac: float) -> dict:
    return {
        "event": "device_account", "window": [2, 3], "window_steps": 2,
        "busy_ms": 10.0, "buckets_ms": {"attn": 10.0}, "collectives": {},
        "overlap": {"overlap_frac": overlap_frac, "collective_ms": 4.0},
    }


def _router(**fields) -> dict:
    return {"event": "router_summary", "requests": 10, "completed": 10, **fields}


def _sweep_point(attainment: float, p99_ms: float | None) -> dict:
    return {
        "event": "loadgen_point", "process": "poisson", "seed": 0,
        "offered_qps": 2.0, "slo_attainment": attainment,
        "ttft_p99_ms": p99_ms,
    }


def _serve_summary(**fields) -> dict:
    return {"event": "serve_summary", "requests": 4, "tokens": 32, **fields}


def _memory(peak_gib: float, budget_gib: float = 16.0) -> dict:
    gib = 1 << 30
    return {
        "event": "memory_account", "peak_bytes": int(peak_gib * gib),
        "hbm_budget_bytes": int(budget_gib * gib),
        "hbm_headroom_gib": budget_gib - peak_gib,
    }


# a run that trained and logged, and measured none of the gated numbers
_NO_MEASUREMENT = [{"step": 1, "loss": 1.0}]

# flag, gate value, the run that meets it, the run that misses it
GATES = [
    ("--min-dispatch-efficiency", "0.9", [_budget(0.97)], [_budget(0.5)]),
    ("--max-gradient-bytes-per-step", "2000", [_gauges(1000)], [_gauges(4000)]),
    ("--min-overlap-frac", "0.6", [_device(0.8)], [_device(0.3)]),
    ("--max-request-retry-rate", "0.1",
     [_router(request_retry_rate=0.0)], [_router(request_retry_rate=0.4)]),
    ("--min-serve-goodput-frac", "0.9",
     [_router(goodput_frac=1.0)], [_router(goodput_frac=0.5)]),
    ("--min-slo-attainment", "0.9",
     [_sweep_point(0.95, 120.0)], [_sweep_point(0.4, 120.0)]),
    ("--max-p99-ttft-ms", "500",
     [_sweep_point(0.95, 120.0)], [_sweep_point(0.95, 900.0)]),
    ("--min-prefix-hit-rate", "0.5",
     [_serve_summary(prefix_cache=True, prefix_hit_rate=0.75)],
     [_serve_summary(prefix_cache=True, prefix_hit_rate=0.25)]),
    ("--min-acceptance-rate", "0.5",
     [_serve_summary(spec_decode=True, acceptance_rate=0.75)],
     [_serve_summary(spec_decode=True, acceptance_rate=0.25)]),
    ("--max-peak-hbm-frac", "0.8", [_memory(8.0)], [_memory(15.0)]),
    ("--min-hbm-headroom-gib", "2.0", [_memory(8.0)], [_memory(15.0)]),
]


@pytest.mark.parametrize(
    "flag, value, meets, misses", GATES, ids=[g[0] for g in GATES]
)
def test_strict_gate(tmp_path, capsys, flag, value, meets, misses):
    runs = {
        "meets": _run(tmp_path, "meets", meets),
        "misses": _run(tmp_path, "misses", misses),
        "empty": _run(tmp_path, "empty", _NO_MEASUREMENT),
    }

    def gate(name: str, *extra: str) -> tuple[int, str]:
        rc = report_main([runs[name], "--strict", "--json", *extra])
        return rc, capsys.readouterr().err

    assert gate("meets", flag, value) == (0, "")
    rc, err = gate("misses", flag, value)
    assert rc == 1 and "strict:" in err
    rc, err = gate("empty", flag, value)
    assert rc == 1 and flag in err, "a missing measurement read as a pass"
    # the gate is the flag's doing: without it all three runs are green
    for name in runs:
        assert gate(name) == (0, "")
    # and --strict is what arms it
    assert report_main([runs["misses"], "--json", flag, value]) == 0
    capsys.readouterr()
