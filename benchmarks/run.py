#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip.  It finds the cell in BENCHMARK.json, its
configuration and traffic files by name, the traffic's driver, and (traced
runs) one reader per per-layer metric; it never branches on a cell's or a
configuration's name.  The last line of stdout is the result object; every
other number (sample counts, percentiles, the pieces of set-up, each number
compared beside its limit) is on earlier lines.

``--rehearse`` runs the same control flow on the CPU at the toy sizes the
configuration and traffic files name under ``rehearsal``; its last line says
``"correct": false, "rehearsal": true`` and it exits 1: it can never pass.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)


def emit(record: dict) -> None:
    sys.stdout.write(json.dumps(record) + "\n")
    sys.stdout.flush()


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="CPU, toy sizes, same control flow; never prints a passing line")
    p.add_argument("--control", action="store_true",
                   help="also read the control: the reference one precision down, in the program's place")
    args = p.parse_args(argv)

    from benchmarks.harness import spec as spec_mod

    bench = spec_mod.load_benchmark()
    cell = spec_mod.Cell(bench, args.workload)
    if args.rehearse:
        cell.rehearse()

    os.makedirs(spec_mod.CACHE_DIR, exist_ok=True)
    import jax

    # One fixed directory inside the checkout, whatever the environment says:
    # the path is part of the cache key, and the chip machine's own directory
    # is a ~110 MiB LRU that cannot hold one step executable (PERF.md).
    cache_dir = os.path.join(spec_mod.CACHE_DIR, "jax")
    if args.rehearse:  # a CPU rehearsal proves nothing about the cache, and its reloads only log noise
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from benchmarks.harness import peaks as peaks_mod
    from benchmarks.harness import program as program_mod
    from benchmarks.harness.runtime import Run

    device = program_mod.device_facts(cell.chips)
    if args.rehearse:
        peaks = None
    else:
        if device["platform"] != "tpu" or device["count"] < cell.chips:
            sys.stderr.write(
                f"cell {cell.name} needs {cell.chips} TPU chip(s); jax found "
                f"{device['count']} x {device['platform']}\n")
            return 2
        peaks = peaks_mod.peaks_for(device["kind"])
    emit({"phase": "start", "workload": cell.name, "seed": args.seed, "seconds": args.seconds,
          "trace": args.trace, "device": device, "compile_cache": program_mod.cache_dir_stats(cache_dir),
          "imports_s": time.perf_counter() - T_START})

    run = Run(cell=cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
              rehearse=args.rehearse, t_start=T_START, peaks=peaks, emit=emit, control=args.control)
    driver = spec_mod.load_module("drivers", cell.driver)
    try:
        result = driver.run(run)
    except BaseException:  # noqa: BLE001 — reported, then the process exits non-zero with no result line
        traceback.print_exc(file=sys.stderr)
        sys.stderr.flush()
        return 1

    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in cell.reported(section):
        if args.trace:
            value = spec_mod.load_module("layer_metrics", m["name"]).read(result["layers"])
        else:
            value = result["end_to_end"].get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    emit({"phase": "end", "compile_cache": program_mod.cache_dir_stats(cache_dir),
          "compiles_in_window": result["compiles_in_window"],
          "reference_s": result.get("reference_s"), "wall_s": time.perf_counter() - T_START})

    dev = {"platform": device["platform"], "kind": device["kind"], "count": cell.chips,
           "memory_peak_bytes": result["memory_peak_bytes"]}
    line = {
        "correct": bool(result["correct"]) and result["compiles_in_window"] == 0 and not args.rehearse,
        "attempted": int(result["attempted"]), "failed": int(result["failed"]),
        "metrics": metrics, "device": dev,
    }
    if args.trace and result["layers"].get("trace") is not None:
        tr = result["layers"]["trace"]
        dev["busy_s"], dev["window_s"] = tr["busy_s"], tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
    if args.rehearse:
        line["rehearsal"] = True
    emit(line)
    return 1 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
