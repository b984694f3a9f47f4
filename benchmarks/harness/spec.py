"""BENCHMARK.json and the files it names, found by name and never by `if`.

A cell (one entry of ``workloads``) names a configuration and a traffic mix.
The configuration is ``configs/<name>.json`` (the file the entry gives), the
traffic mix is ``traffic/<traffic>.json``; the traffic file names its driver
(``drivers/<driver>.py``), the configuration its family, which selects
``reference/<family>.py``, ``adapters/<family>.py`` and ``flops/<family>.py``.
A per-layer metric ``m`` is read by ``layer_metrics/<m>.py``.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(BENCH_DIR, ".cache")  # compile cache, traces: git-ignored


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def load_module(kind: str, name: str):
    """Import ``benchmarks/<kind>/<name>.py`` by path (names may hold dots)."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} module for {name!r}: {path}")
    mod_name = "benchmarks_%s_%s" % (kind, "".join(c if c.isalnum() else "_" for c in name))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of BENCHMARK.json with its configuration and traffic."""

    def __init__(self, bench: dict, name: str):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"unknown workload {name!r}; have {sorted(cells)}")
        self.bench = bench
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in bench["configs"]}[self.entry["config"]]
        self.config = load_json(os.path.join(ROOT, cfg_entry["file"]))
        self.traffic = load_json(os.path.join(BENCH_DIR, "traffic", self.entry["traffic"] + ".json"))
        self.family = self.config["family"]
        self.driver = self.traffic["driver"]

    def rehearse(self) -> None:
        """Swap in the toy twins the files name under ``rehearsal`` (a CPU
        rehearsal; the configuration may name a larger twin for one driver)."""
        toy = self.config["rehearsal"]
        self.config = load_json(os.path.join(BENCH_DIR, "configs", toy.get(self.driver, toy["config"]) + ".json"))
        self.traffic = {**self.traffic, **self.traffic["rehearsal"]}

    def recipe(self, key: str, default: Any = None) -> Any:
        """A recipe value: the configuration's own ``recipe`` overrides the
        traffic file's (how THIS model fits the mix, e.g. microbatching)."""
        own = self.config.get("recipe", {}).get(self.driver, {})
        if key in own:
            return own[key]
        return self.traffic.get(key, default)

    def reported(self, section: str) -> list[dict]:
        """The metrics of ``end_to_end`` / ``per_layer`` this cell reports."""
        e2e_here = {
            m["name"] for m in self.bench["end_to_end"]
            if "workloads" not in m or self.name in m["workloads"]
        }
        out = []
        for m in self.bench[section]:
            if "workloads" in m:
                if self.name in m["workloads"]:
                    out.append(m)
            elif section == "end_to_end" or m["moves"] in e2e_here:
                out.append(m)
        return out
