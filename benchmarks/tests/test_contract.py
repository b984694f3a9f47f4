"""BENCHMARK.json against the contract's mechanical rules, the files it names,
the result line's shape in a rehearsal of every cell, and the promise that a
new cell and metric are files plus entries, with no edit to what is there."""

import json
import os
import re
import shutil

import pytest

from conftest import ROOT, run_cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_keys_names_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", [])) <= cells
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["source"] in SOURCES
        assert m["moves"] in e2e and set(m.get("workloads", [])) <= cells
        for w in m.get("workloads", []):  # each listed cell reports the metric it moves
            assert "workloads" not in e2e[m["moves"]] or w in e2e[m["moves"]]["workloads"]
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    assert len({(w["config"], w["traffic"]) for w in bench["workloads"]}) == len(bench["workloads"])
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(1, len(bench["workloads"]) // 4)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"} and c["name"] in used
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"])) and len(c["reduced"]) <= 16
        assert len(c["why"]) <= 200
    assert len(json.dumps(bench)) < 64 * 1024


def test_every_named_file_is_there(bench):
    bdir = os.path.join(ROOT, "benchmarks")
    for c in bench["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["source"] == c["source"] and sorted(cfg["reduced"]) == sorted(c["reduced"])
        for kind in ("reference", "adapters", "flops"):
            assert os.path.exists(os.path.join(bdir, kind, cfg["family"] + ".py"))
        assert os.path.exists(os.path.join(bdir, "configs", cfg["rehearsal"]["config"] + ".json"))
    for w in bench["workloads"]:
        traffic = json.load(open(os.path.join(bdir, "traffic", w["traffic"] + ".json")))
        assert os.path.exists(os.path.join(bdir, "drivers", traffic["driver"] + ".py"))
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(bdir, "layer_metrics", m["name"] + ".py")), m["name"]


def test_a_serve_cells_rate_is_its_share_of_its_swept_knee(bench):
    """One rule for every cell that offers load at a fixed rate: ``rate_rps`` = ``knee_share`` x ``knee_rps`` to
    one decimal, the share at most four fifths, and ``knee_from`` says which sweep the knee is from."""
    bdir = os.path.join(ROOT, "benchmarks", "traffic")
    files = sorted({w["traffic"] for w in bench["workloads"]})
    paced = {t: x for t in files for x in [json.load(open(os.path.join(bdir, t + ".json")))] if "rate_rps" in x}
    assert len(paced) >= 4
    for name, traffic in paced.items():
        assert 0 < traffic["knee_share"] <= 0.8, name
        assert traffic["rate_rps"] == round(traffic["knee_share"] * traffic["knee_rps"], 1), name
        assert traffic["knee_from"], name


def _check_line(bench, cell, line, trace):
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is False and line["rehearsal"] is True  # a rehearsal can never pass
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    section = "per_layer" if trace else "end_to_end"
    allowed = {m["name"]: m["unit"] for m in bench[section] if cell in m.get("workloads", [cell])}
    assert line["metrics"], "no metric reported"
    for name, m in line["metrics"].items():
        assert name in allowed and m["unit"] == allowed[name] and isinstance(m["value"], float)
    if not trace:
        assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
        assert set(line["metrics"]) == set(allowed)


@pytest.mark.parametrize("trace", [0, 1])
def test_every_cell_end_to_end_in_rehearsal(bench, trace):
    for w in bench["workloads"]:
        rc, lines, err = run_cell(["--workload", w["name"], "--seed", str(2**31 + 7), "--seconds", "2",
                                   "--trace", str(trace), "--rehearse"])
        assert rc == 1, err[-2000:]
        _check_line(bench, w["name"], lines[-1], trace)
        end = [x for x in lines if x.get("phase") == "end"][-1]
        assert end["compiles_in_window"] == 0
        checks = [x for x in lines if "check" in x]
        assert checks and all(x["ok"] for x in checks), checks  # the toy limits hold on the sound program


def test_no_chip_no_passing_line(bench):
    rc, lines, err = run_cell(["--workload", bench["workloads"][0]["name"], "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert rc != 0 and not any("correct" in x for x in lines)


def test_a_new_cell_and_metric_are_files_and_entries_only(tmp_path, bench):
    """Copy the benchmark, drop in a configuration, a traffic mix and a
    per-layer metric as NEW files, add their entries, run the new cell."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    os.symlink(os.path.join(ROOT, "distributed_llms_example_tpu"), os.path.join(root, "distributed_llms_example_tpu"))
    before = {}
    for d, _, files in os.walk(os.path.join(root, "benchmarks")):
        for f in files:
            before[os.path.join(d, f)] = open(os.path.join(d, f), "rb").read()
    bdir = os.path.join(root, "benchmarks")
    cfg = json.load(open(os.path.join(bdir, "configs", "t5-large.json")))
    cfg.update({"name": "t5-large-again", "source": "https://example.org/another"})
    json.dump(cfg, open(os.path.join(bdir, "configs", "t5-large-again.json"), "w"))
    traffic = json.load(open(os.path.join(bdir, "traffic", "summarize-train-b16.json")))
    traffic["rehearsal"]["steps_per_pass"] = 4
    json.dump(traffic, open(os.path.join(bdir, "traffic", "summarize-train-short.json"), "w"))
    with open(os.path.join(bdir, "layer_metrics", "train_steps_counted.py"), "w") as f:
        f.write("def read(ctx):\n    return float(sum(a['window_steps'] for a in ctx['accounts']))\n")
    new = json.loads(json.dumps(bench))
    new["configs"].append({"name": "t5-large-again", "source": cfg["source"], "reduced": cfg["reduced"],
                           "file": "benchmarks/configs/t5-large-again.json", "why": "drop-in test"})
    new["workloads"].append({"name": "t5-large-again.train-short", "config": "t5-large-again",
                             "traffic": "summarize-train-short", "chips": 1, "why": "drop-in test"})
    for m in new["end_to_end"]:
        if m["name"] == "train_tokens_per_s_chip":
            m["workloads"].append("t5-large-again.train-short")
    new["per_layer"].append({"name": "train_steps_counted", "unit": "steps", "better": "higher",
                             "source": "program_counter", "layer": "trainer loop", "moves": "train_tokens_per_s_chip",
                             "workloads": ["t5-large-again.train-short"]})
    json.dump(new, open(os.path.join(root, "BENCHMARK.json"), "w"))
    rc, lines, err = run_cell(["--workload", "t5-large-again.train-short", "--seed", "5", "--seconds", "1",
                               "--trace", "1", "--rehearse"], root=root)
    assert rc == 1, err[-2000:]
    assert lines[-1]["metrics"]["train_steps_counted"]["value"] > 0
    for path, content in before.items():  # nothing that was there was touched
        assert open(path, "rb").read() == content, path
