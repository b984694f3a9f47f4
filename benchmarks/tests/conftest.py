"""The benchmark's own tests: CPU, toy sizes, not part of the repo's tier-1
suite (run them with ``python -m pytest benchmarks/tests -q`` from the root)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def run_cell(args: list[str], root: str = ROOT, timeout: int = 900) -> tuple[int, list[dict], str]:
    """Run ``benchmarks/run.py`` of ``root`` in a child on the CPU; returns
    (exit code, the JSON lines of its stdout, stderr)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, os.path.join(root, "benchmarks", "run.py"), *args],
                          capture_output=True, text=True, env=env, cwd=root, timeout=timeout)
    lines = []
    for x in proc.stdout.splitlines():
        if x.startswith("{"):
            try:
                lines.append(json.loads(x))
            except ValueError:
                pass
    return proc.returncode, lines, proc.stderr


@pytest.fixture(scope="session")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
