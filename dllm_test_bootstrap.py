"""Early pytest bootstrap (loaded via ``-p dllm_test_bootstrap`` in addopts).

Tests need JAX on an 8-device virtual CPU mesh, which requires
``JAX_PLATFORMS=cpu`` and ``--xla_force_host_platform_device_count=8`` to be
set before the interpreter initializes JAX — whatever environment pytest
was started from — so pytest re-execs itself once with a corrected
environment.  This module is imported during pytest's pre-parse phase,
before output capture starts, so the re-exec'ed process keeps the original
stdout/stderr.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _dllm_env import cpu_mesh_env  # noqa: E402

if os.environ.get("_DLLM_TPU_TEST_REEXEC") != "1":
    env = cpu_mesh_env(os.environ, n_devices=8)
    env["_DLLM_TPU_TEST_REEXEC"] = "1"
    os.execve(sys.executable, [sys.executable, "-m", "pytest", *sys.argv[1:]], env)
