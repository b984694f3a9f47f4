"""Shared environment fixup for CPU-mesh child processes.

A process that wants a virtual CPU mesh needs ``JAX_PLATFORMS=cpu`` and
``--xla_force_host_platform_device_count=N`` in its environment before the
interpreter initializes JAX.  Every entry point that spawns (or re-execs
into) such a process applies the same fixup — keep the logic in exactly
one place.

Used by ``dllm_test_bootstrap.py`` (pytest re-exec) and
``__graft_entry__.py`` (driver dryrun subprocess).
"""

from __future__ import annotations


def cpu_mesh_env(env: dict, n_devices: int = 8) -> dict:
    """A copy of ``env`` corrected for an n-device virtual CPU mesh."""
    env = dict(env)
    env["JAX_PLATFORMS"] = "cpu"
    flag = f"--xla_force_host_platform_device_count={n_devices}"
    keep = [
        f
        for f in env.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count")
    ]
    env["XLA_FLAGS"] = " ".join([*keep, flag])
    return env
