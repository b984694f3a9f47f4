"""Device mesh construction and multi-host bootstrap.

Replaces the reference's process-group plumbing with the TPU-native pair:

- ``jax.distributed.initialize(coordinator, num_processes, process_id)``
  consumes exactly the three rendezvous facts the reference pulls from the
  Valohai platform — master IP, world size, and rank
  (reference train-task.py:420-425, ``tcp://{primary_local_ip}:1234``) —
  but instead of a NCCL process group (train-task.py:405) it bootstraps the
  XLA runtime, after which all communication is compiler-inserted
  collectives over ICI/DCN.

- ``jax.sharding.Mesh`` over named axes ("stage", "data", "fsdp",
  "expert", "sequence", "tensor") — pipeline, data, ZeRO-3, MoE expert,
  ring-attention context, and tensor parallelism respectively — is the
  single object that expresses every parallelism strategy; the reference
  needed three different mechanisms (torchrun env vars, Accelerate,
  hand-rolled all_reduce) for data parallelism alone.
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh

# AXES lives in core/config.py (the canonical home — importable without
# jax, which is what the CLI parser and the sharding lint need); it is
# re-exported here because the device-mesh constructor is its main user.
from distributed_llms_example_tpu.core.config import AXES, MeshConfig

logger = logging.getLogger(__name__)

DEFAULT_COORDINATOR_PORT = 1234  # parity with reference train-task.py:420


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Resolved (all positive) mesh axis sizes."""

    data: int
    fsdp: int
    sequence: int
    tensor: int
    stage: int = 1
    expert: int = 1

    @property
    def size(self) -> int:
        return self.stage * self.data * self.fsdp * self.expert * self.sequence * self.tensor

    @property
    def batch_shards(self) -> int:
        """Number of ways the global batch is split (data × fsdp × expert)."""
        return self.data * self.fsdp * self.expert

    def as_tuple(self) -> tuple[int, int, int, int, int, int]:
        """Axis sizes in mesh-axis order (AXES)."""
        return (self.stage, self.data, self.fsdp, self.expert, self.sequence, self.tensor)


def resolve_mesh_shape(cfg: MeshConfig, n_devices: int) -> MeshSpec:
    """Resolve -1 axes and validate the product against the device count."""
    sizes = cfg.axis_sizes()
    bad = {k: v for k, v in sizes.items() if v == 0 or v < -1}
    if bad:
        raise ValueError(f"mesh axis sizes must be positive or -1, got {bad}")
    wild = [k for k, v in sizes.items() if v == -1]
    if len(wild) > 1:
        raise ValueError(f"at most one mesh axis may be -1, got {wild}")
    fixed = int(np.prod([v for v in sizes.values() if v != -1]))
    if wild:
        if n_devices % fixed != 0:
            raise ValueError(f"{n_devices} devices not divisible by fixed axes product {fixed}")
        sizes[wild[0]] = n_devices // fixed
    total = int(np.prod(list(sizes.values())))
    if total != n_devices:
        raise ValueError(f"mesh {sizes} has size {total}, but {n_devices} devices are available")
    return MeshSpec(**sizes)


def build_mesh(cfg: MeshConfig | MeshSpec | None = None, devices: Sequence[jax.Device] | None = None) -> Mesh:
    """Build the global device mesh.

    On TPU ``jax.experimental.mesh_utils.create_device_mesh`` lays the axes
    onto the physical ICI topology (tensor innermost).
    """
    devices = list(devices if devices is not None else jax.devices())
    if cfg is None:
        cfg = MeshConfig()
    spec = cfg if isinstance(cfg, MeshSpec) else resolve_mesh_shape(cfg, len(devices))
    shape = spec.as_tuple()
    if devices[0].platform == "tpu":
        # a failure here propagates: enumeration order is not ICI order,
        # and a mesh laid out against the torus still runs — only slower
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    else:  # CPU test meshes have no topology to honor
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, AXES)


def initialize_distributed(
    coordinator_address: str = "",
    num_processes: int = 0,
    process_id: int = -1,
) -> None:
    """Multi-host bootstrap from the Valohai rendezvous triple.

    Mirrors reference train-task.py:404-430: the master's primary local IP,
    the required execution count (world size), and this member's rank are
    taken — in priority order — from explicit arguments, from the
    ``valohai.distributed`` platform config if importable, or from
    environment variables (``VH_MASTER_IP`` / ``VH_WORLD_SIZE`` /
    ``VH_RANK``, falling back to torchrun-style ``MASTER_ADDR`` /
    ``WORLD_SIZE`` / ``RANK`` for drop-in compatibility).  Single-process
    runs (no facts found, or world size 1) skip initialization entirely —
    the local-run fallback the reference only has for run identification
    (helpers.py:37-39) applied to distribution itself.
    """
    if not coordinator_address or num_processes <= 0 or process_id < 0:
        ip, world, rank = _valohai_facts()
        coordinator_address = coordinator_address or ip
        num_processes = num_processes if num_processes > 0 else world
        process_id = process_id if process_id >= 0 else (rank if rank is not None else -1)
    if num_processes <= 1:
        logger.info("single-process run; skipping jax.distributed.initialize")
        return
    # A multi-process run with unresolvable rendezvous facts must fail loudly:
    # silently skipping would degrade to N independent single-host trainings
    # with no gradient sync (wrong model, no error).
    if not coordinator_address:
        raise ValueError(
            f"num_processes={num_processes} but no coordinator address found "
            "(pass --coordinator-address, or set VH_MASTER_IP/MASTER_ADDR)"
        )
    if process_id < 0:
        raise ValueError(
            f"num_processes={num_processes} but no process id found "
            "(pass --process-id, or set VH_RANK/RANK)"
        )
    if ":" not in coordinator_address:
        coordinator_address = f"{coordinator_address}:{DEFAULT_COORDINATOR_PORT}"
    # The CPU client defaults to NO cross-process collectives backend
    # (jax_cpu_collectives_implementation="none") and then every
    # multi-process computation — put_batch's global arrays, the
    # preemption/heartbeat allgathers — dies with "Multiprocess
    # computations aren't implemented on the CPU backend".  Gloo over TCP
    # is jax's supported CPU answer; the flag only affects CPU client
    # construction (TPU/GPU ignore it), so set it before initialize.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    logger.info(
        "jax.distributed initialized: coordinator=%s process=%d/%d local_devices=%d",
        coordinator_address,
        process_id,
        num_processes,
        jax.local_device_count(),
    )


def elastic_mesh_spec(cfg: MeshConfig, n_devices: int) -> MeshSpec:
    """Resolve a mesh shape for a CHANGED device count (topology-change
    recovery, ISSUE 14): the configured factorization re-resolved against
    the surviving slice.

    A ``-1`` axis absorbs the change exactly as at startup.  A fully
    pinned factorization whose product no longer matches re-scales the
    DATA axis (the replica dimension is the one elasticity semantically
    varies — model sharding axes keep their meaning); when the remaining
    axes' product does not divide the device count there is no
    well-typed shrink and this raises with both factorizations named."""
    sizes = cfg.axis_sizes()
    try:
        return resolve_mesh_shape(cfg, n_devices)
    except ValueError:
        pass
    rest = int(np.prod([v for k, v in sizes.items() if k != "data"]))
    if -1 in sizes.values() or rest <= 0 or n_devices % rest:
        raise ValueError(
            f"cannot re-factorize mesh {sizes} onto {n_devices} surviving "
            f"device(s): the non-data axes' product ({rest}) must divide "
            "the device count — resume on a slice shape the configured "
            "model sharding fits, or change the mesh config"
        )
    sizes["data"] = n_devices // rest
    return MeshSpec(**sizes)


def reinitialize_distributed(
    coordinator_address: str = "",
    num_processes: int = 0,
    process_id: int = -1,
) -> None:
    """Tear down and re-run the multi-host bootstrap on a CHANGED slice
    (topology-change recovery): ``jax.distributed.shutdown`` if a client
    is live, then :func:`initialize_distributed` with the new rendezvous
    facts (argument > platform > env, exactly like startup).  This is
    the ONE owner of the re-init path — ``scripts/repo_lint.py`` forbids
    ``jax.distributed`` calls and raw ``Mesh`` construction outside this
    module, so a second, subtly different re-init cannot grow elsewhere.
    Single-process (no facts, or world size 1): shutdown only — the
    surviving slice needs no rendezvous."""
    try:
        jax.distributed.shutdown()
    except Exception:
        # no client initialized (single-process run, or a client torn
        # down by the failure itself): nothing to shut down
        pass
    initialize_distributed(coordinator_address, num_processes, process_id)


def _valohai_facts() -> tuple[str, int, int | None]:
    """(master_ip, world_size, rank) from the platform, else env, else local.

    ``rank`` is None when no source supplied it — callers must not default
    it for multi-process runs (every host claiming rank 0 is not a rendezvous).
    """
    try:
        import valohai  # type: ignore

        dist = valohai.distributed
        if dist.is_distributed_task():
            return (
                dist.master().primary_local_ip,
                int(dist.required_count),
                int(dist.me().rank),
            )
    except Exception:
        pass
    env = os.environ
    ip = env.get("VH_MASTER_IP", env.get("MASTER_ADDR", ""))
    world = int(env.get("VH_WORLD_SIZE", env.get("WORLD_SIZE", "1")))
    rank_s = env.get("VH_RANK", env.get("RANK"))
    return ip, world, (int(rank_s) if rank_s is not None else None)


def device_report() -> dict:
    """TPU analog of the reference's ``print_gpu_report``
    (train-torchrun.py:37-58): versions + device inventory, as a dict for the
    JSON-lines metadata channel instead of ``nvidia-smi`` stdout scraping."""
    devs = jax.devices()
    return {
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "global_device_count": jax.device_count(),
        "local_device_count": jax.local_device_count(),
        "devices": [
            {
                "id": d.id,
                "platform": d.platform,
                "kind": getattr(d, "device_kind", "?"),
                "process": d.process_index,
            }
            for d in devs[:32]
        ],
    }
