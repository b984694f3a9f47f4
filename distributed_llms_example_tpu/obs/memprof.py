"""Per-device HBM attribution: the bucketed byte account + OOM forensics.

HBM is the binding constraint for every 7B-class config on a 16 GB v5e
chip, and until now it had no account: the memory audit was a one-shot
CLI, runtime ``memory_stats`` reads were scattered ad hoc, peaks were
process-lifetime, and an OOM died with a raw RESOURCE_EXHAUSTED and no
record of where the bytes went.  This module is the one owner of both
faces of the question "where did the bytes go":

- **the static account** (``account_from_compiled`` /
  ``static_memory_account``): walk the AOT-compiled train step's
  ``memory_analysis()`` plus the abstract state tree's per-shard byte
  counts (both via ``utils/memory_audit.py``'s shared accounting
  functions — single owner, no forked arithmetic) into ONE bucketed
  peak composition over the shared scheme ``BUCKETS`` (params /
  optimizer_state / grad_accum — the EF carry — / activations+temps /
  kv_cache / other), with donation/aliasing credited (outputs minus
  aliased), the largest-N buffers named, and a fit verdict against an
  ``--hbm-budget-gib`` ceiling.  The decomposition is ADDITIVE: the
  bucket bytes sum to the compiled peak up to a stamped
  ``additivity_gap_bytes`` (test-pinned within 5% on the real compiled
  fsdp=8 program), and the params/optimizer buckets equal the memory
  audit's analytic shard-byte counts EXACTLY because they ARE the same
  numbers from the same function.

- **the runtime side** (``Watermark`` / ``MemoryMonitor``): sample the
  backend's ``memory_stats`` at log cadence into ``memory_window``
  events.  PJRT peaks are PROCESS-LIFETIME — a per-phase "did this pass
  allocate a new high-water mark?" needs reset-or-delta semantics, and
  there is no public reset, so ``Watermark`` owns the delta form:
  ``mark()`` snapshots per-device peaks, readings report
  ``watermark_delta_bytes`` since the mark.  Everyone who used to
  hand-roll this (bench's per-pass ``peak_hbm_new_high_water``, the
  serving engine's peak reads) now goes through here — repo-lint rule
  15 forbids raw ``memory_stats()``/``live_buffers()`` outside the
  owners.  On backends that report nothing (CPU PJRT) the account
  degrades to STATIC-ONLY with one named ``memory_window_skipped``
  event — absent beats zero, never a silent 0.

- **OOM forensics** (``is_resource_exhausted`` / ``dump_postmortem``):
  when a RESOURCE_EXHAUSTED escapes the trainer or the serving engine,
  a schema-stamped ``memory-postmortem-p*.json`` bundle lands via the
  recorder's atomic-write discipline (tmp + fsync + rename — a kill -9
  mid-dump leaves either nothing or a complete bundle) carrying the
  last static account, the watermark history, and a live-buffer top-N
  where the backend supports it; then the error re-raises.  The report
  CLI (obs/report.py "Where did the bytes go") renders account, windows
  and postmortems from the JSONL/bundle files alone.
"""

from __future__ import annotations

import json
import os
from collections import deque
from typing import Any, Iterable, Mapping

from distributed_llms_example_tpu.obs import sink as sink_mod
from distributed_llms_example_tpu.obs.sink import SCHEMA_VERSION

# The ONE bucket scheme both faces (and the serving account) share.
# grad_accum covers the in-step fp32 accumulation carry AND the
# error-feedback tree (TrainState.ef); kv_cache is the serving cache
# (flat or paged pool); activations is the compiled program's temp
# arena (saved residuals + recompute working set + logits).
BUCKETS = (
    "params", "optimizer_state", "grad_accum", "activations", "kv_cache",
    "other",
)

GIB = 1024**3


# ---------------------------------------------------------------------------
# runtime readings: memory_stats ownership + watermark semantics
# ---------------------------------------------------------------------------


def hbm_stats() -> list[dict] | None:
    """Per-local-device live memory: bytes in use / peak / limit.  None
    when the backend does not report (CPU PJRT) — absent beats zero.
    The ONE raw ``memory_stats()`` read of the runtime side (repo-lint
    rule 15); ``obs/gauges.py`` re-exports this for its callers."""
    import jax

    out = []
    for d in jax.local_devices():
        stats = d.memory_stats() if hasattr(d, "memory_stats") else None
        if not stats:
            return None
        out.append({
            "device": d.id,
            "bytes_in_use": int(stats.get("bytes_in_use", 0)),
            "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
            "bytes_limit": int(stats.get("bytes_limit", 0)),
        })
    return out


class Watermark:
    """Reset-or-delta semantics over the process-lifetime PJRT peak.

    ``peak_bytes_in_use`` never goes down, so "what did THIS pass / THIS
    window newly touch?" cannot be read off the raw stat.  There is no
    public peak-reset API either; the delta form is the honest one:
    ``mark()`` snapshots each device's current peak, and every reading
    reports ``watermark_delta_bytes`` = max over devices of (peak now −
    peak at the mark) — 0 when the phase stayed under the old high-water
    mark, the newly claimed bytes when it did not."""

    def __init__(self):
        self._marked: dict[int, int] = {}

    def mark(self) -> None:
        """Snapshot per-device peaks as the new baseline.  A no-op (the
        baseline stays empty ⇒ deltas read as absolute peaks) on
        backends without memory_stats."""
        stats = hbm_stats()
        if stats:
            self._marked = {
                s["device"]: s["peak_bytes_in_use"] for s in stats
            }

    def read(self) -> dict | None:
        """One reading, maxed over local devices: ``bytes_in_use``,
        ``peak_bytes_in_use``, ``watermark_delta_bytes`` (since the last
        ``mark()``), ``bytes_limit``.  None when the backend reports
        nothing — the caller emits a named skip, never zeros."""
        stats = hbm_stats()
        if not stats:
            return None
        return {
            "bytes_in_use": max(s["bytes_in_use"] for s in stats),
            "peak_bytes_in_use": max(s["peak_bytes_in_use"] for s in stats),
            "watermark_delta_bytes": max(
                s["peak_bytes_in_use"] - self._marked.get(s["device"], 0)
                for s in stats
            ),
            "bytes_limit": max(s["bytes_limit"] for s in stats),
            "devices": len(stats),
        }

    def peak_bytes(self) -> int:
        """Current process-lifetime peak (max over local devices), 0 when
        the backend reports nothing — the legacy ``device_peak_bytes``
        shape the serving summary stamps."""
        stats = hbm_stats()
        if not stats:
            return 0
        return max(s["peak_bytes_in_use"] for s in stats)

    def delta_bytes(self) -> int | None:
        """Peak bytes newly claimed since ``mark()`` (None when the
        backend reports nothing) — bench's per-pass high-water delta."""
        reading = self.read()
        return None if reading is None else reading["watermark_delta_bytes"]


def is_resource_exhausted(e: BaseException) -> bool:
    """Does this exception look like an HBM/host OOM?  XLA surfaces
    RESOURCE_EXHAUSTED through ``XlaRuntimeError`` (message-matched —
    the type is not constructible for tests), chaos injects a plain
    RuntimeError with the same marker, and MemoryError covers the host
    side."""
    if isinstance(e, MemoryError):
        return True
    text = f"{type(e).__name__}: {e}".lower()
    return (
        "resource_exhausted" in text
        or "resource exhausted" in text
        or "out of memory" in text
        or "allocation failure" in text
    )


# ---------------------------------------------------------------------------
# the static account
# ---------------------------------------------------------------------------


def account_from_compiled(
    compiled: Any,
    a_state: Any,
    sh: Any,
    *,
    hbm_budget_gib: float = 16.0,
    top_n: int = 8,
    model: str = "",
    mesh: Mapping[str, int] | None = None,
) -> dict:
    """The bucketed peak composition of one AOT-compiled train step.

    Every byte comes from the memory audit's shared accounting functions
    (``compiled_byte_view`` over XLA's ``memory_analysis()``,
    ``state_bucket_bytes`` over the abstract state's shard shapes) so
    this account and the audit's ``analytic_*``/``compiled_*`` views can
    never fork.  Decomposition, per device:

    - params / optimizer_state / grad_accum (EF carry) / other(step
      counter): the donated state argument, split by TrainState field —
      these ARE the audit's analytic shard-byte counts;
    - activations: the compiled temp arena (saved residuals, recompute
      working set, fp32 logits — plus the in-step grad-accum scan carry,
      which XLA allocates as a temp);
    - other also absorbs non-state arguments (the batch) and the
      non-aliased output slack (donation credited: outputs − aliased).

    The buckets sum to the compiled peak up to ``additivity_gap_bytes``
    (0 by construction unless XLA reports arguments smaller than the
    state that rides them)."""
    import jax

    from distributed_llms_example_tpu.utils.memory_audit import (
        compiled_byte_view,
        state_bucket_bytes,
    )

    view = compiled_byte_view(compiled.memory_analysis())
    state_buckets = state_bucket_bytes(a_state, sh)
    state_total = sum(state_buckets.values())
    buckets = {b: 0 for b in BUCKETS}
    for k, v in state_buckets.items():
        buckets[k] += int(v)
    buckets["activations"] = int(view["temp_bytes"])
    buckets["other"] += max(0, view["arguments_bytes"] - state_total)
    buckets["other"] += max(0, view["output_bytes"] - view["aliased_bytes"])
    total = sum(buckets.values())
    peak = int(view["peak_bytes"])
    budget_bytes = int(float(hbm_budget_gib) * GIB)
    account: dict[str, Any] = {
        "model": model,
        "mesh": dict(mesh) if mesh is not None else None,
        "backend": jax.default_backend(),
        "buckets_bytes": buckets,
        "bucket_total_bytes": total,
        "peak_bytes": peak,
        "peak_gib": round(peak / GIB, 3),
        "additivity_gap_bytes": peak - total,
        "compiled": view,
        "largest_buffers": largest_state_buffers(a_state, sh, n=top_n),
        "hbm_budget_gib": float(hbm_budget_gib),
        "hbm_budget_bytes": budget_bytes,
        "peak_frac_of_budget": (
            round(peak / budget_bytes, 4) if budget_bytes else None
        ),
        "hbm_headroom_gib": round((budget_bytes - peak) / GIB, 3),
        "fits_budget": peak < budget_bytes,
    }
    return account


def largest_state_buffers(a_state: Any, sh: Any, *, n: int = 8) -> list[dict]:
    """The N largest per-device state buffers, named by pytree path and
    tagged with the coarse model-module bucket
    (``analysis/ir_lint.py``'s MODULE_BUCKET_PATTERNS) where the path
    names one."""
    import jax
    import numpy as np

    from distributed_llms_example_tpu.analysis.ir_lint import module_bucket_of

    rows: list[dict] = []
    leaves, _ = jax.tree_util.tree_flatten_with_path(a_state)
    sh_leaves = jax.tree.leaves(sh)
    for (path, leaf), shard in zip(leaves, sh_leaves):
        name = jax.tree_util.keystr(path)
        shard_shape = shard.shard_shape(leaf.shape)
        nbytes = int(np.prod(shard_shape)) * leaf.dtype.itemsize
        row = {
            "name": name,
            "shape": list(leaf.shape),
            "shard_shape": list(shard_shape),
            "dtype": str(leaf.dtype),
            "bytes": nbytes,
        }
        module = module_bucket_of(name)
        if module is not None:
            row["module"] = module
        rows.append(row)
    rows.sort(key=lambda r: (-r["bytes"], r["name"]))
    return rows[: max(0, int(n))]


def static_memory_account(
    model_name: str,
    mesh: Any,
    *,
    global_batch: int = 8,
    src_len: int = 1024,
    tgt_len: int = 128,
    dtype: str = "bfloat16",
    remat: bool = True,
    remat_policy: str = "full",
    grad_accum_steps: int = 1,
    grad_compression: str = "",
    hbm_budget_gib: float = 16.0,
    top_n: int = 8,
) -> dict:
    """Compile the train step via the shared AOT recipe and account it —
    the stand-alone entry ``analysis/lint.py --memory`` and tests use
    when no caller already holds a compiled program."""
    from distributed_llms_example_tpu.utils.memory_audit import (
        aot_compile_train_step,
    )

    compiled, _, _, a_state, sh = aot_compile_train_step(
        model_name, mesh,
        global_batch=global_batch, src_len=src_len, tgt_len=tgt_len,
        dtype=dtype, remat=remat, remat_policy=remat_policy,
        grad_accum_steps=grad_accum_steps, grad_compression=grad_compression,
    )
    return account_from_compiled(
        compiled, a_state, sh,
        hbm_budget_gib=hbm_budget_gib, top_n=top_n,
        model=model_name, mesh=dict(mesh.shape),
    )


def serving_account(
    *,
    params_bytes: int,
    kv_cache_bytes: int,
    hbm_budget_gib: float = 16.0,
) -> dict:
    """The serving tier's bucketed account over the SAME scheme: the
    capacity gauges' cache-bytes arithmetic (serving/engine.py) lands in
    ``kv_cache``, the loaded weights in ``params``.  Shares the fit
    fields with the training account so the report renders both with one
    table shape."""
    buckets = {b: 0 for b in BUCKETS}
    buckets["params"] = int(params_bytes)
    buckets["kv_cache"] = int(kv_cache_bytes)
    total = sum(buckets.values())
    budget_bytes = int(float(hbm_budget_gib) * GIB)
    return {
        "buckets_bytes": buckets,
        "bucket_total_bytes": total,
        "peak_bytes": total,
        "peak_gib": round(total / GIB, 3),
        "hbm_budget_gib": float(hbm_budget_gib),
        "hbm_budget_bytes": budget_bytes,
        "peak_frac_of_budget": (
            round(total / budget_bytes, 4) if budget_bytes else None
        ),
        "hbm_headroom_gib": round((budget_bytes - total) / GIB, 3),
        "fits_budget": total < budget_bytes,
    }


# ---------------------------------------------------------------------------
# the runtime monitor
# ---------------------------------------------------------------------------


class MemoryMonitor:
    """Log-cadence memory telemetry + the OOM postmortem's state.

    Owns one ``Watermark`` (marked after every window, so each
    ``memory_window`` event carries the delta SINCE THE LAST WINDOW) and
    a bounded history of recent readings — exactly what the postmortem
    bundle replays.  ``sample()`` off a reporting backend emits ONE
    named ``memory_window_skipped`` event and then stays silent: the
    account degrades to static-only, never to a stream of zeros."""

    def __init__(self, *, history: int = 64):
        self.account: dict | None = None
        self.watermark = Watermark()
        self.history: deque = deque(maxlen=max(1, int(history)))
        self._skip_emitted = False

    def attach_account(self, account: dict | None) -> None:
        """The last static account — stamped into postmortem bundles."""
        self.account = account

    def sample(self, step: int, *, emit: bool = True) -> dict | None:
        """One log-cadence reading → a ``memory_window`` event (local:
        every rank's file carries its own devices' numbers).  Returns the
        record, or None when the backend reports nothing."""
        reading = self.watermark.read()
        if reading is None:
            if emit and not self._skip_emitted:
                self._skip_emitted = True
                sink_mod.emit({
                    "event": "memory_window_skipped",
                    "step": int(step),
                    "reason": (
                        "backend reports no memory_stats (CPU PJRT) — "
                        "memory account degrades to static-only"
                    ),
                }, local=True)
            return None
        record = {"event": "memory_window", "step": int(step), **reading}
        self.history.append({
            "step": int(step),
            "bytes_in_use": reading["bytes_in_use"],
            "peak_bytes_in_use": reading["peak_bytes_in_use"],
            "watermark_delta_bytes": reading["watermark_delta_bytes"],
        })
        self.watermark.mark()
        if emit:
            sink_mod.emit(record, local=True)
        return record

    def maybe_dump_postmortem(
        self, output_dir: str, *, step: int, error: BaseException
    ) -> str | None:
        """The tripwire: when ``error`` is a RESOURCE_EXHAUSTED, dump the
        postmortem bundle (atomic) and return its path; otherwise do
        nothing.  The caller re-raises either way — forensics never
        swallow the failure."""
        if not is_resource_exhausted(error):
            return None
        return dump_postmortem(
            output_dir,
            reason=f"{type(error).__name__}: {str(error)[:300]}",
            step=step,
            account=self.account,
            watermark_history=list(self.history),
        )


# ---------------------------------------------------------------------------
# OOM postmortem bundles
# ---------------------------------------------------------------------------


def postmortem_path(output_dir: str) -> str:
    import jax

    return os.path.join(
        output_dir, "obs", f"memory-postmortem-p{jax.process_index():03d}.json"
    )


def _live_buffer_top(n: int = 10) -> list[dict] | None:
    """Largest live device buffers at dump time, where the backend can
    enumerate them.  Broad except: this runs on the crash path against a
    runtime that may have just OOMed — losing the top-N must not lose
    the bundle."""
    import jax

    try:
        arrays = jax.live_arrays()
        rows = sorted(
            (
                {
                    "shape": list(a.shape),
                    "dtype": str(a.dtype),
                    "bytes": int(a.size) * a.dtype.itemsize,
                }
                for a in arrays
            ),
            key=lambda r: -r["bytes"],
        )[: max(0, int(n))]
        return rows or None
    except Exception:
        return None


def dump_postmortem(
    output_dir: str,
    *,
    reason: str,
    step: int,
    account: dict | None = None,
    watermark_history: Iterable[Mapping] = (),
    top_n: int = 10,
) -> str | None:
    """Write the schema-stamped ``memory-postmortem-p*.json`` bundle via
    the recorder's atomic-write discipline (tmp + fsync + rename: a kill
    mid-dump leaves the previous bundle or the complete new one, never a
    torn JSON) and announce it on the sink.  Telemetry never takes down
    the run — IO errors are reported as ``memory_postmortem_failed``,
    not raised."""
    import jax

    path = postmortem_path(output_dir)
    final_reading = Watermark().read()
    bundle: dict[str, Any] = {
        "schema_version": SCHEMA_VERSION,
        "event": "memory_postmortem",
        "reason": str(reason)[:400],
        "step": int(step),
        "process_index": int(jax.process_index()),
        "account": account,
        "watermark_history": [dict(w) for w in watermark_history],
        "final_reading": final_reading,
    }
    top = _live_buffer_top(top_n)
    if top is not None:
        bundle["live_buffers_top"] = top
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(bundle, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except OSError as e:
        sink_mod.emit(
            {"event": "memory_postmortem_failed", "reason": str(e)[:200]},
            local=True,
        )
        return None
    sink_mod.emit(
        {
            "event": "memory_postmortem",
            "path": path,
            "reason": str(reason)[:200],
            "step": int(step),
        },
        local=True,
    )
    return path
