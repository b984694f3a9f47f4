"""Derived device gauges: MFU, live HBM, and the collective-traffic account.

Three signals, all computed without ever materializing a weight:

- **MFU numerator** — per-step FLOPs from XLA's cost analysis of the
  AOT-compiled train step (the shared compile recipe in
  utils/memory_audit.py, the SAME program the memory audit and IR lint
  reason about), with the standard ``6·N·tokens`` training estimate as a
  backend-independent fallback.  The Trainer divides by measured window
  step time × chips × peak FLOPs at the logging cadence.
- **Live HBM** — ``device.memory_stats()`` per local device (bytes in
  use / peak / limit).  CPU's PJRT client reports None; the gauge then
  reports nothing rather than zeros an operator might believe.
- **Collective-traffic account** — a static per-step byte account of the
  compiled program's collectives (all-reduce / all-gather /
  reduce-scatter / all-to-all / collective-permute), split into
  gradient/parameter traffic vs activation traffic.  Classification: a
  collective whose tensor element count matches a model-tree leaf (full,
  or an even mesh shard of one — ``analysis/ir_lint.py``'s candidate
  set, so the lint census and this account can never disagree) moves the
  parameter/gradient tree; everything else moves activations.  Byte
  totals count each instruction once per program pass (a grad-accum scan
  body is counted once, not per microbatch).

This is the runtime face of the IR lint's open reduce-scatter item: a
correctly sharded FSDP step reduce-scatters its gradients; an account
showing the same bytes all-REDUCED instead is the 2× gradient-traffic
smell (arxiv 2004.13336) showing up in production telemetry.
"""

from __future__ import annotations

import math
from typing import Any, Iterable

from distributed_llms_example_tpu.analysis.ir_lint import (
    model_tree_element_candidates,
    op_bucket_index,
    parse_hlo_instructions,
)

# async -start forms account like their sync ops; -done carries no bytes
_TRAFFIC_OPS = {
    "all-gather": "all-gather",
    "all-gather-start": "all-gather",
    "all-reduce": "all-reduce",
    "all-reduce-start": "all-reduce",
    "reduce-scatter": "reduce-scatter",
    "all-to-all": "all-to-all",
    "collective-permute": "collective-permute",
    "collective-permute-start": "collective-permute",
}


def training_flops_estimate(n_params: int, tokens_per_step: int) -> float:
    """The standard 6·N FLOPs/token training estimate (fwd 2N + bwd 4N
    matmul FLOPs; attention excluded, so MFU built on it runs slightly
    conservative)."""
    return 6.0 * float(n_params) * float(tokens_per_step)


# Peak dense bf16 FLOP/s per chip, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud TPU documentation, "TPU v5e" system architecture
# page (197 TFLOP/s bf16 per chip).  A device that is not listed has no
# MFU: callers omit the gauge (and a benchmark fails) rather than divide
# by another chip's peak.
PEAK_BF16_FLOPS: dict[str, float] = {
    "TPU v5 lite": 197e12,
}


def mfu(
    flops_per_step: float,
    step_time_s: float,
    n_chips: int,
    peak_flops_per_chip: float,
) -> float:
    """Model FLOPs utilization: achieved FLOP rate over aggregate peak."""
    denom = step_time_s * n_chips * peak_flops_per_chip
    if denom <= 0:
        return 0.0
    return flops_per_step / denom


def hbm_stats() -> list[dict] | None:
    """Per-local-device live memory: bytes in use / peak / limit.  None
    when the backend does not report (CPU PJRT) — absent beats zero.
    Since the memprof PR, ``obs/memprof.py`` owns the raw
    ``memory_stats`` read (repo-lint rule 15); this re-export keeps the
    historical import site working."""
    from distributed_llms_example_tpu.obs import memprof

    return memprof.hbm_stats()


def collective_traffic(
    hlo_text,
    param_element_counts: Iterable[int],
    mesh_size: int,
) -> dict:
    """Static per-step collective-traffic account from compiled HLO text
    (or an already-parsed instruction dict — see ``op_bucket_index``).

    Returns ``{op: {count, gradient_bytes, activation_bytes}, ...}`` plus
    ``total_bytes``/``gradient_bytes``/``activation_bytes`` rollups.
    Sizes are the per-device tensor bytes the instruction defines (max
    tuple element for async starts) — the same sizing the IR lint census
    reports, via the same parser.
    """
    instrs = (
        parse_hlo_instructions(hlo_text)
        if isinstance(hlo_text, str)
        else hlo_text
    )
    candidates = model_tree_element_candidates(param_element_counts, mesh_size)
    account: dict[str, dict[str, int]] = {}
    total = grad_total = 0
    for instr in instrs.values():
        op = _TRAFFIC_OPS.get(instr.op)
        if op is None:
            continue
        touched = {instr.elems} | {
            instrs[o].elems for o in instr.operands if o in instrs
        }
        is_grad = bool(touched & candidates)
        slot = account.setdefault(
            op, {"count": 0, "gradient_bytes": 0, "activation_bytes": 0}
        )
        slot["count"] += 1
        slot["gradient_bytes" if is_grad else "activation_bytes"] += instr.bytes
        total += instr.bytes
        grad_total += instr.bytes if is_grad else 0
    return {
        **account,
        "total_bytes": total,
        "gradient_bytes": grad_total,
        "activation_bytes": total - grad_total,
    }


def train_step_static_gauges(
    model_name: str,
    mesh: Any,
    *,
    global_batch: int = 8,
    src_len: int = 1024,
    tgt_len: int = 128,
    dtype: str = "bfloat16",
    remat: bool = False,
    remat_policy: str = "full",
    grad_accum_steps: int = 1,
    grad_compression: str = "",
    hbm_budget_gib: float = 16.0,
) -> dict:
    """AOT-compile the train step (the shared recipe the memory audit and
    IR lint use — utils/memory_audit.py) and derive the static gauges:
    per-step FLOPs for the MFU numerator, the collective-traffic account,
    and the bucketed HBM account (obs/memprof.py) — all from the ONE
    compiled program.  No weights materialize; the compile is the only
    cost."""
    import jax

    from distributed_llms_example_tpu.obs import memprof
    from distributed_llms_example_tpu.utils.memory_audit import (
        aot_compile_train_step,
    )

    compiled, lm, a_params, a_state, state_sh = aot_compile_train_step(
        model_name,
        mesh,
        global_batch=global_batch,
        src_len=src_len,
        tgt_len=tgt_len,
        dtype=dtype,
        remat=remat,
        remat_policy=remat_policy,
        grad_accum_steps=grad_accum_steps,
        grad_compression=grad_compression,
    )
    leaves = jax.tree.leaves(a_params)
    n_params = int(sum(int(math.prod(x.shape)) for x in leaves))
    tokens_per_step = global_batch * (
        src_len + tgt_len if lm.is_seq2seq else src_len
    )
    mesh_size = 1
    for v in dict(mesh.shape).values():
        mesh_size *= int(v)
    flops_source = "hlo_cost_analysis"
    flops = 0.0
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, list):  # some backends return one dict per device
            ca = ca[0] if ca else {}
        # the compiled (post-SPMD) module is the PER-DEVICE program —
        # measured: an 8-way sharded matmul reports 1/8 of the lowered
        # module's flops — so scale to the global per-step count the MFU
        # formula divides by aggregate peak.  Under grad accumulation the
        # cost analysis counts the scan's while BODY exactly once
        # (measured on jax 0.4.37: flops(accum=4) ≈ flops(accum=1)/4 +
        # loop bookkeeping at the same effective batch — pinned in
        # tests/test_obs.py), so scale by N to cover all N microbatches.
        # This overcounts the once-per-step optimizer tail by (N-1)× —
        # visible only at toy widths (~10% on t5-test), vanishing at real
        # model widths where the tail is <0.1% of model flops.
        flops = float((ca or {}).get("flops", 0.0)) * mesh_size * int(grad_accum_steps)
    except Exception:
        pass
    if flops <= 0.0:
        flops = training_flops_estimate(n_params, tokens_per_step)
        flops_source = "6N_tokens_estimate"
    # ONE parse of the (potentially tens-of-MB) compiled text feeds both
    # the traffic account and the device-attribution index
    instrs = parse_hlo_instructions(compiled.as_text())
    comm = collective_traffic(
        instrs,
        [int(math.prod(x.shape)) for x in leaves],
        mesh_size,
    )
    return {
        "model": model_name,
        "mesh": dict(mesh.shape),
        "global_batch": global_batch,
        "grad_accum_steps": int(grad_accum_steps),
        # stamped so the byte account reads in context: an s8-dominated
        # gradient account is correct under int8 and a bug under off
        "grad_compression": grad_compression or "off",
        "params": n_params,
        "tokens_per_step": tokens_per_step,
        "flops_per_step": flops,
        "flops_source": flops_source,
        "comm": comm,
        # the bucketed HBM account of the SAME compiled program — the
        # trainer pops this into its own memory_account event and hands
        # it to the memory monitor for OOM postmortems
        "memory_account": memprof.account_from_compiled(
            compiled, a_state, state_sh,
            hbm_budget_gib=hbm_budget_gib,
            model=model_name, mesh=dict(mesh.shape),
        ),
        # instruction→bucket index for the device-time attribution
        # (obs/devprof.py): CPU-backend traces name device events by HLO
        # instruction, and this program is the same lowering the runtime
        # executes.  Popped off before the obs_gauges record is emitted —
        # thousands of entries have no place on a metric line.
        "op_bucket_index": op_bucket_index(instrs),
    }
