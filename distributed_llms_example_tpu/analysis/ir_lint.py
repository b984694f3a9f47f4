"""Pass 2 — lint the COMPILED train-step program for sharding smells.

The spec lint (pass 1) checks what the operator *declared*; this pass
checks what the compiler actually *built*.  The train step is lowered and
compiled ahead-of-time from abstract ShapeDtypeStruct arguments — no
weights are ever materialized (the same AOT plumbing as
utils/memory_audit.py) — and the post-optimization HLO text is scanned:

- ``full-param-all-gather``: an all-gather materializing ≥ threshold bytes
  on a mesh with NO model-sharding axes (pure data parallel keeps params
  replicated — any big gather is GSPMD resharding churn; error), or a
  gather ≥ 2× the largest single parameter on an fsdp mesh (the prefetch
  path gathers one param at a time; a mega-gather means XLA fused a
  whole-tree gather and the memory cliff is back; warning).
- ``bf16-matmul-promoted-to-f32``: a ``convert`` promoting a bf16 value to
  f32 that then feeds a ``dot`` — the hot-path precision-policy violation
  (core/precision.py supplies the (from, to) pair, so the pattern follows
  the ACTIVE policy).  fp32 *accumulation* of a bf16 dot is fine and not
  matched.
- ``degenerate-collective``: a collective whose replica groups are all
  singletons (or a self-loop collective-permute) — traffic over an axis
  the config says is size 1; usually a spec naming an axis the mesh
  doesn't actually split.
- ``host-transfer-in-step``: infeed/outfeed, ``is_host_transfer=true``
  send/recv/copy, or host-offloading custom-calls inside the step body —
  a host round-trip per step serializes async dispatch (error; the
  compiled-IR twin of scripts/repo_lint.py rule 4).

The text scanner is pure (string in, findings out) so tests can seed
violations deterministically; the compile driver wraps it.
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Any, Iterable, Mapping

from distributed_llms_example_tpu.analysis.findings import Finding

# HLO element-type byte widths (only what transformer programs produce).
_ITEMSIZE = {
    "f64": 8, "s64": 8, "u64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

# `  %name = f32[8,128]{1,0} opcode(...operands...)` — also matches
# layout-less and scalar forms; ROOT prefix optional.
_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<dtype>[a-z]\w*)\[(?P<dims>[0-9,]*)\]\S*\s+"
    r"(?P<op>[\w\-]+)\("
)
# Async collective forms define a TUPLE: `%ags = (bf16[..], bf16[..])
# all-gather-start(...)` — the shape regex above cannot parse the leading
# paren, so tuple defs get their own pattern; the per-element shapes are
# re-parsed with _TUPLE_ELEM_RE (max element ≈ the gathered result size).
_TUPLE_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
    r"\((?P<elems>[^)]*)\)\s+"
    r"(?P<op>[\w\-]+)\("
)
_TUPLE_ELEM_RE = re.compile(r"([a-z]\w*)\[([0-9,]*)\]")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
# jax stamps every lowered instruction with the originating scope path:
# metadata={op_name="jit(f)/jit(main)/Model/encoder/block_0/self_attn/..."}
_OP_NAME_RE = re.compile(r'op_name="(?P<op_name>[^"]*)"')
_REPLICA_GROUPS_RE = re.compile(r"replica_groups=\{(\{[^}]*\}(?:,\s*\{[^}]*\})*)\}")
_SOURCE_TARGET_RE = re.compile(r"source_target_pairs=\{(\{[^}]*\}(?:,\s*\{[^}]*\})*)\}")

_COLLECTIVE_OPS = (
    "all-gather", "all-gather-start", "all-reduce", "all-reduce-start",
    "reduce-scatter", "all-to-all", "collective-permute",
    "collective-permute-start",
)

# Ops that ALWAYS mean host traffic; send/recv/copy additionally carry an
# ``is_host_transfer=true`` attribute when they cross to the host (plain
# send/recv pairs can be legitimate device-to-device channel traffic on
# some backends, so only the attributed forms are flagged).
_HOST_TRANSFER_OPS = ("infeed", "outfeed")
_HOST_ATTRIBUTED_OPS = ("send", "send-done", "recv", "recv-done",
                        "copy-start", "copy-done")
# GSPMD/XLA host-offloading custom-call targets
_HOST_CUSTOM_CALLS = ("MoveToHost", "MoveToDevice", "PinToHost",
                      "annotate_device_placement")


def _bytes_of(dtype: str, dims: str) -> int:
    shape = [int(d) for d in dims.split(",") if d]
    return int(math.prod(shape)) * _ITEMSIZE.get(dtype, 4)


def _elems_of(dims: str) -> int:
    return int(math.prod([int(d) for d in dims.split(",") if d]))


@dataclasses.dataclass(frozen=True)
class HloInstr:
    """One parsed HLO instruction definition (post-optimization text).

    For tuple-shaped defs (async collective ``-start`` forms) ``bytes``/
    ``elems``/``dtype``/``dims`` describe the LARGEST tuple element — for
    an all-gather-start that is the gathered result, the size that
    matters for traffic and memory accounting alike.
    """

    name: str
    dtype: str
    dims: str
    op: str
    bytes: int
    elems: int
    operands: tuple[str, ...]
    line: str
    op_name: str = ""  # metadata scope path ("" when the text carries none)


def parse_hlo_instructions(hlo_text: str) -> dict[str, HloInstr]:
    """Instruction-name → parsed def, for every definition in the text.

    THE one HLO text parser: the lint passes below, the obs collective
    -traffic account (obs/gauges.py) and the device-time attribution
    index (obs/devprof.py via ``op_bucket_index``) all consume it, so
    their byte/bucket arithmetic cannot drift."""
    out: dict[str, HloInstr] = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if m:
            name = m.group("name")
            meta = _OP_NAME_RE.search(line)
            out[name] = HloInstr(
                name=name,
                dtype=m.group("dtype"),
                dims=m.group("dims"),
                op=m.group("op"),
                bytes=_bytes_of(m.group("dtype"), m.group("dims")),
                elems=_elems_of(m.group("dims")),
                operands=tuple(_OPERAND_RE.findall(line[m.end():])),
                line=line,
                op_name=meta.group("op_name") if meta else "",
            )
            continue
        t = _TUPLE_DEF_RE.match(line)
        if t:
            name = t.group("name")
            elems = _TUPLE_ELEM_RE.findall(t.group("elems"))
            if elems:
                dt, dims = max(elems, key=lambda e: _bytes_of(*e))
            else:
                dt, dims = "f32", ""
            meta = _OP_NAME_RE.search(line)
            out[name] = HloInstr(
                name=name,
                dtype=dt,
                dims=dims,
                op=t.group("op"),
                bytes=_bytes_of(dt, dims),
                elems=_elems_of(dims),
                operands=tuple(_OPERAND_RE.findall(line[t.end():])),
                line=line,
                op_name=meta.group("op_name") if meta else "",
            )
    return out


# --------------------------------------------------------------------------
# op_name scope → module bucket (shared with train/step.py's bucket_of_path
# and obs/devprof.py's device-time attribution — ONE name-matching table, so
# the health telemetry's param buckets and the profiler's device buckets can
# never disagree on what "attn" means).
# --------------------------------------------------------------------------

# Ordered: first match wins.  head before embed (an "lm_head" tied to the
# embedding table must not read as embed), embed before attn/mlp.
MODULE_BUCKET_PATTERNS: tuple[tuple[str, tuple[str, ...]], ...] = (
    ("head", ("lm_head", "logits")),
    ("embed", ("embed", "shared", "wte", "wpe")),
    ("attn", ("attn", "attention")),
    ("mlp", ("mlp", "ffn", "feed_forward", "densereludense", "fc1", "fc2")),
)

# scope substrings that mark the optimizer/clip/health tail (optax traces
# carry no flax module scope, so these name fragments are the signal)
_OPTIMIZER_SCOPE_HINTS = (
    "adam", "optax", "optimizer", "opt_state", "fused_optim",
    "apply_updates", "clip_by_global_norm", "weight_decay",
)


def module_bucket_of(scope: str) -> str | None:
    """The coarse model-module bucket a scope/path string names, or None
    when it carries no module signal.  ``train.step.bucket_of_path``
    (param paths, falls back to "mlp" — a param bucket must be total) and
    ``obs/devprof`` (device op_name scopes, falls back to "other") both
    route through this table."""
    p = scope.lower()
    for bucket, needles in MODULE_BUCKET_PATTERNS:
        if any(n in p for n in needles):
            return bucket
    return None


def classify_op_scope(scope: str) -> str | None:
    """Device-account class for one HLO ``op_name`` scope: "optimizer"
    for the clip/AdamW/health tail, else the module bucket, else None
    (loss arithmetic, layout ops, scan plumbing — "other")."""
    p = scope.lower()
    if any(h in p for h in _OPTIMIZER_SCOPE_HINTS):
        return "optimizer"
    return module_bucket_of(p)


def base_collective_op(op: str) -> str | None:
    """"all-reduce-start.1" → "all-reduce"; None for non-collectives.
    Accepts instruction NAMES (trailing ".N" / ".clone" suffixes) as well
    as opcodes — trace events name device ops by instruction name."""
    base = op.split(".", 1)[0]
    for suffix in ("-start", "-done"):
        if base.endswith(suffix):
            base = base[: -len(suffix)]
    return base if base in (
        "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
        "collective-permute", "collective-broadcast",
    ) else None


# host↔device transfer opcodes — the "infeed" class of the device account
_INFEED_OPS = ("infeed", "outfeed", "send", "send-done", "recv", "recv-done")


def op_bucket_index(
    hlo: "str | Mapping[str, HloInstr]",
) -> dict[str, str]:
    """Instruction name → device-account bucket, from compiled HLO text
    (or an already-parsed instruction dict — a large model's HLO text is
    tens of MB and callers holding a parse must not pay it twice).

    The join key for backends whose profiler traces name device events by
    HLO *instruction* (CPU thunk runtime: ``args.hlo_op = "fusion.3"``)
    rather than by op_name scope: classify each instruction once —
    collective and infeed by opcode, everything else by its ``op_name``
    scope metadata."""
    instrs = parse_hlo_instructions(hlo) if isinstance(hlo, str) else hlo
    out: dict[str, str] = {}
    for name, instr in instrs.items():
        if base_collective_op(instr.op) is not None:
            out[name] = "collective"
        elif instr.op in _INFEED_OPS:
            out[name] = "infeed"
        else:
            bucket = classify_op_scope(instr.op_name) if instr.op_name else None
            out[name] = bucket or "other"
    return out


def model_tree_element_candidates(
    param_elems: Iterable[int], mesh_size: int
) -> set[int]:
    """Element counts a model-tree (parameter/gradient) tensor can carry
    in the compiled per-device program: each leaf's full count plus every
    even shard of it over a divisor of the mesh size.  Collectives whose
    tensors match one of these counts are gradient/parameter traffic; the
    rest move activations.  Shared by the IR lint census and the obs
    collective-traffic account so both classify identically."""
    divisors = [d for d in range(1, max(1, int(mesh_size)) + 1) if mesh_size % d == 0]
    out: set[int] = set()
    for e in param_elems:
        e = int(e)
        if e <= 0:
            continue
        for d in divisors:
            if e % d == 0:
                out.add(e // d)
    return out


# Wire-byte weights for the census estimate: a ring all-reduce moves ~2x
# its tensor bytes per device (a reduce-scatter phase plus an all-gather
# phase); reduce-scatter / all-gather / all-to-all / permute move ~1x the
# instruction's output bytes.  An ESTIMATE of relative wire cost from the
# instruction census — the device account (obs/devprof.py) measures the
# real thing; this exists so a compression A/B can be judged on bytes
# actually moved rather than on output-buffer sizes (an all-gather's
# output is W x what it moved).
_WIRE_WEIGHT = {"all-reduce": 2.0}


def quantized_gradient_census(
    instrs: Mapping[str, HloInstr],
    param_element_counts: Iterable[int],
    mesh_axes: Mapping[str, int],
) -> dict[str, Any]:
    """Census of GRADIENT-classified collectives split by element width —
    the compiled-program proof of ``--grad-compression int8``: the
    quantized program's gradient reduction rides s8 tensors (the
    quantize-reduce-dequantize wrapper's all-to-all / all-gather legs)
    where the fp32 program rode f32.  Returns per-dtype byte totals, the
    s8 instruction names, and ``gradient_wire_bytes`` (the
    direction-weighted estimate above) — ``tests`` and the obs gate
    compare it between the off and int8 programs (~4x on the replica
    leg).  Classification (element count matches a model-tree leaf or an
    even shard of one) is the SAME candidate set the byte account uses,
    so the two can never disagree."""
    mesh_size = 1
    for v in mesh_axes.values():
        mesh_size *= max(1, int(v))
    candidates = model_tree_element_candidates(param_element_counts, mesh_size)
    by_dtype: dict[str, int] = {}
    wire = 0.0
    s8_names: list[str] = []
    for name, instr in instrs.items():
        if instr.op not in _COLLECTIVE_OPS:
            continue
        touched = {instr.elems} | {
            instrs[o].elems for o in instr.operands if o in instrs
        }
        if not (touched & candidates):
            continue
        by_dtype[instr.dtype] = by_dtype.get(instr.dtype, 0) + instr.bytes
        base = instr.op[: -len("-start")] if instr.op.endswith("-start") else instr.op
        wire += _WIRE_WEIGHT.get(base, 1.0) * instr.bytes
        if instr.dtype == "s8":
            s8_names.append(name)
    return {
        "gradient_bytes_by_dtype": by_dtype,
        "gradient_wire_bytes": int(wire),
        "s8_gradient_collectives": s8_names,
    }


def int8_compression_missing_finding(
    census: Mapping[str, Any], grad_compression: str
) -> Finding | None:
    """Error when a program built with ``--grad-compression int8``
    carries NO s8 gradient collective: the partitioner folded the wire
    back to fp32 (a hoisted reshard, a dropped pin) and the run would
    silently pay uncompressed traffic while stamping itself compressed —
    the lint-time twin of ``obs.report --strict
    --max-gradient-bytes-per-step``."""
    if grad_compression != "int8":
        return None
    if census.get("s8_gradient_collectives"):
        return None
    return Finding(
        severity="error",
        pass_name="ir",
        code="int8-compression-missing",
        message=(
            "the step was built with --grad-compression int8 but the "
            "compiled program contains no s8 gradient collective — the "
            "partitioner folded the quantized wire back to fp32 (hoisted "
            "reshard or dropped sharding pin); the run would pay full "
            f"fp32 gradient traffic ({census.get('gradient_bytes_by_dtype')})"
        ),
        context=dict(census),
    )


def int8_kv_missing_finding(
    instrs: Mapping[str, HloInstr],
    kv_cache_dtype: str,
    *,
    min_elems: int = 1024,
) -> Finding | None:
    """Error when a decode program built with ``--kv-cache-dtype int8``
    carries NO s8 cache operand: the quantized buffers never reached the
    compiled step (a dropped context, a stale f32 cache tree) and every
    decode pays full f32 HBM traffic while stamping itself int8 — the
    decode-census twin of ``int8-compression-missing`` (PR 12).  The
    predicate is deliberately simple: any s8 instruction at cache scale
    (``min_elems`` keeps a stray byte-wide scalar from vouching for the
    whole cache); a correctly built int8 decode step carries its cache
    parameters, the updated buffers, and their scatter ops all in s8."""
    if kv_cache_dtype != "int8":
        return None
    s8 = [
        name
        for name, instr in instrs.items()
        if instr.dtype == "s8" and instr.elems >= min_elems
    ]
    if s8:
        return None
    return Finding(
        severity="error",
        pass_name="ir",
        code="int8-kv-missing",
        message=(
            "the decode step was built with --kv-cache-dtype int8 but the "
            "compiled program carries no cache-sized s8 operand — the "
            "quantized cache never reached the compiled step (dropped "
            "kv_cache_context, stale f32 cache tree); decode would pay "
            "full f32 cache traffic while stamping itself int8"
        ),
    )


def account_gradient_bytes_by_op(account: Mapping[str, Any]) -> dict[str, int]:
    """Adapter: the obs collective-traffic account (obs/gauges.py
    ``collective_traffic`` — per-op dicts with ``gradient_bytes``) →
    the flat ``{op: gradient_bytes}`` map the reduce-scatter predicate
    consumes, so the SAME predicate runs over the IR census and the
    runtime account."""
    out: dict[str, int] = {}
    for op, slot in account.items():
        if isinstance(slot, Mapping) and "gradient_bytes" in slot:
            out[op] = int(slot["gradient_bytes"])
    return out


def reduce_scatter_smell(
    gradient_bytes_by_op: Mapping[str, int],
    mesh_axes: Mapping[str, Any],
    *,
    ratio: float = 2.0,
    min_bytes: int = 1 << 20,
) -> Finding | None:
    """The ROADMAP reduce-scatter smell as a PURE predicate over a
    gradient-byte account: on an fsdp mesh, gradient bytes riding
    all-reduce ≫ bytes riding reduce-scatter means the partitioner kept
    the gradients replicated through the reduction — the 2× gradient-
    traffic anti-pattern (arxiv 2004.13336).  ``-start`` async forms are
    folded into their base op; ``min_bytes`` keeps toy programs quiet.
    Works identically over the IR census's ``gradient_bytes_by_op`` and
    the obs runtime account (via ``account_gradient_bytes_by_op``)."""
    if int(mesh_axes.get("fsdp", 1) or 1) <= 1:
        return None
    merged: dict[str, int] = {}
    for op, b in gradient_bytes_by_op.items():
        base = op[: -len("-start")] if op.endswith("-start") else op
        merged[base] = merged.get(base, 0) + int(b)
    ar = merged.get("all-reduce", 0)
    rs = merged.get("reduce-scatter", 0)
    if ar < max(int(min_bytes), int(ratio * max(rs, 1))):
        return None
    return Finding(
        severity="warning",
        pass_name="ir",
        code="gradient-all-reduce-not-reduce-scatter",
        message=(
            f"{ar / 1024**2:.1f} MiB of gradient bytes ride all-reduce vs "
            f"{rs / 1024**2:.1f} MiB on reduce-scatter on an fsdp mesh "
            f"(fsdp={mesh_axes.get('fsdp')}) — sharded gradients should "
            "reduce-scatter; an all-reduce keeps them replicated through "
            "the reduction and pays ~2× the gradient traffic"
        ),
        context={
            "all_reduce_gradient_bytes": ar,
            "reduce_scatter_gradient_bytes": rs,
            "ratio_threshold": ratio,
        },
    )


# --------------------------------------------------------------------------
# Computation-level HLO structure (the once-per-step placement pass needs to
# know WHICH loop body an instruction lives in, which the flat parse above
# deliberately ignores).
# --------------------------------------------------------------------------

# `%name (params...) -> result {` — computation header (ENTRY optional).
_COMP_HEAD_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?(?P<name>[\w.\-]+)\s*\(.*\{\s*$")
# references to other computations from inside an instruction line
_CALLED_RE = re.compile(
    r"(?:to_apply|body|condition|calls|branch_computations)="
    r"(?:\{([^}]*)\}|%?([\w.\-]+))"
)
_WHILE_BODY_RE = re.compile(r"\bwhile\(.*?body=%?([\w.\-]+)")
_SOURCE_LINE_RE = re.compile(r'source_file="(?P<file>[^"]+)"\s+source_line=(?P<line>\d+)')


def split_computations(hlo_text: str) -> dict[str, list[str]]:
    """HLO text → {computation name: its instruction lines}."""
    out: dict[str, list[str]] = {}
    current: list[str] | None = None
    for line in hlo_text.splitlines():
        m = _COMP_HEAD_RE.match(line)
        if m is not None and line.rstrip().endswith("{"):
            current = out.setdefault(m.group("name"), [])
            continue
        if line.strip() == "}":
            current = None
            continue
        if current is not None:
            current.append(line)
    return out


def _called_names(lines: Iterable[str]) -> set[str]:
    names: set[str] = set()
    for line in lines:
        for grouped, single in _CALLED_RE.findall(line):
            if single:
                names.add(single)
            else:
                names.update(n.strip().lstrip("%") for n in grouped.split(",") if n.strip())
    return names


def loop_body_computations(hlo_text: str) -> set[str]:
    """Names of every computation reachable from a ``while`` body — i.e.
    code that executes ONCE PER LOOP ITERATION.  The grad-accumulation
    scan lowers to a while; so do unrelated loops (gather/sort helpers),
    which is fine: the once-per-step contract is that the optimizer tail
    sits inside NO loop at all."""
    comps = split_computations(hlo_text)
    roots: set[str] = set()
    for lines in comps.values():
        for line in lines:
            m = _WHILE_BODY_RE.search(line)
            if m:
                roots.add(m.group(1))
    reachable: set[str] = set()
    frontier = list(roots)
    while frontier:
        name = frontier.pop()
        if name in reachable or name not in comps:
            continue
        reachable.add(name)
        frontier.extend(_called_names(comps[name]))
    return reachable


# Copies below this element count are excluded from the in-place census:
# XLA's layout assignment inserts small transpose-normalization copies
# around fused elementwise ops (observed: 512-element relayouts on tiny
# fallback leaves), which are noise next to the contract's target — a
# param-scale second fp32 buffer.  256 KiB fp32; every 7B-class leaf
# shard sits orders of magnitude above it.
MIN_COPY_CENSUS_ELEMS = 1 << 16


def once_per_step_placement(
    hlo_text: str,
    spans: Iterable[tuple[str, int, int]],
    param_elems: Iterable[int] | None = None,
    *,
    min_copy_elems: int = MIN_COPY_CENSUS_ELEMS,
) -> dict[str, Any]:
    """Census of the optimizer/clip/health block's placement in the
    compiled program, from instruction source metadata.

    ``spans`` is ``train.step.once_per_step_source_spans()`` — the
    ``(file, first_line, last_line)`` ranges of the code that must run
    exactly once per optimizer step.  jax stamps each HLO instruction
    with its originating source line, so counting span-attributed
    instructions inside loop-body computations proves (or refutes) that
    the optimizer apply stayed OUT of the grad-accumulation scan — on
    the real compiled program, regardless of ``grad_accum_steps``.

    ``param_elems`` (full per-leaf element counts of the model's param
    tree) extends the census with the IN-PLACE contract of the fused
    optimizer apply: span-attributed f32 ``copy`` instructions whose
    element count matches a parameter leaf are counted as
    ``fp32_param_copies`` — a fused apply that genuinely updates in
    place (``input_output_aliases``) shows zero; a copy there means the
    compiler materialized a second fp32 param buffer the fusion exists
    to avoid.

    Returns ``{"total": N, "in_loop": M, "in_loop_examples": [...]}``
    (plus ``fp32_param_copies``/``fp32_copy_examples`` when
    ``param_elems`` is given); a healthy step has ``total > 0`` (the
    block exists) and ``in_loop == 0`` (none of it slid into a loop
    body)."""
    span_list = [(str(f), int(a), int(b)) for f, a, b in spans]
    elem_set = {int(e) for e in param_elems} if param_elems is not None else None

    def in_spans(fname: str, line: int) -> bool:
        return any(fname.endswith(f) or f.endswith(fname) or fname == f for f, a, b in span_list if a <= line <= b)

    comps = split_computations(hlo_text)
    loop_comps = loop_body_computations(hlo_text)
    total = 0
    in_loop = 0
    examples: list[str] = []
    copies = 0
    copy_examples: list[str] = []
    for cname, lines in comps.items():
        for line in lines:
            m = _SOURCE_LINE_RE.search(line)
            if not m or not in_spans(m.group("file"), int(m.group("line"))):
                continue
            total += 1
            d = _DEF_RE.match(line)
            if cname in loop_comps:
                in_loop += 1
                if len(examples) < 8:
                    examples.append(
                        f"{cname}:%{d.group('name')}" if d else cname
                    )
            if elem_set is None:
                continue
            # sync `copy` parses via _DEF_RE; the async `copy-start` form
            # defines a TUPLE shape only _TUPLE_DEF_RE can read (largest
            # element = the copied buffer)
            name = op = dtype = None
            elems = 0
            if d is not None:
                name, op = d.group("name"), d.group("op")
                dtype, elems = d.group("dtype"), _elems_of(d.group("dims"))
            else:
                t = _TUPLE_DEF_RE.match(line)
                if t is not None and t.group("op") == "copy-start":
                    name, op = t.group("name"), "copy-start"
                    pairs = _TUPLE_ELEM_RE.findall(t.group("elems"))
                    if pairs:
                        dtype, dims = max(pairs, key=lambda e: _bytes_of(*e))
                        elems = _elems_of(dims)
            if (
                op in ("copy", "copy-start")
                and dtype == "f32"
                and elems >= min_copy_elems
                and elems in elem_set
            ):
                copies += 1
                if len(copy_examples) < 8:
                    copy_examples.append(f"{cname}:%{name}")
    out: dict[str, Any] = {
        "total": total, "in_loop": in_loop, "in_loop_examples": examples,
    }
    if elem_set is not None:
        out["fp32_param_copies"] = copies
        out["fp32_copy_examples"] = copy_examples
    return out


def in_place_apply_finding(
    hlo_text: str,
    spans: Iterable[tuple[str, int, int]],
    param_elems: Iterable[int],
    *,
    min_copy_elems: int = MIN_COPY_CENSUS_ELEMS,
) -> Finding | None:
    """The fused-apply in-place contract as a finding: warning when any
    span-attributed f32 param-sized ``copy`` survived in the compiled
    program — the buffer aliasing the fused kernel declares
    (``input_output_aliases``) should leave none.  ``param_elems`` is
    matched against the PER-DEVICE program's buffer sizes: multi-device
    callers must pass ``model_tree_element_candidates(full_counts,
    mesh_size)`` (as ``lint_train_step`` does), or sharded leaves'
    copies are invisible.  A warning, not an error: XLA legitimately
    inserts copies around donation on some backends, and a copy costs
    bandwidth, not correctness.  Copies under ``min_copy_elems`` are
    ignored — layout-normalization relayouts of small leaves are not the
    bandwidth the contract protects."""
    census = once_per_step_placement(
        hlo_text, spans, param_elems, min_copy_elems=min_copy_elems
    )
    if not census.get("fp32_param_copies"):
        return None
    return Finding(
        severity="warning",
        pass_name="ir",
        code="optimizer-param-copy",
        message=(
            f"{census['fp32_param_copies']} f32 param-sized copy "
            f"instruction(s) in the optimizer-apply span (e.g. "
            f"{census['fp32_copy_examples'][:3]}) — the fused apply "
            "declares in-place aliasing precisely so no second fp32 "
            "param buffer is materialized per step"
        ),
        context=census,
    )


def once_per_step_finding(
    hlo_text: str, spans: Iterable[tuple[str, int, int]]
) -> Finding | None:
    """The placement census as a finding: error when any optimizer-block
    instruction landed inside a loop body (it would re-run every
    microbatch — the non-layer overhead grad accumulation exists to
    amortize), or when NO instruction carries the block's source spans
    (the metadata went missing and the census proves nothing)."""
    census = once_per_step_placement(hlo_text, spans)
    if census["in_loop"]:
        return Finding(
            severity="error",
            pass_name="ir",
            code="optimizer-in-scan-body",
            message=(
                f"{census['in_loop']} optimizer/health instruction(s) were "
                "scheduled inside a loop body (e.g. "
                f"{census['in_loop_examples'][:3]}) — clip/AdamW/health must "
                "run once per optimizer step, after the grad-accumulation "
                "scan, not once per microbatch"
            ),
            context=census,
        )
    if census["total"] == 0:
        return Finding(
            severity="warning",
            pass_name="ir",
            code="optimizer-census-empty",
            message=(
                "no instruction carries the optimizer-apply-block source "
                "spans — source metadata is missing from this HLO text, so "
                "the once-per-step placement cannot be proven"
            ),
            context=census,
        )
    return None


def collective_permute_chain_depth(instrs: Mapping[str, HloInstr]) -> int:
    """Longest dependency chain of collective-permutes in the parsed
    instruction graph: the number of permutes on the longest operand path
    ending at (and including) each permute.  Data moved around a
    pipeline's stage ring needs at most one hop per ring edge; a chain
    longer than the ring means some tensor was permuted around more than
    once — a resharded pipeline hop."""
    permute_ops = ("collective-permute", "collective-permute-start")
    depth: dict[str, int] = {}

    # iterative post-order: a real compiled step's operand chains run far
    # past Python's recursion limit (one frame per instruction would
    # RecursionError on any 7B program), so expand-then-combine on an
    # explicit stack.  ``on_path`` guards cycles (HLO is a DAG, but a
    # malformed text must not hang the lint): a back-edge operand scores 0.
    for root in instrs:
        if root in depth:
            continue
        stack: list[tuple[str, bool]] = [(root, False)]
        on_path: set[str] = set()
        while stack:
            name, expanded = stack.pop()
            if expanded:
                on_path.discard(name)
                instr = instrs[name]
                child = max((depth.get(o, 0) for o in instr.operands), default=0)
                depth[name] = child + (1 if instr.op in permute_ops else 0)
                continue
            if name in depth or name not in instrs or name in on_path:
                continue
            on_path.add(name)
            stack.append((name, True))
            for o in instrs[name].operands:
                if o not in depth and o in instrs and o not in on_path:
                    stack.append((o, False))
    return max(depth.values(), default=0)


def ppermute_chain_smell(
    instrs: Mapping[str, HloInstr], mesh_axes: Mapping[str, int]
) -> Finding | None:
    """The ROADMAP smell: a collective-permute chain longer than the
    stage ring.  A pipeline with S stages moves activations/gradients at
    most S hops around the ring per pass; a longer chain means a tensor
    was resharded through extra permute hops (usually a spec mismatch
    between stages making GSPMD route data the long way around).

    Gated to meshes where the stage ring is the ONLY permute ring: with
    sequence/context parallelism in play, ring attention and halo
    exchanges legitimately chain one permute per layer (depth ≫ stage)
    and HLO text does not say which axis a permute's pairs ride — the
    stage-ring bound would fire on every deep network."""
    stage = int(mesh_axes.get("stage", 1) or 1)
    if stage <= 1:
        return None
    if int(mesh_axes.get("sequence", 1) or 1) > 1:
        return None
    if not any(
        i.op in ("collective-permute", "collective-permute-start")
        for i in instrs.values()
    ):
        return None
    longest = collective_permute_chain_depth(instrs)
    if longest <= stage:
        return None
    return Finding(
        severity="warning",
        pass_name="ir",
        code="ppermute-chain-exceeds-stage-ring",
        message=(
            f"a collective-permute dependency chain of length {longest} "
            f"exceeds the stage ring (stage={stage}) — data is being "
            "permuted around the pipeline more than one full pass, i.e. a "
            "resharded pipeline hop (a spec mismatch between stages makes "
            "GSPMD route tensors the long way around the ring)"
        ),
        context={"chain_length": longest, "stage": stage},
    )


def prefill_in_decode_smell(
    instrs: Mapping[str, HloInstr],
    *,
    enc_len: int,
    batch: int,
    heads: int,
    q_len: int = 1,
    margin: float = 2.0,
) -> Finding | None:
    """The serving twin of the once-per-step census: error when the
    compiled DECODE-STEP program contains encoder/prefill-sized matmuls.

    Contract: prefill runs the encoder and projects cross-attention K/V
    exactly ONCE per sequence (``cross_kv``-computed-once); the per-token
    decode step may only read them.  The largest legitimate tensor with an
    ``enc_len`` dimension a decode step PRODUCES in a dot is the
    cross-attention score block — ``batch·heads·q_len·enc_len`` elements.
    A re-projected cross K/V is ``head_dim/q_len`` times that; a re-run
    encoder matmul (d_model/d_ff wide) is orders of magnitude past it.  So
    the predicate is: any ``dot`` whose output shape carries a dim equal
    to ``enc_len`` AND whose element count exceeds ``margin ×`` the score
    bound.  ``enc_len`` is the encoder length (seq2seq) or the cache/mask
    width (causal — a re-run prompt pass shows the same signature).  Pure
    over parsed instructions; ``lint_decode_step`` wires it to the real
    AOT-compiled step."""
    bound = margin * batch * heads * max(q_len, 1) * enc_len
    offenders: list[str] = []
    for name, instr in instrs.items():
        if instr.op != "dot":
            continue
        dims = [int(d) for d in instr.dims.split(",") if d]
        if enc_len in dims and instr.elems > bound:
            offenders.append(name)
    if not offenders:
        return None
    worst = max(offenders, key=lambda n: instrs[n].elems)
    return Finding(
        severity="error",
        pass_name="ir",
        code="prefill-in-decode",
        message=(
            f"{len(offenders)} dot(s) in the compiled decode step produce "
            f"prefill-sized tensors (an {enc_len}-long dim at "
            f"{instrs[worst].elems} elements, e.g. %{worst}) — the decode "
            "step is re-running encoder/prefill compute or re-projecting "
            "cross-attention K/V every token; prefill computes those ONCE "
            "per sequence (the cross_kv contract)"
        ),
        context={
            "count": len(offenders),
            "instructions": offenders[:8],
            "bound_elems": int(bound),
        },
    )


def host_transfer_instructions(instrs: Mapping[str, HloInstr]) -> list[str]:
    """Names of instructions that move data between host and device —
    the ROADMAP "host-transfer ops inside the step body" smell.  Pure
    predicate over parsed instructions (shared by the IR pass and tests):
    infeed/outfeed always; send/recv/copy only when the instruction is
    attributed ``is_host_transfer=true``; host-offloading custom-calls
    (MoveToHost / MoveToDevice / annotate_device_placement)."""
    out: list[str] = []
    for name, instr in instrs.items():
        if instr.op in _HOST_TRANSFER_OPS:
            out.append(name)
        elif instr.op in _HOST_ATTRIBUTED_OPS and "is_host_transfer=true" in instr.line:
            out.append(name)
        elif instr.op == "custom-call" and any(
            t in instr.line for t in _HOST_CUSTOM_CALLS
        ):
            out.append(name)
    return out


def scan_hlo_text(
    hlo_text: str,
    *,
    mesh_axes: Mapping[str, int],
    promotion_smell: tuple[str, str] | None = None,
    largest_param_bytes: int = 0,
    gather_bytes_threshold: int = 16 * 1024**2,
    param_element_counts: Iterable[int] | None = None,
    decode_contract: Mapping[str, int] | None = None,
    grad_compression: str = "",
    kv_cache_dtype: str = "",
) -> list[Finding]:
    """Scan post-optimization HLO text.  Pure function of the text.

    ``param_element_counts`` (full per-leaf element counts of the model's
    parameter tree) additionally splits the collective census byte totals
    into gradient/parameter vs activation traffic.

    ``decode_contract`` marks the text as a SERVING decode step and runs
    ``prefill_in_decode_smell`` over it; keys: ``enc_len``, ``batch``,
    ``heads``, optional ``q_len``/``margin``.  ``kv_cache_dtype``
    ("int8") additionally asserts the program carries s8 cache operands
    (``int8_kv_missing_finding``)."""
    findings: list[Finding] = []
    instrs = parse_hlo_instructions(hlo_text)
    defs = {n: (i.dtype, i.dims, i.op) for n, i in instrs.items()}
    sizes = {n: i.bytes for n, i in instrs.items()}
    operands = {n: list(i.operands) for n, i in instrs.items()}
    lines = hlo_text.splitlines()

    model_sharded = any(
        mesh_axes.get(a, 1) > 1 for a in ("fsdp", "tensor", "expert", "stage")
    )

    # ---- all-gather size accounting ------------------------------------
    gathers = [
        (name, sizes[name])
        for name, (_, _, op) in defs.items()
        if op in ("all-gather", "all-gather-start")
    ]
    big = [(n, b) for n, b in gathers if b >= gather_bytes_threshold]
    if big and not model_sharded:
        worst = max(big, key=lambda t: t[1])
        findings.append(Finding(
            severity="error",
            pass_name="ir",
            code="full-param-all-gather",
            message=(
                f"{len(big)} all-gather(s) materialize ≥ "
                f"{gather_bytes_threshold / 1024**2:.0f} MiB (largest "
                f"{worst[1] / 1024**2:.1f} MiB at %{worst[0]}) on a mesh with "
                "no model-sharding axes — params should already be "
                "replicated; this is GSPMD resharding churn from a spec "
                "mismatch"
            ),
            context={"count": len(big), "max_bytes": worst[1]},
        ))
    elif largest_param_bytes and gathers:
        mega = [(n, b) for n, b in gathers if b > 2 * largest_param_bytes]
        if mega:
            worst = max(mega, key=lambda t: t[1])
            findings.append(Finding(
                severity="warning",
                pass_name="ir",
                code="fused-mega-all-gather",
                message=(
                    f"an all-gather materializes {worst[1] / 1024**2:.1f} MiB "
                    f"(> 2× the largest single parameter, "
                    f"{largest_param_bytes / 1024**2:.1f} MiB) at %{worst[0]} "
                    "— the fsdp prefetch path gathers one param at a time; a "
                    "fused whole-tree gather brings the replicated-memory "
                    "cliff back"
                ),
                context={"count": len(mega), "max_bytes": worst[1]},
            ))

    # ---- precision policy: convert(from→to) feeding a dot --------------
    if promotion_smell is not None:
        src_dt, dst_dt = promotion_smell
        promoted = {
            name
            for name, (dt, _, op) in defs.items()
            if op == "convert"
            and dt == dst_dt
            and any(defs.get(o, ("",))[0] == src_dt for o in operands[name])
        }
        bad_dots = [
            name
            for name, (_, _, op) in defs.items()
            if op == "dot" and any(o in promoted for o in operands[name])
        ]
        if bad_dots:
            findings.append(Finding(
                severity="warning",
                pass_name="ir",
                code="matmul-precision-promotion",
                message=(
                    f"{len(bad_dots)} dot(s) consume operands promoted "
                    f"{src_dt}→{dst_dt} (e.g. %{bad_dots[0]}) — hot-path "
                    f"matmuls should run in {src_dt} per the precision "
                    f"policy; {dst_dt} is for reductions"
                ),
                context={"count": len(bad_dots), "instructions": bad_dots[:8]},
            ))

    # ---- host transfers inside the step body ---------------------------
    host_xfers = host_transfer_instructions(instrs)
    if host_xfers:
        findings.append(Finding(
            severity="error",
            pass_name="ir",
            code="host-transfer-in-step",
            message=(
                f"{len(host_xfers)} host-transfer op(s) inside the compiled "
                f"train step (e.g. %{host_xfers[0]}) — a host round-trip on "
                "the step body serializes async dispatch every single step; "
                "device→host conversions belong at the log cadence "
                "(the invariant scripts/repo_lint.py rule 4 guards on the "
                "Python side)"
            ),
            context={"count": len(host_xfers), "instructions": host_xfers[:8]},
        ))

    # ---- prefill-sized compute inside a decode step --------------------
    if decode_contract is not None:
        smell = prefill_in_decode_smell(instrs, **decode_contract)
        if smell is not None:
            findings.append(smell)

    # ---- int8 KV cache actually present in the decode step -------------
    kv_missing = int8_kv_missing_finding(instrs, kv_cache_dtype)
    if kv_missing is not None:
        findings.append(kv_missing)

    # ---- collective-permute chains vs the stage ring -------------------
    chain = ppermute_chain_smell(instrs, mesh_axes)
    if chain is not None:
        findings.append(chain)

    # ---- degenerate collectives ----------------------------------------
    degenerate: list[str] = []
    for line in lines:
        m = _DEF_RE.match(line) or _TUPLE_DEF_RE.match(line)
        if not m or m.group("op") not in _COLLECTIVE_OPS:
            continue
        rg = _REPLICA_GROUPS_RE.search(line)
        if rg:
            groups = re.findall(r"\{([^}]*)\}", rg.group(1) if "{" in rg.group(1) else rg.group(0))
            if groups and all(len([x for x in g.split(",") if x.strip()]) <= 1 for g in groups):
                degenerate.append(m.group("name"))
                continue
        st = _SOURCE_TARGET_RE.search(line)
        if st:
            pairs = re.findall(r"\{(\d+),\s*(\d+)\}", st.group(0))
            if pairs and all(a == b for a, b in pairs):
                degenerate.append(m.group("name"))
    if degenerate:
        findings.append(Finding(
            severity="warning",
            pass_name="ir",
            code="degenerate-collective",
            message=(
                f"{len(degenerate)} collective(s) have singleton replica "
                f"groups / self-loop permutes (e.g. %{degenerate[0]}) — "
                "communication over an axis of size 1; usually a spec names "
                "an axis the mesh does not actually split"
            ),
            context={"count": len(degenerate), "instructions": degenerate[:8]},
        ))

    # ---- census ---------------------------------------------------------
    census: dict[str, int] = {}
    bytes_by_op: dict[str, int] = {}
    for name, (_, _, op) in defs.items():
        if op in _COLLECTIVE_OPS:
            census[op] = census.get(op, 0) + 1
            bytes_by_op[op] = bytes_by_op.get(op, 0) + sizes[name]
    context: dict[str, Any] = {"census": census, "bytes_by_op": bytes_by_op}
    if param_element_counts is not None:
        mesh_size = 1
        for v in mesh_axes.values():
            mesh_size *= max(1, int(v))
        candidates = model_tree_element_candidates(param_element_counts, mesh_size)
        grad_bytes: dict[str, int] = {}
        for name, instr in instrs.items():
            if instr.op not in _COLLECTIVE_OPS:
                continue
            touched = {instr.elems} | {
                instrs[o].elems for o in instr.operands if o in instrs
            }
            if touched & candidates:
                grad_bytes[instr.op] = grad_bytes.get(instr.op, 0) + instr.bytes
        context["gradient_bytes_by_op"] = grad_bytes
        # element-width split of the same classification (the int8
        # compression proof) + the direction-weighted wire estimate
        quant_census = quantized_gradient_census(
            instrs, param_element_counts, mesh_axes
        )
        context.update(quant_census)
        missing = int8_compression_missing_finding(quant_census, grad_compression)
        if missing is not None:
            findings.append(missing)
        smell = reduce_scatter_smell(grad_bytes, mesh_axes)
        if smell is not None:
            findings.append(smell)
    findings.append(Finding(
        severity="info",
        pass_name="ir",
        code="collective-census",
        message=(
            "collectives in the compiled step: "
            + (", ".join(f"{k}×{v}" for k, v in sorted(census.items())) or "none")
        ),
        context=context,
    ))
    return findings


def lint_train_step(
    model_name: str,
    *,
    mesh_config: Any = None,
    global_batch: int = 8,
    src_len: int = 1024,
    tgt_len: int = 128,
    dtype: str = "bfloat16",
    remat: bool = False,
    grad_accum_steps: int = 1,
    optim_impl: str = "",
    grad_compression: str = "",
    gather_bytes_threshold: int = 16 * 1024**2,
    collect: "dict[str, str] | None" = None,
    program: str = "train_step",
) -> list[Finding]:
    """AOT-compile the sharded train step from abstract args and scan it.

    ``collect`` (divergence census mode): the post-optimization HLO text
    is stored under ``collect[program]`` so the cross-program collective
    census reads the SAME compile this pass scanned.

    ``optim_impl`` builds the step with that optimizer apply (e.g.
    ``"fused"`` — the Pallas clip+AdamW path); the fused program is
    additionally checked against the IN-PLACE contract
    (``in_place_apply_finding``: no f32 param-sized copies in the
    apply's source spans).

    Needs a real device mesh (the SPMD partitioner inserts the collectives
    this pass looks for at compile time); callers skip the pass when the
    requested mesh exceeds the attached device count.
    """
    import jax

    from distributed_llms_example_tpu.core.config import MeshConfig
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.core.precision import Policy, parse_dtype
    from distributed_llms_example_tpu.utils.memory_audit import (
        aot_compile_train_step,
    )

    mesh = build_mesh(mesh_config or MeshConfig())
    # the ONE abstract-compile recipe, shared with the memory audit so the
    # program linted here is the program audited there
    compiled, lm, a_params, _, _ = aot_compile_train_step(
        model_name, mesh,
        global_batch=global_batch, src_len=src_len, tgt_len=tgt_len,
        dtype=dtype, remat=remat, grad_accum_steps=grad_accum_steps,
        optim_impl=optim_impl, grad_compression=grad_compression,
    )
    text = compiled.as_text()
    if collect is not None:
        collect[program] = text
    leaves = jax.tree.leaves(a_params)
    largest_param = max(
        (int(math.prod(x.shape)) * x.dtype.itemsize for x in leaves),
        default=0,
    )
    policy = Policy(compute_dtype=parse_dtype(dtype))
    findings = scan_hlo_text(
        text,
        mesh_axes=dict(mesh.shape),
        promotion_smell=policy.matmul_promotion_smell(),
        largest_param_bytes=largest_param,
        gather_bytes_threshold=gather_bytes_threshold,
        param_element_counts=[int(math.prod(x.shape)) for x in leaves],
        grad_compression=grad_compression,
    )
    if grad_accum_steps > 1 or optim_impl:
        from distributed_llms_example_tpu.train.step import (
            once_per_step_source_spans,
        )

        spans = once_per_step_source_spans()
        if grad_accum_steps > 1:
            # grad accumulation adds its own compiled-program contract:
            # the clip/AdamW/health tail must sit OUTSIDE the microbatch
            # scan
            placement = once_per_step_finding(text, spans)
            if placement is not None:
                findings.append(placement)
        from distributed_llms_example_tpu.ops.fused_optim import resolve_impl

        if optim_impl and resolve_impl(optim_impl) == "fused":
            # the fused apply's IN-PLACE contract: no f32 param-sized
            # copy instruction in the apply's source spans (the xla path
            # is not held to it — XLA legitimately copies around its
            # unaliased buffers there).  The compiled text is the
            # PER-DEVICE program, so a sharded leaf's buffers carry
            # shard-sized element counts — expand the full counts with
            # the same full-plus-even-shard candidate set the traffic
            # classifier uses, or sharded-leaf copies are invisible on
            # any multi-device mesh
            mesh_size = 1
            for v in dict(mesh.shape).values():
                mesh_size *= max(1, int(v))
            inplace = in_place_apply_finding(
                text, spans,
                model_tree_element_candidates(
                    [int(math.prod(x.shape)) for x in leaves], mesh_size
                ),
            )
            if inplace is not None:
                findings.append(inplace)
    return findings


def decode_heads(config: Any) -> int:
    """Decoder attention head count across the model families' config
    spellings (bart/t5/llama) — the heads term of the decode contract."""
    for attr in ("decoder_attention_heads", "num_heads", "num_attention_heads"):
        n = getattr(config, attr, None)
        if n:
            return int(n)
    return 1


def lint_decode_step(
    model_name: str,
    *,
    mesh_config: Any = None,
    slots: int = 8,
    src_len: int = 64,
    max_new_tokens: int = 16,
    dtype: str = "float32",
    kv_cache_dtype: str = "",
    collect: "dict[str, str] | None" = None,
    program: str = "decode",
    prefill_program: str = "",
) -> list[Finding]:
    """AOT-compile the SERVING decode step (the per-token program of the
    prefill/decode split, evaluation/generation.py) from abstract args and
    scan it: ``prefill_in_decode_smell`` (no encoder recompute, no
    per-step cross-KV re-projection) plus host transfers and the
    collective census.  The prefill carry is ``eval_shape``-derived — no
    weights, same recipe as ``lint_train_step``.  ``src_len`` is the
    admission width, so callers loop it over every ``--prefill-buckets``
    entry to prove each bucket's decode step clean.  ``kv_cache_dtype``
    "int8" builds the step under ``kv_cache_context`` and additionally
    requires s8 cache operands in the compiled text
    (``int8_kv_missing_finding``)."""
    import jax

    from distributed_llms_example_tpu.core.config import MeshConfig
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.core.precision import parse_dtype
    from distributed_llms_example_tpu.evaluation.generation import (
        CausalGenerator,
        Seq2SeqGenerator,
    )
    from distributed_llms_example_tpu.models.registry import load_model
    from distributed_llms_example_tpu.parallel.activation import (
        activation_mesh,
        kv_cache_context,
    )

    mesh = build_mesh(mesh_config or MeshConfig())
    lm = load_model(model_name, load_weights=False, dtype=parse_dtype(dtype))
    a_params = jax.eval_shape(lambda: lm.init_params(0))
    cls = Seq2SeqGenerator if lm.is_seq2seq else CausalGenerator
    gen = cls(lm.module, lm.config, max_new_tokens, num_beams=1)
    ids = jax.ShapeDtypeStruct((slots, src_len), jnp_int32())
    mask = jax.ShapeDtypeStruct((slots, src_len), jnp_int32())
    with activation_mesh(mesh), kv_cache_context(kv_cache_dtype or "f32"):
        a_carry = jax.eval_shape(gen.prefill, a_params, ids, mask)
        compiled = jax.jit(gen.decode_step).lower(a_params, a_carry).compile()
        if collect is not None and prefill_program:
            # census mode also wants the PREFILL program's signature —
            # compiled from the same abstract args, the other half of the
            # prefill/decode pair the census cross-checks
            collect[prefill_program] = (
                jax.jit(gen.prefill).lower(a_params, ids, mask)
                .compile().as_text()
            )
    text = compiled.as_text()
    if collect is not None:
        collect[program] = text
    # causal decode attends the full prompt+generation cache width; a
    # re-run prompt pass shows up at the same width
    enc_len = src_len if lm.is_seq2seq else src_len + max_new_tokens
    return scan_hlo_text(
        text,
        mesh_axes=dict(mesh.shape),
        decode_contract={
            "enc_len": enc_len,
            "batch": slots,
            "heads": decode_heads(lm.config),
            "q_len": 1,
        },
        kv_cache_dtype=kv_cache_dtype,
    )


def jnp_int32():
    import jax.numpy as jnp

    return jnp.int32


def skipped(reason: str) -> list[Finding]:
    return [Finding(
        severity="info",
        pass_name="ir",
        code="ir-pass-skipped",
        message=f"lowered-program lint skipped: {reason}",
    )]


# --------------------------------------------------------------------------
# Layer 2 of the pod-agreement static analysis: the cross-program
# collective-matching census.  Every AOT-compiled program in the lint set
# (train step across accum/compression variants, prefill, decode, the
# reshard-restore target) is reduced to its ORDERED collective signature —
# (op kind, replica_groups, channel id, operand bytes) in program text
# order — and the census errors on nondeterministic ordering (two compiles
# of the same program disagree) or on paired programs whose worker-group
# factorizations are incompatible (e.g. expert all-to-all groups vs
# --grad-compression worker groups slicing the same devices differently).
# Layer 1 — the host-AST divergence lint — lives in analysis/divergence.py.
# --------------------------------------------------------------------------

_CHANNEL_ID_RE = re.compile(r"channel_id=(\d+)")
# newer XLA also prints the iota form: replica_groups=[4,2]<=[8]
_IOTA_GROUPS_RE = re.compile(
    r"replica_groups=\[(?P<dims>[0-9,]+)\]<=\[(?P<perm>[0-9,()T]+)\]"
)


@dataclasses.dataclass(frozen=True)
class CollectiveSig:
    """One collective in a compiled program's ordered signature."""

    op: str            # base kind ("all-reduce", "reduce-scatter", ...)
    groups: str        # canonical replica_groups text ("" when absent)
    channel_id: int    # -1 when the op carries no channel
    operand_bytes: int  # summed operand buffer bytes (wire payload proxy)


def _canonical_groups(line: str) -> str:
    m = _REPLICA_GROUPS_RE.search(line)
    if m:
        return m.group(1).replace(" ", "")
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        return f"[{m.group('dims')}]<=[{m.group('perm')}]"
    return ""


def collective_signature(
    hlo: "str | Mapping[str, HloInstr]",
) -> tuple[CollectiveSig, ...]:
    """The ordered collective signature of one compiled program.

    Order is post-optimization text order (the scheduler's order — what
    every device executes); ``-done`` halves of async pairs are dropped so
    each collective counts once, at its issue point."""
    instrs = parse_hlo_instructions(hlo) if isinstance(hlo, str) else hlo
    sigs: list[CollectiveSig] = []
    for instr in instrs.values():
        base = base_collective_op(instr.op)
        if base is None or instr.op.split(".", 1)[0].endswith("-done"):
            continue
        ch = _CHANNEL_ID_RE.search(instr.line)
        operand_bytes = sum(
            instrs[o].bytes for o in instr.operands if o in instrs
        ) or instr.bytes
        sigs.append(CollectiveSig(
            op=base,
            groups=_canonical_groups(instr.line),
            channel_id=int(ch.group(1)) if ch else -1,
            operand_bytes=operand_bytes,
        ))
    return tuple(sigs)


def parse_group_partition(groups: str) -> tuple[tuple[int, ...], ...] | None:
    """Explicit replica_groups text → partition of device ids, or None
    for empty/iota/world groups (world groups partition trivially)."""
    if not groups or "<=" in groups:
        return None
    out = []
    for grp in re.findall(r"\{([0-9,\s]*)\}", groups):
        ids = tuple(int(x) for x in grp.split(",") if x.strip())
        if ids:
            out.append(ids)
    return tuple(out) or None


def canonical_partition_text(partition: tuple[tuple[int, ...], ...]) -> str:
    """Order-independent rendering: groups sorted by first member, ids
    sorted within each group — two collectives whose groups enumerate the
    same partition in different order are the SAME factorization."""
    groups = sorted(tuple(sorted(g)) for g in partition)
    return ",".join("{" + ",".join(str(i) for i in g) + "}" for g in groups)


def partitions_compatible(
    p: tuple[tuple[int, ...], ...], q: tuple[tuple[int, ...], ...],
) -> bool:
    """Two worker-group factorizations of the same device set commute iff
    every pairwise intersection has ONE uniform size (mesh-axis-derived
    partitions always do: |p∩q| is 0 or the product of the shared axes).
    A hand-rolled grouping that straddles the other's groups unevenly —
    the expert-a2a-vs-compression-worker hazard — fails this."""
    sizes = {
        len(set(a) & set(b))
        for a in p for b in q
        if set(a) & set(b)
    }
    return len(sizes) <= 1


def signature_order_finding(
    program: str,
    first: tuple[CollectiveSig, ...],
    second: tuple[CollectiveSig, ...],
) -> Finding | None:
    """Two independent compiles of the same program must schedule the same
    collective sequence — rank k's executable is built on rank k from the
    same inputs, so ANY compile-time nondeterminism here is a pod-scale
    mismatched-collective hang waiting for a cache miss."""
    if first == second:
        return None
    diverge = next(
        (i for i, (a, b) in enumerate(zip(first, second)) if a != b),
        min(len(first), len(second)),
    )
    return Finding(
        severity="error",
        pass_name="ir",
        code="nondeterministic-collective-order",
        message=(
            f"{program}: two compiles of the same program disagree on the "
            f"collective sequence (lengths {len(first)} vs {len(second)}, "
            f"first divergence at position {diverge}) — per-rank "
            "compilation would execute mismatched collectives and hang "
            "the pod",
        ),
        context={"program": program, "position": diverge},
    )


def census_findings(
    signatures: Mapping[str, tuple[CollectiveSig, ...]],
    pairs: Iterable[tuple[str, str]] = (),
) -> list[Finding]:
    """The cross-program collective-matching census.

    - per program: an info ``collective-signature`` row (count + op
      histogram + distinct factorizations) — the operator-readable census.
    - within each program: every pair of distinct explicit factorizations
      must be compatible (``partitions_compatible``) — error
      ``collective-group-incompatible``.
    - across each requested pair of programs: the union of their
      factorizations must stay pairwise compatible — error
      ``collective-group-mismatch`` (paired programs run back-to-back
      over the same devices; incompatible worker groupings mean the two
      programs disagree about which ranks move together).
    """
    findings: list[Finding] = []
    facts: dict[str, dict[str, tuple[tuple[int, ...], ...]]] = {}
    for name, sigs in signatures.items():
        ops: dict[str, int] = {}
        for s in sigs:
            ops[s.op] = ops.get(s.op, 0) + 1
        fact: dict[str, tuple[tuple[int, ...], ...]] = {}
        for s in sigs:
            partition = parse_group_partition(s.groups)
            if partition is not None:
                fact[canonical_partition_text(partition)] = partition
        facts[name] = fact
        findings.append(Finding(
            severity="info",
            pass_name="ir",
            code="collective-signature",
            message=(
                f"{name}: {len(sigs)} collective(s) "
                f"[{', '.join(f'{k}x{v}' for k, v in sorted(ops.items()))}]"
                f", {len(fact)} distinct replica-group factorization(s)"
            ),
            context={
                "program": name,
                "collectives": len(sigs),
                "ops": ops,
                "factorizations": sorted(fact),
            },
        ))
        keys = sorted(fact)
        for i, ga in enumerate(keys):
            for gb in keys[i + 1:]:
                if not partitions_compatible(fact[ga], fact[gb]):
                    findings.append(Finding(
                        severity="error",
                        pass_name="ir",
                        code="collective-group-incompatible",
                        message=(
                            f"{name}: replica-group factorizations "
                            f"{ga} and {gb} straddle each other unevenly "
                            "— two collectives in ONE program disagree "
                            "about which ranks move together (the "
                            "expert-all-to-all vs compression-worker "
                            "hazard)"
                        ),
                        context={"program": name, "groups": [ga, gb]},
                    ))
    for a, b in pairs:
        if a not in facts or b not in facts:
            continue
        for ga, pa in sorted(facts[a].items()):
            for gb, pb in sorted(facts[b].items()):
                if not partitions_compatible(pa, pb):
                    findings.append(Finding(
                        severity="error",
                        pass_name="ir",
                        code="collective-group-mismatch",
                        message=(
                            f"{a} and {b}: worker-group factorizations "
                            f"disagree ({ga} vs {gb}) — paired programs "
                            "run over the same devices and must slice "
                            "them compatibly, or the two programs' "
                            "collectives imply different pod groupings"
                        ),
                        context={"programs": [a, b], "groups": [ga, gb]},
                    ))
    return findings
