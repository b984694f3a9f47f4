"""Pass 1 — lint ShardingRules against the mesh and an abstract param tree.

Runs entirely from ShapeDtypeStructs: no weights, no devices, CPU-safe.
Catches the failure classes a typo'd rule produces at scale:

- an axis name not in the mesh (``P("tensro", ...)``) — jax surfaces this
  as an opaque KeyError at device_put time, after minutes of setup;
- the same axis used twice in one spec (undivisible by construction);
- a rule regex that matches no parameter path — the params it meant to
  shard silently fall through to the replicated default;
- a parameter above ``replicated_bytes_threshold`` that ends up fully
  replicated on a mesh that HAS model-sharding axes to offer — the
  "typo'd spec replicates a 7B weight until HBM blows" case;
- spec'd dims the mesh cannot divide (``divisible_spec`` replicates them
  at runtime with one log line; the lint says so up front).
"""

from __future__ import annotations

import math
from typing import Any, Mapping

from distributed_llms_example_tpu.analysis.findings import Finding
from distributed_llms_example_tpu.core.config import AXES

# Replicating anything past this on a model-sharded mesh is flagged as an
# error: 16 MiB is far above every legitimate replicated leaf (norm scales,
# biases, small position tables) and far below any transformer matmul
# weight at 7B scale (a llama-2-7b attention kernel is 64 MiB in fp32).
DEFAULT_REPLICATED_BYTES_THRESHOLD = 16 * 1024**2

# Axes whose purpose is splitting the MODEL (params/optimizer state);
# ``data`` replicates params by design, so a pure-DP mesh never triggers
# the oversized-replicated check.
MODEL_SHARDING_AXES = ("fsdp", "tensor", "expert", "stage")


def _spec_axes(spec) -> list[str]:
    """Flat axis names referenced by a PartitionSpec."""
    out: list[str] = []
    for entry in spec:
        if entry is None:
            continue
        out.extend(entry if isinstance(entry, tuple) else (entry,))
    return out


def _leaf_bytes(leaf: Any) -> int:
    shape = tuple(getattr(leaf, "shape", ()))
    dtype = getattr(leaf, "dtype", None)
    itemsize = getattr(dtype, "itemsize", 4) if dtype is not None else 4
    return int(math.prod(shape)) * itemsize


def lint_accumulator_mirror(params: Any, rules: Any = None) -> list[Finding]:
    """The grad-accumulation layout contract: the in-step fp32 gradient
    accumulators must be sharded EXACTLY like the parameters, leaf for
    leaf (``train/step.py accumulator_shardings`` — the weight-update-
    sharding recipe of arXiv:2004.13336).  This pass feeds the live
    function a tree of the params' resolved PartitionSpecs and errors on
    any leaf it fails to mirror — so an edit that replicates the
    accumulators (a param-sized fp32 copy per device) or re-shards them
    against the carry (a GSPMD reshard per microbatch) fails the lint
    before it ever compiles.  Device-free: specs only, no mesh."""
    import jax.tree_util as jtu

    from distributed_llms_example_tpu.parallel.sharding import _path_str
    from distributed_llms_example_tpu.train.step import accumulator_shardings

    if rules is None:
        from distributed_llms_example_tpu.parallel.sharding import default_rules

        rules = default_rules()

    paths: list[str] = []
    specs: list[Any] = []
    jtu.tree_map_with_path(
        lambda path, x: (
            paths.append(_path_str(path)),
            specs.append(rules.spec_for(_path_str(path), len(getattr(x, "shape", ())))),
        )
        and None,
        params,
    )
    param_spec_tree = jtu.tree_unflatten(jtu.tree_structure(params), specs)
    mirrored = accumulator_shardings(param_spec_tree)
    mirrored_leaves = jtu.tree_leaves(mirrored)
    findings: list[Finding] = []
    if len(mirrored_leaves) != len(specs):
        return [
            Finding(
                severity="error",
                pass_name="spec",
                code="accumulator-tree-mismatch",
                message=(
                    f"accumulator_shardings returned {len(mirrored_leaves)} "
                    f"leaves for a {len(specs)}-leaf param tree — the fp32 "
                    "accumulator tree no longer mirrors the params"
                ),
            )
        ]
    for path, want, got in zip(paths, specs, mirrored_leaves):
        if got != want:
            findings.append(
                Finding(
                    severity="error",
                    pass_name="spec",
                    code="accumulator-spec-mismatch",
                    message=(
                        f"{path}: gradient accumulator spec {got} differs "
                        f"from the param spec {want} — the in-step fp32 "
                        "accumulators must mirror the param shardings "
                        "exactly (anything else replicates a param-sized "
                        "fp32 tree per device, or forces GSPMD to reshard "
                        "every microbatch's gradients against the carry)"
                    ),
                    context={"param": path, "param_spec": str(want), "accum_spec": str(got)},
                )
            )
    return findings


def lint_error_feedback_mirror(params: Any, rules: Any = None) -> list[Finding]:
    """The grad-compression layout contract (``--grad-compression int8``,
    ``ops/quant_collectives.py``): every error-feedback leaf is the
    param's spec with the worker dim prefixed over the replica axes —
    ``P(GRAD_WORKER_AXES, *param_spec)`` — i.e. the inner dims mirror the
    params EXACTLY, leaf for leaf, like the grad-accum carry.  This pass
    feeds the live ``error_feedback_specs`` function the params' resolved
    specs and errors on any leaf whose inner spec drifts from its param's
    (a drifted EF replicates a param-sized fp32 residual per device, or
    forces GSPMD to reshard the residual against the tiled gradients
    every step) or whose worker prefix is not the replica axes (the
    residual would shard over a model axis and stop being per-worker).
    Device-free: specs only, no mesh."""
    import jax.tree_util as jtu

    from distributed_llms_example_tpu.ops.quant_collectives import (
        GRAD_WORKER_AXES,
        error_feedback_specs,
    )
    from distributed_llms_example_tpu.parallel.sharding import _path_str

    if rules is None:
        from distributed_llms_example_tpu.parallel.sharding import default_rules

        rules = default_rules()

    paths: list[str] = []
    specs: list[Any] = []
    jtu.tree_map_with_path(
        lambda path, x: (
            paths.append(_path_str(path)),
            specs.append(rules.spec_for(_path_str(path), len(getattr(x, "shape", ())))),
        )
        and None,
        params,
    )
    param_spec_tree = jtu.tree_unflatten(jtu.tree_structure(params), specs)
    ef_leaves = jtu.tree_leaves(error_feedback_specs(param_spec_tree))
    findings: list[Finding] = []
    if len(ef_leaves) != len(specs):
        return [
            Finding(
                severity="error",
                pass_name="spec",
                code="error-feedback-tree-mismatch",
                message=(
                    f"error_feedback_specs returned {len(ef_leaves)} leaves "
                    f"for a {len(specs)}-leaf param tree — the EF tree no "
                    "longer mirrors the params"
                ),
            )
        ]
    want_prefix = (
        GRAD_WORKER_AXES[0] if len(GRAD_WORKER_AXES) == 1 else GRAD_WORKER_AXES
    )
    for path, pspec, ef in zip(paths, specs, ef_leaves):
        prefix = ef[0] if len(ef) else None
        inner = tuple(ef[1:])
        if prefix != want_prefix or inner != tuple(pspec):
            findings.append(
                Finding(
                    severity="error",
                    pass_name="spec",
                    code="error-feedback-spec-mismatch",
                    message=(
                        f"{path}: error-feedback spec {ef} does not mirror "
                        f"the param spec {pspec} under the "
                        f"{GRAD_WORKER_AXES} worker prefix — the EF tree "
                        "must be the param layout with the worker dim over "
                        "the replica axes (anything else replicates the "
                        "fp32 residual per device or re-shards it against "
                        "the tiled gradients every step)"
                    ),
                    context={
                        "param": path,
                        "param_spec": str(pspec),
                        "ef_spec": str(ef),
                    },
                )
            )
    return findings


def lint_optimizer_moment_mirror(params: Any, rules: Any = None) -> list[Finding]:
    """The fused-optimizer layout contract (``ops/fused_optim.py``): the
    AdamW moments' resolved specs must equal the param specs, leaf for
    leaf.  The moments live in the optax chain state at paths ENDING
    with the param path (``opt_state/1/0/mu/<param path>``), and
    ``state_shardings`` resolves them through the same unanchored
    path-regex rules — so mirroring normally holds by construction.
    This pass errors when it does NOT (an anchored rule, a rule matching
    'mu'/'nu' path segments): the fused apply shard_maps (param, mu, nu,
    grad) with ONE spec per leaf, and a diverging moment spec would
    force GSPMD to reshard the moments against the kernel's layout
    every step.
    Device-free: specs only, no mesh."""
    import jax.tree_util as jtu

    from distributed_llms_example_tpu.parallel.sharding import _path_str

    if rules is None:
        from distributed_llms_example_tpu.parallel.sharding import default_rules

        rules = default_rules()

    findings: list[Finding] = []
    leaves: list[tuple[str, int]] = []
    jtu.tree_map_with_path(
        lambda path, x: leaves.append(
            (_path_str(path), len(getattr(x, "shape", ())))
        ),
        params,
    )
    for path, ndim in leaves:
        want = rules.spec_for(path, ndim)
        for moment in ("mu", "nu"):
            moment_path = f"opt_state/1/0/{moment}/{path}"
            got = rules.spec_for(moment_path, ndim)
            if got != want:
                findings.append(
                    Finding(
                        severity="error",
                        pass_name="spec",
                        code="optimizer-moment-spec-mismatch",
                        message=(
                            f"{moment_path}: adam {moment} resolves to spec "
                            f"{got} but its param resolves to {want} — the "
                            "fused optimizer apply shard_maps (param, mu, "
                            "nu, grad) with ONE spec per leaf; a rule that "
                            "distinguishes the moment path breaks the "
                            "mirror (and costs a GSPMD reshard per step on "
                            "the xla path too)"
                        ),
                        context={
                            "param": path,
                            "param_spec": str(want),
                            "moment_spec": str(got),
                        },
                    )
                )
    return findings


def lint_cache_sharding(
    cache: Any,
    mesh_axes: Mapping[str, int],
    *,
    rules: Any = None,
    replicated_bytes_threshold: int = DEFAULT_REPLICATED_BYTES_THRESHOLD,
) -> list[Finding]:
    """Pass 1 for the SERVING state: the per-layer KV cache is the second
    long-lived sharded tree (params being the first), so its rule set
    (``parallel/sharding.py CACHE_RULES``) gets the same validation —
    unknown axes, duplicate axes, dead rules, ragged dims, and any
    cached_key/cached_value leaf that would end up fully replicated on a
    mesh with batch/tensor capacity (a replicated cache multiplies decode
    HBM by the mesh size, exactly the unsharded-cache failure this
    subsystem exists to close).  ``cache`` is an abstract tree
    (ShapeDtypeStruct leaves) — e.g. ``evaluation.generation
    abstract_cache``."""
    if rules is None:
        from distributed_llms_example_tpu.parallel.sharding import cache_rules

        rules = cache_rules()
    findings = lint_sharding_rules(
        rules, mesh_axes, cache,
        replicated_bytes_threshold=replicated_bytes_threshold,
    )
    # the oversized-replicated check above only fires on rule FALLTHROUGH;
    # for the cache the contract is stronger — every K/V buffer must hit a
    # sharding rule (a cache leaf no rule matches decodes replicated).
    # The int8 KV cache's ``*_scale`` leaves are held to the same bar:
    # an unmatched scale leaf replicates batch×len×heads f32 per device
    # AND desyncs from the s8 buffers it dequantizes (a GSPMD reshard on
    # every decode step).  So is every other leaf that holds state (3-D:
    # (batch, len, heads x head_dim) K/V, the scales, a conv state).
    import jax.tree_util as jtu

    from distributed_llms_example_tpu.parallel.sharding import _path_str

    leaves: list[tuple[str, Any]] = []
    jtu.tree_map_with_path(lambda p, x: leaves.append((_path_str(p), x)), cache)
    for path, leaf in leaves:
        if len(getattr(leaf, "shape", ())) < 3:
            continue
        if rules.match_path(path) is None:
            findings.append(
                Finding(
                    severity="error",
                    pass_name="spec",
                    code="unmatched-cache-leaf",
                    message=(
                        f"cache leaf {path} matches no cache sharding rule — "
                        "it would decode fully replicated (per-device HBM × "
                        "mesh size for the serving state)"
                    ),
                    context={"leaf": path},
                )
            )
    return findings


# Axes a topology change may NOT move when either side uses them (>1):
# ``stage`` because the stacked-block storage layout is a function of the
# stage count (a resized axis silently permutes layers — composition row
# reshard-pipelined), ``expert`` because the MoE program structure
# (expert placement, the a2a groups, capacity math) is built around the
# expert count — restoring an expert>1 checkpoint onto an expert=1 mesh
# used to surface as an opaque restore exception deep in the walk-back.
RESHARD_PINNED_AXES = ("stage", "expert")


def lint_reshard_layout(
    saved_layout: Mapping[str, Any],
    mesh_axes: Mapping[str, int],
    params: Any,
    *,
    rules: Any = None,
) -> list[Finding]:
    """The resharding-restore proof pass (ISSUE 14): cross-check a
    checkpoint's recorded topology — the ``mesh_layout`` payload leaf /
    recovery-sidecar dict, ``{"axes": {axis: size}, "processes": N,
    "ef_workers": W}`` — against an ARBITRARY target mesh.

    Errors are the unmappable factorizations (the restore must fail fast
    and named, not deep in orbax): an axis name the live build does not
    know, or a moved ``stage``/``expert`` axis (see
    ``RESHARD_PINNED_AXES``).  ``data``/``fsdp``/``tensor``/``sequence``
    re-factorizations are exactly what the resharding restore exists
    for — for those the pass instead proves the TARGET layout is
    well-typed: every param leaf's spec resolves on the target mesh
    (ragged dims → warning: they silently replicate), and the
    accumulator / error-feedback mirrors re-derive leaf-for-leaf from
    the target param specs (the arXiv:2004.13336 discipline that makes
    the reshard well-typed in the first place).  The EF worker-count
    transition is reported as info (re-tile) or warning (zero-fill).
    Device-free: specs + shapes only."""
    import jax.tree_util as jtu

    from distributed_llms_example_tpu.parallel.sharding import (
        _clip_spec,
        _path_str,
        divisible_spec,
    )

    if rules is None:
        from distributed_llms_example_tpu.parallel.sharding import default_rules

        rules = default_rules()

    findings: list[Finding] = []
    saved_axes = dict(saved_layout.get("axes", {}) or {})
    for a, size in sorted(saved_axes.items()):
        if a not in AXES:
            findings.append(
                Finding(
                    severity="error",
                    pass_name="spec",
                    code="unknown-saved-axis",
                    message=(
                        f"checkpoint layout names mesh axis {a!r} "
                        f"(size {size}), which this build does not know "
                        f"(axes: {', '.join(AXES)}) — the payload was "
                        "written by an incompatible mesh schema"
                    ),
                    context={"axis": a, "size": int(size)},
                )
            )
    for a in RESHARD_PINNED_AXES:
        old = int(saved_axes.get(a, 1) or 1)
        new = int(mesh_axes.get(a, 1) or 1)
        if old != new and (old > 1 or new > 1):
            findings.append(
                Finding(
                    severity="error",
                    pass_name="spec",
                    code=f"reshard-{a}-mismatch",
                    message=(
                        f"checkpoint was saved with {a}={old} but the "
                        f"target mesh has {a}={new} — the {a} "
                        "factorization is part of the program structure "
                        + (
                            "(stacked-block storage layout is a function "
                            "of the stage count; a resized axis silently "
                            "permutes layers)"
                            if a == "stage"
                            else "(expert placement, all-to-all groups and "
                            "capacity math are built around the expert "
                            "count)"
                        )
                        + "; resume on a slice with the same "
                        f"{a} factorization"
                    ),
                    context={"axis": a, "saved": old, "target": new},
                )
            )

    # target-layout well-typedness: every leaf resolvable, ragged dims
    # named (they replicate at runtime — legal, but the operator should
    # know the reshard costs per-device memory)
    mesh_view = type("_MeshView", (), {"shape": dict(mesh_axes)})()
    leaves: list[tuple[str, Any]] = []
    jtu.tree_map_with_path(
        lambda path, x: leaves.append((_path_str(path), x)), params
    )
    for path, leaf in leaves:
        shape = tuple(getattr(leaf, "shape", ()))
        spec = rules.spec_for(path, len(shape))
        if any(a not in AXES for a in _spec_axes(spec)):
            continue  # a broken rule set is lint_sharding_rules' job
        effective = divisible_spec(spec, shape, mesh_view)
        if effective != _clip_spec(spec, len(shape)):
            findings.append(
                Finding(
                    severity="warning",
                    pass_name="spec",
                    code="reshard-leaf-replicated",
                    message=(
                        f"{path}: shape {shape} resolves to spec {spec} on "
                        f"the target mesh {dict(mesh_axes)} but the ragged "
                        "dims will be replicated — the reshard lands, at a "
                        "per-device memory cost the saving mesh did not pay"
                    ),
                    context={"param": path, "spec": str(spec), "shape": list(shape)},
                )
            )

    # the mirrors that make the reshard well-typed: accumulator and (when
    # the payload carries an EF tree) error-feedback specs re-derived
    # leaf-for-leaf from the TARGET param specs
    findings.extend(lint_accumulator_mirror(params, rules))
    ef_workers = int(saved_layout.get("ef_workers", 0) or 0)
    if ef_workers > 0:
        findings.extend(lint_error_feedback_mirror(params, rules))
        from distributed_llms_example_tpu.ops.quant_collectives import (
            worker_count,
        )

        new_workers = worker_count(dict(mesh_axes))
        if new_workers != ef_workers:
            retile = new_workers > 1 and ef_workers % new_workers == 0
            findings.append(
                Finding(
                    severity="info" if retile else "warning",
                    pass_name="spec",
                    code="reshard-ef-retile" if retile else "reshard-ef-zero-fill",
                    message=(
                        f"error-feedback tree moves from {ef_workers} to "
                        f"{new_workers} worker group(s): "
                        + (
                            "merged groups' residuals sum (total deferred "
                            "error preserved)"
                            if retile
                            else "no residual regrouping preserves the "
                            "per-worker error — it zero-fills (one "
                            "residual's worth of deferred error dropped)"
                        )
                    ),
                    context={"saved_workers": ef_workers, "target_workers": new_workers},
                )
            )
    return findings


def lint_sharding_rules(
    rules: Any,
    mesh_axes: Mapping[str, int],
    params: Any,
    *,
    replicated_bytes_threshold: int = DEFAULT_REPLICATED_BYTES_THRESHOLD,
) -> list[Finding]:
    """Lint ``rules`` (a ShardingRules) against axis sizes and an abstract
    param tree (ShapeDtypeStruct leaves are fine)."""
    from distributed_llms_example_tpu.core.config import unknown_axis_error
    from distributed_llms_example_tpu.parallel.sharding import (
        _clip_spec,
        _path_str,
        divisible_spec,
        rule_match_counts,
    )
    import jax.tree_util as jtu

    findings: list[Finding] = []
    rule_seq = rules.match_rules()

    # --- per-rule checks -------------------------------------------------
    for pattern, spec in rule_seq:
        axes = _spec_axes(spec)
        for a in axes:
            if a not in AXES:
                findings.append(
                    Finding(
                        severity="error",
                        pass_name="spec",
                        code="unknown-mesh-axis",
                        message=f"rule {pattern!r}: {unknown_axis_error(a)}",
                        context={"rule": pattern, "axis": a},
                    )
                )
        dupes = sorted({a for a in axes if axes.count(a) > 1})
        if dupes:
            findings.append(
                Finding(
                    severity="error",
                    pass_name="spec",
                    code="duplicate-spec-axis",
                    message=(
                        f"rule {pattern!r} names mesh axis(es) {dupes} more "
                        "than once in one PartitionSpec — an array dim cannot "
                        "be split twice over the same axis"
                    ),
                    context={"rule": pattern, "axes": dupes},
                )
            )

    # The stock DEFAULT_RULES are a deliberate multi-family union (llama
    # MoE rows are dead on t5, position-table rows dead on llama): dead
    # entries there are design, not typos — info, so `--strict` stays
    # green on every clean default config.  A CUSTOM rule set's dead rule
    # is the typo this check exists for — warning.
    from distributed_llms_example_tpu.parallel.sharding import DEFAULT_RULES

    dead_severity = "info" if rule_seq is DEFAULT_RULES else "warning"
    for (pattern, _), n in zip(rule_seq, rule_match_counts(rules, params)):
        if n == 0:
            findings.append(
                Finding(
                    severity=dead_severity,
                    pass_name="spec",
                    code="dead-rule",
                    message=(
                        f"rule {pattern!r} matched zero parameter paths "
                        "(typo, or shadowed by an earlier rule); anything it "
                        "targeted falls through to the replicated default"
                    ),
                    context={"rule": pattern},
                )
            )

    # --- per-parameter checks -------------------------------------------
    # Capacity = the model-sharding ways this RULE SET can actually use:
    # fsdp/tensor/expert always (the default rules' axes), stage only when
    # a rule names it — on a pure-stage mesh the non-stacked params are
    # replicated by design (the pipeline shards the stacked blocks), not a
    # lint error.
    relevant = {"fsdp", "tensor", "expert"}
    for _, spec in rule_seq:
        relevant.update(a for a in _spec_axes(spec) if a in MODEL_SHARDING_AXES)
    model_capacity = math.prod(max(1, mesh_axes.get(a, 1)) for a in sorted(relevant))
    leaves: list[tuple[str, Any]] = []
    jtu.tree_map_with_path(
        lambda path, x: leaves.append((_path_str(path), x)), params
    )

    # divisible_spec wants a mesh-like object with ``.shape``; give it one
    # so the lint stays device-free
    mesh_view = type("_MeshView", (), {"shape": dict(mesh_axes)})()

    for path, leaf in leaves:
        shape = tuple(getattr(leaf, "shape", ()))
        ndim = len(shape)
        spec = rules.spec_for(path, ndim)
        if any(a not in AXES for a in _spec_axes(spec)):
            continue  # already reported per-rule; divisibility is moot
        effective = divisible_spec(spec, shape, mesh_view)
        if effective != _clip_spec(spec, ndim):
            findings.append(
                Finding(
                    severity="warning",
                    pass_name="spec",
                    code="ragged-dim-replicated",
                    message=(
                        f"{path}: shape {shape} is not divisible by spec "
                        f"{spec} on mesh {dict(mesh_axes)}; the ragged dims "
                        "will be replicated at runtime (per-device memory "
                        "grows by the dropped factor)"
                    ),
                    context={"param": path, "spec": str(spec), "shape": list(shape)},
                )
            )
        sharded_ways = math.prod(
            max(1, mesh_axes.get(a, 1)) for a in _spec_axes(effective)
        )
        nbytes = _leaf_bytes(leaf)
        if (
            sharded_ways == 1
            and model_capacity > 1
            and nbytes > replicated_bytes_threshold
            # only the DEFAULT fallthrough is an error: a matched rule that
            # ends up replicated is either operator intent (an explicit
            # P()) or a ragged fallback the warning above already names
            and rules.match_path(path) is None
        ):
            findings.append(
                Finding(
                    severity="error",
                    pass_name="spec",
                    code="oversized-replicated-param",
                    message=(
                        f"{path} ({nbytes / 1024**2:.1f} MiB) fell through "
                        "to the replicated default (no rule matched) "
                        f"although the mesh offers {model_capacity}-way "
                        "model sharding "
                        f"({', '.join(a for a in sorted(relevant) if mesh_axes.get(a, 1) > 1)}) "
                        "— every device pays the full copy"
                    ),
                    context={"param": path, "bytes": nbytes},
                )
            )
    return findings
