#!/usr/bin/env python
"""AST lint for repo conventions the type system cannot hold.

Seventeen rules, all born from real regressions at TPU scale:

1. **No host syncs in the train-step hot path.**  ``jax.device_get`` /
   ``.block_until_ready()`` inside ``train/step.py`` stall async dispatch —
   one stray sync in the step function serializes every device round-trip
   and the pipelining the whole module exists for is gone.

2. **No bare PartitionSpec literals outside the sharding layer.**  A
   ``P("tensro", ...)`` typo'd in some far-away module bypasses every rule
   check and surfaces as an opaque KeyError inside jax.  Axis-name specs
   belong in ``parallel/`` (the sharding/pipeline layer); the few
   historical exceptions are pinned in an explicit allowlist so NEW ones
   fail review here.

3. **No direct ``print(json.dumps(...))`` metric emission outside the
   sink layer.**  The JSON-lines stdout stream is a parsed platform
   contract (Valohai metadata) with one schema and one process gate —
   a rogue producer bypasses the ``--obs`` sink (its records never reach
   the JSONL file channel), skips ``schema_version`` stamping, and emits
   from every process.  Emission belongs in ``obs/`` and
   ``utils/jsonlog.py``; everyone else calls ``log_json``.

4. **No device→host conversions on step-cadence paths outside the
   log-cadence window.**  ``float(...)`` / ``.item()`` /
   ``jax.device_get`` on a value the step loop produced is a device sync
   — one per step serializes async dispatch, the exact invariant the
   health telemetry is designed around ("values ride the existing
   log-cadence fetch").  The files whose code runs at step cadence are
   enumerated in ``STEP_CADENCE_FILES`` with the functions that ARE the
   cadence window (summary emission, health resolve, recorder dump,
   build-time constructors) allowlisted by name; a conversion anywhere
   else in those files fails here.

5a. **No second gradient-accumulation layer in models/ and train/.**
   ``train/step.py`` owns in-step accumulation (the lax.scan with fp32
   accumulators sharded like the params, ONE optimizer apply per step)
   and the pipeline executors (parallel/) own their schedule-internal
   microbatching.  A manual ``acc += grads`` / ``tree.map(add, acc,
   grads)`` anywhere else in models/ or train/ is a rogue third layer:
   it would double-count against the step's scan, its accumulators would
   carry no sharding contract (a replicated fp32 param-tree per device),
   and the once-per-step optimizer census could no longer prove
   anything.  Flagged: augmented ``+=`` on grad-named values and
   tree-map calls combining an add with grad-named operands, outside
   ``train/step.py``.

5. **No raw dropout primitives in models/ and train/.**  ``nn.Dropout``
   or ``jax.random.bernoulli`` in a model or train file bypasses the
   shared dropout helper (``ops/fused_dropout.py``) — the call site would
   silently miss the fused Pallas path (``--dropout-impl``) and its mask
   would be threefry-generated and HBM-materialized again.  Dropout goes
   through ``ops.fused_dropout.Dropout`` / ``dropout``; raw primitives
   are allowed only inside ``ops/`` (the helper and the attention
   reference path are the implementation).

6. **No bare orbax ``manager.save`` / ``manager.restore`` outside
   ``io/checkpoint.py``.**  The Checkpointer wrappers are where save
   retry-with-backoff, the checksum-manifest sidecar, and
   verify-before-restore-with-fallback live — a direct ``manager.save``
   skips the manifest (its checkpoint can never be verified) and a
   direct ``manager.restore`` trusts a possibly-corrupt highest step
   unconditionally, the exact crash the integrity layer exists to
   prevent.  Everything goes through ``Checkpointer.save`` /
   ``restore_latest`` / ``restore_before``.

7. (Removed with ``obs/trace.py``, PR 31: a timeline is a
   ``--profile-steps`` capture.  The other rules keep their numbers.)

8. **No raw optimizer apply in models/ and train/ outside
   ``train/optim.py``.**  ``optax.apply_updates`` (or a hand-rolled
   ``p - lr*u`` tree-map) anywhere else bypasses the ``--optim-impl``
   dispatch in ``optimizer_apply_block``: the call site would silently
   miss the fused Pallas clip+AdamW path, its update would not ride the
   in-place/aliasing contract the IR census checks, and the fused-vs-xla
   bit-equivalence pin would no longer cover it — the optimizer twin of
   rules 5/5a.  The apply is owned by ``train.optim.optimizer_update``
   (xla impl) and ``fused_optimizer_apply`` (fused impl).

9. **No hand-rolled gradient collectives or gradient quantization in
   models/ and train/ outside ``train/step.py``.**  A raw ``lax.psum`` /
   ``psum_scatter`` / ``all_to_all`` over a gradient tree — or a manual
   ``grads.astype(int8)`` quantize/dequantize — bypasses the
   ``--grad-compression`` dispatch (``ops/quant_collectives.py``): the
   call site would silently miss the error-feedback buffer (its
   quantization error is LOST, not carried), its bytes would not ride
   the int-safe shared-scale wire protocol the census proves, and the
   off-path bit-identity pin would no longer cover it.  The compression
   layer is the one owner; the step (``train/step.py``) is the one
   caller.

10. **No raw int8 casts of KV-cache values outside the owning modules.**
   ``ops/flash_attention.py`` (quantize_kv/dequantize_kv + in-kernel
   dequant) and ``serving/cache_pool.py`` own the int8 KV cache's
   number format.  A stray ``k.astype(jnp.int8)`` in models/, serving/
   or evaluation/ forks the format: its values would quantize without
   the per-head per-position scale contract, the kernel and XLA decode
   paths would stop reconstructing identical K/V, and the token-parity
   pins (engine == static under int8) would no longer cover it.  In
   those dirs (plus ops/mha.py, the cache-write site) ANY
   ``.astype(int8/uint8)`` fails here — creation via ``jnp.zeros(...,
   jnp.int8)`` is allocation, not quantization, and stays legal.

11. **No mesh construction or ``jax.distributed`` lifecycle calls
   outside ``core/mesh.py``.**  Elastic training (ISSUE 14) makes the
   distributed bootstrap a thing that happens MID-RUN: the
   topology-change path shuts the client down and re-initializes it on
   the surviving slice, and the resharding restore assumes every mesh
   in the process came from the one constructor (axis names, ICI-aware
   device order, the gloo-on-CPU flag).  A stray ``Mesh(...)`` or
   ``jax.distributed.initialize/shutdown`` elsewhere forks that
   lifecycle: its mesh would skip topology-aware device ordering, and a
   second initializer would fight the re-init path's teardown ordering.
   ``build_mesh`` / ``initialize_distributed`` /
   ``reinitialize_distributed`` in ``core/mesh.py`` are the owners.

12. **No ad-hoc retry loops — ``time.sleep`` inside an ``except``
   handler — outside the designated backoff helper
   (``utils/backoff.py``).**  A hand-rolled sleep-in-except is a retry
   loop with its own (usually unbounded, uncapped) policy: invisible to
   the shared capped-exponential schedule, no ``*_retry`` event before
   the sleep, and in the serving tier it would block the router's
   single scheduler thread where the tick-unit backoff
   (``backoff_ticks``) is the sanctioned form.  Retry sleeps go through
   ``utils.backoff.sleep_backoff``; any call named ``sleep`` lexically
   inside an except handler elsewhere fails here.

13. **No bare rank conditionals — ``jax.process_index()`` /
   ``process_count()`` inside an ``if``/``while``/ternary/assert test —
   outside the whitelisted owners.**  A branch on raw rank identity is
   the seed of every pod-deadlock bug class this repo has shipped review
   fixes for (the one-rank walk-back, the p0-only verdict, the
   rank-varying retry ladder): the moment the branch reaches a
   collective, ranks disagree about the collective sequence.  The owners
   — ``core/mesh.py`` (bootstrap), ``obs/heartbeat.py`` (the agreement
   channel itself), ``io/checkpoint.py`` (the agreement helpers), and
   ``obs/sink.py`` (the p0 emission gate) — are where rank branching is
   the mechanism; everyone else routes decisions through the agreement
   helpers (``_agreed_ok``/``_agreed_step``/``_agreed_count``/
   ``gather_probe`` — the registry in ``analysis/divergence.py``) or
   annotates the line ``# pod-agreed: <mechanism>`` naming why the
   branch is pod-uniform (e.g. ``process_count() == 1`` fast paths: the
   count is the same number everywhere).  The taint-tracking twin of
   this lexical rule is the divergence pass (``analysis/divergence.py``),
   which follows rank-local values into collectives across assignments.

14. **No inline percentile/quantile computation outside ``obs/spans.py``.**
   The repo has ONE quantile definition — ``obs.spans.percentiles``
   (nearest-rank over sorted values) — and every tail-latency gate
   (ttft_p99, queue_delay_p99, the loadgen SLO curves) compares numbers
   produced by it.  A stray ``np.percentile(..., 99)`` (linear
   interpolation by default) or a hand-rolled ``sorted(xs)[int(0.99 *
   len(xs))]`` (off-by-one at the rank boundary) silently disagrees with
   the owner on small samples — exactly where serving p99s live — so
   two reports of the same run would gate differently.  Flagged: calls
   named ``percentile``/``quantile``/``nanpercentile``/``nanquantile``
   in any spelling, and subscripts of a ``sorted(...)`` result whose
   index arithmetic involves ``len``/a multiplication (the sorted-index
   idiom).  Everyone imports ``percentiles`` from the owner.

15. **No raw ``memory_stats()`` / ``live_buffers()`` reads outside the
   memory owners.**  ``obs/memprof.py`` (runtime watermarks, OOM
   forensics) and ``utils/memory_audit.py`` (the static audit CLI) own
   every HBM byte count.  A stray ``d.memory_stats()`` elsewhere forks
   the account the report gates on: its reading skips the
   absent-beats-zero contract (CPU PJRT returns nothing — a raw read
   happily stamps 0), its "peak" is the process-lifetime allocator
   high-water mark with no ``Watermark`` mark/delta semantics (every
   per-phase claim built on it is silently cumulative), and its numbers
   never reach the ``memory_window`` events the "Where did the bytes
   go" report renders.  Readers call ``memprof.hbm_stats()`` /
   ``Watermark`` — one read path, one semantics.

16. **No KV-block identity outside ``serving/cache_pool.py``.**  The
   chained content hash and the refcount ledger ARE the correctness
   argument for cross-request block sharing: a second hash definition
   in serving/ forks the identity (two prefixes collide, or identical
   prefixes stop matching), and a refcount poked from outside the
   owner breaks the refcount == live-references invariant its own
   ``ref_invariant_violations()`` audits.  Everyone else uses the
   public API: chain_hashes / match_chain / acquire / register / free
   / drop_warm.

17. **No speculative-decode acceptance math outside ``serving/spec.py``
   (+ the cache_pool span scatter).**  The acceptance rule IS the
   bit-identity contract — accept the longest draft == target-argmax
   prefix, emit the target's bonus token, rebuild the mask span.  An
   inline draft-vs-target compare or cumprod prefix fold in the engine
   or router is a second copy of that contract; the copies drift, and
   "spec output == greedy output" stops being one provable property.

Run: ``python scripts/repo_lint.py`` (nonzero exit on violations).  Wired
into the fast test suite (tests/test_analysis.py, tests/test_obs.py,
tests/test_health.py) next to the analysis-CLI smoke run.
"""

from __future__ import annotations

import ast
import os
import sys

PACKAGE = "distributed_llms_example_tpu"

# Files where .block_until_ready / jax.device_get would poison the async
# dispatch pipeline.
HOT_PATH_FILES = (
    os.path.join(PACKAGE, "train", "step.py"),
)

# Directories whose job IS axis-name specs.
SPEC_LAYER_DIRS = (
    os.path.join(PACKAGE, "parallel"),
)

# Pinned exceptions: (file, why).  Add here only with a comment-worthy
# reason — the point is that new bare specs fail loudly.
SPEC_LITERAL_ALLOWLIST = {
    # micro-batch sharding constraint for the grad-accum scan; the axis
    # tuple mirrors batch_sharding() and changing either means both
    os.path.join(PACKAGE, "train", "step.py"),
    # the MoE dispatch spec is part of the expert-parallel kernel contract
    os.path.join(PACKAGE, "ops", "moe.py"),
}

FORBIDDEN_SYNC_ATTRS = ("block_until_ready",)
FORBIDDEN_SYNC_CALLS = (("jax", "device_get"),)

# The sink layer: the only places allowed to print JSON lines directly.
JSON_EMIT_ALLOW_DIRS = (
    os.path.join(PACKAGE, "obs"),
)
JSON_EMIT_ALLOW_FILES = {
    os.path.join(PACKAGE, "utils", "jsonlog.py"),
}

# Files whose code runs at STEP cadence: device→host conversions
# (float(), .item(), jax.device_get) are forbidden outside the named
# functions — which are exactly the log-cadence window (summary/health
# resolve, dump paths) and build-time constructors.  Guards the
# zero-extra-syncs invariant the in-graph health telemetry depends on.
STEP_CADENCE_FILES: dict[str, frozenset] = {
    # the step function itself is all device-side; make_loss_fn/
    # make_train_step run once at build time (config floats)
    os.path.join(PACKAGE, "train", "step.py"): frozenset(
        {"make_loss_fn", "make_train_step"}
    ),
    # span() / step_complete() are per-step; summary() IS the cadence
    os.path.join(PACKAGE, "obs", "spans.py"): frozenset(
        {"__init__", "summary", "percentiles"}
    ),
    # record() is per-step; annotate()/dump() run at cadence / shutdown
    os.path.join(PACKAGE, "obs", "recorder.py"): frozenset(
        {"annotate", "dump", "_to_jsonable", "batch_fingerprint"}
    ),
    # the watchdog's one device_get lives in to_host (cadence only)
    os.path.join(PACKAGE, "obs", "health.py"): frozenset(
        {"__init__", "to_host", "_check_one", "_absorb", "check", "agree_and_emit"}
    ),
    # on_step appends pointers; everything that converts is cadenced
    os.path.join(PACKAGE, "obs", "__init__.py"): frozenset(
        {"__init__", "_health_cadence", "emit_window", "window_mfu",
         "startup_gauges", "finalize"}
    ),
}
CADENCE_SYNC_CALLS = (("jax", "device_get"),)

# Rule 5: directories whose dropout must route through the shared helper
# (ops/fused_dropout.py).  ops/ itself is the implementation layer and
# parallel/ hosts the pipeline shim that delegates to the helper.
DROPOUT_RULE_DIRS = (
    os.path.join(PACKAGE, "models"),
    os.path.join(PACKAGE, "train"),
)

# Rule 5a: gradient accumulation is owned by train/step.py (the in-step
# scan) and the pipeline executors (parallel/); a manual accumulator
# anywhere else in these dirs is a rogue second accumulation layer.
GRAD_ACCUM_RULE_DIRS = DROPOUT_RULE_DIRS
GRAD_ACCUM_OWNER = os.path.join(PACKAGE, "train", "step.py")
_GRAD_NAMES = ("grad", "grads", "gradient")

# Rule 6: checkpoint save/restore is owned by io/checkpoint.py — its
# wrappers carry the retry/backoff, checksum manifest, and
# verify-with-fallback contracts a bare manager call would skip.
CKPT_OWNER = os.path.join(PACKAGE, "io", "checkpoint.py")
_MANAGER_NAMES = ("manager", "_manager", "checkpoint_manager", "ckpt_manager")

# rule 11: the ONE owner of mesh construction and the jax.distributed
# lifecycle (init/shutdown/reinit) — the elastic-recovery path re-enters
# both mid-run, so a second constructor/initializer elsewhere would fork
# the teardown ordering and the device-order contract
MESH_OWNER = os.path.join(PACKAGE, "core", "mesh.py")

# Rule 9: gradient collectives / quantization are owned by
# ops/quant_collectives.py, called only from train/step.py — a raw
# psum/psum_scatter/all_to_all (or int8 cast) over grad-named values
# anywhere else in models/ and train/ bypasses the --grad-compression
# dispatch and its error-feedback contract.
GRAD_COLLECTIVE_RULE_DIRS = DROPOUT_RULE_DIRS
GRAD_COLLECTIVE_OWNER = os.path.join(PACKAGE, "train", "step.py")
_GRAD_COLLECTIVE_FNS = ("psum", "psum_scatter", "pmean", "all_to_all")

# Rule 8: the optimizer apply is owned by train/optim.py — raw
# optax.apply_updates / manual p - lr*u tree-maps elsewhere in models/
# and train/ bypass the --optim-impl dispatch (fused Pallas apply,
# in-place contract, bit-equivalence pin).
OPTIM_RULE_DIRS = DROPOUT_RULE_DIRS
OPTIM_OWNER = os.path.join(PACKAGE, "train", "optim.py")
_LR_NAMES = ("lr", "learning_rate", "step_size")

# Rule 10: the int8 KV cache's number format is owned by
# ops/flash_attention.py (quantize_kv / dequantize_kv / in-kernel tile
# dequant) and serving/cache_pool.py.  Any raw astype-to-int8 in the
# dirs that touch cache values forks the format outside the scale
# contract; jnp.zeros(..., jnp.int8) allocation stays legal.
KV_CAST_RULE_DIRS = (
    os.path.join(PACKAGE, "models"),
    os.path.join(PACKAGE, "serving"),
    os.path.join(PACKAGE, "evaluation"),
)
KV_CAST_RULE_FILES = {os.path.join(PACKAGE, "ops", "mha.py")}
KV_CAST_OWNERS = {
    os.path.join(PACKAGE, "ops", "flash_attention.py"),
    os.path.join(PACKAGE, "serving", "cache_pool.py"),
}

# Rule 12: retry sleeps are owned by utils/backoff.py (capped
# exponential schedule, one definition); a sleep inside an except
# handler anywhere else is an ad-hoc retry loop.
BACKOFF_OWNER = os.path.join(PACKAGE, "utils", "backoff.py")

# Rule 13: bare rank conditionals live only where rank branching IS the
# mechanism — the bootstrap, the agreement channel, the agreement
# helpers, and the p0 emission gate.  Everyone else goes through the
# agreement helpers or carries a `# pod-agreed: <mechanism>` pragma.
RANK_CONDITIONAL_OWNERS = {
    os.path.join(PACKAGE, "core", "mesh.py"),
    os.path.join(PACKAGE, "obs", "heartbeat.py"),
    os.path.join(PACKAGE, "io", "checkpoint.py"),
    os.path.join(PACKAGE, "obs", "sink.py"),
}
_RANK_CALLS = ("process_index", "process_count")
_POD_AGREED_PRAGMA = "# pod-agreed:"

# Rule 14: the quantile definition is owned by obs/spans.py
# (`percentiles`, nearest-rank) — every tail-latency gate compares its
# numbers, so a second definition (np.percentile's interpolation, a
# sorted-index one-liner) disagrees exactly on the small samples where
# serving p99s live.
PERCENTILE_OWNER = os.path.join(PACKAGE, "obs", "spans.py")
_PERCENTILE_FNS = ("percentile", "quantile", "nanpercentile", "nanquantile")

# Rule 15: HBM byte counts have two owners — the runtime side
# (obs/memprof.py: hbm_stats/Watermark/postmortems) and the static audit
# (utils/memory_audit.py).  A raw memory_stats()/live_buffers() read
# anywhere else forks the absent-beats-zero and watermark-delta
# semantics the report's memory gates are built on.
MEMSTATS_OWNERS = {
    os.path.join(PACKAGE, "obs", "memprof.py"),
    os.path.join(PACKAGE, "utils", "memory_audit.py"),
}
_MEMSTATS_FNS = ("memory_stats", "live_buffers")

# Rule 16: KV-block identity is owned by serving/cache_pool.py — the
# chained content hash and the refcount ledger ARE the correctness
# argument for cross-request block sharing.  A second hash definition
# (or a refcount poked from outside the owner) silently breaks the
# "refcount == live references" invariant the pool's own
# ref_invariant_violations() audits, and a divergent hash chain makes
# two different prefixes collide into one block.  Everyone else goes
# through the owner's public API: chain_hashes / match_chain / acquire
# / register / free / drop_warm.
PREFIX_IDENTITY_OWNER = os.path.join(PACKAGE, "serving", "cache_pool.py")
_PREFIX_LEDGER_ATTRS = ("_ref", "_hash_of", "_index", "_lru")
_PREFIX_HASH_MODULE = "hashlib"
PREFIX_HASH_RULE_DIRS = (os.path.join(PACKAGE, "serving"),)

# Rule 17: speculative-decode acceptance/rollback math is owned by
# serving/spec.py (the acceptance rule IS the bit-identity contract:
# accept the longest draft == target-argmax prefix, then the target's
# own bonus token) and serving/cache_pool.py (the span scatter whose
# sentinel discipline keeps speculative writes inside owned blocks).  A
# second acceptance expression inline in the engine or router — a
# draft-vs-target token compare, or the cumprod longest-prefix fold —
# forks the contract: the two copies drift, and "spec output ==
# greedy output" silently stops being one provable property.
SPEC_DECODE_OWNERS = {
    os.path.join(PACKAGE, "serving", "spec.py"),
    PREFIX_IDENTITY_OWNER,
}
SPEC_DECODE_RULE_DIRS = (os.path.join(PACKAGE, "serving"),)
_SPEC_DRAFT_NAMES = ("draft", "drafts", "proposed", "spec_toks")
_SPEC_TARGET_NAMES = ("target", "argmax", "verified")


def _names_contain_lr(node: ast.AST) -> bool:
    return any(
        any(t == name or name.endswith("_" + t) or name.startswith(t + "_")
            for t in _LR_NAMES)
        for name in _names_in(node)
    )


def _optim_apply_violations(tree: ast.AST, rel: str) -> list[str]:
    violations: list[str] = []
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Call)
            and (
                (isinstance(node.func, ast.Attribute) and node.func.attr == "apply_updates")
                or (isinstance(node.func, ast.Name) and node.func.id == "apply_updates")
            )
        ):
            violations.append(
                f"{rel}:{node.lineno}: raw apply_updates(...) outside "
                "train/optim.py bypasses the --optim-impl dispatch (fused "
                "Pallas clip+AdamW, in-place aliasing, bit-equivalence pin) "
                "— route through train.optim.optimizer_update / "
                "optimizer_apply_block"
            )
        elif (
            isinstance(node, ast.Call)
            and (
                (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("map", "tree_map", "tree_multimap")
                )
                or (
                    isinstance(node.func, ast.Name)
                    and node.func.id in ("tree_map", "tree_multimap")
                )
            )
            and node.args
            and isinstance(node.args[0], ast.Lambda)
            and any(
                isinstance(n, ast.BinOp)
                and isinstance(n.op, (ast.Sub, ast.Add))
                and any(
                    isinstance(side, ast.BinOp)
                    and isinstance(side.op, ast.Mult)
                    and _names_contain_lr(side)
                    for side in (n.left, n.right)
                )
                for n in ast.walk(node.args[0].body)
            )
        ):
            violations.append(
                f"{rel}:{node.lineno}: manual 'p - lr*u' tree-map optimizer "
                "apply outside train/optim.py — a hand-rolled update skips "
                "clip/AdamW/health AND the --optim-impl dispatch; use "
                "optimizer_apply_block (train/step.py)"
            )
    return violations


def _is_int8_node(node: ast.AST) -> bool:
    if isinstance(node, ast.Attribute):
        return node.attr in ("int8", "uint8")
    if isinstance(node, ast.Constant):
        return node.value in ("int8", "uint8")
    if isinstance(node, ast.Name):
        return node.id in ("int8", "uint8")
    return False


def _grad_collective_violations(tree: ast.AST, rel: str) -> list[str]:
    violations: list[str] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        name = (
            fn.attr if isinstance(fn, ast.Attribute)
            else fn.id if isinstance(fn, ast.Name)
            else None
        )
        if name in _GRAD_COLLECTIVE_FNS and any(
            _is_grad_named(a) for a in list(node.args) + [
                kw.value for kw in node.keywords
            ]
        ):
            violations.append(
                f"{rel}:{node.lineno}: raw {name}(...) over a gradient "
                "tree outside train/step.py bypasses the "
                "--grad-compression dispatch (ops/quant_collectives.py: "
                "error feedback, shared-scale int8 wire, off-path "
                "bit-identity pin) — the step owns the gradient "
                "reduction"
            )
        elif (
            name == "astype"
            and isinstance(fn, ast.Attribute)
            and _is_grad_named(fn.value)
            and any(
                _is_int8_node(a)
                for a in list(node.args) + [kw.value for kw in node.keywords]
            )
        ):
            violations.append(
                f"{rel}:{node.lineno}: manual int8 cast of a gradient "
                "value outside train/step.py — hand-rolled gradient "
                "quantization loses its error to nowhere (no "
                "error-feedback buffer) and skips the shared-scale "
                "int-safe wire protocol; route through "
                "ops.quant_collectives.quantized_tree_reduce"
            )
    return violations


def _kv_cast_violations(tree: ast.AST, rel: str) -> list[str]:
    violations: list[str] = []
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "astype"
            and any(
                _is_int8_node(a)
                for a in list(node.args) + [kw.value for kw in node.keywords]
            )
        ):
            violations.append(
                f"{rel}:{node.lineno}: raw .astype(int8) outside the KV "
                "quantization owners (ops/flash_attention.py, "
                "serving/cache_pool.py) — a hand-rolled int8 cast of cache "
                "values forks the number format away from the per-head "
                "per-position scale contract and breaks the kernel/XLA "
                "dequant identity; route through "
                "ops.flash_attention.quantize_kv / dequantize_kv"
            )
    return violations


def _retry_sleep_violations(tree: ast.AST, rel: str) -> list[str]:
    """Rule 12: any call named ``sleep`` (``time.sleep``, an aliased
    ``sleep``, a method ``.sleep``) lexically inside an ``except``
    handler, outside utils/backoff.py."""
    violations: list[str] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        for inner in ast.walk(node):
            if not isinstance(inner, ast.Call):
                continue
            fn = inner.func
            name = (
                fn.attr if isinstance(fn, ast.Attribute)
                else fn.id if isinstance(fn, ast.Name)
                else None
            )
            if name == "sleep":
                violations.append(
                    f"{rel}:{inner.lineno}: sleep(...) inside an except "
                    "handler outside utils/backoff.py is an ad-hoc retry "
                    "loop — no capped schedule, no retry event, and it "
                    "would block the serving router's scheduler thread; "
                    "route wall-clock retry waits through "
                    "utils.backoff.sleep_backoff (tick-based paths use "
                    "backoff_ticks)"
                )
    return violations


def _rank_conditional_violations(
    tree: ast.AST, rel: str, src: str,
) -> list[str]:
    """Rule 13: a ``jax.process_index()`` / ``process_count()`` call
    inside the TEST of an ``if``/``while``/ternary/``assert``, outside
    the whitelisted owners, without a ``# pod-agreed:`` pragma on the
    call line or the statement line."""
    pragma_lines = {
        i for i, line in enumerate(src.splitlines(), start=1)
        if _POD_AGREED_PRAGMA in line
    }
    violations: list[str] = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.If, ast.While, ast.IfExp, ast.Assert)):
            test = node.test
        else:
            continue
        for inner in ast.walk(test):
            if not isinstance(inner, ast.Call):
                continue
            fn = inner.func
            name = (
                fn.attr if isinstance(fn, ast.Attribute)
                else fn.id if isinstance(fn, ast.Name)
                else None
            )
            if name not in _RANK_CALLS:
                continue
            if node.lineno in pragma_lines or inner.lineno in pragma_lines:
                continue
            violations.append(
                f"{rel}:{inner.lineno}: bare `{name}()` conditional "
                "outside the rank-branching owners (core/mesh.py, "
                "obs/heartbeat.py, io/checkpoint.py, obs/sink.py) — a "
                "branch on raw rank identity feeding a collective "
                "deadlocks the pod; route the decision through an "
                "agreement helper (_agreed_ok/_agreed_step/_agreed_count/"
                "gather_probe — see analysis/divergence.py SANITIZERS) "
                "or annotate the line `# pod-agreed: <mechanism>` naming "
                "why the branch is pod-uniform"
            )
    return violations


def _percentile_violations(tree: ast.AST, rel: str) -> list[str]:
    """Rule 14: calls named percentile/quantile (any qualifier) and
    sorted-index quantile idioms — ``sorted(xs)[<arith with len/mult>]``
    — outside obs/spans.py."""
    violations: list[str] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            fn = node.func
            name = (
                fn.attr if isinstance(fn, ast.Attribute)
                else fn.id if isinstance(fn, ast.Name)
                else None
            )
            if name in _PERCENTILE_FNS:
                violations.append(
                    f"{rel}:{node.lineno}: {name}(...) outside obs/spans.py "
                    "forks the quantile definition (interpolation vs the "
                    "owner's nearest-rank) — tail-latency gates comparing "
                    "the two disagree on small samples; import "
                    "obs.spans.percentiles"
                )
        elif (
            isinstance(node, ast.Subscript)
            and isinstance(node.value, ast.Call)
            and isinstance(node.value.func, ast.Name)
            and node.value.func.id == "sorted"
            and any(
                (isinstance(n, ast.Call)
                 and isinstance(n.func, ast.Name)
                 and n.func.id == "len")
                or (isinstance(n, ast.BinOp) and isinstance(n.op, ast.Mult))
                for n in ast.walk(node.slice)
            )
        ):
            violations.append(
                f"{rel}:{node.lineno}: sorted(...)[...] rank-index "
                "quantile idiom outside obs/spans.py — hand-rolled rank "
                "math is off-by-one at the boundary vs the owner's "
                "nearest-rank definition; import obs.spans.percentiles"
            )
    return violations


def _memstats_violations(tree: ast.AST, rel: str) -> list[str]:
    """Rule 15: calls named memory_stats/live_buffers (any qualifier)
    outside the memory owners (obs/memprof.py, utils/memory_audit.py)."""
    violations: list[str] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        name = (
            fn.attr if isinstance(fn, ast.Attribute)
            else fn.id if isinstance(fn, ast.Name)
            else None
        )
        if name in _MEMSTATS_FNS:
            violations.append(
                f"{rel}:{node.lineno}: raw {name}(...) outside the memory "
                "owners (obs/memprof.py, utils/memory_audit.py) forks the "
                "HBM account — no absent-beats-zero contract (CPU PJRT "
                "stamps 0), no Watermark mark/delta semantics (per-phase "
                "peaks read as process-lifetime), invisible to the "
                "memory_window events the report gates on; read through "
                "memprof.hbm_stats()/Watermark"
            )
    return violations


def _prefix_identity_violations(tree: ast.AST, rel: str) -> list[str]:
    """Rule 16: the block-identity ledger (``._ref``/``._hash_of``/
    ``._index``/``._lru`` attribute access) anywhere outside the owner,
    and hashlib (import or call) anywhere in serving/ outside the owner
    — a second block-hash computation forks the chained-hash identity
    the pool's dedup is keyed on."""
    violations: list[str] = []
    in_serving = any(
        rel.startswith(d + os.sep) for d in PREFIX_HASH_RULE_DIRS
    )
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Attribute)
            and node.attr in _PREFIX_LEDGER_ATTRS
        ):
            violations.append(
                f"{rel}:{node.lineno}: .{node.attr} access outside "
                "serving/cache_pool.py pokes the block-identity ledger "
                "directly — refcounts mutated outside the owner break the "
                "refcount == live-references invariant "
                "(ref_invariant_violations); go through acquire/register/"
                "free/match_chain/drop_warm"
            )
        elif in_serving and isinstance(node, (ast.Import, ast.ImportFrom)):
            mod = (
                node.module if isinstance(node, ast.ImportFrom)
                else ",".join(a.name for a in node.names)
            )
            if mod and _PREFIX_HASH_MODULE in mod.split(","):
                violations.append(
                    f"{rel}:{node.lineno}: hashlib in serving/ outside "
                    "cache_pool.py — a second block-hash definition forks "
                    "the chained content identity (two prefixes can "
                    "collide, or identical prefixes stop matching); use "
                    "cache_pool.block_hash/chain_hashes"
                )
    return violations


def _spec_decode_violations(tree: ast.AST, rel: str) -> list[str]:
    """Rule 17: speculative acceptance/rollback math in serving/ outside
    its owners — a ``cumprod`` call (the longest-accepted-prefix fold)
    or an Eq compare whose one side is draft-named and other side
    target-named (the acceptance comparison itself)."""
    if not any(rel.startswith(d + os.sep) for d in SPEC_DECODE_RULE_DIRS):
        return []
    violations: list[str] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and (
            (isinstance(node.func, ast.Attribute) and node.func.attr == "cumprod")
            or (isinstance(node.func, ast.Name) and node.func.id == "cumprod")
        ):
            violations.append(
                f"{rel}:{node.lineno}: cumprod in serving/ outside "
                "serving/spec.py — the longest-accepted-prefix fold is "
                "the speculative acceptance rule, owned by "
                "spec.acceptance_lengths; a second copy drifts from the "
                "bit-identity contract"
            )
        elif isinstance(node, ast.Compare) and any(
            isinstance(op, ast.Eq) for op in node.ops
        ):
            sides = [node.left] + list(node.comparators)
            names = [_names_in(s) for s in sides]
            drafty = any(
                any(any(d in n for d in _SPEC_DRAFT_NAMES) for n in ns)
                for ns in names
            )
            targety = any(
                any(any(t in n for t in _SPEC_TARGET_NAMES) for n in ns)
                for ns in names
            )
            if drafty and targety:
                violations.append(
                    f"{rel}:{node.lineno}: draft-vs-target token compare "
                    "in serving/ outside serving/spec.py — inline "
                    "acceptance logic forks the bit-identity contract; "
                    "call spec.acceptance_lengths"
                )
    return violations


def _mesh_ownership_violations(tree: ast.AST, rel: str) -> list[str]:
    """Rule 11: ``Mesh(...)`` construction (``jax.sharding.Mesh`` /
    imported ``Mesh`` — ``AbstractMesh`` and mesh-SHAPED helpers are
    fine) and any ``jax.distributed.*`` call outside core/mesh.py."""
    violations: list[str] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        # Mesh(...) / jax.sharding.Mesh(...) / sharding.Mesh(...)
        name = (
            func.id if isinstance(func, ast.Name)
            else func.attr if isinstance(func, ast.Attribute)
            else None
        )
        if name == "Mesh":
            violations.append(
                f"{rel}:{node.lineno}: raw Mesh(...) construction outside "
                "core/mesh.py skips the topology-aware device ordering and "
                "the elastic-recovery lifecycle — build meshes through "
                "core.mesh.build_mesh"
            )
            continue
        # jax.distributed.initialize/shutdown(...) in any spelling that
        # goes through an attribute chain ending `.distributed.<fn>`
        if (
            isinstance(func, ast.Attribute)
            and isinstance(func.value, ast.Attribute)
            and func.value.attr == "distributed"
        ):
            violations.append(
                f"{rel}:{node.lineno}: jax.distributed.{func.attr}(...) "
                "outside core/mesh.py forks the distributed lifecycle the "
                "topology-change path owns (teardown ordering, rendezvous "
                "facts, the gloo-on-CPU flag) — go through "
                "core.mesh.initialize_distributed / reinitialize_distributed"
            )
    return violations


def _ckpt_manager_violations(tree: ast.AST, rel: str) -> list[str]:
    violations: list[str] = []
    for node in ast.walk(tree):
        if not (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in ("save", "restore")
        ):
            continue
        base = node.func.value
        name = (
            base.attr if isinstance(base, ast.Attribute)
            else base.id if isinstance(base, ast.Name)
            else None
        )
        if name in _MANAGER_NAMES:
            violations.append(
                f"{rel}:{node.lineno}: bare {name}.{node.func.attr}(...) "
                "outside io/checkpoint.py bypasses the verified checkpoint "
                "wrappers (save retry/backoff, checksum manifest, "
                "verify-before-restore with fallback) — go through "
                "Checkpointer.save / restore_latest / restore_before"
            )
    return violations


def _names_in(node: ast.AST) -> set[str]:
    out: set[str] = set()
    for n in ast.walk(node):
        if isinstance(n, ast.Name):
            out.add(n.id.lower())
        elif isinstance(n, ast.Attribute):
            out.add(n.attr.lower())
    return out


def _is_grad_named(node: ast.AST) -> bool:
    return any(
        any(g in name for g in _GRAD_NAMES) for name in _names_in(node)
    )


def _is_add_fn(node: ast.AST) -> bool:
    """jnp.add / np.add / operator.add / a bare ``add`` / an add-lambda."""
    if isinstance(node, ast.Attribute) and node.attr == "add":
        return True
    if isinstance(node, ast.Name) and node.id == "add":
        return True
    if isinstance(node, ast.Lambda) and isinstance(node.body, ast.BinOp):
        return isinstance(node.body.op, ast.Add)
    return False


def _grad_accum_violations(tree: ast.AST, rel: str) -> list[str]:
    violations: list[str] = []
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.AugAssign)
            and isinstance(node.op, ast.Add)
            and (_is_grad_named(node.target) or _is_grad_named(node.value))
        ):
            violations.append(
                f"{rel}:{node.lineno}: manual '+=' gradient accumulator "
                "outside train/step.py — the compiled step owns in-step "
                "accumulation (sharded fp32 carry, one optimizer apply per "
                "step) and the pipeline executors own their microbatching; "
                "a third layer double-accumulates with no sharding contract"
            )
        elif (
            isinstance(node, ast.Call)
            and (
                (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("map", "tree_map", "tree_multimap")
                )
                or (
                    # `from jax.tree_util import tree_map` must not evade
                    isinstance(node.func, ast.Name)
                    and node.func.id in ("tree_map", "tree_multimap")
                )
            )
            and node.args
            and _is_add_fn(node.args[0])
            and any(_is_grad_named(a) for a in node.args[1:])
        ):
            violations.append(
                f"{rel}:{node.lineno}: tree-map(add, ..., grads) "
                "accumulator outside train/step.py — use "
                "make_train_step(..., grad_accum_steps=N); the step owns "
                "accumulation (sharded fp32 carry, one optimizer apply)"
            )
    return violations


def _is_json_dumps_call(node: ast.AST) -> bool:
    return (
        isinstance(node, ast.Call)
        and isinstance(node.func, ast.Attribute)
        and node.func.attr == "dumps"
        and isinstance(node.func.value, ast.Name)
        and node.func.value.id == "json"
    )


def _spec_call_has_str_literal(node: ast.Call) -> bool:
    def holds_str(n: ast.AST) -> bool:
        if isinstance(n, ast.Constant) and isinstance(n.value, str):
            return True
        if isinstance(n, ast.Tuple):
            return any(holds_str(e) for e in n.elts)
        return False

    return any(holds_str(a) for a in node.args)


def _cadence_violations(tree: ast.AST, rel: str, allowed: frozenset) -> list[str]:
    """Rule 4: device→host conversions in a step-cadence file outside the
    allowlisted log-cadence-window functions."""
    violations: list[str] = []

    def describe(node: ast.Call) -> str | None:
        fn = node.func
        if isinstance(fn, ast.Name) and fn.id == "float":
            return "float(...)"
        if isinstance(fn, ast.Attribute) and fn.attr == "item" and not node.args:
            return ".item()"
        if (
            isinstance(fn, ast.Attribute)
            and isinstance(fn.value, ast.Name)
            and (fn.value.id, fn.attr) in CADENCE_SYNC_CALLS
        ):
            return f"{fn.value.id}.{fn.attr}(...)"
        return None

    def visit(node: ast.AST, func: str | None) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            func = node.name
        elif isinstance(node, ast.Call) and (func is None or func not in allowed):
            what = describe(node)
            if what is not None:
                violations.append(
                    f"{rel}:{node.lineno}: {what} on a step-cadence path "
                    f"(outside the log-cadence window functions "
                    f"{sorted(allowed)}) — a per-step device sync breaks "
                    "the zero-extra-syncs health-telemetry invariant; "
                    "convert only inside the cadenced window (or pin a "
                    "new window function in scripts/repo_lint.py with a "
                    "reason)"
                )
        for child in ast.iter_child_nodes(node):
            visit(child, func)

    visit(tree, None)
    return violations


def lint_file(path: str, rel: str) -> list[str]:
    with open(path) as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=rel)
    except SyntaxError as e:
        return [f"{rel}: syntax error: {e}"]
    violations: list[str] = []
    hot = rel in HOT_PATH_FILES
    dropout_ruled = any(rel.startswith(d + os.sep) for d in DROPOUT_RULE_DIRS)
    in_spec_layer = any(rel.startswith(d + os.sep) for d in SPEC_LAYER_DIRS)
    allowed_spec = rel in SPEC_LITERAL_ALLOWLIST
    json_emit_ok = rel in JSON_EMIT_ALLOW_FILES or any(
        rel.startswith(d + os.sep) for d in JSON_EMIT_ALLOW_DIRS
    )
    if rel in STEP_CADENCE_FILES:
        violations.extend(_cadence_violations(tree, rel, STEP_CADENCE_FILES[rel]))
    if rel != GRAD_ACCUM_OWNER and any(
        rel.startswith(d + os.sep) for d in GRAD_ACCUM_RULE_DIRS
    ):
        violations.extend(_grad_accum_violations(tree, rel))
    if rel != OPTIM_OWNER and any(
        rel.startswith(d + os.sep) for d in OPTIM_RULE_DIRS
    ):
        violations.extend(_optim_apply_violations(tree, rel))
    if rel != GRAD_COLLECTIVE_OWNER and any(
        rel.startswith(d + os.sep) for d in GRAD_COLLECTIVE_RULE_DIRS
    ):
        violations.extend(_grad_collective_violations(tree, rel))
    if rel not in KV_CAST_OWNERS and (
        rel in KV_CAST_RULE_FILES
        or any(rel.startswith(d + os.sep) for d in KV_CAST_RULE_DIRS)
    ):
        violations.extend(_kv_cast_violations(tree, rel))
    if rel != CKPT_OWNER:
        violations.extend(_ckpt_manager_violations(tree, rel))
    if rel != MESH_OWNER:
        violations.extend(_mesh_ownership_violations(tree, rel))
    if rel != BACKOFF_OWNER:
        violations.extend(_retry_sleep_violations(tree, rel))
    if rel not in RANK_CONDITIONAL_OWNERS:
        violations.extend(_rank_conditional_violations(tree, rel, src))
    if rel != PERCENTILE_OWNER:
        violations.extend(_percentile_violations(tree, rel))
    if rel not in MEMSTATS_OWNERS:
        violations.extend(_memstats_violations(tree, rel))
    if rel != PREFIX_IDENTITY_OWNER:
        violations.extend(_prefix_identity_violations(tree, rel))
    if rel not in SPEC_DECODE_OWNERS:
        violations.extend(_spec_decode_violations(tree, rel))
    # rule 5: does this file import Dropout from the shared helper?
    helper_dropout_import = any(
        isinstance(n, ast.ImportFrom)
        and n.module
        and n.module.endswith("ops.fused_dropout")
        and any(a.name == "Dropout" for a in n.names)
        for n in ast.walk(tree)
    )

    for node in ast.walk(tree):
        if (
            not json_emit_ok
            and isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "print"
            and any(_is_json_dumps_call(a) for a in node.args)
        ):
            violations.append(
                f"{rel}:{node.lineno}: print(json.dumps(...)) outside "
                "obs//utils/jsonlog.py bypasses the metric sink (no "
                "schema_version, no process gate, invisible to --obs "
                "jsonl) — emit through utils.jsonlog.log_json"
            )
        if dropout_ruled and isinstance(node, ast.Call):
            fn = node.func
            # match the ATTRIBUTE NAME regardless of qualifier so aliased
            # imports (linen.Dropout, flax.linen.Dropout, random.bernoulli)
            # can't slip past; a bare `Dropout(...)` is fine only when the
            # file imports it from the shared helper (helper_dropout_import)
            if isinstance(fn, ast.Attribute) and fn.attr == "Dropout":
                violations.append(
                    f"{rel}:{node.lineno}: raw {ast.unparse(fn)}(...) in "
                    "models//train/ bypasses the shared fused-dropout helper "
                    "— use ops.fused_dropout.Dropout (same contract, routes "
                    "through --dropout-impl)"
                )
            if (
                isinstance(fn, ast.Name)
                and fn.id == "Dropout"
                and not helper_dropout_import
            ):
                violations.append(
                    f"{rel}:{node.lineno}: Dropout(...) in models//train/ "
                    "without importing it from ops.fused_dropout — only the "
                    "shared helper's Dropout routes through --dropout-impl"
                )
            if (isinstance(fn, ast.Attribute) and fn.attr == "bernoulli") or (
                isinstance(fn, ast.Name) and fn.id == "bernoulli"
            ):
                violations.append(
                    f"{rel}:{node.lineno}: bernoulli(...) in models//train/ "
                    "hand-rolls a dropout mask outside the shared helper — "
                    "use ops.fused_dropout.dropout (the fused path never "
                    "materializes the mask)"
                )
        if hot and isinstance(node, ast.Attribute) and node.attr in FORBIDDEN_SYNC_ATTRS:
            violations.append(
                f"{rel}:{node.lineno}: .{node.attr}() in the train-step hot "
                "path stalls async dispatch"
            )
        if hot and isinstance(node, ast.Call):
            fn = node.func
            if (
                isinstance(fn, ast.Attribute)
                and isinstance(fn.value, ast.Name)
                and (fn.value.id, fn.attr) in FORBIDDEN_SYNC_CALLS
            ):
                violations.append(
                    f"{rel}:{node.lineno}: {fn.value.id}.{fn.attr}() in the "
                    "train-step hot path forces a device sync"
                )
        if (
            not in_spec_layer
            and not allowed_spec
            and isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id in ("P", "PartitionSpec")
            and _spec_call_has_str_literal(node)
        ):
            violations.append(
                f"{rel}:{node.lineno}: bare PartitionSpec with literal axis "
                "names outside parallel/ — route it through "
                "parallel/sharding.py rules (or pin an allowlist entry in "
                "scripts/repo_lint.py with a reason)"
            )
    return violations


def main(argv: list[str] | None = None) -> int:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    violations: list[str] = []
    pkg_root = os.path.join(root, PACKAGE)
    for dirpath, _, files in os.walk(pkg_root):
        for name in sorted(files):
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            rel = os.path.relpath(path, root)
            violations.extend(lint_file(path, rel))
    for v in violations:
        print(v)
    if not violations:
        print("repo_lint: clean")
    return 1 if violations else 0


if __name__ == "__main__":
    raise SystemExit(main())
