"""Unified training CLI — replaces the reference's three entry-point scripts.

Subcommand ``serve`` runs the continuous-batching inference engine over a
prompts file (``python -m distributed_llms_example_tpu.launch.cli serve
--model-ckpt ... --prompts-file prompts.json``): prefill/decode split,
sharded KV-cache slots, admit/evict per token step, serve_window /
serve_summary obs events — see README "Serving" and serving/engine.py.
``serve-router`` fronts N engine replicas with the fault-tolerant
router; ``serve-loadgen`` drives either through the open-loop QPS sweep
(serving/loadgen.py): seeded Poisson/bursty/ramp arrivals, goodput and
TTFT-percentile curves per offered rate, a detected saturation knee —
see README "Open-loop load testing & SLO curves".

One (sub)command serves all three of the reference's launch modes (SURVEY.md §7):

- local / single host:   ``python -m distributed_llms_example_tpu.launch.cli
                           --train-file train.json --val-file val.json``
- multi-host (the train-task equivalent): same command per host; rendezvous
  facts come from ``--coordinator-address/--num-processes/--process-id``,
  the ``valohai.distributed`` platform config, or VH_*/torchrun env vars
  (reference train-task.py:420-425 consumed the same triple);
- Valohai step: dataset files resolve via ``valohai.inputs('dataset')``
  exactly like the reference's ``run()`` functions
  (reference train-torchrun.py:151-159) when no --train-file is given.

Observability: ``--obs jsonl`` tees every metric line into
``<output-dir>/obs/metrics-p*.jsonl`` and turns on the derived gauges
(MFU, collective-traffic account); ``--obs-heartbeat-steps N`` adds the
multi-host liveness probe; ``--profile-steps 100:105`` captures a
jax.profiler trace for that step window; ``--obs-budget`` (auto-on)
closes every logging window into a ``step_budget`` account — wall time
decomposed into data_wait / dispatch / device_busy / sync_block /
host_overhead, a ``dispatch_efficiency`` gauge, and a runtime tripwire
for host-blocking transfers off the log cadence.  Post-run, ``python -m
distributed_llms_example_tpu.obs.report <output-dir> --trace trace.json``
merges every rank's spans, budget gauges and serving request lifecycles
into one Perfetto-loadable timeline (see README "Observability").

Dropout & RNG: ``--dropout-impl auto|fused|xla`` picks the dropout
execution path (auto = the fused Pallas kernel on TPU — in-kernel RNG,
no mask in HBM, seed-recompute backward; see README "Dropout & RNG
performance") and ``--prng-impl auto|threefry|rbg`` the key stream
(auto = TPU hardware RNG on TPU, bit-reproducible threefry elsewhere);
the resolved pair is logged as an ``rng_config`` event at startup.

Optimizer: ``--optim-impl auto|fused|xla`` picks the optimizer apply
(auto = the fused Pallas clip+AdamW kernel on TPU — one in-place pass
per leaf-shard, ``--health`` stats from the same pass; the optax chain
elsewhere; see README "Optimizer & step overhead").  Both impls run the
identical op sequence (equal up to XLA float contraction) and write the
SAME optax opt-state pytree, so checkpoints roam between them; the
resolved impl is logged as an ``optim_config`` event at startup.

Gradient compression: ``--grad-compression off|int8`` (off = compiled
step bit-identical to the uncompressed path; int8 = the cross-replica
gradient reduction on an s8 wire with stochastic rounding, int-safe
partial sums and a checkpointed error-feedback tree — see README
"Gradient compression").

Training health: ``--health`` (auto under ``--obs jsonl``) makes the
compiled step return in-graph numerics (param norm, per-bucket update
ratios, non-finite counts — zero extra device syncs) and arms the
anomaly watchdog; ``--on-anomaly warn|halt|checkpoint`` sets the agreed
policy; ``--recorder-steps N`` keeps a flight-recorder ring dumped on
anomaly/SIGTERM/crash.  Post-mortem: ``python -m
distributed_llms_example_tpu.obs.report <output-dir>`` merges the
per-process JSONL into a cross-host timeline (see README "Training
health & post-mortem").
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from distributed_llms_example_tpu.core.compile_cache import place_compile_cache
from distributed_llms_example_tpu.core.config import (
    add_reference_args,
    add_tpu_args,
    config_from_args,
)
from distributed_llms_example_tpu.core.mesh import initialize_distributed
from distributed_llms_example_tpu.data.dataset import load_json_records


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="dllm-train", description=__doc__)
    add_reference_args(p)
    add_tpu_args(p)
    p.add_argument("--train-file", type=str, default="", help="path to train.json (JSON array or JSONL)")
    p.add_argument("--val-file", type=str, default="", help="path to val.json")
    p.add_argument("--source-column", type=str, default="")
    p.add_argument("--target-column", type=str, default="")
    p.add_argument("--dry-run", action="store_true", help="print resolved config and exit")
    p.add_argument(
        "--lint", type=str, default="warn", choices=("off", "warn", "strict"),
        help="run the static sharding lint (analysis/) at startup: warn "
             "logs findings and proceeds (default); strict aborts on any "
             "error-level finding",
    )
    return p


def resolve_dataset_files(train_file: str, val_file: str) -> tuple[str, str]:
    """Explicit paths win; otherwise resolve train.json/val.json beside the
    first Valohai 'dataset' input (reference train-torchrun.py:152-159)."""
    if train_file:
        return train_file, val_file
    try:
        import valohai  # type: ignore

        base = os.path.dirname(valohai.inputs("dataset").path())
        return os.path.join(base, "train.json"), os.path.join(base, "val.json")
    except Exception:
        raise SystemExit(
            "no --train-file given and no Valohai 'dataset' input available; "
            "pass --train-file/--val-file"
        ) from None


def build_serve_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="dllm-train serve",
        description="continuous-batching inference over a prompts file "
                    "(serving/engine.py): prefill/decode split, sharded "
                    "KV-cache slots, admit/evict per token step",
    )
    p.add_argument("--model-ckpt", type=str, default="t5-small")
    p.add_argument("--tokenizer", type=str, default="")
    p.add_argument("--prompts-file", type=str, required=True,
                   help="JSON array / JSONL of records (source column "
                        "resolved like training data) or plain strings")
    p.add_argument("--source-column", type=str, default="")
    p.add_argument("--output-file", type=str, default="",
                   help="write {prompt, output, tokens} JSONL here "
                        "(default: stdout)")
    p.add_argument("--num-prompts", type=int, default=0, help="0 = all")
    p.add_argument("--max-slots", type=int, default=8,
                   help="concurrent decode slots (the fixed serving batch)")
    p.add_argument("--prefill-batch", type=int, default=0,
                   help="most sequences one admission wave prefills; a "
                        "wave that fits the mesh's batch shards runs a "
                        "program of that many rows instead "
                        "(0 = max-slots, which always shards when the "
                        "slot count does)")
    p.add_argument("--max-new-tokens", type=int, default=128)
    p.add_argument("--max-source-length", type=int, default=1024)
    p.add_argument("--log-every-steps", type=int, default=50)
    p.add_argument("--ttft-slo-ms", type=float, default=0.0,
                   help="first-token SLO for the serve_summary goodput "
                        "fields (useful tokens/sec + slo_attainment); "
                        "0 = no SLO")
    p.add_argument("--kv-cache-dtype", type=str, default="f32",
                   choices=("f32", "int8"),
                   help="KV-cache storage dtype: int8 quantizes on cache "
                        "write (per-head per-position scales, ~4x less "
                        "cache HBM and decode traffic at a token-match "
                        "tolerance; README 'Serving capacity')")
    p.add_argument("--prefill-buckets", type=str, default="",
                   help="comma list of compiled admission widths (e.g. "
                        "128,256,512); each chunk pads to the smallest "
                        "covering bucket instead of max-source-length, "
                        "all AOT-warmed before the first request")
    p.add_argument("--paged-kv", action="store_true",
                   help="causal families: slots hold block lists over a "
                        "shared pool (serving/cache_pool.py) so short "
                        "prompts stop paying worst-case cache memory; "
                        "bit-identical tokens to the flat cache")
    p.add_argument("--pool-blocks", type=int, default=0,
                   help="paged: shared pool size in blocks (0 = worst "
                        "case, every slot at full width — shrink it to "
                        "trade admission deferrals for memory)")
    p.add_argument("--kv-block-size", type=int, default=0,
                   help="paged: block length in cache positions (0 = the "
                        "kv tile size for the cache width)")
    p.add_argument("--prefix-cache", action="store_true",
                   help="paged: content-hash full blocks and share them "
                        "across requests — admission walks the longest "
                        "cached prefix, bumps refcounts, and prefills "
                        "only the uncached tail (copy-on-write at the "
                        "first divergent block); tokens stay bit-identical "
                        "to cold admission")
    p.add_argument("--prefix-cache-budget-gib", type=float, default=0.0,
                   help="prefix cache: per-replica LRU byte budget for "
                        "keeping FINISHED requests' blocks warm (evicted "
                        "strictly at refcount 0), so a follow-up turn "
                        "prefills only its delta (0 = no warm retention; "
                        "live sharing still applies)")
    p.add_argument("--spec-tokens", type=int, default=0,
                   help="causal families: speculative decode — draft this "
                        "many tokens per slot per round and verify all "
                        "k+1 positions in ONE decode call "
                        "(serving/spec.py); output stays bit-identical "
                        "to plain greedy, only cheaper per token "
                        "(0 = off, max 7 = the flash-decode q-row cap "
                        "minus the bonus row)")
    p.add_argument("--spec-draft-model", type=str, default="",
                   help="registry name of a shrunk causal draft model "
                        "sharing the target's vocab ('' = n-gram "
                        "self-drafting over each slot's own prompt + "
                        "generated tokens, zero extra model)")
    p.add_argument("--hbm-budget-gib", type=float, default=16.0,
                   help="per-chip HBM ceiling in GiB for the serve "
                        "summary's bucketed memory account (obs/memprof.py "
                        "fit verdict; v5e = 16)")
    p.add_argument("--postmortem-dir", type=str, default="",
                   help="where a RESOURCE_EXHAUSTED mid-serve dumps its "
                        "atomic memory-postmortem-p*.json bundle "
                        "('' = tripwire off)")
    p.add_argument("--mesh", type=str, default="data=-1")
    p.add_argument("--compute-dtype", type=str, default="bfloat16")
    p.add_argument("--attention-impl", type=str, default="",
                   choices=("", "auto", "flash", "ring", "xla"))
    p.add_argument("--lint", type=str, default="warn",
                   choices=("off", "warn", "strict"),
                   help="serving startup lint: cache sharding rules vs the "
                        "mesh + the decode composition rows")
    return p


def _prompt_text(record, source_column: str) -> str:
    if isinstance(record, str):
        return record
    if source_column:
        return str(record[source_column])
    for col in ("dialogue", "article", "prompt", "text", "source"):
        if col in record:
            return str(record[col])
    raise SystemExit(
        f"cannot resolve a prompt column in record keys {sorted(record)}; "
        "pass --source-column"
    )


def _serve_setup(args, *, extra_flags: tuple = ()):
    """The shared serve/serve-router prologue: prompts → model → mesh →
    startup lint → tokenizer → sharded params → encoded requests.
    Returns (lm, mesh, tok, params, prompts, requests)."""
    import jax

    from distributed_llms_example_tpu.core.config import parse_mesh_arg
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.core.precision import parse_dtype
    from distributed_llms_example_tpu.data.tokenizer import get_tokenizer
    from distributed_llms_example_tpu.models.registry import load_model
    from distributed_llms_example_tpu.parallel.sharding import shard_params

    if jax.process_count() > 1:  # pod-agreed: pod-uniform guard; every rank fails fast together
        raise SystemExit(
            "the serving engine is single-controller; run one process "
            "(the serve-router replica pool is in-process — multi-host "
            "serving is a network tier above it, not a collective)"
        )
    records = load_json_records(args.prompts_file)
    if args.num_prompts > 0:
        records = records[: args.num_prompts]
    prompts = [_prompt_text(r, args.source_column) for r in records]
    lm = load_model(
        args.model_ckpt,
        dtype=parse_dtype(args.compute_dtype),
        attention_impl=args.attention_impl or None,
    )
    mesh = build_mesh(parse_mesh_arg(args.mesh))
    if args.lint != "off":
        from distributed_llms_example_tpu.analysis.composition import (
            check_composition,
        )
        from distributed_llms_example_tpu.analysis.findings import (
            emit as emit_findings,
            has_errors,
        )
        from distributed_llms_example_tpu.analysis.spec_lint import (
            lint_cache_sharding,
        )
        from distributed_llms_example_tpu.evaluation.generation import abstract_cache

        a_params = jax.eval_shape(lambda: lm.init_params(0))
        findings = lint_cache_sharding(
            abstract_cache(
                lm.module, a_params,
                batch=args.max_slots, max_new_tokens=args.max_new_tokens,
                src_len=args.max_source_length, is_seq2seq=lm.is_seq2seq,
                kv_cache_dtype=args.kv_cache_dtype,
            ),
            dict(mesh.shape),
        )
        if args.paged_kv:
            # the pool is the resident serving tree under --paged-kv:
            # spec-lint it like CACHE_RULES (POOL_RULES is its rule set)
            from distributed_llms_example_tpu.ops.flash_attention import (
                auto_block,
            )
            from distributed_llms_example_tpu.parallel.sharding import (
                pool_rules,
            )
            from distributed_llms_example_tpu.serving.cache_pool import (
                pool_cache_tree,
            )

            width = args.max_source_length + args.max_new_tokens
            bs = args.kv_block_size or auto_block(width) or width
            a_cache = abstract_cache(
                lm.module, a_params,
                batch=args.max_slots, max_new_tokens=args.max_new_tokens,
                src_len=args.max_source_length, is_seq2seq=lm.is_seq2seq,
                kv_cache_dtype=args.kv_cache_dtype,
            )
            n_blocks = args.pool_blocks or args.max_slots * max(width // bs, 1)
            findings += lint_cache_sharding(
                jax.eval_shape(
                    lambda: pool_cache_tree(a_cache, n_blocks, bs)
                ),
                dict(mesh.shape),
                rules=pool_rules(),
            )
        findings += check_composition(
            family=lm.family, mesh_axes=dict(mesh.shape),
            flags=("decode", "seq2seq" if lm.is_seq2seq else "causal")
            + tuple(extra_flags),
        )
        # Layer 1 of the pod-agreement analysis: a rank-divergent branch
        # into a collective hangs the serve replica pool the same way it
        # hangs a train pod — same AST pass as the trainer startup lint
        from distributed_llms_example_tpu.analysis.divergence import (
            analyze_tree as divergence_tree,
        )

        div_findings, _ = divergence_tree()
        findings += div_findings
        emit_findings(findings, as_json=True)
        if args.lint == "strict" and has_errors(findings):
            raise SystemExit(
                "serving lint found error-level findings; rerun with "
                "--lint warn to proceed anyway"
            )
    tok = get_tokenizer(args.tokenizer, args.model_ckpt)
    params = lm.params if lm.params is not None else lm.init_params(0)
    params = shard_params(params, mesh)
    encode = tok.encode_source if lm.is_seq2seq else tok.encode_prompt
    requests = [encode(t, args.max_source_length) for t in prompts]
    return lm, mesh, tok, params, prompts, requests


def _serve_config_from_args(args):
    from distributed_llms_example_tpu.serving.engine import ServeConfig

    return ServeConfig(
        max_slots=args.max_slots,
        prefill_batch=args.prefill_batch,
        max_new_tokens=args.max_new_tokens,
        max_source_length=args.max_source_length,
        log_every_steps=args.log_every_steps,
        ttft_slo_ms=args.ttft_slo_ms,
        kv_cache_dtype=args.kv_cache_dtype,
        prefill_buckets=tuple(
            int(b) for b in args.prefill_buckets.split(",") if b.strip()
        ),
        paged_kv=args.paged_kv,
        pool_blocks=args.pool_blocks,
        kv_block_size=args.kv_block_size,
        prefix_cache=args.prefix_cache,
        prefix_cache_budget_gib=args.prefix_cache_budget_gib,
        spec_tokens=getattr(args, "spec_tokens", 0),
        spec_draft_model=getattr(args, "spec_draft_model", ""),
        hbm_budget_gib=args.hbm_budget_gib,
        postmortem_dir=args.postmortem_dir,
    )


def _write_serve_output(args, lm, tok, prompts, outputs, *, extra=None):
    """Request OUTPUTS (the served product), not telemetry: a plain
    JSONL document through the crash-safe product writer (obs/sink.py
    ``ProductJsonlWriter``: one os-level write per line + fsync on
    close), so a killed serve run leaves no torn output lines — the
    metric/obs channel stays log_json's."""
    from distributed_llms_example_tpu.obs.sink import ProductJsonlWriter
    from distributed_llms_example_tpu.serving.engine import trim_eos
    from distributed_llms_example_tpu.utils.jsonlog import log_json

    eos, pad = lm.config.eos_token_id, lm.config.pad_token_id
    lines = []
    for i, (prompt, ids) in enumerate(zip(prompts, outputs)):
        kept = [t for t in trim_eos(ids, eos, pad) if t != eos]
        rec = {"prompt": prompt, "output": tok.decode(kept), "tokens": len(kept)}
        if extra is not None:
            rec.update(extra[i])
        lines.append(rec)
    if not args.output_file:
        for rec in lines:
            sys.stdout.write(json.dumps(rec) + "\n")
        return
    writer = ProductJsonlWriter(args.output_file)
    try:
        for rec in lines:
            writer.write(rec)
    finally:
        writer.close()
    log_json({
        "event": "serve_output",
        "path": args.output_file,
        "records": len(lines),
    })


def serve_main(argv: list[str] | None = None) -> int:
    """The ``serve`` subcommand: load → shard → continuous-batching decode."""
    place_compile_cache()
    args = build_serve_parser().parse_args(argv)
    from distributed_llms_example_tpu.serving.engine import ServingEngine

    lm, mesh, tok, params, prompts, requests = _serve_setup(args)
    engine = ServingEngine(
        lm.module, lm.config, mesh, _serve_config_from_args(args),
        is_seq2seq=lm.is_seq2seq,
    )
    outputs = engine.generate(params, requests)
    _write_serve_output(args, lm, tok, prompts, outputs)
    return 0


def build_router_parser() -> argparse.ArgumentParser:
    """``serve-router`` = every serve flag + the router tier's knobs."""
    p = build_serve_parser()
    p.prog = "dllm-train serve-router"
    p.description = (
        "fault-tolerant serving tier (serving/router.py): N in-process "
        "engine replicas behind a router with session affinity, "
        "queue-depth dispatch, bounded retry/re-prefill on replica "
        "failure, admission control, graceful drain, and the serving "
        "chaos kinds (replica_crash/replica_stall/request_storm)"
    )
    p.add_argument("--replicas", type=int, default=2,
                   help="engine replicas in the pool (each owns its own "
                        "compiled programs and slot state)")
    p.add_argument("--max-retries", type=int, default=2,
                   help="re-dispatch budget per request after replica "
                        "failures; exceeding it sheds the request")
    p.add_argument("--deadline-ms", type=float, default=0.0,
                   help="per-request wall deadline while waiting for "
                        "dispatch (0 = none); expired requests shed with "
                        "reason 'deadline'")
    p.add_argument("--router-max-queue", type=int, default=0,
                   help="router queue bound (0 = unbounded); submissions "
                        "over it shed or defer per --shed-policy")
    p.add_argument("--shed-policy", type=str, default="defer",
                   choices=("defer", "shed"),
                   help="what happens to a submission over the queue "
                        "bound: defer parks it client-side, shed rejects")
    p.add_argument("--suspect-after-ticks", type=int, default=3,
                   help="missed heartbeats (router ticks without replica "
                        "progress) before live -> suspect")
    p.add_argument("--dead-after-ticks", type=int, default=6,
                   help="missed heartbeats before suspect -> dead "
                        "(in-flight requests re-prefill elsewhere)")
    p.add_argument("--chaos", type=str, default="",
                   help="serving chaos grammar (obs/chaos.py): "
                        "replica_crash@K,replica_stall@K,request_storm@K "
                        "with K a router scheduler tick")
    return p


def serve_router_main(argv: list[str] | None = None) -> int:
    """The ``serve-router`` subcommand: load once, shard once, N engine
    replicas over the one mesh, route to completion."""
    place_compile_cache()
    args = build_router_parser().parse_args(argv)
    from distributed_llms_example_tpu.obs.chaos import parse_chaos
    from distributed_llms_example_tpu.serving.engine import ServingEngine
    from distributed_llms_example_tpu.serving.router import (
        ReplicaRouter,
        RouterConfig,
    )

    if args.replicas < 1:
        raise SystemExit("--replicas must be >= 1")
    lm, mesh, tok, params, prompts, requests = _serve_setup(
        args, extra_flags=("router",)
    )
    serve_cfg = _serve_config_from_args(args)
    engines = [
        ServingEngine(
            lm.module, lm.config, mesh, serve_cfg, is_seq2seq=lm.is_seq2seq
        )
        for _ in range(args.replicas)
    ]
    router = ReplicaRouter(
        engines, params,
        RouterConfig(
            max_retries=args.max_retries,
            deadline_s=args.deadline_ms / 1e3,
            max_queue=args.router_max_queue,
            shed_policy=args.shed_policy,
            suspect_after_ticks=args.suspect_after_ticks,
            dead_after_ticks=args.dead_after_ticks,
            log_every_ticks=args.log_every_steps,
            chaos=parse_chaos(args.chaos) if args.chaos else None,
        ),
    )
    outputs = router.serve(requests)
    extra = [
        {"shed": q.shed_reason} if q.shed else {}
        for q in router.requests
        if not q.synthetic
    ]
    _write_serve_output(args, lm, tok, prompts, outputs, extra=extra)
    return 0


def build_loadgen_parser() -> argparse.ArgumentParser:
    """``serve-loadgen`` = every serve-router flag + the open-loop sweep
    knobs.  ``--replicas`` is repurposed: 0 (the default here) drives a
    bare engine session; >= 1 drives a ReplicaRouter pool, which is how
    the sweep composes with ``--chaos``."""
    p = build_router_parser()
    p.prog = "dllm-train serve-loadgen"
    p.description = (
        "open-loop load sweep (serving/loadgen.py): seeded arrival "
        "schedules (arrivals never wait for completions, so queues "
        "genuinely build) over an offered-QPS grid, producing "
        "offered-vs-goodput and TTFT-percentile curves with a detected "
        "saturation knee; --replicas 0 drives one engine session, >= 1 "
        "a router pool (composable with --chaos)"
    )
    p.set_defaults(replicas=0)
    p.add_argument("--arrival-process", type=str, default="poisson",
                   choices=("poisson", "bursty", "ramp"),
                   help="arrival process: exponential inter-arrivals, "
                        "bursts of --burst-size, or a linear rate ramp "
                        "from --ramp-start-frac x rate")
    p.add_argument("--loadgen-seed", type=int, default=0,
                   help="arrival-schedule RNG seed (same seed + config = "
                        "bit-identical schedule)")
    p.add_argument("--qps-grid", type=str, default="1,2,4,8",
                   help="comma list of ascending offered QPS points")
    p.add_argument("--burst-size", type=int, default=4,
                   help="bursty: simultaneous arrivals per burst")
    p.add_argument("--ramp-start-frac", type=float, default=0.25,
                   help="ramp: starting rate as a fraction of the "
                        "point's offered rate")
    p.add_argument("--max-wall-s", type=float, default=0.0,
                   help="per-point wall cap (0 = none); a point far past "
                        "saturation stops here and reports its "
                        "unfinished tail")
    p.add_argument("--track-tol", type=float, default=0.9,
                   help="knee sensitivity: a point with achieved QPS "
                        "below track-tol x offered has saturated")
    p.add_argument("--workload", type=str, default="random",
                   choices=("random", "chatbot"),
                   help="request mix: 'random' drives the prompts file; "
                        "'chatbot' generates the seeded shared-prefix "
                        "multi-turn mix (serving/loadgen.py "
                        "chatbot_requests — >=90%% shared system prompt, "
                        "growing per-session history, session keys for "
                        "router affinity), ignoring the prompts file")
    p.add_argument("--chat-sessions", type=int, default=8,
                   help="chatbot: concurrent conversation sessions")
    p.add_argument("--chat-turns", type=int, default=4,
                   help="chatbot: turns per session (turn-major order)")
    p.add_argument("--chat-shared-frac", type=float, default=0.9,
                   help="chatbot: fraction of sessions opening with the "
                        "one shared system prompt")
    return p


def serve_loadgen_main(argv: list[str] | None = None) -> int:
    """The ``serve-loadgen`` subcommand: load once, shard once, one
    fresh session (or router pool) per offered-QPS grid point."""
    place_compile_cache()
    args = build_loadgen_parser().parse_args(argv)
    from distributed_llms_example_tpu.serving.engine import ServingEngine
    from distributed_llms_example_tpu.serving.loadgen import (
        EngineTarget,
        LoadgenConfig,
        RouterTarget,
        sweep_qps,
    )

    lm, mesh, tok, params, prompts, requests = _serve_setup(
        args, extra_flags=("router",) if args.replicas >= 1 else ()
    )
    sessions = None
    budgets = None
    if args.workload == "chatbot":
        from distributed_llms_example_tpu.serving.loadgen import (
            chatbot_requests,
        )

        # synthetic seeded token streams (prompts file ignored): the
        # shared-prefix structure, not the text, is what the mix drives;
        # the scripted reply lengths become per-request decode budgets
        # so every sweep over one seed decodes the same token counts
        requests, sessions, budgets = chatbot_requests(
            sessions=args.chat_sessions,
            turns=args.chat_turns,
            seed=args.loadgen_seed,
            vocab=int(lm.config.vocab_size),
            shared_frac=args.chat_shared_frac,
            max_len=args.max_source_length,
            with_budgets=True,
        )
    serve_cfg = _serve_config_from_args(args)
    cfg = LoadgenConfig(
        process=args.arrival_process,
        seed=args.loadgen_seed,
        burst_size=args.burst_size,
        ramp_start_frac=args.ramp_start_frac,
        qps_grid=tuple(
            float(q) for q in args.qps_grid.split(",") if q.strip()
        ),
        # the serve parser's SLO default (0 = no SLO) would make
        # attainment vacuous; the sweep judges against a real bar
        ttft_slo_ms=args.ttft_slo_ms or 500.0,
        max_wall_s=args.max_wall_s,
        track_tol=args.track_tol,
    )
    if args.replicas >= 1:
        from distributed_llms_example_tpu.obs.chaos import parse_chaos
        from distributed_llms_example_tpu.serving.router import (
            ReplicaRouter,
            RouterConfig,
        )

        router_cfg = RouterConfig(
            max_retries=args.max_retries,
            deadline_s=args.deadline_ms / 1e3,
            max_queue=args.router_max_queue,
            shed_policy=args.shed_policy,
            suspect_after_ticks=args.suspect_after_ticks,
            dead_after_ticks=args.dead_after_ticks,
            log_every_ticks=args.log_every_steps,
            chaos=parse_chaos(args.chaos) if args.chaos else None,
        )

        def target_factory():
            engines = [
                ServingEngine(
                    lm.module, lm.config, mesh, serve_cfg,
                    is_seq2seq=lm.is_seq2seq,
                )
                for _ in range(args.replicas)
            ]
            return RouterTarget(ReplicaRouter(engines, params, router_cfg))
    else:
        engine = ServingEngine(
            lm.module, lm.config, mesh, serve_cfg, is_seq2seq=lm.is_seq2seq
        )

        def target_factory():
            return EngineTarget(engine.open(params))

    summary = sweep_qps(
        target_factory, requests, cfg, sessions=sessions, budgets=budgets
    )
    if args.output_file:
        from distributed_llms_example_tpu.obs.sink import ProductJsonlWriter

        writer = ProductJsonlWriter(args.output_file)
        try:
            writer.write(summary)
        finally:
            writer.close()
    return 0


def main(argv: list[str] | None = None) -> int:
    place_compile_cache()
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "serve":
        return serve_main(argv[1:])
    if argv and argv[0] == "serve-router":
        return serve_router_main(argv[1:])
    if argv and argv[0] == "serve-loadgen":
        return serve_loadgen_main(argv[1:])
    args = build_parser().parse_args(argv)
    cfg = config_from_args(args)
    if args.source_column:
        cfg = cfg.replace(source_column=args.source_column)
    if args.target_column:
        cfg = cfg.replace(target_column=args.target_column)
    if args.dry_run:
        print(cfg.to_json())
        return 0
    initialize_distributed(args.coordinator_address, args.num_processes, args.process_id)
    if args.lint != "off":
        # spec + composition passes from abstract shapes — milliseconds,
        # and a typo'd spec or known-crash combo surfaces BEFORE minutes
        # of weight loading and compilation.  Must run AFTER
        # initialize_distributed: the lint touches the jax backend
        # (device_count, eval_shape), and jax.distributed.initialize
        # refuses to run once any computation has initialized XLA — and
        # the lint wants the GLOBAL device count anyway.
        from distributed_llms_example_tpu.analysis.findings import (
            emit as emit_findings,
            has_errors,
        )
        from distributed_llms_example_tpu.analysis.lint import startup_lint

        findings = startup_lint(cfg)
        emit_findings(findings, as_json=True)
        if args.lint == "strict" and has_errors(findings):
            raise SystemExit(
                "startup lint found error-level findings (see lint_finding "
                "lines above); rerun with --lint warn to proceed anyway"
            )
    train_path, val_path = resolve_dataset_files(args.train_file, args.val_file)
    train_records = load_json_records(train_path)
    val_records = load_json_records(val_path) if val_path and os.path.exists(val_path) else None

    from distributed_llms_example_tpu.train.trainer import Trainer

    trainer = Trainer(cfg, train_records=train_records, val_records=val_records)
    try:
        trainer.train()
    finally:
        # flush the JSONL file channel (--obs jsonl) even on a crash —
        # the telemetry written so far is exactly what the postmortem needs
        from distributed_llms_example_tpu.obs.sink import current_sink

        current_sink().close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
