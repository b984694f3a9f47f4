"""Continuous-batching serving engine: padded decode slots, admit/evict per step.

The static eval path (evaluation/generation.py) decodes one padded batch to
completion — every finished row keeps "decoding" pads until the SLOWEST row
is done, so utilization decays over the batch's lifetime.  This engine is
the Orca-style iteration-level alternative (arXiv:2412.14374's serving
discussion): a fixed set of ``max_slots`` decode slots, each holding ONE
in-flight sequence at its own offset, with finished sequences EVICTED and
new ones ADMITTED between per-token steps.  The compiled programs stay
fixed-shape (slot count never changes); only the host-side slot bookkeeping
moves.

Three compiled programs per model, all traced under the ambient mesh so
cache/activation sharding constraints bake in (batch rows over
data×fsdp×expert, heads over tensor — ``CACHE_RULES``):

- **prefill** (once per admitted chunk): the encoder + cross-KV projection
  (seq2seq) or the prompt pass into a chunk-sized cache (causal).
- **admit** (scatter): chunk rows land in their slots via ``.at[idx].set``
  with ``mode="drop"`` — an out-of-range index is a no-op, which is how
  partially-filled chunks park their padding rows.  Slot caches are NOT
  zeroed on reuse: every read is masked to ``k_pos <= offset``, so stale
  K/V from the previous occupant is unreachable by construction (the
  determinism test pins engine output == static-batch output through slot
  reuse).
- **decode step** (every token): one token per slot, per-slot offsets
  (``cache_positions`` per-row cache writes), idle slots parked at an
  out-of-range offset so their writes drop.

Host loop per round: admit into free slots (if any), dispatch the next
decode step, THEN read back the (slots,) token vector of the step dispatched
the round before, append/evict: the device always has one program queued
behind the one it runs (``ServeSession``: the round's order).  Greedy only —
beam search keeps the static split path (the per-step beam reorder has no
per-slot form).
Single-controller: multi-process serving is a queueing layer above this,
not a collective program.

Obs events (utils/jsonlog → obs sink): ``serve_window`` at the log cadence
(decode tokens/sec[/chip], slot occupancy, queue depth, the window's
prefill-vs-decode time split), a ``serve_request`` lifecycle record per
finished request (queue-wait → prefill → first-token → decode → evict,
with times relative to the batch's submit instant so ``obs.report
--trace`` can draw each request as a slot-track slice), and a final
``serve_summary`` (tokens/sec/chip, TTFT p50/p95 **with its queue-vs-
prefill decomposition**, occupancy, evictions, and the **goodput
fields** — useful tokens/sec and the SLO-attainment fraction at the
configured ``ttft_slo_ms``, the router tier's dispatch inputs) — TTFT
p95 stops being one opaque aggregate and becomes "the tail waited in
queue" vs "prefill is slow".

Host spans (obs/spans.py, scope ``serve``): each round is ``serve/round``,
partitioned into ``admit_prep``, ``prefill_dispatch``, ``decode_dispatch``,
``token_fetch``, ``emit`` and ``window_log``.  ``token_fetch`` and ``emit``
name the round dispatched one call earlier, one behind the ``decode_dispatch``
beside them; a causal wave's first tokens are a second ``token_fetch`` /
``emit`` pair at the round's end.  Under a profiler session they sit on the
device trace's clock.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from distributed_llms_example_tpu.evaluation.generation import (
    _causal_prefill,
    _init_cache,
    causal_cache_shapes,
)
from distributed_llms_example_tpu.parallel.activation import (
    BATCH_AXES,
    activation_mesh,
    constrain_cache,
    kv_cache_context,
)
from distributed_llms_example_tpu.obs import setup
from distributed_llms_example_tpu.obs.spans import SpanRecorder, percentiles
from distributed_llms_example_tpu.parallel.sharding import (
    CACHE_LENGTH_AXIS,
    WINDOW_LEAVES,
    _path_str,
    cache_kv_heads,
    cache_leaf_name,
)
from distributed_llms_example_tpu.serving import cache_pool
from distributed_llms_example_tpu.serving import spec as spec_decode
from distributed_llms_example_tpu.utils.jsonlog import log_json


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine shape/behavior knobs (all compiled shapes derive from these).

    ``max_slots``: concurrent in-flight sequences — the decode batch.
    ``prefill_batch``: the most sequences one admission wave prefills — a
    cap, not the size every wave computes: the programs are compiled at two
    row counts, this one and the mesh's batch shards (1 on one chip), and a
    wave no larger than the shards runs the small one
    (``ServingEngine.wave_rows``); 0 = auto (``max_slots`` — always divides
    the mesh's batch shards when the slot count does, so the defaults work
    on any mesh).  ``max_source_length``: fixed prompt width (prompts are
    padded to it; the serving twin of the trainer's bucketed max).
    ``max_new_tokens``: decode budget per sequence = the KV-cache length
    (seq2seq) or its decode tail (causal).  ``request_spans``: emit one
    ``serve_request`` lifecycle event per finished request (queue-wait /
    prefill / ttft / decode breakdown — the trace exporter's feed).
    ``ttft_slo_ms``: the first-token SLO the goodput fields are judged
    against (0 = no SLO: every finished request's tokens are useful) —
    the router tier's dispatch inputs (``serve_summary``:
    ``goodput_tokens_per_sec`` + ``slo_attainment``).

    Decode-capacity knobs (README "Serving capacity"):

    ``kv_cache_dtype``: "f32" (store K/V at compute dtype) or "int8"
    (quantize on cache write, per-head per-position scales; ~4× less
    cache HBM and decode traffic at a token-match-rate tolerance — the
    paged/bucketed knobs below stay BIT-exact instead).
    ``prefill_buckets``: ascending compiled admission widths (e.g.
    ``(128, 256, 512)``); each admission chunk pads to the smallest
    bucket covering it instead of always paying ``max_source_length``,
    and every bucket's programs are AOT-warmed before the first request
    so no request ever hits a compile.  ``max_source_length`` is always
    an implicit last bucket.
    ``paged_kv`` (causal families only): slots hold block lists over a
    shared pool (serving/cache_pool.py) instead of worst-case-width
    rows; ``pool_blocks`` (0 = worst case: every slot at full width) and
    ``kv_block_size`` (0 = auto kv tile size) shape the pool.  Admission
    defers while the free list is short; eviction returns all blocks.
    ``prefix_cache`` (requires ``paged_kv``): share immutable full
    prompt blocks across requests by chained content hash — admission
    walks its longest cached prefix, bumps refcounts on the matched
    chain, and prefills only the uncached tail (README "Prefix caching
    & multi-turn sessions"; tokens stay BIT-identical to cold-start).
    ``prefix_cache_budget_gib``: warm-retention LRU budget for finished
    requests' prefix blocks, evicted strictly at refcount 0 (0 = no
    retention: sharing only among concurrently-live requests).
    ``spec_tokens`` (causal families only): speculative decode — draft
    k tokens per slot per round and verify all k+1 positions in ONE
    decode call (serving/spec.py); output is BIT-identical to plain
    greedy, only cheaper per token (0 = off; at most
    ``core.config.SPEC_MAX_DRAFT_TOKENS``, the flash-decode q-row cap
    minus the bonus row).  ``spec_draft_model``: registry name of a
    shrunk causal draft model sharing the target's vocab ("" = n-gram
    self-drafting, zero extra model)."""

    max_slots: int = 8
    prefill_batch: int = 0  # 0 = max_slots
    max_new_tokens: int = 128
    max_source_length: int = 1024
    log_every_steps: int = 50
    request_spans: bool = True
    ttft_slo_ms: float = 0.0
    kv_cache_dtype: str = "f32"
    prefill_buckets: tuple = ()
    paged_kv: bool = False
    pool_blocks: int = 0  # 0 = worst case (max_slots x tiles per slot)
    kv_block_size: int = 0  # 0 = auto (the kv tile size for the cache width)
    prefix_cache: bool = False
    prefix_cache_budget_gib: float = 0.0
    spec_tokens: int = 0  # speculative decode: drafts per verify round (0 = off)
    spec_draft_model: str = ""  # registry draft model ("" = n-gram self-draft)
    # the bucketed HBM account (obs/memprof.py): the capacity gauges'
    # cache-bytes arithmetic lands in the shared params/kv_cache scheme
    # and the serve_summary carries its fit verdict against this ceiling
    hbm_budget_gib: float = 16.0
    # where a RESOURCE_EXHAUSTED mid-serve dumps its atomic
    # memory-postmortem-p*.json bundle ("" = tripwire off)
    postmortem_dir: str = ""


@dataclasses.dataclass
class ServeStats:
    """Filled by ``ServingEngine.generate`` — the bench/obs read surface."""

    sequences: int = 0
    decode_steps: int = 0
    decode_tokens: int = 0
    # the cadence a client sees: for each decode round, from the instant the
    # host last held new tokens (the previous round's fetch, or a wave's first
    # tokens) to the end of this round's fetch; from the round's own dispatch
    # where nothing was in flight before it (a lockstep round, the first round
    # after an idle spell)
    decode_seconds: float = 0.0
    # a wave's dispatch until the host holds its first tokens (causal), or the
    # dispatch alone (seq2seq: a wave has no token of its own to wait for)
    prefill_seconds: float = 0.0
    # the round's order (ServeSession.step): rounds dispatched while the round
    # before was still unfetched, and tokens computed for a slot that had
    # already ended by EOS (found one round late, dropped at emit)
    rounds_ahead: int = 0
    tokens_discarded: int = 0
    slot_occupancy: float = 0.0
    # capacity gauges (static byte accounting — measured, not inferred):
    # resident = the serving state's fixed allocation; in_use = what live
    # requests actually hold (= resident on the flat path; blocks×block
    # bytes on the paged path); bytes_per_live_token averages in_use over
    # the live tokens at each decode step
    cache_bytes_resident: int = 0
    peak_cache_bytes_in_use: int = 0
    bytes_per_live_token: float = 0.0
    admit_deferrals: int = 0  # paged: admissions deferred on a short free list
    # prefix-cache gauges (prefix_cache only): a lookup per admitted
    # eligible request, a hit when its longest cached chain is >= 1
    # block; tokens saved = prompt tokens served from shared blocks
    # instead of re-prefilled
    prefix_lookups: int = 0
    prefix_hits: int = 0
    prefill_tokens_total: int = 0
    prefill_tokens_saved: int = 0
    # speculative-decode ledger (spec_tokens > 0 only): a step is one
    # verify round; drafted counts k proposals per live slot, accepted
    # the drafts the target's argmax confirmed, emitted every appended
    # token (accepted + the bonus token).  slot_rounds counts one per
    # LIVE slot per verify round, so accepted_tokens_per_step =
    # spec_emitted / spec_slot_rounds is the per-sequence multi-token
    # yield in [1, k+1] — plain decode is 1.0 by construction, so > 1.0
    # is the speculative win (a batch-wide tokens/round reading would
    # exceed 1 with two live slots even with every draft rejected)
    spec_steps: int = 0
    spec_slot_rounds: int = 0
    spec_drafted: int = 0
    spec_accepted: int = 0
    spec_emitted: int = 0
    ttft_s: list[float] = dataclasses.field(default_factory=list)
    # per-request TTFT decomposition (same order as ttft_s): time spent
    # waiting for a slot vs inside the request's prefill call
    queue_wait_s: list[float] = dataclasses.field(default_factory=list)
    prefill_share_s: list[float] = dataclasses.field(default_factory=list)
    # goodput fields (filled by generate): useful tokens/sec at the
    # configured TTFT SLO + the attainment fraction — the router tier's
    # dispatch inputs
    goodput: dict = dataclasses.field(default_factory=dict)

    def tokens_per_sec(self) -> float:
        return self.decode_tokens / max(self.decode_seconds, 1e-9)

    def ttft_percentiles(self) -> tuple[float, float]:
        if not self.ttft_s:
            return 0.0, 0.0
        p50, p95 = percentiles(self.ttft_s, (0.50, 0.95))
        return p50, p95

    def ttft_decomposition(self) -> dict:
        """Queue-wait vs prefill share of TTFT over finished requests —
        the serve_summary fields that make a fat TTFT p95 actionable
        (admit more slots vs speed up prefill)."""
        q50, q95 = percentiles(self.queue_wait_s, (0.50, 0.95))
        p50, p95 = percentiles(self.prefill_share_s, (0.50, 0.95))
        total = sum(self.ttft_s)
        return {
            "ttft_queue_p50_ms": round(q50 * 1e3, 1),
            "ttft_queue_p95_ms": round(q95 * 1e3, 1),
            "ttft_prefill_p50_ms": round(p50 * 1e3, 1),
            "ttft_prefill_p95_ms": round(p95 * 1e3, 1),
            "ttft_queue_share": round(sum(self.queue_wait_s) / total, 4) if total else 0.0,
            "ttft_prefill_share": round(sum(self.prefill_share_s) / total, 4) if total else 0.0,
        }


def compute_goodput(
    ttft_s: Sequence[float | None],
    tokens_out: Sequence[int],
    *,
    wall_s: float,
    ttft_slo_ms: float,
    n_chips: int,
) -> dict:
    """Goodput: USEFUL tokens per wall second, + SLO attainment.

    Useful = tokens of requests whose first token met the TTFT SLO (all
    FINISHED requests when no SLO is set — ``ttft_s[i] is None`` marks an
    unfinished request); wall = submit → batch done, so queue-wait and
    prefill stalls cost goodput the way they cost a user.
    ``slo_attainment`` is the fraction of finished requests served within
    the SLO — the router tier's per-replica health signal.  Pure
    host-float arithmetic; shared by the engine summary and tests so the
    numbers are pinnable."""
    wall_s = max(float(wall_s), 1e-9)
    slo_s = float(ttft_slo_ms) / 1e3
    finished = [
        (i, t) for i, t in enumerate(ttft_s) if t is not None
    ]
    met = [i for i, t in finished if slo_s <= 0 or t <= slo_s]
    useful = sum(int(tokens_out[i]) for i in met)
    out = {
        "goodput_tokens_per_sec": round(useful / wall_s, 1),
        "goodput_tokens_per_sec_chip": round(useful / wall_s / max(n_chips, 1), 1),
    }
    if slo_s > 0:
        out["ttft_slo_ms"] = round(float(ttft_slo_ms), 1)
        out["slo_attainment"] = (
            round(len(met) / len(finished), 4) if finished else 0.0
        )
    return out


def device_peak_bytes() -> int | None:
    """Peak allocator bytes where the backend supports ``memory_stats``
    (TPU/GPU); None on CPU — callers fall back to the static account,
    which is why the capacity gauges never claim a live number they
    didn't measure.  Delegates to the one raw-read owner
    (obs/memprof.py, repo-lint rule 15)."""
    try:
        from distributed_llms_example_tpu.obs import memprof

        stats = memprof.hbm_stats()
    except Exception:
        return None
    if not stats:
        return None
    return max(s["peak_bytes_in_use"] for s in stats)


# what a decode round of a model with experts appends to its token vector,
# summed (hit, assignments) or maximised (max_load) over the expert layers:
# experts that received a row, the largest expert's rows, all rows routed.
# Every slot of the round is routed, idle ones too: the program computes them.
MOE_COUNTERS = ("moe_experts_hit", "moe_max_load", "moe_assignments")
# The summary's byte account of the flat cache: a leaf that is not K/V (or its
# int8 scales) by the kind it is counted under.
CACHE_BYTES_KIND = {
    "conv_state": "conv_state_bytes",
    "retention_state": "retention_state_bytes",
    "retention_norm": "retention_state_bytes",
    "ssm_state": "ssm_state_bytes",
}


class UnsupportedServeMode(ValueError):
    """A ``ServeConfig`` mode this model's cache state cannot run under."""


class ServingEngine:
    """Greedy continuous-batching decode over a fixed slot set.

    ``model``/``config`` as in the Evaluator; ``mesh`` (or None) is the
    ambient mesh every program traces under.  ``is_seq2seq`` picks the
    adapter: encoder+cross-KV slots (BART/T5) or prompt-cache slots
    (LLaMA-family)."""

    @setup.phase("engine_init", awaits="serve")
    def __init__(self, model: Any, config: Any, mesh: Any,
                 serve: ServeConfig | None = None, *, is_seq2seq: bool = True):
        self.model, self.config, self.mesh = model, config, mesh
        self.kv_heads = cache_kv_heads(config)
        self.serve = serve or ServeConfig()
        self.is_seq2seq = is_seq2seq
        self.eos = config.eos_token_id
        self.pad = config.pad_token_id
        self.start = getattr(config, "decoder_start_token_id", None)
        self.forced_bos = getattr(config, "forced_bos_token_id", None)
        self.forced_eos = getattr(config, "forced_eos_token_id", None)
        self.L = self.serve.max_new_tokens
        self.S = self.serve.max_slots
        self.W = self.serve.max_source_length
        self.prefill_batch = self.serve.prefill_batch or self.S  # 0 = auto
        if self.prefill_batch < 1 or self.prefill_batch > self.S:
            raise ValueError(
                f"prefill_batch {self.prefill_batch} must be in "
                f"[1, max_slots={self.S}]"
            )
        if self.serve.kv_cache_dtype not in ("f32", "int8"):
            raise ValueError(
                f"kv_cache_dtype={self.serve.kv_cache_dtype!r}: "
                "must be 'f32' or 'int8'"
            )
        # admission buckets: ascending widths, max_source_length always the
        # implicit last bucket (every prompt fits somewhere)
        self.buckets = tuple(
            sorted({int(b) for b in self.serve.prefill_buckets if 0 < int(b) < self.W})
        ) + (self.W,)
        # a model whose cache holds state other than K/V (LFM2's conv state,
        # Brumby's retention state, Falcon-H1's state-space state and taps
        # beside its K/V), or K/V of a sliding window (Mellum's
        # ``window_key`` / ``window_value``: a ring of the last positions),
        # serves on the flat cache only: the block pool pages K/V by cache
        # position (a state leaf has none; a window layer's blocks would have to
        # be freed as they slide out); the speculative verify and the warm prefix
        # admission would have to roll a state back or continue one, or continue
        # a ring with several tokens at per-row positions
        flat_only = None
        if getattr(config, "has_recurrent_state", False):
            flat_only = (
                "a recurrent state (a convolution state or a state-space state beside "
                "K/V, or a retention state), which the block pool cannot page and a "
                "rejected draft cannot roll back"
            )
        elif getattr(config, "has_window_cache", False):
            flat_only = (
                "a window leaf (window_key / window_value: the K/V of a sliding-window "
                "layer's last positions, written as a ring), whose blocks the pool "
                "cannot free as they slide out and which a prefix hit or a draft's "
                "verify cannot continue at per-row positions"
            )
        if flat_only:
            for mode, on in (("paged_kv", self.serve.paged_kv),
                             ("prefix_cache", self.serve.prefix_cache),
                             ("spec_tokens", self.serve.spec_tokens)):
                if on:
                    raise UnsupportedServeMode(
                        f"{mode} is not supported for {type(config).__name__}: its cache "
                        f"holds {flat_only}; serve it on the flat cache (the default)"
                    )
        # experts: the decode round reports their load (moe_* counters)
        self.moe = getattr(config, "num_experts", 0) > 0
        # what a decode round streams (the ``slots_streamed`` counter): the
        # model's own predicate, the one that chooses its decode step
        self.streams_live_slots = bool(getattr(config, "decode_streams_live_slots", False))
        # a seq2seq model that can lay a slot's cross K/V as a cache keeps K/V
        # (its ``cross_kv(..., rows=True)``): the cross step then fetches live slots alone
        self.cross_kv_rows = is_seq2seq and bool(getattr(model, "cross_kv_rows", False))
        self.paged = bool(self.serve.paged_kv)
        self.pool: cache_pool.CachePool | None = None
        if self.paged:
            if self.is_seq2seq:
                raise ValueError(
                    "paged_kv applies to the causal KV cache (prompt + "
                    "decode tail in one buffer); the seq2seq slot state is "
                    "encoder output + cross-KV, which pages nothing — run "
                    "the flat cache for seq2seq families"
                )
            from distributed_llms_example_tpu.ops.flash_attention import auto_block

            width = self.W + self.L
            bs = self.serve.kv_block_size
            if not bs:
                # the block size must tile the cache width AND every
                # admission bucket (decode tiles start on tile boundaries),
                # so the auto default divides their gcd — kernel-preferred
                # tile when the gcd allows, else the gcd itself (8-aligned)
                g = math.gcd(width, *self.buckets)
                bs = auto_block(g) or (g if g >= 8 and g % 8 == 0 else 0)
            if not bs or width % bs:
                raise ValueError(
                    f"kv_block_size={self.serve.kv_block_size} does not tile "
                    f"the cache width {width} (prompt {self.W} + decode "
                    f"{self.L}); pass an explicit 8-aligned divisor of "
                    f"gcd(width, buckets) = "
                    f"{math.gcd(width, *self.buckets)}"
                )
            for b in self.buckets:
                if b % bs:
                    raise ValueError(
                        f"prefill bucket {b} is not a multiple of the kv "
                        f"block size {bs} — decode tiles must start on a "
                        "tile boundary"
                    )
            self.block_size = int(bs)
            self.n_tiles = width // self.block_size
            n_blocks = self.serve.pool_blocks or self.S * self.n_tiles
            worst = cache_pool.blocks_needed(self.W, self.L, self.block_size)
            if n_blocks < worst:
                raise ValueError(
                    f"pool_blocks={n_blocks} cannot hold even one "
                    f"worst-case request ({worst} blocks at block size "
                    f"{self.block_size}) — admission would livelock"
                )
            self.pool = cache_pool.CachePool(n_blocks, self.block_size)
        self.prefix = bool(self.serve.prefix_cache)
        if self.prefix and not self.paged:
            raise ValueError(
                "prefix_cache shares paged pool blocks — it requires "
                "paged_kv (the flat cache has no block identity to share)"
            )
        # speculative decode (serving/spec.py): the verify q block is
        # spec_tokens + 1 rows, capped by the flash-decode kernel's q-row
        # limit (ops/flash_attention.py MAX_DECODE_Q_ROWS)
        self.spec = int(self.serve.spec_tokens or 0)
        self.drafter: spec_decode.DraftRunner | None = None
        if self.spec:
            from distributed_llms_example_tpu.core.config import (
                SPEC_MAX_DRAFT_TOKENS,
            )

            if self.is_seq2seq:
                raise ValueError(
                    "spec_tokens applies to causal decode (the verify q "
                    "block rides the causal decode cache's staggered "
                    "per-row offsets); seq2seq families run plain decode"
                )
            if not 1 <= self.spec <= SPEC_MAX_DRAFT_TOKENS:
                raise ValueError(
                    f"spec_tokens={self.spec} must be in "
                    f"[1, {SPEC_MAX_DRAFT_TOKENS}]: the verify step "
                    "scores spec_tokens + 1 positions in one decode call "
                    "and the flash decode q block caps at "
                    f"{SPEC_MAX_DRAFT_TOKENS + 1} rows"
                )
        mesh_axes = dict(mesh.shape) if mesh is not None else {}
        # known-bad serving compositions are matrix rows, not scattered
        # raises — same table the trainer/lint consult
        from distributed_llms_example_tpu.analysis.composition import (
            validate_composition,
        )

        validate_composition(
            family=None, schedule=None, mesh_axes=mesh_axes,
            flags=("decode", "seq2seq" if is_seq2seq else "causal"),
        )
        batch_shards = 1
        for a in BATCH_AXES:
            batch_shards *= mesh_axes.get(a, 1)
        for what, n in (("max_slots", self.S), ("prefill_batch", self.prefill_batch)):
            if n % max(batch_shards, 1):
                raise ValueError(
                    f"{what}={n} must divide evenly over the mesh's "
                    f"{batch_shards} batch shards (data×fsdp×expert) — "
                    "uneven slot rows cannot shard"
                )
        # the row counts an admission wave is compiled at: the fewest rows
        # the mesh can shard, and the cap.  Below the knee nearly every
        # wave holds one request, and a wave of prefill_batch rows would
        # compute rows nobody sent while every live slot waits for it
        self.wave_sizes = tuple(sorted({batch_shards, self.prefill_batch}))
        # per-program Python trace counts, a test's contract: equal before and
        # after traffic means no program was traced again (AOT-warmed buckets,
        # fixed-shape churn).  What a trace, its lowering and its compile or
        # cache load COST, and whether the cache held, is the set-up account's
        # (obs/setup.py: seconds by program; ``late_compile`` after ready)
        self.trace_counts: dict[str, int] = {}
        self._warmed = False
        self._slot_cache = None  # _slot_cache_shapes' memo
        with setup.span("engine_build"):  # the drafter if any, and the programs (jit wrappers: nothing traces yet)
            if self.spec and self.serve.spec_draft_model:
                from distributed_llms_example_tpu.models.registry import load_model

                dm = load_model(self.serve.spec_draft_model)
                if dm.is_seq2seq:
                    raise ValueError(
                        f"spec_draft_model={self.serve.spec_draft_model!r} is "
                        "seq2seq — the draft model proposes causal decode "
                        "tokens, so it must be a causal family"
                    )
                if dm.config.vocab_size != config.vocab_size:
                    raise ValueError(
                        f"spec_draft_model={self.serve.spec_draft_model!r} "
                        f"vocab {dm.config.vocab_size} != target vocab "
                        f"{config.vocab_size} — draft proposals are token ids "
                        "compared against the target argmax, so the vocabs "
                        "must be the same id space"
                    )
                self.drafter = spec_decode.DraftRunner(
                    dm, slots=self.S, src_width=self.W, max_new=self.L,
                    buckets=self.buckets, wave_sizes=self.wave_sizes,
                    k=self.spec, pad=self.pad,
                    kv_cache_dtype=self.serve.kv_cache_dtype, wrap=self._wrap,
                )
            self._build_programs()
        self.last_stats: ServeStats | None = None

    # ------------------------------------------------------------ programs
    def _wrap(self, fn, donate: tuple[int, ...] = (), name: str = ""):
        # donate the slot-state buffers where the backend supports it: the
        # engine holds the only reference and rebinds the result, so the
        # per-step cache update happens in place instead of copying the
        # whole serving state every token (CPU lacks donation — keep the
        # test backend quiet)
        if jax.default_backend() == "cpu":
            donate = ()
        name = name or getattr(fn, "__name__", "program")

        def counted(*args):
            # runs at TRACE time only: one bump per compiled specialization
            self.trace_counts[name] = self.trace_counts.get(name, 0) + 1
            return fn(*args)

        # the program's name on the profiler's XLA Modules line: jit_serve_<name>
        counted.__name__ = counted.__qualname__ = f"serve_{name}"
        jitted = jax.jit(counted, donate_argnums=donate)

        def under_contexts(call):
            def run(*args):
                with activation_mesh(self.mesh), kv_cache_context(
                    self.serve.kv_cache_dtype
                ):
                    return call(*args)

            return run

        run = under_contexts(jitted)
        # the same program lowered for abstract arguments, not run: what a
        # compile for a described chip reads (tests/test_chip_compile.py)
        run.lower = under_contexts(jitted.lower)
        # its output shapes, from the trace a later call with such arguments reuses
        run.eval_shape = under_contexts(jitted.eval_shape)
        return run

    def wave_rows(self, n: int) -> int:
        """Rows of the program that admits a chunk of ``n`` requests: the
        small compiled size where they fit it, else ``prefill_batch``.  A
        row past ``n`` parks at slot ``S`` (its writes drop) and was
        mask-invisible to the others, so the size changes no served token."""
        return self.wave_sizes[0] if n <= self.wave_sizes[0] else self.prefill_batch

    def step_reads(self, leaf, ring: bool = False) -> tuple[int, int]:
        """How a decode round's attention reads the K/V leaf ``leaf`` (slots,
        length, kv_heads x head_dim): (length, tile).  ``tile`` > 0: the decode
        kernel runs the step (``ops/mha.py`` ``select_cached_step``, the
        function that routes it, asked what the layer asks) and fetches of a
        live slot the tiles of that many positions up to its last one (a
        ``ring`` leaf: tile = length, the live slot's ring whole) and of an
        idle slot nothing; 0: XLA's path, every slot's whole leaf."""
        from distributed_llms_example_tpu.ops.flash_attention import decode_block
        from distributed_llms_example_tpu.ops.mha import select_cached_step

        length, lanes = int(leaf.shape[1]), int(leaf.shape[2])
        cfg = self.config
        heads = next(int(n) for n in (getattr(cfg, a, None) for a in (
            "num_attention_heads", "decoder_attention_heads", "num_heads")) if n)
        kernel = select_cached_step(
            getattr(cfg, "attention_impl", "auto"), batch=self.S, heads=heads, kv_heads=self.kv_heads,
            head_dim=lanes // self.kv_heads, q_len=1, kv_len=length, mesh=self.mesh, kv_dtype=leaf.dtype,
        )[0] == "flash_decode"
        return length, (length if ring else decode_block(length)) if kernel else 0

    def positions_read(self, reads, last: np.ndarray) -> int:
        """K/V positions a decode round's attention fetches, over the leaves
        ``reads`` ((length, tile) -> how many, ``step_reads``), the live slots'
        last positions being ``last``: whole tiles of the live slots up to the
        last live one where the kernel runs (at most the leaf: a ring is read
        whole from wherever the slot's position stands), every slot's leaf elsewhere."""
        return int(sum(
            n * (int(np.minimum((last // tile + 1) * tile, length).sum()) if tile else self.S * length)
            for (length, tile), n in reads
        ))

    def _build_programs(self) -> None:
        model, L, S = self.model, self.L, self.S

        if self.is_seq2seq:
            # the cross K/V a slot keeps for its decode steps: (row, length, heads x
            # head_dim) like the self cache where the model's ``cross_kv`` lays it
            # so (``cross_kv_rows``: its cross step then goes the self step's way,
            # the decode kernel over live slots), else (row, heads, length, head_dim)
            rows_kw = {"rows": True} if self.cross_kv_rows else {}

            def prefill(params, ids, mask):
                enc = model.apply({"params": params}, ids, mask, method="encode")
                ckv = constrain_cache(model.apply({"params": params}, enc, method="cross_kv", **rows_kw), self.kv_heads)
                return enc, mask, ckv

            def admit(state, enc, mask, ckv, slot_idx):
                put = lambda dst, src: dst.at[slot_idx].set(src, mode="drop")  # noqa: E731
                # bucket-width chunks pad to the slot width here, inside
                # the (per-bucket-compiled) admit program
                enc = cache_pool.pad_axis(enc, 1, self.W)
                mask = cache_pool.pad_axis(mask, 1, self.W)
                ckv = jax.tree.map(  # the length axis by the leaf's rank
                    lambda x: cache_pool.pad_axis(x, {4: 2, 3: 1}[x.ndim], self.W), ckv,
                )
                return {
                    **state,
                    "enc": put(state["enc"], enc),
                    "enc_mask": put(state["enc_mask"], mask),
                    "ckv": jax.tree.map(put, state["ckv"], ckv),
                    "last": state["last"].at[slot_idx].set(
                        jnp.full((slot_idx.shape[0], 1), self.start, jnp.int32),
                        mode="drop",
                    ),
                }

            def step(params, state, offsets, active):
                # idle slots park at L: their cache writes drop
                # (mode="drop") and their tokens are masked to pad below
                offs = jnp.where(active, offsets, L)
                logits, mut = model.apply(
                    {"params": params, "cache": state["cache"]},
                    state["last"],
                    state["enc"],
                    state["enc_mask"],
                    use_cache=True,
                    cache_offset=offs,
                    max_kv_len=L,
                    cross_kv=state["ckv"],
                    method="decode",
                    mutable=["cache"],
                )
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                if self.forced_bos is not None:
                    nxt = jnp.where(offs == 0, self.forced_bos, nxt)
                if self.forced_eos is not None:
                    nxt = jnp.where(offs == L - 1, self.forced_eos, nxt)
                nxt = jnp.where(active, nxt, self.pad)
                return nxt, {
                    **state,
                    "cache": constrain_cache(mut["cache"], self.kv_heads),
                    "last": nxt[:, None],
                }
        else:
            def prefill(params, ids, mask):
                # the chunk's cache, by leaf, from the slots' own shapes: a
                # row a request, K/V as long as this bucket's prompt + tail
                rows, width = ids.shape[0], ids.shape[1] + L

                def chunk(path, a):
                    shape = list(a.shape)
                    if shape:
                        shape[0] = rows
                    axis = CACHE_LENGTH_AXIS.get(cache_leaf_name(path))
                    if axis is not None:
                        shape[axis] = width
                    return jax.ShapeDtypeStruct(tuple(shape), a.dtype)

                cache, full_mask, lengths, first = _causal_prefill(
                    model, params, ids, mask, L,
                    jax.tree_util.tree_map_with_path(chunk, self._slot_cache_shapes(params)),
                )
                return cache, full_mask, lengths, jnp.argmax(first, axis=-1).astype(jnp.int32)

            width_full = self.W + L

            if self.paged:
                n_blocks, bs = self.pool.num_blocks, self.block_size

                def admit(state, cache, full_mask, first_tok, slot_idx,
                          admit_blocks):
                    put = lambda dst, src: (  # noqa: E731
                        dst.at[slot_idx].set(src, mode="drop") if dst.ndim > 0 else dst
                    )
                    return {
                        **state,
                        "pool": cache_pool.scatter_admit(
                            state["pool"], cache, admit_blocks, bs
                        ),
                        "mask": put(state["mask"], cache_pool.pad_axis(full_mask, 1, width_full)),
                        "last": put(state["last"], first_tok),
                    }

                def warm_admit(params, state, ids_tail, mask_full, start,
                               tail_last, slot_idx, block_tables,
                               admit_blocks):
                    """Warm admission: the prompt's longest cached chain is
                    already pool-resident, so the model runs over ONLY the
                    uncached tail (``ids_tail``, at the tail bucket width)
                    against a gathered slot view — per-row absolute
                    positions starting at ``start`` (= cached prefix
                    length), per-row multi-token cache writes (the mha
                    ``cache_positions`` span contract).  The first output
                    token reads off the last valid tail position's logits,
                    exactly where cold prefill reads it; only fresh tail
                    tiles scatter back (``admit_blocks`` sentinels the
                    shared chain, which is never written)."""
                    view = constrain_cache(
                        cache_pool.gather_cache(state["pool"], block_tables), self.kv_heads
                    )
                    positions = start[:, None] + jnp.arange(ids_tail.shape[1])[None, :]
                    logits, mut = model.apply(
                        {"params": params, "cache": view},
                        ids_tail,
                        mask_full,
                        use_cache=True,
                        positions=positions,
                        cache_positions=start,
                        mutable=["cache"],
                    )
                    first = jnp.take_along_axis(
                        logits, tail_last[:, None, None], axis=1
                    )[:, 0, :]
                    first_tok = jnp.argmax(first, axis=-1).astype(jnp.int32)
                    put = lambda dst, src: (  # noqa: E731
                        dst.at[slot_idx].set(src, mode="drop") if dst.ndim > 0 else dst
                    )
                    return first_tok, {
                        **state,
                        "pool": cache_pool.scatter_admit(
                            state["pool"], mut["cache"], admit_blocks, bs
                        ),
                        "mask": put(state["mask"], mask_full),
                        "last": put(state["last"], first_tok),
                    }

                self._warm_admit_core = warm_admit

                def step(params, state, block_tables, write_pos, rope_pos, active):
                    width = state["mask"].shape[1]
                    offs = jnp.where(active, write_pos, width)
                    mask = state["mask"].at[jnp.arange(S), offs].set(1, mode="drop")
                    # the slot view is a step-transient: only the pool is
                    # resident between steps (serving/cache_pool.py)
                    cache = constrain_cache(
                        cache_pool.gather_cache(state["pool"], block_tables), self.kv_heads
                    )
                    logits, mut = model.apply(
                        {"params": params, "cache": cache},
                        state["last"][:, None],
                        mask,
                        use_cache=True,
                        positions=rope_pos[:, None],
                        cache_positions=offs,
                        mutable=["cache"],
                    )
                    nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                    nxt = jnp.where(active, nxt, self.pad)
                    pool = cache_pool.scatter_step(
                        state["pool"], mut["cache"], block_tables, offs,
                        num_blocks=n_blocks, block_size=bs,
                    )
                    return nxt, {
                        **state,
                        "pool": pool,
                        "mask": mask,
                        "last": nxt,
                    }
            else:
                def admit(state, cache, full_mask, first_tok, slot_idx):
                    put = lambda dst, src: (  # noqa: E731
                        dst.at[slot_idx].set(src, mode="drop") if dst.ndim > 0 else dst
                    )
                    return {
                        **state,
                        "cache": jax.tree.map(put, state["cache"], cache_pool.pad_cache_length(cache, width_full)),
                        "mask": put(state["mask"], cache_pool.pad_axis(full_mask, 1, width_full)),
                        "last": put(state["last"], first_tok),
                    }

                def step(params, state, write_pos, rope_pos, active):
                    width = state["mask"].shape[1]
                    offs = jnp.where(active, write_pos, width)
                    mask = state["mask"].at[jnp.arange(S), offs].set(1, mode="drop")
                    logits, mut = model.apply(
                        {"params": params, "cache": state["cache"]},
                        state["last"][:, None],
                        mask,
                        use_cache=True,
                        positions=rope_pos[:, None],
                        cache_positions=offs,
                        mutable=["cache", "moe_stats"] if self.moe else ["cache"],
                    )
                    nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                    nxt = jnp.where(active, nxt, self.pad)
                    state = {
                        **state,
                        "cache": constrain_cache(mut["cache"], self.kv_heads),
                        "mask": mask,
                        "last": nxt,
                    }
                    if self.moe:
                        # the round's expert load rides the token vector's tail,
                        # so that one fetch brings both (MOE_COUNTERS' order)
                        load = jnp.stack(jax.tree.leaves(mut["moe_stats"]))  # (layers, E)
                        nxt = jnp.concatenate([nxt, jnp.stack(
                            [jnp.sum(load > 0), jnp.max(load), jnp.sum(load)]
                        ).astype(jnp.int32)])
                    return nxt, state

        self._prefill_core = prefill
        self._prefill = self._wrap(prefill, name="prefill")
        self._admit = self._wrap(admit, donate=(0,), name="admit")
        self._step = self._wrap(step, donate=(1,), name="decode_step")
        if self.paged and self.prefix:
            self._warm_admit = self._wrap(
                self._warm_admit_core, donate=(1,), name="warm_admit"
            )
        if self.spec:
            verify = spec_decode.build_verify(
                model, slots=S, k=self.spec, pad=self.pad,
                paged=self.paged,
                num_blocks=self.pool.num_blocks if self.paged else 0,
                block_size=self.block_size if self.paged else 0,
            )
            self._verify = self._wrap(verify, donate=(1,), name="spec_verify")

    # --------------------------------------------------------------- state
    def _leaf_spec(self, path: str, x):
        from jax.sharding import PartitionSpec as P

        from distributed_llms_example_tpu.parallel.sharding import (
            cache_leaf_spec,
            kv_leaf_spec,
        )

        mesh_axes = dict(self.mesh.shape)
        batch_shards = 1
        for a in BATCH_AXES:
            batch_shards *= mesh_axes.get(a, 1)
        nd = getattr(x, "ndim", 0)
        if nd == 0:
            return P()
        leaf = path.rsplit("/", 1)[-1]
        if path.startswith(("pool", "cache")):
            # the slot cache, or the shared block pool (blocks belong to
            # single slots, so the block dim never shards over the batch
            # axes — POOL_RULES): the ONE shared layout definition
            spec = cache_leaf_spec(leaf, x.shape, mesh_axes, self.kv_heads, pool=path.startswith("pool"))
            if spec is not None:
                return spec
        if nd == 4:  # precomputed cross K/V, (slots, heads, len, head_dim)
            return kv_leaf_spec(x.shape, mesh_axes)
        if nd == 3 and path.startswith("ckv"):  # (slots, len, heads x head_dim): the self cache's layout and spec
            return cache_leaf_spec("cached_key", x.shape, mesh_axes, self.kv_heads)
        batch = BATCH_AXES if x.shape[0] % max(batch_shards, 1) == 0 else None
        return P(batch, *([None] * (nd - 1)))

    def _place(self, tree):
        if self.mesh is None:
            return tree
        import jax.tree_util as jtu
        from jax.sharding import NamedSharding

        return jtu.tree_map_with_path(
            lambda p, x: jax.device_put(
                x, NamedSharding(self.mesh, self._leaf_spec(_path_str(p), x))
            ),
            tree,
        )

    def _slot_cache_shapes(self, params):
        """Abstract cache of the ``S`` slots of a causal model at full width:
        one abstract trace of ``model.init``, made once an engine.  The
        slot state is zeros of it, and every prefill program sizes its
        chunk's cache from it instead of tracing ``model.init`` again."""
        if self._slot_cache is None:
            with kv_cache_context(self.serve.kv_cache_dtype):
                self._slot_cache = causal_cache_shapes(
                    self.model, params, self.S, self.W + self.L
                )
        return self._slot_cache

    def _init_state(self, params) -> dict:
        S, W, L = self.S, self.W, self.L
        if self.is_seq2seq:
            # what a slot holds of the encoder is what a prefill row returns:
            # read from the small wave's own trace, which ``warm`` then runs
            ids = jnp.zeros((self.wave_sizes[0], W), jnp.int32)
            slots = lambda tree: jax.tree.map(  # noqa: E731 — a wave's rows -> S slots
                lambda a: jnp.zeros((S, *a.shape[1:]), a.dtype), tree
            )
            a_enc, a_mask, a_ckv = self._prefill.eval_shape(params, ids, ids)
            enc0, mask = slots(a_enc), slots(a_mask)
            with kv_cache_context(self.serve.kv_cache_dtype):
                cache = _init_cache(self.model, params, S, L, enc0, mask)
            state = {
                "cache": cache,
                "enc": enc0,
                "enc_mask": mask,
                "ckv": slots(a_ckv),
                "last": jnp.full((S, 1), self.pad, jnp.int32),
            }
        else:
            a_cache = self._slot_cache_shapes(params)
            state = {
                "mask": jnp.zeros((S, W + L), jnp.int32),
                "last": jnp.full((S,), self.pad, jnp.int32),
            }
            if self.paged:
                state["pool"] = cache_pool.pool_cache_tree(
                    a_cache, self.pool.num_blocks, self.block_size
                )
            else:
                state["cache"] = jax.tree.map(
                    lambda a: jnp.zeros(a.shape, a.dtype), a_cache
                )
        return self._place(state)

    # ------------------------------------------------------------ capacity
    def _state_byte_account(self, state) -> tuple[int, int]:
        """(resident bytes, per-block bytes) of the serving K/V state —
        static accounting over the cache/pool/enc/ckv leaves (masks and
        token vectors are noise).  per-block is 0 on the flat path."""
        if self.paged:
            kv = state["pool"]
            resident = cache_pool.tree_bytes(kv)
            per_block = cache_pool.block_bytes(kv, self.pool.num_blocks)
            return resident, per_block
        keys = ("cache", "enc", "ckv") if self.is_seq2seq else ("cache",)
        resident = sum(cache_pool.tree_bytes(state[k]) for k in keys if k in state)
        return resident, 0

    @setup.phase("warm")
    def warm(self, params, state) -> Any:
        """AOT-warm every compiled program before the first real request:
        one prefill+admit trace per bucket and wave size (zeros, all writes
        dropped via out-of-range slot indices) and one all-slots-idle decode
        step — so no request ever pays a compile, and the trace counts are
        pinned BEFORE traffic (``trace_counts``).  Returns the (possibly
        donated-and-rebound) state.

        Each program call is a child span of ``setup/warm`` (obs/setup.py):
        ``warm_prefill`` and ``warm_admit`` (counters ``rows``, ``bucket``; the
        prefix path's warm admission is a ``warm_admit`` too), ``warm_decode_step``
        and, with speculative decode, ``warm_verify``; the session opens
        ``warm_draft`` for the draft model.  A call returns once its program is
        traced, lowered and compiled or loaded: no span waits on the device."""
        if self._warmed:
            return state

        def call(span, program, *args, **counters):
            with setup.span(span) as sp:
                if counters:
                    sp.set(**counters)
                return program(*args)

        S = self.S
        width_full = self.W + self.L
        for rows, bucket in itertools.product(self.wave_sizes, self.buckets):
            at = {"rows": rows, "bucket": bucket}
            park = jnp.full((rows,), S, jnp.int32)  # out of range: every write drops
            ids = jnp.zeros((rows, bucket), jnp.int32)
            pre = call("warm_prefill", self._prefill, params, ids, ids, **at)
            if self.is_seq2seq:
                enc, pmask, ckv = pre
                state = call("warm_admit", self._admit, state, enc, pmask, ckv, park, **at)
            elif self.paged:
                cache, full_mask, _, first = pre
                ntc = (bucket + self.L) // self.block_size
                sentinel = jnp.full((rows * ntc,), self.pool.num_blocks, jnp.int32)
                state = call("warm_admit", self._admit, state, cache, full_mask, first, park, sentinel, **at)
            else:
                cache, full_mask, _, first = pre
                state = call("warm_admit", self._admit, state, cache, full_mask, first, park, **at)
            if self.paged and self.prefix:
                # the warm admission of a tail of this width, all writes dropped
                # (park slots, sentinel block tables, out-of-range starts)
                _, state = call(
                    "warm_admit", self._warm_admit,
                    params, state,
                    ids,
                    jnp.zeros((rows, width_full), jnp.int32),
                    jnp.full((rows,), width_full, jnp.int32),
                    jnp.zeros((rows,), jnp.int32),
                    park,
                    jnp.full((rows, self.n_tiles), self.pool.num_blocks, jnp.int32),
                    jnp.full((rows * self.n_tiles,), self.pool.num_blocks, jnp.int32),
                    **at,
                )
        idle = jnp.zeros((S,), bool)
        pos = jnp.zeros((S,), jnp.int32)
        if self.is_seq2seq:
            _, state = call("warm_decode_step", self._step, params, state, pos, idle)
        elif self.paged:
            bt = jnp.full((S, self.n_tiles), self.pool.num_blocks, jnp.int32)
            _, state = call("warm_decode_step", self._step, params, state, bt, pos, pos, idle)
        else:
            _, state = call("warm_decode_step", self._step, params, state, pos, pos, idle)
        if self.spec:
            # one all-idle verify round: the spec program joins the
            # zero-recompile contract alongside the plain step
            x0 = jnp.full((S, self.spec + 1), self.pad, jnp.int32)
            room0 = jnp.zeros((S,), jnp.int32)
            if self.paged:
                sbt = jnp.full(
                    (S, self.n_tiles), self.pool.num_blocks, jnp.int32
                )
                _, _, state = call("warm_verify", self._verify, params, state, x0, sbt, pos, pos, idle, room0)
            else:
                _, _, state = call("warm_verify", self._verify, params, state, x0, pos, pos, idle, room0)
        self._warmed = True
        return state

    # ---------------------------------------------------------------- loop
    @setup.phase("session_open", ready="serve")
    def open(self, params: Any, *, replica: int | None = None,
             spans: SpanRecorder | None = None) -> "ServeSession":
        """Open a stepwise serving session over this engine: ``submit``
        requests as they arrive, drive ``step()`` per scheduler round,
        ``finalize()`` at end of life.  ``generate`` below is the batch
        wrapper; the replica router (serving/router.py) drives one open
        session per replica.  ``replica`` stamps the serve events so the
        router tier's streams stay attributable per engine.  ``spans``
        replaces the session's span recorder (tests: a virtual clock and
        a fake annotation factory).

        Where the model's config states the dtype its weights are stored in
        (``param_dtype``), the session keeps the floating-point weights
        resident in it: a model published in bfloat16 is not served from a
        float32 copy that every use casts again.  A config that states none
        (BART, T5, LLaMA here) is served from ``params`` as handed in."""
        stated = getattr(self.config, "param_dtype", None)
        if stated is not None:
            dtype = jnp.dtype(stated)
            with setup.span("weights_resident"):
                params = jax.jit(lambda p: jax.tree.map(
                    lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, p
                ))(params)
        return ServeSession(self, params, replica=replica, spans=spans)

    def generate(
        self,
        params: Any,
        requests: Sequence[Sequence[int]],
        *,
        attention_masks: Sequence[Sequence[int]] | None = None,
        max_new: Sequence[int] | None = None,
    ) -> list[list[int]]:
        """Serve ``requests`` (token-id prompts, request order preserved)
        to completion; returns per-request generated ids (eos included when
        emitted).  ``max_new`` optionally caps each request below the
        engine-wide ``max_new_tokens`` (the per-request ``max_tokens`` of a
        real serving API — and the lever continuous batching exists for:
        a short request frees its slot when its last token is emitted).  Fills
        ``self.last_stats`` and emits serve_window / serve_summary obs
        events.  Thin wrapper over a ``ServeSession``: submit everything,
        step until drained, finalize."""
        if max_new is not None and len(max_new) != len(requests):
            raise ValueError(
                f"max_new has {len(max_new)} entries for {len(requests)} requests"
            )
        sess = self.open(params)
        for i, req in enumerate(requests):
            sess.submit(
                req,
                max_new=(max_new[i] if max_new is not None else None),
                attention_mask=(
                    attention_masks[i] if attention_masks is not None else None
                ),
            )
        while sess.has_work():
            sess.step()
        sess.finalize()
        return list(sess.outputs)


class ServeSession:
    """One serving lifetime over an engine, stepwise.

    The engine's former monolithic ``generate`` loop, split at the
    scheduler-round boundary so a tier ABOVE the engine can drive it:
    ``submit`` enqueues a request (any time, not just up front),
    ``step()`` runs one admit-then-decode round and returns the requests
    that finished during it, ``finalize()`` closes the books
    (serve_summary, ``engine.last_stats``).  All compiled programs, slot
    bookkeeping, byte accounting, and obs events are exactly the
    engine's — the split moves control flow, not semantics, which is why
    the engine-vs-static determinism pins keep covering every driver.

    **The round's order** (``step``): the host keeps one decode program
    queued behind the one that runs.  A round admits, dispatches decode
    program n+1, and only THEN fetches and emits the tokens of program n:
    nothing the dispatch needs is in those tokens (the next input token is
    on the device in ``state["last"]``, positions advance by one, and a slot
    leaves the dispatch mask by budget, which the host knows).  A slot that
    ends by EOS is found one round late: the token program n+1 computed for
    it is dropped at emit (``tokens_discarded``), and whatever that step
    wrote is overwritten whole by the slot's next admission, which the
    device runs after it.  A causal wave's first tokens are fetched after
    the decode dispatch behind it.  How far the host runs ahead follows from
    what a round's input depends on (``_depth``): a speculative round is
    built from the fetched tokens and stays in lockstep.

    **What a round says it read** (counters on ``serve/decode_dispatch``,
    all host arithmetic over what the dispatch already knows): ``slots_live``
    / ``slots_streamed`` (the slots that get a token, and those whose
    recurrent state the program moves: the live ones where the model's step
    kernel walks a live list, every one otherwise); for a flat causal cache
    ``kv_positions_live`` (the K/V positions the round's attention needs: a
    live slot's own, on a window leaf at most the window) and
    ``kv_positions_streamed`` (those its program FETCHES, by leaf:
    ``ServingEngine.step_reads`` asks the function that routes the step;
    where the decode kernel runs it, whole kv tiles of the live slots up to
    each one's write position and a window leaf's ring whole, else every
    slot's whole leaf); for a seq2seq model ``cross_positions_live`` /
    ``cross_positions_streamed``, the same two for the cross K/V (a live
    slot's source positions; their whole tiles where the slots keep the pair
    as a cache keeps K/V and the kernel runs the cross step, else slots x
    source width).  Streamed is never under live.

    The replica router (serving/router.py) opens one session per engine
    replica; ``progress`` (bumped on every admit chunk and decode step)
    is its per-replica heartbeat, ``take_pending`` is its drain path,
    and ``label`` lets it thread router-global request ids through the
    ``serve_request`` span stream."""

    def __init__(self, engine: ServingEngine, params: Any,
                 *, replica: int | None = None,
                 spans: SpanRecorder | None = None):
        import collections

        eng = self.eng = engine
        self.params = params
        self.replica = replica
        # host spans of each round's stages; a ring "step" is one round
        # period (round end to round end): its outermost span ``round``, the
        # rest the driver's time between rounds
        self.spans = spans if spans is not None else SpanRecorder(scope="serve")
        self.n_chips = max(jax.device_count(), 1)
        S = eng.S
        # per-request tables, session-local rid = index (grow on submit)
        self.requests: list[list[int]] = []
        self.attn_masks: list[Sequence[int] | None] = []
        self.budgets: list[int] = []
        self.labels: list[Any] = []
        self.outputs: list[list[int]] = []
        self.ttft: list[float | None] = []
        self.submit_t: list[float] = []
        # absolute arrival instant per request (perf_counter timeline).
        # Closed-loop drivers never pass one, so arrival == submit and the
        # arrival→submit queue delay reads 0; an open-loop driver
        # (serving/loadgen.py) stamps the SCHEDULED arrival, so the time a
        # request waited before the driver could even submit it becomes a
        # first-class, JSONL-visible queueing stage instead of vanishing
        self.arrival_t: list[float] = []
        self.first_tok_wall: list[float | None] = []
        self.admit_t: list[float | None] = []
        self.prefill_dt: list[float] = []
        self.pending: "collections.deque[int]" = collections.deque()
        self.stats = ServeStats()
        # the router's heartbeat: bumps on every admit chunk and decode
        # step — a replica whose counter stops moving while it has work
        # is stalled (live → suspect → dead in the router's machine)
        self.progress = 0
        # slot bookkeeping (the generate loop's former closure state)
        self.slot_req = np.full(S, -1, np.int64)  # request index per slot
        self.emitted = np.zeros(S, np.int64)  # tokens the host holds, per slot
        # tokens the device computes or has computed for the slot's request
        # that the host has not fetched yet: a causal wave's first token, the
        # round in flight; a slot's next position is ``emitted + ahead``
        self.ahead = np.zeros(S, np.int64)
        self.slot_budget = np.zeros(S, np.int64)  # the request's budget, per slot
        self.lengths = np.zeros(S, np.int64)  # true prompt lengths
        self.base = np.full(S, eng.W, np.int64)  # causal: decode tail start
        # a slot holds a request until its LAST token is emitted (not: computed)
        self.active = np.zeros(S, bool)
        # decode rounds dispatched and not fetched (at most ``_depth`` between
        # two calls of ``step``), and the waves whose first tokens are unfetched
        # (always fetched before the round that dispatched them returns)
        self._inflight: "collections.deque[dict]" = collections.deque()
        self._firsts: list[dict] = []
        self._tokens_t = float("-inf")  # when the host last held new tokens
        # paged bookkeeping: block ownership per slot + the block table
        # the step program reads (sentinel = num_blocks → reads fill
        # zeros, writes drop)
        self.slot_blocks: list[list[int]] = [[] for _ in range(S)]
        # prefix-cache bookkeeping: the slot's registered full-prompt
        # chain (root → tail order), a subset of slot_blocks — eviction
        # releases the chain tail-first so the LRU keeps roots longest
        # (a shorter prefix stays matchable after partial eviction)
        self.slot_chain: list[list[int]] = [[] for _ in range(S)]
        self.slot_bt = (
            np.full((S, eng.n_tiles), eng.pool.num_blocks, np.int32)
            if eng.paged
            else None
        )
        with setup.span("init_cache"):  # abstract traces of model.init, zeros, their placement
            self.state = eng._init_state(params)
        self.state = eng.warm(params, self.state)
        self.t_open = self.spans.clock()
        self.stats.cache_bytes_resident, self._per_block = (
            eng._state_byte_account(self.state)
        )
        # the flat cache's static bytes by kind of leaf (K/V with their int8
        # scales, conv state; retention state with its normaliser where the
        # model has one): metadata arithmetic, no device fetch
        by_kind = {"kv_bytes": 0, "conv_state_bytes": 0}
        window_bytes = 0
        # the lengths of the K/V leaves, a (K, V) pair an attention layer: what a
        # decode round's ``kv_positions_*`` counters sum over (flat causal cache)
        kv_lengths = []
        # the same leaves by how a round's step reads them (``step_reads``) -> how many
        kv_reads: collections.Counter = collections.Counter()
        for path, x in jax.tree_util.tree_leaves_with_path(self.state.get("cache", self.state.get("pool", {}))):
            leaf = cache_leaf_name(path)
            if leaf != "cache_index":  # a counter, not state
                kind = CACHE_BYTES_KIND.get(leaf, "kv_bytes")
                by_kind[kind] = by_kind.get(kind, 0) + int(np.prod(x.shape)) * x.dtype.itemsize
            if leaf in WINDOW_LEAVES:
                window_bytes += int(np.prod(x.shape)) * x.dtype.itemsize
            if leaf in ("cached_key", "window_key") and not (eng.paged or eng.is_seq2seq):
                kv_lengths.append(int(x.shape[1]))
                kv_reads[eng.step_reads(x, ring=leaf in WINDOW_LEAVES)] += 1
        if window_bytes:
            # a model with window layers: ``kv_bytes`` split by kind of leaf
            by_kind["kv_window_bytes"] = window_bytes
            by_kind["kv_full_bytes"] = by_kind["kv_bytes"] - window_bytes
        self._cache_bytes_by_kind = by_kind
        self._kv_lengths = np.asarray(kv_lengths, np.int64)
        self._kv_reads = sorted(kv_reads.items())
        # a seq2seq slot's cross K/V, a (K, V) pair a decoder layer, the same way
        # (a (slots, heads, length, head_dim) pair is XLA's to read, whole)
        self._cross_reads = sorted(collections.Counter(
            eng.step_reads(k) if k.ndim == 3 else (int(k.shape[2]), 0) for k, _ in self.state.get("ckv", ())
        ).items())
        if eng.paged and eng.prefix:
            # the device pool tensor was just re-zeroed (_init_state), so
            # any warm chains a PREVIOUS session retained now index
            # garbage — matching them would splice zeros into a prompt.
            # Warm content is session-lifetime state: drop it with it.
            eng.pool.drop_warm()
            if self._per_block:
                # warm-retention budget in BLOCKS, derived from the byte
                # budget once the per-block byte account exists (0 = off)
                eng.pool.warm_capacity = int(
                    eng.serve.prefix_cache_budget_gib * (1 << 30)
                    // self._per_block
                )
        # loaded-weight bytes for the shared memory account (metadata
        # arithmetic only — no device fetch)
        self.params_bytes = int(sum(
            int(np.prod(x.shape)) * x.dtype.itemsize
            for x in jax.tree.leaves(params)
        ))
        self._bpt_samples: list[float] = []
        self._win_tokens, self._win_occ = 0, 0.0
        self._win_t0 = self.t_open
        self._win_prefill, self._win_decode = 0.0, 0.0
        # queueing-telemetry window counters: submissions vs completions
        # inside the window — their imbalance IS the queue growing
        self._win_arrivals, self._win_done = 0, 0
        # speculative decode: what each slot appended last round (the
        # draft model's catch-up feed next round; None until the slot's
        # first post-admit round) + the draft model's own cache state
        self._spec_fed: list[list[int] | None] = [None] * S
        self.draft_state = None
        if eng.drafter is not None:
            with setup.span("warm_draft"):
                self.draft_state = eng.drafter.init_state()
                self.draft_state = eng.drafter.warm(self.draft_state)
        self._win_spec_steps, self._win_spec_emitted = 0, 0
        # prefill waves by the rows their programs computed (the summary's)
        self._waves_by_rows: dict[int, int] = {}
        self._finalized = False

    # ------------------------------------------------------------- intake
    def submit(
        self,
        tokens: Sequence[int],
        *,
        max_new: int | None = None,
        attention_mask: Sequence[int] | None = None,
        label: Any = None,
        arrival: float | None = None,
    ) -> int:
        """Enqueue one request; returns the session-local rid.  ``label``
        (default: the rid) is what the ``serve_request`` event carries as
        ``request`` — the router passes its global request id.
        ``arrival`` (absolute perf_counter instant, default: now) is when
        the request ARRIVED, which under open-loop load precedes the
        submit — the gap is the driver-side queueing delay the
        ``serve_request`` record stamps as ``queue_delay_ms``."""
        if self._finalized:
            raise RuntimeError("session already finalized")
        rid = len(self.requests)
        now = self.spans.clock()
        self.requests.append(list(tokens))
        self.attn_masks.append(
            list(attention_mask) if attention_mask is not None else None
        )
        self.budgets.append(
            min(int(max_new), self.eng.L) if max_new is not None else self.eng.L
        )
        self.labels.append(rid if label is None else label)
        self.outputs.append([])
        self.ttft.append(None)
        self.submit_t.append(now)
        self.arrival_t.append(float(arrival) if arrival is not None else now)
        self.first_tok_wall.append(None)
        self.admit_t.append(None)
        self.prefill_dt.append(0.0)
        self.pending.append(rid)
        self.stats.sequences += 1
        self._win_arrivals += 1
        return rid

    def take_pending(self) -> list[Any]:
        """Remove every not-yet-admitted request and return their labels
        — the router's drain path (re-dispatch elsewhere; live slots keep
        decoding to completion here).  The removed requests' outputs stay
        empty and they never reach the serve_request stream."""
        labels = [self.labels[rid] for rid in self.pending]
        self.pending.clear()
        return labels

    # ------------------------------------------------------------- gauges
    @property
    def queue_depth(self) -> int:
        return len(self.pending)

    @property
    def active_count(self) -> int:
        return int(self.active.sum())

    def has_work(self) -> bool:
        """Something is queued, a slot's last token is unemitted, or a round
        is in flight (its tokens, if only dropped ones, are still to fetch)."""
        return bool(self.pending) or bool(self.active.any()) or bool(self._inflight)

    @property
    def _depth(self) -> int:
        """Decode rounds the host may leave unfetched when ``step`` returns:
        decided by what the next round's input is built from, not by a flag.
        A plain greedy round (flat or paged) needs nothing of the fetched
        tokens: 1.  A speculative round drafts from ``outputs[rid][-1]`` and
        the request's n-gram history, i.e. from the fetched tokens: 0.
        Paged + prefix runs ahead too: a dropped token's write lands at
        ``base + emitted - 1 >= bucket``, in the slot's own decode-tail blocks,
        and the prefix index keeps prompt blocks alone (positions < prompt)."""
        return 0 if self.eng.spec else 1

    def output(self, rid: int) -> list[int]:
        return self.outputs[rid]

    def first_token_wall(self, rid: int) -> float | None:
        """Absolute perf_counter instant of the request's first token —
        the router computes its own TTFT from its own submit instant."""
        return self.first_tok_wall[rid]

    def prefix_ref_violations(self) -> list[str]:
        """The refcount invariant, walked from THIS session's live block
        tables: every pool block's refcount must equal its live
        references (slot ownership) + warm-LRU membership.  Empty list =
        invariant holds; tests and the lint contract pin this after
        admit/evict/COW churn."""
        return self.eng.pool.ref_invariant_violations(
            [sb for sb in self.slot_blocks if sb]
        )

    def _bytes_in_use(self) -> int:
        if self.eng.paged:
            return self.eng.pool.blocks_in_use * self._per_block
        return self.stats.cache_bytes_resident

    def _live_tokens(self) -> int:
        # tokens the serving state holds for live requests: true prompt
        # + generated so far, per active slot
        return int((self.lengths[self.active] + self.emitted[self.active]).sum())

    # ---------------------------------------------------------- lifecycle
    def _finish_request(self, rid: int, slot: int, now: float) -> None:
        """Evict-time lifecycle record — the trace exporter's feed and
        the post-hoc 'why was THIS request's TTFT fat' answer."""
        if not self.eng.serve.request_spans:
            return
        t_sub = self.submit_t[rid]
        t_arr = self.arrival_t[rid]
        t_admit = self.admit_t[rid] if self.admit_t[rid] is not None else t_sub
        queue_wait = t_admit - t_sub
        t = self.ttft[rid]
        record = {
            "event": "serve_request",
            "request": self.labels[rid],
            "slot": int(slot),
            # arrival→submit: the open-loop driver-side wait (0 under
            # closed-loop driving, where arrival is stamped == submit);
            # queue_wait_ms below is the submit→admit stage — total
            # queueing delay = queue_delay_ms + queue_wait_ms, readable
            # off this one record
            "t_arrival_s": round(t_arr - self.t_open, 6),
            "queue_delay_ms": round((t_sub - t_arr) * 1e3, 3),
            "queue_wait_ms": round(queue_wait * 1e3, 3),
            "prefill_ms": round(self.prefill_dt[rid] * 1e3, 3),
            "ttft_ms": round(t * 1e3, 3) if t is not None else None,
            "decode_ms": round(
                (now - t_sub - (t if t is not None else queue_wait)) * 1e3, 3
            ),
            "tokens": len(self.outputs[rid]),
            "t_admit_s": round(t_admit - self.t_open, 6),
            "t_done_s": round(now - self.t_open, 6),
            "finished_at_step": int(self.stats.decode_steps),
        }
        if self.replica is not None:
            record["replica"] = int(self.replica)
        log_json(record)

    def _evict_slot(self, slot: int) -> None:
        """Free the slot: called when its request's last token is EMITTED
        (one round after the device computed it; a round still in flight for
        the slot finds another request there, or none, and drops its token)
        — and, paged, drop one reference per block it held (the
        evict-returns-all-blocks contract; under prefix_cache a shared
        block survives until its LAST holder evicts).  The
        registered chain releases tail-first so warm retention ages the
        DEEP end of a prefix out before its root — a partially-evicted
        chain still matches at shorter prefixes."""
        self.active[slot] = False
        self.slot_req[slot] = -1
        self.ahead[slot] = 0
        self._spec_fed[slot] = None
        self._win_done += 1
        if self.eng.paged and self.slot_blocks[slot]:
            chain = self.slot_chain[slot]
            if chain:
                in_chain = set(chain)
                rest = [b for b in self.slot_blocks[slot] if b not in in_chain]
                self.eng.pool.free(rest + list(reversed(chain)))
            else:
                self.eng.pool.free(self.slot_blocks[slot])
            self.slot_blocks[slot] = []
            self.slot_chain[slot] = []
            self.slot_bt[slot, :] = self.eng.pool.num_blocks

    def _admission_counters(self, prep, rids: list[int]) -> None:
        """Stamp an admitting ``admit_prep`` span with how many it admits and
        how long they queued: this span's start minus each request's arrival."""
        wait = sum(prep.t0 - self.arrival_t[rid] for rid in rids)
        prep.set(n=len(rids), queue_wait_us_sum=int(round(wait * 1e6)))

    def _prompt_rows(self, rids: Sequence[int], bucket: int):
        """``(ids, mask)`` of an admission chunk at ``bucket`` width: a row a
        request, at the compiled row count that holds them
        (``ServingEngine.wave_rows``); rows past the last are padding."""
        rows = self.eng.wave_rows(len(rids))
        ids = np.full((rows, bucket), self.eng.pad, np.int32)
        mask = np.zeros((rows, bucket), np.int32)
        for r, rid in enumerate(rids):
            toks = self.requests[rid][:bucket]
            ids[r, : len(toks)] = toks
            mask[r, : len(toks)] = 1
            if self.attn_masks[rid] is not None:
                m = self.attn_masks[rid][:bucket]
                mask[r, : len(m)] = m
        return ids, mask

    def _count_wave(self, dispatch, admitted: int, rows: int) -> None:
        """Stamp a ``prefill_dispatch`` span with the requests its wave admits
        and the rows its programs compute, and keep the summary's tally."""
        dispatch.set(rows=admitted, rows_computed=rows)
        self._waves_by_rows[rows] = self._waves_by_rows.get(rows, 0) + 1

    def _seat(self, rid: int, slot: int, length: int, base: int) -> None:
        """The slot's tables for the request an admission puts there.  A causal
        wave computes the request's first token: one token ahead of the host."""
        self.slot_req[slot] = rid
        self.emitted[slot] = 0
        self.ahead[slot] = 0 if self.eng.is_seq2seq else 1
        self.slot_budget[slot] = self.budgets[rid]
        self.lengths[slot] = length
        self.base[slot] = base
        self.active[slot] = True

    def _wave_dispatched(self, sp, first, rows: list[tuple[int, int, int]]) -> None:
        """Books of a wave whose programs are dispatched (``sp``, its closed
        ``prefill_dispatch`` span): ``rows`` are its (row, slot, rid).  Nothing
        waits for the device here: a causal wave's first tokens (``first``, on
        the device; already in ``state["last"]``) are fetched by
        ``_emit_firsts``; a seq2seq wave has none and its prefill share is the
        dispatch."""
        self.progress += 1
        for _, _, rid in rows:
            self.admit_t[rid] = sp.t0
        if first is None:
            self.stats.prefill_seconds += sp.dur
            self._win_prefill += sp.dur
            for _, _, rid in rows:
                self.prefill_dt[rid] = sp.dur
        else:
            self._firsts.append({"first": first, "rows": rows, "t0": sp.t0})
        self.stats.peak_cache_bytes_in_use = max(
            self.stats.peak_cache_bytes_in_use, self._bytes_in_use()
        )

    def _emit_firsts(self, finished: list) -> None:
        """Fetch and emit the first tokens of the waves dispatched this round:
        a request's first token is stamped when its wave ends.  A first token
        that is EOS, or a budget of one, ends the request here; the decode
        round dispatched behind the wave left a budget-of-one slot out, and
        drops the token it computed for one that ended by EOS."""
        for wave in self._firsts:
            with self.spans.span("token_fetch") as fetch:  # the host waits for the wave here
                first_h = np.asarray(jax.device_get(wave["first"]))
            now = self._tokens_t = fetch.end
            dt = now - wave["t0"]
            self.stats.prefill_seconds += dt
            self._win_prefill += dt
            with self.spans.span("emit"):
                for r, slot, rid in wave["rows"]:
                    self.prefill_dt[rid] = dt
                    self._emit_token(slot, rid, int(first_h[r]), now, finished)
        self._firsts.clear()

    def _emit_token(self, slot: int, rid: int, tok: int, now: float, finished: list) -> None:
        """One token the host now holds, appended to its request; the slot is
        free when that was the request's last (EOS, or its budget)."""
        self.outputs[rid].append(tok)
        if self.ttft[rid] is None:
            self.ttft[rid] = now - self.submit_t[rid]
            self.first_tok_wall[rid] = now
        self.emitted[slot] += 1
        self.ahead[slot] -= 1
        if tok == self.eng.eos or self.emitted[slot] >= self.budgets[rid]:
            self._evict_slot(slot)  # its last token is emitted: slot (and its blocks) free
            self._finish_request(rid, slot, now)
            finished.append(rid)

    def _admit_now(self) -> None:
        """Admit queued requests into free slots: one prefill wave, its
        programs dispatched and NOT waited for.  Prompt lengths are the sums of
        the mask the host built; a causal wave's first token stays on the
        device (``state["last"]``) for the decode round dispatched behind it
        and is fetched after that dispatch (``_emit_firsts``)."""
        eng = self.eng
        if eng.paged and eng.prefix:
            return self._admit_now_prefix()
        S, W, C = eng.S, eng.W, eng.prefill_batch
        with self.spans.span("admit_prep") as prep:
            free = [i for i in range(S) if not self.active[i]]
            n = min(len(free), C, len(self.pending))
            if n == 0:
                return
            plen = lambda rid: min(len(self.requests[rid]), W)  # noqa: E731
            if eng.paged:
                # shrink the chunk until the free list funds it: admission
                # DEFERS on a short pool instead of over-committing — every
                # eviction frees blocks, so deferred requests admit later
                while n > 0:
                    needed = sum(
                        cache_pool.blocks_needed(
                            plen(self.pending[i]), self.budgets[self.pending[i]],
                            eng.block_size,
                        )
                        for i in range(n)
                    )
                    if eng.pool.can_alloc(needed):
                        break
                    n -= 1
                if n == 0:
                    self.stats.admit_deferrals += 1
                    return
            reqs = [self.pending.popleft() for _ in range(n)]
            self._admission_counters(prep, reqs)
            # the smallest compiled admission width covering this chunk —
            # short prompts stop paying the max_source_length program
            bucket = next(
                b for b in eng.buckets if b >= max(plen(rid) for rid in reqs)
            )
            ids, mask = self._prompt_rows(reqs, bucket)
            rows = len(ids)
            slot_idx = np.full(rows, S, np.int32)  # padding rows drop
            slot_idx[:n] = free[:n]
            admit_rows = None
            if eng.paged:
                # fund + map each row's blocks BEFORE the program runs: the
                # flat (chunk × chunk-tiles) assignment carries sentinels for
                # tiles that must not copy (padding rows, prompt gap)
                ntc = (bucket + eng.L) // eng.block_size
                admit_rows = np.full((rows, ntc), eng.pool.num_blocks, np.int32)
                for r, rid in enumerate(reqs):
                    blocks = eng.pool.alloc(
                        cache_pool.blocks_needed(
                            plen(rid), self.budgets[rid], eng.block_size
                        )
                    )
                    assert blocks is not None  # funded above
                    slot = free[r]
                    self.slot_blocks[slot] = blocks
                    row = cache_pool.build_block_row(
                        eng.n_tiles, blocks,
                        prompt_len=plen(rid), bucket_width=bucket,
                        budget=self.budgets[rid], block_size=eng.block_size,
                        sentinel=eng.pool.num_blocks,
                    )
                    self.slot_bt[slot, :] = row
                    admit_rows[r, :] = row[:ntc]
            # a causal prompt's length is what the prefill program sums: the mask's ones
            lengths = mask.sum(axis=1)
            for r, rid in enumerate(reqs):
                self._seat(rid, free[r], plen(rid) if eng.is_seq2seq else int(lengths[r]), bucket)
        with self.spans.span("prefill_dispatch") as sp:
            self._count_wave(sp, n, rows)
            pre = eng._prefill(self.params, jnp.asarray(ids), jnp.asarray(mask))
            first = None
            if eng.is_seq2seq:
                enc, pmask, ckv = pre
                self.state = eng._admit(
                    self.state, enc, pmask, ckv, jnp.asarray(slot_idx)
                )
            else:
                cache, full_mask, _, first = pre
                if eng.paged:
                    self.state = eng._admit(
                        self.state, cache, full_mask, first, jnp.asarray(slot_idx),
                        jnp.asarray(admit_rows.reshape(-1)),
                    )
                else:
                    self.state = eng._admit(
                        self.state, cache, full_mask, first, jnp.asarray(slot_idx)
                    )
        self._wave_dispatched(sp, first, [(r, free[r], rid) for r, rid in enumerate(reqs)])

    def _admit_now_prefix(self) -> None:
        """Prefix-cache admission: per-row transactional packing (match
        the longest cached chain → acquire it → alloc only the tail,
        rolling the acquire back when the pool comes up short), then at
        most TWO dispatches — the plain cold-prefill chunk for rows with
        no cached prefix, followed by one warm-admit chunk that gathers
        the matched chains from the pool and prefills only the tails.

        Cold dispatches FIRST so a warm row may match a chain registered
        by a cold row of the SAME wave (the warm gather reads the cold
        scatter's pool state); a warm row must NOT match another warm
        row's fresh tail blocks — those land in the same program call it
        would gather from — so matches truncate before any block first
        written by this wave's warm chunk.  Neither dispatch is waited for
        (``_admit_now``): both chunks' first tokens are fetched after the
        decode round dispatched behind them."""
        eng = self.eng
        S, W, C = eng.S, eng.W, eng.prefill_batch
        bs, N = eng.block_size, eng.pool.num_blocks
        with self.spans.span("admit_prep") as prep:
            free = [i for i in range(S) if not self.active[i]]
            n = min(len(free), C, len(self.pending))
            if n == 0:
                return
            plen = lambda rid: min(len(self.requests[rid]), W)  # noqa: E731
            cold: list[tuple[int, int, int, list[str]]] = []  # rid, slot, p, hashes
            warm: list[dict] = []
            warm_written: set[int] = set()
            taken = 0
            while taken < n:
                rid = self.pending[0]
                p = plen(rid)
                budget = self.budgets[rid]
                toks = self.requests[rid][:p]
                # custom-masked prompts have no token-only identity: their KV
                # depends on the mask too, so they neither match nor register
                eligible = self.attn_masks[rid] is None
                hashes = cache_pool.chain_hashes(toks, bs) if eligible else []
                # keep >= 1 prompt token in the tail — the first output token
                # is computed from the LAST prompt position's logits, so a
                # fully-cached prompt still re-prefills its final block
                chain = (
                    eng.pool.match_chain(hashes[: (p - 1) // bs])
                    if eligible else []
                )
                for i, b in enumerate(chain):
                    if b in warm_written:
                        chain = chain[:i]
                        break
                k = len(chain)
                need = (
                    max(1, math.ceil(p / bs)) - k
                    + math.ceil(max(budget, 1) / bs)
                )
                if k:
                    eng.pool.acquire(chain)
                fresh = eng.pool.alloc(need)
                if fresh is None:
                    if k:
                        eng.pool.free(list(reversed(chain)))  # roll back
                    break
                self.pending.popleft()
                slot = free[taken]
                taken += 1
                blocks = chain + fresh
                self.slot_blocks[slot] = blocks
                full_tiles = p // bs
                if eligible and full_tiles:
                    eng.pool.register(blocks[:full_tiles], hashes[:full_tiles])
                    self.slot_chain[slot] = list(blocks[:full_tiles])
                else:
                    self.slot_chain[slot] = []
                if eligible:
                    self.stats.prefix_lookups += 1
                self.stats.prefill_tokens_total += p
                if k:
                    self.stats.prefix_hits += 1
                    self.stats.prefill_tokens_saved += k * bs
                    warm_written.update(blocks[k:full_tiles])
                    warm.append({
                        "rid": rid, "slot": slot, "p": p,
                        "bucket": next(b for b in eng.buckets if b >= p),
                        "start": k * bs, "tail": toks[k * bs:],
                    })
                else:
                    cold.append((rid, slot, p, hashes))
            if taken == 0:
                self.stats.admit_deferrals += 1
                return
            self._admission_counters(
                prep, [rid for rid, *_ in cold] + [w["rid"] for w in warm]
            )
        # ---- cold chunk: the plain prefill+admit path over cold rows
        if cold:
            with self.spans.span("admit_prep"):
                bucket = next(
                    b for b in eng.buckets if b >= max(p for _, _, p, _ in cold)
                )
                ids, mask = self._prompt_rows([rid for rid, *_ in cold], bucket)
                rows = len(ids)
                slot_idx = np.full(rows, S, np.int32)
                ntc = (bucket + eng.L) // bs
                admit_rows = np.full((rows, ntc), N, np.int32)
                for r, (rid, slot, p, _h) in enumerate(cold):
                    slot_idx[r] = slot
                    row = cache_pool.build_block_row(
                        eng.n_tiles, self.slot_blocks[slot],
                        prompt_len=p, bucket_width=bucket,
                        budget=self.budgets[rid], block_size=bs, sentinel=N,
                    )
                    self.slot_bt[slot, :] = row
                    admit_rows[r, :] = row[:ntc]
            with self.spans.span("prefill_dispatch") as sp:
                self._count_wave(sp, len(cold), rows)
                cache, full_mask, _, first = eng._prefill(
                    self.params, jnp.asarray(ids), jnp.asarray(mask)
                )
                self.state = eng._admit(
                    self.state, cache, full_mask, first, jnp.asarray(slot_idx),
                    jnp.asarray(admit_rows.reshape(-1)),
                )
            lengths = mask.sum(axis=1)  # what the prefill program sums
            for r, (rid, slot, _p, _h) in enumerate(cold):
                self._seat(rid, slot, int(lengths[r]), bucket)
            self._wave_dispatched(sp, first, [(r, slot, rid) for r, (rid, slot, _p, _h) in enumerate(cold)])
        # ---- warm chunk: gather matched chains, prefill only the tails
        if warm:
            with self.spans.span("admit_prep"):
                width_full = W + eng.L
                tail_bucket = next(
                    b for b in eng.buckets if b >= max(len(w["tail"]) for w in warm)
                )
                rows = eng.wave_rows(len(warm))
                ids_t = np.full((rows, tail_bucket), eng.pad, np.int32)
                mask_f = np.zeros((rows, width_full), np.int32)
                start = np.full(rows, width_full, np.int32)  # park rows write nowhere
                tail_last = np.zeros(rows, np.int32)
                slot_idx = np.full(rows, S, np.int32)
                bt = np.full((rows, eng.n_tiles), N, np.int32)
                admit_rows = np.full((rows, eng.n_tiles), N, np.int32)
                for r, wr in enumerate(warm):
                    slot = wr["slot"]
                    tail = wr["tail"]
                    ids_t[r, : len(tail)] = tail
                    mask_f[r, : wr["p"]] = 1
                    start[r] = wr["start"]
                    tail_last[r] = len(tail) - 1
                    slot_idx[r] = slot
                    row = cache_pool.build_block_row(
                        eng.n_tiles, self.slot_blocks[slot],
                        prompt_len=wr["p"], bucket_width=wr["bucket"],
                        budget=self.budgets[wr["rid"]], block_size=bs, sentinel=N,
                    )
                    self.slot_bt[slot, :] = row
                    bt[r, :] = row
                    # scatter ONLY the fresh tail prompt tiles back: the
                    # matched chain is immutable (shared), and decode tiles
                    # keep pool garbage until decode writes them (the
                    # poisoned-pool invariant — masked until valid)
                    k_tiles = wr["start"] // bs
                    full_tiles = max(1, math.ceil(wr["p"] / bs))
                    admit_rows[r, k_tiles:full_tiles] = row[k_tiles:full_tiles]
            with self.spans.span("prefill_dispatch") as sp:
                self._count_wave(sp, len(warm), rows)
                first_w, self.state = eng._warm_admit(
                    self.params, self.state,
                    jnp.asarray(ids_t), jnp.asarray(mask_f), jnp.asarray(start),
                    jnp.asarray(tail_last), jnp.asarray(slot_idx),
                    jnp.asarray(bt), jnp.asarray(admit_rows.reshape(-1)),
                )
            for wr in warm:
                self._seat(wr["rid"], wr["slot"], wr["p"], wr["bucket"])
            self._wave_dispatched(sp, first_w, [(r, wr["slot"], wr["rid"]) for r, wr in enumerate(warm)])

    def step(self) -> list[int]:
        """One scheduler round: admit into free slots (a slot is free when
        its request's last token has been EMITTED), dispatch the next decode
        program for every slot that still needs a token computed, then fetch
        and emit the tokens of the program dispatched the round BEFORE (and
        the first tokens of this round's causal wave).  So the first round
        after an idle spell emits no decode token and the last one dispatches
        nothing; a speculative session dispatches and fetches the same round
        (``_depth``).  Returns the session-local rids of requests that
        finished during this call: whose last token this call emitted
        (finish-at-prefill included).  ``outputs[rid]`` grows only with tokens
        the host holds and the request keeps.  The batch ``generate`` loop is
        ``while has_work(): step()``.  A RESOURCE_EXHAUSTED escaping the
        round trips the OOM forensics (obs/memprof.py): the postmortem
        bundle lands atomically, then the error re-raises — the session
        never swallows it."""
        try:
            return self._step_round()
        except Exception as e:
            self._oom_tripwire(e)
            raise

    def _memory_account(self) -> dict:
        """The serving tier's bucketed HBM account over the shared
        scheme: loaded weights in ``params``, the live cache/pool bytes
        (the capacity gauges' arithmetic) in ``kv_cache``."""
        from distributed_llms_example_tpu.obs import memprof

        return memprof.serving_account(
            params_bytes=self.params_bytes,
            kv_cache_bytes=self._bytes_in_use(),
            hbm_budget_gib=self.eng.serve.hbm_budget_gib,
        )

    def _oom_tripwire(self, e: BaseException) -> None:
        """Dump the memory postmortem when ``e`` is an OOM and a dump dir
        is configured; the caller re-raises either way."""
        out_dir = self.eng.serve.postmortem_dir
        if not out_dir:
            return
        from distributed_llms_example_tpu.obs import memprof

        if not memprof.is_resource_exhausted(e):
            return
        memprof.dump_postmortem(
            out_dir,
            reason=f"{type(e).__name__}: {str(e)[:300]}",
            step=self.stats.decode_steps,
            account=self._memory_account(),
        )

    def _spec_dispatch(self, offsets, rope):
        """Assemble one draft-then-verify round (in lockstep: nothing is ahead,
        every live slot's last token is in ``outputs``).  Drafts come from the
        n-gram self-drafter or the shrunk draft model; serving/spec.py
        owns BOTH drafters and all acceptance/rollback math (repo_lint
        rule 17) — this method only packs inputs and runs the compiled
        programs.  Returns device arrays ``(target_tokens (S, k+1),
        n_emit (S,))``: the round fetches them."""
        eng = self.eng
        K, S = eng.spec, eng.S
        x = np.full((S, K + 1), eng.pad, np.int32)
        room = np.zeros((S,), np.int32)
        live = np.nonzero(self.active)[0]
        for s in live:
            rid = int(self.slot_req[s])
            x[s, 0] = self.outputs[rid][-1]
            # remaining budget minus the always-emitted bonus token: the
            # verify clamp that keeps a round from decoding past
            # max_new_tokens (clamping truncates, never alters, output)
            room[s] = max(int(self.budgets[rid]) - int(self.emitted[s]) - 1, 0)
        if eng.drafter is not None:
            self._draft_admissions()
            fed = np.full((S, K + 1), eng.pad, np.int32)
            n_fed = np.zeros((S,), np.int32)
            pos0 = np.zeros((S,), np.int32)
            rope0 = np.zeros((S,), np.int32)
            for s in live:
                f = self._spec_fed[s]
                fed[s, : len(f)] = f
                n_fed[s] = len(f)
                pos0[s] = int(self.base[s]) + int(self.emitted[s]) - len(f)
                rope0[s] = int(self.lengths[s]) + int(self.emitted[s]) - len(f)
            drafts, self.draft_state = eng.drafter.round(
                self.draft_state, jnp.asarray(fed), jnp.asarray(n_fed),
                jnp.asarray(pos0), jnp.asarray(rope0),
                jnp.asarray(self.active),
            )
            dr = np.asarray(jax.device_get(drafts))
            x[live, 1:] = dr[live]
        else:
            hist = [
                self.requests[int(self.slot_req[s])]
                + self.outputs[int(self.slot_req[s])]
                if self.active[s]
                else None
                for s in range(S)
            ]
            x[:, 1:] = spec_decode.ngram_drafts(hist, K, eng.pad)
        if eng.paged:
            target, n_emit, self.state = eng._verify(
                self.params, self.state, jnp.asarray(x),
                jnp.asarray(self.slot_bt),
                jnp.asarray(offsets), jnp.asarray(rope),
                jnp.asarray(self.active), jnp.asarray(room),
            )
        else:
            target, n_emit, self.state = eng._verify(
                self.params, self.state, jnp.asarray(x),
                jnp.asarray(offsets), jnp.asarray(rope),
                jnp.asarray(self.active), jnp.asarray(room),
            )
        return target, n_emit

    def _draft_admissions(self) -> None:
        """Bring slots admitted this round into the draft model's cache:
        the target prefilled their prompts during admission, so the draft
        prefills the SAME prompts at the same bucket width into its own
        flat cache (full prompts even under warm prefix hits — the draft
        cache shares nothing) and the catch-up feed starts from the
        admission's first emitted token."""
        eng = self.eng
        need = [
            s for s in np.nonzero(self.active)[0] if self._spec_fed[s] is None
        ]
        if not need:
            return
        for s in need:
            self._spec_fed[s] = [self.outputs[int(self.slot_req[s])][-1]]
        import collections

        by_bucket = collections.defaultdict(list)
        for s in need:
            by_bucket[int(self.base[s])].append(s)
        C = eng.prefill_batch
        for bucket, slots_ in sorted(by_bucket.items()):
            for i in range(0, len(slots_), C):
                chunk = slots_[i : i + C]
                ids, mask = self._prompt_rows(
                    [int(self.slot_req[s]) for s in chunk], bucket
                )
                slot_idx = np.full(len(ids), eng.S, np.int32)
                slot_idx[: len(chunk)] = chunk
                self.draft_state = eng.drafter.admit_prompt(
                    self.draft_state, jnp.asarray(ids), jnp.asarray(mask),
                    jnp.asarray(slot_idx),
                )

    def _spec_append(self, toks, n_emit, now, finished) -> int:
        """Append one verify round's accepted-prefix + bonus tokens per
        live slot, with the SAME eos/budget eviction as the plain loop —
        a round whose accepted prefix crosses eos stops emitting there
        (trailing accepted tokens are discarded with the slot; greedy
        would never have decoded past eos either).  Returns the number of
        tokens actually appended."""
        eng, stats = self.eng, self.stats
        appended = 0
        slot_rounds = 0
        for slot in np.nonzero(self.active)[0]:
            rid = int(self.slot_req[slot])
            n = int(n_emit[slot])
            slot_rounds += 1
            stats.spec_drafted += eng.spec
            stats.spec_accepted += n - 1
            fed: list[int] = []
            evicted = False
            for j in range(n):
                tok = int(toks[slot, j])
                self.outputs[rid].append(tok)
                fed.append(tok)
                appended += 1
                if self.ttft[rid] is None:
                    self.ttft[rid] = now - self.submit_t[rid]
                    self.first_tok_wall[rid] = now
                self.emitted[slot] += 1
                if tok == eng.eos or self.emitted[slot] >= self.budgets[rid]:
                    self._evict_slot(slot)
                    self._finish_request(rid, slot, now)
                    finished.append(rid)
                    evicted = True
                    break
            self.ahead[slot] = 0  # the round's tokens are all emitted
            if not evicted:
                self._spec_fed[slot] = fed
        stats.spec_steps += 1
        stats.spec_slot_rounds += slot_rounds
        stats.spec_emitted += appended
        self._win_spec_steps += slot_rounds
        self._win_spec_emitted += appended
        return appended

    def _step_round(self) -> list[int]:
        if self._finalized:
            raise RuntimeError("session already finalized")
        with self.spans.span("round"):
            finished = self._round_stages()
        self.spans.step_complete()
        return finished

    def _round_stages(self) -> list[int]:
        """Admit, dispatch round n+1, then fetch and emit round n: one loop at
        the depth ``_depth`` gives.  At depth 0 (speculative) a wave's first
        tokens are fetched before the dispatch that is built from them, and
        the round dispatched is fetched before this returns.  A round that
        dispatches nothing (every live slot's last token is in flight) fetches
        what is in flight."""
        finished: list[int] = []
        depth = self._depth
        self._admit_now()
        if depth == 0:
            self._emit_firsts(finished)
        dispatched = self._dispatch_decode()
        while len(self._inflight) > (depth if dispatched else 0):
            self._fetch_and_emit(self._inflight.popleft(), finished)
        self._emit_firsts(finished)
        return finished

    def _dispatch_decode(self) -> bool:
        """Dispatch one decode program for the slots that still need a token
        computed: live, and not already computing their last by budget (exact:
        the host knows every budget).  Its inputs are what the host knows now:
        a slot's position is its emitted count plus its tokens ahead, its input
        token is on the device.  False when there is no such slot."""
        eng = self.eng
        held = self.emitted + self.ahead  # tokens of the request before this round's
        todo = self.active & (held < self.slot_budget)
        if not todo.any():
            return False
        offsets = (held if eng.is_seq2seq else self.base + held - 1).astype(np.int32)
        rope = (self.lengths + held - 1).astype(np.int32)
        with self.spans.span("decode_dispatch") as dispatch:
            if eng.spec:
                tokens = self._spec_dispatch(offsets, rope)
            elif eng.is_seq2seq:
                tokens, self.state = eng._step(
                    self.params, self.state, jnp.asarray(offsets), jnp.asarray(todo),
                )
            elif eng.paged:
                tokens, self.state = eng._step(
                    self.params, self.state,
                    jnp.asarray(self.slot_bt),
                    jnp.asarray(offsets), jnp.asarray(rope), jnp.asarray(todo),
                )
            else:
                tokens, self.state = eng._step(
                    self.params, self.state,
                    jnp.asarray(offsets), jnp.asarray(rope), jnp.asarray(todo),
                )
            # slots whose state the round moves: every one, live or not, except
            # where the decode program's steps walk the live slots alone
            n_live = int(todo.sum())
            ahead = len(self._inflight)  # the round before is unfetched
            counters = {"slots_live": n_live, "slots_streamed": n_live if eng.streams_live_slots else eng.S,
                        "ahead": ahead}
            if len(self._kv_lengths) and not eng.spec:
                # K/V positions the round's attention needs (a live slot's own, the
                # step's included; on a window leaf at most the window) against those
                # its program reads (``positions_read``), over the attention layers
                needed = (self.lengths + held)[todo].astype(np.int64)
                counters["kv_positions_live"] = int(np.minimum(needed[:, None], self._kv_lengths[None, :]).sum())
                counters["kv_positions_streamed"] = eng.positions_read(self._kv_reads, offsets[todo])
            if self._cross_reads:
                # the same for a seq2seq round's cross attention: a live slot's source
                # positions against what the cross step reads, over the decoder layers
                source = self.lengths[todo]
                counters["cross_positions_live"] = int(source.sum()) * len(self.state["ckv"])
                counters["cross_positions_streamed"] = eng.positions_read(self._cross_reads, source - 1)
            dispatch.set(**counters)
        slots = np.flatnonzero(todo)
        self.ahead[slots] += 1
        self.stats.rounds_ahead += ahead
        self.progress += 1
        # the round in flight: its tokens on the device, the slot -> rid it was
        # dispatched for (a slot freed since holds another request, or none),
        # its dispatch instant
        self._inflight.append({"tokens": tokens, "slots": slots, "rids": self.slot_req[slots],
                               "t0": dispatch.t0})
        return True

    def _fetch_and_emit(self, rnd: dict, finished: list) -> None:
        """Fetch a dispatched round's tokens (the host waits for the device
        here, and nowhere else in a plain round) and emit them.  A token whose
        slot no longer holds the request it was computed for is dropped: the
        request ended by EOS a round before (``tokens_discarded``)."""
        eng, stats = self.eng, self.stats
        with self.spans.span("token_fetch") as fetch:
            if eng.spec:
                spec_toks, spec_emit = (np.asarray(jax.device_get(x)) for x in rnd["tokens"])
            else:
                toks = np.asarray(jax.device_get(rnd["tokens"]))
                if len(toks) > eng.S:  # a flat round of a model with experts
                    fetch.set(**{k: int(v) for k, v in zip(MOE_COUNTERS, toks[eng.S:])})
        dt = fetch.end - max(rnd["t0"], self._tokens_t)
        self._tokens_t = fetch.end
        with self.spans.span("emit") as emit:
            now = emit.t0
            stats.decode_seconds += dt
            stats.decode_steps += 1
            self.progress += 1
            self._win_decode += dt
            n_computed = len(rnd["slots"])
            stats.slot_occupancy += n_computed / eng.S
            self._win_occ += n_computed / eng.S
            self._bpt_samples.append(
                self._bytes_in_use() / max(self._live_tokens(), 1)
            )
            if eng.spec:
                # a verify round appends 1..k+1 tokens per slot — the
                # accounting counts tokens actually emitted, so tok/s stays
                # an honest cross-mode comparison
                appended = self._spec_append(spec_toks, spec_emit, now, finished)
            else:
                appended = 0
                for slot, rid in zip(rnd["slots"].tolist(), rnd["rids"].tolist()):
                    if self.slot_req[slot] != rid:
                        stats.tokens_discarded += 1
                        continue
                    self._emit_token(slot, rid, int(toks[slot]), now, finished)
                    appended += 1
            stats.decode_tokens += appended
            self._win_tokens += appended
        every = eng.serve.log_every_steps
        if every and stats.decode_steps % every == 0:
            with self.spans.span("window_log"):
                self._log_window(now)

    def _log_window(self, now: float) -> None:
        eng, every = self.eng, self.eng.serve.log_every_steps
        w_dt = max(now - self._win_t0, 1e-9)
        window = {
            "event": "serve_window",
            "step": self.stats.decode_steps,
            "decode_tokens_per_sec": round(self._win_tokens / w_dt, 1),
            "decode_tokens_per_sec_chip": round(
                self._win_tokens / w_dt / self.n_chips, 1
            ),
            "slot_occupancy": round(self._win_occ / every, 4),
            "queue_depth": len(self.pending),
            # queueing telemetry: the window's offered vs served rate
            # and their imbalance — a sustained positive queue_growth
            # is the open-loop collapse signal (arrivals outpacing
            # service), visible live instead of post-hoc
            "arrival_rate_per_sec": round(self._win_arrivals / w_dt, 2),
            "service_rate_per_sec": round(self._win_done / w_dt, 2),
            "queue_growth": int(self._win_arrivals - self._win_done),
            # the window's wall split: admission prefill vs decode
            # steps — a window whose prefill share balloons is paying
            # admission on the decode critical path
            "prefill_ms": round(self._win_prefill * 1e3, 1),
            "decode_ms": round(self._win_decode * 1e3, 1),
            # capacity gauges: what the cache state holds RIGHT NOW
            # per live token — the number the paged pool shrinks
            "cache_bytes_in_use": self._bytes_in_use(),
            "cache_bytes_per_token": round(
                self._bytes_in_use() / max(self._live_tokens(), 1), 1
            ),
        }
        if eng.paged:
            window["pool_blocks_in_use"] = eng.pool.blocks_in_use
            window["pool_blocks_free"] = eng.pool.blocks_free
            if eng.prefix:
                # cumulative-to-date prefix-cache gauges: hit rate over
                # eligible admissions, prefill tokens served from the
                # pool instead of recomputed, and the warm set's bytes
                window["prefix_hit_rate"] = round(
                    self.stats.prefix_hits
                    / max(self.stats.prefix_lookups, 1), 4
                )
                window["prefill_tokens_saved_frac"] = round(
                    self.stats.prefill_tokens_saved
                    / max(self.stats.prefill_tokens_total, 1), 4
                )
                window["pool_blocks_warm"] = eng.pool.blocks_warm
                window["warm_bytes"] = (
                    eng.pool.blocks_warm * self._per_block
                )
        if eng.spec:
            # the speculative ledger live: window-local multi-token
            # yield + the cumulative draft acceptance rate
            window["accepted_tokens_per_step"] = round(
                self._win_spec_emitted / max(self._win_spec_steps, 1), 4
            )
            window["acceptance_rate"] = round(
                self.stats.spec_accepted
                / max(self.stats.spec_drafted, 1), 4
            )
        if self.replica is not None:
            window["replica"] = int(self.replica)
        log_json(window)
        self._win_tokens, self._win_t0, self._win_occ = 0, now, 0.0
        self._win_prefill, self._win_decode = 0.0, 0.0
        self._win_arrivals, self._win_done = 0, 0
        self._win_spec_steps, self._win_spec_emitted = 0, 0

    # ------------------------------------------------------------ closing
    def finalize(self) -> ServeStats:
        """Close the books: TTFT decomposition, goodput, the
        serve_summary event; sets ``engine.last_stats``.  Safe to call
        once per session; requests still pending (a drained replica) stay
        unfinished and count against goodput, never silently vanish."""
        if self._finalized:
            return self.stats
        while self._inflight:  # a round in flight: its tokens are served before the books close
            self._fetch_and_emit(self._inflight.popleft(), [])
        self._finalized = True
        eng, stats = self.eng, self.stats
        stats.ttft_s = [t for t in self.ttft if t is not None]
        # TTFT decomposition rows, kept in ttft_s order (finished requests)
        for rid, t in enumerate(self.ttft):
            if t is None:
                continue
            t_admit = (
                self.admit_t[rid]
                if self.admit_t[rid] is not None
                else self.submit_t[rid]
            )
            stats.queue_wait_s.append(t_admit - self.submit_t[rid])
            stats.prefill_share_s.append(self.prefill_dt[rid])
        stats.slot_occupancy = (
            stats.slot_occupancy / stats.decode_steps if stats.decode_steps else 0.0
        )
        stats.goodput = compute_goodput(
            self.ttft,
            [len(o) for o in self.outputs],
            wall_s=self.spans.clock() - self.t_open,
            ttft_slo_ms=eng.serve.ttft_slo_ms,
            n_chips=self.n_chips,
        )
        stats.bytes_per_live_token = (
            sum(self._bpt_samples) / len(self._bpt_samples)
            if self._bpt_samples
            else 0.0
        )
        p50, p95 = stats.ttft_percentiles()
        # arrival→submit delay percentiles over every request (0s under
        # closed-loop driving; the open-loop driver's queueing signature)
        qd50, qd95, qd99 = percentiles(
            [s - a for s, a in zip(self.submit_t, self.arrival_t)],
            (0.50, 0.95, 0.99),
        )
        summary = {
            "event": "serve_summary",
            "sequences": stats.sequences,
            "decode_steps": stats.decode_steps,
            "decode_tokens": stats.decode_tokens,
            "decode_tokens_per_sec": round(stats.tokens_per_sec(), 1),
            "decode_tokens_per_sec_chip": round(
                stats.tokens_per_sec() / self.n_chips, 1
            ),
            "ttft_p50_ms": round(p50 * 1e3, 1),
            "ttft_p95_ms": round(p95 * 1e3, 1),
            "queue_delay_p50_ms": round(qd50 * 1e3, 3),
            "queue_delay_p95_ms": round(qd95 * 1e3, 3),
            "queue_delay_p99_ms": round(qd99 * 1e3, 3),
            **stats.ttft_decomposition(),
            **stats.goodput,
            "slot_occupancy": round(stats.slot_occupancy, 4),
            "prefill_seconds": round(stats.prefill_seconds, 3),
            # the round's order: rounds dispatched with the round before
            # unfetched, and tokens computed for a slot that had ended by EOS
            "rounds_ahead": stats.rounds_ahead,
            "tokens_discarded": stats.tokens_discarded,
            # waves by the row count of the programs that ran them
            "prefill_waves_by_rows": {
                str(r): n for r, n in sorted(self._waves_by_rows.items())
            },
            "slots": eng.S,
            "chips": self.n_chips,
            # capacity block: config knobs + the measured static account —
            # so capacity claims are read off the log, not inferred
            "kv_cache_dtype": eng.serve.kv_cache_dtype,
            "paged_kv": eng.paged,
            "prefill_buckets": list(eng.buckets),
            "cache_bytes_resident": stats.cache_bytes_resident,
            **self._cache_bytes_by_kind,
            "peak_cache_bytes_in_use": stats.peak_cache_bytes_in_use,
            "cache_bytes_per_token": round(stats.bytes_per_live_token, 1),
            # the host spans since the session opened (obs/spans.py summary):
            # round-period percentiles over the ring, per-span total/count/max
            "host_spans": self.spans.summary(),
        }
        if eng.paged:
            summary["pool_blocks"] = eng.pool.num_blocks
            summary["kv_block_size"] = eng.block_size
            summary["admit_deferrals"] = stats.admit_deferrals
            if eng.prefix:
                # the prefix-cache ledger: how often admission matched a
                # cached chain, how much prefill it skipped, and what the
                # warm retention holds at close — the bench's hit_rate /
                # prefill_tokens_saved_frac read straight off this block
                summary["prefix_cache"] = True
                summary["prefix_cache_budget_gib"] = (
                    eng.serve.prefix_cache_budget_gib
                )
                summary["prefix_lookups"] = stats.prefix_lookups
                summary["prefix_hits"] = stats.prefix_hits
                summary["prefix_hit_rate"] = round(
                    stats.prefix_hits / max(stats.prefix_lookups, 1), 4
                )
                summary["prefill_tokens_total"] = stats.prefill_tokens_total
                summary["prefill_tokens_saved"] = stats.prefill_tokens_saved
                summary["prefill_tokens_saved_frac"] = round(
                    stats.prefill_tokens_saved
                    / max(stats.prefill_tokens_total, 1), 4
                )
                summary["pool_blocks_warm"] = eng.pool.blocks_warm
                summary["warm_bytes"] = (
                    eng.pool.blocks_warm * self._per_block
                )
        if eng.spec:
            # the speculative-decode ledger: how many target tokens each
            # verify round yielded (accepted_tokens_per_step > 1.0 is the
            # win) and how often drafts survived the target's argmax —
            # the serve-spec bench and the --min-acceptance-rate strict
            # gate read straight off this block
            summary["spec_decode"] = True
            summary["spec_tokens"] = eng.spec
            summary["spec_draft_model"] = eng.serve.spec_draft_model or "ngram"
            summary["spec_steps"] = stats.spec_steps
            summary["spec_drafted_tokens"] = stats.spec_drafted
            summary["spec_accepted_tokens"] = stats.spec_accepted
            summary["accepted_tokens_per_step"] = round(
                stats.spec_emitted / max(stats.spec_slot_rounds, 1), 4
            )
            summary["acceptance_rate"] = round(
                stats.spec_accepted / max(stats.spec_drafted, 1), 4
            )
        if self.replica is not None:
            summary["replica"] = int(self.replica)
        # programs traced, lowered, compiled or loaded after set-up was over,
        # process-wide (obs/setup.py: each a ``late_compile`` event with its name)
        summary["late_compiles"] = setup.late_compiles()
        # the shared bucketed account (params + kv_cache over the one
        # scheme) with its fit verdict — the capacity gauges' bytes,
        # re-pointed through obs/memprof.py
        acct = self._memory_account()
        summary["memory_account"] = acct
        summary["hbm_headroom_gib"] = acct["hbm_headroom_gib"]
        peak_hbm = device_peak_bytes()
        if peak_hbm is not None:
            # live allocator peak where the backend supports memory_stats
            # (TPU); the static account above is the portable fallback
            summary["peak_hbm_bytes"] = peak_hbm
        log_json(summary)
        eng.last_stats = stats
        return stats


def make_static_runner(
    model: Any, config: Any, mesh: Any, *,
    max_new_tokens: int, width: int, batch: int, is_seq2seq: bool = True,
    kv_cache_dtype: str = "f32",
):
    """The pre-engine contract as ONE compiled runner: pad every request
    chunk to a static batch and decode EVERY row to ``max_new_tokens``
    regardless of when it finishes.  Returns ``run_all(params, requests)
    -> list of generated-id rows``; the jit lives in the closure, so a
    warm-up call and a timed call share the compile (bench) and the
    determinism test compares against exactly this contract.
    ``kv_cache_dtype`` matches the engine flag, so the engine-vs-static
    determinism pins hold under int8 too (same quantized cache on both
    sides)."""
    from distributed_llms_example_tpu.evaluation.generation import (
        CausalGenerator,
        Seq2SeqGenerator,
    )

    cls = Seq2SeqGenerator if is_seq2seq else CausalGenerator
    run = jax.jit(cls(model, config, max_new_tokens, num_beams=1).run)

    def run_all(params: Any, requests: Sequence[Sequence[int]]) -> list[list[int]]:
        outs: list[list[int]] = []
        for lo in range(0, len(requests), batch):
            chunk = list(requests[lo : lo + batch])
            ids = np.full((batch, width), config.pad_token_id, np.int32)
            mask = np.zeros((batch, width), np.int32)
            for r, req in enumerate(chunk):
                toks = list(req)[:width]
                ids[r, : len(toks)] = toks
                mask[r, : len(toks)] = 1
            with activation_mesh(mesh), kv_cache_context(kv_cache_dtype):
                got = np.asarray(run(params, jnp.asarray(ids), jnp.asarray(mask)))
            outs.extend(got[r].tolist() for r in range(len(chunk)))
        return outs

    return run_all


def static_batch_generate(
    model: Any, config: Any, mesh: Any, params: Any,
    requests: Sequence[Sequence[int]], *,
    max_new_tokens: int, width: int, batch: int | None = None,
    is_seq2seq: bool = True, kv_cache_dtype: str = "f32",
) -> list[list[int]]:
    """One-shot form of ``make_static_runner`` (the determinism tests'
    entry point)."""
    return make_static_runner(
        model, config, mesh,
        max_new_tokens=max_new_tokens, width=width,
        batch=batch or len(requests), is_seq2seq=is_seq2seq,
        kv_cache_dtype=kv_cache_dtype,
    )(params, requests)


def trim_eos(ids: Sequence[int], eos: int, pad: int) -> list[int]:
    """Generated ids up to and including the first EOS, pads stripped —
    the canonical form both decode paths agree on."""
    out: list[int] = []
    for t in ids:
        t = int(t)
        if t == pad:
            continue
        out.append(t)
        if t == eos:
            break
    return out
