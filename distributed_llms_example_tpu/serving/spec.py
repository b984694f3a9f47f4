"""Speculative multi-token decode: draft-then-verify, bit-identical to greedy.

Plain continuous batching pays one full decode-step dispatch per generated
token per slot.  ``flash_decode`` already scores q blocks of up to 8 rows in
one kernel call (the per-row length masks express staggered offsets), so
*verifying* k draft tokens costs about one decode step — the classic
draft-then-verify win.  This module owns ALL of the speculative math; the
engine only assembles inputs and appends accepted tokens (repo_lint rule 17
fences acceptance/rollback to this file + serving/cache_pool.py).

Two draft sources, both proposing ``k`` tokens per slot per round:

- **n-gram self-drafting** (default, zero extra model): the longest-suffix
  n-gram match over the slot's prompt + already-generated tokens proposes
  the tokens that followed the last occurrence — free lookahead that pays
  off exactly when decode output is locally repetitive (code, templated
  prose, greedy loops).
- **a shrunk draft model** resolved through the model registry
  (``--spec-draft-model``): a causal model sharing the target's vocab,
  decoded greedily ``k`` steps per round on its own flat cache
  (``DraftRunner``).

The acceptance rule is the whole contract: run the target model ONCE over
``x = [last_emitted, d_1 .. d_k]`` (a q block of k+1 rows), take the
target's greedy argmax at every position, accept the longest prefix where
``draft == target argmax``, then emit the target's OWN next token after the
accepted prefix.  Every emitted token is therefore a token greedy decoding
would have produced — speculative output is **bit-identical to plain
greedy**, only cheaper per token.  (That is the engine-vs-static
determinism pattern: same argmax expression, same kernel path — int8 KV
dequant included — so the tests pin equality, not closeness.)

Rollback is mask discipline, not data movement: the verify program opens
the k+1 mask span up front, and after acceptance rebuilds the span to
``accepted + 1`` bits.  Rejected positions hold garbage K/V but are
mask-invisible (the poisoned-pool invariant), and the NEXT round's span
write covers exactly those positions before any read — write-before-attend
makes the stale tail unreachable by construction.  On the paged path the
span write scatters through ``cache_pool.scatter_span`` (per-row block
tables, sentinel drops), so speculative writes only ever land in blocks the
slot already owns: rejection returns nothing to the free-list because
nothing was ever taken, and the prefix-cache hash index never sees a
speculative block (registration happens only at admission).
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from distributed_llms_example_tpu.evaluation.generation import _causal_prefill
from distributed_llms_example_tpu.parallel.activation import kv_cache_context
from distributed_llms_example_tpu.serving import cache_pool

__all__ = [
    "ngram_draft",
    "ngram_drafts",
    "acceptance_lengths",
    "build_verify",
    "DraftRunner",
]


# ----------------------------------------------------------------- drafting
def ngram_draft(history: Sequence[int], k: int, *, max_n: int = 3) -> list[int]:
    """Self-drafting lookahead: find the most recent earlier occurrence of
    the longest suffix n-gram (n = max_n .. 1) of ``history`` and propose
    the ``k`` tokens that followed it, extending from ``history`` itself
    when the match runs off the end.  Falls back to repeating the last
    token, so the result always has exactly ``k`` entries — the verify
    step prices a wrong draft at zero emitted tokens, never at
    correctness."""
    h = list(history)
    if not h:
        return [0] * k
    for n in range(min(max_n, len(h) - 1), 0, -1):
        suffix = h[-n:]
        # scan right-to-left for the most recent PRIOR occurrence
        for i in range(len(h) - n - 1, -1, -1):
            if h[i : i + n] == suffix:
                out = h[i + n : i + n + k]
                comb = suffix + out
                while len(out) < k:
                    # the match ran off the end: continue period-n
                    # repetition over the proposed stream itself
                    nxt = comb[-n]
                    out.append(nxt)
                    comb.append(nxt)
                return out[:k]
    return [h[-1]] * k


def ngram_drafts(
    histories: Sequence[Sequence[int] | None], k: int, pad: int,
) -> np.ndarray:
    """Batch ``ngram_draft`` over per-slot histories (None = idle slot →
    pad row).  Returns an (slots, k) int32 array — the verify program's
    draft columns."""
    out = np.full((len(histories), k), pad, np.int32)
    for s, h in enumerate(histories):
        if h:
            out[s] = ngram_draft(h, k)
    return out


# --------------------------------------------------------------- acceptance
def acceptance_lengths(
    x: jnp.ndarray, target: jnp.ndarray, room: jnp.ndarray,
) -> jnp.ndarray:
    """The acceptance rule: longest prefix where draft == target argmax.

    ``x`` is (S, k+1) = [last_emitted, d_1..d_k]; ``target`` is (S, k+1),
    the target model's greedy argmax at each of those positions (so
    ``target[:, j]`` is what greedy decoding emits after seeing
    ``x[:, :j+1]``).  Draft ``d_{j+1}`` is accepted iff it EQUALS
    ``target[:, j]`` and every earlier draft was accepted — the cumprod
    over matches.  ``room`` (S,) clamps acceptance to the slot's remaining
    budget minus one (the bonus token always lands), so a round never
    emits past ``max_new_tokens``; clamping only truncates the prefix, it
    never changes a token, so emitted output stays exactly the greedy
    string.  Returns (S,) int32 accepted-draft counts in [0, k]."""
    k = x.shape[1] - 1
    j = jnp.arange(k)
    matches = (x[:, 1:] == target[:, :-1]) & (j[None, :] < room[:, None])
    return jnp.cumprod(matches.astype(jnp.int32), axis=1).sum(axis=1)


# ------------------------------------------------------------ verify program
def build_verify(
    model: Any, *, slots: int, k: int, pad: int,
    paged: bool = False, num_blocks: int = 0, block_size: int = 0,
):
    """Build the engine's spec-verify program: ONE target-model call over a
    q block of k+1 rows per slot, acceptance, and the mask-rebuild
    rollback.  Flat signature ``(params, state, x, write_pos, rope_pos,
    active, room)``; paged inserts ``block_tables`` after ``x``.  Returns
    ``(target, n_emit, state)`` where ``target`` (S, k+1) holds the greedy
    tokens (pad on idle rows) and ``n_emit = accepted + 1`` counts how
    many of ``target``'s leading entries the host appends.

    Position contract: cache position ``write_pos + j`` receives the K/V
    of ``x[:, j]``.  An accepted prefix of length m means positions
    ``write_pos .. write_pos + m`` hold [last, target_0..target_{m-1}] —
    all tokens greedy decode would have cached there.  The bonus token
    ``target[:, m]`` becomes the next round's ``x[:, 0]``, written at the
    next round's ``write_pos' = write_pos + m + 1`` — exactly where the
    rejected tail starts, so stale K/V is overwritten before its mask bit
    can ever be re-set (write-before-attend)."""
    from distributed_llms_example_tpu.parallel.sharding import cache_kv_heads

    S, K = slots, k
    span = jnp.arange(K + 1)
    rows = jnp.arange(S)
    kv_heads = cache_kv_heads(model.config)

    def _verify_core(params, state, x, block_tables, write_pos, rope_pos,
                     active, room):
        width = state["mask"].shape[1]
        offs = jnp.where(active, write_pos, width)
        # open the whole candidate span; per-row causality within the span
        # rides the decode-step bias (q_pos = offset + row index)
        mask = state["mask"].at[
            rows[:, None], offs[:, None] + span[None, :]
        ].set(1, mode="drop")
        if paged:
            from distributed_llms_example_tpu.parallel.activation import (
                constrain_cache,
            )

            cache = constrain_cache(
                cache_pool.gather_cache(state["pool"], block_tables), kv_heads
            )
        else:
            cache = state["cache"]
        logits, mut = model.apply(
            {"params": params, "cache": cache},
            x,
            mask,
            use_cache=True,
            positions=rope_pos[:, None] + span[None, :],
            cache_positions=offs,
            mutable=["cache"],
        )
        target = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (S, K+1)
        accept = acceptance_lengths(x, target, room)
        n_emit = jnp.where(active, accept + 1, 0).astype(jnp.int32)
        # rollback = mask rebuild: only the accepted prefix (+ the row for
        # x[:,0], always valid) keeps its bits; rejected positions go dark
        keep = (span[None, :] <= accept[:, None]).astype(state["mask"].dtype)
        mask = state["mask"].at[
            rows[:, None], offs[:, None] + span[None, :]
        ].set(keep, mode="drop")
        last = jnp.take_along_axis(target, accept[:, None], axis=1)[:, 0]
        last = jnp.where(active, last, pad)
        target = jnp.where(active[:, None], target, pad)
        out = {**state, "mask": mask, "last": last}
        if paged:
            out["pool"] = cache_pool.scatter_span(
                state["pool"], mut["cache"], block_tables, offs, K + 1,
                num_blocks=num_blocks, block_size=block_size,
            )
        else:
            from distributed_llms_example_tpu.parallel.activation import (
                constrain_cache,
            )

            out["cache"] = constrain_cache(mut["cache"], kv_heads)
        return target, n_emit, out

    if paged:
        def verify(params, state, x, block_tables, write_pos, rope_pos,
                   active, room):
            return _verify_core(params, state, x, block_tables, write_pos,
                                rope_pos, active, room)
    else:
        def verify(params, state, x, write_pos, rope_pos, active, room):
            return _verify_core(params, state, x, None, write_pos,
                                rope_pos, active, room)

    return verify


# ------------------------------------------------------------- draft runner
class DraftRunner:
    """The shrunk-draft-model path: a second causal model (same vocab,
    resolved through the registry) greedily proposes ``k`` tokens per slot
    per round on its own FLAT cache, mirroring the target's slot layout
    (prompt at positions 0..len-1 inside the admission bucket, decode tail
    at ``base = bucket``).

    The per-round program is catch-up-then-draft: the draft cache always
    trails the target by exactly the tokens the engine appended last round
    (``fed``, between 1 and k+1 of them), so each round first writes that
    span in one multi-token call — whose logits at the last fed position
    already yield draft token 1 — then single-steps k-1 more.  The final
    mask rebuild keeps only the fed positions: the draft's own speculative
    writes roll back by the same mask discipline as the verify program,
    and the next round's catch-up span overwrites them before any read."""

    def __init__(self, loaded: Any, *, slots: int, src_width: int,
                 max_new: int, buckets: Sequence[int], wave_sizes: Sequence[int],
                 k: int, pad: int, kv_cache_dtype: str, wrap: Any):
        self.model = loaded.module
        self.config = loaded.config
        params = loaded.params
        if params is None:
            params = jax.device_get(loaded.init_params(0))
        self.params = params
        self.S, self.W, self.L, self.K = slots, src_width, max_new, k
        self.wave_sizes = tuple(wave_sizes)  # the engine's: admissions share its rows
        self.pad = pad
        self.width = src_width + max_new
        self.buckets = tuple(buckets)
        self.kv_cache_dtype = kv_cache_dtype
        self._warmed = False
        self._build(wrap)

    # ------------------------------------------------------------ programs
    def _build(self, wrap) -> None:
        model, S, K, L = self.model, self.S, self.K, self.L
        width = self.width
        # the round touches the catch-up span (n_fed ≤ K+1 rows from pos0)
        # AND the draft tail (K-1 single steps from pos0+n_fed-1): open
        # every position either can reach up front, rebuild at the end
        open_w = max(K + 1, 2 * K)
        ospan = jnp.arange(open_w)
        kspan = jnp.arange(K + 1)
        rows = jnp.arange(S)

        def prefill(params, ids, mask):
            cache, full_mask, _lengths, _first = _causal_prefill(
                model, params, ids, mask, L
            )
            return cache, full_mask

        def admit(state, cache, full_mask, slot_idx):
            put = lambda dst, src: (  # noqa: E731
                dst.at[slot_idx].set(src, mode="drop") if dst.ndim > 0 else dst
            )
            # bucket-width chunk -> slot width, along each leaf's length axis
            return {
                "cache": jax.tree.map(
                    put, state["cache"], cache_pool.pad_cache_length(cache, width)
                ),
                "mask": put(state["mask"], cache_pool.pad_axis(full_mask, 1, width)),
            }

        def round_(params, state, fed, n_fed, pos0, rope0, active):
            pos = jnp.where(active, pos0, width)
            mask = state["mask"].at[
                rows[:, None], pos[:, None] + ospan[None, :]
            ].set(1, mode="drop")
            # catch-up: write the fed span (garbage pad-K/V lands at
            # positions >= n_fed but is overwritten by the draft steps
            # below before any read — write-before-attend); the logits at
            # the last fed row are the first draft token
            logits, mut = model.apply(
                {"params": params, "cache": state["cache"]},
                fed,
                mask,
                use_cache=True,
                positions=rope0[:, None] + kspan[None, :],
                cache_positions=pos,
                mutable=["cache"],
            )
            cache = mut["cache"]
            toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            idx = jnp.clip(n_fed - 1, 0, K)  # idle rows have n_fed = 0
            cur = jnp.take_along_axis(toks, idx[:, None], axis=1)[:, 0]
            drafts = [cur]
            q = pos0 + n_fed - 1  # the last fed position
            rq = rope0 + n_fed - 1
            for t in range(1, K):
                cp = jnp.where(active, q + t, width)
                lg, mut = model.apply(
                    {"params": params, "cache": cache},
                    cur[:, None],
                    mask,
                    use_cache=True,
                    positions=(rq + t)[:, None],
                    cache_positions=cp,
                    mutable=["cache"],
                )
                cache = mut["cache"]
                cur = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)
                drafts.append(cur)
            # rollback: only the fed tokens stay visible — every
            # speculative draft position goes dark until the next round's
            # catch-up span rewrites it
            keep = (ospan[None, :] < n_fed[:, None]).astype(state["mask"].dtype)
            final_mask = state["mask"].at[
                rows[:, None], pos[:, None] + ospan[None, :]
            ].set(keep, mode="drop")
            return jnp.stack(drafts, axis=1), {
                "cache": cache, "mask": final_mask,
            }

        self._prefill_core = prefill
        self._prefill = wrap(prefill, name="draft_prefill")
        self._admit = wrap(admit, donate=(0,), name="draft_admit")
        self._round = wrap(round_, donate=(0,), name="draft_round")

    # --------------------------------------------------------------- state
    def init_state(self) -> dict:
        ids = jnp.zeros((self.S, self.W), jnp.int32)
        mask = jnp.zeros((self.S, self.W), jnp.int32)
        with kv_cache_context(self.kv_cache_dtype):
            a_cache, a_mask = jax.eval_shape(
                lambda p: self._prefill_core(p, ids, mask), self.params
            )
        zeros = lambda t: jax.tree.map(  # noqa: E731
            lambda a: jnp.zeros(a.shape, a.dtype), t
        )
        return {"cache": zeros(a_cache), "mask": zeros(a_mask)}

    def warm(self, state) -> Any:
        """One prefill+admit trace per bucket and wave size (parked writes)
        plus one all-idle round — the draft programs join the engine's
        zero-recompile contract."""
        if self._warmed:
            return state
        S, K = self.S, self.K
        for rows in self.wave_sizes:
            park = jnp.full((rows,), S, jnp.int32)
            for bucket in self.buckets:
                ids = jnp.zeros((rows, bucket), jnp.int32)
                cache, fm = self._prefill(self.params, ids, ids)
                state = self._admit(state, cache, fm, park)
        idle = jnp.zeros((S,), bool)
        z = jnp.zeros((S,), jnp.int32)
        _, state = self._round(
            self.params, state, jnp.full((S, K + 1), self.pad, jnp.int32),
            z, z, z, idle,
        )
        self._warmed = True
        return state

    def admit_prompt(self, state, ids, mask, slot_idx) -> Any:
        """Prefill + admit one bucket-width chunk of prompts into the
        draft cache (host passes rows padded to one of ``wave_sizes``,
        parked rows at slot index S)."""
        cache, fm = self._prefill(self.params, ids, mask)
        return self._admit(state, cache, fm, slot_idx)

    def round(self, state, fed, n_fed, pos0, rope0, active):
        """One draft round; returns ((S, k) proposed tokens, new state)."""
        return self._round(self.params, state, fed, n_fed, pos0, rope0, active)
