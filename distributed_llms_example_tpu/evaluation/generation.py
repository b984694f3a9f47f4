"""Autoregressive generation: explicit prefill/decode split, greedy + beam.

The reference calls ``model.generate(max_length=128, num_beams=2)`` for its
live eval loop (reference train-accelerator.py:239-249).  On TPU the decode
loop must be a fixed-shape compiled program; here it is built from two
separately-compiled (and separately AOT-inspectable) pieces:

- **prefill** — everything that runs once per sequence: the encoder +
  once-per-sequence cross-attention K/V projection (seq2seq) or the prompt
  pass into the KV cache (decoder-only), plus zeroed cache buffers.
- **decode step** — ONE fixed-shape token step: read the cache, emit one
  token per row, write one K/V slot.  The static eval path drives it with
  a ``lax.fori_loop`` (``decode_loop`` — one compile, same per-token
  program); the continuous-batching engine (serving/engine.py) drives a
  jitted step per token from the host so it can admit/evict between steps.

The split is what the IR lint's ``prefill_in_decode_smell`` checks: the
compiled decode step must contain NO encoder/prefill-sized matmuls and
never re-project cross-attention K/V (the ``cross_kv``-computed-once
contract).  Cache buffers and cross-KV trees are pinned to the serving
layout (batch rows over data×fsdp, heads over tensor — ``CACHE_RULES``)
via ``constrain_cache``, so multi-chip decode shards the cache instead of
replicating it.

Beam search keeps a flattened (batch × beams) leading dim so every step is
one big MXU-friendly batch.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from distributed_llms_example_tpu.parallel.activation import constrain_cache
from distributed_llms_example_tpu.parallel.sharding import cache_kv_heads

NEG_INF = -1.0e7


def _init_cache(model: Any, params: Any, batch: int, max_len: int, enc: jnp.ndarray, enc_mask: jnp.ndarray):
    """Zero cache buffers for a (batch, max_len) decode, via eval_shape (no
    real forward pass)."""
    dummy = jnp.zeros((batch, max_len), jnp.int32)
    shapes = jax.eval_shape(
        lambda p: model.init(
            jax.random.PRNGKey(0), dummy, enc, enc_mask, use_cache=True, max_kv_len=max_len, method="decode"
        ),
        params,
    )
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes["cache"])


def abstract_cache(
    model: Any,
    abstract_params: Any,
    *,
    batch: int,
    max_new_tokens: int,
    src_len: int = 64,
    is_seq2seq: bool = True,
    kv_cache_dtype: str = "f32",
):
    """Shape-only decode-cache tree (ShapeDtypeStruct leaves) — the input
    the cache spec lint (``analysis/spec_lint.py lint_cache_sharding``)
    validates, built without weights or devices.  ``kv_cache_dtype``
    "int8" yields the quantized layout: s8 K/V buffers plus the per-head
    per-position ``key_scale``/``value_scale`` f32 leaves the scale rules
    in ``CACHE_RULES`` cover."""
    from distributed_llms_example_tpu.parallel.activation import kv_cache_context
    if is_seq2seq:
        def build(p):
            ids = jnp.zeros((batch, src_len), jnp.int32)
            mask = jnp.ones((batch, src_len), jnp.int32)
            enc = model.apply({"params": p}, ids, mask, method="encode")
            return model.init(
                jax.random.PRNGKey(0),
                jnp.zeros((batch, max_new_tokens), jnp.int32),
                enc, mask, use_cache=True, max_kv_len=max_new_tokens,
                method="decode",
            )["cache"]
    else:
        def build(p):
            width = src_len + max_new_tokens
            return model.init(
                jax.random.PRNGKey(0), jnp.zeros((batch, width), jnp.int32),
                use_cache=True,
            )["cache"]

    with kv_cache_context(kv_cache_dtype):
        return jax.eval_shape(build, abstract_params)


# --------------------------------------------------------------- seq2seq


class Seq2SeqGenerator:
    """Prefill/decode split for encoder-decoder (BART/T5) generation.

    ``prefill`` runs the encoder, projects cross-attention K/V ONCE, and
    allocates sharded cache buffers; ``decode_step`` is the fixed-shape
    per-token program; ``decode_loop`` wraps it in a ``fori_loop`` for
    static batches; ``finalize`` extracts the output ids.  Greedy when
    ``num_beams == 1``, HF-parity beam search otherwise (banked finished
    beams, length-normalized scores — see ``_beam_step_select``)."""

    def __init__(self, model: Any, config: Any, max_new_tokens: int,
                 num_beams: int = 1, length_penalty: float = 1.0):
        self.model, self.config = model, config
        self.kv_heads = cache_kv_heads(config)
        self.L, self.K = max_new_tokens, num_beams
        self.length_penalty = length_penalty
        self.eos, self.pad = config.eos_token_id, config.pad_token_id
        self.start = config.decoder_start_token_id
        self.forced_bos = getattr(config, "forced_bos_token_id", None)
        self.forced_eos = getattr(config, "forced_eos_token_id", None)

    # ---- once per sequence -------------------------------------------
    def prefill(self, params: Any, input_ids: jnp.ndarray, attention_mask: jnp.ndarray) -> dict:
        B = input_ids.shape[0]
        enc = self.model.apply({"params": params}, input_ids, attention_mask, method="encode")
        # cross-attention K/V projected ONCE: per-step re-projection of the
        # full encoder output (2·S·d² per layer) would dominate decode —
        # the contract the IR lint's prefill_in_decode_smell pins
        ckv = constrain_cache(self.model.apply({"params": params}, enc, method="cross_kv"))
        t0 = jnp.zeros((), jnp.int32)
        if self.K > 1:
            # beams share the row's encoder output for DECODING (replicated
            # to the flat beam batch); cross-KV stays at batch B — the
            # attention folds the beam group next to heads so K/V stream
            # from HBM once per row per step (beam_grouped_attention)
            enc_rep = jnp.repeat(enc, self.K, axis=0)
            mask_rep = jnp.repeat(attention_mask, self.K, axis=0)
            cache = constrain_cache(
                _init_cache(self.model, params, B * self.K, self.L, enc_rep, mask_rep), self.kv_heads
            )
            return {
                "t": t0,
                "cache": cache,
                "enc": enc_rep,
                "enc_mask": mask_rep,
                "ckv": ckv,
                "last": jnp.full((B * self.K, 1), self.start, jnp.int32),
                "state": _beam_init(B, self.K, self.L, self.pad),
            }
        cache = constrain_cache(
            _init_cache(self.model, params, B, self.L, enc, attention_mask), self.kv_heads
        )
        return {
            "t": t0,
            "cache": cache,
            "enc": enc,
            "enc_mask": attention_mask,
            "ckv": ckv,
            "last": jnp.full((B, 1), self.start, jnp.int32),
            "out": jnp.full((B, self.L), self.pad, jnp.int32),
            "done": jnp.zeros((B,), bool),
        }

    # ---- once per token ----------------------------------------------
    def decode_step(self, params: Any, carry: dict) -> dict:
        t = carry["t"]
        logits, mut = self.model.apply(
            {"params": params, "cache": carry["cache"]},
            carry["last"],
            carry["enc"],
            carry["enc_mask"],
            use_cache=True,
            cache_offset=t,
            max_kv_len=self.L,
            cross_kv=carry["ckv"],
            method="decode",
            mutable=["cache"],
        )
        cache = constrain_cache(mut["cache"], self.kv_heads)
        if self.K > 1:
            logp = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32), axis=-1)  # (B*K, V)
            V = logp.shape[-1]
            if self.forced_bos is not None:  # HF forced_bos_token_id processor
                forced_mask = jnp.full((V,), NEG_INF, jnp.float32).at[self.forced_bos].set(0.0)
                logp = jnp.where(t == 0, logp + forced_mask[None, :], logp)
            if self.forced_eos is not None:  # HF forced_eos_token_id: EOS at max length
                eos_mask = jnp.full((V,), NEG_INF, jnp.float32).at[self.forced_eos].set(0.0)
                logp = jnp.where(t == self.L - 1, logp + eos_mask[None, :], logp)
            B = carry["state"][0].shape[0]
            state, chosen, parents = _beam_step_select(
                logp, t, carry["state"], eos=self.eos, K=self.K,
                length_penalty=self.length_penalty,
            )
            cache = _gather_beams(cache, parents, B, self.K)
            return {
                **carry,
                "t": t + 1,
                "cache": cache,
                "last": chosen.reshape(B * self.K, 1),
                "state": state,
            }
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        if self.forced_bos is not None:
            nxt = jnp.where(t == 0, self.forced_bos, nxt)
        if self.forced_eos is not None:
            nxt = jnp.where(t == self.L - 1, self.forced_eos, nxt)
        nxt = jnp.where(carry["done"], self.pad, nxt)
        out = carry["out"].at[:, t].set(nxt)
        done = carry["done"] | (nxt == self.eos)
        return {
            **carry,
            "t": t + 1,
            "cache": cache,
            "last": nxt[:, None],
            "out": out,
            "done": done,
        }

    def decode_loop(self, params: Any, carry: dict) -> dict:
        return jax.lax.fori_loop(
            0, self.L, lambda i, c: self.decode_step(params, c), carry
        )

    def finalize(self, carry: dict) -> jnp.ndarray:
        if self.K > 1:
            # final decoder length = start token + L generated (banking at
            # step t uses t+1; the live-beam convention must match)
            return _beam_finalize(carry["state"], self.L + 1, self.length_penalty)
        return carry["out"]

    def run(self, params: Any, input_ids: jnp.ndarray, attention_mask: jnp.ndarray) -> jnp.ndarray:
        """Whole-program form (traceable; jit for the one-compile path)."""
        return self.finalize(self.decode_loop(params, self.prefill(params, input_ids, attention_mask)))


def make_greedy_generate(model: Any, config: Any, max_new_tokens: int) -> Callable:
    """Jittable greedy decoding: (params, input_ids, attention_mask) → ids
    of shape (batch, max_new_tokens), pad-filled after EOS."""
    return Seq2SeqGenerator(model, config, max_new_tokens, num_beams=1).run


def make_beam_search(
    model: Any,
    config: Any,
    max_new_tokens: int,
    num_beams: int = 2,
    length_penalty: float = 1.0,
) -> Callable:
    """Jittable beam search matching HF ``generate(num_beams=K)`` semantics:
    score = sum logprobs / (length ** length_penalty), finished beams
    banked when EOS is chosen, best finished (or live) beam returned."""
    return Seq2SeqGenerator(
        model, config, max_new_tokens, num_beams=num_beams, length_penalty=length_penalty
    ).run


# ----------------------------------------------------------- decoder-only


def causal_cache_shapes(model: Any, params: Any, batch: int, width: int):
    """Abstract decode cache of a decoder-only model for ``batch`` rows of
    ``width`` positions: one abstract trace of ``model.init``."""
    return jax.eval_shape(
        lambda p: model.init(
            jax.random.PRNGKey(0), jnp.zeros((batch, width), jnp.int32), use_cache=True
        ),
        params,
    )["cache"]


def _causal_prefill(
    model: Any, params: Any, input_ids: jnp.ndarray, attention_mask: jnp.ndarray, new_tokens: int,
    cache_shapes: Any = None,
):
    """One-pass prompt prefill for decoder-only decode.

    Allocates cache buffers for prompt + generation (``cache_shapes`` where
    the caller knows them already, else ``causal_cache_shapes``), runs the
    prompt through once, and returns ``(cache, full_mask, lengths,
    first_logits)`` where ``first_logits`` is each row's logits at its last
    *valid* prompt position.  Right-padded prompts are supported: RoPE
    positions follow the true sequence (cumsum over the mask), not the cache
    slot, and pad slots stay masked out of attention."""
    B, P = input_ids.shape
    if cache_shapes is None:
        cache_shapes = causal_cache_shapes(model, params, B, P + new_tokens)
    kv_heads = cache_kv_heads(model.config)
    cache = constrain_cache(
        jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cache_shapes), kv_heads
    )
    full_mask = jnp.concatenate([attention_mask, jnp.zeros((B, new_tokens), jnp.int32)], axis=1)
    lengths = jnp.sum(attention_mask, axis=1).astype(jnp.int32)
    prefill_pos = jnp.clip(jnp.cumsum(attention_mask, axis=1) - 1, 0, None)
    logits, mut = model.apply(
        {"params": params, "cache": cache},
        input_ids,
        full_mask,
        use_cache=True,
        positions=prefill_pos,
        mutable=["cache"],
    )
    first = jnp.take_along_axis(logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
    return constrain_cache(mut["cache"], kv_heads), full_mask, lengths, first


class CausalGenerator:
    """Prefill/decode split for decoder-only (LLaMA-family) generation.

    ``prefill`` runs the right-padded prompt into the KV cache in one pass
    (beams share the prefix, so prefill compute is NOT multiplied by K);
    ``decode_step`` decodes one token per row with true-sequence RoPE
    positions.  Greedy or HF-parity beam search (reference live contract:
    ``num_beams=2``, train-accelerator.py:247)."""

    def __init__(self, model: Any, config: Any, max_new_tokens: int,
                 num_beams: int = 1, length_penalty: float = 1.0):
        self.model, self.config = model, config
        self.kv_heads = cache_kv_heads(config)
        self.L, self.K = max_new_tokens, num_beams
        self.length_penalty = length_penalty
        self.eos, self.pad = config.eos_token_id, config.pad_token_id

    def prefill(self, params: Any, input_ids: jnp.ndarray, attention_mask: jnp.ndarray) -> dict:
        B, P = input_ids.shape
        cache, full_mask, lengths, first = _causal_prefill(
            self.model, params, input_ids, attention_mask, self.L
        )
        if self.K > 1:
            logp0 = jax.nn.log_softmax(first.astype(jnp.float32), axis=-1)  # (B, V)
            # beams share the prefilled prompt: replicate cache rows K-ways
            cache = constrain_cache(
                jax.tree.map(lambda x: jnp.repeat(x, self.K, axis=0) if x.ndim > 0 else x, cache), self.kv_heads
            )
            full_mask = jnp.repeat(full_mask, self.K, axis=0)  # (B*K, width)
            lengths_rep = jnp.repeat(lengths, self.K, axis=0)  # (B*K,)
            # token index 0: run the shared selection on the prefill logits —
            # with live_scores initialized to [0, -inf, ...] only beam 0's
            # distribution contributes, which is exactly the first HF step
            state = _beam_init(B, self.K, self.L, self.pad)
            state, chosen, parents = _beam_step_select(
                jnp.repeat(logp0, self.K, axis=0), 0, state,
                eos=self.eos, K=self.K, length_penalty=self.length_penalty,
                len_offset=P - 1,
            )
            cache = _gather_beams(cache, parents, B, self.K)  # parents all 0: no-op reorder
            return {
                "t": jnp.ones((), jnp.int32),
                "cache": cache,
                "full_mask": full_mask,
                "lengths": lengths_rep,
                "last": chosen.reshape(B * self.K, 1),
                "state": state,
            }
        nxt = jnp.argmax(first, axis=-1).astype(jnp.int32)
        return {
            "t": jnp.zeros((), jnp.int32),
            "cache": cache,
            "full_mask": full_mask,
            "lengths": lengths,
            "last": nxt,
            "out": jnp.full((B, self.L), self.pad, jnp.int32),
            "done": jnp.zeros((B,), bool),
        }

    def decode_step(self, params: Any, carry: dict) -> dict:
        t = carry["t"]
        P = carry["full_mask"].shape[1] - self.L
        if self.K > 1:
            # `last` is token index t-1; it occupies cache slot P + t - 1
            full_mask = carry["full_mask"].at[:, P + t - 1].set(1)
            logits, mut = self.model.apply(
                {"params": params, "cache": carry["cache"]},
                carry["last"],
                full_mask,
                use_cache=True,
                positions=(carry["lengths"] + t - 1)[:, None],
                mutable=["cache"],
            )
            logp = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32), axis=-1)
            B = carry["state"][0].shape[0]
            state, chosen, parents = _beam_step_select(
                logp, t, carry["state"], eos=self.eos, K=self.K,
                length_penalty=self.length_penalty, len_offset=P - 1,
            )
            cache = _gather_beams(constrain_cache(mut["cache"], self.kv_heads), parents, B, self.K)
            return {
                **carry,
                "t": t + 1,
                "cache": cache,
                "last": chosen.reshape(B * self.K, 1),
                "full_mask": full_mask,
                "state": state,
            }
        out = carry["out"].at[:, t].set(carry["last"])
        full_mask = carry["full_mask"].at[:, P + t].set(1)
        logits, mut = self.model.apply(
            {"params": params, "cache": carry["cache"]},
            carry["last"][:, None],
            full_mask,
            use_cache=True,
            positions=(carry["lengths"] + t)[:, None],
            mutable=["cache"],
        )
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        done = carry["done"] | (carry["last"] == self.eos)
        nxt = jnp.where(done, self.pad, nxt)
        return {
            **carry,
            "t": t + 1,
            "cache": constrain_cache(mut["cache"], self.kv_heads),
            "full_mask": full_mask,
            "last": nxt,
            "out": out,
            "done": done,
        }

    def decode_loop(self, params: Any, carry: dict) -> dict:
        t0 = 1 if self.K > 1 else 0  # beam prefill consumed token index 0
        return jax.lax.fori_loop(
            t0, self.L, lambda i, c: self.decode_step(params, c), carry
        )

    def finalize(self, carry: dict) -> jnp.ndarray:
        if self.K > 1:
            P = carry["full_mask"].shape[1] - self.L
            return _beam_finalize(carry["state"], P + self.L, self.length_penalty)
        return carry["out"]

    def run(self, params: Any, input_ids: jnp.ndarray, attention_mask: jnp.ndarray) -> jnp.ndarray:
        """Whole-program form (traceable; jit for the one-compile path)."""
        return self.finalize(self.decode_loop(params, self.prefill(params, input_ids, attention_mask)))


def make_causal_greedy(model: Any, config: Any, max_new_tokens: int) -> Callable:
    """Greedy decoding for decoder-only (causal) models.

    Prefills the prompt into the KV cache in one pass, then decodes one
    token at a time.  Right-padded prompts are supported (see
    ``_causal_prefill``).  With uniform-length prompts this matches HF
    ``generate`` exactly.
    """
    return CausalGenerator(model, config, max_new_tokens, num_beams=1).run


def make_causal_beam_search(
    model: Any,
    config: Any,
    max_new_tokens: int,
    num_beams: int = 2,
    length_penalty: float = 1.0,
) -> Callable:
    """Beam search for decoder-only models — HF-parity semantics shared
    with the seq2seq search via ``_beam_step_select``."""
    return CausalGenerator(
        model, config, max_new_tokens, num_beams=num_beams, length_penalty=length_penalty
    ).run


# ------------------------------------------------------- beam primitives


def _gather_beams(tree: Any, beam_idx: jnp.ndarray, batch: int, beams: int) -> Any:
    """Reorder the flattened (batch*beams, ...) leading dim by per-batch beam
    indices (batch, beams)."""
    flat_idx = (jnp.arange(batch)[:, None] * beams + beam_idx).reshape(-1)
    return jax.tree.map(lambda x: x[flat_idx] if x.ndim > 0 else x, tree)


def _beam_step_select(
    logp: jnp.ndarray,
    t: jnp.ndarray,
    state: tuple,
    *,
    eos: int,
    K: int,
    length_penalty: float,
    len_offset: int = 0,
) -> tuple:
    """One beam-search selection step from per-beam next-token logprobs.

    Shared by the seq2seq and causal searches so the HF-parity semantics
    live in exactly one place.  ``state`` is ``(live_scores, live_seqs,
    fin_scores, fin_seqs, row_done)``; ``logp`` is (B*K, V); ``t`` is the
    token index being chosen.  Matches HF BeamSearchScorer.process:

    - only EOS candidates ranked < num_beams among the top-2K are banked
      (``is_beam_token_worse_than_top_num_beams``);
    - a row is "done" (early_stopping=False) once it holds K banked
      hypotheses whose worst beats the best attainable continuation at the
      current length normalization; done rows stop banking;
    - the normalization length is ``t + 1 + len_offset``: HF divides by the
      full ``input_ids`` length, which for seq2seq is the decoder length
      (offset 0: start token + t generated) and for decoder-only includes
      the prompt (offset P - 1, so the length is P + t).
    """
    live_scores, live_seqs, fin_scores, fin_seqs, row_done = state
    B = live_scores.shape[0]
    V = logp.shape[-1]
    cand = live_scores[:, :, None] + logp.reshape(B, K, V)
    flat = cand.reshape(B, K * V)
    top_scores, top_idx = jax.lax.top_k(flat, 2 * K)  # (B, 2K)
    beam_idx = top_idx // V
    token = (top_idx % V).astype(jnp.int32)

    cand_seqs = jnp.take_along_axis(live_seqs, beam_idx[:, :, None], axis=1)  # (B, 2K, L)
    cand_seqs = cand_seqs.at[:, :, t].set(token)

    is_eos = token == eos
    rank_ok = jnp.arange(2 * K)[None, :] < K
    lp = jnp.asarray(t + 1 + len_offset, jnp.float32) ** length_penalty
    bankable = is_eos & rank_ok & ~row_done[:, None]
    fin_cand = jnp.where(bankable, top_scores / lp, NEG_INF)
    all_fin_scores = jnp.concatenate([fin_scores, fin_cand], axis=1)  # (B, 3K)
    all_fin_seqs = jnp.concatenate([fin_seqs, cand_seqs], axis=1)
    fin_scores_new, fin_keep = jax.lax.top_k(all_fin_scores, K)
    fin_seqs_new = jnp.take_along_axis(all_fin_seqs, fin_keep[:, :, None], axis=1)

    live_cand = jnp.where(is_eos, NEG_INF, top_scores)
    live_scores_new, live_keep = jax.lax.top_k(live_cand, K)
    live_seqs_new = jnp.take_along_axis(cand_seqs, live_keep[:, :, None], axis=1)
    chosen_tokens = jnp.take_along_axis(token, live_keep, axis=1)  # (B, K)
    parent_beams = jnp.take_along_axis(beam_idx, live_keep, axis=1)  # (B, K)

    has_k_banked = fin_scores_new[:, K - 1] > NEG_INF / 2
    # HF is_done uses the best overall candidate sum (next_scores.max(),
    # eos candidates included), not the best surviving live beam
    attainable = top_scores[:, 0] / lp
    row_done_new = row_done | (has_k_banked & (fin_scores_new[:, K - 1] >= attainable))

    new_state = (live_scores_new, live_seqs_new, fin_scores_new, fin_seqs_new, row_done_new)
    return new_state, chosen_tokens, parent_beams


def _beam_init(batch: int, K: int, L: int, pad: int) -> tuple:
    live_scores = jnp.tile(jnp.array([0.0] + [NEG_INF] * (K - 1), jnp.float32), (batch, 1))
    live_seqs = jnp.full((batch, K, L), pad, jnp.int32)
    fin_scores = jnp.full((batch, K), NEG_INF, jnp.float32)
    fin_seqs = jnp.full((batch, K, L), pad, jnp.int32)
    row_done = jnp.zeros((batch,), bool)
    return live_scores, live_seqs, fin_scores, fin_seqs, row_done


def _beam_finalize(state: tuple, final_len: int, length_penalty: float) -> jnp.ndarray:
    """Best sequence per row, HF finalize semantics: rows not yet done also
    consider their best live beam at max length, normalized by the full
    final sequence length (decoder length for seq2seq; prompt + generated
    for decoder-only)."""
    live_scores, live_seqs, fin_scores, fin_seqs, row_done = state
    none_finished = jnp.all(fin_scores <= NEG_INF / 2, axis=1)
    live_final = live_scores[:, 0] / (jnp.asarray(final_len, jnp.float32) ** length_penalty)
    take_live = ~row_done & (none_finished | (live_final > fin_scores[:, 0]))
    return jnp.where(take_live[:, None], live_seqs[:, 0], fin_seqs[:, 0])
