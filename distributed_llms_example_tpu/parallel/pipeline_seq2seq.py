"""Fused 1F1B for TWIN encoder→decoder pipelines (BART/T5, stage>1).

The gpipe seq2seq adapters (``PipelinedBart``/``PipelinedT5``) run two
``pipeline_apply`` calls back to back — encoder drains fully, then its
output feeds every decoder stage's cross-attention — and differentiate the
whole thing with autodiff, which must keep O(M) microbatch activations
alive per stage between the forward and reversed-backward scans.  This
module gives the reference's flagship model family (bart-large-cnn,
reference valohai.yaml:10) and the flan-t5-xl BASELINE config the same
O(S)-memory fused schedule ``pipeline_value_and_grad`` gives LLaMA.

Design — ONE pipeline of 2S chunks over S devices, table-driven:

- Device ``s`` holds encoder part ``s`` (global chunk ``s``) and decoder
  part ``s`` (global chunk ``S + s``): exactly the interleaved-schedule
  chunk placement ``g = c*S + s`` with v=2 virtual chunks, so the
  precomputed tables from ``parallel/interleave.py`` orchestrate the twin
  pipeline unchanged — a microbatch rides the stage ring through all S
  encoder chunks, wraps 0→S-1→0, and rides it again through the S decoder
  chunks; forwards and backwards interleave 1F1B-style with the loss vjp
  folded into the last decoder chunk's tick.
- The carried value is an ``{"enc", "dec"}`` PAIR (source and target
  lengths differ, so one buffer cannot hold both).  Encoder chunks map
  ``enc`` and pass ``dec`` through; decoder chunks pass ``enc`` through —
  every later decoder chunk still needs it for cross-attention — and map
  ``dec``.  The pass-throughs are differentiated with everything else, so
  the backward ring's ``enc`` component accumulates each decoder chunk's
  cross-attention gradient for free.
- Each tick a device runs EITHER its encoder chunk or its decoder chunk.
  On pure stage(×data) meshes that is a ``lax.cond`` on the table's chunk
  id — a device-varying predicate; one branch executes, so a tick costs
  one chunk.  On meshes whose AUTO axes shard the block params (fsdp /
  tensor: GSPMD inserts all-gathers/all-reduces INSIDE the chunk bodies)
  the cond is unsound: stages on different branches would execute
  different collective sequences and the rendezvous deadlocks (observed
  as an XLA collective-permute rendezvous abort on CPU; a hang on TPU).
  There the executor computes BOTH chunks and selects — collectives run
  uniformly on every device, at the honest price of one extra
  decoder-chunk-equivalent per tick (small next to the encoder chunk at
  summarization shapes: tgt 128 vs src 1024).  fsdp>1 is guarded off
  entirely: the partitioner crashes compiling the chunk-pair program
  with dim-0-sharded params under either dispatch mode (gpipe remains
  the fsdp×stage path for seq2seq).
- The enc→dec SEAM (device 0's decoder chunk): the decoder embedding
  enters from the microbatch store (like global chunk 0's input), an
  optional differentiable ``seam_fn`` (T5's encoder final-norm + dropout)
  transforms the arriving encoder output once per microbatch, and on the
  backward the pair's ``dec`` gradient is emitted as d(decoder embedding)
  and cut from the ring before it would leak into the encoder phase.
- ``diff_extras``: replicated per-call inputs that DO need gradients
  (T5's relative-position bias tensors) — chunk vjps accumulate their
  cotangents across every (chunk, microbatch), psum'd in the epilogue.

Same contracts as ``pipeline_value_and_grad`` otherwise: microbatch math
is identical to the sequential computation (schedule-only reordering,
pinned by tests/test_pipeline_seq2seq.py against the plain modules), all
manual-axis reductions run in fp32, and the loss head is tick-gated to
its M real ticks (``_pvg_loss_vjp``).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from distributed_llms_example_tpu.parallel.pipeline import (
    _full_spec,
    _make_run_stage,
    _pvg_check_batch,
    _pvg_loss_vjp,
    _vary,
)


def _tree_where(pred, a, b):
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def _tree_astype(tree, dt):
    return jax.tree.map(lambda x: x.astype(dt), tree)


def _tree_index(tree, i, depth):
    return jax.tree.map(
        lambda b: jax.lax.dynamic_index_in_dim(
            b, jnp.clip(i, 0, depth - 1), 0, keepdims=False
        ),
        tree,
    )


def _tree_update(tree, val, i, depth):
    return jax.tree.map(
        lambda b, v: jax.lax.dynamic_update_index_in_dim(
            b, v, jnp.clip(i, 0, depth - 1), 0
        ),
        tree,
        val,
    )


def pipeline_value_and_grad_seq2seq(
    enc_layer_fn: Callable,
    dec_layer_fn: Callable,
    post_loss_fn: Callable,
    stacked_enc: Any,
    stacked_dec: Any,
    post_params: Any,
    enc_hidden: jnp.ndarray,
    dec_hidden: jnp.ndarray,
    extras: Any,
    loss_batch: Any,
    *,
    mesh: Mesh,
    num_microbatches: int,
    seam_fn: Callable | None = None,
    seam_params: Any = None,
    diff_extras: Any = None,
    axis_name: str = "stage",
    batch_axes: tuple[str, ...] = ("data", "fsdp", "expert"),
    checkpoint: bool = True,
    rng: jnp.ndarray | None = None,
):
    """Twin-pipeline 1F1B: loss and ALL parameter gradients in one fused
    scan over the interleaved v=2 schedule tables.

    ``enc_layer_fn(p, h, ex[, key]) -> h`` applies one encoder layer;
    ``dec_layer_fn`` one decoder layer, reading the (seamed) encoder
    output from ``ex["enc"]``.  Both also see ``diff_extras`` merged into
    their ``ex``.  ``post_loss_fn(post_params, pair, loss_microbatch,
    key) -> (loss_sum, tokens)`` runs the model tail + loss on
    ``pair["dec"]`` for ONE microbatch (token-SUM semantics).
    ``seam_fn(seam_params, enc_out, key) -> enc_out`` (optional) is
    applied exactly once per microbatch where the encoder output enters
    the decoder pipeline — T5's encoder final-norm + dropout; BART has no
    seam (pass None).  ``key`` args are None when ``rng`` is None.

    Returns ``(loss_sum, tokens, d_enc_stacked, d_dec_stacked, d_post,
    d_seam, d_diff_extras, d_enc_hidden, d_dec_hidden)`` — unnormalized
    sums and gradients of loss_sum w.r.t. every differentiable input.
    """
    from distributed_llms_example_tpu.parallel.interleave import (
        make_interleaved_schedule,
    )

    S = mesh.shape.get(axis_name, 1)
    M = num_microbatches
    if S > 1 and mesh.shape.get("fsdp", 1) > 1:
        # The crash class lives as a row in the composition matrix
        # (analysis/composition.py, id "seq2seq-1f1b-fsdp"); the adapters
        # reject it at construction, and this deep guard covers direct
        # executor calls with the same table-driven message.  Technical
        # detail: the partitioner SIGABRTs under BOTH dispatch modes and
        # with the param gather hoisted out of the branches — reproduced
        # on XLA CPU; the llama 1f1b executor (single chunk body, no pair)
        # compiles fine on the same mesh, so this is specific to the twin
        # shape.  Until the compiler moves: seq2seq fsdp×stage uses gpipe.
        from distributed_llms_example_tpu.analysis.composition import reason_for

        raise ValueError(reason_for("seq2seq-1f1b-fsdp"))
    seam_params = {} if seam_params is None else seam_params
    diff_extras = {} if diff_extras is None else diff_extras
    for stacked, what in ((stacked_enc, "encoder"), (stacked_dec, "decoder")):
        L = jax.tree.leaves(stacked)[0].shape[0]
        if L % max(S, 1):
            raise ValueError(f"{L} {what} layers not divisible into {S} stages")
    run_enc = _make_run_stage(enc_layer_fn, checkpoint)
    run_dec = _make_run_stage(dec_layer_fn, checkpoint)
    B = enc_hidden.shape[0]
    if dec_hidden.shape[0] != B:
        raise ValueError(
            f"encoder batch {B} != decoder batch {dec_hidden.shape[0]}"
        )
    _pvg_check_batch(B, mesh, M, batch_axes)

    compute_dtype = enc_hidden.dtype

    def keys_for(key, m):
        # distinct streams per (role, microbatch); role 0=enc 1=dec 2=seam
        if key is None:
            return None, None, None
        return tuple(
            jax.random.fold_in(jax.random.fold_in(key, role), m) for role in range(3)
        )

    if S == 1:
        # no pipeline: one vjp over (embeds already outside) enc → seam →
        # dec → tail under plain GSPMD
        k_enc, k_dec, k_seam = keys_for(rng, 0)

        def whole(se, sd, pp, sp, dex, eh, dh):
            ex = {**extras, **dex}
            enc = run_enc(se, eh, ex, k_enc)
            if seam_fn is not None:
                enc = seam_fn(sp, enc, k_seam)
            y = run_dec(sd, dh, {**ex, "enc": enc}, k_dec)
            return post_loss_fn(pp, {"enc": enc, "dec": y}, loss_batch, k_dec)

        (lsum, tokens), vjp = jax.vjp(
            whole, stacked_enc, stacked_dec, post_params, seam_params,
            diff_extras, enc_hidden, dec_hidden,
        )
        d_se, d_sd, d_pp, d_sp, d_dex, d_eh, d_dh = vjp(
            (jnp.ones((), lsum.dtype), jnp.zeros((), tokens.dtype))
        )
        return lsum, tokens, d_se, d_sd, d_pp, d_sp, d_dex, d_eh, d_dh

    sc = make_interleaved_schedule(S, 2, M)
    # chunk dispatch mode: see the module docstring.  ``data`` only shards
    # the batch (no collectives in a chunk body); fsdp/tensor/expert shard
    # the block params themselves, putting partitioner collectives inside
    # the would-be cond branches.
    branch_free = any(
        mesh.shape.get(a, 1) > 1 for a in ("fsdp", "tensor", "expert")
    )
    plumb_dtype = jnp.float32 if compute_dtype == jnp.bfloat16 else compute_dtype
    axes_all = (axis_name,)
    is_batched = jax.tree.map(lambda m: m.ndim > 0 and m.shape[0] == B, extras)
    ex_dtypes = jax.tree.map(lambda m: m.dtype, extras)

    # schedule tables as device constants; each tick reads its own row
    tbl = {
        name: jnp.asarray(getattr(sc, name))
        for name in (
            "f_active", "f_micro", "f_chunk", "f_src_q", "f_save", "arr_f",
            "b_active", "b_micro", "b_chunk", "b_act", "b_src_q", "arr_b",
            "b_emit_dh",
        )
    }
    # tick-level (device-unvarying) loss gate: device S-1 forwards the
    # last decoder chunk on exactly M ticks
    _t_loss_np = (sc.f_active[:, S - 1] == 1) & (sc.f_chunk[:, S - 1] == 1)
    if int(_t_loss_np.sum()) != M:  # not assert: must survive python -O
        raise ValueError(
            f"twin schedule runs the loss chunk {int(_t_loss_np.sum())} "
            f"times, expected {M}"
        )
    t_loss = jnp.asarray(_t_loss_np)

    def body(se_local, sd_local, pp, sp, dex, eh, dh, ex, lb, rt):
        eh_shape, dh_shape = eh.shape, dh.shape
        s_idx = jax.lax.axis_index(axis_name)
        is_last = s_idx == S - 1
        ex = jax.tree.map(
            lambda m: m.astype(plumb_dtype) if m.dtype == jnp.bfloat16 else m, ex
        )
        se_local, sd_local = _vary(se_local, axes_all), _vary(sd_local, axes_all)
        pp, sp, dex = _vary(pp, axes_all), _vary(sp, axes_all), _vary(dex, axes_all)
        eh = _vary(eh.astype(plumb_dtype), axes_all)
        dh = _vary(dh.astype(plumb_dtype), axes_all)
        ex, lb = _vary(ex, axes_all), _vary(lb, axes_all)
        key = rt.get("key")
        if key is not None:
            key = jax.random.fold_in(_vary(key, axes_all), s_idx)
        mb = eh.shape[0] // M
        micro = {
            "enc": eh.reshape(M, mb, *eh.shape[1:]),
            "dec": dh.reshape(M, mb, *dh.shape[1:]),
        }
        micro_ex = jax.tree.map(
            lambda m, batched: m.reshape(M, m.shape[0] // M, *m.shape[1:]) if batched else m,
            ex, is_batched,
        )
        micro_lb = jax.tree.map(lambda m: m.reshape(M, m.shape[0] // M, *m.shape[1:]), lb)

        def ex_at(m_idx):
            return jax.tree.map(
                lambda m, batched, dt: (
                    jax.lax.dynamic_index_in_dim(m, m_idx, 0, keepdims=False)
                    if batched else m
                ).astype(dt),
                micro_ex, is_batched, ex_dtypes,
            )

        def zpair(*lead):
            return {
                k: _vary(jnp.zeros((*lead, mb, *shape[1:]), plumb_dtype), axes_all)
                for k, shape in (("enc", eh_shape), ("dec", dh_shape))
            }

        zeros_like_f32 = lambda t: jax.tree.map(  # noqa: E731
            lambda x: _vary(jnp.zeros(x.shape, jnp.float32), axes_all), t
        )
        fwd_in = zpair()
        bwd_in = zpair()
        fqbuf = zpair(sc.fq_depth)
        bqbuf = zpair(sc.bq_depth)
        act = zpair(sc.act_depth)
        d_se = zeros_like_f32(se_local)
        d_sd = zeros_like_f32(sd_local)
        d_sp = zeros_like_f32(sp)
        d_dex = zeros_like_f32(dex)
        d_pp = zeros_like_f32(pp)
        d_he = _vary(jnp.zeros((M, mb, *eh.shape[1:]), jnp.float32), axes_all)
        d_hd = _vary(jnp.zeros((M, mb, *dh.shape[1:]), jnp.float32), axes_all)
        scal0 = _vary(jnp.zeros((), jnp.float32), axes_all)
        perm_fwd = [(i, (i + 1) % S) for i in range(S)]
        perm_bwd = [(i, (i - 1) % S) for i in range(S)]

        def at(name, t):
            return tbl[name][t, s_idx]

        def chunk_apply(se_, sd_, sp_, dex_, c_idx, x, ex_m, keys):
            """One chunk on the {enc, dec} pair.  c_idx 0 = this device's
            encoder chunk, 1 = its decoder chunk (device-varying: each
            device branches on its own table entry)."""
            k_enc, k_dec, k_seam = keys

            def enc_branch(ops):
                se_o, sd_o, sp_o, dex_o, x_o = ops
                y = run_enc(
                    se_o, x_o["enc"].astype(compute_dtype),
                    {**ex_m, **dex_o}, k_enc,
                )
                return {"enc": y.astype(plumb_dtype), "dec": x_o["dec"]}

            def dec_branch(ops):
                se_o, sd_o, sp_o, dex_o, x_o = ops
                enc_in = x_o["enc"].astype(compute_dtype)
                if seam_fn is not None:
                    # the seam transform applies only where the encoder
                    # output ENTERS the decoder pipeline (device 0's
                    # decoder chunk); later devices receive the already-
                    # seamed value through the ring pass-through
                    seamed = seam_fn(sp_o, enc_in, k_seam)
                    enc_in = jnp.where(s_idx == 0, seamed, enc_in)
                y = run_dec(
                    sd_o, x_o["dec"].astype(compute_dtype),
                    {**ex_m, **dex_o, "enc": enc_in}, k_dec,
                )
                return {"enc": enc_in.astype(plumb_dtype), "dec": y.astype(plumb_dtype)}

            ops = (se_, sd_, sp_, dex_, x)
            if branch_free:
                # both chunks, select: collective sequence is device-uniform
                # (the unselected side's vjp cotangent is zero, so gradients
                # stay exact)
                return _tree_where(c_idx == 0, enc_branch(ops), dec_branch(ops))
            return jax.lax.cond(c_idx == 0, enc_branch, dec_branch, ops)

        def tick(carry, t):
            (fwd_in, bwd_in, fqbuf, bqbuf, act, d_se, d_sd, d_sp, d_dex,
             d_pp, d_he, d_hd, lsum, toks) = carry

            # ---- queue arrivals (values sent on the rings last tick)
            af = at("arr_f", t)
            fqbuf = _tree_where(af >= 0, _tree_update(fqbuf, fwd_in, af, sc.fq_depth), fqbuf)
            ab = at("arr_b", t)
            bqbuf = _tree_where(ab >= 0, _tree_update(bqbuf, bwd_in, ab, sc.bq_depth), bqbuf)

            # ---- forward slot
            f_on = at("f_active", t) == 1
            fm = at("f_micro", t)
            fc = at("f_chunk", t)
            fsrc = at("f_src_q", t)
            x0 = {
                "enc": jax.lax.dynamic_index_in_dim(micro["enc"], fm, 0, keepdims=False),
                "dec": jax.tree.map(jnp.zeros_like, fwd_in["dec"]),
            }
            xq = _tree_index(fqbuf, fsrc, sc.fq_depth)
            x_in = _tree_where(fsrc < 0, x0, xq)
            # enc→dec seam: the decoder embedding enters HERE, from the
            # microbatch store (device 0's decoder chunk — global chunk S)
            is_seam_f = (s_idx == 0) & (fc == 1)
            x_in["dec"] = jnp.where(
                is_seam_f,
                jax.lax.dynamic_index_in_dim(micro["dec"], fm, 0, keepdims=False),
                x_in["dec"],
            )
            ex_f = ex_at(fm)
            keys_f = keys_for(key, fm)
            y = chunk_apply(se_local, sd_local, sp, dex, fc, x_in, ex_f, keys_f)
            a_save = at("f_save", t)
            act = _tree_where(f_on, _tree_update(act, x_in, a_save, sc.act_depth), act)

            # ---- loss vjp on the in-tick forward output (tick-gated)
            lb_f = jax.tree.map(
                lambda m: jax.lax.dynamic_index_in_dim(m, fm, 0, keepdims=False),
                micro_lb,
            )
            k_loss = None if keys_f is None else keys_f[1]

            def loss_f(pp_, y_):
                return post_loss_fn(pp_, _tree_astype(y_, compute_dtype), lb_f, k_loss)

            ls_m, tk_m, d_pp_m, dy_loss = _pvg_loss_vjp(loss_f, pp, y, t_loss[t])
            take_loss = f_on & is_last & (fc == 1)
            lsum = lsum + jnp.where(take_loss, ls_m.astype(jnp.float32), 0.0)
            toks = toks + jnp.where(take_loss, tk_m.astype(jnp.float32), 0.0)
            d_pp = jax.tree.map(
                lambda a_, g: a_ + jnp.where(take_loss, g.astype(jnp.float32), 0.0),
                d_pp, d_pp_m,
            )

            # ---- backward slot (recomputes its chunk forward under vjp)
            b_on = at("b_active", t) == 1
            bm = at("b_micro", t)
            bc = at("b_chunk", t)
            bsrc = at("b_src_q", t)
            x_b = _tree_index(act, at("b_act", t), sc.act_depth)
            ex_b = ex_at(bm)
            keys_b = keys_for(key, bm)

            def chunk_b(se_, sd_, sp_, dex_, x_):
                return chunk_apply(se_, sd_, sp_, dex_, bc, x_, ex_b, keys_b)

            _, chunk_vjp = jax.vjp(chunk_b, se_local, sd_local, sp, dex, x_b)
            dy_q = _tree_index(bqbuf, bsrc, sc.bq_depth)
            dy_in = _tree_where(bsrc < 0, _tree_astype(dy_loss, plumb_dtype), dy_q)
            d_se_m, d_sd_m, d_sp_m, d_dex_m, dx = chunk_vjp(dy_in)
            acc = lambda a_, g: a_ + jnp.where(b_on, g.astype(jnp.float32), 0.0)  # noqa: E731
            d_se = jax.tree.map(acc, d_se, d_se_m)
            d_sd = jax.tree.map(acc, d_sd, d_sd_m)
            d_sp = jax.tree.map(acc, d_sp, d_sp_m)
            d_dex = jax.tree.map(acc, d_dex, d_dex_m)

            # seam backward: the pair's dec gradient IS d(decoder
            # embedding) — emit it and cut it from the ring so it cannot
            # leak into the encoder phase's pass-throughs
            is_seam_b = b_on & (s_idx == 0) & (bc == 1)
            d_hd = jnp.where(
                is_seam_b,
                jax.lax.dynamic_update_index_in_dim(
                    d_hd, dx["dec"].astype(jnp.float32), bm, 0
                ),
                d_hd,
            )
            dx["dec"] = jnp.where(is_seam_b, jnp.zeros_like(dx["dec"]), dx["dec"])
            # global chunk 0 backward: d(encoder embedding)
            emit = (at("b_emit_dh", t) == 1) & b_on
            d_he = jnp.where(
                emit,
                jax.lax.dynamic_update_index_in_dim(
                    d_he, dx["enc"].astype(jnp.float32), bm, 0
                ),
                d_he,
            )

            # ---- ring hops
            fwd_in = jax.tree.map(
                lambda v: jax.lax.ppermute(v, axis_name, perm_fwd), y
            )
            bwd_in = jax.tree.map(
                lambda v: jax.lax.ppermute(v.astype(plumb_dtype), axis_name, perm_bwd), dx
            )
            return (fwd_in, bwd_in, fqbuf, bqbuf, act, d_se, d_sd, d_sp, d_dex,
                    d_pp, d_he, d_hd, lsum, toks), None

        carry = (fwd_in, bwd_in, fqbuf, bqbuf, act, d_se, d_sd, d_sp, d_dex,
                 d_pp, d_he, d_hd, scal0, scal0)
        carry, _ = jax.lax.scan(tick, carry, jnp.arange(sc.T))
        (_, _, _, _, _, d_se, d_sd, d_sp, d_dex, d_pp, d_he, d_hd,
         lsum, toks) = carry

        # reductions: loss/tail grads live on the last stage, seam grads on
        # device 0, diff-extra grads on every device, d_hidden on device 0
        # — psum replicates (and, for d_dex, sums the real contributions)
        lsum = jax.lax.psum(lsum, axes_all)
        toks = jax.lax.psum(toks, axes_all)
        d_pp = jax.tree.map(lambda g: jax.lax.psum(g, axes_all), d_pp)
        d_sp = jax.tree.map(lambda g: jax.lax.psum(g, axes_all), d_sp)
        d_dex = jax.tree.map(lambda g: jax.lax.psum(g, axes_all), d_dex)
        d_he = jax.lax.psum(d_he, axis_name).reshape(eh_shape)
        d_hd = jax.lax.psum(d_hd, axis_name).reshape(dh_shape)
        return lsum, toks, d_se, d_sd, d_pp, d_sp, d_dex, d_he, d_hd

    enc_specs = jax.tree.map(lambda x: _full_spec(axis_name, x.ndim), stacked_enc)
    dec_specs = jax.tree.map(lambda x: _full_spec(axis_name, x.ndim), stacked_dec)
    rng_tree = {} if rng is None else {"key": rng}
    repl = lambda t: jax.tree.map(lambda _: P(), t)  # noqa: E731

    return jax.shard_map(
        body,
        mesh=mesh,
        axis_names={axis_name},
        in_specs=(
            enc_specs, dec_specs, repl(post_params), repl(seam_params),
            repl(diff_extras), P(), P(), repl(extras), repl(loss_batch),
            repl(rng_tree),
        ),
        out_specs=(
            P(), P(), enc_specs, dec_specs, repl(post_params),
            repl(seam_params), repl(diff_extras), P(), P(),
        ),
        check_vma=True,
    )(stacked_enc, stacked_dec, post_params, seam_params, diff_extras,
      enc_hidden, dec_hidden, extras, loss_batch, rng_tree)
