"""BART seq2seq in flax.linen — the reference's flagship model family
(``facebook/bart-large-cnn``, reference valohai.yaml:10).

Architecture facts matched against HF ``BartForConditionalGeneration``
(verified by parity tests): post-layernorm residual blocks, learned
positional embeddings with the +2 offset quirk, optional sqrt(d) embedding
scale, gelu FFN, biased attention/FFN projections, tied LM head plus
``final_logits_bias``, decoder starts at EOS with a forced BOS first token
for the -cnn checkpoints.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_llms_example_tpu.ops.attention import mask_to_bias
from distributed_llms_example_tpu.ops.fused_dropout import Dropout
from distributed_llms_example_tpu.utils.remat import remat_block
from distributed_llms_example_tpu.ops.mha import MultiHeadAttention
from distributed_llms_example_tpu.ops.norms import LayerNorm
from distributed_llms_example_tpu.parallel.activation import constrain_hidden, constrain_logits


@dataclasses.dataclass(frozen=True)
class BartConfig:
    vocab_size: int = 50265
    d_model: int = 1024
    encoder_layers: int = 12
    decoder_layers: int = 12
    encoder_attention_heads: int = 16
    decoder_attention_heads: int = 16
    encoder_ffn_dim: int = 4096
    decoder_ffn_dim: int = 4096
    max_position_embeddings: int = 1024
    dropout_rate: float = 0.1
    # HF ``attention_dropout`` (probs dropout; bart-large ships 0.0).
    # Rides the flash kernels' in-kernel mask stream when > 0.
    attn_dropout_rate: float = 0.0
    scale_embedding: bool = False
    pad_token_id: int = 1
    bos_token_id: int = 0
    eos_token_id: int = 2
    decoder_start_token_id: int = 2
    forced_bos_token_id: Optional[int] = None
    forced_eos_token_id: Optional[int] = 2  # HF BART default: force EOS at max length
    layer_norm_epsilon: float = 1e-5
    attention_impl: str = "auto"  # "auto" | "flash" | "xla" (see ops/mha.py)

    POSITION_OFFSET = 2  # HF BartLearnedPositionalEmbedding quirk

    @property
    def head_dim(self) -> int:
        return self.d_model // self.encoder_attention_heads

    @property
    def embed_scale(self) -> float:
        return self.d_model**0.5 if self.scale_embedding else 1.0


class BartEncoderLayer(nn.Module):
    config: BartConfig
    dtype: jnp.dtype = jnp.float32

    def setup(self) -> None:
        cfg = self.config
        self.self_attn = MultiHeadAttention(
            num_heads=cfg.encoder_attention_heads,
            head_dim=cfg.d_model // cfg.encoder_attention_heads,
            model_dim=cfg.d_model,
            use_bias=True,
            dtype=self.dtype,
            attention_impl=cfg.attention_impl,
            probs_dropout_rate=cfg.attn_dropout_rate,
            name="self_attn",
        )
        self.self_attn_layer_norm = LayerNorm(cfg.layer_norm_epsilon, self.dtype, name="self_attn_layer_norm")
        self.mlp = BartMLP(cfg.encoder_ffn_dim, cfg.d_model, cfg.dropout_rate, self.dtype, name="mlp")
        self.final_layer_norm = LayerNorm(cfg.layer_norm_epsilon, self.dtype, name="final_layer_norm")
        self.dropout = Dropout(cfg.dropout_rate)

    def __call__(self, hidden, bias, deterministic: bool = True):
        residual = hidden
        h = self.self_attn(hidden, bias=bias, deterministic=deterministic)
        # the residual add rides the dropout kernel (one fused pass on TPU)
        hidden = self.self_attn_layer_norm(self.dropout(h, deterministic, residual=residual))
        residual = hidden
        h = self.mlp(hidden, deterministic=deterministic)
        hidden = self.final_layer_norm(self.dropout(h, deterministic, residual=residual))
        return hidden


class BartMLP(nn.Module):
    ffn_dim: int
    model_dim: int
    dropout_rate: float
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        h = nn.gelu(nn.Dense(self.ffn_dim, dtype=self.dtype, name="fc1")(x), approximate=False)
        h = Dropout(self.dropout_rate)(h, deterministic)
        return nn.Dense(self.model_dim, dtype=self.dtype, name="fc2")(h)


class BartDecoderLayer(nn.Module):
    config: BartConfig
    dtype: jnp.dtype = jnp.float32

    def setup(self) -> None:
        cfg = self.config
        mk_attn = lambda causal, name: MultiHeadAttention(  # noqa: E731
            num_heads=cfg.decoder_attention_heads,
            head_dim=cfg.d_model // cfg.decoder_attention_heads,
            model_dim=cfg.d_model,
            use_bias=True,
            causal=causal,
            dtype=self.dtype,
            attention_impl=cfg.attention_impl,
            probs_dropout_rate=cfg.attn_dropout_rate,
            name=name,
        )
        self.self_attn = mk_attn(True, "self_attn")
        self.self_attn_layer_norm = LayerNorm(cfg.layer_norm_epsilon, self.dtype, name="self_attn_layer_norm")
        self.cross_attn = mk_attn(False, "cross_attn")
        self.cross_attn_layer_norm = LayerNorm(cfg.layer_norm_epsilon, self.dtype, name="cross_attn_layer_norm")
        self.mlp = BartMLP(cfg.decoder_ffn_dim, cfg.d_model, cfg.dropout_rate, self.dtype, name="mlp")
        self.final_layer_norm = LayerNorm(cfg.layer_norm_epsilon, self.dtype, name="final_layer_norm")
        self.dropout = Dropout(cfg.dropout_rate)

    def __call__(
        self,
        hidden,
        self_bias,
        encoder_hidden,
        cross_bias,
        deterministic: bool = True,
        use_cache: bool = False,
        cross_kv=None,
        cache_positions=None,
        encoder_mask=None,
        live=None,
    ):
        """``encoder_mask`` (the 0/1 mask ``cross_bias`` was made from) and
        ``live`` (which rows hold a request) are what a cross step over a
        holder's (batch, length, heads x head_dim) ``cross_kv`` reads its
        rows' lengths and its idle rows from (``MultiHeadAttention``)."""
        residual = hidden
        h = self.self_attn(
            hidden, bias=self_bias, use_cache=use_cache, deterministic=deterministic,
            cache_positions=cache_positions,
        )
        hidden = self.self_attn_layer_norm(self.dropout(h, deterministic, residual=residual))
        residual = hidden
        h = self.cross_attn(
            hidden, kv_hidden=encoder_hidden, bias=cross_bias, cross_kv=cross_kv,
            deterministic=deterministic, mask=encoder_mask, live=live,
        )
        hidden = self.cross_attn_layer_norm(self.dropout(h, deterministic, residual=residual))
        residual = hidden
        h = self.mlp(hidden, deterministic=deterministic)
        hidden = self.final_layer_norm(self.dropout(h, deterministic, residual=residual))
        return hidden


class BartForConditionalGeneration(nn.Module):
    config: BartConfig
    dtype: jnp.dtype = jnp.float32
    remat: bool = False
    remat_policy: str = "full"  # "full" | "dots" (utils/remat.py)
    cross_kv_rows = True  # ``cross_kv`` takes ``rows``: what the serving engine asks before it asks for them

    def setup(self) -> None:
        cfg = self.config
        self.shared = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=self.dtype, name="shared")
        self.encoder_embed_positions = nn.Embed(
            cfg.max_position_embeddings + cfg.POSITION_OFFSET, cfg.d_model, dtype=self.dtype,
            name="encoder_embed_positions",
        )
        self.decoder_embed_positions = nn.Embed(
            cfg.max_position_embeddings + cfg.POSITION_OFFSET, cfg.d_model, dtype=self.dtype,
            name="decoder_embed_positions",
        )
        self.encoder_layernorm_embedding = LayerNorm(
            cfg.layer_norm_epsilon, self.dtype, name="encoder_layernorm_embedding"
        )
        self.decoder_layernorm_embedding = LayerNorm(
            cfg.layer_norm_epsilon, self.dtype, name="decoder_layernorm_embedding"
        )
        enc_layer = remat_block(BartEncoderLayer, (3,), self.remat_policy) if self.remat else BartEncoderLayer
        dec_layer = remat_block(BartDecoderLayer, (5, 6), self.remat_policy) if self.remat else BartDecoderLayer
        self.encoder_blocks = [
            enc_layer(cfg, dtype=self.dtype, name=f"encoder_block_{i}") for i in range(cfg.encoder_layers)
        ]
        self.decoder_blocks = [
            dec_layer(cfg, dtype=self.dtype, name=f"decoder_block_{i}") for i in range(cfg.decoder_layers)
        ]
        self.final_logits_bias = self.param(
            "final_logits_bias", nn.initializers.zeros, (cfg.vocab_size,), jnp.float32
        )
        self.dropout = Dropout(cfg.dropout_rate)

    def encode(self, input_ids, attention_mask=None, *, deterministic: bool = True):
        cfg = self.config
        pos = jnp.arange(input_ids.shape[1]) + cfg.POSITION_OFFSET
        hidden = self.shared(input_ids) * cfg.embed_scale + self.encoder_embed_positions(pos)[None]
        hidden = self.dropout(self.encoder_layernorm_embedding(hidden), deterministic=deterministic)
        hidden = constrain_hidden(hidden)
        bias = mask_to_bias(attention_mask) if attention_mask is not None else None
        for blk in self.encoder_blocks:
            hidden = constrain_hidden(blk(hidden, bias, deterministic))
        return hidden

    def cross_kv(self, encoder_hidden, rows: bool = False):
        """Per-decoder-layer cross-attention K/V, projected ONCE from the
        encoder output.  The decode loop's per-step cross projections
        (2·S·d_model² FLOPs per layer) dwarf everything else it does at
        summarization shapes; generation precomputes this tuple after
        ``encode`` and threads it through every decode step.  ``rows``: each
        pair as a cache keeps K/V, (B, S, heads x head_dim), for a holder of
        many one-token steps (``MultiHeadAttention.project_kv``): the serving
        engine asks for it wherever a model's ``cross_kv`` takes the word."""
        return tuple(
            blk.cross_attn.project_kv(encoder_hidden, rows) for blk in self.decoder_blocks
        )

    def decode(
        self,
        decoder_input_ids,
        encoder_hidden,
        encoder_mask=None,
        decoder_attention_mask=None,
        *,
        deterministic: bool = True,
        use_cache: bool = False,
        cache_offset: int | jnp.ndarray = 0,
        max_kv_len: int | None = None,
        cross_kv=None,
    ):
        cfg = self.config
        q_len = decoder_input_ids.shape[1]
        # a (B,) cache_offset is the continuous-batching form: each serving
        # slot decodes at its own position (per-row position embeddings +
        # per-row cache writes)
        cache_positions = None
        off = jnp.asarray(cache_offset)
        if off.ndim == 1:
            cache_positions = off.astype(jnp.int32)
            pos = off[:, None] + jnp.arange(q_len)[None, :] + cfg.POSITION_OFFSET
            pos_embed = self.decoder_embed_positions(pos)  # (B, q, d)
        else:
            pos = jnp.arange(q_len) + cache_offset + cfg.POSITION_OFFSET
            pos_embed = self.decoder_embed_positions(pos)[None]
        hidden = self.shared(decoder_input_ids) * cfg.embed_scale + pos_embed
        hidden = self.dropout(self.decoder_layernorm_embedding(hidden), deterministic=deterministic)
        if use_cache:
            self_bias = None  # causal/validity handled inside cached attention
        else:
            # causal masking lives inside MultiHeadAttention (natively in the
            # flash kernel); only the padding mask is passed as a bias
            self_bias = (
                mask_to_bias(decoder_attention_mask)
                if decoder_attention_mask is not None
                else None
            )
        cross_bias = mask_to_bias(encoder_mask) if encoder_mask is not None else None
        # a serving step parks an idle slot at the self cache's length: its cross K/V is not read either
        live = None if cache_positions is None or max_kv_len is None else cache_positions < max_kv_len
        hidden = constrain_hidden(hidden)
        for i, blk in enumerate(self.decoder_blocks):
            hidden = constrain_hidden(blk(
                hidden, self_bias, encoder_hidden, cross_bias, deterministic, use_cache,
                cross_kv=None if cross_kv is None else cross_kv[i],
                cache_positions=cache_positions, encoder_mask=encoder_mask, live=live,
            ))
        logits = constrain_logits(hidden @ self.shared.embedding.astype(self.dtype).T)
        return logits + self.final_logits_bias.astype(logits.dtype)

    def __call__(
        self,
        input_ids,
        attention_mask=None,
        decoder_input_ids=None,
        decoder_attention_mask=None,
        *,
        deterministic: bool = True,
    ):
        enc = self.encode(input_ids, attention_mask, deterministic=deterministic)
        return self.decode(
            decoder_input_ids,
            enc,
            encoder_mask=attention_mask,
            decoder_attention_mask=decoder_attention_mask,
            deterministic=deterministic,
        )


class PipelinedBart:
    """Train-time ``apply()`` adapter running BOTH BART stacks as GPipe
    pipelines over the ``stage`` mesh axis (parallel/pipeline.py) — the
    encoder pipeline drains fully, then its output rides the decoder
    pipeline as a per-example extra feeding every stage's cross-attention.

    Drop-in for ``BartForConditionalGeneration.apply`` in the train step's
    loss fn (same signature/logits) with the param tree holding
    ``stacked_encoder_blocks`` / ``stacked_decoder_blocks``
    (``stack_for_family("bart", ...)``).  Embeddings / logits run outside
    the pipelines under plain GSPMD; ``stage`` composes with data/fsdp and
    ``tensor`` (partial-manual shard_map), not ``sequence``.  Dropout is
    fully supported: pass ``deterministic=False`` with a ``dropout`` rng —
    the key is folded per (pipeline, microbatch, stage, layer) inside the
    stage loop so every layer of every microbatch draws an independent
    mask.  Training + teacher-forced scoring only (no KV-cache generation
    path).
    """

    def __init__(self, config: BartConfig, mesh, dtype=jnp.float32,
                 num_microbatches: int = 0, remat: bool = True,
                 schedule: str = "gpipe"):
        if schedule not in ("gpipe", "1f1b", "interleaved"):
            raise ValueError(
                f"unknown pipeline schedule {schedule!r}: must be gpipe, "
                "1f1b, or interleaved"
            )
        # known-bad schedule × sharding combos (1f1b×fsdp partitioner
        # crash, interleaved, sequence parallelism) are table rows in
        # analysis/composition.py — one declarative check instead of
        # scattered raises
        from distributed_llms_example_tpu.analysis.composition import (
            validate_composition,
        )

        validate_composition(
            family="bart", schedule=schedule, mesh_axes=dict(mesh.shape),
            flags=("pipelined",),
        )
        stages = mesh.shape.get("stage", 1)
        for n, what in ((config.encoder_layers, "encoder"), (config.decoder_layers, "decoder")):
            if n % max(stages, 1):
                raise ValueError(f"{n} {what} layers not divisible into {stages} stages")
        self.config = config
        self.mesh = mesh
        self.dtype = dtype
        self.num_microbatches = num_microbatches or max(stages, 1)
        self.remat = remat
        self.pipeline_schedule = schedule
        cfg = config
        self._shared = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=dtype)
        self._pos = nn.Embed(cfg.max_position_embeddings + cfg.POSITION_OFFSET, cfg.d_model, dtype=dtype)
        self._ln = LayerNorm(cfg.layer_norm_epsilon, dtype)
        self._enc_layer = BartEncoderLayer(cfg, dtype=dtype)
        self._dec_layer = BartDecoderLayer(cfg, dtype=dtype)

    def _embed(self, params, shared, ids, pos_key, ln_key):
        cfg = self.config
        pos = jnp.arange(ids.shape[1]) + cfg.POSITION_OFFSET
        h = shared * cfg.embed_scale + self._pos.apply({"params": params[pos_key]}, pos)[None]
        return constrain_hidden(self._ln.apply({"params": params[ln_key]}, h))

    def _dropout(self, x, key):
        from distributed_llms_example_tpu.parallel.pipeline import dropout

        return dropout(x, key, self.config.dropout_rate)

    def make_value_and_grad(self, label_smoothing: float = 0.0,
                            is_seq2seq: bool = True):
        """Twin-pipeline 1F1B training path: ``(params, batch, rng) ->
        (loss_sum, tokens, grads)`` with the fused schedule owning the
        backward (``pipeline_value_and_grad_seq2seq``).  Embeddings run
        outside under GSPMD with their own ``jax.vjp``; the tied LM head +
        ``final_logits_bias`` + CE run per-microbatch on the last stage's
        decoder chunk; the shared embedding's gradient sums its input-side
        and output-side contributions."""
        from distributed_llms_example_tpu.parallel.activation import activation_mesh
        from distributed_llms_example_tpu.parallel.pipeline_seq2seq import (
            pipeline_value_and_grad_seq2seq,
        )
        from distributed_llms_example_tpu.train.step import cross_entropy_sums

        assert is_seq2seq
        cfg = self.config

        def post_loss(pp, y, mb, key):
            # BART has no tail dropout: logits come straight off the last
            # decoder layer's final_layer_norm output (``decode``)
            del key
            logits = y["dec"] @ pp["shared"]["embedding"].astype(self.dtype).T
            logits = logits + pp["final_logits_bias"].astype(logits.dtype)
            return cross_entropy_sums(logits, mb["labels"], label_smoothing)

        def enc_fn(lp, h, ex, key=None):
            with activation_mesh(None):
                if key is None:
                    return self._enc_layer.apply({"params": lp}, h, ex.get("src_bias"), True)
                return self._enc_layer.apply(
                    {"params": lp}, h, ex.get("src_bias"), False, rngs={"dropout": key}
                )

        def dec_fn(lp, h, ex, key=None):
            # decoder self-attention bias is None in training (causality
            # lives in the attention impl; padded labels are masked in CE)
            with activation_mesh(None):
                if key is None:
                    return self._dec_layer.apply(
                        {"params": lp}, h, None, ex["enc"], ex.get("src_bias"), True
                    )
                return self._dec_layer.apply(
                    {"params": lp}, h, None, ex["enc"], ex.get("src_bias"),
                    False, rngs={"dropout": key},
                )

        embed_keys = (
            "shared", "encoder_embed_positions", "decoder_embed_positions",
            "encoder_layernorm_embedding", "decoder_layernorm_embedding",
        )

        def value_and_grad_sums(params, batch, rng=None):
            from distributed_llms_example_tpu.models.t5 import shift_right

            labels = batch["labels"]
            dec_ids = shift_right(labels, cfg.decoder_start_token_id, cfg.pad_token_id)
            embed_params = {k: params[k] for k in embed_keys}

            def embed_all(ep):
                sh = lambda ids: self._shared.apply({"params": ep["shared"]}, ids)  # noqa: E731
                eh = self._embed(ep, sh(batch["input_ids"]), batch["input_ids"],
                                 "encoder_embed_positions", "encoder_layernorm_embedding")
                dh = self._embed(ep, sh(dec_ids), dec_ids,
                                 "decoder_embed_positions", "decoder_layernorm_embedding")
                if rng is not None:
                    eh = self._dropout(eh, jax.random.fold_in(rng, 2))
                    dh = self._dropout(dh, jax.random.fold_in(rng, 3))
                return eh, dh

            (enc_h, dec_h), embed_vjp = jax.vjp(embed_all, embed_params)
            src_bias = (
                mask_to_bias(batch["attention_mask"])
                if batch.get("attention_mask") is not None else None
            )
            extras = {} if src_bias is None else {"src_bias": src_bias}
            post_params = {
                "shared": params["shared"],
                "final_logits_bias": params["final_logits_bias"],
            }
            (lsum, tokens, d_se, d_sd, d_pp, _d_seam, _d_dex, d_eh, d_dh) = (
                pipeline_value_and_grad_seq2seq(
                    enc_fn, dec_fn, post_loss,
                    params["stacked_encoder_blocks"], params["stacked_decoder_blocks"],
                    post_params, enc_h, dec_h, extras, {"labels": labels},
                    mesh=self.mesh, num_microbatches=self.num_microbatches,
                    checkpoint=self.remat,
                    rng=None if rng is None else jax.random.fold_in(rng, 7),
                )
            )
            (d_embed,) = embed_vjp((d_eh.astype(enc_h.dtype), d_dh.astype(dec_h.dtype)))
            grads = {
                **{k: d_embed[k] for k in embed_keys},
                "stacked_encoder_blocks": d_se,
                "stacked_decoder_blocks": d_sd,
                "final_logits_bias": d_pp["final_logits_bias"],
            }
            # tied embedding: input-side (both embed lookups) + output-side
            # (logits projection) gradient contributions add
            grads["shared"] = jax.tree.map(jnp.add, d_embed["shared"], d_pp["shared"])
            return lsum, tokens, grads

        return value_and_grad_sums

    def apply(self, variables, input_ids, attention_mask=None, decoder_input_ids=None,
              decoder_attention_mask=None, *, deterministic: bool = True, rngs=None):
        from distributed_llms_example_tpu.parallel.activation import activation_mesh
        from distributed_llms_example_tpu.parallel.pipeline import pipeline_apply

        rng = None
        if not deterministic and rngs and "dropout" in rngs and self.config.dropout_rate > 0:
            rng = rngs["dropout"]

        p = variables["params"]
        shared = lambda ids: self._shared.apply({"params": p["shared"]}, ids)  # noqa: E731
        enc_bias = mask_to_bias(attention_mask) if attention_mask is not None else None

        hidden = self._embed(p, shared(input_ids), input_ids,
                             "encoder_embed_positions", "encoder_layernorm_embedding")
        if rng is not None:
            hidden = self._dropout(hidden, jax.random.fold_in(rng, 2))

        def enc_fn(lp, h, ex, key=None):
            with activation_mesh(None):
                if key is None:
                    return self._enc_layer.apply({"params": lp}, h, ex.get("bias"), True)
                return self._enc_layer.apply(
                    {"params": lp}, h, ex.get("bias"), False, rngs={"dropout": key}
                )

        hidden = pipeline_apply(
            enc_fn, p["stacked_encoder_blocks"], hidden,
            {"bias": enc_bias} if enc_bias is not None else {},
            mesh=self.mesh, num_microbatches=self.num_microbatches, checkpoint=self.remat,
            rng=None if rng is None else jax.random.fold_in(rng, 0),
        )

        dh = self._embed(p, shared(decoder_input_ids), decoder_input_ids,
                         "decoder_embed_positions", "decoder_layernorm_embedding")
        if rng is not None:
            dh = self._dropout(dh, jax.random.fold_in(rng, 3))
        extras = {"enc": hidden}
        if enc_bias is not None:
            extras["cross_bias"] = enc_bias
        if decoder_attention_mask is not None:
            extras["self_bias"] = mask_to_bias(decoder_attention_mask)

        def dec_fn(lp, h, ex, key=None):
            with activation_mesh(None):
                if key is None:
                    return self._dec_layer.apply(
                        {"params": lp}, h, ex.get("self_bias"), ex["enc"], ex.get("cross_bias"), True
                    )
                return self._dec_layer.apply(
                    {"params": lp}, h, ex.get("self_bias"), ex["enc"], ex.get("cross_bias"),
                    False, rngs={"dropout": key},
                )

        dh = pipeline_apply(
            dec_fn, p["stacked_decoder_blocks"], dh, extras,
            mesh=self.mesh, num_microbatches=self.num_microbatches, checkpoint=self.remat,
            rng=None if rng is None else jax.random.fold_in(rng, 1),
        )
        logits = constrain_logits(dh @ p["shared"]["embedding"].astype(self.dtype).T)
        return logits + p["final_logits_bias"].astype(logits.dtype)
