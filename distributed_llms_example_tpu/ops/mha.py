"""Shared multi-head attention module (BART/LLaMA families).

One module covers: scaled dot-product attention with optional biases in the
projections, causal masking, fixed-shape KV caching for autoregressive
decode, rotary position embeddings (LLaMA), and grouped-query attention
(fewer KV heads than Q heads).  T5 keeps its own attention (unscaled
scores + relative position bias are peculiar to it).
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


from distributed_llms_example_tpu.ops.attention import (
    NEG_INF,
    beam_grouped_attention,
    dot_product_attention,
    make_causal_bias,
)
from distributed_llms_example_tpu.ops.flash_attention import (
    MAX_DECODE_Q_ROWS,
    flash_attention,
    flash_decode_run,
    flash_decode_supported,
    flash_supported,
)
from distributed_llms_example_tpu.ops.norms import RMSNorm
from distributed_llms_example_tpu.ops.ring_attention import ring_attention, ring_attention_sharded
from distributed_llms_example_tpu.parallel.activation import (
    BATCH_AXES,
    current_manual_seq,
    current_mesh,
)
from distributed_llms_example_tpu.utils.jsonlog import log_json

_IMPL_LOGGED: set[tuple] = set()


def _log_impl_once(impl: str, reason: str) -> None:
    """One-time JSON line saying which attention path a module selected —
    so "flash is wired in" claims are verifiable from any run log."""
    key = (impl, reason)
    if key not in _IMPL_LOGGED:
        _IMPL_LOGGED.add(key)
        log_json({"event": "attention_impl", "impl": impl, "reason": reason})


def _mesh_batch_shards(mesh: Mesh) -> int:
    return math.prod(mesh.shape.get(a, 1) for a in BATCH_AXES)


def _uneven_split_blocker(mesh: Mesh, *, heads: int, batch: int) -> str | None:
    """Both shard_map paths (flash per-shard, ring) need batch and heads to
    split evenly over (data×fsdp) and ``tensor``; None when they do."""
    tensor = mesh.shape.get("tensor", 1)
    shards = _mesh_batch_shards(mesh)
    if heads % tensor or batch % shards:
        return (
            f"uneven split: heads={heads} over tensor={tensor}, "
            f"batch={batch} over {shards} data/fsdp shards"
        )
    return None


def select_attention_impl(
    attention_impl: str,
    *,
    batch: int,
    heads: int,
    head_dim: int,
    q_len: int,
    kv_len: int,
    use_cache: bool,
    mesh: Mesh | None,
    backend: str,
    device_count: int,
    causal: bool = False,
    bias_kv_only: bool | None = None,
    has_learned_bias: bool = False,
) -> tuple[str, str]:
    """(impl, reason) — pure selection logic, unit-testable without TPUs.

    ``auto`` picks, in priority order: **ring attention** when the mesh has
    a ``sequence`` axis of size > 1 and the shapes split evenly over it
    (sequence/context parallelism — the Pallas/XLA single-shard paths
    would force GSPMD to all-gather the sequence); the **Pallas flash
    kernel** on TPU for non-trivial score matrices — under a multi-device
    mesh it additionally requires the batch and head counts to split
    evenly over the (data×fsdp) and ``tensor`` axes, because multi-device
    flash runs per-shard under ``shard_map`` (an opaque pallas call can't
    be partitioned by GSPMD itself); **XLA attention** otherwise.

    ``bias_kv_only``: None = no bias, True = (b|1, 1, 1, K) padding-style
    bias (the only form the ring can rotate), False = anything wider.
    """
    if attention_impl not in ("auto", "flash", "ring", "xla"):
        raise ValueError(
            f"attention_impl={attention_impl!r}: must be 'auto', 'flash', 'ring', or 'xla'"
        )
    if attention_impl == "xla":
        return "xla", "forced"
    if use_cache:
        return "xla", "kv-cache decode step"
    seq_shards = mesh.shape.get("sequence", 1) if mesh is not None else 1
    if attention_impl == "ring" or (attention_impl == "auto" and seq_shards > 1):
        why = _ring_blocker(
            seq_shards, batch=batch, heads=heads, q_len=q_len, kv_len=kv_len,
            causal=causal, bias_kv_only=bias_kv_only, mesh=mesh,
        )
        if why is None:
            return "ring", ("forced" if attention_impl == "ring" else "auto: sequence-parallel mesh")
        if attention_impl == "ring":
            if mesh is None:
                # not a config error: module init and other traces outside a
                # mesh context legitimately can't ring — fall back quietly
                # so a forced-ring training run can still initialize
                return "xla", f"ring requested but {why}"
            raise ValueError(f"attention_impl='ring' but {why}")
        # a sequence-sharded mesh where ring can't run: XLA attention is
        # correct (GSPMD gathers the sequence) but loses the SP memory win
        return "xla", f"sequence axis present but {why}"
    if not flash_supported(
        q_len, kv_len, head_dim, causal=causal, has_learned_bias=has_learned_bias
    ):
        # 'flash' means "wherever eligible": single-token decode steps and
        # other non-tileable shapes silently use the XLA path
        return "xla", f"shape not tileable (q={q_len}, kv={kv_len}, d={head_dim})"
    multi_device = device_count > 1
    if multi_device:
        if mesh is None:
            return "xla", "multi-device jit without a mesh context"
        why = _uneven_split_blocker(mesh, heads=heads, batch=batch)
        if why is not None:
            return "xla", why
    if attention_impl == "flash":
        return "flash", "forced"
    if backend != "tpu":
        return "xla", f"auto: backend={backend} (interpreted kernel is pure overhead)"
    if q_len * kv_len < 128 * 128:
        return "xla", "auto: score matrix too small to tile"
    return "flash", "auto: TPU" + (" (shard_map per-shard)" if multi_device else "")


def select_decode_impl(
    attention_impl: str,
    *,
    batch: int,
    heads: int,
    head_dim: int,
    q_len: int,
    kv_len: int,
    mesh: Mesh | None,
    backend: str,
    device_count: int,
) -> tuple[str, str]:
    """(impl, reason) for a CACHED decode step — the serving twin of
    ``select_attention_impl``, pure and unit-testable.

    ``auto`` picks the Pallas **decode kernel** (``flash_decode``: one
    short q block — a single decode row, or the speculative verify's
    k+1 rows — against the cached K/V buffer, per-row length mask,
    dead-tile skip; one grid step streams a kv tile of every head of a
    cache slot, ``decode_step_heads``) on TPU when the cache length
    tiles and — under a multi-device mesh — batch/heads split evenly
    over (data×fsdp) and ``tensor`` (the kernel runs per-shard under
    ``shard_map``, like training flash; the step then holds the shard's
    heads).  ``flash`` forces the kernel wherever eligible; XLA attention
    (per-row masked ``dot_product_attention``) otherwise.  ``ring`` has no
    KV-cache path and falls back to XLA.

    Where the line is and why it did not move in PR 26: a cache shorter
    than 128 takes XLA.  At 128 (bart-large-cnn's serving shape, 64 slots
    x 16 heads x d 64, bf16, one row) the kernel's twelve calls of a round
    take 0.60 ms inside the serving program on v5e (0.90 alone), under the
    2 ms that would have moved the line.  The same cell with this shape
    sent to XLA instead runs a round of the same length (14.5 ms either
    way, ``gap_p95_ms`` 48.4 against 48.6): XLA's one-row fusions take
    1.4 ms there, not the 0.56 they take alone, and the cache is relaid on
    both paths, because it rests with its length on the lanes
    (``{2,3,1,0}``: d = 64 would waste half of them) where neither the
    custom call (row-major) nor XLA's own scatter and products
    (``{3,1,2,0}``) read it: ``copy bf16[64,16,128,64]``, 4.4 ms a round
    in front of the kernel, 3.2 around XLA's path.  That relayout, not the
    kernel, is what the next change to this rule, to the kernel's operand
    layout or to how the cache rests is judged by (PERF.md, Findings
    PR 26)."""
    if attention_impl not in ("auto", "flash", "ring", "xla"):
        raise ValueError(
            f"attention_impl={attention_impl!r}: must be 'auto', 'flash', 'ring', or 'xla'"
        )
    if attention_impl == "xla":
        return "xla", "forced"
    if attention_impl == "ring":
        return "xla", "ring attention has no KV-cache decode path"
    if not flash_decode_supported(q_len, kv_len, head_dim):
        return "xla", (
            f"decode shape not tileable (q={q_len}, kv={kv_len}, d={head_dim})"
        )
    if device_count > 1:
        if mesh is None:
            return "xla", "multi-device jit without a mesh context"
        why = _uneven_split_blocker(mesh, heads=heads, batch=batch)
        if why is not None:
            return "xla", why
    if attention_impl == "flash":
        return "flash_decode", "forced"
    if backend != "tpu":
        return "xla", f"auto: backend={backend} (interpreted kernel is pure overhead)"
    if kv_len < 128:
        return "xla", "auto: cache too short to tile"
    return "flash_decode", "auto: TPU decode" + (
        " (shard_map per-shard)" if device_count > 1 else ""
    )


def decode_step_bias(offsets: jnp.ndarray, q_len: int, kv_len: int) -> jnp.ndarray:
    """(B, 1, q_len, kv_len) additive validity+causality mask for a cached
    decode step: q row r (absolute position ``offsets[b] + r``) attends
    cache slots <= its own position — the XLA reference semantics for the
    decode kernel's in-kernel length mask, per-row so continuous-batching
    slots at different offsets share one program."""
    k_pos = jnp.arange(kv_len)[None, None, None, :]
    q_pos = offsets[:, None, None, None] + jnp.arange(q_len)[None, None, :, None]
    return jnp.where(k_pos <= q_pos, 0.0, NEG_INF)


def _ring_blocker(
    seq_shards: int,
    *,
    batch: int,
    heads: int,
    q_len: int,
    kv_len: int,
    causal: bool,
    bias_kv_only: bool | None,
    mesh: Mesh | None,
) -> str | None:
    """None if ring attention can run, else a human-readable blocker."""
    if mesh is None:
        return "no mesh context"
    if seq_shards <= 1:
        return "mesh has no sequence axis > 1"
    if q_len % seq_shards or kv_len % seq_shards:
        return f"q_len={q_len}/kv_len={kv_len} not divisible by sequence={seq_shards}"
    if causal and q_len != kv_len:
        return f"causal ring needs square attention, got q={q_len} kv={kv_len}"
    if bias_kv_only is False:
        return "bias is not K-only (ring rotates only (b,1,1,K) biases)"
    return _uneven_split_blocker(mesh, heads=heads, batch=batch)


def rope_cos_sin(positions: jnp.ndarray, head_dim: int, theta: float = 10000.0) -> tuple:
    """(..., head_dim) cos/sin tables for the given integer positions, in the
    HF half-rotation layout (freqs repeated, not interleaved)."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    freqs = positions.astype(jnp.float32)[..., None] * inv_freq  # (..., head_dim/2)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: (batch, heads, seq, head_dim); cos/sin: (seq, head_dim) or
    broadcastable."""
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return (x * cos + rotated * sin).astype(x.dtype)


class MultiHeadAttention(nn.Module):
    num_heads: int
    head_dim: int
    model_dim: int
    num_kv_heads: int | None = None  # None → == num_heads
    use_bias: bool = True
    causal: bool = False
    use_rope: bool = False
    rope_theta: float = 10000.0
    dtype: jnp.dtype = jnp.float32
    # "auto": ring attention on sequence-parallel meshes, Pallas flash
    # attention on TPU for flash-eligible shapes, XLA attention otherwise;
    # "ring"/"flash"/"xla" force a path.  The causal mask is applied inside
    # this module (natively by the flash/ring kernels), so callers pass
    # only padding/cross-attention biases.
    attention_impl: str = "auto"
    # attention-PROBS dropout (HF ``attention_dropout``); active only with
    # ``deterministic=False`` and a "dropout" rng.  On the flash path the
    # keep-mask is drawn in-kernel from a folded seed — the (B, H, S, S)
    # mask never materializes in HBM (ops/flash_attention.py); the XLA
    # path applies the reference bernoulli mask to the probs.
    probs_dropout_rate: float = 0.0
    # RMSNorm over head_dim of every q and k head, before RoPE (LFM2,
    # Qwen3-class ``q_layernorm``/``k_layernorm``); None = the model has none
    qk_norm_eps: float | None = None

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads if self.num_kv_heads is not None else self.num_heads

    def setup(self) -> None:
        inner_q = self.num_heads * self.head_dim
        inner_kv = self.kv_heads * self.head_dim
        mk = lambda feats, name: nn.Dense(feats, use_bias=self.use_bias, dtype=self.dtype, name=name)  # noqa: E731
        self.q_proj = mk(inner_q, "q_proj")
        self.k_proj = mk(inner_kv, "k_proj")
        self.v_proj = mk(inner_kv, "v_proj")
        self.o_proj = mk(self.model_dim, "o_proj")
        if self.qk_norm_eps is not None:
            self.q_norm = RMSNorm(self.qk_norm_eps, self.dtype, name="q_norm")
            self.k_norm = RMSNorm(self.qk_norm_eps, self.dtype, name="k_norm")

    def _split(self, x: jnp.ndarray, heads: int) -> jnp.ndarray:
        b, s, _ = x.shape
        return x.reshape(b, s, heads, self.head_dim).transpose(0, 2, 1, 3)

    def project_kv(self, kv_hidden: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
        """K/V projections alone, as ``__call__`` would compute them —
        (B, kv_heads, S, head_dim) each.  Generation precomputes these ONCE
        per sequence for cross-attention (the encoder output is fixed for
        the whole decode) and feeds them back via ``cross_kv``; without
        this, every decode step re-projects the full encoder output
        through k/v_proj — 2·S·d_model² FLOPs per layer per token, ~100×
        the rest of the step for src 1024 summarization."""
        return (
            self._split(self.k_proj(kv_hidden), self.kv_heads),
            self._split(self.v_proj(kv_hidden), self.kv_heads),
        )

    @nn.compact
    def _cache_kv(self, key: jnp.ndarray, value: jnp.ndarray,
                  cache_positions: jnp.ndarray | None = None):
        """Append this step's k/v into the cache.

        ``cache_positions`` (B,) int32 switches to PER-ROW writes — each
        row lands at its own cache slot, the continuous-batching contract
        where every serving slot sits at a different decode offset
        (``mode="drop"`` makes an out-of-range position a no-op, which is
        how idle slots park).  q_len may exceed 1: row b's queries write
        the contiguous span ``cache_positions[b] + [0, q_len)`` — the
        warm-admission contract, where each slot ingests its uncached
        prompt tail at its own start offset.  Without ``cache_positions``
        the whole batch writes at the shared ``cache_index`` (the
        static-batch generation loops).

        Under ``kv_cache_context("int8")`` the buffers are s8 with
        per-head per-position f32 ``key_scale``/``value_scale`` leaves
        (``ops.flash_attention.quantize_kv`` — the owning quantize
        implementation): each write quantizes its own rows, so nothing
        ever requantizes.  Returns ``(k, v, k_scale, v_scale, idx)``;
        scales are None on the f32 path."""
        from distributed_llms_example_tpu.ops.flash_attention import quantize_kv
        from distributed_llms_example_tpu.parallel.activation import (
            current_kv_cache_dtype,
        )

        int8_kv = current_kv_cache_dtype() == "int8"
        store_dtype = jnp.int8 if int8_kv else key.dtype
        is_initialized = self.has_variable("cache", "cached_key")
        cached_k = self.variable("cache", "cached_key", jnp.zeros, key.shape, store_dtype)
        cached_v = self.variable("cache", "cached_value", jnp.zeros, value.shape, store_dtype)
        if int8_kv:
            k_scale = self.variable(
                "cache", "key_scale", jnp.zeros, key.shape[:3], jnp.float32
            )
            v_scale = self.variable(
                "cache", "value_scale", jnp.zeros, value.shape[:3], jnp.float32
            )
        cache_index = self.variable("cache", "cache_index", lambda: jnp.array(0, dtype=jnp.int32))
        idx = cache_index.value
        if is_initialized:
            if int8_kv:
                key, ks_new = quantize_kv(key)
                value, vs_new = quantize_kv(value)
            if cache_positions is not None:
                b = jnp.arange(key.shape[0])
                if key.shape[2] == 1:
                    k = cached_k.value.at[b, :, cache_positions].set(
                        key[:, :, 0, :], mode="drop"
                    )
                    v = cached_v.value.at[b, :, cache_positions].set(
                        value[:, :, 0, :], mode="drop"
                    )
                    cached_k.value, cached_v.value = k, v
                    if int8_kv:
                        k_scale.value = k_scale.value.at[b, :, cache_positions].set(
                            ks_new[:, :, 0], mode="drop"
                        )
                        v_scale.value = v_scale.value.at[b, :, cache_positions].set(
                            vs_new[:, :, 0], mode="drop"
                        )
                else:
                    # per-row multi-token span: row b writes positions
                    # cache_positions[b] + [0, T).  Advanced indexing with
                    # a mid-axis slice puts the (B, T) index result in
                    # front, so values transpose to (B, T, H[, D]).
                    pos = cache_positions[:, None] + jnp.arange(key.shape[2])[None, :]
                    k = cached_k.value.at[b[:, None], :, pos].set(
                        key.transpose(0, 2, 1, 3), mode="drop"
                    )
                    v = cached_v.value.at[b[:, None], :, pos].set(
                        value.transpose(0, 2, 1, 3), mode="drop"
                    )
                    cached_k.value, cached_v.value = k, v
                    if int8_kv:
                        k_scale.value = k_scale.value.at[b[:, None], :, pos].set(
                            ks_new.transpose(0, 2, 1), mode="drop"
                        )
                        v_scale.value = v_scale.value.at[b[:, None], :, pos].set(
                            vs_new.transpose(0, 2, 1), mode="drop"
                        )
                # the engine owns per-slot offsets; the shared counter is
                # meaningless here and stays put
            else:
                k = jax.lax.dynamic_update_slice(cached_k.value, key, (0, 0, idx, 0))
                v = jax.lax.dynamic_update_slice(cached_v.value, value, (0, 0, idx, 0))
                cached_k.value, cached_v.value = k, v
                if int8_kv:
                    k_scale.value = jax.lax.dynamic_update_slice(
                        k_scale.value, ks_new, (0, 0, idx)
                    )
                    v_scale.value = jax.lax.dynamic_update_slice(
                        v_scale.value, vs_new, (0, 0, idx)
                    )
                cache_index.value = idx + key.shape[2]
        else:
            k, v = cached_k.value, cached_v.value
        if int8_kv:
            return k, v, k_scale.value, v_scale.value, idx
        return k, v, None, None, idx

    def __call__(
        self,
        hidden: jnp.ndarray,
        kv_hidden: jnp.ndarray | None = None,
        bias: jnp.ndarray | None = None,
        use_cache: bool = False,
        positions: jnp.ndarray | None = None,
        cross_kv: tuple[jnp.ndarray, jnp.ndarray] | None = None,
        deterministic: bool = True,
        cache_positions: jnp.ndarray | None = None,
    ) -> jnp.ndarray:
        """``positions``: optional (batch, q_len) absolute positions for RoPE
        — needed when cache slots don't equal sequence positions (right-
        padded prompts).  Defaults to cache-index/arange positions.
        ``cross_kv``: precomputed ``project_kv`` output — skips the k/v
        projections entirely (cross-attention decode).  ``deterministic``
        gates ``probs_dropout_rate`` (training passes False + a "dropout"
        rng, like every other dropout).  ``cache_positions``: (batch,)
        per-row cache write offsets for continuous-batching decode (each
        serving slot at its own position; q_len rows > 1 write the
        contiguous span starting there — warm prefix admission and the
        speculative verify block both ride this, up to the decode
        kernel's ``MAX_DECODE_Q_ROWS``) — defaults to the shared
        ``cache_index`` counter."""
        q = self._split(self.q_proj(hidden), self.num_heads)
        if cross_kv is not None:
            k, v = cross_kv
            if k.shape[0] != hidden.shape[0]:
                if self.kv_heads != self.num_heads:
                    # GQA cross-attention cannot fold beams next to heads
                    # (head counts already differ): replicate K/V per beam
                    # instead — correct, just without the traffic saving
                    G = hidden.shape[0] // k.shape[0]
                    k = jnp.repeat(k, G, axis=0)
                    v = jnp.repeat(v, G, axis=0)
                else:
                    # beam decode: every beam of a row shares the row's
                    # cross K/V — fold the beam group next to heads so K/V
                    # stream once per row instead of once per beam copy
                    # (the dominant decode-step HBM traffic)
                    out = beam_grouped_attention(q, k, v, bias, dtype=self.dtype)
                    b_, h_, s_, d_ = out.shape
                    return self.o_proj(out.transpose(0, 2, 1, 3).reshape(b_, s_, h_ * d_))
        else:
            kv_src = hidden if kv_hidden is None else kv_hidden
            k = self._split(self.k_proj(kv_src), self.kv_heads)
            v = self._split(self.v_proj(kv_src), self.kv_heads)
            if self.qk_norm_eps is not None:
                q, k = self.q_norm(q), self.k_norm(k)

        offset = 0
        decode_offsets = None  # (B,) absolute position of q row 0, cached decode
        k_scale = v_scale = None  # int8 KV cache scales (f32 path: None)
        if use_cache and self.causal:
            # RoPE must see absolute positions, so rotate before caching
            if self.use_rope:
                if positions is None:
                    if cache_positions is not None:
                        positions = cache_positions[:, None] + jnp.arange(q.shape[2])[None, :]
                    else:
                        # peek the index without mutating (mutation happens in _cache_kv)
                        idx = (
                            self.get_variable("cache", "cache_index")
                            if self.has_variable("cache", "cache_index")
                            else 0
                        )
                        positions = (jnp.arange(q.shape[2]) + idx)[None, :]
                cos, sin = rope_cos_sin(positions, self.head_dim, self.rope_theta)
                cos, sin = cos[:, None], sin[:, None]  # add heads axis
                q = apply_rope(q, cos, sin)
                k = apply_rope(k, cos, sin)
            k, v, k_scale, v_scale, offset = self._cache_kv(k, v, cache_positions)
            # validity + causality are the DECODE dispatch's job below:
            # per-row offsets feed either the decode kernel's in-kernel
            # length mask or decode_step_bias on the XLA path
            decode_offsets = (
                cache_positions
                if cache_positions is not None
                else jnp.full((q.shape[0],), offset, jnp.int32)
            )
        elif self.use_rope:
            if positions is None:
                pos = jnp.arange(q.shape[2])[None, :]
                manual = current_manual_seq()
                if manual is not None:
                    # inside a manual sequence region q holds a LOCAL shard;
                    # RoPE must see absolute positions
                    pos = pos + jax.lax.axis_index(manual[0]) * q.shape[2]
            else:
                pos = positions
            cos, sin = rope_cos_sin(pos, self.head_dim, self.rope_theta)
            cos, sin = cos[:, None], sin[:, None]  # add heads axis
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)

        # grouped-query attention: the cached decode kernel reads each KV head
        # once for the ``rep`` query heads that share it (folded into its q
        # rows below); every other path gets K and V repeated to the q heads
        rep = self.num_heads // self.kv_heads
        mesh = current_mesh()
        fold_gqa = (
            rep > 1
            and decode_offsets is not None
            and q.shape[2] * rep <= MAX_DECODE_Q_ROWS
            and (bias is None or bias.shape[1] == bias.shape[2] == 1)
            and (mesh is None or self.kv_heads % mesh.shape.get("tensor", 1) == 0)
            and (deterministic or not self.probs_dropout_rate)
            and select_decode_impl(
                self.attention_impl, batch=q.shape[0], heads=self.kv_heads,
                head_dim=self.head_dim, q_len=q.shape[2] * rep, kv_len=k.shape[2],
                mesh=mesh, backend=jax.default_backend(), device_count=jax.device_count(),
            )[0] == "flash_decode"
        )
        if rep > 1 and not fold_gqa:
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
            if k_scale is not None:
                k_scale = jnp.repeat(k_scale, rep, axis=1)
                v_scale = jnp.repeat(v_scale, rep, axis=1)

        # causal masking for the non-cached path is applied here (the cached
        # path built step_bias above): natively by the flash kernel, or as an
        # additive bias for the XLA path.
        causal_here = self.causal and not use_cache
        manual = current_manual_seq()
        if manual is not None and use_cache:
            # no KV-cache path inside the manual region: cache slots would
            # be indexed with LOCAL shard positions — fail loudly rather
            # than decode silently wrong logits
            raise ValueError(
                "use_cache is not supported inside a manual sequence region "
                "(pipeline stage×sequence is training/teacher-forced only; "
                "unstack the pipelined params to decode)"
            )
        if manual is not None:
            if self.attention_impl in ("xla", "flash"):
                # the region is manual over the sequence axis: activations
                # hold local shards and only the ring body can run.  A
                # forced non-ring impl must fail loudly, not be silently
                # overridden (same contract as the trainer's forced-ring
                # startup validation).
                raise ValueError(
                    f"attention_impl={self.attention_impl!r} cannot run inside a "
                    "manual sequence region (pipeline stage×sequence executes "
                    "ring attention only); use 'auto' or 'ring'"
                )
            # Tracing inside a shard_map that is manual over the sequence
            # axis (the stage×sequence pipeline): q/k/v hold LOCAL sequence
            # shards and the normal dispatch (which opens its own shard_map
            # over global arrays) cannot run.  Use the in-region ring body
            # directly — collectives over the manual axis are exactly what
            # is legal here.
            if bias is not None and (bias.shape[1] != 1 or bias.shape[2] != 1):
                raise ValueError(
                    "manual sequence region needs a K-only bias (b|1, 1, 1, K); "
                    f"got {bias.shape}"
                )
            _log_impl_once("ring", "manual sequence region (pipeline stage×sequence)")
            out = ring_attention(
                q, k, v, bias,
                axis_name=manual[0], axis_size=manual[1],
                causal=causal_here, dtype=self.dtype,
                # partial-manual region: bf16 ppermute transposes hit the
                # partitioner's copy-chain bug — ride the ring in fp32
                plumb_fp32=True,
            )
            b, h, s, d = out.shape
            return self.o_proj(out.transpose(0, 2, 1, 3).reshape(b, s, h * d))
        if fold_gqa:
            _log_impl_once("flash_decode", f"grouped: {rep} query heads a KV head as q rows")
            b, _, t, d = q.shape
            rows = q.reshape(b, self.kv_heads, rep, t, d).swapaxes(2, 3).reshape(b, self.kv_heads, t * rep, d)
            out = flash_decode_run(
                rows, k, v, bias, offsets=decode_offsets, mesh=mesh,
                k_scale=k_scale, v_scale=v_scale, dtype=self.dtype, q_group=rep,
            )
            out = out.reshape(b, self.kv_heads, t, rep, d).swapaxes(2, 3).reshape(b, self.num_heads, t, d)
            return self.o_proj(out.transpose(0, 2, 1, 3).reshape(b, t, self.num_heads * d))
        if decode_offsets is not None:
            decode_dropout = (
                float(self.probs_dropout_rate) if not deterministic else 0.0
            )
            impl, reason = select_decode_impl(
                self.attention_impl,
                batch=q.shape[0],
                heads=self.num_heads,
                head_dim=self.head_dim,
                q_len=q.shape[2],
                kv_len=k.shape[2],
                mesh=mesh,
                backend=jax.default_backend(),
                device_count=jax.device_count(),
            )
            if decode_dropout > 0.0 and impl == "flash_decode":
                # the decode kernel has no in-kernel mask stream; a decode
                # pass that WANTS probs dropout (MC-dropout eval) keeps the
                # old XLA semantics instead of silently going deterministic
                impl, reason = "xla", "probs dropout requested on cached decode"
            _log_impl_once(impl, reason)
            if impl == "flash_decode":
                # bias here is the caller's constant padding mask only —
                # validity/causality ride the kernel's per-row length mask;
                # int8 KV scales dequantize per kv tile inside the kernel
                out = flash_decode_run(
                    q, k, v, bias, offsets=decode_offsets, mesh=mesh,
                    k_scale=k_scale, v_scale=v_scale,
                    dtype=self.dtype,
                )
            else:
                if k_scale is not None:
                    # the XLA fallback dequantizes through the IDENTICAL
                    # expression the kernel evaluates per tile
                    from distributed_llms_example_tpu.ops.flash_attention import (
                        dequantize_kv,
                    )

                    k = dequantize_kv(k, k_scale)
                    v = dequantize_kv(v, v_scale)
                step = decode_step_bias(decode_offsets, q.shape[2], k.shape[2])
                out = dot_product_attention(
                    q, k, v, step if bias is None else bias + step,
                    dtype=self.dtype,
                    dropout_rate=decode_dropout,
                    dropout_rng=(
                        self.make_rng("dropout") if decode_dropout > 0.0 else None
                    ),
                )
            b, h, s, d = out.shape
            return self.o_proj(out.transpose(0, 2, 1, 3).reshape(b, s, h * d))
        impl, reason = select_attention_impl(
            self.attention_impl,
            batch=q.shape[0],
            heads=self.num_heads,
            head_dim=self.head_dim,
            q_len=q.shape[2],
            kv_len=k.shape[2],
            use_cache=use_cache,
            mesh=mesh,
            backend=jax.default_backend(),
            device_count=jax.device_count(),
            causal=causal_here,
            bias_kv_only=None if bias is None else (bias.shape[1] == 1 and bias.shape[2] == 1),
        )
        _log_impl_once(impl, reason)
        probs_dropout = (
            float(self.probs_dropout_rate) if not deterministic else 0.0
        )
        if impl == "ring":
            if probs_dropout > 0.0:
                raise ValueError(
                    "probs_dropout_rate > 0 is not supported on the ring "
                    "attention path (the rotating kv blocks would need a "
                    "ring-aware mask stream); train with attention_impl "
                    "'flash'/'xla' or probs dropout off"
                )
            out = ring_attention_sharded(
                q, k, v, bias, mesh=mesh, causal=causal_here, dtype=self.dtype
            )
        elif impl == "flash":
            seed = None
            if probs_dropout > 0.0:
                from distributed_llms_example_tpu.ops.fused_dropout import (
                    seed_from_key,
                )

                seed = seed_from_key(self.make_rng("dropout"))
            out = self._flash_run(
                q, k, v, bias, causal_here, mesh,
                dropout_rate=probs_dropout, dropout_seed=seed,
            )
        else:
            if causal_here:
                step = make_causal_bias(q.shape[2], k.shape[2])
                bias = step if bias is None else bias + step
            out = dot_product_attention(
                q, k, v, bias, dtype=self.dtype,
                dropout_rate=probs_dropout,
                dropout_rng=(
                    self.make_rng("dropout") if probs_dropout > 0.0 else None
                ),
            )
        b, h, s, d = out.shape
        return self.o_proj(out.transpose(0, 2, 1, 3).reshape(b, s, h * d))

    def _flash_run(
        self,
        q: jnp.ndarray,
        k: jnp.ndarray,
        v: jnp.ndarray,
        bias: jnp.ndarray | None,
        causal: bool,
        mesh: Mesh | None,
        dropout_rate: float = 0.0,
        dropout_seed=None,
    ) -> jnp.ndarray:
        return flash_run(
            q, k, v, bias, causal=causal, mesh=mesh, dtype=self.dtype,
            dropout_rate=dropout_rate, dropout_seed=dropout_seed,
        )


def flash_run(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: jnp.ndarray | None,
    *,
    causal: bool,
    mesh: Mesh | None,
    dtype: jnp.dtype,
    scale: float | None = None,
    dropout_rate: float = 0.0,
    dropout_seed=None,
) -> jnp.ndarray:
    """Run the Pallas kernel — directly on one device, per-shard under
    ``shard_map`` on a mesh (batch over data×fsdp×expert, heads over
    tensor; attention itself never mixes batches or heads, so the kernel
    body needs no collectives).  Constant-mask biases only: the shard_map
    runs with check_vma=False, under which a learned bias's gradient would
    silently miss its cross-shard psum — learned biases use
    ops/flash_attention.flash_attention_lbias_sharded, whose hand-written
    vjp performs that psum explicitly.

    ``dropout_rate`` > 0 (with an int32 ``dropout_seed``) turns on the
    in-kernel attention-probs dropout; each shard folds its axis indices
    into the seed so shards draw independent masks."""
    if mesh is None or math.prod(mesh.devices.shape) == 1:
        return flash_attention(
            q, k, v, bias, causal=causal, dtype=dtype, scale=scale,
            dropout_rate=dropout_rate, dropout_seed=dropout_seed,
        )
    batch_axes = tuple(a for a in BATCH_AXES if a in mesh.shape)
    head_axis = "tensor" if "tensor" in mesh.shape else None
    qkv_spec = P(batch_axes or None, head_axis, None, None)
    has_dropout = dropout_rate > 0.0 and dropout_seed is not None
    fold_axes = batch_axes + ((head_axis,) if head_axis else ())

    def run(q, k, v, *rest):
        rest = list(rest)
        seed = rest.pop() if has_dropout else None
        if seed is not None and fold_axes:
            from distributed_llms_example_tpu.ops.fused_dropout import _shard_seed

            seed = _shard_seed(seed, fold_axes)
        return flash_attention(
            q, k, v, rest[0] if rest else None, causal=causal, dtype=dtype,
            scale=scale, dropout_rate=dropout_rate, dropout_seed=seed,
        )

    args = (q, k, v)
    in_specs = (qkv_spec, qkv_spec, qkv_spec)
    if bias is not None:
        bias_spec = P(
            (batch_axes or None) if bias.shape[0] != 1 else None,
            head_axis if bias.shape[1] != 1 else None,
            None,
            None,
        )
        args = (*args, bias)
        in_specs = (*in_specs, bias_spec)
    if has_dropout:
        args = (*args, jnp.asarray(dropout_seed, jnp.int32).reshape(()))
        in_specs = (*in_specs, P())
    return jax.shard_map(
        run, mesh=mesh, in_specs=in_specs, out_specs=qkv_spec, check_vma=False
    )(*args)
