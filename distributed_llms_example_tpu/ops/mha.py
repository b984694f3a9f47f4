"""Shared multi-head attention module (BART/LLaMA families).

One module covers: scaled dot-product attention with optional biases in the
projections, causal masking, fixed-shape KV caching for autoregressive
decode, rotary position embeddings (LLaMA), and grouped-query attention
(fewer KV heads than Q heads).  T5 keeps its own attention (unscaled
scores + relative position bias are peculiar to it).
"""

from __future__ import annotations

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


from distributed_llms_example_tpu.ops.attention import (
    NEG_INF,
    beam_grouped_attention,
    dot_product_attention,
    make_band_bias,
    make_causal_bias,
)
from distributed_llms_example_tpu.ops.flash_attention import (
    MAX_DECODE_Q_ROWS,
    decode_step_fits,
    flash_attention,
    flash_decode_run,
    flash_decode_supported,
    flash_prompt_attention,
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


def _log_impl_once(impl: str, reason: str, **counts: int) -> None:
    """One-time JSON line saying which attention path a module selected —
    so "flash is wired in" claims are verifiable from any run log.
    ``counts``: trace-time tallies the line carries as numbers."""
    key = (impl, reason)
    if key not in _IMPL_LOGGED:
        _IMPL_LOGGED.add(key)
        log_json({"event": "attention_impl", "impl": impl, "reason": reason, **counts})


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
    prompt: bool = False,
    window: int = 0,
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

    ``use_cache``: a cached call.  A decode step (or any call that continues
    a stored cache) is not this function's: ``select_decode_impl``.  A
    ``prompt`` is a cached call that STARTS its sequence (the prefill of a
    causal model: ``q_len`` new tokens attend each other, ``kv_len`` =
    ``q_len``, and the cache is written beside).  It takes the flash kernel
    under the kernel's usual conditions, as an uncached causal call does:
    XLA's path materialises float32 scores (8.6 GB a layer for one
    8,192-token row of 32 heads), and at lfm2's 1,024-token wave, whose
    0.13 GB would fit, the kernel read no slower (``serve_prefill_device_ms``
    10.84 -> 10.67; ``PERF.md``, PR 37).  ``window`` > 0 is a sliding-window
    layer: the kernel's window flavour, and the same rule.
    """
    if attention_impl not in ("auto", "flash", "ring", "xla"):
        raise ValueError(
            f"attention_impl={attention_impl!r}: must be 'auto', 'flash', 'ring', or 'xla'"
        )
    if attention_impl == "xla":
        return "xla", "forced"
    if use_cache:
        if not prompt:
            return "xla", "kv-cache decode step"
        if attention_impl == "ring":
            return "xla", "ring attention has no KV-cache path"
    seq_shards = mesh.shape.get("sequence", 1) if mesh is not None and not use_cache else 1
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
        q_len, kv_len, head_dim, causal=causal, has_learned_bias=has_learned_bias, window=window
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
    kv_dtype: jnp.dtype = jnp.bfloat16,
) -> tuple[str, str]:
    """(impl, reason) for a CACHED decode step — the serving twin of
    ``select_attention_impl``, pure and unit-testable.

    ``auto`` picks the Pallas **decode kernel** (``flash_decode``: one
    short q block — a single decode row, or the speculative verify's
    k+1 rows — against the cached K/V buffer, per-row length mask,
    dead-tile skip; one grid step streams a kv tile of every head of a
    cache slot as the leaf holds it, ``decode_step_heads``) on TPU when the
    cache length tiles and — under a multi-device mesh — batch/heads split evenly
    over (data×fsdp) and ``tensor`` (the kernel runs per-shard under
    ``shard_map``, like training flash; the step then holds the shard's
    heads).  ``flash`` forces the kernel wherever eligible; XLA attention
    (per-row masked ``dot_product_attention``) otherwise.  ``ring`` has no
    KV-cache path and falls back to XLA.  So does a step of which no group
    of heads fits the kernel's VMEM budget at the cache's kv tile
    (``decode_step_fits``, from ``kv_dtype``, the dtype the leaf holds: s8
    under the int8 cache, whose tiles count as the f32 they dequantize to).

    Where the line is: a cache shorter than 128 takes XLA.  At 128
    (bart-large-cnn's serving shape, 64 slots x 16 heads x d 64, bf16, one
    row) the kernel's twelve calls of a round take 0.60 ms inside the
    serving program on v5e, and the same cell with this shape sent to XLA
    ran a round of the same length (PR 26).  What then cost 4.4 ms a round
    on either route was the cache relaid around its reader; since PR 32
    the leaf is (B, L, H x d), which the row write and the kernel both take
    as it rests (``_cache_kv``), and XLA's path views it as (B, H, L, d)
    inside the program (PERF.md, Findings PR 32)."""
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
    kv_dtype = jnp.dtype(kv_dtype)
    shard_heads = heads // (mesh.shape.get("tensor", 1) if mesh is not None and device_count > 1 else 1)
    if not decode_step_fits(
        shard_heads, kv_len, head_dim, kv_dtype.itemsize, q_len=q_len, int8_scales=kv_dtype == jnp.int8,
    ):
        return "xla", (
            f"no group of {shard_heads} heads of {head_dim} fits the decode kernel's "
            f"VMEM budget (kv={kv_len}, {kv_dtype.name})"
        )
    if attention_impl == "flash":
        return "flash_decode", "forced"
    if backend != "tpu":
        return "xla", f"auto: backend={backend} (interpreted kernel is pure overhead)"
    if kv_len < 128:
        return "xla", "auto: cache too short to tile"
    return "flash_decode", "auto: TPU decode" + (
        " (shard_map per-shard)" if device_count > 1 else ""
    )


def select_cached_step(
    attention_impl: str,
    *,
    batch: int,
    heads: int,
    kv_heads: int,
    head_dim: int,
    q_len: int,
    kv_len: int,
    mesh: Mesh | None,
    kv_dtype: jnp.dtype = jnp.bfloat16,
    bias_kv_only: bool = True,
    dropout: bool = False,
) -> tuple[str, str, bool]:
    """(impl, reason, fold) of ``MultiHeadAttention._cached_attend``: which
    way a cached step of ``heads`` query heads over (batch, kv_len, kv_heads x
    head_dim) leaves goes (``select_decode_impl`` on this process's backend and
    devices), and whether grouped-query attention is FOLDED into the decode
    kernel's q rows (each KV head read once for the query heads that share
    it; any other route repeats K and V to the query heads).  ``bias_kv_only``:
    no bias, or one with no head or row axis (a wider one cannot fold);
    ``dropout``: probs dropout is wanted, which the kernel does not draw.
    The serving engine asks the same question of the same function to count
    what a round's attention reads: ``flash_decode`` fetches the live slots'
    tiles alone, XLA's path every slot's whole leaf."""
    backend, devices = jax.default_backend(), jax.device_count()
    ask = lambda **kw: select_decode_impl(  # noqa: E731
        attention_impl, batch=batch, head_dim=head_dim, kv_len=kv_len, mesh=mesh,
        backend=backend, device_count=devices, kv_dtype=kv_dtype, **kw,
    )
    rep = heads // kv_heads
    if (
        rep > 1
        and q_len * rep <= MAX_DECODE_Q_ROWS
        and bias_kv_only
        and (mesh is None or kv_heads % mesh.shape.get("tensor", 1) == 0)
        and not dropout
        and ask(heads=kv_heads, q_len=q_len * rep)[0] == "flash_decode"
    ):
        return "flash_decode", f"grouped: {rep} query heads a KV head as q rows", True
    impl, reason = ask(heads=heads, q_len=q_len)
    if dropout and impl == "flash_decode":
        # the decode kernel has no in-kernel mask stream; a decode
        # pass that WANTS probs dropout (MC-dropout eval) keeps the
        # old XLA semantics instead of silently going deterministic
        impl, reason = "xla", "probs dropout requested on cached decode"
    return impl, reason, False


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


@dataclasses.dataclass(frozen=True)
class YarnRope:
    """YaRN's numbers as a published ``rope_parameters`` section gives them
    (``rope_type`` "yarn"): the context is stretched ``factor`` times past
    ``original_max_position_embeddings``, the dimensions that turn more than
    ``beta_fast`` times over that length are left alone, those that turn less
    than ``beta_slow`` times are slowed ``factor`` times, the ones between are
    blended linearly; cos and sin are multiplied by ``attention_factor``."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


def rope_inv_freq(head_dim: int, theta: float, yarn: YarnRope | None = None) -> jnp.ndarray:
    """(head_dim / 2,) rotation frequencies, float32.  Plain: ``theta^(-2n /
    head_dim)``.  YaRN: with ``dim(b) = head_dim ln(L0 / (2 pi b)) / (2 ln
    theta)`` (the dimension that turns ``b`` times over the original length
    ``L0``), ``lo = max(floor(dim(beta_fast)), 0)``, ``hi = min(ceil(dim(
    beta_slow)), head_dim - 1)`` and the ramp ``r_n = clip((n - lo) / (hi -
    lo), 0, 1)``: ``inv_freq_n / factor`` where ``r_n`` = 1, ``inv_freq_n``
    where it is 0, their blend between."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if yarn is None:
        return inv_freq
    turns_at = lambda b: head_dim * math.log(yarn.original_max_position_embeddings / (2 * math.pi * b)) / (  # noqa: E731
        2 * math.log(theta))
    lo = max(math.floor(turns_at(yarn.beta_fast)), 0)
    hi = min(math.ceil(turns_at(yarn.beta_slow)), head_dim - 1)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return inv_freq / yarn.factor * ramp + inv_freq * (1.0 - ramp)


def rope_cos_sin(positions: jnp.ndarray, head_dim: int, theta: float = 10000.0,
                 yarn: YarnRope | None = None) -> tuple:
    """(..., head_dim) cos/sin tables for the given integer positions, in the
    HF half-rotation layout (freqs repeated, not interleaved), float32; under
    ``yarn`` at its frequencies and times its attention factor."""
    inv_freq = rope_inv_freq(head_dim, theta, yarn)
    freqs = positions.astype(jnp.float32)[..., None] * inv_freq  # (..., head_dim/2)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    if yarn is None:
        return jnp.cos(emb), jnp.sin(emb)
    return jnp.cos(emb) * yarn.attention_factor, jnp.sin(emb) * yarn.attention_factor


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: (batch, heads, seq, head_dim); cos/sin: (seq, head_dim) or
    broadcastable."""
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return (x * cos + rotated * sin).astype(x.dtype)


def cache_kv(module: nn.Module, key: jnp.ndarray, value: jnp.ndarray,
             cache_positions: jnp.ndarray | None = None):
    """Append this step's k/v into ``module``'s cache (called inside its
    ``@nn.compact`` ``_cache_kv``): the package's ONE cache layout, for
    ``MultiHeadAttention`` and ``models/t5.py`` alike.

    ``key``/``value``: (B, kv_heads, T, head_dim), after RoPE and the
    q/k norms.  A cache leaf is ``(batch, length, kv_heads x
    head_dim)``: a position's heads side by side, head_dim minor —
    what ``k_proj`` returned before ``_split``.  That is the layout
    XLA's row write fills and ``flash_decode`` reads, and the one the
    chip lets the array rest in, so a decode step copies no leaf
    (PR 32; (B, H, L, d) was relaid three times a leaf a round).

    ``cache_positions`` (B,) int32 switches to PER-ROW writes — each
    row lands at its own cache slot, the continuous-batching contract
    where every serving slot sits at a different decode offset
    (``mode="drop"`` makes an out-of-range position a no-op, which is
    how idle slots park).  T may exceed 1: row b's queries write
    the contiguous span ``cache_positions[b] + [0, T)`` — the
    warm-admission contract, where each slot ingests its uncached
    prompt tail at its own start offset.  Without ``cache_positions``
    the whole batch writes at the shared ``cache_index`` (the
    static-batch generation loops).

    Under ``kv_cache_context("int8")`` the buffers are s8 with
    per-head per-position f32 ``key_scale``/``value_scale`` leaves,
    ``(batch, length, kv_heads)`` (``ops.flash_attention.quantize_kv``
    — the owning quantize implementation): each write quantizes its
    own rows, so nothing ever requantizes.  Returns ``(k, v, k_scale,
    v_scale, idx)``, the leaves whole; scales are None on the f32
    path."""
    from distributed_llms_example_tpu.ops.flash_attention import quantize_kv
    from distributed_llms_example_tpu.parallel.activation import (
        current_kv_cache_dtype,
    )

    int8_kv = current_kv_cache_dtype() == "int8"
    store_dtype = jnp.int8 if int8_kv else key.dtype
    b, heads, t, d = key.shape
    rows = lambda x: x.transpose(0, 2, 1, 3).reshape(b, t, heads * d)  # noqa: E731
    is_initialized = module.has_variable("cache", "cached_key")
    cached_k = module.variable("cache", "cached_key", jnp.zeros, (b, t, heads * d), store_dtype)
    cached_v = module.variable("cache", "cached_value", jnp.zeros, (b, t, heads * d), store_dtype)
    if int8_kv:
        k_scale = module.variable(
            "cache", "key_scale", jnp.zeros, (b, t, heads), jnp.float32
        )
        v_scale = module.variable(
            "cache", "value_scale", jnp.zeros, (b, t, heads), jnp.float32
        )
    cache_index = module.variable("cache", "cache_index", lambda: jnp.array(0, dtype=jnp.int32))
    idx = cache_index.value
    if is_initialized:
        if int8_kv:
            # one scale a (position, head): quantize the head_dim rows,
            # then lay values and scales as their leaves keep them
            (key, ks_new), (value, vs_new) = quantize_kv(key), quantize_kv(value)
        leaves = [(cached_k, rows(key)), (cached_v, rows(value))]
        if int8_kv:
            leaves += [(k_scale, ks_new.transpose(0, 2, 1)), (v_scale, vs_new.transpose(0, 2, 1))]
        if cache_positions is not None:
            batch = jnp.arange(b)
            if t == 1:
                # one contiguous row of kv_heads x head_dim lanes a slot
                for leaf, new in leaves:
                    leaf.value = leaf.value.at[batch, cache_positions].set(
                        new[:, 0], mode="drop"
                    )
            else:
                # per-row multi-token span: row b writes positions
                # cache_positions[b] + [0, T)
                pos = cache_positions[:, None] + jnp.arange(t)[None, :]
                for leaf, new in leaves:
                    leaf.value = leaf.value.at[batch[:, None], pos].set(new, mode="drop")
            # the engine owns per-slot offsets; the shared counter is
            # meaningless here and stays put
        else:
            for leaf, new in leaves:
                leaf.value = jax.lax.dynamic_update_slice(leaf.value, new, (0, idx, 0))
            cache_index.value = idx + t
    if int8_kv:
        return cached_k.value, cached_v.value, k_scale.value, v_scale.value, idx
    return cached_k.value, cached_v.value, None, None, idx


def cache_window_kv(module: nn.Module, key: jnp.ndarray, value: jnp.ndarray, window: int,
                    positions: jnp.ndarray, real: jnp.ndarray, count: bool = True):
    """A sliding-window layer's cache: ``cache_kv``'s twin for a layer whose
    queries read the last ``window`` positions and no more.  The leaves
    ``window_key`` / ``window_value`` are ``(batch, window, kv_heads x
    head_dim)`` at ANY context: the memory is the mechanism.  A key is cached
    after RoPE and the head norms, so the order of the entries does not matter
    to the softmax, and position p rests at entry ``p mod window``: entry r
    holds the newest position congruent to r, which is there from position r
    on (``flash_decode``'s ``ring``: validity by the row's position).

    ``key``/``value``: (B, kv_heads, T, head_dim).  ``positions`` (B, T): the
    tokens' absolute positions; ``real`` (B, T) bool: which of them are tokens
    (a right-padded prompt's tail is not, an idle serving slot's step is not).

    * T == 1, a decode step: row b writes entry ``positions[b] mod window``
      where ``real``; nothing else moves.
    * T > 1, a PROMPT, which starts its sequence: the leaf is written whole
      from the prompt's last ``window`` real positions, whatever the padding
      (entry r takes the newest real position congruent to r), so nothing a
      slot's last occupant left can be read: its entries are overwritten or
      lie past the new sequence's position.  The real tokens are the row's
      first ``sum(real)`` (right padding, as everywhere in the package).

    ``count``: the call advances the shared ``cache_index`` counter (the
    static generation loops; a serving engine owns per-slot positions and
    passes False).  The int8 K/V cache is not implemented for a window leaf.
    Returns the two leaves whole."""
    from distributed_llms_example_tpu.parallel.activation import current_kv_cache_dtype

    if current_kv_cache_dtype() == "int8":
        raise NotImplementedError("the int8 K/V cache has no window leaf: serve a sliding-window model with f32")
    b, heads, t, d = key.shape
    rows = lambda x: x.transpose(0, 2, 1, 3).reshape(b, t, heads * d)  # noqa: E731
    is_initialized = module.has_variable("cache", "window_key")
    ring_k = module.variable("cache", "window_key", jnp.zeros, (b, window, heads * d), key.dtype)
    ring_v = module.variable("cache", "window_value", jnp.zeros, (b, window, heads * d), key.dtype)
    cache_index = module.variable("cache", "cache_index", lambda: jnp.array(0, dtype=jnp.int32))
    if is_initialized:
        if count:
            cache_index.value = cache_index.value + t
        positions = jnp.broadcast_to(positions, (b, t))
        if t == 1:
            at = jnp.where(real[:, 0], positions[:, 0] % window, window)  # past the leaf: the write drops
            for leaf, new in ((ring_k, rows(key)), (ring_v, rows(value))):
                leaf.value = leaf.value.at[jnp.arange(b), at].set(new[:, 0], mode="drop")
        else:
            n = jnp.sum(real, axis=1, dtype=jnp.int32)[:, None]  # (B, 1) real tokens, the row's first
            r = jnp.arange(window, dtype=jnp.int32)[None, :]
            newest = r + window * ((n - 1 - r) // window)  # the newest real position congruent to r
            src = jnp.where(r < n, newest, jnp.minimum(r, t - 1))  # past the prompt: never valid, any row
            for leaf, new in ((ring_k, rows(key)), (ring_v, rows(value))):
                leaf.value = jnp.take_along_axis(new, src[:, :, None], axis=1)
    return ring_k.value, ring_v.value


def cache_heads_view(k: jnp.ndarray, v: jnp.ndarray, k_scale, v_scale, kv_heads: int):
    """XLA's cached path: the leaves ``k``/``v`` (B, L, kv_heads x d) viewed
    as (B, kv_heads, L, d) inside the program, the int8 cache dequantized
    through the IDENTICAL expression the kernel evaluates per tile
    (``dequantize_kv``, scales (B, L, kv_heads)).  If the compiler copies
    here, that is this path's cost: the leaf itself rests as it is."""
    from distributed_llms_example_tpu.ops.flash_attention import dequantize_kv

    b, kv_len, _ = k.shape
    k, v = (x.reshape(b, kv_len, kv_heads, -1).transpose(0, 2, 1, 3) for x in (k, v))
    if k_scale is not None:
        k = dequantize_kv(k, k_scale.transpose(0, 2, 1))
        v = dequantize_kv(v, v_scale.transpose(0, 2, 1))
    return k, v


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
    # sliding-window attention (causal self-attention only): query i reads
    # keys j with 0 <= i - j < window, and the layer's cache holds the last
    # ``window`` positions and no more (``cache_window_kv``); None = all keys
    window: int | None = None
    # YaRN's scaling of the rotation (``rope_cos_sin``); None = the plain one
    rope_yarn: YarnRope | None = None
    # a fixed factor on every key, ``k = key_multiplier x W_k s`` (Falcon-H1's
    # µP ``key_multiplier``); 1.0 = the model has none, and nothing is traced
    key_multiplier: float = 1.0

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

    def project_kv(self, kv_hidden: jnp.ndarray, rows: bool = False) -> tuple[jnp.ndarray, jnp.ndarray]:
        """K/V projections alone, as ``__call__`` would compute them —
        (B, kv_heads, S, head_dim) each.  Generation precomputes these ONCE
        per sequence for cross-attention (the encoder output is fixed for
        the whole decode) and feeds them back via ``cross_kv``; without
        this, every decode step re-projects the full encoder output
        through k/v_proj — 2·S·d_model² FLOPs per layer per token, ~100×
        the rest of the step for src 1024 summarization.

        ``rows``: the pair as a CACHE keeps K/V, (B, S, kv_heads x head_dim)
        each — what ``k_proj`` returns before ``_split``, the self cache's
        layout (``cache_kv``) and the decode kernel's — for a holder that
        keeps it across many one-token steps (the serving engine's slots):
        ``__call__`` tells the two by rank and sends a 3-D pair through
        ``_cached_attend``.  A grouped-query cross attention keeps the 4-D
        pair and XLA's path."""
        k, v = self.k_proj(kv_hidden), self.v_proj(kv_hidden)
        if self.key_multiplier != 1.0:
            k = k * self.key_multiplier
        if rows and self.kv_heads == self.num_heads:
            return k, v
        return self._split(k, self.kv_heads), self._split(v, self.kv_heads)

    @nn.compact
    def _cache_kv(self, key: jnp.ndarray, value: jnp.ndarray,
                  cache_positions: jnp.ndarray | None = None, ring: tuple | None = None):
        """The layer's cache write: ``cache_kv``, or for a ``window`` layer
        ``cache_window_kv`` with ``ring`` = (positions, real)."""
        if ring is not None:
            return cache_window_kv(self, key, value, self.window, *ring, count=cache_positions is None)
        return cache_kv(self, key, value, cache_positions)

    @nn.nowrap  # no scope of its own: the kernel's call site stays ``self_attn`` (serve_decode_attn_ms finds it so)
    def _cached_attend(
        self,
        q: jnp.ndarray,
        k: jnp.ndarray,
        v: jnp.ndarray,
        k_scale: jnp.ndarray | None,
        v_scale: jnp.ndarray | None,
        bias: jnp.ndarray | None,
        offsets: jnp.ndarray,
        deterministic: bool,
        ring: bool = False,
        live: jnp.ndarray | None = None,
    ) -> jnp.ndarray:
        """A cached decode step's attention: ``q`` (B, heads, T, d) against
        the cache leaves ``k``/``v`` (B, L, kv_heads x d) (int8 scales (B,
        L, kv_heads) or None), row b's first query at absolute position
        ``offsets[b]``.  ``bias`` is the caller's constant padding mask
        only: validity and causality are the dispatch's job here — the
        decode kernel's in-kernel per-row length mask, or
        ``decode_step_bias`` on XLA's path, which views the leaf as (B, H,
        L, d) inside the program.  ``ring``: the leaves are a window layer's
        (``cache_window_kv``) and ``offsets`` the rows' absolute positions;
        an entry is valid by the row's position, the first ``min(position,
        window - 1) + 1`` of them, on either path.

        ``live`` (B,) bool: the rows that hold a sequence, which is all the
        decode kernel fetches (an idle row's output is zero there; XLA's path
        reads every row and its idle rows hold whatever their leaves give,
        which no caller reads).  None = what the offsets say: a row at or past
        the leaf's length is PARKED (its write was dropped, ``cache_kv``),
        which is how the serving engine's step programs mark an idle slot; an
        evaluation decode parks nothing, so every row is live."""
        b, _, t, d = q.shape
        kv_len = k.shape[1]
        if live is None:
            live = offsets < kv_len
        mesh = current_mesh()
        dropout = float(self.probs_dropout_rate) if not deterministic else 0.0
        # grouped-query attention: the decode kernel reads each KV head once
        # for the ``rep`` query heads that share it, folded into its q rows;
        # every other path gets K and V repeated to the q heads
        rep = self.num_heads // self.kv_heads
        impl, reason, fold_gqa = select_cached_step(
            self.attention_impl, batch=b, heads=self.num_heads, kv_heads=self.kv_heads, head_dim=d,
            q_len=t, kv_len=kv_len, mesh=mesh, kv_dtype=k.dtype,
            bias_kv_only=bias is None or bias.shape[1] == bias.shape[2] == 1, dropout=dropout > 0.0,
        )
        _log_impl_once(impl, reason)
        if fold_gqa:
            rows = q.reshape(b, self.kv_heads, rep, t, d).swapaxes(2, 3).reshape(b, self.kv_heads, t * rep, d)
            out = flash_decode_run(
                rows, k, v, bias, offsets=offsets, live=live, mesh=mesh,
                k_scale=k_scale, v_scale=v_scale, dtype=self.dtype, q_group=rep, ring=ring,
            )
            return out.reshape(b, self.kv_heads, t, rep, d).swapaxes(2, 3).reshape(b, self.num_heads, t, d)
        if impl == "flash_decode":
            if rep > 1:
                # the leaf's merged axis apart, each KV head repeated in place
                k, v = (
                    jnp.repeat(x.reshape(b, kv_len, self.kv_heads, d), rep, axis=2).reshape(b, kv_len, -1)
                    for x in (k, v)
                )
                if k_scale is not None:
                    k_scale, v_scale = (jnp.repeat(x, rep, axis=2) for x in (k_scale, v_scale))
            # int8 KV scales dequantize per kv tile inside the kernel
            return flash_decode_run(
                q, k, v, bias, offsets=offsets, live=live, mesh=mesh,
                k_scale=k_scale, v_scale=v_scale, dtype=self.dtype, ring=ring,
            )
        k, v = cache_heads_view(k, v, k_scale, v_scale, self.kv_heads)
        if rep > 1:
            k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        # a ring's entries are valid by the row's position: the first min(position, window - 1) + 1
        step = decode_step_bias(jnp.clip(offsets, 0, kv_len - 1) if ring else offsets, t, kv_len)
        return dot_product_attention(
            q, k, v, step if bias is None else bias + step,
            dtype=self.dtype,
            dropout_rate=dropout,
            dropout_rng=self.make_rng("dropout") if dropout > 0.0 else None,
        )

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
        mask: jnp.ndarray | None = None,
        live: jnp.ndarray | None = None,
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
        ``cache_index`` counter.  ``mask`` (batch, cache width), the 0/1
        mask ``bias`` was made from: a cached ``window`` layer asks it which
        of the new tokens are real (its ring keeps a prompt's last real
        positions; an idle slot's step writes nothing); None = all are.

        A ``cross_kv`` pair of rank 3 is a holder's, (batch, source length,
        heads x head_dim) as ``project_kv(rows=True)`` returns it: the step
        goes the way a cached self-attention step goes (``_cached_attend``:
        the decode kernel where ``select_decode_impl`` says so, which then
        fetches a row's tiles up to the source's last real position and
        nothing of an idle row).  ``mask`` is then the source's 0/1 padding
        mask (batch, source length), from which that position is read, and
        ``live`` (batch,) bool says which rows hold a request (a serving
        slot's; None = all).

        A cached call of several tokens without ``cache_positions`` is a
        PROMPT and starts its sequence: where
        ``select_attention_impl`` sends it to the flash kernel (or the layer
        has a ``window``), the new tokens attend each other and what the
        cache held before is not read."""
        if self.window is not None and not (self.causal and cross_kv is None and kv_hidden is None):
            raise ValueError("window attention is causal self-attention")
        q = self._split(self.q_proj(hidden), self.num_heads)
        if cross_kv is not None:
            k, v = cross_kv
            if k.ndim == 3:
                b, src_len = k.shape[:2]
                # the kernel's per-row length: the source's last real position (the padding
                # mask rides along as the bias, so a mask with holes stays right)
                last = jnp.full((b,), src_len - 1, jnp.int32) if mask is None else jnp.max(
                    jnp.where(mask > 0, jnp.arange(src_len, dtype=jnp.int32), 0), axis=1)
                out = self._cached_attend(q, k, v, None, None, bias, last, deterministic, live=live)
                b_, h_, s_, d_ = out.shape
                return self.o_proj(out.transpose(0, 2, 1, 3).reshape(b_, s_, h_ * d_))
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
            if self.key_multiplier != 1.0:
                k = k * self.key_multiplier
            if self.qk_norm_eps is not None:
                q, k = self.q_norm(q), self.k_norm(k)

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
        if use_cache and self.causal:
            # RoPE must see absolute positions, so rotate before caching
            t = q.shape[2]
            if positions is None and (self.use_rope or self.window is not None):
                if cache_positions is not None:
                    positions = cache_positions[:, None] + jnp.arange(t)[None, :]
                else:
                    # peek the index without mutating (mutation happens in _cache_kv)
                    idx = (
                        self.get_variable("cache", "cache_index")
                        if self.has_variable("cache", "cache_index")
                        else 0
                    )
                    positions = (jnp.arange(t) + idx)[None, :]
            if self.use_rope:
                cos, sin = rope_cos_sin(positions, self.head_dim, self.rope_theta, self.rope_yarn)
                cos, sin = cos[:, None], sin[:, None]  # add heads axis
                q = apply_rope(q, cos, sin)
                k = apply_rope(k, cos, sin)
            prompt = cache_positions is None and t > 1
            if self.window is not None:
                out = self._window_cached(q, k, v, bias, positions, cache_positions, mask, prompt, deterministic)
            else:
                k_new, v_new = k, v
                k, v, k_scale, v_scale, offset = self._cache_kv(k, v, cache_positions)
                dropout = not deterministic and self.probs_dropout_rate > 0.0  # the prompt's kernel draws no mask
                if prompt and not dropout and self._prompt_impl(q, bias) == "flash":
                    out = self._prompt_attend(q, k_new, v_new, bias)
                else:
                    # (B,) absolute position of q row 0
                    decode_offsets = (
                        cache_positions
                        if cache_positions is not None
                        else jnp.full((q.shape[0],), offset, jnp.int32)
                    )
                    out = self._cached_attend(
                        q, k, v, k_scale, v_scale, bias, decode_offsets, deterministic
                    )
            b, h, s, d = out.shape
            return self.o_proj(out.transpose(0, 2, 1, 3).reshape(b, s, h * d))
        if self.use_rope:
            if positions is None:
                pos = jnp.arange(q.shape[2])[None, :]
                if manual is not None:
                    # inside a manual sequence region q holds a LOCAL shard;
                    # RoPE must see absolute positions
                    pos = pos + jax.lax.axis_index(manual[0]) * q.shape[2]
            else:
                pos = positions
            cos, sin = rope_cos_sin(pos, self.head_dim, self.rope_theta, self.rope_yarn)
            cos, sin = cos[:, None], sin[:, None]  # add heads axis
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)

        # grouped-query attention: K and V repeated to the q heads (a cached
        # step went its own way above and reads each KV head once)
        rep = self.num_heads // self.kv_heads
        mesh = current_mesh()
        if rep > 1:
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)

        # causal masking is applied here: natively by the flash kernel, or as
        # an additive bias for the XLA path.
        causal_here = self.causal and not use_cache
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
        if self.window is not None:
            # an uncached window layer (teacher forcing, training): the band as a
            # bias on XLA's path; the window kernel is forward only (a prompt's)
            impl, reason = "xla", "sliding window without a cache: the band as a bias"
            causal_here = False
            band = make_band_bias(q.shape[2], k.shape[2], self.window)
            bias = band if bias is None else bias + band
        else:
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

    @nn.nowrap
    def _prompt_impl(self, q: jnp.ndarray, bias: jnp.ndarray | None) -> str:
        """Which path a cached PROMPT's attention takes (``select_attention_impl``)."""
        t = q.shape[2]
        impl, reason = select_attention_impl(
            self.attention_impl, batch=q.shape[0], heads=self.num_heads, head_dim=self.head_dim,
            q_len=t, kv_len=t, use_cache=True, prompt=True, window=self.window or 0, mesh=current_mesh(),
            backend=jax.default_backend(), device_count=jax.device_count(), causal=True,
            bias_kv_only=None if bias is None else (bias.shape[1] == 1 and bias.shape[2] == 1),
        )
        if impl == "flash" and bias is not None and not (bias.shape[1] == 1 and bias.shape[2] == 1):
            impl, reason = "xla", "cached prompt with a bias wider than the keys' padding mask"
        if impl == "flash":  # XLA's path logs itself where it runs (``_cached_attend``)
            _log_impl_once(impl, "cached prompt, " + reason)
        return impl

    @nn.nowrap
    def _prompt_attend(self, q, k, v, bias):
        """A cached prompt's attention on the flash kernel: its ``T`` new
        tokens against each other, causal (a ``window`` layer's band), the
        padding mask cut to the prompt's own keys.  ``k``/``v`` (B, kv_heads,
        T, d) as projected, repeated to the query heads."""
        t, rep = q.shape[2], self.num_heads // self.kv_heads
        if rep > 1:
            k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        if bias is not None:
            bias = bias[..., :t]
        return flash_run(q, k, v, bias, causal=True, mesh=current_mesh(), dtype=self.dtype, prompt=True,
                         window=self.window)

    @nn.nowrap
    def _window_cached(self, q, k, v, bias, positions, cache_positions, mask, prompt: bool, deterministic: bool):
        """A cached call of a ``window`` layer: a decode step over the ring
        (one token a row), or a prompt (band attention over its own tokens,
        then the ring written from its last real positions)."""
        b, _, t, _ = q.shape
        if t > 1 and not prompt:
            raise NotImplementedError(
                "a cached sliding-window call of several tokens starts a sequence; continuing a ring at "
                "per-row positions (warm admission, speculative verify) is not implemented"
            )
        if not deterministic and self.probs_dropout_rate:
            raise NotImplementedError("probs dropout on a cached sliding-window layer")
        # which of the new tokens are real: the cache-width mask at their slots
        # (a prompt fills slots 0 .. T-1; a step's slot is ``cache_positions``,
        # parked past the mask for an idle serving slot)
        if mask is None:
            real = jnp.ones((b, t), bool)
        elif prompt:
            real = mask[:, :t] > 0
        elif cache_positions is not None:
            real = (cache_positions < mask.shape[1])[:, None]
        else:
            real = jnp.ones((b, 1), bool)
        ring_k, ring_v = self._cache_kv(k, v, cache_positions, ring=(positions, real))
        if not prompt:
            return self._cached_attend(
                q, ring_k, ring_v, None, None, None,
                jnp.broadcast_to(positions[:, 0], (b,)).astype(jnp.int32), deterministic, ring=True,
                live=real[:, 0],  # an idle slot's step wrote nothing, and its ring is not read
            )
        if self._prompt_impl(q, bias) == "flash":
            return self._prompt_attend(q, k, v, bias)
        rep = self.num_heads // self.kv_heads
        if rep > 1:
            k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        band = make_band_bias(t, t, self.window)
        return dot_product_attention(q, k, v, band if bias is None else bias[..., :t] + band, dtype=self.dtype)

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
    prompt: bool = False,
    window: int | None = None,
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
    into the seed so shards draw independent masks.

    ``prompt``: a cached prompt's attention, ``flash_prompt_attention`` (the
    forward kernel alone, causal, its ``window`` flavour where given)."""
    if prompt:
        if not causal or dropout_rate:
            raise ValueError("a cached prompt's attention is causal and takes no probs dropout")
        attend = lambda q, k, v, bias, **_: flash_prompt_attention(  # noqa: E731
            q, k, v, bias, window=window, scale=scale, dtype=dtype)
    else:
        attend = flash_attention
    if mesh is None or math.prod(mesh.devices.shape) == 1:
        return attend(
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
        return attend(
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
