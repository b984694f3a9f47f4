#!/usr/bin/env python3
"""Chip smoke: the trainer and the serving engine on one TPU v5e chip, at the
full published width of bart-large-cnn, through the entry points a user calls.

    python chip_smoke.py             one chip: device, kernels, train, serve
    python chip_smoke.py --chips 4   fsdp=4 training against a one-device leg,
                                     and nothing else
    python chip_smoke.py --rehearse  CPU rehearsal of the control flow at a
                                     *-test size; can never print a passing line

One process, which holds the chip: nothing here starts a child that needs it,
and ``JAX_PLATFORMS`` is never set.  Every phase prints one JSON line as it
finishes; the last line of stdout is the contract line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and is printed only when every phase passed on a TPU.  Any failed assertion
or exception exits non-zero with no such line.  The weights are random (from
``--seed``), the data is synthetic, and the timings printed are smoke numbers
for orientation, not benchmark results.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import shutil
import statistics
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".chip_smoke")  # bulky run-time output (.gitignore)
KEPT = os.path.join(HERE, "chiprun_out")  # small records the chip tool brings back

# ---- sizes: the one-chip width ISSUE 22 names, and the CPU rehearsal's ----
# The optimizer batch of 16 is taken as two in-step microbatches of 8: compiled
# for a described v5e, the one-microbatch step with dropout needs 16.09 GiB of
# the chip's 15.75 (PERF.md, Findings PR 22); with two it compiles and fits.
FULL = dict(
    model="bart-large-cnn", batch=16, src=1024, tgt=128, heads=16, head_dim=64,
    d_model=1024, ffn=4096, vocab=50265, train_rows=128, val_rows=8, prompts=8,
    new_tokens=128, decode_caches=(128, 1024), pad_to=128,
    train_extra=("--grad-accum-steps", "2"),
)
# the sparse decoder's operators at LFM2-8B-A1B's published widths (lfm2_phase)
FULL_LFM2 = dict(d=2048, taps=3, experts=32, top_k=4, expert_ff=1792, batch=4, seq=1024)
TINY_LFM2 = dict(d=64, taps=3, experts=8, top_k=4, expert_ff=32, batch=2, seq=32)
TINY = dict(
    model="bart-test", batch=4, src=64, tgt=32, heads=4, head_dim=16,
    d_model=1024, ffn=256, vocab=264, train_rows=16, val_rows=4, prompts=4,
    new_tokens=32, decode_caches=(32, 64), pad_to=32,
    train_extra=("--grad-accum-steps", "2"),
)

# ---- tolerances (bf16 kernels against an fp32 XLA reference) -------------
# error is max|kernel - ref| over max|ref|: bf16 carries 8 bits of mantissa
# (2^-8 = 0.4 %), so a few roundings stay under 2 %; a wrong mask, scale or
# block offset is an error of order 1.
TOL_FWD = 2e-2
TOL_GRAD = 4e-2
TOL_ADAMW = 1e-5  # fp32 both sides, equal up to float contraction
KEEP_BAND = 5e-3  # |keep rate - (1 - p)| for >= 1M draws (sigma ~ 3e-4)
TOL_FSDP_LOSS = 2e-2  # |loss_fsdp4 - loss_1dev| per step; bf16 matmuls reduce in another order

# attention_impl events that may say "xla" at these lengths, and why
EXPECTED_XLA = (
    "kv-cache decode step",  # cross-attention of a cached decode step: one q row, no kernel shape
    "auto: score matrix too small to tile",  # a bucket under the 128x128 floor (ops/mha.py)
    "auto: cache too short to tile",  # self-attention cache under the decode kernel's 128 floor
    "shape not tileable",  # 1-row decode / beam steps
    "decode shape not tileable",
)


class Failed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise Failed(what)


def emit(record: dict) -> None:
    sys.stdout.write(json.dumps(record) + "\n")
    sys.stdout.flush()


@contextlib.contextmanager
def phase(name: str, results: dict):
    """Run one phase: print its JSON line, or its failure and exit 1."""
    t0 = time.perf_counter()
    out: dict = {}
    try:
        yield out
    except BaseException as e:  # noqa: BLE001 — reported, then the process exits non-zero
        traceback.print_exc(file=sys.stderr)
        emit({"phase": name, "ok": False, "error": f"{type(e).__name__}: {e}"[:2000],
              "seconds": round(time.perf_counter() - t0, 1), **out})
        raise SystemExit(1) from None
    results[name] = out
    emit({"phase": name, "ok": True, "seconds": round(time.perf_counter() - t0, 1), **out})


class Tee:
    """stdout that also keeps every line — the program's JSON-lines stream is
    what the train and serve phases assert on."""

    def __init__(self, real):
        self.real, self.lines, self._buf = real, [], ""

    def write(self, s):
        self.real.write(s)
        self._buf += s
        *done, self._buf = self._buf.split("\n")
        self.lines.extend(done)
        return len(s)

    def flush(self):
        self.real.flush()

    def records(self) -> list[dict]:
        out = []
        for line in self.lines:
            if line.startswith("{"):
                try:
                    out.append(json.loads(line))
                except ValueError:
                    pass
        return out

    def __getattr__(self, name):
        return getattr(self.real, name)


@contextlib.contextmanager
def captured_stdout():
    """Tee stdout for the block; yields the Tee (``.records()`` when done)."""
    from distributed_llms_example_tpu.ops import mha

    # the attention_impl line is printed once per process per (impl, reason):
    # re-arm it so each captured stream carries its own
    mha._IMPL_LOGGED.clear()
    tee = Tee(sys.stdout)
    sys.stdout = tee
    try:
        yield tee
    finally:
        sys.stdout = tee.real


def run_main(argv: list[str]) -> list[dict]:
    """``launch.cli.main(argv)`` in this process; returns the JSON records it printed."""
    from distributed_llms_example_tpu.launch.cli import main

    with captured_stdout() as tee:
        rc = main(argv)
    check(rc == 0, f"launch.cli.main({argv[:1]}...) returned {rc}")
    return tee.records()


def events(records: list[dict], name: str) -> list[dict]:
    return [r for r in records if r.get("event") == name]


def check_attention(records: list[dict], want: str) -> list[list[str]]:
    """Every attention_impl event is the kernel, or xla for a listed reason
    (``want == "xla"`` is the CPU rehearsal, where auto never picks a kernel)."""
    allowed = EXPECTED_XLA + (("auto: backend=cpu",) if want == "xla" else ())
    seen = [[r["impl"], r["reason"]] for r in events(records, "attention_impl")]
    for impl, reason in seen:
        if impl == "xla":
            check(any(reason.startswith(x) for x in allowed),
                  f"attention fell back to xla for an unlisted reason: {reason!r} (seen: {seen})")
    check(any(impl == want for impl, _ in seen), f"no attention_impl event says {want!r} (seen: {seen})")
    return seen


def cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def rel_err(a, b) -> float:
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


# ------------------------------------------------------------------ device


def device_phase(out: dict, rehearse: bool, chips: int) -> dict:
    import jax
    import jaxlib

    from distributed_llms_example_tpu import native
    from distributed_llms_example_tpu.core.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()
    devs = jax.devices()
    out.update(
        platform=devs[0].platform, device_kind=devs[0].device_kind, count=len(devs),
        jax=jax.__version__, jaxlib=jaxlib.__version__, compile_cache_dir=cache_dir,
        compile_cache_entries_at_start=cache_entries(cache_dir),
    )
    try:
        from importlib.metadata import version

        out["libtpu"] = version("libtpu")
    except Exception as e:  # noqa: BLE001 — reported, not fatal: the device line below decides
        out["libtpu"] = f"unknown ({e})"
    if not rehearse:
        check(devs[0].platform == "tpu",
              f"JAX found no accelerator: platform={devs[0].platform!r} kind={devs[0].device_kind!r}")
    check(len(devs) >= chips, f"--chips {chips} but jax.devices() has {len(devs)}")
    if chips == 1 and not rehearse:
        check(len(devs) == 1, f"the one-chip smoke saw {len(devs)} devices; run --chips 4 for those")
    ok = native.available()
    out.update(native_jsonl_available=ok, native_jsonl_built_from_source_this_run=native.compiled_here())
    check(ok, f"native JSONL loader did not build: {native.build_error()}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


# ----------------------------------------------------------------- kernels


def kernels_phase(out: dict, sz: dict, seed: int, interpret: bool) -> None:
    """Each main-path kernel, compiled, against its XLA reference in this process."""
    import jax
    import jax.numpy as jnp

    from distributed_llms_example_tpu.ops.attention import dot_product_attention
    from distributed_llms_example_tpu.ops.flash_attention import (
        auto_block, decode_block, decode_step_heads, dequantize_kv, flash_attention, flash_decode, quantize_kv,
    )
    from distributed_llms_example_tpu.ops.fused_dropout import fused_dropout
    from distributed_llms_example_tpu.ops.fused_optim import SCALARS, adamw_leaf_reference, fused_adamw_leaf
    from distributed_llms_example_tpu.ops.mha import decode_step_bias

    B, H, D, SRC, TGT = sz["batch"], sz["heads"], sz["head_dim"], sz["src"], sz["tgt"]
    hw = not interpret  # the hardware RNG exists only compiled on the chip
    key = jax.random.PRNGKey(seed)

    def rnd(i, shape, dtype=jnp.bfloat16, scale=1.0):
        return (jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * scale).astype(dtype)

    def kv_mask(i, kv_len):
        # a padding-style (B,1,1,K) mask: each row keeps a prefix of random length >= K/2
        lens = jax.random.randint(jax.random.fold_in(key, i), (B,), kv_len // 2, kv_len + 1)
        return jnp.where(jnp.arange(kv_len)[None, :] < lens[:, None], 0.0, -1e9)[:, None, None, :].astype(jnp.float32)

    def ref_attention(q, k, v, bias, causal):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        if causal:
            tri = jnp.tril(jnp.ones((q.shape[2], k.shape[2]), bool))
            cb = jnp.where(tri, 0.0, -1e9)[None, None]
            bias = cb if bias is None else bias + cb
        with jax.default_matmul_precision("highest"):
            return dot_product_attention(q, k, v, bias, dtype=jnp.float32)

    flash = {}
    for name, q_len, kv_len, causal, masked in (
        ("encoder_noncausal_mask", SRC, SRC, False, True),
        ("decoder_causal", TGT, TGT, True, False),
        ("cross", TGT, SRC, False, True),
    ):
        q, k, v = rnd(1, (B, H, q_len, D)), rnd(2, (B, H, kv_len, D)), rnd(3, (B, H, kv_len, D))
        do = rnd(4, (B, H, q_len, D))
        bias = kv_mask(5, kv_len) if masked else None

        def kern(q, k, v):
            return flash_attention(q, k, v, bias, causal=causal, interpret=interpret)

        def ref(q, k, v):
            return ref_attention(q, k, v, bias, causal)

        o_k, vjp_k = jax.vjp(jax.jit(kern), q, k, v)
        o_r, vjp_r = jax.vjp(jax.jit(ref), q, k, v)
        errs = {"fwd": rel_err(o_k, o_r)}
        for n, gk, gr in zip(("dq", "dk", "dv"), vjp_k(do), vjp_r(do.astype(jnp.float32))):
            errs[n] = rel_err(gk, gr)
        check(all(math.isfinite(e) for e in errs.values()), f"flash {name}: non-finite error {errs}")
        check(errs["fwd"] <= TOL_FWD, f"flash {name} fwd off by {errs['fwd']:.4f} > {TOL_FWD}")
        check(max(errs["dq"], errs["dk"], errs["dv"]) <= TOL_GRAD, f"flash {name} grads off: {errs}")
        flash[name] = {k_: round(e, 5) for k_, e in errs.items()}
    out["flash_rel_err"] = flash

    # one grid step of flash_decode streams a kv tile of every head of a slot
    # (decode_step_heads, from the shapes); the q block is one row for a plain
    # step and up to 8 for a speculative verify
    decode, tiling = {}, {}
    for cache in sz["decode_caches"]:
        for q_len in (1, 8):
            q, k, v = rnd(11, (B, H, q_len, D)), rnd(12, (B, H, cache, D)), rnd(13, (B, H, cache, D))
            offsets = jax.random.randint(jax.random.fold_in(key, 14), (B,), 0, cache - q_len + 1).astype(jnp.int32)
            # the kernel takes K/V as the cache keeps them: (B, length, heads x d)
            leaf = lambda x: x.transpose(0, 2, 1, 3).reshape(B, cache, H * D)  # noqa: E731
            got = jax.jit(lambda q, k, v, o: flash_decode(q, k, v, offsets=o, interpret=interpret))(q, leaf(k), leaf(v), offsets)
            want = ref_attention(q, k, v, decode_step_bias(offsets, q_len, cache), False)
            name = f"{cache}" if q_len == 1 else f"{cache}x{q_len}rows"
            decode[name] = round(rel_err(got, want), 5)
            check(decode[name] <= TOL_FWD, f"flash_decode cache {cache}, {q_len} q rows, off by {decode[name]}")
        tiling[str(cache)] = decode_step_heads(H, auto_block(cache), D, 2)
        check(tiling[str(cache)] == H, f"flash_decode cache {cache}: a step holds {tiling[str(cache)]} of {H} heads")
    # the int8 cache of a 7B's shape (32 heads of 128, kv tile 512): the heads go
    # four a step, and every step takes all heads' scales and picks its group's
    h7, d7, cache, slots = 32, 128, 1024, 4
    q, k, v = rnd(15, (slots, h7, 1, d7)), rnd(16, (slots, h7, cache, d7), jnp.float32), rnd(17, (slots, h7, cache, d7), jnp.float32)
    (qk, ks), (qv, vs) = quantize_kv(k), quantize_kv(v)
    offsets = jnp.asarray([0, 300, 700, cache - 1], jnp.int32)
    leaf = lambda x: x.transpose(0, 2, 1, 3).reshape(slots, cache, h7 * d7)  # noqa: E731
    got = jax.jit(lambda q, k, v, o, ks, vs: flash_decode(q, k, v, offsets=o, k_scale=ks, v_scale=vs, interpret=interpret))(
        q, leaf(qk), leaf(qv), offsets, ks.transpose(0, 2, 1), vs.transpose(0, 2, 1))
    want = ref_attention(q, dequantize_kv(qk, ks), dequantize_kv(qv, vs), decode_step_bias(offsets, 1, cache), False)
    decode["int8_32x128"] = round(rel_err(got, want), 5)
    check(decode["int8_32x128"] <= TOL_FWD, f"flash_decode int8, 32 heads of 128, off by {decode['int8_32x128']}")
    tiling["int8_32x128"] = decode_step_heads(h7, decode_block(cache), d7, 1, int8_scales=True)
    check(1 < tiling["int8_32x128"] < h7, f"flash_decode int8, 32 heads of 128: a step holds {tiling['int8_32x128']} heads")
    out["flash_decode_rel_err"] = decode
    out["flash_decode_heads_per_step"] = tiling

    # fused dropout(+residual): statistics, determinism, fwd mask == bwd mask
    p = 0.1
    shape = (B, SRC, sz["d_model"])
    ones, res = jnp.ones(shape, jnp.bfloat16), rnd(21, shape)

    @jax.jit
    def drop(x, r, s):
        f = lambda x: fused_dropout(x, s, p, residual=r, interpret=interpret, hw_rng=hw)  # noqa: E731
        y, vjp = jax.vjp(f, x)
        return y, vjp(jnp.ones_like(y))[0]

    y1, dx1 = drop(ones, res, jnp.int32(seed))
    y2, _ = drop(ones, res, jnp.int32(seed))
    y3, _ = drop(ones, res, jnp.int32(seed + 1))
    fwd_keep = (y1.astype(jnp.float32) - res.astype(jnp.float32)) != 0  # x = 1: kept <=> y != residual
    bwd_keep = dx1 != 0
    keep = float(fwd_keep.mean())
    check(abs(keep - (1 - p)) <= KEEP_BAND, f"fused dropout keep rate {keep:.5f} outside {1 - p} +- {KEEP_BAND}")
    check(bool((y1 == y2).all()), "fused dropout: same seed gave another mask")
    check(float((y1 != y3).mean()) > 0.05, "fused dropout: another seed gave the same mask")
    check(bool((fwd_keep == bwd_keep).all()), "fused dropout: backward mask differs from forward mask")
    kept_val = float(jnp.where(fwd_keep, y1.astype(jnp.float32) - res.astype(jnp.float32), 0).max())
    # bf16 output: y = residual + 1/(1-p) is rounded at |y| < 8 (spacing 2^-5)
    check(abs(kept_val - 1 / (1 - p)) < 4e-2, f"fused dropout scale {kept_val} != {1 / (1 - p):.4f}")
    out["fused_dropout"] = {"hw_rng": hw, "keep_rate": round(keep, 5), "fwd_mask_eq_bwd_mask": True}

    # probs dropout inside flash: with q = k = 0 the probabilities are uniform
    # and with v = 1 each output equals (kept count) / (K (1 - p)), so the mean
    # output is the keep rate; the op is linear in v, so <do, f(v)> == <dv, v>
    # holds exactly when the backward redraws the forward's mask
    q0 = jnp.zeros((B, H, SRC, D), jnp.bfloat16)
    v1 = jnp.ones((B, H, SRC, D), jnp.bfloat16)

    def pdrop(q, k, v, s):
        return flash_attention(q, k, v, dropout_rate=p, dropout_seed=s, interpret=interpret, hw_rng=hw)

    pj = jax.jit(pdrop)
    o1 = pj(q0, q0, v1, jnp.int32(seed))
    keep_p = float(o1.astype(jnp.float32).mean()) * (1 - p)
    check(abs(keep_p - (1 - p)) <= KEEP_BAND, f"probs dropout keep rate {keep_p:.5f} outside band")
    q, k, v, do = rnd(31, (B, H, SRC, D)), rnd(32, (B, H, SRC, D)), rnd(33, (B, H, SRC, D)), rnd(34, (B, H, SRC, D))
    oa, vjp = jax.vjp(lambda v: pj(q, k, v, jnp.int32(seed)), v)
    ob = pj(q, k, v, jnp.int32(seed))
    oc = pj(q, k, v, jnp.int32(seed + 1))
    check(bool((oa == ob).all()), "probs dropout: same seed gave another output")
    check(float((oa != oc).mean()) > 0.05, "probs dropout: another seed gave the same output")
    (dv,) = vjp(do)
    lhs = float((do.astype(jnp.float32) * oa.astype(jnp.float32)).sum())
    rhs = float((dv.astype(jnp.float32) * v.astype(jnp.float32)).sum())
    scale = float(jnp.abs(do.astype(jnp.float32) * oa.astype(jnp.float32)).sum())
    check(abs(lhs - rhs) <= TOL_GRAD * scale, f"probs dropout: <do,f(v)>={lhs} != <dv,v>={rhs} (bwd mask != fwd mask)")
    out["probs_dropout"] = {"hw_rng": hw, "keep_rate": round(keep_p, 5), "linear_identity_gap": round(abs(lhs - rhs) / scale, 6)}

    # fused AdamW against the plain reference, on the real leaves
    adamw = {}
    hyper = dict(b1=0.9, b2=0.999, eps=1e-8, max_norm=1.0, wd=0.01)
    scal = jnp.zeros((SCALARS,), jnp.float32).at[:5].set(jnp.asarray([2.5, 0.0, 0.1, 0.001, -5e-5]))
    for leaf in ((sz["vocab"], sz["d_model"]), (sz["d_model"], sz["ffn"]), (sz["d_model"],)):
        args = [rnd(40 + i, leaf, jnp.float32, s) for i, s in enumerate((0.02, 0.01, 1e-4, 0.05))]
        args[2] = jnp.abs(args[2])  # nu >= 0
        want = jax.jit(lambda *a: adamw_leaf_reference(*a, **hyper))(*args, scal)
        got = jax.jit(lambda *a: fused_adamw_leaf(*a, interpret=interpret, **hyper))(*args, scal)
        err = max(rel_err(g, w) for g, w in zip(got, want))
        adamw["x".join(map(str, leaf))] = float(f"{err:.3g}")
        check(err <= TOL_ADAMW, f"fused_adamw_leaf {leaf} off by {err}")
    out["fused_adamw_rel_err"] = adamw
    out["tolerances"] = {"fwd": TOL_FWD, "grad": TOL_GRAD, "adamw": TOL_ADAMW, "keep_band": KEEP_BAND}


# ---------------------------------------------- LFM2's operators, at width


def lfm2_phase(out: dict, sz: dict, seed: int) -> None:
    """The gated short convolution (a prefill, then cached steps that carry its
    state) and the no-drop expert layer (sorted assignments, grouped products)
    in bfloat16 at LFM2-8B-A1B's widths, against the plain float32 reference
    (``benchmarks/reference/lfm2_moe.py``), in this process."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import precision, spec as spec_mod
    from distributed_llms_example_tpu.models.lfm2 import Lfm2Config, ShortConv
    from distributed_llms_example_tpu.ops.moe import MoEMLP

    ref = spec_mod.load_module("reference", "lfm2_moe")
    dot = precision.make_dot("fp32")
    d, taps, E, K, ff, B, T = (sz[k] for k in ("d", "taps", "experts", "top_k", "expert_ff", "batch", "seq"))
    key = jax.random.PRNGKey(seed)
    rnd = lambda i, shape, scale: jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * scale  # noqa: E731
    std = 0.9 / d ** 0.5  # projections write ~0.9 a channel from a unit-rms input, as the cell's weights do
    x = rnd(0, (B, T, d), 1.0)

    # -- conv operator: the whole sequence at once, then prefill T-8 and 8 cached steps
    cfg = Lfm2Config(hidden_size=d, conv_L_cache=taps, num_hidden_layers=1, layer_types=("conv",))
    conv = ShortConv(cfg, dtype=jnp.bfloat16)
    p = {"in_proj": {"kernel": rnd(1, (d, 3 * d), std)}, "conv_weight": rnd(2, (d, taps), 0.33),
         "out_proj": {"kernel": rnd(3, (d, d), std)}}
    names = {"L.conv.in_proj.weight": p["in_proj"]["kernel"], "L.conv.conv.weight": p["conv_weight"],
             "L.conv.out_proj.weight": p["out_proj"]["kernel"]}
    want = jax.jit(jax.vmap(lambda row: ref.conv_operator(dot, names, "L", row)))(x)
    got = jax.jit(lambda v: conv.apply({"params": p}, v))(x.astype(jnp.bfloat16))
    err = rel_err(got, want)
    check(err <= TOL_FWD, f"short conv, whole sequence: off by {err}")
    cache = jax.tree.map(jnp.zeros_like, conv.init(key, x.astype(jnp.bfloat16), use_cache=True)["cache"])
    step = jax.jit(lambda c, v: conv.apply({"params": p, "cache": c}, v, use_cache=True, mutable=["cache"]))
    head, mut = step(cache, x[:, : T - 8].astype(jnp.bfloat16))
    pieces = [head]
    for t in range(T - 8, T):
        y, mut = step(mut["cache"], x[:, t : t + 1].astype(jnp.bfloat16))
        pieces.append(y)
    err_cached = rel_err(jnp.concatenate(pieces, axis=1), want)
    check(err_cached <= TOL_FWD, f"short conv, prefill then cached steps: off by {err_cached}")
    out["short_conv_rel_err"] = {"whole": float(f"{err:.3g}"), "prefill_then_steps": float(f"{err_cached:.3g}")}

    # -- expert layer: tokens whose 4th and 5th scores nearly tie may route
    # otherwise in bfloat16 than in float32; they are counted, not compared
    moe = MoEMLP(num_experts=E, intermediate_size=ff, top_k=K, capacity_factor=-1.0, dtype=jnp.bfloat16,
                 scorer="sigmoid", use_expert_bias=True, aux_loss=False)
    mp = {"router": {"kernel": rnd(4, (d, E), std)}, "expert_bias": rnd(5, (E,), 0.05),
          "gate_proj": rnd(6, (E, d, ff), std), "up_proj": rnd(7, (E, d, ff), std), "down_proj": rnd(8, (E, ff, d), 0.9 / ff ** 0.5)}
    mnames = {"L.feed_forward.gate.weight": mp["router"]["kernel"], "L.feed_forward.expert_bias": mp["expert_bias"],
              "L.feed_forward.experts.w1.weight": mp["gate_proj"], "L.feed_forward.experts.w3.weight": mp["up_proj"],
              "L.feed_forward.experts.w2.weight": mp["down_proj"]}
    rcfg = {"num_experts_per_tok": K, "use_expert_bias": True, "norm_topk_prob": True, "routed_scaling_factor": 1.0}
    xb = x.astype(jnp.bfloat16)  # both sides start from the same rounded input
    want, margin = jax.jit(lambda v: ref.expert_layer(dot, mnames, "L", v, rcfg))(xb.astype(jnp.float32).reshape(B * T, d))
    got, stats = jax.jit(lambda v: moe.apply({"params": mp}, v, mutable=["moe_stats"]))(xb)
    load = jax.tree.leaves(stats["moe_stats"])[0]
    check(int(load.sum()) == B * T * K, f"expert layer: {int(load.sum())} assignments for {B * T * K} routed")
    clear = margin >= ref.NEAR_TIE
    token_err = jnp.max(jnp.abs(got.reshape(B * T, d).astype(jnp.float32) - want), axis=-1) / jnp.max(jnp.abs(want))
    err = float(jnp.max(jnp.where(clear, token_err, 0.0)))
    check(err <= TOL_FWD, f"no-drop expert layer: off by {err} on tokens with a clear routing margin")
    out["expert_layer"] = {"rel_err_clear_margin": float(f"{err:.3g}"), "near_tie_share": float(1.0 - jnp.mean(clear)),
                           "rel_err_near_tie_max": float(f"{float(jnp.max(jnp.where(clear, 0.0, token_err))):.3g}"),
                           "experts_hit": int((load > 0).sum()), "max_load_over_mean": float(load.max() / load.mean())}


# ------------------------------------------------------------------- train


def write_data(sz: dict, seed: int) -> tuple[str, str]:
    """Seeded synthetic dialogue/summary records; sources exceed the source
    width in bytes so the byte tokenizer's buckets reach it."""
    import random

    rng = random.Random(seed)
    words = ["".join(rng.choice("abcdefghijklmnopqrstuvwxyz") for _ in range(rng.randint(2, 9))) for _ in range(500)]

    def text(n_bytes):
        s = ""
        while len(s) < n_bytes:
            s += rng.choice(words) + " "
        return s

    os.makedirs(WORK, exist_ok=True)
    paths = []
    for name, n in (("train", sz["train_rows"]), ("val", sz["val_rows"])):
        path = os.path.join(WORK, f"{name}.jsonl")
        with open(path, "w") as f:
            for _ in range(n):
                f.write(json.dumps({"dialogue": text(sz["src"] + 64), "summary": text(sz["tgt"] + 16)}) + "\n")
        paths.append(path)
    return paths[0], paths[1]


def train_phase(out: dict, sz: dict, seed: int, on_tpu: bool, cache_dir: str) -> None:
    import jax

    train_file, val_file = write_data(sz, seed)
    run_dir = os.path.join(WORK, "train")
    shutil.rmtree(run_dir, ignore_errors=True)
    steps = sz["train_rows"] // sz["batch"]
    argv = [
        "--model-ckpt", sz["model"], "--tokenizer", "byte", "--output-dir", run_dir,
        "--train-file", train_file, "--val-file", val_file,
        "--batch-size", str(sz["batch"]), "--warmup-steps", "2", "--evaluation-steps", "0",
        "--max-source-length", str(sz["src"]), "--max-target-length", str(sz["tgt"]),
        "--pad-to-multiple", str(sz["pad_to"]), "--eval-max-new-tokens", str(sz["new_tokens"]),
        "--shuffle-seed", str(seed), "--log-every-steps", "1", "--obs", "jsonl",
        *sz["train_extra"],
    ]
    first = run_main([*argv, "--num-epochs", "1"])
    second = run_main([*argv, "--num-epochs", "2"])  # same output dir, steps left: resumes

    def losses(records):
        return [(r["step"], r["loss"]) for r in records if "loss" in r and "step" in r and "event" not in r]

    l1, l2 = losses(first), losses(second)
    check(len(l1) >= steps, f"first run logged {len(l1)} steps, expected {steps}")
    check(all(math.isfinite(v) for _, v in l1 + l2), f"non-finite loss: {l1 + l2}")
    check(events(first, "done") and events(first, "done")[0]["steps"] == steps, "first run did not finish its epoch")
    check(not events(first, "resumed"), "first run resumed from a checkpoint that should not exist")
    resumed = events(second, "resumed")
    check(bool(resumed) and resumed[0]["step"] == steps,
          f"second run did not resume at the saved step {steps}: {resumed}")
    check(l2 and l2[0][0] == steps + 1, f"resumed run's first step is {l2[:1]}, expected {steps + 1}")
    check(events(second, "done")[0]["steps"] == 2 * steps, "resumed run did not reach the end")
    for run, recs in (("first", first), ("resumed", second)):
        ev = events(recs, "eval")
        check(bool(ev) and any(k.startswith("rouge") for k in ev[-1]), f"{run} run: no eval event with ROUGE: {ev}")
        for bad in ("obs_gauges_skipped", "device_account_skipped", "ckpt_restore_failed",
                    "ckpt_verify_failed", "fused_optim_fallback", "obs_anomaly"):
            check(not events(recs, bad), f"{run} run fired {bad}: {events(recs, bad)[:1]}")
    check(os.path.isdir(os.path.join(run_dir, "checkpoints")), "no checkpoint directory was written")
    check(os.path.exists(os.path.join(run_dir, "model", "model.safetensors")), "no exported model")

    rng_cfg, optim_cfg = events(first, "rng_config"), events(first, "optim_config")
    out["rng_config"] = rng_cfg[0] if rng_cfg else None
    out["optim_impl"] = optim_cfg[0]["optim_impl"] if optim_cfg else None
    gauges = events(first, "obs_gauges")
    check(bool(gauges), "no obs_gauges event")
    want_flash = "flash" if on_tpu else "xla"
    out["attention_impl_events"] = check_attention(first, want_flash)
    if on_tpu:
        check(bool(rng_cfg) and rng_cfg[0]["dropout_impl"] == "fused" and rng_cfg[0]["prng_impl"] == "rbg",
              f"dropout did not resolve to the fused kernel with the rbg PRNG: {rng_cfg}")
        check(out["optim_impl"] == "fused", f"optimizer resolved to {out['optim_impl']!r}, not the fused kernel")
        check("mfu_skipped" not in gauges[0], f"no MFU on this device: {gauges[0].get('mfu_skipped')}")
        stats = jax.devices()[0].memory_stats()
        peak, limit = int(stats["peak_bytes_in_use"]), int(stats["bytes_limit"])
        check(0 < peak < limit, f"memory_stats peak {peak} not below the device's {limit}")
        out["peak_hbm_gib"], out["hbm_limit_gib"] = round(peak / 2**30, 2), round(limit / 2**30, 2)

    def step_seconds(records):
        return [1.0 / r["steps_per_sec"] for r in records if "steps_per_sec" in r and "event" not in r]

    s1, s2 = step_seconds(first), step_seconds(second)
    tokens = [r["tokens_per_sec"] for r in first if "tokens_per_sec" in r and "event" not in r][1:]
    windows = [w["mfu"] for w in events(first, "obs_window") if "mfu" in w][1:]
    out.update(
        steps_first_run=len(l1), resumed_at_step=resumed[0]["step"], steps_resumed_run=len(l2),
        first_loss=round(l1[0][1], 4), last_loss=round(l2[-1][1], 4),
        eval_first_run={k: v for k, v in events(first, "eval")[-1].items() if k != "event"},
        first_step_seconds_incl_compile=round(s1[0], 2),
        resumed_first_step_seconds_incl_compile=round(s2[0], 2) if s2 else None,
        smoke_median_step_seconds=round(statistics.median(s1[1:]), 4) if len(s1) > 1 else None,
        smoke_median_tokens_per_sec=round(statistics.median(tokens), 1) if tokens else None,
        smoke_median_window_mfu=round(statistics.median(windows), 4) if windows else None,
        compile_cache_entries_now=cache_entries(cache_dir),
    )


# ------------------------------------------------------------------- serve


def serve_phase(out: dict, sz: dict, on_tpu: bool) -> None:
    prompts = os.path.join(WORK, "val.jsonl")
    output_file = os.path.join(WORK, "serve_output.jsonl")
    if os.path.exists(output_file):
        os.unlink(output_file)
    records = run_main([
        "serve", "--model-ckpt", sz["model"], "--tokenizer", "byte", "--prompts-file", prompts,
        "--num-prompts", str(sz["prompts"]), "--max-slots", str(sz["prompts"]),
        "--max-new-tokens", str(sz["new_tokens"]), "--max-source-length", str(sz["src"]),
        "--output-file", output_file, "--log-every-steps", "32",
    ])
    summary = events(records, "serve_summary")
    check(bool(summary), "no serve_summary event")
    check(summary[0]["sequences"] == sz["prompts"], f"served {summary[0]['sequences']} of {sz['prompts']} requests")
    with open(output_file) as f:
        served = [json.loads(line) for line in f]
    check(len(served) == sz["prompts"], f"{len(served)} output records for {sz['prompts']} prompts")
    check(all(r["tokens"] > 0 for r in served), f"a request returned no tokens: {[r['tokens'] for r in served]}")
    check(bool(events(records, "serve_output")), "_write_serve_output did not report the output file")
    out["attention_impl_events"] = check_attention(records, "flash_decode" if on_tpu else "xla")
    out.update(
        requests=len(served), tokens=[r["tokens"] for r in served],
        smoke_decode_tokens_per_sec=summary[0]["decode_tokens_per_sec"],
        smoke_ttft_p50_ms=summary[0]["ttft_p50_ms"], decode_steps=summary[0]["decode_steps"],
    )


# --------------------------------------------------------- four chips: fsdp


def fsdp_phase(out: dict, sz: dict, seed: int, chips: int) -> None:
    """The same seeded steps on a one-device mesh and under fsdp=N, through
    the same ``make_train_step`` (dropout off: shards draw independent masks
    by design, so only the dropout-free step is comparable)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import mesh_utils

    from distributed_llms_example_tpu.core.config import MeshConfig
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.data.batching import LABEL_PAD
    from distributed_llms_example_tpu.models.registry import load_model
    from distributed_llms_example_tpu.parallel.sharding import shard_params
    from distributed_llms_example_tpu.train.optim import make_optimizer_bundle
    from distributed_llms_example_tpu.train.step import create_train_state, make_train_step, put_batch, state_shardings

    n_steps, B = 4, sz["batch"]
    lm = load_model(sz["model"], dtype=jnp.bfloat16, load_weights=False)
    lm = dataclasses.replace(lm, config=dataclasses.replace(lm.config, dropout_rate=0.0, attn_dropout_rate=0.0))
    lm = dataclasses.replace(lm, module=type(lm.module)(lm.config, dtype=jnp.bfloat16))
    host_params = jax.device_get(lm.init_params(seed))
    rng = np.random.RandomState(seed)
    vocab = lm.config.vocab_size
    batches = []
    for _ in range(n_steps):
        labels = rng.randint(3, vocab, (B, sz["tgt"])).astype(np.int32)
        labels[:, -8:] = LABEL_PAD
        batches.append({
            "input_ids": rng.randint(3, vocab, (B, sz["src"])).astype(np.int32),
            "attention_mask": np.ones((B, sz["src"]), np.int32), "labels": labels,
        })

    def leg(devices, fsdp):
        mesh = build_mesh(MeshConfig(data=1, fsdp=fsdp), devices=devices)
        if devices[0].platform == "tpu":
            want = mesh_utils.create_device_mesh(mesh.devices.shape, devices=devices)
            check([d.id for d in mesh.devices.flat] == [d.id for d in want.flat],
                  "mesh device order is not create_device_mesh's")
        tx, schedule, spec = make_optimizer_bundle(total_steps=100, warmup_steps=1)
        state = create_train_state(shard_params(host_params, mesh), tx)
        state = jax.tree.map(jax.device_put, state, state_shardings(state, mesh))
        build = make_train_step(lm.module, lm.config, tx, schedule, mesh, grad_accum_steps=2,
                                optim_spec=spec, optim_impl="auto")
        step_fn, _ = build(state)
        losses, times = [], []
        with captured_stdout() as tee:
            for b in batches:
                t0 = time.perf_counter()
                state, metrics = step_fn(state, put_batch(b, mesh))
                losses.append(float(jax.device_get(metrics["loss"])))
                times.append(time.perf_counter() - t0)
        impls = events(tee.records(), "attention_impl")
        leaves = jax.tree.leaves((state.params, state.opt_state))
        per_dev = {d.id: 0 for d in devices}
        for x in leaves:
            for s in getattr(x, "addressable_shards", ()):
                per_dev[s.device.id] += s.data.nbytes
        logical = sum(x.nbytes for x in leaves)
        del state
        return losses, times, [[r["impl"], r["reason"]] for r in impls], per_dev, logical

    devs = jax.devices()[:chips]
    one = leg(devs[:1], 1)
    many = leg(devs, chips)
    dev = [abs(a - b) for a, b in zip(one[0], many[0])]
    check(all(math.isfinite(v) for v in one[0] + many[0]), f"non-finite loss: {one[0]} / {many[0]}")
    check(max(dev) <= TOL_FSDP_LOSS, f"fsdp={chips} losses {many[0]} differ from one-device {one[0]} by {max(dev)} > {TOL_FSDP_LOSS}")
    # each device's share of the logical parameter+optimizer bytes: 1/chips
    # for what fsdp shards, plus the leaves it must replicate (bart's 50265-row
    # embedding does not divide by 4, so it and its moments stay whole: ~13 %
    # of the state) — "everything on device 0" would read 1.0 / 0 / 0 / 0
    total = many[4]
    shares = {str(k): round(v / total, 4) for k, v in many[3].items()}
    check(all(1 / chips - 0.01 <= s <= 1 / chips + 0.15 for s in shares.values()),
          f"parameter+optimizer bytes are not spread over {chips} devices: {shares}")
    check(max(shares.values()) - min(shares.values()) <= 0.01, f"uneven spread: {shares}")
    if devs[0].platform == "tpu":
        mem = [int(d.memory_stats()["peak_bytes_in_use"]) for d in devs]
        check(all(m > 0 for m in mem), f"a device reports no memory in use: {mem}")
        out["peak_hbm_gib_per_device"] = [round(m / 2**30, 2) for m in mem]
        check(any(i == "flash" and "shard_map" in r for i, r in many[2]),
              f"the fsdp leg's attention did not run the kernel per shard: {many[2]}")
        check(any(i == "flash" for i, _ in one[2]), f"the one-device leg did not run flash: {one[2]}")
    out.update(
        losses_one_device=[round(v, 5) for v in one[0]], losses_fsdp=[round(v, 5) for v in many[0]],
        max_abs_loss_deviation=round(max(dev), 6), tolerance=TOL_FSDP_LOSS,
        state_bytes_share_per_device=shares, attention_impl_fsdp=many[2], attention_impl_one_device=one[2],
        smoke_step_seconds_one_device=[round(t, 3) for t in one[1]],
        smoke_step_seconds_fsdp=[round(t, 3) for t in many[1]],
        state_gib_total=round(total / 2**30, 2),
    )


# -------------------------------------------------------------------- main


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = only the sharded-training phase and the one-device leg it is compared with")
    ap.add_argument("--seed", type=int, default=0, help="seed of the random weights and the synthetic data")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at a *-test size: lifts the must-be-a-TPU check, never prints a passing line")
    args = ap.parse_args(argv)
    sz = TINY if args.rehearse else FULL
    results: dict = {}
    try:
        with phase("device", results) as out:
            device = device_phase(out, args.rehearse, args.chips)
        on_tpu = device["platform"] == "tpu"
        if args.chips == 4:
            with phase("fsdp", results) as out:
                fsdp_phase(out, sz, args.seed, args.chips)
        else:
            with phase("kernels", results) as out:
                kernels_phase(out, sz, args.seed, interpret=not on_tpu)
            with phase("lfm2", results) as out:
                lfm2_phase(out, TINY_LFM2 if args.rehearse else FULL_LFM2, args.seed)
            with phase("train", results) as out:
                train_phase(out, sz, args.seed, on_tpu, results["device"]["compile_cache_dir"])
            with phase("serve", results) as out:
                serve_phase(out, sz, on_tpu)
    finally:
        # keep the small records, drop the gigabytes (checkpoints, the export)
        os.makedirs(KEPT, exist_ok=True)
        with open(os.path.join(KEPT, f"chip_smoke_{'4chips' if args.chips == 4 else '1chip'}.json"), "w") as f:
            json.dump(results, f, indent=1)
        shutil.rmtree(WORK, ignore_errors=True)
    if args.rehearse or not on_tpu:
        emit({"ok": False, "rehearsal": True, "device": device,
              "note": "control flow only: kernels ran interpreted on the CPU at a test size"})
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
