"""The main path's Pallas kernels, compiled for a described TPU v5e.

The chip's compiler is installed in the CPU sandbox and compiles for a
``v5e:2x2`` topology that is described, not attached — so every kernel
``chip_smoke.py`` runs on the chip is first compiled here, at
bart-large-cnn widths (B16 H16 D64, src 1024 / tgt 128), with
``interpret=False``.  Nothing executes: these tests say what Mosaic accepts,
not what the kernels compute (interpret-mode tests and the smoke's
kernel-vs-reference phase do that).  The hardware-RNG cases are the ones
interpret mode can never reach — it takes the counter-hash branch.

Only one process may hold libtpu, so the topology is described inside a
module-scoped fixture (never at import or collection time), every compile
runs in the test's own process, and all of it lives in this one file.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

B, H, D, SRC, TGT = 16, 16, 64, 1024, 128
D_MODEL, VOCAB = 1024, 50265
BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe means no compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep it out of the cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def fsdp4_mesh(topo):
    from distributed_llms_example_tpu.core.config import MeshConfig
    from distributed_llms_example_tpu.core.mesh import build_mesh

    return build_mesh(MeshConfig(data=1, fsdp=4), devices=topo.devices)


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Steer the kernels' interpret default from the test: the process is on
    the CPU backend, the compile target is the described chip.  The matmul
    precision is put back to jax's default, which is what the program runs
    with: other test files raise it to "highest" as they are imported, and
    Mosaic refuses an fp32-precision matmul on bf16 operands."""
    from distributed_llms_example_tpu.ops import flash_attention, fused_dropout, fused_optim, retention, ssm

    for mod in (flash_attention, fused_dropout, fused_optim, retention, ssm):
        monkeypatch.setattr(mod, "_default_interpret", lambda: False)
    with jax.default_matmul_precision(None):
        yield


def _compile(fn, sharding, *shapes):
    """Lower ``fn`` for the described device(s) and return the compiled text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled program"
    return text


_COPY = re.compile(r"= (?:\([^=]*\)|\S+) copy(?:-start)?\(")
_SHAPE = re.compile(r"\w+\[([\d,]*)\]")


def _large_copies(text: str, elements: int) -> list[str]:
    """The ``copy`` / ``copy-start`` lines of a compiled program whose result
    holds at least ``elements`` elements.  A ``copy-start`` whose two buffers
    have one shape and one tiling and differ in the memory they lie in
    (``S(1)``) is the compiler prefetching an operand whole (bart's bfloat16
    embedding table, every round): a move, not a relayout, and not counted."""
    found = []
    for line in text.splitlines():
        if _COPY.search(line):
            result = line.split(" copy", 1)[0]
            buffers = re.findall(r"\w+\[[\d,]*\]\{[^}]*\}", result)
            if len(buffers) >= 2 and len({re.sub(r"S\(\d+\)", "", b) for b in buffers[:2]}) == 1:
                continue
            sizes = [math.prod(int(n) for n in dims.split(",") if n) for dims in _SHAPE.findall(result)]
            if sizes and max(sizes) >= elements:
                found.append(line.strip()[:200])
    return found


def _entry_layouts(text: str) -> list[str]:
    """``dtype[shape]{layout...}`` of every parameter of the entry computation."""
    line = next(ln for ln in text.splitlines() if "entry_computation_layout" in ln)
    params = line.split("entry_computation_layout={(", 1)[1].split(")->", 1)[0]
    return re.findall(r"\w+\[[\d,]*\]\{[^}]*\}", params)


def _qkv(q_len, kv_len, heads=H, d=D):
    return [((B, heads, q_len, d), BF16), ((B, heads, kv_len, d), BF16), ((B, heads, kv_len, d), BF16)]


FLASH_CASES = {
    # name: (q_len, kv_len, causal, kv-mask bias, relative bias, probs dropout)
    "encoder-noncausal-mask": (SRC, SRC, False, True, False, 0.0),
    "decoder-causal": (TGT, TGT, True, False, False, 0.0),
    "cross": (TGT, SRC, False, True, False, 0.0),
    "t5-learned-bias": (SRC, SRC, False, True, True, 0.0),
    "encoder-probs-dropout-hw-rng": (SRC, SRC, False, True, False, 0.1),
    "decoder-causal-probs-dropout-hw-rng": (TGT, TGT, True, False, False, 0.1),
}


@pytest.mark.parametrize("case", sorted(FLASH_CASES))
def test_flash_fwd_bwd_compiles(case, one_chip, compiled_kernels):
    from distributed_llms_example_tpu.ops.flash_attention import flash_attention

    q_len, kv_len, causal, masked, learned, rate = FLASH_CASES[case]
    shapes = _qkv(q_len, kv_len)
    if masked:
        shapes.append(((B, 1, 1, kv_len), F32))
    if learned:
        shapes.append(((H, q_len + kv_len - 1), F32))
    if rate:
        shapes.append(((), jnp.int32))

    def loss(q, k, v, *rest):
        rest = list(rest)
        seed = rest.pop() if rate else None
        lbias = rest.pop() if learned else None
        bias = rest.pop() if masked else None
        out = flash_attention(
            q, k, v, bias, relative_bias=lbias, causal=causal,
            dropout_rate=rate, dropout_seed=seed,
        )
        return out.astype(F32).sum()

    argnums = (0, 1, 2) + ((3 + masked,) if learned else ())
    text = _compile(jax.grad(loss, argnums=argnums), one_chip, *shapes)
    # forward + dq + dkv kernels, plus the dbias kernel of the learned flavor
    assert text.count("tpu_custom_call") >= (4 if learned else 3)
    if learned:
        # the dbias kernel hands back diagonal sums (heads, q tiles, kv tiles, 8 sublanes, 2 x block_k lanes),
        # and no kernel writes a (1, H, Q, K) gradient: the one matrix of that shape is the bias XLA lays out
        calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln and " custom-call(" in ln]
        assert any(re.search(r"= f32\[16,2,1,8,2048\]", ln) for ln in calls), [ln[:120] for ln in calls]
        assert not any(re.search(r"= \w+\[1,16,1024,1024\]", ln) for ln in calls)


def test_t5_cell_microbatch_gradient_sums_its_bias_gradient_along_diagonals(topo, one_chip, compiled_kernels, monkeypatch):
    """``t5-large.train``'s microbatch (one row, source 1,024, target 128, dropout
    on, bfloat16 compute) lowered for the described chip, forward and backward:
    every one of its 48 self-attention sites hands its relative bias to the flash
    kernel as a per-diagonal vector (the trace-time tally the run's log carries),
    no kernel writes a (1, 16, 1024, 1024) bias gradient, and the two
    (32 buckets, 16 heads) tables get theirs from 2,047 and 255 per-diagonal
    values, not from 1,048,576 x 16 scattered ones (145.8 ms of a 904 ms step
    before PR 40).  Lowered, not compiled: 60 s against several minutes."""
    from benchmarks.harness import program, spec as spec_mod
    from distributed_llms_example_tpu.core.config import MeshConfig
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.models import registry, t5
    from distributed_llms_example_tpu.parallel.activation import activation_mesh

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the kernels' auto rules ask it
    tallies = []
    flush = t5.flush_relative_bias_sites
    monkeypatch.setattr(t5, "flush_relative_bias_sites", lambda: tallies.append(flush()))
    cfg = spec_mod.load_json(os.path.join(spec_mod.BENCH_DIR, "configs", "t5-large.json"))
    monkeypatch.setattr(registry, "T5_CONFIGS", dict(registry.T5_CONFIGS))  # the cell's entry leaves with the test
    lm = registry.load_model(
        program.register_bench_model(cfg, spec_mod.load_module("adapters", cfg["family"])), dtype=BF16, load_weights=False)
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
                          jax.eval_shape(lambda: lm.init_params(0)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)  # noqa: E731

    def loss(p, key, src, mask, dec):
        out = lm.module.apply({"params": p}, src, mask, dec, deterministic=False, rngs={"dropout": key})
        return out.astype(F32).sum()

    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    with activation_mesh(build_mesh(MeshConfig(data=-1), devices=topo.devices[:1])):  # as the trainer traces its step
        text = jax.jit(jax.grad(loss)).lower(params, key, i32(1, SRC), i32(1, SRC), i32(1, TGT)).as_text()
    assert tallies[-1] == {"diagonal": 48, "matrix": 0}, tallies  # (the tallies before it: init's toy shapes)
    kernels = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    results = [ln.rsplit(" -> ", 1)[1] for ln in kernels]
    assert sum("tensor<16x2x1x8x2048xf32>" in r for r in results) == 24  # the encoder's sites: 2 q tiles of 512 x 1,024
    assert sum("tensor<16x1x1x8x256xf32>" in r for r in results) == 24  # the decoder's: one tile of 128 x 128
    assert not any("1x16x1024x1024x" in r or "1x16x128x128xbf16" in r for r in results), results
    table_scatters = re.findall(
        r"\}\) : \(tensor<32x16xf32>, tensor<([\dx]+)xi32>, tensor<([\dx]+)xf32>\) -> tensor<32x16xf32>", text)
    assert sorted(set(table_scatters)) == [("2047x1", "2047x16"), ("255x1", "255x16")], table_scatters


def test_flash_llama7b_shape_compiles(one_chip, compiled_kernels):
    from distributed_llms_example_tpu.ops.flash_attention import flash_attention

    shapes = [((2, 32, 1024, 128), BF16)] * 3

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(F32).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, *shapes)


DECODE_CASES = {
    # name: (slots, q rows, cache length, int8 K/V with (B, L, H) scales, heads, head_dim)
    "train-batch-cache128": (B, 1, TGT, False, H, D),
    "train-batch-cache1024": (B, 1, SRC, False, H, D),
    # bart-large-cnn.serve-steady's decode step, exactly: 64 slots, one row, cache 128
    "serve-cell": (64, 1, TGT, False, H, D),
    "serve-cell-verify-8-rows": (64, 8, TGT, False, H, D),
    "serve-cell-cache1024": (64, 1, SRC, False, H, D),
    "serve-cell-int8": (64, 1, TGT, True, H, D),
    # the registered 7B / 13B shapes (32 / 40 heads of 128): the heads go in
    # groups of whole 128-lane tiles, under the int8 cache too, where every
    # step takes all heads' scales and picks its group's (4 and 5 heads a step)
    "llama-7b-cache4096": (8, 1, 4096, False, 32, 128),
    "llama-7b-cache4096-int8": (8, 1, 4096, True, 32, 128),
    "llama-7b-cache128-int8": (8, 1, 128, True, 32, 128),
    "llama-13b-cache4096-int8-verify-8-rows": (8, 8, 4096, True, 40, 128),
    # heads of 64 under int8, split in pairs of pairs (12 heads, kv tile 512)
    "heads-of-64-cache2048-int8": (8, 1, 2048, True, 24, 64),
}


@pytest.mark.parametrize("case", sorted(DECODE_CASES))
def test_flash_decode_compiles(case, one_chip, compiled_kernels):
    from distributed_llms_example_tpu.ops.flash_attention import flash_decode

    slots, q_len, cache_len, int8, heads, d = DECODE_CASES[case]
    kv = ((slots, cache_len, heads * d), jnp.int8 if int8 else BF16)  # the cache leaf: (slots, length, heads x d)
    shapes = [((slots, heads, q_len, d), BF16), kv, kv, ((slots,), jnp.int32)]
    if int8:
        shapes += [((slots, cache_len, heads), F32)] * 2

    def step(q, k, v, offsets, k_scale=None, v_scale=None):
        return flash_decode(q, k, v, offsets=offsets, k_scale=k_scale, v_scale=v_scale)

    text = _compile(step, one_chip, *shapes)
    # the kernel takes the leaf as it rests: nothing the size of a leaf is relaid around it
    assert not _large_copies(text, slots * cache_len * heads * d)


@pytest.mark.parametrize("with_residual", [False, True], ids=["plain", "residual"])
def test_fused_dropout_hw_rng_compiles(with_residual, one_chip, compiled_kernels):
    """The case the chip's compiler refused before the seed fold: the TPU
    PRNG takes at most two seed words (``hw_seed_words``)."""
    from distributed_llms_example_tpu.ops.fused_dropout import fused_dropout

    def loss(x, res, seed):
        y = fused_dropout(x, seed, 0.1, residual=res if with_residual else None)
        return y.astype(F32).sum()

    act = ((B, SRC, D_MODEL), BF16)
    text = _compile(jax.value_and_grad(loss, argnums=(0, 1)), one_chip, act, act, ((), jnp.int32))
    assert text.count("tpu_custom_call") >= 2  # the forward and the mask-recomputing backward


@pytest.mark.parametrize("shape", [(VOCAB, D_MODEL), (D_MODEL, 4 * D_MODEL), (D_MODEL,)], ids=str)
def test_fused_adamw_leaf_compiles(shape, one_chip, compiled_kernels):
    from distributed_llms_example_tpu.ops.fused_optim import (
        SCALARS,
        fused_adamw_leaf,
        fused_adamw_supported,
    )

    assert fused_adamw_supported(math.prod(shape))

    def apply(p, mu, nu, g, scal):
        return fused_adamw_leaf(
            p, mu, nu, g, scal, b1=0.9, b2=0.999, eps=1e-8, max_norm=1.0, wd=0.01
        )

    _compile(apply, one_chip, *[(shape, F32)] * 4, ((SCALARS,), F32))


def test_flash_run_on_fsdp4_mesh_compiles(fsdp4_mesh, compiled_kernels):
    """The sharded trainer's attention: one kernel per shard under
    ``shard_map`` (batch over fsdp), and no gather around it."""
    from distributed_llms_example_tpu.ops.mha import flash_run

    assert fsdp4_mesh.devices.size == 4 and fsdp4_mesh.shape["fsdp"] == 4
    batch = NamedSharding(fsdp4_mesh, P(("data", "fsdp", "expert")))

    def loss(q, k, v, bias):
        out = flash_run(q, k, v, bias, causal=False, mesh=fsdp4_mesh, dtype=BF16)
        return out.astype(F32).sum()

    text = _compile(
        jax.grad(loss, argnums=(0, 1, 2)), batch, *_qkv(SRC, SRC), ((B, 1, 1, SRC), F32)
    )
    assert text.count("tpu_custom_call") >= 3
    assert "all-gather" not in text


# ---- lfm2-8b-a1b.serve-steady: the cell's three serving programs, whole ----

LFM2_SLOTS, LFM2_WAVE, LFM2_PROMPT, LFM2_NEW = 128, 4, 1024, 256


def test_flash_decode_grouped_query_cell_shape_compiles(one_chip, compiled_kernels):
    """The new cell's decode attention: 8 KV heads, each streamed once for its 4
    query heads (folded into the q rows), a 1280-row cache (kv tile 256: the
    mask's lane block must be a multiple of 128) and the padding mask."""
    from distributed_llms_example_tpu.ops.flash_attention import decode_block, flash_decode

    cache = LFM2_PROMPT + LFM2_NEW
    assert decode_block(cache) == 256 and decode_block(128) == 128 and decode_block(1024) == 512
    kv = ((LFM2_SLOTS, cache, 8 * D), BF16)
    text = _compile(
        lambda q, k, v, bias, offsets: flash_decode(q, k, v, bias, offsets=offsets, q_group=4),
        one_chip, ((LFM2_SLOTS, 8, 4, D), BF16), kv, kv, ((LFM2_SLOTS, 1, 1, cache), F32), ((LFM2_SLOTS,), jnp.int32),
    )
    assert not _large_copies(text, LFM2_SLOTS * cache * 8 * D)


@pytest.fixture
def lfm2_cell_engine(topo, compiled_kernels, monkeypatch):
    """A ``ServingEngine`` at the cell's sizes on a described chip: the model the
    benchmark registers from its configuration file, bfloat16 weights."""
    from benchmarks.harness import program, spec as spec_mod
    from distributed_llms_example_tpu.core.config import MeshConfig
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.models import registry
    from distributed_llms_example_tpu.serving.engine import ServeConfig, ServingEngine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the kernels' auto rules and donation ask it
    cfg = spec_mod.load_json(os.path.join(spec_mod.BENCH_DIR, "configs", "lfm2-8b-a1b.json"))
    lm = registry.load_model(
        program.register_bench_model(cfg, spec_mod.load_module("adapters", cfg["family"])), dtype=BF16)
    serve = ServeConfig(max_slots=LFM2_SLOTS, prefill_batch=LFM2_WAVE, max_new_tokens=LFM2_NEW,
                        max_source_length=LFM2_PROMPT)
    mesh = build_mesh(MeshConfig(data=-1), devices=topo.devices[:1])
    return lm, ServingEngine(lm.module, lm.config, mesh, serve, is_seq2seq=False)


def test_lfm2_cell_serving_programs_compile_and_fit(lfm2_cell_engine, one_chip):
    """Decode step, prefill wave and admit at 128 slots, 1 x and 4 x 1024, cache 1280,
    32 query / 8 KV heads of 64, every expert: what the chip's compiler says of
    their memory, that the decode step holds no K/V repeated to the query
    heads (3.0 GB of temporaries before the heads were grouped, 1.35 GB after),
    and that it relays no K/V leaf (PR 32)."""
    lm, eng = lfm2_cell_engine

    def abstract(tree, dtype=None):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, dtype if dtype is not None and jnp.issubdtype(x.dtype, jnp.floating) else x.dtype,
            sharding=one_chip), tree)

    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)  # noqa: E731
    params = abstract(jax.eval_shape(lambda: lm.init_params(0)), BF16)
    zeros = lambda n: (jnp.zeros((n, LFM2_PROMPT), jnp.int32),) * 2  # noqa: E731
    slots_cache, slots_mask, _, _ = jax.eval_shape(lambda p: eng._prefill_core(p, *zeros(LFM2_SLOTS)), params)
    shapes = {jax.tree_util.keystr(p): x.shape for p, x in jax.tree_util.tree_leaves_with_path(slots_cache)}
    assert sorted(set(shapes.values())) == [(), (128, 1280, 8 * 64), (128, 2048, 2)], shapes  # K/V: (slots, length, kv_heads x d)
    state = {"cache": abstract(slots_cache), "mask": abstract(slots_mask), "last": i32(LFM2_SLOTS)}
    active = jax.ShapeDtypeStruct((LFM2_SLOTS,), jnp.bool_, sharding=one_chip)

    step = eng._step.lower(params, state, i32(LFM2_SLOTS), i32(LFM2_SLOTS), active).compile()
    text, mem = step.as_text(), step.memory_analysis()
    assert "tpu_custom_call" in text and "%gmm" in text and "ragged-dot" not in text  # the Pallas grouped product
    assert "bf16[128,32,1280,64]" not in text and "bf16[128,1280,2048]" not in text  # K/V are read at their 8 heads
    assert mem.argument_size_in_bytes < 3.8e9 and mem.temp_size_in_bytes < 1.6e9, mem
    _assert_cache_rests_where_it_is_read(text, mem, (128, 1280, 512), leaves=2)
    assert len(_decode_attn_calls(text, (128, 8, 4, 64))) == 1  # the one attention layer, 4 query heads a KV head

    assert eng.wave_sizes == (1, LFM2_WAVE)  # a wave of one request runs a one-row program (PR 30)
    for rows in eng.wave_sizes:
        wave_cache, wave_mask, _, wave_first = jax.eval_shape(lambda p: eng._prefill_core(p, *zeros(rows)), params)
        wave = eng._prefill.lower(params, i32(rows, LFM2_PROMPT), i32(rows, LFM2_PROMPT)).compile()
        assert "%gmm" in wave.as_text() and wave.memory_analysis().temp_size_in_bytes < 1.5e9
        assert "%prompt_attn" in wave.as_text()  # the cached prompt's attention on the flash kernel (PR 37)
        admit = eng._admit.lower(state, abstract(wave_cache), abstract(wave_mask), abstract(wave_first), i32(rows)).compile()
        assert admit.memory_analysis().temp_size_in_bytes < 0.1e9


def _assert_cache_rests_where_it_is_read(text, mem, leaf, leaves):
    """The static evidence of PR 32 on a compiled decode step: no ``copy`` of a
    K/V leaf's size or more is left (before, three a leaf a round: rest ->
    the scatter's layout -> the kernel's, and back), every K/V leaf enters the
    program in the descending layout the row write and ``flash_decode`` take,
    and the cache is still updated in place."""
    elements = math.prod(leaf)
    assert not _large_copies(text, elements), _large_copies(text, elements)
    dims = ",".join(str(n) for n in leaf)
    entry = [p for p in _entry_layouts(text) if p.startswith(f"bf16[{dims}]")]
    assert len(entry) == leaves, entry
    assert all(p.startswith(f"bf16[{dims}]{{2,1,0:") for p in entry), entry
    assert mem.alias_size_in_bytes >= leaves * elements * 2, mem  # bf16 leaves, donated


def _decode_attn_calls(text, result):
    """The decode kernel's custom calls as the chip's trace will name them: call
    site ``self_attn`` alone and the q block as result, which is how
    ``benchmarks/layer_metrics/serve_decode_attn_ms.py`` finds them."""
    dims = ",".join(str(n) for n in result)
    return re.findall(rf"%self_attn\.\d+ = bf16\[{dims}\]\{{[^}}]*\}} custom-call\(", text)


BART_SLOTS, BART_WAVE, BART_PROMPT, BART_NEW = 64, 8, 1024, 128


def test_bart_cell_decode_step_copies_no_cache_leaf(topo, one_chip, compiled_kernels, monkeypatch):
    """``bart-large-cnn.serve-steady``'s decode step, whole, for the described chip:
    64 slots, prompt 1024, 128 new tokens, abstract float32 weights, bfloat16
    compute.  24 K/V leaves of (64, 128, 16 x 64)."""
    from benchmarks.harness import program, spec as spec_mod
    from distributed_llms_example_tpu.core.config import MeshConfig
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.evaluation.generation import _init_cache
    from distributed_llms_example_tpu.models import registry
    from distributed_llms_example_tpu.serving.engine import ServeConfig, ServingEngine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the kernels' auto rules and donation ask it
    cfg = spec_mod.load_json(os.path.join(spec_mod.BENCH_DIR, "configs", "bart-large-cnn.json"))
    lm = registry.load_model(
        program.register_bench_model(cfg, spec_mod.load_module("adapters", cfg["family"])), dtype=BF16)
    serve = ServeConfig(max_slots=BART_SLOTS, prefill_batch=BART_WAVE, max_new_tokens=BART_NEW,
                        max_source_length=BART_PROMPT)
    eng = ServingEngine(lm.module, lm.config, build_mesh(MeshConfig(data=-1), devices=topo.devices[:1]),
                        serve, is_seq2seq=True)
    abstract = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    slots = lambda tree: jax.tree.map(  # noqa: E731 — a wave's rows -> the slots
        lambda x: jax.ShapeDtypeStruct((BART_SLOTS, *x.shape[1:]), x.dtype, sharding=one_chip), tree)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)  # noqa: E731
    params = abstract(jax.eval_shape(lambda: lm.init_params(0)))  # float32, as the cell serves them
    ids = jnp.zeros((1, BART_PROMPT), jnp.int32)
    enc, mask, ckv = slots(eng._prefill.eval_shape(params, ids, ids))
    cache = abstract(jax.eval_shape(lambda p: _init_cache(
        eng.model, p, BART_SLOTS, BART_NEW, jnp.zeros(enc.shape, enc.dtype), jnp.zeros(mask.shape, mask.dtype)), params))
    shapes = {x.shape for x in jax.tree.leaves(cache)}
    assert shapes == {(), (64, 128, 16 * 64)}, shapes  # K/V: (slots, length, heads x d)
    state = {"cache": cache, "enc": enc, "enc_mask": mask, "ckv": ckv, "last": i32(BART_SLOTS, 1)}
    active = jax.ShapeDtypeStruct((BART_SLOTS,), jnp.bool_, sharding=one_chip)

    step = eng._step.lower(params, state, i32(BART_SLOTS), active).compile()
    text = step.as_text()
    assert len(_decode_attn_calls(text, (64, 16, 1, 64))) == 12  # flash_decode, one a decoder layer
    # the cross step on the same kernel (PR 46), as ``serve_cross_attn_ms`` finds it; the slots keep the cross K/V
    # as a cache keeps K/V, 24 leaves that enter in the descending layout and are copied nowhere; XLA's two cross
    # fusions are gone; and the 24 call sites' live lists are ONE sort (one expression, merged), not 24
    assert len(re.findall(r"%cross_attn\.\d+ = bf16\[64,16,1,64\]\{[^}]*\} custom-call\(", text)) == 12
    assert {x.shape for x in jax.tree.leaves(ckv)} == {(64, 1024, 1024)}
    cross = [p for p in _entry_layouts(text) if p.startswith("bf16[64,1024,1024]")]
    # 24 cross K/V leaves and the slots' encoder states, the same shape
    assert len(cross) == 24 + 1 and all(p.startswith("bf16[64,1024,1024]{2,1,0:") for p in cross), cross
    assert not _large_copies(text, 64 * 1024 * 1024)
    assert not re.search(r"f32\[64,16,1024\]|bf16\[64,16,1024,64\]", text)
    assert len(re.findall(r" sort\(", text)) == 1
    _assert_cache_rests_where_it_is_read(text, step.memory_analysis(), (64, 128, 1024), leaves=24)


def test_lfm2_seeded_weights_are_made_in_one_copy(one_chip):
    """The one jitted call that makes the program's float32 tree from the seed
    holds the tree and next to nothing beside it: 6.66 GB of output, no
    stacked or transposed second copy (``reference/lfm2_moe.py`` names its
    tensors per layer in the program's layout for this)."""
    from benchmarks.harness import program, spec as spec_mod, weights

    cfg = spec_mod.load_json(os.path.join(spec_mod.BENCH_DIR, "configs", "lfm2-8b-a1b.json"))
    ref, adapter = (spec_mod.load_module(kind, cfg["family"]) for kind in ("reference", "adapters"))
    spec, to_tree = ref.param_spec(cfg), program.to_program_tree(adapter.leaf_map(cfg))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    mem = jax.jit(lambda k: to_tree(weights._generate(spec, k))).lower(key).compile().memory_analysis()
    assert 6.6e9 < mem.output_size_in_bytes < 6.7e9 and mem.temp_size_in_bytes < 0.1e9, mem


@pytest.mark.parametrize("rows,k,n", [(512, 2048, 1792), (512, 1792, 2048), (16384, 2048, 1792), (16384, 1792, 2048)],
                         ids=["decode-gate", "decode-down", "wave-gate", "wave-down"])
def test_grouped_expert_product_compiles_at_the_cell_shapes(rows, k, n, one_chip, compiled_kernels):
    """``ops/moe.py`` ``grouped_dot``'s Pallas path (megablox ``gmm`` under
    ``gmm_tiling``) at the new cell's decode round (128 slots x top-4) and
    prefill wave (4 x 1024 x top-4): 32 experts of 2048 x 1792, bfloat16."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from distributed_llms_example_tpu.ops.moe import gmm_tiling

    tiling = gmm_tiling(rows, k, n, 2)
    assert tiling == ((128 if rows == 512 else 256), k, n // 2)
    text = _compile(lambda x, w, load: gmm(x, w, load, BF16, tiling), one_chip,
                    ((rows, k), BF16), ((32, k, n), BF16), ((32,), jnp.int32))
    assert "ragged-dot" not in text


# ---- brumby-14b.serve-steady: the retention step's kernel, and the cell's three serving programs, whole ----

BRUMBY_SLOTS, BRUMBY_WAVE, BRUMBY_PROMPT, BRUMBY_NEW = 24, 4, 1024, 128
_RETENTION_STEP = re.compile(r"%retention_step(?:\.\d+)? = \(f32\[24,8,5,128\]\{[^}]*\}, f32\[24,8,65,128,128\]\{[^}]*\}, ")
# the live-slot list and its length lead the call's operands (scalar prefetch), and the state
# (operand 6, counted from them) and the normaliser are the results' buffers
_RETENTION_STEP_OPERANDS = re.compile(
    r"%retention_step(?:\.\d+)? = .*operand_layout_constraints=\{s32\[24\]\{0\}, s32\[1\]\{0\}, f32\[192\]\{0\}, "
    r".*output_to_operand_aliasing=\{\{1\}: \(6, \{\}\), \{2\}: \(7, \{\}\)\}")


def test_retention_step_kernel_compiles_and_updates_the_state_in_place(one_chip, compiled_kernels):
    """The decode kernel at the cell's shapes (24 slots, 40 query / 8 KV heads of
    128, state (24, 8, 65, 128, 128) float32) with a live-slot mask: Mosaic
    takes the tiling and the scalar-prefetched list, the custom call carries
    the kernel's name (what ``serve_retention_step_ms`` looks for), and the
    state is aliased to the result, not copied."""
    from distributed_llms_example_tpu.ops import retention

    s_shape, z_shape = retention.state_shapes(BRUMBY_SLOTS, 8, 128, 128)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((BRUMBY_SLOTS, 40, 128), BF16), ((BRUMBY_SLOTS, 8, 128), BF16), ((BRUMBY_SLOTS, 8, 128), BF16),
        ((BRUMBY_SLOTS, 8), F32), (s_shape, F32), (z_shape, F32), ((BRUMBY_SLOTS,), jnp.bool_))]
    step = jax.jit(lambda *a: retention.retention_step(*a[:6], live=a[6]), donate_argnums=(4, 5)).lower(*args).compile()
    text, mem = step.as_text(), step.memory_analysis()
    assert len(_RETENTION_STEP.findall(text)) == 1, [ln[:120] for ln in text.splitlines() if "custom-call(" in ln]
    assert len(_RETENTION_STEP_OPERANDS.findall(text)) == 1
    assert mem.alias_size_in_bytes >= math.prod(s_shape) * 4 and mem.temp_size_in_bytes < 0.1e9, mem
    assert not _large_copies(text, math.prod(s_shape))


def test_brumby_cell_serving_programs_compile_and_fit(topo, one_chip, compiled_kernels, monkeypatch):
    """Decode step, prefill wave (1 and 4 rows) and admit at 24 slots x 1,024 +
    128 tokens, four layers at the published widths, bfloat16 weights: one
    ``retention_step`` call a layer on the state as it rests (3.25 GB, aliased,
    no copy of a state leaf), and a four-row wave whose temporaries are one KV
    head's (13 GB when the wave's heads were computed at once)."""
    from benchmarks.harness import program, spec as spec_mod
    from distributed_llms_example_tpu.core.config import MeshConfig
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.models import registry
    from distributed_llms_example_tpu.serving.engine import ServeConfig, ServingEngine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the kernel's choice and donation ask it
    cfg = spec_mod.load_json(os.path.join(spec_mod.BENCH_DIR, "configs", "brumby-14b.json"))
    lm = registry.load_model(
        program.register_bench_model(cfg, spec_mod.load_module("adapters", cfg["family"])), dtype=BF16)
    assert lm.config.num_hidden_layers == 4 and lm.config.vocab_size == 18992 and lm.config.eos_token_id is None
    serve = ServeConfig(max_slots=BRUMBY_SLOTS, prefill_batch=BRUMBY_WAVE, max_new_tokens=BRUMBY_NEW,
                        max_source_length=BRUMBY_PROMPT)
    eng = ServingEngine(lm.module, lm.config, build_mesh(MeshConfig(data=-1), devices=topo.devices[:1]),
                        serve, is_seq2seq=False)

    def abstract(tree, dtype=None):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, dtype if dtype is not None and jnp.issubdtype(x.dtype, jnp.floating) else x.dtype,
            sharding=one_chip), tree)

    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)  # noqa: E731
    params = abstract(jax.eval_shape(lambda: lm.init_params(0)), BF16)
    zeros = lambda n: (jnp.zeros((n, BRUMBY_PROMPT), jnp.int32),) * 2  # noqa: E731
    slots_cache, slots_mask, _, _ = jax.eval_shape(lambda p: eng._prefill_core(p, *zeros(BRUMBY_SLOTS)), params)
    shapes = sorted({x.shape for x in jax.tree.leaves(slots_cache)})
    assert shapes == [(), (24, 8, 65, 128), (24, 8, 65, 128, 128)], shapes  # no leaf with a length axis
    state = {"cache": abstract(slots_cache), "mask": abstract(slots_mask), "last": i32(BRUMBY_SLOTS)}
    active = jax.ShapeDtypeStruct((BRUMBY_SLOTS,), jnp.bool_, sharding=one_chip)
    state_bytes = 4 * 24 * 8 * 65 * 128 * 129 * 4

    step = eng._step.lower(params, state, i32(BRUMBY_SLOTS), i32(BRUMBY_SLOTS), active).compile()
    text, mem = step.as_text(), step.memory_analysis()
    assert len(_RETENTION_STEP.findall(text)) == 4  # one call a layer, named after the kernel
    assert len(_RETENTION_STEP_OPERANDS.findall(text)) == 4  # each walks the round's live slots
    assert mem.alias_size_in_bytes >= state_bytes and mem.temp_size_in_bytes < 0.3e9, mem
    assert not _large_copies(text, 24 * 8 * 65 * 128 * 128)

    assert eng.wave_sizes == (1, BRUMBY_WAVE)
    for rows in eng.wave_sizes:
        wave_cache, wave_mask, _, wave_first = jax.eval_shape(lambda p: eng._prefill_core(p, *zeros(rows)), params)
        wave = eng._prefill.lower(params, i32(rows, BRUMBY_PROMPT), i32(rows, BRUMBY_PROMPT)).compile()
        assert wave.memory_analysis().temp_size_in_bytes < 1.0e9, wave.memory_analysis()
        admit = eng._admit.lower(state, abstract(wave_cache), abstract(wave_mask), abstract(wave_first), i32(rows)).compile()
        assert admit.memory_analysis().temp_size_in_bytes < 0.1e9
        assert admit.memory_analysis().alias_size_in_bytes >= state_bytes


MELLUM_SLOTS, MELLUM_WAVE, MELLUM_PROMPT, MELLUM_NEW = 48, 1, 8192, 256
_WINDOW_DECODE = re.compile(r"%window_decode(?:\.\d+)? = bf16\[48,4,8,128\]\{[^}]*\} custom-call\(")
_PROMPT_ATTN = re.compile(r"%prompt_attn(?:\.\d+)? = \(bf16\[1,32,8192,128\]\{[^}]*\}, ")


def test_window_kernels_compile_at_the_cell_shapes_under_their_own_names(one_chip, compiled_kernels):
    """The window flavour of the forward kernel over one 8,192-token row of 32
    heads (grid: 8 q tiles x 2 band tiles of 1,024, not x 8) and the ring step
    over 48 slots' 1,024-entry leaves, 8 query heads a KV head as q rows: what
    Mosaic accepts, and the names a device trace will show."""
    from distributed_llms_example_tpu.ops import flash_attention as fa

    assert fa._band_tiles(8192, 1024, 1024, 1024) == 2 and fa._band_tiles(8192, 512, 512, 1024) == 3
    qkv = [((1, 32, MELLUM_PROMPT, 128), BF16)] * 3
    for window, calls in ((1024, 1), (None, 1)):
        text = _compile(lambda q, k, v: fa.flash_prompt_attention(q, k, v, window=window), one_chip, *qkv)  # noqa: B023
        assert len(_PROMPT_ATTN.findall(text)) == calls, [ln[:120] for ln in text.splitlines() if "custom-call(" in ln]
    text = _compile(
        lambda q, k, v, pos: fa.flash_decode(q, k, v, offsets=pos, q_group=8, ring=True), one_chip,
        ((MELLUM_SLOTS, 4, 8, 128), BF16), ((MELLUM_SLOTS, 1024, 512), BF16), ((MELLUM_SLOTS, 1024, 512), BF16),
        ((MELLUM_SLOTS,), jnp.int32))
    assert len(_WINDOW_DECODE.findall(text)) == 1, [ln[:120] for ln in text.splitlines() if "custom-call(" in ln]


def test_mellum_cell_serving_programs_compile_and_fit(topo, one_chip, compiled_kernels, monkeypatch):
    """Decode step, the one-row prefill wave and admit at 48 slots x 8,192 + 256
    tokens, one period (3 window layers + 1 full) at the published widths,
    bfloat16 weights: the window leaves hold 1,024 positions and no more, a
    window layer's step is ``window_decode`` and the full layer's is found at
    its call site, no K/V leaf is copied, and the prompt's attention is four
    ``prompt_attn`` calls with no (8192, 8192) scores anywhere."""
    from benchmarks.harness import program, spec as spec_mod
    from distributed_llms_example_tpu.core.config import MeshConfig
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.models import registry
    from distributed_llms_example_tpu.serving.engine import ServeConfig, ServingEngine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the kernels' auto rules and donation ask it
    cfg = spec_mod.load_json(os.path.join(spec_mod.BENCH_DIR, "configs", "mellum2-12b-a2.5b.json"))
    lm = registry.load_model(
        program.register_bench_model(cfg, spec_mod.load_module("adapters", cfg["family"])), dtype=BF16)
    assert lm.config.layer_types == ("sliding_attention",) * 3 + ("full_attention",) and lm.config.vocab_size == 24576
    serve = ServeConfig(max_slots=MELLUM_SLOTS, prefill_batch=MELLUM_WAVE, max_new_tokens=MELLUM_NEW,
                        max_source_length=MELLUM_PROMPT)
    eng = ServingEngine(lm.module, lm.config, build_mesh(MeshConfig(data=-1), devices=topo.devices[:1]),
                        serve, is_seq2seq=False)

    def abstract(tree, dtype=None):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, dtype if dtype is not None and jnp.issubdtype(x.dtype, jnp.floating) else x.dtype,
            sharding=one_chip), tree)

    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)  # noqa: E731
    params = abstract(jax.eval_shape(lambda: lm.init_params(0)), BF16)
    zeros = lambda n: (jnp.zeros((n, MELLUM_PROMPT), jnp.int32),) * 2  # noqa: E731
    slots_cache = eng._slot_cache_shapes(params)
    shapes = sorted({x.shape for x in jax.tree.leaves(slots_cache)})
    assert shapes == [(), (48, 1024, 512), (48, 8448, 512)], shapes  # two lengths of K/V leaf in one slot cache
    state = {"cache": abstract(slots_cache), "mask": i32(MELLUM_SLOTS, MELLUM_PROMPT + MELLUM_NEW), "last": i32(MELLUM_SLOTS)}
    active = jax.ShapeDtypeStruct((MELLUM_SLOTS,), jnp.bool_, sharding=one_chip)

    step = eng._step.lower(params, state, i32(MELLUM_SLOTS), i32(MELLUM_SLOTS), active).compile()
    text, mem = step.as_text(), step.memory_analysis()
    assert len(_WINDOW_DECODE.findall(text)) == 3, [ln[:120] for ln in text.splitlines() if "custom-call(" in ln]
    assert len(_decode_attn_calls(text, (48, 4, 8, 128))) == 1  # the full layer: 8 query heads a KV head as q rows
    assert "%gmm" in text and "ragged-dot" not in text  # the Pallas grouped product at 64 experts of 896
    kv_bytes = 2 * 48 * (3 * 1024 + 8448) * 512 * 2
    assert mem.alias_size_in_bytes >= kv_bytes and mem.temp_size_in_bytes < 0.5e9, mem
    assert not _large_copies(text, 48 * 1024 * 512)

    assert eng.wave_sizes == (1,)
    wave_cache, wave_mask, _, wave_first = jax.eval_shape(lambda p: eng._prefill_core(p, *zeros(1)), params)
    assert sorted({x.shape for x in jax.tree.leaves(wave_cache)}) == [(), (1, 1024, 512), (1, 8448, 512)]
    wave = eng._prefill.lower(params, i32(1, MELLUM_PROMPT), i32(1, MELLUM_PROMPT)).compile()
    text = wave.as_text()
    assert len(_PROMPT_ATTN.findall(text)) == 4, [ln[:120] for ln in text.splitlines() if "custom-call(" in ln]
    assert "8192,8192]" not in text and "8192,8448]" not in text  # no materialised scores
    assert "%gmm" in text and wave.memory_analysis().temp_size_in_bytes < 2.5e9, wave.memory_analysis()
    admit = eng._admit.lower(state, abstract(wave_cache), abstract(wave_mask), abstract(wave_first), i32(1)).compile()
    assert admit.memory_analysis().temp_size_in_bytes < 0.1e9 and admit.memory_analysis().alias_size_in_bytes >= kv_bytes


# ---- falcon-h1-34b.serve-steady: the state-space step's kernel, and the cell's three serving programs, whole ----

FALCON_SLOTS, FALCON_WAVE, FALCON_PROMPT, FALCON_NEW = 64, 4, 256, 256
_SSM_STEP = re.compile(r"%ssm_step(?:\.\d+)? = \(f32\[64,32,1,128\]\{[^}]*\}, f32\[64,32,256,128\]\{[^}]*\}\) custom-call\(")
# the live-slot list and its length lead the call's operands (scalar prefetch), the decays go whole
# (64 x 32), and the state (operand 6, counted from them) is the result's buffer
_SSM_STEP_OPERANDS = re.compile(
    r"%ssm_step(?:\.\d+)? = .*operand_layout_constraints=\{s32\[64\]\{0\}, s32\[1\]\{0\}, f32\[2048\]\{0\}, "
    r".*output_to_operand_aliasing=\{\{1\}: \(6, \{\}\)\}")
_FALCON_PROMPT_ATTN = re.compile(r"%prompt_attn(?:\.\d+)? = \(bf16\[(\d),20,256,128\]\{[^}]*\}, ")


def test_ssm_step_kernel_compiles_and_updates_the_state_in_place(one_chip, compiled_kernels):
    """The decode kernel at the cell's shapes (64 slots, 32 heads of 128 in 2
    groups, state (64, 32, 256, 128) float32) with a live-slot mask: Mosaic
    takes the tiling, the turn of B and C and the scalar-prefetched list, the
    custom call carries the kernel's name (what ``serve_ssm_step_ms`` looks
    for), and the state is aliased to the result, not copied."""
    from distributed_llms_example_tpu.ops import ssm

    s_shape = ssm.state_shape(FALCON_SLOTS, 32, 128, 256)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((FALCON_SLOTS, 32, 128), BF16), ((FALCON_SLOTS, 32), F32), ((32,), F32), ((FALCON_SLOTS, 2, 256), BF16),
        ((FALCON_SLOTS, 2, 256), BF16), ((32,), F32), (s_shape, F32), ((FALCON_SLOTS,), jnp.bool_))]
    step = jax.jit(lambda *a: ssm.ssm_step(*a[:7], live=a[7]), donate_argnums=(6,)).lower(*args).compile()
    text, mem = step.as_text(), step.memory_analysis()
    assert len(_SSM_STEP.findall(text)) == 1, [ln[:160] for ln in text.splitlines() if "custom-call(" in ln]
    assert len(_SSM_STEP_OPERANDS.findall(text)) == 1
    assert mem.alias_size_in_bytes >= math.prod(s_shape) * 4 and mem.temp_size_in_bytes < 0.01e9, mem
    assert not _large_copies(text, math.prod(s_shape))


def test_falcon_h1_cell_serving_programs_compile_and_fit(topo, one_chip, compiled_kernels, monkeypatch):
    """Decode step, prefill wave (1 and 4 rows) and admit at 64 slots x 256 + 256
    tokens, four layers at the published widths, bfloat16 weights: THREE kinds of
    leaf a layer in one slot cache; one ``ssm_step`` call a layer on the state as
    it rests (1.07 GB, aliased, no copy of a state or K/V leaf) beside one decode
    attention a layer (5 query heads a KV head as q rows); a wave's attention is
    four ``prompt_attn`` calls; and every result that ``serve_ssm_prefill_ms``
    tells by its shape is the scan's (no attention or projection result has one)."""
    from benchmarks.harness import program, spec as spec_mod
    from distributed_llms_example_tpu.core.config import MeshConfig
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.models import registry
    from distributed_llms_example_tpu.serving.engine import ServeConfig, ServingEngine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the kernels' auto rules and donation ask it
    cfg = spec_mod.load_json(os.path.join(spec_mod.BENCH_DIR, "configs", "falcon-h1-34b.json"))
    lm = registry.load_model(
        program.register_bench_model(cfg, spec_mod.load_module("adapters", cfg["family"])), dtype=BF16)
    assert lm.config.num_hidden_layers == 4 and lm.config.vocab_size == 32640 and lm.config.eos_token_id is None
    assert lm.config.decode_streams_live_slots
    serve = ServeConfig(max_slots=FALCON_SLOTS, prefill_batch=FALCON_WAVE, max_new_tokens=FALCON_NEW,
                        max_source_length=FALCON_PROMPT)
    eng = ServingEngine(lm.module, lm.config, build_mesh(MeshConfig(data=-1), devices=topo.devices[:1]),
                        serve, is_seq2seq=False)

    def abstract(tree, dtype=None):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, dtype if dtype is not None and jnp.issubdtype(x.dtype, jnp.floating) else x.dtype,
            sharding=one_chip), tree)

    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)  # noqa: E731
    params = abstract(jax.eval_shape(lambda: lm.init_params(0)), BF16)
    zeros = lambda n: (jnp.zeros((n, FALCON_PROMPT), jnp.int32),) * 2  # noqa: E731
    slots_cache = eng._slot_cache_shapes(params)
    shapes = sorted({x.shape for x in jax.tree.leaves(slots_cache)})
    assert shapes == [(), (64, 32, 256, 128), (64, 512, 512), (64, 5120, 3)], shapes  # state, K/V, taps
    state = {"cache": abstract(slots_cache), "mask": i32(FALCON_SLOTS, FALCON_PROMPT + FALCON_NEW), "last": i32(FALCON_SLOTS)}
    active = jax.ShapeDtypeStruct((FALCON_SLOTS,), jnp.bool_, sharding=one_chip)
    state_bytes, kv_bytes = 4 * 64 * 32 * 256 * 128 * 4, 2 * 4 * 64 * 512 * 512 * 2

    step = eng._step.lower(params, state, i32(FALCON_SLOTS), i32(FALCON_SLOTS), active).compile()
    text, mem = step.as_text(), step.memory_analysis()
    assert len(_SSM_STEP.findall(text)) == 4, [ln[:160] for ln in text.splitlines() if "custom-call(" in ln]
    assert len(_SSM_STEP_OPERANDS.findall(text)) == 4  # each walks the round's live slots
    assert len(_decode_attn_calls(text, (64, 4, 5, 128))) == 4  # 5 query heads a KV head as q rows
    assert mem.alias_size_in_bytes >= state_bytes + kv_bytes and mem.temp_size_in_bytes < 0.3e9, mem
    assert not _large_copies(text, 64 * 512 * 512)  # neither a K/V leaf nor (16 x larger) a state leaf

    assert eng.wave_sizes == (1, FALCON_WAVE)
    is_ssm = spec_mod.load_module("layer_metrics", "serve_ssm_prefill_ms").ssm_shapes(32, 2, 128, 256, 128)
    for rows in eng.wave_sizes:
        wave_cache, wave_mask, _, wave_first = jax.eval_shape(lambda p: eng._prefill_core(p, *zeros(rows)), params)
        wave = eng._prefill.lower(params, i32(rows, FALCON_PROMPT), i32(rows, FALCON_PROMPT)).compile()
        text = wave.as_text()
        assert [int(n) for n in _FALCON_PROMPT_ATTN.findall(text)] == [rows] * 4
        assert wave.memory_analysis().temp_size_in_bytes < 1.0e9, wave.memory_analysis()
        told = [ln for ln in text.splitlines() if " = " in ln and ln.startswith("  ")
                and (m := _SHAPE.search(ln.split(" = ", 1)[1])) and is_ssm(tuple(int(x) for x in m.group(1).split(",") if x))]
        assert told and all("ssm_prefill" in ln or "/mixer/" in ln for ln in told if "op_name=" in ln), [
            ln[:200] for ln in told if "op_name=" in ln and "ssm_prefill" not in ln][:5]
        admit = eng._admit.lower(state, abstract(wave_cache), abstract(wave_mask), abstract(wave_first), i32(rows)).compile()
        assert admit.memory_analysis().temp_size_in_bytes < 0.1e9
        assert admit.memory_analysis().alias_size_in_bytes >= state_bytes + kv_bytes
