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
    from distributed_llms_example_tpu.ops import flash_attention, fused_dropout, fused_optim

    for mod in (flash_attention, fused_dropout, fused_optim):
        monkeypatch.setattr(mod, "_default_interpret", lambda: False)
    with jax.default_matmul_precision(None):
        yield


def _compile(fn, sharding, *shapes):
    """Lower ``fn`` for the described device(s) and return the compiled text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled program"
    return text


def _qkv(q_len, kv_len, heads=H, d=D):
    return [((B, heads, q_len, d), BF16), ((B, heads, kv_len, d), BF16), ((B, heads, kv_len, d), BF16)]


FLASH_CASES = {
    # name: (q_len, kv_len, causal, kv-mask bias, learned bias, probs dropout)
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
        shapes.append(((1, H, q_len, kv_len), F32))
    if rate:
        shapes.append(((), jnp.int32))

    def loss(q, k, v, *rest):
        rest = list(rest)
        seed = rest.pop() if rate else None
        lbias = rest.pop() if learned else None
        bias = rest.pop() if masked else None
        out = flash_attention(
            q, k, v, bias, learned_bias=lbias, causal=causal,
            dropout_rate=rate, dropout_seed=seed,
        )
        return out.astype(F32).sum()

    argnums = (0, 1, 2) + ((3 + masked,) if learned else ())
    text = _compile(jax.grad(loss, argnums=argnums), one_chip, *shapes)
    # forward + dq + dkv kernels, plus the dbias kernel of the learned flavor
    assert text.count("tpu_custom_call") >= (4 if learned else 3)


def test_flash_llama7b_shape_compiles(one_chip, compiled_kernels):
    from distributed_llms_example_tpu.ops.flash_attention import flash_attention

    shapes = [((2, 32, 1024, 128), BF16)] * 3

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(F32).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, *shapes)


DECODE_CASES = {
    # name: (slots, q rows, cache length, int8 K/V with (B, H, L) scales)
    "train-batch-cache128": (B, 1, TGT, False),
    "train-batch-cache1024": (B, 1, SRC, False),
    # bart-large-cnn.serve-steady's decode step, exactly: 64 slots, one row, cache 128
    "serve-cell": (64, 1, TGT, False),
    "serve-cell-verify-8-rows": (64, 8, TGT, False),
    "serve-cell-cache1024": (64, 1, SRC, False),
    "serve-cell-int8": (64, 1, TGT, True),
}


@pytest.mark.parametrize("case", sorted(DECODE_CASES))
def test_flash_decode_compiles(case, one_chip, compiled_kernels):
    from distributed_llms_example_tpu.ops.flash_attention import flash_decode

    slots, q_len, cache_len, int8 = DECODE_CASES[case]
    kv = ((slots, H, cache_len, D), jnp.int8 if int8 else BF16)
    shapes = [((slots, H, q_len, D), BF16), kv, kv, ((slots,), jnp.int32)]
    if int8:
        shapes += [((slots, H, cache_len), F32)] * 2

    def step(q, k, v, offsets, k_scale=None, v_scale=None):
        return flash_decode(q, k, v, offsets=offsets, k_scale=k_scale, v_scale=v_scale)

    _compile(step, one_chip, *shapes)


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
