"""Model-level flash-vs-XLA attention parity (forward + gradients).

Forces ``attention_impl='flash'`` (interpreted Pallas on CPU) on the tiny
BART and LLaMA configs and checks logits/grads against the XLA path — the
guarantee that flipping the kernel on TPU cannot change training numerics.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_example_tpu.models.bart import BartForConditionalGeneration
from distributed_llms_example_tpu.models.llama import LlamaForCausalLM
from distributed_llms_example_tpu.models.registry import BART_CONFIGS, LLAMA_CONFIGS


def _variants(cfg, module_cls):
    mods = {}
    for impl in ("xla", "flash"):
        mods[impl] = module_cls(dataclasses.replace(cfg, attention_impl=impl))
    return mods


@pytest.mark.slow  # ~9s dual-impl compile: slow tier (t5 flash parity
# stays fast)
def test_llama_flash_matches_xla():
    cfg = LLAMA_CONFIGS["llama-test"]  # head_dim 16
    mods = _variants(cfg, LlamaForCausalLM)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(3, cfg.vocab_size, (2, 64)), jnp.int32)
    mask = jnp.ones((2, 64), jnp.int32).at[0, 50:].set(0)
    params = mods["xla"].init(jax.random.PRNGKey(0), ids, mask)["params"]

    def loss(m):
        def f(p):
            logits = m.apply({"params": p}, ids, mask)
            return jnp.mean(logits.astype(jnp.float32) ** 2)

        return jax.value_and_grad(f)(params)

    (l_x, g_x), (l_f, g_f) = loss(mods["xla"]), loss(mods["flash"])
    np.testing.assert_allclose(float(l_x), float(l_f), rtol=1e-5)
    flat_x, flat_f = jax.tree.leaves(g_x), jax.tree.leaves(g_f)
    for a, b in zip(flat_x, flat_f):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-3)


def test_bart_flash_matches_xla():
    cfg = BART_CONFIGS["bart-test"]  # head_dim 16
    mods = _variants(cfg, BartForConditionalGeneration)
    rng = np.random.RandomState(1)
    src = jnp.asarray(rng.randint(3, cfg.vocab_size, (2, 64)), jnp.int32)
    src_mask = jnp.ones((2, 64), jnp.int32).at[1, 40:].set(0)
    tgt = jnp.asarray(rng.randint(3, cfg.vocab_size, (2, 32)), jnp.int32)
    params = mods["xla"].init(jax.random.PRNGKey(0), src, src_mask, tgt)["params"]

    out_x = mods["xla"].apply({"params": params}, src, src_mask, tgt)
    out_f = mods["flash"].apply({"params": params}, src, src_mask, tgt)
    np.testing.assert_allclose(
        np.asarray(out_x), np.asarray(out_f), atol=2e-4, rtol=1e-3
    )


def test_bart_flash_cached_generation_falls_back():
    """attention_impl='flash' must not break cached decode (q_len=1 steps
    silently use the XLA path) and must produce identical greedy tokens."""
    from distributed_llms_example_tpu.evaluation.generation import make_greedy_generate

    cfg = BART_CONFIGS["bart-test"]
    mods = _variants(cfg, BartForConditionalGeneration)
    rng = np.random.RandomState(2)
    src = jnp.asarray(rng.randint(3, cfg.vocab_size, (2, 32)), jnp.int32)
    src_mask = jnp.ones((2, 32), jnp.int32).at[0, 20:].set(0)
    params = mods["xla"].init(jax.random.PRNGKey(0), src, src_mask, src[:, :8])["params"]

    toks = {}
    for impl, mod in mods.items():
        gen = make_greedy_generate(mod, dataclasses.replace(cfg, attention_impl=impl), max_new_tokens=12)
        toks[impl] = np.asarray(gen(params, src, src_mask))
    np.testing.assert_array_equal(toks["xla"], toks["flash"])


def test_t5_flash_matches_xla_incl_bias_table_grad(monkeypatch):
    """T5 with attention_impl='flash': the learned relative-position bias
    rides the kernel's differentiable relative_bias input as a per-diagonal
    vector — logits AND gradients (including the two bias tables', which the
    kernels hand back as diagonal sums) must match the XLA path's, which goes
    through the (1, H, Q, K) matrix, and the table gradients must be nonzero
    (a silently-constant bias was exactly the round-2 failure mode this
    guards against).  The trace-time tally says which way each site went."""
    from distributed_llms_example_tpu.core.config import MeshConfig
    from distributed_llms_example_tpu.core.mesh import build_mesh
    from distributed_llms_example_tpu.models import t5
    from distributed_llms_example_tpu.models.registry import T5_CONFIGS
    from distributed_llms_example_tpu.models.t5 import T5ForConditionalGeneration
    from distributed_llms_example_tpu.parallel.activation import activation_mesh

    tallies = []
    flush = t5.flush_relative_bias_sites
    monkeypatch.setattr(t5, "flush_relative_bias_sites", lambda: tallies.append(flush()))
    # a one-device mesh, as a one-chip trainer traces its step: without a mesh
    # the eight virtual CPU devices send a forced "flash" to XLA's path
    mesh1 = build_mesh(MeshConfig(data=-1), devices=jax.devices()[:1])

    cfg = dataclasses.replace(T5_CONFIGS["t5-test"], dropout_rate=0.0)
    mods = _variants(cfg, T5ForConditionalGeneration)
    rng = np.random.RandomState(2)
    src = jnp.asarray(rng.randint(3, cfg.vocab_size, (2, 64)), jnp.int32)
    src_mask = jnp.ones((2, 64), jnp.int32).at[1, 48:].set(0)
    tgt = jnp.asarray(rng.randint(3, cfg.vocab_size, (2, 32)), jnp.int32)
    params = mods["xla"].init(jax.random.PRNGKey(3), src, src_mask, tgt)["params"]

    def loss(m):
        def f(p):
            logits = m.apply({"params": p}, src, src_mask, tgt)
            return jnp.mean(logits.astype(jnp.float32) ** 2)

        with activation_mesh(mesh1):
            return jax.value_and_grad(f)(params)

    (l_x, g_x), (l_f, g_f) = loss(mods["xla"]), loss(mods["flash"])
    sites = cfg.num_layers + cfg.decoder_layers  # one self-attention a block, both stacks
    assert tallies[-2:] == [{"diagonal": 0, "matrix": sites}, {"diagonal": sites, "matrix": 0}], tallies
    np.testing.assert_allclose(float(l_x), float(l_f), rtol=1e-5)
    tables = 0
    paths_x = jax.tree_util.tree_flatten_with_path(g_x)[0]
    paths_f = jax.tree.leaves(g_f)
    for (path, a), b in zip(paths_x, paths_f):
        name = jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-3, err_msg=name
        )
        if "relative_attention_bias" in name:
            tables += 1
            assert np.abs(np.asarray(b)).sum() > 0, f"{name}: zero bias-table grad"
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6, rtol=1e-5, err_msg=name)
    assert tables == 2  # the encoder's and the decoder's


@pytest.mark.slow  # ~20s sharded-lbias compile: slow tier (single-device
# t5 flash parity incl. the bias-table grad stays fast)
def test_t5_flash_multi_device_bias_table_grads(mesh8):
    """T5 with attention_impl='flash' on an 8-device mesh: self-attention
    takes the sharded learned-bias path (hand-written vjp) — logits and
    grads incl. the relative-position tables match the XLA path."""
    from distributed_llms_example_tpu.models.registry import T5_CONFIGS
    from distributed_llms_example_tpu.models.t5 import T5ForConditionalGeneration
    from distributed_llms_example_tpu.parallel.activation import activation_mesh

    cfg = dataclasses.replace(T5_CONFIGS["t5-test"], dropout_rate=0.0)
    mods = _variants(cfg, T5ForConditionalGeneration)
    rng = np.random.RandomState(6)
    src = jnp.asarray(rng.randint(3, cfg.vocab_size, (8, 128)), jnp.int32)
    src_mask = jnp.ones((8, 128), jnp.int32).at[1, 96:].set(0)
    tgt = jnp.asarray(rng.randint(3, cfg.vocab_size, (8, 32)), jnp.int32)
    params = mods["xla"].init(jax.random.PRNGKey(4), src, src_mask, tgt)["params"]

    def loss(m):
        def f(p):
            logits = m.apply({"params": p}, src, src_mask, tgt)
            return jnp.mean(logits.astype(jnp.float32) ** 2)

        with activation_mesh(mesh8):
            return jax.jit(jax.value_and_grad(f))(params)

    (l_x, g_x), (l_f, g_f) = loss(mods["xla"]), loss(mods["flash"])
    np.testing.assert_allclose(float(l_x), float(l_f), rtol=1e-5)
    paths_x = jax.tree_util.tree_flatten_with_path(g_x)[0]
    for (path, a), b in zip(paths_x, jax.tree.leaves(g_f)):
        name = jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-3, err_msg=name
        )
        if "relative_attention_bias" in name:
            assert np.abs(np.asarray(b)).sum() > 0, f"{name}: zero bias-table grad"
