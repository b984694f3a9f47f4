"""The plain references against (a) the published implementations in
``transformers`` and (b) the program's own models, at toy sizes in float32.
(a) is what makes them references; (b) is what the benchmark relies on."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ROOT

from benchmarks.harness import precision, program, spec as spec_mod, weights

CFG = {n: json.load(open(os.path.join(ROOT, "benchmarks", "configs", n + ".json"))) for n in ("bart-test", "t5-test")}


def _inputs(cfg, seed=3, b=3, s=24, t=9):
    rng = np.random.default_rng(seed)
    ids = rng.integers(3, cfg["vocab_size"], (b, s)).astype(np.int32)
    mask = np.ones((b, s), np.int32)
    mask[1, s - 5:] = 0
    dec = rng.integers(3, cfg["vocab_size"], (b, t)).astype(np.int32)
    return ids, mask, dec


def _reference_logits(name, seed=3):
    cfg = CFG[name]
    ref = spec_mod.load_module("reference", cfg["family"])
    w = weights.make_reference_weights(ref.param_spec(cfg), seed)
    ids, mask, dec = _inputs(cfg)
    with jax.default_matmul_precision("highest"):
        logits = ref.forward(w, cfg, jnp.asarray(ids), jnp.asarray(mask), jnp.asarray(dec), precision.make_dot("fp32"))
    return cfg, ref, w, (ids, mask, dec), np.asarray(logits)


def _hf_name_bart(name, i):
    return ("final_logits_bias" if name == "final_logits_bias" else "model." + name).replace("*", str(i))


def _hf_name_t5(name, i):
    if ".block.*." not in name:
        if "relative_attention_bias" in name:
            side = name.split(".")[0]
            return f"{side}.block.0.layer.0.SelfAttention.relative_attention_bias.weight"
        return name
    side, _, _, module, *rest = name.split(".")
    sub = {"SelfAttention": 0, "EncDecAttention": 1, "DenseReluDense": 2 if side == "decoder" else 1}[module]
    tail = ".".join(rest)
    inner = tail if tail.startswith("layer_norm") else f"{module}.{tail}"
    return f"{side}.block.{i}.layer.{sub}.{inner}"


@pytest.mark.parametrize("name", ["bart-test", "t5-test"])
def test_reference_equals_the_published_implementation(name):
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    cfg, ref, w, (ids, mask, dec), ours = _reference_logits(name)
    if cfg["family"] == "bart":
        hf_cfg = transformers.BartConfig(**{k: cfg[k] for k in (
            "vocab_size", "d_model", "encoder_layers", "decoder_layers", "encoder_attention_heads",
            "decoder_attention_heads", "encoder_ffn_dim", "decoder_ffn_dim", "max_position_embeddings",
            "activation_function", "scale_embedding", "pad_token_id", "bos_token_id", "eos_token_id",
            "decoder_start_token_id")}, dropout=0.0, attention_dropout=0.0, activation_dropout=0.0)
        model, rename = transformers.BartForConditionalGeneration(hf_cfg), _hf_name_bart
    else:
        hf_cfg = transformers.T5Config(**{k: cfg[k] for k in (
            "vocab_size", "d_model", "d_kv", "d_ff", "num_layers", "num_decoder_layers", "num_heads",
            "relative_attention_num_buckets", "relative_attention_max_distance", "feed_forward_proj",
            "tie_word_embeddings", "layer_norm_epsilon", "pad_token_id", "eos_token_id",
            "decoder_start_token_id")}, dropout_rate=0.0)
        model, rename = transformers.T5ForConditionalGeneration(hf_cfg), _hf_name_t5
    state = {}
    for n, x in w.items():
        x = np.asarray(x)
        if ".*" in n:
            for i in range(x.shape[0]):
                state[rename(n, i)] = torch.tensor(x[i])
        else:
            state[rename(n, None)] = torch.tensor(x.reshape(1, -1) if n == "final_logits_bias" else x)
    missing, unexpected = model.load_state_dict(state, strict=False)
    tied = ("embed_tokens", "lm_head")
    assert not unexpected and all(any(t in m for t in tied) for m in missing), (missing, unexpected)
    model.tie_weights()
    model.eval()
    with torch.no_grad():
        theirs = model(input_ids=torch.tensor(ids, dtype=torch.long), attention_mask=torch.tensor(mask, dtype=torch.long),
                       decoder_input_ids=torch.tensor(dec, dtype=torch.long)).logits.numpy()
    # float32 both sides, different summation orders: 1e-4 of the logits' spread
    assert np.abs(ours - theirs).max() <= 1e-4 * max(1.0, np.abs(theirs).max()), np.abs(ours - theirs).max()


@pytest.mark.parametrize("name", ["bart-test", "t5-test"])
def test_reference_equals_the_programs_model(name):
    from distributed_llms_example_tpu.models import registry

    cfg, ref, w, (ids, mask, dec), ours = _reference_logits(name)
    adapter = spec_mod.load_module("adapters", cfg["family"])
    table = getattr(registry, adapter.REGISTRY_TABLE)
    program.patched_model_config(table[cfg["registry_name"]], adapter.program_config_checks(cfg), {})
    lm = registry.load_model(cfg["registry_name"], dtype=jnp.float32, load_weights=False)
    params = weights.make_program_weights(ref.param_spec(cfg), 3, program.to_program_tree(adapter.leaf_map(cfg)))
    init = jax.eval_shape(lambda: lm.init_params(0))
    assert jax.tree.structure(params) == jax.tree.structure(init)  # the leaf map covers the tree, nothing else
    assert all(a.shape == b.shape for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(init)))
    with jax.default_matmul_precision("highest"):
        theirs = np.asarray(lm.module.apply({"params": params}, jnp.asarray(ids), jnp.asarray(mask), jnp.asarray(dec)))
    assert np.abs(ours - theirs).max() <= 1e-4 * max(1.0, np.abs(theirs).max()), np.abs(ours - theirs).max()


def test_weights_are_a_function_of_the_seed_alone():
    cfg = CFG["bart-test"]
    ref = spec_mod.load_module("reference", "bart")
    a = weights.make_reference_weights(ref.param_spec(cfg), 2**31 + 5)
    b = weights.make_reference_weights(ref.param_spec(cfg), 2**31 + 5)
    c = weights.make_reference_weights(ref.param_spec(cfg), 2**31 + 6)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["shared.weight"], c["shared.weight"])


def test_flop_counts_against_a_hand_count():
    """bart-large-cnn at batch 16, 1024/128: 6*N*tokens for the products plus
    the attention sites, each worked out by hand here."""
    cfg = json.load(open(os.path.join(ROOT, "benchmarks", "configs", "bart-large-cnn.json")))
    f = spec_mod.load_module("flops", "bart")
    b, s, t, d, ffn, v, h, hd = 16, 1024, 128, 1024, 4096, 50265, 16, 64
    enc_tok, dec_tok = b * s, b * t
    products = 6 * (enc_tok * 12 * (4 * d * d + 2 * d * ffn) + enc_tok * 12 * 2 * d * d
                    + dec_tok * 12 * (6 * d * d + 2 * d * ffn) + dec_tok * d * v)
    attn = 12 * 12 * b * h * hd * (s * s + t * s + t * t / 2)
    assert f.train_step_flops(cfg, b, s, t) == pytest.approx(products + attn, rel=1e-12)
    assert f.attention_flops(f.attention_sites(cfg, b, s, t)) == pytest.approx(attn, rel=1e-12)
