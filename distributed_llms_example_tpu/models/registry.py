"""Model registry: named configs + checkpoint loading.

Stands in for ``AutoModelForSeq2SeqLM.from_pretrained(model_ckpt)``
(reference train-torchrun.py:35): a name resolves to (a) a built-in config
— sized to match the public checkpoints — plus random init, or (b) a local
directory containing HF ``config.json`` + ``pytorch_model.bin`` /
``model.safetensors``, which is converted into framework params.  There is
no network path at all (the image has zero egress; weight download is the
platform's job, mirroring how the reference receives datasets as Valohai
inputs).
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any

import jax
import jax.numpy as jnp

from distributed_llms_example_tpu.models import t5 as t5_mod
from distributed_llms_example_tpu.models.bart import BartConfig, BartForConditionalGeneration
from distributed_llms_example_tpu.models.brumby import BrumbyConfig, BrumbyForCausalLM
from distributed_llms_example_tpu.models.falcon_h1 import FalconH1Config, FalconH1ForCausalLM
from distributed_llms_example_tpu.models.convert import convert_state_dict
from distributed_llms_example_tpu.models.lfm2 import Lfm2Config, Lfm2ForCausalLM
from distributed_llms_example_tpu.models.mellum import MellumConfig, MellumForCausalLM
from distributed_llms_example_tpu.ops.mha import YarnRope
from distributed_llms_example_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from distributed_llms_example_tpu.models.t5 import T5Config, T5ForConditionalGeneration

# Built-in configs sized like the public checkpoints (dims from the public
# HF config.json files; no weights are bundled).
T5_CONFIGS: dict[str, T5Config] = {
    "t5-test": T5Config(vocab_size=256, d_model=64, d_kv=16, d_ff=128, num_layers=2, num_heads=4),
    "t5-small": T5Config(d_model=512, d_kv=64, d_ff=2048, num_layers=6, num_heads=8),
    "t5-base": T5Config(d_model=768, d_kv=64, d_ff=3072, num_layers=12, num_heads=12),
    "t5-large": T5Config(d_model=1024, d_kv=64, d_ff=4096, num_layers=24, num_heads=16),
    "flan-t5-xl": T5Config(
        d_model=2048,
        d_kv=64,
        d_ff=5120,
        num_layers=24,
        num_heads=32,
        feed_forward_proj="gated-gelu",
        tie_word_embeddings=False,
    ),
}

BART_CONFIGS: dict[str, BartConfig] = {
    "bart-test": BartConfig(
        vocab_size=256, d_model=64, encoder_layers=2, decoder_layers=2,
        encoder_attention_heads=4, decoder_attention_heads=4,
        encoder_ffn_dim=128, decoder_ffn_dim=128, max_position_embeddings=128,
        forced_bos_token_id=0,
    ),
    "bart-base": BartConfig(
        d_model=768, encoder_layers=6, decoder_layers=6,
        encoder_attention_heads=12, decoder_attention_heads=12,
        encoder_ffn_dim=3072, decoder_ffn_dim=3072,
    ),
    # the reference's default model (reference valohai.yaml:10)
    "bart-large-cnn": BartConfig(forced_bos_token_id=0),
    "bart-large": BartConfig(),
}

LLAMA_CONFIGS: dict[str, LlamaConfig] = {
    "llama-test": LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128,
    ),
    # 4/8 layers: enough depth for stage x virtual_stages interleaved-
    # pipeline tests (llama-test's 2 layers only split into 2 plain stages)
    "llama-test-4l": LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128,
    ),
    "llama-test-8l": LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128,
    ),
    "llama-2-7b": LlamaConfig(),
    "llama-2-13b": LlamaConfig(
        hidden_size=5120, intermediate_size=13824, num_hidden_layers=40, num_attention_heads=40
    ),
    # Mixtral-class sparse MoE (ops/moe.py): LLaMA blocks with top-2 routed
    # expert MLPs, experts sharded over ``tensor`` (expert parallelism)
    "mixtral-test": LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128,
        num_experts=4, num_experts_per_tok=2, moe_aux_weight=0.01,
    ),
    # 4 layers: MoE × interleaved pipeline tests need stage=2 × v=2 chunks
    "mixtral-test-4l": LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128,
        num_experts=4, num_experts_per_tok=2, moe_aux_weight=0.01,
    ),
    "mixtral-8x7b": LlamaConfig(
        hidden_size=4096, intermediate_size=14336, num_hidden_layers=32,
        num_attention_heads=32, num_key_value_heads=8, vocab_size=32000,
        max_position_embeddings=32768, rope_theta=1e6,
        num_experts=8, num_experts_per_tok=2, moe_aux_weight=0.02,
    ),
}


# LFM2-MoE (models/lfm2.py): gated short convolutions and GQA by layer, a
# sigmoid-routed expert layer after the leading dense ones.  Sizes from
# LiquidAI/LFM2-8B-A1B's config.json; pad/eos are the byte tokenizer's (no
# tokenizer files offline).
LFM2_CONFIGS: dict[str, Lfm2Config] = {
    # every layer kind in five layers: conv+dense, attention+experts, conv+experts
    "lfm2-moe-test": Lfm2Config(
        vocab_size=256, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
        num_hidden_layers=5,
        layer_types=("conv", "full_attention", "conv", "conv", "full_attention"),
        num_dense_layers=1, num_attention_heads=4, num_key_value_heads=2,
        num_experts=8, num_experts_per_tok=4, max_position_embeddings=256,
        param_dtype=None,
    ),
    "lfm2-8b-a1b": Lfm2Config(),
}


# Brumby (models/brumby.py): every layer a power-retention layer, whose whole
# memory is a fixed-size matrix state a KV head (no K/V).  Sizes from
# manifestai/Brumby-14B-Base's config.json; pad/eos are the byte tokenizer's.
BRUMBY_CONFIGS: dict[str, BrumbyConfig] = {
    # 5 query heads a KV head, as published; head_dim 16 (the state is 9 x 16 x 16 a KV head)
    "brumby-test": BrumbyConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
        num_attention_heads=10, num_key_value_heads=2, head_dim=16,
        max_position_embeddings=256, param_dtype=None,
    ),
    "brumby-14b": BrumbyConfig(),
}


# Mellum (models/mellum.py): three sliding-window layers to one full layer, each
# kind with its own rotation, 64 softmax-routed experts in every layer.  Sizes
# from JetBrains/Mellum2-12B-A2.5B-Instruct's config.json; pad/eos are the byte
# tokenizer's.
MELLUM_CONFIGS: dict[str, MellumConfig] = {
    # one period, a window a prompt of 3 windows wraps twice, 4 query heads a KV head
    "mellum-test": MellumConfig(
        vocab_size=256, hidden_size=64, moe_intermediate_size=32, num_hidden_layers=4,
        layer_types=("sliding_attention",) * 3 + ("full_attention",),
        num_attention_heads=8, num_key_value_heads=2, head_dim=16, sliding_window=16,
        num_experts=8, num_experts_per_tok=2, max_position_embeddings=512,
        rope_yarn=YarnRope(factor=16.0, original_max_position_embeddings=32, attention_factor=1.2772588722239782),
        param_dtype=None,
    ),
    "mellum2-12b-a2.5b": MellumConfig(),
}


# Falcon-H1 (models/falcon_h1.py): a Mamba-2 mixer and GQA side by side in every
# block, so a layer's cache entry holds K/V, a matrix state and convolution taps.
# Sizes and the µP multipliers from tiiuae/Falcon-H1-34B-Instruct's config.json;
# pad/eos are the byte tokenizer's.
FALCON_H1_CONFIGS: dict[str, FalconH1Config] = {
    # 2 groups of 2 mixer heads, 5 query heads a KV head as published; every
    # multiplier differs from 1 (the published ones are kept)
    "falcon-h1-test": FalconH1Config(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
        num_attention_heads=10, num_key_value_heads=2, head_dim=16,
        mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16, mamba_d_state=32, mamba_n_groups=2,
        mamba_chunk_size=16, max_position_embeddings=256, attention_in_multiplier=0.8, param_dtype=None,
    ),
    "falcon-h1-34b": FalconH1Config(),
}


@dataclasses.dataclass
class LoadedModel:
    family: str
    config: Any
    module: Any  # the flax module (not bound)
    params: Any | None  # None until init_params/load
    is_seq2seq: bool = True

    def init_params(self, rng: jax.Array | int = 0) -> Any:
        if isinstance(rng, int):
            rng = jax.random.PRNGKey(rng)
        if self.is_seq2seq:
            dummy = jnp.ones((1, 8), jnp.int32)
            variables = self.module.init(rng, dummy, jnp.ones_like(dummy), dummy)
        else:
            dummy = jnp.ones((1, 8), jnp.int32)
            variables = self.module.init(rng, dummy)
        return variables["params"]


def _t5_from_hf_config(cfg: dict) -> T5Config:
    return T5Config(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["d_model"],
        d_kv=cfg["d_kv"],
        d_ff=cfg["d_ff"],
        num_layers=cfg["num_layers"],
        num_decoder_layers=cfg.get("num_decoder_layers"),
        num_heads=cfg["num_heads"],
        relative_attention_num_buckets=cfg.get("relative_attention_num_buckets", 32),
        relative_attention_max_distance=cfg.get("relative_attention_max_distance", 128),
        dropout_rate=cfg.get("dropout_rate", 0.1),
        layer_norm_epsilon=cfg.get("layer_norm_epsilon", 1e-6),
        feed_forward_proj=cfg.get("feed_forward_proj", "relu").replace("gated-gelu_new", "gated-gelu"),
        tie_word_embeddings=cfg.get("tie_word_embeddings", True),
        pad_token_id=cfg.get("pad_token_id", 0),
        eos_token_id=cfg.get("eos_token_id", 1),
        decoder_start_token_id=cfg.get("decoder_start_token_id", 0),
    )


def _load_local_state_dict(path: str) -> dict:
    # sharded layouts first: large checkpoints (7B+, mixtral-8x7b) are always
    # shipped as model-0000N-of-000NN files plus an index json
    for index_name, loader in (
        ("model.safetensors.index.json", "safetensors"),
        ("pytorch_model.bin.index.json", "torch"),
    ):
        index_path = os.path.join(path, index_name)
        if not os.path.exists(index_path):
            continue
        with open(index_path) as f:
            weight_map = json.load(f)["weight_map"]
        out: dict = {}
        for shard in sorted(set(weight_map.values())):
            shard_path = os.path.join(path, shard)
            if loader == "safetensors":
                from safetensors.numpy import load_file  # ships with transformers

                out.update(load_file(shard_path))
            else:
                import torch

                out.update(torch.load(shard_path, map_location="cpu", weights_only=True))
        return out
    st_path = os.path.join(path, "model.safetensors")
    if os.path.exists(st_path):
        from safetensors.numpy import load_file  # ships with transformers

        return dict(load_file(st_path))
    bin_path = os.path.join(path, "pytorch_model.bin")
    if os.path.exists(bin_path):
        import torch

        return torch.load(bin_path, map_location="cpu", weights_only=True)
    raise FileNotFoundError(
        f"no model.safetensors(.index.json) or pytorch_model.bin(.index.json) under {path}"
    )


def _bart_from_hf_config(cfg: dict) -> BartConfig:
    return BartConfig(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["d_model"],
        encoder_layers=cfg["encoder_layers"],
        decoder_layers=cfg["decoder_layers"],
        encoder_attention_heads=cfg["encoder_attention_heads"],
        decoder_attention_heads=cfg["decoder_attention_heads"],
        encoder_ffn_dim=cfg["encoder_ffn_dim"],
        decoder_ffn_dim=cfg["decoder_ffn_dim"],
        max_position_embeddings=cfg.get("max_position_embeddings", 1024),
        dropout_rate=cfg.get("dropout", 0.1),
        # HF probs dropout (bart-large ships 0.0); rides the flash
        # kernels' in-kernel mask stream when a checkpoint sets it
        attn_dropout_rate=cfg.get("attention_dropout", 0.0),
        scale_embedding=cfg.get("scale_embedding", False),
        pad_token_id=cfg.get("pad_token_id", 1),
        bos_token_id=cfg.get("bos_token_id", 0),
        eos_token_id=cfg.get("eos_token_id", 2),
        decoder_start_token_id=cfg.get("decoder_start_token_id", 2),
        forced_bos_token_id=cfg.get("forced_bos_token_id"),
        forced_eos_token_id=cfg.get("forced_eos_token_id"),
    )


def _llama_from_hf_config(cfg: dict) -> LlamaConfig:
    return LlamaConfig(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg.get("num_key_value_heads"),
        max_position_embeddings=cfg.get("max_position_embeddings", 4096),
        attn_dropout_rate=cfg.get("attention_dropout", 0.0),
        rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
        rope_theta=cfg.get("rope_theta", 10000.0),
        pad_token_id=cfg.get("pad_token_id") or 0,
        bos_token_id=cfg.get("bos_token_id", 1),
        eos_token_id=cfg.get("eos_token_id", 2),
    )


def _build(family: str, cfg: Any, dtype: jnp.dtype, remat: bool, params: Any = None,
           remat_policy: str = "full") -> LoadedModel:
    if family == "t5":
        module = T5ForConditionalGeneration(cfg, dtype=dtype, remat=remat, remat_policy=remat_policy)
        return LoadedModel("t5", cfg, module, params, is_seq2seq=True)
    if family == "bart":
        module = BartForConditionalGeneration(cfg, dtype=dtype, remat=remat, remat_policy=remat_policy)
        return LoadedModel("bart", cfg, module, params, is_seq2seq=True)
    if family in ("llama", "mixtral"):  # mixtral = llama blocks + MoE MLP
        module = LlamaForCausalLM(cfg, dtype=dtype, remat=remat, remat_policy=remat_policy)
        return LoadedModel("llama", cfg, module, params, is_seq2seq=False)
    if family == "lfm2":
        module = Lfm2ForCausalLM(cfg, dtype=dtype, remat=remat, remat_policy=remat_policy)
        return LoadedModel("lfm2", cfg, module, params, is_seq2seq=False)
    if family == "brumby":
        module = BrumbyForCausalLM(cfg, dtype=dtype, remat=remat, remat_policy=remat_policy)
        return LoadedModel("brumby", cfg, module, params, is_seq2seq=False)
    if family == "mellum":
        module = MellumForCausalLM(cfg, dtype=dtype, remat=remat, remat_policy=remat_policy)
        return LoadedModel("mellum", cfg, module, params, is_seq2seq=False)
    if family == "falcon_h1":
        module = FalconH1ForCausalLM(cfg, dtype=dtype, remat=remat, remat_policy=remat_policy)
        return LoadedModel("falcon_h1", cfg, module, params, is_seq2seq=False)
    raise ValueError(f"unsupported model family {family!r}")


def _mixtral_from_hf_config(cfg: dict) -> LlamaConfig:
    base = _llama_from_hf_config(cfg)
    return dataclasses.replace(
        base,
        num_experts=cfg.get("num_local_experts", 8),
        num_experts_per_tok=cfg.get("num_experts_per_tok", 2),
        # HF MixtralConfig default; a larger fallback would silently apply
        # stronger load-balance pressure than the same checkpoint under HF
        moe_aux_weight=cfg.get("router_aux_loss_coef", 0.001),
        # HF routes densely (no capacity limit): <=0 = no-drop everywhere,
        # so converted checkpoints reproduce HF logits on every path
        moe_capacity_factor=-1.0,
    )


_HF_CONFIG_PARSERS = {
    "t5": _t5_from_hf_config,
    "bart": _bart_from_hf_config,
    "llama": _llama_from_hf_config,
    "mixtral": _mixtral_from_hf_config,
}


def load_model(
    name_or_path: str,
    *,
    dtype: jnp.dtype = jnp.float32,
    remat: bool = False,
    remat_policy: str = "full",
    load_weights: bool = True,
    attention_impl: str | None = None,
    moe_capacity_factor: float | None = None,
    fused_ce: bool | None = None,
) -> LoadedModel:
    """Resolve a model name or local HF checkpoint dir into a LoadedModel.

    ``attention_impl`` overrides the config's attention path ("auto" /
    "flash" / "ring" / "xla", see ops/mha.py) for every family.  T5's
    learned relative-position bias rides the flash kernel's differentiable
    ``relative_bias`` input (a per-diagonal vector) on any mesh (multi-device
    via the sharded path whose hand-written vjp psums the bias's diagonal
    sums across batch shards); T5
    cross-attention takes the same flash/ring paths as BART/LLaMA.

    ``moe_capacity_factor`` overrides the MoE expert capacity factor for
    models that have experts.  HF-converted Mixtral checkpoints default to
    no-drop routing (<= 0) for exact logit parity with HF, but no-drop
    sizes the dispatch tensors at capacity = group_size — a memory cliff
    at fine-tune batch/length.  Passing e.g. 1.25 here restores the
    standard capacity-factor trade for training while leaving parity
    evals (which load without the override) exact.
    """
    if attention_impl not in (None, "auto", "flash", "ring", "xla"):
        raise ValueError(
            f"attention_impl={attention_impl!r}: must be 'auto', 'flash', 'ring', or 'xla'"
        )

    def _apply_impl(cfg):
        if attention_impl is not None and hasattr(cfg, "attention_impl"):
            cfg = dataclasses.replace(cfg, attention_impl=attention_impl)
        if (
            moe_capacity_factor is not None
            and getattr(cfg, "num_experts", 0) > 0
            and hasattr(cfg, "moe_capacity_factor")  # LFM2's experts never drop
        ):
            cfg = dataclasses.replace(cfg, moe_capacity_factor=moe_capacity_factor)
        if fused_ce is not None and hasattr(cfg, "fused_ce"):
            # vocab-chunked LM-head + CE (ops/blockwise_ce.py); causal
            # families only — seq2seq configs have no such field
            cfg = dataclasses.replace(cfg, fused_ce=fused_ce)
        return cfg

    if os.path.isdir(name_or_path):
        with open(os.path.join(name_or_path, "config.json")) as f:
            hf_cfg = json.load(f)
        model_type = hf_cfg.get("model_type", "t5")
        if model_type not in _HF_CONFIG_PARSERS:
            raise ValueError(f"unsupported model_type {model_type!r} at {name_or_path}")
        cfg = _apply_impl(_HF_CONFIG_PARSERS[model_type](hf_cfg))
        params = None
        if load_weights:
            params = convert_state_dict(model_type, _load_local_state_dict(name_or_path))
            params = jax.tree.map(jnp.asarray, params)
        return _build(model_type, cfg, dtype, remat, params, remat_policy=remat_policy)
    # short names: strip org prefixes like "google/" or "facebook/"
    short = name_or_path.rsplit("/", 1)[-1]
    if short in T5_CONFIGS:
        return _build("t5", _apply_impl(T5_CONFIGS[short]), dtype, remat, remat_policy=remat_policy)
    if short in BART_CONFIGS:
        return _build("bart", _apply_impl(BART_CONFIGS[short]), dtype, remat, remat_policy=remat_policy)
    if short in LLAMA_CONFIGS:
        return _build("llama", _apply_impl(LLAMA_CONFIGS[short]), dtype, remat, remat_policy=remat_policy)
    if short in LFM2_CONFIGS:
        return _build("lfm2", _apply_impl(LFM2_CONFIGS[short]), dtype, remat, remat_policy=remat_policy)
    if short in BRUMBY_CONFIGS:
        return _build("brumby", _apply_impl(BRUMBY_CONFIGS[short]), dtype, remat, remat_policy=remat_policy)
    if short in MELLUM_CONFIGS:
        return _build("mellum", _apply_impl(MELLUM_CONFIGS[short]), dtype, remat, remat_policy=remat_policy)
    if short in FALCON_H1_CONFIGS:
        return _build("falcon_h1", _apply_impl(FALCON_H1_CONFIGS[short]), dtype, remat, remat_policy=remat_policy)
    known = (sorted(T5_CONFIGS) + sorted(BART_CONFIGS) + sorted(LLAMA_CONFIGS) + sorted(LFM2_CONFIGS)
             + sorted(BRUMBY_CONFIGS) + sorted(MELLUM_CONFIGS) + sorted(FALCON_H1_CONFIGS))
    raise ValueError(
        f"unknown model {name_or_path!r}: not a local checkpoint dir and not one of {known}"
    )


__all__ = [
    "LoadedModel",
    "load_model",
    "T5_CONFIGS",
    "BART_CONFIGS",
    "LLAMA_CONFIGS",
    "LFM2_CONFIGS",
    "BRUMBY_CONFIGS",
    "MELLUM_CONFIGS",
    "FALCON_H1_CONFIGS",
    "t5_mod",
]
