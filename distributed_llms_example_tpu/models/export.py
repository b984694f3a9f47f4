"""Framework parameter tree → HF checkpoint export (the reverse of convert.py).

The reference's final artifact is ``model.save_pretrained(output_dir)``
(reference helpers.py:13) — an HF-loadable directory any downstream tool
(transformers, vLLM, the reference itself) can consume.  This module gives
the framework the same exit door: ``save_hf_checkpoint`` writes HF
``config.json`` + ``model.safetensors`` (sharded with an index when large),
with tensor names and layouts exactly inverse to ``convert.py`` — flax
(in, out) kernels transpose back to torch (out, in), stacked Mixtral expert
tensors unstack into per-expert linears, and tied embeddings are emitted
once under their canonical name (transformers re-ties on load).

Round-trip contract (tested in tests/test_export.py): for every family,
``load_model(export_dir)`` reproduces the original logits bit-for-bit, and
``transformers.*.from_pretrained(export_dir)`` loads with no unexpected
keys.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Mapping

import numpy as np

# HF's default shard size; checkpoints above this split into
# model-0000N-of-0000M.safetensors + model.safetensors.index.json (the
# layout _load_local_state_dict already reads back)
MAX_SHARD_BYTES = 5 * 1024**3


def _t(x: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(x.T)


def _flat(tree: Mapping[str, Any], prefix: str = "") -> dict[str, np.ndarray]:
    out: dict[str, np.ndarray] = {}
    for k, v in tree.items():
        p = f"{prefix}/{k}" if prefix else str(k)
        if isinstance(v, Mapping):
            out.update(_flat(v, p))
        else:
            arr = np.asarray(v)
            if arr.dtype not in (np.float32, np.float64):
                arr = arr.astype(np.float32)  # bf16 params → fp32 artifact
            out[p] = arr
    return out


# --- T5 -------------------------------------------------------------------

_T5_MLP_LAYER = {"encoder": 1, "decoder": 2}


def export_t5_state_dict(params: Mapping[str, Any]) -> dict[str, np.ndarray]:
    """Our T5 tree → HF ``T5ForConditionalGeneration`` names (inverse of
    ``convert_t5_state_dict``; encoder layers are [self_attn, mlp], decoder
    layers are [self_attn, cross_attn, mlp])."""
    out: dict[str, np.ndarray] = {}
    for path, arr in _flat(params).items():
        if path == "shared/embedding":
            out["shared.weight"] = arr
            continue
        if path == "lm_head/kernel":  # only present when untied
            out["lm_head.weight"] = _t(arr)
            continue
        m = re.fullmatch(r"(encoder|decoder)/final_norm/scale", path)
        if m:
            out[f"{m.group(1)}.final_layer_norm.weight"] = arr
            continue
        m = re.fullmatch(r"(encoder|decoder)/relative_attention_bias/embedding", path)
        if m:
            out[f"{m.group(1)}.block.0.layer.0.SelfAttention.relative_attention_bias.weight"] = arr
            continue
        m = re.fullmatch(r"(encoder|decoder)/block_(\d+)/(.+)", path)
        if not m:
            raise ValueError(f"unrecognized T5 parameter path: {path}")
        stack, i, rest = m.groups()
        base = f"{stack}.block.{i}.layer"
        m = re.fullmatch(r"self_attn/([qkvo])_proj/kernel", rest)
        if m:
            out[f"{base}.0.SelfAttention.{m.group(1)}.weight"] = _t(arr)
            continue
        if rest == "self_attn_norm/scale":
            out[f"{base}.0.layer_norm.weight"] = arr
            continue
        m = re.fullmatch(r"cross_attn/([qkvo])_proj/kernel", rest)
        if m:
            out[f"{base}.1.EncDecAttention.{m.group(1)}.weight"] = _t(arr)
            continue
        if rest == "cross_attn_norm/scale":
            out[f"{base}.1.layer_norm.weight"] = arr
            continue
        mlp_layer = _T5_MLP_LAYER[stack]
        m = re.fullmatch(r"mlp/(wi|wo|wi_0|wi_1)/kernel", rest)
        if m:
            out[f"{base}.{mlp_layer}.DenseReluDense.{m.group(1)}.weight"] = _t(arr)
            continue
        if rest == "mlp_norm/scale":
            out[f"{base}.{mlp_layer}.layer_norm.weight"] = arr
            continue
        raise ValueError(f"unrecognized T5 parameter path: {path}")
    return out


# --- BART -----------------------------------------------------------------

_BART_ATTN_OUT = {"q_proj": "q_proj", "k_proj": "k_proj", "v_proj": "v_proj", "o_proj": "out_proj"}
_BART_SUB_OUT = {"self_attn": "self_attn", "cross_attn": "encoder_attn"}
_BART_NORM_OUT = {
    "self_attn_layer_norm": "self_attn_layer_norm",
    "cross_attn_layer_norm": "encoder_attn_layer_norm",
    "final_layer_norm": "final_layer_norm",
}


def export_bart_state_dict(params: Mapping[str, Any]) -> dict[str, np.ndarray]:
    """Our BART tree → HF ``BartForConditionalGeneration`` names (inverse
    of ``convert_bart_state_dict``)."""
    out: dict[str, np.ndarray] = {}
    for path, arr in _flat(params).items():
        if path == "shared/embedding":
            out["model.shared.weight"] = arr
            continue
        if path == "final_logits_bias":
            out["final_logits_bias"] = arr.reshape(1, -1)
            continue
        m = re.fullmatch(r"(encoder|decoder)_embed_positions/embedding", path)
        if m:
            out[f"model.{m.group(1)}.embed_positions.weight"] = arr
            continue
        m = re.fullmatch(r"(encoder|decoder)_layernorm_embedding/(scale|bias)", path)
        if m:
            leaf = "weight" if m.group(2) == "scale" else "bias"
            out[f"model.{m.group(1)}.layernorm_embedding.{leaf}"] = arr
            continue
        m = re.fullmatch(r"(encoder|decoder)_block_(\d+)/(.+)", path)
        if not m:
            raise ValueError(f"unrecognized BART parameter path: {path}")
        stack, i, rest = m.groups()
        base = f"model.{stack}.layers.{i}"
        m = re.fullmatch(r"(self_attn|cross_attn)/([qkvo]_proj)/(kernel|bias)", rest)
        if m:
            sub, proj, kind = m.groups()
            leaf = "weight" if kind == "kernel" else "bias"
            val = _t(arr) if kind == "kernel" else arr
            out[f"{base}.{_BART_SUB_OUT[sub]}.{_BART_ATTN_OUT[proj]}.{leaf}"] = val
            continue
        m = re.fullmatch(r"mlp/(fc1|fc2)/(kernel|bias)", rest)
        if m:
            proj, kind = m.groups()
            leaf = "weight" if kind == "kernel" else "bias"
            out[f"{base}.{proj}.{leaf}"] = _t(arr) if kind == "kernel" else arr
            continue
        m = re.fullmatch(
            r"(self_attn_layer_norm|cross_attn_layer_norm|final_layer_norm)/(scale|bias)", rest
        )
        if m:
            norm, kind = m.groups()
            leaf = "weight" if kind == "scale" else "bias"
            out[f"{base}.{_BART_NORM_OUT[norm]}.{leaf}"] = arr
            continue
        raise ValueError(f"unrecognized BART parameter path: {path}")
    return out


# --- LLaMA / Mixtral ------------------------------------------------------

_MIXTRAL_W = {"gate_proj": "w1", "up_proj": "w3", "down_proj": "w2"}


def export_llama_state_dict(params: Mapping[str, Any]) -> dict[str, np.ndarray]:
    """Our LLaMA/Mixtral tree → HF ``LlamaForCausalLM`` /
    ``MixtralForCausalLM`` names (inverse of ``convert_llama_state_dict``).
    Stacked (E, d_in, d_out) expert tensors unstack into per-expert
    ``block_sparse_moe.experts.{j}.w{1,2,3}`` linears."""
    out: dict[str, np.ndarray] = {}
    for path, arr in _flat(params).items():
        if path == "embed_tokens/embedding":
            out["model.embed_tokens.weight"] = arr
            continue
        if path == "final_norm/scale":
            out["model.norm.weight"] = arr
            continue
        if path == "lm_head/kernel":
            out["lm_head.weight"] = _t(arr)
            continue
        m = re.fullmatch(r"block_(\d+)/(.+)", path)
        if not m:
            raise ValueError(f"unrecognized LLaMA parameter path: {path}")
        i, rest = m.groups()
        base = f"model.layers.{i}"
        m = re.fullmatch(r"self_attn/([qkvo])_proj/kernel", rest)
        if m:
            out[f"{base}.self_attn.{m.group(1)}_proj.weight"] = _t(arr)
            continue
        m = re.fullmatch(r"mlp/(gate_proj|up_proj|down_proj)(/kernel)?", rest)
        if m:
            name, is_dense = m.group(1), m.group(2) is not None
            if is_dense:
                out[f"{base}.mlp.{name}.weight"] = _t(arr)
            else:  # stacked experts: (E, d_in, d_out)
                for j in range(arr.shape[0]):
                    out[f"{base}.block_sparse_moe.experts.{j}.{_MIXTRAL_W[name]}.weight"] = _t(arr[j])
            continue
        if rest == "mlp/router/kernel":
            out[f"{base}.block_sparse_moe.gate.weight"] = _t(arr)
            continue
        if rest == "attn_norm/scale":
            out[f"{base}.input_layernorm.weight"] = arr
            continue
        if rest == "mlp_norm/scale":
            out[f"{base}.post_attention_layernorm.weight"] = arr
            continue
        raise ValueError(f"unrecognized LLaMA parameter path: {path}")
    return out


_LFM2_DENSE = {"gate_proj": "w1", "up_proj": "w3", "down_proj": "w2"}
_LFM2_LEAVES = {
    "operator_norm/scale": ("operator_norm.weight", False),
    "ffn_norm/scale": ("ffn_norm.weight", False),
    "conv/in_proj/kernel": ("conv.in_proj.weight", True),
    "conv/out_proj/kernel": ("conv.out_proj.weight", True),
    "self_attn/q_proj/kernel": ("self_attn.q_proj.weight", True),
    "self_attn/k_proj/kernel": ("self_attn.k_proj.weight", True),
    "self_attn/v_proj/kernel": ("self_attn.v_proj.weight", True),
    "self_attn/o_proj/kernel": ("self_attn.out_proj.weight", True),
    "self_attn/q_norm/scale": ("self_attn.q_layernorm.weight", False),
    "self_attn/k_norm/scale": ("self_attn.k_layernorm.weight", False),
    "mlp/router/kernel": ("feed_forward.gate.weight", True),
    "mlp/expert_bias": ("feed_forward.expert_bias", False),
}


def export_lfm2_state_dict(params: Mapping[str, Any]) -> dict[str, np.ndarray]:
    """Our LFM2-MoE tree (models/lfm2.py) → HF ``Lfm2MoeForCausalLM`` names:
    projections transposed to (out, in), the depthwise taps as a Conv1d
    weight (channels, 1, taps), stacked experts unstacked into
    ``feed_forward.experts.{j}.w{1,2,3}``; the head is tied, so there is no
    ``lm_head``."""
    out: dict[str, np.ndarray] = {}
    for path, arr in _flat(params).items():
        if path == "embed_tokens/embedding":
            out["model.embed_tokens.weight"] = arr
            continue
        if path == "final_norm/scale":
            out["model.embedding_norm.weight"] = arr
            continue
        m = re.fullmatch(r"block_(\d+)/(.+)", path)
        if not m:
            raise ValueError(f"unrecognized LFM2 parameter path: {path}")
        base, rest = f"model.layers.{m.group(1)}", m.group(2)
        if rest in _LFM2_LEAVES:
            name, transpose = _LFM2_LEAVES[rest]
            out[f"{base}.{name}"] = _t(arr) if transpose else arr
            continue
        if rest == "conv/conv_weight":
            out[f"{base}.conv.conv.weight"] = np.ascontiguousarray(arr[:, None, :])
            continue
        m = re.fullmatch(r"mlp/(gate_proj|up_proj|down_proj)(/kernel)?", rest)
        if not m:
            raise ValueError(f"unrecognized LFM2 parameter path: {path}")
        name = _LFM2_DENSE[m.group(1)]
        if m.group(2) is not None:  # a leading dense layer
            out[f"{base}.feed_forward.{name}.weight"] = _t(arr)
        else:  # stacked experts: (E, d_in, d_out)
            for j in range(arr.shape[0]):
                out[f"{base}.feed_forward.experts.{j}.{name}.weight"] = _t(arr[j])
    return out


EXPORTERS = {
    "lfm2": export_lfm2_state_dict,
    "t5": export_t5_state_dict,
    "bart": export_bart_state_dict,
    "llama": export_llama_state_dict,
    "mixtral": export_llama_state_dict,
}


# --- HF config.json -------------------------------------------------------


def hf_config_dict(family: str, cfg: Any) -> dict:
    """Framework config dataclass → the HF ``config.json`` fields that
    ``transformers`` needs to reconstruct the architecture (the same
    fields registry._*_from_hf_config reads, so the round trip is exact)."""
    if family == "t5":
        return {
            "model_type": "t5",
            "architectures": ["T5ForConditionalGeneration"],
            "is_encoder_decoder": True,
            "vocab_size": cfg.vocab_size,
            "d_model": cfg.d_model,
            "d_kv": cfg.d_kv,
            "d_ff": cfg.d_ff,
            "num_layers": cfg.num_layers,
            "num_decoder_layers": cfg.num_decoder_layers or cfg.num_layers,
            "num_heads": cfg.num_heads,
            "relative_attention_num_buckets": cfg.relative_attention_num_buckets,
            "relative_attention_max_distance": cfg.relative_attention_max_distance,
            "dropout_rate": cfg.dropout_rate,
            "layer_norm_epsilon": cfg.layer_norm_epsilon,
            "feed_forward_proj": cfg.feed_forward_proj,
            "tie_word_embeddings": cfg.tie_word_embeddings,
            "pad_token_id": cfg.pad_token_id,
            "eos_token_id": cfg.eos_token_id,
            "decoder_start_token_id": cfg.decoder_start_token_id,
        }
    if family == "bart":
        return {
            "model_type": "bart",
            "architectures": ["BartForConditionalGeneration"],
            "is_encoder_decoder": True,
            "vocab_size": cfg.vocab_size,
            "d_model": cfg.d_model,
            "encoder_layers": cfg.encoder_layers,
            "decoder_layers": cfg.decoder_layers,
            "encoder_attention_heads": cfg.encoder_attention_heads,
            "decoder_attention_heads": cfg.decoder_attention_heads,
            "encoder_ffn_dim": cfg.encoder_ffn_dim,
            "decoder_ffn_dim": cfg.decoder_ffn_dim,
            "max_position_embeddings": cfg.max_position_embeddings,
            "dropout": cfg.dropout_rate,
            "attention_dropout": cfg.attn_dropout_rate,
            "scale_embedding": cfg.scale_embedding,
            "pad_token_id": cfg.pad_token_id,
            "bos_token_id": cfg.bos_token_id,
            "eos_token_id": cfg.eos_token_id,
            "decoder_start_token_id": cfg.decoder_start_token_id,
            "forced_bos_token_id": cfg.forced_bos_token_id,
            "forced_eos_token_id": cfg.forced_eos_token_id,
        }
    if family in ("llama", "mixtral"):
        is_moe = getattr(cfg, "num_experts", 0) > 0
        out = {
            "model_type": "mixtral" if is_moe else "llama",
            "architectures": ["MixtralForCausalLM" if is_moe else "LlamaForCausalLM"],
            "vocab_size": cfg.vocab_size,
            "hidden_size": cfg.hidden_size,
            "intermediate_size": cfg.intermediate_size,
            "num_hidden_layers": cfg.num_hidden_layers,
            "num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_key_value_heads or cfg.num_attention_heads,
            "max_position_embeddings": cfg.max_position_embeddings,
            "attention_dropout": cfg.attn_dropout_rate,
            "rms_norm_eps": cfg.rms_norm_eps,
            "rope_theta": cfg.rope_theta,
            "tie_word_embeddings": False,
            "pad_token_id": cfg.pad_token_id,
            "bos_token_id": cfg.bos_token_id,
            "eos_token_id": cfg.eos_token_id,
        }
        if is_moe:
            out["num_local_experts"] = cfg.num_experts
            out["num_experts_per_tok"] = cfg.num_experts_per_tok
            out["router_aux_loss_coef"] = cfg.moe_aux_weight
        return out
    if family == "lfm2":
        return {
            "model_type": "lfm2_moe",
            "architectures": ["Lfm2MoeForCausalLM"],
            **{k: getattr(cfg, k) for k in (
                "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_hidden_layers", "num_dense_layers", "num_attention_heads", "num_key_value_heads",
                "conv_L_cache", "num_experts", "num_experts_per_tok", "norm_topk_prob", "use_expert_bias",
                "routed_scaling_factor", "norm_eps", "rope_theta", "max_position_embeddings",
                "pad_token_id", "bos_token_id", "eos_token_id",
            )},
            "layer_types": list(cfg.layer_types),
            "conv_bias": False,
            "tie_word_embeddings": True,
        }
    raise ValueError(f"no HF config export for family {family!r}")


# --- checkpoint writer ----------------------------------------------------


def save_hf_checkpoint(out_dir: str, family: str, cfg: Any, params: Mapping[str, Any]) -> None:
    """Write ``config.json`` + ``model.safetensors`` (sharded + indexed
    above MAX_SHARD_BYTES, HF's file layout) to ``out_dir``."""
    from safetensors.numpy import save_file  # ships with transformers

    os.makedirs(out_dir, exist_ok=True)
    state = EXPORTERS[family](params)
    with open(os.path.join(out_dir, "config.json"), "w") as f:
        json.dump(hf_config_dict(family, cfg), f, indent=2, sort_keys=True)

    total = sum(a.nbytes for a in state.values())
    if total <= MAX_SHARD_BYTES:
        save_file(state, os.path.join(out_dir, "model.safetensors"), metadata={"format": "pt"})
        return
    # size-based sharding, preserving insertion order
    shards: list[dict[str, np.ndarray]] = [{}]
    size = 0
    for name, arr in state.items():
        if size + arr.nbytes > MAX_SHARD_BYTES and shards[-1]:
            shards.append({})
            size = 0
        shards[-1][name] = arr
        size += arr.nbytes
    n = len(shards)
    weight_map: dict[str, str] = {}
    for k, shard in enumerate(shards, start=1):
        fname = f"model-{k:05d}-of-{n:05d}.safetensors"
        save_file(shard, os.path.join(out_dir, fname), metadata={"format": "pt"})
        for name in shard:
            weight_map[name] = fname
    with open(os.path.join(out_dir, "model.safetensors.index.json"), "w") as f:
        json.dump({"metadata": {"total_size": total}, "weight_map": weight_map}, f, indent=2)
