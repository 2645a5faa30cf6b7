"""Hugging Face safetensors -> param-pytree conversion (no torch in the path).

Replaces the reference's ``AutoModel.from_pretrained`` weight loading (reference:
assistant/ai/embedders/transformers.py:12-13, providers/transformers.py:22-29) with a
direct safetensors->numpy->jax route: weights are read shard by shard, transposed to
our [in, out] einsum convention, stacked along the leading layer axis (scan layout),
cast to the target dtype on host, then sharded onto the mesh in one ``device_put``
(:func:`..parallel.sharding.shard_pytree`).

Supported decoder families: Llama-3/-3.1 / Mistral (sliding window), Qwen2
(qkv biases, optional windowing), Gemma-1 (GeGLU, (1+w) norm fold in f32,
scaled embeddings), Phi-3 (fused qkv / gate_up split at load, longrope),
Mixtral MoE.  Rope scalings: llama3, linear, longrope (Phi-3 128k), yarn.
Encoders: BERT (ruBert-base / MiniLM).  Unknown decoder model_types and
unsupported rope_scaling types are rejected rather than silently mis-loaded
(gemma-2/3 add norms this mapping does not carry).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

import numpy as np

from .config import DecoderConfig, EncoderConfig


def _read_safetensors(model_dir: str) -> Dict[str, np.ndarray]:
    from safetensors import safe_open

    tensors: Dict[str, np.ndarray] = {}
    files = sorted(
        f for f in os.listdir(model_dir) if f.endswith(".safetensors")
    )
    if not files:
        raise FileNotFoundError(f"no .safetensors files in {model_dir}")
    for fname in files:
        with safe_open(os.path.join(model_dir, fname), framework="np") as f:
            for key in f.keys():
                tensors[key] = f.get_tensor(key)
    return tensors


def read_hf_config(model_dir: str) -> Dict[str, Any]:
    with open(os.path.join(model_dir, "config.json")) as f:
        return json.load(f)


def _stack(tensors: Dict[str, np.ndarray], fmt: str, n: int, *, T: bool = False, dtype=None) -> np.ndarray:
    """Stack per-layer tensors fmt.format(i) into [n, ...]; T transposes each."""
    mats = []
    for i in range(n):
        t = tensors[fmt.format(i)]
        mats.append(t.T if T else t)
    out = np.stack(mats)
    return out.astype(dtype) if dtype is not None else out


def load_encoder(model_dir: str, dtype=None) -> tuple[EncoderConfig, Dict[str, Any]]:
    """Load a BERT-family checkpoint directory -> (EncoderConfig, params)."""
    import jax.numpy as jnp

    dtype = dtype or jnp.bfloat16
    hf = read_hf_config(model_dir)
    cfg = EncoderConfig.from_hf(hf, dtype=dtype)
    t = _read_safetensors(model_dir)
    # strip optional "bert." prefix
    if any(k.startswith("bert.") for k in t):
        t = {k[len("bert."):] if k.startswith("bert.") else k: v for k, v in t.items()}
    L = cfg.num_layers
    pre = "encoder.layer.{}."
    params = {
        "tok_embed": t["embeddings.word_embeddings.weight"],
        "pos_embed": t["embeddings.position_embeddings.weight"],
        "type_embed": t["embeddings.token_type_embeddings.weight"],
        "embed_ln_w": t["embeddings.LayerNorm.weight"],
        "embed_ln_b": t["embeddings.LayerNorm.bias"],
        "layers": {
            "wq": _stack(t, pre + "attention.self.query.weight", L, T=True),
            "bq": _stack(t, pre + "attention.self.query.bias", L),
            "wk": _stack(t, pre + "attention.self.key.weight", L, T=True),
            "bk": _stack(t, pre + "attention.self.key.bias", L),
            "wv": _stack(t, pre + "attention.self.value.weight", L, T=True),
            "bv": _stack(t, pre + "attention.self.value.bias", L),
            "wo": _stack(t, pre + "attention.output.dense.weight", L, T=True),
            "bo": _stack(t, pre + "attention.output.dense.bias", L),
            "attn_ln_w": _stack(t, pre + "attention.output.LayerNorm.weight", L),
            "attn_ln_b": _stack(t, pre + "attention.output.LayerNorm.bias", L),
            "w1": _stack(t, pre + "intermediate.dense.weight", L, T=True),
            "b1": _stack(t, pre + "intermediate.dense.bias", L),
            "w2": _stack(t, pre + "output.dense.weight", L, T=True),
            "b2": _stack(t, pre + "output.dense.bias", L),
            "mlp_ln_w": _stack(t, pre + "output.LayerNorm.weight", L),
            "mlp_ln_b": _stack(t, pre + "output.LayerNorm.bias", L),
        },
    }
    params = _to_jax(params, dtype)
    return cfg, params


# families whose tensors AND math this loader maps faithfully; anything else
# (e.g. gemma2's extra pre/post_feedforward norms) would load without error but
# produce silently wrong logits, so it is rejected up front
_SUPPORTED_DECODERS = {"llama", "mistral", "mixtral", "qwen2", "gemma", "phi3"}


def load_decoder(model_dir: str, dtype=None) -> tuple[DecoderConfig, Dict[str, Any]]:
    """Load a Llama/Qwen2/Gemma/Mixtral checkpoint dir -> (DecoderConfig, params)."""
    import jax.numpy as jnp

    dtype = dtype or jnp.bfloat16
    hf = read_hf_config(model_dir)
    model_type = hf.get("model_type")
    if model_type in ("axk1", "deepseek_v3") or hf.get("kv_lora_rank"):
        raise ValueError(
            f"model_type {model_type!r}: loading a latent-attention MoE checkpoint directory is not "
            "implemented (its parameter names are not mapped, and kv_b_proj and the rotary pairs need "
            "permuting into models/mla_moe.py's checkpoint layout); serve it from a native checkpoint written "
            "by checkpoint.save_model (the registry re-lays it out for the device: models.held_params)"
        )
    if model_type is not None and model_type not in _SUPPORTED_DECODERS:
        raise ValueError(
            f"unsupported decoder model_type {model_type!r}; "
            f"supported: {sorted(_SUPPORTED_DECODERS)}"
        )
    cfg = DecoderConfig.from_hf(hf, dtype=dtype)
    t = _read_safetensors(model_dir)
    L = cfg.num_layers
    pre = "model.layers.{}."

    layers: Dict[str, np.ndarray] = {
        "attn_norm": _stack(t, pre + "input_layernorm.weight", L),
        "wo": _stack(t, pre + "self_attn.o_proj.weight", L, T=True),
        "mlp_norm": _stack(t, pre + "post_attention_layernorm.weight", L),
    }
    if model_type == "phi3":
        # phi3 fuses qkv and gate/up; split along the output dim
        qkv = _stack(t, pre + "self_attn.qkv_proj.weight", L, T=True)  # [L,E,(H+2KH)*D]
        qd = cfg.num_heads * cfg.head_dim
        kd = cfg.num_kv_heads * cfg.head_dim
        layers["wq"] = qkv[:, :, :qd]
        layers["wk"] = qkv[:, :, qd : qd + kd]
        layers["wv"] = qkv[:, :, qd + kd :]
    else:
        layers["wq"] = _stack(t, pre + "self_attn.q_proj.weight", L, T=True)
        layers["wk"] = _stack(t, pre + "self_attn.k_proj.weight", L, T=True)
        layers["wv"] = _stack(t, pre + "self_attn.v_proj.weight", L, T=True)
    if cfg.attn_bias:  # Qwen2 family: qkv biases (o_proj stays bias-free)
        layers.update(
            {
                "bq": _stack(t, pre + "self_attn.q_proj.bias", L),
                "bk": _stack(t, pre + "self_attn.k_proj.bias", L),
                "bv": _stack(t, pre + "self_attn.v_proj.bias", L),
            }
        )
    if cfg.is_moe:
        X = cfg.num_experts

        def stack_experts(w: str) -> np.ndarray:
            per_layer = []
            for i in range(L):
                per_layer.append(
                    np.stack(
                        [
                            t[f"model.layers.{i}.block_sparse_moe.experts.{j}.{w}.weight"].T
                            for j in range(X)
                        ]
                    )
                )
            return np.stack(per_layer)  # [L, X, in, out]

        layers.update(
            {
                "router": _stack(t, pre + "block_sparse_moe.gate.weight", L, T=True),
                "w_gate": stack_experts("w1"),
                "w_up": stack_experts("w3"),
                "w_down": stack_experts("w2"),
            }
        )
    elif model_type == "phi3":
        gate_up = _stack(t, pre + "mlp.gate_up_proj.weight", L, T=True)  # [L,E,2F]
        F = cfg.intermediate_size
        layers.update(
            {
                "w_gate": gate_up[:, :, :F],
                "w_up": gate_up[:, :, F:],
                "w_down": _stack(t, pre + "mlp.down_proj.weight", L, T=True),
            }
        )
    else:
        layers.update(
            {
                "w_gate": _stack(t, pre + "mlp.gate_proj.weight", L, T=True),
                "w_up": _stack(t, pre + "mlp.up_proj.weight", L, T=True),
                "w_down": _stack(t, pre + "mlp.down_proj.weight", L, T=True),
            }
        )

    params: Dict[str, Any] = {
        "tok_embed": t["model.embed_tokens.weight"],
        "final_norm": t["model.norm.weight"],
        "layers": layers,
    }
    if hf.get("model_type") == "gemma":
        # Gemma's RMSNorm multiplies by (1 + w); folding the +1 into the stored
        # weights keeps a single norm implementation for every family.  HF
        # computes 1+w in float32 inside the norm — fold in f32 too, or the
        # bf16 addition carries ~2^-9 relative rounding vs reference logits
        # (the final dtype cast below then matches HF's single rounding).
        layers["attn_norm"] = np.asarray(layers["attn_norm"], np.float32) + 1.0
        layers["mlp_norm"] = np.asarray(layers["mlp_norm"], np.float32) + 1.0
        params["final_norm"] = np.asarray(params["final_norm"], np.float32) + 1.0
    if not cfg.tie_embeddings:
        head = t.get("lm_head.weight")
        if head is None:  # some checkpoints tie implicitly
            cfg = DecoderConfig(**{**cfg.__dict__, "tie_embeddings": True})
        else:
            params["lm_head"] = head.T
    params = _to_jax(params, dtype)
    return cfg, params


def _to_jax(tree: Any, dtype) -> Any:
    import jax
    import jax.numpy as jnp

    def conv(x):
        if isinstance(x, np.ndarray):
            if np.issubdtype(x.dtype, np.floating):
                return jnp.asarray(x).astype(dtype)
            return jnp.asarray(x)
        return x

    return jax.tree.map(conv, tree)
