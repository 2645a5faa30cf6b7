"""Model configs for the encoder / decoder / MoE families.

``from_hf`` classmethods map Hugging Face ``config.json`` dicts (BertConfig /
LlamaConfig / MixtralConfig) onto these, so checkpoints the reference serves
(sberbank-ai/ruBert-base, Llama-3-8B, Mixtral-8x7B — see BASELINE.md configs) load
without the transformers modelling code.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Mapping, Optional

import jax.numpy as jnp

_logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    """BERT-family encoder (ruBert-base: 12L/768E/12H; MiniLM-L6: 6L/384E/12H)."""

    vocab_size: int = 119_547
    hidden_size: int = 768
    intermediate_size: int = 3072
    num_layers: int = 12
    num_heads: int = 12
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    pad_token_id: int = 0
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @classmethod
    def from_hf(cls, hf: Mapping[str, Any], dtype=jnp.bfloat16) -> "EncoderConfig":
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            max_position_embeddings=hf.get("max_position_embeddings", 512),
            type_vocab_size=hf.get("type_vocab_size", 2),
            layer_norm_eps=hf.get("layer_norm_eps", 1e-12),
            pad_token_id=hf.get("pad_token_id", 0),
            dtype=dtype,
        )

    @classmethod
    def tiny(cls) -> "EncoderConfig":
        """Test-size config (runs on the 8-device CPU mesh in milliseconds)."""
        return cls(
            vocab_size=512,
            hidden_size=64,
            intermediate_size=128,
            num_layers=2,
            num_heads=4,
            max_position_embeddings=128,
            dtype=jnp.float32,
        )


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    """What the latent-attention + routed-expert block (DeepSeek-V3 family:
    ``kv_lora_rank``, ``n_routed_experts``) needs beyond :class:`DecoderConfig`.

    Attention: queries through a rank-``q_lora_rank`` bottleneck with its own
    RMSNorm; keys and values through ONE rank-``kv_lora_rank`` latent plus a
    ``qk_rope_head_dim``-wide rotary key shared by all heads.  The cache holds
    that ``kv_lora_rank + qk_rope_head_dim`` row per token per layer.

    Feed-forward: the first ``first_dense_layers`` layers are dense SwiGLU
    (``DecoderConfig.intermediate_size``), the rest route every token over
    ``router_experts`` sigmoid-scored experts of width
    ``moe_intermediate_size`` (top ``DecoderConfig.experts_per_token``, picks
    limited to the ``topk_group`` best of ``n_group`` groups, weights
    normalised over the picks and scaled) beside ``n_shared_experts`` shared
    ones.  **This process holds only the experts of its rank**:
    ``[ep_rank * experts_held, (ep_rank + 1) * experts_held)`` of a
    ``ep_size``-way expert-parallel deployment; it routes over all of them,
    computes its own, and leaves the rest of the sum to the absent ranks (no
    code stands in for them or their exchange).  ``router_bias``: the router
    carries a score-correction bias (``topk_method: noaux_tc``) that enters the
    scores which PICK groups and experts and never the weights.

    Learned sparse attention (``deepseek_v32``; ``index_topk`` 0 = none, and
    then the block is the dense one, weight for weight and program for
    program): an indexer of ``index_n_heads`` heads of ``index_head_dim``
    scores every cached token for every query, the ``index_topk`` best are
    attended, and the cache holds an ``index_head_dim``-wide index key per
    token per layer beside the latent row.

    The shortcut-connected double layer (``longcat_flash``; ``double_layer``):
    a layer is attention, dense FFN, attention, dense FFN, and ONE expert layer
    that reads the first sublayer's normed hidden state and joins the residual
    at the layer's end.  No dense stack and no shared expert; the cache holds a
    row per attention SUBLAYER, two a layer.  Its router scores by softmax
    (``scoring_func``) over ``router_experts`` routed experts and
    ``zero_experts`` identity experts after them: a pick of one adds its weight
    times the expert layer's input, on the rank the token lives on.  ``q_scale``
    / ``kv_scale`` multiply the queries after their up-projection and the normed
    latent (``mla_scale_q_lora`` / ``mla_scale_kv_lora``); 1.0 elsewhere.
    """

    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    moe_intermediate_size: int
    router_experts: int
    first_dense_layers: int = 1
    n_shared_experts: int = 1
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    # yarn with mscale_all_dim: the softmax scale is qk_head_dim^-0.5 times this
    # (mscale(factor, mscale_all_dim)^2); cos/sin carry mscale/mscale_all_dim
    softmax_scale_mult: float = 1.0
    ep_size: int = 1
    ep_rank: int = 0
    router_bias: bool = False
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    scoring_func: str = "sigmoid"
    zero_experts: int = 0
    q_scale: float = 1.0
    kv_scale: float = 1.0
    double_layer: bool = False

    def __post_init__(self):
        if self.scoring_func not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring_func {self.scoring_func!r}: sigmoid or softmax")
        if self.scoring_func == "softmax" and self.n_group != 1:
            raise ValueError("softmax scores are not picked by groups (n_group 1)")
        if self.double_layer and (self.first_dense_layers or self.n_shared_experts or self.index_topk):
            raise ValueError("the double layer has no leading dense layer, no shared expert and no indexer")
        if not self.double_layer and (self.first_dense_layers < 1 or self.zero_experts):
            raise ValueError("only the double layer runs without a leading dense layer or with identity experts")
        if self.router_experts % self.ep_size or not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(
                f"{self.router_experts} experts do not divide over ep_size={self.ep_size}, "
                f"or ep_rank={self.ep_rank} is outside it"
            )
        if self.router_experts % self.n_group or not 1 <= self.topk_group <= self.n_group:
            raise ValueError(
                f"{self.router_experts} experts do not split into n_group={self.n_group} "
                f"groups of which topk_group={self.topk_group} are kept"
            )
        if self.index_topk and not (self.index_n_heads > 0 and self.qk_rope_head_dim <= self.index_head_dim):
            raise ValueError(
                f"index_topk={self.index_topk} needs an indexer: index_n_heads={self.index_n_heads}, "
                f"index_head_dim={self.index_head_dim} (at least qk_rope_head_dim={self.qk_rope_head_dim} wide)"
            )

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def experts_held(self) -> int:
        return self.router_experts // self.ep_size

    @property
    def first_expert(self) -> int:
        return self.ep_rank * self.experts_held

    @property
    def router_width(self) -> int:
        """The router's outputs: every rank's routed experts, then the identity experts."""
        return self.router_experts + self.zero_experts

    @property
    def latent_width(self) -> int:
        """One cached row: latent | rotary key, padded to whole 128-lane tiles."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128


# keys that change a block's mathematics and that the plain block ignores: a
# config carrying one is another family, never a dense Llama
_OTHER_BLOCK_KEYS = ("kv_lora_rank", "n_routed_experts", "layer_types")
_PLAIN_MODEL_TYPES = ("llama", "mistral", "mixtral", "qwen2", "phi3", "gemma")
_LATENT_MOE_MODEL_TYPES = ("axk1", "deepseek_v3", "deepseek_v32")
_DOUBLE_LAYER_MODEL_TYPES = ("longcat_flash",)


def _expert_share(hf: Mapping[str, Any], refuse):
    """-> (ep_size, ep_rank, routed experts over all ranks).  A published config
    has ``ep_size`` 1 and ``n_routed_experts`` is all of them; a deployment's
    names ``ep_rank`` beside ``ep_size``, and ``n_routed_experts`` counts the
    experts held by THIS rank."""
    ep_size, held = int(hf.get("ep_size", 1)), int(hf["n_routed_experts"])
    if "ep_rank" in hf:
        return ep_size, int(hf["ep_rank"]), held * ep_size
    if ep_size == 1:
        return 1, 0, held
    refuse(f"ep_size {ep_size} without ep_rank: which share of the experts is held here?")


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    """Llama-3 family decoder; ``num_experts > 0`` turns the MLP into Mixtral MoE;
    ``latent_moe`` set makes it the latent-attention + routed-expert block
    (``models/mla_moe.py``; ``arch`` names which module runs the config)."""

    vocab_size: int = 128_256
    hidden_size: int = 4096
    intermediate_size: int = 14_336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: Optional[int] = None
    max_seq_len: int = 8192
    rope_theta: float = 500_000.0
    # Llama-3.1-style rope frequency remap as a hashable 4-tuple
    # (factor, low_freq_factor, high_freq_factor, original_max_len); None = plain rope
    rope_scaling: Optional[tuple] = None
    rms_norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # Qwen2 family: biases on the q/k/v projections (o stays bias-free)
    attn_bias: bool = False
    # Sliding-window attention (Mistral, Phi-3, optionally Qwen2): a query
    # attends to the `sliding_window` most recent positions including itself
    # (HF masking_utils.sliding_window_overlay semantics).  None = full causal.
    sliding_window: Optional[int] = None
    # First windowed layer: layers [0, window_layer_start) use full attention,
    # [window_layer_start, L) the window — Qwen2's max_window_layers split;
    # 0 = every layer windowed (Mistral/Phi-3).
    window_layer_start: int = 0
    # Gemma family: GeGLU MLP ("gelu_tanh") and sqrt(E)-scaled embeddings.
    # Gemma's (1+w) RMSNorm needs no flag — the +1 folds into the stored norm
    # weights at load time (hf_loader), keeping one norm implementation.
    hidden_act: str = "silu"
    embed_multiplier: float = 1.0
    # MoE (Mixtral): 0 experts = dense SwiGLU MLP
    num_experts: int = 0
    experts_per_token: int = 2
    expert_capacity_factor: float = 1.25
    latent_moe: Optional[LatentMoEConfig] = None
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if isinstance(self.latent_moe, Mapping):  # a checkpoint's JSON
            object.__setattr__(self, "latent_moe", LatentMoEConfig(**self.latent_moe))
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.hidden_size // self.num_heads)
        if self.rope_scaling and self.rope_scaling[0] == "longrope":
            orig = self.rope_scaling[3]
            if self.max_seq_len > orig:
                # Static-shape serving commits to ONE factor list per deployment
                # (ops/rope.py); HF flips short/long per running sequence, so in
                # a long-context deployment prompts shorter than the pretrained
                # context get LONG factors where HF uses SHORT ones.
                _logger.warning(
                    "longrope deployment with max_seq_len=%d > pretrained "
                    "context %d: LONG rope factors apply to every sequence, so "
                    "logits for prompts shorter than %d diverge from HF (which "
                    "switches factor lists per sequence).  For exact "
                    "short-context parity deploy with max_seq_len <= %d.",
                    self.max_seq_len,
                    int(orig),
                    int(orig),
                    int(orig),
                )

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def arch(self) -> str:
        """Which model module runs this config (``models.module_for``)."""
        return "mla_moe" if self.latent_moe is not None else "llama"

    @classmethod
    def from_hf(cls, hf: Mapping[str, Any], dtype=jnp.bfloat16) -> "DecoderConfig":
        model_type = hf.get("model_type")
        other = [k for k in _OTHER_BLOCK_KEYS if hf.get(k)]
        if model_type in _DOUBLE_LAYER_MODEL_TYPES:
            return cls._from_hf_longcat_flash(hf, dtype)
        if model_type in _LATENT_MOE_MODEL_TYPES or {"kv_lora_rank", "n_routed_experts"} <= set(other):
            return cls._from_hf_latent_moe(hf, dtype)
        if other and model_type not in _PLAIN_MODEL_TYPES:
            # Llama's six keys are all there, and so are keys this block would
            # ignore: reading it as a dense Llama would serve another model
            raise ValueError(
                f"model_type {model_type!r} is not known and its config carries {other}: "
                "these change the block's mathematics and the plain decoder block would "
                "ignore them; refusing to read it as a dense Llama"
            )
        num_experts = hf.get("num_local_experts", 0)
        is_gemma = hf.get("model_type") == "gemma"
        act = hf.get("hidden_activation") or hf.get("hidden_act") or "silu"
        rs = hf.get("rope_scaling")
        rope_scaling = None
        if rs:
            import math

            kind = rs.get("rope_type") or rs.get("type")
            max_pos = hf.get("max_position_embeddings", 8192)
            if kind == "llama3":
                rope_scaling = (
                    float(rs["factor"]),
                    float(rs["low_freq_factor"]),
                    float(rs["high_freq_factor"]),
                    float(rs["original_max_position_embeddings"]),
                )
            elif kind == "linear":
                rope_scaling = ("linear", float(rs["factor"]))
            elif kind == "longrope":
                # Phi-3 128k (transformers modeling_rope_utils
                # _compute_longrope_parameters): per-frequency factor lists +
                # an attention factor derived from the context extension ratio
                orig = float(
                    hf.get("original_max_position_embeddings")
                    or rs.get("original_max_position_embeddings")
                    or max_pos
                )
                factor = rs.get("factor")
                if hf.get("original_max_position_embeddings"):
                    factor = max_pos / float(hf["original_max_position_embeddings"])
                af = rs.get("attention_factor")
                if af is None:
                    af = (
                        1.0
                        if factor is None or factor <= 1.0
                        else math.sqrt(1.0 + math.log(factor) / math.log(orig))
                    )
                rope_scaling = (
                    "longrope",
                    tuple(float(x) for x in rs["short_factor"]),
                    tuple(float(x) for x in rs["long_factor"]),
                    orig,
                    float(af),
                )
            elif kind == "yarn":
                factor = float(rs["factor"])
                orig = float(rs.get("original_max_position_embeddings") or max_pos)
                mscale = rs.get("mscale")
                mscale_all = rs.get("mscale_all_dim")

                def _mscale(scale, m=1.0):
                    return 1.0 if scale <= 1.0 else 0.1 * m * math.log(scale) + 1.0

                af = rs.get("attention_factor")
                if af is None:
                    if mscale and mscale_all:
                        af = _mscale(factor, mscale) / _mscale(factor, mscale_all)
                    else:
                        af = _mscale(factor)
                rope_scaling = (
                    "yarn",
                    factor,
                    float(rs.get("beta_fast") or 32),
                    float(rs.get("beta_slow") or 1),
                    orig,
                    float(af),
                    bool(rs.get("truncate", True)),
                )
            elif kind != "default":  # HF "default" = plain rope, i.e. None
                # silently dropping the scaling would mis-place every position
                # beyond the original context — reject instead
                raise ValueError(f"unsupported rope_scaling type {kind!r}")
        # Sliding-window attention runs natively (banded masks + block-skipping
        # flash kernel), so the full advertised context is usable — no clamp.
        # Qwen2 ships sliding_window but gates it behind use_sliding_window
        # (HF defaults that flag OFF for the qwen2 family, on elsewhere) and
        # windows only layers >= max_window_layers.
        max_seq = hf.get("max_position_embeddings", 8192)
        window = hf.get("sliding_window")
        window_on = hf.get(
            "use_sliding_window", hf.get("model_type") != "qwen2"
        )
        sliding_window = int(window) if (window and window_on) else None
        window_layer_start = 0
        if sliding_window and hf.get("model_type") == "qwen2":
            mwl = hf.get("max_window_layers")
            # HF Qwen2Config defaults max_window_layers=28 when absent — a
            # fallback of 0 would window every layer HF keeps full
            window_layer_start = int(mwl) if mwl is not None else 28
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
            head_dim=hf.get("head_dim"),
            hidden_act="gelu_tanh" if "gelu" in act else "silu",
            embed_multiplier=float(hf["hidden_size"]) ** 0.5 if is_gemma else 1.0,
            max_seq_len=max_seq,
            rope_theta=hf.get("rope_theta", 500_000.0),
            rope_scaling=rope_scaling,
            rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
            tie_embeddings=hf.get("tie_word_embeddings", False),
            # Qwen2 checkpoints predate the attention_bias flag; the family
            # always uses qkv biases (HF modeling hardcodes them)
            attn_bias=bool(
                hf.get("attention_bias", hf.get("model_type") == "qwen2")
            ),
            sliding_window=sliding_window,
            window_layer_start=window_layer_start,
            num_experts=num_experts,
            experts_per_token=hf.get("num_experts_per_tok", 2),
            dtype=dtype,
        )

    @classmethod
    def _from_hf_latent_moe(cls, hf: Mapping[str, Any], dtype) -> "DecoderConfig":
        """The DeepSeek-V3 family's keys (``model_type`` ``deepseek_v3`` / ``axk1``
        / ``deepseek_v32``: the same block with a lightning indexer and top-k
        sparse attention, ``index_*``).  ``num_nextn_predict_layers`` (the
        multi-token-prediction module, a draft head for speculation) is
        accepted and NO such layer is built or loaded: speculation is refused
        for this block (:func:`..mla_moe.check_serving`).

        What cannot be honoured is refused, not dropped.  The expert share: a
        published config has ``ep_size`` 1 and ``n_routed_experts`` is all of
        them.  A deployment's config names ``ep_rank`` beside ``ep_size``; there
        ``n_routed_experts`` counts the experts held by THIS rank and the router
        keeps its published width, ``n_routed_experts * ep_size``."""
        import math

        def refuse(why):
            raise ValueError(f"latent-attention MoE config (model_type {hf.get('model_type')!r}): {why}")

        if hf.get("scoring_func", "sigmoid") != "sigmoid":
            refuse(f"scoring_func {hf.get('scoring_func')!r}: only sigmoid scores are implemented")
        topk_method = hf.get("topk_method", "none")
        if topk_method not in ("none", "group_limited_greedy", "noaux_tc"):
            refuse(f"topk_method {topk_method!r}: only none, group_limited_greedy and noaux_tc are implemented")
        index_topk = int(hf.get("index_topk") or 0)
        if hf.get("model_type") == "deepseek_v32" and not index_topk:
            refuse("deepseek_v32 without index_topk: the sparse attention's size is not a default")
        if int(hf.get("moe_layer_freq", 1)) != 1:
            refuse("moe_layer_freq != 1: only 'leading dense layers, then expert layers' is implemented")
        if hf.get("attention_bias"):
            refuse("attention_bias: the latent projections carry no biases here")
        if not hf.get("q_lora_rank"):
            refuse("q_lora_rank is null: full-rank queries are not implemented")
        if (hf.get("hidden_act") or "silu") != "silu":
            refuse(f"hidden_act {hf.get('hidden_act')!r}")
        ep_size, ep_rank, router = _expert_share(hf, refuse)
        rs = hf.get("rope_scaling")
        rope_scaling, scale_mult = None, 1.0
        if rs:
            kind = rs.get("rope_type") or rs.get("type")
            if kind != "yarn":
                refuse(f"rope_scaling type {kind!r}: only yarn is implemented for this family")
            factor = float(rs["factor"])

            def mscale(m):
                return 1.0 if factor <= 1.0 or not m else 0.1 * float(m) * math.log(factor) + 1.0

            m_all = rs.get("mscale_all_dim") or 0
            scale_mult = mscale(m_all) ** 2
            rope_scaling = (
                "yarn", factor, float(rs.get("beta_fast") or 32), float(rs.get("beta_slow") or 1),
                float(rs.get("original_max_position_embeddings") or hf.get("max_position_embeddings", 4096)),
                mscale(rs.get("mscale") or 1) / mscale(m_all), True,
            )
        if not 0 < int(hf.get("first_k_dense_replace", 0)) < int(hf["num_hidden_layers"]):
            refuse("needs at least one leading dense layer and one expert layer")
        lm = LatentMoEConfig(
            q_lora_rank=int(hf["q_lora_rank"]), kv_lora_rank=int(hf["kv_lora_rank"]),
            qk_nope_head_dim=int(hf["qk_nope_head_dim"]), qk_rope_head_dim=int(hf["qk_rope_head_dim"]),
            v_head_dim=int(hf["v_head_dim"]), moe_intermediate_size=int(hf["moe_intermediate_size"]),
            router_experts=router, first_dense_layers=int(hf.get("first_k_dense_replace", 0)),
            n_shared_experts=int(hf.get("n_shared_experts") or 0),
            n_group=int(hf.get("n_group") or 1), topk_group=int(hf.get("topk_group") or 1),
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
            norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
            softmax_scale_mult=scale_mult, ep_size=ep_size, ep_rank=ep_rank,
            # noaux_tc: a learnt correction bias on the scores that pick the experts
            router_bias=topk_method == "noaux_tc",
            index_n_heads=int(hf.get("index_n_heads") or 0) if index_topk else 0,
            index_head_dim=int(hf.get("index_head_dim") or 0) if index_topk else 0,
            index_topk=index_topk,
        )
        return cls(
            vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"], num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"], num_kv_heads=hf["num_attention_heads"],
            head_dim=lm.qk_head_dim, max_seq_len=hf.get("max_position_embeddings", 8192),
            rope_theta=float(hf.get("rope_theta", 10_000.0)), rope_scaling=rope_scaling,
            rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
            tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
            experts_per_token=int(hf["num_experts_per_tok"]), latent_moe=lm, dtype=dtype,
        )

    @classmethod
    def _from_hf_longcat_flash(cls, hf: Mapping[str, Any], dtype) -> "DecoderConfig":
        """LongCat-Flash's keys (``model_type`` ``longcat_flash``): ``num_layers``
        shortcut-connected double layers, ``ffn_hidden_size`` /
        ``expert_ffn_hidden_size``, ``moe_topk`` of ``n_routed_experts`` +
        ``zero_expert_num`` softmax scores under a correction bias, weights not
        normalised, ``mla_scale_q_lora`` / ``mla_scale_kv_lora``.  The expert
        share as for the DeepSeek-V3 family: a deployment's ``n_routed_experts``
        counts the experts held by ``ep_rank`` of ``ep_size``."""

        def refuse(why):
            raise ValueError(f"shortcut-connected MoE config (model_type {hf.get('model_type')!r}): {why}")

        zero = int(hf.get("zero_expert_num") or 0)
        if zero and hf.get("zero_expert_type", "identity") != "identity":
            refuse(f"zero_expert_type {hf.get('zero_expert_type')!r}: only identity experts are implemented")
        if hf.get("attention_bias") or hf.get("router_bias"):
            refuse("attention_bias / router_bias: the projections and the router carry no biases here")
        if not hf.get("q_lora_rank"):
            refuse("q_lora_rank is null: full-rank queries are not implemented")
        if hf.get("rope_scaling"):
            refuse("rope_scaling: only plain rotary tables are implemented for this block")
        if (hf.get("hidden_act") or "silu") != "silu":
            refuse(f"hidden_act {hf.get('hidden_act')!r}")
        ep_size, ep_rank, router = _expert_share(hf, refuse)
        E, R, C = int(hf["hidden_size"]), int(hf["q_lora_rank"]), int(hf["kv_lora_rank"])
        lm = LatentMoEConfig(
            q_lora_rank=R, kv_lora_rank=C,
            qk_nope_head_dim=int(hf["qk_nope_head_dim"]), qk_rope_head_dim=int(hf["qk_rope_head_dim"]),
            v_head_dim=int(hf["v_head_dim"]), moe_intermediate_size=int(hf["expert_ffn_hidden_size"]),
            router_experts=router, first_dense_layers=0, n_shared_experts=0,
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
            norm_topk_prob=bool(hf.get("norm_topk_prob", False)),
            ep_size=ep_size, ep_rank=ep_rank, router_bias=True, scoring_func="softmax", zero_experts=zero,
            q_scale=(E / R) ** 0.5 if hf.get("mla_scale_q_lora") else 1.0,
            kv_scale=(E / C) ** 0.5 if hf.get("mla_scale_kv_lora") else 1.0,
            double_layer=True,
        )
        return cls(
            vocab_size=hf["vocab_size"], hidden_size=E, intermediate_size=hf["ffn_hidden_size"],
            num_layers=hf["num_layers"], num_heads=hf["num_attention_heads"], num_kv_heads=hf["num_attention_heads"],
            head_dim=lm.qk_head_dim, max_seq_len=hf.get("max_position_embeddings", 8192),
            rope_theta=float(hf.get("rope_theta", 10_000.0)), rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
            tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
            experts_per_token=int(hf["moe_topk"]), latent_moe=lm, dtype=dtype,
        )

    @classmethod
    def llama3_8b(cls, dtype=jnp.bfloat16) -> "DecoderConfig":
        return cls(dtype=dtype)

    @classmethod
    def mixtral_8x7b(cls, dtype=jnp.bfloat16) -> "DecoderConfig":
        return cls(
            vocab_size=32_000,
            hidden_size=4096,
            intermediate_size=14_336,
            num_layers=32,
            num_heads=32,
            num_kv_heads=8,
            rope_theta=1e6,
            num_experts=8,
            experts_per_token=2,
            max_seq_len=32_768,
            dtype=dtype,
        )

    @classmethod
    def tiny(cls, *, num_experts: int = 0) -> "DecoderConfig":
        return cls(
            vocab_size=512,
            hidden_size=64,
            intermediate_size=128,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            max_seq_len=256,
            rope_theta=10_000.0,
            num_experts=num_experts,
            dtype=jnp.float32,
        )
