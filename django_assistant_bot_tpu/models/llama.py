"""Llama-3-family decoder: RMSNorm + RoPE + GQA + SwiGLU, KV-cache prefill/decode.

TPU-native replacement for the reference's ``AutoModelForCausalLM.generate`` single
stream (reference: assistant/ai/providers/transformers.py:35-94).  Differences that
matter on TPU:

- layers stacked on a leading axis, iterated with ``lax.scan`` — one compiled body;
- a static-shape page pool shared by every slot, addressed through per-slot block
  tables (continuous batching: no dynamic shapes ever reach XLA);
- prefill uses the pallas flash-attention kernel for long buckets; decode writes the
  step's K/V row in place and reads only the live pages (a Pallas kernel on a TPU,
  the jnp path elsewhere);
- tensor parallelism: heads/mlp sharded over the ``model`` mesh axis via logical
  axis annotations; XLA inserts the per-layer psums over ICI.

MoE note: when ``cfg.is_moe``, the MLP block is delegated to
:func:`.mixtral.moe_mlp` (experts sharded over ``expert``).
"""

from __future__ import annotations

import functools
import logging
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import (
    attention,
    dot_product_attention,
    gqa_dot_product_attention,
    paged_decode_kv_path,
    paged_decode_plan,
    paged_decode_update_attend,
    paged_gqa_decode_attention,
    paged_tree_attention,
)
from ..ops.norms import rms_norm
from ..ops.quant import INT4_GROUP_SIZE, QTensor, qeinsum
from ..ops.rope import apply_rope, rope_frequencies
from ..parallel.sharding import with_constraint
from .config import DecoderConfig

Params = Dict[str, Any]

_logger = logging.getLogger(__name__)


def logical_axes(cfg: DecoderConfig) -> Params:
    E, F = "embed", "mlp"
    layers: Dict[str, tuple] = {
        "attn_norm": (None, E),
        "wq": (None, E, "heads"),
        "wk": (None, E, "kv_heads"),
        "wv": (None, E, "kv_heads"),
        "wo": (None, "heads", E),
        "mlp_norm": (None, E),
    }
    if cfg.attn_bias:
        layers.update(
            {"bq": (None, "heads"), "bk": (None, "kv_heads"), "bv": (None, "kv_heads")}
        )
    if cfg.is_moe:
        layers.update(
            {
                "router": (None, E, "expert"),
                "w_gate": (None, "expert", E, F),
                "w_up": (None, "expert", E, F),
                "w_down": (None, "expert", F, E),
            }
        )
    else:
        layers.update(
            {"w_gate": (None, E, F), "w_up": (None, E, F), "w_down": (None, F, E)}
        )
    axes = {
        "tok_embed": ("vocab_in", E),
        "final_norm": (E,),
        "layers": layers,
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = (E, "vocab_out")
    return axes


def init(cfg: DecoderConfig, rng: jax.Array) -> Params:
    keys = jax.random.split(rng, 12)
    E, F, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    H, KH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    s = E ** -0.5

    def dense(key, shape, scale=None):
        return (jax.random.normal(key, shape) * (scale or s)).astype(cfg.dtype)

    layers = {
        "attn_norm": jnp.ones((L, E), cfg.dtype),
        "wq": dense(keys[0], (L, E, H * D)),
        "wk": dense(keys[1], (L, E, KH * D)),
        "wv": dense(keys[2], (L, E, KH * D)),
        "wo": dense(keys[3], (L, H * D, E)),
        "mlp_norm": jnp.ones((L, E), cfg.dtype),
    }
    if cfg.attn_bias:
        layers.update(
            {
                "bq": jnp.zeros((L, H * D), cfg.dtype),
                "bk": jnp.zeros((L, KH * D), cfg.dtype),
                "bv": jnp.zeros((L, KH * D), cfg.dtype),
            }
        )
    if cfg.is_moe:
        X = cfg.num_experts
        layers.update(
            {
                "router": dense(keys[4], (L, E, X)),
                "w_gate": dense(keys[5], (L, X, E, F)),
                "w_up": dense(keys[6], (L, X, E, F)),
                "w_down": dense(keys[7], (L, X, F, E), scale=F ** -0.5),
            }
        )
    else:
        layers.update(
            {
                "w_gate": dense(keys[5], (L, E, F)),
                "w_up": dense(keys[6], (L, E, F)),
                "w_down": dense(keys[7], (L, F, E), scale=F ** -0.5),
            }
        )
    params = {
        "tok_embed": dense(keys[8], (cfg.vocab_size, E), scale=1.0),
        "final_norm": jnp.ones((E,), cfg.dtype),
        "layers": layers,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(keys[9], (E, cfg.vocab_size))
    return params


def _synth_quant_params(
    cfg: DecoderConfig,
    rng: jax.Array,
    *,
    proj_fmt: str,
    group_size: int = INT4_GROUP_SIZE,
    quantize_embed: bool = False,
    host_rng: bool = False,
) -> Params:
    """Shared scaffolding of :func:`init_int8` / :func:`init_int4`: draw the
    random integer payloads directly into HBM (one fused program per shape —
    run eagerly, every leaf's transient would coexist under async dispatch:
    ~2x the whole model, the 8B init that "randomly" OOM'd a chip with 12 GB
    free), set constant scales so dequantized magnitudes match :func:`init`'s
    normal(0, E^-0.5), and assemble the same params skeleton.  Only the
    projection constructor differs between the two formats — everything else
    lives ONCE here so the int8 and int4 synthetic recipes cannot drift.

    ``host_rng`` draws the random bytes with numpy on the host instead of
    on-device threefry.  On a real chip the device draw wins (no transfer);
    on the virtual CPU mesh threefry runs on the same cores it's "offloading"
    to and is ~100x slower than numpy — the 8B/Mixtral dryrun stages spent
    minutes of their budget inside it (r4's multichip timeout).
    """
    from ..ops.quant import QTensor, QTensor4, _int4_group

    E, F, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    H, KH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    s = E ** -0.5
    # uniform int8 has std ~127/sqrt(3); uniform int4 in [-8, 7] has std
    # sqrt((16^2 - 1) / 12); the constant scale recovers the target std
    UNIFORM8_STD = 127.0 / (3.0 ** 0.5)
    UNIFORM4_STD = (255.0 / 12.0) ** 0.5
    keys = iter(jax.random.split(rng, 16))

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def _gen_bits(key, shape, to_int8):
        # the uint8 draw converts (int8) or stays raw (int4 packed — one
        # random byte IS two uniform nibbles) INSIDE the jit, so XLA writes
        # the final dtype directly with a transient of the result's size
        bits = jax.random.bits(key, shape, jnp.uint8)
        return bits.astype(jnp.int8) if to_int8 else bits

    host = (
        np.random.default_rng(int(np.asarray(jax.random.key_data(rng)).ravel()[-1]))
        if host_rng
        else None
    )

    def qdense8(shape, target_std=None):
        if host is not None:
            q = jnp.asarray(host.integers(-127, 128, shape, np.int8))
        else:
            q = _gen_bits(next(keys), shape, True)
            q.block_until_ready()  # serialize: peak transient = one leaf, not all
        scale_shape = shape[:-2] + (1, shape[-1])
        scale = jnp.full(scale_shape, (target_std or s) / UNIFORM8_STD, jnp.float32)
        return QTensor(q=q, scale=scale)

    def qdense4(shape, target_std=None):
        *lead, dim, out_dim = shape
        g = _int4_group(dim, group_size)
        packed_shape = tuple(lead) + (dim // 2, out_dim)
        if host is not None:
            q = jnp.asarray(
                host.integers(0, 256, packed_shape, np.uint8, endpoint=False)
            )
        else:
            q = _gen_bits(next(keys), packed_shape, False)
            q.block_until_ready()
        scale_shape = tuple(lead) + (dim // g, out_dim)
        scale = jnp.full(
            scale_shape, (target_std or s) / UNIFORM4_STD, jnp.float32
        )
        return QTensor4(q=q, scale=scale)

    def ndense(shape, scale=1.0):
        # dense (non-quantized) leaves: embeddings/head/router
        if host is not None:
            arr = host.standard_normal(shape, np.float32) * scale
            return jnp.asarray(arr).astype(cfg.dtype)
        return jax.random.normal(next(keys), shape, cfg.dtype) * jnp.asarray(
            scale, cfg.dtype
        )

    qdense = qdense4 if proj_fmt == "int4" else qdense8
    layers: Dict[str, Any] = {
        "attn_norm": jnp.ones((L, E), cfg.dtype),
        "wq": qdense((L, E, H * D)),
        "wk": qdense((L, E, KH * D)),
        "wv": qdense((L, E, KH * D)),
        "wo": qdense((L, H * D, E)),
        "mlp_norm": jnp.ones((L, E), cfg.dtype),
    }
    if cfg.attn_bias:
        layers.update(
            {
                "bq": jnp.zeros((L, H * D), cfg.dtype),
                "bk": jnp.zeros((L, KH * D), cfg.dtype),
                "bv": jnp.zeros((L, KH * D), cfg.dtype),
            }
        )
    if cfg.is_moe:
        X = cfg.num_experts
        layers.update(
            {
                # the router stays dense: moe_mlp reads it in f32 (and
                # quantize_decoder_params leaves it out too — tiny + routing
                # quality is disproportionately sensitive)
                "router": ndense((L, E, X), s),
                "w_gate": qdense((L, X, E, F)),
                "w_up": qdense((L, X, E, F)),
                "w_down": qdense((L, X, F, E), target_std=F ** -0.5),
            }
        )
    else:
        layers.update(
            {
                "w_gate": qdense((L, E, F)),
                "w_up": qdense((L, E, F)),
                "w_down": qdense((L, F, E), target_std=F ** -0.5),
            }
        )
    # embed/head quantize as INT8 in both formats: the row gather dequantizes
    # only the gathered slice, and per-channel int8 is the established
    # embedding format here (embedding/head quality is disproportionately
    # sensitive — 4-bit tables buy little and cost much)
    params: Params = {
        "tok_embed": (
            qdense8((cfg.vocab_size, E), target_std=1.0)
            if quantize_embed
            else ndense((cfg.vocab_size, E))
        ),
        "final_norm": jnp.ones((E,), cfg.dtype),
        "layers": layers,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            qdense8((E, cfg.vocab_size))
            if quantize_embed
            else ndense((E, cfg.vocab_size), s)
        )
    return params


def init_int8(
    cfg: DecoderConfig,
    rng: jax.Array,
    *,
    quantize_embed: bool = False,
    host_rng: bool = False,
) -> Params:
    """Synthetic int8-quantized params generated ON DEVICE — no host staging.

    ``quantize_embed`` also makes ``tok_embed``/``lm_head`` int8 (QTensor):
    at 8B geometry with a 128k vocab that is another ~1 GB of HBM saved.

    For serving benches, sharding dryruns and seeded checkpoints at flagship
    geometry (e.g. Llama-3-8B: ~8 GB int8): a host-side init would draw and
    quantize 16 GB of floats on the host and then copy 1-2 bytes/param to the
    device.  Here the
    int8 weights are random bits drawn directly into HBM and scales are set so
    dequantized magnitudes match :func:`init`'s normal(0, E^-0.5) — decode
    throughput is weight-value independent, so the result benches identically
    to a quantized real checkpoint of the same geometry.

    Layer projections become :class:`~..ops.quant.QTensor` (int8 + per-output
    -channel f32 scales, contraction dim -2 = 1) exactly like
    ``quantize_decoder_params`` output; norms/embeddings/head stay in
    ``cfg.dtype``.  Shared scaffolding (incl. the ``host_rng`` virtual-mesh
    escape hatch): :func:`_synth_quant_params`.
    """
    return _synth_quant_params(
        cfg,
        rng,
        proj_fmt="int8",
        quantize_embed=quantize_embed,
        host_rng=host_rng,
    )


def init_int4(
    cfg: DecoderConfig,
    rng: jax.Array,
    *,
    group_size: int = INT4_GROUP_SIZE,
    quantize_embed: bool = False,
    host_rng: bool = False,
) -> Params:
    """Synthetic grouped-int4 params generated ON DEVICE (docs/QUANT.md).

    The int4 analog of :func:`init_int8`: layer projections become
    :class:`~..ops.quant.QTensor4` (two values packed per byte along the
    contraction axis + per-(group, channel) f32 scales) exactly like
    ``quantize_decoder_params(..., fmt="int4")`` output — 0.5 bytes/weight of
    HBM read on the decode path vs int8's 1 and bf16's 2.  One random uint8
    draw IS two uniform int4 nibbles, so the packed weights are drawn
    directly into HBM with a transient of exactly the result's size; scales
    are set so dequantized magnitudes match :func:`init`'s normal(0, E^-0.5)
    (uniform [-8, 7] has std sqrt(255/12) ~ 4.61), keeping the bench
    weight-value independent like the int8 path.

    ``quantize_embed`` opts the embedding/head tables into INT8 (not int4 —
    see :func:`_synth_quant_params`), and ``host_rng`` mirrors
    :func:`init_int8`'s virtual-CPU-mesh escape hatch; the whole skeleton is
    shared with the int8 recipe so the two cannot drift.
    """
    return _synth_quant_params(
        cfg,
        rng,
        proj_fmt="int4",
        group_size=group_size,
        quantize_embed=quantize_embed,
        host_rng=host_rng,
    )


def _embed(params: Params, cfg: DecoderConfig, ids: jnp.ndarray) -> jnp.ndarray:
    """Token embedding lookup; Gemma scales by sqrt(E) (in model dtype, like HF).

    int8 tables (QTensor) gather int8 rows and dequantize only the gathered
    slice — the table itself is never upcast in HBM."""
    w = params["tok_embed"]
    if isinstance(w, QTensor):
        x = w.q[ids].astype(cfg.dtype) * w.scale[0].astype(cfg.dtype)
    else:
        x = w[ids].astype(cfg.dtype)
    if cfg.embed_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embed_multiplier, cfg.dtype)
    return x


# Block names (jax.named_scope): metadata on the compiled operations, so a
# fusion in a device trace reads as the block it belongs to — attn/qkv,
# attn/kv_write, attn/kv_read (attn/core where prefill attends to what it just
# computed), attn/out, ffn/gate_up, ffn/down, head; the engine's tick adds
# sample.  No operation is added or moved by them.
@jax.named_scope("head")
def _head_logits(params: Params, cfg: DecoderConfig, x: jnp.ndarray) -> jnp.ndarray:
    """Logits projection ``[..., E] -> [..., V]`` in model dtype.

    int8 heads stay on the int8 read path — the dot's weight operand is a pure
    convert (fusable), never a materialized bf16 copy of the largest tensor in
    the model (~1 GB at 8B/128k vocab).  Untied: scale is per-vocab-column and
    commutes past the dot (qeinsum).  Tied: the table is [V, E] with per-E
    scales, so the scale lands on ``x`` instead — x·(q·s)ᵀ == (x·s)·qᵀ."""
    if cfg.tie_embeddings:
        w = params["tok_embed"]
        if isinstance(w, QTensor):
            xs = x * jnp.squeeze(w.scale, axis=-2).astype(cfg.dtype)
            return jnp.einsum("...e,ve->...v", xs, w.q.astype(cfg.dtype))
        return jnp.einsum("...e,ve->...v", x, w.astype(cfg.dtype))
    return qeinsum("...e,ev->...v", x, params["lm_head"], cfg.dtype)


def _mlp(cfg: DecoderConfig, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.is_moe:
        from .mixtral import moe_mlp

        with jax.named_scope("ffn/moe"):
            return moe_mlp(cfg, p, x)
    act = (
        functools.partial(jax.nn.gelu, approximate=True)
        if cfg.hidden_act == "gelu_tanh"
        else jax.nn.silu
    )
    with jax.named_scope("ffn/gate_up"):
        h = act(qeinsum("bse,ef->bsf", x, p["w_gate"], cfg.dtype)) * qeinsum("bse,ef->bsf", x, p["w_up"], cfg.dtype)
        h = with_constraint(h, ("batch", "length", "mlp"))
    with jax.named_scope("ffn/down"):
        return qeinsum("bsf,fe->bse", h, p["w_down"], cfg.dtype)


@jax.named_scope("attn/out")
def _attn_out(cfg: DecoderConfig, p: Params, o: jnp.ndarray) -> jnp.ndarray:
    """The attention output projection ``[B,S,H*D] -> [B,S,E]``."""
    return qeinsum("bso,oe->bse", o, p["wo"], cfg.dtype)


@jax.named_scope("attn/qkv")
def _attn_proj(cfg: DecoderConfig, p: Params, x: jnp.ndarray, cos, sin):
    """QKV projections + RoPE.  Returns q:[B,H,S,D], k/v:[B,KH,S,D]."""
    B, S, E = x.shape
    H, KH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = qeinsum("bse,eo->bso", x, p["wq"], cfg.dtype)
    k = qeinsum("bse,eo->bso", x, p["wk"], cfg.dtype)
    v = qeinsum("bse,eo->bso", x, p["wv"], cfg.dtype)
    if cfg.attn_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(B, S, H, D)
    k = k.reshape(B, S, KH, D)
    v = v.reshape(B, S, KH, D)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    q = with_constraint(q.transpose(0, 2, 1, 3), ("batch", "heads", "length", "head_dim"))
    k = with_constraint(k.transpose(0, 2, 1, 3), ("batch", "kv_heads", "length", "head_dim"))
    v = with_constraint(v.transpose(0, 2, 1, 3), ("batch", "kv_heads", "length", "head_dim"))
    return q, k, v


@jax.named_scope("attn/qkv")
def _decode_qkv(cfg: DecoderConfig, p: Params, h: jnp.ndarray, cos, sin):
    """One decode step's QKV projections + RoPE at per-slot positions
    (``h``: [B,1,E]).  Returns q:[B,H,1,D], k/v:[B,KH,1,D]."""
    B = h.shape[0]
    H, KH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = qeinsum("bse,eo->bso", h, p["wq"], cfg.dtype)
    k = qeinsum("bse,eo->bso", h, p["wk"], cfg.dtype)
    v = qeinsum("bse,eo->bso", h, p["wv"], cfg.dtype)
    if cfg.attn_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(B, 1, H, D)
    k = k.reshape(B, 1, KH, D)
    v = v.reshape(B, 1, KH, D)
    q = apply_rope(q, cos, sin).transpose(0, 2, 1, 3)
    k = apply_rope(k, cos, sin).transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    return q, k, v


def _repeat_kv(cfg: DecoderConfig, k: jnp.ndarray) -> jnp.ndarray:
    """[B,KH,S,D] -> [B,H,S,D]; contiguous blocks so TP sharding stays aligned."""
    if cfg.q_per_kv == 1:
        return k
    return jnp.repeat(k, cfg.q_per_kv, axis=1)


def _rope_tables(cfg: DecoderConfig, max_len: int):
    # deployed_len pins seq-regime-dependent scalings (longrope) to ONE factor
    # list across prefill (bucket-length tables) and decode (cache-length
    # tables) — mixed lists would corrupt attention between cached K and
    # fresh queries
    cos, sin = rope_frequencies(
        cfg.head_dim,
        max_len,
        cfg.rope_theta,
        scaling=cfg.rope_scaling,
        deployed_len=cfg.max_seq_len,
    )
    return jnp.asarray(cos), jnp.asarray(sin)


def _window_split(cfg: DecoderConfig) -> int:
    """Index of the first sliding-window layer (== num_layers -> none windowed)."""
    if cfg.sliding_window is None:
        return cfg.num_layers
    return min(max(cfg.window_layer_start, 0), cfg.num_layers)


def _scan_window_split(cfg: DecoderConfig, make_body, carry, xs):
    """``lax.scan`` over stacked layers with an optional full/windowed split.

    ``make_body(window)`` returns a scan body; layers [0, split) run full
    attention, [split, L) the sliding window — Qwen2's ``max_window_layers``
    semantics (Mistral/Phi-3 have split=0: every layer windowed).  Still at
    most two compiled bodies regardless of depth; per-layer outputs
    concatenate back on the stacked-layer axis.
    """
    split = _window_split(cfg)
    if split == cfg.num_layers:
        return jax.lax.scan(make_body(None), carry, xs)
    if split == 0:
        return jax.lax.scan(make_body(cfg.sliding_window), carry, xs)
    head = jax.tree.map(lambda a: a[:split], xs)
    tail = jax.tree.map(lambda a: a[split:], xs)
    carry, y_head = jax.lax.scan(make_body(None), carry, head)
    carry, y_tail = jax.lax.scan(make_body(cfg.sliding_window), carry, tail)
    y = jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=0), y_head, y_tail)
    return carry, y


def forward(
    params: Params,
    cfg: DecoderConfig,
    input_ids: jnp.ndarray,  # [B, S]
    *,
    mask: Optional[jnp.ndarray] = None,  # [B,1,1,S] or [B,1,S,S] keep-mask
) -> jnp.ndarray:
    """Training/eval forward over full sequences -> logits [B, S, V] (f32).

    Causal masking always applies; ``mask`` adds padding masking on top.
    """
    B, S = input_ids.shape
    cos, sin = _rope_tables(cfg, S)
    x = _embed(params, cfg, input_ids)
    x = with_constraint(x, ("batch", "length", "embed"))

    def make_body(window):
        def body(x, p):
            h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
            q, k, v = _attn_proj(cfg, p, h, cos, sin)
            k, v = _repeat_kv(cfg, k), _repeat_kv(cfg, v)
            if mask is None:
                o = attention(q, k, v, causal=True, window=window)
            else:
                o = dot_product_attention(q, k, v, causal=True, mask=mask, window=window)
            o = o.transpose(0, 2, 1, 3).reshape(B, S, -1)
            x = x + _attn_out(cfg, p, o)
            h = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
            x = x + _mlp(cfg, p, h)
            return with_constraint(x, ("batch", "length", "embed")), None

        return body

    x, _ = _scan_window_split(cfg, make_body, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = _head_logits(params, cfg, x)
    return with_constraint(logits.astype(jnp.float32), ("batch", "length", "vocab_out"))


def forward_layers(
    layer_params: Params,
    cfg: DecoderConfig,
    x: jnp.ndarray,  # [B, S, E] activations entering the span
    cos: jnp.ndarray,
    sin: jnp.ndarray,
) -> jnp.ndarray:
    """Run a CONTIGUOUS SPAN of stacked decoder layers on activations.

    The pipeline-parallel building block (parallel/pipeline.py): each pipeline
    stage holds ``L/P`` layers ([Lp, ...] leaves of ``params['layers']``) and
    advances a microbatch through just its span.  Full causal attention only —
    the window split of :func:`forward` is per-absolute-layer-index state that
    a span cannot see; windowed families bound their own context instead
    (same restriction as :func:`forward_long`).
    """
    B, S = x.shape[0], x.shape[1]

    def body(x, p):
        h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        q, k, v = _attn_proj(cfg, p, h, cos, sin)
        k, v = _repeat_kv(cfg, k), _repeat_kv(cfg, v)
        o = attention(q, k, v, causal=True)
        o = o.transpose(0, 2, 1, 3).reshape(B, S, -1)
        x = x + _attn_out(cfg, p, o)
        h = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
        x = x + _mlp(cfg, p, h)
        return x, None

    x, _ = jax.lax.scan(body, x, layer_params)
    return x


def forward_long(
    params: Params,
    cfg: DecoderConfig,
    input_ids: jnp.ndarray,  # [B, S]; S sharded over the mesh `seq` axis
    mesh,
) -> jnp.ndarray:
    """Sequence-parallel forward for long contexts: activations shard over the
    ``seq`` axis and attention runs as ring attention — K/V chunks rotate around
    the ICI ring (O(S/n) attention memory per chip).  The reference caps context
    at 8k instead (SURVEY.md §5.7); this is the scale-it path.

    Semantics match :func:`forward` exactly (same params, causal masking).
    Sliding-window families are rejected: the ring rotation assumes full
    causal attention (a window shorter than one shard would make most hops
    no-ops; implement block-skipping rotation before lifting this).
    """
    from ..ops.ring_attention import ring_attention

    if _window_split(cfg) < cfg.num_layers:
        # configs where window_layer_start >= num_layers are de-facto full
        # attention (HF layer_types all "full_attention") and pass through
        raise NotImplementedError(
            "forward_long (ring attention) does not support sliding-window "
            "attention; use forward() — windowed models bound their own context"
        )

    B, S = input_ids.shape
    cos, sin = _rope_tables(cfg, S)
    x = _embed(params, cfg, input_ids)
    x = with_constraint(x, ("batch", "length", "embed"))

    def body(x, p):
        h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        q, k, v = _attn_proj(cfg, p, h, cos, sin)
        k, v = _repeat_kv(cfg, k), _repeat_kv(cfg, v)
        o = ring_attention(q, k, v, mesh, causal=True)
        o = o.transpose(0, 2, 1, 3).reshape(B, S, -1)
        x = x + _attn_out(cfg, p, o)
        h = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
        x = x + _mlp(cfg, p, h)
        return with_constraint(x, ("batch", "length", "embed")), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = _head_logits(params, cfg, x)
    return with_constraint(logits.astype(jnp.float32), ("batch", "length", "vocab_out"))


@jax.named_scope("attn/kv_write")
def _write_cache(cache_k, new_k, starts):
    """vmap'd dynamic_update_slice: cache_k [B,KH,S,D], new_k [B,KH,Sn,D], starts [B]."""
    def upd(c, n, s):
        return jax.lax.dynamic_update_slice(c, n.astype(c.dtype), (0, s, 0))

    return jax.vmap(upd)(cache_k, new_k, starts)


def prefill(
    params: Params,
    cfg: DecoderConfig,
    input_ids: jnp.ndarray,  # [B, S] right-padded bucket
    lengths: jnp.ndarray,  # [B] true lengths
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Run prompts through the model.

    Returns (last-token logits [B,V] f32, ks [L,B,KH,S,D], vs) — the K/V tensors are
    written into the slots' pages by :func:`insert_sequences_paged` (prefill runs on
    its own small batch so it never touches other live slots' cache rows).
    """
    B, S = input_ids.shape
    cos, sin = _rope_tables(cfg, S)
    x = _embed(params, cfg, input_ids)

    def make_body(window):
        def body(x, p):
            h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
            q, k, v = _attn_proj(cfg, p, h, cos, sin)
            kr, vr = _repeat_kv(cfg, k), _repeat_kv(cfg, v)
            # No pad mask needed: input is right-padded, so causal masking already
            # restricts every real query to real keys; pad rows' outputs are discarded
            # (lengths-1 gather below) and their cache entries are overwritten/masked at
            # decode.  Keeping the call mask-free lets the flash kernel take long
            # buckets — windowed too (the kernel skips kv blocks below the band).
            o = attention(q, kr, vr, causal=True, window=window)
            o = o.transpose(0, 2, 1, 3).reshape(B, S, -1)
            x = x + _attn_out(cfg, p, o)
            h = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
            x = x + _mlp(cfg, p, h)
            return with_constraint(x, ("batch", "length", "embed")), (k, v)

        return body

    x, (ks, vs) = _scan_window_split(cfg, make_body, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    last = jnp.take_along_axis(
        x, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1
    )[:, 0]  # [B, E]
    logits = _head_logits(params, cfg, last)
    return logits.astype(jnp.float32), ks, vs


# ---------------------------------------------------------------------------
# Paged KV memory plane (vLLM-style block tables) — docs/KV_PAGING.md
# ---------------------------------------------------------------------------


class PagedKVCache(NamedTuple):
    """Page-pool KV cache.  k/v: [L, P, KH, page, D] — a flat pool of P
    fixed-size pages shared by every slot; lengths: [B] tokens present per
    slot.  Which physical page holds a slot's logical block lives in a
    separate ``[B, NB]`` block table (host-owned, passed per call — NOT part
    of the donated device chain), where entries >= P mean "unallocated"."""

    k: jnp.ndarray
    v: jnp.ndarray
    lengths: jnp.ndarray  # int32 [B]

    @property
    def n_pages(self) -> int:
        return self.k.shape[1]

    @property
    def page_size(self) -> int:
        return self.k.shape[3]


KV_KIND = "kv"  # what a cached token is: keys and values per KV head (models.module_for)


def kv_bytes_per_token(cfg: DecoderConfig, kv_dtype=None) -> int:
    """Bytes one cached token takes over all layers, K and V."""
    return cfg.num_layers * cfg.num_kv_heads * cfg.head_dim * 2 * jnp.dtype(kv_dtype or cfg.dtype).itemsize


def decode_kv_path(cfg: DecoderConfig, kv_dtype, page: int, *, fp8_dot: bool = False) -> str:
    return paged_decode_kv_path(kv_dtype or cfg.dtype, page, cfg.head_dim, fp8_dot=fp8_dot)


def init_paged_cache(
    cfg: DecoderConfig, batch: int, n_pages: int, page_size: int, dtype=None
) -> PagedKVCache:
    dtype = dtype or cfg.dtype
    shape = (cfg.num_layers, n_pages, cfg.num_kv_heads, page_size, cfg.head_dim)
    return PagedKVCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        lengths=jnp.zeros((batch,), jnp.int32),
    )


def paged_cache_shardings(cfg: DecoderConfig, mesh, batch: int) -> PagedKVCache:
    """NamedShardings for the page pool: KV heads over the TP (``model``) axis
    (replicated when the head count does not divide it); the page axis stays
    replicated across ``data`` — the block-table gather is global, so sharding
    pages would need collectives
    (multi-chip serving promotes to per-replica pools instead, ROADMAP 3)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.mesh import MODEL_AXIS

    if cfg.num_kv_heads % mesh.shape[MODEL_AXIS] == 0 and mesh.shape[MODEL_AXIS] > 1:
        kv = NamedSharding(mesh, P(None, None, MODEL_AXIS, None, None))
    else:
        kv = NamedSharding(mesh, P())
    return PagedKVCache(k=kv, v=kv, lengths=NamedSharding(mesh, P()))


def copy_pages(
    cache: PagedKVCache,
    src: jnp.ndarray,  # [n] int32 physical page ids
    dst: jnp.ndarray,  # [n] int32
) -> PagedKVCache:
    """Clone whole pages inside the pool (the allocator's copy-on-write
    primitive: a prefix sharer clones the boundary page its own suffix will
    write into).  Pure HBM copy; dst entries >= P drop."""
    P = cache.n_pages
    k = cache.k.at[:, jnp.minimum(dst, P)].set(
        jnp.take(cache.k, jnp.clip(src, 0, P - 1), axis=1), mode="drop"
    )
    v = cache.v.at[:, jnp.minimum(dst, P)].set(
        jnp.take(cache.v, jnp.clip(src, 0, P - 1), axis=1), mode="drop"
    )
    return PagedKVCache(k=k, v=v, lengths=cache.lengths)


@jax.named_scope("attn/kv_read")
def _gather_layer_rows(
    pool: jnp.ndarray,  # [L, P, KH, page, D]
    layer: jnp.ndarray,  # scalar int32
    block_tables: jnp.ndarray,  # [B, NB]
) -> jnp.ndarray:
    """One layer's logical KV view of each row, from its pages ->
    ``[B, KH, NB*page, D]``.  Unallocated blocks gather a clamped page —
    garbage the caller masks.

    Per LAYER, inside the layer scan, with the pool riding the scan carry
    (updated in place): gathering every layer's rows up front, scanning them
    out and scattering them back holds ``4 * L * B * S`` K/V vectors of
    temporaries next to the pool.  For a wave of 8 rows x 2048 positions of a
    32-layer, 8-KV-head model that program needed 6.6-8.9 GB of temporaries
    and would not load beside 7.5 GB of int8 weights on a 16 GB chip; this
    form needs 1.2-2.3 GB (tests/test_tpu_compile.py)."""
    L, P, KH, page, D = pool.shape
    B, NB = block_tables.shape
    phys = jnp.clip(block_tables, 0, P - 1).reshape(-1)
    layer_pool = jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)
    rows = jnp.take(layer_pool, phys, axis=0)  # [B*NB, KH, page, D]
    rows = rows.reshape(B, NB, KH, page, D)
    return rows.transpose(0, 2, 1, 3, 4).reshape(B, KH, NB * page, D)


@jax.named_scope("attn/kv_write")
def _scatter_layer_rows(
    pool: jnp.ndarray,  # [L, P, KH, page, D]
    layer: jnp.ndarray,  # scalar int32
    rows: jnp.ndarray,  # [B, KH, S, D] this layer's updated logical rows
    block_tables: jnp.ndarray,  # [B, NB]
    write_mask,  # [B, NB] bool — blocks this call actually wrote
) -> jnp.ndarray:
    """Write back only the blocks ``write_mask`` marks (per-row private pages
    — shared prefix pages must never be re-written, even with identical
    values, so the mask is part of the sharing contract).  Masked/pad blocks
    scatter to the P sentinel and drop."""
    L, P, KH, page, D = pool.shape
    B, NB = block_tables.shape
    for j in range(NB):
        blk = jax.lax.slice_in_dim(rows, j * page, (j + 1) * page, axis=2)
        tgt = jnp.where(write_mask[:, j], block_tables[:, j], P)
        pool = pool.at[layer, jnp.minimum(tgt, P)].set(
            blk.astype(pool.dtype), mode="drop"
        )
    return pool


def insert_sequences_paged(
    cache: PagedKVCache,
    ks: jnp.ndarray,  # [L, B, KH, Sb, D] from prefill
    vs: jnp.ndarray,
    lengths: jnp.ndarray,  # [B]
    slots: jnp.ndarray,  # [B] int32 — target slot (max_slots sentinel = pad row)
    block_tables: jnp.ndarray,  # [B, NB] — pad rows carry the P sentinel
) -> PagedKVCache:
    """Write prefilled K/V rows into their slots' pages (positions [0, Sb)).
    Blocks past a row's allocation (bucket padding beyond the reserved demand)
    and pad rows drop via the sentinel."""
    L, P, KH, page, D = cache.k.shape
    B, Sb = ks.shape[1], ks.shape[3]
    NB = block_tables.shape[1]
    nbw = min(NB, -(-Sb // page))
    pad_s = nbw * page - Sb
    if pad_s:
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, 0), (0, pad_s), (0, 0)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, 0), (0, pad_s), (0, 0)))
    k, v = cache.k, cache.v
    for j in range(nbw):
        blk_k = jax.lax.slice_in_dim(ks, j * page, (j + 1) * page, axis=3)
        blk_v = jax.lax.slice_in_dim(vs, j * page, (j + 1) * page, axis=3)
        tgt = jnp.minimum(block_tables[:, j], P)
        k = k.at[:, tgt].set(blk_k.astype(k.dtype), mode="drop")
        v = v.at[:, tgt].set(blk_v.astype(v.dtype), mode="drop")
    new_lengths = cache.lengths.at[slots].set(
        lengths.astype(cache.lengths.dtype), mode="drop"
    )
    return PagedKVCache(k=k, v=v, lengths=new_lengths)


def prefill_suffix_paged(
    params: Params,
    cfg: DecoderConfig,
    input_ids: jnp.ndarray,  # [B, C] right-padded suffix tokens (C static bucket)
    cache: PagedKVCache,
    block_tables: jnp.ndarray,  # [B, NB] — each row's full logical page chain
    slots: jnp.ndarray,  # [B] int32 (max_slots sentinel = pad row)
    starts: jnp.ndarray,  # [B] int32 — tokens already present (the prefix length)
    valids: jnp.ndarray,  # [B] int32 — real (non-pad) tokens per row
) -> tuple[jnp.ndarray, PagedKVCache]:
    """Batched continuation prefill on top of already-cached prefixes.

    Each row's page chain already holds ``starts[b]`` tokens of K/V (a shared
    system/RAG-context prefix found in the page pool's registry — the
    reference re-sends that context in full every turn,
    assistant/bot/services/context_service/steps/final_prompt.py:14, and
    re-prefills it from scratch).  Only the per-request suffix runs through
    the model: queries take absolute positions ``starts[b] + i`` (so RoPE
    matches a monolithic prefill exactly) and attend to the row's whole
    logical view up to their own position.  One dispatch serves a whole
    admission wave; ``slots``/``starts``/``valids`` are traced, so one
    compiled program per (batch-bucket, C) shape.

    Layer by layer: gather each row's logical view from its pages, run the
    suffix forward, then scatter back ONLY the blocks overlapping the written
    window ``[start, start+C)``.  Blocks below it are the shared prefix pages
    — physically shared with other requests, so they must not be touched
    (their gathered values are unchanged, but a duplicate-index scatter's
    winner is undefined).

    Returns (logits [B, V] f32 at each row's last real token, cache with
    ``lengths[slot] = start + valid``)."""
    B, C = input_ids.shape
    L, P, KH, page, D = cache.k.shape
    NB = block_tables.shape[1]
    S = NB * page
    pos = starts[:, None] + jnp.arange(C)[None, :]
    cos_t, sin_t = _rope_tables(cfg, S)
    cos, sin = cos_t[pos], sin_t[pos]
    x = _embed(params, cfg, input_ids)
    kpos = jnp.arange(S)[None, None, None, :]
    causal_keep = kpos <= pos[:, None, :, None]
    blk = jnp.arange(NB)
    write_mask = ((blk[None, :] + 1) * page > starts[:, None]) & (
        blk[None, :] * page < (starts + valids)[:, None]
    )

    def make_body(window):
        attn_mask = causal_keep
        if window is not None:
            attn_mask = attn_mask & (kpos > pos[:, None, :, None] - window)

        def body(carry, inputs):
            x, k_pool, v_pool = carry
            p, layer = inputs
            h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
            q, k, v = _attn_proj(cfg, p, h, cos, sin)
            k_row = _write_cache(
                _gather_layer_rows(k_pool, layer, block_tables), k, starts
            )
            v_row = _write_cache(
                _gather_layer_rows(v_pool, layer, block_tables), v, starts
            )
            o = gqa_dot_product_attention(q, k_row, v_row, mask=attn_mask)
            o = o.transpose(0, 2, 1, 3).reshape(B, C, -1)
            x = x + _attn_out(cfg, p, o)
            h = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
            x = x + _mlp(cfg, p, h)
            k_pool = _scatter_layer_rows(k_pool, layer, k_row, block_tables, write_mask)
            v_pool = _scatter_layer_rows(v_pool, layer, v_row, block_tables, write_mask)
            return (x, k_pool, v_pool), None

        return body

    (x, k, v), _ = _scan_window_split(
        cfg, make_body, (x, cache.k, cache.v), (params["layers"], jnp.arange(L))
    )
    lengths = cache.lengths.at[slots].set(
        (starts + valids).astype(cache.lengths.dtype), mode="drop"
    )
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    last = jnp.take_along_axis(
        x, jnp.maximum(valids - 1, 0)[:, None, None], axis=1
    )[:, 0]
    logits = _head_logits(params, cfg, last)
    return logits.astype(jnp.float32), PagedKVCache(k=k, v=v, lengths=lengths)


def prefill_chunk_paged(
    params: Params,
    cfg: DecoderConfig,
    input_ids: jnp.ndarray,  # [1, C] one chunk of one prompt
    cache: PagedKVCache,
    block_table: jnp.ndarray,  # [NB] int32 — the target slot's page chain
    slot: jnp.ndarray,  # scalar int32
    start: jnp.ndarray,  # scalar int32 — tokens already written for this slot
    valid: jnp.ndarray,  # scalar int32 — real (non-pad) tokens in this chunk
) -> tuple[jnp.ndarray, PagedKVCache]:
    """Extend one slot's page chain by a chunk of prompt tokens.

    The disaggregation primitive (SURVEY.md §7 hard part (c)): instead of one
    monolithic prefill call that stalls every live decode stream for its full
    duration, the engine splits long prompts into fixed-size chunks and
    interleaves one chunk per decode tick — the decode head-of-line delay is
    bounded by a chunk, not the prompt.  ``slot``/``start``/``valid`` are
    traced scalars, so one compiled program serves every chunk position of
    every request.  Write-back covers only the blocks overlapping
    ``[start, start+C)`` (earlier blocks may be shared prefix pages).

    Returns (logits [1, V] f32 at chunk index ``valid-1``, cache with
    ``lengths[slot] = start + valid``).  Only the final chunk's logits are used."""
    B, C = input_ids.shape
    L, P, KH, page, D = cache.k.shape
    NB = block_table.shape[0]
    S = NB * page
    pos = start + jnp.arange(C)
    cos_t, sin_t = _rope_tables(cfg, S)
    cos, sin = cos_t[pos], sin_t[pos]
    x = _embed(params, cfg, input_ids)
    kpos = jnp.arange(S)[None, None, None, :]
    causal_keep = kpos <= pos[None, None, :, None]
    bt = block_table[None, :]
    blk = jnp.arange(NB)
    write_mask = (((blk + 1) * page > start) & (blk * page < start + valid))[None, :]

    def make_body(window):
        attn_mask = causal_keep
        if window is not None:
            attn_mask = attn_mask & (kpos > pos[None, None, :, None] - window)

        def body(carry, inputs):
            x, k_pool, v_pool = carry
            p, layer = inputs
            h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
            q, k, v = _attn_proj(cfg, p, h, cos, sin)
            k_row = _gather_layer_rows(k_pool, layer, bt)
            v_row = _gather_layer_rows(v_pool, layer, bt)
            k_row = jax.lax.dynamic_update_slice(
                k_row, k.astype(k_row.dtype), (0, 0, start, 0)
            )
            v_row = jax.lax.dynamic_update_slice(
                v_row, v.astype(v_row.dtype), (0, 0, start, 0)
            )
            o = gqa_dot_product_attention(q, k_row, v_row, mask=attn_mask)
            o = o.transpose(0, 2, 1, 3).reshape(B, C, -1)
            x = x + _attn_out(cfg, p, o)
            h = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
            x = x + _mlp(cfg, p, h)
            k_pool = _scatter_layer_rows(k_pool, layer, k_row, bt, write_mask)
            v_pool = _scatter_layer_rows(v_pool, layer, v_row, bt, write_mask)
            return (x, k_pool, v_pool), None

        return body

    (x, k, v), _ = _scan_window_split(
        cfg, make_body, (x, cache.k, cache.v), (params["layers"], jnp.arange(L))
    )
    lengths = jax.lax.dynamic_update_index_in_dim(
        cache.lengths, (start + valid).astype(cache.lengths.dtype), slot, 0
    )
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    last = jax.lax.dynamic_index_in_dim(x[0], jnp.maximum(valid - 1, 0), 0, keepdims=False)
    logits = _head_logits(params, cfg, last)[None]
    return logits.astype(jnp.float32), PagedKVCache(k=k, v=v, lengths=lengths)


def decode_step_paged(
    params: Params,
    cfg: DecoderConfig,
    tokens: jnp.ndarray,  # [B] int32 — last sampled token per slot
    cache: PagedKVCache,
    block_tables: jnp.ndarray,  # [B, NB] int32
    *,
    active: Optional[jnp.ndarray] = None,  # [B] bool; inactive slots are frozen
    attn_fp8: bool = False,  # static: fp8 in-dot attention (requires fp8 pool)
) -> tuple[jnp.ndarray, PagedKVCache]:
    """One autoregressive step for every active slot against the page pool
    -> (logits [B,V] f32, cache).

    Per layer the step writes one ``[KH, D]`` row per slot into
    ``block_table[b, pos // page]`` at offset ``pos % page`` and reads the
    pages the slot's query can see.  Inactive rows and rows whose position
    has run past their allocation write NOTHING: a garbage write could land
    in a page since re-assigned to another request, so this is part of the
    correctness contract on both paths below.

    Which path is :func:`~..ops.attention.paged_decode_kv_path`'s answer,
    from the platform and the pool's shape (docs/KV_PAGING.md "Decode
    read/write"):

    - ``"kernel"`` (a TPU): the 5-D pool rides the layer scan's CARRY with the
      layer index in ``xs``, and one Pallas call per layer
      (:func:`~..ops.attention.paged_decode_update_attend`) patches the row in
      place and DMAs the live pages — nothing in the step loop or the layer
      loop makes a value the size of a layer of the pool.  Scanning the pool
      ``xs -> ys`` around an XLA scatter and gather, the form below, made XLA
      slice, re-lay-out and write back a whole layer for K and for V in every
      layer: 5.5 ms of a 17.8 ms step at 7B widths (PERF.md section 5).
    - ``"xla"`` (the CPU; toy shapes; ``attn_fp8``): a per-row scatter with
      ``mode="drop"`` (the P sentinel drops) and
      :func:`~..ops.attention.paged_gqa_decode_attention` — an online
      softmax over the live pages (tests/test_kv_paging.py compares it with
      plain masked attention).  The kernel is tested against this form
      (tests/test_paged_decode_kernel.py)."""
    B = tokens.shape[0]
    L, P, KH, page, D = cache.k.shape
    NB = block_tables.shape[1]
    S = NB * page
    if active is None:
        active = jnp.ones((B,), bool)
    active = active & (cache.lengths < S)
    positions = jnp.minimum(cache.lengths, S - 1)
    cos_t, sin_t = _rope_tables(cfg, S)
    cos = cos_t[positions][:, None, :]
    sin = sin_t[positions][:, None, :]

    x = _embed(params, cfg, tokens)[:, None, :]  # [B,1,E]

    def layer(x, p, attend):
        """One decoder layer; ``attend(q, k, v) -> (o [B,H,1,D], pools)``
        writes the step's K/V and reads the cache."""
        h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        o, pools = attend(*_decode_qkv(cfg, p, h, cos, sin))
        o = o.transpose(0, 2, 1, 3).reshape(B, 1, -1)
        x = x + _attn_out(cfg, p, o)
        h = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
        return x + _mlp(cfg, p, h), pools

    if paged_decode_kv_path(cache.k.dtype, page, D, fp8_dot=attn_fp8) == "kernel":

        def make_body(window):
            # the pages this step touches: the same for every layer
            plan = paged_decode_plan(
                block_tables, positions, active, n_pages=P, page=page, window=window
            )

            def body(carry, inputs):
                x, k_pool, v_pool = carry
                p, layer_idx = inputs

                def attend(q, k, v):
                    o, k_new, v_new = paged_decode_update_attend(
                        q, k, v, k_pool, v_pool, layer_idx, block_tables,
                        positions, plan, window=window,
                    )
                    return o, (k_new, v_new)

                x, pools = layer(x, p, attend)
                return (x, *pools), None

            return body

        (x, ks, vs), _ = _scan_window_split(
            cfg, make_body, (x, cache.k, cache.v), (params["layers"], jnp.arange(L))
        )
    else:
        blk = positions // page
        off = positions % page
        phys = jnp.take_along_axis(block_tables, blk[:, None], axis=1)[:, 0]
        phys_w = jnp.where(active, jnp.minimum(phys, P), P)

        def make_body(window):
            def body(x, inputs):
                p, k_pool, v_pool = inputs  # [P, KH, page, D] per layer

                def attend(q, k, v):
                    with jax.named_scope("attn/kv_write"):
                        k_new = k_pool.at[phys_w, :, off, :].set(
                            k[:, :, 0, :].astype(k_pool.dtype), mode="drop"
                        )
                        v_new = v_pool.at[phys_w, :, off, :].set(
                            v[:, :, 0, :].astype(v_pool.dtype), mode="drop"
                        )
                    o = paged_gqa_decode_attention(
                        q, k_new, v_new, block_tables, positions,
                        active=active, window=window, fp8_dot=attn_fp8,
                    )
                    return o, (k_new, v_new)

                return layer(x, p, attend)

            return body

        x, (ks, vs) = _scan_window_split(
            cfg, make_body, x, (params["layers"], cache.k, cache.v)
        )
    new_cache = PagedKVCache(
        k=ks,
        v=vs,
        lengths=jnp.where(active, cache.lengths + 1, cache.lengths),
    )
    x = rms_norm(x[:, 0], params["final_norm"], cfg.rms_norm_eps)
    logits = _head_logits(params, cfg, x)
    return logits.astype(jnp.float32), new_cache


@jax.named_scope("attn/qkv")
def _tree_qkv(cfg: DecoderConfig, p: Params, h: jnp.ndarray, cos, sin):
    """QKV projections + RoPE for the tree-verify forward, ``h`` [B, T, E].

    Deliberately NOT :func:`_attn_proj`: that helper annotates the position
    dim with the logical ``length`` axis, and on this jaxlib the SPMD
    partitioner miscompiles the fused speculative tick whenever the tiny
    tree dim happens to divide the mesh ``seq`` axis — the "replicated"
    input tokens come back multiplied by the axis size (observed 2x: token
    351 -> 702 on a seq=2 mesh; the root cause of the old engine-level
    greedy-equivalence xfail).  A <= 32-wide dim is not worth sequence-
    sharding anyway, so the tree forward keeps it unannotated/replicated,
    exactly like :func:`decode_step_paged`'s Sq=1."""
    B, T, _ = h.shape
    H, KH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = qeinsum("bse,eo->bso", h, p["wq"], cfg.dtype)
    k = qeinsum("bse,eo->bso", h, p["wk"], cfg.dtype)
    v = qeinsum("bse,eo->bso", h, p["wv"], cfg.dtype)
    if cfg.attn_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = apply_rope(q.reshape(B, T, H, D), cos, sin).transpose(0, 2, 1, 3)
    k = apply_rope(k.reshape(B, T, KH, D), cos, sin).transpose(0, 2, 1, 3)
    v = v.reshape(B, T, KH, D).transpose(0, 2, 1, 3)
    return q, k, v


def verify_tree_step_paged(
    params: Params,
    cfg: DecoderConfig,
    tree: jnp.ndarray,  # [B, T]
    cache: PagedKVCache,
    block_tables: jnp.ndarray,  # [B, NB]
    depths: jnp.ndarray,
    anc_mask: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Tree-verify step: one forward over every node of the speculation tree.

    Node t takes absolute position ``lengths[b] + depths[t]`` (RoPE matches
    what sequential decode would use), attends to the VERIFIED prefix (cache
    positions < lengths) plus its own root-path ancestors through the tree's
    freshly-projected K/V.  READ-ONLY with respect to the cache: returns
    ``(logits [B,T,V], tks, tvs [L,B,KH,T,D])`` and the caller commits ONLY
    the accepted root-to-leaf path via :func:`commit_tree_path_paged`.

    The prefix is read IN PLACE from the page pool
    (:func:`~..ops.attention.paged_tree_attention` — one block-table gather
    per logical page inside the online-softmax loop, the decode read's
    structure with tree-wide queries).  The speculative tick is a
    steady-state decode path, so it must not materialise a dense
    [L, B, KH, S, D] copy of every logical row per tick the way the
    batched-prefill gathers do.

    Traces under ``constraints_disabled()``: any logical ``length``
    annotation on the tiny tree dim (e.g. :func:`_mlp`'s hidden constraint)
    lets this jaxlib's SPMD partitioner sequence-shard it when T happens to
    divide the mesh ``seq`` axis, and that miscompiles the fused speculative
    tick (see :func:`_tree_qkv`)."""
    from ..parallel.sharding import constraints_disabled

    B, T = tree.shape
    L, P, KH, page, D = cache.k.shape
    NB = block_tables.shape[1]
    S = NB * page
    lengths = cache.lengths
    pos = jnp.minimum(lengths[:, None] + depths[None, :], S - 1)
    cos_t, sin_t = _rope_tables(cfg, S)
    cos, sin = cos_t[pos], sin_t[pos]
    x = _embed(params, cfg, tree)

    def make_body(window):
        def body(x, inputs):
            p, k_pool, v_pool = inputs  # [P, KH, page, D] per layer
            h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
            q, k, v = _tree_qkv(cfg, p, h, cos, sin)
            o = paged_tree_attention(
                q, k_pool, v_pool, block_tables, lengths, k, v,
                anc_mask, depths, window=window,
            )
            o = o.transpose(0, 2, 1, 3).reshape(B, T, -1)
            x = x + _attn_out(cfg, p, o)
            h = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
            x = x + _mlp(cfg, p, h)
            return x, (k, v)

        return body

    with constraints_disabled():
        x, (tks, tvs) = _scan_window_split(
            cfg, make_body, x, (params["layers"], cache.k, cache.v)
        )
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        logits = _head_logits(params, cfg, x)
    return logits.astype(jnp.float32), tks, tvs


def _gather_tree_path(tks: jnp.ndarray, path_idx: jnp.ndarray) -> jnp.ndarray:
    """[L, B, KH, T, D] tree K/V stack + [B, C] flat node ids -> [L, B, KH, C, D]."""
    L, B, KH, T, D = tks.shape
    idx = jnp.broadcast_to(
        path_idx[None, :, None, :, None], (L, B, KH, path_idx.shape[1], D)
    )
    return jnp.take_along_axis(tks, idx, axis=3)


def commit_tree_path_paged(
    cache: PagedKVCache,
    tks: jnp.ndarray,  # [L, B, KH, T, D] from verify_tree_step_paged
    tvs: jnp.ndarray,
    path_idx: jnp.ndarray,  # [B, C]
    block_tables: jnp.ndarray,  # [B, NB]
    n_commit: jnp.ndarray,  # [B] — tokens of the path to commit (1 + accepted)
    active: jnp.ndarray,  # [B] bool
) -> PagedKVCache:
    """Accepted-path commit: a drop-masked ``[B, C]`` scatter through
    the block table — position ``lengths + j`` lands in page
    ``block_table[b, (lengths+j) // page]`` at offset ``(lengths+j) % page``.
    ``cache.lengths`` is NOT advanced (the caller sets it once acceptance is
    known).

    The commit may NOT write garbage:
    a rejected-candidate write beyond the accepted run could land in the
    slot's reservation tail — harmless — but one beyond the reservation
    would alias a page since handed to another request.  So the scatter
    drops (page-sentinel discipline, PR 6) everything except the accepted
    prefix of active rows inside the row's allocation: ``j < n_commit``,
    ``active``, block table entry < P, and position inside the logical row.
    """
    L, P, KH, page, D = cache.k.shape
    B, C = path_idx.shape
    NB = block_tables.shape[1]
    S = NB * page
    lengths = cache.lengths
    pk = _gather_tree_path(tks, path_idx)  # [L, B, KH, C, D]
    pv = _gather_tree_path(tvs, path_idx)
    k, v = cache.k, cache.v
    for j in range(C):
        pos = lengths + j
        ok = active & (j < n_commit) & (pos < S)
        blk = jnp.minimum(pos // page, NB - 1)
        off = jnp.where(ok, pos % page, 0)
        phys = jnp.take_along_axis(block_tables, blk[:, None], axis=1)[:, 0]
        phys_w = jnp.where(ok, jnp.minimum(phys, P), P)
        # advanced indices (dims 1 and 3) are separated by a slice, so the
        # batch dim moves to the FRONT of the updated view: values [B, L, KH, D]
        kj = pk[:, :, :, j, :].transpose(1, 0, 2, 3)
        vj = pv[:, :, :, j, :].transpose(1, 0, 2, 3)
        k = k.at[:, phys_w, :, off, :].set(kj.astype(k.dtype), mode="drop")
        v = v.at[:, phys_w, :, off, :].set(vj.astype(v.dtype), mode="drop")
    return PagedKVCache(k=k, v=v, lengths=lengths)
