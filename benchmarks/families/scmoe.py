"""The ``scmoe`` family: LongCat-Flash's shortcut-connected double layer (two
latent-attention sublayers, two dense SwiGLUs, one expert layer beside them
that joins the residual at the layer's end), a softmax router over the routed
experts and ``zero_expert_num`` identity experts, of which this rank of an
expert-parallel deployment holds a share
(``benchmarks/configs/longcat-flash-omni-ep32.json``; served bfloat16).

Seeded weights in the program's layout (``dense_layers``: the ``2 *
num_layers`` sublayers in order, each the leaves a dense layer has;
``moe_layers``: the ``num_layers`` routers, correction biases and held experts;
only the held experts and the vocabulary slice are ever drawn), the plain
reference (``benchmarks/reference/scmoe.py``, given the same share), the
controls, the limits, and the bytes and operations of a decode step.  It
imports nothing of the program and shares with ``families/mla_moe.py`` what is
the same (the share, the top leaves, the decode tick's readers).

**The share.**  ``hf["n_routed_experts"]`` counts the experts HELD here,
``hf["ep_size"]`` the ranks that share a layer, ``hf["ep_rank"]`` which of them
this is; the router is ``n_routed_experts * ep_size + zero_expert_num`` wide, as
published.  The identity experts' part is every rank's in full: a zero-compute
expert is evaluated where the token lives.

**The draw of the up-projections.**  Every matrix is normal with ``fan_in^-0.5``
but the three out of the low-rank latents, ``w_uq``, ``w_uk`` and ``w_uv``,
which are drawn with ``hidden^-0.5``: the variance a full-rank projection from
the hidden state would give, and so the misalignment that ``mla_scale_q_lora``
and ``mla_scale_kv_lora`` exist to correct (a query out of a rank-1536 latent
has 1536/6144 of that variance, times ``sqrt(6144/1536)^2`` is one).  Scaled
queries, keys and values then have unit variance like every other activation
here.  Drawn with ``rank^-0.5`` they are unit variance BEFORE the scales, the
attention scores come out ``2 x 3.46`` times too sharp (a standard deviation of
5.8: a near-argmax over the context) and bfloat16's rounding of a score moves
the softmax five times as far as in the other latent families: the first chip
runs of this family read ``logit_gap_p99`` 0.44-0.49 and a fifth of the served
tokens not the reference's first, program and reference being the same
function (a one-layer cut at full width on the CPU reads the same 2.2-2.7% of a
logit's spread with the scales and 0.45% without: my runs, PR 44).

**The router's draw.**  ``router`` is normal with ``hidden^-0.5`` like every
matrix, so a token's logits over the 768 outputs are standard normal and its
softmax scores have mean 1/768 and a standard deviation of 1.3/768; the 12
picks hold 0.10-0.13 of the mass and weigh ``6 x`` that together, a third of
it on identity experts (even routing: 256 of 768).  ``router_bias`` is drawn
normal with a standard deviation of ``0.15 / width``, a tenth of the scores'
spread, rounded to bfloat16 so that the served leaf and the reference's are the
same numbers; it changes picks and never a weight (``tests/test_scmoe.py``).

**Near-tied picks, and why the limits are on the gaps' 99th percentile and
mean**: as ``families/mla_moe.py`` says of its own.  The program routes from a
bfloat16 residual stream, the reference from a float32 one; where the last pick
and the best output left out are close the two pick differently, and a flip
between a held expert, an absent one and an identity expert adds or removes one
weighted result.  The reference counts such pairs (stderr, every check).
"""

from __future__ import annotations

import functools
import math
import os
from typing import Any, Dict, List, Optional, Sequence

from benchmarks.families import mla_moe as base
from benchmarks.reference import scmoe as reference
from benchmarks.weights import key_words, scalar_items

# re-exported: the readers of the decode tick (moe_*, mla_dev_share, ...) ask the family
share, top_leaves = base.share, base.top_leaves
live_context_tokens, tick_scope_seconds, traced_decode_steps = (
    base.live_context_tokens, base.tick_scope_seconds, base.traced_decode_steps)
experts_hit_per_layer_step, latent_row_bytes = base.experts_hit_per_layer_step, base.latent_row_bytes

# Every matrix rounded through float8 e4m3 (the nearest precision below the stated bfloat16); the
# reference WITHOUT the identity experts' part (a program that dropped it); the reference without the
# two attention scales (a program that read the config as DeepSeek-V3's).
CONTROLS = ("w_fp8", "no_zero", "no_scale")

# Set from longcat-flash-omni-ep32 on the chip (PERF.md section 2; my chip runs, PR 44, call 2), the check being six requests a
# run (1,400-1,750 served tokens), 9 sound runs on 9 seeds (4444100001-06, 4444100101-02, 4444300001) against the controls on 1
# (4444300001):
#   logit_gap_p99   sound 0.0000-0.0065   w_fp8 0.229    no_zero 0.776    no_scale 1.917
#   logit_gap_mean  sound 0.00008-0.00020 w_fp8 0.0166   no_zero 0.0483   no_scale 0.631
#   logit_gap_max   sound 0.026-0.045     w_fp8 0.546    no_zero 1.131    no_scale 2.327   (no limit: routing flips, as mla_moe)
# 0.05 is 7.7 times the sound runs' largest p99 and 4.6 times under the nearest control's (float8 weights); 0.002 is 10 times over
# and 8.3 times under.  Every control fails BOTH limits.  The sound gaps are a tenth of mla_moe's (0.002-0.083 / 0.0004-0.0025): a
# routing flip moves a logit less here (a pick weighs ~0.05 of a unit-norm stream, unnormalised softmax x 6, where a sigmoid pick
# normalised over 8 weighs 0.31), and 0.6-2.0% of the served tokens are not the reference's first (A.X-K1: 1.3-5.8%).  Under the
# family's first draw of the up-projections (the docstring) the same check read 0.444 / 0.0374 and 0.489 / 0.0395.
LIMITS = {"logit_gap_p99": 0.05, "logit_gap_mean": 0.002}

BIAS_SPREAD = 0.15  # the bias's standard deviation times the router's width
ATTN = base.ATTN
NORMS = base.NORMS
FFN = ("w_gate", "w_up", "w_down")
UP = ("w_uq", "w_uk", "w_uv")  # the up-projections out of the two low-rank latents


def routed(hf: Dict[str, Any]) -> int:
    """Routed experts over all ranks: the router is this plus ``zero_expert_num`` wide."""
    return share(hf)[1]


def sub_shapes(hf: Dict[str, Any]) -> Dict[str, tuple]:
    """One sublayer: latent attention, its norms and a dense SwiGLU."""
    E, H, F = hf["hidden_size"], hf["num_attention_heads"], hf["ffn_hidden_size"]
    R, C = hf["q_lora_rank"], hf["kv_lora_rank"]
    dn, dr, dv = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"]
    return {
        "w_dq": (E, R), "w_uq": (R, H * (dn + dr)), "w_dkv": (E, C + dr), "w_uk": (C, H * dn),
        "w_uv": (C, H * dv), "wo": (H * dv, E),
        "attn_norm": (E,), "q_norm": (R,), "kv_norm": (C,), "mlp_norm": (E,),
        "w_gate": (E, F), "w_up": (E, F), "w_down": (F, E),
    }


def moe_shapes(hf: Dict[str, Any]) -> Dict[str, tuple]:
    """One expert layer: the router over all routed and identity experts, its bias, the HELD experts."""
    E, Fm, held = hf["hidden_size"], hf["expert_ffn_hidden_size"], share(hf)[0]
    width = routed(hf) + int(hf.get("zero_expert_num") or 0)
    return {"router": (E, width), "router_bias": (width,),
            "w_gate": (held, E, Fm), "w_up": (held, E, Fm), "w_down": (held, Fm, E)}


def _leaves(shapes: Dict[str, tuple], key, hidden: int) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    keys = dict(zip(sorted(shapes), jax.random.split(key, len(shapes))))
    out = {}
    for name, shape in shapes.items():
        z = jax.random.normal(keys[name], shape, jnp.float32)
        if name in NORMS:
            w = 1.0 + 0.1 * z
        elif name == "router_bias":
            w = BIAS_SPREAD / shape[0] * z
        elif name in UP:  # out of a low-rank latent: the variance of a projection from the hidden state (the docstring)
            w = z * hidden ** -0.5
        else:
            w = z * shape[-2] ** -0.5
        out[name] = w.astype(jnp.bfloat16)
    return out


def layer_leaves(hf: Dict[str, Any], key) -> Dict[str, Any]:
    """One double layer from its key, bfloat16: ``{"sub": the two sublayers'
    leaves on a leading axis of 2, "moe": router, bias, held experts}``;
    matrices normal with ``fan_in^-0.5`` (the three of ``UP``: ``hidden^-0.5``), norms near one."""
    import jax
    import jax.numpy as jnp

    E = hf["hidden_size"]
    subs = [_leaves(sub_shapes(hf), jax.random.fold_in(key, i), E) for i in (0, 1)]
    return {"sub": {k: jnp.stack([s[k] for s in subs]) for k in subs[0]},
            "moe": _leaves(moe_shapes(hf), jax.random.fold_in(key, 2), E)}


def _root(seed: int):
    import jax
    import jax.numpy as jnp

    return jax.random.wrap_key_data(jnp.asarray(key_words(seed, 0x53434D), jnp.uint32))


def layer_key(seed: int, layer):
    import jax

    return jax.random.fold_in(_root(seed), layer)


def all_keys(seed: int, hf: Dict[str, Any]):
    """(top key, the double layers' keys): :func:`stacked_fn`'s arguments."""
    import jax
    import jax.numpy as jnp

    return (jax.random.fold_in(_root(seed), 0xFFFF),
            jax.vmap(lambda i: layer_key(seed, i))(jnp.arange(int(hf["num_layers"]))))


def stacked_fn(hf: Dict[str, Any], head_ids):
    """The one jitted call that makes every served weight."""
    import jax

    @jax.jit
    def make(top_k, layer_ks):
        layers = jax.lax.map(functools.partial(layer_leaves, hf), layer_ks)  # a layer at a time: no temporaries
        return {
            **top_leaves(hf, top_k, head_ids),
            # [layers, 2, ...] -> the 2 * layers sublayers in order
            "dense_layers": {k: v.reshape((-1,) + v.shape[2:]) for k, v in layers["sub"].items()},
            "moe_layers": layers["moe"],
        }

    return make


def served_params(conf: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """All served weights on the device, from one jitted call, in the program's
    parameter layout (a checkpoint's form); every leaf bfloat16."""
    hf = conf["hf"]
    return stacked_fn(hf, tuple(conf["weights"]["head_ids"]))(*all_keys(seed, hf))


@functools.lru_cache(maxsize=None)
def _float32_layer_fn(hf_items, rounded: bool):
    import jax
    import jax.numpy as jnp

    hf = dict(hf_items)

    def cast(name, w):
        w = w.astype(jnp.float32)
        return reference.round_through_e4m3(w) if rounded and name not in NORMS + ("router_bias",) else w

    @jax.jit
    def make(key):
        return {part: {name: cast(name, w) for name, w in leaves.items()} for part, leaves in layer_leaves(hf, key).items()}

    return make


def float32_layer(hf: Dict[str, Any], seed: int, layer: int, rounded: bool = False) -> Dict[str, Any]:
    """Double layer ``layer`` as the reference takes it; ``rounded`` gives the
    control: every matrix rounded through float8 e4m3 first."""
    return _float32_layer_fn(scalar_items(hf), rounded)(layer_key(seed, layer))


def reference_logits(conf: Dict[str, Any], seed: int, sequences: Sequence[Sequence[int]],
                     first_positions: Sequence[int], columns: Sequence[int],
                     control: Optional[str] = None, counts: Optional[List[float]] = None) -> List[Any]:
    """``reference.logits_at`` over weights regenerated from the seed, a layer
    resident at a time, given this rank's share.  ``control`` names one of ``CONTROLS``."""
    import jax
    import jax.numpy as jnp

    if control not in (None, *CONTROLS):
        raise ValueError(f"the scmoe family has no control {control!r}: {CONTROLS}")
    hf = conf["hf"]
    top = jax.tree.map(lambda x: x.astype(jnp.float32),
                       jax.jit(lambda k: top_leaves(hf, k, tuple(conf["weights"]["head_ids"])))(all_keys(seed, hf)[0]))
    return reference.logits_at(
        hf, lambda i: float32_layer(hf, seed, i, control == "w_fp8"), top, sequences, first_positions,
        n_routed=routed(hf), first_expert=share(hf)[2], zero=control != "no_zero", scaled=control != "no_scale",
        columns=columns, counts=counts,
    )


# ---------------------------------------------------------------------------
# bytes and operations of a decode step, from shapes (bfloat16: 2 bytes)
# ---------------------------------------------------------------------------


def expert_bytes(conf: Dict[str, Any]) -> int:
    """One routed expert's three matrices."""
    hf = conf["hf"]
    return 2 * 3 * hf["hidden_size"] * hf["expert_ffn_hidden_size"]


def attention_sublayers(conf: Dict[str, Any]) -> int:
    """Rows a cached token takes: one per attention sublayer, two a layer."""
    return 2 * int(conf["hf"]["num_layers"])


def weight_bytes(conf: Dict[str, Any], experts_hit: Optional[float] = None) -> Dict[str, float]:
    """Bytes of weights a decode step reads, by part: every weight of both
    sublayers of every layer once, the routers, and of the held experts those
    HIT (``experts_hit``: the mean number of distinct held experts a step hits
    in an expert layer, the program's counter; None counts every held expert).
    An identity expert has no weight."""
    hf = conf["hf"]
    L, held = int(hf["num_layers"]), share(hf)[0]
    hit = held if experts_hit is None else min(float(experts_hit), held)
    sub, moe = sub_shapes(hf), moe_shapes(hf)
    return {
        "attention": 2 * L * 2 * sum(math.prod(sub[k]) for k in ATTN + NORMS),
        "dense_ffn": 2 * L * 2 * sum(math.prod(sub[k]) for k in FFN),
        "router": L * 2 * (math.prod(moe["router"]) + math.prod(moe["router_bias"])),
        "experts": L * hit * expert_bytes(conf),
        "head": 2 * hf["hidden_size"] * hf["vocab_size"] + 2 * hf["hidden_size"],
    }


def decode_step_bytes(conf: Dict[str, Any], live_context_tokens: float, experts_hit: Optional[float] = None) -> float:
    """The least a decode step must move: the weights once (of the experts,
    those hit) and the latent row of every live context token once per
    attention sublayer, twice a layer."""
    return (sum(weight_bytes(conf, experts_hit).values())
            + attention_sublayers(conf) * latent_row_bytes(conf) * live_context_tokens)


def decode_step_flops(conf: Dict[str, Any], rows: float, live_context_tokens: float,
                      local_picks_per_row: Optional[float] = None) -> float:
    """Multiply-adds x 2 of one step: per row both sublayers' projections and
    dense SwiGLUs, the router and the picks that land on held experts (default:
    an even share; an identity pick is one multiply-add a lane, not counted),
    and the absorbed attention over the latent (scores over 576, values over
    512, per head) in every attention sublayer."""
    hf = conf["hf"]
    L = int(hf["num_layers"])
    held, n_routed, _ = share(hf)
    H, C, dr = hf["num_attention_heads"], hf["kv_lora_rank"], hf["qk_rope_head_dim"]
    sub, moe = sub_shapes(hf), moe_shapes(hf)
    width = moe["router"][1]
    picks = hf["moe_topk"] * held / width if local_picks_per_row is None else local_picks_per_row
    per_row = (2 * L * sum(math.prod(sub[k]) for k in ATTN + FFN)
               + L * (math.prod(moe["router"]) + picks * expert_bytes(conf) / 2)
               + hf["hidden_size"] * hf["vocab_size"])
    return 2.0 * rows * per_row + 2.0 * attention_sublayers(conf) * H * (2 * C + dr) * live_context_tokens


def sizing_programs(conf: Dict[str, Any], sharding):
    """The family's own big programs for ``sizing.py``: every served weight in
    one call, and one reference double layer at the check's size."""
    import jax
    import jax.numpy as jnp

    hf = conf["hf"]

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)

    keys = jax.eval_shape(lambda: all_keys(0, hf))
    B, T = int(os.environ.get("SIZING_B", 6)), int(os.environ.get("SIZING_T", 1536))
    layer = jax.eval_shape(lambda: float32_layer(hf, 0, 0))
    x = jax.ShapeDtypeStruct((B, T, hf["hidden_size"]), jnp.float32, sharding=sharding)
    cs = jax.ShapeDtypeStruct((T, hf["qk_rope_head_dim"] // 2), jnp.float32, sharding=sharding)
    return [
        ("scmoe.stacked (all served weights, one call)", stacked_fn(hf, tuple(conf["weights"]["head_ids"])), shaped(keys)),
        (f"reference double layer, float32 highest, [{B}, {T}]",
         reference._layer_fn(scalar_items(hf), routed(hf), share(hf)[2]),
         (x, shaped(layer), cs, cs, jax.ShapeDtypeStruct((B, T), jnp.bool_, sharding=sharding))),
    ]


# ---------------------------------------------------------------------------
# what this family's per-layer readers share (benchmarks/layer_metrics/scmoe_*.py, and the
# moe_* / mla_* readers that ask the family): each returns None where the run has nothing to read
# ---------------------------------------------------------------------------


def moe_window(ctx, kinds=("decode", "prefill")) -> Optional[Dict[str, Any]]:
    """The program's routed-expert counters (``tick_stats()["moe"]``) over the
    window, summed over ``kinds``: ``mla_moe``'s and, where the program counts
    them, ``picks_zero`` and ``real_picks_hist`` (tokens by their number of
    real picks, 0..top-k); None where the program has none."""
    out = base.moe_window(ctx, kinds)
    a, b = (ctx[c].get("tick_stats", {}).get("moe") for c in ("c0", "c1"))
    if out is None or any("real_picks_hist" not in s[k] for s in (a, b) for k in kinds):
        return out
    out["picks_zero"] = sum(b[k]["picks_zero"] - a[k]["picks_zero"] for k in kinds)
    out["real_picks_hist"] = [sum(b[k]["real_picks_hist"][i] - a[k]["real_picks_hist"][i] for k in kinds)
                              for i in range(len(b[kinds[0]]["real_picks_hist"]))]
    return out


def real_picks_quantile(hist: Sequence[float], q: float) -> Optional[float]:
    """The smallest number of real picks that ``q`` of the tokens do not exceed."""
    total, seen = sum(hist), 0.0
    for n, tokens in enumerate(hist):
        seen += tokens
        if total and seen >= q * total:
            return float(n)
    return None


def window_counts(ctx) -> Dict[str, float]:
    """For every run's diagnostics line: what the routing made of the window."""
    out = dict(base.window_counts(ctx))
    w = moe_window(ctx)
    if w and sum(w.get("real_picks_hist", ())):
        hist = w["real_picks_hist"]
        out["real_picks_mean"] = sum(n * t for n, t in enumerate(hist)) / sum(hist)
    return out
