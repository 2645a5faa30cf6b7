"""The ``mla_moe`` family: latent attention (MLA) over one cached row per token,
a leading dense layer, then sigmoid-routed experts of which this rank of an
expert-parallel deployment holds a share, beside a shared expert (DeepSeek-V3's
block; ``benchmarks/configs/a.x-k1-ep16.json``).  Served bfloat16 as published.

Seeded weights in the program's layout (``dense_layers`` and ``moe_layers``
stacked on a leading axis each; only the held experts and the vocabulary slice
are ever drawn), the plain reference (``benchmarks/reference/mla_moe.py``,
given the same share), the controls, the limits, and the bytes and operations
of a decode step.  It imports nothing of the program.

**The share.**  ``hf["n_routed_experts"]`` counts the experts HELD here,
``hf["ep_size"]`` the ranks that share a layer, ``hf["ep_rank"]`` which of them
this is; the router is ``n_routed_experts * ep_size`` wide, as published.

**Near-tied picks, and why the limits are on the gaps' 99th percentile and
mean, not on their maximum.**  The program routes from a bfloat16 residual
stream, the reference from a float32 one, so where the last pick and the best
expert left out are close the two pick differently.  The reference counts such
pairs over the real positions (stderr, every check).  With seeded routers the
sigmoid scores are densely packed: on the chip 24.4-25.7% of real (position,
expert layer) pairs have the two within a relative 2^-8 (what one bfloat16
rounding tells apart), 2.1-3.2% with a held expert among the two (every check;
my chip runs, PR 29).  A flip that involves a held expert adds or removes one
expert's weighted result (~0.31 of the routed sum) at that position: a
discrete jump no precision avoids, and it is what the served tokens' largest
gaps are: ``logit_gap_max`` read 0.12-0.89 over 19 sound runs while float8
weights in the reference's place read 0.84-1.49, so the maximum cannot
tell them apart and carries no limit.  The flips touch a few tokens of
~1,600; the 99th percentile and the mean barely feel them, and both stand
2.5-5 times clear of the sound runs on one side and of the weights control on
the other (``LIMITS`` below).
"""

from __future__ import annotations

import functools
import math
import os
from typing import Any, Dict, List, Optional, Sequence

from benchmarks.reference import mla_moe as reference
from benchmarks.weights import key_words, scalar_items

# The lower-precision controls: every matrix rounded through float8 e4m3 (below
# the stated bfloat16 weights), and the cached latent row rounded through float8
# (below the stated bfloat16 cache).
CONTROLS = ("w_fp8", "kv_fp8")

# Set from a.x-k1-ep16 on the chip (PERF.md section 2; my chip runs, PR 29),
# 19 sound runs on 19 seeds (2929200001-05, 2929300011-19, 2929300021-22,
# 2929400001-03) against the controls on 3 (2929300021-22, 2929400003):
#   logit_gap_p99   sound 0.002-0.066   w_fp8 0.51, 0.64, 0.79     kv_fp8 0.048, 0.107, 0.145
#   logit_gap_mean  sound 0.0004-0.0025 w_fp8 0.031, 0.066, 0.067  kv_fp8 0.0017, 0.0038, 0.0055
#   logit_gap_max   sound 0.12-0.89     w_fp8 0.84, 1.15, 1.49     kv_fp8 0.37, 0.39, 0.70   (no limit: above)
# 0.20 is 3.0 times the sound runs' largest p99 and 2.5 times under the weights
# control's smallest; 0.012 is 4.8 times and 2.5 times.  The float8 latent cache
# passes both (at most 2.2 times the sound runs' largest): this check cannot see
# the cache's precision, as llama's cannot.
LIMITS = {"logit_gap_p99": 0.20, "logit_gap_mean": 0.012}

ATTN = ("w_dq", "w_uq", "w_dkv", "w_uk", "w_uv", "wo")
NORMS = ("attn_norm", "q_norm", "kv_norm", "mlp_norm")


def share(hf: Dict[str, Any]):
    """-> (experts held, router width, first held expert)."""
    held, ranks = int(hf["n_routed_experts"]), int(hf.get("ep_size", 1))
    return held, held * ranks, int(hf.get("ep_rank", 0)) * held


def shapes(hf: Dict[str, Any], is_moe: bool) -> Dict[str, tuple]:
    E, H = hf["hidden_size"], hf["num_attention_heads"]
    R, C = hf["q_lora_rank"], hf["kv_lora_rank"]
    dn, dr, dv = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"]
    out = {
        "w_dq": (E, R), "w_uq": (R, H * (dn + dr)), "w_dkv": (E, C + dr), "w_uk": (C, H * dn),
        "w_uv": (C, H * dv), "wo": (H * dv, E),
        "attn_norm": (E,), "q_norm": (R,), "kv_norm": (C,), "mlp_norm": (E,),
    }
    if not is_moe:
        F = hf["intermediate_size"]
        out.update(w_gate=(E, F), w_up=(E, F), w_down=(F, E))
        return out
    held, router, _ = share(hf)
    Fm, Fs = hf["moe_intermediate_size"], hf["moe_intermediate_size"] * int(hf.get("n_shared_experts") or 0)
    out.update(router=(E, router), w_gate=(held, E, Fm), w_up=(held, E, Fm), w_down=(held, Fm, E))
    if Fs:
        out.update(ws_gate=(E, Fs), ws_up=(E, Fs), ws_down=(Fs, E))
    return out


def layer_leaves(hf: Dict[str, Any], key, is_moe: bool) -> Dict[str, Any]:
    """One layer's leaves from its key, bfloat16: matrices normal with
    ``fan_in^-0.5``, norms near one.  A held expert's matrices depend on the
    layer's key and the expert's place among the held ones."""
    import jax
    import jax.numpy as jnp

    sh = shapes(hf, is_moe)
    keys = dict(zip(sorted(sh), jax.random.split(key, len(sh))))
    out = {}
    for name, shape in sh.items():
        if name in NORMS:
            w = 1.0 + 0.1 * jax.random.normal(keys[name], shape, jnp.float32)
        else:
            w = jax.random.normal(keys[name], shape, jnp.float32) * shape[-2] ** -0.5
        out[name] = w.astype(jnp.bfloat16)
    return out


def top_leaves(hf: Dict[str, Any], key, head_ids) -> Dict[str, Any]:
    """Embedding, final norm, output head over the vocabulary slice; only the
    head's columns ``head_ids[0] .. head_ids[1]`` are drawn (benchmarks/weights.py
    says why)."""
    import jax
    import jax.numpy as jnp

    E, V = hf["hidden_size"], hf["vocab_size"]
    ke, kn, kh = jax.random.split(key, 3)
    col = jnp.arange(V)
    drawn = (col >= head_ids[0]) & (col <= head_ids[1])
    return {
        "tok_embed": jax.random.normal(ke, (V, E), jnp.float32).astype(jnp.bfloat16),
        "final_norm": (1.0 + 0.1 * jax.random.normal(kn, (E,), jnp.float32)).astype(jnp.bfloat16),
        "lm_head": (E ** -0.5 * jax.random.normal(kh, (E, V), jnp.float32) * drawn[None, :]).astype(jnp.bfloat16),
    }


def _root(seed: int):
    import jax
    import jax.numpy as jnp

    return jax.random.wrap_key_data(jnp.asarray(key_words(seed, 0x4D4C41), jnp.uint32))


def layer_key(seed: int, layer):
    import jax

    return jax.random.fold_in(_root(seed), layer)


def all_keys(seed: int, hf: Dict[str, Any]):
    """(top key, dense layers' keys, expert layers' keys): :func:`stacked_fn`'s arguments."""
    import jax
    import jax.numpy as jnp

    nd, L = int(hf["first_k_dense_replace"]), int(hf["num_hidden_layers"])
    keys = jax.vmap(lambda i: layer_key(seed, i))(jnp.arange(L))
    return jax.random.fold_in(_root(seed), 0xFFFF), keys[:nd], keys[nd:]


def stacked_fn(hf: Dict[str, Any], head_ids):
    """The one jitted call that makes every served weight."""
    import jax

    @jax.jit
    def make(top_k, dense_ks, moe_ks):
        return {
            **top_leaves(hf, top_k, head_ids),
            # a layer at a time (lax.map, not vmap: half the compile time and no temporaries; the same values)
            "dense_layers": jax.lax.map(functools.partial(layer_leaves, hf, is_moe=False), dense_ks),
            "moe_layers": jax.lax.map(functools.partial(layer_leaves, hf, is_moe=True), moe_ks),
        }

    return make


def served_params(conf: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """All served weights on the device, from one jitted call, in the program's
    parameter layout; every leaf bfloat16 (no quantised tuple)."""
    hf = conf["hf"]
    return stacked_fn(hf, tuple(conf["weights"]["head_ids"]))(*all_keys(seed, hf))


@functools.lru_cache(maxsize=None)
def _float32_layer_fn(hf_items, is_moe: bool, rounded: bool):
    import jax
    import jax.numpy as jnp

    hf = dict(hf_items)

    @jax.jit
    def make(key):
        out = {}
        for name, w in layer_leaves(hf, key, is_moe).items():
            w = w.astype(jnp.float32)
            out[name] = reference.round_through_e4m3(w) if rounded and name not in NORMS else w
        return out

    return make


def float32_layer(hf: Dict[str, Any], seed: int, layer: int, rounded: bool = False) -> Dict[str, Any]:
    """Layer ``layer`` as the reference takes it; ``rounded`` gives the
    control: every matrix rounded through float8 e4m3 first."""
    is_moe = layer >= int(hf["first_k_dense_replace"])
    return _float32_layer_fn(scalar_items(hf), is_moe, rounded)(layer_key(seed, layer))


def reference_logits(conf: Dict[str, Any], seed: int, sequences: Sequence[Sequence[int]],
                     first_positions: Sequence[int], columns: Sequence[int],
                     control: Optional[str] = None) -> List[Any]:
    """``reference.logits_at`` over weights regenerated from the seed, a layer
    resident at a time, given this rank's share.  ``control`` names one of ``CONTROLS``."""
    import jax
    import jax.numpy as jnp

    if control not in (None, *CONTROLS):
        raise ValueError(f"the mla_moe family has no control {control!r}: {CONTROLS}")
    hf = conf["hf"]
    top = jax.tree.map(lambda x: x.astype(jnp.float32),
                       jax.jit(lambda k: top_leaves(hf, k, tuple(conf["weights"]["head_ids"])))(all_keys(seed, hf)[0]))
    return reference.logits_at(
        hf, lambda i: float32_layer(hf, seed, i, control == "w_fp8"), top, sequences, first_positions,
        first_expert=share(hf)[2], kv_round=control == "kv_fp8", columns=columns,
    )


# ---------------------------------------------------------------------------
# bytes and operations of a decode step, from shapes (bfloat16: 2 bytes)
# ---------------------------------------------------------------------------


def expert_bytes(conf: Dict[str, Any]) -> int:
    """One routed expert's three matrices."""
    hf = conf["hf"]
    return 2 * 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def latent_row_bytes(conf: Dict[str, Any]) -> int:
    """The least a cached token takes in one layer: latent and rotary key
    (the pool pads the row to whole 128-lane tiles; the pad is not counted)."""
    hf = conf["hf"]
    return 2 * (hf["kv_lora_rank"] + hf["qk_rope_head_dim"])


def weight_bytes(conf: Dict[str, Any], experts_hit: Optional[float] = None) -> Dict[str, float]:
    """Bytes of weights a decode step reads, by part.  ``experts_hit``: the
    mean number of distinct held experts a step hits in an expert layer (the
    program's counter); None counts every held expert, which is what a dense
    pass over the held experts reads.  A step that stops reading idle experts
    is then measured against the bytes of the experts it had to read."""
    hf = conf["hf"]
    nd, L = int(hf["first_k_dense_replace"]), int(hf["num_hidden_layers"])
    held = share(hf)[0]
    hit = held if experts_hit is None else min(float(experts_hit), held)
    dense, moe = shapes(hf, False), shapes(hf, True)
    attn = 2 * sum(math.prod(dense[k]) for k in ATTN + NORMS)
    shared = 2 * sum(math.prod(moe[k]) for k in ("ws_gate", "ws_up", "ws_down") if k in moe)
    return {
        "attention": L * attn,
        "dense_ffn": nd * 2 * sum(math.prod(dense[k]) for k in ("w_gate", "w_up", "w_down")),
        "router_and_shared": (L - nd) * (2 * math.prod(moe["router"]) + shared),
        "experts": (L - nd) * hit * expert_bytes(conf),
        "head": 2 * hf["hidden_size"] * hf["vocab_size"] + 2 * hf["hidden_size"],
    }


def decode_step_bytes(conf: Dict[str, Any], live_context_tokens: float, experts_hit: Optional[float] = None) -> float:
    """The least a decode step must move: the weights once (of the experts,
    those hit) and the latent row of every live context token once per layer."""
    L = int(conf["hf"]["num_hidden_layers"])
    return sum(weight_bytes(conf, experts_hit).values()) + L * latent_row_bytes(conf) * live_context_tokens


def decode_step_flops(conf: Dict[str, Any], rows: float, live_context_tokens: float,
                      local_picks_per_row: Optional[float] = None) -> float:
    """Multiply-adds x 2 of one step: projections per row, the absorbed
    attention over the latent (scores over 576, values over 512, per head), and
    per row the picks that land on held experts (default: an even share)."""
    hf = conf["hf"]
    nd, L = int(hf["first_k_dense_replace"]), int(hf["num_hidden_layers"])
    held, router, _ = share(hf)
    H, C, dr = hf["num_attention_heads"], hf["kv_lora_rank"], hf["qk_rope_head_dim"]
    dense, moe = shapes(hf, False), shapes(hf, True)
    attn = sum(math.prod(dense[k]) for k in ATTN)
    picks = hf["num_experts_per_tok"] * held / router if local_picks_per_row is None else local_picks_per_row
    per_row = (L * attn + nd * sum(math.prod(dense[k]) for k in ("w_gate", "w_up", "w_down"))
               + (L - nd) * (math.prod(moe["router"]) + sum(math.prod(moe[k]) for k in ("ws_gate", "ws_up", "ws_down") if k in moe)
                             + picks * expert_bytes(conf) / 2)
               + hf["hidden_size"] * hf["vocab_size"])
    return 2.0 * rows * per_row + 2.0 * L * H * (2 * C + dr) * live_context_tokens


def sizing_programs(conf: Dict[str, Any], sharding):
    """The family's own big programs for ``sizing.py``: every served weight in
    one call, and one reference expert layer at the check's size."""
    import jax
    import jax.numpy as jnp

    hf = conf["hf"]

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)

    keys = jax.eval_shape(lambda: all_keys(0, hf))
    B, T = int(os.environ.get("SIZING_B", 6)), int(os.environ.get("SIZING_T", 1536))
    layer = jax.eval_shape(lambda: float32_layer(hf, 0, int(hf["first_k_dense_replace"])))
    x = jax.ShapeDtypeStruct((B, T, hf["hidden_size"]), jnp.float32, sharding=sharding)
    cs = jax.ShapeDtypeStruct((T, hf["qk_rope_head_dim"] // 2), jnp.float32, sharding=sharding)
    return [
        ("mla_moe.stacked (all served weights, one call)", stacked_fn(hf, tuple(conf["weights"]["head_ids"])), shaped(keys)),
        (f"reference expert layer, float32 highest, [{B}, {T}]",
         reference._layer_fn(scalar_items(hf), reference.softmax_scale(hf), True, share(hf)[2], False),
         (x, shaped(layer), cs, cs, jax.ShapeDtypeStruct((B, T), jnp.bool_, sharding=sharding))),
    ]


# ---------------------------------------------------------------------------
# what this family's per-layer readers share (benchmarks/layer_metrics/moe_*.py,
# mla_*.py): each returns None where the run has nothing to read
# ---------------------------------------------------------------------------


def moe_window(ctx, kinds=("decode", "prefill")) -> Optional[Dict[str, Any]]:
    """The program's routed-expert counters (``tick_stats()["moe"]``) over the
    window, summed over ``kinds``; None where the program has none."""
    a, b = (ctx[c].get("tick_stats", {}).get("moe") for c in ("c0", "c1"))
    if not a or not b:
        return None
    out: Dict[str, Any] = {"tokens_per_expert": [0] * len(b["decode"]["tokens_per_expert"])}
    for kind in kinds:
        for k in ("picks", "picks_local", "layer_steps", "experts_hit"):
            out[k] = out.get(k, 0) + b[kind][k] - a[kind][k]
        out["tokens_per_expert"] = [t + y - x for t, x, y in zip(
            out["tokens_per_expert"], a[kind]["tokens_per_expert"], b[kind]["tokens_per_expert"])]
    return out


def experts_hit_per_layer_step(ctx) -> Optional[float]:
    """Mean distinct held experts a decode step hit in an expert layer, over the window."""
    w = moe_window(ctx, ("decode",))
    return w["experts_hit"] / w["layer_steps"] if w and w["layer_steps"] else None


def window_counts(ctx) -> Dict[str, float]:
    """For every run's diagnostics line: what the routing made of the window,
    the number the held experts' bytes follow."""
    hit = experts_hit_per_layer_step(ctx)
    return {} if hit is None else {"experts_hit_per_layer_step": hit}


def live_context_tokens(ctx) -> Optional[float]:
    """Mean, over the traced span, of the context tokens of the requests
    decoding (prompt and tokens served so far): the client's log."""
    a, b = ctx["trace_span"]
    if a is None or b is None:
        return None
    live = []
    for i in range(50):
        t = a + (b - a) * (i + 0.5) / 50
        live.append(sum(e["prompt_len"] + sum(1 for x in e["times"] if x <= t)
                        for e in ctx["events"] if e.get("times") and e["times"][0] <= t <= e["times"][-1]))
    return sum(live) / len(live)


def tick_scope_seconds(ctx, part: str) -> Optional[float]:
    """Device seconds of the decode tick's operations traced under a scope
    containing ``part`` (``"/moe/"``, ``"/attn/"``, ``""`` for all of the tick)."""
    scopes = (ctx.get("trace") or {}).get("scope_s")
    if not scopes:
        return None
    return sum(s for k, s in scopes.items() if k.startswith("jit(tick)") and part in k)


def traced_decode_steps(ctx) -> Optional[float]:
    tr = ctx.get("trace")
    runs = tr["program_runs"].get("jit_tick") if tr else None
    steps = ctx["c1"].get("decode_steps")
    return runs * steps if runs and steps else None
