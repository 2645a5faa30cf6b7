"""The ``dsa_moe`` family: the ``mla_moe`` block (latent attention over one
cached row per token, sigmoid-routed experts of which this rank of an
expert-parallel deployment holds a share, a shared expert) with DeepSeek-V3.2's
learned sparse attention (a lightning indexer of ``index_n_heads`` x
``index_head_dim`` that scores every cached token and keeps ``index_topk`` per
query, its key cached beside the latent row) and the router's score-correction
bias (``topk_method: noaux_tc``).  ``benchmarks/configs/deepseek-v3.2-ep16.json``;
served bfloat16.

Seeded weights in the program's layout, the plain reference
(``benchmarks/reference/dsa_moe.py``, given the same share), the controls, the
limits, and the operations and bytes the new per-layer readers divide by.  It
imports nothing of the program and shares with ``families/mla_moe.py`` what is
the same (the share, the top leaves, the keys, the decode tick's readers).

**The bias.**  ``router_bias`` is drawn normal with a standard deviation of
0.02, a tenth of the spread of the sigmoid scores it is added to (seeded
routers give scores with a standard deviation of 0.21), rounded to bfloat16 so
that the served leaf and the reference's are the same numbers.  It changes
picks: at the tiny size a fifth of the tokens pick another set of experts with
it (16 experts, top-4: ``tests/test_dsa_moe.py``), and never a weight.

**Selection flips, and why the limits are on the gaps' 99th percentile and
mean.**  What routing flips are to ``mla_moe`` (its docstring) selection flips
are to this family too: the program scores index keys cached in bfloat16 from
a bfloat16 residual stream, the reference in float32, so where a query's
2,048th and 2,049th scores are close the two keep different keys.  The
reference counts such queries (stderr, every check): on the chip 88.5-90.3% of
(query, layer) pairs that select have the two within a relative 2^-8 (what one
bfloat16 rounding tells apart), because ~8,000 candidate scores lie far denser
than that around the 2,048th; 27.9-29.7% of (position, expert layer) pairs have
a near-tied last routed pick, 3.3-4.5% with a held expert among the two (my chip
runs, PR 40).  One flipped key of 2,048 moves a softmax by its own small
weight, far less than a routed expert does: the served tokens' gaps stay at
0.0008-0.003 in the mean, and their maximum (0.002-0.32) is still the routing
flips', so it carries no limit.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Any, Dict, List, Optional, Sequence

from benchmarks.families import mla_moe as base
from benchmarks.reference import dsa_moe as reference
from benchmarks.weights import scalar_items

# re-exported: the readers of the decode tick (moe_*, mla_dev_share, ...) and run.py ask the family
share, top_leaves, layer_key, all_keys = base.share, base.top_leaves, base.layer_key, base.all_keys
moe_window, experts_hit_per_layer_step = base.moe_window, base.experts_hit_per_layer_step
live_context_tokens, tick_scope_seconds, traced_decode_steps = (
    base.live_context_tokens, base.tick_scope_seconds, base.traced_decode_steps)
expert_bytes, latent_row_bytes = base.expert_bytes, base.latent_row_bytes

# The lower-precision controls: every matrix rounded through float8 e4m3 (below the stated
# bfloat16 weights); the block WITHOUT its selection (every s <= t attended: a program that
# skipped the indexer); index keys rounded through float8 (the published cache's precision,
# below the bfloat16 stated here: read and reported, not expected to fail).
CONTROLS = ("w_fp8", "dense", "idx_fp8")

# Set from deepseek-v3.2-ep16 on the chip (PERF.md section 2; my chip runs, PR 40), the check being ONE request a
# run (the window's longest; 84-125 served tokens), 11 sound runs on 11 seeds (4040000201/202/204/206/211/212/301/302,
# 3141592653, 2718281828, 2222222222) against the controls on 2 (4040000211/212):
#   logit_gap_p99   sound 0.000-0.131    w_fp8 0.60, 0.86     dense 1.88, 2.55   idx_fp8 0.0002, 0.131
#   logit_gap_mean  sound 0.00002-0.0029 w_fp8 0.107, 0.155   dense 0.35, 0.73   idx_fp8 0.0000, 0.0046
#   logit_gap_max   sound 0.002-0.150    w_fp8 0.62, 0.94     dense 2.27, 2.55   idx_fp8 0.001, 0.145   (no limit: routing flips)
# and two earlier sound runs that checked two requests (203 and 211 tokens): p99 0.082, 0.203, mean 0.0035, 0.0046,
# max 0.32, 0.30.  0.35 is 2.7 times the sound runs' largest p99 (1.7 times the two-request 0.203) and 1.7 times under
# the weights control's smallest; 0.02 is 6.9 times (4.3 times) over and 5.4 times under.  With ~100 tokens a check the
# 99th percentile is the second-largest gap, which one routing flip cannot reach and two rarely do; the mean is what
# tells float8 weights (37 times the sound runs' largest) and a program without its selection (120 times) apart.
# Float8 index keys pass both: this check cannot see the index cache's precision.
LIMITS = {"logit_gap_p99": 0.35, "logit_gap_mean": 0.02}

BIAS_SD = 0.02
INDEXER = ("w_iq", "w_ik", "w_iw")
NORMS = base.NORMS + ("ik_norm",)
SMALL = ("ik_bias",)  # drawn like a norm's offset: 0.1 sd around zero


def shapes(hf: Dict[str, Any], is_moe: bool) -> Dict[str, tuple]:
    out = dict(base.shapes(hf, is_moe))
    E, R, Hi, Di = hf["hidden_size"], hf["q_lora_rank"], hf["index_n_heads"], hf["index_head_dim"]
    out.update(w_iq=(R, Hi * Di), w_ik=(E, Di), w_iw=(E, Hi), ik_norm=(Di,), ik_bias=(Di,))
    if is_moe:
        out["router_bias"] = (out["router"][1],)
    return out


def layer_leaves(hf: Dict[str, Any], key, is_moe: bool) -> Dict[str, Any]:
    """One layer's leaves from its key, bfloat16: matrices normal with
    ``fan_in^-0.5``, norms near one, the index key's offset and the router's
    bias small around zero."""
    import jax
    import jax.numpy as jnp

    sh = shapes(hf, is_moe)
    keys = dict(zip(sorted(sh), jax.random.split(key, len(sh))))
    out = {}
    for name, shape in sh.items():
        z = jax.random.normal(keys[name], shape, jnp.float32)
        if name in NORMS:
            w = 1.0 + 0.1 * z
        elif name in SMALL:
            w = 0.1 * z
        elif name == "router_bias":
            w = BIAS_SD * z
        else:
            w = z * shape[-2] ** -0.5
        out[name] = w.astype(jnp.bfloat16)
    return out


def stacked_fn(hf: Dict[str, Any], head_ids):
    """The one jitted call that makes every served weight."""
    import jax

    @jax.jit
    def make(top_k, dense_ks, moe_ks):
        return {
            **top_leaves(hf, top_k, head_ids),
            "dense_layers": jax.lax.map(functools.partial(layer_leaves, hf, is_moe=False), dense_ks),
            "moe_layers": jax.lax.map(functools.partial(layer_leaves, hf, is_moe=True), moe_ks),
        }

    return make


def served_params(conf: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """All served weights on the device, from one jitted call, in the program's
    parameter layout; every leaf bfloat16."""
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
            matrix = name not in NORMS + SMALL + ("router_bias",)
            out[name] = reference.round_through_e4m3(w) if rounded and matrix else w
        return out

    return make


def float32_layer(hf: Dict[str, Any], seed: int, layer: int, rounded: bool = False) -> Dict[str, Any]:
    """Layer ``layer`` as the reference takes it; ``rounded`` gives the
    control: every matrix rounded through float8 e4m3 first."""
    is_moe = layer >= int(hf["first_k_dense_replace"])
    return _float32_layer_fn(scalar_items(hf), is_moe, rounded)(layer_key(seed, layer))


def reference_logits(conf: Dict[str, Any], seed: int, sequences: Sequence[Sequence[int]],
                     first_positions: Sequence[int], columns: Sequence[int],
                     control: Optional[str] = None, counts: Optional[List[float]] = None) -> List[Any]:
    """``reference.logits_at`` over weights regenerated from the seed, a layer
    resident at a time, given this rank's share.  ``control`` names one of ``CONTROLS``."""
    import jax
    import jax.numpy as jnp

    if control not in (None, *CONTROLS):
        raise ValueError(f"the dsa_moe family has no control {control!r}: {CONTROLS}")
    hf = conf["hf"]
    top = jax.tree.map(lambda x: x.astype(jnp.float32),
                       jax.jit(lambda k: top_leaves(hf, k, tuple(conf["weights"]["head_ids"])))(all_keys(seed, hf)[0]))
    return reference.logits_at(
        hf, lambda i: float32_layer(hf, seed, i, control == "w_fp8"), top, sequences, first_positions,
        first_expert=share(hf)[2], select=control != "dense", idx_round=control == "idx_fp8", columns=columns,
        counts=counts,
    )


# ---------------------------------------------------------------------------
# bytes and operations, from shapes (bfloat16: 2 bytes)
# ---------------------------------------------------------------------------


def index_key_bytes(conf: Dict[str, Any]) -> int:
    """The index key of one cached token in one layer."""
    return 2 * int(conf["hf"]["index_head_dim"])


def pair_flops(conf: Dict[str, Any]) -> Dict[str, float]:
    """Multiply-adds x 2 of one (query, key) pair in one layer: its index score
    (every index head), and its attention in the expanded form, the cheaper
    count (scores over ``qk_head_dim``, values over ``v_head_dim``, per head)."""
    hf = conf["hf"]
    return {
        "index": 2.0 * hf["index_n_heads"] * hf["index_head_dim"],
        "attention": 2.0 * hf["num_attention_heads"] * (hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"] + hf["v_head_dim"]),
    }


def _indexer_weights(hf: Dict[str, Any]) -> int:
    sh = shapes(hf, False)
    return sum(math.prod(sh[k]) for k in INDEXER)


def weight_bytes(conf: Dict[str, Any], experts_hit: Optional[float] = None) -> Dict[str, float]:
    out = dict(base.weight_bytes(conf, experts_hit))
    out["indexer"] = int(conf["hf"]["num_hidden_layers"]) * 2 * _indexer_weights(conf["hf"])
    return out


def decode_step_bytes(conf: Dict[str, Any], live_context_tokens: float, experts_hit: Optional[float] = None,
                      selected_tokens: Optional[float] = None) -> float:
    """The least a decode step must move: the weights once (of the experts,
    those hit), the index key of every live context token and the latent row of
    every SELECTED one (default: all of them, a short context's case) per layer."""
    L = int(conf["hf"]["num_hidden_layers"])
    selected = live_context_tokens if selected_tokens is None else selected_tokens
    return (sum(weight_bytes(conf, experts_hit).values())
            + L * (index_key_bytes(conf) * live_context_tokens + latent_row_bytes(conf) * selected))


def token_flops(conf: Dict[str, Any], local_picks: float) -> float:
    """Multiply-adds x 2 that ONE token costs in the projections and the
    feed-forward over all layers (no pair of tokens): the latent projections
    with the new token's own key and value expansion, the indexer's three, the
    dense layers' SwiGLU, and per expert layer the router, the shared expert
    and ``local_picks`` routed experts (the picks that landed on experts held
    here, per token per expert layer: the program's counter)."""
    hf = conf["hf"]
    nd, L = int(hf["first_k_dense_replace"]), int(hf["num_hidden_layers"])
    dense, moe = base.shapes(hf, False), base.shapes(hf, True)
    attn = sum(math.prod(dense[k]) for k in base.ATTN) + _indexer_weights(hf)
    ffn = sum(math.prod(dense[k]) for k in ("w_gate", "w_up", "w_down"))
    shared = sum(math.prod(moe[k]) for k in ("ws_gate", "ws_up", "ws_down") if k in moe)
    return 2.0 * (L * attn + nd * ffn + (L - nd) * (math.prod(moe["router"]) + shared + local_picks * expert_bytes(conf) / 2))


def decode_step_flops(conf: Dict[str, Any], rows: float, live_context_tokens: float,
                      local_picks_per_row: Optional[float] = None, selected_tokens: Optional[float] = None) -> float:
    """One decode step: the projections per row, the index scores over the live
    context and the absorbed attention over the selected rows (scores over 576,
    values over 512, per head)."""
    hf = conf["hf"]
    held, router, _ = share(hf)
    picks = hf["num_experts_per_tok"] * held / router if local_picks_per_row is None else local_picks_per_row
    selected = live_context_tokens if selected_tokens is None else selected_tokens
    L, H, C, dr = int(hf["num_hidden_layers"]), hf["num_attention_heads"], hf["kv_lora_rank"], hf["qk_rope_head_dim"]
    return (rows * (token_flops(conf, picks) + 2.0 * hf["hidden_size"] * hf["vocab_size"])
            + L * (pair_flops(conf)["index"] * live_context_tokens + 2.0 * H * (2 * C + dr) * selected))


def prefill_chunk_flops(conf: Dict[str, Any], queries: float, pairs_causal: float, pairs_selected: float,
                        picks_local: float) -> float:
    """The operations ``queries`` chunk tokens had to cost: their projections
    and feed-forward (``picks_local``: routed picks that landed here, summed
    over tokens and expert layers), an index score for each of ``pairs_causal``
    (query, key <= query) pairs and attention over each of ``pairs_selected``,
    both counted for ONE layer as the program's counters give them, in every
    layer; one row of the output head a program.  Re-expanding the keys and
    values of the context a chunk attends, and scoring or attending a pair the
    selection dropped, is not counted: work the program may do, not work the
    mathematics needs."""
    hf = conf["hf"]
    L = int(hf["num_hidden_layers"])
    pf = pair_flops(conf)
    return (queries * token_flops(conf, 0.0) + picks_local * expert_bytes(conf)
            + L * (pf["index"] * pairs_causal + pf["attention"] * pairs_selected))


def sizing_programs(conf: Dict[str, Any], sharding):
    """The family's own big programs for ``sizing.py``: every served weight in
    one call, and one reference expert layer at the check's size."""
    import jax
    import jax.numpy as jnp

    hf = conf["hf"]

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)

    keys = jax.eval_shape(lambda: all_keys(0, hf))
    T = int(os.environ.get("SIZING_T", 14336))
    layer = jax.eval_shape(lambda: float32_layer(hf, 0, int(hf["first_k_dense_replace"])))
    x = jax.ShapeDtypeStruct((1, T, hf["hidden_size"]), jnp.float32, sharding=sharding)
    cs = jax.ShapeDtypeStruct((T, hf["qk_rope_head_dim"] // 2), jnp.float32, sharding=sharding)
    return [
        ("dsa_moe.stacked (all served weights, one call)", stacked_fn(hf, tuple(conf["weights"]["head_ids"])), shaped(keys)),
        (f"reference expert layer, float32 highest, [1, {T}]",
         reference._layer_fn(scalar_items(hf), reference.softmax_scale(hf), True, share(hf)[2]),
         (x, shaped(layer), cs, cs, jax.ShapeDtypeStruct((1, T), jnp.bool_, sharding=sharding))),
    ]


# ---------------------------------------------------------------------------
# what this family's per-layer readers share (benchmarks/layer_metrics/dsa_*.py,
# prefill_chunk_*.py).  A count that 3 s of trace can leave empty is taken over the
# WINDOW (the counters at its edges) as a mean per program run, and multiplied by
# the runs the trace holds; only device time comes from the trace.
# ---------------------------------------------------------------------------

CHUNK_SCOPE, CHUNK_PROGRAM, TICK_SCOPE, TICK_PROGRAM = "jit(_prefill_chunk_paged)", "jit__prefill_chunk_paged", "jit(tick)", "jit_tick"
DSA_SCOPES = ("attn/index_q", "attn/index_k", "attn/index_score", "attn/select", "attn/sparse_core")


def dsa_window(ctx, kind: str) -> Optional[Dict[str, float]]:
    """The sparse attention's counters (``tick_stats()["dsa"][kind]``) over the
    window; None where the program has none (a commit before the indexer)."""
    a, b = (ctx[c].get("tick_stats", {}).get("dsa") for c in ("c0", "c1"))
    if not a or not b or kind not in b:
        return None
    return {k: float(b[kind][k] - a[kind][k]) for k in ("programs", "queries", "pairs_causal", "pairs_selected")}


def scope_seconds(ctx, program_scope: str, parts: Sequence[str]) -> Optional[float]:
    """Device seconds of ``program_scope``'s operations traced under a scope
    that contains one of ``parts`` (``("",)``: all of the program)."""
    scopes = (ctx.get("trace") or {}).get("scope_s")
    if not scopes:
        return None
    return sum(s for k, s in scopes.items() if k.startswith(program_scope) and any(p in k for p in parts))


def chunk_runs(ctx) -> Optional[float]:
    """Chunk programs the trace holds."""
    tr = ctx.get("trace")
    return tr["program_runs"].get(CHUNK_PROGRAM) if tr else None


def chunk_mean(ctx) -> Optional[Dict[str, float]]:
    """The window's mean chunk program: queries, causal and selected pairs (a
    layer's), and routed picks that landed here (all expert layers)."""
    w = dsa_window(ctx, "chunk")
    if not w or not w["programs"]:
        return None
    moe = moe_window(ctx, ("prefill",))
    out = {k: w[k] / w["programs"] for k in ("queries", "pairs_causal", "pairs_selected")}
    out["picks_local"] = (moe["picks_local"] / w["programs"]) if moe else 0.0
    return out


def chunk_pairs_share(ctx, scope: str, kind: str, pairs: str) -> Optional[float]:
    """% of the bf16 peak: ``pair_flops()[kind]`` for every pair the counter
    ``pairs`` counted (the window's mean chunk program, every layer) times the
    chunk programs traced, over the device time under ``scope`` in them."""
    mean, runs, t = chunk_mean(ctx), chunk_runs(ctx), scope_seconds(ctx, CHUNK_SCOPE, (scope,))
    if not mean or not runs or not t:
        return None
    flops = pair_flops(ctx["conf"])[kind] * mean[pairs] * ctx["conf"]["hf"]["num_hidden_layers"]
    return 100.0 * flops * runs / t / ctx["roofline"].peaks(ctx["device"]["kind"])["bf16_flops_per_s"]


def decode_mean(ctx) -> Optional[Dict[str, float]]:
    """The window's mean decode step: rows, their live context tokens (the
    causal pairs) and the rows selected for them (a layer's)."""
    w = dsa_window(ctx, "decode")
    if not w or not w["programs"]:
        return None
    return {k: w[k] / w["programs"] for k in ("queries", "pairs_causal", "pairs_selected")}


def window_counts(ctx) -> Dict[str, float]:
    """For every run's diagnostics line: what the routing and the selection made of the window."""
    out = dict(base.window_counts(ctx))
    for kind in ("decode", "chunk", "prefill"):
        w = dsa_window(ctx, kind)
        if w and w["pairs_causal"]:
            out[f"dsa_{kind}_programs"] = w["programs"]
            out[f"dsa_{kind}_selected_share"] = 100.0 * w["pairs_selected"] / w["pairs_causal"]
    return out
