"""Shape-static token sampling (temperature / top-k / top-p) for the decode loop.

The reference samples via torch ``generate(do_sample=True, top_p=0.95, top_k=50)``
(reference: assistant/ai/providers/transformers.py:61-68).  Here sampling lives inside
the jit'd decode step: all ops are static-shape (sort + cumsum masking), so the whole
prefill→decode loop stays on-device with no host round-trip per token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .attention import NEG_INF

# Use hierarchical top-k above this vocab size; below it plain lax.top_k wins
# (the two-stage version's gather overhead isn't worth it on small vocabs).
_HIER_TOPK_MIN_VOCAB = 16_384
_GROUP = 128  # lane width — group reductions vectorize cleanly


def top_k_hierarchical(x: jnp.ndarray, k: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact top-k over a large last axis in two small stages.

    ``lax.top_k`` over a 128k vocab costs ~7 ms/step on a v5e-class chip —
    measured at ~70% of the whole 1B decode step (the sort dwarfs the model).
    Instead: reduce each 128-lane group to its max (one cheap pass), take the
    top-k GROUPS by max, gather only those groups' lanes (k*128 candidates)
    and top-k within them.

    Exactness: if an element x is in the global top-k, at most k-1 groups can
    have max > x (each would contribute an element > x, outranking it), so
    x's group is always among the top-k groups by max.  Ties at the boundary
    may pick different (equal-valued) ids than lax.top_k — same top-k SET of
    values either way.

    Returns (values [B, k] desc, indices [B, k] int32) like ``lax.top_k``.
    """
    B, V = x.shape
    G = -(-V // _GROUP)  # ceil
    pad = G * _GROUP - V
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)), constant_values=NEG_INF)
    xg = x.reshape(B, G, _GROUP)
    gmax = xg.max(axis=-1)  # [B, G]
    kg = min(k, G)
    _, gidx = jax.lax.top_k(gmax, kg)  # [B, kg] group ids
    cand = jnp.take_along_axis(xg, gidx[:, :, None], axis=1).reshape(B, kg * _GROUP)
    vals, cidx = jax.lax.top_k(cand, k)  # [B, k] within candidates
    idx = jnp.take_along_axis(gidx, cidx // _GROUP, axis=1) * _GROUP + cidx % _GROUP
    # Pad lanes hold NEG_INF (finite): with fewer than k candidates above it
    # (e.g. a degenerate FSM state masking everything at an unaligned vocab) a
    # pad lane can win a slot and carry an index >= V — and a uniform draw over
    # all-NEG_INF rows could then emit an out-of-vocab id.  lax.top_k never
    # returns out-of-range ids; match that contract by clamping (the clamped
    # slot's value is still NEG_INF, so it can't outrank any real candidate).
    idx = jnp.minimum(idx, V - 1)
    return vals, idx.astype(jnp.int32)


def top_k_auto(x: jnp.ndarray, k: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Size-dispatching exact top-k: hierarchical past _HIER_TOPK_MIN_VOCAB
    (where the flat sort's cost dominates), plain lax.top_k below it."""
    if x.shape[-1] >= _HIER_TOPK_MIN_VOCAB:
        return top_k_hierarchical(x, k)
    vals, idx = jax.lax.top_k(x, k)
    return vals, idx.astype(jnp.int32)


_top_k = top_k_auto  # internal alias used by sample_logits


@jax.named_scope("sample")
def sample_logits(
    logits: jnp.ndarray,  # [batch, vocab] float
    rng: jax.Array,
    *,
    temperature: jnp.ndarray | float = 1.0,  # [batch] or scalar; <=0 means greedy
    top_k: int = 50,
    top_p: jnp.ndarray | float = 0.95,  # [batch] or scalar
) -> jnp.ndarray:
    """Returns sampled token ids [batch] (int32).

    Greedy is expressed per-row via temperature<=0 so one compiled fn serves mixed
    batches (continuous batching requirement: different requests, one XLA program).
    """
    logits = logits.astype(jnp.float32)
    V = logits.shape[-1]
    temperature = jnp.asarray(temperature, dtype=jnp.float32)
    temperature = jnp.broadcast_to(temperature, (logits.shape[0],))
    top_p = jnp.broadcast_to(jnp.asarray(top_p, dtype=jnp.float32), (logits.shape[0],))

    safe_t = jnp.where(temperature > 0, temperature, 1.0)
    scaled = logits / safe_t[:, None]

    if top_k and 0 < top_k < V:
        # Everything past top_k is filtered anyway, so top-p and the draw both
        # live in the [B, top_k] subspace (hierarchical top-k at large vocab —
        # a full-vocab lax.top_k was ~70% of the whole 1B decode step); the
        # cumsum runs over 50 values and categorical draws over 50.  Greedy
        # rows reuse the candidates' head (sorted desc) — no argmax pass.
        vals, idx = _top_k(scaled, top_k)  # [B, k] desc + their ids
        probs = jax.nn.softmax(vals, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = (cum - probs) < top_p[:, None]  # first token always kept
        vals = jnp.where(keep, vals, NEG_INF)
        choice = jax.random.categorical(rng, vals, axis=-1)  # [B] in [0, k)
        sampled = jnp.take_along_axis(idx, choice[:, None], axis=1)[:, 0].astype(jnp.int32)
        return jnp.where(temperature > 0, sampled, idx[:, 0])

    greedy_ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    # no top-k bound: top-p needs the full distribution sorted
    sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]
    sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(sorted_probs, axis=-1)
    keep_sorted = (cum - sorted_probs) < top_p[:, None]  # first token always kept
    # threshold = smallest kept logit
    threshold = jnp.min(
        jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1, keepdims=True
    )
    scaled = jnp.where(scaled < threshold, NEG_INF, scaled)

    sampled = jax.random.categorical(rng, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temperature > 0, sampled, greedy_ids)
