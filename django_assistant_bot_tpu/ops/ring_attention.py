"""Ring attention — sequence/context parallelism over the mesh ``seq`` axis.

The reference *bounds* context to 8k tokens instead of scaling it (SURVEY.md §5.7,
reference: assistant/ai/providers/*.py ``context_size = 8000``).  Here long context is
first-class: the sequence dimension is sharded over the ``seq`` mesh axis and K/V
chunks rotate around the ICI ring via ``lax.ppermute`` while each device accumulates
blockwise online-softmax statistics — attention memory stays O(S/n) per chip and the
K/V transfers overlap with the per-chunk matmuls (XLA overlaps the ppermute DMA with
compute since the loop body's matmul does not depend on the incoming chunk).

Causal variant skips fully-masked chunk pairs' contributions via masking (compute is
still uniform per step — predictable ICI schedule beats raggedness on TPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.mesh import SEQ_AXIS
from .attention import NEG_INF


def _ring_body(q, k, v, axis_name: str, *, causal: bool):
    """Per-device blockwise attention with rotating K/V.  Shapes: [B,H,Sl,D]."""
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    B, H, Sl, D = q.shape
    scale = D ** -0.5
    qf = q.astype(jnp.float32) * scale

    m = jnp.full((B, H, Sl, 1), NEG_INF, dtype=jnp.float32)
    l = jnp.zeros((B, H, Sl, 1), dtype=jnp.float32)
    o = jnp.zeros((B, H, Sl, D), dtype=jnp.float32)

    def step(i, carry):
        m, l, o, k_cur, v_cur = carry
        src_idx = (my_idx - i) % axis_size  # which shard's K/V we hold this step
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_cur.astype(jnp.float32))
        if causal:
            qpos = my_idx * Sl + jax.lax.broadcasted_iota(jnp.int32, (Sl, Sl), 0)
            kpos = src_idx * Sl + jax.lax.broadcasted_iota(jnp.int32, (Sl, Sl), 1)
            s = jnp.where((qpos >= kpos)[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        o_new = alpha * o + jnp.einsum("bhqk,bhkd->bhqd", p, v_cur.astype(jnp.float32))
        perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return m_new, l_new, o_new, k_nxt, v_nxt

    m, l, o, _, _ = jax.lax.fori_loop(0, axis_size, step, (m, l, o, k, v))
    return (o / jnp.maximum(l, 1e-30)).astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,  # [B, H, S, D] with S sharded over `seq`
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    *,
    causal: bool = False,
    axis_name: str = SEQ_AXIS,
) -> jnp.ndarray:
    """shard_map'd ring attention.  q/k/v sequence dims must be divisible by the
    ``seq`` axis size; batch rides ``data`` untouched."""
    spec = P(None, None, axis_name, None)
    fn = jax.shard_map(
        functools.partial(_ring_body, axis_name=axis_name, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
