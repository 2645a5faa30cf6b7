"""Logical-axis → PartitionSpec mapping and pytree sharding helpers.

Models annotate every parameter with *logical* axis names (e.g. ``("embed", "mlp")``);
this module maps them to mesh axes and produces :class:`NamedSharding` trees that
``jax.jit``'s ``in_shardings``/``out_shardings`` consume.  This is the scaling-book
recipe: pick a mesh, annotate shardings, let XLA insert the collectives.
"""

from __future__ import annotations

import contextlib
import logging
import threading
from typing import Any, Mapping, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import DATA_AXIS, EXPERT_AXIS, MODEL_AXIS, SEQ_AXIS

_logger = logging.getLogger(__name__)


def _is_quantized(subtree: Any) -> bool:
    """True when a logical-annotation position covers a quantized-weight
    subtree (QTensor / QTensor4) — the only case where :func:`shard_pytree`
    relaxes non-dividing dims to replication instead of failing loudly."""
    from ..ops.quant import QTensor, QTensor4

    return isinstance(subtree, (QTensor, QTensor4))

# Default logical→mesh mapping.  "heads"/"mlp"/"vocab_out" shard over the TP axis;
# "expert" over EP; "batch" over DP; "length" over SP.  Everything else replicates.
DEFAULT_RULES: Mapping[str, Optional[str]] = {
    "batch": DATA_AXIS,
    "length": SEQ_AXIS,
    "heads": MODEL_AXIS,
    "kv_heads": MODEL_AXIS,
    "mlp": MODEL_AXIS,
    "vocab_out": MODEL_AXIS,
    "expert": EXPERT_AXIS,
    "embed": None,
    "head_dim": None,
    "vocab_in": None,
    "pos": None,
}


def logical_to_pspec(
    logical_axes: tuple[Optional[str], ...],
    rules: Mapping[str, Optional[str]] = DEFAULT_RULES,
) -> P:
    """Map a tuple of logical axis names to a PartitionSpec."""
    return P(*(rules.get(a) if a is not None else None for a in logical_axes))


def named_sharding(
    mesh: Mesh,
    logical_axes: tuple[Optional[str], ...],
    rules: Mapping[str, Optional[str]] = DEFAULT_RULES,
) -> NamedSharding:
    return NamedSharding(mesh, logical_to_pspec(logical_axes, rules))


def tree_pspecs(logical_tree: Any, rules: Mapping[str, Optional[str]] = DEFAULT_RULES):
    """Map a pytree whose leaves are logical-axis tuples to a pytree of PartitionSpecs."""
    return jax.tree.map(
        lambda axes: logical_to_pspec(axes, rules),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def tree_shardings(
    mesh: Mesh, logical_tree: Any, rules: Mapping[str, Optional[str]] = DEFAULT_RULES
):
    return jax.tree.map(
        lambda axes: NamedSharding(mesh, logical_to_pspec(axes, rules)),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def shard_pytree(
    params: Any,
    logical_tree: Any,
    mesh: Mesh,
    rules: Mapping[str, Optional[str]] = DEFAULT_RULES,
):
    """Device-put a parameter pytree according to its logical axis annotations.

    Host→HBM transfer happens once here; afterwards jit-compiled steps consume the
    already-resident sharded arrays (minimising host↔device traffic, the usual HBM
    bottleneck — see SURVEY.md §7 hard parts).

    An annotation position may cover a *subtree* of arrays (e.g. a quantized
    weight is a QTensor of int8 values + per-channel scales); the spec applies
    per leaf, with size-1 dims never sharded — so a scale whose contracted dim
    collapsed to 1 rides the same annotation as its weight.
    """

    def leaf_sharding(axes: tuple, arr, lenient: bool) -> NamedSharding:
        spec = list(logical_to_pspec(axes, rules))
        shape = getattr(arr, "shape", ())
        if len(shape) != len(spec):
            # a silent fallback here would replicate a mis-annotated weight on
            # every device (N-fold HBM) with no diagnostic — fail loudly instead
            raise ValueError(
                f"logical axes {axes} (rank {len(spec)}) do not match array "
                f"shape {tuple(shape)}"
            )
        spec = [None if shape[i] == 1 else s for i, s in enumerate(spec)]
        if lenient:
            # quantized-subtree leaves only: int4-packed weights halve the
            # contraction dim and their grouped scales shrink it to n_groups,
            # either of which can stop dividing a TP axis the full-width
            # weight divided (docs/QUANT.md) — replicate that dim, loudly.
            # Plain weights keep the fail-loudly contract: a silent
            # replicate there would mask a mis-sharded config as N-fold HBM.
            for i, s in enumerate(spec):
                if s is not None and shape[i] % mesh.shape[s] != 0:
                    _logger.warning(
                        "quantized leaf dim %d (size %d) no longer divides "
                        "mesh axis %r (%d): replicating that dim",
                        i,
                        shape[i],
                        s,
                        mesh.shape[s],
                    )
                    spec[i] = None
        return NamedSharding(mesh, P(*spec))

    def subtree_shardings(axes: tuple, subtree):
        lenient = _is_quantized(subtree)
        return jax.tree.map(
            lambda arr: leaf_sharding(axes, arr, lenient), subtree
        )

    shardings = jax.tree.map(
        subtree_shardings,
        logical_tree,
        params,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x
        ),
    )

    def put(arr, sharding):
        # Skip no-op re-shardings: device_put of an already-correctly-placed
        # array can still COPY through some backends, and with async dispatch
        # every leaf copies at once — a transient 2x of the whole model's HBM.
        # At 8B geometry that transient (not the model) is what OOM'd a chip
        # with 12 GB free.  Equivalence (not equality) also catches
        # SingleDeviceSharding vs a 1-device mesh NamedSharding.
        cur = getattr(arr, "sharding", None)
        if cur is not None and cur.is_equivalent_to(sharding, getattr(arr, "ndim", 0)):
            return arr
        return jax.device_put(arr, sharding)

    return jax.tree.map(put, params, shardings)


_scope = threading.local()


@contextlib.contextmanager
def mesh_scope(mesh: Optional[Mesh]):
    """Trace/run device steps under ``mesh`` (``None`` = no mesh: a no-op).

    Enters the mesh so :func:`with_constraint`'s PartitionSpecs bind, and
    records it for this thread so code that must partition by hand — the
    Pallas kernel, which the SPMD partitioner cannot split
    (``ops.attention.attention``) — can find it through :func:`active_mesh`.
    """
    if mesh is None:
        yield
        return
    prev = getattr(_scope, "mesh", None)
    _scope.mesh = mesh
    try:
        with mesh:
            yield
    finally:
        _scope.mesh = prev


def active_mesh() -> Optional[Mesh]:
    """The :func:`mesh_scope` mesh whose axes are still automatic here: None
    outside any scope, and None inside a ``shard_map`` body traced under
    :func:`constraints_disabled` (every axis is manual there already)."""
    if getattr(_constraints_off, "depth", 0):
        return None
    return getattr(_scope, "mesh", None)


_constraints_off = threading.local()


@contextlib.contextmanager
def constraints_disabled():
    """Suppress :func:`with_constraint` in this thread's dynamic extent.

    Inside a ``shard_map`` body every mesh axis is manual and the body is
    already explicitly partitioned — the logical-axis constraints the model
    code emits would name manual axes there.  Wrapping the shard_map call
    keeps the primitive out of the trace entirely."""
    prev = getattr(_constraints_off, "depth", 0)
    _constraints_off.depth = prev + 1
    try:
        yield
    finally:
        _constraints_off.depth = prev


def with_constraint(
    x: jax.Array,
    logical_axes: tuple[Optional[str], ...],
    rules: Mapping[str, Optional[str]] = DEFAULT_RULES,
) -> jax.Array:
    """`with_sharding_constraint` by logical axis names; a no-op when no mesh
    is in context (single-device engines and plain ``jax.jit`` callers trace
    the same model code without one).

    With a mesh in context the constraint always binds: JAX accepts dims that
    do not divide their mesh axis (uneven sharding), so nothing here ever
    falls back to replication, and a mis-annotated rank raises."""
    if getattr(_constraints_off, "depth", 0):
        return x
    try:
        return jax.lax.with_sharding_constraint(x, logical_to_pspec(logical_axes, rules))
    except RuntimeError:
        # the only RuntimeError with_sharding_constraint raises for a
        # PartitionSpec: no mesh in context
        return x
