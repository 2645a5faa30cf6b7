"""Pallas grouped SwiGLU over the held experts that have rows.

A rank of an expert-parallel deployment holds ``experts_held`` experts of every
expert layer, stacked ``[expert layers, held, ...]`` in HBM.  A decode step's
few rows land on some of them; the rest have nothing to compute, and their
88 MB apiece (A.X-K1's widths) need not be read.  :func:`grouped_swiglu` runs
a **work list**: per item one expert and one block of rows, the expert's three
matrices DMA'd from the whole stack by ``(layer, expert)`` in ``[E, tf]`` /
``[tf, E]`` slabs.  An expert that is not on the list is never read, and
nothing the size of a layer's experts is ever made (PERF.md section 5, PR 30).

The list is built in XLA (:func:`..models.mixtral.held_experts_mlp`) and rides
in as scalar prefetch; the grid is static and the items past the live count
repeat the last live item's block indices, so the pipeline issues no DMA for
them, and skip their compute: how ``jax.experimental.pallas.ops.tpu.megablox``
treats empty groups.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..parallel.sharding import active_mesh

# lanes of the per-row weight as the kernel takes it (every lane the same)
ROW_WEIGHT_LANES = 128
# columns of the expert's width in one slab: [E, 512] and [512, E] blocks of the three matrices
SLAB_COLUMNS = 512


def held_experts_path(hidden: int, width: int) -> str:
    """Which implementation the held experts take: ``"kernel"``
    (:func:`grouped_swiglu`) or ``"xla"`` (the plain einsums and tile loop of
    :func:`..models.mixtral.held_experts_mlp`).

    By platform and shape, as :func:`..ops.attention.paged_decode_kv_path`:
    the CPU has no Mosaic compiler and the kernel moves lane-wide slabs, so it
    takes a hidden size and an expert width in whole 128-lane tiles (every
    served geometry; toy models keep the plain function); a Mosaic call cannot
    be partitioned, so a mesh of several devices keeps the plain function too.
    On a TPU a kernel-shaped layer always reaches the kernel: one that does not
    compile fails the boot, it does not fall back."""
    mesh = active_mesh()
    kernel_shaped = hidden % 128 == 0 and width % 128 == 0 and (mesh is None or mesh.size == 1)
    return "kernel" if kernel_shaped and jax.default_backend() == "tpu" else "xla"


def _held_experts_kernel(
    # scalar prefetch (SMEM)
    layer_ref,  # [1] which expert layer of the stack
    n_ref,  # [1] live items of the work list
    expert_ref,  # [I] the item's expert (read only below the live count)
    # inputs
    x_ref,  # [R, E] the item's rows (shared: every item's)
    w_ref,  # [R, 128] f32 the item's weight per row, every lane the same
    wg_ref,  # [E, tf] of w_gate[layer, expert]
    wu_ref,  # [E, tf] of w_up[layer, expert]
    wd_ref,  # [tf, E] of w_down[layer, expert]
    # output
    o_ref,  # [R, E] f32, resident across an item's slabs (shared: across the whole grid)
    *,
    shared_rows: bool,
):
    """One ``[.., tf]`` slab of one item: ``o += ((silu(x Wg) * (x Wu)) * w) Wd``
    over the slab's ``tf`` columns of the expert's width.  bfloat16 operands,
    float32 accumulation, the row weight applied in float32 before the
    down-projection and the product cast back: the plain function's rounding."""
    del layer_ref, expert_ref  # the index maps' business
    i, j = pl.program_id(0), pl.program_id(1)
    live = i < n_ref[0]

    # a block of the result starts at zero: once for the shared block, once per live item otherwise
    @pl.when((j == 0) & ((i == 0) if shared_rows else (live | (i == 0))))
    def _zero():
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    @pl.when(live)
    def _slab():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32).astype(x.dtype).astype(jnp.float32)
        u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32).astype(x.dtype).astype(jnp.float32)
        h = (g * jax.nn.sigmoid(g) * u).astype(x.dtype).astype(jnp.float32)
        h = (h * w_ref[:, :1]).astype(x.dtype)
        o_ref[...] += jnp.dot(h, wd_ref[...], preferred_element_type=jnp.float32)


def grouped_swiglu(
    x_rows: jnp.ndarray,  # shared_rows: [R, E]; else [I * R, E], item i's rows its i-th block
    w_rows: jnp.ndarray,  # [I, R] f32: item i's weight per row (0 for a row that is not its own)
    w_gate: jnp.ndarray,  # [L, X, E, F] the whole stack, every expert layer
    w_up: jnp.ndarray,  # [L, X, E, F]
    w_down: jnp.ndarray,  # [L, X, F, E]
    layer: jnp.ndarray,  # scalar int32: the expert layer of the stack
    experts: jnp.ndarray,  # [I] int32: item i's expert; entries from n_items on are not read
    n_items: jnp.ndarray,  # [1] int32: live items, the first of the list
    *,
    shared_rows: bool,
    interpret: bool = False,
) -> jnp.ndarray:
    """The work list's SwiGLUs as ONE Pallas call -> float32 ``[R, E]``
    (``shared_rows``: the sum over the live items, zeros where there is none)
    or ``[I * R, E]`` (item i's result its i-th block; **the blocks of items
    past the live count are not written** and hold whatever the buffer held).

    Two call shapes of one kernel.  ``shared_rows`` (a decode step): every item
    is one expert over ALL ``R`` rows with that expert's column of the combine
    matrix as row weights, accumulated into one result: no sort, no gather, no
    scatter.  Otherwise (prefill): an item is one ``R``-row tile of picks sorted
    by expert.  Grid ``(I, F / tf)``, the slab axis inner, so an item's rows and
    its result stay in VMEM across its slabs while the next slab, of this or
    of the next item's expert, is already in flight; an expert's consecutive
    tiles each stream its slabs again, as the plain tile loop does.

    The stacks stay in HBM whole; the pipeline DMAs ``3 * E * tf`` weights a
    grid step and nothing for an item past the live count.  An empty list reads
    one slab (the pipeline's first fetch) and returns zeros (``shared_rows``)."""
    L, X, E, F = w_gate.shape
    n_list, R = w_rows.shape
    if E % 128 or F % 128 or R % 8:
        raise ValueError(
            f"held-experts kernel needs hidden and expert widths in whole lane tiles and rows in whole "
            f"sublane tiles, got E={E}, F={F}, rows={R}"
        )
    tf = SLAB_COLUMNS if F % SLAB_COLUMNS == 0 else 128
    nf = F // tf

    # index maps, over (item step i, slab step j, *scalar prefetch): a step past the live
    # count stays on the last live step's blocks, so the pipeline fetches nothing for it
    def item(i, n):
        return jnp.minimum(i, jnp.maximum(n[0] - 1, 0))

    def slab(i, j, n):
        return jnp.where(i < n[0], j, nf - 1)

    def rows_map(i, j, layer, n, ex):
        return (0 if shared_rows else item(i, n), 0)

    def weights_map(i, j, layer, n, ex):
        return (item(i, n), 0)

    def up_map(i, j, layer, n, ex):  # w_gate, w_up: [E, tf] of [L, X, E, F]
        return (layer[0], ex[item(i, n)], 0, slab(i, j, n))

    def down_map(i, j, layer, n, ex):  # w_down: [tf, E] of [L, X, F, E]
        return (layer[0], ex[item(i, n)], slab(i, j, n), 0)

    itemsize = jnp.dtype(w_gate.dtype).itemsize
    return pl.pallas_call(
        functools.partial(_held_experts_kernel, shared_rows=shared_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_list, nf),
            in_specs=[
                pl.BlockSpec((R, E), rows_map),
                pl.BlockSpec((R, ROW_WEIGHT_LANES), weights_map),
                pl.BlockSpec((None, None, E, tf), up_map),
                pl.BlockSpec((None, None, E, tf), up_map),
                pl.BlockSpec((None, None, tf, E), down_map),
            ],
            out_specs=pl.BlockSpec((R, E), rows_map),
        ),
        out_shape=jax.ShapeDtypeStruct((R if shared_rows else n_list * R, E), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # three slabs double-buffered; the rows, their result (both twice) and
            # the down-projection's product; room for the slab's [R, tf] values
            vmem_limit_bytes=2 * 3 * E * tf * itemsize + R * E * (2 * itemsize + 3 * 4) + (16 << 20),
        ),
        name="held_experts",
        interpret=interpret,
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32), n_items.astype(jnp.int32), experts.astype(jnp.int32),
        x_rows, jnp.broadcast_to(w_rows.reshape(n_list * R, 1), (n_list * R, ROW_WEIGHT_LANES)),
        w_gate, w_up, w_down,
    )
