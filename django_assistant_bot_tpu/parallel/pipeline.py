"""Pipeline parallelism: GPipe microbatch schedule as SPMD over a ``pipe`` mesh axis.

The one parallelism axis the framework lacked (SURVEY.md §2.3 group "PP/EP/SP";
the reference has no distributed execution at all — its scale story is N
gunicorn workers x full model replicas, gpu_service/gunicorn_conf.py:9).  PP is
what serves/trains a model DEEPER than one chip's HBM: each stage holds only
``L/P`` contiguous layers, so per-chip layer memory drops P-fold — orthogonal
to TP (which splits each layer wide) and DP (which splits the batch).

TPU-native formulation (scaling-book collective-pipelining recipe) — no
torch-style per-rank send/recv processes:

- ``params['layers']`` leaves ([L, ...]) shard their LAYER axis over ``pipe``:
  inside ``shard_map`` every stage sees a local ``[L/P, ...]`` span and runs it
  with :func:`~..models.llama.forward_layers`.
- The GPipe schedule is a ``lax.scan`` over ``T = M + P - 1`` clock ticks.  At
  tick ``t`` stage ``s`` works on microbatch ``t - s``; between ticks the
  activation block moves to the next stage with ONE ``ppermute`` hop riding
  neighbouring ICI links (``pipe`` is the innermost mesh axis — mesh.py).
- Stages run one identical SPMD program: stage 0 *injects* (selects its own
  embedding output over the rotated-in activation), the last stage *collects*
  per-microbatch logits.  Embedding/norm/head weights are replicated over
  ``pipe`` (at depth P the layer span dominates memory; placing embed/head on
  the edge stages is a further refinement the sharding spec localises here).
- Backward is just ``jax.grad`` THROUGH the scan+ppermute (the transpose of a
  ppermute is the reverse ppermute): XLA derives the reverse schedule, no
  hand-written 1F1B.  Replicated-leaf gradients are psum'd over ``pipe``
  explicitly; layer-span gradients stay local to their stage.

Bubble fraction is the GPipe ``(P-1)/(M+P-1)`` — callers pick ``n_micro >> P``
to amortise.  Full causal attention families only (forward_layers); windowed
families bound their own context instead.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import optax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import llama
from ..models.config import DecoderConfig
from .mesh import DATA_AXIS, PIPE_AXIS

Params = Any


def pipeline_param_specs(cfg: DecoderConfig, params: Params) -> Params:
    """PartitionSpec tree: layer-stacked leaves shard axis 0 over ``pipe``,
    everything else (embed/head/norms) replicates."""

    def spec_for(path, leaf):
        # params['layers'] subtree: leading axis is the layer axis
        return P(PIPE_AXIS) if path[0].key == "layers" else P()

    return jax.tree_util.tree_map_with_path(spec_for, params)


def _check(cfg: DecoderConfig, mesh: Mesh, n_micro: int, batch: int, seq: int):
    n_stages = mesh.shape[PIPE_AXIS]
    if n_stages < 2:
        raise ValueError(f"pipeline needs a pipe axis >= 2, mesh has {n_stages}")
    if cfg.num_layers % n_stages != 0:
        raise ValueError(
            f"num_layers={cfg.num_layers} must divide over {n_stages} stages"
        )
    if llama._window_split(cfg) < cfg.num_layers:
        raise NotImplementedError(
            "pipeline parallelism supports full causal attention only "
            "(sliding-window layer indices are absolute, a stage span is not)"
        )
    if batch % n_micro != 0:
        raise ValueError(f"batch={batch} must divide into n_micro={n_micro}")
    dp = mesh.shape[DATA_AXIS]
    if (batch // n_micro) % dp != 0:
        raise ValueError(
            f"microbatch size {batch // n_micro} must divide over data axis {dp}"
        )
    return n_stages


def pipeline_forward(
    params: Params,
    cfg: DecoderConfig,
    input_ids: jnp.ndarray,  # [B, S]
    mesh: Mesh,
    *,
    n_micro: int,
) -> jnp.ndarray:
    """Pipeline-parallel forward -> logits [B, S, V] f32.

    Semantics match :func:`~..models.llama.forward` exactly (tested against it);
    only the execution schedule differs.
    """
    B, S = input_ids.shape
    n_stages = _check(cfg, mesh, n_micro, B, S)

    def spmd(layer_span, rest, ids_mb):
        # layer_span: [L/P, ...] local span;  ids_mb: [M, B/M/dp, S]
        logits_mb = _gpipe_schedule(layer_span, rest, ids_mb, cfg, n_stages, n_micro)
        return logits_mb  # [M, B/M/dp, S, V]

    layers = params["layers"]
    rest = {k: v for k, v in params.items() if k != "layers"}
    ids_mb = input_ids.reshape(n_micro, B // n_micro, S)

    # the body runs fully manual — suppress the model code's logical-axis
    # constraints while it traces (older jax rejects them at lowering)
    from .sharding import constraints_disabled

    with constraints_disabled():
        out = shard_map(
            spmd,
            mesh=mesh,
            in_specs=(P(PIPE_AXIS), P(), P(None, DATA_AXIS)),
            out_specs=P(None, DATA_AXIS),
            check_vma=False,
        )(layers, rest, ids_mb)
    return out.reshape(B, S, -1)


def _gpipe_schedule(layer_span, rest, ids_mb, cfg, n_stages, n_micro):
    """The per-device GPipe clock: runs inside shard_map.

    ``layer_span`` is this stage's [L/P, ...] layers; ``ids_mb`` [M, b, S] is
    the full microbatch queue (replicated over ``pipe``).  Returns the last
    stage's logits for every microbatch, psum'd over ``pipe`` so each device
    holds the full [M, b, S, V] result (zeros from non-final stages).
    """
    M = n_micro
    b, S = ids_mb.shape[1], ids_mb.shape[2]
    stage = jax.lax.axis_index(PIPE_AXIS)
    cos, sin = llama._rope_tables(cfg, S)
    is_first = stage == 0
    is_last = stage == n_stages - 1

    def tick(carry, t):
        state = carry  # [b, S, E]: the activation this stage holds
        # stage 0 injects microbatch t (clamped index; past-M ticks feed
        # garbage that never reaches a collect — schedule masks it out)
        inject = llama._embed({"tok_embed": rest["tok_embed"]}, cfg, ids_mb[jnp.minimum(t, M - 1)])
        x = jnp.where(is_first, inject, state)
        x = llama.forward_layers(layer_span, cfg, x, cos, sin)
        # the last stage finishes microbatch m = t - (P-1) at tick t; collect
        # the E-wide ACTIVATION, not logits — the final-norm+head runs once
        # after the scan, so the [*, V] tensor (the largest in training at a
        # 128k vocab) is neither computed P times per tick nor psum'd
        # pipe-wide (r4 advisor finding)
        m = t - (n_stages - 1)
        collect = (is_last & (m >= 0)).astype(x.dtype)
        out_t = (x * collect, jnp.maximum(m, 0))
        # rotate activations one stage forward (P-1 -> 0 carries garbage that
        # stage 0 overwrites by injecting)
        nxt = jax.lax.ppermute(
            x, PIPE_AXIS, [(i, (i + 1) % n_stages) for i in range(n_stages)]
        )
        return nxt, out_t

    state0 = jnp.zeros((b, S, cfg.hidden_size), cfg.dtype)
    _, (outs, ms) = jax.lax.scan(
        tick, state0, jnp.arange(M + n_stages - 1), length=M + n_stages - 1
    )
    # scatter the T collected slots into [M, ...] (non-collect ticks wrote
    # zeros at m=0; summing with the one real m=0 entry keeps it intact only
    # if the zeros stay zero — they do, `collect` zeroes whole blocks)
    acts_mb = jnp.zeros((M, b, S, cfg.hidden_size), outs.dtype)
    acts_mb = acts_mb.at[ms].add(outs)
    # only the final stage holds real values; psum replicates them pipe-wide
    # (E-wide — V/E-fold less collective traffic than psum'ing logits)
    acts_mb = jax.lax.psum(acts_mb, PIPE_AXIS)
    # final norm + shared head projection (handles int8 QTensor tables too),
    # applied ONCE over all microbatches
    normed = llama.rms_norm(acts_mb, rest["final_norm"], cfg.rms_norm_eps)
    return llama._head_logits(rest, cfg, normed).astype(jnp.float32)


def pipeline_loss(
    params: Params,
    cfg: DecoderConfig,
    input_ids: jnp.ndarray,
    loss_mask: jnp.ndarray,
    mesh: Mesh,
    *,
    n_micro: int,
) -> jnp.ndarray:
    """Next-token cross-entropy through the pipeline schedule (== train.lm_loss)."""
    logits = pipeline_forward(params, cfg, input_ids, mesh, n_micro=n_micro)
    targets = input_ids[:, 1:]
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None].astype(jnp.int32), axis=-1)[..., 0]
    mask = loss_mask[:, 1:].astype(jnp.float32)
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def init_pipeline_state(cfg: DecoderConfig, optimizer, *, rng, mesh: Mesh):
    """Init params + opt state with layers sharded over ``pipe`` (and the
    usual logical TP axes inert — PP composes with DP here; PP x TP would
    shard the span leaves' head/mlp axes too)."""
    from ..training.train import TrainState

    params = llama.init(cfg, rng)
    specs = pipeline_param_specs(cfg, params)
    params = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )
    opt_state = optimizer.init(params)
    return TrainState(params=params, opt_state=opt_state, step=0)


def make_pipeline_train_step(
    cfg: DecoderConfig,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    *,
    n_micro: int,
):
    """jit-able (params, opt_state, ids, mask) -> (params, opt_state, metrics).

    Gradients flow through the scan+ppermute schedule (XLA derives the reverse
    pipeline); the optimizer update is ordinary optax on the sharded trees.
    """

    def step(params, opt_state, input_ids, loss_mask):
        loss, grads = jax.value_and_grad(pipeline_loss)(
            params, cfg, input_ids, loss_mask, mesh, n_micro=n_micro
        )
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, {"loss": loss}

    return step
