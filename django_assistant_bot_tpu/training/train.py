"""Causal-LM training step: loss, optimizer wiring, sharded jit compilation.

Design (scaling-book recipe, SURVEY.md §7):

- the loss reuses :func:`~django_assistant_bot_tpu.models.llama.forward` — one model
  definition serves and trains;
- parameters / optimizer state are sharded by the model's logical axes
  (``heads``/``mlp``/``vocab_out`` → TP, ``expert`` → EP); the batch is sharded
  ``("data", "seq")`` so DP and sequence parallelism both apply;
- the whole step is one ``jax.jit`` — XLA inserts the gradient psums over the
  ``data`` axis and the per-layer TP collectives over ``model``; nothing is
  hand-scheduled;
- ``jax.checkpoint`` (rematerialisation) can be applied by callers via
  ``remat=True`` to trade FLOPs for HBM on long sequences.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import llama
from ..models.config import DecoderConfig
from ..parallel.mesh import DATA_AXIS, SEQ_AXIS
from ..parallel.sharding import shard_pytree

Params = Any


@dataclasses.dataclass
class TrainState:
    """Params + optimizer state + step counter (a minimal flax-free TrainState)."""

    params: Params
    opt_state: optax.OptState
    step: int = 0


def lm_loss(
    params: Params,
    cfg: DecoderConfig,
    input_ids: jnp.ndarray,  # [B, S]
    loss_mask: jnp.ndarray,  # [B, S] 1 where the token counts toward the loss
) -> jnp.ndarray:
    """Next-token cross-entropy, mean over unmasked target positions."""
    logits = llama.forward(params, cfg, input_ids)  # [B, S, V] f32
    targets = input_ids[:, 1:]
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None].astype(jnp.int32), axis=-1)[..., 0]
    mask = loss_mask[:, 1:].astype(jnp.float32)
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def lm_loss_long(
    params: Params,
    cfg: DecoderConfig,
    input_ids: jnp.ndarray,
    loss_mask: jnp.ndarray,
    mesh,
) -> jnp.ndarray:
    """Ring-attention variant of :func:`lm_loss` — sequence sharded over ``seq``."""
    logits = llama.forward_long(params, cfg, input_ids, mesh)
    targets = input_ids[:, 1:]
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None].astype(jnp.int32), axis=-1)[..., 0]
    mask = loss_mask[:, 1:].astype(jnp.float32)
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def make_train_step(
    cfg: DecoderConfig,
    optimizer: optax.GradientTransformation,
    *,
    remat: bool = False,
    long_context_mesh: Optional[Mesh] = None,
) -> Callable[[Params, optax.OptState, jnp.ndarray, jnp.ndarray], tuple]:
    """Build a jittable ``(params, opt_state, input_ids, loss_mask) ->
    (params, opt_state, metrics)`` step.

    Call under ``parallel.sharding.mesh_scope(mesh)`` with sharded inputs; XLA
    derives every collective.  With
    ``remat=True`` the loss is wrapped in :func:`jax.checkpoint` so activations are
    recomputed in the backward pass instead of held in HBM.  With
    ``long_context_mesh`` the forward uses ring attention over the ``seq`` axis
    (sequence/context parallelism for sequences too long for one chip).
    """
    if long_context_mesh is not None:
        mesh = long_context_mesh

        def loss_fn(params, cfg, input_ids, loss_mask):
            return lm_loss_long(params, cfg, input_ids, loss_mask, mesh)
    else:
        loss_fn = lm_loss
    if remat:
        loss_fn = jax.checkpoint(loss_fn, static_argnums=(1,))

    def step(params, opt_state, input_ids, loss_mask):
        loss, grads = jax.value_and_grad(loss_fn)(params, cfg, input_ids, loss_mask)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        gnorm = optax.global_norm(grads)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    return step


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Token batches shard over DP (rows) and SP (sequence dim)."""
    return NamedSharding(mesh, P(DATA_AXIS, SEQ_AXIS))


def init_train_state(
    cfg: DecoderConfig,
    optimizer: optax.GradientTransformation,
    *,
    rng: Optional[jax.Array] = None,
    params: Optional[Params] = None,
    mesh: Optional[Mesh] = None,
) -> TrainState:
    """Initialise (or adopt) params and build matching sharded optimizer state.

    ``optax`` state trees mirror the param tree (``zeros_like``), so initialising
    them from already-sharded params yields identically-sharded state with no extra
    sharding spec plumbing.
    """
    if params is None:
        if rng is None:
            rng = jax.random.PRNGKey(0)
        params = llama.init(cfg, rng)
    if mesh is not None:
        params = shard_pytree(params, llama.logical_axes(cfg), mesh)
    opt_state = optimizer.init(params)
    return TrainState(params=params, opt_state=opt_state, step=0)


def save_train_state(
    directory: str,
    state: TrainState,
    cfg: DecoderConfig,
    *,
    keep: int = 3,
    meta: Optional[Mapping[str, Any]] = None,
) -> str:
    """Snapshot sharded params + optimizer state under ``directory/step_NNN``.

    Atomic (rename-into-place) and rolling (newest ``keep`` kept) — the
    checkpoint/resume obligation SURVEY.md §5.4 assigns to the TPU build."""
    from .. import checkpoint as ckpt

    path = ckpt.step_path(directory, state.step)
    tree = {"params": state.params, "opt_state": state.opt_state}
    from ..checkpoint import _config_to_dict  # single source for config encoding

    ckpt.save_checkpoint(
        path, tree, step=state.step, meta={"config": _config_to_dict(cfg), **(meta or {})}
    )
    ckpt.prune_checkpoints(directory, keep)
    return path


def restore_train_state(
    directory: str,
    cfg: DecoderConfig,
    optimizer: optax.GradientTransformation,
    *,
    mesh: Optional[Mesh] = None,
) -> Optional[TrainState]:
    """Resume from the newest checkpoint in ``directory`` (None if there is none).

    Leaves restore onto exactly the shardings a fresh ``init_train_state`` would
    use on ``mesh`` — re-sharding across a different mesh shape than the one that
    saved is handled by the per-shard format."""
    from .. import checkpoint as ckpt

    import contextlib

    path = ckpt.latest_checkpoint(directory)
    if path is None:
        return None
    from ..parallel.sharding import tree_shardings

    # Structure comes from eval_shape (nothing materialises on device — resuming
    # must not need 2x the train state's HBM); shardings come from the model's
    # logical axes.  Optax state trees embed the param tree (mu/nu are
    # tree_map(zeros_like, params)), so each opt leaf takes the sharding of the
    # param whose key path is the longest suffix of its own; scalar leaves (e.g.
    # adam's count) and unmatched leaves replicate.
    def abstract_state():
        params = llama.init(cfg, jax.random.PRNGKey(0))
        return {"params": params, "opt_state": optimizer.init(params)}

    template = jax.eval_shape(abstract_state)
    replicated = NamedSharding(mesh, P()) if mesh is not None else None
    if mesh is not None:
        param_shardings = {
            f"['params']{jax.tree_util.keystr(p)}": s
            for (p, s) in jax.tree_util.tree_flatten_with_path(
                tree_shardings(mesh, llama.logical_axes(cfg))
            )[0]
        }

        def sharding_for(key: str, leaf):
            if leaf.ndim == 0:
                return replicated
            best = None
            for pkey, s in param_shardings.items():
                suffix = pkey[len("['params']"):]
                if key.endswith(suffix) and (best is None or len(suffix) > best[0]):
                    best = (len(suffix), s)
            return best[1] if best else replicated

        shardings = sharding_for
    else:
        shardings = None

    with mesh if mesh is not None else contextlib.nullcontext():
        restored, step, _ = ckpt.restore_checkpoint(
            path, like=template, shardings=shardings
        )
    return TrainState(
        params=restored["params"], opt_state=restored["opt_state"], step=step
    )
