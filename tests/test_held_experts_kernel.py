"""The held experts' Pallas kernel (``ops/moe.py`` ``grouped_swiglu``) against the
plain function, on the CPU in Pallas interpret mode: ``mixtral.held_experts_mlp``
steered onto its ``kernel`` path (as on a TPU at lane-wide widths) beside the same
call on its ``xla`` path, at hidden 128, expert width 1024 (two slabs of 512), 12
held experts of 24 routed (rank 0 of 2), top-4, a stack of 3 expert layers.

**Tolerances.**  In float32 both paths sum the same products in another order:
1e-4 on values of order one.  In bfloat16 (what is served) the kernel rounds the
gate and up products and the weighted hidden value to bfloat16 where the plain
function does, but keeps ``silu`` and the product in float32 between them, two
roundings of 2^-9 fewer on values of order one summed over 1024 columns with random
signs; each side then rounds its result once to bfloat16 (2^-8 relative of values up
to ~4, an ulp of 2^-6).  ``2^-5`` absolute holds a flip of that last rounding with
room; an expert left out moves a row by its whole weighted result, ~0.3 and more.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from django_assistant_bot_tpu.models import DecoderConfig, mixtral
from django_assistant_bot_tpu.ops import moe as moe_ops

HERE = os.path.dirname(os.path.abspath(__file__))
E, F, HELD, ROUTED, LAYERS, K = 128, 1024, 12, 24, 3, 4
TOL = {jnp.float32: 1e-4, jnp.bfloat16: 2.0**-5}


def _cfg(dtype):
    with open(os.path.join(HERE, "data", "mla_moe_tiny.json")) as f:
        hf = json.load(f)["hf"]
    hf.update(hidden_size=E, moe_intermediate_size=F, n_routed_experts=HELD, ep_size=ROUTED // HELD, ep_rank=0,
              num_hidden_layers=1 + LAYERS, num_experts_per_tok=K)
    cfg = DecoderConfig.from_hf(hf, dtype=dtype)
    assert cfg.latent_moe.experts_held == HELD and cfg.latent_moe.router_experts == ROUTED
    return cfg


def _stack(dtype, seed=30):
    rng = np.random.default_rng(seed)

    def draw(*shape, fan_in):
        return jnp.asarray(rng.standard_normal(shape) * fan_in ** -0.5, jnp.float32).astype(dtype)

    return {
        "w_gate": draw(LAYERS, HELD, E, F, fan_in=E), "w_up": draw(LAYERS, HELD, E, F, fan_in=E),
        "w_down": draw(LAYERS, HELD, F, E, fan_in=F),
    }


def _router(rng, silent=()):
    """Inputs have mean 0.5 over 128 columns, so a column of -1 scores far under
    every drawn column: the experts in ``silent`` are never picked."""
    router = rng.standard_normal((E, ROUTED)).astype(np.float32) * E ** -0.5
    router[:, list(silent)] = -1.0
    return jnp.asarray(router)


def _one_expert_router():
    """Every token's best pick is held expert 5 (its group wins, it leads its group)."""
    router = np.zeros((E, ROUTED), np.float32)
    router[:, 0:6] = 0.01
    router[:, 5] = 0.03
    return jnp.asarray(router)


# name -> (tokens [B, S], layer of the stack, experts never picked or "one", rows not valid); inputs
# and routers are drawn from the name's length, which hits the other 7 in the first case
CASES = {
    "decode-five-of-12-idle": ((32, 1), 1, (0, 3, 4, 8, 11), ()),
    "decode-inactive-slots-and-a-pad-row": ((13, 1), 1, (2, 7), (1, 6, 12)),
    "decode-no-local-pick": ((32, 1), 1, tuple(range(HELD)), ()),
    "prefill-picks-spread": ((1, 256), 1, (), tuple(range(200, 256))),
    "prefill-1024-tokens-to-one-expert": ((1, 1024), 1, "one", ()),
    "decode-first-layer-of-the-stack": ((32, 1), 0, (1, 2, 3), ()),
    "prefill-last-layer-of-the-stack": ((2, 100), LAYERS - 1, (9,), tuple(range(161, 200))),
}


@pytest.fixture
def on_the_kernel_path(monkeypatch):
    """Call to steer ``held_experts_mlp`` as a TPU at lane-wide widths would, the kernel interpreted."""
    def steer():
        monkeypatch.setattr(moe_ops, "held_experts_path", lambda hidden, width: "kernel")
        monkeypatch.setattr(moe_ops, "grouped_swiglu", functools.partial(moe_ops.grouped_swiglu, interpret=True))

    return steer


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_kernel_path_equals_the_plain_function_and_counts_the_same(case, dtype, on_the_kernel_path):
    (B, S), layer, silent, invalid = CASES[case]
    cfg = _cfg(dtype)
    rng = np.random.default_rng(len(case))
    router = _one_expert_router() if silent == "one" else _router(rng, silent)
    p = dict(_stack(dtype), router=router)
    x = jnp.asarray(rng.standard_normal((B, S, E)) + 0.5, jnp.float32).astype(dtype)
    valid = np.ones(B * S, bool)
    valid[list(invalid)] = False
    valid = jnp.asarray(valid.reshape(B, S))

    run = jax.jit(lambda p, x, valid, layer: mixtral.held_experts_mlp(cfg, p, x, valid, layer))
    assert moe_ops.held_experts_path(E, F) == "xla"  # the CPU keeps the plain function
    want, want_stats = run(p, x, valid, jnp.int32(layer))
    on_the_kernel_path()
    got, stats = jax.jit(lambda p, x, valid, layer: mixtral.held_experts_mlp(cfg, p, x, valid, layer))(
        p, x, valid, jnp.int32(layer))

    assert np.array_equal(np.asarray(stats), np.asarray(want_stats))
    hit, per_expert = int(stats[3]), np.asarray(stats[mixtral.MOE_STAT_HEAD:])
    if silent == "one":
        assert per_expert[5] == 1024 and -(-1024 // mixtral.GROUP_TILE) >= 8  # 8 tiles of one expert, no token left out
    else:
        assert hit <= HELD - len(silent) and not per_expert[list(silent)].any()
        assert case != "decode-five-of-12-idle" or hit == 7
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, atol=TOL[dtype], rtol=TOL[dtype])
    v = np.asarray(valid)
    assert not got[~v].any()  # inactive slots and pad positions are no expert's row
    if hit:
        assert np.abs(want[v]).max() > 0.3  # the routed part is not nothing
    else:
        assert not got.any()  # an empty work list: zeros


def test_a_layer_alone_equals_its_place_in_the_stack(on_the_kernel_path):
    """``layer=None`` with one layer's experts ``[held, ...]`` (how the tests of
    the block call it) is the stack of one."""
    cfg = _cfg(jnp.float32)
    rng = np.random.default_rng(3)
    stack = _stack(jnp.float32)
    p = dict(stack, router=_router(rng, (4, 5)))
    x = jnp.asarray(rng.standard_normal((8, 1, E)) + 0.5, jnp.float32)
    valid = jnp.ones((8, 1), bool)
    on_the_kernel_path()
    in_stack, _ = mixtral.held_experts_mlp(cfg, p, x, valid, jnp.int32(2))
    alone, _ = mixtral.held_experts_mlp(cfg, dict(p, **{k: v[2] for k, v in stack.items()}), x, valid)
    assert np.array_equal(np.asarray(in_stack), np.asarray(alone))


@pytest.mark.parametrize("backend, hidden, width, want", [
    ("tpu", 7168, 2048, "kernel"),  # the benchmark configuration's widths
    ("tpu", 64, 32, "xla"),  # toy widths keep the plain function on a chip too
    ("tpu", 7168, 2000, "xla"),
    ("cpu", 7168, 2048, "xla"),
])
def test_the_path_is_chosen_by_platform_and_shape(backend, hidden, width, want, monkeypatch):
    monkeypatch.setattr(moe_ops.jax, "default_backend", lambda: backend)
    assert moe_ops.held_experts_path(hidden, width) == want


def test_a_mesh_of_several_devices_keeps_the_plain_function(monkeypatch):
    from django_assistant_bot_tpu.parallel import MeshAxes, make_mesh
    from django_assistant_bot_tpu.parallel.sharding import mesh_scope

    monkeypatch.setattr(moe_ops.jax, "default_backend", lambda: "tpu")
    with mesh_scope(make_mesh(MeshAxes(model=2), devices=jax.devices()[:2])):
        assert moe_ops.held_experts_path(7168, 2048) == "xla"
    with mesh_scope(make_mesh(MeshAxes(), devices=jax.devices()[:1])):
        assert moe_ops.held_experts_path(7168, 2048) == "kernel"
