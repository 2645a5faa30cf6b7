"""The paged decode kernel (``ops.attention.paged_decode_update_attend``) against
the plain function it replaces on a TPU, in Pallas interpret mode on the CPU.

What is compared, per case: the attention output of every row whose keys both
forms define, and EVERY byte of the pool.  The plain form is the one the CPU
serves: a per-row scatter with ``mode="drop"`` into the layer's pool, then
``paged_gqa_decode_attention``.

Tolerance of the output: the kernel folds each row's own pages into its online
softmax, the plain loop folds the batch's ``[lo, hi)`` pages into every row
(pages outside a row's range are fully masked there and contribute exactly
zero), and the two matmuls sum in whatever order their backend picks.  Both
round one float32 result to bfloat16, so they agree to one bfloat16 rounding:
``rtol = 2**-7`` (the spacing of bfloat16 just above a power of two, relative),
with ``atol = 2**-14`` for entries that cancel to near zero, where float32
reordering of O(1) terms is all that is left.  The pool has no tolerance.

The Mosaic lowering, VMEM and the compiled program's shape are the business of
``tests/test_tpu_compile.py``; results on the chip are ``chip_smoke.py``'s and
the benchmark's.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from django_assistant_bot_tpu.models import DecoderConfig, llama
from django_assistant_bot_tpu.ops import attention as attn

PAGE, D, NB, L = 32, 128, 4, 2  # 16-row packed tiles divide the page; lane-wide heads
RTOL, ATOL = 2.0**-7, 2.0**-14


def _inputs(seed, B, H, KH, dtype=jnp.bfloat16):
    P = B * NB
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape) * 0.5, jnp.float32)

    return dict(
        q=draw(B, H, 1, D).astype(jnp.bfloat16),
        k_new=draw(B, KH, 1, D).astype(jnp.bfloat16),
        v_new=draw(B, KH, 1, D).astype(jnp.bfloat16),
        k_pool=draw(L, P, KH, PAGE, D).astype(dtype),
        v_pool=draw(L, P, KH, PAGE, D).astype(dtype),
        bt=rng.permutation(P).reshape(B, NB).astype(np.int32),
    )


def _kernel_step(x, layer, bt, positions, active, window):
    P = x["k_pool"].shape[1]
    plan = attn.paged_decode_plan(bt, positions, active, n_pages=P, page=PAGE, window=window)
    return jax.jit(
        functools.partial(attn.paged_decode_update_attend, window=window, interpret=True)
    )(x["q"], x["k_new"], x["v_new"], x["k_pool"], x["v_pool"], layer, bt, positions, plan)


def _plain_step(x, layer, bt, positions, active, window):
    """``llama.decode_step_paged``'s plain body, for one layer of the 5-D pool."""
    P = x["k_pool"].shape[1]
    dtype = x["k_pool"].dtype
    blk, off = positions // PAGE, positions % PAGE
    phys = jnp.take_along_axis(bt, blk[:, None], axis=1)[:, 0]
    phys_w = jnp.where(active, jnp.minimum(phys, P), P)
    kl = x["k_pool"][layer].at[phys_w, :, off, :].set(
        x["k_new"][:, :, 0, :].astype(dtype), mode="drop"
    )
    vl = x["v_pool"][layer].at[phys_w, :, off, :].set(
        x["v_new"][:, :, 0, :].astype(dtype), mode="drop"
    )
    o = attn.paged_gqa_decode_attention(
        x["q"], kl, vl, bt, positions, active=active, window=window
    )
    return o, x["k_pool"].at[layer].set(kl), x["v_pool"].at[layer].set(vl)


def _bits(a):
    return np.asarray(jax.lax.bitcast_convert_type(a, jnp.uint16 if a.dtype.itemsize == 2 else jnp.uint8))


def _assert_step_agrees(x, bt, positions, active, window, *, rows=None, layer=1):
    bt = jnp.asarray(bt, jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)
    active = jnp.asarray(active, bool)
    o_k, k_k, v_k = _kernel_step(x, jnp.int32(layer), bt, positions, active, window)
    o_p, k_p, v_p = _plain_step(x, layer, bt, positions, active, window)
    np.testing.assert_array_equal(_bits(k_k), _bits(k_p))
    np.testing.assert_array_equal(_bits(v_k), _bits(v_p))
    rows = np.asarray(active if rows is None else rows, bool)
    np.testing.assert_allclose(
        np.asarray(o_k, np.float32)[rows], np.asarray(o_p, np.float32)[rows],
        rtol=RTOL, atol=ATOL,
    )
    return o_k, k_k, v_k


CASES = {
    # name: (H, KH, window, positions, active, sentinel (slot, block) pairs, rows compared)
    "qwen-heads-28-4": (28, 4, None, [0, 31, 32, 70], [1, 1, 1, 1], (), None),
    "mistral-heads-32-8": (32, 8, None, [5, 95, 64, 127], [1, 1, 1, 1], (), None),
    "window-smaller-than-the-cache": (28, 4, 40, [5, 95, 64, 127], [1, 1, 1, 1], (), None),
    "window-within-one-page": (32, 8, 8, [0, 31, 32, 100], [1, 1, 1, 1], (), None),
    "position-zero": (28, 4, None, [0, 0, 0, 0], [1, 1, 1, 1], (), None),
    "a-pages-last-row": (28, 4, None, [31, 63, 95, 127], [1, 1, 1, 1], (), None),
    "a-pages-first-row": (28, 4, None, [32, 64, 96, 0], [1, 1, 1, 1], (), None),
    # blocks past the position hold the P sentinel: never read, and both forms mask them
    "sentinel-blocks-past-the-position": (
        28, 4, None, [10, 40, 70, 100], [1, 1, 1, 1], ((0, 1), (0, 2), (0, 3), (1, 2), (2, 3)), None,
    ),
    # an inactive row: nothing of it is read, nothing is written, its output is zero
    "an-inactive-row": (28, 4, None, [10, 40, 70, 100], [1, 0, 1, 0], (), None),
    # a row past its allocation: the block its position falls in is a sentinel,
    # so it writes nothing; the plain form reads a clamped page there, so its
    # output is garbage by contract and is not compared
    "a-row-past-its-allocation": (
        28, 4, None, [10, 40, 70, 100], [1, 1, 1, 1], ((1, 1), (3, 3)), [1, 0, 1, 0],
    ),
    "every-row-inactive": (32, 8, None, [10, 40, 70, 100], [0, 0, 0, 0], (), None),
}


@pytest.mark.parametrize("case", list(CASES), ids=list(CASES))
def test_kernel_step_matches_the_plain_step(case):
    H, KH, window, positions, active, sentinels, rows = CASES[case]
    B = len(positions)
    x = _inputs(list(CASES).index(case), B, H, KH)
    P = B * NB
    bt = x["bt"].copy()
    for slot, block in sentinels:
        bt[slot, block] = P
    active = np.asarray(active, bool)
    o, k, v = _assert_step_agrees(x, bt, positions, active, window, rows=rows)
    # written at all: exactly one [KH, D] row per active, allocated slot, in one layer
    writers = sum(
        bool(a) and bt[b, p // PAGE] < P for b, (p, a) in enumerate(zip(positions, active))
    )
    changed = (_bits(k) != _bits(x["k_pool"])).any(axis=-1)  # [L, P, KH, page]
    assert changed[0].sum() == 0 and changed[1].sum() <= writers * KH
    assert float(jnp.abs(o[~active]).max(initial=0.0)) == 0.0


def test_an_inactive_rows_pages_are_untouched_bit_for_bit():
    x = _inputs(11, 4, 28, 4)
    active = np.array([True, False, True, True])
    _, k, v = _assert_step_agrees(x, x["bt"], [3, 50, 64, 127], active, None)
    mine = x["bt"][1]
    for new, old in ((k, x["k_pool"]), (v, x["v_pool"])):
        np.testing.assert_array_equal(_bits(new[:, mine]), _bits(old[:, mine]))


def test_fp8_pool_dequantises_per_page_and_writes_fp8_rows():
    x = _inputs(12, 4, 28, 4, dtype=jnp.float8_e4m3fn)
    _assert_step_agrees(x, x["bt"], [0, 31, 32, 100], [1, 1, 1, 1], None)


def test_two_chained_steps_the_second_reads_what_the_first_wrote():
    x = _inputs(13, 4, 28, 4)
    bt = jnp.asarray(x["bt"])
    active = jnp.ones((4,), bool)
    pos = jnp.asarray([0, 31, 62, 100], jnp.int32)  # row 1 crosses into its next page
    y = dict(x)
    z = dict(x)
    for step in range(2):
        o_k, y["k_pool"], y["v_pool"] = _kernel_step(y, jnp.int32(0), bt, pos + step, active, None)
        o_p, z["k_pool"], z["v_pool"] = _plain_step(z, 0, bt, pos + step, active, None)
        np.testing.assert_array_equal(_bits(y["k_pool"]), _bits(z["k_pool"]))
        np.testing.assert_array_equal(_bits(y["v_pool"]), _bits(z["v_pool"]))
        np.testing.assert_allclose(
            np.asarray(o_k, np.float32), np.asarray(o_p, np.float32), rtol=RTOL, atol=ATOL
        )
        # a new query and new rows for the second step; the pools chain
        nxt = _inputs(14 + step, 4, 28, 4)
        for name in ("q", "k_new", "v_new"):
            y[name] = z[name] = nxt[name]
    # the first step's row is in the pool the second step read: with a query
    # equal to that row's key, dropping it from the pool changes the answer
    row = x["k_new"][0, :, 0, :]
    assert np.array_equal(_bits(y["k_pool"][0, bt[0, 0], :, 0, :]), _bits(row))


def test_plan_lists_each_rows_own_live_pages():
    bt = jnp.asarray([[3, 9, 1, 7], [0, 8, 8, 8], [2, 4, 5, 8], [6, 8, 8, 8]], jnp.int32)
    pos = jnp.asarray([70, 10, 100, 40], jnp.int32)
    act = jnp.asarray([True, True, True, True])
    items, n = attn.paged_decode_plan(bt, pos, act, n_pages=8, page=PAGE)
    # row 0: blocks 0-2 but block 1 names page 9 >= 8 (a sentinel); row 3's
    # position falls in a sentinel block, so only its block 0 is live
    assert items[: int(n[0])].tolist() == [0, 2, 4, 8, 9, 10, 12] and int(n[0]) == 7
    items, n = attn.paged_decode_plan(bt, pos, act, n_pages=10, page=PAGE, window=16)
    # only pages the window reaches: row 0 drops block 0, row 2 blocks 0-1 (page 8 is a page now)
    assert items[: int(n[0])].tolist() == [1, 2, 4, 10, 11, 12, 13]
    items, n = attn.paged_decode_plan(bt, pos, ~act, n_pages=10, page=PAGE)
    assert int(n[0]) == 0


@pytest.mark.parametrize(
    "kv_dtype,page,head_dim,fp8_dot,backend,path",
    [
        (jnp.bfloat16, 512, 128, False, "tpu", "kernel"),
        (jnp.float8_e4m3fn, 512, 128, False, "tpu", "kernel"),
        (jnp.float8_e4m3fn, 512, 128, True, "tpu", "xla"),  # fp8 through the dots
        (jnp.bfloat16, 512, 64, False, "tpu", "xla"),  # toy head width
        (jnp.bfloat16, 8, 128, False, "tpu", "xla"),  # page smaller than a packed tile
        (jnp.bfloat16, 512, 128, False, "cpu", "xla"),  # no Mosaic compiler
    ],
)
def test_path_is_chosen_by_platform_and_shape(monkeypatch, kv_dtype, page, head_dim, fp8_dot, backend, path):
    monkeypatch.setattr(attn.jax, "default_backend", lambda: backend)
    assert attn.paged_decode_kv_path(kv_dtype, page, head_dim, fp8_dot=fp8_dot) == path


@pytest.mark.parametrize("window,split", [(None, 0), (24, 0), (24, 1)], ids=["full", "windowed", "qwen2-split"])
def test_decode_step_paged_on_the_kernel_path_matches_the_plain_path(monkeypatch, window, split):
    """The model step's wiring: pool in the layer scan's carry, layer index in
    xs, one plan per window, two steps chained through the donated cache."""
    cfg = DecoderConfig(
        vocab_size=64, hidden_size=256, intermediate_size=512, num_layers=3,
        num_heads=4, num_kv_heads=2, head_dim=D, max_seq_len=NB * PAGE,
        sliding_window=window, window_layer_start=split, attn_bias=True,
        dtype=jnp.bfloat16,
    )
    params = llama.init(cfg, jax.random.key(0))
    B, P = 3, 3 * NB
    rng = np.random.default_rng(5)
    bt = jnp.asarray(rng.permutation(P).reshape(B, NB), jnp.int32)
    shape = (cfg.num_layers, P, cfg.num_kv_heads, PAGE, D)
    cache = llama.PagedKVCache(
        k=jnp.asarray(rng.standard_normal(shape) * 0.5, jnp.bfloat16),
        v=jnp.asarray(rng.standard_normal(shape) * 0.5, jnp.bfloat16),
        lengths=jnp.asarray([31, 70, 5], jnp.int32),
    )
    active = jnp.asarray([True, True, False])
    tokens = jnp.asarray([1, 2, 3], jnp.int32)

    def two_steps():
        step = jax.jit(lambda t, c: llama.decode_step_paged(params, cfg, t, c, bt, active=active))
        l1, c1 = step(tokens, cache)
        l2, c2 = step(tokens + 1, c1)
        return l1, l2, c2

    p1, p2, pc = two_steps()
    monkeypatch.setattr(llama, "paged_decode_kv_path", lambda *a, **k: "kernel")
    monkeypatch.setattr(
        llama, "paged_decode_update_attend",
        functools.partial(attn.paged_decode_update_attend, interpret=True),
    )
    k1, k2, kc = two_steps()
    assert kc.lengths.tolist() == pc.lengths.tolist() == [33, 72, 5]
    live = np.asarray(active)
    # logits: float32 of a bf16 head matmul over hidden states that differ by
    # bf16 roundings of the attention output, through 3 layers
    np.testing.assert_allclose(np.asarray(k1)[live], np.asarray(p1)[live], rtol=0.05, atol=0.05)
    np.testing.assert_allclose(np.asarray(k2)[live], np.asarray(p2)[live], rtol=0.05, atol=0.05)
    # the rows written differ by those roundings too; everything else is bit-equal
    same = _bits(kc.k) == _bits(pc.k)
    assert (~same).any(axis=-1).sum() <= 2 * 2 * cfg.num_layers * cfg.num_kv_heads
    np.testing.assert_allclose(
        np.asarray(kc.k, np.float32), np.asarray(pc.k, np.float32), rtol=0.05, atol=0.05
    )
