"""Ops correctness: flash kernel vs reference, ring attention on the 8-device mesh,
sampling semantics, norms/rope vs straightforward numpy."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from django_assistant_bot_tpu.ops import (
    dot_product_attention,
    flash_attention,
    layer_norm,
    ring_attention,
    rms_norm,
    sample_logits,
)


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(0)
    B, H, S, D = 2, 4, 256, 64
    mk = lambda: jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    return mk(), mk(), mk()


# (id, B, H, S, D, Dv, causal, window, scale, forced tiles / chunk).  Two heads unless an
# earlier test's case is kept as it was.  Chosen so that every tile ``flash_tiles`` can pick
# occurs, and every kind of tile the kernel tells apart (``_tile_kinds`` below names them).
FLASH_CASES = [
    # the cases of the tests this one replaces (PR 38), as they were
    ("s256-full", 2, 4, 256, 64, 64, False, None, None, {}),
    ("s256-causal", 2, 4, 256, 64, 64, True, None, None, {}),
    ("s256-window64", 2, 4, 256, 64, 64, True, 64, None, {}),
    ("s256-window100", 2, 4, 256, 64, 64, True, 100, None, {}),
    ("s256-window256", 2, 4, 256, 64, 64, True, 256, None, {}),
    ("s512-window64-skips-below", 1, 2, 512, 64, 64, True, 64, None, {}),
    ("s512-window150-skips-below", 1, 2, 512, 64, 64, True, 150, None, {}),
    ("chunks-of-64-full", 2, 4, 256, 64, 64, False, None, None, dict(block_q=64, block_kv=64, chunk_kv=64)),
    ("chunks-of-64-causal", 2, 4, 256, 64, 64, True, None, None, dict(block_q=64, block_kv=64, chunk_kv=64)),
    ("chunks-of-64-causal-window100", 2, 4, 256, 64, 64, True, 100, None, dict(block_q=64, block_kv=64, chunk_kv=64)),
    # every bucket the engine derives (serving/engine.py prefill_shapes) and one beyond a chunk
    ("s384-d128", 1, 2, 384, 128, 128, True, None, None, {}),
    ("s512-latent-widths", 1, 2, 512, 256, 128, True, None, 0.1309, {}),
    ("s640-short-last-key-tile", 1, 2, 640, 128, 128, True, None, None, {}),
    ("s768-d128-two-rows", 2, 2, 768, 128, 128, True, None, None, {}),
    ("s896-short-last-key-tile", 1, 2, 896, 64, 64, True, None, None, {}),
    ("s1024-latent-widths", 1, 2, 1024, 256, 128, True, None, 0.1309, {}),
    ("s1024-d128", 1, 2, 1024, 128, 128, True, None, None, {}),
    ("s1152-three-tiles-of-384", 1, 2, 1152, 64, 64, True, None, None, {}),
    ("s1280-five-tiles-of-256", 1, 2, 1280, 64, 64, True, None, None, {}),
    ("s1408-short-last-tiles", 1, 2, 1408, 64, 64, True, None, None, {}),
    ("s2048-d64", 1, 2, 2048, 64, 64, True, None, None, {}),
    ("s640-full-short-last-key-tile", 1, 2, 640, 64, 64, False, None, None, {}),
    # the window: narrower than a tile, off the block, and nearly the sequence
    ("s1024-window128", 1, 2, 1024, 64, 64, True, 128, None, {}),
    ("s896-window200-two-rows", 2, 2, 896, 64, 64, True, 200, None, {}),
    ("s1024-window1000", 1, 2, 1024, 128, 128, True, 1000, None, {}),
    ("s2048-window1000", 1, 2, 2048, 64, 64, True, 1000, None, {}),
    ("s640-window200-not-causal", 1, 2, 640, 64, 64, False, 200, None, {}),
    # a power of two folds into q; any other scale stays on the scores
    ("s256-scale-quarter", 1, 2, 256, 64, 64, True, None, 0.25, {}),
    ("s384-scale-0.3", 1, 2, 384, 64, 64, True, None, 0.3, {}),
    # long keys stream in chunks: the state outlives a grid step, dead chunks are clamped
    ("s1024-chunks-of-256", 1, 2, 1024, 64, 64, True, None, None, dict(chunk_kv=256)),
    ("s1024-chunks-of-512-window200", 1, 2, 1024, 64, 64, True, 200, None, dict(chunk_kv=512)),
    ("s768-chunks-of-384-short-last-tile", 1, 2, 768, 64, 64, True, None, None, dict(block_kv=256, chunk_kv=384)),
    ("s512-chunks-of-128-full", 1, 2, 512, 128, 128, False, None, None, dict(chunk_kv=128)),
]


def _case_tiles(S, D, Dv, heads, window, forced):
    from django_assistant_bot_tpu.ops.attention import flash_tiles

    tile_q, tile_kv, _ = flash_tiles(S, S, D, Dv, heads, window=window, itemsize=4)
    chunk = forced.get("chunk_kv", min(8192, S))
    return min(forced.get("block_q", tile_q), S), min(forced.get("block_kv", tile_kv), chunk), chunk


def _tile_kinds(S, block_q, block_kv, chunk, causal, window):
    """What the kernel tells apart, worked out here in plain Python from the
    positions alone: for each (query tile, key tile of a chunk) whether it is
    never visited (``above`` the diagonal, ``below`` the band), takes no mask
    (``inside``), or is masked because the ``diagonal`` or the band's ``edge``
    crosses it; plus ``short-q`` / ``short-kv`` where a last tile is short."""
    kinds = set()
    for q0 in range(0, S, block_q):
        rows = range(q0, min(q0 + block_q, S))
        if len(rows) < block_q:
            kinds.add("short-q")
        for c0 in range(0, S, chunk):
            for k0 in range(c0, c0 + chunk, block_kv):
                keys = range(k0, min(k0 + block_kv, c0 + chunk))
                if len(keys) < block_kv:
                    kinds.add("short-kv")
                seen = [
                    (not causal or k <= r) and (window is None or k > r - window)
                    for r in (rows[0], rows[-1]) for k in (keys[0], keys[-1])
                ]
                if causal and keys[0] > rows[-1]:
                    kinds.add("above")
                elif window is not None and keys[-1] <= rows[0] - window:
                    kinds.add("below")
                elif all(seen):
                    kinds.add("inside")
                else:
                    if causal and keys[-1] > rows[0]:
                        kinds.add("diagonal")
                    if window is not None and keys[0] <= rows[-1] - window:
                        kinds.add("edge")
    return kinds


@pytest.mark.parametrize("B,H,S,D,Dv,causal,window,scale,forced", [c[1:] for c in FLASH_CASES], ids=[c[0] for c in FLASH_CASES])
def test_flash_matches_reference(B, H, S, D, Dv, causal, window, scale, forced):
    """The kernel in interpret mode against the plain function, over every tile
    the rule can choose: short last tiles, diagonal, under-diagonal and band-edge
    tiles, tiles never visited, a value width and a scale of its own, key chunks."""
    rng = np.random.default_rng(S + D)
    q, k = (jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32) for _ in range(2))
    v = jnp.asarray(rng.normal(size=(B, H, S, Dv)), jnp.float32)
    ref = dot_product_attention(q, k, v, causal=causal, window=window, scale=scale)
    out = flash_attention(q, k, v, causal=causal, window=window, scale=scale, interpret=True, **forced)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)


def test_flash_cases_meet_every_kind_of_tile():
    """The set above is chosen, not the whole product: this says what it was chosen for."""
    met, tiles = set(), set()
    for _, B, H, S, D, Dv, causal, window, _, forced in FLASH_CASES:
        block_q, block_kv, chunk = _case_tiles(S, D, Dv, B * H, window, forced)
        met |= _tile_kinds(S, block_q, block_kv, chunk, causal, window)
        if not forced:
            tiles.add((block_q, block_kv))
    assert met == {"above", "below", "inside", "diagonal", "edge", "short-q", "short-kv"}
    # every tile the rule picks for a sequence the engine can dispatch (whole blocks, 256 up
    # to two chunks of 1,024) is met by a case
    from django_assistant_bot_tpu.ops.attention import FLASH_BLOCK, flash_tiles

    for S in range(2 * FLASH_BLOCK, 2048 + 1, FLASH_BLOCK):
        for D, Dv in ((64, 64), (128, 128), (256, 128)):
            assert flash_tiles(S, S, D, Dv, 2)[:2] in tiles, (S, D, Dv)


# what the benchmark's two configurations dispatch (tick_stats()["prefill_shapes"], PERF.md
# section 6): Qwen2.5-7B's 28 heads x rows at every bucket of whole blocks, A.X-K1's 64 heads
# at key width 256 / value width 128; and the heads a device holds under a four-way mesh
DISPATCHED_TILES = [
    *[(28 * rows, S, 128, 128, tile + (4,))
      for S, tile in {256: (256, 256), 384: (384, 384), 512: (512, 512), 640: (640, 512),
                      768: (768, 512), 896: (896, 512), 1024: (512, 512)}.items()
      for rows in (1, 2, 4) if rows * S <= 1024],
    (64, 512, 256, 128, (512, 512, 4)),
    (64, 1024, 256, 128, (512, 512, 4)),
    (7, 384, 128, 128, (384, 384, 1)),
    (7, 1024, 128, 128, (512, 512, 1)),
    (16, 1024, 256, 128, (512, 512, 4)),
]


@pytest.mark.parametrize("heads,S,D,Dv,tiles", DISPATCHED_TILES, ids=[f"{c[0]}x{c[1]}x{c[2]}" for c in DISPATCHED_TILES])
def test_flash_tiles_names_the_tile_of_each_dispatched_shape(heads, S, D, Dv, tiles):
    """(query tile, key tile, heads a program) as measured on the v5e (PR 38):
    a change of the rule shows here, and wants its own measurement."""
    from django_assistant_bot_tpu.ops.attention import FLASH_BLOCK, flash_tiles

    assert flash_tiles(S, S, D, Dv, heads) == tiles
    assert FLASH_BLOCK == 128  # what attention() admits and prefill_shapes steps by: not the tile


def test_flash_tiles_follow_a_window_and_long_keys():
    from django_assistant_bot_tpu.ops.attention import flash_tiles

    # a band narrower than the tile caps it at the band's whole blocks; a wider one does not
    assert flash_tiles(2048, 2048, 128, 128, 32, window=256)[:2] == (256, 256)
    assert flash_tiles(1024, 1024, 128, 128, 32, window=200)[:2] == (128, 128)
    assert flash_tiles(2048, 2048, 128, 128, 32, window=4096)[:2] == (512, 512)
    # keys beyond a chunk: fewer heads a program hold the 8,192-key chunk in VMEM
    assert flash_tiles(16384, 16384, 128, 128, 32) == (512, 512, 2)
    assert flash_tiles(16384, 16384, 256, 128, 64) == (512, 512, 1)


def test_window_mask_semantics():
    """keep iff kpos > qpos - W (HF sliding_window_overlay): with W=1 every
    query sees only itself, so softmax returns exactly its own value row."""
    rng = np.random.default_rng(2)
    B, H, S, D = 1, 1, 8, 4
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    out = dot_product_attention(q, k, v, causal=True, window=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(v), atol=1e-6)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(qkv, mesh8, causal):
    q, k, v = qkv
    ref = dot_product_attention(q, k, v, causal=causal)
    out = ring_attention(q, k, v, mesh8, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)


def test_decode_attention_with_offset():
    """q_offset makes single-token decode equal the last row of full attention."""
    rng = np.random.default_rng(1)
    B, H, S, D = 1, 2, 16, 8
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    full = dot_product_attention(q, k, v, causal=True)
    last = dot_product_attention(q[:, :, -1:], k, v, causal=True, q_offset=S - 1)
    np.testing.assert_allclose(np.asarray(last[:, :, 0]), np.asarray(full[:, :, -1]), atol=1e-5)


def test_sample_greedy_and_temperature():
    logits = jnp.asarray([[0.0, 5.0, 1.0], [9.0, 0.0, 0.0]], jnp.float32)
    toks = sample_logits(logits, jax.random.key(0), temperature=0.0, top_k=0, top_p=1.0)
    assert toks.tolist() == [1, 0]
    # mixed greedy/sampled batch compiles as one call
    toks = sample_logits(
        logits, jax.random.key(0), temperature=jnp.asarray([0.0, 1.0]), top_k=2, top_p=0.9
    )
    assert toks[0] == 1


def test_top_p_restricts_support():
    # one dominant token, p small -> always that token even at high temperature
    logits = jnp.asarray([[10.0, 0.0, 0.0, 0.0]], jnp.float32)
    for i in range(5):
        t = sample_logits(logits, jax.random.key(i), temperature=2.0, top_k=0, top_p=0.5)
        assert t.tolist() == [0]


def test_norms_match_numpy():
    rng = np.random.default_rng(2)
    x = rng.normal(size=(3, 7, 16)).astype(np.float32)
    w = rng.normal(size=(16,)).astype(np.float32)
    b = rng.normal(size=(16,)).astype(np.float32)

    rms = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5) * w
    np.testing.assert_allclose(np.asarray(rms_norm(jnp.asarray(x), jnp.asarray(w))), rms, atol=1e-5)

    mu, var = x.mean(-1, keepdims=True), x.var(-1, keepdims=True)
    ln = (x - mu) / np.sqrt(var + 1e-12) * w + b
    np.testing.assert_allclose(
        np.asarray(layer_norm(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b))), ln, atol=1e-4
    )


def test_top_k_hierarchical_matches_lax_top_k():
    """Exact at large vocab (the decode hot path): same values, and ids agree
    wherever values are unique; padding lanes never leak in."""
    from django_assistant_bot_tpu.ops.sampling import top_k_hierarchical

    rng = np.random.default_rng(0)
    for V in (16_384, 128_256, 5000):  # aligned, unaligned (pad), small
        x = jnp.asarray(rng.normal(size=(4, V)).astype(np.float32))
        vals, idx = jax.jit(lambda a: top_k_hierarchical(a, 50))(x)
        ref_vals, ref_idx = jax.lax.top_k(x, 50)
        np.testing.assert_allclose(np.asarray(vals), np.asarray(ref_vals))
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_idx))
        assert int(idx.max()) < V  # no padded-lane index escapes


def test_top_k_hierarchical_adversarial_clusters():
    """All top-k values packed into ONE group must still all be found (the
    pigeonhole argument the implementation relies on)."""
    from django_assistant_bot_tpu.ops.sampling import top_k_hierarchical

    V, k = 32_768, 50
    x = np.zeros((2, V), np.float32)
    x[0, 256 : 256 + k] = np.arange(k, 0, -1)  # contiguous block in one group
    x[1, ::701] = np.arange(len(x[1, ::701]), 0, -1)  # scattered
    vals, idx = top_k_hierarchical(jnp.asarray(x), k)
    ref_vals, ref_idx = jax.lax.top_k(jnp.asarray(x), k)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(ref_vals))


def test_top_k_hierarchical_degenerate_rows_stay_in_vocab():
    """A row with fewer than k entries above the finite NEG_INF pad value (a
    fully-masked FSM state at an unaligned vocab) must never return an index
    >= V — a uniform draw over the all-NEG_INF candidates would otherwise
    emit an out-of-vocab token id (r4 advisor finding)."""
    from django_assistant_bot_tpu.ops.attention import NEG_INF
    from django_assistant_bot_tpu.ops.sampling import top_k_hierarchical

    V, k = 130, 50  # unaligned: 126 pad lanes tie with the masked row
    x = np.full((2, V), NEG_INF, np.float32)
    x[1, 7] = 1.0  # one live candidate; row 0 fully masked
    vals, idx = top_k_hierarchical(jnp.asarray(x), k)
    assert int(np.asarray(idx).max()) < V
    assert int(np.asarray(idx)[1, 0]) == 7


def test_sample_logits_large_vocab_greedy_matches_argmax():
    from django_assistant_bot_tpu.ops.sampling import sample_logits

    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(3, 128_256)).astype(np.float32))
    out = sample_logits(
        logits, jax.random.key(0), temperature=jnp.zeros((3,)), top_k=50, top_p=0.95
    )
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(jnp.argmax(logits, axis=-1))
    )


def test_longrope_long_regime_warns_short_does_not():
    """A deployment past the pretrained context commits to the LONG factor
    list for all sequences — diverging from HF on short prompts.  That choice
    must be visible at load time (VERDICT r4 missing #2)."""
    import warnings

    from django_assistant_bot_tpu.ops.rope import rope_frequencies

    scaling = ("longrope", [1.0, 1.1, 1.2, 1.3], [2.0, 2.5, 3.0, 4.0], 32, 1.5)
    with pytest.warns(UserWarning, match="LONG factor list"):
        rope_frequencies(8, 64, theta=1e4, scaling=scaling, deployed_len=128)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # short regime: silent
        rope_frequencies(8, 16, theta=1e4, scaling=scaling, deployed_len=32)
