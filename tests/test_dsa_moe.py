"""The latent-attention MoE block with a lightning indexer, top-k sparse
attention and a score-correction bias (``models/mla_moe.py`` under a
``deepseek_v32`` config) against its plain reference
(``benchmarks/reference/dsa_moe.py``, which imports nothing of the program), at
a tiny preset on the CPU in float32: ``tests/data/dsa_moe_tiny.json`` is
``mla_moe_tiny.json`` with 4 index heads of 16, ``index_topk`` 8 and
``noaux_tc``, so every context here is several times the selection.

Tolerances as ``tests/test_mla_moe.py`` has them (``ATOL = 2e-4`` on O(1)
logits, float32 on both sides).  A selection is a discrete choice: where the
program and the reference kept different keys the logits would differ by far
more than the tolerance (the ``dense`` control moves them by ~3), so equal
logits at every position also say the selected sets were the same; the sets are
compared directly too.
"""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import families
from benchmarks.reference import dsa_moe as ref
from django_assistant_bot_tpu.models import DecoderConfig, mixtral, mla_moe, module_for
from django_assistant_bot_tpu.ops import attention as A

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DATA = os.path.join(ROOT, "benchmarks")
ATOL = 2e-4
SEED = 2**31 + 77


def _conf(**hf):
    with open(os.path.join(HERE, "data", "dsa_moe_tiny.json")) as f:
        conf = json.load(f)
    conf["hf"].update(hf)
    return conf


@pytest.fixture(scope="module")
def family():
    return families.load(_conf(), DATA)


def _program(family, conf):
    cfg = dataclasses.replace(DecoderConfig.from_hf(conf["hf"], dtype=jnp.float32), max_seq_len=256)
    params = jax.tree.map(lambda x: x.astype(jnp.float32), family.served_params(conf, SEED))
    return cfg, mla_moe.held_params(cfg, params)  # the family's tree is a checkpoint's; the entry points take the held one


def _reference(family, conf, seqs, firsts=None, control=None):
    cols = list(range(conf["hf"]["vocab_size"]))
    return family.reference_logits(conf, SEED, seqs, firsts or [0] * len(seqs), cols, control=control)


def _ids(n, seed=0, vocab=512):
    return [int(t) for t in np.random.default_rng(seed).integers(0, vocab, n)]


def test_from_hf_reads_deepseek_v32_the_indexer_the_bias_and_the_draft_head():
    hf = _conf()["hf"]
    cfg = DecoderConfig.from_hf(hf)
    lm = cfg.latent_moe
    assert cfg.arch == "mla_moe" and module_for(cfg) is mla_moe
    assert (lm.index_n_heads, lm.index_head_dim, lm.index_topk, lm.router_bias) == (4, 16, 8, True)
    assert hf["num_nextn_predict_layers"] == 1 and cfg.num_layers == 3  # accepted, and no such layer is built
    assert mla_moe.kv_kind(cfg) == "latent+index"
    # one cached token: the padded latent row and the index key, in every layer
    assert mla_moe.kv_bytes_per_token(cfg) == 3 * (128 + 16) * 2
    plain = DecoderConfig.from_hf({k: v for k, v in hf.items() if not k.startswith("index_")} | {"model_type": "deepseek_v3"})
    assert (plain.latent_moe.index_topk, plain.latent_moe.router_bias) == (0, True)  # noaux_tc alone is read too
    assert mla_moe.kv_kind(plain) == "latent" and mla_moe.kv_bytes_per_token(plain) == 3 * 128 * 2
    with pytest.raises(ValueError, match="index_topk"):
        DecoderConfig.from_hf({**hf, "index_topk": None})
    with pytest.raises(ValueError, match="speculative"):
        mla_moe.check_serving(speculative=2)
    with pytest.raises(ValueError, match="second kind of row"):
        mla_moe.check_serving(prefix_cache=4)


@pytest.mark.parametrize("first_dense", [1, 2])
def test_any_number_of_leading_dense_layers_is_read(first_dense):
    cfg = DecoderConfig.from_hf(_conf(first_k_dense_replace=first_dense)["hf"])
    assert cfg.latent_moe.first_dense_layers == first_dense
    cfg32 = dataclasses.replace(cfg, dtype=jnp.float32)
    p = jax.eval_shape(lambda: mla_moe.init(cfg32, jax.random.key(0)))  # a checkpoint's tree
    assert p["dense_layers"]["w_iq"].shape == (first_dense, 32, 64) and p["moe_layers"]["router_bias"].shape == (3 - first_dense, 16)
    held = jax.eval_shape(lambda: mla_moe.held_params(cfg32, mla_moe.init(cfg32, jax.random.key(0))))  # what the device holds
    assert held["dense_layers"]["w_iq"].shape == (first_dense, 4, 16, 32) and held["moe_layers"]["w_iq"].shape == (3 - first_dense, 4, 16, 32)
    axes, is_axes = mla_moe.logical_axes(cfg), lambda x: isinstance(x, tuple)
    assert jax.tree.structure(axes, is_leaf=is_axes) == jax.tree.structure(held)
    assert all(len(a) == x.ndim for a, x in zip(jax.tree.leaves(axes, is_leaf=is_axes), jax.tree.leaves(held)))


@pytest.mark.parametrize("lengths", [[18, 7], [40, 29], [48, 33]])
def test_prefill_logits_equal_the_plain_reference_at_contexts_several_times_the_selection(family, lengths):
    conf = _conf()
    cfg, params = _program(family, conf)
    seqs = [_ids(48, 1), _ids(48, 2)]
    logits, (rows, keys), stats = mla_moe.prefill(params, cfg, jnp.asarray(seqs), jnp.asarray(lengths))
    want = _reference(family, conf, [s[:n] + [0] for s, n in zip(seqs, lengths)])
    for i in range(2):
        np.testing.assert_allclose(np.asarray(logits[i]), want[i][-1], atol=ATOL)
    assert rows.shape == (3, 2, 48, 128) and keys.shape == (3, 2, 48, 16) and stats.shape == (4 + 16 + 10,)
    # the sparse attention's counters, a layer's worth, in the columns of "every other prefill program"
    n = np.asarray(lengths)
    causal = int((n * (n + 1) // 2).sum())
    kept = int(sum(sum(min(8, t + 1) for t in range(m)) for m in lengths))
    # the selection counted over the view up to the step the longest row reaches: both rows' 48 queries x that
    scanned = 2 * 48 * min(w for w in A.select_widths(48, 8, 8) if w >= max(lengths))
    assert [int(x) for x in stats[-10:]] == [0, 0, 0, 0, 0, 1, int(n.sum()), causal, kept, scanned]
    assert float(np.abs(want[0][-1]).max()) > 1.0  # logits are O(1): the tolerance means something


def test_the_dense_control_is_another_function(family):
    """Attending every s <= t (the block without its indexer) moves the logits by far more than any tolerance."""
    conf = _conf()
    seqs = [_ids(48, 1)]
    sparse, dense = _reference(family, conf, seqs), _reference(family, conf, seqs, control="dense")
    assert np.abs(sparse[0][:8] - dense[0][:8]).max() < 1e-5  # the first index_topk positions select everything
    assert np.abs(sparse[0][16:] - dense[0][16:]).max() > 0.5


def _paged(cfg, page=8, NB=8, P=32, slots=4):
    cache = mla_moe.init_paged_cache(cfg, slots, P, page)
    bt = np.full((slots, NB), P, np.int32)
    bt[0, :7], bt[2, :7] = [3, 5, 7, 9, 11, 13, 15], [2, 4, 6, 8, 10, 12, 14]
    return cache, jnp.asarray(bt)


def test_prefill_then_paged_decode_equals_the_reference_at_every_position(family):
    """Both caches: a step writes its latent row and its index key, scores the slot's index keys, and
    gathers only the selected latent rows (absorbed form) where the reference expands every position."""
    conf = _conf()
    cfg, params = _program(family, conf)
    seqs = [_ids(52, 3), _ids(45, 4)]
    want = _reference(family, conf, seqs)
    cache, bt = _paged(cfg)
    n0 = [20, 12]
    ids = np.zeros((2, 24), np.int32)
    for i, s in enumerate(seqs):
        ids[i, : n0[i]] = s[: n0[i]]
    logits, rows, stats = mla_moe.prefill(params, cfg, jnp.asarray(ids), jnp.asarray(n0))
    cache = mla_moe.insert_sequences_paged(cache, rows, stats, jnp.asarray(n0), jnp.asarray([0, 2]), bt[jnp.asarray([0, 2])])
    step = jax.jit(lambda t, c, a: mla_moe.decode_step_paged(params, cfg, t, c, bt, active=a))
    active = jnp.asarray([True, False, True, False])
    for k in range(25):
        toks = jnp.asarray([seqs[0][n0[0] + k], 0, seqs[1][n0[1] + k], 0], jnp.int32)
        logits, cache = step(toks, cache, active)
        np.testing.assert_allclose(np.asarray(logits[0]), want[0][n0[0] + k], atol=ATOL)
        np.testing.assert_allclose(np.asarray(logits[2]), want[1][n0[1] + k], atol=ATOL)
    assert [int(x) for x in cache.lengths] == [45, 0, 37, 0]  # frozen slots wrote nothing
    # decode's counters: 25 steps, 2 rows each, every row past the selection keeps exactly index_topk
    steps, queries, causal, kept, scanned = (int(x) for x in cache.stats[0, -10:-5])
    assert (steps, queries, kept, scanned) == (25, 50, 50 * 8, 50 * 64)  # a step counts over an active row's whole view
    assert causal == sum(n + k + 1 for n in n0 for k in range(25))
    assert int(cache.stats[0, 2]) == 25 * 2  # the routed layers' counters stay where they were


@pytest.mark.parametrize("chunk", [24, 16])
def test_chunked_prefill_against_the_cache_equals_the_reference(family, chunk):
    conf = _conf()
    cfg, params = _program(family, conf)
    s = _ids(53, 5)
    want = _reference(family, conf, [s + [0]])[0][-1]
    page, NB, P = 8, 8, 16
    bt_row = jnp.asarray([9, 1, 4, 2, 7, 11, 3, 0], jnp.int32)
    cache = mla_moe.init_paged_cache(cfg, 2, P, page)
    for start in range(0, 53, chunk):
        valid = min(chunk, 53 - start)
        ids = (s[start:start + valid] + [0] * chunk)[:chunk]
        logits, cache = mla_moe.prefill_chunk_paged(
            params, cfg, jnp.asarray([ids]), cache, bt_row, jnp.int32(1), jnp.int32(start), jnp.int32(valid))
    np.testing.assert_allclose(np.asarray(logits[0]), want, atol=ATOL)
    assert int(cache.lengths[1]) == 53
    programs, queries, causal, kept, scanned = (int(x) for x in cache.stats[1, -10:-5])  # the chunk programs' columns
    assert (programs, queries, causal) == (-(-53 // chunk), 53, 53 * 54 // 2)
    assert kept == sum(min(8, t + 1) for t in range(53))
    # every chunk counts over the view up to the first step (8 positions each here) at or past its last key
    assert scanned == chunk * sum(-(-min(start + chunk, 53) // 8) * 8 for start in range(0, 53, chunk))
    assert not np.asarray(cache.stats[1, -5:]).any()
    # the sliding last chunk of the engine re-feeds positions already written: the same rows, the same answer
    again, cache = mla_moe.prefill_chunk_paged(
        params, cfg, jnp.asarray([s[53 - chunk:]]), cache, bt_row, jnp.int32(1), jnp.int32(53 - chunk), jnp.int32(chunk))
    np.testing.assert_allclose(np.asarray(again[0]), want, atol=ATOL)


def test_suffix_prefill_and_copy_pages_carry_the_index_keys(family):
    conf = _conf()
    cfg, params = _program(family, conf)
    s = _ids(53, 5)
    want = _reference(family, conf, [s + [0]])[0][-1]
    page, NB, P = 8, 8, 16
    bt_row = jnp.asarray([9, 1, 4, 2, 7, 11, 3, 0], jnp.int32)
    cache = mla_moe.init_paged_cache(cfg, 2, P, page)
    _, cache = mla_moe.prefill_chunk_paged(
        params, cfg, jnp.asarray([s[:24]]), cache, bt_row, jnp.int32(0), jnp.int32(0), jnp.int32(24))
    # clone the three prefix pages elsewhere and continue from the clones: the index keys came along
    cache = mla_moe.copy_pages(cache, jnp.asarray([9, 1, 4], jnp.int32), jnp.asarray([13, 14, 15], jnp.int32))
    np.testing.assert_array_equal(np.asarray(cache.idx[:, [13, 14, 15]]), np.asarray(cache.idx[:, [9, 1, 4]]))
    np.testing.assert_array_equal(np.asarray(cache.kv[:, [13, 14, 15]]), np.asarray(cache.kv[:, [9, 1, 4]]))
    assert float(np.abs(np.asarray(cache.idx[:, 13])).max()) > 0.1
    bts = jnp.stack([bt_row.at[:3].set(jnp.asarray([13, 14, 15])), jnp.full((NB,), P, jnp.int32)])
    suffix = jnp.asarray([(s[24:] + [0] * 3), [0] * 32])
    logits, cache = mla_moe.prefill_suffix_paged(
        params, cfg, suffix, cache, bts, jnp.asarray([0, 2]), jnp.asarray([24, 0]), jnp.asarray([29, 0]))
    np.testing.assert_allclose(np.asarray(logits[0]), want, atol=ATOL)
    assert [int(x) for x in cache.stats[1, -5:]] == [1, 29, sum(range(25, 54)), 29 * 8, 2 * 32 * 56]


def test_a_reused_pages_stale_rows_are_never_selected(family):
    """A freed page keeps its rows.  Handed to another slot it holds index keys that would score high:
    positions at or past the slot's length, and blocks the table does not name, are never selected."""
    conf = _conf()
    cfg, params = _program(family, conf)
    s = _ids(40, 8)
    want = _reference(family, conf, [s])[0]
    page, NB, P = 8, 8, 16
    bt_row = jnp.asarray([5, 6, 7, 8, 9, 10, P, P], jnp.int32)
    cache = mla_moe.init_paged_cache(cfg, 1, P, page)
    # every page starts full of "stale" rows: large index keys aligned with everything, and latent rows of ones
    cache = cache._replace(idx=jnp.full(cache.idx.shape, 50.0, cache.idx.dtype), kv=jnp.ones(cache.kv.shape, cache.kv.dtype))
    logits, cache = mla_moe.prefill_chunk_paged(
        params, cfg, jnp.asarray([s[:32]]), cache, bt_row, jnp.int32(0), jnp.int32(0), jnp.int32(32))
    np.testing.assert_allclose(np.asarray(logits[0]), want[31], atol=ATOL)
    step = jax.jit(lambda t, c: mla_moe.decode_step_paged(params, cfg, t, c, bt_row[None]))
    for k in range(32, 39):
        logits, cache = step(jnp.asarray([s[k]], jnp.int32), cache)
        np.testing.assert_allclose(np.asarray(logits[0]), want[k], atol=ATOL)


def test_with_index_topk_at_least_the_context_the_block_is_the_dense_one(family):
    """The same weights under a selection that keeps everything equal the ``mla_moe`` path without an
    indexer (its programs: the causal flash attention, the whole-page decode), logit for logit."""
    conf = _conf(index_topk=4096)
    cfg, params = _program(family, conf)
    hf = {k: v for k, v in conf["hf"].items() if not k.startswith("index_")} | {"model_type": "deepseek_v3"}
    plain_cfg = dataclasses.replace(DecoderConfig.from_hf(hf, dtype=jnp.float32), max_seq_len=256)
    drop = ("w_iq", "w_ik", "w_iw", "ik_norm", "ik_bias")
    plain = {k: ({n: w for n, w in v.items() if n not in drop} if isinstance(v, dict) else v) for k, v in params.items()}
    seqs = [_ids(40, 11), _ids(40, 12)]
    lengths = jnp.asarray([40, 23])
    a, (rows, keys), _ = mla_moe.prefill(params, cfg, jnp.asarray(seqs), lengths)
    b, rows_b, _ = mla_moe.prefill(plain, plain_cfg, jnp.asarray(seqs), lengths)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(rows_b))
    want = _reference(family, conf, [seqs[0] + [0]], control="dense")[0][-1]
    np.testing.assert_allclose(np.asarray(a[0]), want, atol=ATOL)
    # and a paged decode step on both
    ca, bt = _paged(cfg)
    cb, _ = _paged(plain_cfg)
    st = jnp.zeros((4 + 16,), jnp.int32)
    ca = mla_moe.insert_sequences_paged(ca, (rows, keys), jnp.zeros((4 + 16 + mla_moe.DSA_STAT,), jnp.int32), lengths, jnp.asarray([0, 2]), bt[jnp.asarray([0, 2])])
    cb = mla_moe.insert_sequences_paged(cb, rows_b, st, lengths, jnp.asarray([0, 2]), bt[jnp.asarray([0, 2])])
    toks, active = jnp.asarray([7, 0, 9, 0], jnp.int32), jnp.asarray([True, False, True, False])
    la, _ = mla_moe.decode_step_paged(params, cfg, toks, ca, bt, active=active)
    lb, _ = mla_moe.decode_step_paged(plain, plain_cfg, toks, cb, bt, active=active)
    np.testing.assert_allclose(np.asarray(la)[[0, 2]], np.asarray(lb)[[0, 2]], atol=1e-5)


def test_the_bias_changes_picks_and_never_weights(family):
    conf = _conf()
    cfg, params = _program(family, conf)
    lm = cfg.latent_moe
    h = jnp.asarray(np.random.default_rng(6).standard_normal((400, 64)), jnp.float32)
    router, bias = params["moe_layers"]["router"][0], params["moe_layers"]["router_bias"][0]
    assert 0.005 < float(jnp.std(bias)) < 0.05
    idx, w = mixtral.route_sigmoid_groups(lm, 4, h, router, bias)
    idx0, w0 = mixtral.route_sigmoid_groups(lm, 4, h, router)
    changed = np.mean([set(a) != set(b) for a, b in zip(np.asarray(idx).tolist(), np.asarray(idx0).tolist())])
    assert 0.02 < changed < 0.5  # a small bias: some tokens pick another set of experts, most do not
    # the weights are the UNBIASED sigmoid scores of the picks, normalised, times 2.5
    sigma = jax.nn.sigmoid(jnp.einsum("te,ex->tx", h, router, precision=jax.lax.Precision.HIGHEST))
    picked = jnp.take_along_axis(sigma, idx, axis=-1)
    np.testing.assert_allclose(np.asarray(w), np.asarray(2.5 * picked / picked.sum(-1, keepdims=True)), atol=1e-6)
    # a large bias on one expert puts it in every token's picks (its group wins too) at its own small weight
    big = jnp.zeros((16,)).at[9].set(10.0)
    idx1, w1 = mixtral.route_sigmoid_groups(lm, 4, h, router, big)
    assert bool((idx1 == 9).any(-1).all())
    w9 = jnp.where(idx1 == 9, w1, 0.0).sum(-1)
    assert float(w9.max()) < 2.5 * 0.9 and float(np.asarray(w1).sum(-1).max()) == pytest.approx(2.5, abs=1e-5)
    # and the reference routes the same way
    with jax.default_matmul_precision("highest"):
        ridx, rw, _ = ref.route(conf["hf"], h, router, bias)
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(np.asarray(ridx), -1))
    order, rorder = np.argsort(np.asarray(idx), -1), np.argsort(np.asarray(ridx), -1)
    np.testing.assert_allclose(np.take_along_axis(np.asarray(w), order, -1), np.take_along_axis(np.asarray(rw), rorder, -1), atol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer(family):
    """Over ep_rank 0..3 the routed parts under the router's bias, with the shared expert counted once,
    equal what the uncut reference gives for the whole layer (model-configs guide, section 4)."""
    hf = _conf()["hf"]
    layer = family.float32_layer(hf, SEED, 2)
    h = jnp.asarray(np.random.default_rng(9).standard_normal((1, 256, 64)), jnp.float32)
    held_keys = ("w_gate", "w_up", "w_down")
    with jax.default_matmul_precision("highest"):
        uncut, _ = ref.moe_ffn(hf, layer, h)
        shared, _ = ref.moe_ffn(hf, {k: (v[:0] if k in held_keys else v) for k, v in layer.items()}, h)
    program, reference = np.asarray(shared), np.asarray(shared)
    for rank in range(4):
        share = _conf(n_routed_experts=4, ep_size=4, ep_rank=rank)["hf"]
        held = {k: (v[4 * rank: 4 * rank + 4] if k in held_keys else v) for k, v in layer.items()}
        cfg = DecoderConfig.from_hf(share, dtype=jnp.float32)
        y, stats = mixtral.held_experts_mlp(cfg, held, h, jnp.ones((1, 256), bool))
        program = program + np.asarray(y)
        with jax.default_matmul_precision("highest"):
            reference = reference + np.asarray(ref.moe_ffn(share, held, h, first_expert=4 * rank)[0]) - np.asarray(shared)
        assert int(stats[0]) == 256 * 4 and 0 < int(stats[1]) < 256 * 4
    np.testing.assert_allclose(program, np.asarray(uncut), atol=ATOL)
    np.testing.assert_allclose(reference, np.asarray(uncut), atol=ATOL)
    assert float(np.abs(np.asarray(uncut) - np.asarray(shared)).max()) > 0.1


# ---------------------------------------------------------------------------
# the selection, as sets
# ---------------------------------------------------------------------------


def _index_inputs(B=2, C=16, S=64, Hi=4, Di=16, seed=0):
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.standard_normal((B, C, Hi, Di)), jnp.float32)
    w = jnp.asarray(r.standard_normal((B, C, Hi)), jnp.float32)
    k = jnp.asarray(r.standard_normal((B, S, Di)), jnp.float32)
    return q, w, k


@pytest.mark.parametrize("topk", [8, 24, 63])
def test_the_programs_selected_sets_equal_the_references(topk):
    """``sparse_select`` (scores, then the threshold found by counting) against the reference's sort,
    in float32, on the same scores: the same set for every query, queries with fewer candidates than
    ``topk`` included."""
    q, w, k = _index_inputs()
    starts = np.asarray([40, 3])
    qpos = jnp.asarray(starts[:, None] + np.arange(16)[None, :])
    ok = jnp.arange(64)[None, None, :] <= qpos[:, :, None]
    keep, _ = A.sparse_select(q, w, k, qpos, ok, topk, jnp.asarray(starts + 16))
    with jax.default_matmul_precision("highest"):
        si = jnp.einsum("bqhk,bqh->bqk", jnp.maximum(jnp.einsum("bqhd,bkd->bqhk", q, k), 0.0), w)
    want, _ = ref.select_block(si, ok, topk)
    np.testing.assert_array_equal(np.asarray(keep), np.asarray(want))
    n = np.asarray(keep).sum(-1)
    np.testing.assert_array_equal(n, np.minimum(topk, np.asarray(qpos) + 1))


def _whole_view_select(q, w, k, qpos, ok, topk, live=None, *, kernel=False):
    """The selection as it ran before it followed the live keys: scores and counting over the WHOLE view."""
    if kernel:
        scores = A.index_scores_t(q, w, k, qpos[:, 0], interpret=True)
        return A.topk_mask(scores, topk, ok.transpose(0, 2, 1), axis=1).transpose(0, 2, 1)
    return A.topk_mask(A.index_scores(q, w, k), topk, ok, axis=2)


# (view, queries a row, index width, topk): the CPU's own path, steps of 8, and the TPU's (the index kernel
# interpreted, steps of its 512-key tile); ``live`` [2] per case: the second row's is never the longer one but once
SELECT_SHAPES = {"plain": (64, 16, 16, 8), "kernel": (2048, 256, 128, 512)}
SELECT_LIVE = {
    "plain": {"within-topk": [8, 3], "topk+1": [9, 9], "under-an-edge": [31, 30], "at-an-edge": [32, 32],
              "over-an-edge": [33, 12], "the-second-row-longer": [12, 41], "whole-view": [64, 50]},
    "kernel": {"within-topk": [512, 100], "topk+1": [513, 513], "under-an-edge": [1023, 700], "at-an-edge": [1024, 1024],
               "over-an-edge": [1025, 700], "the-second-row-longer": [700, 1537], "whole-view": [2048, 1500]},
}
_SELECT_JIT = {}


@pytest.mark.parametrize("case", list(SELECT_LIVE["plain"]))
@pytest.mark.parametrize("path", ["plain", "kernel"])
def test_the_stepped_selection_equals_the_whole_views_bit_for_bit(monkeypatch, path, case):
    """``sparse_select`` scores and counts only up to the step its rows' live keys reach, and nothing at all where
    they are within ``topk``: the mask is ``topk_mask``'s over the whole view, entry for entry, with every key in
    the view twice (so most k-th values are shared and the tie rule decides) and weights of both signs."""
    S, C, Di, topk = SELECT_SHAPES[path]
    kernel = path == "kernel"
    if kernel:
        monkeypatch.setattr(A, "sparse_kernel_shaped", lambda *a: True)
        monkeypatch.setattr(A, "index_scores_t", functools.partial(A.index_scores_t, interpret=True))
    widths = A.select_widths(S, topk, 512 if kernel else 8)
    assert widths == ((1024, 1536, 2048) if kernel else (16, 24, 32, 40, 48, 56, 64))
    q, w, k = _index_inputs(B=2, C=C, S=S, Hi=4, Di=Di, seed=7)
    k = k.at[:, 1::2].set(k[:, 0::2])
    live = np.asarray(SELECT_LIVE[path][case])
    starts = np.maximum(live - C, 0)
    qpos = jnp.asarray(starts[:, None] + np.arange(C)[None, :], jnp.int32)
    real = np.arange(C)[None, :] < (live - starts)[:, None]
    ok = (jnp.arange(S)[None, None, :] <= qpos[:, :, None]) & jnp.asarray(real)[:, :, None]
    if path not in _SELECT_JIT:
        _SELECT_JIT[path] = (jax.jit(functools.partial(A.sparse_select, topk=topk)),
                             jax.jit(functools.partial(_whole_view_select, topk=topk, kernel=kernel)))
    stepped, whole = _SELECT_JIT[path]
    keep, scanned = stepped(q, w, k, qpos, ok, live=jnp.asarray(live, jnp.int32))
    want = whole(q, w, k, qpos, ok)
    np.testing.assert_array_equal(np.asarray(keep), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(keep).sum(-1), np.where(real, np.minimum(topk, np.asarray(qpos) + 1), 0))
    width = 0 if live.max() <= topk else min(x for x in widths if x >= live.max())
    assert int(scanned) == 2 * C * width
    if live.max() > topk + 1:  # some query's k-th value is shared with the key's twin just outside the selection
        sc = np.asarray(A.index_scores(q, w, k))
        kth = np.sort(np.where(np.asarray(ok), sc, -np.inf), -1)[..., -topk]
        assert ((np.where(np.asarray(ok), sc, -np.inf) == kth[..., None]).sum(-1) > 1)[np.isfinite(kth)].any() and (sc < 0).any()


@pytest.mark.parametrize("chunk", [24, 16])
def test_chunk_logits_and_pairs_selected_equal_the_whole_view_selections(family, monkeypatch, chunk):
    """``_prefill_against_cache`` over a prompt of three and four chunks: logits and the pairs kept are those of
    the same program with the selection run over the whole view, bit for bit; only ``pairs_scanned`` differs."""
    conf = _conf()
    cfg, params = _program(family, conf)
    s = _ids(53, 5)
    bt_row = jnp.asarray([9, 1, 4, 2, 7, 11, 3, 0], jnp.int32)

    def run():
        cache, out = mla_moe.init_paged_cache(cfg, 2, 16, 8), []
        for start in range(0, 53, chunk):
            valid = min(chunk, 53 - start)
            ids = (s[start:start + valid] + [0] * chunk)[:chunk]
            logits, cache = mla_moe.prefill_chunk_paged(
                params, cfg, jnp.asarray([ids]), cache, bt_row, jnp.int32(1), jnp.int32(start), jnp.int32(valid))
            out.append(np.asarray(logits))
        return out, np.asarray(cache.stats[1, -10:-5]), cache

    stepped, counts, cache = run()
    monkeypatch.setattr(mla_moe, "sparse_select", lambda *a: (_whole_view_select(*a), a[4].shape[1] * a[4].shape[2]))
    whole, counts_whole, cache_whole = run()
    for a, b in zip(stepped, whole):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(counts[:4], counts_whole[:4])
    assert counts_whole[4] == -(-53 // chunk) * chunk * 64 and 0 < counts[4] < counts_whole[4]
    for a, b in zip(jax.tree.leaves(cache._replace(stats=None)), jax.tree.leaves(cache_whole._replace(stats=None))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("axis", [1, 2])
def test_topk_mask_is_exact_under_ties_negative_scores_and_too_few_candidates(axis):
    r = np.random.default_rng(3)
    scores = r.standard_normal((3, 40, 40)).astype(np.float32)
    scores[0] = np.round(scores[0] * 2) / 2  # many exact ties, of both signs, and zeros of both signs
    scores[0, :4, :4] = -0.0
    ok = r.random((3, 40, 40)) < 0.8
    ok[1, 5] = False  # nothing to select
    ok[1, 6] = np.arange(40) < 3  # fewer than k
    if axis == 1:
        ok[1, :, 5], ok[1, :, 6] = False, (np.arange(40) < 3)
    k = 7
    got = np.asarray(A.topk_mask(jnp.asarray(scores), k, jnp.asarray(ok), axis=axis))
    s, o = (scores, ok) if axis == 2 else (scores.transpose(0, 2, 1), ok.transpose(0, 2, 1))
    g = got if axis == 2 else got.transpose(0, 2, 1)
    for b in range(3):
        for row in range(40):
            cand = np.flatnonzero(o[b, row])
            order = cand[np.lexsort((cand, -s[b, row, cand]))]  # by score descending, lowest position first
            want = np.zeros(40, bool)
            want[order[:k]] = True
            assert np.array_equal(g[b, row], want), (b, row)
    assert got[1].sum() > 0 and not (got & ~ok).any()


def test_the_decode_selection_picks_what_the_mask_picks():
    r = np.random.default_rng(4)
    scores = jnp.asarray(r.standard_normal((3, 64)), jnp.float32)
    ok = jnp.asarray(np.arange(64)[None, :] <= np.asarray([50, 5, 63])[:, None])
    idx, picked = A.sparse_decode_select(scores, ok, 8)
    mask = np.asarray(A.topk_mask(scores, 8, ok, axis=1))
    for b in range(3):
        assert set(np.asarray(idx[b])[np.asarray(picked[b])].tolist()) == set(np.flatnonzero(mask[b]).tolist())
    assert [int(x) for x in picked.sum(-1)] == [8, 6, 8]


# ---------------------------------------------------------------------------
# the two Pallas kernels, interpreted on the CPU, against the plain forms
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("starts", [[0, 0], [96, 32]])
def test_the_index_score_kernel_equals_the_plain_scores_below_the_diagonal(starts):
    q, w, k = _index_inputs(B=2, C=32, S=128, Hi=4, Di=128, seed=5)
    got = A.index_scores_t(q, w, k, jnp.asarray(starts, jnp.int32), block_q=16, block_k=32, interpret=True)
    want = A.index_scores(q, w, k).transpose(0, 2, 1)  # [B, S, C]
    qpos = np.asarray(starts)[:, None] + np.arange(32)[None, :]
    causal = np.arange(128)[None, :, None] <= qpos[:, None, :]
    np.testing.assert_allclose(np.asarray(got)[causal], np.asarray(want)[causal], atol=1e-4)
    assert got.shape == (2, 128, 32)
    dead = np.arange(128)[None, :, None] >= (np.asarray(starts)[:, None, None] + 32 + 32)  # whole tiles past every query
    assert not np.asarray(got)[np.broadcast_to(dead, got.shape)].any()


@pytest.mark.parametrize("live", [[128, 128], [70, 17]])
def test_the_masked_flash_kernel_equals_the_masked_softmax(live):
    r = np.random.default_rng(6)
    B, H, Sq, Sk, D, Dv = 2, 4, 32, 128, 128, 128
    q, k, v = (jnp.asarray(r.standard_normal(s), jnp.float32) for s in ((B, H, Sq, D), (B, H, Sk, D), (B, H, Sk, Dv)))
    keep = r.random((B, Sq, Sk)) < 0.3
    keep &= np.arange(Sk)[None, None, :] < np.asarray(live)[:, None, None]
    keep[0, 3] = False  # a query that keeps nothing reads zero
    keep[1, 4] = np.arange(Sk) == 16  # a query whose only key lies past the first tile
    kt = k.swapaxes(2, 3)  # the kernel takes the keys transposed
    got = A.masked_flash_attention(q, kt, v, jnp.asarray(keep, jnp.int8), jnp.asarray(live, jnp.int32), scale=0.1,
                                   block_q=16, block_kv=32, chunk_kv=64, interpret=True)
    want = A.sparse_attention(q, kt, v, jnp.asarray(keep), jnp.asarray(live, jnp.int32), scale=0.1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert not np.asarray(got[0, :, 3]).any() and float(np.abs(np.asarray(got[1, :, 4])).max()) > 0


# ---------------------------------------------------------------------------
# the sparse decode step's Pallas calls, interpreted on the CPU, against the three plain functions
# ---------------------------------------------------------------------------

SD_PAGE, SD_NB, SD_L, SD_HI, SD_DI, SD_H, SD_W, SD_WV, SD_K = 32, 4, 2, 8, 128, 8, 256, 128, 40


def _sparse_decode_case(case):
    """-> inputs of one layer of one step: 5 slots on 4 pages of 32, ``index_topk`` 40.  The index queries, keys and
    head weights are small integers and powers of two, so a score is the same float in whatever order the heads are
    summed, and many keys share one: the k-th value is shared in every row, the ties run over page boundaries, and
    (the weights have both signs) half the scores are negative."""
    r = np.random.default_rng(sum(case.encode()))
    B = 5
    P = B * SD_NB
    ints = lambda lo, hi, *s: jnp.asarray(r.integers(lo, hi + 1, s), jnp.float32).astype(jnp.bfloat16)  # noqa: E731
    draw = lambda *s: jnp.asarray(r.standard_normal(s) * 0.5, jnp.float32).astype(jnp.bfloat16)  # noqa: E731
    q_idx, k_new, ipool = ints(-2, 2, B, SD_HI, SD_DI), ints(-1, 1, B, SD_DI), ints(-1, 1, SD_L, P, SD_PAGE, SD_DI)
    w_idx = jnp.asarray(r.choice([-1.0, -0.5, 0.25, 0.5, 1.0], (B, SD_HI)), jnp.float32)
    q, row, pool = draw(B, SD_H, SD_W), draw(B, SD_W), draw(SD_L, P, SD_PAGE, SD_W)
    bt = r.permutation(P).reshape(B, SD_NB).astype(np.int32)
    positions = np.asarray([127, 64, 95, 70, 100])  # the whole view; one key on a page; a page's last row; partly live
    active = np.ones(B, bool)
    if case == "ties-over-page-boundaries":
        # two heads over two lanes, one weighted below zero: ~20 distinct scores, a good part of them negative
        ipool, w_idx = ipool.at[:, :, :, 2:].set(0), w_idx.at[:, 2:].set(0).at[:, 1].set(-0.5)
    elif case == "fewer-keys-than-topk":
        positions = np.asarray([5, 38, 39, 40, 31])  # 6, 39, 40 (exactly topk), 41 keys; one whole page
    elif case == "inactive-and-unallocated":
        active[1] = False
        bt[3, 2] = P  # its write block has no page: it selects among the pages below and writes nothing
        bt[4, 1] = P  # a hole in the middle: those 32 positions are never selected
    elif case == "stale-rows-past-pos":
        ipool = ipool.at[:, :, 20:].set(jnp.asarray(8.0, jnp.bfloat16))  # a reused page's rows: huge scores if ever read
        positions = np.asarray([83, 64, 51, 70, 115])  # all but one row end below row 20 of their last page
    elif case == "own-key-wins":
        k_new = (q_idx[:, 0] * 4).astype(jnp.bfloat16)  # aligned with head 0's query: far the largest score
        w_idx = w_idx.at[:, 0].set(1.0)
    elif case.startswith("active-"):
        active[:] = False
        active[: int(case.split("-")[1])] = True
    return dict(q_idx=q_idx, w_idx=w_idx, k_new=k_new, ipool=ipool, q=q, row=row, pool=pool, bt=jnp.asarray(bt),
                positions=jnp.asarray(positions, jnp.int32), active=jnp.asarray(active), P=P)


SPARSE_DECODE_CASES = ["ties-over-page-boundaries", "fewer-keys-than-topk", "inactive-and-unallocated",
                       "stale-rows-past-pos", "own-key-wins", "last-page-partly-live", "active-1", "active-2", "active-5"]


@pytest.mark.parametrize("case", SPARSE_DECODE_CASES)
def test_the_sparse_decode_kernels_select_the_same_keys_and_attend_them(case):
    """Index scores, selection and attention over the plan's pages against ``index_scores`` +
    ``sparse_decode_select`` + ``sparse_latent_decode_attention`` on the scattered pools: every byte of both
    pools, every score, THE SELECTED SET KEY FOR KEY, and the output of every live row."""
    c = _sparse_decode_case(case)
    P, bt, positions, active = c["P"], c["bt"], c["positions"], c["active"]
    S = SD_NB * SD_PAGE
    layer = jnp.int32(1)
    plan = A.paged_decode_plan(bt, positions, active, n_pages=P, page=SD_PAGE)

    @jax.jit
    def kernels(q_idx, w_idx, k_new, ipool, q, row, pool):
        scores, ipool = A.paged_index_scores(q_idx, w_idx, k_new, ipool, layer, bt, positions, plan, interpret=True)
        keep = A.topk_select_paged(scores, active, SD_K, interpret=True)
        o, pool = A.latent_decode_update_attend(q, row, pool, layer, bt, positions, plan, scale=0.11, value_width=SD_WV,
                                                keep=keep, interpret=True)
        return scores, keep, o, ipool, pool

    scores, keep, o, ipool, pool = kernels(*(c[k] for k in ("q_idx", "w_idx", "k_new", "ipool", "q", "row", "pool")))
    phys = jnp.take_along_axis(bt, (positions // SD_PAGE)[:, None], axis=1)[:, 0]
    phys_w, off = jnp.where(active, jnp.minimum(phys, P), P), positions % SD_PAGE
    want_ipool = c["ipool"].at[1, phys_w, off].set(c["k_new"], mode="drop")
    want_pool = c["pool"].at[1, phys_w, off].set(c["row"], mode="drop")
    assert np.array_equal(np.asarray(ipool, np.float32), np.asarray(want_ipool, np.float32))
    assert np.array_equal(np.asarray(pool, np.float32), np.asarray(want_pool, np.float32))
    keys = want_ipool[1, jnp.clip(bt, 0, P - 1)].reshape(5, S, SD_DI)
    want_scores = np.asarray(A.index_scores(c["q_idx"][:, None], c["w_idx"][:, None], keys)[:, 0])
    allocated = np.repeat((np.asarray(bt) >= 0) & (np.asarray(bt) < P), SD_PAGE, axis=1)
    ok = (np.arange(S)[None, :] <= np.asarray(positions)[:, None]) & allocated & np.asarray(active)[:, None]
    got_scores = np.asarray(scores).reshape(5, S)
    assert np.array_equal(got_scores[ok], want_scores[ok]) and np.isneginf(got_scores[~ok]).all()
    idx, picked = A.sparse_decode_select(jnp.asarray(want_scores), jnp.asarray(ok), SD_K)
    got = np.asarray(keep).reshape(5, S) != 0
    for b in range(5):
        want_set = set(np.asarray(idx[b])[np.asarray(picked[b])].tolist())
        assert set(np.flatnonzero(got[b]).tolist()) == want_set, (b, sorted(want_set ^ set(np.flatnonzero(got[b]).tolist())))
        assert len(want_set) == min(SD_K, int(ok[b].sum()))
    assert not (got & ~ok).any()
    if case == "own-key-wins":
        assert all(got[b, int(positions[b])] for b in range(5))
    if case == "ties-over-page-boundaries":  # the k-th value is shared, in and out of the set, on more than one page
        for b in range(5):
            kth = np.sort(want_scores[b][ok[b]])[-SD_K]
            tied = ok[b] & (want_scores[b] == kth)
            assert (tied & got[b]).any() and (tied & ~got[b]).any() and len(set(np.flatnonzero(tied) // SD_PAGE)) > 1
            assert (want_scores[b][ok[b]] < 0).sum() > 10
    want = A.sparse_latent_decode_attention(c["q"], want_pool, layer, bt, idx, picked, scale=0.11, value_width=SD_WV)
    live = np.asarray(active)
    np.testing.assert_allclose(np.asarray(o, np.float32)[live], np.asarray(want, np.float32)[live], rtol=2.0**-7, atol=2.0**-9)
    assert not np.asarray(o, np.float32)[~live].any()


@pytest.mark.parametrize("kind", ["normal", "all-negative", "signed-zeros"])
def test_the_selection_kernel_equals_top_k_on_any_floats(kind):
    """The counting works on the floats' bit patterns: a k-th value below zero, and -0.0 against +0.0."""
    r = np.random.default_rng(len(kind))
    s = r.standard_normal((3, 4, 32)).astype(np.float32)
    if kind == "all-negative":
        s = -np.abs(s) - 0.5
    if kind == "signed-zeros":
        s = np.where(r.random(s.shape) < 0.5, np.float32(-0.0), np.float32(0.0)) * (r.random(s.shape) < 0.8) + (r.random(s.shape) < 0.1)
        s = s.astype(np.float32)
    ok = np.arange(128)[None, :] <= np.asarray([127, 90, 20])[:, None]
    scores = jnp.asarray(np.where(ok.reshape(3, 4, 32), s, -np.inf))
    keep = np.asarray(A.topk_select_paged(scores, jnp.asarray([True, True, True]), 24, interpret=True)).reshape(3, 128) != 0
    want = np.asarray(A.topk_mask(jnp.asarray(s.reshape(3, 128)), 24, jnp.asarray(ok), axis=1))
    assert np.array_equal(keep, want)
    assert [int(x) for x in keep.sum(-1)] == [24, 24, 21]


def _kernel_step(monkeypatch):
    """Steer ``decode_step_paged`` onto its Pallas calls, interpreted (the CPU's answer is ``xla``)."""
    import functools

    monkeypatch.setattr(mla_moe, "latent_decode_kv_path", lambda *a, **k: "kernel")
    for name in ("paged_index_scores", "topk_select_paged", "latent_decode_update_attend"):
        monkeypatch.setattr(mla_moe, name, functools.partial(getattr(A, name), interpret=True))


@pytest.mark.parametrize("topk", [8, 4096], ids=["selects", "view-within-topk"])
def test_the_kernel_decode_step_equals_the_plain_step_and_the_reference_at_every_position(family, monkeypatch, topk):
    """``test_prefill_then_paged_decode_equals_the_reference_at_every_position`` with the step on its Pallas calls
    (index heads and key widths the kernels admit): logits, both pools and the counters against the plain path's at
    every step, and the logits against the reference's.  ``selects``: the three calls of the sparse path.
    ``view-within-topk``: an indexer whose ``index_topk`` covers the view, so the latent kernel attends everything,
    the index key is scattered for a longer view's sake and the counters count every causal pair."""
    conf = _conf(num_attention_heads=8, num_key_value_heads=8, index_n_heads=8, index_head_dim=128, kv_lora_rank=128,
                 index_topk=topk)
    selects = topk == 8
    cfg, params = _program(families.load(conf, DATA), conf)
    seqs = [_ids(52, 3), _ids(45, 4)]
    want = _reference(families.load(conf, DATA), conf, seqs)
    cache, bt = _paged(cfg)
    n0 = [20, 12]
    ids = np.zeros((2, 24), np.int32)
    for i, s in enumerate(seqs):
        ids[i, : n0[i]] = s[: n0[i]]
    logits, rows, stats = mla_moe.prefill(params, cfg, jnp.asarray(ids), jnp.asarray(n0))
    plain = mla_moe.insert_sequences_paged(cache, rows, stats, jnp.asarray(n0), jnp.asarray([0, 2]), bt[jnp.asarray([0, 2])])
    kern = jax.tree.map(jnp.copy, plain)
    plain_step = jax.jit(lambda t, c, a: mla_moe.decode_step_paged(params, cfg, t, c, bt, active=a))
    assert mla_moe.decode_kv_path(cfg, jnp.float32, 8) == "xla"
    plain_text = str(jax.make_jaxpr(lambda t, c, a: mla_moe.decode_step_paged(params, cfg, t, c, bt, active=a))(
        jnp.zeros((4,), jnp.int32), plain, jnp.ones((4,), bool)))
    _kernel_step(monkeypatch)
    kernel_fn = lambda t, c, a: mla_moe.decode_step_paged(params, cfg, t, c, bt, active=a)  # noqa: E731
    active = jnp.asarray([True, False, True, False])
    text = str(jax.make_jaxpr(kernel_fn)(jnp.zeros((4,), jnp.int32), kern, active))
    # the router's top_k stays; the selection's (k = index_topk = 8), one in each stack's scan body, is gone
    assert ("top_k[axis=1 k=8]" in plain_text) == selects and "k=8]" not in text and " sort[" not in text
    assert text.count("top_k[") == plain_text.count("top_k[") - 2 * selects
    for name in ("paged_index_scores", "topk_select", "sparse_latent_decode"):
        assert (name in text) == selects, name
    assert selects or text.count("name=latent_decode") == 2  # the latent kernel as a block without an indexer calls it
    kernel_step = jax.jit(kernel_fn)
    for k in range(25):
        toks = jnp.asarray([seqs[0][n0[0] + k], 0, seqs[1][n0[1] + k], 0], jnp.int32)
        lp, plain = plain_step(toks, plain, active)
        lk, kern = kernel_step(toks, kern, active)
        np.testing.assert_allclose(np.asarray(lk)[[0, 2]], np.asarray(lp)[[0, 2]], atol=ATOL)
        np.testing.assert_allclose(np.asarray(lk[0]), want[0][n0[0] + k], atol=ATOL)
        np.testing.assert_allclose(np.asarray(lk[2]), want[1][n0[1] + k], atol=ATOL)
        for a, b in zip(jax.tree.leaves(kern), jax.tree.leaves(plain)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    assert [int(x) for x in kern.lengths] == [45, 0, 37, 0]
    steps, queries, causal, kept, scanned = (int(x) for x in kern.stats[0, -10:-5])
    assert causal == sum(n + k + 1 for n in n0 for k in range(25))
    assert (steps, queries, kept, scanned) == (25, 50, 50 * 8 if selects else causal, 50 * 64 if selects else 0)
