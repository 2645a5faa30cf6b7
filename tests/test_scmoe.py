"""The shortcut-connected double layer (``models/mla_moe.py`` under a
``longcat_flash`` config: two latent-attention sublayers, two dense FFNs, one
expert layer that joins the residual at the layer's end, a softmax router with
identity experts) against its plain reference (``benchmarks/reference/scmoe.py``,
which imports nothing of the program), at a tiny preset on the CPU in float32:
``tests/data/scmoe_tiny.json``: hidden 64, 4 heads, ranks 32/16 (scales
``sqrt(2)`` and ``2``), head widths 16+8/16, two double layers, 16 routed + 8
identity experts, top-4 times 6.

**Tolerances**, as ``tests/test_mla_moe.py`` has them: weights are the family's,
bfloat16-valued, computed in float32 on both sides; the two sides sum the same
float32 products in different orders (~1e-6 a matmul, ~1e-5 after four
sublayers): ``ATOL = 2e-4`` on O(1) logits is 50 times under what one bfloat16
rounding of an activation would move.  A pick is a discrete choice: where the
program and the reference picked differently the logits would differ by far more
(the ``no_zero`` control moves them by ~0.5), so equal logits at every position
also say the picks were the same.  Routing weights are compared at 1e-6.
"""

import dataclasses
import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import families
from benchmarks.reference import scmoe as ref
from django_assistant_bot_tpu.models import DecoderConfig, mixtral, mla_moe, module_for
from django_assistant_bot_tpu.ops import attention as A
from django_assistant_bot_tpu.ops import moe as moe_ops

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DATA = os.path.join(ROOT, "benchmarks")
ATOL = 2e-4
SEED = 2**31 + 77


def _conf(**hf):
    with open(os.path.join(HERE, "data", "scmoe_tiny.json")) as f:
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


# ---------------------------------------------------------------------------
# the configuration and the tree
# ---------------------------------------------------------------------------


def test_from_hf_reads_longcat_flash_under_its_own_key_names():
    hf = _conf()["hf"]
    cfg = DecoderConfig.from_hf(hf)
    lm = cfg.latent_moe
    assert cfg.arch == "mla_moe" and module_for(cfg) is mla_moe and mla_moe.kv_kind(cfg) == "latent"
    assert (cfg.num_layers, cfg.intermediate_size, cfg.experts_per_token, cfg.rope_theta) == (2, 128, 4, 1e7)
    assert cfg.rope_scaling is None and cfg.rms_norm_eps == 1e-5 and not cfg.tie_embeddings
    assert (lm.double_layer, lm.scoring_func, lm.zero_experts, lm.router_bias, lm.norm_topk_prob) == (True, "softmax", 8, True, False)
    assert (lm.router_experts, lm.router_width, lm.experts_held, lm.first_dense_layers, lm.n_shared_experts) == (16, 24, 16, 0, 0)
    assert (lm.moe_intermediate_size, lm.routed_scaling_factor) == (32, 6.0)
    assert lm.q_scale == pytest.approx(2 ** 0.5) and lm.kv_scale == 2.0 and mla_moe.softmax_scale(cfg) == pytest.approx(24 ** -0.5)
    # a deployment's file: n_routed_experts counts the experts held, the router keeps its width
    lm = DecoderConfig.from_hf({**hf, "n_routed_experts": 4, "ep_size": 4, "ep_rank": 3}).latent_moe
    assert (lm.router_experts, lm.router_width, lm.experts_held, lm.first_expert) == (16, 24, 4, 12)
    # without the two flags the scales are 1: the other configurations' attention
    lm = DecoderConfig.from_hf({**hf, "mla_scale_q_lora": False, "mla_scale_kv_lora": False}).latent_moe
    assert (lm.q_scale, lm.kv_scale) == (1.0, 1.0)


@pytest.mark.parametrize("change, why", [
    ({"zero_expert_type": "copy"}, "only identity experts"),
    ({"attention_bias": True}, "no biases"),
    ({"q_lora_rank": None}, "full-rank queries"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "plain rotary"),
    ({"ep_size": 4}, "which share"),
    ({"hidden_act": "gelu"}, "hidden_act"),
])
def test_from_hf_refuses_what_the_block_cannot_honour(change, why):
    with pytest.raises(ValueError, match=why):
        DecoderConfig.from_hf({**_conf()["hf"], **change})


def test_the_other_block_still_refuses_what_only_the_double_layer_admits():
    with open(os.path.join(HERE, "data", "mla_moe_tiny.json")) as f:
        hf = json.load(f)["hf"]
    with pytest.raises(ValueError, match="leading dense layer"):
        DecoderConfig.from_hf({**hf, "first_k_dense_replace": 0})
    with pytest.raises(ValueError, match="only sigmoid"):
        DecoderConfig.from_hf({**hf, "scoring_func": "softmax"})
    lm = DecoderConfig.from_hf(hf).latent_moe
    assert (lm.double_layer, lm.scoring_func, lm.zero_experts, lm.q_scale, lm.kv_scale) == (False, "sigmoid", 0, 1.0, 1.0)
    with pytest.raises(ValueError, match="double layer"):
        dataclasses.replace(lm, zero_experts=8)
    with pytest.raises(ValueError, match="no indexer"):
        dataclasses.replace(DecoderConfig.from_hf(_conf()["hf"]).latent_moe, index_topk=8, index_n_heads=4, index_head_dim=16)


def test_the_tree_holds_the_sublayers_in_order_and_one_expert_stack(family):
    conf = _conf()
    cfg = dataclasses.replace(DecoderConfig.from_hf(conf["hf"]), dtype=jnp.float32)
    ckpt = jax.eval_shape(lambda: mla_moe.init(cfg, jax.random.key(0)))  # a checkpoint's tree
    served = jax.eval_shape(lambda: family.served_params(conf, SEED))  # the benchmark's seeded one: the same form
    assert jax.tree.map(lambda x: x.shape, ckpt) == jax.tree.map(lambda x: x.shape, served)
    assert ckpt["dense_layers"]["w_uq"].shape == (4, 32, 4 * 24) and ckpt["dense_layers"]["w_gate"].shape == (4, 64, 128)
    assert set(ckpt["moe_layers"]) == {"router", "router_bias", "w_gate", "w_up", "w_down"}
    assert ckpt["moe_layers"]["router"].shape == (2, 64, 24) and ckpt["moe_layers"]["w_down"].shape == (2, 16, 32, 64)
    held = jax.eval_shape(lambda: mla_moe.held_params(cfg, mla_moe.init(cfg, jax.random.key(0))))  # what the device holds
    assert held["dense_layers"]["w_uq_nope"].shape == (4, 4, 16, 32) and held["dense_layers"]["w_dkv"].shape == (4, 64, 128)
    assert jax.tree.map(lambda x: x.shape, held["moe_layers"]) == jax.tree.map(lambda x: x.shape, ckpt["moe_layers"])
    axes, is_axes = mla_moe.logical_axes(cfg), lambda x: isinstance(x, tuple)
    assert jax.tree.structure(axes, is_leaf=is_axes) == jax.tree.structure(held)
    assert all(len(a) == x.ndim for a, x in zip(jax.tree.leaves(axes, is_leaf=is_axes), jax.tree.leaves(held)))


def test_the_familys_up_projections_are_drawn_at_the_variance_the_two_scales_correct(family):
    """``w_uq``, ``w_uk``, ``w_uv`` with ``hidden^-0.5`` (the family's docstring has why), every other matrix with
    ``fan_in^-0.5``: scaled queries and latent keys then have unit variance, and the softmax is no near-argmax."""
    sub = jax.tree.map(lambda x: np.asarray(x, np.float32), family.served_params(_conf(), SEED)["dense_layers"])
    for name, fan_in in (("w_uq", 64), ("w_uk", 64), ("w_uv", 64), ("w_dq", 64), ("w_down", 128), ("wo", 64)):
        assert sub[name].std() * fan_in ** 0.5 == pytest.approx(1.0, abs=0.04), name
    x = np.random.default_rng(0).standard_normal((256, 32)).astype(np.float32)  # a normed query latent
    assert (2 ** 0.5 * x @ sub["w_uq"][0]).std() == pytest.approx(1.0, abs=0.08)
    c = np.random.default_rng(1).standard_normal((256, 16)).astype(np.float32)  # a normed latent, scaled by 2
    assert (2.0 * c @ sub["w_uk"][0]).std() == pytest.approx(1.0, abs=0.08)


def test_the_cache_holds_two_rows_a_layer_and_the_identity_experts_counters():
    cfg = DecoderConfig.from_hf(_conf()["hf"])
    assert mla_moe.cache_layers(cfg) == 4 and mla_moe.kv_bytes_per_token(cfg) == 4 * 128 * 2
    cache = mla_moe.init_paged_cache(cfg, 4, 32, 8)
    assert cache.kv.shape == (4, 32, 8, 128) and cache.idx is None
    assert cache.stats.shape == (2, 4 + 16 + 1 + 5)  # picks_zero and tokens by 0..4 real picks ride the row
    with open(os.path.join(HERE, "data", "mla_moe_tiny.json")) as f:
        other = DecoderConfig.from_hf(json.load(f)["hf"])
    assert mla_moe.init_paged_cache(other, 4, 32, 8).stats.shape == (2, 4 + 16)  # the other block's row is as it was
    assert mla_moe.cache_layers(other) == 3 and mixtral.zero_stat_width(other) == 0


# ---------------------------------------------------------------------------
# the programs against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("lengths", [[18, 7], [40, 29], [48, 33]])
def test_prefill_logits_equal_the_plain_reference(family, lengths):
    conf = _conf()
    cfg, params = _program(family, conf)
    seqs = [_ids(48, 1), _ids(48, 2)]
    logits, rows, stats = mla_moe.prefill(params, cfg, jnp.asarray(seqs), jnp.asarray(lengths))
    full = _reference(family, conf, [s[:n] + [0] for s, n in zip(seqs, lengths)])
    for i in range(2):
        np.testing.assert_allclose(np.asarray(logits[i]), full[i][-1], atol=ATOL)
        assert float(np.abs(full[i][-1]).max()) > 1.0  # logits are O(1): the tolerance means something
    assert rows.shape == (4, 2, 48, 128) and stats.shape == (4 + 16 + 6,)
    # the counters, summed over the two expert layers, against the reference's own count of the same tokens
    tokens = sum(lengths)
    assert int(stats[0]) == 2 * 4 * tokens and int(stats[1]) + int(stats[20]) == int(stats[0])  # every pick is held or zero at ep 1
    assert [int(x) for x in stats[21:]] and int(stats[21:].sum()) == 2 * tokens  # each token once a layer in the histogram
    assert sum(n * int(t) for n, t in enumerate(stats[21:])) == int(stats[1])  # real picks
    counts = []  # the reference's own count over exactly these tokens
    family.reference_logits(conf, SEED, [s[:n] for s, n in zip(seqs, lengths)], [n - 2 for n in lengths], [0], counts=counts)
    assert (int(stats[20]), int(stats[1]), 2 * tokens) == (int(counts[3]), int(counts[4]), int(counts[2]))
    assert 0.2 < int(stats[20]) / int(stats[0]) < 0.45  # 8 of 24 outputs are identity experts


def _paged(cfg, page=8, NB=8, P=32, slots=4):
    cache = mla_moe.init_paged_cache(cfg, slots, P, page)
    bt = np.full((slots, NB), P, np.int32)
    bt[0, :7], bt[2, :7] = [3, 5, 7, 9, 11, 13, 15], [2, 4, 6, 8, 10, 12, 14]
    return cache, jnp.asarray(bt)


def _prefilled(cfg, params, seqs, n0, cache, bt):
    ids = np.zeros((2, 24), np.int32)
    for i, s in enumerate(seqs):
        ids[i, : n0[i]] = s[: n0[i]]
    logits, rows, stats = mla_moe.prefill(params, cfg, jnp.asarray(ids), jnp.asarray(n0))
    return mla_moe.insert_sequences_paged(cache, rows, stats, jnp.asarray(n0), jnp.asarray([0, 2]), bt[jnp.asarray([0, 2])])


def test_prefill_then_paged_decode_equals_the_reference_at_every_position(family):
    """Through the cache: a step writes one latent row per attention SUBLAYER (rows 2l and 2l + 1 of the pool) and
    attends over the latent itself with the up-projections absorbed, where the reference expands every position."""
    conf = _conf()
    cfg, params = _program(family, conf)
    seqs = [_ids(52, 3), _ids(45, 4)]
    want = _reference(family, conf, seqs)
    cache, bt = _paged(cfg)
    n0 = [20, 12]
    cache = _prefilled(cfg, params, seqs, n0, cache, bt)
    # two rows a layer in the pool: every sublayer wrote its own, and no two are the same row
    first = np.asarray(cache.kv[:, 3, 0, :24])  # slot 0's first token, the four sublayers
    assert all(np.abs(first[i]).max() > 0.1 for i in range(4))
    assert all(np.abs(first[i] - first[j]).max() > 0.1 for i in range(4) for j in range(i))
    step = jax.jit(lambda t, c, a: mla_moe.decode_step_paged(params, cfg, t, c, bt, active=a))
    active = jnp.asarray([True, False, True, False])
    for k in range(25):
        toks = jnp.asarray([seqs[0][n0[0] + k], 0, seqs[1][n0[1] + k], 0], jnp.int32)
        logits, cache = step(toks, cache, active)
        np.testing.assert_allclose(np.asarray(logits[0]), want[0][n0[0] + k], atol=ATOL)
        np.testing.assert_allclose(np.asarray(logits[2]), want[1][n0[1] + k], atol=ATOL)
    assert [int(x) for x in cache.lengths] == [45, 0, 37, 0]  # frozen slots wrote nothing
    assert not np.asarray(cache.kv[:, 0]).any()  # ... and no page they do not own
    dec = np.asarray(cache.stats[0])
    assert int(dec[0]) == 25 * 2 * 2 * 4 and int(dec[2]) == 25 * 2  # 25 steps x 2 rows x 2 expert layers x top-4
    assert int(dec[21:].sum()) == 25 * 2 * 2 and int(dec[20]) + int(dec[1]) == int(dec[0])


@pytest.mark.parametrize("chunk", [24, 16])
def test_chunked_prefill_against_the_cache_equals_the_reference(family, chunk):
    conf = _conf()
    cfg, params = _program(family, conf)
    s = _ids(53, 5)
    want = _reference(family, conf, [s + [0]])[0][-1]
    page, P = 8, 16
    bt_row = jnp.asarray([9, 1, 4, 2, 7, 11, 3, 0], jnp.int32)
    cache = mla_moe.init_paged_cache(cfg, 2, P, page)
    for start in range(0, 53, chunk):
        valid = min(chunk, 53 - start)
        ids = (s[start:start + valid] + [0] * chunk)[:chunk]
        logits, cache = mla_moe.prefill_chunk_paged(
            params, cfg, jnp.asarray([ids]), cache, bt_row, jnp.int32(1), jnp.int32(start), jnp.int32(valid))
    np.testing.assert_allclose(np.asarray(logits[0]), want, atol=ATOL)
    assert int(cache.lengths[1]) == 53
    assert int(cache.stats[1, 0]) == 53 * 2 * 4 and not np.asarray(cache.stats[0]).any()  # prefill's row, pad tokens not counted
    # the sliding last chunk of the engine re-feeds positions already written: the same rows, the same answer
    again, cache = mla_moe.prefill_chunk_paged(
        params, cfg, jnp.asarray([s[53 - chunk:]]), cache, bt_row, jnp.int32(1), jnp.int32(53 - chunk), jnp.int32(chunk))
    np.testing.assert_allclose(np.asarray(again[0]), want, atol=ATOL)


def test_suffix_prefill_copy_pages_and_the_stale_rows_of_a_reused_page(family):
    conf = _conf()
    cfg, params = _program(family, conf)
    s = _ids(53, 5)
    want = _reference(family, conf, [s + [0]])[0][-1]
    page, P = 8, 16
    bt_row = jnp.asarray([9, 1, 4, 2, 7, 11, 3, 0], jnp.int32)
    cache = mla_moe.init_paged_cache(cfg, 2, P, page)
    # every page starts full of another request's rows: what a reused page holds past the slot's position
    cache = cache._replace(kv=jax.random.normal(jax.random.key(9), cache.kv.shape, jnp.float32))
    _, cache = mla_moe.prefill_chunk_paged(
        params, cfg, jnp.asarray([s[:24]]), cache, bt_row, jnp.int32(0), jnp.int32(0), jnp.int32(24))
    # clone the three prefix pages elsewhere, all four sublayers' rows of each, and continue from the clones
    cache = mla_moe.copy_pages(cache, jnp.asarray([9, 1, 4], jnp.int32), jnp.asarray([13, 14, 15], jnp.int32))
    np.testing.assert_array_equal(np.asarray(cache.kv[:, 13]), np.asarray(cache.kv[:, 9]))
    bt2 = jnp.asarray([[13, 14, 15, 2, 7, 11, 3, 0]], jnp.int32)
    ids = np.zeros((1, 32), np.int32)
    ids[0, :29] = s[24:]
    logits, cache = mla_moe.prefill_suffix_paged(
        params, cfg, jnp.asarray(ids), cache, bt2, jnp.asarray([1]), jnp.asarray([24]), jnp.asarray([29]))
    np.testing.assert_allclose(np.asarray(logits[0]), want, atol=ATOL)
    assert int(cache.lengths[1]) == 53
    # a decode step on the reused pages: the stale rows past position 53 are never attended
    full = _reference(family, conf, [s + [7, 0]])[0][-1]
    logits, cache = mla_moe.decode_step_paged(params, cfg, jnp.asarray([0, 7], jnp.int32), cache, jnp.concatenate([bt2, bt2]),
                                              active=jnp.asarray([False, True]))
    np.testing.assert_allclose(np.asarray(logits[1]), full, atol=ATOL)


# ---------------------------------------------------------------------------
# the router and the identity experts
# ---------------------------------------------------------------------------


def _moe_inputs(family, conf, tokens=40, seed=11):
    cfg, params = _program(family, conf)
    h = jnp.asarray(np.random.default_rng(seed).standard_normal((1, tokens, 64)), jnp.float32)
    p = {k: v[0] for k, v in params["moe_layers"].items()}  # the first expert layer alone
    return cfg, p, h


def test_the_bias_changes_picks_and_never_a_weight(family):
    cfg, p, h = _moe_inputs(family, _conf(), tokens=400)
    lm, xt = cfg.latent_moe, h[0]
    idx_b, w_b = mixtral.route_softmax(lm, 4, xt, p["router"], p["router_bias"])
    idx_0, w_0 = mixtral.route_softmax(lm, 4, xt, p["router"], None)
    changed = np.mean([set(a) != set(b) for a, b in zip(np.asarray(idx_b).tolist(), np.asarray(idx_0).tolist())])
    assert 0.02 < changed < 0.5, changed  # a bias a tenth of the scores' spread: some tokens, not most
    scores = jax.nn.softmax(jnp.einsum("te,ex->tx", xt, p["router"], precision="highest"), axis=-1)
    np.testing.assert_allclose(np.asarray(w_b), 6.0 * np.take_along_axis(np.asarray(scores), np.asarray(idx_b), -1), rtol=1e-6)
    # the reference routes the same: ids and weights
    idx_r, w_r, _ = ref.route(_conf()["hf"], xt, p["router"], p["router_bias"])
    assert np.array_equal(np.sort(np.asarray(idx_b), -1), np.sort(np.asarray(idx_r), -1))
    np.testing.assert_allclose(np.sort(np.asarray(w_b), -1), np.sort(np.asarray(w_r), -1), rtol=1e-6)


def test_the_weights_are_not_normalised_over_the_picks(family):
    cfg, p, h = _moe_inputs(family, _conf())
    _, w = mixtral.route_softmax(cfg.latent_moe, 4, h[0], p["router"], p["router_bias"])
    total = np.asarray(w.sum(-1))
    assert total.max() < 6.0 * 0.8 and total.std() > 0.05  # 6 x the picks' share of the softmax mass, token by token
    normed = dataclasses.replace(cfg.latent_moe, norm_topk_prob=True)
    _, w = mixtral.route_softmax(normed, 4, h[0], p["router"], p["router_bias"])
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 6.0, rtol=1e-6)


@pytest.mark.parametrize("zero_picks", [4, 0], ids=["no-real-pick", "every-pick-real"])
def test_a_token_with_no_real_pick_and_one_with_every_pick_real(family, zero_picks):
    """The bias pushed far to one side: every pick an identity expert (the layer is ``w x h`` and no held expert is
    read), or every pick a real one (no identity part)."""
    cfg, p, h = _moe_inputs(family, _conf())
    bias = jnp.where(jnp.arange(24) >= 16, 1.0, 0.0) * (1.0 if zero_picks else -1.0)
    p = dict(p, router_bias=bias)
    valid = jnp.ones((1, 40), bool)
    y, stats = mixtral.held_experts_mlp(cfg, p, h, valid)
    stats = np.asarray(stats)
    assert int(stats[0]) == 160 and int(stats[20]) == 40 * zero_picks and int(stats[1]) == 160 - 40 * zero_picks
    hist = stats[21:]
    assert int(hist[4 - zero_picks]) == 40 and int(hist.sum()) == 40
    idx, w = mixtral.route_softmax(cfg.latent_moe, 4, h[0], p["router"], bias)
    if zero_picks:
        assert not stats[3] and not stats[4:20].any()  # no held expert hit, none in the work list
        np.testing.assert_allclose(np.asarray(y[0]), np.asarray(w.sum(-1)[:, None] * h[0]), atol=1e-6)
    else:
        m, _ = ref.moe(_conf()["hf"], p, h, 16, zero=False)  # the reference without an identity part: the same layer
        np.testing.assert_allclose(np.asarray(y), np.asarray(m), atol=1e-5)
    want, _ = ref.moe(_conf()["hf"], p, h, 16)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5)


def test_the_ranks_held_parts_and_the_identity_part_once_add_up_to_the_uncut_layer(family):
    """The share tied to the model: four ranks of 4 experts each compute their own experts' part and, each of them,
    the identity part in full; the four results with the identity part counted ONCE are the uncut reference's layer."""
    full_conf = _conf()
    cfg, p, h = _moe_inputs(family, full_conf)
    hf = full_conf["hf"]
    whole, _ = ref.moe(hf, p, h, 16)  # every expert held: the uncut model's expert layer
    identity = whole - ref.moe(hf, p, h, 16, zero=False)[0]
    assert float(np.abs(identity).max()) > 0.05 and float(np.abs(whole - identity).max()) > 0.05
    valid = jnp.ones(h.shape[:2], bool)
    total = jnp.zeros_like(h)
    for rank in range(4):
        rcfg = dataclasses.replace(DecoderConfig.from_hf({**hf, "n_routed_experts": 4, "ep_size": 4, "ep_rank": rank},
                                                         dtype=jnp.float32), max_seq_len=256)
        share = dict(p, **{k: p[k][4 * rank:4 * rank + 4] for k in mixtral.HELD_KEYS})
        y, stats = mixtral.held_experts_mlp(rcfg, share, h, valid)
        assert int(stats[1]) == int(np.asarray(stats[4:8]).sum()) and stats.shape == (4 + 4 + 6,)
        # the reference given the same share computes the same part
        part, _ = ref.moe(hf, share, h, 16, first_expert=4 * rank)
        np.testing.assert_allclose(np.asarray(y), np.asarray(part), atol=1e-5)
        total = total + y
    np.testing.assert_allclose(np.asarray(total - 3 * identity), np.asarray(whole), atol=2e-5)


@pytest.mark.parametrize("scale", ["q_scale", "kv_scale"])
def test_each_attention_scale_bites(family, scale):
    conf = _conf()
    cfg, params = _program(family, conf)
    seqs = [_ids(32, 6)]
    logits, _, _ = mla_moe.prefill(params, cfg, jnp.asarray(seqs), jnp.asarray([32]))
    off = dataclasses.replace(cfg, latent_moe=dataclasses.replace(cfg.latent_moe, **{scale: 1.0}))
    other, _, _ = mla_moe.prefill(params, off, jnp.asarray(seqs), jnp.asarray([32]))
    assert float(np.abs(np.asarray(logits) - np.asarray(other)).max()) > 0.1
    # with both at 1 the program is the reference's `no_scale` control: what a program that dropped them computes
    both = dataclasses.replace(cfg, latent_moe=dataclasses.replace(cfg.latent_moe, q_scale=1.0, kv_scale=1.0))
    got, _, _ = mla_moe.prefill(params, both, jnp.asarray(seqs), jnp.asarray([32]))
    want = _reference(family, conf, [seqs[0] + [0]], control="no_scale")[0][-1]
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=ATOL)


@pytest.mark.parametrize("control, least", [("w_fp8", 0.1), ("no_zero", 0.2), ("no_scale", 0.5)])
def test_every_control_is_another_function(family, control, least):
    conf = _conf()
    seqs = [_ids(48, 1)]
    sound, other = _reference(family, conf, seqs), _reference(family, conf, seqs, control=control)
    assert np.abs(sound[0] - other[0]).max() > least


def test_a_rank_of_four_serves_its_share_through_all_three_programs(family):
    """ep_rank 1 of 4 (experts 4-7 held): prefill, then decode, against the reference given the same share."""
    conf = _conf(n_routed_experts=4, ep_size=4, ep_rank=1)
    fam = families.load(conf, DATA)
    cfg, params = _program(fam, conf)
    assert params["moe_layers"]["w_gate"].shape == (2, 4, 64, 32) and params["moe_layers"]["router"].shape == (2, 64, 24)
    seqs = [_ids(40, 8), _ids(33, 9)]
    want = _reference(fam, conf, seqs)
    cache, bt = _paged(cfg)
    n0 = [20, 12]
    cache = _prefilled(cfg, params, seqs, n0, cache, bt)
    step = jax.jit(lambda t, c, a: mla_moe.decode_step_paged(params, cfg, t, c, bt, active=a))
    active = jnp.asarray([True, False, True, False])
    for k in range(12):
        toks = jnp.asarray([seqs[0][n0[0] + k], 0, seqs[1][n0[1] + k], 0], jnp.int32)
        logits, cache = step(toks, cache, active)
        np.testing.assert_allclose(np.asarray(logits[0]), want[0][n0[0] + k], atol=ATOL)
        np.testing.assert_allclose(np.asarray(logits[2]), want[1][n0[1] + k], atol=ATOL)
    dec = np.asarray(cache.stats[0])
    assert dec.shape == (4 + 4 + 6,) and 0 < int(dec[1]) < int(dec[0]) - int(dec[8])  # some picks land on absent ranks


# ---------------------------------------------------------------------------
# the Pallas paths, interpreted
# ---------------------------------------------------------------------------


def test_the_kernel_paths_interpreted_equal_the_plain_step_and_the_reference(family, monkeypatch):
    """The decode step on its two Pallas calls (the latent kernel a sublayer, ``grouped_swiglu`` an expert layer), at
    widths the kernels admit, interpreted on the CPU: logits, the pool and the counters against the plain path's at
    every step and the logits against the reference's; then the one-shot prefill on the grouped call."""
    conf = _conf(hidden_size=128, ffn_hidden_size=256, expert_ffn_hidden_size=128, num_attention_heads=8, kv_lora_rank=128)
    fam = families.load(conf, DATA)
    cfg, params = _program(fam, conf)
    seqs = [_ids(40, 3), _ids(33, 4)]
    want = _reference(fam, conf, seqs)
    cache, bt = _paged(cfg)
    n0 = [20, 12]
    plain = _prefilled(cfg, params, seqs, n0, cache, bt)
    kern = jax.tree.map(jnp.copy, plain)
    plain_step = jax.jit(lambda t, c, a: mla_moe.decode_step_paged(params, cfg, t, c, bt, active=a))
    assert mla_moe.decode_kv_path(cfg, jnp.float32, 8) == "xla" and mla_moe.moe_experts_path(cfg) == "xla"
    monkeypatch.setattr(mla_moe, "latent_decode_kv_path", lambda *a, **k: "kernel")
    monkeypatch.setattr(mla_moe, "latent_decode_update_attend", functools.partial(A.latent_decode_update_attend, interpret=True))
    monkeypatch.setattr(moe_ops, "held_experts_path", lambda hidden, width: "kernel")
    monkeypatch.setattr(moe_ops, "grouped_swiglu", functools.partial(moe_ops.grouped_swiglu, interpret=True))
    kernel_fn = lambda t, c, a: mla_moe.decode_step_paged(params, cfg, t, c, bt, active=a)  # noqa: E731
    active = jnp.asarray([True, False, True, False])
    text = str(jax.make_jaxpr(kernel_fn)(jnp.zeros((4,), jnp.int32), kern, active))
    assert text.count("name=latent_decode") == 2 and text.count("name=held_experts") == 1  # a body: two sublayers, one expert layer
    kernel_step = jax.jit(kernel_fn)
    for k in range(12):
        toks = jnp.asarray([seqs[0][n0[0] + k], 0, seqs[1][n0[1] + k], 0], jnp.int32)
        lp, plain = plain_step(toks, plain, active)
        lk, kern = kernel_step(toks, kern, active)
        np.testing.assert_allclose(np.asarray(lk)[[0, 2]], np.asarray(lp)[[0, 2]], atol=ATOL)
        np.testing.assert_allclose(np.asarray(lk[0]), want[0][n0[0] + k], atol=ATOL)
        np.testing.assert_allclose(np.asarray(lk[2]), want[1][n0[1] + k], atol=ATOL)
        for a, b in zip(jax.tree.leaves(kern), jax.tree.leaves(plain)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    assert np.array_equal(np.asarray(kern.stats), np.asarray(plain.stats))
    # prefill of 80 tokens (over DENSE_MAX_TOKENS: the sorted tiles) on the grouped call
    s = _ids(80, 12)
    got, _, stats = jax.jit(lambda i, n: mla_moe.prefill(params, cfg, i, n))(jnp.asarray([s]), jnp.asarray([80]))
    np.testing.assert_allclose(np.asarray(got[0]), _reference(fam, conf, [s + [0]])[0][-1], atol=ATOL)
    assert int(stats[21:].sum()) == 2 * 80


# ---------------------------------------------------------------------------
# the other configurations' programs are what they were
# ---------------------------------------------------------------------------

OP = re.compile(r"^\s*(?:ROOT )?[%\w.\-]+ = [^=]*? ([a-z][\w\-]*)\(", re.M)
PROGRAMS = ("decode_step_paged", "prefill", "prefill_chunk_paged")


def _lowered(hf, program):
    cfg = dataclasses.replace(DecoderConfig.from_hf(hf, dtype=jnp.float32), max_seq_len=256)
    params = jax.eval_shape(lambda: mla_moe.held_params(cfg, mla_moe.init(cfg, jax.random.key(0))))
    cache = jax.eval_shape(lambda: mla_moe.init_paged_cache(cfg, 4, 32, 8))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    if program == "decode_step_paged":
        return jax.jit(lambda p, t, c, b: mla_moe.decode_step_paged(p, cfg, t, c, b)).lower(params, i32(4), cache, i32(4, 8))
    if program == "prefill":
        return jax.jit(lambda p, i, n: mla_moe.prefill(p, cfg, i, n)).lower(params, i32(2, 128), i32(2))
    return jax.jit(lambda p, i, c, bt, sl, st, v: mla_moe.prefill_chunk_paged(p, cfg, i, c, bt, sl, st, v)).lower(
        params, i32(1, 24), cache, i32(8), i32(), i32(), i32())


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("config", ["mla_moe_tiny", "dsa_moe_tiny", "scmoe_tiny"])
def test_the_blocks_compile_to_the_operations_they_did_before_a_change_to_another(config, program):
    """A change to one block form leaves the others' compiled programs alone: the operation counts of the optimised
    HLO (CPU, the tests' tiny sizes) of the decode step, the one-shot prefill and the chunk prefill, against
    ``tests/data/mla_moe_hlo_ops.json`` (made by this file's ``_lowered`` and ``OP``).  ``mla_moe_tiny``: the counts
    of the commit before the double layer was added; ``scmoe_tiny``: of the commit before the selection followed the
    live keys (PR 45), which changed ``dsa_moe_tiny``'s three programs on purpose (its counts are that PR's).  A PR
    that changes a block's programs on purpose makes that block's counts anew with ``PYTHONPATH=. JAX_PLATFORMS=cpu
    python tests/test_scmoe.py <config>``."""
    with open(os.path.join(HERE, "data", config + ".json")) as f:
        hf = json.load(f)["hf"]
    with open(os.path.join(HERE, "data", "mla_moe_hlo_ops.json")) as f:
        before = json.load(f)[config][program]
    assert _op_counts(_lowered(hf, program).compile().as_text()) == before


def _op_counts(text):
    counts = {}
    for op in OP.findall(text):
        counts[op] = counts.get(op, 0) + 1
    return dict(sorted(counts.items()))


def test_the_sparse_chunk_program_selects_in_one_conditional_over_the_steps_of_its_view():
    """The chunk program of the block with an indexer (24 queries against a view of 64, ``index_topk`` 8): each of
    its two scan bodies holds ONE conditional of ``1 + len(select_widths)`` branches (the others are ``topk_mask``'s
    two-way tie rule, one a counting branch); a counting branch's sortable keys exist at its step's width and at no
    other, and the branch taken while every key is kept makes no score and no key at all."""
    from django_assistant_bot_tpu.ops.attention import select_widths

    with open(os.path.join(HERE, "data", "dsa_moe_tiny.json")) as f:
        hf = json.load(f)["hf"]
    text = _lowered(hf, "prefill_chunk_paged").compile().as_text()
    widths = select_widths(64, hf["index_topk"], 8)
    assert widths == (16, 24, 32, 40, 48, 56, 64)
    branches = [m.split(", ") for m in re.findall(r" conditional\(.*?branch_computations=\{([^}]*)\}", text)]
    switches = [b for b in branches if len(b) > 2]
    assert [len(b) for b in switches] == [1 + len(widths)] * 2 and len(branches) == 2 * (1 + len(widths))
    for switch in switches:
        bodies = [re.search(rf"^{re.escape(name)} .*?^}}", text, re.M | re.S).group(0) for name in switch]
        assert not re.search(r"(f32|u32)\[", bodies[0])  # every key kept: no score, no key to count over
        for body, width in zip(bodies[1:], widths):  # the counting's sortable keys: [queries, this step of the view]
            assert set(re.findall(r"u32\[1,24,(\d+)\]", body)) == {str(width), "1"}


if __name__ == "__main__":  # the named configurations' operation counts as the tree stands -> tests/data/mla_moe_hlo_ops.json
    import sys

    path = os.path.join(HERE, "data", "mla_moe_hlo_ops.json")
    with open(path) as f:
        out = json.load(f)
    for config in sys.argv[1:]:
        with open(os.path.join(HERE, "data", config + ".json")) as f:
            hf = json.load(f)["hf"]
        out[config] = {program: _op_counts(_lowered(hf, program).compile().as_text()) for program in PROGRAMS}
    with open(path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
