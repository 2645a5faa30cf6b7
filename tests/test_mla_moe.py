"""The latent-attention MoE block (``models/mla_moe.py``) against its plain
reference (``benchmarks/reference/mla_moe.py``, which imports nothing of the
program), at the tiny preset on the CPU in float32: hidden 64, 4 heads, ranks
32/16, head widths 16+8/16, one dense + two expert layers, 16 experts in 4
groups, top-4 of 2 groups, one shared expert.

Weights come from the benchmark's family (``benchmarks/families/mla_moe.py``):
bfloat16-valued, computed in float32 on both sides.  **Tolerances.**  Logits
are O(1); the two sides sum the same float32 products in different orders
(fused einsums against ``@`` under ``highest``), which leaves ~1e-6 per
matmul and ~1e-5 after three layers: ``ATOL = 2e-4`` on logits leaves room
for a 20-fold pile-up and is 50 times under what one bfloat16 rounding of an
activation (2^-9 relative of O(1)) would move.  Routing weights are compared
at 1e-6 (one float32 sigmoid and a division).
"""

import copy
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import families
from django_assistant_bot_tpu.models import DecoderConfig, mixtral, mla_moe, module_for
from django_assistant_bot_tpu.models.config import LatentMoEConfig

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DATA = os.path.join(ROOT, "benchmarks")
ATOL = 2e-4
SEED = 2**31 + 77


def _conf(**hf):
    with open(os.path.join(HERE, "data", "mla_moe_tiny.json")) as f:
        conf = json.load(f)
    conf["hf"].update(hf)
    return conf


@pytest.fixture(scope="module")
def family():
    return families.load(_conf(), DATA)


def _program(family, conf):
    cfg = DecoderConfig.from_hf(conf["hf"], dtype=jnp.float32)
    cfg = dataclasses.replace(cfg, max_seq_len=256)
    params = jax.tree.map(lambda x: x.astype(jnp.float32), family.served_params(conf, SEED))
    return cfg, mla_moe.held_params(cfg, params)  # the family's tree is a checkpoint's; the entry points take the held one


def _reference(family, conf, seqs, firsts=None):
    cols = list(range(conf["hf"]["vocab_size"]))
    return family.reference_logits(conf, SEED, seqs, firsts or [0] * len(seqs), cols)


def _ids(n, seed=0, vocab=512):
    return [int(t) for t in np.random.default_rng(seed).integers(0, vocab, n)]


def test_from_hf_reads_the_family_and_the_share():
    conf = _conf(n_routed_experts=4, ep_size=4, ep_rank=2)
    cfg = DecoderConfig.from_hf(conf["hf"])
    lm = cfg.latent_moe
    assert cfg.arch == "mla_moe" and module_for(cfg) is mla_moe
    assert (lm.router_experts, lm.experts_held, lm.first_expert) == (16, 4, 8)
    assert (lm.q_lora_rank, lm.kv_lora_rank, lm.qk_head_dim, lm.v_head_dim) == (32, 16, 24, 16)
    assert lm.latent_width == 128  # 16 + 8, padded to whole lane tiles
    # published form: ep_size 1, every expert held
    lm = DecoderConfig.from_hf(_conf()["hf"]).latent_moe
    assert (lm.router_experts, lm.experts_held, lm.first_expert) == (16, 16, 0)


def test_the_benchmark_configuration_is_the_published_one_but_for_what_reduced_lists():
    """`benchmarks/configs/a.x-k1-ep16.json`: published widths, the four cut keys listed, the top-level
    copy (what the driver compares with the catalog) equal to what `hf` hands the program."""
    with open(os.path.join(DATA, "configs", "a.x-k1-ep16.json")) as f:
        conf = json.load(f)
    hf = conf["hf"]
    assert {k: v for k, v in hf.items() if k != "ep_rank"} == {k: conf[k] for k in hf if k != "ep_rank"}
    assert sorted(conf["reduced"]) == sorted(conf["published"].keys() - {"why"})
    assert all(conf[k] != conf["published"][k] for k in conf["reduced"])
    cfg = DecoderConfig.from_hf(hf)
    lm = cfg.latent_moe
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.num_heads, cfg.experts_per_token) == (7168, 18432, 64, 8)
    assert (lm.q_lora_rank, lm.kv_lora_rank, lm.qk_nope_head_dim, lm.qk_rope_head_dim, lm.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (lm.router_experts, lm.experts_held, lm.n_group, lm.topk_group, lm.moe_intermediate_size) == (192, 12, 8, 4, 2048)
    assert (cfg.num_layers, lm.first_dense_layers, cfg.vocab_size, lm.latent_width) == (7, 1, 20480, 640)


def test_yarn_softmax_factor_and_unscaled_tables():
    """mscale_all_dim 1, factor 32: the softmax scale is qk^-0.5 (0.1 ln 32 + 1)^2 and cos/sin carry
    mscale/mscale_all_dim = 1 (the repo's one `attention_factor` on cos/sin came out 1.0 and the
    softmax factor was missing)."""
    cfg = DecoderConfig.from_hf(_conf()["hf"])
    m = 0.1 * np.log(32.0) + 1.0
    assert cfg.latent_moe.softmax_scale_mult == pytest.approx(m * m, rel=1e-12)
    assert mla_moe.softmax_scale(cfg) == pytest.approx(24 ** -0.5 * m * m, rel=1e-12)
    assert cfg.rope_scaling[0] == "yarn" and cfg.rope_scaling[5] == 1.0
    from benchmarks.reference import mla_moe as ref

    assert ref.softmax_scale(_conf()["hf"]) == pytest.approx(mla_moe.softmax_scale(cfg), rel=1e-12)
    cos, _ = mla_moe._rope_tables(cfg, 256)
    ang = np.arange(256)[:, None] * ref.yarn_inv_freq(_conf()["hf"])[None, :]
    np.testing.assert_allclose(np.asarray(cos), np.cos(ang), atol=2e-5)  # float32 tables of float64 angles


@pytest.mark.parametrize("model_type,keys", [
    ("zaya", {"kv_lora_rank": 64}), ("someday", {"n_routed_experts": 8}), ("mixed", {"layer_types": ["full_attention"]}),
])
def test_from_hf_refuses_an_unknown_model_type_whose_keys_change_the_mathematics(model_type, keys):
    hf = {"model_type": model_type, "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
          "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2, **keys}
    with pytest.raises(ValueError, match="refusing to read it as a dense Llama"):
        DecoderConfig.from_hf(hf)
    plain = {k: v for k, v in hf.items() if k not in keys}
    assert DecoderConfig.from_hf(plain).arch == "llama"  # the six keys alone still read as before
    assert DecoderConfig.from_hf({**hf, "model_type": "qwen2"}).arch == "llama"  # a known block that carries the key


@pytest.mark.parametrize("change,why", [
    ({"scoring_func": "softmax"}, "scoring_func"), ({"topk_method": "greedy_v9"}, "topk_method"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"), ({"ep_size": 4}, "ep_size 4 without ep_rank"),
    ({"q_lora_rank": None}, "q_lora_rank"), ({"attention_bias": True}, "attention_bias"),
])
def test_from_hf_refuses_what_the_block_cannot_honour(change, why):
    with pytest.raises(ValueError, match=why):
        DecoderConfig.from_hf(_conf(**change)["hf"])


def test_prefill_logits_equal_the_plain_reference(family):
    conf = _conf()
    cfg, params = _program(family, conf)
    seqs = [_ids(48, 1), _ids(48, 2)]
    for lengths in ([18, 7], [40, 29]):  # the last-token logits at two lengths of each row
        logits, rows, stats = mla_moe.prefill(params, cfg, jnp.asarray(seqs), jnp.asarray(lengths))
        ref = _reference(family, conf, [s[: n + 1] for s, n in zip(seqs, lengths)])
        for i in range(2):
            np.testing.assert_allclose(np.asarray(logits[i]), ref[i][-1], atol=ATOL)
    assert rows.shape == (3, 2, 48, 128) and stats.shape == (4 + 16,)
    assert float(np.abs(ref[0][-1]).max()) > 1.0  # logits are O(1): the tolerance means something


def test_prefill_then_paged_decode_equals_the_reference_at_every_position(family):
    """Absorbed against expanded: the program decodes over the latent rows themselves, the reference
    builds keys and values for every position."""
    conf = _conf()
    cfg, params = _program(family, conf)
    seqs = [_ids(44, 3), _ids(37, 4)]
    ref = _reference(family, conf, seqs)
    page, NB, P = 8, 8, 32
    cache = mla_moe.init_paged_cache(cfg, 4, P, page)
    bt = np.full((4, NB), P, np.int32)
    bt[0, :6], bt[2, :6] = [3, 5, 7, 9, 11, 13], [2, 4, 6, 8, 10, 12]  # slots 0 and 2; 1 and 3 stay empty
    bt = jnp.asarray(bt)
    n0 = [20, 12]
    ids = np.zeros((2, 24), np.int32)
    for i, s in enumerate(seqs):
        ids[i, : n0[i]] = s[: n0[i]]
    logits, rows, stats = mla_moe.prefill(params, cfg, jnp.asarray(ids), jnp.asarray(n0))
    cache = mla_moe.insert_sequences_paged(cache, rows, stats, jnp.asarray(n0), jnp.asarray([0, 2]), bt[jnp.asarray([0, 2])])
    step = jax.jit(lambda t, c, a: mla_moe.decode_step_paged(params, cfg, t, c, bt, active=a))
    active = jnp.asarray([True, False, True, False])
    for k in range(17):
        toks = jnp.asarray([seqs[0][n0[0] + k], 0, seqs[1][n0[1] + k], 0], jnp.int32)
        logits, cache = step(toks, cache, active)
        np.testing.assert_allclose(np.asarray(logits[0]), ref[0][n0[0] + k], atol=ATOL)
        np.testing.assert_allclose(np.asarray(logits[2]), ref[1][n0[1] + k], atol=ATOL)
    assert [int(x) for x in cache.lengths] == [37, 0, 29, 0]  # frozen slots wrote nothing
    assert int(cache.stats[0, 2]) == 17 * 2 and int(cache.stats[0, 0]) == 17 * 2 * 2 * 4  # layer-steps; picks of live rows


def test_chunked_and_suffix_prefill_equal_one_shot_prefill(family):
    conf = _conf()
    cfg, params = _program(family, conf)
    s = _ids(53, 5)
    page, NB, P = 8, 8, 16
    bt_row = jnp.asarray([9, 1, 4, 2, 7, 11, 3, 0], jnp.int32)
    one, _, _ = mla_moe.prefill(params, cfg, jnp.asarray([s + [0] * 3]), jnp.asarray([53]))
    cache = mla_moe.init_paged_cache(cfg, 2, P, page)
    for start, valid in ((0, 24), (24, 24), (48, 5)):
        chunk = (s[start:start + valid] + [0] * 24)[:24]
        logits, cache = mla_moe.prefill_chunk_paged(
            params, cfg, jnp.asarray([chunk]), cache, bt_row, jnp.int32(1), jnp.int32(start), jnp.int32(valid))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(one), atol=ATOL)
    assert int(cache.lengths[1]) == 53
    # suffix: two rows share nothing but the call; row 0 continues a 24-token prefix held in the pool
    cache2 = mla_moe.init_paged_cache(cfg, 2, P, page)
    _, cache2 = mla_moe.prefill_chunk_paged(
        params, cfg, jnp.asarray([s[:24]]), cache2, bt_row, jnp.int32(0), jnp.int32(0), jnp.int32(24))
    bts = jnp.stack([bt_row, jnp.full((NB,), P, jnp.int32)])
    suffix = jnp.asarray([(s[24:] + [0] * 3), [0] * 32])
    logits, cache2 = mla_moe.prefill_suffix_paged(
        params, cfg, suffix, cache2, bts, jnp.asarray([0, 2]), jnp.asarray([24, 0]), jnp.asarray([29, 0]))
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(one[0]), atol=ATOL)
    # the same rows reached the same pages either way
    live = np.asarray(bt_row[:7])
    np.testing.assert_allclose(np.asarray(cache2.kv[:, live]).reshape(3, 56, -1)[:, :53],
                               np.asarray(cache.kv[:, live]).reshape(3, 56, -1)[:, :53], atol=1e-5)


def test_routing_picks_weights_and_scaling_equal_the_reference(family):
    from benchmarks.reference import mla_moe as ref

    conf = _conf()
    cfg, params = _program(family, conf)
    h = jnp.asarray(np.random.default_rng(6).standard_normal((200, 64)), jnp.float32)
    router = params["moe_layers"]["router"][0]
    idx, w = mixtral.route_sigmoid_groups(cfg.latent_moe, cfg.experts_per_token, h, router)
    with jax.default_matmul_precision("highest"):
        ridx, rw, _ = ref.route(conf["hf"], h, router)
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(np.asarray(ridx), -1))
    order, rorder = np.argsort(np.asarray(idx), -1), np.argsort(np.asarray(ridx), -1)
    np.testing.assert_allclose(np.take_along_axis(np.asarray(w), order, -1), np.take_along_axis(np.asarray(rw), rorder, -1), atol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, atol=1e-5)  # normalised over the picks, times 2.5
    groups = np.asarray(idx) // 4
    assert all(len(set(g)) <= 2 for g in groups)  # picks limited to 2 of the 4 groups


def test_no_token_is_dropped_when_a_router_sends_all_1024_tokens_to_one_expert(family):
    """No capacity: the grouped path gives the busiest expert as many tiles as it draws."""
    conf = _conf()
    cfg, params = _program(family, conf)
    p = jax.tree.map(lambda a: a[0], params["moe_layers"])
    router = np.full((64, 16), 0.0, np.float32)
    router[:, [5, 4, 6, 7]] = 1.0  # with positive inputs: expert 5's group wins for every token
    router[:, 5] = 3.0
    p = dict(p, router=jnp.asarray(router))
    x = jnp.abs(jnp.asarray(np.random.default_rng(7).standard_normal((1, 1024, 64)), jnp.float32)) + 0.1
    y, stats = mixtral.held_experts_mlp(cfg, p, x, jnp.ones((1, 1024), bool))
    assert int(stats[4 + 5]) == 1024 and int(stats[1]) == 4096  # every token reached expert 5; every pick is held here
    idx, w = mixtral.route_sigmoid_groups(cfg.latent_moe, 4, x[0], p["router"])
    want = jnp.zeros((1024, 64))
    for e in range(16):
        g = jnp.where(idx == e, w, 0.0).sum(-1)
        hdn = jax.nn.silu(x[0] @ p["w_gate"][e]) * (x[0] @ p["w_up"][e])
        want = want + g[:, None] * (hdn @ p["w_down"][e])
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want), atol=ATOL)


def test_grouped_and_dense_expert_paths_agree(family, monkeypatch):
    conf = _conf(n_routed_experts=4, ep_size=4, ep_rank=1)
    cfg, params = _program(family, conf)
    p = jax.tree.map(lambda a: a[1], params["moe_layers"])
    x = jnp.asarray(np.random.default_rng(8).standard_normal((2, 100, 64)), jnp.float32)
    valid = jnp.arange(100)[None, :] < jnp.asarray([100, 61])[:, None]
    grouped, s_g = mixtral.held_experts_mlp(cfg, p, x, valid)
    monkeypatch.setattr(mixtral, "DENSE_MAX_TOKENS", 4096)
    dense, s_d = mixtral.held_experts_mlp(cfg, p, x, valid)
    assert np.array_equal(np.asarray(s_g), np.asarray(s_d)) and int(s_g[0]) == 161 * 4
    v = np.asarray(valid)
    np.testing.assert_allclose(np.asarray(grouped)[v], np.asarray(dense)[v], atol=ATOL)
    assert not np.asarray(grouped)[~v].any()  # pad rows are kept out of the work


def test_the_shares_add_up_to_the_uncut_layer(family):
    """Over ep_rank 0..3 the routed parts, with the shared expert counted once, equal what the uncut
    reference gives for the whole layer (model-configs guide, section 4): for the program's shares
    and for the reference's own."""
    from benchmarks.reference import mla_moe as ref

    hf = _conf()["hf"]
    layer = family.float32_layer(hf, SEED, 2)
    h = jnp.asarray(np.random.default_rng(9).standard_normal((1, 256, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut, _ = ref.moe_ffn(hf, layer, h)
        shared, _ = ref.moe_ffn(hf, {k: (v[:0] if k in ("w_gate", "w_up", "w_down") else v) for k, v in layer.items()}, h)
    program, reference = np.asarray(shared), np.asarray(shared)
    for rank in range(4):
        share = _conf(n_routed_experts=4, ep_size=4, ep_rank=rank)["hf"]
        held = {k: (v[4 * rank: 4 * rank + 4] if k in ("w_gate", "w_up", "w_down") else v) for k, v in layer.items()}
        cfg = DecoderConfig.from_hf(share, dtype=jnp.float32)
        y, stats = mixtral.held_experts_mlp(cfg, held, h, jnp.ones((1, 256), bool))
        program = program + np.asarray(y)
        with jax.default_matmul_precision("highest"):
            reference = reference + np.asarray(ref.moe_ffn(share, held, h, first_expert=4 * rank)[0]) - np.asarray(shared)
        assert int(stats[0]) == 256 * 4 and 0 < int(stats[1]) < 256 * 4  # each rank holds some of the picks
    np.testing.assert_allclose(program, np.asarray(uncut), atol=ATOL)
    np.testing.assert_allclose(reference, np.asarray(uncut), atol=ATOL)
    assert float(np.abs(np.asarray(uncut) - np.asarray(shared)).max()) > 0.1  # the routed part is not nothing


# --- the held form (PR 43): what the device holds is the checkpoint's tree in another order of memory ---------------

TINY = ("mla_moe_tiny.json", "dsa_moe_tiny.json")  # without and with an indexer


def _tiny(name, dtype=jnp.float32, **hf):
    """(conf, cfg, the family's checkpoint-form tree) of one of the two tiny configurations."""
    with open(os.path.join(HERE, "data", name)) as f:
        conf = json.load(f)
    conf["hf"].update(hf)
    cfg = dataclasses.replace(DecoderConfig.from_hf(conf["hf"], dtype=dtype), max_seq_len=256)
    tree = jax.tree.map(lambda x: x.astype(dtype), families.load(conf, DATA).served_params(conf, SEED))
    if hf.get("vocab_size"):  # a vocabulary that is not whole lane tiles: the head cut to it
        tree = dict(tree, tok_embed=tree["tok_embed"][:cfg.vocab_size], lm_head=tree["lm_head"][:, :cfg.vocab_size])
    return conf, cfg, tree


def _as_the_checkpoint_form_contracted(cfg):
    """``mla_moe._mm`` as the code before PR 43 wrote each of the contractions that changed, over the weight put back into the
    checkpoint's form (a transposition and a slice: no arithmetic): the oracle the held form is compared with."""
    lm = cfg.latent_moe
    H, dr, C, R = cfg.num_heads, lm.qk_rope_head_dim, lm.kv_lora_rank, lm.q_lora_rank

    def mm(pattern, x, w, dtype):
        w = w.astype(dtype)
        B, S = x.shape[:2]
        if pattern == "bsr,dhr->bshd":  # w_uq_rope [dr, H, R]: the last dr of a head's dn+dr columns
            return jnp.einsum("bsr,ro->bso", x, w.transpose(2, 1, 0).reshape(R, H * dr)).reshape(B, S, H, dr)
        if pattern == "bsr,hdr->bshd":  # w_uq_nope [H, dn, R], or w_iq [Hi, Di, R]
            return jnp.einsum("bsr,ro->bso", x, w.transpose(2, 0, 1).reshape(R, -1)).reshape(B, S, *w.shape[:2])
        if pattern == "bhd,hdc->bhc":  # w_uk [H, dn, C], absorbed into the query
            return jnp.einsum("bhd,chd->bhc", x, w.transpose(2, 0, 1))
        if pattern == "bhc,hdc->bhd":  # w_uv [H, dv, C], applied to the attended latent
            return jnp.einsum("bhc,chd->bhd", x, w.transpose(2, 0, 1))
        if pattern == "bsc,oc->bso":  # w_uk [H*dn, C] and w_uv [H*dv, C] expanding a page's keys and values
            return jnp.einsum("bsc,co->bso", x, w.T)
        if pattern == "bse,ec->bsc":  # w_dkv without its zero columns
            return jnp.einsum(pattern, x, w[:, :C + dr])
        return jnp.einsum(pattern, x, w)

    return mm


def _run_every_entry_point(cfg, params):
    """One-shot prefill, three chunks, a suffix over a cached prefix and five decode steps of two slots -> every
    logit, the pools' live pages and the counters, as one flat list."""
    s = _ids(53, 5, vocab=cfg.vocab_size)
    page, NB, P = 8, 8, 16
    bt_row = jnp.asarray([9, 1, 4, 2, 7, 11, 3, 0], jnp.int32)
    out = list(jax.tree.leaves(mla_moe.prefill(params, cfg, jnp.asarray([s + [0] * 3]), jnp.asarray([53]))))
    cache = mla_moe.init_paged_cache(cfg, 2, P, page)
    for start, valid in ((0, 24), (24, 24), (48, 5)):
        logits, cache = mla_moe.prefill_chunk_paged(
            params, cfg, jnp.asarray([(s[start:start + valid] + [0] * 24)[:24]]), cache, bt_row, jnp.int32(1),
            jnp.int32(start), jnp.int32(valid))
        out.append(logits)
    cache2 = mla_moe.init_paged_cache(cfg, 2, P, page)
    _, cache2 = mla_moe.prefill_chunk_paged(
        params, cfg, jnp.asarray([s[:24]]), cache2, bt_row, jnp.int32(0), jnp.int32(0), jnp.int32(24))
    bts = jnp.stack([bt_row, jnp.full((NB,), P, jnp.int32)])
    logits, cache2 = mla_moe.prefill_suffix_paged(
        params, cfg, jnp.asarray([(s[24:] + [0] * 3), [0] * 32]), cache2, bts, jnp.asarray([0, 2]), jnp.asarray([24, 0]),
        jnp.asarray([29, 0]))
    out.append(logits)
    bt2 = jnp.stack([jnp.full((NB,), P, jnp.int32), bt_row])
    for k in range(5):  # slot 1 holds the chunked prompt's 53 tokens
        logits, cache = mla_moe.decode_step_paged(
            params, cfg, jnp.asarray([0, 7 + k], jnp.int32), cache, bt2, active=jnp.asarray([False, True]))
        out.append(logits[1])
    return out + [x for c in (cache, cache2) for x in jax.tree.leaves(c)]


@pytest.mark.parametrize("vocab", [0, 500], ids=["vocab-512", "vocab-500"])
@pytest.mark.parametrize("name", TINY)
def test_every_entry_point_over_the_held_form_equals_the_checkpoint_forms_contractions(name, vocab, monkeypatch):
    """Float32 on the CPU: prefill, chunk, suffix and decode from ``held_params(checkpoint tree)`` give the logits,
    cache rows and counters the contractions written over the checkpoint's form give from the same weights; with a
    vocabulary of 500 the head is held 512 wide and the logits are cut back.  Counters and lengths are equal; the
    floats are the same float32 products summed in another order (XLA's CPU dot takes another loop nest for a
    weight whose contracted axis is last), so NOT bit for bit: 2.3e-6 at most was seen on O(1) logits, and the
    tolerance is a tenth of ``ATOL``, the one the plain reference is held to."""
    conf, cfg, tree = _tiny(name, **({"vocab_size": vocab} if vocab else {}))
    held = mla_moe.held_params(cfg, tree)
    assert held["lm_head"].shape == (64, 512)
    got = _run_every_entry_point(cfg, held)
    monkeypatch.setattr(mla_moe, "_mm", _as_the_checkpoint_form_contracted(cfg))
    want = _run_every_entry_point(cfg, dict(held, lm_head=tree["lm_head"]))
    assert len(got) == len(want) and got[0].shape == (1, cfg.vocab_size)
    for a, b in zip(got, want):
        if jnp.issubdtype(a.dtype, jnp.integer):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=ATOL / 10)


@pytest.mark.parametrize("name", TINY)
def test_in_bfloat16_the_held_form_is_within_a_rounding_of_the_checkpoint_forms_contractions(name, monkeypatch):
    """bfloat16 activations: the same products summed in another order, each result rounded once (2^-8 relative)."""
    conf, cfg, tree = _tiny(name, dtype=jnp.bfloat16)
    held = mla_moe.held_params(cfg, tree)
    ids, n = jnp.asarray([_ids(40, 3)]), jnp.asarray([40])
    got = mla_moe.prefill(held, cfg, ids, n)
    monkeypatch.setattr(mla_moe, "_mm", _as_the_checkpoint_form_contracted(cfg))
    want = mla_moe.prefill(held, cfg, ids, n)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), atol=0.06, rtol=2.0**-5)
    rows = lambda r: np.asarray((r[0] if isinstance(r, tuple) else r).astype(jnp.float32))  # noqa: E731
    np.testing.assert_allclose(rows(got[1]), rows(want[1]), atol=0.06, rtol=2.0**-5)


@pytest.mark.parametrize("name", TINY)
def test_held_params_moves_six_leaves_and_keeps_every_value(name):
    """The forms the module docstring states, at the tiny widths; nothing of the checkpoint's form is kept; what is
    transposed comes back on the device, what is padded or untouched stays the host's; and every value is where the
    checkpoint's was."""
    conf, cfg, tree = _tiny(name, vocab_size=500)
    lm = cfg.latent_moe
    H, dn, dr, dv, C, R, E = 4, lm.qk_nope_head_dim, lm.qk_rope_head_dim, lm.v_head_dim, lm.kv_lora_rank, lm.q_lora_rank, 64
    held = mla_moe.held_params(cfg, tree)
    assert set(held) == set(tree) and held["lm_head"].shape == (E, 512) and not np.asarray(held["lm_head"][:, 500:]).any()
    for stack, L in (("dense_layers", 1), ("moe_layers", 2)):
        ck, p = tree[stack], held[stack]
        assert set(p) == set(ck) - {"w_uq"} | {"w_uq_nope", "w_uq_rope"}  # a leaf is replaced, never held twice
        assert p["w_uq_nope"].shape == (L, H, dn, R) and p["w_uq_rope"].shape == (L, dr, H, R)
        assert p["w_uk"].shape == (L, H, dn, C) and p["w_uv"].shape == (L, H, dv, C)
        assert p["w_dkv"].shape == (L, E, lm.latent_width) and not np.asarray(p["w_dkv"][..., C + dr:]).any()
        w_uq = np.concatenate([np.asarray(p["w_uq_nope"]).transpose(0, 3, 1, 2), np.asarray(p["w_uq_rope"]).transpose(0, 3, 2, 1)], -1)
        np.testing.assert_array_equal(w_uq.reshape(L, R, -1), np.asarray(ck["w_uq"]))  # a head's columns: [nope | rope]
        np.testing.assert_array_equal(np.asarray(p["w_uk"]).transpose(0, 3, 1, 2).reshape(L, C, -1), np.asarray(ck["w_uk"]))
        np.testing.assert_array_equal(np.asarray(p["w_uv"]).transpose(0, 3, 1, 2).reshape(L, C, -1), np.asarray(ck["w_uv"]))
        np.testing.assert_array_equal(np.asarray(p["w_dkv"][..., :C + dr]), np.asarray(ck["w_dkv"]))
        if lm.index_topk:
            assert p["w_iq"].shape == (L, lm.index_n_heads, lm.index_head_dim, R)
            np.testing.assert_array_equal(np.asarray(p["w_iq"]).transpose(0, 3, 1, 2).reshape(L, R, -1), np.asarray(ck["w_iq"]))
        else:  # index_topk 0: a tree without indexer leaves stays without
            assert not {"w_iq", "w_ik", "w_iw", "ik_norm", "ik_bias"} & set(p)
        for k in set(ck) - {"w_uq", "w_uk", "w_uv", "w_dkv", "w_iq"}:
            assert p[k] is ck[k]
    host = jax.tree.map(lambda x: np.asarray(x.astype(jnp.bfloat16)), tree)  # what load_model hands the registry
    from_host = mla_moe.held_params(cfg, host)
    moved = ("w_uq_nope", "w_uq_rope", "w_uk", "w_uv", "w_iq")
    for stack in ("dense_layers", "moe_layers"):
        assert all(isinstance(x, jax.Array) == (k in moved) for k, x in from_host[stack].items())
    assert isinstance(from_host["lm_head"], np.ndarray) and from_host["tok_embed"] is host["tok_embed"]
    for a, b in zip(jax.tree.leaves(from_host), jax.tree.leaves(mla_moe.held_params(cfg, jax.tree.map(jnp.asarray, host)))):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a).astype(np.float32), np.asarray(b).astype(np.float32))


@pytest.mark.parametrize("name", TINY)
def test_logical_axes_describe_the_held_tree_and_a_mesh_takes_it(name):
    from django_assistant_bot_tpu.parallel import MeshAxes, make_mesh, shard_pytree

    conf, cfg, tree = _tiny(name)
    held = mla_moe.held_params(cfg, tree)
    axes = mla_moe.logical_axes(cfg)
    is_axes = lambda x: isinstance(x, tuple)  # noqa: E731
    assert jax.tree.structure(axes, is_leaf=is_axes) == jax.tree.structure(held)
    for names, leaf in zip(jax.tree.leaves(axes, is_leaf=is_axes), jax.tree.leaves(held)):
        assert len(names) == leaf.ndim
        assert all(leaf.shape[i] == cfg.num_heads * (cfg.latent_moe.v_head_dim if leaf.ndim == 3 else 1)
                   for i, n in enumerate(names) if n == "heads")  # heads on an axis of their own, but for wo's rows
    mesh = make_mesh(MeshAxes(), devices=jax.devices()[:1])
    with mesh:
        placed = shard_pytree(held, axes, mesh)
    for a, b in zip(jax.tree.leaves(placed), jax.tree.leaves(held)):
        assert a.shape == b.shape and np.array_equal(np.asarray(a), np.asarray(b))
