"""The latent-attention MoE block on the serving path, on the CPU: the decode
kernel in Pallas interpret mode against the plain function, the two-stack
checkpoint, ``ModelRegistry`` -> ``GenerationEngine`` -> greedy tokens against
the plain reference, the counters, and the registry's refusals."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import families
from django_assistant_bot_tpu.checkpoint import load_model, save_model
from django_assistant_bot_tpu.models import DecoderConfig, mla_moe
from django_assistant_bot_tpu.ops import attention as attn
from django_assistant_bot_tpu.serving.registry import ModelRegistry

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(os.path.dirname(HERE), "benchmarks")
SEED = 29_000_017


def _conf():
    with open(os.path.join(HERE, "data", "mla_moe_tiny.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A seeded tiny model as a native checkpoint (the benchmark family's tree,
    the program's ``save_model``), and what made it."""
    conf = _conf()
    family = families.load(conf, DATA)
    cfg = dataclasses.replace(DecoderConfig.from_hf(conf["hf"], dtype=jnp.float32), max_seq_len=256)
    params = jax.tree.map(lambda x: x.astype(jnp.float32), family.served_params(conf, SEED))
    path = str(tmp_path_factory.mktemp("mla_moe") / "ckpt")
    save_model(path, "decoder", cfg, params)
    return conf, family, cfg, params, path


def _spec(path, **over):
    spec = dict(kind="decoder", checkpoint=path, dtype="float32", arch="mla_moe", max_slots=4, max_seq_len=128,
                chunk_size=64, kv_page_size=16, kv_pages=32, prefix_cache=0, warmup=False)
    spec.update(over)
    return {"m": spec}


# --- the kernel, interpreted ------------------------------------------------

PAGE, W, WV, NB, L, H = 32, 256, 128, 4, 2, 8  # 16-row packed tiles divide the page; rows of whole lane tiles
# Both forms round the softmax weights to bfloat16 before the value matmul, the kernel unnormalised
# and page by page, the plain function normalised over the row: a relative 2^-9 on weights that sum
# to one over values of scale 0.5, so outputs differ by up to ~2^-10, then each is rounded once to
# bfloat16 (a relative 2^-8).  ATOL 2^-9 holds both; a row left out of 17 keys moves an output by
# ~0.5/17, fifteen times that.
RTOL, ATOL = 2.0**-7, 2.0**-9


@pytest.mark.parametrize("case", ["mixed-positions", "page-edges", "inactive-and-unallocated"])
def test_latent_decode_kernel_equals_the_plain_function_and_writes_one_row(case):
    """Every byte of the pool, and the output of every live row: the kernel patches one row per live
    slot in place and reads each listed page once, for scores and for values."""
    B = 5
    P = B * NB
    rng = np.random.default_rng(len(case))
    draw = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.5, jnp.float32).astype(jnp.bfloat16)  # noqa: E731
    q, row, pool = draw(B, H, W), draw(B, W), draw(L, P, PAGE, W)
    bt = rng.permutation(P).reshape(B, NB).astype(np.int32)
    positions = {"mixed-positions": [0, 17, 40, 95, 127], "page-edges": [31, 32, 63, 64, 96],
                 "inactive-and-unallocated": [5, 50, 70, 100, 127]}[case]
    active = np.ones(B, bool)
    if case == "inactive-and-unallocated":
        active[1] = False
        bt[3, 3] = P  # the block its position falls in has no page: it reads its pages below, writes nothing
    positions, active, bt = jnp.asarray(positions, jnp.int32), jnp.asarray(active), jnp.asarray(bt)
    layer = jnp.int32(1)
    plan = attn.paged_decode_plan(bt, positions, active, n_pages=P, page=PAGE)
    o, new_pool = jax.jit(functools.partial(
        attn.latent_decode_update_attend, scale=0.11, value_width=WV, interpret=True)
    )(q, row, pool, layer, bt, positions, plan)
    phys = jnp.take_along_axis(bt, (positions // PAGE)[:, None], axis=1)[:, 0]
    want_pool = pool.at[1, jnp.where(active, jnp.minimum(phys, P), P), positions % PAGE].set(row, mode="drop")
    assert np.array_equal(np.asarray(new_pool, np.float32), np.asarray(want_pool, np.float32))
    want = attn.latent_decode_attention(q, want_pool[1], bt, positions, scale=0.11, value_width=WV, active=active)
    live = np.asarray(active)
    np.testing.assert_allclose(np.asarray(o, np.float32)[live], np.asarray(want, np.float32)[live], rtol=RTOL, atol=ATOL)
    assert not np.asarray(o, np.float32)[~live].any()


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, nested ones too."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_pallas_calls(sub))
    return out


@pytest.mark.parametrize("masked", [False, True], ids=["no-indexer", "under-a-selection"])
def test_latent_decode_call_takes_a_mask_operand_only_where_the_block_selects(masked):
    """Without ``keep`` (``index_topk`` 0: A.X-K1's step) the call is the one it was before the sparse decode path
    was written: eight operands, the pool aliased through, the kernel's name; the selection adds one operand."""
    B = 3
    P = B * NB
    q, row, pool = jnp.zeros((B, H, W), jnp.bfloat16), jnp.zeros((B, W), jnp.bfloat16), jnp.zeros((L, P, PAGE, W), jnp.bfloat16)
    bt, positions = jnp.arange(P, dtype=jnp.int32).reshape(B, NB), jnp.asarray([5, 40, 100], jnp.int32)
    plan = attn.paged_decode_plan(bt, positions, jnp.ones(B, bool), n_pages=P, page=PAGE)
    keep = jnp.ones((B, NB, PAGE), jnp.int32) if masked else None
    jaxpr = jax.make_jaxpr(functools.partial(attn.latent_decode_update_attend, scale=0.11, value_width=WV))(
        q, row, pool, jnp.int32(1), bt, positions, plan, keep=keep)
    (call,) = _pallas_calls(jaxpr.jaxpr)
    assert call.params["input_output_aliases"] == ((7, 1),)
    assert [tuple(v.aval.shape) for v in call.invars[5:]] == [(B, H, W), (B, 1, W), (L, P, PAGE, W)] + [(B, NB, PAGE)] * masked
    assert len(call.invars) == 8 + masked
    assert ("name=sparse_latent_decode" if masked else "name=latent_decode") in str(call)
    assert "attn/sparse_core" in str(call.source_info.name_stack) if masked else "attn/kv_read" in str(call.source_info.name_stack)


def test_the_step_without_an_indexer_makes_the_one_kernel_call_it_made(served, monkeypatch):
    """``index_topk`` 0 on the kernel path: one ``latent_decode`` call a scan body, no score or selection call."""
    conf, family, cfg, params, _ = served
    monkeypatch.setattr(mla_moe, "latent_decode_kv_path", lambda *a, **k: "kernel")
    # the kernel wants heads in whole sublane tiles and a lane-wide latent; nothing runs here
    cfg8 = dataclasses.replace(cfg, num_heads=8, latent_moe=dataclasses.replace(cfg.latent_moe, kv_lora_rank=128))
    params8 = jax.eval_shape(lambda: mla_moe.held_params(cfg8, mla_moe.init(cfg8, jax.random.key(0))))
    cache = jax.eval_shape(lambda: mla_moe.init_paged_cache(cfg8, 4, 32, 16))
    jaxpr = jax.make_jaxpr(lambda p, t, c, bt: mla_moe.decode_step_paged(p, cfg8, t, c, bt))(
        params8, jnp.zeros((4,), jnp.int32), cache, jnp.zeros((4, 8), jnp.int32))
    calls = _pallas_calls(jaxpr.jaxpr)
    assert len(calls) == 2 and all("name=latent_decode" in str(c) and len(c.invars) == 8 for c in calls)


# --- checkpoint, registry, engine ---------------------------------------------


def test_save_model_load_round_trip_of_the_two_stack_tree(served):
    conf, family, cfg, params, path = served
    kind, cfg2, loaded, _ = load_model(path)
    assert kind == "decoder" and cfg2 == cfg and cfg2.latent_moe == cfg.latent_moe  # nested config, tuples and all
    want, got = jax.tree_util.tree_flatten_with_path(params), jax.tree_util.tree_flatten_with_path(loaded)
    assert [k for k, _ in want[0]] == [k for k, _ in got[0]]
    assert set(loaded) == {"tok_embed", "final_norm", "lm_head", "dense_layers", "moe_layers"}
    assert loaded["dense_layers"]["w_gate"].shape == (1, 64, 128) and loaded["moe_layers"]["w_gate"].shape == (2, 16, 64, 32)
    for (_, a), (_, b) in zip(want[0], got[0]):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_family_tree_matches_the_programs_init_tree_leaf_for_leaf(served):
    conf, family, cfg, params, _ = served
    own = jax.eval_shape(lambda: mla_moe.init(cfg, jax.random.key(0)))
    a, b = (jax.tree_util.tree_flatten_with_path(t)[0] for t in (own, params))
    assert [(k, v.shape) for k, v in a] == [(k, v.shape) for k, v in b]
    axes = mla_moe.logical_axes(cfg)  # of the tree the device holds: `w_uq` in two leaves, five more in another form
    held = jax.eval_shape(lambda: mla_moe.held_params(cfg, mla_moe.init(cfg, jax.random.key(0))))
    assert jax.tree.structure(axes, is_leaf=lambda x: isinstance(x, tuple)) == jax.tree.structure(held) != jax.tree.structure(own)
    # the share: a rank's tree holds its experts only, the router stays whole
    share = dict(conf, hf=dict(conf["hf"], n_routed_experts=4, ep_size=4, ep_rank=3))
    tree = jax.eval_shape(lambda: family.served_params(share, 1))
    assert tree["moe_layers"]["w_gate"].shape == (2, 4, 64, 32) and tree["moe_layers"]["router"].shape == (2, 64, 16)


@pytest.mark.parametrize("name", ["mla_moe_tiny.json", "dsa_moe_tiny.json"])
def test_a_checkpoint_of_inits_tree_loads_to_the_tree_the_in_memory_path_holds(name, tmp_path):
    """``held_params`` is the one place that knows both forms: a checkpoint written from ``init``'s tree (the
    checkpoint's keys and shapes) reaches the engine, through the registry, as the tree ``models.held_params`` makes
    of the same arrays in memory, and as ``logical_axes`` describes."""
    from django_assistant_bot_tpu import models

    with open(os.path.join(HERE, "data", name)) as f:
        hf = json.load(f)["hf"]
    cfg = dataclasses.replace(DecoderConfig.from_hf(hf, dtype=jnp.float32), max_seq_len=256)
    tree = mla_moe.init(cfg, jax.random.key(3))
    path = str(tmp_path / "ckpt")
    save_model(path, "decoder", cfg, tree)
    assert load_model(path)[2]["moe_layers"]["w_uq"].shape == tree["moe_layers"]["w_uq"].shape  # on disk: as it was
    reg = ModelRegistry.from_config(_spec(path))
    try:
        got = reg.get_generator("m").params
    finally:
        reg.stop()
    want = models.held_params(cfg, tree)
    assert jax.tree.structure(got) == jax.tree.structure(want) == jax.tree.structure(
        mla_moe.logical_axes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and np.array_equal(np.asarray(a), np.asarray(b))
    assert models.held_params(models.DecoderConfig.tiny(), {"layers": {}}) == {"layers": {}}  # llama holds a checkpoint's own tree


def test_engine_streams_the_references_greedy_tokens_and_counts_the_picks(served):
    """cli serve's path: registry (native checkpoint) -> engine -> fused tick over the latent pool."""
    conf, family, cfg, params, path = served
    reg = ModelRegistry.from_config(_spec(path))
    try:
        eng = reg.get_generator("m")
        assert eng.decode_kv_path == "xla"  # the CPU; "kernel" on a TPU
        kv = eng.kv_stats()
        assert kv["kv_cache_kind"] == "latent" and kv["kv_bytes_per_token"] == 3 * 128 * 4
        prompt = [int(t) for t in np.random.default_rng(5).integers(32, 127, 23)]
        got = eng.submit(prompt, max_tokens=10, temperature=0.0).result(timeout=300)
        got = list(getattr(got, "token_ids", got))[:10]
        seq = list(prompt)
        cols = list(range(conf["hf"]["vocab_size"]))
        for _ in range(len(got)):  # the plain reference, one full forward pass per token
            seq.append(int(np.argmax(family.reference_logits(conf, SEED, [seq + [0]], [len(seq) - 1], cols)[0][-1])))
        assert got == seq[len(prompt):]
        moe = eng.tick_stats()["moe"]
        assert (moe["experts_held"], moe["router_experts"], moe["ep_size"]) == (16, 16, 1)
        assert moe["prefill"]["picks"] == 23 * 2 * 4 == moe["prefill"]["picks_local"]  # every expert is held here
        assert moe["decode"]["picks"] >= 9 * 2 * 4 and moe["decode"]["layer_steps"] >= 9 * 2
        assert sum(moe["decode"]["tokens_per_expert"]) == moe["decode"]["picks_local"]
        from django_assistant_bot_tpu.serving.obs import render_prometheus

        text = render_prometheus(reg)
        assert 'dabt_moe_picks_total{' in text and 'dabt_moe_expert_tokens_total{' in text
    finally:
        reg.stop()


def test_engine_names_the_held_experts_path_and_the_share_of_experts_skipped(served):
    """PR 30: the label beside ``decode_kv_path`` and the counter that says how often skipping engages."""
    from django_assistant_bot_tpu.serving.obs import render_prometheus

    conf, family, cfg, params, path = served
    reg = ModelRegistry.from_config(_spec(path))
    try:
        eng = reg.get_generator("m")
        assert eng.moe_experts_path == "xla" == eng.tick_stats()["moe_experts_path"]  # the CPU; "kernel" on a TPU
        eng.submit([40 + i for i in range(9)], max_tokens=6, temperature=0.0).result(timeout=300)
        moe = eng.tick_stats()["moe"]
        for kind in ("decode", "prefill"):
            m = moe[kind]
            assert m["layer_steps"] > 0 and 0.0 <= m["experts_skipped_share"] < 1.0
            assert m["experts_skipped_share"] == round(1 - m["experts_hit"] / (16 * m["layer_steps"]), 4)
        text = render_prometheus(reg)
        assert 'dabt_moe_experts_skipped_share{' in text and 'dabt_moe_experts_kernel{' in text
    finally:
        reg.stop()


@pytest.mark.parametrize("over,why", [
    ({"speculative": 4}, "tree verification"),
    ({"prefix_cache": 8}, "prefix cache"),
    ({"quantize": "int8"}, "int8/int4"),
    ({"kv_cache_dtype": "fp8"}, "reduced-precision latent cache"),
    ({"arch": "llama"}, "spec says arch='llama'"),
])
def test_the_registry_refuses_what_the_block_does_not_implement(served, over, why):
    with pytest.raises(ValueError, match=why):
        ModelRegistry.from_config(_spec(served[4], **over))


def test_hf_loader_refuses_a_latent_moe_checkpoint_directory(tmp_path):
    from django_assistant_bot_tpu.models.hf_loader import load_decoder

    (tmp_path / "config.json").write_text(json.dumps(dict(_conf()["hf"], model_type="axk1")))
    with pytest.raises(ValueError, match="parameter names are not mapped"):
        load_decoder(str(tmp_path))


def test_flash_attention_takes_a_value_width_and_scale_of_its_own():
    """Prefill's shape: keys 256 wide (192 padded), values 128, an explicit scale."""
    rng = np.random.default_rng(3)
    q, k = (jnp.asarray(rng.standard_normal((1, 2, 256, 256)), jnp.float32) for _ in range(2))
    v = jnp.asarray(rng.standard_normal((1, 2, 256, 128)), jnp.float32)
    got = attn.flash_attention(q, k, v, causal=True, scale=0.05, interpret=True)
    want = attn.dot_product_attention(q, k, v, causal=True, scale=0.05)
    assert got.shape == (1, 2, 256, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
