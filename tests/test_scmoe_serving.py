"""The shortcut-connected double layer on the serving path, on the CPU: the
checkpoint of two stacks (sublayers, expert layers) through ``ModelRegistry`` ->
``GenerationEngine``, prompts streamed as the plain reference's greedy tokens
over a pool of two latent rows a layer, ``kv_bytes_per_token``, the identity
experts' counters in ``tick_stats()["moe"]`` and on ``/metrics``, and what the
registry refuses for the block."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import families
from django_assistant_bot_tpu.checkpoint import load_model, save_model
from django_assistant_bot_tpu.models import DecoderConfig, held_params, mla_moe
from django_assistant_bot_tpu.serving.registry import ModelRegistry

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(os.path.dirname(HERE), "benchmarks")
SEED = 44_000_017


def _conf():
    with open(os.path.join(HERE, "data", "scmoe_tiny.json")) as f:
        conf = json.load(f)
    conf["hf"].update(n_routed_experts=4, ep_size=4, ep_rank=2)  # a rank's share: experts 8-11 of 16
    return conf


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    conf = _conf()
    family = families.load(conf, DATA)
    cfg = dataclasses.replace(DecoderConfig.from_hf(conf["hf"], dtype=jnp.float32), max_seq_len=256)
    params = jax.tree.map(lambda x: x.astype(jnp.float32), family.served_params(conf, SEED))
    path = str(tmp_path_factory.mktemp("scmoe") / "ckpt")
    save_model(path, "decoder", cfg, params)
    return conf, family, cfg, params, path


def _spec(path, **over):
    spec = dict(kind="decoder", checkpoint=path, dtype="float32", arch="mla_moe", max_slots=2, max_seq_len=128,
                chunk_size=32, kv_page_size=16, kv_pages=16, prefix_cache=0, warmup=False, prefill_piggyback=False)
    spec.update(over)
    return {"m": spec}


def _greedy(family, conf, prompt, n):
    seq, cols = list(prompt), list(range(conf["hf"]["vocab_size"]))
    for _ in range(n):  # the plain reference, one full forward pass per token
        seq.append(int(np.argmax(family.reference_logits(conf, SEED, [seq + [0]], [len(seq) - 1], cols)[0][-1])))
    return seq[len(prompt):]


def test_the_checkpoint_round_trip_keeps_the_config_and_both_stacks(served):
    conf, family, cfg, params, path = served
    kind, cfg2, loaded, _ = load_model(path)
    lm = cfg2.latent_moe
    assert kind == "decoder" and cfg2 == cfg
    assert (lm.double_layer, lm.scoring_func, lm.zero_experts, lm.ep_rank, lm.first_expert) == (True, "softmax", 8, 2, 8)
    assert lm.q_scale == pytest.approx(2 ** 0.5) and lm.kv_scale == 2.0
    own = jax.eval_shape(lambda: mla_moe.init(cfg, jax.random.key(0)))
    a, b = (jax.tree_util.tree_flatten_with_path(t)[0] for t in (own, loaded))
    assert [(k, v.shape) for k, v in a] == [(k, v.shape) for k, v in b]
    assert loaded["dense_layers"]["w_uq"].shape == (4, 32, 96) and loaded["moe_layers"]["w_gate"].shape == (2, 4, 64, 32)
    for stack, leaf in (("dense_layers", "wo"), ("moe_layers", "router_bias"), ("moe_layers", "w_down")):
        np.testing.assert_array_equal(np.asarray(loaded[stack][leaf]), np.asarray(params[stack][leaf]))
    # the one call between loading and placing re-lays out the sublayers' stack and leaves the expert stack alone
    held = held_params(cfg2, loaded)
    assert held["dense_layers"]["w_uk"].shape == (4, 4, 16, 16) and held["moe_layers"]["router"].shape == (2, 64, 24)


@pytest.mark.parametrize("prompt_len", [75, 23])
def test_engine_streams_the_references_greedy_tokens_and_counts_the_identity_picks(served, prompt_len):
    """75 tokens: three chunks of 32 against the cache (the last slides left), then decode; 23: one one-shot
    prefill.  Greedy tokens equal to the reference's at every step: the double layer, both rows of every
    layer of the pool and the rank's share are the reference's."""
    conf, family, cfg, params, path = served
    reg = ModelRegistry.from_config(_spec(path))
    try:
        eng = reg.get_generator("m")
        kv = eng.kv_stats()
        assert kv["kv_cache_kind"] == "latent" and kv["kv_bytes_per_token"] == 4 * 128 * 4  # two rows a layer, float32
        assert eng._kv_pool.page_bytes == 16 * kv["kv_bytes_per_token"]
        assert eng._cache.kv.shape[0] == 4
        prompt = [int(t) for t in np.random.default_rng(prompt_len).integers(32, 127, prompt_len)]
        got = eng.submit(prompt, max_tokens=8, temperature=0.0).result(timeout=600)
        got = list(getattr(got, "token_ids", got))[:8]
        assert got == _greedy(family, conf, prompt, len(got))
        moe = eng.tick_stats()["moe"]
        assert (moe["experts_held"], moe["first_expert"], moe["router_experts"], moe["zero_experts"]) == (4, 8, 16, 8)
        # prefill ran every prompt token once a layer (the sliding last chunk re-feeds 21 of the 75)
        fed = 96 if prompt_len > 32 else prompt_len
        pre, dec = moe["prefill"], moe["decode"]
        assert pre["picks"] == fed * 2 * 4 and sum(pre["real_picks_hist"]) == fed * 2 and len(pre["real_picks_hist"]) == 5
        for kind in (pre, dec):
            assert kind["picks_zero"] == sum((4 - n) * t for n, t in enumerate(kind["real_picks_hist"]))
            assert sum(kind["tokens_per_expert"]) == kind["picks_local"] <= kind["picks"] - kind["picks_zero"]
            assert 0.15 < kind["picks_zero"] / kind["picks"] < 0.5
        assert dec["picks"] >= 7 * 2 * 4 and dec["layer_steps"] * 4 == dec["picks"]
        from django_assistant_bot_tpu.serving.obs import render_prometheus

        text = render_prometheus(reg)
        assert 'dabt_moe_picks_zero_total{' in text and 'dabt_moe_real_picks_tokens_total{' in text
        assert 'real_picks="4"' in text and 'real_picks="5"' not in text and 'kind="decode"' in text
    finally:
        reg.stop()


def test_a_block_without_identity_experts_keeps_its_counters_and_metrics_as_they_were():
    with open(os.path.join(HERE, "data", "mla_moe_tiny.json")) as f:
        conf = json.load(f)
    cfg = dataclasses.replace(DecoderConfig.from_hf(conf["hf"], dtype=jnp.float32), max_seq_len=256)
    assert jax.eval_shape(lambda: mla_moe.init_paged_cache(cfg, 2, 4, 8)).stats.shape == (2, 4 + 16)
    from django_assistant_bot_tpu.serving.engine import GenerationEngine

    eng = GenerationEngine.__new__(GenerationEngine)  # moe_stats reads the config and the totals alone
    eng.cfg, eng._moe_totals = cfg, None
    moe = eng.moe_stats()
    assert "zero_experts" not in moe and "picks_zero" not in moe["decode"] and "real_picks_hist" not in moe["prefill"]


@pytest.mark.parametrize("over,why", [
    ({"speculative": 4}, "tree verification"),
    ({"prefix_cache": 8}, "prefix cache"),
    ({"kv_cache_dtype": "fp8"}, "reduced-precision latent cache"),
    ({"quantize": "int8"}, "int8/int4"),
    ({"arch": "llama"}, "arch"),
])
def test_the_registry_refuses_what_the_block_does_not_implement(served, over, why):
    with pytest.raises(ValueError, match=why):
        ModelRegistry.from_config(_spec(served[4], **over))


def test_the_timing_tool_builds_the_engine_and_runs_the_tick_alone():
    """``tools/time_prefill.py --config <a double-layer configuration> [--decode]``: its engine holds the module's
    form of the tree and a pool of the module's layer count (two rows a layer), and its tick runs over seeded pools."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("time_prefill", os.path.join(os.path.dirname(HERE), "tools", "time_prefill.py"))
    tp = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tp)
    conf = _conf()
    conf["weights"]["seed"] = SEED
    conf["serving"].update(prefill_buckets=[32], prefill_wave=1, prefill_piggyback=False, chunk_size=64)
    eng = tp.build_engine(conf)
    try:
        assert eng._cache.kv.shape[0] == 4 and eng.params["dense_layers"]["w_uk"].shape == (4, 4, 16, 16)
        assert list(eng.prefill_shapes) == [32, 64]  # the named bucket and the chunk
        logits, rows, stats = eng._prefill(eng.params, *tp.full_rows(1, 32))
        assert rows.shape == (4, 1, 32, 128) and int(stats[0]) == 32 * 2 * 4
        toks = tp.decode_program(eng)(3, 64)()
        assert toks.shape == (eng.burst, 4) and int(np.asarray(eng._tick_aux)[0, 0]) == eng.burst * 3 * 2 * 4
    finally:
        eng.stop()
