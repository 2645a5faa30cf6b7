"""The latent-attention MoE block with an indexer on the serving path, on the
CPU: the two-pool checkpoint and cache through ``ModelRegistry`` ->
``GenerationEngine``, a prompt of several chunks streamed as the plain
reference's greedy tokens, the ``dsa`` counters, the page size, ``/metrics``."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import families
from django_assistant_bot_tpu.checkpoint import load_model, save_model
from django_assistant_bot_tpu.models import DecoderConfig, mla_moe
from django_assistant_bot_tpu.serving.registry import ModelRegistry

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(os.path.dirname(HERE), "benchmarks")
SEED = 40_000_017


def _conf():
    with open(os.path.join(HERE, "data", "dsa_moe_tiny.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    conf = _conf()
    family = families.load(conf, DATA)
    cfg = dataclasses.replace(DecoderConfig.from_hf(conf["hf"], dtype=jnp.float32), max_seq_len=256)
    params = jax.tree.map(lambda x: x.astype(jnp.float32), family.served_params(conf, SEED))
    path = str(tmp_path_factory.mktemp("dsa_moe") / "ckpt")
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


def test_the_checkpoint_and_the_family_tree_carry_the_indexer_and_the_bias(served):
    conf, family, cfg, params, path = served
    kind, cfg2, loaded, _ = load_model(path)
    assert kind == "decoder" and cfg2 == cfg and cfg2.latent_moe.index_topk == 8 and cfg2.latent_moe.router_bias
    own = jax.eval_shape(lambda: mla_moe.init(cfg, jax.random.key(0)))
    a, b = (jax.tree_util.tree_flatten_with_path(t)[0] for t in (own, loaded))
    assert [(k, v.shape) for k, v in a] == [(k, v.shape) for k, v in b]
    assert loaded["moe_layers"]["router_bias"].shape == (2, 16) and loaded["dense_layers"]["w_iw"].shape == (1, 64, 4)
    np.testing.assert_array_equal(np.asarray(loaded["moe_layers"]["router_bias"]), np.asarray(params["moe_layers"]["router_bias"]))


@pytest.mark.parametrize("prompt_len", [75, 23])
def test_engine_streams_the_references_greedy_tokens_and_counts_the_pairs(served, prompt_len):
    """75 tokens: three chunks of 32 (the last slides left), then decode over both pools; 23: one
    suffix-free prefill.  Every context past 8 tokens drops keys, so a token equal to the reference's
    is a selection equal to the reference's."""
    conf, family, cfg, params, path = served
    reg = ModelRegistry.from_config(_spec(path))
    try:
        eng = reg.get_generator("m")
        kv = eng.kv_stats()
        assert kv["kv_cache_kind"] == "latent+index" and kv["kv_bytes_per_token"] == 3 * (128 + 16) * 4
        # a page is sized from the module's bytes: 16 tokens of latent row AND index key
        assert eng._kv_pool.page_bytes == 16 * kv["kv_bytes_per_token"]
        prompt = [int(t) for t in np.random.default_rng(prompt_len).integers(32, 127, prompt_len)]
        got = eng.submit(prompt, max_tokens=8, temperature=0.0).result(timeout=600)
        got = list(getattr(got, "token_ids", got))[:8]
        assert got == _greedy(family, conf, prompt, len(got))
        dsa = eng.tick_stats()["dsa"]
        assert dsa["index_topk"] == 8
        kind = "chunk" if prompt_len > 32 else "prefill"
        other = "prefill" if kind == "chunk" else "chunk"
        n = prompt_len
        if kind == "chunk":  # chunks at 0, 32 and 43: positions 43-63 run twice
            starts = [0, 32, n - 32]
            assert dsa[kind]["programs"] == 3 and dsa[kind]["queries"] == 96
            assert dsa[kind]["pairs_causal"] == sum(t + 1 for s in starts for t in range(s, s + 32))
            assert dsa[kind]["pairs_selected"] == sum(min(8, t + 1) for s in starts for t in range(s, s + 32))
        else:
            assert dsa[kind] == {"programs": 1, "queries": n, "pairs_causal": n * (n + 1) // 2,
                                 "pairs_selected": sum(min(8, t + 1) for t in range(n))}
        assert dsa[other] == {"programs": 0, "queries": 0, "pairs_causal": 0, "pairs_selected": 0}
        d = dsa["decode"]
        assert d["programs"] >= 7 and d["queries"] == d["programs"]
        # every step keeps index_topk keys.  The ticks issued behind the one that ends the request take the block
        # table the host holds when each is issued, with the pages or freed (a freed table names no page: nothing
        # is selectable).  So the first tick, which holds the request's 7 steps, keeps every key, and each later
        # tick keeps all of its steps' keys or none: never some
        per_tick = 8 * eng.decode_steps
        assert eng.decode_steps >= 7 and d["queries"] % eng.decode_steps == 0
        assert d["pairs_selected"] % per_tick == 0 and per_tick <= d["pairs_selected"] <= 8 * d["queries"]
        assert d["pairs_causal"] == sum(n + k + 1 for k in range(d["programs"]))
        moe = eng.tick_stats()["moe"]  # the routed layers' counters keep their columns beside the new ones
        assert len(moe["decode"]["tokens_per_expert"]) == 16
        assert sum(moe["decode"]["tokens_per_expert"]) == moe["decode"]["picks_local"]
        from django_assistant_bot_tpu.serving.obs import render_prometheus

        text = render_prometheus(reg)
        assert 'dabt_dsa_pairs_selected_total{' in text and 'kind="chunk"' in text and 'dabt_dsa_index_topk{' in text
    finally:
        reg.stop()


def test_a_block_without_an_indexer_reports_no_dsa_counters():
    with open(os.path.join(HERE, "data", "mla_moe_tiny.json")) as f:
        conf = json.load(f)
    cfg = dataclasses.replace(DecoderConfig.from_hf(conf["hf"], dtype=jnp.float32), max_seq_len=256)
    cache = jax.eval_shape(lambda: mla_moe.init_paged_cache(cfg, 2, 4, 8))
    assert cache.idx is None and cache.stats.shape == (2, 4 + 16)  # the cache, and so every program, as before
    assert mla_moe.kv_kind(cfg) == "latent"


@pytest.mark.parametrize("over,why", [({"speculative": 4}, "tree verification"), ({"prefix_cache": 8}, "second kind of row")])
def test_the_registry_refuses_speculation_and_a_prefix_cache_over_two_kinds_of_row(served, over, why):
    with pytest.raises(ValueError, match=why):
        ModelRegistry.from_config(_spec(served[4], **over))
