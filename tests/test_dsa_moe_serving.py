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
from django_assistant_bot_tpu.ops.attention import select_widths
from django_assistant_bot_tpu.serving.registry import ModelRegistry

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(os.path.dirname(HERE), "benchmarks")
SEED = 40_000_017


def _conf(**hf):
    with open(os.path.join(HERE, "data", "dsa_moe_tiny.json")) as f:
        conf = json.load(f)
    conf["hf"].update(hf)
    return conf


def _scanned(view, topk, queries, lives):
    """What the chunk and prefill programs report as ``pairs_scanned``: ``queries`` x the first step of the
    view (``select_widths``; the CPU's steps are multiples of 8) at or past a program's last live key, and
    nothing for a program whose keys are all within ``topk``."""
    widths = select_widths(view, topk, 8)
    return sum(queries * min(w for w in widths if w >= live) for live in lives if live > topk)


def _serve(tmp_path_factory, conf):
    family = families.load(conf, DATA)
    cfg = dataclasses.replace(DecoderConfig.from_hf(conf["hf"], dtype=jnp.float32), max_seq_len=256)
    params = jax.tree.map(lambda x: x.astype(jnp.float32), family.served_params(conf, SEED))
    path = str(tmp_path_factory.mktemp("dsa_moe") / "ckpt")
    save_model(path, "decoder", cfg, params)
    return conf, family, cfg, params, path


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    return _serve(tmp_path_factory, _conf())


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
            # every chunk holds more than index_topk keys: 32 queries x the step of the 128-position view it reaches
            assert dsa[kind]["pairs_scanned"] == _scanned(128, 8, 32, [s + 32 for s in starts]) == 32 * (32 + 64 + 96)
        else:
            bucket = min(b for b in eng.prefill_shapes if b >= n)  # one row of the bucket the prompt fits
            assert dsa[kind] == {"programs": 1, "queries": n, "pairs_causal": n * (n + 1) // 2,
                                 "pairs_selected": sum(min(8, t + 1) for t in range(n)),
                                 "pairs_scanned": _scanned(bucket, 8, bucket, [n])}
        assert dsa[other] == {"programs": 0, "queries": 0, "pairs_causal": 0, "pairs_selected": 0, "pairs_scanned": 0}
        d = dsa["decode"]
        assert d["programs"] >= 7 and d["queries"] == d["programs"]
        assert d["pairs_scanned"] == 128 * d["queries"]  # a step's selection counts over an active row's whole view
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
        for k in ("decode", "chunk", "prefill"):
            line = next(ln for ln in text.splitlines() if ln.startswith("dabt_dsa_pairs_scanned_total{") and f'kind="{k}"' in ln)
            assert float(line.rsplit(" ", 1)[1]) == dsa[k]["pairs_scanned"]
    finally:
        reg.stop()


def test_a_chunk_whose_keys_are_all_kept_scans_nothing_and_the_later_ones_scan_their_step(tmp_path_factory):
    """``index_topk`` 40 over chunks of 32: the first chunk (32 keys) keeps every causal key without a score or a
    count, the second and third (64 and 75 live keys) count over 64 and 80 of the view's 128 positions; the
    tokens are the reference's, so the stepped selection kept the reference's keys."""
    conf, family, cfg, params, path = _serve(tmp_path_factory, _conf(index_topk=40))
    reg = ModelRegistry.from_config(_spec(path))
    try:
        eng = reg.get_generator("m")
        prompt = [int(t) for t in np.random.default_rng(75).integers(32, 127, 75)]
        got = eng.submit(prompt, max_tokens=4, temperature=0.0).result(timeout=600)
        got = list(getattr(got, "token_ids", got))[:4]
        assert got == _greedy(family, conf, prompt, len(got))
        dsa = eng.tick_stats()["dsa"]
        starts = [0, 32, 43]
        assert select_widths(128, 40, 8) == (48, 64, 80, 96, 112, 128)
        assert dsa["chunk"]["programs"] == 3 and dsa["chunk"]["queries"] == 96
        assert dsa["chunk"]["pairs_scanned"] == _scanned(128, 40, 32, [s + 32 for s in starts]) == 32 * (0 + 64 + 80)
        assert dsa["chunk"]["pairs_selected"] == sum(min(40, t + 1) for s in starts for t in range(s, s + 32))
        d = dsa["decode"]  # whole ticks only: the ticks behind the one that ends the request still count their steps
        assert d["queries"] % eng.decode_steps == 0 and d["pairs_scanned"] == 128 * d["queries"]
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


def test_the_timing_tool_runs_a_chunk_program_alone_at_any_start():
    """``tools/time_prefill.py --config <a configuration with an indexer> --chunk``: the engine's own chunk program
    over seeded pools, at a start whose keys are all within ``index_topk`` and at two past it; the donated cache
    comes back each call, and its counters say which step of the view each chunk's selection reached."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("time_prefill", os.path.join(os.path.dirname(HERE), "tools", "time_prefill.py"))
    tp = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tp)
    conf = _conf(index_topk=40)
    conf["weights"]["seed"] = SEED
    conf["serving"].update(max_seq_len=128, chunk_size=32, kv_page_size=16, kv_pages=16, prefill_piggyback=False, warmup=False)
    eng = tp.build_engine(conf)
    try:
        program = tp.chunk_program(eng)
        for start in (0, 32, 96):
            logits = program(1, start)()
            assert logits.shape == (1, conf["hf"]["vocab_size"]) and np.isfinite(np.asarray(logits)).all()
        with pytest.raises(ValueError, match="one row"):
            program(1, 128)
        programs, queries, _, _, scanned = (int(x) for x in program.cache().stats[1, -10:-5])
        assert (programs, queries, scanned) == (3, 96, _scanned(128, 40, 32, [32, 64, 128])) and scanned == 32 * (64 + 128)
    finally:
        eng.stop()
