"""The benchmark's side of the ``dsa_moe`` family (``benchmarks/families/dsa_moe.py``,
``benchmarks/reference/dsa_moe.py``, the configuration, the eight readers): the configuration is the
catalog's but for what ``reduced`` lists, the family takes nothing of the program, every new reader
gives a number where a 3 s trace holds no submit and nothing on a program without the counters, and a
rehearsal of the whole command on the CPU in the cell's shape (closed loop, every prompt several
chunks and several times the selection) comes out ``correct``, and not ``correct`` with the selection
switched off or float8 weights in the reference's place."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DATA = os.path.join(ROOT, "benchmarks")
CELL = "deepseek-v3.2-ep16-longrag-saturated"
READERS = ("prefill_chunk_dev_ms", "prefill_chunk_mfu", "dsa_dev_share", "dsa_index_roofline",
           "dsa_sparse_attn_roofline", "dsa_decode_roofline", "dsa_select_ms_per_kquery", "dsa_selected_share")

# deepseek-ai/DeepSeek-V3.2's config.json as the catalog beside the model-configs guide has it, copied here
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3, "hidden_act": "silu", "hidden_size": 7168,
    "index_head_dim": 128, "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 163840, "model_type": "deepseek_v32", "moe_intermediate_size": 2048, "moe_layer_freq": 1,
    "n_group": 8, "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 61, "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096, "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 4, "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280,
}
REDUCED = {"ep_size": 16, "n_routed_experts": 16, "vocab_size": 16160, "first_k_dense_replace": 1, "num_hidden_layers": 5}


def _cell_conf():
    with open(os.path.join(DATA, "configs", "deepseek-v3.2-ep16.json")) as f:
        return json.load(f)


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_configuration_is_the_catalogs_but_for_what_reduced_lists():
    conf = _cell_conf()
    hf = conf["hf"]
    assert sorted(conf["reduced"]) == sorted(REDUCED) == sorted(conf["published"].keys() - {"why"})
    assert {k: v for k, v in hf.items() if k not in REDUCED and k != "ep_rank"} == {k: v for k, v in CATALOG.items() if k not in REDUCED}
    assert {k: hf[k] for k in REDUCED} == REDUCED and hf["ep_rank"] == 0
    assert {k: conf["published"][k] for k in REDUCED} == {k: CATALOG[k] for k in REDUCED}
    assert {k: conf[k] for k in hf if k != "ep_rank"} == {k: v for k, v in hf.items() if k != "ep_rank"}  # the top-level copy the driver compares
    assert set(conf["reduced_why"]) == set(REDUCED) and {"indexer", "mtp", "router_bias", "ep_rank"} <= set(conf["assumed"])
    entry = {c["name"]: c for c in _bench()["configs"]}["deepseek-v3.2-ep16"]
    assert entry["reduced"] == conf["reduced"] and entry["source"] == conf["source"] == "https://huggingface.co/deepseek-ai/DeepSeek-V3.2/blob/main/config.json"
    import jax.numpy as jnp

    from django_assistant_bot_tpu.models import DecoderConfig, mla_moe

    cfg = DecoderConfig.from_hf(hf, dtype=jnp.bfloat16)
    lm = cfg.latent_moe
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_layers, cfg.vocab_size, cfg.experts_per_token) == (7168, 128, 5, 16160, 8)
    assert (lm.router_experts, lm.experts_held, lm.first_dense_layers, lm.n_group, lm.topk_group) == (256, 16, 1, 8, 4)
    assert (lm.index_n_heads, lm.index_head_dim, lm.index_topk, lm.router_bias, lm.latent_width) == (64, 128, 2048, True, 640)
    assert mla_moe.kv_bytes_per_token(cfg) == 5 * 1536  # 1,280 B of latent row and 256 B of index key, a layer
    s = conf["serving"]
    assert (s["max_slots"], s["max_seq_len"], s["chunk_size"], s["kv_page_size"], s["kv_pages"]) == (8, 16384, 1024, 512, 256)
    assert s["prefill_piggyback"] is False and s["prefix_cache"] == 0 and isinstance(conf["weights"]["seed"], int)


def test_the_cell_joins_the_metrics_the_issue_names_and_no_other():
    from benchmarks import run

    bench = _bench()
    names = [m["name"] for m in run.metrics_for(bench, "per_layer", CELL)]
    assert len(names) == 24 and set(READERS) <= set(names) and "decode_step_dev_ms" in names and "stream_lag_ms_p99" in names
    # PR 42's readers of the device-queue ledger: the five this cell lists (its tick rides behind a chunk, so no
    # `decode_step_ms_window`; it runs no one-shot prefill, so no `prefill_ms_per_ktok_window`)
    assert names[-5:] == ["prefill_dev_share_window", "prefill_chunk_ms_window", "prefill_start_lag_ms",
                          "device_queue_idle_share", "device_queue_observed_share"]
    assert not {"prefill_dev_ms_per_ktok", "mla_decode_roofline", "mla_moe_decode_step_roofline", "moe_experts_roofline"} & set(names)
    # `out_tok_per_s` is not this cell's: 96 tokens x the requests a 51 s window happens to prefill (40-44 of 4k-14k
    # tokens) spread 6.6% over six seeds against the 2% that admits a metric (PERF.md section 6), so the cell is judged
    # on the time per token, which every new reader moves (a chunk and a fused tick alternate)
    assert [m["name"] for m in run.metrics_for(bench, "end_to_end", CELL)] == ["tpot_p50_ms", "setup_s"]
    for m in bench["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_p50_ms"
            assert os.path.isfile(os.path.join(DATA, "layer_metrics", m["name"] + ".py"))
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("deepseek-v3.2-ep16", "longrag-saturated", 1)
    mix = json.load(open(os.path.join(DATA, "traffic", "longrag-saturated.json")))
    assert mix["arrival"] == {"kind": "closed", "clients": 12, "cycle": 36}
    assert mix["prompt_tokens"] == {"dist": "loguniform", "lo": 4096, "hi": 14336}
    assert mix["output_tokens"] == {"dist": "uniform", "lo": 64, "hi": 128}
    from benchmarks.traffic_gen import Plan

    plan = Plan(mix, 2**31 + 5, 51)
    assert plan.longest_total() <= 16384 - 1
    lengths = sorted(len(t.prompt_ids) for c in plan.one_cycle() for t in c.turns)
    assert lengths[0] > 2 * 2048 and 8000 < sum(lengths) / 36 < 8400  # every context at least twice index_topk


def test_the_family_and_its_reference_import_nothing_of_the_program_and_no_jax_at_load():
    for rel in ("families/dsa_moe.py", "reference/dsa_moe.py"):
        with open(os.path.join(DATA, rel)) as f:
            assert "django_assistant_bot_tpu" not in f.read()
    code = ("import sys, json; sys.path.insert(0, %r)\n"
            "from benchmarks import run\n"
            "conf = json.load(open(%r))\n"
            "fam = run.load_family(conf, %r)\n"
            "assert 'jax' not in sys.modules and 'django_assistant_bot_tpu' not in sys.modules\n"
            "print(sorted(fam.LIMITS), fam.CONTROLS)\n") % (ROOT, os.path.join(DATA, "configs", "deepseek-v3.2-ep16.json"), DATA)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "logit_gap_p99" in out.stdout and "logit_gap_mean" in out.stdout
    assert "('w_fp8', 'dense', 'idx_fp8')" in out.stdout


def test_the_familys_counts_are_the_issues():
    from benchmarks import families

    conf = _cell_conf()
    f = families.load(conf, DATA)
    assert f.index_key_bytes(conf) == 256 and f.latent_row_bytes(conf) == 1152
    assert f.pair_flops(conf) == {"index": 2.0 * 64 * 128, "attention": 2.0 * 128 * 320}
    w = f.weight_bytes(conf)
    assert w["indexer"] == 5 * 2 * (1536 * 8192 + 7168 * 128 + 7168 * 64)  # 14.0 M parameters a layer
    assert w["experts"] == 4 * 16 * 3 * 7168 * 2048 * 2
    total = sum(w.values())
    assert 8.7e9 < total < 9.1e9  # 9.27 GB resident less the embedding's 0.23 GB (gathered, not read) and the biases
    # a token's projections and feed-forward: 187.1 + 14.0 M parameters of attention and indexer in 5 layers,
    # 396.4 M of dense SwiGLU, 4 x (1.8 + 44.0) M of router and shared expert; x 2
    per_token = f.token_flops(conf, 0.0)
    assert per_token == pytest.approx(2 * (5 * 201.1e6 + 396.4e6 + 4 * 45.9e6), rel=0.01)
    one = f.prefill_chunk_flops(conf, 1024, 1024 * 8192, 1024 * 2048, 1024 * 4 * 0.5)
    assert one == pytest.approx(1024 * per_token + 2048 * 3 * 7168 * 2048 * 2
                                + 5 * (16384 * 1024 * 8192 + 81920 * 1024 * 2048))
    assert 4.0e12 < one < 5.0e12  # about 4.4 TFLOP a chunk at a context of 8k
    live, sel = 8 * 9000, 8 * 2048
    assert f.decode_step_bytes(conf, live, selected_tokens=sel) - f.decode_step_bytes(conf, 0, selected_tokens=0) == 5 * (256 * live + 1152 * sel)
    assert f.decode_step_flops(conf, 8, live, selected_tokens=sel) > 8 * per_token


def _ctx(conf, family, stats0, stats1, trace):
    from benchmarks import roofline, run

    ctx = {"conf": conf, "family": family, "roofline": roofline, "device": {"kind": "TPU v5 lite"}, "trace": trace,
           "trace_span": (40.0, 43.0), "c0": {"tick_stats": stats0}, "c1": {"tick_stats": stats1, "decode_steps": 1},
           # the window's requests were all submitted before the traced span and finish after it
           "events": [{"prompt_len": 9000, "submit": 12.0 + i, "due": 12.0 + i, "measured": True,
                       "times": [30.0 + 0.1 * k for k in range(200)]} for i in range(8)]}
    ctx["read"] = lambda name: run.read_layer_metric(name, ctx, os.path.join(DATA, "layer_metrics"))
    return ctx


def test_every_new_reader_gives_a_number_where_the_traced_span_holds_no_submit():
    from benchmarks import families

    conf = _cell_conf()
    family = families.load(conf, DATA)

    def stats(n):  # n windows' worth: 400 chunk programs at a mean context of 6k, 300 decode steps of 4 rows
        k = lambda q, c, s, p: {"programs": p * n, "queries": q * n, "pairs_causal": c * n, "pairs_selected": s * n}  # noqa: E731
        moe = lambda m: {"picks": 8 * m * n, "picks_local": m * n // 2, "layer_steps": 4 * n, "experts_hit": 40 * n,  # noqa: E731
                         "tokens_per_expert": [m * n // 32] * 16}
        return {"dsa": {"index_topk": 2048, "decode": k(1200, 1200 * 9000, 1200 * 2048, 300),
                        "chunk": k(400 * 1024, 400 * 1024 * 6000, 400 * 1024 * 2048, 400),
                        "prefill": k(0, 0, 0, 0)},
                "moe": {"decode": moe(1200 * 4), "prefill": moe(400 * 1024 * 4)}}

    chunk, tick = "jit(_prefill_chunk_paged)/while/body/closed_call/", "jit(tick)/while/body/closed_call/"
    trace = {"program_runs": {"jit__prefill_chunk_paged": 24, "jit_tick": 24}, "program_s": {"jit__prefill_chunk_paged": 2.4, "jit_tick": 0.48},
             "op_s": {}, "scope_s": {
                 chunk + "attn/index_score/index_scores/pallas_call:": 0.30, chunk + "attn/select/while/body/reduce_sum:": 0.40,
                 chunk + "attn/sparse_core/masked_flash_attention/pallas_call:": 0.70, chunk + "attn/index_q/dot_general:": 0.05,
                 chunk + "attn/index_k/dot_general:": 0.01, chunk + "attn/kv_up/dot_general:": 0.30, chunk + "ffn/gate_up/dot_general:": 0.64,
                 tick + "attn/index_score/dot_general:": 0.02, tick + "attn/select/top_k:": 0.10, tick + "attn/sparse_core/gather:": 0.04,
                 tick + "moe/experts/pallas_call:": 0.32, "jit(_prefill)/attn/index_score/x:": 5.0}}
    ctx = _ctx(conf, family, stats(1), stats(2), trace)
    got = {name: ctx["read"](name) for name in READERS}
    assert all(isinstance(v, float) and v > 0 for v in got.values()), got
    assert got["prefill_chunk_dev_ms"] == pytest.approx(100.0)
    flops = family.prefill_chunk_flops(conf, 1024, 1024 * 6000, 1024 * 2048, 1024 * 4 / 2)
    assert got["prefill_chunk_mfu"] == pytest.approx(100 * flops * 24 / 2.4 / 197e12)
    assert got["dsa_dev_share"] == pytest.approx(100 * (1.46 + 0.16) / (2.40 + 0.48))
    assert got["dsa_index_roofline"] == pytest.approx(100 * 16384 * 1024 * 6000 * 5 * 24 / 0.30 / 197e12)
    assert got["dsa_sparse_attn_roofline"] == pytest.approx(100 * 81920 * 1024 * 2048 * 5 * 24 / 0.70 / 197e12)
    assert got["dsa_decode_roofline"] == pytest.approx(100 * 5 * (256 * 36000 + 1152 * 8192) * 24 / 819e9 / 0.16)
    assert got["dsa_select_ms_per_kquery"] == pytest.approx(0.50 * 1e3 / (1024 * 24 + 4 * 24) * 1000)
    assert got["dsa_selected_share"] == pytest.approx(100 * (1200 * 2048 + 400 * 1024 * 2048) / (1200 * 9000 + 400 * 1024 * 6000))
    assert all(got[n] < 100 for n in READERS if "roofline" in n or "mfu" in n)


def test_the_new_readers_return_nothing_on_a_program_without_the_counters():
    """The parent commit and the other families: no ``dsa`` block in tick_stats, no indexer's scopes.
    The line then leaves the metric out; it does not raise."""
    from benchmarks import families

    trace = {"program_runs": {"jit_tick": 30}, "program_s": {"jit_tick": 2.9}, "op_s": {"fusion.1": 1.0},
             "scope_s": {"jit(tick)/while/body/ffn/gate_up/dot_general:": 2.0, "jit(tick)/attn/absorb/x:": 0.5}}
    for name in ("deepseek-v3.2-ep16", "a.x-k1-ep16", "qwen2.5-7b-instruct"):
        conf = json.load(open(os.path.join(DATA, "configs", name + ".json")))
        ctx = _ctx(conf, families.load(conf, DATA), {}, {}, trace)
        for reader in READERS:
            assert ctx["read"](reader) is None, (name, reader)
        ctx["trace"] = None  # an untraced run
        assert all(ctx["read"](r) is None for r in READERS if r != "dsa_selected_share")


TINY_MIX = {"why": "rehearsal only: the cell's shape at the tiny size", "who": "the tests",
            "arrival": {"kind": "closed", "clients": 3, "cycle": 6},
            "prompt_tokens": {"dist": "loguniform", "lo": 40, "hi": 150}, "output_tokens": {"dist": "uniform", "lo": 8, "hi": 16},
            "warm_s": 1.0, "finish_cap_s": 120.0, "check_requests": 3}


@pytest.fixture(scope="module")
def rehearsal_root(tmp_path_factory):
    """The benchmark's data directories as they are, plus the tests' tiny configuration of the family as a
    rank's share (4 of 16 experts held) under the cell's kind of traffic: closed loop, every prompt longer
    than a chunk (32) and several times the selection (8), piggyback off."""
    root = tmp_path_factory.mktemp("dsa_moe_rehearsal")
    data = root / "benchmarks"
    for sub in ("configs", "traffic", "layer_metrics", "families"):
        shutil.copytree(os.path.join(DATA, sub), data / sub)
    json.dump(TINY_MIX, open(data / "traffic" / "tiny-longrag.json", "w"))
    bench = json.load(open(os.path.join(DATA, "tests", "rehearsal.json")))
    conf = json.load(open(os.path.join(HERE, "data", "dsa_moe_tiny.json")))
    conf["hf"].update(n_routed_experts=4, ep_size=4, ep_rank=1)
    conf["serving"].update(max_slots=2, chunk_size=32, kv_page_size=16, kv_pages=32, prefill_buckets=[32],
                           prefill_wave=1, prefill_piggyback=False)
    json.dump(conf, open(data / "configs" / "dsa-moe-tiny.json", "w"))
    bench["configs"].append({"name": "dsa-moe-tiny", "source": "none", "why": "test", "reduced": [],
                             "file": "benchmarks/configs/dsa-moe-tiny.json"})
    bench["workloads"].append({"name": "dsa-moe-tiny.longrag", "config": "dsa-moe-tiny", "traffic": "tiny-longrag",
                               "chips": 1, "why": "test"})
    units = {m["name"]: m for m in _bench()["per_layer"]}
    for name in READERS:
        bench["per_layer"].append(dict(units[name], workloads=["dsa-moe-tiny.longrag"]))
    json.dump(bench, open(root / "BENCHMARK.json", "w"))
    return root


def _run(capsys, root, *argv):
    from benchmarks import run

    capsys.readouterr()
    assert run.main(["--benchmark-json", str(root / "BENCHMARK.json"), "--data-root", str(root), *argv]) == 0
    lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    return json.loads(lines[-2]), json.loads(lines[-1])


def test_rehearsal_in_the_cells_shape_is_correct_and_the_dense_and_weights_controls_are_not(capsys, rehearsal_root):
    from benchmarks import run

    diag, res = _run(capsys, rehearsal_root, "--workload", "dsa-moe-tiny.longrag", "--seed", str(2**31 + 40),
                     "--seconds", "6", "--trace", "1", "--rehearsal", "--controls")
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 2, diag["compared"]
    assert diag["compiles_in_window"] == 0 and diag["early_stops"] == 0 and diag["engine_restarts"] == 0
    assert diag["compared"]["prompt_mismatches"] == [0, 0] and diag["prefill_chunks_piggybacked"] == 0
    # the float32 rehearsal agrees with the reference to rounding.  The block without its selection
    # (``dense``) and float8 weights in the reference's place fail both of the family's limits; float8
    # index keys select other keys now and then and show against a float32 program
    for number in ("p99", "mean"):
        gap, limit = diag["compared"][f"logit_gap_{number}"]
        dense, w_fp8, idx_fp8 = (diag["compared"][f"control_{c}_gap_{number}"][0] for c in ("dense", "w_fp8", "idx_fp8"))
        assert gap < 0.05 * limit and limit < dense and limit < w_fp8 and gap < idx_fp8, (number, gap, limit, dense, w_fp8, idx_fp8)
    assert diag["compared"]["logit_gap_max"][1] is None
    # every name the cell lists is a reader the harness finds; the counters reached them over the side channel
    bench = json.load(open(rehearsal_root / "BENCHMARK.json"))
    listed = [m["name"] for m in run.metrics_for(bench, "per_layer", "dsa-moe-tiny.longrag")]
    assert set(READERS) <= set(listed)
    share = res["metrics"]["dsa_selected_share"]["value"]
    assert 5.0 < share < 35.0  # 8 of a context of 40-170
    counts = diag["counter_metrics"]
    assert counts["dsa_selected_share"] == pytest.approx(share) and counts["dsa_chunk_programs"] >= 4
    assert counts["dsa_chunk_selected_share"] < 40.0 and counts["dsa_decode_selected_share"] < 25.0
    assert "dsa_prefill_programs" not in counts  # every prompt is longer than a chunk: only chunk programs prefill
    # device metrics come from a chip's trace alone: a CPU trace has no programs and no scopes to read
    assert not [n for n in READERS if n != "dsa_selected_share" and n in res["metrics"]]
