"""The benchmark's side of the ``scmoe`` family (``benchmarks/families/scmoe.py``,
``benchmarks/reference/scmoe.py``, the configuration, the cell, the four readers): the configuration is
the catalog's but for what ``reduced`` lists, the family takes nothing of the program, its byte counts
are the issue's, every new reader gives a number where a 3 s trace holds no submit and nothing on a
program without the counters, and a rehearsal of the whole command on the CPU in the cell's shape
(closed loop, every prompt one prefill program, piggyback off) comes out ``correct``, and not
``correct`` under each of the three controls."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DATA = os.path.join(ROOT, "benchmarks")
CONFIG, CELL = "longcat-flash-omni-ep32", "longcat-flash-omni-ep32-decode-saturated"
READERS = ("scmoe_decode_step_roofline", "scmoe_experts_roofline", "scmoe_zero_pick_share", "scmoe_real_picks_p90")
APPENDED = ("decode_step_dev_ms", "decode_step_ms_window", "prefill_dev_share_window", "prefill_ms_per_ktok_window",
            "prefill_start_lag_ms", "device_queue_idle_share", "device_queue_observed_share", "moe_dev_share",
            "mla_dev_share", "moe_local_pick_share", "moe_load_imbalance")

# meituan-longcat/LongCat-Flash-Omni's config.json as the catalog beside the model-configs guide has it, copied here
CATALOG = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144, "ffn_hidden_size": 12288,
    "expert_ffn_hidden_size": 2048, "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512, "q_lora_rank": 1536,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128, "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "routed_scaling_factor": 6, "n_routed_experts": 512, "max_position_embeddings": 131072, "rms_norm_eps": 1e-05,
    "rope_theta": 10000000, "attention_method": "MLA", "zero_expert_num": 256, "zero_expert_type": "identity", "moe_topk": 12,
}
REDUCED = {"num_layers": 4, "n_routed_experts": 16, "vocab_size": 16384, "ep_size": 32}
ADDED = {"model_type": "longcat_flash", "ep_rank": 0}  # not the catalog's: listed under `assumed`


def _cell_conf():
    with open(os.path.join(DATA, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_configuration_is_the_catalogs_but_for_what_reduced_lists():
    conf = _cell_conf()
    hf = conf["hf"]
    assert sorted(conf["reduced"]) == sorted(REDUCED) == sorted(conf["published"].keys() - {"why"})
    assert {k: v for k, v in hf.items() if k not in REDUCED and k not in ADDED} == {k: v for k, v in CATALOG.items() if k not in REDUCED}
    assert {k: hf[k] for k in REDUCED} == REDUCED and {k: hf[k] for k in ADDED} == ADDED
    assert {k: conf["published"][k] for k in REDUCED} == {**{k: CATALOG[k] for k in REDUCED if k != "ep_size"}, "ep_size": 1}
    assert {k: conf[k] for k in hf if k != "ep_rank"} == {k: v for k, v in hf.items() if k != "ep_rank"}  # the top-level copy the driver compares
    assert set(conf["reduced_why"]) == set(REDUCED)
    assert {"longcat_flash", "ep_rank", "router_bias", "router_draw", "up_projection_draw", "tokenizer", "head", "rotary_layout"} <= set(conf["assumed"])
    assert conf["family"] == "scmoe" and "32 chips share each layer" in conf["deployment"]
    entry = {c["name"]: c for c in _bench()["configs"]}[CONFIG]
    assert entry["reduced"] == conf["reduced"] and entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["source"] == conf["source"] == "https://huggingface.co/meituan-longcat/LongCat-Flash-Omni/blob/main/config.json"
    import jax.numpy as jnp

    from django_assistant_bot_tpu.models import DecoderConfig, mla_moe

    cfg = DecoderConfig.from_hf(hf, dtype=jnp.bfloat16)
    lm = cfg.latent_moe
    # every published width unchanged
    assert (cfg.hidden_size, cfg.num_heads, cfg.intermediate_size, cfg.experts_per_token, cfg.rope_theta) == (6144, 64, 12288, 12, 1e7)
    assert (lm.qk_nope_head_dim, lm.qk_rope_head_dim, lm.v_head_dim, lm.q_lora_rank, lm.kv_lora_rank) == (128, 64, 128, 1536, 512)
    assert (lm.moe_intermediate_size, lm.router_experts, lm.zero_experts, lm.router_width, lm.routed_scaling_factor) == (2048, 512, 256, 768, 6.0)
    assert (cfg.num_layers, cfg.vocab_size, lm.experts_held, lm.first_expert, lm.ep_size) == (4, 16384, 16, 0, 32)
    assert lm.q_scale == 2.0 and lm.kv_scale == pytest.approx(12 ** 0.5) and lm.latent_width == 640
    assert mla_moe.kv_bytes_per_token(cfg) == 10_240  # 8 attention sublayers x 640 lanes x 2 B
    s = conf["serving"]
    assert (s["max_slots"], s["max_seq_len"], s["chunk_size"], s["kv_page_size"], s["kv_pages"]) == (32, 2048, 1024, 512, 128)
    assert s["prefill_buckets"] == [512, 1024] and s["prefill_wave"] == 1 and s["prefill_piggyback"] is False
    assert s["prefix_cache"] == 0 and s["arch"] == cfg.arch and isinstance(conf["weights"]["seed"], int)
    ax = json.load(open(os.path.join(DATA, "configs", "a.x-k1-ep16.json")))["serving"]
    assert {k: v for k, v in s.items() if k != "why"} == {k: v for k, v in ax.items() if k != "why"}  # the two cells differ in the block alone


def test_the_cell_joins_the_metrics_the_issue_names_and_no_other():
    from benchmarks import run

    bench = _bench()
    assert len(bench["workloads"]) == 5 and not [w for w in bench["workloads"] if w["chips"] != 1]
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "answers-saturated", 1) and len(cell["why"]) <= 200
    names = [m["name"] for m in run.metrics_for(bench, "per_layer", CELL)]
    assert set(READERS) <= set(names) and set(APPENDED) <= set(names) and names[-4:] == list(READERS)
    # readers of other families' keys (`num_hidden_layers`, `first_k_dense_replace`) and the one 3 s of trace can leave empty
    assert not {"prefill_dev_ms_per_ktok", "mla_decode_roofline", "mla_moe_decode_step_roofline", "moe_experts_roofline",
                "decode_step_roofline"} & set(names)
    e2e = [m["name"] for m in run.metrics_for(bench, "end_to_end", CELL)]
    assert e2e == ["tpot_p50_ms", "out_tok_per_s", "setup_s"]  # six untraced runs spread 0.86% in tokens/s (under the 2% that admits it)
    for m in bench["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_p50_ms" and set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
            assert os.path.isfile(os.path.join(DATA, "layer_metrics", m["name"] + ".py"))
        if m["name"] in APPENDED:
            assert m["workloads"][-1] == CELL and "a.x-k1-ep16-decode-saturated" in m["workloads"]
    mix = json.load(open(os.path.join(DATA, "traffic", "answers-saturated.json")))
    assert mix["arrival"] == {"kind": "closed", "clients": 64, "cycle": 192} and mix["check_requests"] == 6
    from benchmarks.traffic_gen import Plan

    assert Plan(mix, 2**31 + 5, 51).longest_total() <= 2048 - 1


def test_the_family_and_its_reference_import_nothing_of_the_program_and_no_jax_at_load():
    for rel in ("families/scmoe.py", "reference/scmoe.py"):
        with open(os.path.join(DATA, rel)) as f:
            assert "django_assistant_bot_tpu" not in f.read()
    code = ("import sys, json; sys.path.insert(0, %r)\n"
            "from benchmarks import run\n"
            "conf = json.load(open(%r))\n"
            "fam = run.load_family(conf, %r)\n"
            "assert 'jax' not in sys.modules and 'django_assistant_bot_tpu' not in sys.modules\n"
            "print(sorted(fam.LIMITS), fam.CONTROLS)\n") % (ROOT, os.path.join(DATA, "configs", CONFIG + ".json"), DATA)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "logit_gap_p99" in out.stdout and "logit_gap_mean" in out.stdout
    assert "('w_fp8', 'no_zero', 'no_scale')" in out.stdout


def test_the_familys_counts_are_the_issues():
    from benchmarks import families

    conf = _cell_conf()
    f = families.load(conf, DATA)
    assert f.expert_bytes(conf) == 2 * 37_748_736 and f.latent_row_bytes(conf) == 1152 and f.attention_sublayers(conf) == 8
    w = f.weight_bytes(conf)
    # one latent-attention sublayer is 90.57 M parameters, a dense FFN 226.5 M, the router 4.72 M: 638.9 M a layer outside its experts
    assert w["attention"] == pytest.approx(8 * 2 * 90.57e6, rel=2e-3) and w["dense_ffn"] == 8 * 2 * 3 * 6144 * 12288
    assert w["router"] == 4 * 2 * (6144 * 768 + 768) and w["experts"] == 4 * 16 * 2 * 3 * 6144 * 2048
    assert w["head"] == pytest.approx(2 * 100.7e6, rel=1e-3)
    assert sum(w.values()) - w["experts"] == pytest.approx(5.31e9, rel=5e-3)  # whatever the routing
    assert sum(w.values()) == pytest.approx(10.35e9 - 0.20e9, rel=5e-3)  # the weights resident less the embedding (gathered, not read)
    hit = f.weight_bytes(conf, 6.4)
    assert hit["experts"] == pytest.approx(1.93e9, rel=5e-3) and {k: v for k, v in hit.items() if k != "experts"} == {k: v for k, v in w.items() if k != "experts"}
    live = 32 * 800
    assert f.decode_step_bytes(conf, live, 6.4) - f.decode_step_bytes(conf, 0, 6.4) == 8 * 1152 * live  # two rows a layer
    flops = f.decode_step_flops(conf, 32, live)
    per_row = 8 * (90.57e6 + 226.5e6) + 4 * (4.72e6 + 12 * 16 / 768 * 37.75e6) + 100.7e6
    assert flops == pytest.approx(2 * 32 * per_row + 2 * 8 * 64 * 1088 * live, rel=2e-3)
    assert f.real_picks_quantile([0, 0, 5, 10, 60, 20, 5], 0.9) == 5.0 and f.real_picks_quantile([0] * 13, 0.9) is None


def _ctx(conf, family, stats0, stats1, trace):
    from benchmarks import roofline, run

    ctx = {"conf": conf, "family": family, "roofline": roofline, "device": {"kind": "TPU v5 lite"}, "trace": trace,
           "trace_span": (40.0, 43.0), "c0": {"tick_stats": stats0}, "c1": {"tick_stats": stats1, "decode_steps": 8},
           # the window's requests were all submitted before the traced span and finish after it
           "events": [{"prompt_len": 600, "submit": 12.0 + 0.1 * i, "due": 12.0 + 0.1 * i, "measured": True,
                       "times": [30.0 + 0.1 * k for k in range(200)]} for i in range(32)]}
    ctx["read"] = lambda name: run.read_layer_metric(name, ctx, os.path.join(DATA, "layer_metrics"))
    return ctx


def _stats(n):
    """n windows' worth of the program's counters: 3,000 decode steps of 32 rows and 100 prompts of 600 tokens."""
    def moe(tokens, steps):
        hist = [0, 0, 0, 0, 0, tokens // 16, tokens // 8, tokens // 4, tokens // 4, tokens // 8, tokens // 8, tokens // 16, 0]
        real = sum(k * t for k, t in enumerate(hist))
        return {"picks": 12 * tokens * n, "picks_local": real * n // 32, "layer_steps": 4 * steps * n, "experts_hit": 26 * steps * n,
                "tokens_per_expert": [real * n // 512] * 16, "picks_zero": (12 * tokens - real) * n,
                "real_picks_hist": [t * n for t in hist], "experts_skipped_share": 0.6}
    return {"moe": {"experts_held": 16, "zero_experts": 256, "decode": moe(3000 * 32 * 4, 3000), "prefill": moe(100 * 600 * 4, 100)}}


def test_every_new_reader_gives_a_number_where_the_traced_span_holds_no_submit():
    from benchmarks import families

    conf = _cell_conf()
    family = families.load(conf, DATA)
    tick = "jit(tick)/while/body/closed_call/while/body/closed_call/"
    trace = {"program_runs": {"jit_tick": 30}, "program_s": {"jit_tick": 2.7}, "op_s": {}, "scope_s": {
        tick + "moe/experts/held_experts/pallas_call:": 0.60, tick + "moe/dispatch/reduce_sum:": 0.05, tick + "moe/router/dot_general:": 0.03,
        tick + "ffn/gate_up/dot_general:": 1.1, tick + "attn/absorb/dot_general:": 0.12, tick + "attn/kv_read/latent_decode:": 0.25,
        "jit(_prefill)/moe/experts/x:": 5.0}}  # no scope under moe/zero: the readers must not need one
    ctx = _ctx(conf, family, _stats(1), _stats(2), trace)
    got = {name: ctx["read"](name) for name in READERS}
    assert all(isinstance(v, float) and v > 0 for v in got.values()), got
    hit, live = 26 / 4, 32 * (600 + 115)  # held experts hit a layer-step; context of the 32 requests decoding mid-span
    assert ctx["read"]("decode_step_dev_ms") == pytest.approx(2.7e3 / 240)
    assert got["scmoe_decode_step_roofline"] == pytest.approx(100 * family.decode_step_bytes(conf, live, hit) / 819e9 / (2.7 / 240), rel=0.01)
    assert got["scmoe_experts_roofline"] == pytest.approx(100 * hit * 75_497_472 * 4 * 240 / 819e9 / 0.60)
    assert got["scmoe_zero_pick_share"] == pytest.approx(100 * (1 - 7.875 / 12))
    assert got["scmoe_real_picks_p90"] == 10.0
    assert all(got[n] < 100 for n in READERS if "roofline" in n)
    # the accepted readers the cell joins read the new family's functions as they are
    others = {name: ctx["read"](name) for name in ("moe_dev_share", "mla_dev_share", "moe_local_pick_share", "moe_load_imbalance")}
    assert all(isinstance(v, float) and v > 0 for v in others.values()), others
    assert others["moe_dev_share"] == pytest.approx(100 * 0.68 / 2.15) and others["moe_load_imbalance"] == pytest.approx(1.0)
    assert family.window_counts(ctx) == {"experts_hit_per_layer_step": pytest.approx(6.5), "real_picks_mean": pytest.approx(7.875)}
    ctx["trace"] = None  # an untraced run: the two counters' readers still print, on the diagnostics line
    assert [ctx["read"](r) is None for r in READERS] == [True, True, False, False]


def test_the_new_readers_return_nothing_on_a_program_without_the_counters():
    """The parent commit and the other families: no ``picks_zero`` in tick_stats.  The line then leaves the metric
    out; it does not raise."""
    from benchmarks import families

    trace = {"program_runs": {"jit_tick": 30}, "program_s": {"jit_tick": 2.9}, "op_s": {"fusion.1": 1.0},
             "scope_s": {"jit(tick)/while/body/ffn/gate_up/dot_general:": 2.0, "jit(tick)/moe/experts/x:": 0.5}}
    old = {"moe": {k: {kk: vv for kk, vv in v.items() if kk not in ("picks_zero", "real_picks_hist")} if isinstance(v, dict) else v
                   for k, v in _stats(1)["moe"].items()}}
    for name in (CONFIG, "a.x-k1-ep16", "deepseek-v3.2-ep16", "qwen2.5-7b-instruct"):
        conf = json.load(open(os.path.join(DATA, "configs", name + ".json")))
        for stats in ({}, old):
            ctx = _ctx(conf, families.load(conf, DATA), stats, stats, trace)
            for reader in READERS:
                if name == CONFIG and stats and "roofline" in reader:
                    continue  # this family over the old counters still has the experts hit to read
                assert ctx["read"](reader) is None, (name, reader)
            ctx["trace"] = None
            assert all(ctx["read"](r) is None for r in READERS)


TINY_MIX = {"why": "rehearsal only: the cell's shape at the tiny size", "who": "the tests",
            "arrival": {"kind": "closed", "clients": 6, "cycle": 12},
            "prompt_tokens": {"dist": "loguniform", "lo": 24, "hi": 60}, "output_tokens": {"dist": "uniform", "lo": 12, "hi": 20},
            "warm_s": 1.0, "finish_cap_s": 120.0, "check_requests": 4}


@pytest.fixture(scope="module")
def rehearsal_root(tmp_path_factory):
    """The benchmark's data directories as they are, plus the tests' tiny configuration of the family as a
    rank's share (4 of 16 experts held) under the cell's kind of traffic: closed loop, twice as many clients
    as slots, every prompt one prefill program, piggyback off."""
    root = tmp_path_factory.mktemp("scmoe_rehearsal")
    data = root / "benchmarks"
    for sub in ("configs", "traffic", "layer_metrics", "families"):
        shutil.copytree(os.path.join(DATA, sub), data / sub)
    json.dump(TINY_MIX, open(data / "traffic" / "tiny-answers.json", "w"))
    bench = json.load(open(os.path.join(DATA, "tests", "rehearsal.json")))
    conf = json.load(open(os.path.join(HERE, "data", "scmoe_tiny.json")))
    conf["hf"].update(n_routed_experts=4, ep_size=4, ep_rank=1)
    conf["weights"]["seed"] = 4444
    conf["serving"].update(max_slots=3, max_seq_len=128, chunk_size=64, kv_page_size=16, kv_pages=24, prefill_buckets=[32, 64],
                           prefill_wave=1, prefill_piggyback=False)
    json.dump(conf, open(data / "configs" / "scmoe-tiny.json", "w"))
    bench["configs"].append({"name": "scmoe-tiny", "source": "none", "why": "test", "reduced": [],
                             "file": "benchmarks/configs/scmoe-tiny.json"})
    bench["workloads"].append({"name": "scmoe-tiny.answers", "config": "scmoe-tiny", "traffic": "tiny-answers",
                               "chips": 1, "why": "test"})
    units = {m["name"]: m for m in _bench()["per_layer"]}
    for name in READERS + ("moe_local_pick_share", "moe_load_imbalance"):
        bench["per_layer"].append(dict(units[name], workloads=["scmoe-tiny.answers"]))
    json.dump(bench, open(root / "BENCHMARK.json", "w"))
    return root


def _run(capsys, root, *argv):
    from benchmarks import run

    capsys.readouterr()
    assert run.main(["--benchmark-json", str(root / "BENCHMARK.json"), "--data-root", str(root), *argv]) == 0
    lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    return json.loads(lines[-2]), json.loads(lines[-1])


def test_rehearsal_in_the_cells_shape_is_correct_and_not_under_each_control(capsys, rehearsal_root):
    diag, res = _run(capsys, rehearsal_root, "--workload", "scmoe-tiny.answers", "--seed", str(2**31 + 44),
                     "--seconds", "6", "--trace", "1", "--rehearsal", "--controls")
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 4, diag["compared"]
    assert diag["compiles_in_window"] == 0 and diag["early_stops"] == 0 and diag["engine_restarts"] == 0
    assert diag["compared"]["prompt_mismatches"] == [0, 0] and diag["prefill_chunks_piggybacked"] == 0
    # the float32 rehearsal agrees with the reference to rounding; each control fails both of the family's limits
    for number in ("p99", "mean"):
        gap, limit = diag["compared"][f"logit_gap_{number}"]
        controls = {c: diag["compared"][f"control_{c}_gap_{number}"][0] for c in ("w_fp8", "no_zero", "no_scale")}
        assert gap < 0.05 * limit and all(limit < v for v in controls.values()), (number, gap, limit, controls)
    assert diag["compared"]["logit_gap_max"][1] is None
    # the counters reached the readers over the side channel; 8 of the 24 outputs are identity experts
    share = res["metrics"]["scmoe_zero_pick_share"]["value"]
    assert 20.0 < share < 45.0 and 2.0 <= res["metrics"]["scmoe_real_picks_p90"]["value"] <= 4.0
    counts = diag["counter_metrics"]
    assert counts["scmoe_zero_pick_share"] == pytest.approx(share) and 2.0 < counts["real_picks_mean"] < 3.4
    assert 10.0 < counts["moe_local_pick_share"] < 25.0  # 4 of 24 outputs are held here
    # device metrics come from a chip's trace alone: a CPU trace has no programs and no scopes to read
    assert not [n for n in READERS if "roofline" in n and n in res["metrics"]]
