"""The weights are the configuration's, the traffic is the seed's: a run's
``--seed`` reaches the plan and the sample ``correct`` checks, and nothing of
the model.  CPU, tiny sizes; this process imports JAX only where a tree is
drawn."""
import copy
import glob
import hashlib
import json
import os
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCHMARKS = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCHMARKS)
BENCH = os.path.join(HERE, "rehearsal.json")
CONFIG_FILES = sorted(glob.glob(os.path.join(BENCHMARKS, "configs", "*.json")))

# the tests' tiny configuration of the ``mla_moe`` family (tests/data/mla_moe_tiny.json's sizes, a
# quarter of the experts held), here with the key that file predates
MLA_MOE_TINY = {
    "name": "mla-moe-tiny", "family": "mla_moe",
    "hf": {"model_type": "deepseek_v3", "attention_bias": False, "ep_size": 4, "ep_rank": 1,
           "first_k_dense_replace": 1, "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
           "kv_lora_rank": 16, "max_position_embeddings": 512, "moe_intermediate_size": 32, "moe_layer_freq": 1,
           "n_group": 4, "n_routed_experts": 4, "n_shared_experts": 1, "norm_topk_prob": True,
           "num_attention_heads": 4, "num_experts_per_tok": 4, "num_hidden_layers": 3, "num_key_value_heads": 4,
           "q_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-06,
           "rope_theta": 10000,
           "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1, "mscale_all_dim": 1,
                            "original_max_position_embeddings": 64, "type": "yarn"},
           "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "tie_word_embeddings": False,
           "topk_group": 2, "topk_method": "none", "v_head_dim": 16, "vocab_size": 512},
    "weights": {"format": "bfloat16", "seed": 3, "head_ids": [32, 126]},
    "serving": {"dtype": "float32", "arch": "mla_moe", "max_slots": 4, "max_seq_len": 256},
}


def _tiny_llama():
    with open(os.path.join(BENCHMARKS, "configs", "tiny-rehearsal.json")) as f:
        return json.load(f)


def _job(conf, run_seed, rehearsal=False):
    from benchmarks import run

    args = types.SimpleNamespace(seed=run_seed, rehearsal=rehearsal)
    return run.child_job(args, {"chips": 1, "config": conf["name"]}, conf, BENCHMARKS, "/nowhere/.cache", {})


def _digest(tree) -> str:
    import jax

    h = hashlib.sha256()
    for path, leaf in sorted(((jax.tree_util.keystr(p), l) for p, l in jax.tree_util.tree_flatten_with_path(tree)[0]),
                             key=lambda kv: kv[0]):
        a = np.asarray(leaf)
        h.update(f"{path}|{a.dtype}|{a.shape}|".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("conf", [_tiny_llama(), MLA_MOE_TINY], ids=["llama", "mla_moe"])
def test_two_runs_under_different_seeds_serve_the_same_weights_and_send_other_traffic(conf):
    from benchmarks import families
    from benchmarks.traffic_gen import Plan, load_mix

    a, b = _job(conf, 2**31 + 77), _job(conf, 12)
    assert a["weights_seed"] == b["weights_seed"] == conf["weights"]["seed"]
    assert (a["seed"], b["seed"]) == (2**31 + 77, 12)  # beside it, for the child's log
    family = families.load(conf, BENCHMARKS)
    trees = [family.served_params(j["conf"], j["weights_seed"]) for j in (a, b)]
    assert _digest(trees[0]) == _digest(trees[1])  # bit for bit: the routers among them
    assert _digest(family.served_params(conf, conf["weights"]["seed"] + 1)) != _digest(trees[0])  # the key is read

    mix = load_mix("tiny-open")
    plans = [[(len(t.prompt_ids), t.max_tokens, t.messages[0]["content"]) for c in Plan(mix, s, 3.0).one_cycle()
              for t in c.turns] for s in (a["seed"], b["seed"])]
    for column in (0, 1):  # prompt lengths, output lengths: each one multiset, dealt out by the seed on its own
        lengths = [[p[column] for p in plan] for plan in plans]
        assert sorted(lengths[0]) == sorted(lengths[1])  # the same work
        assert lengths[0] != lengths[1]                  # in another order
    texts = [{p[2] for p in plan if len(p[2]) > 8} for plan in plans]  # (the shortest prompts leave no room for text)
    assert texts[0] and texts[1] and not texts[0] & texts[1]  # and other text


@pytest.mark.parametrize("seed, named", [("absent", "None"), (None, "None"), ("7", "'7'"), (1.5, "1.5"), (True, "True"), (-1, "-1")])
def test_a_configuration_without_a_whole_numbered_weights_seed_is_refused_by_name(capsys, tmp_path, seed, named):
    """At load, before a child is started (a child on this CPU would be refused for another reason)."""
    from benchmarks import run

    conf = _tiny_llama()
    if seed == "absent":
        del conf["weights"]["seed"]
    else:
        conf["weights"]["seed"] = seed
    data = tmp_path / "benchmarks"
    (data / "configs").mkdir(parents=True)
    json.dump(conf, open(data / "configs" / "tiny-rehearsal.json", "w"))
    os.symlink(os.path.join(BENCHMARKS, "traffic"), data / "traffic")
    os.symlink(os.path.join(BENCHMARKS, "families"), data / "families")
    argv = ["--benchmark-json", BENCH, "--data-root", str(tmp_path), "--workload", "tiny.open", "--seed", "5", "--seconds", "2"]
    with pytest.raises(SystemExit) as e:
        run.main(argv)
    assert "weights.seed" in str(e.value) and named in str(e.value) and "no default" in str(e.value)
    assert not capsys.readouterr().out  # and no result
    if seed not in ("absent", None):  # a value that is there and wrong is refused in a rehearsal too
        with pytest.raises(SystemExit, match="weights.seed"):
            run.main(argv + ["--rehearsal"])


def test_only_a_rehearsal_lets_a_configuration_from_before_the_key_draw_from_the_runs_seed(capsys):
    """``tests/data/mla_moe_tiny.json`` (tier-1, outside the benchmark's directories) predates the key."""
    from benchmarks import run

    conf = copy.deepcopy(MLA_MOE_TINY)
    del conf["weights"]["seed"]
    assert run.weights_seed(conf, 41, rehearsal=True) == 41
    assert "weights.seed" in capsys.readouterr().err  # said, not silent
    with pytest.raises(SystemExit, match="weights.seed"):
        run.weights_seed(conf, 41, rehearsal=False)


@pytest.mark.parametrize("path", CONFIG_FILES, ids=[os.path.basename(p) for p in CONFIG_FILES])
def test_every_configuration_states_its_weights_seed_and_names_no_kv_layout(path):
    from benchmarks import run

    conf = json.load(open(path))
    assert run.weights_seed(conf, 99, rehearsal=False) == conf["weights"]["seed"] != 99
    assert len(conf["weights"]["seed_why"]) > 20
    assert "kv_layout" not in conf["serving"]  # ModelSpec's default; the field goes with the legacy layout


def test_every_configuration_of_benchmark_json_is_among_them():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert {os.path.join(ROOT, c["file"]) for c in bench["configs"]} <= set(CONFIG_FILES)


def _run(capsys, *argv):
    from benchmarks import run

    capsys.readouterr()
    assert run.main(["--benchmark-json", BENCH, "--workload", "tiny.open", "--seconds", "2", "--trace", "0",
                     "--rehearsal", *argv]) == 0
    lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    return json.loads(lines[-2]), json.loads(lines[-1])


def test_the_reference_is_handed_the_weights_seed_and_a_child_handed_another_is_not_correct(capsys):
    """``--seed`` 31 is not ``weights.seed`` (21): the sound run is ``correct`` because child and
    reference both draw 21; a child that draws 22 is served tokens the reference ranks far lower."""
    diag, res = _run(capsys, "--seed", "31")
    assert (diag["seed"], diag["weights_seed"]) == (31, 21)
    assert res["correct"] is True and res["failed"] == 0, diag["compared"]
    diag, res = _run(capsys, "--seed", "31", "--sut", os.path.join(HERE, "other_weights_sut.py"))
    assert (diag["seed"], diag["weights_seed"]) == (31, 21)
    assert res["correct"] is False and res["failed"] == 0
    assert diag["compared"]["logit_gap_max"][0] > 3 * diag["compared"]["logit_gap_max"][1]
