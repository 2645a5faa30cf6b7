"""The benchmark's side of the ``mla_moe`` family (``benchmarks/families/mla_moe.py``,
``benchmarks/reference/mla_moe.py``, the seven readers): found through
``benchmarks.families.load``, takes nothing of the program, counts the bytes the issue counted, and
a rehearsal of the whole command on the CPU at the tests' own tiny configuration comes out
``correct``, and not ``correct`` under the weights control and against ``llama``'s reference."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DATA = os.path.join(ROOT, "benchmarks")
READERS = ("moe_dev_share", "mla_dev_share", "moe_experts_roofline", "mla_decode_roofline",
           "mla_moe_decode_step_roofline", "moe_local_pick_share", "moe_load_imbalance")


def _cell_conf():
    with open(os.path.join(DATA, "configs", "a.x-k1-ep16.json")) as f:
        return json.load(f)


def test_the_family_and_its_reference_import_nothing_of_the_program_and_no_jax_at_load():
    for rel in ("families/mla_moe.py", "reference/mla_moe.py"):
        with open(os.path.join(DATA, rel)) as f:
            assert "django_assistant_bot_tpu" not in f.read().replace("django_assistant_bot_tpu``", "")
    code = ("import sys, json; sys.path.insert(0, %r)\n"
            "from benchmarks import run\n"
            "conf = json.load(open(%r))\n"
            "fam = run.load_family(conf, %r)\n"
            "assert 'jax' not in sys.modules and 'django_assistant_bot_tpu' not in sys.modules\n"
            "print(sorted(fam.LIMITS), fam.CONTROLS)\n") % (ROOT, os.path.join(DATA, "configs", "a.x-k1-ep16.json"), DATA)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "logit_gap_p99" in out.stdout and "logit_gap_mean" in out.stdout and "w_fp8" in out.stdout


def test_the_controls_rounding_is_float8_e4m3s_own_by_arithmetic():
    """The controls round by arithmetic (a dtype round trip left bfloat16 values untouched on the
    chip): the same values as the dtype's own conversion here, subnormals, ties and bfloat16 inputs
    included."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.reference import mla_moe as ref

    rng = np.random.default_rng(7)
    x = np.concatenate([
        rng.normal(0, s, 4096) for s in (0.0074, 0.0118, 0.044, 1.0, 30.0)  # fan-in^-0.5 of the real widths, and O(1) rows
    ] + [np.asarray([0.0, -0.0, 2.0 ** -9, 1.5 * 2.0 ** -9, 2.5 * 2.0 ** -9, 2.0 ** -6, 0.0625 + 2.0 ** -8, 448.0, -448.0, 1.0625, 1.1875])]).astype(np.float32)
    for xs in (x, np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))):
        want = np.asarray(jnp.asarray(xs).astype(jnp.float8_e4m3fn).astype(jnp.float32))
        np.testing.assert_array_equal(np.asarray(ref.round_through_e4m3(jnp.asarray(xs))), want)
    assert (want != xs).mean() > 0.9  # and it does round: the control is not the reference again


def test_a_decode_steps_bytes_are_the_issues_and_follow_the_experts_hit():
    from benchmarks import families

    conf = _cell_conf()
    f = families.load(conf, DATA)
    w = f.weight_bytes(conf)
    assert w["experts"] == 6 * 12 * 3 * 7168 * 2048 * 2  # 6.34 GB: six layers of twelve held experts
    assert f.latent_row_bytes(conf) == 1152 and f.expert_bytes(conf) == 88_080_384
    total = f.decode_step_bytes(conf, 0)
    assert 9.3e9 < total < 9.5e9 and 0.66 < w["experts"] / total < 0.69  # 9.39 GB of weights a step (the embedding is gathered, not read), two thirds of it experts
    live = 32 * 770
    assert f.decode_step_bytes(conf, live) - total == 7 * 1152 * live
    # a step that reads only the experts it hit is measured against those: the share cannot pass 100%
    assert f.decode_step_bytes(conf, live, experts_hit=9.0) == pytest.approx(
        f.decode_step_bytes(conf, live) - 6 * 3 * f.expert_bytes(conf))
    assert f.decode_step_bytes(conf, live, experts_hit=40.0) == f.decode_step_bytes(conf, live)
    assert f.decode_step_flops(conf, 32, live) > 2 * 32 * total / 2 * 0.2  # rows x parameters read, in order of size


def test_the_new_readers_return_nothing_on_a_program_without_the_counters():
    """The parent commit: no `moe` block in tick_stats, no latent scopes.  The line then leaves the
    metric out; it does not raise."""
    from benchmarks import families, roofline, run

    for conf, fam_name in ((_cell_conf(), "mla_moe"), (json.load(open(os.path.join(DATA, "configs", "qwen2.5-7b-instruct.json"))), "llama")):
        family = families.load(conf, DATA)
        trace = {"program_runs": {"jit_tick": 30}, "program_s": {"jit_tick": 2.9}, "op_s": {"fusion.1": 1.0},
                 "scope_s": {"jit(tick)/while/body/ffn/gate_up/dot_general:": 2.0, "jit(tick)/attn/qkv/x:": 0.5}}
        ctx = {"conf": conf, "family": family, "roofline": roofline, "device": {"kind": "TPU v5 lite"}, "trace": trace,
               "trace_span": (10.0, 13.0), "events": [], "c0": {"tick_stats": {}}, "c1": {"tick_stats": {}, "decode_steps": 8}}
        ctx["read"] = lambda name: run.read_layer_metric(name, ctx, os.path.join(DATA, "layer_metrics"))
        for name in READERS:
            assert ctx["read"](name) is None, (fam_name, name)
        assert ctx["read"]("decode_step_dev_ms") == pytest.approx(2.9e3 / 240)


def test_the_readers_read_counters_and_scopes():
    from benchmarks import families, roofline, run

    conf = _cell_conf()
    family = families.load(conf, DATA)

    def moe(n):
        block = lambda k: {"picks": 1000 * n * k, "picks_local": 60 * n * k, "layer_steps": 10 * n * k,  # noqa: E731
                           "experts_hit": 90 * n * k, "tokens_per_expert": [5 * n * k] * 11 + [5 * n * k * 2]}
        return {"moe": {"decode": block(1), "prefill": block(2)}}

    trace = {"program_runs": {"jit_tick": 30}, "program_s": {"jit_tick": 3.0},
             "op_s": {"latent_decode.3": 0.2, "fusion.9": 2.0},
             "scope_s": {"jit(tick)/while/body/moe/experts/dot_general:": 1.8, "jit(tick)/while/body/moe/shared/dot_general:": 0.2,
                         "jit(tick)/while/body/attn/absorb/dot_general:": 0.3, "jit(tick)/while/body/attn/kv_read/latent_decode/pallas_call:": 0.2,
                         "jit(tick)/head/dot_general:": 0.5, "jit(_prefill)/moe/experts/x:": 9.0}}
    events = [{"prompt_len": 500, "times": [9.0 + 0.0125 * i for i in range(400)]}] * 32
    ctx = {"conf": conf, "family": family, "roofline": roofline, "device": {"kind": "TPU v5 lite"}, "trace": trace,
           "trace_span": (10.0, 13.0), "events": events, "c0": {"tick_stats": moe(1)}, "c1": {"tick_stats": moe(3), "decode_steps": 8}}
    ctx["read"] = lambda name: run.read_layer_metric(name, ctx, os.path.join(DATA, "layer_metrics"))
    assert ctx["read"]("moe_dev_share") == pytest.approx(100 * 2.0 / 3.0)  # the tick's scopes only, not prefill's
    assert ctx["read"]("mla_dev_share") == pytest.approx(100 * 0.5 / 3.0)
    assert ctx["read"]("moe_local_pick_share") == pytest.approx(6.0)
    assert ctx["read"]("moe_load_imbalance") == pytest.approx(2 * 12 / 13)
    hit = 9.0  # the decode rows' experts hit per layer-step
    assert ctx["read"]("moe_experts_roofline") == pytest.approx(100 * hit * 88_080_384 * 6 * 240 / 819e9 / 1.8)
    live = family.live_context_tokens(ctx)
    assert 32 * 580 < live < 32 * 900
    assert ctx["read"]("mla_decode_roofline") == pytest.approx(100 * live * 1152 * 7 * 240 / 819e9 / 0.2)
    assert ctx["read"]("mla_moe_decode_step_roofline") == pytest.approx(
        100 * family.decode_step_bytes(conf, live, hit) / 819e9 / 12.5e-3)
    assert all(ctx["read"](n) < 100 for n in READERS if "roofline" in n)


# llama's reference under this family's weights: what a check against the wrong block reads
WRONG_REFERENCE = """
import os
from benchmarks import families

_here = os.path.dirname(os.path.dirname(__file__))
_own, _llama = (families.load({"family": name}, _here) for name in ("mla_moe", "llama"))
served_params, LIMITS, CONTROLS = _own.served_params, _own.LIMITS, ()
reference_logits = _llama.reference_logits
decode_step_bytes, decode_step_flops = _llama.decode_step_bytes, _llama.decode_step_flops
"""


@pytest.fixture(scope="module")
def rehearsal_root(tmp_path_factory):
    """The benchmark's data directories as they are, plus the tests' tiny configuration of the family
    (a rank's share: 4 of 16 experts held), a wrong-reference twin, and their cells."""
    root = tmp_path_factory.mktemp("mla_moe_rehearsal")
    data = root / "benchmarks"
    for sub in ("configs", "traffic", "layer_metrics", "families"):
        shutil.copytree(os.path.join(DATA, sub), data / sub)
    (data / "families" / "mla_moe_wrong_reference.py").write_text(WRONG_REFERENCE)
    bench = json.load(open(os.path.join(DATA, "tests", "rehearsal.json")))
    for name, fam in (("mla-moe-tiny", "mla_moe"), ("mla-moe-tiny-wrong", "mla_moe_wrong_reference")):
        conf = json.load(open(os.path.join(HERE, "data", "mla_moe_tiny.json")))
        conf.update(name=name, family=fam)
        conf["hf"].update(n_routed_experts=4, ep_size=4, ep_rank=1)
        json.dump(conf, open(data / "configs" / f"{name}.json", "w"))
        bench["configs"].append({"name": name, "source": "none", "why": "test", "reduced": [],
                                 "file": f"benchmarks/configs/{name}.json"})
        bench["workloads"].append({"name": name + ".open", "config": name, "traffic": "tiny-open", "chips": 1, "why": "test"})
    for name in READERS:
        bench["per_layer"].append({"name": name, "unit": "%", "better": "higher", "source": "program_counter",
                                   "layer": "model step", "moves": "tpot_p50_ms", "workloads": ["mla-moe-tiny.open"]})
    json.dump(bench, open(root / "BENCHMARK.json", "w"))
    return root


def _run(capsys, root, *argv):
    from benchmarks import run

    capsys.readouterr()
    assert run.main(["--benchmark-json", str(root / "BENCHMARK.json"), "--data-root", str(root), *argv]) == 0
    lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    return json.loads(lines[-2]), json.loads(lines[-1])


def test_rehearsal_on_the_cpu_is_correct_and_the_weights_control_is_not(capsys, rehearsal_root):
    diag, res = _run(capsys, rehearsal_root, "--workload", "mla-moe-tiny.open", "--seed", str(2**31 + 29),
                     "--seconds", "3", "--trace", "1", "--rehearsal", "--controls")
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] == 12, diag["compared"]
    assert diag["compiles_in_window"] == 0 and diag["early_stops"] == 0 and diag["engine_restarts"] == 0
    assert diag["compared"]["prompt_mismatches"] == [0, 0]
    # the float32 rehearsal agrees with the reference to rounding; float8 weights in its place do not,
    # by each of the family's limits.  A float8 latent cache shows against a float32 program (on the
    # chip it hides under bfloat16's own noise: families/mla_moe.py)
    for number in ("p99", "mean"):
        gap, limit = diag["compared"][f"logit_gap_{number}"]
        w_fp8, kv_fp8 = (diag["compared"][f"control_{c}_gap_{number}"][0] for c in ("w_fp8", "kv_fp8"))
        assert gap < 0.05 * limit and limit < w_fp8 and 10 * gap < kv_fp8, (number, gap, limit, w_fp8, kv_fp8)
    assert diag["compared"]["logit_gap_max"][1] is None  # the maximum carries no limit in this family, and why
    # the counters reached the readers over HTTP's side channel: a quarter of the experts is held here
    assert 10.0 < res["metrics"]["moe_local_pick_share"]["value"] < 45.0
    assert 1.0 <= res["metrics"]["moe_load_imbalance"]["value"] < 4.0


def test_rehearsal_against_llamas_reference_is_not_correct(capsys, rehearsal_root):
    diag, res = _run(capsys, rehearsal_root, "--workload", "mla-moe-tiny-wrong.open", "--seed", "31",
                     "--seconds", "2", "--trace", "0", "--rehearsal")
    assert res["correct"] is False and res["failed"] == 0
    assert all(diag["compared"][k][0] > diag["compared"][k][1] for k in ("logit_gap_p99", "logit_gap_mean"))
