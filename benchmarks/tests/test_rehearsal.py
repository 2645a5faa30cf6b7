"""The command end to end at a tiny size on the CPU (``--rehearsal``): two
processes, a seeded checkpoint, the program's registry and server, requests
over HTTP.  The control that must come out not correct, a broken timed path
that must come out not correct, a cell added as files of its own, and an
architecture added as files of its own.  This process never imports JAX; the
children do."""
import asyncio
import json
import os
import shutil

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(HERE, "rehearsal.json")
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA_DIRS = ("configs", "traffic", "layer_metrics", "families")  # what a later PR adds files to


def _run(capsys, *argv):
    from benchmarks import run

    capsys.readouterr()
    assert run.main(list(argv)) == 0
    lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    return json.loads(lines[-2]), json.loads(lines[-1])


def test_a_cpu_is_refused_without_the_rehearsal_flag(capsys):
    from benchmarks import run

    with pytest.raises(SystemExit) as e:
        run.main(["--benchmark-json", BENCH, "--workload", "tiny.open", "--seed", "1", "--seconds", "2"])
    assert "needs a TPU" in str(e.value)
    assert not [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")]  # and no result


@pytest.mark.parametrize("seed", [2**31 + 9, 21, 22])
def test_open_loop_cell_end_to_end_and_the_int4_control_fails(capsys, seed):
    diag, res = _run(capsys, "--benchmark-json", BENCH, "--workload", "tiny.open", "--seed", str(seed),
                     "--seconds", "3", "--trace", "0", "--rehearsal", "--controls")
    assert set(res) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] == 12
    assert res["device"]["platform"] == "cpu"  # said, never hidden
    assert set(res["metrics"]) == {"tpot_p50_ms", "out_tok_per_s", "setup_s"}
    assert diag["compiles_in_window"] == 0 and diag["early_stops"] == 0
    assert diag["compared"]["prompt_mismatches"] == [0, 0]  # the server's chat format is the one built here
    gap, limit = diag["compared"]["logit_gap_max"]
    control = diag["compared"]["control_int4_gap_max"][0]
    assert diag["compared"]["control_int4_mismatch_share"][0] > diag["compared"]["logit_mismatch_share"][0] == 0.0
    # the float32 rehearsal agrees with the reference to rounding; int4 weights do not
    assert gap < 0.01 < limit < control


def test_a_token_altered_where_it_is_produced_comes_out_not_correct(capsys):
    diag, res = _run(capsys, "--benchmark-json", BENCH, "--workload", "tiny.open", "--seed", "11",
                     "--seconds", "2", "--trace", "0", "--rehearsal",
                     "--sut", os.path.join(HERE, "tampered_sut.py"))
    assert res["correct"] is False and res["failed"] == 0
    assert diag["compared"]["logit_gap_max"][0] > diag["compared"]["logit_gap_max"][1]


def test_a_cell_added_as_files_of_its_own_runs_without_editing_any(capsys, tmp_path):
    """A later PR adds a configuration, a mix, a per-layer metric and their
    entries; nothing that was there is edited."""
    data = tmp_path / "benchmarks"
    for sub in DATA_DIRS:
        shutil.copytree(os.path.join(ROOT, "benchmarks", sub), data / sub)
    conf = json.load(open(data / "configs" / "tiny-rehearsal.json"))
    conf["name"] = "tiny-wider"
    conf["hf"]["intermediate_size"] = 192
    json.dump(conf, open(data / "configs" / "tiny-wider.json", "w"))
    mix = json.load(open(data / "traffic" / "tiny-sessions.json"))
    mix["arrival"]["clients"] = 2
    json.dump(mix, open(data / "traffic" / "tiny-pairs.json", "w"))
    (data / "layer_metrics" / "turns_done.v2.py").write_text(
        "def read(ctx):\n    return float(ctx['e2e']['n_completed'])\n")
    (data / "layer_metrics" / "never_there.py").write_text("def read(ctx):\n    return None\n")
    (data / "layer_metrics" / "prefix_hits.py").write_text(
        "def read(ctx):\n    return float(ctx['c1']['prefix_hits'] - ctx['c0']['prefix_hits'])\n")
    bench = json.load(open(BENCH))
    bench["configs"].append({"name": "tiny-wider", "source": "none", "why": "test",
                             "file": "benchmarks/configs/tiny-wider.json", "reduced": []})
    bench["workloads"].append({"name": "wider.pairs", "config": "tiny-wider", "traffic": "tiny-pairs",
                               "chips": 1, "why": "test"})
    for name in ("turns_done.v2", "never_there", "prefix_hits"):
        bench["per_layer"].append({"name": name, "unit": "count", "better": "higher", "source": "host_clock",
                                   "layer": "entry", "moves": "tpot_p50_ms", "workloads": ["wider.pairs"]})
    path = tmp_path / "BENCHMARK.json"
    json.dump(bench, open(path, "w"))
    diag, res = _run(capsys, "--benchmark-json", str(path), "--data-root", str(tmp_path),
                     "--workload", "wider.pairs", "--seed", "3", "--seconds", "3", "--trace", "1", "--rehearsal")
    assert res["correct"] is True, (diag["compared"], diag["failed"], diag["attempted"], diag["errors"])
    assert res["metrics"]["turns_done.v2"]["value"] == diag["n_completed"] > 0
    assert "never_there" not in res["metrics"]  # nothing to read: left out, not zero
    # the server found the turn before's prompt in its page pool: the sessions do share over HTTP
    assert res["metrics"]["prefix_hits"]["value"] > 0 and res["metrics"]["rows_active_mean"]["value"] > 0
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    assert len(res["breakdown"]["device_ops"]) <= 10


# the llama family's reference under the new family's weights: what a check against the wrong block reads
WRONG_REFERENCE = """
import os
from benchmarks import families

_here = os.path.dirname(os.path.dirname(__file__))
_own, _llama = (families.load({"family": name}, _here) for name in ("tiny_vscale", "llama"))
served_params, LIMITS, CONTROLS = _own.served_params, _own.LIMITS, ()
reference_logits = _llama.reference_logits
decode_step_bytes, decode_step_flops = _llama.decode_step_bytes, _llama.decode_step_flops
"""


@pytest.fixture(scope="module")
def second_architecture(tmp_path_factory):
    """What a ``model_config`` PR brings, written into a copy of the data
    directories: a family, its configuration and cell, three readers, and their
    entries.  No file that was there is edited."""
    root = tmp_path_factory.mktemp("second_architecture")
    data = root / "benchmarks"
    for sub in DATA_DIRS:
        shutil.copytree(os.path.join(ROOT, "benchmarks", sub), data / sub)
    before = {os.path.join(d, f): open(os.path.join(d, f), "rb").read() for d, _, fs in os.walk(data) for f in fs}
    shutil.copy(os.path.join(HERE, "vscale_family.py"), data / "families" / "tiny_vscale.py")
    (data / "families" / "tiny_vscale_wrong_reference.py").write_text(WRONG_REFERENCE)
    bench = json.load(open(BENCH))
    for name, family in (("tiny-vscale", "tiny_vscale"), ("tiny-vscale-wrong", "tiny_vscale_wrong_reference")):
        conf = json.load(open(data / "configs" / "tiny-rehearsal.json"))
        conf.update(name=name, family=family)
        json.dump(conf, open(data / "configs" / f"{name}.json", "w"))
        bench["configs"].append({"name": name, "source": "none", "why": "test", "reduced": [],
                                 "file": f"benchmarks/configs/{name}.json"})
        bench["workloads"].append({"name": name + ".open", "config": name, "traffic": "tiny-open",
                                   "chips": 1, "why": "test"})
    readers = {
        # a key of the engine's loop ledger, which only the pass-through of the whole tick_stats carries
        "loop_tick_block_s": "def read(ctx):\n    at = lambda c: c['tick_stats']['loop']['tick_block']['s']\n"
                             "    return at(ctx['c1']) - at(ctx['c0'])\n",
        # the time of an operation that is not among the ten longest
        "op_outside_the_ten_s": "def read(ctx):\n    t = ctx['trace']\n    top = {n for n, _ in t['device_ops']}\n"
                                "    rest = [v for k, v in t['op_s'].items() if k not in top]\n"
                                "    return max(rest) if rest else None\n",
        # the family's own count, through ctx
        "family_step_mb": "def read(ctx):\n    return ctx['family'].decode_step_bytes(ctx['conf'], 100.0) / 1e6\n",
    }
    for name, text in readers.items():
        (data / "layer_metrics" / (name + ".py")).write_text(text)
        bench["per_layer"].append({"name": name, "unit": "s", "better": "lower", "source": "program_span",
                                   "layer": "engine", "moves": "tpot_p50_ms", "workloads": ["tiny-vscale.open"]})
    json.dump(bench, open(root / "BENCHMARK.json", "w"))
    yield root
    assert all(open(p, "rb").read() == b for p, b in before.items())  # nothing that was there was edited


def _run_second(capsys, root, workload, *more):
    return _run(capsys, "--benchmark-json", str(root / "BENCHMARK.json"), "--data-root", str(root),
                "--workload", workload, "--seed", "7", "--seconds", "3", "--rehearsal", *more)


def test_an_architecture_added_as_files_of_its_own_runs_and_is_held_to_its_own_limits(capsys, second_architecture):
    diag, res = _run_second(capsys, second_architecture, "tiny-vscale.open", "--trace", "1", "--controls")
    assert res["correct"] is True and res["failed"] == 0, diag["compared"]
    gap, limit = diag["compared"]["logit_gap_max"]
    assert limit == 0.05 and gap < 0.01  # the family's own limit, not llama's 0.30
    assert diag["compared"]["control_int4_gap_max"][0] > 0.3  # its own control, through its own reference
    assert "control_kv_fp8_gap_max" not in diag["compared"]   # and only the controls it names
    assert list(res)[-1] == "compared" and res["compared"] == diag["compared"]  # last in the result's line too
    assert res["metrics"]["loop_tick_block_s"]["value"] > 0
    assert res["metrics"]["op_outside_the_ten_s"]["value"] > 0
    assert res["metrics"]["family_step_mb"]["value"] > 0
    assert res["metrics"]["rows_active_mean"]["value"] > 0  # the readers that were there still read


def test_the_new_architecture_checked_against_the_llama_reference_is_not_correct(capsys, second_architecture):
    diag, res = _run_second(capsys, second_architecture, "tiny-vscale-wrong.open", "--trace", "0")
    assert res["correct"] is False and res["failed"] == 0
    assert diag["compared"]["logit_gap_max"][0] > 0.3  # far past either family's limit


def test_the_new_architecture_with_a_tampered_timed_path_is_not_correct(capsys, second_architecture):
    diag, res = _run_second(capsys, second_architecture, "tiny-vscale.open", "--trace", "0",
                            "--sut", os.path.join(HERE, "tampered_sut.py"))
    assert res["correct"] is False and res["failed"] == 0
    assert diag["compared"]["logit_gap_max"][0] > diag["compared"]["logit_gap_max"][1] == 0.05


async def _fake_dialog_server(log):
    """Streams ``max_tokens`` one-character events, one every 2 ms, the way ``/dialog/`` does."""
    from aiohttp import web
    from aiohttp.test_utils import TestServer

    async def dialog(request):
        body = await request.json()
        log.append(body)
        resp = web.StreamResponse(headers={"Content-Type": "text/event-stream"})
        await resp.prepare(request)
        for i in range(body["max_tokens"]):
            await asyncio.sleep(0.002)
            await resp.write(b'data: {"delta": "a", "index": %d}\n\n' % i)
        n = 1 + len("\n".join([f"{m['role']}: {m['content']}" for m in body["messages"]] + ["assistant:"]))
        done = {"done": True, "finish_reason": "length",
                "usage": {"prompt_tokens": n, "completion_tokens": body["max_tokens"]}}
        await resp.write(b"data: " + json.dumps(done).encode() + b"\n\ndata: [DONE]\n\n")
        return resp

    app = web.Application()
    app.router.add_post("/dialog/", dialog)
    server = TestServer(app)
    await server.start_server()
    return server


def test_the_session_driver_is_closed_loop_and_times_turns_from_when_they_were_due():
    from benchmarks import driver, metrics
    from benchmarks.traffic_gen import Plan, load_mix

    plan = Plan(load_mix("tiny-sessions"), 4, 1.5)
    marks, sent = [], []

    async def go():
        server = await _fake_dialog_server(sent)
        try:
            return await driver.run(str(server.make_url("")).rstrip("/"), "m", plan,
                                    lambda: marks.append("open"), lambda: marks.append("close"))
        finally:
            await server.close()

    res = asyncio.run(go())
    assert marks == ["open", "close"]
    events = res["events"]
    assert all(not e["error"] and e["tokens"] == [97] * 16 for e in events)
    assert all(b["stream"] is True and b["temperature"] == 0.0 for b in sent)
    by_chain = {}
    for e in events:
        by_chain.setdefault(e["chain"], []).append(e)
    assert len(by_chain) >= 3 and any(len(v) == 3 for v in by_chain.values())
    for turns in by_chain.values():
        for a, b in zip(turns, turns[1:]):
            assert b["turn"] == a["turn"] + 1
            assert b["due"] >= a["done"] + 0.05 - 1e-3   # the think time, after the answer
            assert b["prefix_len"] == a["prompt_len"] - len("assistant:")  # the prompt extends the previous turn's
        assert all(e["times"][0] >= e["due"] for e in turns)
    measured = [e for e in events if e["measured"]]
    assert measured and all(res["t_open"] <= e["due"] < res["t_close"] for e in measured)
    assert any(not e["measured"] for e in events)  # warm traffic before the window
    out = metrics.end_to_end(events, res["t_open"], res["t_close"])
    assert out["prompt_mismatches"] == 0 and out["short_outputs"] == 0 and out["failed"] == 0
