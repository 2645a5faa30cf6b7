"""The command end to end at a tiny size on the CPU (``--rehearsal``): two
processes, a seeded checkpoint, the program's registry and server, requests
over HTTP.  The control that must come out not correct, a broken timed path
that must come out not correct, and a cell added as files of its own.  This
process never imports JAX; the children do."""
import asyncio
import json
import os
import shutil

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(HERE, "rehearsal.json")
ROOT = os.path.dirname(os.path.dirname(HERE))


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
    for sub in ("configs", "traffic", "layer_metrics"):
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
