"""The benchmark's readers of the device-queue ledger (``tick_stats()["device_queue"]``,
``serving/obs.py`` ``LoopLedger``): seven per-layer metrics, a file each under
``benchmarks/layer_metrics/`` (and ``device_queue_window.py``, the window's difference they share), an
entry each in ``BENCHMARK.json``.  Each is loaded as the harness loads it (``run.read_layer_metric``)
on a hand-made window: a short-prompt cell's, a window with no tick alone, a long-context cell's
``chunk+tick`` window with and without a traced step, a program from before the ledger.  And one
rehearsal of the whole command on the CPU, untraced: the counter-only readers print on the
diagnostics line of every run."""

import json
import os
import shutil

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DATA = os.path.join(ROOT, "benchmarks")
LAYER_DIR = os.path.join(DATA, "layer_metrics")
FIELDS = ("s", "n", "ticks", "groups", "tokens", "start_tokens", "lag_s", "lag_n")
QWEN, AXK1, CHAT, DEEPSEEK = ("qwen2.5-7b-batch-saturated", "a.x-k1-ep16-decode-saturated", "qwen2.5-7b-chat-steady",
                              "deepseek-v3.2-ep16-longrag-saturated")
# name -> (unit, better, layer, the cells that list it)
READERS = {
    "decode_step_ms_window": ("ms", "lower", "model step", [QWEN, AXK1, CHAT]),
    "prefill_dev_share_window": ("%", "lower", "engine", [QWEN, AXK1, CHAT, DEEPSEEK]),
    "prefill_ms_per_ktok_window": ("ms", "lower", "model step", [QWEN, AXK1, CHAT]),
    "prefill_chunk_ms_window": ("ms", "lower", "model step", [DEEPSEEK]),
    "prefill_start_lag_ms": ("ms", "lower", "admission", [QWEN, AXK1, CHAT, DEEPSEEK]),
    "device_queue_idle_share": ("%", "lower", "device", [QWEN, AXK1, CHAT, DEEPSEEK]),
    "device_queue_observed_share": ("%", "higher", "engine", [QWEN, AXK1, CHAT, DEEPSEEK]),
}


def _queue(idle_s=0.0, waited=0, not_waited=0, **segs):
    """A ledger snapshot: the keys an engine lists, each at 0 but for ``segs``
    (``chunk_tick`` stands for ``chunk+tick``, ``prefill_2x256`` for ``prefill:2x256``)."""
    q = {k: dict.fromkeys(FIELDS, 0) for k in ("tick", "piggyback", "spec", "chunk", "chunk+tick", "mixed",
                                               "prefill:1x128", "prefill:2x256", "suffix:1x128", "suffix:2x256")}
    for name, tot in segs.items():
        key = name.replace("chunk_tick", "chunk+tick").replace("prefill_", "prefill:").replace("suffix_", "suffix:")
        q[key] = dict(q[key], **tot)
    q["idle"] = {"s": idle_s, "n": 1 if idle_s else 0, "by_phase": {"idle_wait": idle_s}}
    q["markers"] = {"waited": waited, "not_waited": not_waited}
    return q


def _ctx(q0, q1, *, steps=8, traced_step_ms=None):
    from benchmarks import run

    trace = None
    if traced_step_ms is not None:  # what trace_reduce gives decode_step_dev_ms: 30 runs of the fused tick
        trace = {"program_runs": {"jit_tick": 30}, "program_s": {"jit_tick": 30 * steps * traced_step_ms / 1e3}}
    wrap = lambda q: {"tick_stats": {} if q is None else {"device_queue": q}, "decode_steps": steps}  # noqa: E731
    ctx = {"c0": wrap(q0), "c1": wrap(q1), "trace": trace}
    ctx["read"] = lambda name: run.read_layer_metric(name, ctx, LAYER_DIR)
    return ctx


def _all(ctx):
    return {name: ctx["read"](name) for name in READERS}


def test_a_short_prompt_window_reads_all_but_the_chunk():
    """400 ticks alone at 90 ms, 50 one-shot prefill groups: what the two Qwen
    cells and the A.X-K1 cell look like.  Everything is the window's
    difference: the boot's and the warm traffic's totals drop out."""
    before = _queue(idle_s=3.0, waited=100, not_waited=40, tick={"s": 10.0, "n": 100, "ticks": 100},
                    prefill_2x256={"s": 1.0, "n": 20, "groups": 20, "tokens": 6000, "lag_s": 5.0, "lag_n": 20})
    after = _queue(idle_s=3.5, waited=100 + 455, not_waited=40 + 5, tick={"s": 10.0 + 36.0, "n": 500, "ticks": 500},
                   prefill_2x256={"s": 1.0 + 2.4, "n": 50, "groups": 50, "tokens": 6000 + 12000, "lag_s": 5.0 + 7.5, "lag_n": 50},
                   prefill_1x128={"s": 0.6, "n": 20, "groups": 20, "tokens": 2000, "lag_s": 4.0, "lag_n": 20},
                   mixed={"s": 0.5, "n": 5, "ticks": 5, "groups": 2, "tokens": 300, "lag_s": 0.0, "lag_n": 2})
    got = _all(_ctx(before, after))
    total = 36.0 + 2.4 + 0.6 + 0.5 + 0.5
    assert got["decode_step_ms_window"] == pytest.approx(36.0 / 400 / 8 * 1e3)  # 11.25 ms a step
    # the mixed segments' five ticks come off at the window's own mean tick, 90 ms
    assert got["prefill_dev_share_window"] == pytest.approx(100 * (2.4 + 0.6 + 0.5 - 5 * 0.090) / total)
    assert got["prefill_ms_per_ktok_window"] == pytest.approx(3.0 * 1e6 / 14000)
    assert got["prefill_start_lag_ms"] == pytest.approx(11.5 / 52 * 1e3)
    assert got["device_queue_idle_share"] == pytest.approx(100 * 0.5 / total)
    assert got["device_queue_observed_share"] == pytest.approx(100 * 455 / 460)
    assert got["prefill_chunk_ms_window"] == pytest.approx((0.5 - 5 * 0.090) / 2 * 1e3)  # the mixed segments' two groups


def test_a_window_with_no_tick_alone_reads_what_needs_no_tick_time():
    """Nine ``tick`` segments are too few for a mean; segments that held prefill
    alone still count whole, and nothing that held a tick is in the window."""
    after = _queue(waited=39, tick={"s": 0.8, "n": 9, "ticks": 9},
                   prefill_1x128={"s": 0.9, "n": 30, "groups": 30, "tokens": 3000, "lag_s": 0.3, "lag_n": 30})
    got = _all(_ctx(_queue(), after))
    assert got["decode_step_ms_window"] is None and got["prefill_chunk_ms_window"] is None
    assert got["prefill_dev_share_window"] == pytest.approx(100 * 0.9 / 1.7)
    assert got["prefill_ms_per_ktok_window"] == pytest.approx(300.0) and got["prefill_start_lag_ms"] == pytest.approx(10.0)
    assert got["device_queue_idle_share"] == 0.0 and got["device_queue_observed_share"] == 100.0


@pytest.mark.parametrize("traced_step_ms", [None, 6.5])
def test_a_chunk_and_tick_window_splits_only_with_a_step_time(traced_step_ms):
    """The long-context cell: a chunk and the tick behind it share a segment,
    and a tick alone is rare (here 4): the split needs the traced step.  What
    needs no split reads in an untraced run too."""
    after = _queue(idle_s=0.08, waited=354, not_waited=2,
                   chunk_tick={"s": 37.2, "n": 310, "ticks": 310, "groups": 310, "tokens": 310 * 1024,
                               "start_tokens": 310 * 4096, "lag_s": 31.0, "lag_n": 310},
                   chunk={"s": 3.3, "n": 40, "groups": 40, "tokens": 40 * 900, "start_tokens": 40 * 9000, "lag_s": 4.4, "lag_n": 40},
                   tick={"s": 0.21, "n": 4, "ticks": 4})
    got = _all(_ctx(_queue(), after, traced_step_ms=traced_step_ms))
    total = 37.2 + 3.3 + 0.21 + 0.08
    assert got["prefill_start_lag_ms"] == pytest.approx(35.4 / 350 * 1e3)
    assert got["device_queue_idle_share"] == pytest.approx(100 * 0.08 / total)
    assert got["device_queue_observed_share"] == pytest.approx(100 * 354 / 356)
    assert got["decode_step_ms_window"] is None and got["prefill_ms_per_ktok_window"] is None
    if traced_step_ms is None:
        assert got["prefill_chunk_ms_window"] is None and got["prefill_dev_share_window"] is None
    else:
        chunks_s = 37.2 - 310 * 8 * 6.5e-3 + 3.3
        assert got["prefill_chunk_ms_window"] == pytest.approx(chunks_s / 350 * 1e3)  # 69.5 ms a chunk
        assert got["prefill_dev_share_window"] == pytest.approx(100 * chunks_s / total)


def test_an_older_program_without_the_ledger_reads_nothing_and_raises_nothing():
    """The parent commit's ``tick_stats`` has no ``device_queue``: the line leaves the metrics out."""
    for q0, q1 in ((None, None), (None, _queue(waited=5, tick={"s": 1.0, "n": 20, "ticks": 20}))):
        got = _all(_ctx(q0, q1, traced_step_ms=11.0))
        assert got == dict.fromkeys(READERS), got
    assert _ctx(None, None)["read"]("device_queue_window") is None
    empty = _all(_ctx(_queue(), _queue()))  # the ledger is there and the window held nothing
    assert empty == dict.fromkeys(READERS), empty


def test_the_seven_entries_are_appended_with_their_cells_in_full_and_nothing_else_moved():
    from benchmarks import run

    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    at = [m["name"] for m in bench["per_layer"]].index("decode_step_ms_window")
    tail = bench["per_layer"][at:at + len(READERS)]
    assert [m["name"] for m in tail] == list(READERS)  # appended together, in the issue's order (later PRs append after them)
    layers = {m["layer"] for m in bench["per_layer"][:at]}
    for m in tail:
        unit, better, layer, cells = READERS[m["name"]]
        later = m["workloads"][len(cells):]  # cells that later PRs appended to the list: appended, nothing else changed
        assert m == {"name": m["name"], "unit": unit, "better": better, "source": "program_counter", "layer": layer,
                     "moves": "tpot_p50_ms", "workloads": cells + later}
        assert layer in layers and os.path.isfile(os.path.join(LAYER_DIR, m["name"] + ".py"))
    assert [w["name"] for w in bench["workloads"]][:4] == [QWEN, AXK1, CHAT, DEEPSEEK]
    # program_counter: run.py prints them under counter_metrics in every run, untraced too
    for cell, n in ((QWEN, 6), (AXK1, 6), (CHAT, 6), (DEEPSEEK, 5)):
        listed = [m["name"] for m in run.metrics_for(bench, "per_layer", cell) if m["name"] in READERS]
        assert len(listed) == n and ("prefill_chunk_ms_window" in listed) is (cell == DEEPSEEK)


def test_rehearsal_prints_the_counter_only_readers_on_an_untraced_runs_diagnostics_line(capsys, tmp_path):
    """The whole command on the CPU at the tests' tiny size, ``--trace 0``: the program's ledger reaches the
    readers over the snapshots' pass-through of ``tick_stats``, and each prints under ``counter_metrics``."""
    from benchmarks import run

    data = tmp_path / "benchmarks"
    for sub in ("configs", "traffic", "layer_metrics", "families"):
        shutil.copytree(os.path.join(DATA, sub), data / sub)
    bench = json.load(open(os.path.join(DATA, "tests", "rehearsal.json")))
    entries = {m["name"]: m for m in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["per_layer"]}
    for name in READERS:
        bench["per_layer"].append(dict(entries[name], workloads=["tiny.open"]))
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))
    capsys.readouterr()
    assert run.main(["--benchmark-json", str(tmp_path / "BENCHMARK.json"), "--data-root", str(tmp_path), "--workload",
                     "tiny.open", "--seed", str(2**31 + 42), "--seconds", "6", "--trace", "0", "--rehearsal"]) == 0
    lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    diag, res = json.loads(lines[-2]), json.loads(lines[-1])
    assert res["correct"] is True and res["failed"] == 0 and diag["compiles_in_window"] == 0
    assert set(res["metrics"]) == {"tpot_p50_ms", "out_tok_per_s", "setup_s"}  # untraced: the end-to-end metrics alone
    counts = diag["counter_metrics"]
    # an open loop of 24 short requests, one-shot prefill groups between fused ticks.  The three readers that
    # need no tick time always find something; the others do where the tiny programs outlast the host's
    # bookkeeping often enough (on a CPU a result is often there before it is asked for: `observed` says how
    # often).  Counts and shares of the host's clock: never written anywhere as a device's time
    assert {"prefill_start_lag_ms", "device_queue_idle_share", "device_queue_observed_share"} <= set(counts), counts
    assert 0.0 <= counts["device_queue_idle_share"] < 100.0 and 0.0 < counts["device_queue_observed_share"] <= 100.0
    assert counts["prefill_start_lag_ms"] >= 0.0
    assert all(counts[name] >= 0.0 for name in READERS if name in counts)


def test_the_check_tool_joins_ring_and_trace_by_seq_on_a_hand_made_trace():
    """``tools/check_device_queue.py``'s join, on the chip the builder's check of the instrument: the
    engine's clock and the profiler's differ by a constant, a result reaches the host 0.2 ms after its
    program ended, and a segment is held against its programs and the gap before them."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("check_device_queue", os.path.join(ROOT, "tools", "check_device_queue.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    off, late = 1000.0, 0.0002  # profiler's clock - engine's; device end -> the host has the result
    # device: tick 0.000-0.090, tick 0.090-0.180, prefill+insert+act 0.1805-0.2205 (0.5 ms of gap before), tick 0.2205-0.3105
    modules = [("jit_tick", 0.0, 0.090), ("jit_tick", 0.090, 0.180), ("jit__prefill", 0.1805, 0.2195),
               ("jit__insert", 0.2195, 0.2200), ("jit_act", 0.2200, 0.2205), ("jit_tick", 0.2205, 0.3105)]
    modules = [(n, a + off, b + off) for n, a, b in modules]
    ends = {1: 0.090, 2: 0.180, 3: 0.2205, 4: 0.3105}
    host = {"dabt/tick_block": [(seq, off + e - 0.05, off + e + late) for seq, e in ends.items()]}
    ring = [{"seq": [s, s], "key": key, "kinds": [key.split(":")[0]], "shapes": [""],
             "start": ends[s - 1] + late if s > 1 else 0.0, "ready": ends[s] + late}
            for s, key in ((1, "tick"), (2, "tick"), (3, "prefill:2x256"), (4, "tick"))]
    rows = tool.join(ring, modules, host)
    assert [r["key"] for r in rows] == ["tick", "prefill:2x256", "tick"]  # the first has no predecessor in the trace
    assert [r["programs"] for r in rows] == [["jit_tick"], ["jit__prefill", "jit__insert", "jit_act"], ["jit_tick"]]
    assert [r["device_s"] for r in rows] == pytest.approx([0.090, 0.0405, 0.090])  # the gap before the prefill is its segment's
    assert all(abs(r["err"]) < 1e-9 and r["names_match"] and not r["queue_was_empty"] for r in rows)
    got = tool.summarise(rows)
    assert got["segments"] == 3 and got["by_kind"]["tick"]["within_1pct"] == 1.0 and abs(got["sum_err"]) < 1e-9
    assert got["clock_offset_spread_us"] < 1.0 and got["busy_share"] == pytest.approx(0.220 / 0.2205)
    ring[2]["kinds"] = ["chunk"]  # what the ledger says ran is not what the device ran
    assert tool.summarise(tool.join(ring, modules, host))["names_match"] is False
    assert tool.summarise([])["segments"] == 0
