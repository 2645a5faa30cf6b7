"""The four per-layer metrics that read the program's own spans (``usage.timings``
on the terminal event of every response): each reader on a hand-made ``ctx``, an
older program (no ``timings``) reads as nothing, and the rehearsal reports all four."""
import json
import os
import shutil

import pytest

from benchmarks import run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
LAYER_DIR = os.path.join(ROOT, "benchmarks", "layer_metrics")
NAMES = ("entry_encode_ms_p50", "stream_lag_ms_p99", "decode_displaced_share", "prefill_pad_share")


def _event(prompt_len, *, measured=True, error=None, done=True, **timings):
    ev = {"measured": measured, "error": error, "prompt_len": prompt_len, "tokens": [65] * 4,
          "usage": {"prompt_tokens": prompt_len, "completion_tokens": 4, "timings": timings or None}}
    if not timings:
        del ev["usage"]["timings"]
    if done:
        ev["done"] = 1.0
    return ev


def _ctx(events, step_ms=10.0):
    ctx = {"events": events, "trace": {} if step_ms else None}
    ctx["read"] = lambda name: step_ms if name == "decode_step_dev_ms" else run.read_layer_metric(name, ctx, LAYER_DIR)
    return ctx


WAVE = [  # a wave of 2 that rode a 256 x 4 program, and one request alone in a 512 x 1 program
    _event(130, encode_s=0.001, stream_lag_max_s=0.002, decode_s=2.0, decode_steps=160,
           prefill_bucket=256, wave_rows=2, wave_rows_padded=4, prefill_chunks=0, prefix_hit_tokens=0),
    _event(200, encode_s=0.003, stream_lag_max_s=0.004, decode_s=2.0, decode_steps=120,
           prefill_bucket=256, wave_rows=2, wave_rows_padded=4, prefill_chunks=0, prefix_hit_tokens=0),
    _event(300, encode_s=0.002, stream_lag_max_s=0.050, decode_s=1.0, decode_steps=80,
           prefill_bucket=512, wave_rows=1, wave_rows_padded=1, prefill_chunks=0, prefix_hit_tokens=44),
    # not the window's, failed, unfinished: none of them is read
    _event(999, measured=False, encode_s=9.0, stream_lag_max_s=9.0, decode_s=9.0, decode_steps=1,
           prefill_bucket=1024, wave_rows=1, wave_rows_padded=8, prefill_chunks=0, prefix_hit_tokens=0),
    _event(999, error="HTTP 500", encode_s=9.0, stream_lag_max_s=9.0, decode_s=9.0, decode_steps=1,
           prefill_bucket=1024, wave_rows=1, wave_rows_padded=8, prefill_chunks=0, prefix_hit_tokens=0),
    _event(999, done=False, encode_s=9.0, stream_lag_max_s=9.0, decode_s=9.0, decode_steps=1,
           prefill_bucket=1024, wave_rows=1, wave_rows_padded=8, prefill_chunks=0, prefix_hit_tokens=0),
]


@pytest.mark.parametrize("name, expected", [
    ("entry_encode_ms_p50", 2.0),
    ("stream_lag_ms_p99", 4.0 + (50.0 - 4.0) * 0.98),  # between the two largest
    ("decode_displaced_share", 100.0 * (1 - 360 * 10.0 / 5000.0)),
    ("prefill_pad_share", 100.0 * (1 - (130 + 200 + 300 - 44) / (512 + 512 + 512))),
])
def test_reader_values_on_a_hand_made_ctx(name, expected):
    assert run.read_layer_metric(name, _ctx(WAVE), LAYER_DIR) == pytest.approx(expected)


@pytest.mark.parametrize("name", NAMES)
def test_reader_reads_nothing_from_an_older_program(name):
    """No ``timings`` on any terminal event (the parent commit): left out, not 0."""
    assert run.read_layer_metric(name, _ctx([_event(130), _event(200)]), LAYER_DIR) is None
    assert run.read_layer_metric(name, _ctx([]), LAYER_DIR) is None


def test_displaced_share_needs_the_traces_step_time_and_chunks_are_charged():
    assert run.read_layer_metric("decode_displaced_share", _ctx(WAVE, step_ms=None), LAYER_DIR) is None
    chunked = [_event(40, prefill_bucket=16, wave_rows=1, wave_rows_padded=1, prefill_chunks=3, prefix_hit_tokens=0)]
    assert run.read_layer_metric("prefill_pad_share", _ctx(chunked), LAYER_DIR) == pytest.approx(100 * (1 - 40 / 48))


def test_benchmark_json_lists_the_four_with_their_layers_and_cells():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NAMES[0])
    assert names[at:at + 4] == list(NAMES)  # appended together; what later PRs appended comes after, nothing moved
    cell = ["qwen2.5-7b-batch-saturated"]
    assert {n: (by_name[n]["layer"], by_name[n]["moves"], by_name[n].get("workloads")) for n in NAMES} == {
        "entry_encode_ms_p50": ("entry", "out_tok_per_s", cell),
        "stream_lag_ms_p99": ("entry", "tpot_p50_ms", None),
        "decode_displaced_share": ("engine", "tpot_p50_ms", cell),  # not PR 29's cell: its step time is not llama's
        "prefill_pad_share": ("model step", "out_tok_per_s", cell),
    }
    assert all(by_name[n]["source"] == "program_span" and os.path.exists(os.path.join(LAYER_DIR, n + ".py")) for n in NAMES)


def test_the_rehearsal_reports_all_four(capsys, tmp_path):
    """The command end to end on the CPU with the four entries added.  A CPU trace has no compiled-program
    line, so ``decode_step_dev_ms`` reads nothing there: a reader of a fixed step time stands in for it."""
    data = tmp_path / "benchmarks"
    for sub in ("configs", "traffic", "layer_metrics", "families"):
        shutil.copytree(os.path.join(ROOT, "benchmarks", sub), data / sub)
    (data / "layer_metrics" / "decode_step_dev_ms.py").write_text("def read(ctx):\n    return 0.05 if ctx['trace'] else None\n")
    bench = json.load(open(os.path.join(HERE, "rehearsal.json")))
    real = {m["name"]: m for m in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["per_layer"]}
    for n in NAMES:
        bench["per_layer"].append({k: v for k, v in real[n].items() if k != "workloads"})
    path = tmp_path / "BENCHMARK.json"
    json.dump(bench, open(path, "w"))
    capsys.readouterr()
    assert run.main(["--benchmark-json", str(path), "--data-root", str(tmp_path), "--workload", "tiny.sessions",
                     "--seed", "5", "--seconds", "3", "--trace", "1", "--rehearsal"]) == 0
    lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    diag, res = json.loads(lines[-2]), json.loads(lines[-1])
    assert res["correct"] is True, (diag["compared"], diag["errors"])
    got = {n: res["metrics"][n]["value"] for n in NAMES}
    assert 0 < got["entry_encode_ms_p50"] < 1000 and 0 <= got["stream_lag_ms_p99"] < 5000
    assert 0 <= got["prefill_pad_share"] < 100 and got["decode_displaced_share"] <= 100
    assert diag["compared"]["prompt_mismatches"] == [0, 0]
