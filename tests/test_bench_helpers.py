"""Host-side bench helpers (no device): failure diagnosis + record hygiene.

The bench record is the round's canonical evidence (BENCH_r*.json) — these
lock the helpers that keep failures diagnosable (VERDICT r3 weak #1: failures
were recorded blind) and the headline well-formed.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench


def test_error_tail_prefers_root_cause_over_wrapper():
    stderr = "\n".join(
        [
            "Traceback (most recent call last):",
            '  File "x.py", line 1, in <module>',
            "jax.errors.JaxRuntimeError: RESOURCE_EXHAUSTED: TPU backend error.",
            "During handling of the above exception, another exception occurred:",
            "RuntimeError: generation engine failure",
        ]
    )
    tail = bench._error_tail(stderr)
    assert "RESOURCE_EXHAUSTED" in tail
    assert "generation engine failure" not in tail


def test_error_tail_falls_back_to_last_exception_line():
    assert "ValueError: boom" in bench._error_tail("ValueError: boom")
    assert bench._error_tail("") == "no stderr"
    out = bench._error_tail("line1\nline2\nline3\nline4")
    assert "line4" in out


def test_subprocess_bench_returns_error_tail():
    res, err = bench._subprocess_bench(
        "raise RuntimeError('intentional-test-failure')", timeout_s=120
    )
    assert res is None
    assert "intentional-test-failure" in err


def test_subprocess_bench_parses_final_json_line():
    res, err = bench._subprocess_bench(
        "import json\nprint('noise'); print(json.dumps({'ok': 1}))", timeout_s=120
    )
    assert res == {"ok": 1} and err == ""


def test_bench_8b_budget_walk_semantics(monkeypatch):
    """The 8B section's budget discipline (what blew the r4 driver cap):
    exhausted budget records a skip without spawning anything; the fp8
    walk-down uses SHRINKING per-attempt caps (900 then 400) so a hang can't
    eat three full timeouts; per-slot error keys never overwrite each other."""
    out = bench.bench_8b(time_left=lambda: 100)
    assert out == {"decode_8b_skipped": "budget exhausted (100s left)"}

    calls = []

    def fake(snippet, timeout_s=1800):
        calls.append(timeout_s)
        if len(calls) == 1:
            return {"decode_8b_int8_tokens_per_s_per_chip": 1.0}, ""
        return None, "simulated OOM"

    monkeypatch.setattr(bench, "_subprocess_bench", fake)
    out = bench.bench_8b(time_left=lambda: 10**6)
    assert calls == [900, 900, 400, 400]
    assert {"decode_8b_fp8kv_error_64", "decode_8b_fp8kv_error_32",
            "decode_8b_fp8kv_error_16"} <= set(out)


def test_compact_record_is_bounded_and_parseable():
    """The LAST stdout line must always fit the driver's 2,000-char tail and
    carry the headline (VERDICT r5 #1: two rounds of `parsed: null`)."""
    import json

    extras = {k: 1234.5678 for k in bench._COMPACT_KEYS}
    extras["rag_req_per_s"] = 9.87654
    record = {
        "metric": "rag_req_per_s_plus_p50_ttft",
        "value": 9.87654,
        "unit": "req/s",
        "vs_baseline": 171.959,
        "extras": extras,
    }
    line = bench._compact_record(record)
    assert len(line) < 1500
    parsed = json.loads(line)
    assert parsed["rag_req_per_s"] == 9.877  # 4 sig figs
    assert parsed["value"] == 9.877
    # a pathologically bloated extras set still fits: low-priority keys drop,
    # the headline survives
    extras["moe_geometry"] = "x" * 4000
    line = bench._compact_record(record)
    assert len(line) < 1500
    assert "rag_req_per_s" in json.loads(line)


def test_compact_record_carries_error_headline():
    import json

    record = {"metric": "m", "value": None, "vs_baseline": None,
              "error": "core section produced no result (yet)", "extras": {}}
    parsed = json.loads(bench._compact_record(record))
    assert "core section" in parsed["error"]


def test_sig4_rounding():
    assert bench._sig4(1234.5678) == 1235.0
    assert bench._sig4(0.0123456) == 0.01235
    assert bench._sig4(12) == 12  # ints pass through
    assert bench._sig4("str") == "str"
    assert bench._sig4(True) is True
