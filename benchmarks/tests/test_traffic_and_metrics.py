"""Traffic that is steady by construction, and the end-to-end arithmetic: no JAX."""
import json
import os

import pytest

from benchmarks import metrics, trace_reduce
from benchmarks.traffic_gen import Plan, load_mix, quantile_lengths, totals

HERE = os.path.dirname(os.path.abspath(__file__))


def test_quantile_lengths_are_one_multiset_inside_the_stated_range():
    spec = {"dist": "loguniform", "lo": 64, "hi": 768}
    a = quantile_lengths(spec, 82)
    assert a == quantile_lengths(spec, 82) and len(a) == 82
    assert 64 <= min(a) and max(a) <= 768 and a == sorted(a)
    # log-uniform: the median sits at the geometric mean, not the arithmetic one
    assert abs(a[41] - (64 * 768) ** 0.5) < 10
    assert quantile_lengths({"fixed": 128}, 5) == [128] * 5


@pytest.mark.parametrize("mix", ["batch-saturated", "tiny-open", "tiny-sessions"])
def test_two_seeds_give_equal_counts_and_token_totals_in_another_order(mix):
    m = load_mix(mix)
    plans = [Plan(m, seed, 51) for seed in (7, 2**31 + 12345)]
    chains = [p.one_cycle() for p in plans]
    assert totals(chains[0]) == totals(chains[1])
    # one multiset of lengths, dealt out in an order of the seed's
    shape = lambda ch: [(len(t.prompt_ids), t.max_tokens) for c in ch for t in c.turns]
    if not m.get("session"):  # (a session's prompts are sums of dealt parts: the next test)
        for col in (0, 1):
            assert sorted(x[col] for x in shape(chains[0])) == sorted(x[col] for x in shape(chains[1]))
    assert shape(chains[0]) != shape(chains[1])
    assert chains[0][-1].turns[0].messages != chains[1][-1].turns[0].messages
    assert shape(Plan(m, 7, 51).one_cycle()) == shape(chains[0])  # the same seed, the same plan


def test_open_arrivals_are_one_per_slot_and_tile_the_window():
    p = Plan(load_mix("tiny-open"), 99, 51)
    due = [c.due_s for c in p.open_chains() if c.measured]
    slot = 51 / len(due)
    assert len(due) == int(4.0 * 51)
    assert all(i * slot <= d < (i + 1) * slot for i, d in enumerate(due))
    warm = [c.due_s for c in p.open_chains() if not c.measured]
    assert warm and max(warm) < 0 <= min(due)
    assert due != [c.due_s for c in Plan(load_mix("tiny-open"), 100, 51).open_chains() if c.measured]


def test_a_prompt_is_as_long_as_the_mix_says_under_the_servers_chat_format():
    m = load_mix("batch-saturated")
    for c in Plan(m, 3, 51).one_cycle():
        (t,) = c.turns
        ids = t.prompt_ids
        assert m["prompt_tokens"]["lo"] <= len(ids) <= m["prompt_tokens"]["hi"]
        assert ids[0] == 257 and bytes(ids[1:]).decode() == "user: " + t.messages[0]["content"] + "\nassistant:"
        assert all(32 <= i <= 126 or i == 10 for i in ids[1:]) and t.prefix_len == 0


def test_sessions_share_a_prefix_and_cycles_repeat_the_multiset():
    m = load_mix("tiny-sessions")
    p = Plan(m, 5, 51)
    gen = p.closed_chains()
    n = p.cycle
    first, second = [next(gen) for _ in range(n)], [next(gen) for _ in range(n)]
    # openings and per-turn additions are each one multiset, dealt out anew in every cycle
    shape = lambda chains: (sorted(c.turns[0].prefix_len for c in chains),
                            sorted(len(t.prompt_ids) - t.prefix_len for c in chains for t in c.turns),
                            sum(len(t.prompt_ids) for c in chains for t in c.turns))
    assert shape(first) == shape(second)
    other = Plan(m, 6, 51).closed_chains()
    assert shape([next(other) for _ in range(n)]) == shape(first)
    for c in first:
        for a, b in zip(c.turns, c.turns[1:]):
            # what the server may share of a turn is the whole of the turn before, less its cue
            assert b.prompt_ids[: b.prefix_len] == a.prompt_ids[: len(a.prompt_ids) - len("assistant:")]
            assert b.prefix_len > a.prefix_len
        assert len(c.turns) == m["session"]["turns"]
        lo, hi = m["session"]["opening_tokens"]["lo"], m["session"]["turn_tokens"]["hi"]
        assert len(c.turns[0].prompt_ids) >= lo + m["session"]["turn_tokens"]["lo"]
    assert p.longest_total() == max(len(t.prompt_ids) + t.max_tokens for c in first for t in c.turns)


def _ev(due, first, n, gap, measured=True, error=None, max_tokens=None):
    times = [first + i * gap for i in range(n)]
    return {"measured": measured, "error": error, "due": due, "times": times, "tokens": list(range(n)),
            "prompt_len": 100, "prefix_len": 0, "max_tokens": max_tokens or n,
            "usage": {"prompt_tokens": 100, "completion_tokens": n}}


def test_metric_arithmetic_on_a_synthetic_log():
    events = [_ev(0.0, 0.5, 21, 0.04), _ev(1.0, 1.7, 21, 0.05), _ev(2.0, 2.9, 21, 0.06),
              _ev(3.0, 3.2, 8, 0.04),                      # too short for a time per token
              _ev(4.0, 4.1, 21, 0.04, error="refused"),     # failed: counted, no latency
              _ev(-1.0, -0.5, 21, 0.04, measured=False)]    # warm traffic: tokens only
    out = metrics.end_to_end(events, 0.0, 10.0)
    assert (out["attempted"], out["failed"], out["n_completed"]) == (5, 1, 4)
    assert out["ttft_p50_ms"] == pytest.approx(600.0)       # median of 500, 700, 900, 200
    assert out["tpot_p50_ms"] == pytest.approx(50.0)
    assert out["tpot_mean_ms"] == pytest.approx(50.0)
    in_window = sum(1 for e in events for t in e["times"] if 0.0 <= t < 10.0)
    assert out["out_tok_per_s"] == pytest.approx(in_window / 10.0)
    assert out["short_outputs"] == 0 and out["output_tokens"] == 71 and out["prompt_mismatches"] == 0
    events[0]["usage"]["prompt_tokens"] = 99  # the server counted another prompt than was built here
    assert metrics.end_to_end(events, 0.0, 10.0)["prompt_mismatches"] == 1
    assert metrics.percentile([1, 2, 3, 4, 5], 95) == pytest.approx(4.8)
    assert metrics.max_token_gap_ms(events, 0.0, 10.0) > 0
    prof = metrics.window_profile(events, 0.0, 10.0, parts=5)
    assert prof["tokens_in_window"] == in_window
    assert sum(prof["tok_per_s_by_part"]) * 2.0 == pytest.approx(in_window)
    assert prof["token_gaps_over_300ms"] == 1  # 1.3 s to 1.7 s: nothing in flight streams


def test_trace_reduce_on_the_recorded_tpu_trace():
    """benchmarks/fixtures/tpu_small.xplane.pb: eight runs of one jitted matmul
    on a TPU v5e (PR 23), between the two window marks."""
    r = trace_reduce.reduce(os.path.join(HERE, "..", "fixtures", "tpu_small.xplane.pb"),
                            lambda a, b: "long" if b - a > 0.005 else "short")
    assert r["marked"] and r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"] < 0.1
    assert r["program_runs"] == {"jit__lambda": pytest.approx(6.0)}  # two ran before the host's mark
    assert r["program_s"]["jit__lambda"] == pytest.approx(r["busy_s"], rel=0.01)
    assert r["device_ops"][0][0] == "convolution_reduce_fusion"
    assert {k for k, _ in r["idle_gaps"]} == {"long", "short"}
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert trace_reduce.op_name("%fusion.12 = bf16[8]{0} fusion(%p)") == "fusion.12"
    assert trace_reduce.program_name("jit_tick(123)") == "jit_tick"
    assert trace_reduce.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
