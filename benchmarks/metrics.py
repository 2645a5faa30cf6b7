"""End-to-end arithmetic: from the client's event log to the numbers a user
would see.  No JAX, no program: a list of events in, a dict of floats out.

Every statistic is over all the turns that were due inside the window; a turn
that failed, was refused or did not finish counts in ``failed`` and has no
latency.  ``out_tok_per_s`` counts the tokens that arrived inside the window,
from whatever turn, over the window's length.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Sequence

MIN_TOKENS_FOR_TPOT = 16


def percentile(vals: Sequence[float], p: float) -> float:
    """Linear interpolation between closest ranks, ``p`` in (0, 100)."""
    s = sorted(vals)
    if len(s) == 1:
        return float(s[0])
    pos = (len(s) - 1) * p / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    return float(s[lo] + (s[hi] - s[lo]) * (pos - lo))


def end_to_end(events: List[Dict[str, Any]], t_open: float, t_close: float) -> Dict[str, Any]:
    measured = [e for e in events if e["measured"]]
    ok = [e for e in measured if not e.get("error") and e.get("tokens")]
    failed = len(measured) - len(ok)
    ttft = [(e["times"][0] - e["due"]) * 1e3 for e in ok]
    tpot = [
        (e["times"][-1] - e["times"][0]) * 1e3 / (len(e["tokens"]) - 1)
        for e in ok
        if len(e["tokens"]) >= MIN_TOKENS_FOR_TPOT
    ]
    in_window = sum(1 for e in events for t in e.get("times", ()) if t_open <= t < t_close)
    out: Dict[str, Any] = {
        "attempted": len(measured),
        "failed": failed,
        "n_completed": len(ok),
        "prompt_tokens": sum(e["prompt_len"] for e in ok),
        "output_tokens": sum(len(e["tokens"]) for e in ok),
        "out_tok_per_s": in_window / (t_close - t_open),
        "short_outputs": sum(1 for e in ok if len(e["tokens"]) != e["max_tokens"]),
        # the server's own counts against what was sent and what was read off the wire
        "prompt_mismatches": sum(1 for e in ok if (e.get("usage") or {}).get("prompt_tokens") != e["prompt_len"]
                                 or (e.get("usage") or {}).get("completion_tokens") != len(e["tokens"])),
    }
    if ttft:
        out["ttft_p50_ms"] = statistics.median(ttft)
        out["ttft_p95_ms"] = percentile(ttft, 95)
        out["ttft_max_ms"] = max(ttft)
    if tpot:
        out["tpot_p50_ms"] = statistics.median(tpot)
        out["tpot_mean_ms"] = statistics.fmean(tpot)
        out["tpot_max_ms"] = max(tpot)
    return out


def max_token_gap_ms(events: List[Dict[str, Any]], t_open: float, t_close: float) -> float:
    """Longest time inside the window in which no token reached any client
    while some turn was waiting for one: the stall detector."""
    stamps = sorted(t for e in events for t in e.get("times", ()) if t_open <= t < t_close)
    gaps = [b - a for a, b in zip(stamps, stamps[1:])]
    return max(gaps) * 1e3 if gaps else 0.0


def window_profile(events: List[Dict[str, Any]], t_open: float, t_close: float, parts: int = 6) -> Dict[str, Any]:
    """Where inside the window the rate was made, for the diagnostics line: the
    tokens a second of each equal part of the window (one low part is an
    event, all parts shifted is another regime of the same work) and how often
    no token reached any client for over 300 ms (two fused ticks)."""
    stamps = sorted(t for e in events for t in e.get("times", ()) if t_open <= t < t_close)
    width = (t_close - t_open) / parts
    counts = [0] * parts
    for t in stamps:
        counts[min(parts - 1, int((t - t_open) / width))] += 1
    return {
        "tokens_in_window": len(stamps),
        "tok_per_s_by_part": [round(c / width, 2) for c in counts],
        "token_gaps_over_300ms": sum(1 for a, b in zip(stamps, stamps[1:]) if b - a > 0.3),
    }
