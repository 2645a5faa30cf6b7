"""Decides ``correct``: what the timed window served, against the plain
reference, after the window has closed and the program's state is freed.

A sample of the window's finished requests, drawn from the seed and always
holding the longest, is run once through ``benchmarks/reference`` (the prompt
as ``traffic_gen.prompt_ids`` builds it from the messages that were sent, then
the tokens the client read off the wire, teacher-forced).  The number compared
is the widest gap, over every served token of the sample, by which that token's
reference logit lies below the reference's best at its position, in units of
the standard deviation of that row's drawn columns.  Greedy decoding in the
stated precision picks the reference's best token or a near-tie; a lower
precision, a wrong chat format or a wrong tokenisation picks tokens the
reference ranks far lower.  ``LIMITS`` holds each number's limit; PERF.md gives
the readings they were set from.

The controls put the reference in the program's place in the nearest precision
below the configuration's: every projection re-quantised to int4 (below the
stated int8 weights), and keys and values rounded through float8 (below the
stated bfloat16 cache).  At each position they read the gap of the token the
lower precision puts first.  The benchmark's own runs never compute them;
``run.py --controls`` and the tests do.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np

from benchmarks import weights
from benchmarks.reference import decoder

# each number compared and its limit (PERF.md section 2 has the readings)
LIMITS = {
    "logit_gap_max": 0.30,     # PERF.md section 2: sound runs' largest / the controls' smallest
    "short_outputs": 0,        # exact: every finished request streamed max_tokens characters
    "prompt_mismatches": 0,    # exact: the server counted the prompt tokens traffic_gen.prompt_ids counts
}
INT4_GROUP = 64
KV_CONTROL_DTYPE = "float8_e4m3fn"


def sample(events: List[Dict[str, Any]], seed: int, n: int) -> List[Dict[str, Any]]:
    ok = [e for e in events if e["measured"] and not e.get("error") and e.get("tokens")]
    if not ok:
        return []
    longest = max(ok, key=lambda e: e["prompt_len"] + len(e["tokens"]))
    rest = [e for e in ok if e is not longest]
    rng = np.random.default_rng([seed & 0xFFFFFFFFFFFFFFFF, 0xC4EC])
    picks = [rest[i] for i in rng.permutation(len(rest))[: max(0, n - 1)]]
    return [longest] + picks


def _gaps(ref: Sequence[np.ndarray], picks: Sequence[np.ndarray]) -> np.ndarray:
    """For each row of each sequence: (best - logit of the picked column) / row's sd."""
    out = []
    for L, p in zip(ref, picks):
        inside = (p >= 0) & (p < L.shape[1])
        got = L[np.arange(len(p)), np.clip(p, 0, L.shape[1] - 1)]
        g = (L.max(axis=1) - got) / L.std(axis=1)
        out.append(np.where(inside, g, 1e9))  # a token outside the drawn columns: no reference logit at all
    return np.concatenate(out)


def _summary(g: np.ndarray, prefix: str) -> Dict[str, float]:
    """The gaps of one pass over the sample, as the numbers a limit can be set on."""
    return {
        prefix + "gap_max": float(g.max()),
        prefix + "gap_p99": float(np.quantile(g, 0.99)),
        prefix + "gap_mean": float(np.minimum(g, 10.0).mean()),
        prefix + "mismatch_share": float(np.mean(g > 0.0)),  # tokens that are not the reference's first
    }


def logit_gaps(conf: Dict[str, Any], seed: int, picked: List[Dict[str, Any]],
               controls: bool = False, dump: str = "") -> Dict[str, float]:
    hf = conf["hf"]
    lo, hi = conf["weights"]["head_ids"]
    cols = list(range(lo, hi + 1))
    seqs = [list(e["prompt_ids"]) + list(e["tokens"]) for e in picked]
    firsts = [len(e["prompt_ids"]) - 1 for e in picked]
    top = weights.dequantised_top(hf, seed, (lo, hi))

    def run(int4_group: int = 0, kv_round=None):
        return decoder.logits_at(hf, lambda i: weights.dequantised_layer(hf, seed, i, int4_group), top,
                                 seqs, firsts, kv_round=kv_round, columns=cols)

    ref = run()
    served = [np.asarray(e["tokens"]) - lo for e in picked]
    g = _gaps(ref, served)
    out = {**_summary(g, "logit_"), "checked_tokens": float(len(g))}
    all_gaps = {"served": g.tolist()}
    if controls:
        for name, low in (("control_int4_", run(int4_group=INT4_GROUP)),
                          ("control_kv_fp8_", run(kv_round=KV_CONTROL_DTYPE))):
            cg = _gaps(ref, [C.argmax(axis=1) for C in low])
            out.update(_summary(cg, name))
            all_gaps[name] = cg.tolist()
    if dump:  # every gap, for setting a limit from the readings
        import json

        with open(dump, "w") as f:
            json.dump(all_gaps, f)
    return out


def verdict(numbers: Dict[str, float]) -> bool:
    return all(numbers[k] <= LIMITS[k] for k in LIMITS if k in numbers)
