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
reference ranks far lower.

The reference, the controls and the limits of the numbers read from it belong
to the configuration's *family* (``benchmarks/families/<name>.py``): its
``reference_logits`` is the plain forward pass, its ``CONTROLS`` name the
lower precisions it can put in the program's place (for ``llama``: int4
weights below the stated int8, float8 keys and values below the stated
bfloat16 cache), its ``LIMITS`` hold each number's limit with the readings it
was set from.  The exact comparisons (``EXACT``) are the same for every
family.  At each position a control reads the gap of the token the lower
precision puts first.  The benchmark's own runs never compute the controls;
``run.py --controls`` and the tests do.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np

# the exact comparisons, for every family
EXACT = {
    "short_outputs": 0,        # every finished request streamed max_tokens characters
    "prompt_mismatches": 0,    # the server counted the prompt tokens traffic_gen.prompt_ids counts
}


def limits(family) -> Dict[str, float]:
    """Each number compared and its limit: the family's own, and the exact ones."""
    return {**family.LIMITS, **EXACT}


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


def logit_gaps(family, conf: Dict[str, Any], seed: int, picked: List[Dict[str, Any]],
               controls: bool = False, dump: str = "") -> Dict[str, float]:
    lo, hi = conf["weights"]["head_ids"]
    cols = list(range(lo, hi + 1))
    seqs = [list(e["prompt_ids"]) + list(e["tokens"]) for e in picked]
    firsts = [len(e["prompt_ids"]) - 1 for e in picked]
    ref = family.reference_logits(conf, seed, seqs, firsts, cols)
    served = [np.asarray(e["tokens"]) - lo for e in picked]
    g = _gaps(ref, served)
    out = {**_summary(g, "logit_"), "checked_tokens": float(len(g))}
    all_gaps = {"served": g.tolist()}
    if controls:
        for name in family.CONTROLS:
            low = family.reference_logits(conf, seed, seqs, firsts, cols, control=name)
            cg = _gaps(ref, [C.argmax(axis=1) for C in low])
            out.update(_summary(cg, f"control_{name}_"))
            all_gaps[f"control_{name}_"] = cg.tolist()
    if dump:  # every gap, for setting a limit from the readings
        import json

        with open(dump, "w") as f:
            json.dump(all_gaps, f)
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(k in numbers and numbers[k] <= limits[k] for k in limits)  # a limit with no number to hold: not correct
