"""The one traffic generator: a mix file of parameters in, a plan of requests out.

A mix (``benchmarks/traffic/<name>.json``) states how requests arrive and how
long they are.  The plan it gives does the same work whatever the seed, which
is the traffic's half of the benchmark's one rule (``README.md``: the weights
are the configuration's, drawn from its file's ``weights.seed`` whatever
``--seed``; this module is all that ``--seed`` reaches, beside the sample that
``correct`` draws):

- lengths are taken at evenly spaced quantiles of the stated distribution: one
  multiset of prompt and output lengths, the same for every seed;
- ``--seed`` shuffles which request gets which length, jitters the arrivals
  inside their slots, draws the think times' order and draws the contents;
- open-loop arrivals are one request per slot of ``1/rate`` seconds, jittered
  inside the slot: a fixed count, no Poisson noise in the count;
- closed-loop clients and sessions draw chains from the same kind of cycle, as
  many cycles as the window turns out to need.

A request is what a client of ``/dialog/`` sends: a list of chat messages and
``max_tokens``.  The lengths a mix states are prompt tokens as the served model
sees them, so the generator sizes the message texts by the chat format the
server applies (:func:`prompt_ids`: the plain ``role: content`` join with a
trailing ``assistant:`` cue, one token per byte after a begin-of-sequence id).
That copy of the format is part of the yardstick: the reference checks the
served tokens against a prompt built here, and the client checks the server's
own count of prompt tokens against it on every request.

A *chain* is what one client does without interleaving: a single request, or a
session of turns whose messages extend the previous turn's (the server shares
everything before the last message as a prefix).  Pure Python and NumPy:
nothing here touches JAX or the program.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, Iterator, List, Optional, Sequence

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BOS_ID = 257  # the byte tokenizer's begin-of-sequence id (ids 0-255 are bytes)
LETTERS = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz     ", dtype=np.uint8)


def render(messages: Sequence[Dict[str, str]]) -> str:
    """The prompt text the server builds from chat messages when the model
    ships no chat template: ``role: content`` lines and an ``assistant:`` cue."""
    return "\n".join([f"{m['role']}: {m['content']}" for m in messages] + ["assistant:"])


def prompt_ids(messages: Sequence[Dict[str, str]]) -> List[int]:
    return [BOS_ID] + list(render(messages).encode("utf-8"))


def shared_prefix_len(messages: Sequence[Dict[str, str]]) -> int:
    """Tokens of everything before the last message: what the server may share."""
    if len(messages) < 2:
        return 0
    head = "\n".join(f"{m['role']}: {m['content']}" for m in messages[:-1]) + "\n"
    return 1 + len(head.encode("utf-8"))


@dataclasses.dataclass
class Turn:
    messages: List[Dict[str, str]]
    max_tokens: int
    think_s: float = 0.0  # pause before this turn is due (closed loops)

    @property
    def prompt_ids(self) -> List[int]:
        return prompt_ids(self.messages)

    @property
    def prefix_len(self) -> int:
        return shared_prefix_len(self.messages)


@dataclasses.dataclass
class Chain:
    index: int
    turns: List[Turn]
    due_s: Optional[float] = None  # open loop: seconds from window open (negative: warm-up)
    measured: bool = True


def load_mix(name: str, directory: Optional[str] = None) -> Dict[str, Any]:
    path = os.path.join(directory or os.path.join(HERE, "traffic"), name + ".json")
    with open(path) as f:
        return json.load(f)


def quantile_lengths(spec: Dict[str, Any], n: int) -> List[int]:
    """``n`` lengths at the mid-points of ``n`` equal slices of the
    distribution: the same multiset whatever the seed."""
    if "fixed" in spec:
        return [int(spec["fixed"])] * n
    lo, hi = float(spec["lo"]), float(spec["hi"])
    us = (np.arange(n) + 0.5) / n
    if spec["dist"] == "loguniform":
        vals = np.exp(np.log(lo) + us * (np.log(hi) - np.log(lo)))
    elif spec["dist"] == "uniform":
        vals = lo + us * (hi - lo)
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    return [int(round(v)) for v in vals]


def _rng(seed: int, *path: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, *path])


def _text(rng: np.random.Generator, n: int) -> str:
    """``n`` characters of lower-case words: one byte, one token, each."""
    return LETTERS[rng.integers(0, len(LETTERS), size=max(0, n))].tobytes().decode("ascii")


def _message(rng: np.random.Generator, role: str, tokens: int, first: bool) -> Dict[str, str]:
    """A message that adds ``tokens`` tokens to a prompt: its ``role: `` label
    and the newline that joins it count; the first also carries the
    begin-of-sequence id and the ``assistant:`` cue (a fixed 12 tokens)."""
    overhead = len(role) + 3 + (11 if first else 0)
    return {"role": role, "content": _text(rng, tokens - overhead)}


class Plan:
    """Chains in the order clients take them.  ``cycle`` chains make one pass
    over the length multiset; chain ``i`` belongs to cycle ``i // cycle``."""

    def __init__(self, mix: Dict[str, Any], seed: int, seconds: float):
        self.mix, self.seed, self.seconds = mix, int(seed), float(seconds)
        arr = mix["arrival"]
        self.kind = arr["kind"]
        self.warm_s = float(mix.get("warm_s", 4.0))
        if self.kind == "open":
            self.rate = float(arr["requests_per_10s"]) / 10.0
            # whole requests only, and slots that tile the window exactly
            self.cycle = max(1, int(self.rate * self.seconds + 1e-9))
            self.n_warm = int(self.rate * self.warm_s + 1e-9)
        else:
            self.clients = int(arr["clients"])
            self.cycle = int(arr.get("cycle", 24))
            self.stagger_s = float(arr.get("stagger_s", 0.0))
        self.session = mix.get("session")

    # -- one cycle's worth of shapes, shuffled by (seed, cycle number) ---------
    def _shapes(self, cycle_no: int, n: int) -> List[Dict[str, Any]]:
        rng = _rng(self.seed, 1, cycle_no)

        def dealt(spec: Dict[str, Any]) -> List[int]:
            col = quantile_lengths(spec, n)
            rng.shuffle(col)
            return col

        out = dealt(self.mix["output_tokens"])
        if not self.session:
            prompt = dealt(self.mix["prompt_tokens"])
            return [{"prompt": prompt[i], "out": out[i]} for i in range(n)]
        turns = int(self.session["turns"])
        opening = dealt(self.session["opening_tokens"])
        incs = [dealt(self.session["turn_tokens"]) for _ in range(turns)]
        lo, hi = self.session["think_s"]
        thinks = [dealt({"dist": "uniform", "lo": lo * 1000, "hi": hi * 1000}) for _ in range(turns)]
        return [{"opening": opening[i], "incs": [c[i] for c in incs], "out": out[i],
                 "thinks": [c[i] / 1000.0 for c in thinks]} for i in range(n)]

    def _chain(self, index: int, shape: Dict[str, Any], salt: int) -> Chain:
        rng = _rng(self.seed, 2, salt, index)
        if not self.session:
            return Chain(index, [Turn([_message(rng, "user", shape["prompt"], True)], shape["out"])])
        keep_history = self.session.get("carry", "history") == "history"
        opening = _message(rng, "system", shape["opening"], True)
        turns: List[Turn] = []
        asked: List[Dict[str, str]] = []
        for t, inc in enumerate(shape["incs"]):
            new = _message(rng, "user", inc, False)
            turns.append(Turn([opening] + asked + [new], shape["out"], shape["thinks"][t]))
            if keep_history:
                asked = asked + [new]
        return Chain(index, turns)

    # -- open loop --------------------------------------------------------------
    def open_chains(self) -> List[Chain]:
        """Warm-up arrivals (due < 0, not measured) then the window's: one per
        slot of 1/rate, jittered inside it."""
        assert self.kind == "open"
        chains: List[Chain] = []
        slot = self.seconds / self.cycle
        for part, n, salt, t0 in (("warm", self.n_warm, 0, -self.n_warm * slot),
                                  ("window", self.cycle, 1, 0.0)):
            if n == 0:
                continue
            shapes = self._shapes(salt, n)
            jit = _rng(self.seed, 3, salt).uniform(0.0, 1.0, size=n)
            for i in range(n):
                c = self._chain(i, shapes[i], salt)
                c.due_s = t0 + (i + float(jit[i])) * slot
                c.measured = part == "window"
                chains.append(c)
        return chains

    # -- closed loops -------------------------------------------------------------
    def closed_chains(self) -> Iterator[Chain]:
        """Endless: cycle after cycle of the multiset, each shuffled anew."""
        assert self.kind in ("closed", "sessions")
        cycle_no = 0
        while True:
            shapes = self._shapes(cycle_no, self.cycle)
            for i in range(self.cycle):
                yield self._chain(cycle_no * self.cycle + i, shapes[i], 100 + cycle_no)
            cycle_no += 1

    def one_cycle(self) -> List[Chain]:
        if self.kind == "open":
            return self.open_chains()
        gen = self.closed_chains()
        return [next(gen) for _ in range(self.cycle)]

    def longest_total(self) -> int:
        """Prompt plus output tokens of the longest turn a run can send: what
        the configuration's ``max_seq_len`` has to hold."""
        return max(len(t.prompt_ids) + t.max_tokens for c in self.one_cycle() for t in c.turns)


def totals(chains: Sequence[Chain]) -> Dict[str, int]:
    m = [c for c in chains if c.measured]
    return {
        "requests": sum(len(c.turns) for c in m),
        "prompt_tokens": sum(len(t.prompt_ids) for c in m for t in c.turns),
        "output_tokens": sum(t.max_tokens for c in m for t in c.turns),
    }
