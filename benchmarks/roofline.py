"""Operations and bytes a step needs, computed from shapes: the numerators of
every roofline share.  Kept with the benchmark so that no later PR can move
them.  (The byte arithmetic follows ``bench.py`` ``decode_byte_ledger``, which
a later PR may delete: PERF.md, Open questions.)
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} in benchmarks/peaks.json")
    return table[device_kind]


def weight_bytes(conf: Dict[str, Any]) -> Dict[str, int]:
    """Bytes of weights one decode step has to read: every layer projection
    (int8 + float32 scales), the norms, the output head; of the embedding only
    the gathered rows, which are not counted."""
    hf = conf["hf"]
    E, F, L, V = hf["hidden_size"], hf["intermediate_size"], hf["num_hidden_layers"], hf["vocab_size"]
    H, KH = hf["num_attention_heads"], hf["num_key_value_heads"]
    D = hf.get("head_dim") or E // H
    proj = E * H * D + 2 * E * KH * D + H * D * E + 3 * E * F
    scales = 4 * (H * D + 2 * KH * D + E + 2 * F + E)
    return {"layers": L * (proj * 1 + scales + 2 * 2 * E), "head": E * V * 2 + 2 * E}


def kv_bytes_per_token(conf: Dict[str, Any]) -> int:
    hf = conf["hf"]
    D = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
    return hf["num_hidden_layers"] * 2 * hf["num_key_value_heads"] * D * 2  # bf16 keys and values


def decode_step_bytes(conf: Dict[str, Any], live_context_tokens: float) -> float:
    """The least a decode step must move: all weights once, and the keys and
    values of every live context token once."""
    w = weight_bytes(conf)
    return w["layers"] + w["head"] + kv_bytes_per_token(conf) * live_context_tokens


def decode_step_flops(conf: Dict[str, Any], rows: float, live_context_tokens: float) -> float:
    hf = conf["hf"]
    E, F, L, V = hf["hidden_size"], hf["intermediate_size"], hf["num_hidden_layers"], hf["vocab_size"]
    H, KH = hf["num_attention_heads"], hf["num_key_value_heads"]
    D = hf.get("head_dim") or E // H
    proj = E * H * D + 2 * E * KH * D + H * D * E + 3 * E * F
    return 2.0 * rows * (L * proj + E * V) + 4.0 * L * H * D * live_context_tokens
