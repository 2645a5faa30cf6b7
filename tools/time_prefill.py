#!/usr/bin/env python3
"""One prefill program alone, by shape, on the chip: what sets
``serving/engine.py`` ``PREFILL_PROGRAM_POSITIONS``.

    python3 tools/time_prefill.py            # on a TPU; ~2 minutes

Builds the Qwen2.5-7B int8 engine of ``benchmarks/configs/qwen2.5-7b-instruct.json``
(seeded weights, nothing served), and for every shape of its ``prefill_shapes``
times ``_prefill`` with full rows: the median of 7 calls, each ended by
``block_until_ready``, after 2 warm ones.  Prints ``{"<rows>x<bucket>": ms}``
as its last line and writes the same to ``chiprun_out/time_prefill.json``.
"""

import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import families, sut  # noqa: E402
from django_assistant_bot_tpu.models.config import DecoderConfig  # noqa: E402
from django_assistant_bot_tpu.serving import ByteTokenizer, GenerationEngine  # noqa: E402


def main() -> int:
    sut.enable_compile_cache()
    print("device", jax.devices()[0].platform, jax.devices()[0].device_kind, flush=True)
    with open(os.path.join(ROOT, "benchmarks", "configs", "qwen2.5-7b-instruct.json")) as f:
        conf = json.load(f)
    family = families.load(conf, os.path.join(ROOT, "benchmarks"))
    params = sut.wrap_params(family.served_params(conf, 1), jnp.bfloat16)
    jax.block_until_ready(params)
    s = conf["serving"]
    eng = GenerationEngine(
        DecoderConfig.from_hf(conf["hf"], dtype=jnp.bfloat16), params, ByteTokenizer(),
        max_slots=s["max_slots"], max_seq_len=s["max_seq_len"], chunk_size=s["chunk_size"],
        kv_page_size=s["kv_page_size"], kv_pages=s["kv_pages"], prefix_cache_size=0,
    )
    rng = np.random.default_rng(0)
    out = {}
    for bucket, row_counts in eng.prefill_shapes.items():
        for rows in row_counts:
            ids = jnp.asarray(rng.integers(32, 127, size=(rows, bucket)), jnp.int32)
            lengths = jnp.full((rows,), bucket, jnp.int32)
            ms = []
            for i in range(9):
                t = time.monotonic()
                jax.block_until_ready(eng._prefill(eng.params, ids, lengths))
                if i >= 2:
                    ms.append((time.monotonic() - t) * 1e3)
            out[f"{rows}x{bucket}"] = round(statistics.median(ms), 3)
            print(f"{rows}x{bucket}", out[f"{rows}x{bucket}"], flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "time_prefill.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
