#!/usr/bin/env python3
"""One prefill program alone, by shape, on the chip: what sets
``serving/engine.py`` ``PREFILL_PROGRAM_POSITIONS``; and the flash prefill
kernel alone, by shape: what sets ``ops/attention.py`` ``flash_tiles``.

    python3 tools/time_prefill.py                       # on a TPU; ~2 minutes
    python3 tools/time_prefill.py --config a.x-k1-ep16  # another configuration's programs
    python3 tools/time_prefill.py --flash               # the kernel alone; ~1 minute
    python3 tools/time_prefill.py --trace 1x512,1x1024  # where a program's device time goes
    python3 tools/time_prefill.py --config deepseek-v3.2-ep16 --decode                 # the decode tick alone
    python3 tools/time_prefill.py --config deepseek-v3.2-ep16 --decode --trace 2x8192  # ... and by scope
    python3 tools/time_prefill.py --config deepseek-v3.2-ep16 --decode --trace 2x8192 --hlo  # ... and what each operation reads
    python3 tools/time_prefill.py --config deepseek-v3.2-ep16 --chunk                  # a chunk program alone, by its start
    python3 tools/time_prefill.py --config deepseek-v3.2-ep16 --chunk --trace 1x0,1x8192  # ... and by scope

Builds the engine of ``benchmarks/configs/<config>.json`` (default
``qwen2.5-7b-instruct``: Qwen2.5-7B int8; the configuration's seeded weights,
nothing served), and for every shape of its ``prefill_shapes`` times
``_prefill`` with full rows: the median of 7 calls, each ended by
``block_until_ready``, after 2 warm ones.  Prints ``{"<rows>x<bucket>": ms}``
as its last line and writes the same to ``chiprun_out/time_prefill.json``
(``time_prefill.<config>.json`` for another configuration).

``--flash`` times ``flash_attention`` alone (causal, bfloat16, one row) at the
shapes the two configurations dispatch, ``{"<heads>x<S>x<D>/<Dv>": ms a
call}``: programs of 24 and of 8 calls, each call's output the next call's
values so that nothing is copied between them; the difference of their medians
of 7 runs over 16, so that a program's launch and the wait for its end (0.7 ms
on a v5e's host, 0.09 ms a call of 8) cancel; to ``chiprun_out/time_flash.json``.  ``--trace <rows>x<bucket>[,...]`` profiles 3
calls of each such program and prints its device time by named scope
(``attn/core``, ``attn/kv_up``, ...) and by operation, ms a call; to
``chiprun_out/trace_prefill.json``.

``--decode`` times the decode tick alone (the engine's fused tick of
``decode_steps`` steps over a seeded pool; greedy) at rows 1 / 2 / all slots x
contexts of a quarter, a half and seven eighths of ``max_seq_len`` (4k / 8k /
14k for ``deepseek-v3.2-ep16``): ``{"<rows>x<context>": ms a STEP}`` to
``chiprun_out/time_decode.<config>.json``; with ``--trace <rows>x<context>[,...]``
the same ticks by scope and operation, ms a step, to
``chiprun_out/trace_decode.<config>.json``.  With ``--hlo`` besides, the
optimised HLO of the engine's ``jit_tick`` goes to
``chiprun_out/hlo_decode.<config>.txt`` and every operation over 0.05 ms a
step is printed with its scope, the parameter leaf it reads and the bytes it
reads and writes (``tools/hlo_table.py``; ``"table"`` in the JSON): how PERF.md
section 5's table of PR 43 was made.

``--chunk`` times the chunk program alone (``_prefill_chunk``: one row of
``chunk_size`` tokens against the slot's pages, over a seeded pool) with the
chunk starting at 0, a quarter, a half and ``max_seq_len - 3 * chunk_size``
(0 / 4,096 / 8,192 / 13,312 for ``deepseek-v3.2-ep16``): ``{"1x<start>": ms}``
to ``chiprun_out/time_chunk.<config>.json``; with ``--trace 1x<start>[,...]``
by scope and operation, to ``chiprun_out/trace_chunk.<config>.json``.
"""

import argparse
import json
import os
import shutil
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import families, sut  # noqa: E402
from django_assistant_bot_tpu import models  # noqa: E402
from django_assistant_bot_tpu.models.config import DecoderConfig  # noqa: E402
from django_assistant_bot_tpu.serving import ByteTokenizer, GenerationEngine  # noqa: E402

FLASH_CALLS = (24, 8)  # the kernel's time is the difference of two programs of this many calls
# (heads, [S], D, Dv, the configuration whose softmax scale the call carries)
FLASH_SHAPES = (
    (64, (512, 1024), 256, 128, "a.x-k1-ep16"),
    (28, (256, 384, 512, 768, 1024), 128, 128, "qwen2.5-7b-instruct"),
)


def load_conf(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)


def median_ms(fn, *args) -> float:
    """Median wall time of 7 calls of ``fn(*args)``, each ended by
    ``block_until_ready``, after 2 warm ones."""
    ms = []
    for i in range(9):
        t = time.monotonic()
        jax.block_until_ready(fn(*args))
        if i >= 2:
            ms.append((time.monotonic() - t) * 1e3)
    return statistics.median(ms)


def build_engine(conf: dict) -> GenerationEngine:
    family = families.load(conf, os.path.join(ROOT, "benchmarks"))
    s = conf["serving"]
    act = getattr(jnp, s.get("dtype", "bfloat16"))
    cfg = DecoderConfig.from_hf(conf["hf"], dtype=act)
    # the family's tree is a checkpoint's: the form the block holds, as the registry makes it at load
    params = models.held_params(cfg, sut.wrap_params(family.served_params(conf, conf["weights"]["seed"]), act))
    jax.block_until_ready(params)
    return GenerationEngine(
        cfg, params, ByteTokenizer(),
        max_slots=s["max_slots"], max_seq_len=s["max_seq_len"], chunk_size=s["chunk_size"],
        kv_page_size=s["kv_page_size"], kv_pages=s["kv_pages"], prefix_cache_size=0,
        prefill_buckets=s.get("prefill_buckets"), prefill_wave=s.get("prefill_wave", 0),
        prefill_piggyback=s.get("prefill_piggyback", True),
    )


def full_rows(rows: int, bucket: int):
    ids = np.random.default_rng(0).integers(32, 127, size=(rows, bucket))
    return jnp.asarray(ids, jnp.int32), jnp.full((rows,), bucket, jnp.int32)


def time_programs(eng: GenerationEngine) -> dict:
    out = {}
    for bucket, row_counts in eng.prefill_shapes.items():
        for rows in row_counts:
            out[f"{rows}x{bucket}"] = round(median_ms(eng._prefill, eng.params, *full_rows(rows, bucket)), 3)
            print(f"{rows}x{bucket}", out[f"{rows}x{bucket}"], flush=True)
    return out


def trace_programs(program, shapes: str, trace_dir: str, per: int = 1, hlo: str = "") -> dict:
    """Device time of each program of ``shapes`` (``1x512,1x1024``) by named
    scope and by operation, ms a call (``per`` steps a call: ms a step), from a
    profiler trace of 3 calls of ``program(rows, size)()``
    (``benchmarks/trace_reduce.py``).  ``hlo``: the program's optimised HLO
    text; every operation over 0.05 ms is then listed with what it reads and
    writes (``tools/hlo_table.py``)."""
    from benchmarks import trace_reduce
    from tools import hlo_table

    def per_call(seconds: dict) -> dict:
        return {k: round(v / (3 * per) * 1e3, 4) for k, v in sorted(seconds.items(), key=lambda kv: -kv[1])}

    out = {}
    for shape in shapes.split(","):
        call = program(*(int(x) for x in shape.split("x")))
        for _ in range(2):
            jax.block_until_ready(call())
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        for _ in range(3):
            jax.block_until_ready(call())
        jax.profiler.stop_trace()
        r = trace_reduce.reduce(trace_reduce.find_xplane(trace_dir))
        ops = per_call(r["op_s"])
        out[shape] = {"program_ms": per_call(r["program_s"]), "scope_ms": per_call(r["scope_s"] or {}),
                      "op_ms": dict(list(ops.items())[:24])}
        print(shape, json.dumps(out[shape]), flush=True)
        if hlo:  # every operation then, so that tools/hlo_table.py can make the table again from the two files
            out[shape].update(op_ms=ops, table=hlo_table.table(hlo, ops))
            print(hlo_table.render(out[shape]["table"]), flush=True)
    return out


def prefill_program(eng: GenerationEngine):
    def program(rows: int, bucket: int):
        args = (eng.params, *full_rows(rows, bucket))
        return lambda: eng._prefill(*args)

    return program


def seeded_cache(eng: GenerationEngine):
    """The engine's cache, taken from it (the programs timed here donate it: the
    engine's own reference must not outlive that), its pools filled once with
    seeded values (a zero pool ties every index score)."""
    cache, eng._cache = eng._cache, None
    keys = iter(jax.random.split(jax.random.key(0), 4))
    fill = jax.jit(lambda key, like: (jax.random.normal(key, like.shape, jnp.float32) * 0.5).astype(like.dtype))
    return cache._replace(**{name: fill(next(keys), pool) for name, pool in cache._asdict().items()
                             if name not in ("lengths", "stats") and pool is not None})


def decode_program(eng: GenerationEngine):
    """-> ``program(rows, context)``: the engine's decode tick over ``rows``
    slots that each hold ``context`` tokens, as a call that runs one tick
    (``eng.burst`` steps) and keeps the donated cache for the next.  The pools
    are filled once with seeded values (a zero pool ties every index score);
    slot ``i`` owns an equal share of the pages; every call starts from ``context``
    again, so the ticks timed are the same tick."""
    state = {"cache": seeded_cache(eng), "rng": eng._rng}
    B, NB, pages = eng.max_slots, eng.max_seq_len // eng.kv_page_size, state["cache"].n_pages
    bt = np.full((B, NB), pages, np.int32)
    per_slot = min(pages // B, NB)
    for i in range(B):
        bt[i, :per_slot] = np.arange(i * per_slot, (i + 1) * per_slot)
    bt = jnp.asarray(bt)
    tokens = jnp.asarray(np.random.default_rng(0).integers(32, 127, size=(B,)), jnp.int32)
    temps, top_ps = jnp.zeros((B,), jnp.float32), jnp.ones((B,), jnp.float32)

    def program(rows: int, context: int):
        if context + eng.burst > per_slot * eng.kv_page_size:
            raise ValueError(f"a slot holds {per_slot} pages: no room for {context} + {eng.burst} tokens")
        active = jnp.arange(B) < rows
        lengths = jnp.where(active, context, 0).astype(state["cache"].lengths.dtype)

        def call():
            cache = state["cache"]._replace(lengths=jnp.copy(lengths))  # the tick donates every leaf it is handed
            toks, _, state["cache"], state["rng"] = eng._decode_tick(
                eng.params, tokens, cache, active, bt, temps, top_ps, state["rng"])
            return toks

        return call

    # the tick as the calls above compile it (lowering donates nothing)
    program.hlo = lambda: eng._decode_tick.lower(
        eng.params, tokens, state["cache"], jnp.arange(B) < 1, bt, temps, top_ps, state["rng"]).compile().as_text()
    return program


def chunk_program(eng: GenerationEngine):
    """-> ``program(1, start)``: the engine's chunk program on ``chunk_size``
    tokens at positions ``start ...`` of slot 0, which owns the first pages of
    pools filled once with seeded values, as a call that keeps the donated
    cache for the next."""
    state = {"cache": seeded_cache(eng)}
    C, NB = eng.chunk_size, eng.max_seq_len // eng.kv_page_size
    bt_row = jnp.arange(NB, dtype=jnp.int32)
    ids = jnp.asarray(np.random.default_rng(0).integers(32, 127, size=(1, C)), jnp.int32)

    def program(rows: int, start: int):
        if rows != 1 or start + C > eng.max_seq_len:
            raise ValueError(f"a chunk is one row of {C} tokens inside {eng.max_seq_len}, got {rows}x{start}")
        args = (jnp.asarray(0, jnp.int32), jnp.asarray(start, jnp.int32), jnp.asarray(C, jnp.int32))

        def call():
            logits, state["cache"] = eng._prefill_chunk(eng.params, ids, state["cache"], bt_row, *args)
            return logits

        return call

    program.cache = lambda: state["cache"]  # the cache as the last call left it: its counters say what ran
    return program


def time_chunks(eng: GenerationEngine) -> dict:
    program, out = chunk_program(eng), {}
    for start in (0, eng.max_seq_len // 4, eng.max_seq_len // 2, eng.max_seq_len - 3 * eng.chunk_size):
        out[f"1x{start}"] = round(median_ms(program(1, start)), 3)
        print(f"1x{start}", out[f"1x{start}"], flush=True)
    return out


def time_decode(eng: GenerationEngine) -> dict:
    program, out = decode_program(eng), {}
    for rows in sorted({1, 2, eng.max_slots}):
        for context in (eng.max_seq_len // 4, eng.max_seq_len // 2, eng.max_seq_len * 7 // 8):
            out[f"{rows}x{context}"] = round(median_ms(program(rows, context)) / eng.burst, 4)
            print(f"{rows}x{context}", out[f"{rows}x{context}"], flush=True)
    return out


def time_flash() -> dict:
    from django_assistant_bot_tpu.models.mla_moe import softmax_scale
    from django_assistant_bot_tpu.ops.attention import flash_attention

    out = {}
    for heads, lengths, D, Dv, config in FLASH_SHAPES:
        cfg = DecoderConfig.from_hf(load_conf(config)["hf"], dtype=jnp.bfloat16)
        scale = softmax_scale(cfg) if cfg.latent_moe is not None else None

        def chain(calls):
            def run(q, k, v):
                for _ in range(calls):
                    v = flash_attention(q, k, v, causal=True, scale=scale)
                return v
            return jax.jit(run)

        for S in lengths:
            keys = jax.random.split(jax.random.key(S), 3)
            q, k = (jax.random.normal(key, (1, heads, S, D), jnp.bfloat16) for key in keys[:2])
            v = jax.random.normal(keys[2], (1, heads, S, Dv), jnp.bfloat16)
            name = f"{heads}x{S}x{D}/{Dv}"
            # what a program costs whatever it holds (launch, the wait for its end) cancels
            long, short = (median_ms(chain(n), q, k, v) for n in FLASH_CALLS)
            out[name] = round((long - short) / (FLASH_CALLS[0] - FLASH_CALLS[1]), 4)
            print(name, out[name], flush=True)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="qwen2.5-7b-instruct", help="a file of benchmarks/configs/, without .json")
    ap.add_argument("--flash", action="store_true", help="time the flash kernel alone instead of the programs")
    ap.add_argument("--decode", action="store_true", help="time the decode tick alone, by rows and context, instead of the prefill programs")
    ap.add_argument("--chunk", action="store_true", help="time the chunk program alone, by the chunk's start, instead of the prefill programs")
    ap.add_argument("--trace", metavar="ROWSxBUCKET[,...]", help="trace these programs instead: device ms a call by scope and operation")
    ap.add_argument("--hlo", action="store_true", help="with --decode --trace: write the tick's optimised HLO and list what each operation over 0.05 ms a step reads and writes")
    args = ap.parse_args()
    sut.enable_compile_cache()
    print("device", jax.devices()[0].platform, jax.devices()[0].device_kind, flush=True)
    suffix = ".json" if args.config == "qwen2.5-7b-instruct" else f".{args.config}.json"
    trace_dir = os.path.join(ROOT, ".cache", "time_prefill_trace")
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    if args.flash:
        out, name = time_flash(), "time_flash.json"
    elif args.decode:
        eng = build_engine(load_conf(args.config))
        if args.trace:
            program, hlo = decode_program(eng), ""
            if args.hlo:
                hlo = program.hlo()
                with open(os.path.join(ROOT, "chiprun_out", "hlo_decode" + suffix.replace(".json", ".txt")), "w") as f:
                    f.write(hlo)
            out = trace_programs(program, args.trace, trace_dir, per=eng.burst, hlo=hlo)
        else:
            out = time_decode(eng)
        name = ("trace_decode" if args.trace else "time_decode") + suffix
    elif args.chunk:
        eng = build_engine(load_conf(args.config))
        out = trace_programs(chunk_program(eng), args.trace, trace_dir) if args.trace else time_chunks(eng)
        name = ("trace_chunk" if args.trace else "time_chunk") + suffix
    elif args.trace:
        out = trace_programs(prefill_program(build_engine(load_conf(args.config))), args.trace, trace_dir)
        name = "trace_prefill" + suffix
    else:
        out, name = time_programs(build_engine(load_conf(args.config))), "time_prefill" + suffix
    with open(os.path.join(ROOT, "chiprun_out", name), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
