#!/usr/bin/env python3
"""The system under test, as the benchmark holds it: the one process that
holds the chip, and the only file that imports the program
(``django_assistant_bot_tpu``).

``run.py`` (which never touches JAX) starts this file as a child, gives it one
line of JSON (the job) on its standard input and reads JSON lines back.  The
child

1. names its device and refuses anything but a TPU with enough chips;
2. draws the configuration's weights on the device (``served_params`` of the
   configuration's family, ``benchmarks/families/``, from the job's
   ``weights_seed``: the configuration file's ``weights.seed``, never the
   run's ``--seed``), wraps the tree in the program's types, saves it with the
   program's own ``save_model`` as a native checkpoint under the checkout, and
   frees it;
3. boots the program the way ``cli serve --config ... --warmup`` does: the
   persistent compile cache, a ``ModelRegistry`` from a model config whose
   entries are the configuration file's ``serving`` block word for word
   (``ModelSpec`` defaults for everything it does not name), which loads the
   checkpoint through ``load_model``, places it, builds the engine and warms
   it up, and then ``run_server`` on a local port, blocking;
4. meanwhile answers commands from ``run.py`` on a side thread: counter
   snapshots (the engine's public ``tick_stats``/``kv_stats``/``wait_stats``,
   twelve picked keys and the whole dictionaries),
   the profiler around the traced part of the window, gauge samples;
5. on ``finish`` sends itself SIGTERM (the server drains and stops its
   engines), frees the program and checks the sample ``run.py`` wrote against
   the family's plain reference (``benchmarks/correct.py``), over weights drawn
   again from ``weights_seed``;
6. ends itself when the control pipe reaches end-of-file before ``finish``:
   ``run.py`` is gone, and nothing may go on holding the chip and the port.

Nothing here computes a metric or decides ``correct``.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import shutil
import signal
import sys
import threading
import time
import traceback
from typing import Any, Dict, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def device_info(chips: int, rehearsal: bool) -> Dict[str, Any]:
    """The device as JAX reports it.  Anything but a TPU with enough chips is
    refused, unless this is the tests' rehearsal on the CPU."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if rehearsal:
        return info
    if info["platform"] != "tpu":
        raise SystemExit(f"benchmark needs a TPU, JAX found {info}: refusing to run")
    if info["count"] < chips:
        raise SystemExit(f"cell needs {chips} chip(s), JAX found {info['count']}: refusing to run")
    return info


def enable_compile_cache() -> Optional[str]:
    """The program's own switch (it takes the directory ``run.py`` named in
    ``JAX_COMPILATION_CACHE_DIR``), and then every program kept, however fast
    it compiled: the program keeps only those that took 0.5 s, so a small
    program first met in warm traffic compiled anew in every run and stalled
    the engine long enough to queue requests into the window (PR 23)."""
    import jax

    from django_assistant_bot_tpu.utils.compile_cache import enable_persistent_compile_cache

    path = enable_persistent_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileCounter:
    """Counts the programs JAX builds (compiled or fetched from the cache)."""

    def __init__(self):
        from jax import monitoring

        self.n = 0
        self.misses = 0
        self.names = []
        monitoring.register_event_duration_secs_listener(self._dur)
        monitoring.register_event_listener(self._ev)

    def _dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            # which line of the program (or the benchmark) asked for it
            frames = [f for f in traceback.extract_stack() if "site-packages" not in f.filename]
            where = f"{os.path.basename(frames[-1].filename)}:{frames[-1].lineno}" if frames else "?"
            self.names.append(f"{kw.get('fun_name', '?')} @ {where}")

    def _ev(self, event, **kw):
        if event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def wrap_params(tree, act):
    """A family's tree in the program's types, at any depth: an ``(int8
    payload, float32 scale)`` tuple becomes a ``QTensor``, every other leaf is
    cast to the serving dtype."""
    import jax

    from django_assistant_bot_tpu.ops.quant import QTensor

    quantised = lambda x: isinstance(x, tuple)
    return jax.tree.map(lambda leaf: QTensor(q=leaf[0], scale=leaf[1]) if quantised(leaf) else leaf.astype(act),
                        tree, is_leaf=quantised)


def write_checkpoint(family, conf: Dict[str, Any], seed: int, path: str) -> None:
    """The family's seeded weights, in the program's parameter layout, written
    by the program's ``save_model``: what ``ModelSpec.checkpoint`` loads.  Made
    on the device from ``seed`` (the weights' seed) and freed before the boot."""
    import jax
    import jax.numpy as jnp

    from django_assistant_bot_tpu.checkpoint import save_model
    from django_assistant_bot_tpu.models.config import DecoderConfig

    act = getattr(jnp, conf["serving"].get("dtype", "bfloat16"))
    params = wrap_params(family.served_params(conf, seed), act)
    jax.block_until_ready(params)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    # the checkpoint's context cap is the deployment's: the engine clamps to it anyway
    cfg = dataclasses.replace(DecoderConfig.from_hf(conf["hf"], dtype=act), max_seq_len=int(conf["serving"]["max_seq_len"]))
    save_model(path, "decoder", cfg, params, meta={"benchmark_weights_seed": int(seed)})


def boot_registry(conf: Dict[str, Any], model: str, checkpoint: str, overrides: Dict[str, Any]):
    """``cli serve``'s boot: a registry from a model config (here: the
    configuration file's ``serving`` block plus the checkpoint's path)."""
    from django_assistant_bot_tpu.serving.registry import ModelRegistry

    spec = {k: v for k, v in conf["serving"].items() if k != "why"}
    spec.update(kind="decoder", checkpoint=checkpoint, **overrides)
    return ModelRegistry.from_config({model: spec})


def engine_programs(family, conf: Dict[str, Any], sharding):
    """For ``sizing.py``: the program's decode step, chunk prefill and a full
    admission wave of suffix prefill at the configuration's geometry, over the
    family's own parameter tree, as (name, jitted function, argument shapes).
    Shapes only: the chip is described, not attached."""
    import jax
    import jax.numpy as jnp

    from django_assistant_bot_tpu.models import llama
    from django_assistant_bot_tpu.models.config import DecoderConfig

    cfg = DecoderConfig.from_hf(conf["hf"], dtype=getattr(jnp, conf["serving"].get("dtype", "bfloat16")))
    s = conf["serving"]
    slots, page, pages = int(s["max_slots"]), int(s["kv_page_size"]), int(s["kv_pages"])
    blocks, chunk = int(s["max_seq_len"]) // page, int(s["chunk_size"])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    params = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          jax.eval_shape(lambda: wrap_params(family.served_params(conf, 0), cfg.dtype)))
    pool = (cfg.num_layers, pages, cfg.num_kv_heads, page, cfg.head_dim)
    cache = llama.PagedKVCache(k=sds(pool, cfg.dtype), v=sds(pool, cfg.dtype), lengths=sds((slots,), jnp.int32))
    i32 = lambda *shape: sds(shape, jnp.int32)
    return [
        ("decode_step_paged, %d slots" % slots,
         jax.jit(lambda p, t, c, b: llama.decode_step_paged(p, cfg, t, c, b)),
         (params, i32(slots), cache, i32(slots, blocks))),
        ("prefill_chunk_paged, 1 x %d" % chunk,
         jax.jit(lambda p, i, c, bt, sl, st, v: llama.prefill_chunk_paged(p, cfg, i, c, bt, sl, st, v), donate_argnums=(2,)),
         (params, i32(1, chunk), cache, i32(blocks), i32(), i32(), i32())),
        ("prefill_suffix_paged, %d x %d" % (slots, chunk),
         jax.jit(lambda p, i, c, bt, sl, st, v: llama.prefill_suffix_paged(p, cfg, i, c, bt, sl, st, v), donate_argnums=(2,)),
         (params, i32(slots, chunk), cache, i32(slots, blocks), i32(slots), i32(slots), i32(slots))),
    ]


DROP = object()


def json_safe(x):
    """``x`` as JSON can carry it: finite numbers, strings, None, and dicts and
    lists of those; anything else is dropped (``DROP``)."""
    if x is None or isinstance(x, (bool, str, int)):
        return x
    if isinstance(x, float):
        return x if math.isfinite(x) else DROP
    if isinstance(x, dict):
        return {str(k): v for k, v in ((k, json_safe(v)) for k, v in x.items()) if v is not DROP}
    if isinstance(x, (list, tuple)):
        return [v for v in map(json_safe, x) if v is not DROP]
    if getattr(x, "shape", None) == () and hasattr(x, "item"):  # a NumPy or JAX scalar
        return json_safe(x.item())
    return DROP


def counters(engine) -> Dict[str, Any]:
    """One snapshot of the program's own counters (all public calls): twelve
    picked keys, flat, and the whole ``tick_stats``/``kv_stats``/``wait_stats``
    dictionaries under those names, so that a reader added later reads a
    counter this file has never heard of."""
    ts = engine.tick_stats()
    kv = ts.get("kv", {})
    sup = ts.get("supervision", {})
    out = {
        "ticks": ts["ticks"],
        "tick_issue_total_ms": ts["issue_ms"] * ts["ticks"],
        "tick_block_ms_avg": ts["block_ms"],
        "decode_steps": ts.get("decode_steps"),
        "prefill_chunks_piggybacked": ts.get("prefill_chunks_piggybacked", 0),
        "prefix_hits": kv.get("prefix_hits", 0),
        "prefix_misses": kv.get("prefix_misses", 0),
        "kv_pages_total": kv.get("kv_pages_total"),
        "kv_pages_used": kv.get("kv_pages_used"),
        "kv_evictions": kv.get("kv_evictions", 0),
        "engine_restarts": sup.get("engine_restarts", 0),
        "poisoned_requests": sup.get("poisoned_requests", 0),
    }
    out["tick_stats"] = json_safe(ts)
    out["kv_stats"] = json_safe(engine.kv_stats())
    if engine.scheduler is not None:
        waits = engine.scheduler.wait_stats()
        w = waits.get("interactive")
        if w and w["n"]:
            out["sched_wait_p95_ms"] = w["p95_ms"]
        out["wait_stats"] = json_safe(waits)
    return out


# how long a child whose parent is gone gives the server's graceful stop before it just exits
ORPHAN_GRACE_S = 8.0


class Control(threading.Thread):
    """Answers ``run.py``'s commands, one JSON line each, while the main
    thread boots and serves; the engine is bound once there is one (``run.py``
    asks nothing before it is told of the boot).  End-of-file before
    ``finish`` means ``run.py`` is gone: the child ends itself, whatever the
    main thread is doing."""

    def __init__(self, say, compiles: CompileCounter):
        super().__init__(daemon=True, name="bench-control")
        self.say, self.engine, self.compiles = say, None, compiles
        self.finish: Optional[Dict[str, Any]] = None
        self.checked = False  # the main thread has sent the check's numbers: end-of-file is the run's end
        self.samples: Dict[str, list] = {"rows_active": [], "kv_pages_used": []}
        self._sampling = threading.Event()

    def _sample(self):
        """Every 100 ms: slots in use and pages in use (public gauges)."""
        while self._sampling.is_set():
            self.samples["rows_active"].append(self.engine.num_active)
            self.samples["kv_pages_used"].append(self.engine.kv_stats().get("kv_pages_used", 0))
            time.sleep(0.1)

    def run(self):
        import jax

        from benchmarks import trace_reduce

        for line in sys.stdin:
            msg = json.loads(line)
            cmd, reply = msg["cmd"], {"ok": True}
            if cmd == "snapshot":
                peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices())
                reply = {"counters": counters(self.engine), "compiles": self.compiles.n,
                         "cache_misses": self.compiles.misses, "programs": list(self.compiles.names),
                         "peak_bytes": int(peak), "t": time.monotonic()}
            elif cmd == "trace_start":
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                shutil.rmtree(msg["dir"], ignore_errors=True)
                jax.profiler.start_trace(msg["dir"], profiler_options=opts)
                with jax.profiler.TraceAnnotation(trace_reduce.OPEN_MARK):
                    pass
                reply = {"t": time.monotonic()}
                self._sampling.set()
                threading.Thread(target=self._sample, daemon=True).start()
            elif cmd == "trace_mark_close":
                with jax.profiler.TraceAnnotation(trace_reduce.CLOSE_MARK):
                    pass
                reply = {"t": time.monotonic()}
                self._sampling.clear()
            elif cmd == "trace_stop":
                jax.profiler.stop_trace()
                reply = {"samples": self.samples}
            elif cmd == "finish":
                self.finish = msg
                self.say(reply)
                os.kill(os.getpid(), signal.SIGTERM)  # the server's own graceful stop
                continue
            self.say(reply)
        if self.checked:
            return
        # end-of-file and nobody left to read a result.  During the check: out at once.  Before
        # it, SIGTERM ends a boot at once and makes a server drain (and the main thread then
        # returns); whatever is still there after the grace is cut
        print("control pipe closed before the run's end: run.py is gone, ending", file=sys.stderr, flush=True)
        if self.finish is not None:
            os._exit(3)
        cut = threading.Timer(ORPHAN_GRACE_S, os._exit, (3,))
        cut.daemon = True
        cut.start()
        os.kill(os.getpid(), signal.SIGTERM)


def main() -> int:
    t_start = time.monotonic()
    proto = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)  # whatever the program prints goes to the log, not into the protocol
    sys.stdout = sys.stderr

    def say(obj: Dict[str, Any]) -> None:
        proto.write(json.dumps(obj) + "\n")
        proto.flush()

    job = json.loads(sys.stdin.readline())
    # the weights are the configuration's; the run's seed is the traffic's and reaches nothing here
    conf, weights_seed = job["conf"], int(job["weights_seed"])
    print(f"job: configuration {conf.get('name')!r}, weights' seed {weights_seed}, run's seed {job.get('seed')}",
          file=sys.stderr, flush=True)
    from benchmarks import families

    family = families.load(conf, job["data_dir"])
    device = device_info(int(job["chips"]), bool(job["rehearsal"]))
    t_device = time.monotonic() - t_start
    enable_compile_cache()
    compiles = CompileCounter()
    control = Control(say, compiles)
    control.start()

    import jax
    import jax.numpy as jnp

    t = time.monotonic()
    write_checkpoint(family, conf, weights_seed, job["checkpoint"])
    gc.collect()
    t_weights = time.monotonic() - t
    t = time.monotonic()
    registry = boot_registry(conf, job["model"], job["checkpoint"], job.get("spec_overrides") or {})
    shutil.rmtree(job["checkpoint"], ignore_errors=True)  # loaded and placed: 7 GB of disk given back
    engine = registry.get_generator(job["model"])
    # two eager conversions the engine's warm-up does not reach: the slot mask it
    # uploads from a list of bools, and the one-page index lists of a copy-on-write
    # clone (first prefix hit).  Each is a program of its own that would otherwise
    # be first met in warm traffic or inside the window (PR 23, engine.py:2230).
    jnp.asarray([False] * int(conf["serving"]["max_slots"])).block_until_ready()
    jnp.asarray([0], jnp.int32).block_until_ready()
    t_boot = time.monotonic() - t
    control.engine = engine
    say({"event": "booting", "device": device, "boot_s": registry.boot_s.get(job["model"]),
         "programs_in_setup": compiles.n,
         "setup_parts_s": {"imports_and_device": t_device, "weights_and_checkpoint": t_weights,
                           "load_place_warmup": t_boot}})

    from django_assistant_bot_tpu.serving.server import run_server

    run_server(registry=registry, host="127.0.0.1", port=int(job["port"]), drain_deadline_s=5.0)

    # -- the server has stopped its engines: free the program, then the reference
    finish = control.finish
    control.engine = None
    del engine, registry
    gc.collect()
    jax.clear_caches()
    if not finish or not finish.get("sample"):
        return 0
    from benchmarks import correct

    t = time.monotonic()
    with open(finish["sample"]) as f:
        picked = json.load(f)
    controls = bool(finish.get("controls"))
    numbers = correct.logit_gaps(family, conf, weights_seed, picked, controls,
                                 dump=finish["sample"] + ".gaps.json" if controls else "") if picked else {}
    control.checked = True
    say({"event": "checked", "numbers": numbers, "check_s": time.monotonic() - t})
    return 0


if __name__ == "__main__":
    sys.exit(main())
