#!/usr/bin/env python3
"""The system under test, as the benchmark holds it: the one process that
holds the chip, and the only file that imports the program
(``django_assistant_bot_tpu``).

``run.py`` (which never touches JAX) starts this file as a child, gives it one
line of JSON (the job) on its standard input and reads JSON lines back.  The
child

1. names its device and refuses anything but a TPU with enough chips;
2. draws the seeded weights on the device (``benchmarks/weights.py``), saves
   them with the program's own ``save_model`` as a native checkpoint under the
   checkout, and frees them;
3. boots the program the way ``cli serve --config ... --warmup`` does: the
   persistent compile cache, a ``ModelRegistry`` from a model config whose
   entries are the configuration file's ``serving`` block word for word
   (``ModelSpec`` defaults for everything it does not name), which loads the
   checkpoint through ``load_model``, places it, builds the engine and warms
   it up, and then ``run_server`` on a local port, blocking;
4. meanwhile answers commands from ``run.py`` on a side thread: counter
   snapshots (the engine's public ``tick_stats``/``kv_stats``/``wait_stats``),
   the profiler around the traced part of the window, gauge samples;
5. on ``finish`` sends itself SIGTERM (the server drains and stops its
   engines), frees the program and checks the sample ``run.py`` wrote against
   the plain reference (``benchmarks/correct.py``).

Nothing here computes a metric or decides ``correct``.
"""

from __future__ import annotations

import dataclasses
import gc
import json
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


def write_checkpoint(conf: Dict[str, Any], seed: int, path: str) -> None:
    """The benchmark's seeded weights, in the program's parameter layout,
    written by the program's ``save_model``: what ``ModelSpec.checkpoint``
    loads.  Made on the device in one jitted call and freed before the boot."""
    import jax
    import jax.numpy as jnp

    from benchmarks import weights
    from django_assistant_bot_tpu.checkpoint import save_model
    from django_assistant_bot_tpu.models.config import DecoderConfig
    from django_assistant_bot_tpu.ops.quant import QTensor

    act = getattr(jnp, conf["serving"].get("dtype", "bfloat16"))
    w = weights.stacked(conf["hf"], seed, conf["weights"]["head_ids"])
    layers = {name: QTensor(q=leaf[0], scale=leaf[1]) if isinstance(leaf, tuple) else leaf.astype(act)
              for name, leaf in w["layers"].items()}
    params = {"layers": layers, **{k: v.astype(act) for k, v in w["top"].items()}}
    jax.block_until_ready(params)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    # the checkpoint's context cap is the deployment's: the engine clamps to it anyway
    cfg = dataclasses.replace(DecoderConfig.from_hf(conf["hf"], dtype=act), max_seq_len=int(conf["serving"]["max_seq_len"]))
    save_model(path, "decoder", cfg, params, meta={"benchmark_seed": int(seed)})


def boot_registry(conf: Dict[str, Any], model: str, checkpoint: str, overrides: Dict[str, Any]):
    """``cli serve``'s boot: a registry from a model config (here: the
    configuration file's ``serving`` block plus the checkpoint's path)."""
    from django_assistant_bot_tpu.serving.registry import ModelRegistry

    spec = {k: v for k, v in conf["serving"].items() if k != "why"}
    spec.update(kind="decoder", checkpoint=checkpoint, **overrides)
    return ModelRegistry.from_config({model: spec})


def counters(engine) -> Dict[str, Any]:
    """One flat snapshot of the program's own counters (all public calls)."""
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
    if engine.scheduler is not None:
        w = engine.scheduler.wait_stats().get("interactive")
        if w and w["n"]:
            out["sched_wait_p95_ms"] = w["p95_ms"]
    return out


class Control(threading.Thread):
    """Answers ``run.py``'s commands, one JSON line each, while the main
    thread serves."""

    def __init__(self, say, engine, compiles: CompileCounter):
        super().__init__(daemon=True, name="bench-control")
        self.say, self.engine, self.compiles = say, engine, compiles
        self.finish: Optional[Dict[str, Any]] = None
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
                return
            self.say(reply)


def main() -> int:
    t_start = time.monotonic()
    proto = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)  # whatever the program prints goes to the log, not into the protocol
    sys.stdout = sys.stderr

    def say(obj: Dict[str, Any]) -> None:
        proto.write(json.dumps(obj) + "\n")
        proto.flush()

    job = json.loads(sys.stdin.readline())
    conf, seed = job["conf"], int(job["seed"])
    device = device_info(int(job["chips"]), bool(job["rehearsal"]))
    t_device = time.monotonic() - t_start
    enable_compile_cache()
    compiles = CompileCounter()

    import jax
    import jax.numpy as jnp

    t = time.monotonic()
    write_checkpoint(conf, seed, job["checkpoint"])
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
    say({"event": "booting", "device": device, "boot_s": registry.boot_s.get(job["model"]),
         "programs_in_setup": compiles.n,
         "setup_parts_s": {"imports_and_device": t_device, "weights_and_checkpoint": t_weights,
                           "load_place_warmup": t_boot}})

    control = Control(say, engine, compiles)
    control.start()
    from django_assistant_bot_tpu.serving.server import run_server

    run_server(registry=registry, host="127.0.0.1", port=int(job["port"]), drain_deadline_s=5.0)

    # -- the server has stopped its engines: free the program, then the reference
    finish = control.finish
    del control, engine, registry
    gc.collect()
    jax.clear_caches()
    if not finish or not finish.get("sample"):
        return 0
    from benchmarks import correct

    t = time.monotonic()
    with open(finish["sample"]) as f:
        picked = json.load(f)
    controls = bool(finish.get("controls"))
    numbers = correct.logit_gaps(conf, seed, picked, controls,
                                 dump=finish["sample"] + ".gaps.json" if controls else "") if picked else {}
    say({"event": "checked", "numbers": numbers, "check_s": time.monotonic() - t})
    return 0


if __name__ == "__main__":
    sys.exit(main())
