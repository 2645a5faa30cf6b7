#!/usr/bin/env python3
"""The device-queue ledger held against the chip's own trace, for one cell of the benchmark.

    python3 tools/check_device_queue.py --workload qwen2.5-7b-batch-saturated   # on a TPU; ~6 minutes

``tick_stats()["device_queue"]`` (``serving/obs.py`` ``LoopLedger``) cuts the device's time into
segments with two host clock reads: the dispatches between two results the engine's thread waited
for, from the previous result's arrival to this one's.  Whether such a segment is the time its
programs took on the device is a question only the device's trace answers, and ``benchmarks/run.py``
deletes its trace, so this is a session of its own: it boots the cell's configuration as
``benchmarks/sut.py`` does (seeded checkpoint, the program's registry, warm-up, ``run_server``),
has a child of its own (this file with ``--client``: ``benchmarks/driver.py``, no JAX) send the
cell's traffic over HTTP, lets it settle, wraps ``--trace-seconds`` in ``jax.profiler`` and keeps
the ledger's ring of segments (``engine.loop_stats(recent=True)``) from the moment the span ends.

The join is by number: every segment ends at a ``dabt/tick_block`` host event that carries the
``seq`` of the last dispatch it covers, on the profiler's clock, which is the device's.  A program
run on the device (line ``XLA Modules``) belongs to the segment in whose (previous result, this
result] its end falls.  For each segment the ledger's ``ready - start`` is held against the
device's own "end of its last program - end of the previous segment's last program" (its runs and
the gap before them), by kind; the sum of all segments and idle time against the device's span; and
the kinds against the programs' names.  Prints a summary as its last line and writes every segment
to ``chiprun_out/check_device_queue.<workload>.json``.
"""

import argparse
import asyncio
import json
import os
import shutil
import signal
import statistics
import subprocess
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
MODEL = "bench"
# what a kind's group runs on the device, by a word of the program's name
EXPECT = {"tick": ("tick",), "prefill": ("prefill",), "suffix": ("prefill_suffix",), "chunk": ("prefill_chunk",),
          "piggyback": ("tick",), "spec": ("tick",)}


def client(args) -> int:
    """The cell's traffic from a process that holds no chip, until it is killed."""
    from benchmarks import driver, run
    from benchmarks.traffic_gen import Plan

    _, _, _, mix, _ = run.load_cell(args.workload, args.benchmark_json, ROOT)
    asyncio.run(driver.run(args.base, MODEL, Plan(mix, args.seed, args.seconds), lambda: None, lambda: None))
    return 0


def _wait_healthy(base: str, deadline: float) -> None:
    while time.monotonic() < deadline:
        try:
            with urllib.request.urlopen(base + "/healthz", timeout=2.0) as r:
                if r.status == 200:
                    return
        except OSError:
            time.sleep(0.1)
    raise SystemExit("the server never answered /healthz")


def read_trace(path: str):
    """Program runs on the device, in order, and the engine thread's ``dabt/*`` events that carry a
    ``seq``: ``(modules [(name, start_s, end_s)], host {event name: [(seq, start_s, end_s)]})``."""
    from jax.profiler import ProfileData

    from benchmarks import trace_reduce

    modules, host = [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:0"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = sorted((trace_reduce.program_name(e.name), e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                                     for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("dabt/"):
                        seq = dict(e.stats).get("seq")
                        if seq is not None:
                            host.setdefault(e.name, []).append((int(seq), e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9))
    return modules, host


def join(ring, modules, host):
    """One row a segment whose result and whose predecessor's were read inside the trace."""
    ready_p = {seq: end for seq, _, end in host.get("dabt/tick_block", [])}  # profiler's clock
    inside = [seg for seg in ring if seg["seq"][1] in ready_p]
    ends = sorted(m[2] for m in modules)
    rows = []
    for before, seg in zip(inside, inside[1:]):
        lo, hi = ready_p[before["seq"][1]], ready_p[seg["seq"][1]]
        if seg["seq"][0] != before["seq"][1] + 1 or not ends or lo < ends[0]:
            continue  # a marker in between closed nothing, or the trace began after the predecessor's programs
        mine = [m for m in modules if lo < m[2] <= hi]
        earlier = [e for e in ends if e <= lo]
        if not mine or not earlier:
            continue
        dev_s = mine[-1][2] - earlier[-1]  # its programs and the gap before them
        led_s = seg["ready"] - seg["start"]
        wanted = [w for kind in seg["kinds"] for w in EXPECT.get(kind, ())]
        rows.append({
            "seq": seg["seq"], "key": seg["key"], "kind": seg["key"].partition(":")[0], "kinds": seg["kinds"],
            "ledger_s": led_s, "device_s": dev_s,
            "err": (led_s - dev_s) / dev_s, "programs": [m[0] for m in mine], "busy_s": sum(m[2] - m[1] for m in mine),
            "names_match": all(any(w in m[0] for m in mine) for w in wanted),
            "clock_offset_s": hi - seg["ready"],  # profiler's clock - engine's: one constant if both tick alike
            "queue_was_empty": seg["start"] > before["ready"] + 1e-9,
        })
    return rows


def summarise(rows):
    out = {}
    for kind in sorted({r["kind"] for r in rows}):
        mine = [r for r in rows if r["kind"] == kind]
        errs = [abs(r["err"]) for r in mine]
        out[kind] = {"n": len(errs), "abs_err_median": statistics.median(errs), "abs_err_max": max(errs),
                     "within_1pct": sum(e <= 0.01 for e in errs) / len(errs), "within_3pct": sum(e <= 0.03 for e in errs) / len(errs),
                     "mean_device_ms": statistics.fmean(r["device_s"] for r in mine) * 1e3}
    total_led, total_dev = sum(r["ledger_s"] for r in rows), sum(r["device_s"] for r in rows)
    offs = [r["clock_offset_s"] for r in rows]
    return {"segments": len(rows), "by_kind": out, "sum_ledger_s": total_led, "sum_device_s": total_dev,
            "sum_err": (total_led - total_dev) / total_dev if total_dev else None,
            "names_match": all(r["names_match"] for r in rows), "busy_share": sum(r["busy_s"] for r in rows) / total_dev if total_dev else None,
            "clock_offset_spread_us": (max(offs) - min(offs)) * 1e6 if offs else None,
            "segments_after_an_empty_queue": sum(r["queue_was_empty"] for r in rows)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=4242000001)
    ap.add_argument("--settle", type=float, default=20.0, help="seconds of traffic before the traced span")
    ap.add_argument("--trace-seconds", type=float, default=3.0)
    ap.add_argument("--client", action="store_true", help="internal: be the traffic's sender")
    ap.add_argument("--base")
    ap.add_argument("--seconds", type=float, default=600.0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="the control flow on a CPU (with --benchmark-json benchmarks/tests/rehearsal.json): its trace has no device plane, so nothing is joined")
    ap.add_argument("--benchmark-json", default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args()
    if args.client:
        return client(args)

    from benchmarks import families, run, sut

    _, cell, conf, _, data_dir = run.load_cell(args.workload, args.benchmark_json, ROOT)
    cache = os.path.join(ROOT, ".cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(cache, "benchmarks_xla")  # the benchmark's own, so its programs are shared
    os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
    family = families.load(conf, data_dir)
    device = sut.device_info(int(cell["chips"]), args.rehearsal)
    sut.enable_compile_cache()
    import jax
    import jax.numpy as jnp

    ckpt = os.path.join(cache, "benchmarks_ckpt", cell["config"])
    sut.write_checkpoint(family, conf, run.weights_seed(conf, args.seed, args.rehearsal), ckpt)
    registry = sut.boot_registry(conf, MODEL, ckpt, {})
    shutil.rmtree(ckpt, ignore_errors=True)
    engine = registry.get_generator(MODEL)
    jnp.asarray([False] * int(conf["serving"]["max_slots"])).block_until_ready()  # as sut.py: not first met in traffic
    port = run._free_port()
    base = f"http://127.0.0.1:{port}"
    trace_dir = os.path.join(cache, "check_device_queue_trace", args.workload)
    shutil.rmtree(trace_dir, ignore_errors=True)
    kept = {}

    def session():
        sender = None
        try:
            _wait_healthy(base, time.monotonic() + 120.0)
            sender = subprocess.Popen([sys.executable, os.path.abspath(__file__), "--client", "--base", base, "--workload",
                                       args.workload, "--seed", str(args.seed), "--seconds", str(args.seconds),
                                       "--benchmark-json", args.benchmark_json], cwd=ROOT)
            time.sleep(args.settle)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            kept["q0"] = engine.loop_stats()["device_queue"]
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            time.sleep(args.trace_seconds)
            kept["stats"] = engine.loop_stats(recent=True)  # the ring, before later segments push these out
            t = time.monotonic()
            jax.profiler.stop_trace()
            kept["stop_trace_s"] = time.monotonic() - t
        except BaseException as e:  # the main thread is blocked in the server: say it and stop the server
            kept["error"] = repr(e)
        finally:
            if sender is not None:
                sender.kill()
                sender.wait()
            os.kill(os.getpid(), signal.SIGTERM)  # the server's own graceful stop

    from django_assistant_bot_tpu.serving.server import run_server

    watcher = threading.Thread(target=session, name="check-session", daemon=True)
    watcher.start()
    run_server(registry=registry, host="127.0.0.1", port=port, drain_deadline_s=5.0)
    watcher.join(timeout=30.0)
    if "error" in kept or "stats" not in kept:
        raise SystemExit(f"the session failed: {kept.get('error', 'no ring was kept')}")

    from benchmarks import trace_reduce

    modules, host = read_trace(trace_reduce.find_xplane(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    ring = kept["stats"]["device_queue_recent"]
    rows = join(ring, modules, host)
    q0, q1 = kept["q0"], kept["stats"]["device_queue"]
    window = {k: {f: q1[k][f] - q0[k][f] for f in q1[k]} for k in q1 if k not in ("idle", "markers") and q1[k]["n"] != q0[k]["n"]}
    summary = dict(summarise(rows), workload=args.workload, seed=args.seed, device=device, ring=len(ring),
                   modules_in_trace=len(modules), tick_block_events=len(host.get("dabt/tick_block", [])),
                   issue_events=len(host.get("dabt/tick_issue", [])) + len(host.get("dabt/prefill_dispatch", [])),
                   stop_trace_s=kept["stop_trace_s"], ledger_over_the_span=window,
                   idle_over_the_span_s=q1["idle"]["s"] - q0["idle"]["s"],
                   markers_over_the_span={k: q1["markers"][k] - q0["markers"][k] for k in q1["markers"]})
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", f"check_device_queue.{args.workload}.json"), "w") as f:
        json.dump({"summary": summary, "segments": rows}, f, indent=1)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
