#!/usr/bin/env python3
"""The benchmark's command: runs one cell once and ends in one JSON line.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything of a cell is data found by name: ``BENCHMARK.json`` names the cell's
configuration (``benchmarks/configs/``) and traffic mix (``benchmarks/traffic/``)
and the metrics it reports; the configuration names its architecture's family
(``benchmarks/families/``: seeded weights, plain reference, limits, counts);
each per-layer metric has a reader of its own in ``benchmarks/layer_metrics/``.

Two processes.  This one never touches JAX: it plans the traffic, starts the
child that holds the chip (``benchmarks/sut.py``: seeded weights to a native
checkpoint, then the program's own registry, warm-up and ``run_server``), and
is the client: it sends the mix to ``/dialog/`` over HTTP with ``stream: true``
and times every token on its own clock.  It serves warm traffic, measures for
``--seconds``, has the child stop the server, free the program and check a
sample of what the window served against the plain reference.

Every run does the same work whatever ``--seed``.  The weights are the
configuration's: drawn from the seed its file states (``weights.seed``), the
same checkpoint in every run, as a deployment has one checkpoint and many
users.  ``--seed`` reaches the traffic (which request gets which length, the
prompts' text, the arrivals: ``traffic_gen.Plan``) and the sample that
``correct`` checks, and nothing else.

However this process ends, the child does not outlive it: SIGTERM, SIGINT and
the run's own deadline raise through ``main``'s ``finally``, which kills the
child's process group, and a child whose control pipe reaches end-of-file
(this process killed outright) ends itself.

A line of diagnostics (JSON, ``"diagnostics"``) is printed before the result
line in every run, traced or not.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse
import asyncio
import importlib.util
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# the traced part of the window.  Stopping the profiler costs about 30 s for each
# second traced at 7B (a 6 s part made a warm traced run 350 s long, PR 23), and a
# run has to end within 360 s
TRACE_SECONDS = 3.0
# the traced part ends this long before the window; the trace is written out only
# after the window has closed (writing it stalled the engine for 6.5 s mid-window once)
TRACE_BEFORE_CLOSE_S = 0.5
# A run ends itself just before the driver would: it has to be done within 360 s of
# its start, and a cell's first run in a checkout, which compiles, within 1,200.  At
# the deadline the run kills its child and exits with no result, as it does on SIGTERM.
DEADLINE_S = 355.0
FIRST_RUN_DEADLINE_S = 1190.0
MODEL = "bench"  # the name requests address the served model by


def _load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, bench_path: str, root: str):
    bench = _load_json(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in {bench_path}: {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    data = os.path.join(root, bench["paths"][0])  # where this benchmark's data files live
    conf = _load_json(os.path.join(root, cfg_entry["file"]))
    mix = _load_json(os.path.join(data, "traffic", cell["traffic"] + ".json"))
    return bench, cell, conf, mix, data


def weights_seed(conf, run_seed: int, rehearsal: bool) -> int:
    """The seed the configuration's weights are drawn from: its file's
    ``weights.seed``.  The one place that reads the key; the child and, through
    it, the reference are handed this number and never ``--seed``.  There is no
    default.  (Only the tests' CPU rehearsal lets a configuration from before
    the key draw from the run's seed, as it did then: ``tests/data/`` is not
    the benchmark's to edit.)"""
    seed = conf["weights"].get("seed")
    if isinstance(seed, int) and not isinstance(seed, bool) and seed >= 0:
        return seed
    if seed is None and rehearsal:
        print(f"configuration {conf.get('name')!r} states no weights.seed: the rehearsal draws its weights "
              f"from --seed {run_seed}", file=sys.stderr)
        return int(run_seed)
    raise SystemExit(f"configuration {conf.get('name')!r}: weights.seed has to be a whole number, the seed its "
                     f"weights are drawn from in every run (got {seed!r}); there is no default")


def load_family(conf, data_dir: str):
    """The configuration's family module; a family that has no file ends the
    run here, before the child that holds the chip is started."""
    from benchmarks import families

    before = "jax" in sys.modules  # only ever true in a test's process
    family = families.load(conf, data_dir)
    if "jax" in sys.modules and not before:
        raise SystemExit(f"{family.__file__} imports JAX as it is loaded: this process never touches JAX, "
                         "import it inside the functions")
    return family


def metrics_for(bench, group: str, cell_name: str):
    return [m for m in bench[group] if "workloads" not in m or cell_name in m["workloads"]]


def read_layer_metric(name: str, ctx, directory: str):
    path = os.path.join(directory, name + ".py")
    spec = importlib.util.spec_from_file_location("layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


class Child:
    """The process that holds the chip, and the line protocol with it."""

    def __init__(self, sut_path: str, job: dict, log_path: str):
        os.makedirs(os.path.dirname(log_path), exist_ok=True)
        self.log_path = log_path
        self.log = open(log_path, "w")
        # a process group of its own, so that whatever the child starts ends with it
        self.proc = subprocess.Popen([sys.executable, sut_path], cwd=ROOT, stdin=subprocess.PIPE,
                                     stdout=subprocess.PIPE, stderr=self.log, text=True, bufsize=1,
                                     start_new_session=True)
        self.proc.stdin.write(json.dumps(job) + "\n")
        self.proc.stdin.flush()
        self.turn = threading.Lock()  # one question and its answer at a time

    def read(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            self.fail(f"the serving child ended (rc={self.proc.wait()}) before answering")
        return json.loads(line)

    def ask(self, cmd: str, **kw) -> dict:
        with self.turn:
            self.proc.stdin.write(json.dumps(dict(kw, cmd=cmd)) + "\n")
            self.proc.stdin.flush()
            return self.read()

    def fail(self, why: str):
        self.stop()
        with open(self.log_path, errors="replace") as f:
            tail = f.read()[-6000:]
        raise SystemExit(f"{why}\n--- {self.log_path} (tail) ---\n{tail}")

    def stop(self, timeout: float = 120.0) -> int:
        """Waits for the child to end (end-of-file on its control pipe tells it
        to); kills it when it does not."""
        try:
            self.proc.stdin.close()
        except OSError:
            pass
        try:
            rc = self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            rc = self.kill()
        self.log.close()
        return rc

    def kill(self) -> int:
        """SIGKILL to the child's whole group, and the wait for the child."""
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        return self.proc.wait()


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _wait_healthy(child: Child, base: str) -> dict:
    while True:
        if child.proc.poll() is not None:
            child.fail(f"the serving child ended (rc={child.proc.returncode}) before /healthz answered")
        try:
            with urllib.request.urlopen(base + "/healthz", timeout=2.0) as r:
                if r.status == 200:
                    return json.loads(r.read())
        except OSError:
            pass
        time.sleep(0.05)  # the run's deadline ends a boot that never answers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tests only: allow a CPU and say so in the result's device")
    ap.add_argument("--benchmark-json", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--data-root", default=ROOT,
                    help="tests only: where the paths inside the benchmark JSON are resolved")
    ap.add_argument("--sut", default=os.path.join(ROOT, "benchmarks", "sut.py"),
                    help="tests only: the child to start in place of benchmarks/sut.py (one that breaks the timed path)")
    ap.add_argument("--controls", action="store_true",
                    help="never set by the driver: also read the reference-side controls (int4 weights, float8 keys and values) beside the program's numbers")
    ap.add_argument("--spec", action="append", default=[], metavar="KEY=JSON",
                    help="never set by the driver: override one field of the model spec, e.g. kv_cache_dtype='\"fp8\"': the program's own lower-precision path as the control")
    args = ap.parse_args(argv)

    bench, cell, conf, mix, data_dir = load_cell(args.workload, args.benchmark_json, args.data_root)
    cache = os.path.join(ROOT, ".cache")
    overrides = {k: json.loads(v) for k, v in (s.split("=", 1) for s in args.spec)}
    job = child_job(args, cell, conf, data_dir, cache, overrides)  # refuses a configuration that states no weights.seed
    family = load_family(conf, data_dir)
    from benchmarks.traffic_gen import Plan

    plan = Plan(mix, args.seed, args.seconds)
    if plan.longest_total() > int(conf["serving"]["max_seq_len"]) - 1:
        raise SystemExit(f"traffic reaches {plan.longest_total()} tokens, past max_seq_len")

    # the compile cache lives inside the checkout, at a fixed path; the program
    # takes the directory this variable names
    env = os.environ
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(cache, "benchmarks_xla")
    env.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
    os.makedirs(env["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
    env.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")
    base = f"http://127.0.0.1:{job['port']}"
    # a cell that has ended a run in this checkout finds its programs in the cache
    ran_before = os.path.join(cache, "benchmarks_log", args.workload + ".ran")
    # the command's clock started with the process; a test that calls main() starts one with the call
    deadline = (T_START if argv is None else time.monotonic()) + (
        DEADLINE_S if os.path.exists(ran_before) else FIRST_RUN_DEADLINE_S)
    child = None
    restore = _raise_on_signals(deadline)
    try:
        child = Child(args.sut, job, os.path.join(cache, "benchmarks_log", args.workload + ".log"))
        rc = _run_cell(args, bench, cell, conf, mix, data_dir, family, plan, child, base, cache, overrides, job)
        open(ran_before, "w").close()
        return rc
    finally:
        restore()
        if child is not None and child.proc.poll() is None:  # never leave the chip's holder behind
            child.kill()


def child_job(args, cell, conf, data_dir: str, cache: str, overrides) -> dict:
    """What the child is told: the weights' seed is the configuration's, and
    the run's seed is beside it for the log alone."""
    return {"conf": conf, "weights_seed": weights_seed(conf, args.seed, args.rehearsal), "seed": args.seed,
            "chips": int(cell["chips"]), "rehearsal": args.rehearsal, "port": _free_port(), "model": MODEL,
            "checkpoint": os.path.join(cache, "benchmarks_ckpt", cell["config"]),
            "spec_overrides": overrides, "data_dir": data_dir}


def _raise_on_signals(deadline: float):
    """SIGTERM, SIGINT and the deadline (SIGALRM) raise ``SystemExit`` in the
    main thread, so that ``main``'s ``finally`` runs and no result is printed.
    Returns what puts the process's handlers back (the tests call ``main``)."""
    if threading.current_thread() is not threading.main_thread():
        return lambda: None

    def ended(signum, frame):
        why = "its deadline" if signum == signal.SIGALRM else signal.Signals(signum).name
        raise SystemExit(f"run.py: ended by {why} after {time.monotonic() - T_START:.0f} s; the child is killed, no result")

    signals = (signal.SIGTERM, signal.SIGINT, signal.SIGALRM)
    before = [signal.signal(s, ended) for s in signals]
    signal.setitimer(signal.ITIMER_REAL, max(1.0, deadline - time.monotonic()))

    def restore():
        signal.setitimer(signal.ITIMER_REAL, 0.0)
        for s, h in zip(signals, before):
            signal.signal(s, h)

    return restore


def _run_cell(args, bench, cell, conf, mix, data_dir, family, plan, child, base, cache, overrides, job) -> int:
    from benchmarks import correct, driver, metrics, roofline, trace_reduce

    booting = child.read()  # the child has a device, a checkpoint, a warmed engine; or it has ended
    device = booting["device"]
    health = _wait_healthy(child, base)
    if health.get("device", {}).get("platform") != device["platform"]:
        child.fail(f"/healthz names another device than the child: {health.get('device')} / {device}")

    snap = {}
    trace_dir = os.path.join(cache, "benchmarks_trace", args.workload)

    def on_open():
        snap["s0"] = child.ask("snapshot")
        snap["setup_s"] = time.monotonic() - T_START
        print(f"window open: child {child.proc.pid} serves at {base}", file=sys.stderr, flush=True)

    def on_close():
        snap["s1"] = child.ask("snapshot")

    async def traced_part(t_open: float):
        """Profiles TRACE_SECONDS of the window, inside the child."""
        span = min(TRACE_SECONDS, max(0.5, args.seconds - 1.0))
        await asyncio.sleep(max(0.0, t_open + args.seconds - TRACE_BEFORE_CLOSE_S - span - time.monotonic()))
        loop = asyncio.get_running_loop()
        snap["trace_t0"] = (await loop.run_in_executor(None, lambda: child.ask("trace_start", dir=trace_dir)))["t"]
        await asyncio.sleep(span)
        snap["trace_t1"] = (await loop.run_in_executor(None, lambda: child.ask("trace_mark_close")))["t"]

    async def everything():
        side = asyncio.create_task(traced_part(time.monotonic() + plan.warm_s)) if args.trace else None
        res = await driver.run(base, MODEL, plan, on_open, on_close)
        if side is not None:
            await side
        return res

    res = asyncio.run(everything())
    samples = child.ask("trace_stop")["samples"] if args.trace else {"rows_active": [], "kv_pages_used": []}
    end = child.ask("snapshot")
    events, t_open, t_close = res["events"], res["t_open"], res["t_close"]
    peak = end["peak_bytes"]

    e2e = metrics.end_to_end(events, t_open, t_close)
    e2e["setup_s"] = snap["setup_s"]

    # -- the child stops the server, frees the program, then runs the reference
    picked = correct.sample(events, args.seed, int(mix.get("check_requests", 6)))
    sample_path = os.path.join(cache, "benchmarks_log", args.workload + ".sample.json")
    with open(sample_path, "w") as f:
        json.dump([{"prompt_ids": e["prompt_ids"], "tokens": e["tokens"]} for e in picked], f)
    child.ask("finish", sample=sample_path, controls=args.controls)
    checked = child.read()
    rc = child.stop()
    if rc != 0:
        raise SystemExit(f"the serving child ended with rc={rc} (log: {child.log_path})")
    numbers = {"short_outputs": e2e["short_outputs"], "prompt_mismatches": e2e["prompt_mismatches"],
               **checked["numbers"]}
    limits = correct.limits(family)
    is_correct = bool(picked) and e2e["failed"] == 0 and correct.verdict(numbers, limits)

    c0, c1 = snap["s0"]["counters"], snap["s1"]["counters"]
    ctx = {
        "cell": cell["name"], "conf": conf, "mix": mix, "device": device, "e2e": e2e,
        "events": events, "t_open": t_open, "t_close": t_close, "late_ms": res["late_ms"],
        "c0": c0, "c1": c1, "trace": None, "roofline": roofline, "family": family,
        "compiles_in_window": snap["s1"]["compiles"] - snap["s0"]["compiles"],
        "hbm_peak_bytes": peak, "samples": samples,
        "trace_span": (snap.get("trace_t0"), snap.get("trace_t1")),
    }
    layer_dir = os.path.join(data_dir, "layer_metrics")
    ctx["read"] = lambda name: read_layer_metric(name, ctx, layer_dir)  # one reader may build on another
    dev_out = dict(device, memory_peak_bytes=int(peak))
    breakdown = None
    if args.trace:
        a = snap["trace_t0"]
        spans = [(e["due"], e.get("done", e["times"][-1] if e["times"] else e["due"])) for e in events if "due" in e]

        def label(g0: float, g1: float) -> str:
            mid = a + (g0 + g1) / 2
            return "requests_in_flight" if any(s <= mid <= d for s, d in spans) else "no_request_in_flight"

        os.environ["JAX_PLATFORMS"] = "cpu"  # reading the trace file imports jax: never reach for the chip from here
        red = trace_reduce.reduce(trace_reduce.find_xplane(trace_dir), label)
        shutil.rmtree(trace_dir, ignore_errors=True)  # tens of megabytes a run: reduced, then gone
        ctx["trace"] = red
        dev_out["busy_s"] = red["busy_s"]
        dev_out["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}

    counter_metrics = {m["name"]: float(v) for m in metrics_for(bench, "per_layer", cell["name"])
                       if m["source"] == "program_counter" and (v := ctx["read"](m["name"])) is not None}
    if hasattr(family, "window_counts"):  # a family's own counts over the window, for the diagnostics line
        counter_metrics.update(family.window_counts(ctx))
    out_metrics = {}
    if args.trace:
        for m in metrics_for(bench, "per_layer", cell["name"]):
            v = ctx["read"](m["name"])
            if v is not None:
                out_metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in metrics_for(bench, "end_to_end", cell["name"]):
            out_metrics[m["name"]] = {"value": float(e2e[m["name"]]), "unit": m["unit"]}

    diag = {
        "diagnostics": cell["name"], "seed": args.seed, "weights_seed": job["weights_seed"],
        "seconds": args.seconds, "trace": args.trace,
        "compiles_in_window": ctx["compiles_in_window"],
        "cache_misses_total": end["cache_misses"],
        # programs first met after the boot (warm traffic, window): should be few and cached
        "programs_after_boot": end["programs"][booting["programs_in_setup"]:],
        "max_tick_gap_ms": metrics.max_token_gap_ms(events, t_open, t_close),
        "client_lag_max_ms": max(res["lag_ms"], default=0.0),
        # what the rate was made of: where in the window, and from how many ticks and steps
        **metrics.window_profile(events, t_open, t_close),
        "ticks_in_window": c1["ticks"] - c0["ticks"],
        # what the program counted over the window, in every run: the cell's per-layer metrics that
        # are read from counters alone (a traced run prints them again among its metrics)
        "counter_metrics": counter_metrics,
        "decode_kv_path": c1["tick_stats"].get("decode_kv_path"),  # "kernel" on a TPU, "xla" elsewhere
        "prefill_chunks_piggybacked": (c1.get("prefill_chunks_piggybacked") or 0) - (c0.get("prefill_chunks_piggybacked") or 0),
        "gen_late_max_ms": max(res["late_ms"], default=0.0),
        "engine_restarts": end["counters"]["engine_restarts"], "poisoned_requests": end["counters"]["poisoned_requests"],
        "prefix_hits": c1["prefix_hits"] - c0["prefix_hits"],
        "prefix_misses": c1["prefix_misses"] - c0["prefix_misses"],
        "kv_evictions": c1["kv_evictions"] - c0["kv_evictions"],
        "early_stops": e2e["short_outputs"],
        **{k: e2e.get(k) for k in ("attempted", "failed", "n_completed", "prompt_tokens", "output_tokens",
                                   "ttft_p50_ms", "ttft_p95_ms", "ttft_max_ms", "tpot_p50_ms",
                                   "tpot_mean_ms", "tpot_max_ms", "out_tok_per_s", "setup_s")},
        "errors": sorted({str(e["error"])[:120] for e in events if e.get("error")})[:5],
        "hbm_peak_gb": peak / 1e9,
        "setup_parts_s": dict(booting["setup_parts_s"], program_boot=booting["boot_s"], warm_traffic=plan.warm_s),
        "spec_overrides": overrides, "check_s": checked["check_s"],
        # a traced run: the ten named scopes that took most device time (None without xplane_pb2)
        "device_scopes": trace_reduce.top(ctx["trace"]["scope_s"] or {}) if ctx["trace"] else None,
        "compared": {k: [v, limits.get(k)] for k, v in numbers.items()},
    }
    print(json.dumps(diag))
    result = {"correct": is_correct, "attempted": e2e["attempted"], "failed": e2e["failed"],
              "metrics": out_metrics, "device": dev_out}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = diag["compared"]  # each number beside its limit (null: read, not held), last in the line
    print(json.dumps(result))
    sys.stdout.flush()
    for k, (v, limit) in diag["compared"].items():  # and as the run's last lines on standard error
        print(f"compared {k} {v} limit {limit}", file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
