"""From a profiler trace (``.xplane.pb``) to what the metrics need: per-device
busy time inside the window, time per compiled program and per operation, and
the idle gaps with a label for each.

Read with ``jax.profiler.ProfileData``; only the block an operation belongs to
(the named scope in its ``tf_op`` stat, which ``ProfileData`` does not show)
is read from the protocol buffer itself, with TensorFlow's ``xplane_pb2``
loaded by its file's path (importing ``tensorflow`` takes 8 s and JAX with it).
A TPU trace has one plane per chip (``/device:TPU:<n>``) whose line ``XLA Ops``
holds one event per operation run and ``XLA Modules`` one per compiled program run.  A CPU trace (the tests'
rehearsal only) has no device plane: XLA's CPU client threads on the host plane
stand in.  The window is marked by two host annotations that the harness
writes, ``bench_window_open`` and ``bench_window_close``; without them it is
the span of the device's events.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Callable, Dict, List, Optional, Tuple

OPEN_MARK, CLOSE_MARK = "bench_window_open", "bench_window_close"
Interval = Tuple[float, float]
CONTAINERS = ("while", "conditional", "call")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _clip(iv: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def program_name(event_name: str) -> str:
    """``jit__decode_tick(123456)`` -> ``jit__decode_tick``."""
    return re.sub(r"\(.*\)$", "", event_name).strip()


def op_name(event_name: str) -> str:
    """A TPU trace names an operation by its whole HLO line (``%fusion.12 =
    bf16[...] fusion(...)``): keep the instruction's name."""
    m = re.match(r"%?([\w.\-]+)", event_name)
    return m.group(1) if m else event_name[:64]


def _xplane_pb2():
    """TensorFlow's generated ``xplane_pb2`` module, loaded from its file
    without importing ``tensorflow``; None where it is not installed."""
    import importlib.util

    try:
        pkg = importlib.util.find_spec("tensorflow")
        path = os.path.join(pkg.submodule_search_locations[0], "tsl", "profiler", "protobuf", "xplane_pb2.py")
        spec = importlib.util.spec_from_file_location("benchmarks_xplane_pb2", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    except Exception:  # not installed, moved, or a protobuf runtime that refuses it: no scopes, the rest stands
        return None


def op_scopes(path: str) -> Optional[Dict[str, Dict[str, str]]]:
    """For each device plane, an operation's event name -> its ``tf_op`` stat:
    the named scopes it was traced under and its primitive, as the profiler
    wrote them (``jit(tick)/.../ffn/gate_up/dot_general:``).  None when
    ``xplane_pb2`` cannot be loaded."""
    pb = _xplane_pb2()
    if pb is None:
        return None
    space = pb.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        scopes = out.setdefault(plane.name, {})
        for md in plane.event_metadata.values():
            for s in md.stats:
                if stat_names.get(s.metadata_id) == "tf_op":
                    scopes.setdefault(md.name, s.str_value or (stat_names.get(s.ref_value, "") if s.ref_value else ""))
    return out


def top(seconds_by_name: Dict[str, float], n: int = 10) -> List[List[Any]]:
    """The ``n`` largest, as ``[[name, seconds], ...]``."""
    return [[k, v] for k, v in sorted(seconds_by_name.items(), key=lambda kv: -kv[1])[:n]]


def reduce(path: str, label: Optional[Callable[[float, float], str]] = None) -> Dict[str, Any]:
    """``label(t0, t1)`` names an idle gap given its edges in seconds from the
    window's opening; gaps are summed by label."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Tuple[str, float, float]]]] = {}
    marks: Dict[str, float] = {}
    host_xla: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if is_dev and line.name in ("XLA Ops", "XLA Modules"):
                evs = [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]
                devices.setdefault(plane.name, {})[line.name] = evs
            elif plane.name.startswith("/host:"):
                cpu_client = "XLAPjRtCpuClient" in line.name
                for e in line.events:
                    if e.name in (OPEN_MARK, CLOSE_MARK):
                        marks[e.name] = e.start_ns * 1e-9
                    elif cpu_client and e.duration_ns > 0:
                        host_xla.append((e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9))
    if not devices and host_xla:
        devices = {"/host:CPU (rehearsal)": {"XLA Ops": host_xla, "XLA Modules": []}}
    if not devices:
        raise ValueError(f"{path}: no device plane and no XLA events")
    all_ops = [ev for d in devices.values() for ev in d.get("XLA Ops", [])]
    lo = marks.get(OPEN_MARK, min(a for _, a, _ in all_ops))
    hi = marks.get(CLOSE_MARK, max(b for _, _, b in all_ops))
    window_s = hi - lo
    busy, op_time, prog_time, prog_runs = [], {}, {}, {}
    scopes = op_scopes(path)
    scope_time: Optional[Dict[str, float]] = None if scopes is None else {}
    gaps_by_label: Dict[str, float] = {}
    longest_gap = 0.0
    for i, (name, d) in enumerate(sorted(devices.items())):
        ops = [(n, max(a, lo), min(b, hi)) for n, a, b in d.get("XLA Ops", []) if b > lo and a < hi]
        iv = union([(a, b) for _, a, b in ops])
        busy.append(sum(b - a for a, b in iv))
        plane_scopes = (scopes or {}).get(name, {})
        for n, a, b in ops:
            if op_name(n).startswith(CONTAINERS):
                continue  # a loop's event spans its body's events: counted there
            op_time[op_name(n)] = op_time.get(op_name(n), 0.0) + (b - a) / len(devices)
            if scope_time is not None:
                scope = plane_scopes.get(n, "")  # "": an operation under no named scope
                scope_time[scope] = scope_time.get(scope, 0.0) + (b - a) / len(devices)
        for n, a, b in d.get("XLA Modules", []):
            if b > lo and a < hi:
                p = program_name(n)
                prog_time[p] = prog_time.get(p, 0.0) + (min(b, hi) - max(a, lo)) / len(devices)
                prog_runs[p] = prog_runs.get(p, 0) + 1.0 / len(devices)
        if i == 0:
            edges = [lo] + [x for ab in iv for x in ab] + [hi]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    longest_gap = max(longest_gap, b - a)
                    what = label(a - lo, b - lo) if label else "unlabelled"
                    gaps_by_label[what] = gaps_by_label.get(what, 0.0) + (b - a)
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy),
        "devices": len(devices),
        "marked": OPEN_MARK in marks and CLOSE_MARK in marks,
        "device_ops": top(op_time),
        "op_s": op_time,        # every operation's time, by the name ``device_ops`` prints
        "scope_s": scope_time,  # the same time by the operation's ``tf_op`` scope; None without ``xplane_pb2``
        "idle_gaps": top(gaps_by_label),
        "longest_gap_s": longest_gap,
        "program_s": prog_time,
        "program_runs": prog_runs,
    }
