"""engine: not a metric: the window's difference of the program's device-queue ledger
(``tick_stats()["device_queue"]``: the device's seconds by what its queue held between two results the host
waited for; ``serving/obs.py`` ``LoopLedger``), which ``decode_step_ms_window``, ``prefill_dev_share_window``,
``prefill_ms_per_ktok_window``, ``prefill_chunk_ms_window``, ``prefill_start_lag_ms`` and the two
``device_queue_*`` shares build on through ``ctx["read"]``.  ``None`` on a program without the ledger (an older
one).  Else ``segs`` (``{key: {"s", "n", "ticks", "groups", "tokens", "start_tokens", "lag_s", "lag_n"}}``),
``idle_s``, ``total_s`` (segments + idle), ``waited`` / ``not_waited`` (markers), ``steps`` (what a tick fuses),
``tick_s`` (one tick: the mean ``tick`` segment; under 10 of them the traced ``decode_step_dev_ms`` x steps; None
without either), and what of the segments was prefill, ``prefill_s`` over all of them and ``chunk_s`` /
``chunk_groups`` over ``chunk``, ``chunk+tick`` and ``mixed``: a segment of prefill programs alone counts whole,
one that held ticks too less its ticks at ``tick_s`` (None where that is unknown), ``tick`` and ``spec`` not at all."""

MIN_TICK_SEGMENTS = 10
CHUNK_KEYS = ("chunk", "chunk+tick", "mixed")


def _prefill(segs, tick_s, keys=None):
    seconds, groups = 0.0, 0
    for key, tot in segs.items():
        if key in ("tick", "spec") or (keys is not None and key not in keys):
            continue
        if tot["ticks"] and tick_s is None:
            return None, 0
        seconds += tot["s"] - tot["ticks"] * (tick_s or 0.0)
        groups += tot["groups"]
    return max(0.0, seconds), groups


def read(ctx):
    q0 = (ctx["c0"].get("tick_stats") or {}).get("device_queue")
    q1 = (ctx["c1"].get("tick_stats") or {}).get("device_queue")
    if not q0 or not q1:
        return None
    segs = {key: {f: v - q0.get(key, {}).get(f, 0) for f, v in tot.items()}
            for key, tot in q1.items() if key not in ("idle", "markers")}
    idle_s = q1["idle"]["s"] - q0["idle"]["s"]
    steps = ctx["c1"].get("decode_steps") or 1
    tick = segs.get("tick", {"s": 0.0, "n": 0})
    windowed = tick["n"] >= MIN_TICK_SEGMENTS
    if windowed:
        tick_s = tick["s"] / tick["n"]
    else:
        step_ms = ctx["read"]("decode_step_dev_ms")
        tick_s = step_ms * steps / 1e3 if step_ms else None
    chunk_s, chunk_groups = _prefill(segs, tick_s, CHUNK_KEYS)
    return {
        "segs": segs, "idle_s": idle_s, "total_s": sum(t["s"] for t in segs.values()) + idle_s,
        "waited": q1["markers"]["waited"] - q0["markers"]["waited"],
        "not_waited": q1["markers"]["not_waited"] - q0["markers"]["not_waited"],
        "steps": steps, "tick_s": tick_s, "tick_windowed": windowed,
        "prefill_s": _prefill(segs, tick_s)[0], "chunk_s": chunk_s, "chunk_groups": chunk_groups,
    }
