"""One span vocabulary from socket to device (serving/obs.py LoopLedger +
``usage.timings``; docs/OBSERVABILITY.md "Span catalogue").

- the engine-loop time ledger: its phases tile the engine thread's wall time
  over cranked ``_loop_iteration``s on a fake clock, ``tick_stats()`` keeps
  ``ticks`` / ``issue_ms`` / ``block_ms`` with their old meaning and gains
  the running totals, ``/metrics`` exports the three families;
- ``usage.timings`` on every ``/dialog/`` response shape: consecutive,
  non-negative spans from receipt to finish that agree with ``ttft_s`` and
  ``latency_s``; the prefill program's shape per request;
- ``/traces``: real spans under one parent, the shared trace_id, and the
  workload capture still reads the records;
- under ``jax.profiler`` the same spans are ``dabt/*`` host events on the
  engine's thread.

Where a clock is read it is a fake one: time passes only where the test
says so, so "within 2%" is exact arithmetic, not luck.
"""

from __future__ import annotations

import asyncio
import dataclasses
import glob
import json
import threading
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from django_assistant_bot_tpu.models import DecoderConfig, llama
from django_assistant_bot_tpu.serving import (
    ByteTokenizer,
    GenerationEngine,
    ModelRegistry,
    parse_prometheus_text,
    render_prometheus,
)
from django_assistant_bot_tpu.serving.engine import plan_prefill
from django_assistant_bot_tpu.serving.fleet import FleetResult
from django_assistant_bot_tpu.serving.obs import (
    _SPAN_KEYS,
    LOOP_PHASES,
    MARKER_WAIT_MIN_S,
    QUEUE_FIELDS,
    QUEUE_RING,
    TIMING_KEYS,
    LoopLedger,
    request_spans,
)
from django_assistant_bot_tpu.serving.server import create_app
from django_assistant_bot_tpu.workload.capture import requests_from_traces

ENGINE_KEYS = set(TIMING_KEYS) - {
    "deliver_s", "stream_lag_max_s", "stream_lag_sum_s", "stream_events"
}
SPAN_KEYS = ("encode_s", "queue_s", "prefill_s", "decode_s", "detok_s")


class _Clock:
    """Time passes only through ``burn``/``sleep`` (and, with ``per_read``,
    by a fixed step at every reading)."""

    def __init__(self, per_read: float = 0.0):
        self.t = 100.0
        self.per_read = per_read

    def __call__(self) -> float:
        self.t += self.per_read
        return self.t

    def burn(self, dt: float) -> None:
        self.t += dt

    sleep = burn


class _NoAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation`` with no session."""

    @staticmethod
    def is_enabled() -> bool:
        return False


def _engine(clock=None, *, context=256, **kw):
    cfg = dataclasses.replace(DecoderConfig.tiny(), max_seq_len=context)
    params = llama.init(cfg, jax.random.key(0))
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("lookahead", 1)
    if clock is not None:
        kw.update(clock=clock, sleep=clock.sleep)
    return GenerationEngine(cfg, params, ByteTokenizer(), **kw)


def _crank(eng, futs, iters=800):
    for _ in range(iters):
        if all(f.done() for f in futs):
            return
        eng._loop_iteration()
    raise AssertionError("requests did not finish within the crank budget")


# ------------------------------------------------------------- ledger units
def test_ledger_nested_spans_are_exclusive_and_tile():
    clk = _Clock()
    led = LoopLedger(clk, annotation=_NoAnnotation)
    t0 = clk()
    with led.span("admit"):
        clk.burn(1.0)
        with led.span("prefill_dispatch", bucket=128, rows=2, rows_padded=4):
            clk.burn(5.0)
        clk.burn(0.5)
    with led.span("tick_issue"):
        clk.burn(2.0)
    snap = led.snapshot()
    assert set(snap) == set(LOOP_PHASES)
    assert snap["admit"] == {"s": 1.5, "n": 1}  # its child's 5 s are not in it
    assert snap["prefill_dispatch"] == {"s": 5.0, "n": 1}
    assert snap["tick_issue"]["s"] == 2.0
    assert sum(v["s"] for v in snap.values()) == clk() - t0
    led.note_dispatch("prefill", 4, 256, 330)
    assert (led.prefill_tokens_real, led.prefill_tokens_padded) == (330, 1024)
    assert led.prefill_shapes == {"4x256": 1}
    led.note_dispatch("tick")  # a decode program pads nothing
    assert (led.prefill_tokens_real, led.prefill_tokens_padded, led.seq) == (330, 1024, 2)


def test_ledger_span_closes_on_exception_and_parent_resumes():
    clk = _Clock()
    led = LoopLedger(clk, annotation=_NoAnnotation)
    with led.span("consume"):
        clk.burn(1.0)
        with pytest.raises(RuntimeError):
            with led.span("tick_issue"):
                clk.burn(2.0)
                raise RuntimeError("device step")
        clk.burn(1.0)
    assert led.seconds("tick_issue") == 2.0 and led.seconds("consume") == 2.0
    assert not led._stack


# ------------------------------------------------- the device's queue: units
def _queue_ledger():
    clk = _Clock()
    led = LoopLedger(clk, annotation=_NoAnnotation)
    led.list_shapes(["2x128", "1x64"])
    return clk, led


def _result(led, clk, seq, wait_s):
    """What ``_process_tick`` does with a result: the wait, then the marker."""
    with led.span("tick_block", seq=seq):
        clk.burn(wait_s)
    led.note_marker(seq)


def _nonzero(queue):
    return {k: v for k, v in queue.items() if k not in ("idle", "markers") and v["n"]}


def test_device_queue_lists_every_key_at_zero_before_anything_ran():
    _, led = _queue_ledger()
    q = led.queue_snapshot()
    assert set(q) == {"tick", "piggyback", "spec", "chunk", "chunk+tick", "mixed", "prefill:2x128", "suffix:2x128",
                      "prefill:1x64", "suffix:1x64", "idle", "markers"}
    assert all(q[k] == dict.fromkeys(QUEUE_FIELDS, 0) for k in q if k not in ("idle", "markers"))
    assert q["idle"] == {"s": 0.0, "n": 0, "by_phase": dict.fromkeys(LOOP_PHASES, 0.0)}
    assert q["markers"] == {"waited": 0, "not_waited": 0} and led.recent_segments() == []


def test_device_queue_scripted_sequence_gives_exactly_these_segments():
    """tick, tick, a 2 x 128 prefill group with its first-token result, a
    chunk with no result of its own, a tick: the queue is never empty, so a
    segment runs from the result before it to its own."""
    clk, led = _queue_ledger()
    t0 = clk.t
    a = led.note_dispatch("tick")
    clk.burn(0.002)
    b = led.note_dispatch("tick")
    clk.burn(0.002)
    _result(led, clk, a, 0.096)  # ready at t0 + 0.100
    c = led.note_dispatch("prefill", 2, 128, 200)  # enqueued at 0.100, behind tick b
    clk.burn(0.005)
    _result(led, clk, b, 0.095)  # ready at 0.200
    d = led.note_dispatch("chunk", 1, 64, 64, 192)  # at 0.200; starts once the prefill group has ended
    e = led.note_dispatch("tick")
    _result(led, clk, c, 0.040)  # the wave's first tokens: ready at 0.240
    _result(led, clk, e, 0.160)  # covers the chunk and the tick: ready at 0.400
    assert (a, b, c, d, e) == (1, 2, 3, 4, 5) == tuple(range(1, led.seq + 1))
    q = led.queue_snapshot()
    got = _nonzero(q)
    assert set(got) == {"tick", "prefill:2x128", "chunk+tick"}
    assert got["tick"] == pytest.approx({"s": 0.100 + 0.100, "n": 2, "ticks": 2, "groups": 0, "tokens": 0,
                                         "start_tokens": 0, "lag_s": 0.0, "lag_n": 0})
    # the prefill waited 0.100 in the queue behind tick b, the chunk 0.040 behind the prefill
    assert got["prefill:2x128"] == pytest.approx({"s": 0.040, "n": 1, "ticks": 0, "groups": 1, "tokens": 200,
                                                  "start_tokens": 0, "lag_s": 0.100, "lag_n": 1})
    assert got["chunk+tick"] == pytest.approx({"s": 0.160, "n": 1, "ticks": 1, "groups": 1, "tokens": 64,
                                               "start_tokens": 192, "lag_s": 0.040, "lag_n": 1})
    assert q["idle"]["s"] == 0.0 and q["markers"] == {"waited": 4, "not_waited": 0}
    assert sum(v["s"] for v in got.values()) == pytest.approx(clk.t - t0)  # first dispatch to last result
    # the padding counters are fed by the same dispatches
    assert (led.prefill_tokens_real, led.prefill_tokens_padded) == (264, 2 * 128 + 64)
    assert led.prefill_shapes == {"2x128": 1, "1x64": 1}
    ring = led.recent_segments()
    assert [r["key"] for r in ring] == ["tick", "tick", "prefill:2x128", "chunk+tick"]
    assert ring[-1] == {"seq": [4, 5], "key": "chunk+tick", "kinds": ["chunk", "tick"], "shapes": ["1x64", ""],
                        "start": pytest.approx(t0 + 0.240), "ready": pytest.approx(t0 + 0.400)}
    assert all(x["ready"] == y["start"] for x, y in zip(ring, ring[1:]))  # back to back: the queue never emptied


def test_a_result_that_was_ready_early_closes_nothing_and_its_dispatches_ride_on():
    clk, led = _queue_ledger()
    a = led.note_dispatch("tick")
    _result(led, clk, a, 0.100)
    b = led.note_dispatch("tick")
    clk.burn(0.300)  # the host was busy elsewhere: by now the tick has long ended
    c = led.note_dispatch("prefill", 1, 64, 40)
    _result(led, clk, b, MARKER_WAIT_MIN_S / 2)  # no wait: when it ended is not known
    q = led.queue_snapshot()
    assert q["markers"] == {"waited": 1, "not_waited": 1} and _nonzero(q).keys() == {"tick"}
    _result(led, clk, c, 0.050)
    q = led.queue_snapshot()
    assert _nonzero(q).keys() == {"tick", "mixed"}
    assert q["mixed"] == pytest.approx({"s": 0.300 + MARKER_WAIT_MIN_S / 2 + 0.050, "n": 1, "ticks": 1, "groups": 1,
                                        "tokens": 40, "start_tokens": 0, "lag_s": 0.0, "lag_n": 1})
    assert led.recent_segments()[-1]["seq"] == [b, c]


def test_an_empty_queue_is_idle_time_charged_to_the_phase_the_thread_spent_it_in():
    clk, led = _queue_ledger()
    t0 = clk.t
    a = led.note_dispatch("tick")
    _result(led, clk, a, 0.100)
    with led.span("consume"):
        clk.burn(0.004)
    with led.span("idle_wait"):
        clk.burn(0.500)
    with led.span("admit"):
        clk.burn(0.001)
        with led.span("prefill_dispatch"):
            clk.burn(0.002)  # building the wave's arrays
            b = led.note_dispatch("prefill", 1, 64, 30)
            clk.burn(0.003)
    _result(led, clk, b, 0.030)
    q = led.queue_snapshot()
    assert q["idle"]["s"] == pytest.approx(0.507) and q["idle"]["n"] == 1
    assert {p: s for p, s in q["idle"]["by_phase"].items() if s} == pytest.approx(
        {"consume": 0.004, "idle_wait": 0.500, "admit": 0.001, "prefill_dispatch": 0.002})
    # the segment starts where the program was enqueued, not where the last result was read: no lag
    assert q["prefill:1x64"] == pytest.approx({"s": 0.033, "n": 1, "ticks": 0, "groups": 1, "tokens": 30,
                                               "start_tokens": 0, "lag_s": 0.0, "lag_n": 1})
    assert q["tick"]["s"] + q["prefill:1x64"]["s"] + q["idle"]["s"] == pytest.approx(clk.t - t0)


def test_riders_name_no_segment_a_restart_drops_what_was_pending_and_the_ring_is_bounded():
    clk, led = _queue_ledger()
    led.note_dispatch("cow")  # a page clone rides with the suffix prefill behind it
    a = led.note_dispatch("suffix", 2, 128, 90)
    _result(led, clk, a, 0.020)
    assert _nonzero(led.queue_snapshot()).keys() == {"suffix:2x128"}
    assert led.recent_segments()[-1]["kinds"] == ["cow", "suffix"]
    led.note_dispatch("tick")
    led.reset_queue()  # crash-only restart: that tick's result never comes
    clk.burn(5.0)
    for _ in range(QUEUE_RING + 7):
        _result(led, clk, led.note_dispatch("spec"), 0.010)
    q = led.queue_snapshot()
    assert q["spec"]["n"] == QUEUE_RING + 7 and q["mixed"]["n"] == 0
    assert q["idle"]["s"] == 0.0  # nothing is charged across a restart; after it the results were all waited for
    ring = led.recent_segments()
    assert len(ring) == QUEUE_RING and ring[-1]["seq"] == [led.seq, led.seq] and ring[0]["key"] == "spec"


# ------------------------------------------- the loop, cranked on a fake clock
class _SlowResult:
    """A device result whose host copy takes ``wait_s`` of the fake clock:
    what ``np.asarray(ref.nxt)`` blocks on."""

    def __init__(self, arr, clk, wait_s):
        self.arr, self.clk, self.wait_s = arr, clk, wait_s

    def copy_to_host_async(self):
        self.arr.copy_to_host_async()

    def __array__(self, dtype=None, copy=None):
        self.clk.burn(self.wait_s)
        return np.asarray(self.arr)


def _rigged_engine(clk, log):
    """Every place the engine thread spends time burns a known amount of the
    fake clock: the prefill and tick dispatches, the results' waits, the
    detokeniser, the sampling upload.  A chunk of 256: a wave of three short
    prompts rides one 4 x 64 program (at the default 64 a program holds one)."""
    eng = _engine(clk, max_seq_len=256)
    eng._running = True  # lockstep: the test cranks _loop_iteration itself
    prefill, insert, activate, tick = eng._prefill, eng._insert, eng._activate_fn, eng._decode_tick
    decode, upload = eng.tokenizer.decode, eng._upload_dirty

    def slow_prefill(*a, **k):
        clk.burn(0.004)
        log["prefill_s"] += 0.004
        return prefill(*a, **k)

    def slow_insert(*a, **k):
        clk.burn(0.001)
        log["prefill_s"] += 0.001
        return insert(*a, **k)

    def slow_activate(*a, **k):
        clk.burn(0.0005)
        log["prefill_s"] += 0.0005
        first, *rest = activate(*a, **k)
        return (_SlowResult(first, clk, 0.03), *rest)

    def slow_tick(*a, **k):
        clk.burn(0.002)
        log["ticks"] += 1
        toks, *rest = tick(*a, **k)
        return (_SlowResult(toks, clk, 0.1), *rest)

    def slow_decode(ids):
        clk.burn(0.0007)
        log["detok_s"] += 0.0007
        return decode(ids)

    def slow_upload():
        did = upload()
        if did:
            clk.burn(0.0003)
            log["upload_s"] += 0.0003
        return did

    eng._prefill, eng._insert, eng._activate_fn, eng._decode_tick = (
        slow_prefill, slow_insert, slow_activate, slow_tick)
    eng.tokenizer.decode, eng._upload_dirty = slow_decode, slow_upload
    return eng


@pytest.fixture()
def cranked():
    clk = _Clock()
    log = {"prefill_s": 0.0, "ticks": 0, "detok_s": 0.0, "upload_s": 0.0}
    eng = _rigged_engine(clk, log)
    t_start = clk()
    futs = [eng.submit([1 + i, 2, 3, 4 + i], max_tokens=6 + 5 * i, temperature=0.0) for i in range(3)]
    for _ in range(2):
        eng._loop_iteration()
    futs.append(eng.submit([9, 8, 7], max_tokens=4, temperature=0.0))
    _crank(eng, futs)
    yield SimpleNamespace(eng=eng, clk=clk, log=log, wall=clk() - t_start,
                          results=[f.result(timeout=5) for f in futs])
    eng.stop(drain_timeout_s=5.0)


def test_loop_phases_sum_to_the_engine_threads_wall_time(cranked):
    loop = cranked.eng.tick_stats()["loop"]
    total = sum(v["s"] for v in loop.values())
    assert cranked.wall > 0.3  # the rig did burn time
    assert abs(total - cranked.wall) <= 0.02 * cranked.wall
    # and each kind of time landed in the phase that names it
    assert loop["prefill_dispatch"]["s"] == pytest.approx(cranked.log["prefill_s"])
    assert loop["prefill_dispatch"]["n"] == 2  # two admission waves, one bucket each
    assert loop["tick_issue"]["n"] == cranked.log["ticks"]
    assert loop["consume"]["s"] == pytest.approx(cranked.log["detok_s"])
    assert loop["admit"]["s"] == 0.0  # bookkeeping only: its dispatches are not in it
    assert loop["tick_block"]["s"] == pytest.approx(0.03 * 2 + 0.1 * cranked.log["ticks"])
    assert loop["tick_issue"]["s"] + loop["prestage"]["s"] == pytest.approx(
        0.002 * cranked.log["ticks"] + cranked.log["upload_s"])


def test_tick_stats_issue_block_and_ticks_read_what_they_read_before(cranked):
    ts = cranked.eng.tick_stats()
    loop, n = ts["loop"], cranked.log["ticks"]
    assert ts["ticks"] == n == cranked.eng._ticks_issued
    # issue_ms: dispatch enqueue per tick issued; block_ms: the result wait per
    # result processed (activations included), as the two accumulators read
    assert ts["issue_ms"] == round(loop["tick_issue"]["s"] / n * 1e3, 3)
    assert ts["block_ms"] == round((0.03 * 2 + 0.1 * n) / (2 + n) * 1e3, 3)
    assert cranked.eng._ticks_processed == 2 + n
    assert ts["prefill_tokens_real"] == 4 + 4 + 4 + 3
    # waves of 3 and of 1 in the 64 bucket: programs of {1, 2, 4} rows
    assert ts["prefill_tokens_padded"] == 4 * 64 + 1 * 64
    assert {k: n for k, n in ts["prefill_shapes"].items() if n} == {"4x64": 1, "1x64": 1}


def test_device_queue_segments_and_idle_tile_first_dispatch_to_last_result(cranked):
    """The device's half, as the phases' test does the host's: on the rig's
    clock every result is waited for, so the segments and the idle time
    between them add up to the time from the first dispatch to the last
    result, and what a segment held is what the loop enqueued."""
    ls = cranked.eng.loop_stats(recent=True)
    q, ring = ls["device_queue"], ls["device_queue_recent"]
    segs = _nonzero(q)
    assert set(segs) == {"tick", "prefill:4x64", "prefill:1x64"}  # waves of 3 and of 1; every tick alone
    assert set(q) - {"idle", "markers"} == {"tick", "piggyback", "spec", "chunk", "chunk+tick", "mixed"} | {
        f"{kind}:{r}x{b}" for kind in ("prefill", "suffix") for b, rows in cranked.eng.prefill_shapes.items() for r in rows}
    assert q["markers"] == {"waited": 2 + cranked.log["ticks"], "not_waited": 0}
    assert segs["tick"]["n"] == segs["tick"]["ticks"] == cranked.log["ticks"]
    assert (segs["prefill:4x64"]["tokens"], segs["prefill:1x64"]["tokens"]) == (12, 3)
    assert sum(v["groups"] for v in segs.values()) == sum(v["lag_n"] for v in segs.values()) == 2
    wall = ring[-1]["ready"] - ring[0]["start"]
    assert 0.3 < wall <= cranked.wall
    assert sum(v["s"] for v in segs.values()) + q["idle"]["s"] == pytest.approx(wall)
    assert sum(r["ready"] - r["start"] for r in ring) == pytest.approx(wall - q["idle"]["s"])
    assert [r["seq"][0] for r in ring] == list(range(1, len(ring) + 1))  # one dispatch a segment, none lost
    # a tick's segment is its result's wait plus what the host did since the result before it
    assert all(r["ready"] - r["start"] >= 0.1 for r in ring if r["key"] == "tick")
    # the second wave was enqueued behind a tick in flight: its lag is that tick's remaining time
    assert segs["prefill:1x64"]["lag_s"] > 0.05
    assert "device_queue_recent" not in cranked.eng.tick_stats()  # the ring is not on the scrape path


def test_an_idle_engine_charges_the_empty_queue_to_idle_wait():
    clk = _Clock()
    eng = _rigged_engine(clk, {"prefill_s": 0.0, "ticks": 0, "detok_s": 0.0, "upload_s": 0.0})
    _crank(eng, [eng.submit([1, 2, 3], max_tokens=3, temperature=0.0)])
    assert eng.loop_stats()["device_queue"]["idle"]["n"] == 0
    with eng._ledger.span("idle_wait"):  # what _loop does between iterations that found nothing
        clk.sleep(0.75)
    _crank(eng, [eng.submit([4, 5, 6], max_tokens=3, temperature=0.0)])
    idle = eng.loop_stats()["device_queue"]["idle"]
    assert idle["n"] == 1 and idle["by_phase"]["idle_wait"] == pytest.approx(0.75)
    # the rest of the gap: the last results' bookkeeping, and building the wave up to its enqueue
    assert idle["s"] == pytest.approx(sum(idle["by_phase"].values())) and 0.75 <= idle["s"] < 0.76
    assert idle["by_phase"]["tick_block"] == idle["by_phase"]["tick_issue"] == 0.0
    eng.stop(drain_timeout_s=5.0)


@pytest.mark.parametrize("family", ["dabt_device_queue_seconds_total", "dabt_device_queue_segments_total",
                                    "dabt_device_queue_idle_seconds_total", "dabt_prefill_start_lag_seconds_total",
                                    "dabt_prefill_start_lag_dispatches_total"])
def test_metrics_export_the_device_queue(cranked, family):
    reg = SimpleNamespace(generators={"m": cranked.eng}, embedders={})
    fam = parse_prometheus_text(render_prometheus(reg))[family]
    q = cranked.eng.tick_stats()["device_queue"]
    assert fam["type"] == "counter"
    keyed = {(lab.get("kind"), lab.get("shape"), lab.get("phase")): v for _, lab, v in fam["samples"]}
    segs = {k: v for k, v in q.items() if k not in ("idle", "markers")}
    if family == "dabt_device_queue_idle_seconds_total":
        assert {k[2]: v for k, v in keyed.items()} == pytest.approx(q["idle"]["by_phase"])
    elif family.startswith("dabt_prefill_start_lag"):
        field = "lag_s" if "seconds" in family else "lag_n"
        assert list(keyed.values()) == [pytest.approx(sum(v[field] for v in segs.values()))] and keyed[(None, None, None)] > 0
    else:
        field = "s" if "seconds" in family else "n"
        want = {(k.partition(":")[0], k.partition(":")[2], None): v[field] for k, v in segs.items()}
        assert keyed == pytest.approx(want) and keyed[("prefill", "4x64", None)] > 0 and keyed[("tick", "", None)] > 0


@pytest.mark.parametrize("phase", LOOP_PHASES)
def test_metrics_export_the_loop_ledger(cranked, phase):
    reg = SimpleNamespace(generators={"m": cranked.eng}, embedders={})
    fams = parse_prometheus_text(render_prometheus(reg))
    loop = cranked.eng.tick_stats()["loop"]
    secs = {lab["phase"]: v for _, lab, v in fams["dabt_engine_loop_seconds_total"]["samples"]}
    spans = {lab["phase"]: v for _, lab, v in fams["dabt_engine_loop_spans_total"]["samples"]}
    assert fams["dabt_engine_loop_seconds_total"]["type"] == "counter"
    assert secs[phase] == pytest.approx(loop[phase]["s"]) and spans[phase] == loop[phase]["n"]
    pads = {lab["kind"]: v for _, lab, v in fams["dabt_prefill_tokens_total"]["samples"]}
    assert pads == {"real": 15.0, "padded": 320.0}
    shapes = {lab["shape"]: v for _, lab, v in fams["dabt_prefill_programs_total"]["samples"]}
    assert shapes == {f"{r}x{b}": float((r, b) in ((4, 64), (1, 64)))
                      for b, rows in cranked.eng.prefill_shapes.items() for r in rows}


def test_idle_and_recover_time_is_in_the_ledger_too():
    """``_loop``'s own steps: the idle sleep, and a crash-only restart with
    its backoff, are phases like the rest."""
    clk = _Clock()
    eng = _engine(clk, idle_poll_s=0.25, restart_backoff_s=0.5)
    calls = {"n": 0}

    def iteration():
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("boom")
        if calls["n"] >= 3:
            eng._running = False
        return False

    eng._loop_iteration = iteration
    eng._running = True
    eng._loop()  # on this thread: three iterations, the second one fatal
    loop = eng.loop_stats()["loop"]
    assert loop["idle_wait"]["s"] == pytest.approx(0.25 + 0.5 + 0.25)
    assert loop["recover"]["n"] == 1


# ------------------------------------------------------------ usage.timings
def _assert_tiles(usage):
    tm = usage["timings"]
    assert ENGINE_KEYS <= set(tm) <= set(TIMING_KEYS)
    assert all(tm[k] >= 0 for k in tm)
    assert usage["ttft_s"] == pytest.approx(tm["queue_s"] + tm["prefill_s"], abs=1e-9)
    assert usage["latency_s"] == pytest.approx(
        tm["queue_s"] + tm["prefill_s"] + tm["decode_s"], abs=1e-9)
    # back to back from receipt: every span starts where the one before ended.
    # Held against the timings as they are: a span's `t_s` and `dur_s` are each
    # rounded to 1e-6, so a sum of rounded durations drifts half a microsecond a span
    spans = request_spans(tm)
    assert spans[0]["name"] == "request" and spans[0]["parent"] is None
    durs = {name: tm[key] for name, key in _SPAN_KEYS if tm.get(key) is not None}
    t = 0.0
    for sp in spans[1:]:
        assert sp["parent"] == "request" and sp["t_s"] == pytest.approx(t, abs=1e-6)
        assert sp["dur_s"] == pytest.approx(durs[sp["name"]], abs=1e-6)
        t += durs[sp["name"]]
    assert spans[0]["dur_s"] == pytest.approx(t, abs=1e-6)
    return tm


def test_timings_tile_receipt_to_finish_on_a_clock_that_moves_at_every_read():
    clk = _Clock(per_read=0.001)
    eng = _engine(clk)
    eng._running = True
    received = clk()
    clk.burn(0.25)  # body parse, chat format, tokenizer
    fut = eng.submit([1, 2, 3, 4, 5], max_tokens=11, temperature=0.0, received_at=received)
    _crank(eng, [fut])
    r = fut.result(timeout=5)
    tm = _assert_tiles(r.usage_dict("m"))
    assert tm["recv_mono_s"] == received and tm["encode_s"] == pytest.approx(0.251)
    assert received + sum(tm[k] for k in SPAN_KEYS) <= clk.t  # the finish lies behind us
    assert tm["queue_s"] > 0 and tm["prefill_s"] > 0 and tm["decode_s"] > 0
    # 1 token with the activation, 10 from two fused ticks of 8 steps
    assert (tm["decode_ticks"], tm["decode_steps"], tm["prefill_chunks"]) == (3, 16, 0)
    eng.stop(drain_timeout_s=5.0)


@pytest.mark.parametrize("obs", [True, False])
def test_ledger_and_timings_are_the_engines_own_with_obs_off(obs):
    eng = _engine(obs=obs).start()
    try:
        r = eng.submit([1, 2, 3], max_tokens=3, temperature=0.0).result(timeout=300)
        assert (eng.obs is None) is (not obs)
        _assert_tiles(r.usage_dict("m"))
        ts = eng.tick_stats()
        assert set(ts["loop"]) == set(LOOP_PHASES) and ts["loop"]["tick_issue"]["n"] == ts["ticks"] >= 1
        assert ts["prefill_tokens_real"] == 3 and ts["prefill_tokens_padded"] == 64
        assert ts["prefill_shapes"] == {"1x64": 1}
        # the device's half too: the wave's group and every tick noted, every result a marker
        q = ts["device_queue"]
        # (the last tick's result may still be on its way to the engine thread)
        assert 1 <= q["markers"]["waited"] + q["markers"]["not_waited"] <= 1 + ts["ticks"] == eng._ledger.seq
        assert {"tick", "prefill:1x64", "suffix:1x64", "chunk+tick", "mixed", "idle"} <= set(q)
    finally:
        eng.stop()


def test_prefill_bucket_and_wave_rows_match_plan_prefill():
    """A wave of 130/200/300-token prompts: two ride a 2 x 256 program (a
    wave of 2 is not padded to 4), one a 1 x 384 program (not 512)."""
    eng = _engine(context=1024, max_seq_len=1024, chunk_size=512)
    eng._running = True
    lens = (130, 200, 300)
    futs = [eng.submit([1 + (j % 200) for j in range(n)], max_tokens=2, temperature=0.0) for n in lens]
    _crank(eng, futs)
    tms = [f.result(timeout=5).timings for f in futs]
    for rows, bucket, members in plan_prefill(eng.prefill_shapes, lens):
        for i in members:
            assert tms[i]["prefill_bucket"] == bucket
            assert (tms[i]["wave_rows"], tms[i]["wave_rows_padded"]) == (len(members), rows)
            assert tms[i]["prefix_hit_tokens"] == 0 and tms[i]["prefill_chunks"] == 0
    assert [(t["prefill_bucket"], t["wave_rows"], t["wave_rows_padded"]) for t in tms] == [
        (256, 2, 2), (256, 2, 2), (384, 1, 1)]
    ts = eng.tick_stats()
    assert ts["prefill_tokens_real"] == sum(lens)
    assert ts["prefill_tokens_padded"] == 2 * 256 + 1 * 384
    assert {k: n for k, n in ts["prefill_shapes"].items() if n} == {"2x256": 1, "1x384": 1}
    eng.stop(drain_timeout_s=5.0)


def test_chunked_prefill_counts_its_chunks_and_new_positions():
    eng = _engine(max_seq_len=128, chunk_size=16, prefix_cache_size=0)
    eng._running = True
    fut = eng.submit(list(range(1, 41)), max_tokens=3, temperature=0.0)  # 40 ids: chunks at 0, 16, 24
    _crank(eng, [fut])
    tm = fut.result(timeout=5).timings
    assert (tm["prefill_bucket"], tm["wave_rows"], tm["wave_rows_padded"], tm["prefill_chunks"]) == (16, 1, 1, 3)
    ts = eng.tick_stats()
    assert ts["prefill_tokens_real"] == 40 and ts["prefill_tokens_padded"] == 3 * 16
    assert ts["loop"]["prefill_dispatch"]["n"] + ts["prefill_chunks_piggybacked"] == 3
    eng.stop(drain_timeout_s=5.0)


def test_fleet_result_passes_the_peers_timings_through_unchanged():
    tm = {"recv_mono_s": 5.0, "encode_s": 0.001, "queue_s": 0.2, "decode_ticks": 3}
    fr = FleetResult(token_ids=[1], text="a", prompt_tokens=4, completion_tokens=1, length_limited=True,
                     peer="p0", reroutes=0, trace_id="t", timings=tm)
    usage = fr.usage_dict("m")
    assert usage["timings"] == tm and usage["peer"] == "p0" and usage["total_tokens"] == 5
    bare = FleetResult(token_ids=[1], text="a", prompt_tokens=4, completion_tokens=1, length_limited=True,
                       peer="p0", reroutes=0, trace_id="t")
    assert "timings" not in bare.usage_dict("m")


# ------------------------------------------------------------------ over HTTP
@pytest.fixture(scope="module")
def http():
    from aiohttp.test_utils import TestClient, TestServer

    loop = asyncio.new_event_loop()
    registry = ModelRegistry.from_config(
        {"tiny-chat": {"kind": "decoder", "tiny": True, "max_slots": 2, "max_seq_len": 256}})
    client = TestClient(TestServer(create_app(registry)), loop=loop)
    loop.run_until_complete(client.start_server())
    yield loop, client, registry
    loop.run_until_complete(client.close())
    registry.stop()
    loop.close()


async def _sse(resp):
    events = []
    async for raw in resp.content:
        line = raw.decode("utf-8").strip()
        if line.startswith("data:") and line != "data: [DONE]":
            events.append(json.loads(line[5:]))
    return events


def _body(**kw):
    return dict({"model": "tiny-chat", "messages": [{"role": "user", "content": "hello there"}],
                 "max_tokens": 12, "temperature": 0.0}, **kw)


def test_usage_timings_on_the_sse_terminal_event(http):
    loop, client, registry = http

    async def go():
        resp = await client.post("/dialog/", json=_body(stream=True), headers={"X-Request-Id": "sse-1"})
        assert resp.status == 200
        return await _sse(resp)

    events = loop.run_until_complete(go())
    usage = events[-1]["usage"]
    tm = _assert_tiles(usage)
    assert set(tm) == set(TIMING_KEYS)  # the server's keys too
    assert tm["encode_s"] > 0  # body parse, chat format, tokenizer
    # every delta written from a token (the flushed hold-back tail has no stamp)
    assert 0 < tm["stream_events"] <= sum(1 for e in events if "delta" in e)
    assert 0 <= tm["stream_lag_max_s"] <= tm["stream_lag_sum_s"] < 5.0
    assert usage["prompt_tokens"] > 0 and usage["completion_tokens"] <= 12
    # the /traces record is built from the same dict: deliver is a real span now
    tr = registry.get_generator("tiny-chat").obs.trace("sse-1")
    assert [s["name"] for s in tr["spans"]] == [
        "request", "encode", "queue_wait", "prefill", "decode", "detok", "deliver"]
    assert tr["trace_id"] == "sse-1" and tr["timings"] == tm
    assert tr["total_s"] == pytest.approx(sum(tm[k] for k in SPAN_KEYS) + tm["deliver_s"], abs=1e-5)


def test_usage_timings_on_the_json_body(http):
    loop, client, _ = http

    async def go():
        resp = await client.post("/dialog/", json=_body())
        assert resp.status == 200
        return await resp.json()

    usage = loop.run_until_complete(go())["response"]["usage"]
    tm = _assert_tiles(usage)
    assert set(tm) == ENGINE_KEYS  # nothing is streamed: no lag, no deliver
    assert tm["encode_s"] > 0


def test_traces_carry_parent_and_trace_id_and_still_feed_the_workload_capture(http):
    loop, client, _ = http

    async def go():
        for i in range(2):
            r = await client.post("/dialog/", json=_body(max_tokens=3 + i), headers={"X-Request-Id": f"cap-{i}"})
            assert r.status == 200
        return await (await client.get("/traces")).json()

    traces = [t for t in loop.run_until_complete(go())["traces"] if t["trace_id"].startswith("cap-")]
    assert len(traces) == 2
    for tr in traces:
        assert {"t_submit_s", "prompt_tokens", "completion_tokens", "priority", "tenant"} <= set(tr)
        assert tr["spans"][0] == {"name": "request", "parent": None, "t_s": 0.0, "dur_s": tr["total_s"]}
        assert {s["parent"] for s in tr["spans"][1:]} == {"request"}
        assert not any(s["name"] in ("admit",) or "dur_s" not in s for s in tr["spans"])
    reqs, skipped = requests_from_traces(traces)
    assert skipped == 0 and len(reqs) == 2
    assert sorted(r.max_tokens for r in reqs) == sorted(t["completion_tokens"] for t in traces)


# ------------------------------------------------------- on the profiler's clock
def test_spans_are_host_events_on_the_engine_thread_under_the_profiler(tmp_path):
    from jax.profiler import ProfileData

    eng = _engine(max_slots=2).start()
    try:
        eng.submit([1, 2, 3], max_tokens=3, temperature=0.0).result(timeout=300)  # compile outside
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("test_thread_mark"):
                pass
            eng.submit([1, 2, 3, 4], max_tokens=6, temperature=0.0).result(timeout=300)
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.stop()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    lines = [(pl.name, i, [(e.name, dict(e.stats)) for e in ln.events])
             for pl in ProfileData.from_file(path).planes for i, ln in enumerate(pl.lines)]
    # (another engine idling in this process has a line of its own: reap,
    # admit, prestage, idle_wait and never a tick)
    engine_lines = [(p, i, evs) for p, i, evs in lines if any(n == "dabt/tick_issue" for n, _ in evs)]
    assert len(engine_lines) == 1  # one thread issued them all
    plane, idx, evs = engine_lines[0]
    ev = dict(evs)
    assert plane.startswith("/host:")
    assert {"dabt/tick_issue", "dabt/prefill_dispatch", "dabt/tick_block", "dabt/consume"} <= set(ev)
    seq = ev["dabt/prefill_dispatch"]["seq"]
    assert ev["dabt/prefill_dispatch"] == {"bucket": 64, "rows": 1, "rows_padded": 1, "seq": seq}
    assert "test_thread_mark" not in ev  # this thread's line is another
    assert threading.current_thread().name != "gen-engine"
    # the device-queue ledger's numbers join the three: a dispatch's span carries
    # its seq, the wait for a result the seq of the last dispatch it covers
    issued = [st["seq"] for n, st in evs if n in ("dabt/tick_issue", "dabt/prefill_dispatch")]
    waited = [st["seq"] for n, st in evs if n == "dabt/tick_block"]
    assert issued == sorted(set(issued)) and seq in issued  # one number a dispatch, in order
    # (a result of the first request's last tick may still be read as the session starts)
    assert waited == sorted(waited) and all(w in issued or w < issued[0] for w in waited)
    assert seq in waited  # the wave's first tokens are a result of their own
