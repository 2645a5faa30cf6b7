"""Serving-plane observability: request tracing, /metrics, crash flight recorder.

Until now the serving plane's only operational surfaces were point-in-time
gauges (``tick_stats()``, ``/healthz``) and free-text logs: no per-request
causality, no scrapeable time series, and no post-mortem trail when a
crash-only restart (docs/RESILIENCE.md) or a router re-route fires.  This
module is the missing layer, three pillars in one place:

- **Per-request tracing.**  Every request carries a ``trace_id`` (client
  ``X-Request-Id`` or generated at admission) on the engine's ``_Request``,
  across router re-route hops, and over the ``gpu_service:`` provider wire.
  Span timings come from host-side timestamps stamped where the work
  happens (receipt at the socket, ``submitted_at`` / ``started_at`` /
  ``first_token_at`` / finish in the engine, the terminal event's write in
  the server) — the recorder adds ZERO device syncs, enforced mechanically
  by dabtlint's DABT104 hot-path registry (the ``EngineObs.on_*`` entry
  points are roots).  The same stamps travel to the client as
  ``usage.timings`` (:data:`TIMING_KEYS`); completed traces land in a
  bounded ring (:meth:`EngineObs.traces`) whose span list is built from
  that one dict (:func:`request_spans`).
- **Engine-loop time ledger.**  :class:`LoopLedger` / ``span(name)``: every
  step of the engine thread's loop adds its elapsed time to a per-phase
  total on the injectable clock AND, while a profiler session runs, wraps
  the same interval in ``jax.profiler.TraceAnnotation("dabt/<name>")`` — a
  host event on the device trace's own clock.  The ledger is the engine's
  own (it exists with ``obs=False`` too); ``tick_stats()["loop"]`` and the
  ``dabt_engine_loop_*`` families read it.
- **Prometheus metrics.**  Fixed-bucket :class:`Histogram` state updated from
  ``_process_tick``'s host bookkeeping (TTFT, inter-token latency, queue
  wait, tick duration, speculative accept ratio) plus the existing
  engine/scheduler/KV/router gauges, rendered as text exposition format by
  :func:`render_prometheus` — scraped by ``GET /metrics`` without holding
  any router lock across engine calls (the PR 7 ABBA family; the stats
  surfaces do their own locking).  :func:`parse_prometheus_text` is the
  small in-repo parser CI and the bench use to validate the exposition.
- **Crash flight recorder.**  A bounded ring of recent engine events
  (admissions, periodic tick summaries, quarantines, restarts, re-routes,
  fault-injector fires, drains) that the failure paths dump to a JSON file
  + log line (:meth:`FlightRecorder.dump`), so a chaos failure is
  diagnosable from the artifact alone.  ``DABT_FLIGHT_DIR`` overrides the
  dump location.

Everything is injectable-clock (dabtlint DABT105): no raw ``time.*()`` call
anywhere in this module — fake-clock tests drive spans and flight stamps
deterministically.  Format details and the metric catalog live in
docs/OBSERVABILITY.md.
"""

from __future__ import annotations

import bisect
import collections
import json
import logging
import math
import os
import sys
import tempfile
import threading
import time
import uuid
from typing import Any, Callable, Dict, Iterable, List, Mapping, Optional, Tuple

logger = logging.getLogger(__name__)

ENV_FLIGHT_DIR = "DABT_FLIGHT_DIR"
ENV_LOG_JSON = "DABT_LOG_JSON"

# Fixed histogram bucket ladders (seconds unless noted).  Fixed buckets — not
# reservoirs — so scrapes are mergeable across time and replicas and the
# hot-path observe cost is one bisect + one increment.
TTFT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0)
ITL_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0)
WAIT_BUCKETS = TTFT_BUCKETS
TICK_BUCKETS = ITL_BUCKETS
ACCEPT_BUCKETS = (0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 1.0)


def new_trace_id() -> str:
    """16-hex-char request/trace id (collision odds are irrelevant at the
    ring-buffer horizons this plane keeps)."""
    return uuid.uuid4().hex[:16]


# --------------------------------------------------------------------- metrics
class Histogram:
    """Fixed-bucket histogram, Prometheus semantics (cumulative at render).

    Thread contract: :meth:`observe` is called from the engine thread's tick
    bookkeeping (a DABT104 hot-path root — it must never touch device state),
    :meth:`snapshot` from scrape threads; one small lock covers both.
    """

    __slots__ = ("bounds", "_counts", "_sum", "_n", "_lock")

    def __init__(self, bounds: Tuple[float, ...]):
        self.bounds = tuple(float(b) for b in bounds)
        self._counts = [0] * (len(self.bounds) + 1)  # +1 = the +Inf bucket
        self._sum = 0.0
        self._n = 0
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        idx = bisect.bisect_left(self.bounds, value)
        with self._lock:
            self._counts[idx] += 1
            self._sum += value
            self._n += 1

    @property
    def count(self) -> int:
        with self._lock:
            return self._n

    def raw_counts(self) -> Tuple[List[int], int]:
        """(per-bucket raw counts incl. the +Inf bucket, total n) — the
        windowing substrate: consumers diff two snapshots to quantile over
        only the observations BETWEEN them (serving/scheduler.py)."""
        with self._lock:
            return list(self._counts), self._n

    def quantile(self, q: float) -> float:
        """Bucket-interpolated ``q``-quantile of the observed values.

        The estimate linearly interpolates inside the bucket that contains
        the target rank; values in the ``+Inf`` bucket report the largest
        finite bound (a deliberate *under*-estimate — the admission plane
        uses this as a prediction, and an unbounded guess would shed
        everything forever).  Returns 0.0 on an empty histogram — callers
        gate on :attr:`count` to tell "cold" from "fast"."""
        counts, n = self.raw_counts()
        return quantile_from_counts(self.bounds, counts, q)

    def snapshot(self) -> Tuple[List[Tuple[float, int]], float, int]:
        """(cumulative ``le`` buckets, sum, count) — the exposition shape."""
        with self._lock:
            counts = list(self._counts)
            total, n = self._sum, self._n
        out: List[Tuple[float, int]] = []
        acc = 0
        for b, c in zip(self.bounds, counts):
            acc += c
            out.append((b, acc))
        out.append((float("inf"), acc + counts[-1]))
        return out, total, n


def quantile_from_counts(
    bounds: Tuple[float, ...], counts: List[int], q: float
) -> float:
    """The bucket-interpolation quantile over RAW per-bucket counts (the last
    entry being the +Inf bucket).  Shared by :meth:`Histogram.quantile` and
    the scheduler's windowed predictive-admission floor, which quantiles the
    DIFFERENCE of two count snapshots."""
    q = min(1.0, max(0.0, float(q)))
    n = sum(counts)
    if n == 0:
        return 0.0
    target = max(1, math.ceil(q * n))
    acc = 0
    for i, c in enumerate(counts):
        if c == 0:
            continue
        if acc + c >= target:
            if i >= len(bounds):  # +Inf bucket: report the finite ceiling
                return bounds[-1]
            lo = bounds[i - 1] if i > 0 else 0.0
            hi = bounds[i]
            frac = (target - acc) / c
            return lo + frac * (hi - lo)
        acc += c
    return bounds[-1]  # pragma: no cover - defensive


class _Exposition:
    """Accumulates metric families and renders Prometheus text format."""

    def __init__(self) -> None:
        self._families: "collections.OrderedDict[str, dict]" = collections.OrderedDict()

    @staticmethod
    def _fmt_labels(labels: Optional[Mapping[str, str]]) -> str:
        if not labels:
            return ""
        inner = ",".join(
            '%s="%s"' % (k, str(v).replace("\\", "\\\\").replace('"', '\\"'))
            for k, v in sorted(labels.items())
        )
        return "{%s}" % inner

    @staticmethod
    def _fmt_value(v: float) -> str:
        if v != v:  # NaN
            return "NaN"
        if v == float("inf"):
            return "+Inf"
        if v == float("-inf"):
            return "-Inf"
        if isinstance(v, bool):
            return "1" if v else "0"
        if isinstance(v, int) or float(v).is_integer():
            return str(int(v))
        return repr(float(v))

    def _family(self, name: str, mtype: str, help_text: str) -> dict:
        fam = self._families.get(name)
        if fam is None:
            fam = self._families[name] = {
                "type": mtype,
                "help": help_text,
                "samples": [],
            }
        return fam

    def add(
        self,
        name: str,
        mtype: str,
        help_text: str,
        value: Any,
        labels: Optional[Mapping[str, str]] = None,
    ) -> None:
        if value is None:
            return
        if isinstance(value, bool):
            value = 1.0 if value else 0.0
        self._family(name, mtype, help_text)["samples"].append(
            (name, dict(labels or {}), float(value))
        )

    def add_histogram(
        self,
        name: str,
        help_text: str,
        hist: Histogram,
        labels: Optional[Mapping[str, str]] = None,
    ) -> None:
        fam = self._family(name, "histogram", help_text)
        buckets, total, n = hist.snapshot()
        base = dict(labels or {})
        for le, cum in buckets:
            lab = dict(base)
            lab["le"] = "+Inf" if le == float("inf") else self._fmt_value(le)
            fam["samples"].append((f"{name}_bucket", lab, float(cum)))
        fam["samples"].append((f"{name}_sum", base, float(total)))
        fam["samples"].append((f"{name}_count", base, float(n)))

    def render(self) -> str:
        lines: List[str] = []
        for name, fam in self._families.items():
            lines.append(f"# HELP {name} {fam['help']}")
            lines.append(f"# TYPE {name} {fam['type']}")
            for sample_name, labels, value in fam["samples"]:
                lines.append(
                    f"{sample_name}{self._fmt_labels(labels)} {self._fmt_value(value)}"
                )
        return "\n".join(lines) + "\n"


def parse_prometheus_text(text: str) -> Dict[str, dict]:
    """Small in-repo exposition parser/validator (CI + bench + tests).

    Returns ``{family: {"type": ..., "samples": [(name, labels, value)]}}``.
    Raises :class:`ValueError` on malformed input: a sample without a TYPE,
    an unparseable value, or a histogram whose cumulative buckets decrease or
    whose ``+Inf`` bucket disagrees with ``_count``.
    """
    families: Dict[str, dict] = {}
    typed: Dict[str, str] = {}
    for raw in text.splitlines():
        line = raw.strip()
        if not line:
            continue
        if line.startswith("# TYPE "):
            _, _, rest = line.partition("# TYPE ")
            parts = rest.split()
            if len(parts) != 2:
                raise ValueError(f"malformed TYPE line: {raw!r}")
            typed[parts[0]] = parts[1]
            families.setdefault(parts[0], {"type": parts[1], "samples": []})
            continue
        if line.startswith("#"):
            continue
        # sample: name{labels} value
        if "{" in line:
            name, _, rest = line.partition("{")
            labels_raw, _, value_raw = rest.rpartition("}")
            labels: Dict[str, str] = {}
            if labels_raw:
                for pair in _split_labels(labels_raw):
                    k, _, v = pair.partition("=")
                    if not (v.startswith('"') and v.endswith('"')):
                        raise ValueError(f"unquoted label value: {raw!r}")
                    labels[k] = v[1:-1].replace('\\"', '"').replace("\\\\", "\\")
        else:
            name, _, value_raw = line.partition(" ")
            labels = {}
        name = name.strip()
        value_raw = value_raw.strip()
        try:
            value = float(value_raw.replace("+Inf", "inf").replace("-Inf", "-inf"))
        except ValueError:
            raise ValueError(f"unparseable sample value: {raw!r}") from None
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[: -len(suffix)] in typed:
                base = name[: -len(suffix)]
                break
        if base not in typed:
            raise ValueError(f"sample {name!r} has no preceding TYPE line")
        families[base]["samples"].append((name, labels, value))
    _validate_histograms(families)
    return families


def _split_labels(raw: str) -> List[str]:
    """Split ``k="v",k2="v2"`` on commas outside quotes."""
    out, buf, in_q, esc = [], [], False, False
    for ch in raw:
        if esc:
            buf.append(ch)
            esc = False
            continue
        if ch == "\\":
            buf.append(ch)
            esc = True
            continue
        if ch == '"':
            in_q = not in_q
        if ch == "," and not in_q:
            out.append("".join(buf))
            buf = []
            continue
        buf.append(ch)
    if buf:
        out.append("".join(buf))
    return out


def _validate_histograms(families: Dict[str, dict]) -> None:
    for base, fam in families.items():
        if fam["type"] != "histogram":
            continue
        # group by the label set minus `le`
        series: Dict[tuple, dict] = {}
        for name, labels, value in fam["samples"]:
            key = tuple(sorted((k, v) for k, v in labels.items() if k != "le"))
            s = series.setdefault(key, {"buckets": [], "count": None})
            if name.endswith("_bucket"):
                le = labels.get("le")
                if le is None:
                    raise ValueError(f"{base}: bucket sample without le label")
                s["buckets"].append((float(le.replace("+Inf", "inf")), value))
            elif name.endswith("_count"):
                s["count"] = value
        for key, s in series.items():
            buckets = sorted(s["buckets"])
            if not buckets:
                raise ValueError(f"{base}: histogram series {key} has no buckets")
            prev = -1.0
            for le, cum in buckets:
                if cum < prev:
                    raise ValueError(f"{base}: non-cumulative buckets at le={le}")
                prev = cum
            if buckets[-1][0] != float("inf"):
                raise ValueError(f"{base}: histogram missing +Inf bucket")
            if s["count"] is not None and buckets[-1][1] != s["count"]:
                raise ValueError(
                    f"{base}: +Inf bucket {buckets[-1][1]} != _count {s['count']}"
                )


# ------------------------------------------------------------ flight recorder
class FlightRecorder:
    """Bounded ring of recent serving events + the crash-dump writer.

    ``record()`` is cheap (one deque append under a small lock) and safe from
    any thread; ``dump()`` snapshots the ring and writes a JSON artifact —
    called from failure paths (restart, quarantine, drain), it must never
    crash recovery, so I/O errors log and return ``None``.

    Clock discipline (DABT105): event stamps use the injectable monotonic
    ``clock`` (comparable with every other serving timestamp); the dump
    artifact additionally carries one wall-clock stamp from the injectable
    ``walltime`` so operators can line artifacts up with external logs.
    """

    def __init__(
        self,
        capacity: int = 256,
        *,
        name: str = "engine",
        clock: Callable[[], float] = time.monotonic,
        walltime: Callable[[], float] = time.time,
        dump_dir: Optional[str] = None,
    ):
        self.name = name
        self._clock = clock
        self._walltime = walltime
        self._dump_dir = dump_dir
        self._lock = threading.Lock()
        self._events: "collections.deque[dict]" = collections.deque(
            maxlen=max(16, int(capacity))
        )
        self._seq = 0
        self.dumps = 0

    def record(self, event: str, **fields: Any) -> None:
        entry = {"t_mono_s": round(self._clock(), 4), "event": event}
        entry.update(fields)
        with self._lock:
            self._seq += 1
            entry["seq"] = self._seq
            self._events.append(entry)

    def events(self) -> List[dict]:
        with self._lock:
            return list(self._events)

    def dump(self, reason: str, **context: Any) -> Optional[str]:
        """Write the ring to ``<dir>/flight-<name>-<pid>-<n>.json``; returns
        the path (None on failure — dumping must never break recovery)."""
        with self._lock:
            events = list(self._events)
            self.dumps += 1
            n = self.dumps
        payload = {
            "reason": reason,
            "recorder": self.name,
            "dumped_at_unix": round(self._walltime(), 3),
            "dumped_at_mono_s": round(self._clock(), 4),
            **context,
            "events": events,
        }
        directory = (
            os.environ.get(ENV_FLIGHT_DIR, "").strip()
            or self._dump_dir
            or os.path.join(tempfile.gettempdir(), "dabt-flight")
        )
        safe = "".join(c if (c.isalnum() or c in "._-") else "_" for c in self.name)
        path = os.path.join(directory, f"flight-{safe}-{os.getpid()}-{n:03d}.json")
        try:
            os.makedirs(directory, exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as fh:
                json.dump(payload, fh, indent=1, default=str)
            os.replace(tmp, path)
        except OSError as e:
            logger.warning("flight recorder dump failed (%s): %s", reason, e)
            return None
        logger.error(
            "flight recorder dumped: reason=%s recorder=%s events=%d -> %s",
            reason,
            self.name,
            len(events),
            path,
        )
        return path


# ------------------------------------------------------------ loop time ledger
# The engine loop's phases, in the order one iteration runs them.  Exclusive
# times: a span opened inside another (``prefill_dispatch`` inside ``admit``)
# pauses its parent, so the totals tile the engine thread's wall time.
LOOP_PHASES: Tuple[str, ...] = (
    "reap",              # _reap_dead_slots: deadlines, cancelled futures
    "admit",             # _admit bookkeeping: queue drain, prefix lookup, pages
    "prefill_dispatch",  # _start_batch / _start_suffix_batch / _chunk_step
    "tick_issue",        # _issue_tick / _piggyback_step: one decode dispatch
    "tick_block",        # np.asarray(ref.nxt): waiting for a tick's result
    "consume",           # token bookkeeping, detokenise at finish, stream wake-ups
    "prestage",          # _prestage_uploads
    "idle_wait",         # the idle / degraded / backoff sleep
    "recover",           # crash-only restart after an engine-fatal error
)
TRACE_PREFIX = "dabt/"
# The device-queue half of the ledger (LoopLedger.note_dispatch / note_marker).
# The wait for a result ends when its program ends on the device; a wait
# shorter than this found the result already there, so its end says only "no
# later than": such a marker closes no segment.
MARKER_PHASE = "tick_block"
MARKER_WAIT_MIN_S = 100e-6
QUEUE_RING = 512  # segments kept for loop_stats(recent=True)
# what a dispatch advances: decode rows, a prompt, or (piggyback) both; any
# other kind (a page clone, a host-tier restore or spill) rides with the
# programs around it and names no segment
TICK_KINDS = frozenset(("tick", "piggyback", "spec"))
PREFILL_KINDS = frozenset(("prefill", "suffix", "chunk", "piggyback"))
QUEUE_FIELDS = ("s", "n", "ticks", "groups", "tokens", "start_tokens", "lag_s", "lag_n")


def _zero_totals() -> List[float]:
    """One segment key's running totals, in :data:`QUEUE_FIELDS`' order."""
    return [0.0, 0, 0, 0, 0, 0, 0.0, 0]


def _segment_key(held: List[tuple]) -> str:
    """What a segment is filed under: a single tick, chunk or group by its
    kind (a group by its shape too), a chunk with the tick behind it, and
    ``mixed`` for anything else.  Riders (``cow``, ``restore``, ``spill``)
    do not count."""
    main = [d for d in held if d[1] in TICK_KINDS or d[1] in PREFILL_KINDS]
    if len(main) == 1:
        _, kind, shape, _, _, _ = main[0]
        return f"{kind}:{shape}" if kind in ("prefill", "suffix") else kind
    if [d[1] for d in main] == ["chunk", "tick"]:
        return "chunk+tick"
    return "mixed"


class _PhaseSpan:
    """One phase's reusable enter/exit pair (phases never nest in themselves,
    so the hot path allocates nothing but the profiler's own annotation)."""

    __slots__ = ("_ledger", "name", "_label", "_t0", "_t1", "_args", "_ann", "s", "n")

    def __init__(self, ledger: "LoopLedger", name: str):
        self._ledger = ledger
        self.name = name
        self._label = TRACE_PREFIX + name
        self._t0 = 0.0
        self._t1 = 0.0  # when it last closed
        self._args: Optional[dict] = None
        self._ann: Any = None
        self.s = 0.0  # exclusive seconds
        self.n = 0  # spans closed

    def __enter__(self) -> "_PhaseSpan":
        led = self._ledger
        now = led._clock()
        stack = led._stack
        if stack:
            parent = stack[-1]
            parent.s += now - parent._t0
        stack.append(self)
        self._t0 = now
        if led._annotation.is_enabled():  # one flag test while no session runs
            self._ann = led._annotation(self._label, **(self._args or {}))
        return self

    def __exit__(self, *exc) -> bool:
        ann = self._ann
        if ann is not None:
            self._ann = None
            ann.__exit__(None, None, None)
        led = self._ledger
        now = led._clock()
        self.s += now - self._t0
        self._t1 = now
        self.n += 1
        stack = led._stack
        stack.pop()
        if stack:
            stack[-1]._t0 = now
        return False


class LoopLedger:
    """Where the engine thread's time went, by phase (see :data:`LOOP_PHASES`),
    and where the device's went, by what its queue held.

    ``with ledger.span("tick_issue"):`` costs two reads of the injectable
    clock and one flag test.  While a ``jax.profiler`` session runs, the
    interval is also a ``dabt/<name>`` event on the calling thread's host
    line of the same xplane the device's events land in, on its clock
    (nested there, exclusive here).  Written by the engine thread only;
    :meth:`snapshot` may be read from any thread (each total is one float).

    **The device's queue.**  The device runs one program at a time in the
    order enqueued, and the engine reads one small result per tick and per
    admission wave, in that order, and waits for nearly every one.  So
    :meth:`note_dispatch` (where a program is enqueued: a running number
    ``seq`` and a stamp) and :meth:`note_marker` (where the wait for a result
    ended) cut the device's time into **segments**: the dispatches between
    two results waited for, from ``max(previous result ready, first of them
    enqueued)`` to ``this result ready``.  Running totals by what a segment
    held (:meth:`queue_snapshot`): ``tick``, ``piggyback``, ``spec``,
    ``chunk`` (one program alone), ``prefill:<rows>x<bucket>`` /
    ``suffix:<rows>x<bucket>`` (one group alone: the program, its insert, its
    activation), ``chunk+tick`` and ``mixed`` for anything else; the time
    the queue stood empty, by the loop phase the engine thread spent it in;
    and the markers by whether they were waited for.  No profiler, no sync,
    no transfer: one clock read a dispatch, none a marker.

    Also carries the prefill padding counters, fed by the same dispatches:
    tokens the prompts held (``real``) against rows x bucket of the programs
    that ran them (``padded``), and the dispatches by shape
    (``prefill_shapes``, keyed ``"<rows>x<bucket>"``; :meth:`list_shapes`
    lists every shape the engine warms at 0, so no key appears under a
    reader)."""

    def __init__(
        self,
        clock: Callable[[], float],
        phases: Tuple[str, ...] = LOOP_PHASES,
        annotation: Any = None,
    ):
        if annotation is None:
            from jax.profiler import TraceAnnotation as annotation
        self._clock = clock
        self._annotation = annotation
        self._stack: List[_PhaseSpan] = []
        self._spans: Dict[str, _PhaseSpan] = {p: _PhaseSpan(self, p) for p in phases}
        self.prefill_tokens_real = 0
        self.prefill_tokens_padded = 0
        self.prefill_shapes: Dict[str, int] = {}
        # -- the device's queue
        self.seq = 0  # the last dispatch's number
        # dispatches no waited marker has covered yet, oldest first:
        # (seq, kind, shape, tokens, start, enqueue stamp)
        self._pending: "collections.deque[tuple]" = collections.deque()
        self._ready: Optional[float] = None  # when the last closing marker's result was there
        # the phase totals at that moment, kept only while nothing is pending
        # (only then can the next dispatch find the queue empty)
        self._ready_totals: Optional[Dict[str, float]] = None
        self._queue: Dict[str, List[float]] = {
            k: _zero_totals() for k in ("tick", "piggyback", "spec", "chunk", "chunk+tick", "mixed")
        }
        self._idle_s = 0.0
        self._idle_n = 0
        self._idle_by_phase: Dict[str, float] = {p: 0.0 for p in phases}
        self._markers = [0, 0]  # waited, not waited
        self._recent: "collections.deque[tuple]" = collections.deque(maxlen=QUEUE_RING)

    def span(self, name: str, **args: Any) -> _PhaseSpan:
        """The phase's enter/exit pair; ``args`` become the profiler
        annotation's arguments (bucket, rows, seq, ...) when a session runs."""
        sp = self._spans[name]
        sp._args = args or None
        return sp

    def list_shapes(self, shapes: Iterable[str]) -> None:
        """Every ``"<rows>x<bucket>"`` the engine warms, listed at 0."""
        for shape in shapes:
            self.prefill_shapes.setdefault(shape, 0)
            for kind in ("prefill", "suffix"):
                self._queue.setdefault(f"{kind}:{shape}", _zero_totals())

    def note_dispatch(
        self, kind: str, rows: int = 0, bucket: int = 0, tokens: int = 0, start: int = 0
    ) -> int:
        """A program (or a group: prefill + insert + activation) is about to
        be enqueued.  ``rows`` x ``bucket`` is a prefill program's shape,
        ``tokens`` the prompt tokens it really runs, ``start`` a chunk's start
        position.  Returns the dispatch's ``seq``."""
        now = self._clock()
        self.seq = seq = self.seq + 1
        shape = ""
        if kind in PREFILL_KINDS:
            shape = f"{rows}x{bucket}"
            self.prefill_tokens_real += int(tokens)
            self.prefill_tokens_padded += int(rows) * int(bucket)
            self.prefill_shapes[shape] = self.prefill_shapes.get(shape, 0) + 1
        if not self._pending and self._ready_totals is not None:
            # every earlier program is known to have ended: the queue stood
            # empty since then, while the engine thread was in these phases
            before, self._ready_totals = self._ready_totals, None
            self._idle_s += now - self._ready
            self._idle_n += 1
            by = self._idle_by_phase
            for p, s in self._totals_at(now).items():
                by[p] += s - before[p]
        self._pending.append((seq, kind, shape, int(tokens), int(start), now))
        return seq

    def note_marker(self, seq: int) -> None:
        """The wait for a result has just ended (the :data:`MARKER_PHASE` span
        closed): every dispatch up to ``seq`` has ended on the device.  If the
        host did wait, that is when, and the dispatches since the last such
        moment become one segment."""
        sp = self._spans[MARKER_PHASE]
        ready = sp._t1
        if ready - sp._t0 < MARKER_WAIT_MIN_S:
            self._markers[1] += 1
            return
        self._markers[0] += 1
        pending = self._pending
        held = []
        while pending and pending[0][0] <= seq:
            held.append(pending.popleft())
        if held:
            begin = held[0][5] if self._ready is None else max(self._ready, held[0][5])
            key = _segment_key(held)
            tot = self._queue.get(key)
            if tot is None:  # a shape nobody listed
                tot = self._queue[key] = _zero_totals()
            tot[0] += ready - begin
            tot[1] += 1
            for _, kind, _, tokens, start, enq in held:
                tot[2] += kind in TICK_KINDS
                if kind in PREFILL_KINDS:
                    tot[3] += 1
                    tot[4] += tokens
                    tot[5] += start
                    tot[6] += max(0.0, begin - enq)
                    tot[7] += 1
            self._recent.append(
                (held[0][0], held[-1][0], key, tuple(d[1] for d in held),
                 tuple(d[2] for d in held), begin, ready)
            )
        self._ready = ready
        self._ready_totals = None if pending else self._totals_at(ready)

    def reset_queue(self) -> None:
        """The programs in flight are gone (a crash-only restart): what was
        pending closes nothing, and no idle time is charged across the gap."""
        self._pending.clear()
        self._ready = self._ready_totals = None

    def _totals_at(self, now: float) -> Dict[str, float]:
        tot = {p: sp.s for p, sp in self._spans.items()}
        if self._stack:
            top = self._stack[-1]
            tot[top.name] += now - top._t0
        return tot

    def seconds(self, name: str) -> float:
        return self._spans[name].s

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """``{phase: {"s": exclusive seconds, "n": spans closed}}``."""
        return {p: {"s": sp.s, "n": sp.n} for p, sp in self._spans.items()}

    def queue_snapshot(self) -> Dict[str, Any]:
        """The device-queue totals: ``{key: {"s", "n", "ticks", "groups",
        "tokens", "start_tokens", "lag_s", "lag_n"}}`` by what a segment held
        (``lag_s``: over its prefill dispatches, segment start - enqueue: what
        was queued in front), ``idle`` (the queue empty: seconds, times, and
        seconds by loop phase) and ``markers`` (results waited for or not)."""
        out: Dict[str, Any] = {
            k: dict(zip(QUEUE_FIELDS, v)) for k, v in list(self._queue.items())
        }
        out["idle"] = {"s": self._idle_s, "n": self._idle_n, "by_phase": dict(self._idle_by_phase)}
        out["markers"] = {"waited": self._markers[0], "not_waited": self._markers[1]}
        return out

    def recent_segments(self) -> List[Dict[str, Any]]:
        """The last :data:`QUEUE_RING` segments, oldest first, on the
        ledger's clock."""
        return [
            {"seq": [lo, hi], "key": key, "kinds": list(kinds), "shapes": list(shapes),
             "start": begin, "ready": ready}
            for lo, hi, key, kinds, shapes, begin, ready in list(self._recent)
        ]


# --------------------------------------------------------- per-request timings
# The keys of ``usage.timings`` (docs/OBSERVABILITY.md "usage.timings").  The
# engine fills the first block at finish; the server adds ``deliver_s`` and
# the ``stream_*`` keys when it hands the terminal event to the socket.
TIMING_KEYS: Tuple[str, ...] = (
    "recv_mono_s", "encode_s", "queue_s", "prefill_s", "decode_s", "detok_s",
    "deliver_s", "stream_lag_max_s", "stream_lag_sum_s", "stream_events",
    "prefill_bucket", "wave_rows", "wave_rows_padded", "prefill_chunks",
    "prefix_hit_tokens", "decode_ticks", "decode_steps",
)
# (span name, timings key) in wire order: consecutive, so they tile the
# interval from receipt to the terminal event's write
_SPAN_KEYS: Tuple[Tuple[str, str], ...] = (
    ("encode", "encode_s"),
    ("queue_wait", "queue_s"),
    ("prefill", "prefill_s"),
    ("decode", "decode_s"),
    ("detok", "detok_s"),
    ("deliver", "deliver_s"),
)


def request_spans(timings: Mapping[str, Any], *, tokens: Optional[int] = None) -> List[dict]:
    """The ``/traces`` span list of one request, from its ``timings``: a
    ``request`` root from receipt to the last stamp known, and its children
    back to back (``t_s`` is seconds since receipt).  ``deliver`` appears
    once the server has written the terminal event (streamed responses)."""
    spans: List[dict] = []
    t = 0.0
    for name, key in _SPAN_KEYS:
        dur = timings.get(key)
        if dur is None:
            continue
        sp = {"name": name, "parent": "request", "t_s": round(t, 6), "dur_s": round(dur, 6)}
        if name == "decode" and tokens is not None:
            sp["tokens"] = tokens
        spans.append(sp)
        t += dur
    return [{"name": "request", "parent": None, "t_s": 0.0, "dur_s": round(t, 6)}] + spans


# ----------------------------------------------------------------- engine obs
class EngineObs:
    """Per-engine observability: span traces, metric histograms, flight ring.

    The ``on_*`` methods are the hot-path entry points (registered in
    dabtlint's DABT104 registry): pure host-side bookkeeping over values
    ``_process_tick`` already holds — a device sync or raw ``time.*()`` call
    anywhere under them is a lint failure, not a code-review hope.
    """

    def __init__(
        self,
        name: str = "engine",
        *,
        clock: Callable[[], float] = time.monotonic,
        trace_capacity: int = 256,
        flight_capacity: int = 256,
        tick_summary_every: int = 64,
        dump_dir: Optional[str] = None,
    ):
        self.name = name
        self._clock = clock
        self.ttft_s = Histogram(TTFT_BUCKETS)
        self.itl_s = Histogram(ITL_BUCKETS)
        self.queue_wait_s = Histogram(WAIT_BUCKETS)
        self.tick_s = Histogram(TICK_BUCKETS)
        self.accept_ratio = Histogram(ACCEPT_BUCKETS)
        self.flight = FlightRecorder(
            flight_capacity, name=name, clock=clock, dump_dir=dump_dir
        )
        self._lock = threading.Lock()
        self._traces: "collections.deque[dict]" = collections.deque(
            maxlen=max(16, int(trace_capacity))
        )
        self.traces_total = 0
        self._tick_summary_every = max(1, int(tick_summary_every))
        self._ticks_seen = 0

    # ---- hot path (DABT104 roots; called from _process_tick bookkeeping) ----
    def on_tick(self, block_s: float, active: int) -> None:
        """One processed tick: duration histogram + a periodic flight-ring
        summary (every Nth tick, so admissions/faults aren't drowned)."""
        self.tick_s.observe(block_s)
        self._ticks_seen += 1
        if self._ticks_seen % self._tick_summary_every == 0:
            self.flight.record(
                "tick_summary",
                ticks=self._ticks_seen,
                active=active,
                block_ms=round(block_s * 1e3, 3),
            )

    def on_spec_tick(self, accepted: int, drafted: int) -> None:
        if drafted > 0:
            self.accept_ratio.observe(accepted / drafted)

    def on_first_token(self, ttft_s: float) -> None:
        self.ttft_s.observe(ttft_s)

    def on_token_gap(self, gap_s: float) -> None:
        self.itl_s.observe(gap_s)

    # ---- request lifecycle (off the per-token path) -------------------------
    def on_admit(self, trace_id: str, priority: str, tenant: str, prompt_tokens: int) -> None:
        self.flight.record(
            "admit",
            trace_id=trace_id,
            priority=priority,
            tenant=tenant,
            prompt_tokens=prompt_tokens,
        )

    def on_shed(self, reason: str, priority: str, trace_id: str = "") -> None:
        self.flight.record(
            "shed", trace_id=trace_id, reason=reason, priority=priority
        )

    def on_finish(self, req: Any, result: Any) -> None:
        """Close a request's trace: observes queue-wait and appends the
        record to the trace ring.  The record keeps ``result.timings`` itself
        (one dict: what ``usage.timings`` carries, and what the server later
        adds ``deliver_s`` to); :meth:`traces` builds the span list from it."""
        tm = result.timings
        self.queue_wait_s.observe(tm["queue_s"])
        trace = {
            "trace_id": req.trace_id,
            "engine": self.name,
            "priority": req.priority,
            "tenant": req.tenant,
            "prompt_tokens": result.prompt_tokens,
            "completion_tokens": result.completion_tokens,
            "restarts": req.restarts,
            # submission stamp in the engine's monotonic clock domain: only
            # DIFFERENCES are meaningful, which is exactly what the workload
            # trace export needs (relative arrival offsets — workload/capture.py)
            "t_submit_s": round(req.submitted_at, 6),
            "timings": tm,
        }
        with self._lock:
            self._traces.append(trace)
            self.traces_total += 1
        self.flight.record(
            "finish",
            trace_id=req.trace_id,
            tokens=result.completion_tokens,
            total_s=round(result.latency_s + tm["detok_s"], 4),
        )

    @staticmethod
    def _rendered(trace: dict) -> dict:
        """A ring record as ``/traces`` shows it: spans and their total from
        the timings as they stand now (``deliver`` once the server wrote)."""
        out = dict(trace)
        out["timings"] = dict(trace["timings"])
        out["spans"] = request_spans(out["timings"], tokens=trace["completion_tokens"])
        out["total_s"] = out["spans"][0]["dur_s"]
        return out

    def traces(self) -> List[dict]:
        with self._lock:
            raw = list(self._traces)
        return [self._rendered(t) for t in raw]

    def trace(self, trace_id: str) -> Optional[dict]:
        with self._lock:
            for t in reversed(self._traces):
                if t["trace_id"] == trace_id:
                    return self._rendered(t)
        return None


# ------------------------------------------------------------------ /metrics
# Task-plane stats provider (tasks/queue.py Worker.register_metrics): the
# queue/bot/delivery plane lives in worker processes without engines, so it
# publishes through a module-level hook instead of the engine registry.  The
# provider is a plain callable returning the queue_stats() shape; a failing
# provider must never break a scrape.
_task_plane_provider: Optional[Callable[[], Dict[str, Any]]] = None


def set_task_plane_provider(fn: Optional[Callable[[], Dict[str, Any]]]) -> None:
    global _task_plane_provider
    _task_plane_provider = fn


def _render_task_plane(x: "_Exposition") -> None:
    prov = _task_plane_provider
    if prov is None:
        return
    try:
        q = prov() or {}
    except Exception:
        logger.warning("task-plane stats provider failed", exc_info=True)
        return
    for qname, qs in sorted((q.get("queues") or {}).items()):
        lab = {"queue": qname}
        x.add("dabt_queue_depth", "gauge", "pending tasks (due + scheduled)", qs.get("pending"), lab)
        x.add("dabt_queue_running", "gauge", "leased (executing) tasks", qs.get("running"), lab)
        x.add("dabt_queue_oldest_pending_age_seconds", "gauge", "age of the oldest pending task", qs.get("oldest_pending_age_s"), lab)
        x.add("dabt_queue_dead", "gauge", "dead-lettered tasks", qs.get("dead"), lab)
    x.add("dabt_queue_dlq_size", "gauge", "dead-letter queue size across queues", q.get("dlq_size"))
    w = q.get("worker") or {}
    x.add("dabt_queue_claims_total", "counter", "task claims by this worker", w.get("claims"))
    x.add("dabt_queue_executed_total", "counter", "task executions started", w.get("executed"))
    x.add("dabt_queue_done_total", "counter", "tasks completed", w.get("done"))
    x.add("dabt_queue_retries_total", "counter", "retries scheduled (backoff or RetryLater)", w.get("retries"))
    x.add("dabt_queue_dead_letters_total", "counter", "tasks dead-lettered by this worker", w.get("dead_lettered"))
    x.add("dabt_queue_reclaimed_leases_total", "counter", "expired leases reclaimed to pending", w.get("reclaimed_leases"))
    x.add("dabt_queue_heartbeats_total", "counter", "lease heartbeat renewals", w.get("heartbeats"))
    x.add("dabt_queue_leases_lost_total", "counter", "executions that lost their lease", w.get("leases_lost"))
    x.add("dabt_queue_completions_discarded_total", "counter", "late completions discarded after a lease loss", w.get("completions_discarded"))
    d = q.get("delivery") or {}
    x.add("dabt_queue_delivery_deduped_total", "counter", "answer parts skipped by the delivery ledger", d.get("deduped_parts"))
    x.add("dabt_queue_delivery_uncertain_total", "counter", "parts skipped after a mid-POST worker death", d.get("uncertain_parts_skipped"))
    x.add("dabt_queue_turn_replays_skipped_total", "counter", "fully-delivered turns skipped on re-execution", d.get("turn_replays_skipped"))
    x.add("dabt_queue_inbound_deduped_total", "counter", "duplicate platform update_ids not re-enqueued", d.get("inbound_updates_deduped"))


# RAG-plane stats provider (rag/index_registry.rag_plane_stats): same hook
# discipline as the task plane — the vector indexes live in whatever process
# built them (API server or ingestion worker), not in the engine registry.
# When no provider is set, fall back to the registry module *if it is already
# imported* — serve-only processes that never touched the rag plane pay
# nothing on a scrape.
_rag_plane_provider: Optional[Callable[[], Dict[str, Any]]] = None


def set_rag_plane_provider(fn: Optional[Callable[[], Dict[str, Any]]]) -> None:
    global _rag_plane_provider
    _rag_plane_provider = fn


def rag_plane_snapshot() -> Dict[str, Any]:
    """Provider output (or the lazily-discovered registry's), never raising —
    shared by /metrics rendering and the /healthz ``rag`` block."""
    prov = _rag_plane_provider
    if prov is None:
        mod = sys.modules.get("django_assistant_bot_tpu.rag.index_registry")
        prov = getattr(mod, "rag_plane_stats", None)
    if prov is None:
        return {}
    try:
        return prov() or {}
    except Exception:
        logger.warning("rag-plane stats provider failed", exc_info=True)
        return {}


def _render_rag_plane(x: "_Exposition") -> None:
    snap = rag_plane_snapshot()
    for name, st in sorted((snap.get("indexes") or {}).items()):
        lab = {"index": name}
        x.add("dabt_rag_index_rows", "gauge", "live vectors in this index", st.get("rows"), lab)
        if st.get("kind") != "ivfpq":
            continue
        x.add("dabt_ann_trained", "gauge", "IVF-PQ structure trained (0=exact fallback)", 1 if st.get("trained") else 0, lab)
        x.add("dabt_ann_exact_fallback", "gauge", "searches currently served by the exact tier", 1 if st.get("exact_fallback") else 0, lab)
        x.add("dabt_ann_nlist", "gauge", "IVF coarse lists", st.get("nlist"), lab)
        x.add("dabt_ann_nprobe", "gauge", "default lists probed per query", st.get("nprobe"), lab)
        x.add("dabt_ann_codes_bytes", "gauge", "device bytes held by PQ code blocks", st.get("codes_bytes"), lab)
        x.add("dabt_ann_codes_bytes_per_vector", "gauge", "PQ code bytes per stored vector", st.get("codes_bytes_per_vector"), lab)
        x.add("dabt_ann_rerank_depth", "gauge", "exact-rerank shortlist depth", st.get("rerank_depth"), lab)
        x.add("dabt_ann_tombstones", "gauge", "removed-but-uncompacted slots", st.get("tombstones"), lab)
        x.add("dabt_ann_pending_appends", "gauge", "rows appended since the last train/compact", st.get("pending_appends"), lab)
        x.add("dabt_ann_drift_frac", "gauge", "fraction of sampled rows nearer a foreign centroid", st.get("drift_frac"), lab)
        x.add("dabt_ann_retrain_advised", "gauge", "drift gauge past the advisory threshold", 1 if st.get("retrain_advised") else 0, lab)
        x.add("dabt_ann_searches_total", "counter", "batched searches served", st.get("searches"), lab)
        x.add("dabt_ann_compactions_total", "counter", "tombstone compactions", st.get("compactions"), lab)
        x.add("dabt_ann_retrains_total", "counter", "full retrains", st.get("retrains"), lab)
        lr = st.get("last_recall") or {}
        if lr.get("recall_at_k") is not None:
            x.add("dabt_ann_last_recall", "gauge", "recall@k from the last probe_recall()", lr.get("recall_at_k"), lab)
        dur = st.get("durability")
        if dur:
            # WAL+snapshot plane (storage/durable.py, docs/DURABILITY.md):
            # wal_records is the writer's sequence high-water mark; snapshot
            # age only renders once a snapshot exists (None until then)
            x.add("dabt_ann_wal_records", "gauge", "WAL sequence high-water mark", dur.get("wal_records"), lab)
            x.add("dabt_ann_wal_bytes", "gauge", "bytes across live WAL segments", dur.get("wal_bytes"), lab)
            x.add("dabt_ann_wal_segments", "gauge", "live WAL segment files", dur.get("wal_segments"), lab)
            if dur.get("snapshot_age_s") is not None:
                x.add("dabt_ann_snapshot_age_s", "gauge", "seconds since the last committed snapshot", dur.get("snapshot_age_s"), lab)
            x.add("dabt_ann_snapshot_count", "gauge", "committed snapshots on disk", dur.get("snapshot_count"), lab)
            x.add("dabt_ann_writable", "gauge", "this process owns the WAL flock (0=read-only recovery)", 1 if dur.get("writable") else 0, lab)
            x.add("dabt_ann_recovery_replayed_records", "gauge", "WAL records replayed at last startup recovery", dur.get("replayed_records"), lab)
            x.add("dabt_ann_recovery_s", "gauge", "wall seconds spent in last startup recovery", dur.get("recovery_s"), lab)
            x.add("dabt_ann_snapshot_fallbacks_total", "counter", "corrupt snapshots skipped for an older valid one", dur.get("snapshot_fallbacks"), lab)
            x.add("dabt_ann_wal_torn_tail_truncations_total", "counter", "torn WAL tails healed at open", dur.get("torn_tail_truncations"), lab)
            x.add("dabt_ann_ledger_entries", "gauge", "idempotency-ledger keys tracked", dur.get("ledger_entries"), lab)
            x.add("dabt_ann_ledger_dedup_hits_total", "counter", "ingests no-opped by the idempotency ledger", dur.get("ledger_dedup_hits"), lab)


def _engine_rows(registry: Any) -> List[Tuple[str, str, Any, Optional[Any]]]:
    """(model, replica, engine, router-or-None) rows for every generator.

    Routers expand into their replicas; the router object itself contributes
    fleet-level samples once.  No lock is taken here — every stats surface
    the renderer touches does its own (fine-grained) locking, so a scrape
    can never hold one component's lock across another's call (the PR 7
    ABBA family this plane is witness-tested against).
    """
    rows: List[Tuple[str, str, Any, Optional[Any]]] = []
    for model, eng in sorted(getattr(registry, "generators", {}).items()):
        reps = getattr(eng, "replicas", None)
        if reps is not None:  # EngineRouter
            for rep in reps:
                rows.append((model, rep.name, rep.engine, eng))
        else:
            rows.append((model, getattr(eng, "name", "0"), eng, None))
    return rows


def render_prometheus(registry: Any) -> str:
    """Render one scrape of everything the registry serves.

    Unifies the existing gauges (engine supervision, scheduler, KV plane,
    speculation, router) with the obs histograms.  Pure read path: safe to
    call from the HTTP event loop while replicas are dead, draining, or
    mid-restart (the scrape-under-duress regression net in tests/test_obs.py).
    """
    x = _Exposition()
    routers_done: set = set()
    for model, replica, eng, router in _engine_rows(registry):
        lab = {"model": model, "replica": replica}
        sup = eng.supervision_stats()
        x.add("dabt_engine_steps_total", "counter", "device decode steps issued", eng.steps, lab)
        x.add("dabt_engine_active_slots", "gauge", "live decode slots", eng.num_active, lab)
        x.add("dabt_engine_queued_depth", "gauge", "accepted-but-unslotted requests", eng.queued_depth(), lab)
        x.add("dabt_engine_healthy", "gauge", "engine liveness predicate (1=serving)", sup["healthy"], lab)
        x.add("dabt_engine_degraded", "gauge", "restart circuit open", sup["degraded"], lab)
        x.add("dabt_engine_heartbeat_age_seconds", "gauge", "engine loop heartbeat age", sup["loop_heartbeat_age_s"], lab)
        x.add("dabt_engine_restarts_total", "counter", "crash-only engine restarts", sup["engine_restarts"], lab)
        x.add("dabt_engine_poisoned_requests_total", "counter", "requests quarantined as poison", sup["poisoned_requests"], lab)
        x.add("dabt_engine_circuit_trips_total", "counter", "restart-circuit trips", sup["circuit_trips"], lab)
        x.add("dabt_engine_restart_resubmitted_total", "counter", "token-less requests salvaged across restarts", sup["restarted_requests_resubmitted"], lab)
        x.add("dabt_engine_reclaimed_slots_total", "counter", "slots reclaimed before finish (deadline/cancel)", eng.reclaimed_slots, lab)
        led_fn = getattr(eng, "loop_stats", None)
        if callable(led_fn):
            # engine-loop time ledger (LoopLedger): where the engine thread's
            # time went, and what the prefill programs padded
            ls = led_fn()
            for phase, tot in ls["loop"].items():
                plab = {**lab, "phase": phase}
                x.add("dabt_engine_loop_seconds_total", "counter", "engine-thread seconds by loop phase (exclusive)", tot["s"], plab)
                x.add("dabt_engine_loop_spans_total", "counter", "engine-loop spans closed, by phase", tot["n"], plab)
            x.add("dabt_prefill_tokens_total", "counter", "prefill positions: prompt tokens run (real) vs rows x bucket of the programs (padded)", ls["prefill_tokens_real"], {**lab, "kind": "real"})
            x.add("dabt_prefill_tokens_total", "counter", "prefill positions: prompt tokens run (real) vs rows x bucket of the programs (padded)", ls["prefill_tokens_padded"], {**lab, "kind": "padded"})
            for shape, n in ls["prefill_shapes"].items():
                x.add("dabt_prefill_programs_total", "counter", "prefill programs dispatched, by rows x bucket (every warmed shape is listed)", n, {**lab, "shape": shape})
            # the device's half: its time by what the queue held between two
            # results the host waited for, and the time the queue stood empty
            dq = ls["device_queue"]
            lag_s = lag_n = 0.0
            for key, tot in dq.items():
                if key in ("idle", "markers"):
                    continue
                kind, _, shape = key.partition(":")
                klab = {**lab, "kind": kind, "shape": shape}
                x.add("dabt_device_queue_seconds_total", "counter", "device seconds by what its queue held between two results the host waited for", tot["s"], klab)
                x.add("dabt_device_queue_segments_total", "counter", "such segments closed, by what they held", tot["n"], klab)
                lag_s += tot["lag_s"]
                lag_n += tot["lag_n"]
            for phase, sec in dq["idle"]["by_phase"].items():
                x.add("dabt_device_queue_idle_seconds_total", "counter", "seconds the device's queue stood empty, by the loop phase the engine thread spent them in", sec, {**lab, "phase": phase})
            x.add("dabt_prefill_start_lag_seconds_total", "counter", "enqueue to start on the device, summed over prefill dispatches (what was queued in front)", lag_s, lab)
            x.add("dabt_prefill_start_lag_dispatches_total", "counter", "prefill dispatches whose start lag was summed", lag_n, lab)
        moe_fn = getattr(eng, "moe_stats", None)
        moe = moe_fn() if callable(moe_fn) else None
        if moe:
            # an expert-parallel rank's routed layers (models/mla_moe.py): where the
            # picks went and how evenly the held experts were loaded
            x.add("dabt_moe_experts_held", "gauge", "routed experts this rank holds", moe["experts_held"], lab)
            for kind in ("decode", "prefill"):
                klab = {**lab, "kind": kind}
                x.add("dabt_moe_picks_total", "counter", "routed picks over all experts", moe[kind]["picks"], klab)
                x.add("dabt_moe_picks_local_total", "counter", "routed picks that landed on experts held here", moe[kind]["picks_local"], klab)
                x.add("dabt_moe_layer_steps_total", "counter", "expert layers run (one per layer per step or program)", moe[kind]["layer_steps"], klab)
                x.add("dabt_moe_experts_hit_total", "counter", "distinct held experts hit, summed over layer-steps", moe[kind]["experts_hit"], klab)
                x.add("dabt_moe_experts_skipped_share", "gauge", "held experts no token landed on, of held x layer-steps (the kernel path does not read them)", moe[kind]["experts_skipped_share"], klab)
                for e, n in enumerate(moe[kind]["tokens_per_expert"]):
                    x.add("dabt_moe_expert_tokens_total", "counter", "tokens routed to a held expert", n, {**klab, "expert": str(moe["first_expert"] + e)})
                if "picks_zero" in moe[kind]:  # a router with identity experts: picks that cost nothing
                    x.add("dabt_moe_picks_zero_total", "counter", "routed picks that fell on identity (zero-compute) experts", moe[kind]["picks_zero"], klab)
                    for n_real, tokens in enumerate(moe[kind]["real_picks_hist"]):
                        x.add("dabt_moe_real_picks_tokens_total", "counter", "tokens by their number of picks on real experts (the spread of compute a token)", tokens, {**klab, "real_picks": str(n_real)})
        dsa_fn = getattr(eng, "dsa_stats", None)
        dsa = dsa_fn() if callable(dsa_fn) else None
        if dsa:
            # learned sparse attention (an indexer's top-k): what a dense causal attention
            # would have attended against what the selection kept, one layer's worth
            x.add("dabt_dsa_index_topk", "gauge", "keys a query attends at most (the indexer's top-k)", dsa["index_topk"], lab)
            for kind in ("decode", "chunk", "prefill"):
                klab = {**lab, "kind": kind}
                x.add("dabt_dsa_programs_total", "counter", "decode steps / chunk programs / other prefill programs that ran a query", dsa[kind]["programs"], klab)
                x.add("dabt_dsa_queries_total", "counter", "queries scored by the indexer", dsa[kind]["queries"], klab)
                x.add("dabt_dsa_pairs_causal_total", "counter", "(query, key) pairs a dense causal attention attends, a layer", dsa[kind]["pairs_causal"], klab)
                x.add("dabt_dsa_pairs_selected_total", "counter", "(query, key) pairs the selection kept, a layer", dsa[kind]["pairs_selected"], klab)
                x.add("dabt_dsa_pairs_scanned_total", "counter", "(query, position) pairs the selection's counting ran over, a layer", dsa[kind]["pairs_scanned"], klab)
        dec_fn = getattr(eng, "decode_path_stats", None)
        if callable(dec_fn):
            # decode fast-path gauges (docs/QUANT.md): configured vs
            # effective fused-tick depth, weight format bits, and the
            # double-buffered upload fraction — the operator evidence that
            # the roofline knobs are actually engaged
            dec = dec_fn()
            x.add("dabt_decode_steps", "gauge", "configured fused decode-tick depth", dec.get("decode_steps"), lab)
            x.add("dabt_decode_steps_effective", "gauge", "decode steps the last tick actually ran (1 = json downgrade)", dec.get("decode_steps_effective"), lab)
            x.add("dabt_decode_json_downgraded_ticks_total", "counter", "fused ticks downgraded to single-step by live json slots", dec.get("json_downgraded_ticks"), lab)
            x.add("dabt_upload_overlap_frac", "gauge", "sampling/block-table upload cycles overlapped with an in-flight tick", dec.get("upload_overlap_frac"), lab)
            x.add("dabt_weight_bits", "gauge", "decode weight format width in bits (16/8/4)", dec.get("weight_bits"), lab)
            # continuous batching (docs/QUANT.md "Continuous batching"):
            # how many chunks rode inside fused ticks (what decode waits out
            # on the others is dabt_device_queue_seconds_total{kind="chunk+tick"})
            x.add("dabt_prefill_chunks_piggybacked_total", "counter", "prefill chunks run inside a fused decode tick", dec.get("prefill_chunks_piggybacked"), lab)
            x.add("dabt_prefill_piggyback", "gauge", "piggybacked-prefill program compiled for this engine", dec.get("prefill_piggyback"), lab)
            x.add("dabt_attn_fp8", "gauge", "fp8 in-dot decode attention engaged", dec.get("attn_fp8"), lab)
            x.add("dabt_decode_kv_kernel", "gauge", "decode K/V write+read is the Pallas paged kernel (1) or the plain XLA path (0)", dec.get("decode_kv_path") == "kernel", lab)
            if dec.get("moe_experts_path"):
                x.add("dabt_moe_experts_kernel", "gauge", "held experts run as the Pallas grouped kernel over the experts hit (1) or the plain XLA pass (0)", dec["moe_experts_path"] == "kernel", lab)
        sl_fn = getattr(eng, "slice_stats", None)
        if callable(sl_fn):
            # mesh-sliced fleet (docs/MULTICHIP.md): which devices this
            # replica's mesh spans and its device-resident HBM ledger — the
            # operator evidence that a replica's footprint lives only on its
            # slice (per-slice ledgers sum to the fleet footprint)
            sl = sl_fn()
            if sl.get("devices"):
                x.add("dabt_slice_devices", "gauge", "devices in this replica's mesh (its slice when pinned)", len(sl["devices"]), lab)
                x.add("dabt_slice_hbm_bytes", "gauge", "device-resident bytes on this replica's devices (weights + KV pool)", sl.get("hbm_bytes"), lab)
                x.add("dabt_slice_hbm_weight_bytes", "gauge", "device-resident weight bytes", sl.get("hbm_weight_bytes"), lab)
                x.add("dabt_slice_hbm_kv_bytes", "gauge", "device-resident KV pool/cache bytes", sl.get("hbm_kv_bytes"), lab)
            if sl.get("slice_id") is not None:
                x.add("dabt_slice_id", "gauge", "device-slice id this replica is pinned to", sl["slice_id"], lab)
        sched = getattr(eng, "scheduler", None)
        if sched is not None:
            st = sched.stats()
            x.add("dabt_sched_queue_depth", "gauge", "admission queue depth", st["queue_depth"], lab)
            x.add("dabt_sched_pressure", "gauge", "queue depth / max_queue", st["pressure"], lab)
            x.add("dabt_sched_est_wait_seconds", "gauge", "estimated queue wait", st["est_wait_s"], lab)
            x.add("dabt_sched_degraded", "gauge", "degradation band active", st["degraded"], lab)
            for reason, n in sorted(st["shed"].items()):
                x.add("dabt_sched_shed_total", "counter", "requests shed at admission, by reason", n, {**lab, "reason": reason})
            for cls, n in sorted(st["admitted"].items()):
                x.add("dabt_sched_admitted_total", "counter", "requests admitted, by class", n, {**lab, "class": cls})
        kv = eng.kv_stats()
        x.add("dabt_kv_prefix_hits_total", "counter", "prefix-cache hits", kv.get("prefix_hits"), lab)
        x.add("dabt_kv_prefix_misses_total", "counter", "prefix-cache misses", kv.get("prefix_misses"), lab)
        x.add("dabt_kv_pages_used", "gauge", "KV pool pages in use", kv.get("kv_pages_used"), lab)
        x.add("dabt_kv_pages_free", "gauge", "KV pool pages free", kv.get("kv_pages_free"), lab)
        x.add("dabt_kv_pages_total", "gauge", "KV pool size in pages", kv.get("kv_pages_total"), lab)
        if "kv_host_entries" in kv:
            # host/disk KV tier (docs/KV_PAGING.md "Tiered KV"): every tier
            # transition is also a flight event; these are the scrape side
            x.add("dabt_kv_tier_host_entries", "gauge", "warm prefixes resident in host DRAM", kv.get("kv_host_entries"), lab)
            x.add("dabt_kv_tier_host_bytes", "gauge", "host-tier bytes in use", kv.get("kv_host_bytes"), lab)
            x.add("dabt_kv_tier_host_pages", "gauge", "pages' worth of KV held in host DRAM", kv.get("kv_host_pages"), lab)
            x.add("dabt_kv_tier_disk_entries", "gauge", "warm prefixes demoted to disk", kv.get("kv_disk_entries"), lab)
            x.add("dabt_kv_tier_spills_total", "counter", "prefix entries spilled into the host tier", kv.get("kv_spills"), lab)
            x.add("dabt_kv_tier_restores_total", "counter", "host-tier entries restored into HBM pages", kv.get("kv_restores"), lab)
            x.add("dabt_kv_tier_restores_inflight", "gauge", "restores dispatched but not yet consumed by a prefill", kv.get("kv_restores_inflight"), lab)
            x.add("dabt_kv_tier_restore_p95_seconds", "gauge", "p95 host-visible restore dispatch latency", (kv.get("kv_restore_p95_ms") or 0.0) / 1e3, lab)
            x.add("dabt_kv_tier_dropped_total", "counter", "warm entries lost (budget/disk failure)", kv.get("kv_tier_dropped"), lab)
            x.add("dabt_kv_tier_migrated_in_total", "counter", "entries absorbed from detaching replicas", kv.get("kv_migrated_in"), lab)
        spec = eng.spec_stats() if callable(getattr(eng, "spec_stats", None)) else None
        if spec is not None:
            x.add("dabt_spec_drafted_total", "counter", "speculative tokens drafted", spec["spec_drafted"], lab)
            x.add("dabt_spec_accepted_total", "counter", "speculative tokens accepted", spec["spec_accepted"], lab)
            x.add("dabt_spec_accept_rate", "gauge", "cumulative speculative accept rate", spec["spec_accept_rate"], lab)
            # spec x fused: the controller's live rung and the scanned
            # verify depth — effective tokens/dispatch ceiling is
            # steps * (depth + 1) on a fully-accepting greedy row
            x.add("dabt_spec_tree_width", "gauge", "speculative tree width the controller currently issues", spec.get("spec_tree_width"), lab)
            x.add("dabt_spec_tree_depth", "gauge", "speculative tree depth (K) the controller currently issues", spec.get("spec_tree_depth"), lab)
            x.add("dabt_spec_verify_steps", "gauge", "scanned verify passes per speculative tick (decode_steps)", getattr(eng, "burst", 1), lab)
        obs = getattr(eng, "obs", None)
        if obs is not None:
            x.add_histogram("dabt_ttft_seconds", "time to first token (submit -> first host token)", obs.ttft_s, lab)
            x.add_histogram("dabt_itl_seconds", "inter-token latency (host batch-arrival gaps)", obs.itl_s, lab)
            x.add_histogram("dabt_queue_wait_seconds", "admission queue wait (submit -> prefill start)", obs.queue_wait_s, lab)
            x.add_histogram("dabt_tick_seconds", "decode tick result wait in _process_tick", obs.tick_s, lab)
            x.add_histogram("dabt_spec_tick_accept_ratio", "per-tick speculative accept ratio (greedy rows)", obs.accept_ratio, lab)
            x.add("dabt_traces_total", "counter", "completed request traces recorded", obs.traces_total, lab)
            x.add("dabt_flight_dumps_total", "counter", "flight-recorder dumps written", obs.flight.dumps, lab)
        if router is not None and id(router) not in routers_done:
            routers_done.add(id(router))
            rlab = {"model": model}
            rs = router.router_stats()
            x.add("dabt_router_replicas", "gauge", "replicas behind the router", rs["n_replicas"], rlab)
            x.add("dabt_router_reroutes_total", "counter", "token-less re-routes off failed replicas", rs["reroutes"], rlab)
            x.add("dabt_router_rerouted_failed_total", "counter", "re-routable failures past the hop budget", rs["rerouted_failed"], rlab)
            x.add("dabt_router_failed_past_first_token_total", "counter", "replica failures not re-routable (tokens emitted)", rs["failed_past_first_token"], rlab)
            x.add("dabt_router_no_replica_total", "counter", "submissions with no replica available", rs["no_replica_available"], rlab)
            x.add("dabt_router_drains_total", "counter", "replica drains", rs["drains"], rlab)
            x.add("dabt_router_replicas_added_total", "counter", "replicas added to the fleet (scale-up)", rs.get("replicas_added"), rlab)
            x.add("dabt_router_replicas_removed_total", "counter", "replicas drained and detached (scale-down)", rs.get("replicas_removed"), rlab)
            x.add("dabt_router_replica_restarts_total", "counter", "replica restarts (operator or drain-restart)", rs.get("replica_restarts"), rlab)
            x.add("dabt_router_affinity_hit_rate", "gauge", "prefix-affinity dispatch hit rate", rs["affinity_hit_rate"], rlab)
            if "slices_total" in rs:
                # sliced-fleet capacity: free slices == honest scale-up
                # headroom (0 free -> add_replica is a no_capacity rejection)
                x.add("dabt_router_slices_total", "gauge", "device slices planned on this host", rs["slices_total"], rlab)
                x.add("dabt_router_slices_free", "gauge", "device slices not pinned to a replica", rs["slices_free"], rlab)
                x.add("dabt_router_replica_devices", "gauge", "devices per replica slice", rs["replica_devices"], rlab)
            # fleet warm-state durability (scale-down migration; the
            # pages_lost counter is the pre-migration visibility satellite)
            x.add("dabt_kv_tier_pages_lost_at_detach_total", "counter", "warm KV pages dropped by replica detaches", rs.get("pages_lost_at_detach"), rlab)
            x.add("dabt_kv_tier_pages_migrated_total", "counter", "warm KV pages migrated at scale-down", rs.get("pages_migrated"), rlab)
            x.add("dabt_kv_tier_entries_migrated_total", "counter", "warm prefix entries migrated at scale-down", rs.get("entries_migrated"), rlab)
            preg = rs.get("prefix_registry")
            if preg:
                x.add("dabt_kv_fleet_prefixes", "gauge", "distinct warm prefixes known fleet-wide", preg.get("prefixes"), rlab)
                for tier in ("hbm", "host", "disk"):
                    x.add("dabt_kv_fleet_holdings", "gauge", "fleet prefix-registry holdings by tier", preg.get(tier), {**rlab, "tier": tier})
            for rep_stats in rs["replicas"]:
                plab = {"model": model, "replica": rep_stats["name"]}
                x.add("dabt_replica_draining", "gauge", "replica drain flag", rep_stats["draining"], plab)
                x.add("dabt_replica_breaker_open", "gauge", "router breaker not closed", rep_stats["breaker"] != "closed", plab)
                x.add("dabt_replica_dispatched_total", "counter", "requests dispatched to replica", rep_stats["dispatched"], plab)
    for model, asc in sorted(getattr(registry, "autoscalers", {}).items()):
        # SLO autoscaler (serving/autoscaler.py): every decision is
        # scrapeable — fleet size vs bounds, scale/degrade counters, and the
        # last control tick's raw signals
        lab = {"model": model}
        st = asc.stats()
        x.add("dabt_autoscale_replicas", "gauge", "current fleet size", st["replicas"], lab)
        x.add("dabt_autoscale_min_replicas", "gauge", "fleet floor", st["min_replicas"], lab)
        x.add("dabt_autoscale_max_replicas", "gauge", "fleet ceiling", st["max_replicas"], lab)
        x.add("dabt_autoscale_ticks_total", "counter", "control-loop iterations", st["ticks"], lab)
        x.add("dabt_autoscale_scale_ups_total", "counter", "replicas added by the controller", st["scale_ups"], lab)
        x.add("dabt_autoscale_scale_downs_total", "counter", "replicas removed by the controller", st["scale_downs"], lab)
        x.add("dabt_autoscale_scale_up_failures_total", "counter", "failed scale-up attempts", st["scale_up_failures"], lab)
        for reason, n in sorted(st.get("scale_up_skipped", {}).items()):
            # WHY a wanted scale-up was held back: no_capacity (slices
            # exhausted — at the hardware limit) vs cooldown (flap-damped)
            # vs bounds (the configured max_replicas ceiling)
            x.add("dabt_autoscale_scale_up_skipped_total", "counter", "overloaded ticks whose scale-up was held back, by reason", n, {**lab, "reason": reason})
        x.add("dabt_autoscale_at_hardware_limit", "gauge", "last scale-up attempt found no free device slice", st.get("at_hardware_limit"), lab)
        x.add("dabt_autoscale_degrade_active", "gauge", "load-adaptive degradation engaged", st["degrade_active"], lab)
        x.add("dabt_autoscale_degrade_engaged_total", "counter", "degradation band engagements", st["degrade_engaged"], lab)
        x.add("dabt_autoscale_replica_seconds_total", "counter", "integral of fleet size over time", st["replica_seconds"], lab)
        sig = st.get("last_signals", {})
        x.add("dabt_autoscale_slo_burn", "gauge", "last tick's p95 TTFT / SLO", sig.get("burn"), lab)
        x.add("dabt_autoscale_ttft_p95_seconds", "gauge", "last tick's observed p95 TTFT", sig.get("ttft_p95_s"), lab)
        x.add("dabt_autoscale_shed_rate", "gauge", "last tick's admission sheds per second", sig.get("shed_rate"), lab)
        x.add("dabt_autoscale_est_wait_seconds", "gauge", "last tick's worst predicted queue wait", sig.get("est_wait_s"), lab)
        x.add("dabt_autoscale_kv_frac", "gauge", "last tick's KV pool occupancy", sig.get("kv_frac"), lab)
    for model, emb in sorted(getattr(registry, "embedders", {}).items()):
        lab = {"model": model}
        x.add("dabt_embed_queue_depth", "gauge", "embedding coalescer queue depth", emb._queue.qsize(), lab)
        x.add("dabt_embed_shed_total", "counter", "embedding requests shed", getattr(emb, "shed", 0), lab)
    # cross-process fleet plane (serving/fleet.py, docs/FLEET.md): the server
    # side (every serve process has a plane) and — when this process also
    # fronts the fleet — the FleetRouter's dispatch counters
    plane = getattr(registry, "fleet_plane", None)
    if plane is not None:
        try:
            ps = plane.stats()
        except Exception:  # pragma: no cover - defensive scrape path
            ps = None
        if ps:
            flab = {"peer": ps.get("name", ""), "pool": ps.get("pool", "")}
            x.add("dabt_fleet_pool_info", "gauge", "fleet pool role of this process (labels carry identity)", 1, flab)
            x.add("dabt_fleet_gossip_seq", "counter", "prefix gossip delta-log sequence", ps.get("gossip_seq"), flab)
            x.add("dabt_fleet_kv_puts_total", "counter", "KV wire entries absorbed from peers", ps.get("kv_puts"), flab)
            x.add("dabt_fleet_kv_gets_total", "counter", "KV wire entries exported to peers", ps.get("kv_gets"), flab)
            x.add("dabt_fleet_kv_put_rejects_total", "counter", "KV wire entries refused at absorb", ps.get("kv_put_rejects"), flab)
            x.add("dabt_fleet_pages_in_total", "counter", "KV pages received over the fleet wire", ps.get("pages_in"), flab)
            x.add("dabt_fleet_pages_out_total", "counter", "KV pages shipped over the fleet wire", ps.get("pages_out"), flab)
            x.add("dabt_fleet_handoff_pushes_total", "counter", "prefill->decode handoff pushes", ps.get("pushes"), flab)
            x.add("dabt_fleet_handoff_push_failures_total", "counter", "failed handoff pushes", ps.get("push_failures"), flab)
            x.add("dabt_fleet_pool_rejects_total", "counter", "requests shed by the pool-role guard", ps.get("pool_rejects"), flab)
            x.add("dabt_fleet_pool_bypasses_total", "counter", "forced requests past the pool-role guard", ps.get("pool_bypasses"), flab)
            x.add("dabt_fleet_kv_integrity_rejects_total", "counter", "checksum-failed KV wire payloads rejected", ps.get("kv_integrity_rejects"), flab)
            x.add("dabt_fleet_idem_executions_total", "counter", "idempotency-keyed executions owned by this process", ps.get("idem_executions"), flab)
            x.add("dabt_fleet_idem_hits_total", "counter", "duplicate dispatches answered from the idempotency ledger", ps.get("idem_hits"), flab)
            x.add("dabt_fleet_idem_coalesced_total", "counter", "duplicate dispatches coalesced onto an in-flight execution", ps.get("idem_coalesced"), flab)
            x.add("dabt_fleet_idem_ledger_entries", "gauge", "live idempotency ledger entries", ps.get("idem_ledger"), flab)
    frouter = getattr(registry, "fleet_router", None)
    if frouter is not None:
        try:
            fs = frouter.stats()
        except Exception:  # pragma: no cover - defensive scrape path
            fs = None
        if fs:
            flab = {"model": fs.get("model", "")}
            x.add("dabt_fleet_peers_total", "gauge", "configured fleet peers", fs.get("peers_total"), flab)
            x.add("dabt_fleet_peers_healthy", "gauge", "fleet peers passing health refresh", fs.get("peers_healthy"), flab)
            x.add("dabt_fleet_reroutes_total", "counter", "token-less cross-peer re-routes", fs.get("reroutes"), flab)
            x.add("dabt_fleet_rerouted_failed_total", "counter", "requests failed after exhausting re-routes", fs.get("rerouted_failed"), flab)
            x.add("dabt_fleet_no_peer_available_total", "counter", "dispatches that found no live peer", fs.get("no_peer_available"), flab)
            x.add("dabt_fleet_affinity_hits_total", "counter", "dispatches landing on a prefix-holder peer", fs.get("affinity_hits"), flab)
            x.add("dabt_fleet_affinity_misses_total", "counter", "dispatches missing every holder peer", fs.get("affinity_misses"), flab)
            x.add("dabt_fleet_prefix_pulls_total", "counter", "cross-process prefix pulls completed", fs.get("prefix_pulls"), flab)
            x.add("dabt_fleet_pages_shipped_total", "counter", "KV pages shipped by pulls and handoffs", fs.get("pages_shipped"), flab)
            x.add("dabt_fleet_handoffs_total", "counter", "disaggregated prefill->decode handoffs", fs.get("handoffs"), flab)
            x.add("dabt_fleet_handoff_fallbacks_total", "counter", "handoffs that fell back to unified dispatch", fs.get("handoff_fallbacks"), flab)
            x.add("dabt_fleet_net_timeout_retries_total", "counter", "same-peer retries after a read-phase wire death", fs.get("timeout_retries"), flab)
            x.add("dabt_fleet_net_ttl_drops_total", "counter", "partitioned peers whose gossip holdings aged out", fs.get("ttl_drops"), flab)
            x.add("dabt_fleet_net_gossip_digest_mismatches_total", "counter", "diverged gossip logs forced onto the reset-snapshot path", fs.get("gossip_digest_mismatches"), flab)
            x.add("dabt_fleet_net_reconciles_total", "counter", "post-heal anti-entropy reconciliations completed", fs.get("reconciles"), flab)
            x.add("dabt_fleet_net_reconcile_last_seconds", "gauge", "last heal-to-converged reconciliation time", fs.get("reconcile_last_s"), flab)
            x.add("dabt_fleet_pull_integrity_rejects_total", "counter", "prefix pulls rejected by the receiver's checksum", fs.get("pull_integrity_rejects"), flab)
            x.add("dabt_fleet_pull_refetches_total", "counter", "prefix pulls re-fetched after a corrupt transfer", fs.get("pull_refetches"), flab)
            for reason, n in sorted((fs.get("refresh_failure_reasons") or {}).items()):
                x.add("dabt_fleet_refresh_failures_total", "counter", "peer refresh failures by classified reason", n, {"model": fs.get("model", ""), "reason": reason})
            for peer in fs.get("peers", []):
                plab = {"model": fs.get("model", ""), "peer": peer["name"], "pool": peer.get("pool", "")}
                x.add("dabt_fleet_peer_healthy", "gauge", "peer health from the last refresh", 1 if peer.get("healthy") else 0, plab)
                x.add("dabt_fleet_peer_dispatched_total", "counter", "requests dispatched to this peer", peer.get("dispatched"), plab)
                x.add("dabt_fleet_peer_ttl_dropped", "gauge", "peer currently aged out of the prefix registry", 1 if peer.get("ttl_dropped") else 0, plab)
    _render_task_plane(x)
    _render_rag_plane(x)
    return x.render()


# ------------------------------------------------------------- JSON logging
class JsonLogFormatter(logging.Formatter):
    """One JSON object per log line: ``ts``/``level``/``logger``/``event``
    plus any of the structured serving fields (``trace_id``, ``model``,
    ``replica``, ``reason``, ...) attached via ``logger.info(..., extra=...)``.
    (``record.created`` is stamped by the logging module itself — this
    formatter makes no time calls of its own.)"""

    FIELDS = ("trace_id", "model", "replica", "event", "reason", "site", "tenant")

    def format(self, record: logging.LogRecord) -> str:
        out: Dict[str, Any] = {
            "ts": round(record.created, 3),
            "level": record.levelname.lower(),
            "logger": record.name,
            "event": record.getMessage(),
        }
        for f in self.FIELDS:
            v = record.__dict__.get(f)
            if v is not None and f not in out:
                out[f] = v
        if record.exc_info and record.exc_info[0] is not None:
            out["exc"] = repr(record.exc_info[1])
        return json.dumps(out, ensure_ascii=False, default=str)


def setup_json_logging(*, force: bool = False, stream: Any = None) -> bool:
    """Opt-in structured logging for the serving process: ``DABT_LOG_JSON=1``
    (or ``--log-json`` / ``force=True``) swaps the root handler's formatter
    for :class:`JsonLogFormatter`.  Plain-text default is untouched when the
    gate is off.  Returns whether JSON logging is active."""
    if not force and os.environ.get(ENV_LOG_JSON, "").strip() not in ("1", "true", "yes"):
        return False
    root = logging.getLogger()
    if not root.handlers:
        handler = logging.StreamHandler(stream)
        root.addHandler(handler)
        root.setLevel(logging.INFO)
    for handler in root.handlers:
        handler.setFormatter(JsonLogFormatter())
    return True
