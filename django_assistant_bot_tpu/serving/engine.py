"""Continuous-batching generation engine + coalescing embedding engine.

This is the serving-side fix for the two deficiencies SURVEY.md §3.3 flags in the
reference's gpu_service: the unbatched per-text embedding loop
(assistant/ai/embedders/transformers.py:15-29) and single-stream ``generate`` with no
KV-cache reuse across requests (assistant/ai/providers/transformers.py:35-94).

Design (TPU-first):

- **Slot-based continuous batching.**  A fixed-size KV cache (``max_slots`` rows)
  lives in HBM.  New requests are prefilled on their own small batch (bucketed
  sequence lengths — a handful of compiled shapes, no dynamic shapes ever), then
  their K/V rows are inserted into free slots; one jit'd ``decode_tick`` advances
  *all* live slots a token per call.  Requests join and leave the batch without
  recompilation or disturbing other streams.
- **Sampling on device.**  temperature/top-p ride as [slots] arrays inside the tick;
  only sampled token ids (a few ints) cross back to host per step.
- **Cache donation.**  The decode tick donates the cache buffers, so XLA updates the
  multi-GB cache in place instead of copying.
- **Dedicated engine thread.**  Device steps are blocking; the engine runs them on
  its own thread and talks to asyncio via thread-safe futures, so the HTTP event
  loop never stalls (the reference instead forked gunicorn workers with a full model
  replica each — gpu_service/gunicorn_conf.py:9).
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import queue
import threading
import time
from concurrent.futures import Future, InvalidStateError
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..models import DecoderConfig, EncoderConfig, encoder, module_for
from ..models.mixtral import zero_stat_width
from ..ops.attention import FLASH_BLOCK
from ..ops.sampling import sample_logits
from ..parallel.sharding import mesh_scope
from .obs import EngineObs, LoopLedger, new_trace_id
from .scheduler import DeadlineExceeded, RequestScheduler, SchedulerRejected
from .tokenizer import Tokenizer

logger = logging.getLogger(__name__)


class RequestPoisoned(RuntimeError):
    """A failure attributable to ONE request (garbage sampled ids from a NaN'd
    logits row, a detokenization crash): that request's future fails with this
    and its slot is quarantined — batch-mates keep decoding."""

    def __init__(self, detail: str, slot: Optional[int] = None):
        super().__init__(detail)
        self.slot = slot


class EngineUnavailable(RuntimeError):
    """The engine's restart circuit is open (too many crash-only restarts in
    the window): ``submit()`` fast-fails with this instead of queueing work
    the engine cannot serve.  The HTTP layer maps it to 503 + ``Retry-After``
    (``retry_after_s`` is the remaining cooldown)."""

    def __init__(self, detail: str, retry_after_s: float):
        super().__init__(f"{detail} (retry after {retry_after_s:.1f}s)")
        self.retry_after_s = float(retry_after_s)


def _resident_bytes(tree) -> int:
    """Device-RESIDENT bytes of a pytree: one charge per addressable shard,
    so an array replicated across a mesh axis is charged per copy and a
    sharded array is charged exactly once in total.  Host (numpy) leaves
    charge their plain nbytes.  Init-time accounting only (the per-slice HBM
    ledger, docs/MULTICHIP.md) — reads array METADATA, never device memory."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards:
            total += sum(int(s.data.nbytes) for s in shards)
        else:
            total += int(getattr(leaf, "nbytes", 0))
    return total


def _replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P())


def _safe_resolve(fut: Future, *, result=None, exc: Optional[BaseException] = None):
    """set_result/set_exception tolerant of a client cancelling concurrently."""
    try:
        if exc is not None:
            fut.set_exception(exc)
        else:
            fut.set_result(result)
    except InvalidStateError:  # future was cancelled mid-flight
        pass


def pick_bucket(n: int, buckets: Sequence[int], cap: int) -> int:
    for b in buckets:
        if n <= b and b <= cap:
            return b
    return cap


def prefill_shapes(
    chunk_size: int, wave: int, buckets: Optional[Sequence[int]] = None
) -> Dict[int, tuple]:
    """Every prefill program an engine dispatches, as ``{bucket: row counts}``:
    what warm-up compiles and what :func:`plan_prefill` picks from, so the two
    cannot drift apart.

    Sequence buckets: the ones a deployment names, or else half a flash block
    (``FLASH_BLOCK``: the 128 positions by which the flash kernel is admitted,
    not its tile; a chat format alone is ~18 tokens) and then every whole block up to
    ``chunk_size``, so a prompt of more than a block is padded by less than
    one; ``chunk_size`` itself is always the last (a prompt of up to a chunk
    rides one program).  Rows: a program holds at most one chunk's positions
    (rows x bucket <= ``chunk_size``) and at most ``wave`` rows; under that
    cap a bucket has 1, 2 and the largest power of two, so a wave of one or
    two pays for no padding row and many short prompts still share one read
    of the weights."""
    seq = buckets or (FLASH_BLOCK // 2, *range(FLASH_BLOCK, chunk_size, FLASH_BLOCK))
    shapes = {}
    for b in sorted({int(b) for b in seq if b < chunk_size} | {chunk_size}):
        cap = max(1, min(chunk_size // b, wave))
        shapes[b] = tuple(sorted({1, min(2, cap), 1 << (cap.bit_length() - 1)}))
    return shapes


# What a prefill program costs beyond its positions, in positions: its read of
# the weights and its dispatch, which it pays whatever its size.  Set from one
# ``_prefill`` program's time by shape on a v5e at Qwen2.5-7B int8 widths
# (``tools/time_prefill.py``; CHANGES.md, PR 35: 1 x 128 15.1 ms, 1 x 512
# 45.8, 8 x 128 77.0): with 96 to 127 here, 1-8 rows of any one bucket ride
# the programs those times make cheapest; at 128 six rows of 128 take 8 x 128
# (77.0 ms) where 2 + 2 + 2 reads 67.7, below 48 seven take 2 + 2 + 2 + 1.
PREFILL_PROGRAM_POSITIONS = 96


def plan_prefill(shapes: Dict[int, tuple], lengths: Sequence[int]) -> List[tuple]:
    """The programs one admission wave dispatches: ``[(rows, bucket, members)]``
    with ``members`` the indices into ``lengths`` (the tokens each admitted row
    runs, in admission order) that ride the program, ``rows - len(members)`` of
    it padding.  Rows are grouped by their own bucket (a short prompt never
    pays a long one's), and a group takes the programs of ``shapes`` that cost
    least: a program costs its positions plus ``PREFILL_PROGRAM_POSITIONS``,
    fewer programs on a tie.  3 rows at 128 ride 2 + 1, not 8; 5 rows at 64
    ride one program of 8."""
    groups: Dict[int, List[int]] = {}
    for i, n in enumerate(lengths):
        groups.setdefault(pick_bucket(n, shapes, max(shapes)), []).append(i)
    programs = []
    for bucket, members in groups.items():
        # best[n]: (cost, programs, their rows) of the cheapest cover of n rows
        best: List[tuple] = [(0, 0, ())]
        for n in range(1, len(members) + 1):
            best.append(min(
                (cost + r * bucket + PREFILL_PROGRAM_POSITIONS, count + 1, rows + (r,))
                for r in shapes[bucket]
                for cost, count, rows in (best[max(0, n - r)],)
            ))
        for rows in sorted(best[-1][2], reverse=True):
            programs.append((rows, bucket, members[:rows]))
            members = members[rows:]
    return programs


@dataclasses.dataclass
class GenerationResult:
    token_ids: List[int]
    text: str
    prompt_tokens: int
    completion_tokens: int
    length_limited: bool
    ttft_s: float = 0.0
    latency_s: float = 0.0
    # per-request spans and counts from the socket inward (serving/obs.py
    # TIMING_KEYS): filled by ``_finish`` from the stamps below; the server
    # adds ``deliver_s`` / ``stream_*`` when it writes the terminal event
    timings: Optional[dict] = None

    def usage_dict(self, model: str) -> dict:
        """The wire-format usage object (HTTP responses, provider AIResponse
        usage, SSE terminal events) — one construction for every consumer."""
        out = {
            "model": model,
            "prompt_tokens": self.prompt_tokens,
            "completion_tokens": self.completion_tokens,
            "total_tokens": self.prompt_tokens + self.completion_tokens,
            "ttft_s": self.ttft_s,
            "latency_s": self.latency_s,
        }
        if self.timings is not None:
            out["timings"] = dict(self.timings)
        return out


@dataclasses.dataclass
class _Request:
    prompt_ids: List[int]
    max_tokens: int
    temperature: float
    top_p: float
    future: Future
    submitted_at: float
    json: bool = False  # grammar-constrained JSON decoding (ops/json_fsm.py)
    # leading prompt tokens that form a cacheable shared prefix (system prompt
    # + packed RAG context); 0 = no prefix-cache participation
    prefix_len: int = 0
    first_token_at: Optional[float] = None
    # scheduling metadata (serving/scheduler.py): class tag, fair-share tenant,
    # absolute monotonic deadline, and whether try_admit already reserved depth
    priority: str = "interactive"
    tenant: str = "default"
    deadline_at: Optional[float] = None
    admitted: bool = False
    # slot-residency start (prefill begins): the service-time sample the
    # scheduler's estimated-wait model is fed on finish
    started_at: Optional[float] = None
    # crash-only restarts this request survived (re-submitted with no tokens
    # emitted); bounded by the engine's max_request_restarts so one poisoned
    # prompt that deterministically kills the device cannot retry forever
    restarts: int = 0
    # per-request token event sink (serving/streaming.py TokenStream): fed a
    # deque-append per sampled id from _process_tick — already host-resident
    # data, so streaming adds zero device syncs.  None = request/response.
    stream: Any = None
    # paged KV plane: worst-case page reservation (ceil((prompt + max_tokens)
    # / page_size)) — the scheduler's KV-pressure admission charge
    kv_pages: int = 0
    # observability (serving/obs.py): the request/trace correlation id —
    # client X-Request-Id or generated at submit; stable across router
    # re-route hops and crash-restart re-submissions
    trace_id: str = ""
    # host-tier KV restore: admission found this request's prefix in the
    # host tier and uploaded it into fresh pages ahead of the suffix prefill
    # (the restores-in-flight gauge decrements when the slot activates)
    restored_from_host: bool = False
    # receipt at the socket (the /dialog/ handler's first line, or the top of
    # generate()): encode_s = submitted_at - received_at.  None = submit()
    # was the entry point
    received_at: Optional[float] = None
    # what the prefill program this request rode looked like: its sequence
    # bucket, its real rows and the rows it was compiled for (chunked
    # prefills: chunk_size, 1, 1), and the prompt tokens a prefix hit spared it
    prefill_bucket: int = 0
    wave_rows: int = 0
    wave_rows_padded: int = 0
    prefix_hit_tokens: int = 0


# slot-cache precision knob -> concrete dtype (None = the model's cfg.dtype);
# "bf16" is explicit bfloat16 even on f32 dev models, fp8 halves KV bytes
KV_CACHE_DTYPES = {
    None: None,
    "bf16": jnp.bfloat16,
    "fp8": jnp.float8_e4m3fn,
    "fp8_e5m2": jnp.float8_e5m2,
}


@dataclasses.dataclass
class _HostHit:
    """A prefix found in the HOST tier (the HBM registry missed).  Admission
    allocates fresh pages, uploads the spilled K/V into them ahead of the
    slot's suffix prefill (restore-then-suffix-prefill — bit-identical to a
    cold full prefill, since the bytes ARE the prefill's bytes), and
    re-registers the restored pages so later requests share them in HBM.
    Carries ``.length`` so the admission/suffix machinery treats it exactly
    like a device registry hit."""

    entry: Any  # kv_pool.HostPrefixEntry

    @property
    def length(self) -> int:
        return self.entry.length


@dataclasses.dataclass
class _Slot:
    request: _Request
    generated: List[int] = dataclasses.field(default_factory=list)
    # host arrival time of the previous token (inter-token-latency samples)
    last_token_at: Optional[float] = None
    # decode steps this slot sat through (fused ticks advance it by the tick's
    # step count even when EOS lands mid-tick) — the per-token denominator the
    # scheduler's service-time EMA needs so N-step ticks don't inflate the
    # predicted queue wait (docs/SCHEDULING.md)
    resident_steps: int = 0
    # prefill chunk dispatches this request consumed before activation —
    # charged to the scheduler's per-token service model alongside
    # resident_steps so piggybacked (continuous-batching) prefill work
    # doesn't vanish from the predicted queue wait / Retry-After math
    prefill_chunks: int = 0
    # tick results that carried at least one token for this slot (the
    # activation's first token included)
    decode_ticks: int = 0


def _take_stats(cache):
    """Inside a tick program: the counters a cache carries (``cache.stats``,
    e.g. the routed layers' of ``models/mla_moe.py``) as a 1-tuple to return
    with the tokens, and the cache with them zeroed, so each count is handed
    out once; ``()`` for a cache that carries none."""
    stats = getattr(cache, "stats", None)
    if stats is None:
        return cache, ()
    return cache._replace(stats=jnp.zeros_like(stats)), (stats,)


@dataclasses.dataclass
class _TickRef:
    """One issued-but-not-yet-processed device result.

    ``slots`` records (slot, epoch) for every slot that was live at issue time;
    processing skips entries whose slot epoch has moved on (request finished by an
    earlier tick — its later speculative tokens are garbage and are dropped).

    ``first=True`` marks an activation: ``nxt`` is the [Bp] first sampled tokens
    of a freshly-prefilled admission wave (kept on device so admission never
    blocks on a host round trip); entry ``offset + j`` belongs to ``slots[j]``
    (rows below ``offset`` are batch-bucket padding).  FIFO order in the
    inflight deque guarantees they are appended before any burst tokens of the
    same slots.
    """

    nxt: Any  # device array: [burst, max_slots] sampled ids, or [Bp] when first
    slots: List[tuple]
    first: bool = False
    offset: int = 0
    # speculative tick: [max_slots] valid-token counts — entry k of nxt[:, b]
    # is real only for k < n_new[b] (the rest are rejected-draft garbage)
    n_new: Any = None
    # (width, depth) rung the speculative tick drafted at (the controller may
    # issue a narrower/shallower rung than the config maximum — acceptance
    # accounting needs the per-tick value, not the engine knob)
    spec_rung: Any = None
    # the program's own counters for this tick (a cache that carries `stats`):
    # a device array that arrives with `nxt`, or None
    aux: Any = None
    # the number of the last dispatch this result covers (LoopLedger.seq where
    # it was enqueued): when the host has it, all of them have ended
    seq: int = 0


@dataclasses.dataclass
class _ChunkedPrefill:
    """An in-flight chunked prefill: one chunk advances per engine-loop iteration,
    interleaved with decode ticks (prefill/decode disaggregation)."""

    request: _Request
    slot: int
    ids: np.ndarray  # [n_chunks, chunk_size] — every chunk is full of real tokens
    starts: List[int]  # absolute start position of each chunk
    n: int  # true prompt length
    step: int = 0  # chunks completed


class GenerationEngine:
    """Continuous-batching decode engine over one decoder model."""

    def __init__(
        self,
        cfg: DecoderConfig,
        params,
        tokenizer: Tokenizer,
        *,
        max_slots: int = 8,
        max_seq_len: Optional[int] = None,
        top_k: int = 50,
        prefill_buckets: Optional[Sequence[int]] = None,
        idle_poll_s: float = 0.002,
        chunk_size: int = 512,
        lookahead: int = 3,
        burst: int = 8,
        decode_steps: Optional[int] = None,
        prefix_cache_size: int = 8,
        prefix_min_tokens: int = 32,
        prefix_cache_max_bytes: int = 1 << 30,
        kv_cache_dtype: Optional[str] = None,
        speculative: int = 0,
        spec_width: int = 4,
        spec_probe_every: int = 64,
        spec_explore_every: int = 32,
        prefill_piggyback: bool = True,
        prefill_wave: int = 0,
        attn_fp8: bool = False,
        kv_page_size: int = 0,
        kv_pages: int = 0,
        kv_host_bytes: int = 0,
        kv_spill_dir: Optional[str] = None,
        kv_host_writethrough: bool = True,
        scheduler: Optional[RequestScheduler] = None,
        faults=None,
        max_restarts: int = 5,
        restart_window_s: float = 60.0,
        restart_backoff_s: float = 0.05,
        restart_backoff_max_s: float = 2.0,
        degraded_cooldown_s: float = 30.0,
        heartbeat_degraded_s: float = 30.0,
        max_request_restarts: int = 2,
        name: str = "engine",
        obs: bool = True,
        obs_dump_dir: Optional[str] = None,
        mesh=None,
        clock: Callable[[], float] = time.monotonic,
        sleep: Callable[[float], None] = time.sleep,
    ):
        # Injectable time (dabtlint DABT105): every timestamp and backoff in
        # the engine flows through these two callables, so fake-clock tests
        # can drive deadlines/backoff/heartbeats deterministically.  Defaults
        # are the real thing — production behavior is byte-identical.
        self._clock = clock
        self._sleep = sleep
        # Observability plane (serving/obs.py, docs/OBSERVABILITY.md): span
        # traces, metric histograms and the crash flight recorder.  On by
        # default — recording is pure host bookkeeping over values the tick
        # path already holds (enforced by dabtlint's DABT104 registry); what
        # it costs was measured on the chip (PERF.md section 6, PR 24: four
        # runs of one seed, two of them under the profiler).  obs=False
        # leaves no recorder object at all (no histograms, no trace ring, no
        # flight ring); the hot path pays one `is None` check.  The loop
        # ledger below and the per-request stamps are the engine's own, as
        # submitted_at/started_at always were: tick_stats() and
        # usage.timings read the same either way.
        self.name = name
        self._ledger = LoopLedger(clock)
        if obs:
            self.obs = EngineObs(name=name, clock=clock, dump_dir=obs_dump_dir)
        else:
            self.obs = None
        self.cfg = cfg
        # the module whose entry points run this config (models.module_for):
        # picked once, here; every device program below goes through it
        self._model = module_for(cfg)
        check = getattr(self._model, "check_serving", None)
        if check is not None:  # a block that refuses what it does not implement
            check(
                speculative=speculative, prefix_cache=prefix_cache_size,
                kv_cache_dtype=kv_cache_dtype, attn_fp8=attn_fp8,
                kv_host_tier=bool(int(kv_host_bytes) > 0 or kv_spill_dir),
            )
        # routed-expert counters (tick_stats()["moe"]): summed on the device
        # inside the programs, handed out with each tick's tokens
        self._moe_totals: Optional[np.ndarray] = None
        self._tick_aux = None
        self.params = params
        self.tokenizer = tokenizer
        self.max_slots = max_slots
        # the most rows one admission wave takes (0 = all slots), and so the
        # most one prefill program holds (prefill_shapes)
        self.prefill_wave = min(max_slots, int(prefill_wave) or max_slots)
        self.max_seq_len = int(min(max_seq_len or cfg.max_seq_len, cfg.max_seq_len))
        self.top_k = top_k
        self.idle_poll_s = idle_poll_s
        # Prompts longer than one chunk prefill incrementally: one chunk per engine
        # loop iteration, a decode tick for the live slots in between.  Decode
        # head-of-line blocking is bounded by a chunk, not by the longest prompt.
        self.chunk_size = int(min(chunk_size, self.max_seq_len))
        # the (rows, bucket) shapes of the prefill programs: derived from the
        # chunk, the wave and the flash kernel's admission block unless the deployment
        # names its buckets; warm-up compiles them all and admission
        # dispatches no other (tick_stats()["prefill_shapes"] counts each)
        self.prefill_shapes = prefill_shapes(self.chunk_size, self.prefill_wave, prefill_buckets)
        self._ledger.list_shapes(
            f"{rows}x{b}" for b, rs in self.prefill_shapes.items() for rows in rs
        )
        # Decode lookahead pipeline: ticks are issued with the *device* token array
        # chained tick-to-tick (no host value needed), results stream back via
        # copy_to_host_async, and the host processes them `lookahead` ticks behind.
        # This removes a blocking host<->device sync per token.  Cost: up to
        # `lookahead` speculative ticks per finished request (their tokens are
        # dropped via slot epochs).
        self.lookahead = max(0, int(lookahead))
        # Fused multi-token decode tick: one jit call advances every live slot
        # `decode_steps` tokens via a lax.scan over chained decode steps
        # (gather -> attention -> MLP -> sample, donated cache chain), so host
        # bookkeeping, sampling-array uploads, and per-dispatch overhead (the
        # decode bottleneck once ticks are pipelined — each dispatch is a host
        # round trip) amortise over N tokens.  `decode_steps` is the canonical
        # knob (docs/QUANT.md roofline notes); `burst` is its historical alias
        # and keeps working.
        # Costs: finished slots decode garbage for the rest of their tick
        # (dropped via slot epochs), admission waits for the tick in flight
        # (bounded by N * per-step time, same order as a prefill chunk), and
        # deadline/cancel reaping happens at tick granularity — a reaped slot
        # can burn up to N-1 extra garbage steps before it freezes.
        # JSON-constrained (json_fsm) slots disable fusion: while any json
        # slot is live the engine issues SINGLE-step ticks (the json tick
        # program is built with steps=1), so FSM semantics never depend on a
        # multi-step scan — `decode_steps_effective` in tick_stats shows
        # which path is active.
        if decode_steps is not None and int(decode_steps) < 1:
            raise ValueError(f"decode_steps must be >= 1 (got {decode_steps})")
        # Spec x fused composition (docs/SPECULATIVE.md): a tree-verify step
        # IS a multi-token tick, so `decode_steps` now scans N verify steps
        # into one speculative dispatch instead of being rejected.  A
        # speculative engine still defaults to ONE verify step per tick
        # unless decode_steps is set explicitly — the historical `burst`
        # default (8) describes plain-decode dispatch amortization and would
        # silently 8x the per-tick token budget of every existing spec
        # deployment.
        if decode_steps is not None:
            self.burst = max(1, int(decode_steps))
        else:
            self.burst = 1 if speculative else max(1, int(burst))
        # Tree-verified prompt-lookup speculative decoding
        # (ops/speculative.py): per tick, the on-device n-gram drafter emits
        # the top-`spec_width` distinct continuations of depth `speculative`
        # as a static token TREE, one fused forward verifies every node
        # through a precomputed ancestor mask, and acceptance takes the
        # longest root-to-leaf path matching the model's argmax — greedy rows
        # advance up to K+1 tokens per tick at identical output.  The
        # reference's answer-from-context workload is the high-acceptance
        # regime.  An acceptance-EMA controller shrinks the tree (then
        # disables speculation) below the measured verify/decode breakeven,
        # so speculation can never be a sustained slowdown.  Replaces burst
        # (one tick IS multi-token); incompatible with JSON-constrained
        # decoding (FSM state is inherently sequential) — submit() rejects
        # json_format when enabled.
        self.speculative = max(0, int(speculative))
        self.spec_width = max(1, int(spec_width)) if self.speculative else 0
        if self.speculative:
            # each scanned verify step writes K+1 positions and
            # _should_finish reserves N*(K+1)-1 tokens of headroom — a
            # budget near max_seq_len would crash the jitted tick (opaquely)
            # or instantly length-limit every request; fail at load with the
            # same clarity as the other config knobs
            if self.burst * (self.speculative + 1) > self.max_seq_len // 4:
                raise ValueError(
                    f"speculative={self.speculative} x decode_steps="
                    f"{self.burst} too large for max_seq_len="
                    f"{self.max_seq_len}: each tick writes up to "
                    f"decode_steps*(K+1) positions and that many tokens of "
                    f"finish headroom are reserved; keep decode_steps*(K+1) "
                    f"<= max_seq_len // 4 ({self.max_seq_len // 4})"
                )
        # canonical alias for the fused-tick depth + the operator gauges
        # behind tick_stats / /healthz / /metrics
        # (`decode_steps_effective`, `weight_bits`, `upload_overlap_frac`):
        # which decode fast path is ACTUALLY active
        self.decode_steps = self.burst
        self._decode_steps_effective = self.burst
        self._json_downgraded_ticks = 0
        # double-buffered host->device uploads: sampling/block-table arrays
        # re-staged at end-of-iteration while `lookahead` ticks are still in
        # flight, so the next tick's dispatch finds them already committed
        # instead of paying the upload enqueue on the issue path
        self._uploads_prestaged = 0
        self._uploads_issue = 0
        # dominant layer-projection weight width (16/8/4) — int4 grouped
        # quantization (ops/quant.py QTensor4) reads 0.5 bytes/weight
        from ..ops.quant import weight_bits as _weight_bits

        try:
            self.weight_bits = _weight_bits(params)
        except Exception:
            self.weight_bits = 16
        self.spec_drafted = 0  # draft tokens proposed (greedy rows only)
        self.spec_accepted = 0  # draft tokens accepted
        self.spec_ticks_issued = 0  # speculative ticks dispatched
        self.spec_skipped_load = 0  # plain ticks forced by queue pressure
        self.spec_skipped_accept = 0  # plain ticks forced by the controller
        self._spec_probe_every = max(1, int(spec_probe_every))
        self._spec_explore_every = max(1, int(spec_explore_every))
        # Prefix KV cache: the pages holding shared prompt prefixes (system +
        # packed RAG context) stay registered in the page pool and are shared,
        # refcounted, instead of being re-prefilled — the reference re-sends
        # and recomputes that context EVERY turn (assistant/bot/services/
        # context_service/steps/final_prompt.py:14).  LRU over at most
        # `prefix_cache_size` prefixes of >= `prefix_min_tokens` tokens; 0
        # disables the path (and its warmup compiles).
        self.prefix_cache_size = max(0, int(prefix_cache_size))
        self.prefix_min_tokens = max(1, int(prefix_min_tokens))
        # Hard HBM budget for shared prefix pages: entries evict (LRU) until
        # the total fits.  Without it, long shared contexts on a deep model
        # pin multi-GB of cache next to the weights (e.g. 8B/32L/8KV/128D bf16
        # at 8192 tokens is ~1 GB per entry).
        self.prefix_cache_max_bytes = int(prefix_cache_max_bytes)
        self.prefix_hits = 0
        self.prefix_misses = 0
        # Reduced-precision page pool: "fp8" halves KV bytes (the dominant
        # HBM consumer after the weights at long context) — K/V convert to
        # fp8 at cache-write and upcast inside the attention dot at read.
        # Lossy (~2 significand bits): opt-in per model.
        if kv_cache_dtype not in KV_CACHE_DTYPES:
            raise ValueError(
                f"unknown kv_cache_dtype {kv_cache_dtype!r}; "
                f"expected one of {sorted(k for k in KV_CACHE_DTYPES if k)}"
            )
        self.kv_cache_dtype = KV_CACHE_DTYPES[kv_cache_dtype]
        # the share of a slot's pages the decode read covers, per tick, tracked
        # host-side and reported as ``kv_read_frac`` in :meth:`tick_stats`
        self._kv_frac_sum = 0.0
        # --- paged KV memory plane (docs/KV_PAGING.md) ------------------------
        # The KV cache is a fixed pool of fixed-size pages plus per-slot block
        # tables — requests reserve only ceil((prompt + max_tokens) / page)
        # pages, common prompt prefixes share pages refcounted (copy-on-write
        # at the boundary page), and admission sheds on KV pressure.  The
        # decode read skips every page past the batch's longest live position.
        self._kv_host = None
        # host-tier restore bookkeeping: counters + a bounded window of
        # restore DISPATCH times (host fetch + upload issue — the async
        # restore's host-visible cost; the device overlap hides the rest)
        self.kv_restores = 0
        self.kv_host_hits = 0
        self._kv_restores_inflight = 0
        self._restore_s: "collections.deque[float]" = collections.deque(maxlen=512)
        # fleet prefix listener (router-owned registry): tier-transition
        # events forward here AFTER the engine's own flight recording
        self._prefix_listener: Optional[Callable[..., None]] = None
        page = int(kv_page_size)
        if not page:
            # the largest page that still divides the context into >= 2 pages
            for c in (512, 256, 128, 64, 32, 16, 8):
                if self.max_seq_len % c == 0 and self.max_seq_len // c >= 2:
                    page = c
                    break
        if page <= 0 or self.max_seq_len % page or self.max_seq_len // page < 2:
            raise ValueError(
                f"max_seq_len={self.max_seq_len} is not divided into >= 2 pages by "
                f"kv_page_size={int(kv_page_size)} (0 tries 512, 256, ... 8): choose a "
                "max_seq_len that is a multiple of 8 and at least 16, or name a "
                "kv_page_size that divides it"
            )
        self.kv_page_size = page
        self._kv_blocks = self.max_seq_len // page
        n_pages = int(kv_pages) or self.max_slots * self._kv_blocks
        if n_pages < self._kv_blocks:
            raise ValueError(
                f"kv_pages={n_pages} cannot hold even one max-length "
                f"request ({self._kv_blocks} pages of {page})"
            )
        import os as _os

        from .kv_pool import HostKVTier, PageAllocator

        page_bytes = page * self._model.kv_bytes_per_token(cfg, self.kv_cache_dtype)
        # --- host KV tier (docs/KV_PAGING.md "Tiered KV") ---------
        # kv_host_bytes > 0 (or a spill dir) arms the durability tier:
        # evicted/registered prefixes keep a host-DRAM copy (then disk),
        # admission restores them into fresh pages ahead of the suffix
        # prefill, and crash-only _restart re-seeds warm sessions from here
        # instead of losing them.
        spill_dir = kv_spill_dir or _os.environ.get("DABT_KV_SPILL_DIR", "").strip() or None
        host_tier = None
        if int(kv_host_bytes) > 0 or spill_dir:
            host_tier = HostKVTier(
                # a spill dir alone gets a small DRAM staging budget
                # (entries flow through host DRAM on their way down)
                int(kv_host_bytes) or 64 * page_bytes,
                page_size=page,
                page_bytes=page_bytes,
                spill_dir=spill_dir,
                name=f"{name}-kv-host",
            )
        self._kv_host = host_tier
        # the r4 prefix-LRU knobs map straight onto the page pool:
        # entry count -> registry entries, byte budget -> shared-page
        # budget, min tokens -> registration threshold
        self._kv_pool = PageAllocator(
            n_pages,
            page,
            page_bytes=page_bytes,
            max_shared_bytes=self.prefix_cache_max_bytes,
            max_shared_entries=self.prefix_cache_size,
            min_prefix_tokens=self.prefix_min_tokens,
            host_tier=host_tier,
            writethrough=bool(kv_host_writethrough),
        )
        self._kv_pool.bind_spill_fetch(self._fetch_pages_host)
        self._kv_pool.on_event = self._on_kv_tier_event
        if host_tier is not None:
            host_tier.on_event = self._on_kv_tier_event
        self._kv_sentinel = n_pages  # block-table "unallocated" marker
        # --- continuous batching: piggybacked chunked prefill ----------------
        # One jitted program runs a bounded prefill chunk for the admitting
        # slot AND the fused decode scan for resident slots per dispatch, so
        # a long prompt stops displacing decode ticks (ROADMAP item 2).
        # Token-identical to the sequential chunk-then-tick path: the chunk
        # consumes no rng, writes only its own slot's pages/rows, and runs
        # before the decode scan inside the program — the same order the
        # sequential loop executes them.  prefill_piggyback=False keeps the
        # sequential path (one prefill program per bucket to compile, not one
        # per bucket and tick shape: a.x-k1-ep16 boots with it).
        self.prefill_piggyback = bool(prefill_piggyback)
        # fp8 in-dot attention (docs/QUANT.md): keep the fp8 KV read operand
        # at storage width through the decode attention dots.  Requires an
        # fp8 cache.
        self.attn_fp8 = bool(attn_fp8)
        if self.attn_fp8:
            import jax.numpy as _jnp

            kv_dt = self.kv_cache_dtype
            if kv_dt is None or _jnp.dtype(kv_dt).itemsize != 1:
                raise ValueError(
                    "attn_fp8=True requires an fp8 KV cache "
                    "(kv_cache_dtype='fp8' or 'fp8_e5m2')"
                )
        # Which implementation the decode tick's K/V write and attention read
        # take (docs/KV_PAGING.md "Decode read/write"): "kernel" — the Pallas
        # call that writes one row per slot in place and reads only the pages
        # the block tables name — on a TPU, "xla" everywhere else.  A gauge
        # and a boot log line, so a run that took the plain path on a chip
        # cannot pass for the kernel.
        self.decode_kv_path = self._model.decode_kv_path(
            cfg, self.kv_cache_dtype, self.kv_page_size, fp8_dot=self.attn_fp8
        )
        logger.info(
            "decode K/V path: %s (page=%d, platform=%s)",
            self.decode_kv_path, self.kv_page_size, jax.default_backend(),
        )
        # ... and the held experts of an expert-parallel rank (ops/moe.py):
        # "kernel" reads only the experts a token landed on, where they lie;
        # None for a block without them
        path_fn = getattr(self._model, "moe_experts_path", None)
        with mesh_scope(mesh):
            self.moe_experts_path = path_fn(cfg) if path_fn else None
        if path_fn:
            logger.info("held experts path: %s (platform=%s)", self.moe_experts_path, jax.default_backend())
        # Admission-controlled scheduling (serving/scheduler.py): when present,
        # submit() runs its admission test (bounded queue, estimated wait) and
        # _admit pulls requests in weighted-fair-share order instead of FIFO.
        # None = the unbounded FIFO path (what an engine built without a
        # registry gets, e.g. in tests).
        self.scheduler = scheduler
        if scheduler is not None:
            scheduler.bind_slots(max_slots)
            # KV-pressure admission: the scheduler compares a request's
            # projected page demand against the pool's obtainable pages
            # (free + evictable cached prefixes) minus what the queue has
            # already reserved — shedding with its own 429 reason instead
            # of queueing work the pool cannot place (docs/SCHEDULING.md)
            scheduler.bind_kv(
                self._kv_pool.available, self._kv_pool.n_pages
            )
            if self._kv_host is not None:
                # host/disk-tier gauges ride in the scheduler's stats()
                # block so operators (and the autoscaler) read pool
                # pressure and warm-tier depth side by side
                scheduler.bind_kv_tier(self._kv_host.stats)
            if self.obs is not None:
                # predictive admission (docs/AUTOSCALING.md): once warm, the
                # obs plane's queue-wait histogram floors the estimated-wait
                # model with the measured tail of realized waits, and the 429
                # Retry-After becomes that prediction instead of a heuristic
                scheduler.bind_wait_hist(self.obs.queue_wait_s)
        # --- supervision (docs/RESILIENCE.md) ---------------------------------
        # Deterministic fault injection (serving/faults.py).  None = off: the
        # hot path pays one `is None` check per tick, nothing else.
        self._faults = faults
        # Loop errors are classified request-poison (quarantine one slot) vs
        # engine-fatal (crash-only restart: rebuild device state, salvage
        # work).  Restarts back off exponentially, and max_restarts inside
        # restart_window_s opens a circuit: submit() fast-fails
        # EngineUnavailable until degraded_cooldown_s elapses (half-open).
        self.max_restarts = max(1, int(max_restarts))
        self.restart_window_s = float(restart_window_s)
        self.restart_backoff_s = max(0.0, float(restart_backoff_s))
        self.restart_backoff_max_s = max(
            self.restart_backoff_s, float(restart_backoff_max_s)
        )
        self.degraded_cooldown_s = max(0.0, float(degraded_cooldown_s))
        self.heartbeat_degraded_s = max(0.1, float(heartbeat_degraded_s))
        self.max_request_restarts = max(0, int(max_request_restarts))
        self.engine_restarts = 0
        self.poisoned_requests = 0
        self.circuit_trips = 0
        self.restarted_resubmitted = 0
        self.restarted_failed = 0
        self._restart_times: "collections.deque[float]" = collections.deque(maxlen=64)
        self._consecutive_failures = 0
        self._degraded_until: Optional[float] = None
        # loop heartbeat: stamped at the top of every loop iteration so a
        # wedged engine thread (stuck XLA call) is visible as a growing
        # loop_heartbeat_age_s in /healthz instead of stale-but-green stats
        self._beat = self._clock()
        # live slots reclaimed before finishing (expired deadline / client
        # cancel) — each one freed mid-decode instead of burning ticks
        self.reclaimed_slots = 0
        # the client-cancel subset of the above: a streaming consumer that
        # disconnected mid-generation (its iterator cancelled the future) —
        # the disconnect-reaping evidence /healthz and tick_stats expose
        self.cancelled_slots = 0
        # perceived-latency samples, host-side: TTFT (submit -> first token on
        # host) and inter-token gaps as _process_tick consumes device results.
        # Bounded windows; read via latency_stats()/tick_stats()/healthz.
        self._ttft_s: "collections.deque[float]" = collections.deque(maxlen=1024)
        self._itl_s: "collections.deque[float]" = collections.deque(maxlen=4096)
        # streams owed a wakeup, flushed at the end of each _process_tick:
        # one cross-thread notify per stream per tick, delivered just before
        # the engine thread returns to device work (engine-thread-only state)
        self._stream_notify: set = set()
        self.mesh = mesh
        # Mesh-scoped serving (TP): the page pool shards over the mesh (kv_heads
        # -> `model`) and every device step is jit'd with explicit cache
        # out_shardings so donation updates shards in place.
        self._cache_shardings = (
            self._model.paged_cache_shardings(cfg, mesh, max_slots)
            if mesh is not None
            else None
        )
        # --- mesh-sliced fleet identity (parallel/slicing.py;
        # docs/MULTICHIP.md) ------------------------------------------------
        # slice_id/release_slice are set by the registry when this replica is
        # pinned to its own device slice; slice_devices is derived from
        # whatever mesh THIS engine actually traces onto, so the gauge can
        # never disagree with placement.  The per-slice HBM ledger below is
        # the operator evidence that a replica's footprint lives only on its
        # slice: device-RESIDENT bytes (one entry per addressable shard, so
        # replication across mesh axes is charged, sharding is not
        # double-charged), computed once here — weights never move and the
        # cache/pool allocation is fixed for the engine's lifetime.
        self.slice_id: Optional[int] = None
        self.release_slice: Optional[Callable[[], None]] = None
        if mesh is not None:
            self.slice_devices = [d.id for d in np.asarray(mesh.devices).flatten()]
        else:
            self.slice_devices = []
        self.hbm_weight_bytes = _resident_bytes(params)

        self._queue: "queue.Queue[_Request]" = queue.Queue()
        self._pending: "collections.deque[_Request]" = collections.deque()
        self._chunking: Optional[_ChunkedPrefill] = None
        # requests currently mid-start (popped from _pending, not yet slotted):
        # must be failed explicitly if their prefill/activation raises
        self._starting_batch: Optional[List[tuple]] = None
        self._slots: List[Optional[_Slot]] = [None] * max_slots
        self._slot_epoch = [0] * max_slots
        self._inflight: "collections.deque[_TickRef]" = collections.deque()
        self._cache = self._fresh_cache()
        # KV side of the per-slice HBM ledger: the page pool's allocation
        # (fixed for the engine's lifetime — restarts rebuild the same shape
        # on the same devices)
        self.hbm_kv_bytes = _resident_bytes(self._cache)
        # per-slot block tables (host-owned): logical block -> physical page,
        # with n_pages as the "unallocated" sentinel.  Uploaded lazily like
        # the sampling arrays (committed replicated array, re-sent only when
        # admissions/frees change it) — NOT part of the donated cache chain,
        # so host edits never race a device step.
        self._slot_pages: List[List[int]] = [[] for _ in range(max_slots)]
        self._block_tables = np.full(
            (max_slots, self._kv_blocks), self._kv_sentinel, np.int32
        )
        self._bt_dev = jax.device_put(
            jnp.asarray(self._block_tables),
            _replicated(mesh) if mesh is not None else None,
        )
        self._bt_dirty = False
        self._tokens_dev = self._fresh_tokens()
        self._temps = np.zeros((max_slots,), np.float32)
        self._top_ps = np.ones((max_slots,), np.float32)
        self._sampling_dirty = True
        self._temps_dev = None
        self._top_ps_dev = None
        self._active_dev = None
        # grammar-constrained JSON decoding: tables built lazily on first use
        self._json = np.zeros((max_slots,), bool)
        self._json_dev = None
        self._fsm = None  # ops.json_fsm.TokenFSM
        self._fsm_next_dev = None
        self._fsm_allowed_dev = None
        self._fsm_states_dev = self._fresh_tokens()
        self._decode_tick_json = None
        self._reseeds = 0  # distinct recovery seeds even for back-to-back failures
        self._rng = self._fresh_rng(0)
        self._running = False
        self._thread: Optional[threading.Thread] = None
        self.steps = 0
        # Serializes one engine-loop iteration against probe_decode: the probe
        # mutates engine-thread-owned device state (_cache/_tokens_dev/_rng),
        # so it must never interleave with an admission/tick.  Uncontended in
        # normal serving (the loop is the only taker).
        # CALLBACK CONTRACT (dabtlint DABT102 baseline + witness allowlist):
        # futures resolve INSIDE the iteration, so a Future done-callback runs
        # with this lock held — callbacks must therefore never acquire any
        # engine's _iter_lock (router re-dispatch takes router/scheduler
        # locks and the TARGET engine's submit queue only; idle() is the one
        # _iter_lock taker outside the loop and resolves nothing).  See
        # docs/STATIC_ANALYSIS.md.
        self._iter_lock = threading.Lock()
        # Per-tick wall breakdown (engine thread only): where a decode token's
        # time actually goes — the ledger's `tick_issue` phase is dispatch
        # enqueue, `tick_block` is waiting on a tick's sampled ids in
        # _process_tick, the other phases are host bookkeeping.  Read via
        # :meth:`tick_stats`; the roofline work (VERDICT r3 weak #2) tunes
        # burst/slots from these instead of guessing.
        self._ticks_issued = 0
        self._ticks_processed = 0

        cfg_c = cfg
        self._decode_tick = self._make_decode_tick(json_mode=False)
        # continuous-batching program: prefill chunk + decode scan fused into
        # one dispatch.  Speculative engines keep sequential chunking (the
        # spec tick owns the token/history chain the piggyback scan would
        # fork).
        self._piggyback_tick = (
            self._make_piggyback_tick()
            if self.prefill_piggyback and not self.speculative
            else None
        )
        self._prefill_chunks_piggybacked = 0
        self._activate_fn = self._make_activate(json_mode=False)
        self._activate_fn_json = None  # built in _ensure_fsm
        self._spec_ticks: Dict[tuple, Any] = {}
        self._spec_ctl = None
        self._history_dev = self._fresh_history() if self.speculative else None
        if self.speculative:
            from ..ops.speculative import SpecController, default_rungs

            # one compiled program per rung of the controller's shrink
            # ladder; the controller switches between them per tick (the
            # tree SHAPE is static inside each program)
            self._spec_ctl = SpecController(
                rungs=default_rungs(self.spec_width, self.speculative),
                probe_every=self._spec_probe_every,
                explore_every=self._spec_explore_every,
            )
            for rung in self._spec_ctl.rungs:
                self._spec_ticks[rung] = self._make_spec_tick(*rung)
            if scheduler is not None:
                # load-disable vs acceptance-disable, side by side in the
                # scheduler's own stats: operators watching the degradation
                # band can tell which mechanism turned speculation off
                scheduler.bind_spec(self._spec_disabled_gauge)
            rep = _replicated(self.mesh) if self.mesh is not None else None
            self._hist_set = jax.jit(
                lambda h, row, slot: jax.lax.dynamic_update_slice(
                    h, row[None], (slot, 0)
                ),
                donate_argnums=(0,),
                out_shardings=rep,
            )

        if mesh is not None:
            insert_out = self._cache_shardings
            chunk_out = (_replicated(mesh), self._cache_shardings)
        else:
            insert_out = chunk_out = None

        def _prefill(params, ids, lengths):
            return self._model.prefill(params, cfg_c, ids, lengths)

        self._prefill = jax.jit(_prefill)
        # donate the cache here too: slot insertion is a scatter into HBM, not a copy
        self._insert = jax.jit(
            self._model.insert_sequences_paged,
            donate_argnums=(0,),
            out_shardings=insert_out,
        )

        def _prefill_chunk_paged(params, ids, cache, bt_row, slot, start, valid):
            return self._model.prefill_chunk_paged(
                params, cfg_c, ids, cache, bt_row, slot, start, valid
            )

        self._prefill_chunk = jax.jit(
            _prefill_chunk_paged, donate_argnums=(2,), out_shardings=chunk_out
        )

        def _prefill_suffix_paged(params, ids, cache, bt, slots, starts, valids):
            return self._model.prefill_suffix_paged(
                params, cfg_c, ids, cache, bt, slots, starts, valids
            )

        self._prefill_suffix = jax.jit(
            _prefill_suffix_paged, donate_argnums=(2,), out_shardings=chunk_out
        )
        # the allocator's COW primitive: clone the boundary page a prefix
        # sharer will write its own suffix into
        self._copy_pages = jax.jit(
            self._model.copy_pages, donate_argnums=(0,), out_shardings=insert_out
        )
        # host-tier spill/restore primitives (docs/KV_PAGING.md "Tiered
        # KV").  The gather does NOT donate the cache — it is a read-only
        # device->host copy off the hot path (the spill side); the write
        # donates like every other cache mutation (the restore side: the
        # upload is dispatched ahead of the slot's suffix prefill and the
        # device stream orders them, so admission never blocks on it).
        def _gather_pages(cache, idx):
            return (
                jnp.take(cache.k, idx, axis=1),
                jnp.take(cache.v, idx, axis=1),
            )

        gather_out = (
            (_replicated(mesh), _replicated(mesh)) if mesh is not None else None
        )
        self._gather_pages = jax.jit(_gather_pages, out_shardings=gather_out)

        def _write_pages(cache, idx, k, v):
            return self._model.PagedKVCache(
                k=cache.k.at[:, idx].set(k.astype(cache.k.dtype)),
                v=cache.v.at[:, idx].set(v.astype(cache.v.dtype)),
                lengths=cache.lengths,
            )

        self._write_pages = jax.jit(
            _write_pages, donate_argnums=(0,), out_shardings=insert_out
        )

    def _make_activate(self, json_mode: bool):
        """Build the jitted activation: mask (JSON), sample the first token per
        row, scatter into the decode token array (pad/non-JSON rows drop via
        out-of-bounds indices), and advance FSM states.  One fused program per
        batch bucket — eagerly composing these ops would pay a dispatch (and
        a first-use compile) PER OP."""
        from ..ops.attention import NEG_INF

        top_k_c = self.top_k
        oob = self.max_slots  # out-of-bounds scatter index -> mode="drop"

        def act(logits, tokens_dev, rng, temps, top_ps, scatter_idx,
                fsm_states=None, jmask=None, init_row=None, next_tab=None,
                initial=None):
            rng, sub = jax.random.split(rng)
            if json_mode:
                logits = jnp.where(
                    jmask[:, None] & ~init_row[None, :], NEG_INF, logits
                )
            first = sample_logits(
                logits, sub, temperature=temps, top_k=top_k_c, top_p=top_ps
            )
            tokens_dev = tokens_dev.at[scatter_idx].set(first, mode="drop")
            if json_mode:
                safe = jnp.minimum(first, next_tab.shape[1] - 1)
                new_states = next_tab[initial, safe]
                fsm_idx = jnp.where(jmask, scatter_idx, oob)
                fsm_states = fsm_states.at[fsm_idx].set(new_states, mode="drop")
                return first, tokens_dev, rng, fsm_states
            return first, tokens_dev, rng

        if self.mesh is not None:
            rep = _replicated(self.mesh)
            out = (rep, rep, rep) + ((rep,) if json_mode else ())
        else:
            out = None
        return jax.jit(act, out_shardings=out, static_argnames=("initial",))

    def _n_tick_aux(self) -> int:
        """How many counter outputs a tick program of this engine's cache kind
        appends (:func:`_take_stats`), from a toy cache's shapes alone."""
        return len(jax.eval_shape(lambda: _take_stats(self._model.init_paged_cache(self.cfg, 1, 2, 8))[1]))

    def _with_aux(self, jitted, n_aux: int):
        """A tick program whose cache carries counters returns them as one more
        output; the callers' tuples stay as they are and the counters wait in
        ``_tick_aux`` for the ``_TickRef`` (:meth:`_take_aux`)."""
        if not n_aux:
            return jitted

        def call(*args):
            *out, self._tick_aux = jitted(*args)
            return tuple(out)

        call.lower = jitted.lower
        return call

    def _take_aux(self):
        aux, self._tick_aux = self._tick_aux, None
        if aux is not None:
            aux.copy_to_host_async()
        return aux

    def _make_decode_tick(self, json_mode: bool, steps: Optional[int] = None):
        """Build the jitted fused tick: ``steps`` chained decode steps in one
        dispatch -> (toks [K,B], last tokens [B], cache[, fsm states]).

        ``steps`` defaults to the engine's ``decode_steps``; the JSON variant
        is built with ``steps=1`` — fused ticks are disabled while json_fsm
        slots are live (the FSM advance stays on-device either way, but
        keeping constrained decoding on the single-step program means its
        semantics never ride a multi-step scan and a mixed batch degrades
        predictably — ``decode_steps_effective`` reports the downgrade).
        ``json_mode`` adds the grammar mask before sampling and the FSM
        advance after it (trace-time branches, so the plain path pays nothing
        for them).  The cache (argnum 2) is donated — in-place HBM update,
        no copy."""
        from ..ops.attention import NEG_INF

        cfg_c, top_k_c = self.cfg, self.top_k
        burst_c = int(steps) if steps is not None else self.burst
        fp8_c = self.attn_fp8

        def tick(params, tokens, cache, active, bt, temps, top_ps, rng,
                 fsm_s=None, jmask=None, next_tab=None, allowed_tab=None):
            def body(carry, _):
                tokens, cache, rng, fsm_s = carry
                # The params are invariant across the burst scan, so XLA's
                # loop-invariant code motion will HOIST their dequantization
                # out of the loop — materializing a full bf16 copy of every
                # int8 weight (2x HBM: an 8B int8 model OOMs a 16 GB chip at
                # compile, and a 1B model silently reads bf16-sized traffic,
                # erasing the int8 bandwidth win).  The barrier pins the
                # weights inside the body: dequant stays per-layer-slice.
                # At burst=1 there is no loop to hoist out of and the barrier
                # is pure cost (it can force program-local weight copies) —
                # skip it.
                p = jax.lax.optimization_barrier(params) if burst_c > 1 else params
                rng, sub = jax.random.split(rng)
                logits, cache = self._model.decode_step_paged(
                    p, cfg_c, tokens, cache, bt, active=active,
                    attn_fp8=fp8_c,
                )
                if json_mode:
                    ok = allowed_tab[fsm_s]  # [B, V]
                    logits = jnp.where(jmask[:, None] & ~ok, NEG_INF, logits)
                nxt = sample_logits(
                    logits, sub, temperature=temps, top_k=top_k_c, top_p=top_ps
                )
                if json_mode:
                    safe = jnp.minimum(nxt, next_tab.shape[1] - 1)
                    fsm_s = jnp.where(jmask, next_tab[fsm_s, safe], fsm_s)
                return (nxt, cache, rng, fsm_s), nxt

            carry = (tokens, cache, rng, fsm_s if json_mode else jnp.zeros_like(tokens))
            if burst_c == 1:
                # No scan wrapper: at flagship (8B) geometry the scanned tick's
                # compiled scratch is what tips a 16 GB chip into OOM — the
                # unrolled single step compiles with the same footprint as the
                # plain decode_step_paged program.
                carry, tok = body(carry, None)
                tokens, cache, rng, fsm_s = carry
                toks = tok[None]
            else:
                (tokens, cache, rng, fsm_s), toks = jax.lax.scan(
                    body, carry, None, length=burst_c
                )
            # the advanced rng is an output: the host threads it call-to-call as
            # opaque device state — an eager jax.random.split per burst would be
            # one more dispatch round trip on the critical host path
            cache, aux = _take_stats(cache)
            if json_mode:
                return (toks, tokens, cache, rng, fsm_s) + aux
            return (toks, tokens, cache, rng) + aux

        n_aux = self._n_tick_aux()
        if self.mesh is not None:
            rep = _replicated(self.mesh)
            out = (rep, rep, self._cache_shardings, rep) + ((rep,) if json_mode else ()) + (rep,) * n_aux
        else:
            out = None
        return self._with_aux(jax.jit(tick, donate_argnums=(2,), out_shardings=out), n_aux)

    def _make_piggyback_tick(self):
        """Continuous-batching tick: ONE jitted program runs a bounded prefill
        chunk for the admitting slot AND the fused ``decode_steps`` scan for
        the resident slots (ROADMAP item 2 "chunked prefill piggybacked into
        the fused decode tick").

        Token-identity with the sequential chunk-then-tick path holds by
        construction: the chunk runs FIRST inside the program (the order the
        sequential loop executes them), consumes no rng, and touches only the
        admitting slot's pages/row — which the decode reads never visit (the
        admitting slot is not yet active, and shared prefix pages are never
        in the chunk's write window: the chunk starts past the shared prefix,
        boundary page COW-cloned at admission).  The decode scan body is the
        same computation as :meth:`_make_decode_tick`'s over the same
        operands, so sampled ids match bit-for-bit (pinned by
        tests/test_contbatch.py).  JSON-constrained and speculative ticks
        never piggyback (host-side gate in the loop)."""
        from ..ops.attention import NEG_INF  # noqa: F401 (parity with decode tick)

        cfg_c, top_k_c = self.cfg, self.top_k
        burst_c = self.burst
        fp8_c = self.attn_fp8

        def tick(params, tokens, cache, active, bt, temps, top_ps, rng,
                 c_ids, c_slot, c_start, c_valid):
            # --- the piggybacked prefill chunk (admitting slot only) -------
            bt_row = jax.lax.dynamic_index_in_dim(bt, c_slot, 0, keepdims=False)
            _, cache = self._model.prefill_chunk_paged(
                params, cfg_c, c_ids, cache, bt_row, c_slot, c_start, c_valid
            )

            # --- the fused decode scan (resident slots) --------------------
            def body(carry, _):
                tokens, cache, rng = carry
                p = jax.lax.optimization_barrier(params) if burst_c > 1 else params
                rng, sub = jax.random.split(rng)
                logits, cache = self._model.decode_step_paged(
                    p, cfg_c, tokens, cache, bt, active=active,
                    attn_fp8=fp8_c,
                )
                nxt = sample_logits(
                    logits, sub, temperature=temps, top_k=top_k_c, top_p=top_ps
                )
                return (nxt, cache, rng), nxt

            carry = (tokens, cache, rng)
            if burst_c == 1:
                carry, tok = body(carry, None)
                tokens, cache, rng = carry
                toks = tok[None]
            else:
                (tokens, cache, rng), toks = jax.lax.scan(
                    body, carry, None, length=burst_c
                )
            cache, aux = _take_stats(cache)
            return (toks, tokens, cache, rng) + aux

        n_aux = self._n_tick_aux()
        if self.mesh is not None:
            rep = _replicated(self.mesh)
            out = (rep, rep, self._cache_shardings, rep) + (rep,) * n_aux
        else:
            out = None
        return self._with_aux(jax.jit(tick, donate_argnums=(2,), out_shardings=out), n_aux)

    def _ensure_fsm(self):
        """Build the JSON token-FSM tables on first constrained request (one-time:
        char DFA + vectorised closure over the tokenizer) and the json tick jit."""
        if self._fsm is not None:
            return
        from ..ops.json_fsm import fsm_for_tokenizer

        fsm = fsm_for_tokenizer(self.tokenizer)
        V_model = self.cfg.vocab_size
        S, V_tok = fsm.allowed.shape
        # pad to the model vocab: ids beyond the tokenizer are never valid JSON
        allowed = np.zeros((S, V_model), bool)
        allowed[:, : min(V_tok, V_model)] = fsm.allowed[:, :V_model]
        nxt = np.full((S, V_model), fsm.dead, np.int32)
        nxt[:, : min(V_tok, V_model)] = fsm.next_state[:, :V_model]
        self._fsm = fsm
        rep = _replicated(self.mesh) if self.mesh is not None else None
        self._fsm_allowed_dev = jax.device_put(allowed, rep)
        self._fsm_next_dev = jax.device_put(nxt, rep)
        self._fsm_init_row_dev = jax.device_put(allowed[fsm.initial], rep)
        # json ticks are single-step: fused (N-step) decoding is disabled
        # whenever a json_fsm slot is live (see _make_decode_tick)
        self._decode_tick_json = self._make_decode_tick(json_mode=True, steps=1)
        self._activate_fn_json = self._make_activate(json_mode=True)

    def _fresh_rng(self, seed: int) -> jnp.ndarray:
        """Committed-sharding rng key — the rng threads through jit outputs and
        must round-trip with the exact sharding the programs emit (see
        :meth:`_fresh_tokens`)."""
        return jax.device_put(
            jax.random.key(seed),
            _replicated(self.mesh) if self.mesh is not None else None,
        )

    def _fresh_tokens(self) -> jnp.ndarray:
        """Zeroed [max_slots] int32 with the SAME committed sharding the jitted
        steps emit — warmup and serving must present identical input shardings
        or the fused programs silently recompile at serve time."""
        z = jnp.zeros((self.max_slots,), jnp.int32)
        if self.mesh is not None:
            return jax.device_put(z, _replicated(self.mesh))
        return jax.device_put(z)

    def _fresh_history(self):
        """Zeroed [max_slots, max_seq_len] int32 device token history (the
        prompt-lookup draft source), replicated like the token array."""
        z = jnp.zeros((self.max_slots, self.max_seq_len), jnp.int32)
        if self.mesh is not None:
            return jax.device_put(z, _replicated(self.mesh))
        return jax.device_put(z)

    def _make_spec_tick(self, width: int, depth: int, steps: Optional[int] = None):
        """Fused tree-speculative tick for one (width, depth) rung: on-device
        n-gram TREE draft -> one read-only verify forward over every node
        (ancestor-masked) -> longest root-to-leaf acceptance -> accepted-path
        K/V commit (a drop-masked block-table scatter) -> history/length
        update — all chained device state (lookahead-compatible; zero host
        round trips per tick).  See ops/speculative.py for the acceptance
        semantics and models/llama.verify_tree_step_paged for the forward.

        Spec x fused composition (docs/SPECULATIVE.md): a verify step IS a
        multi-token tick, so ``decode_steps`` scans N whole
        draft->verify->accept->commit passes into ONE dispatch — the same
        program family (and the same optimization-barrier discipline) as the
        plain fused tick, with the rung ladder choosing the tree shape per
        dispatch.  Outputs are stacked per step: ``toks [N, K+1, B]`` /
        ``n_new [N, B]`` (N = 1 included, so the host consumer has one
        shape contract)."""
        from ..ops.speculative import (
            accept_tree,
            build_tree_draft,
            flatten_tree,
            make_tree_spec,
        )

        cfg_c, top_k_c, K = self.cfg, self.top_k, int(depth)
        N = int(width)
        S = self.max_seq_len
        steps_c = int(steps) if steps is not None else self.burst
        spec = make_tree_spec(N, K)
        depths_c = jnp.asarray(spec.depths)
        anc_c = jnp.asarray(spec.anc_mask)

        def tick(params, tokens, history, cache, bt, active, temps, top_ps, rng):
            def body(carry, _):
                tokens, history, cache, rng = carry
                # same anti-hoisting barrier as the fused decode scan: keep
                # the weights' dequantization inside the scanned body
                p = jax.lax.optimization_barrier(params) if steps_c > 1 else params
                draft = build_tree_draft(history, cache.lengths, tokens, N, K)
                tree = flatten_tree(tokens, draft)  # [B, 1 + N*K]
                logits, tks, tvs = self._model.verify_tree_step_paged(
                    p, cfg_c, tree, cache, bt, depths_c, anc_c
                )
                out, n_new, bonus, path_idx, rng = accept_tree(
                    logits, tree, spec, rng,
                    temperature=temps, top_k=top_k_c, top_p=top_ps,
                )
                n_new = jnp.where(active, n_new, 0)
                # accepted-prefix-only commit: everything past the accepted
                # run (and every inactive row) drops at the page sentinel — a
                # garbage write could land in a page since handed to another
                # request, so masking is part of the contract
                cache = self._model.commit_tree_path_paged(
                    cache, tks, tvs, path_idx, bt, n_new, active
                )
                # persist this step's input token + accepted tokens into the
                # history at sequence positions lengths..lengths+K+1;
                # positions beyond the accepted run hold garbage that later
                # steps overwrite (exactly the KV-cache discipline), and the
                # draft search never reads past the valid length
                row_tokens = jnp.concatenate([tokens[:, None], out], axis=1)
                # gather+where instead of a vmapped dynamic_update_slice: the
                # per-row scatter that vmap lowers to trips this jaxlib's HLO
                # verifier (broadcast rank RET_CHECK) on CPU; the masked
                # gather writes the identical window and lowers everywhere
                pos = jnp.minimum(cache.lengths, S - (K + 2))  # [B]
                rel = jnp.arange(S)[None, :] - pos[:, None]  # [B,S]
                in_window = (rel >= 0) & (rel < K + 2)
                gathered = jnp.take_along_axis(
                    row_tokens, jnp.clip(rel, 0, K + 1), axis=1
                )
                upd = jnp.where(in_window, gathered, history)
                history = jnp.where(active[:, None], upd, history)
                new_len = jnp.where(
                    active, jnp.minimum(cache.lengths + n_new, S), cache.lengths
                )
                cache = cache._replace(lengths=new_len.astype(cache.lengths.dtype))
                tokens = jnp.where(active, bonus, tokens)
                return (tokens, history, cache, rng), (out.T, n_new)

            carry = (tokens, history, cache, rng)
            if steps_c == 1:
                # no scan wrapper at depth 1 (the OOM discipline of
                # _make_decode_tick): unrolled, then stacked to the [1, ...]
                # shape contract
                carry, (tok, n_new) = body(carry, None)
                tokens, history, cache, rng = carry
                toks, n_news = tok[None], n_new[None]
            else:
                (tokens, history, cache, rng), (toks, n_news) = jax.lax.scan(
                    body, carry, None, length=steps_c
                )
            return toks, n_news, tokens, history, cache, rng

        if self.mesh is not None:
            rep = _replicated(self.mesh)
            out_sh = (rep, rep, rep, rep, self._cache_shardings, rep)
        else:
            out_sh = None
        return jax.jit(tick, donate_argnums=(2, 3), out_shardings=out_sh)

    def _fresh_cache(self):
        dt = self.kv_cache_dtype
        n_pages, page = self._kv_pool.n_pages, self.kv_page_size

        def make():
            return self._model.init_paged_cache(
                self.cfg, self.max_slots, n_pages, page, dtype=dt
            )

        if self._cache_shardings is not None:
            # Allocate *sharded*: an eager init would materialise the whole
            # pool on device 0 first — at slice-sized pools that alone overflows
            # one chip's HBM.
            with self.mesh:
                return jax.jit(make, out_shardings=self._cache_shardings)()
        return make()

    def _mesh_scope(self):
        """Trace/run device steps inside the mesh so sharding constraints bind
        and the attention kernel partitions over it."""
        return mesh_scope(self.mesh)

    # ------------------------------------------------------------------ public
    def start(self) -> "GenerationEngine":
        if self._running:
            return self
        if self._thread is not None and self._thread.is_alive():
            # a deadline-expired stop() left the old loop draining (stuck in an
            # XLA call); a second loop would race it over engine-private state
            raise RuntimeError(
                "previous engine thread is still draining; cannot restart yet"
            )
        self._running = True
        self._beat = self._clock()
        self._thread = threading.Thread(target=self._loop, daemon=True, name="gen-engine")
        self._thread.start()
        return self

    def stop(self, drain_timeout_s: float = 120.0):
        """Stop the engine and fail unfinished requests.

        The engine thread drains its own private state (slots, pending queue)
        when its loop exits — ``stop`` only waits for that, bounded by
        ``drain_timeout_s``.  A first-call XLA compile can hold a device step
        for minutes; past the deadline we dump the engine thread's stack (so
        a hung drain is diagnosable from the log alone) and return — the
        daemon thread finishes the drain itself when the in-flight call
        returns, so no future is ever left dangling."""
        self._running = False
        t = self._thread
        if t is not None:
            start = self._clock()
            deadline = start + drain_timeout_s
            t.join(timeout=min(5.0, drain_timeout_s))
            while t.is_alive() and self._clock() < deadline:
                logger.warning(
                    "engine thread still draining (device step or compile in "
                    "flight); %.0fs elapsed, waiting up to %.0fs",
                    self._clock() - start,
                    drain_timeout_s,
                )
                t.join(timeout=min(15.0, max(0.0, deadline - self._clock())))
            if t.is_alive():
                logger.error(
                    "engine thread did not drain within %.0fs; its requests "
                    "will fail when the in-flight XLA call returns",
                    drain_timeout_s,
                )
                try:  # diagnose the stuck XLA call: where is the thread?
                    import faulthandler
                    import sys

                    faulthandler.dump_traceback(file=sys.stderr)
                except Exception:  # pragma: no cover - diagnostics only
                    pass
            else:
                self._thread = None
        # anything submitted after the loop exited (or with no thread at all)
        self._drain_incoming(RuntimeError("generation engine stopped"))

    def _drain_queue(self, err: BaseException):
        """Fail everything not yet started.  Only called from the engine thread
        itself (end-of-loop _shutdown) — ``_pending``/``_chunking`` are
        engine-thread-private state."""
        if self._chunking is not None:
            _safe_resolve(self._chunking.request.future, exc=err)
            self._chunking = None
        while self._pending:
            _safe_resolve(self._pending.popleft().future, exc=err)
        if self.scheduler is not None:
            self.scheduler.drain(err)
        self._drain_incoming(err)

    def _drain_incoming(self, err: BaseException):
        """Drain the thread-safe submission queue only (safe from any thread)."""
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            _safe_resolve(req.future, exc=err)

    def submit(
        self,
        prompt_ids: Sequence[int],
        *,
        max_tokens: int = 1024,
        temperature: float = 0.8,
        top_p: float = 0.95,
        json_format: bool = False,
        prefix_len: int = 0,
        priority: str = "interactive",
        tenant: str = "default",
        deadline_s: Optional[float] = None,
        stream: Any = None,
        trace_id: Optional[str] = None,
        received_at: Optional[float] = None,
    ) -> Future:
        """Thread-safe submission; returns a concurrent Future[GenerationResult].

        ``prefix_len``: the first N prompt tokens are a shared, cacheable
        prefix (identical across requests, e.g. the system + RAG-context block)
        — the engine reuses their K/V across requests when it can.  Purely an
        optimization hint: results are identical with 0.

        ``priority``/``tenant``/``deadline_s``: scheduling metadata (see
        serving/scheduler.py).  With a scheduler attached, submission may
        raise :class:`SchedulerRejected` synchronously (load shed — the
        request was never queued); an expired deadline fails the future with
        :class:`DeadlineExceeded` and frees its decode slot.

        ``stream``: a :class:`~.streaming.TokenStream` to receive per-token
        events as device results resolve (EOS is not emitted) plus a terminal
        event wired through the future's done-callback — every resolution
        path (finish, deadline, failure, cancel) closes the stream.

        ``trace_id``: the request's correlation id (client ``X-Request-Id``
        or a router-assigned id); generated here when absent, stamped on the
        ``_Request``, and carried through the obs plane's trace ring and
        flight recorder (docs/OBSERVABILITY.md).

        ``received_at``: when the request reached the process, on this
        engine's clock (the ``/dialog/`` handler's first line): the start of
        ``usage.timings``' ``encode_s``."""
        trace_id = trace_id or new_trace_id()
        if self.degraded():
            # restart circuit open: fail fast (503 at the server) instead of
            # queueing work behind a device that keeps killing the loop
            remaining = max(0.1, (self._degraded_until or 0.0) - self._clock())
            raise EngineUnavailable(
                "engine degraded after repeated restarts", retry_after_s=remaining
            )
        prompt_ids = list(prompt_ids)
        if json_format and self.speculative:
            raise ValueError(
                "speculative decoding and json_format are mutually exclusive "
                "(the JSON token-FSM advances one sequential state per token); "
                "serve JSON traffic from a non-speculative model entry"
            )
        # keep room for at least one generated token (truncate BEFORE the
        # admission test: the KV demand below is computed from what will
        # actually occupy pages)
        limit = self.max_seq_len - 1
        if len(prompt_ids) > limit:
            prompt_ids = prompt_ids[-limit:]
            prefix_len = 0  # truncation drops leading tokens — prefix gone
        prefix_len = max(0, min(int(prefix_len), len(prompt_ids) - 1))
        # worst-case page reservation: the whole prompt plus every token
        # the request may generate, capped at the context.  Reserving up
        # front means decode can never run out of pages mid-stream — the
        # pool pressure surfaces at ADMISSION (429), not as a mid-decode
        # stall.  Prefix sharing only reduces the pages actually taken.
        demand_tokens = min(len(prompt_ids) + int(max_tokens), self.max_seq_len)
        kv_pages = -(-demand_tokens // self.kv_page_size)
        admitted = False
        if self.scheduler is not None:
            if deadline_s is None:
                deadline_s = self.scheduler.cfg.default_deadline_s
            adm = self.scheduler.try_admit(priority, deadline_s, kv_pages=kv_pages)
            if not adm.ok:
                if self.obs is not None:
                    # a shed 429 used to be uncorrelatable with the client
                    # retry that follows — the flight ring keeps the evidence,
                    # trace_id included, so a post-mortem dump matches the
                    # client-reported request id
                    self.obs.on_shed(adm.reason, priority, trace_id=trace_id)
                raise SchedulerRejected(adm.reason, adm.retry_after_s)
            if adm.clamp_max_tokens is not None:
                max_tokens = min(max_tokens, adm.clamp_max_tokens)
                # the clamp shrinks the worst case; release the difference
                demand_tokens = min(
                    len(prompt_ids) + int(max_tokens), self.max_seq_len
                )
                new_pages = -(-demand_tokens // self.kv_page_size)
                if new_pages < kv_pages:
                    self.scheduler.release_kv(kv_pages - new_pages)
                    kv_pages = new_pages
            admitted = True
        now = self._clock()
        fut: Future = Future()
        if stream is not None:
            # attach BEFORE the queue put: if the engine resolves (or drains)
            # the future immediately, the callback still fires post-hoc
            fut.add_done_callback(stream.finish)
        if self.obs is not None:
            self.obs.on_admit(trace_id, priority, tenant, len(prompt_ids))
        self._queue.put(
            _Request(
                prompt_ids=prompt_ids,
                max_tokens=max_tokens,
                temperature=temperature,
                top_p=top_p,
                future=fut,
                submitted_at=now,
                json=json_format,
                prefix_len=prefix_len,
                priority=priority,
                tenant=tenant,
                deadline_at=(now + deadline_s) if deadline_s is not None else None,
                admitted=admitted,
                stream=stream,
                kv_pages=kv_pages,
                trace_id=trace_id,
                received_at=received_at,
            )
        )
        # A stop() racing (or preceding) the put above would leave the request
        # enqueued forever with no engine thread to fail it.  Re-checking after the
        # put closes the race: either the engine was still draining (it resolves the
        # future) or we drain it here — _safe_resolve makes double-resolution benign.
        # Only the thread-safe queue is touched from this (client) thread.
        if not self._running:
            self._drain_incoming(RuntimeError("generation engine stopped"))
        return fut

    async def generate(
        self,
        prompt: str | Sequence[dict],
        *,
        max_tokens: int = 1024,
        temperature: float = 0.8,
        top_p: float = 0.95,
        json_format: bool = False,
        priority: str = "interactive",
        tenant: str = "default",
        deadline_s: Optional[float] = None,
        trace_id: Optional[str] = None,
        received_at: Optional[float] = None,
    ) -> GenerationResult:
        """Async convenience: tokenize (chat-templating message lists), run, decode."""
        import asyncio

        from .tokenizer import encode_chat_split

        if received_at is None:  # no socket above: encode_s is the tokenizer's
            received_at = self._clock()
        if isinstance(prompt, str):
            ids, plen = self.tokenizer.encode(prompt), 0
        else:
            # everything before the final user message is the shared-prefix
            # candidate for the KV prefix cache
            ids, plen = encode_chat_split(self.tokenizer, prompt)
        fut = self.submit(
            ids,
            max_tokens=max_tokens,
            temperature=temperature,
            top_p=top_p,
            json_format=json_format,
            prefix_len=plen,
            priority=priority,
            tenant=tenant,
            deadline_s=deadline_s,
            trace_id=trace_id,
            received_at=received_at,
        )
        return await asyncio.wrap_future(fut)

    async def generate_stream(
        self,
        prompt: str | Sequence[dict],
        *,
        max_tokens: int = 1024,
        temperature: float = 0.8,
        top_p: float = 0.95,
        json_format: bool = False,
        priority: str = "interactive",
        tenant: str = "default",
        deadline_s: Optional[float] = None,
        trace_id: Optional[str] = None,
        received_at: Optional[float] = None,
    ):
        """Async iterator of :class:`~.streaming.StreamChunk`: per-token
        UTF-8-safe text deltas as device results resolve, then one terminal
        chunk with the finish reason and the full :class:`GenerationResult`.

        The concatenation of every chunk's ``text`` is byte-identical to the
        non-streaming ``generate()`` result for the same request + seed —
        incomplete multi-byte fragments are held back, never replaced.

        Abandoning the iterator (``aclose``/GC on client disconnect) cancels
        the request; the engine's per-iteration reap frees its decode slot
        within one tick via the deadline epoch mechanism, so an abandoned
        generation stops burning device capacity immediately.

        ``json_format`` streams the grammar-constrained tokens as ordinary
        text deltas (each prefix is a prefix of one valid JSON document); the
        HTTP layer rejects ``stream`` + ``json_format`` instead — see
        docs/STREAMING.md."""
        import asyncio

        from .streaming import IncrementalDetokenizer, StreamChunk, TokenStream
        from .tokenizer import encode_chat_split

        if received_at is None:
            received_at = self._clock()
        if isinstance(prompt, str):
            ids, plen = self.tokenizer.encode(prompt), 0
        else:
            ids, plen = encode_chat_split(self.tokenizer, prompt)
        stream = TokenStream().bind(
            asyncio.get_running_loop(), capacity=int(max_tokens) + 2
        )
        fut = self.submit(
            ids,
            max_tokens=max_tokens,
            temperature=temperature,
            top_p=top_p,
            json_format=json_format,
            prefix_len=plen,
            priority=priority,
            tenant=tenant,
            deadline_s=deadline_s,
            stream=stream,
            trace_id=trace_id,
            received_at=received_at,
        )
        detok = IncrementalDetokenizer(self.tokenizer)
        idx = 0
        try:
            async for kind, payload, at in stream.stamped():
                if kind == "token":
                    text = detok.push(payload)
                    yield StreamChunk(index=idx, token_id=payload, text=text, at=at)
                    idx += 1
                    continue
                if isinstance(payload, BaseException):
                    raise payload
                result: GenerationResult = payload
                yield StreamChunk(
                    index=idx,
                    token_id=None,
                    text=detok.flush(),
                    done=True,
                    finish_reason="length" if result.length_limited else "stop",
                    result=result,
                )
                return
        finally:
            # consumer gone (disconnect / break / error): cancel so the
            # per-iteration reap frees the slot within one decode tick
            if not fut.done():
                fut.cancel()

    @property
    def num_active(self) -> int:
        return sum(s is not None for s in self._slots)

    def queued_depth(self) -> int:
        """Requests accepted but not yet slotted (any thread; approximate —
        the router's least-loaded dispatch reads this, and a race of one
        entry only shifts a tie-break).  With a scheduler its depth ledger is
        the single source of truth: admission charges it synchronously in
        ``submit`` (before the request even reaches the staging queue), so
        adding ``_queue.qsize()`` on top would double-count in-transit work."""
        if self.scheduler is not None:
            return self.scheduler.queue_depth
        return self._queue.qsize() + len(self._pending)

    def idle(self) -> bool:
        """No work anywhere: no live slot, no in-flight tick, no chunked
        prefill, nothing queued or mid-admission.  The graceful-drain paths
        (router ``drain()``, the server's SIGTERM drain) poll this until the
        replica has finished what it accepted.

        Takes the loop-iteration lock: between a queue pop and the wave's
        slot activation a request is in NO queue and NO slot (its prefill is
        running), and an unlocked read in that window would report an idle
        engine holding live work — the drain would then stop the engine and
        kill the request it promised to finish."""
        with self._iter_lock:
            return (
                self.num_active == 0
                and not self._inflight
                and self._chunking is None
                and self._starting_batch is None
                and self.queued_depth() == 0
                and self._queue.qsize() == 0
            )

    def holds_prefix(self, prompt_ids: Sequence[int], prefix_len: int) -> bool:
        """Does this engine's KV plane already hold a usable cached prefix of
        this prompt?  Read-only, LRU-neutral, safe from any thread — the
        router's affinity dispatch asks every replica this.  False whenever
        prefix caching is off."""
        if self.prefix_cache_size <= 0 or prefix_len < self.prefix_min_tokens:
            return False
        if self._kv_pool.holds_prefix(prompt_ids, prefix_len):
            return True
        # a host/disk-tier copy is still a reason to route here: the
        # restore costs an upload, not a prefill
        return self._kv_host is not None and self._kv_host.holds(
            prompt_ids, prefix_len
        )

    # ------------------------------------------------------- host KV tier
    @property
    def kv_host_tier(self):
        """The engine's host-DRAM KV tier (None when tiering is off) — the
        router's scale-down migration exports/imports through this."""
        return self._kv_host

    def _drop_restore_inflight(self, req: _Request) -> None:
        if req.restored_from_host:
            req.restored_from_host = False
            self._kv_restores_inflight = max(0, self._kv_restores_inflight - 1)

    def _fetch_pages_host(self, pages: Sequence[int]):
        """Device->host copy of whole pages (``[L, n, KH, page, D]`` x2) —
        the spill side of the tier.  Engine-thread-only (the cache is
        engine-thread-owned); called from the allocator's eviction/
        write-through paths, which run under admission, never under the
        decode hot path (dabtlint DABT104 stays at 0 findings)."""
        if not pages:
            return None
        self._ledger.note_dispatch("spill")
        with self._mesh_scope():
            k, v = self._gather_pages(
                self._cache, jnp.asarray(list(pages), jnp.int32)
            )
        return np.asarray(jax.device_get(k)), np.asarray(jax.device_get(v))

    def _on_kv_tier_event(
        self, event: str, key: tuple, length: int, pages: int
    ) -> None:
        """Every tier transition is a flight-recorder event, then forwards to
        the fleet prefix registry's listener (router-owned).  Fired outside
        the allocator/tier locks; thread-safe (engine thread for
        spill/restore/register, router thread when a migration target
        absorbs entries)."""
        if self.obs is not None:
            self.obs.flight.record(
                "kv_tier",
                op=event,
                prefix_tokens=int(length),
                pages=int(pages),
            )
        fn = self._prefix_listener
        if fn is not None:
            try:
                fn(event, key, length, pages)
            except Exception:
                logger.exception("fleet prefix listener failed (%s)", event)

    def set_prefix_listener(self, fn: Optional[Callable[..., None]]) -> None:
        """Subscribe the router's fleet prefix registry to this engine's
        tier-transition events (register/spill/restore/evict)."""
        self._prefix_listener = fn

    def spill_registered_to_host(self) -> int:
        """Force a host copy of every device-registry entry that lacks one —
        the scale-down migration's export step (a cheap ``has()`` sweep when
        write-through already mirrored everything, which is the default).
        Takes ``_iter_lock`` so the page gather cannot interleave with a loop
        iteration (the probe_decode discipline); resolves no futures under
        it.  Returns how many entries were newly spilled."""
        if self._kv_host is None:
            return 0
        n = 0
        with self._iter_lock:
            for key, ent in self._kv_pool.shared_entries():
                if self._kv_host.has(key):
                    continue
                try:
                    fetched = self._fetch_pages_host(ent.pages)
                except Exception:
                    # a dead/poisoned device mid-migration: the entry is
                    # lost (counted by the router), migration continues —
                    # charged to the same gauge as the evict/write-through
                    # spill paths so telemetry counts every failed spill
                    self._kv_pool.spill_failures += 1
                    logger.exception("migration spill fetch failed")
                    continue
                if fetched is not None and self._kv_host.put(
                    key, ent.length, *fetched
                ):
                    n += 1
        return n

    def absorb_remote_entry(self, key: tuple, length: int, k, v) -> bool:
        """Import ONE wire-shipped prefix entry (``/fleet/kv/put`` —
        serving/fleet.py) into this engine's HOST tier, never directly into
        HBM: the entry enters through the same host-tier ``put`` every spill
        uses (same ``host_put`` event for the gossip log / prefix registry /
        flight ring) and reaches device pages only through the existing
        restore-at-admission path — so restore bit-identity across a process
        boundary is the SAME tested property as the local spill/restore
        round-trip.  Geometry and dtype are validated against THIS pool
        first: a mismatched peer's bytes would reinterpret, not restore.
        Thread-safe (host-tier lock); returns whether the entry stored."""
        tier = self._kv_host
        if tier is None:
            return False
        key = tuple(int(t) for t in key)
        k = np.asarray(k)
        v = np.asarray(v)
        if int(length) != len(key):
            logger.warning(
                "refusing remote KV entry: length %d != key tokens %d",
                int(length), len(key),
            )
            return False
        if k.ndim != 5 or v.ndim != 5 or k.shape[3] != self.kv_page_size:
            logger.warning(
                "refusing remote KV entry: page geometry %s does not match "
                "this pool (page=%d)", tuple(k.shape), self.kv_page_size,
            )
            return False
        expected = jnp.dtype(self.kv_cache_dtype or self.cfg.dtype)
        if k.dtype != expected or v.dtype != expected:
            logger.warning(
                "refusing remote KV entry: dtype %s does not match this "
                "pool's %s", k.dtype, expected,
            )
            return False
        return tier.put(key, int(length), k, v)

    # ---------------------------------------------------------------- internal
    def _free_slots(self) -> List[int]:
        busy = {self._chunking.slot} if self._chunking is not None else set()
        return [i for i, s in enumerate(self._slots) if s is None and i not in busy]

    def _loop_iteration(self) -> bool:
        """ONE engine-loop iteration under ``_iter_lock``: reap, admit, run a
        prefill chunk (piggybacked into the decode tick when possible) and/or
        a decode tick, then drain results ``lookahead`` ticks behind.
        Returns whether any admission/chunk progress was made (the loop's
        idle predicate).  Factored out of :meth:`_loop` so deterministic
        tests can crank iterations single-threaded (tests/test_contbatch.py's
        lockstep bit-identity rig)."""
        span = self._ledger.span
        with self._iter_lock:  # excludes probe_decode (see there)
            with span("reap"):
                self._reap_dead_slots()
            with span("admit"):  # its prefill dispatches open spans of their own
                admitted = self._admit()
            ticked = False
            if self._chunking is not None:
                if (
                    self._piggyback_tick is not None
                    and self.num_active > 0
                    and not self._json.any()
                    and self._chunking.step < len(self._chunking.starts) - 1
                ):
                    # continuous batching: fold this chunk into the decode
                    # tick — resident slots advance decode_steps tokens in
                    # the SAME dispatch instead of waiting a chunk out.  The
                    # final chunk always runs sequentially: its logits feed
                    # the activation (first-token sample), which is its own
                    # program.
                    with span("tick_issue", piggyback=1, seq=self._ledger.seq + 1):
                        self._piggyback_step()
                    ticked = True
                else:
                    # decode waits this chunk's whole program out: the
                    # displacement the piggybacked path exists to remove (the
                    # ledger's `chunk+tick` / `chunk` segments against its
                    # `piggyback` ones)
                    with span(
                        "prefill_dispatch",
                        bucket=self.chunk_size,
                        rows=1,
                        rows_padded=1,
                        chunk=self._chunking.step,
                        seq=self._ledger.seq + 1,
                    ):
                        self._chunk_step()
                admitted = True
            if self.num_active > 0 and not ticked:
                with span("tick_issue", seq=self._ledger.seq + 1):
                    self._issue_tick()
            # process results `lookahead` ticks behind; drain fully
            # when no slot is live (remaining in-flight ticks carry
            # final tokens)
            while self._inflight and (
                len(self._inflight) > self.lookahead
                or self.num_active == 0
            ):
                self._process_tick()
            # double-buffer next tick's sampling/block-table
            # uploads against the ticks still in flight (the
            # finishes above are what dirtied the arrays)
            with span("prestage"):
                self._prestage_uploads()
        return admitted

    def _loop(self):
        try:
            while self._running:
                self._beat = self._clock()
                if self._degraded_until is not None and not self._degraded_wait():
                    continue
                try:
                    admitted = self._loop_iteration()
                    # a clean iteration closes any failure streak (the restart
                    # backoff escalates over CONSECUTIVE failures only)
                    self._consecutive_failures = 0
                    if not admitted and self.num_active == 0 and not self._inflight:
                        with self._ledger.span("idle_wait"):
                            self._sleep(self.idle_poll_s)
                except Exception as e:
                    logger.exception(
                        "engine-fatal loop error; attempting crash-only restart"
                    )
                    with self._ledger.span("recover"):
                        with self._iter_lock:
                            self._restart(e)
                    # bounded exponential backoff between restarts: a
                    # persistent device fault must not spin the loop hot
                    with self._ledger.span("idle_wait"):
                        self._backoff_after_failure()
        finally:
            self._shutdown()

    def _degraded_wait(self) -> bool:
        """One degraded-mode loop beat.  Returns True when the cooldown has
        elapsed (half-open: restart history clears and the loop resumes —
        the next fault inside the window re-trips immediately)."""
        now = self._clock()
        if self._degraded_until is not None and now >= self._degraded_until:
            logger.warning(
                "engine circuit half-open: resuming after %.1fs degraded cooldown",
                self.degraded_cooldown_s,
            )
            # restart HISTORY is kept: a still-broken device re-trips on its
            # first post-cooldown crash (while prior restarts remain inside
            # restart_window_s) instead of burning max_restarts fresh crash/
            # rebuild cycles; a healthy resume ages the history out naturally
            self._degraded_until = None
            self._consecutive_failures = 0
            return True
        # new work fast-fails in submit(); anything already queued keeps
        # honoring deadlines/cancels while the engine cools down
        with self._iter_lock, self._ledger.span("reap"):
            self._reap_dead_slots()
        with self._ledger.span("idle_wait"):
            self._sleep(min(0.05, max(0.0, (self._degraded_until or now) - now)))
        return False

    def _backoff_after_failure(self) -> None:
        self._consecutive_failures += 1
        if not self._running or self.degraded():
            return  # the degraded wait (or shutdown) is the backoff
        delay = min(
            self.restart_backoff_max_s,
            self.restart_backoff_s * (2 ** (self._consecutive_failures - 1)),
        )
        if delay > 0:
            self._sleep(delay)

    def _shutdown(self):
        """End-of-loop drain, run BY the engine thread: fail live slots and
        everything queued.  Keeping this on the engine thread means stop() can
        deadline its join without racing engine-private state."""
        # however the loop exited (stop(), loop crash, failed recovery), the
        # flag must drop so submit()'s post-put re-check fails new work fast
        self._running = False
        err = RuntimeError("generation engine stopped")
        self._inflight.clear()
        self._ledger.reset_queue()
        for i, s in enumerate(self._slots):
            if s is not None:
                _safe_resolve(s.request.future, exc=err)
                self._slots[i] = None
                self._slot_epoch[i] += 1
            self._free_slot_pages(i)
        self._drain_queue(err)

    def _reap_dead_slots(self) -> None:
        """Free live slots whose request is dead: deadline expired or future
        cancelled by the client.  Runs at the top of every loop iteration, so
        an expired request's slot is reclaimed within ONE decode tick — the
        epoch bump drops its in-flight speculative tokens and the inactive row
        stops burning decode work (``active=False`` in the next tick; the
        stale cache row is overwritten by the next admission, the same
        discipline ``_finish`` relies on).

        QUEUED dead entries are reaped here too — every iteration, not only
        when a free slot pulls them to the fair-share head — so a queued
        request's DeadlineExceeded lands at ~its deadline even on a saturated
        engine, and dead entries stop inflating queue depth (which would shed
        admittable work with spurious queue_full 429s)."""
        now = self._clock()
        if self.scheduler is not None:
            self.scheduler.reap(now)
        elif self._pending:
            keep: "collections.deque[_Request]" = collections.deque()
            while self._pending:
                req = self._pending.popleft()
                if req.future.cancelled():
                    continue
                if req.deadline_at is not None and now >= req.deadline_at:
                    _safe_resolve(
                        req.future,
                        exc=DeadlineExceeded(
                            f"deadline expired after "
                            f"{now - req.submitted_at:.2f}s in queue"
                        ),
                    )
                    continue
                keep.append(req)
            self._pending = keep
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            req = s.request
            expired = req.deadline_at is not None and now >= req.deadline_at
            if not expired and not req.future.cancelled():
                continue
            self._slots[i] = None
            self._slot_epoch[i] += 1
            self._json[i] = False
            self._sampling_dirty = True
            self._free_slot_pages(i)
            self.reclaimed_slots += 1
            if not expired:
                # future.cancelled(): a streaming consumer disconnected (or a
                # client dropped its future) — same reap, separate counter
                self.cancelled_slots += 1
            if expired:
                _safe_resolve(
                    req.future,
                    exc=DeadlineExceeded(
                        f"deadline expired after {len(s.generated)} generated "
                        f"tokens ({now - req.submitted_at:.2f}s since submit)"
                    ),
                )
                if self.scheduler is not None:
                    self.scheduler.note_expired_running(req.priority)

    def _prefix_lookup(self, req: _Request):
        """LONGEST cached prefix this prompt starts with, or None.

        Longest-match (not exact-key) is what makes multi-turn dialogs hit:
        turn N's prompt extends turn N-1's [system, ...history] block, so the
        previous turn's registered prefix is a proper prefix of the new prompt
        even though the declared split point moved.  LRU-touches the winner.

        The allocator's registry answers (a :class:`~.kv_pool.SharedPrefix` of
        physical pages), then the host tier (a :class:`_HostHit`).  Both carry
        ``.length``."""
        if self.prefix_cache_size <= 0 or req.prefix_len < self.prefix_min_tokens:
            return None
        hit = self._kv_pool.lookup(req.prompt_ids, req.prefix_len)
        hit = self._paged_usable_hit(req, hit)
        if hit is not None:
            return hit
        if self._kv_host is not None:
            # HBM missed (evicted, or a pre-restart registration): the
            # host tier may still hold the prefix — admission restores
            # it into fresh pages instead of re-prefilling.  An HBM hit
            # always wins over a host hit (no upload, no fresh pages).
            ent = self._kv_host.lookup(
                req.prompt_ids,
                req.prefix_len,
                min_tokens=self.prefix_min_tokens,
            )
            if ent is not None:
                return self._paged_usable_hit(req, _HostHit(ent))
        return None

    def _paged_usable_hit(self, req: _Request, hit):
        """Reject a registry hit whose bucketed suffix prefill would have to
        slide left past the prefix boundary (prefix within one bucket of the
        context end): the slid window would re-WRITE physically shared pages,
        and a duplicate-index scatter with near-identical recomputed values is
        undefined.  The smallest derived bucket is 64, so a hit whose prefix
        ends within 64 tokens of the context's end is prefilled in full.  The
        chunked path never slides into the prefix (remainder > chunk_size
        guarantees the final chunk starts past it)."""
        if hit is None:
            return None
        n_eff = len(req.prompt_ids) - hit.length
        if n_eff > self.chunk_size:
            return hit
        b = pick_bucket(n_eff, self.prefill_shapes, self.chunk_size)
        if hit.length + b > self.max_seq_len:
            return None
        return hit

    def _paged_admit_restore(self, slot: int, req: _Request, hit: _HostHit) -> bool:
        """Host-tier restore admission: allocate the request's full page
        demand, upload the spilled prefix K/V into the leading pages (async
        dispatch — the device stream orders it ahead of the suffix prefill
        that consumes those pages), and re-register the restored prefix so
        later requests share it in HBM again.  False = out of pages (the
        request stays queued, or retries as a full prefill)."""
        page = self.kv_page_size
        ent = hit.entry
        demand_tokens = min(
            len(req.prompt_ids) + req.max_tokens, self.max_seq_len
        )
        total = -(-demand_tokens // page)
        pages = self._kv_pool.alloc(total)
        if pages is None:
            return False
        t0 = self._clock()
        prefix_pages = pages[: ent.pages]
        self._ledger.note_dispatch("restore")
        with self._mesh_scope():
            self._cache = self._write_pages(
                self._cache,
                jnp.asarray(prefix_pages, jnp.int32),
                jnp.asarray(ent.k),
                jnp.asarray(ent.v),
            )
        # re-register: the registry increfs the restored pages, so they
        # outlive this request like any warm prefix.  Write-through skips
        # the redundant device->host copy (the host tier already has it).
        self._kv_pool.register(list(ent.key), ent.length, prefix_pages)
        self.kv_restores += 1
        self._kv_restores_inflight += 1
        # the tier counts the serve HERE (not in lookup — a queued head
        # re-runs the lookup every admission attempt) and LRU-touches
        self._kv_host.note_restored(ent.key)
        req.restored_from_host = True
        # the host-visible restore cost: tier lookup was already paid; this
        # window is host->device upload DISPATCH (the async-restore claim —
        # the device overlaps the copy with whatever is in flight)
        self._restore_s.append(self._clock() - t0)
        self._on_kv_tier_event("restore", ent.key, ent.length, ent.pages)
        self._slot_pages[slot] = pages
        self._block_tables[slot, :] = self._kv_sentinel
        self._block_tables[slot, : len(pages)] = pages
        self._bt_dirty = True
        return True

    def _paged_admit_slot(self, slot: int, req: _Request, hit) -> bool:
        """Reserve and wire pages for ``req`` in ``slot``: shared full prefix
        pages by reference (incref), the boundary page by copy-on-write clone,
        everything else fresh from the pool.  A host-tier hit routes to
        :meth:`_paged_admit_restore` instead.  False = the pool cannot place
        the request right now (it stays queued; pages free as slots finish)."""
        if isinstance(hit, _HostHit):
            return self._paged_admit_restore(slot, req, hit)
        page = self.kv_page_size
        demand_tokens = min(
            len(req.prompt_ids) + req.max_tokens, self.max_seq_len
        )
        total = -(-demand_tokens // page)
        shared: List[int] = []
        pinned: List[int] = []
        cow_src = None
        if hit is not None:
            # pin EVERY hit page (incl. the COW source) BEFORE alloc: alloc's
            # on-demand LRU eviction could otherwise evict this very entry and
            # hand its just-freed pages back as "fresh" pages of the same
            # request — aliasing prefix and suffix blocks to one physical page
            pinned = list(hit.pages)
            self._kv_pool.incref(pinned)
            shared = pinned[: hit.full_pages]
            if len(pinned) > hit.full_pages:
                cow_src = pinned[hit.full_pages]
        fresh = self._kv_pool.alloc(total - len(shared))
        if fresh is None:
            if pinned:
                self._kv_pool.decref(pinned)
            return False
        if cow_src is not None:
            # the sharer's own suffix K/V lands in the boundary page — clone
            # it (positions below the prefix length carry the owner's valid
            # prefix K/V; at/above it the clone holds garbage the sharer's
            # suffix prefill overwrites before it is ever unmasked)
            self._ledger.note_dispatch("cow")
            with self._mesh_scope():
                self._cache = self._copy_pages(
                    self._cache,
                    jnp.asarray([cow_src], jnp.int32),
                    jnp.asarray([fresh[0]], jnp.int32),
                )
            self._kv_pool.cow_copies += 1
            # the clone is done — the boundary page only needed the pin
            self._kv_pool.decref([cow_src])
        row = shared + fresh
        self._slot_pages[slot] = row
        self._block_tables[slot, :] = self._kv_sentinel
        self._block_tables[slot, : len(row)] = row
        self._bt_dirty = True
        return True

    def _free_slot_pages(self, slot: int) -> None:
        """Release a slot's page references (request finished / reclaimed /
        quarantined).  Registered prefix entries keep their own refs, so
        shared pages survive the owner; everything refcount-0 returns to the
        free list for the next admission."""
        if not self._slot_pages[slot]:
            return
        self._kv_pool.decref(self._slot_pages[slot])
        self._slot_pages[slot] = []
        self._block_tables[slot, :] = self._kv_sentinel
        self._bt_dirty = True

    def _peek_next(self, now: float) -> Optional[_Request]:
        """Head-of-queue inspection without removal.  Scheduler path: the
        weighted-fair-share winner (dead entries reaped inside).  Legacy FIFO
        path: the `_pending` head, skipping cancelled/expired entries.

        peek()/pop() resolve reaped DeadlineExceeded futures after releasing
        the SCHEDULER lock, but this caller runs under _iter_lock — so those
        done-callbacks execute under the iteration lock and fall under the
        CALLBACK CONTRACT at _iter_lock's creation site (callbacks must never
        acquire any engine's _iter_lock)."""
        if self.scheduler is not None:
            return self.scheduler.peek(now)
        while self._pending:
            req = self._pending[0]
            if req.future.cancelled():
                self._pending.popleft()
                continue
            if req.deadline_at is not None and now >= req.deadline_at:
                self._pending.popleft()
                _safe_resolve(
                    req.future,
                    exc=DeadlineExceeded(
                        f"deadline expired after {now - req.submitted_at:.2f}s in queue"
                    ),
                )
                continue
            return req
        return None

    def _take_next(self, now: float) -> Optional[_Request]:
        # same _iter_lock callback-contract note as _peek_next
        if self.scheduler is not None:
            return self.scheduler.pop(now)
        return self._pending.popleft() if self._pending else None

    def _requeue_front(self, req: _Request) -> None:
        """Put a just-popped request back at the head of its queue (admission
        could not start it this iteration: pool out of pages, or a chunked
        prefill is already in flight)."""
        if self.scheduler is not None:
            self.scheduler.enqueue(req, front=True)
        else:
            self._pending.appendleft(req)

    def _admit(self) -> bool:
        admitted = False
        # stage queued requests: into the scheduler (which orders them by
        # class/tenant fair share) or the FIFO deque so the head can be
        # inspected without losing order
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            if self.scheduler is not None:
                self.scheduler.enqueue(req)
            else:
                self._pending.append(req)
        now = self._clock()
        free = self._free_slots()
        batch: List[tuple[int, _Request, Any]] = []
        while free and len(batch) < self.prefill_wave:
            req = self._peek_next(now)
            if req is None:
                break
            hit = self._prefix_lookup(req)
            # with a cached prefix only the suffix runs through the model, so
            # the chunked path is needed only when the REMAINDER exceeds a chunk
            n_eff = len(req.prompt_ids) - (hit.length if hit else 0)
            if n_eff > self.chunk_size and (self._chunking is not None or batch):
                break  # one chunked prefill at a time; scheduling order preserved
            slot = free[0]
            if not self._paged_admit_slot(slot, req, hit):
                if hit is not None:
                    # the pinned hit itself may be what eviction needed — drop
                    # it and retry as a full prefill (the entry becomes
                    # evictable), so a registry-heavy pool cannot wedge the
                    # queue head
                    hit = None
                    n_eff = len(req.prompt_ids)
                    if n_eff > self.chunk_size and (
                        self._chunking is not None or batch
                    ):
                        break
                    if not self._paged_admit_slot(slot, req, None):
                        break
                else:
                    break  # out of pages: the head waits for a slot to free
            taken = self._take_next(now)
            if taken is None:
                # the peeked request vanished between peek and pop — if its
                # admission already dispatched a restore, the pages free but
                # the in-flight gauge must drop too (the restored prefix
                # itself survives: it was re-registered)
                self._drop_restore_inflight(req)
                self._free_slot_pages(slot)
                break
            if taken is not req:
                # the head moved between peek and pop (a client cancelled the
                # peeked request, or a concurrent enqueue re-ordered the fair
                # share) — the POPPED request is the one that must be served;
                # dropping it would leave its future unresolved forever
                self._drop_restore_inflight(req)
                self._free_slot_pages(slot)
                req = taken
                hit = self._prefix_lookup(req)
                n_eff = len(req.prompt_ids) - (hit.length if hit else 0)
                if n_eff > self.chunk_size and (
                    self._chunking is not None or batch
                ):
                    self._requeue_front(req)
                    break
                if not self._paged_admit_slot(slot, req, hit):
                    self._requeue_front(req)
                    break
            free.pop(0)
            self._count_prefix(req, hit)
            req.prefix_hit_tokens = hit.length if hit is not None else 0
            if n_eff > self.chunk_size:
                self._begin_chunked(slot, req, prefix=hit)
                admitted = True
            else:
                batch.append((slot, req, hit))
        if batch:
            # one dispatch per program of the plan (plan_prefill): the wave
            # grouped by seq bucket, so short prompts do not pay the longest
            # prompt's O(S^2) attention, and each group on the fewest
            # positions the warmed shapes allow.  Prefix-hit rows prefill only
            # their SUFFIX (bucketed by suffix length) via prefill_suffix;
            # misses take the full-prompt path.
            full = [(slot, req) for slot, req, hit in batch if hit is None]
            suffix = [(slot, req, hit) for slot, req, hit in batch if hit is not None]
            # every not-yet-slotted request of the wave stays in
            # _starting_batch until its program succeeds — if an earlier
            # program's prefill raises, _restart salvages the rest instead of
            # orphaning them
            remaining = full + [(s, r) for s, r, _ in suffix]
            self._starting_batch = remaining
            for rows, bucket, members in plan_prefill(
                self.prefill_shapes, [len(r.prompt_ids) for _, r in full]
            ):
                group = [full[i] for i in members]
                self._start_batch(group, rows, bucket)
                for pair in group:
                    remaining.remove(pair)
            for rows, bucket, members in plan_prefill(
                self.prefill_shapes, [len(r.prompt_ids) - h.length for _, r, h in suffix]
            ):
                sgroup = [suffix[i] for i in members]
                self._start_suffix_batch(sgroup, rows, bucket)
                for s, r, _ in sgroup:
                    remaining.remove((s, r))
            self._starting_batch = None
            admitted = True
        return admitted

    def _count_prefix(self, req: _Request, hit) -> None:
        if self.prefix_cache_size > 0 and req.prefix_len >= self.prefix_min_tokens:
            if hit is not None:
                self.prefix_hits += 1
                if isinstance(hit, _HostHit):
                    # the warm-but-not-HBM subset: served via restore
                    self.kv_host_hits += 1
            else:
                self.prefix_misses += 1

    def warmup(self, json: bool = False) -> None:
        """Deterministically compile every (rows, bucket) prefill + insert +
        activation shape of ``prefill_shapes`` (the set admission picks from)
        and the decode tick.  Admission-wave sizes are
        timing-dependent, so relying on warm *traffic* to hit every shape is
        racy — a multi-second XLA compile can land mid-measurement (or mid-SLA).
        ``json=True`` additionally builds the token FSM and compiles the
        JSON-constrained activation/tick variants.  Call before :meth:`start`:
        every warm-up write targets the slot / page sentinels and drops on the
        device."""
        if self._running:
            raise RuntimeError("warmup() must run before start() — the engine "
                               "thread owns the cache once running")
        if json:
            self._ensure_fsm()
        with self._mesh_scope():
            for bucket, row_counts in self.prefill_shapes.items():
                for bp in row_counts:
                    ids = jnp.zeros((bp, bucket), jnp.int32)
                    lengths = jnp.zeros((bp,), jnp.int32)
                    logits, ks, vs = self._prefill(self.params, ids, lengths)
                    # sentinel slots + block tables: the compiled scatter
                    # shapes are exercised, every write drops on device
                    self._cache = self._insert(
                        self._cache,
                        ks,
                        vs,
                        lengths,
                        jnp.full((bp,), self.max_slots, jnp.int32),
                        jnp.full(
                            (bp, self._kv_blocks), self._kv_sentinel, jnp.int32
                        ),
                    )
                    # the fused activation program keys on the batch bucket too
                    # — compile it here, discarding results (all rows OOB-drop)
                    self._activate_fn(
                        logits,
                        self._tokens_dev,
                        self._rng,
                        np.ones((bp,), np.float32),
                        np.ones((bp,), np.float32),
                        np.full((bp,), self.max_slots, np.int32),
                    )
                    if json:
                        self._activate_fn_json(
                            logits,
                            self._tokens_dev,
                            self._rng,
                            np.ones((bp,), np.float32),
                            np.ones((bp,), np.float32),
                            np.full((bp,), self.max_slots, np.int32),
                            fsm_states=self._fsm_states_dev,
                            jmask=np.zeros((bp,), bool),
                            init_row=self._fsm_init_row_dev,
                            next_tab=self._fsm_next_dev,
                            initial=self._fsm.initial,
                        )
            if self.chunk_size < self.max_seq_len - 1:
                # chunked prefill (prompts > chunk_size) has one fixed shape;
                # unreachable (and not worth compiling) when prompts are
                # truncated to max_seq_len - 1 <= chunk_size
                _, self._cache = self._prefill_chunk(
                    self.params,
                    jnp.zeros((1, self.chunk_size), jnp.int32),
                    self._cache,
                    jnp.full((self._kv_blocks,), self._kv_sentinel, jnp.int32),
                    jnp.asarray(0, jnp.int32),
                    jnp.asarray(0, jnp.int32),
                    jnp.asarray(0, jnp.int32),
                )
                if self._piggyback_tick is not None:
                    # the continuous-batching program (chunk + decode scan):
                    # valid=0 drops every chunk write, all-False active
                    # freezes every decode row — warm is a pure compile
                    _, _pg_last, self._cache, self._rng = (
                        self._piggyback_tick(
                            self.params,
                            self._tokens_dev,
                            self._cache,
                            jnp.zeros((self.max_slots,), bool),
                            self._bt_dev,
                            jnp.asarray(self._temps),
                            jnp.asarray(self._top_ps),
                            self._rng,
                            jnp.zeros((1, self.chunk_size), jnp.int32),
                            jnp.asarray(0, jnp.int32),
                            jnp.asarray(0, jnp.int32),
                            jnp.asarray(0, jnp.int32),
                        )
                    )
            if self.prefix_cache_size > 0:
                # prefix path: the batched suffix prefill per (batch, seq)
                # bucket plus the COW page clone — sentinel targets, so every
                # warmup write drops
                for bucket, row_counts in self.prefill_shapes.items():
                    for bp in row_counts:
                        logits, self._cache = self._prefill_suffix(
                            self.params,
                            jnp.zeros((bp, bucket), jnp.int32),
                            self._cache,
                            jnp.full(
                                (bp, self._kv_blocks), self._kv_sentinel, jnp.int32
                            ),
                            jnp.full((bp,), self.max_slots, jnp.int32),
                            jnp.zeros((bp,), jnp.int32),
                            jnp.zeros((bp,), jnp.int32),
                        )
                self._cache = self._copy_pages(
                    self._cache,
                    jnp.zeros((1,), jnp.int32),
                    jnp.full((1,), self._kv_sentinel, jnp.int32),
                )
                if self._kv_host is not None:
                    # host-tier spill/restore shapes for small page counts:
                    # a serve-time restore of a 1-2 page prefix (the common
                    # case) must not pay an XLA compile.  Gather-then-write
                    # of page 0 onto itself is an identity write — safe on
                    # the empty pre-start cache.
                    for n_warm in (1, 2, 3, 4):
                        if n_warm > self._kv_pool.n_pages:
                            break
                        idx = jnp.zeros((n_warm,), jnp.int32)
                        wk, wv = self._gather_pages(self._cache, idx)
                        self._cache = self._write_pages(
                            self._cache, idx, wk, wv
                        )
            toks, last, self._cache, self._rng = self._decode_tick(
                self.params,
                self._tokens_dev,
                self._cache,
                jnp.zeros((self.max_slots,), bool),
                self._bt_dev,
                jnp.asarray(self._temps),
                jnp.asarray(self._top_ps),
                self._rng,
            )
            if self.speculative:
                # every rung's spec tick + the per-admission history write,
                # then a timed micro-probe per rung so the controller's
                # breakeven test runs on MEASURED verify/decode cost ratios
                # instead of the conservative default
                self._history_dev = self._hist_set(
                    self._history_dev,
                    jnp.zeros((self.max_seq_len,), jnp.int32),
                    jnp.int32(0),
                )
                for rung in self._spec_ctl.rungs:
                    _, _, last2, self._history_dev, self._cache, self._rng = (
                        self._spec_ticks[rung](
                            self.params,
                            last,
                            self._history_dev,
                            self._cache,
                            self._bt_dev,
                            jnp.zeros((self.max_slots,), bool),
                            jnp.asarray(self._temps),
                            jnp.asarray(self._top_ps),
                            self._rng,
                        )
                    )
                jax.block_until_ready(last2)
                self._measure_spec_costs(iters=4)
            if json:
                toks, last, self._cache, self._rng, _ = self._decode_tick_json(
                    self.params,
                    last,
                    self._cache,
                    jnp.zeros((self.max_slots,), bool),
                    self._bt_dev,
                    jnp.asarray(self._temps),
                    jnp.asarray(self._top_ps),
                    self._rng,
                    self._fsm_states_dev,
                    jnp.zeros((self.max_slots,), bool),
                    self._fsm_next_dev,
                    self._fsm_allowed_dev,
                )
            jax.block_until_ready(last)

    def _kv_read_frac(self) -> float:
        """Host-side mirror of the device's paged-read window for THIS tick:
        pages covering the longest live slot / pages of a full context.  An
        estimate (a burst advances positions mid-tick; in-flight speculation
        lags a little), but it tracks the device's traced ``hi`` bound to
        within one page."""
        mx = 0
        for s in self._slots:
            if s is not None:
                pos = len(s.request.prompt_ids) + len(s.generated)
                mx = max(mx, min(pos, self.max_seq_len - 1))
        return (mx // self.kv_page_size + 1) / self._kv_blocks

    def _wave_block_tables(self, slots: List[int], pad: int) -> np.ndarray:
        """Block-table rows for a prefill wave ([Bp, n_blocks]); the first
        ``pad`` rows are batch-bucket padding and carry the page sentinel
        everywhere — their writes drop on device."""
        bt = np.full(
            (pad + len(slots), self._kv_blocks), self._kv_sentinel, np.int32
        )
        for j, slot in enumerate(slots):
            bt[pad + j] = self._block_tables[slot]
        return bt

    def _start_batch(self, batch: List[tuple[int, _Request]], Bp: int, bucket: int):
        """One prefill dispatch: the ``Bp`` x ``bucket`` program of the wave's
        plan (:func:`plan_prefill`) that ``batch`` rides.

        Pad rows carry zero lengths, PRECEDE the real rows, and carry the
        ``max_slots`` / page sentinels, so their writes drop on the device."""
        reqs = [r for _, r in batch]
        slots = [s for s, _ in batch]
        B = len(batch)
        with self._ledger.span(
            "prefill_dispatch", bucket=bucket, rows=B, rows_padded=Bp, seq=self._ledger.seq + 1
        ):
            pad = Bp - B
            ids = np.full((Bp, bucket), self.tokenizer.pad_id, np.int32)
            lengths = np.zeros((Bp,), np.int32)
            slot_arr = np.full((Bp,), self.max_slots, np.int32)
            for j, req in enumerate(reqs):
                n = len(req.prompt_ids)
                ids[pad + j, :n] = req.prompt_ids
                lengths[pad + j] = n
                slot_arr[pad + j] = slots[j]
            self._note_wave("prefill", reqs, int(lengths.sum()), bucket, Bp)
            with self._mesh_scope():
                logits, ks, vs = self._prefill(
                    self.params, jnp.asarray(ids), jnp.asarray(lengths)
                )
                self._cache = self._insert(
                    self._cache,
                    ks,
                    vs,
                    jnp.asarray(lengths),
                    jnp.asarray(slot_arr),
                    jnp.asarray(self._wave_block_tables(slots, pad)),
                )
            # a miss with a declared prefix: register its pages for future
            # requests (pure refcounting — admission never blocks on it)
            for slot, req in batch:
                self._maybe_register_prefix(slot, req)
            # activation consumes the FULL [Bp, V] logits so its sampling and
            # scatter shapes key on the program's rows, not the wave size —
            # otherwise every distinct wave size would trigger fresh compiles
            self._activate_batch(slots, reqs, logits, pad=pad)

    def _note_wave(
        self, kind: str, reqs: List[_Request], real: int, bucket: int, Bp: int, start: int = 0
    ) -> None:
        """One prefill program about to be dispatched (with the insert and the
        activation that follow it, one group): the ledger's dispatch, which
        feeds the padding counters, and on each request the shape of the
        program it rides (``usage.timings``)."""
        self._ledger.note_dispatch(kind, Bp, bucket, real, start)
        for req in reqs:
            req.prefill_bucket = bucket
            req.wave_rows = len(reqs)
            req.wave_rows_padded = Bp

    def _start_suffix_batch(
        self, group: List[tuple[int, _Request, Any]], Bp: int, bucket: int
    ):
        """Admit prefix-cache hits on one ``Bp`` x ``bucket`` program of the
        wave's plan: admission already wired the shared pages into each
        slot's block table, so ONE batched suffix prefill continues all rows
        from their prefix lengths; the skipped work is exactly the prefix
        recompute the reference pays every turn."""
        slots = [s for s, _, _ in group]
        reqs = [r for _, r, _ in group]
        hits = [h for _, _, h in group]
        B = len(group)
        with self._ledger.span(
            "prefill_dispatch", bucket=bucket, rows=B, rows_padded=Bp, suffix=1,
            seq=self._ledger.seq + 1,
        ):
            pad = Bp - B
            ids = np.full((Bp, bucket), self.tokenizer.pad_id, np.int32)
            starts = np.zeros((Bp,), np.int32)
            valids = np.zeros((Bp,), np.int32)
            slot_arr = np.full((Bp,), self.max_slots, np.int32)
            for j, (req, hit) in enumerate(zip(reqs, hits)):
                # the bucketed write window [start, start+bucket) must not cross
                # max_seq_len — dynamic_update_slice would CLAMP the start and
                # smear the window over the prefix.  _paged_usable_hit rejects
                # every hit whose window would (a slid window would re-write
                # SHARED pages), so the min() is a guard that never binds.
                start = min(hit.length, self.max_seq_len - bucket)
                chunk = req.prompt_ids[start : start + bucket]
                ids[pad + j, : len(chunk)] = chunk
                starts[pad + j] = start
                valids[pad + j] = len(chunk)
                slot_arr[pad + j] = slots[j]
            self._note_wave(
                "suffix", reqs, sum(len(r.prompt_ids) - h.length for r, h in zip(reqs, hits)),
                bucket, Bp,
            )
            with self._mesh_scope():
                logits, self._cache = self._prefill_suffix(
                    self.params,
                    jnp.asarray(ids),
                    self._cache,
                    jnp.asarray(self._wave_block_tables(slots, pad)),
                    jnp.asarray(slot_arr),
                    jnp.asarray(starts),
                    jnp.asarray(valids),
                )
            # a hit whose DECLARED split extends past the matched prefix (multi-turn:
            # the history grew) registers the longer prefix for the next turn
            for slot, req in zip(slots, reqs):
                self._maybe_register_prefix(slot, req)
            self._activate_batch(slots, reqs, logits, pad=pad)

    def _maybe_register_prefix(self, slot: int, req: _Request) -> None:
        """After a full prefill of ``slot``, make the request's declared prefix
        shareable: register the pages covering it with the allocator (pure
        refcounting — no copy, no extra HBM beyond what the request already
        holds)."""
        if self.prefix_cache_size <= 0 or req.prefix_len < self.prefix_min_tokens:
            return
        nbp = -(-req.prefix_len // self.kv_page_size)
        pages = [int(p) for p in self._block_tables[slot, :nbp]]
        if any(p >= self._kv_sentinel for p in pages):
            return  # allocation didn't cover the prefix (shouldn't happen)
        self._kv_pool.register(req.prompt_ids, req.prefix_len, pages)

    def _begin_chunked(self, slot: int, req: _Request, prefix=None):
        """Split a long prompt into full-size chunks.  The final chunk *slides left*
        to end exactly at the prompt end (re-feeding a few already-written positions
        — their K/V recompute to identical values) so no chunk ever carries pad
        tokens and no cache write can cross ``max_seq_len``.

        With a cached ``prefix`` (its shared pages already wired into the
        block table, and the boundary page COW-cloned, by
        :meth:`_paged_admit_slot`) chunking covers only the remainder: starts
        begin at the prefix length, and the final chunk never slides into the
        prefix (remainder > chunk_size)."""
        n = len(req.prompt_ids)
        base = prefix.length if prefix is not None else 0
        c = self.chunk_size
        flat = np.asarray(req.prompt_ids, np.int32)
        starts = list(range(base, n - c, c)) + [n - c]
        ids = np.stack([flat[s : s + c] for s in starts])
        req.started_at = self._clock()
        self._chunking = _ChunkedPrefill(
            request=req, slot=slot, ids=ids, starts=starts, n=n
        )

    def _note_chunk(self, kind: str, st: "_ChunkedPrefill", j: int) -> None:
        """Chunk ``j`` about to be dispatched (alone, ``chunk``, or inside a
        tick, ``piggyback``): one row of ``chunk_size``; the sliding last
        chunk re-feeds what it overlaps."""
        new = self.chunk_size if j == 0 else st.starts[j] - st.starts[j - 1]
        self._note_wave(kind, [st.request], new, self.chunk_size, 1, st.starts[j])

    def _chunk_step(self):
        st = self._chunking
        assert st is not None
        j = st.step
        self._note_chunk("chunk", st, j)
        with self._mesh_scope():
            logits, self._cache = self._prefill_chunk(
                self.params,
                jnp.asarray(st.ids[j : j + 1]),
                self._cache,
                jnp.asarray(self._block_tables[st.slot]),
                jnp.asarray(st.slot, jnp.int32),
                jnp.asarray(st.starts[j], jnp.int32),
                jnp.asarray(self.chunk_size, jnp.int32),
            )
        st.step += 1
        if st.request.future.cancelled():
            # the consumer vanished mid-prefill: abandon the remaining chunks
            self.reclaimed_slots += 1
            self.cancelled_slots += 1
            self._drop_restore_inflight(st.request)
            self._free_slot_pages(st.slot)
            self._chunking = None
            return
        dl = st.request.deadline_at
        if dl is not None and self._clock() >= dl:
            # expired mid-prefill: abandon the remaining chunks entirely
            self.reclaimed_slots += 1
            if self.scheduler is not None:
                self.scheduler.note_expired_running(st.request.priority)
            _safe_resolve(
                st.request.future,
                exc=DeadlineExceeded("deadline expired during chunked prefill"),
            )
            self._drop_restore_inflight(st.request)
            self._free_slot_pages(st.slot)
            self._chunking = None
            return
        if st.step >= len(st.starts):
            self._chunking = None
            self._maybe_register_prefix(st.slot, st.request)
            self._starting_batch = [(st.slot, st.request)]
            self._activate(st.slot, st.request, logits)
            self._starting_batch = None
            s = self._slots[st.slot]
            if s is not None:
                # service-model charge: every chunk dispatch (sequential or
                # piggybacked) was a unit of engine service this request
                # consumed before its first decode step
                s.prefill_chunks = st.step

    def _piggyback_step(self):
        """One continuous-batching dispatch: the admitting slot's next prefill
        chunk AND a fused decode tick for the resident slots, in ONE jitted
        program (:meth:`_make_piggyback_tick`).  Combines :meth:`_issue_tick`'s
        dispatch/pipeline bookkeeping with :meth:`_chunk_step`'s chunk
        bookkeeping; the gate in :meth:`_loop_iteration` guarantees this is
        never the FINAL chunk (whose logits feed the activation) and that no
        json/speculative state is live."""
        st = self._chunking
        assert st is not None and self._piggyback_tick is not None
        if self._faults is not None:
            # same chaos sites as the plain tick: a raise here is engine-
            # fatal mid-piggyback (the chaos case tests/test_contbatch.py
            # pins: restart must leave the page pool clean)
            self._faults.maybe_raise("tick_raise", "device step")
            delay = self._faults.sleep_s("slow_tick")
            if delay:
                self._sleep(delay)
        self._refresh_sampling()
        self._decode_steps_effective = self.burst
        j = st.step
        self._note_chunk("piggyback", st, j)
        with self._mesh_scope():
            toks, last, self._cache, self._rng = self._piggyback_tick(
                self.params,
                self._tokens_dev,
                self._cache,
                self._active_dev,
                self._bt_dev,
                self._temps_dev,
                self._top_ps_dev,
                self._rng,
                jnp.asarray(st.ids[j : j + 1]),
                jnp.asarray(st.slot, jnp.int32),
                jnp.asarray(st.starts[j], jnp.int32),
                jnp.asarray(self.chunk_size, jnp.int32),
            )
        toks.copy_to_host_async()
        self._tokens_dev = last
        self.steps += self.burst
        self._ticks_issued += 1
        self._kv_frac_sum += self._kv_read_frac()
        live = [
            (i, self._slot_epoch[i]) for i, s in enumerate(self._slots) if s is not None
        ]
        self._inflight.append(
            _TickRef(nxt=toks, slots=live, aux=self._take_aux(), seq=self._ledger.seq)
        )
        st.step += 1
        self._prefill_chunks_piggybacked += 1
        # the same mid-prefill reaping as _chunk_step (the decode side of the
        # dispatch needs none of this — its slots reap via _reap_dead_slots)
        if st.request.future.cancelled():
            self.reclaimed_slots += 1
            self.cancelled_slots += 1
            self._drop_restore_inflight(st.request)
            self._free_slot_pages(st.slot)
            self._chunking = None
            return
        dl = st.request.deadline_at
        if dl is not None and self._clock() >= dl:
            self.reclaimed_slots += 1
            if self.scheduler is not None:
                self.scheduler.note_expired_running(st.request.priority)
            _safe_resolve(
                st.request.future,
                exc=DeadlineExceeded("deadline expired during chunked prefill"),
            )
            self._drop_restore_inflight(st.request)
            self._free_slot_pages(st.slot)
            self._chunking = None

    def _activate(self, slot: int, req: _Request, logits):
        self._activate_batch([slot], [req], logits, pad=0)

    def _activate_batch(
        self, slots: List[int], reqs: List[_Request], logits, *, pad: int
    ):
        """Sample first tokens from prefill logits ([Bp, V], first ``pad`` rows
        are batch-bucket padding) and make the wave's slots live.

        Fully asynchronous: tokens stay on device (chained into the decode token
        array and, for JSON, the FSM states) via ONE fused jit call per batch
        bucket (:meth:`_make_activate`); host values arrive through the inflight
        pipeline — admission never pays a device sync.  Pad rows sample garbage
        dropped on device (out-of-bounds scatter index + ``mode="drop"``)."""
        temps = np.asarray([1.0] * pad + [r.temperature for r in reqs], np.float32)
        top_ps = np.asarray([1.0] * pad + [r.top_p for r in reqs], np.float32)
        scatter_idx = np.asarray([self.max_slots] * pad + slots, np.int32)
        with self._mesh_scope():
            if any(r.json for r in reqs):
                self._ensure_fsm()
                jmask = np.asarray([False] * pad + [r.json for r in reqs])
                first, self._tokens_dev, self._rng, self._fsm_states_dev = (
                    self._activate_fn_json(
                        logits,
                        self._tokens_dev,
                        self._rng,
                        temps,
                        top_ps,
                        scatter_idx,
                        fsm_states=self._fsm_states_dev,
                        jmask=jmask,
                        init_row=self._fsm_init_row_dev,
                        next_tab=self._fsm_next_dev,
                        initial=self._fsm.initial,
                    )
                )
            else:
                first, self._tokens_dev, self._rng = self._activate_fn(
                    logits, self._tokens_dev, self._rng, temps, top_ps, scatter_idx
                )
        ref_slots = []
        now_started = self._clock()
        for slot, req in zip(slots, reqs):
            if req.started_at is None:  # chunked prefills set it at begin
                req.started_at = now_started
            if req.restored_from_host:
                # the restore's consumer (the suffix prefill) is dispatched:
                # the in-flight gauge drops here, where admission completes
                req.restored_from_host = False
                self._kv_restores_inflight = max(0, self._kv_restores_inflight - 1)
            self._slots[slot] = _Slot(request=req)
            self._temps[slot] = req.temperature
            self._top_ps[slot] = req.top_p
            self._json[slot] = req.json
            ref_slots.append((slot, self._slot_epoch[slot]))
            if self.speculative:
                # seed the slot's device token history with the prompt — the
                # prompt IS the draft source (prompt-lookup); ~2-4 KB h2d per
                # admission, off the decode hot path
                row = np.zeros((self.max_seq_len,), np.int32)
                n = min(len(req.prompt_ids), self.max_seq_len)
                row[:n] = req.prompt_ids[:n]
                with self._mesh_scope():
                    self._history_dev = self._hist_set(
                        self._history_dev, jnp.asarray(row), jnp.int32(slot)
                    )
        self._sampling_dirty = True
        first.copy_to_host_async()
        self._inflight.append(
            _TickRef(nxt=first, slots=ref_slots, first=True, offset=pad, seq=self._ledger.seq)
        )

    def _upload_dirty(self) -> bool:
        """Stage any dirty sampling/block-table arrays to the device; returns
        True when something was actually uploaded (the shared body of the
        issue-path :meth:`_refresh_sampling` and the overlapped
        :meth:`_prestage_uploads`)."""
        did = False
        if self._sampling_dirty:
            self._active_dev = jnp.asarray([s is not None for s in self._slots])
            self._temps_dev = jnp.asarray(self._temps)
            self._top_ps_dev = jnp.asarray(self._top_ps)
            self._json_dev = jnp.asarray(self._json)
            self._sampling_dirty = False
            did = True
        if self._bt_dirty:
            # [max_slots, n_blocks] int32 — a few KB, re-sent only when an
            # admission or free actually changed a block table
            self._bt_dev = jax.device_put(
                jnp.asarray(self._block_tables),
                _replicated(self.mesh) if self.mesh is not None else None,
            )
            self._bt_dirty = False
            did = True
        return did

    def _refresh_sampling(self):
        if self._upload_dirty():
            # paid on the issue path: the upload enqueue sat between this
            # tick's bookkeeping and its dispatch instead of overlapping the
            # previous tick's device time
            self._uploads_issue += 1

    def _prestage_uploads(self):
        """Double-buffer the host->device sampling/block-table uploads against
        the in-flight tick: called at the END of a loop iteration — after
        :meth:`_process_tick` freed finished slots (dirtying the arrays) and
        while up to ``lookahead`` ticks are still executing on device — so
        the next tick's arrays are already committed when its
        :meth:`_issue_tick` runs.  Uploads superseded by a later admission
        are re-staged on the issue path (counted there), standard
        double-buffer cost.  ``upload_overlap_frac`` in tick_stats is the
        fraction of upload cycles this path absorbed."""
        if self._inflight and (self._sampling_dirty or self._bt_dirty):
            if self._upload_dirty():
                self._uploads_prestaged += 1

    def upload_overlap_frac(self) -> float:
        """Fraction of sampling/block-table upload cycles dispatched while a
        tick was in flight (double-buffered) rather than on the issue path."""
        total = self._uploads_prestaged + self._uploads_issue
        return round(self._uploads_prestaged / total, 4) if total else 0.0

    def tick_stats(self) -> dict:
        """Aggregate per-tick wall breakdown (ms/tick).  `block` near zero means
        the lookahead pipeline fully hides device latency; `block` dominating
        means the device is the bottleneck and burst/slots are
        the knobs; `issue` dominating means dispatch enqueue is."""
        n = max(1, self._ticks_issued)
        led = self._ledger
        out = {
            "ticks": self._ticks_issued,
            "issue_ms": round(led.seconds("tick_issue") / n * 1e3, 3),
            "block_ms": round(
                led.seconds("tick_block") / max(1, self._ticks_processed) * 1e3, 3
            ),
            # average fraction of a full context's pages the decode attention
            # actually read (< 1 whenever live contexts are shorter than
            # max_seq_len)
            "kv_read_frac": round(self._kv_frac_sum / n, 4)
            if self._ticks_issued
            else 1.0,
        }
        # decode-path gauges (docs/QUANT.md): which fast path is ACTUALLY
        # active — the configured fused depth vs what the last tick ran
        # (json_fsm slots downgrade to 1), the weight format's bit width,
        # and how much of the upload traffic the double-buffer absorbed
        out.update(self.decode_path_stats())
        # running totals a window's difference can be taken of: the engine
        # thread's time by loop phase, and what the prefill programs padded
        out.update(self.loop_stats())
        if self.speculative:
            out.update(self.spec_stats())
        # KV memory plane gauges: pool occupancy, sharing fraction, allocator
        # eviction/COW counters
        out["kv"] = self.kv_stats()
        moe = self.moe_stats()
        if moe is not None:
            out["moe"] = moe
        dsa = self.dsa_stats()
        if dsa is not None:
            out["dsa"] = dsa
        out["reclaimed_slots"] = self.reclaimed_slots
        # device-slice identity + per-slice HBM ledger (docs/MULTICHIP.md)
        out["slice"] = self.slice_stats()
        # restart/quarantine/circuit counters + loop heartbeat (supervision)
        out["supervision"] = self.supervision_stats()
        out.update(self.latency_stats())
        if self.scheduler is not None:
            # queue-pressure snapshot: depth/pressure/shed/wait percentiles
            out["sched"] = self.scheduler.stats()
        return out

    def loop_stats(self, recent: bool = False) -> dict:
        """The engine-loop time ledger (serving/obs.py ``LoopLedger``), as
        running totals: ``loop`` = ``{phase: {"s": exclusive seconds, "n":
        spans}}`` over the engine thread's whole life (the phases tile its
        wall time); ``device_queue`` = the device's time by what its queue
        held between two results the host waited for
        (``LoopLedger.queue_snapshot``: segments by kind and shape, the time
        the queue stood empty by loop phase, markers waited for or not); and
        the prefill positions run: prompt tokens (``real``) against rows x
        bucket of the dispatched programs (``padded``), and the dispatches by
        shape (``prefill_shapes``: ``"<rows>x<bucket>"`` -> count, every
        warmed shape listed; a chunk counts as 1 x chunk).  ``recent=True``
        adds ``device_queue_recent``: the last 512 segments one by one, on
        the engine's clock (to hold against a trace, or after a stall)."""
        led = self._ledger
        out = {
            "loop": led.snapshot(),
            "device_queue": led.queue_snapshot(),
            "prefill_tokens_real": led.prefill_tokens_real,
            "prefill_tokens_padded": led.prefill_tokens_padded,
            "prefill_shapes": dict(led.prefill_shapes),
        }
        if recent:
            out["device_queue_recent"] = led.recent_segments()
        return out

    def decode_path_stats(self) -> dict:
        """Decode fast-path gauges for tick_stats / /healthz / /metrics:
        ``decode_steps`` (configured fused depth), ``decode_steps_effective``
        (what the last plain tick actually ran — 1 while json_fsm slots are
        live), ``json_downgraded_ticks``, ``upload_overlap_frac`` (fraction
        of sampling/block-table upload cycles double-buffered against an
        in-flight tick), and ``weight_bits`` (16/8/4 — the weight format the
        decode dot is reading): the active configuration is a gauge, not a
        boot log line."""
        return {
            "decode_steps": self.decode_steps,
            "decode_steps_effective": self._decode_steps_effective,
            "json_downgraded_ticks": self._json_downgraded_ticks,
            "upload_overlap_frac": self.upload_overlap_frac(),
            "weight_bits": self.weight_bits,
            # continuous batching (docs/SCHEDULING.md "Continuous batching"):
            # is the piggyback program armed, and how many chunks rode a
            # decode tick (what decode still waits out on sequential chunks
            # is device time: tick_stats()["device_queue"] `chunk+tick` /
            # `chunk` against `piggyback`)
            "prefill_piggyback": bool(self._piggyback_tick is not None),
            "prefill_chunks_piggybacked": self._prefill_chunks_piggybacked,
            # fp8 in-dot attention (docs/QUANT.md): whether the decode
            # attention dots read the KV operand at fp8 storage width
            "attn_fp8": self.attn_fp8,
            # "kernel": the decode step writes its K/V row in place and reads
            # only the pages the block tables name; "xla": scatter + gather
            "decode_kv_path": self.decode_kv_path,
            # the same for an expert-parallel rank's held experts (None: none here)
            "moe_experts_path": self.moe_experts_path,
        }

    def moe_stats(self) -> Optional[dict]:
        """Routed-expert counters of an expert-parallel rank (``tick_stats()
        ["moe"]``, ``dabt_moe_*``), or None for a block without routed experts:
        running totals, for decode steps and for prefill programs apart, of
        routed picks and of those that landed on experts held here, tokens per
        held expert, layer-steps run and distinct held experts hit in them;
        ``experts_skipped_share``: 1 - hit / (held x layer-steps), the share of
        held experts no token landed on, which the ``kernel`` path never reads.
        Where the router has identity experts, also ``picks_zero`` (picks that
        fell on them: they cost nothing) and ``real_picks_hist`` (tokens by
        their number of real picks, 0..top-k: the spread of compute a token)."""
        lm = getattr(self.cfg, "latent_moe", None)
        if lm is None:
            return None
        tot = self._moe_totals
        zero_at = 4 + lm.experts_held  # the identity experts' counters follow the held experts' tokens
        if tot is None:
            tot = np.zeros((2, zero_at + zero_stat_width(self.cfg)), np.int64)
        out = {"experts_held": lm.experts_held, "first_expert": lm.first_expert,
               "router_experts": lm.router_experts, "ep_size": lm.ep_size, "ep_rank": lm.ep_rank}
        for row, kind in enumerate(("decode", "prefill")):
            out[kind] = {
                "picks": int(tot[row, 0]), "picks_local": int(tot[row, 1]),
                "layer_steps": int(tot[row, 2]), "experts_hit": int(tot[row, 3]),
                "tokens_per_expert": [int(v) for v in tot[row, 4:4 + lm.experts_held]],
                "experts_skipped_share": round(1.0 - float(tot[row, 3]) / max(1, lm.experts_held * int(tot[row, 2])), 4),
            }
            if lm.zero_experts:
                out[kind]["picks_zero"] = int(tot[row, zero_at])
                out[kind]["real_picks_hist"] = [int(v) for v in tot[row, zero_at + 1:zero_at + zero_stat_width(self.cfg)]]
        if lm.zero_experts:
            out["zero_experts"] = lm.zero_experts
        return out

    def dsa_stats(self) -> Optional[dict]:
        """Counters of the learned sparse attention (``tick_stats()["dsa"]``,
        ``dabt_dsa_*``), or None for a block without an indexer: running totals,
        for decode steps, chunk programs and every other prefill program apart,
        of programs run, queries, the (query, key) pairs a dense causal
        attention would attend, the pairs the selection kept and the (query,
        position) pairs its counting ran over (``pairs_scanned``: a chunk's
        queries x the step of the view its live keys reach, 0 where they are
        within ``index_topk`` and all are kept; a decode step's rows x the
        view), each of ONE layer (every layer selects as many).  Summed on the
        device and handed out with a tick's tokens, as the ``moe`` counters are."""
        lm = getattr(self.cfg, "latent_moe", None)
        if lm is None or not lm.index_topk:
            return None
        tot = self._moe_totals
        tail = np.zeros((2, self._model.DSA_STAT), np.int64) if tot is None else tot[:, 4 + lm.experts_held:]
        names = ("programs", "queries", "pairs_causal", "pairs_selected", "pairs_scanned")
        out: dict = {"index_topk": lm.index_topk}
        n = len(names)  # DSA_STAT is two such groups: a chunk program's, then any other prefill program's
        for kind, vals in (("decode", tail[0, :n]), ("chunk", tail[1, :n]), ("prefill", tail[1, n:2 * n])):
            out[kind] = {k: int(v) for k, v in zip(names, vals)}
        return out

    def slice_stats(self) -> dict:
        """Device-slice identity + HBM ledger for tick_stats / /healthz /
        /metrics (docs/MULTICHIP.md): which devices this replica's mesh
        actually spans, the slice id when the registry pinned it to one
        (None on the global-mesh path), and the device-resident byte
        footprint — weights plus the KV pool/cache allocation.  On an
        UNSLICED multi-replica fleet the weights are shared, so every
        replica's ``hbm_weight_bytes`` reports the same shared allocation;
        with slicing each replica's numbers are exclusively its own slice's
        (what makes the per-slice ledgers summable)."""
        return {
            "slice_id": self.slice_id,
            "devices": list(self.slice_devices),
            "sliced": self.slice_id is not None,
            "hbm_weight_bytes": self.hbm_weight_bytes,
            "hbm_kv_bytes": self.hbm_kv_bytes,
            "hbm_bytes": self.hbm_weight_bytes + self.hbm_kv_bytes,
        }

    def spec_stats(self) -> Optional[dict]:
        """Speculation gauges for tick_stats / healthz, or None on a
        non-speculative engine: cumulative draft/accept counters, the
        adaptive controller's state (acceptance EMA, per-arm EMAs, the tree
        shape currently issued) and whether — and WHY — speculation is off:
        ``spec_auto_disabled`` is the controller's breakeven verdict,
        ``spec_load_disabled`` the scheduler's degradation band."""
        if not self.speculative:
            return None
        out = {
            "spec_drafted": self.spec_drafted,
            "spec_accepted": self.spec_accepted,
            "spec_accept_rate": round(
                self.spec_accepted / max(1, self.spec_drafted), 4
            ),
            "spec_load_disabled": bool(
                self.scheduler is not None and self.scheduler.degraded()
            ),
            "spec_ticks": self.spec_ticks_issued,
            "spec_skipped_load": self.spec_skipped_load,
            "spec_skipped_accept": self.spec_skipped_accept,
        }
        out.update(self._spec_ctl.stats())
        return out

    def kv_stats(self) -> dict:
        """KV memory plane snapshot for tick_stats / healthz: what a cached
        token is, the pool gauges (``kv_pages_used`` / ``kv_pages_free`` /
        ``kv_shared_page_frac`` and the allocator's eviction/COW counters),
        the host tier's restore gauges, and the prefix hit/miss counters."""
        # what a cached token is ("kv": keys and values per KV head; "latent":
        # one latent row read as both; "latent+index": that and an indexer's
        # key) and what it takes over all layers
        out: dict = {
            "kv_cache_kind": getattr(self._model, "kv_kind", lambda cfg: self._model.KV_KIND)(self.cfg),
            "kv_bytes_per_token": int(
                self._model.kv_bytes_per_token(self.cfg, self.kv_cache_dtype)
            ),
        }
        out.update(self._kv_pool.stats())
        if self._kv_host is not None:
            # restore-side gauges (the tier's own spill/disk gauges ride
            # in through the allocator's stats): counts, in-flight, and
            # the host-visible restore-dispatch latency percentiles
            out["kv_restores"] = self.kv_restores
            out["kv_host_hits"] = self.kv_host_hits
            out["kv_restores_inflight"] = self._kv_restores_inflight
            # the engine thread appends concurrently; CPython's deque
            # raises RuntimeError when a copy races an append, which
            # must not fail a /metrics scrape mid-restore
            for _ in range(4):
                try:
                    restore = list(self._restore_s)
                    break
                except RuntimeError:
                    continue
            else:
                restore = []
            out["kv_restore_p50_ms"] = self._pctl_ms(restore, 0.50)
            out["kv_restore_p95_ms"] = self._pctl_ms(restore, 0.95)
        out["prefix_hits"] = self.prefix_hits
        out["prefix_misses"] = self.prefix_misses
        return out

    @staticmethod
    def _pctl_ms(samples, frac: float) -> float:
        vals = sorted(samples)
        if not vals:
            return 0.0
        idx = min(len(vals) - 1, max(0, round(frac * (len(vals) - 1))))
        return round(vals[idx] * 1e3, 3)

    def latency_stats(self) -> dict:
        """Perceived-latency percentiles over the recent sample windows:
        TTFT (submit -> first token on host) and inter-token latency, plus
        the disconnect counter — the streaming plane's operator dashboard
        (also exposed per-generator in /healthz).  ITL samples are host
        BATCH-arrival gaps: burst/speculative ticks deliver several tokens
        at once, so per-token cadence is roughly the gap divided by the
        tokens-per-tick."""
        ttft = list(self._ttft_s)
        itl = list(self._itl_s)
        return {
            "ttft_p50_ms": self._pctl_ms(ttft, 0.50),
            "ttft_p95_ms": self._pctl_ms(ttft, 0.95),
            "ttft_n": len(ttft),
            "itl_p50_ms": self._pctl_ms(itl, 0.50),
            "itl_p95_ms": self._pctl_ms(itl, 0.95),
            "itl_n": len(itl),
            "cancelled_slots": self.cancelled_slots,
        }

    def probe_decode(self, iters: int = 16, fill_len: Optional[int] = None) -> float:
        """Pure device decode rate: `iters` burst ticks issued back-to-back with
        device-chained state, one block at the end -> seconds per STEP (not per
        burst).  Separates the model's on-device step cost from engine/host
        overhead — the roofline denominator.  The loop-iteration lock excludes
        the engine thread for the probe's whole duration, so a request
        submitted mid-probe waits in the queue instead of racing the probe over
        the donated cache.

        ``fill_len=None`` probes with every slot inactive (cache lengths don't
        advance) — with the length-bucketed decode read that measures a
        near-empty cache, so callers wanting the cost at a *given* context fill
        pass ``fill_len``: the probe sets every free slot's cache length there
        and runs the ticks active, so the chunked attention reads the same KV
        window real traffic at that fill would.  Lengths advance by
        ``iters * burst`` and are reset to 0 afterwards; the garbage K/V the
        active probe writes sits beyond every future request's valid length
        until overwritten — the cache discipline decode already relies on.

        Waits up to 10 s for the loop to drain its speculative lookahead ticks
        (requests resolve `lookahead` ticks before the deque empties)."""
        deadline = self._clock() + 10.0
        while True:
            self._iter_lock.acquire()
            if self.num_active == 0 and not self._inflight and not self._chunking:
                break  # idle, and the loop is parked outside its iteration body
            self._iter_lock.release()
            if self._clock() >= deadline:
                raise RuntimeError("probe_decode requires an idle engine")
            self._sleep(0.01)
        try:
            return self._probe_decode_locked(iters, fill_len)
        finally:
            self._iter_lock.release()

    def _set_cache_lengths(self, values) -> None:
        lens = jnp.asarray(values, jnp.int32)
        if self._cache_shardings is not None:
            lens = jax.device_put(lens, self._cache_shardings.lengths)
        self._cache = self._cache._replace(lengths=lens)

    def _probe_decode_locked(self, iters: int, fill_len: Optional[int]) -> float:
        if fill_len is not None:
            # give every slot a DISTINCT round-robin page chain so the probe's
            # block-table gathers stream the same page spread real traffic at
            # this fill would (sentinel rows would collapse every gather onto
            # one clamped page — cache-resident, overstating the rate).
            # Registry-shared pages hold VALID prefix K/V a live cache may
            # serve later — the probe's garbage writes must not touch them.
            avoid = self._kv_pool.shared_page_ids()
            scratch = [p for p in range(self._kv_pool.n_pages) if p not in avoid]
            if scratch:
                for b in range(self.max_slots):
                    for j in range(self._kv_blocks):
                        self._block_tables[b, j] = scratch[
                            (b * self._kv_blocks + j) % len(scratch)
                        ]
                self._bt_dirty = True
        self._refresh_sampling()
        active = self._active_dev
        if fill_len is not None:
            # keep headroom so rows stay active (unfrozen) for the whole probe:
            # the warm tick below also advances lengths by one burst, hence
            # iters + 1 — under-reserving would freeze rows mid-final-tick and
            # silently time near-idle micro-steps
            fill = max(
                0,
                min(int(fill_len), self.max_seq_len - (iters + 1) * self.burst - 2),
            )
            self._set_cache_lengths(np.full((self.max_slots,), fill, np.int32))
            active = jnp.ones((self.max_slots,), bool)
        try:
            return self._probe_decode_timed(iters, active)
        finally:
            if fill_len is not None:
                # every slot is free (probe requires an idle engine): stale
                # lengths carry no meaning, and zeroing keeps the next live
                # batch's paged read window minimal.  In a finally so a
                # mid-probe dispatch error can't leave phantom fill lengths
                # widening every later batch's read window.
                self._set_cache_lengths(np.zeros((self.max_slots,), np.int32))
                self._block_tables[:] = self._kv_sentinel
                self._bt_dirty = True
                self._refresh_sampling()

    def _probe_decode_timed(self, iters: int, active) -> float:
        with self._mesh_scope():
            # one warm call (jit cache is hot after warmup(); cheap regardless)
            toks, last, self._cache, self._rng = self._decode_tick(
                self.params, self._tokens_dev, self._cache, active,
                self._bt_dev, self._temps_dev, self._top_ps_dev, self._rng,
            )
            self._tokens_dev = last
            np.asarray(toks)
            # the timed chain ends in a fetch of its last value, so the wall
            # covers every tick's device work plus one device->host copy of a
            # [burst, slots] int array — nothing is subtracted
            t0 = self._clock()
            for _ in range(iters):
                toks, last, self._cache, self._rng = self._decode_tick(
                    self.params, self._tokens_dev, self._cache, active,
                    self._bt_dev, self._temps_dev, self._top_ps_dev, self._rng,
                )
                self._tokens_dev = last
            np.asarray(toks)
        return (self._clock() - t0) / (iters * self.burst)

    def _spec_disabled_gauge(self) -> dict:
        """The spec_disabled gauge bound into the scheduler's stats: which
        mechanism (if any) is currently holding speculation off, plus the
        tick counters behind it."""
        return {
            "load": bool(self.scheduler is not None and self.scheduler.degraded()),
            "acceptance": bool(
                self._spec_ctl is not None and self._spec_ctl.disabled
            ),
            "skipped_load_ticks": self.spec_skipped_load,
            "skipped_accept_ticks": self.spec_skipped_accept,
        }

    def probe_spec(self, iters: int = 8) -> dict:
        """Measured verify/decode tick costs per tree rung on an idle engine
        (same lock discipline as :meth:`probe_decode`): seconds per plain
        tick, seconds per speculative tick for every (width, depth) rung,
        the cost ratios, and each rung's breakeven accept rate.  Feeds the
        controller's cost table as a side effect."""
        if not self.speculative:
            raise RuntimeError("probe_spec requires a speculative engine")
        deadline = self._clock() + 10.0
        while True:
            self._iter_lock.acquire()
            if self.num_active == 0 and not self._inflight and not self._chunking:
                break
            self._iter_lock.release()
            if self._clock() >= deadline:
                raise RuntimeError("probe_spec requires an idle engine")
            self._sleep(0.01)
        try:
            return self._measure_spec_costs(iters)
        finally:
            self._iter_lock.release()

    def _measure_spec_costs(self, iters: int = 4) -> dict:
        """Time the plain tick and every rung's tree tick back-to-back with
        chained device state (all slots inactive — the verify forward's cost
        is fill-independent at a fixed allocation) and feed the measured
        cost ratios into the controller.  Called from warmup() (pre-start,
        lock-free) and probe_spec() (idle-locked)."""
        from ..ops.speculative import breakeven_accept_rate

        self._refresh_sampling()
        inactive = jnp.zeros((self.max_slots,), bool)

        def time_plain():
            t0 = self._clock()
            for _ in range(iters):
                toks, self._tokens_dev, self._cache, self._rng = self._decode_tick(
                    self.params, self._tokens_dev, self._cache, inactive,
                    self._bt_dev, self._temps_dev, self._top_ps_dev, self._rng,
                )
            np.asarray(toks)
            return (self._clock() - t0) / iters

        def time_rung(rung):
            t0 = self._clock()
            for _ in range(iters):
                toks, n_new, self._tokens_dev, self._history_dev, self._cache, \
                    self._rng = self._spec_ticks[rung](
                        self.params, self._tokens_dev, self._history_dev,
                        self._cache, self._bt_dev, inactive,
                        self._temps_dev, self._top_ps_dev, self._rng,
                    )
            np.asarray(toks)
            return (self._clock() - t0) / iters

        with self._mesh_scope():
            time_plain()  # warm (jit cache is hot after warmup; cheap anyway)
            plain_s = time_plain()
            out = {"plain_tick_s": plain_s, "rungs": {}}
            for rung in self._spec_ctl.rungs:
                time_rung(rung)  # warm
                spec_s = time_rung(rung)
                ratio = spec_s / max(plain_s, 1e-9)
                self._spec_ctl.note_cost(rung, ratio)
                # string keys ("WxK", the spec_rung_accept_emas convention):
                # the result is JSON-able like every other stats surface
                out["rungs"][f"{rung[0]}x{rung[1]}"] = {
                    "width": rung[0],
                    "depth": rung[1],
                    "tick_s": spec_s,
                    "cost_ratio": ratio,
                    "breakeven_accept_rate": breakeven_accept_rate(
                        ratio, rung[1]
                    ),
                }
        return out

    def _issue_tick(self):
        """Dispatch one decode tick without waiting for its result.  The token
        input chains device-to-device from the previous tick (the rng state
        too); the sampled ids stream back asynchronously and are consumed by
        :meth:`_process_tick`."""
        if self._faults is not None:
            # deterministic chaos (serving/faults.py): a thrown device
            # dispatch (engine-fatal -> crash-only restart) or injected
            # latency (heartbeat-age evidence); inert when no injector is set
            self._faults.maybe_raise("tick_raise", "device step")
            delay = self._faults.sleep_s("slow_tick")
            if delay:
                self._sleep(delay)
        self._refresh_sampling()
        if self.speculative:
            if self.scheduler is not None and self.scheduler.degraded():
                # graceful degradation: under queue pressure the tree verify
                # forward is wasted work at low acceptance — fall back to
                # the plain tick (correctness is tick-kind-independent; only
                # the draft source quality suffers when speculation resumes)
                self.spec_skipped_load += 1
            else:
                # acceptance-EMA controller: pick the best rung of the tree
                # ladder, or None when even the narrowest tree cannot pay
                # for its verify forward at the measured acceptance (it
                # keeps probing so a workload shift can re-enable)
                rung = self._spec_ctl.rung()
                if rung is None:
                    self.spec_skipped_accept += 1
                else:
                    self._issue_spec_tick(rung)
                    return
        # (a load- or acceptance-disabled speculative engine falls through to
        # the plain tick: _decode_tick is built at the same decode_steps
        # depth, so the cache/token chaining is identical either way)
        json_live = bool(self._json.any())
        issued_steps = 1 if json_live else self.burst
        if json_live and self.burst > 1:
            # fused ticks are disabled while json_fsm slots are live: the
            # whole batch rides the single-step json program this tick
            self._json_downgraded_ticks += 1
        self._decode_steps_effective = issued_steps
        self._ledger.note_dispatch("tick")
        with self._mesh_scope():
            if json_live:
                toks, last, self._cache, self._rng, self._fsm_states_dev = (
                    self._decode_tick_json(
                        self.params,
                        self._tokens_dev,
                        self._cache,
                        self._active_dev,
                        self._bt_dev,
                        self._temps_dev,
                        self._top_ps_dev,
                        self._rng,
                        self._fsm_states_dev,
                        self._json_dev,
                        self._fsm_next_dev,
                        self._fsm_allowed_dev,
                    )
                )
            else:
                toks, last, self._cache, self._rng = self._decode_tick(
                    self.params,
                    self._tokens_dev,
                    self._cache,
                    self._active_dev,
                    self._bt_dev,
                    self._temps_dev,
                    self._top_ps_dev,
                    self._rng,
                )
        toks.copy_to_host_async()
        self._tokens_dev = last
        self.steps += issued_steps
        self._ticks_issued += 1
        self._kv_frac_sum += self._kv_read_frac()
        live = [
            (i, self._slot_epoch[i]) for i, s in enumerate(self._slots) if s is not None
        ]
        self._inflight.append(
            _TickRef(nxt=toks, slots=live, aux=self._take_aux(), seq=self._ledger.seq)
        )

    def _issue_spec_tick(self, rung: tuple):
        """Dispatch one fused tree-speculative tick at the controller's
        current (width, depth) rung (draft + verify + accept + commit on
        device, chained state — same pipelining discipline as the burst
        tick, but each of its ``decode_steps`` scanned verify steps advances
        a variable 1..depth+1 tokens/slot)."""
        self._ledger.note_dispatch("spec")
        with self._mesh_scope():
            toks, n_new, last, self._history_dev, self._cache, self._rng = (
                self._spec_ticks[rung](
                    self.params,
                    self._tokens_dev,
                    self._history_dev,
                    self._cache,
                    self._bt_dev,
                    self._active_dev,
                    self._temps_dev,
                    self._top_ps_dev,
                    self._rng,
                )
            )
        toks.copy_to_host_async()
        n_new.copy_to_host_async()
        self._tokens_dev = last
        self.steps += self.burst
        self._decode_steps_effective = self.burst
        self.spec_ticks_issued += 1
        self._ticks_issued += 1
        self._kv_frac_sum += 1.0  # the tree verify reads the full cache row
        live = [
            (i, self._slot_epoch[i]) for i, s in enumerate(self._slots) if s is not None
        ]
        self._inflight.append(
            _TickRef(nxt=toks, slots=live, n_new=n_new, spec_rung=rung, seq=self._ledger.seq)
        )

    def _process_tick(self):
        """Consume the oldest in-flight result (blocks until it arrives)."""
        ref = self._inflight.popleft()
        led = self._ledger
        blocked = led.seconds("tick_block")
        with led.span("tick_block", seq=ref.seq):
            vals = np.asarray(ref.nxt)
            if ref.aux is not None:  # same program as `nxt`: already here
                aux = np.asarray(ref.aux).astype(np.int64)
                self._moe_totals = aux if self._moe_totals is None else self._moe_totals + aux
        # the result is here, so every program up to ref.seq has ended on the
        # device: the ledger's device half closes a segment at this stamp
        led.note_marker(ref.seq)
        with led.span("consume"):
            try:
                self._process_tick_inner(ref, vals, led.seconds("tick_block") - blocked)
            finally:
                # deferred stream wakeups: one notify per touched stream per
                # tick (see TokenStream.push_token), flushed even on a
                # mid-tick error so no consumer is left waiting on
                # already-appended events
                if self._stream_notify:
                    for st in self._stream_notify:
                        st.notify_now()
                    self._stream_notify.clear()

    def _process_tick_inner(self, ref: "_TickRef", vals, block_s: float):
        self._ticks_processed += 1
        if self.obs is not None:
            # tick-duration histogram + periodic flight-ring summary — host
            # floats only, no device state (dabtlint DABT104 hot-path root)
            self.obs.on_tick(block_s, len(ref.slots))
        now = self._clock()
        if (
            self._faults is not None
            and ref.slots
            and self._faults.should_fire("nan_logits")
        ):
            # simulate what a NaN'd logits row yields downstream of on-device
            # sampling: garbage ids for ONE slot.  The id validation in
            # _consume_token quarantines that slot; batch-mates keep decoding.
            vals = np.array(vals, copy=True)
            if ref.first:
                vals[ref.offset] = -1
            else:
                vals[..., ref.slots[0][0]] = -1
        if ref.first:
            for j, (slot, epoch) in enumerate(ref.slots):
                s = self._slots[slot]
                if s is None or self._slot_epoch[slot] != epoch:
                    continue
                s.resident_steps += 1
                s.decode_ticks += 1
                self._consume_token(slot, s, int(vals[ref.offset + j]), now)
            return
        if ref.n_new is not None:  # speculative tick: variable tokens/slot
            counts = np.asarray(ref.n_new)  # [N, B] — one row per verify step
            K = ref.spec_rung[1] if ref.spec_rung else self.speculative
            greedy_row_steps = 0
            tick_accepted = 0
            for step in range(counts.shape[0]):  # scanned steps, oldest first
                for slot, epoch in ref.slots:
                    s = self._slots[slot]
                    if s is None or self._slot_epoch[slot] != epoch:
                        continue  # finished by an earlier step; drafts dropped
                    n = int(counts[step, slot])
                    # a verify step advances 1..K+1 tokens in ~one (costlier)
                    # step; charging the tokens committed keeps the per-token
                    # service rate honest on speculative engines too
                    s.resident_steps += max(1, n)
                    if step == 0:
                        s.decode_ticks += 1
                    # greedy rows proposed K drafts and n-1 were accepted
                    if s.request.temperature <= 0:
                        self.spec_drafted += K
                        self.spec_accepted += max(0, n - 1)
                        greedy_row_steps += 1
                        tick_accepted += max(0, n - 1)
                    for k in range(n):
                        if self._consume_token(slot, s, int(vals[step, k, slot]), now):
                            break  # remaining accepted tokens are post-EOS garbage
            if self._spec_ctl is not None and greedy_row_steps:
                # acceptance evidence for the adaptive controller — greedy
                # rows only (sampled rows never accept, by design), credited
                # per verify STEP to the rung that drafted this tick (the
                # rate normalizer is rows x steps x depth)
                self._spec_ctl.note_tick(
                    tick_accepted, K, greedy_row_steps, rung=ref.spec_rung
                )
                if self.obs is not None:
                    self.obs.on_spec_tick(tick_accepted, K * greedy_row_steps)
            return
        for slot, epoch in ref.slots:
            # a fused tick occupies the slot for ALL its steps even when EOS
            # lands mid-tick — charge the full tick so per-token residency
            # (the scheduler's service EMA denominator) reflects the real
            # tick-granularity occupancy
            s = self._slots[slot]
            if s is not None and self._slot_epoch[slot] == epoch:
                s.resident_steps += vals.shape[0]
                s.decode_ticks += 1
        for k in range(vals.shape[0]):  # fused-tick steps, oldest first
            for slot, epoch in ref.slots:
                s = self._slots[slot]
                if s is None or self._slot_epoch[slot] != epoch:
                    continue  # finished by an earlier token; speculation dropped
                self._consume_token(slot, s, int(vals[k, slot]), now)

    def _consume_token(self, slot: int, s: _Slot, tok: int, now: float) -> bool:
        """Append one host-resident sampled id to its slot; returns True when
        the slot is no longer live (finished or quarantined).  Out-of-vocab
        ids — what a NaN'd logits row degenerates to after on-device top-k —
        are request-poison: quarantine this slot, keep the batch alive."""
        if not 0 <= tok < self.cfg.vocab_size:
            self._quarantine(
                slot,
                RequestPoisoned(
                    f"sampled id {tok} outside vocab [0, {self.cfg.vocab_size})"
                    " — NaN/corrupt logits suspected; request quarantined",
                    slot=slot,
                ),
            )
            return True
        s.generated.append(tok)
        self._note_token(s, tok, now)
        if self._should_finish(slot, tok):
            self._finish(slot)
            return True
        return False

    def _note_token(self, s: _Slot, tok: int, now: float) -> None:
        """Per-token host bookkeeping where device results land: TTFT and
        inter-token-latency samples, plus fan-out to the request's token
        stream (a deque append — the id is already host-resident from the
        inflight pipeline, so streaming adds no device sync).  EOS is not
        emitted: ``_finish`` strips it from the result text too."""
        req = s.request
        if req.first_token_at is None:
            req.first_token_at = now
            self._ttft_s.append(now - req.submitted_at)
            if self.obs is not None:
                self.obs.on_first_token(now - req.submitted_at)
        elif s.last_token_at is not None and now > s.last_token_at:
            # tokens of one tick batch share `now` — a zero "gap" between
            # burst/speculative batch-mates would collapse the percentiles to
            # 0; sampling only across batches measures the real host-arrival
            # cadence (per-token ITL ~ gap / tokens-per-tick)
            self._itl_s.append(now - s.last_token_at)
            if self.obs is not None:
                self.obs.on_token_gap(now - s.last_token_at)
        s.last_token_at = now
        if req.stream is not None and tok != self.tokenizer.eos_id:
            # `at=now`: tokens of one tick share the stamp this method was
            # handed — the server measures stream lag from it, no clock read here
            if req.stream.push_token(tok, notify=False, at=now):
                self._stream_notify.add(req.stream)

    def _should_finish(self, slot: int, tok: int) -> bool:
        s = self._slots[slot]
        assert s is not None
        if tok == self.tokenizer.eos_id:
            return True
        if len(s.generated) >= s.request.max_tokens:
            return True
        # cache full -> decode_step_paged freezes the slot; finish as length-limited.
        # Speculative mode leaves N*(K+1)-1 tokens of headroom: one tick's N
        # scanned verify steps commit up to N*(K+1) accepted-path positions,
        # so live rows must always fit them (commit_tree_path_paged docstring) —
        # those last tokens would have been length_limited a tick later
        # anyway.  (N=1 reduces to the historical K-token headroom.)
        headroom = (
            self.burst * (self.speculative + 1) - 1 if self.speculative else 0
        )
        if (
            len(s.request.prompt_ids) + len(s.generated)
            >= self.max_seq_len - headroom
        ):
            return True
        return False

    def _finish(self, slot: int):
        s = self._slots[slot]
        assert s is not None
        self._slots[slot] = None
        self._slot_epoch[slot] += 1  # invalidate this slot's in-flight ticks
        self._json[slot] = False
        self._sampling_dirty = True
        self._free_slot_pages(slot)
        req = s.request
        ids = s.generated
        hit_eos = bool(ids) and ids[-1] == self.tokenizer.eos_id
        if hit_eos:
            ids = ids[:-1]
        now = self._clock()
        try:
            if self._faults is not None:
                self._faults.maybe_raise("detok_raise", "detokenize")
            text = self.tokenizer.decode(ids)
        except Exception as e:
            # request-poison: only THIS request's result text is unrecoverable
            # — fail it and keep serving (the slot is already freed above)
            logger.warning("detokenization failed; quarantining request: %s", e)
            self.poisoned_requests += 1
            if self.obs is not None:
                self.obs.flight.record(
                    "quarantine", trace_id=req.trace_id, error=str(e)
                )
                self.obs.flight.dump("quarantine", trace_id=req.trace_id)
            _safe_resolve(req.future, exc=e)
            return
        detok_s = max(0.0, self._clock() - now)
        # usage.timings (serving/obs.py TIMING_KEYS): consecutive spans from
        # receipt to here, so queue_s + prefill_s is ttft_s and adding
        # decode_s gives latency_s; `now` is where the last token was
        # consumed (s.last_token_at is the stamp of the tick that carried it)
        first = req.first_token_at if req.first_token_at is not None else now
        started = min(req.started_at if req.started_at is not None else first, first)
        received = req.received_at if req.received_at is not None else req.submitted_at
        timings = {
            "recv_mono_s": received,
            "encode_s": max(0.0, req.submitted_at - received),
            "queue_s": started - req.submitted_at,
            "prefill_s": first - started,
            "decode_s": now - first,
            "detok_s": detok_s,
            "prefill_bucket": req.prefill_bucket,
            "wave_rows": req.wave_rows,
            "wave_rows_padded": req.wave_rows_padded,
            "prefill_chunks": s.prefill_chunks,
            "prefix_hit_tokens": req.prefix_hit_tokens,
            "decode_ticks": s.decode_ticks,
            # fused-tick steps the slot sat through; the activation's one
            # (its first token came with the prefill) is not a decode step
            "decode_steps": max(0, s.resident_steps - 1),
        }
        result = GenerationResult(
            token_ids=ids,
            text=text,
            prompt_tokens=len(req.prompt_ids),
            completion_tokens=len(ids),
            length_limited=not hit_eos,
            ttft_s=first - req.submitted_at,
            latency_s=now - req.submitted_at,
            timings=timings,
        )
        if self.scheduler is not None:
            # feed the estimated-wait admission model with true service time:
            # slot residency from prefill start (latency minus queue wait) —
            # first_token_at would omit the prefill, and under long-prompt
            # traffic prefill is the dominant component.  `tokens` is the
            # decode steps the slot actually sat through (fused ticks charge
            # their full N even when EOS lands mid-tick), so the scheduler
            # can model service per TOKEN and a decode_steps=N engine doesn't
            # inflate predicted queue waits by the tick-quantized lookahead
            # lag a short request pays (docs/SCHEDULING.md).  Prefill chunk
            # dispatches count too: piggybacked chunks ride decode ticks, so
            # without the charge a long-prompt request would look like pure
            # decode service and skew predicted waits / Retry-After /
            # autoscaler backlog optimistic.
            self.scheduler.note_service(
                now - (req.started_at or req.first_token_at or now),
                tokens=max(1, s.resident_steps + s.prefill_chunks),
            )
        if self.obs is not None:
            # close the request's trace: the ring keeps `timings` itself, so
            # the deliver span the server stamps later shows up there too
            self.obs.on_finish(req, result)
        _safe_resolve(req.future, result=result)

    def _quarantine(self, slot: int, err: BaseException) -> None:
        """Fail ONE slot's request and free the slot — the epoch bump drops
        its in-flight speculative tokens, and batch-mates keep decoding.  The
        slot's stale cache row is overwritten by the next admission (the same
        discipline ``_finish`` relies on)."""
        s = self._slots[slot]
        if s is None:
            return
        self._slots[slot] = None
        self._slot_epoch[slot] += 1
        self._json[slot] = False
        self._sampling_dirty = True
        self._free_slot_pages(slot)
        self.poisoned_requests += 1
        if self.obs is not None:
            self.obs.flight.record(
                "quarantine",
                trace_id=s.request.trace_id,
                slot=slot,
                error=str(err),
            )
            self.obs.flight.dump("quarantine", trace_id=s.request.trace_id)
        _safe_resolve(s.request.future, exc=err)

    def degraded(self) -> bool:
        """True while the restart circuit is open (submit() fast-fails)."""
        dl = self._degraded_until
        return dl is not None and self._clock() < dl

    def healthy(self) -> bool:
        """The single liveness predicate (any thread): running loop, alive
        thread (None = a single-threaded test/bench driver, not a death),
        circuit closed, fresh heartbeat.  /healthz (via supervision_stats)
        and the multi-replica router's dispatch gate both use THIS — they
        must never disagree about whether a replica is servable."""
        if not self._running or self.degraded():
            return False
        t = self._thread
        if t is not None and not t.is_alive():
            return False
        return (self._clock() - self._beat) < self.heartbeat_degraded_s

    def supervision_stats(self) -> dict:
        """Restart/quarantine/circuit counters + the loop heartbeat — the
        /healthz evidence that distinguishes a live engine from a wedged or
        degraded one (stale-but-green stats were the old failure mode)."""
        now = self._clock()
        age = now - self._beat
        degraded = self.degraded()
        # dead-thread detection: a loop thread that died without running its
        # finally (killed un-pythonically) leaves _running True forever; a
        # None thread is the single-threaded test/bench driver, not a death
        t = self._thread
        thread_alive = t is None or t.is_alive()
        return {
            "running": self._running,
            "thread_alive": thread_alive,
            "healthy": self.healthy(),
            "degraded": degraded,
            "loop_heartbeat_age_s": round(age, 3),
            "heartbeat_degraded_s": self.heartbeat_degraded_s,
            "engine_restarts": self.engine_restarts,
            "poisoned_requests": self.poisoned_requests,
            "circuit_trips": self.circuit_trips,
            "restarted_requests_resubmitted": self.restarted_resubmitted,
            "restarted_requests_failed": self.restarted_failed,
        }

    def _restart(self, err: BaseException):
        """Crash-only restart after an engine-fatal error: rebuild every piece
        of device state from scratch, salvage what is safely retryable, fail
        the rest.

        Salvage rules: queued work is untouched (it never reached the device);
        in-flight requests that have emitted NO tokens yet (mid-prefill,
        awaiting activation — including streams before their first delta) are
        re-submitted at the head of their (class, tenant) queue with their
        original futures, so the client never sees the crash; requests past
        their first token fail cleanly with the error (a non-stream replay
        would double-bill latency, a streamed one would repeat output).  Each
        request survives at most ``max_request_restarts`` restarts.  After
        ``max_restarts`` restarts inside ``restart_window_s`` the circuit
        opens: submit() fast-fails EngineUnavailable until the cooldown."""
        now = self._clock()
        self.engine_restarts += 1
        self._restart_times.append(now)
        if self.obs is not None:
            from .faults import FaultInjected

            if isinstance(err, FaultInjected):
                # the injector fire is its own flight event, distinct from the
                # restart it provoked — a chaos dump names the site directly
                self.obs.flight.record("fault_fire", site=err.site, error=str(err))
            self.obs.flight.record(
                "restart",
                error=f"{type(err).__name__}: {err}",
                engine_restarts=self.engine_restarts,
            )
        salvage: List[_Request] = []
        if self._starting_batch is not None:
            salvage.extend(req for _, req in self._starting_batch)
            self._starting_batch = None
        if self._chunking is not None:
            salvage.append(self._chunking.request)
            self._chunking = None
        self._inflight.clear()
        self._ledger.reset_queue()
        for i, s in enumerate(self._slots):
            if s is not None:
                if s.generated:
                    _safe_resolve(s.request.future, exc=err)
                else:
                    salvage.append(s.request)
            self._slots[i] = None
            self._slot_epoch[i] += 1
        self._json[:] = False
        self._sampling_dirty = True
        # crash-only discipline for the page plane too: every page back on
        # the free list, every block table unallocated, the registry
        # emptied (its pages were part of the poisoned lineage).  The
        # device pool itself is rebuilt below with the rest.  The HOST
        # tier deliberately survives: its numpy copies were taken from a
        # healthy pool (write-through at registration), so warmed
        # sessions re-seed the fresh pool via restore on their next hit
        # instead of paying a cold prefill — the durability contract
        # docs/KV_PAGING.md "Tiered KV" chaos-tests.
        self._kv_pool.reset()
        self._kv_restores_inflight = 0
        self._slot_pages = [[] for _ in range(self.max_slots)]
        self._block_tables[:] = self._kv_sentinel
        self._bt_dirty = True
        if self.obs is not None and self._kv_host is not None:
            hs = self._kv_host.stats()
            self.obs.flight.record(
                "kv_tier_survives_restart",
                host_entries=hs["kv_host_entries"],
                disk_entries=hs["kv_disk_entries"],
            )
        # a failure inside _activate_batch can leave a request both slotted
        # AND in _starting_batch — salvage each request once
        seen: set = set()
        requeue: List[_Request] = []
        for req in salvage:
            if id(req) in seen:
                continue
            seen.add(id(req))
            if req.future.cancelled():
                continue
            if req.restarts >= self.max_request_restarts:
                self.restarted_failed += 1
                if self.obs is not None:
                    self.obs.flight.record(
                        "restart_failed", trace_id=req.trace_id, restarts=req.restarts
                    )
                _safe_resolve(req.future, exc=err)
                continue
            req.restarts += 1
            req.started_at = None
            req.first_token_at = None
            self.restarted_resubmitted += 1
            if self.obs is not None:
                self.obs.flight.record(
                    "resubmit", trace_id=req.trace_id, restarts=req.restarts
                )
            requeue.append(req)
        # head of the queue, class/tenant tags riding on the request —
        # salvaged work must not requeue behind later arrivals.  Head inserts
        # reverse, so insert newest-submitted first: each (class, tenant)
        # queue ends up with its salvaged requests back in FIFO order.
        requeue.sort(key=lambda r: r.submitted_at, reverse=True)
        for req in requeue:
            if self.scheduler is not None:
                self.scheduler.enqueue(req, front=True)
            else:
                self._pending.appendleft(req)
        try:
            # the cache may have been donated into a failed call — rebuild it
            self._cache = self._fresh_cache()
            self._tokens_dev = self._fresh_tokens()
            self._fsm_states_dev = self._fresh_tokens()
            if self.speculative:
                self._history_dev = self._fresh_history()
            # the rng threads through jit outputs, so a failed device call may
            # have poisoned it — rebuild it like the rest of the device state,
            # with a reseed counter so back-to-back failures get distinct streams
            self._reseeds += 1
            self._rng = self._fresh_rng(self.steps + self._reseeds)
        except Exception:
            # Recovery itself failed (seen in practice: the original fault was
            # an OOM and the fresh cache can't allocate either).  Declare the
            # engine dead with an explicit diagnosis instead of letting the
            # raise escape as an anonymous loop crash — either way the loop
            # exits and _shutdown (which drops _running) fails everything
            # queued, so later submits fail fast rather than enqueue forever.
            logger.exception(
                "engine recovery failed; declaring the engine dead"
            )
            self._running = False
            if self.obs is not None:
                self.obs.flight.record("engine_dead", error=f"{type(err).__name__}: {err}")
                self.obs.flight.dump("engine_dead", error=str(err))
            return
        recent = [t for t in self._restart_times if t >= now - self.restart_window_s]
        if len(recent) >= self.max_restarts:
            self.circuit_trips += 1
            self._degraded_until = now + self.degraded_cooldown_s
            if self.obs is not None:
                self.obs.flight.record(
                    "circuit_open",
                    restarts_in_window=len(recent),
                    cooldown_s=self.degraded_cooldown_s,
                )
            logger.error(
                "engine circuit OPEN: %d restarts in %.0fs; degraded for %.1fs "
                "(submit fast-fails EngineUnavailable)",
                len(recent),
                self.restart_window_s,
                self.degraded_cooldown_s,
            )
        if self.obs is not None:
            # the post-mortem artifact: the whole recent-event ring (fault
            # fire, restart, per-request resubmits) as one JSON file — a
            # chaos failure is diagnosable without reproducing it
            self.obs.flight.dump("restart", error=str(err))


class EmbeddingEngine:
    """Batched, coalescing sentence-embedding engine over one encoder model.

    Requests from concurrent callers coalesce into one device batch (bucketed seq
    len, padded batch) — the docs/sec/chip fix for the reference's one-text-at-a-time
    loop.
    """

    def __init__(
        self,
        cfg: EncoderConfig,
        params,
        tokenizer: Tokenizer,
        *,
        max_batch: int = 64,
        seq_buckets: Sequence[int] = (32, 64, 128, 256, 512),
        normalize: bool = False,
        max_queue: int = 1024,
        mesh=None,
    ):
        self.cfg = cfg
        self.params = params
        self.tokenizer = tokenizer
        self.max_batch = max_batch
        self.seq_buckets = tuple(
            b for b in seq_buckets if b <= cfg.max_position_embeddings
        ) or (cfg.max_position_embeddings,)
        self.normalize = normalize
        self.mesh = mesh
        # bounded: an ingestion burst must shed (429 at the server) instead of
        # queueing unboundedly behind a single coalescer thread
        self.max_queue = max(1, int(max_queue))
        self.shed = 0
        self.dropped_cancelled = 0
        self._queue: "queue.Queue[tuple[List[str], Future]]" = queue.Queue(
            maxsize=self.max_queue
        )
        self._running = False
        self._thread: Optional[threading.Thread] = None

        cfg_c, norm_c = cfg, normalize

        def _encode(params, ids, mask):
            return encoder.encode(params, cfg_c, ids, mask, normalize=norm_c)

        if mesh is not None:
            # embeddings come back to host per request — replicate the output
            self._encode = jax.jit(_encode, out_shardings=_replicated(mesh))
        else:
            self._encode = jax.jit(_encode)

    def _mesh_scope(self):
        return mesh_scope(self.mesh)

    def start(self) -> "EmbeddingEngine":
        if self._running:
            return self
        self._running = True
        self._thread = threading.Thread(target=self._loop, daemon=True, name="emb-engine")
        self._thread.start()
        return self

    def stop(self):
        self._running = False
        if self._thread:
            self._thread.join(timeout=10)
            self._thread = None
        err = RuntimeError("embedding engine stopped")
        while True:
            try:
                _, fut = self._queue.get_nowait()
            except queue.Empty:
                break
            _safe_resolve(fut, exc=err)

    def embed_sync(self, texts: Sequence[str]) -> List[List[float]]:
        """Blocking batched embed (used by the engine thread and CLI paths)."""
        out: List[List[float]] = []
        for i in range(0, len(texts), self.max_batch):
            out.extend(self._embed_batch(list(texts[i : i + self.max_batch])))
        return out

    async def embed(self, texts: Sequence[str]) -> List[List[float]]:
        import asyncio

        if not texts:
            return []
        fut: Future = Future()
        try:
            self._queue.put_nowait((list(texts), fut))
        except queue.Full:
            self.shed += 1
            # retry hint: one queue's worth of batches at ~the coalescer's
            # cadence; coarse but monotone in backlog size
            raise SchedulerRejected(
                "embedding queue full", retry_after_s=min(30.0, 1.0 + self.max_queue * 0.01)
            ) from None
        if not self._running:
            self.start()
        return await asyncio.wrap_future(fut)

    # ---------------------------------------------------------------- internal
    def _loop(self):
        while self._running:
            try:
                texts, fut = self._queue.get(timeout=0.05)
            except queue.Empty:
                continue
            # coalesce whatever else is waiting right now; clients that
            # already cancelled are dropped HERE — before their texts pad out
            # a batched forward pass nobody will read
            jobs: List[tuple[List[str], Future]] = []
            total = 0
            if not fut.cancelled():
                jobs.append((texts, fut))
                total = len(texts)
            else:
                self.dropped_cancelled += 1
            while total < self.max_batch:
                try:
                    t2, f2 = self._queue.get_nowait()
                except queue.Empty:
                    break
                if f2.cancelled():
                    self.dropped_cancelled += 1
                    continue
                jobs.append((t2, f2))
                total += len(t2)
            if not jobs:
                continue
            flat = [t for ts, _ in jobs for t in ts]
            try:
                embs = self.embed_sync(flat)
            except Exception as e:
                for _, f in jobs:
                    _safe_resolve(f, exc=e)
                continue
            pos = 0
            for ts, f in jobs:
                _safe_resolve(f, result=embs[pos : pos + len(ts)])
                pos += len(ts)

    def _batch_buckets(self) -> List[int]:
        sizes, b = [], 1
        while b < self.max_batch:
            sizes.append(b)
            b *= 2
        sizes.append(self.max_batch)
        return sizes

    def warmup(self, seq_buckets: Optional[Sequence[int]] = None) -> None:
        """Deterministically compile every (batch-bucket, seq-bucket) encode
        shape so no XLA compile lands on the first oddly-sized live batch."""
        for bucket in seq_buckets if seq_buckets is not None else self.seq_buckets:
            for b in self._batch_buckets():
                ids = np.zeros((b, bucket), np.int32)
                mask = np.ones((b, bucket), np.int32)
                with self._mesh_scope():
                    self._encode(self.params, jnp.asarray(ids), jnp.asarray(mask))

    def _embed_batch(self, texts: List[str]) -> List[List[float]]:
        cap = self.seq_buckets[-1]
        encoded = [self.tokenizer.encode(t)[:cap] for t in texts]
        longest = max((len(e) for e in encoded), default=1)
        bucket = pick_bucket(longest, self.seq_buckets, cap)
        B = len(encoded)
        # pad the batch dim to a power-of-two bucket: every distinct live batch
        # size would otherwise compile its own encode program
        Bp = pick_bucket(B, self._batch_buckets(), self.max_batch)
        ids = np.full((Bp, bucket), self.tokenizer.pad_id, np.int32)
        mask = np.zeros((Bp, bucket), np.int32)
        mask[B:, 0] = 1  # pad rows see one pad token; all-zero masks divide by 0
        for i, e in enumerate(encoded):
            ids[i, : len(e)] = e
            mask[i, : len(e)] = 1
        with self._mesh_scope():
            embs = self._encode(self.params, jnp.asarray(ids), jnp.asarray(mask))
        return np.asarray(embs, np.float32)[:B].tolist()
