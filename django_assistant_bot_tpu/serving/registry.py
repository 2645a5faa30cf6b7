"""Model registry: spec -> loaded engine on the mesh.

Replaces the reference's module-level model lists + lifespan loading loop
(reference: gpu_service/models.py:1-9, gpu_service/main.py:57-70).  Differences:
one process drives the whole slice (no per-worker replicas), params are sharded
onto the mesh at load, and a ``tiny: true`` spec gives every test/dev environment a
random-weights model with the byte tokenizer — no checkpoint assets needed.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Dict, List, Mapping, Optional

import jax

from ..ops.quant import INT4_GROUP_SIZE

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class ModelSpec:
    name: str
    kind: str  # "encoder" | "decoder"
    path: Optional[str] = None  # HF checkpoint dir; None + tiny=True -> random tiny
    checkpoint: Optional[str] = None  # native sharded checkpoint dir (checkpoint.py)
    tiny: bool = False
    dtype: str = "bfloat16"
    max_slots: int = 8
    max_seq_len: Optional[int] = None
    chunk_size: int = 512
    # pipeline depth is lookahead * burst speculative tokens per finished slot —
    # keep these in step with the GenerationEngine defaults
    lookahead: int = 3
    burst: int = 8
    # fused multi-token decode tick depth (docs/QUANT.md roofline notes): one
    # jit call advances every live slot N tokens, amortizing host
    # bookkeeping, sampling-array uploads, and dispatch overhead over N.
    # 0 = inherit `burst` (the historical alias — same machinery); >= 1 is
    # the canonical knob (1 = single-step ticks).
    # json_fsm slots downgrade live ticks to single-step
    # (decode_steps_effective in tick_stats).  Composes with speculative > 0:
    # the spec tick scans decode_steps full draft->verify->commit passes per
    # dispatch, so a greedy slot can advance up to decode_steps * (K+1)
    # tokens per dispatch (docs/SPECULATIVE.md "Spec x fused").  NOTE: a
    # speculative engine defaults to ONE verify pass per tick unless
    # decode_steps is set explicitly — `burst` is not inherited there.
    decode_steps: int = 0
    # chunked prefill piggybacked into the fused decode tick (continuous
    # batching): while one slot is mid-chunked-prefill, each dispatch runs
    # ONE bounded prefill chunk AND the full N-step decode scan for resident
    # slots, so a long admit no longer displaces decode ticks (the
    # `piggyback` segments of tick_stats()["device_queue"] against its
    # `chunk+tick` ones).  Token-identical to the
    # sequential path; False keeps sequential chunking (and one prefill
    # program per bucket to compile: a.x-k1-ep16 boots with it).
    prefill_piggyback: bool = True
    # prefill program shapes (serving/engine.py prefill_shapes: what warm-up
    # compiles and admission dispatches).  Unnamed, the engine derives them:
    # sequence buckets 64, then every 128 (what admits the flash kernel) up to
    # chunk_size; rows 1, 2 and the largest power of two with rows x bucket
    # <= chunk_size (17 programs at chunk 1024 and 8 slots, none larger than
    # one chunk's positions).  prefill_buckets names the buckets instead, for
    # a deployment that wants fewer programs (chunk_size stays the last one);
    # prefill_wave caps the rows of an admission wave (0 = all slots).
    prefill_buckets: Optional[List[int]] = None
    prefill_wave: int = 0
    # fp8 in-dot decode attention: keep the fp8 KV read operand in fp8
    # through the QK/PV dots (per-block scales applied to the f32 partials,
    # mirroring the int4 in-dot discipline) instead of dequantizing to bf16
    # first.  Requires kv_cache_dtype fp8/fp8_e5m2 and the chunked or paged
    # KV read; lossy — see docs/QUANT.md for the measured logit-error bound.
    attn_fp8: bool = False
    # weight-only quantization for decoders: None | "int8" (per-channel) |
    # "int4" (per-group, packed two-per-byte — 0.5 bytes/weight of HBM read;
    # ops/quant.py, docs/QUANT.md) — decode is bandwidth-bound, so bytes are
    # the roofline
    quantize: Optional[str] = None
    # int4 group width along the contraction axis (accuracy knob: smaller
    # groups -> tighter scales -> lower logit error, more scale bytes);
    # default IS ops.quant.INT4_GROUP_SIZE — the single source the synthetic
    # inits also read
    quant_group_size: int = INT4_GROUP_SIZE
    # prefix KV cache: LRU size for shared prompt-prefix K/V (system + RAG
    # context) reused across requests; 0 disables (serving/engine.py)
    prefix_cache: int = 8
    prefix_min_tokens: int = 32
    # HBM budget for pinned prefix K/V (entries LRU-evict past it)
    prefix_cache_max_bytes: int = 1 << 30
    # slot-cache precision: None/"bf16" | "fp8" (e4m3) | "fp8_e5m2" — fp8
    # halves KV bytes (lossy; opt-in per model)
    kv_cache_dtype: Optional[str] = None
    # tree-verified prompt-lookup speculative decoding: up to `spec_width`
    # distinct n-gram continuations of depth `speculative` verified per tick
    # as one ancestor-masked token tree (greedy rows advance up to K+1
    # tokens/tick at identical output; ops/speculative.py,
    # docs/SPECULATIVE.md).  Excludes json_format traffic on this model
    # entry.  An acceptance-EMA controller shrinks the tree and disables
    # speculation below the measured verify/decode breakeven, so sampled or
    # low-acceptance traffic degrades to plain ticks instead of paying the
    # verify forward forever (the r5 regression: 0.24x single-stream at a
    # fixed K=6 / ~5% acceptance).  Watch `spec_accept_rate` /
    # `spec_auto_disabled` in tick_stats.
    speculative: int = 0
    spec_width: int = 4
    # the block the checkpoint must be (``DecoderConfig.arch``: "llama",
    # "mla_moe"); None = whatever the checkpoint says.  A deployment that
    # states it is refused at load, before any program is built, when the
    # checkpoint holds another block.
    arch: Optional[str] = None
    # --- paged KV memory plane (docs/KV_PAGING.md) ---
    # a fixed pool of KV pages + per-request block tables with refcounted
    # copy-on-write prefix sharing and KV-pressure admission; requests reserve
    # ceil((prompt + max_tokens) / page) pages, and the decode read skips the
    # pages past the longest live position.
    # page size in tokens; 0 = the largest of 512, 256, 128, 64, 32, 16, 8
    # that divides max_seq_len at least twice.  A max_seq_len no page divides
    # is refused at load.
    kv_page_size: int = 0
    # pool size in pages; 0 = max_slots * max_seq_len / page_size (a whole
    # context per slot) — raise max_slots past that to bank what short
    # requests leave free as extra concurrency
    kv_pages: int = 0
    # --- tiered KV durability (docs/KV_PAGING.md "Tiered KV") ---
    # host-DRAM byte budget for spilled prefix K/V: > 0 arms the host tier —
    # evicted/registered prefixes keep a host copy, admission restores them
    # into fresh pages instead of re-prefilling, crash-only restarts and
    # scale-down migrations preserve warm sessions.  0 = off (HBM only).
    kv_host_bytes: int = 0
    # optional disk tier under this dir (host-tier evictions demote to .npz
    # files instead of dropping); None also honors DABT_KV_SPILL_DIR
    kv_spill_dir: Optional[str] = None
    # copy every NEW registry entry down to the host tier at registration
    # (one device->host page gather, off the hot path) — what makes warm
    # state survive a crash-only restart; False spills only at eviction
    kv_host_writethrough: bool = True
    # compile every (batch, seq) prefill/activation shape + decode ticks at
    # load time instead of on first traffic (GenerationEngine.warmup) — slower
    # boot, no multi-second serve-time compile stalls.  warmup_json also
    # builds the token FSM + JSON-constrained programs (costs boot time and
    # device memory for the [S, V] tables — enable when json_format is used)
    warmup: bool = False
    warmup_json: bool = False
    max_batch: int = 64
    normalize: bool = False
    num_experts: int = 0
    # --- admission-controlled scheduling (serving/scheduler.py) ---
    # scheduler=False reverts to the unbounded FIFO admission path
    scheduler: bool = True
    # bound on queued-but-not-slotted generation requests; past it /dialog/
    # sheds with 429 + Retry-After instead of queueing unboundedly
    sched_max_queue: int = 256
    # priority-class weights (weighted share, not strict priority) and
    # per-tenant weights within a class; None = scheduler defaults (8:1)
    sched_class_weights: Optional[Mapping[str, float]] = None
    sched_tenant_weights: Optional[Mapping[str, float]] = None
    # estimated-wait admission ceiling in seconds (None disables the test)
    sched_admit_max_wait_s: Optional[float] = 60.0
    # deadline applied when the client sends none (None = no deadline)
    sched_default_deadline_s: Optional[float] = None
    # degradation band: past this queue-pressure fraction, clamp max_tokens
    # and disable speculative decoding; 1.0 disables the band
    sched_degrade_at: float = 0.75
    sched_degrade_max_tokens: int = 256
    # embedding coalescer queue bound (encoder entries): past it /embeddings/
    # sheds with 429 instead of queueing unboundedly
    max_queue: int = 1024
    # --- resilience (serving/faults.py + engine supervision; docs/RESILIENCE.md)
    # deterministic fault injection: site name -> probability or schedule dict
    # (None = also honor the DABT_FAULTS env var; {} = force-off for this model)
    faults: Optional[Mapping[str, Any]] = None
    fault_seed: int = 0
    # crash-only restart circuit: after max_restarts restarts inside
    # restart_window_s the engine goes degraded (submit fast-fails
    # EngineUnavailable -> HTTP 503 + Retry-After) for degraded_cooldown_s
    max_restarts: int = 5
    restart_window_s: float = 60.0
    # bounded exponential backoff between restarts (the hot-spin fix)
    restart_backoff_s: float = 0.05
    restart_backoff_max_s: float = 2.0
    degraded_cooldown_s: float = 30.0
    # /healthz flips to degraded when the engine loop's heartbeat is older
    # than this (a wedged thread no longer reports stale-but-green stats)
    heartbeat_degraded_s: float = 30.0
    # how many restarts one request may ride through via re-submission before
    # it fails (bounds retries of a prompt that deterministically kills the
    # device)
    max_request_restarts: int = 2
    # --- observability (serving/obs.py; docs/OBSERVABILITY.md) ---
    # per-request span traces, /metrics histograms, and the crash flight
    # recorder.  On by default (host-side bookkeeping only; its cost was
    # measured on the chip: PERF.md section 6, PR 24); False leaves no
    # recorder object at all — the engine's loop ledger and usage.timings
    # are not part of it and stay.
    obs: bool = True
    # flight-recorder dump directory (None = DABT_FLIGHT_DIR env, else
    # <tmpdir>/dabt-flight)
    obs_dump_dir: Optional[str] = None
    # --- multi-replica serving (serving/router.py; docs/RESILIENCE.md) ---
    # decoder-only: >1 loads N independently supervised engine replicas (each
    # with its own scheduler, KV page pool, and fault injector — seeds offset
    # per replica) behind an EngineRouter doing health- and prefix-affinity-
    # aware dispatch with per-replica circuit breakers and token-less
    # re-route.  1 = the single-engine path (no router object exists at
    # all).  With a dynamic fleet
    # (max_replicas above this, or autoscale on) this is the INITIAL and
    # MINIMUM size, not a fixed count.
    replicas: int = 1
    # --- mesh-sliced fleet (parallel/slicing.py; docs/MULTICHIP.md) ---------
    # devices per replica: > 0 pins every replica to its OWN disjoint device
    # slice (len(jax.devices()) // replica_devices slices, tensor-parallel
    # INSIDE each slice), so weights, KV pool, and compiled ticks live only
    # on that slice and aggregate tok/s scales with chips — e.g. 8 devices at
    # replica_devices=2 -> up to 4 replicas x TP-2.  Scale-up past the last
    # free slice is an honest `no_capacity` rejection instead of another
    # cache clone on the same chips.  0 (default) = every replica traces onto
    # the registry's one global mesh.
    replica_devices: int = 0
    # ceiling for the dynamic fleet: the router's add_replica/remove_replica
    # (and the autoscaler driving them) keep the fleet within
    # [replicas, max_replicas].  0 = fixed fleet at `replicas` exactly.
    # Any value above `replicas` builds a router (even at replicas=1) so the
    # fleet can grow; validated >= replicas.
    max_replicas: int = 0
    # per-replica router breaker: consecutive replica-shaped failures before
    # the breaker opens, and how long it stays open before one probe request
    router_breaker_threshold: int = 3
    router_breaker_reset_s: float = 10.0
    # --- SLO-driven autoscaling (serving/autoscaler.py; docs/AUTOSCALING.md)
    # closes the control loop over the obs plane: scales the fleet within
    # [replicas, max_replicas] on p95-TTFT SLO burn / shed rate / queue
    # backlog / KV pressure, and engages load-adaptive degradation
    # (max_tokens clamp + speculative decode off) when a replica can't help
    autoscale: bool = False
    autoscale_interval_s: float = 1.0
    autoscale_slo_ttft_p95_s: float = 1.0
    autoscale_up_cooldown_s: float = 5.0
    autoscale_down_cooldown_s: float = 30.0
    autoscale_degrade_max_tokens: int = 256
    # --- cross-process fleet plane (serving/fleet.py; docs/FLEET.md) --------
    # pool role for disaggregated prefill/decode serving: "unified" (default,
    # the single-pool behavior) | "prefill" (chunked prefill only — serves
    # prefill_only handoff requests, pushes finished prefix pages to the
    # decode pool over /fleet/kv/put) | "decode" (admits via warm-prefix
    # restore; long prefill sheds with reason "pool_role" so the FleetRouter
    # hands it off).  A prefill pool with kv_host_bytes=0 gets a default
    # host-tier budget — finished prefixes need somewhere durable to live
    # before they ship.
    pool: str = "unified"
    # decode-pool autoscaling signal: scale up when p95 inter-token latency
    # burns past this (the decode pool's SLO is ITL, not TTFT — TTFT lives
    # in the prefill pool); also read by unified fleets when set via config
    autoscale_slo_itl_p95_s: float = 0.25

    @classmethod
    def from_dict(cls, name: str, d: Mapping[str, Any]) -> "ModelSpec":
        d = dict(d)
        # deprecation shim: the r4 prefix-LRU knob name keeps working, mapped
        # onto the page-pool prefix registry (same budget semantics)
        if "prefix_cache_size" in d:
            val = d.pop("prefix_cache_size")
            if "prefix_cache" not in d:
                logger.warning(
                    "model %s: 'prefix_cache_size' is deprecated — mapped onto "
                    "the paged prefix registry ('prefix_cache'); the byte "
                    "budget knob is 'prefix_cache_max_bytes' as before",
                    name,
                )
                d["prefix_cache"] = val
        d.pop("name", None)
        # an operator's file: name the key and the model, not the dataclass
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(k for k in d if k not in known)
        if unknown:
            raise ValueError(
                f"model {name}: unknown setting(s) {', '.join(map(repr, unknown))} "
                "(ModelSpec in serving/registry.py lists what a model entry may name)"
            )
        return cls(name=name, **d)


class ModelRegistry:
    """Loads and owns engines; lookup is lowercase (as the reference's dicts are)."""

    def __init__(self, specs: Optional[Mapping[str, ModelSpec]] = None, mesh=None):
        from ..parallel import get_mesh

        self.mesh = mesh if mesh is not None else get_mesh()
        self.specs: Dict[str, ModelSpec] = {}
        self.embedders: Dict[str, Any] = {}
        self.generators: Dict[str, Any] = {}
        # SLO autoscalers by model name (autoscale=true decoder entries):
        # /healthz and /metrics read their stats; stop() halts them FIRST so
        # no scale decision races engine shutdown
        self.autoscalers: Dict[str, Any] = {}
        # set-up wall times by model name: {"load_s", "warmup_s"} — checkpoint
        # read + device placement, and the warm-up compiles (0.0 when the spec
        # asks for none).  /healthz reports them; they are set-up numbers,
        # never rates.
        self.boot_s: Dict[str, Dict[str, float]] = {}
        for spec in (specs or {}).values():
            self.load(spec)

    @classmethod
    def from_config(cls, config: Mapping[str, Any], mesh=None) -> "ModelRegistry":
        """``config`` maps model name -> spec dict (parsed from TOML/JSON)."""
        specs = {
            name.lower(): ModelSpec.from_dict(name.lower(), d)
            for name, d in config.items()
        }
        return cls(specs, mesh=mesh)

    def load(self, spec: ModelSpec):
        import jax.numpy as jnp

        from ..models import DecoderConfig, EncoderConfig, encoder, held_params, llama, module_for
        from ..models.hf_loader import load_decoder, load_encoder
        from ..parallel import shard_pytree
        from .engine import EmbeddingEngine, GenerationEngine
        from .tokenizer import load_tokenizer

        name = spec.name.lower()
        dtype = getattr(jnp, spec.dtype)
        # validate config knobs BEFORE the (potentially multi-GB) weight load
        if spec.quantize and spec.kind == "encoder":
            raise ValueError(
                f"model {name}: quantize={spec.quantize!r} is decoder-only "
                "(encoders are compute-bound, not weight-read-bound)"
            )
        if spec.quantize and spec.quantize not in ("int8", "int4"):
            raise ValueError(f"model {name}: unknown quantize={spec.quantize!r}")
        if spec.quant_group_size < 2 or spec.quant_group_size % 2:
            raise ValueError(
                f"model {name}: quant_group_size must be an even int >= 2 "
                f"(got {spec.quant_group_size})"
            )
        if spec.decode_steps < 0:
            raise ValueError(
                f"model {name}: decode_steps must be >= 1 (or 0 = inherit "
                f"burst); got {spec.decode_steps}"
            )
        if spec.decode_steps and spec.kind == "encoder":
            raise ValueError(f"model {name}: decode_steps is decoder-only")
        if spec.warmup_json and spec.kind == "encoder":
            raise ValueError(f"model {name}: warmup_json is decoder-only")
        if spec.speculative and spec.kind == "encoder":
            raise ValueError(f"model {name}: speculative is decoder-only")
        if spec.speculative and spec.warmup_json:
            raise ValueError(
                f"model {name}: speculative excludes JSON-constrained decoding "
                "(the token FSM is sequential); use a separate model entry"
            )
        from .engine import KV_CACHE_DTYPES

        if spec.kv_cache_dtype is not None and spec.kind == "encoder":
            raise ValueError(
                f"model {name}: kv_cache_dtype is decoder-only (encoders have "
                "no KV cache)"
            )
        if spec.kv_cache_dtype not in KV_CACHE_DTYPES:
            raise ValueError(
                f"model {name}: unknown kv_cache_dtype={spec.kv_cache_dtype!r}; "
                f"expected one of {sorted(k for k in KV_CACHE_DTYPES if k)}"
            )
        if spec.attn_fp8 and spec.kind == "encoder":
            raise ValueError(f"model {name}: attn_fp8 is decoder-only")
        if spec.attn_fp8 and spec.kv_cache_dtype not in ("fp8", "fp8_e5m2"):
            raise ValueError(
                f"model {name}: attn_fp8 requires an fp8 KV cache "
                f"(kv_cache_dtype='fp8' or 'fp8_e5m2', got "
                f"{spec.kv_cache_dtype!r}) — the in-dot scheme consumes the "
                "stored fp8 operand directly (docs/QUANT.md)"
            )
        if spec.kv_host_bytes < 0:
            raise ValueError(f"model {name}: kv_host_bytes must be >= 0")
        if (spec.kv_host_bytes or spec.kv_spill_dir) and spec.kind == "encoder":
            raise ValueError(
                f"model {name}: kv_host_bytes/kv_spill_dir are decoder-only "
                "(encoders have no KV cache)"
            )
        if spec.replicas < 1:
            raise ValueError(f"model {name}: replicas must be >= 1")
        if spec.replicas > 1 and spec.kind == "encoder":
            raise ValueError(
                f"model {name}: replicas is decoder-only (the embedding "
                "coalescer already batches across callers in one engine)"
            )
        if spec.max_replicas and spec.max_replicas < spec.replicas:
            raise ValueError(
                f"model {name}: max_replicas ({spec.max_replicas}) must be "
                f">= replicas ({spec.replicas} — the initial/min fleet size)"
            )
        if (spec.max_replicas or spec.autoscale) and spec.kind == "encoder":
            raise ValueError(
                f"model {name}: max_replicas/autoscale are decoder-only"
            )
        if spec.replica_devices < 0:
            raise ValueError(f"model {name}: replica_devices must be >= 0")
        if spec.replica_devices and spec.kind == "encoder":
            raise ValueError(
                f"model {name}: replica_devices is decoder-only (the "
                "embedding coalescer runs one engine on the global mesh)"
            )
        if spec.pool not in ("unified", "prefill", "decode"):
            raise ValueError(
                f"model {name}: pool must be 'unified', 'prefill' or "
                f"'decode' (got {spec.pool!r})"
            )
        if spec.pool != "unified" and spec.kind == "encoder":
            raise ValueError(f"model {name}: pool is decoder-only")
        if spec.pool == "prefill" and not spec.kv_host_bytes:
            # finished prefill pages must survive in the host tier long
            # enough to ship to the decode pool; a prefill pool with no
            # tier would prefill into HBM and have nothing to hand off
            logger.info(
                "model %s: pool='prefill' with kv_host_bytes=0 — defaulting "
                "the host KV tier to 256 MiB so handoff pages have a home",
                name,
            )
            spec.kv_host_bytes = 1 << 28
        tokenizer_path = spec.path
        logger.info("loading model %r (%s, tiny=%s)", name, spec.kind, spec.tiny)
        t_start = time.monotonic()
        warmup_s = 0.0

        if spec.checkpoint:
            from ..checkpoint import load_model, read_manifest

            if spec.kind == "decoder":
                # what the checkpoint's block does not implement is refused from
                # its manifest alone, before gigabytes of weights are read
                ck_cfg = read_manifest(spec.checkpoint)["meta"].get("config", {})
                if ck_cfg.get("latent_moe"):
                    from ..models import mla_moe

                    try:
                        mla_moe.check_serving(
                            speculative=spec.speculative,
                            prefix_cache=spec.prefix_cache, kv_cache_dtype=spec.kv_cache_dtype,
                            attn_fp8=spec.attn_fp8, quantize=spec.quantize,
                            kv_host_tier=bool(spec.kv_host_bytes or spec.kv_spill_dir),
                        )
                    except ValueError as e:
                        raise ValueError(f"model {name}: {e}") from None
            kind, _cfg, _params, _meta = load_model(spec.checkpoint, dtype=dtype)
            if kind != spec.kind:
                raise ValueError(
                    f"model {name}: checkpoint is a {kind}, spec says {spec.kind}"
                )
            if spec.arch and getattr(_cfg, "arch", None) != spec.arch:
                raise ValueError(
                    f"model {name}: checkpoint holds a {getattr(_cfg, 'arch', kind)!r} block, "
                    f"spec says arch={spec.arch!r}"
                )
            tokenizer_path = tokenizer_path or _meta.get("tokenizer")
        tokenizer = load_tokenizer(tokenizer_path)

        if spec.kind == "encoder":
            if spec.checkpoint:
                cfg, params = _cfg, _params
            elif spec.path:
                cfg, params = load_encoder(spec.path, dtype=dtype)
            elif spec.tiny:
                cfg = EncoderConfig.tiny()
                params = encoder.init(cfg, jax.random.key(0))
            else:
                raise ValueError(f"model {name}: need path, checkpoint, or tiny=true")
            with self.mesh:
                params = shard_pytree(params, encoder.logical_axes(cfg), self.mesh)
            jax.block_until_ready(params)
            eng = EmbeddingEngine(
                cfg,
                params,
                tokenizer,
                max_batch=spec.max_batch,
                normalize=spec.normalize,
                max_queue=spec.max_queue,
                mesh=self.mesh,
            )
            if spec.warmup:
                t_warm = time.monotonic()
                eng.warmup()
                warmup_s = time.monotonic() - t_warm
            eng.start()
            self.embedders[name] = eng
        elif spec.kind == "decoder":
            if spec.checkpoint:
                cfg, params = _cfg, _params
            elif spec.path:
                cfg, params = load_decoder(spec.path, dtype=dtype)
            elif spec.tiny:
                cfg = DecoderConfig.tiny(num_experts=spec.num_experts)
                if spec.max_seq_len and spec.max_seq_len > cfg.max_seq_len:
                    # synthetic tiny models have no pretrained context limit:
                    # let the spec RAISE it (the engine clamps max_seq_len to
                    # cfg.max_seq_len, so without this a tiny model is stuck
                    # at the factory's 256 no matter what the config asks for)
                    cfg = dataclasses.replace(
                        cfg, max_seq_len=int(spec.max_seq_len)
                    )
                params = llama.init(cfg, jax.random.key(0))
            else:
                raise ValueError(f"model {name}: need path, checkpoint, or tiny=true")
            # the checkpoint's tree -> the form the block holds on the device
            # (models.held_params): once, here, before anything is placed
            params = held_params(cfg, params)
            if spec.quantize in ("int8", "int4"):
                # quantize BEFORE device placement: the packed integers are
                # what transfers and shards (QTensor/QTensor4 ride the same
                # sharding tree as a pytree prefix)
                from ..ops.quant import quantize_decoder_params, weight_bits

                bits = weight_bits(params)
                want = {"int8": 8, "int4": 4}[spec.quantize]
                if bits != 16:
                    # a converted checkpoint arrives pre-quantized: feeding
                    # QTensor leaves back through the quantizer dies with an
                    # opaque numpy shape error — match is a no-op, mismatch
                    # is a config error worth naming
                    if bits == want:
                        logger.info(
                            "model %s: checkpoint is already %s-quantized; "
                            "quantize=%r is a no-op",
                            name,
                            spec.quantize,
                            spec.quantize,
                        )
                        if want == 4:
                            # the accuracy knob cannot re-group a packed
                            # checkpoint — say so instead of silently serving
                            # a different group size than the spec believes
                            from ..ops.quant import QTensor4

                            ck_groups = {
                                leaf.group_size
                                for leaf in params["layers"].values()
                                if isinstance(leaf, QTensor4)
                            }
                            if ck_groups and ck_groups != {
                                spec.quant_group_size
                            }:
                                logger.warning(
                                    "model %s: quant_group_size=%d has no "
                                    "effect — the checkpoint was packed at "
                                    "group size(s) %s; re-convert to change "
                                    "it",
                                    name,
                                    spec.quant_group_size,
                                    sorted(ck_groups),
                                )
                    else:
                        raise ValueError(
                            f"model {name}: checkpoint is already quantized "
                            f"(int{bits}) but the spec asks for "
                            f"quantize={spec.quantize!r}; re-convert the "
                            "checkpoint in the desired format or drop the knob"
                        )
                else:
                    params = quantize_decoder_params(
                        params,
                        fmt=spec.quantize,
                        group_size=spec.quant_group_size,
                    )
            # --- device placement (docs/MULTICHIP.md weight-placement
            # contract) -------------------------------------------------
            # Global-mesh path: ONE device_put shards the weights over the
            # whole mesh and every replica shares them read-only.  Sliced
            # path (replica_devices > 0): `params` stays the SHARED HOST
            # COPY — each replica's build does its own one-time device_put
            # onto its slice, so a replica's weights live ONLY on its slice
            # and a scale-up transfers exactly one slice's worth of bytes.
            planner = None
            if spec.replica_devices:
                import numpy as _np

                from ..parallel import MeshPlanner

                mesh_devices = list(_np.asarray(self.mesh.devices).flatten())
                if spec.replica_devices > len(mesh_devices):
                    raise ValueError(
                        f"model {name}: replica_devices="
                        f"{spec.replica_devices} exceeds the mesh's "
                        f"{len(mesh_devices)} device(s)"
                    )
                planner = MeshPlanner(
                    spec.replica_devices, devices=mesh_devices
                )
                if spec.replicas > planner.n_slices:
                    raise ValueError(
                        f"model {name}: replicas={spec.replicas} needs more "
                        f"device slices than the host has "
                        f"({planner.n_slices} slice(s) of "
                        f"{spec.replica_devices} device(s))"
                    )
                logical_tree = module_for(cfg).logical_axes(cfg)
                host_params = params
            else:
                with self.mesh:
                    params = shard_pytree(
                        params, module_for(cfg).logical_axes(cfg), self.mesh
                    )
                jax.block_until_ready(params)
            from .faults import FaultInjector

            def _build_sched():
                if not spec.scheduler:
                    return None
                from .scheduler import RequestScheduler, SchedulerConfig

                sched = RequestScheduler(
                    SchedulerConfig.from_knobs(
                        max_queue=spec.sched_max_queue,
                        class_weights=spec.sched_class_weights,
                        tenant_weights=spec.sched_tenant_weights,
                        degrade_at=spec.sched_degrade_at,
                        degrade_max_tokens=spec.sched_degrade_max_tokens,
                    )
                )
                # these two are None-able knobs (None is meaningful: "off"),
                # so they bypass the None-dropping from_knobs filter
                sched.cfg.admit_max_wait_s = spec.sched_admit_max_wait_s
                sched.cfg.default_deadline_s = spec.sched_default_deadline_s
                return sched

            def _build_faults(seed_offset: int = 0):
                # explicit spec wins ({} forces off); otherwise the env gate
                # (DABT_FAULTS / DABT_FAULT_SEED) applies — a chaos session
                # can target a running config without editing it.  Replicas
                # offset the seed so probabilistic sites fire DIFFERENT
                # (deterministic) patterns per replica instead of N copies of
                # one pattern failing in lockstep.
                if spec.faults is not None:
                    return FaultInjector.from_spec(
                        spec.faults, seed=spec.fault_seed + seed_offset
                    )
                return FaultInjector.from_env(seed_offset=seed_offset)

            # dynamic fleet: max_replicas above the initial size (or the
            # autoscaler on) needs the router's add/remove surface even when
            # the fleet STARTS at one replica
            max_replicas = spec.max_replicas or spec.replicas
            fleet = spec.replicas > 1 or max_replicas > spec.replicas or spec.autoscale

            def _build_engine(i: int):
                """Replica ``i`` from the SHARED weight tree — used for the
                initial fleet and as the router's scale-up factory (the
                autoscaler spawns replicas through this exact closure, so a
                scaled-up replica is indistinguishable from a boot-time one).

                With slicing on, the replica first acquires its own device
                slice from the planner (NoCapacity propagates — the router/
                autoscaler turn it into the honest `no_capacity` decision)
                and places the shared host weights onto THAT slice only."""
                nonlocal warmup_s
                rep_slice = None
                rep_mesh = self.mesh
                rep_params = params
                if planner is not None:
                    rep_slice = planner.acquire()
                    rep_mesh = rep_slice.mesh
                    try:
                        with rep_mesh:
                            rep_params = shard_pytree(
                                host_params, logical_tree, rep_mesh
                            )
                    except Exception:
                        planner.release(rep_slice)
                        raise
                try:
                    eng = _construct(i, rep_params, rep_mesh)
                except Exception:
                    if rep_slice is not None:
                        planner.release(rep_slice)
                    raise
                if rep_slice is not None:
                    eng.slice_id = rep_slice.slice_id
                    # detach epilogue hook: the router releases the slice
                    # AFTER the replica is stopped (idempotent in the planner)
                    eng.release_slice = (
                        lambda _p=planner, _s=rep_slice: _p.release(_s)
                    )
                try:
                    if spec.warmup or spec.warmup_json:
                        # replicas on one mesh share compiled programs;
                        # sliced TPU replicas each compile fresh (the planner
                        # turns the persistent cache off: parallel/slicing.py)
                        t_warm = time.monotonic()
                        eng.warmup(json=spec.warmup_json)
                        warmup_s += time.monotonic() - t_warm
                    eng.start()
                except Exception:
                    # a failed warmup/start (transient compile error, OOM)
                    # must not LEAK the slice: this engine never joins the
                    # fleet, so the detach epilogue will never release it —
                    # a leaked slice would shrink hardware capacity for the
                    # life of the process (every later scale-up NoCapacity
                    # on free chips)
                    try:
                        eng.stop(drain_timeout_s=1.0)
                    except Exception:  # pragma: no cover - teardown belt
                        logger.exception(
                            "model %s: half-built replica stop failed", name
                        )
                    if rep_slice is not None:
                        planner.release(rep_slice)
                    raise
                return eng

            def _construct(i: int, rep_params, rep_mesh):
                return GenerationEngine(
                    cfg,
                    rep_params,  # read-only: shared fleet-wide (global mesh)
                    tokenizer,  # or this slice's exclusive copy (sliced)
                    max_slots=spec.max_slots,
                    max_seq_len=spec.max_seq_len,
                    chunk_size=spec.chunk_size,
                    lookahead=spec.lookahead,
                    burst=spec.burst,
                    decode_steps=spec.decode_steps or None,
                    prefix_cache_size=spec.prefix_cache,
                    prefix_min_tokens=spec.prefix_min_tokens,
                    prefix_cache_max_bytes=spec.prefix_cache_max_bytes,
                    kv_cache_dtype=spec.kv_cache_dtype,
                    speculative=spec.speculative,
                    spec_width=spec.spec_width,
                    prefill_piggyback=spec.prefill_piggyback,
                    prefill_wave=spec.prefill_wave,
                    prefill_buckets=spec.prefill_buckets,
                    attn_fp8=spec.attn_fp8,
                    kv_page_size=spec.kv_page_size,
                    kv_pages=spec.kv_pages,
                    kv_host_bytes=spec.kv_host_bytes,
                    kv_spill_dir=spec.kv_spill_dir,
                    kv_host_writethrough=spec.kv_host_writethrough,
                    scheduler=_build_sched(),
                    faults=_build_faults(i),
                    max_restarts=spec.max_restarts,
                    restart_window_s=spec.restart_window_s,
                    restart_backoff_s=spec.restart_backoff_s,
                    restart_backoff_max_s=spec.restart_backoff_max_s,
                    degraded_cooldown_s=spec.degraded_cooldown_s,
                    heartbeat_degraded_s=spec.heartbeat_degraded_s,
                    max_request_restarts=spec.max_request_restarts,
                    # replica-qualified name: flight-recorder artifacts and
                    # /metrics `replica` labels match the router's names
                    name=f"{name}/r{i}" if fleet else name,
                    obs=spec.obs,
                    obs_dump_dir=spec.obs_dump_dir,
                    mesh=rep_mesh,
                )

            engines = [_build_engine(i) for i in range(spec.replicas)]
            if not fleet:
                # single fixed engine, no router object
                self.generators[name] = engines[0]
            else:
                from .router import EngineRouter

                router = EngineRouter(
                    engines,
                    names=[f"{name}/r{i}" for i in range(spec.replicas)],
                    breaker_threshold=spec.router_breaker_threshold,
                    breaker_reset_s=spec.router_breaker_reset_s,
                    max_reroutes=spec.max_request_restarts,
                    faults=_build_faults(len(engines)),
                    replica_factory=_build_engine,
                )
                # slice topology surface: /healthz + /metrics read free/total
                # slice gauges off the router (None on an unsliced fleet)
                router.mesh_planner = planner
                self.generators[name] = router
                if spec.autoscale:
                    from .autoscaler import AutoscalerConfig, SLOAutoscaler

                    self.autoscalers[name] = SLOAutoscaler(
                        router,
                        AutoscalerConfig(
                            min_replicas=spec.replicas,
                            max_replicas=max_replicas,
                            interval_s=spec.autoscale_interval_s,
                            slo_ttft_p95_s=spec.autoscale_slo_ttft_p95_s,
                            up_cooldown_s=spec.autoscale_up_cooldown_s,
                            down_cooldown_s=spec.autoscale_down_cooldown_s,
                            degrade_max_tokens=spec.autoscale_degrade_max_tokens,
                            # decode pools scale on their OWN signal: p95
                            # inter-token latency, not TTFT (docs/FLEET.md)
                            up_itl_p95_s=(
                                spec.autoscale_slo_itl_p95_s
                                if spec.pool == "decode"
                                else None
                            ),
                        ),
                        name=f"{name}-autoscaler",
                    ).start()
        else:
            raise ValueError(f"model {name}: unknown kind {spec.kind!r}")
        self.specs[name] = spec
        total_s = time.monotonic() - t_start
        self.boot_s[name] = {
            "load_s": round(total_s - warmup_s, 3),
            "warmup_s": round(warmup_s, 3),
        }
        logger.info(
            "model %r ready: load %.1fs, warmup %.1fs",
            name, total_s - warmup_s, warmup_s,
        )

    def stop(self):
        # autoscalers first: a scale decision must not race engine shutdown
        for asc in self.autoscalers.values():
            asc.stop()
        for eng in list(self.embedders.values()) + list(self.generators.values()):
            eng.stop()

    def idle(self) -> bool:
        """No engine holds accepted-but-unfinished work (every generator —
        or every replica behind a router — idle, every embedder queue empty).
        The server's SIGTERM graceful drain polls this until the deadline."""
        for eng in self.generators.values():
            fn = getattr(eng, "idle", None)
            if callable(fn) and not fn():
                return False
        for eng in self.embedders.values():
            if not eng._queue.empty():
                return False
        return True

    def get_embedder(self, model: str):
        return self.embedders.get(model.lower())

    def get_generator(self, model: str):
        return self.generators.get(model.lower())
