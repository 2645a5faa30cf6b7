"""``serve`` — run the TPU model server (replaces the reference's
``gunicorn -c gunicorn_conf.py main:app`` gpu_service entry)."""

from __future__ import annotations

import logging


def _probe_engine_factory(spec, cfg):
    """One weight load for ``serve --autotune --measure``; the returned
    closure builds a throwaway probe engine per ledger candidate dict
    ({kv_page_size, max_slots, decode_steps}) sharing those weights.
    Probe engines carry the spec's quantize/KV/speculative knobs so the
    measured step cost is the cost of the program the operator would run."""
    import jax

    from ..models import llama
    from ..serving.engine import GenerationEngine
    from ..serving.tokenizer import load_tokenizer

    if spec.checkpoint:
        from ..checkpoint import load_model

        kind, cfg, params, meta = load_model(spec.checkpoint)
        if kind != "decoder":
            raise ValueError(f"{spec.name}: checkpoint is a {kind}")
        tok = load_tokenizer(spec.path or meta.get("tokenizer"))
    elif spec.path:
        from ..models.hf_loader import load_decoder

        cfg, params = load_decoder(spec.path)
        tok = load_tokenizer(spec.path)
    else:  # tiny (validated by the caller's config resolution)
        params = llama.init(cfg, jax.random.key(0))
        tok = load_tokenizer(None)
    if spec.quantize in ("int8", "int4"):
        from ..ops.quant import quantize_decoder_params, weight_bits

        if weight_bits(params) == 16:
            params = quantize_decoder_params(
                params, fmt=spec.quantize, group_size=spec.quant_group_size
            )

    def factory(cand):
        return GenerationEngine(
            cfg,
            params,
            tok,
            max_slots=int(cand["max_slots"]),
            max_seq_len=spec.max_seq_len,
            chunk_size=spec.chunk_size,
            decode_steps=int(cand["decode_steps"]),
            kv_cache_dtype=spec.kv_cache_dtype,
            speculative=spec.speculative,
            spec_width=spec.spec_width,
            prefill_piggyback=spec.prefill_piggyback,
            attn_fp8=spec.attn_fp8,
            kv_page_size=int(cand["kv_page_size"]),
            prefix_cache_size=0,
            scheduler=None,
            obs=False,
            name=f"{spec.name}/probe",
        )

    return factory


def add_parser(sub):
    p = sub.add_parser("serve", help="run the TPU model server")
    p.add_argument("--config", help="TOML/JSON model config file", default=None)
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=11435)
    p.add_argument(
        "--tiny",
        action="store_true",
        help="serve tiny random models (dev/testing without checkpoints)",
    )
    p.add_argument(
        "--warmup",
        action="store_true",
        help="compile the prefill/decode/embed shapes before accepting traffic "
        "(JSON-constrained programs compile on first json request unless "
        "warmup_json is set per model in the config file)",
    )
    p.add_argument(
        "--autotune",
        action="store_true",
        help="byte-ledger geometry autotune (docs/QUANT.md): for every "
        "decoder entry, sweep kv_page_size x max_slots x decode_steps "
        "through the decode byte ledger and print the recommended config "
        "as JSON, then exit without starting the server.  Pure config "
        "arithmetic — no weights load.  Standalone twin: tools/autotune.py",
    )
    p.add_argument(
        "--autotune-hbm-gb",
        type=float,
        default=None,
        metavar="GB",
        help="per-DEVICE HBM budget for --autotune (default 16.0).  The "
        "effective budget is per-device x one replica's devices: its slice "
        "(--replica-devices / replica_devices in the config) on a sliced "
        "fleet, the whole host otherwise — so the recommendation matches "
        "what a sliced replica can actually hold (docs/MULTICHIP.md)",
    )
    p.add_argument(
        "--autotune-hbm-gbps",
        type=float,
        default=None,
        metavar="GBPS",
        help="assumed achieved HBM bandwidth for --autotune (default 819; "
        "feed the bench's measured decode_hbm_gbps for a calibrated sweep)",
    )
    p.add_argument(
        "--measure",
        action="store_true",
        help="with --autotune: load weights once per decoder, compile and "
        "micro-probe the top-k ledger-ranked candidates on the live device "
        "(probe_decode: idle-locked burst ticks, seconds/step) and re-rank "
        "by measured step time.  The report keeps both rankings so "
        "ledger-vs-measured disagreement is a visible artifact",
    )
    p.add_argument(
        "--measure-top-k",
        type=int,
        default=3,
        metavar="K",
        help="how many ledger-ranked candidates --measure probes (default 3; "
        "each costs one engine construction + tick compile)",
    )
    p.add_argument(
        "--replicas",
        type=int,
        default=None,
        metavar="N",
        help="data-parallel engine replicas per decoder behind a health- and "
        "prefix-affinity-aware router with per-replica circuit breakers and "
        "token-less re-route (serving/router.py; docs/RESILIENCE.md).  1 "
        "(the default) keeps the single-engine path byte-identical to "
        "before — no router object exists at all",
    )
    p.add_argument(
        "--replica-devices",
        type=int,
        default=None,
        metavar="N",
        help="mesh-sliced fleet (docs/MULTICHIP.md): pin every decoder "
        "replica to its OWN disjoint slice of N devices (tensor-parallel "
        "inside the slice), so weights, KV pool, and compiled ticks live "
        "only on that slice and aggregate tok/s scales with chips — e.g. 8 "
        "devices at N=2 serve up to 4 replicas x TP-2.  Scale-up past the "
        "last free slice is an honest no_capacity rejection.  0/unset = all "
        "replicas share the one global mesh (the pre-slicing behavior)",
    )
    p.add_argument(
        "--autoscale",
        action="store_true",
        help="SLO-driven autoscaling for every decoder (serving/autoscaler.py; "
        "docs/AUTOSCALING.md): a controller thread scales the replica fleet "
        "within [--min-replicas, --max-replicas] on p95-TTFT SLO burn, shed "
        "rate, queue backlog and KV pressure, and engages load-adaptive "
        "degradation (max_tokens clamp + speculative decode off) when a "
        "replica can't help.  Every decision is a dabt_autoscale_* metric "
        "and a flight-recorder event",
    )
    p.add_argument(
        "--min-replicas",
        type=int,
        default=None,
        metavar="N",
        help="initial/minimum replica count per decoder for the dynamic "
        "fleet (alias for replicas when autoscaling; the autoscaler never "
        "drains below it)",
    )
    p.add_argument(
        "--max-replicas",
        type=int,
        default=None,
        metavar="N",
        help="replica-count ceiling per decoder (>= --min-replicas); the "
        "router's add_replica spawns up to here from the shared weights",
    )
    p.add_argument(
        "--slo-ttft-p95-s",
        type=float,
        default=None,
        metavar="S",
        help="the p95 time-to-first-token SLO the autoscaler defends "
        "(default 1.0); p95/SLO is the burn signal driving scale-up and "
        "degradation",
    )
    p.add_argument(
        "--pool",
        choices=("unified", "prefill", "decode"),
        default=None,
        help="fleet pool role for every decoder (docs/FLEET.md): 'prefill' "
        "serves chunked prefill only and pushes finished prefix pages to "
        "the decode pool over /fleet/kv/put; 'decode' admits via warm-prefix "
        "restore and sheds long prefill with reason 'pool_role' so the "
        "FleetRouter hands it off; 'unified' (default) serves both",
    )
    p.add_argument(
        "--fleet-name",
        default=None,
        metavar="NAME",
        help="this process's name on the fleet wire (defaults to proc-<pid>; "
        "also honors DABT_FLEET_SELF)",
    )
    p.add_argument(
        "--fleet-peers",
        default=None,
        metavar="NAME=URL,...",
        help="comma-separated fleet peers, e.g. "
        "'a=http://10.0.0.1:11435,b=http://10.0.0.2:11435' — /fleet/healthz "
        "probes them and degrades the fleet status when one is unreachable "
        "(also honors DABT_FLEET_PEERS; docs/FLEET.md)",
    )
    p.add_argument(
        "--decode-max-prefill-tokens",
        type=int,
        default=None,
        metavar="N",
        help="decode-pool admission bound: the longest un-restorable suffix a "
        "decode process will prefill itself before shedding with "
        "'pool_role' (default 64)",
    )
    p.add_argument(
        "--fleet-idem-ledger-size",
        type=int,
        default=None,
        metavar="N",
        help="bounded /fleet/generate idempotency ledger: how many recent "
        "idempotency keys this process remembers so a peer's timeout-retry "
        "returns the original result instead of re-executing (default 512; "
        "docs/FLEET.md 'Failure modes')",
    )
    p.add_argument(
        "--slo-itl-p95-s",
        type=float,
        default=None,
        metavar="S",
        help="decode-pool autoscaling signal: scale up when p95 inter-token "
        "latency burns past this (default 0.25; only read when --pool "
        "decode — docs/FLEET.md)",
    )
    p.add_argument(
        "--log-json",
        action="store_true",
        help="structured JSON logging for the serving process: one JSON line "
        "per event with trace_id/model/replica fields where the event "
        "carries them (equivalent to DABT_LOG_JSON=1; plain-text default "
        "unchanged — docs/OBSERVABILITY.md)",
    )
    p.add_argument(
        "--drain-deadline-s",
        type=float,
        default=None,
        metavar="S",
        help="graceful-shutdown budget: on SIGTERM the server stops admitting "
        "(503 + Retry-After), lets in-flight requests finish within this "
        "deadline, then exits 0 (default 30)",
    )
    p.add_argument(
        "--kv-pages",
        type=int,
        default=None,
        metavar="N",
        help="page-pool size in pages for every decoder (0 = a whole context "
        "per slot: max_slots * max_seq_len / page_size)",
    )
    p.add_argument(
        "--kv-page-size",
        type=int,
        default=None,
        metavar="TOKENS",
        help="KV page size in tokens (0 = the largest of 512 ... 8 that divides "
        "max_seq_len at least twice)",
    )
    p.add_argument(
        "--kv-host-bytes",
        type=int,
        default=None,
        metavar="BYTES",
        help="host-DRAM budget for the KV durability tier on every decoder: "
        "evicted/registered prefixes keep a host copy and restore instead of "
        "re-prefilling — warm sessions survive eviction, crash restarts, and "
        "scale-downs (0 = off; docs/KV_PAGING.md 'Tiered KV')",
    )
    p.add_argument(
        "--kv-spill-dir",
        default=None,
        metavar="DIR",
        help="disk tier for the KV durability plane: host-tier evictions "
        "demote to .npz files here instead of dropping (also honors the "
        "DABT_KV_SPILL_DIR env var)",
    )
    # deprecated r4 prefix-LRU flags: kept working, mapped onto the page-pool
    # prefix registry (run() logs a one-line warning when used)
    p.add_argument("--prefix-cache-size", type=int, default=None, help=(
        "DEPRECATED: max shareable-prefix entries (now the page-pool prefix "
        "registry bound; still honored)"))
    p.add_argument("--prefix-min-tokens", type=int, default=None, help=(
        "DEPRECATED: min prefix tokens to register for sharing (still honored)"))
    p.add_argument("--prefix-cache-max-bytes", type=int, default=None, help=(
        "DEPRECATED: byte budget for shared prefix pages (still honored)"))
    p.add_argument(
        "--no-scheduler",
        action="store_true",
        help="disable the admission-controlled scheduler on every decoder "
        "(reverts to unbounded FIFO admission; see docs/SCHEDULING.md)",
    )
    p.add_argument(
        "--sched-max-queue",
        type=int,
        default=None,
        metavar="N",
        help="override every decoder's admission-queue bound (requests past "
        "it shed with HTTP 429 + Retry-After)",
    )
    p.add_argument(
        "--sched-deadline-s",
        type=float,
        default=None,
        metavar="S",
        help="default per-request deadline in seconds applied when the client "
        "sends none (expired requests free their decode slot immediately)",
    )
    p.add_argument(
        "--faults",
        default=None,
        metavar="JSON",
        help="chaos session: fault-injection spec for every decoder, e.g. "
        '\'{"tick_raise": {"every": 50}}\' (sites/schedules in '
        "docs/RESILIENCE.md; equivalent to the DABT_FAULTS env var)",
    )
    p.add_argument(
        "--fault-seed",
        type=int,
        default=0,
        metavar="N",
        help="seed for probabilistic fault sites (same seed -> same pattern)",
    )
    p.add_argument(
        "--max-restarts",
        type=int,
        default=None,
        metavar="N",
        help="restart circuit: crash-only restarts tolerated per window "
        "before the engine goes degraded (503 + Retry-After)",
    )
    p.add_argument(
        "--restart-window-s",
        type=float,
        default=None,
        metavar="S",
        help="sliding window for the restart circuit",
    )
    p.add_argument(
        "--degraded-cooldown-s",
        type=float,
        default=None,
        metavar="S",
        help="how long a tripped engine fast-fails submits before resuming",
    )
    return p


def run(args) -> int:
    from ..serving.obs import setup_json_logging
    from ..serving.registry import ModelRegistry
    from ..serving.server import load_config_file, run_server
    from ..utils.compile_cache import enable_persistent_compile_cache

    # structured logging first, so even model-load lines come out as JSON
    # when opted in (--log-json or DABT_LOG_JSON=1); no-op otherwise
    setup_json_logging(force=bool(getattr(args, "log_json", False)))

    # turn on XLA's persistent compilation cache BEFORE any model loads/warms:
    # a second boot then skips the warm-up compiles.  It lives where
    # JAX_COMPILATION_CACHE_DIR says, else at <checkout>/.cache/xla
    # (utils/compile_cache.py); DABT_COMPILE_CACHE_OFF=1 opts out.
    enable_persistent_compile_cache()

    if args.tiny:
        config = {
            "tiny-emb": {"kind": "encoder", "tiny": True, "normalize": False},
            "tiny-chat": {"kind": "decoder", "tiny": True, "max_slots": 4, "max_seq_len": 256},
        }
    elif args.config:
        config = dict(load_config_file(args.config))
    else:
        print("need --config or --tiny")
        return 2
    if args.warmup:
        config = {
            name: {**spec, "warmup": True} for name, spec in config.items()
        }
    # scheduler/resilience overrides apply to decoder entries only (encoders
    # have no admission scheduler or decode loop; their coalescer bound is the
    # max_queue spec knob)
    sched_overrides = {}
    if getattr(args, "replicas", None) is not None:
        sched_overrides["replicas"] = args.replicas
    # dynamic-fleet flags (docs/AUTOSCALING.md): --min-replicas is the
    # initial/min size (same knob as --replicas), --max-replicas the ceiling,
    # --autoscale turns the controller on per decoder
    if getattr(args, "min_replicas", None) is not None:
        sched_overrides["replicas"] = args.min_replicas
    if getattr(args, "replica_devices", None) is not None:
        sched_overrides["replica_devices"] = args.replica_devices
    if getattr(args, "max_replicas", None) is not None:
        sched_overrides["max_replicas"] = args.max_replicas
    if getattr(args, "autoscale", False):
        sched_overrides["autoscale"] = True
        if getattr(args, "max_replicas", None) is None:
            # max_replicas defaults to the min size: a controller with
            # min == max can only engage degradation, never add a replica —
            # legitimate, but almost never what `--autoscale` meant
            print(
                "warning: --autoscale without --max-replicas leaves the fleet "
                "ceiling at the minimum size; the controller can clamp load "
                "(degradation) but never scale up — pass --max-replicas N "
                "to allow replica growth (docs/AUTOSCALING.md)"
            )
    if getattr(args, "slo_ttft_p95_s", None) is not None:
        sched_overrides["autoscale_slo_ttft_p95_s"] = args.slo_ttft_p95_s
    if getattr(args, "pool", None) is not None:
        sched_overrides["pool"] = args.pool
    if getattr(args, "slo_itl_p95_s", None) is not None:
        sched_overrides["autoscale_slo_itl_p95_s"] = args.slo_itl_p95_s
    if getattr(args, "kv_pages", None) is not None:
        sched_overrides["kv_pages"] = args.kv_pages
    if getattr(args, "kv_page_size", None) is not None:
        sched_overrides["kv_page_size"] = args.kv_page_size
    if getattr(args, "kv_host_bytes", None) is not None:
        sched_overrides["kv_host_bytes"] = args.kv_host_bytes
    if getattr(args, "kv_spill_dir", None) is not None:
        sched_overrides["kv_spill_dir"] = args.kv_spill_dir
    # deprecated prefix-LRU flags: one-line warning, then mapped onto the
    # page-pool prefix registry (identical semantics under the paged layout)
    _dep = {
        "prefix_cache_size": "prefix_cache",
        "prefix_min_tokens": "prefix_min_tokens",
        "prefix_cache_max_bytes": "prefix_cache_max_bytes",
    }
    for flag, knob in _dep.items():
        val = getattr(args, flag, None)
        if val is not None:
            print(
                f"warning: --{flag.replace('_', '-')} is deprecated; mapped "
                f"onto the paged KV prefix registry ({knob})"
            )
            sched_overrides[knob] = val
    if getattr(args, "no_scheduler", False):
        sched_overrides["scheduler"] = False
    if getattr(args, "sched_max_queue", None) is not None:
        sched_overrides["sched_max_queue"] = args.sched_max_queue
    if getattr(args, "sched_deadline_s", None) is not None:
        sched_overrides["sched_default_deadline_s"] = args.sched_deadline_s
    if getattr(args, "faults", None) is not None:
        import json as _json

        sched_overrides["faults"] = _json.loads(args.faults)
        sched_overrides["fault_seed"] = getattr(args, "fault_seed", 0)
    if getattr(args, "max_restarts", None) is not None:
        sched_overrides["max_restarts"] = args.max_restarts
    if getattr(args, "restart_window_s", None) is not None:
        sched_overrides["restart_window_s"] = args.restart_window_s
    if getattr(args, "degraded_cooldown_s", None) is not None:
        sched_overrides["degraded_cooldown_s"] = args.degraded_cooldown_s
    if sched_overrides:
        config = {
            name: {**spec, **(sched_overrides if spec.get("kind") == "decoder" else {})}
            for name, spec in config.items()
        }
    if getattr(args, "autotune", False):
        # geometry planning mode: sweep the decode byte ledger per decoder
        # and print the recommended {kv_page_size, max_slots, decode_steps}
        # — no weights load, no server start (docs/QUANT.md "Autotuning")
        import dataclasses as _dc
        import json as _json

        from ..models import DecoderConfig
        from ..serving.autotune import recommend_for_spec
        from ..serving.registry import ModelSpec

        overrides = {}
        if getattr(args, "autotune_hbm_gbps", None) is not None:
            overrides["hbm_gbps"] = args.autotune_hbm_gbps
        # slice-aware budget (docs/MULTICHIP.md): the sweep is bounded by
        # what ONE replica's devices can hold — its slice on a sliced
        # fleet, the whole host otherwise — never the global device count
        # for a replica that only spans a slice of it.  The host query is
        # LAZY and fallible: planning mode promises "no weights load, no
        # server start", and only an UNSLICED spec needs the host device
        # count — initializing the backend for a sliced sweep (e.g. while a
        # live server holds the TPU runtime lock) would crash planning mode
        # for nothing.
        _host_n: list = []

        def _n_host_devices():
            if not _host_n:
                try:
                    import jax as _jax

                    _host_n.append(len(_jax.devices()))
                except Exception as e:  # noqa: BLE001 - planning mode
                    print(
                        "warning: could not query the device count "
                        f"({type(e).__name__}: {e}); budgeting for 1 device"
                    )
                    _host_n.append(1)
            return _host_n[0]
        results = []
        for name, d in config.items():
            if d.get("kind") != "decoder":
                continue
            spec = ModelSpec.from_dict(name.lower(), d)
            model_overrides = dict(overrides)  # per-model (manifest bits)
            try:
                if spec.checkpoint:
                    # the native manifest carries the full model config as
                    # JSON — geometry without any weight load
                    from ..checkpoint import _config_from_dict, read_manifest

                    manifest = read_manifest(spec.checkpoint)
                    meta = manifest["meta"]
                    cfg = _config_from_dict(
                        meta["kind"], dict(meta["config"])
                    )
                    if not spec.quantize:
                        # pre-quantized checkpoints declare themselves via
                        # their packed-weight leaf dtypes (".q" fields)
                        qd = {
                            e.get("dtype")
                            for e in manifest.get("leaves", [])
                            if str(e.get("key", "")).endswith(".q")
                        }
                        if "uint8" in qd:
                            model_overrides.setdefault("weight_bits", 4)
                        elif "int8" in qd:
                            model_overrides.setdefault("weight_bits", 8)
                elif spec.path:
                    from ..models.hf_loader import read_hf_config

                    cfg = DecoderConfig.from_hf(read_hf_config(spec.path))
                elif spec.tiny:
                    cfg = DecoderConfig.tiny(num_experts=spec.num_experts)
                    if spec.max_seq_len and spec.max_seq_len > cfg.max_seq_len:
                        cfg = _dc.replace(
                            cfg, max_seq_len=int(spec.max_seq_len)
                        )
                else:
                    results.append(
                        {
                            "model": name,
                            "skipped": "autotune needs a tiny, path-, or "
                            "checkpoint-backed decoder",
                        }
                    )
                    continue
            except Exception as e:  # noqa: BLE001 - planning mode reports
                results.append({"model": name, "error": str(e)})
                continue
            rep = recommend_for_spec(
                spec,
                cfg,
                n_host_devices=(
                    None if spec.replica_devices else _n_host_devices()
                ),
                hbm_gb_per_device=getattr(args, "autotune_hbm_gb", None),
                **model_overrides,
            )
            if getattr(args, "measure", False) and rep.get("top"):
                # measured-cost re-rank: ONE weight load for this decoder,
                # then an engine construction + probe per candidate.  The
                # probe is idle-locked by construction (fresh engine, no
                # traffic) — compile cost is the price of ground truth.
                from ..serving.autotune import measure_report

                try:
                    factory = _probe_engine_factory(spec, cfg)
                    measure_report(
                        rep,
                        factory,
                        top_k=max(1, int(getattr(args, "measure_top_k", 3))),
                    )
                except Exception as e:  # noqa: BLE001 - planning mode
                    rep["measure_error"] = f"{type(e).__name__}: {e}"
            results.append(rep)
        print(_json.dumps({"autotune": results}, indent=2))
        return 0

    # name the device before any weight lands on it: a serve that came up on
    # the CPU (or on fewer chips than meant) must say so in its first line
    from ..utils.device import device_info, device_line

    logging.getLogger(__name__).info("serving on %s", device_line(device_info()))
    registry = ModelRegistry.from_config(config)
    # cross-process fleet plane (serving/fleet.py; docs/FLEET.md): attach it
    # HERE so create_app reuses the CLI-configured identity/pool/peer list
    # instead of building a default unified plane
    from ..parallel.distributed import fleet_peers_from_env, fleet_self_name
    from ..serving.fleet import FleetPlane

    peers = fleet_peers_from_env(getattr(args, "fleet_peers", None))
    plane_kwargs = {}
    if getattr(args, "decode_max_prefill_tokens", None) is not None:
        plane_kwargs["decode_max_prefill_tokens"] = args.decode_max_prefill_tokens
    if getattr(args, "fleet_idem_ledger_size", None) is not None:
        plane_kwargs["idem_ledger_size"] = args.fleet_idem_ledger_size
    registry.fleet_plane = FleetPlane(
        registry,
        name=fleet_self_name(getattr(args, "fleet_name", None)),
        pool=getattr(args, "pool", None),
        peers=peers,
        **plane_kwargs,
    )
    # SIGTERM-triggered graceful drain (whole-router when --replicas > 1):
    # run_server's shutdown handler stops admission, waits for in-flight
    # work within the deadline, then returns — and we exit 0, so rolling
    # restarts under an init system read as clean stops
    run_server(
        host=args.host,
        port=args.port,
        registry=registry,
        drain_deadline_s=(
            args.drain_deadline_s
            if getattr(args, "drain_deadline_s", None) is not None
            else 30.0
        ),
    )
    return 0
