"""Roofline decode push (docs/QUANT.md): fused multi-token decode tick
(`decode_steps`), int4 grouped-quant serving, double-buffered uploads, the
decode-path operator gauges, and the byte-ledger autotune sweep."""

import jax
import numpy as np
import pytest
from types import SimpleNamespace

from django_assistant_bot_tpu.models import DecoderConfig, llama
from django_assistant_bot_tpu.ops.quant import quantize_decoder_params
from django_assistant_bot_tpu.serving import ByteTokenizer, GenerationEngine


def _tiny():
    cfg = DecoderConfig.tiny()
    return cfg, llama.init(cfg, jax.random.PRNGKey(0))


def _drive(eng, futs, limit=4000):
    """Single-threaded deterministic engine loop (no engine thread) — the
    test_kv_paging discipline: every request queued before the first
    admission, so both arms see the identical wave structure."""
    steps = 0
    while not all(f.done() for f in futs):
        eng._reap_dead_slots()
        eng._admit()
        if eng._chunking is not None:
            eng._chunk_step()
        if eng.num_active > 0:
            eng._issue_tick()
        while eng._inflight and (
            len(eng._inflight) > eng.lookahead or eng.num_active == 0
        ):
            eng._process_tick()
        eng._prestage_uploads()
        steps += 1
        assert steps < limit, "engine made no progress"


def _run(cfg, params, prompts, *, temps=None, **kw):
    eng = GenerationEngine(
        cfg, params, ByteTokenizer(), max_slots=4, max_seq_len=256,
        prefix_cache_size=0, **kw,
    )
    eng._running = True
    temps = temps or [0.0] * len(prompts)
    futs = [
        eng.submit(p, max_tokens=12, temperature=t, top_p=0.9)
        for p, t in zip(prompts, temps)
    ]
    _drive(eng, futs)
    eng._running = False
    return [f.result(timeout=0).token_ids for f in futs], eng


def _ragged_prompts(seed=5, n=4):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 200, ln).tolist() for ln in (3, 17, 40, 9)][:n]


# --------------------------------------------------------------- bit identity
def test_decode_steps_one_byte_identical_to_unfused_burst():
    """The rollback contract: decode_steps=1 IS the unfused tick — greedy AND
    sampled traffic byte-identical to the historical burst=1 alias."""
    cfg, params = _tiny()
    prompts = _ragged_prompts()
    temps = [0.0, 0.9, 0.0, 0.7]
    a, _ = _run(cfg, params, prompts, temps=temps, decode_steps=1)
    b, _ = _run(cfg, params, prompts, temps=temps, burst=1)
    assert a == b


@pytest.mark.parametrize("quantize", [None, "int8", "int4"])
def test_fused_greedy_token_identical(quantize):
    """N>1 fused ticks are greedy token-identical to N=1 across weight
    formats over ragged prompt fills — the acceptance criterion's
    bit-identity subset."""
    cfg, params = _tiny()
    if quantize:
        params = quantize_decoder_params(params, fmt=quantize)
    prompts = _ragged_prompts()
    a, ea = _run(cfg, params, prompts, decode_steps=1)
    b, eb = _run(cfg, params, prompts, decode_steps=3)
    assert a == b
    assert ea.decode_steps == 1 and eb.decode_steps == 3
    if quantize == "int4":
        assert eb.weight_bits == 4
    elif quantize == "int8":
        assert eb.weight_bits == 8


def test_fused_sampled_token_identical_across_n():
    """Sampled rows too: the fused scan splits the chained rng once per STEP,
    exactly like N=1 tick-per-step — same split chain, same ids."""
    cfg, params = _tiny()
    prompts = _ragged_prompts(seed=9)
    temps = [0.8, 0.9, 0.7, 1.0]
    a, _ = _run(cfg, params, prompts, temps=temps, decode_steps=1)
    b, _ = _run(cfg, params, prompts, temps=temps, decode_steps=4)
    assert a == b


# ------------------------------------------------------------- int4 serving
def test_int4_engine_serves_threaded():
    """Grouped-int4 weights through the real threaded engine: decode works,
    the weight_bits gauge reports 4, and the fused tick stays engaged."""
    cfg = DecoderConfig.tiny()
    params = llama.init_int4(cfg, jax.random.PRNGKey(2))
    eng = GenerationEngine(
        cfg, params, ByteTokenizer(), max_slots=4, max_seq_len=128,
        decode_steps=4, prefix_cache_size=0,
    ).start()
    try:
        futs = [
            eng.submit(list(range(1, 10)), max_tokens=8, temperature=0.0)
            for _ in range(3)
        ]
        for f in futs:
            r = f.result(timeout=120)
            assert len(r.token_ids) >= 1
        st = eng.tick_stats()
        assert st["weight_bits"] == 4
        assert st["decode_steps"] == 4
        assert st["decode_steps_effective"] == 4
    finally:
        eng.stop()


# ------------------------------------------------------- json downgrade path
def test_json_slots_downgrade_fused_tick_to_single_step():
    cfg, params = _tiny()
    eng = GenerationEngine(
        cfg, params, ByteTokenizer(), max_slots=4, max_seq_len=128,
        decode_steps=4, prefix_cache_size=0,
    )
    eng.warmup(json=True)
    eng.start()
    try:
        f = eng.submit([1, 2, 3], max_tokens=8, temperature=0.0, json_format=True)
        f.result(timeout=120)
        st = eng.tick_stats()
        assert st["json_downgraded_ticks"] > 0
        assert st["decode_steps_effective"] == 1
        assert st["decode_steps"] == 4
        # plain traffic afterwards re-engages the fused tick
        eng.submit([1, 2, 3], max_tokens=6, temperature=0.0).result(timeout=120)
        assert eng.tick_stats()["decode_steps_effective"] == 4
    finally:
        eng.stop()


def test_speculative_composes_with_decode_steps():
    """Spec x fused: decode_steps now scans N verify passes per dispatch
    instead of being rejected; the engine reports both knobs and the
    oversized product still fails loudly at construction."""
    cfg, params = _tiny()
    eng = GenerationEngine(
        cfg, params, ByteTokenizer(), max_slots=2, max_seq_len=128,
        decode_steps=4, speculative=3,
    )
    assert eng.burst == 4 and eng.speculative == 3
    # a spec engine WITHOUT an explicit decode_steps stays at one verify
    # pass per tick — `burst` must not silently multiply existing deploys
    eng1 = GenerationEngine(
        cfg, params, ByteTokenizer(), max_slots=2, max_seq_len=128,
        burst=8, speculative=3,
    )
    assert eng1.burst == 1
    with pytest.raises(ValueError, match="too large"):
        GenerationEngine(
            cfg, params, ByteTokenizer(), max_slots=2, max_seq_len=128,
            decode_steps=8, speculative=7,
        )


# -------------------------------------------------- double-buffered uploads
def test_upload_overlap_reported_and_positive():
    """Staggered finishes dirty the sampling arrays while ticks are still in
    flight — the prestage path must absorb some upload cycles and the gauge
    must ride tick_stats."""
    cfg, params = _tiny()
    eng = GenerationEngine(
        cfg, params, ByteTokenizer(), max_slots=4, max_seq_len=128,
        decode_steps=2, prefix_cache_size=0,
    ).start()
    try:
        futs = [
            eng.submit(list(range(1, 6)), max_tokens=4 + 10 * i, temperature=0.7)
            for i in range(4)
        ]
        for f in futs:
            f.result(timeout=120)
        st = eng.tick_stats()
        assert 0.0 <= st["upload_overlap_frac"] <= 1.0
        assert eng._uploads_prestaged > 0
    finally:
        eng.stop()


# ------------------------------------------------------------ chaos restart
def test_tick_raise_mid_fused_tick_restart_leaves_page_pool_clean():
    """tick_raise armed mid-fused-tick (decode_steps=4, paged): crash-only
    restart resets the page plane — every page back on the free list, block
    tables unallocated — and salvaged requests complete on the rebuilt pool
    (the speculative chaos test's contract, now on the fused plain tick)."""
    from django_assistant_bot_tpu.serving.faults import FaultInjector

    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.PRNGKey(9))
    tok = ByteTokenizer()
    inj = FaultInjector({})
    eng = GenerationEngine(
        cfg, params, tok, max_slots=2, max_seq_len=96, decode_steps=4,
        prefix_cache_size=0, faults=inj,
    )
    eng.start()
    try:
        f0 = eng.submit(tok.encode("ab ab ab ab"), max_tokens=6, temperature=0.0)
        f0.result(timeout=120)
        inj.arm("tick_raise")
        futs = [
            eng.submit(tok.encode("cd cd cd cd"), max_tokens=6, temperature=0.0)
            for _ in range(2)
        ]
        done = 0
        for f in futs:
            try:
                r = f.result(timeout=120)
                assert len(r.token_ids) >= 1
                done += 1
            except RuntimeError:
                pass  # past-first-token requests fail cleanly on restart
        assert done >= 1
        assert eng.engine_restarts == 1
        assert eng.healthy()
        kv = eng.kv_stats()
        assert kv["kv_pages_used"] == 0
        assert kv["kv_pages_free"] == eng._kv_pool.n_pages
        assert all(not pages for pages in eng._slot_pages)
    finally:
        eng.stop(drain_timeout_s=60.0)


# ------------------------------------------------------------ operator plane
def test_decode_path_gauges_in_metrics_exposition():
    from django_assistant_bot_tpu.serving.obs import (
        parse_prometheus_text,
        render_prometheus,
    )

    cfg = DecoderConfig.tiny()
    params = llama.init_int4(cfg, jax.random.PRNGKey(3))
    eng = GenerationEngine(
        cfg, params, ByteTokenizer(), max_slots=2, max_seq_len=128,
        decode_steps=2, prefix_cache_size=0, name="q4",
    )
    reg = SimpleNamespace(generators={"q4": eng}, embedders={})
    fams = parse_prometheus_text(render_prometheus(reg))
    assert fams["dabt_weight_bits"]["samples"][0][2] == 4
    assert fams["dabt_decode_steps"]["samples"][0][2] == 2
    assert "dabt_decode_steps_effective" in fams
    assert "dabt_upload_overlap_frac" in fams


def test_registry_accepts_decode_steps_with_speculative():
    """The registry-level mutual exclusion is gone: a spec x fused entry
    loads and threads both knobs into the engine."""
    from django_assistant_bot_tpu.serving.registry import ModelRegistry, ModelSpec

    spec = ModelSpec(
        name="m", kind="decoder", tiny=True, decode_steps=2, speculative=3,
        max_seq_len=128, scheduler=False,
    )
    reg = ModelRegistry(specs={"m": spec})
    try:
        eng = reg.generators["m"]
        assert eng.burst == 2 and eng.speculative == 3
    finally:
        reg.stop()


def test_registry_rejects_bad_quant_knobs():
    from django_assistant_bot_tpu.serving.registry import ModelRegistry, ModelSpec

    with pytest.raises(ValueError, match="quantize"):
        ModelRegistry(
            specs={"m": ModelSpec(name="m", kind="decoder", tiny=True, quantize="int2")}
        )
    with pytest.raises(ValueError, match="quant_group_size"):
        ModelRegistry(
            specs={
                "m": ModelSpec(
                    name="m", kind="decoder", tiny=True,
                    quantize="int4", quant_group_size=3,
                )
            }
        )


# ----------------------------------------------------- quantized checkpoints
@pytest.mark.parametrize("fmt", ["int8", "int4"])
def test_quantized_checkpoint_roundtrip_preserves_qtensor(fmt, tmp_path):
    """Regression: the checkpoint loader used to collapse a QTensor onto
    whichever field restored LAST (keystr attr paths weren't parsed), so a
    `fetch_models --convert --quantize int8` checkpoint restored with wq ==
    its SCALE array — unservable.  Both formats must round-trip exactly,
    with scales kept f32 through the dtype cast."""
    import jax.numpy as jnp

    from django_assistant_bot_tpu.checkpoint import load_model, save_model
    from django_assistant_bot_tpu.ops.quant import QTensor, QTensor4

    cfg, params = _tiny()
    qp = quantize_decoder_params(params, fmt=fmt)
    save_model(str(tmp_path / "m"), "decoder", cfg, qp)
    kind, cfg2, back, _meta = load_model(str(tmp_path / "m"), dtype=jnp.bfloat16)
    assert kind == "decoder"
    cls = QTensor4 if fmt == "int4" else QTensor
    wq = back["layers"]["wq"]
    assert isinstance(wq, cls)
    np.testing.assert_array_equal(
        np.asarray(wq.q), np.asarray(qp["layers"]["wq"].q)
    )
    assert np.asarray(wq.scale).dtype == np.float32
    ids = jnp.asarray([[3, 4, 5, 6]], jnp.int32)
    np.testing.assert_allclose(
        np.asarray(llama.forward(qp, cfg, ids)),
        np.asarray(llama.forward(back, cfg, ids)),
        atol=2e-2,
    )


def test_prequantized_checkpoint_guard(tmp_path):
    """A converted checkpoint arrives pre-quantized: a MATCHING quantize knob
    is a logged no-op, a MISMATCHED one is a named config error — not the
    opaque numpy shape crash double-quantization used to die with."""
    from django_assistant_bot_tpu.checkpoint import save_model
    from django_assistant_bot_tpu.serving.registry import ModelRegistry, ModelSpec

    cfg, params = _tiny()
    qp = quantize_decoder_params(params, fmt="int4")
    save_model(str(tmp_path / "m"), "decoder", cfg, qp)
    reg = ModelRegistry(
        specs={
            "a": ModelSpec(
                name="a", kind="decoder",
                checkpoint=str(tmp_path / "m"), quantize="int4",
            )
        }
    )
    try:
        assert reg.get_generator("a").weight_bits == 4
    finally:
        reg.stop()
    with pytest.raises(ValueError, match="already quantized"):
        ModelRegistry(
            specs={
                "b": ModelSpec(
                    name="b", kind="decoder",
                    checkpoint=str(tmp_path / "m"), quantize="int8",
                )
            }
        )


# ------------------------------------------------------------------ autotune
def test_autotune_sweep_ranks_and_respects_budget():
    from django_assistant_bot_tpu.serving.autotune import Geometry, recommend, sweep

    geom = Geometry(
        num_layers=16, hidden_size=2048, intermediate_size=8192,
        num_heads=32, num_kv_heads=8, head_dim=64, vocab_size=128256,
    )
    cands = sweep(geom, max_seq_len=1024, weight_bits=8, hbm_budget_gb=8.0)
    assert cands, "no feasible geometry"
    # ranked by modeled tok/s, every candidate inside the budget
    rates = [c.est_tokens_per_s for c in cands]
    assert rates == sorted(rates, reverse=True)
    assert all(c.hbm_total_gb <= 8.0 for c in cands)
    rec = recommend(geom, max_seq_len=1024, weight_bits=4, hbm_budget_gb=8.0)
    assert set(rec["recommended"]) == {"kv_page_size", "max_slots", "decode_steps"}
    assert rec["assumptions"]["weight_bits"] == 4


def test_autotune_int4_reads_fewer_bytes_and_steps_amortize_overhead():
    from django_assistant_bot_tpu.serving.autotune import Geometry, sweep

    geom = Geometry(
        num_layers=16, hidden_size=2048, intermediate_size=8192,
        num_heads=32, num_kv_heads=8, head_dim=64, vocab_size=128256,
    )
    assert geom.weight_read_bytes(4) < geom.weight_read_bytes(8)
    assert geom.weight_read_bytes(8) < geom.weight_read_bytes(16)
    # untied models hold a second embedding table decode never streams:
    # the feasibility side must charge it, the read side must not
    emb_bytes = geom.head_weights() * geom.dtype_bytes
    assert geom.resident_weight_bytes(16) == geom.weight_read_bytes(16) + emb_bytes
    import dataclasses

    tied = dataclasses.replace(geom, tie_embeddings=True)
    assert tied.resident_weight_bytes(16) == tied.weight_read_bytes(16)
    # with a large host overhead the sweep must prefer deeper fused ticks
    # at fixed page/slots: tok/s strictly rises with decode_steps
    cands = sweep(
        geom, max_seq_len=1024, weight_bits=8, hbm_budget_gb=8.0,
        host_overhead_us=10_000.0, page_sizes=(256,), slots=(8,),
        decode_steps=(1, 4, 16),
    )
    by_steps = {c.decode_steps: c.est_tokens_per_s for c in cands}
    assert by_steps[16] > by_steps[4] > by_steps[1]


def test_measure_report_reranks_by_probe():
    """`--measure` discipline: probe the top-k, keep BOTH rankings, make
    ledger-vs-measured disagreement a visible artifact, and never let one
    failed probe abort the sweep."""
    from django_assistant_bot_tpu.serving.autotune import measure_report

    class FakeEng:
        def __init__(self, step_s):
            self._s = step_s
            self.stopped = False

        def probe_decode(self, iters=16, fill_len=None):
            if self._s is None:
                raise RuntimeError("compile exploded")
            return self._s

        def stop(self, drain_timeout_s=None):
            self.stopped = True

    # ledger rank 0 probes SLOWER than rank 1, rank 2's probe dies
    step_by_depth = {2: 0.010, 4: 0.004, 8: None}
    built = []

    def factory(cand):
        eng = FakeEng(step_by_depth[cand["decode_steps"]])
        built.append(eng)
        return eng

    report = {
        "top": [
            {"kv_page_size": 32, "max_slots": 8, "decode_steps": d}
            for d in (2, 4, 8)
        ],
        "recommended": {"kv_page_size": 32, "max_slots": 8, "decode_steps": 2},
    }
    measure_report(report, factory, top_k=3)
    assert report["ledger_recommended"]["decode_steps"] == 2
    assert report["recommended"]["decode_steps"] == 4
    assert report["measured_agrees_with_ledger"] is False
    assert report["measured"][0]["measured_tokens_per_s"] == 8 / 0.004
    errs = [r for r in report["measured"] if "probe_error" in r]
    assert len(errs) == 1 and errs[0]["decode_steps"] == 8
    assert all(e.stopped for e in built), "a probed engine leaked"


def test_autotune_recommend_for_spec_tiny():
    import dataclasses

    from django_assistant_bot_tpu.serving.autotune import recommend_for_spec
    from django_assistant_bot_tpu.serving.registry import ModelSpec

    spec = ModelSpec(
        name="t", kind="decoder", tiny=True, quantize="int4", max_seq_len=256
    )
    cfg = DecoderConfig.tiny()
    cfg = dataclasses.replace(cfg, max_seq_len=256)
    out = recommend_for_spec(spec, cfg)
    assert out["model"] == "t"
    assert out["assumptions"]["weight_bits"] == 4
    assert out["recommended"]["kv_page_size"] in (32, 64, 128)
    # spec x fused composition (round 15): the sweep covers every verify
    # depth inside the construction bound n*(K+1) <= max_seq_len/4 instead
    # of clamping a speculative decoder to decode_steps=1
    spec_s = ModelSpec(
        name="s", kind="decoder", tiny=True, speculative=3, max_seq_len=256
    )
    out_s = recommend_for_spec(spec_s, cfg)
    steps = {c["decode_steps"] for c in out_s["top"]}
    assert steps - {1}, "spec sweep still clamped to decode_steps=1"
    assert all(n * (3 + 1) <= 256 // 4 for n in steps)


def test_shard_pytree_keeps_fail_loudly_for_plain_weights():
    """The non-dividing-dim replication fallback applies ONLY to quantized
    subtrees (int4 packing/grouping can stop dividing a TP axis the
    full-width weight divided) — a mis-annotated plain weight still fails
    loudly instead of silently replicating N-fold."""
    from django_assistant_bot_tpu.parallel.sharding import _is_quantized
    from django_assistant_bot_tpu.ops.quant import (
        QTensor4,
        quantize_tensor_int4,
    )
    import jax.numpy as jnp

    w = jnp.asarray(np.random.default_rng(0).normal(size=(24, 8)), jnp.float32)
    assert _is_quantized(quantize_tensor_int4(w, group_size=8))
    assert isinstance(quantize_tensor_int4(w, group_size=8), QTensor4)
    assert not _is_quantized(w)
    assert not _is_quantized({"q": w})
