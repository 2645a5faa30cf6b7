"""Tree-verified speculative decoding: drafter/acceptance semantics, engine
equivalence, paged byte-identity, chaos, and the adaptive controller.

The non-negotiable property is IDENTICAL greedy output with speculation on vs
off — speculation may only change how fast tokens arrive, never which tokens.
On the f32 CPU mesh that equality is exact (property-tested below across
ragged batches, mixed greedy/sampled rows and no-match rows); the bf16 MXU
near-tie caveat lives in docs/SPECULATIVE.md.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from django_assistant_bot_tpu.models import DecoderConfig, llama
from django_assistant_bot_tpu.ops.speculative import (
    SpecController,
    accept_tree,
    breakeven_accept_rate,
    build_prompt_lookup_draft,
    build_tree_draft,
    default_rungs,
    make_tree_spec,
)
from paged import Paged


@pytest.fixture(scope="module")
def tiny():
    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.PRNGKey(7))
    return cfg, params


def _prefill_into(cfg, params, prompt, batch=2, max_len=64):
    kv = Paged(cfg, batch=batch, max_len=max_len, dtype=jnp.float32)
    logits = kv.prefill(params, prompt, [prompt.shape[1]], slots=[0])
    return int(jnp.argmax(logits[0])), kv


def _greedy_reference(cfg, params, prompt, n_new):
    tok, kv = _prefill_into(cfg, params, prompt)
    got = [tok]
    tokens = jnp.zeros((2,), jnp.int32)
    active = jnp.asarray([True, False])
    for _ in range(n_new - 1):
        tokens = tokens.at[0].set(got[-1])
        logits = kv.decode(params, tokens, active=active)
        got.append(int(jnp.argmax(logits[0])))
    return got


def _run_tree(cfg, params, kv, tree_tokens, spec, temps=None):
    """verify_tree_step_paged + accept_tree on a [2, T] batch (row 1 inert)."""
    depths = jnp.asarray(spec.depths)
    anc = jnp.asarray(spec.anc_mask)
    logits, tks, tvs = llama.verify_tree_step_paged(
        params, cfg, jnp.asarray(tree_tokens, jnp.int32), kv.cache, kv.bt, depths, anc
    )
    out, n_new, bonus, path_idx, _ = accept_tree(
        logits,
        jnp.asarray(tree_tokens, jnp.int32),
        spec,
        jax.random.key(0),
        temperature=temps if temps is not None else jnp.zeros((2,)),
        top_k=50,
        top_p=jnp.ones((2,)),
    )
    return logits, tks, tvs, out, n_new, bonus, path_idx


# ------------------------------------------------------------------ tree spec
def test_make_tree_spec_layout():
    spec = make_tree_spec(3, 4)
    assert spec.size == 1 + 3 * 4
    assert spec.depths[0] == 0 and spec.parent[0] == 0
    for n in range(3):
        nodes = spec.branch_nodes[n]
        assert spec.parent[nodes[0]] == 0  # depth-1 nodes hang off the root
        for d in range(1, 4):
            assert spec.parent[nodes[d]] == nodes[d - 1]
            assert spec.depths[nodes[d]] == d + 1
        # ancestor chain: every node sees the root, itself, and its branch
        # prefix — and nothing from other branches
        for d in range(4):
            t = nodes[d]
            anc = set(np.nonzero(spec.anc_mask[t])[0].tolist())
            assert anc == {0, *nodes[: d + 1].tolist()}


# ------------------------------------------------------------------- drafter
def test_build_tree_draft_branches_dedup_and_fallbacks():
    """Branches are distinct bigram continuations most-recent-first; duplicate
    first tokens dedup to the most recent occurrence; one spare branch takes
    the unigram; unfilled branches draft rejectable tail garbage."""
    # row 0: bigram (7, 8) occurs thrice; two of the continuations start with
    # 50 (positions 1 and 8 — dedup keeps position 8's), one with 40 (pos 4)
    hist0 = [9, 7, 8, 50, 7, 8, 40, 9, 7, 8, 50, 61, 2, 9, 7, 8, 0, 0, 0, 0]
    #        0  1  2   3  4  5   6  7  8  9  10  11 12 13 14 15  (pending 8 @15)
    # row 1: no bigram for (5, 9); unigram 9 at pos 2 -> draft follows it
    hist1 = [4, 5, 9, 70, 71, 72, 6, 5, 9, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0]
    hist = jnp.asarray([hist0, hist1], jnp.int32)
    lengths = jnp.asarray([15, 8], jnp.int32)
    tokens = jnp.asarray([8, 9], jnp.int32)
    draft = np.asarray(build_tree_draft(hist, lengths, tokens, 3, 3))
    # branch 0: most recent distinct bigram hit (pos 8) -> [50, 61, 2]
    assert draft[0, 0].tolist() == [50, 61, 2]
    # branch 1: next most recent distinct (pos 4) -> [40, 9, 7]
    assert draft[0, 1].tolist() == [40, 9, 7]
    # branch 2: only 2 distinct continuations exist; no unigram strictly
    # before the tail that isn't part of a bigram hit... row 0 has unigram 8
    # at positions 2/5/9 -> fallback branch follows the last one (pos 9)
    assert draft[0, 2].tolist() == [50, 61, 2] or draft[0, 2][0] == hist0[10]
    # row 1: no bigram anywhere -> branch 0 is the unigram continuation
    assert draft[1, 0].tolist() == [70, 71, 72]


def test_width1_tree_matches_linear_prompt_lookup():
    """The width-1 tree IS the old single-candidate prompt-lookup draft."""
    hist = jnp.asarray(
        [[1, 7, 8, 50, 60, 61, 2, 3, 7, 8, 0, 0, 0, 0, 0, 0]], jnp.int32
    )
    lengths = jnp.asarray([9], jnp.int32)
    tokens = jnp.asarray([8], jnp.int32)
    lin = np.asarray(build_prompt_lookup_draft(hist, lengths, tokens, 3))
    tre = np.asarray(build_tree_draft(hist, lengths, tokens, 1, 3))[:, 0]
    assert lin.tolist() == tre.tolist() == [[50, 60, 61]]


# ------------------------------------------------------------- verify/accept
def test_tree_accepts_oracle_branch_at_any_position(tiny):
    """The true greedy continuation planted in a NON-FIRST branch (garbage in
    the others) must be fully accepted, with the correct bonus token."""
    cfg, params = tiny
    prompt = np.array([[1, 5, 9, 17, 3]], np.int32)
    K, N = 3, 3
    ref = _greedy_reference(cfg, params, prompt, K + 2)
    tok, kv = _prefill_into(cfg, params, prompt)
    assert tok == ref[0]
    spec = make_tree_spec(N, K)
    tree = np.zeros((2, spec.size), np.int32)
    tree[0, 0] = ref[0]
    tree[0, spec.branch_nodes[0]] = [499, 498, 497]  # garbage branch
    tree[0, spec.branch_nodes[1]] = ref[1 : K + 1]  # the oracle branch
    tree[0, spec.branch_nodes[2]] = [3, 499, 3]
    _, tks, tvs, out, n_new, bonus, path_idx = _run_tree(
        cfg, params, kv, tree, spec
    )
    assert int(n_new[0]) == K + 1  # every oracle draft accepted + bonus
    assert np.asarray(out)[0, : K + 1].tolist() == ref[1 : K + 2]
    assert int(bonus[0]) == ref[K + 1]
    # the commit path is root + the winning (oracle) branch
    assert np.asarray(path_idx)[0].tolist() == [0, *spec.branch_nodes[1]]


def test_tree_rejects_garbage_and_cache_stays_sound(tiny):
    """All-garbage trees accept nothing; position-0 output equals the plain
    step's, and after committing the path the cache supports continued plain
    decoding that tracks the non-speculative reference exactly."""
    cfg, params = tiny
    prompt = np.array([[2, 11, 4, 30]], np.int32)
    n_total = 6
    ref = _greedy_reference(cfg, params, prompt, n_total)
    tok, kv = _prefill_into(cfg, params, prompt)
    K, N = 3, 2
    spec = make_tree_spec(N, K)
    tree = np.full((2, spec.size), 499, np.int32)
    tree[0, 0] = tok
    tree[1, :] = 0
    _, tks, tvs, out, n_new, bonus, path_idx = _run_tree(
        cfg, params, kv, tree, spec
    )
    assert int(n_new[0]) == 1
    assert int(out[0, 0]) == ref[1]
    active = jnp.asarray([True, False])
    cache = llama.commit_tree_path_paged(kv.cache, tks, tvs, path_idx, kv.bt, n_new, active)
    kv.cache = cache._replace(
        lengths=cache.lengths.at[0].set(int(cache.lengths[0]) + 1)
    )
    got = [tok, int(out[0, 0])]
    tokens = jnp.zeros((2,), jnp.int32)
    for _ in range(n_total - 2):
        tokens = tokens.at[0].set(got[-1])
        lg = kv.decode(params, tokens, active=active)
        got.append(int(jnp.argmax(lg[0])))
    assert got == ref


def test_accept_tree_sampled_rows_take_position_zero():
    """temperature>0 rows never accept drafts (n_new==1) and their token is a
    valid sample of position-0 logits."""
    V = 32
    spec = make_tree_spec(2, 3)
    logits = jnp.full((1, spec.size, V), -30.0)
    logits = logits.at[0, 0, 5].set(10.0)  # position-0 mass on token 5
    tree = jnp.full((1, spec.size), 5, jnp.int32)
    out, n_new, bonus, _, _ = accept_tree(
        logits,
        tree,
        spec,
        jax.random.key(2),
        temperature=jnp.asarray([0.7]),
        top_k=10,
        top_p=jnp.asarray([0.9]),
    )
    assert int(n_new[0]) == 1
    assert int(out[0, 0]) == 5 and int(bonus[0]) == 5


def test_verify_tree_is_read_only_wrt_cache(tiny):
    """The tree verify must not mutate the cache — the accepted-path commit
    is the ONLY write."""
    cfg, params = tiny
    prompt = np.array([[1, 5, 9, 17, 3]], np.int32)
    tok, kv = _prefill_into(cfg, params, prompt)
    k_before = np.asarray(kv.cache.k)
    spec = make_tree_spec(2, 2)
    tree = np.zeros((2, spec.size), np.int32)
    tree[0, 0] = tok
    llama.verify_tree_step_paged(
        params, cfg, jnp.asarray(tree), kv.cache, kv.bt,
        jnp.asarray(spec.depths), jnp.asarray(spec.anc_mask),
    )
    assert np.array_equal(k_before, np.asarray(kv.cache.k))


# ---------------------------------------------------------------- controller
def test_controller_upshift_downshift_under_forced_accept_rates():
    ctl = SpecController(
        rungs=default_rungs(4, 6), probe_every=8, explore_every=1000
    )
    # measured costs: wide trees are expensive, narrow ones cheap
    ctl.note_cost((4, 6), 3.0)
    ctl.note_cost((2, 6), 2.0)
    ctl.note_cost((1, 6), 1.5)
    ctl.note_cost((1, 3), 1.2)
    # force per-rung acceptance: the wide tree's extra candidates land
    # (p ~ 1.0) while the single branch only half-lands — the width pays
    # its 2x cost premium and the controller UPSHIFTS to it
    for _ in range(50):
        ctl.note_tick(accepted=6, depth=6, rung=(4, 6))
        ctl.note_tick(accepted=3, depth=6, rung=(2, 6))
        ctl.note_tick(accepted=3, depth=6, rung=(1, 6))
        ctl.note_tick(accepted=2, depth=3, rung=(1, 3))
    assert ctl.rung() == (4, 6)
    assert not ctl.disabled
    # the wide tree's acceptance collapses while the single branch keeps
    # half-landing: DOWNSHIFT off the wide rung
    for _ in range(50):
        ctl.note_tick(accepted=0, depth=6, rung=(4, 6))
    rung = ctl.rung()
    assert rung is not None and rung != (4, 6)
    # every rung collapses: disable entirely — below breakeven, a verify
    # forward can never pay for itself
    for r in [(2, 6), (1, 6)]:
        for _ in range(80):
            ctl.note_tick(accepted=0, depth=6, rung=r)
    for _ in range(80):
        ctl.note_tick(accepted=0, depth=3, rung=(1, 3))
    assert ctl.rung() is None
    assert ctl.disabled
    stats = ctl.stats()
    assert stats["spec_auto_disabled"] is True


def test_controller_explores_wider_rung_periodically():
    ctl = SpecController(rungs=[(4, 4), (1, 4)], explore_every=5)
    ctl.note_cost((4, 4), 2.0)
    ctl.note_cost((1, 4), 1.2)
    # wide rung measured bad, narrow rung good -> narrow is the workhorse
    for _ in range(60):
        ctl.note_tick(accepted=0, depth=4, rung=(4, 4))
        ctl.note_tick(accepted=3, depth=4, rung=(1, 4))
    picks = [ctl.rung() for _ in range(10)]
    assert picks.count((4, 4)) == 2  # one exploration tick per explore_every
    assert all(p in ((1, 4), (4, 4)) for p in picks)


def test_controller_probes_while_disabled_and_reenables():
    ctl = SpecController(rungs=[(1, 4)], probe_every=5)
    ctl.note_cost((1, 4), 2.0)
    for _ in range(100):
        ctl.note_tick(accepted=0, depth=4)
    assert ctl.rung() is None and ctl.disabled
    # plain ticks until the probe cadence elapses, then one speculative probe
    fired = [ctl.rung() for _ in range(5)]
    assert fired[:4] == [None] * 4
    assert fired[4] == (1, 4)
    # probe evidence of a workload shift (context-quoting traffic arrived)
    for _ in range(60):
        ctl.note_tick(accepted=4, depth=4)
    assert ctl.rung() == (1, 4)
    assert not ctl.disabled


def test_breakeven_accept_rate_math():
    assert breakeven_accept_rate(1.0, 6) == 0.0
    assert breakeven_accept_rate(0.5, 6) == 0.0
    assert breakeven_accept_rate(8.0, 6) == 1.0
    p = breakeven_accept_rate(2.0, 6)
    assert 0.0 < p < 1.0
    # the expected tokens/tick at the breakeven rate equals the cost ratio
    e = (1 - p ** 7) / (1 - p)
    assert abs(e - 2.0) < 1e-6
    # deeper trees break even at lower acceptance
    assert breakeven_accept_rate(2.0, 12) < p


def test_default_rungs_ladder():
    assert default_rungs(4, 6) == [(4, 6), (2, 6), (1, 6), (1, 3)]
    assert default_rungs(1, 1) == [(1, 1)]


# ---------------------------------------------------------------- engine level
def _spec_engine(cfg, params, tok, *, spec, mesh=None, **kw):
    from django_assistant_bot_tpu.serving import GenerationEngine

    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq_len", 96)
    kw.setdefault("prefix_cache_size", 0)
    if spec:
        # probe_every=1: the controller may disable on low acceptance but
        # then re-probes EVERY tick, so the speculative path (and its paged
        # commits) stays exercised for the whole equivalence run
        kw.setdefault("spec_probe_every", 1)
    return GenerationEngine(
        cfg, params, tok, mesh=mesh, speculative=spec, **kw
    )


def _run_engine(eng, jobs, timeout=600):
    eng.start()
    try:
        futs = [
            eng.submit(ids, max_tokens=mt, temperature=t) for ids, mt, t in jobs
        ]
        out = [f.result(timeout=timeout).token_ids for f in futs]
        stats = eng.tick_stats()
    finally:
        eng.stop(drain_timeout_s=60.0)
    return out, stats


def test_spec_engine_greedy_equivalence_property():
    """Pinned-seed equivalence property on the default (paged) plane, no
    mesh: ragged prompts (repetitive / quoting / no-match), mixed greedy and
    sampled rows, several seeds — greedy outputs must be identical with
    speculation on vs off, and the speculative engine must report the paged
    layout as effective."""
    from django_assistant_bot_tpu.serving import ByteTokenizer

    tok = ByteTokenizer()
    cfg = DecoderConfig.tiny()
    for seed in (0, 3):
        params = llama.init(cfg, jax.random.PRNGKey(seed))
        prompts = [
            "abc abc abc abc abc abc",
            "the cat sat on the mat the cat sat on the",
            "xyz",
            "quote me: pay invoices in the portal. pay invoices in the",
        ]
        # greedy rows interleaved with one sampled row (index 2)
        jobs = [
            (tok.encode(p), 16, 0.0 if i != 2 else 0.9)
            for i, p in enumerate(prompts)
        ]
        plain, _ = _run_engine(
            _spec_engine(cfg, params, tok, spec=0, lookahead=1, burst=4), jobs
        )
        spec, stats = _run_engine(
            _spec_engine(cfg, params, tok, spec=4, spec_width=2, lookahead=1),
            jobs,
        )
        for i in range(len(jobs)):
            if jobs[i][2] == 0.0:  # greedy rows: identical token ids
                assert spec[i] == plain[i], (seed, i)
            else:  # sampled rows: just complete within bounds
                assert 1 <= len(spec[i]) <= 16
        assert stats["spec_drafted"] > 0
        assert stats["kv"]["kv_pages_total"] > 0


def test_spec_k_bounded_against_max_seq_len():
    """An oversized K must fail at engine construction with a clear error,
    not crash opaquely inside the jitted tick (r5 review finding)."""
    from django_assistant_bot_tpu.serving import ByteTokenizer, GenerationEngine

    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.PRNGKey(5))
    with pytest.raises(ValueError, match="speculative=40 .*too large"):
        GenerationEngine(
            cfg, params, ByteTokenizer(), max_slots=2, max_seq_len=64,
            speculative=40,
        )


# --------------------------------------------------------------------- chaos
def test_tick_raise_mid_verify_restart_leaves_page_pool_clean():
    """An engine-fatal fault fired during a speculative verify dispatch:
    crash-only restart must reset the page plane (every page back on the
    free list, block tables unallocated) and the salvaged/token-less
    requests must still complete on the rebuilt pool."""
    from django_assistant_bot_tpu.serving import ByteTokenizer, GenerationEngine
    from django_assistant_bot_tpu.serving.faults import FaultInjector

    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.PRNGKey(9))
    tok = ByteTokenizer()
    inj = FaultInjector({})
    eng = GenerationEngine(
        cfg, params, tok, max_slots=2, max_seq_len=96, speculative=3,
        spec_width=2, spec_probe_every=1, prefix_cache_size=0, faults=inj,
    )
    eng.start()
    try:
        # let the engine go live, then arm: the NEXT dispatch — a speculative
        # verify tick for the in-flight request — raises mid-verify
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
        # pool clean on the LIVE engine: every page back on the free list,
        # every block table unallocated — the restart (and per-finish frees)
        # leaked nothing, no shutdown sweep involved
        kv = eng.kv_stats()
        assert kv["kv_pages_used"] == 0
        assert kv["kv_pages_free"] == eng._kv_pool.n_pages
        assert all(not pages for pages in eng._slot_pages)
    finally:
        eng.stop(drain_timeout_s=60.0)


def test_nan_logits_quarantine_frees_spec_slot_pages():
    """A poisoned speculative tick quarantines ONE slot: its pages return to
    the pool while the batch keeps decoding."""
    from django_assistant_bot_tpu.serving import ByteTokenizer, GenerationEngine
    from django_assistant_bot_tpu.serving.faults import FaultInjector

    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.PRNGKey(10))
    tok = ByteTokenizer()
    inj = FaultInjector({})
    # lookahead=0: every tick is processed the iteration it issues, so the
    # armed fault deterministically lands on the NEW wave's first live tick
    # (with a pipeline it can fire on a stale-epoch ref of the finished
    # warm request and poison nobody)
    eng = GenerationEngine(
        cfg, params, tok, max_slots=2, max_seq_len=96, speculative=3,
        spec_width=2, spec_probe_every=1, prefix_cache_size=0, faults=inj,
        lookahead=0,
    ).start()
    try:
        f0 = eng.submit(tok.encode("ab ab ab ab"), max_tokens=8, temperature=0.0)
        f0.result(timeout=120)
        inj.arm("nan_logits")
        futs = [
            eng.submit(tok.encode("ef ef ef ef"), max_tokens=8, temperature=0.0)
            for _ in range(2)
        ]
        outcomes = []
        for f in futs:
            try:
                outcomes.append(("ok", len(f.result(timeout=120).token_ids)))
            except Exception as e:
                outcomes.append(("poisoned", type(e).__name__))
        assert ("poisoned", "RequestPoisoned") in outcomes
        assert any(kind == "ok" for kind, _ in outcomes)
        assert eng.poisoned_requests == 1
        assert eng.engine_restarts == 0  # quarantine, not a restart
    finally:
        eng.stop(drain_timeout_s=60.0)
    kv = eng.kv_stats()
    assert kv["kv_pages_used"] == 0


# ----------------------------------------------------------------- slow suite
@pytest.mark.slow
def test_spec_engine_greedy_bit_identical_and_accepts(mesh8):
    """The speculative engine must produce BIT-IDENTICAL greedy output to the
    plain engine on the f32 CPU mesh, and on a repetitive prompt it must
    actually accept drafts (the counters prove the fast path ran, not a
    silent fallback).  Previously xfail'd: the old linear verify program let
    the SPMD partitioner sequence-shard its K+1 dim, which this jaxlib
    miscompiles (input tokens doubled across the seq axis); the tree verify
    forward pins that dim replicated — root-caused and fixed, so this
    passes on its merits."""
    from django_assistant_bot_tpu.parallel import shard_pytree
    from django_assistant_bot_tpu.serving import ByteTokenizer

    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.PRNGKey(3))
    with mesh8:
        params = shard_pytree(params, llama.logical_axes(cfg), mesh8)
    tok = ByteTokenizer()
    prompts = [
        "abc abc abc abc abc abc",
        "the cat sat on the mat the cat sat on the",
        "xyz",
    ]
    jobs = [(tok.encode(p), 24, 0.0) for p in prompts]

    plain, _ = _run_engine(
        _spec_engine(cfg, params, tok, spec=0, mesh=mesh8, lookahead=1, burst=4),
        jobs,
    )
    spec, stats = _run_engine(
        _spec_engine(cfg, params, tok, spec=5, spec_width=2, mesh=mesh8,
                     lookahead=1),
        jobs,
    )
    assert spec == plain  # speculation must never change greedy output
    assert stats["spec_drafted"] > 0
    # a tiny random model still loops enough for SOME acceptance on these
    # prompts; zero would mean the draft path is broken end to end
    assert stats["spec_accepted"] > 0, stats


@pytest.mark.slow
def test_spec_engine_mixed_temperature_batch_and_json_rejected(mesh8):
    """Sampled requests ride the same spec ticks (one token per tick) and
    json_format is rejected up front."""
    from django_assistant_bot_tpu.parallel import shard_pytree
    from django_assistant_bot_tpu.serving import ByteTokenizer

    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.PRNGKey(4))
    with mesh8:
        params = shard_pytree(params, llama.logical_axes(cfg), mesh8)
    tok = ByteTokenizer()
    eng = _spec_engine(
        cfg, params, tok, spec=4, spec_width=2, mesh=mesh8, max_seq_len=64
    ).start()
    try:
        with pytest.raises(ValueError, match="speculative"):
            eng.submit(tok.encode("x"), max_tokens=4, json_format=True)
        futs = [
            eng.submit(tok.encode("ab ab ab ab"), max_tokens=10, temperature=t)
            for t in (0.0, 0.9, 0.0)
        ]
        results = [f.result(timeout=600) for f in futs]
        assert all(len(r.token_ids) >= 1 for r in results)
        assert all(r.completion_tokens <= 10 for r in results)
    finally:
        eng.stop(drain_timeout_s=60.0)


@pytest.mark.slow
def test_spec_engine_with_prefix_cache_matches_plain(mesh8):
    """Speculation composed with the prefix KV cache (the production RAG
    combination: shared context prefix + greedy answer) must still match the
    plain engine's greedy output bit-for-bit on the f32 mesh, and the prefix
    cache must actually hit.  Previously xfail'd — same partitioner root
    cause as test_spec_engine_greedy_bit_identical_and_accepts."""
    from django_assistant_bot_tpu.parallel import shard_pytree
    from django_assistant_bot_tpu.serving import ByteTokenizer

    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.PRNGKey(6))
    with mesh8:
        params = shard_pytree(params, llama.logical_axes(cfg), mesh8)
    tok = ByteTokenizer()
    shared = "context: pay invoices in the portal. " * 2
    prompts = [shared + "q1?", shared + "q2 about invoices?"]
    # the byte tokenizer has no merges: [bos] + bytes(shared) is exactly the
    # shared leading block of both prompts
    plen = len(tok.encode(shared))

    def run(spec):
        eng = _spec_engine(
            cfg, params, tok, spec=spec, spec_width=2, mesh=mesh8,
            max_slots=2, max_seq_len=160, prefix_cache_size=4,
            prefix_min_tokens=8,
        ).start()
        try:
            outs = []
            for p in prompts:  # sequential: turn 2 hits turn 1's prefix
                f = eng.submit(
                    tok.encode(p), max_tokens=16, temperature=0.0,
                    prefix_len=plen,
                )
                outs.append(f.result(timeout=600).token_ids)
            hits = eng.prefix_hits
            stats = eng.tick_stats()
        finally:
            eng.stop(drain_timeout_s=60.0)
        return outs, hits, stats

    plain, _, _ = run(0)
    spec, hits, stats = run(5)
    assert spec == plain
    assert hits >= 1  # the shared context block was reused from the cache
    # the spec path must have actually run (not a silent plain fallback)
    assert stats.get("spec_drafted", 0) > 0, stats


def test_healthz_carries_spec_gauges():
    """/healthz exposes the adaptive controller per generator (accept EMA,
    rung, auto/load-disable) so operators can tell a disabled mechanism from
    a broken one without shelling into tick_stats."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from django_assistant_bot_tpu.serving.registry import ModelRegistry, ModelSpec
    from django_assistant_bot_tpu.serving.server import create_app

    registry = ModelRegistry(
        {
            "tiny-spec": ModelSpec(
                name="tiny-spec", kind="decoder", tiny=True, max_slots=2,
                max_seq_len=256, speculative=3, spec_width=2,
            )
        }
    )

    async def drive():
        client = TestClient(TestServer(create_app(registry)))
        await client.start_server()
        try:
            r = await client.get("/healthz")
            body = await r.json()
            g = body["generators"]["tiny-spec"]
            spec = g["spec"]
            for key in (
                "spec_accept_rate", "spec_accept_ema", "spec_rung_accept_emas",
                "spec_tree_width", "spec_tree_depth", "spec_auto_disabled",
                "spec_load_disabled", "spec_skipped_load", "spec_skipped_accept",
            ):
                assert key in spec, key
            assert g["kv"]["kv_pages_total"] > 0
            # the scheduler's stats carry the same gauge (bind_spec): load-
            # vs acceptance-disable side by side where queue pressure lives
            assert "spec_disabled" in g["sched"]
        finally:
            await client.close()

    try:
        asyncio.new_event_loop().run_until_complete(drive())
    finally:
        registry.stop()
