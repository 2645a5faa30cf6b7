"""Real-size compiles with the chip's own compiler, against a DESCRIBED
``v5e:2x2`` topology (on-chip-measurement §2.3): nothing runs, no chip is
needed, and what the compiler would refuse on the chip — a slice off the
tiling, too much VMEM, a program that does not fit HBM, a Mosaic kernel the
partitioner cannot split — it refuses here.  A compile that passes is not a
chip run and is never reported as one; ``chip_smoke.py`` is the chip run.

Shapes are the ones the smoke serves: the flash kernel at every whole-bucket
prefill length of Mistral-7B-v0.1 (D=128, 32 heads, the model's window), the
D=64 encoder-class head, the shard-mapped kernel on a four-device mesh, and
the paged decode/prefill programs and the encoder/KNN kernels at full widths.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding  # noqa: E402

from django_assistant_bot_tpu.models import encoder, llama  # noqa: E402
from django_assistant_bot_tpu.models.config import DecoderConfig, EncoderConfig  # noqa: E402
from django_assistant_bot_tpu.ops import attention as attn  # noqa: E402
from django_assistant_bot_tpu.ops.quant import QTensor  # noqa: E402
from django_assistant_bot_tpu.parallel import MeshAxes, make_mesh  # noqa: E402
from django_assistant_bot_tpu.serving.engine import prefill_shapes  # noqa: E402

MISTRAL_7B = DecoderConfig(
    vocab_size=32_000, hidden_size=4096, intermediate_size=14_336, num_layers=32,
    num_heads=32, num_kv_heads=8, head_dim=128, max_seq_len=32_768,
    rope_theta=10_000.0, sliding_window=4096, dtype=jnp.bfloat16,
)
QWEN25_7B = DecoderConfig(  # the benchmark's configuration (benchmarks/configs/qwen2.5-7b-instruct.json)
    vocab_size=152_064, hidden_size=3584, intermediate_size=18_944, num_layers=28,
    num_heads=28, num_kv_heads=4, head_dim=128, max_seq_len=32_768,
    rope_theta=1_000_000.0, attn_bias=True, dtype=jnp.bfloat16,
)
SLOTS, MAX_SEQ, PAGE = 8, 2048, 512  # the smoke's and the benchmark's serving geometry


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A described-device compile is written to the persistent cache but can
    never be read back without a chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _int8_decoder_shapes(cfg: DecoderConfig, sharding):
    """The int8 parameter tree's shapes, built by hand: ``llama.init_int8`` and
    ``quantize_decoder_params`` do host work ``jax.eval_shape`` cannot trace."""
    L, E, F, V = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    H, KH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    def dense(*shape):
        return _sds(shape, cfg.dtype, sharding)

    def q8(*shape):
        return QTensor(
            q=_sds(shape, jnp.int8, sharding),
            scale=_sds(shape[:-2] + (1, shape[-1]), jnp.float32, sharding),
        )

    layers = {
        "attn_norm": dense(L, E), "mlp_norm": dense(L, E),
        "wq": q8(L, E, H * D), "wk": q8(L, E, KH * D), "wv": q8(L, E, KH * D),
        "wo": q8(L, H * D, E),
        "w_gate": q8(L, E, F), "w_up": q8(L, E, F), "w_down": q8(L, F, E),
    }
    if cfg.attn_bias:
        layers.update(bq=dense(L, H * D), bk=dense(L, KH * D), bv=dense(L, KH * D))
    return {
        "tok_embed": dense(V, E),
        "final_norm": dense(E),
        "lm_head": dense(E, V),
        "layers": layers,
    }


def _paged_cache_shapes(cfg: DecoderConfig, kv_dtype, sharding):
    n_pages = SLOTS * MAX_SEQ // PAGE
    shape = (cfg.num_layers, n_pages, cfg.num_kv_heads, PAGE, cfg.head_dim)
    return llama.PagedKVCache(
        k=_sds(shape, kv_dtype, sharding),
        v=_sds(shape, kv_dtype, sharding),
        lengths=_sds((SLOTS,), jnp.int32, sharding),
    )


FLASH_CASES = [
    # (B, H, S, D, Dv, window, id)
    (1, 32, 256, 128, 128, 4096, "mistral-bucket-256"),
    (1, 32, 512, 128, 128, 4096, "mistral-bucket-512"),
    (1, 32, 1024, 128, 128, 4096, "mistral-bucket-1024"),
    (1, 32, 2048, 128, 128, 4096, "mistral-bucket-2048"),
    (8, 32, 512, 128, 128, 4096, "mistral-wave-8x512"),
    (1, 32, 1024, 64, 64, None, "head-dim-64"),
    (1, 32, 1024, 128, 128, 256, "window-256-bites"),
    (1, 32, 16384, 64, 64, None, "chunked-kv-16384"),
    # what the benchmark's two configurations dispatch (tick_stats()["prefill_shapes"]):
    # Qwen2.5-7B's 28 heads at every bucket of whole blocks and its waves, A.X-K1's 64
    # heads at key width 192 padded to 256 against value width 128
    *[(1, 28, S, 128, 128, None, f"qwen-1x{S}") for S in (256, 384, 512, 640, 768, 896, 1024)],
    (2, 28, 512, 128, 128, None, "qwen-2x512"),
    (4, 28, 256, 128, 128, None, "qwen-4x256"),
    (1, 64, 512, 256, 128, None, "a.x-k1-1x512"),
    (1, 64, 1024, 256, 128, None, "a.x-k1-1x1024"),
]
# what a flash program may ask of a core's 128 MiB of VMEM (its blocks twice, its
# state, a score tile's temporaries, and the room Mosaic wants): half
FLASH_VMEM_CAP = 64 << 20


def _flash_call_vmem_bytes(text: str) -> int:
    """The scoped VMEM the compiled flash call was given (its ``vmem_limit_bytes``),
    from the custom call's own line of the optimised HLO."""
    line = next(l for l in text.splitlines() if "tpu_custom_call" in l and "flash_attention" in l)
    return int(re.search(r'"scoped_memory_configs":\[\{"memory_space":"1","offset":"\d+","size":"(\d+)"', line).group(1))


@pytest.mark.parametrize(
    "B,H,S,D,Dv,window", [c[:6] for c in FLASH_CASES], ids=[c[6] for c in FLASH_CASES]
)
def test_flash_kernel_compiles_for_one_v5e(topo, B, H, S, D, Dv, window):
    """Mosaic takes the kernel at the tile ``flash_tiles`` picks for the shape
    (it refuses a program whose VMEM passes what it asked for), the call asks
    for no more than half a core's VMEM, and nothing is copied around it
    at lane-wide heads."""
    one = SingleDeviceSharding(topo.devices[0])
    qk, vv = _sds((B, H, S, D), jnp.bfloat16, one), _sds((B, H, S, Dv), jnp.bfloat16, one)
    compiled = (
        jax.jit(lambda q, k, v: attn.flash_attention(q, k, v, causal=True, window=window, scale=0.1309))
        .lower(qk, qk, vv)
        .compile()
    )
    assert _flash_call_vmem_bytes(compiled.as_text()) <= FLASH_VMEM_CAP
    if D % 128 == 0:  # a 64-wide head is laid out anew around the call by XLA, as it was before
        assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize(
    "axes,B,H,S,D,Dv",
    [
        (MeshAxes(model=4), 1, 32, 1024, 128, 128),
        (MeshAxes(data=2, model=2), 8, 32, 1024, 128, 128),
        (MeshAxes(data=2, model=2), 1, 32, 1024, 128, 128),
        (MeshAxes(model=4), 1, 28, 384, 128, 128),
        (MeshAxes(model=4), 1, 28, 1024, 128, 128),
        (MeshAxes(model=4), 1, 64, 1024, 256, 128),
    ],
    ids=["tp4-one-row", "dp2xtp2-wave", "dp2xtp2-one-row-replicated-batch",
         "tp4-qwen-7-heads-a-device-384", "tp4-qwen-7-heads-a-device-1024", "tp4-a.x-k1-16-heads-a-device"],
)
def test_shard_mapped_flash_kernel_compiles_on_a_four_device_mesh(topo, axes, B, H, S, D, Dv):
    """Bare, this call is refused on a mesh ("Mosaic kernels cannot be
    automatically partitioned"); under shard_map every device gets its heads,
    and the tile is sized from the heads a device holds."""
    mesh = make_mesh(axes, devices=topo.devices)
    sharding = NamedSharding(mesh, P("data" if B % axes.data == 0 else None, "model", None, None))
    qk, vv = _sds((B, H, S, D), jnp.bfloat16, sharding), _sds((B, H, S, Dv), jnp.bfloat16, sharding)
    compiled = (
        jax.jit(
            lambda q, k, v: attn.sharded_flash_attention(
                q, k, v, mesh, causal=True, window=4096
            )
        )
        .lower(qk, qk, vv)
        .compile()
    )
    text = compiled.as_text()
    assert _flash_call_vmem_bytes(text) <= FLASH_VMEM_CAP
    # heads split over `model`: no device computes more than its share
    assert f"bf16[{B // axes.data if B % axes.data == 0 else B},{H // axes.model},{S},{Dv}]" in text


def test_bare_flash_kernel_is_refused_on_a_mesh(topo):
    """The fault the shard_map repairs, kept as a test so the reason stays true."""
    mesh = make_mesh(MeshAxes(model=4), devices=topo.devices)
    x = _sds((1, 32, 1024, 128), jnp.bfloat16, NamedSharding(mesh, P(None, "model", None, None)))
    with pytest.raises(Exception, match="Mosaic kernels cannot be automatically partitioned"):
        jax.jit(lambda q, k, v: attn.flash_attention(q, k, v, causal=True)).lower(x, x, x).compile()


def test_attention_dispatch_reaches_the_kernel_under_a_mesh(topo, monkeypatch):
    """``attention()`` asks JAX for the backend, which is the CPU here; the test
    steers that one question and checks the dispatch takes the shard-mapped
    kernel inside ``mesh_scope`` and never the jnp path."""
    from django_assistant_bot_tpu.parallel.sharding import mesh_scope

    monkeypatch.setattr(attn.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        attn, "dot_product_attention", lambda *a, **k: pytest.fail("fell to the jnp path")
    )
    mesh = make_mesh(MeshAxes(model=4), devices=topo.devices)
    x = _sds((1, 32, 512, 128), jnp.bfloat16, NamedSharding(mesh, P(None, "model", None, None)))
    with mesh_scope(mesh):
        compiled = (
            jax.jit(lambda q, k, v: attn.attention(q, k, v, causal=True, window=4096))
            .lower(x, x, x)
            .compile()
        )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kv_dtype", [jnp.float8_e4m3fn, jnp.bfloat16], ids=["fp8-kv", "bf16-kv"])
def test_paged_decode_compiles_at_mistral_7b_widths(topo, kv_dtype):
    one = SingleDeviceSharding(topo.devices[0])
    params = _int8_decoder_shapes(MISTRAL_7B, one)
    cache = _paged_cache_shapes(MISTRAL_7B, kv_dtype, one)
    tokens = _sds((SLOTS,), jnp.int32, one)
    bt = _sds((SLOTS, MAX_SEQ // PAGE), jnp.int32, one)
    compiled = (
        jax.jit(lambda p, t, c, b: llama.decode_step_paged(p, MISTRAL_7B, t, c, b))
        .lower(params, tokens, cache, bt)
        .compile()
    )
    mem = compiled.memory_analysis()
    # int8 weights resident (~7.5 GB) + the page pool, inside one chip's 16 GB
    assert 7.0e9 < mem.argument_size_in_bytes < 12.0e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.0e9


def _fused_tick(cfg, steps=8):
    """``GenerationEngine._make_decode_tick``'s body (plain, paged): the params
    behind an ``optimization_barrier``, ``decode_step_paged``, sampling, scanned
    ``steps`` times with the cache in the carry."""
    from django_assistant_bot_tpu.ops.sampling import sample_logits

    def tick(params, tokens, cache, active, bt, temps, top_ps, rng):
        def body(carry, _):
            tokens, cache, rng = carry
            p = jax.lax.optimization_barrier(params)
            rng, sub = jax.random.split(rng)
            logits, cache = llama.decode_step_paged(p, cfg, tokens, cache, bt, active=active)
            nxt = sample_logits(logits, sub, temperature=temps, top_k=0, top_p=top_ps)
            return (nxt, cache, rng), nxt

        (tokens, cache, rng), toks = jax.lax.scan(body, (tokens, cache, rng), None, length=steps)
        return toks, tokens, cache, rng

    return tick


def _tick_args(cfg, params_sharding, cache_shardings, rep):
    cache = _paged_cache_shapes(cfg, jnp.bfloat16, rep)
    cache = llama.PagedKVCache(
        k=_sds(cache.k.shape, cache.k.dtype, cache_shardings.k),
        v=_sds(cache.v.shape, cache.v.dtype, cache_shardings.v),
        lengths=cache.lengths,
    )
    return (
        _int8_decoder_shapes(cfg, params_sharding), _sds((SLOTS,), jnp.int32, rep), cache,
        _sds((SLOTS,), jnp.bool_, rep), _sds((SLOTS, MAX_SEQ // PAGE), jnp.int32, rep),
        _sds((SLOTS,), jnp.float32, rep), _sds((SLOTS,), jnp.float32, rep),
        _sds((2,), jnp.uint32, rep),
    )


# opcodes that name a pool-sized value without making one: the loops' own
# plumbing, and the kernel, whose pool outputs alias its pool operands
_NO_NEW_BUFFER = {
    "parameter", "get-tuple-element", "bitcast", "tuple", "while", "conditional",
    "call", "opt-barrier", "custom-call",
}


def _pool_sized_values_made_in_loops(text: str, shapes) -> list[str]:
    """Instructions outside the entry computation whose result holds a whole
    layer of the pool, or the whole pool, and that would have to write it."""
    import re

    made, in_entry = [], False
    for line in text.splitlines():
        if line.startswith(("ENTRY ", "%", "}")) and not line.startswith("  "):
            in_entry = line.startswith("ENTRY ")
            continue
        m = re.match(r"\s+(?:ROOT )?%?[\w.\-]+ = (.*?) ([a-z][\w\-]*)\(", line)
        if not m or in_entry or m.group(2) in _NO_NEW_BUFFER:
            continue
        if any(f"bf16[{shape}]" in m.group(1) for shape in shapes):
            made.append(line.strip()[:160])
    return made


@pytest.mark.parametrize(
    "cfg,temp_limit",
    # Qwen: 1.41 GB of temporaries before, 0.94 GB of it the pool's double; 0.48 GB now.
    # Mistral's pool is 2.15 GB (8 KV heads, 32 layers): 0.81 GB of temporaries cannot hold
    # a second one, nor one of K or V alone (1.07 GB)
    [(QWEN25_7B, 0.6e9), (MISTRAL_7B, 1.0e9)],
    ids=["qwen2.5-7b-cell", "mistral-7b-smoke"],
)
def test_fused_tick_touches_the_pool_only_where_it_must(topo, monkeypatch, cfg, temp_limit):
    """ISSUE 25's compiled-program criterion, at the benchmark cell's widths and
    the smoke's: the donated pool is updated in place by the kernel, there is
    no second pool among the temporaries, and no operation inside the tick's
    step loop or layer loop makes a value the size of one layer of the pool.  The xs -> ys form
    sliced, re-laid-out and wrote back a whole layer, for K and for V, in every
    layer of every step: 5.5 ms of a 17.8 ms step (PERF.md section 5)."""
    monkeypatch.setattr(attn.jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(topo.devices[0])
    args = _tick_args(cfg, one, llama.PagedKVCache(k=one, v=one, lengths=one), one)
    compiled = jax.jit(_fused_tick(cfg), donate_argnums=(2,)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    mem = compiled.memory_analysis()
    pool_bytes = 2 * 2 * cfg.num_layers * (SLOTS * MAX_SEQ // PAGE) * cfg.num_kv_heads * PAGE * cfg.head_dim
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < temp_limit
    n_pages, kh = SLOTS * MAX_SEQ // PAGE, cfg.num_kv_heads
    layer = f"{n_pages},{kh},{PAGE},{cfg.head_dim}"
    assert _pool_sized_values_made_in_loops(text, [layer, f"{cfg.num_layers},{layer}"]) == []


def test_fused_tick_kernel_is_shard_mapped_over_kv_heads_on_a_four_device_mesh(topo, monkeypatch):
    """Bare, a Mosaic call on a mesh is refused (above); under ``mesh_scope``
    the kernel runs per device on its KV heads' share of every page."""
    from django_assistant_bot_tpu.parallel.sharding import mesh_scope

    monkeypatch.setattr(attn.jax, "default_backend", lambda: "tpu")
    cfg = QWEN25_7B
    mesh = make_mesh(MeshAxes(model=4), devices=topo.devices)
    rep = NamedSharding(mesh, P())
    with mesh_scope(mesh):
        args = _tick_args(cfg, rep, llama.paged_cache_shardings(cfg, mesh, SLOTS), rep)
        compiled = jax.jit(_fused_tick(cfg), donate_argnums=(2,)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    n_pages, kh = SLOTS * MAX_SEQ // PAGE, cfg.num_kv_heads // 4
    assert f"bf16[{cfg.num_layers},{n_pages},{kh},{PAGE},{cfg.head_dim}]" in text  # each device its head
    layer = f"{n_pages},{kh},{PAGE},{cfg.head_dim}"
    assert _pool_sized_values_made_in_loops(text, [layer, f"{cfg.num_layers},{layer}"]) == []
    assert compiled.memory_analysis().alias_size_in_bytes >= (
        2 * 2 * cfg.num_layers * n_pages * kh * PAGE * cfg.head_dim
    )


def test_paged_prefill_chunk_compiles_at_mistral_7b_widths(topo):
    one = SingleDeviceSharding(topo.devices[0])
    params = _int8_decoder_shapes(MISTRAL_7B, one)
    cache = _paged_cache_shapes(MISTRAL_7B, jnp.bfloat16, one)
    scalar = _sds((), jnp.int32, one)
    jax.jit(
        lambda p, i, c, bt, s, st, v: llama.prefill_chunk_paged(
            p, MISTRAL_7B, i, c, bt, s, st, v
        )
    ).lower(
        params, _sds((1, 1024), jnp.int32, one), cache,
        _sds((MAX_SEQ // PAGE,), jnp.int32, one), scalar, scalar, scalar,
    ).compile()


# the largest prefill programs the smoke's and the benchmark's geometry warms: one chunk's positions
CHUNK_FULL = [(rows, bucket) for bucket, row_counts in prefill_shapes(1024, SLOTS).items()
              for rows in row_counts if rows * bucket == 1024]


@pytest.mark.parametrize("rows,bucket", CHUNK_FULL, ids=[f"{r}x{b}" for r, b in CHUNK_FULL])
def test_paged_suffix_prefill_wave_loads_beside_the_weights(topo, rows, bucket):
    """The kind of program that stopped the first chip boot: a full admission
    wave of prefix-hit suffix prefill (then 8 rows x bucket 1024: gathering
    every layer's rows up front needed 8.9 GB of temporaries next to 9.7 GB of
    weights and pool; per layer, with the pool updated in place, ~2.3 GB,
    almost all of it one layer's f32 attention scores).  A program now holds
    one chunk's positions at most (``prefill_shapes``): every row count that
    fills a chunk is compiled here."""
    one = SingleDeviceSharding(topo.devices[0])
    params = _int8_decoder_shapes(MISTRAL_7B, one)
    cache = _paged_cache_shapes(MISTRAL_7B, jnp.bfloat16, one)

    def i32(*shape):
        return _sds(shape, jnp.int32, one)

    compiled = (
        jax.jit(
            lambda p, i, c, bt, sl, st, v: llama.prefill_suffix_paged(
                p, MISTRAL_7B, i, c, bt, sl, st, v
            ),
            donate_argnums=(2,),
        )
        .lower(params, i32(rows, bucket), cache, i32(rows, MAX_SEQ // PAGE),
               i32(rows), i32(rows), i32(rows))
        .compile()
    )
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2.0e9  # the donated pool is updated in place
    assert mem.temp_size_in_bytes < 3.0e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13.0e9


@pytest.mark.parametrize("rows,bucket", [(1, 1024), (2, 384), (1, 640)])
def test_whole_bucket_prefill_takes_the_kernel_at_mistral_7b_widths(topo, monkeypatch, rows, bucket):
    """The program the smoke's 512-1024-token prompt runs: ``llama.prefill`` at
    bucket 1024, int8 — the Pallas kernel inside the layer scan; and buckets
    that are whole flash blocks without being powers of two take it too."""
    monkeypatch.setattr(attn.jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(topo.devices[0])
    params = _int8_decoder_shapes(MISTRAL_7B, one)
    compiled = (
        jax.jit(lambda p, i, n: llama.prefill(p, MISTRAL_7B, i, n))
        .lower(params, _sds((rows, bucket), jnp.int32, one), _sds((rows,), jnp.int32, one))
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_encoder_compiles_at_rubert_base_geometry(topo):
    one = SingleDeviceSharding(topo.devices[0])
    cfg = EncoderConfig()
    shapes = jax.eval_shape(lambda: encoder.init(cfg, jax.random.key(0)))
    params = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one), shapes)
    ids = _sds((32, 128), jnp.int32, one)
    jax.jit(lambda p, i, m: encoder.encode(p, cfg, i, m)).lower(params, ids, ids).compile()


@pytest.mark.parametrize("rows", [131_072, 1_048_576], ids=["100k-padded", "1M-padded"])
def test_knn_topk_compiles_at_corpus_scale(topo, rows):
    """Exact KNN scoring + top-k at the smoke's corpus (100,000 rows pad to
    2^17) and at the 1M corpus the README quotes (pads to 2^20)."""
    from django_assistant_bot_tpu.storage.knn import _topk_scores_impl

    one = SingleDeviceSharding(topo.devices[0])
    jax.jit(_topk_scores_impl, static_argnums=(3,)).lower(
        _sds((rows, 768), jnp.bfloat16, one),
        _sds((8, 768), jnp.float32, one),
        _sds((rows,), jnp.bool_, one),
        16,
    ).compile()


# ---------------------------------------------------------------------------
# the latent-attention MoE block at the benchmark configuration's sizes
# (benchmarks/configs/a.x-k1-ep16.json: rank 0 of a 16-way expert-parallel
# deployment, one dense + six expert layers, 32 slots x 2048, pages of 512)
# ---------------------------------------------------------------------------

LM_SLOTS, LM_PAGES = 32, 128
LM_LAYER_EXPERTS = ["12,7168,2048", "12,2048,7168"]  # one layer's held experts: gate / up, down


def _latent_moe_cfg():
    import json

    from django_assistant_bot_tpu.models import mla_moe  # noqa: F401

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmarks", "configs", "a.x-k1-ep16.json")
    with open(path) as f:
        return DecoderConfig.from_hf(json.load(f)["hf"], dtype=jnp.bfloat16)


def _latent_moe_args(cfg, one):
    from django_assistant_bot_tpu.models import mla_moe

    params = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one),
                          jax.eval_shape(lambda: mla_moe.held_params(cfg, mla_moe.init(cfg, jax.random.key(0)))))
    cache = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one),
                         jax.eval_shape(lambda: mla_moe.init_paged_cache(cfg, LM_SLOTS, LM_PAGES, PAGE)))
    return params, cache


def test_latent_moe_fused_tick_touches_the_latent_pool_only_where_it_must(topo, monkeypatch):
    """PR 25's criterion for the pool of one array: the compiled tick reaches
    the Pallas call, the donated pool is aliased through, and nothing inside
    the step loop or the layer loops makes a value the size of a layer of it."""
    from django_assistant_bot_tpu.models import mla_moe
    from django_assistant_bot_tpu.ops.sampling import sample_logits

    monkeypatch.setattr(attn.jax, "default_backend", lambda: "tpu")
    cfg = _latent_moe_cfg()
    one = SingleDeviceSharding(topo.devices[0])
    params, cache = _latent_moe_args(cfg, one)

    def tick(params, tokens, cache, active, bt, temps, top_ps, rng):
        def body(carry, _):
            tokens, cache, rng = carry
            p = jax.lax.optimization_barrier(params)
            rng, sub = jax.random.split(rng)
            logits, cache = mla_moe.decode_step_paged(p, cfg, tokens, cache, bt, active=active)
            nxt = sample_logits(logits, sub, temperature=temps, top_k=0, top_p=top_ps)
            return (nxt, cache, rng), nxt

        (tokens, cache, rng), toks = jax.lax.scan(body, (tokens, cache, rng), None, length=8)
        return toks, tokens, cache, rng

    args = (
        params, _sds((LM_SLOTS,), jnp.int32, one), cache, _sds((LM_SLOTS,), jnp.bool_, one),
        _sds((LM_SLOTS, MAX_SEQ // PAGE), jnp.int32, one), _sds((LM_SLOTS,), jnp.float32, one),
        _sds((LM_SLOTS,), jnp.float32, one), _sds((2,), jnp.uint32, one),
    )
    compiled = jax.jit(tick, donate_argnums=(2,)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    mem = compiled.memory_analysis()
    W = cfg.latent_moe.latent_width
    pool_bytes = 2 * cfg.num_layers * LM_PAGES * PAGE * W
    assert mem.alias_size_in_bytes >= pool_bytes
    # weights 9.7 GB + pool 0.59 GB resident; no second pool among the temporaries, and (PR 43) no weight
    # either: held as the checkpoint holds them, `w_uq`, `w_uk`, `w_uv` and `w_dkv` were re-laid out at the
    # tick's entry, 456 MB of temporaries, and a layer of the first three copied into fast memory in every
    # layer of every step (the scan's `constant_dynamic-slice_fusion`); what is left is the router's 16.5 MB
    assert 9.0e9 < mem.argument_size_in_bytes < 11.5e9
    assert mem.temp_size_in_bytes < 64e6
    assert "constant_dynamic-slice_fusion" not in text
    layer = f"{LM_PAGES},{PAGE},{W}"
    assert _pool_sized_values_made_in_loops(text, [layer, f"{cfg.num_layers},{layer}"]) == []
    # the held experts are read where they lie, by (layer, expert), by the step's own Pallas
    # call: no loop makes a value the size of a layer's experts (1.06 GB for the three)
    assert "held_experts" in text
    assert _pool_sized_values_made_in_loops(text, LM_LAYER_EXPERTS) == []


@pytest.mark.parametrize("bucket", [256, 1024])
def test_latent_moe_prefill_takes_the_flash_kernel_and_fits(topo, monkeypatch, bucket):
    """One admission's prefill at the cell's buckets: the flash kernel at key
    width 192 (padded to 256) against value width 128, the grouped matmul over
    the held experts, and temporaries that fit beside 10.3 GB of weights and pool."""
    from django_assistant_bot_tpu.models import mla_moe

    monkeypatch.setattr(attn.jax, "default_backend", lambda: "tpu")
    cfg = _latent_moe_cfg()
    one = SingleDeviceSharding(topo.devices[0])
    params, _ = _latent_moe_args(cfg, one)
    compiled = (
        jax.jit(lambda p, i, n: mla_moe.prefill(p, cfg, i, n))
        .lower(params, _sds((1, bucket), jnp.int32, one), _sds((1,), jnp.int32, one))
        .compile()
    )
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "flash_attention" in text and "held_experts" in text
    # no copy of a layer's 12 held experts out of the stack for a tile loop to index
    # (a scan's slice of them was one: 10% of the cell's device time, PERF.md section 6, PR 30)
    assert _pool_sized_values_made_in_loops(text, LM_LAYER_EXPERTS) == []
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes < 14.5e9


def test_latent_moe_chunk_prefill_reads_the_held_experts_in_place(topo, monkeypatch):
    """The third program that runs the expert layers (a 1,024-token chunk against the
    slot's pages): the same call over the live tiles, no layer's experts copied, the
    donated pool aliased through."""
    from django_assistant_bot_tpu.models import mla_moe

    monkeypatch.setattr(attn.jax, "default_backend", lambda: "tpu")
    cfg = _latent_moe_cfg()
    one = SingleDeviceSharding(topo.devices[0])
    params, cache = _latent_moe_args(cfg, one)
    scalar = _sds((), jnp.int32, one)
    compiled = (
        jax.jit(lambda p, i, c, bt, s, st, v: mla_moe.prefill_chunk_paged(p, cfg, i, c, bt, s, st, v),
                donate_argnums=(2,))
        .lower(params, _sds((1, 1024), jnp.int32, one), cache, _sds((MAX_SEQ // PAGE,), jnp.int32, one),
               scalar, scalar, scalar)
        .compile()
    )
    text = compiled.as_text()
    assert "held_experts" in text
    assert _pool_sized_values_made_in_loops(text, LM_LAYER_EXPERTS) == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * cfg.num_layers * LM_PAGES * PAGE * cfg.latent_moe.latent_width
    assert mem.temp_size_in_bytes < 1.0e9


# ---------------------------------------------------------------------------
# the module's second block form, the shortcut-connected double layer, at the
# benchmark configuration's widths, depth and serving geometry
# (benchmarks/configs/longcat-flash-omni-ep32.json: hidden 6144, 64 heads, 4 double
# layers, 16 of 512 routed experts held beside 256 identity experts, 32 slots x 2,048)
# ---------------------------------------------------------------------------

SC_LAYER_EXPERTS = ["16,6144,2048", "16,2048,6144"]  # one layer's held experts: gate / up, down
SC_SUBLAYER_FFN = ["2,6144,12288", "2,12288,6144", "6144,12288", "12288,6144"]  # a dense FFN matrix, of both sublayers or one


def _scmoe_cfg():
    import json

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmarks", "configs", "longcat-flash-omni-ep32.json")
    with open(path) as f:
        return DecoderConfig.from_hf(json.load(f)["hf"], dtype=jnp.bfloat16)


def test_double_layer_fused_tick_reads_every_weight_where_it_lies(topo, monkeypatch):
    """The tick of the double layer at the cell's widths: both Pallas calls, the donated pool (two rows a layer)
    aliased through, temporaries that hold no copy of a sublayer's weights (with the sublayers' stack as the scan's
    ``xs`` in ``[layers, 2, ...]`` form the compiler copied both sublayers' FFN matrices out a layer: 302 MB of
    temporaries, three such copies a layer a step), no layer-sized copy of the pool or of the held experts."""
    from django_assistant_bot_tpu.models import mla_moe
    from django_assistant_bot_tpu.ops.sampling import sample_logits

    monkeypatch.setattr(attn.jax, "default_backend", lambda: "tpu")
    cfg = _scmoe_cfg()
    one = SingleDeviceSharding(topo.devices[0])
    params, cache = _latent_moe_args(cfg, one)
    assert cache.kv.shape == (8, LM_PAGES, PAGE, 640)

    def tick(params, tokens, cache, active, bt, temps, top_ps, rng):
        def body(carry, _):
            tokens, cache, rng = carry
            p = jax.lax.optimization_barrier(params)
            rng, sub = jax.random.split(rng)
            logits, cache = mla_moe.decode_step_paged(p, cfg, tokens, cache, bt, active=active)
            nxt = sample_logits(logits, sub, temperature=temps, top_k=0, top_p=top_ps)
            return (nxt, cache, rng), nxt

        (tokens, cache, rng), toks = jax.lax.scan(body, (tokens, cache, rng), None, length=8)
        return toks, tokens, cache, rng

    args = (
        params, _sds((LM_SLOTS,), jnp.int32, one), cache, _sds((LM_SLOTS,), jnp.bool_, one),
        _sds((LM_SLOTS, MAX_SEQ // PAGE), jnp.int32, one), _sds((LM_SLOTS,), jnp.float32, one),
        _sds((LM_SLOTS,), jnp.float32, one), _sds((2,), jnp.uint32, one),
    )
    compiled = jax.jit(tick, donate_argnums=(2,)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "latent_decode" in text and "held_experts" in text
    mem = compiled.memory_analysis()
    pool_bytes = 2 * 8 * LM_PAGES * PAGE * 640
    assert mem.alias_size_in_bytes >= pool_bytes
    # weights 10.35 GB + pool 0.67 GB resident; among the temporaries no second pool and no weight
    assert 10.8e9 < mem.argument_size_in_bytes < 11.3e9
    assert mem.temp_size_in_bytes < 64e6
    assert "constant_dynamic-slice_fusion" not in text
    layer = f"{LM_PAGES},{PAGE},640"
    assert _pool_sized_values_made_in_loops(text, [layer, f"8,{layer}"]) == []
    assert _pool_sized_values_made_in_loops(text, SC_LAYER_EXPERTS) == []
    # a sublayer's weights are the scan body's own slice of the stack and feed the dot: nothing writes one out
    writes = [l for l in _pool_sized_values_made_in_loops(text, SC_SUBLAYER_FFN) if " fusion(" not in l and " dynamic-slice(" not in l]
    assert writes == []


def test_double_layer_prefill_takes_the_flash_kernel_and_fits(topo, monkeypatch):
    """One admission's prefill at the cell's larger bucket: the flash kernel at key width 192 (padded to 256), the
    grouped call over the held experts' live tiles (picks on identity experts are in none), and temporaries
    that fit beside 11.0 GB of weights and pool."""
    from django_assistant_bot_tpu.models import mla_moe

    monkeypatch.setattr(attn.jax, "default_backend", lambda: "tpu")
    cfg = _scmoe_cfg()
    one = SingleDeviceSharding(topo.devices[0])
    params, _ = _latent_moe_args(cfg, one)
    compiled = (
        jax.jit(lambda p, i, n: mla_moe.prefill(p, cfg, i, n))
        .lower(params, _sds((1, 1024), jnp.int32, one), _sds((1,), jnp.int32, one))
        .compile()
    )
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "flash_attention" in text and "held_experts" in text
    assert _pool_sized_values_made_in_loops(text, SC_LAYER_EXPERTS) == []
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.0e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes + 0.67e9 < 14.5e9


# ---------------------------------------------------------------------------
# the same block with a lightning indexer and top-k sparse attention, at the
# benchmark configuration's widths and serving geometry and a depth of two
# (benchmarks/configs/deepseek-v3.2-ep16.json: 128 heads, 8 slots x 16,384,
# pages of 512; one dense + one expert layer: the two scan bodies)
# ---------------------------------------------------------------------------

DSA_SLOTS, DSA_SEQ, DSA_PAGES = 8, 16_384, 264  # 264: a layer of a pool is no other value's shape


def _dsa_args(one):
    import json

    from django_assistant_bot_tpu.models import mla_moe

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmarks", "configs", "deepseek-v3.2-ep16.json")
    with open(path) as f:
        hf = dict(json.load(f)["hf"], num_hidden_layers=2)
    cfg = DecoderConfig.from_hf(hf, dtype=jnp.bfloat16)
    params = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one),
                          jax.eval_shape(lambda: mla_moe.held_params(cfg, mla_moe.init(cfg, jax.random.key(0)))))
    cache = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one),
                         jax.eval_shape(lambda: mla_moe.init_paged_cache(cfg, DSA_SLOTS, DSA_PAGES, PAGE)))
    return cfg, params, cache


def _dsa_pool_shapes(cfg):
    """One layer of either pool.  (A whole pool is the result of the in-place
    scatters that write a step's rows; that it is not copied shows in the
    aliased bytes and in temporaries smaller than it.)"""
    lm = cfg.latent_moe
    layers = [f"{DSA_PAGES},{PAGE},{w}" for w in (lm.latent_width, lm.index_head_dim)]
    return layers + [f"1,{s}" for s in layers]


def test_sparse_chunk_prefill_makes_nothing_of_heads_x_chunk_x_context_size(topo, monkeypatch):
    """A 1,024-token chunk against 16,384 positions of pages: both Pallas
    kernels lower for the v5e (index scores reduced over the 64 index heads in
    VMEM; the flash kernel under the selection as an int8 mask), the donated
    pools are aliased through, no layer of either pool is copied, and the
    temporaries hold no [heads, chunk, context] scores (8.6 GB in float32 at
    128 heads; 2.1 GB in bfloat16 at the indexer's 64)."""
    from django_assistant_bot_tpu.models import mla_moe

    monkeypatch.setattr(attn.jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(topo.devices[0])
    cfg, params, cache = _dsa_args(one)
    scalar = _sds((), jnp.int32, one)
    compiled = (
        jax.jit(lambda p, i, c, bt, s, st, v: mla_moe.prefill_chunk_paged(p, cfg, i, c, bt, s, st, v),
                donate_argnums=(2,))
        .lower(params, _sds((1, 1024), jnp.int32, one), cache, _sds((DSA_SEQ // PAGE,), jnp.int32, one),
               scalar, scalar, scalar)
        .compile()
    )
    text = compiled.as_text()
    assert "index_scores" in text and "masked_flash_attention" in text and "held_experts" in text
    lm = cfg.latent_moe
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * cfg.num_layers * DSA_PAGES * PAGE * (lm.latent_width + lm.index_head_dim)
    # the expanded keys and values of the view (1.6 GB), the [context, chunk] index scores and
    # the selection (64 + 16 MB) and the experts' tiles: under 3 GB, where the scores alone were 8.6
    assert mem.temp_size_in_bytes < 3.0e9
    for heads in (cfg.num_heads, lm.index_n_heads):
        for dims in (f"{heads},1024,{DSA_SEQ}", f"1024,{heads},{DSA_SEQ}", f"{DSA_SEQ},{heads},1024", f"{DSA_SEQ},1024,{heads}"):
            assert not re.search(rf"= \(?(bf16|f32)\[(1,)?{dims}\]", text), dims
    assert _pool_sized_values_made_in_loops(text, _dsa_pool_shapes(cfg)) == []


def test_sparse_chunk_selection_is_one_conditional_over_the_live_steps_of_the_view(topo, monkeypatch):
    """``sparse_select`` alone at the cell's shapes (1,024 queries, 64 index heads of 128, a view of 16,384,
    ``index_topk`` 2,048): ONE conditional of 8 branches; seven score and count, the index kernel lowered at 4,096 ...
    16,384 keys by 2,048 and the float32 scores of each at that width and no wider; the branch for ``live <=
    index_topk`` holds no float32 ``[view, queries]`` operand (no score is made, nothing is counted)."""
    monkeypatch.setattr(attn.jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(topo.devices[0])
    C, S, Hi, Di, topk = 1024, DSA_SEQ, 64, 128, 2048
    widths = attn.select_widths(S, topk, 512)
    assert widths == tuple(range(4096, S + 1, 2048))
    text = (
        jax.jit(lambda q, w, k, qpos, ok, live: attn.sparse_select(q, w, k, qpos, ok, topk, live))
        .lower(_sds((1, C, Hi, Di), jnp.bfloat16, one), _sds((1, C, Hi), jnp.float32, one), _sds((1, S, Di), jnp.bfloat16, one),
               _sds((1, C), jnp.int32, one), _sds((1, C, S), jnp.bool_, one), _sds((1,), jnp.int32, one))
        .compile().as_text()
    )
    branches = [m.split(", ") for m in re.findall(r" conditional\(.*?branch_computations=\{([^}]*)\}", text)]
    (switch,) = [b for b in branches if len(b) > 2]
    assert len(switch) == 1 + len(widths) and len(branches) == 1 + len(widths)  # and a two-way tie rule a counting branch
    bodies = [re.search(rf"^{re.escape(name)} .*?^}}", text, re.M | re.S).group(0) for name in switch]
    assert not re.search(r"f32\[", bodies[0]) and "tpu_custom_call" not in bodies[0]
    for body, width in zip(bodies[1:], widths):
        assert "index_scores" in body
        assert set(re.findall(rf"f32\[1,(\d+),{C}\]", body)) == {str(width)}


def test_sparse_decode_step_follows_the_live_pages_in_three_pallas_calls(topo, monkeypatch):
    """The decode step with an indexer: index scores, the selection by counting
    and the attention under it lower for the v5e as Pallas calls over the
    block tables; both donated pools are aliased through and no layer of
    either is copied; nothing of [slots, view] x (heads or key width) size is
    made, and nothing the size of a slot's view is sorted (the router's and
    the sampler's sorts are over experts and vocabulary)."""
    from django_assistant_bot_tpu.models import mla_moe

    monkeypatch.setattr(attn.jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(topo.devices[0])
    cfg, params, cache = _dsa_args(one)
    assert mla_moe.decode_kv_path(cfg, jnp.bfloat16, PAGE) == "kernel"
    compiled = (
        jax.jit(lambda p, t, c, bt, a: mla_moe.decode_step_paged(p, cfg, t, c, bt, active=a), donate_argnums=(2,))
        .lower(params, _sds((DSA_SLOTS,), jnp.int32, one), cache, _sds((DSA_SLOTS, DSA_SEQ // PAGE), jnp.int32, one),
               _sds((DSA_SLOTS,), jnp.bool_, one))
        .compile()
    )
    text = compiled.as_text()
    for kernel in ("paged_index_scores", "topk_select", "sparse_latent_decode"):
        assert kernel in text, kernel
    lm = cfg.latent_moe
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * cfg.num_layers * DSA_PAGES * PAGE * (lm.latent_width + lm.index_head_dim)
    # no weight among the temporaries (PR 43): in the checkpoint's form this step re-laid out `w_uq`, `w_uk`, `w_uv`,
    # `w_iq`, `w_dkv` and the head (16,160 columns: the device's layout of it is the transposed one) at its entry
    assert mem.temp_size_in_bytes < 64e6
    assert "constant_dynamic-slice_fusion" not in text
    assert _pool_sized_values_made_in_loops(text, _dsa_pool_shapes(cfg)) == []
    NB = DSA_SEQ // PAGE
    # a slot's view appears only as the [slots, blocks, page] scores and selection: never with a heads or a width axis,
    # never flat (the plain path's [slots, view] scores, `ok` and top_k), never gathered rows
    assert f"f32[{DSA_SLOTS},{NB},{PAGE}]" in text and f"s32[{DSA_SLOTS},{NB},{PAGE}]" in text
    # (bf16[slots, 16384] is the attention's output, heads x value width, on its way into `wo`)
    assert not re.search(rf"(f32|s32|u32|pred)\[{DSA_SLOTS},{DSA_SEQ}\]", text)
    assert not re.search(rf"\[{DSA_SLOTS},(1,)?\d+,({DSA_SEQ}|{NB},{PAGE})\]", text)
    assert not re.search(rf"\[{DSA_SLOTS},({DSA_SEQ}|{NB},{PAGE}|{lm.index_topk}),\d+\]", text)
    for line in text.splitlines():
        if re.search(r"\b(sort|topk|top_k|TopK)\b", line):
            assert str(DSA_SEQ) not in line and f"{NB},{PAGE}" not in line, line
