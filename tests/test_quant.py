"""Weight-only int8 quantization (ops/quant.py): accuracy, decode parity,
sharded serving integration."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from django_assistant_bot_tpu.models import DecoderConfig, llama
from django_assistant_bot_tpu.ops.quant import (
    QTensor,
    QTensor4,
    QUANTIZABLE,
    deq,
    num_weights,
    pack_int4,
    qeinsum,
    quantize_decoder_params,
    quantize_tensor,
    quantize_tensor_int4,
    unpack_int4,
    weight_bits,
)
from paged import Paged


def test_quantize_tensor_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(3, 64, 32)).astype(np.float32))
    qt = quantize_tensor(w)
    assert qt.q.dtype == jnp.int8 and qt.scale.shape == (3, 1, 32)
    back = deq(qt, jnp.float32)
    # symmetric int8: error bounded by scale/2 per element
    max_err = float(jnp.max(jnp.abs(back - w)))
    assert max_err <= float(jnp.max(qt.scale)) * 0.51


# ------------------------------------------------------- int4 grouped format
def test_int4_pack_unpack_roundtrip_exact():
    rng = np.random.default_rng(1)
    vals = rng.integers(-8, 8, (5, 10, 7)).astype(np.int8)
    packed = pack_int4(vals)
    assert packed.dtype == np.uint8 and packed.shape == (5, 5, 7)
    np.testing.assert_array_equal(
        np.asarray(unpack_int4(jnp.asarray(packed))), vals
    )


def test_quantize_tensor_int4_roundtrip_error_bounded():
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.normal(size=(3, 64, 32)).astype(np.float32))
    qt = quantize_tensor_int4(w, group_size=16)
    assert qt.q.dtype == jnp.uint8 and qt.q.shape == (3, 32, 32)
    assert qt.scale.shape == (3, 4, 32) and qt.group_size == 16
    back = deq(qt, jnp.float32)
    # symmetric int4: error bounded by scale/2 per element
    max_err = float(jnp.max(jnp.abs(back - w)))
    assert max_err <= float(jnp.max(qt.scale)) * 0.51


def test_int4_group_size_clamps_to_even_divisor():
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.normal(size=(24, 8)).astype(np.float32))
    qt = quantize_tensor_int4(w, group_size=64)  # 64 > dim -> whole-dim group
    assert qt.group_size == 24
    qt = quantize_tensor_int4(w, group_size=10)  # 10 doesn't divide -> 8
    assert 24 % qt.group_size == 0 and qt.group_size % 2 == 0


def test_int4_qeinsum_matches_dequantized_reference():
    """The in-dot grouped contraction IS the dequantized dot, reassociated —
    the kernel-identity bound every int4 throughput claim rides on."""
    rng = np.random.default_rng(4)
    w = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32))
    qt = quantize_tensor_int4(w, group_size=16)
    x = jnp.asarray(rng.normal(size=(2, 5, 64)).astype(np.float32))
    got = qeinsum("bse,eo->bso", x, qt, jnp.float32)
    ref = jnp.einsum("bse,eo->bso", x, deq(qt, jnp.float32))
    assert float(jnp.max(jnp.abs(got - ref))) < 1e-4
    # ellipsis pattern (the lm_head shape) takes the same path
    got2 = qeinsum("...e,eo->...o", x, qt, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(got2), rtol=1e-6)


def test_quantize_decoder_params_int4_and_weight_accounting():
    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.PRNGKey(0))
    q4 = quantize_decoder_params(params, fmt="int4", group_size=16)
    for key in QUANTIZABLE:
        if key in q4["layers"]:
            assert isinstance(q4["layers"][key], QTensor4)
    # packed formats count UNPACKED weights, scales excluded
    assert num_weights(q4) == num_weights(params)
    assert weight_bits(q4) == 4
    assert weight_bits(quantize_decoder_params(params)) == 8
    assert weight_bits(params) == 16
    with pytest.raises(ValueError, match="format"):
        quantize_decoder_params(params, fmt="int2")


def test_int4_forward_error_bounded_vs_full_precision():
    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.PRNGKey(0))
    q4 = quantize_decoder_params(params, fmt="int4", group_size=16)
    ids = jnp.asarray(
        np.random.default_rng(1).integers(1, 100, (2, 12)), jnp.int32
    )
    full = np.asarray(llama.forward(params, cfg, ids))
    quant = np.asarray(llama.forward(q4, cfg, ids))
    rel = np.abs(quant - full).max() / max(np.abs(full).max(), 1e-6)
    # 4-bit grouped on a RANDOM tiny model is the worst case (no outlier
    # structure); the bench records the measured bound per run
    assert rel < 0.5, rel


def test_init_int4_shapes_and_decode():
    cfg = DecoderConfig.tiny()
    p4 = llama.init_int4(cfg, jax.random.PRNGKey(0), group_size=16)
    wq = p4["layers"]["wq"]
    assert isinstance(wq, QTensor4) and wq.q.dtype == jnp.uint8
    assert wq.group_size == 16
    # prefill + decode run end to end on the packed weights
    kv = Paged(cfg, batch=1, max_len=32)
    logits = kv.prefill(p4, [[5, 6, 7, 8, 9]], [5])
    logits2 = kv.decode(p4, [int(jnp.argmax(logits[0]))])
    assert np.isfinite(np.asarray(logits2)).all()


@pytest.mark.slow
def test_quantized_forward_close_and_decode_consistent():
    cfg = DecoderConfig.tiny()
    params = llama.init(cfg, jax.random.PRNGKey(0))
    qparams = quantize_decoder_params(params)
    for key in QUANTIZABLE:
        if key in qparams["layers"]:
            assert isinstance(qparams["layers"][key], QTensor)
    ids = jnp.asarray(np.random.default_rng(1).integers(1, 100, (2, 12)), jnp.int32)
    full = np.asarray(llama.forward(params, cfg, ids))
    quant = np.asarray(llama.forward(qparams, cfg, ids))
    # int8 per-channel error stays a small fraction of the logit scale
    rel = np.abs(quant - full).max() / max(np.abs(full).max(), 1e-6)
    assert rel < 0.05, rel

    # prefill+decode on the QUANTIZED params agrees with the quantized forward
    prompt = np.asarray(ids[:1, :5])
    seq = prompt.copy()
    for _ in range(4):
        logits = llama.forward(qparams, cfg, jnp.asarray(seq))
        seq = np.concatenate([seq, [[int(jnp.argmax(logits[0, -1]))]]], axis=1)
    expected = seq[0, prompt.shape[1]:].tolist()

    kv = Paged(cfg, batch=1, max_len=32)
    logits = kv.prefill(qparams, prompt, [prompt.shape[1]])
    got = [int(jnp.argmax(logits[0]))]
    for _ in range(3):
        logits = kv.decode(qparams, [got[-1]])
        got.append(int(jnp.argmax(logits[0])))
    assert got == expected


def test_quantized_sharded_engine_generates(mesh8, tmp_db):
    """QTensor leaves ride shard_pytree's sharding tree as a prefix; the full
    registry->engine path serves a quantized model on the 8-device mesh."""
    from django_assistant_bot_tpu.serving.registry import ModelRegistry, ModelSpec

    registry = ModelRegistry(mesh=mesh8)
    spec = ModelSpec(
        name="tiny-q8", kind="decoder", tiny=True, quantize="int8",
        max_slots=2, max_seq_len=64,
    )
    registry.specs = {"tiny-q8": spec}
    registry.load(spec)
    eng = registry.get_generator("tiny-q8")
    try:
        fut = eng.submit([3, 7, 11], max_tokens=6, temperature=0.0)
        res = fut.result(timeout=600)
        assert len(res.token_ids) == 6
        # greedy determinism across a second request
        fut2 = eng.submit([3, 7, 11], max_tokens=6, temperature=0.0)
        assert fut2.result(timeout=600).token_ids == res.token_ids
    finally:
        registry.stop()


@pytest.mark.slow
def test_registry_warmup_knob(mesh8, tmp_db):
    """warmup=true compiles shapes at load; the engine then serves normally."""
    from django_assistant_bot_tpu.serving.registry import ModelRegistry, ModelSpec

    registry = ModelRegistry(mesh=mesh8)
    spec = ModelSpec(
        name="warm", kind="decoder", tiny=True, warmup=True, warmup_json=True,
        max_slots=2, max_seq_len=64,
    )
    registry.specs = {"warm": spec}
    registry.load(spec)
    eng = registry.get_generator("warm")
    try:
        res = eng.submit([5, 9], max_tokens=4, temperature=0.0).result(timeout=600)
        assert len(res.token_ids) == 4
        # json variants were compiled too (FSM exists before first json request)
        assert eng._fsm is not None and eng._decode_tick_json is not None
    finally:
        registry.stop()


def test_unknown_quantize_rejected(mesh8):
    from django_assistant_bot_tpu.serving.registry import ModelRegistry, ModelSpec

    registry = ModelRegistry(mesh=mesh8)
    # int4 became a supported format (docs/QUANT.md) — int2 stays unknown
    with pytest.raises(ValueError, match="unknown quantize"):
        registry.load(
            ModelSpec(name="bad", kind="decoder", tiny=True, quantize="int2")
        )


def test_init_int8_quantize_embed_serves():
    """int8 embedding/head tables (the 8B HBM-fit path): forward, prefill and
    decode all run, logits finite, and param bytes shrink accordingly."""
    import jax
    import numpy as np

    from django_assistant_bot_tpu.models import DecoderConfig, llama

    cfg = DecoderConfig.tiny()
    p_bf16 = llama.init_int8(cfg, jax.random.PRNGKey(0))
    p_q = llama.init_int8(cfg, jax.random.PRNGKey(0), quantize_embed=True)
    from django_assistant_bot_tpu.ops.quant import QTensor

    assert isinstance(p_q["tok_embed"], QTensor)
    assert sum(l.nbytes for l in jax.tree.leaves(p_q)) < sum(
        l.nbytes for l in jax.tree.leaves(p_bf16)
    )
    ids = np.arange(1, 9, dtype=np.int32)[None]
    logits = llama.forward(p_q, cfg, ids)
    assert np.isfinite(np.asarray(logits)).all()
    kv = Paged(cfg, batch=1, max_len=32)
    kv.prefill(p_q, ids, [ids.shape[1]])
    step_logits = kv.decode(p_q, [3])
    assert np.isfinite(np.asarray(step_logits)).all()


def test_init_int8_host_rng_same_structure_and_serves():
    """host_rng=True (the virtual-mesh fast path — numpy bytes instead of
    on-device threefry) must produce the identical pytree structure/shapes/
    dtypes as the device draw, and the model must run on it."""
    import jax
    import numpy as np

    from django_assistant_bot_tpu.models import DecoderConfig, llama

    for cfg in (DecoderConfig.tiny(), DecoderConfig.tiny(num_experts=4)):
        p_dev = llama.init_int8(cfg, jax.random.PRNGKey(1))
        p_host = llama.init_int8(cfg, jax.random.PRNGKey(1), host_rng=True)
        flat_d = jax.tree_util.tree_flatten_with_path(p_dev)[0]
        flat_h = jax.tree_util.tree_flatten_with_path(p_host)[0]
        assert [p for p, _ in flat_d] == [p for p, _ in flat_h]
        for (_, a), (_, b) in zip(flat_d, flat_h):
            assert a.shape == b.shape and a.dtype == b.dtype
        ids = np.arange(1, 9, dtype=np.int32)[None]
        logits = llama.forward(p_host, cfg, ids)
        assert np.isfinite(np.asarray(logits)).all()
