"""The paged entry points against the benchmark's plain float32 reference.

``benchmarks/reference/decoder.py`` imports nothing of the program and keeps no
cache: it is what ``correct`` holds a served token to on the chip.  Here the
same comparison runs at tiny widths on the CPU, over every way the engine
fills and reads the page pool (ROADMAP D7's first step): a model test that
compares ``llama``'s paths with ``llama.forward`` shares the program's rope,
norm and projections with what it checks; this one shares nothing.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import decoder as reference
from django_assistant_bot_tpu.models import DecoderConfig, llama
from django_assistant_bot_tpu.ops.quant import QTensor, quantize_decoder_params
from paged import Paged

PAGE, CTX, STEPS = 8, 64, 4
# float32 on both sides; the orders of summation differ (pages, online softmax):
# read 1.2e-6 to 2.4e-6 on logits of standard deviation 1
F32_ATOL = 2e-5


def _hf(cfg):
    """The published keys the reference reads, from the program's config."""
    return {
        "model_type": "mistral", "hidden_size": cfg.hidden_size, "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads, "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim, "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
        "sliding_window": cfg.sliding_window,
    }


def _f32(leaf, i):
    if isinstance(leaf, QTensor):  # int8 weight-only: the value the step's dot stands for
        return jnp.asarray(np.asarray(leaf.q[i], np.float32) * np.asarray(leaf.scale[i], np.float32))
    return jnp.asarray(leaf[i], jnp.float32)


def _reference_rows(cfg, params, seqs, firsts):
    """Reference logits of each sequence at positions ``first .. len - 2``."""
    top = {k: jnp.asarray(params[k], jnp.float32) for k in ("tok_embed", "final_norm", "lm_head")}
    return reference.logits_at(
        _hf(cfg), lambda i: {k: _f32(v, i) for k, v in params["layers"].items()}, top, seqs, firsts
    )


def _plain(kv, params, prompts):
    ids = np.zeros((len(prompts), max(map(len, prompts))), np.int32)
    for b, p in enumerate(prompts):
        ids[b, : len(p)] = p
    return kv.prefill(params, ids, [len(p) for p in prompts])


def _chunked(kv, params, prompts):
    """Row 0 in two chunks of 12: [0, 12) ends inside page 1, [12, 20) crosses
    into page 2; the second carries 4 pad tokens.  Row 1 in one prefill."""
    (long, short) = prompts
    assert len(long) == 20
    kv.chunk(params, [long[:12]], 0, 0, 12)
    last = kv.chunk(params, [long[12:] + [0] * 4], 0, 12, 8)
    one = kv.prefill(params, [short], [len(short)], slots=[1])
    return jnp.concatenate([last, one])


def _shared_prefix(kv, params, prompts):
    """Row 0 prefills its whole prompt; row 1, whose first 16 tokens are row
    0's, is handed row 0's first two pages and prefills its suffix alone."""
    (owner, sharer) = prompts
    assert owner[:16] == sharer[:16]
    first = kv.prefill(params, [owner], [len(owner)], slots=[0])
    kv.bt = kv.bt.at[1, :2].set(kv.bt[0, :2])
    suffix = sharer[16:]
    second = kv.suffix(params, [suffix + [0] * (8 - len(suffix))], [1], [16], [len(suffix)])
    return jnp.concatenate([first, second])


def _bias(params, cfg):
    """``llama.init`` draws zero biases; give q/k/v ones the logits feel."""
    layers = dict(params["layers"])
    for n, key in enumerate(("bq", "bk", "bv")):
        layers[key] = 0.5 * jax.random.normal(jax.random.key(40 + n), layers[key].shape, jnp.float32)
    return {**params, "layers": layers}


_RNG = np.random.default_rng(34)
_P20, _P5, _P13, _P9 = (_RNG.integers(1, 512, n).tolist() for n in (20, 5, 13, 9))


@pytest.mark.parametrize(
    "cfg_kw, prompts, fill, active, kv_dtype, quantize, atol",
    [
        pytest.param({}, [_P13, _P9], _plain, None, None, None, F32_ATOL, id="gqa"),
        pytest.param({"attn_bias": True}, [_P13, _P9], _plain, None, None, None, F32_ATOL, id="qkv_biases"),
        # 20-token prompts, 4 more decoded: every row read lies past a window of 6
        pytest.param({"sliding_window": 6}, [_P20, _P13], _plain, None, None, None, F32_ATOL, id="window_shorter_than_context"),
        pytest.param({}, [_P20, _P5], _chunked, None, None, None, F32_ATOL, id="prompt_chunked_across_a_page_boundary"),
        pytest.param({}, [_P20, _P20[:16] + _P5], _shared_prefix, None, None, None, F32_ATOL, id="suffix_prefill_after_a_shared_prefix"),
        # row 1 is never decoded: its length and pages stay as prefill left them
        pytest.param({}, [_P20, _P9, _P5], _plain, [True, False, True], None, None, F32_ATOL, id="ragged_batch_with_an_inactive_row"),
        # e4m3 keeps 3 bits of mantissa: K and V are off by up to 2^-4 of their
        # value; over 2 layers that reaches the logits (standard deviation 1) as 0.06-0.09
        pytest.param({}, [_P13, _P9], _plain, None, jnp.float8_e4m3fn, None, 0.15, id="fp8_kv"),
        # weight-only int8: the reference is given q * scale, so what is left
        # is the order of the scaling (after the dot, not before)
        pytest.param({}, [_P13, _P9], _plain, None, None, "int8", F32_ATOL, id="int8_weights"),
    ],
)
def test_paged_prefill_and_decode_match_the_plain_reference(cfg_kw, prompts, fill, active, kv_dtype, quantize, atol):
    cfg = dataclasses.replace(DecoderConfig.tiny(), max_seq_len=CTX, **cfg_kw)
    params = llama.init(cfg, jax.random.key(34))
    if cfg.attn_bias:
        params = _bias(params, cfg)
    if quantize:
        params = quantize_decoder_params(params, fmt=quantize)
    B = len(prompts)
    live = [True] * B if active is None else active
    forced = np.random.default_rng(B).integers(1, cfg.vocab_size, (B, STEPS)).tolist()  # teacher-forced: any tokens do
    seqs = [p + (f if a else f[:1]) for p, f, a in zip(prompts, forced, live)]
    want = _reference_rows(cfg, params, seqs, [len(p) - 1 for p in prompts])

    kv = Paged(cfg, batch=B, max_len=CTX, page=PAGE, dtype=kv_dtype or jnp.float32)
    got = [[row] for row in np.asarray(fill(kv, params, prompts))]
    for step in range(STEPS - 1):
        logits = np.asarray(kv.decode(
            params, [f[step] for f in forced], **({} if active is None else {"active": jnp.asarray(active)})
        ))
        for b in range(B):
            if live[b]:
                got[b].append(logits[b])
    for b in range(B):
        assert len(got[b]) == len(want[b]) == (STEPS if live[b] else 1)
        np.testing.assert_allclose(np.stack(got[b]), want[b], atol=atol, rtol=0, err_msg=f"row {b}")
    assert np.asarray(kv.cache.lengths).tolist() == [len(s) - 1 for s in seqs]
