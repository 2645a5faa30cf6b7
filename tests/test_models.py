"""Model correctness: parity vs HF transformers (torch CPU) + decode/forward agreement.

This is the test style SURVEY.md §4 prescribes adapted to the model plane: real
checkpoints are too big for CI, so tiny randomly-initialised HF models are saved to
disk and loaded through the production safetensors loader — the full load→convert→
forward path runs for real, only the scale is fake.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from django_assistant_bot_tpu.models import DecoderConfig, encoder, llama
from django_assistant_bot_tpu.models.hf_loader import load_decoder, load_encoder
from paged import Paged


@pytest.fixture(scope="module")
def tiny_bert_dir(tmp_path_factory):
    from transformers import BertConfig, BertModel

    cfg = BertConfig(
        vocab_size=128,
        hidden_size=32,
        num_hidden_layers=2,
        num_attention_heads=4,
        intermediate_size=64,
        max_position_embeddings=64,
    )
    model = BertModel(cfg)
    model.eval()
    d = tmp_path_factory.mktemp("tiny_bert")
    model.save_pretrained(d, safe_serialization=True)
    return str(d), model


@pytest.fixture(scope="module")
def tiny_llama_dir(tmp_path_factory):
    from transformers import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(
        vocab_size=128,
        hidden_size=32,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        intermediate_size=64,
        max_position_embeddings=128,
        rope_theta=10000.0,
        tie_word_embeddings=False,
    )
    model = LlamaForCausalLM(cfg)
    model.eval()
    d = tmp_path_factory.mktemp("tiny_llama")
    model.save_pretrained(d, safe_serialization=True)
    return str(d), model


@pytest.fixture(scope="module")
def tiny_qwen2_dir(tmp_path_factory):
    from transformers import Qwen2Config, Qwen2ForCausalLM

    cfg = Qwen2Config(
        vocab_size=128,
        hidden_size=32,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        intermediate_size=64,
        max_position_embeddings=128,
        rope_theta=10000.0,
        tie_word_embeddings=False,
    )
    model = Qwen2ForCausalLM(cfg)
    model.eval()
    d = tmp_path_factory.mktemp("tiny_qwen2")
    model.save_pretrained(d, safe_serialization=True)
    return str(d), model


@pytest.fixture(scope="module")
def tiny_gemma_dir(tmp_path_factory):
    from transformers import GemmaConfig, GemmaForCausalLM

    cfg = GemmaConfig(
        vocab_size=128,
        hidden_size=32,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=16,  # gemma decouples head_dim from hidden/heads
        intermediate_size=64,
        max_position_embeddings=128,
        rope_theta=10000.0,
    )
    model = GemmaForCausalLM(cfg)
    model.eval()
    d = tmp_path_factory.mktemp("tiny_gemma")
    model.save_pretrained(d, safe_serialization=True)
    return str(d), model


def test_encoder_matches_hf(tiny_bert_dir):
    import torch

    d, hf_model = tiny_bert_dir
    cfg, params = load_encoder(d, dtype=jnp.float32)
    ids = np.array([[5, 9, 17, 3, 0, 0], [8, 2, 0, 0, 0, 0]], np.int32)
    mask = np.array([[1, 1, 1, 1, 0, 0], [1, 1, 0, 0, 0, 0]], np.int32)

    with torch.no_grad():
        hf_out = hf_model(
            input_ids=torch.tensor(ids, dtype=torch.long),
            attention_mask=torch.tensor(mask, dtype=torch.long),
        ).last_hidden_state.numpy()

    ours = np.asarray(encoder.forward(params, cfg, jnp.asarray(ids), jnp.asarray(mask)))
    # padding positions diverge (we don't mask them out of the residual stream) —
    # compare only real tokens
    for b in range(ids.shape[0]):
        n = mask[b].sum()
        np.testing.assert_allclose(ours[b, :n], hf_out[b, :n], atol=2e-4, rtol=1e-3)


def test_encoder_encode_pools_and_normalizes(tiny_bert_dir):
    d, _ = tiny_bert_dir
    cfg, params = load_encoder(d, dtype=jnp.float32)
    ids = jnp.asarray(np.random.default_rng(0).integers(1, 100, (3, 8)), jnp.int32)
    mask = jnp.ones((3, 8), jnp.int32)
    out = encoder.encode(params, cfg, ids, mask, normalize=True)
    assert out.shape == (3, cfg.hidden_size)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(out), axis=-1), 1.0, atol=1e-5)


def test_llama_matches_hf(tiny_llama_dir):
    import torch

    d, hf_model = tiny_llama_dir
    cfg, params = load_decoder(d, dtype=jnp.float32)
    ids = np.array([[1, 5, 9, 17, 3, 25, 7, 2]], np.int32)
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids, dtype=torch.long)).logits.numpy()
    ours = np.asarray(llama.forward(params, cfg, jnp.asarray(ids)))
    np.testing.assert_allclose(ours, hf_logits, atol=3e-4, rtol=1e-3)


def test_qwen2_matches_hf(tiny_qwen2_dir):
    """Qwen2 family = Llama geometry + q/k/v projection biases."""
    import torch

    d, hf_model = tiny_qwen2_dir
    cfg, params = load_decoder(d, dtype=jnp.float32)
    assert cfg.attn_bias
    # saved biases are random (HF init), so this exercises the bias path for real
    ids = np.array([[1, 5, 9, 17, 3, 25, 7, 2]], np.int32)
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids, dtype=torch.long)).logits.numpy()
    ours = np.asarray(llama.forward(params, cfg, jnp.asarray(ids)))
    np.testing.assert_allclose(ours, hf_logits, atol=3e-4, rtol=1e-3)


def test_gemma_matches_hf(tiny_gemma_dir):
    """Gemma family: GeGLU MLP, (1+w) RMSNorm (folded at load), sqrt(E)-scaled
    embeddings, tied head, decoupled head_dim."""
    import torch

    d, hf_model = tiny_gemma_dir
    cfg, params = load_decoder(d, dtype=jnp.float32)
    assert cfg.hidden_act == "gelu_tanh"
    assert cfg.embed_multiplier == pytest.approx(32 ** 0.5)
    assert cfg.tie_embeddings and cfg.head_dim == 16
    ids = np.array([[1, 5, 9, 17, 3, 25, 7, 2]], np.int32)
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids, dtype=torch.long)).logits.numpy()
    ours = np.asarray(llama.forward(params, cfg, jnp.asarray(ids)))
    np.testing.assert_allclose(ours, hf_logits, atol=3e-4, rtol=1e-3)


def test_llama31_rope_scaling_matches_hf(tmp_path):
    """Llama-3.1-style checkpoints carry rope_scaling type 'llama3'; the
    frequency remap must match HF's (silently ignoring it would misplace
    every position past the original context)."""
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(
        vocab_size=128,
        hidden_size=32,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        intermediate_size=64,
        max_position_embeddings=256,
        rope_theta=10000.0,
        rope_scaling={
            "rope_type": "llama3",
            "factor": 8.0,
            "low_freq_factor": 1.0,
            "high_freq_factor": 4.0,
            "original_max_position_embeddings": 64,
        },
        tie_word_embeddings=False,
    )
    model = LlamaForCausalLM(cfg)
    model.eval()
    d = tmp_path / "llama31"
    model.save_pretrained(d, safe_serialization=True)
    jcfg, params = load_decoder(str(d), dtype=jnp.float32)
    assert jcfg.rope_scaling == (8.0, 1.0, 4.0, 64.0)
    # long enough that scaled and unscaled frequencies clearly diverge
    ids = np.asarray(
        np.random.default_rng(3).integers(1, 128, (1, 96)), np.int32
    )
    with torch.no_grad():
        hf_logits = model(torch.tensor(ids, dtype=torch.long)).logits.numpy()
    ours = np.asarray(llama.forward(params, jcfg, jnp.asarray(ids)))
    np.testing.assert_allclose(ours, hf_logits, atol=5e-4, rtol=1e-3)


def test_sliding_window_config_semantics():
    """Windowed attention runs natively now: full advertised context stays
    usable (no clamp) and HF's per-family gating flags map onto
    (sliding_window, window_layer_start)."""
    from django_assistant_bot_tpu.models.config import DecoderConfig

    base = dict(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=4, num_attention_heads=4,
        max_position_embeddings=4096,
    )
    # Mistral/Phi-3 style: window active in every layer, context NOT clamped
    cfg = DecoderConfig.from_hf({**base, "sliding_window": 1024})
    assert cfg.max_seq_len == 4096
    assert cfg.sliding_window == 1024
    assert cfg.window_layer_start == 0
    # Qwen2 style: window present but disabled -> full attention
    cfg = DecoderConfig.from_hf(
        {**base, "sliding_window": 1024, "use_sliding_window": False}
    )
    assert cfg.sliding_window is None
    # qwen2 family omitting the flag: HF defaults it OFF for qwen2 only
    cfg = DecoderConfig.from_hf(
        {**base, "model_type": "qwen2", "sliding_window": 1024}
    )
    assert cfg.sliding_window is None
    # qwen2 with the flag on: layers [0, max_window_layers) stay full
    cfg = DecoderConfig.from_hf(
        {
            **base,
            "model_type": "qwen2",
            "sliding_window": 1024,
            "use_sliding_window": True,
            "max_window_layers": 2,
        }
    )
    assert cfg.sliding_window == 1024
    assert cfg.window_layer_start == 2
    # absent max_window_layers falls back to HF's default of 28 (not 0 — that
    # would window every layer HF keeps full)
    cfg = DecoderConfig.from_hf(
        {
            **base,
            "model_type": "qwen2",
            "sliding_window": 1024,
            "use_sliding_window": True,
        }
    )
    assert cfg.window_layer_start == 28


def test_mistral_sliding_window_matches_hf(tmp_path):
    """Prompt LONGER than the window — the parity case the round-2 clamp
    truncated (reference capability bar: 8k contexts via Ollama serve the
    full prompt, .env.example:12-19)."""
    import torch
    from transformers import MistralConfig, MistralForCausalLM

    cfg = MistralConfig(
        vocab_size=128,
        hidden_size=32,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        intermediate_size=64,
        max_position_embeddings=128,
        rope_theta=10000.0,
        sliding_window=4,
        tie_word_embeddings=False,
    )
    model = MistralForCausalLM(cfg)
    model.eval()
    d = tmp_path / "mistral"
    model.save_pretrained(d, safe_serialization=True)
    jcfg, params = load_decoder(str(d), dtype=jnp.float32)
    assert jcfg.sliding_window == 4
    assert jcfg.max_seq_len == 128
    ids = np.array([[1, 5, 9, 17, 3, 25, 7, 2, 11, 4, 19, 6]], np.int32)  # 12 > 4
    with torch.no_grad():
        hf_logits = model(torch.tensor(ids, dtype=torch.long)).logits.numpy()
    ours = np.asarray(llama.forward(params, jcfg, jnp.asarray(ids)))
    np.testing.assert_allclose(ours, hf_logits, atol=3e-4, rtol=1e-3)
    # sanity: the window actually changes the result
    full = dataclasses.replace(jcfg, sliding_window=None)
    ours_full = np.asarray(llama.forward(params, full, jnp.asarray(ids)))
    assert np.abs(ours_full - ours).max() > 1e-3


def test_qwen2_window_layer_split_matches_hf(tmp_path):
    """Qwen2 max_window_layers: layer 0 full, layer 1 windowed — the split-scan
    path must agree with HF's per-layer layer_types masks."""
    import torch
    from transformers import Qwen2Config, Qwen2ForCausalLM

    cfg = Qwen2Config(
        vocab_size=128,
        hidden_size=32,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        intermediate_size=64,
        max_position_embeddings=128,
        rope_theta=10000.0,
        use_sliding_window=True,
        sliding_window=4,
        max_window_layers=1,
        tie_word_embeddings=False,
    )
    model = Qwen2ForCausalLM(cfg)
    model.eval()
    d = tmp_path / "qwen2win"
    model.save_pretrained(d, safe_serialization=True)
    jcfg, params = load_decoder(str(d), dtype=jnp.float32)
    assert jcfg.sliding_window == 4
    assert jcfg.window_layer_start == 1
    ids = np.array([[1, 5, 9, 17, 3, 25, 7, 2, 11, 4, 19, 6]], np.int32)
    with torch.no_grad():
        hf_logits = model(torch.tensor(ids, dtype=torch.long)).logits.numpy()
    ours = np.asarray(llama.forward(params, jcfg, jnp.asarray(ids)))
    np.testing.assert_allclose(ours, hf_logits, atol=3e-4, rtol=1e-3)


@pytest.mark.slow
def test_windowed_prefill_chunk_decode_matches_forward(tmp_path):
    """Windowed banded masks over the slot cache: prefill / chunked prefill /
    decode must all agree with the full windowed forward beyond the window."""
    import torch
    from transformers import MistralConfig, MistralForCausalLM

    hf_cfg = MistralConfig(
        vocab_size=128,
        hidden_size=32,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        intermediate_size=64,
        max_position_embeddings=128,
        rope_theta=10000.0,
        sliding_window=4,
        tie_word_embeddings=False,
    )
    model = MistralForCausalLM(hf_cfg)
    model.eval()
    d = tmp_path / "mistral2"
    model.save_pretrained(d, safe_serialization=True)
    cfg, params = load_decoder(str(d), dtype=jnp.float32)
    prompt = np.array([[1, 5, 9, 17, 3, 25, 7, 2, 11, 4]], np.int32)  # 10 > 4
    n_new = 5

    seq = prompt.copy()
    for _ in range(n_new):
        logits = llama.forward(params, cfg, jnp.asarray(seq))
        seq = np.concatenate([seq, [[int(jnp.argmax(logits[0, -1]))]]], axis=1)
    expected = seq[0, prompt.shape[1]:].tolist()

    # monolithic prefill + decode
    kv = Paged(cfg, batch=1, max_len=32, dtype=jnp.float32)
    logits = kv.prefill(params, prompt, [prompt.shape[1]], slots=[0])
    got = [int(jnp.argmax(logits[0]))]
    for _ in range(n_new - 1):
        logits = kv.decode(params, [got[-1]])
        got.append(int(jnp.argmax(logits[0])))
    assert got == expected

    # chunked prefill (two chunks of 5; the second spans the window boundary)
    kv = Paged(cfg, batch=1, max_len=32, dtype=jnp.float32)
    kv.chunk(params, prompt[:, :5], slot=0, start=0, valid=5)
    logits = kv.chunk(params, prompt[:, 5:], slot=0, start=5, valid=5)
    got = [int(jnp.argmax(logits[0]))]
    for _ in range(n_new - 1):
        logits = kv.decode(params, [got[-1]])
        got.append(int(jnp.argmax(logits[0])))
    assert got == expected


def test_unsupported_rope_scaling_rejected(tiny_llama_dir, tmp_path):
    import json
    import shutil

    d, _ = tiny_llama_dir
    bad = tmp_path / "badrope"
    shutil.copytree(d, bad)
    cfg = json.loads((bad / "config.json").read_text())
    cfg["rope_scaling"] = {"rope_type": "dynamic", "factor": 4.0}
    (bad / "config.json").write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match="unsupported rope_scaling"):
        load_decoder(str(bad))


def test_phi3_longrope_matches_hf(tmp_path):
    """Phi-3 128k longrope: short-factor regime (prompt within the pretrained
    context) AND long-factor regime (table built past it) both match HF.
    Round 2 rejected these checkpoints at load (hf_loader)."""
    import torch
    from transformers import Phi3Config, Phi3ForCausalLM

    common = dict(
        vocab_size=128,
        hidden_size=32,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        intermediate_size=64,
        rope_theta=10000.0,
        tie_word_embeddings=False,
        pad_token_id=0,
        original_max_position_embeddings=32,
        rope_scaling={
            "type": "longrope",
            "short_factor": [1.0, 1.1, 1.2, 1.3],
            "long_factor": [2.0, 2.5, 3.0, 4.0],
        },
    )
    rng = np.random.default_rng(4)

    # Our short/long choice is PER DEPLOYMENT (cfg.max_seq_len vs pretrained
    # original) — one factor list for prefill AND decode, where HF flips per
    # running sequence.  Each regime therefore gets its own checkpoint whose
    # deployed context selects the same list HF uses for the tested prompt.

    # short regime: deployed context == pretrained 32 -> short_factor;
    # HF also uses short_factor for every prompt <= 32
    model = Phi3ForCausalLM(Phi3Config(**common, max_position_embeddings=32))
    model.eval()
    d = tmp_path / "phi3lr_short"
    model.save_pretrained(d, safe_serialization=True)
    jcfg, params = load_decoder(str(d), dtype=jnp.float32)
    assert jcfg.rope_scaling[0] == "longrope"
    ids = np.asarray(rng.integers(1, 128, (1, 16)), np.int32)
    with torch.no_grad():
        hf_logits = model(torch.tensor(ids, dtype=torch.long)).logits.numpy()
    ours = np.asarray(llama.forward(params, jcfg, jnp.asarray(ids)))
    np.testing.assert_allclose(ours, hf_logits, atol=5e-4, rtol=1e-3)

    # long regime: deployed context 128 > 32 -> long_factor;
    # HF flips the whole sequence to long_factor once the prompt passes 32
    model = Phi3ForCausalLM(Phi3Config(**common, max_position_embeddings=128))
    model.eval()
    d = tmp_path / "phi3lr_long"
    model.save_pretrained(d, safe_serialization=True)
    jcfg, params = load_decoder(str(d), dtype=jnp.float32)
    ids = np.asarray(rng.integers(1, 128, (1, 48)), np.int32)
    with torch.no_grad():
        hf_logits = model(torch.tensor(ids, dtype=torch.long)).logits.numpy()
    ours = np.asarray(llama.forward(params, jcfg, jnp.asarray(ids)))
    np.testing.assert_allclose(ours, hf_logits, atol=5e-4, rtol=1e-3)
    # decode path consistency: chained prefill+decode equals repeated forward
    # (one factor list everywhere; mixed lists would corrupt cached K)
    prompt = np.asarray(rng.integers(1, 128, (1, 40)), np.int32)
    seq = prompt.copy()
    for _ in range(3):
        lg = llama.forward(params, jcfg, jnp.asarray(seq))
        seq = np.concatenate([seq, [[int(jnp.argmax(lg[0, -1]))]]], axis=1)
    expected = seq[0, prompt.shape[1]:].tolist()
    kv = Paged(jcfg, batch=1, max_len=64, dtype=jnp.float32)
    lg = kv.prefill(params, prompt, [prompt.shape[1]], slots=[0])
    got = [int(jnp.argmax(lg[0]))]
    for _ in range(2):
        lg = kv.decode(params, [got[-1]])
        got.append(int(jnp.argmax(lg[0])))
    assert got == expected


def test_yarn_rope_scaling_matches_hf(tmp_path):
    """YaRN (Qwen2 long-context variants): NTK-by-parts interpolation with the
    mscale attention factor."""
    import torch
    from transformers import Qwen2Config, Qwen2ForCausalLM

    cfg = Qwen2Config(
        vocab_size=128,
        hidden_size=32,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        intermediate_size=64,
        max_position_embeddings=128,
        rope_theta=10000.0,
        tie_word_embeddings=False,
        rope_scaling={
            "rope_type": "yarn",
            "factor": 4.0,
            "original_max_position_embeddings": 32,
        },
    )
    model = Qwen2ForCausalLM(cfg)
    model.eval()
    d = tmp_path / "qwen2yarn"
    model.save_pretrained(d, safe_serialization=True)
    jcfg, params = load_decoder(str(d), dtype=jnp.float32)
    assert jcfg.rope_scaling[0] == "yarn"
    ids = np.asarray(np.random.default_rng(5).integers(1, 128, (1, 80)), np.int32)
    with torch.no_grad():
        hf_logits = model(torch.tensor(ids, dtype=torch.long)).logits.numpy()
    ours = np.asarray(llama.forward(params, jcfg, jnp.asarray(ids)))
    np.testing.assert_allclose(ours, hf_logits, atol=5e-4, rtol=1e-3)


def test_phi3_matches_hf(tmp_path):
    """Phi-3: fused qkv_proj / gate_up_proj split at load time."""
    import torch
    from transformers import Phi3Config, Phi3ForCausalLM

    cfg = Phi3Config(
        vocab_size=128,
        hidden_size=32,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        intermediate_size=64,
        max_position_embeddings=128,
        rope_theta=10000.0,
        tie_word_embeddings=False,
        pad_token_id=0,  # Phi3Config defaults to 32000, past this tiny vocab
    )
    model = Phi3ForCausalLM(cfg)
    model.eval()
    d = tmp_path / "phi3"
    model.save_pretrained(d, safe_serialization=True)
    jcfg, params = load_decoder(str(d), dtype=jnp.float32)
    ids = np.array([[1, 5, 9, 17, 3, 25, 7, 2]], np.int32)
    with torch.no_grad():
        hf_logits = model(torch.tensor(ids, dtype=torch.long)).logits.numpy()
    ours = np.asarray(llama.forward(params, jcfg, jnp.asarray(ids)))
    np.testing.assert_allclose(ours, hf_logits, atol=3e-4, rtol=1e-3)


def test_unsupported_decoder_family_rejected(tiny_gemma_dir, tmp_path):
    """gemma-2 etc. would load without error but mis-compute; reject up front."""
    import json
    import shutil

    d, _ = tiny_gemma_dir
    bad = tmp_path / "fake_gemma2"
    shutil.copytree(d, bad)
    cfg = json.loads((bad / "config.json").read_text())
    cfg["model_type"] = "gemma2"
    (bad / "config.json").write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match="unsupported decoder model_type"):
        load_decoder(str(bad))


def test_gemma_prefill_decode_matches_forward(tiny_gemma_dir):
    d, _ = tiny_gemma_dir
    cfg, params = load_decoder(d, dtype=jnp.float32)
    prompt = np.array([[1, 5, 9, 17, 3]], np.int32)
    seq = prompt.copy()
    for _ in range(4):
        logits = llama.forward(params, cfg, jnp.asarray(seq))
        seq = np.concatenate([seq, [[int(jnp.argmax(logits[0, -1]))]]], axis=1)
    expected = seq[0, prompt.shape[1]:].tolist()

    kv = Paged(cfg, batch=1, max_len=32, dtype=jnp.float32)
    logits = kv.prefill(params, prompt, [prompt.shape[1]], slots=[0])
    got = [int(jnp.argmax(logits[0]))]
    for _ in range(3):
        logits = kv.decode(params, [got[-1]])
        got.append(int(jnp.argmax(logits[0])))
    assert got == expected


def test_qwen2_prefill_decode_matches_forward(tiny_qwen2_dir):
    """The decode step's bias path must agree with the full forward."""
    d, _ = tiny_qwen2_dir
    cfg, params = load_decoder(d, dtype=jnp.float32)
    prompt = np.array([[1, 5, 9, 17, 3]], np.int32)
    seq = prompt.copy()
    for _ in range(4):
        logits = llama.forward(params, cfg, jnp.asarray(seq))
        seq = np.concatenate([seq, [[int(jnp.argmax(logits[0, -1]))]]], axis=1)
    expected = seq[0, prompt.shape[1]:].tolist()

    kv = Paged(cfg, batch=1, max_len=32, dtype=jnp.float32)
    logits = kv.prefill(params, prompt, [prompt.shape[1]], slots=[0])
    got = [int(jnp.argmax(logits[0]))]
    for _ in range(3):
        logits = kv.decode(params, [got[-1]])
        got.append(int(jnp.argmax(logits[0])))
    assert got == expected


def test_prefill_decode_matches_forward(tiny_llama_dir):
    """Greedy generation via prefill+decode must equal repeated full forwards."""
    d, _ = tiny_llama_dir
    cfg, params = load_decoder(d, dtype=jnp.float32)
    prompt = np.array([[1, 5, 9, 17, 3]], np.int32)
    n_new = 6

    # ground truth: repeated full forward, greedy
    seq = prompt.copy()
    for _ in range(n_new):
        logits = llama.forward(params, cfg, jnp.asarray(seq))
        nxt = int(jnp.argmax(logits[0, -1]))
        seq = np.concatenate([seq, [[nxt]]], axis=1)
    expected = seq[0, prompt.shape[1]:].tolist()

    # engine path: prefill into slot 0 of a 2-slot cache, then decode steps
    kv = Paged(cfg, batch=2, max_len=32, dtype=jnp.float32)
    logits = kv.prefill(params, prompt, [prompt.shape[1]], slots=[0])
    got = []
    tok = int(jnp.argmax(logits[0]))
    got.append(tok)
    tokens = jnp.zeros((2,), jnp.int32)
    active = jnp.asarray([True, False])
    for _ in range(n_new - 1):
        tokens = tokens.at[0].set(tok)
        logits = kv.decode(params, tokens, active=active)
        tok = int(jnp.argmax(logits[0]))
        got.append(tok)
    assert got == expected


def test_sharded_forward_matches_single_device(tiny_llama_dir, mesh8):
    from django_assistant_bot_tpu.models.llama import logical_axes
    from django_assistant_bot_tpu.parallel import shard_pytree

    d, _ = tiny_llama_dir
    cfg, params = load_decoder(d, dtype=jnp.float32)
    ids = jnp.asarray(np.random.default_rng(1).integers(1, 100, (4, 16)), jnp.int32)
    ref = np.asarray(llama.forward(params, cfg, ids))

    with mesh8:
        sharded = shard_pytree(params, logical_axes(cfg), mesh8)
        out = jax.jit(lambda p, i: llama.forward(p, cfg, i))(sharded, ids)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-4, rtol=1e-3)


def test_moe_forward_matches_hf_mixtral(tmp_path):
    """Capacity set high enough that no token drops -> exact parity with HF."""
    import torch
    from transformers import MixtralConfig, MixtralForCausalLM

    hf_cfg = MixtralConfig(
        vocab_size=128,
        hidden_size=32,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        intermediate_size=64,
        num_local_experts=4,
        num_experts_per_tok=2,
        rope_theta=10000.0,
        max_position_embeddings=128,
    )
    model = MixtralForCausalLM(hf_cfg)
    model.eval()
    model.save_pretrained(tmp_path, safe_serialization=True)

    cfg, params = load_decoder(str(tmp_path), dtype=jnp.float32)
    # no-drop capacity: every token could route to the same expert
    cfg = DecoderConfig(**{**cfg.__dict__, "expert_capacity_factor": float(cfg.num_experts)})
    assert cfg.is_moe
    ids = np.array([[1, 5, 9, 17, 3, 25]], np.int32)
    with torch.no_grad():
        hf_logits = model(torch.tensor(ids, dtype=torch.long)).logits.numpy()
    ours = np.asarray(llama.forward(params, cfg, jnp.asarray(ids)))
    np.testing.assert_allclose(ours, hf_logits, atol=5e-4, rtol=1e-3)
