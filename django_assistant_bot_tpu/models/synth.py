"""Synthesize REAL-format HF checkpoints locally (air-gapped bootstrap).

The reference proves its serving path on real downloaded weights
(reference: gpu_service/bin/fetch_models.py:10-30 pre-downloads, main.py:57-70
loads them at boot).  An air-gapped TPU environment can't download — but the
*format* is what the serving path must be proven against, not the weight
values.  This module writes a checkpoint that is byte-for-byte the real HF
layout: ``model.safetensors`` + ``config.json`` via ``save_pretrained``, plus a
genuinely trained fast tokenizer (``tokenizer.json``, BPE learned from a local
corpus) with a chat template — so fetch -> convert -> serve -> ``/dialog``
exercises every branch real weights would (safetensors parse, HF config
translation, real-tokenizer encode/decode, chat templating, prefix splitting),
with zero egress.

Weight VALUES are random (generation quality is meaningless); every code path
is the production one.

:func:`synth_native` is the full-width sibling: seeded random weights at a
published config's own widths, drawn on the device and saved in the native
checkpoint layout ``ModelSpec.checkpoint`` loads (``chip_smoke.py`` serves a
7B-class model from one).
"""

from __future__ import annotations

import os

# A plain-text corpus for tokenizer training: enough lexical variety that BPE
# learns real merges (multi-byte tokens), which is what shakes out id-space
# bugs the byte tokenizer can't (ids > 255, merges straddling chat-template
# boundaries, specials that decode to empty text).
_CORPUS = [
    "the assistant answers questions from the provided context",
    "please summarise the document and list the key facts",
    "what does the context say about deployment and scaling",
    "the quick brown fox jumps over the lazy dog",
    "benchmark question about topic seven",
    "привет как дела что нового в документе",
    "ответ на вопрос находится в контексте ниже",
]

# Exercises apply_chat_template + add_generation_prompt + the prefix split
# (encode_chat_split): message boundaries are explicit tokens, so the
# head-of-chat encoding is a strict prefix of the full encoding.
_CHAT_TEMPLATE = (
    "{% for message in messages %}<|{{ message['role'] }}|>"
    "{{ message['content'] }}</s>{% endfor %}"
    "{% if add_generation_prompt %}<|assistant|>{% endif %}"
)


def make_tokenizer(vocab_size: int = 512):
    """Train a small byte-level-BPE fast tokenizer from the local corpus."""
    from tokenizers import Tokenizer, decoders, models, pre_tokenizers, trainers
    from transformers import PreTrainedTokenizerFast

    tok = Tokenizer(models.BPE(unk_token=None))
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tok.decoder = decoders.ByteLevel()
    trainer = trainers.BpeTrainer(
        vocab_size=vocab_size,
        special_tokens=["<s>", "</s>", "<pad>", "<|user|>", "<|assistant|>", "<|system|>"],
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet(),
    )
    tok.train_from_iterator(_CORPUS * 8, trainer)
    fast = PreTrainedTokenizerFast(
        tokenizer_object=tok,
        bos_token="<s>",
        eos_token="</s>",
        pad_token="<pad>",
    )
    fast.chat_template = _CHAT_TEMPLATE
    return fast


def synth_decoder(
    out_dir: str,
    *,
    vocab_size: int = 512,
    hidden_size: int = 128,
    num_layers: int = 2,
    num_heads: int = 4,
    num_kv_heads: int = 2,
    intermediate_size: int = 256,
    max_seq_len: int = 512,
    seed: int = 0,
) -> str:
    """Write a Llama-architecture HF checkpoint dir (safetensors + tokenizer)."""
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    fast = make_tokenizer(vocab_size)
    # the trained vocab may come out slightly under the target; the model's
    # embedding table must cover every id the tokenizer can emit
    v = max(len(fast), vocab_size)
    torch.manual_seed(seed)
    cfg = LlamaConfig(
        vocab_size=v,
        hidden_size=hidden_size,
        intermediate_size=intermediate_size,
        num_hidden_layers=num_layers,
        num_attention_heads=num_heads,
        num_key_value_heads=num_kv_heads,
        max_position_embeddings=max_seq_len,
        tie_word_embeddings=False,
        bos_token_id=fast.bos_token_id,
        eos_token_id=fast.eos_token_id,
        pad_token_id=fast.pad_token_id,
    )
    model = LlamaForCausalLM(cfg)
    model.eval()
    os.makedirs(out_dir, exist_ok=True)
    model.save_pretrained(out_dir, safe_serialization=True)
    fast.save_pretrained(out_dir)
    return out_dir


def synth_encoder(
    out_dir: str,
    *,
    vocab_size: int = 512,
    hidden_size: int = 64,
    num_layers: int = 2,
    num_heads: int = 2,
    intermediate_size: int = 128,
    seed: int = 1,
) -> str:
    """Write a BERT-architecture HF checkpoint dir (the ruBert-class format
    the reference's embedding service loads, gpu_service/models.py:1-3)."""
    import torch
    from transformers import BertConfig, BertModel, BertTokenizerFast

    # WordPiece vocab: specials + the corpus' words + suffix pieces
    words = sorted({w for line in _CORPUS for w in line.split()})
    vocab = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"] + words
    vocab += [f"##{c}" for c in "abcdefghijklmnopqrstuvwxyz"]
    os.makedirs(out_dir, exist_ok=True)
    vocab_file = os.path.join(out_dir, "vocab.txt")
    with open(vocab_file, "w") as f:
        f.write("\n".join(dict.fromkeys(vocab)))
    fast = BertTokenizerFast(vocab_file=vocab_file, lowercase=True)
    torch.manual_seed(seed)
    cfg = BertConfig(
        vocab_size=len(fast),
        hidden_size=hidden_size,
        num_hidden_layers=num_layers,
        num_attention_heads=num_heads,
        intermediate_size=intermediate_size,
    )
    model = BertModel(cfg)
    model.eval()
    model.save_pretrained(out_dir, safe_serialization=True)
    fast.save_pretrained(out_dir)
    return out_dir


def synth_native(
    out_dir: str, kind: str, cfg, *, seed: int = 0, int8: bool = False
) -> bool:
    """Write seeded random weights at ``cfg``'s own widths as a NATIVE
    checkpoint (``checkpoint.py`` layout) — what ``ModelSpec.checkpoint``
    loads — drawing them on the device instead of through torch on the host,
    so a 7B-class model takes seconds, not a 16 GB host init.

    ``int8`` (decoders) draws the layer projections directly as int8
    ``QTensor`` s (:func:`..models.llama.init_int8`), the layout a
    ``quantize = "int8"`` spec serves as-is.  No tokenizer is written: the
    serving plane's byte tokenizer covers it.

    Returns False, writing nothing, when ``out_dir`` already holds a
    checkpoint stamped with the same kind, config, seed and format.
    """
    import json

    import jax

    from ..checkpoint import _config_to_dict, read_manifest, save_model
    from . import encoder, llama

    stamp = {"seed": int(seed), "int8": bool(int8)}
    if os.path.exists(os.path.join(out_dir, "manifest.json")):
        meta = read_manifest(out_dir)["meta"]
        # the manifest's config went through JSON (tuples became lists)
        want = json.loads(json.dumps(_config_to_dict(cfg)))
        if (
            meta.get("kind") == kind
            and meta.get("config") == want
            and meta.get("synth") == stamp
        ):
            return False
    rng = jax.random.key(seed)
    if kind == "encoder":
        params = encoder.init(cfg, rng)
    elif int8:
        params = llama.init_int8(cfg, rng)
    else:
        params = llama.init(cfg, rng)
    os.makedirs(os.path.dirname(os.path.abspath(out_dir)), exist_ok=True)
    save_model(out_dir, kind, cfg, params, meta={"synth": stamp})
    return True
