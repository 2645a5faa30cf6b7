"""TPU-native model definitions (functional: config + param pytree + pure apply fns).

Replaces the reference's ``AutoModel``/``AutoModelForCausalLM`` torch path
(reference: assistant/ai/embedders/transformers.py, assistant/ai/providers/transformers.py)
with three families, all jit/pjit-first:

- :mod:`.encoder` — BERT-family bidirectional encoder (ruBert-base / MiniLM class)
  for embeddings; masked mean-pool matches the reference embedder's semantics.
- :mod:`.llama`   — Llama-3-family decoder (RMSNorm, RoPE, GQA, SwiGLU), layers
  stacked for ``lax.scan`` (fast compiles, PP-ready), KV-cache prefill/decode.
- :mod:`.mixtral` — Mixtral-style MoE decoder: top-2 router with capacity-based
  dense dispatch einsums (MXU-friendly), experts sharded over the ``expert`` axis;
  and the dropless sigmoid-routed layer that is told which experts it holds.
- :mod:`.mla_moe` — latent-attention (MLA) decoder over a paged latent cache with
  a leading dense layer and routed + shared experts (DeepSeek-V3 family), or as
  shortcut-connected double layers with a softmax router and identity experts
  (LongCat-Flash), with :mod:`.llama`'s paged entry points; :func:`module_for`
  picks by ``cfg.arch``.

Parameters are plain pytrees of jnp arrays with a parallel pytree of logical axis
names consumed by :mod:`..parallel.sharding`.
"""

from .config import DecoderConfig, EncoderConfig  # noqa: F401
from . import encoder, llama, mixtral, mla_moe  # noqa: F401


def module_for(cfg: DecoderConfig):
    """The module whose entry points run ``cfg``: chosen once, by ``cfg.arch``."""
    return {"llama": llama, "mla_moe": mla_moe}[cfg.arch]


def held_params(cfg: DecoderConfig, params):
    """A checkpoint's tree of ``cfg`` in the form its module holds on the device
    and its entry points and ``logical_axes`` take: the one call between
    loading weights and placing them.  :mod:`.mla_moe` re-lays out six leaves
    (``mla_moe.held_params``); a module without such a function holds the
    checkpoint's own tree."""
    relay = getattr(module_for(cfg), "held_params", None)
    return params if relay is None else relay(cfg, params)
