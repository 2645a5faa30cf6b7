"""A page pool with an identity block table, for tests that drive the model's
paged entry points directly: slot ``b``'s logical block ``j`` is physical page
``b * NB + j``, so a model test reads as it would over one row per slot."""

import jax.numpy as jnp

from django_assistant_bot_tpu.models import llama


def _i32(x):
    return jnp.asarray(x, jnp.int32)


class Paged:
    def __init__(self, cfg, batch, max_len, *, page=8, dtype=None):
        assert max_len % page == 0
        nb = max_len // page
        self.cfg = cfg
        self.bt = jnp.arange(batch * nb, dtype=jnp.int32).reshape(batch, nb)
        self.cache = llama.init_paged_cache(cfg, batch, batch * nb, page, dtype=dtype)

    def insert(self, ks, vs, lengths, slots=None):
        """Write ``llama.prefill``'s K/V rows into ``slots`` (default: rows 0..B-1)."""
        slots = _i32(range(ks.shape[1]) if slots is None else slots)
        self.cache = llama.insert_sequences_paged(
            self.cache, ks, vs, _i32(lengths), slots, self.bt[slots]
        )

    def prefill(self, params, ids, lengths, slots=None):
        logits, ks, vs = llama.prefill(params, self.cfg, _i32(ids), _i32(lengths))
        self.insert(ks, vs, lengths, slots)
        return logits

    def chunk(self, params, ids, slot, start, valid):
        logits, self.cache = llama.prefill_chunk_paged(
            params, self.cfg, _i32(ids), self.cache, self.bt[slot], _i32(slot), _i32(start), _i32(valid)
        )
        return logits

    def suffix(self, params, ids, slots, starts, valids):
        slots = _i32(slots)
        logits, self.cache = llama.prefill_suffix_paged(
            params, self.cfg, _i32(ids), self.cache, self.bt[slots], slots, _i32(starts), _i32(valids)
        )
        return logits

    def decode(self, params, tokens, **kw):
        logits, self.cache = llama.decode_step_paged(
            params, self.cfg, _i32(tokens), self.cache, self.bt, **kw
        )
        return logits
