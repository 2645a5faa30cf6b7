"""Persistent XLA compilation cache wiring.

A ``serve`` boot compiles every warmed prefill/decode/embed shape, and every
bench section child and test process would re-pay its share — all of it
redundant across processes of the same code on the same device.  JAX's
persistent on-disk compilation cache removes that; this module decides where
it lives, in ONE place, so every process of a command shares it.

The directory is part of the cache key's locality: a path that moves never
hits.  So there are exactly two places it can be:

- ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it — JAX reads that
  variable itself, and nothing here overrides it;
- otherwise :data:`IN_CHECKOUT_DIR` (``<checkout>/.cache/xla``, git-ignored):
  a fixed path that is the same for every process started from this checkout,
  never ``~``, a temporary name, a pid or a time.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

logger = logging.getLogger(__name__)

ENV_JAX_DIR = "JAX_COMPILATION_CACHE_DIR"
ENV_DISABLE = "DABT_COMPILE_CACHE_OFF"

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
IN_CHECKOUT_DIR = os.path.join(_CHECKOUT, ".cache", "xla")


def enable_persistent_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache and return its directory.

    Must run before the first jit compile to cover everything (later is still
    useful — subsequent compiles cache).  ``DABT_COMPILE_CACHE_OFF=1`` opts out
    (a cold-compile measurement run) and returns None.
    """
    if os.environ.get(ENV_DISABLE, "") not in ("", "0"):
        return None
    import jax

    path = os.environ.get(ENV_JAX_DIR)
    if not path:
        path = IN_CHECKOUT_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # JAX's default floor (1 s) skips most of the small bucket shapes a warmup
    # compiles; 0.5 s lets them hit while still keeping trivial programs out,
    # so the in-checkout directory stays small (the chip tool copies the
    # checkout as it stands on disk)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    logger.info("persistent XLA compile cache at %s", path)
    return path


def disable_persistent_compile_cache(reason: str) -> None:
    """Turn the persistent cache off for the rest of this process, reads and
    writes, and say why.  For code that knows cached executables would be
    wrong for it (``parallel/slicing.py``); operators use ``DABT_COMPILE_CACHE_OFF``."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()  # the cache latches "in use" at its first compile
    logger.warning("persistent XLA compile cache turned OFF for this process: %s", reason)
