"""Lazy g++ build of native libraries from the committed sources.

Libraries land in :data:`BUILD_DIR` (``<checkout>/.cache/native``, git-ignored)
under a name carrying the source hash, so an edited ``.cpp`` rebuilds and no
prebuilt ``.so`` is ever committed."""

from __future__ import annotations

import hashlib
import logging
import os
import shutil
import subprocess
import threading
from typing import Optional

logger = logging.getLogger(__name__)

_SRC_DIR = os.path.dirname(os.path.abspath(__file__))
BUILD_DIR = os.path.join(os.path.dirname(os.path.dirname(_SRC_DIR)), ".cache", "native")
_lock = threading.Lock()
_cache: dict[str, Optional[str]] = {}


def build_library(source_name: str) -> Optional[str]:
    """Compile ``<source_name>.cpp`` into a cached .so; None when unavailable."""
    with _lock:
        if source_name in _cache:
            return _cache[source_name]
        path = _build(source_name)
        _cache[source_name] = path
        return path


def _build(source_name: str) -> Optional[str]:
    src = os.path.join(_SRC_DIR, f"{source_name}.cpp")
    if not os.path.exists(src):
        return None
    gxx = shutil.which("g++") or shutil.which("c++")
    if gxx is None:
        logger.warning("no C++ compiler; %s falls back to Python", source_name)
        return None
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    os.makedirs(BUILD_DIR, exist_ok=True)
    out = os.path.join(BUILD_DIR, f"lib{source_name}-{digest}.so")
    if os.path.exists(out):
        return out
    cmd = [gxx, "-O2", "-shared", "-fPIC", "-std=c++17", src, "-o", out]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        logger.info("built native %s -> %s", source_name, out)
        return out
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired) as e:
        stderr = getattr(e, "stderr", b"") or b""
        logger.warning("native build failed for %s: %s", source_name, stderr.decode()[:500])
        return None
