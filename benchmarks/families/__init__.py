"""One module per architecture family: everything of the benchmark that knows
the shape of a model's block.  A configuration file names its family
(``"family": "<name>"``; absent means ``"llama"``) and the harness loads
``<data directory>/families/<name>.py`` by path, the way ``run.py`` loads a
per-layer metric's reader, so a later PR adds an architecture as a file.

What a family module holds (``benchmarks/README.md``, "Adding an
architecture", has the table): ``served_params``, ``reference_logits``,
``CONTROLS``, ``LIMITS``, ``decode_step_bytes``, ``decode_step_flops`` and,
optionally, ``sizing_programs``.  It imports nothing of the program, and JAX
only inside its functions: ``run.py`` loads it too, and never touches JAX.
"""

from __future__ import annotations

import importlib.util
import os
from typing import Any, Dict

REQUIRED = ("served_params", "reference_logits", "CONTROLS", "LIMITS", "decode_step_bytes", "decode_step_flops")


def load(conf: Dict[str, Any], data_dir: str):
    """The family module of configuration ``conf``, from ``data_dir`` (the
    benchmark's data directory: ``benchmarks/`` of the checkout, or a test's copy)."""
    name = conf.get("family", "llama")
    path = os.path.join(data_dir, "families", name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"configuration {conf.get('name')!r} names family {name!r}, and there is no {path}")
    spec = importlib.util.spec_from_file_location("benchmark_family_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [k for k in REQUIRED if not hasattr(mod, k)]
    if missing:
        raise SystemExit(f"{path} lacks {missing}: a family module has {list(REQUIRED)}")
    return mod
