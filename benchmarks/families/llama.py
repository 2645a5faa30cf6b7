"""The ``llama`` family: a decoder-only block with RMSNorm, rotary embeddings,
grouped-query attention (window and q/k/v biases optional) and SwiGLU, served
int8 weight-only.  Made of ``benchmarks/weights.py`` (the seeded weights),
``benchmarks/reference/decoder.py`` (the plain float32 forward pass) and
``benchmarks/roofline.py`` (bytes and operations of a decode step), as they
stand.  It imports nothing of the program.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Sequence

from benchmarks import roofline, weights
from benchmarks.reference import decoder

# The lower-precision controls: every projection re-quantised to int4 in groups
# of 64 rows (below the stated int8 weights), and keys and values rounded
# through float8 (below the stated bfloat16 cache).
CONTROLS = ("int4", "kv_fp8")
INT4_GROUP = 64
KV_CONTROL_DTYPE = "float8_e4m3fn"

# Set from Qwen2.5-7B at int8 on the chip (PERF.md section 2): sound runs'
# largest 0.091 over 20 runs on 11 seeds (0.023-0.061 in PRs 24 and 25), the
# int4 control's smallest 0.80 over 5 seeds.  The float8 cache reads 0.008-0.037,
# under the sound runs: this number cannot see the cache's precision.
LIMITS = {"logit_gap_max": 0.30}


def served_params(conf: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """All served weights on the device, from one jitted call, in the program's
    parameter layout: ``layers`` stacked on a leading axis, the top leaves
    beside it; ``(int8, float32 scale)`` tuples for the seven projections."""
    w = weights.stacked(conf["hf"], seed, conf["weights"]["head_ids"])
    return {"layers": w["layers"], **w["top"]}


def reference_logits(conf: Dict[str, Any], seed: int, sequences: Sequence[Sequence[int]],
                     first_positions: Sequence[int], columns: Sequence[int],
                     control: Optional[str] = None) -> List[Any]:
    """``decoder.logits_at`` over weights regenerated from the seed, a layer
    resident at a time.  ``control`` names one of ``CONTROLS``."""
    if control not in (None, *CONTROLS):
        raise ValueError(f"the llama family has no control {control!r}: {CONTROLS}")
    hf = conf["hf"]
    top = weights.dequantised_top(hf, seed, tuple(conf["weights"]["head_ids"]))
    int4_group = INT4_GROUP if control == "int4" else 0
    return decoder.logits_at(hf, lambda i: weights.dequantised_layer(hf, seed, i, int4_group), top,
                             sequences, first_positions,
                             kv_round=KV_CONTROL_DTYPE if control == "kv_fp8" else None, columns=columns)


decode_step_bytes = roofline.decode_step_bytes
decode_step_flops = roofline.decode_step_flops


def sizing_programs(conf: Dict[str, Any], sharding):
    """The family's own big programs, as (name, jitted function, argument
    shapes) for ``sizing.py``: every served weight in one call, and one
    reference layer at the check's size (6 sequences padded to 1024)."""
    import jax
    import jax.numpy as jnp

    hf = conf["hf"]

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)

    keys = jax.eval_shape(lambda: weights.all_keys(0, hf["num_hidden_layers"]))
    make = weights.stacked_fn(hf, tuple(conf["weights"]["head_ids"]))
    B, T = int(os.environ.get("SIZING_B", 6)), int(os.environ.get("SIZING_T", 1024))
    layer = jax.eval_shape(lambda: weights.dequantised_layer(hf, 0, 0))
    D = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
    x = jax.ShapeDtypeStruct((B, T, hf["hidden_size"]), jnp.float32, sharding=sharding)
    cs = jax.ShapeDtypeStruct((T, D // 2), jnp.float32, sharding=sharding)
    return [
        ("weights.stacked (all served weights, one call)", make, shaped(keys)),
        (f"reference layer, float32 highest, [{B}, {T}]", decoder._layer_fn(weights.scalar_items(hf), None),
         (x, shaped(layer), cs, cs)),
    ]
