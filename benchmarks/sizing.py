#!/usr/bin/env python3
"""Sizing without the chip: compiles the benchmark's own big programs, and the
program's decode tick and largest prefill, at a configuration's real sizes for
a described TPU v5e chip and prints ``memory_analysis()`` of each.

    JAX_PLATFORMS=cpu python3 benchmarks/sizing.py <config> [--engine]

Nothing runs and no time is measured: a compile that passes is not a chip run.
It counts one program at a time, not what else the process keeps on the device.
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _gb(x) -> float:
    return round(x / 1e9, 3)


def _report(name, compiled):
    m = compiled.memory_analysis()
    print(json.dumps({"program": name, "arguments_gb": _gb(m.argument_size_in_bytes),
                      "outputs_gb": _gb(m.output_size_in_bytes), "temporaries_gb": _gb(m.temp_size_in_bytes),
                      "aliased_gb": _gb(m.alias_size_in_bytes)}))


def sizing_programs(conf: Dict[str, Any], sharding):
    """The program's decode step, chunk prefill
    and a full admission wave of suffix prefill at the configuration's
    geometry, as (name, jitted function, argument shapes).  Shapes only: the
    chip is described, not attached."""
    import jax
    import jax.numpy as jnp

    from django_assistant_bot_tpu.models import llama
    from django_assistant_bot_tpu.ops.quant import QTensor

    from django_assistant_bot_tpu.models.config import DecoderConfig

    cfg = DecoderConfig.from_hf(conf["hf"], dtype=getattr(jnp, conf["serving"].get("dtype", "bfloat16")))
    s = conf["serving"]
    slots, page, pages = int(s["max_slots"]), int(s["kv_page_size"]), int(s["kv_pages"])
    blocks, chunk = int(s["max_seq_len"]) // page, int(s["chunk_size"])
    L, E, F, V = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    H, KH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def q8(*shape):
        return QTensor(q=sds(shape, jnp.int8), scale=sds(shape[:-2] + (1, shape[-1]), jnp.float32))

    layers = {"attn_norm": sds((L, E), cfg.dtype), "mlp_norm": sds((L, E), cfg.dtype),
              "wq": q8(L, E, H * D), "wk": q8(L, E, KH * D), "wv": q8(L, E, KH * D), "wo": q8(L, H * D, E),
              "w_gate": q8(L, E, F), "w_up": q8(L, E, F), "w_down": q8(L, F, E)}
    if cfg.attn_bias:
        layers.update(bq=sds((L, H * D), cfg.dtype), bk=sds((L, KH * D), cfg.dtype), bv=sds((L, KH * D), cfg.dtype))
    params = {"tok_embed": sds((V, E), cfg.dtype), "final_norm": sds((E,), cfg.dtype),
              "lm_head": sds((E, V), cfg.dtype), "layers": layers}
    pool = (L, pages, KH, page, D)
    cache = llama.PagedKVCache(k=sds(pool, cfg.dtype), v=sds(pool, cfg.dtype), lengths=sds((slots,), jnp.int32))
    i32 = lambda *shape: sds(shape, jnp.int32)
    return [
        ("decode_step_paged, %d slots" % slots,
         jax.jit(lambda p, t, c, b: llama.decode_step_paged(p, cfg, t, c, b)),
         (params, i32(slots), cache, i32(slots, blocks))),
        ("prefill_chunk_paged, 1 x %d" % chunk,
         jax.jit(lambda p, i, c, bt, sl, st, v: llama.prefill_chunk_paged(p, cfg, i, c, bt, sl, st, v), donate_argnums=(2,)),
         (params, i32(1, chunk), cache, i32(blocks), i32(), i32(), i32())),
        ("prefill_suffix_paged, %d x %d" % (slots, chunk),
         jax.jit(lambda p, i, c, bt, sl, st, v: llama.prefill_suffix_paged(p, cfg, i, c, bt, sl, st, v), donate_argnums=(2,)),
         (params, i32(slots, chunk), cache, i32(slots, blocks), i32(slots), i32(slots), i32(slots))),
    ]


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks import weights
    from benchmarks.reference import decoder

    jax.config.update("jax_enable_compilation_cache", False)
    conf = json.load(open(os.path.join(ROOT, "benchmarks", "configs", sys.argv[1] + ".json")))
    hf = conf["hf"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)

    L = hf["num_hidden_layers"]
    keys = jax.eval_shape(lambda: weights.all_keys(0, L))
    make = weights.stacked_fn(hf, tuple(conf["weights"]["head_ids"]))
    _report("weights.stacked (all served weights, one call)", make.lower(*shaped(keys)).compile())

    # the reference's layer at the chat cells' check size: 6 sequences padded to 1024
    B, T = int(os.environ.get("SIZING_B", 6)), int(os.environ.get("SIZING_T", 1024))
    layer = jax.eval_shape(lambda: weights.dequantised_layer(hf, 0, 0))
    hf_items = weights.scalar_items(hf)
    fn = decoder._layer_fn(hf_items, None)
    x = jax.ShapeDtypeStruct((B, T, hf["hidden_size"]), jnp.float32, sharding=chip)
    D = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
    cs = jax.ShapeDtypeStruct((T, D // 2), jnp.float32, sharding=chip)
    with jax.default_matmul_precision("highest"):
        _report(f"reference layer, float32 highest, [{B}, {T}]", fn.lower(x, shaped(layer), cs, cs).compile())
    if "--engine" in sys.argv:
        for name, fn, shapes in sizing_programs(conf, chip):
            _report("program: " + name, fn.lower(*shapes).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main())
