#!/usr/bin/env python3
"""Sizing without the chip: compiles the big programs of the configuration's
family (its seeded weights, its reference layer: ``sizing_programs`` of
``benchmarks/families/<name>.py``) and, with ``--engine``, the program's decode
step and largest prefills (``sut.engine_programs``, over the family's own
parameter tree), at a configuration's real sizes for a described TPU v5e chip,
and prints ``memory_analysis()`` of each.

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


def main() -> int:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks import families

    jax.config.update("jax_enable_compilation_cache", False)
    data = os.path.join(ROOT, "benchmarks")
    conf = json.load(open(os.path.join(data, "configs", sys.argv[1] + ".json")))
    family = families.load(conf, data)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    # the family's own programs first: its seeded weights, its reference (float32, highest)
    own = getattr(family, "sizing_programs", None)
    with jax.default_matmul_precision("highest"):
        for name, fn, shapes in own(conf, chip) if own else []:
            _report(name, fn.lower(*shapes).compile())
    if "--engine" in sys.argv:
        from benchmarks import sut

        for name, fn, shapes in sut.engine_programs(family, conf, chip):
            _report("program: " + name, fn.lower(*shapes).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main())
