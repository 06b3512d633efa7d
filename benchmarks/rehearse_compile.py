#!/usr/bin/env python3
"""The third rehearsal: compile the cells' programs for a described v5e.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_compile.py [configuration ...]

Not a test (it loads the TPU's compiler library, which one process at a time
may hold) and not a chip run: nothing executes, so it says nothing about
results or times. It asks the chip's compiler, from the sandbox, whether each
program compiles at the real sizes and what memory it needs
(``memory_analysis()``): what each configuration's family lists in its
``rehearsal`` (for ``llama``: the benchmark's init program, the program's
``forward`` at T = 1 and at a prefill piece, ``forward_batched`` at the pool's
8 rows and 1024-token slab, and the reference's layer and head at the
comparison's sizes). Run it before the first chip call of a change to any of
them.
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

import jax  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import families  # noqa: E402

GB = 1e9


def report(name: str, compiled) -> None:
    m = compiled.memory_analysis()
    text = compiled.as_text()
    print(f"{name}: arguments {m.argument_size_in_bytes / GB:.2f} GB, "
          f"outputs {m.output_size_in_bytes / GB:.2f} GB, temporaries "
          f"{m.temp_size_in_bytes / GB:.2f} GB, custom calls "
          f"{text.count('tpu_custom_call')}", flush=True)


def main(argv) -> int:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = argv or [os.path.basename(c["file"])[:-5]
                         for c in json.load(f)["configs"]]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)

    for name in names:
        with open(os.path.join(HERE, "configs", name + ".json")) as f:
            conf = json.load(f)
        print(f"== {name}", flush=True)
        for what, fn, args, static in families.load(conf).rehearsal(conf):
            report(what, fn.lower(*on_chip(args), **static).compile())
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
