#!/usr/bin/env python3
"""The third rehearsal: compile the cells' programs for a described v5e.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_compile.py [configuration ...]

Not a test (it loads the TPU's compiler library, which one process at a time
may hold) and not a chip run: nothing executes, so it says nothing about
results or times. It asks the chip's compiler, from the sandbox, whether each
program compiles at the real sizes and what memory it needs
(``memory_analysis()``): the benchmark's init program, the program's
``forward`` at T = 1 and at a prefill piece, ``forward_batched`` at the pool's
8 rows and 1024-token slab, and the reference's layer and head at the
comparison's sizes. Run it before the first chip call of a change to any of
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
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import launcher  # noqa: E402
import reference  # noqa: E402
import shapes  # noqa: E402
import weights  # noqa: E402

GB = 1e9


def report(name: str, compiled) -> None:
    m = compiled.memory_analysis()
    text = compiled.as_text()
    print(f"{name}: arguments {m.argument_size_in_bytes / GB:.2f} GB, "
          f"outputs {m.output_size_in_bytes / GB:.2f} GB, temporaries "
          f"{m.temp_size_in_bytes / GB:.2f} GB, custom calls "
          f"{text.count('tpu_custom_call')}", flush=True)


def main(argv) -> int:
    names = argv or ["mistral-7b-v0.3-q40", "mixtral-8x7b-d10-q40"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)

    from dllama_tpu.models import llama
    from dllama_tpu.ops import qmatmul

    qmatmul._interpret_default = lambda: False  # compile the real kernels

    for name in names:
        with open(os.path.join(HERE, "configs", name + ".json")) as f:
            conf = json.load(f)
        print(f"== {name}", flush=True)
        d = shapes.dims(conf)
        dims = weights.dims_of(conf)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip)
        init = jax.jit(weights._init, static_argnums=1, out_shardings=chip)
        report("init program", init.lower(key, dims).compile())

        planes = on_chip(weights.planes_shape(conf))
        cfg = launcher.model_config(conf, conf["server"])
        params = launcher.wrap_planes(planes, conf)
        rope = on_chip(jax.eval_shape(lambda: llama.rope_tables(cfg)))
        cache = on_chip(jax.eval_shape(
            lambda: llama.init_cache(cfg, jnp.bfloat16)))
        for t in (1, 64):
            toks = jax.ShapeDtypeStruct((t,), jnp.int32, sharding=chip)
            pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
            fn = jax.jit(lambda p, r, tk, c, ps: llama.forward(cfg, p, r, tk, c, ps),
                         donate_argnums=3)
            report(f"forward T={t}", fn.lower(params, rope, toks, cache, pos).compile())
        rows, slab = int(conf["server"]["batch_max"]), int(conf["server"]["kv_bucket_min"])
        bcache = on_chip(jax.eval_shape(
            lambda: llama.init_batch_cache(cfg, rows, jnp.bfloat16, seq_len=slab)))
        toks = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=chip)
        fn = jax.jit(lambda p, r, tk, c, ps: llama.forward_batched(cfg, p, r, tk, c, ps),
                     donate_argnums=3)
        report(f"forward_batched B={rows} slab={slab}",
               fn.lower(params, rope, toks, bcache, toks).compile())

        m = reference.model_sizes(conf)
        n, t_pad = 6, 640
        x = jax.ShapeDtypeStruct((n, t_pad, d["D"]), jnp.float32, sharding=chip)
        cs = jax.ShapeDtypeStruct((t_pad, m[4] // 2), jnp.float32, sharding=chip)
        idx = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
        for lower in (None, reference.CONTROL):
            report(f"reference layer N={n} T={t_pad} lower={lower}",
                   reference._layer.lower(x, planes["layers"], idx, cs, cs,
                                          m=m, lower=lower).compile())
        xr = jax.ShapeDtypeStruct((t_pad, d["D"]), jnp.float32, sharding=chip)
        rws = jax.ShapeDtypeStruct((96,), jnp.int32, sharding=chip)
        report("reference head R=96",
               reference._head.lower(xr, rws, planes["rms_final"], planes["wcls"],
                                     dim=d["D"], eps=m[7], lower=None).compile())
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
