"""The MiMo-V2 family's weights: random Q40 planes made on the device from
``--seed``, one jitted program, in the layout the program serves.

One stack of planes a layer kind (``shapes.kinds``): ``full_dense``,
``window_moe``, ``full_moe``, each with the fused ``wqkv`` (q | k | v columns,
the kind's own KV head count, v heads narrower than q/k heads) and ``wo``;
the dense kind ``w13`` (gate | up) and ``w2``; the expert kinds the float32
router over ALL published experts, its correction bias, and the HELD experts'
``moe_upgate`` (up | gate) and ``moe_down``; window kinds one sink a head.

Distributions as the ``llama`` family's init program (uniform nibbles with
nibble 0 redrawn as 8 so that weights have mean 0, scales uniform in
[0, 0.004), norms 1 + 0.1 N(0, 1), an N(0, 0.02) router, the classifier's
columns of ``<unk> <s> </s>`` and the byte tokens scaled to 0), but: the
embedding is N(0, 1) an element, so that a token's identity is not drowned by
what 13 layers add (``PERF.md`` Open question 0c names the 0.02 embedding as
the lead for looping greedy text); the correction bias is 0.1 N(0, 1) against
sigmoid scores that spread by about 0.25, so that it changes some of a row's
picks and not all; the sinks are 3 + N(0, 1), of the order of the log of a
full window's summed exponentials, so that they take a share of the softmax
that a comparison can see. Both are ``assumed`` in the configuration file.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# what knows no model: the ids that never win, the packed K's multiple, the
# key from any seed
from ..llama.weights import (N_FIXED_PIECES, Q40_K_MULTIPLE, _pad_up,
                             seed_key)
from . import shapes


def dims_of(model: dict) -> tuple:
    """Everything ``_init`` needs, hashable: the sizes and the kinds."""
    d = shapes.dims(model)
    return (tuple(sorted(d.items())),
            tuple((k, n, shapes.qkv_width(model, k[0]))
                  for k, n in shapes.kinds(model).items()))


def _init(key, dims: tuple):
    d = dict(dims[0])
    keys = iter(jax.random.split(key, 16 * (len(dims[1]) + 1)))

    def plane(k_in: int, out: int, prefix: tuple):
        kp = _pad_up(k_in, Q40_K_MULTIPLE)
        w = jax.random.bits(next(keys), (*prefix, kp // 2, out), jnp.uint8)
        lo, hi = w & 0xF, w >> 4
        lo = jnp.where(lo == 0, jnp.uint8(8), lo)
        hi = jnp.where(hi == 0, jnp.uint8(8), hi)
        w = (hi << 4) | lo
        s = jax.random.uniform(next(keys), (*prefix, kp // 64, out),
                               jnp.float32) * 0.004
        s2 = jax.random.uniform(next(keys), (*prefix, kp // 64, out),
                                jnp.float32) * 0.004
        return {"w": w, "s": s, "s2": s2}

    def normal(shape, scale=1.0, mean=0.0):
        return mean + scale * jax.random.normal(next(keys), shape, jnp.float32)

    D = d["D"]
    layers = {}
    for (att, ffn), n, qkv in dims[1]:
        st = {"wqkv": plane(D, qkv, (n,)),
              "wo": plane(d["heads"] * d["vd"], D, (n,)),
              "rms_att": normal((n, D), 0.1, 1.0),
              "rms_ffn": normal((n, D), 0.1, 1.0)}
        if att == "window":
            st["sink"] = normal((n, d["heads"]), 1.0, 3.0)
        if ffn == "moe":
            st["moe_router"] = normal((n, D, d["E"]), 0.02)
            st["moe_bias"] = normal((n, d["E"]), 0.1)
            st["moe_upgate"] = plane(D, 2 * d["He"], (n, d["Eh"]))
            st["moe_down"] = plane(d["He"], D, (n, d["Eh"]))
        else:
            st["w13"] = plane(D, 2 * d["Hd"], (n,))
            st["w2"] = plane(d["Hd"], D, (n,))
        layers[f"{att}_{ffn}"] = st
    wcls = plane(D, d["V"], ())
    live = (jnp.arange(d["V"]) >= N_FIXED_PIECES).astype(jnp.float32)
    wcls["s"] = wcls["s"] * live
    wcls["s2"] = wcls["s2"] * live
    return {"embedding": normal((d["V"], D)),
            "rms_final": normal((D,), 0.1, 1.0), "wcls": wcls,
            "layers": layers}


def make_planes(model: dict, seed: int) -> dict:
    """All planes of the configuration, in one jitted call on the device."""
    return jax.jit(_init, static_argnums=1)(seed_key(seed), dims_of(model))


def planes_shape(model: dict):
    """The planes as ShapeDtypeStructs (for the compile rehearsal)."""
    return jax.eval_shape(lambda k: _init(k, dims_of(model)),
                          jax.random.PRNGKey(0))


def logical_k(name: str, model: dict) -> int:
    """The logical input width of a named matrix."""
    d = shapes.dims(model)
    return {"wo": d["heads"] * d["vd"], "w2": d["Hd"],
            "moe_down": d["He"]}.get(name, d["D"])
