"""The benchmark's weights: random Q40 planes made on the device from ``--seed``.

One jitted program makes every plane in the layout the program serves (the
fused ``wqkv``, ``w13`` and ``moe_upgate`` planes, so that ``Engine`` finds
nothing left to concatenate: made unfused and fused afterwards, Mixtral at
depth 10 would hold its expert planes twice and pass the chip's 16 GB). The
distributions are those of the program's ``llama._quant_init``: uniform
nibbles, scales uniform in [0, 0.004), an N(0, 0.02) float32 embedding and
router. Four things differ: the nibble 0 is redrawn as 8, so that the weights
have mean 0 (see ``_init``); the packed bytes are drawn as bytes
(``jax.random.bits``), not as int32 and narrowed; the norm weights are
1 + 0.1 N(0, 1) a layer, so that no two layers compute the same function; and
the classifier columns of ``<unk> <s> </s>`` and the 256 byte tokens have
scale 0, so that their logit is 0 and greedy decoding never emits them: every
request runs to its ``max_tokens`` and the response text spells its ids.

The planes are plain arrays in nested dicts ({"w", "s", "s2"} a matrix): the
reference takes them as they are, and the family's ``wrap_planes`` wraps each
matrix in the program's ``QuantTensor`` for the ``Engine``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import shapes

N_FIXED_PIECES = 259  # ids below this never win: see the docstring
Q40_K_MULTIPLE = 512  # the packed K is padded to this, as the program packs it


def _pad_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def _init(key, dims: tuple, fused: bool = True):
    """``fused``: the single-device layout (``wqkv``, ``w13``, ``moe_upgate``).
    Unfused (``wq wk wv``, ``w1 w3``, ``moe_up moe_gate``) is what the
    tensor-parallel engine shards, each matrix on its own output axis."""
    L, D, H, KV, V, E = dims
    keys = iter(jax.random.split(key, 40))

    def plane(k_in: int, out: int, prefix: tuple):
        kp = _pad_up(k_in, Q40_K_MULTIPLE)
        w = jax.random.bits(next(keys), (*prefix, kp // 2, out), jnp.uint8)
        # a nibble stores q + 8 with q in -8..7, whose mean is -0.5: a model
        # of weights with a common sign drifts into one direction and emits
        # one token whatever the prompt (chip run, PR 23). Nibble 0 (q = -8)
        # becomes 8 (q = 0): q is then symmetric about 0
        lo, hi = w & 0xF, w >> 4
        lo = jnp.where(lo == 0, jnp.uint8(8), lo)
        hi = jnp.where(hi == 0, jnp.uint8(8), hi)
        w = (hi << 4) | lo
        s = jax.random.uniform(next(keys), (*prefix, kp // 64, out),
                               jnp.float32) * 0.004
        s2 = jax.random.uniform(next(keys), (*prefix, kp // 64, out),
                                jnp.float32) * 0.004
        return {"w": w, "s": s, "s2": s2}

    def norm(shape):
        return 1.0 + 0.1 * jax.random.normal(next(keys), shape, jnp.float32)

    layers = {"wo": plane(D, D, (L,)), "rms_att": norm((L, D)),
              "rms_ffn": norm((L, D))}
    if fused:
        layers["wqkv"] = plane(D, D + 2 * KV, (L,))
    else:
        layers.update(wq=plane(D, D, (L,)), wk=plane(D, KV, (L,)),
                      wv=plane(D, KV, (L,)))
    if E:
        layers["moe_router"] = 0.02 * jax.random.normal(
            next(keys), (L, D, E), jnp.float32)
        layers["moe_down"] = plane(H, D, (L, E))
        if fused:
            layers["moe_upgate"] = plane(D, 2 * H, (L, E))
        else:
            layers.update(moe_up=plane(D, H, (L, E)),
                          moe_gate=plane(D, H, (L, E)))
    else:
        layers["w2"] = plane(H, D, (L,))
        if fused:
            layers["w13"] = plane(D, 2 * H, (L,))
        else:
            layers.update(w1=plane(D, H, (L,)), w3=plane(D, H, (L,)))
    wcls = plane(D, V, ())
    live = (jnp.arange(V) >= N_FIXED_PIECES).astype(jnp.float32)
    wcls["s"] = wcls["s"] * live
    wcls["s2"] = wcls["s2"] * live
    return {
        "embedding": 0.02 * jax.random.normal(next(keys), (V, D), jnp.float32),
        "rms_final": norm((D,)),
        "wcls": wcls,
        "layers": layers,
    }


def seed_key(seed: int):
    """A key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def dims_of(model: dict) -> tuple:
    d = shapes.dims(model)
    return (d["L"], d["D"], d["H"], d["KV"], d["V"], d["E"])


def make_planes(model: dict, seed: int) -> dict:
    """All planes of the configuration, in one jitted call on the device."""
    return jax.jit(_init, static_argnums=1)(seed_key(seed), dims_of(model))


def fuse_planes(planes: dict) -> dict:
    """Unfused planes (the tensor-parallel layout, logical widths) in the
    fused layout the reference reads: columns side by side."""
    layers = dict(planes["layers"])

    def cat(*names):
        parts = [layers.pop(n) for n in names]
        return {k: jnp.concatenate([p[k] for p in parts], axis=-1)
                for k in ("w", "s", "s2")}

    if "wq" in layers:
        layers["wqkv"] = cat("wq", "wk", "wv")
    if "w1" in layers:
        layers["w13"] = cat("w1", "w3")
    if "moe_up" in layers:
        layers["moe_upgate"] = cat("moe_up", "moe_gate")
    return dict(planes, layers=layers)


def planes_shape(model: dict):
    """The planes as ShapeDtypeStructs (for the compile rehearsal)."""
    return jax.eval_shape(lambda k: _init(k, dims_of(model)),
                          jax.random.PRNGKey(0))


def logical_k(name: str, model: dict) -> int:
    """The logical input width of a named matrix."""
    d = shapes.dims(model)
    return d["H"] if name in ("w2", "moe_down") else d["D"]
