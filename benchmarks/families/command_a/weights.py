"""The Command A+ family's weights: random Q40 planes made on the device from
``--seed``, one jitted program of its own, in the layout the program serves.

One stack of planes a layer kind (``shapes.kinds``): ``window_moe`` and
``full_moe``, each with the fused ``wqkv`` (q | k | v columns) and ``wo``, ONE
norm a layer (``rms_att``: the parallel block's LayerNorm weight, the
program's name for a layer's first norm), the float32 router over ALL
published experts (no bias), the HELD experts' ``moe_upgate`` (up | gate) and
``moe_down``, and the always-on experts as one gated FFN of their summed
width, ``shared_upgate`` (up | gate, expert after expert within each half)
and ``shared_down``.

**The tied table is made once**: the classifier's planes ``wcls`` are drawn,
and the float32 lookup table ``embedding`` is those planes dequantised and
transposed, so the tie is exact for the program and the reference alike.

Distributions as the other families' init programs (uniform nibbles with
nibble 0 redrawn as 8 so that weights have mean 0, scales uniform in
[0, 0.004), norms 1 + 0.1 N(0, 1), an N(0, 0.02) router), but two things the
tie forces, both ``assumed`` in the configuration file. (1) ``PLANE_SCALES``:
the experts' planes (shared and routed) are three times as loud as
attention's and the table's. Under a tie the served token's own row pulls
the next logits towards itself by ``D sigma_e^2 / sigma_x``, and attention
over flat scores adds the same context mean at every position; with every
plane at 0.004 the residual stream stays quiet, both win, and greedy text
repeats 1-3 tokens (chip run, PR 31, at table scales 0.001, 0.004 and 0.016
alike). With the experts' planes at 0.012 the stream is the FFNs', a function
of the token, the pull is a tenth of a position's spread, and text does not
loop (173-192 distinct tokens of 192; a LayerNorm restores a quiet table row
to full size before the first projection). (2) The columns of ``<unk> <s>
</s>`` and the byte tokens are scaled by ``FIXED_PIECE_SCALE`` and not by 0:
their logits are an eighth as wide as the others' and never come first, so
every request runs to its ``max_tokens`` and the text spells its ids; but a
prompt is MADE of byte tokens, and rows of zeros would make every prompt the
same.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# what knows no model: the ids that never win, the packed K's multiple, the
# key from any seed
from ..llama.weights import (N_FIXED_PIECES, Q40_K_MULTIPLE, _pad_up,
                             seed_key)
from . import shapes
from .reference import dequant_q40

#: the planes' Q40 scales are uniform in [0, this), by the matrix's part in
#: the layer (a configuration's ``plane_scales`` may set one anew)
PLANE_SCALES = {"attention": 0.004, "ffn": 0.012, "table": 0.004}
FIXED_PIECE_SCALE = 0.125


def dims_of(model: dict) -> tuple:
    """Everything ``_init`` needs, hashable: the sizes, the kinds and the
    planes' scales (``plane_scales`` in the file over ``PLANE_SCALES``)."""
    scales = dict(PLANE_SCALES, **model.get("plane_scales", {}))
    d = dict(shapes.dims(model),
             **{f"scale_{k}": float(v) for k, v in scales.items()})
    return (tuple(sorted(d.items())),
            tuple((k, n) for k, n in shapes.kinds(model).items()),
            shapes.qkv_width(model))


def _init(key, dims: tuple):
    d = dict(dims[0])
    keys = iter(jax.random.split(key, 24 * (len(dims[1]) + 1)))

    def plane(k_in: int, out: int, prefix: tuple, scale: float):
        kp = _pad_up(k_in, Q40_K_MULTIPLE)
        w = jax.random.bits(next(keys), (*prefix, kp // 2, out), jnp.uint8)
        lo, hi = w & 0xF, w >> 4
        lo = jnp.where(lo == 0, jnp.uint8(8), lo)
        hi = jnp.where(hi == 0, jnp.uint8(8), hi)
        w = (hi << 4) | lo
        s = jax.random.uniform(next(keys), (*prefix, kp // 64, out),
                               jnp.float32) * scale
        s2 = jax.random.uniform(next(keys), (*prefix, kp // 64, out),
                                jnp.float32) * scale
        return {"w": w, "s": s, "s2": s2}

    def normal(shape, scale=1.0, mean=0.0):
        return mean + scale * jax.random.normal(next(keys), shape, jnp.float32)

    D, s_att, s_ffn = d["D"], d["scale_attention"], d["scale_ffn"]
    layers = {}
    for (att, ffn), n in dims[1]:
        layers[f"{att}_{ffn}"] = {
            "wqkv": plane(D, dims[2], (n,), s_att),
            "wo": plane(d["heads"] * d["hd"], D, (n,), s_att),
            "rms_att": normal((n, D), 0.1, 1.0),
            "moe_router": normal((n, D, d["E"]), 0.02),
            "moe_upgate": plane(D, 2 * d["He"], (n, d["Eh"]), s_ffn),
            "moe_down": plane(d["He"], D, (n, d["Eh"]), s_ffn),
            "shared_upgate": plane(D, 2 * d["Hs"], (n,), s_ffn),
            "shared_down": plane(d["Hs"], D, (n,), s_ffn),
        }
    wcls = plane(D, d["V"], (), d["scale_table"])
    loud = jnp.where(jnp.arange(d["V"]) >= N_FIXED_PIECES, 1.0,
                     FIXED_PIECE_SCALE).astype(jnp.float32)
    wcls["s"] = wcls["s"] * loud
    wcls["s2"] = wcls["s2"] * loud
    return {"embedding": dequant_q40(wcls, D).T,
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
    return {"wo": d["heads"] * d["hd"], "moe_down": d["He"],
            "shared_down": d["Hs"]}.get(name, d["D"])
