"""Explicit activation collectives for the shard_map (quantized-TP) forward.

The quantized-TP design shards every matrix on its OUTPUT axis only
(parallel.quant_tp), so each matmul's input must be re-gathered — these are
the TPU analog of the reference's per-layer broadcast/gather wire trips
(`/root/reference/src/tasks.cpp:44-90`), ridden over ICI as XLA ring
all-gathers, optionally Q80-compressed like the reference's
``--buffer-float-type q80`` wire compression.

The REDUCE direction (``--tp-reduce``) is the mirror image: a K-sharded
(row-parallel) ``wo``/``w2`` produces full-width f32 *partial sums* on every
device, combined by :func:`reduce_columns` — a ``lax.ppermute`` ring
reduce-scatter with a pinned, device-order summation schedule, optionally
Q80-compressing each hop's payload (EQuARX-style quantized all-reduce).
``reduce_scatter_columns`` exposes the scattered shard so the model can fold
the residual add + rmsnorm into it before the next gather (TokenWeave-style
fused epilogue), and :func:`rms_inv_scattered` computes that norm's scale
from the shards with one scalar psum instead of a full-width gather.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


class RingAxis(str):
    """Marker for a tp axis whose gathers take the ppermute ring schedule.

    Subclassing ``str`` keeps the axis usable everywhere a plain axis name
    is (``is None`` checks, ``jax.lax`` axis-name arguments), so the
    microbatch-overlap drivers can opt whole call chains into ring gathers
    without threading an extra flag through every helper signature. The
    ring is bit-identical to the fused all-gather (pure data movement,
    same chunk order) — it exists because tp-1 small async permutes give
    XLA's latency-hiding scheduler boundaries to overlap with the other
    microbatch's compute, where one fused all-gather is a single blocking
    wait."""

    __slots__ = ()


def _all_gather_last(x: jnp.ndarray, tp_axis) -> jnp.ndarray:
    """All-gather on the feature (last) axis with chunks concatenated in
    axis order — one fused collective, or the ``lax.ppermute`` chunk
    rotation when ``tp_axis`` is a :class:`RingAxis` (the same primitive
    ``parallel/pipeline.py`` rotates microbatches with). Identical results
    either way; the assembly writes the chunk received on hop ``h`` at
    slot ``(idx - h) mod tp``, which is exactly the tiled all-gather's
    concatenation order."""
    if not isinstance(tp_axis, RingAxis):
        return jax.lax.all_gather(x, tp_axis, axis=-1, tiled=True)
    axis = str(tp_axis)
    tp = jax.lax.axis_size(axis)  # static under shard_map
    if tp == 1:
        return x
    idx = jax.lax.axis_index(axis)
    lead, f = x.shape[:-1], x.shape[-1]
    perm = [(i, (i + 1) % tp) for i in range(tp)]
    out = jnp.zeros((*lead, tp, f), x.dtype)
    out = jax.lax.dynamic_update_index_in_dim(out, x, idx, len(lead))
    buf = x
    for hop in range(1, tp):
        buf = jax.lax.ppermute(buf, axis, perm)
        out = jax.lax.dynamic_update_index_in_dim(
            out, buf, (idx - hop) % tp, len(lead))
    return out.reshape(*lead, tp * f)


def _require_q80_blocks(f: int, what: str) -> None:
    """The Q80 wire packs 32-value blocks; a feature dim off that grid would
    make the int8+scale payload reshape silently mix quants and scale bytes
    (the corruption is valid-shaped, so nothing downstream would notice)."""
    if f % 32:
        raise ValueError(
            f"{what}: local feature dim {f} is not a multiple of the 32-value "
            f"Q80 block, so the compressed payload cannot be packed — pad the "
            f"shard to a 32-multiple or run compress=False")


def _q80_encode(xf: jnp.ndarray) -> jnp.ndarray:
    """Block-quantize f32 ``[..., f]`` to ONE int8 payload ``[..., f + f//8]``:
    int8 quants followed by the bitcast bytes of one f32 scale per 32-value
    block — the reference's single packed Q80 buffer (``quantizeQ80Row``,
    `/root/reference/src/tasks.cpp:124-163`). One payload per collective: at
    decode the hops are latency-bound, so collective count matters more than
    the scale bytes."""
    lead, f = xf.shape[:-1], xf.shape[-1]
    xb = xf.reshape(*lead, f // 32, 32)
    absmax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    scale = absmax / 127.0
    q = jnp.round(xb / jnp.where(scale == 0.0, 1.0, scale)).astype(jnp.int8)
    scale_bytes = jax.lax.bitcast_convert_type(
        scale[..., 0], jnp.int8
    ).reshape(*lead, f // 8)
    return jnp.concatenate([q.reshape(*lead, f), scale_bytes], axis=-1)


def _q80_decode(payload: jnp.ndarray, f: int) -> jnp.ndarray:
    """Inverse of :func:`_q80_encode`: ``[..., f + f//8]`` int8 -> f32
    ``[..., f]`` (exact for the quantized values — int8 x f32-scale products
    are exact in f32)."""
    lead = payload.shape[:-1]
    q = payload[..., :f].astype(jnp.float32).reshape(*lead, f // 32, 32)
    s = jax.lax.bitcast_convert_type(
        payload[..., f:].reshape(*lead, f // 32, 4), jnp.float32
    )
    return (q * s[..., None]).reshape(*lead, f)


def gather_columns(x: jnp.ndarray, tp_axis, compress: bool = False) -> jnp.ndarray:
    """Concatenate the feature (last) axis across the tp axis (identity when
    tp_axis is None). The quantized-TP forward shards every matrix on its
    *output* axis only — so each matmul's input must be gathered, but no
    K-axis resharding of packed quant blocks is ever needed and every local
    kernel keeps its Mosaic-valid tiling (see parallel.quant_tp).

    ``compress=True`` moves the activation over the interconnect Q80-style:
    int8 quants + one f32 scale per 32-value block (the reference's wire
    compression, ``quantizeQ80Row`` -> TCP -> dequantize,
    `/root/reference/src/tasks.cpp:124-163`), ~1.8x less ICI traffic than
    bf16. Requires the local feature dim % 32 == 0 (always true for the
    lane-aligned shards)."""
    if tp_axis is None:
        return x
    if not compress:
        return _all_gather_last(x, tp_axis)
    lead = x.shape[:-1]
    f = x.shape[-1]
    _require_q80_blocks(f, "gather_columns(compress=True)")
    payload = _q80_encode(x.astype(jnp.float32))
    pg = _all_gather_last(payload, tp_axis)
    tp = pg.shape[-1] // (f + f // 8)
    deq = _q80_decode(pg.reshape(*lead, tp, f + f // 8), f)
    return deq.reshape(*lead, tp * f).astype(x.dtype)


def scatter_features(x: jnp.ndarray, tp_axis) -> jnp.ndarray:
    """This device's (``axis_index``-th) contiguous chunk of the feature
    (last) axis — a pure local slice, no communication. The row-parallel
    residual enters the layer scan scattered this way;
    ``gather_columns(scatter_features(x), tp_axis)`` reassembles ``x``."""
    if tp_axis is None:
        return x
    axis = str(tp_axis)
    tp = jax.lax.axis_size(axis)
    if tp == 1:
        return x
    f = x.shape[-1]
    if f % tp:
        raise ValueError(
            f"scatter_features: feature dim {f} is not divisible by tp={tp}")
    idx = jax.lax.axis_index(axis)
    return jax.lax.dynamic_slice_in_dim(x, idx * (f // tp), f // tp, axis=-1)


def rms_inv_scattered(x_s: jnp.ndarray, tp_axis, full_dim: int,
                      eps: float) -> jnp.ndarray:
    """``1/sqrt(mean(x^2) + eps)`` of the FULL row computed from its
    scattered shard ``[..., full_dim/tp]``: local f32 sum-of-squares plus one
    scalar psum. This is the fused norm+reduce epilogue's entire extra wire
    cost — a ``[...]`` scalar per row, where the un-fused path would spend a
    full-width gather just to reassemble the residual before normalizing."""
    xf = x_s.astype(jnp.float32)
    ss = jnp.sum(xf * xf, axis=-1)
    if tp_axis is not None:
        ss = jax.lax.psum(ss, str(tp_axis))
    return jnp.reciprocal(jnp.sqrt(ss / full_dim + eps))


def reduce_scatter_columns(partial: jnp.ndarray, tp_axis,
                           compress: bool = False) -> jnp.ndarray:
    """Sum ``[..., f]`` f32 partials across tp, returning this device's
    fully-reduced ``[..., f/tp]`` chunk (chunk ``axis_index``) — the reduce
    half of the row-parallel ``wo``/``w2`` wire.

    The schedule is a ``lax.ppermute`` ring with a PINNED summation order:
    device ``i`` seeds its accumulator with its local copy of chunk
    ``(i+tp-1) % tp``; on hop ``h`` every accumulator moves one step around
    the ring (``i -> i+1``) and the receiver adds its local chunk
    ``(i+tp-1-h) % tp``. After ``tp-1`` hops device ``i`` holds chunk ``i``
    summed in ring order ``p[i+1], p[i+2], ..., p[i]`` — deterministic, so
    ``compress=False`` is bit-identical to ``jax.lax.psum`` modulo exactly
    that reassociation (and bitwise-reproducible run to run, which psum's
    implementation-defined order need not be).

    ``compress=True`` Q80-block-quantizes each hop's accumulator payload
    (int8 quants + bitcast f32 scales in ONE payload, the same wire as
    ``gather_columns(compress=True)``), dequantizes and accumulates in f32
    on arrival — EQuARX-style quantized reduce. Each element's error is
    bounded by the sum over hops of half that hop's block scale
    (``absmax_block / 254``); tests assert the analytic bound."""
    if tp_axis is None:
        return partial
    axis = str(tp_axis)
    tp = jax.lax.axis_size(axis)
    x = partial.astype(jnp.float32)
    if tp == 1:
        return x
    lead, f = x.shape[:-1], x.shape[-1]
    if f % tp:
        raise ValueError(
            f"reduce_scatter_columns: feature dim {f} is not divisible by "
            f"tp={tp} — row-parallel partials must split into whole chunks")
    c = f // tp
    if compress:
        _require_q80_blocks(c, "reduce_scatter_columns(compress=True)")
    idx = jax.lax.axis_index(axis)
    xc = x.reshape(*lead, tp, c)
    perm = [(i, (i + 1) % tp) for i in range(tp)]

    def chunk(h):
        return jax.lax.dynamic_index_in_dim(
            xc, (idx + tp - 1 - h) % tp, len(lead), keepdims=False)

    acc = chunk(0)
    for hop in range(1, tp):
        if compress:
            wire = _q80_decode(
                jax.lax.ppermute(_q80_encode(acc), axis, perm), c)
        else:
            wire = jax.lax.ppermute(acc, axis, perm)
        acc = wire + chunk(hop)
    return acc


def reduce_columns(partial: jnp.ndarray, tp_axis,
                   compress: bool = False) -> jnp.ndarray:
    """Full-width sum of ``[..., f]`` f32 partials across tp (identity when
    ``tp_axis`` is None): :func:`reduce_scatter_columns` followed by the
    all-gather of the scattered result. The gather honors :class:`RingAxis`,
    so the reduce direction composes with ``--tp-overlap``'s hop-granular
    scheduling exactly like the gather direction does. The row-parallel
    forward itself prefers the scattered entry point — its fused epilogue
    folds residual-add + rmsnorm into the shard, making the trailing gather
    carry the next layer's already-normalized input instead."""
    if tp_axis is None:
        return partial
    if jax.lax.axis_size(str(tp_axis)) == 1:
        return partial.astype(jnp.float32)
    return _all_gather_last(
        reduce_scatter_columns(partial, tp_axis, compress), tp_axis)
