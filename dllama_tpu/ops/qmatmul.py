"""Fused dequantize-matmul Pallas TPU kernels for block-quantized weights.

The reference's production decode path is ``matmulQ40vQ80`` — activations
quantized to Q80 on the fly, weights stored as Q40 nibbles, SIMD dot in int
space (`/root/reference/src/funcs.cpp:267-385`). On TPU the equivalent win is
**bandwidth**, not ALU width: single-token decode is HBM-bound, so keeping
weights as 4-bit blocks in HBM and dequantizing *inside* the matmul kernel
(VMEM tiles, never materializing the bf16 matrix in HBM) cuts the bytes/token
by ~4x versus bf16 weights.

Layouts (chosen for Mosaic-friendly unpacking — all kernel ops are int32/f32
vector ops; int8/uint8 arithmetic does not legalize on TPU):

* **Q80**: ``int8 [in, out]`` quants + ``f32 [in/32, out]`` per-block scales.
  Block b covers input rows ``32b..32b+31`` (the reference's 32-value blocks,
  `/root/reference/src/quants.hpp:21-24`, transposed to kernel layout).
* **Q40**: ``uint8 [in/2, out]`` packed nibbles + two ``f32 [in/64, out]``
  scale planes. Byte ``32s + j`` holds input row ``64s + j`` in its low nibble
  (scale plane ``s_lo[s]``) and row ``64s + 32 + j`` in its high nibble
  (``s_hi[s]``) — i.e. consecutive 32-blocks pair into one byte column, so
  the kernel splits the activation by 32-row half-superblocks *outside* the
  kernel (pure reshape) instead of interleaving lanes inside it.

Nibbles store ``q + 8`` with dequant ``(q - 8) * delta``, matching
`/root/reference/src/quants.cpp:166-180` bit-for-bit, so repacking a published
Q40 checkpoint is lossless (see ``repack_q40`` / ``formats.weights``).

Kernels run on TPU via Mosaic and anywhere else via ``interpret=True``
(automatic on non-TPU backends), which is how the CPU test suite covers them.
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from dllama_tpu.quants import blocks

QK = blocks.QK  # 32 values per quantization block

#: q40 "no-subtract" dequant: the kernel drops the ``- 8`` nibble recentering
#: (the VPU op the dequant is bound on) and the caller subtracts the exact
#: correction ``8 * sum_blocks blocksum(x) * delta`` via two small MXU dots
#: against the scale planes. Its speed on the chip is not measured (a lead
#: from a deleted builder log had it ahead of the subtracting kernel on one
#: shape; ROADMAP S3 settles it). It costs ~2x the (still
#: block-quantization-sized) rounding error — 7.6e-3 vs 3.7e-3 max-rel, both
#: well inside the 2e-2 the q40 format itself implies. Opt out with
#: DLLAMA_Q40_NOSUB=0 for the bit-conservative kernel.
Q40_NOSUB = os.environ.get("DLLAMA_Q40_NOSUB", "1") != "0"


def norm_fusion_enabled() -> bool:
    """DLLAMA_FUSE_NORM=1: fuse the rmsnorm epilogue into the projection
    kernels' t-blocks (``qmatmul_norm``) instead of materializing the
    normalized activation in HBM between two dispatches. Read per call (not
    import time) so tests and the bench can flip it."""
    return os.environ.get("DLLAMA_FUSE_NORM", "0") == "1"


def norm_fusion_engages(w) -> bool:
    """THE gate for the norm+projection fusion at one call site: the flag is
    on AND the matrix is quantized (dense matmuls already fuse their norm
    under XLA; the Pallas custom call is what breaks that fusion)."""
    return norm_fusion_enabled() and isinstance(w, QuantTensor)


def rmsnorm_inv(x: jnp.ndarray, eps: float) -> jnp.ndarray:
    """The per-row normalizer ``1/sqrt(mean(x^2) + eps)`` as [T, 1] f32 —
    computed OUTSIDE the fused kernels (it needs the whole logical K row;
    the kernels see K in bk-blocks) with exactly ops.norms.rmsnorm's op
    order so the in-kernel epilogue is bit-identical to the composition."""
    xf = x.astype(jnp.float32)
    return jnp.reciprocal(
        jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps))


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


#: cap on bk*bo cells per tile. The binding constraint is not the ~0.625 B/cell
#: the packed tile + scales occupy in HBM but the kernel's scoped VMEM: the
#: uint8 tile widens to int32 and dequantizes through f32 intermediates, which
#: Mosaic stack-allocates at ~3 B/cell (measured: a 5.77M-cell tile asked for
#: 17.9 MB of scoped VMEM against the 16 MB limit). 2M cells ≈ 6.5 MB scoped,
#: leaving room for the rest of the decode program's kernels.
_TILE_CELL_CAP = 2 * 2**20


#: input-dim padding unit per kind. Mosaic requires the second-to-minor dim of
#: every block to be a multiple of 8 sublanes; the q40 scale planes have one
#: row per 64 input rows (8 * 64 = 512) and the q80 plane one per 32
#: (8 * 32 = 256). Packing pads K up to this, with zero scales in the pad
#: region and zero-padded activation rows at call time, so the padding
#: contributes exactly 0 to every dot product. Without this, shapes like
#: Llama-2-7B's hidden 11008 (divisible by 256, not 512) force a (4, bo)
#: scale block and crash Mosaic — the round-2 bench failure.
K_MULTIPLE = {"q40": 512, "q80": 256}


def _pad_up(n: int, multiple: int) -> int:
    return (n + multiple - 1) // multiple * multiple


def _pad_rows(x: jnp.ndarray, multiple: int = 8) -> tuple[jnp.ndarray, int]:
    """Pad the leading (token) dim up to a sublane multiple."""
    t = x.shape[0]
    tp = max(multiple, (t + multiple - 1) // multiple * multiple)
    if tp != t:
        x = jnp.pad(x, ((0, tp - t), (0, 0)))
    return x, t


#: token-row block: decode (T <= 8) runs one t-block; big prefill batches tile
#: so the x / out tiles stay a bounded slice of VMEM (a 2048-token prefill
#: with whole-T blocks would need ~16 MB for x + out alone). t is OUTERMOST in
#: the grid so the out block still accumulates over the innermost k sweep;
#: weights re-stream once per t-block, which large-T prefill (MXU-bound)
#: amortizes. The t grid is ragged like o: token rows are independent.
T_BLOCK = 256


def _pad_cols(x: jnp.ndarray, k_padded: int) -> jnp.ndarray:
    """Zero-pad the input-feature dim of activations up to the packed K."""
    if x.shape[1] != k_padded:
        x = jnp.pad(x, ((0, 0), (0, k_padded - x.shape[1])))
    return x


def tile_plan(kind: str, k_padded: int, out_features: int) -> tuple[int, int]:
    """The (bk, bo) grid block sizes the kernels use for a packed matrix.

    The O grid is ragged — ``ceil(O / bo)`` blocks with Mosaic masking the
    boundary block's stores — so bo never shrinks to fit an awkward O. This
    matters for decode throughput: Llama-2-7B's hidden dim 11008 only
    divides by 256, which would mean a (43, 4)-step grid of tiny tiles where
    full 1024-lane tiles fit (how much it matters on the chip: not measured,
    ROADMAP S3). Raggedness is safe on
    the O axis only: each output column depends on exactly its own weight
    column, so boundary-block garbage lands in masked-out columns. The K axis
    by contrast is contracted, so bk MUST divide k_padded exactly (pack_q40 /
    pack_q80 pad K to K_MULTIPLE, and every candidate here divides it).

    Invariant (asserted by tests/test_qmatmul.py over the real model shapes):
    every operand block satisfies Mosaic's (8, 128) tiling — in particular the
    scale planes, whose sublane count is bk/64 (q40) or bk/32 (q80)."""
    if k_padded % K_MULTIPLE[kind] != 0:
        raise ValueError(
            f"{kind} packed input dim {k_padded} is not a multiple of "
            f"{K_MULTIPLE[kind]} — build QuantTensors via pack_q40/pack_q80, "
            "which pad K so every Mosaic block satisfies (8, 128) tiling"
        )
    if out_features < 128:
        bo = out_features  # toy dims (interpret-mode tests): one lane tile
    else:
        bo = min(1024, _pad_up(out_features, 128))
    align = K_MULTIPLE[kind]  # keeps the scale planes at >= 8 sublanes
    for bk in sorted({k_padded, k_padded // 2, 8192, 4096, 2048, 1024,
                      512, 256}, reverse=True):
        if bk and k_padded % bk == 0 and bk % align == 0 \
                and bk * bo <= _TILE_CELL_CAP:
            return bk, bo
    # unreachable: bk = K_MULTIPLE[kind] always divides k_padded (the
    # precondition above), is self-aligned, and 512 * 1024 < _TILE_CELL_CAP
    raise AssertionError(f"no valid bk for {kind} k_padded={k_padded} bo={bo}")


# ---------------------------------------------------------------------------
# Q80: int8 weights, one f32 scale per 32 input rows
# ---------------------------------------------------------------------------

def kernel_name(projection: str | None, kind: str) -> str | None:
    """The name a kernel's custom call carries in the compiled program and
    so in a profiler trace: ``<projection>_<kind>`` (``wqkv_q40_matmul``,
    ``w2_q40_corr``). None keeps the enclosing jitted function's name, one
    for every projection. The kind comes last and ends in a letter because
    trace reductions strip a trailing instruction number (``w13.44`` would
    read ``w``): a name must not end in a digit."""
    return None if projection is None else f"{projection}_{kind}"


def _q80_kernel(*refs, acc_dtype, stacked=False, fuse_norm=False):
    from jax.experimental import pallas as pl

    if stacked:  # scalar-prefetch layout: leading layer axis, idx_ref first
        refs = refs[1:]
        x_ref, w_ref, s_ref, *refs = refs
        wq, s = w_ref[0], s_ref[0]
    else:
        x_ref, w_ref, s_ref, *refs = refs
        wq, s = w_ref[...], s_ref[...]
    if fuse_norm:  # rmsnorm epilogue operands: [bt, 1] inv, [1, bk] weight
        inv_ref, nw_ref, o_ref = refs
    else:
        (o_ref,) = refs

    @pl.when(pl.program_id(2) == 0)  # grid (t, o, k): init at each k sweep
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    if fuse_norm:
        # exactly ops.norms.rmsnorm's elementwise tail — f32 product order
        # weight * (x * inv), cast once to bf16 — so the fused activation
        # tile is bit-identical to the unfused rmsnorm's output
        nw = nw_ref[0] if stacked else nw_ref[...]  # drop the layer axis
        x = (nw * (x_ref[...].astype(jnp.float32) * inv_ref[...])
             ).astype(jnp.bfloat16)
    else:
        x = x_ref[...]
    w = wq.astype(jnp.int32).astype(jnp.float32)  # [bk, bo]
    bk, bo = w.shape
    scale = jnp.reshape(
        jnp.broadcast_to(s[:, None, :], (bk // QK, QK, bo)), (bk, bo)
    )
    wd = (w * scale).astype(jnp.bfloat16)
    o_ref[...] += jnp.dot(x, wd, preferred_element_type=acc_dtype)


def _norm_operands(norm_w, norm_inv, k_padded):
    """Pad the fused-rmsnorm epilogue operands to kernel layout: the norm
    weight as a [1, k_padded] f32 plane (zero pad cols, so padded activation
    columns stay exactly 0 after the in-kernel epilogue) and the
    ``rmsnorm_inv`` normalizer row-padded like the activations."""
    nw = norm_w.astype(jnp.float32)
    if nw.shape[-1] != k_padded:
        pad = [(0, 0)] * (nw.ndim - 1) + [(0, k_padded - nw.shape[-1])]
        nw = jnp.pad(nw, pad)
    nw = nw[..., None, :]  # [1, K] flat | [L, 1, K] layer-stacked
    inv_p, _ = _pad_rows(norm_inv)
    return nw, inv_p


def _norm_layer_map(norm_w):
    """Plane selector for the stacked kernels' norm-weight index_map: the
    scalar-prefetched layer for a stacked [L, K] weight, plane 0 for a
    flat [K] weight the caller already sliced (llama's scan body)."""
    if norm_w.ndim == 2:
        return lambda idx: idx[0]
    return lambda idx: 0


@functools.partial(jax.jit, static_argnames=("interpret", "name"))
def q80_matmul(x: jnp.ndarray, w: jnp.ndarray, scales: jnp.ndarray,
               interpret: bool | None = None,
               norm_w: jnp.ndarray | None = None,
               norm_inv: jnp.ndarray | None = None,
               name: str | None = None) -> jnp.ndarray:
    """``x [T, K] @ dequant(w int8 [K, O], scales [K/32, O]) -> [T, O]`` f32.

    ``norm_w``/``norm_inv`` (both or neither): fuse the rmsnorm epilogue
    into the kernel's t-block — x arrives RAW and each tile is normalized
    in VMEM (``norm_w [K]`` f32, ``norm_inv = rmsnorm_inv(x, eps) [T, 1]``),
    bit-identical to ``q80_matmul(rmsnorm(x, norm_w), ...)`` while never
    materializing the normalized activation in HBM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = _interpret_default()
    fused = norm_w is not None
    K, O = w.shape  # K is the *packed* (padded) input dim
    # fused: keep x's own dtype (the epilogue normalizes in f32 from the raw
    # activation, exactly like rmsnorm) — bf16 only for the plain kernel
    xp, t = _pad_rows(_pad_cols(x if fused else x.astype(jnp.bfloat16), K))
    T = xp.shape[0]
    bk, bo = tile_plan("q80", K, O)
    bt = min(T, T_BLOCK)
    in_specs = [
        pl.BlockSpec((bt, bk), lambda t_, o, k: (t_, k)),
        pl.BlockSpec((bk, bo), lambda t_, o, k: (k, o)),
        pl.BlockSpec((bk // QK, bo), lambda t_, o, k: (k, o)),
    ]
    operands = [xp, w, scales]
    if fused:
        nw, inv_p = _norm_operands(norm_w, norm_inv, K)
        in_specs += [
            pl.BlockSpec((bt, 1), lambda t_, o, k: (t_, 0)),  # dllama: allow[PALLAS-001] reason=whole-array lane dim (proven: tests/test_lowering.py sweep)
            pl.BlockSpec((1, bk), lambda t_, o, k: (0, k)),  # dllama: allow[PALLAS-001] reason=whole-array sublane dim (proven: tests/test_lowering.py sweep)
        ]
        operands += [inv_p, nw]
    out = pl.pallas_call(
        functools.partial(_q80_kernel, acc_dtype=jnp.float32,
                          fuse_norm=fused),
        grid=(pl.cdiv(T, bt), pl.cdiv(O, bo), K // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bt, bo), lambda t_, o, k: (t_, o)),
        out_shape=jax.ShapeDtypeStruct((T, O), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name=kernel_name(name, "q80_matmul"),
    )(*operands)
    return out[:t]


@functools.partial(jax.jit, static_argnames=("interpret", "name"))
def q80_matmul_stacked(x: jnp.ndarray, w: jnp.ndarray, scales: jnp.ndarray,
                       layer: jnp.ndarray,
                       interpret: bool | None = None,
                       norm_w: jnp.ndarray | None = None,
                       norm_inv: jnp.ndarray | None = None,
                       name: str | None = None) -> jnp.ndarray:
    """Layer-indexed ``x [T, K] @ dequant(w[layer])`` over STACKED planes
    ``w int8 [L, K, O]``, ``scales [L, K/32, O]``, with a traced ``layer``.

    Why this exists: the decode forward scans over layers. If the scan body
    sliced the stacked planes (``w[idx]``) before calling the kernel, XLA
    would have to MATERIALIZE each layer's slice every step — a Pallas
    custom-call operand can't fuse a dynamic-slice — tripling the per-token
    HBM traffic (read + write the copy, then read it again in the kernel).
    Instead the whole stacked plane is the operand and a scalar-prefetched
    layer index steers the kernel's own DMA via the BlockSpec index_map, so
    each layer's bytes are read from HBM exactly once, in place."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = _interpret_default()
    fused = norm_w is not None
    _, K, O = w.shape
    xp, t = _pad_rows(_pad_cols(x if fused else x.astype(jnp.bfloat16), K))
    T = xp.shape[0]
    bk, bo = tile_plan("q80", K, O)
    bt = min(T, T_BLOCK)
    in_specs = [
        pl.BlockSpec((bt, bk), lambda t_, o, k, idx: (t_, k)),
        pl.BlockSpec((1, bk, bo), lambda t_, o, k, idx: (idx[0], k, o)),
        pl.BlockSpec((1, bk // QK, bo),
                     lambda t_, o, k, idx: (idx[0], k, o)),
    ]
    operands = [xp, w, scales]
    if fused:
        # norm weight: layer-stacked [L, K] (kernel indexes plane idx[0]) or
        # already-sliced flat [K] (the scan body's lp dict — plane 0)
        lsel = _norm_layer_map(norm_w)
        nw, inv_p = _norm_operands(
            norm_w if norm_w.ndim == 2 else norm_w[None], norm_inv, K)
        in_specs += [
            pl.BlockSpec((bt, 1), lambda t_, o, k, idx: (t_, 0)),  # dllama: allow[PALLAS-001] reason=whole-array lane dim (proven: tests/test_lowering.py sweep)
            pl.BlockSpec((1, 1, bk), lambda t_, o, k, idx: (lsel(idx), 0, k)),  # dllama: allow[PALLAS-001] reason=whole-array sublane dim (proven: tests/test_lowering.py sweep)
        ]
        operands += [inv_p, nw]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(pl.cdiv(T, bt), pl.cdiv(O, bo), K // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bt, bo), lambda t_, o, k, idx: (t_, o)),
    )
    out = pl.pallas_call(
        functools.partial(_q80_kernel, acc_dtype=jnp.float32, stacked=True,
                          fuse_norm=fused),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, O), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name=kernel_name(name, "q80_matmul"),
    )(jnp.asarray(layer, jnp.int32).reshape(1), *operands)
    return out[:t]


# ---------------------------------------------------------------------------
# Q40: packed nibbles, two scale planes (even/odd 32-blocks)
# ---------------------------------------------------------------------------

def _q40_kernel(*refs, acc_dtype, stacked=False, nosub=False, fuse_norm=False):
    from jax.experimental import pallas as pl

    if stacked:  # scalar-prefetch layout: leading layer axis, idx_ref first
        refs = refs[1:]
        xlo_ref, xhi_ref, w_ref, slo_ref, shi_ref, *refs = refs
        pk8, slo, shi = w_ref[0], slo_ref[0], shi_ref[0]
    else:
        xlo_ref, xhi_ref, w_ref, slo_ref, shi_ref, *refs = refs
        pk8, slo, shi = w_ref[...], slo_ref[...], shi_ref[...]
    if fuse_norm:  # rmsnorm epilogue: [bt, 1] inv + split norm-weight planes
        inv_ref, nwlo_ref, nwhi_ref, o_ref = refs
    else:
        (o_ref,) = refs

    @pl.when(pl.program_id(2) == 0)  # grid (t, o, k): init at each k sweep
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    if fuse_norm:  # same f32 order as ops.norms.rmsnorm -> bit-identical
        inv = inv_ref[...]
        nwlo = nwlo_ref[0] if stacked else nwlo_ref[...]  # drop layer axis
        nwhi = nwhi_ref[0] if stacked else nwhi_ref[...]
        xlo = (nwlo * (xlo_ref[...].astype(jnp.float32) * inv)
               ).astype(jnp.bfloat16)
        xhi = (nwhi * (xhi_ref[...].astype(jnp.float32) * inv)
               ).astype(jnp.bfloat16)
    else:
        xlo, xhi = xlo_ref[...], xhi_ref[...]
    pk = pk8.astype(jnp.int32)  # [bk/2, bo]
    hk, bo = pk.shape
    # nosub drops the nibble recentering (the binding VPU op); the caller
    # subtracts the exact 8 * blocksum(x) * delta correction outside
    lo = (pk & 0xF).astype(jnp.float32)
    hi = ((pk >> 4) & 0xF).astype(jnp.float32)
    if not nosub:
        lo = lo - 8.0
        hi = hi - 8.0
    nsb = slo.shape[0]  # bk/64 superblocks in this tile
    s_lo = jnp.reshape(
        jnp.broadcast_to(slo[:, None, :], (nsb, QK, bo)), (hk, bo)
    )
    s_hi = jnp.reshape(
        jnp.broadcast_to(shi[:, None, :], (nsb, QK, bo)), (hk, bo)
    )
    w_lo = (lo * s_lo).astype(jnp.bfloat16)
    w_hi = (hi * s_hi).astype(jnp.bfloat16)
    o_ref[...] += jnp.dot(xlo, w_lo, preferred_element_type=acc_dtype)
    o_ref[...] += jnp.dot(xhi, w_hi, preferred_element_type=acc_dtype)


def _q40_corr_kernel(*refs):
    """8 * (blocksums(x) @ scale planes) — the exact recentering term the
    nosub kernel omits. Tiny MXU dots (contraction dim = K/64); the scale
    planes are re-read from HBM (+~20% of the q40 bytes), a trade the VPU
    savings win back several times over (see Q40_NOSUB)."""
    if len(refs) == 6:  # stacked: scalar-prefetch layer index first
        _idx_ref, xslo_ref, xshi_ref, slo_ref, shi_ref, o_ref = refs
        slo, shi = slo_ref[0], shi_ref[0]
    else:
        xslo_ref, xshi_ref, slo_ref, shi_ref, o_ref = refs
        slo, shi = slo_ref[...], shi_ref[...]
    o_ref[...] = 8.0 * (
        jnp.dot(xslo_ref[...], slo, preferred_element_type=jnp.float32)
        + jnp.dot(xshi_ref[...], shi, preferred_element_type=jnp.float32)
    )


def _q40_block_sums(xp: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-32-block activation sums, split into the even/odd planes matching
    the packed nibble layout (even 32-block = low nibble / s plane, odd =
    high nibble / s2 plane). xp is the padded [T, K] activation."""
    T, K = xp.shape
    xs = xp.astype(jnp.float32).reshape(T, K // QK, QK).sum(-1)
    return xs[:, 0::2], xs[:, 1::2]  # each [T, K/64]


def _q40_correction(xp, s_lo, s_hi, layer=None, interpret=False, name=None):
    """Run the correction kernel. ``s_lo/s_hi`` are [K/64, O] (or stacked
    [L, K/64, O] with a traced ``layer``); returns [T, O] f32. A Pallas
    kernel — not two jnp dots — so the stacked case steers the layer choice
    through the scalar-prefetched index_map instead of materializing a
    dynamic-slice of the scale planes every scan step."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    xs_lo, xs_hi = _q40_block_sums(xp)
    T, NS = xs_lo.shape
    O = s_lo.shape[-1]
    bo = O if O < 128 else min(1024, _pad_up(O, 128))
    bt = min(T, T_BLOCK)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"))
    if layer is None:
        return pl.pallas_call(
            _q40_corr_kernel,
            grid=(pl.cdiv(T, bt), pl.cdiv(O, bo)),
            in_specs=[
                pl.BlockSpec((bt, NS), lambda t_, o: (t_, 0)),
                pl.BlockSpec((bt, NS), lambda t_, o: (t_, 0)),
                pl.BlockSpec((NS, bo), lambda t_, o: (0, o)),
                pl.BlockSpec((NS, bo), lambda t_, o: (0, o)),
            ],
            out_specs=pl.BlockSpec((bt, bo), lambda t_, o: (t_, o)),
            out_shape=jax.ShapeDtypeStruct((T, O), jnp.float32),
            compiler_params=params,
            interpret=interpret,
            name=name,
        )(xs_lo, xs_hi, s_lo, s_hi)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(pl.cdiv(T, bt), pl.cdiv(O, bo)),
        in_specs=[
            pl.BlockSpec((bt, NS), lambda t_, o, idx: (t_, 0)),
            pl.BlockSpec((bt, NS), lambda t_, o, idx: (t_, 0)),
            pl.BlockSpec((1, NS, bo), lambda t_, o, idx: (idx[0], 0, o)),
            pl.BlockSpec((1, NS, bo), lambda t_, o, idx: (idx[0], 0, o)),
        ],
        out_specs=pl.BlockSpec((bt, bo), lambda t_, o, idx: (t_, o)),
    )
    return pl.pallas_call(
        _q40_corr_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, O), jnp.float32),
        compiler_params=params,
        interpret=interpret,
        name=name,
    )(jnp.asarray(layer, jnp.int32).reshape(1), xs_lo, xs_hi, s_lo, s_hi)


def _q40_split(xp: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """[T, K] -> the lo/hi 32-row halves of each 64-row superblock (the
    packed-nibble pairing), each [T, K/2] — a pure reshape."""
    T, K = xp.shape
    xr = xp.reshape(T, K // 64, 64)
    return xr[:, :, :QK].reshape(T, K // 2), xr[:, :, QK:].reshape(T, K // 2)


def _q40_normed(xp, norm_w, norm_inv, layer=None):
    """The normalized padded activation the fused q40 kernel computes in its
    tiles, materialized OUTSIDE for the nosub correction's block sums only
    (an elementwise+reduce XLA fuses; [T, K/64] output, no [T, K] HBM
    round-trip). Must match the in-kernel epilogue bit-for-bit."""
    nw = norm_w[layer] if (layer is not None and norm_w.ndim == 2) else norm_w
    nw = nw.astype(jnp.float32)
    if nw.shape[-1] != xp.shape[-1]:
        nw = jnp.pad(nw, (0, xp.shape[-1] - nw.shape[-1]))
    inv_p, _ = _pad_rows(norm_inv)
    return (nw * (xp.astype(jnp.float32) * inv_p)).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("interpret", "nosub", "name"))
def q40_matmul(x: jnp.ndarray, packed: jnp.ndarray, s_lo: jnp.ndarray,
               s_hi: jnp.ndarray, interpret: bool | None = None,
               nosub: bool | None = None,
               norm_w: jnp.ndarray | None = None,
               norm_inv: jnp.ndarray | None = None,
               name: str | None = None) -> jnp.ndarray:
    """``x [T, K] @ dequant(packed uint8 [K/2, O]) -> [T, O]`` f32.

    ``norm_w``/``norm_inv``: fused rmsnorm epilogue (see ``q80_matmul``) —
    the norm weight rides split into the same lo/hi half-superblock planes
    as the activations."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = _interpret_default()
    if nosub is None:
        nosub = Q40_NOSUB
    fused = norm_w is not None
    O = packed.shape[1]
    K = packed.shape[0] * 2  # the *packed* (padded) input dim
    xp, t = _pad_rows(_pad_cols(x if fused else x.astype(jnp.bfloat16), K))
    T = xp.shape[0]
    # split activations into the lo/hi 32-row halves of each 64-row superblock
    x_lo, x_hi = _q40_split(xp)
    bk, bo = tile_plan("q40", K, O)
    bt = min(T, T_BLOCK)
    in_specs = [
        pl.BlockSpec((bt, bk // 2), lambda t_, o, k: (t_, k)),
        pl.BlockSpec((bt, bk // 2), lambda t_, o, k: (t_, k)),
        pl.BlockSpec((bk // 2, bo), lambda t_, o, k: (k, o)),
        pl.BlockSpec((bk // 64, bo), lambda t_, o, k: (k, o)),
        pl.BlockSpec((bk // 64, bo), lambda t_, o, k: (k, o)),
    ]
    operands = [x_lo, x_hi, packed, s_lo, s_hi]
    if fused:
        nw, inv_p = _norm_operands(norm_w, norm_inv, K)
        nw_lo, nw_hi = _q40_split(nw)
        in_specs += [
            pl.BlockSpec((bt, 1), lambda t_, o, k: (t_, 0)),  # dllama: allow[PALLAS-001] reason=whole-array lane dim (proven: tests/test_lowering.py sweep)
            pl.BlockSpec((1, bk // 2), lambda t_, o, k: (0, k)),  # dllama: allow[PALLAS-001] reason=whole-array sublane dim (proven: tests/test_lowering.py sweep)
            pl.BlockSpec((1, bk // 2), lambda t_, o, k: (0, k)),  # dllama: allow[PALLAS-001] reason=whole-array sublane dim (proven: tests/test_lowering.py sweep)
        ]
        operands += [inv_p, nw_lo, nw_hi]
    out = pl.pallas_call(
        functools.partial(_q40_kernel, acc_dtype=jnp.float32, nosub=nosub,
                          fuse_norm=fused),
        grid=(pl.cdiv(T, bt), pl.cdiv(O, bo), K // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bt, bo), lambda t_, o, k: (t_, o)),
        out_shape=jax.ShapeDtypeStruct((T, O), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name=kernel_name(name, "q40_matmul"),
    )(*operands)
    if nosub:
        xn = _q40_normed(xp, norm_w, norm_inv) if fused else xp
        out = out - _q40_correction(xn, s_lo, s_hi, interpret=interpret,
                                    name=kernel_name(name, "q40_corr"))
    return out[:t]


@functools.partial(jax.jit, static_argnames=("interpret", "nosub", "name"))
def q40_matmul_stacked(x: jnp.ndarray, packed: jnp.ndarray, s_lo: jnp.ndarray,
                       s_hi: jnp.ndarray, layer: jnp.ndarray,
                       interpret: bool | None = None,
                       nosub: bool | None = None,
                       norm_w: jnp.ndarray | None = None,
                       norm_inv: jnp.ndarray | None = None,
                       name: str | None = None) -> jnp.ndarray:
    """Layer-indexed q40 matmul over STACKED planes ``packed uint8 [L, K/2,
    O]`` with a traced ``layer`` — see ``q80_matmul_stacked`` for why the
    layer selection must happen inside the kernel's index_map. ``norm_w``
    ([L, K] stacked) / ``norm_inv``: fused rmsnorm epilogue."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = _interpret_default()
    if nosub is None:
        nosub = Q40_NOSUB
    fused = norm_w is not None
    O = packed.shape[2]
    K = packed.shape[1] * 2
    xp, t = _pad_rows(_pad_cols(x if fused else x.astype(jnp.bfloat16), K))
    T = xp.shape[0]
    x_lo, x_hi = _q40_split(xp)
    bk, bo = tile_plan("q40", K, O)
    bt = min(T, T_BLOCK)
    in_specs = [
        pl.BlockSpec((bt, bk // 2), lambda t_, o, k, idx: (t_, k)),
        pl.BlockSpec((bt, bk // 2), lambda t_, o, k, idx: (t_, k)),
        pl.BlockSpec((1, bk // 2, bo), lambda t_, o, k, idx: (idx[0], k, o)),
        pl.BlockSpec((1, bk // 64, bo), lambda t_, o, k, idx: (idx[0], k, o)),
        pl.BlockSpec((1, bk // 64, bo), lambda t_, o, k, idx: (idx[0], k, o)),
    ]
    operands = [x_lo, x_hi, packed, s_lo, s_hi]
    if fused:  # norm weight [L, K] stacked | flat [K] -> split lo/hi planes
        lsel = _norm_layer_map(norm_w)
        nw, inv_p = _norm_operands(
            norm_w if norm_w.ndim == 2 else norm_w[None], norm_inv, K)
        L = nw.shape[0]
        nw_lo, nw_hi = _q40_split(nw.reshape(L, K))
        nw_lo, nw_hi = nw_lo[:, None, :], nw_hi[:, None, :]  # [L, 1, K/2]
        in_specs += [
            pl.BlockSpec((bt, 1), lambda t_, o, k, idx: (t_, 0)),  # dllama: allow[PALLAS-001] reason=whole-array lane dim (proven: tests/test_lowering.py sweep)
            pl.BlockSpec((1, 1, bk // 2), lambda t_, o, k, idx: (lsel(idx), 0, k)),  # dllama: allow[PALLAS-001] reason=whole-array sublane dim (proven: tests/test_lowering.py sweep)
            pl.BlockSpec((1, 1, bk // 2), lambda t_, o, k, idx: (lsel(idx), 0, k)),  # dllama: allow[PALLAS-001] reason=whole-array sublane dim (proven: tests/test_lowering.py sweep)
        ]
        operands += [inv_p, nw_lo, nw_hi]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(pl.cdiv(T, bt), pl.cdiv(O, bo), K // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bt, bo), lambda t_, o, k, idx: (t_, o)),
    )
    out = pl.pallas_call(
        functools.partial(_q40_kernel, acc_dtype=jnp.float32, stacked=True,
                          nosub=nosub, fuse_norm=fused),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, O), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name=kernel_name(name, "q40_matmul"),
    )(jnp.asarray(layer, jnp.int32).reshape(1), *operands)
    if nosub:
        xn = (_q40_normed(xp, norm_w, norm_inv, layer=layer) if fused
              else xp)
        out = out - _q40_correction(xn, s_lo, s_hi, layer=layer,
                                    interpret=interpret,
                                    name=kernel_name(name, "q40_corr"))
    return out[:t]


# ---------------------------------------------------------------------------
# QuantTensor: the weight-pytree leaf for quantized matrices
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclass
class QuantTensor:
    """A [in, out] matrix stored block-quantized for the fused kernels.

    ``kind`` is static metadata ("q40" | "q80"). For q40, ``w`` is the packed
    uint8 plane and ``s2`` the second (odd-block) scale plane; for q80, ``w``
    is int8 and ``s2`` is an empty placeholder (pytree leaves must be arrays).
    Works stacked: a leading layer axis on every field makes it scannable.

    ``k_logical`` is the pre-padding input dim (0 = no padding; see
    ``K_MULTIPLE``). The padded tail rows multiply zero-padded activation
    rows, so every matmul result is exact for the logical shape.
    """

    w: jnp.ndarray
    s: jnp.ndarray
    s2: jnp.ndarray
    kind: str = field(metadata=dict(static=True), default="q40")
    k_logical: int = field(metadata=dict(static=True), default=0)

    @property
    def k_padded(self) -> int:
        return self.w.shape[-2] * (2 if self.kind == "q40" else 1)

    @property
    def in_features(self) -> int:
        return self.k_logical or self.k_padded

    @property
    def out_features(self) -> int:
        return self.w.shape[-1]


def qmatmul(x: jnp.ndarray, qt: QuantTensor, layer=None,
            name: str | None = None) -> jnp.ndarray:
    """Dispatch ``x @ dequant(qt)`` to the right fused kernel. Output dtype
    follows ``x`` (the caller's activation dtype), accumulation is f32.

    ``layer``: a traced int32 selecting one layer of a layer-STACKED
    QuantTensor (planes with a leading L axis) — the scalar-prefetch path
    used by the scan-over-layers forward. None = qt is a single matrix.

    ``name`` (static): which projection this is (``wqkv``, ``w2``,
    ``expert_down``): the kernel's custom call is named for it
    (``kernel_name``), so a device trace splits kernel time by projection."""
    if qt.kind == "q40":
        if layer is None:
            out = q40_matmul(x, qt.w, qt.s, qt.s2, name=name)
        else:
            out = q40_matmul_stacked(x, qt.w, qt.s, qt.s2, layer, name=name)
    elif qt.kind == "q80":
        if layer is None:
            out = q80_matmul(x, qt.w, qt.s, name=name)
        else:
            out = q80_matmul_stacked(x, qt.w, qt.s, layer, name=name)
    else:
        raise ValueError(f"unknown QuantTensor kind {qt.kind!r}")
    return out.astype(x.dtype)


def qmatmul_norm(x: jnp.ndarray, norm_w: jnp.ndarray, qt: QuantTensor,
                 layer=None, eps: float = 1e-5,
                 name: str | None = None) -> jnp.ndarray:
    """``rmsnorm(x, norm_w) @ dequant(qt)`` with the norm fused into the
    matmul kernel as an x-block epilogue (DLLAMA_FUSE_NORM): the raw
    activation streams into VMEM once and the normalized bf16 tile is
    produced in-register, eliminating the separate rmsnorm HBM round trip.
    Bit-identical to the unfused composition — same f32 op order, same final
    bf16 cast (tests/test_fused_ops.py). ``norm_w`` is ``[K]`` flat or
    ``[L, K]`` when ``layer`` selects a layer of a stacked QuantTensor."""
    inv = rmsnorm_inv(x, eps)
    if qt.kind == "q40":
        if layer is None:
            out = q40_matmul(x, qt.w, qt.s, qt.s2, norm_w=norm_w,
                             norm_inv=inv, name=name)
        else:
            out = q40_matmul_stacked(x, qt.w, qt.s, qt.s2, layer,
                                     norm_w=norm_w, norm_inv=inv, name=name)
    elif qt.kind == "q80":
        if layer is None:
            out = q80_matmul(x, qt.w, qt.s, norm_w=norm_w, norm_inv=inv,
                             name=name)
        else:
            out = q80_matmul_stacked(x, qt.w, qt.s, layer, norm_w=norm_w,
                                     norm_inv=inv, name=name)
    else:
        raise ValueError(f"unknown QuantTensor kind {qt.kind!r}")
    return out.astype(x.dtype)


def matmul_any(x: jnp.ndarray, w, layer=None,
               name: str | None = None) -> jnp.ndarray:
    """``x @ w`` where w is a plain array or a QuantTensor. ``layer`` selects
    a layer of a stacked QuantTensor (ignored for plain arrays, which the
    caller indexes itself — XLA fuses a dense dynamic-slice into the dot).
    ``name``: the projection, for the kernel's name in a trace (``qmatmul``)."""
    if isinstance(w, QuantTensor):
        return qmatmul(x, w, layer, name)
    return x @ w


def slice_to_in_features(h: jnp.ndarray, w) -> jnp.ndarray:
    """Trim a gathered activation down to ``w``'s (packed) input width.

    Under quantized TP the up-projections lane-pad their output axis
    (parallel.quant_tp); when the matching down-projection took the dense
    fallback (its input not packable) the gathered hidden is wider than the
    matrix expects — the pad columns are exact zeros, so dropping them is
    exact. No-op when the widths already agree."""
    w_in = w.k_padded if isinstance(w, QuantTensor) else w.shape[-2]
    return h[..., :w_in] if h.shape[-1] > w_in else h


# ---------------------------------------------------------------------------
# Packing (host-side, numpy)
# ---------------------------------------------------------------------------

def pack_q40(quants: np.ndarray, deltas: np.ndarray,
             to_device: bool = True) -> QuantTensor:
    """Build the kernel layout from unpacked quants ``int [K, O]`` in -8..7
    and per-block deltas ``[K/32, O]`` (block = 32 consecutive input rows).
    K is padded up to ``K_MULTIPLE['q40']`` (zero quants + zero scales) so the
    kernel's scale-plane blocks always satisfy Mosaic's 8-sublane tiling.

    ``to_device=False`` keeps the planes as host numpy arrays — the streaming
    sharded loader stacks layers on host and places the stacked tensor
    directly into its mesh sharding, so no single device ever holds the whole
    model (parallel.quant_tp)."""
    K, O = quants.shape
    assert K % 64 == 0, f"q40 kernel needs in_features % 64 == 0, got {K}"
    kp = _pad_up(K, K_MULTIPLE["q40"])
    if kp != K:
        quants = np.concatenate(
            [quants, np.zeros((kp - K, O), quants.dtype)], axis=0
        )
        deltas = np.concatenate(
            [deltas, np.zeros(((kp - K) // QK, O), np.float32)], axis=0
        )
    u = (quants.astype(np.int16) + 8).astype(np.uint8)
    ur = u.reshape(kp // 64, 2, QK, O)
    packed = (ur[:, 0] | (ur[:, 1] << 4)).reshape(kp // 2, O)
    d = deltas.astype(np.float32).reshape(kp // 64, 2, O)
    put = jnp.asarray if to_device else np.ascontiguousarray
    return QuantTensor(
        w=put(packed), s=put(d[:, 0].copy()),
        s2=put(d[:, 1].copy()), kind="q40", k_logical=K,
    )


def pack_q80(quants: np.ndarray, deltas: np.ndarray,
             to_device: bool = True) -> QuantTensor:
    """int8 quants [K, O] + per-block deltas [K/32, O] -> kernel layout.
    K is padded up to ``K_MULTIPLE['q80']`` like ``pack_q40``."""
    K, O = quants.shape
    assert K % QK == 0
    kp = _pad_up(K, K_MULTIPLE["q80"])
    if kp != K:
        quants = np.concatenate(
            [quants, np.zeros((kp - K, O), quants.dtype)], axis=0
        )
        deltas = np.concatenate(
            [deltas, np.zeros(((kp - K) // QK, O), np.float32)], axis=0
        )
    put = jnp.asarray if to_device else np.ascontiguousarray
    return QuantTensor(
        w=put(quants.astype(np.int8)),
        s=put(deltas.astype(np.float32)),
        s2=put(np.zeros((0,), np.float32)), kind="q80", k_logical=K,
    )


def quantize_tensor(w: np.ndarray, kind: str, to_device: bool = True) -> QuantTensor:
    """Quantize a dense ``[K, O]`` f32 matrix with the reference's block math
    (`/root/reference/converter/writer.py:26-75`), blocks along K."""
    w = np.ascontiguousarray(w, np.float32)
    K, O = w.shape
    # blocks run down the input dim: quantize the transposed rows
    flat = np.ascontiguousarray(w.T).reshape(-1)  # [O*K], rows of K
    if kind == "q40":
        raw = blocks.quantize_q40(flat)
        q, d = blocks.unpack_q40(raw)  # [O*K/32, 32], [O*K/32]
        q = q.reshape(O, K).T  # [K, O]
        d = d.reshape(O, K // QK).T  # [K/32, O]
        return pack_q40(q, d, to_device)
    if kind == "q80":
        raw = blocks.quantize_q80(flat)
        q, d = blocks.unpack_q80(raw)
        return pack_q80(q.reshape(O, K).T, d.reshape(O, K // QK).T, to_device)
    raise ValueError(f"unknown quant kind {kind!r}")


def repack_q40(raw: np.ndarray, d: int, n: int, to_device: bool = True) -> QuantTensor:
    """Losslessly repack a reference-format Q40 tensor (``d`` rows of ``n``
    values, blocks along n — `/root/reference/src/quants.hpp:16-19`) into the
    kernel layout for the transposed ``[n, d]`` kernel matrix."""
    q, deltas = blocks.unpack_q40(raw)  # [d*n/32, 32] in -8..7, [d*n/32]
    q = q.reshape(d, n).T  # [n, d] = [K, O]
    deltas = deltas.reshape(d, n // QK).T  # [K/32, O]
    return pack_q40(q, deltas, to_device)


def repack_q80(raw: np.ndarray, d: int, n: int, to_device: bool = True) -> QuantTensor:
    q, deltas = blocks.unpack_q80(raw)
    return pack_q80(q.reshape(d, n).T, deltas.reshape(d, n // QK).T, to_device)


def dequantize(qt: QuantTensor) -> np.ndarray:
    """QuantTensor -> dense f32 [K, O] at the *logical* K (padding stripped;
    reference semantics, for tests)."""
    if qt.kind == "q80":
        q = np.asarray(qt.w, np.float32)
        s = np.repeat(np.asarray(qt.s, np.float32), QK, axis=-2)
        dense = q * s
    else:
        pk = np.asarray(qt.w)
        half, O = pk.shape[-2:]
        lo = (pk & 0xF).astype(np.float32) - 8.0
        hi = ((pk >> 4) & 0xF).astype(np.float32) - 8.0
        s_lo = np.repeat(np.asarray(qt.s, np.float32), QK, axis=-2)
        s_hi = np.repeat(np.asarray(qt.s2, np.float32), QK, axis=-2)
        dq_lo = (lo * s_lo).reshape(*pk.shape[:-2], half // QK, QK, O)
        dq_hi = (hi * s_hi).reshape(*pk.shape[:-2], half // QK, QK, O)
        dense = np.concatenate([dq_lo, dq_hi], axis=-2).reshape(
            *pk.shape[:-2], half * 2, O
        )
    return dense[..., : qt.in_features, :]
