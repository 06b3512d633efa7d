"""Fused rope + KV-cache-write epilogue for the decode hot path.

The unfused decode step rotates K on the VPU (``ops.rope.apply_rope``), casts
it to the cache dtype, and dynamic-update-slices it into the stacked
[L, (B,) S, kv, hd] cache — three HBM touches (read K, write rotated K, the
DUS read-modify-write of the cache slab) for what is arithmetically a handful
of multiplies per element. This kernel does the whole epilogue in one pass
(the memory-bound-neighbor fusion of PAPERS.md "Efficient Operation Fusion",
arXiv 2502.17728): K and V stream into VMEM once, K rotates in-register in
f32, both cast to the cache dtype in VMEM scratch, and a single async copy
lands exactly T rows at (layer, b, pos..pos+T) in the HBM-resident cache —
the caches ride ``memory_space=ANY`` with input→output aliasing, so the rest
of the cache is never touched.

Bit-identity with the unfused composition (tests/test_fused_ops.py): the
rotation uses the exact f32 op order of ``apply_rope`` and the exact cast
chain of the unfused write (f32 → activation dtype → cache dtype), and the
write start clamps the way ``dynamic_update_slice`` clamps (solo: start in
[0, S-T]; batched: each row in [0, S-1]).

Opt in with DLLAMA_FUSE_ROPE_CACHE=1 (decode-only: T <= 16, same bound as
flash decode's spec-verify ceiling). Engaged by models.llama's stacked-cache
attention blocks — the quantized layer-scan and the flash index-scan routes,
i.e. solo, batched, paged, and spec-verify serving — via the single
``engages`` gate below.
"""

from __future__ import annotations

import functools
import os
import sys

import jax
import jax.numpy as jnp

from dllama_tpu.ops.rope import HALF, INTERLEAVED


def fuse_enabled() -> bool:
    return os.environ.get("DLLAMA_FUSE_ROPE_CACHE", "0") == "1"


def supports(T: int, cache_dtype) -> bool:
    """Shapes/dtypes the kernel handles; anything else → unfused path.

    T covers decode (1) through spec-verify batches with margin; prefill
    stays unfused BY DESIGN (its [T, kv, hd] scratch would be VMEM-sized,
    and prefill is MXU-bound, not epilogue-bound)."""
    return (
        T <= 16
        and jnp.dtype(cache_dtype) in (jnp.dtype(jnp.bfloat16),
                                       jnp.dtype(jnp.float32),
                                       jnp.dtype(jnp.float8_e4m3fn))
    )


#: (T, dtype) combinations already warned about — the fallback must be
#: observable but not per-trace noisy (same contract as flash_decode).
_declined: set = set()


def engages(T: int, cache_dtype) -> bool:
    """THE single gate for whether the decode cache write runs this kernel —
    used by models.llama's solo and batched attention blocks so the fused
    and unfused paths can never silently drift apart."""
    if not fuse_enabled():
        return False
    if supports(T, cache_dtype):
        return True
    if T > 16:
        # prefill-sized T declining is the design — see supports(); warning
        # would misread as "fusion is off" on runs whose decode engages it
        return False
    key = (T, jnp.dtype(cache_dtype).name)
    if key not in _declined:
        _declined.add(key)
        print(f"dllama: DLLAMA_FUSE_ROPE_CACHE=1 but rope+cache fusion "
              f"declines T={T} cache={key[1]} (need a bf16/f32/f8 cache) — "
              f"unfused rope + cache write used",
              file=sys.stderr, flush=True)
    return False


def _kernel(idx_ref, k_ref, v_ref, cos_ref, sin_ref, kc_hbm, vc_hbm,
            ko_hbm, vo_hbm, k_scr, v_scr, k_sem, v_sem, *, style):
    """Grid (B,). idx_ref = [layer, start_0, ..., start_{B-1}] (starts
    pre-clamped by the launchers); k/v blocks are [1, T, kv, hd]; caches
    [L, B, S, kv, hd] in HBM, aliased input→output so untouched rows carry
    through. kc_hbm/vc_hbm are the aliased inputs — never read here."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del kc_hbm, vc_hbm
    b = pl.program_id(0)
    layer = idx_ref[0]
    start = idx_ref[1 + b]
    kf = k_ref[0].astype(jnp.float32)   # [T, kv, hd]
    c = cos_ref[0].astype(jnp.float32)  # [T, 1, hd//2]
    s = sin_ref[0].astype(jnp.float32)
    # exactly ops.rope.apply_rope's f32 op order, then the unfused write's
    # cast chain (f32 -> activation dtype -> cache dtype) — bit-identical
    if style == INTERLEAVED:
        x0 = kf[..., 0::2]
        x1 = kf[..., 1::2]
        rot = jnp.stack([x0 * c - x1 * s, x0 * s + x1 * c],
                        axis=-1).reshape(kf.shape)
    elif style == HALF:
        half = kf.shape[-1] // 2
        x0 = kf[..., :half]
        x1 = kf[..., half:]
        rot = jnp.concatenate([x0 * c - x1 * s, x0 * s + x1 * c], axis=-1)
    else:
        raise ValueError(f"unknown rope style {style!r}")
    k_scr[...] = rot.astype(k_ref.dtype).astype(k_scr.dtype)
    v_scr[...] = v_ref[0].astype(v_scr.dtype)
    T = k_scr.shape[0]
    # one copy of EXACTLY T rows: rows beyond start+T are never written, so
    # a clamped start near the end of the sequence overwrites the same rows
    # dynamic_update_slice would, nothing more
    k_cp = pltpu.make_async_copy(
        k_scr, ko_hbm.at[layer, b, pl.ds(start, T)], k_sem)
    v_cp = pltpu.make_async_copy(
        v_scr, vo_hbm.at[layer, b, pl.ds(start, T)], v_sem)
    k_cp.start()
    v_cp.start()
    k_cp.wait()
    v_cp.wait()


def _launch(kr, vr, cos, sin, k5, v5, starts, layer, style, interpret):
    """kr/vr [B, T, kv, hd], cos/sin [B, T, 1, hd//2], caches [L, B, S, kv,
    hd], starts [B] i32 pre-clamped write rows -> (k_cache, v_cache)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, n_kv, hd = kr.shape
    idx = jnp.concatenate(
        [jnp.asarray(layer, jnp.int32).reshape(1), starts.astype(jnp.int32)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, T, n_kv, hd), lambda b, idx: (b, 0, 0, 0)),
            pl.BlockSpec((1, T, n_kv, hd), lambda b, idx: (b, 0, 0, 0)),
            pl.BlockSpec((1, T, 1, hd // 2), lambda b, idx: (b, 0, 0, 0)),  # dllama: allow[PALLAS-001] reason=whole-array dims (proven: tests/test_lowering.py sweep)
            pl.BlockSpec((1, T, 1, hd // 2), lambda b, idx: (b, 0, 0, 0)),  # dllama: allow[PALLAS-001] reason=whole-array dims (proven: tests/test_lowering.py sweep)
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((T, n_kv, hd), k5.dtype),
            pltpu.VMEM((T, n_kv, hd), v5.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, style=style),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(k5.shape, k5.dtype),
            jax.ShapeDtypeStruct(v5.shape, v5.dtype),
        ],
        # operand index counts the scalar-prefetch idx (=0): k_cache is
        # operand 5, v_cache 6, aliased onto outputs 0/1 — the cache is
        # updated in place, untouched rows carried through
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(idx, kr, vr, cos, sin, k5, v5)


@functools.partial(jax.jit, static_argnames=("style", "interpret"))
def rope_cache_update(
    k: jnp.ndarray,        # [T, n_kv, hd] — UNrotated K projection
    v: jnp.ndarray,        # [T, n_kv, hd]
    cos: jnp.ndarray,      # [T, 1, hd//2] — table rows pos..pos+T
    sin: jnp.ndarray,      # same
    k_cache: jnp.ndarray,  # [L, S, n_kv, hd]
    v_cache: jnp.ndarray,  # same
    pos: jnp.ndarray,      # scalar int32
    layer: jnp.ndarray,    # scalar int32
    style: str = INTERLEAVED,
    interpret: bool | None = None,
) -> tuple:
    """Solo decode: rotate K and land K/V at (layer, pos..pos+T) in one
    kernel. Returns the updated (k_cache, v_cache); bit-identical to
    ``apply_rope`` + ``dynamic_update_slice`` (incl. its end-clamp)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    T = k.shape[0]
    L, S, n_kv, hd = k_cache.shape
    start = jnp.clip(jnp.asarray(pos, jnp.int32), 0, S - T).reshape(1)
    kc, vc = _launch(
        k[None], v[None], cos.reshape(1, T, 1, hd // 2),
        sin.reshape(1, T, 1, hd // 2), k_cache[:, None], v_cache[:, None],
        start, layer, style, interpret)
    return kc[:, 0], vc[:, 0]


@functools.partial(jax.jit, static_argnames=("style", "interpret"))
def rope_cache_update_verify(
    k: jnp.ndarray,        # [B, T, n_kv, hd] — UNrotated draft-row K
    v: jnp.ndarray,        # [B, T, n_kv, hd]
    cos: jnp.ndarray,      # [B, T, 1, hd//2] — per-row, per-draft angles
    sin: jnp.ndarray,      # same
    k_cache: jnp.ndarray,  # [L, B, S, n_kv, hd]
    v_cache: jnp.ndarray,  # same
    pos: jnp.ndarray,      # [B] int32 — row b's base position
    layer: jnp.ndarray,    # scalar int32
    style: str = INTERLEAVED,
    interpret: bool | None = None,
) -> tuple:
    """Spec-verify decode: B rows x T draft tokens each, row b landing at
    (layer, b, pos[b]..pos[b]+T). The general [B, T] case of the two
    wrappers above (solo is B=1, batched is T=1); per-row starts clamp to
    [0, S-T] exactly like the vmapped ``dynamic_update_slice``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, T = k.shape[:2]
    S = k_cache.shape[2]
    starts = jnp.clip(jnp.asarray(pos, jnp.int32), 0, S - T)
    return _launch(k, v, cos, sin, k_cache, v_cache, starts, layer, style,
                   interpret)


@functools.partial(jax.jit, static_argnames=("style", "interpret"))
def rope_cache_update_batched(
    k: jnp.ndarray,        # [B, n_kv, hd] — one UNrotated token per sequence
    v: jnp.ndarray,        # [B, n_kv, hd]
    cos: jnp.ndarray,      # [B, 1, hd//2] — each row's own angle
    sin: jnp.ndarray,      # same
    k_cache: jnp.ndarray,  # [L, B, S, n_kv, hd]
    v_cache: jnp.ndarray,  # same
    pos: jnp.ndarray,      # [B] int32 — each row's position
    layer: jnp.ndarray,    # scalar int32
    style: str = INTERLEAVED,
    interpret: bool | None = None,
) -> tuple:
    """Batched decode: B independent rows, row b landing at (layer, b,
    pos[b]). Clamps each row to the last slot exactly like the unfused
    scatter/DUS path, so overrun rows leave identical cache contents."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, n_kv, hd = k.shape
    L, Bc, S, _, _ = k_cache.shape
    assert B == Bc, (B, Bc)
    starts = jnp.clip(jnp.asarray(pos, jnp.int32), 0, S - 1)
    return _launch(
        k[:, None], v[:, None], cos[:, None], sin[:, None],
        k_cache, v_cache, starts, layer, style, interpret)
