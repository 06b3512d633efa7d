"""Flash-decode attention: read ONLY the live KV-cache prefix.

The dense decode path (ops.attention.gqa_attention) is a static-shape masked
einsum — idiomatic XLA, but it streams the ENTIRE [S, kv, hd] cache from HBM
every token and, under the layer scan, first materializes each layer's slab
out of the stacked [L, S, kv, hd] cache (a dynamic-slice copy, the same
failure mode the stacked qmatmul kernels eliminated for weights). At short
context that is a few percent of decode bytes; at S=4096 the cache is
2.1 GB/token on a 7B — comparable to the weights themselves — and almost all
of it masked out.

This kernel is the TPU-native fix (the online-softmax flash-decoding
pattern): the caches stay in HBM (``memory_space=ANY``); a scalar-prefetched
``[layer, n_live_blocks]`` pair steers a ``fori_loop`` whose trip count is
the number of CACHE BLOCKS THAT ACTUALLY CONTAIN HISTORY, each iteration
DMA-ing one [BS, hd] K and V block per kv-head into VMEM scratch and folding
it into running (m, l, acc) online-softmax state. Bytes/token scale with
``pos``, not ``seq_len``, and the stacked cache is read in place.

Decode-only by design (T <= a few spec-verify rows): prefill stays on the
dense path, where the causal mask is half-live anyway and the MXU is the
bottleneck, not bandwidth.

Semantics match gqa_attention exactly (same masking: query row t attends to
cache positions <= pos + t; softmax in f32). Verified against it by
tests/test_flash_decode.py in interpret mode. Opt-in (DLLAMA_FLASH_DECODE=1)
and NOT yet runnable on a chip: the v5e compiler refuses the per-head cache
slice (tests/test_chip_compile.py holds the case as a strict xfail with the
compiler's message until the kernel is repaired).
"""

from __future__ import annotations

import functools
import os
import sys

import jax
import jax.numpy as jnp

#: cache-block length (sequence positions per DMA). 256 divides every model
#: seq_len the bench/CLI loads (512/1024/2048/4096/...); callers must fall
#: back to the dense path when S % block is nonzero.
BLOCK_S = 256


def flash_enabled() -> bool:
    return os.environ.get("DLLAMA_FLASH_DECODE", "0") == "1"


def supports(T: int, S: int, cache_dtype) -> bool:
    """Shapes/dtypes this kernel handles; anything else → dense path.

    T covers plain decode (1) through spec-verify batches (draft_len+1 = 9
    at the default draft_len=8) with margin; row padding rounds T*group up
    to a sublane multiple either way. f8 (float8_e4m3fn) caches are read
    through the same VMEM scratch path with the f32 upcast in compute —
    the combination long context wants (half the cache bytes AND
    live-prefix-only reads)."""
    return (
        T <= 16
        and S % BLOCK_S == 0
        and jnp.dtype(cache_dtype) in (jnp.dtype(jnp.bfloat16),
                                       jnp.dtype(jnp.float32),
                                       jnp.dtype(jnp.float8_e4m3fn))
    )


#: (T, S, dtype) combinations already warned about — the fallback must be
#: observable (ADVICE r04) but not per-trace noisy.
_declined: set = set()


def engages(T: int, S: int, cache_dtype) -> bool:
    """THE single gate for whether decode attention runs this kernel —
    used by the model layers (quantized layer-scan AND dense index-scan
    paths) and the bench's result tagging, so label and measured path can
    never drift. When the user asked for flash but the shapes decline it,
    say so once on stderr: a silent dense fallback under
    DLLAMA_FLASH_DECODE=1 reads as "flash is on" otherwise."""
    if not flash_enabled():
        return False
    if supports(T, S, cache_dtype):
        return True
    if T > 16:
        # prefill-sized T declining is the DESIGN (the causal mask is
        # half-live and the MXU is the bottleneck there, not bandwidth) —
        # warning would misread as "flash is off" on runs whose T=1 decode
        # engages it normally
        return False
    key = (T, S, jnp.dtype(cache_dtype).name)
    if key not in _declined:
        _declined.add(key)
        print(f"dllama: DLLAMA_FLASH_DECODE=1 but flash decode declines "
              f"T={T} S={S} cache={key[2]} (need S%{BLOCK_S}==0 and a "
              f"bf16/f32/f8 cache) — dense attention path used",
              file=sys.stderr, flush=True)
    return False


def _kernel(idx_ref, q_ref, qpos_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, k_sem, v_sem, *, block_s):
    """Unified (batch, kv-head) grid program. idx_ref = [layer, n_blk[0],
    ..., n_blk[B-1]]; caches are [L, B, S, kv, hd]; each program reads only
    row b's live blocks for head h."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    h = pl.program_id(1)
    layer = idx_ref[0]
    n_blk = idx_ref[1 + b]
    q = q_ref[0, 0].astype(jnp.float32)  # [Tg, hd]
    Tg, hd = q.shape
    qpos = qpos_ref[0]  # [Tg, 1] int32
    scale = jax.lax.rsqrt(jnp.float32(hd))

    # double-buffered: DMA for block i+1 is in flight while block i computes
    # (k_buf/v_buf are [2, BS, hd]; per-slot semaphores)
    def k_dma(i, slot):
        return pltpu.make_async_copy(
            k_hbm.at[layer, b, pl.ds(i * block_s, block_s), h],
            k_buf.at[slot], k_sem.at[slot])

    def v_dma(i, slot):
        return pltpu.make_async_copy(
            v_hbm.at[layer, b, pl.ds(i * block_s, block_s), h],
            v_buf.at[slot], v_sem.at[slot])

    k_dma(0, 0).start()
    v_dma(0, 0).start()

    def body(i, carry):
        m, l, acc = carry
        slot = jax.lax.rem(i, 2)
        nxt = jax.lax.rem(i + 1, 2)

        @pl.when(i + 1 < n_blk)
        def _prefetch():
            k_dma(i + 1, nxt).start()
            v_dma(i + 1, nxt).start()

        k_dma(i, slot).wait()
        k = k_buf[slot].astype(jnp.float32)  # [BS, hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [Tg, BS]
        key_idx = i * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (Tg, block_s), 1)
        s = jnp.where(key_idx <= qpos, s, jnp.float32(-1e30))
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        v_dma(i, slot).wait()
        v = v_buf[slot].astype(jnp.float32)  # [BS, hd]
        acc_new = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    init = (
        jnp.full((Tg, 1), -1e30, jnp.float32),
        jnp.zeros((Tg, 1), jnp.float32),
        jnp.zeros((Tg, hd), jnp.float32),
    )
    m, l, acc = jax.lax.fori_loop(0, n_blk, body, init)
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)


def _launch(qr, qpos, k5, v5, n_blk, layer, interpret):
    """qr [B, n_kv, Tgp, hd], qpos [B, Tgp, 1] i32, caches [L, B, S, kv,
    hd], n_blk [B] i32 live-block counts -> [B, n_kv, Tgp, hd]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, n_kv, Tgp, hd = qr.shape
    idx = jnp.concatenate(
        [jnp.asarray(layer, jnp.int32).reshape(1), n_blk.astype(jnp.int32)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, Tgp, hd), lambda b, h, idx: (b, h, 0, 0)),
            pl.BlockSpec((1, Tgp, 1), lambda b, h, idx: (b, 0, 0)),  # dllama: allow[PALLAS-001] reason=whole-array lane dim (proven: tests/test_lowering.py sweep)
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, Tgp, hd), lambda b, h, idx: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, BLOCK_S, hd), k5.dtype),
            pltpu.VMEM((2, BLOCK_S, hd), v5.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, block_s=BLOCK_S),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n_kv, Tgp, hd), qr.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(idx, qr, qpos, k5, v5)


def _rows(q, n_kv, group, Tg, Tgp):
    """[.., T, n_heads, hd] -> row layout [.., n_kv, Tgp, hd] (row = t*group+g)."""
    lead = q.shape[:-3]
    T, _, hd = q.shape[-3:]
    qr = (q.reshape(*lead, T, n_kv, group, hd)
          .swapaxes(-4, -3)
          .reshape(*lead, n_kv, Tg, hd))
    if Tgp != Tg:
        pad = [(0, 0)] * (qr.ndim - 2) + [(0, Tgp - Tg), (0, 0)]
        qr = jnp.pad(qr, pad)
    return qr


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_decode_attention(
    q: jnp.ndarray,        # [T, n_heads, head_size]
    k_cache: jnp.ndarray,  # [L, S, n_kv_heads, head_size] (L=1 for unstacked)
    v_cache: jnp.ndarray,  # same
    pos: jnp.ndarray,      # scalar int32: sequence position of q[0]
    layer: jnp.ndarray,    # scalar int32 selecting the cache layer
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Online-softmax decode attention over the live cache prefix only.

    Returns [T, n_heads, head_size], numerically matching
    ``gqa_attention(q, k_cache[layer], v_cache[layer], pos)``.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    T, n_heads, hd = q.shape
    L, S, n_kv, _ = k_cache.shape
    group = n_heads // n_kv
    assert S % BLOCK_S == 0, (S, BLOCK_S)

    # rows = (t, g) pairs per kv head: row // group = query offset t,
    # rounded UP to a sublane multiple (pad rows are discarded after)
    Tg = T * group
    Tgp = max(8, -(-Tg // 8) * 8)
    qr = _rows(q, n_kv, group, Tg, Tgp)[None]  # B=1
    row_t = (jnp.arange(Tgp, dtype=jnp.int32) // group).clip(0, T - 1)
    pos = jnp.asarray(pos, jnp.int32)
    qpos = (pos + row_t)[None, :, None]  # [1, Tgp, 1]; pads clamp live
    n_blk = ((pos + T + BLOCK_S - 1) // BLOCK_S).reshape(1)

    out = _launch(qr, qpos, k_cache[:, None], v_cache[:, None], n_blk,
                  layer, interpret)
    return (
        out[0, :, :Tg]
        .reshape(n_kv, T, group, hd)
        .transpose(1, 0, 2, 3)
        .reshape(T, n_heads, hd)
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_decode_attention_batched(
    q: jnp.ndarray,        # [B, n_heads, head_size] — one token per sequence
    k_cache: jnp.ndarray,  # [L, B, S, n_kv_heads, head_size]
    v_cache: jnp.ndarray,  # same
    pos: jnp.ndarray,      # [B] int32: each row's position
    layer: jnp.ndarray,    # scalar int32 selecting the cache layer
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Batched decode: B independent sequences, each reading only ITS OWN
    live prefix (row b stops at pos[b], not max(pos)). Matches
    vmap(gqa_attention) over the per-row slabs."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, n_heads, hd = q.shape
    L, Bc, S, n_kv, _ = k_cache.shape
    assert B == Bc and S % BLOCK_S == 0, (B, Bc, S, BLOCK_S)
    group = n_heads // n_kv
    Tg = group
    Tgp = max(8, -(-Tg // 8) * 8)
    qr = _rows(q[:, None], n_kv, group, Tg, Tgp)  # [B, n_kv, Tgp, hd]
    pos = jnp.asarray(pos, jnp.int32)
    qpos = jnp.broadcast_to(pos[:, None, None], (B, Tgp, 1))
    n_blk = (pos + 1 + BLOCK_S - 1) // BLOCK_S  # [B]

    out = _launch(qr, qpos, k_cache, v_cache, n_blk, layer, interpret)
    return out[:, :, :Tg].reshape(B, n_kv * group, hd)

