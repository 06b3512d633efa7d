"""Candidate q40 kernel optimizations, ready to A/B on the real chip.

The production kernel (ops.qmatmul) measured ~500 GB/s effective on 7B
shapes vs ~750 GB/s for a dense bf16 matvec (scripts/kernel_bench.py), i.e.
still VPU-dequant-bound, not HBM-bound. Variants here trade VPU ops for
bytes or MXU work; each is validated against dequantize() and timed with the
differencing harness. Integrate a variant only after it wins on hardware.

  A  production kernel (baseline)
  B  no-subtract: dequant w = q * s (drops the `- 8`), correcting with
     out -= 8 * (block_sums(x) @ s) — two tiny MXU dots OUTSIDE the kernel
     (re-reads the scale planes, +4% bytes, saves ~12% VPU)
  D  bf16 scale planes: same kernel, s/s2 stored bf16 — 20% -> 10% of bytes
     spent on scales (checkpoint deltas are f16, so bf16 rounds 3 mantissa
     bits: NOT bit-exact with the published file; opt-in if it wins)

  C  the PRODUCTION no-subtract path (what Q40_NOSUB=1 ships)
  E  int8-MXU accumulation: q80-quantized x, per-32-block int8xint8->int32
     MXU dots, scales applied to partials (the reference's Q40xQ80
     integer-dot idea, /root/reference/src/funcs.cpp:329-334, on the MXU)
  F  variant B with 2048-lane O tiles (tile_plan caps at 1024)
  G  variant B with bf16 scale copies for the correction dots only
  S  layer-stacked scalar-prefetch A/B (the decode scan's real form)

Usage: python scripts/qkernel_experiments.py [A|B|C|D|E|F|G|S|all] [K] [O]
"""

import functools
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, __import__("os").path.dirname(__import__("os").path.dirname(
    __import__("os").path.abspath(__file__))))

from dllama_tpu.ops import qmatmul  # noqa: E402
from dllama_tpu.ops.qmatmul import QK, QuantTensor  # noqa: E402


def variant_a(x, qt):
    # pin nosub=False: A is the subtracting-kernel baseline regardless of
    # the Q40_NOSUB production default
    return qmatmul.q40_matmul(x.astype(jnp.bfloat16), qt.w, qt.s, qt.s2,
                              nosub=False)


def _q40_nosub_kernel(*refs, acc_dtype):
    from jax.experimental import pallas as pl

    xlo_ref, xhi_ref, w_ref, slo_ref, shi_ref, o_ref = refs

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    pk = w_ref[...].astype(jnp.int32)
    hk, bo = pk.shape
    lo = (pk & 0xF).astype(jnp.float32)        # 0..15, no -8
    hi = ((pk >> 4) & 0xF).astype(jnp.float32)
    nsb = slo_ref.shape[0]
    s_lo = jnp.reshape(
        jnp.broadcast_to(slo_ref[...][:, None, :], (nsb, QK, bo)), (hk, bo))
    s_hi = jnp.reshape(
        jnp.broadcast_to(shi_ref[...][:, None, :], (nsb, QK, bo)), (hk, bo))
    o_ref[...] += jnp.dot(xlo_ref[...], (lo * s_lo).astype(jnp.bfloat16),
                          preferred_element_type=acc_dtype)
    o_ref[...] += jnp.dot(xhi_ref[...], (hi * s_hi).astype(jnp.bfloat16),
                          preferred_element_type=acc_dtype)


@jax.jit
def variant_b(x, qt):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    packed, s_lo, s_hi = qt.w, qt.s, qt.s2
    O = packed.shape[1]
    K = packed.shape[0] * 2
    xp, t = qmatmul._pad_rows(qmatmul._pad_cols(x.astype(jnp.bfloat16), K))
    T = xp.shape[0]
    xr = xp.reshape(T, K // 64, 64)
    x_lo = xr[:, :, :QK].reshape(T, K // 2)
    x_hi = xr[:, :, QK:].reshape(T, K // 2)
    bk, bo = qmatmul.tile_plan("q40", K, O)
    bt = min(T, qmatmul.T_BLOCK)
    out = pl.pallas_call(
        functools.partial(_q40_nosub_kernel, acc_dtype=jnp.float32),
        grid=(pl.cdiv(T, bt), pl.cdiv(O, bo), K // bk),
        in_specs=[
            pl.BlockSpec((bt, bk // 2), lambda t_, o, k: (t_, k)),
            pl.BlockSpec((bt, bk // 2), lambda t_, o, k: (t_, k)),
            pl.BlockSpec((bk // 2, bo), lambda t_, o, k: (k, o)),
            pl.BlockSpec((bk // 64, bo), lambda t_, o, k: (k, o)),
            pl.BlockSpec((bk // 64, bo), lambda t_, o, k: (k, o)),
        ],
        out_specs=pl.BlockSpec((bt, bo), lambda t_, o, k: (t_, o)),
        out_shape=jax.ShapeDtypeStruct((T, O), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=jax.default_backend() != "tpu",
    )(x_lo, x_hi, packed, s_lo, s_hi)
    # correction: sum_k (q-8)*s*x = sum q*s*x - 8 * sum_blocks s * blocksum(x)
    xs = xp.astype(jnp.float32).reshape(T, K // QK, QK).sum(-1)  # [T, K/32]
    xs_lo, xs_hi = xs[:, 0::2], xs[:, 1::2]  # even/odd 32-blocks -> planes
    corr = 8.0 * (xs_lo @ s_lo + xs_hi @ s_hi)
    return (out - corr)[:t]


def variant_c(x, qt):
    """The PRODUCTION no-subtract path (ops.qmatmul nosub=True): nosub
    Pallas kernel + the Pallas correction kernel (vs B's out-of-kernel jnp
    correction dots). This is what Q40_NOSUB=1 actually ships."""
    return qmatmul.q40_matmul(x.astype(jnp.bfloat16), qt.w, qt.s, qt.s2,
                              nosub=True)


def variant_d(x, qt):
    qd = QuantTensor(w=qt.w, s=qt.s.astype(jnp.bfloat16),
                     s2=qt.s2.astype(jnp.bfloat16), kind=qt.kind,
                     k_logical=qt.k_logical)
    return qmatmul.q40_matmul(x.astype(jnp.bfloat16), qd.w, qd.s, qd.s2,
                              nosub=False)


def _q40_int8_kernel(*refs):
    """Variant E compute: the reference's Q40xQ80 integer-dot idea
    (`/root/reference/src/funcs.cpp:329-334`, NEON vdotq_s32) mapped to the
    MXU's int8 path. x arrives pre-quantized q80-style (int8 + per-32-block
    f32 scale); each 32-row block runs an int8xint8->int32 MXU dot and the
    scale product (sx_b outer s_b) applies to the [bt, bo] PARTIAL — nsb x
    bo scale multiplies instead of the nosub kernel's hk x bo, trading the
    VPU dequant multiply for small-K MXU dots."""
    from jax.experimental import pallas as pl

    xlo_ref, xhi_ref, sxlo_ref, sxhi_ref, w_ref, slo_ref, shi_ref, o_ref = refs

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    pk = w_ref[...].astype(jnp.int32)
    lo = (pk & 0xF).astype(jnp.int8)          # 0..15 fits int8; no -8
    hi = ((pk >> 4) & 0xF).astype(jnp.int8)
    nsb = slo_ref.shape[0]
    acc = jnp.zeros_like(o_ref[...])
    for i in range(nsb):
        xl = xlo_ref[:, i * QK:(i + 1) * QK]
        xh = xhi_ref[:, i * QK:(i + 1) * QK]
        dl = jax.lax.dot_general(
            xl, lo[i * QK:(i + 1) * QK, :], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32).astype(jnp.float32)
        dh = jax.lax.dot_general(
            xh, hi[i * QK:(i + 1) * QK, :], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32).astype(jnp.float32)
        acc += dl * (sxlo_ref[:, i:i + 1] * slo_ref[i, :][None, :])
        acc += dh * (sxhi_ref[:, i:i + 1] * shi_ref[i, :][None, :])
    o_ref[...] += acc


@jax.jit
def variant_e(x, qt):
    """int8-MXU accumulation (see _q40_int8_kernel). Adds x-quantization
    (q80-style, rel ~4e-3) on top of q40's own noise."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    packed, s_lo, s_hi = qt.w, qt.s, qt.s2
    O = packed.shape[1]
    K = packed.shape[0] * 2
    xp, t = qmatmul._pad_rows(qmatmul._pad_cols(x.astype(jnp.float32), K))
    T = xp.shape[0]
    # q80-quantize x per 32-block, split into the lo/hi planes matching the
    # packed layout (64-block: first 32 -> lo nibbles, last 32 -> hi)
    xb = xp.reshape(T, K // QK, QK)
    absmax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    scale = absmax / 127.0
    xq = jnp.round(xb / jnp.where(scale == 0.0, 1.0, scale)).astype(jnp.int8)
    sx = scale[..., 0]  # [T, K/32]
    xr = xq.reshape(T, K // 64, 64)
    x_lo = xr[:, :, :QK].reshape(T, K // 2)
    x_hi = xr[:, :, QK:].reshape(T, K // 2)
    sx_lo, sx_hi = sx[:, 0::2], sx[:, 1::2]  # [T, K/64]

    bk, bo = qmatmul.tile_plan("q40", K, O)
    bt = min(T, qmatmul.T_BLOCK)
    out = pl.pallas_call(
        _q40_int8_kernel,
        grid=(pl.cdiv(T, bt), pl.cdiv(O, bo), K // bk),
        in_specs=[
            pl.BlockSpec((bt, bk // 2), lambda t_, o, k: (t_, k)),
            pl.BlockSpec((bt, bk // 2), lambda t_, o, k: (t_, k)),
            pl.BlockSpec((bt, bk // 64), lambda t_, o, k: (t_, k)),
            pl.BlockSpec((bt, bk // 64), lambda t_, o, k: (t_, k)),
            pl.BlockSpec((bk // 2, bo), lambda t_, o, k: (k, o)),
            pl.BlockSpec((bk // 64, bo), lambda t_, o, k: (k, o)),
            pl.BlockSpec((bk // 64, bo), lambda t_, o, k: (k, o)),
        ],
        out_specs=pl.BlockSpec((bt, bo), lambda t_, o, k: (t_, o)),
        out_shape=jax.ShapeDtypeStruct((T, O), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=jax.default_backend() != "tpu",
    )(x_lo, x_hi, sx_lo, sx_hi, packed, s_lo, s_hi)
    # -8 correction against the SAME quantized x the kernel saw
    xs = (sx * xq.astype(jnp.float32).sum(-1))  # [T, K/32]
    xs_lo, xs_hi = xs[:, 0::2], xs[:, 1::2]
    corr = 8.0 * (xs_lo @ s_lo + xs_hi @ s_hi)
    return (out - corr)[:t]


@jax.jit
def variant_f(x, qt):
    """variant B with 2048-lane O tiles (tile_plan caps bo at 1024): fewer,
    fatter grid steps — tests whether the cap costs bandwidth at 7B widths
    (11008 -> six 2048-blocks with one masked boundary block)."""
    import functools as ft

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    packed, s_lo, s_hi = qt.w, qt.s, qt.s2
    O = packed.shape[1]
    K = packed.shape[0] * 2
    xp, t = qmatmul._pad_rows(qmatmul._pad_cols(x.astype(jnp.bfloat16), K))
    T = xp.shape[0]
    xr = xp.reshape(T, K // 64, 64)
    x_lo = xr[:, :, :QK].reshape(T, K // 2)
    x_hi = xr[:, :, QK:].reshape(T, K // 2)
    bk, _ = qmatmul.tile_plan("q40", K, O)
    bo = min(2048, qmatmul._pad_up(O, 128))
    bt = min(T, qmatmul.T_BLOCK)
    out = pl.pallas_call(
        functools.partial(_q40_nosub_kernel, acc_dtype=jnp.float32),
        grid=(pl.cdiv(T, bt), pl.cdiv(O, bo), K // bk),
        in_specs=[
            pl.BlockSpec((bt, bk // 2), lambda t_, o, k: (t_, k)),
            pl.BlockSpec((bt, bk // 2), lambda t_, o, k: (t_, k)),
            pl.BlockSpec((bk // 2, bo), lambda t_, o, k: (k, o)),
            pl.BlockSpec((bk // 64, bo), lambda t_, o, k: (k, o)),
            pl.BlockSpec((bk // 64, bo), lambda t_, o, k: (k, o)),
        ],
        out_specs=pl.BlockSpec((bt, bo), lambda t_, o, k: (t_, o)),
        out_shape=jax.ShapeDtypeStruct((T, O), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=jax.default_backend() != "tpu",
    )(x_lo, x_hi, packed, s_lo, s_hi)
    xs = xp.astype(jnp.float32).reshape(T, K // QK, QK).sum(-1)
    xs_lo, xs_hi = xs[:, 0::2], xs[:, 1::2]
    corr = 8.0 * (xs_lo @ s_lo + xs_hi @ s_hi)
    return (out - corr)[:t]


#: variant G: B's kernel (f32 scales in-kernel) + CORRECTION dots reading
#: persistent bf16 scale copies — the nosub path's +100% scale re-read
#: becomes +50%, without D's in-kernel rounding (the correction term is
#: itself small, so bf16 rounding there is second-order). The bf16 copies
#: are cached per QuantTensor so the timed loop reads them from HBM, not
#: re-casts them.
_G_CACHE: dict = {}


def variant_g(x, qt):
    key = id(qt)
    # the cached entry keeps qt itself alive, so a recycled id() after GC
    # can never alias a different tensor's scales
    if key not in _G_CACHE or _G_CACHE[key][0] is not qt:
        _G_CACHE[key] = (qt, jnp.asarray(qt.s, jnp.bfloat16),
                         jnp.asarray(qt.s2, jnp.bfloat16))
    _, s_lo16, s_hi16 = _G_CACHE[key]
    return _variant_g_impl(x, qt, s_lo16, s_hi16)


@jax.jit
def _variant_g_impl(x, qt, s_lo_bf16, s_hi_bf16):
    import functools as ft

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    packed, s_lo, s_hi = qt.w, qt.s, qt.s2
    O = packed.shape[1]
    K = packed.shape[0] * 2
    xp, t = qmatmul._pad_rows(qmatmul._pad_cols(x.astype(jnp.bfloat16), K))
    T = xp.shape[0]
    xr = xp.reshape(T, K // 64, 64)
    x_lo = xr[:, :, :QK].reshape(T, K // 2)
    x_hi = xr[:, :, QK:].reshape(T, K // 2)
    bk, bo = qmatmul.tile_plan("q40", K, O)
    bt = min(T, qmatmul.T_BLOCK)
    out = pl.pallas_call(
        ft.partial(_q40_nosub_kernel, acc_dtype=jnp.float32),
        grid=(pl.cdiv(T, bt), pl.cdiv(O, bo), K // bk),
        in_specs=[
            pl.BlockSpec((bt, bk // 2), lambda t_, o, k: (t_, k)),
            pl.BlockSpec((bt, bk // 2), lambda t_, o, k: (t_, k)),
            pl.BlockSpec((bk // 2, bo), lambda t_, o, k: (k, o)),
            pl.BlockSpec((bk // 64, bo), lambda t_, o, k: (k, o)),
            pl.BlockSpec((bk // 64, bo), lambda t_, o, k: (k, o)),
        ],
        out_specs=pl.BlockSpec((bt, bo), lambda t_, o, k: (t_, o)),
        out_shape=jax.ShapeDtypeStruct((T, O), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=jax.default_backend() != "tpu",
    )(x_lo, x_hi, packed, s_lo, s_hi)
    xs = xp.astype(jnp.float32).reshape(T, K // QK, QK).sum(-1)
    xs_lo, xs_hi = xs[:, 0::2], xs[:, 1::2]
    corr = 8.0 * (xs_lo @ s_lo_bf16.astype(jnp.float32)
                  + xs_hi @ s_hi_bf16.astype(jnp.float32))
    return (out - corr)[:t]


#: (fn, scale-plane byte multiplier): A reads scales once; B/C read them
#: twice (in-kernel dequant + the correction dots); D stores them bf16,
#: halving their bytes; E reads them twice plus x-quant scales (small);
#: F like B; G = f32 kernel read + bf16 correction read = 1.5x
VARIANTS = {"A": (variant_a, 1.0), "B": (variant_b, 2.0),
            "C": (variant_c, 2.0), "D": (variant_d, 0.5),
            "E": (variant_e, 2.0), "F": (variant_f, 2.0),
            "G": (variant_g, 1.5)}


def nbytes_of(qt, scale_mult):
    return qt.w.nbytes + (qt.s.nbytes + qt.s2.nbytes) * scale_mult


def check(name, fn, qt, K):
    rng = np.random.default_rng(1)
    x = rng.standard_normal((2, K)).astype(np.float32)
    got = np.asarray(fn(jnp.asarray(x, jnp.bfloat16), qt), np.float32)
    want = x @ qmatmul.dequantize(qt)
    rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
    tol = 3e-2 if name != "D" else 4e-2  # D adds bf16 scale rounding
    print(f"{name}: rel-err {rel:.2e}", flush=True)
    return rel < tol


def timed(name, fn, qt, K, nbytes, n1=768, n2=1536, reps=5):
    @functools.partial(jax.jit, static_argnames=("n",))
    def run(x, n):
        def step(x, _):
            y = fn(x, qt)[:, :K]
            return (y * 1e-2).astype(x.dtype), ()
        x, _ = jax.lax.scan(step, x, None, length=n)
        return jnp.sum(x.astype(jnp.float32))

    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, K)),
                    jnp.bfloat16)

    def go(n):
        float(np.asarray(run(x, n)))
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            float(np.asarray(run(x, n)))
            ts.append(time.perf_counter() - t0)
        return statistics.median(ts)

    ms = max(go(n2) - go(n1), 1e-9) * 1e3 / (n2 - n1)
    print(f"{name}: {ms:7.4f} ms/call -> {nbytes/(ms*1e-3)/1e9:7.1f} GB/s",
          flush=True)


def stacked_ab(K, O, L=8, n1=96, n2=192, reps=5):
    """A/B the LAYER-STACKED scalar-prefetch path (the decode scan's form):
    scan over L layers calling q40_matmul_stacked with nosub False vs True.
    This is the integration actually driving per-token decode latency —
    the flat-variant numbers above can't see prefetch/correction-kernel
    interactions."""
    rng = np.random.default_rng(0)
    qts = [qmatmul.quantize_tensor(
        rng.standard_normal((K, O)).astype(np.float32) * 0.1, "q40",
        to_device=False) for _ in range(L)]
    w = jnp.asarray(np.stack([q.w for q in qts]))
    s = jnp.asarray(np.stack([q.s for q in qts]))
    s2 = jnp.asarray(np.stack([q.s2 for q in qts]))
    nbytes = w.nbytes / L  # per layer-call; scales accounted via multiplier

    for name, nosub in (("S-sub", False), ("S-nosub", True)):
        # w/s/s2 are traced ARGUMENTS: closure capture would bake ~300 MB
        # of planes into the program as constants (a compile that takes
        # minutes; see ablate_decode.py)
        @functools.partial(jax.jit, static_argnames=("n", "nosub"))
        def run(x, w, s, s2, n, nosub=nosub):
            def step(carry, i):
                y = qmatmul.q40_matmul_stacked(
                    carry, w, s, s2, i % jnp.int32(L), nosub=nosub)[:, :K]
                return (y * 1e-2).astype(carry.dtype), ()
            x, _ = jax.lax.scan(step, x, jnp.arange(n, dtype=jnp.int32))
            return jnp.sum(x.astype(jnp.float32))

        x = jnp.asarray(rng.standard_normal((1, K)), jnp.bfloat16)

        def go(n):
            float(np.asarray(run(x, w, s, s2, n)))
            ts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                float(np.asarray(run(x, w, s, s2, n)))
                ts.append(time.perf_counter() - t0)
            return statistics.median(ts)

        ms = max(go(n2) - go(n1), 1e-9) * 1e3 / (n2 - n1)
        mult = 2.0 if nosub else 1.0
        nb = nbytes + (s.nbytes + s2.nbytes) / L * mult
        print(f"{name}: {ms:7.4f} ms/layer-call -> {nb/(ms*1e-3)/1e9:7.1f}"
              " GB/s", flush=True)


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    K = int(sys.argv[2]) if len(sys.argv) > 2 else 4096
    O = int(sys.argv[3]) if len(sys.argv) > 3 else 11008
    on_tpu = jax.default_backend() == "tpu"
    if which in ("all", "S"):
        if on_tpu:
            stacked_ab(K, O)
        else:
            print("stacked A/B skipped: not on TPU", flush=True)
        if which == "S":
            sys.exit(0 if on_tpu else 1)
    qt = qmatmul.quantize_tensor(
        np.random.default_rng(0).standard_normal((K, O)).astype(np.float32) * 0.1,
        "q40")
    names = list(VARIANTS) if which == "all" else [which]
    for n in names:
        fn, scale = VARIANTS[n]
        if check(n, fn, qt, K) and on_tpu:
            timed(n, fn, qt, K, nbytes_of(qt, scale))
    if not on_tpu:
        print("(CPU interpret mode: correctness only, no timing)", flush=True)
