"""Microbench the fused dequant-matmul kernels at decode shapes.

Dispatch is asynchronous and a host sync (pulling bytes) has a fixed cost,
so this bench times ``iters`` and ``2*iters`` chained kernel calls inside one
jitted ``lax.scan`` each, with a host pull at the end, and reports the
DIFFERENCE — the fixed round trip and compile-cached dispatch cancel, leaving
device time per call. Host-clock timing: ROADMAP S2's trace reduction
supersedes it. Effective GB/s is against the bytes the kernel must
stream (weights + scales; activations are noise at T=1).

Usage: python scripts/kernel_bench.py [q40|q80|bf16|all] [K] [O] [iters]

``gather`` mode microbenches the TP activation wire instead of the matmul
kernels: the plain fused all-gather vs the Q80-compressed payload vs the
``lax.ppermute`` ring schedule (collectives.RingAxis — what ``--tp-overlap``
pipelines against the other microbatch's compute), at decode activation
sizes (T rows x F features, gathered across all visible devices). Same
difference-timing idiom, so the host round trip cancels.

Usage: python scripts/kernel_bench.py gather [F] [T] [iters]

``fused`` mode times the two decode epilogue fusions against their unfused
compositions at decode activation sizes — rmsnorm folded into the q40/q80
projection (DLLAMA_FUSE_NORM's kernel) vs rmsnorm-then-qmatmul, and the
one-pass rope+cache write (DLLAMA_FUSE_ROPE_CACHE's kernel) vs
apply_rope + dynamic_update_slice. Same difference-timing idiom; each pair
appends a delta row (fused_ms, unfused_ms, delta_ms) to
results/trajectory.jsonl so the win is tracked across rounds, not eyeballed.

Usage: python scripts/kernel_bench.py fused [K] [O] [iters] [T]

``reduce`` mode microbenches the row-parallel reduce direction
(``--tp-reduce``) at decode partial-sum shapes: a fused ``jax.lax.psum``
vs the pinned-order ``lax.ppermute`` ring reduce-scatter(+gather) vs the
Q80-compressed ring (int8 quants + bitcast f32 scales per hop). Each
schedule appends a row to results/trajectory.jsonl with its modeled
wire bytes, so the quantized ring's win (or loss) on real hardware is
tracked across rounds. Same difference-timing idiom.

Usage: python scripts/kernel_bench.py reduce [F] [T] [iters]
"""

import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, __import__("os").path.dirname(__import__("os").path.dirname(
    __import__("os").path.abspath(__file__))))

from dllama_tpu.ops import qmatmul  # noqa: E402


def _timed_host_sync(run, *args, reps=3):
    float(np.asarray(run(*args)))  # compile + warm
    best = 1e9
    for _ in range(reps):
        t0 = time.perf_counter()
        float(np.asarray(run(*args)))
        best = min(best, time.perf_counter() - t0)
    return best


def bench(kind, K, O, iters=256, T=1):
    rng = np.random.default_rng(0)
    if kind == "bf16":
        w = jnp.asarray(rng.standard_normal((K, O)).astype(np.float32)).astype(jnp.bfloat16)
        nbytes = w.nbytes
        mm = lambda x, w: x @ w
        wargs = (w,)
    else:
        qt = qmatmul.quantize_tensor(
            rng.standard_normal((K, O)).astype(np.float32), kind)
        nbytes = qt.w.nbytes + qt.s.nbytes + qt.s2.nbytes
        mm = lambda x, qt: qmatmul.qmatmul(x, qt)
        wargs = (qt,)

    @functools.partial(jax.jit, static_argnames=("n",))
    def run(x, *w, n):
        def step(x, _):
            y = mm(x, *w)
            y = y[:, :K] if O >= K else jnp.pad(y, ((0, 0), (0, K - O)))
            return (y * 1e-2).astype(x.dtype), ()
        x, _ = jax.lax.scan(step, x, None, length=n)
        return jnp.sum(x.astype(jnp.float32))

    x = jnp.asarray(rng.standard_normal((T, K)).astype(np.float32)).astype(jnp.bfloat16)
    t1 = _timed_host_sync(functools.partial(run, n=iters), x, *wargs)
    t2 = _timed_host_sync(functools.partial(run, n=2 * iters), x, *wargs)
    ms = max(t2 - t1, 1e-9) * 1e3 / iters
    gbs = nbytes / (ms * 1e-3) / 1e9
    print(f"{kind:5s} K={K} O={O} T={T}: {ms:7.3f} ms/call  "
          f"{nbytes/1e6:8.1f} MB streamed  -> {gbs:7.1f} GB/s effective"
          f"   [t({iters})={t1*1e3:.0f}ms t({2*iters})={t2*1e3:.0f}ms]",
          flush=True)
    return ms, gbs


def bench_gather(F=4096, T=1, iters=256):
    """Time one TP activation gather three ways at a decode shape: plain
    fused all-gather, Q80-compressed payload (1.125 bytes/feature in ONE
    collective), and the ppermute ring schedule the overlap mode uses.
    Wire bytes are the (tp-1)/tp fraction each chip must receive."""
    from dllama_tpu.parallel import collectives
    from dllama_tpu.parallel.mesh import tp_mesh


    tp = len(jax.devices())
    if tp < 2:
        raise SystemExit(
            "gather mode needs >1 device (TPU slice, or CPU with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    mesh = tp_mesh(tp)
    f_local = F // tp // 32 * 32  # local shard, q80-block aligned
    F_eff = f_local * tp
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((T, F_eff)).astype(np.float32)
                    ).astype(jnp.bfloat16)

    results = {}
    for name, axis, compress in (
        ("plain", "tp", False),
        ("q80", "tp", True),
        ("ring", collectives.RingAxis("tp"), False),
        ("ring+q80", collectives.RingAxis("tp"), True),
    ):
        def tp_gather(xs, _axis=axis, _c=compress):
            g = collectives.gather_columns(xs, _axis, compress=_c)
            # feed the local shard back in so scan iterations chain (no CSE)
            idx = jax.lax.axis_index("tp")
            lo = idx * f_local
            return (jax.lax.dynamic_slice_in_dim(g, lo, f_local, axis=-1)
                    * jnp.bfloat16(1.0))

        sharded = jax.shard_map(
            tp_gather, mesh=mesh,
            in_specs=jax.sharding.PartitionSpec(None, "tp"),
            out_specs=jax.sharding.PartitionSpec(None, "tp"))

        @functools.partial(jax.jit, static_argnames=("n",))
        def run(xs, n):
            def step(xs, _):
                return sharded(xs), ()
            xs, _ = jax.lax.scan(step, xs, None, length=n)
            return jnp.sum(xs.astype(jnp.float32))

        t1 = _timed_host_sync(functools.partial(run, n=iters), x)
        t2 = _timed_host_sync(functools.partial(run, n=2 * iters), x)
        ms = max(t2 - t1, 1e-9) * 1e3 / iters
        wire = (T * F_eff * (1.125 if compress else 2.0)) * (tp - 1) / tp
        results[name] = ms
        print(f"gather {name:8s} F={F_eff} T={T} tp={tp}: {ms:7.4f} ms/call"
              f"  {wire/1e3:7.1f} KB wire/chip"
              f"   [t({iters})={t1*1e3:.0f}ms t({2*iters})={t2*1e3:.0f}ms]",
              flush=True)
    return results


def bench_reduce(F=4096, T=1, iters=256):
    """Time one full-width f32 partial-sum reduction three ways at a
    decode shape: the fused ``jax.lax.psum`` (XLA's schedule, baseline),
    the pinned-order ring reduce-scatter + gather (``--tp-reduce plain``
    — bit-reproducible), and the Q80-compressed ring (``--tp-reduce
    q80``).  Ring wire bytes per chip: (tp-1) hops x F/tp chunk at 4.0
    (plain) or 1.125 (q80) bytes/feature for the scatter half, plus the
    (tp-1)/tp x F x 4.0 trailing f32 gather."""
    from dllama_tpu.obsv import trajectory
    from dllama_tpu.parallel import collectives
    from dllama_tpu.parallel.mesh import tp_mesh

    tp = len(jax.devices())
    if tp < 2:
        raise SystemExit(
            "reduce mode needs >1 device (TPU slice, or CPU with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    mesh = tp_mesh(tp)
    F_eff = F // (32 * tp) * (32 * tp)  # whole q80-aligned chunks per device
    rng = np.random.default_rng(0)
    # [tp, T, F]: axis 0 sharded, so each device carries one full-width partial
    x = jnp.asarray(rng.standard_normal((tp, T, F_eff)).astype(np.float32))

    results = {}
    for name, red in (
        ("psum", lambda p: jax.lax.psum(p, "tp")),
        ("ring", lambda p: collectives.reduce_columns(p, "tp", False)),
        ("ring+q80", lambda p: collectives.reduce_columns(p, "tp", True)),
    ):
        def tp_reduce(xs, _red=red):
            # scale down so the chained sum of sums stays finite over the scan
            return (_red(xs[0]) * np.float32(1.0 / (2.0 * tp)))[None]

        sharded = jax.shard_map(
            tp_reduce, mesh=mesh,
            in_specs=jax.sharding.PartitionSpec("tp"),
            out_specs=jax.sharding.PartitionSpec("tp"))

        @functools.partial(jax.jit, static_argnames=("n",))
        def run(xs, n):
            def step(xs, _):
                return sharded(xs), ()
            xs, _ = jax.lax.scan(step, xs, None, length=n)
            return jnp.sum(xs)

        t1 = _timed_host_sync(functools.partial(run, n=iters), x)
        t2 = _timed_host_sync(functools.partial(run, n=2 * iters), x)
        ms = max(t2 - t1, 1e-9) * 1e3 / iters
        scat_feat = 1.125 if name == "ring+q80" else 4.0
        if name == "psum":
            wire = T * F_eff * 4.0 * 2 * (tp - 1) / tp  # reduce-scatter+gather
        else:
            wire = T * F_eff * (tp - 1) / tp * (scat_feat + 4.0)
        results[name] = ms
        print(f"reduce {name:8s} F={F_eff} T={T} tp={tp}: {ms:7.4f} ms/call"
              f"  {wire/1e3:7.1f} KB wire/chip"
              f"   [t({iters})={t1*1e3:.0f}ms t({2*iters})={t2*1e3:.0f}ms]",
              flush=True)
        trajectory.append_row(
            f"kernel_reduce/{name}", "ok",
            result={"metric": f"{name}_ms", "value": ms,
                    "wire_kb_chip": wire / 1e3, "F": F_eff, "T": T, "tp": tp,
                    "backend": jax.default_backend()})
    return results


def _timed_scan(step_fn, carry, iters):
    """Difference-timed ms/call for ``step_fn`` chained through one jitted
    scan — same round-trip-cancelling idiom as bench()."""
    @functools.partial(jax.jit, static_argnames=("n",))
    def run(c, n):
        c, _ = jax.lax.scan(lambda c, _: (step_fn(c), ()), c, None, length=n)
        return jnp.sum(jax.tree.leaves(c)[0].astype(jnp.float32))

    t1 = _timed_host_sync(functools.partial(run, n=iters), carry)
    t2 = _timed_host_sync(functools.partial(run, n=2 * iters), carry)
    return max(t2 - t1, 1e-9) * 1e3 / iters


def bench_fused(kind="q40", K=4096, O=4096, iters=256, T=1):
    """Fused-vs-unfused delta for both decode epilogues; one trajectory
    row per pair. delta_ms = fused - unfused, so negative is a win and
    the trajectory comparator's "_ms means lower-is-better" rule flags a
    fusion that stops paying for itself."""
    from dllama_tpu.obsv import trajectory
    from dllama_tpu.ops import fused_rope_cache, rope
    from dllama_tpu.ops.norms import rmsnorm

    rng = np.random.default_rng(0)
    rows = {}

    # -- rmsnorm folded into the quantized projection -----------------------
    qt = qmatmul.quantize_tensor(
        rng.standard_normal((K, O)).astype(np.float32) * 0.1, kind)
    nw = jnp.asarray(rng.standard_normal((K,)).astype(np.float32) * 0.5 + 1.0)
    x = jnp.asarray(rng.standard_normal((T, K)).astype(np.float32)
                    ).astype(jnp.bfloat16)

    def _chain(y):  # feed output back as the next activation (no CSE)
        y = y[:, :K] if O >= K else jnp.pad(y, ((0, 0), (0, K - O)))
        return (y * 1e-2).astype(jnp.bfloat16)

    norm_ms = {
        "unfused": _timed_scan(
            lambda c: _chain(qmatmul.qmatmul(rmsnorm(c, nw, 1e-5), qt)),
            x, iters),
        "fused": _timed_scan(
            lambda c: _chain(qmatmul.qmatmul_norm(c, nw, qt)), x, iters),
    }
    rows[f"norm_{kind}"] = norm_ms

    # -- rope + cache write -------------------------------------------------
    L, S, n_kv, hd = 1, 2048, 8, 128
    k0 = jnp.asarray(rng.standard_normal((T, n_kv, hd)).astype(np.float32)
                     ).astype(jnp.bfloat16)
    kc0 = jnp.zeros((L, S, n_kv, hd), jnp.bfloat16)
    cos_t, sin_t = map(jnp.asarray, rope.rope_table(S, hd, 10000.0))
    pos, layer = jnp.int32(S // 2), jnp.int32(0)
    cos = jax.lax.dynamic_slice_in_dim(cos_t, pos, T)[:, None, :]
    sin = jax.lax.dynamic_slice_in_dim(sin_t, pos, T)[:, None, :]

    def rope_unfused(c):
        kc, vc = c
        kr = rope.apply_rope(k0, cos, sin, rope.INTERLEAVED)
        z = jnp.int32(0)
        kc = jax.lax.dynamic_update_slice(kc, kr.astype(kc.dtype)[None],
                                          (layer, pos, z, z))
        vc = jax.lax.dynamic_update_slice(vc, k0.astype(vc.dtype)[None],
                                          (layer, pos, z, z))
        return kc, vc

    def rope_fused(c):
        return fused_rope_cache.rope_cache_update(
            k0, k0, cos, sin, c[0], c[1], pos, layer, rope.INTERLEAVED)

    rope_ms = {
        "unfused": _timed_scan(rope_unfused, (kc0, kc0), iters),
        "fused": _timed_scan(rope_fused, (kc0, kc0), iters),
    }
    rows["rope_cache"] = rope_ms

    for name, ms in rows.items():
        delta = ms["fused"] - ms["unfused"]
        print(f"fused {name:10s} K={K} O={O} T={T}: "
              f"fused {ms['fused']:7.4f} ms  unfused {ms['unfused']:7.4f} ms"
              f"  delta {delta:+.4f} ms/call", flush=True)
        trajectory.append_row(
            f"kernel_fused/{name}", "ok",
            result={"metric": f"{name}_delta_ms", "value": delta,
                    "fused_ms": ms["fused"], "unfused_ms": ms["unfused"],
                    "K": K, "O": O, "T": T,
                    "backend": jax.default_backend()})
    return rows


if __name__ == "__main__":
    kind = sys.argv[1] if len(sys.argv) > 1 else "all"
    if kind == "gather":
        F = int(sys.argv[2]) if len(sys.argv) > 2 else 4096
        T = int(sys.argv[3]) if len(sys.argv) > 3 else 1
        iters = int(sys.argv[4]) if len(sys.argv) > 4 else 256
        bench_gather(F, T, iters)
        sys.exit(0)
    if kind == "reduce":
        F = int(sys.argv[2]) if len(sys.argv) > 2 else 4096
        T = int(sys.argv[3]) if len(sys.argv) > 3 else 1
        iters = int(sys.argv[4]) if len(sys.argv) > 4 else 256
        bench_reduce(F, T, iters)
        sys.exit(0)
    if kind == "fused":
        K = int(sys.argv[2]) if len(sys.argv) > 2 else 4096
        O = int(sys.argv[3]) if len(sys.argv) > 3 else 4096
        iters = int(sys.argv[4]) if len(sys.argv) > 4 else 64
        T = int(sys.argv[5]) if len(sys.argv) > 5 else 1
        for k in ("q40", "q80"):
            bench_fused(k, K, O, iters, T)
        sys.exit(0)
    K = int(sys.argv[2]) if len(sys.argv) > 2 else 4096
    O = int(sys.argv[3]) if len(sys.argv) > 3 else 11008
    iters = int(sys.argv[4]) if len(sys.argv) > 4 else 256
    kinds = ("q40", "q80", "bf16") if kind == "all" else (kind,)
    for k in kinds:
        bench(k, K, O, iters)
