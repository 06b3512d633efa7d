"""Decode-latency ablation on the real TPU — finds where the ms/token go.

Times each variant as ONE fused scanned program, so per-dispatch host
latency is paid once per variant and not once per kernel. Host-clock timing:
ROADMAP S2's trace reduction supersedes it. Variants:

  full        the production fused decode step (fused wqkv/w13 kernels)
  unfused     same but per-matrix kernels (pre-fusion layout)
  matmuls     per-layer quant matmuls only (no attention/norms/sampling)
  no_wcls     full minus the final vocab projection
  bf16        dense bf16 weights (the non-quant baseline)

Usage: python scripts/ablate_decode.py [tiny|7b] [steps]
"""

import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, __import__("os").path.dirname(__import__("os").path.dirname(
    __import__("os").path.abspath(__file__))))

from bench import LLAMA2_7B, TINYLLAMA_1_1B  # noqa: E402
from dllama_tpu.models import llama  # noqa: E402
from dllama_tpu.models.config import ModelConfig  # noqa: E402
from dllama_tpu.ops.qmatmul import QuantTensor, matmul_any  # noqa: E402
from dllama_tpu.runtime.generate import Engine  # noqa: E402
from dllama_tpu.runtime.sampler import SamplerConfig  # noqa: E402


def timed(label, fn, *args, reps=3):
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best


def engine_variant(cfg, params, steps, fuse_quant=True):
    eng = Engine(cfg, params, SamplerConfig(temperature=0.0),
                 cache_dtype=jnp.bfloat16, fuse_quant=fuse_quant)
    eng.generate_fused([1], steps=steps)  # compile
    t0 = time.perf_counter()
    eng.generate_fused([1], steps=steps)
    return (time.perf_counter() - t0) * 1000.0 / steps


def matmuls_only(cfg, params, steps):
    """Scan of per-layer quant matmuls with data dependency, no attention."""

    # layers MUST be a traced argument, not a closure capture: jit bakes
    # captured arrays in as constants, and shipping a 7B model's 3.5 GB of
    # quant planes as compile-time literals makes the compile take minutes
    @jax.jit
    def run(x, layers):
        def step(x, _):
            def layer(x, lp):
                names = [n for n in ("wqkv", "wq", "wk", "wv") if n in lp]
                acc = 0.0
                for n in names:
                    acc = acc + matmul_any(x, lp[n])[:, : cfg.dim].sum()
                o = matmul_any(x, lp["wo"])
                h13 = lp.get("w13")
                if h13 is not None:
                    h = matmul_any(x, h13)
                    half = h.shape[-1] // 2
                    h = h[:, :half] + h[:, half:]
                else:
                    h = matmul_any(x, lp["w1"]) + matmul_any(x, lp["w3"])
                d = matmul_any(h, lp["w2"])
                return x + (o + d) * 0.0 + acc * 0.0, None

            x, _ = jax.lax.scan(layer, x, layers)
            return x, x[0, 0]

        x, ys = jax.lax.scan(step, x, None, length=steps)
        return ys.sum()

    x = jnp.ones((1, cfg.dim), jnp.bfloat16)
    dt = timed("matmuls", run, x, params["layers"])
    return dt * 1000.0 / steps


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "7b"
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 64
    cfg = ModelConfig(**(LLAMA2_7B if which == "7b" else TINYLLAMA_1_1B))
    print(f"backend={jax.default_backend()} model={which} steps={steps}")

    qp = llama.device_random_quant_params(cfg, kind="q40", seed=0)
    jax.block_until_ready(qp)

    fused = llama.fuse_qkv_ffn(qp)
    print(f"full (fused):   {engine_variant(cfg, dict(fused), steps):8.3f} ms/token")
    print(f"matmuls only:   {matmuls_only(cfg, fused, steps):8.3f} ms/token (fused)")
    print(f"full (unfused): {engine_variant(cfg, qp, steps, fuse_quant=False):8.3f} ms/token")
    print(f"matmuls only:   {matmuls_only(cfg, qp, steps):8.3f} ms/token (unfused)")

    # no-wcls: replace the classifier with a tiny dense matrix
    import dataclasses

    nw = dict(fused)
    nw["wcls"] = jnp.zeros((cfg.dim, 128), jnp.bfloat16)
    cfg_small_vocab = dataclasses.replace(cfg, vocab_size=128)
    print(f"tiny wcls:      {engine_variant(cfg_small_vocab, nw, steps):8.3f} ms/token")

    del qp, fused, nw
    jax.clear_caches()
    bp = llama.device_random_params(cfg, seed=0)
    print(f"bf16 dense:     {engine_variant(cfg, bp, steps):8.3f} ms/token")


if __name__ == "__main__":
    main()
