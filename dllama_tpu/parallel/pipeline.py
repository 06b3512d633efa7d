"""Pipeline parallelism — layers partitioned into stages over a ``pp`` mesh
axis, GPipe-style microbatch schedule.

The reference has no pipeline axis (every node holds slices of ALL layers,
SURVEY.md §2.3); PP exists here because a TPU pod has more chips than a
kv-head-constrained tensor-parallel dimension can use — stages scale along a
second mesh axis with only point-to-point ``ppermute`` traffic between
neighbors (cheap on an ICI torus), instead of widening the per-layer
AllReduces.

Construction (the standard circular-pipeline formulation): under
``shard_map`` each device holds ``n_layers / S`` consecutive layers (the
stacked layer pytree is simply sharded on its leading axis). The batch is cut
into ``M`` microbatches; the schedule runs ``M + S - 1`` ticks. Every tick,
each stage runs its layer block on its current activation and passes the
result to the next stage with a single ``ppermute`` rotation; stage 0 ingests
a fresh microbatch each of the first ``M`` ticks, and the last stage emits a
finished microbatch on each of the final ``M`` ticks. The pipeline "bubble"
is the usual (S-1)/(M+S-1) fraction — pick M >= S to amortize it.

Differentiable end-to-end (``ppermute`` and ``scan`` both have transpose
rules), so the same schedule serves training; wrap the stage body in
``jax.checkpoint`` for rematerialized backprop if activations dominate HBM.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dllama_tpu.models import llama
from dllama_tpu.models.config import ModelConfig


def pipeline_forward_train(
    cfg: ModelConfig,
    params: dict,
    tokens: jnp.ndarray,  # [B, T] int32
    mesh,
    rope: dict = None,
    pp_axis: str = "pp",
    n_microbatches: int = None,
    remat: bool = False,
) -> jnp.ndarray:
    """Cache-free causal forward with the layer stack pipelined over
    ``pp_axis``. Returns logits [B, T, vocab] — numerically identical to
    ``llama.forward_train`` (proven in tests/test_pipeline.py).

    Requires ``n_layers % S == 0`` and ``B % n_microbatches == 0``.
    Embedding and the logits head run outside the pipelined region (they are
    layer-independent; keep them under whatever dp/tp sharding the caller's
    pjit chose).
    """
    S = mesh.shape[pp_axis]
    B, T = tokens.shape
    M = n_microbatches if n_microbatches is not None else max(S, 1)
    if cfg.n_layers % S != 0:
        raise ValueError(f"n_layers {cfg.n_layers} not divisible by pp={S}")
    if B % M != 0:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")

    rope_t = rope if rope is not None else llama.rope_tables(cfg)
    cos = rope_t["cos"][:T][None, :, None, :]
    sin = rope_t["sin"][:T][None, :, None, :]

    x = llama.embed(cfg, params, tokens)  # [B, T, D]
    xs = x.reshape(M, B // M, T, cfg.dim)  # microbatches

    def stage_body(local_layers, cos_, sin_, h):
        def step(h, lp):
            return llama.train_layer(cfg, lp, cos_, sin_, h), None

        body = jax.checkpoint(lambda h_: jax.lax.scan(step, h_, local_layers)[0]) \
            if remat else (lambda h_: jax.lax.scan(step, h_, local_layers)[0])
        return body(h)

    def pipelined(local_layers, cos_, sin_, xs_):
        idx = jax.lax.axis_index(pp_axis)
        n_ticks = M + S - 1
        # pad the input stream to n_ticks (stage 0 only reads the first M)
        pad = jnp.zeros((n_ticks - M,) + xs_.shape[1:], xs_.dtype)
        stream = jnp.concatenate([xs_, pad], axis=0)

        def tick(buf, xt):
            # stage 0 ingests the fresh microbatch; others take what the
            # previous stage handed over on the last rotation
            inp = jnp.where(idx == 0, xt, buf)
            out = stage_body(local_layers, cos_, sin_, inp)
            nxt = jax.lax.ppermute(
                out, pp_axis, [(i, (i + 1) % S) for i in range(S)]
            )
            return nxt, out

        _, outs = jax.lax.scan(tick, jnp.zeros_like(xs_[0]), stream)
        # the last stage's outputs on the final M ticks are the finished
        # microbatches, in order; psum broadcasts them to every stage
        finished = outs[S - 1 :]
        mask = (idx == S - 1).astype(finished.dtype)
        return jax.lax.psum(finished * mask, pp_axis)

    mapped = jax.shard_map(
        pipelined,
        mesh=mesh,
        in_specs=(P(pp_axis), P(), P(), P()),
        out_specs=P(),
        check_vma=False,
        axis_names={pp_axis},
    )
    y = mapped(params["layers"], cos, sin, xs).reshape(B, T, cfg.dim)

    y = llama.rmsnorm(y, params["rms_final"], cfg.norm_eps)
    logits = (y @ params["wcls"]).astype(jnp.float32)
    return logits * cfg.logit_scale if cfg.logit_scale != 1.0 else logits
