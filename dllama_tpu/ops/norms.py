"""Normalization ops.

RMSNorm semantics match the reference exactly
(`/root/reference/src/funcs.cpp:94-123`): ``inv = 1/sqrt(mean(x^2) + 1e-5)``,
``y = w * (inv * x)`` — note eps is added to the *mean*, and the reference
computes everything in f32. We keep the accumulation in f32 regardless of the
activation dtype so bf16 runs stay numerically anchored.
"""

from __future__ import annotations

import jax.numpy as jnp

RMS_EPS = 1e-5


def rmsnorm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = RMS_EPS) -> jnp.ndarray:
    """RMS-normalize the last axis. x: [..., dim], weight: [dim]."""
    xf = x.astype(jnp.float32)
    inv = jnp.reciprocal(jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps))
    return (weight.astype(jnp.float32) * (xf * inv)).astype(x.dtype)


def layernorm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = RMS_EPS) -> jnp.ndarray:
    """LayerNorm without bias over the last axis, in f32:
    ``w * (x - mean(x)) / sqrt(var(x) + eps)``, which is ``rmsnorm`` of the
    centred input."""
    xf = x.astype(jnp.float32)
    xc = xf - jnp.mean(xf, axis=-1, keepdims=True)
    return rmsnorm(xc, weight, eps).astype(x.dtype)


def centred(x: jnp.ndarray) -> jnp.ndarray:
    """``x - mean(x)`` over the last axis, in f32, back in x's dtype: what a
    fused rmsnorm epilogue takes to compute a LayerNorm."""
    xf = x.astype(jnp.float32)
    return (xf - jnp.mean(xf, axis=-1, keepdims=True)).astype(x.dtype)


NORMS = {"rms": rmsnorm, "layer": layernorm}
