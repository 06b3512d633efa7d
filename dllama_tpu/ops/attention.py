"""Grouped-query attention over a fixed-size KV cache.

Semantics mirror the reference per-head loop
(`/root/reference/src/llama2-tasks.cpp:54-94`): score = q.k / sqrt(head_size),
softmax over positions 0..pos (inclusive), weighted sum of V. The reference
iterates positions serially per token; here the whole history is one masked
MXU-friendly einsum, and prefill processes T query positions at once under a
causal mask — numerically identical, shapes static for XLA.

Softmax runs in f32 whatever the activation dtype (the reference is all-f32).

Layers of a model with a layer plan add three things, all off by default (a
uniform model's call traces to what it did): value heads narrower than the
query/key heads (the output takes the values' width), a sliding window over
a RING cache (``window``: slot ``s`` holds the latest position ``p <= pos +
T - 1`` with ``p % S == s``, and query ``i`` sees ``i - window < p <= i``),
and a sink (one learned score a head that joins the softmax's denominator
and carries no value).

A caller that knows how far its queries reach may hand in a PREFIX of the
cache (``prefix_rungs``, ``covering_rung``: a ladder of static prefix lengths
and the first that covers a reach) and, for a ring, the ring's whole slot
count beside it (``ring``): a slot beyond a prefix that covers every position
the queries see is one the mask gives weight 0, so the result is the same
and the slots left out are neither read nor scored.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

#: the shortest prefix of a cache that attention is cut to: a cache of no
#: more slots has a ladder of one rung and is read whole, as ever
LEAST_RUNG = 1024


def prefix_rungs(slots: int, least: int) -> tuple:
    """The prefix lengths a cache of ``slots`` is read at: ``least`` doubled
    while it stays under ``slots``, then ``slots`` itself."""
    rungs = []
    while least < slots:
        rungs.append(least)
        least *= 2
    return (*rungs, slots)


def covering_rung(reach, rungs: tuple):
    """Index of the first rung with ``reach`` slots or more (the last where
    none has): ``reach`` is how many leading slots can hold a position some
    query sees. numpy or traced, any shape of ``reach``."""
    return (reach[..., None] > np.asarray(rungs[:-1], np.int32)).sum(
        axis=-1, dtype=np.int32)


def gqa_attention(
    q: jnp.ndarray,  # [T, n_heads, head_size]
    k_cache: jnp.ndarray,  # [S, n_kv_heads, head_size]
    v_cache: jnp.ndarray,  # [S, n_kv_heads, v_head_size]
    pos: jnp.ndarray,  # scalar int32: position of q[0] in the sequence
    window: int = 0,  # > 0: the caches are rings of S slots, see above
    sink: jnp.ndarray | None = None,  # [n_heads] f32
    ring: int = 0,  # > 0: the caches are the first S of a ring of ``ring``
) -> jnp.ndarray:
    """Masked GQA attention. Returns [T, n_heads, v_head_size].

    The cache must already contain this step's K/V at positions pos..pos+T-1.
    Query t attends to cache positions <= pos + t; everything later is masked.
    """
    T, n_heads, head_size = q.shape
    S, n_kv_heads, _ = k_cache.shape
    group = n_heads // n_kv_heads

    qf = q.astype(jnp.float32).reshape(T, n_kv_heads, group, head_size)
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)

    scores = jnp.einsum("tkgh,skh->tkgs", qf, kf) / jnp.sqrt(jnp.float32(head_size))

    key_idx = jnp.arange(S, dtype=jnp.int32)[None, :]  # [1, S]
    query_pos = pos + jnp.arange(T, dtype=jnp.int32)[:, None]  # [T, 1]
    if window:
        # the position each ring slot holds: the latest one written into it
        last = pos + (T - 1)
        key_idx = last - jnp.mod(last - key_idx, ring or S)
        mask = ((key_idx <= query_pos) & (key_idx > query_pos - window)
                & (key_idx >= 0))
    else:
        mask = key_idx <= query_pos  # [T, S]
    scores = jnp.where(mask[:, None, None, :], scores, jnp.float32(-1e30))

    top = scores.max(axis=-1, keepdims=True)
    if sink is not None:
        sk = sink.astype(jnp.float32).reshape(1, n_kv_heads, group, 1)
        top = jnp.maximum(top, sk)
    att = jnp.exp(scores - top)
    den = att.sum(axis=-1, keepdims=True)
    if sink is not None:
        den = den + jnp.exp(sk - top)
    att = att / den

    out = jnp.einsum("tkgs,skh->tkgh", att, vf)
    return out.reshape(T, n_heads, vf.shape[-1]).astype(q.dtype)
