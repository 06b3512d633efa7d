"""Ring attention — sequence/context parallelism over the ``sp`` mesh axis.

The reference never distributes the sequence dimension: its attention iterates
the full local KV history serially per token and seqLen is capped by a 16-bit
position type (`/root/reference/src/llama2-tasks.cpp:62-93`,
`/root/reference/src/transformer.hpp:9`). On TPU, long context is a
first-class axis: each device holds a contiguous sequence chunk of Q/K/V, and
K/V chunks rotate around the ring over ICI (``jax.lax.ppermute``) while every
device accumulates its queries' attention with an online (streaming) softmax —
compute and memory per device stay O(seq/n_sp), and the rotation overlaps
with the per-step attention matmuls.

This is the Ring Attention construction (Liu et al. 2023; see PAPERS.md) — the
blockwise-parallel formulation with a running (max, denominator, accumulator)
triple, causal masking resolved per (query-chunk, kv-chunk) pair:

* kv chunk strictly before the query chunk -> attend to all of it
* same chunk -> local causal mask
* kv chunk after the query chunk -> fully masked, contributes nothing

Differentiable end-to-end (ppermute has a transpose rule), so the training
step shards sequence the same way.

Usage: wrap with ``shard_map`` over a mesh with an ``sp`` axis — see
``ring_self_attention`` for the canonical causal self-attention entry and
``tests/test_ring_attention.py`` for the invariance proof vs dense attention.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _chunk_scores(q, k):
    """Raw scaled scores for one (q-chunk, kv-chunk) pair.

    q [B, Tq, Hkv, G, D]; k [B, Tkv, Hkv, D] -> [B, Hkv, G, Tq, Tkv].
    """
    return jnp.einsum("btkgh,bskh->bkgts", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1])
    )


def ring_attention_kernel(
    q: jnp.ndarray,  # [B, Tc, Hkv, G, D] f32 — local query chunk
    k: jnp.ndarray,  # [B, Tc, Hkv, D] f32 — local key chunk
    v: jnp.ndarray,  # [B, Tc, Hkv, D] f32 — local value chunk
    axis_name: str,
    causal: bool = True,
) -> jnp.ndarray:
    """Per-device body (call under shard_map). Returns [B, Tc, Hkv, G, D].

    Chunks are laid out in ring order: device i holds sequence positions
    ``[i*Tc, (i+1)*Tc)``.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, Tc, Hkv, G, D = q.shape

    local_mask = (
        jnp.tril(jnp.ones((Tc, Tc), bool)) if causal else None
    )

    acc = jnp.zeros((B, Hkv, G, Tc, D), jnp.float32)
    row_max = jnp.full((B, Hkv, G, Tc), NEG_INF, jnp.float32)
    denom = jnp.zeros((B, Hkv, G, Tc), jnp.float32)

    # rotate kv around the ring: after s steps we hold the chunk of device
    # (idx - s) mod n
    perm = [(j, (j + 1) % n) for j in range(n)]

    def accumulate(s, k_cur, v_cur, acc, row_max, denom):
        src = (idx - s) % n  # whose chunk we hold this step

        scores = _chunk_scores(q, k_cur)  # [B,Hkv,G,Tq,Tkv]
        if causal:
            # src > idx: kv chunk is entirely in the future -> mask all.
            # src == idx: local causal. src < idx: no mask.
            scores = jnp.where(
                src == idx,
                jnp.where(local_mask[None, None, None], scores, NEG_INF),
                jnp.where(src > idx, jnp.full_like(scores, NEG_INF), scores),
            )

        chunk_max = scores.max(axis=-1)  # [B,Hkv,G,Tq]
        new_max = jnp.maximum(row_max, chunk_max)
        # guard fully-masked rows: exp(NEG_INF - NEG_INF) would be 1
        safe_max = jnp.where(new_max <= NEG_INF, 0.0, new_max)
        correction = jnp.exp(jnp.maximum(row_max - safe_max, NEG_INF))
        correction = jnp.where(row_max <= NEG_INF, 0.0, correction)
        p = jnp.exp(scores - safe_max[..., None])
        p = jnp.where(scores <= NEG_INF, 0.0, p)

        acc = acc * correction[..., None] + jnp.einsum("bkgts,bskh->bkgth", p, v_cur)
        denom = denom * correction + p.sum(axis=-1)
        return acc, new_max, denom

    def step(carry, s):
        k_cur, v_cur, acc, row_max, denom = carry
        acc, row_max, denom = accumulate(s, k_cur, v_cur, acc, row_max, denom)
        # scan over static length: reverse-differentiable (the training path
        # shards sequence too), unlike fori_loop/while_loop
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, acc, row_max, denom), None

    k_f, v_f = k.astype(jnp.float32), v.astype(jnp.float32)
    if n > 1:
        # the last chunk is accumulated OUTSIDE the scan: n-1 rotations move
        # the data n-1 hops, and no dead final ppermute rides the critical path
        (k_f, v_f, acc, row_max, denom), _ = jax.lax.scan(
            step, (k_f, v_f, acc, row_max, denom), jnp.arange(n - 1)
        )
    acc, row_max, denom = accumulate(n - 1, k_f, v_f, acc, row_max, denom)
    out = acc / jnp.where(denom == 0.0, 1.0, denom)[..., None]
    return jnp.transpose(out, (0, 3, 1, 2, 4))  # [B, Tc, Hkv, G, D]


def ring_self_attention(
    q: jnp.ndarray,  # [B, T, Hq, D] — sequence-sharded over axis_name
    k: jnp.ndarray,  # [B, T, Hkv, D]
    v: jnp.ndarray,  # [B, T, Hkv, D]
    mesh,
    axis_name: str = "sp",
    causal: bool = True,
) -> jnp.ndarray:
    """Causal GQA self-attention with the sequence dim sharded over
    ``axis_name``. Drop-in for a dense softmax(QK^T)V — returns [B, T, Hq, D]
    with the same sharding as q.

    All other mesh axes stay automatic (XLA keeps whatever batch/head
    shardings the surrounding program chose).
    """
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv

    spec = P(None, axis_name, None, None)

    def body(qc, kc, vc):
        out = ring_attention_kernel(
            qc.astype(jnp.float32).reshape(*qc.shape[:2], Hkv, G, D),
            kc.astype(jnp.float32), vc.astype(jnp.float32),
            axis_name, causal=causal,
        )
        return out.reshape(*qc.shape[:2], Hq, D).astype(q.dtype)

    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
        axis_names={axis_name},
    )
    return mapped(q, k, v)
