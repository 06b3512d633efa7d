"""The forward pass of a model whose layers differ in kind.

``ModelConfig.layer_plan`` gives every layer an attention kind ("full": the
whole context, or "window": a sliding window with its own KV head count,
rotary base and a sink) and an FFN kind ("dense", or "moe": routed experts,
of which this process may hold a share). ``models.llama``'s entry points
(``forward``, ``forward_batched``, ``init_cache``, ``init_batch_cache``,
``rope_tables``) hand over to this module when the configuration has a plan;
a uniform model never comes here.

Parameters: ``params["layers"]`` holds one stack a kind, ``"full_dense"``,
``"window_moe"``, ... (``cfg.plan_kinds``), each laid out as a uniform
model's ``layers`` (``wqkv`` or ``wq wk wv``, ``wo``, ``rms_att``,
``rms_ffn``; ``w13``/``w2`` or ``w1 w3 w2``; ``moe_router``, ``moe_bias``,
``moe_upgate`` or ``moe_up moe_gate``, ``moe_down``) plus ``sink`` [n, heads]
on window kinds and, where experts are always on (``cfg.shared_dim``),
``shared_upgate`` / ``shared_down``. A parallel block (``cfg.block``) has one
norm a layer, ``rms_att`` (the name stays whatever ``cfg.norm`` is), and an
attention kind outside ``cfg.rope_attention`` does not rotate (it has no
tables). The forward walks ``cfg.plan_runs``: a ``lax.scan`` over
each run of like layers, indexing the kind's stack (quantized planes stay
stacked and the kernels' scalar prefetch picks the layer, as in
``llama.forward``).

The cache is a small tree by attention kind: ``k``/``v`` are the full
layers' ``[Lf, (B,) S, kv, hd]`` / ``[.., v_hd]`` stacks, and ``wk``/``wv``
the window layers' RINGS ``[Lw, (B,) R, kv_w, hd]``: position ``p`` lives in
slot ``p % R`` (``cfg.ring_slots``), so a window layer's cache does not grow
with the context. A ring is the same in a solo cache, a staging cache and a
pool's slab, whatever the slab's context, so the engine's tree-mapped copies
(insert, migrate, grow) move a ring whole.

A layer's attention reads and scores its cache only as far as the step's
queries reach (``_attend``): ``forward`` and ``forward_batched`` work out
one scalar a step, the ``reach`` (``pos + T``, or the longest LIVE row's
``pos + 1``), and every layer takes the shortest prefix of its slots, off a
ladder of static lengths, that covers it. A ring that has wrapped, and a
cache no longer than the ladder's least rung, are read whole.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from dllama_tpu.models import llama
from dllama_tpu.models.config import ModelConfig
from dllama_tpu.models.moe import (moe_ffn, moe_ffn_counted, pick_counts,
                                   route_topk)
from dllama_tpu.ops import attention
from dllama_tpu.ops.attention import gqa_attention
from dllama_tpu.ops.norms import NORMS
from dllama_tpu.ops.rope import apply_rope, rope_table

#: cache leaves and rope tables of each attention kind
CACHE_KEYS = {"full": ("k", "v"), "window": ("wk", "wv")}
ROPE_KEYS = {"full": ("cos", "sin"), "window": ("wcos", "wsin")}


def kind_name(kind: tuple) -> str:
    return f"{kind[0]}_{kind[1]}"


def _kv_heads(cfg: ModelConfig, att: str) -> int:
    return cfg.n_kv_heads_window if att == "window" else cfg.n_kv_heads


def _cache_shapes(cfg: ModelConfig, lead: tuple, seq_len: int) -> dict:
    out = {}
    for att, (kk, vk) in CACHE_KEYS.items():
        n = cfg.plan_count(att)
        if not n:
            continue
        slots = cfg.ring_slots if att == "window" else seq_len
        head = (n, *lead, slots, _kv_heads(cfg, att))
        out[kk] = (*head, cfg.head_size)
        out[vk] = (*head, cfg.v_size)
    return out


def init_cache(cfg: ModelConfig, cache_dtype=jnp.float32) -> dict:
    return {k: jnp.zeros(s, cache_dtype)
            for k, s in _cache_shapes(cfg, (), cfg.seq_len).items()}


def init_batch_cache(cfg: ModelConfig, batch: int, cache_dtype=jnp.float32,
                     seq_len: int = None) -> dict:
    S = cfg.seq_len if seq_len is None else seq_len
    return {k: jnp.zeros(s, cache_dtype)
            for k, s in _cache_shapes(cfg, (batch,), S).items()}


def kv_resident_bytes(cache: dict) -> dict:
    """Bytes of a cache tree by attention kind: {"full", "window"}."""
    return {att: sum(cache[k].nbytes for k in keys if k in cache)
            for att, keys in CACHE_KEYS.items()}


def rope_tables(cfg: ModelConfig) -> dict:
    """One pair of tables an attention kind, over the rotating dimensions."""
    rd = cfg.rope_dim or cfg.head_size
    out = {}
    for att, (ck, sk) in ROPE_KEYS.items():
        if cfg.plan_count(att) and att in cfg.rope_attention:
            theta = cfg.rope_theta_window if att == "window" else cfg.rope_theta
            cos, sin = rope_table(cfg.seq_len, rd, theta)
            out[ck], out[sk] = jnp.asarray(cos), jnp.asarray(sin)
    return out


def _rope(cfg: ModelConfig, x, cos, sin):
    """Rotate the first ``rope_dim`` dimensions of every head; the rest
    pass. ``cos`` None: an attention kind that does not rotate."""
    if cos is None:
        return x
    rd = cfg.rope_dim or cfg.head_size
    if rd == x.shape[-1]:
        return apply_rope(x, cos, sin, cfg.rope_style)
    return jnp.concatenate(
        [apply_rope(x[..., :rd], cos, sin, cfg.rope_style), x[..., rd:]],
        axis=-1)


def _attend(cfg: ModelConfig, att: str, lp: dict, cidx, reach,
            rows: bool = False, least: int = None):
    """The attention of this kind over layer ``cidx`` of its cache stacks,
    under its own scope: ``attend(q, k_cache, v_cache, pos)`` for one
    sequence (``q`` [T, heads, hd] from ``pos``) or, with ``rows``, for B
    sequences of one token each at ``pos[b]``.

    It reads and scores a PREFIX of the layer's slots that covers ``reach``,
    the step's one scalar: how many leading slots can hold a position that
    some query of the step sees (``min(reach, S)`` is the extent; a ring
    that has wrapped is read whole). Shapes are static, so the prefix is a
    rung of ``attention.prefix_rungs`` (``least``, doubled, up to the S
    slots) and a ``lax.switch`` takes the first that covers the reach; each
    branch slices ``(cidx, all rows, :rung)`` out of the stacked cache and
    runs the one-pass ``gqa_attention`` on it. A cache of no more than
    ``least`` slots has one rung: no switch, the program it always was
    (``reach`` may then be None: ``_laddered``)."""
    window = cfg.window if att == "window" else 0
    sink = lp.get("sink") if cfg.window_sink else None

    def attend(q, k_cache, v_cache, pos):
        S = k_cache.shape[-3]

        def one(q, k_slab, v_slab, pos):
            with jax.named_scope(f"attention_{att}"):
                return gqa_attention(q, k_slab, v_slab, pos, window=window,
                                     sink=sink, ring=S)

        def over(rung):
            def branch(q, k_cache, v_cache, pos):
                slabs = llama._layer_slabs(k_cache, v_cache, cidx, rung)
                if not rows:
                    return one(q, *slabs, pos)
                return jax.vmap(
                    lambda qb, ks, vs, p: one(qb[None], ks, vs, p)[0])(
                    q, *slabs, pos)
            return branch

        rungs = attention.prefix_rungs(
            S, attention.LEAST_RUNG if least is None else least)
        if len(rungs) == 1:
            return over(None)(q, k_cache, v_cache, pos)
        # a branch takes the stacked caches as they are carried and slices
        # inside: pinned, so that no branch turns a whole cache around
        k_cache, v_cache = llama._plain_layout(k_cache, v_cache)
        return jax.lax.switch(attention.covering_rung(reach, rungs),
                              [over(r) for r in rungs],
                              q, k_cache, v_cache, pos)

    return attend


def _laddered(cache: dict) -> bool:
    """Whether some layer's cache is longer than the ladder's least rung. A
    step works out its reach only then: a program whose every cache has one
    rung traces to exactly what it did before there was a ladder."""
    return any(a.shape[-3] > attention.LEAST_RUNG for a in cache.values())


def ring_slots_scored(cfg: ModelConfig, reach):
    """The slots of a ring that a step of reach ``reach`` scores, a row a
    window layer: the host's mirror (numpy) of the rung ``_attend`` takes."""
    rungs = attention.prefix_rungs(cfg.ring_slots, attention.LEAST_RUNG)
    return np.asarray(rungs)[attention.covering_rung(np.asarray(reach), rungs)]


def _solo_core(cfg: ModelConfig, att: str, lp: dict, rope: dict, pos, cidx,
               reach):
    """The core (``llama._attention``) of one sequence's T tokens at
    ``pos..pos+T``: layer ``cidx`` of this kind's cache stacks is written
    and read as far as ``reach`` = ``pos + T``."""
    ck, sk = ROPE_KEYS[att]

    def core(q, k, v, k_cache, v_cache, layer):
        T = q.shape[0]
        cos = sin = None
        if ck in rope:
            cos = jax.lax.dynamic_slice_in_dim(rope[ck], pos, T)[:, None, :]
            sin = jax.lax.dynamic_slice_in_dim(rope[sk], pos, T)[:, None, :]
        q, k = _rope(cfg, q, cos, sin), _rope(cfg, k, cos, sin)
        if att == "window":
            if T > cfg.max_prefill_piece:
                raise ValueError(
                    f"a forward over {T} tokens does not fit the window "
                    f"layers' ring ({cfg.ring_slots} slots, window "
                    f"{cfg.window}): at most {cfg.max_prefill_piece} tokens "
                    f"a piece")
            slots = jnp.mod(pos + jnp.arange(T, dtype=jnp.int32),
                            k_cache.shape[1])
            with jax.named_scope("kv_ring_write"):
                k_cache = k_cache.at[cidx, slots].set(k.astype(k_cache.dtype))
                v_cache = v_cache.at[cidx, slots].set(v.astype(v_cache.dtype))
        else:
            k_cache, v_cache = llama._write_kv_seq(k_cache, v_cache, k, v,
                                                   cidx, pos)
        out = _attend(cfg, att, lp, cidx, reach)(q, k_cache, v_cache, pos)
        return out, k_cache, v_cache

    return core


def _rows_core(cfg: ModelConfig, att: str, lp: dict, rope: dict, pos, cidx,
               reach):
    """The core of B independent sequences, one token each at ``pos[b]``;
    the caches carry the row axis after the layer axis. ``reach``: the
    longest counted row's ``pos + 1`` (``forward_batched``)."""
    ck, sk = ROPE_KEYS[att]

    def core(q, k, v, k_cache, v_cache, layer):
        cos = sin = None
        if ck in rope:
            cos = rope[ck][pos][:, None, :]
            sin = rope[sk][pos][:, None, :]
        q, k = _rope(cfg, q, cos, sin), _rope(cfg, k, cos, sin)
        if att == "window":
            rows = jnp.arange(q.shape[0], dtype=jnp.int32)
            slots = jnp.mod(pos, k_cache.shape[2])
            with jax.named_scope("kv_ring_write"):
                k_cache = k_cache.at[cidx, rows, slots].set(
                    k.astype(k_cache.dtype))
                v_cache = v_cache.at[cidx, rows, slots].set(
                    v.astype(v_cache.dtype))
        else:
            k_cache, v_cache = llama._write_kv_rows(
                k_cache, v_cache, k[:, None], v[:, None], cidx, pos)
        out = _attend(cfg, att, lp, cidx, reach, rows=True)(
            q, k_cache, v_cache, pos)
        return out, k_cache, v_cache

    return core


def _ffn(cfg: ModelConfig, ffn: str, lp: dict, x, norm_w, layer, live):
    """The FFN of the residual ``x`` behind the norm ``norm_w`` -> (its
    output, picks or None)."""
    if ffn == "dense":
        return llama._dense_ffn(cfg, lp, x, norm_w, layer=layer), None
    xb = NORMS[cfg.norm](x, norm_w, cfg.norm_eps)
    if live is None:
        return moe_ffn(cfg, lp, xb, layer), None
    # the same product as inside moe_ffn: the compiler keeps one
    topi, _ = route_topk(cfg, lp["moe_router"], xb, lp.get("moe_bias"))
    out, reads = moe_ffn_counted(cfg, lp, xb, layer, live=live)
    picks = jnp.append(pick_counts(cfg, topi, live),
                       jnp.asarray(reads, jnp.int32))
    return out, picks


def _run_step(cfg: ModelConfig, stack: dict, rope: dict, pos, core_of, live,
              reach, kind: tuple, p0: int, c0: int):
    """The scan body of one run of layers of ``kind``: layer ``p0 + i`` of
    the kind's parameter stack, ``c0 + i`` of its attention kind's caches;
    ``llama._attention`` around this kind's core, then ``_ffn``: on the
    residual after attention behind ``rms_ffn`` (a sequential block), or on
    the layer's input behind the attention's own norm, joined ``x + att +
    ffn`` (``cfg.block`` "parallel": one norm a layer, no ``rms_ffn``)."""
    att = kind[0]
    kk, vk = CACHE_KEYS[att]
    widths = (cfg.n_heads * cfg.head_size, _kv_heads(cfg, att) * cfg.head_size)

    def step(carry, i):
        x, cache, picks = carry
        idx = jnp.int32(p0) + i
        lp = llama._layer_params(stack, idx)
        core = core_of(cfg, att, lp, rope, pos, jnp.int32(c0) + i, reach)
        att_out, k_cache, v_cache = llama._attention(
            cfg, lp, x, core, cache[kk], cache[vk], idx, widths=widths)
        cache = dict(cache, **{kk: k_cache, vk: v_cache})
        if cfg.block == "parallel":
            out, got = _ffn(cfg, kind[1], lp, x, lp["rms_att"], idx, live)
            x = (x + att_out + out).astype(x.dtype)
        else:
            x = x + att_out
            out, got = _ffn(cfg, kind[1], lp, x, lp["rms_ffn"], idx, live)
            x = x + out
        if got is not None:
            picks = picks + got
        return (x, cache, picks), None

    return step


def _run_layers(cfg: ModelConfig, params: dict, rope: dict, x, cache: dict,
                pos, core_of, reach, live=None):
    """Every run of like layers in turn; ``reach`` is the step's one scalar
    for every layer's attention (``_attend``).
    -> (x, cache, picks [4] or None)"""
    carry = (x, cache, None if live is None else jnp.zeros((4,), jnp.int32))
    for kind, p0, c0, count in cfg.plan_runs:
        step = _run_step(cfg, params["layers"][kind_name(kind)], rope, pos,
                         core_of, live, reach, kind, p0, c0)
        if count == 1:
            carry, _ = step(carry, jnp.int32(0))
        else:
            carry, _ = jax.lax.scan(step, carry,
                                    jnp.arange(count, dtype=jnp.int32))
    return carry


def forward(cfg: ModelConfig, params: dict, rope: dict, tokens, cache: dict,
            pos, last_pos=None) -> tuple:
    """T tokens of one sequence from ``pos`` -> (logits [T, vocab] f32, or
    [1, vocab] at row ``last_pos``; the new cache tree)."""
    x = llama.embed(cfg, params, tokens)
    reach = pos + x.shape[0] if _laddered(cache) else None
    x, cache, _ = _run_layers(cfg, params, rope, x, cache, pos, _solo_core,
                              reach)
    return llama._head(cfg, params, x, last_pos=last_pos), cache


def forward_batched(cfg: ModelConfig, params: dict, rope: dict, tokens,
                    cache: dict, pos, live=None) -> tuple:
    """One decode step for B independent sequences -> (logits [B, vocab],
    cache), and with ``live`` [B] (bool: the rows that are decoding) a third
    value, int32 [4], summed over the expert layers: the live rows' picks
    that fell on held experts, all their picks, the distinct held experts
    they picked (``moe.pick_counts``), and the expert plane sets the step
    READ (``moe.moe_ffn_counted``). Rows that are not live activate no
    expert where the expert layer runs only the picked experts."""
    x = llama.embed(cfg, params, tokens)
    reach = None
    if _laddered(cache):
        # a row that is not live may hold a stale, longer position: not counted
        reach = (pos + 1 if live is None
                 else jnp.where(live, pos + 1, 0)).max()
    x, cache, picks = _run_layers(cfg, params, rope, x, cache, pos,
                                  _rows_core, reach, live)
    logits = llama._head(cfg, params, x)
    return (logits, cache) if live is None else (logits, cache, picks)
