"""The plain reference of the MiMo-V2 layer plan, and the comparison behind
``correct``.

The equations of ``XiaomiMiMo/MiMo-V2-Flash``'s ``config.json``
(``model_type: mimo_v2_flash``) as ISSUE 27 writes them down, in
straightforward ``jax.numpy`` and float32 at ``highest`` matmul precision: no
kernels, no cache, no batching of requests, one full causal forward a
sequence. It imports nothing of the program and is handed nothing the program
made. Pre-norm residual blocks, RMSNorm, no biases:

- attention, both kinds: 64 query heads, q and k heads ``head_dim`` wide, v
  heads ``v_head_dim`` wide; rotary on the first ``int(head_dim *
  partial_rotary_factor)`` dimensions of every q and k head (half-split
  pairs over those dimensions), the rest pass; ``v <- attention_value_scale
  * v``; scores ``q.k / sqrt(head_dim)``, causal. Full layers:
  ``num_key_value_heads``, ``rope_theta``. Window layers:
  ``swa_num_key_value_heads``, ``swa_rope_theta``, query ``i`` sees keys
  ``i - sliding_window < j <= i``, and one learned scalar a head (the sink)
  joins the softmax's denominator and carries no value;
- layer 0's FFN is a dense SwiGLU, ``down(silu(gate x) * up x)``; the others
  route: ``s = sigmoid(x W_r)`` over all published experts, the top
  ``num_experts_per_tok`` of ``s + b`` are chosen, their weights are ``s_e /
  sum of the chosen s``, ``y = sum_e w_e down_e(silu(gate_e x) * up_e x)``.

The chip's share, taken the same way as the program takes it: only the HELD
experts' terms of that sum are computed (``held = (first, count)``; what the
absent experts would add is left out, and that partial result goes on), and
the logits are over the vocabulary's slice.

Departures and assumptions: random weights; the fused planes' column orders
(``wqkv = q|k|v``, ``w13 = gate|up``, ``moe_upgate = up|gate``) follow
``weights.py``; the value scale is applied to v after its projection; the
window's edge is ``i - j < sliding_window``; ``attention_chunk_size`` has no
term here; the multi-token-prediction layers are not part of this forward.
The Q40 bit layout, the rounding of the stand-ins, the norm and the head are
the ``llama`` family's reference's, which knows no model: imported, not
copied.

``without`` takes one mechanism OUT of the reference (tests and the builder's
readings: the comparison must then fail): ``"window"`` (window layers see the
whole past), ``"sink"``, ``"value_scale"``, ``"rotary"`` (every dimension
rotates), ``"router_bias"`` (scores choose), ``"expert"`` (the first held
expert's term is dropped).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..llama.reference import (CONTROL, F32, HI, WITNESS,  # noqa: F401
                               _head, _mm, _rmsnorm, _round, dequant_q40,
                               rope_tables)
from . import shapes


def sizes(model: dict) -> tuple:
    """The static sizes ``_layer`` needs, hashable."""
    d = shapes.dims(model)
    return tuple(sorted(dict(
        d, eps=float(model["layernorm_epsilon"]),
        value_scale=float(model["attention_value_scale"]),
        first=int(model.get("share", {}).get("expert_first", 0)),
    ).items()))


def _rope_half(x, cos, sin, rd: int):
    """Half-split pairs over the first ``rd`` dimensions of x [N, T, h, hd];
    cos, sin [T, rd/2]."""
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    x0, x1, rest = x[..., :rd // 2], x[..., rd // 2:rd], x[..., rd:]
    return jnp.concatenate([x0 * c - x1 * s, x0 * s + x1 * c, rest], axis=-1)


def attention(h, lp, cos, sin, m: dict, att: str, lower, without):
    """h [N, T, D] (already normed) -> the attention output before ``wo``'s
    residual add, [N, T, D]."""
    n, t, _ = h.shape
    heads, hd, vd = m["heads"], m["hd"], m["vd"]
    kv = m["kv_window"] if att == "window" else m["kv_full"]
    qkv = _mm(h, dequant_q40(lp["wqkv"], m["D"]), lower)
    q = qkv[..., :heads * hd].reshape(n, t, heads, hd)
    k = qkv[..., heads * hd:(heads + kv) * hd].reshape(n, t, kv, hd)
    v = qkv[..., (heads + kv) * hd:].reshape(n, t, kv, vd)
    rd = 2 * cos.shape[-1]  # the tables' width says how many dimensions rotate
    q = _rope_half(q, cos, sin, rd)
    k = _round(_rope_half(k, cos, sin, rd), lower)
    if without != "value_scale":
        v = v * m["value_scale"]
    v = _round(v, lower)
    qg = q.reshape(n, t, kv, heads // kv, hd)
    scores = jnp.einsum("ntkgh,nskh->nkgts", qg, k,
                        precision=HI) / np.sqrt(float(hd))
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = j <= i
    if att == "window" and without != "window":
        seen = seen & (i - j < m["window"])
    scores = jnp.where(seen[None, None, None], scores, -jnp.inf)
    if att == "window" and without != "sink":
        sink = jnp.broadcast_to(
            lp["sink"].reshape(1, kv, heads // kv, 1, 1), (n, kv, heads // kv, t, 1))
        p = jax.nn.softmax(jnp.concatenate([scores, sink], axis=-1),
                           axis=-1)[..., :-1]
    else:
        p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("nkgts,nskh->ntkgh", p, v, precision=HI)
    return _mm(out.reshape(n, t, heads * vd),
               dequant_q40(lp["wo"], heads * vd), lower)


def route(h, lp, m: dict, without):
    """-> combine weights [..., E] over ALL experts, zero off the chosen."""
    s = jax.nn.sigmoid(jnp.matmul(h, lp["moe_router"], precision=HI))
    choose = s if without == "router_bias" else s + lp["moe_bias"]
    _, topi = jax.lax.top_k(choose, m["k"])
    hot = jax.nn.one_hot(topi, m["E"], dtype=F32).sum(axis=-2)
    chosen = s * hot
    return chosen / chosen.sum(axis=-1, keepdims=True)


def experts(h, lp, m: dict, held: tuple, lower, without):
    """The part of the routed sum that the experts ``[first, first + count)``
    give; ``lp``'s expert planes hold exactly those, in order."""
    first, count = held
    combine = route(h, lp, m, without)

    def expert(acc, e):
        up = jax.tree.map(lambda a: a[e], lp["moe_upgate"])
        down = jax.tree.map(lambda a: a[e], lp["moe_down"])
        ug = _mm(h, dequant_q40(up, m["D"]), lower)
        g = ug[..., :m["He"]] * jax.nn.silu(ug[..., m["He"]:])
        y = _mm(g, dequant_q40(down, m["He"]), lower)
        return acc + y * combine[..., first + e][..., None], None

    start = 1 if without == "expert" else 0
    acc, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(start, count))
    return acc


@functools.partial(jax.jit, static_argnames=("m", "kind", "lower", "without"))
def _layer(x, stack, idx, cos, sin, m: tuple, kind: tuple, lower=None,
           without=None):
    """Layer ``idx`` of the kind's stack over x [N, T, D]."""
    m = dict(m)
    lp = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, idx, 0, keepdims=False),
        stack)
    att, ffn = kind
    x = x + attention(_rmsnorm(x, lp["rms_att"], m["eps"]), lp, cos, sin, m,
                      att, lower, without)
    h = _rmsnorm(x, lp["rms_ffn"], m["eps"])
    if ffn == "dense":
        u = _mm(h, dequant_q40(lp["w13"], m["D"]), lower)
        g = jax.nn.silu(u[..., :m["Hd"]]) * u[..., m["Hd"]:]
        return x + _mm(g, dequant_q40(lp["w2"], m["Hd"]), lower)
    return x + experts(h, lp, m, (m["first"], m["Eh"]), lower, without)


def logits_at(planes: dict, model: dict, seqs: list, rows: list, lower=None,
              without=None, block_budget: float = 1.5e9) -> list:
    """The reference over each token sequence -> per sequence the logits
    [len(rows[i]), V] at the positions ``rows[i]`` (numpy f32). Sequences are
    padded to one length and run in blocks whose attention scores stay under
    ``block_budget`` bytes; the layers run one at a time, each dequantising
    its own planes (an expert at a time)."""
    m = sizes(model)
    d = dict(m)
    t_pad = -(-max(len(s) for s in seqs) // 128) * 128
    rd = d["hd"] if without == "rotary" else d["rd"]
    tables = {"full": rope_tables(t_pad, rd, float(model["rope_theta"])),
              "window": rope_tables(t_pad, rd, float(model["swa_rope_theta"]))}
    block = max(1, int(block_budget // (4.0 * d["heads"] * t_pad * t_pad)))
    r_pad = -(-max(len(r) for r in rows) // 32) * 32  # one compile of _head
    out: list = []
    for b0 in range(0, len(seqs), block):
        chunk = seqs[b0:b0 + block]
        toks = np.zeros((len(chunk), t_pad), np.int32)
        for i, s in enumerate(chunk):
            toks[i, :len(s)] = s
        x = planes["embedding"][jnp.asarray(toks)].astype(F32)
        at: dict = {}
        for kind in shapes.plan(model):
            i = at.get(kind, 0)
            at[kind] = i + 1
            x = _layer(x, planes["layers"][f"{kind[0]}_{kind[1]}"],
                       jnp.int32(i), *tables[kind[0]], m=m, kind=kind,
                       lower=lower, without=without)
        for i in range(len(chunk)):
            r = rows[b0 + i]
            sel = np.zeros(r_pad, np.int32)
            sel[:len(r)] = r
            out.append(np.asarray(_head(
                x[i], jnp.asarray(sel), planes["rms_final"], planes["wcls"],
                dim=d["D"], eps=d["eps"], lower=lower))[:len(r)])
    return out


def compare(planes: dict, model: dict, samples: list,
            stand_ins: dict | None = None, without=None) -> dict:
    """The comparison behind ``correct``, as the ``llama`` family's: the
    reference runs once over prompt + served tokens; a served token's gap is
    how far its reference logit lies below the reference's best at that
    position, in standard deviations of that position's logits. For every
    ``{name: lower precision}`` of ``stand_ins``, ``<name>_gaps`` are those
    of the tokens the forward in that precision puts first."""
    seqs, rows = [], []
    for s in samples:
        p, g = list(s["prompt"]), list(s["served"])
        seqs.append(p + g)
        rows.append([len(p) - 1 + j for j in range(len(g))])
    ref = logits_at(planes, model, seqs, rows, without=without)

    def gaps_of(firsts):
        out: list = []
        for lg, first in zip(ref, firsts):
            chosen = lg[np.arange(len(first)), first]
            out.extend(((lg.max(axis=1) - chosen) / lg.std(axis=1)).tolist())
        return out

    res = {"gaps": gaps_of([np.asarray(s["served"], np.int64) for s in samples]),
           "finite": bool(all(np.isfinite(lg).all() for lg in ref))}
    for name, mode in (stand_ins or {}).items():
        low = logits_at(planes, model, seqs, rows, lower=mode, without=without)
        res[name + "_gaps"] = gaps_of([ll.argmax(axis=1) for ll in low])
    return res
