"""The plain reference of the Command A+ layer plan, and the comparison behind
``correct``.

The equations of ``CohereLabs/command-a-plus-05-2026``'s ``config.json``
(``model_type: cohere2_moe``) as ISSUE 31 writes them down, in
straightforward ``jax.numpy`` and float32 at ``highest`` matmul precision: no
kernels, no cache, no batching of requests, one full causal forward a
sequence. It imports nothing of the program and nothing of another family's
reference, and is handed nothing the program made. No biases anywhere:

- norm: LayerNorm without bias, ``w * (x - mean x) / sqrt(var x + eps)``
  (``layer_norm_eps``);
- block (``use_parallel_block``): ONE norm a layer, ``h = LN(x)``, then
  ``x <- x + Attn(h) + FFN(h)``: the FFN does not see the attention's output;
- attention, both kinds: 128 query heads on 8 KV heads of 128, scores
  ``q.k / sqrt(head_dim)``, causal, softmax in float32, no q/k norm. Window
  layers (``layer_types[l] == "sliding_attention"``) rotate all 128
  dimensions of q and k as interleaved pairs ``(2i, 2i + 1)``
  (``rope_gptj``), theta ``rope_theta``, and query ``i`` sees keys
  ``i - sliding_window < j <= i``. Full layers rotate nothing and see the
  whole past;
- FFN: ``s = sigmoid(h W_r)`` over all published experts, the top
  ``num_experts_per_tok`` by ``s`` are chosen, their weights are ``s_e / sum
  of the chosen s`` (``norm_topk_prob``), ``routed = sum_e w_e down_e(up_e h
  * silu(gate_e h))``; ``num_shared_experts`` experts of the same form and
  width are always on, ``shared = (1 / 4) sum_s FFN_s(h)`` (``average``);
  ``FFN(h) = routed + shared``;
- head: final LayerNorm, ``logits = logit_scale * LN(x) E^T`` with ``E`` the
  embedding table (``tie_word_embeddings``).

The chip's share, taken the same way as the program takes it: only the HELD
experts' terms of the routed sum are computed (``held = (first, count)``;
what the absent experts would add is left out, and that partial result goes
on), the shared experts whole, the logits over the vocabulary's slice.

Departures and assumptions: random weights; the fused planes' column orders
(``wqkv = q|k|v``; ``moe_upgate = up|gate`` an expert; ``shared_upgate`` =
every shared expert's up, then every one's gate, and ``shared_down``'s rows
in the same order: the four are one gated FFN of their summed width) follow
``weights.py``; ``average`` is read as the mean over the shared experts,
added to the routed sum; ``intermediate_size`` is one expert's width, shared
or routed; the window's edge is ``i - j < sliding_window``; full layers are
NoPE; the vision tower is not part of this forward.

Q40 planes, as ``dllama_tpu/ops/qmatmul.py`` lays them out: ``w`` is
``uint8 [K/2, O]``, byte ``32 s + j`` holding input row ``64 s + j`` in its
low nibble (scale ``s[s]``) and row ``64 s + 32 + j`` in its high nibble
(scale ``s2[s]``); a nibble stores ``q + 8`` and the weight is ``(q - 8) *
scale``.

``lower="float8_e4m3fn"`` is the control: the same forward with every
matmul's activations and the keys and values rounded to float8, the nearest
precision below the bfloat16 the configuration states; ``lower="bfloat16"``
is the witness.

``without`` takes one mechanism OUT of the reference (tests and the builder's
readings: the comparison must then fail): ``"parallel"`` (a sequential block:
the FFN sees the residual after attention), ``"layernorm"`` (RMSNorm: no
centring), ``"nope"`` (full layers rotate too), ``"interleaved"`` (half-split
pairs), ``"average"`` (the shared sum without its 1 / 4), ``"sigmoid"``
(softmax scores), ``"tied"`` (the head reads planes of its own, ``wcls``,
whatever the table holds), ``"window"`` (window layers see the whole past),
``"expert"`` (the first held expert's term is dropped).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import shapes

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
CONTROL = "float8_e4m3fn"
WITNESS = "bfloat16"


def dequant_q40(plane: dict, k_logical: int) -> jnp.ndarray:
    """{"w","s","s2"} -> dense f32 [..., K, O] at the logical K."""
    w, s, s2 = plane["w"], plane["s"], plane["s2"]
    half, out = w.shape[-2:]
    lead = w.shape[:-2]
    wi = w.astype(jnp.int32)
    lo = ((wi & 0xF) - 8).astype(F32).reshape(*lead, half // 32, 32, out)
    hi = ((wi >> 4) - 8).astype(F32).reshape(*lead, half // 32, 32, out)
    lo = lo * s[..., :, None, :]
    hi = hi * s2[..., :, None, :]
    dense = jnp.concatenate([lo, hi], axis=-2).reshape(*lead, half * 2, out)
    return dense[..., :k_logical, :]


def _round(x, lower):
    """``lower``: None, or the name of the type activations are rounded to."""
    if not lower:
        return x
    return x.astype(jnp.dtype(lower)).astype(F32)


def _mm(x, w, lower):
    return jnp.matmul(_round(x, lower), w, precision=HI)


def _norm(x, weight, eps, without=None):
    if without != "layernorm":
        x = x - jnp.mean(x, axis=-1, keepdims=True)
    return weight * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope_tables(seq_len: int, head_dim: int, theta: float):
    j = np.arange(0, head_dim, 2, dtype=np.float64)
    freqs = 1.0 / np.power(float(theta), j / head_dim)
    ang = np.arange(seq_len, dtype=np.float64)[:, None] * freqs[None, :]
    return (jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32))


def _rope(x, cos, sin, interleaved: bool):
    """x [N, T, heads, hd]; cos, sin [T, hd/2]: pairs ``(2i, 2i + 1)``, or
    ``(i, i + hd/2)``."""
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    if interleaved:
        x0, x1 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x0 * c - x1 * s, x0 * s + x1 * c],
                         axis=-1).reshape(x.shape)
    half = x.shape[-1] // 2
    x0, x1 = x[..., :half], x[..., half:]
    return jnp.concatenate([x0 * c - x1 * s, x0 * s + x1 * c], axis=-1)


def sizes(model: dict) -> tuple:
    """The static sizes ``_layer`` needs, hashable."""
    d = shapes.dims(model)
    return tuple(sorted(dict(
        d, eps=float(model["layer_norm_eps"]),
        first=int(model.get("share", {}).get("expert_first", 0)),
    ).items()))


def attention(h, lp, cos, sin, m: dict, att: str, lower, without):
    """h [N, T, D] (already normed) -> the attention's output, [N, T, D]."""
    n, t, _ = h.shape
    heads, kv, hd = m["heads"], m["kv"], m["hd"]
    qkv = _mm(h, dequant_q40(lp["wqkv"], m["D"]), lower)
    q = qkv[..., :heads * hd].reshape(n, t, heads, hd)
    k = qkv[..., heads * hd:(heads + kv) * hd].reshape(n, t, kv, hd)
    v = qkv[..., (heads + kv) * hd:].reshape(n, t, kv, hd)
    if att == "window" or without == "nope":
        q = _rope(q, cos, sin, without != "interleaved")
        k = _rope(k, cos, sin, without != "interleaved")
    k, v = _round(k, lower), _round(v, lower)
    qg = q.reshape(n, t, kv, heads // kv, hd)
    scores = jnp.einsum("ntkgh,nskh->nkgts", qg, k,
                        precision=HI) / np.sqrt(float(hd))
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = j <= i
    if att == "window" and without != "window":
        seen = seen & (i - j < m["window"])
    scores = jnp.where(seen[None, None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("nkgts,nskh->ntkgh", p, v, precision=HI)
    return _mm(out.reshape(n, t, heads * hd),
               dequant_q40(lp["wo"], heads * hd), lower)


def route(h, lp, m: dict, without):
    """-> combine weights [..., E] over ALL experts, zero off the chosen."""
    logits = jnp.matmul(h, lp["moe_router"], precision=HI)
    s = (jax.nn.softmax(logits, axis=-1) if without == "sigmoid"
         else jax.nn.sigmoid(logits))
    _, topi = jax.lax.top_k(s, m["k"])
    chosen = s * jax.nn.one_hot(topi, m["E"], dtype=F32).sum(axis=-2)
    return chosen / chosen.sum(axis=-1, keepdims=True)


def _gated(h, up, gate, down, lower):
    return _mm(_mm(h, up, lower) * jax.nn.silu(_mm(h, gate, lower)), down,
               lower)


def experts(h, lp, m: dict, held: tuple, lower, without):
    """The part of the routed sum that the experts ``[first, first + count)``
    give; ``lp``'s expert planes hold exactly those, in order."""
    first, count = held
    combine = route(h, lp, m, without)

    def expert(acc, e):
        ug = dequant_q40(jax.tree.map(lambda a: a[e], lp["moe_upgate"]), m["D"])
        down = dequant_q40(jax.tree.map(lambda a: a[e], lp["moe_down"]),
                           m["He"])
        y = _gated(h, ug[:, :m["He"]], ug[:, m["He"]:], down, lower)
        return acc + y * combine[..., first + e][..., None], None

    start = 1 if without == "expert" else 0
    acc, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(start, count))
    return acc


def shared(h, lp, m: dict, lower, without):
    """The always-on experts, one after another, averaged."""
    ug = dequant_q40(lp["shared_upgate"], m["D"])
    down = dequant_q40(lp["shared_down"], m["Hs"])
    he, hs = m["He"], m["Hs"]
    total = sum(_gated(h, ug[:, s * he:(s + 1) * he],
                       ug[:, hs + s * he:hs + (s + 1) * he],
                       down[s * he:(s + 1) * he], lower)
                for s in range(m["Ns"]))
    return total if without == "average" else total / m["Ns"]


@functools.partial(jax.jit, static_argnames=("m", "kind", "lower", "without"))
def _layer(x, stack, idx, cos, sin, m: tuple, kind: tuple, lower=None,
           without=None):
    """Layer ``idx`` of the kind's stack over x [N, T, D]."""
    m = dict(m)
    lp = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, idx, 0, keepdims=False),
        stack)
    h = _norm(x, lp["rms_att"], m["eps"], without)
    att = attention(h, lp, cos, sin, m, kind[0], lower, without)
    if without == "parallel":  # a sequential block, behind the same norm
        h = _norm(x + att, lp["rms_att"], m["eps"], without)
    ffn = (experts(h, lp, m, (m["first"], m["Eh"]), lower, without)
           + shared(h, lp, m, lower, without))
    return x + att + ffn


@functools.partial(jax.jit, static_argnames=("eps", "lower", "without"))
def _head(x, rows, norm_w, table_t, eps: float, lower=None, without=None):
    """Final norm and the tied head at rows [R] of x [T, D] -> [R, V];
    ``table_t`` is the embedding table transposed, [D, V]."""
    return _mm(_norm(x[rows], norm_w, eps, without), table_t, lower)


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
    cos, sin = rope_tables(t_pad, d["hd"], float(model["rope_theta"]))
    block = max(1, int(block_budget // (4.0 * d["heads"] * t_pad * t_pad)))
    r_pad = -(-max(len(r) for r in rows) // 32) * 32  # one compile of _head
    scale = float(model.get("logit_scale", 1.0))
    # the tie: the head is the lookup table itself (``without="tied"``: a
    # head of its own, the planes ``wcls``)
    table_t = (dequant_q40(planes["wcls"], d["D"]) if without == "tied"
               else planes["embedding"].T)
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
                       jnp.int32(i), cos, sin, m=m, kind=kind, lower=lower,
                       without=without)
        for i in range(len(chunk)):
            r = rows[b0 + i]
            sel = np.zeros(r_pad, np.int32)
            sel[:len(r)] = r
            out.append(scale * np.asarray(_head(
                x[i], jnp.asarray(sel), planes["rms_final"], table_t,
                eps=d["eps"], lower=lower, without=without))[:len(r)])
    return out


def compare(planes: dict, model: dict, samples: list,
            stand_ins: dict | None = None, without=None) -> dict:
    """The comparison behind ``correct``: the reference runs once over
    prompt + served tokens; a served token's gap is how far its reference
    logit lies below the reference's best at that position, in standard
    deviations of that position's logits. For every ``{name: lower
    precision}`` of ``stand_ins``, ``<name>_gaps`` are those of the tokens
    the forward in that precision puts first at the same positions: the
    reference in lower precision put in the program's place."""
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
