"""The plain reference of both configurations, and the comparison for ``correct``.

A decoder-only transformer as the two models' papers and public code give it
(Mistral-7B: arXiv:2310.06825; Mixtral-8x7B: arXiv:2401.04088), in
straightforward ``jax.numpy`` and float32 at ``highest`` matmul precision: no
kernels, no cache, no batching of requests. It imports nothing of the program
and is handed nothing the program made: the weight planes come from
``weights.py`` (the benchmark's own init program) as plain arrays, and what
their bits mean is restated here.

Q40 planes, as ``dllama_tpu/ops/qmatmul.py`` lays them out: ``w`` is
``uint8 [K/2, O]``, byte ``32 s + j`` holding input row ``64 s + j`` in its low
nibble (scale ``s[s]``) and row ``64 s + 32 + j`` in its high nibble (scale
``s2[s]``); a nibble stores ``q + 8`` and the weight is ``(q - 8) * scale``.

Departures from the published models, all forced by the configuration files:
the weights are random; the fused planes ``wqkv = wq|wk|wv``,
``w13 = w1|w3`` (dense: ``silu(first half) * second half``) and
``moe_upgate = up|gate`` (experts: ``first half * silu(second half)``) follow
the program's layouts; ``arch: llama`` rotates interleaved pairs and
``arch: mixtral`` the two halves of a head, as the program's converter lays
real checkpoints out.

The model is run layer by layer (one layer's planes dequantized at a time), on
blocks of sequences, so that it fits beside the resident planes.

``lower="float8_e4m3fn"`` is the control: the same forward with every matmul's
activations and the keys and values rounded to float8, the nearest precision
below the bfloat16 the configurations state. ``lower="bfloat16"`` is the
witness: the reference in the configurations' own precision, which shows what
part of a gap is bfloat16 itself and what part is the program's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


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


def _rmsnorm(x, weight, eps):
    inv = 1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return weight * (x * inv)


def _rope(x, cos, sin, style: str):
    """x [N, T, heads, hd]; cos, sin [T, hd/2]."""
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    if style == "interleaved":
        x0, x1 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x0 * c - x1 * s, x0 * s + x1 * c],
                         axis=-1).reshape(x.shape)
    half = x.shape[-1] // 2
    x0, x1 = x[..., :half], x[..., half:]
    return jnp.concatenate([x0 * c - x1 * s, x0 * s + x1 * c], axis=-1)


def rope_tables(seq_len: int, head_dim: int, theta: float):
    j = np.arange(0, head_dim, 2, dtype=np.float64)
    freqs = 1.0 / np.power(float(theta), j / head_dim)
    ang = np.arange(seq_len, dtype=np.float64)[:, None] * freqs[None, :]
    return (jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32))


@functools.partial(jax.jit, static_argnames=("m", "lower"))
def _layer(x, layers, idx, cos, sin, m: tuple, lower):
    """Decoder layer ``idx`` over x [N, T, D]; ``layers`` holds every layer's
    planes stacked on their first axis. ``m``: the model's sizes."""
    (dim, hidden, n_heads, n_kv, hd, n_experts, top_k, eps, style) = m
    lp = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, idx, 0, keepdims=False),
        layers)
    n, t, _ = x.shape
    kv_dim = n_kv * hd
    h = _rmsnorm(x, lp["rms_att"], eps)
    qkv = _mm(h, dequant_q40(lp["wqkv"], dim), lower)
    q = qkv[..., :dim].reshape(n, t, n_heads, hd)
    k = qkv[..., dim:dim + kv_dim].reshape(n, t, n_kv, hd)
    v = qkv[..., dim + kv_dim:].reshape(n, t, n_kv, hd)
    q = _rope(q, cos, sin, style)
    k = _round(_rope(k, cos, sin, style), lower)
    v = _round(v, lower)
    group = n_heads // n_kv
    qg = q.reshape(n, t, n_kv, group, hd)
    scores = jnp.einsum("ntkgh,nskh->nkgts", qg, k,
                        precision=HI) / np.sqrt(float(hd))
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    scores = jnp.where(causal[None, None, None], scores, -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("nkgts,nskh->ntkgh", att, v, precision=HI)
    x = x + _mm(out.reshape(n, t, dim), dequant_q40(lp["wo"], dim), lower)

    h = _rmsnorm(x, lp["rms_ffn"], eps)
    if n_experts == 0:
        u = _mm(h, dequant_q40(lp["w13"], dim), lower)
        g = jax.nn.silu(u[..., :hidden]) * u[..., hidden:]
        return x + _mm(g, dequant_q40(lp["w2"], hidden), lower)

    # sparse experts: softmax over all experts, the top k renormalised
    logits = jnp.matmul(h, lp["moe_router"], precision=HI)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, top_k)
    topv = topv / topv.sum(axis=-1, keepdims=True)
    combine = jnp.einsum("ntke,ntk->nte",
                         jax.nn.one_hot(topi, n_experts, dtype=F32), topv)

    def expert(acc, e):
        up = jax.tree.map(lambda a: a[e], lp["moe_upgate"])
        down = jax.tree.map(lambda a: a[e], lp["moe_down"])
        ug = _mm(h, dequant_q40(up, dim), lower)
        g = ug[..., :hidden] * jax.nn.silu(ug[..., hidden:])
        d = _mm(g, dequant_q40(down, hidden), lower)
        return acc + d * combine[..., e][..., None], None

    acc, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(n_experts))
    return x + acc


@functools.partial(jax.jit, static_argnames=("dim", "eps", "lower"))
def _head(x, rows, rms_final, wcls, dim: int, eps: float, lower):
    """Final norm and classifier at rows [R] of x [T, D] -> [R, V]."""
    return _mm(_rmsnorm(x[rows], rms_final, eps), dequant_q40(wcls, dim),
               lower)


def model_sizes(model: dict) -> tuple:
    """The static sizes ``_layer`` needs, from a configuration file."""
    hd = int(model.get("head_dim")
             or model["hidden_size"] // model["num_attention_heads"])
    style = "half" if model["arch"] == "mixtral" else "interleaved"
    return (int(model["hidden_size"]), int(model["intermediate_size"]),
            int(model["num_attention_heads"]),
            int(model["num_key_value_heads"]), hd,
            int(model.get("num_local_experts", 0)),
            int(model.get("num_experts_per_tok", 0)),
            float(model["rms_norm_eps"]), style)


def logits_at(planes: dict, model: dict, seqs: list, rows: list,
              lower=None, block_budget: float = 1.5e9) -> list:
    """Run the reference over each token sequence and return, per sequence,
    the logits [len(rows[i]), V] at the positions ``rows[i]`` (numpy f32).

    Sequences are padded to one length and run in blocks whose attention
    scores stay under ``block_budget`` bytes; the layers run one at a time."""
    m = model_sizes(model)
    dim, n_heads, hd = m[0], m[2], m[4]
    t_pad = -(-max(len(s) for s in seqs) // 128) * 128
    cos, sin = rope_tables(t_pad, hd, float(model["rope_theta"]))
    per_seq = 4.0 * n_heads * t_pad * t_pad
    block = max(1, int(block_budget // per_seq))
    n_layers = int(model["num_hidden_layers"])
    r_pad = -(-max(len(r) for r in rows) // 32) * 32  # one compile of _head
    out: list = []
    for b0 in range(0, len(seqs), block):
        chunk = seqs[b0:b0 + block]
        toks = np.zeros((len(chunk), t_pad), np.int32)
        for i, s in enumerate(chunk):
            toks[i, :len(s)] = s
        x = planes["embedding"][jnp.asarray(toks)].astype(F32)
        for layer in range(n_layers):
            x = _layer(x, planes["layers"], jnp.int32(layer), cos, sin, m=m,
                       lower=lower)
        for i in range(len(chunk)):
            r = rows[b0 + i]
            sel = np.zeros(r_pad, np.int32)
            sel[:len(r)] = r
            out.append(np.asarray(_head(
                x[i], jnp.asarray(sel), planes["rms_final"], planes["wcls"],
                dim=dim, eps=m[7], lower=lower))[:len(r)])
    return out


CONTROL = "float8_e4m3fn"
WITNESS = "bfloat16"


def compare(planes: dict, model: dict, samples: list,
            stand_ins: dict | None = None) -> dict:
    """The comparison behind ``correct``.

    ``samples``: [{"prompt": ids, "served": ids}], greedy requests the window
    finished. The reference runs once over prompt + served tokens; a served
    token's gap is how far its reference logit lies below the reference's
    best at that position, in standard deviations of that position's logits
    (random weights give flat logits: the unit makes depths comparable).
    Returns the served tokens' gaps (``gapstats.py`` turns them into the
    numbers compared) and, for every ``{name: lower precision}`` of
    ``stand_ins``, ``<name>_gaps``: those of the tokens that the forward in
    that precision puts first at the same positions, which is the reference
    in lower precision put in the program's place (``CONTROL``: float8, the
    step below the bfloat16 the configurations state; ``WITNESS``: bfloat16
    itself)."""
    seqs, rows = [], []
    for s in samples:
        p, g = list(s["prompt"]), list(s["served"])
        seqs.append(p + g)
        rows.append([len(p) - 1 + j for j in range(len(g))])
    ref = logits_at(planes, model, seqs, rows)
    gaps: list = []
    for s, lg in zip(samples, ref):
        served = np.asarray(s["served"], np.int64)
        chosen = lg[np.arange(len(served)), served]
        gaps.extend(((lg.max(axis=1) - chosen) / lg.std(axis=1)).tolist())
    res = {"gaps": gaps,
           "finite": bool(all(np.isfinite(lg).all() for lg in ref))}
    for name, mode in (stand_ins or {}).items():
        low = logits_at(planes, model, seqs, rows, lower=mode)
        cg: list = []
        for lg, ll in zip(ref, low):
            first = ll.argmax(axis=1)
            cg.extend(((lg.max(axis=1) - lg[np.arange(len(first)), first])
                       / lg.std(axis=1)).tolist())
        res[name + "_gaps"] = cg
    return res
