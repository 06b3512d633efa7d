"""The toy family's plain reference: numpy, float64, one sequence at a time.
Imports nothing of the program and nothing of another family.

Q40, as the program lays it out: ``w`` is ``uint8 [K/2, O]``, byte
``32 s + j`` holding input row ``64 s + j`` in its low nibble (scale
``s[s]``) and row ``64 s + 32 + j`` in its high nibble (scale ``s2[s]``); a
nibble stores ``q + 8``. ``arch: llama`` rotates interleaved pairs.
"""

from __future__ import annotations

import numpy as np

LOWER = {"control": "float8_e4m3fn", "witness": "bfloat16"}


def dequant(plane: dict, k: int) -> np.ndarray:
    w = np.asarray(plane["w"]).astype(np.int32)
    half, out = w.shape
    lo = ((w & 0xF) - 8).reshape(half // 32, 32, out) * np.asarray(plane["s"])[:, None, :]
    hi = ((w >> 4) - 8).reshape(half // 32, 32, out) * np.asarray(plane["s2"])[:, None, :]
    return np.concatenate([lo, hi], axis=1).reshape(half * 2, out)[:k].astype(np.float64)


def rounded(x, lower):
    if lower is None:
        return x
    import ml_dtypes

    return x.astype(getattr(ml_dtypes, lower)).astype(np.float64)


def rmsnorm(x, weight, eps):
    return np.asarray(weight, np.float64) * x / np.sqrt((x * x).mean(-1, keepdims=True) + eps)


def rope(x, base):
    """x [T, heads, hd], interleaved pairs."""
    t, _, hd = x.shape
    freqs = 1.0 / base ** (np.arange(0, hd, 2) / hd)
    ang = np.arange(t)[:, None] * freqs[None, :]
    c, s = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return np.stack([x0 * c - x1 * s, x0 * s + x1 * c], axis=-1).reshape(x.shape)


def logits(planes: dict, conf: dict, seq: list, lower=None) -> np.ndarray:
    """-> [T, V] over the whole sequence."""
    d, f, n_h, n_kv = conf["width"], conf["ffn_width"], conf["heads"], conf["kv_heads"]
    hd, t = d // n_h, len(seq)
    mm = lambda a, w: rounded(a, lower) @ w
    x = np.asarray(planes["embedding"], np.float64)[np.asarray(seq)]
    lay = {k: ({kk: np.asarray(vv) for kk, vv in v.items()} if isinstance(v, dict)
               else np.asarray(v)) for k, v in planes["layers"].items()}
    causal = np.tril(np.ones((t, t), bool))
    for i in range(conf["depth"]):
        at = lambda name, k: dequant({kk: vv[i] for kk, vv in lay[name].items()}, k)
        qkv = mm(rmsnorm(x, lay["rms_att"][i], conf["eps"]), at("wqkv", d))
        q = rope(qkv[:, :d].reshape(t, n_h, hd), conf["rope_base"])
        k = rounded(rope(qkv[:, d:d + n_kv * hd].reshape(t, n_kv, hd),
                         conf["rope_base"]), lower)
        v = rounded(qkv[:, d + n_kv * hd:].reshape(t, n_kv, hd), lower)
        k, v = (np.repeat(a, n_h // n_kv, axis=1) for a in (k, v))
        scores = np.einsum("thd,shd->hts", q, k) / np.sqrt(hd)
        scores = np.where(causal[None], scores, -np.inf)
        p = np.exp(scores - scores.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        x = x + mm(np.einsum("hts,shd->thd", p, v).reshape(t, d), at("wo", d))
        u = mm(rmsnorm(x, lay["rms_ffn"][i], conf["eps"]), at("w13", d))
        g = u[:, :f] / (1.0 + np.exp(-u[:, :f])) * u[:, f:]
        x = x + mm(g, at("w2", f))
    wcls = {k: np.asarray(v) for k, v in planes["wcls"].items()}
    return mm(rmsnorm(x, np.asarray(planes["rms_final"]), conf["eps"]),
              dequant(wcls, d))


def compare(planes: dict, conf: dict, samples: list, stand_ins=()) -> dict:
    """A served token's gap: how far its reference logit lies below the
    reference's best at its position, in standard deviations of that
    position's logits; ``<stand-in>_gaps``: those of the tokens that the
    forward in the stand-in's precision puts first at the same positions."""
    res: dict = {"gaps": [], "finite": True}
    res.update({name + "_gaps": [] for name in stand_ins})
    for s in samples:
        p, g = list(s["prompt"]), list(s["served"])
        rows = np.arange(len(p) - 1, len(p) - 1 + len(g))
        ref = logits(planes, conf, p + g)[rows]
        res["finite"] = res["finite"] and bool(np.isfinite(ref).all())
        below = lambda ids: ((ref.max(1) - ref[np.arange(len(g)), ids])
                             / ref.std(1)).tolist()
        res["gaps"] += below(np.asarray(g))
        for name in stand_ins:
            low = logits(planes, conf, p + g, LOWER[name])[rows]
            res[name + "_gaps"] += below(low.argmax(1))
    return res
