"""Mixture-of-experts FFN (Grok-1 / Mixtral).

Reference semantics (`/root/reference/src/grok1-tasks.cpp:56-243`):
router logits -> softmax over ALL experts -> top-k (k = n_active_experts,
the reference hard-codes 2) -> selected probs renormalized to sum 1 ->
per selected expert: ``down_e( up_e(x) * act(gate_e(x)) )`` weighted-summed.

TP mapping: every shard holds a 1/tp slice of EVERY expert (the reference
slices within experts, not across them — `/root/reference/src/transformer.cpp:479-487`),
so the expert einsums below shard exactly like w1/w2/w3 and no expert-routing
communication is needed. An optional ``ep`` mesh axis can additionally shard
the leading expert dim of the stacked tensors (expert parallelism — beyond
the reference's capabilities). Under *quantized* TP (shard_map,
parallel.quant_tp) the expert planes carry output-axis shards and ``tp_axis``
drives explicit per-expert hidden gathers, mirroring the dense FFN's
gather-before-w2 (`models.llama._dense_ffn`); the gathers live
in `parallel.collectives`.

Compute paths:

* Dense stacks / no layer index: evaluate all E experts, combine with a
  [.., E] weight matrix that is zero off the top-k — dense and MXU-friendly,
  exact same math. For small E (8) that trades <=E/k extra FLOPs for zero
  gather/scatter.
* Quantized stacks under the scalar-prefetch layer scan (``layer`` given):
  the expert planes stay layer-stacked ([L, E, ...] folded to [L*E, ...], a
  free bitcast) and a traced ``layer * E + e`` steers each fused kernel's
  DMA. For small T (decode T==1, speculative verify T==k_spec+1) only the
  UNION of the rows' top-k selected experts is computed — at most
  min(E, T*k) expert plane reads instead of E — the bandwidth win that
  makes Q40 Grok-1-class models decode at quantized speed, the analog of
  the reference running only active experts
  (`/root/reference/src/grok1-tasks.cpp:128-143`). For batched prefill every
  expert runs once (different rows pick different experts) with the same
  zero-copy indexing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dllama_tpu.models.config import ModelConfig
from dllama_tpu.ops.activations import ACTIVATIONS
from dllama_tpu.ops.qmatmul import QuantTensor, matmul_any, slice_to_in_features
from dllama_tpu.parallel.collectives import gather_columns as _gather


@jax.named_scope("moe_router")
def route_topk(cfg: ModelConfig, router_kernel: jnp.ndarray,
               xb: jnp.ndarray, bias: jnp.ndarray | None = None) -> tuple:
    """Top-k routing -> (indices [..., k], renormalized weights [..., k]).

    ``cfg.router`` "sigmoid_bias" (with ``bias`` [E], the per-expert
    correction): the scores are sigmoids, the bias only CHOOSES (top k of
    score + bias) and the chosen experts' unbiased scores weigh,
    renormalized to sum 1. The indices are over all ``cfg.n_experts`` the
    router scores, whichever of them this process holds.

    Router math runs in f32 like the reference (router matmul outputs F32,
    `/root/reference/src/grok1-tasks.cpp:56-60`); selected probabilities are
    renormalized to sum 1 (`:99-114`). Single source of truth for BOTH the
    dense-combine path and the T==1 selected-experts decode path — they must
    agree exactly or decode would diverge from prefill on the same weights.
    """
    logits = xb.astype(jnp.float32) @ router_kernel.astype(jnp.float32)  # [..., E]
    if cfg.router == "sigmoid_bias":
        scores = jax.nn.sigmoid(logits)
        _, topi = jax.lax.top_k(scores + bias.astype(jnp.float32),
                                cfg.n_active_experts)
        topv = jnp.take_along_axis(scores, topi, axis=-1)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        topv, topi = jax.lax.top_k(probs, cfg.n_active_experts)
    weights = topv / topv.sum(axis=-1, keepdims=True)  # renormalize over selected
    return topi, weights


def route(cfg: ModelConfig, router_kernel: jnp.ndarray, xb: jnp.ndarray,
          bias: jnp.ndarray | None = None) -> jnp.ndarray:
    """Top-k routing -> dense combine weights [..., E] (zeros off the top-k)
    over the experts this process holds: a row that chose none of them has a
    row of zeros, and a row's weights sum to 1 only over ALL its picks."""
    topi, weights = route_topk(cfg, router_kernel, xb, bias)
    one_hot = jax.nn.one_hot(topi, cfg.n_experts, dtype=jnp.float32)  # [..., k, E]
    combine = jnp.einsum("...ke,...k->...e", one_hot, weights.astype(jnp.float32))
    if cfg.n_experts_held != cfg.n_experts:
        combine = combine[..., cfg.expert_first:
                          cfg.expert_first + cfg.expert_count]
    return combine


def pick_counts(cfg: ModelConfig, topi: jnp.ndarray,
                live: jnp.ndarray) -> jnp.ndarray:
    """What the ``live`` rows [T] (bool) chose, from ``route_topk``'s indices
    [T, k] -> int32 [3]: their picks that fell on held experts, all their
    picks, and the distinct held experts they picked."""
    held = ((topi >= cfg.expert_first)
            & (topi < cfg.expert_first + cfg.n_experts_held)
            & live[:, None])
    hot = jax.nn.one_hot(topi - cfg.expert_first, cfg.n_experts_held,
                         dtype=jnp.bool_) & held[..., None]
    return jnp.stack([held.sum(), live.sum() * topi.shape[-1],
                      hot.any(axis=(0, 1)).sum()]).astype(jnp.int32)


def _flat_experts(qt: QuantTensor) -> QuantTensor:
    """Fold a layer-stacked expert stack [L, E, ...] (or a per-layer stack
    [E, ...]) to a flat [n, ...] stack for index-steered kernels. Leading-axis
    reshapes are bitcasts — no copy, the planes stay in place in HBM."""
    return QuantTensor(
        w=qt.w.reshape(-1, *qt.w.shape[-2:]),
        s=qt.s.reshape(-1, *qt.s.shape[-2:]),
        s2=(qt.s2.reshape(-1, *qt.s2.shape[-2:]) if qt.kind == "q40"
            else qt.s2.reshape(-1)),
        kind=qt.kind, k_logical=qt.k_logical,
    )


def _expert_up(xb: jnp.ndarray, w, base=None,
               name: str = "expert_up") -> jnp.ndarray:
    """``xb [..., D] x w [E, D, H] -> [..., E, H]``; ``w`` is a dense stack or
    an expert-stacked QuantTensor. Quantized experts run one fused
    dequant-matmul per expert; with ``base`` (= layer * E, the scalar-prefetch
    path) the planes are layer-stacked and indexed in the kernel, otherwise
    the scan slices the per-layer stack. ``name``: which expert projection
    (``expert_up`` | ``expert_gate`` | ``expert_upgate``), for the kernels'
    names in a trace."""
    if not isinstance(w, QuantTensor):
        return jnp.einsum("...d,edh->...eh", xb, w)
    lead = xb.shape[:-1]
    x2 = xb.reshape(-1, xb.shape[-1])  # [N, D]

    if base is not None:
        flat = _flat_experts(w)
        n_e = w.w.shape[1]

        def step(_, e):
            return None, matmul_any(x2, flat, base + e, name=name)

        _, outs = jax.lax.scan(step, None, jnp.arange(n_e, dtype=jnp.int32))
    else:
        def step(_, qt_e):
            return None, matmul_any(x2, qt_e, name=name)

        _, outs = jax.lax.scan(step, None, w)  # [E, N, H]
    return jnp.moveaxis(outs, 0, 1).reshape(*lead, outs.shape[0], outs.shape[-1])


def _expert_down(h: jnp.ndarray, w, base=None) -> jnp.ndarray:
    """``h [..., E, H] x w [E, H, D] -> [..., E, D]`` (dense or QuantTensor)."""
    if not isinstance(w, QuantTensor):
        return jnp.einsum("...eh,ehd->...ed", h, w)
    lead = h.shape[:-2]
    E, H = h.shape[-2], h.shape[-1]
    hm = jnp.moveaxis(h.reshape(-1, E, H), 1, 0)  # [E, N, H]

    if base is not None:
        flat = _flat_experts(w)

        def step(_, eh):
            e, h_e = eh
            return None, matmul_any(h_e, flat, base + e, name="expert_down")

        _, outs = jax.lax.scan(
            step, None, (jnp.arange(E, dtype=jnp.int32), hm))
    else:
        def step(_, eh):
            h_e, qt_e = eh
            return None, matmul_any(h_e, qt_e, name="expert_down")

        _, outs = jax.lax.scan(step, None, (hm, w))  # [E, N, D]
    return jnp.moveaxis(outs, 0, 1).reshape(*lead, E, outs.shape[-1])


def _moe_decode_selected(cfg: ModelConfig, lp: dict, xb: jnp.ndarray, layer,
                         tp_axis=None, tp_compress: bool = False) -> jnp.ndarray:
    """Small-T decode/verify with layer-stacked quantized experts: run ONLY
    the union of the rows' top-k selected experts, each kernel DMA-ing just
    that expert's planes. T==1 is plain decode (the union is exactly the
    top-k); T==k_spec+1 is a speculative verify step, which still reads at
    most min(E, T*k) expert plane sets instead of all E. Exact same math as
    the dense combine: every expert outside the union has zero combine
    weight for every row, and union slots beyond the actually-selected set
    (ties in the top-cap selection) multiply a zero weight.

    Under quantized TP (``tp_axis``): the expert planes are output shards;
    all selected experts' hidden activations are gathered in ONE collective
    (decode payloads are latency-bound — collective count matters more than
    bytes, see ``parallel.collectives``), then each feeds its down matmul and the
    combined output — accumulated in output shards — is gathered at the end:
    2 collectives per MoE FFN, like the dense FFN's pair.
    """
    act = ACTIVATIONS[cfg.hidden_act]
    E, k = cfg.n_experts_held, cfg.n_active_experts
    T = xb.shape[0]
    cap = min(E, T * k)
    # [T, E] f32, zero off top-k
    combine = route(cfg, lp["moe_router"], xb, lp.get("moe_bias"))
    # every expert any row selected has a positive combine weight somewhere,
    # and there are at most T*k of them — the top `cap` column-maxima cover
    # the whole union (extra slots carry zero weight and contribute nothing)
    _, expert_ids = jax.lax.top_k(combine.max(axis=0), cap)  # [cap]
    base = layer * E

    fused = "moe_upgate" in lp
    up_flat = _flat_experts(lp["moe_upgate" if fused else "moe_up"])
    gate_flat = None if fused else _flat_experts(lp["moe_gate"])
    down_flat = _flat_experts(lp["moe_down"])
    out_dim = down_flat.out_features  # local under tp, full otherwise

    def up_step(_, j):
        idx = base + expert_ids[j]
        if fused:
            ug = matmul_any(xb, up_flat, idx, name="expert_upgate")
            half = ug.shape[-1] // 2
            h = ug[..., :half] * act(ug[..., half:])
        else:
            h = (matmul_any(xb, up_flat, idx, name="expert_up")
                 * act(matmul_any(xb, gate_flat, idx, name="expert_gate")))
        return None, h

    _, hs = jax.lax.scan(up_step, None, jnp.arange(cap, dtype=jnp.int32))
    hs = _gather(hs, tp_axis, tp_compress)  # [cap, T, full hidden] in one hop

    def down_step(acc, jh):
        j, h = jh
        e = expert_ids[j]
        d = matmul_any(h, down_flat, base + e,
                       name="expert_down")  # [T, out_dim]
        w_e = jax.lax.dynamic_index_in_dim(combine, e, axis=1)  # [T, 1]
        return acc + d * w_e.astype(d.dtype), None

    acc, _ = jax.lax.scan(
        down_step, jnp.zeros((T, out_dim), xb.dtype),
        (jnp.arange(cap, dtype=jnp.int32), hs))
    return _gather(acc, tp_axis, tp_compress)


@jax.named_scope("moe")
def moe_ffn(cfg: ModelConfig, lp: dict, xb: jnp.ndarray, layer=None,
            tp_axis=None, tp_compress: bool = False) -> jnp.ndarray:
    """MoE FFN over xb [..., dim] -> [..., dim].

    lp holds: moe_router [dim, E], moe_up/moe_gate [E, dim, hidden],
    moe_down [E, hidden, dim] — each expert stack a dense array or a
    quantized (QuantTensor) stack — and, for the "sigmoid_bias" router,
    moe_bias [E]. Where the process holds a share of the experts
    (``cfg.expert_count``) the stacks hold those, the router still scores
    all E, and the result is the held experts' PART of the sum: what expert
    parallelism adds up across processes. With ``layer`` (the scalar-prefetch scan),
    quantized stacks carry a leading layer axis and dense leaves arrive
    already layer-indexed. ``tp_axis`` (inside shard_map, quantized TP):
    expert stacks are output shards; the hidden activation is gathered
    before the down matmuls and the output once after the combine.
    """
    act = ACTIVATIONS[cfg.hidden_act]
    up_names = ("moe_upgate",) if "moe_upgate" in lp else ("moe_up", "moe_gate")
    quant_experts = all(
        isinstance(lp.get(n), QuantTensor) for n in up_names + ("moe_down",)
    )
    if (layer is not None and quant_experts and xb.ndim == 2
            and xb.shape[0] * cfg.n_active_experts < cfg.n_experts_held):
        return _moe_decode_selected(cfg, lp, xb, layer, tp_axis, tp_compress)

    # Under the layer scan, EVERY QuantTensor stack is layer-stacked and needs
    # index-steered kernels — even if a sibling stack fell back to dense (the
    # hidden_dim % 64 != 0 load fallback), which arrives already layer-indexed
    # and ignores base. A global quant_experts gate here would feed a 4D
    # [L, E, ...] stack into the per-expert slicing scan below.
    base = layer * cfg.n_experts_held if layer is not None else None
    combine = route(cfg, lp["moe_router"], xb,
                    lp.get("moe_bias")).astype(xb.dtype)  # [..., E]

    if "moe_upgate" in lp:  # fused up|gate expert stacks (llama.fuse_qkv_ffn)
        ug = _expert_up(xb, lp["moe_upgate"], base, "expert_upgate")
        half = ug.shape[-1] // 2
        h = ug[..., :half] * act(ug[..., half:])
    else:
        up = _expert_up(xb, lp["moe_up"], base)
        gate = _expert_up(xb, lp["moe_gate"], base, "expert_gate")
        h = up * act(gate)
    h = _gather(h, tp_axis, tp_compress)  # [..., E, full hidden] under tp
    h = slice_to_in_features(h, lp["moe_down"])
    down = _expert_down(h, lp["moe_down"], base)
    out = jnp.einsum("...ed,...e->...d", down, combine)
    return _gather(out, tp_axis, tp_compress)
