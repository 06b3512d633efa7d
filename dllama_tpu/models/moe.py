"""Mixture-of-experts FFN (Grok-1 / Mixtral, and a layer plan's expert kinds).

Routers (``cfg.router``, ``route_topk``): "softmax" (softmax over ALL
experts, the top k renormalised: Grok-1, Mixtral), "sigmoid_bias" (sigmoid
scores, the top k of score + correction bias chosen, the chosen experts'
unbiased scores renormalised) and "sigmoid" (the same without a bias). Beside
the routed experts a layer may hold experts that are ALWAYS on
(``cfg.shared_dim``, ``shared_ffn``): one gated FFN of their summed width
whose output is scaled (by 1 / their number where they are averaged), added
to the routed sum inside ``moe_ffn_counted`` under the scope ``moe_shared``;
its kernels are named ``shared_upgate`` / ``shared_down``.

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
  DMA. **The rule that picks a branch reads shapes and ``cfg`` only:** where
  the rows' picks cannot be expected to cover the held experts,
  ``T * k < n_experts`` (ALL the experts the router scores: a process that
  holds a share of them has the same fraction of its experts picked), the
  step runs ``_moe_decode_selected``: only the distinct held experts that
  its counted rows picked, one trip of a loop each (up -> act -> down ->
  accumulate in float32). The trip count is a TRACED number, read from the
  combine matrix; ``cap = min(n_experts_held, T * k)`` is only its static
  bound, and rows a caller masks out (``live``: a pool's free and finished
  rows) count for nothing. That is decode T==1, a pooled step of a model
  that holds 32 of 256 experts (8 rows x 8 picks reach about 4 of the 32),
  a speculative verify step T==k_spec+1: the bandwidth win that makes Q40
  MoE models decode at quantized speed, the analog of the reference running
  only active experts (`/root/reference/src/grok1-tasks.cpp:128-143`).
  ``moe_ffn_counted`` also returns how many expert plane sets the call read.
  Otherwise (batched prefill; a pooled step of Mixtral, whose 24 rows x 2
  picks do cover its 8 experts) every held expert runs once with the same
  zero-copy indexing, and the combine matrix zeroes what a row did not pick.
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
    renormalized to sum 1; "sigmoid": the scores choose. The indices are
    over all ``cfg.n_experts`` the router scores, whichever of them this
    process holds.

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
    elif cfg.router == "sigmoid":
        topv, topi = jax.lax.top_k(jax.nn.sigmoid(logits),
                                   cfg.n_active_experts)
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
                         tp_axis=None, tp_compress: bool = False,
                         live=None) -> tuple:
    """Small-T step with layer-stacked quantized experts: run ONLY the
    distinct held experts that the step's counted rows picked, each kernel
    DMA-ing just that expert's planes -> (out [T, dim], the expert plane
    sets read, int32). How many that is comes from the input: ``n``, the
    columns of the combine matrix whose maximum is positive, is a traced
    number and the trip count of the loop over experts; ``cap = min(E, T*k)``
    is only its static upper bound. ``n == 0`` (no counted row picked a held
    expert) runs no expert and gives zeros. T==1 is plain decode, T==B a
    pooled step, T==k_spec+1 a speculative verify step.

    ``live`` [T] bool: the rows that count. A row that is not decoding (a
    free or finished row of a pool) has its combine weights zeroed BEFORE
    the experts are chosen, so it activates no expert and its expert part is
    zero; without ``live`` every row counts.

    Same math as the dense combine: an expert outside the chosen ones has
    zero combine weight for every row. The sum over the experts is kept in
    float32 and rounded once.

    Under quantized TP (``tp_axis``): the expert planes are output shards;
    all selected experts' hidden activations are gathered in ONE collective
    (decode payloads are latency-bound — collective count matters more than
    bytes, see ``parallel.collectives``), which needs the static
    ``[cap, T, H]`` stack: there the scans run all ``cap`` slots (slots past
    ``n`` multiply a zero weight) and ``cap`` plane sets are read. Each
    hidden feeds its down matmul and the combined output — accumulated in
    output shards — is gathered at the end: 2 collectives per MoE FFN, like
    the dense FFN's pair.
    """
    act = ACTIVATIONS[cfg.hidden_act]
    E, k = cfg.n_experts_held, cfg.n_active_experts
    T = xb.shape[0]
    cap = min(E, T * k)
    # [T, E] f32, zero off top-k
    combine = route(cfg, lp["moe_router"], xb, lp.get("moe_bias"))
    if live is not None:
        combine = jnp.where(live[:, None], combine, 0.0)
    # every expert a counted row selected has a positive combine weight
    # somewhere, and there are at most T*k of them: top_k over the column
    # maxima puts them first, n of them
    col_max = combine.max(axis=0)
    _, expert_ids = jax.lax.top_k(col_max, cap)  # [cap]
    n = (col_max > 0).sum().astype(jnp.int32)  # <= cap
    base = layer * E

    fused = "moe_upgate" in lp
    up_flat = _flat_experts(lp["moe_upgate" if fused else "moe_up"])
    gate_flat = None if fused else _flat_experts(lp["moe_gate"])
    down_flat = _flat_experts(lp["moe_down"])
    out_dim = down_flat.out_features  # local under tp, full otherwise

    def hidden(e):
        idx = base + e
        if fused:
            ug = matmul_any(xb, up_flat, idx, name="expert_upgate")
            half = ug.shape[-1] // 2
            return ug[..., :half] * act(ug[..., half:])
        return (matmul_any(xb, up_flat, idx, name="expert_up")
                * act(matmul_any(xb, gate_flat, idx, name="expert_gate")))

    def weighted_down(acc, e, h):
        d = matmul_any(h, down_flat, base + e,
                       name="expert_down")  # [T, out_dim]
        w_e = jax.lax.dynamic_index_in_dim(combine, e, axis=1)  # [T, 1] f32
        return acc + d.astype(jnp.float32) * w_e

    acc = jnp.zeros((T, out_dim), jnp.float32)
    if tp_axis is None:
        # up -> act -> down -> accumulate of one expert a trip, n trips
        def one_expert(j, acc):
            e = expert_ids[j]
            return weighted_down(acc, e, hidden(e))

        acc = jax.lax.fori_loop(0, n, one_expert, acc)
        return acc.astype(xb.dtype), n

    _, hs = jax.lax.scan(lambda _, e: (None, hidden(e)), None, expert_ids)
    hs = _gather(hs, tp_axis, tp_compress)  # [cap, T, full hidden] in one hop
    acc, _ = jax.lax.scan(
        lambda acc, eh: (weighted_down(acc, *eh), None), acc,
        (expert_ids, hs))
    return _gather(acc.astype(xb.dtype), tp_axis, tp_compress), cap


@jax.named_scope("moe_shared")
def shared_ffn(cfg: ModelConfig, lp: dict, xb: jnp.ndarray,
               layer=None) -> jnp.ndarray:
    """The experts that are always on, as ONE gated FFN of their summed
    width: ``shared_scale * down(up(x) * act(gate(x)))`` with
    ``shared_upgate`` = up | gate (the experts' column order) and
    ``shared_down``, dense or layer-stacked quantized planes."""
    act = ACTIVATIONS[cfg.hidden_act]
    ug = matmul_any(xb, lp["shared_upgate"], layer, name="shared_upgate")
    half = ug.shape[-1] // 2
    out = matmul_any(ug[..., :half] * act(ug[..., half:]), lp["shared_down"],
                     layer, name="shared_down")
    if cfg.shared_scale != 1.0:
        out = out * jnp.asarray(cfg.shared_scale, out.dtype)
    return out


@jax.named_scope("moe")
def moe_ffn_counted(cfg: ModelConfig, lp: dict, xb: jnp.ndarray, layer=None,
                    tp_axis=None, tp_compress: bool = False,
                    live=None) -> tuple:
    """``moe_ffn`` -> (out, the expert plane sets this call read: a traced
    int32 where the input decides it, else a Python int). ``live`` [T] bool
    (it matters to a small-T step of quantized stacks only): the rows that
    are decoding; the others activate no expert (``_moe_decode_selected``).
    Where the layer has always-on experts (``cfg.shared_dim``) their part is
    added here, for every row, and is no expert plane set of the count."""
    out, reads = _routed_counted(cfg, lp, xb, layer, tp_axis, tp_compress,
                                 live)
    if cfg.shared_dim:
        out = out + shared_ffn(cfg, lp, xb, layer)
    return out, reads


def _routed_counted(cfg: ModelConfig, lp: dict, xb: jnp.ndarray, layer,
                    tp_axis, tp_compress: bool, live) -> tuple:
    """The routed experts' part of ``moe_ffn_counted``."""
    act = ACTIVATIONS[cfg.hidden_act]
    up_names = ("moe_upgate",) if "moe_upgate" in lp else ("moe_up", "moe_gate")
    quant_experts = all(
        isinstance(lp.get(n), QuantTensor) for n in up_names + ("moe_down",)
    )
    # the selected path where the rows' picks cannot be expected to cover
    # the held experts: from shapes alone (a process that holds a share has
    # T*k/n_experts of its experts picked, whatever it holds)
    if (layer is not None and quant_experts and xb.ndim == 2
            and xb.shape[0] * cfg.n_active_experts < cfg.n_experts):
        return _moe_decode_selected(cfg, lp, xb, layer, tp_axis, tp_compress,
                                    live)

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
    return _gather(out, tp_axis, tp_compress), cfg.n_experts_held


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
    return moe_ffn_counted(cfg, lp, xb, layer, tp_axis, tp_compress)[0]
