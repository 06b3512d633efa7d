"""Tensor parallelism for block-quantized weights (shard_map + Pallas).

The reference's production configuration is Q40 weights sliced across *every*
node (`/root/reference/src/transformer.cpp:454-493` slicing fed to the Q40
matmul `/root/reference/src/funcs.cpp:267-385`). XLA cannot auto-partition a
``pallas_call``, so the quantized forward runs under ``shard_map``: every
device executes the fused dequant-matmul kernels on its *local* weight shard
and the activations move with explicit collectives.

Sharding scheme — **output-axis only**:

Every quantized matrix (and each of its planes: packed bits ``w``, scale
planes ``s``/``s2``) is sharded on its OUT axis; the packed K axis is never
split. Two reasons this beats K-slicing for quant blocks:

* K is padded to ``K_MULTIPLE`` at pack time (ops.qmatmul); a K-split of the
  padded planes would misalign superblock boundaries per shard (e.g. 7B's
  11264-padded K / 8 devices = 1408, not a multiple of 512) and force
  per-shard repadding. O-splitting leaves every plane's K layout intact, so
  any tp degree that divides O yields a shard with exactly the same
  Mosaic-valid tiling as the unsharded tensor.
* The matmul result for each output element is computed from the full K on
  one device — no f32 partial-sum psum; the only collectives are small
  activation all-gathers (4 per layer), mirroring the reference's 4 wire
  trips per layer (`SURVEY.md` §3.3) but over ICI.

The attention out-projection ``wo`` and FFN down-projection ``w2`` therefore
consume *gathered* inputs instead of producing psum partials — see
``parallel.collectives.gather_columns``.

Opt-in ROW-PARALLEL mode (``--tp-reduce``): ``wo``/``w2`` alone switch to
K-sharding, so they consume the up-projections' *local* output shards with
no gather at all and emit full-width f32 partials, reduced by
``parallel.collectives.reduce_columns``'s quantizable ring reduce-scatter.
The superblock-misalignment objection above is sidestepped by re-packing
each K-shard INDEPENDENTLY (``row_shard_quant_leaf``): every shard's K is
padded to ``K_MULTIPLE`` on its own, so each local plane keeps exactly the
Mosaic-valid tiling of an unsharded tensor — at the cost of requiring the
per-shard logical K to land on the scale-plane slicing granularity
(64 input rows for q40's even/odd twin scales, 32 for q80).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from dllama_tpu.models.config import ModelConfig
from dllama_tpu.ops.qmatmul import K_MULTIPLE, QuantTensor, _pad_up
from dllama_tpu.parallel.mesh import TP
from dllama_tpu.parallel.sharding import cache_spec, check_tp_compatible


def has_quant_leaves(params) -> bool:
    is_qt = lambda x: isinstance(x, QuantTensor)  # noqa: E731
    return any(is_qt(leaf) for leaf in jax.tree.leaves(params, is_leaf=is_qt))


def _out_shard_spec(arr) -> P:
    """Shard the last (output) axis over tp; empty placeholders replicate."""
    if arr.ndim == 0 or arr.shape[-1] == 0:
        return P(*([None] * arr.ndim))
    return P(*([None] * (arr.ndim - 1)), TP)


def _replicated_spec(arr) -> P:
    return P(*([None] * arr.ndim))


#: per-layer matrices that shard their output axis over tp. MoE expert stacks
#: shard exactly like their dense twins — every device holds a 1/tp output
#: slice of EVERY expert, the reference's TP-within-expert scheme
#: (`/root/reference/src/transformer.cpp:479-487`, expert matmuls on slices at
#: `/root/reference/src/grok1-tasks.cpp:128-143`) — which is what lets a Q40
#: Grok-1/Mixtral fit: each chip stores n-th of the expert bytes.
SHARDED_MATRICES = frozenset(
    {"wq", "wk", "wv", "wo", "w1", "w2", "w3", "moe_up", "moe_gate", "moe_down"}
)

#: matrices that K-shard (row-parallel) under ``--tp-reduce`` instead of
#: output-sharding — exactly the two whose inputs are produced sharded by
#: the preceding matmuls (local heads feed wo, local up/gate halves feed w2)
ROW_SHARDED_MATRICES = frozenset({"wo", "w2"})

#: K rows covered by one scale-plane row: q40's s/s2 twins each span a
#: 64-row superblock half; q80 scales span one 32-row block
ROW_SHARD_GRANULARITY = {"q40": 64, "q80": 32}


def validate_quant_tp(cfg: ModelConfig, n_tp: int) -> None:
    check_tp_compatible(cfg, n_tp)
    if cfg.dim % n_tp or cfg.kv_dim % n_tp:
        raise ValueError(f"tp={n_tp} must divide dim={cfg.dim} and kv_dim={cfg.kv_dim}")


def row_shard_chunk_k(cfg: ModelConfig, name: str, kind: str, n_tp: int) -> int:
    """Logical K rows each device's row shard of ``name`` consumes: wo eats
    the local head concat (dim/tp); w2 eats the local half of the
    lane-aligned hidden width w1/w3 produce (ffn_padded_width/tp)."""
    base = cfg.dim if name == "wo" else ffn_padded_width(cfg, kind, n_tp)
    return base // n_tp


def validate_tp_reduce(cfg: ModelConfig, kind: str, n_tp: int):
    """None when row-parallel wo/w2 can engage, else a machine-visible
    decline reason (the Engine's warn-and-drop surfaces it on /stats)."""
    if cfg.is_moe:
        return ("moe: row-parallel reduce needs a dense FFN (the "
                "selected-experts union spans all rows)")
    for name in sorted(ROW_SHARDED_MATRICES):
        chunk = row_shard_chunk_k(cfg, name, kind, n_tp)
        gran = ROW_SHARD_GRANULARITY[kind]
        if chunk % gran:
            return (f"{name}: per-shard K {chunk} off the {kind} slicing "
                    f"granularity {gran} (scale planes cover {gran} input "
                    f"rows; need dim and the padded hidden divisible by "
                    f"{gran}*tp)")
    return None


# ---------------------------------------------------------------------------
# Lane-alignment padding.
#
# On real TPUs every Mosaic block's lane (last) dim must be a multiple of
# 128, so a *local* shard of an output axis must be 128-aligned. Head-carrying
# axes (dim, kv_dim) can't be padded (the pad would land inside a head's
# columns), so those must be 128*tp-aligned by the model itself — true for
# every published model at any tp the kv-head constraint allows. The FFN
# hidden axis and the vocab CAN be padded:
#
# * w1/w3 output and w2 input pad to the SAME lcm(K_MULTIPLE, 128*tp) width,
#   so the gathered hidden activation feeds w2 with no slicing; the pad
#   columns/rows carry zero scales and contribute exactly 0.
# * sharded wcls pads its vocab axis; the forward slices logits back to
#   vocab_size after the gather (zero logits in the pad would otherwise win
#   an argmax over negative real logits).
# ---------------------------------------------------------------------------


def ffn_padded_width(cfg: ModelConfig, kind: str, n_tp: int) -> int:
    return _pad_up(cfg.hidden_dim, math.lcm(K_MULTIPLE[kind], 128 * n_tp))


def _pad_axis(arr, axis: int, target: int):
    if arr.ndim == 0 or arr.shape[axis] in (0, target):
        return arr
    xp = np if isinstance(arr, np.ndarray) else jnp
    pad = [(0, 0)] * arr.ndim
    pad[axis] = (0, target - arr.shape[axis])
    return xp.pad(arr, pad)


def _pad_last(arr, target: int):
    return _pad_axis(arr, -1, target)


def _pad_qt_out(qt: QuantTensor, target_o: int) -> QuantTensor:
    return QuantTensor(
        w=_pad_last(qt.w, target_o), s=_pad_last(qt.s, target_o),
        s2=_pad_last(qt.s2, target_o), kind=qt.kind, k_logical=qt.k_logical,
    )


def _pad_qt_in(qt: QuantTensor, target_k: int) -> QuantTensor:
    """Extend the packed K axis with zero-scale rows (inert: zero scales x
    anything = 0), e.g. w2's input to the padded FFN width."""
    if qt.kind == "q40":
        w = _pad_axis(qt.w, -2, target_k // 2)
        s = _pad_axis(qt.s, -2, target_k // 64)
        s2 = _pad_axis(qt.s2, -2, target_k // 64)
    else:
        w = _pad_axis(qt.w, -2, target_k)
        s = _pad_axis(qt.s, -2, target_k // 32)
        s2 = qt.s2
    return QuantTensor(w=w, s=s, s2=s2, kind=qt.kind, k_logical=qt.k_logical)


def row_shard_quant_leaf(name: str, leaf: QuantTensor, cfg: ModelConfig,
                         n_tp: int) -> QuantTensor:
    """Re-pack ``wo``/``w2`` for row-parallel (K-sharded) execution: slice
    the packed planes into ``n_tp`` K-chunks along the LOGICAL input rows,
    pad each chunk's K to ``K_MULTIPLE`` independently with inert zero-scale
    rows, and concatenate the repacked chunks back along the packed-K axis.
    The global planes carry ``n_tp * kp_shard`` K rows sharded with
    ``_row_shard_spec``, so under shard_map every device sees a standard
    stacked QuantTensor of its own chunk — same Mosaic tiling as an
    unsharded pack — with ``k_logical`` set to the LOCAL chunk width the
    sharded activation actually has. Idempotent (a repacked leaf passes
    through), like the other prepare helpers."""
    kind = leaf.kind
    chunk = row_shard_chunk_k(cfg, name, kind, n_tp)
    gran = ROW_SHARD_GRANULARITY[kind]
    if chunk % gran:
        raise ValueError(
            f"row-parallel {name}: per-shard K {chunk} is not a multiple of "
            f"the {kind} scale-plane granularity {gran} — the K slice would "
            f"split a superblock (use validate_tp_reduce to gate)")
    kp_shard = _pad_up(chunk, K_MULTIPLE[kind])
    if leaf.k_logical == chunk and leaf.k_padded == n_tp * kp_shard:
        return leaf
    if name == "w2":
        # align to the padded hidden width first so chunk boundaries match
        # the w1/w3 output shards (idempotent when already padded)
        leaf = _pad_qt_in(leaf, ffn_padded_width(cfg, kind, n_tp))

    def repack(plane, per):  # ``per`` = logical K rows per plane row
        xp = np if isinstance(plane, np.ndarray) else jnp
        parts = [
            _pad_axis(plane[..., i * chunk // per:(i + 1) * chunk // per, :],
                      -2, kp_shard // per)
            for i in range(n_tp)
        ]
        return xp.concatenate(parts, axis=-2)

    if kind == "q40":
        return QuantTensor(w=repack(leaf.w, 2), s=repack(leaf.s, 64),
                           s2=repack(leaf.s2, 64), kind=kind, k_logical=chunk)
    return QuantTensor(w=repack(leaf.w, 1), s=repack(leaf.s, 32),
                       s2=leaf.s2, kind=kind, k_logical=chunk)


def prepare_quant_leaf(name: str, leaf, cfg: ModelConfig, n_tp: int,
                       tp_reduce: bool = False):
    """Lane-align one param leaf for tp-sharded Pallas execution (see above).
    Identity for dense arrays, unsharded matrices, and already-aligned dims.
    ``tp_reduce=True`` re-packs wo/w2 per K-shard for the row-parallel
    reduce path instead of the output-axis treatment."""
    if not isinstance(leaf, QuantTensor) or n_tp <= 1:
        return leaf
    if tp_reduce and name in ROW_SHARDED_MATRICES:
        return row_shard_quant_leaf(name, leaf, cfg, n_tp)
    if name in ("w1", "w3", "moe_up", "moe_gate"):
        return _pad_qt_out(leaf, ffn_padded_width(cfg, leaf.kind, n_tp))
    if name in ("w2", "moe_down"):
        return _pad_qt_in(leaf, ffn_padded_width(cfg, leaf.kind, n_tp))
    if name == "wcls" and cfg.vocab_size % n_tp == 0:
        return _pad_qt_out(leaf, _pad_up(cfg.vocab_size, 128 * n_tp))
    return leaf


def _row_shard_spec(arr) -> P:
    """Shard the packed-K (second-to-last) axis over tp; empty placeholder
    planes (q80's s2) replicate."""
    if arr.ndim < 2 or arr.shape[-1] == 0 or arr.shape[-2] == 0:
        return P(*([None] * arr.ndim))
    spec = [None] * arr.ndim
    spec[-2] = TP
    return P(*spec)


def leaf_specs(leaf, sharded: bool, row: bool = False):
    """PartitionSpec(s) for one param leaf — a QuantTensor gets a spec per
    plane (same treedef), a plain array a single spec. ``row=True`` shards
    the packed-K axis (a ``row_shard_quant_leaf``-repacked wo/w2) instead of
    the output axis."""
    mk = (_row_shard_spec if row
          else _out_shard_spec if sharded else _replicated_spec)
    if isinstance(leaf, QuantTensor):
        return QuantTensor(
            w=mk(leaf.w), s=mk(leaf.s), s2=mk(leaf.s2),
            kind=leaf.kind, k_logical=leaf.k_logical,
        )
    return mk(leaf)


def quant_param_specs(params: dict, cfg: ModelConfig, n_tp: int,
                      tp_reduce: bool = False) -> dict:
    """Leaf-level PartitionSpec tree matching ``params`` (QuantTensor fields
    get their own specs). Quantized matrices and the dense big matrices are
    output-sharded; norms/embedding are replicated (the root holds them whole
    in the reference too). ``wcls`` is sharded only when tp divides vocab.
    ``tp_reduce``: wo/w2 K-shard instead (quantized leaves only — a dense
    wo/w2 stays output-sharded, the Engine declines row mode there)."""
    validate_quant_tp(cfg, n_tp)
    shard_wcls = cfg.vocab_size % n_tp == 0

    def _row(name, leaf):
        return (tp_reduce and name in ROW_SHARDED_MATRICES
                and isinstance(leaf, QuantTensor))

    specs: dict = {
        "embedding": _replicated_spec(params["embedding"]),
        "rms_final": _replicated_spec(params["rms_final"]),
        "wcls": leaf_specs(params["wcls"], shard_wcls),
        "layers": {
            name: leaf_specs(leaf, name in SHARDED_MATRICES,
                             row=_row(name, leaf))
            for name, leaf in params["layers"].items()
        },
    }
    return specs


def prepare_quant_params(params: dict, cfg: ModelConfig, n_tp: int,
                         tp_reduce: bool = False) -> dict:
    """Lane-align every leaf (idempotent: already-padded leaves pass through)."""
    return {
        "embedding": params["embedding"],
        "rms_final": params["rms_final"],
        "wcls": prepare_quant_leaf("wcls", params["wcls"], cfg, n_tp),
        "layers": {
            k: prepare_quant_leaf(k, v, cfg, n_tp, tp_reduce=tp_reduce)
            for k, v in params["layers"].items()
        },
    }


def shard_quant_params(params: dict, mesh, cfg: ModelConfig,
                       tp_reduce: bool = False) -> dict:
    """Place a (possibly quantized) param pytree onto the mesh output-sharded,
    lane-aligning shardable axes first (see the padding notes above).
    ``tp_reduce=True`` re-packs and K-shards wo/w2 for row-parallel mode."""
    n_tp = mesh.shape[TP]
    params = prepare_quant_params(params, cfg, n_tp, tp_reduce=tp_reduce)
    specs = quant_param_specs(params, cfg, n_tp, tp_reduce=tp_reduce)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )


def batch_cache_spec() -> P:
    # [L, B, S, n_kv_heads, head_size] — shard kv heads, batch replicated
    return P(None, None, None, TP, None)


def _make_tp_program(cfg: ModelConfig, mesh, params: dict, compress: bool,
                     inner_fn, cache_spec_fn, tp_reduce=None):
    """THE shard_map builder behind every quantized-TP program — solo
    decode/prefill, batched decode, batched spec-verify. One place for the
    in/out specs, the vocab-divisibility gather_logits condition, and the
    check_vma setting, so the three entry points can never drift.
    ``inner_fn(cfg, params, rope, tokens, cache, pos, *, tp_axis,
    gather_logits, tp_compress, tp_reduce)`` is the llama forward variant;
    ``cache_spec_fn`` its cache PartitionSpec ([L,S,...] vs [L,B,S,...]).
    ``tp_reduce`` (None | 'plain' | 'q80') runs wo/w2 row-parallel — the
    params must have been sharded with ``tp_reduce=True``."""
    n_tp = mesh.shape[TP]
    pspecs = quant_param_specs(params, cfg, n_tp, tp_reduce=bool(tp_reduce))
    gather_logits = cfg.vocab_size % n_tp == 0
    cspec = {"k": cache_spec_fn(), "v": cache_spec_fn()}

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(pspecs, P(), cspec, P(), P()),
        out_specs=(P(), cspec),
        check_vma=False,
    )
    def fwd(params, rope, cache, tokens, pos):
        return inner_fn(
            cfg, params, rope, tokens, cache, pos,
            tp_axis=TP, gather_logits=gather_logits, tp_compress=compress,
            tp_reduce=tp_reduce,
        )

    return fwd


def make_tp_forward_batched(cfg: ModelConfig, mesh, params: dict,
                            compress: bool = False, overlap: bool = False,
                            overlap_ring: bool = True, tp_reduce=None):
    """``fwd(params, rope, cache, tokens, pos) -> (logits, cache)`` for the
    BATCHED decode step (``llama.forward_batched``: tokens/pos are [B]) as a
    shard_map program over the same output-sharded quant planes as
    ``make_tp_forward`` — multi-chip batched serving, B sequences sharing
    every local weight stream AND every ICI gather.

    ``overlap=True`` builds the two-microbatch compute/communication
    overlap variant (``llama.forward_batched_overlap`` — bit-identical,
    needs B >= 2 and a dense FFN); ``overlap_ring`` picks ppermute ring
    gathers vs fused all-gathers + XLA latency hiding. ``tp_reduce``
    (None | 'plain' | 'q80') row-parallelizes wo/w2 (see _make_tp_program);
    it composes with overlap — each microbatch's reduce-scatters are ring
    hops already, so they interleave with the other microbatch's compute
    exactly like the ring gathers do."""
    from dllama_tpu.models import llama

    inner = (partial(llama.forward_batched_overlap, ring=overlap_ring)
             if overlap else llama.forward_batched)
    return _make_tp_program(cfg, mesh, params, compress,
                            inner, batch_cache_spec, tp_reduce=tp_reduce)


def make_tp_verify_batched(cfg: ModelConfig, mesh, params: dict,
                           compress: bool = False, overlap: bool = False,
                           overlap_ring: bool = True, tp_reduce=None):
    """``fwd(params, rope, cache, tokens, pos) -> (logits, cache)`` for the
    BATCHED speculative-verify step (``llama.forward_batched_verify``:
    tokens [B, T], pos [B]) as a shard_map program over the same
    output-sharded quant planes — batched speculation under tensor
    parallelism: draft_len+1 positions x B rows share every local weight
    stream AND every ICI gather per launch. ``overlap``/``overlap_ring``/
    ``tp_reduce`` as in ``make_tp_forward_batched``."""
    from dllama_tpu.models import llama

    inner = (partial(llama.forward_batched_verify_overlap, ring=overlap_ring)
             if overlap else llama.forward_batched_verify)
    return _make_tp_program(cfg, mesh, params, compress,
                            inner, batch_cache_spec, tp_reduce=tp_reduce)


def make_tp_forward(cfg: ModelConfig, mesh, params: dict, compress: bool = False,
                    tp_reduce=None):
    """Build ``fwd(params, rope, cache, tokens, pos) -> (logits, cache)``:
    the quantized-TP decode/prefill forward as one shard_map program.

    Activations/logits are replicated in and out; params carry output shards;
    the KV cache is sharded by kv-head (axis 2). Jit-able and scannable —
    the Engine wraps it exactly like the single-chip ``llama.forward``.

    ``compress=True`` moves the per-layer activation gathers as int8 blocks
    with f32 block scales — the reference's Q80 wire compression
    (``--buffer-float-type q80``) applied to the ICI collectives.
    ``tp_reduce`` (None | 'plain' | 'q80') row-parallelizes wo/w2 (see
    ``_make_tp_program``).
    """
    from dllama_tpu.models import llama

    return _make_tp_program(cfg, mesh, params, compress,
                            llama.forward, cache_spec, tp_reduce=tp_reduce)
