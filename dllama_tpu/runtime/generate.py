"""Decode engine: jitted prefill + single-token decode steps with a resident
KV cache, per-token timing stats, and on-device sampling.

This subsumes the reference's `Inference::infer` loop
(`/root/reference/src/tasks.cpp:199-215`) and the per-token stats surface the
CLI prints (`/root/reference/src/apps/dllama/dllama.cpp:43-92`). Differences
by design, all TPU-motivated:

* The prompt is processed in *batched* prefill (bucketed padded lengths, so a
  handful of compiles serve any prompt) instead of one forward per token.
* One jitted program covers embed -> all layers -> logits -> sample; the host
  sees 4 bytes (the token id) per step, not the logits.
* The KV cache is donated between steps, so XLA updates it in place in HBM.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from functools import partial
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dllama_tpu import faults, observability
from dllama_tpu.models import layer_plan, llama
from dllama_tpu.models.config import ModelConfig
from dllama_tpu.runtime import paged_kv
from dllama_tpu.runtime.sampler import SamplerConfig, sample_dynamic

PREFILL_BUCKETS = (8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 8192)
DECODE_CHUNK = 64  # fused-loop chunk size: one compile serves any steps count

#: sentinel for Engine(metrics=...): "the shared default registry"
DEFAULT_METRICS = object()


class NumericHealthError(RuntimeError):
    """The decode-step watchdog saw non-finite logits (NaN/Inf from corrupt
    weights, a bad kernel, or hardware error). Solo decode fails fast with
    this; a BatchSession quarantines the poisoned row instead (finish reason
    ``"error"``) and the server maps it to a 500 / ``finish_reason:"error"``
    SSE event."""

    def __init__(self, where: str):
        super().__init__(f"non-finite logits detected {where}; "
                         f"output is unusable from this point")
        self.where = where


def prefill_bucket(n: int) -> int:
    for b in PREFILL_BUCKETS:
        if n <= b:
            return b
    return n


def dense_stack_wire_feat_bytes(cfg: ModelConfig, hidden: int,
                                per_feat: float, tp_reduce=None) -> float:
    """Modeled per-row wire bytes (before the (tp-1)/tp ring fraction) the
    dense layer stack's collectives carry in one forward — the analytic
    model Engine._wire_bytes and BENCH_REDUCE share, so the benchmark's
    reported delta IS the serving model's delta.

    Gather-only: 4 all-gathers per layer (heads, wo out, padded hidden,
    w2 out) at ``per_feat`` bytes/feature (1.125 under q80 wire
    compression).  Row-parallel (``tp_reduce``): per layer 2 normalized
    gathers (dim each, still ``per_feat``) + 2 reduce-scatters (dim each —
    f32 partials at 4 B/feature, or 1.125 under q80 hop compression) + 2
    scalar f32 psums for the fused rmsnorm, plus one extra final-norm
    gather and psum per forward.  The hidden-width gather — the widest
    collective of the gather-only schedule — disappears entirely."""
    if not tp_reduce:
        return cfg.n_layers * (3 * cfg.dim + hidden) * per_feat
    red_feat = 1.125 if tp_reduce == "q80" else 4.0
    gather_feats = (2 * cfg.n_layers + 1) * cfg.dim
    reduce_feats = 2 * cfg.n_layers * cfg.dim
    psum_scalars = (2 * cfg.n_layers + 1) * 4.0
    return (gather_feats * per_feat + reduce_feats * red_feat + psum_scalars)


@dataclasses.dataclass
class TokenStats:
    """Per-token timing — the reference's G/I/T/S/R line
    (`/root/reference/src/utils.cpp:179-182`, socket counters
    `/root/reference/src/socket.cpp:266-271`, printed at
    `/root/reference/src/apps/dllama/dllama.cpp:74-75`), re-based on what the
    boundaries actually are on TPU:

    * ``generation_ms`` (G): total wall time for the token.
    * ``inference_ms`` (I): time spent waiting on the device program — the
      on-chip compute (including, under TP, the ICI collectives XLA fused in).
    * ``transfer_ms`` (T): G - I — host work + dispatch/launch latency, the
      host<->device round trip that replaces the reference's Ethernet hops.
    * ``sent_kb`` / ``recv_kb`` (S/R): per-device ICI bytes this token's
      collectives move. The reference reads socket counters; under SPMD the
      collective schedule is static, so these are computed analytically
      (ring all-gather: each device sends and receives (tp-1)/tp of every
      gathered feature vector — see Engine._wire_bytes_per_token).
    """

    generation_ms: float
    inference_ms: float
    transfer_ms: float = 0.0
    sent_kb: float = 0.0
    recv_kb: float = 0.0


@dataclasses.dataclass
class Session:
    """Conversation state carried across generate() calls (chat mode).

    ``pending_token`` is the last sampled token, which has NOT yet been fed
    through the model — the next call must consume it first so the KV cache
    sees every conversation token exactly once (the reference feeds every
    sampled token back through ``infer``, including EOS —
    `/root/reference/src/apps/dllama/dllama.cpp:152-166`).
    """

    cache: dict
    pos: int
    pending_token: Optional[int] = None


class Engine:
    """Holds device-resident params + cache and the compiled step functions."""

    def __init__(
        self,
        cfg: ModelConfig,
        params: dict,
        sampler_cfg: SamplerConfig = SamplerConfig(),
        cache_dtype=jnp.float32,
        mesh=None,
        fuse_quant: bool = True,
        tp_compress: bool = False,
        tp_overlap: bool = False,
        tp_reduce: str = "off",
        decode_chunk: int = DECODE_CHUNK,
        numeric_checks: bool = True,
        metrics=DEFAULT_METRICS,
    ):
        """``mesh``: a 1-D ``tp`` Mesh (see parallel.mesh.tp_mesh) to run
        tensor-parallel — params are placed with the reference's row/col
        slicing as NamedShardings and XLA emits the AllReduces the reference
        hand-rolls as broadcast+gather+root-sum.

        ``tp_overlap``: compile microbatch-overlap variants of the batched
        decode / spec-verify TP programs alongside the monolithic ones
        (llama.forward_batched_overlap): the batch splits into two
        half-batches whose per-layer gathers are ring-scheduled
        (collectives.RingAxis) so one microbatch's wire time hides under
        the other's compute. Bit-identical to the monolithic programs; a
        dispatch engages the overlap program only when >= 2 rows are
        resident (see batch_loop/paged_loop/verify_program). Requested but
        unavailable combinations (no mesh, dense-pjit TP, MoE) warn and
        drop to monolithic — ``tp_overlap_active``/``tp_overlap_reason``
        record the resolution machine-visibly (the server surfaces them
        on /stats).

        ``tp_reduce`` ('off' | 'plain' | 'q80'): row-parallel reduce
        direction — wo/w2 K-shard (parallel.quant_tp.row_shard_quant_leaf),
        their full-width f32 partial sums ride a pinned-order ppermute ring
        reduce-scatter (collectives.reduce_scatter_columns; 'q80'
        block-quantizes each hop's payload), and the residual add + rmsnorm
        fold into the scattered shard so the next gather carries
        already-normalized data. 'plain' keeps a deterministic summation
        order (bit-reproducible run to run); 'q80' trades an analytically
        bounded per-hop error for ~3.6x less reduce-direction wire.
        Requested but unavailable combinations (no mesh, dense-pjit TP,
        MoE, shard granularity misfit) warn and drop to the gather-only
        programs — ``tp_reduce_active``/``tp_reduce_reason`` record the
        resolution machine-visibly, like ``tp_overlap``'s. Composes with
        ``tp_overlap``: each microbatch's reduce-scatters are ring hops
        already, so they interleave exactly like the ring gathers.

        ``numeric_checks``: fuse the numeric-health watchdog — an
        ``isfinite(logits)`` per-row flag — into every decode step (plus the
        ``logits:nan`` fault-injection seam). Elementwise over [B, vocab],
        dwarfed by the [vocab, dim] classifier matmul; BENCH_INTEGRITY
        measures the overhead (<1% target). Off only for that A/B.

        ``metrics``: an observability.MetricsRegistry to record prefill /
        decode-chunk wall times, spec-decode acceptance, and watchdog
        quarantines into. Defaults to the shared default registry; pass
        ``None`` to disable all engine telemetry (the BENCH_OBS A/B
        baseline) — the disabled hot path is a single ``is not None``
        check per handle."""
        if decode_chunk < 1:
            raise ValueError(f"decode_chunk must be >= 1, got {decode_chunk}")
        if metrics is DEFAULT_METRICS:
            metrics = observability.default_registry()
        self.metrics = metrics
        if metrics is not None:
            self._m_prefill = metrics.histogram(
                "dllama_prefill_ms", "Prompt prefill wall time per request")
            self._m_step = metrics.histogram(
                "dllama_decode_step_ms",
                "Per-token decode wall time (solo streaming path)")
            self._m_chunk = metrics.histogram(
                "dllama_decode_chunk_ms",
                "Fused decode-chunk wall time (fused/batched/pooled paths)")
            self._m_prefill_chunk = metrics.histogram(
                "dllama_prefill_chunk_ms",
                "Incremental prefill chunk wall time (chunked admission)")
            self._m_prefill_tokens = metrics.counter(
                "dllama_prefill_tokens_total",
                "Prompt tokens written to a pool row's KV by the way they "
                "got there: how=\"ride\" beside the decode rows of a pooled "
                "decode launch, how=\"piece\" by a standalone prefill piece",
                labelnames=("how",))
            self._m_ride_slots = metrics.counter(
                "dllama_ride_slots_total",
                "Prompt-token slots (steps x width) of the pooled decode "
                "launches that carried a riding prompt; "
                "dllama_prefill_tokens_total{how=\"ride\"} of them were "
                "filled, the rest was padding")
            self._m_live_rows = metrics.histogram(
                "dllama_decode_live_rows",
                "Rows a pooled decode launch advances (live: resident, "
                "prefilled, not done), one observation per launch",
                buckets=observability.TOKEN_BUCKETS)
            self._m_migrations = metrics.counter(
                "dllama_kv_migrations_total",
                "Pooled rows migrated to the next larger KV bucket")
            self._m_ahead = metrics.counter(
                "dllama_decode_ahead_total",
                "Pooled decode launches by what the launch before them was "
                "doing at their dispatch: state=\"queued\" still running (the "
                "new one waits behind it), \"late\" already ended (the "
                "device idled), \"idle\" none in flight",
                labelnames=("state",))
            self._m_overrun = metrics.counter(
                "dllama_decode_overrun_rows_total",
                "Rows a pooled decode launch carried one chunk past a stop "
                "token or a quarantine that the launch before it found "
                "(their tokens are dropped)")
            self._m_quarantine = metrics.counter(
                "dllama_numeric_quarantines_total",
                "Rows/streams stopped by the numeric-health watchdog")
            self._m_spec_steps = metrics.counter(
                "dllama_spec_verify_steps_total",
                "Speculative-decode verify launches")
            self._m_spec_accepted = metrics.counter(
                "dllama_spec_drafts_accepted_total",
                "Draft tokens accepted by speculative verify")
            self._m_spec_emitted = metrics.counter(
                "dllama_spec_tokens_emitted_total",
                "Tokens emitted by speculative decode paths")
            self._m_prefix_hits = metrics.counter(
                "dllama_prefix_cache_hits_total",
                "Paged admissions that aliased at least one cached KV page")
            self._m_prefix_misses = metrics.counter(
                "dllama_prefix_cache_misses_total",
                "Paged admissions with no cached prefix page to alias")
            self._m_prefix_tokens = metrics.counter(
                "dllama_prefix_tokens_matched_total",
                "Prompt tokens served from the radix prefix cache instead "
                "of being re-prefilled")
            self._m_cow = metrics.counter(
                "dllama_kv_cow_copies_total",
                "Boundary KV pages copied (copy-on-write) at paged admission")
            self._m_prefix_evictions = metrics.counter(
                "dllama_prefix_evictions_total",
                "Refcount-zero prefix-cache pages evicted (LRU) to satisfy "
                "an allocation")
            self._m_overlap = metrics.counter(
                "dllama_tp_overlap_chunks_total",
                "Decode/verify dispatches routed through the microbatch "
                "compute/communication-overlap TP programs")
            self._m_reduce = metrics.counter(
                "dllama_tp_reduce_chunks_total",
                "Decode/verify dispatches served by the row-parallel "
                "(K-sharded wo/w2, ring reduce-scatter) TP programs")
            # a model with a layer plan only (models/layer_plan.py): what the
            # pooled decode step's live rows routed to, and the pools' KV
            self._m_moe_picks = metrics.counter(
                "dllama_moe_picks_total",
                "Expert picks of the pooled decode step's live rows, by "
                "whether the picked expert is held here (held=\"1\") or on "
                "another chip of the deployment (held=\"0\")",
                labelnames=("held",))
            self._m_moe_active = metrics.counter(
                "dllama_moe_active_experts_total",
                "Distinct held experts the live rows picked, summed over "
                "expert layers and decode steps")
            self._m_moe_reads = metrics.counter(
                "dllama_moe_expert_reads_total",
                "Held experts whose planes the pooled decode step read, "
                "summed over expert layers and decode steps (the trip "
                "counts of the expert loop; every held expert where an "
                "expert layer runs them all)")
            self._m_moe_layer_steps = metrics.counter(
                "dllama_moe_layer_steps_total",
                "Expert layers x decode steps the three counters above were "
                "summed over (a chunk adds its steps x the expert layers)")
            self._m_moe_groups = metrics.counter(
                "dllama_moe_group_picks_total",
                "Expert groups a grouped router (n_group > 1) chose for the "
                "pooled decode step's live rows (topk_group a row, an expert "
                "layer and a step), by whether the group holds experts held "
                "here (held=\"1\")",
                labelnames=("held",))
            self._m_kv_resident = metrics.gauge(
                "dllama_kv_resident_bytes",
                "Bytes of the slot pools' KV caches by attention kind "
                "(kind=\"full\": grows with the slab's context; "
                "kind=\"window\": rings, the same at any context; "
                "kind=\"latent\": one shared vector a token a latent layer)",
                labelnames=("kind",))
            self._m_latent_live = metrics.counter(
                "dllama_kv_latent_live_slots_total",
                "Latent cache slots that held a position the query saw, "
                "summed over the pooled decode step's live rows, steps and "
                "latent layers: position + 1 a row a step a layer")
            self._m_latent_scored = metrics.counter(
                "dllama_kv_latent_scored_slots_total",
                "Latent cache slots the latent attention scored for those "
                "rows, steps and layers: a step reads the slab as far as its "
                "longest live row reaches, up to a rung of 1024, 2048, ... "
                "the slab's context")
            self._m_ring_live = metrics.counter(
                "dllama_kv_ring_live_slots_total",
                "Ring slots that held a position the query saw, summed over "
                "the pooled decode step's live rows, steps and window "
                "layers: min(position + 1, window) a row a step a layer")
            self._m_ring_scored = metrics.counter(
                "dllama_kv_ring_scored_slots_total",
                "Ring slots the window attention scored for those rows, "
                "steps and layers: a step reads the rings as far as its "
                "longest live row reaches, up to a rung of 1024, 2048, ... "
                "ModelConfig.ring_slots (the whole ring once one has wrapped)")
        else:
            self._m_ring_live = self._m_ring_scored = None
            self._m_latent_live = self._m_latent_scored = None
            self._m_moe_picks = self._m_moe_active = self._m_moe_groups = None
            self._m_moe_reads = self._m_moe_layer_steps = None
            self._m_kv_resident = None
            self._m_prefill = self._m_step = self._m_chunk = None
            self._m_prefill_chunk = self._m_migrations = None
            self._m_ahead = self._m_overrun = None
            self._m_live_rows = None
            self._m_prefill_tokens = self._m_ride_slots = None
            self._m_quarantine = None
            self._m_spec_steps = self._m_spec_accepted = None
            self._m_spec_emitted = None
            self._m_prefix_hits = self._m_prefix_misses = None
            self._m_prefix_tokens = self._m_cow = None
            self._m_prefix_evictions = self._m_overlap = None
            self._m_reduce = None
        self.cfg = cfg
        self.sampler_cfg = sampler_cfg
        self.mesh = mesh
        if mesh is not None:
            cfg.refuse_for_plan("a tensor-parallel engine (--tp > 1)")
        self.numeric_checks = numeric_checks
        self._tp_compress = tp_compress
        #: machine-visible wire/overlap resolution (served on /stats):
        #: ``tp_wire`` is what actually crosses the interconnect per gather,
        #: ``tp_overlap_active``/``tp_overlap_reason`` say whether the
        #: microbatch-overlap programs were built and, if not, why the
        #: request was dropped (warn-and-drop, never an error).
        self.tp_wire = "plain"
        self.tp_overlap_active = False
        self.tp_overlap_reason = ("not requested" if not tp_overlap
                                  else "no mesh (single device)")
        if tp_reduce in (None, "off"):
            tp_reduce = None
        elif tp_reduce not in ("plain", "q80"):
            raise ValueError(f"tp_reduce must be 'off', 'plain' or 'q80', "
                             f"got {tp_reduce!r}")
        #: row-parallel reduce-direction resolution, same warn-and-drop
        #: contract as tp_overlap above: ``tp_reduce`` is the resolved mode
        #: ('off' when dropped), active/reason the machine-visible why
        self.tp_reduce = "off"
        self.tp_reduce_active = False
        self.tp_reduce_reason = ("not requested" if tp_reduce is None
                                 else "no mesh (single device)")
        #: decode kernel-fusion resolution, machine-visible like the TP
        #: wire above: what each DLLAMA_* fusion flag resolved to on THIS
        #: engine (served on /stats), so a flag that silently declined —
        #: dense weights, dense-pjit TP — shows up without log scraping
        from dllama_tpu.ops import flash_decode as _flash
        from dllama_tpu.ops import fused_rope_cache as _frc
        from dllama_tpu.ops import qmatmul as _qm
        from dllama_tpu.parallel.quant_tp import has_quant_leaves as _hql

        self.kernel_fusions = {
            "flash_decode": "on" if _flash.flash_enabled() else "off",
            "fuse_norm": (
                "off" if not _qm.norm_fusion_enabled()
                else "on" if _hql(params)
                else "requested (dense weights: no quant projection "
                     "epilogue to fuse into)"),
            "fuse_rope_cache": "on" if _frc.fuse_enabled() else "off",
        }
        # fused-loop chunk: one host round trip per chunk of tokens. Bigger
        # chunks amortize dispatch/sync latency at the cost of coarser
        # streaming granularity.
        self.decode_chunk = decode_chunk
        fwd = llama.forward
        fwd_b = llama.forward_batched
        fwd_v = llama.forward_batched_verify
        # prefill-only forward computing the lm_head at ONE row (see
        # llama.forward last_pos): at a 128k vocab the [bucket, vocab]
        # classifier matmul dwarfs the single row prefill consumes. None on
        # the quant-TP path — its shard_map wrappers carry a fixed signature
        # and the vocab-sharded gather wants the full [T, vocab] layout.
        fwd_last = llama.forward
        #: generate_batch_spec availability: single mesh, or quant-TP
        #: shard_map (the dense-pjit mesh path has no verify wrapper)
        self.supports_batch_spec = True
        self._batch_cache_sharding = None
        # microbatch-overlap forward variants (quant-TP shard_map only);
        # stay None when the overlap programs are unavailable or unwanted
        fwd_b_ov = fwd_v_ov = None
        if mesh is not None:
            from dllama_tpu.parallel import quant_tp, sharding as _sh
            from jax.sharding import NamedSharding

            if quant_tp.has_quant_leaves(params):
                # quantized weights x TP: pallas kernels don't auto-partition
                # under pjit, so the forward runs as a shard_map program over
                # output-sharded quant planes (parallel.quant_tp)
                red = None
                if tp_reduce is not None:
                    from dllama_tpu.parallel.mesh import TP as _TP

                    kind = next(
                        (leaf.kind for leaf in jax.tree.leaves(
                            params,
                            is_leaf=lambda x: hasattr(x, "kind"))
                         if hasattr(leaf, "kind")), "q40")
                    why = quant_tp.validate_tp_reduce(
                        cfg, kind, mesh.shape[_TP])
                    if why is not None:
                        self.tp_reduce_reason = why
                        import sys as _sys

                        print(f"dllama: tp_reduce requested but declined "
                              f"({why}); gather-only TP programs used",
                              file=_sys.stderr, flush=True)
                    else:
                        red = tp_reduce
                        self.tp_reduce = tp_reduce
                        self.tp_reduce_active = True
                        self.tp_reduce_reason = "on"
                self.params = quant_tp.shard_quant_params(
                    params, mesh, cfg, tp_reduce=red is not None)
                tp_fwd = quant_tp.make_tp_forward(
                    cfg, mesh, self.params, compress=tp_compress,
                    tp_reduce=red
                )
                tp_fwd_b = quant_tp.make_tp_forward_batched(
                    cfg, mesh, self.params, compress=tp_compress,
                    tp_reduce=red
                )
                tp_fwd_v = quant_tp.make_tp_verify_batched(
                    cfg, mesh, self.params, compress=tp_compress,
                    tp_reduce=red
                )
                if tp_compress:
                    self.tp_wire = "q80"
                if tp_overlap:
                    if cfg.is_moe:
                        # the MoE decode's selected-experts union spans all
                        # rows (llama._check_overlap_split) — a half-batch
                        # would change which experts load
                        self.tp_overlap_reason = (
                            "moe: selected-experts union spans rows")
                        import sys as _sys

                        print("dllama: tp_overlap requested but the model "
                              "is MoE — the selected-experts union spans "
                              "all rows, so the microbatch split is not "
                              "exact; monolithic TP programs used",
                              file=_sys.stderr, flush=True)
                    else:
                        tp_fwd_b_ov = quant_tp.make_tp_forward_batched(
                            cfg, mesh, self.params, compress=tp_compress,
                            overlap=True, tp_reduce=red,
                        )
                        tp_fwd_v_ov = quant_tp.make_tp_verify_batched(
                            cfg, mesh, self.params, compress=tp_compress,
                            overlap=True, tp_reduce=red,
                        )

                        def fwd_b_ov(cfg_, params_, rope_, tokens_, cache_,
                                     pos_):
                            return tp_fwd_b_ov(params_, rope_, cache_,
                                               tokens_, pos_)

                        def fwd_v_ov(cfg_, params_, rope_, tokens_, cache_,
                                     pos_):
                            return tp_fwd_v_ov(params_, rope_, cache_,
                                               tokens_, pos_)

                        self.tp_overlap_active = True
                        self.tp_overlap_reason = "on"

                fwd_last = None

                def fwd(cfg_, params_, rope_, tokens_, cache_, pos_):
                    return tp_fwd(params_, rope_, cache_, tokens_, pos_)

                def fwd_b(cfg_, params_, rope_, tokens_, cache_, pos_):
                    return tp_fwd_b(params_, rope_, cache_, tokens_, pos_)

                def fwd_v(cfg_, params_, rope_, tokens_, cache_, pos_):
                    return tp_fwd_v(params_, rope_, cache_, tokens_, pos_)

            else:
                self.supports_batch_spec = False
                if tp_reduce is not None:
                    self.tp_reduce_reason = (
                        "dense-pjit TP path (row-parallel reduce needs the "
                        "shard_map quant path's K-sharded packs)")
                    import sys as _sys

                    print("dllama: tp_reduce requested but the params are "
                          "dense — the row-parallel programs ride the "
                          "shard_map quant-TP path; gather-only pjit used",
                          file=_sys.stderr, flush=True)
                if tp_overlap:
                    self.tp_overlap_reason = (
                        "dense-pjit TP path (overlap needs the shard_map "
                        "quant path)")
                    import sys as _sys

                    print("dllama: tp_overlap requested but the params are "
                          "dense — the microbatch-overlap programs ride the "
                          "shard_map quant-TP path; monolithic pjit used",
                          file=_sys.stderr, flush=True)
                # dense pjit: forward_batched partitions like forward (the
                # per-row vmap'd attention shards by kv head unchanged).
                # allow_flash=False — GSPMD cannot partition a Pallas custom
                # call, so routing this path into the flash kernel would
                # compile it replicated against an all-gathered cache,
                # destroying the TP scaling the mesh exists for; only the
                # shard_map (quant) path may take flash under a mesh
                self.params = _sh.shard_params(params, mesh, cfg)
                from dllama_tpu.ops.flash_decode import flash_enabled

                if flash_enabled():
                    import sys as _sys

                    print("dllama: DLLAMA_FLASH_DECODE=1 ignored on the "
                          "dense-pjit TP path (Pallas calls don't partition "
                          "under pjit); dense attention used — quantized "
                          "weights take flash under TP via shard_map",
                          file=_sys.stderr, flush=True)
                    self.kernel_fusions["flash_decode"] = (
                        "requested (dense-pjit TP: Pallas calls don't "
                        "partition under pjit)")

                def fwd(cfg_, params_, rope_, tokens_, cache_, pos_):
                    return llama.forward(cfg_, params_, rope_, tokens_,
                                         cache_, pos_, allow_flash=False)

                fwd_last = partial(llama.forward, allow_flash=False)

                def fwd_b(cfg_, params_, rope_, tokens_, cache_, pos_):
                    return llama.forward_batched(cfg_, params_, rope_,
                                                 tokens_, cache_, pos_,
                                                 allow_flash=False)
            self._cache_sharding = NamedSharding(mesh, _sh.cache_spec())
            self._batch_cache_sharding = NamedSharding(
                mesh, quant_tp.batch_cache_spec())
        else:
            from dllama_tpu.parallel.quant_tp import has_quant_leaves

            if fuse_quant and has_quant_leaves(params):
                # fewer, larger fused kernels per layer (exact same math).
                # NOTE: if the leaves are already device-resident, the concat
                # transiently holds originals + fused copies; models near HBM
                # capacity should load pre-fused on host instead
                # (llama.quant_params_from_reader fuse=True does exactly that)
                params = llama.fuse_qkv_ffn(params)
            # a latent layer's W_ukv in float32, once (no-op for any other)
            params = layer_plan.load_params(cfg, params)
            self.params = jax.tree.map(jnp.asarray, params)
            self._cache_sharding = None
        self.rope = llama.rope_tables(cfg)
        self.cache_dtype = cache_dtype
        self._key = jax.random.PRNGKey(sampler_cfg.seed)
        self._last_prefill_bucket = 1  # rows the latest prefill's gathers moved

        # params/rope MUST be jit arguments, not closure captures: a closed-over
        # sharded array is inlined as a (replicated) constant, silently turning
        # tensor-parallel into full replication with zero collectives.
        # temperature/topp are traced scalars (see sampler.sample_dynamic): one
        # compile serves every per-request sampler setting.
        def _health(logits, poison, ok):
            """Watchdog + fault seam, fused into every decode program: poison
            FIRST (injection must look like a real numeric blowup to the
            check), then fold the row's isfinite flag into ``ok``. Compiles
            to elementwise+reduce over the logits the program already holds."""
            if not numeric_checks:
                return logits, ok
            nan = jnp.asarray(jnp.nan, logits.dtype)
            if logits.ndim == 2 and poison.ndim == 1:  # [B, vocab] rows
                logits = jnp.where(poison[:, None], nan, logits)
                return logits, ok & jnp.all(jnp.isfinite(logits), axis=-1)
            logits = jnp.where(poison, nan, logits)
            return logits, ok & jnp.all(jnp.isfinite(logits))

        def _take_fix(tokens, keys, fix):
            """A slot pool's launch: ``tokens`` and ``keys`` are the last
            launch's own, still on the device; ``fix`` int32 [B, 4] holds
            (flag, token, key's two words as int32 bits) for the rows that
            went live or moved since, which take those instead."""
            new = fix[:, 0] != 0
            tokens = jnp.where(new, fix[:, 1], tokens)
            keys = jnp.where(
                new[:, None],
                jax.lax.bitcast_convert_type(fix[:, 2:], jnp.uint32), keys)
            return tokens, keys

        @partial(jax.jit, donate_argnums=(2,))
        def _decode_step(params, rope, cache, token, pos, key, temp, topp, poison):
            logits, cache = fwd(cfg, params, rope, token[None], cache, pos)
            logits, ok = _health(logits, poison, jnp.bool_(True))
            nxt = sample_dynamic(logits[0], key, temp, topp)
            return nxt, ok, cache

        @partial(jax.jit, donate_argnums=(2,))
        def _prefill(params, rope, cache, padded_tokens, n_tokens, pos):
            # n_tokens is traced (dynamic slice/index) so one compile serves
            # every prompt length within a bucket
            if fwd_last is not None:
                # lm_head at the final prompt row only ([1, vocab]) — the
                # other bucket-1 rows of logits were never read
                logits, cache = fwd_last(cfg, params, rope, padded_tokens,
                                         cache, pos, last_pos=n_tokens - 1)
                return logits[0], cache
            logits, cache = fwd(cfg, params, rope, padded_tokens, cache, pos)
            return jax.lax.dynamic_index_in_dim(logits, n_tokens - 1, keepdims=False), cache

        @partial(jax.jit, donate_argnums=(2,), static_argnames=("n_steps",))
        def _decode_loop(params, rope, cache, token, pos, key, temp, topp,
                         poison, n_steps):
            """N decode steps fused into ONE device program (lax.scan over
            steps, sampling on device). The host sees one dispatch per N
            tokens instead of per token — essential when host<->device launch
            latency rivals the step itself. ``ok`` accumulates the watchdog
            flag across the chunk's steps."""

            def body(carry, _):
                cache, token, pos, key, ok = carry
                key, sub = jax.random.split(key)
                logits, cache = fwd(cfg, params, rope, token[None], cache, pos)
                logits, ok = _health(logits, poison, ok)
                nxt = sample_dynamic(logits[0], sub, temp, topp)
                return (cache, nxt, pos + 1, key, ok), nxt

            (cache, token, pos, key, ok), toks = jax.lax.scan(
                body, (cache, token, pos, key, jnp.bool_(True)), length=n_steps
            )
            return toks, cache, ok

        def _make_decode_loop_batch(fwd_b):
            """Build the fused batched-decode chunk program around one
            batched forward — called twice under tp_overlap (monolithic
            fwd_b and the microbatch-overlap variant) so both programs run
            the byte-identical scan/sampler/watchdog body."""
            if cfg.layer_plan:
                return _make_decode_loop_batch_plan()

            @partial(jax.jit, donate_argnums=(2,),
                     static_argnames=("n_steps",))
            def _decode_loop_batch(params, rope, cache, tokens, pos, keys,
                                   temps, topps, poison, ride=None, fix=None,
                                   *, n_steps):
                """N batched decode steps fused into one program: every step
                streams the weights ONCE for all B sequences
                (llama.forward_batched) and samples each row on device. A row
                whose own context fills before the batch's step budget pins
                at slot seq_len-1 (its later tokens are garbage the caller
                discards); other rows are unaffected — no cross-row
                truncation.

                ``keys`` [B, 2] / ``temps`` [B] / ``topps`` [B]: every row
                runs its OWN sampler chain and settings, split once per step
                exactly like the solo paths' ``key, sub = split(key)`` — a
                sampled row seeded like a solo request emits the solo
                request's exact stream (the server batches mixed-sampler
                requests on this invariant).

                ``ok`` [B] accumulates each row's watchdog flag over the
                chunk; a poisoned row's garbage stays confined to its own row
                (per-row sampling, per-row cache slab) — siblings are
                bit-identical.

                ``ride`` int32 ``[n_steps, t + 3]``: the prompt rides the
                chunk. Step s's line holds ``t`` prompt tokens, then the
                pool row that waits on them, the position of the first, and
                how many of the ``t`` are real (0: nobody rides this step):
                the step carries them beside its decode rows and writes
                their K/V into that row's slab (``llama.forward_batched``);
                the sampler, the watchdog and the outputs see the decode
                rows only. One operand, so that a launch copies one more
                array to the device, not four. A caller without a slot pool
                (a fixed batch, the ``--tp`` wrappers' ``fwd_b``) names no
                riders and traces the program as it was.

                ``fix`` int32 [B, 4]: the slot pool hands the last launch's
                tokens and keys back as they are on the device and names the
                rows that take new ones (``_take_fix``); the program then
                also returns its last tokens, the next launch's. A caller
                that names none traces the program as it was."""
                if fix is not None:
                    tokens, keys = _take_fix(tokens, keys, fix)

                def body(carry, ride_s):
                    cache, toks, pos_, keys_, ok = carry
                    with_ride = {} if ride_s is None else {"ride": (
                        ride_s[:-3], ride_s[-3], ride_s[-2], ride_s[-1])}
                    logits, cache = fwd_b(cfg, params, rope, toks, cache,
                                          pos_, **with_ride)
                    logits, ok = _health(logits, poison, ok)
                    with jax.named_scope("sample"):
                        split = jax.vmap(jax.random.split)(keys_)  # [B, 2, 2]
                        keys_, subs = split[:, 0], split[:, 1]
                        nxt = jax.vmap(sample_dynamic)(
                            logits, subs, temps, topps).astype(jnp.int32)
                    pos_ = jnp.minimum(pos_ + 1, jnp.int32(cfg.seq_len - 1))
                    return (cache, nxt, pos_, keys_, ok), nxt

                (cache, toks, pos, keys, ok), out = jax.lax.scan(
                    body,
                    (cache, tokens, pos, keys,
                     jnp.ones(tokens.shape, jnp.bool_)),
                    ride, length=n_steps,
                )
                if fix is not None:
                    return out, cache, keys, ok, toks
                return out, cache, keys, ok  # out [n_steps, B], ok [B]

            return _decode_loop_batch

        def _make_decode_loop_batch_plan():
            """The pooled decode program of a model with a layer plan: the
            same steps as above, and beside the tokens what the ``live`` rows
            [B] routed to and the expert plane sets read, summed over the
            chunk (``layer_plan.forward_batched``: int32
            [``layer_plan.pick_width``]). Its own
            program, so that a uniform model's keeps its fingerprint; the
            name stays (``jit__decode_loop_batch``)."""

            @partial(jax.jit, donate_argnums=(2,),
                     static_argnames=("n_steps",))
            def _decode_loop_batch(params, rope, cache, tokens, pos, keys,
                                   temps, topps, poison, live, fix=None, *,
                                   n_steps):
                if fix is not None:
                    tokens, keys = _take_fix(tokens, keys, fix)

                def body(carry, _):
                    cache, toks, pos_, keys_, ok, picks = carry
                    logits, cache, got = llama.forward_batched(
                        cfg, params, rope, toks, cache, pos_, live=live)
                    logits, ok = _health(logits, poison, ok)
                    with jax.named_scope("sample"):
                        split = jax.vmap(jax.random.split)(keys_)  # [B, 2, 2]
                        keys_, subs = split[:, 0], split[:, 1]
                        nxt = jax.vmap(sample_dynamic)(
                            logits, subs, temps, topps).astype(jnp.int32)
                    pos_ = jnp.minimum(pos_ + 1, jnp.int32(cfg.seq_len - 1))
                    return (cache, nxt, pos_, keys_, ok, picks + got), nxt

                (cache, toks, pos, keys, ok, picks), out = jax.lax.scan(
                    body,
                    (cache, tokens, pos, keys,
                     jnp.ones(tokens.shape, jnp.bool_),
                     jnp.zeros((layer_plan.pick_width(cfg),), jnp.int32)),
                    length=n_steps,
                )
                if fix is not None:
                    return out, cache, keys, ok, toks, picks
                return out, cache, keys, ok, picks

            def run(params, rope, cache, tokens, pos, keys, temps, topps,
                    poison, n_steps, live=None, fix=None):
                """A caller that names no live rows (a fixed batch) counts
                all of them and gets the four values of a uniform model's
                program; the slot pool names them and gets the picks too
                (after the last tokens, where it hands a ``fix``)."""
                mask = (jnp.ones(tokens.shape, jnp.bool_) if live is None
                        else live)
                out = _decode_loop_batch(params, rope, cache, tokens, pos,
                                         keys, temps, topps, poison, mask,
                                         fix, n_steps=n_steps)
                return out[:4] if live is None else out

            return run

        def _make_decode_loop_paged(fwd_b):
            """The paged twin of _make_decode_loop_batch — same
            two-instantiation contract for the overlap variant."""

            @partial(jax.jit, donate_argnums=(2,),
                     static_argnames=("n_steps",))
            def _decode_loop_paged(params, rope, arena, tables, tokens, pos,
                                   keys, temps, topps, poison, n_steps):
                """N batched decode steps over PAGED KV: the resident cache is
                one arena of fixed-size token pages ``{k,v: [L, P, page, kv,
                hd]}`` and ``tables`` [B, nb] maps each row's logical block b
                to a physical page (scratch page 0 pads unallocated tails).

                Each step gathers every row's pages into a contiguous
                [L, B, nb*page, kv, hd] window — logical position i of the row
                IS window index i, so ``forward_batched`` (rope by pos,
                mask by pos, write-before-attend) runs on it unchanged and the
                math is bit-identical to a bucketed slab of ctx=nb*page — then
                scatters back ONLY the page containing the position this step
                wrote. Aliased (prefix-cache) pages are never the written page:
                a live row writes at pos >= prompt_len-1, strictly past every
                fully-shared block, and pinned/done rows resolve to the scratch
                page. Duplicate scatter indices (several pinned rows on
                scratch) are harmless garbage-on-garbage.

                Sampling/health semantics are _decode_loop_batch's exactly:
                per-row key chains split once per step, per-row watchdog ``ok``
                accumulation, pos clamped at the window's last slot."""
                page = arena["k"].shape[2]
                B, nb = tables.shape
                W = nb * page

                def gather(a):
                    w = jnp.take(a, tables, axis=1)  # [L, B, nb, page, kv, hd]
                    return w.reshape(a.shape[0], B, W, a.shape[3], a.shape[4])

                def body(carry, _):
                    arena, toks, pos_, keys_, ok = carry
                    with jax.named_scope("kv_page_gather"):
                        window = jax.tree.map(gather, arena)
                    logits, window = fwd_b(cfg, params, rope, toks, window, pos_)
                    logits, ok = _health(logits, poison, ok)
                    with jax.named_scope("sample"):
                        split = jax.vmap(jax.random.split)(keys_)
                        keys_, subs = split[:, 0], split[:, 1]
                        nxt = jax.vmap(sample_dynamic)(
                            logits, subs, temps, topps).astype(jnp.int32)
                    wpos = jnp.clip(pos_, 0, W - 1)  # [B] position written
                    blk = wpos // page
                    phys = jnp.take_along_axis(tables, blk[:, None],
                                               axis=1)[:, 0]  # [B]
                    off = blk * page

                    def scat(a, w):
                        # per row: the page-sized slice of the updated window
                        # holding this step's K/V write, back to its arena page
                        pg = jax.vmap(
                            lambda wb, o: jax.lax.dynamic_slice_in_dim(
                                wb, o, page, axis=1),
                            in_axes=(1, 0), out_axes=1)(w, off)
                        return a.at[:, phys].set(pg)  # [L, B, page, kv, hd]

                    with jax.named_scope("kv_page_scatter"):
                        arena = jax.tree.map(scat, arena, window)
                    pos_ = jnp.minimum(pos_ + 1, jnp.int32(W - 1))
                    return (arena, nxt, pos_, keys_, ok), nxt

                (arena, toks, pos, keys, ok), out = jax.lax.scan(
                    body,
                    (arena, tokens, pos, keys,
                     jnp.ones(tokens.shape, jnp.bool_)),
                    length=n_steps,
                )
                return out, arena, keys, ok  # out [n_steps, B], ok [B]

            return _decode_loop_paged

        bsh = (None if self._batch_cache_sharding is None else
               {"k": self._batch_cache_sharding, "v": self._batch_cache_sharding})
        self._batch_cache_init = jax.jit(
            lambda b: llama.init_batch_cache(cfg, b, cache_dtype),
            static_argnums=0, out_shardings=bsh,
        )
        self._bucket_cache_init = jax.jit(
            lambda b, s: llama.init_batch_cache(cfg, b, cache_dtype, seq_len=s),
            static_argnums=(0, 1), out_shardings=bsh,
        )
        self._batch_cache_insert = jax.jit(
            # A single-sequence cache [L, S, kv, hd] into row ``b`` of a slot
            # slab [L, B, ctx, kv, hd]. The slab may be a short-context bucket:
            # only the slab's own context window is copied — by construction
            # the row's prefill never wrote past it (admission places rows in
            # a bucket that covers the prompt).
            lambda bc, c, b: jax.tree.map(
                lambda s, x: jax.lax.dynamic_update_slice(
                    s, jax.lax.slice_in_dim(x, 0, s.shape[2], axis=1)[:, None],
                    (0, b, *(0,) * (s.ndim - 2))), bc, c),
            donate_argnums=0,
        )
        self._bucket_cache_migrate = jax.jit(
            # Row ``sb`` of a small-bucket slab into row ``db`` of the next
            # bucket's slab: the copied prefix is the row's entire attended
            # history (pos < src ctx), positions past it are garbage the row
            # overwrites before attending — migration is exact.
            lambda dst, src, sb, db: jax.tree.map(
                lambda d, s: jax.lax.dynamic_update_slice(
                    d, jax.lax.dynamic_slice_in_dim(s, sb, 1, axis=1),
                    (0, db, *(0,) * (d.ndim - 2))), dst, src),
            donate_argnums=0,
        )
        self._bucket_cache_grow = jax.jit(
            # Carry an exhausted pool's rows into a double-capacity slab
            # (same context): rows keep their indices, the new tail rows are
            # zero/free. src is NOT donated — on allocation failure the pool
            # must survive untouched.
            lambda dst, src: jax.tree.map(
                lambda d, s: jax.lax.dynamic_update_slice(
                    d, s, (0,) * d.ndim), dst, src),
            donate_argnums=0,
        )
        def _pages_to_single(single, arena, pages, ntok):
            """Arena pages ``pages`` [NB] into token positions [0, ntok) of a
            single-sequence staging cache — how a paged admission preloads
            its whole aliased prefix in ONE gather dispatch (it used to loop
            one dispatch per page). ``pages`` may be scratch-padded past the
            prefix (callers pad to a power-of-two count so compiles stay
            O(log max_nb), like the window ladder); the traced ``ntok`` mask
            keeps the padding out of the staging cache."""

            def go(s, a):
                nb, page = pages.shape[0], a.shape[2]
                w = jnp.take(a, pages, axis=1).reshape(
                    a.shape[0], nb * page, a.shape[3], a.shape[4])
                n = min(nb * page, s.shape[1])
                w = jax.lax.slice_in_dim(w, 0, n, axis=1)
                keep = (jnp.arange(n) < ntok)[None, :, None, None]
                head = jax.lax.slice_in_dim(s, 0, n, axis=1)
                return jax.lax.dynamic_update_slice(
                    s, jnp.where(keep, w, head), (0, 0, 0, 0))

            return jax.tree.map(go, single, arena)

        self._pages_to_single = jax.jit(_pages_to_single, donate_argnums=0)

        def _single_to_pages(arena, single, pages, offs):
            """Token blocks [offs[i], offs[i]+page) of a filled staging
            cache into arena pages ``pages[i]`` — a completed prefill's
            fresh tail blocks scattered into the pool in ONE dispatch (the
            staging cache is then dropped). Scratch-padded (page, off=0)
            pairs land harmless garbage on the scratch page, the paged
            decode loop's own duplicate-scatter convention."""

            def go(a, s):
                pg = jax.vmap(
                    lambda o: jax.lax.dynamic_slice(
                        s, (0, o, 0, 0),
                        (s.shape[0], a.shape[2], s.shape[2], s.shape[3]))
                )(offs)  # [M, L, page, kv, hd]
                return a.at[:, pages].set(jnp.moveaxis(pg, 0, 1))

            return jax.tree.map(go, arena, single)

        self._single_to_pages = jax.jit(_single_to_pages, donate_argnums=0)

        def _pages_import(arena, pages, blob):
            """Imported page payloads ``blob`` (leaves [L, M, page, kv, hd]
            — the decoded wire frames of a migrating row) scattered into
            arena pages ``pages`` [M] in ONE dispatch. Scratch-padded
            entries land harmless garbage on the scratch page, like
            _single_to_pages' padding convention."""
            return jax.tree.map(
                lambda a, x: a.at[:, pages].set(x.astype(a.dtype)),
                arena, blob)

        self._pages_import = jax.jit(_pages_import, donate_argnums=0)
        self._page_copy = jax.jit(
            # Arena page ``src`` duplicated into page ``dst``: the
            # copy-on-write boundary — an admission whose prompt ends flush
            # on a cached block takes a private copy of that block (its
            # pending-token position will be rewritten by the first decode
            # step) instead of re-prefilling up to page-1 tokens.
            lambda arena, dst, src: jax.tree.map(
                lambda a: a.at[:, dst].set(
                    jax.lax.dynamic_index_in_dim(a, src, axis=1,
                                                 keepdims=False)), arena),
            donate_argnums=0,
        )

        def _make_verify_batch(fwd_v):
            """Build the batched verify program around one verify forward —
            instantiated for the monolithic and (under tp_overlap) the
            microbatch-overlap variants."""

            @partial(jax.jit, donate_argnums=(2,))
            def _verify_batch(params, rope, cache, tokens, pos):
                """Batched greedy speculative verify: [B, T] candidate rows
                -> every (row, position)'s argmax next token in ONE program —
                the batching and speculation bandwidth wins composed (weights
                stream once for B sequences x T positions). Single mesh or
                quant-TP shard_map (fwd_v resolves to make_tp_verify_batched
                there)."""
                logits, cache = fwd_v(cfg, params, rope, tokens, cache, pos)
                return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

            return _verify_batch

        @partial(jax.jit, donate_argnums=(2,))
        def _verify_step(params, rope, cache, tokens, pos):
            """Speculative verify: feed [pending, draft_1..draft_k] at pos,
            return every position's greedy next token. One device program
            scores k+1 candidate continuations — the MXU sees a T=k+1 batch,
            barely costlier than a single-token step on a bandwidth-bound
            decode (the weights stream once either way)."""
            logits, cache = fwd(cfg, params, rope, tokens, cache, pos)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

        @partial(jax.jit, donate_argnums=(2,))
        def _verify_sampled(params, rope, cache, tokens, pos, keys, temp, topp):
            """Sampled speculative verify: position i gets the token that
            sequential decoding would have SAMPLED with keys[i] — so the
            host-side acceptance (draft matches the sampled choice) yields a
            stream bit-identical to plain sampled decode as long as the key
            chain is replayed faithfully (see generate_spec)."""
            logits, cache = fwd(cfg, params, rope, tokens, cache, pos)
            toks = jax.vmap(
                lambda l, k: sample_dynamic(l, k, temp, topp)
            )(logits, keys)
            return toks.astype(jnp.int32), cache

        self._decode_step = partial(_decode_step, self.params, self.rope)
        self._prefill = partial(_prefill, self.params, self.rope)
        # preallocated watchdog/poison flags: python bools would retrace on
        # value change, and a fresh device array per token is host overhead
        self._flag_false = jnp.zeros((), jnp.bool_)
        self._flag_true = jnp.ones((), jnp.bool_)
        self._no_poison: dict = {}  # B -> cached all-False [B] flags
        self._decode_loop = partial(_decode_loop, self.params, self.rope)
        self._decode_loop_batch = partial(
            _make_decode_loop_batch(fwd_b), self.params, self.rope)
        self._decode_loop_paged = partial(
            _make_decode_loop_paged(fwd_b), self.params, self.rope)
        self._verify_step = partial(_verify_step, self.params, self.rope)
        self._verify_batch = partial(
            _make_verify_batch(fwd_v), self.params, self.rope)
        self._verify_sampled = partial(_verify_sampled, self.params, self.rope)
        # overlap twins of the batched programs: same loop bodies around the
        # microbatch-overlap forwards; None when overlap is inactive. A
        # dispatch picks per call via batch_loop/paged_loop/verify_program.
        self._decode_loop_batch_ov = (
            partial(_make_decode_loop_batch(fwd_b_ov), self.params, self.rope)
            if fwd_b_ov is not None else None)
        self._decode_loop_paged_ov = (
            partial(_make_decode_loop_paged(fwd_b_ov), self.params, self.rope)
            if fwd_b_ov is not None else None)
        self._verify_batch_ov = (
            partial(_make_verify_batch(fwd_v_ov), self.params, self.rope)
            if fwd_v_ov is not None else None)

        # compiled once; materializes the cache already-sharded (allocate-then-
        # reshard would transiently put the FULL cache in one device's HBM,
        # the exact OOM tensor parallelism exists to avoid)
        if self._cache_sharding is not None:
            sh = {"k": self._cache_sharding, "v": self._cache_sharding}
            self._init_cache = jax.jit(
                lambda: llama.init_cache(cfg, cache_dtype), out_shardings=sh
            )
        else:
            self._init_cache = jax.jit(lambda: llama.init_cache(cfg, cache_dtype))

        #: per-device ICI kB one decode step moves (the reference's S/R line)
        self._wire_kb_cache: dict = {}
        self.wire_kb_per_token = self.wire_kb(1)
        #: quant-TP counts ITS OWN collective schedule (exact); the dense
        #: pjit path estimates from XLA's canonical all-reduce lowering —
        #: surfaced so the CLI can mark estimated S/R columns as such
        if mesh is None:
            self.wire_stats_exact = True  # vacuous: no wire traffic at all
        else:
            from dllama_tpu.parallel.quant_tp import has_quant_leaves

            self.wire_stats_exact = has_quant_leaves(self.params)

    def wire_kb(self, rows: int) -> float:
        """Per-device ICI kB a T=rows forward (prefill bucket, spec verify
        batch) moves. NOT simply rows x the decode number: an MoE batch whose
        row union can cover every expert (rows*k >= E) takes the dense-combine
        path and gathers E hidden vectors per row instead of k. Memoized —
        _wire_bytes walks the params pytree, far too slow for the per-batch
        dispatch loop."""
        kb = self._wire_kb_cache.get(rows)
        if kb is None:
            kb = self._wire_kb_cache[rows] = self._wire_bytes(rows) / 1024.0
        return kb

    def _wire_bytes(self, rows: int) -> float:
        """Per-device ICI bytes a T=rows forward's collectives move (0
        without a mesh; rows=1 is a decode step). The reference counts wire
        bytes at its sockets; here the collective schedule is static so the
        count is analytic:

        * quantized TP (shard_map, parallel.quant_tp): dense archs run 4 ring
          all-gathers per layer — attention heads (dim), wo output (dim), FFN
          hidden (lane-padded H'), w2 output (dim); MoE archs swap the FFN
          pair for one H' gather per selected expert (k at decode) plus one
          combined-output gather (dim). Plus the f32 logits gather when the
          vocab shards. A ring all-gather moves (tp-1)/tp of
          the full vector through each device, in each direction. Activations
          travel in cfg dtype; Q80 wire compression (tp_compress) ships
          1 byte + 1/8 byte of scale per feature instead — 1.78x less than
          bf16, 3.56x less than f32 (the reference's 4.06x table is f32 with
          slightly different framing overheads).
        * dense TP (pjit): XLA emits ~2 all-reduces per layer (attention out,
          FFN out), each ~2x(tp-1)/tp of dim per device per direction
          (reduce-scatter + all-gather decomposition).
        """
        if self.mesh is None:
            return 0.0
        from dllama_tpu.parallel.mesh import TP
        from dllama_tpu.parallel.quant_tp import ffn_padded_width, has_quant_leaves

        tp = self.mesh.shape[TP]
        if tp <= 1:
            return 0.0
        cfg = self.cfg
        frac = (tp - 1) / tp
        act_bytes = float(jnp.dtype(cfg.jax_dtype).itemsize)
        if has_quant_leaves(self.params):
            from dllama_tpu.ops.qmatmul import _pad_up

            # q80 wire compression ships 1 int8 + 1/8 B of f32 scale per
            # feature regardless of the activation dtype; plain gathers move
            # activations as-is (bf16 or f32 per --dtype)
            per_feat = 1.125 if self._tp_compress else act_bytes
            kind = "q40"
            for leaf in jax.tree.leaves(
                self.params, is_leaf=lambda x: hasattr(x, "kind")
            ):
                if hasattr(leaf, "kind"):
                    kind = leaf.kind
                    break
            hidden = ffn_padded_width(cfg, kind, tp)
            if cfg.is_moe:
                # expert stacks carry output shards like w1/w2/w3. Per layer
                # and per row: 2 attention gathers (dim each), the hidden
                # gather, one combined-output gather (dim). The hidden
                # gather moves min(E, rows*k) expert hiddens for EVERY row —
                # small batches (rows*k < E) run the selected-experts path
                # whose union caps at rows*k experts, each computed for all
                # rows; bigger batches take the dense combine over all E.
                E, k = cfg.n_experts, cfg.n_active_experts
                layer_feats = cfg.n_layers * (
                    3 * cfg.dim + min(E, rows * k) * hidden
                )
                bytes_ = layer_feats * per_feat
            else:
                bytes_ = dense_stack_wire_feat_bytes(
                    cfg, hidden, per_feat,
                    self.tp_reduce if self.tp_reduce_active else None)
            if cfg.vocab_size % tp == 0:
                # the logits gather moves the lane-PADDED vocab (sliced back
                # after the gather), already cast to f32 and never compressed
                bytes_ += _pad_up(cfg.vocab_size, 128 * tp) * 4.0
            return bytes_ * frac * rows
        # dense pjit path: estimated from XLA's canonical all-reduce lowering
        return cfg.n_layers * 2 * cfg.dim * act_bytes * 2 * frac * rows

    def new_cache(self) -> dict:
        return self._init_cache()

    def _overlap_engaged(self, rows: int) -> bool:
        """One overlap dispatch decision: True routes this call through the
        microbatch-overlap program. Engages only when >= 2 rows are live —
        a lone resident row has no second microbatch to hide wire time
        behind, so it takes the monolithic program (same math either way;
        the overlap twin's static batch split is pool-sized regardless).
        Fires the ``overlap_split`` fault seam and counts the engagement
        (dllama_tp_overlap_chunks_total) so A/B replays and the obs drill
        can prove which program served each chunk."""
        if rows < 2:
            return False
        faults.fire("overlap_split")
        if self._m_overlap is not None:
            self._m_overlap.inc()
        return True

    def _reduce_dispatch(self) -> None:
        """Per-dispatch accounting for the row-parallel reduce direction:
        unlike overlap there is no program choice (row mode rebuilds ALL
        the TP programs), so this fires the ``tp_reduce`` fault seam and
        counts the dispatch (dllama_tp_reduce_chunks_total) — the
        machine-visible proof a replay was actually served by the
        reduce-direction programs, scraped by BENCH_REDUCE."""
        if not self.tp_reduce_active:
            return
        faults.fire("tp_reduce")
        if self._m_reduce is not None:
            self._m_reduce.inc()

    @property
    def pooled_rides(self) -> bool:
        """Whether the pooled decode program takes riding prompt tokens
        (``_decode_loop_batch``'s ``ride``): the uniform models' program on
        one device. A layer plan's pooled program and the ``--tp`` wrappers
        are programs of their own and prefill by standalone pieces."""
        return self.mesh is None and not self.cfg.layer_plan

    def batch_loop(self, rows: int):
        """The fused batched-decode chunk program for a dispatch with
        ``rows`` live rows — the overlap twin when built and engaged,
        else the monolithic program."""
        self._reduce_dispatch()
        if self._decode_loop_batch_ov is not None \
                and self._overlap_engaged(rows):
            return self._decode_loop_batch_ov
        return self._decode_loop_batch

    def paged_loop(self, rows: int):
        """Paged twin of :meth:`batch_loop` (same engagement rule)."""
        self._reduce_dispatch()
        if self._decode_loop_paged_ov is not None \
                and self._overlap_engaged(rows):
            return self._decode_loop_paged_ov
        return self._decode_loop_paged

    def verify_program(self, rows: int):
        """The batched spec-verify program for ``rows`` live rows (see
        :meth:`batch_loop`)."""
        self._reduce_dispatch()
        if self._verify_batch_ov is not None \
                and self._overlap_engaged(rows):
            return self._verify_batch_ov
        return self._verify_batch

    def next_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def _poison_flag(self) -> jax.Array:
        """Scalar ``logits:nan`` fault seam for the solo decode programs."""
        fv = faults.fire("logits")
        if fv is not None and fv["action"] == "nan":
            return self._flag_true
        return self._flag_false

    def _poison_rows(self, B: int) -> jax.Array:
        """[B] ``logits:nan`` fault seam for the batched decode programs —
        ``row=N`` selects which row gets poisoned."""
        flags = self._no_poison.get(B)
        if flags is None:
            flags = self._no_poison[B] = jnp.zeros((B,), jnp.bool_)
        fv = faults.fire("logits")
        if fv is not None and fv["action"] == "nan":
            flags = flags.at[min(max(fv["row"], 0), B - 1)].set(True)
        return flags

    def prefill(self, cache: dict, tokens: list, pos: int = 0,
                chunk: Optional[int] = None) -> tuple:
        """Run the prompt starting at ``pos``. Returns (last_logits, cache).

        Tail-padding to a bucket is safe: padded queries produce garbage
        logits we never read, and padded cache slots sit at positions a
        causal query never attends before a real decode overwrites them.

        ``chunk`` splits the prompt into pieces of at most that many tokens,
        each its own bucketed forward at an advancing ``pos`` into the SAME
        cache. Causal attention reads chunk N-1's K/V exactly as the fused
        forward computed them (every forward writes the cache before
        attending), so the chunked result is bit-identical to the monolithic
        one — the split only bounds how long one dispatch can occupy the
        device while a serving pool has resident rows waiting to decode.
        """
        if not 0 < pos + len(tokens) <= self.cfg.seq_len:
            raise ValueError(
                f"prompt of {len(tokens)} tokens at pos {pos} exceeds seq_len {self.cfg.seq_len}"
            )
        faults.fire("prefill")
        if chunk is not None and chunk < 1:
            raise ValueError(f"prefill chunk must be >= 1, got {chunk}")
        if self.prefill_piece_cap:
            chunk = min(chunk or self.prefill_piece_cap,
                        self.prefill_piece_cap)
        if chunk is None or chunk >= len(tokens):
            return self._prefill_piece(cache, tokens, pos)
        logits = None
        for i in range(0, len(tokens), chunk):
            faults.fire("prefill_chunk")
            logits, cache = self._prefill_piece(cache, tokens[i:i + chunk],
                                                pos + i)
        return logits, cache

    @property
    def prefill_piece_cap(self) -> int:
        """The most tokens one prefill forward may take (0: any). A window
        layer's ring bounds it (``ModelConfig.max_prefill_piece``); a piece
        is padded to a bucket, so the cap is the largest bucket under it."""
        cap = self.cfg.max_prefill_piece
        return max((b for b in PREFILL_BUCKETS if b <= cap), default=cap)

    def _prefill_piece(self, cache: dict, tokens, pos: int,
                       bucket: Optional[int] = None) -> tuple:
        """One bucketed prefill forward (validated by the callers).
        ``bucket``: pad to this many rows instead of the tokens' own bucket
        (a caller that wants one program for every piece it sends)."""
        # clamp the padded bucket to the remaining context: an out-of-range
        # dynamic_update_slice start would be silently clamped by XLA, writing
        # K/V into wrong slots with wrong rope angles
        bucket = min(max(bucket or 0, prefill_bucket(len(tokens))),
                     self.cfg.seq_len - pos)
        self._last_prefill_bucket = bucket
        padded = np.zeros(bucket, np.int32)
        padded[: len(tokens)] = tokens
        return self._prefill(cache, jnp.asarray(padded), len(tokens), jnp.int32(pos))

    def generate(
        self,
        prompt_tokens: list,
        steps: int,
        session: Optional[Session] = None,
        stop_tokens: tuple = (),
        sampler: Optional[SamplerConfig] = None,
    ) -> Iterator[tuple]:
        """Yield (token_id, TokenStats) for up to ``steps`` generated tokens.

        Pass the previous call's ``engine.final_session`` to continue a
        conversation with one continuous KV cache and position counter (the
        reference keeps one continuous pos across turns,
        `/root/reference/src/apps/dllama/dllama.cpp:154-161`).

        ``sampler`` overrides the engine-level SamplerConfig for this call
        only (per-request temperature/topp/seed, the API-server surface) —
        no recompilation, the settings are traced scalars.
        """
        scfg = sampler if sampler is not None else self.sampler_cfg
        temp, topp = jnp.float32(scfg.temperature), jnp.float32(scfg.topp)
        if sampler is not None:
            local_key = jax.random.PRNGKey(scfg.seed)

            def next_key():
                nonlocal local_key
                local_key, sub = jax.random.split(local_key)
                return sub
        else:
            next_key = self.next_key
        if session is None:
            cache, pos = self.new_cache(), 0
        else:
            cache, pos = session.cache, session.pos
            if session.pending_token is not None:
                prompt_tokens = [session.pending_token] + list(prompt_tokens)
        steps = min(steps, self.cfg.seq_len - pos - len(prompt_tokens))

        t0 = time.perf_counter()
        if len(prompt_tokens) > 1:
            last_logits, cache = self.prefill(cache, prompt_tokens, pos)
            # sample the first generated token from the prefill logits
            token = sample_dynamic(last_logits, next_key(), temp, topp)
        else:
            token = jnp.asarray(prompt_tokens[0], jnp.int32)
        token.block_until_ready()
        self.prefill_ms = (time.perf_counter() - t0) * 1000.0
        if self._m_prefill is not None and len(prompt_tokens) > 1:
            self._m_prefill.observe(self.prefill_ms)

        tok_int: Optional[int] = None
        if len(prompt_tokens) > 1:
            pos += len(prompt_tokens)
            if steps <= 0:
                # caller asked for no tokens (or the context is full): the
                # prefill still advanced the session, but nothing is emitted
                self.final_session = Session(cache, pos, pending_token=None)
                return
            tok_int = int(token)
            # final_session is refreshed BEFORE every yield so a consumer that
            # abandons the generator mid-stream (stop-string hit, client
            # disconnect) still observes the state matching what it received
            self.final_session = Session(cache, pos, pending_token=tok_int)
            # prefill gathers move `bucket` rows of every collective at once
            pf_kb = self.wire_kb(self._last_prefill_bucket)
            yield tok_int, TokenStats(self.prefill_ms, self.prefill_ms,
                                      sent_kb=pf_kb, recv_kb=pf_kb)
            steps -= 1
            if tok_int in stop_tokens:
                return
        for _ in range(max(steps, 0)):
            t1 = time.perf_counter()
            self._reduce_dispatch()  # solo steps ride the row programs too
            token, ok, cache = self._decode_step(
                cache, token, jnp.int32(pos), next_key(), temp, topp,
                self._poison_flag()
            )
            # the call above returns as soon as the program is enqueued; the
            # dispatch wall time is host+launch overhead ("transfer"), the
            # block from here to the result is device execution ("inference")
            t2 = time.perf_counter()
            token.block_until_ready()
            t3 = time.perf_counter()
            if not bool(ok):
                # fail fast: the sampled token is garbage — don't emit it
                if self._m_quarantine is not None:
                    self._m_quarantine.inc()
                raise NumericHealthError(f"at decode position {pos}")
            tok_int = int(token)
            t4 = time.perf_counter()
            dt = (t4 - t1) * 1000.0
            if self._m_step is not None:
                self._m_step.observe(dt)
            pos += 1
            self.final_session = Session(cache, pos, pending_token=tok_int)
            yield tok_int, TokenStats(
                generation_ms=dt,
                inference_ms=(t3 - t2) * 1000.0,
                transfer_ms=(t2 - t1 + t4 - t3) * 1000.0,
                sent_kb=self.wire_kb_per_token,
                recv_kb=self.wire_kb_per_token,
            )
            if tok_int in stop_tokens:
                break
        if tok_int is None:
            # nothing was generated: a 1-token prompt with steps<=0 leaves the
            # prompt token itself unconsumed
            pending = prompt_tokens[0] if len(prompt_tokens) == 1 else None
        else:
            pending = tok_int
        self.final_session = Session(cache, pos, pending_token=pending)

    def generate_fused(
        self, prompt_tokens: list, steps: int, sampler: Optional[SamplerConfig] = None
    ) -> tuple:
        """Batch-generate ``steps`` tokens with the fused on-device loop.

        Returns (tokens list, prefill_ms, decode_ms_total). No early stop —
        the whole loop runs on device; use generate() when stop tokens or
        streaming matter more than raw latency. With ``sampler`` given, the
        key chain starts from its seed — reproducible per request like
        ``generate``, but NOT bit-identical to it at temperature > 0: the
        fused loop consumes one chain key per CHUNK (splitting per step on
        device), while generate() splits the chain once per token.
        """
        scfg = sampler if sampler is not None else self.sampler_cfg
        temp, topp = jnp.float32(scfg.temperature), jnp.float32(scfg.topp)
        if sampler is not None:
            local_key = jax.random.PRNGKey(scfg.seed)

            def next_key():
                nonlocal local_key
                local_key, sub = jax.random.split(local_key)
                return sub
        else:
            next_key = self.next_key
        cache = self.new_cache()
        steps = min(steps, self.cfg.seq_len - len(prompt_tokens))
        t0 = time.perf_counter()
        if steps <= 0 and len(prompt_tokens) > 1:
            # nothing to emit; prefill still advances the session
            _, cache = self.prefill(cache, prompt_tokens, 0)
            self.prefill_ms = (time.perf_counter() - t0) * 1000.0
            self.final_session = Session(cache, len(prompt_tokens), pending_token=None)
            return [], self.prefill_ms, 0.0
        if len(prompt_tokens) > 1:
            last_logits, cache = self.prefill(cache, prompt_tokens, 0)
            token = sample_dynamic(last_logits, next_key(), temp, topp)
            pos = len(prompt_tokens)
            first = [int(token)]
            steps -= 1
        else:
            token = jnp.asarray(prompt_tokens[0], jnp.int32)
            pos = 0
            first = []
        token.block_until_ready()
        self.prefill_ms = prefill_ms = (time.perf_counter() - t0) * 1000.0
        if self._m_prefill is not None and len(prompt_tokens) > 1:
            self._m_prefill.observe(prefill_ms)

        # run the scan in BUCKETED chunk sizes so distinct `steps` values reuse
        # a handful of compiles (like prefill); overshooting the last chunk is
        # safe for the same reason tail-padded prefill is — discarded tokens
        # only touch cache slots a later decode overwrites before attending
        t1 = time.perf_counter()
        toks: list = []
        remaining = steps
        chunk_size = self.decode_chunk
        while remaining > 0:
            tc = time.perf_counter()
            # tail chunks reuse prefill buckets for compile sharing, but never
            # exceed the caller's chunk size (it bounds program size/latency);
            # prefill_bucket(r) >= r, so full chunks resolve to chunk_size
            n = min(chunk_size, prefill_bucket(remaining))
            n = min(n, self.cfg.seq_len - pos)  # never write cache out of range
            self._reduce_dispatch()  # solo chunks ride the row programs too
            chunk, cache, ok = self._decode_loop(
                cache, token, jnp.int32(pos), next_key(), temp, topp,
                self._poison_flag(), n_steps=n
            )
            take = min(n, remaining)
            if not bool(ok):
                if self._m_quarantine is not None:
                    self._m_quarantine.inc()
                raise NumericHealthError(
                    f"in fused decode chunk starting at position {pos}")
            chunk_list = [int(t) for t in np.asarray(chunk)]
            if self._m_chunk is not None:
                self._m_chunk.observe((time.perf_counter() - tc) * 1000.0)
            toks.extend(chunk_list[:take])
            token = chunk[-1]
            pos += take
            remaining -= take
        decode_ms = (time.perf_counter() - t1) * 1000.0

        emitted = first + toks
        if emitted:
            pending = emitted[-1]
        else:
            pending = prompt_tokens[0] if len(prompt_tokens) == 1 else None
        self.final_session = Session(cache, pos, pending_token=pending)
        return emitted, prefill_ms, decode_ms

    def generate_batch(
        self, prompts: list, steps: int,
        sampler: Optional[SamplerConfig] = None, stop_tokens: tuple = (),
        row_steps: Optional[list] = None,
        samplers: Optional[list] = None,
        on_chunk=None,
    ) -> list:
        """Decode B independent prompts TOGETHER: one weight-streaming pass
        per step serves every sequence (llama.forward_batched) — on
        bandwidth-bound decode that is ~B x the aggregate tokens/s of B
        sequential runs, a throughput mode the reference's batch=1 design
        has no analog for. Returns a list of B token lists; each row carries
        min(steps, its own remaining context) tokens — one near-full row
        never truncates the others (it pins at its last slot while the rest
        keep decoding). ``stop_tokens``: once EVERY row has emitted one (or
        reached its own budget) the remaining decode chunks are skipped —
        rows still carry tokens past their stop (the caller truncates, as
        the server batcher does); a short-reply batch doesn't pay the full
        step budget. ``row_steps``: per-row budgets for that done check
        (the server's mixed max_tokens; defaults to ``steps`` for all).

        Sampling: every row runs its OWN key chain, split once per step —
        the exact schedule ``generate`` walks. ``samplers`` gives row b its
        full per-request settings (temperature/topp/seed) — a sampled row
        is then BIT-IDENTICAL to a solo ``generate`` call with the same
        SamplerConfig (the server batches mixed concurrent requests on
        this; ``generate_fused`` differs at temperature > 0, see its
        docstring). With a single ``sampler``, rows share its
        temperature/topp and draw per-row chains split from its seed;
        greedy (temperature 0) rows are exact solo streams either way. With
        neither, the engine chain seeds the split.

        ``on_chunk(rows)``: called after every fused device chunk with the
        list of per-row tokens decoded so far THIS chunk (garbage past a
        row's own budget already trimmed) — the server's batched SSE
        streaming hook; tokens arrive in decode_chunk-sized bursts.

        Numeric health: ``self.row_health`` holds, after the call, one bool
        per row — False once the watchdog saw non-finite logits in that row
        (its tokens are garbage from that chunk on; siblings are unaffected).
        The caller decides the policy (the server maps False to
        ``finish_reason:"error"``); this fixed-membership path keeps
        decoding, unlike BatchSession's quarantine.
        """
        if not prompts or any(not p for p in prompts):
            raise ValueError("generate_batch needs non-empty prompts")
        B = len(prompts)
        if samplers is not None:
            if len(samplers) != B:
                raise ValueError(f"samplers must have {B} entries")
            temps = jnp.asarray([s.temperature for s in samplers], jnp.float32)
            topps = jnp.asarray([s.topp for s in samplers], jnp.float32)
            keys = jnp.stack([jax.random.PRNGKey(s.seed) for s in samplers])
        else:
            scfg = sampler if sampler is not None else self.sampler_cfg
            temps = jnp.full((B,), scfg.temperature, jnp.float32)
            topps = jnp.full((B,), scfg.topp, jnp.float32)
            base = (jax.random.PRNGKey(scfg.seed) if sampler is not None
                    else self.next_key())
            keys = jax.random.split(base, B)

        cache, pend, poss = self._prefill_batch_rows(prompts)
        tokens = jnp.asarray(pend, jnp.int32)
        pos = jnp.asarray(poss, jnp.int32)

        rooms = [self.cfg.seq_len - p for p in poss]  # feeds each row allows
        steps = min(steps, max(rooms))
        budgets = [
            min(rooms[b], row_steps[b] if row_steps else steps)
            for b in range(B)
        ]
        out: list = [[] for _ in range(B)]
        self.row_health = [True] * B
        if steps <= 0:
            self.decode_ms = 0.0
            return out
        remaining = steps
        t1 = time.perf_counter()
        while remaining > 0:
            tc = time.perf_counter()
            n = min(self.decode_chunk, prefill_bucket(remaining))
            chunk, cache, keys, ok = self.batch_loop(B)(
                cache, tokens, pos, keys, temps, topps,
                self._poison_rows(B), n_steps=n
            )
            take = min(n, remaining)
            arr = np.asarray(chunk)  # [n, B]
            okh = np.asarray(ok)  # [B]
            if self._m_chunk is not None:
                self._m_chunk.observe((time.perf_counter() - tc) * 1000.0)
            for b in range(B):
                if self.row_health[b] and not bool(okh[b]) \
                        and self._m_quarantine is not None:
                    self._m_quarantine.inc()
                self.row_health[b] = self.row_health[b] and bool(okh[b])
            done = steps - remaining  # tokens every row was offered so far
            fresh: list = [[] for _ in range(B)]
            for b in range(B):
                # a context-exhausted row pinned at its last slot: its tokens
                # past rooms[b] are garbage — keep only its own budget
                keep = max(0, min(take, rooms[b] - done))
                fresh[b] = [int(t) for t in arr[:keep, b]]
                out[b].extend(fresh[b])
            tokens = chunk[-1]
            # mirror the in-program per-row cap across chunk boundaries
            pos = jnp.minimum(pos + take, jnp.int32(self.cfg.seq_len - 1))
            remaining -= take
            if on_chunk is not None:
                on_chunk(fresh)
            if (stop_tokens or row_steps) and all(
                len(out[b]) >= budgets[b]
                or (stop_tokens and any(t in stop_tokens for t in out[b]))
                for b in range(B)
            ):
                break
        self.decode_ms = (time.perf_counter() - t1) * 1000.0
        return out

    def _prefill_batch_rows(self, prompts: list) -> tuple:
        """Shared-prefix batched prefill for the batch decode paths: init the
        [L, B, S, kv, hd] cache, prefill each DISTINCT prompt prefix once
        (rows sharing a prefix — the OpenAI `n` case — reuse it) and write
        it straight into the batch cache (donated in-place update), so peak
        HBM is the batch cache plus ONE single cache — never B side by
        side. The last prompt token stays pending (the uniform first
        batched step feeds it, so a row emits min(steps, room) tokens).
        Returns (cache, pending tokens [B], positions [B]); sets
        prefill_ms."""
        t0 = time.perf_counter()
        cache = self._batch_cache_init(len(prompts))
        groups: dict = {}
        for b, p in enumerate(prompts):
            if len(p) > 1:
                groups.setdefault(tuple(p[:-1]), []).append(b)
        for prefix, rows_b in groups.items():
            single = self.new_cache()
            _, single = self.prefill(single, list(prefix), 0)
            for b in rows_b:
                cache = self._batch_cache_insert(cache, single, jnp.int32(b))
            del single  # 1-token-prompt rows keep their zero slots
        pend = [int(p[-1]) for p in prompts]
        poss = [len(p) - 1 for p in prompts]
        self.prefill_ms = (time.perf_counter() - t0) * 1000.0
        if self._m_prefill is not None:
            self._m_prefill.observe(self.prefill_ms)
        return cache, pend, poss

    def batch_session(self, max_batch: int,
                      chunk: Optional[int] = None,
                      bucket_kv: bool = False,
                      min_bucket: Optional[int] = None,
                      prefill_chunk: int = 0,
                      kv_budget=None,
                      kv_pages: int = 0) -> "BatchSession":
        """Open a persistent slot-pool decode session (continuous batching):
        resident donated batch cache slabs whose rows are admitted, stepped,
        and released INDEPENDENTLY — see BatchSession.
        ``chunk`` is the fused steps per ``step_chunk`` call (defaults to the
        engine's decode_chunk). With ``bucket_kv=False`` (the default) the
        session is the classic single [L, max_batch, S, kv, hd] slab and
        (max_batch, chunk) picks the single _decode_loop_batch compile every
        chunk reuses; ``bucket_kv=True`` replaces it with power-of-two
        length-bucketed slot pools (from ``min_bucket`` up to seq_len) under
        the SAME modeled HBM budget of max_batch*seq_len KV token-slots, so
        short requests stop paying full-context HBM and strictly more rows
        fit. ``kv_pages`` > 0 goes further: TRUE PAGED KV — one arena of
        kv_pages-token pages under the same budget, per-row page tables, a
        radix prefix cache aliasing shared prompt pages copy-on-write, and
        zero migration copies (growing a row appends a page). 0 keeps the
        bucketed/uniform slab modes as the degenerate configurations.
        ``prefill_chunk`` > 0 sets the default token budget of
        prefill_step() for chunked (admit_begin) admissions. ``kv_budget``
        is an optional external accountant (serving.lifecycle.KVBudget) that
        mirrors reservations/occupancy into gauges (and, in paged mode,
        owns the page free list + refcounts via ``attach_pages``)."""
        return BatchSession(self, max_batch, chunk, bucket_kv=bucket_kv,
                            min_bucket=min_bucket, prefill_chunk=prefill_chunk,
                            kv_budget=kv_budget, kv_pages=kv_pages)

    def generate_batch_spec(
        self, prompts: list, steps: int,
        stop_tokens: tuple = (),
        row_steps: Optional[list] = None,
        draft_len: int = 8,
        ngram: int = 3,
        sampler: Optional[SamplerConfig] = None,
        on_step=None,
        row_cancel=None,
    ) -> tuple:
        """Batched GREEDY decode with prompt-lookup speculative drafting:
        every verify step scores draft_len+1 candidate positions for ALL B
        sequences in one weight-streaming pass — the two bandwidth
        multipliers (batching across sequences, speculation across
        positions) composed. Beyond both the reference (one token, one
        sequence per step) and this engine's own generate_batch /
        generate_spec taken alone.

        Returns (rows, stats): row b equals generate_batch's greedy row b
        truncated at its first stop token (speculation changes the
        schedule, never the tokens — per-position argmax is what the plain
        batched step computes; generate_batch rows may CARRY tokens past a
        stop for the caller to truncate, this path truncates itself);
        stats = {"verify_steps", "accepted_drafts", "emitted"}.

        Greedy only (``sampler`` with temperature > 0 raises): replaying B
        per-row sampled key chains through a shared-T verify is bookkeeping
        this path doesn't carry yet — sampled batches run generate_batch,
        sampled solo spec runs generate_spec. Runs single-device AND under
        quantized TP (the shard_map verify wrapper,
        parallel.quant_tp.make_tp_verify_batched); only the dense-pjit
        mesh path raises (supports_batch_spec). Rows with no matching
        n-gram still verify their pending token (a T-row step emits at
        least 1 token per row, exactly like plain decode).

        ``on_step(fresh)``: called after every verify launch with each
        row's tokens emitted by THAT launch (empty for finished rows) —
        the server's batched-spec SSE hook. Unlike generate_batch's
        on_chunk, bursts here are final (budget- and stop-truncated
        already) and arrive every 1..draft_len+1 tokens.

        ``row_cancel(b) -> bool``: re-checked for every unfinished row
        between verify launches; True marks the row done on the spot — a
        cancelled/expired request stops consuming verify work at the next
        launch boundary instead of riding to batch end (the row then
        re-verifies its pending token in place like any finished row, which
        is how speculation's fixed row set is preserved). Its emissions up
        to the cancellation stand.

        Cache safety mirrors generate_spec: rejected/pad slots hold garbage
        K/V that later steps overwrite before any query attends them; a
        FINISHED row keeps verifying its pending token in place without
        advancing — its emissions are already taken, and its (per-row) cache
        slab can't affect other rows.
        """
        self.cfg.refuse_for_plan("speculative decoding (--spec-draft)")
        if not prompts or any(not p for p in prompts):
            raise ValueError("generate_batch_spec needs non-empty prompts")
        if not self.supports_batch_spec:
            raise ValueError(
                "generate_batch_spec does not run on the dense-pjit mesh "
                "path (no shard_map wrapper for the batched verify "
                "forward); quantized-TP and single-device engines support "
                "it — use generate_batch here")
        scfg = sampler if sampler is not None else self.sampler_cfg
        if scfg.temperature > 0.0:
            raise ValueError(
                "generate_batch_spec is greedy-only; use generate_batch for "
                "sampled batches or generate_spec for sampled solo decoding")
        B = len(prompts)
        S = self.cfg.seq_len
        if sampler is None:
            # mirror generate_batch's no-sampler branch, which burns one
            # engine-chain key even when greedy — substituting this path
            # must not desync later sampled calls on the same engine chain
            self.next_key()

        cache, pend, poss = self._prefill_batch_rows(prompts)

        rooms = [S - p for p in poss]
        budgets = [min(rooms[b], row_steps[b] if row_steps else steps,
                       steps) for b in range(B)]
        indexes = [_NgramIndex(ngram) for _ in range(B)]
        for b, p in enumerate(prompts):
            indexes[b].extend(p[:-1])
        out: list = [[] for _ in range(B)]
        done = [budgets[b] <= 0 for b in range(B)]
        verify_steps = accepted = 0

        t1 = time.perf_counter()
        while not all(done):
            if row_cancel is not None:
                for b in range(B):
                    if not done[b] and row_cancel(b):
                        done[b] = True
                if all(done):
                    break
            # shared static T, shrunk so the most context-constrained ACTIVE
            # row's write window stays in range (T values bucket to at most
            # draft_len+1 distinct compiles)
            T = min(draft_len + 1,
                    min(S - poss[b] for b in range(B) if not done[b]))
            T = max(T, 1)
            feeds, drafts = [], []
            for b in range(B):
                if done[b]:
                    drafts.append([])
                    feeds.append([pend[b]] * T)  # re-verify in place
                    continue
                k = min(T - 1, budgets[b] - len(out[b]) - 1)
                d = indexes[b].draft(pend[b], k) if k > 0 else []
                drafts.append(d)
                feeds.append([pend[b]] + d + [0] * (T - 1 - len(d)))
            g, cache = self.verify_program(B)(
                cache, jnp.asarray(feeds, jnp.int32),
                jnp.asarray([min(poss[b], S - T) if done[b] else poss[b]
                             for b in range(B)], jnp.int32))
            g = np.asarray(g)  # [B, T]
            verify_steps += 1
            fresh: list = [[] for _ in range(B)]
            for b in range(B):
                if done[b]:
                    continue
                row = [int(v) for v in g[b]]
                m = 0
                while m < len(drafts[b]) and drafts[b][m] == row[m]:
                    m += 1
                accepted += m
                emit = row[: m + 1]
                take = min(len(emit), budgets[b] - len(out[b]))
                for j in range(take):
                    if emit[j] in stop_tokens:
                        take = j + 1
                        break
                emit = emit[:take]
                indexes[b].extend([pend[b]] + drafts[b][:m])
                out[b].extend(emit)
                fresh[b] = emit
                pend[b] = emit[-1]
                poss[b] += m + 1
                if (len(out[b]) >= budgets[b]
                        or (stop_tokens and emit
                            and emit[-1] in stop_tokens)):
                    done[b] = True
            if on_step is not None:
                on_step(fresh)
        self.decode_ms = (time.perf_counter() - t1) * 1000.0
        emitted_total = sum(len(r) for r in out)
        if self._m_spec_steps is not None:
            self._m_spec_steps.inc(verify_steps)
            self._m_spec_accepted.inc(accepted)
            self._m_spec_emitted.inc(emitted_total)
        return out, {"verify_steps": verify_steps,
                     "accepted_drafts": accepted,
                     "emitted": emitted_total}

    def generate_spec(
        self,
        prompt_tokens: list,
        steps: int,
        session: Optional[Session] = None,
        stop_tokens: tuple = (),
        draft_len: int = 8,
        ngram: int = 3,
        history: Optional[list] = None,
        sampler: Optional[SamplerConfig] = None,
    ) -> Iterator[tuple]:
        """Decoding with prompt-lookup speculative drafting — greedy or
        sampled, both EXACT.

        Drafts the next ``draft_len`` tokens by matching the trailing
        ``ngram`` of the context against its own history (the continuation
        that followed the same n-gram last time), then scores pending +
        draft in ONE verify step and accepts the longest matching prefix —
        m matched drafts emit m+1 tokens for one weight-streaming pass, a
        pure win on bandwidth-bound decode whenever text repeats (quoting,
        code, structured output). Beyond the reference's capabilities
        (single token per step, `src/tasks.cpp:199-210`).

        Exactness: at temperature 0 the verify compares against per-position
        argmax. At temperature > 0 it compares against the token sequential
        decoding would have SAMPLED — the verify step evaluates position i
        with the i-th key of the same per-token key chain ``generate`` walks
        (``sampler`` given: a fresh chain from its seed, as in generate;
        otherwise the engine chain) — so the emitted stream is identical to
        plain decode with the same sampler, batch boundaries and all.
        Acceptance just happens less often as temperature rises. The chain
        advances exactly once per EMITTED token — at temperature 0 too
        (plain generate() burns one key per token via next_key() even when
        greedy ignores it, so the greedy path here must consume identically
        or a later sampled call on the same engine chain would diverge) —
        and a stop token or the steps cap truncating a batch truncates the
        advancement with it, keeping later turns on the engine chain
        aligned with plain decode.

        Cache safety on rejection needs no rollback: rejected draft slots
        hold garbage K/V, but every future step writes position p before any
        query attends it — the same overwrite-before-attend invariant as
        tail-padded prefill.

        ``history``: tokens already consumed into the session's cache before
        this call (exclusive of its pending token) — resuming callers (e.g.
        the API server's prefix cache) pass the prior conversation so the
        n-gram lookup can draft from earlier turns, which is where the
        repetition lives. Draft quality only; output is exact regardless.
        """
        scfg = sampler if sampler is not None else self.sampler_cfg
        temp, topp = jnp.float32(scfg.temperature), jnp.float32(scfg.topp)
        sampled = scfg.temperature > 0.0
        chain = jax.random.PRNGKey(scfg.seed) if sampler is not None else self._key

        def peek(n):
            """n per-token keys + the chain state after each — the caller
            commits to a prefix of them via commit(states[i])."""
            c, subs, states = chain, [], []
            for _ in range(n):
                c, sub = jax.random.split(c)
                subs.append(sub)
                states.append(c)
            return subs, states

        def commit(state):
            nonlocal chain
            chain = state
            if sampler is None:
                self._key = chain  # mirror next_key()'s engine-chain use

        if session is None:
            cache, pos = self.new_cache(), 0
        else:
            cache, pos = session.cache, session.pos
            if session.pending_token is not None:
                prompt_tokens = [session.pending_token] + list(prompt_tokens)
        if not prompt_tokens:
            raise ValueError(
                "generate_spec needs at least one token to feed — an empty "
                "prompt requires a session with a pending_token"
            )
        # a rejected draft's K/V would overwrite ring slots still in a window
        self.cfg.refuse_for_plan("speculative decoding (--spec-draft)")
        steps = min(steps, self.cfg.seq_len - pos - len(prompt_tokens))

        t0 = time.perf_counter()
        # the index covers tokens already consumed into the cache; the
        # pending `token` joins it only when a verify step consumes it
        index = _NgramIndex(ngram)
        if history:
            index.extend(history)
        if len(prompt_tokens) > 1:
            index.extend(prompt_tokens)
            last_logits, cache = self.prefill(cache, prompt_tokens, pos)
            subs, states = peek(1)
            commit(states[0])
            if sampled:
                token = int(sample_dynamic(last_logits, subs[0], temp, topp))
            else:
                token = int(jnp.argmax(last_logits))
            pos += len(prompt_tokens)
        else:
            token = int(prompt_tokens[0])
        self.prefill_ms = (time.perf_counter() - t0) * 1000.0

        if steps <= 0:
            # token is the pending next input in both branches above
            self.final_session = Session(cache, pos, pending_token=token)
            return

        emitted = 0
        first = len(prompt_tokens) > 1
        while emitted < steps:
            t1 = time.perf_counter()
            from_prefill = first
            if first:
                # the prefill already produced one token "for free"; the
                # prompt is consumed, so per-token pos below starts at pos-1.
                # Its stats report the prefill cost (like generate()'s first
                # token) — the loop did no work for it
                out, first, base = [token], False, pos - 1
                batch_rows = self._last_prefill_bucket
            else:
                # fixed feed length -> ONE verify compile for the whole run;
                # pad slots write garbage K/V at pos+m+1.. which every later
                # step overwrites before attending (see docstring). Only the
                # sequence tail shrinks the feed (at most one extra compile
                # per distinct tail length).
                L = min(draft_len + 1, self.cfg.seq_len - pos)
                k = min(L - 1, steps - emitted - 1)  # >= 0: emitted < steps
                draft = index.draft(token, k)
                feed = jnp.asarray(
                    [token] + draft + [0] * (L - 1 - len(draft)), jnp.int32)
                subs, states = peek(L)
                if sampled:
                    g, cache = self._verify_sampled(
                        cache, feed, jnp.int32(pos), jnp.stack(subs), temp, topp)
                else:
                    g, cache = self._verify_step(cache, feed, jnp.int32(pos))
                g = [int(v) for v in np.asarray(g)]
                # accept drafts while they match the model's own (greedy or
                # key-chain-sampled) choice
                m = 0
                while m < len(draft) and draft[m] == g[m]:
                    m += 1
                out = g[: m + 1]  # m matched drafts + the correcting token
                # how many of them will actually be EMITTED (steps cap, stop
                # tokens) — the key chain must advance by exactly that many,
                # or later turns on the engine chain diverge from plain decode
                take = min(len(out), steps - emitted)
                for j in range(take):
                    if out[j] in stop_tokens:
                        take = j + 1
                        break
                out = out[:take]
                commit(states[take - 1])
                if self._m_spec_steps is not None:
                    self._m_spec_steps.inc()
                    self._m_spec_accepted.inc(m)
                    self._m_spec_emitted.inc(take)
                index.extend([token] + draft[:m])
                # (on a truncated batch the generator is about to return /
                # exit, so the pending token is never fed again)
                token = out[-1]
                base = pos  # position before this batch's tokens
                pos += m + 1
                batch_rows = L
            dt = self.prefill_ms if from_prefill else (time.perf_counter() - t1) * 1000.0
            # this batch's collectives gathered batch_rows rows, not one
            # (cf. the prefill row's accounting in generate())
            batch_kb = self.wire_kb(batch_rows)
            for i, tk in enumerate(out):
                emitted += 1
                # per-token session pos: a consumer stopping at token i must
                # resume as if only tokens 0..i were ever consumed — slots
                # written beyond are overwritten before any resume attends
                self.final_session = Session(cache, base + i + 1, pending_token=tk)
                yield tk, TokenStats(
                    generation_ms=dt if i == 0 else 0.0,
                    inference_ms=dt if i == 0 else 0.0,
                    sent_kb=batch_kb if i == 0 else 0.0,
                    recv_kb=batch_kb if i == 0 else 0.0,
                )
                if tk in stop_tokens:
                    return
        # final_session is already exact: the last yield recorded (cache,
        # pos-of-that-token, pending) — tokens speculated past the `steps`
        # cap were never emitted and their cache slots will be overwritten
        # before any resumed decode attends them


@dataclasses.dataclass
class _SlotState:
    """Host-side bookkeeping for one admitted BatchSession row."""

    room: int  # feeds the row's remaining context allows (S - admit pos)
    budget: int  # min(room, the caller's step budget)
    stop_tokens: tuple
    reserved: int  # KV token-slots reserved against the session budget
    offered: int = 0  # tokens the fused chunks have offered this row so far
    done: bool = False  # budget/stop reached; pinned in place until release()
    emitted: int = 0  # tokens actually kept (post budget/stop truncation)
    finish: Optional[str] = None  # "stop" | "length" | "error" once done
    prefilling: bool = False  # admit_begin()ed, prompt not fully consumed
    prefill_ms: float = 0.0  # accumulated admission-prefill wall time
    span_id: int = 0  # the request's trace track, on its prefill phase spans


def _drop(tree) -> None:
    """Free a pytree's device buffers once the work that writes them (a
    piece or a launch not waited for) has ended."""
    leaves = jax.tree.leaves(tree)
    jax.block_until_ready(leaves)
    for leaf in leaves:
        leaf.delete()


def host_prng_key(seed: int) -> np.ndarray:
    """``jax.random.PRNGKey(seed)`` worked out on the host, with no device
    program and no sync: the default threefry key of a seed is its two
    32-bit words, the high one 0 unless 64-bit types are on."""
    if jax.config.jax_default_prng_impl != "threefry2x32":
        return np.asarray(jax.random.PRNGKey(seed), np.uint32)
    seed = int(seed)
    hi = (seed >> 32) & 0xFFFFFFFF if jax.config.jax_enable_x64 else 0
    return np.array([hi, seed & 0xFFFFFFFF], np.uint32)


class _Part:
    """One pool's program of a decode launch, from its dispatch to its
    collection: the rows it advanced as they stood then (row, handle, their
    ``_SlotState``, tokens offered them before it), the prompts that rode
    it (handle, state, pending prompt, cursor after, prefix complete)."""

    __slots__ = ("pool", "rows", "riders", "out", "ok", "keys", "picks",
                 "live_pos", "t0")

    def __init__(self, pool, rows: list, riders: list, t0: float):
        self.pool = pool
        self.rows = rows
        self.riders = riders
        self.t0 = t0
        self.out = self.ok = self.keys = self.live_pos = None
        self.picks: list = []


class _Launch:
    """One decode launch: a program per occupied pool, dispatched together.
    ``state`` is what the launch before it was doing at the dispatch
    (``queued``: still running, ``late``: ended, ``idle``: none in flight);
    ``start`` when the device began it, as far as the host can tell (None
    while unknown), ``end`` when it ended where the host waited for it, or
    else ``bound``, a time by which it had ended; ``kind`` (riders, the
    bucket of the standalone piece before it, 0 for none) groups launches
    of like length for ``BatchSession.due``."""

    __slots__ = ("parts", "state", "kind", "dispatched", "start", "end",
                 "bound")

    def __init__(self, state: str, piece: int):
        self.parts: list = []
        self.state = state
        self.kind = (False, piece)
        self.dispatched = 0.0
        self.start = self.end = self.bound = None


class _PendingPrefill:
    """A chunked admission's in-flight prompt state (admit_begin)."""

    __slots__ = ("prompt", "scfg", "cache", "cursor", "rides", "pub_nodes",
                 "scattered")

    def __init__(self, prompt: list, scfg: SamplerConfig,
                 cache: Optional[dict]):
        self.prompt = prompt
        self.scfg = scfg
        # the staging cache: single-sequence [L, S, kv, hd] being filled by
        # standalone pieces. None while the way is undecided in a session
        # whose prompts can ride, and for good once the row rides: its K/V
        # are written where they live, in its pool row
        self.cache = cache
        self.cursor = 0  # prompt-prefix tokens already prefilled
        #: the way the prompt reaches the cache, decided at its first tokens
        #: and kept: True rides the pool's decode chunks, False takes
        #: standalone pieces into ``cache``, None not begun
        self.rides: Optional[bool] = None
        # paged publish-at-admit state: the radix nodes this admission
        # created ready=False (index-aligned with the row's blocks; None
        # where another row's node already existed), and the token count
        # already scattered from the staging cache into arena pages
        self.pub_nodes: list = []
        self.scattered = 0


class _BucketPool:
    """One context bucket's slot pool: a [L, cap, ctx, kv, hd] donated slab
    plus host-side per-row decode state (numpy mirrors, shipped to the
    device per fused chunk). ``ctx`` may be shorter than the model context:
    attention masks by ``pos`` and clamps writes to the slab, so a short
    slab is exact as long as every live row's position stays inside it —
    the session migrates rows out before they outgrow it."""

    __slots__ = ("ctx", "cap", "cache", "tokens", "pos", "keys", "temps",
                 "topps", "rows", "dev", "fix", "sampling", "live")

    def __init__(self, eng: Engine, ctx: int, cap: int):
        self.ctx = ctx
        self.cap = cap
        self.cache = eng._bucket_cache_init(cap, ctx)
        self.tokens = np.zeros((cap,), np.int32)
        # free rows pin at the slab's last slot, like exhausted rows
        self.pos = np.full((cap,), ctx - 1, np.int32)
        self.keys = np.zeros((cap, 2), np.uint32)
        self.temps = np.zeros((cap,), np.float32)
        self.topps = np.ones((cap,), np.float32)
        self.rows: list = [None] * cap  # handle occupying each row
        #: a session whose launches run ahead (``BatchSession.ahead``): the
        #: last launch's tokens and keys, on the device, which the next
        #: launch takes as they are; None until the first launch, and after
        #: ``BatchSession._pull`` brought them back to ``tokens`` / ``keys``
        self.dev = None
        #: rows whose next launch takes ``tokens`` / ``keys`` from the host
        #: (they went live or moved since the last launch)
        self.fix = np.zeros((cap,), np.bool_)
        #: (temps, topps) on the device as last uploaded, None once changed
        self.sampling = None
        #: a layer plan's (live mask on the host, on the device) as last
        #: uploaded
        self.live = None

    def grow(self, eng: Engine) -> None:
        """Double the pool's capacity in place: rows keep their indices (no
        handle in the session moves), the new tail rows start free/pinned.
        Doubling bounds the retraces of the pool's decode program to
        log2(rows) for the whole session."""
        new_cap = self.cap * 2
        bigger = eng._bucket_cache_init(new_cap, self.ctx)
        self.cache = eng._bucket_cache_grow(bigger, self.cache)
        pad = new_cap - self.cap
        self.tokens = np.concatenate(
            [self.tokens, np.zeros((pad,), np.int32)])
        self.pos = np.concatenate(
            [self.pos, np.full((pad,), self.ctx - 1, np.int32)])
        self.keys = np.concatenate(
            [self.keys, np.zeros((pad, 2), np.uint32)])
        self.temps = np.concatenate(
            [self.temps, np.zeros((pad,), np.float32)])
        self.topps = np.concatenate(
            [self.topps, np.ones((pad,), np.float32)])
        self.rows.extend([None] * pad)
        self.cap = new_cap
        # the caller brought the device's tokens and keys back first
        self.fix = np.zeros((new_cap,), np.bool_)
        self.sampling = self.live = None


class _RowPages:
    """One paged row's page-table state: ``blocks[b]`` is the physical
    arena page holding logical token block b (aliased prefix pages first,
    private tail pages appended as the row grows). ``outstanding`` is the
    row's reserved-but-unallocated private page count (returned to the
    allocator at release); ``cap_tokens`` its worst-case context
    (admission's _need_ctx), the hard bound page appends never exceed."""

    __slots__ = ("blocks", "outstanding", "cap_tokens", "plen")

    def __init__(self, blocks: list, outstanding: int, cap_tokens: int,
                 plen: int):
        self.blocks = blocks
        self.outstanding = outstanding
        self.cap_tokens = cap_tokens
        self.plen = plen


class _PagedGroup:
    """Host-side row state for one paged decode shape: every row whose page
    table currently spans ``nb`` blocks shares one compiled decode program
    (window = nb*page tokens). Unlike _BucketPool there is NO device cache
    here — KV lives in the session-wide arena — so moving a growing row to
    a wider group is a host-side table rewrite, never a device copy: the
    bucket-migration copy is gone by construction. Free rows pin at the
    window's last slot with an all-scratch table (their writes land on the
    garbage page)."""

    __slots__ = ("nb", "cap", "tables", "tokens", "pos", "keys", "temps",
                 "topps", "rows")

    def __init__(self, nb: int, cap: int, page: int):
        self.nb = nb
        self.cap = cap
        self.tables = np.full((cap, nb), paged_kv.SCRATCH_PAGE, np.int32)
        self.tokens = np.zeros((cap,), np.int32)
        self.pos = np.full((cap,), nb * page - 1, np.int32)
        self.keys = np.zeros((cap, 2), np.uint32)
        self.temps = np.zeros((cap,), np.float32)
        self.topps = np.ones((cap,), np.float32)
        self.rows: list = [None] * cap

    def grow(self, page: int) -> None:
        """Double capacity in place (host arrays only; compile count per
        group stays log2(rows) like _BucketPool.grow)."""
        pad = self.cap
        self.tables = np.concatenate(
            [self.tables,
             np.full((pad, self.nb), paged_kv.SCRATCH_PAGE, np.int32)])
        self.tokens = np.concatenate(
            [self.tokens, np.zeros((pad,), np.int32)])
        self.pos = np.concatenate(
            [self.pos, np.full((pad,), self.nb * page - 1, np.int32)])
        self.keys = np.concatenate(
            [self.keys, np.zeros((pad, 2), np.uint32)])
        self.temps = np.concatenate(
            [self.temps, np.zeros((pad,), np.float32)])
        self.topps = np.concatenate(
            [self.topps, np.ones((pad,), np.float32)])
        self.rows.extend([None] * pad)
        self.cap *= 2


class BatchSession:
    """Slot-pool decode over resident donated batch cache slabs — the
    continuous-batching primitive. Where ``generate_batch`` forms a batch
    once and runs it to completion (a long row holds the device while short
    rows' slots idle), a BatchSession lets rows join (``admit`` /
    ``admit_begin``), step (``step_chunk``), and leave (``release``)
    independently BETWEEN fused decode chunks: the serving scheduler admits
    newly arrived requests into freed capacity while their neighbours keep
    decoding.

    Row math is EXACTLY generate_batch's: every chunk is one
    ``_decode_loop_batch`` program per occupied pool, each row running its
    OWN sampler chain (key split once per step) — so a row admitted
    mid-flight emits a stream BIT-IDENTICAL to a solo ``generate`` call
    with the same SamplerConfig, no matter what its neighbours are doing.
    Free/finished rows ride along pinned in place (pos clamped at the
    slab's last slot, feeding token 0) exactly like context-exhausted rows
    in generate_batch: their writes are garbage at slots no live query
    attends.

    Two residency layouts share this class. ``bucket_kv=False`` (default)
    is the classic single [L, max_batch, S, kv, hd] slab: handles ARE slot
    indices 0..max_batch-1 and one compile serves the whole session.
    ``bucket_kv=True`` shards residency into power-of-two context buckets
    under the SAME modeled HBM budget (max_batch * seq_len KV token-slots):
    a row is admitted into the smallest slab covering its prompt plus one
    decode chunk, reserves its worst-case bucket (prompt+steps) against the
    budget, and MIGRATES to the next bucket just before outgrowing its
    slab — so short requests stop paying full-context HBM and strictly
    more rows fit at fixed memory. One decode program per occupied
    (bucket, capacity) shape; capacities double, bounding retraces.

    Slot-slab reuse needs no clearing: admitting a multi-token prompt
    overwrites the slot's whole attended window (_batch_cache_insert, or
    token by token where the prompt rides the decode chunks), and
    a 1-token prompt starts at pos 0 where overwrite-before-attend holds —
    every position <= pos is written by the CURRENT occupant before any of
    its queries attends it; stale garbage sits only at masked positions.
    A row whose prompt is riding is not live yet: like a free row it is
    stepped at the slab's last slot, which no rider writes or attends, and
    ``_go_live`` sets its true position.
    Migration copies the row's whole slab, i.e. its entire attended
    history, so the invariant carries across buckets.
    """

    def __init__(self, eng: Engine, max_batch: int, chunk: Optional[int] = None,
                 bucket_kv: bool = False, min_bucket: Optional[int] = None,
                 prefill_chunk: int = 0, kv_budget=None, kv_pages: int = 0):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        chunk = eng.decode_chunk if chunk is None else chunk
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        self.eng = eng
        self.max_batch = max_batch
        self.chunk = chunk
        self.paged = kv_pages > 0
        if self.paged:
            eng.cfg.refuse_for_plan("the paged KV pool (--kv-pages), and "
                                    "with it export_row / KV transfer")
        self.bucket_kv = bool(bucket_kv) and not self.paged
        self.prefill_chunk = max(0, int(prefill_chunk))
        #: prompt tokens one step of a pool's decode chunk carries beside
        #: its decode rows: prefill_chunk tokens a tick over the chunk's
        #: steps, at least one. 0 where prompts never ride: monolithic
        #: admission, the paged loop, and an engine whose pooled program
        #: is another (``Engine.pooled_rides``)
        self.ride_t = (max(1, self.prefill_chunk // chunk)
                       if self.prefill_chunk > 0 and not self.paged
                       and eng.pooled_rides else 0)
        #: what the last step_chunk's riders did, for the scheduler's marks:
        #: (handle, launch start, launch end, prefix complete), the times on
        #: time.monotonic as ``piece_span``'s
        self.rode: list = []
        self._no_ride = None  # the ride operand of a launch nobody rides
        #: a slot-pool session on one device runs its launches AHEAD of
        #: their collection (``launch`` / ``collect``): the tokens and keys
        #: a launch leaves stay on the device for the next, a standalone
        #: piece is not waited for, and the scheduler keeps one launch
        #: queued behind the running one. The paged loop and the ``--tp``
        #: programs keep the host's copies and run one launch at a time
        self.ahead = not self.paged and eng.mesh is None
        self._flight: collections.deque = collections.deque()
        self._ahead_tick = False  # step_chunk called from step_ahead
        self._no_fix: dict = {}  # cap -> the fix operand that changes no row
        #: the bucket of a standalone piece dispatched since the last decode
        #: launch (0: none): a 64-token piece runs longer than an 8-token one
        self._piece_pending = 0
        #: launch kind -> its last seconds on the device, where the host saw
        #: both ends or knew it had ended by then
        self._period: dict = {}
        #: when the last collected launch was seen to end, and when the
        #: last launch's dispatch returned (time.monotonic)
        self._last_end = self.dispatched = 0.0
        S = eng.cfg.seq_len
        if self.paged:
            # page size must divide the model context so logical blocks tile
            # it exactly (a partial tail block would misplace the staging
            # copies); halve the requested size until it does
            page = max(1, int(kv_pages))
            while S % page:
                page //= 2
            self.page = page
        if self.bucket_kv:
            # the bucket ladder: powers of two from min_bucket (default: a
            # couple of decode chunks — smaller slabs would migrate every
            # other chunk) up to the full model context
            lo = int(min_bucket) if min_bucket else max(16, 2 * chunk)
            lo = max(2, min(lo, S))
            b = 1
            while b < lo:
                b *= 2
            ladder = []
            while b < S:
                ladder.append(b)
                b *= 2
            ladder.append(S)
            self.buckets = tuple(ladder)
        else:
            self.buckets = (S,)
        #: modeled HBM budget in KV token-slots — what the uniform slab
        #: spends as max_batch full-context rows; bucketed admission packs
        #: strictly more short rows into the same budget
        self.budget_tokens = max_batch * S
        self._reserved_tokens = 0
        self._budget = kv_budget  # duck-typed lifecycle.KVBudget mirror
        self._pools: dict = {}  # ctx -> _BucketPool
        self._slots: dict = {}  # handle -> _SlotState
        self._where: dict = {}  # handle -> (pool, row)
        self._prefills: dict = {}  # handle -> _PendingPrefill (FIFO)
        self._next_handle = 0
        self._closed = False
        self.migrations = 0  # rows moved to a larger bucket, this session
        self.decode_ms = 0.0  # cumulative fused-chunk wall time
        self.prefill_ms = 0.0  # cumulative admit-prefill wall time
        #: (start, end) on time.monotonic of the last prefill_step's piece,
        #: first phase's start to last phase's end: what the scheduler marks
        #: on the request's trace (RequestTrace.mark_prefill_chunk)
        self.piece_span: tuple = (0.0, 0.0)
        # paged-mode telemetry (all stay 0 in slab modes)
        self.prefix_hits = 0  # admits that aliased >= 1 cached page
        self.prefix_misses = 0  # admits with nothing cached to alias
        self.prefix_tokens_matched = 0  # prompt tokens served from cache
        self.cow_copies = 0  # boundary pages privately copied at admit
        self.prefix_evictions = 0  # cached pages LRU-evicted for allocs
        self.regroups = 0  # host-side table moves (the ex-migrations)
        if self.paged:
            # ONE preallocated arena under the same modeled HBM budget the
            # uniform slab spends (+1 scratch page): [L, P, page, kv, hd]
            num_pages = self.budget_tokens // self.page + 1
            self._arena = eng._bucket_cache_init(num_pages, self.page)
            if kv_budget is not None and hasattr(kv_budget, "attach_pages"):
                # the serving accountant owns the free list + refcounts
                # (and publishes them as gauges); the session drives it
                self._alloc = kv_budget.attach_pages(num_pages, self.page)
            else:
                self._alloc = paged_kv.PageAllocator(num_pages, self.page)
            self._radix = paged_kv.RadixPrefixCache(self.page)
            self._pgroups: dict = {}  # nb -> _PagedGroup
            self._rowpages: dict = {}  # handle -> _RowPages
            max_nb = S // self.page
            ladder, nb = [], 1
            while nb < max_nb:
                ladder.append(nb)
                nb *= 2
            ladder.append(max_nb)
            self._nb_ladder = tuple(ladder)
        elif not self.bucket_kv:
            # the classic resident slab, pre-allocated so the pool never
            # grows and handles stay the historical slot indices 0..B-1
            self._pools[S] = _BucketPool(eng, S, max_batch)
            self._publish_kv_resident()

    # -- introspection ----------------------------------------------------
    @property
    def cache(self):
        """The uniform-mode resident slab. Bucketed sessions keep one slab
        per occupied bucket, paged sessions one page arena; neither has a
        single per-session cache to point at."""
        if self._closed or self.bucket_kv or self.paged:
            return None
        return self._pools[self.eng.cfg.seq_len].cache

    @property
    def free_slots(self) -> list:
        """Row indices admit() can take right now (uniform mode: the actual
        free slot indices, the historical contract). Bucketed/paged
        sessions admit by KV budget, not row count — prefer ``can_admit``;
        here the number of smallest admissions (one bucket / one page) that
        still fit is returned as pseudo-indices so ``if sess.free_slots:``
        keeps meaning "can admit something"."""
        if self.paged:
            n = (self._alloc.free_count + self._alloc.evictable_count
                 - self._alloc.reserved_pages)
            return list(range(max(0, n)))
        if not self.bucket_kv:
            pool = self._pools[self.eng.cfg.seq_len]
            return [b for b, h in enumerate(pool.rows) if h is None]
        n = (self.budget_tokens - self._reserved_tokens) // self.buckets[0]
        return list(range(max(0, n)))

    @property
    def occupied(self) -> list:
        """Admitted-and-not-released handles (done + mid-prefill included)."""
        return sorted(self._slots)

    @property
    def num_live(self) -> int:
        """Rows the next step_chunk will actually advance."""
        return sum(1 for st in self._slots.values() if self._decodes(st))

    @staticmethod
    def _decodes(st: _SlotState) -> bool:
        """Whether the next launch advances the row: prefilled, not done,
        and its budget not spent by the launches already dispatched (a
        launch that runs ahead of its collection knows its count)."""
        return not st.done and not st.prefilling and st.offered < st.budget

    @property
    def in_flight(self) -> int:
        """Decode launches dispatched and not yet collected."""
        return len(self._flight)

    @property
    def pending_prefills(self) -> list:
        """Handles admitted via admit_begin whose prompts are still being
        consumed, oldest first."""
        return list(self._prefills)

    @property
    def reserved_tokens(self) -> int:
        """KV token-slots currently reserved against ``budget_tokens``."""
        return self._reserved_tokens

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of paged admits that aliased >= 1 cached page (0.0 in
        slab modes and before any admission)."""
        n = self.prefix_hits + self.prefix_misses
        return self.prefix_hits / n if n else 0.0

    def page_stats(self) -> dict:
        """Paged-mode occupancy snapshot for /stats and /ready ({} in slab
        modes): allocator page counts, radix-tree size, per-window resident
        rows, and the prefix-cache counters."""
        if not self.paged:
            return {}
        s = self._alloc.stats()
        s["radix_nodes"] = len(self._radix)
        s["rows_per_window"] = {
            str(nb * self.page): sum(1 for h in g.rows if h is not None)
            for nb, g in sorted(self._pgroups.items())}
        s["prefix_hits"] = self.prefix_hits
        s["prefix_misses"] = self.prefix_misses
        s["prefix_hit_rate"] = self.prefix_hit_rate
        s["prefix_tokens_matched"] = self.prefix_tokens_matched
        s["cow_copies"] = self.cow_copies
        s["prefix_evictions"] = self.prefix_evictions
        s["regroups"] = self.regroups
        return s

    def _state(self, slot: int) -> _SlotState:
        st = self._slots.get(slot)
        if st is None:
            raise ValueError(f"slot {slot} is not occupied")
        return st

    def is_done(self, slot: int) -> bool:
        """True once the row hit its stop token, budget, or quarantine (it no
        longer receives tokens; release() it to free the slab)."""
        return self._state(slot).done

    def finish_reason(self, slot: int) -> Optional[str]:
        """Why the row finished: ``"stop"``, ``"length"``, ``"error"``
        (watchdog quarantine), or None while still live / after cancel()."""
        return self._state(slot).finish

    def prefill_ms_of(self, slot: int) -> float:
        """Wall time this row's admission prefill has consumed so far."""
        return self._state(slot).prefill_ms

    # -- capacity ---------------------------------------------------------
    def _need_ctx(self, prompt_len: int, steps: int) -> int:
        """Context slots the row can reach: its final write position + 1."""
        S = self.eng.cfg.seq_len
        return max(prompt_len, min(S, prompt_len - 1 + max(0, steps)))

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def can_admit(self, prompt_len: int, steps: int,
                  prompt_tokens: Optional[list] = None) -> bool:
        """True when the session's modeled KV budget (and the external
        kv_budget, if any) has room for this request's WORST-CASE need —
        admission reserves the bucket (or private page count) covering
        prompt+steps up front so later growth can never oversubscribe.
        Paged sessions reserve only the pages the radix prefix cache can't
        alias; pass ``prompt_tokens`` to let this check count the match
        (without it the answer is conservative: zero match assumed)."""
        if self._closed:
            return False
        if self.paged:
            priv, full, _ = self._plan_pages(prompt_len, steps,
                                             prompt_tokens)
            # matched evictable pages would be pinned by this admit, leaving
            # the availability pool — count them alongside the private need
            pinned = sum(1 for n in full
                         if self._alloc.refcount(n.page) == 0)
            if not self._alloc.can_reserve(priv + pinned):
                return False
            if self._budget is not None and not self._budget.can_fit(
                    priv * self.page):
                return False
            return True
        need = self._bucket_for(self._need_ctx(prompt_len, steps))
        if self._reserved_tokens + need > self.budget_tokens:
            return False
        if self._budget is not None and not self._budget.can_fit(need):
            return False
        return True

    # -- paged-mode internals ---------------------------------------------
    def _plan_pages(self, prompt_len: int, steps: int,
                    prompt_tokens: Optional[list]) -> tuple:
        """(private pages to reserve, aliasable full-prefix nodes, COW
        boundary node) for a prospective paged admission. ``full`` nodes
        cache blocks strictly below position prompt_len-1 (never written by
        this row — safe to alias); the COW node, when the prompt ends flush
        on the next cached block, is copied privately instead (its last
        slot is the pending token's write target)."""
        need = paged_kv.pages_for(
            self._need_ctx(prompt_len, steps), self.page)
        if prompt_tokens is None:
            return need, [], None
        path = self._radix.match(prompt_tokens)
        nfull = min(len(path), (prompt_len - 1) // self.page)
        full = path[:nfull]
        cow = None
        if len(path) > nfull and (nfull + 1) * self.page == prompt_len:
            cow = path[nfull]
        return need - nfull, full, cow

    def _page_alloc(self, rp: _RowPages) -> int:
        """One private arena page for ``rp``'s row, evicting LRU prefix-
        cache pages if the free list is dry — guaranteed to succeed for a
        reserved row (admission counted free + evictable)."""
        faults.fire("page_alloc")
        p = self._alloc.alloc()
        if p is None:
            freed = self._radix.evict(1, self._alloc)
            self.prefix_evictions += freed
            if self.eng._m_prefix_evictions is not None and freed:
                self.eng._m_prefix_evictions.inc(freed)
            p = self._alloc.alloc()
        if p is None:
            raise RuntimeError(
                "paged KV pool exhausted despite admission reservation — "
                "page accounting bug")
        rp.outstanding = max(0, rp.outstanding - 1)
        return p

    def _nb_for(self, blocks: int) -> int:
        for nb in self._nb_ladder:
            if nb >= blocks:
                return nb
        return self._nb_ladder[-1]

    def _pad_pages(self, pages: list, offs: Optional[list] = None):
        """Scratch-pad a page (and optional offset) list to the next power
        of two so the batched admit copies (Engine._pages_to_single /
        _single_to_pages) compile one program per size bucket instead of
        one per distinct prefix length. Padded entries resolve to the
        scratch page — garbage writes/reads the copy helpers mask or the
        arena convention already tolerates."""
        n = max(1, len(pages))
        m = 1
        while m < n:
            m *= 2
        pad = m - len(pages)
        out = jnp.asarray(pages + [paged_kv.SCRATCH_PAGE] * pad, jnp.int32)
        if offs is None:
            return out
        return out, jnp.asarray(offs + [0] * pad, jnp.int32)

    def _alloc_prow(self, nb: int) -> tuple:
        """A free row in the ``nb``-block group, materializing/growing it
        on demand (mirrors _alloc_row)."""
        g = self._pgroups.get(nb)
        if g is None:
            g = self._pgroups[nb] = _PagedGroup(nb, 1, self.page)
        for r in range(g.cap):
            if g.rows[r] is None:
                return g, r
        r = g.cap
        g.grow(self.page)
        return g, r

    def _sync_table(self, handle: int) -> None:
        """Mirror the row's logical block list into its group's device-
        bound page table (scratch-padded past the allocated tail)."""
        g, r = self._where[handle]
        rp = self._rowpages[handle]
        g.tables[r, :] = paged_kv.SCRATCH_PAGE
        n = min(len(rp.blocks), g.nb)
        g.tables[r, :n] = rp.blocks[:n]

    def _regroup(self, handle: int, nb: int) -> None:
        """Move a growing row to a wider window group. Pure host-side
        state: the KV never moves (it lives in arena pages) — this is what
        killed the bucket-migration copy."""
        src, srow = self._where[handle]
        dst, drow = self._alloc_prow(nb)
        dst.tokens[drow] = src.tokens[srow]
        dst.pos[drow] = src.pos[srow]
        dst.keys[drow] = src.keys[srow]
        dst.temps[drow] = src.temps[srow]
        dst.topps[drow] = src.topps[srow]
        dst.rows[drow] = handle
        src.rows[srow] = None
        src.pos[srow] = src.nb * self.page - 1
        src.tables[srow, :] = paged_kv.SCRATCH_PAGE
        self._where[handle] = (dst, drow)
        self.regroups += 1
        self._sync_table(handle)

    def _finish_pages(self, handle: int, prompt_tokens: list,
                      staging: Optional[dict] = None) -> None:
        """Complete a paged row's table through its pending-token block:
        allocate the private tail pages, scatter the staging cache's
        prefilled blocks into them (``staging`` None on the no-prefill
        paths — fully cached or 1-token prompts, whose block contents are
        either aliased/COW-copied already or written by the first decode
        step before anything attends them), then publish every fully-
        prompt-covered block into the radix tree."""
        rp = self._rowpages[handle]
        plen = len(prompt_tokens)
        total = (plen - 1) // self.page + 1
        # allocation stays a host loop (per-page fault seam + allocator
        # bookkeeping); the device scatters coalesce into ONE dispatch below
        scat_pages: list = []
        scat_offs: list = []
        for b in range(len(rp.blocks), total):
            p = self._page_alloc(rp)
            if staging is not None and b * self.page < plen - 1:
                scat_pages.append(p)
                scat_offs.append(b * self.page)
            rp.blocks.append(p)
        if scat_pages:
            pages, offs = self._pad_pages(scat_pages, scat_offs)
            self._arena = self.eng._single_to_pages(
                self._arena, staging, pages, offs)
        # blocks with (b+1)*page <= plen-1 hold immutable prompt KV (this
        # row only writes at pos >= plen-1): cacheable for future admits
        nins = (plen - 1) // self.page
        for p in self._radix.insert(prompt_tokens, rp.blocks[:nins]):
            self._alloc.hold(p)
        self._sync_table(handle)

    def _alloc_row(self, ctx: int) -> tuple:
        """A free row in the ``ctx`` pool, materializing/growing it on
        demand (bucketed mode; the uniform pool is pre-sized)."""
        pool = self._pools.get(ctx)
        if pool is None:
            pool = self._pools[ctx] = _BucketPool(self.eng, ctx, 1)
            self._publish_kv_resident()
        for r in range(pool.cap):
            if pool.rows[r] is None:
                return pool, r
        r = pool.cap
        self._pull(pool)
        pool.grow(self.eng)
        self._publish_kv_resident()
        return pool, r

    def _pull(self, pool: _BucketPool) -> None:
        """Bring a pool's tokens and keys from the device to its host
        arrays, reading the launch in flight first (a growing pool and a
        migrating row need them there); rows waiting for an override keep
        theirs. The pool's next launch uploads them all."""
        if pool.dev is None:
            return
        toks, keys = (np.asarray(a) for a in pool.dev)
        keep = ~pool.fix
        pool.tokens[keep] = toks[keep]
        pool.keys[keep] = keys[keep]
        pool.dev = None

    def kv_resident_bytes(self) -> dict:
        """Bytes of the slot pools' caches by attention kind: a uniform
        model's are all ``full``; a window layer's rings (``window``) are
        the same whatever the pools' contexts; a latent layer's (``latent``)
        one shared vector a token."""
        out = {"full": 0, "window": 0}
        for pool in self._pools.values():
            for kind, n in layer_plan.kv_resident_bytes(pool.cache).items():
                out[kind] = out.get(kind, 0) + n
        return out

    def _publish_kv_resident(self) -> None:
        if self.eng._m_kv_resident is not None:
            for kind, n in self.kv_resident_bytes().items():
                self.eng._m_kv_resident.set(n, kind=kind)

    # -- lifecycle --------------------------------------------------------
    def admit(self, prompt_tokens: list, steps: int,
              sampler: Optional[SamplerConfig] = None,
              stop_tokens: tuple = ()) -> int:
        """Prefill ``prompt_tokens`` into a free row and return its handle
        (uniform mode: the slot index, the historical contract).

        The prompt's prefix runs through the engine's bucketed prefill into
        a fresh single cache, written straight into the row's slab (donated
        in-place update); the last prompt token stays pending so the row's
        first fused step samples from the final-prompt-position logits with
        the FIRST key of a fresh PRNGKey(sampler.seed) chain — the exact
        schedule a solo ``generate`` walks (``sampler`` defaults to the
        engine's SamplerConfig). ``steps``/``stop_tokens`` are this row's
        private budget and stop set, checked per chunk like generate_batch's
        row_steps/stop_tokens.

        Equivalent to ``admit_begin`` + prefill_step(handle, whole-prefix):
        the entire prompt runs before this returns, stalling the pool for
        the whole prefill — use admit_begin/prefill_step when resident rows
        shouldn't wait. Raises RuntimeError when nothing can be admitted
        (check ``can_admit`` / ``free_slots``).
        """
        handle = self.admit_begin(prompt_tokens, steps, sampler=sampler,
                                  stop_tokens=stop_tokens)
        while self._slots[handle].prefilling:
            self.prefill_step(handle, budget=len(prompt_tokens))
        return handle

    def admit_begin(self, prompt_tokens: list, steps: int,
                    sampler: Optional[SamplerConfig] = None,
                    stop_tokens: tuple = (), span_id: int = 0) -> int:
        """Reserve a row for the prompt WITHOUT prefilling it: the prompt
        is consumed incrementally, so resident rows keep emitting tokens
        while it fills its cache. One of two ways, decided at the prompt's
        first tokens and kept. If its pool launches a decode chunk then (a
        row of it decodes, or a prompt already rides in it) and the
        session's prompts can ride (``ride_t``), ``step_chunk`` carries
        ``ride_t`` of its tokens in each step of the pool's chunks and
        writes their K/V straight into the reserved row: no staging cache,
        no insert, no pass of its own over the weights. Otherwise
        ``prefill_step`` runs standalone pieces into a staging cache between
        chunks and inserts it into the row when the prefix is complete.
        Once live, the row's stream is that of a monolithic admit() of the
        same request: every prompt token's K/V is computed at its own
        position and written before any later query attends, and the
        sampler chain starts from the same fresh PRNGKey. 1-token prompts
        have nothing to prefill and go live immediately. ``span_id`` (the
        request's ``RequestTrace`` track) is carried by the row's prefill
        and go-live phase spans, so a tick's spans and the request's track
        can be joined."""
        if self._closed:
            raise RuntimeError("batch session is closed")
        if not prompt_tokens:
            raise ValueError("admit needs a non-empty prompt")
        S = self.eng.cfg.seq_len
        if len(prompt_tokens) > S:
            raise ValueError(
                f"prompt of {len(prompt_tokens)} tokens exceeds seq_len {S}")
        if not self.can_admit(len(prompt_tokens), steps,
                              list(prompt_tokens) if self.paged else None):
            raise RuntimeError(
                f"no free slot (max_batch={self.max_batch}, KV budget "
                f"{self._reserved_tokens}/{self.budget_tokens} tokens); "
                "release a finished row first")
        faults.fire("admit")
        scfg = sampler if sampler is not None else self.eng.sampler_cfg
        if self.paged:
            handle = self._admit_begin_paged(list(prompt_tokens), steps, scfg,
                                             tuple(stop_tokens))
            self._slots[handle].span_id = span_id
            return handle
        plen = len(prompt_tokens)
        reserved = self._bucket_for(self._need_ctx(plen, steps))
        # place optimistically small: enough for the prompt plus one decode
        # chunk of headroom — early-stopping rows never touch a big slab;
        # migration (covered by the reservation) grows the long-lived ones
        place = self._bucket_for(min(reserved, plen + self.chunk))
        pool, row = self._alloc_row(place)
        handle = row if not self.bucket_kv else self._next_handle
        self._next_handle += 1
        self._reserved_tokens += reserved
        if self._budget is not None:
            self._budget.reserve(reserved)
            self._budget.place(pool.ctx)
        pos0 = plen - 1
        room = S - pos0
        budget = min(room, steps)
        st = _SlotState(
            room=room, budget=budget, stop_tokens=tuple(stop_tokens),
            reserved=reserved, span_id=span_id,
            done=budget <= 0, finish="length" if budget <= 0 else None)
        self._slots[handle] = st
        self._where[handle] = (pool, row)
        pool.rows[row] = handle
        if budget <= 0:
            return handle  # never decodes; skip the prefill entirely
        if plen == 1:
            self._go_live(handle, prompt_tokens, scfg)
        else:
            faults.fire("prefill")
            st.prefilling = True
            # where prompts can ride, a staging cache waits for the row's
            # first standalone piece: a riding row never has one
            self._prefills[handle] = _PendingPrefill(
                list(prompt_tokens), scfg,
                None if self.ride_t else self.eng.new_cache())
        return handle

    def _admit_begin_paged(self, prompt_tokens: list, steps: int,
                           scfg: SamplerConfig, stop_tokens: tuple) -> int:
        """Paged admission: walk the radix tree, alias the cached prefix,
        reserve only the private tail, and prefill only what the cache
        can't serve. The aliased blocks all sit strictly below position
        plen-1 — this row never writes there (write-before-attend starts at
        the pending token), so sharing is read-only by construction and the
        live stream stays bit-identical to a cold prefill."""
        S = self.eng.cfg.seq_len
        plen = len(prompt_tokens)
        faults.fire("prefix_match")
        priv, full, cow = self._plan_pages(plen, steps, prompt_tokens)
        # pin the aliased prefix FIRST: pinning pulls evictable pages out
        # of the availability pool, so the reservation check below is exact
        # with the pins already in place
        for n in full:
            self._alloc.ref(n.page)
        if not self._alloc.can_reserve(priv) or (
                self._budget is not None
                and not self._budget.can_fit(priv * self.page)):
            for n in full:
                self._alloc.unref(n.page)
            raise RuntimeError(
                f"no free KV pages ({self._alloc.free_count} free + "
                f"{self._alloc.evictable_count} evictable, "
                f"{self._alloc.reserved_pages} reserved, need {priv}); "
                "release a finished row first")
        self._alloc.reserve(priv)
        reserved = priv * self.page
        self._reserved_tokens += reserved
        if self._budget is not None:
            self._budget.reserve(reserved)
        need_ctx = self._need_ctx(plen, steps)
        rp = _RowPages([n.page for n in full], priv, need_ctx, plen)
        # place in a window sized for the prompt plus one chunk of headroom
        # — regroup (a host-side table move) widens the long-lived rows
        place = min(need_ctx, plen + self.chunk)
        g, row = self._alloc_prow(
            self._nb_for(paged_kv.pages_for(place, self.page)))
        handle = self._next_handle
        self._next_handle += 1
        pos0 = plen - 1
        room = S - pos0
        budget = min(room, steps)
        st = _SlotState(
            room=room, budget=budget, stop_tokens=stop_tokens,
            reserved=reserved,
            done=budget <= 0, finish="length" if budget <= 0 else None)
        self._slots[handle] = st
        self._where[handle] = (g, row)
        self._rowpages[handle] = rp
        g.rows[row] = handle
        if budget <= 0:
            return handle  # never decodes; pages stay pinned until release
        cached = len(full) * self.page
        if cow is not None:
            # the prompt ends flush on a cached block whose last slot is
            # this row's first write target: duplicate it privately
            p = self._page_alloc(rp)
            self._arena = self.eng._page_copy(
                self._arena, jnp.int32(p), jnp.int32(cow.page))
            rp.blocks.append(p)
            cached = plen - 1
            self.cow_copies += 1
            if self.eng._m_cow is not None:
                self.eng._m_cow.inc()
        matched = min(cached, plen - 1)
        if matched > 0:
            self.prefix_hits += 1
            self.prefix_tokens_matched += matched
            if self.eng._m_prefix_hits is not None:
                self.eng._m_prefix_hits.inc()
                self.eng._m_prefix_tokens.inc(matched)
        else:
            self.prefix_misses += 1
            if self.eng._m_prefix_misses is not None:
                self.eng._m_prefix_misses.inc()
        if plen == 1 or cached >= plen - 1:
            # nothing left to prefill: every attended prefix position is
            # aliased (or COW-copied) — allocate the tail and go live
            self._finish_pages(handle, prompt_tokens)
            self._go_live(handle, prompt_tokens, scfg)
            return handle
        faults.fire("prefill")
        st.prefilling = True
        staging = self.eng.new_cache()
        if full:
            # preload ALL aliased blocks in one gather dispatch so the
            # chunked prefill continues at ``cached`` over the exact KV a
            # cold prefill would have written (the chunked==monolithic
            # invariant then carries) — a W-block warm prefix costs O(1)
            # dispatches, not O(W)
            staging = self.eng._pages_to_single(
                staging, self._arena,
                self._pad_pages([n.page for n in full]),
                jnp.int32(len(full) * self.page))
        pf = _PendingPrefill(prompt_tokens, scfg, staging)
        pf.cursor = cached
        pf.scattered = cached
        # publish-at-admit: allocate the row's fully-prompt-covered tail
        # blocks NOW and hang them in the radix tree ready=False, so a
        # concurrent admit of the same prefix aliases each block the
        # moment the chunk that fills it lands (COW sharing while BOTH
        # rows are live, not only after this row's go-live)
        nins = (plen - 1) // self.page
        for _ in range(len(rp.blocks), nins):
            rp.blocks.append(self._page_alloc(rp))
        pf.pub_nodes = self._radix.publish_pending(
            prompt_tokens, rp.blocks[:nins])
        for n in pf.pub_nodes:
            if n is not None:
                self._alloc.hold(n.page)
        self._prefills[handle] = pf
        return handle

    def prefill_step(self, handle: Optional[int] = None,
                     budget: Optional[int] = None) -> Optional[tuple]:
        """Advance ONE pending chunked admission by up to ``budget`` prompt
        tokens (default: the session's prefill_chunk; the whole remaining
        prefix when neither is set) — one bucketed prefill forward into the
        admission's own single cache, synced before returning so the call
        bounds the scheduler tick (not where the session runs ahead: the
        device runs it in order, before the next decode launch). Returns
        (handle, finished); ``finished``
        True means the row just went live (its slab is written; the next
        step_chunk decodes it). Returns None when nothing is pending.
        Picks the OLDEST pending admission when ``handle`` is None — FIFO,
        so one call per scheduler tick bounds every resident row's stall to
        one prefill chunk of compute.

        Where prompts can ride (``ride_t``) this is the way of a prompt
        whose pool launches no chunk at its first tokens: nothing decodes
        there whose inter-token gap a separate pass would stretch, and a
        chunk would carry ``chunk x ride_t`` tokens at the price of a whole
        decode launch. A prompt whose pool does launch is left to
        ``step_chunk`` and skipped here, so with every pending prompt riding
        this returns None. The way is decided at the prompt's first tokens
        and kept; naming a ``handle`` that has not begun decides it for
        standalone pieces."""
        if self._closed:
            raise RuntimeError("batch session is closed")
        if handle is None:
            handle = next((h for h, pf in self._prefills.items()
                           if not self._slots[h].done
                           and not self._takes_ride(h, pf)), None)
            if handle is None:
                return None
        pf = self._prefills.get(handle)
        if pf is None:
            raise ValueError(f"slot {handle} has no pending prefill")
        if pf.rides:
            raise ValueError(f"slot {handle}'s prompt rides the decode "
                             "chunks (step_chunk advances it)")
        st = self._slots[handle]
        faults.fire("prefill_chunk")
        with observability.phase("prefill_dispatch", "engine",
                                 span_id=st.span_id) as dispatch:
            pf.rides = False
            if pf.cache is None:
                pf.cache = self.eng.new_cache()
            prefix = pf.prompt[:-1]
            n = budget if budget is not None else self.prefill_chunk
            if n <= 0:
                n = len(prefix) - pf.cursor
            if self.eng.prefill_piece_cap:
                n = min(n, self.eng.prefill_piece_cap)
            piece = prefix[pf.cursor:pf.cursor + n]
            # where prompts ride, a standalone piece is the rare case (a
            # burst at an idle pool) and the last piece of a prompt would
            # be a program of its own for each bucket under the piece's
            # that no warm-up is sure to have met: every piece is padded to
            # the whole piece's bucket, one program, at the same price (a
            # piece streams the planes once whatever its rows)
            _, pf.cache = self.eng._prefill_piece(
                pf.cache, piece, pf.cursor,
                bucket=prefill_bucket(n) if self.ride_t else None)
            observability.launched()
        ended = dispatch
        if self.ahead:
            # not waited for: the piece, its insert and the decode launch
            # after it queue on the device in that order, so a row whose
            # prefix ends here decodes in that launch
            self._piece_pending = self.eng._last_prefill_bucket
        else:
            with observability.phase("prefill_wait", "engine", "device",
                                     span_id=st.span_id) as ended:
                jax.block_until_ready(pf.cache)
        # the piece's wall time IS its phases (where it is not waited for,
        # its dispatch): one pair of clock reads
        dt = (ended.t1 - dispatch.t0) * 1000.0
        with observability.phase("prefill_land", "engine",
                                 span_id=st.span_id) as last:
            self.prefill_ms += dt
            st.prefill_ms += dt
            if self.eng._m_prefill_chunk is not None:
                self.eng._m_prefill_chunk.observe(dt)
                self.eng._m_prefill_tokens.inc(len(piece), how="piece")
            pf.cursor += len(piece)
            if self.paged:
                self._scatter_published(handle, pf)
            finished = pf.cursor >= len(prefix)
            if finished:
                # prefix complete: land the filled single cache in the row's KV
                if self.paged:
                    # scatter the staging blocks into freshly allocated arena
                    # pages (the aliased prefix blocks are already in place)
                    # and publish the fully-covered ones to the radix tree
                    self._finish_pages(handle, pf.prompt, staging=pf.cache)
                else:
                    pool, row = self._where[handle]
                    pool.cache = self.eng._batch_cache_insert(
                        pool.cache, pf.cache, jnp.int32(row))
                del self._prefills[handle]
                st.prefilling = False
        if finished:
            last = self._go_live_phase(handle, pf)
        self.piece_span = (dispatch.t0, last.t1)
        return handle, finished

    def _takes_ride(self, handle: int, pf: _PendingPrefill) -> bool:
        """Whether this pending prompt reaches its row by riding the pool's
        decode chunks. Undecided, it will if its pool launches a chunk this
        tick (``_pool_launches``): ``step_chunk`` then gives it its first
        tokens and records the way."""
        if pf.rides is not None:
            return pf.rides
        return self.ride_t > 0 and self._pool_launches(self._where[handle][0])

    def _pool_launches(self, pool) -> bool:
        """A pool's next step_chunk runs its program when a row of it
        decodes, or when a prompt already rides in it: a rider whose pool
        lost its last live row has the chunk launched for it."""
        for h in pool.rows:
            if h is None:
                continue
            st = self._slots[h]
            if self._decodes(st) or (st.prefilling and not st.done
                                     and self._prefills[h].rides):
                return True
        return False

    def _scatter_published(self, handle: int, pf: _PendingPrefill) -> None:
        """Land the staging cache's newly completed full blocks in their
        (already published, ready=False) arena pages and flip the nodes
        ready — the other half of publish-at-admit: a concurrent admit
        aliases each block as soon as the prefill chunk that filled it
        returns. One batched scatter per chunk; blocks another row's
        pending node shadowed (pub_nodes None) still get their private
        scatter, they just never become cache."""
        rp = self._rowpages[handle]
        plen = len(pf.prompt)
        nins = (plen - 1) // self.page
        done = min(pf.cursor // self.page, nins)
        start = pf.scattered // self.page
        if done <= start:
            return
        pages, offs = self._pad_pages(
            [rp.blocks[b] for b in range(start, done)],
            [b * self.page for b in range(start, done)])
        self._arena = self.eng._single_to_pages(
            self._arena, pf.cache, pages, offs)
        for b in range(start, done):
            n = pf.pub_nodes[b] if b < len(pf.pub_nodes) else None
            if n is not None:
                n.ready = True
        pf.scattered = done * self.page

    # -- migration (disaggregated serving) --------------------------------
    def export_row(self, handle: int, fire_fault: bool = True) -> dict:
        """Snapshot a live paged row for migration to a sibling replica:
        its page payloads (host numpy, arena leaf order), page-table
        geometry, and the decode state a solo run would carry across the
        next chunk boundary — pending token, position, the row's ADVANCED
        per-row sampler chain, and the budget/stop accounting. Importing
        the snapshot with :meth:`admit_from_export` on a session with the
        same model and chunk size continues the stream bit-identically to
        the row never having moved. The row itself is untouched — the
        caller releases it once the transfer is acknowledged (a failed
        transfer loses nothing). ``fire_fault=False`` skips the
        ``kv_export`` fault seam — the mid-stream checkpoint path fires
        its own ``ckpt_write`` seam instead, so each export flavor is
        drilled (and counted) separately."""
        if not self.paged:
            raise RuntimeError(
                "export_row needs a paged session (--kv-pages)")
        self._settle()
        st = self._state(handle)
        if st.prefilling:
            raise RuntimeError(f"slot {handle} is still prefilling")
        if st.done:
            raise RuntimeError(
                f"slot {handle} already finished — nothing to migrate")
        if fire_fault:
            faults.fire("kv_export")
        g, r = self._where[handle]
        rp = self._rowpages[handle]
        idx = jnp.asarray(rp.blocks, jnp.int32)
        leaves = [np.asarray(jnp.take(leaf, idx, axis=1))
                  for leaf in jax.tree.leaves(self._arena)]
        return {
            "page_tokens": self.page,
            "n_blocks": len(rp.blocks),
            "plen": rp.plen,
            "pos": int(g.pos[r]),
            "token": int(g.tokens[r]),
            "keys": [int(g.keys[r, 0]), int(g.keys[r, 1])],
            "temp": float(g.temps[r]),
            "topp": float(g.topps[r]),
            "room": int(st.room),
            "budget": int(st.budget),
            "offered": int(st.offered),
            "emitted": int(st.emitted),
            "stop_tokens": list(st.stop_tokens),
            "leaves": leaves,
        }

    def admit_from_export(self, prompt_tokens: list, snap: dict) -> int:
        """Admit a row exported by a sibling replica WARM: alias every
        full prompt block the local radix cache already holds (the wire
        payload for those blocks is dropped — the local pages are exact),
        allocate private pages for the rest, scatter the imported
        payloads in ONE dispatch, publish the prompt blocks into the
        local radix tree, and arm the row with the carried decode state.
        Decoding then continues bit-identically to the exporting replica
        having kept the row (both replicas run the same serve config, so
        chunk boundaries — and with them the sampler-chain schedule —
        line up). Raises RuntimeError when the local pool can't fit the
        row; the caller falls back to re-prefilling."""
        if not self.paged:
            raise RuntimeError(
                "admit_from_export needs a paged session (--kv-pages)")
        if self._closed:
            raise RuntimeError("batch session is closed")
        if int(snap["page_tokens"]) != self.page:
            raise ValueError(
                f"page size mismatch: wire {snap['page_tokens']} vs "
                f"local {self.page}")
        plen = int(snap["plen"])
        if plen != len(prompt_tokens):
            raise ValueError(
                f"snapshot prompt length {plen} != {len(prompt_tokens)}")
        budget = int(snap["budget"])
        if int(snap["emitted"]) >= budget:
            raise ValueError("snapshot row already finished")
        faults.fire("kv_import")
        nblk = int(snap["n_blocks"])
        cap_tokens = max(plen, plen - 1 + budget)
        total = paged_kv.pages_for(cap_tokens, self.page)
        # alias what the local cache already holds (blocks strictly below
        # plen-1, never written by this row); everything else — the
        # decode-written tail included — imports privately
        path = self._radix.match(prompt_tokens)
        nfull = min(len(path), (plen - 1) // self.page, nblk)
        full = path[:nfull]
        priv = total - nfull
        for n in full:
            self._alloc.ref(n.page)
        if not self._alloc.can_reserve(priv) or (
                self._budget is not None
                and not self._budget.can_fit(priv * self.page)):
            for n in full:
                self._alloc.unref(n.page)
            raise RuntimeError(
                f"no free KV pages for imported row "
                f"({self._alloc.free_count} free + "
                f"{self._alloc.evictable_count} evictable, need {priv})")
        self._alloc.reserve(priv)
        reserved = priv * self.page
        self._reserved_tokens += reserved
        if self._budget is not None:
            self._budget.reserve(reserved)
        rp = _RowPages([n.page for n in full], priv, cap_tokens, plen)
        for b in range(nfull, nblk):
            rp.blocks.append(self._page_alloc(rp))
        if nblk > nfull:
            pages = self._pad_pages(rp.blocks[nfull:nblk])
            m = int(pages.shape[0])
            blob = []
            for leaf in snap["leaves"]:
                x = np.asarray(leaf)[:, nfull:nblk]
                if m > x.shape[1]:
                    pad = np.zeros(
                        (x.shape[0], m - x.shape[1]) + x.shape[2:],
                        x.dtype)
                    x = np.concatenate([x, pad], axis=1)
                blob.append(x)
            self._arena = self.eng._pages_import(
                self._arena, pages,
                jax.tree.unflatten(jax.tree.structure(self._arena), blob))
        g, row = self._alloc_prow(self._nb_for(max(1, len(rp.blocks))))
        handle = self._next_handle
        self._next_handle += 1
        st = _SlotState(
            room=int(snap["room"]), budget=budget,
            stop_tokens=tuple(snap["stop_tokens"]), reserved=reserved,
            offered=int(snap["offered"]), emitted=int(snap["emitted"]))
        self._slots[handle] = st
        self._where[handle] = (g, row)
        self._rowpages[handle] = rp
        g.rows[row] = handle
        g.tokens[row] = int(snap["token"])
        g.pos[row] = int(snap["pos"])
        g.keys[row] = np.asarray(snap["keys"], np.uint32)
        g.temps[row] = float(snap["temp"])
        g.topps[row] = float(snap["topp"])
        self._sync_table(handle)
        # the imported prompt blocks are valid local KV now: publish them
        # so future admits (and imports) of the same prefix alias local
        # pages instead of paying the wire or a re-prefill again
        nins = min((plen - 1) // self.page, len(rp.blocks))
        for p in self._radix.insert(prompt_tokens, rp.blocks[:nins]):
            self._alloc.hold(p)
        matched = nfull * self.page
        if matched > 0:
            self.prefix_hits += 1
            self.prefix_tokens_matched += matched
            if self.eng._m_prefix_hits is not None:
                self.eng._m_prefix_hits.inc()
                self.eng._m_prefix_tokens.inc(matched)
        else:
            self.prefix_misses += 1
            if self.eng._m_prefix_misses is not None:
                self.eng._m_prefix_misses.inc()
        return handle

    def _go_live_phase(self, handle: int, pf: _PendingPrefill):
        """The ``go_live`` phase of the tick whose standalone piece ends a
        prompt: the row's decode state and the request's whole prefill
        time. (A prompt that rides goes live as the launch that ends it is
        dispatched, and its prefill time is counted as that launch is
        collected.)"""
        st = self._slots[handle]
        with observability.phase("go_live", "engine",
                                 span_id=st.span_id) as phase:
            self._go_live(handle, pf.prompt, pf.scfg)
            if self.eng._m_prefill is not None:
                self.eng._m_prefill.observe(st.prefill_ms)
        return phase

    def _go_live(self, handle: int, prompt_tokens: list,
                 scfg: SamplerConfig) -> None:
        """Arm the row's decode state: pending last prompt token, position,
        fresh per-row sampler chain — the exact state a monolithic admit
        leaves behind, its key worked out on the host (``host_prng_key``).
        A slot pool's next launch takes the token and key from here."""
        pool, row = self._where[handle]
        pool.tokens[row] = int(prompt_tokens[-1])
        pool.pos[row] = len(prompt_tokens) - 1
        pool.keys[row] = host_prng_key(scfg.seed)
        pool.temps[row] = scfg.temperature
        pool.topps[row] = scfg.topp
        if not self.paged:
            pool.fix[row] = True
            pool.sampling = None

    def _migrate(self, handle: int) -> None:
        """Move a live row into the next bucket BEFORE it outgrows its
        slab: copy its [L, 1, ctx, kv, hd] slab — its entire attended
        history — into a row of the bigger pool and carry the host decode
        state (pending token, position, sampler chain) unchanged, so the
        stream continues bit-identically. Admission reserved the worst-case
        bucket up front, so migration never oversubscribes the budget.
        Where the token and key live on the device they are read from the
        launch in flight first."""
        src, srow = self._where[handle]
        S = self.eng.cfg.seq_len
        need = min(S, int(src.pos[srow]) + self.chunk + 1)
        new_ctx = min(b for b in self.buckets
                      if b > src.ctx and b >= need)
        dst, drow = self._alloc_row(new_ctx)
        self._pull(src)
        dst.cache = self.eng._bucket_cache_migrate(
            dst.cache, src.cache, jnp.int32(srow), jnp.int32(drow))
        dst.tokens[drow] = src.tokens[srow]
        dst.pos[drow] = src.pos[srow]
        dst.keys[drow] = src.keys[srow]
        dst.temps[drow] = src.temps[srow]
        dst.topps[drow] = src.topps[srow]
        dst.fix[drow] = True
        dst.sampling = None
        dst.rows[drow] = handle
        src.rows[srow] = None
        src.pos[srow] = src.ctx - 1
        self._where[handle] = (dst, drow)
        self.migrations += 1
        if self.eng._m_migrations is not None:
            self.eng._m_migrations.inc()
        if self._budget is not None:
            self._budget.migrate(src.ctx, dst.ctx)

    def step_chunk(self) -> dict:
        """Run ONE fused chunk over every occupied pool and return
        {handle: fresh tokens} for every live row — each list is already
        truncated at the row's own budget and (inclusively) at its first
        stop token, and is never empty UNLESS the row was quarantined: a
        healthy live row always nets at least one token per chunk, so
        staggered admission can never starve a row. Rows that just finished
        are marked done (``is_done``) and skip future chunks until
        released; ``finish_reason`` says why. Returns {} without touching
        the device when nothing is live. Mid-prefill rows are skipped until
        their prefill completes. A slot pool's chunk is ``launch`` then
        ``collect``, read at once (with launches already in flight, every
        one of them is read, in order).

        The prompt rides the chunk (``ride_t`` > 0): a pool that launches
        carries, in each step, up to ``ride_t`` tokens of the oldest pending
        prompt whose row lives in that pool and which did not begin in a
        staging cache; where its prefix ends the next prompt starts at the
        next step of the same chunk. The K/V land in the rows' own slabs.
        As the launch is dispatched the cursors advance and a row whose
        prefix it completes goes live: it decodes from the next chunk. A
        pool whose only work is a riding prompt launches for it. ``rode``
        lists what the riders of this call did.

        Bucketed sessions first migrate any live row that would outgrow its
        slab within this chunk, then run one program per occupied bucket,
        smallest first — a row migrated this tick decodes this tick, in its
        new pool.

        Quarantine: a row whose watchdog flag went non-finite this chunk is
        marked done with finish reason ``"error"`` and emits NOTHING from
        the chunk (its tokens are garbage) — its slot frees at this chunk
        boundary like any finished row, and every other row's stream is
        bit-identical to a run without the poisoned neighbour (per-row
        sampler chains and cache slabs; nothing crosses rows)."""
        if self._closed:
            raise RuntimeError("batch session is closed")
        self.rode = []
        if self.paged:
            if not any(self._decodes(st) for st in self._slots.values()):
                return {}
            faults.fire("step_chunk")
            return self._step_chunk_paged()
        if self._ahead_tick:
            held = bool(self._flight)
            self.launch()
            return self.collect() if held else {}
        self.launch()
        fresh: dict = {}
        rode: list = []
        while self._flight:
            for h, toks in self.collect().items():
                fresh.setdefault(h, []).extend(toks)
            rode += self.rode
        self.rode = rode
        return fresh

    def step_ahead(self) -> dict:
        """One tick of a scheduler that keeps a launch queued behind the
        running one: dispatch the next launch, THEN read the one before it,
        which has ended by then or is about to, while the device goes on
        with the new one. Returns the bursts of the launch it read, as
        ``step_chunk`` does ({} where none was in flight: the new one is
        read by the next call). It runs through ``step_chunk``, so that
        every burst a session hands out passes one method."""
        self._ahead_tick = True
        try:
            return self.step_chunk()
        finally:
            self._ahead_tick = False

    def launch(self) -> Optional[_Launch]:
        """Dispatch ONE fused chunk over every occupied pool and return
        without waiting for it (None where nothing decodes or rides: the
        device is not touched); ``collect`` reads it. What the next launch
        needs is known here: positions are host arithmetic, the tokens and
        keys stay on the device (``ahead``), a row whose budget this launch
        spends is left out of the next, a riding prompt's cursor advances,
        and a row whose prefix this launch completes goes live for the
        next. Counts in ``dllama_decode_ahead_total`` what the launch
        before it was doing at the dispatch."""
        if self._closed:
            raise RuntimeError("batch session is closed")
        if not any(self._decodes(st) for st in self._slots.values()) \
                and not any(pf.rides for pf in self._prefills.values()):
            return None
        faults.fire("step_chunk")
        S = self.eng.cfg.seq_len
        prev = self._flight[-1] if self._flight else None
        rec = _Launch("idle" if prev is None else "queued",
                      self._piece_pending)
        stepped: set = set()
        while True:
            todo = [c for c in sorted(self._pools) if c not in stepped]
            if not todo:
                break
            ctx = todo[0]
            stepped.add(ctx)
            pool = self._pools[ctx]
            with observability.phase("decode_prepare", "engine"):
                if ctx < S:
                    # migrate rows that would outgrow this slab within the
                    # chunk; rows finishing inside it stay (their writes fit
                    # and nothing reads past them afterwards)
                    for r in range(pool.cap):
                        h = pool.rows[r]
                        if h is None or not self._decodes(self._slots[h]):
                            continue
                        st = self._slots[h]
                        useful = min(self.chunk, st.budget - st.offered)
                        p = int(pool.pos[r])
                        if ((useful >= self.chunk and p + self.chunk >= ctx)
                                or (useful < self.chunk
                                    and p + useful > ctx)):
                            self._migrate(h)
                live = [r for r in range(pool.cap)
                        if pool.rows[r] is not None
                        and self._decodes(self._slots[pool.rows[r]])]
                if not live and not (self.ride_t
                                     and self._pool_launches(pool)):
                    continue
                riders = self._plan_ride(pool) if self.ride_t else []
            with observability.phase("decode_dispatch", "engine") as dispatch:
                if not rec.parts:
                    if prev is not None and prev.parts[-1].out.is_ready():
                        rec.state, prev.bound = "late", dispatch.t0
                    if self.eng._m_ahead is not None:
                        self.eng._m_ahead.inc(state=rec.state)
                rec.parts.append(
                    self._dispatch(pool, live, riders, dispatch.t0))
                if len(rec.parts) == 1:
                    self._flight.append(rec)
                observability.launched()
            part = rec.parts[-1]
            for h, st, pf, _, finished in part.riders:
                if finished:
                    with observability.phase("go_live", "engine",
                                             span_id=st.span_id):
                        self._go_live(h, pf.prompt, pf.scfg)
        if not rec.parts:
            return None
        rec.dispatched = self.dispatched = dispatch.t1
        rec.kind = (any(p.riders for p in rec.parts), rec.kind[1])
        if rec.state != "queued":
            rec.start = rec.dispatched
        self._piece_pending = 0
        return rec

    def _dispatch(self, pool: _BucketPool, live: list, riders: list,
                  t0: float) -> _Part:
        """Call the pool's decode program on what the host and the device
        hold for it, then move the host's side on to the launch's end: the
        rows' offered tokens and positions, the riders' cursors."""
        plan = {}
        part = _Part(pool, [], [], t0)
        if self.eng.cfg.layer_plan:
            # its program also counts what the live rows routed to
            mask = np.zeros((pool.cap,), np.bool_)
            mask[live] = True
            if pool.live is None or not np.array_equal(pool.live[0], mask):
                pool.live = (mask, jnp.asarray(mask))
            plan["live"] = pool.live[1]
            part.live_pos = pool.pos[live]  # before the launch moves them
        if self.ride_t:
            plan["ride"] = self._ride_operand(riders)
        if self.ahead:
            if pool.dev is None:
                pool.dev = (jnp.asarray(pool.tokens), jnp.asarray(pool.keys))
                pool.fix[:] = False
            plan["fix"] = self._fix_operand(pool)
            tokens, keys = pool.dev
        else:
            tokens, keys = jnp.asarray(pool.tokens), jnp.asarray(pool.keys)
            pool.fix[:] = False
        if pool.sampling is None:
            pool.sampling = (jnp.asarray(pool.temps), jnp.asarray(pool.topps))
        part.out, pool.cache, keys, part.ok, *rest = self.eng.batch_loop(
            len(live))(
            pool.cache, tokens, jnp.asarray(pool.pos), keys, *pool.sampling,
            self.eng._poison_rows(pool.cap), n_steps=self.chunk, **plan)
        if self.ahead:
            pool.dev = (rest.pop(0), keys)
        else:
            part.keys = keys  # the host's copy is fetched as it is collected
        part.picks = rest
        for r in live:
            st = self._slots[pool.rows[r]]
            part.rows.append((r, pool.rows[r], st, st.offered))
            st.offered += self.chunk
        # mirror the in-program per-row pin across chunk boundaries
        pool.pos = np.minimum(pool.pos + self.chunk,
                              pool.ctx - 1).astype(np.int32)
        for h, _, end in riders:
            pf, st = self._prefills[h], self._slots[h]
            finished = end >= len(pf.prompt) - 1
            part.riders.append((h, st, pf, end - pf.cursor, finished))
            pf.cursor = end
            if finished:
                del self._prefills[h]
                st.prefilling = False
        return part

    def _fix_operand(self, pool: _BucketPool) -> jax.Array:
        """``_decode_loop_batch``'s ``fix`` for the pool's next launch: the
        rows flagged since its last take their host token and key, and the
        flags clear. A launch that changes no row gets the same zeros every
        time."""
        if not pool.fix.any():
            zeros = self._no_fix.get(pool.cap)
            if zeros is None:  # a copy, not a program of its own to compile
                zeros = self._no_fix[pool.cap] = jnp.asarray(
                    np.zeros((pool.cap, 4), np.int32))
            return zeros
        lines = np.zeros((pool.cap, 4), np.int32)
        lines[:, 0] = pool.fix
        lines[:, 1] = pool.tokens
        lines[:, 2:] = pool.keys.view(np.int32)
        pool.fix[:] = False
        return jnp.asarray(lines)

    def collect(self) -> dict:
        """Read the oldest launch in flight and account it: {handle: fresh
        tokens} as ``step_chunk`` returns them ({} where nothing is in
        flight). A row that finished (a stop, a quarantine), was cancelled
        or was released after the launch was dispatched ran one chunk past
        its end: its tokens are dropped, and a stop or a quarantine counts
        in ``dllama_decode_overrun_rows_total``. ``rode`` lists what the
        launch's riders did."""
        self.rode = []
        if not self._flight:
            return {}
        rec = self._flight.popleft()
        fresh: dict = {}
        for part in rec.parts:
            pool = part.pool
            with observability.phase("decode_wait", "engine",
                                     "device") as wait:
                ready = part.out.is_ready()
                arr = np.asarray(part.out)  # [chunk, cap]
            # reads issued after the program ended: the device idles unless
            # a launch is queued behind this one
            with observability.phase("decode_fetch", "engine") as fetch:
                okh = np.asarray(part.ok)  # [cap]
                picked = np.asarray(part.picks[0]) if part.picks else None
                if not self.ahead:
                    # the host keeps the tokens and keys: a row that went
                    # live since the dispatch keeps its own
                    pool.dev = (part.out[-1], part.keys)
                    self._pull(pool)
            if part is rec.parts[-1]:  # the launch ends with its last
                if ready:
                    rec.bound = rec.bound or wait.t0
                else:
                    rec.end = wait.t1
            with observability.phase("account", "engine"):
                # a launch queued behind another began where that one ended
                t0 = max(part.t0, self._last_end)
                self._observe_chunk(t0, fetch.t1, len(part.rows))
                self._account_chunk(part.rows, arr, okh, fresh)
                if picked is not None:
                    self._account_picks(picked)
                    self._account_ring(part.live_pos)
                    self._account_latent(part.live_pos, pool.ctx)
                self._mark_riders(part, t0, fetch.t1)
                if part is rec.parts[-1]:
                    self._time(rec)
            self._last_end = wait.t1
        return fresh

    def _time(self, rec: _Launch) -> None:
        """Learn the launch's seconds on the device, per kind: where the
        host waited for its end, the time from its start; where it had
        ended unseen, the time to when it was known to have ended, if that
        is under what the kind reads (so that a reading too long, a launch
        that compiled, say, cannot keep the scheduler waiting past every
        launch's end). Hand its end to the launch queued behind it, which
        began there."""
        if rec.start is not None:
            seen = self._period.setdefault(
                rec.kind, collections.deque(maxlen=9))
            if rec.end is not None:
                seen.append(rec.end - rec.start)
            elif rec.bound is not None and (
                    not seen or rec.bound - rec.start < np.median(seen)):
                seen.append(rec.bound - rec.start)
        if self._flight and self._flight[0].start is None \
                and rec.end is not None:
            nxt = self._flight[0]
            nxt.start = max(nxt.dispatched, rec.end)

    def due(self) -> Optional[tuple]:
        """(when the newest launch in flight should end on
        ``time.monotonic``, how far launches of its kind stray from that):
        the median of the kind's last launches and their mean distance
        from it. None while nothing is in flight or no launch of its kind
        has been timed. The pipelined scheduler waits for arrivals until
        then, less the time it takes to dispatch the next launch."""
        if not self._flight:
            return None
        rec = self._flight[-1]
        seen = self._period.get(rec.kind)
        if not seen:
            return None
        mid = float(np.median(seen))
        spread = float(np.mean(np.abs(np.asarray(seen) - mid)))
        start = rec.start if rec.start is not None else rec.dispatched
        return start + mid, spread

    def _settle(self) -> None:
        """Wait for the launches in flight to end; their results stay to
        be collected. What must read them first (a row's export, the
        session's close) calls this."""
        for rec in self._flight:
            for part in rec.parts:
                jax.block_until_ready(part.out)

    def _plan_ride(self, pool) -> list:
        """The prompt tokens this launch of ``pool`` carries: [(handle,
        first step, cursor after)], oldest pending prompt first. Each takes
        ``ride_t`` tokens a step from its cursor until its prefix ends, the
        next one starts at the next step, until the chunk's steps are
        taken. A prompt that gets its first tokens here rides from now on;
        one that began in a staging cache is not asked."""
        out, step = [], 0
        for h, pf in self._prefills.items():
            if step >= self.chunk:
                break
            if (pf.rides is False or self._where[h][0] is not pool
                    or self._slots[h].done):
                continue
            pf.rides = True
            left = len(pf.prompt) - 1 - pf.cursor
            steps = min(self.chunk - step, -(-left // self.ride_t))
            out.append((h, step, pf.cursor + min(left, steps * self.ride_t)))
            step += steps
        return out

    def _ride_operand(self, riders: list) -> jax.Array:
        """``_decode_loop_batch``'s ``ride`` for one launch: a line a step,
        ``ride_t`` tokens then (row, position of the first, how many are
        real). A launch that nobody rides gets the same zeros every time."""
        t = self.ride_t
        if not riders:
            if self._no_ride is None:
                self._no_ride = jnp.zeros((self.chunk, t + 3), jnp.int32)
            return self._no_ride
        faults.fire("prefill_chunk")
        lines = np.zeros((self.chunk, t + 3), np.int32)
        for h, step, end in riders:
            pf, row = self._prefills[h], self._where[h][1]
            for at in range(pf.cursor, end, t):
                n = min(t, end - at)
                lines[step, :n] = pf.prompt[at:at + n]
                lines[step, t:] = (row, at, n)
                step += 1
        return jnp.asarray(lines)

    def _mark_riders(self, part: _Part, t0: float, t1: float) -> None:
        """After the launch that carried them: count their tokens and the
        launch's wall time as their prefill chunk, note them in ``rode``
        (marked from the launch's dispatch), and where a prefix is complete
        observe the request's whole prefill time."""
        if not part.riders:
            return
        ms = (t1 - t0) * 1000.0
        self.prefill_ms += ms
        tokens = 0
        for h, st, _, n, finished in part.riders:
            tokens += n
            st.prefill_ms += ms
            if self._slots.get(h) is not st:
                continue  # cancelled and released while the launch ran
            self.rode.append((h, part.t0, t1, finished))
            if finished and self.eng._m_prefill is not None:
                self.eng._m_prefill.observe(st.prefill_ms)
        if self.eng._m_prefill_chunk is not None:
            self.eng._m_prefill_chunk.observe(ms)
            self.eng._m_prefill_tokens.inc(tokens, how="ride")
            self.eng._m_ride_slots.inc(self.chunk * self.ride_t)

    def _account_picks(self, picked) -> None:
        """One chunk's routing of a model with expert layers of which a
        share is held (``layer_plan.forward_batched``, summed over the
        chunk): picks on held experts, all picks, distinct held experts a
        layer-step, and the expert plane sets the program read."""
        eng = self.eng
        if eng._m_moe_picks is None:
            return
        held, total, active, reads = (int(v) for v in picked[:4])
        eng._m_moe_picks.inc(held, held="1")
        eng._m_moe_picks.inc(total - held, held="0")
        eng._m_moe_active.inc(active)
        eng._m_moe_reads.inc(reads)
        eng._m_moe_layer_steps.inc(
            self.chunk * eng.cfg.plan_count(ffn="moe"))
        if len(picked) > 4:  # a grouped router's chosen groups
            held_g, total_g = (int(v) for v in picked[4:])
            eng._m_moe_groups.inc(held_g, held="1")
            eng._m_moe_groups.inc(total_g - held_g, held="0")

    def _account_ring(self, live_pos) -> None:
        """One chunk's window attention, from numbers the session holds
        (``live_pos``: the live rows' positions at the chunk's first step):
        the ring slots that held a position a live row's query saw, and the
        slots the program scored for them: a step reads every ring as far
        as its longest live row reaches, up to a rung
        (``layer_plan.slots_scored``, the program's own rule)."""
        eng, cfg = self.eng, self.eng.cfg
        layers = cfg.plan_count("window")
        if eng._m_ring_live is None or not layers:
            return
        at = live_pos[:, None] + np.arange(self.chunk)[None, :]
        eng._m_ring_live.inc(
            int(np.minimum(at + 1, cfg.window).sum()) * layers)
        # the program pins a row's position at the context's last slot
        reach = np.minimum(at.max(axis=0), cfg.seq_len - 1) + 1
        eng._m_ring_scored.inc(
            int(layer_plan.slots_scored(cfg.ring_slots, reach).sum())
            * len(live_pos) * layers)

    def _account_latent(self, live_pos, ctx: int) -> None:
        """One chunk's latent attention, as ``_account_ring`` counts a
        ring's: the positions each live row's query saw, and the slots the
        program scored for it, a step reading the pool's slab of ``ctx``
        slots as far as its longest live row reaches, up to a rung
        (``layer_plan.slots_scored``, the program's own rule)."""
        eng = self.eng
        layers = eng.cfg.plan_count("latent")
        if eng._m_latent_live is None or not layers:
            return
        at = np.minimum(live_pos[:, None] + np.arange(self.chunk)[None, :],
                        ctx - 1)
        eng._m_latent_live.inc(int((at + 1).sum()) * layers)
        reach = at.max(axis=0) + 1
        eng._m_latent_scored.inc(
            int(layer_plan.slots_scored(ctx, reach).sum())
            * len(live_pos) * layers)

    def _observe_chunk(self, t0: float, t1: float, live: int) -> None:
        """One decode launch's wall time (its dispatch, wait and fetch
        phases, from their own clock reads) and the rows it advanced."""
        chunk_ms = (t1 - t0) * 1000.0
        self.decode_ms += chunk_ms
        if self.eng._m_chunk is not None:
            self.eng._m_chunk.observe(chunk_ms)
            self.eng._m_live_rows.observe(live)

    def _account_chunk(self, rows: list, arr, okh, fresh: dict) -> None:
        """Per-row bookkeeping for one fused chunk's output — shared by the
        slab and paged dispatch paths (identical by design: the accounting
        IS the bit-identity contract, only residency differs). ``rows``:
        (row, handle, its state, tokens offered it before the chunk), as
        they stood at the dispatch."""
        for r, h, st, before in rows:
            if st.done or self._slots.get(h) is not st:
                # finished, cancelled or released after the dispatch: it
                # ran one chunk past its end, and its tokens are garbage
                if st.finish in ("stop", "error") \
                        and self.eng._m_overrun is not None:
                    self.eng._m_overrun.inc()
                continue
            if not okh[r]:
                st.done = True
                st.finish = "error"
                if self.eng._m_quarantine is not None:
                    self.eng._m_quarantine.inc()
                fresh[h] = []
                continue
            # a context-exhausted row pinned at its last slot: tokens
            # past its room are garbage — generate_batch's accounting
            keep = max(0, min(self.chunk, st.room - before))
            toks = [int(t) for t in arr[:keep, r]]
            take = min(len(toks), st.budget - st.emitted)
            for j in range(take):
                if toks[j] in st.stop_tokens:
                    take = j + 1
                    break
            toks = toks[:take]
            st.emitted += len(toks)
            if st.emitted >= st.budget:
                st.done = True
                st.finish = "length"
            elif (st.stop_tokens and toks
                    and toks[-1] in st.stop_tokens):
                st.done = True
                st.finish = "stop"
            fresh[h] = toks

    def _step_chunk_paged(self) -> dict:
        """One fused chunk over every occupied window group. Phase 1
        extends every live row's page table ahead of this chunk's writes
        (appending pages — never copying — and regrouping rows whose table
        outgrew their window, a pure host-side move); phase 2 runs one
        gather-windowed program per occupied shape. A live write target is
        therefore always allocated before dispatch; only the discarded
        post-finish garbage steps ever land on the scratch page."""
        fresh: dict = {}
        with observability.phase("decode_prepare", "engine"):
            for h, st in list(self._slots.items()):
                if st.done or st.prefilling:
                    continue
                g, r = self._where[h]
                rp = self._rowpages[h]
                p = int(g.pos[r])
                needed = min(p + self.chunk + 1, rp.cap_tokens)
                while len(rp.blocks) < paged_kv.pages_for(needed, self.page):
                    rp.blocks.append(self._page_alloc(rp))
                nb = self._nb_for(len(rp.blocks))
                if nb > g.nb:
                    self._regroup(h, nb)
                else:
                    self._sync_table(h)
        for nb in sorted(self._pgroups):
            g = self._pgroups[nb]
            live = [r for r in range(g.cap)
                    if g.rows[r] is not None
                    and not self._slots[g.rows[r]].done
                    and not self._slots[g.rows[r]].prefilling]
            if not live:
                continue
            W = nb * self.page
            with observability.phase("decode_dispatch", "engine") as dispatch:
                chunk, self._arena, keys, ok = self.eng.paged_loop(len(live))(
                    self._arena, jnp.asarray(g.tables),
                    jnp.asarray(g.tokens), jnp.asarray(g.pos),
                    jnp.asarray(g.keys), jnp.asarray(g.temps),
                    jnp.asarray(g.topps), self.eng._poison_rows(g.cap),
                    n_steps=self.chunk)
                observability.launched()
            with observability.phase("decode_wait", "engine", "device"):
                arr = np.asarray(chunk)  # [chunk, cap]
            with observability.phase("decode_fetch", "engine") as fetch:
                okh = np.asarray(ok)  # [cap]
                g.tokens = np.array(chunk[-1])
                g.keys = np.array(keys)
                # mirror the in-program per-row pin across chunk boundaries
                g.pos = np.minimum(g.pos + self.chunk, W - 1).astype(np.int32)
            with observability.phase("account", "engine"):
                self._observe_chunk(dispatch.t0, fetch.t1, len(live))
                rows = []
                for r in live:
                    st = self._slots[g.rows[r]]
                    rows.append((r, g.rows[r], st, st.offered))
                    st.offered += self.chunk
                self._account_chunk(rows, arr, okh, fresh)
        return fresh

    def cancel(self, slot: int) -> None:
        """Stop decoding ``slot``'s row NOW (cancellation / deadline expiry):
        the row is marked done so the next ``step_chunk`` excludes it from
        the live set — exactly the state a budget-exhausted row reaches, so
        no new invariants: it rides along pinned until ``release()`` frees
        its slab (the serving scheduler releases at the same chunk boundary
        it cancels at). Cancelling a mid-prefill admission drops its
        half-filled single cache immediately — the partially written slab
        is garbage the next occupant overwrites before attending.
        Idempotent on an already-done row."""
        st = self._state(slot)
        st.done = True
        pf = self._prefills.pop(slot, None)
        if pf is not None:
            st.prefilling = False
            if self.paged:
                # retract the publish-at-admit nodes this prefill never
                # filled: their pages hold garbage no admit may alias
                self._radix.unpublish(
                    [n for n in pf.pub_nodes
                     if n is not None and not n.ready], self._alloc)
            _drop(pf.cache)

    def release(self, slot: int) -> None:
        """Free the row for the next admission and return its KV
        reservation to the budget. The slab is NOT cleared (see class
        docstring for why reuse is safe); the row re-pins at its slab's
        last slot like a free row."""
        st = self._slots.pop(slot, None)
        if st is None:
            raise ValueError(f"slot {slot} is not occupied")
        pf = self._prefills.pop(slot, None)
        if pf is not None:
            if self.paged:
                self._radix.unpublish(
                    [n for n in pf.pub_nodes
                     if n is not None and not n.ready], self._alloc)
            _drop(pf.cache)
        pool, row = self._where.pop(slot)
        pool.rows[row] = None
        if self.paged:
            # drop the row's holds: private pages published to the radix
            # tree become evictable cache (their KV survives for future
            # admits), unpublished ones go straight back to the free list
            rp = self._rowpages.pop(slot)
            for p in rp.blocks:
                self._alloc.unref(p)
            self._alloc.unreserve(rp.outstanding)
            pool.pos[row] = pool.nb * self.page - 1
            pool.tables[row, :] = paged_kv.SCRATCH_PAGE
        else:
            pool.pos[row] = pool.ctx - 1
        self._reserved_tokens -= st.reserved
        if self._budget is not None:
            self._budget.release(st.reserved)
            if not self.paged:
                self._budget.unplace(pool.ctx)

    def close(self) -> None:
        """Drop every resident slab's (and pending prefill's) device
        buffers and hand all reservations back to the external budget.
        Idempotent."""
        if self._closed:
            return
        self._closed = True
        if self._budget is not None:
            for st in self._slots.values():
                self._budget.release(st.reserved)
            if not self.paged:
                for pool, _ in self._where.values():
                    self._budget.unplace(pool.ctx)
        # what is in flight is read first (and its results dropped)
        self._settle()
        self._flight.clear()
        for pf in self._prefills.values():
            _drop(pf.cache)
        for pool in self._pools.values():
            _drop(pool.cache)
            pool.cache = pool.dev = None
        if self.paged:
            # hand every page back before the arena dies: the allocator may
            # be the serving accountant's (KVBudget.attach_pages), and its
            # gauges must not report cached pages of a deleted arena
            for rp in self._rowpages.values():
                for p in rp.blocks:
                    self._alloc.unref(p)
                self._alloc.unreserve(rp.outstanding)
            self._radix.evict(self._alloc.num_pages, self._alloc)
            for leaf in jax.tree.leaves(self._arena):
                leaf.delete()
            self._arena = None
            self._pgroups = {}
            self._rowpages = {}
        self._pools = {}
        self._slots = {}
        self._where = {}
        self._prefills = {}


class _NgramIndex:
    """Incremental n-gram -> latest-start-position index over the consumed
    context: O(1) amortized per appended token, O(1) per draft lookup. A
    naive backward scan is O(context) per verify step, which on a
    near-context-limit chat burns milliseconds of host time per device
    dispatch — eroding exactly the bandwidth win drafting exists to buy."""

    def __init__(self, ngram: int):
        self.ngram = ngram
        self.ctx: list = []
        self._pos: dict = {}
        self._prev: dict = {}  # the occurrence before the latest, per n-gram

    def extend(self, tokens) -> None:
        for t in tokens:
            self.ctx.append(t)
            if len(self.ctx) >= self.ngram:
                key = tuple(self.ctx[-self.ngram:])
                if key in self._pos:
                    self._prev[key] = self._pos[key]
                self._pos[key] = len(self.ctx) - self.ngram

    def draft(self, pending: int, k: int) -> list:
        """Up to k proposed continuations of context + [pending]: what
        followed the most recent earlier occurrence of its trailing n-gram.
        If the latest occurrence ends flush at the end of the context (its
        continuation is empty — the norm on repeated-token runs, the most
        draftable text there is), fall back to the one before it, whose
        continuation is never empty."""
        if k <= 0 or len(self.ctx) + 1 <= self.ngram:
            return []
        tail = tuple((self.ctx + [pending])[-self.ngram:])
        for j in (self._pos.get(tail), self._prev.get(tail)):
            if j is not None:
                cont = self.ctx[j + self.ngram : j + self.ngram + k]
                if cont:
                    return list(cont)
        return []
