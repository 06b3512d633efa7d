"""Benchmark: average single-token generation time — the reference's headline
metric (README "📊 Measurements": avg token time over N samples, Q40×Q80).

Prints ONE JSON line to stdout:
    {"metric": ..., "value": ms_per_token, "unit": "ms/token", "vs_baseline": x}

vs_baseline compares against the reference's best published *single-node*
Llama 2 7B number: 101.81 ms on a GCP c3d-highcpu-30 VM (BASELINE.md /
reference README.md:88). >1.0 means faster than the reference.

Decoding runs as ONE fused device program per 64 tokens (lax.scan over decode
steps, sampling on device) — the host sees one dispatch per batch of tokens,
not per token.

Model selection: Llama-2-7B shape on TPU (random bf16 weights generated on
device); set BENCH_MODEL=tiny (or run on CPU) for a TinyLlama-1.1B shape.
"""

from __future__ import annotations

import json
import os
import sys
import time


LLAMA2_7B = dict(
    arch="llama", dim=4096, hidden_dim=11008, n_layers=32, n_heads=32, n_kv_heads=32,
    vocab_size=32000, seq_len=512, head_size=128, kv_dim=4096, dtype="bfloat16",
)
TINYLLAMA_1_1B = dict(
    arch="llama", dim=2048, hidden_dim=5632, n_layers=22, n_heads=32, n_kv_heads=4,
    vocab_size=32000, seq_len=1024, head_size=64, kv_dim=256, dtype="bfloat16",
)
# the north-star model (BASELINE.json: <=5 ms/token on v5e-8); GQA 8 kv heads
LLAMA3_8B = dict(
    arch="llama", dim=4096, hidden_dim=14336, n_layers=32, n_heads=32, n_kv_heads=8,
    vocab_size=128256, seq_len=512, head_size=128, kv_dim=1024, dtype="bfloat16",
    rope_theta=500000.0,
)
# Mixtral-shape MoE scaled to one 16 GB chip (~2.6 GB q40): measures the
# selected-experts decode path (_moe_decode_selected) — the reference's
# flagship MoE capability — without a multi-chip slice. Full Mixtral-8x7B
# (~26 GB q40) needs tp>=2; this keeps the per-token expert-read ratio
# (2 of 8 experts, ~6% of weights read per token).
MIXTRAL_LITE = dict(
    arch="mixtral", dim=2048, hidden_dim=5632, n_layers=16, n_heads=16,
    n_kv_heads=8, vocab_size=32000, seq_len=512, head_size=128, kv_dim=1024,
    n_experts=8, n_active_experts=2, dtype="bfloat16",
    rope_style="half", rope_theta=1e6,  # Mixtral's half-split rotary layout
)
# Grok-1-shape MoE scaled to one chip (~2.7 GB q40): the reference's
# flagship arch — x78.38 embedding / x0.577 logit scales, post-attention +
# post-MoE norms, GELU experts, half-split rotary — at 1/8 the layer count
# and 1/2 the width so the selected-experts decode fits a 16 GB chip.
GROK1_LITE = dict(
    arch="grok1", dim=3072, hidden_dim=4096, n_layers=8, n_heads=24,
    n_kv_heads=8, vocab_size=32000, seq_len=512, head_size=128, kv_dim=1024,
    n_experts=8, n_active_experts=2, hidden_act="gelu", dtype="bfloat16",
    rope_style="half",
)

# serving-shape smoke model for the CPU-runnable continuous-batching mode:
# the scheduler comparison (continuous vs static window) is about SCHEDULING,
# not model speed, so a small fast shape keeps the staggered-arrival replay
# inside CI wall clocks while still decoding real tokens.
SMOKE_SERVE = dict(
    arch="llama", dim=256, hidden_dim=512, n_layers=4, n_heads=8,
    n_kv_heads=4, vocab_size=512, seq_len=256, head_size=32, kv_dim=128,
    dtype="float32",
)

# reference's best published single-node Llama 2 7B avg token time (ms)
BASELINE_7B_SINGLE_NODE_MS = 101.81


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _env_count(name: str) -> int:
    """An integer env knob parsed defensively, ONCE, for every consumer: a
    non-numeric or negative value counts as 0 (feature off) rather than
    raising — the bench's contract is to always end in one JSON line, and
    main()'s labeling must agree with what run_decode_bench actually ran."""
    try:
        return max(0, int(os.environ.get(name, "0") or 0))
    except ValueError:
        return 0


def _prefill_count() -> int:
    return _env_count("BENCH_PREFILL")


def _seq_override() -> int:
    return _env_count("BENCH_SEQ")


def _pct(xs, p):
    ys = sorted(xs)
    return ys[min(len(ys) - 1, int(round(p / 100.0 * (len(ys) - 1))))]


def _serving_replay(eng, mode: str, reqs: list, arrivals_s: list,
                    max_batch: int, chunk: int) -> tuple:
    """Replay ONE staggered-arrival workload -> (wall_s, latency_s, tokens).

    ``reqs`` is [(prompt_tokens, steps)]; ``arrivals_s[i]`` is request i's
    arrival offset from replay start. "continuous" admits into the resident
    slot pool between fused chunks (Engine.batch_session); "static" mimics
    the pre-continuous window batcher: run generate_batch to full drain,
    then batch whatever arrived in the meantime. latency_s[i] is request
    i's arrival-to-last-token time; tokens counts everything emitted, so
    tokens/wall_s is the aggregate serving throughput under that scheduler.
    """
    from dllama_tpu.runtime.sampler import SamplerConfig

    greedy = SamplerConfig(temperature=0.0, seed=0)
    lat = [0.0] * len(reqs)
    tokens = 0
    nxt, pending = 0, []
    t0 = time.perf_counter()
    if mode == "continuous":
        sess = eng.batch_session(max_batch, chunk=chunk)
        slot_req, emitted = {}, [0] * len(reqs)
        while nxt < len(reqs) or pending or slot_req:
            while nxt < len(reqs) and arrivals_s[nxt] <= time.perf_counter() - t0:
                pending.append(nxt)
                nxt += 1
            while pending and sess.free_slots:
                j = pending.pop(0)
                slot = sess.admit(list(reqs[j][0]), steps=reqs[j][1],
                                  sampler=greedy)
                slot_req[slot] = j
            if not slot_req:
                # pool empty and the next request is not due yet: idle wait
                time.sleep(max(0.0, arrivals_s[nxt] - (time.perf_counter() - t0)))
                continue
            for slot, burst in sess.step_chunk().items():
                j = slot_req[slot]
                emitted[j] += len(burst)
                if sess.is_done(slot):
                    lat[j] = (time.perf_counter() - t0) - arrivals_s[j]
                    tokens += emitted[j]
                    sess.release(slot)
                    del slot_req[slot]
        sess.close()
    else:
        while nxt < len(reqs) or pending:
            while nxt < len(reqs) and arrivals_s[nxt] <= time.perf_counter() - t0:
                pending.append(nxt)
                nxt += 1
            if not pending:
                time.sleep(max(0.0, arrivals_s[nxt] - (time.perf_counter() - t0)))
                continue
            group, pending = pending[:max_batch], pending[max_batch:]
            rows = eng.generate_batch(
                [list(reqs[j][0]) for j in group],
                steps=max(reqs[j][1] for j in group),
                sampler=greedy,
                row_steps=[reqs[j][1] for j in group])
            end = time.perf_counter() - t0
            for j, row in zip(group, rows):
                lat[j] = end - arrivals_s[j]
                tokens += min(len(row), reqs[j][1])
    return time.perf_counter() - t0, lat, tokens


def run_decode_bench(cfg_dict: dict, bench_steps: int = None):
    """``bench_steps`` trades compile time against timing fidelity: the whole
    run is ONE dispatch + ONE host sync, so more tokens (256, the TPU
    default) dilute that sync's fixed cost further. Off-TPU (CI smoke) the
    default stays 64: CPU steps are slow and nothing is being measured.
    Returns (best ms/token, weights_kind_used)."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.models import llama
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.runtime.generate import Engine
    from dllama_tpu.runtime.sampler import SamplerConfig

    if bench_steps is None:
        bench_steps = _env_count("BENCH_STEPS") or (
            256 if jax.default_backend() == "tpu" else 64
        )
    # BENCH_SEQ=N overrides the context length: decode attention is a
    # static-shape masked read of the WHOLE cache every step, so this
    # measures long-context per-token cost directly (pair with
    # BENCH_CACHE=f8, which halves exactly the bytes this knob adds)
    seq = _seq_override()
    if seq:
        cfg_dict = dict(cfg_dict, seq_len=seq)
    cfg = ModelConfig(**cfg_dict)
    # config tag shared by EVERY return path, so the result record always
    # states the seq/cache configuration it was measured under
    cfg_tag = (f"-seq{seq}" if seq else "") + (
        "-f8cache" if os.environ.get("BENCH_CACHE") == "f8" else "")
    n_dev = len(jax.devices())
    mesh = None
    batch = _env_count("BENCH_BATCH")
    if n_dev > 1 and cfg.n_kv_heads % n_dev == 0:
        from dllama_tpu.parallel.mesh import tp_mesh

        mesh = tp_mesh(n_dev)
        log(f"tensor-parallel over {n_dev} devices")

    # Q40 weights by default on TPU: the baseline numbers are Q40xQ80 runs,
    # and the fused dequant-matmul kernels keep 4-bit weights resident in HBM
    # (4x less weight traffic per token) — including under TP, where the
    # quant planes shard over the mesh (parallel.quant_tp), the reference's
    # production Q40-on-every-node configuration. BENCH_WEIGHTS=bf16|q80
    # overrides. Off-TPU the Pallas kernels run in interpret mode (orders of
    # magnitude slower), so bf16 is the default there. A kernel the chip's
    # compiler refuses fails the run: no probe, no fallback to bf16.
    default_weights = "q40" if jax.default_backend() == "tpu" else "bf16"
    weights = os.environ.get("BENCH_WEIGHTS", default_weights)
    log(f"building params on device: dim={cfg.dim} layers={cfg.n_layers} ({weights})")
    # with a mesh, dense params are written directly into their shards — no
    # chip ever holds the full model
    if weights in ("q40", "q80"):
        params = llama.device_random_quant_params(cfg, kind=weights, seed=0)
    else:
        params = llama.device_random_params(cfg, seed=0, mesh=mesh)
    jax.block_until_ready(params)
    # decode_chunk=bench_steps: ONE device dispatch + host sync for the whole
    # timed run, so the per-dispatch host round trip is paid once
    # BENCH_CACHE=f8 stores the KV cache as float8_e4m3fn (half the cache
    # read traffic; ~2% of 7B decode bytes at seq 512, more at long context)
    cache_dtype = (jnp.float8_e4m3fn if os.environ.get("BENCH_CACHE") == "f8"
                   else jnp.bfloat16)
    eng = Engine(cfg, params, SamplerConfig(temperature=0.0), cache_dtype=cache_dtype,
                 mesh=mesh, decode_chunk=bench_steps)
    # -flash tag, computed ONCE for every decode return path from the SAME
    # gate the model layer uses (flash_decode.engages) PLUS the engine-path
    # condition: the dense-pjit mesh branch pins allow_flash=False (Pallas
    # calls don't partition under pjit), so a dense-weights multi-device
    # run must not be labeled -flash. The -subkernel tag reads the LATCHED
    # qmatmul.Q40_NOSUB gate the kernels dispatched on (the explicit opt-out).
    from dllama_tpu.ops import flash_decode, qmatmul as _qmatmul

    flash_possible = mesh is None or weights in ("q40", "q80")
    flash_tag = "-flash" if (flash_possible and flash_decode.engages(
        1, cfg.seq_len, cache_dtype)) else ""
    if weights == "q40" and not _qmatmul.Q40_NOSUB:
        cfg_tag += "-subkernel"
    # Engine may have fused the projection matrices into new buffers; drop
    # this frame's reference so the unfused originals free immediately
    del params

    # BENCH_PREFILL=N replays the PREFILL STALL: a near-max-length N-token
    # prompt is admitted into a pool whose resident rows are mid-decode, and
    # the measurement is the residents' INTER-TOKEN GAP — monolithic
    # admission stalls every resident for the whole prefill, chunked
    # admission (admit_begin + one prefill_step per tick) bounds the stall
    # to one prefill piece plus one decode chunk. A capacity phase counts
    # rows resident at the SAME modeled HBM budget with uniform vs bucketed
    # slot KV. CPU-runnable (BENCH_MODEL=smoke); the gate FAILS the bench if
    # a chunked-mode resident gap exceeds 2x the per-tick chunk budget, or
    # if bucketed pools don't admit strictly more short rows than uniform.
    # BENCH_PREFILL_CHUNK overrides the piece size (default chunk * pool);
    # BENCH_PREFILL_OUT writes the full report JSON for CI artifacts.
    pf = _prefill_count()
    if pf:
        import numpy as np

        S = cfg.seq_len
        pf = min(pf, S - 1)
        B = max(2, min(batch or 4, 8))
        chunk = 8
        pchunk = _env_count("BENCH_PREFILL_CHUNK") or chunk * B
        rng = np.random.default_rng(0)
        long_prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, pf)]
        res_prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, 6)]
        greedy = SamplerConfig(temperature=0.0, seed=0)
        res_steps = (S - len(res_prompt)) // chunk * chunk
        new_steps = 2 * chunk

        def _stall_replay(chunked):
            """One admission of the long prompt into a busy pool; returns
            (resident gaps ms, decode tick ms, prefill piece ms)."""
            sess = eng.batch_session(
                B, chunk=chunk, prefill_chunk=pchunk if chunked else 0)
            residents = [sess.admit(list(res_prompt), steps=res_steps,
                                    sampler=greedy) for _ in range(B - 1)]
            last, gaps, ticks, pieces = {}, [], [], []

            def tick():
                t0 = time.perf_counter()
                fresh = sess.step_chunk()
                now = time.perf_counter()
                ticks.append((now - t0) * 1000.0)
                for h in residents:
                    if fresh.get(h):
                        if h in last:
                            gaps.append((now - last[h]) * 1000.0)
                        last[h] = now

            tick()  # anchor every resident's clock...
            tick()  # ...and record one steady-state gap before the stall
            if chunked:
                nh = sess.admit_begin(long_prompt, steps=new_steps,
                                      sampler=greedy)
                while not sess.is_done(nh):
                    t0 = time.perf_counter()
                    if sess.prefill_step() is not None:
                        pieces.append((time.perf_counter() - t0) * 1000.0)
                    tick()
            else:
                nh = sess.admit(long_prompt, steps=new_steps, sampler=greedy)
                while not sess.is_done(nh):
                    tick()
            sess.close()
            return gaps, ticks, pieces

        def _capacity(bucketed):
            """Rows admitted before the modeled budget (B * seq_len KV
            token-slots — identical both ways) says no. 1-token prompts:
            the shortest request, where bucketing's win is largest."""
            sess = eng.batch_session(B, chunk=chunk, bucket_kv=bucketed,
                                     min_bucket=16)
            n = 0
            while sess.can_admit(1, chunk) and n < 4096:
                sess.admit_begin([1], steps=chunk, sampler=greedy)
                n += 1
            sess.close()
            return n

        log(f"prefill stall replay: {pf}-token prompt into a busy pool "
            f"(B={B}, chunk={chunk}, prefill_chunk={pchunk}); warmup...")
        t0 = time.perf_counter()
        _stall_replay(True)  # compiles pool decode + every prefill bucket
        _stall_replay(False)
        log(f"warmup done in {time.perf_counter() - t0:.1f}s")
        mono_gaps, _, _ = _stall_replay(False)
        ch_gaps, ch_ticks, ch_pieces = _stall_replay(True)
        budget_ms = _pct(ch_pieces, 50) + _pct(ch_ticks, 50)
        gate_ms = 2.0 * budget_ms
        mono_p99, ch_p99 = _pct(mono_gaps, 99), _pct(ch_gaps, 99)
        log(f"resident inter-token gap p99: monolithic {mono_p99:.1f} ms "
            f"vs chunked {ch_p99:.1f} ms (worst {max(ch_gaps):.1f} ms; "
            f"tick budget {budget_ms:.1f} ms, gate {gate_ms:.1f} ms)")
        rows_uni, rows_bkt = _capacity(False), _capacity(True)
        log(f"rows resident at fixed HBM budget ({B * S} KV token-slots): "
            f"uniform {rows_uni} vs bucketed {rows_bkt}")
        report = {
            "prompt_tokens": pf, "pool": B, "decode_chunk": chunk,
            "prefill_chunk": pchunk,
            "monolithic_gap_p99_ms": round(mono_p99, 3),
            "chunked_gap_p99_ms": round(ch_p99, 3),
            "chunked_gap_max_ms": round(max(ch_gaps), 3),
            "tick_budget_ms": round(budget_ms, 3),
            "gate_ms": round(gate_ms, 3),
            "budget_kv_tokens": B * S,
            "rows_uniform": rows_uni, "rows_bucketed": rows_bkt,
        }
        out_path = os.environ.get("BENCH_PREFILL_OUT")
        if out_path:
            with open(out_path, "w") as f:
                json.dump(report, f, indent=2)
            log(f"report written to {out_path}")
        if ch_p99 > gate_ms:
            raise RuntimeError(
                f"chunked prefill left a resident-row gap of {ch_p99:.1f} "
                f"ms p99, over the 2x chunk budget gate of {gate_ms:.1f} "
                f"ms: {report}")
        if rows_bkt <= rows_uni:
            raise RuntimeError(
                f"bucketed slot KV admitted {rows_bkt} rows vs uniform "
                f"{rows_uni} at the same budget — must be strictly more: "
                f"{report}")
        return ch_p99, f"{weights}-prefillstall{pf}-b{B}{cfg_tag}"

    # BENCH_PREFIX=N replays a SHARED-SYSTEM-PROMPT workload through the
    # paged-KV radix prefix cache: N sequential requests whose prompts are
    # one seq_len/2 system prefix plus a short unique tail (>=50% shared),
    # measured as per-request TTFT (admit -> first token). The cold control
    # replays the SAME lengths with fully unique prompts, so every
    # admission pays full prefill. A capacity phase counts 1-token rows
    # resident at the same modeled HBM budget paged vs uniform. CPU-runnable
    # (BENCH_MODEL=smoke); the gate FAILS the bench unless warm TTFT p50 is
    # strictly below cold, paged rows >= uniform rows, and the paged
    # replays performed ZERO slab-migration copies (growth appends a page).
    # BENCH_PREFIX_PAGE overrides the page size (default 16 tokens);
    # BENCH_PREFIX_OUT writes the full report JSON for CI artifacts.
    px = _env_count("BENCH_PREFIX")
    if px:
        import numpy as np

        S = cfg.seq_len
        n_req = max(4, min(px, 64))
        B = max(2, min(batch or 4, 8))
        chunk = 8
        page = _env_count("BENCH_PREFIX_PAGE") or 16
        rng = np.random.default_rng(0)
        shared = [int(t) for t in rng.integers(1, cfg.vocab_size, S // 2)]
        tail_len = max(4, S // 16)
        greedy = SamplerConfig(temperature=0.0, seed=0)

        def _prompts(share):
            out = []
            for i in range(n_req):
                r = np.random.default_rng((1 if share else 100) + i)
                tail = [int(t) for t in r.integers(1, cfg.vocab_size,
                                                   tail_len)]
                head = shared if share else [
                    int(t) for t in r.integers(1, cfg.vocab_size,
                                               len(shared))]
                out.append(head + tail)
            return out

        def _ttft_replay(share):
            """Sequential replay; returns (per-request TTFT ms, migrations,
            prefix hit rate, evictions). A fresh session per replay: the
            radix cache starts cold both ways."""
            sess = eng.batch_session(B, chunk=chunk, prefill_chunk=4 * chunk,
                                     kv_pages=page)
            ttfts = []
            for prompt in _prompts(share):
                t0 = time.perf_counter()
                h = sess.admit_begin(prompt, steps=chunk, sampler=greedy)
                got = []
                while not got and not sess.is_done(h):
                    sess.prefill_step()
                    got.extend(sess.step_chunk().get(h, []))
                ttfts.append((time.perf_counter() - t0) * 1000.0)
                while not sess.is_done(h):
                    sess.prefill_step()
                    sess.step_chunk()
                sess.release(h)
            stats = (sess.migrations, sess.prefix_hit_rate,
                     sess.prefix_evictions)
            sess.close()
            return (ttfts,) + stats

        def _capacity(paged):
            """1-token rows admitted at the same modeled budget (B * seq_len
            KV token-slots): paged reserves ceil(need/page) pages per row,
            uniform burns a full-context slab row regardless."""
            sess = eng.batch_session(B, chunk=chunk,
                                     kv_pages=page if paged else 0)
            n = 0
            while sess.can_admit(1, chunk, [1]) and n < 4096:
                sess.admit_begin([1], steps=chunk, sampler=greedy)
                n += 1
            migr = getattr(sess, "migrations", 0)
            sess.close()
            return n, migr

        log(f"prefix cache replay: {n_req} requests, {len(shared)}-token "
            f"shared prefix + {tail_len}-token tails (page={page}); warmup...")
        t0 = time.perf_counter()
        _ttft_replay(True)  # compiles prefill pieces + paged decode groups
        log(f"warmup done in {time.perf_counter() - t0:.1f}s")
        cold_ttfts, cold_migr, _, _ = _ttft_replay(False)
        warm_ttfts, warm_migr, hit_rate, evictions = _ttft_replay(True)
        warm = warm_ttfts[1:]  # request 0 seeds the cache: it IS the cold path
        cold = cold_ttfts
        warm_p50, warm_p99 = _pct(warm, 50), _pct(warm, 99)
        cold_p50, cold_p99 = _pct(cold, 50), _pct(cold, 99)
        log(f"TTFT p50: cold {cold_p50:.1f} ms vs warm {warm_p50:.1f} ms "
            f"(p99 {cold_p99:.1f} vs {warm_p99:.1f}; hit rate "
            f"{hit_rate:.2f}, {evictions} evictions)")
        rows_uni, _ = _capacity(False)
        rows_paged, cap_migr = _capacity(True)
        log(f"rows resident at fixed HBM budget ({B * S} KV token-slots): "
            f"uniform {rows_uni} vs paged {rows_paged}")
        report = {
            "requests": n_req, "shared_tokens": len(shared),
            "tail_tokens": tail_len, "page_tokens": page, "pool": B,
            "cold_ttft_p50_ms": round(cold_p50, 3),
            "cold_ttft_p99_ms": round(cold_p99, 3),
            "warm_ttft_p50_ms": round(warm_p50, 3),
            "warm_ttft_p99_ms": round(warm_p99, 3),
            "prefix_hit_rate": round(hit_rate, 4),
            "prefix_evictions": evictions,
            "budget_kv_tokens": B * S,
            "rows_uniform": rows_uni, "rows_paged": rows_paged,
            "migrations": cold_migr + warm_migr + cap_migr,
        }
        out_path = os.environ.get("BENCH_PREFIX_OUT")
        if out_path:
            with open(out_path, "w") as f:
                json.dump(report, f, indent=2)
            log(f"report written to {out_path}")
        if warm_p50 >= cold_p50:
            raise RuntimeError(
                f"warm (prefix-cached) TTFT p50 {warm_p50:.1f} ms is not "
                f"below cold {cold_p50:.1f} ms on >=50%-shared traffic: "
                f"{report}")
        if rows_paged < rows_uni:
            raise RuntimeError(
                f"paged KV admitted {rows_paged} rows vs uniform "
                f"{rows_uni} at the same budget — must not be fewer: "
                f"{report}")
        if report["migrations"] != 0:
            raise RuntimeError(
                f"paged mode performed {report['migrations']} slab "
                f"migration copies — growth must append pages: {report}")
        return warm_p50, f"{weights}-prefix{n_req}-pg{page}{cfg_tag}"

    # BENCH_OVERLAP=N replays ONE N-request mix through a real pooled
    # BatchSession TWICE on the same TP mesh + quant weights — monolithic
    # shard_map programs vs the microbatch compute/communication-overlap
    # programs (--tp-overlap) — and reports the A/B wall-clock delta. The
    # mode is EXACT by construction, so the replay FAILS unless the two
    # runs stream bit-identical tokens AND the overlap engine actually
    # engaged (dllama_tp_overlap_chunks_total moved; >= 2 resident rows).
    # CPU-runnable (BENCH_MODEL=smoke + the CI lanes' 8 virtual devices):
    # off-TPU the delta is plumbing-only — the ring-vs-fused gather win is
    # an ICI property, so TPU numbers are owed for any perf claim.
    # BENCH_OVERLAP_OUT writes the full report JSON for CI artifacts.
    ovn = _env_count("BENCH_OVERLAP")
    if ovn:
        import numpy as np

        from dllama_tpu import observability
        from dllama_tpu.parallel.mesh import tp_mesh

        # the serving smoke shape has n_kv_heads=4: pick the largest TP
        # degree the head count supports instead of requiring n_dev | kv
        tp = n_dev
        while tp > 1 and cfg.n_kv_heads % tp:
            tp -= 1
        if tp < 2:
            raise RuntimeError(
                "BENCH_OVERLAP needs a TP mesh (run on >1 device, or CPU "
                "with XLA_FLAGS=--xla_force_host_platform_device_count=8)")
        ov_mesh = tp_mesh(tp)
        qkind = weights if weights in ("q40", "q80") else "q40"
        log(f"overlap A/B: tp={tp}, {qkind} weights, building engines...")
        qparams = llama.device_random_quant_params(cfg, kind=qkind, seed=0)
        reg = observability.MetricsRegistry()
        greedy = SamplerConfig(temperature=0.0, seed=0)
        e_mono = Engine(cfg, qparams, greedy, cache_dtype=cache_dtype,
                        mesh=ov_mesh, metrics=None)
        e_ov = Engine(cfg, qparams, greedy, cache_dtype=cache_dtype,
                      mesh=ov_mesh, tp_overlap=True, metrics=reg)
        if not e_ov.tp_overlap_active:
            raise RuntimeError(
                f"overlap engine did not come up overlapped: "
                f"{e_ov.tp_overlap_reason}")

        n_req = max(4, min(ovn, 64))
        B = max(2, min(batch or 4, 8))
        chunk = 8
        rng = np.random.default_rng(0)
        reqs = []
        for i in range(n_req):
            plen = int(rng.integers(4, max(8, cfg.seq_len // 8)))
            steps = chunk * int(rng.integers(1, 4))
            prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, plen)]
            reqs.append((prompt, steps))

        def _overlap_replay(eng):
            """Admit-all pooled drain -> (wall_s, tokens, [streams])."""
            sess = eng.batch_session(B, chunk=chunk)
            got = {}
            pending = list(range(n_req))
            handle_req = {}
            t0 = time.perf_counter()
            while pending or handle_req:
                while pending and sess.free_slots:
                    j = pending.pop(0)
                    h = sess.admit(list(reqs[j][0]), steps=reqs[j][1],
                                   sampler=greedy)
                    handle_req[h] = j
                for h, burst in sess.step_chunk().items():
                    got.setdefault(handle_req[h], []).extend(burst)
                    if sess.is_done(h):
                        sess.release(h)
                        del handle_req[h]
            wall = time.perf_counter() - t0
            sess.close()
            streams = [got[j] for j in range(n_req)]
            return wall, sum(len(s) for s in streams), streams

        def _chunks(registry):
            for line in registry.render().splitlines():
                if line.startswith("dllama_tp_overlap_chunks_total"):
                    return float(line.split()[-1])
            return 0.0

        _overlap_replay(e_mono)  # compile both ways before timing
        _overlap_replay(e_ov)
        engaged_at = _chunks(reg)
        mono_wall, mono_tok, mono_streams = _overlap_replay(e_mono)
        ov_wall, ov_tok, ov_streams = _overlap_replay(e_ov)
        engaged = _chunks(reg) - engaged_at
        if ov_streams != mono_streams:
            diff = [j for j in range(n_req)
                    if ov_streams[j] != mono_streams[j]]
            raise RuntimeError(
                f"overlap replay diverged from monolithic on request(s) "
                f"{diff} — the mode must be bit-identical")
        if engaged <= 0:
            raise RuntimeError(
                "overlap programs never engaged during the timed replay "
                "(dllama_tp_overlap_chunks_total did not move)")
        delta_pct = (mono_wall - ov_wall) / mono_wall * 100.0
        log(f"monolithic {mono_tok / mono_wall:.1f} tok/s "
            f"({mono_wall:.2f}s) vs overlap {ov_tok / ov_wall:.1f} tok/s "
            f"({ov_wall:.2f}s): {delta_pct:+.1f}% wall "
            f"({engaged:.0f} overlapped dispatches)")
        on_tpu = jax.default_backend() == "tpu"
        if not on_tpu:
            log("CPU smoke: delta is plumbing-only — ring-vs-fused gather "
                "wins need ICI; TPU numbers owed")
        report = {
            "requests": n_req, "pool": B, "tp": tp, "weights": qkind,
            "wire": e_ov.tp_wire, "tokens": mono_tok,
            "mono_wall_s": round(mono_wall, 3),
            "overlap_wall_s": round(ov_wall, 3),
            "mono_tok_s": round(mono_tok / mono_wall, 2),
            "overlap_tok_s": round(ov_tok / ov_wall, 2),
            "delta_pct": round(delta_pct, 2),
            "overlap_chunks": engaged,
            "bit_identical": True,
            "backend": jax.default_backend(),
            "tpu_deltas_owed": not on_tpu,
        }
        if not on_tpu:
            report["note"] = ("CPU smoke: structural gates only (bit-"
                              "identity + engagement); throughput deltas "
                              "owed to the TPU battery — the ring-vs-fused "
                              "gather win is an ICI property")
        out_path = os.environ.get("BENCH_OVERLAP_OUT")
        if out_path:
            with open(out_path, "w") as f:
                json.dump(report, f, indent=2)
            log(f"report written to {out_path}")
        return (ov_wall / max(ov_tok, 1)) * 1000.0, \
            f"{qkind}-overlap{n_req}-tp{tp}{cfg_tag}"

    # BENCH_REDUCE=N replays ONE N-request mix through a real pooled
    # BatchSession on the same TP mesh + quant weights THREE ways —
    # gather-only baseline, --tp-reduce plain (row-parallel wo/w2 over the
    # pinned-order ring reduce-scatter), and --tp-reduce q80 (each hop's
    # payload block-quantized) — and gates on the mode's contract: both
    # row modes must replay DETERMINISTICALLY (the pinned ring order) and
    # actually engage (dllama_tp_reduce_chunks_total moved), plain must
    # agree with the baseline streams modulo a bounded handful of greedy
    # near-tie flips (the K-split matmul reassociates the f32 sum), and
    # the analytic per-layer wire model at 7B shapes must come out
    # STRICTLY below the gather-only schedule for the q80 reduce. CPU-runnable (BENCH_MODEL=smoke + the CI lanes' 8
    # virtual devices): off-TPU the wall delta is plumbing-only — the
    # reduce-scatter win is an ICI property, so TPU deltas are owed in the
    # trajectory. BENCH_REDUCE_OUT writes the report JSON for CI.
    redn = _env_count("BENCH_REDUCE")
    if redn:
        import numpy as np

        from dllama_tpu import observability
        from dllama_tpu.parallel.mesh import tp_mesh
        from dllama_tpu.parallel.quant_tp import validate_tp_reduce
        from dllama_tpu.runtime.generate import dense_stack_wire_feat_bytes

        tp = n_dev
        while tp > 1 and cfg.n_kv_heads % tp:
            tp -= 1
        if tp < 2:
            raise RuntimeError(
                "BENCH_REDUCE needs a TP mesh (run on >1 device, or CPU "
                "with XLA_FLAGS=--xla_force_host_platform_device_count=8)")
        qkind = weights if weights in ("q40", "q80") else "q40"
        while tp > 1 and validate_tp_reduce(cfg, qkind, tp) is not None:
            tp //= 2  # shard-granularity misfit at this degree
        if tp < 2:
            raise RuntimeError(
                f"BENCH_REDUCE: no tp degree satisfies the {qkind} "
                f"row-shard granularity at dim={cfg.dim}")
        red_mesh = tp_mesh(tp)
        log(f"reduce A/B/C: tp={tp}, {qkind} weights, building engines...")
        qparams = llama.device_random_quant_params(cfg, kind=qkind, seed=0)
        greedy = SamplerConfig(temperature=0.0, seed=0)
        e_base = Engine(cfg, qparams, greedy, cache_dtype=cache_dtype,
                        mesh=red_mesh, metrics=None)
        engines = {}
        regs = {}
        for mode in ("plain", "q80"):
            regs[mode] = observability.MetricsRegistry()
            engines[mode] = Engine(
                cfg, qparams, greedy, cache_dtype=cache_dtype,
                mesh=red_mesh, tp_reduce=mode, metrics=regs[mode])
            if not engines[mode].tp_reduce_active:
                raise RuntimeError(
                    f"tp_reduce={mode} engine did not come up row-parallel: "
                    f"{engines[mode].tp_reduce_reason}")

        n_req = max(4, min(redn, 64))
        B = max(2, min(batch or 4, 8))
        chunk = 8
        rng = np.random.default_rng(0)
        reqs = []
        for i in range(n_req):
            plen = int(rng.integers(4, max(8, cfg.seq_len // 8)))
            steps = chunk * int(rng.integers(1, 4))
            prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, plen)]
            reqs.append((prompt, steps))

        def _reduce_replay(eng):
            """Admit-all pooled drain -> (wall_s, tokens, [streams])."""
            sess = eng.batch_session(B, chunk=chunk)
            got = {}
            pending = list(range(n_req))
            handle_req = {}
            t0 = time.perf_counter()
            while pending or handle_req:
                while pending and sess.free_slots:
                    j = pending.pop(0)
                    h = sess.admit(list(reqs[j][0]), steps=reqs[j][1],
                                   sampler=greedy)
                    handle_req[h] = j
                for h, burst in sess.step_chunk().items():
                    got.setdefault(handle_req[h], []).extend(burst)
                    if sess.is_done(h):
                        sess.release(h)
                        del handle_req[h]
            wall = time.perf_counter() - t0
            sess.close()
            streams = [got[j] for j in range(n_req)]
            return wall, sum(len(s) for s in streams), streams

        def _red_chunks(registry):
            for line in registry.render().splitlines():
                if line.startswith("dllama_tp_reduce_chunks_total"):
                    return float(line.split()[-1])
            return 0.0

        _reduce_replay(e_base)  # compile all three before timing
        for mode in ("plain", "q80"):
            _reduce_replay(engines[mode])
        engaged_at = {m: _red_chunks(regs[m]) for m in regs}
        base_wall, base_tok, base_streams = _reduce_replay(e_base)
        walls, toks = {}, {}
        walls["plain"], toks["plain"], plain_streams = \
            _reduce_replay(engines["plain"])
        walls["q80"], toks["q80"], q80_streams = \
            _reduce_replay(engines["q80"])
        _, _, plain_again = _reduce_replay(engines["plain"])
        _, _, q80_again = _reduce_replay(engines["q80"])
        engaged = {m: _red_chunks(regs[m]) - engaged_at[m] for m in regs}
        # the ring's bitwise guarantee is the PINNED ORDER (reproducible
        # run to run — gated hard below); vs the gather-only baseline the
        # K-split matmul legitimately reassociates the f32 sum, so a
        # greedy near-tie can flip a token on rare requests. Plain must
        # therefore match the baseline on all but a bounded few requests
        # (same lengths always), not bitwise on every stream — the
        # bitwise schedule property itself is pinned by
        # tests/test_tp_reduce.py against a numpy reference.
        if plain_again != plain_streams or q80_again != q80_streams:
            raise RuntimeError(
                "row-parallel replay is not deterministic — the ring "
                "order is pinned, so identical replays must stream "
                "identical tokens")
        for mode, streams in (("plain", plain_streams),
                              ("q80", q80_streams)):
            if [len(s) for s in streams] != [len(s) for s in base_streams]:
                raise RuntimeError(
                    f"{mode} row-parallel replay lost/added tokens "
                    f"vs baseline")
        plain_flips = [j for j in range(n_req)
                       if plain_streams[j] != base_streams[j]]
        if len(plain_flips) > max(1, n_req // 4):
            raise RuntimeError(
                f"plain row-parallel replay diverged from gather-only on "
                f"{len(plain_flips)}/{n_req} request(s) {plain_flips} — "
                f"beyond near-tie reassociation flips; row matmuls wrong?")
        if plain_flips:
            log(f"plain row replay: {len(plain_flips)}/{n_req} request(s) "
                f"flipped a greedy near-tie vs baseline "
                f"(f32 reassociation): {plain_flips}")
        for mode in ("plain", "q80"):
            if engaged[mode] <= 0:
                raise RuntimeError(
                    f"tp_reduce={mode} programs never engaged during the "
                    f"timed replay (dllama_tp_reduce_chunks_total "
                    f"did not move)")
        # analytic per-layer wire model at 7B shapes (q80-compressed
        # gathers both sides, the deployed configuration): the q80 reduce
        # must model strictly below the gather-only schedule. The plain
        # f32 reduce does NOT (its reduce hops are 4 B/feature vs the
        # baseline's 1.125 B/feature hidden gather) — it is the
        # bit-reproducibility mode, not the bandwidth mode.
        cfg7 = type("", (), {"n_layers": 32, "dim": 4096})()
        hidden7 = 11008
        base7 = dense_stack_wire_feat_bytes(cfg7, hidden7, 1.125)
        row7 = dense_stack_wire_feat_bytes(cfg7, hidden7, 1.125, "q80")
        if row7 >= base7:
            raise RuntimeError(
                f"modeled 7B bytes-on-wire per token: row-parallel q80 "
                f"{row7:.0f} is not below gather-only {base7:.0f}")
        log(f"modeled 7B wire/token: gather-only {base7 / 1e3:.1f} KB vs "
            f"row+q80 reduce {row7 / 1e3:.1f} KB "
            f"({(1 - row7 / base7) * 100.0:+.1f}% saved)")
        for mode in ("plain", "q80"):
            log(f"baseline {base_tok / base_wall:.1f} tok/s "
                f"({base_wall:.2f}s) vs tp_reduce={mode} "
                f"{toks[mode] / walls[mode]:.1f} tok/s "
                f"({walls[mode]:.2f}s): "
                f"{(base_wall - walls[mode]) / base_wall * 100.0:+.1f}% "
                f"wall ({engaged[mode]:.0f} row dispatches)")
        on_tpu = jax.default_backend() == "tpu"
        if not on_tpu:
            log("CPU smoke: structural gates only (determinism, engagement, "
                "bounded plain flips, wire model); TPU deltas owed")
        report = {
            "requests": n_req, "pool": B, "tp": tp, "weights": qkind,
            "tokens": base_tok,
            "base_wall_s": round(base_wall, 3),
            "plain_wall_s": round(walls["plain"], 3),
            "q80_wall_s": round(walls["q80"], 3),
            "base_tok_s": round(base_tok / base_wall, 2),
            "plain_tok_s": round(toks["plain"] / walls["plain"], 2),
            "q80_tok_s": round(toks["q80"] / walls["q80"], 2),
            "plain_near_tie_flips": len(plain_flips),
            "deterministic": True,
            "reduce_chunks_plain": engaged["plain"],
            "reduce_chunks_q80": engaged["q80"],
            "wire_kb_token_smoke_base": round(e_base.wire_kb(1), 3),
            "wire_kb_token_smoke_q80": round(engines["q80"].wire_kb(1), 3),
            "modeled_7b_wire_base_kb": round(base7 / 1e3, 2),
            "modeled_7b_wire_row_q80_kb": round(row7 / 1e3, 2),
            "modeled_7b_wire_saved_pct": round((1 - row7 / base7) * 100, 2),
            "backend": jax.default_backend(),
            "tpu_deltas_owed": not on_tpu,
        }
        if not on_tpu:
            report["note"] = ("CPU smoke: structural gates only — the "
                              "reduce-scatter bandwidth win is an ICI "
                              "property, TPU deltas owed to the battery")
        out_path = os.environ.get("BENCH_REDUCE_OUT")
        if out_path:
            with open(out_path, "w") as f:
                json.dump(report, f, indent=2)
            log(f"report written to {out_path}")
        return (walls["q80"] / max(toks["q80"], 1)) * 1000.0, \
            f"{qkind}-reduce{n_req}-tp{tp}{cfg_tag}"

    # BENCH_CONTINUOUS=N replays a staggered-arrival serving workload of N
    # requests through BOTH schedulers — the continuous slot pool
    # (Engine.batch_session: rows admitted mid-flight between fused chunks)
    # and the old static window batcher (generate_batch run to full drain,
    # then re-batch the queue) — and reports aggregate tok/s plus
    # per-request latency for each. Every third request gets a 4x budget:
    # that is the static pathology (short rows queue behind the long row's
    # drain) continuous batching exists to remove. CPU-runnable; pair with
    # BENCH_MODEL=smoke off-TPU so the replay fits a CI wall clock.
    cont = _env_count("BENCH_CONTINUOUS")
    if cont:
        rng_c = __import__("numpy").random.default_rng(2)
        prompt = [int(t) for t in rng_c.integers(1, cfg.vocab_size, 6)]
        B = min(max(2, batch or 4), cont)
        chunk = 8
        # budgets in whole chunks so every decode dispatch compiles at ONE
        # n_steps; same prompt length -> one prefill bucket
        base = max(chunk, bench_steps // 4 // chunk * chunk)
        cap = (cfg.seq_len - len(prompt)) // chunk * chunk
        reqs = [(prompt, min(cap, 4 * base if i % 3 == 2 else base))
                for i in range(cont)]
        log(f"continuous-batching replay: {cont} requests, pool={B}, "
            f"chunk={chunk}, budgets {base}/{min(cap, 4 * base)}")
        old_chunk = eng.decode_chunk
        eng.decode_chunk = chunk  # static batcher drains at the same grain
        greedy = SamplerConfig(temperature=0.0, seed=0)
        # warmup compiles every shape either replay can hit — the pool's
        # (B, chunk) decode loop, the single-row prefill bucket, and each
        # static group size 1..B — and times one resident chunk to set a
        # near-capacity arrival gap (pool service rate ~1 request/chunk at
        # these budgets; 1.5 chunks/arrival -> ~0.7 utilization)
        log("warmup (compile: pool chunk + static group sizes)...")
        t0 = time.perf_counter()
        sess = eng.batch_session(B, chunk=chunk)
        s0 = sess.admit(list(prompt), steps=3 * chunk, sampler=greedy)
        sess.step_chunk()  # first chunk pays the compile; don't time it
        t1 = time.perf_counter()
        sess.step_chunk()
        chunk_s = time.perf_counter() - t1
        while not sess.is_done(s0):
            sess.step_chunk()
        sess.close()
        for b in range(1, B + 1):
            eng.generate_batch([list(prompt)] * b, steps=chunk,
                               sampler=greedy)
        log(f"warmup done in {time.perf_counter() - t0:.1f}s "
            f"({chunk_s * 1000:.0f} ms/resident chunk)")
        arrivals = [i * 1.5 * chunk_s for i in range(cont)]
        results = {}
        for mode in ("static", "continuous"):
            wall, lats, toks = _serving_replay(eng, mode, reqs, arrivals,
                                               B, chunk)
            results[mode] = (wall, toks)
            ms_sorted = sorted(x * 1000.0 for x in lats)
            log(f"{mode:>10}: {toks} tokens in {wall:.2f}s = "
                f"{toks / wall:.1f} tok/s aggregate | request latency mean "
                f"{sum(ms_sorted) / len(ms_sorted):.0f} ms, "
                f"p50 {ms_sorted[len(ms_sorted) // 2]:.0f} ms, "
                f"max {ms_sorted[-1]:.0f} ms")
        eng.decode_chunk = old_chunk
        (c_wall, c_toks), (s_wall, s_toks) = (results["continuous"],
                                              results["static"])
        log(f"continuous vs static: {c_toks / c_wall:.1f} vs "
            f"{s_toks / s_wall:.1f} tok/s aggregate "
            f"({(c_toks / c_wall) / (s_toks / s_wall):.2f}x)")
        return (c_wall * 1000.0 / max(1, c_toks),
                f"{weights}-continuous{cont}x{B}{cfg_tag}")

    # BENCH_FAULTS=N replays a concurrent workload through the REAL serving
    # scheduler (ServerState + Batcher + supervisor) with a deterministic
    # fault plan installed (DLLAMA_FAULTS, default step_chunk:raise:every=3).
    # The measurement is BOUNDEDNESS, not speed: every request must resolve
    # — tokens or a typed error — within the join timeout, with the
    # supervisor restarting the scheduler through every injected crash. A
    # hang fails the bench. CPU-runnable (BENCH_MODEL=smoke).
    nfaults = _env_count("BENCH_FAULTS")
    if nfaults:
        import threading as _threading

        from dllama_tpu import faults as _faults
        from dllama_tpu.serving.api_server import ServerState

        class _FakeTok:
            # stop handling off: rows run to budget (no tokenizer needed —
            # the replay exercises the scheduler, not detokenization)
            eos_id = -1

            def piece_id(self, _b):
                return -1

        fspec = os.environ.get("DLLAMA_FAULTS") or "step_chunk:raise:every=3"
        plan = _faults.install(fspec)
        st = ServerState(eng, _FakeTok(), cfg, model_name="bench",
                         batch_window_ms=5.0, batch_max=min(4, nfaults),
                         batch_chunk=4)
        rng_f = __import__("numpy").random.default_rng(3)
        fprompt = [int(t) for t in rng_f.integers(1, cfg.vocab_size, 6)]
        fsteps = max(8, bench_steps // 8)
        outcomes = {"ok": 0, "error": 0, "hang": 0}
        olock = _threading.Lock()

        def _one_request():
            try:
                st.batcher.submit(list(fprompt), fsteps,
                                  SamplerConfig(temperature=0.0, seed=0))
                key = "ok"
            except RuntimeError:
                key = "error"  # typed + bounded: exactly the contract
            with olock:
                outcomes[key] += 1

        log(f"fault replay: {nfaults} requests under '{fspec}'")
        t0 = time.perf_counter()
        threads = [_threading.Thread(target=_one_request, daemon=True)
                   for _ in range(nfaults)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120.0)
            if t.is_alive():
                with olock:
                    outcomes["hang"] += 1
        wall = time.perf_counter() - t0
        _faults.clear()
        log(f"fault replay: {outcomes} in {wall:.2f}s | injected "
            f"{plan.counters()} | scheduler crashes "
            f"{st.batcher.crash_count}")
        if outcomes["hang"]:
            raise RuntimeError(
                f"fault replay left requests hanging: {outcomes}")
        return (wall * 1000.0 / max(1, nfaults),
                f"{weights}-faults{nfaults}{cfg_tag}")

    # BENCH_INTEGRITY=1 measures the numeric-health watchdog two ways.
    # (1) Overhead: batched decode with checks on vs a second engine built
    #     with numeric_checks=False — the per-row isfinite AND rides the
    #     fused decode scan, so the target is < 1% (CPU numbers are noisy;
    #     the number is REPORTED, the bench does not fail on it).
    # (2) Quarantine replay: a slot-pool run is repeated with
    #     ``logits:nan:row=1`` installed — the poisoned row must finish
    #     "error" and every sibling row's stream must be BIT-IDENTICAL to
    #     the clean run (per-row sampler chains + per-row cache slabs mean
    #     corruption must never cross rows). A divergence fails the bench.
    if _env_count("BENCH_INTEGRITY"):
        from dllama_tpu import faults as _faults

        B = max(2, min(batch or 4, 8))
        isteps = max(16, min(bench_steps, cfg.seq_len - 8) // 2)
        greedy = SamplerConfig(temperature=0.0, seed=0)

        def _timed_batch(e):
            e.generate_batch([[1]] * B, steps=isteps, sampler=greedy)
            best = None
            for _ in range(3):
                t1 = time.perf_counter()
                out = e.generate_batch([[1]] * B, steps=isteps,
                                       sampler=greedy)
                eff = ((time.perf_counter() - t1) * 1000.0
                       / max(1, len(out[0])) / B)
                best = eff if best is None else min(best, eff)
            return best

        log(f"integrity: timing watchdog overhead (B={B}, {isteps} steps)")
        on_ms = _timed_batch(eng)
        # second engine without the watchdog: rebuild params (the first
        # Engine may have fused this frame's reference away)
        if weights in ("q40", "q80"):
            params2 = llama.device_random_quant_params(cfg, kind=weights,
                                                       seed=0)
        else:
            params2 = llama.device_random_params(cfg, seed=0, mesh=mesh)
        eng_off = Engine(cfg, params2, SamplerConfig(temperature=0.0),
                         cache_dtype=cache_dtype, mesh=mesh,
                         decode_chunk=bench_steps, numeric_checks=False)
        del params2
        off_ms = _timed_batch(eng_off)
        overhead = (on_ms - off_ms) / off_ms * 100.0
        log(f"watchdog overhead: on {on_ms:.4f} vs off {off_ms:.4f} "
            f"ms/token effective = {overhead:+.2f}% (target < 1%)")

        def _pool_run(e, fault_spec=None):
            """Admit B sampled rows, drain, return (streams, finishes)."""
            if fault_spec:
                _faults.install(fault_spec)
            try:
                sess = e.batch_session(B, chunk=8)
                slots = [sess.admit([1], steps=isteps,
                                    sampler=SamplerConfig(temperature=0.8,
                                                          seed=100 + i))
                         for i in range(B)]
                streams = {b: [] for b in slots}
                fins = {}
                while len(fins) < B:
                    for b, burst in sess.step_chunk().items():
                        streams[b].extend(burst)
                        if sess.is_done(b) and b not in fins:
                            fins[b] = sess.finish_reason(b)
                            sess.release(b)
                sess.close()
            finally:
                if fault_spec:
                    _faults.clear()
            return ([streams[b] for b in slots], [fins[b] for b in slots])

        log("integrity: quarantine replay (clean, then logits:nan:row=1)")
        clean_streams, clean_fins = _pool_run(eng)
        pois_streams, pois_fins = _pool_run(eng, "logits:nan:row=1")
        if pois_fins[1] != "error":
            raise RuntimeError(
                f"poisoned row finished {pois_fins[1]!r}, want 'error' "
                f"(finishes: {pois_fins})")
        diverged = [i for i in range(B)
                    if i != 1 and pois_streams[i] != clean_streams[i]]
        if diverged:
            raise RuntimeError(
                f"sibling rows {diverged} diverged from the clean run "
                "under a row-1 poisoning — quarantine is not row-isolated")
        log(f"quarantine replay: row 1 finished 'error' after "
            f"{len(pois_streams[1])} tokens; {B - 1} sibling rows "
            f"bit-identical (finishes: {pois_fins})")
        return (on_ms,
                f"{weights}-integrity-b{B}-overhead"
                f"{overhead:.2f}pct{cfg_tag}")

    # BENCH_OBS=N measures the observability subsystem two ways.
    # (1) Overhead: batched decode on the instrumented engine vs a second
    #     engine built with metrics=None — every telemetry point on the hot
    #     path is one `is not None` check plus a histogram observe per
    #     CHUNK (not per token), so the budget is < 1% and the bench FAILS
    #     above it (min-of-reps on identical work keeps CPU noise out).
    # (2) Latency telemetry: N requests replayed through the REAL serving
    #     scheduler on a FRESH registry, once per decode path (solo
    #     sequential, spec all-greedy window, continuous sampled window),
    #     reporting TTFT/TPOT p50/p95 per path from the histogram
    #     reservoirs — the numbers RESULTS.md quotes. CPU-runnable
    #     (BENCH_MODEL=smoke).
    nobs = _env_count("BENCH_OBS")
    if nobs:
        import threading as _threading

        from dllama_tpu import observability as _obs
        from dllama_tpu.serving.api_server import ServerState

        B = max(2, min(batch or 4, 8))
        osteps = max(16, min(bench_steps, cfg.seq_len - 8) // 2)
        greedy = SamplerConfig(temperature=0.0, seed=0)

        def _timed_obs(e):
            e.generate_batch([[1]] * B, steps=osteps, sampler=greedy)
            best = None
            for _ in range(8):
                t1 = time.perf_counter()
                out = e.generate_batch([[1]] * B, steps=osteps,
                                       sampler=greedy)
                eff = ((time.perf_counter() - t1) * 1000.0
                       / max(1, len(out[0])) / B)
                best = eff if best is None else min(best, eff)
            return best

        log(f"obs: timing telemetry overhead (B={B}, {osteps} steps)")
        # the on-leg carries the FULL observability stack: the history
        # sampler + burn-rate engine run at 4x production cadence (0.25s
        # vs the 1s default) against the engine's registry while it
        # decodes, so the <1% budget now covers the sampler thread too.
        # (One full-registry pass costs ~0.8ms of GIL; 20Hz would burn
        # 1.6% on the sampler alone — more than the whole budget.)
        from dllama_tpu.obsv import (BurnRateEngine as _BurnEng,
                                     Sampler as _TsSampler,
                                     TimeSeriesStore as _TsStore)
        from dllama_tpu.serving.lifecycle import parse_slo_classes as _pslo

        _tstore = _TsStore()
        _tsampler = _TsSampler(
            _obs.default_registry(), _tstore, interval_s=0.25,
            hooks=(_BurnEng(_tstore,
                            _pslo("interactive:ttft=500,tpot=50,err=0.01"),
                            _obs.default_registry()).evaluate,))
        if weights in ("q40", "q80"):
            params2 = llama.device_random_quant_params(cfg, kind=weights,
                                                       seed=0)
        else:
            params2 = llama.device_random_params(cfg, seed=0, mesh=mesh)
        eng_off = Engine(cfg, params2, SamplerConfig(temperature=0.0),
                         cache_dtype=cache_dtype, mesh=mesh,
                         decode_chunk=bench_steps, metrics=None)
        del params2
        # paired trials, median delta — a fixed on-first ordering folds
        # ambient machine noise into one side of a sub-percent
        # comparison, and any single trial can catch a burst; a genuine
        # per-token cost shifts every trial. The sampler thread only runs
        # while the instrumented engine is the one being timed.
        deltas, pairs = [], []
        for _ in range(5):
            off_t = _timed_obs(eng_off)
            _tsampler.start()
            try:
                on_t = _timed_obs(eng)
            finally:
                _tsampler.stop()
            pairs.append((on_t, off_t))
            deltas.append((on_t - off_t) / off_t * 100.0)
        overhead = sorted(deltas)[len(deltas) // 2]
        on_ms, off_ms = pairs[sorted(range(len(deltas)),
                                     key=lambda i: deltas[i])[
                                         len(deltas) // 2]]
        log(f"telemetry overhead: on {on_ms:.4f} vs off {off_ms:.4f} "
            f"ms/token effective, median of 5 trials = {overhead:+.2f}% "
            "(budget < 1%; trials "
            + " ".join(f"{d:+.2f}%" for d in deltas) + ")")
        if overhead >= 1.0:
            raise RuntimeError(
                f"telemetry overhead {overhead:+.2f}% exceeds the 1% "
                "budget (instrumented vs metrics=None engine)")

        class _ObsTok:
            eos_id = -1  # no stops: rows run to budget (scheduler replay)

            def piece_id(self, _b):
                return -1

        reg = _obs.MetricsRegistry()  # fresh: percentiles from THIS replay
        st = ServerState(eng, _ObsTok(), cfg, model_name="bench",
                         spec_draft=4, batch_window_ms=5.0, batch_max=B,
                         batch_chunk=8, metrics=reg)
        rng_o = __import__("numpy").random.default_rng(5)
        oprompt = [int(t) for t in rng_o.integers(1, cfg.vocab_size, 6)]
        rsteps = max(8, min(bench_steps // 4, cfg.seq_len - len(oprompt)))

        def _one(i, sampler):
            tr = _obs.RequestTrace(_obs.new_request_id())
            tr.tokens_in = len(oprompt)
            try:
                row = st.batcher.submit(list(oprompt), rsteps, sampler,
                                        trace=tr)
                tr.tokens_out = len(row)
                tr.finish_reason = "length"
            except RuntimeError as e:
                tr.finish_reason = "error"
                log(f"obs replay request failed: {e!r}")
            st.finish_request(tr)

        # solo: sequential singletons; spec: concurrent all-greedy window
        # (spec_draft=4 routes it to the batched verify); continuous:
        # concurrent sampled window (mixed samplers can't speculate)
        plans = [
            ("solo", False, lambda i: greedy),
            ("spec", True, lambda i: greedy),
            ("continuous", True,
             lambda i: SamplerConfig(temperature=0.8, seed=100 + i)),
        ]
        for pname, concurrent, mk in plans:
            log(f"obs replay: {nobs} requests -> {pname} path")
            if concurrent:
                ths = [_threading.Thread(target=_one, args=(i, mk(i)),
                                         daemon=True)
                       for i in range(nobs)]
                for t in ths:
                    t.start()
                for t in ths:
                    t.join(timeout=300.0)
            else:
                for i in range(nobs):
                    _one(i, mk(i))
        for pname in ("solo", "spec", "continuous"):
            n = st._m_ttft.count(path=pname)
            if not n:
                log(f"{pname:>10}: no requests routed here (window "
                    "timing); see dllama_requests_path_total")
                continue
            log(f"{pname:>10}: n={n} TTFT p50 "
                f"{st._m_ttft.percentile(50, path=pname):.1f} ms, p95 "
                f"{st._m_ttft.percentile(95, path=pname):.1f} ms | TPOT "
                f"p50 {st._m_tpot.percentile(50, path=pname):.2f} ms, p95 "
                f"{st._m_tpot.percentile(95, path=pname):.2f} ms")
        routed = {c["labels"].get("path"): c["value"]
                  for c in reg.snapshot()
                  .get("dllama_requests_path_total", {}).get("values", [])}
        log(f"paths routed: {routed}")

        # (3) Fleet front-door A/B: the same proxy hot path through a REAL
        #     RouterState twice — fleet observability on (flight recorder +
        #     a federation scrape loop hitting /metrics/fleet while traffic
        #     flows) vs off — against in-process stub replicas, so the
        #     delta isolates the router-side cost of parent-span headers,
        #     Server-Timing hop attribution, the flight ring, and
        #     concurrent federation. Same < 1% hard-fail budget; stubs are
        #     stdlib HTTP, no jax: CPU-smokeable.
        import http.client as _hc
        import json as _jsn
        from http.server import BaseHTTPRequestHandler as _BH
        from http.server import ThreadingHTTPServer as _TS

        from dllama_tpu.serving import router as _rt

        class _StubReplica(_BH):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *a):
                pass

            def _send(self, body, ctype="application/json", extra=()):
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                for k, v in extra:
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/ready":
                    self._send(_jsn.dumps({
                        "status": "ok", "replica_id": "bench-stub",
                        "time_us": _obs.mono_to_us(),
                        "load": {"slots_occupied": 0, "slots_total": 8,
                                 "queue_depth": 0, "kv_pages_free": 64,
                                 "kv_pages_total": 64,
                                 "prefix_hit_rate": 0.0}}).encode())
                else:  # /metrics for the federation scrape loop
                    self._send(
                        b"# TYPE dllama_http_requests_total counter\n"
                        b'dllama_http_requests_total{route="/x"} 1\n',
                        ctype="text/plain; version=0.0.4")

            def do_POST(self):
                n = int(self.headers.get("Content-Length") or 0)
                self.rfile.read(n)
                self._send(
                    _jsn.dumps({"choices": [{"message": {
                        "role": "assistant", "content": "ok"}}]}).encode(),
                    extra=(("Server-Timing",
                            "queue;dur=0.1, prefill;dur=0.2, "
                            "decode;dur=0.3"),))

        def _fleet_up(obs_on):
            """One router fleet (2 stub replicas) with observability on or
            off; returns (router_port, teardown). The on fleet carries the
            full stack — flight recorder, 0.05s history sampler, and a
            hostile federation loop (/metrics/fleet at 20Hz, history +
            alerts at 2Hz, 10-30x denser than any real dashboard)."""
            ups = [_TS(("127.0.0.1", 0), _StubReplica) for _ in range(2)]
            for u in ups:
                _threading.Thread(target=u.serve_forever,
                                  daemon=True).start()
            state = _rt.RouterState(
                [_rt.Replica("127.0.0.1", u.server_address[1])
                 for u in ups],
                probe_interval_s=3600.0, metrics=_obs.MetricsRegistry(),
                enable_flight=obs_on,
                # 0 = the sampler thread never starts on the off fleet
                ts_interval=0.05 if obs_on else 0.0)
            state.probe_once()
            state.sampler.start()
            srv = _rt.create_router_server(state, host="127.0.0.1", port=0)
            port = srv.server_address[1]
            _threading.Thread(target=srv.serve_forever, daemon=True).start()
            stop = _threading.Event()
            if obs_on:
                def _scrape_loop():
                    i = 0
                    while not stop.is_set():
                        state.federate()
                        if i % 10 == 0:
                            state.federate_history(60.0)
                            state.federate_alerts()
                        i += 1
                        stop.wait(0.05)
                _threading.Thread(target=_scrape_loop, daemon=True).start()

            def _down():
                stop.set()
                state.sampler.stop()
                srv.shutdown()
                srv.server_close()
                for u in ups:
                    u.shutdown()
                    u.server_close()
            return port, _down

        log("obs: fleet front-door A/B (proxy hot path, fleet obs on/off)")
        # Both fleets serve SIMULTANEOUSLY and the probe alternates single
        # requests between them (swapping within-pair order every
        # iteration), so both sides sample identical machine conditions —
        # sequential legs fold ambient noise into whichever side runs in
        # the worse window (measured at +-10% phantom deltas on this very
        # comparison). Per trial the p10 per-request floor beats a min
        # (a min is a rare-event statistic); the gate takes the median of
        # three trial deltas — a genuine per-request cost shifts every
        # trial, a burst shifts one.
        body = _jsn.dumps({
            "model": "bench", "max_tokens": 1,
            "messages": [{"role": "user", "content": "x"}]}).encode()
        port_off, down_off = _fleet_up(False)
        port_on, down_on = _fleet_up(True)
        try:
            conn_off = _hc.HTTPConnection("127.0.0.1", port_off)
            conn_on = _hc.HTTPConnection("127.0.0.1", port_on)

            def _one(conn):
                t1 = time.perf_counter()
                conn.request("POST", "/v1/chat/completions", body=body,
                             headers={"Content-Type": "application/json"})
                r = conn.getresponse()
                r.read()
                return (time.perf_counter() - t1) * 1000.0

            for _ in range(100):  # warm sockets, code paths, scrape loop
                _one(conn_off), _one(conn_on)
            deltas, floors = [], []
            for _trial in range(3):
                offs, ons = [], []
                for i in range(400):
                    if i % 2:
                        offs.append(_one(conn_off))
                        ons.append(_one(conn_on))
                    else:
                        ons.append(_one(conn_on))
                        offs.append(_one(conn_off))
                offs.sort()
                ons.sort()
                p_off, p_on = offs[len(offs) // 10], ons[len(ons) // 10]
                floors.append((p_on, p_off))
                deltas.append((p_on - p_off) / p_off * 100.0)
            conn_off.close()
            conn_on.close()
        finally:
            down_on()
            down_off()
        fl_over = sorted(deltas)[len(deltas) // 2]
        fl_on, fl_off = floors[sorted(range(3),
                                      key=lambda i: deltas[i])[1]]
        log(f"fleet front-door overhead: on {fl_on:.3f} vs off "
            f"{fl_off:.3f} ms/request p10, median of 3 trials = "
            f"{fl_over:+.2f}% (budget < 1%; trials "
            + " ".join(f"{d:+.2f}%" for d in deltas) + ")")
        if fl_over >= 1.0:
            raise RuntimeError(
                f"fleet observability overhead {fl_over:+.2f}% exceeds "
                "the 1% budget (flight+sampler+federation on vs off "
                "through the router front door)")
        return (on_ms,
                f"{weights}-obs-b{B}-overhead{overhead:.2f}pct{cfg_tag}")

    # BENCH_SPEC=K measures speculative decoding (prompt-lookup drafts of up
    # to K tokens, exact greedy): solo generate_spec, or — with BENCH_BATCH —
    # generate_batch_spec (draft_len+1 positions x B rows per weight pass).
    # The prompt repeats a short phrase so drafting has something to match;
    # the acceptance rate is printed so the number can be read honestly
    # (random weights don't generate Shakespeare, but greedy loops repeat).
    spec = _env_count("BENCH_SPEC")
    if spec and batch > 1 and not getattr(eng, "supports_batch_spec", True):
        # dense-pjit mesh engines have no shard_map verify wrapper — the
        # spec-batch combination would raise; measure plain batched decode
        # and SAY so instead of dying mid-battery (ADVICE r05)
        log(f"BENCH_SPEC={spec} with BENCH_BATCH={batch}: batched spec "
            "verify unavailable on the dense-pjit mesh path; falling back "
            "to plain batched decode")
        spec = 0
    if spec:
        rng_p = __import__("numpy").random.default_rng(1)
        phrase = [int(t) for t in rng_p.integers(1, cfg.vocab_size, 6)]
        prompt = (phrase * 6)[:30]
        if batch > 1:
            prompts = [list(prompt)] * batch
            log(f"warmup (batched spec, B={batch}, draft={spec})...")
            eng.generate_batch_spec(prompts, steps=bench_steps, draft_len=spec)
            times = []
            for rep in range(3):
                t1 = time.perf_counter()
                rows, stats = eng.generate_batch_spec(
                    prompts, steps=bench_steps, draft_len=spec)
                wall = (time.perf_counter() - t1) * 1000.0
                emitted = stats["emitted"]
                times.append(wall / emitted)
                log(f"rep {rep}: {wall / emitted:.3f} ms/token effective "
                    f"({emitted} tokens, {stats['verify_steps']} launches, "
                    f"{stats['accepted_drafts']} drafts accepted)")
            return min(times), f"{weights}-spec{spec}-batch{batch}{cfg_tag}"
        log(f"warmup (solo spec, draft={spec})...")
        list(eng.generate_spec(list(prompt), steps=bench_steps))
        times = []
        for rep in range(3):
            t1 = time.perf_counter()
            toks = [t for t, _ in eng.generate_spec(list(prompt),
                                                    steps=bench_steps)]
            wall = (time.perf_counter() - t1) * 1000.0
            times.append(wall / max(1, len(toks)))
            log(f"rep {rep}: {wall / max(1, len(toks)):.3f} ms/token "
                f"({len(toks)} tokens)")
        return min(times), f"{weights}-spec{spec}{cfg_tag}{flash_tag}"

    # BENCH_BATCH=N measures BATCHED decode: N sequences share one weight
    # stream per step (Engine.generate_batch), so the reported value is the
    # EFFECTIVE ms/token across the batch (wall / emitted / N) — decode is
    # bandwidth-bound, so this is the throughput headline the reference's
    # batch=1 design cannot post
    if batch > 1:
        log(f"warmup (batch={batch}, {bench_steps} fused steps, incl. compile)...")
        t0 = time.perf_counter()
        eng.generate_batch([[1]] * batch, steps=bench_steps)
        log(f"warmup done in {time.perf_counter() - t0:.1f}s")
        times = []
        for rep in range(3):
            t1 = time.perf_counter()
            out = eng.generate_batch([[1]] * batch, steps=bench_steps)
            wall_ms = (time.perf_counter() - t1) * 1000.0
            emitted = len(out[0])  # generate_batch clamps to the context
            eff = wall_ms / emitted / batch
            times.append(eff)
            log(f"rep {rep}: {wall_ms / emitted:.3f} ms/step over {emitted} "
                f"steps, {eff:.3f} ms/token effective x{batch}")
        return min(times), f"{weights}-batch{batch}{cfg_tag}{flash_tag}"

    log(f"warmup ({bench_steps} fused steps, incl. compile)...")
    t0 = time.perf_counter()
    eng.generate_fused([1], steps=bench_steps)  # same n_steps as the timed runs
    log(f"warmup done in {time.perf_counter() - t0:.1f}s")

    times = []
    for rep in range(3):
        t1 = time.perf_counter()
        toks, _, decode_ms = eng.generate_fused([1], steps=bench_steps)
        wall_ms = (time.perf_counter() - t1) * 1000.0
        times.append(wall_ms / bench_steps)
        log(f"rep {rep}: {wall_ms / bench_steps:.3f} ms/token ({bench_steps} tokens)")
    return min(times), f"{weights}{cfg_tag}{flash_tag}"


def run_router_bench(n: int) -> dict:
    """BENCH_ROUTER=N: fleet front-door replay, jax-free IN THIS PROCESS
    (the replicas are `cli serve` subprocesses pinned to CPU). Four phases
    against a 2-replica fleet of the smoke shape:

      solo      N staggered chat requests through a router over ONE replica
      fleet     the same workload through a router over both — aggregate
                req/s must beat solo (gate enforced only on multi-core
                hosts: a 1-CPU runner timeshares the replicas, recorded as
                gate_fleet_enforced=false)
      affinity  two-turn conversations: warm-turn TTFT under prefix
                affinity (second turn lands where the radix-cache pages
                are hot) vs the EXPECTED VALUE of uniform-random routing
                over 2 replicas (half the warm turns deliberately land on
                the cold replica) — affinity p50 must win; the baseline
                even skips the router hop, so the comparison is
                conservative
      failover  SIGKILL one replica mid-replay: every request must
                resolve. Requests already in flight on the dead replica
                may error (reported as inflight_errors; buffered responses
                actually re-dispatch, so usually zero) but anything
                started AFTER the kill must come back 200 via the
                surviving replica. Zero dropped non-inflight requests.

    BENCH_ROUTER_OUT writes the full report JSON for CI artifacts. The
    final metric line is fleet req/s with vs_baseline = fleet/solo."""
    import http.client
    import shutil
    import socket
    import tempfile
    import threading

    import numpy as np

    from dllama_tpu.formats.spec import ArchType, ModelSpec
    from dllama_tpu.formats.tokenizer_file import (TokenizerData,
                                                   write_tokenizer)
    from dllama_tpu.formats.weights import tensor_plan, write_model
    from dllama_tpu.quants import blocks
    from dllama_tpu.serving import fleet as fleet_mod
    from dllama_tpu.serving import router as router_mod

    n_req = max(6, min(n, 32))
    k_conv = 8
    tmp = tempfile.mkdtemp(prefix="bench_router_")
    # a deeper/longer-context cousin of the BENCH_PREFIX smoke shape: the
    # affinity phase needs a ~700-token shared prefix whose prefill COST
    # dominates the router hop (+~0.5 ms), or warm-vs-cold TTFT drowns in
    # HTTP noise — yet small enough that a 2-CPU-replica fleet fits CI
    spec = ModelSpec(arch=ArchType.LLAMA, dim=256, hidden_dim=512,
                     n_layers=6, n_heads=8, n_kv_heads=4, vocab_size=512,
                     seq_len=1024, weights_float_type=blocks.Q40)
    rng = np.random.default_rng(0)
    model, tok = os.path.join(tmp, "m.m"), os.path.join(tmp, "t.t")
    write_model(model, spec,
                {e.name: 0.05 * rng.standard_normal(e.d * e.n).astype(
                    np.float32) for e in tensor_plan(spec)})
    vocab = ([b"<unk>", b"<s>", b"</s>"] + [bytes([i]) for i in range(256)]
             + [b"hi"] * (512 - 259))
    write_tokenizer(tok, TokenizerData(vocab=vocab, scores=[0.0] * 512,
                                       bos_id=1, eos_id=2))

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_PLATFORM_NAME", None)

    def _free_base(span: int) -> int:
        """A base port with `span` consecutive free ports above it."""
        for _ in range(64):
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                base = s.getsockname()[1]
            if base + span > 65500:
                continue
            try:
                for i in range(1, span):
                    with socket.socket() as t:
                        t.bind(("127.0.0.1", base + i))
                return base
            except OSError:
                continue
        raise RuntimeError("no free port span for the replica fleet")

    fl = fleet_mod.Fleet(
        model, tok, n_replicas=2, base_port=_free_base(2), host="127.0.0.1",
        # --tp 1: CI lanes force 8 virtual CPU devices via XLA_FLAGS and
        # the smoke shape's 4 kv heads can't shard 8 ways; --kv-pages
        # turns on the radix prefix cache the affinity phase measures; the
        # 40 ms window makes request+companion pairing reliable (the
        # scheduler routes singleton windows to the solo path, which
        # bypasses the paged radix cache)
        # --batch-chunk 2: content bursts every 2 decode steps, so TTFT
        # reflects PREFILL (what affinity saves) instead of a full fused
        # chunk; --prefill-chunk 256 keeps the cold ~800-token prefill a
        # handful of scheduler ticks and the warm aliased tail a single one
        replica_args=["--batch-window", "40", "--batch-max", "4",
                      "--batch-chunk", "2", "--prefill-chunk", "256",
                      "--kv-pages", "16", "--tp", "1"],
        log_dir=os.path.join(tmp, "logs"), env=env)
    rep_ports = [r.port for r in fl.replicas]
    routers = []  # (state, server) for teardown

    def _mk_router(reps):
        st = router_mod.RouterState(
            [router_mod.Replica("127.0.0.1", p) for p in reps],
            probe_interval_s=0.5, affinity_block=64)
        st.probe_once()
        srv = router_mod.create_router_server(st, "127.0.0.1", 0)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        st.start_probes()
        routers.append((st, srv))
        return srv.server_address[1]

    def _msgs(i, tag, turns=1):
        # ~700-char system prompt (byte-fallback tokenizer: ~1 token/char):
        # covers the 64-byte affinity block and ~44 replica KV pages, so a
        # warm second turn skips a prefill the stopwatch can actually see
        sys_p = (f"[{tag}-{i}] You are a terse operations assistant. "
                 + "Answer in one word. Never apologize, never elaborate, "
                   "never repeat the question back to the user. " * 6)
        msgs = [{"role": "system", "content": sys_p},
                {"role": "user", "content": f"first question for {tag}{i}"}]
        if turns > 1:
            msgs += [{"role": "assistant", "content": "ok"},
                     {"role": "user",
                      "content": f"second question for {tag}{i}"}]
        return msgs

    def _chat(port, messages, stream=False, timeout=120.0):
        """-> (status, total_ms, ttft_ms-or-None). TTFT = first CONTENT
        delta arriving at this client — the server emits its role-preamble
        chunk at admission, BEFORE prefill, so `data:` alone lands ~2 ms
        after connect regardless of prompt length."""
        body = json.dumps({"model": "bench", "messages": messages,
                           "max_tokens": 8, "temperature": 0.0,
                           "stream": stream}).encode()
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
        t0 = time.perf_counter()
        try:
            conn.request("POST", "/v1/chat/completions", body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            ttft = None
            if stream and resp.status == 200:
                buf = b""
                while b'"content"' not in buf:
                    chunk = resp.read1(65536)
                    if not chunk:
                        break
                    buf += chunk
                ttft = (time.perf_counter() - t0) * 1000.0
            resp.read()
            return resp.status, (time.perf_counter() - t0) * 1000.0, ttft
        finally:
            conn.close()

    def _replay(port, tag, count, stagger_s=0.05):
        """Staggered-arrival replay -> (req/s, n_ok)."""
        results = [None] * count

        def _one(i):
            try:
                status, ms, _ = _chat(port, _msgs(i, tag))
                results[i] = status
            except Exception:  # noqa: BLE001 — a reset mid-response counts as a drop
                results[i] = -1
        threads = []
        t0 = time.perf_counter()
        for i in range(count):
            th = threading.Thread(target=_one, args=(i,), daemon=True)
            th.start()
            threads.append(th)
            time.sleep(stagger_s)
        for th in threads:
            th.join(timeout=240.0)
        wall = time.perf_counter() - t0
        return count / wall, sum(1 for r in results if r == 200)

    gates = []
    try:
        log(f"router bench: booting 2-replica CPU fleet "
            f"(ports {rep_ports})...")
        t0 = time.perf_counter()
        fl.start()
        if not fl.wait_ready(timeout_s=300.0):
            raise RuntimeError("fleet replicas never became ready")
        log(f"fleet ready in {time.perf_counter() - t0:.1f}s")
        solo_port = _mk_router(rep_ports[:1])
        fleet_port = _mk_router(rep_ports)

        # -- throughput: solo vs fleet under the SAME staggered arrivals
        rps_solo, ok_solo = _replay(solo_port, "solo", n_req)
        log(f"solo: {rps_solo:.2f} req/s ({ok_solo}/{n_req} ok)")
        rps_fleet, ok_fleet = _replay(fleet_port, "fleet", n_req)
        log(f"fleet-of-2: {rps_fleet:.2f} req/s ({ok_fleet}/{n_req} ok)")
        gate_fleet = (os.cpu_count() or 1) >= 2
        if ok_solo != n_req or ok_fleet != n_req:
            gates.append(f"throughput replay dropped requests: "
                         f"solo {ok_solo}/{n_req}, fleet {ok_fleet}/{n_req}")
        if gate_fleet and rps_fleet <= rps_solo:
            gates.append(f"fleet {rps_fleet:.2f} req/s did not beat solo "
                         f"{rps_solo:.2f} on a {os.cpu_count()}-core host")

        # -- affinity: warm-turn TTFT, routed vs expected-uniform-random.
        # Every measured request ships with a concurrent cheap companion:
        # a singleton admission window takes the solo path, which bypasses
        # the paged radix cache entirely — only a window of >=2 rows runs
        # the continuous (paged) path where the seed's prompt pages get
        # published and the warm turn aliases them. Seed and warm run back
        # to back per conversation so LRU pressure can't evict the pages
        # in between.
        co_seq = [0]

        def _with_companion(port, msgs, stream=False, co_ports=None):
            # co_ports: where the companions go. A routed request's landing
            # replica is the router's choice, so router-phase callers pass
            # BOTH replica ports — the one the request hits gets a window
            # partner, the other digests a lone ping on the solo path
            dones = []
            for cp in (co_ports or [port]):
                co_seq[0] += 1
                done = threading.Event()
                dones.append(done)

                def _co(seq, cport, ev):
                    try:
                        _chat(cport, [{"role": "user",
                                       "content": f"companion ping {seq}"}])
                    except Exception:  # noqa: BLE001 — best-effort
                        pass
                    finally:
                        ev.set()
                threading.Thread(target=_co, args=(co_seq[0], cp, done),
                                 daemon=True).start()
            out = _chat(port, msgs, stream=stream)
            for done in dones:
                done.wait(timeout=120.0)
            return out

        # compile warm-up: the first long-prompt prefill piece and the
        # batch>=2 decode groups each compile once per replica — pay that
        # on a throwaway conversation so neither measured phase eats it
        for p in rep_ports:
            _with_companion(p, _msgs(99, "wup"))
            _with_companion(p, _msgs(99, "wup", turns=2), stream=True)

        aff_ttfts, uni_ttfts = [], []
        for i in range(k_conv):
            st, _, _ = _with_companion(fleet_port, _msgs(i, "aff"),
                                       co_ports=rep_ports)
            if st != 200:
                raise RuntimeError(f"affinity seed {i} got {st}")
            st, _, ttft = _with_companion(
                fleet_port, _msgs(i, "aff", turns=2), stream=True,
                co_ports=rep_ports)
            if st != 200 or ttft is None:
                raise RuntimeError(f"affinity warm turn {i} got {st}")
            aff_ttfts.append(ttft)
        for i in range(k_conv):
            # co_ports=rep_ports here too: BOTH phases pay the same lone
            # companion on the other replica, so the 1-CPU host's
            # timesharing penalty cancels out of the comparison
            st, _, _ = _with_companion(rep_ports[i % 2], _msgs(i, "uni"),
                                       co_ports=rep_ports)
            if st != 200:
                raise RuntimeError(f"uniform seed {i} got {st}")
            # half hit the seeded replica, half the other one: the
            # deterministic expected value of coin-flip routing
            hit = i < k_conv // 2
            port_i = rep_ports[i % 2 if hit else (i + 1) % 2]
            st, _, ttft = _with_companion(
                port_i, _msgs(i, "uni", turns=2), stream=True,
                co_ports=rep_ports)
            if st != 200 or ttft is None:
                raise RuntimeError(f"uniform warm turn {i} got {st}")
            uni_ttfts.append(ttft)
        # diagnostic, not a gate: a nonzero replica hit rate proves the
        # radix cache (not scheduling noise) produced the TTFT split
        hit_rates = []
        for p in rep_ports:
            try:
                c = http.client.HTTPConnection("127.0.0.1", p, timeout=5.0)
                c.request("GET", "/ready")
                rd = json.loads(c.getresponse().read())
                c.close()
                hit_rates.append(round(
                    float(rd.get("prefix_hit_rate", 0.0)), 4))
            except (OSError, ValueError):
                hit_rates.append(None)
        aff_p50, uni_p50 = _pct(aff_ttfts, 50), _pct(uni_ttfts, 50)
        log(f"warm-turn TTFT p50: affinity {aff_p50:.1f} ms vs "
            f"uniform-random {uni_p50:.1f} ms "
            f"(replica prefix hit rates {hit_rates})")
        if aff_p50 >= uni_p50:
            gates.append(f"affinity warm TTFT p50 {aff_p50:.1f} ms is not "
                         f"below uniform-random {uni_p50:.1f} ms")

        # -- failover: SIGKILL replica 0 mid-replay
        m = 10
        results, started = [None] * m, [0.0] * m
        kill_marker = [None]
        t0 = time.perf_counter()

        def _one(i):
            started[i] = time.perf_counter() - t0
            try:
                st, _, _ = _chat(fleet_port, _msgs(i, "kill"), timeout=90.0)
                results[i] = st
            except Exception:  # noqa: BLE001 — a reset mid-response counts as an error
                results[i] = -1

        def _kill():
            time.sleep(0.45)
            kill_marker[0] = time.perf_counter() - t0
            fl.replicas[0].proc.kill()
            log(f"killed replica 0 at t+{kill_marker[0]:.2f}s")
        threading.Thread(target=_kill, daemon=True).start()
        threads = []
        for i in range(m):
            th = threading.Thread(target=_one, args=(i,), daemon=True)
            th.start()
            threads.append(th)
            time.sleep(0.15)
        for th in threads:
            th.join(timeout=180.0)
        hung = sum(1 for r in results if r is None)
        kill_t = kill_marker[0] if kill_marker[0] is not None else 0.0
        post_kill_errors = sum(
            1 for i, r in enumerate(results)
            if r != 200 and r is not None and started[i] >= kill_t)
        inflight_errors = sum(
            1 for i, r in enumerate(results)
            if r != 200 and r is not None and started[i] < kill_t)
        n_ok = sum(1 for r in results if r == 200)
        log(f"failover: {n_ok}/{m} ok, {inflight_errors} in-flight errors, "
            f"{post_kill_errors} post-kill errors, {hung} hung")
        if hung:
            gates.append(f"{hung} requests never resolved after the kill")
        if post_kill_errors:
            gates.append(f"{post_kill_errors} requests started after the "
                         "kill failed — failover dropped non-inflight work")
    finally:
        for st, srv in routers:
            st.stop_probes()
            srv.shutdown()
            srv.server_close()
        fl.drain(timeout_s=10.0)
        shutil.rmtree(tmp, ignore_errors=True)

    report = {
        "requests": n_req, "replicas": 2, "cpu_count": os.cpu_count(),
        "solo_req_per_s": round(rps_solo, 3),
        "fleet_req_per_s": round(rps_fleet, 3),
        "fleet_vs_solo": round(rps_fleet / rps_solo, 3),
        "gate_fleet_enforced": gate_fleet,
        "affinity_warm_ttft_p50_ms": round(aff_p50, 3),
        "uniform_warm_ttft_p50_ms": round(uni_p50, 3),
        "affinity_warm_ttft_ms": [round(t, 1) for t in aff_ttfts],
        "uniform_warm_ttft_ms": [round(t, 1) for t in uni_ttfts],
        "replica_prefix_hit_rates": hit_rates,
        "failover": {"total": m, "ok": n_ok,
                     "inflight_errors": inflight_errors,
                     "post_kill_errors": post_kill_errors, "hung": hung},
        "gates_failed": gates,
    }
    out_path = os.environ.get("BENCH_ROUTER_OUT")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=2)
        log(f"report written to {out_path}")
    result = {
        "metric": "smoke_router_req_per_s",
        "value": round(rps_fleet, 3),
        "unit": "req/s",
        "vs_baseline": round(rps_fleet / rps_solo, 2),
        "baseline": "same workload through a router over ONE replica",
        "weights": "q40-router-fleet2",
        "platform": "cpu-subprocess-fleet",
        "n_devices": 2,
    }
    if gates:
        result["error"] = "; ".join(gates)
    return result


def run_disagg_bench(n: int) -> dict:
    """BENCH_DISAGG=N: disaggregated-serving replay, jax-free IN THIS
    PROCESS (replicas are `cli serve` subprocesses pinned to CPU). The
    SAME staggered streamed workload runs through two 2-replica fleets of
    the router-bench shape, booted back to back:

      colocated   two "both" replicas — every request prefills and
                  decodes on one replica, no migration (the baseline)
      disagg      one dedicated-prefill + one dedicated-decode replica —
                  every request prefills on the prefill replica and
                  migrates its KV pages to the decode replica at first
                  token

    Gates (the bench itself FAILS on any):
      * zero dropped requests in either leg
      * the disagg leg actually migrated EVERY request (the router's
        outcome="ok" counter delta equals the request count — a leg that
        silently fell back to normal routing would "win" the latency
        comparison by not doing the work)
      * migrated TTFB p50 <= colocated TTFB p50 x DISAGG_SLACK + 250 ms
        (slack 1.5 by default: the handoff adds one HTTP hop plus a page
        encode/decode, which must stay a bounded tax on first-token
        latency, not a multiple; the additive grace absorbs CPU-runner
        scheduling noise on what is a sub-second quantity)

    BENCH_DISAGG_OUT writes the full report JSON for CI artifacts. The
    final metric line is migrated TTFB p50 with vs_baseline =
    colocated/migrated (below 1.0 = migration costs latency)."""
    import http.client
    import shutil
    import socket
    import tempfile
    import threading

    import numpy as np

    from dllama_tpu.formats.spec import ArchType, ModelSpec
    from dllama_tpu.formats.tokenizer_file import (TokenizerData,
                                                   write_tokenizer)
    from dllama_tpu.formats.weights import tensor_plan, write_model
    from dllama_tpu.quants import blocks
    from dllama_tpu.serving import fleet as fleet_mod
    from dllama_tpu.serving import router as router_mod

    n_req = max(4, min(n, 24))
    slack = float(os.environ.get("DISAGG_SLACK", "1.5"))
    tmp = tempfile.mkdtemp(prefix="bench_disagg_")
    # the router-bench shape: a ~700-token prompt whose prefill cost
    # dominates the HTTP hop, so TTFB measures work, not socket latency
    spec = ModelSpec(arch=ArchType.LLAMA, dim=256, hidden_dim=512,
                     n_layers=6, n_heads=8, n_kv_heads=4, vocab_size=512,
                     seq_len=1024, weights_float_type=blocks.Q40)
    rng = np.random.default_rng(0)
    model, tok = os.path.join(tmp, "m.m"), os.path.join(tmp, "t.t")
    write_model(model, spec,
                {e.name: 0.05 * rng.standard_normal(e.d * e.n).astype(
                    np.float32) for e in tensor_plan(spec)})
    vocab = ([b"<unk>", b"<s>", b"</s>"] + [bytes([i]) for i in range(256)]
             + [b"hi"] * (512 - 259))
    write_tokenizer(tok, TokenizerData(vocab=vocab, scores=[0.0] * 512,
                                       bos_id=1, eos_id=2))

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_PLATFORM_NAME", None)
    env.pop("DLLAMA_FAULTS", None)

    def _free_base(span: int) -> int:
        for _ in range(64):
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                base = s.getsockname()[1]
            if base + span > 65500:
                continue
            try:
                for i in range(1, span):
                    with socket.socket() as t:
                        t.bind(("127.0.0.1", base + i))
                return base
            except OSError:
                continue
        raise RuntimeError("no free port span for the replica fleet")

    def _msgs(i, tag):
        sys_p = (f"[{tag}-{i}] You are a terse operations assistant. "
                 + "Answer in one word. Never apologize, never elaborate, "
                   "never repeat the question back to the user. " * 6)
        return [{"role": "system", "content": sys_p},
                {"role": "user", "content": f"question for {tag}{i}"}]

    def _chat_ttfb(port, messages, timeout=180.0):
        """-> (status, ttfb_ms-or-None): streamed request, clocking the
        first CONTENT delta (the role preamble lands pre-prefill)."""
        body = json.dumps({"model": "bench", "messages": messages,
                           "max_tokens": 8, "temperature": 0.0,
                           "stream": True}).encode()
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
        t0 = time.perf_counter()
        try:
            conn.request("POST", "/v1/chat/completions", body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            ttfb = None
            if resp.status == 200:
                buf = b""
                while b'"content"' not in buf:
                    chunk = resp.read1(65536)
                    if not chunk:
                        break
                    buf += chunk
                else:
                    ttfb = (time.perf_counter() - t0) * 1000.0
            resp.read()
            return resp.status, ttfb
        finally:
            conn.close()

    def _leg(tag, roles):
        """Boot a 2-replica fleet with the given roles behind a fresh
        router, replay the workload, tear it all down. Returns
        (ttfbs, n_ok, migrations_by_outcome)."""
        fl = fleet_mod.Fleet(
            model, tok, n_replicas=2, base_port=_free_base(2),
            host="127.0.0.1",
            replica_args=["--batch-window", "40", "--batch-max", "4",
                          "--batch-chunk", "2", "--prefill-chunk", "256",
                          "--kv-pages", "16", "--tp", "1"],
            log_dir=os.path.join(tmp, f"logs-{tag}"), env=env, roles=roles)
        st = None
        srv = None
        try:
            log(f"disagg bench [{tag}]: booting {'+'.join(roles)} fleet "
                f"(ports {[r.port for r in fl.replicas]})...")
            t0 = time.perf_counter()
            fl.start()
            if not fl.wait_ready(timeout_s=300.0):
                raise RuntimeError(f"[{tag}] replicas never became ready")
            log(f"[{tag}] fleet ready in {time.perf_counter() - t0:.1f}s")
            st = router_mod.RouterState(
                [router_mod.Replica("127.0.0.1", r.port)
                 for r in fl.replicas], probe_interval_s=0.5)
            st.probe_once()
            srv = router_mod.create_router_server(st, "127.0.0.1", 0)
            threading.Thread(target=srv.serve_forever, daemon=True).start()
            st.start_probes()
            port = srv.server_address[1]

            def _migrations():
                fam = st.metrics.snapshot().get(
                    "dllama_kv_transfer_migrations_total", {})
                return {v["labels"]["outcome"]: v["value"]
                        for v in fam.get("values", [])}

            # warm-up through the front door: compiles each replica's
            # prefill/decode programs — and, in the disagg leg, the whole
            # export/import path — outside the stopwatch. Two requests so
            # BOTH colocated replicas compile (least-load alternates).
            for w in range(2):
                stt, _ = _chat_ttfb(port, _msgs(w, f"wup-{tag}"))
                if stt != 200:
                    raise RuntimeError(f"[{tag}] warm-up {w} got {stt}")
            base_ok = _migrations().get("ok", 0)

            ttfbs, statuses = [None] * n_req, [None] * n_req

            def _one(i):
                try:
                    statuses[i], ttfbs[i] = _chat_ttfb(
                        port, _msgs(i, tag))
                except Exception:  # noqa: BLE001 — a reset counts as a drop
                    statuses[i] = -1
            threads = []
            for i in range(n_req):
                th = threading.Thread(target=_one, args=(i,), daemon=True)
                th.start()
                threads.append(th)
                time.sleep(0.2)
            for th in threads:
                th.join(timeout=240.0)
            n_ok = sum(1 for s_ in statuses if s_ == 200)
            mig = _migrations()
            mig["ok_delta"] = mig.get("ok", 0) - base_ok
            return [t for t in ttfbs if t is not None], n_ok, mig
        finally:
            if st is not None:
                st.stop_probes()
            if srv is not None:
                srv.shutdown()
                srv.server_close()
            fl.drain(timeout_s=10.0)

    gates = []
    try:
        colo_ttfbs, colo_ok, _ = _leg("colo", ["both", "both"])
        colo_p50 = _pct(colo_ttfbs, 50)
        log(f"colocated: {colo_ok}/{n_req} ok, TTFB p50 {colo_p50:.1f} ms")
        mig_ttfbs, mig_ok, mig = _leg("disagg", ["prefill", "decode"])
        mig_p50 = _pct(mig_ttfbs, 50)
        log(f"disaggregated: {mig_ok}/{n_req} ok, TTFB p50 "
            f"{mig_p50:.1f} ms, migrations {mig}")
        if colo_ok != n_req or mig_ok != n_req:
            gates.append(f"dropped requests: colocated {colo_ok}/{n_req}, "
                         f"disaggregated {mig_ok}/{n_req}")
        if mig["ok_delta"] < n_req:
            gates.append(
                f"only {mig['ok_delta']:.0f}/{n_req} requests migrated "
                f"(outcomes {mig}) — the latency comparison would credit "
                "normal routing, not the handoff")
        bound = colo_p50 * slack + 250.0
        if mig_p50 > bound:
            gates.append(f"migrated TTFB p50 {mig_p50:.1f} ms exceeds "
                         f"colocated {colo_p50:.1f} ms x {slack} + 250 ms "
                         f"= {bound:.1f} ms")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    report = {
        "requests": n_req, "slack": slack, "cpu_count": os.cpu_count(),
        # CPU smoke: scheduling + handoff correctness only. The latency
        # case for disaggregation (prefill interference on decode TPOT,
        # inter-chip page transfer) is a hardware property — not measured
        # (ROADMAP S1: it needs a cell on the chip).
        "tpu_deltas_owed": True,
        "colocated_ttfb_p50_ms": round(colo_p50, 3),
        "migrated_ttfb_p50_ms": round(mig_p50, 3),
        "colocated_ttfb_ms": [round(t, 1) for t in colo_ttfbs],
        "migrated_ttfb_ms": [round(t, 1) for t in mig_ttfbs],
        "migrations": {k: round(v, 0) for k, v in mig.items()},
        "gates_failed": gates,
    }
    out_path = os.environ.get("BENCH_DISAGG_OUT")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=2)
        log(f"report written to {out_path}")
    result = {
        "metric": "smoke_disagg_ttfb_ms",
        "value": round(mig_p50, 3),
        "unit": "ms",
        "vs_baseline": round(colo_p50 / mig_p50, 2) if mig_p50 else None,
        "baseline": "same streamed workload on a colocated 2-replica fleet "
                    "(no migration)",
        "weights": "q40-disagg-fleet2",
        "platform": "cpu-subprocess-fleet",
        "n_devices": 2,
    }
    if gates:
        result["error"] = "; ".join(gates)
    return result


def run_failover_bench(n: int) -> dict:
    """BENCH_FAILOVER=N: checkpointing-overhead replay, jax-free IN THIS
    PROCESS (replicas are `cli serve` subprocesses pinned to CPU). ONE
    2-replica "both" fleet boots once; the SAME sequential decode-heavy
    workload then runs through two routers back to back:

      base   router with --ckpt-interval 0 — no checkpoint frames are
             requested, the stream is the plain batched decode path
      ckpt   router with the default --ckpt-interval — every stream
             opts in, replicas serialize + ship a KV checkpoint every
             K emitted tokens

    Both legs measure per-request TPOT (first content delta -> [DONE],
    divided by the tokens decoded after the first burst), so the delta
    is exactly the checkpoint tax: export_row + encode + one extra SSE
    frame per K tokens, amortized.

    Gates (the bench itself FAILS on any):
      * zero dropped requests in either leg
      * the ckpt leg actually checkpointed — the replicas'
        dllama_ckpt_writes_total{outcome="ok"} sum grew by at least one
        per request (a leg that silently skipped checkpointing would
        "win" the overhead comparison by not doing the work)
      * ckpt TPOT p50 <= base TPOT p50 x 1.01 + FAILOVER_TPOT_SLACK_MS
        (default 20 ms: the ISSUE's <1% overhead budget, plus an
        additive grace because a tiny-model CPU TPOT is a handful of
        milliseconds and scheduler noise would otherwise dwarf the
        quantity being gated)

    BENCH_FAILOVER_OUT writes the full report JSON for CI artifacts.
    The final metric line is ckpt-leg TPOT p50 with vs_baseline =
    base/ckpt (below 1.0 = checkpointing costs decode throughput)."""
    import http.client
    import shutil
    import socket
    import tempfile
    import threading

    import numpy as np

    from dllama_tpu.formats.spec import ArchType, ModelSpec
    from dllama_tpu.formats.tokenizer_file import (TokenizerData,
                                                   write_tokenizer)
    from dllama_tpu.formats.weights import tensor_plan, write_model
    from dllama_tpu.quants import blocks
    from dllama_tpu.serving import fleet as fleet_mod
    from dllama_tpu.serving import router as router_mod

    n_req = max(4, min(n, 16))
    max_tok = 48
    ckpt_k = 32  # the default --ckpt-interval: the cadence the gate is
    #              specified against
    slack_ms = float(os.environ.get("FAILOVER_TPOT_SLACK_MS", "20"))
    tmp = tempfile.mkdtemp(prefix="bench_failover_")
    spec = ModelSpec(arch=ArchType.LLAMA, dim=256, hidden_dim=512,
                     n_layers=6, n_heads=8, n_kv_heads=4, vocab_size=512,
                     seq_len=1024, weights_float_type=blocks.Q40)
    rng = np.random.default_rng(0)
    model, tok = os.path.join(tmp, "m.m"), os.path.join(tmp, "t.t")
    write_model(model, spec,
                {e.name: 0.05 * rng.standard_normal(e.d * e.n).astype(
                    np.float32) for e in tensor_plan(spec)})
    vocab = ([b"<unk>", b"<s>", b"</s>"] + [bytes([i]) for i in range(256)]
             + [b"hi"] * (512 - 259))
    write_tokenizer(tok, TokenizerData(vocab=vocab, scores=[0.0] * 512,
                                       bos_id=1, eos_id=2))

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_PLATFORM_NAME", None)
    env.pop("DLLAMA_FAULTS", None)

    def _free_base(span: int) -> int:
        for _ in range(64):
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                base = s.getsockname()[1]
            if base + span > 65500:
                continue
            try:
                for i in range(1, span):
                    with socket.socket() as t:
                        t.bind(("127.0.0.1", base + i))
                return base
            except OSError:
                continue
        raise RuntimeError("no free port span for the replica fleet")

    def _chat_tpot(port, i, tag, timeout=180.0):
        """-> (status, tpot_ms-or-None): streamed request, clocking first
        content delta -> [DONE] over the tokens decoded after the first
        burst (batch-chunk 2, so max_tok - 2 of them)."""
        body = json.dumps({
            "model": "bench",
            "messages": [{"role": "user", "content": f"[{tag}-{i}] go"}],
            "max_tokens": max_tok, "temperature": 0.0,
            "stream": True}).encode()
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
        try:
            conn.request("POST", "/v1/chat/completions", body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status != 200:
                resp.read()
                return resp.status, None
            buf, t_first, t_done = b"", None, None
            while True:
                chunk = resp.read1(65536)
                if not chunk:
                    break
                buf += chunk
                if t_first is None and b'"content"' in buf:
                    t_first = time.perf_counter()
                if b"data: [DONE]" in buf:
                    t_done = time.perf_counter()
                    break
            resp.read()
            if t_first is None or t_done is None:
                return -1, None  # torn stream = a drop
            return 200, (t_done - t_first) * 1000.0 / max(1, max_tok - 2)
        finally:
            conn.close()

    def _ckpt_writes(ports):
        total = 0.0
        for p in ports:
            conn = http.client.HTTPConnection("127.0.0.1", p, timeout=10.0)
            try:
                conn.request("GET", "/metrics")
                text = conn.getresponse().read().decode()
            finally:
                conn.close()
            for line in text.splitlines():
                if (line.startswith("dllama_ckpt_writes_total")
                        and 'outcome="ok"' in line):
                    total += float(line.rsplit(" ", 1)[1])
        return total

    gates = []
    fl = fleet_mod.Fleet(
        model, tok, n_replicas=2, base_port=_free_base(2), host="127.0.0.1",
        replica_args=["--batch-window", "40", "--batch-max", "4",
                      "--batch-chunk", "2", "--prefill-chunk", "256",
                      "--kv-pages", "16", "--tp", "1",
                      "--ckpt-interval", str(ckpt_k)],
        log_dir=os.path.join(tmp, "logs"), env=env, roles=["both", "both"])
    legs = {}
    try:
        log("failover bench: booting both+both fleet "
            f"(ports {[r.port for r in fl.replicas]})...")
        t0 = time.perf_counter()
        fl.start()
        if not fl.wait_ready(timeout_s=300.0):
            raise RuntimeError("replicas never became ready")
        log(f"fleet ready in {time.perf_counter() - t0:.1f}s")
        ports = [r.port for r in fl.replicas]

        for tag, interval in (("base", 0), ("ckpt", ckpt_k)):
            st = router_mod.RouterState(
                [router_mod.Replica("127.0.0.1", p) for p in ports],
                probe_interval_s=0.5, ckpt_interval=interval)
            st.probe_once()
            srv = router_mod.create_router_server(st, "127.0.0.1", 0)
            threading.Thread(target=srv.serve_forever, daemon=True).start()
            st.start_probes()
            port = srv.server_address[1]
            try:
                # warm-up: compile both replicas' programs (and, in the
                # ckpt leg, the export path) outside the stopwatch
                for w in range(2):
                    stt, _ = _chat_tpot(port, w, f"wup-{tag}")
                    if stt != 200:
                        raise RuntimeError(f"[{tag}] warm-up {w} got {stt}")
                writes0 = _ckpt_writes(ports)
                tpots, n_ok = [], 0
                for i in range(n_req):  # sequential: TPOT, not throughput
                    stt, tpot = _chat_tpot(port, i, tag)
                    if stt == 200 and tpot is not None:
                        n_ok += 1
                        tpots.append(tpot)
                writes = _ckpt_writes(ports) - writes0
                legs[tag] = {"tpots": tpots, "ok": n_ok, "writes": writes}
                log(f"[{tag}] {n_ok}/{n_req} ok, TPOT p50 "
                    f"{_pct(tpots, 50):.2f} ms/token, "
                    f"ckpt writes {writes:.0f}")
            finally:
                st.stop_probes()
                srv.shutdown()
                srv.server_close()

        base_p50 = _pct(legs["base"]["tpots"], 50)
        ckpt_p50 = _pct(legs["ckpt"]["tpots"], 50)
        if legs["base"]["ok"] != n_req or legs["ckpt"]["ok"] != n_req:
            gates.append(f"dropped requests: base {legs['base']['ok']}"
                         f"/{n_req}, ckpt {legs['ckpt']['ok']}/{n_req}")
        if legs["ckpt"]["writes"] < n_req:
            gates.append(
                f"only {legs['ckpt']['writes']:.0f} checkpoints written "
                f"for {n_req} requests — the overhead comparison would "
                "credit a leg that skipped the work")
        bound = base_p50 * 1.01 + slack_ms
        if ckpt_p50 > bound:
            gates.append(f"ckpt TPOT p50 {ckpt_p50:.2f} ms exceeds base "
                         f"{base_p50:.2f} ms x 1.01 + {slack_ms:.0f} ms "
                         f"= {bound:.2f} ms")
    finally:
        fl.drain(timeout_s=10.0)
        shutil.rmtree(tmp, ignore_errors=True)

    report = {
        "requests": n_req, "max_tokens": max_tok,
        "ckpt_interval": ckpt_k, "tpot_slack_ms": slack_ms,
        "cpu_count": os.cpu_count(),
        # CPU smoke: checkpoint-cadence correctness + a noise-bounded
        # overhead gate. The real <1% TPOT budget is a hardware claim
        # (export_row DMA + codec cost vs TPU decode step) — not
        # measured (ROADMAP S1: it needs a cell on the chip).
        "tpu_deltas_owed": True,
        "base_tpot_p50_ms": round(base_p50, 3),
        "ckpt_tpot_p50_ms": round(ckpt_p50, 3),
        "base_tpot_ms": [round(t, 2) for t in legs["base"]["tpots"]],
        "ckpt_tpot_ms": [round(t, 2) for t in legs["ckpt"]["tpots"]],
        "ckpt_writes": round(legs["ckpt"]["writes"], 0),
        "gates_failed": gates,
    }
    out_path = os.environ.get("BENCH_FAILOVER_OUT")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=2)
        log(f"report written to {out_path}")
    result = {
        "metric": "smoke_failover_tpot_ms",
        "value": round(ckpt_p50, 3),
        "unit": "ms/token",
        "vs_baseline": round(base_p50 / ckpt_p50, 2) if ckpt_p50 else None,
        "baseline": "same sequential streamed workload through a router "
                    "with checkpointing disabled (--ckpt-interval 0)",
        "weights": "q40-failover-fleet2",
        "platform": "cpu-subprocess-fleet",
        "n_devices": 2,
    }
    if gates:
        result["error"] = "; ".join(gates)
    return result


def run_workloads_bench(n: int) -> dict:
    """BENCH_WORKLOADS=N: the SLO-class chaos battery, jax-free IN THIS
    PROCESS (replicas are `cli serve` subprocesses pinned to CPU). One
    2-replica fleet boots with per-class lanes on
    (``--slo-classes interactive:...;batch:...``) and the deterministic
    scenarios from scripts/workloads.py replay against it:

      pin      preemption bit-identity, direct against one replica: a
               batch-class stream sized to saturate the KV page budget
               runs solo (the reference), then again with an interactive
               arrival forcing a chunk-boundary preemption — the
               preempted+resumed output must be byte-identical, with
               dllama_preemptions_total{outcome="resumed"} >= 1 and
               zero outcome="error"
      bursty   interactive bursts through the router while batch jobs
               saturate the batch lane: zero errors in either class and
               interactive TTFT p99 <= WORKLOADS_TTFT_P99_MS (default
               30000 — "bounded", with CPU CI slack, not a latency claim)
      mixed    long-context + multi-turn prefix reuse + abusive mid-SSE
               disconnects: zero errors outside the deliberate drops,
               and the fleet still answers afterwards
      kill     a replica SIGKILLed mid-burst with router checkpointing
               on: every stream still ends 200/[DONE]/no error event,
               and the router counted >= 1 ok resume

    Plus a federation gate: after the bursty mix, /metrics/fleet must
    carry the per-class gauge series (lane pressure is an operator
    surface, not replica-local state). BENCH_WORKLOADS_OUT writes the
    full report JSON for CI artifacts. The final metric line is the
    bursty-mix interactive TTFT p99; vs_baseline divides the unloaded
    interactive TTFT by it (below 1.0 = saturation costs latency)."""
    import http.client
    import importlib.util
    import shutil
    import signal
    import socket
    import tempfile
    import threading

    import numpy as np

    from dllama_tpu.formats.spec import ArchType, ModelSpec
    from dllama_tpu.formats.tokenizer_file import (TokenizerData,
                                                   write_tokenizer)
    from dllama_tpu.formats.weights import tensor_plan, write_model
    from dllama_tpu.quants import blocks
    from dllama_tpu.serving import fleet as fleet_mod
    from dllama_tpu.serving import router as router_mod

    repo = os.path.dirname(os.path.abspath(__file__))
    spec_wl = importlib.util.spec_from_file_location(
        "dllama_workloads", os.path.join(repo, "scripts", "workloads.py"))
    wl = importlib.util.module_from_spec(spec_wl)
    spec_wl.loader.exec_module(wl)

    bursts = max(2, min(n, 6))
    ttft_bound_ms = float(os.environ.get("WORKLOADS_TTFT_P99_MS", "30000"))
    # batch request budget deliberately past any row's room: admission
    # clamps steps to seq_len - plen, so ONE such row reserves exactly
    # half the 2-slot paged budget and TWO saturate it — the interactive
    # arrival then must preempt, whatever the chat template's overhead
    batch_steps = 450
    tmp = tempfile.mkdtemp(prefix="bench_workloads_")
    spec = ModelSpec(arch=ArchType.LLAMA, dim=128, hidden_dim=256,
                     n_layers=2, n_heads=4, n_kv_heads=2, vocab_size=512,
                     seq_len=512, weights_float_type=blocks.Q40)
    rng = np.random.default_rng(0)
    model, tok = os.path.join(tmp, "m.m"), os.path.join(tmp, "t.t")
    write_model(model, spec,
                {e.name: 0.05 * rng.standard_normal(e.d * e.n).astype(
                    np.float32) for e in tensor_plan(spec)})
    vocab = ([b"<unk>", b"<s>", b"</s>"] + [bytes([i]) for i in range(256)]
             + [b"hi"] * (512 - 259))
    write_tokenizer(tok, TokenizerData(vocab=vocab, scores=[0.0] * 512,
                                       bos_id=1, eos_id=2))

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_PLATFORM_NAME", None)
    env.pop("DLLAMA_FAULTS", None)

    def _free_base(span: int) -> int:
        for _ in range(64):
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                base = s.getsockname()[1]
            if base + span > 65500:
                continue
            try:
                for i in range(1, span):
                    with socket.socket() as t:
                        t.bind(("127.0.0.1", base + i))
                return base
            except OSError:
                continue
        raise RuntimeError("no free port span for the replica fleet")

    def _scrape(port, family, match=(), path="/metrics"):
        """Sum of the family's samples whose label text contains every
        ``match`` fragment."""
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10.0)
        try:
            conn.request("GET", path)
            text = conn.getresponse().read().decode()
        finally:
            conn.close()
        total = 0.0
        for line in text.splitlines():
            if line.startswith(family) and all(m in line for m in match):
                total += float(line.rsplit(" ", 1)[1])
        return total

    gates = []
    phases: dict = {}
    fl = fleet_mod.Fleet(
        model, tok, n_replicas=2, base_port=_free_base(2), host="127.0.0.1",
        # --batch-max 2 sizes the paged budget at 2*seq_len tokens (paged
        # rows are bounded by pages, not slots); --batch-chunk 2 makes
        # chunk-boundary preemption latency two tokens
        replica_args=["--batch-window", "5", "--batch-max", "2",
                      "--batch-chunk", "2", "--prefill-chunk", "64",
                      "--kv-pages", "16", "--tp", "1",
                      "--ckpt-interval", "2",
                      "--slo-classes",
                      "interactive:depth=32,deadline=240;batch:depth=8"],
        log_dir=os.path.join(tmp, "logs"), env=env, roles=["both", "both"])
    rstate = rsrv = None
    try:
        log("workloads bench: booting both+both fleet "
            f"(ports {[r.port for r in fl.replicas]})...")
        t0 = time.perf_counter()
        fl.start()
        if not fl.wait_ready(timeout_s=300.0):
            raise RuntimeError("replicas never became ready")
        log(f"fleet ready in {time.perf_counter() - t0:.1f}s")
        ports = [r.port for r in fl.replicas]

        # warm-up: compile every replica's programs outside the clocks;
        # the LAST warm request per replica doubles as the unloaded-TTFT
        # baseline sample
        base_ttfts = []
        for p in ports:
            for w in range(2):
                r = wl.do_request("127.0.0.1", p, wl.Req(
                    0.0, f"warm-{p}-{w}", "interactive",
                    [{"role": "user", "content": f"warm {w} up"}], 8),
                    timeout=300.0)
                if r["status"] != 200 or r["error"]:
                    raise RuntimeError(
                        f"warm-up on :{p} failed: {r['status']} "
                        f"{r['error']!r}")
                if w == 1 and r["ttft_ms"] is not None:
                    base_ttfts.append(r["ttft_ms"])
        baseline_ttft = _pct(base_ttfts, 50)

        # ---- pin: preemption bit-identity (replica 0, direct) --------
        p0 = ports[0]
        pin_req = wl.Req(0.0, "pin", "batch",
                         [{"role": "user",
                           "content": "pin me alpha bravo cedar delta"}],
                         batch_steps)
        fill_req = wl.Req(0.0, "fill", "batch",
                          [{"role": "user",
                            "content": "fill me echo fjord gamma haze"}],
                          batch_steps)
        solo = wl.do_request("127.0.0.1", p0, pin_req, timeout=600.0)
        if solo["status"] != 200 or solo["error"] or not solo["text"]:
            gates.append(f"pin solo run failed: {solo['status']} "
                         f"{solo['error']!r}")
            raise RuntimeError(gates[-1])
        res0 = _scrape(p0, "dllama_preemptions_total",
                       ('outcome="resumed"',))
        err0 = _scrape(p0, "dllama_preemptions_total",
                       ('outcome="error"',))
        slots = [None, None]
        # filler first, pin second: the preemptor exports the YOUNGEST
        # batch row, so the pin is the one parked and resumed
        t_fill = threading.Thread(target=lambda: slots.__setitem__(
            0, wl.do_request("127.0.0.1", p0, fill_req, timeout=600.0)),
            daemon=True)
        t_fill.start()
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if _scrape(p0, "dllama_class_resident_rows",
                       ('slo_class="batch"',)) >= 1:
                break
            time.sleep(0.01)
        t_pin = threading.Thread(target=lambda: slots.__setitem__(
            1, wl.do_request("127.0.0.1", p0, pin_req, timeout=600.0)),
            daemon=True)
        t_pin.start()
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if _scrape(p0, "dllama_class_resident_rows",
                       ('slo_class="batch"',)) >= 2:
                break
            time.sleep(0.01)
        inter = wl.do_request("127.0.0.1", p0, wl.Req(
            0.0, "pin-int", "interactive",
            [{"role": "user", "content": "quick question"}], 8),
            timeout=600.0)
        t_fill.join(timeout=600.0)
        t_pin.join(timeout=600.0)
        resumed = _scrape(p0, "dllama_preemptions_total",
                          ('outcome="resumed"',)) - res0
        perrs = _scrape(p0, "dllama_preemptions_total",
                        ('outcome="error"',)) - err0
        phases["pin"] = {"solo_len": len(solo["text"]),
                         "resumed": resumed, "preempt_errors": perrs,
                         "interactive_status": inter["status"]}
        if inter["status"] != 200 or inter["error"]:
            gates.append(f"interactive arrival failed during saturation: "
                         f"{inter['status']} {inter['error']!r}")
        if resumed < 1:
            gates.append("no preemption resumed during the pin phase — "
                         "the bit-identity comparison never exercised "
                         "the park/resume path")
        if perrs:
            gates.append(f"{perrs:.0f} preemption export errors")
        pinned = slots[1]
        if pinned is None or pinned["status"] != 200 or pinned["error"]:
            gates.append(f"pinned batch stream failed: {pinned!r}"[:300])
        elif pinned["text"] != solo["text"]:
            gates.append(
                "preempted batch output != unpreempted reference "
                f"(lens {len(pinned['text'])} vs {len(solo['text'])})")
        log(f"[pin] resumed {resumed:.0f}, errors {perrs:.0f}, "
            f"bit-identical={pinned is not None and pinned['text'] == solo['text']}")

        # ---- router up for the fleet phases --------------------------
        rstate = router_mod.RouterState(
            [router_mod.Replica("127.0.0.1", p) for p in ports],
            probe_interval_s=0.3, ckpt_interval=2)
        rstate.probe_once()
        rsrv = router_mod.create_router_server(rstate, "127.0.0.1", 0)
        threading.Thread(target=rsrv.serve_forever, daemon=True).start()
        rstate.start_probes()
        r_port = rsrv.server_address[1]

        # ---- bursty: interactive TTFT under a saturated batch lane ---
        sched = wl.bursty_mix(seed=11, bursts=bursts, burst_size=4,
                              gap_s=1.5, batch_jobs=2, batch_tokens=160,
                              interactive_tokens=12)
        results = wl.run_schedule("127.0.0.1", r_port, sched,
                                  timeout=600.0)
        summ = wl.summarize(results)
        phases["bursty"] = summ
        for cls in ("interactive", "batch"):
            for msg in summ.get(cls, {}).get("errors", []):
                gates.append(f"bursty {cls}: {msg}")
        ttft_p99 = (summ.get("interactive") or {}).get("ttft_p99_ms")
        if ttft_p99 is None:
            gates.append("bursty mix produced no interactive TTFT sample")
        elif ttft_p99 > ttft_bound_ms:
            gates.append(f"interactive TTFT p99 {ttft_p99:.0f} ms exceeds "
                         f"the {ttft_bound_ms:.0f} ms class bound under "
                         "the saturated batch lane")
        log(f"[bursty] {json.dumps(summ, sort_keys=True)}")
        # federation: the per-class gauges must be visible fleet-wide
        conn = http.client.HTTPConnection("127.0.0.1", r_port,
                                          timeout=10.0)
        try:
            conn.request("GET", "/metrics/fleet")
            fed = conn.getresponse().read().decode()
        finally:
            conn.close()
        for fam in ("dllama_class_queue_depth", "dllama_class_ttft_ms"):
            if fam not in fed:
                gates.append(f"{fam} missing from /metrics/fleet — "
                             "lane pressure is not federated")

        # ---- mixed: long-context + prefix reuse + mid-SSE drops ------
        mixed = (wl.long_context(seed=5, n=3, target_chars=280,
                                 max_tokens=16)
                 + wl.multi_turn(seed=3, conversations=2, turns=3,
                                 max_tokens=12)
                 + wl.abusive_disconnects(seed=9, n=3, max_tokens=64))
        msumm = wl.summarize(
            wl.run_schedule("127.0.0.1", r_port, mixed, timeout=600.0))
        phases["mixed"] = msumm
        for cls, c in msumm.items():
            for msg in c["errors"]:
                gates.append(f"mixed {cls}: {msg}")
        after = wl.do_request("127.0.0.1", r_port, wl.Req(
            0.0, "post-abuse", "interactive",
            [{"role": "user", "content": "still there?"}], 4),
            timeout=300.0)
        if after["status"] != 200 or after["error"]:
            gates.append("fleet unhealthy after the mid-SSE disconnects: "
                         f"{after['status']} {after['error']!r}")
        log(f"[mixed] {json.dumps(msumm, sort_keys=True)}")

        # ---- kill: SIGKILL a replica mid-burst -----------------------
        ok0 = rstate._m_resumes.value(outcome="ok")
        kres = [None] * 4
        killed = {}

        def _streamer(i, rq):
            kres[i] = wl.do_request("127.0.0.1", r_port, rq,
                                    timeout=600.0)

        # streams long enough that the kill lands mid-decode: past the
        # first router checkpoint (interval 2), well before [DONE]
        burst = wl.kill_burst(seed=13, n=4, max_tokens=160)
        th = [threading.Thread(target=_streamer, args=(i, rq),
                               daemon=True)
              for i, rq in enumerate(burst[:2])]
        for t in th:
            t.start()
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            busy = [r for r in rstate.replicas
                    if r.snapshot().get("inflight", 0) > 0]
            if len(busy) >= 1 and sum(
                    r.snapshot().get("inflight", 0)
                    for r in rstate.replicas) >= 2:
                break
            time.sleep(0.01)
        time.sleep(0.3)  # let the first checkpoints land in the store
        for i, r in enumerate(rstate.replicas):
            if r.snapshot().get("inflight", 0) > 0:
                os.kill(fl.replicas[i].proc.pid, signal.SIGKILL)
                killed["replica"] = r.name
                log(f"[kill] SIGKILLed {r.name} mid-burst")
                break
        # the back half of the burst arrives AFTER the kill: routed (or
        # retried) onto the survivor without the client noticing
        th += [threading.Thread(target=_streamer, args=(2 + i, rq),
                                daemon=True)
               for i, rq in enumerate(burst[2:])]
        for t in th[2:]:
            t.start()
        for t in th:
            t.join(timeout=600.0)
        resumes = rstate._m_resumes.value(outcome="ok") - ok0
        phases["kill"] = {"killed": killed.get("replica"),
                          "resumes_ok": resumes,
                          "results": [
                              {"name": r["name"], "status": r["status"],
                               "done": r["done"], "error": r["error"]}
                              if r else None for r in kres]}
        if not killed:
            gates.append("no in-flight replica found to SIGKILL")
        for r in kres:
            if r is None or r["status"] != 200 or r["error"] \
                    or not r["done"]:
                gates.append(
                    "client-visible error across the kill: "
                    + (f"{r['name']}: {r['status']} {r['error']!r} "
                       f"done={r['done']}" if r else "stream never "
                       "resolved"))
        if killed and resumes < 1:
            gates.append("replica killed but the router counted no ok "
                         f"resume (got {resumes:.0f})")
        log(f"[kill] resumes ok {resumes:.0f}, "
            f"results {[r['status'] if r else None for r in kres]}")
    finally:
        if rstate is not None:
            rstate.stop_probes()
        if rsrv is not None:
            rsrv.shutdown()
            rsrv.server_close()
        fl.drain(timeout_s=10.0)
        shutil.rmtree(tmp, ignore_errors=True)

    report = {
        "bursts": bursts, "batch_steps": batch_steps,
        "ttft_bound_ms": ttft_bound_ms,
        "cpu_count": os.cpu_count(),
        # CPU smoke: class-lane correctness, preemption bit-identity and
        # chaos survival. The TTFT bound is a CI noise envelope — the
        # real interactive SLO is a hardware claim (not measured;
        # ROADMAP S1: it needs a cell on the chip).
        "tpu_deltas_owed": True,
        "baseline_ttft_ms": (round(baseline_ttft, 3)
                             if baseline_ttft is not None else None),
        "interactive_ttft_p99_ms": (round(ttft_p99, 3)
                                    if ttft_p99 is not None else None),
        "phases": phases,
        "gates_failed": gates,
    }
    out_path = os.environ.get("BENCH_WORKLOADS_OUT")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=2, default=str)
        log(f"report written to {out_path}")
    result = {
        "metric": "smoke_workloads_ttft_ms",
        "value": round(ttft_p99, 3) if ttft_p99 is not None else None,
        "unit": "ms",
        "vs_baseline": (round(baseline_ttft / ttft_p99, 2)
                        if ttft_p99 and baseline_ttft else None),
        "baseline": "unloaded interactive TTFT p50 on the same fleet "
                    "(warm replicas, empty lanes)",
        "weights": "q40-workloads-fleet2",
        "platform": "cpu-subprocess-fleet",
        "n_devices": 2,
    }
    if gates:
        result["error"] = "; ".join(gates)
    return result


def run_elastic_bench(n: int) -> dict:
    """BENCH_ELASTIC=N: the closed-loop elastic fleet vs a static fleet
    on the same bursty-diurnal replay, jax-free IN THIS PROCESS (replicas
    are `cli serve` subprocesses pinned to CPU).

    Leg 1 (elastic): a 1-replica fleet with the autoscale supervisor on
    (min 1 / max 2, aggressive thresholds sized to the burst shape)
    serves ``scripts/workloads.py diurnal`` — busy burst windows
    alternating with idle troughs. The policy must scale up into the
    bursts (pre-warming the joining replica from the hot prefix) and
    shed back down in the troughs. Replica-seconds are integrated from
    0.1 s samples of the router's registered-replica count.

    Leg 2 (chaos): with both replicas up, a live SSE stream's replica is
    force-retired and then SIGKILLed MID-DRAIN — the stream must still
    end 200/[DONE]/error-free via the router's checkpoint resume, with
    ``drain_killed`` counted.

    Leg 3 (static): a fixed 2-replica fleet replays the same schedule.

    Gates (each failure lands in result["error"]):
      * the policy drove >= 1 scale-up AND >= 1 scale-down
        (policy decisions counted, joined/retired events counted)
      * zero client-visible errors in EVERY leg, chaos stream included
      * both legs meet the interactive TTFT p99 envelope
        (ELASTIC_TTFT_P99_MS, default 30000 — equal-SLO, CPU slack)
      * elastic replica-seconds STRICTLY below static on the same replay
      * the chaos leg counted >= 1 ok resume and >= 1 drain_killed

    BENCH_ELASTIC_OUT writes the full report JSON for CI artifacts. The
    final metric is elastic replica-seconds; vs_baseline divides the
    static fleet's replica-seconds by it (above 1.0 = elasticity saved
    capacity at equal SLO compliance)."""
    import importlib.util
    import shutil
    import signal
    import socket
    import tempfile
    import threading

    import numpy as np

    from dllama_tpu.formats.spec import ArchType, ModelSpec
    from dllama_tpu.formats.tokenizer_file import (TokenizerData,
                                                   write_tokenizer)
    from dllama_tpu.formats.weights import tensor_plan, write_model
    from dllama_tpu.quants import blocks
    from dllama_tpu.serving import autoscale as asc
    from dllama_tpu.serving import fleet as fleet_mod
    from dllama_tpu.serving import router as router_mod

    repo = os.path.dirname(os.path.abspath(__file__))
    spec_wl = importlib.util.spec_from_file_location(
        "dllama_workloads", os.path.join(repo, "scripts", "workloads.py"))
    wl = importlib.util.module_from_spec(spec_wl)
    spec_wl.loader.exec_module(wl)

    # >= 3 diurnal cycles: the LAST burst always triggers a scale-up
    # whose boot cost the replay tail pays without reaping the benefit
    # (the replay ends before the newcomer does useful work) — a one-off
    # artifact that dominates a 2-cycle replay but amortizes over the
    # troughs, where elasticity actually earns its keep
    cycles = max(3, min(n, 4))
    ttft_bound_ms = float(os.environ.get("ELASTIC_TTFT_P99_MS", "30000"))
    tmp = tempfile.mkdtemp(prefix="bench_elastic_")
    spec = ModelSpec(arch=ArchType.LLAMA, dim=64, hidden_dim=96,
                     n_layers=2, n_heads=4, n_kv_heads=2, vocab_size=300,
                     seq_len=96, weights_float_type=blocks.Q40)
    rng = np.random.default_rng(0)
    model, tok = os.path.join(tmp, "m.m"), os.path.join(tmp, "t.t")
    write_model(model, spec,
                {e.name: 0.05 * rng.standard_normal(e.d * e.n).astype(
                    np.float32) for e in tensor_plan(spec)})
    vocab = ([b"<unk>", b"<s>", b"</s>"] + [bytes([i]) for i in range(256)]
             + [b"hi"] * 41)
    write_tokenizer(tok, TokenizerData(vocab=vocab, scores=[0.0] * 300,
                                       bos_id=1, eos_id=2))

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_PLATFORM_NAME", None)
    # slow every SSE frame a little so streams outlive the policy's tick
    # cadence and the chaos SIGKILL lands squarely inside a live stream
    env["DLLAMA_FAULTS"] = "stream:slow:delay_ms=30"

    def _free_base(span: int) -> int:
        for _ in range(64):
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                base = s.getsockname()[1]
            if base + span > 65500:
                continue
            try:
                for i in range(1, span):
                    with socket.socket() as t:
                        t.bind(("127.0.0.1", base + i))
                return base
            except OSError:
                continue
        raise RuntimeError("no free port span for the replica fleet")

    replica_args = ["--batch-window", "5", "--batch-max", "2",
                    "--batch-chunk", "2", "--kv-pages", "16", "--tp", "1",
                    "--ckpt-interval", "2"]
    schedule_kw = dict(cycles=cycles, bursts_per_cycle=3, burst_size=4,
                       burst_gap_s=1.5, idle_s=16.0, max_tokens=24)

    def integrate(samples, t0, t1) -> float:
        """Replica-seconds: piecewise-constant integral of the sampled
        registered count over [t0, t1]."""
        total, prev_t, prev_v = 0.0, None, None
        for t, v in samples + [(t1, samples[-1][1] if samples else 0)]:
            t = min(max(t, t0), t1)
            if prev_t is not None:
                total += prev_v * (t - prev_t)
            prev_t, prev_v = t, v
        return total

    def boot(n_replicas: int, base_port: int):
        fl = fleet_mod.Fleet(
            model, tok, n_replicas=n_replicas, base_port=base_port,
            host="127.0.0.1", replica_args=replica_args,
            log_dir=os.path.join(tmp, f"logs-{base_port}"), env=env)
        fl.start()
        if not fl.wait_ready(timeout_s=300.0):
            raise RuntimeError("replicas never became ready")
        fl.start_supervision(interval_s=0.5)
        state = router_mod.RouterState(
            [router_mod.Replica("127.0.0.1", r.port) for r in fl.replicas],
            probe_interval_s=0.25, ckpt_interval=2)
        state.probe_once()
        state.start_probes()
        srv = router_mod.create_router_server(state, "127.0.0.1", 0)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        # compile the boot replicas' programs outside the clocks
        for r in fl.replicas:
            w = wl.do_request("127.0.0.1", srv.server_address[1], wl.Req(
                0.0, f"warm-{r.port}", "interactive",
                [{"role": "user", "content": "warm up"}], 4), timeout=300.0)
            if w["status"] != 200 or w["error"]:
                raise RuntimeError(f"warm-up failed: {w['status']} "
                                   f"{w['error']!r}")
        return fl, state, srv

    gates = []
    report: dict = {"cycles": cycles, "ttft_bound_ms": ttft_bound_ms,
                    "cpu_count": os.cpu_count()}
    elastic_rs = static_rs = None

    # ---- leg 1+2: the elastic fleet ----------------------------------
    fl = state = srv = sup = None
    try:
        log("elastic bench: booting 1-replica fleet + autoscale loop...")
        fl, state, srv = boot(1, _free_base(4))
        r_port = srv.server_address[1]
        cfg = asc.PolicyConfig(
            min_replicas=1, max_replicas=2, up_pressure=0.5,
            down_pressure=0.2, up_consecutive=2, down_consecutive=4,
            cooldown_up_s=2.0, cooldown_down_s=3.0)
        sup = fleet_mod.ElasticSupervisor(
            fl, state, asc.AutoscalePolicy(cfg), interval_s=0.25,
            ready_timeout_s=120.0, drain_timeout_s=20.0, prewarm_tokens=8)
        ups0 = state._m_policy_evals.value(decision="up")
        downs0 = state._m_policy_evals.value(decision="down")
        joined0 = state._m_scale_events.value(event="joined")
        retired0 = state._m_scale_events.value(event="retired")
        fallback0 = state._m_scale_events.value(event="prewarm_fallback")
        sup.start()

        samples = []
        stop_sampling = threading.Event()

        def _sampler():
            while not stop_sampling.is_set():
                samples.append((time.monotonic(),
                                state._count_registered()))
                time.sleep(0.1)

        threading.Thread(target=_sampler, daemon=True).start()
        sched = wl.diurnal(seed=7, **schedule_kw)
        t0 = time.monotonic()
        results = wl.run_schedule("127.0.0.1", r_port, sched, timeout=600.0)
        t1 = time.monotonic()
        stop_sampling.set()
        elastic_rs = integrate(samples, t0, t1)
        summ = wl.summarize(results)
        ups = state._m_policy_evals.value(decision="up") - ups0
        downs = state._m_policy_evals.value(decision="down") - downs0
        joined = state._m_scale_events.value(event="joined") - joined0
        retired = state._m_scale_events.value(event="retired") - retired0
        fallback = (state._m_scale_events.value(event="prewarm_fallback")
                    - fallback0)
        report["elastic"] = {
            "replica_seconds": round(elastic_rs, 1),
            "wall_s": round(t1 - t0, 1), "summary": summ,
            "policy_ups": ups, "policy_downs": downs,
            "joined": joined, "retired": retired,
            "prewarm_fallbacks": fallback,
        }
        for cls, c in summ.items():
            for msg in c["errors"]:
                gates.append(f"elastic {cls}: {msg}")
        e_p99 = (summ.get("interactive") or {}).get("ttft_p99_ms")
        if e_p99 is None:
            gates.append("elastic replay produced no TTFT sample")
        elif e_p99 > ttft_bound_ms:
            gates.append(f"elastic TTFT p99 {e_p99:.0f} ms exceeds the "
                         f"{ttft_bound_ms:.0f} ms envelope — not "
                         "equal-SLO, the replica-seconds win is void")
        if ups < 1 or joined < 1:
            gates.append("the policy never scaled up into a burst "
                         f"(decisions up={ups:.0f}, joined={joined:.0f})")
        if downs < 1 or retired < 1:
            gates.append("the policy never scaled down in a trough "
                         f"(decisions down={downs:.0f}, "
                         f"retired={retired:.0f})")
        log(f"[elastic] replica-seconds {elastic_rs:.1f} over "
            f"{t1 - t0:.1f}s wall; ups {ups:.0f} downs {downs:.0f} "
            f"prewarm_fallbacks {fallback:.0f}")

        # the loop may be mid-transition (a tail-burst scale-up still
        # booting): stop new policy ticks, then wait out the in-flight
        # transition before staging the chaos leg — otherwise the
        # SIGKILL below lands on an unmanaged (not-yet-retiring)
        # replica, the crash-restart supervisor resurrects it mid-gate,
        # and the resume finds no ACTIVE sibling
        sup.stop()
        if sup._lock.acquire(timeout=240.0):
            sup._lock.release()
        else:
            gates.append("a scale transition never settled before the "
                         "chaos leg")

        # ---- leg 2: SIGKILL mid-drain on a live stream ---------------
        if state._count_registered() < 2:
            sup.scale_up()  # forced: the chaos leg needs a sibling
        if state._count_registered() < 2:
            gates.append("could not restore a 2-replica fleet for the "
                         "chaos leg")
        else:
            ok0 = state._m_resumes.value(outcome="ok")
            dk0 = state._m_scale_events.value(event="drain_killed")
            chaos_res = [None]

            def _chaos_stream():
                chaos_res[0] = wl.do_request(
                    "127.0.0.1", r_port, wl.Req(
                        0.0, "chaos", "interactive",
                        [{"role": "user",
                          "content": "chaos stream ride the drain"}], 64),
                    timeout=600.0)

            ct = threading.Thread(target=_chaos_stream, daemon=True)
            ct.start()
            victim = None
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline and victim is None:
                for rep in state.replicas:
                    if rep.snapshot().get("inflight", 0) > 0:
                        victim = rep.name
                        break
                time.sleep(0.01)
            if victim is None:
                gates.append("chaos stream never showed up in-flight")
            else:
                time.sleep(0.3)  # let checkpoints land in the store
                proc = next(p for p in fl.replicas if p.name == victim)
                dt = threading.Thread(
                    target=lambda: sup.scale_down(target=victim),
                    daemon=True)
                dt.start()
                time.sleep(0.3)  # drain under way (SIGTERM delivered)
                if proc.proc.poll() is None:
                    os.kill(proc.proc.pid, signal.SIGKILL)
                    log(f"[chaos] SIGKILLed {victim} mid-drain")
                dt.join(timeout=120.0)
            ct.join(timeout=600.0)
            cres = chaos_res[0]
            resumes = state._m_resumes.value(outcome="ok") - ok0
            drain_killed = (state._m_scale_events.value(
                event="drain_killed") - dk0)
            report["chaos"] = {
                "victim": victim, "resumes_ok": resumes,
                "drain_killed": drain_killed,
                "stream": ({"status": cres["status"], "done": cres["done"],
                            "error": cres["error"]} if cres else None)}
            if cres is None or cres["status"] != 200 or cres["error"] \
                    or not cres["done"]:
                gates.append(
                    "client-visible damage across the mid-drain SIGKILL: "
                    + (f"{cres['status']} {cres['error']!r} "
                       f"done={cres['done']}" if cres
                       else "stream never resolved"))
            if victim and resumes < 1:
                gates.append("mid-drain SIGKILL but no ok resume counted "
                             f"(got {resumes:.0f})")
            if victim and drain_killed < 1:
                gates.append("mid-drain SIGKILL not counted as "
                             "drain_killed")
            log(f"[chaos] resumes ok {resumes:.0f}, "
                f"drain_killed {drain_killed:.0f}")
    finally:
        if sup is not None:
            sup.stop()
        if state is not None:
            state.stop_probes()
        if srv is not None:
            srv.shutdown()
            srv.server_close()
        if fl is not None:
            fl.drain(timeout_s=10.0)

    # ---- leg 3: the static 2-replica fleet on the same replay --------
    fl = state = srv = None
    try:
        log("elastic bench: booting the static 2-replica fleet...")
        fl, state, srv = boot(2, _free_base(4))
        sched = wl.diurnal(seed=7, **schedule_kw)
        t0 = time.monotonic()
        results = wl.run_schedule("127.0.0.1", srv.server_address[1],
                                  sched, timeout=600.0)
        t1 = time.monotonic()
        static_rs = 2.0 * (t1 - t0)
        ssumm = wl.summarize(results)
        report["static"] = {"replica_seconds": round(static_rs, 1),
                            "wall_s": round(t1 - t0, 1), "summary": ssumm}
        for cls, c in ssumm.items():
            for msg in c["errors"]:
                gates.append(f"static {cls}: {msg}")
        s_p99 = (ssumm.get("interactive") or {}).get("ttft_p99_ms")
        if s_p99 is not None and s_p99 > ttft_bound_ms:
            gates.append(f"static TTFT p99 {s_p99:.0f} ms exceeds the "
                         f"{ttft_bound_ms:.0f} ms envelope")
        log(f"[static] replica-seconds {static_rs:.1f} over "
            f"{t1 - t0:.1f}s wall")
    finally:
        if state is not None:
            state.stop_probes()
        if srv is not None:
            srv.shutdown()
            srv.server_close()
        if fl is not None:
            fl.drain(timeout_s=10.0)
        shutil.rmtree(tmp, ignore_errors=True)

    if elastic_rs is not None and static_rs is not None \
            and elastic_rs >= static_rs:
        gates.append(
            f"elastic fleet used {elastic_rs:.1f} replica-seconds vs the "
            f"static fleet's {static_rs:.1f} on the same replay — "
            "elasticity saved nothing")
    report["gates_failed"] = gates
    out_path = os.environ.get("BENCH_ELASTIC_OUT")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=2, default=str)
        log(f"report written to {out_path}")
    result = {
        "metric": "smoke_elastic_replica_seconds",
        "value": round(elastic_rs, 1) if elastic_rs is not None else None,
        "unit": "replica_s",
        "vs_baseline": (round(static_rs / elastic_rs, 2)
                        if elastic_rs and static_rs else None),
        "baseline": "a static 2-replica fleet on the same bursty-diurnal "
                    "replay (equal SLO envelope)",
        "weights": "q40-elastic-fleet",
        "platform": "cpu-subprocess-fleet",
        "n_devices": 2,
    }
    if gates:
        result["error"] = "; ".join(gates)
    return result


def run_c10k_bench(n: int) -> dict:
    """BENCH_C10K=N: N concurrent slow-drip SSE sessions through ONE
    event-loop router with adversarial chaos peers running alongside —
    jax-free and fully in-process (the replicas are evloop stub servers,
    not engines: this bench measures the DATA PLANE, not decode).

    Topology: 2 stub replicas (selectors loops) <- the router's evloop
    front door <- N well-behaved SSE clients on sharded selectors loops,
    PLUS a chaos cohort (scripts/chaos_peer.py: slow-loris dribblers,
    midstream-hang readers fed a firehose, RST peers) PLUS one mid-SSE
    STALL session whose upstream goes silent right after a checkpoint
    frame and must be checkpoint-resumed on the sibling byte-identically
    (dllama_stream_resume_total{outcome="stall"}).

    Every event carries the replica's monotonic send stamp, so "added
    latency" is exactly the router + scheduling cost, not the drip.
    N is scaled down only when RLIMIT_NOFILE demands it (~5 fds per
    session across the four sockets each one fans out to).

    Gates (each failure lands in result["error"]):
      * zero client-visible errors on the well-behaved cohort, chaos on
      * peak concurrent streams >= 0.9 * N (the sessions truly overlap)
      * p99 added event latency <= C10K_P99_MS (default 2000 ms)
      * RSS growth <= max(N * C10K_RSS_KB (default 64 KiB), 192 MiB)
      * the stall session's body is EXACTLY the no-failure stream and
        the resume was accounted with outcome="stall"
      * every chaos mode bit: slow-loris cut at --header-timeout,
        midstream-hang killed at --client-stall-timeout, RST absorbed —
        and the router still answers /health afterwards
      * admission control: a --max-conns 4 router sheds connection 5
        with the canned 503 BEFORE allocating state (reason=max_conns)

    BENCH_C10K_OUT writes the full report JSON for CI artifacts."""
    import base64
    import http.client as hc
    import importlib.util
    import resource
    import socket
    import threading

    from dllama_tpu.serving import evloop
    from dllama_tpu.serving import router as router_mod
    from dllama_tpu.serving.protocol import HDR_RESUME_OFFSET

    # ---- fd budget: ~5 fds per session (client sock, router front +
    # upstream, replica sock, slack) — raise the soft limit, then scale
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if soft < hard:
        try:
            resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
            soft = hard
        except (ValueError, OSError):
            pass
    n_eff = max(8, min(n, (soft - 512) // 5))
    if n_eff < n:
        log(f"c10k: RLIMIT_NOFILE {soft} caps the run at {n_eff} "
            f"sessions (asked {n})")

    # ---- pacing: ramp at a bounded accept rate; drip slowly enough that
    # the single-process GIL can push every event through all three hops
    rate = max(100.0, float(os.environ.get("C10K_RAMP_RATE", "1500")))
    ramp_s = n_eff / rate
    drip_s = max(0.4, n_eff / 6000.0)
    n_events = max(8, min(60, int((ramp_s + 3.0) / drip_s) + 2))
    # the inter-byte stall budget must clear one drip interval with slack
    stall_timeout_s = drip_s * 2.0 + 1.0

    # ---- the stall-session fixture: what the client must end up with
    ev_a = b"data: alpha\n\n"
    ev_b = b"data: bravo\n\n"
    ev_c = b"data: charlie\n\n"
    sse_done = b"data: [DONE]\n\n"
    visible = ev_a + ev_b + ev_c + sse_done
    snap = b"c10k-stall-snapshot"
    ckpt_off = len(ev_a)
    ckpt_frame = (b"event: dllama-ckpt\ndata: %d %s\n\n"
                  % (ckpt_off, base64.b64encode(snap)))
    resume_bodies: list = []

    # ---- stub replica: /ready, slow-drip SSE chat (send-stamped), the
    # stall session, a firehose for the hanging chaos readers, resume
    def stub_handler(server, sock, addr):
        buf = bytearray()
        while True:
            req = yield from evloop.read_request(sock, buf)
            if req is None:
                return
            if req.method == "GET" and req.path == "/ready":
                body = json.dumps({
                    "status": "ready", "slots_occupied": 0,
                    "slots_total": 65536, "queue_depth": 0,
                    "kv_pages_free": 65536, "kv_pages_total": 65536,
                    "prefix_hit_rate": 0.0}).encode()
                yield from evloop.send_all(sock, evloop.response_bytes(
                    200, [("Content-Type", "application/json"),
                          ("Content-Length", str(len(body)))], body))
            elif req.method == "POST" and req.path == "/v1/kv/resume":
                resume_bodies.append(req.body)
                cont = visible[ckpt_off:]
                yield from evloop.send_all(sock, evloop.response_bytes(
                    200, [("Content-Type", "text/event-stream"),
                          (HDR_RESUME_OFFSET, str(ckpt_off)),
                          ("Content-Length", str(len(cont)))], cont))
            elif req.method == "POST":
                head = evloop.response_bytes(
                    200, [("Content-Type", "text/event-stream"),
                          ("Connection", "close")])
                if b"stall-session" in req.body:
                    # checkpoint, one more event, then SILENCE with the
                    # socket open: the death only the stall budget sees
                    yield from evloop.send_all(
                        sock, head + ev_a + ckpt_frame + ev_b)
                    yield from evloop.sleep(120.0)
                    return
                if b"chaos" in req.body:
                    # firehose for midstream-hang peers: the bounded
                    # relay buffer pauses THIS send (backpressure) until
                    # the client-stall kill tears the path down (OSError
                    # here ends the task — the loop treats that as the
                    # normal teardown)
                    yield from evloop.send_all(sock, head)
                    block = b"data: " + b"x" * 8192 + b"\n\n"
                    while True:
                        yield from evloop.send_all(sock, block)
                yield from evloop.send_all(sock, head)
                for k in range(n_events):
                    yield from evloop.sleep(drip_s)
                    ev = (b"data: " + json.dumps(
                        {"k": k, "t_us": int(time.monotonic() * 1e6)}
                    ).encode() + b"\n\n")
                    yield from evloop.send_all(sock, ev)
                yield from evloop.send_all(sock, b"data: [DONE]\n\n")
                return
            else:
                yield from evloop.send_all(sock, evloop.response_bytes(
                    404, [("Content-Length", "0")]))
            if not req.keep_alive:
                return

    def boot_stub(name: str):
        srv = evloop.EventLoopServer(("127.0.0.1", 0), stub_handler)
        threading.Thread(target=srv.serve_forever, daemon=True,
                         name=f"c10k-replica-{name}").start()
        return srv

    def _rss_kb() -> int:
        try:
            with open("/proc/self/status") as f:
                for line in f:
                    if line.startswith("VmRSS:"):
                        return int(line.split()[1])
        except OSError:
            pass
        return 0

    def _drain(sock, timeout: float) -> bytes:
        sock.settimeout(timeout)
        out = bytearray()
        try:
            while True:
                b = sock.recv(65536)
                if not b:
                    break
                out += b
        except OSError:
            pass
        return bytes(out)

    repo = os.path.dirname(os.path.abspath(__file__))
    spec_cp = importlib.util.spec_from_file_location(
        "dllama_chaos_peer", os.path.join(repo, "scripts", "chaos_peer.py"))
    chaos = importlib.util.module_from_spec(spec_cp)
    spec_cp.loader.exec_module(chaos)

    gates: list = []
    report: dict = {"n_requested": n, "n_sessions": n_eff,
                    "events_per_session": n_events,
                    "drip_s": drip_s, "ramp_s": round(ramp_s, 2),
                    "stall_timeout_s": stall_timeout_s}
    rep_a = rep_b = state = srv = None
    stop_mon = threading.Event()
    shards: list = []
    try:
        rep_a, rep_b = boot_stub("a"), boot_stub("b")
        state = router_mod.RouterState(
            [router_mod.Replica("127.0.0.1", rep_a.server_address[1]),
             router_mod.Replica("127.0.0.1", rep_b.server_address[1])],
            probe_interval_s=3600.0, connect_timeout_s=5.0,
            header_timeout_s=3.0, first_byte_timeout_s=15.0,
            stall_timeout_s=stall_timeout_s, client_stall_timeout_s=2.0,
            ckpt_interval=2, probe_read_timeout_s=2.0)
        srv = router_mod.create_router_server(state, "127.0.0.1", 0)
        threading.Thread(target=srv.serve_forever, daemon=True,
                         name="c10k-router").start()
        port = srv.server_address[1]
        ready0 = state.probe_once()
        if ready0 != 2:
            gates.append(f"boot probe saw {ready0}/2 stub replicas ready")
        log(f"c10k: router on :{port}, {n_eff} sessions x {n_events} "
            f"events, drip {drip_s:.2f}s, ramp {ramp_s:.1f}s")

        # ---- the well-behaved cohort: sharded selectors client loops
        n_shards = 4 if n_eff >= 1000 else 2
        for i in range(n_shards):
            shards.append({"loop": evloop.Loop(), "count": 0, "done": 0,
                           "active": 0, "errors": 0, "err_samples": [],
                           "lats": []})

        def make_session(shard, gidx):
            def session():
                counted = False
                sock = None
                try:
                    yield from evloop.sleep(gidx / rate)
                    dl = time.monotonic() + 60.0
                    sock = yield from evloop.dial(("127.0.0.1", port), dl)
                    up = evloop.Upstream(sock, "127.0.0.1", port)
                    body = json.dumps({
                        "model": "m", "stream": True,
                        "messages": [{"role": "user",
                                      "content": f"c10k-{gidx}"}]}).encode()
                    yield from up.request(
                        "POST", "/v1/chat/completions",
                        {"Content-Type": "application/json"}, body, dl)
                    resp = yield from up.get_response(dl)
                    if resp.status != 200:
                        raise OSError(f"status {resp.status}")
                    shard["active"] += 1
                    counted = True
                    buf = bytearray()
                    seen_done, n_ev = False, 0
                    while not seen_done:
                        data = yield from resp.read_some(
                            time.monotonic() + drip_s + 10.0)
                        if not data:
                            break
                        now_us = time.monotonic() * 1e6
                        buf += data
                        while True:
                            cut = buf.find(b"\n\n")
                            if cut < 0:
                                break
                            frame = bytes(buf[:cut])
                            del buf[:cut + 2]
                            if frame == b"data: [DONE]":
                                seen_done = True
                            elif frame.startswith(b"data: {"):
                                stamp = json.loads(frame[6:])
                                shard["lats"].append(
                                    (now_us - stamp["t_us"]) / 1000.0)
                                n_ev += 1
                    if not seen_done or n_ev != n_events:
                        raise OSError(f"incomplete stream: done="
                                      f"{seen_done} events {n_ev}"
                                      f"/{n_events}")
                except Exception as e:  # noqa: BLE001 — every failure gates
                    shard["errors"] += 1
                    if len(shard["err_samples"]) < 5:
                        shard["err_samples"].append(
                            f"{type(e).__name__}: {e}")
                finally:
                    if sock is not None:
                        try:
                            sock.close()
                        except OSError:
                            pass
                    if counted:
                        shard["active"] -= 1
                    shard["done"] += 1
                    if shard["done"] == shard["count"]:
                        shard["loop"].stop()
            return session()

        for gidx in range(n_eff):
            shards[gidx % n_shards]["count"] += 1
        for gidx in range(n_eff):
            sh = shards[gidx % n_shards]
            sh["loop"].spawn(make_session(sh, gidx))

        base_rss = _rss_kb()
        peak = {"active": 0, "rss_kb": base_rss}

        def monitor():
            while not stop_mon.is_set():
                act = sum(sh["active"] for sh in shards)
                peak["active"] = max(peak["active"], act)
                peak["rss_kb"] = max(peak["rss_kb"], _rss_kb())
                stop_mon.wait(0.1)

        mon = threading.Thread(target=monitor, daemon=True)
        mon.start()

        # ---- chaos cohorts + the stall session, live during the ramp
        n_peers = max(5, min(20, n_eff // 50))
        chaos_dur = max(8.0, ramp_s + 4.0)
        chaos_out: dict = {}
        chaos_threads = [
            threading.Thread(
                target=lambda m=mode: chaos_out.__setitem__(
                    m, chaos.run_cohort(m, "127.0.0.1", port, n_peers,
                                        chaos_dur)),
                daemon=True, name=f"c10k-chaos-{mode}")
            for mode in ("slowloris", "midstream_hang", "reset")]
        stall_out: dict = {}

        def run_stall():
            time.sleep(min(2.0, ramp_s / 2 + 0.2))
            try:
                conn = hc.HTTPConnection("127.0.0.1", port, timeout=90)
                conn.request(
                    "POST", "/v1/chat/completions",
                    json.dumps({"model": "m", "stream": True,
                                "messages": [{"role": "user",
                                              "content": "stall-session"}]
                                }).encode(),
                    headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                stall_out["status"] = resp.status
                stall_out["body"] = resp.read()
                conn.close()
            except Exception as e:  # noqa: BLE001 — gated below
                stall_out["error"] = f"{type(e).__name__}: {e}"

        stall_thread = threading.Thread(target=run_stall, daemon=True)

        shard_threads = [
            threading.Thread(target=sh["loop"].run, daemon=True,
                             name=f"c10k-shard-{i}")
            for i, sh in enumerate(shards)]
        t0 = time.monotonic()
        for t in shard_threads + chaos_threads + [stall_thread]:
            t.start()
        join_budget = ramp_s + n_events * drip_s + 90.0
        for t in shard_threads:
            t.join(max(10.0, join_budget - (time.monotonic() - t0)))
        for sh in shards:
            sh["loop"].call_threadsafe(sh["loop"].stop)  # no-op if done
        for t in chaos_threads:
            t.join(30.0)
        stall_thread.join(120.0)
        stop_mon.set()
        mon.join(5.0)
        wall_s = time.monotonic() - t0

        # ---- gates ----------------------------------------------------
        total_err = sum(sh["errors"] for sh in shards)
        total_done = sum(sh["done"] for sh in shards)
        samples = [s for sh in shards for s in sh["err_samples"]][:5]
        if total_err:
            gates.append(f"{total_err} well-behaved client error(s), "
                         f"e.g. {samples}")
        if total_done != n_eff:
            gates.append(f"only {total_done}/{n_eff} sessions finished "
                         f"inside {join_budget:.0f}s")
        if peak["active"] < 0.9 * n_eff:
            gates.append(f"peak concurrency {peak['active']} never "
                         f"reached 0.9 x {n_eff} — sessions did not "
                         "overlap")
        lats = [x for sh in shards for x in sh["lats"]]
        p50 = _pct(lats, 50) if lats else None
        p99 = _pct(lats, 99) if lats else None
        p99_bound = float(os.environ.get("C10K_P99_MS", "2000"))
        if p99 is None:
            gates.append("no event latencies recorded")
        elif p99 > p99_bound:
            gates.append(f"p99 added event latency {p99:.0f} ms exceeds "
                         f"the {p99_bound:.0f} ms budget")
        rss_growth_kb = max(0, peak["rss_kb"] - base_rss)
        rss_budget_kb = max(
            n_eff * float(os.environ.get("C10K_RSS_KB", "64")),
            192 * 1024)
        if rss_growth_kb > rss_budget_kb:
            gates.append(f"RSS grew {rss_growth_kb} KiB "
                         f"(> {rss_budget_kb:.0f} KiB budget)")
        if stall_out.get("status") != 200:
            gates.append(f"stall session: {stall_out}")
        elif stall_out.get("body") != visible:
            gates.append("stall session body is not byte-identical to "
                         "the no-failure stream "
                         f"({len(stall_out.get('body') or b'')} vs "
                         f"{len(visible)} bytes)")
        if state._m_resumes.value(outcome="stall") < 1:
            gates.append("no resume was accounted with outcome=stall")
        if snap not in resume_bodies:
            gates.append("the sibling never received the checkpoint "
                         "snapshot on /v1/kv/resume")
        for mode, key in (("slowloris", "cut_by_router"),
                          ("midstream_hang", "killed_by_router"),
                          ("reset", "sent_rst")):
            got = (chaos_out.get(mode) or {}).get(key, 0)
            if got < 1:
                gates.append(f"chaos {mode}: {key}=0 of {n_peers} peers "
                             f"({chaos_out.get(mode)})")
        try:
            conn = hc.HTTPConnection("127.0.0.1", port, timeout=10)
            conn.request("GET", "/health")
            health = conn.getresponse().status
            conn.close()
        except OSError as e:
            health = f"unreachable: {e}"
        if health != 200:
            gates.append(f"router /health after chaos: {health}")

        report.update({
            "wall_s": round(wall_s, 1), "peak_active": peak["active"],
            "sessions_done": total_done, "client_errors": total_err,
            "error_samples": samples,
            "added_latency_ms": {"p50": p50, "p99": p99,
                                 "n_events": len(lats)},
            "rss_base_kb": base_rss, "rss_peak_kb": peak["rss_kb"],
            "rss_growth_kb": rss_growth_kb,
            "rss_per_conn_kb": round(rss_growth_kb / n_eff, 1),
            "chaos": chaos_out,
            "stall": {"status": stall_out.get("status"),
                      "byte_identical":
                          stall_out.get("body") == visible,
                      "error": stall_out.get("error"),
                      "resume_outcome_stall":
                          state._m_resumes.value(outcome="stall")},
            "router_health_after": health,
        })
        log(f"c10k: {total_done}/{n_eff} sessions, peak {peak['active']} "
            f"concurrent, p99 added {p99 if p99 is None else round(p99)} "
            f"ms, +{rss_growth_kb} KiB RSS over {wall_s:.1f}s")

        # ---- admission-control proof on a tiny --max-conns router ------
        mini_state = router_mod.RouterState(
            [router_mod.Replica("127.0.0.1", rep_a.server_address[1])],
            probe_interval_s=3600.0, max_conns=4)
        mini = router_mod.create_router_server(mini_state, "127.0.0.1", 0)
        threading.Thread(target=mini.serve_forever, daemon=True,
                         name="c10k-mini-router").start()
        held = []
        try:
            for _ in range(4):
                c = hc.HTTPConnection("127.0.0.1",
                                      mini.server_address[1], timeout=10)
                c.request("GET", "/health")
                c.getresponse().read()
                held.append(c)  # keep-alive: the slot stays occupied
            s = socket.create_connection(
                ("127.0.0.1", mini.server_address[1]), timeout=10)
            data = _drain(s, timeout=5.0)
            s.close()
            got_503 = b"503" in data.split(b"\r\n", 1)[0]
            sheds = mini_state._m_sheds.value(reason="max_conns")
            report["shed"] = {"got_503": got_503, "sheds": sheds}
            if not got_503 or sheds < 1:
                gates.append(f"max-conns shed proof failed: 503="
                             f"{got_503} sheds={sheds} "
                             f"({data[:80]!r})")
        finally:
            for c in held:
                c.close()
            mini_state.stop_probes()
            mini.shutdown()
            mini.server_close()
    finally:
        stop_mon.set()
        for sh in shards:
            try:
                sh["loop"].call_threadsafe(sh["loop"].stop)
            except Exception:  # noqa: BLE001 — loop already torn down
                pass
        if state is not None:
            state.stop_probes()
        if srv is not None:
            srv.shutdown()
            srv.server_close()
        for rep in (rep_a, rep_b):
            if rep is not None:
                rep.shutdown()
                rep.server_close()

    report["gates_failed"] = gates
    out_path = os.environ.get("BENCH_C10K_OUT")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=2, default=str)
        log(f"report written to {out_path}")
    result = {
        "metric": "smoke_c10k_conns",
        "value": n_eff,
        "unit": "conns",
        "vs_baseline": None,
        "baseline": "the same process's thread-per-connection ceiling "
                    "(a threaded data plane cannot hold this many "
                    "concurrent SSE relays at bounded RSS)",
        "weights": "none-data-plane-only",
        "platform": "cpu-evloop",
        "n_devices": 2,
    }
    if gates:
        result["error"] = "; ".join(gates)
    return result


def _trajectory_note(status: str, result=None, error=None) -> None:
    """Append this round to the durable bench trajectory
    (results/trajectory.jsonl) and surface comparator regressions.

    Both exit paths of main() that end in a JSON line land here (a run
    that raises ends in its traceback instead). Never raises."""
    from dllama_tpu.obsv import trajectory as _traj

    bench = (result or {}).get("metric") or "bench"
    gates = {"hard_fail": status == "ok"}
    rep = _traj.append_row(bench, status, result=result, gates=gates,
                           error=error)
    for flag in rep["regressions"]:
        log(f"trajectory REGRESSION vs last same-host {bench} run: {flag}")
    if rep["path"]:
        log(f"trajectory: {status} row appended to {rep['path']} "
            f"({len(rep['regressions'])} regression flag(s))")


def main() -> None:
    # metric name for the error path, resolvable without touching jax
    choice = os.environ.get("BENCH_MODEL", "")
    err_phase = ("prefill" if _prefill_count()
                 else "prefix" if _env_count("BENCH_PREFIX")
                 else "overlap" if _env_count("BENCH_OVERLAP")
                 else "reduce" if _env_count("BENCH_REDUCE")
                 else "serve" if _env_count("BENCH_CONTINUOUS")
                 else "faults" if _env_count("BENCH_FAULTS")
                 else "integrity" if _env_count("BENCH_INTEGRITY")
                 else "obs" if _env_count("BENCH_OBS")
                 else "router" if _env_count("BENCH_ROUTER")
                 else "disagg" if _env_count("BENCH_DISAGG")
                 else "failover" if _env_count("BENCH_FAILOVER")
                 else "workloads" if _env_count("BENCH_WORKLOADS")
                 else "elastic" if _env_count("BENCH_ELASTIC")
                 else "c10k" if _env_count("BENCH_C10K")
                 else "decode")
    err_metric = {"tiny": "tinyllama_1.1b", "llama3": "llama3_8b",
                  "moe": "mixtral_lite", "grok": "grok1_lite",
                  "smoke": "smoke"}.get(
        choice, "llama2_7b") + f"_{err_phase}_ms_per_token"

    nrouter = _env_count("BENCH_ROUTER")
    ndisagg = _env_count("BENCH_DISAGG")
    nfailover = _env_count("BENCH_FAILOVER")
    nworkloads = _env_count("BENCH_WORKLOADS")
    nelastic = _env_count("BENCH_ELASTIC")
    nc10k = _env_count("BENCH_C10K")
    if nrouter or ndisagg or nfailover or nworkloads or nelastic or nc10k:
        # the router, disaggregation, failover and workload replays are
        # jax-free IN THIS PROCESS (replicas are CPU subprocesses), so
        # branch before anything here imports jax
        try:
            result = (run_router_bench(nrouter) if nrouter
                      else run_disagg_bench(ndisagg) if ndisagg
                      else run_failover_bench(nfailover) if nfailover
                      else run_workloads_bench(nworkloads) if nworkloads
                      else run_elastic_bench(nelastic) if nelastic
                      else run_c10k_bench(nc10k))
        except Exception as e:  # noqa: BLE001 — emit the machine-readable record
            result = {"metric": err_metric, "value": None,
                      "unit": ("req/s" if nrouter
                               else "conns" if nc10k else "ms"),
                      "vs_baseline": None,
                      "error": f"{type(e).__name__}: {e}"}
        print(json.dumps(result), flush=True)
        _trajectory_note("error" if result.get("error") else "ok",
                         result=result, error=result.get("error"))
        raise SystemExit(1 if result.get("error") else 0)

    import jax

    from dllama_tpu.runtime.device import configure_compile_cache

    log(f"compile cache: {configure_compile_cache()}")

    platform = jax.devices()[0].platform
    choice = os.environ.get("BENCH_MODEL", "")
    if choice == "smoke" or (not choice and platform == "cpu"
                             and (_env_count("BENCH_CONTINUOUS")
                                  or _env_count("BENCH_FAULTS")
                                  or _env_count("BENCH_INTEGRITY")
                                  or _env_count("BENCH_OBS")
                                  or _env_count("BENCH_PREFIX")
                                  or _env_count("BENCH_OVERLAP")
                                  or _env_count("BENCH_REDUCE")
                                  or _prefill_count())):
        # the scheduling replays (continuous-vs-static, fault boundedness,
        # prefill stall) measure SCHEDULING, so the CPU default is a shape
        # small enough to replay inside CI budgets
        name, cfg_dict = "smoke", SMOKE_SERVE
    elif choice == "tiny" or (not choice and platform == "cpu"):
        name, cfg_dict = "tinyllama_1.1b", TINYLLAMA_1_1B
    elif choice == "llama3":
        # the north-star config (no published same-hardware baseline number;
        # vs_baseline stays null — the 7B default is the comparable metric)
        name, cfg_dict = "llama3_8b", LLAMA3_8B
    elif choice == "moe":
        name, cfg_dict = "mixtral_lite", MIXTRAL_LITE
    elif choice == "grok":
        name, cfg_dict = "grok1_lite", GROK1_LITE
    else:
        name, cfg_dict = "llama2_7b", LLAMA2_7B

    ms, weights = run_decode_bench(cfg_dict)

    phase = ("prefill" if _prefill_count()
             else "prefix" if _env_count("BENCH_PREFIX")
             else "overlap" if _env_count("BENCH_OVERLAP")
             else "reduce" if _env_count("BENCH_REDUCE")
             else "serve" if _env_count("BENCH_CONTINUOUS")
             else "faults" if _env_count("BENCH_FAULTS")
             else "integrity" if _env_count("BENCH_INTEGRITY")
             else "obs" if _env_count("BENCH_OBS")
             else "decode")
    result = {
        "metric": f"{name}_{phase}_ms_per_token",
        "value": round(ms, 3),
        "unit": "ms/token",
        # only meaningful for the same model the baseline measured (7B);
        # a ratio against a 1.1B run would be apples-to-oranges; the prefill
        # mode compares legitimately (the reference prefills at decode cost)
        # but stays unclaimed here — the phase-tagged metric speaks for itself
        # ... and only at the stock context length (BENCH_SEQ changes the
        # per-token work, so the ratio would compare different jobs)
        "vs_baseline": (round(BASELINE_7B_SINGLE_NODE_MS / ms, 2)
                        if name == "llama2_7b" and phase == "decode"
                        and not _seq_override() else None),
        "baseline": "llama2-7b 1x GCP c3d-highcpu-30, 101.81 ms/token (reference README.md:88)",
        "weights": weights,
        "platform": jax.devices()[0].device_kind,
        "n_devices": len(jax.devices()),
    }
    print(json.dumps(result), flush=True)
    _trajectory_note("ok", result=result)


if __name__ == "__main__":
    main()
