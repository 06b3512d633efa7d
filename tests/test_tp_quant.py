"""Quantized weights x tensor parallelism (parallel.quant_tp).

The reference's production configuration is Q40 weights on every node of a
multi-node run (`/root/reference/src/transformer.cpp:454-493` +
`/root/reference/src/funcs.cpp:267-385`). The TPU equivalent runs the fused
dequant-matmul kernels under shard_map with output-sharded quant planes.
These tests assert the distributed result equals the single-device result on
the 8-virtual-device CPU mesh — the sharding-invariance pattern of
`/root/reference/src/transformer-test.cpp:6-84`, applied to the quant path
the reference never automates.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.models import llama
from dllama_tpu.models.config import ModelConfig
from dllama_tpu.parallel import quant_tp
from dllama_tpu.parallel.mesh import tp_mesh
from dllama_tpu.runtime.generate import Engine
from dllama_tpu.runtime.sampler import SamplerConfig

CFG = ModelConfig(
    arch="llama", dim=256, hidden_dim=512, n_layers=2, n_heads=8, n_kv_heads=8,
    vocab_size=512, seq_len=64, head_size=32, kv_dim=256, dtype="float32",
)


def _quant_params(kind="q40", seed=0):
    dense = llama.random_params(CFG, seed=seed, dtype=np.float32)
    return llama.quantize_params(dense, kind)


@pytest.mark.parametrize("tp", [2, 8])
@pytest.mark.parametrize("kind", ["q40", "q80"])
def test_tp_forward_matches_single_device(tp, kind):
    """One forward step: shard_map quant-TP logits == single-device logits."""
    qp = _quant_params(kind)
    rope = llama.rope_tables(CFG)
    tokens = jnp.asarray([5], jnp.int32)

    cache1 = llama.init_cache(CFG)
    ref_logits, _ = jax.jit(
        lambda p, r, c, t: llama.forward(CFG, p, r, t, c, jnp.int32(0))
    )(jax.tree.map(jnp.asarray, qp), rope, cache1, tokens)

    mesh = tp_mesh(tp)
    sharded = quant_tp.shard_quant_params(qp, mesh, CFG)
    fwd = quant_tp.make_tp_forward(CFG, mesh, sharded)
    cache2 = llama.init_cache(CFG)
    tp_logits, _ = jax.jit(fwd)(sharded, rope, cache2, tokens, jnp.int32(0))

    np.testing.assert_allclose(
        np.asarray(tp_logits), np.asarray(ref_logits), rtol=1e-4, atol=1e-4
    )


def test_tp_engine_greedy_decode_invariance():
    """Engine-level: greedy tokens from the quant-TP engine == single-device."""
    qp = _quant_params("q40")
    e1 = Engine(CFG, qp, SamplerConfig(temperature=0.0))
    t1, _, _ = e1.generate_fused([3, 7, 11], steps=8)

    e2 = Engine(CFG, qp, SamplerConfig(temperature=0.0), mesh=tp_mesh(8))
    t2, _, _ = e2.generate_fused([3, 7, 11], steps=8)
    assert t1 == t2


def test_quant_specs_shard_every_plane():
    """Every quant plane of the big matrices must actually shard (no silent
    replication — the failure mode that keeps the 4x HBM win from being real)."""
    qp = _quant_params("q40")
    specs = quant_tp.quant_param_specs(qp, CFG, 8)
    for name in ("wq", "wk", "wv", "wo", "w1", "w2", "w3"):
        qt = specs["layers"][name]
        assert qt.w[-1] == "tp" and qt.s[-1] == "tp" and qt.s2[-1] == "tp", name
    assert specs["wcls"].w[-1] == "tp"  # 512 % 8 == 0


def test_quant_tp_indivisible_vocab_replicates_wcls():
    cfg = ModelConfig(
        arch="llama", dim=256, hidden_dim=512, n_layers=1, n_heads=8, n_kv_heads=8,
        vocab_size=500, seq_len=32, head_size=32, kv_dim=256, dtype="float32",
    )
    dense = llama.random_params(cfg, seed=1, dtype=np.float32)
    qp = llama.quantize_params(dense, "q40")
    specs = quant_tp.quant_param_specs(qp, cfg, 8)
    assert all(s is None for s in specs["wcls"].w)

    mesh = tp_mesh(8)
    sharded = quant_tp.shard_quant_params(qp, mesh, cfg)
    fwd = quant_tp.make_tp_forward(cfg, mesh, sharded)
    rope = llama.rope_tables(cfg)
    logits, _ = jax.jit(fwd)(
        sharded, rope, llama.init_cache(cfg), jnp.asarray([2], jnp.int32), jnp.int32(0)
    )
    ref, _ = jax.jit(
        lambda p, r, c, t: llama.forward(cfg, p, r, t, c, jnp.int32(0))
    )(jax.tree.map(jnp.asarray, qp), rope, llama.init_cache(cfg), jnp.asarray([2], jnp.int32))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref), rtol=1e-4, atol=1e-4)


def test_quant_reader_streams_onto_mesh(tmp_path):
    """quant_params_from_reader(mesh=...) must place every big-matrix plane
    sharded (never whole on one device — the 70B-class load path) and decode
    identically to the host-loaded single-device engine."""
    from dllama_tpu.formats.spec import ArchType, ModelSpec
    from dllama_tpu.formats.weights import tensor_plan, write_model, WeightFileReader
    from dllama_tpu.quants import blocks

    spec = ModelSpec(
        arch=ArchType.LLAMA, dim=CFG.dim, hidden_dim=CFG.hidden_dim,
        n_layers=CFG.n_layers, n_heads=CFG.n_heads, n_kv_heads=CFG.n_kv_heads,
        vocab_size=CFG.vocab_size, seq_len=CFG.seq_len,
        weights_float_type=blocks.Q40,
    )
    rng = np.random.default_rng(9)
    path = str(tmp_path / "stream_q40.m")
    write_model(
        path, spec,
        {e.name: 0.05 * rng.standard_normal(e.d * e.n).astype(np.float32)
         for e in tensor_plan(spec)},
    )
    mesh = tp_mesh(8)
    with WeightFileReader(path) as reader:
        sharded = llama.quant_params_from_reader(reader, CFG, "q40", mesh=mesh)
    with WeightFileReader(path) as reader:
        host = llama.quant_params_from_reader(reader, CFG, "q40")

    wq = sharded["layers"]["wq"]
    # packed plane sharded on its output axis: a single device holds 1/8
    assert wq.w.sharding.spec[-1] == "tp"
    local = wq.w.addressable_shards[0].data.shape
    assert local[-1] == CFG.dim // 8

    e_tp = Engine(CFG, sharded, SamplerConfig(temperature=0.0), mesh=mesh)
    t_tp, _, _ = e_tp.generate_fused([3, 7, 11], steps=6)
    e_host = Engine(CFG, host, SamplerConfig(temperature=0.0))
    t_host, _, _ = e_host.generate_fused([3, 7, 11], steps=6)
    assert t_tp == t_host


def test_lane_alignment_padding_preserves_logits():
    """Misaligned hidden/vocab dims (320, 384) get lane-padded for tp — the
    padded columns/rows carry zero scales, so the distributed logits still
    equal the unpadded single-device ones exactly."""
    cfg = ModelConfig(
        arch="llama", dim=256, hidden_dim=320, n_layers=2, n_heads=8, n_kv_heads=8,
        vocab_size=384, seq_len=64, head_size=32, kv_dim=256, dtype="float32",
    )
    qp = llama.quantize_params(llama.random_params(cfg, seed=5, dtype=np.float32), "q40")
    mesh = tp_mesh(8)
    sharded = quant_tp.shard_quant_params(qp, mesh, cfg)

    # w1 output and w2 packed input pad to the same lcm(512, 128*8) width...
    target = quant_tp.ffn_padded_width(cfg, "q40", 8)
    assert target % (128 * 8) == 0 and target % 512 == 0
    assert sharded["layers"]["w1"].w.shape[-1] == target
    assert sharded["layers"]["w2"].k_padded == target
    # ...and every local lane count is 128-aligned
    for name in ("w1", "w3", "wcls"):
        leaf = sharded["layers"][name] if name != "wcls" else sharded["wcls"]
        local = leaf.w.addressable_shards[0].data.shape[-1]
        assert local % 128 == 0, (name, local)

    e_tp = Engine(cfg, sharded, SamplerConfig(temperature=0.0), mesh=mesh)
    t_tp, _, _ = e_tp.generate_fused([3, 5], steps=6)
    e_host = Engine(cfg, qp, SamplerConfig(temperature=0.0))
    t_host, _, _ = e_host.generate_fused([3, 5], steps=6)
    assert t_tp == t_host


@pytest.mark.parametrize("tp", [2, 4, 8])
@pytest.mark.parametrize("hidden", [5632, 11008, 13824, 14336])
def test_real_model_ffn_lanes_align(tp, hidden):
    """For every published model's hidden dim and tp degree, the padded FFN
    width must make the local shard 128-lane aligned AND stay a valid packed
    K for the quant kernels — the (deeper) twin of the round-2 K-axis bug."""
    cfg = ModelConfig(
        arch="llama", dim=4096, hidden_dim=hidden, n_layers=1, n_heads=32,
        n_kv_heads=32, vocab_size=32000, seq_len=64, head_size=128,
        kv_dim=4096, dtype="float32",
    )
    for kind in ("q40", "q80"):
        w = quant_tp.ffn_padded_width(cfg, kind, tp)
        assert w % tp == 0 and (w // tp) % 128 == 0
        from dllama_tpu.ops.qmatmul import K_MULTIPLE
        assert w % K_MULTIPLE[kind] == 0
        assert w - hidden < K_MULTIPLE[kind] + 128 * tp  # padding stays small


def test_compressed_gathers_close_to_plain():
    """Q80-style int8 activation gathers (the reference's wire compression,
    `/root/reference/src/tasks.cpp:124-163`) must stay within block-quant
    error of the uncompressed TP forward."""
    qp = _quant_params("q40")
    rope = llama.rope_tables(CFG)
    tokens = jnp.asarray([5], jnp.int32)
    mesh = tp_mesh(8)
    sharded = quant_tp.shard_quant_params(qp, mesh, CFG)

    plain_fwd = quant_tp.make_tp_forward(CFG, mesh, sharded)
    comp_fwd = quant_tp.make_tp_forward(CFG, mesh, sharded, compress=True)
    plain, _ = jax.jit(plain_fwd)(sharded, rope, llama.init_cache(CFG), tokens, jnp.int32(0))
    comp, _ = jax.jit(comp_fwd)(sharded, rope, llama.init_cache(CFG), tokens, jnp.int32(0))

    plain, comp = np.asarray(plain), np.asarray(comp)
    assert not np.array_equal(plain, comp)  # compression actually engaged
    # int8 block quantization of activations: ~0.4% per hop, a few hops/layer
    scale = np.abs(plain).max()
    np.testing.assert_allclose(comp, plain, atol=0.05 * scale)
    corr = np.corrcoef(plain.reshape(-1), comp.reshape(-1))[0, 1]
    assert corr > 0.999, corr


def test_compressed_engine_decodes():
    qp = _quant_params("q40")
    eng = Engine(CFG, qp, SamplerConfig(temperature=0.0), mesh=tp_mesh(8),
                 tp_compress=True)
    toks, _, _ = eng.generate_fused([3, 7, 11], steps=6)
    assert len(toks) == 6 and all(0 <= t < CFG.vocab_size for t in toks)


def test_wire_stats_analytic_bytes():
    """TokenStats S/R: the analytic per-token ICI byte count matches the
    collective schedule — 4 all-gathers per layer (3*dim + padded hidden)
    plus the logits gather, each moving (tp-1)/tp per device (the reference's
    socket counters, surfaced at dllama.cpp:74-75)."""
    qp = _quant_params("q40")
    mesh = tp_mesh(8)
    eng = Engine(CFG, qp, SamplerConfig(temperature=0.0), mesh=mesh)
    hidden = quant_tp.ffn_padded_width(CFG, "q40", 8)
    layer_feats = CFG.n_layers * (3 * CFG.dim + hidden)
    # activations move in cfg dtype (CFG is float32 -> 4 B/feature); the
    # logits gather moves the lane-PADDED vocab (512 -> 1024 at tp=8) in f32
    # (forward casts before gathering) — exactly what the shard_map ships
    vocab_bytes = ((CFG.vocab_size + 1023) // 1024) * 1024 * 4.0
    want_kb = (layer_feats * 4.0 + vocab_bytes) * (7 / 8) / 1024.0
    assert abs(eng.wire_kb_per_token - want_kb) < 1e-9
    stats = [s for _, s in eng.generate([1, 2], steps=2)]
    assert stats[-1].sent_kb == stats[-1].recv_kb == eng.wire_kb_per_token
    # prefill row: bucket x per-token bytes
    assert stats[0].sent_kb == eng.wire_kb_per_token * 8  # bucket(2) == 8

    # q80 wire compression: 1.125 B/feature on the per-layer gathers only
    # (the logits gather stays plain f32)
    engc = Engine(CFG, qp, SamplerConfig(temperature=0.0), mesh=mesh,
                  tp_compress=True)
    want_c = (layer_feats * 1.125 + vocab_bytes) * (7 / 8) / 1024.0
    assert abs(engc.wire_kb_per_token - want_c) < 1e-9

    # no mesh -> no wire traffic
    assert Engine(CFG, qp, SamplerConfig(temperature=0.0)).wire_kb_per_token == 0.0


def test_spec_decode_under_tp_matches_single_device():
    """generate_spec rides the same shard_map forward: the speculative
    greedy stream on an 8-device quant-TP mesh must equal the single-device
    one (and plain generate's)."""
    qp = _quant_params("q40")
    single = Engine(CFG, qp, SamplerConfig(temperature=0.0))
    want = [t for t, _ in single.generate([1, 2, 3], steps=16)]
    tp_eng = Engine(CFG, qp, SamplerConfig(temperature=0.0), mesh=tp_mesh(8))
    got = [t for t, _ in tp_eng.generate_spec([1, 2, 3], steps=16)]
    assert got == want


# distinct sizes (dim=256, hidden' in {512,1024}, padded vocab=2048) so every
# collective in the compiled HLO is attributable by payload size alone
CFG_AUDIT = ModelConfig(
    arch="llama", dim=256, hidden_dim=512, n_layers=2, n_heads=8, n_kv_heads=8,
    vocab_size=2048, seq_len=64, head_size=32, kv_dim=256, dtype="float32",
)


def _collectives(txt):
    """[(numel, dtype, op)] for every collective in compiled HLO text."""
    import re

    ops = re.findall(
        r"=\s+(\w+)\[([^\]]*)\][^\n]*?\b"
        r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)\(",
        txt,
    )
    out = []
    for dtype, dims, op in ops:
        ns = [int(d) for d in dims.split(",") if d.strip().isdigit()]
        out.append((int(np.prod(ns)) if ns else 1, dtype, op))
    return out


def _decode_step_hlo(eng):
    """Compiled HLO text of one engine decode step (T=1, greedy params)."""
    cache = eng.new_cache()
    return eng._decode_step.func.lower(
        eng.params, eng.rope, cache, jnp.asarray(3, jnp.int32), jnp.int32(0),
        jax.random.PRNGKey(0), jnp.float32(0.0), jnp.float32(0.9),
        jnp.bool_(False),  # poison: the fault seam's flag, off
    ).compile().as_text()


def _padded_vocab(cfg, tp):
    from dllama_tpu.ops.qmatmul import _pad_up

    return _pad_up(cfg.vocab_size, 128 * tp)


@pytest.mark.parametrize("tp", [2, 8])
def test_quant_tp_wire_exact_claim_matches_compiled_hlo(tp):
    """The quant-TP (shard_map) path reports its wire stats as EXACT
    (Engine.wire_stats_exact). Audit the claim against the COMPILED decode
    step at tp in {2, 8}: the layer scan body (appearing once, executing
    n_layers times) must contain exactly the 4 all-gathers _wire_bytes
    prices — 3 dim-payload (attention heads, wo out, w2 out) + 1 padded-
    hidden-payload (FFN up) — plus the one padded-vocab f32 logits gather,
    and NO other activation-scale collective. Payload bytes recomputed from
    the HLO must equal _wire_bytes(1) to the byte."""
    qp = _quant_params("q40")
    mesh = tp_mesh(tp)
    eng = Engine(CFG_AUDIT, qp, SamplerConfig(temperature=0.0), mesh=mesh)
    assert eng.wire_stats_exact
    txt = _decode_step_hlo(eng)

    cfg = CFG_AUDIT
    hidden = quant_tp.ffn_padded_width(cfg, "q40", tp)
    vocab_padded = _padded_vocab(cfg, tp)
    big = [c for c in _collectives(txt) if c[0] >= cfg.dim]
    # every big collective is an all-gather (no psum partials by design)
    assert all(op == "all-gather" for _, _, op in big), big
    by_size: dict = {}
    for n, dt, _ in big:
        by_size.setdefault(n, []).append(dt)
    assert sorted(by_size) == sorted({cfg.dim, hidden, vocab_padded} - {0}), by_size
    assert len(by_size[cfg.dim]) == 3, by_size
    assert len(by_size[hidden]) == 1, by_size
    assert by_size[vocab_padded] == ["f32"], by_size

    # reprice from the HLO and compare to the byte (f32 activations = 4 B)
    frac = (tp - 1) / tp
    hlo_bytes = (cfg.n_layers * (3 * cfg.dim + hidden) * 4.0
                 + vocab_padded * 4.0) * frac
    assert hlo_bytes == eng._wire_bytes(1)


@pytest.mark.parametrize("tp", [8])
def test_quant_tp_compressed_wire_matches_compiled_hlo(tp):
    """Same audit for q80 wire compression: the per-layer gathers become
    int8 payloads of features*1.125 bytes (quants + bitcast f32 block
    scales in ONE collective); the logits gather stays plain f32."""
    qp = _quant_params("q40")
    eng = Engine(CFG_AUDIT, qp, SamplerConfig(temperature=0.0),
                 mesh=tp_mesh(tp), tp_compress=True)
    txt = _decode_step_hlo(eng)

    cfg = CFG_AUDIT
    hidden = quant_tp.ffn_padded_width(cfg, "q40", tp)
    vocab_padded = _padded_vocab(cfg, tp)
    big = [c for c in _collectives(txt) if c[0] >= cfg.dim]
    assert all(op == "all-gather" for _, _, op in big), big
    s8 = sorted(n for n, dt, _ in big if dt == "s8")
    want_s8 = sorted([int(cfg.dim * 1.125)] * 3 + [int(hidden * 1.125)])
    assert s8 == want_s8, (s8, want_s8)
    f32 = [n for n, dt, _ in big if dt == "f32"]
    assert f32 == [vocab_padded], big

    frac = (tp - 1) / tp
    hlo_bytes = (sum(want_s8) * cfg.n_layers + vocab_padded * 4.0) * frac
    assert hlo_bytes == eng._wire_bytes(1)


def test_batched_spec_under_quant_tp_matches_single_device():
    """generate_batch_spec on an 8-device quant-TP mesh (the shard_map
    verify wrapper) must emit exactly the single-device rows — batching x
    speculation x tensor parallelism composed, sharding-invariant."""
    qp = _quant_params("q40")
    prompts = [[5, 9, 3, 5, 9, 3, 5, 9], [7, 7, 7, 7], [4, 2]]
    single = Engine(CFG, qp, SamplerConfig(temperature=0.0))
    want, stats_s = single.generate_batch_spec(prompts, steps=10, draft_len=4)
    tp_eng = Engine(CFG, qp, SamplerConfig(temperature=0.0), mesh=tp_mesh(8))
    assert tp_eng.supports_batch_spec
    got, stats_tp = tp_eng.generate_batch_spec(prompts, steps=10, draft_len=4)
    assert got == want
    assert stats_tp["emitted"] == stats_s["emitted"]
