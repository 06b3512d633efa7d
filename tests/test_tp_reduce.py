"""Row-parallel reduce direction for TP decode (--tp-reduce).

Two layers of contract, tested separately:

* The COLLECTIVE (`collectives.reduce_scatter_columns` / `reduce_columns`):
  the plain ring must be BITWISE identical to a numpy simulation of the
  pinned summation schedule (device i ends owning chunk i summed in ring
  order p[i+1], ..., p[i]) at every tp degree and dtype — determinism is
  the whole point of pinning the order; at tp=2 the two-term sum is
  order-free so the ring must also match `jax.lax.psum` bitwise.  The q80
  ring's per-element error must stay within the ANALYTIC bound: each hop
  quantizes its payload to 32-value int8 blocks (scale = absmax/127), so
  rounding contributes at most scale/2 = absmax/254 per hop, and the
  bound is the sum over hops of that hop's actual block scale/2 —
  computed here by an exact numpy re-simulation of the quantized ring.

* The ENGINE (Engine(tp_reduce=...)): row-parallel wo/w2 + fused
  norm+reduce epilogue must emit the gather-only engine's greedy streams
  (plain mode — deterministic; q80 within quantization noise but pinned),
  across decode, the pooled session, and speculative verify, composing
  with --tp-overlap; requested-but-impossible combinations (no mesh,
  dense pjit, MoE, shard-granularity misfit) must warn-and-drop with the
  machine-visible `tp_reduce`/`tp_reduce_active`/`tp_reduce_reason`
  /stats fields; the `tp_reduce` fault seam and the
  `dllama_tp_reduce_chunks_total` counter must fire per dispatch; and the
  analytic wire model must report strictly fewer bytes per decode step
  than the gather-only schedule.

Engines compile a full layer-scan program set per (tp, mode) point, so
the module caches them (same pattern as test_tp_overlap).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P
from dllama_tpu import faults, observability
from dllama_tpu.models import llama
from dllama_tpu.models.config import ModelConfig
from dllama_tpu.parallel import collectives, quant_tp
from dllama_tpu.parallel.mesh import TP, tp_mesh
from dllama_tpu.runtime.generate import Engine
from dllama_tpu.runtime.sampler import SamplerConfig

CFG = ModelConfig(
    arch="llama", dim=128, hidden_dim=256, n_layers=2, n_heads=4,
    n_kv_heads=4, vocab_size=256, seq_len=64, head_size=32, kv_dim=128,
    dtype="float32",
)

MIXTRAL = ModelConfig(
    arch="mixtral", dim=128, hidden_dim=256, n_layers=2, n_heads=4,
    n_kv_heads=4, vocab_size=256, seq_len=64, head_size=32, kv_dim=128,
    n_experts=4, n_active_experts=2, rope_style="half", dtype="float32",
)

GREEDY = SamplerConfig(temperature=0.0, seed=7)
PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]

_ENGINES = {}


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture(scope="module")
def qp40():
    dense = llama.random_params(CFG, seed=0, dtype=np.float32)
    return llama.quantize_params(dense, "q40")


@pytest.fixture(scope="module")
def qp80():
    dense = llama.random_params(CFG, seed=0, dtype=np.float32)
    return llama.quantize_params(dense, "q80")


# ---------------------------------------------------------------------------
# collective level: pinned-order ring, q80 analytic bound, guards
# ---------------------------------------------------------------------------


def _run_reduce_scatter(x, tp, compress):
    """x [tp, rows, f] per-device partials -> [tp, rows, f//tp] chunks."""
    mesh = tp_mesh(tp)

    @jax.jit
    def run(x):
        return shard_map(
            lambda p: collectives.reduce_scatter_columns(p[0], TP, compress)[None],
            mesh=mesh, in_specs=P(TP), out_specs=P(TP), check_vma=False,
        )(x)

    return np.asarray(run(x))


def _np_ring_plain(parts):
    """Numpy replica of the pinned schedule: parts [tp, rows, f] f32 ->
    [tp, rows, f//tp], device i's chunk summed in order p[i+1], ..., p[i]."""
    tp, rows, f = parts.shape
    c = f // tp
    out = np.empty((tp, rows, c), np.float32)
    for i in range(tp):
        # hop h adds device (i - h) mod tp's copy; the seed (h = tp-1 ago)
        # came from device (i+1) mod tp, so the order is p[i+1], ..., p[i]
        acc = parts[(i + 1) % tp, :, i * c:(i + 1) * c].astype(np.float32)
        for j in range(2, tp + 1):
            acc = acc + parts[(i + j) % tp, :, i * c:(i + 1) * c]
        out[i] = acc
    return out


def _np_q80(x):
    """Exact numpy twin of the wire codec: returns (dequantized, scale/2
    per element) for one hop's payload."""
    rows, f = x.shape
    xb = x.reshape(rows, f // 32, 32).astype(np.float32)
    absmax = np.abs(xb).max(axis=-1, keepdims=True)
    scale = absmax / 127.0
    safe = np.where(scale == 0.0, 1.0, scale)
    deq = np.round(xb / safe).astype(np.int8).astype(np.float32) * scale
    halfs = np.broadcast_to(scale / 2.0, xb.shape)
    return deq.reshape(rows, f), halfs.reshape(rows, f)


def _np_ring_q80(parts):
    """Numpy simulation of the QUANTIZED ring: returns (result, analytic
    per-element error bound = sum over hops of that hop's scale/2)."""
    tp, rows, f = parts.shape
    c = f // tp
    out = np.empty((tp, rows, c), np.float32)
    bound = np.zeros((tp, rows, c), np.float32)
    # device-parallel simulation: acc[i] lives on device i and moves i->i+1
    acc = np.stack([
        parts[i, :, ((i + tp - 1) % tp) * c:((i + tp - 1) % tp + 1) * c]
        for i in range(tp)
    ]).astype(np.float32)
    err = np.zeros_like(acc)
    for hop in range(1, tp):
        deq = np.empty_like(acc)
        halfs = np.empty_like(acc)
        for i in range(tp):
            deq[i], halfs[i] = _np_q80(acc[i])
        err = np.roll(err + halfs, 1, axis=0)  # bound travels with the wire
        acc = np.roll(deq, 1, axis=0)          # ppermute i -> i+1
        for i in range(tp):
            k = (i + tp - 1 - hop) % tp
            acc[i] = acc[i] + parts[i, :, k * c:(k + 1) * c]
    for i in range(tp):
        out[i], bound[i] = acc[i], err[i]
    return out, bound


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("tp", [2, 4, 8])
def test_plain_ring_matches_pinned_order_bitwise(tp, dtype):
    """compress=False == the pinned-order schedule BITWISE, every tp/dtype
    (the collective always accumulates in f32, whatever the partial dtype)."""
    rng = np.random.default_rng(tp)
    parts = rng.standard_normal((tp, 3, 64 * tp)).astype(np.float32)
    x = jnp.asarray(parts).astype(dtype)
    got = _run_reduce_scatter(x, tp, compress=False)
    want = _np_ring_plain(np.asarray(jnp.asarray(x).astype(jnp.float32)))
    assert got.dtype == np.float32
    assert np.array_equal(got, want)


@pytest.mark.parametrize("tp", [2, 4, 8])
def test_plain_ring_vs_psum(tp):
    """tp=2: two-term sums are order-free, so ring == psum bitwise.  tp>2:
    psum's summation order is implementation-defined, so only allclose —
    the ring's value is that ITS order is pinned (bit-reproducible)."""
    rng = np.random.default_rng(100 + tp)
    parts = rng.standard_normal((tp, 3, 32 * tp)).astype(np.float32)
    mesh = tp_mesh(tp)

    @jax.jit
    def via_psum(x):
        return shard_map(
            lambda p: jax.lax.psum(p[0], TP)[None],
            mesh=mesh, in_specs=P(TP), out_specs=P(TP), check_vma=False,
        )(x)

    ring = _run_reduce_scatter(jnp.asarray(parts), tp, compress=False)
    full = np.asarray(via_psum(jnp.asarray(parts)))
    c = parts.shape[-1] // tp
    scat = np.stack([full[i, :, i * c:(i + 1) * c] for i in range(tp)])
    if tp == 2:
        assert np.array_equal(ring, scat)
    else:
        np.testing.assert_allclose(ring, scat, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("tp", [2, 4, 8])
def test_q80_ring_within_analytic_bound(tp, dtype):
    """compress=True: per-element |q80 - exact ring| <= sum over hops of
    that hop's block scale/2 (absmax/254), verified against an exact numpy
    re-simulation of the quantized schedule; and the q80 result matches
    the simulation bitwise (same codec, same order)."""
    rng = np.random.default_rng(200 + tp)
    parts = (rng.standard_normal((tp, 5, 64 * tp)) *
             rng.uniform(0.1, 8.0, (tp, 5, 1))).astype(np.float32)
    x = jnp.asarray(parts).astype(dtype)
    xf = np.asarray(jnp.asarray(x).astype(jnp.float32))
    got = _run_reduce_scatter(x, tp, compress=True)
    sim, bound = _np_ring_q80(xf)
    exact = _np_ring_plain(xf)
    # the codec round-trips bit-exactly, but XLA may contract the decode
    # multiply + accumulate into an FMA: the device's f32 quotient can sit
    # 1 ULP from the simulation's, which (rarely, mostly for the coarse
    # bf16 grid) flips an int8 round at a .5 boundary.  Both choices of a
    # boundary round are ~scale/2 from the true value, so the analytic
    # bound survives with ULP + small multiplicative slack; the sim must
    # still agree to within one quant step per hop (2x the bound), with
    # flips rare.
    ulp = np.spacing(np.abs(exact).max(), dtype=np.float32) * (tp + 1)
    assert np.all(np.abs(got - sim) <= 2.0 * bound + ulp), \
        "device ring drifted beyond round-flip noise from the simulation"
    assert np.mean(np.abs(got - sim) > ulp) < 0.01, \
        "device ring disagrees with the codec simulation too often"
    assert np.all(np.abs(got - exact) <= 1.05 * bound + ulp), (
        f"q80 ring error exceeds the analytic bound at tp={tp}")
    assert bound.max() > 0  # the bound is real, not vacuously zero


def test_reduce_columns_full_width():
    """reduce_columns = reduce_scatter + all-gather: full-width psum-close
    result, replicated across the axis."""
    tp = 4
    rng = np.random.default_rng(7)
    parts = rng.standard_normal((tp, 3, 128)).astype(np.float32)
    mesh = tp_mesh(tp)

    @jax.jit
    def run(x):
        return shard_map(
            lambda p: collectives.reduce_columns(p[0], TP)[None],
            mesh=mesh, in_specs=P(TP), out_specs=P(TP), check_vma=False,
        )(x)

    got = np.asarray(run(jnp.asarray(parts)))
    want = parts.sum(axis=0)
    for i in range(tp):
        np.testing.assert_allclose(got[i], want, rtol=1e-5, atol=1e-5)


def test_scatter_roundtrip_and_rms_inv():
    """On a replicated residual, scatter_features is the exact local slice
    (gather o scatter == identity) and rms_inv_scattered matches the
    full-width rmsnorm scale to f32 precision."""
    tp = 4
    rng = np.random.default_rng(9)
    x0 = rng.standard_normal((3, 128)).astype(np.float32)
    x = np.broadcast_to(x0, (tp, 3, 128)).copy()
    mesh = tp_mesh(tp)

    def inner(p):
        s = collectives.scatter_features(p[0], TP)
        back = collectives.gather_columns(s, TP)
        inv = collectives.rms_inv_scattered(s, TP, 128, 1e-5)
        return back[None], inv[None]

    run = jax.jit(shard_map(inner, mesh=mesh, in_specs=P(TP),
                            out_specs=(P(TP), P(TP)), check_vma=False))
    back, inv = run(jnp.asarray(x))
    assert np.array_equal(np.asarray(back), x)
    want = 1.0 / np.sqrt((x0.astype(np.float64) ** 2).mean(-1) + 1e-5)
    for i in range(tp):
        np.testing.assert_allclose(np.asarray(inv)[i], want, rtol=1e-6)


def test_q80_block_guards():
    """The 32-value-block guard names the offending dim in BOTH directions
    (the gather_columns path used to silently mis-reshape)."""
    tp = 2
    mesh = tp_mesh(tp)
    x = jnp.ones((tp, 2, 48), jnp.float32)  # 48 % 32 != 0

    @jax.jit
    def bad_gather(x):
        return shard_map(
            lambda p: collectives.gather_columns(p[0], TP, compress=True)[None],
            mesh=mesh, in_specs=P(TP), out_specs=P(TP), check_vma=False,
        )(x)

    with pytest.raises(ValueError, match=r"gather_columns.*48.*32-value"):
        bad_gather(x)

    y = jnp.ones((tp, 2, 96), jnp.float32)  # chunks of 48: guard on c

    @jax.jit
    def bad_reduce(y):
        return shard_map(
            lambda p: collectives.reduce_scatter_columns(
                p[0], TP, compress=True)[None],
            mesh=mesh, in_specs=P(TP), out_specs=P(TP), check_vma=False,
        )(y)

    with pytest.raises(ValueError, match=r"reduce_scatter_columns.*48"):
        bad_reduce(y)

    with pytest.raises(ValueError, match="not divisible"):
        _run_reduce_scatter(jnp.ones((2, 2, 63), jnp.float32), 2, False)


# ---------------------------------------------------------------------------
# engine level: stream equality, composition, resolution, seam, wire model
# ---------------------------------------------------------------------------


def _engines(qp, kind, tp, mode, overlap=False):
    """Cached (gather-only engine, row-mode engine, row registry) on one
    mesh + params; tests share and never mutate (counters only count up)."""
    key = (kind, tp, mode, overlap)
    if key not in _ENGINES:
        mesh = tp_mesh(tp)
        reg = observability.MetricsRegistry()
        e0 = Engine(CFG, qp, GREEDY, mesh=mesh, metrics=None,
                    tp_overlap=overlap)
        e1 = Engine(CFG, qp, GREEDY, mesh=mesh, metrics=reg,
                    tp_overlap=overlap, tp_reduce=mode)
        _ENGINES[key] = (e0, e1, reg)
    return _ENGINES[key]


def _counter(reg, name="dllama_tp_reduce_chunks_total"):
    for line in reg.render().splitlines():
        if line.startswith(name):
            return float(line.split()[-1])
    return 0.0


_POINTS = [("q40", 2, "plain"), ("q40", 2, "q80"),
           ("q80", 4, "plain"), ("q80", 4, "q80")]


@pytest.mark.parametrize("kind,tp,mode", _POINTS,
                         ids=[f"{k}-tp{t}-{m}" for k, t, m in _POINTS])
def test_row_decode_matches_gather_only(qp40, qp80, kind, tp, mode):
    """Plain row-parallel decode emits the gather-only engine's EXACT
    greedy streams (the pinned-order ring reassociates the sum but the
    logits stay bitwise equal at these shapes).  q80 rounds each hop's
    payload, so a near-tie greedy token may legitimately flip — there the
    contract is pinned DETERMINISM (identical streams run-to-run) plus
    engagement, with the error magnitude asserted analytically at the
    collective level."""
    qp = qp40 if kind == "q40" else qp80
    e0, e1, reg = _engines(qp, kind, tp, mode)
    assert e1.tp_reduce_active and e1.tp_reduce_reason == "on"
    assert e1.tp_reduce == mode
    before = _counter(reg)
    got = e1.generate_batch(PROMPTS, steps=8)
    want = e0.generate_batch(PROMPTS, steps=8)
    if mode == "plain":
        assert got == want
    else:
        assert [len(s) for s in got] == [len(s) for s in want]
        assert got == e1.generate_batch(PROMPTS, steps=8)
    assert _counter(reg) > before  # dispatches were counted


@pytest.mark.parametrize("kind,tp,mode", _POINTS[:2],
                         ids=["q40-tp2-plain", "q40-tp2-q80"])
def test_row_verify_matches_gather_only(qp40, qp80, kind, tp, mode):
    """Speculative verify runs the row-parallel `_layer` — plain
    mode must match the gather-only engine's streams and acceptance
    statistics exactly; q80 must be pinned-deterministic (see decode)."""
    qp = qp40 if kind == "q40" else qp80
    e0, e1, _ = _engines(qp, kind, tp, mode)
    got, s1 = e1.generate_batch_spec(PROMPTS, steps=8, draft_len=3)
    if mode == "plain":
        want, s0 = e0.generate_batch_spec(PROMPTS, steps=8, draft_len=3)
        assert got == want
        assert s1["emitted"] == s0["emitted"]
    else:
        got2, s2 = e1.generate_batch_spec(PROMPTS, steps=8, draft_len=3)
        assert got == got2
        assert s1["emitted"] == s2["emitted"]


def test_row_composes_with_overlap(qp40):
    """--tp-reduce x --tp-overlap: the reduce-scatters are ppermute hops
    already, so the overlap twin must stream identically to the
    non-overlap row engine AND to the gather-only baseline."""
    e0, e1, _ = _engines(qp40, "q40", 2, "plain", overlap=True)
    assert e1.tp_reduce_active and e1.tp_overlap_active
    assert e1.generate_batch(PROMPTS, steps=8) == \
        e0.generate_batch(PROMPTS, steps=8)


def test_row_pooled_session(qp40):
    """The pooled BatchSession (the serving path) dispatches through the
    row-parallel programs — stream equality vs the gather-only session."""
    e0, e1, _ = _engines(qp40, "q40", 2, "plain")

    def stream(eng):
        sess = eng.batch_session(4, chunk=4)
        hs = [sess.admit_begin(p, steps=8) for p in PROMPTS]
        while sess.prefill_step() is not None:
            pass
        got = {h: [] for h in hs}
        while any(not sess.is_done(h) for h in hs):
            for h, toks in sess.step_chunk().items():
                got[h].extend(toks)
        sess.close()
        return [got[h] for h in hs]

    assert stream(e1) == stream(e0)


def test_reduce_fault_seam(qp40):
    """`tp_reduce` fires on every row-mode dispatch: an injected raise
    surfaces as FaultInjected; the engine survives (per-dispatch seam)."""
    _, e1, _ = _engines(qp40, "q40", 2, "plain")
    faults.install("tp_reduce:raise:times=1")
    with pytest.raises(faults.FaultInjected) as exc:
        e1.generate_batch(PROMPTS, steps=4)
    assert exc.value.site == "tp_reduce"
    faults.clear()
    assert e1.generate_batch(PROMPTS, steps=4)


def test_row_wire_model_strictly_below_gather(qp40):
    """The analytic per-token wire model must report strictly fewer bytes
    for the row-parallel schedule — the hidden-width gather (the widest
    collective) is gone; q80 hops shrink the reduce direction further."""
    e0, e1, _ = _engines(qp40, "q40", 2, "q80")
    assert e1.wire_kb(1) < e0.wire_kb(1)
    assert e1.wire_kb(4) < e0.wire_kb(4)


# ---------------------------------------------------------------------------
# warn-and-drop resolution (what /stats and dllama_tp_wire_info report)
# ---------------------------------------------------------------------------


def test_reduce_resolution_not_requested(qp40):
    eng = Engine(CFG, qp40, GREEDY, mesh=tp_mesh(2), metrics=None)
    assert not eng.tp_reduce_active
    assert eng.tp_reduce == "off"
    assert eng.tp_reduce_reason == "not requested"


def test_reduce_resolution_no_mesh(qp40):
    eng = Engine(CFG, qp40, GREEDY, tp_reduce="plain", metrics=None)
    assert not eng.tp_reduce_active
    assert eng.tp_reduce_reason == "no mesh (single device)"


def test_reduce_resolution_bad_mode(qp40):
    with pytest.raises(ValueError, match="tp_reduce"):
        Engine(CFG, qp40, GREEDY, tp_reduce="zstd", metrics=None)


def test_reduce_resolution_granularity_misfit(qp40):
    """q40 at tp=4: wo's per-shard K = 128/4 = 32 splits a 64-row q40
    superblock — must decline with the granularity reason, not crash."""
    eng = Engine(CFG, qp40, GREEDY, mesh=tp_mesh(4), tp_reduce="plain",
                 metrics=None)
    assert not eng.tp_reduce_active
    assert "granularity" in eng.tp_reduce_reason
    # gather-only programs still serve the engine
    assert eng.generate_batch([[1, 2, 3]], steps=2)


def test_reduce_resolution_moe_declines():
    dense = llama.random_params(MIXTRAL, seed=0, dtype=np.float32)
    qmoe = llama.quantize_params(dense, "q40")
    eng = Engine(MIXTRAL, qmoe, GREEDY, mesh=tp_mesh(2), tp_reduce="plain",
                 metrics=None)
    assert not eng.tp_reduce_active
    assert "moe" in eng.tp_reduce_reason


def test_reduce_resolution_dense_pjit_declines():
    dense = llama.random_params(CFG, seed=0, dtype=np.float32)
    eng = Engine(CFG, dense, GREEDY, mesh=tp_mesh(2), tp_reduce="plain",
                 metrics=None)
    assert not eng.tp_reduce_active
    assert "dense-pjit" in eng.tp_reduce_reason


def test_validate_tp_reduce_reasons():
    """The static validator (shared by the CLI streamer and the Engine)
    names the matrix and the granularity in its decline."""
    assert quant_tp.validate_tp_reduce(CFG, "q40", 2) is None
    why = quant_tp.validate_tp_reduce(CFG, "q40", 4)
    assert why is not None and "w" in why and "64" in why
    assert quant_tp.validate_tp_reduce(CFG, "q80", 4) is None
    assert "moe" in quant_tp.validate_tp_reduce(MIXTRAL, "q40", 2)


def test_row_shard_repack_is_idempotent_and_tiled(qp40):
    """row_shard_quant_leaf: per-shard K pads to K_MULTIPLE independently
    (every local shard keeps Mosaic-valid tiling) and a repacked leaf
    passes through unchanged."""
    from dllama_tpu.ops.qmatmul import K_MULTIPLE, _pad_up

    w2 = qp40["layers"]["w2"]
    packed = quant_tp.row_shard_quant_leaf("w2", w2, CFG, 2)
    chunk = quant_tp.row_shard_chunk_k(CFG, "w2", "q40", 2)
    kp_shard = _pad_up(chunk, K_MULTIPLE["q40"])
    assert packed.k_logical == chunk
    assert packed.k_padded == 2 * kp_shard  # each shard padded on its own
    again = quant_tp.row_shard_quant_leaf("w2", packed, CFG, 2)
    assert again is packed
