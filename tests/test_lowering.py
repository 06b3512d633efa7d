"""Static TPU tiling verifier (ops.lowering) — the CPU gate for Mosaic.

An early bench round (round 2; its record is deleted) reached a real TPU
backend and failed inside our own kernel: the q40 scale-plane BlockSpec produced
a (4, 1024) block against the (172, 4096) array — the last two block dims
must each be divisible by the (8, 128) min tile or equal to the array dim.
These tests prove, without a TPU, that every pallas_call in the inventory
satisfies that contract for every real model shape, and that the verifier
still *recognizes* the historical failure when fed the legacy plan.

The verifier is static: it passed the flash-decode and rope+cache kernels
that the v5e compiler refuses. tests/test_chip_compile.py asks the compiler.
"""

import jax.numpy as jnp
import pytest

from dllama_tpu.ops import lowering, qmatmul
from dllama_tpu.ops.lowering import MODEL_DIMS, SWEEP_T, TilingError


# ---------------------------------------------------------------------------
# The pinned round-2 regression case
# ---------------------------------------------------------------------------

def test_pinned_bench_r02_shape_passes_for_every_kernel():
    """Llama-2-7B q40 down-projection (K=11008, O=4096) — the exact shape
    whose scale plane was (172, 4096) on hardware — must pass the verifier
    through the PACKED path (K_MULTIPLE padding) for every kernel variant."""
    for L in (None, 32):
        for fused in (False, True):
            plans = lowering.check("q40", dict(
                T=1, K=11008, O=4096, L=L, nosub=True, fused_norm=fused))
            assert plans, "check returned no plans"
            for p in plans:
                assert not p.violations()


def test_pinned_bench_r02_legacy_plan_is_flagged():
    """Feeding the UNpadded K (k_padded=11008, the pre-K_MULTIPLE packing)
    must reproduce the historical violation signature: bk=256 gives a
    (4, 1024) scale block against the (172, 4096) plane."""
    with pytest.raises(TilingError) as ei:
        lowering.check("q40", dict(T=1, K=11008, O=4096, k_padded=11008))
    msg = str(ei.value)
    assert "(4, 1024)" in msg and "(172, 4096)" in msg, msg


def test_verifier_catches_raw_sublane_violation():
    """Direct OperandPlan check: a 4-row f32 block in an 8-sublane world."""
    op = lowering.OperandPlan("s", (172, 4096), (4, 1024), "float32")
    v = op.violations()
    assert len(v) == 1 and "sublane" in v[0]


def test_verifier_dtype_aware_sublane():
    """Sublane minimum widens with narrower dtypes: 8 rows is fine for f32,
    a violation for bf16 (16) and int8 (32) unless equal to the dim."""
    assert not lowering.OperandPlan("x", (64, 256), (8, 128), "float32").violations()
    assert lowering.OperandPlan("x", (64, 256), (8, 128), "bfloat16").violations()
    assert lowering.OperandPlan("x", (64, 256), (16, 128), "bfloat16").violations() == []
    assert lowering.OperandPlan("x", (64, 256), (16, 128), "int8").violations()
    # equal-to-dim escape: whole-array blocks lower at any size
    assert not lowering.OperandPlan("x", (4, 100), (4, 100), "int8").violations()


def test_verifier_checks_lane_dim():
    op = lowering.OperandPlan("x", (64, 384), (8, 192), "float32")
    v = op.violations()
    assert len(v) == 1 and "lane" in v[0]


# ---------------------------------------------------------------------------
# The full CPU sweep: 7B/8B/MoE x q40/q80 x T in {1,8,64} (+ flash, + rope)
# ---------------------------------------------------------------------------

def test_full_sweep_zero_violations():
    report = lowering.sweep()
    bad = {case: [v for p in plans for v in p["violations"]]
           for case, plans in report.items()
           if any(p["violations"] for p in plans)}
    assert not bad, bad
    # the matrix really covers what it claims
    assert len(report) > 400
    for name, *_ in MODEL_DIMS:
        for kind in ("q40", "q80"):
            for T in SWEEP_T:
                assert f"{name}/{kind}/down/T{T}" in report
    assert "llama2_7b/flash/T1/float8_e4m3fn" in report
    assert "llama2_7b/rope_cache/B8/T9/float8_e4m3fn" in report


@pytest.mark.parametrize("kind", ["q40", "q80"])
@pytest.mark.parametrize("T", SWEEP_T)
def test_plan_matches_real_tile_plan(kind, T):
    """The verifier must derive blocks from the SAME tile_plan the launchers
    call — if the planner and the plan drift, the gate is meaningless."""
    K, O = 4096, 11008
    kp = qmatmul._pad_up(K, qmatmul.K_MULTIPLE[kind])
    bk, bo = qmatmul.tile_plan(kind, kp, O)
    (plan,) = lowering.lowering_plan(kind, dict(T=T, K=K, O=O, nosub=False))
    note = plan.note
    assert f"bk={bk}" in note and f"bo={bo}" in note
    x = plan.operands[0]
    assert x.block[-1] == (bk // 2 if kind == "q40" else bk)


def test_flash_plans_cover_f8_cache():
    """The standing "hardware-validate f8" item, lowerability half: the f8
    cache dtype must pass the verifier at every swept flash shape (1-byte
    itemsize -> 32-sublane minimum, satisfied by whole-dim cache blocks and
    the BLOCK_S=256 VMEM scratch)."""
    for T in (1, 8):
        plans = lowering.check("flash_decode", dict(
            T=T, L=32, S=4096, n_heads=32, n_kv_heads=8, head_size=128,
            cache_dtype="float8_e4m3fn"))
        names = {o.name for p in plans for o in p.operands}
        assert "k_buf[scratch]" in names


def test_rope_cache_plans_all_wrappers():
    """Solo (B=1), batched (T=1) and verify (B x T) wrappers all plan
    clean, for every cache dtype the caches support."""
    for dt in ("bfloat16", "float32", "float8_e4m3fn"):
        for B, T, name in ((1, 4, "rope_cache_update"),
                           (8, 1, "rope_cache_update_batched"),
                           (8, 9, "rope_cache_update_verify")):
            (plan,) = lowering.check("rope_cache", dict(
                T=T, B=B, L=32, S=2048, n_kv_heads=8, head_size=128,
                cache_dtype=dt, batched=B > 1))
            assert plan.kernel == name
            assert plan.grid == (B,)


def test_main_json_report(capsys):
    """The CI artifact: --json emits a machine-readable report with case
    count and violation count."""
    import json

    rc = lowering.main(["--json"])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert report["n_violations"] == 0
    assert report["n_cases"] == len(report["cases"]) > 400


def test_tile_cell_cap_respected_across_sweep():
    """No planned compute block may exceed the VMEM cell cap the tile
    planner enforces — guards against a future tile_plan edit raising
    blocks past what fits."""
    for kind in ("q40", "q80"):
        for _, dim, hidden, *_ in MODEL_DIMS:
            for K, O in ((dim, hidden), (hidden, dim)):
                kp = qmatmul._pad_up(K, qmatmul.K_MULTIPLE[kind])
                bk, bo = qmatmul.tile_plan(kind, kp, O)
                assert bk * bo <= qmatmul._TILE_CELL_CAP


def test_unknown_kind_raises():
    with pytest.raises(ValueError):
        lowering.lowering_plan("conv2d", dict(K=1, O=1))
