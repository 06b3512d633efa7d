"""Bit-identity tests for the fused decode epilogues.

Two fusions, both flag-gated and both required to be *bit-identical* to
the unfused composition they replace (not just close — identical, so the
flags can be flipped on a live deployment without changing any sampled
token):

- DLLAMA_FUSE_NORM: rmsnorm folded into the q40/q80 projection kernels
  (qmatmul.qmatmul_norm vs rmsnorm + qmatmul).
- DLLAMA_FUSE_ROPE_CACHE: rope rotation + KV cache write in one kernel
  (fused_rope_cache.* vs apply_rope + dynamic_update_slice / scatter).

One numerical subtlety, pinned by these tests: for float32 activations
the unfused REFERENCE must be jitted, because XLA's jit contracts
``x0*c - x1*s`` into an FMA and the fused kernel matches that contracted
form. Production always runs jitted, so jit-vs-jit is the real contract;
the eager composition differs by ~1 ulp and is NOT the oracle.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.models import llama
from dllama_tpu.ops import flash_decode, fused_rope_cache, qmatmul, rope
from dllama_tpu.ops.norms import rmsnorm
from tests.test_llama_forward import tiny_cfg

EPS = 1e-5


def _rand(shape, seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32) * scale)


# ---------------------------------------------------------------------------
# Fused rmsnorm -> quantized projection
# ---------------------------------------------------------------------------

#: (kind, K, O, T, activation dtype) where the q40 kernel's default form
#: (``Q40_NOSUB``: the nibbles' recentering is subtracted outside the kernel,
#: ``8 * blocksums(xn) @ scales``) is NOT bitwise the unfused composition on
#: the CPU: the 32-value block sums are XLA's, and fused it sums the
#: normalized activation inside the sum's own loop, in another order than
#: over an operand in memory. Here one block sum of 3 x 44 (row 1, block 22)
#: lands a last place apart, which moves 161 of the row's 1,376 outputs by
#: at most 2 last places of the row's largest; held to 4.
_SUM_ORDER_CASES = {("q40", 1408, 1376, 3, "float32")}


@pytest.mark.parametrize("kind", ["q40", "q80"])
@pytest.mark.parametrize("K,O", [(256, 384), (192, 128), (1408, 1376)])
@pytest.mark.parametrize("T", [1, 3])
@pytest.mark.parametrize("xdt", [jnp.float32, jnp.bfloat16])
def test_fused_norm_bit_identity(kind, K, O, T, xdt):
    """Flat-weight launcher, padded and ragged (TP-shard) K/O, both
    activation dtypes: fused epilogue == rmsnorm-then-qmatmul, bitwise, but
    for ``_SUM_ORDER_CASES``."""
    x = _rand((T, K), seed=K + O + T).astype(xdt)
    nw = _rand((K,), seed=1, scale=0.5) + 1.0
    qt = qmatmul.quantize_tensor(np.asarray(_rand((K, O), seed=2, scale=0.1)), kind)
    xn = rmsnorm(x, nw, EPS)
    unfused = np.asarray(qmatmul.qmatmul(xn, qt))
    fused = np.asarray(qmatmul.qmatmul_norm(x, nw, qt, eps=EPS))
    if (kind, K, O, T, jnp.dtype(xdt).name) not in _SUM_ORDER_CASES:
        np.testing.assert_array_equal(fused, unfused)
        return
    # the kernel's own products and the normalized activation ARE exact ...
    inv = qmatmul.rmsnorm_inv(x, EPS)
    np.testing.assert_array_equal(
        np.asarray(qmatmul.q40_matmul(x, qt.w, qt.s, qt.s2, nosub=False,
                                      norm_w=nw, norm_inv=inv)),
        np.asarray(qmatmul.q40_matmul(xn, qt.w, qt.s, qt.s2, nosub=False)))
    # ... and the recentering term is apart by a last place of one block sum
    assert qmatmul.Q40_NOSUB
    apart = np.abs(fused - unfused).max(axis=-1)
    assert (apart <= 4 * np.spacing(np.abs(unfused).max(axis=-1))).all()


@pytest.mark.parametrize("kind", ["q40", "q80"])
def test_fused_norm_stacked_and_flat_weight(kind):
    """Stacked (all-layers) launcher with both norm-weight shapes it must
    accept: the full [L, K] stack, and the pre-sliced [K] row that
    models.llama's layer scan actually passes."""
    K, O, L = 256, 384, 3
    qts = [qmatmul.quantize_tensor(np.asarray(_rand((K, O), seed=10 + i, scale=0.1)), kind)
           for i in range(L)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *qts)
    nws = _rand((L, K), seed=20, scale=0.5) + 1.0
    x = _rand((2, K), seed=21)
    for i in range(L):
        unfused = qmatmul.qmatmul(rmsnorm(x, nws[i], EPS), qts[i])
        for norm_w in (nws, nws[i]):
            fused = qmatmul.qmatmul_norm(x, norm_w, stacked, layer=jnp.int32(i), eps=EPS)
            np.testing.assert_array_equal(np.asarray(fused), np.asarray(unfused))


def test_fused_norm_dense_weights_never_engage():
    """norm_fusion_engages is the llama-side gate: dense (unquantized)
    weights have no Pallas epilogue to fuse into."""
    qt = qmatmul.quantize_tensor(np.asarray(_rand((64, 64))), "q80")
    os.environ["DLLAMA_FUSE_NORM"] = "1"
    try:
        assert qmatmul.norm_fusion_engages(qt)
        assert not qmatmul.norm_fusion_engages(jnp.zeros((64, 64)))
    finally:
        del os.environ["DLLAMA_FUSE_NORM"]
    assert not qmatmul.norm_fusion_engages(qt)  # flag off -> off


# ---------------------------------------------------------------------------
# Fused rope + cache write
# ---------------------------------------------------------------------------

CACHE_DTS = [jnp.bfloat16, jnp.float32, jnp.float8_e4m3fn]


@pytest.mark.parametrize("style", [rope.INTERLEAVED, rope.HALF])
@pytest.mark.parametrize("cache_dt", CACHE_DTS)
def test_rope_cache_solo_bit_identity(style, cache_dt):
    L, S, kv, hd, T = 2, 64, 4, 32, 3
    cos_t, sin_t = map(jnp.asarray, rope.rope_table(S, hd, 10000.0))

    @jax.jit
    def ref(k, v, cos, sin, kc, vc, pos, layer):
        kr = rope.apply_rope(k, cos, sin, style)
        z = jnp.int32(0)
        return (jax.lax.dynamic_update_slice(
                    kc, kr.astype(kc.dtype)[None], (layer, pos, z, z)),
                jax.lax.dynamic_update_slice(
                    vc, v.astype(vc.dtype)[None], (layer, pos, z, z)))

    for act_dt in (jnp.bfloat16, jnp.float32):
        for pos_v in (0, 10, S - 2):  # S-2 with T=3 exercises the end clamp
            k = _rand((T, kv, hd), seed=pos_v).astype(act_dt)
            v = _rand((T, kv, hd), seed=pos_v + 1).astype(act_dt)
            kc = _rand((L, S, kv, hd), seed=pos_v + 2).astype(cache_dt)
            vc = _rand((L, S, kv, hd), seed=pos_v + 3).astype(cache_dt)
            pos, layer = jnp.int32(pos_v), jnp.int32(1)
            cos = jax.lax.dynamic_slice_in_dim(cos_t, pos, T)[:, None, :]
            sin = jax.lax.dynamic_slice_in_dim(sin_t, pos, T)[:, None, :]
            ref_kc, ref_vc = ref(k, v, cos, sin, kc, vc, pos, layer)
            got_kc, got_vc = fused_rope_cache.rope_cache_update(
                k, v, cos, sin, kc, vc, pos, layer, style)
            np.testing.assert_array_equal(
                np.asarray(got_kc, np.float32), np.asarray(ref_kc, np.float32))
            np.testing.assert_array_equal(
                np.asarray(got_vc, np.float32), np.asarray(ref_vc, np.float32))


@pytest.mark.parametrize("style", [rope.INTERLEAVED, rope.HALF])
def test_rope_cache_batched_bit_identity(style):
    L, B, S, kv, hd = 2, 3, 64, 4, 32
    cos_t, sin_t = map(jnp.asarray, rope.rope_table(S, hd, 10000.0))
    k = _rand((B, kv, hd), seed=30).astype(jnp.bfloat16)
    v = _rand((B, kv, hd), seed=31).astype(jnp.bfloat16)
    kc = _rand((L, B, S, kv, hd), seed=32).astype(jnp.bfloat16)
    vc = _rand((L, B, S, kv, hd), seed=33).astype(jnp.bfloat16)
    pos = jnp.asarray([0, 17, S + 5], jnp.int32)  # last row overruns -> clamps
    layer = jnp.int32(0)
    cos = cos_t[jnp.clip(pos, 0, S - 1)][:, None, :]
    sin = sin_t[jnp.clip(pos, 0, S - 1)][:, None, :]

    @jax.jit
    def ref(k, v, cos, sin, kc, vc, pos, layer):
        kr = rope.apply_rope(k, cos, sin, style)
        rows = jnp.arange(B, dtype=jnp.int32)
        wpos = jnp.clip(pos, 0, S - 1)
        return (kc.at[layer, rows, wpos].set(kr.astype(kc.dtype)),
                vc.at[layer, rows, wpos].set(v.astype(vc.dtype)))

    ref_kc, ref_vc = ref(k, v, cos, sin, kc, vc, pos, layer)
    got_kc, got_vc = fused_rope_cache.rope_cache_update_batched(
        k, v, cos, sin, kc, vc, pos, layer, style)
    np.testing.assert_array_equal(np.asarray(got_kc, np.float32),
                                  np.asarray(ref_kc, np.float32))
    np.testing.assert_array_equal(np.asarray(got_vc, np.float32),
                                  np.asarray(ref_vc, np.float32))


@pytest.mark.parametrize("style", [rope.INTERLEAVED, rope.HALF])
def test_rope_cache_verify_bit_identity(style):
    """The [B, T] spec-verify wrapper vs the vmapped unfused write."""
    L, B, S, kv, hd, T = 2, 3, 64, 4, 32, 4
    cos_t, sin_t = map(jnp.asarray, rope.rope_table(S, hd, 10000.0))
    k = _rand((B, T, kv, hd), seed=40).astype(jnp.bfloat16)
    v = _rand((B, T, kv, hd), seed=41).astype(jnp.bfloat16)
    kc = _rand((L, B, S, kv, hd), seed=42).astype(jnp.bfloat16)
    vc = _rand((L, B, S, kv, hd), seed=43).astype(jnp.bfloat16)
    pos = jnp.asarray([0, 13, S - 1], jnp.int32)  # last row clamps to S-T
    layer = jnp.int32(1)
    starts = jnp.clip(pos, 0, S - T)
    idx = starts[:, None] + jnp.arange(T)
    cos = cos_t[idx][:, :, None, :]
    sin = sin_t[idx][:, :, None, :]

    @jax.jit
    def ref(k, v, cos, sin, kc, vc, starts, layer):
        kr = rope.apply_rope(k, cos, sin, style)

        def write(cache, rows, start):
            return jax.lax.dynamic_update_slice(
                cache, rows.astype(cache.dtype),
                (start, jnp.int32(0), jnp.int32(0)))

        kl = jax.vmap(write)(kc[layer], kr, starts)
        vl = jax.vmap(write)(vc[layer], v, starts)
        return (jax.lax.dynamic_update_slice_in_dim(kc, kl[None], layer, 0),
                jax.lax.dynamic_update_slice_in_dim(vc, vl[None], layer, 0))

    ref_kc, ref_vc = ref(k, v, cos, sin, kc, vc, starts, layer)
    got_kc, got_vc = fused_rope_cache.rope_cache_update_verify(
        k, v, cos, sin, kc, vc, pos, layer, style)
    np.testing.assert_array_equal(np.asarray(got_kc, np.float32),
                                  np.asarray(ref_kc, np.float32))
    np.testing.assert_array_equal(np.asarray(got_vc, np.float32),
                                  np.asarray(ref_vc, np.float32))


def test_rope_cache_engagement_gate(capsys):
    os.environ["DLLAMA_FUSE_ROPE_CACHE"] = "1"
    try:
        assert fused_rope_cache.engages(1, jnp.bfloat16)
        assert fused_rope_cache.engages(16, jnp.float8_e4m3fn)
        # prefill-sized T declines silently (by design, not a fallback)
        assert not fused_rope_cache.engages(64, jnp.bfloat16)
        assert capsys.readouterr().err == ""
        # unsupported cache dtype declines with a one-shot note
        fused_rope_cache._declined.clear()
        assert not fused_rope_cache.engages(1, jnp.float16)
        assert "declines" in capsys.readouterr().err
        assert not fused_rope_cache.engages(1, jnp.float16)
        assert capsys.readouterr().err == ""  # only once
    finally:
        del os.environ["DLLAMA_FUSE_ROPE_CACHE"]
    assert not fused_rope_cache.engages(1, jnp.bfloat16)  # flag off -> off


# ---------------------------------------------------------------------------
# End-to-end: full model forward with the flags flipped
# ---------------------------------------------------------------------------

def _model(seq_len):
    cfg = tiny_cfg(seq_len=seq_len, hidden_dim=128)  # q40 needs K % 64 == 0
    params = llama.quantize_params(llama.random_params(cfg, seed=3), "q40")
    params = jax.tree.map(
        lambda a: jnp.asarray(a) if isinstance(a, np.ndarray) else a, params)
    return cfg, params, llama.rope_tables(cfg)


def _run_all_paths(cfg, params, rope_t):
    logits, cache = llama.forward(
        cfg, params, rope_t, jnp.asarray([5, 99, 3, 42, 17], jnp.int32),
        llama.init_cache(cfg), 0)
    logits2, cache = llama.forward(
        cfg, params, rope_t, jnp.asarray([7], jnp.int32), cache, jnp.int32(5))
    bcache = llama.init_batch_cache(cfg, 3)
    _, bcache = llama.forward_batched(
        cfg, params, rope_t, jnp.asarray([1, 2, 3], jnp.int32), bcache,
        jnp.asarray([0, 0, 0], jnp.int32))
    blogits, bcache = llama.forward_batched(
        cfg, params, rope_t, jnp.asarray([4, 5, 6], jnp.int32), bcache,
        jnp.asarray([1, 1, 1], jnp.int32))
    vcache = llama.init_batch_cache(cfg, 2)
    vlogits, vcache = llama.forward_batched_verify(
        cfg, params, rope_t, jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32),
        vcache, jnp.asarray([0, 0], jnp.int32))
    return (np.asarray(logits), np.asarray(logits2), np.asarray(blogits),
            np.asarray(vlogits), np.asarray(cache["k"]),
            np.asarray(bcache["k"]), np.asarray(vcache["k"]))


def _flag_flip(monkeypatch, seq_len, extra_env=()):
    for key in ("DLLAMA_FUSE_NORM", "DLLAMA_FUSE_ROPE_CACHE"):
        monkeypatch.delenv(key, raising=False)
    for key, val in extra_env:
        monkeypatch.setenv(key, val)
    cfg, params, rope_t = _model(seq_len)
    jax.clear_caches()
    base = _run_all_paths(cfg, params, rope_t)
    monkeypatch.setenv("DLLAMA_FUSE_NORM", "1")
    monkeypatch.setenv("DLLAMA_FUSE_ROPE_CACHE", "1")
    jax.clear_caches()
    fused = _run_all_paths(cfg, params, rope_t)
    for i, (b, f) in enumerate(zip(base, fused)):
        np.testing.assert_array_equal(b, f, err_msg=f"output {i}")


def test_forward_paths_bit_identical_under_fusion(monkeypatch):
    """Solo prefill+decode, batched decode and spec-verify all produce the
    SAME logits and the SAME caches with both fusion flags on."""
    _flag_flip(monkeypatch, seq_len=32)


def test_fusion_composes_with_flash_decode(monkeypatch):
    """Both fusions + DLLAMA_FLASH_DECODE together (the production decode
    configuration): still bit-identical to the same stack unfused."""
    _flag_flip(monkeypatch, seq_len=256,
               extra_env=(("DLLAMA_FLASH_DECODE", "1"),))


# ---------------------------------------------------------------------------
# f8 cache: in-kernel upcast vs bf16-upcast oracle
# ---------------------------------------------------------------------------

def test_flash_f8_cache_matches_bf16_upcast_oracle():
    """flash_decode reading an f8 cache must equal reading the SAME cache
    pre-upcast to bf16: f8->f32 and f8->bf16->f32 are both exact (bf16
    keeps every f8 mantissa bit), so the in-kernel upcast path has no
    excuse for divergence. This is the CPU half of the standing
    'hardware-validate the f8 cache' roadmap item."""
    L, S, n_heads, n_kv, hd, T = 2, 512, 4, 2, 32, 2
    q = _rand((T, n_heads, hd), seed=50).astype(jnp.bfloat16)
    kc8 = _rand((L, S, n_kv, hd), seed=51).astype(jnp.float8_e4m3fn)
    vc8 = _rand((L, S, n_kv, hd), seed=52).astype(jnp.float8_e4m3fn)
    pos, layer = jnp.int32(300), jnp.int32(1)
    out_f8 = flash_decode.flash_decode_attention(q, kc8, vc8, pos, layer)
    out_bf16 = flash_decode.flash_decode_attention(
        q, kc8.astype(jnp.bfloat16), vc8.astype(jnp.bfloat16), pos, layer)
    np.testing.assert_array_equal(np.asarray(out_f8, np.float32),
                                  np.asarray(out_bf16, np.float32))


def test_rope_cache_f8_matches_bf16_roundtrip_oracle():
    """The fused rope+cache write into an f8 cache: rotating in f32 and
    casting act->f8 must leave exactly the bytes the unfused DUS path
    leaves (covered per-style above); here we additionally pin that the
    f8 rows, upcast back, equal the unfused bf16-cache rows downcast to
    f8 — i.e. the fusion changes WHERE the cast happens, never its input."""
    L, S, kv, hd, T = 1, 64, 2, 32, 2
    cos_t, sin_t = map(jnp.asarray, rope.rope_table(S, hd, 10000.0))
    k = _rand((T, kv, hd), seed=60).astype(jnp.bfloat16)
    v = _rand((T, kv, hd), seed=61).astype(jnp.bfloat16)
    pos, layer = jnp.int32(7), jnp.int32(0)
    cos = jax.lax.dynamic_slice_in_dim(cos_t, pos, T)[:, None, :]
    sin = jax.lax.dynamic_slice_in_dim(sin_t, pos, T)[:, None, :]
    kc8 = jnp.zeros((L, S, kv, hd), jnp.float8_e4m3fn)
    kc16 = jnp.zeros((L, S, kv, hd), jnp.bfloat16)
    got8, _ = fused_rope_cache.rope_cache_update(
        k, v, cos, sin, kc8, kc8, pos, layer, rope.INTERLEAVED)
    got16, _ = fused_rope_cache.rope_cache_update(
        k, v, cos, sin, kc16, kc16, pos, layer, rope.INTERLEAVED)
    rows8 = np.asarray(got8[0, 7:7 + T], np.float32)
    rows16 = np.asarray(got16[0, 7:7 + T].astype(jnp.float8_e4m3fn), np.float32)
    np.testing.assert_array_equal(rows8, rows16)
