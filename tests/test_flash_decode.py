"""flash_decode_attention vs the dense masked oracle (interpret mode).

The kernel must match ops.attention.gqa_attention bit-for-tolerance at every
(T, pos, GQA group, layer) combination the decode/spec-verify paths produce —
including positions that end mid-block and the padded sublane rows.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.ops import flash_decode
from dllama_tpu.ops.attention import gqa_attention


def _mk(seed, T, S, n_heads, n_kv, hd, dtype=jnp.float32, L=1):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((T, n_heads, hd)), dtype)
    k = jnp.asarray(rng.standard_normal((L, S, n_kv, hd)), dtype)
    v = jnp.asarray(rng.standard_normal((L, S, n_kv, hd)), dtype)
    return q, k, v


@pytest.mark.parametrize("T,pos", [(1, 0), (1, 5), (1, 255), (1, 256),
                                   (1, 300), (5, 250), (8, 0),
                                   (9, 120), (16, 64)])
def test_matches_dense_oracle(T, pos):
    S, n_heads, n_kv, hd = 512, 8, 4, 128
    q, k, v = _mk(1, T, S, n_heads, n_kv, hd)
    want = gqa_attention(q, k[0], v[0], jnp.int32(pos))
    got = flash_decode.flash_decode_attention(
        q, k, v, jnp.int32(pos), jnp.int32(0))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_no_group_and_wide_group():
    S, hd = 512, 64
    for n_heads, n_kv in ((4, 4), (16, 2)):
        q, k, v = _mk(2, 2, S, n_heads, n_kv, hd)
        want = gqa_attention(q, k[0], v[0], jnp.int32(100))
        got = flash_decode.flash_decode_attention(
            q, k, v, jnp.int32(100), jnp.int32(0))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_stacked_layer_selection():
    """The kernel must read layer L's slab from the stacked cache in place."""
    S, n_heads, n_kv, hd, L = 512, 8, 4, 128, 3
    q, k, v = _mk(3, 1, S, n_heads, n_kv, hd, L=L)
    for layer in range(L):
        want = gqa_attention(q, k[layer], v[layer], jnp.int32(77))
        got = flash_decode.flash_decode_attention(
            q, k, v, jnp.int32(77), jnp.int32(layer))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_bf16_cache():
    S, n_heads, n_kv, hd = 512, 8, 8, 128
    q, k, v = _mk(4, 1, S, n_heads, n_kv, hd, dtype=jnp.bfloat16)
    want = gqa_attention(q, k[0], v[0], jnp.int32(200))
    got = flash_decode.flash_decode_attention(
        q, k, v, jnp.int32(200), jnp.int32(0))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_reads_only_live_blocks():
    """Garbage (NaN) beyond the live prefix must not reach the output — the
    proof the kernel's trip count really skips dead cache blocks."""
    S, n_heads, n_kv, hd = 1024, 4, 4, 64
    q, k, v = _mk(5, 1, S, n_heads, n_kv, hd)
    pos = 100  # one live block of 256
    kn = k.at[:, 256:].set(jnp.nan)
    vn = v.at[:, 256:].set(jnp.nan)
    got = flash_decode.flash_decode_attention(
        q, kn, vn, jnp.int32(pos), jnp.int32(0))
    assert np.isfinite(np.asarray(got)).all()
    want = gqa_attention(q, k[0], v[0], jnp.int32(pos))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_supports_gate(monkeypatch, capsys):
    assert flash_decode.supports(1, 512, jnp.bfloat16)
    assert flash_decode.supports(8, 4096, jnp.float32)
    assert flash_decode.supports(9, 512, jnp.bfloat16)   # default spec verify
    assert flash_decode.supports(1, 4096, jnp.float8_e4m3fn)  # f8 composes
    assert not flash_decode.supports(17, 512, jnp.bfloat16)  # prefill-sized
    assert not flash_decode.supports(1, 500, jnp.bfloat16)   # ragged S
    # flag off -> never engages
    monkeypatch.delenv("DLLAMA_FLASH_DECODE", raising=False)
    assert not flash_decode.engages(1, 512, jnp.bfloat16)
    # flag on + unsupported shape -> declines AND says so once (ADVICE r04)
    monkeypatch.setenv("DLLAMA_FLASH_DECODE", "1")
    flash_decode._declined.clear()
    assert flash_decode.engages(1, 512, jnp.bfloat16)
    assert not flash_decode.engages(1, 500, jnp.bfloat16)
    assert not flash_decode.engages(1, 500, jnp.bfloat16)
    err = capsys.readouterr().err
    assert err.count("flash decode declines") == 1 and "S=500" in err


def test_f8_cache_matches_oracle():
    """f8_e4m3 cache blocks upcast in the kernel must match the dense oracle
    reading the same f8 slabs — the long-context composition (f8 halves
    cache bytes, flash skips dead blocks) that an earlier review flagged as
    mutually exclusive."""
    S, n_heads, n_kv, hd = 512, 8, 4, 128
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((1, n_heads, hd)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, S, n_kv, hd)), jnp.float8_e4m3fn)
    v = jnp.asarray(rng.standard_normal((1, S, n_kv, hd)), jnp.float8_e4m3fn)
    for pos in (0, 255, 300):
        want = gqa_attention(q, k[0], v[0], jnp.int32(pos))
        got = flash_decode.flash_decode_attention(
            q, k, v, jnp.int32(pos), jnp.int32(0))
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=3e-2, atol=3e-2)


def test_dense_engine_engages_flash(monkeypatch):
    """A DENSE (bf16/f32-weight) engine must also take the flash path now:
    forward() routes dense weights through the index-scan when the gate
    engages (an earlier review: dense-weight engines never used flash)."""
    from dllama_tpu.models import llama
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.ops import flash_decode as fd
    from dllama_tpu.runtime.generate import Engine
    from dllama_tpu.runtime.sampler import SamplerConfig

    cfg = ModelConfig(
        arch="llama", dim=64, hidden_dim=128, n_layers=2, n_heads=4,
        n_kv_heads=2, vocab_size=64, seq_len=512, head_size=16, kv_dim=32,
        dtype="float32",
    )
    params = llama.random_params(cfg, seed=0)

    def run(spy_calls=None):
        if spy_calls is not None:
            real = fd.flash_decode_attention

            def spy(*a, **kw):
                spy_calls.append(1)
                return real(*a, **kw)

            monkeypatch.setattr(fd, "flash_decode_attention", spy)
            monkeypatch.setattr(
                "dllama_tpu.models.llama.flash_decode.flash_decode_attention",
                spy)
        eng = Engine(cfg, params, SamplerConfig(temperature=0.0))
        return [t for t, _ in eng.generate([1, 5, 9], steps=16)]

    monkeypatch.delenv("DLLAMA_FLASH_DECODE", raising=False)
    dense = run()
    monkeypatch.setenv("DLLAMA_FLASH_DECODE", "1")
    calls = []
    flash = run(spy_calls=calls)
    assert calls, "flash never traced on the dense-weight path"
    assert flash == dense and len(dense) == 16


def test_engine_decode_matches_dense_path(monkeypatch):
    """Greedy decode through the full Engine with DLLAMA_FLASH_DECODE=1 must
    emit exactly the dense-path token stream. The engine must be QUANTIZED:
    the flash wiring lives on the layer-scan (scalar-prefetch) path, which
    only quantized params take — a dense engine runs layer=None and would
    compare dense vs dense vacuously."""
    from dllama_tpu.models import llama
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.ops import flash_decode as fd
    from dllama_tpu.runtime.generate import Engine
    from dllama_tpu.runtime.sampler import SamplerConfig

    cfg = ModelConfig(
        arch="llama", dim=64, hidden_dim=128, n_layers=2, n_heads=4,
        n_kv_heads=2, vocab_size=64, seq_len=512, head_size=16, kv_dim=32,
        dtype="float32",
    )
    params = llama.quantize_params(llama.random_params(cfg, seed=0), "q40")

    def run(spy_calls=None):
        if spy_calls is not None:
            real = fd.flash_decode_attention

            def spy(*a, **kw):
                spy_calls.append(1)
                return real(*a, **kw)

            monkeypatch.setattr(fd, "flash_decode_attention", spy)
            monkeypatch.setattr(
                "dllama_tpu.models.llama.flash_decode.flash_decode_attention",
                spy)
        eng = Engine(cfg, params, SamplerConfig(temperature=0.0))
        return [t for t, _ in eng.generate([1, 5, 9], steps=16)]

    monkeypatch.delenv("DLLAMA_FLASH_DECODE", raising=False)
    dense = run()
    monkeypatch.setenv("DLLAMA_FLASH_DECODE", "1")
    calls = []
    flash = run(spy_calls=calls)
    assert calls, "flash kernel was never traced — the flag did not engage"
    assert flash == dense and len(dense) == 16


def test_batched_matches_per_row_oracle():
    """Each batch row must attend over exactly ITS OWN prefix — matching
    vmap(gqa_attention) over per-row slabs, with rows at very different
    positions (different live-block counts) in one launch."""
    B, S, n_heads, n_kv, hd, L = 3, 1024, 8, 4, 64, 2
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((B, n_heads, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((L, B, S, n_kv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((L, B, S, n_kv, hd)), jnp.float32)
    pos = jnp.asarray([0, 300, 700], jnp.int32)
    for layer in range(L):
        want = jax.vmap(
            lambda qb, ks, vs, p: gqa_attention(qb[None], ks, vs, p)[0]
        )(q, k[layer], v[layer], pos)
        got = flash_decode.flash_decode_attention_batched(
            q, k, v, pos, jnp.int32(layer))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_batched_rows_ignore_other_rows_dead_blocks():
    """NaNs beyond each row's OWN prefix (including rows with more history
    than this one) must never leak in."""
    B, S, n_heads, n_kv, hd = 2, 512, 4, 4, 64
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.standard_normal((B, n_heads, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, B, S, n_kv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, B, S, n_kv, hd)), jnp.float32)
    pos = jnp.asarray([10, 400], jnp.int32)
    # poison row 0 beyond its single live block; row 1's history stays real
    kn = k.at[:, 0, 256:].set(jnp.nan)
    vn = v.at[:, 0, 256:].set(jnp.nan)
    got = flash_decode.flash_decode_attention_batched(
        q, kn, vn, pos, jnp.int32(0))
    assert np.isfinite(np.asarray(got)).all()
    want = jax.vmap(
        lambda qb, ks, vs, p: gqa_attention(qb[None], ks, vs, p)[0]
    )(q, k[0], v[0], pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_batched_engine_matches_dense_path(monkeypatch):
    """generate_batch through a quantized engine with the flag on must emit
    the same per-row streams as the dense path, with the batched kernel
    spy-verified to have traced."""
    from dllama_tpu.models import llama
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.ops import flash_decode as fd
    from dllama_tpu.runtime.generate import Engine
    from dllama_tpu.runtime.sampler import SamplerConfig

    cfg = ModelConfig(
        arch="llama", dim=64, hidden_dim=128, n_layers=2, n_heads=4,
        n_kv_heads=2, vocab_size=64, seq_len=512, head_size=16, kv_dim=32,
        dtype="float32",
    )
    params = llama.quantize_params(llama.random_params(cfg, seed=0), "q40")
    prompts = [[1, 5, 9], [7], [3, 3]]

    def run():
        eng = Engine(cfg, params, SamplerConfig(temperature=0.0))
        return eng.generate_batch(prompts, steps=10)

    monkeypatch.delenv("DLLAMA_FLASH_DECODE", raising=False)
    dense = run()
    calls = []
    real = fd.flash_decode_attention_batched

    def spy(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(fd, "flash_decode_attention_batched", spy)
    monkeypatch.setenv("DLLAMA_FLASH_DECODE", "1")
    flash = run()
    assert calls, "batched flash kernel never traced"
    assert flash == dense


def test_spec_decode_engine_matches_with_flash(monkeypatch):
    """generate_spec (T = draft+1 = 9 verify batches, newly admitted by the
    T<=16 cap) with the flag on must emit exactly the dense-path stream."""
    from dllama_tpu.models import llama
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.runtime.generate import Engine
    from dllama_tpu.runtime.sampler import SamplerConfig

    cfg = ModelConfig(
        arch="llama", dim=64, hidden_dim=128, n_layers=2, n_heads=4,
        n_kv_heads=2, vocab_size=64, seq_len=512, head_size=16, kv_dim=32,
        dtype="float32",
    )
    params = llama.quantize_params(llama.random_params(cfg, seed=0), "q40")

    def run():
        eng = Engine(cfg, params, SamplerConfig(temperature=0.0))
        return [t for t, _ in eng.generate_spec([1, 5, 9], steps=14)]

    monkeypatch.delenv("DLLAMA_FLASH_DECODE", raising=False)
    dense = run()
    monkeypatch.setenv("DLLAMA_FLASH_DECODE", "1")
    flash = run()
    assert flash == dense and len(dense) == 14


def test_quant_tp_forward_matches_with_flash(monkeypatch):
    """Flash decode inside the shard_map quant-TP forward (per-device local
    kv heads, cache shard [L, S, kv_local, hd]) must equal the single-device
    dense-path logits — the sharding-invariance pattern applied to the
    flash kernel."""
    from dllama_tpu.models import llama
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.parallel import quant_tp
    from dllama_tpu.parallel.mesh import tp_mesh

    cfg = ModelConfig(
        arch="llama", dim=256, hidden_dim=512, n_layers=2, n_heads=8,
        n_kv_heads=8, vocab_size=128, seq_len=256, head_size=32, kv_dim=256,
        dtype="float32",
    )
    qp = llama.quantize_params(llama.random_params(cfg, seed=0, dtype=np.float32), "q40")
    rope = llama.rope_tables(cfg)
    tokens = jnp.asarray([5], jnp.int32)

    monkeypatch.delenv("DLLAMA_FLASH_DECODE", raising=False)
    ref_logits, _ = jax.jit(
        lambda p, r, c, t: llama.forward(cfg, p, r, t, c, jnp.int32(0))
    )(jax.tree.map(jnp.asarray, qp), rope, llama.init_cache(cfg), tokens)

    monkeypatch.setenv("DLLAMA_FLASH_DECODE", "1")
    # pin the intent: the kernel must actually trace inside shard_map — a
    # gate change that silently falls back to dense would otherwise leave
    # this comparing dense vs dense
    calls = []
    real = flash_decode.flash_decode_attention

    def spy(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(flash_decode, "flash_decode_attention", spy)
    mesh = tp_mesh(4)
    sharded = quant_tp.shard_quant_params(qp, mesh, cfg)
    fwd = quant_tp.make_tp_forward(cfg, mesh, sharded)
    tp_logits, _ = jax.jit(fwd)(sharded, rope, llama.init_cache(cfg), tokens,
                                jnp.int32(0))
    assert calls, "flash kernel never traced under shard_map"
    np.testing.assert_allclose(np.asarray(tp_logits), np.asarray(ref_logits),
                               rtol=1e-4, atol=1e-4)


def test_dense_mesh_engine_declines_flash(monkeypatch, capsys):
    """Dense weights under a pjit TP mesh must NOT route into the Pallas
    flash kernel (GSPMD can't partition a custom call — it would compile
    replicated against an all-gathered cache). The engine pins
    allow_flash=False there and says so on stderr."""
    import numpy as np

    from dllama_tpu.models import llama
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.ops import flash_decode as fd
    from dllama_tpu.parallel.mesh import tp_mesh
    from dllama_tpu.runtime.generate import Engine
    from dllama_tpu.runtime.sampler import SamplerConfig

    cfg = ModelConfig(
        arch="llama", dim=64, hidden_dim=128, n_layers=2, n_heads=4,
        n_kv_heads=4, vocab_size=64, seq_len=512, head_size=16, kv_dim=64,
        dtype="float32",
    )
    params = llama.random_params(cfg, seed=0, dtype=np.float32)

    def run():
        eng = Engine(cfg, params, SamplerConfig(temperature=0.0),
                     mesh=tp_mesh(4))
        return [t for t, _ in eng.generate([1, 5], steps=6)]

    monkeypatch.delenv("DLLAMA_FLASH_DECODE", raising=False)
    want = run()

    calls = []
    real = fd.flash_decode_attention

    def spy(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(fd, "flash_decode_attention", spy)
    monkeypatch.setattr(
        "dllama_tpu.models.llama.flash_decode.flash_decode_attention", spy)
    monkeypatch.setenv("DLLAMA_FLASH_DECODE", "1")
    got = run()
    assert not calls, "flash kernel traced under the dense pjit mesh path"
    assert got == want
    assert "dense-pjit TP path" in capsys.readouterr().err
