"""Batched multi-sequence decode (Engine.generate_batch / llama.forward_batched).

The reference decodes one token for one sequence per step
(`/root/reference/src/tasks.cpp:199-210`); on TPU a [B, K] activation streams
the weights once for all B sequences. These tests pin the row-wise math to
the single-sequence engine: every greedy row must equal its solo run exactly,
across dense, quantized, and quantized-MoE models and mixed prompt lengths.
"""

import numpy as np
import pytest

from dllama_tpu.models import llama
from dllama_tpu.models.config import ModelConfig
from dllama_tpu.runtime.generate import Engine
from dllama_tpu.runtime.sampler import SamplerConfig

CFG = ModelConfig(
    arch="llama", dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
    vocab_size=96, seq_len=64, head_size=16, kv_dim=32, dtype="float32",
)

MOE_CFG = ModelConfig(
    arch="mixtral", dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=4,
    vocab_size=96, seq_len=64, head_size=16, kv_dim=64, n_experts=8,
    n_active_experts=2, rope_style="half", dtype="float32",
)

PROMPTS = [[5, 9, 3], [7], [1, 2, 3, 4, 5, 6, 11]]  # mixed lengths incl. 1


def _solo_rows(cfg, params, prompts, steps):
    rows = []
    for p in prompts:
        eng = Engine(cfg, params, SamplerConfig(temperature=0.0))
        rows.append([t for t, _ in eng.generate(list(p), steps=steps)])
    return rows


@pytest.mark.parametrize("quant", [None, "q40"])
def test_batched_greedy_rows_equal_solo(quant):
    params = llama.random_params(CFG, seed=0, dtype=np.float32)
    if quant:
        params = llama.quantize_params(params, quant)
    want = _solo_rows(CFG, params, PROMPTS, steps=10)
    eng = Engine(CFG, params, SamplerConfig(temperature=0.0))
    got = eng.generate_batch(PROMPTS, steps=10)
    assert got == want


def test_batched_moe_quant_rows_equal_solo():
    """B rows through the quantized-MoE union path: per-row routing must not
    leak across sequences."""
    params = llama.quantize_params(
        llama.random_params(MOE_CFG, seed=1, dtype=np.float32), "q40"
    )
    want = _solo_rows(MOE_CFG, params, PROMPTS, steps=8)
    eng = Engine(MOE_CFG, params, SamplerConfig(temperature=0.0))
    got = eng.generate_batch(PROMPTS, steps=8)
    assert got == want


def test_batched_steps_clamped_per_row():
    """A near-full row exhausts ITS context without truncating the others
    (it pins at its last cache slot; its surplus tokens are discarded)."""
    params = llama.random_params(CFG, seed=2, dtype=np.float32)
    eng = Engine(CFG, params, SamplerConfig(temperature=0.0))
    long_prompt = list(range(1, CFG.seq_len - 3))  # 60 tokens -> pos 59
    got = eng.generate_batch([[5], long_prompt], steps=50)
    assert len(got[0]) == 50  # the roomy row gets its full budget
    assert len(got[1]) == 5   # slots 59..63 = 5 feeds for the full row
    # the roomy row's stream equals its solo run despite the pinned sibling
    solo = Engine(CFG, params, SamplerConfig(temperature=0.0))
    want = [t for t, _ in solo.generate([5], steps=50)]
    assert got[0] == want


def test_batched_sampled_rows_are_valid_tokens():
    params = llama.random_params(CFG, seed=3, dtype=np.float32)
    eng = Engine(CFG, params, SamplerConfig(temperature=0.9, seed=7))
    got = eng.generate_batch(PROMPTS, steps=6)
    assert all(len(r) == 6 for r in got)
    assert all(0 <= t < CFG.vocab_size for r in got for t in r)


def test_batched_under_quant_tp_mesh_matches_solo():
    """Multi-chip batched serving: expert... quant planes output-sharded,
    B sequences share every local weight stream AND every ICI gather —
    greedy rows must equal the single-device solo streams."""
    from dllama_tpu.parallel.mesh import tp_mesh

    params = llama.quantize_params(
        llama.random_params(CFG, seed=0, dtype=np.float32), "q40"
    )
    want = _solo_rows(CFG, params, PROMPTS, steps=8)
    eng = Engine(CFG, params, SamplerConfig(temperature=0.0), mesh=tp_mesh(2))
    got = eng.generate_batch(PROMPTS, steps=8)
    assert got == want


def test_batched_under_dense_tp_mesh_matches_solo():
    from dllama_tpu.parallel.mesh import tp_mesh

    params = llama.random_params(CFG, seed=0, dtype=np.float32)
    want = _solo_rows(CFG, params, PROMPTS, steps=8)
    eng = Engine(CFG, params, SamplerConfig(temperature=0.0), mesh=tp_mesh(2))
    got = eng.generate_batch(PROMPTS, steps=8)
    assert got == want


def test_batched_rejects_empty():
    params = llama.random_params(CFG, seed=0, dtype=np.float32)
    solo = Engine(CFG, params, SamplerConfig(temperature=0.0))
    with pytest.raises(ValueError):
        solo.generate_batch([[1], []], steps=2)


def test_batched_stop_tokens_skip_remaining_chunks():
    """Once every row has emitted a stop token, later decode chunks are
    skipped — and the emitted prefixes still equal the no-stop run."""
    params = llama.random_params(CFG, seed=5, dtype=np.float32)
    eng = Engine(CFG, params, SamplerConfig(temperature=0.0), decode_chunk=4)
    full = eng.generate_batch(PROMPTS, steps=32)
    stops = tuple({row[2] for row in full})  # every row stops by chunk 1
    got = eng.generate_batch(PROMPTS, steps=32, stop_tokens=stops)
    for b in range(len(PROMPTS)):
        assert len(got[b]) < 32  # early exit actually happened
        assert got[b] == full[b][: len(got[b])]


def test_batched_row_budgets_drive_early_exit():
    """A row that never stops but has a tiny max_tokens budget counts as
    done at its budget, so a co-batched stopping row isn't forced through
    the whole step envelope (r4 review: mixed-max_tokens server batches)."""
    params = llama.random_params(CFG, seed=6, dtype=np.float32)
    eng = Engine(CFG, params, SamplerConfig(temperature=0.0), decode_chunk=4)
    full = eng.generate_batch([[5, 9], [7, 3]], steps=32)
    stop_b = full[1][2]  # row 1 stops in chunk 1; row 0's budget is 4
    got = eng.generate_batch(
        [[5, 9], [7, 3]], steps=32,
        stop_tokens=(stop_b,) if stop_b not in full[0][:4] else (stop_b, full[0][0]),
        row_steps=[4, 32],
    )
    assert len(got[0]) < 32 and len(got[1]) < 32  # early exit fired
    assert got[0] == full[0][: len(got[0])]
    assert got[1] == full[1][: len(got[1])]


def test_batched_moe_under_quant_tp_mesh_matches_solo():
    """The full production matrix cell: quantized MoE expert shards x TP
    mesh x batched rows — per-row routing on shared expert slices."""
    from dllama_tpu.parallel.mesh import tp_mesh

    params = llama.quantize_params(
        llama.random_params(MOE_CFG, seed=1, dtype=np.float32), "q40"
    )
    want = _solo_rows(MOE_CFG, params, PROMPTS[:2], steps=6)
    eng = Engine(MOE_CFG, params, SamplerConfig(temperature=0.0), mesh=tp_mesh(4))
    got = eng.generate_batch(PROMPTS[:2], steps=6)
    assert got == want


def test_batched_row_budgets_early_exit_without_stop_tokens():
    """row_steps alone (no stop tokens — e.g. a vocab with no EOS) must
    still end the batch once every row reaches its own budget."""
    params = llama.random_params(CFG, seed=7, dtype=np.float32)
    eng = Engine(CFG, params, SamplerConfig(temperature=0.0), decode_chunk=4)
    got = eng.generate_batch([[5, 9], [7]], steps=32, row_steps=[3, 4])
    assert len(got[0]) == 4 and len(got[1]) == 4  # one 4-step chunk, then exit


def test_batched_per_row_samplers_bit_identical_to_solo():
    """Row b with samplers[b]=SamplerConfig(T, p, seed) must emit EXACTLY
    the stream of a solo generate() with that config: per-row key chains
    split once per step like the solo paths (the server batches mixed
    sampled requests on this invariant)."""
    params = llama.random_params(CFG, seed=3, dtype=np.float32)
    samplers = [
        SamplerConfig(temperature=0.9, topp=0.95, seed=7),
        SamplerConfig(temperature=0.0, seed=1),      # greedy row in the mix
        SamplerConfig(temperature=1.3, topp=0.8, seed=42),
    ]
    want = []
    for p, s in zip(PROMPTS, samplers):
        eng = Engine(CFG, params, SamplerConfig(temperature=0.0))
        want.append([t for t, _ in eng.generate(list(p), steps=10, sampler=s)])
    eng = Engine(CFG, params, SamplerConfig(temperature=0.0))
    got = eng.generate_batch(PROMPTS, steps=10, samplers=samplers)
    assert got == want


def test_batched_on_chunk_streams_every_token_once():
    """on_chunk bursts concatenated must equal the returned rows (the SSE
    streaming hook must neither drop nor duplicate)."""
    params = llama.random_params(CFG, seed=0, dtype=np.float32)
    eng = Engine(CFG, params, SamplerConfig(temperature=0.0), decode_chunk=4)
    seen = [[] for _ in PROMPTS]

    def on_chunk(fresh):
        assert len(fresh) == len(PROMPTS)
        for b, burst in enumerate(fresh):
            seen[b].extend(burst)

    rows = eng.generate_batch(PROMPTS, steps=10, on_chunk=on_chunk)
    assert seen == rows
    assert all(len(r) == 10 for r in rows)


def test_batched_samplers_wrong_length_rejected():
    params = llama.random_params(CFG, seed=0, dtype=np.float32)
    eng = Engine(CFG, params, SamplerConfig(temperature=0.0))
    with pytest.raises(ValueError):
        eng.generate_batch(PROMPTS, steps=4,
                           samplers=[SamplerConfig(temperature=0.0)])


# ---------------------------------------------------------------------------
# PR 25: the pooled step writes its K/V rows into the stacked cache in place.
# The form it had before, the layer's slab copied out, updated and written
# back, stays here as the oracle: same values into the same slots.
# ---------------------------------------------------------------------------

def _round_trip_write(k_cache, v_cache, k, v, layer, pos):
    """``llama._write_kv_rows`` as the slab round trip it replaced: copy the
    layer's [B, S, kv, hd] slab out of the stacked cache, write each
    sequence's T rows into the copy (a vmapped ``dynamic_update_slice``: its
    clamp is the contract), write the whole slab back."""
    import jax

    def one(cache, new):
        slab = jax.lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)
        slab = jax.vmap(
            lambda c, n, p: jax.lax.dynamic_update_slice_in_dim(
                c, n.astype(c.dtype), p, axis=0))(slab, new, pos)
        return jax.lax.dynamic_update_slice(cache, slab[None],
                                            (layer, 0, 0, 0, 0))

    return one(k_cache, k), one(v_cache, v)


def _pool_positions(rows: int, ctx: int) -> np.ndarray:
    """Row 0 walks off the slab's end inside the chunk (``ctx - 3`` onwards:
    the write clamps to ``ctx - 1``); of the others, every third is a free
    row pinned at ``ctx - 1``, one starts past the end, the rest are live at
    positions of their own."""
    pos = np.full((rows,), ctx - 1, np.int32)
    pos[0] = ctx - 3
    for b in range(1, rows):
        if b % 3 == 1:
            pos[b] = 2 + 3 * b
        elif b % 3 == 2:
            pos[b] = ctx + 5 if b == 2 else 1 + b
    return pos


_F8 = "float8_e4m3fn"
_POOL_CASES = [
    # arch, cache dtype, rows, tp
    ("llama", "float32", 4, 0), ("llama", "bfloat16", 4, 0),
    ("llama", _F8, 4, 0), ("llama", "float32", 1, 0),
    ("llama", "bfloat16", 8, 0), ("mixtral", "float32", 4, 0),
    ("mixtral", "bfloat16", 8, 0), ("mixtral", _F8, 1, 0),
    ("llama", "float32", 4, 2), ("llama", "bfloat16", 8, 2),
    ("mixtral", "float32", 4, 2),
]


@pytest.mark.parametrize("arch,cache_dtype,rows,tp", _POOL_CASES, ids=[
    f"{a}-{d}-B{b}" + (f"-tp{t}" if t else "") for a, d, b, t in _POOL_CASES])
def test_pooled_steps_leave_the_cache_the_round_trip_left(
        monkeypatch, arch, cache_dtype, rows, tp):
    """Six pooled steps (``Engine._decode_loop_batch`` over a bucket slab
    shorter than the model's context, the quantized layer scan, so the
    stacked cache rides the carry) through the in-place write and through
    the round trip: the whole stacked cache, every layer, row and slot, and
    the tokens are bit for bit the same."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.parallel.mesh import tp_mesh

    cfg = CFG if arch == "llama" else MOE_CFG
    params = llama.quantize_params(
        llama.random_params(cfg, seed=4, dtype=np.float32), "q40")
    ctx, steps = 32, 6
    pos = _pool_positions(rows, ctx)
    rng = np.random.default_rng(5)
    fill = {n: rng.standard_normal(
        (cfg.n_layers, rows, ctx, cfg.n_kv_heads, cfg.head_size)
    ).astype(np.float32) for n in ("k", "v")}

    def run():
        eng = Engine(cfg, params, SamplerConfig(temperature=0.0),
                     cache_dtype=jnp.dtype(cache_dtype),
                     mesh=tp_mesh(tp) if tp else None)
        cache = {n: jax.device_put(jnp.asarray(fill[n]).astype(a.dtype),
                                   a.sharding)
                 for n, a in eng._bucket_cache_init(rows, ctx).items()}
        out, cache, _, ok = eng._decode_loop_batch(
            cache, jnp.arange(3, 3 + rows, dtype=jnp.int32),
            jnp.asarray(pos), jnp.zeros((rows, 2), jnp.uint32),
            jnp.zeros(rows, jnp.float32), jnp.ones(rows, jnp.float32),
            jnp.zeros(rows, jnp.bool_), n_steps=steps)
        assert bool(np.all(np.asarray(ok)))
        return np.asarray(out), {n: np.asarray(a.astype(jnp.float32))
                                 for n, a in cache.items()}

    toks, cache = run()
    monkeypatch.setattr(llama, "_write_kv_rows", _round_trip_write)
    want_toks, want_cache = run()
    np.testing.assert_array_equal(toks, want_toks)
    for n in ("k", "v"):
        np.testing.assert_array_equal(cache[n], want_cache[n])
        # the step did write: the slots it owns differ from what was there
        was = np.asarray(jnp.asarray(fill[n]).astype(jnp.dtype(cache_dtype))
                         .astype(jnp.float32))
        assert (cache[n][:, 0, ctx - 3:] != was[:, 0, ctx - 3:]).any()
        # and nothing but: a free row's slots below ctx - 1 are untouched
        free = [b for b in range(rows) if pos[b] == ctx - 1]
        for b in free:
            np.testing.assert_array_equal(cache[n][:, b, :ctx - 1],
                                          was[:, b, :ctx - 1])


@pytest.mark.parametrize("cache_dtype", ["float32", "bfloat16", _F8])
@pytest.mark.parametrize("T", [1, 3])
def test_write_kv_rows_clamps_like_the_slab_update(cache_dtype, T):
    """The helper alone, T rows a sequence (T > 1 is the speculative verify
    step): starts below, at and past ``S - T`` land where the vmapped
    ``dynamic_update_slice`` put them, in every layer index."""
    import jax.numpy as jnp

    L, B, S, kv, hd = 3, 5, 16, 2, 8
    rng = np.random.default_rng(6)
    dt = jnp.dtype(cache_dtype)
    caches = [jnp.asarray(rng.standard_normal((L, B, S, kv, hd)),
                          jnp.float32).astype(dt) for _ in range(2)]
    new = [jnp.asarray(rng.standard_normal((B, T, kv, hd)), jnp.float32)
           for _ in range(2)]
    pos = jnp.asarray([0, S - T - 1, S - T, S - 1, S + 7], jnp.int32)
    for layer in range(L):
        got = llama._write_kv_rows(*caches, *new, jnp.int32(layer), pos)
        want = _round_trip_write(*caches, *new, jnp.int32(layer), pos)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(
                np.asarray(g.astype(jnp.float32)),
                np.asarray(w.astype(jnp.float32)))
