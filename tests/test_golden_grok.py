"""Grok-1 golden cross-check against the reference's pinned spot values.

The reference pins the output of a 1-layer Grok-1 block whose weights come
from a seeded xorshift64* stream (`/root/reference/src/grok1-tasks-test.cpp:
13-15,29-91`, RNG at `/root/reference/src/utils.cpp:53-64`). Reproducing the
same stream here and hitting the same numbers rules out a shared sign/scale
error between this framework's MoE math and its own self-built numpy oracle
(tests/reference_impl.py) — the two implementations now agree with an
*independent third* implementation's published constants.

The stream (239M floats) is produced by the C++ ``xorshift-gen`` tool
(native/src/xorshift_gen.cc) because a sequential PRNG at that scale is not
feasible in Python.
"""

import os
import subprocess

import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.models import llama
from dllama_tpu.models.config import ModelConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "native")

DIM, HIDDEN, VOCAB, E = 6144, 1024, 1024, 8
N_HEADS, N_KV, HEAD = 48, 8, 128
KV_DIM = 1024

# /root/reference/src/grok1-tasks-test.cpp:13-15
GOLDEN = {
    0: [0.00940248929, 0.0191232786, 0.0147766126, 0.0102868658],
    256: [0.0191071425, 0.0134582901, 0.0146755828, 0.019181719],
    5012: [0.0126675405, 0.0169415697, 0.0183475353, 0.0182626117],
}


def _take(stream, shape_rows, shape_cols, pos):
    """Next [rows, cols] row-major matrix from the stream; returns (arr, pos)."""
    n = shape_rows * shape_cols
    arr = stream[pos : pos + n].reshape(shape_rows, shape_cols)
    return arr, pos + n


@pytest.mark.skipif(
    __import__("shutil").which("g++") is None,
    reason="needs g++ to build the xorshift stream generator",
)
def test_grok1_block_matches_reference_golden(tmp_path):
    n_block = (
        DIM * DIM + 2 * DIM * KV_DIM + DIM * DIM + DIM * E
        + E * (2 * DIM * HIDDEN + HIDDEN * DIM) + 4 * DIM
    )
    n_total = n_block + DIM  # + the input activation values

    gen = os.path.join(NATIVE, "build", "xorshift-gen")
    subprocess.run(
        ["make", "-C", NATIVE, "build/xorshift-gen"], check=True, capture_output=True
    )
    stream_path = str(tmp_path / "stream.f32")
    subprocess.run(
        [gen, "123456789", str(n_total), stream_path], check=True
    )
    raw = np.fromfile(stream_path, np.float32, count=n_total)
    assert raw.size == n_total
    os.unlink(stream_path)

    # the reference stores block[f] = (float)(randomF32() / 100.0) and
    # x[i] = (float)(randomF32() / 100.0 / 78.38367176906169f)
    block = (raw[:n_block].astype(np.float64) / 100.0).astype(np.float32)
    x_pre = (
        raw[n_block:].astype(np.float64)
        / 100.0
        / np.float64(np.float32(78.38367176906169))
    ).astype(np.float32)

    # parse in the reference's load order (/root/reference/src/transformer.cpp:
    # 648-678): q, k, v, wo, router, per-expert up/gate/down, then the norms.
    # File matrices are [out, in] row-major; kernels here are [in, out].
    pos = 0
    wq, pos = _take(block, DIM, DIM, pos)
    wk, pos = _take(block, KV_DIM, DIM, pos)
    wv, pos = _take(block, KV_DIM, DIM, pos)
    wo, pos = _take(block, DIM, DIM, pos)
    router, pos = _take(block, E, DIM, pos)
    ups, gates, downs = [], [], []
    for _ in range(E):
        u, pos = _take(block, HIDDEN, DIM, pos)
        g, pos = _take(block, HIDDEN, DIM, pos)
        d, pos = _take(block, DIM, HIDDEN, pos)
        ups.append(u.T)
        gates.append(g.T)
        downs.append(d.T)
    rms_att = block[pos : pos + DIM]; pos += DIM
    rms_ffn = block[pos : pos + DIM]; pos += DIM
    rms_moe = block[pos : pos + DIM]; pos += DIM
    rms_ffn2 = block[pos : pos + DIM]; pos += DIM
    assert pos == n_block

    from dllama_tpu.models.config import GROK_EMBEDDING_SCALE, GROK_LOGIT_SCALE

    cfg = ModelConfig(
        arch="grok1", dim=DIM, hidden_dim=HIDDEN, n_layers=1, n_heads=N_HEADS,
        n_kv_heads=N_KV, vocab_size=VOCAB, seq_len=64, head_size=HEAD,
        kv_dim=KV_DIM, n_experts=E, n_active_experts=2, rope_style="half",
        hidden_act="gelu", dtype="float32",
        embedding_scale=GROK_EMBEDDING_SCALE, logit_scale=GROK_LOGIT_SCALE,
        post_norms=True,
    )
    # token 0's embedding row carries the pre-scale input; embed() applies
    # the 78.38 Grok input scale exactly like grokMulInput
    embedding = np.zeros((VOCAB, DIM), np.float32)
    embedding[0] = x_pre

    lp = {
        "wq": jnp.asarray(wq.T), "wk": jnp.asarray(wk.T), "wv": jnp.asarray(wv.T),
        "wo": jnp.asarray(wo.T),
        "moe_router": jnp.asarray(router.T),
        "moe_up": jnp.asarray(np.stack(ups)),
        "moe_gate": jnp.asarray(np.stack(gates)),
        "moe_down": jnp.asarray(np.stack(downs)),
        "rms_att": jnp.asarray(rms_att), "rms_ffn": jnp.asarray(rms_ffn),
        "rms_moe": jnp.asarray(rms_moe), "rms_ffn2": jnp.asarray(rms_ffn2),
    }
    params = {"embedding": jnp.asarray(embedding)}
    rope = llama.rope_tables(cfg)
    x = llama.embed(cfg, params, jnp.asarray([0], jnp.int32))

    k_cache = jnp.zeros((cfg.seq_len, N_KV, HEAD), jnp.float32)
    v_cache = jnp.zeros((cfg.seq_len, N_KV, HEAD), jnp.float32)
    att_out, _, _ = llama._attention(
        cfg, lp, x, llama._solo_core(cfg, rope, jnp.int32(0)), k_cache,
        v_cache, None
    )
    out = np.asarray(llama._ffn_residual(cfg, lp, x, att_out))[0]

    for off, want in GOLDEN.items():
        got = out[off : off + 4]
        np.testing.assert_allclose(
            got, np.asarray(want, np.float32), atol=3.5e-5,
            err_msg=f"offset {off}",
        )
