"""Chunked prefill + bucketed slot KV (the perf tentpole).

Two invariants under test. (1) Identity: consuming a prompt in fixed
token-budget pieces — solo (``Engine.prefill(chunk=...)``) or pooled
(``admit_begin`` + ``prefill_step`` interleaved with ``step_chunk``) —
produces EXACTLY the streams of monolithic prefill, and its logits to the
last places of a float32 sum (a shorter piece is another compiled program:
``test_solo_chunked_prefill_logits_bit_identical`` says what is exact and
what is bounded): each piece
writes its K/V before any later query attends, so causal masking makes the
split invisible. Migration between KV buckets carries the whole attended
slab plus the host sampler chain, so a row crossing buckets mid-stream is
equally invisible. (2) Capacity: under the same modeled HBM budget
(max_batch * seq_len KV token-slots), length-bucketed slot pools admit
STRICTLY more short rows than the uniform full-context slab — the reason
the bucketing exists.
"""

import numpy as np
import pytest

from dllama_tpu import faults
from dllama_tpu.models import llama
from dllama_tpu.models.config import ModelConfig
from dllama_tpu.runtime.generate import Engine
from dllama_tpu.runtime.sampler import SamplerConfig

CFG = ModelConfig(
    arch="llama", dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
    vocab_size=96, seq_len=64, head_size=16, kv_dim=32, dtype="float32",
)

LONG_PROMPT = [(i * 7 + 3) % 96 for i in range(23)]


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def _solo(params, prompt, steps, sampler=None):
    eng = Engine(CFG, params, SamplerConfig(temperature=0.0))
    return [t for t, _ in eng.generate(list(prompt), steps=steps,
                                       sampler=sampler)]


def _drain_interleaved(sess, out):
    """One prefill_step per step_chunk — the scheduler's tick — until every
    tracked slot is done; extends ``out`` in place."""
    while any(not sess.is_done(b) for b in out):
        sess.prefill_step()
        for b, burst in sess.step_chunk().items():
            if b in out:
                out[b].extend(burst)
    return out


# ---------------------------------------------------------------------------
# solo: chunked == monolithic, to the bit
# ---------------------------------------------------------------------------

def _last_places(got, want) -> float:
    """The largest difference, in units of the last place of ``want``'s
    largest magnitude."""
    return float(np.abs(got - want).max() / np.spacing(np.abs(want).max()))


def test_solo_chunked_prefill_logits_bit_identical():
    """Every chunk size (including ragged last pieces and chunk=1) must
    reproduce the monolithic final-position logits — the causal
    write-before-attend argument. Cache contents are compared only over
    REAL positions: padded-tail slots hold whatever garbage the prefill
    bucket wrote, by design.

    EXACT, to the bit: the greedy token; layer 0's K/V (every position
    written where and as the monolithic prefill writes it); and everything
    when the one piece is the monolithic program (chunk >= n). A piece
    shorter than the monolithic bucket is ANOTHER program, and XLA:CPU sums
    attention's two contractions (``gqa_attention``'s q.K and att.V einsums,
    batched over the KV heads) in an order that follows the number of query
    rows: 8 rows against the same cache give other last bits than 32 (a
    plain ``x @ w`` does not depend on its row count, nor do the softmax's
    sums). So from layer 0's attention on, the two sides are apart by the
    rounding of a float32 sum and no more: measured 2.2 last places of the
    largest logit and 2.0 of the largest cached value; held to 8 and 8."""
    params = llama.random_params(CFG, seed=0, dtype=np.float32)
    eng = Engine(CFG, params, SamplerConfig(temperature=0.0))
    logits_mono, cache_mono = eng.prefill(eng.new_cache(), LONG_PROMPT)
    ref = np.asarray(logits_mono)
    n = len(LONG_PROMPT)
    for chunk in (1, 4, 7, 16, n, n + 5):
        logits, cache = eng.prefill(eng.new_cache(), LONG_PROMPT, chunk=chunk)
        logits = np.asarray(logits)
        assert logits.argmax() == ref.argmax(), f"chunk={chunk}"
        if chunk >= n:
            assert np.array_equal(logits, ref), f"chunk={chunk}"
        else:
            assert _last_places(logits, ref) <= 8, f"chunk={chunk}"
        for k in cache_mono:  # [L, S, kv, hd]: positions on axis 1
            a = np.asarray(cache[k])[:, :n]
            b = np.asarray(cache_mono[k])[:, :n]
            exact = slice(None) if chunk >= n else slice(0, 1)
            assert np.array_equal(a[exact], b[exact]), \
                f"chunk={chunk} cache[{k}]"
            assert _last_places(a, b) <= 8, f"chunk={chunk} cache[{k}]"


def test_solo_prefill_chunk_validation():
    params = llama.random_params(CFG, seed=0, dtype=np.float32)
    eng = Engine(CFG, params, SamplerConfig(temperature=0.0))
    with pytest.raises(ValueError):
        eng.prefill(eng.new_cache(), LONG_PROMPT, chunk=0)


# ---------------------------------------------------------------------------
# pooled: chunked admission under live neighbours, buckets, migration
# ---------------------------------------------------------------------------

def test_chunked_admission_bit_identical_with_resident_row():
    """The tentpole scenario: a long prompt admitted incrementally into a
    pool where a resident row KEEPS DECODING between prefill pieces. Both
    streams must equal their solo runs bit for bit — the resident row must
    not see the newcomer's prefill, and the newcomer's chunked cache must
    equal a monolithic one. Both ways a prompt reaches the cache are here:
    in the uniform slab the newcomer shares the resident's pool and RIDES
    its decode chunks (prefill_chunk 5 over 4 steps: one token a step;
    tests/test_prompt_rides.py has the mechanism's own tests); bucketed, it
    is placed in a 32-slot pool of its own where nothing decodes, and takes
    standalone pieces of 5."""
    params = llama.random_params(CFG, seed=1, dtype=np.float32)
    s_res = SamplerConfig(temperature=0.9, topp=0.95, seed=7)
    s_new = SamplerConfig(temperature=1.2, topp=0.9, seed=23)
    want_res = _solo(params, [5, 9, 3], 16, s_res)
    want_new = _solo(params, LONG_PROMPT, 10, s_new)

    eng = Engine(CFG, params, SamplerConfig(temperature=0.0))
    for bucket_kv in (False, True):
        sess = eng.batch_session(max_batch=3, chunk=4, bucket_kv=bucket_kv,
                                 min_bucket=8, prefill_chunk=5)
        got = {}
        res = sess.admit([5, 9, 3], steps=16, sampler=s_res)
        got[res] = []
        for b, burst in sess.step_chunk().items():  # resident row is 4 deep
            got[b].extend(burst)
        new = sess.admit_begin(LONG_PROMPT, steps=10, sampler=s_new)
        got[new] = []
        assert new in sess.pending_prefills
        # 22-token prefix at 5 tokens/tick: the row must stay mid-prefill
        # across several ticks while the resident row nets tokens each tick
        ticks_mid_prefill = 0
        while new in sess.pending_prefills:
            adv = sess.prefill_step()
            fresh = sess.step_chunk()
            assert (adv is None) == (not bucket_kv)
            assert [r[0] for r in sess.rode] == ([] if bucket_kv else [new])
            finished = adv[1] if bucket_kv else sess.rode[0][3]
            if not finished:
                assert new not in fresh  # not live until the prefix completes
                ticks_mid_prefill += 1
            if res in fresh and fresh[res] == []:
                pytest.fail("resident row starved during prefill")
            for b, burst in fresh.items():
                got[b].extend(burst)
        assert ticks_mid_prefill >= 3
        _drain_interleaved(sess, got)
        assert sess.prefill_ms > 0.0
        sess.close()
        assert got[res] == want_res, f"bucket_kv={bucket_kv}"
        assert got[new] == want_new, f"bucket_kv={bucket_kv}"


def test_migration_preserves_stream_and_counts():
    """A tiny min_bucket forces rows through several bucket migrations
    mid-stream; tokens (sampled — the PRNG chain must survive the move)
    still equal solo, and the session counts the migrations."""
    params = llama.random_params(CFG, seed=2, dtype=np.float32)
    samplers = [SamplerConfig(temperature=1.1, topp=0.9, seed=5),
                SamplerConfig(temperature=0.0, seed=1)]
    prompts = [[9, 2, 4], [7]]
    want = [_solo(params, p, 30, s) for p, s in zip(prompts, samplers)]

    eng = Engine(CFG, params, SamplerConfig(temperature=0.0))
    sess = eng.batch_session(max_batch=4, chunk=3, bucket_kv=True,
                             min_bucket=4, prefill_chunk=2)
    out = {}
    for p, s in zip(prompts, samplers):
        h = sess.admit_begin(p, steps=30, sampler=s)
        out[h] = []
    _drain_interleaved(sess, out)
    # rows reach position ~32 from 4-slot slabs: 4->8->16->32 per row
    assert sess.migrations >= 4
    got = [out[h] for h in sorted(out)]
    sess.close()
    assert got == want


def test_bucketed_pools_admit_strictly_more_rows():
    """The capacity acceptance bar: at the SAME modeled budget
    (max_batch * seq_len token-slots), short requests pack strictly more
    rows bucketed than uniform — uniform spends a full-context row per
    request regardless of length."""
    params = llama.random_params(CFG, seed=0, dtype=np.float32)
    eng = Engine(CFG, params, SamplerConfig(temperature=0.0))

    def admit_until_full(sess):
        n = 0
        while sess.can_admit(3, 4):  # short prompt, short completion
            sess.admit([5, 9, 3], steps=4)
            n += 1
        return n

    uni = eng.batch_session(max_batch=2, chunk=4)
    bkt = eng.batch_session(max_batch=2, chunk=4, bucket_kv=True,
                            min_bucket=8)
    n_uni = admit_until_full(uni)
    n_bkt = admit_until_full(bkt)
    assert uni.budget_tokens == bkt.budget_tokens
    assert n_uni == 2  # the uniform slab: one row per slot, length-blind
    assert n_bkt > n_uni  # 8-slot reservations pack 64/8 = 8 rows per slot
    # worst-case requests degrade gracefully TO the uniform count, never
    # below it: bucketing is a strict win
    full = eng.batch_session(max_batch=2, chunk=4, bucket_kv=True,
                             min_bucket=8)
    m = 0
    while full.can_admit(3, CFG.seq_len):
        full.admit_begin([5, 9, 3], steps=CFG.seq_len)
        m += 1
    assert m == 2
    for s in (uni, bkt, full):
        s.close()


def test_cancel_mid_prefill_frees_slot_and_budget():
    """Cancelling an admission whose prompt is still being consumed must
    drop the pending prefill immediately and, after release(), hand back
    the row AND the KV reservation — the slab is reusable by a successor
    whose stream still matches solo."""
    params = llama.random_params(CFG, seed=3, dtype=np.float32)
    eng = Engine(CFG, params, SamplerConfig(temperature=0.0))
    sess = eng.batch_session(max_batch=1, chunk=4, bucket_kv=True,
                             min_bucket=8, prefill_chunk=4)
    h = sess.admit_begin(LONG_PROMPT, steps=40)
    adv = sess.prefill_step()  # consume one piece, then abandon
    assert adv == (h, False)
    assert not sess.can_admit(3, 4)  # worst-case reservation holds the pool
    sess.cancel(h)
    assert sess.pending_prefills == []
    assert sess.is_done(h) and sess.finish_reason(h) is None
    assert sess.step_chunk() == {}  # cancelled row never decodes
    sess.release(h)
    assert sess.reserved_tokens == 0
    assert sess.can_admit(3, 4)
    scfg = SamplerConfig(temperature=0.8, seed=11)
    h2 = sess.admit([7], steps=10, sampler=scfg)
    out = _drain_interleaved(sess, {h2: []})[h2]
    sess.close()
    assert out == _solo(params, [7], 10, scfg)


def test_prefill_chunk_fault_seam():
    """The chaos seam: a fault planted at the prefill_chunk site fires
    inside prefill_step (typed, not a hang), and the admission survives —
    the cursor hasn't advanced, so a retry consumes the same piece and the
    stream still matches solo."""
    params = llama.random_params(CFG, seed=4, dtype=np.float32)
    scfg = SamplerConfig(temperature=0.0, seed=1)
    want = _solo(params, LONG_PROMPT, 6, scfg)
    eng = Engine(CFG, params, SamplerConfig(temperature=0.0))
    sess = eng.batch_session(max_batch=1, chunk=4, prefill_chunk=6)
    h = sess.admit_begin(LONG_PROMPT, steps=6, sampler=scfg)
    faults.install("prefill_chunk:raise:times=1")
    with pytest.raises(faults.FaultInjected):
        sess.prefill_step()
    assert h in sess.pending_prefills  # still admitted, still resumable
    out = _drain_interleaved(sess, {h: []})[h]
    sess.close()
    assert out == want
