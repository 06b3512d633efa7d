"""Prompt-lookup speculative decoding (Engine.generate_spec): exact
greedy/sampled equivalence, multi-token acceptance on repetitive output,
session resume."""

import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.models import llama
from dllama_tpu.models.config import ModelConfig
from dllama_tpu.runtime.generate import Engine, _NgramIndex
from dllama_tpu.runtime.sampler import SamplerConfig

CFG = ModelConfig(
    arch="llama", dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=4,
    vocab_size=64, seq_len=128, head_size=16, kv_dim=64, dtype="float32",
)


def _engine(seed=0, kind=None, cfg=CFG):
    params = llama.random_params(cfg, seed=seed)
    if kind:
        params = llama.quantize_params(params, kind)
    return Engine(cfg, params, SamplerConfig(temperature=0.0, seed=1))


def test_ngram_index_draft_lookup():
    idx = _NgramIndex(3)
    idx.extend([1, 2, 3, 9, 9, 1, 2])
    assert idx.draft(3, 2) == [9, 9]  # [1,2]+pending 3 matched at position 0
    assert idx.draft(7, 2) == []      # tail [2,7] ... no such n-gram
    assert idx.draft(3, 0) == []
    fresh = _NgramIndex(3)
    fresh.extend([1, 2])
    assert fresh.draft(3, 2) == []    # no earlier occurrence yet
    # incremental extension keeps the LATEST occurrence
    idx.extend([3, 5, 1, 2])
    assert idx.draft(3, 2) == [5, 1]  # now matches the more recent [1,2,3]


def test_ngram_index_repeated_token_runs_still_draft():
    """Degenerate repetition (ctx [5,5,5,5], pending 5): the LATEST [5,5,5]
    ends flush at the context end with an empty continuation — the index
    must fall back to the prior occurrence and still draft (regression:
    returning [] here degrades spec decoding to 1 token/step on exactly the
    most draftable text)."""
    idx = _NgramIndex(3)
    idx.extend([5, 5, 5, 5])
    assert idx.draft(5, 4) == [5]  # prior occurrence's 1-token continuation
    idx.extend([5, 5])
    assert idx.draft(5, 3) == [5]  # same as the old backward scan drafted


def test_spec_matches_plain_greedy():
    """Speculative greedy must emit EXACTLY the plain greedy stream — same
    tokens, same count — for multi-token and single-token prompts."""
    for prompt in ([1, 5, 9], [7]):
        want = [t for t, _ in _engine().generate(prompt, steps=40)]
        got = [t for t, _ in _engine().generate_spec(prompt, steps=40)]
        assert got == want, (prompt, got, want)


def test_spec_matches_plain_greedy_quantized():
    want = [t for t, _ in _engine(kind="q40").generate([2, 4], steps=24)]
    got = [t for t, _ in _engine(kind="q40").generate_spec([2, 4], steps=24)]
    assert got == want


@pytest.mark.parametrize("temp,topp", [(0.7, 1.0), (1.0, 0.9)])
def test_spec_sampled_matches_plain_sampled(temp, topp):
    """Sampled spec decoding replays generate()'s per-token key chain, so
    the stream must be bit-identical to plain sampled decode with the same
    SamplerConfig — acceptance rate changes, output never does."""
    scfg = SamplerConfig(temperature=temp, topp=topp, seed=123)
    for prompt in ([1, 5, 9], [7]):
        want = [t for t, _ in _engine().generate(prompt, steps=32, sampler=scfg)]
        got = [t for t, _ in _engine().generate_spec(
            prompt, steps=32, sampler=scfg)]
        assert got == want, (prompt, got, want)


def test_spec_accepts_multi_token_batches():
    """Random tiny models collapse into repeating tokens under greedy decode;
    the n-gram draft must then accept >1 token per verify step (fewer device
    steps than tokens), which is the whole point."""
    eng = _engine()
    toks = []
    steps_with_time = 0
    for t, s in eng.generate_spec([1, 5, 9], steps=40):
        toks.append(t)
        if s.generation_ms > 0.0:
            steps_with_time += 1  # one per device dispatch (first of a batch)
    assert len(toks) == 40
    # the output must actually repeat for this test to mean anything
    assert len(set(toks[-16:])) < 8
    assert steps_with_time < len(toks), (steps_with_time, len(toks))


def test_spec_session_resume_matches_uninterrupted():
    eng = _engine()
    part1 = [t for t, _ in eng.generate_spec([1, 5, 9], steps=10)]
    sess = eng.final_session
    part2 = [t for t, _ in eng.generate_spec([], steps=10, session=sess)]
    full = [t for t, _ in _engine().generate_spec([1, 5, 9], steps=20)]
    assert part1 + part2 == full


def test_spec_resume_with_history_stays_exact():
    """history= feeds the prior conversation to the n-gram index (better
    drafts on warm resumes); the emitted stream must be unchanged by it."""
    eng = _engine()
    part1 = [t for t, _ in eng.generate_spec([1, 5, 9], steps=10)]
    sess = eng.final_session
    consumed = [1, 5, 9] + part1[:-1]  # pending = part1[-1], not yet consumed
    part2 = [t for t, _ in eng.generate_spec(
        [], steps=10, session=sess, history=consumed)]
    full = [t for t, _ in _engine().generate_spec([1, 5, 9], steps=20)]
    assert part1 + part2 == full


def test_spec_stop_token_mid_batch():
    eng = _engine()
    ref = [t for t, _ in _engine().generate_spec([1, 5, 9], steps=40)]
    stop = ref[len(ref) // 2]
    got = [t for t, _ in eng.generate_spec([1, 5, 9], steps=40,
                                           stop_tokens=(stop,))]
    assert got == ref[: ref.index(stop) + 1]
    # resume after the stop continues the exact greedy stream
    sess = eng.final_session
    cont = [t for t, _ in eng.generate_spec([], steps=5, session=sess)]
    assert cont == ref[ref.index(stop) + 1 : ref.index(stop) + 6]


def test_spec_sampled_stop_keeps_engine_chain_aligned():
    """A stop token truncating an accepted batch must truncate the key-chain
    advancement with it: after the stop, a PLAIN generation on the same
    engine must match an engine that never speculated (regression: advancing
    the chain by the full batch desynced later turns)."""
    def mk():
        return Engine(CFG, llama.random_params(CFG, seed=0),
                      SamplerConfig(temperature=0.8, seed=9))
    probe = [t for t, _ in mk().generate([1, 5, 9], steps=24)]
    stop = probe[12]  # a token known to occur mid-stream

    e_plain, e_spec = mk(), mk()
    a1 = [t for t, _ in e_plain.generate([1, 5, 9], steps=24,
                                         stop_tokens=(stop,))]
    b1 = [t for t, _ in e_spec.generate_spec([1, 5, 9], steps=24,
                                             stop_tokens=(stop,))]
    assert a1 == b1
    # the engines' key chains must now be in the same state: continue PLAIN
    # on both and compare
    a2 = [t for t, _ in e_plain.generate([], steps=6,
                                         session=e_plain.final_session)]
    b2 = [t for t, _ in e_spec.generate([], steps=6,
                                        session=e_spec.final_session)]
    assert a2 == b2


def test_greedy_spec_advances_engine_key_chain_like_plain():
    """At temperature 0 plain generate() still consumes one engine key per
    emitted token; generate_spec must consume identically, so a later
    SAMPLED call on the same engine chain is bit-identical whether the
    earlier greedy call was speculated or not (ADVICE r3)."""
    plain, spec = _engine(), _engine()
    n = len([t for t, _ in plain.generate([1, 5, 9], steps=10)])
    m = len([t for t, _ in spec.generate_spec([1, 5, 9], steps=10)])
    assert n == m
    assert np.array_equal(np.asarray(plain._key), np.asarray(spec._key))


def test_spec_first_token_stats_report_prefill():
    """The first (prefill-produced) token's stats carry the prefill cost,
    exactly like plain generate()'s first token (ADVICE r3: spec runs must
    not silently exclude prefill from per-token averages)."""
    eng = _engine()
    stats = [s for _, s in eng.generate_spec([1, 5, 9], steps=4)]
    assert stats[0].generation_ms == eng.prefill_ms > 0.0
    assert stats[0].inference_ms == eng.prefill_ms


# --- speculative decoding x quantized MoE (the r03-flagged combination) ---

MOE_CFG = ModelConfig(
    arch="mixtral", dim=64, hidden_dim=128, n_layers=2, n_heads=4,
    n_kv_heads=4, vocab_size=64, seq_len=128, head_size=16, kv_dim=64,
    n_experts=16, n_active_experts=2, rope_style="half", dtype="float32",
)


def test_spec_matches_plain_greedy_quantized_moe():
    """Greedy spec decoding on a QUANTIZED MoE must emit exactly the plain
    stream: the verify step runs T = draft+1 rows through the MoE FFN, a
    shape plain decode never sees."""
    want = [t for t, _ in _engine(kind="q40", cfg=MOE_CFG).generate(
        [1, 5, 9], steps=24)]
    got = [t for t, _ in _engine(kind="q40", cfg=MOE_CFG).generate_spec(
        [1, 5, 9], steps=24, draft_len=4)]
    assert got == want and len(want) == 24


def test_spec_verify_routes_to_selected_experts(monkeypatch):
    """A spec verify batch (T = draft+1 = 5, T*k = 10 < E = 16) must ROUTE
    to the selected-experts decode path rather than the all-experts dense
    combine (an earlier review: the old T==1 gate streamed every expert's
    planes on exactly the verify steps). What this proves: the gate admits
    the verify shape; _moe_decode_selected's own cap=min(E, T*k) slicing is
    covered by tests/test_moe.py and test_tp_moe_quant.py."""
    from dllama_tpu.models import moe as moe_mod

    seen_t = []
    real = moe_mod._moe_decode_selected

    def spy(cfg, lp, xb, layer, *a, **k):
        seen_t.append(int(xb.shape[0]))
        return real(cfg, lp, xb, layer, *a, **k)

    monkeypatch.setattr(moe_mod, "_moe_decode_selected", spy)
    list(_engine(kind="q40", cfg=MOE_CFG).generate_spec(
        [1, 5, 9], steps=12, draft_len=4))
    # each shape traces exactly once (jit caching), so one T=5 record
    # proves every verify step took the selected path
    assert 5 in seen_t, seen_t
