"""Continuous batching (Engine.batch_session + the rolling-admission server).

The slot-pool session's contract is that membership in the pool is
invisible in the tokens: a row admitted mid-flight into a half-busy pool —
or into a slab a previous request just vacated — emits EXACTLY the stream
of a solo ``generate()`` with the same SamplerConfig, and a live row nets
at least one token per chunk, so staggered arrivals can never starve or
deadlock. These tests pin all of that, engine-level and over real HTTP.
"""

import http.client
import json
import threading
import time

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

PROMPTS = [[5, 9, 3], [7], [1, 2, 3, 4, 5, 6, 11]]  # mixed lengths incl. 1


def _solo(params, prompt, steps, sampler=None, cfg=CFG):
    eng = Engine(cfg, params, SamplerConfig(temperature=0.0))
    return [t for t, _ in eng.generate(list(prompt), steps=steps,
                                       sampler=sampler)]


def _drain(sess, slots):
    """Step until every slot in ``slots`` is done; return {slot: tokens}."""
    out = {b: [] for b in slots}
    while any(not sess.is_done(b) for b in slots):
        for b, burst in sess.step_chunk().items():
            if b in out:
                out[b].extend(burst)
    return out


def test_mid_flight_admit_bit_identical_to_solo():
    """The tentpole invariant: a row admitted while the pool is mid-decode
    (sampled or greedy, any slot) emits exactly its solo stream — the
    resident batch cache, the pinned free rows, and the other rows'
    key-chain splits must all be invisible to it."""
    params = llama.random_params(CFG, seed=0, dtype=np.float32)
    samplers = [
        SamplerConfig(temperature=0.9, topp=0.95, seed=7),
        SamplerConfig(temperature=0.0, seed=1),      # greedy row in the mix
        SamplerConfig(temperature=1.3, topp=0.8, seed=42),
    ]
    want = [_solo(params, p, 12, s) for p, s in zip(PROMPTS, samplers)]

    eng = Engine(CFG, params, SamplerConfig(temperature=0.0))
    sess = eng.batch_session(max_batch=3, chunk=4)
    got = {}
    s0 = sess.admit(PROMPTS[0], steps=12, sampler=samplers[0])
    got[s0] = []
    for b, burst in sess.step_chunk().items():  # row 0 is already 4 deep...
        got[b].extend(burst)
    s1 = sess.admit(PROMPTS[1], steps=12, sampler=samplers[1])  # ...join now
    got[s1] = []
    for b, burst in sess.step_chunk().items():
        got[b].extend(burst)
    s2 = sess.admit(PROMPTS[2], steps=12, sampler=samplers[2])  # 8 deep
    got[s2] = []
    for b, tokens in _drain(sess, [s0, s1, s2]).items():
        got[b].extend(tokens)
    sess.close()
    assert [got[s0], got[s1], got[s2]] == want


def test_released_slot_reuse_no_contamination():
    """A slab vacated by release() is reused WITHOUT clearing (admit
    overwrites the prefix; positions past the new row's pos are masked) —
    the successor must still match its solo stream bit for bit."""
    params = llama.random_params(CFG, seed=3, dtype=np.float32)
    eng = Engine(CFG, params, SamplerConfig(temperature=0.0))
    sess = eng.batch_session(max_batch=2, chunk=4)

    first = sess.admit([9, 2, 4, 8, 1, 3], steps=8,
                       sampler=SamplerConfig(temperature=1.1, seed=5))
    _drain(sess, [first])
    sess.release(first)
    assert sess.free_slots == [0, 1]

    # the 1-token successor lands in the dirtiest possible slab state:
    # its pos-0 write leaves every other position holding the first
    # request's stale KV, all of which must stay masked out
    reuse = sess.admit([7], steps=10,
                       sampler=SamplerConfig(temperature=0.8, seed=11))
    assert reuse == first  # lowest free slot: genuinely the same slab
    got = _drain(sess, [reuse])[reuse]
    sess.close()
    assert got == _solo(params, [7], 10, SamplerConfig(temperature=0.8, seed=11))


def test_stop_token_truncates_inclusively_and_frees_early():
    params = llama.random_params(CFG, seed=5, dtype=np.float32)
    eng = Engine(CFG, params, SamplerConfig(temperature=0.0))
    full = _solo(params, PROMPTS[0], 16)
    # first token that does not appear earlier in the stream: stopping on it
    # pins exactly where the solo stream first emits it
    k = next(i for i, t in enumerate(full) if t not in full[:i])
    sess = eng.batch_session(max_batch=2, chunk=4)
    s0 = sess.admit(PROMPTS[0], steps=16, stop_tokens=(full[k],))
    got = _drain(sess, [s0])[s0]
    assert got == full[: k + 1]  # stop token emitted, nothing after
    sess.release(s0)
    sess.close()


def test_budget_and_accounting():
    """Bookkeeping the scheduler leans on: per-chunk bursts are never empty
    for a live row, sum to the budget, and done rows leave step_chunk()."""
    params = llama.random_params(CFG, seed=6, dtype=np.float32)
    eng = Engine(CFG, params, SamplerConfig(temperature=0.0))
    sess = eng.batch_session(max_batch=2, chunk=4)
    short = sess.admit(PROMPTS[0], steps=6)   # done after chunk 2
    long = sess.admit(PROMPTS[2], steps=11)
    bursts = {short: [], long: []}
    while not (sess.is_done(short) and sess.is_done(long)):
        fresh = sess.step_chunk()
        assert all(burst for burst in fresh.values())  # live rows always net
        for b, burst in fresh.items():
            bursts[b].append(len(burst))
    assert sum(bursts[short]) == 6 and sum(bursts[long]) == 11
    assert len(bursts[short]) == 2  # 4 + 2, absent from later chunks
    assert sess.num_live == 0 and sess.is_done(short) and sess.is_done(long)
    sess.release(short)
    assert sess.free_slots == [0]
    with pytest.raises(ValueError):
        sess.is_done(short)  # released slot is no longer queryable
    sess.close()


def test_admit_validation():
    params = llama.random_params(CFG, seed=0, dtype=np.float32)
    eng = Engine(CFG, params, SamplerConfig(temperature=0.0))
    sess = eng.batch_session(max_batch=1, chunk=2)
    with pytest.raises(ValueError):
        sess.admit([], steps=4)  # empty prompt
    sess.admit([5], steps=4)
    with pytest.raises(RuntimeError):
        sess.admit([7], steps=4)  # pool full
    sess.close()
    with pytest.raises(RuntimeError):
        sess.admit([5], steps=4)  # closed session


# ---------------------------------------------------------------------------
# Server-level: staggered arrivals through the rolling-admission scheduler
# ---------------------------------------------------------------------------

def _request(port, body):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request("POST", "/v1/chat/completions", body=json.dumps(body),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def test_staggered_arrivals_drain_without_deadlock():
    """More requests than slots, arriving spread across several decode
    chunks: the scheduler must admit them into freed slots mid-flight and
    answer every one (timeout-guarded), with the same tokens a
    batching-disabled server returns."""
    from dllama_tpu.formats.tokenizer_file import TokenizerData
    from dllama_tpu.serving.api_server import ServerState, create_server
    from dllama_tpu.tokenizer.bpe import Tokenizer

    from tests.test_llama_forward import tiny_cfg

    vocab = [b"<unk>", b"<s>", b"</s>"]
    vocab += [b"<0x%02X>" % b for b in range(256)]
    vocab += [b" ", b"e", b"t", b"he", b" the", b"hello", b" world"]
    scores = [0.0] * 259 + [-1.0, -2.0, -2.0, -1.5, -1.2, -1.1, -1.1]
    tok = Tokenizer(TokenizerData(vocab=vocab, scores=scores, bos_id=1, eos_id=2))
    cfg = tiny_cfg(vocab_size=tok.vocab_size, seq_len=512, dim=32, kv_dim=16,
                   head_size=8, hidden_dim=64)
    params = llama.random_params(cfg, seed=13)

    def run_server(window_ms):
        engine = Engine(cfg, params, SamplerConfig(temperature=0.0, seed=1))
        state = ServerState(engine, tok, cfg, model_name="tiny-test",
                            template="llama3", batch_window_ms=window_ms,
                            batch_max=2, batch_chunk=2)
        srv = create_server(state, host="127.0.0.1", port=0)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        return srv, srv.server_address[1]

    prompts = ["hello world", "the the cat", "world hello the",
               "hello the world", "t e t e"]

    def ask_all(port, stagger_s=0.0):
        replies = [None] * len(prompts)

        def one(i):
            if stagger_s:
                time.sleep(i * stagger_s)
            _, d = _request(port, {
                "model": "tiny-test", "temperature": 0.0,
                "max_tokens": 4 + 4 * (i % 3),  # mixed budgets
                "messages": [{"role": "user", "content": prompts[i]}],
            })
            replies[i] = json.loads(d)["choices"][0]["message"]["content"]

        threads = [threading.Thread(target=one, args=(i,), daemon=True)
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 120
        for t in threads:
            t.join(timeout=max(1.0, deadline - time.monotonic()))
        assert not any(t.is_alive() for t in threads), \
            "staggered requests deadlocked"
        return replies

    srv_plain, port_plain = run_server(0)
    srv_batch, port_batch = run_server(40.0)
    try:
        # warm compile caches so arrival timing isn't swamped by tracing
        _request(port_batch, {"model": "tiny-test", "temperature": 0.0,
                              "max_tokens": 2,
                              "messages": [{"role": "user", "content": "hi"}]})
        want = ask_all(port_plain)
        got = ask_all(port_batch, stagger_s=0.05)
        assert None not in got
        assert got == want
    finally:
        srv_plain.shutdown()
        srv_batch.shutdown()


# ---------------------------------------------------------------------------
# Launches that run ahead of their collection (BatchSession.ahead): the next
# decode launch is dispatched before the running one is read, its tokens and
# keys stay on the device, and what each row emits does not change
# ---------------------------------------------------------------------------

from dllama_tpu import faults, observability  # noqa: E402
from dllama_tpu.runtime.generate import host_prng_key  # noqa: E402

SAMPLED = SamplerConfig(temperature=0.9, topp=0.95, seed=2**32 + 5)
GREEDY = SamplerConfig(temperature=0.0, seed=1)


@pytest.fixture
def no_faults():
    faults.clear()
    yield
    faults.clear()


@pytest.mark.parametrize("seed", [0, 1, 2**31 - 1, 2**32 + 5, -3])
def test_the_host_key_of_a_seed_is_jax_prng_key(seed):
    import jax
    want = np.asarray(jax.random.PRNGKey(seed), np.uint32)
    got = host_prng_key(seed)
    assert got.dtype == np.uint32 and np.array_equal(got, want)


def drive(sess, arrivals, ahead: bool, each_tick=None,
          first: dict = None) -> tuple:
    """The scheduler's loop over a session, with every arrival at a fixed
    tick: admit the tick's arrivals ``(tick, prompt, steps, sampler, stop
    tokens)``, at most one standalone piece, then a chunk: ``step_chunk``
    (the synchronous loop) or ``step_ahead`` (the next launch dispatched
    before the one before it is read); release what finished.
    ``each_tick(sess, handles)`` runs at the end of every tick; ``first``
    gets the tick at which each arrival's first tokens were read.
    -> ({arrival: tokens}, {arrival: finish reason})"""
    handles, got, why = {}, {}, {}
    first = {} if first is None else first
    last = max(a[0] for a in arrivals)
    for tick in range(400):
        for i, (at, prompt, steps, sampler, stop) in enumerate(arrivals):
            if at == tick:
                h = sess.admit_begin(prompt, steps, sampler=sampler,
                                     stop_tokens=stop)
                handles[h], got[i] = i, []
        sess.prefill_step()
        fresh = sess.step_ahead() if ahead else sess.step_chunk()
        for h, toks in fresh.items():
            got[handles[h]].extend(toks)
            if toks:
                first.setdefault(handles[h], tick)
        for h in list(handles):
            if sess.is_done(h):
                why[handles.pop(h)] = sess.finish_reason(h)
                sess.release(h)
        if each_tick is not None:
            each_tick(sess, handles)
        if tick >= last and not handles and not sess.in_flight:
            return got, why
    raise AssertionError("the session did not drain")


def both(arrivals, cfg=CFG, params=None, each_tick=None, **kw) -> tuple:
    """``drive`` twice over fresh engines, synchronous (a session made to
    wait on each launch and keep the host's copies, as the paged and
    ``--tp`` sessions do) then ahead: (sync tokens, ahead tokens, sync
    reasons, ahead reasons, the ahead engine's registry). Each arrival's
    first tokens are read one tick later ahead, never more: a row that
    goes live decodes in the launch that follows, as it does in step."""
    if params is None:
        params = llama.random_params(cfg, seed=0, dtype=np.float32)
    out, firsts = [], ({}, {})
    for ahead, first in zip((False, True), firsts):
        reg = observability.MetricsRegistry()
        eng = Engine(cfg, params, SamplerConfig(temperature=0.0), metrics=reg)
        sess = eng.batch_session(**dict(dict(max_batch=3, chunk=4), **kw))
        assert sess.ahead
        sess.ahead = ahead
        out.append(drive(sess, arrivals, ahead, each_tick, first) + (reg,))
        sess.close()
    (want, want_why, _), (got, got_why, reg) = out
    assert firsts[1] == {i: t + 1 for i, t in firsts[0].items()}
    return want, got, want_why, got_why, reg


def value(reg, name: str, **labels) -> float:
    snap = reg.snapshot().get(name, {"values": []})
    return sum(v["value"] for v in snap["values"]
               if all(v["labels"].get(k) == x for k, x in labels.items()))


@pytest.mark.parametrize("layout", ["uniform", "bucketed"])
def test_launches_run_ahead_and_every_row_emits_what_it_does_in_step(layout):
    """Greedy and sampled rows admitted at different ticks, into a pool with
    a launch in flight: the ahead loop's streams are the synchronous loop's
    and each row's solo stream; one launch in flight behind the running one
    (``dllama_decode_ahead_total``), none idle but the first."""
    params = llama.random_params(CFG, seed=0, dtype=np.float32)
    arrivals = [(0, PROMPTS[0], 12, SAMPLED, ()),
                (0, PROMPTS[1], 9, GREEDY, ()),
                (2, PROMPTS[2], 10, SamplerConfig(temperature=1.3, topp=0.8,
                                                  seed=-3), ()),
                (3, [4, 4, 2], 7, GREEDY, ())]
    kw = {"bucket_kv": True, "min_bucket": 32} if layout == "bucketed" else {}
    want, got, _, why, reg = both(arrivals, max_batch=4, **kw)
    assert got == want
    for i, (_, prompt, steps, sampler, _) in enumerate(arrivals):
        assert got[i] == _solo(params, prompt, steps, sampler), i
    states = {s: value(reg, "dllama_decode_ahead_total", state=s)
              for s in ("queued", "late", "idle")}
    assert states["idle"] == 1 and states["queued"] + states["late"] > 3
    assert set(why.values()) == {"length"}
    assert value(reg, "dllama_decode_overrun_rows_total") == 0


def test_a_stop_in_mid_chunk_runs_one_chunk_over_and_is_dropped():
    """A row that stops inside launch n is in launch n + 1, dispatched
    before n was read: that chunk's tokens are dropped and counted as an
    overrun, and the stream ends where the synchronous loop's does."""
    params = llama.random_params(CFG, seed=5, dtype=np.float32)
    full = _solo(params, PROMPTS[0], 24)
    # a token first emitted in mid-chunk (not a chunk's last), launches
    # before the budget's last
    k = next(i for i, t in enumerate(full)
             if t not in full[:i] and i % 4 != 3 and 4 <= i < 16)
    arrivals = [(0, PROMPTS[0], 24, GREEDY, (full[k],)),
                (0, PROMPTS[2], 24, SAMPLED, ())]
    want, got, want_why, why, reg = both(arrivals, params=params)
    assert got == want and got[0] == full[:k + 1]
    assert why == want_why and why[0] == "stop"
    assert value(reg, "dllama_decode_overrun_rows_total") == 1


def test_a_quarantine_runs_one_chunk_over_and_its_neighbours_do_not_see_it(
        no_faults):
    """``logits:nan`` poisons row 1 of the second launch: its request ends
    with ``error`` and nothing from that launch, one launch over is dropped
    and counted, and the rows beside it emit their solo streams."""
    params = llama.random_params(CFG, seed=0, dtype=np.float32)
    arrivals = [(0, [7], 12, GREEDY, ()), (0, [5], 12, SAMPLED, ()),
                (0, [9], 12, GREEDY, ())]
    out = []
    for ahead in (False, True):
        faults.install("logits:nan:row=1,after=1,times=1")
        reg = observability.MetricsRegistry()
        eng = Engine(CFG, params, SamplerConfig(temperature=0.0), metrics=reg)
        sess = eng.batch_session(max_batch=3, chunk=4)
        out.append(drive(sess, arrivals, ahead) + (reg,))
        sess.close()
        faults.clear()
    (want, want_why, _), (got, why, reg) = out
    assert got == want and why == want_why
    assert why[1] == "error" and got[1] == want[1] and len(got[1]) == 4
    for i in (0, 2):
        assert got[i] == _solo(params, [arrivals[i][1][0]], 12,
                               arrivals[i][3])
    assert value(reg, "dllama_decode_overrun_rows_total") == 1
    assert value(reg, "dllama_numeric_quarantines_total") == 1


def test_cancel_and_export_with_a_launch_in_flight():
    """A row cancelled and released while a launch that holds it is in
    flight: that launch's tokens for it are dropped (a cancel is no
    overrun), its slot takes a new request at once, and the rows beside it
    emit what they do in the synchronous loop. ``export_row`` (preemption)
    reads the launch in flight first; a slot pool has nothing to export."""
    params = llama.random_params(CFG, seed=2, dtype=np.float32)
    arrivals = [(0, PROMPTS[0], 16, GREEDY, ()), (0, PROMPTS[2], 16,
                                                  SAMPLED, ()),
                (4, [3], 8, SAMPLED, ())]
    ticks: dict = {}

    def each_tick(sess, handles):
        n = ticks[id(sess)] = ticks.get(id(sess), -1) + 1
        h = next((h for h, i in handles.items() if i == 0), None)
        if n == 0:
            with pytest.raises(RuntimeError, match="paged"):
                sess.export_row(h)
        elif n == 1:
            sess.cancel(h)
            sess.release(h)
            del handles[h]

    want, got, _, _, reg = both(arrivals, params=params, each_tick=each_tick)
    assert got[1] == want[1] and got[2] == want[2]
    assert got[2] == _solo(params, [3], 8, SAMPLED)
    # cancelled at the second tick: there the synchronous loop has read two
    # launches, the ahead loop one (the second, in flight, is dropped)
    assert len(want[0]) == 8 and got[0] == want[0][:4]
    assert value(reg, "dllama_decode_overrun_rows_total") == 0


def test_a_bucket_migration_reads_the_tokens_from_the_device():
    """Rows outgrow their slab of 8 and migrate to 16, 32, 64 while their
    tokens and keys live on the device: the streams are the synchronous
    loop's and the solo ones."""
    params = llama.random_params(CFG, seed=4, dtype=np.float32)
    arrivals = [(0, PROMPTS[2], 30, SAMPLED, ()), (1, [6], 28, GREEDY, ()),
                (1, [1, 2], 20, SamplerConfig(temperature=1.1, seed=9), ())]
    migrations = []
    want, got, _, _, _ = both(
        arrivals, params=params, bucket_kv=True, min_bucket=8,
        each_tick=lambda s, h: migrations.append(s.migrations))
    assert got == want and migrations[-1] >= 3
    for i, (_, prompt, steps, sampler, _) in enumerate(arrivals):
        assert got[i] == _solo(params, prompt, steps, sampler), i


def test_close_with_a_launch_in_flight_reads_it_first():
    params = llama.random_params(CFG, seed=0, dtype=np.float32)
    eng = Engine(CFG, params, SamplerConfig(temperature=0.0), metrics=None)
    sess = eng.batch_session(max_batch=2, chunk=4)
    h = sess.admit(PROMPTS[0], steps=12)
    assert sess.step_ahead() == {} and sess.in_flight == 1
    first = sess.step_ahead()
    assert len(first[h]) == 4 and sess.in_flight == 1
    sess.close()
    assert sess.in_flight == 0 and sess.cache is None
    sess.close()  # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        sess.launch()


def _serve(requests, ahead: bool, stop=(), **state_kw) -> list:
    """``requests`` [(prompt, max tokens, SamplerConfig)] submitted a few ms
    apart from threads to a ServerState's batcher over a tiny engine -> each
    one's tokens. ``ahead=False`` has every session wait on each launch: the
    synchronous loop, for comparison."""
    from dllama_tpu.serving.api_server import ServerState
    from tests.test_api_server import make_tokenizer
    from tests.test_llama_forward import tiny_cfg

    tok = make_tokenizer()
    cfg = tiny_cfg(vocab_size=tok.vocab_size, seq_len=256, dim=32, kv_dim=16,
                   head_size=8, hidden_dim=64)
    engine = Engine(cfg, llama.random_params(cfg, seed=13),
                    SamplerConfig(temperature=0.0, seed=1))
    real = engine.batch_session

    def session(*a, **k):
        sess = real(*a, **k)
        sess.ahead = sess.ahead and ahead
        return sess

    engine.batch_session = session
    state = ServerState(engine, tok, cfg, model_name="tiny", template="llama3",
                        **dict(dict(batch_window_ms=20.0, batch_max=3,
                                    batch_chunk=4, prefill_chunk=8,
                                    kv_bucket_min=64), **state_kw))
    if stop:
        state.stop_token_ids = lambda: tuple(stop)
    out = [None] * len(requests)

    def one(i):
        prompt, n, sampler = requests[i]
        out[i] = state.batcher.submit(prompt, n, sampler, slo_class="batch")

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(requests))]
    for t in threads:
        t.start()
        time.sleep(0.004)
    for t in threads:
        t.join(timeout=300)
    assert not any(t.is_alive() for t in threads)
    return out


#: prompts of 20-34 tokens ride 8 tokens a launch (2 a step): their
#: prefixes end in mid-launch; the first of a burst takes standalone pieces
SERVED = [([1] + [5 + i] * (19 + 5 * i), 10 + 3 * i,
           SAMPLED if i % 2 else GREEDY) for i in range(5)]


def test_the_ahead_server_loop_streams_what_the_synchronous_loop_streams():
    assert _serve(SERVED, ahead=True) == _serve(SERVED, ahead=False)


def test_the_ahead_server_loop_stops_where_the_synchronous_loop_stops():
    free = _serve(SERVED[:3], ahead=False)
    stop = next(t for t in free[1][5:] if t not in free[1][:5])
    want = _serve(SERVED[:3], ahead=False, stop=(stop,))
    assert want[1] == free[1][:free[1].index(stop) + 1]
    assert _serve(SERVED[:3], ahead=True, stop=(stop,)) == want


def test_a_launch_length_read_too_long_comes_down_from_launches_seen_ended():
    """A launch that compiled reads seconds: one such reading does not move
    the median, and launches the host found already ended bring a kind
    that reads too long down to when they were known to have ended, so
    the scheduler cannot keep waiting past every launch's end."""
    from dllama_tpu.runtime.generate import _Launch

    eng = Engine(CFG, llama.random_params(CFG, seed=0, dtype=np.float32),
                 SamplerConfig(temperature=0.0), metrics=None)
    sess = eng.batch_session(max_batch=2, chunk=4)

    def timed(start, end=None, bound=None):
        rec = _Launch("late", 0)
        rec.start, rec.end, rec.bound = start, end, bound
        sess._time(rec)

    for i in range(4):
        timed(i, end=i + 0.06)
    timed(5, end=9.0)  # compiled
    for i in range(10, 14):
        timed(i, end=i + 0.06)
    live = _Launch("queued", 0)
    live.start = 20.0
    sess._flight.append(live)
    due, spread = sess.due()
    assert due == pytest.approx(20.06) and spread < 1.0
    # a stale reading of 0.5 s, then launches found ended 0.1 s in
    sess._period[(False, 0)].clear()
    timed(30, end=30.5)
    for i in range(40, 45):
        timed(i, bound=i + 0.1)
    assert sess.due()[0] == pytest.approx(20.1)
    sess._flight.clear()
    sess.close()
