"""OpenAI-compatible API server tests: request/response shape, SSE streaming,
stop sequences, per-request sampler settings (mirrors the reference server's
handled surface, `/root/reference/src/apps/dllama-api/dllama-api.cpp:202-322`)."""

import http.client
import json
import threading
import time

import pytest

from dllama_tpu.formats.tokenizer_file import TokenizerData
from dllama_tpu.models import llama
from dllama_tpu.runtime.generate import Engine
from dllama_tpu.runtime.sampler import SamplerConfig
from dllama_tpu.serving.api_server import ServerState, StopDetector, create_server
from dllama_tpu.tokenizer.bpe import Tokenizer

from tests.test_llama_forward import tiny_cfg


# ---------------------------------------------------------------------------
# StopDetector unit tests
# ---------------------------------------------------------------------------

def test_stop_detector_basic():
    d = StopDetector(["END"])
    assert d.feed("hello ") == ("hello ", False)
    assert d.feed("END world") == ("", True)
    assert d.stopped


def test_stop_detector_spanning_pieces():
    d = StopDetector(["STOP"])
    out1, s1 = d.feed("abcST")
    assert (out1, s1) == ("abc", False)  # "ST" withheld: possible prefix
    out2, s2 = d.feed("OPxyz")
    assert (out2, s2) == ("", True)


def test_stop_detector_false_prefix_released():
    d = StopDetector(["STOP"])
    out1, _ = d.feed("abST")
    assert out1 == "ab"
    out2, stopped = d.feed("izzle")  # "ST"+"izzle" is not a stop
    assert out2 == "STizzle"
    assert not stopped
    assert d.flush() == ""


def test_stop_detector_earliest_occurrence_wins():
    # stop list order must not matter: "l" occurs before "world"
    d = StopDetector(["world", "l"])
    out, stopped = d.feed("hello world")
    assert (out, stopped) == ("he", True)


def test_stop_detector_no_stops_passthrough():
    d = StopDetector([])
    assert d.feed("anything") == ("anything", False)


def test_stop_detector_flush_releases_partial_prefix():
    # a dangling possible-prefix is legitimate output when the stream ends
    # on EOS/length (only an actual stop hit may eat it), and flush drains
    d = StopDetector(["STOP"])
    assert d.feed("abST") == ("ab", False)
    assert d.flush() == "ST"
    assert d.flush() == ""


def test_stop_detector_flush_after_stop_is_empty():
    d = StopDetector(["END"])
    out, stopped = d.feed("the END tail")
    assert (out, stopped) == ("the ", True)
    assert d.flush() == ""  # the hold died with the stop hit
    assert d.feed("more") == ("", True)  # stopped detectors stay stopped


def test_stop_detector_longest_partial_held_across_stops():
    # with several stops, the LONGEST tail that prefixes any of them is
    # withheld — flushing exactly that tail at end of stream
    d = StopDetector(["abcd", "cd"])
    assert d.feed("xabc") == ("x", False)
    assert d.flush() == "abc"


def test_stop_detector_single_char_stop_holds_nothing():
    # a 1-char stop has no proper prefix: nothing is ever withheld
    d = StopDetector(["\n"])
    assert d.feed("line") == ("line", False)
    assert d.flush() == ""


# ---------------------------------------------------------------------------
# Server integration (tiny synthetic model, real HTTP over localhost)
# ---------------------------------------------------------------------------

def make_tokenizer() -> Tokenizer:
    vocab = [b"<unk>", b"<s>", b"</s>"]
    vocab += [b"<0x%02X>" % b for b in range(256)]
    vocab += [b" ", b"e", b"t", b"he", b" the", b"hello", b" world"]
    scores = [0.0] * 259 + [-1.0, -2.0, -2.0, -1.5, -1.2, -1.1, -1.1]
    return Tokenizer(TokenizerData(vocab=vocab, scores=scores, bos_id=1, eos_id=2))


@pytest.fixture(scope="module")
def server():
    tok = make_tokenizer()
    cfg = tiny_cfg(vocab_size=tok.vocab_size, seq_len=512, dim=32, kv_dim=16,
                   head_size=8, hidden_dim=64)
    params = llama.random_params(cfg, seed=13)
    engine = Engine(cfg, params, SamplerConfig(temperature=0.0, seed=1))
    state = ServerState(engine, tok, cfg, model_name="tiny-test", template="llama3")
    srv = create_server(state, host="127.0.0.1", port=0)
    port = srv.server_address[1]
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    yield port
    srv.shutdown()


def request(port, method, path, body=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request(method, path, body=json.dumps(body) if body else None,
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def chat_body(**kw):
    body = {
        "model": "tiny-test",
        "messages": [{"role": "user", "content": "hello world"}],
        "max_tokens": 8,
        "temperature": 0.0,
    }
    body.update(kw)
    return body


def test_paged_kv_server_surfaces_occupancy():
    """With --kv-pages active, /ready and /stats must carry the page-pool
    and prefix-cache picture the multi-replica router weighs by."""
    tok = make_tokenizer()
    cfg = tiny_cfg(vocab_size=tok.vocab_size, seq_len=512, dim=32, kv_dim=16,
                   head_size=8, hidden_dim=64)
    params = llama.random_params(cfg, seed=13)
    engine = Engine(cfg, params, SamplerConfig(temperature=0.0, seed=1))
    state = ServerState(engine, tok, cfg, model_name="tiny-test",
                        template="llama3", batch_window_ms=5.0, kv_pages=16)
    srv = create_server(state, host="127.0.0.1", port=0)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        status, _ = request(port, "POST", "/v1/chat/completions", chat_body())
        assert status == 200
        status, data = request(port, "GET", "/ready")
        assert status == 200
        info = json.loads(data)
        assert "kv_pages" in info and "prefix_hit_rate" in info
        assert info["kv_tokens_reserved"] == 0  # request finished: released
        status, data = request(port, "GET", "/stats")
        assert status == 200
        assert "kv_pages" in json.loads(data)["load"]
    finally:
        srv.shutdown()


def test_models_endpoint(server):
    status, data = request(server, "GET", "/v1/models")
    assert status == 200
    obj = json.loads(data)
    assert obj["data"][0]["id"] == "tiny-test"


def test_completion_basic(server):
    status, data = request(server, "POST", "/v1/chat/completions", chat_body())
    assert status == 200
    obj = json.loads(data)
    choice = obj["choices"][0]
    assert choice["message"]["role"] == "assistant"
    assert isinstance(choice["message"]["content"], str)
    assert choice["finish_reason"] in ("stop", "length")
    assert obj["usage"]["completion_tokens"] <= 8
    assert obj["usage"]["total_tokens"] == (
        obj["usage"]["prompt_tokens"] + obj["usage"]["completion_tokens"]
    )


def _handler_cpu_seconds(port) -> float:
    _, text = request(port, "GET", "/metrics")
    for line in text.decode().splitlines():
        if line.startswith("dllama_handler_cpu_seconds_total "):
            return float(line.split()[1])
    raise AssertionError("no dllama_handler_cpu_seconds_total on /metrics")


def test_a_request_adds_its_handler_threads_cpu_seconds(server):
    before = _handler_cpu_seconds(server)
    status, _ = request(server, "POST", "/v1/chat/completions", chat_body())
    assert status == 200
    # counted as do_POST ends, a moment after the client has its answer
    deadline = time.monotonic() + 30
    while (_handler_cpu_seconds(server) <= before
           and time.monotonic() < deadline):
        time.sleep(0.01)
    assert _handler_cpu_seconds(server) > before


def test_metrics_carry_no_family_that_nobody_reads(server):
    _, text = request(server, "GET", "/metrics")
    for gone in ("dllama_batch_queue_depth", "dllama_slots_occupied",
                 "dllama_class_preempted_rows", "dllama_class_rejections_total",
                 "dllama_kv_tokens_budget", "dllama_kv_transfer_pages_total"):
        assert gone not in text.decode()


def test_completion_deterministic_at_temp0(server):
    _, d1 = request(server, "POST", "/v1/chat/completions", chat_body())
    _, d2 = request(server, "POST", "/v1/chat/completions", chat_body())
    c1 = json.loads(d1)["choices"][0]["message"]["content"]
    c2 = json.loads(d2)["choices"][0]["message"]["content"]
    assert c1 == c2


def test_streaming_matches_nonstreaming(server):
    _, data = request(server, "POST", "/v1/chat/completions", chat_body())
    want = json.loads(data)["choices"][0]["message"]["content"]

    status, raw = request(server, "POST", "/v1/chat/completions",
                          chat_body(stream=True))
    assert status == 200
    events = [ln[len(b"data: "):] for ln in raw.split(b"\n\n")
              if ln.startswith(b"data: ")]
    assert events[-1] == b"[DONE]"
    deltas = [json.loads(e) for e in events[:-1]]
    text = "".join(d["choices"][0]["delta"].get("content", "") for d in deltas)
    assert text == want
    assert deltas[-1]["choices"][0]["finish_reason"] in ("stop", "length")
    assert all(d["object"] == "chat.completion.chunk" for d in deltas)


def test_stop_sequence_truncates(server):
    _, data = request(server, "POST", "/v1/chat/completions",
                      chat_body(max_tokens=16))
    full = json.loads(data)["choices"][0]["message"]["content"]
    if len(full) < 4:
        pytest.skip("model generated too little text to test stop strings")
    stop = full[2:4]
    _, data2 = request(server, "POST", "/v1/chat/completions",
                       chat_body(max_tokens=16, stop=[stop]))
    obj = json.loads(data2)
    content = obj["choices"][0]["message"]["content"]
    assert stop not in content
    assert content == full[: full.find(stop)]
    assert obj["choices"][0]["finish_reason"] == "stop"


def test_bad_request_400(server):
    status, data = request(server, "POST", "/v1/chat/completions",
                           {"messages": []})
    assert status == 400
    assert "error" in json.loads(data)

    status, _ = request(server, "POST", "/v1/chat/completions",
                        {"messages": [{"role": "user"}]})
    assert status == 400


def test_malformed_params_400_not_dropped_connection(server):
    for bad in ({"seed": "abc"}, {"temperature": "hot"}, {"max_tokens": "x"},
                {"stop": 5}, {"stop": [1, 2]}):
        status, data = request(server, "POST", "/v1/chat/completions",
                               chat_body(**bad))
        assert status == 400, bad
        assert "error" in json.loads(data)


def test_utf8_multibyte_across_tokens():
    """A char split across byte-fallback tokens must reach the client whole,
    not as per-token replacement chars."""
    import codecs

    utf8 = codecs.getincrementaldecoder("utf-8")("replace")
    pieces = ["é".encode()[:1], "é".encode()[1:]]  # two byte-fallback tokens
    out = "".join(utf8.decode(p) for p in pieces)
    assert out == "é"


def test_unknown_path_404(server):
    status, _ = request(server, "GET", "/v1/nope")
    assert status == 404


def test_max_tokens_respected(server):
    _, data = request(server, "POST", "/v1/chat/completions",
                      chat_body(max_tokens=3, stop=None))
    obj = json.loads(data)
    assert obj["usage"]["completion_tokens"] <= 3


# ---------------------------------------------------------------------------
# Prefix-cache (KV reuse across requests)
# ---------------------------------------------------------------------------

def test_take_prefix_session_logic():
    from dllama_tpu.runtime.generate import Session

    class _S(ServerState):
        def __init__(self):  # no engine needed for the cache logic
            self._sessions = []
            self.session_cache = 2

    st = _S()
    sess = Session(cache={}, pos=3, pending_token=7)
    st.store_prefix_session([1, 5, 6, 7], sess)

    # extending prompt -> reuse, feed only the suffix
    got, feed = st.take_prefix_session([1, 5, 6, 7, 9, 9])
    assert got is sess and feed == [9, 9]
    # cache is claimed (single-slot): a second take misses
    got2, feed2 = st.take_prefix_session([1, 5, 6, 7, 9, 9])
    assert got2 is None and feed2 == [1, 5, 6, 7, 9, 9]

    # diverging prompt -> no reuse
    st.store_prefix_session([1, 5, 6, 7], sess)
    got3, feed3 = st.take_prefix_session([1, 5, 2])
    assert got3 is None and feed3 == [1, 5, 2]

    # identical prompt with a pending token -> reuse with empty suffix
    st.store_prefix_session([1, 5, 6, 7], sess)
    got4, feed4 = st.take_prefix_session([1, 5, 6, 7])
    assert got4 is sess and feed4 == []

    # identical prompt, nothing pending -> cannot resume (nothing to feed)
    st.store_prefix_session([1, 5, 6], Session(cache={}, pos=3, pending_token=None))
    got5, feed5 = st.take_prefix_session([1, 5, 6])
    assert got5 is None and feed5 == [1, 5, 6]


def test_multi_turn_prefix_reuse_matches_fresh(server):
    """A second request that extends the conversation must produce the same
    greedy completion whether or not the KV prefix cache is hit."""
    first = [{"role": "user", "content": "hello world"}]
    status, data = request(server, "POST", "/v1/chat/completions",
                           chat_body(messages=first, max_tokens=4))
    assert status == 200
    reply = json.loads(data)["choices"][0]["message"]["content"]

    followup = first + [
        {"role": "assistant", "content": reply},
        {"role": "user", "content": "hello the world"},
    ]
    # warm path: prefix cache was just populated by the first request
    status, data = request(server, "POST", "/v1/chat/completions",
                           chat_body(messages=followup, max_tokens=6))
    assert status == 200
    warm = json.loads(data)["choices"][0]["message"]["content"]

    # cold path: an unrelated request evicts the cache, then repeat
    request(server, "POST", "/v1/chat/completions",
            chat_body(messages=[{"role": "user", "content": "the the the"}]))
    status, data = request(server, "POST", "/v1/chat/completions",
                           chat_body(messages=followup, max_tokens=6))
    assert status == 200
    cold = json.loads(data)["choices"][0]["message"]["content"]
    assert warm == cold


def test_spec_draft_server_matches_plain_greedy():
    """A --spec-draft server must return byte-identical greedy completions to
    a plain server (speculative decoding is exact), including across the
    prefix-cache multi-turn path."""
    tok = make_tokenizer()
    cfg = tiny_cfg(vocab_size=tok.vocab_size, seq_len=512, dim=32, kv_dim=16,
                   head_size=8, hidden_dim=64)
    params = llama.random_params(cfg, seed=13)

    class ForcedWarmEncoder:
        """Tokenizer wrapper: a ``<<WARM>>`` prompt re-encodes to the exact
        cached raw prefix + a fixed suffix, FORCING the prefix-cache warm
        path (assistant text does not decode->encode round-trip through BPE,
        so a natural follow-up may cold-miss and test nothing)."""

        def __init__(self, tok, state_box):
            self._tok, self._box = tok, state_box

        def __getattr__(self, name):
            return getattr(self._tok, name)

        def encode(self, text, add_bos=True):
            if "<<WARM>>" in text:
                return list(self._box[0]._sessions[-1][0]) + [263, 264, 265]
            return self._tok.encode(text, add_bos=add_bos)

    def run_server(spec):
        engine = Engine(cfg, params, SamplerConfig(temperature=0.0, seed=1))
        box = []
        state = ServerState(engine, ForcedWarmEncoder(tok, box), cfg,
                            model_name="tiny-test", template="llama3",
                            spec_draft=spec)
        box.append(state)
        claims = []
        orig = state.take_prefix_session

        def spying_take(prompt_tokens):
            session, feed = orig(prompt_tokens)
            claims.append(session is not None)
            return session, feed

        state.take_prefix_session = spying_take
        srv = create_server(state, host="127.0.0.1", port=0)
        port = srv.server_address[1]
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        return srv, port, claims

    srv_a, port_a, claims_a = run_server(0)
    srv_b, port_b, claims_b = run_server(6)
    try:
        replies = {}
        for port in (port_a, port_b):
            first = [{"role": "user", "content": "hello world"}]
            _, d1 = request(port, "POST", "/v1/chat/completions",
                            chat_body(messages=first, max_tokens=12))
            r1 = json.loads(d1)["choices"][0]["message"]["content"]
            # the forced-warm follow-up claims the session, exercising the
            # warm-resume spec branch (pending_token + history drafting)
            _, d2 = request(port, "POST", "/v1/chat/completions",
                            chat_body(messages=[
                                {"role": "user", "content": "<<WARM>>"}],
                                max_tokens=12))
            r2 = json.loads(d2)["choices"][0]["message"]["content"]
            replies[port] = (r1, r2)
        assert claims_a == [False, True], claims_a  # cold, then forced warm
        assert claims_b == [False, True], claims_b
        assert replies[port_a] == replies[port_b], replies
        # sampled requests also go through the spec path on server B and must
        # match server A byte for byte (same per-request key chain)
        body = chat_body(temperature=0.9, seed=5)
        _, da = request(port_a, "POST", "/v1/chat/completions", body)
        _, db = request(port_b, "POST", "/v1/chat/completions", body)
        assert (json.loads(da)["choices"][0]["message"]["content"]
                == json.loads(db)["choices"][0]["message"]["content"])
    finally:
        srv_a.shutdown()
        srv_b.shutdown()


def test_lru_prefix_cache_serves_interleaved_conversations():
    """Two conversations alternating requests must BOTH keep hitting the
    prefix cache (the round-3 single-slot cache evicted on every switch),
    with the LRU evicting only beyond capacity."""
    from dllama_tpu.runtime.generate import Session

    class _S(ServerState):
        def __init__(self, n):
            self._sessions = []
            self.session_cache = n

    st = _S(2)
    sa = Session(cache={}, pos=4, pending_token=7)
    sb = Session(cache={}, pos=4, pending_token=8)
    st.store_prefix_session([1, 2, 3, 7], sa)
    st.store_prefix_session([9, 8, 5, 8], sb)

    # conversation A returns: hits ITS entry, B's stays cached
    got, feed = st.take_prefix_session([1, 2, 3, 7, 4, 4])
    assert got is sa and feed == [4, 4]
    sa2 = Session(cache={}, pos=6, pending_token=5)
    st.store_prefix_session([1, 2, 3, 7, 4, 4, 5], sa2)

    # conversation B returns: still hits
    got, feed = st.take_prefix_session([9, 8, 5, 8, 6])
    assert got is sb and feed == [6]
    sb2 = Session(cache={}, pos=7, pending_token=3)
    st.store_prefix_session([9, 8, 5, 8, 6, 3], sb2)

    # both advanced entries resident; longest-match selection picks the
    # right one even when a shorter prefix also matches
    st.store_prefix_session([1, 2], Session(cache={}, pos=1, pending_token=2))
    # capacity 2: storing a third evicted the OLDEST (A's advanced entry)
    got, feed = st.take_prefix_session([1, 2, 3, 7, 4, 4, 5, 1])
    assert got is not sa2  # evicted
    # B's entry survived the churn
    got, feed = st.take_prefix_session([9, 8, 5, 8, 6, 3, 2])
    assert got is sb2 and feed == [2]


def test_lru_eviction_deletes_device_buffers():
    """Evicted sessions free their KV cache buffers immediately (a leaked
    cache is a seq_len x L x kv HBM slab per stale conversation)."""
    import jax.numpy as jnp

    from dllama_tpu.runtime.generate import Session

    class _S(ServerState):
        def __init__(self):
            self._sessions = []
            self.session_cache = 1

    st = _S()
    old_cache = {"k": jnp.zeros((4, 4)), "v": jnp.zeros((4, 4))}
    st.store_prefix_session([1, 2, 3], Session(cache=old_cache, pos=3, pending_token=3))
    st.store_prefix_session([5, 6, 7], Session(cache={}, pos=3, pending_token=7))
    assert old_cache["k"].is_deleted() and old_cache["v"].is_deleted()
    assert len(st._sessions) == 1


def test_miss_at_capacity_evicts_before_fresh_prefill():
    """A cache miss with all slots full must free the oldest cache BEFORE the
    caller allocates a fresh one — otherwise peak HBM transiently holds
    session_cache + 1 full KV caches (r4 review finding)."""
    import jax.numpy as jnp

    from dllama_tpu.runtime.generate import Session

    class _S(ServerState):
        def __init__(self):
            self._sessions = []
            self.session_cache = 1

    st = _S()
    old_cache = {"k": jnp.zeros((4, 4)), "v": jnp.zeros((4, 4))}
    st.store_prefix_session([1, 2, 3], Session(cache=old_cache, pos=3, pending_token=3))
    got, feed = st.take_prefix_session([9, 9, 9])  # miss, at capacity
    assert got is None and feed == [9, 9, 9]
    assert old_cache["k"].is_deleted() and old_cache["v"].is_deleted()
    assert st._sessions == []


def test_concurrent_greedy_requests_batch_into_one_decode():
    """K greedy non-streaming requests inside the batch window must share
    ONE slot-pool decode session (>= 2 rows co-resident) and return exactly
    the replies a batching-disabled server gives for the same prompts."""
    tok = make_tokenizer()
    cfg = tiny_cfg(vocab_size=tok.vocab_size, seq_len=512, dim=32, kv_dim=16,
                   head_size=8, hidden_dim=64)
    params = llama.random_params(cfg, seed=13)

    def run_server(window_ms):
        engine = Engine(cfg, params, SamplerConfig(temperature=0.0, seed=1))
        state = ServerState(engine, tok, cfg, model_name="tiny-test",
                            template="llama3", batch_window_ms=window_ms)
        sizes = []  # pool occupancy after every admit
        if state.batcher is not None:
            orig = engine.batch_session

            def spy(max_batch, chunk=None, **skw):
                sess = orig(max_batch, chunk, **skw)
                orig_admit = sess.admit_begin  # admit() delegates here too

                def admit_begin(*a, **kw):
                    slot = orig_admit(*a, **kw)
                    sizes.append(len(sess.occupied))
                    return slot

                sess.admit_begin = admit_begin
                return sess

            engine.batch_session = spy
        srv = create_server(state, host="127.0.0.1", port=0)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        return srv, srv.server_address[1], sizes

    prompts = ["hello world", "the the cat", "world hello the"]

    def ask_all(port):
        replies = [None] * len(prompts)

        def one(i):
            _, d = request(port, "POST", "/v1/chat/completions",
                           chat_body(messages=[{"role": "user",
                                                "content": prompts[i]}],
                                     max_tokens=6))
            replies[i] = json.loads(d)["choices"][0]["message"]["content"]

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return replies

    srv_plain, port_plain, _ = run_server(0)
    srv_batch, port_batch, sizes = run_server(400.0)
    try:
        # warm the batched server's compile caches so the window isn't
        # swamped by first-compile time when the concurrent burst lands
        request(port_batch, "POST", "/v1/chat/completions",
                chat_body(max_tokens=2))
        want = ask_all(port_plain)
        got = ask_all(port_batch)
        assert got == want
        assert sizes and max(sizes) >= 2, sizes  # requests actually merged
    finally:
        srv_plain.shutdown()
        srv_batch.shutdown()


def test_n_sampled_choices_one_batch(server):
    """n=3 sampled completions return 3 choices from ONE batched decode,
    seed-reproducible; n>1 with stream is rejected."""
    body = chat_body(temperature=0.9, seed=11, n=3, max_tokens=6)
    status, data = request(server, "POST", "/v1/chat/completions", body)
    assert status == 200
    obj = json.loads(data)
    assert [c["index"] for c in obj["choices"]] == [0, 1, 2]
    texts = [c["message"]["content"] for c in obj["choices"]]
    assert all(isinstance(t, str) for t in texts)
    # the per-row key split must yield genuinely distinct samples — all-
    # identical choices would mean every row got the same key (r4 review)
    assert len(set(texts)) > 1, texts
    assert obj["usage"]["completion_tokens"] <= 18

    # same seed -> same 3 choices (per-request key chain)
    _, data2 = request(server, "POST", "/v1/chat/completions", body)
    assert [c["message"]["content"] for c in json.loads(data2)["choices"]] == texts

    status, _ = request(server, "POST", "/v1/chat/completions",
                        chat_body(n=3, stream=True))
    assert status == 400
    status, _ = request(server, "POST", "/v1/chat/completions",
                        chat_body(n=99))
    assert status == 400


def test_n_greedy_choices_are_identical(server):
    status, data = request(server, "POST", "/v1/chat/completions",
                           chat_body(n=2, max_tokens=5))
    assert status == 200
    c = json.loads(data)["choices"]
    assert len(c) == 2
    assert c[0]["message"]["content"] == c[1]["message"]["content"]


def test_concurrent_sampled_requests_batch_and_match_solo():
    """Two concurrent temperature>0 requests inside the window must share
    ONE slot-pool decode session AND return exactly the replies the
    batching-disabled server gives for the same (seed, temperature) —
    per-row sampler chains make pooled sampled rows bit-identical to solo."""
    tok = make_tokenizer()
    cfg = tiny_cfg(vocab_size=tok.vocab_size, seq_len=512, dim=32, kv_dim=16,
                   head_size=8, hidden_dim=64)
    params = llama.random_params(cfg, seed=13)

    def run_server(window_ms):
        engine = Engine(cfg, params, SamplerConfig(temperature=0.0, seed=1))
        state = ServerState(engine, tok, cfg, model_name="tiny-test",
                            template="llama3", batch_window_ms=window_ms)
        sizes = []  # pool occupancy after every admit
        if state.batcher is not None:
            orig = engine.batch_session

            def spy(max_batch, chunk=None, **skw):
                sess = orig(max_batch, chunk, **skw)
                orig_admit = sess.admit_begin  # admit() delegates here too

                def admit_begin(*a, **kw):
                    slot = orig_admit(*a, **kw)
                    sizes.append(len(sess.occupied))
                    return slot

                sess.admit_begin = admit_begin
                return sess

            engine.batch_session = spy
        srv = create_server(state, host="127.0.0.1", port=0)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        return srv, srv.server_address[1], sizes

    reqs = [
        dict(messages=[{"role": "user", "content": "hello world"}],
             temperature=0.9, seed=5, max_tokens=6),
        dict(messages=[{"role": "user", "content": "the the cat"}],
             temperature=1.2, seed=11, max_tokens=6),
    ]

    def ask_all(port):
        replies = [None] * len(reqs)

        def one(i):
            _, d = request(port, "POST", "/v1/chat/completions",
                           chat_body(**reqs[i]))
            replies[i] = json.loads(d)["choices"][0]["message"]["content"]

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(reqs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return replies

    srv_plain, port_plain, _ = run_server(0)
    srv_batch, port_batch, sizes = run_server(400.0)
    try:
        request(port_batch, "POST", "/v1/chat/completions",
                chat_body(max_tokens=2))  # warm compiles before the burst
        want = ask_all(port_plain)
        got = ask_all(port_batch)
        assert got == want
        assert sizes and max(sizes) >= 2, sizes  # requests actually merged
    finally:
        srv_plain.shutdown()
        srv_batch.shutdown()


def test_batched_streaming_sse_semantics():
    """A streaming request through the batcher must emit well-formed SSE
    (role chunk, content deltas, finish chunk, [DONE]) whose concatenated
    text equals the batching-disabled server's streamed text for the same
    request."""
    tok = make_tokenizer()
    cfg = tiny_cfg(vocab_size=tok.vocab_size, seq_len=512, dim=32, kv_dim=16,
                   head_size=8, hidden_dim=64)
    params = llama.random_params(cfg, seed=13)

    def run_server(window_ms):
        engine = Engine(cfg, params, SamplerConfig(temperature=0.0, seed=1))
        state = ServerState(engine, tok, cfg, model_name="tiny-test",
                            template="llama3", batch_window_ms=window_ms)
        srv = create_server(state, host="127.0.0.1", port=0)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        return srv, srv.server_address[1]

    def stream_text(port):
        import http.client as hc

        conn = hc.HTTPConnection("127.0.0.1", port, timeout=120)
        conn.request("POST", "/v1/chat/completions",
                     body=json.dumps(chat_body(
                         messages=[{"role": "user", "content": "hello world"}],
                         stream=True, temperature=0.8, seed=3, max_tokens=8)),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read().decode()
        conn.close()
        events = [ln[len("data: "):] for ln in raw.split("\n")
                  if ln.startswith("data: ")]
        assert events[-1] == "[DONE]"
        chunks = [json.loads(e) for e in events[:-1]]
        assert chunks[0]["choices"][0]["delta"].get("role") == "assistant"
        finals = [c for c in chunks
                  if c["choices"][0]["finish_reason"] is not None]
        assert len(finals) == 1 and chunks[-1] is finals[0]
        return "".join(c["choices"][0]["delta"].get("content", "")
                       for c in chunks)

    srv_plain, port_plain = run_server(0)
    srv_batch, port_batch = run_server(400.0)
    try:
        request(port_batch, "POST", "/v1/chat/completions",
                chat_body(max_tokens=2))  # warm compiles
        want = stream_text(port_plain)
        got = stream_text(port_batch)
        assert got == want and got
    finally:
        srv_plain.shutdown()
        srv_batch.shutdown()


def test_batched_server_singleton_keeps_prefix_cache():
    """With --batch-window on and ZERO concurrency, a multi-turn chat must
    still reuse its cached KV session: the singleton batch delegates to
    the solo path (claiming AND storing sessions), so turn 2 prefills only
    the suffix — not the whole history through the batch path. Turn 2 uses
    the ForcedWarmEncoder pattern: random-weight replies don't BPE
    round-trip, so a natural follow-up would cold-miss and test nothing."""
    tok = make_tokenizer()
    cfg = tiny_cfg(vocab_size=tok.vocab_size, seq_len=512, dim=32, kv_dim=16,
                   head_size=8, hidden_dim=64)
    params = llama.random_params(cfg, seed=13)
    engine = Engine(cfg, params, SamplerConfig(temperature=0.0, seed=1))
    state_box = [None]

    class WarmTok:
        def __getattr__(self, name):
            return getattr(tok, name)

        def encode(self, text, add_bos=True):
            if "<<WARM>>" in text:
                return list(state_box[0]._sessions[-1][0]) + [263, 264, 265]
            return tok.encode(text, add_bos=add_bos)

    state = ServerState(engine, WarmTok(), cfg, model_name="tiny-test",
                        template="llama3", batch_window_ms=30.0)
    state_box[0] = state
    fed = []
    orig = engine.generate

    def spy(feed_tokens, *a, **kw):
        fed.append(len(feed_tokens))
        return orig(feed_tokens, *a, **kw)

    engine.generate = spy
    srv = create_server(state, host="127.0.0.1", port=0)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        msgs = [{"role": "user", "content": "hello world"}]
        _, d1 = request(port, "POST", "/v1/chat/completions",
                        chat_body(messages=msgs, max_tokens=4))
        assert json.loads(d1)["choices"][0]["message"]["content"] is not None
        assert fed, "singleton batch did not take the solo generate path"
        assert state._sessions, "singleton batch did not store its session"
        _, d2 = request(port, "POST", "/v1/chat/completions",
                        chat_body(messages=[{"role": "user",
                                             "content": "<<WARM>>"}],
                                  max_tokens=4))
        assert json.loads(d2)["choices"][0]["message"]["content"] is not None
        # turn 2 claimed the cached session: only the 3-token suffix (plus
        # the session's pending token) was fed, not the whole history
        assert len(fed) >= 2 and fed[-1] <= 4, fed
    finally:
        srv.shutdown()


def test_spec_server_batches_concurrent_greedy_via_batched_verify():
    """--spec-draft + --batch-window: concurrent greedy non-streaming
    requests must run through Engine.generate_batch_spec (spy-pinned) and
    return exactly the replies a plain server (no spec, no batching) gives —
    batched speculation is exact."""
    tok = make_tokenizer()
    cfg = tiny_cfg(vocab_size=tok.vocab_size, seq_len=512, dim=32, kv_dim=16,
                   head_size=8, hidden_dim=64)
    params = llama.random_params(cfg, seed=13)

    def run_server(window_ms, spec):
        engine = Engine(cfg, params, SamplerConfig(temperature=0.0, seed=1))
        state = ServerState(engine, tok, cfg, model_name="tiny-test",
                            template="llama3", batch_window_ms=window_ms,
                            spec_draft=spec)
        calls = []
        orig = engine.generate_batch_spec

        def spy(*a, **kw):
            calls.append(1)
            return orig(*a, **kw)

        engine.generate_batch_spec = spy
        srv = create_server(state, host="127.0.0.1", port=0)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        return srv, srv.server_address[1], calls

    prompts = ["hello world hello world", "the the the cat"]

    def ask_all(port):
        replies = [None] * len(prompts)

        def one(i):
            _, d = request(port, "POST", "/v1/chat/completions",
                           chat_body(messages=[{"role": "user",
                                                "content": prompts[i]}],
                                     max_tokens=6))
            replies[i] = json.loads(d)["choices"][0]["message"]["content"]

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return replies

    srv_plain, port_plain, _ = run_server(0, 0)
    srv_spec, port_spec, calls = run_server(400.0, 4)
    try:
        request(port_spec, "POST", "/v1/chat/completions",
                chat_body(max_tokens=2))  # warm compiles before the burst
        want = ask_all(port_plain)
        got = ask_all(port_spec)
        assert got == want
        assert calls, "generate_batch_spec never ran for the greedy batch"
    finally:
        srv_plain.shutdown()
        srv_spec.shutdown()


def test_spec_server_default_sampled_engine_still_batches_greedy_requests():
    """A --spec-draft --batch-window server whose ENGINE default is sampled
    (CLI --temperature 0.8) must still serve a batch of greedy REQUESTS
    through the batched verify — the explicit greedy sampler in the batcher
    keeps the greedy-only guard out of the way (r5 review catch)."""
    tok = make_tokenizer()
    cfg = tiny_cfg(vocab_size=tok.vocab_size, seq_len=512, dim=32, kv_dim=16,
                   head_size=8, hidden_dim=64)
    params = llama.random_params(cfg, seed=13)
    engine = Engine(cfg, params, SamplerConfig(temperature=0.8, seed=1))
    state = ServerState(engine, tok, cfg, model_name="tiny-test",
                        template="llama3", batch_window_ms=300.0,
                        default_sampler=SamplerConfig(temperature=0.8),
                        spec_draft=4)
    calls = []
    orig = engine.generate_batch_spec

    def spy(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    engine.generate_batch_spec = spy
    srv = create_server(state, host="127.0.0.1", port=0)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        request(port, "POST", "/v1/chat/completions",
                chat_body(max_tokens=2, temperature=0.0))  # warm (singleton)
        replies = [None, None]

        def one(i):
            st, d = request(port, "POST", "/v1/chat/completions",
                            chat_body(messages=[{"role": "user",
                                                 "content": f"hey {i} hey {i}"}],
                                      max_tokens=5, temperature=0.0))
            replies[i] = (st, json.loads(d))

        threads = [threading.Thread(target=one, args=(i,)) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for st_, obj in replies:
            assert st_ == 200, obj
            assert isinstance(obj["choices"][0]["message"]["content"], str)
        assert calls, "batched verify never ran"
    finally:
        srv.shutdown()


def test_spec_server_batched_streaming_sse():
    """Streaming requests join the batched speculative verify on a
    --spec-draft --batch-window server: SSE stream well-formed, text equal
    to the batching-disabled plain server's stream."""
    tok = make_tokenizer()
    cfg = tiny_cfg(vocab_size=tok.vocab_size, seq_len=512, dim=32, kv_dim=16,
                   head_size=8, hidden_dim=64)
    params = llama.random_params(cfg, seed=13)

    def run_server(window_ms, spec):
        engine = Engine(cfg, params, SamplerConfig(temperature=0.0, seed=1))
        state = ServerState(engine, tok, cfg, model_name="tiny-test",
                            template="llama3", batch_window_ms=window_ms,
                            spec_draft=spec)
        calls = []
        orig = engine.generate_batch_spec

        def spy(*a, **kw):
            calls.append(1)
            return orig(*a, **kw)

        engine.generate_batch_spec = spy
        srv = create_server(state, host="127.0.0.1", port=0)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        return srv, srv.server_address[1], calls

    def stream_text(port, content):
        import http.client as hc

        conn = hc.HTTPConnection("127.0.0.1", port, timeout=120)
        conn.request("POST", "/v1/chat/completions",
                     body=json.dumps(chat_body(
                         messages=[{"role": "user", "content": content}],
                         stream=True, temperature=0.0, max_tokens=8)),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read().decode()
        conn.close()
        events = [ln[len("data: "):] for ln in raw.split("\n")
                  if ln.startswith("data: ")]
        assert events[-1] == "[DONE]"
        chunks = [json.loads(e) for e in events[:-1]]
        return "".join(c["choices"][0]["delta"].get("content", "")
                       for c in chunks)

    srv_plain, port_plain, _ = run_server(0, 0)
    srv_spec, port_spec, calls = run_server(250.0, 4)
    try:
        request(port_spec, "POST", "/v1/chat/completions",
                chat_body(max_tokens=2))  # warm (singleton, solo path)
        # two concurrent STREAMING requests so the batch path engages
        texts = {}

        def one(name, content):
            texts[name] = stream_text(port_spec, content)

        threads = [threading.Thread(target=one, args=(f"s{i}", p))
                   for i, p in enumerate(["hello world", "the the cat"])]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        want0 = stream_text(port_plain, "hello world")
        want1 = stream_text(port_plain, "the the cat")
        assert texts["s0"] == want0 and texts["s1"] == want1
        assert calls, "batched spec verify never engaged for the stream batch"
    finally:
        srv_plain.shutdown()
        srv_spec.shutdown()
