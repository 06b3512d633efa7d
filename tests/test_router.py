"""Fleet front door: routing policy, failover, passthrough semantics, SSE
relay, fault seams (route_pick / proxy_upstream / probe), and a fleet-of-2
end-to-end chat smoke over `cli fleet`.

Most tests run the real RouterState/RouterHandler against in-process
FakeReplica HTTP servers (no jax, no engine — the router never knows the
difference); only the e2e smoke boots real replicas in subprocesses.
"""

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from dllama_tpu import faults
from dllama_tpu.serving import router as rt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# fakes + helpers
# ---------------------------------------------------------------------------

class FakeReplica:
    """An in-process stand-in for one dllama-api replica: /ready with a
    configurable load picture, and POST /v1/chat/completions answering in
    one of several modes (json / sse / 429 / 503 / 504)."""

    def __init__(self, name="fake"):
        self.name = name
        self.ready = True
        self.load = {"slots_occupied": 0, "slots_total": 8, "queue_depth": 0,
                     "kv_pages_free": 64, "kv_pages_total": 64,
                     "prefix_hit_rate": 0.0}
        self.mode = "json"
        self.sse_chunks = 5
        self.sse_interval_s = 0.02
        self.requests = []       # (path, body, headers) per POST
        self.chunks_written = 0
        self.sse_aborted = threading.Event()
        owner = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                pass

            def _json(self, code, obj, headers=None):
                body = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/ready":
                    info = {"status": "ready" if owner.ready
                            else "not_ready", **owner.load}
                    self._json(200 if owner.ready else 503, info)
                elif self.path == "/v1/models":
                    self._json(200, {"object": "list", "served_by":
                                     owner.name, "data": []})
                else:
                    self._json(404, {"error": "nope"})

            def do_POST(self):
                length = int(self.headers.get("Content-Length", "0"))
                body = self.rfile.read(length)
                owner.requests.append(
                    (self.path, body, dict(self.headers)))
                if owner.mode == "json":
                    self._json(200, {"object": "chat.completion",
                                     "served_by": owner.name})
                elif owner.mode == "429":
                    self._json(429, {"error": {"message": "full"}},
                               headers={"Retry-After": "7"})
                elif owner.mode == "503":
                    self._json(503, {"error": {"message": "draining"}},
                               headers={"Retry-After": "3"})
                elif owner.mode == "504":
                    self._json(504, {"error": {"message": "deadline"}})
                elif owner.mode == "sse":
                    self.send_response(200)
                    self.send_header("Content-Type", "text/event-stream")
                    self.send_header("Connection", "close")
                    self.end_headers()
                    try:
                        for i in range(owner.sse_chunks):
                            self.wfile.write(
                                f"data: chunk{i}\n\n".encode())
                            self.wfile.flush()
                            owner.chunks_written += 1
                            time.sleep(owner.sse_interval_s)
                        self.wfile.write(b"data: [DONE]\n\n")
                        self.wfile.flush()
                    except OSError:
                        owner.sse_aborted.set()

        self.srv = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.port = self.srv.server_address[1]
        self.addr = f"127.0.0.1:{self.port}"
        self.thread = threading.Thread(target=self.srv.serve_forever,
                                       daemon=True)
        self.thread.start()

    def close(self):
        self.srv.shutdown()
        self.srv.server_close()


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def make_state(replica_addrs, **kw):
    reps = []
    for a in replica_addrs:
        host, port = a.rsplit(":", 1)
        reps.append(rt.Replica(host, int(port)))
    kw.setdefault("probe_interval_s", 0.1)
    return rt.RouterState(reps, **kw)


class RouterUnderTest:
    """RouterState + live HTTP server on an ephemeral port."""

    def __init__(self, replica_addrs, **kw):
        self.state = make_state(replica_addrs, **kw)
        self.srv = rt.create_router_server(self.state, "127.0.0.1", 0)
        self.port = self.srv.server_address[1]
        self.thread = threading.Thread(target=self.srv.serve_forever,
                                       daemon=True)
        self.thread.start()

    def close(self):
        self.state.stop_probes()
        self.srv.shutdown()
        self.srv.server_close()


def request(port, method, path, body=None, headers=None, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path,
                     json.dumps(body).encode() if body is not None else None,
                     headers={"Content-Type": "application/json",
                              **(headers or {})})
        resp = conn.getresponse()
        return resp.status, resp.read(), dict(resp.getheaders())
    finally:
        conn.close()


CHAT = {"model": "m", "messages": [{"role": "user", "content": "hello"}]}


# ---------------------------------------------------------------------------
# routing policy (RouterState direct — probes over real HTTP to the fakes)
# ---------------------------------------------------------------------------

def test_least_load_pick_prefers_idle_replica():
    a, b = FakeReplica("a"), FakeReplica("b")
    try:
        a.load.update(slots_occupied=7, queue_depth=3)
        st = make_state([a.addr, b.addr])
        st.probe_once()
        for _ in range(4):
            r, reason = st.pick([], frozenset())
            assert r.name == b.addr
            assert reason == "least_load"
    finally:
        a.close(), b.close()


def test_least_load_inflight_spreads_between_probe_rounds():
    # two idle replicas, NO fresh probes between picks: the router-side
    # in-flight count is the only live signal and must spread the load
    a, b = FakeReplica("a"), FakeReplica("b")
    try:
        st = make_state([a.addr, b.addr])
        st.probe_once()
        r1, _ = st.pick([], frozenset())
        r1.begin()
        r2, _ = st.pick([], frozenset())
        assert r2.name != r1.name
    finally:
        a.close(), b.close()


def test_kv_pressure_breaks_occupancy_ties():
    a, b = FakeReplica("a"), FakeReplica("b")
    try:
        a.load.update(kv_pages_free=2)   # nearly out of pages
        b.load.update(kv_pages_free=60)
        st = make_state([a.addr, b.addr])
        st.probe_once()
        r, _ = st.pick([], frozenset())
        assert r.name == b.addr
    finally:
        a.close(), b.close()


def test_affinity_hit_and_saturated_fallback():
    a, b = FakeReplica("a"), FakeReplica("b")
    try:
        st = make_state([a.addr, b.addr])
        st.probe_once()
        hashes = rt.prefix_hashes(
            [{"role": "user", "content": "x" * 2000}], 256)
        assert hashes
        st.affinity.record(hashes, b.addr)
        r, reason = st.pick(hashes, frozenset())
        assert (r.name, reason) == (b.addr, "affinity")
        # saturate the affinity target: full slots AND a backlog
        b.load.update(slots_occupied=8, queue_depth=4)
        st.probe_once()
        r, reason = st.pick(hashes, frozenset())
        assert (r.name, reason) == (a.addr, "affinity_fallback")
    finally:
        a.close(), b.close()


def test_affinity_longest_prefix_wins():
    st = make_state(["127.0.0.1:1", "127.0.0.1:2"])
    long_hashes = ["h0", "h1", "h2"]
    st.affinity.record(["h0"], "127.0.0.1:1")       # short prefix -> r1
    st.affinity.record(long_hashes, "127.0.0.1:2")  # longer prefix -> r2
    assert st.affinity.lookup(long_hashes) == "127.0.0.1:2"
    assert st.affinity.lookup(["h0"]) == "127.0.0.1:2"  # last writer won


def test_prefix_hashes_are_cumulative_and_bounded():
    msgs1 = [{"role": "user", "content": "a" * 600}]
    msgs2 = [{"role": "user", "content": "a" * 600},
             {"role": "assistant", "content": "b" * 600}]
    h1 = rt.prefix_hashes(msgs1, 256)
    h2 = rt.prefix_hashes(msgs2, 256)
    # turn 2 extends turn 1 byte-wise -> shares every full-block hash
    assert h2[:len(h1)] == h1 and len(h2) > len(h1)
    assert rt.prefix_hashes(msgs1, 0) == []          # affinity disabled
    huge = [{"role": "user", "content": "z" * 100_000}]
    assert len(rt.prefix_hashes(huge, 256)) == rt.MAX_AFFINITY_BLOCKS


def test_drain_removes_replica_within_one_probe():
    a, b = FakeReplica("a"), FakeReplica("b")
    try:
        st = make_state([a.addr, b.addr])
        st.probe_once()
        a.ready = False  # the replica's /ready flips 503 (SIGTERM drain)
        st.probe_once()
        for _ in range(4):
            r, _ = st.pick([], frozenset())
            assert r.name == b.addr
        b.ready = False
        st.probe_once()
        with pytest.raises(rt.NoReplicaAvailable):
            st.pick([], frozenset())
    finally:
        a.close(), b.close()


# ---------------------------------------------------------------------------
# the proxy path over live HTTP
# ---------------------------------------------------------------------------

def test_proxy_basic_json_and_request_id_propagation():
    a = FakeReplica("a")
    r = RouterUnderTest([a.addr])
    try:
        code, body, headers = request(
            r.port, "POST", "/v1/chat/completions", CHAT,
            headers={"X-Request-Id": "req-test-123"})
        assert code == 200
        assert json.loads(body)["served_by"] == "a"
        assert headers["X-Request-Id"] == "req-test-123"
        # the SAME id crossed the hop: replica and router traces correlate
        assert a.requests[0][2]["X-Request-Id"] == "req-test-123"
        # without a client id the router mints one and still propagates it
        code, _, headers = request(r.port, "POST",
                                   "/v1/chat/completions", CHAT)
        assert code == 200
        rid = headers["X-Request-Id"]
        assert rid and a.requests[1][2]["X-Request-Id"] == rid
    finally:
        r.close(), a.close()


def test_failover_retries_connect_refused_within_budget():
    dead = f"127.0.0.1:{free_port()}"  # nothing listening
    b = FakeReplica("b")
    r = RouterUnderTest([dead, b.addr], retry_budget=2)
    try:
        # no probe round: the dead replica is still optimistically ready
        # and scores best (zero load) -> the POST must fail over to b
        code, body, _ = request(r.port, "POST", "/v1/chat/completions", CHAT)
        assert code == 200 and json.loads(body)["served_by"] == "b"
        assert r.state._m_retries.total() >= 1
        assert r.state._m_upstream_errors.value(replica=dead) >= 1
        # the passive circuit opened: the next pick skips the dead one
        snap = [x for x in r.state.replicas if x.name == dead][0].snapshot()
        assert snap["circuit_open"]
    finally:
        r.close(), b.close()


def test_failover_budget_exhausted_is_clean_error():
    dead1, dead2 = (f"127.0.0.1:{free_port()}" for _ in range(2))
    r = RouterUnderTest([dead1, dead2], retry_budget=1)
    try:
        code, body, _ = request(r.port, "POST", "/v1/chat/completions", CHAT)
        assert code == 502
        assert "request_id" in json.loads(body)["error"]
    finally:
        r.close()


def test_429_passes_through_untouched_no_retry():
    a, b = FakeReplica("a"), FakeReplica("b")
    a.mode = "429"
    a.load.update(slots_occupied=0)
    b.load.update(slots_occupied=7, queue_depth=5)  # b is worse: a picked
    r = RouterUnderTest([a.addr, b.addr], retry_budget=2)
    try:
        r.state.probe_once()
        code, body, headers = request(r.port, "POST",
                                      "/v1/chat/completions", CHAT)
        assert code == 429
        assert headers["Retry-After"] == "7"  # the replica's hint, verbatim
        assert json.loads(body)["error"]["message"] == "full"
        assert len(b.requests) == 0           # 429 NEVER retries
        assert r.state._m_retries.total() == 0
    finally:
        r.close(), a.close(), b.close()


def test_504_passes_through_untouched_no_retry():
    a, b = FakeReplica("a"), FakeReplica("b")
    a.mode = "504"
    b.load.update(slots_occupied=7, queue_depth=5)
    r = RouterUnderTest([a.addr, b.addr], retry_budget=2)
    try:
        r.state.probe_once()
        code, body, _ = request(r.port, "POST",
                                "/v1/chat/completions", CHAT)
        assert code == 504
        assert len(b.requests) == 0  # the deadline is burned; retry helps nobody
    finally:
        r.close(), a.close(), b.close()


def test_503_retries_to_healthy_replica():
    a, b = FakeReplica("a"), FakeReplica("b")
    a.mode = "503"
    b.load.update(slots_occupied=7, queue_depth=5)  # a picked first
    r = RouterUnderTest([a.addr, b.addr], retry_budget=2)
    try:
        r.state.probe_once()
        code, body, _ = request(r.port, "POST",
                                "/v1/chat/completions", CHAT)
        assert code == 200 and json.loads(body)["served_by"] == "b"
        assert r.state._m_retries.total() >= 1
        # the 503 also took a out of rotation without waiting for a probe
        snap = [x for x in r.state.replicas if x.name == a.addr][0].snapshot()
        assert not snap["ready"]
    finally:
        r.close(), a.close(), b.close()


def test_503_everywhere_passes_last_503_through():
    a, b = FakeReplica("a"), FakeReplica("b")
    a.mode = b.mode = "503"
    r = RouterUnderTest([a.addr, b.addr], retry_budget=3)
    try:
        code, body, headers = request(r.port, "POST",
                                      "/v1/chat/completions", CHAT)
        assert code == 503
        assert headers.get("Retry-After")  # the hint survives passthrough
        assert json.loads(body)["error"]["message"] == "draining"
    finally:
        r.close(), a.close(), b.close()


def test_router_503_when_no_replica_routable():
    a = FakeReplica("a")
    a.ready = False
    r = RouterUnderTest([a.addr])
    try:
        r.state.probe_once()
        code, body, headers = request(r.port, "POST",
                                      "/v1/chat/completions", CHAT)
        assert code == 503
        assert "no replica available" in json.loads(body)["error"]["message"]
        assert int(headers["Retry-After"]) >= 1
        code, _, _ = request(r.port, "GET", "/ready")
        assert code == 503
    finally:
        r.close(), a.close()


def test_models_endpoint_proxies(tmp_path):
    a = FakeReplica("a")
    r = RouterUnderTest([a.addr])
    try:
        code, body, _ = request(r.port, "GET", "/v1/models")
        assert code == 200 and json.loads(body)["served_by"] == "a"
    finally:
        r.close(), a.close()


def test_router_local_endpoints():
    a = FakeReplica("a")
    r = RouterUnderTest([a.addr])
    try:
        r.state.probe_once()
        code, body, _ = request(r.port, "GET", "/health")
        assert code == 200 and json.loads(body)["role"] == "router"
        code, body, _ = request(r.port, "GET", "/ready")
        info = json.loads(body)
        assert code == 200 and info["replicas_ready"] == 1
        assert info["replicas"][0]["load"]["slots_total"] == 8
        code, body, _ = request(r.port, "GET", "/stats")
        assert code == 200 and json.loads(body)["role"] == "router"
        code, body, _ = request(r.port, "GET", "/metrics")
        text = body.decode()
        assert "dllama_router_http_requests_total" in text
        assert "dllama_router_replicas_ready 1" in text
        code, _, _ = request(r.port, "GET", "/definitely-not-a-route")
        assert code == 404
    finally:
        r.close(), a.close()


# ---------------------------------------------------------------------------
# SSE passthrough
# ---------------------------------------------------------------------------

def test_sse_passthrough_byte_identity():
    a = FakeReplica("a")
    a.mode = "sse"
    r = RouterUnderTest([a.addr])
    try:
        direct_code, direct_body, _ = request(
            a.port, "POST", "/v1/chat/completions", CHAT)
        routed_code, routed_body, headers = request(
            r.port, "POST", "/v1/chat/completions", CHAT)
        assert (direct_code, routed_code) == (200, 200)
        assert routed_body == direct_body  # byte-identical stream
        assert "text/event-stream" in headers["Content-Type"]
        assert headers["X-Request-Id"]
    finally:
        r.close(), a.close()


def test_client_disconnect_closes_upstream_within_chunks():
    """Satellite bugfix pin: a client that vanishes mid-SSE must take the
    UPSTREAM replica connection down immediately (the relay loop's finally,
    not generator GC) so the replica's cancel-on-disconnect fires within a
    chunk. The fake replica would stream 200 chunks (~10s); the router must
    kill the stream within a handful of chunks of the client's exit."""
    a = FakeReplica("a")
    a.mode = "sse"
    a.sse_chunks = 200
    a.sse_interval_s = 0.05
    r = RouterUnderTest([a.addr])
    try:
        # raw socket client: http.client hides the socket once the response
        # carries Connection: close, and the test needs to hard-close it
        payload = json.dumps(CHAT).encode()
        sock = socket.create_connection(("127.0.0.1", r.port), timeout=10)
        sock.sendall(b"POST /v1/chat/completions HTTP/1.1\r\n"
                     b"Host: x\r\nContent-Type: application/json\r\n"
                     + f"Content-Length: {len(payload)}\r\n\r\n".encode()
                     + payload)
        first = sock.recv(65536)
        assert b"200" in first.split(b"\r\n", 1)[0]  # the stream is live
        sock.setsockopt(  # RST on close: the router sees the disconnect
            socket.SOL_SOCKET, socket.SO_LINGER,  # on its next write, not
            __import__("struct").pack("ii", 1, 0))  # a buffered FIN later
        sock.close()
        assert a.sse_aborted.wait(5.0), \
            "upstream never saw the disconnect — connection leaked to GC"
        chunks_at_abort = a.chunks_written
        assert chunks_at_abort <= 10, \
            f"upstream streamed {chunks_at_abort} chunks past the disconnect"
        deadline = time.monotonic() + 5.0
        while (r.state._m_client_disconnects.total() < 1
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert r.state._m_client_disconnects.total() >= 1
    finally:
        r.close(), a.close()


def test_affinity_recorded_after_success_routes_repeat_traffic():
    a, b = FakeReplica("a"), FakeReplica("b")
    r = RouterUnderTest([a.addr, b.addr])
    try:
        r.state.probe_once()
        long_chat = {"model": "m", "messages": [
            {"role": "user", "content": "tell me a story " * 100}]}
        code, body, _ = request(r.port, "POST",
                                "/v1/chat/completions", long_chat)
        assert code == 200
        first = json.loads(body)["served_by"]
        # the same conversation extended by a turn: must hit the same
        # replica every time (its radix cache holds the prefix pages)
        longer = {"model": "m", "messages": long_chat["messages"] + [
            {"role": "assistant", "content": "once upon a time " * 50},
            {"role": "user", "content": "go on"}]}
        for _ in range(3):
            code, body, _ = request(r.port, "POST",
                                    "/v1/chat/completions", longer)
            assert code == 200
            assert json.loads(body)["served_by"] == first
        assert r.state._m_picks.value(reason="affinity") >= 3
    finally:
        r.close(), a.close(), b.close()


# ---------------------------------------------------------------------------
# fault seams: route_pick / proxy_upstream / probe
# ---------------------------------------------------------------------------

@pytest.mark.faults
def test_fault_route_pick_is_visible_5xx():
    a = FakeReplica("a")
    r = RouterUnderTest([a.addr])
    try:
        faults.install("route_pick:raise:times=1")
        code, body, _ = request(r.port, "POST",
                                "/v1/chat/completions", CHAT)
        assert code == 500
        assert "injected fault at route_pick" in json.loads(
            body)["error"]["message"]
        # visible on the mapped metric family (SITE_METRICS contract)
        assert r.state._m_http.value(
            route="/v1/chat/completions", code="500") == 1
        code, _, _ = request(r.port, "POST", "/v1/chat/completions", CHAT)
        assert code == 200  # one-shot fault: service restored
    finally:
        faults.clear()
        r.close(), a.close()


@pytest.mark.faults
def test_fault_proxy_upstream_takes_retry_path():
    a, b = FakeReplica("a"), FakeReplica("b")
    r = RouterUnderTest([a.addr, b.addr], retry_budget=2)
    try:
        faults.install("proxy_upstream:raise:times=1")
        code, _, _ = request(r.port, "POST", "/v1/chat/completions", CHAT)
        assert code == 200  # the injected hop failure failed over
        assert r.state._m_retries.total() == 1
        assert r.state._m_upstream_errors.total() == 1
    finally:
        faults.clear()
        r.close(), a.close(), b.close()


@pytest.mark.faults
def test_fault_probe_opens_then_recovers():
    a = FakeReplica("a")
    st = make_state([a.addr])
    try:
        faults.install("probe:raise:times=1")
        assert st.probe_once() == 0  # injected probe failure = DOWN verdict
        assert st._m_probe_failures.value(replica=a.addr) == 1
        with pytest.raises(rt.NoReplicaAvailable):
            st.pick([], frozenset())
        faults.clear()
        assert st.probe_once() == 1  # next clean round restores rotation
        r, _ = st.pick([], frozenset())
        assert r.name == a.addr
    finally:
        faults.clear()
        a.close()


# ---------------------------------------------------------------------------
# fleet-of-2 end-to-end chat smoke (`cli fleet`, real replicas, CPU)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fleet_model(tmp_path_factory):
    import numpy as np

    from dllama_tpu.formats.spec import ArchType, ModelSpec
    from dllama_tpu.formats.tokenizer_file import (TokenizerData,
                                                   write_tokenizer)
    from dllama_tpu.formats.weights import tensor_plan, write_model
    from dllama_tpu.quants import blocks

    d = tmp_path_factory.mktemp("fleet_demo")
    spec = ModelSpec(arch=ArchType.LLAMA, dim=64, hidden_dim=96, n_layers=2,
                     n_heads=4, n_kv_heads=2, vocab_size=300, seq_len=96,
                     weights_float_type=blocks.Q40)
    rng = np.random.default_rng(0)
    write_model(str(d / "m.m"), spec,
                {e.name: 0.05 * rng.standard_normal(e.d * e.n).astype(
                    np.float32) for e in tensor_plan(spec)})
    vocab = ([b"<unk>", b"<s>", b"</s>"] + [bytes([i]) for i in range(256)]
             + [b"hi"] * 41)
    write_tokenizer(str(d / "t.t"), TokenizerData(
        vocab=vocab, scores=[0.0] * 300, bos_id=1, eos_id=2))
    return str(d / "m.m"), str(d / "t.t")


def test_fleet_of_two_e2e_chat_smoke(fleet_model, tmp_path):
    model, tok = fleet_model
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_PLATFORM_NAME", None)
    router_port, base_port = free_port(), free_port() + 1000
    proc = subprocess.Popen(
        [sys.executable, "-m", "dllama_tpu.cli", "fleet",
         "--model", model, "--tokenizer", tok,
         "--replicas", "2", "--base-port", str(base_port),
         "--host", "127.0.0.1", "--port", str(router_port),
         "--probe-interval", "0.3", "--ready-timeout", "240",
         "--log-dir", str(tmp_path / "logs"),
         # --tp 1: the pytest env forces 8 virtual CPU devices (conftest
         # XLA_FLAGS) and the tiny model's 2 kv heads can't shard 8 ways
         "--replica-arg", "--batch-window 5 --batch-max 2 --tp 1"],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    try:
        deadline = time.monotonic() + 300
        up = False
        while time.monotonic() < deadline:
            assert proc.poll() is None, proc.stdout.read()[-3000:]
            try:
                code, _, _ = request(router_port, "GET", "/ready", timeout=2)
                if code == 200:
                    up = True
                    break
            except OSError:
                pass  # router not listening yet — keep polling
            time.sleep(0.5)
        assert up, "fleet front door never became ready"

        body = {"model": "m",
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 4, "temperature": 0}
        code, raw, headers = request(
            router_port, "POST", "/v1/chat/completions", body, timeout=120)
        assert code == 200, raw[:500]
        out = json.loads(raw)
        assert out["choices"][0]["message"]["role"] == "assistant"
        assert headers["X-Request-Id"]
        # repeat conversation: affinity routes it (and it still answers)
        code, raw, _ = request(
            router_port, "POST", "/v1/chat/completions", body, timeout=120)
        assert code == 200

        code, raw, _ = request(router_port, "GET", "/stats", timeout=10)
        stats = json.loads(raw)
        assert stats["load"]["replicas_ready"] == 2
        assert stats["load"]["fleet"]["slots_total"] == 4  # 2 x batch-max 2

        # SIGTERM drains the whole topology and exits 0
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=90) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
