"""Serving telemetry: Prometheus exposition, /metrics + /stats endpoints,
per-request traces, and fault-visible counters.

Two contracts under test. (1) The exposition contract: everything /metrics
prints parses as Prometheus text format 0.0.4, and the registry spans all
four layers (server, scheduler, lifecycle gate, engine + weight integrity).
(2) The visibility contract: every DLLAMA_FAULTS site the chaos suite can
fire — quarantine, scheduler crash, queue overflow, deadline expiry, weight
corruption — moves a counter an operator can alert on. Metric handles on
the shared default registry are process-global, so every assertion here is
a DELTA, never an absolute value.
"""

import http.client
import io
import json
import re
import threading
import time

import pytest

from dllama_tpu import faults, observability
from dllama_tpu.observability import MetricsRegistry, RequestTrace

pytestmark = pytest.mark.faults


@pytest.fixture(autouse=True)
def _clean_faults():
    """The fault plan is process-global: never leak one across tests."""
    faults.clear()
    yield
    faults.clear()
    observability.configure_trace(None)


# ---------------------------------------------------------------------------
# metric primitives + exposition format (pure, no jax)
# ---------------------------------------------------------------------------

def test_counter_histogram_gauge_roundtrip():
    reg = MetricsRegistry()
    c = reg.counter("t_requests_total", "requests", ("code",))
    c.inc(code="200")
    c.inc(2, code="500")
    assert c.value(code="200") == 1.0
    assert c.value(code="500") == 2.0
    assert c.total() == 3.0
    g = reg.gauge("t_depth", "depth")
    g.set(4)
    assert g.value() == 4.0
    h = reg.histogram("t_lat_ms", "latency", buckets=(10.0, 100.0))
    for v in (5.0, 50.0, 500.0):
        h.observe(v)
    assert h.count() == 3
    assert h.percentile(50) == 50.0
    # get-or-create returns the SAME family; mismatched kind/labels raise
    assert reg.counter("t_requests_total", "requests", ("code",)) is c
    with pytest.raises(ValueError):
        reg.gauge("t_requests_total")
    with pytest.raises(ValueError):
        reg.counter("t_requests_total", "requests", ("other",))


_LABEL_VAL = r'"(?:[^"\\\n]|\\.)*"'  # quotes/backslashes must be escaped
_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=" + _LABEL_VAL +
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=" + _LABEL_VAL + r")*\})? "
    r"[-+]?(\d+\.?\d*([eE][-+]?\d+)?|Inf|NaN)$")


def test_prometheus_exposition_parses():
    """Every non-comment line of render() is a well-formed sample, every
    family has HELP+TYPE, and histogram buckets are cumulative."""
    reg = MetricsRegistry()
    c = reg.counter("p_total", "with \"quotes\" and label", ("site",))
    c.inc(site='a"b')  # label values must be escaped-or-clean in output
    reg.gauge("p_gauge", "a gauge").set(1.5)
    h = reg.histogram("p_ms", "hist", buckets=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    text = reg.render()
    lines = text.strip().splitlines()
    helps = {l.split()[2] for l in lines if l.startswith("# HELP")}
    types = {l.split()[2] for l in lines if l.startswith("# TYPE")}
    assert {"p_total", "p_gauge", "p_ms"} <= helps
    assert helps == types
    for line in lines:
        if line.startswith("#"):
            continue
        assert _SAMPLE_RE.match(line), f"unparseable sample line: {line!r}"
    # cumulative buckets: le="1" <= le="10" <= le="+Inf" == count
    buckets = [float(l.rsplit(" ", 1)[1]) for l in lines
               if l.startswith("p_ms_bucket")]
    assert buckets == sorted(buckets)
    count = [l for l in lines if l.startswith("p_ms_count")][0]
    assert buckets[-1] == float(count.rsplit(" ", 1)[1]) == 3


def test_request_trace_latencies_and_record():
    tr = RequestTrace("req-abc")
    assert tr.ttft_ms is None and tr.tpot_ms is None
    tr.mark_start("solo")
    tr.mark_prefill(3.5)
    tr.mark_token()
    assert tr.ttft_ms is not None and tr.ttft_ms >= 0.0
    assert tr.tpot_ms is None  # one token has no inter-token gap
    tr.tokens_out = 2
    tr.mark_token()
    assert tr.tpot_ms is not None and tr.tpot_ms >= 0.0
    tr.tokens_in, tr.finish_reason, tr.status = 7, "stop", 200
    tr.prompt_sha = observability.prompt_digest("hi")
    rec = tr.record()
    assert rec["event"] == "request" and rec["request_id"] == "req-abc"
    assert rec["path"] == "solo" and rec["tokens_in"] == 7
    assert rec["finish_reason"] == "stop" and rec["status"] == 200
    assert rec["prompt_sha256"] == observability.prompt_digest("hi")
    assert "prompt" not in rec  # privacy default: never the text
    json.dumps(rec)  # structured-log line must be JSON-serializable


def test_trace_events_nest_under_request_span():
    tr = RequestTrace("req-nest")
    tr.mark_start("continuous")
    tr.mark_prefill(0.5)
    tr.mark_token()
    tr.mark_token()
    tr.tokens_out = 2
    events = tr.trace_events()
    names = [e["name"] for e in events]
    # a thread_name metadata record labels the track; spans follow
    assert names[0] == "thread_name" and events[0]["ph"] == "M"
    assert names[1] == "request"
    assert {"queue_wait", "prefill", "decode"} <= set(names)
    req = events[1]
    for e in events[1:]:
        # one track per request: child spans nest under the request span
        assert e["tid"] == req["tid"] and e["ph"] == "X"
        assert e["ts"] >= req["ts"]
        assert e["ts"] + e["dur"] <= req["ts"] + req["dur"] + 1
    assert events[0]["tid"] == req["tid"]


def test_span_ids_are_small_and_unique():
    """Track ids are allocated sequentially per process — Perfetto shows
    'req <id>' tracks instead of giant hashed tids — and never collide."""
    a, b = RequestTrace("req-a"), RequestTrace("req-b")
    assert isinstance(a.span_id, int) and isinstance(b.span_id, int)
    assert a.span_id != b.span_id
    assert b.span_id > a.span_id  # monotonic allocation
    a.mark_start("solo")
    a.mark_token()
    assert all(e["tid"] == a.span_id for e in a.trace_events())
    assert observability.next_span_id() > b.span_id


def test_prefill_chunk_spans_replace_monolithic_prefill():
    """Chunked admission: each prefill piece becomes its own child span
    (numbered), and the single monolithic 'prefill' span is suppressed."""
    import time

    tr = RequestTrace("req-chunks")
    tr.mark_start("continuous")
    for _ in range(2):
        t_a = time.monotonic()
        time.sleep(0.002)
        tr.mark_prefill_chunk(t_a, time.monotonic())
    tr.mark_prefill(2.0)  # scheduler still records the total
    tr.mark_token()
    tr.tokens_out = 1
    events = tr.trace_events()
    names = [e["name"] for e in events]
    assert names.count("prefill_chunk") == 2
    assert "prefill" not in names
    chunks = [e for e in events if e["name"] == "prefill_chunk"]
    assert [c["args"]["chunk"] for c in chunks] == [0, 1]
    req = [e for e in events if e["name"] == "request"][0]
    for c in chunks:  # chunk spans nest inside the request span
        assert c["tid"] == req["tid"]
        assert c["ts"] >= req["ts"]
        assert c["ts"] + c["dur"] <= req["ts"] + req["dur"] + 1


def test_scheduler_trace_event_uses_reserved_track():
    import time

    t0 = time.monotonic()
    ev = observability.scheduler_trace_event(
        "scheduler_window", t0, t0 + 0.005, {"window": 3})
    assert ev["tid"] == observability.SCHEDULER_TID == 0
    assert ev["ph"] == "X" and ev["cat"] == "scheduler"
    assert ev["args"] == {"window": 3}
    assert ev["dur"] >= 4000  # microseconds
    json.dumps(ev)


def test_token_buckets_are_powers_of_two():
    bk = observability.TOKEN_BUCKETS
    assert all(b == 2.0 ** i for i, b in enumerate(bk))
    assert bk[0] == 1.0 and bk[-1] >= 8192.0
    reg = MetricsRegistry()
    h = reg.histogram("t_tokens", "tokens", buckets=bk)
    for v in (1, 3, 700):
        h.observe(float(v))
    assert h.count() == 3
    # cumulative bucket lines render one sample per power-of-two boundary
    lines = [l for l in reg.render().splitlines()
             if l.startswith("t_tokens_bucket")]
    assert len(lines) == len(bk) + 1  # +Inf bucket
    counts = [float(l.rsplit(" ", 1)[1]) for l in lines]
    assert counts == sorted(counts) and counts[-1] == 3

def test_sanitize_request_id():
    assert observability.sanitize_request_id("abc-123_X") == "abc-123_X"
    # unprintable / quoting characters are stripped, the rest honored
    assert observability.sanitize_request_id('a"b\x01c') == "abc"
    for bad in (None, "", "x" * 200, '"\x01'):
        rid = observability.sanitize_request_id(bad)
        assert rid.startswith("req-") and len(rid) > 8


def test_trace_file_is_chrome_json_array(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    observability.configure_trace(path)
    tr = RequestTrace("req-file")
    tr.mark_start("solo")
    tr.mark_token()
    observability.emit_trace_events(tr.trace_events())
    observability.configure_trace(None)
    raw = open(path).read()
    # Chrome JSON Array Format: leading '[', one event per line, trailing
    # ']' legally omitted — loadable by Perfetto AND line-parseable
    assert raw.startswith("[\n")
    events = [json.loads(l.rstrip(",")) for l in raw.splitlines()[1:] if l]
    assert any(e["name"] == "request" for e in events)
    json.loads(raw.rstrip().rstrip(",") + "]")  # closes to a valid array


# ---------------------------------------------------------------------------
# server integration (tiny synthetic model, real HTTP over localhost)
# ---------------------------------------------------------------------------

from tests.test_lifecycle import (  # noqa: E402
    chat_body,
    engine_bits,
    http_req,
    make_state,
    start_server,
)

_ = engine_bits  # re-exported fixture


def http_req_h(port, method, path, body=None, headers=None, timeout=120):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    h = {"Content-Type": "application/json"}
    h.update(headers or {})
    conn.request(method, path,
                 body=json.dumps(body) if body is not None else None,
                 headers=h)
    resp = conn.getresponse()
    data = resp.read()
    out = dict(resp.getheaders())
    conn.close()
    return resp.status, data, out


def _metric_value(port, name, **labels):
    """Scrape /metrics and return the value of one series (0.0 if absent)."""
    status, data, _ = http_req(port, "GET", "/metrics", timeout=30)
    assert status == 200
    want_labels = {f'{k}="{v}"' for k, v in labels.items()}
    for line in data.decode().splitlines():
        if line.startswith("#"):
            continue
        sample, _, value = line.rpartition(" ")
        base, _, labelstr = sample.partition("{")
        if base != name:
            continue
        have = set(labelstr.rstrip("}").split(",")) if labelstr else set()
        if want_labels <= have:
            return float(value)
    return 0.0


def test_metrics_endpoint_spans_all_layers(engine_bits):
    # batch scheduler on: its families (path counter, occupancy) register
    state = make_state(engine_bits, batch_window_ms=5.0)
    srv, port = start_server(state)
    try:
        status, _, _ = http_req(port, "POST", "/v1/chat/completions",
                                chat_body())
        assert status == 200
        status, data, headers = http_req(port, "GET", "/metrics", timeout=30)
        assert status == 200
        assert headers["Content-Type"].startswith("text/plain; version=0.0.4")
        text = data.decode()
        families = {l.split()[2] for l in text.splitlines()
                    if l.startswith("# TYPE")}
        # >= 12 series spanning server / scheduler / lifecycle / engine /
        # integrity layers (the ISSUE acceptance floor)
        must_have = {
            "dllama_http_requests_total", "dllama_ttft_ms",      # server
            "dllama_queue_wait_ms", "dllama_sse_disconnects_total",
            "dllama_prompt_tokens_total", "dllama_completion_tokens_total",
            "dllama_requests_path_total",                        # scheduler
            "dllama_admission_rejections_total",                 # lifecycle
            "dllama_scheduler_crashes_total",
            "dllama_deadline_expirations_total",
            "dllama_inflight_requests",
            "dllama_prefill_ms", "dllama_decode_step_ms",        # engine
            "dllama_numeric_quarantines_total",
            "dllama_weights_checksum_failures_total",            # integrity
        }
        missing = must_have - families
        assert not missing, f"families missing from /metrics: {missing}"
        assert len(families) >= 12
        for line in text.splitlines():
            if not line.startswith("#"):
                assert _SAMPLE_RE.match(line), f"bad line: {line!r}"
    finally:
        srv.shutdown()


def test_stats_endpoint_reports_percentiles(engine_bits):
    state = make_state(engine_bits)
    srv, port = start_server(state)
    try:
        status, _, _ = http_req(port, "POST", "/v1/chat/completions",
                                chat_body())
        assert status == 200
        status, data, _ = http_req(port, "GET", "/stats", timeout=30)
        assert status == 200
        stats = json.loads(data)
        assert stats["model"] == "tiny-test"
        assert stats["uptime_s"] >= 0.0
        assert "queue_depth" in stats["load"]
        # the device this process runs on, as JAX reports it (forced CPU
        # with eight virtual devices here: tests/conftest.py)
        dev = stats["device"]
        assert (dev["platform"], dev["count"]) == ("cpu", 8)
        assert dev["kind"] and len(dev["bytes_in_use"]) == 8
        assert set(stats["compile_cache"]) == {"dir", "requests", "hits"}
        ttft = stats["metrics"]["dllama_ttft_ms"]
        assert ttft["kind"] == "histogram"
        solo = [v for v in ttft["values"]
                if v["labels"].get("path") == "solo"]
        assert solo and solo[0]["count"] >= 1
        assert solo[0]["p50"] is not None and solo[0]["p50"] >= 0.0
    finally:
        srv.shutdown()


def test_request_id_honored_and_echoed_everywhere(engine_bits):
    state = make_state(engine_bits, queue_depth=1)
    srv, port = start_server(state)
    try:
        # client id honored on a 200
        status, _, headers = http_req_h(
            port, "POST", "/v1/chat/completions", chat_body(),
            headers={"X-Request-Id": "client-id-42"})
        assert status == 200 and headers["X-Request-Id"] == "client-id-42"
        # minted when absent; echoed on GETs and 404s too
        status, _, headers = http_req(port, "GET", "/health", timeout=30)
        assert headers["X-Request-Id"].startswith("req-")
        status, data, headers = http_req(port, "GET", "/nope", timeout=30)
        assert status == 404
        assert headers["X-Request-Id"].startswith("req-")
        assert json.loads(data)["error"]["request_id"] == \
            headers["X-Request-Id"]
        # an insane client id (too long) is replaced, not trusted
        status, _, headers = http_req_h(
            port, "GET", "/health", headers={"X-Request-Id": "x" * 500})
        assert headers["X-Request-Id"].startswith("req-")
        # echoed on a 429 rejection body as well
        ticket = state.gate.acquire()
        try:
            status, data, headers = http_req_h(
                port, "POST", "/v1/chat/completions", chat_body(),
                headers={"X-Request-Id": "rejected-7"}, timeout=30)
            assert status == 429
            assert headers["X-Request-Id"] == "rejected-7"
            assert json.loads(data)["error"]["request_id"] == "rejected-7"
        finally:
            state.gate.release(ticket)
    finally:
        srv.shutdown()


def test_health_and_ready_carry_scheduler_fields(engine_bits):
    state = make_state(engine_bits, batch_window_ms=5.0)
    srv, port = start_server(state)
    try:
        for path in ("/health", "/ready"):
            status, data, _ = http_req(port, "GET", path, timeout=30)
            assert status == 200
            info = json.loads(data)
            assert info["scheduler_alive"] is True
            assert info["crash_count"] == 0
            assert info["queue_depth"] == 0
    finally:
        srv.shutdown()


def test_http_requests_counter_by_route_and_code(engine_bits):
    state = make_state(engine_bits)
    srv, port = start_server(state)
    try:
        before = _metric_value(port, "dllama_http_requests_total",
                               route="/health", code="200")
        http_req(port, "GET", "/health", timeout=30)
        http_req(port, "GET", "/some/unknown/path", timeout=30)
        after = _metric_value(port, "dllama_http_requests_total",
                              route="/health", code="200")
        other = _metric_value(port, "dllama_http_requests_total",
                              route="other", code="404")
        assert after >= before + 1
        assert other >= 1  # unknown paths bucket as "other", not new series
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# every fault site moves a counter (the visibility contract)
# ---------------------------------------------------------------------------

def test_429_moves_rejection_counter(engine_bits):
    state = make_state(engine_bits, queue_depth=1)
    srv, port = start_server(state)
    reg = observability.default_registry()
    rej = reg.counter("dllama_admission_rejections_total",
                      "Requests rejected at the admission gate, by reason",
                      ("reason",))
    try:
        before = rej.value(reason="queue_full")
        ticket = state.gate.acquire()
        try:
            status, _, _ = http_req(port, "POST", "/v1/chat/completions",
                                    chat_body(), timeout=30)
            assert status == 429
        finally:
            state.gate.release(ticket)
        assert rej.value(reason="queue_full") == before + 1
    finally:
        srv.shutdown()


def test_deadline_expiry_moves_counter(engine_bits):
    state = make_state(engine_bits, request_timeout=0.0001)
    srv, port = start_server(state)
    reg = observability.default_registry()
    ded = reg.counter("dllama_deadline_expirations_total")
    try:
        before = ded.value()
        status, _, _ = http_req(port, "POST", "/v1/chat/completions",
                                chat_body(max_tokens=32))
        assert status == 504
        assert ded.value() >= before + 1
    finally:
        srv.shutdown()


def test_scheduler_crash_moves_counter(engine_bits):
    state = make_state(engine_bits, batch_window_ms=5.0, batch_max=2)
    srv, port = start_server(state)
    reg = observability.default_registry()
    crashes = reg.counter("dllama_scheduler_crashes_total")
    try:
        before = crashes.value()
        faults.install("scheduler:raise:times=1")
        status, data, _ = http_req(port, "POST", "/v1/chat/completions",
                                   chat_body())
        faults.clear()
        assert status == 503  # typed SchedulerCrashed, not a hang
        assert crashes.value() == before + 1
        # the restarted scheduler keeps serving, crash count is visible
        status, data, _ = http_req(port, "GET", "/health", timeout=30)
        assert json.loads(data)["crash_count"] >= 1
    finally:
        srv.shutdown()


def test_numeric_quarantine_moves_counter(engine_bits):
    state = make_state(engine_bits)
    srv, port = start_server(state)
    reg = observability.default_registry()
    quar = reg.counter("dllama_numeric_quarantines_total")
    try:
        before = quar.value()
        faults.install("logits:nan:after=2")
        status, _, _ = http_req(port, "POST", "/v1/chat/completions",
                                chat_body(max_tokens=8))
        faults.clear()
        assert status == 500
        assert quar.value() >= before + 1
    finally:
        srv.shutdown()


def test_weight_corruption_moves_counters(tmp_path):
    from dllama_tpu.formats.weights import ChecksumError, WeightFileReader
    from tests.test_integrity import _flip_byte, _write

    reg = observability.default_registry()
    crc = reg.counter("dllama_weights_checksum_failures_total")
    verified = reg.counter("dllama_weights_tensors_verified_total")
    path, _, _ = _write(tmp_path)
    with WeightFileReader(path) as r:
        e = r.entry("layers.0.w1")
    v_before = verified.value()
    c_before = crc.value()
    _flip_byte(path, e.offset + 5)
    with WeightFileReader(path) as r:
        with pytest.raises(ChecksumError):
            r.read_tensor("layers.0.w1")
        r.read_tensor("layers.1.w2")  # healthy sibling still verifies
    assert crc.value() == c_before + 1
    assert verified.value() >= v_before + 1


def test_truncated_weights_move_open_failure_counter(tmp_path):
    import os

    from dllama_tpu.formats.spec import FormatError
    from dllama_tpu.formats.weights import WeightFileReader
    from tests.test_integrity import _write

    reg = observability.default_registry()
    opens = reg.counter("dllama_weights_open_failures_total")
    path, _, _ = _write(tmp_path)
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size // 2)
    before = opens.value()
    with pytest.raises(FormatError):
        WeightFileReader(path)
    assert opens.value() == before + 1


# ---------------------------------------------------------------------------
# TTFT lands for every decode path; JSON logs honor the privacy default
# ---------------------------------------------------------------------------

def _drive_path(state, serve, n=2, sampler=None):
    """Route ``n`` requests through one scheduler path DETERMINISTICALLY by
    invoking the Batcher serve hook directly (no window-timing races), the
    way the scheduler loop would, then emit their traces."""
    from dllama_tpu.runtime.sampler import SamplerConfig

    batcher = state.batcher
    sampler = sampler or SamplerConfig(temperature=0.0, seed=1)
    slots = [
        batcher._Slot([1, 5, 9], 6, sampler, streaming=False,
                      trace=RequestTrace(observability.new_request_id()))
        for _ in range(n)
    ]
    with state.lock:
        serve(batcher, slots)
    for s in slots:
        assert s.done.is_set() and s.error is None, f"slot failed: {s.error}"
        s.trace.tokens_out = len(s.tokens)
        s.trace.finish_reason = "length"
        state.finish_request(s.trace)
    return slots


def test_every_decode_path_emits_ttft(engine_bits):
    reg = MetricsRegistry()  # fresh: counts below are absolute, not deltas
    state = make_state(engine_bits, batch_window_ms=5.0, batch_max=4,
                       batch_chunk=4, metrics=reg)
    _drive_path(state, lambda b, s: b._serve_solo(s[0]), n=1)
    _drive_path(state, lambda b, s: b._serve_continuous(s))
    ttft = state._m_ttft
    assert ttft.count(path="solo") == 1
    assert ttft.count(path="continuous") == 2
    assert ttft.percentile(95, path="continuous") >= 0.0
    assert state._m_queue_wait.count() == 3
    # the path counter agrees with what was routed
    assert state.batcher._m_path.value(path="solo") == 1
    assert state.batcher._m_path.value(path="continuous") == 2


def test_spec_path_emits_ttft(engine_bits):
    engine, tok, cfg = engine_bits
    if not getattr(engine, "supports_batch_spec", False):
        pytest.skip("engine lacks batched speculative verify")
    reg = MetricsRegistry()
    state = make_state(engine_bits, spec_draft=4, batch_window_ms=5.0,
                       batch_max=4, batch_chunk=4, metrics=reg)
    _drive_path(state, lambda b, s: b._serve_spec(s))
    assert state._m_ttft.count(path="spec") == 2
    assert state.batcher._m_path.value(path="spec") == 2


def _wait_log_record(buf: io.StringIO, request_id: str, timeout: float = 5.0):
    """The JSON log line is emitted in the handler's ``finally`` — after the
    response bytes are flushed — so a fast client can read the buffer before
    the server thread writes the record. Poll briefly instead of racing."""
    deadline = time.monotonic() + timeout
    while True:
        recs = [json.loads(l) for l in buf.getvalue().splitlines()]
        hits = [r for r in recs if r["request_id"] == request_id]
        if hits:
            return hits[0]
        if time.monotonic() > deadline:
            raise AssertionError(f"no log record for {request_id!r}: {recs}")
        time.sleep(0.01)


def test_log_json_privacy_default(engine_bits):
    buf = io.StringIO()
    state = make_state(engine_bits, log_json=True, log_stream=buf)
    srv, port = start_server(state)
    try:
        status, _, _ = http_req_h(port, "POST", "/v1/chat/completions",
                                  chat_body(),
                                  headers={"X-Request-Id": "priv-1"})
        assert status == 200
        rec = _wait_log_record(buf, "priv-1")
        assert rec["event"] == "request" and rec["status"] == 200
        assert rec["tokens_in"] > 0 and rec["tokens_out"] > 0
        assert rec["ttft_ms"] >= 0.0
        assert len(rec["prompt_sha256"]) == 16
        assert "prompt" not in rec  # counts and hashes, never the text
    finally:
        srv.shutdown()


def test_log_prompts_opts_in_to_text(engine_bits):
    buf = io.StringIO()
    state = make_state(engine_bits, log_json=True, log_prompts=True,
                       log_stream=buf)
    srv, port = start_server(state)
    try:
        status, _, _ = http_req_h(port, "POST", "/v1/chat/completions",
                                  chat_body(),
                                  headers={"X-Request-Id": "priv-2"})
        assert status == 200
        rec = _wait_log_record(buf, "priv-2")
        assert "hello world" in rec["prompt"]
    finally:
        srv.shutdown()


def test_streaming_requests_traced_to_jsonl(engine_bits, tmp_path):
    """SSE requests: spans land in the DLLAMA_TRACE file, nested per
    request, and the SSE response carries the request-id header."""
    path = str(tmp_path / "serve_trace.jsonl")
    observability.configure_trace(path)
    state = make_state(engine_bits)
    srv, port = start_server(state)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        conn.request("POST", "/v1/chat/completions",
                     body=json.dumps(chat_body(stream=True)),
                     headers={"Content-Type": "application/json",
                              "X-Request-Id": "sse-trace-1"})
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("X-Request-Id") == "sse-trace-1"
        body = resp.read().decode()
        conn.close()
        assert "data: [DONE]" in body
    finally:
        srv.shutdown()
        observability.configure_trace(None)
    events = [json.loads(l.rstrip(","))
              for l in open(path).read().splitlines()[1:] if l]
    mine = [e for e in events
            if e.get("args", {}).get("request_id") == "sse-trace-1"]
    assert mine and mine[0]["name"] == "request"
    tid = mine[0]["tid"]
    spans = {e["name"] for e in events if e["tid"] == tid}
    assert {"queue_wait", "decode"} <= spans
