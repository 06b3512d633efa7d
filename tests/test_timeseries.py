"""Continuous performance observability: the obsv/ subsystem contracts.

Four surfaces under test, all jax-free. (1) The time-series store: memory
stays bounded under ring overflow AND label-cardinality attack, window
queries trim correctly, and the sampler fans histograms into percentile
series — with the ``ts_sample`` fault seam skipping a pass without
killing the sampler. (2) The burn-rate engine: multi-window math on
synthetic series, the idle-lane gate, firing/resolve hysteresis under a
flapping signal, and the ``alert_eval`` seam preserving alert state.
(3) Forensics: the explain waterfall joins router hop spans, replica
phase spans and flight marks for one request id, and the phase sum
accounts for the measured wall time. (4) The durable bench trajectory:
failure rounds (tpu_unreachable) land as structured rows and the
comparator flags a same-host regression.
"""

import json
import os

import pytest

from dllama_tpu import faults
from dllama_tpu.observability import FlightRecorder, MetricsRegistry
from dllama_tpu.obsv import BurnRateEngine, Sampler, TimeSeriesStore
from dllama_tpu.obsv import forensics, trajectory
from dllama_tpu.obsv.burnrate import burn_rate_errors, counter_delta
from dllama_tpu.obsv.timeseries import (parse_series_key, parse_window,
                                        series_key)
from dllama_tpu.serving.lifecycle import parse_slo_classes

pytestmark = pytest.mark.faults

TTFT_P95 = series_key("dllama_class_ttft_ms", {"slo_class": "interactive"},
                      "p95")
TTFT_COUNT = series_key("dllama_class_ttft_ms",
                        {"slo_class": "interactive"}, "count")


@pytest.fixture(autouse=True)
def _clean_faults():
    """The fault plan is process-global: never leak one across tests."""
    faults.clear()
    yield
    faults.clear()


# ---------------------------------------------------------------------------
# time-series store
# ---------------------------------------------------------------------------

def test_series_key_roundtrip():
    key = series_key("dllama_ttft_ms", {"b": "2", "a": "1"}, "p95")
    assert key == 'dllama_ttft_ms:p95{a="1",b="2"}'
    assert parse_series_key(key) == ("dllama_ttft_ms", "p95",
                                     {"a": "1", "b": "2"})
    bare = series_key("dllama_up", {})
    assert parse_series_key(bare) == ("dllama_up", None, {})


def test_parse_window():
    assert parse_window("/metrics/history?window=30") == 30.0
    assert parse_window("/metrics/history") == 300.0
    assert parse_window("/metrics/history?window=bogus",
                        default_s=7.0) == 7.0
    assert parse_window("/metrics/history?window=-5") == 0.0


def test_ring_bound_under_overflow():
    store = TimeSeriesStore(capacity=8, max_series=4)
    for i in range(100):
        assert store.record("k", float(i), float(i))
    pts = store.points("k", window_s=1e9, now_s=100.0)
    assert len(pts) == 8  # ring bound: only the newest capacity points
    assert pts[0] == (92.0, 92.0) and pts[-1] == (99.0, 99.0)


def test_max_series_bound_counts_drops():
    store = TimeSeriesStore(capacity=4, max_series=2)
    assert store.record("a", 1.0, 1.0)
    assert store.record("b", 1.0, 1.0)
    # a label-cardinality accident degrades into refused keys, not growth
    assert not store.record("c", 1.0, 1.0)
    assert not store.record("d", 1.0, 1.0)
    w = store.window(window_s=10.0, now_s=2.0)
    assert w["dropped_series"] == 2
    assert sorted(w["series"]) == ["a", "b"]
    # existing series still accept points at the bound
    assert store.record("a", 2.0, 2.0)


def test_window_queries_trim_by_time():
    store = TimeSeriesStore(capacity=64)
    for t in range(10):
        store.record("k", float(t), float(t * 10))
    assert [t for t, _ in store.points("k", 3.5, now_s=9.0)] == [
        6.0, 7.0, 8.0, 9.0]
    w = store.window(window_s=2.0, now_s=9.0)
    assert [p[0] for p in w["series"]["k"]] == [7.0, 8.0, 9.0]
    # a fully-aged-out series is omitted from the window payload entirely
    assert store.window(window_s=2.0, now_s=100.0)["series"] == {}
    assert store.family_keys("k") == ["k"]
    assert store.family_keys("nope") == []


def test_sampler_fans_histograms_into_percentile_series():
    reg = MetricsRegistry()
    c = reg.counter("t_obs_requests_total", "r", ("code",))
    c.inc(3, code="200")
    h = reg.histogram("t_obs_lat_ms", "l", ("path",))
    for v in (10.0, 20.0, 30.0):
        h.observe(v, path="solo")
    store = TimeSeriesStore()
    n = store.sample_registry(reg, t_s=1.0)
    assert n > 0
    ckey = series_key("t_obs_requests_total", {"code": "200"})
    assert store.points(ckey, 10.0, now_s=1.0) == [(1.0, 3.0)]
    for field in ("p50", "p95", "p99", "count"):
        key = series_key("t_obs_lat_ms", {"path": "solo"}, field)
        assert store.points(key, 10.0, now_s=1.0), key
    assert store.points(
        series_key("t_obs_lat_ms", {"path": "solo"}, "count"),
        10.0, now_s=1.0) == [(1.0, 3.0)]


def test_ts_sample_fault_seam_skips_pass_not_sampler():
    reg = MetricsRegistry()
    reg.counter("t_seam_total", "x").inc()
    store = TimeSeriesStore()
    sampler = Sampler(reg, store, interval_s=0.0)
    faults.install("ts_sample:raise:times=1")
    assert sampler.sample_once(now_s=1.0) is False
    # the injected pass wrote nothing and was counted as a fault...
    assert store.window(1e9, now_s=1.0)["samples"] == 0
    assert sampler._m_samples.value(outcome="fault") == 1.0
    # ...and the NEXT pass succeeds: the sampler survived
    assert sampler.sample_once(now_s=2.0) is True
    assert sampler._m_samples.value(outcome="ok") == 1.0
    assert store.points("t_seam_total", 10.0, now_s=2.0) == [(2.0, 1.0)]


def test_sampler_thread_lifecycle():
    import time as _time

    reg = MetricsRegistry()
    reg.counter("t_live_total", "x").inc()
    store = TimeSeriesStore()
    sampler = Sampler(reg, store, interval_s=0.01)
    sampler.start()
    try:
        deadline = _time.monotonic() + 5.0
        while (_time.monotonic() < deadline
               and not store.window(1e9)["samples"]):
            _time.sleep(0.01)
        assert store.window(1e9)["samples"] > 0
    finally:
        sampler.stop()
    # interval 0 disables the thread entirely (the BENCH_OBS off-leg)
    off = Sampler(reg, TimeSeriesStore(), interval_s=0.0)
    off.start()
    assert off._thread is None
    off.stop()


# ---------------------------------------------------------------------------
# burn-rate engine
# ---------------------------------------------------------------------------

def _breach_store(p95=300.0, t_hi=31):
    """A store where the interactive lane served requests through t_hi
    with the given TTFT p95 (target in the tests is 100ms)."""
    store = TimeSeriesStore(capacity=256)
    for t in range(t_hi):
        store.record(TTFT_COUNT, float(t), float(t))  # lane is serving
        store.record(TTFT_P95, float(t), p95)
    return store


def _engine(store, spec="interactive:ttft=100", **kw):
    reg = MetricsRegistry()
    kw.setdefault("short_s", 10.0)
    kw.setdefault("long_s", 30.0)
    return BurnRateEngine(store, parse_slo_classes(spec), reg, **kw), reg


def test_counter_delta_clamps_restarts():
    pts = [(0.0, 100.0), (1.0, 5.0), (2.0, 8.0)]  # process restart at t=1
    assert counter_delta(pts, 10.0, now_s=2.0) == 0.0
    assert counter_delta([(0.0, 5.0), (2.0, 9.0)], 10.0, now_s=2.0) == 4.0
    assert counter_delta([(0.0, 5.0)], 10.0, now_s=2.0) == 0.0


def test_burn_rate_fires_on_sustained_breach():
    engine, reg = _engine(_breach_store(p95=300.0))
    assert engine.targets() == [("interactive", "ttft", 100.0, "p95")]
    assert engine.evaluate(now_s=30.0) == 1
    pay = engine.alerts_payload()
    assert pay["firing"] == 1
    (alert,) = [a for a in pay["alerts"] if a["slo"] == "interactive:ttft"]
    assert alert["state"] == "firing"
    assert alert["short_burn"] == pytest.approx(3.0)
    assert alert["long_burn"] == pytest.approx(3.0)
    assert reg.counter("dllama_alerts_total", "", ("slo", "state")).value(
        slo="interactive:ttft", state="firing") == 1.0


def test_idle_lane_burns_nothing():
    # same hot percentile snapshots, but the lane's request count is FLAT
    # inside the window: no traffic means no budget burning
    store = TimeSeriesStore(capacity=256)
    for t in range(31):
        store.record(TTFT_COUNT, float(t), 5.0)
        store.record(TTFT_P95, float(t), 300.0)
    engine, _ = _engine(store)
    assert engine.evaluate(now_s=30.0) == 0
    assert engine.alerts_payload()["firing"] == 0


def test_short_spike_alone_does_not_fire():
    # breach only inside the short window: the long window filters it
    store = TimeSeriesStore(capacity=256)
    for t in range(31):
        store.record(TTFT_COUNT, float(t), float(t))
        store.record(TTFT_P95, float(t), 300.0 if t >= 25 else 50.0)
    engine, _ = _engine(store)
    assert engine.evaluate(now_s=30.0) == 0


def test_alert_hysteresis_resolves_and_survives_flap():
    flight = FlightRecorder(capacity=64, process="test")
    store = _breach_store(p95=300.0, t_hi=31)
    engine, reg = _engine(store)
    engine.flight = flight
    assert engine.evaluate(now_s=30.0) == 1  # fires

    # traffic stops at t=30; by t=41 the short window [31,41] holds no
    # count growth -> healthy evals accumulate toward resolve_after=3
    assert engine.evaluate(now_s=41.0) == 1  # healthy 1: still firing
    assert engine.evaluate(now_s=42.0) == 1  # healthy 2: still firing

    # FLAP: the breach returns before the third healthy eval — the
    # hysteresis counter must reset, not resolve on stale credit
    for t in (43, 44):
        store.record(TTFT_COUNT, float(t), 100.0 + t)
        store.record(TTFT_P95, float(t), 300.0)
    assert engine.evaluate(now_s=44.0) == 1  # healthy reset to 0
    assert engine.evaluate(now_s=55.0) == 1  # healthy 1
    assert engine.evaluate(now_s=56.0) == 1  # healthy 2
    assert engine.evaluate(now_s=57.0) == 0  # healthy 3: RESOLVED
    pay = engine.alerts_payload()
    assert pay["firing"] == 0
    (alert,) = [a for a in pay["alerts"] if a["slo"] == "interactive:ttft"]
    assert alert["state"] == "resolved"

    alerts_total = reg.counter("dllama_alerts_total", "", ("slo", "state"))
    assert alerts_total.value(slo="interactive:ttft", state="firing") == 1.0
    assert alerts_total.value(slo="interactive:ttft",
                              state="resolved") == 1.0
    # both transitions are flight-recorded evidence
    kinds = [(e["kind"], e.get("state"))
             for e in flight.snapshot()["events"]]
    assert ("alert", "firing") in kinds and ("alert", "resolved") in kinds


def test_alert_eval_fault_seam_preserves_state():
    engine, reg = _engine(_breach_store(p95=300.0))
    assert engine.evaluate(now_s=30.0) == 1
    faults.install("alert_eval:raise:times=1")
    # the injected pass is skipped and counted — but still reports the
    # live firing count, and the alert state is untouched
    assert engine.evaluate(now_s=30.5) == 1
    assert reg.counter("dllama_alerts_total", "", ("slo", "state")).value(
        slo="_engine", state="eval_error") == 1.0
    assert engine.alerts_payload()["firing"] == 1
    assert engine.evaluate(now_s=31.0) == 1  # next pass evaluates again


def test_error_burn_rate_from_http_counters():
    store = TimeSeriesStore(capacity=256)
    k200 = series_key("dllama_http_requests_total",
                      {"code": "200", "route": "/v1/chat/completions"})
    k503 = series_key("dllama_http_requests_total",
                      {"code": "503", "route": "/v1/chat/completions"})
    for t in range(31):
        store.record(k200, float(t), float(t))      # +30 total
        store.record(k503, float(t), float(t) / 3)  # +10 of them 5xx
    # 25% 5xx over a 10% budget -> burn 2.5
    assert burn_rate_errors(store, 30.0, now_s=30.0,
                            budget=0.1) == pytest.approx(2.5)
    assert burn_rate_errors(store, 30.0, now_s=30.0, budget=0.0) == 0.0
    engine, _ = _engine(store, spec="interactive:err=0.1")
    assert engine.evaluate(now_s=30.0) == 1


# ---------------------------------------------------------------------------
# forensics: the explain waterfall join
# ---------------------------------------------------------------------------

def _canned_trace():
    """One proxied request: a 100ms router hop wrapping a replica whose
    queue/prefill/decode phases sum to 90ms, plus a sibling request that
    the join must NOT pick up."""
    rid = "req-aaaa"
    mk = lambda name, pid, tid, ts, dur, args=None: {  # noqa: E731
        "name": name, "ph": "X", "pid": pid, "tid": tid, "ts": ts,
        "dur": dur, "args": args or {}}
    return rid, [
        mk("router_proxy", "router", 1, 1_000, 100_000,
           {"request_id": rid, "replica": "127.0.0.1:9991", "status": 200}),
        mk("connect", "router", 1, 1_000, 2_000, {"request_id": rid}),
        mk("stream", "router", 1, 40_000, 60_000, {"request_id": rid}),
        mk("request", "replica", 7, 5_000, 92_000, {"request_id": rid}),
        mk("queue_wait", "replica", 7, 5_000, 2_000),
        mk("prefill", "replica", 7, 7_000, 30_000),
        mk("decode", "replica", 7, 37_000, 58_000),
        # sibling request on another track: must be excluded entirely
        mk("request", "replica", 9, 5_000, 50_000,
           {"request_id": "req-bbbb"}),
        mk("decode", "replica", 9, 6_000, 40_000),
    ]


def test_explain_waterfall_joins_phases_and_flight_marks():
    rid, events = _canned_trace()
    flight = [{"kind": "preempt", "request_id": rid, "t_us": 40_000,
               "process": "replica"},
              {"kind": "admit", "request_id": "req-bbbb", "t_us": 1}]
    wf = forensics.build_waterfall(rid, events, flight)
    assert wf["wall_ms"] == pytest.approx(100.0)  # the router hop anchors
    # queue_wait 2 + prefill 30 + decode 58 (the "request" envelope and
    # router spans are NOT double-counted into the phase sum)
    assert wf["phase_sum_ms"] == pytest.approx(90.0)
    assert abs(wf["phase_sum_ms"] - wf["wall_ms"]) / wf["wall_ms"] <= 0.25
    assert {r["phase"] for r in wf["rows"]} == {
        "router_proxy", "connect", "stream", "request", "queue_wait",
        "prefill", "decode"}
    assert wf["hops"] == [{"replica": "127.0.0.1:9991", "status": 200,
                           "dur_ms": 100.0}]
    assert [e["kind"] for e in wf["events"]] == ["preempt"]
    text = forensics.render_waterfall(wf)
    assert rid in text and "▇" in text and "●" in text
    # the sibling's spans leaked nowhere
    assert not any(r["args"].get("request_id") == "req-bbbb"
                   for r in wf["rows"])


def test_explain_without_router_hop_anchors_on_request_span():
    rid, events = _canned_trace()
    solo = [e for e in events if e["pid"] != "router"]
    wf = forensics.build_waterfall(rid, solo, [])
    assert wf["wall_ms"] == pytest.approx(92.0)
    assert wf["hops"] == []
    assert wf["phase_sum_ms"] == pytest.approx(90.0)


def test_forensics_file_loaders(tmp_path):
    rid, events = _canned_trace()
    # line-per-event Chrome JSON Array, torn tail line included; in its
    # own dir to exercise the directory-expansion path of the loader
    trace_dir = tmp_path / "trace"
    trace_dir.mkdir()
    trace_file = trace_dir / "trace.json"
    trace_file.write_text(
        "[\n" + "".join(json.dumps(e) + ",\n" for e in events)
        + '{"name": "torn')
    # a router /debug/flight aggregate document
    flight_file = tmp_path / "flight.json"
    flight_file.write_text(json.dumps({
        "router": {"process": "router", "events": [
            {"kind": "proxy_retry", "request_id": rid, "t_us": 2_000}]},
        "replicas": {"127.0.0.1:9991": {"process": "server", "events": [
            {"kind": "preempt", "request_id": rid, "t_us": 40_000}]}}}))
    tre = forensics.load_trace_events([str(trace_dir)])
    assert len(tre) == len(events)  # torn line skipped, "[" skipped
    fle = forensics.load_flight_events([str(flight_file)])
    assert {(e["kind"], e["process"]) for e in fle} == {
        ("proxy_retry", "router"), ("preempt", "server")}
    wf = forensics.build_waterfall(rid, tre, fle)
    assert wf["wall_ms"] == pytest.approx(100.0)
    assert len(wf["events"]) == 2


def test_newest_trace_part_prefers_hint(tmp_path):
    old = tmp_path / "fleet.json.replica-9991"
    new = tmp_path / "fleet.json.replica-9992"
    old.write_text("[]")
    new.write_text("[]")
    os.utime(old, (1, 1))
    os.utime(new, (2, 2))
    assert forensics.newest_trace_part(str(tmp_path)) == str(new)
    assert forensics.newest_trace_part(str(tmp_path),
                                       hint="9991") == str(old)
    # a hint matching nothing falls back to newest-overall
    assert forensics.newest_trace_part(str(tmp_path),
                                       hint="9999") == str(new)
    assert forensics.newest_trace_part(str(tmp_path / "missing")) is None


# ---------------------------------------------------------------------------
# durable bench trajectory
# ---------------------------------------------------------------------------

def test_trajectory_rows_and_regression_comparator(tmp_path):
    path = str(tmp_path / "trajectory.jsonl")
    base = {"metric": "smoke_decode_ms_per_token", "value": 100.0,
            "n_devices": 1}
    rep = trajectory.append_row("smoke_decode_ms_per_token", "ok",
                                result=base,
                                gates={"hard_fail": True}, path=path)
    assert rep["path"] == path and rep["regressions"] == []
    assert rep["row"]["metrics"]["smoke_decode_ms_per_token"] == 100.0

    # a failure round between the two ok rows: structured, never compared
    unreachable = trajectory.append_row(
        "smoke_decode_ms_per_token", "tpu_unreachable",
        result={"metric": "smoke_decode_ms_per_token"},
        gates={"backend": False},
        error="backend unreachable: no TPU found", path=path)
    assert unreachable["regressions"] == []
    assert unreachable["row"]["status"] == "tpu_unreachable"
    assert unreachable["row"]["git_sha"]
    assert unreachable["row"]["host"] == trajectory.host_fingerprint()

    # a torn tail line (killed bench) must not poison the trajectory
    with open(path, "a", encoding="utf-8") as fh:
        fh.write('{"torn":')
    # 20% latency regression against the last same-host ok row: flagged
    worse = dict(base, value=120.0)
    rep2 = trajectory.append_row("smoke_decode_ms_per_token", "ok",
                                 result=worse,
                                 gates={"hard_fail": False}, path=path)
    flagged = {f.get("metric") or f.get("gate"): f
               for f in rep2["regressions"]}
    assert flagged["smoke_decode_ms_per_token"]["direction"] == "down"
    assert flagged["smoke_decode_ms_per_token"]["delta_pct"] == 20.0
    assert flagged["hard_fail"] == {"gate": "hard_fail", "prev": True,
                                    "cur": False}

    rows = trajectory.load_rows(path)
    assert [r["status"] for r in rows] == ["ok", "tpu_unreachable", "ok"]


def test_trajectory_within_tolerance_and_improvements_pass(tmp_path):
    path = str(tmp_path / "t.jsonl")
    base = {"metric": "x_decode_ms_per_token", "value": 100.0}
    trajectory.append_row("x_decode_ms_per_token", "ok", result=base,
                          path=path)
    for value in (105.0, 80.0):  # +5% (inside 10% tolerance), then better
        rep = trajectory.append_row(
            "x_decode_ms_per_token", "ok",
            result=dict(base, value=value), path=path)
        assert rep["regressions"] == []


def test_trajectory_ignores_other_hosts(tmp_path):
    path = str(tmp_path / "t.jsonl")
    base = {"metric": "x_decode_ms_per_token", "value": 100.0}
    trajectory.append_row("x_decode_ms_per_token", "ok", result=base,
                          path=path)
    # rewrite the prior row as if it came from another machine
    rows = trajectory.load_rows(path)
    rows[0]["host"] = "elsewhere/arm64/py0.0.0"
    with open(path, "w", encoding="utf-8") as fh:
        for r in rows:
            fh.write(json.dumps(r) + "\n")
    rep = trajectory.append_row("x_decode_ms_per_token", "ok",
                                result=dict(base, value=500.0), path=path)
    assert rep["regressions"] == []  # a laptop never "regresses" a TPU row


def test_trajectory_append_never_raises(tmp_path):
    bad = str(tmp_path / "file" / "under" / "a-file")
    (tmp_path / "file").write_text("not a directory")
    rep = trajectory.append_row("b", "ok", result={"v": 1.0}, path=bad)
    assert rep["path"] is None  # unwritable target: row still returned
    assert rep["row"]["metrics"] == {"v": 1.0}


# ---------------------------------------------------------------------------
# router federation skip accounting
# ---------------------------------------------------------------------------

def test_router_federation_counts_skips_by_reason():
    from dllama_tpu.serving import router as rt

    reg = MetricsRegistry()
    # port 1 refuses instantly: the optimistic never-probed replica is
    # "ready" but unreachable, the skip every surface must account for
    state = rt.RouterState([rt.Replica("127.0.0.1", 1)], metrics=reg,
                           connect_timeout_s=0.5, ts_interval=0.0)
    skipped = state._m_federate_skipped
    state.federate()
    assert skipped.value(reason="unreachable") == 1.0
    hist = state.federate_history(60.0)
    assert hist["replicas"] == {}
    assert "series" in hist["router"]
    alerts = state.federate_alerts()
    assert alerts == {"replicas": {}, "firing": 0}
    # every federation surface accounts its skips the same way
    assert skipped.value(reason="unreachable") == 3.0
    assert reg.counter("dllama_router_federate_errors_total", "",
                       ("replica",)).value(replica="127.0.0.1:1") == 3.0
