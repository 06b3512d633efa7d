"""CI observability fault drill: faults must move counters.

Boots the tiny synthetic server in-process, scrapes /metrics, then fires
one fault of each class the chaos suite knows — queue overflow (429),
scheduler crash (503), poisoned logits (quarantine, 500), deadline expiry
(504) — and scrapes again. The drill PASSES only if every injected fault
produced a nonzero counter delta: an outage class with no metric movement
is an outage an operator cannot alert on, and that is the regression this
lane exists to catch.

``--fleet`` runs the FLEET leg instead: a real ``cli fleet`` subprocess
topology (router + 2 replicas, tiny synthetic weights, CPU) with tracing,
the flight recorder, the time-series sampler and a microsecond
interactive TTFT SLO target armed. It passes only if (a) the merged
Perfetto file contains at least one STITCHED request — a router proxy
span and a replica request span sharing the request id, tied by a flow
arrow — with the router and each replica on distinct named process
tracks, (b) the router's /metrics/fleet chat-route counter sums equal
the per-replica /metrics sums, (c) the SIGTERM drain left one
flight-recorder dump per process whose ring holds the drilled request
ids, (d) the drilled chats breach the TTFT SLO so the federated /alerts
flips to FIRING (transition flight-recorded) and then back to RESOLVED
once the burst ages out of both burn windows, (e) the federated
/metrics/history window is non-empty for the router and every replica,
and (f) ``cli explain`` joins a drilled request into a waterfall whose
phase sum is within tolerance of the measured wall time.

Artifacts written to --out-dir (uploaded by CI):
    metrics_before.txt / metrics_after.txt   raw Prometheus expositions
    deltas.json                              per-counter deltas + verdict
    trace.jsonl                              Chrome/Perfetto request spans
    requests.jsonl                           structured JSON request logs
    fleet-trace.json / fleet_verdict.json / flight/   (--fleet leg)
    alerts.json / history.json / explain.json / trajectory.jsonl (--fleet)

Usage:  JAX_PLATFORMS=cpu python scripts/obs_drill.py [--out-dir obs-drill]
                                                      [--fleet]
Exit 0 only if every assertion of the selected leg holds.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import sys
import threading

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# counter -> the fault class whose visibility it proves
WATCHED = {
    "dllama_admission_rejections_total": "queue overflow (429)",
    "dllama_scheduler_crashes_total": "scheduler crash (503)",
    "dllama_numeric_quarantines_total": "poisoned logits (quarantine)",
    "dllama_deadline_expirations_total": "deadline expiry (504)",
    "dllama_http_requests_total": "request accounting",
}


def parse_exposition(text: str) -> dict:
    """Family name -> summed value across its series (labels collapsed:
    the drill asserts movement, not attribution)."""
    totals: dict = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        sample, _, value = line.rpartition(" ")
        name = sample.partition("{")[0]
        # fold histogram series into their family's count
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix):
                name = name[: -len(suffix)]
                break
        try:
            totals[name] = totals.get(name, 0.0) + float(value)
        except ValueError:
            pass
    return totals


def request(port, method, path, body=None, timeout=120):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request(method, path,
                 body=json.dumps(body) if body is not None else None,
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def chat(**kw):
    body = {"model": "drill", "max_tokens": 8, "temperature": 0.0,
            "messages": [{"role": "user", "content": "observability drill"}]}
    body.update(kw)
    return body


def series_sum(text: str, family: str, must_contain: str = "") -> float:
    """Sum one family's sample values across all its series, optionally
    restricted to series whose label block contains ``must_contain``."""
    total = 0.0
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        sample, _, value = line.rpartition(" ")
        if sample.partition("{")[0] != family or must_contain not in sample:
            continue
        try:
            total += float(value)
        except ValueError:
            pass
    return total


def fleet_main(args) -> int:
    """The --fleet leg: real router + 2 replica subprocesses, then assert
    stitching, federation arithmetic, and the SIGTERM flight dumps."""
    import glob
    import signal
    import socket
    import subprocess
    import time

    import numpy as np

    from dllama_tpu.formats.spec import ArchType, ModelSpec
    from dllama_tpu.formats.tokenizer_file import TokenizerData, write_tokenizer
    from dllama_tpu.formats.weights import tensor_plan, write_model
    from dllama_tpu.quants import blocks

    out = os.path.abspath(args.out_dir)
    art = os.path.join(out, "artifacts")
    os.makedirs(art, exist_ok=True)
    model, tokp = os.path.join(art, "m.m"), os.path.join(art, "t.t")
    spec = ModelSpec(arch=ArchType.LLAMA, dim=64, hidden_dim=96, n_layers=2,
                     n_heads=4, n_kv_heads=2, vocab_size=300, seq_len=96,
                     weights_float_type=blocks.Q40)
    rng = np.random.default_rng(0)
    write_model(model, spec,
                {e.name: 0.05 * rng.standard_normal(e.d * e.n).astype(
                    np.float32) for e in tensor_plan(spec)})
    vocab = ([b"<unk>", b"<s>", b"</s>"] + [bytes([i]) for i in range(256)]
             + [b"hi"] * 41)
    write_tokenizer(tokp, TokenizerData(
        vocab=vocab, scores=[0.0] * 300, bos_id=1, eos_id=2))

    trace = os.path.join(out, "fleet-trace.json")
    flight_dir = os.path.join(out, "flight")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               DLLAMA_TRACE=trace, DLLAMA_FLIGHT=flight_dir)
    env.pop("JAX_PLATFORM_NAME", None)

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    router_port, base_port = free_port(), free_port() + 1000
    fleet_log = open(os.path.join(out, "fleet.log"), "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "dllama_tpu.cli", "fleet",
         "--model", model, "--tokenizer", tokp,
         "--replicas", "2", "--base-port", str(base_port),
         "--host", "127.0.0.1", "--port", str(router_port),
         "--probe-interval", "0.3", "--ready-timeout", "240",
         # dense history sampling + a 1-microsecond interactive TTFT
         # target: every real chat is an SLO breach, so the burn-rate
         # engine must fire — and, with the burn windows shrunk to
         # drill scale, resolve again once the drill goes idle
         "--ts-interval", "0.25",
         "--slo-classes", "interactive:ttft=0.001",
         "--log-dir", os.path.join(out, "logs"),
         "--replica-arg", "--batch-window 5 --batch-max 2 --tp 1 "
                          "--burn-short 3 --burn-long 6"],
        env=env, cwd=REPO, stdout=fleet_log, stderr=subprocess.STDOUT)

    failures = []
    drilled_ids = []
    try:
        deadline = time.monotonic() + 300
        up = False
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"fleet exited early ({proc.returncode}); see fleet.log")
            try:
                status, _ = request(router_port, "GET", "/ready", timeout=2)
                if status == 200:
                    up = True
                    break
            except OSError:
                pass  # front door not listening yet — keep polling
            time.sleep(0.5)
        if not up:
            raise RuntimeError("fleet front door never became ready")
        print(f"fleet up: router :{router_port} -> replicas "
              f":{base_port},:{base_port + 1}")

        for i in range(3):
            conn = http.client.HTTPConnection("127.0.0.1", router_port,
                                              timeout=120)
            conn.request("POST", "/v1/chat/completions",
                         body=json.dumps(chat(model="m", max_tokens=4)),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            resp.read()
            rid = resp.getheader("X-Request-Id")
            timing = resp.getheader("Server-Timing") or ""
            conn.close()
            if resp.status != 200:
                failures.append(f"chat #{i} returned {resp.status}")
            if rid:
                drilled_ids.append(rid)
            if i == 0 and "total;dur=" not in timing:
                failures.append(
                    f"router response lacks Server-Timing: {timing!r}")
        print(f"drilled {len(drilled_ids)} chat request(s) through the "
              f"front door")

        # -- SLO burn-rate cycle: the microsecond TTFT target makes the
        #    drilled chats a breach in both burn windows -> federated
        #    /alerts must show interactive:ttft FIRING; then, idle, the
        #    burst ages out of the windows and it must RESOLVE (the
        #    hysteresis needs resolve_after consecutive healthy evals)
        alert_snaps = {}

        def poll_alerts(phase, want_firing, deadline_s):
            deadline = time.monotonic() + deadline_s
            payload = None
            while time.monotonic() < deadline:
                status, data = request(router_port, "GET", "/alerts",
                                       timeout=10)
                if status == 200:
                    payload = json.loads(data)
                    alert_snaps[phase] = payload
                    if bool(payload.get("firing", 0)) == want_firing:
                        return payload
                time.sleep(0.3)
            return None

        fired = poll_alerts("fired", True, 30)
        if fired is None:
            failures.append(
                "/alerts never fired after the SLO breach burst "
                f"(last: {alert_snaps.get('fired')})")
        else:
            slos = sorted({a["slo"]
                           for r in fired.get("replicas", {}).values()
                           for a in r.get("alerts", [])
                           if a.get("state") == "firing"})
            print(f"  alerts FIRING: {slos}")
            if "interactive:ttft" not in slos:
                failures.append(
                    f"firing alerts {slos} lack interactive:ttft")

        # the transition must be in the flight ring while firing (the
        # post-drain dump assertion below only sees the ring's tail)
        status, data = request(router_port, "GET", "/debug/flight",
                               timeout=30)
        if status == 200:
            report = json.loads(data)
            kinds = {ev.get("kind")
                     for snap in report.get("replicas", {}).values()
                     for ev in snap.get("events", [])}
            if fired is not None and "alert" not in kinds:
                failures.append(
                    f"no 'alert' transition in any replica flight ring "
                    f"while /alerts was firing (kinds: {sorted(kinds)})")

        # -- federated time-series history: non-empty window for the
        #    router's own registry and for every replica
        status, data = request(router_port, "GET",
                               "/metrics/history?window=120", timeout=30)
        if status != 200:
            failures.append(f"/metrics/history returned {status}")
        else:
            hist = json.loads(data)
            with open(os.path.join(out, "history.json"), "w") as f:
                json.dump(hist, f, indent=2, sort_keys=True)
            if not (hist.get("router") or {}).get("series"):
                failures.append(
                    "router /metrics/history window has no series")
            reps = hist.get("replicas") or {}
            if len(reps) != 2:
                failures.append(
                    f"/metrics/history federated {sorted(reps)}, "
                    "wanted 2 replicas")
            for rname, pay in reps.items():
                if not (pay.get("series") or {}):
                    failures.append(
                        f"replica {rname} history window is empty")
            # prefix affinity may pin every drilled chat to one replica,
            # so the served lane's series need only exist SOMEWHERE
            if reps and not any(
                    k.startswith("dllama_class_ttft_ms")
                    for pay in reps.values()
                    for k in (pay.get("series") or {})):
                failures.append(
                    "no replica history holds the sampled per-class "
                    "TTFT percentile series")
            n_series = sum(len(p.get("series") or {})
                           for p in reps.values())
            print(f"  history window: {n_series} replica series "
                  f"+ {len((hist.get('router') or {}).get('series') or {})}"
                  " router series")

        resolved = poll_alerts("resolved", False, 45)
        if resolved is None:
            failures.append(
                "/alerts never resolved after the breach burst aged out "
                f"(last: {alert_snaps.get('resolved')})")
        else:
            print("  alerts RESOLVED (burst aged out of both windows)")
        with open(os.path.join(out, "alerts.json"), "w") as f:
            json.dump(alert_snaps, f, indent=2, sort_keys=True)

        # -- federation arithmetic: /metrics/fleet sums == per-replica sums
        status, data = request(router_port, "GET", "/metrics/fleet",
                               timeout=30)
        fed = data.decode()
        with open(os.path.join(out, "metrics_fleet.txt"), "w") as f:
            f.write(fed)
        if status != 200:
            failures.append(f"/metrics/fleet returned {status}")
        rep_texts = []
        for p in (base_port, base_port + 1):
            status, data = request(p, "GET", "/metrics", timeout=30)
            if status != 200:
                failures.append(f"replica :{p} /metrics returned {status}")
            rep_texts.append(data.decode())
            with open(os.path.join(out, f"metrics_replica_{p}.txt"),
                      "w") as f:
                f.write(rep_texts[-1])
        # chat-route counters are quiescent between the two scrapes (probe
        # traffic only touches /ready and /metrics series), so the sums
        # must agree EXACTLY
        for family, restrict in (
                ("dllama_http_requests_total", 'route="/v1/chat/completions"'),
                ("dllama_completion_tokens_total", "")):
            want = sum(series_sum(t, family, restrict) for t in rep_texts)
            got = series_sum(fed, family, restrict)
            label = f"{family}{{{restrict}}}" if restrict else family
            print(f"  federation {label}: fleet={got:g} replicas={want:g}")
            if got != want or want <= 0:
                failures.append(
                    f"federation mismatch for {label}: "
                    f"fleet={got:g} != sum(replicas)={want:g}")
        if 'replica="127.0.0.1:' not in fed:
            failures.append("/metrics/fleet series lack the replica label")

        # -- flight visibility while alive: router aggregates /debug/flight
        status, data = request(router_port, "GET", "/debug/flight",
                               timeout=30)
        if status != 200:
            failures.append(f"/debug/flight returned {status}")
        else:
            report = json.loads(data)
            if len(report.get("replicas", {})) != 2:
                failures.append(
                    f"/debug/flight aggregated {report.get('replicas')!r}, "
                    f"wanted 2 replicas")
    except Exception as e:
        failures.append(f"fleet drill aborted: {e!r}")
    finally:
        # SIGTERM: replicas dump their flight rings, drain, and the
        # supervisor stitches the trace parts into fleet-trace.json
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                rc = proc.wait(timeout=120)
                if rc != 0:
                    failures.append(f"fleet drain exited {rc}")
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
                failures.append("fleet did not drain within 120s")
        fleet_log.close()

    # -- stitched merged trace: router + replica spans of one request on
    #    one timeline, tied by a flow arrow, on distinct process tracks
    n_stitched = 0
    try:
        raw = open(trace).read()
        if not raw.startswith("[\n"):
            failures.append("fleet-trace.json is not a Perfetto JSON array")
        events = [json.loads(l.rstrip(","))
                  for l in raw.splitlines()[1:] if l.strip()]
        proxy = {e["args"].get("request_id"): e for e in events
                 if e.get("name") == "router_proxy" and "args" in e}
        reqs = {e["args"].get("request_id"): e for e in events
                if e.get("name") == "request" and "args" in e}
        flow_s = {e.get("id") for e in events if e.get("ph") == "s"}
        flow_f = {e.get("id") for e in events if e.get("ph") == "f"}
        for rid in drilled_ids:
            if (rid in proxy and rid in reqs
                    and proxy[rid].get("pid") != reqs[rid].get("pid")
                    and reqs[rid]["args"].get("parent_span") in
                    (flow_s & flow_f)):
                n_stitched += 1
        if n_stitched < 1:
            failures.append(
                f"no stitched request in merged trace "
                f"(proxy spans for {sorted(proxy)}, replica spans for "
                f"{sorted(reqs)}, flows s={sorted(flow_s)} "
                f"f={sorted(flow_f)})")
        names = {e["args"].get("name") for e in events
                 if e.get("name") == "process_name"}
        if "router" not in names or not any(
                str(n).startswith("replica:") for n in names):
            failures.append(f"merged trace process tracks wrong: {names}")
    except OSError as e:
        failures.append(f"merged trace unreadable: {e!r}")

    # -- SIGTERM flight dumps: one black box per replica, holding the
    #    drilled request ids in its recent events
    dumps = sorted(glob.glob(os.path.join(flight_dir, "flight-*.json")))
    if len(dumps) < 2:
        failures.append(
            f"expected >=2 flight dumps under {flight_dir}, got {dumps}")
    seen_ids = set()
    for path in dumps:
        try:
            d = json.load(open(path))
        except (OSError, ValueError) as e:
            failures.append(f"flight dump {path} unreadable: {e!r}")
            continue
        seen_ids.update(ev.get("request_id") for ev in d.get("events", []))
    if drilled_ids and not (seen_ids & set(drilled_ids)):
        failures.append(
            f"no drilled request id in any flight dump "
            f"(drilled {drilled_ids}, dumps held {sorted(seen_ids)})")

    # -- cli explain: the forensics join over the merged trace + flight
    #    dumps must produce a waterfall whose replica phase sum is within
    #    tolerance of the router-measured wall time (generous bounds:
    #    CI boxes jitter, but a sum at 10% or 300% of wall means the
    #    join picked up the wrong spans)
    explain_ok = False
    explain_docs = []
    for rid in drilled_ids:
        exp = subprocess.run(
            [sys.executable, "-m", "dllama_tpu.cli", "explain", rid,
             "--trace", trace, "--flight", flight_dir, "--json"],
            env=env, cwd=REPO, capture_output=True, text=True, timeout=60)
        try:
            wf = json.loads(exp.stdout)
        except ValueError:
            failures.append(
                f"cli explain {rid} emitted no JSON "
                f"(rc={exp.returncode}, stderr={exp.stderr[-200:]!r})")
            continue
        explain_docs.append(wf)
        if not wf.get("rows") or not wf.get("wall_ms"):
            continue
        cov = wf["phase_sum_ms"] / wf["wall_ms"]
        print(f"  explain {rid}: wall {wf['wall_ms']:.1f}ms, phase sum "
              f"{wf['phase_sum_ms']:.1f}ms ({cov:.0%} coverage, "
              f"{len(wf['rows'])} spans, {len(wf['events'])} marks)")
        if 0.25 <= cov <= 1.75:
            explain_ok = True
    if drilled_ids and not explain_ok:
        failures.append(
            "no drilled request produced an explain waterfall whose "
            "phase sum is within tolerance of wall time")
    with open(os.path.join(out, "explain.json"), "w") as f:
        json.dump(explain_docs, f, indent=2, sort_keys=True)

    verdict = {"ok": not failures, "failures": failures,
               "stitched_requests": n_stitched,
               "drilled_request_ids": drilled_ids,
               "flight_dumps": [os.path.basename(p) for p in dumps]}
    with open(os.path.join(out, "fleet_verdict.json"), "w") as f:
        json.dump(verdict, f, indent=2, sort_keys=True)

    # the drill leaves its own trajectory row (same durable format the
    # bench writes), so CI uploads a non-empty trajectory artifact even
    # on pure-CPU runners
    from dllama_tpu.obsv import trajectory
    trajectory.append_row(
        "obs_drill_fleet", "ok" if not failures else "error",
        result={"metric": "obs_drill_fleet",
                "stitched_requests": n_stitched,
                "flight_dumps": len(dumps)},
        error="; ".join(failures) or None,
        path=os.path.join(out, "trajectory.jsonl"))

    print(f"\nstitched requests in merged trace: {n_stitched}")
    print(f"flight dumps: {len(dumps)} -> {flight_dir}")
    if failures:
        for msg in failures:
            print(f"FAIL: {msg}", file=sys.stderr)
        return 1
    print("fleet observability drill: stitched trace + exact federation + "
          "flight dumps + SLO alert cycle + history + explain all verified")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out-dir", default="obs-drill")
    ap.add_argument("--fleet", action="store_true",
                    help="run the fleet leg (subprocess router + replicas) "
                         "instead of the single-process fault drill")
    args = ap.parse_args()
    os.makedirs(args.out_dir, exist_ok=True)
    if args.fleet:
        return fleet_main(args)

    from dllama_tpu import faults, observability
    from dllama_tpu.models import llama
    from dllama_tpu.runtime.generate import Engine
    from dllama_tpu.runtime.sampler import SamplerConfig
    from dllama_tpu.serving.api_server import ServerState, create_server
    from tests.test_api_server import make_tokenizer
    from tests.test_llama_forward import tiny_cfg

    observability.configure_trace(os.path.join(args.out_dir, "trace.jsonl"))
    log_stream = open(os.path.join(args.out_dir, "requests.jsonl"), "w")

    tok = make_tokenizer()
    cfg = tiny_cfg(vocab_size=tok.vocab_size, seq_len=512, dim=32, kv_dim=16,
                   head_size=8, hidden_dim=64)
    params = llama.random_params(cfg, seed=13)
    engine = Engine(cfg, params, SamplerConfig(temperature=0.0, seed=1))
    state = ServerState(engine, tok, cfg, model_name="drill",
                        template="llama3", batch_window_ms=5.0, batch_max=4,
                        queue_depth=4, log_json=True, log_stream=log_stream)
    srv = create_server(state, host="127.0.0.1", port=0)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()

    def scrape(fname: str) -> dict:
        status, data = request(port, "GET", "/metrics", timeout=30)
        assert status == 200, f"/metrics returned {status}"
        text = data.decode()
        with open(os.path.join(args.out_dir, fname), "w") as f:
            f.write(text)
        return parse_exposition(text)

    def expect(label: str, want: int, got: int) -> None:
        ok = "ok" if got == want else f"UNEXPECTED (wanted {want})"
        print(f"  {label}: HTTP {got} [{ok}]")

    try:
        # warm-up: one healthy request so latency series exist
        status, _ = request(port, "POST", "/v1/chat/completions", chat())
        expect("healthy request", 200, status)
        before = scrape("metrics_before.txt")

        print("firing fault classes:")
        # queue overflow -> 429
        tickets = [state.gate.acquire() for _ in range(4)]
        try:
            status, _ = request(port, "POST", "/v1/chat/completions", chat(),
                                timeout=30)
            expect("queue overflow", 429, status)
        finally:
            for t in tickets:
                state.gate.release(t)

        # scheduler crash -> 503 (supervisor restarts it)
        faults.install("scheduler:raise:times=1")
        status, _ = request(port, "POST", "/v1/chat/completions", chat())
        faults.clear()
        expect("scheduler crash", 503, status)

        # poisoned logits -> numeric quarantine -> 500
        faults.install("logits:nan:after=2")
        status, _ = request(port, "POST", "/v1/chat/completions", chat())
        faults.clear()
        expect("poisoned logits", 500, status)

        # deadline expiry -> 504
        state.request_timeout = 0.0001
        status, _ = request(port, "POST", "/v1/chat/completions",
                            chat(max_tokens=32))
        state.request_timeout = 0.0
        expect("deadline expiry", 504, status)

        # prove the server still serves after the whole gauntlet
        status, _ = request(port, "POST", "/v1/chat/completions", chat())
        expect("post-gauntlet request", 200, status)

        after = scrape("metrics_after.txt")
    finally:
        srv.shutdown()
        observability.configure_trace(None)
        log_stream.close()

    deltas = {name: after.get(name, 0.0) - before.get(name, 0.0)
              for name in WATCHED}
    failures = [f"{name} ({why}) did not move"
                for name, why in WATCHED.items() if deltas[name] <= 0]

    trace_file = os.path.join(args.out_dir, "trace.jsonl")
    raw = open(trace_file).read()
    events = [json.loads(l.rstrip(","))
              for l in raw.splitlines()[1:] if l.strip()]
    n_requests = sum(1 for e in events if e.get("name") == "request")
    if not raw.startswith("[\n") or n_requests < 5:
        failures.append(
            f"trace.jsonl malformed or sparse ({n_requests} request spans)")

    verdict = {"ok": not failures, "deltas": deltas, "failures": failures,
               "trace_request_spans": n_requests}
    with open(os.path.join(args.out_dir, "deltas.json"), "w") as f:
        json.dump(verdict, f, indent=2, sort_keys=True)

    print("\ncounter deltas:")
    for name, d in sorted(deltas.items()):
        print(f"  {name}: +{d:g}")
    print(f"trace spans: {n_requests} requests -> {trace_file}")
    if failures:
        for msg in failures:
            print(f"FAIL: {msg}", file=sys.stderr)
        return 1
    print("observability drill: every fault class moved a counter")
    return 0


if __name__ == "__main__":
    sys.exit(main())
