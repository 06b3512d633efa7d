"""CI failover drill: a decode replica dying mid-stream must be
invisible to the client, and every fallback-matrix row must terminate
cleanly.

Part 1 — the real fleet. Two "both" ``cli serve`` subprocesses (tiny
synthetic weights, CPU) behind an IN-PROCESS router with checkpointing
on. A streamed chat request runs once unkilled (the reference), then
again with the SERVING replica SIGKILLed right after its first content
delta. The client must still get HTTP 200, a ``[DONE]``, no error
event, and byte-identical assembled content — zero duplicate and zero
missing bytes across the splice — with the router's
``dllama_stream_resume_total{outcome="ok"}`` counter showing exactly
the one resume. Both replicas are warmed DIRECTLY (not through the
router, whose affinity would park every warm-up on one sibling), so the
survivor's radix cache holds the prompt pages when the resume lands —
and the drill GATES that ``/v1/kv/resume`` aliased them instead of
re-prefilling: ``dllama_prefix_tokens_matched_total`` must grow on the
surviving replica across the resume.

Part 2 — the fallback matrix. Two IN-PROCESS replica servers (so
``DLLAMA_FAULTS``-style plans installed via :mod:`dllama_tpu.faults`
reach both the replicas' ``stream``/``ckpt_write``/``kv_import`` seams
and the router's ``resume`` seam) stage every non-ok outcome:

    injected      resume:raise at the decision point
    no_ckpt       ckpt_write:raise — no checkpoint ever shipped
    stale_ckpt    stored splice offset tampered ahead of the stream
    admit_failed  kv_import:raise — every sibling refuses the snapshot
    no_replica    single-replica fleet, nobody left to resume on
    exhausted     stream:raise,times=2 — the resumed stream dies too

Every leg must end with HTTP 200, a typed SSE ``error`` event, a
terminating ``[DONE]``, and exactly one increment of the expected
outcome — a torn TCP cut in any leg fails the drill.

Artifacts written to --out-dir (uploaded by CI):
    verdict.json                 per-leg verdict + counter evidence
    router_metrics.txt           the part-1 router's exposition
    replica-0.log / replica-1.log

Usage:  JAX_PLATFORMS=cpu python scripts/failover_drill.py
            [--out-dir failover-drill]
Exit 0 only if every leg holds.
"""

from __future__ import annotations

import argparse
import base64
import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

RESUME_OUTCOMES = ("ok", "no_ckpt", "stale_ckpt", "admit_failed",
                   "no_replica", "injected", "exhausted")


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def request(port, method, path, body=None, timeout=120):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request(method, path,
                 body=json.dumps(body) if body is not None else None,
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def chat(max_tokens=48, **kw):
    body = {"model": "m", "max_tokens": max_tokens, "temperature": 0.0,
            "stream": True,
            "messages": [{"role": "user", "content": "hi hi resume me"}]}
    body.update(kw)
    return body


def sse_parts(data: bytes):
    """-> (content_text, saw_done, error_message-or-None)."""
    text, done, err = [], False, None
    for ev in data.split(b"\n\n"):
        for line in ev.split(b"\n"):
            if not line.startswith(b"data: "):
                continue
            payload = line[6:]
            if payload == b"[DONE]":
                done = True
                continue
            try:
                obj = json.loads(payload)
            except ValueError:
                continue
            if "error" in obj:
                err = obj["error"].get("message")
            for ch in obj.get("choices", []):
                text.append((ch.get("delta") or {}).get("content") or "")
    return "".join(text), done, err


def wait_ready(port: int, proc, deadline_s: float = 300.0) -> None:
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(
                f"replica :{port} exited {proc.returncode} before ready")
        try:
            status, _ = request(port, "GET", "/ready", timeout=2)
            if status == 200:
                return
        except OSError:
            pass  # not listening yet
        time.sleep(0.5)
    raise RuntimeError(f"replica :{port} never became ready")


def stream_with_kill(port, body, on_first_content=None):
    """Stream a chat request, invoking ``on_first_content`` (e.g. the
    SIGKILL) as soon as the first content delta lands, then reading the
    stream to its end. Returns (status, raw_bytes)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        conn.request("POST", "/v1/chat/completions",
                     json.dumps(body).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            return resp.status, resp.read()
        buf = b""
        fired = False
        while True:
            chunk = resp.read1(65536)
            if not chunk:
                break
            buf += chunk
            if not fired and on_first_content and b'"content"' in buf:
                fired = True
                on_first_content()
            if buf.endswith(b"data: [DONE]\n\n"):
                break
        return 200, buf
    finally:
        conn.close()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out-dir", default="failover-drill")
    args = ap.parse_args()
    out = os.path.abspath(args.out_dir)
    os.makedirs(out, exist_ok=True)

    import numpy as np

    from dllama_tpu import faults
    from dllama_tpu.formats.spec import ArchType, ModelSpec
    from dllama_tpu.formats.tokenizer_file import (TokenizerData,
                                                   write_tokenizer)
    from dllama_tpu.formats.weights import tensor_plan, write_model
    from dllama_tpu.quants import blocks
    from dllama_tpu.serving import router as router_mod

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

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_PLATFORM_NAME", None)
    env.pop("DLLAMA_FAULTS", None)

    def spawn(idx: int, port: int):
        log = open(os.path.join(out, f"replica-{idx}.log"), "w")
        # a tiny CPU model streams 48 tokens in well under a second —
        # slow every SSE frame write so the SIGKILL lands squarely
        # inside a live stream, not after its [DONE]
        proc = subprocess.Popen(
            [sys.executable, "-m", "dllama_tpu.cli", "serve",
             "--model", model, "--tokenizer", tokp,
             "--host", "127.0.0.1", "--port", str(port),
             "--role", "both", "--kv-pages", "16", "--ckpt-interval", "2",
             "--batch-window", "5", "--batch-max", "2", "--batch-chunk", "2",
             "--tp", "1"],
            env=dict(env, DLLAMA_FAULTS="stream:slow:delay_ms=40"),
            cwd=REPO,
            stdout=log, stderr=subprocess.STDOUT, start_new_session=True)
        log.close()
        return proc

    failures = []
    evidence: dict = {}

    def resume_counts(st) -> dict:
        return {o: st._m_resumes.value(outcome=o) for o in RESUME_OUTCOMES
                if st._m_resumes.value(outcome=o)}

    def prefix_matched(port: int) -> float:
        """The replica's dllama_prefix_tokens_matched_total reading."""
        status, data = request(port, "GET", "/metrics", timeout=10)
        if status != 200:
            raise RuntimeError(f"/metrics on :{port} returned {status}")
        for line in data.decode().splitlines():
            if line.startswith("dllama_prefix_tokens_matched_total"):
                return float(line.rsplit(" ", 1)[1])
        return 0.0

    # ---- part 1: the real fleet, a real SIGKILL ----------------------
    ports = [free_port(), free_port()]
    procs = [spawn(i, p) for i, p in enumerate(ports)]
    state = None
    rsrv = None
    try:
        for p, proc in zip(ports, procs):
            wait_ready(p, proc)
        print(f"replicas up: :{ports[0]}  :{ports[1]}")

        state = router_mod.RouterState(
            [router_mod.Replica("127.0.0.1", p) for p in ports],
            probe_interval_s=0.3, ckpt_interval=2)
        state.probe_once()
        state.start_probes()
        rsrv = router_mod.create_router_server(state, host="127.0.0.1",
                                               port=0)
        r_port = rsrv.server_address[1]
        threading.Thread(target=rsrv.serve_forever, daemon=True).start()
        print(f"router up: :{r_port} (ckpt interval {state.ckpt_interval})")

        # warm each replica DIRECTLY — the router's affinity would park
        # both warm-ups on one sibling. This compiles both programs (so
        # compile time doesn't stretch the killed stream's token cadence)
        # AND leaves the prompt pages warm in each replica's radix cache,
        # so the resume leg below can gate the skipped re-prefill.
        for p in ports:
            status, _ = request(p, "POST", "/v1/chat/completions", chat())
            if status != 200:
                raise RuntimeError(f"warm-up on :{p} returned {status}")
        # reference: the SAME streamed request, nobody killed
        status, data = request(r_port, "POST", "/v1/chat/completions",
                               chat())
        if status != 200:
            raise RuntimeError(f"reference stream returned {status}")
        ref_text, ref_done, ref_err = sse_parts(data)
        if not ref_done or ref_err or not ref_text:
            raise RuntimeError(
                f"reference stream malformed: done={ref_done} "
                f"err={ref_err!r} len={len(ref_text)}")
        if b"dllama-ckpt" in data:
            failures.append("checkpoint control frame leaked to the client")

        def kill_serving():
            # the router state is in-process: the replica with a live
            # stream is the one with nonzero in-flight
            time.sleep(0.1)  # let a checkpoint frame or two land first
            for i, r in enumerate(state.replicas):
                if r.snapshot().get("inflight", 0) > 0:
                    os.kill(procs[i].pid, signal.SIGKILL)
                    evidence["killed_replica"] = f"127.0.0.1:{ports[i]}"
                    print(f"SIGKILLed serving replica :{ports[i]} "
                          "mid-stream")
                    return
            failures.append("no in-flight replica found to kill")

        matched0 = {p: prefix_matched(p) for p in ports}
        status, data = stream_with_kill(r_port, chat(),
                                        on_first_content=kill_serving)
        got_text, got_done, got_err = sse_parts(data)
        evidence["part1_resume_counters"] = resume_counts(state)
        evidence["part1_content_len"] = len(got_text)
        # the skipped re-prefill, GATED: /v1/kv/resume on the survivor
        # must have aliased the warm prompt pages out of its radix cache
        # (the warm-ups above put them there), not re-imported or
        # re-prefilled them
        killed = evidence.get("killed_replica", "")
        survivors = [p for p in ports if not killed.endswith(f":{p}")]
        if killed and len(survivors) == 1:
            delta = prefix_matched(survivors[0]) - matched0[survivors[0]]
            evidence["part1_prefix_tokens_matched_delta"] = delta
            if delta <= 0:
                failures.append(
                    "resume re-prefilled a warm prompt: "
                    "dllama_prefix_tokens_matched_total grew by "
                    f"{delta:.0f} on surviving replica :{survivors[0]}")
        if status != 200:
            failures.append(f"killed stream returned {status}")
        if not got_done:
            failures.append("killed stream ended without [DONE] "
                            "(torn TCP cut, not a clean stream)")
        if got_err:
            failures.append(f"killed stream carried an error event: "
                            f"{got_err!r}")
        if got_text != ref_text:
            # diagnose dup vs gap for the verdict
            kind = ("duplicate bytes" if ref_text in got_text
                    else "missing bytes" if got_text in ref_text
                    else "diverged bytes")
            failures.append(
                f"killed stream content != reference ({kind}): "
                f"{got_text!r} != {ref_text!r}")
        if state._m_resumes.value(outcome="ok") < 1:
            failures.append(
                "no ok resume counted: "
                f"{resume_counts(state)}")
        with open(os.path.join(out, "router_metrics.txt"), "w") as f:
            f.write(state.metrics.render())
        print(f"part 1 done: resumes {resume_counts(state)}")
    except Exception as e:
        failures.append(f"part 1 aborted: {e!r}")
    finally:
        if state is not None:
            state.stop_probes()
        if rsrv is not None:
            rsrv.shutdown()
        for proc in procs:
            if proc.poll() is None:
                proc.terminate()
                try:
                    proc.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait()

    # ---- part 2: every fallback-matrix row, via fault injection ------
    try:
        from dllama_tpu.formats.tokenizer_file import TokenizerData as TD
        from dllama_tpu.models import llama
        from dllama_tpu.models.config import ModelConfig
        from dllama_tpu.runtime.generate import Engine
        from dllama_tpu.runtime.sampler import SamplerConfig
        from dllama_tpu.serving.api_server import ServerState, create_server
        from dllama_tpu.tokenizer.bpe import Tokenizer

        tok = Tokenizer(TD(
            vocab=[b"<unk>", b"<s>", b"</s>"]
                  + [b"<0x%02X>" % b for b in range(256)],
            scores=[0.0] * 259, bos_id=1, eos_id=2))
        cfg = ModelConfig(arch="llama", dim=64, hidden_dim=128, n_layers=2,
                          n_heads=4, n_kv_heads=2,
                          vocab_size=tok.vocab_size, seq_len=128,
                          head_size=16, kv_dim=32, dtype="float32")
        params = llama.random_params(cfg, seed=13)

        def mk_server():
            engine = Engine(cfg, params,
                            SamplerConfig(temperature=0.0, seed=1))
            st = ServerState(engine, tok, cfg, model_name="tiny",
                             template="llama3", batch_window_ms=5.0,
                             batch_chunk=2, kv_pages=16, ckpt_interval=2)
            srv = create_server(st, host="127.0.0.1", port=0)
            threading.Thread(target=srv.serve_forever, daemon=True).start()
            return srv, srv.server_address[1]

        srvA, pA = mk_server()
        srvB, pB = mk_server()
        servers = [srvA, srvB]

        def leg(name, outcome, plan, replicas, tamper=None):
            st = router_mod.RouterState(
                [router_mod.Replica("127.0.0.1", p) for p in replicas],
                probe_interval_s=60.0, ckpt_interval=2)
            st.probe_once()
            if tamper:
                tamper(st)
            rs = router_mod.create_router_server(st, "127.0.0.1", 0)
            threading.Thread(target=rs.serve_forever, daemon=True).start()
            try:
                faults.install(plan)
                status, data = request(rs.server_address[1], "POST",
                                       "/v1/chat/completions",
                                       chat(max_tokens=12))
            finally:
                faults.clear()
                rs.shutdown()
            _, done, err = sse_parts(data)
            counts = resume_counts(st)
            evidence[f"leg_{name}"] = {"status": status, "done": done,
                                       "error": err, "resumes": counts}
            if status != 200:
                failures.append(f"[{name}] returned {status}")
            if name != "ok" and err is None:
                failures.append(f"[{name}] no SSE error event "
                                "(silent termination)")
            if not done:
                failures.append(f"[{name}] stream ended without [DONE]")
            if counts.get(outcome, 0) != 1:
                failures.append(
                    f"[{name}] expected one {outcome!r} resume, "
                    f"got {counts}")
            print(f"leg {name}: {counts} error={err!r}")

        death = "stream:raise:after=4,times=1"

        def stale_put(st):
            real = st.ckpt_store.put

            def put(rid, payload, offset, replica):
                real(rid, payload, offset + 10**9, replica)
            st.ckpt_store.put = put

        leg("injected", "injected", death + ";resume:raise:times=1",
            [pA, pB])
        leg("no_ckpt", "no_ckpt", death + ";ckpt_write:raise", [pA, pB])
        leg("stale_ckpt", "stale_ckpt", death, [pA, pB], tamper=stale_put)
        leg("admit_failed", "admit_failed", death + ";kv_import:raise",
            [pA, pB])
        leg("no_replica", "no_replica", death, [pA])
        leg("exhausted", "exhausted", "stream:raise:after=4,times=2",
            [pA, pB])
        for srv in servers:
            srv.shutdown()
    except Exception as e:
        failures.append(f"part 2 aborted: {e!r}")

    verdict = {"ok": not failures, "failures": failures,
               "evidence": evidence}
    with open(os.path.join(out, "verdict.json"), "w") as f:
        json.dump(verdict, f, indent=2, sort_keys=True)
    if failures:
        for msg in failures:
            print(f"FAIL: {msg}", file=sys.stderr)
        return 1
    print("failover drill: bit-identical resume after SIGKILL + every "
          "fallback-matrix row terminating cleanly all verified")
    return 0


if __name__ == "__main__":
    sys.exit(main())
