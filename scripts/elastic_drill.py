"""CI elastic drill: every scale transition of the closed-loop fleet
must be invisible to clients — scale-up pre-warms before taking
traffic, scale-down drains gracefully, and a SIGKILL mid-drain still
resolves every live stream byte-identically.

One real fleet of tiny ``cli serve`` subprocesses (synthetic Q40
weights, CPU) behind an IN-PROCESS router, with the autoscale policy
stepped BY HAND (``sup.step()``) so every transition in the drill is
deterministic and attributable.

Part 1 — burst -> policy scale-up with pre-warm. A repeated hot prompt
is pushed through the router (recording it in the router's hot-prompt
index and warming the serving replica's radix cache), then saturating
streams drive pressure to 1.0 until the policy decides UP. The joining
replica must be pre-warmed from its sibling over the kv page stream
(``/v1/prefill`` -> ``/v1/kv/import``) BEFORE activation — gated by
``dllama_prefix_tokens_matched_total`` growing on the NEW replica when
the hot prompt is replayed directly against it, and by zero
``prewarm_fallback`` scale events.

Part 2 — idle -> policy scale-down, client-invisible. With the fleet
idle (one slow live stream riding through the transition), policy steps
must decide DOWN; the victim (the least-loaded replica) drains via
SIGTERM and retires gracefully — the live stream ends 200/[DONE]/
error-free and byte-identical to its unkilled reference, with zero
``drain_killed``.

Part 3 — SIGKILL during drain. Back at two replicas (a second forced
pre-warmed scale-up), a live stream's replica is force-retired and then
SIGKILLed mid-drain. The router's checkpoint + ``/v1/kv/resume``
machinery must splice the stream onto the sibling byte-identically:
``dllama_stream_resume_total{outcome="ok"}`` grows and the kill is
counted as ``drain_killed``.

Zero client-visible errors are required across EVERY request the drill
sends, saturation traffic included.

Artifacts written to --out-dir (uploaded by CI):
    verdict.json                 per-part verdict + counter evidence
    router_metrics.txt           the router's final exposition
    replica-*.log                every replica's (fleet log_dir) output

Usage:  JAX_PLATFORMS=cpu python scripts/elastic_drill.py
            [--out-dir elastic-drill]
Exit 0 only if every gate holds.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import signal
import socket
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SCALE_EVENTS = ("joined", "draining", "retired", "spawn_failed",
                "prewarm_fallback", "drain_killed", "injected")


def free_base(span: int) -> int:
    """A base port with ``span`` consecutive free ports above it (the
    fleet binds base..base+n-1 and scale-ups keep counting up)."""
    for _ in range(64):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            base = s.getsockname()[1]
        if base + span > 65500:
            continue
        try:
            for i in range(1, span):
                with socket.socket() as t:
                    t.bind(("127.0.0.1", base + i))
            return base
        except OSError:
            continue
    raise RuntimeError("no free port span for the fleet")


def request(port, method, path, body=None, timeout=300, headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request(method, path,
                 body=json.dumps(body) if body is not None else None,
                 headers=dict({"Content-Type": "application/json"},
                              **(headers or {})))
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def chat(content, max_tokens=48):
    return {"model": "m", "max_tokens": max_tokens, "temperature": 0.0,
            "stream": True,
            "messages": [{"role": "user", "content": content}]}


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


def stream_with_hook(port, body, on_first_content=None):
    """Stream a chat request, invoking ``on_first_content`` as soon as
    the first content delta lands, then reading the stream to its end.
    Returns (status, raw_bytes)."""
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


def prefix_matched(port: int) -> float:
    """The replica's dllama_prefix_tokens_matched_total reading."""
    status, data = request(port, "GET", "/metrics", timeout=10)
    if status != 200:
        raise RuntimeError(f"/metrics on :{port} returned {status}")
    for line in data.decode().splitlines():
        if line.startswith("dllama_prefix_tokens_matched_total"):
            return float(line.rsplit(" ", 1)[1])
    return 0.0


HOT = "hot alpha beta"          # part 1's pre-warm refrain
DRAINED = "drain me softly"     # part 2's ride-along stream
CHAOS = "chaos mid drain"       # part 3's resumed stream


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out-dir", default="elastic-drill")
    args = ap.parse_args()
    out = os.path.abspath(args.out_dir)
    os.makedirs(out, exist_ok=True)

    import numpy as np

    from dllama_tpu.formats.spec import ArchType, ModelSpec
    from dllama_tpu.formats.tokenizer_file import (TokenizerData,
                                                   write_tokenizer)
    from dllama_tpu.formats.weights import tensor_plan, write_model
    from dllama_tpu.quants import blocks
    from dllama_tpu.serving import autoscale as asc
    from dllama_tpu.serving import fleet as fleet_mod
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
    # a tiny CPU model streams its tokens in well under a second — slow
    # every SSE frame so streams outlive the scale transitions they gate
    env["DLLAMA_FAULTS"] = "stream:slow:delay_ms=40"

    failures: list = []
    evidence: dict = {}

    fl = fleet_mod.Fleet(
        model, tokp, n_replicas=1, base_port=free_base(4),
        host="127.0.0.1",
        replica_args=["--kv-pages", "16", "--ckpt-interval", "2",
                      "--batch-window", "5", "--batch-max", "2",
                      "--batch-chunk", "2", "--tp", "1"],
        log_dir=out, env=env)
    state = rsrv = None
    try:
        fl.start()
        if not fl.wait_ready(timeout_s=300.0):
            raise RuntimeError("the seed replica never became ready")
        port0 = fl.replicas[0].port
        state = router_mod.RouterState(
            [router_mod.Replica("127.0.0.1", port0)],
            probe_interval_s=0.25, ckpt_interval=2)
        state.probe_once()
        state.start_probes()
        rsrv = router_mod.create_router_server(state, "127.0.0.1", 0)
        r_port = rsrv.server_address[1]
        threading.Thread(target=rsrv.serve_forever, daemon=True).start()
        print(f"fleet up: replica :{port0}, router :{r_port}")

        cfg = asc.PolicyConfig(
            min_replicas=1, max_replicas=2, up_pressure=0.5,
            down_pressure=0.35, up_consecutive=2, down_consecutive=3,
            cooldown_up_s=1.0, cooldown_down_s=2.0)
        sup = fleet_mod.ElasticSupervisor(
            fl, state, asc.AutoscalePolicy(cfg), interval_s=0.2,
            ready_timeout_s=300.0, drain_timeout_s=30.0,
            prewarm_prompts=4, prewarm_tokens=8)

        def events() -> dict:
            return {e: state._m_scale_events.value(event=e)
                    for e in SCALE_EVENTS
                    if state._m_scale_events.value(event=e)}

        def client(res: tuple, what: str):
            """Every drill request is client traffic: 200/[DONE]/no
            error, or the drill fails."""
            status, data = res
            text, done, err = sse_parts(data)
            if status != 200 or err or not done:
                failures.append(f"client-visible damage [{what}]: "
                                f"{status} err={err!r} done={done}")
            return text

        # ---- part 1: burst -> scale-up with pre-warm -----------------
        # compile the seed replica's programs outside every gate below
        client(request(r_port, "POST", "/v1/chat/completions",
                       chat(HOT, max_tokens=8)), "warm-up")
        for i in range(2):  # make HOT the hottest router prompt
            client(request(r_port, "POST", "/v1/chat/completions",
                           chat(HOT, max_tokens=8)), f"hot-{i}")

        stop_sat = threading.Event()

        def saturate(i):
            while not stop_sat.is_set():
                client(request(r_port, "POST", "/v1/chat/completions",
                               chat(HOT, max_tokens=48)), f"sat-{i}")

        sats = [threading.Thread(target=saturate, args=(i,), daemon=True)
                for i in range(4)]
        for t in sats:
            t.start()
        ups0 = state._m_policy_evals.value(decision="up")
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline and sup.n_replicas() < 2:
            sup.step()
            time.sleep(0.2)
        stop_sat.set()
        for t in sats:
            t.join(timeout=300.0)
        evidence["part1_events"] = events()
        ups = state._m_policy_evals.value(decision="up") - ups0
        if sup.n_replicas() < 2:
            failures.append("the policy never scaled up under a "
                            f"saturating burst (up decisions {ups:.0f})")
            raise RuntimeError("part 1 failed, nothing left to drill")
        if ups < 1:
            failures.append("scaled up without an up decision (policy "
                            "bypassed?)")
        if events().get("prewarm_fallback"):
            failures.append("scale-up fell back to a cold join: "
                            f"{events()}")
        new = [r for r in fl.replicas if r.port != port0][0]
        matched0 = prefix_matched(new.port)
        # the hot prompt DIRECTLY against the new replica: its radix
        # must already hold the prompt pages from the pre-warm import.
        # Batch class on purpose — a lone interactive completion is
        # served on the solo engine path, which never consults the
        # paged pool's radix cache and would read delta 0 even on a
        # perfectly warmed replica
        client(request(new.port, "POST", "/v1/chat/completions",
                       chat(HOT, max_tokens=8),
                       headers={"X-Dllama-Class": "batch"}),
               "prewarm-probe")
        delta = prefix_matched(new.port) - matched0
        evidence["part1_prefix_tokens_matched_delta"] = delta
        evidence["part1_up_decisions"] = ups
        if delta <= 0:
            failures.append(
                "the joining replica was not pre-warmed: replaying the "
                "hot prompt against it matched "
                f"{delta:.0f} prefix tokens (expected > 0)")
        print(f"part 1 done: fleet=2, up decisions {ups:.0f}, "
              f"pre-warm prefix delta {delta:.0f}, events {events()}")

        # ---- part 2: idle -> policy scale-down, client-invisible -----
        ref2 = client(request(r_port, "POST", "/v1/chat/completions",
                              chat(DRAINED, max_tokens=48)), "part2-ref")
        downs0 = state._m_policy_evals.value(decision="down")
        dk0 = state._m_scale_events.value(event="drain_killed")
        live2 = [None]

        def ride2():
            live2[0] = request(r_port, "POST", "/v1/chat/completions",
                               chat(DRAINED, max_tokens=48))

        rt2 = threading.Thread(target=ride2, daemon=True)
        rt2.start()
        # step the policy while the stream rides: one slow stream on a
        # 2-replica fleet sits under down_pressure, so the cold streak
        # plus the post-part-1 cooldown must decide DOWN and retire the
        # LEAST-loaded replica out from under the fleet without the
        # client noticing
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline and sup.n_replicas() > 1:
            sup.step()
            time.sleep(0.2)
        rt2.join(timeout=300.0)
        downs = state._m_policy_evals.value(decision="down") - downs0
        evidence["part2_down_decisions"] = downs
        evidence["part2_events"] = events()
        if sup.n_replicas() != 1:
            failures.append("the policy never scaled down an idle fleet "
                            f"(down decisions {downs:.0f})")
        if downs < 1:
            failures.append("scaled down without a down decision")
        if state._m_scale_events.value(event="drain_killed") != dk0:
            failures.append("an idle graceful drain needed SIGKILL")
        got2 = client(live2[0], "part2-live") if live2[0] else ""
        if live2[0] is None:
            failures.append("part 2 live stream never resolved")
        elif got2 != ref2:
            failures.append(f"stream across graceful scale-down != "
                            f"reference: {got2!r} != {ref2!r}")
        print(f"part 2 done: fleet=1, down decisions {downs:.0f}, "
              f"events {events()}")

        # ---- part 3: SIGKILL during drain ----------------------------
        if not sup.scale_up():  # forced: re-exercises the pre-warm path
            raise RuntimeError("forced scale-up for part 3 failed")
        ref3 = client(request(r_port, "POST", "/v1/chat/completions",
                              chat(CHAOS, max_tokens=48)), "part3-ref")
        ok0 = state._m_resumes.value(outcome="ok")
        dk0 = state._m_scale_events.value(event="drain_killed")

        def kill_mid_drain():
            time.sleep(0.1)  # let a checkpoint frame or two land first
            victim = None
            for rep in state.replicas:
                if rep.snapshot().get("inflight", 0) > 0:
                    victim = rep.name
                    break
            if victim is None:
                failures.append("part 3: no in-flight replica found")
                return
            evidence["part3_victim"] = victim
            proc = next(p for p in fl.replicas if p.name == victim)
            threading.Thread(target=lambda: sup.scale_down(target=victim),
                             daemon=True).start()
            time.sleep(0.3)  # SIGTERM delivered, the drain is under way
            if proc.proc.poll() is None:
                os.kill(proc.proc.pid, signal.SIGKILL)
                print(f"part 3: SIGKILLed {victim} mid-drain")

        status3, data3 = stream_with_hook(r_port, chat(CHAOS, max_tokens=48),
                                          on_first_content=kill_mid_drain)
        got3 = client((status3, data3), "part3-live")
        resumes = state._m_resumes.value(outcome="ok") - ok0
        drain_killed = state._m_scale_events.value(event="drain_killed") - dk0
        evidence["part3_resumes_ok"] = resumes
        evidence["part3_events"] = events()
        if got3 != ref3:
            kind = ("duplicate bytes" if ref3 in got3
                    else "missing bytes" if got3 in ref3
                    else "diverged bytes")
            failures.append(f"stream across SIGKILL-mid-drain != "
                            f"reference ({kind}): {got3!r} != {ref3!r}")
        if resumes < 1:
            failures.append("mid-drain SIGKILL but no ok resume counted")
        if drain_killed < 1:
            failures.append("mid-drain SIGKILL not counted drain_killed")
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and state._count_registered() > 1:
            time.sleep(0.1)
        if state._count_registered() != 1:
            failures.append("the killed replica was never deregistered")
        print(f"part 3 done: resumes ok {resumes:.0f}, "
              f"drain_killed {drain_killed:.0f}, events {events()}")
        with open(os.path.join(out, "router_metrics.txt"), "w") as f:
            f.write(state.metrics.render())
    except Exception as e:
        failures.append(f"drill aborted: {e!r}")
    finally:
        if state is not None:
            state.stop_probes()
        if rsrv is not None:
            rsrv.shutdown()
        fl.drain(timeout_s=30.0)

    verdict = {"ok": not failures, "failures": failures,
               "evidence": evidence}
    with open(os.path.join(out, "verdict.json"), "w") as f:
        json.dump(verdict, f, indent=2, sort_keys=True)
    if failures:
        for msg in failures:
            print(f"FAIL: {msg}", file=sys.stderr)
        return 1
    print("elastic drill: pre-warmed scale-up, client-invisible "
          "scale-down, and byte-identical resume across a SIGKILLed "
          "drain all verified")
    return 0


if __name__ == "__main__":
    sys.exit(main())
