"""CI disaggregation drill: migration must be exact, and a killed
transfer must be invisible to the client.

Topology: one dedicated-prefill and one dedicated-decode ``cli serve``
subprocess (tiny synthetic weights, CPU) behind an IN-PROCESS router —
the drill holds the replica Popen handles, which is what makes the
SIGKILL leg deterministic. The prefill replica boots with a
``kv_export:slow`` fault armed AFTER its first export, so the second
migration has a wide-open transfer window to die in.

Three legs, all must hold:

1. **Exactness** — a chat request through the router migrates
   (prefill -> KV page stream -> decode) and its answer, buffered AND
   streamed, is byte-equal to the same request served end-to-end by one
   replica directly. The router's ``outcome="ok"`` migration counter,
   both replicas' export/import counters, and the federated
   ``dllama_kv_transfer_*`` families (one HELP/TYPE each, replica
   labels) must all show it.
2. **SIGKILL mid-transfer** — the prefill replica is killed while its
   (slowed) export is in flight. The client must still get HTTP 200
   with the exact same answer: the router degrades to a full re-prefill
   on the surviving decode replica, counted as a fallback outcome —
   zero client-visible errors across the whole drill.
3. **Liveness after loss** — the fleet keeps serving normal traffic
   with the prefill replica gone (the migration path simply closes).

Artifacts written to --out-dir (uploaded by CI):
    verdict.json                 per-leg verdict + counter evidence
    router_metrics.txt           the in-process router's exposition
    metrics_fleet.txt            the federated /metrics/fleet body
    replica-prefill.log / replica-decode.log

Usage:  JAX_PLATFORMS=cpu python scripts/disagg_drill.py
            [--out-dir disagg-drill]
Exit 0 only if every leg holds.
"""

from __future__ import annotations

import argparse
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
    ctype = resp.getheader("Content-Type") or ""
    conn.close()
    return resp.status, ctype, data


def chat(**kw):
    body = {"model": "m", "max_tokens": 16, "temperature": 0.0,
            "messages": [{"role": "user", "content": "hi hi migrate me"}]}
    body.update(kw)
    return body


def sse_text(data: bytes) -> str:
    out = []
    for line in data.decode("utf-8", "replace").splitlines():
        if line.startswith("data: ") and line != "data: [DONE]":
            delta = json.loads(line[6:])["choices"][0].get("delta") or {}
            out.append(delta.get("content", ""))
    return "".join(out)


def counter_values(text: str, family: str) -> dict:
    """{label_block: value} for one family in a Prometheus exposition."""
    out = {}
    for line in text.splitlines():
        if not line.startswith(family):
            continue
        sample, _, value = line.rpartition(" ")
        try:
            out[sample[len(family):]] = float(value)
        except ValueError:
            pass
    return out


def wait_ready(port: int, proc, deadline_s: float = 300.0) -> None:
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(
                f"replica :{port} exited {proc.returncode} before ready")
        try:
            status, _, _ = request(port, "GET", "/ready", timeout=2)
            if status == 200:
                return
        except OSError:
            pass  # not listening yet
        time.sleep(0.5)
    raise RuntimeError(f"replica :{port} never became ready")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out-dir", default="disagg-drill")
    args = ap.parse_args()
    out = os.path.abspath(args.out_dir)
    os.makedirs(out, exist_ok=True)

    import numpy as np

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

    def spawn(role: str, port: int, extra_env: dict = None):
        log = open(os.path.join(out, f"replica-{role}.log"), "w")
        proc = subprocess.Popen(
            [sys.executable, "-m", "dllama_tpu.cli", "serve",
             "--model", model, "--tokenizer", tokp,
             "--host", "127.0.0.1", "--port", str(port),
             "--role", role, "--kv-pages", "16",
             "--batch-window", "5", "--batch-max", "2", "--tp", "1"],
            env=dict(env, **(extra_env or {})), cwd=REPO,
            stdout=log, stderr=subprocess.STDOUT, start_new_session=True)
        log.close()
        return proc

    p_port, d_port = free_port(), free_port()
    # leg 1 performs two exports (buffered + SSE); the THIRD stalls 8s so
    # leg 2's SIGKILL lands squarely inside an in-flight transfer, not in
    # a lucky gap between requests
    p_proc = spawn("prefill", p_port,
                   {"DLLAMA_FAULTS": "kv_export:slow:delay_ms=8000,after=2"})
    d_proc = spawn("decode", d_port)

    failures = []
    evidence: dict = {}
    state = None
    rsrv = None
    try:
        wait_ready(p_port, p_proc)
        wait_ready(d_port, d_proc)
        print(f"replicas up: prefill :{p_port}  decode :{d_port}")

        state = router_mod.RouterState(
            [router_mod.Replica("127.0.0.1", p_port),
             router_mod.Replica("127.0.0.1", d_port)],
            probe_interval_s=0.3)
        state.probe_once()
        if not state.disagg_ready():
            raise RuntimeError(
                "router does not see a prefill+decode fleet: "
                + json.dumps([r.snapshot() for r in state.replicas]))
        state.start_probes()
        rsrv = router_mod.create_router_server(state, host="127.0.0.1",
                                               port=0)
        r_port = rsrv.server_address[1]
        threading.Thread(target=rsrv.serve_forever, daemon=True).start()
        print(f"router up: :{r_port} (kv wire {state.kv_wire})")

        def migrations() -> dict:
            snap = state.metrics.snapshot().get(
                "dllama_kv_transfer_migrations_total", {})
            return {v["labels"]["outcome"]: v["value"]
                    for v in snap.get("values", [])}

        # -- leg 1: exactness -----------------------------------------
        # reference: the decode replica serving the SAME request alone
        status, _, data = request(d_port, "POST", "/v1/chat/completions",
                                  chat())
        if status != 200:
            raise RuntimeError(f"solo reference returned {status}")
        solo = json.loads(data)["choices"][0]["message"]["content"]

        status, _, data = request(r_port, "POST", "/v1/chat/completions",
                                  chat())
        if status != 200:
            failures.append(f"migrated request returned {status}")
        else:
            got = json.loads(data)["choices"][0]["message"]["content"]
            if got != solo:
                failures.append(
                    f"migrated answer diverged: {got!r} != solo {solo!r}")

        status, ctype, data = request(r_port, "POST", "/v1/chat/completions",
                                      chat(stream=True))
        if status != 200 or "text/event-stream" not in ctype:
            failures.append(
                f"migrated SSE request returned {status} ({ctype})")
        elif sse_text(data) != solo:
            failures.append(
                f"migrated SSE answer diverged: {sse_text(data)!r}")

        evidence["migrations_after_leg1"] = migrations()
        if migrations().get("ok", 0) < 2:
            failures.append(
                f"expected >=2 ok migrations, got {migrations()}")

        # counters on both sides of the wire, and their federated view
        _, _, p_metrics = request(p_port, "GET", "/metrics", timeout=30)
        _, _, d_metrics = request(d_port, "GET", "/metrics", timeout=30)
        exports = counter_values(p_metrics.decode(),
                                 "dllama_kv_transfer_exports_total")
        imports = counter_values(d_metrics.decode(),
                                 "dllama_kv_transfer_imports_total")
        evidence["prefill_exports"] = exports
        evidence["decode_imports"] = imports
        if exports.get('{outcome="ok"}', 0) < 2:
            failures.append(f"prefill replica exports: {exports}")
        if imports.get('{outcome="ok"}', 0) < 2:
            failures.append(f"decode replica imports: {imports}")
        _, _, fed = request(r_port, "GET", "/metrics/fleet", timeout=30)
        fed = fed.decode()
        with open(os.path.join(out, "metrics_fleet.txt"), "w") as f:
            f.write(fed)
        for fam in ("dllama_kv_transfer_exports_total",
                    "dllama_kv_transfer_bytes_total"):
            if fed.count(f"# HELP {fam}") != 1:
                failures.append(f"/metrics/fleet HELP for {fam} not deduped")
            if f'{fam}{{replica="127.0.0.1:' not in fed:
                failures.append(f"/metrics/fleet lacks labeled {fam}")
        print(f"leg 1 done: migrations {migrations()}")

        # -- leg 2: SIGKILL the prefill replica mid-transfer ----------
        def kill_prefill():
            time.sleep(1.5)  # inside the 8s slowed export, after admit
            os.kill(p_proc.pid, signal.SIGKILL)
            print("SIGKILLed the prefill replica mid-export")

        killer = threading.Thread(target=kill_prefill, daemon=True)
        killer.start()
        t0 = time.monotonic()
        status, _, data = request(r_port, "POST", "/v1/chat/completions",
                                  chat())
        killer.join()
        evidence["leg2_latency_s"] = round(time.monotonic() - t0, 2)
        if status != 200:
            failures.append(
                f"request during prefill death returned {status} "
                f"(must degrade, never error)")
        else:
            got = json.loads(data)["choices"][0]["message"]["content"]
            if got != solo:
                failures.append(
                    f"fallback answer diverged: {got!r} != solo {solo!r}")
        mig = migrations()
        evidence["migrations_after_leg2"] = mig
        if not (mig.get("prefill_fallback") or mig.get("no_prefill")):
            failures.append(
                f"no fallback outcome counted after the kill: {mig}")

        # -- leg 3: the fleet keeps serving without its prefill half --
        for i in range(2):
            status, _, data = request(r_port, "POST", "/v1/chat/completions",
                                      chat())
            if status != 200:
                failures.append(f"post-kill request #{i} returned {status}")
            elif json.loads(data)["choices"][0]["message"]["content"] != solo:
                failures.append(f"post-kill answer #{i} diverged")
        print(f"legs 2+3 done: migrations {mig}")

        with open(os.path.join(out, "router_metrics.txt"), "w") as f:
            f.write(state.metrics.render())
    except Exception as e:
        failures.append(f"drill aborted: {e!r}")
    finally:
        if state is not None:
            state.stop_probes()
        if rsrv is not None:
            rsrv.shutdown()
        for proc in (p_proc, d_proc):
            if proc.poll() is None:
                proc.terminate()
                try:
                    proc.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait()

    verdict = {"ok": not failures, "failures": failures,
               "evidence": evidence}
    with open(os.path.join(out, "verdict.json"), "w") as f:
        json.dump(verdict, f, indent=2, sort_keys=True)
    if failures:
        for msg in failures:
            print(f"FAIL: {msg}", file=sys.stderr)
        return 1
    print("disaggregation drill: exact migration + invisible transfer "
          "death + post-loss liveness all verified")
    return 0


if __name__ == "__main__":
    sys.exit(main())
