#!/usr/bin/env python3
"""The benchmark's entry: one run of one cell.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process never imports JAX. It reads the cell from ``BENCHMARK.json``, the
configuration from ``benchmarks/configs/<configuration>.json`` (whose
``family`` names the one package that knows the model:
``benchmarks/families/<family>/``, see ``families.load``), the traffic mix
from ``benchmarks/traffic/<traffic>.json`` and each metric from
``benchmarks/end_to_end/<metric>.json`` or ``benchmarks/layer_metrics/<metric>.json``
(a ``reader``, which names a module under ``benchmarks/readers/``, and its
``args``; everything else about a metric is said once, in ``BENCHMARK.json``).
The rule that decides ``correct`` is ``benchmarks/correct/<cell>.json`` where
the cell has one, else the configuration's ``correct`` group. A new cell, mix,
metric or architecture is new files and one new entry, never an edit here.

It starts one child (``launcher.py``: the server as ``cli serve`` builds it,
on weights made from the seed, holding the chip), sends the mix's fixed warm
set through ``POST /v1/chat/completions``, lets the seeded traffic ramp up,
then measures for ``--seconds`` seconds. ``setup_s`` runs from this process's
start to the window's first instant. After the window the requests sent in it
run to their end under unchanged load, the child stops the server, reads the
peak memory, frees the program's state and holds a seeded sample of the
finished requests (the longest among them) to the family's plain reference.
The last line of stdout is the result; the last lines of stderr are the
numbers compared, each beside its limit.

Exit code 3 and no result: no accelerator, fewer chips than the cell asks
for, an unknown ``device_kind``, or a directory without the program.
"""

from __future__ import annotations

import argparse
import http.client
import importlib.util
import json
import os
import queue
import random
import shutil
import subprocess
import sys
import threading
import time

T_START = time.monotonic()  # setup_s counts from here
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "readers"))

import families  # noqa: E402
import gapstats  # noqa: E402
import loadgen  # noqa: E402
import promtext  # noqa: E402

READY_TIMEOUT_S = 1100.0
TICK_PHASES = "dllama_tick_phase_seconds_total"
FINISH_TIMEOUT_S = 300.0


class Failure(Exception):
    """The run cannot give a result."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_files(bench: dict, workload: str) -> tuple:
    """-> (cell, path of its configuration, configuration, traffic mix)"""
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise Failure(f"no workload {workload!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    path = os.path.join(ROOT, entry["file"])
    return (cell, path, load_json(path),
            load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json")))


def correct_rule(cell: dict, conf: dict) -> dict:
    """The cell's own rule where it has one, else its configuration's."""
    own = os.path.join(HERE, "correct", cell["name"] + ".json")
    return load_json(own)["correct"] if os.path.exists(own) else conf["correct"]


def metrics_of(bench: dict, group: str, workload: str, reported: set) -> list:
    """The cell's metrics of one group. A per-layer metric without a
    ``workloads`` key belongs to every cell that reports what it moves."""
    out = []
    for m in bench[group]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif group == "end_to_end" or m["moves"] in reported:
            out.append(m)
    return out


def read_metric(group: str, name: str, ctx: dict):
    """One metric by its own file: ``end_to_end/<name>.json`` or
    ``layer_metrics/<name>.json`` names a reader and its arguments."""
    spec = load_json(os.path.join(HERE, group, name + ".json"))
    path = os.path.join(HERE, "readers", spec["reader"] + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "reader_" + spec["reader"], path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(ctx, spec.get("args", {}))


# ---------------------------------------------------------------------------
# the child
# ---------------------------------------------------------------------------

class Child:
    def __init__(self, conf_path: str, seed: int, chips: int, trace_dir: str,
                 rehearse: bool, fault: str, log_path: str):
        env = {k: v for k, v in os.environ.items()
               if k not in ("DLLAMA_TRACE", "DLLAMA_FLIGHT")}
        env["PYTHONUNBUFFERED"] = "1"
        # the program keeps its compile cache where this says: one fixed
        # place inside the checkout, whatever the machine had set
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
            ROOT, ".jax_cache" if not rehearse else ".jax_cache_rehearsal")
        if rehearse:
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
        cmd = [sys.executable, os.path.join(HERE, "launcher.py"),
               "--config", conf_path, "--seed", str(seed),
               "--chips", str(chips), "--trace-dir", trace_dir]
        if rehearse:
            cmd.append("--rehearse")
        if fault:
            cmd += ["--fault", fault]
        self.log = open(log_path, "wb")
        self.proc = subprocess.Popen(
            cmd, cwd=ROOT, env=env, stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=self.log)
        self.answers: queue.Queue = queue.Queue()
        self.log_path = log_path
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        for raw in self.proc.stdout:
            line = raw.decode("utf-8", "replace").rstrip("\n")
            if line.startswith("@@ "):
                self.answers.put(json.loads(line[3:]))
        self.answers.put(None)  # the child's stdout closed

    def answer(self, timeout: float) -> dict:
        try:
            got = self.answers.get(timeout=timeout)
        except queue.Empty:
            raise Failure(f"the child gave no answer in {timeout:.0f}s:\n"
                          + self.log_tail()) from None
        if got is None:
            rc = self.proc.wait(timeout=30)
            if rc == 3:
                raise SystemExit(3)
            raise Failure(f"the child ended (exit code {rc}):\n" + self.log_tail())
        return got

    def ask(self, cmd: dict, timeout: float) -> dict:
        self.proc.stdin.write((json.dumps(cmd) + "\n").encode())
        self.proc.stdin.flush()
        return self.answer(timeout)

    def log_tail(self, n: int = 3000) -> str:
        self.log.flush()
        with open(self.log_path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode("utf-8", "replace")

    def close(self) -> None:
        """Stop the child and wait until it has ended."""
        try:
            if self.proc.poll() is None:
                try:
                    self.proc.stdin.close()
                except OSError:
                    pass
                try:
                    self.proc.wait(timeout=20)
                except subprocess.TimeoutExpired:
                    self.proc.kill()
                    self.proc.wait()
        finally:
            self.log.close()


def http_get(port: int, path: str, timeout: float = 30.0) -> bytes:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        body = resp.read()
        if resp.status != 200:
            raise Failure(f"GET {path} answered {resp.status}")
        return body
    finally:
        conn.close()


def scrape(port: int) -> dict:
    return {"t": time.monotonic(),
            "prom": promtext.parse(http_get(port, "/metrics").decode()),
            "stats": json.loads(http_get(port, "/stats"))}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run(args) -> int:
    bench = load_json(args.benchmark)
    cell, conf_path, conf, mix = cell_files(bench, args.workload)
    try:  # before any device work
        family = families.load(conf)
    except ValueError as e:
        raise Failure(str(e)) from None
    peaks_all = load_json(os.path.join(HERE, "peaks.json"))["device_kinds"]
    scratch = os.path.join(HERE, ".scratch", args.workload)
    shutil.rmtree(scratch, ignore_errors=True)
    os.makedirs(scratch)
    trace_dir = os.path.join(scratch, "trace")

    child = Child(conf_path, args.seed, int(cell["chips"]), trace_dir, args.rehearse, args.fault,
                  os.path.join(scratch, "child.log"))
    try:
        return _drive(args, bench, cell, conf, family, mix, peaks_all, child,
                      scratch)
    finally:
        child.close()


def _drive(args, bench, cell, conf, family, mix, peaks_all, child,
           scratch) -> int:
    ready = child.answer(READY_TIMEOUT_S)
    port, device = ready["port"], ready["device"]
    peaks = peaks_all.get(device["kind"])
    if peaks is None and not args.rehearse:
        raise SystemExit(3)
    note(f"child ready after {time.monotonic() - T_START:.1f}s: {ready['seconds']}")

    # warm every shape the cell's traffic uses, through the same entry
    t_w = time.monotonic()
    warm_results: list = []
    for phase in loadgen.warm_requests(mix):
        for res in loadgen.run_together(port, phase):
            if not res.ok:
                raise Failure(f"a warm request failed: {res.status} {res.error}")
            warm_results.append(res)
    note(f"warm set: {time.monotonic() - t_w:.1f}s, compile cache "
         f"{json.loads(http_get(port, '/stats'))['compile_cache']}")

    requests = loadgen.make_requests(mix, args.seed, int(mix.get("requests", 4000)))
    loop = loadgen.Loop(port, mix, requests)
    loop.start()
    time.sleep(float(mix.get("ramp_s", 0.0)))
    edge0 = scrape(port)
    t0 = edge0["t"]
    setup_s = t0 - T_START
    t1_due = t0 + args.seconds
    trace_edges = None
    if args.trace:
        trace_s = float(mix.get("trace_s", min(4.0, args.seconds / 3.0)))
        time.sleep(max(0.0, t0 + 0.4 * args.seconds - time.monotonic()))
        child.ask({"cmd": "trace_start"}, 60.0)
        ta = scrape(port)
        time.sleep(trace_s)
        tb = scrape(port)
        child.ask({"cmd": "trace_stop"}, 120.0)
        trace_edges = (ta, tb)
    time.sleep(max(0.0, t1_due - time.monotonic()))
    edge1 = scrape(port)
    t1 = edge1["t"]
    # requests sent in the window run to their end under unchanged load:
    # the loop keeps sending until the last of them has ended
    deadline = time.monotonic() + 60.0
    while loop.pending_before(t1) and time.monotonic() < deadline:
        time.sleep(0.05)
    all_ended = loop.stop(timeout=60.0)
    results = loop.snapshot()
    stats = loadgen.window_stats(results, t0, t1)
    if not all_ended:
        stats["failed"] += 1  # a request that never ended

    # the sample for the reference: the longest finished request and a few
    # more drawn from the seed
    finished = sorted(stats["finished"], key=lambda r: r.request.index)
    rng = random.Random(args.seed)
    sample: list = []
    bad_text = 0
    if finished:
        longest = max(finished, key=lambda r: (sum(k for _, k in r.bursts),
                                               r.request.prompt_tokens))
        rest = [r for r in finished if r is not longest]
        k = min(len(rest), int(mix.get("sample_requests", 6)) - 1)
        for r in [longest] + rng.sample(rest, k):
            ids = r.ids()
            if ids is None or not ids:
                bad_text += 1
                continue
            sample.append({"prompt": loadgen.encode_prompt(r.request.user),
                           "served": ids})
    for r in finished:
        if r.ids() is None:
            bad_text += 1
    # what the client counted against what the server counted, over the
    # whole run (every request has ended, so the two must agree exactly; the
    # server counts a request just after its last frame, so ask again for a
    # moment before calling a difference one)
    sent_all = [r for r in warm_results + results if r.ok]
    for _ in range(15):
        prom = promtext.parse(http_get(port, "/metrics").decode())
        count_gap = {
            "prompt_tokens": abs(
                promtext.total(prom, "dllama_prompt_tokens_total")
                - sum(r.request.prompt_tokens for r in sent_all)),
            "output_tokens": abs(
                promtext.total(prom, "dllama_completion_tokens_total")
                - sum(k for r in sent_all for _, k in r.bursts)),
        }
        if not any(count_gap.values()):
            break
        time.sleep(0.2)
    stand_ins = set(gapstats.needs(correct_rule(cell, conf)))
    if args.control:
        stand_ins |= {"control", "witness"}
    fin = child.ask({"cmd": "finish", "samples": sample,
                     "stand_ins": sorted(stand_ins)}, FINISH_TIMEOUT_S)

    ctx = {
        "edge0": edge0, "edge1": edge1, "trace_edges": trace_edges,
        "trace": fin.get("trace"), "client": stats, "results": results,
        "window": (t0, t1), "model": conf, "family": family,
        "server": conf["server"],
        "mix": mix, "peaks": peaks, "chips": int(cell["chips"]),
        "memory_peak_bytes": fin["memory_peak_bytes"],
        "lateness_s": loop.lateness_s, "setup_s": setup_s,
        "sample": sample,
    }
    return report(args, bench, cell, conf, ctx, stats, device, fin, bad_text,
                  count_gap)


def note(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def report(args, bench, cell, conf, ctx, stats, device, fin, bad_text,
           count_gap) -> int:
    window_s = stats["seconds"]
    e2e = metrics_of(bench, "end_to_end", cell["name"], set())
    reported = {m["name"] for m in e2e}
    metrics: dict = {}
    if not args.trace:
        for m in e2e:
            v = read_metric("end_to_end", m["name"], ctx)
            if v is None:
                raise Failure(f"nothing measured for {m['name']}")
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in metrics_of(bench, "per_layer", cell["name"], reported):
            v = read_metric("layer_metrics", m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # the comparison: every number beside its limit
    cmp_ = fin.get("compare") or {}
    gaps = cmp_.get("gaps") or []
    rule = correct_rule(cell, conf)
    others = {k[:-5]: v for k, v in cmp_.items() if k.endswith("_gaps")}
    checks = [
        ("failed_requests", stats["failed"], 0),
        ("unreadable_texts", bad_text, 0),
        ("prompt_tokens_client_vs_server", count_gap["prompt_tokens"], 0),
        ("output_tokens_client_vs_server", count_gap["output_tokens"], 0),
        ("compared_tokens_short", max(0, int(ctx["mix"].get("sample_min_tokens", 1))
                                      - len(gaps)), 0),
        ("reference_finite", 0 if cmp_.get("finite") else 1, 0),
    ] + gapstats.checks(gaps, rule, others)
    correct = gapstats.passes(checks)
    compared = {name: {"value": v, "limit": lim} for name, v, lim in checks}
    extra = gapstats.summary(gaps)
    # --control: the reference in lower precision, put in the program's
    # place at the same positions, through the same rule
    stood_in = {}
    for who in sorted(others) if args.control else ():
        rows = gapstats.checks(others[who], rule, others)
        stood_in[who] = {
            "correct": gapstats.passes(rows),
            "compared": {n: {"value": v, "limit": lim} for n, v, lim in rows},
            "summary": gapstats.summary(others[who])}
        for name, v, lim in rows:
            print(f"{who} compared {name} = {v} (limit {lim})", file=sys.stderr)
        print(f"{who} correct = {stood_in[who]['correct']}", file=sys.stderr)

    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": fin["memory_peak_bytes"]}
    line = {"correct": bool(correct), "attempted": stats["attempted"],
            "failed": stats["failed"], "metrics": metrics, "device": dev}
    if args.trace and ctx["trace"]:
        tr = ctx["trace"]
        dev["busy_s"] = tr["busy_s"]
        dev["window_s"] = tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"][:10],
                             "idle_gaps": tr["idle_gaps"][:10]}
    line["info"] = {
        "workload": cell["name"], "seed": args.seed, "window_s": window_s,
        "requests_finished": len(stats["finished"]),
        "reference_seconds": fin.get("reference_seconds"),
        "trace_reduce_seconds": fin.get("reduce_seconds"),
        "generator_lateness_p99_ms": (
            1000.0 * loadgen.pct(ctx["lateness_s"], 0.99)
            if ctx["lateness_s"] else None),
        "compare_extra": extra,
        # programs the serving process asked the compiler for inside the
        # window (a traced run prints it as ``engine.compiles_in_window``):
        # an untraced run whose tail or rate reads far off shows here why
        "compiles_in_window": (
            ctx["edge1"]["stats"]["compile_cache"]["requests"]
            - ctx["edge0"]["stats"]["compile_cache"]["requests"]),
        # and the seconds the scheduler thread spent in each phase of its
        # tick over the window (``dllama_tick_phase_seconds_total``): a
        # stall of seconds sits in one of them
        "tick_phase_s": {
            labels: round(promtext.delta(ctx["edge0"], ctx["edge1"],
                                         TICK_PHASES, labels), 4)
            for name, labels, _ in ctx["edge1"]["prom"] if name == TICK_PHASES},
        # every request of the window, for whoever asks where a tail sits:
        # [output tokens, bursts, ms from send to first, ms first to last]
        "requests": [
            [sum(k for _, k in r.bursts), len(r.bursts),
             round((r.first - r.sent) * 1000.0, 2),
             round((r.last - r.first) * 1000.0, 2)]
            for r in stats["finished"]],
    }
    if args.trace and ctx["trace"]:
        # the programs the trace counted, heaviest first: what the readers'
        # ``decode_module`` / ``prefill_module`` patterns choose from
        line["info"]["traced_programs"] = sorted(
            ([n, m["launches"], m["seconds"], m["custom_call_s"]]
             for n, m in ctx["trace"].get("modules", {}).items()),
            key=lambda row: -row[2])[:8]
    if stood_in:
        line["info"]["in_the_programs_place"] = stood_in
        # every gap, for whoever sets a limit from the readings
        line["info"]["gaps"] = dict(
            {who: [round(g, 4) for g in v] for who, v in others.items()},
            program=[round(g, 4) for g in gaps])
        # and whose they are: [prompt tokens, served ids] of each sampled
        # request, in the gaps' order
        line["info"]["sample"] = [[len(x["prompt"]), x["served"]]
                                  for x in ctx["sample"]]
    line["compared"] = compared
    for name, v, lim in checks:
        print(f"compared {name} = {v} (limit {lim})", file=sys.stderr)
    print(f"correct = {correct}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # for the harness's own tests and the builder's measurements; the driver
    # passes none of these
    ap.add_argument("--benchmark", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at a tiny size (tests only)")
    ap.add_argument("--fault", default="",
                    help="break the timed path underneath: token | token1")
    ap.add_argument("--control", action="store_true",
                    help="also put the reference in lower precision (the "
                         "control) and in bfloat16 (the witness) in the "
                         "program's place, through the same rule")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "dllama_tpu")):
        print("no program beside the benchmark: nothing to measure",
              file=sys.stderr)
        return 3
    try:
        return run(args)
    except Failure as e:
        print(f"BENCHMARK FAILED: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    raise SystemExit(main())
