"""The phase spans of the scheduler tick (``observability.phase`` / ``tick``).

First the primitive alone, then a tiny engine behind a ``Batcher`` serving a
handful of chunked-prefill requests, slab pool and paged pool as cases of
the same tests: which phases a tick writes, that they tile the loop's wall
time, what the two histograms beside them count, and that under a
``jax.profiler`` session (the benchmark launcher's options) the leaf spans
lie on the scheduler thread's line of ``/host:CPU``, inside the tick span
that carries their number.
"""

import builtins
import glob
import json
import math
import subprocess
import sys
import threading
import time

import jax
import pytest

from dllama_tpu import observability as ob
from dllama_tpu.models import llama
from dllama_tpu.runtime.generate import Engine
from dllama_tpu.runtime.sampler import SamplerConfig
from dllama_tpu.serving.api_server import ServerState
from tests.test_api_server import make_tokenizer
from tests.test_llama_forward import tiny_cfg

FAMILY = "dllama_tick_phase_seconds_total"
#: the leaf phases of a tick that prefills a piece and decodes a chunk, with
#: the (layer, side) each is counted under (PERF.md section 3 has the
#: boundaries); ``go_live`` only in the tick that ends a request's prefill
PHASES = {
    "reap_admit": ("scheduler", "host"),
    "prefill_dispatch": ("engine", "host"),
    "prefill_wait": ("engine", "device"),
    "prefill_land": ("engine", "host"),
    "go_live": ("engine", "host"),
    "publish": ("scheduler", "host"),
    "decode_prepare": ("engine", "host"),
    "decode_dispatch": ("engine", "host"),
    "decode_wait": ("engine", "device"),
    "decode_fetch": ("engine", "host"),
    "account": ("engine", "host"),
    "stream_out": ("scheduler", "host"),
    "arrivals": ("scheduler", "host"),
}
CHUNK = 4


def phase_seconds() -> dict:
    """{(phase, layer, side): seconds} of the default registry, now."""
    snap = ob.default_registry().snapshot()[FAMILY]["values"]
    return {(v["labels"]["phase"], v["labels"]["layer"], v["labels"]["side"]):
            v["value"] for v in snap}


def counter(name: str) -> float:
    values = ob.default_registry().snapshot()[name]["values"]
    return sum(v["value"] for v in values)


def prefill_tokens() -> dict:
    """{how: prompt tokens} of ``dllama_prefill_tokens_total``, now."""
    snap = ob.default_registry().snapshot()
    values = snap.get("dllama_prefill_tokens_total", {}).get("values", [])
    return {v["labels"]["how"]: v["value"] for v in values}


def hist(name: str) -> tuple:
    values = ob.default_registry().snapshot().get(name, {}).get("values", [])
    return (sum(v["count"] for v in values), sum(v["sum"] for v in values))


def delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if v - before.get(k, 0.0) > 0.0}


@pytest.fixture
def trace_file(tmp_path):
    path = tmp_path / "trace.json"
    ob.configure_trace(str(path))
    yield path
    ob.configure_trace(None)


def read_events(path) -> list:
    ob.configure_trace(None)  # closes (and flushes) the file
    out = []
    for line in path.read_text().splitlines():
        line = line.strip().rstrip(",")
        if line and line not in ("[", "]"):
            out.append(json.loads(line))
    return out


# ---------------------------------------------------------------------------
# the primitive alone
# ---------------------------------------------------------------------------

def test_leaf_seconds_land_under_their_labels_and_carry_the_tick():
    before, ticks = phase_seconds(), counter("dllama_ticks_total")
    with ob.tick() as tk:
        with ob.phase("t_host", "scheduler") as a:
            time.sleep(0.01)
        with ob.phase("t_dev", "engine", "device", span_id=7) as b:
            time.sleep(0.01)
        with ob.phase("t_host", "scheduler"):
            pass
    got = delta(phase_seconds(), before)
    assert set(got) == {("t_host", "scheduler", "host"),
                        ("t_dev", "engine", "device")}
    assert got[("t_dev", "engine", "device")] == pytest.approx(b.seconds)
    assert got[("t_host", "scheduler", "host")] >= a.seconds >= 0.01
    assert a._span_args() == {"tick": tk.seq}
    assert b._span_args() == {"span_id": 7, "tick": tk.seq}
    assert tk.t0 <= a.t0 <= a.t1 <= b.t0 <= b.t1 <= tk.t1
    # a device phase ran in it: the pass launched
    assert counter("dllama_ticks_total") == ticks + 1


def test_a_pass_without_a_device_phase_is_not_a_tick_that_launched():
    ticks = counter("dllama_ticks_total")
    first = ob.tick()
    with first:
        with ob.phase("t_idle", "scheduler"):
            pass
    with ob.tick() as second:
        pass
    assert counter("dllama_ticks_total") == ticks
    assert second.seq > first.seq  # every pass still takes a number


def test_an_exception_ends_the_span_and_propagates():
    before = phase_seconds()
    with pytest.raises(KeyError):
        with ob.tick():
            with ob.phase("t_raises", "engine"):
                time.sleep(0.002)
                raise KeyError("inside")
    got = delta(phase_seconds(), before)
    assert got[("t_raises", "engine", "host")] >= 0.002
    # the thread's tick is closed: a later leaf is nobody's
    with ob.phase("t_orphan", "engine"):
        pass
    assert ("t_orphan", "engine", "host") not in phase_seconds()


def test_outside_a_tick_and_without_a_layer_a_span_only_annotates():
    before = phase_seconds()
    with ob.phase("sse_write", span_id=3) as p:
        pass
    with ob.tick():
        with ob.phase("t_no_layer"):
            pass
    assert p.t1 >= p.t0 > 0.0
    assert delta(phase_seconds(), before) == {}


def test_nothing_is_written_and_no_file_opened_without_dllama_trace(
        monkeypatch):
    ob.configure_trace(None)
    opened = []
    real_open = builtins.open
    monkeypatch.setattr(builtins, "open",
                        lambda *a, **k: opened.append(a) or real_open(*a, **k))
    with ob.tick() as tk:
        with ob.phase("t_quiet", "engine"):
            pass
    assert opened == [] and tk._events is None
    assert ob.trace_path() is None


def test_chrome_events_on_the_scheduler_track_with_the_tick_number(trace_file):
    with ob.tick() as tk:
        with ob.phase("t_leaf", "engine", "device", span_id=11):
            time.sleep(0.002)
    events = read_events(trace_file)
    tick_ev = next(e for e in events if e["name"] == "tick")
    leaf = next(e for e in events if e["name"] == "t_leaf")
    for e in (tick_ev, leaf):
        assert (e["ph"], e["tid"], e["cat"]) == ("X", ob.SCHEDULER_TID,
                                                 "scheduler")
    assert tick_ev["args"] == {"tick": tk.seq}
    assert leaf["args"] == {"span_id": 11, "tick": tk.seq}
    assert tick_ev["ts"] <= leaf["ts"]
    assert leaf["ts"] + leaf["dur"] <= tick_ev["ts"] + tick_ev["dur"] + 2


def test_observability_is_importable_and_spans_work_without_jax():
    code = (
        "import sys\n"
        "class NoJax:\n"
        "    def find_spec(self, name, path=None, target=None):\n"
        "        if name == 'jax' or name.startswith('jax.'):\n"
        "            raise ImportError('no jax here')\n"
        "sys.meta_path.insert(0, NoJax())\n"
        "from dllama_tpu import observability as ob\n"
        "with ob.tick():\n"
        "    with ob.phase('p', 'engine', 'device'):\n"
        "        pass\n"
        "assert 'jax' not in sys.modules\n"
        "print(ob.default_registry().render().count('phase=\"p\"'))\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "1"


# ---------------------------------------------------------------------------
# a tiny engine behind a Batcher: slab pool and paged pool
# ---------------------------------------------------------------------------

class Pool:
    """A ServerState whose batcher serves chunked-prefill requests. The slab
    pool's ladder starts at 64 slots, so the prompts below share one pool:
    the first of a burst finds nothing decoding, the others ride."""

    def __init__(self, kv_pages: int):
        tok = make_tokenizer()
        cfg = tiny_cfg(vocab_size=tok.vocab_size, seq_len=256, dim=32,
                       kv_dim=16, head_size=8, hidden_dim=64)
        engine = Engine(cfg, llama.random_params(cfg, seed=13),
                        SamplerConfig(temperature=0.0, seed=1))
        self.state = ServerState(
            engine, tok, cfg, model_name="tiny", template="llama3",
            batch_window_ms=30.0, batch_max=4, batch_chunk=CHUNK,
            prefill_chunk=8, kv_bucket_min=64, kv_pages=kv_pages)

    def burst(self, n: int) -> dict:
        """n requests at once, prompts of 20, 27, 34, ... tokens, 10 output
        tokens each; -> {i: (tokens, RequestTrace)} in admission order."""
        out: dict = {}

        def one(i):
            trace = ob.RequestTrace(f"r{i}")
            toks = self.state.batcher.submit(
                [1] + [5 + i] * (19 + 7 * i), 10,
                SamplerConfig(temperature=0.0, seed=0), trace=trace)
            self.state.finish_request(trace)
            out[i] = (toks, trace)

        threads = [threading.Thread(target=one, args=(i,)) for i in range(n)]
        for t in threads:
            t.start()
            time.sleep(0.002)  # arrival order = index order
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
        assert len(out) == n
        # the scheduler closes its routed window (and writes that span)
        # just after the last waiter is resolved
        deadline = time.monotonic() + 30
        while self.state.batcher._window and time.monotonic() < deadline:
            time.sleep(0.005)
        assert not self.state.batcher._window
        return out


@pytest.fixture(scope="module", params=["slab", "paged"])
def pool(request):
    p = Pool(kv_pages=16 if request.param == "paged" else 0)
    p.burst(3)  # compile every program of the burst below
    return p


@pytest.fixture(scope="module")
def served(pool, tmp_path_factory):
    """One measured burst of five requests: the counters' deltas over it
    and the DLLAMA_TRACE events it wrote."""
    path = tmp_path_factory.mktemp("trace") / "trace.json"
    before = {"phases": phase_seconds(),
              "ticks": counter("dllama_ticks_total"),
              "chunk": hist("dllama_decode_chunk_ms"),
              "live": hist("dllama_decode_live_rows"),
              "turn": hist("dllama_prefill_turn_wait_ms"),
              "tokens": prefill_tokens()}
    ob.configure_trace(str(path))
    try:
        results = pool.burst(5)
    finally:
        events = read_events(path)
    sub = lambda a, b: tuple(x - y for x, y in zip(a, b))  # noqa: E731
    return {
        "results": results, "events": events,
        "phases": delta(phase_seconds(), before["phases"]),
        "ticks": counter("dllama_ticks_total") - before["ticks"],
        "chunk": sub(hist("dllama_decode_chunk_ms"), before["chunk"]),
        "live": sub(hist("dllama_decode_live_rows"), before["live"]),
        "turn": sub(hist("dllama_prefill_turn_wait_ms"), before["turn"]),
        "tokens": delta(prefill_tokens(), before["tokens"]),
        "rides": pool.state.batcher.kv_pages == 0,
    }


def scheduler_events(served, name=None):
    return [e for e in served["events"]
            if e.get("cat") == "scheduler" and e.get("tid") == 0
            and (name is None or e["name"] == name)]


def test_every_phase_of_the_table_is_observed_and_no_other(served):
    assert set(served["phases"]) == {(n, l, s) for n, (l, s) in PHASES.items()}
    names = {e["name"] for e in scheduler_events(served)}
    assert names == set(PHASES) | {"tick", "scheduler_window"}


def test_the_phases_tile_the_loops_wall_time(served):
    ticks = sorted(scheduler_events(served, "tick"), key=lambda e: e["ts"])
    leaves = [e for e in scheduler_events(served)
              if e["name"] in PHASES]
    in_ticks = sum(e["dur"] for e in ticks)
    in_leaves = sum(e["dur"] for e in leaves)
    assert in_leaves == pytest.approx(in_ticks, rel=0.02)
    # and the counter family holds the same seconds as the events
    assert sum(served["phases"].values()) * 1e6 == pytest.approx(
        in_leaves, rel=0.02)
    # the passes follow each other without a hole: the loop's wall time,
    # window by window, is its ticks
    loop = 0.0
    for w in scheduler_events(served, "scheduler_window"):
        mine = [t for t in ticks
                if w["ts"] <= t["ts"] <= w["ts"] + w["dur"]]
        loop += mine[-1]["ts"] + mine[-1]["dur"] - mine[0]["ts"]
    assert in_ticks == pytest.approx(loop, rel=0.02)
    # every leaf lies inside the tick whose number it carries
    by_seq = {t["args"]["tick"]: t for t in ticks}
    for e in leaves:
        t = by_seq[e["args"]["tick"]]
        assert t["ts"] <= e["ts"] and (e["ts"] + e["dur"]
                                       <= t["ts"] + t["dur"] + 2)


def test_ticks_total_counts_the_passes_that_launched(served):
    launched = {e["args"]["tick"] for e in scheduler_events(served)
                if PHASES.get(e["name"], ("", ""))[1] == "device"}
    assert served["ticks"] == len(launched) > 0


def test_live_rows_is_observed_at_every_launch_and_sums_the_rows_that_emitted(
        served):
    n_launches, _ = served["chunk"]
    n_live, rows = served["live"]
    assert n_live == n_launches == len(scheduler_events(served, "decode_wait"))
    # a live row nets a burst from every launch it is in: a request that
    # got n tokens was live in ceil(n / chunk) launches
    expect = sum(math.ceil(len(toks) / CHUNK)
                 for toks, _ in served["results"].values())
    assert rows == expect


def test_turn_wait_counts_every_request_and_grows_behind_others_pieces(served):
    n, _ = served["turn"]
    assert n == len(served["results"])
    waits = [served["results"][i][1].prefill_turn_wait_ms
             for i in sorted(served["results"])]
    queue = [served["results"][i][1].queue_wait_ms
             for i in sorted(served["results"])]
    # the first admitted prefills at once; the last waits for every piece
    # of the four before it, which its queue wait does not show
    assert waits[-1] > waits[0] and waits[-1] > queue[-1] - queue[0] + 1
    # the prefill phases carry the request's track. Where the prompt rides
    # (the slab pool) only the first request finds nothing decoding and
    # takes standalone pieces; the four behind it have no prefill phase
    spans = {e["args"]["span_id"]
             for e in scheduler_events(served, "prefill_wait")}
    staged = (list(served["results"].values())[:1] if served["rides"]
              else served["results"].values())
    assert spans == {tr.span_id for _, tr in staged}


def test_a_riding_tick_has_no_prefill_phase_and_marks_its_riders(served):
    """The slab pool: four of the burst's five prompts ride decode launches.
    A launch that carried one is its prefill chunk on the request's trace
    (``mark_prefill_chunk``: the launch's dispatch start to its fetch end,
    so the turn wait ends where the first tokens reach the device); the
    tick that ends a prompt goes live after ``account``, and no tick of a
    rider has a prefill phase (the leaves still tile every tick: the test
    above). The paged pool never rides: each chunk mark is a piece."""
    by_tick: dict = {}
    for e in scheduler_events(served):
        if e["name"] in PHASES:
            by_tick.setdefault(e["args"]["tick"], []).append(e)
    launches = {e["ts"] for e in scheduler_events(served, "decode_dispatch")}
    pieces = {e["ts"] for e in scheduler_events(served, "prefill_dispatch")}
    prompts = {i: 19 + 7 * i for i in served["results"]}  # prefix tokens
    for i, (_, trace) in served["results"].items():
        marks = {ob._mono_to_us(t0) for t0, _ in trace.prefill_chunks}
        assert marks and trace.prefill_ms > 0.0
        rode = served["rides"] and i > 0
        assert marks <= (launches if rode else pieces), i
        if served["rides"]:
            # 8 tokens a tick either way, so a mark a tick (the paged pool
            # aliases the prefix that the warm burst published)
            assert len(marks) >= math.ceil(prompts[i] / 8)
    went_live = [t for t, evs in by_tick.items()
                 if any(e["name"] == "go_live" for e in evs)]
    assert len(went_live) == len(served["results"])
    riding = [t for t in went_live
              if not any(e["name"] == "prefill_land" for e in by_tick[t])]
    assert len(riding) == (4 if served["rides"] else 0)
    for t in riding:
        names = [e["name"] for e in sorted(by_tick[t], key=lambda e: e["ts"])]
        assert not any(n.startswith("prefill_") for n in names)
        assert names.index("account") < names.index("go_live") \
            < names.index("stream_out")
    if served["rides"]:
        assert served["tokens"] == {
            "piece": prompts[0], "ride": sum(prompts.values()) - prompts[0]}
    else:
        assert set(served["tokens"]) == {"piece"}


def test_decode_chunk_ms_is_its_dispatch_wait_and_fetch(served):
    n, chunk_ms = served["chunk"]
    parts = sum(v for (name, _, _), v in served["phases"].items()
                if name in ("decode_dispatch", "decode_wait", "decode_fetch"))
    # the same clock reads: what differs is the two hand-overs between the
    # three spans of a launch, microseconds each on an idle machine
    assert 0.0 <= chunk_ms - 1000.0 * parts <= 0.2 * n + 0.01 * chunk_ms


def test_a_request_admitted_without_chunked_prefill_waits_no_turn():
    trace = ob.RequestTrace("solo")
    assert trace.prefill_turn_wait_ms is None  # never admitted
    trace.mark_start("solo")
    assert trace.prefill_turn_wait_ms == 0.0
    trace.mark_prefill_chunk(trace.t_start + 0.25, trace.t_start + 0.3)
    assert trace.prefill_turn_wait_ms == pytest.approx(250.0)


def test_profiler_host_plane_holds_the_leaf_spans_inside_their_tick(
        pool, tmp_path):
    """The launcher's options as they are (benchmarks/launcher.py)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        pool.burst(3)
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(files[-1])
    host = next(p for p in data.planes if p.name == "/host:CPU")
    # the scheduler thread's line: the one that holds the tick spans
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
              for e in ln.events] for ln in host.lines]
    mine = [evs for evs in lines if any(n == "tick" for n, *_ in evs)]
    assert len(mine) == 1
    ticks = {st["tick"]: (a, b) for n, a, b, st in mine[0] if n == "tick"}
    leaves = [(n, a, b, st) for n, a, b, st in mine[0] if n in PHASES]
    assert {n for n, *_ in leaves} == set(PHASES)
    for n, a, b, st in leaves:
        t0, t1 = ticks[st["tick"]]
        assert t0 <= a and b <= t1, (n, st)
    assert any(n == "scheduler_window" for n, *_ in mine[0])
    waits = [st for n, _, _, st in leaves if n == "prefill_wait"]
    assert all(st["span_id"] > 0 for st in waits)
