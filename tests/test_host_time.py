"""The host's time between programs, split by cause: the scheduler thread's
CPU seconds beside the wall seconds of every tick phase
(``dllama_tick_phase_cpu_seconds_total``), the collector's pauses by the
phase they stalled (``dllama_gc_pause_seconds_total``), and the benchmark's
two readers of them on recorded scrapes (loaded as ``run.py`` loads a
reader). Every timing assertion is an identity or rests on a sleep, so that
it holds on a loaded machine. The handlers' CPU
(``dllama_handler_cpu_seconds_total``) is in ``tests/test_api_server.py``,
beside the in-process server it needs.
"""

import gc
import importlib.util
import json
import os
import sys
import threading
import time

import pytest

from dllama_tpu import observability as ob

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
# the harness's own layout: ``benchmarks/`` and its readers on the path, as
# run.py has it
for _p in (BENCH, os.path.join(BENCH, "readers")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import promtext  # noqa: E402

WALL = "dllama_tick_phase_seconds_total"
CPU = "dllama_tick_phase_cpu_seconds_total"
GC = "dllama_gc_pause_seconds_total"


def series(family: str) -> dict:
    """{label values: value} of a default-registry family, now."""
    values = ob.default_registry().snapshot().get(family, {}).get("values", [])
    return {tuple(v["labels"].values()): v["value"] for v in values}


def delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if v - before.get(k, 0.0) > 0.0}


# ---------------------------------------------------------------------------
# thread CPU time beside wall time
# ---------------------------------------------------------------------------

def test_a_sleeping_phase_is_off_the_cpu():
    before_wall, before_cpu = series(WALL), series(CPU)
    with ob.tick():
        with ob.phase("t_sleeps", "engine") as p:
            time.sleep(0.05)
    key = ("t_sleeps", "engine", "host")
    wall = delta(series(WALL), before_wall)[key]
    cpu = delta(series(CPU), before_cpu).get(key, 0.0)
    assert wall == pytest.approx(p.seconds) and wall >= 0.05
    assert wall - cpu >= 0.04
    assert cpu < 0.01
    assert 0.0 <= p.c1 - p.c0 == pytest.approx(cpu, abs=1e-9)


def test_cpu_and_off_cpu_add_up_to_wall_for_every_key():
    before_wall, before_cpu = series(WALL), series(CPU)
    with ob.tick() as tk:
        with ob.phase("t_spins", "engine"):
            end = time.monotonic() + 0.02
            while time.monotonic() < end:
                pass
        with ob.phase("t_waits", "engine", "device"):
            time.sleep(0.01)
        with ob.phase("t_spins", "engine"):
            sum(range(1000))
        with ob.phase("t_both", "scheduler"):
            sum(range(1000))
            time.sleep(0.005)
    assert set(tk._cpu) == set(tk._seconds) == {
        ("t_spins", "engine", "host"), ("t_waits", "engine", "device"),
        ("t_both", "scheduler", "host")}
    wall = delta(series(WALL), before_wall)
    cpu = series(CPU)
    for key, seconds in tk._seconds.items():
        c = tk._cpu[key]
        # the counters hold what the tick gathered; CPU plus off-CPU is wall
        assert wall[key] == pytest.approx(seconds)
        assert cpu[key] - before_cpu.get(key, 0.0) == pytest.approx(c, abs=1e-9)
        # the CPU reads lie just outside the wall reads: CPU passes wall by
        # no more than the reads' own time (two clocks: a hair of room)
        assert 0.0 <= c <= seconds * 1.001 + 1e-5, key


# ---------------------------------------------------------------------------
# collector pauses
# ---------------------------------------------------------------------------

def test_a_collection_on_another_thread_lands_under_the_open_leaf():
    ob.watch_gc()
    key = ("2", "decode_wait")
    before = series(GC).get(key, 0.0)
    opened, release = threading.Event(), threading.Event()

    def scheduler():
        with ob.tick():
            with ob.phase("decode_wait", "engine", "device"):
                opened.set()
                release.wait(60)

    sched = threading.Thread(target=scheduler)
    sched.start()
    try:
        assert opened.wait(60)
        collector = threading.Thread(target=gc.collect)
        collector.start()
        collector.join(60)
        assert not collector.is_alive()
    finally:
        release.set()
        sched.join(60)
    assert not sched.is_alive()
    assert series(GC).get(key, 0.0) > before
    assert ob._during == "none"


class _CollectsWhenAdded(float):
    """An amount whose addition runs a full collection: inside
    ``Counter.inc``, under the registry's lock."""

    def __radd__(self, other):
        gc.collect()
        return other + float(self)


def test_a_collection_inside_a_counters_lock_neither_deadlocks_nor_loses():
    ob.watch_gc()
    labels = {"phase": "t_gc_inside", "layer": "engine", "side": "host"}
    before = ob._M_PHASE_SECONDS.value(**labels)
    pauses = sum(series(GC).values())

    worker = threading.Thread(
        target=lambda: ob._M_PHASE_SECONDS.inc(_CollectsWhenAdded(0.25),
                                               **labels))
    worker.start()
    worker.join(60)
    assert not worker.is_alive()
    assert ob._M_PHASE_SECONDS.value(**labels) == before + 0.25
    assert sum(series(GC).values()) > pauses
    # a tick's exit folds too, under the same lock it adds its phases with
    with ob.tick():
        with ob.phase("t_after", "engine"):
            gc.collect()
    assert ob._M_GC_PAUSE.value(generation="2", during="t_after") > 0.0


def test_the_gc_span_carries_its_generation_and_no_tick(monkeypatch):
    spans = []

    class Annotation:
        def __init__(self, name, **kwargs):
            spans.append((name, kwargs))

        @staticmethod
        def is_enabled():
            return True

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    ob.watch_gc()
    monkeypatch.setattr(ob, "_gc_trace", Annotation)
    with ob.tick():
        with ob.phase("t_traced", "engine"):
            gc.collect()
    monkeypatch.undo()
    assert ("gc", {"generation": 2}) in spans
    assert all("tick" not in kw for name, kw in spans if name == "gc")


# ---------------------------------------------------------------------------
# the benchmark's readers, on a recorded pair of scrapes
# ---------------------------------------------------------------------------

def read_metric(name: str, ctx: dict):
    """``run.read_metric`` for a per-layer metric: its file names a reader
    under ``benchmarks/readers/`` and the reader's arguments."""
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    path = os.path.join(BENCH, "readers", spec["reader"] + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "reader_" + spec["reader"], path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(ctx, spec.get("args", {}))


def scrape(ticks: float, wall: dict, cpu: dict, handler=None, pauses=None):
    text = [f"dllama_ticks_total {ticks}"]
    for family, values in ((WALL, wall), (CPU, cpu)):
        for (phase, layer, side), v in values.items():
            text.append(f'{family}{{phase="{phase}",layer="{layer}",'
                        f'side="{side}"}} {v}')
    if handler is not None:
        text.append(f"dllama_handler_cpu_seconds_total {handler}")
    for (generation, during), v in (pauses or {}).items():
        text.append(f'{GC}{{generation="{generation}",during="{during}"}} {v}')
    return {"prom": promtext.parse("\n".join(text))}


DISPATCH = ("decode_dispatch", "engine", "host")
FETCH = ("decode_fetch", "engine", "host")
WAIT = ("decode_wait", "engine", "device")
REAP = ("reap_admit", "scheduler", "host")


@pytest.fixture
def recorded():
    before = scrape(100, {DISPATCH: 1.0, FETCH: 2.0, WAIT: 10.0, REAP: 0.5},
                    {DISPATCH: 0.5, FETCH: 1.5, WAIT: 0.1, REAP: 0.4},
                    handler=3.0, pauses={("0", "none"): 0.01})
    after = scrape(300, {DISPATCH: 1.6, FETCH: 2.8, WAIT: 30.0, REAP: 0.9},
                   {DISPATCH: 0.8, FETCH: 2.0, WAIT: 0.3, REAP: 0.7},
                   handler=3.5, pauses={("0", "none"): 0.03,
                                        ("2", "decode_wait"): 0.02})
    return {"edge0": before, "edge1": after}


@pytest.mark.parametrize("name,expect", [
    # engine host: (0.6 + 0.8) wall - (0.3 + 0.5) CPU over 200 ticks
    ("engine.host_offcpu_ms_per_tick", 1000.0 * (1.4 - 0.8) / 200),
    ("scheduler.host_offcpu_ms_per_tick", 1000.0 * (0.4 - 0.3) / 200),
    ("front.handler_cpu_ms_per_tick", 1000.0 * 0.5 / 200),
    # every generation and every phase: 0.02 + 0.02
    ("host.gc_pause_ms_per_tick", 1000.0 * 0.04 / 200),
])
def test_a_reader_gives_its_number(recorded, name, expect):
    assert read_metric(name, recorded) == pytest.approx(expect)


@pytest.mark.parametrize("name", [
    "engine.host_offcpu_ms_per_tick", "scheduler.host_offcpu_ms_per_tick",
    "front.handler_cpu_ms_per_tick", "host.gc_pause_ms_per_tick"])
def test_nothing_to_read_without_the_family_or_where_ticks_rest(recorded, name):
    # a program that counts its phases' wall seconds and ticks, and nothing
    # of this split: the metric is left out, not read as 0
    parent = {"edge0": scrape(100, {DISPATCH: 1.0}, {}),
              "edge1": scrape(300, {DISPATCH: 1.6}, {})}
    assert read_metric(name, parent) is None
    still = dict(recorded, edge1=recorded["edge0"])
    assert read_metric(name, still) is None
