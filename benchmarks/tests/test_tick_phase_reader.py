"""The tick-phase reader on two made-up scrapes, and each new metric's file
through it."""
import json
import os

import pytest

import promtext
import tick_phase
from conftest import BENCH

FAMILY = "dllama_tick_phase_seconds_total"


def scrape(ticks, chunks, seconds):
    text = [f"dllama_ticks_total {ticks}",
            f"dllama_decode_chunk_ms_count {chunks}"]
    for (phase, layer, side), v in seconds.items():
        text.append(f'{FAMILY}{{phase="{phase}",layer="{layer}",side="{side}"}} {v}')
    return {"prom": promtext.parse("\n".join(text))}


@pytest.fixture
def ctx():
    before = {("decode_wait", "engine", "device"): 10.0,
              ("decode_fetch", "engine", "host"): 1.0,
              ("prefill_wait", "engine", "device"): 2.0,
              ("reap_admit", "scheduler", "host"): 0.5,
              ("stream_out", "scheduler", "host"): 0.25}
    after = {("decode_wait", "engine", "device"): 23.0,
             ("decode_fetch", "engine", "host"): 1.5,
             ("prefill_wait", "engine", "device"): 4.0,
             ("reap_admit", "scheduler", "host"): 0.75,
             ("stream_out", "scheduler", "host"): 0.5}
    return {"edge0": scrape(100, 90, before), "edge1": scrape(200, 180, after)}


def metric(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "tick_phase"
    return spec["args"]


@pytest.mark.parametrize("name,expect", [
    ("scheduler.tick_mean_ms", 1000.0 * 16.0 / 100),
    ("scheduler.host_ms_per_tick", 1000.0 * 0.5 / 100),
    ("engine.host_ms_per_tick", 1000.0 * 0.5 / 100),
    ("engine.decode_wait_mean_ms", 1000.0 * 13.0 / 90),
    ("engine.prefill_wait_ms_per_tick", 1000.0 * 2.0 / 100),
])
def test_metric_reads_its_phases_over_its_counter(ctx, name, expect):
    assert tick_phase.read(ctx, metric(name)) == pytest.approx(expect)


def test_nothing_to_read_where_the_counter_rests_or_the_family_is_missing(ctx):
    args = metric("scheduler.tick_mean_ms")
    still = dict(ctx, edge1=ctx["edge0"])
    assert tick_phase.read(still, args) is None
    # the parent commit: chunks are counted, the phases are not
    parent = {"edge0": scrape(0, 90, {}), "edge1": scrape(0, 180, {})}
    for name in ("scheduler.tick_mean_ms", "engine.decode_wait_mean_ms"):
        assert tick_phase.read(parent, metric(name)) is None
