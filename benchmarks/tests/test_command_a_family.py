"""The ``command_a`` family under the harness, on the CPU: its counts against
a hand count of ISSUE 31's plan and share, and the tiny preset
(``configs/tiny-command-a.json``, never a benchmark cell) through ``run.py
--rehearse``: the line's form, the two new per-layer metrics, and the planted
faults that come out not correct."""
import json
import os
import subprocess
import sys

import pytest

import families
from conftest import BENCH, ROOT

TINY = os.path.join(BENCH, "tests", "tiny_command_a_benchmark.json")
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.fixture(scope="module")
def conf():
    with open(os.path.join(BENCH, "configs",
                           "command-a-plus-d8-e16-q40.json")) as f:
        return json.load(f)


def test_the_cut_is_the_one_the_configuration_states(conf):
    from families.command_a import shapes

    assert shapes.kinds(conf) == {("window", "moe"): 6, ("full", "moe"): 2}
    assert shapes.plan(conf)[:4] == (("window", "moe"),) * 3 + (("full", "moe"),)
    d = shapes.dims(conf)
    assert (d["E"], d["Eh"], d["k"], d["Ns"], d["V"]) == (128, 16, 8, 4, 32768)
    assert (d["D"], d["heads"], d["kv"], d["hd"], d["He"], d["window"]) == (
        4096, 128, 8, 128, 4096, 4096)
    # attention 142.6 M, the shared experts 201.3 M, an expert 50.33 M
    assert shapes.attn_weights(conf) == 4096 * (16384 + 2 * 1024) + 16384 * 4096
    assert shapes.shared_weights(conf) == 3 * 4096 * 16384
    assert shapes.expert_weights(conf) == 3 * 4096 * 4096
    # 215 MB always-on and 31.5 MB an expert a layer, in q40's 0.625 B
    assert shapes.always_on_weights(conf) * 0.625 == pytest.approx(215e6, rel=1e-3)
    assert shapes.expert_weights(conf) * 0.625 == pytest.approx(31.46e6, rel=1e-3)
    layer = (142.6e6 + 201.3e6 + 16 * 50.33e6) * 0.625  # 0.719 GB
    assert layer == pytest.approx(0.719e9, rel=2e-3)
    resident = families.load(conf).resident_bytes(conf)
    # 8 layers, the head's planes, the float32 table, routers and norms
    assert resident == pytest.approx(
        8 * layer + 4096 * 32768 * 0.625 + 4 * 32768 * 4096
        + 4 * 8 * 4096 * 128, rel=2e-3)
    assert 6.3e9 < resident < 6.5e9
    for key, published in conf["published"].items():
        assert conf[key] < published and key in conf["reduced"]
    assert conf["published"]["vocab_size"] == 8 * conf["vocab_size"]


def test_least_work_is_live_positions_and_the_experts_the_rows_reach(conf):
    from families.command_a import shapes

    fam = families.load(conf)
    assert shapes.experts_needed(conf, 5.15) == pytest.approx(4.52, abs=0.01)
    assert shapes.experts_needed(conf, 1) == pytest.approx(1.0)
    assert shapes.experts_needed(conf, 10_000) == pytest.approx(16.0)
    # the counted reads, where a caller has them, take the expectation's place
    assert shapes.experts_needed(conf, 5.15, reads=3.7) == 3.7
    assert (fam.expert_least_seconds(conf, 5.15, PEAKS, reads=3.7)
            < fam.expert_least_seconds(conf, 5.15, PEAKS))
    # a step's plane bytes: 8 x (215 MB + 4.52 x 31.5 MB), the head, the
    # float32 routers: 2.96 GB, 3.6 ms at 819 GB/s; 58 % of it always-on
    step = fam.plane_bytes_per_launch(conf, 5.15)
    assert step == pytest.approx(
        8 * (215e6 + 4.52 * 31.46e6) + 83.9e6 + 8 * 4096 * 128 * 4, rel=2e-3)
    assert fam.launch_least_seconds(conf, 5.15, PEAKS) == pytest.approx(
        3.59e-3, rel=5e-3)
    assert 0.55 < 8 * 215e6 / step < 0.62
    # the shared planes alone: 201.3 M x 0.625 B a layer over the bandwidth
    assert fam.shared_least_seconds(conf, 8, PEAKS) == pytest.approx(
        8 * 201.3e6 * 0.625 / 819e9, rel=1e-3)
    assert (fam.shared_least_seconds(conf, 8, PEAKS)
            + fam.expert_least_seconds(conf, 8, PEAKS)
            < fam.launch_least_seconds(conf, 8, PEAKS))
    # 4 KB of KV a token a layer; a window layer reads min(context, 4096)
    assert fam.kv_read_bytes(conf, 1) == 8 * 4096
    short, long = fam.kv_read_bytes(conf, 4096), fam.kv_read_bytes(conf, 8192)
    assert long - short == 2 * (8192 - 4096) * 4096  # the two full layers
    assert fam.kv_read_bytes(conf, 300) == 8 * 300 * 4096
    resident = shapes.kv_resident_bytes(conf, 8, 1024, 8192)
    assert resident["window"] == 6 * 8 * 8192 * 4096  # 1.61 GB: 33.5 MB a ring
    assert resident["full"] == 2 * 8 * 1024 * 4096
    # attention's operations follow the same positions
    at = fam.flops_per_token(conf, 8192) - fam.flops_per_token(conf, 4096)
    assert at == 2 * (8192 - 4096) * 2.0 * 128 * 256


def _run(extra, seconds="12"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--benchmark", TINY,
         "--workload", "tiny-command-a.closed", "--seed", str(2 ** 31 + 5),
         "--seconds", seconds, "--rehearse"] + extra,
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_a_traced_rehearsal_prints_the_new_per_layer_metrics():
    line = _run(["--trace", "1"])
    assert line["correct"] is True and line["failed"] == 0
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # rows of 16-50 positions in a ring of 16 slots with a window of 8: past
    # the window every step sees 8 of the 16 slots it scores
    assert 25.0 < m["cache.ring_fill_pct"] <= 50.0
    assert "kernels.shared_q40_roofline.decode" not in m  # no device plane


@pytest.mark.parametrize("fault", ["token", "token1"])
def test_a_planted_fault_comes_out_not_correct(fault):
    line = _run(["--trace", "0", "--fault", fault])
    assert line["correct"] is False
    bad = [k for k, c in line["compared"].items() if c["value"] > c["limit"]]
    assert "widest_gap_spreads" in bad, line["compared"]
