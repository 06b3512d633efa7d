"""The ``mimo_v2`` family under the harness, on the CPU: its counts against
the arithmetic of ISSUE 27, and the tiny preset (``configs/tiny-mimo.json``,
never a benchmark cell) through ``run.py --rehearse``: the line's form, the
new per-layer metrics, and a planted fault that comes out not correct."""
import json
import os
import subprocess
import sys

import pytest

import families
from conftest import BENCH, ROOT

TINY = os.path.join(BENCH, "tests", "tiny_mimo_benchmark.json")
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.fixture(scope="module")
def conf():
    with open(os.path.join(BENCH, "configs",
                           "mimo-v2-flash-d13-e32-q40.json")) as f:
        return json.load(f)


def test_the_cut_is_the_one_the_configuration_states(conf):
    from families.mimo_v2 import shapes

    assert shapes.kinds(conf) == {("full", "dense"): 1, ("window", "moe"): 10,
                                  ("full", "moe"): 2}
    d = shapes.dims(conf)
    assert (d["E"], d["Eh"], d["k"], d["rd"], d["V"]) == (256, 32, 8, 64, 19072)
    assert shapes.expert_weights(conf) == 3 * 4096 * 2048
    assert shapes.attn_weights(conf, "window") == 94_371_840  # 94.4 M
    assert shapes.attn_weights(conf, "full") == 89_128_960  # 89.1 M
    assert abs(families.load(conf).resident_bytes(conf) - 7.33e9) < 0.02e9
    for key, published in conf["published"].items():
        assert conf[key] < published and key in conf["reduced"]


def test_least_work_is_of_the_experts_the_rows_reach(conf):
    from families.mimo_v2 import shapes

    fam = families.load(conf)
    assert abs(shapes.experts_needed(conf, 8) - 7.18) < 0.01  # of 32 held
    assert shapes.experts_needed(conf, 1) == pytest.approx(1.0)
    assert shapes.experts_needed(conf, 10_000) == pytest.approx(32.0)
    every = 12 * 32 * shapes.expert_weights(conf) * 0.625
    assert fam.plane_bytes_per_launch(conf, 8) < 0.4 * every
    assert (fam.expert_least_seconds(conf, 8, PEAKS)
            < fam.launch_least_seconds(conf, 8, PEAKS))
    # window layers read the window, full layers the context
    short, long = fam.kv_read_bytes(conf, 128), fam.kv_read_bytes(conf, 8192)
    full = 3 * 4 * (192 + 128) * 2
    assert long - short == (8192 - 128) * full
    resident = shapes.kv_resident_bytes(conf, 8, 1024, 256)
    assert resident["window"] == 10 * 8 * 256 * 8 * 320 * 2  # 105 MB


def _run(extra, seconds="12"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--benchmark", TINY,
         "--workload", "tiny-mimo.closed", "--seed", str(2 ** 31 + 5),
         "--seconds", seconds, "--rehearse"] + extra,
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_a_traced_rehearsal_prints_the_new_per_layer_metrics():
    line = _run(["--trace", "1"])
    assert line["correct"] is True and line["failed"] == 0
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # a quarter of the experts held: a quarter of the picks, on average
    assert 15.0 < m["moe.held_pick_share_pct"] < 35.0
    assert 0.0 < m["moe.active_held_experts_mean"] <= 4.0
    assert 0.0 < m["cache.window_share_pct"] < 100.0
    assert "kernels.expert_q40_roofline.decode" not in m  # no device plane


def test_a_token_altered_after_the_hand_off_comes_out_not_correct():
    line = _run(["--trace", "0", "--fault", "token1"])
    assert line["correct"] is False
    bad = [k for k, c in line["compared"].items() if c["value"] > c["limit"]]
    # the widest gap catches the made-up token; the share of the control's
    # loss cannot be read where the toy's control loses nothing
    assert "widest_gap_spreads" in bad, line["compared"]
