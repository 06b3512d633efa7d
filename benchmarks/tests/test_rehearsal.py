"""The first rehearsal, end to end on the CPU: a tiny configuration kept out
of BENCHMARK.json's workloads, interpret-mode kernels, a 3-second window. A
CPU run gives no device number: what is checked is the line's form, the
counts, and that ``correct`` is decided as it should be."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

TINY = os.path.join(BENCH, "tests", "tiny_benchmark.json")


def run(args, cwd=ROOT, script=os.path.join(BENCH, "run.py"), timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, script] + args, cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=timeout)
    return p


def last_line(p):
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def declared(group, workload, bench_path=TINY):
    with open(bench_path) as f:
        bench = json.load(f)
    e2e = {m["name"] for m in bench["end_to_end"]}
    return {m["name"]: m["unit"] for m in bench[group]
            if workload in m.get("workloads", [workload])
            and (group == "end_to_end" or m["moves"] in e2e)}


@pytest.mark.parametrize("workload", ["tiny.closed", "tiny-dense.closed",
                                      "tiny-tp4.closed"])
def test_a_run_prints_every_end_to_end_metric_and_is_correct(workload):
    p = run(["--benchmark", TINY, "--workload", workload, "--seed",
             str(2 ** 31 + 5), "--seconds", "3", "--trace", "0", "--rehearse"])
    line = last_line(p)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 10
    want = declared("end_to_end", workload)
    assert {k: v["unit"] for k, v in line["metrics"].items()} == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"  # named for what it is
    assert line["device"]["count"] == (4 if "tp4" in workload else 1)
    for name, c in line["compared"].items():
        assert c["value"] <= c["limit"], name
    tail = p.stderr.strip().splitlines()
    assert tail[-1] == "correct = True" and tail[-2].startswith("compared ")


def test_a_traced_run_prints_the_per_layer_metrics_it_can_read():
    p = run(["--benchmark", TINY, "--workload", "tiny.closed", "--seed", "8",
             "--seconds", "3", "--trace", "1", "--rehearse"])
    line = last_line(p)
    want = declared("per_layer", "tiny.closed")
    got = {k: v["unit"] for k, v in line["metrics"].items()}
    # the CPU has no device plane and no allocator statistics: the readers
    # of the trace and of the memory find nothing and return nothing
    for name in ("front.queue_wait_mean_ms", "scheduler.occupancy_mean_rows",
                 "scheduler.pooled_share_pct", "engine.decode_chunk_mean_ms",
                 "engine.compiles_in_window"):
        assert got[name] == want[name]
    assert all(want[k] == u for k, u in got.items())
    assert line["metrics"]["scheduler.pooled_share_pct"]["value"] > 50
    assert line["correct"] is True


def test_the_open_loop_times_from_the_due_time_and_reports_lateness():
    p = run(["--benchmark", TINY, "--workload", "tiny.open", "--seed", "9",
             "--seconds", "3", "--trace", "0", "--rehearse"])
    line = last_line(p)
    assert line["correct"] is True and line["attempted"] >= 3
    assert line["info"]["generator_lateness_p99_ms"] is not None


@pytest.mark.parametrize("workload,fault,fails", [
    ("tiny.closed", "token", "gaps_over_0.04_tokens"),
    ("tiny.closed", "token1", "gaps_over_0.04_tokens"),
    ("tiny-dense.closed", "token1", "widest_gap_spreads"),
])
def test_a_token_altered_where_it_is_produced_comes_out_not_correct(workload, fault, fails):
    """``token``: the first token of every chunk; ``token1``: only the first
    token a request is handed. Either fails the rule's few-tokens number."""
    p = run(["--benchmark", TINY, "--workload", workload, "--seed", "10",
             "--seconds", "3", "--trace", "0", "--rehearse", "--fault", fault])
    line = last_line(p)
    assert line["correct"] is False
    c = line["compared"][fails]
    assert c["value"] > c["limit"]
    assert p.stderr.strip().splitlines()[-1] == "correct = False"


def test_the_control_goes_through_the_same_rule_and_is_printed():
    p = run(["--benchmark", TINY, "--workload", "tiny-dense.closed", "--seed", "11",
             "--seconds", "3", "--trace", "0", "--rehearse", "--control"])
    line = last_line(p)
    stood_in = line["info"]["in_the_programs_place"]
    assert set(stood_in) == {"control", "witness"}
    assert set(stood_in["control"]["compared"]) == \
        {"gap_vs_control_ratio", "widest_gap_spreads"}
    assert stood_in["control"]["compared"]["gap_vs_control_ratio"]["value"] == 1.0
    assert stood_in["control"]["correct"] is False
    assert stood_in["witness"]["correct"] is True
    # every gap is printed with whose it is: [prompt tokens, served ids]
    gaps, sample = line["info"]["gaps"], line["info"]["sample"]
    assert set(gaps) == {"control", "witness", "program"}
    assert sum(len(ids) for _, ids in sample) == len(gaps["program"])
    err = p.stderr.strip().splitlines()
    assert any(l.startswith("control correct = ") for l in err)
    assert err[-1] == "correct = True"  # the program's own lines come last


def test_no_chip_means_no_result():
    p = run(["--benchmark", TINY, "--workload", "tiny.closed", "--seed", "1",
             "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_alone_in_a_directory_it_gives_no_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns(".scratch", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = run(["--workload", "mistral-7b.chat-closed8", "--seed", "1",
             "--seconds", "1", "--trace", "0"], cwd=str(tmp_path),
            script=str(tmp_path / "benchmarks" / "run.py"))
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_a_cell_a_mix_and_a_metric_are_new_files_and_one_entry_each(tmp_path):
    """A later PR's view: a copy of the benchmark, the program beside it, and
    only ADDED files: a configuration, a traffic mix, a per-layer metric
    (with a reader of its own) and the entries that name them."""
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns(".scratch", "__pycache__"))
    os.symlink(os.path.join(ROOT, "dllama_tpu"), tmp_path / "dllama_tpu")
    b = tmp_path / "benchmarks"
    with open(b / "configs" / "tiny-dense.json") as f:
        conf = json.load(f)
    conf.update(name="tiny-new", num_hidden_layers=3)
    (b / "configs" / "tiny-new.json").write_text(json.dumps(conf))
    with open(b / "traffic" / "tiny-closed.json") as f:
        mix = json.load(f)
    mix.update(name="tiny-two", callers=2)
    (b / "traffic" / "tiny-two.json").write_text(json.dumps(mix))
    (b / "readers" / "answered.py").write_text(
        "def read(ctx, args):\n    return float(len(ctx['client']['finished']))\n")
    metric = {"name": "front.requests_answered", "layer": "HTTP front and admission",
              "unit": "requests", "better": "higher", "source": "host_clock",
              "moves": "out_tokens_per_s", "workloads": ["tiny-new.two"]}
    (b / "layer_metrics" / "front.requests_answered.json").write_text(
        json.dumps({"reader": "answered", "args": {}}))
    # the new cell brings a rule of its own; the configuration's stays
    os.makedirs(b / "correct")
    (b / "correct" / "tiny-new.two.json").write_text(json.dumps({"correct": {
        "p95_gap": {"of": "quantile", "q": 0.95, "limit": 0.04, "unit": "spreads"}}}))
    with open(TINY) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-new", "source": "none", "reduced": [],
                             "file": "benchmarks/configs/tiny-new.json", "why": "new"})
    bench["workloads"].append({"name": "tiny-new.two", "config": "tiny-new",
                               "traffic": "tiny-two", "chips": 1, "why": "new"})
    bench["per_layer"].append(metric)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    # 4 s: in a fresh directory nothing is cached, and a request of the cold
    # run can outlast a 2 s window, which then holds none that was sent in it
    p = run(["--workload", "tiny-new.two", "--seed", "4", "--seconds", "4",
             "--trace", "1", "--rehearse"], cwd=str(tmp_path),
            script=str(b / "run.py"))
    line = last_line(p)
    assert line["correct"] is True
    assert line["metrics"]["front.requests_answered"]["value"] > 0
    assert "p95_gap_spreads" in line["compared"]  # the cell's own rule
    assert "widest_gap_spreads" not in line["compared"]
    assert "scheduler.occupancy_mean_rows" in line["metrics"]  # every cell's
