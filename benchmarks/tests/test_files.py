"""Every data file loads and keeps to what the driver accepts."""
import glob
import json
import math
import os
import re

import pytest

import families
from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(path):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    return load(os.path.join(ROOT, "BENCHMARK.json"))


def line_ok(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_benchmark_json_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks"]
    assert 1 <= bench["run_seconds"] <= 51 and isinstance(bench["run_seconds"], int)
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["moves"] in e2e and m["source"] in SOURCES
        assert line_ok(m["layer"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    cells = bench["workloads"]
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and line_ok(w["why"])
    used = {w["config"] for w in cells}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used and NAME.match(c["name"])
        assert c["file"].startswith("benchmarks/") and line_ok(c["why"])
        assert line_ok(c["source"]) and len(c["reduced"]) <= 16
        conf = load(os.path.join(ROOT, c["file"]))
        assert conf["reduced"] == c["reduced"] and conf["source"] == c["source"]
        for key in c["reduced"]:
            assert not re.search(r"(_dim|_rank|_size)$|per_tok", key), key


def test_cells_find_their_files(bench):
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    for group, folder in (("end_to_end", "end_to_end"), ("per_layer", "layer_metrics")):
        for m in bench[group]:
            spec = load(os.path.join(BENCH, folder, m["name"] + ".json"))
            # what a metric is, is said once, in BENCHMARK.json
            assert set(spec) <= {"reader", "args", "what"}
            assert os.path.exists(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    # no metric names its cells: every cell that reports what a metric moves
    # reports the metric, the cells of later PRs too
    assert not any("workloads" in m for m in bench["end_to_end"] + bench["per_layer"])


RULE_KEYS = {"quantile": {"q"}, "max": set(), "count_over": {"over"},
             "share_of": {"against", "cap"}}


def rule_ok(rule):
    assert rule
    for name, spec in rule.items():
        assert spec["of"] in RULE_KEYS and RULE_KEYS[spec["of"]] <= set(spec), name
        assert spec["limit"] >= 0 and UNIT.match(spec["unit"])
        assert set(spec) <= RULE_KEYS[spec["of"]] | {"of", "limit", "unit", "why", "where_zero"}
    return True


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(BENCH, "correct", "*.json"))))
def test_a_cells_own_rule(path, bench):
    assert os.path.basename(path)[:-5] in {w["name"] for w in bench["workloads"]}
    assert rule_ok(load(path)["correct"])


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(BENCH, "configs", "*.json"))))
def test_config_file(path):
    conf = load(path)
    assert NAME.match(conf["name"])
    assert os.path.basename(path) == conf["name"] + ".json"
    assert NAME.match(conf["family"])  # the model's own keys are its family's
    assert line_ok(conf["source"])
    assert isinstance(conf["reduced"], list) and isinstance(conf["assumed"], dict)
    for key in ("session_cache", "batch_window_ms", "batch_max", "batch_chunk"):
        assert key in conf["server"]
    assert rule_ok(conf["correct"])
    assert conf["chips"] in (1, 4) and conf["weights"] == "q40"


def test_every_accepted_configuration_is_among_the_files(bench):
    files = {os.path.relpath(p, ROOT) for p in glob.glob(os.path.join(BENCH, "configs", "*.json"))}
    assert {c["file"] for c in bench["configs"]} <= files


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(BENCH, "configs", "*.json"))))
def test_a_configurations_family_loads_and_counts(path):
    """Every configuration of BENCHMARK.json and every tiny one: its family
    loads with all of its functions, and each count is finite and positive
    at the rows and contexts a reader may hand it."""
    conf = load(path)
    fam = families.load(conf)
    assert all(callable(getattr(fam, f)) for f in families.REQUIRED)
    if conf["tp"] > 1:
        assert all(callable(getattr(fam, f)) for f in families.SHARDING)
    peaks = load(os.path.join(BENCH, "peaks.json"))["device_kinds"]["TPU v5 lite"]
    counts = [fam.resident_bytes(conf)]
    for rows in (1, 8):
        counts += [fam.plane_bytes_per_launch(conf, rows),
                   fam.launch_least_seconds(conf, rows, peaks)]
    for context in (1, 1024):
        counts += [fam.flops_per_token(conf, context), fam.kv_read_bytes(conf, context)]
    assert all(math.isfinite(v) and v > 0 for v in counts), counts
    reads = [fam.kv_read_bytes(conf, c) for c in (0, 1, 2, 127, 128, 129, 1024, 4096, 65536)]
    assert reads == sorted(reads)  # never falls as the context grows
    assert fam.plane_bytes_per_launch(conf, 8) >= fam.plane_bytes_per_launch(conf, 1)
    assert fam.flops_per_token(conf, 1024) > fam.flops_per_token(conf, 1)


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(BENCH, "traffic", "*.json"))))
def test_traffic_file(path):
    mix = load(path)
    assert NAME.match(mix["name"]) and os.path.basename(path) == mix["name"] + ".json"
    assert mix["loop"] in ("closed", "open")
    assert ("callers" in mix) if mix["loop"] == "closed" else ("arrivals" in mix)
    for key in ("prompt_tokens", "max_tokens"):
        assert mix[key]["dist"] in ("loguniform", "uniform", "fixed")
    assert mix["warm"] and all(len(pair) == 2 for ph in mix["warm"] for pair in ph)
    assert mix["why"]


@pytest.mark.parametrize("path", sorted(
    glob.glob(os.path.join(BENCH, "layer_metrics", "*.json"))
    + glob.glob(os.path.join(BENCH, "end_to_end", "*.json"))))
def test_metric_file(path):
    spec = load(path)
    assert NAME.match(os.path.basename(path)[:-5])
    assert os.path.exists(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    assert isinstance(spec.get("args", {}), dict)


def test_shares_of_a_peak_are_named_and_measured_as_such(bench):
    for m in bench["per_layer"]:
        if re.search(r"roofline|(^|[._])mfu([._]|$)", m["name"]):
            assert m["unit"] == "%" and m["source"] == "device_trace"
            spec = load(os.path.join(BENCH, "layer_metrics", m["name"] + ".json"))
            # launches and seconds come from the trace's own programs
            assert {"decode_module", "prefill_module"} <= set(spec["args"])


def test_peaks_table():
    peaks = load(os.path.join(BENCH, "peaks.json"))
    assert "TPU v5 lite" in peaks["device_kinds"] and peaks["source"]
    v5e = peaks["device_kinds"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
