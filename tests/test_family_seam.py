"""The seam between the benchmark's harness and a model: a configuration's
``"family"`` (``benchmarks/families/<name>/``, found by ``families.load``).
Guarded here, in the suite the driver runs, so that a change to the program
or to a family that breaks the seam is seen before a chip run: every family
loads and brings every function of ``REQUIRED``, every configuration of
``BENCHMARK.json`` names a family that loads, and the weights of every one
fill over a quarter of a v5e's memory. No JAX: the counts are plain Python.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
# the harness's own layout: ``benchmarks/`` on the path, as run.py has it
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import families  # noqa: E402

V5E_BYTES = 16e9


def _benchmark_configs() -> list:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    out = []
    for entry in bench["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            out.append(pytest.param(json.load(f), id=entry["name"]))
    return out


@pytest.mark.parametrize("name", ["llama", "mimo_v2", "command_a"])
def test_a_family_loads_and_brings_every_required_function(name):
    mod = families.load({"name": "probe", "family": name})
    assert mod.__name__.endswith("families." + name)
    for fn in families.REQUIRED:
        assert callable(getattr(mod, fn)), fn


def test_a_configuration_without_a_family_is_refused_in_one_line():
    with pytest.raises(ValueError, match='needs a "family" key') as e:
        families.load({"name": "probe"})
    assert "\n" not in str(e.value)
    with pytest.raises(ValueError, match="cannot shard"):
        families.load({"name": "probe", "family": "mimo_v2", "tp": 4})


@pytest.mark.parametrize("conf", _benchmark_configs())
def test_every_benchmark_configuration_names_a_family_that_loads(conf):
    fam = families.load(conf)
    resident = fam.resident_bytes(conf)
    assert resident > 0.25 * V5E_BYTES, (conf["name"], resident)
    assert resident < V5E_BYTES
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    # the counts read the configuration's own keys and grow with the work
    assert fam.plane_bytes_per_launch(conf, 8) <= resident
    assert (fam.plane_bytes_per_launch(conf, 1)
            <= fam.plane_bytes_per_launch(conf, 8))
    assert 0 < fam.launch_least_seconds(conf, 1, peaks) < 0.1
    assert fam.flops_per_token(conf, 1024) > fam.flops_per_token(conf, 1)
    assert fam.kv_read_bytes(conf, 1024) > fam.kv_read_bytes(conf, 64) > 0
