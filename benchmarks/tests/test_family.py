"""The family seam: ``families.load`` is the one way to a model.

The ``llama`` family makes the planes it made before the seam, bit for bit
(digests recorded at the parent commit). A second, throwaway family
(``toy_family/``: its own key names, init program, numpy reference and
counts) is added to a scratch copy of the benchmark as NEW FILES AND ENTRIES
ONLY, as a later PR adds an architecture, and rehearsed through ``run.py``."""
import filecmp
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

import families
from conftest import BENCH, ROOT

TOY = os.path.join(BENCH, "tests", "toy_family")
TINY = os.path.join(BENCH, "tests", "tiny_benchmark.json")


def conf(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def digest(tree) -> str:
    import jax
    import numpy as np

    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves, key=lambda kv: jax.tree_util.keystr(kv[0])):
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


with open(os.path.join(BENCH, "tests", "data", "plane_digests.json")) as f:
    DIGESTS = json.load(f)


@pytest.mark.parametrize("name,seed", [
    (n, s) for n in ("tiny-rehearsal", "tiny-dense", "tiny-tp4") for s in DIGESTS[n]])
def test_the_planes_are_those_of_before_the_seam_bit_for_bit(name, seed):
    c = conf(name)
    assert digest(families.load(c).make_planes(c, int(seed))) == DIGESTS[name][seed]


def test_the_sharded_planes_are_those_of_before_the_seam():
    """Four CPU devices need a process of their own."""
    code = (
        "import json, sys\n"
        f"sys.path[:0] = [{ROOT!r}, {BENCH!r}, {os.path.dirname(__file__)!r}]\n"
        "import families, test_family\n"
        "c = test_family.conf('tiny-tp4')\n"
        "fam = families.load(c)\n"
        "params, _ = fam.make_sharded_params(c, fam.model_config(c, c['server']), 4, 5)\n"
        "print(test_family.digest(fam.planes_of(params, c)))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.split()[-1] == DIGESTS["tiny-tp4.sharded"]["5"]


def test_loading_a_family_imports_no_jax():
    """``run.py`` loads the family for its readers and must stay off the chip."""
    code = (f"import sys; sys.path.insert(0, {BENCH!r}); import families\n"
            "families.load({'name': 'x', 'family': 'llama'})\n"
            "assert 'jax' not in sys.modules and 'numpy' not in sys.modules\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


def test_the_loader_says_what_is_missing_in_one_line():
    with pytest.raises(ValueError, match='needs a "family" key') as e:
        families.load({"name": "no-family", "hidden_size": 64})
    assert "\n" not in str(e.value) and "no-family" in str(e.value)
    with pytest.raises(ValueError, match='needs a "family" key'):
        families.load({"name": "x", "family": "not-there"})
    with pytest.raises(ValueError, match='needs a "family" key'):
        families.load({"name": "x", "family": "../tests"})


# ---------------------------------------------------------------------------
# a later PR's view: the toy family, added as new files and entries only
# ---------------------------------------------------------------------------

def run(tmp, args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(tmp, "benchmarks", "run.py")] + args,
        cwd=tmp, env=env, capture_output=True, text=True, timeout=timeout)


def last_line(p):
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def later_pr(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("later_pr"))
    b = os.path.join(tmp, "benchmarks")
    shutil.copytree(BENCH, b, ignore=shutil.ignore_patterns(".scratch", "__pycache__"))
    os.symlink(os.path.join(ROOT, "dllama_tpu"), os.path.join(tmp, "dllama_tpu"))
    shutil.copytree(os.path.join(TOY, "toy"), os.path.join(b, "families", "toy"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(TOY, "toy-dense.json"), os.path.join(b, "configs"))
    shutil.copy(os.path.join(TOY, "kernels.wcls_roofline.decode.json"),
                os.path.join(b, "layer_metrics"))
    with open(TINY) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy-dense", "source": "none", "reduced": [],
                             "file": "benchmarks/configs/toy-dense.json", "why": "new"})
    bench["workloads"].append({"name": "toy-dense.closed", "config": "toy-dense",
                               "traffic": "tiny-closed", "chips": 1, "why": "new"})
    bench["per_layer"].append({
        "name": "kernels.wcls_roofline.decode", "layer": "kernels", "unit": "%",
        "better": "higher", "source": "device_trace", "moves": "out_tokens_per_s",
        "workloads": ["toy-dense.closed"]})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp


def test_the_toy_family_is_new_files_only(later_pr):
    """Every file the benchmark has is in the copy as it is: what the toy's
    cell needed was added beside them."""
    def differing(cmp):
        yield from (os.path.join(cmp.left, n) for n in cmp.diff_files + cmp.left_only
                    if n not in ("__pycache__", ".scratch"))
        for sub in cmp.subdirs.values():
            yield from differing(sub)

    cmp = filecmp.dircmp(BENCH, os.path.join(later_pr, "benchmarks"),
                         ignore=["__pycache__", ".scratch"])
    assert list(differing(cmp)) == []


def test_the_toy_familys_cell_rehearses_correct(later_pr):
    line = last_line(run(later_pr, ["--workload", "toy-dense.closed", "--seed",
                                    str(2 ** 31 + 6), "--seconds", "3",
                                    "--trace", "0", "--rehearse"]))
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 10
    assert set(line["compared"]) >= {"widest_gap_spreads", "reference_finite"}
    assert line["info"]["compare_extra"]["tokens"] > 50


def test_the_toy_familys_traced_rehearsal_prints_per_layer_metrics(later_pr):
    """The CPU has no device plane, so the readers of the trace, the toy's
    roofline metric among them, are loaded, find nothing and are left out;
    what they read from a device trace is the next test's."""
    line = last_line(run(later_pr, ["--workload", "toy-dense.closed", "--seed", "7",
                                    "--seconds", "3", "--trace", "1", "--rehearse"]))
    assert line["correct"] is True
    assert line["metrics"]["scheduler.pooled_share_pct"]["value"] > 50
    assert "engine.decode_chunk_mean_ms" in line["metrics"]
    assert "kernels.wcls_roofline.decode" not in line["metrics"]


def test_the_readers_divide_by_the_toy_familys_own_counts(later_pr):
    """``run.read_metric`` over the recorded v5e trace, with the toy's
    configuration: ``bytes_share`` reads ITS ``kv_read_bytes`` (float32
    keys and values), and its roofline metric file names the calls
    (``names``) and the family's function (``least``) it divides."""
    code = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/readers"]
import families, loadgen, run, trace_reduce
with open(sys.argv[1] + "/configs/toy-dense.json") as f:
    conf = json.load(f)
with open(sys.argv[1] + "/tests/data/v5e_thin_trace.json") as f:
    trace = trace_reduce.reduce(json.load(f))
res = loadgen.Result(loadgen.Request(0, "ab", 129, 16))
res.status, res.done, res.bursts = 200, True, [(10.2, 8), (10.6, 8)]
edge = lambda t, n: {"t": t, "prom": [("dllama_decode_chunk_ms_count", "", n)]}
ctx = {"trace": trace, "trace_edges": (edge(10.0, 3), edge(11.0, 5)),
       "results": [res], "model": conf, "family": families.load(conf),
       "chips": 1, "server": conf["server"],
       "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
print(json.dumps({n: run.read_metric("layer_metrics", n, ctx) for n in (
    "model_step.hbm_share_pct.decode", "kernels.wcls_roofline.decode",
    "kernels.q40_matmul_roofline.decode")}))
"""
    b = os.path.join(later_pr, "benchmarks")
    p = subprocess.run([sys.executable, "-c", code, b], capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    # by hand: 27 decode launches of 4 steps at 1 row (16 tokens over 2 x 4
    # counted steps: 2 rows) and 27 prefill pieces of 128 / 8 = 16 tokens
    with open(os.path.join(TOY, "toy-dense.json")) as f:
        c = json.load(f)
    sys.path.insert(0, TOY)
    try:
        import toy
    finally:
        sys.path.remove(TOY)
    with open(os.path.join(BENCH, "tests", "data", "v5e_thin_trace.json")) as f:
        import trace_reduce
        tr = trace_reduce.reduce(json.load(f))
    steps, pieces, rows, ctx_len = 27 * 4, 27, 2.0, 129 + 8
    planes = toy.plane_bytes_per_launch(c, rows)
    kv = rows * ctx_len * 2 * 2 * 64 * 4  # float32: twice llama's count
    want = 100.0 * (steps * (planes + kv) + pieces * planes) / (tr["window_s"] * 819e9)
    assert got["model_step.hbm_share_pct.decode"] == pytest.approx(want)
    q40_s = tr["modules"]["jit__prefill"]["custom_calls"]["q40_matmul_stacked [custom-call]"]
    least = (steps + pieces) * 128 * 512 * 0.625 / 819e9
    assert got["kernels.wcls_roofline.decode"] == pytest.approx(100.0 * least / q40_s)
    assert got["kernels.q40_matmul_roofline.decode"] == pytest.approx(
        100.0 * (steps + pieces) * toy.weights_per_token(c) * 0.625 / 819e9 / q40_s)


def test_a_layer_skipped_in_the_toy_familys_reference_is_not_correct(later_pr):
    """The seam hands the comparison to the family's reference and to no
    other: break that file alone, and ``correct`` turns false."""
    path = os.path.join(later_pr, "benchmarks", "families", "toy", "reference.py")
    with open(path) as f:
        sound = f.read()
    broken = sound.replace('for i in range(conf["depth"]):',
                           'for i in range(1, conf["depth"]):')
    assert broken != sound
    try:
        with open(path, "w") as f:
            f.write(broken)
        p = run(later_pr, ["--workload", "toy-dense.closed", "--seed", "8",
                           "--seconds", "3", "--trace", "0", "--rehearse"])
    finally:
        with open(path, "w") as f:
            f.write(sound)
    line = last_line(p)
    assert line["correct"] is False
    c = line["compared"]["widest_gap_spreads"]
    assert c["value"] > 10 * c["limit"]
    assert p.stderr.strip().splitlines()[-1] == "correct = False"


@pytest.mark.parametrize("change,says", [
    (lambda c: c.pop("family"), 'needs a "family" key'),
    (lambda c: c.update(tp=4, chips=4), '"tp": 4'),
])
def test_a_configuration_the_seam_cannot_serve_fails_in_one_line(later_pr, change, says):
    """No ``family``; ``tp > 1`` on a family that cannot shard: a failure
    that names the key, before any child is started."""
    path = os.path.join(later_pr, "benchmarks", "configs", "toy-dense.json")
    with open(path) as f:
        sound = f.read()
    c = json.loads(sound)
    change(c)
    try:
        with open(path, "w") as f:
            json.dump(c, f)
        p = run(later_pr, ["--workload", "toy-dense.closed", "--seed", "1",
                           "--seconds", "1", "--trace", "0", "--rehearse"])
    finally:
        with open(path, "w") as f:
            f.write(sound)
    assert p.returncode != 0 and p.stdout.strip() == ""
    said = [l for l in p.stderr.splitlines() if l.startswith("BENCHMARK FAILED")]
    assert len(said) == 1 and says in said[0] and "toy-dense" in said[0]
    assert "[bench]" not in p.stderr  # no child came up
