"""The reduction from a trace to numbers, on a small recorded v5e trace (the
first 400 events of each device line of a traced run of cell 1, PR 23) and on
a made-up one whose answer is known."""
import json
import os

import pytest

import trace_reduce
from conftest import BENCH


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(BENCH, "tests", "data", "v5e_thin_trace.json")) as f:
        return json.load(f)


def test_recorded_trace_has_the_planes_and_lines_the_reduction_reads(recorded):
    assert list(recorded) == ["/device:TPU:0"]
    assert {"XLA Ops", "XLA Modules"} <= set(recorded["/device:TPU:0"])


def test_recorded_trace_reduces(recorded):
    r = trace_reduce.reduce(recorded)
    assert 0 < r["busy_s"] < r["window_s"] and r["chips"] == 1
    names = [n for n, _ in r["device_ops"]]
    assert "q40_matmul_stacked [custom-call]" in names
    assert not any(n.startswith("while") for n in names)
    q40 = dict(r["device_ops"])["q40_matmul_stacked [custom-call]"]
    assert r["custom_call_s"] == pytest.approx(q40)
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    assert dict(r["idle_gaps"])["unattributed"] == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    # the programs the cells' readers count are named as the trace names
    # them, without their hash; the window is the launches' own
    assert {"jit__decode_loop_batch", "jit__prefill"} <= set(r["modules"])
    mods = recorded["/device:TPU:0"]["XLA Modules"]
    assert r["window_s"] == pytest.approx(
        (max(s + d for _, s, d in mods) - min(s for _, s, _ in mods)) / 1e9)
    assert sum(m["launches"] for m in r["modules"].values()) == len(mods)
    inside = sum(m["custom_call_s"] for m in r["modules"].values())
    assert 0 < inside <= r["custom_call_s"] * (1 + 1e-9)


Q40 = "%q40_matmul.7 = f32[8,64] custom-call(...), custom_call_target=\"tpu_custom_call\""


def test_made_up_trace_busy_union_and_gaps():
    ops = [["%while.1 = (s32[]) while(...)", 0.0, 10e9],  # brackets its body
           ["%fusion.1 = f32[8] fusion(...)", 1e9, 2e9],
           [Q40, 2e9, 3e9],
           ["%fusion.2 = f32[8] fusion(...)", 7e9, 1e9]]
    rec = {"/device:TPU:0": {"XLA Ops": ops},
           "/host:CPU": {"python3": [["np.asarray", 5e9, 2e9]]}}
    r = trace_reduce.reduce(rec)  # no launches recorded: first to last operation
    assert r["window_s"] == 7.0 and r["busy_s"] == 5.0
    assert r["custom_call_s"] == 3.0 and r["modules"] == {}
    assert dict(r["device_ops"]) == {"fusion": 3.0, "q40_matmul [custom-call]": 3.0}
    assert dict(r["idle_gaps"]) == {"unattributed": 2.0, "longest single gap": 2.0}


def test_launches_bound_the_window_and_own_their_custom_calls():
    """A launch the profiler cut at either end is left out with its
    operations; what stays is counted over one interval."""
    ops = [[Q40, 0.5e9, 1e9],  # belongs to a launch that began before the trace
           [Q40, 2e9, 1e9], ["%fusion.1 = f32[8] fusion(...)", 3e9, 0.5e9],
           [Q40, 4e9, 1e9],
           [Q40, 6e9, 2e9],
           [Q40, 9e9, 1e9]]  # after the last whole launch
    mods = [["jit__decode_loop_batch(123)", 2e9, 3e9],
            ["jit__prefill(77)", 6e9, 2e9], ["jit_add(5)", 8e9, 0.1e9]]
    rec = {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": mods}}
    r = trace_reduce.reduce(rec)
    assert r["window_s"] == pytest.approx(6.1) and r["busy_s"] == 4.5
    assert r["custom_call_s"] == 4.0
    assert r["modules"]["jit__decode_loop_batch"] == {
        "launches": 1, "seconds": 3.0, "custom_call_s": 2.0,
        "custom_calls": {"q40_matmul [custom-call]": 2.0}}
    assert r["modules"]["jit__prefill"]["custom_call_s"] == 2.0
    assert r["modules"]["jit_add"]["launches"] == 1


def test_every_custom_call_of_a_launch_is_kept_by_its_short_name():
    """Two kinds of kernel in one launch: each has its seconds under its own
    name (as ``device_ops`` prints it), and together they are the program's
    ``custom_call_s``; two chips give their mean."""
    attn = Q40.replace("%q40_matmul.7", "%window_attention.3")
    ops = [[Q40, 1e9, 1e9], [attn, 2e9, 0.5e9], [Q40, 2.5e9, 1e9],
           ["%fusion.1 = f32[8] fusion(...)", 3.5e9, 0.25e9]]
    mods = [["jit__decode_loop_batch(123)", 1e9, 3e9]]
    rec = {f"/device:TPU:{i}": {"XLA Ops": ops, "XLA Modules": mods}
           for i in range(2)}
    m = trace_reduce.reduce(rec)["modules"]["jit__decode_loop_batch"]
    assert m["custom_calls"] == {"q40_matmul [custom-call]": 2.0,
                                 "window_attention [custom-call]": 0.5}
    assert m["custom_call_s"] == 2.5 and m["launches"] == 1


def test_readers_divide_the_trace_launches_by_the_trace_seconds():
    import importlib

    import loadgen

    rq = loadgen.Request(0, "ab", 129, 16)
    res = loadgen.Result(rq)
    res.status, res.done, res.bursts = 200, True, [(10.2, 8), (10.6, 8)]
    edge = lambda t, n: {"t": t, "prom": [("dllama_decode_chunk_ms_count", "", n)]}
    import families

    model = {"name": "made-up", "family": "llama",
             "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
             "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 512}
    trace = {"window_s": 2.0, "busy_s": 1.5, "modules": {
        "jit__decode_loop_batch": {"launches": 4, "seconds": 1.0, "custom_call_s": 0.5,
                                   "custom_calls": {"w13_q40_matmul [custom-call]": 0.3,
                                                    "window_attention [custom-call]": 0.2}},
        "jit__prefill": {"launches": 2, "seconds": 0.3, "custom_call_s": 0.25,
                         "custom_calls": {"w13_q40_matmul [custom-call]": 0.25}},
        "jit__prefill_other": {"launches": 9, "seconds": 9.0, "custom_call_s": 9.0,
                               "custom_calls": {"w13_q40_matmul [custom-call]": 9.0}}}}
    ctx = {"trace": trace, "trace_edges": (edge(10.0, 3), edge(11.0, 5)),
           "results": [res], "model": model, "family": families.load(model),
           "chips": 1,
           "server": {"batch_chunk": 8, "batch_max": 8},
           "peaks": {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e6}}
    args = {"decode_module": "^jit__decode_loop", "prefill_module": "^jit__prefill$"}
    common = importlib.import_module("common")
    w = common.traced_work(ctx, args)
    assert w["decode_steps"] == 32 and w["prefill_pieces"] == 2
    assert w["rows"] == 1.0 and w["seconds"] == 2.0 and w["custom_call_s"] == 0.75
    assert w["custom_calls"] == {"w13_q40_matmul [custom-call]": 0.55,
                                 "window_attention [custom-call]": 0.2}
    fam = ctx["family"]
    roofline = importlib.import_module("trace_custom_call_roofline")
    least = (32 * fam.launch_least_seconds(model, 1.0, ctx["peaks"])
             + 2 * fam.launch_least_seconds(model, 64.0, ctx["peaks"]))
    assert roofline.read(ctx, args) == pytest.approx(100.0 * least / 0.75)
    # a kernel's own share: the calls its pattern names, under the family's
    # function that its metric file names; no such call, nothing to read
    own = dict(args, names="^w13_", least="launch_least_seconds")
    assert roofline.read(ctx, own) == pytest.approx(100.0 * least / 0.55)
    assert roofline.read(ctx, dict(args, names="_q40_|^window_")) == \
        pytest.approx(100.0 * least / 0.75)
    assert roofline.read(ctx, dict(args, names="^flash_")) is None
    share = importlib.import_module("bytes_share").read(ctx, args)
    assert share > 0
    # a trace whose programs carry other names: nothing to read, never 0
    assert common.traced_work(ctx, {"decode_module": "^x$", "prefill_module": "^y$"}) is None
    assert importlib.import_module("flops_share").read(
        ctx, {"decode_module": "^x$", "prefill_module": "^y$"}) is None


def test_nothing_to_read_gives_nothing():
    assert trace_reduce.reduce({"/host:CPU": {"python3": [["x", 0.0, 1.0]]}}) is None
    assert trace_reduce.reduce({"/device:TPU:0": {"XLA Ops": []}}) is None


def test_readers_return_nothing_without_a_trace():
    import importlib

    for name in ("trace_idle", "trace_custom_call_roofline", "flops_share",
                 "bytes_share", "memory_stat"):
        mod = importlib.import_module(name)
        assert mod.read({"trace": None, "trace_edges": None,
                         "memory_peak_bytes": 0}, {}) is None
