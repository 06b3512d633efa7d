"""Idle gaps named by the program's host spans, on a made-up record whose
answer is known: a gap wholly inside one span, one straddling two, one under
no span at all."""
import pytest

import trace_hostspans
import trace_reduce

S = 1e9


def op(start, dur, name="%fusion.1 = f32[8] fusion(...)"):
    return [name, start * S, dur * S]


def span(name, start, dur, tick):
    return [name, start * S, dur * S, {"tick": tick}]


@pytest.fixture
def record():
    # one launch from 0 to 10 s; the device runs 0-2, 3-5, 6-8 and 9.5-10:
    # gaps 2-3 (inside decode_fetch), 5-6 (publish 5-5.4, then decode_dispatch
    # 5.4-6.5) and 8-9.5 (no span: the tick ended at 8)
    return {
        "/device:TPU:0": {
            "XLA Modules": [["jit__decode_loop_batch(123)", 0.0, 10 * S]],
            "XLA Ops": [op(0, 2), op(3, 2), op(6, 2), op(9.5, 0.5),
                        ["%while.3 = (s32[]) while(...)", 0.0, 10 * S]],
        },
        "/host:CPU": {
            "python3#4": [
                ["tick", 0.0, 4 * S, {"tick": 7}],
                span("decode_wait", 0.0, 1.9, 7),
                span("decode_fetch", 1.9, 1.2, 7),
                ["tick", 4 * S, 4 * S, {"tick": 8}],
                span("publish", 4.9, 0.5, 8),
                span("decode_dispatch", 5.4, 1.1, 8),
                ["scheduler_window", 0.0, 8 * S, {"window": 3}],
            ],
            # a handler thread: spans with a tick live only on the
            # scheduler's line, so this one names nothing
            "python3#9": [["sse_write", 8.2 * S, 0.1 * S, {"span_id": 5}]],
        },
    }


def test_gaps_are_named_by_the_leaf_spans_over_them(record):
    r = trace_hostspans.attribute(record)
    assert r["window_s"] == 10.0 and r["gaps"] == 3
    assert r["idle_s"] == pytest.approx(3.5)
    by = dict(r["idle_by_phase"])
    assert by == pytest.approx({"decode_fetch": 1.0, "publish": 0.4,
                                "decode_dispatch": 0.6, "unattributed": 1.5})
    assert r["attributed_share"] == pytest.approx(2.0 / 3.5)
    longest = r["longest_gaps"]
    assert [g["phase"] for g in longest] == ["unattributed", "decode_fetch",
                                             "decode_dispatch"]
    assert [g["tick"] for g in longest] == [None, 7, 8]
    assert longest[2]["phases"] == [["decode_dispatch", pytest.approx(0.6)],
                                    ["publish", pytest.approx(0.4)]]
    assert r["ticks_in_window"] == 2
    assert dict(r["host_s_by_phase"])["decode_wait"] == pytest.approx(1.9)


def test_the_window_and_the_idle_seconds_are_the_reductions_own(record):
    r = trace_hostspans.attribute(record)
    reduced = trace_reduce.reduce(record)
    assert r["window_s"] == reduced["window_s"]
    assert r["idle_s"] == pytest.approx(reduced["window_s"] - reduced["busy_s"])
    assert r["idle_s"] == pytest.approx(dict(reduced["idle_gaps"])["unattributed"])
    assert max(g["seconds"] for g in r["longest_gaps"]) == pytest.approx(
        dict(reduced["idle_gaps"])["longest single gap"])


def test_a_trace_without_host_spans_is_all_unattributed(record):
    del record["/host:CPU"]
    r = trace_hostspans.attribute(record)
    assert dict(r["idle_by_phase"]) == pytest.approx({"unattributed": 3.5})
    assert r["attributed_share"] == 0.0
    assert trace_hostspans.attribute({"/host:CPU": {}}) is None
