"""The generator: same work for every seed, open arrivals, the arithmetic."""
import json
import os

import loadgen
from conftest import BENCH


def mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def test_every_seed_sends_the_same_lengths_and_other_words():
    m = mix("chat-closed8")
    a = loadgen.make_requests(m, 1, 160)
    b = loadgen.make_requests(m, 2 ** 31 + 12345, 160)
    assert [r.prompt_tokens for r in a] == [r.prompt_tokens for r in b]
    assert [r.max_tokens for r in a] == [r.max_tokens for r in b]
    assert all(x.user != y.user for x, y in zip(a, b))
    assert [r.user for r in a] == [r.user for r in loadgen.make_requests(m, 1, 160)]
    other = loadgen.make_requests(dict(m, order_seed=7), 1, 160)
    assert [r.prompt_tokens for r in a] != [r.prompt_tokens for r in other]
    for i in range(0, 160, 16):  # every block holds every stratum once
        assert sorted(r.prompt_tokens for r in a[i:i + 16]) == \
            sorted(r.prompt_tokens for r in a[:16])
    assert min(r.prompt_tokens for r in a) >= 32 and max(r.prompt_tokens for r in a) <= 512
    assert min(r.max_tokens for r in a) >= 16 and max(r.max_tokens for r in a) <= 96


def test_prompt_length_in_tokens_is_its_length_in_characters():
    r = loadgen.make_requests(mix("chat-closed8"), 3, 4)[0]
    ids = loadgen.encode_prompt(r.user)
    assert len(ids) == r.prompt_tokens and ids[0] == 1
    assert all(3 <= i < 259 for i in ids[1:])
    assert "[" not in r.user and not any(c.isdigit() for c in r.user)


def test_open_loop_arrivals_come_from_the_seed():
    m = mix("tiny-open")
    a = loadgen.make_requests(m, 9, 200)
    b = loadgen.make_requests(m, 9, 200)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all(x.due_s < y.due_s for x, y in zip(a, a[1:]))
    assert 200 / a[-1].due_s == __import__("pytest").approx(3.0, rel=0.35)


def test_served_ids_and_pct():
    assert loadgen.served_ids("[300][4001]") == [300, 4001]
    assert loadgen.served_ids("[300]x[4001]") is None
    assert loadgen.pct([1, 2, 3, 4, 5], 0.5) == 3
    assert loadgen.pct(list(range(101)), 0.9) == 90


def test_window_stats_counts_only_the_window():
    rq = loadgen.Request(0, "ab", 12, 4)
    r = loadgen.Result(rq)
    r.sent, r.first, r.last, r.status, r.done = 10.0, 10.5, 11.5, 200, True
    r.bursts = [(10.5, 2), (11.5, 2)]
    s = loadgen.window_stats([r], 9.0, 11.0)
    assert s["attempted"] == 1 and s["failed"] == 0 and s["out_tokens"] == 2
    assert s["ttft_ms"] == [500.0] and s["tpot_ms"] == [1000.0 / 3]
    assert s["prompt_tokens"] == 12
