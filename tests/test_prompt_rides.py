"""The prompt rides the decode chunk (``BatchSession.ride_t``, PR 28).

A pool that launches a decode chunk carries, in each of its steps, up to
``ride_t`` tokens of the oldest waiting prompt beside its decode rows
(``llama.forward_batched``'s ``ride``): the projections and the FFN run once
on ``[B + t, K]``, the riders' K/V land in their own pool row, and their
queries attend that row alone. Held here, on the CPU at tiny sizes, dense
float32, q40 planes through the kernels (interpret mode) and Mixtral-shaped
expert layers of both kinds as cases of the same tests:

* a row whose prompt rode emits the tokens of a monolithic ``admit()`` of the
  same request, and its slab row holds the same K/V: bit for bit through the
  q40 kernels, whose rows do not depend on the rows beside them. The dense
  float32 models need a tolerance, ``DENSE_ATOL``: XLA:CPU's float32 dot
  picks its blocking from the row count, so a row of a ``[B + t, K]`` product
  and the same row of a ``[bucket, K]`` one differ in the last bit from the
  second layer on (read: 2.7e-7 on values up to 0.8; the first layer's K/V
  are equal). ``test_solo_chunked_prefill_logits_bit_identical`` fails at the
  parent of this PR for that reason. The tokens are the same either way;
* the rows that decode beside a rider emit what they emit without one;
* the edges: a prefix that ends in mid-chunk, prompts of 2 and ``t + 1``
  tokens, a prompt that fills its slab, a pool that loses its last live row,
  a pool with nothing live (standalone pieces, as before), cancel and
  release in mid-prompt, the ``prefill_chunk`` fault seam;
* the paths with loops of their own (a layer plan, ``--kv-pages``, ``--tp``,
  monolithic admission) never ride and emit what they did;
* the two counters say how the prompt tokens reached the cache.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu import faults, observability
from dllama_tpu.models import llama
from dllama_tpu.models.config import ModelConfig
from dllama_tpu.runtime.generate import Engine
from dllama_tpu.runtime.sampler import SamplerConfig

DENSE = ModelConfig(
    arch="llama", dim=128, hidden_dim=256, n_layers=2, n_heads=4,
    n_kv_heads=2, vocab_size=128, seq_len=64, head_size=32, kv_dim=64,
    dtype="float32",
)
MIXTRAL = ModelConfig(
    arch="mixtral", dim=128, hidden_dim=256, n_layers=2, n_heads=4,
    n_kv_heads=2, vocab_size=128, seq_len=64, head_size=32, kv_dim=64,
    n_experts=4, n_active_experts=2, rope_style="half", dtype="float32",
)
#: name -> (config, quantised): the four kinds of layer the riders share
MODELS = {"dense": (DENSE, False), "q40": (DENSE, True),
          "mixtral": (MIXTRAL, False), "mixtral-q40": (MIXTRAL, True)}
CHUNK, T = 4, 2  # steps a launch, prompt tokens a step: prefill_chunk 8
DENSE_ATOL = 2e-6  # a few float32 last bits of values under 1 (see above)
LONG = [(i * 7 + 3) % 96 + 1 for i in range(23)]
SHORT = [5, 9, 3]
S_RES = SamplerConfig(temperature=0.9, topp=0.95, seed=7)
S_NEW = SamplerConfig(temperature=1.2, topp=0.9, seed=23)

_ENGINES: dict = {}


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def engine(model: str) -> Engine:
    """One engine a model for the whole file (its registry only counts up:
    the counters' test takes an engine of its own)."""
    if model not in _ENGINES:
        cfg, quant = MODELS[model]
        params = llama.random_params(cfg, seed=1, dtype=np.float32)
        if quant:
            params = llama.quantize_params(params, "q40")
        _ENGINES[model] = Engine(cfg, params, SamplerConfig(temperature=0.0),
                                 metrics=None)
    return _ENGINES[model]


def session(eng: Engine, layout: str = "uniform", **kw):
    kw = dict(dict(max_batch=3, chunk=CHUNK, prefill_chunk=CHUNK * T), **kw)
    if layout == "bucketed":
        # both prompts of these tests fall in the one 32-slot pool
        kw.update(bucket_kv=True, min_bucket=32)
    return eng.batch_session(**kw)


def tick(sess, got: dict) -> dict:
    """The scheduler's tick: at most one standalone piece, then a chunk."""
    adv = sess.prefill_step()
    fresh = sess.step_chunk()
    for h, burst in fresh.items():
        got.setdefault(h, []).extend(burst)
    return {"adv": adv, "fresh": fresh, "rode": list(sess.rode)}


def drain(sess, got: dict, limit: int = 200) -> dict:
    for _ in range(limit):
        if all(sess.is_done(h) for h in got):
            return got
        tick(sess, got)
    raise AssertionError("the session did not drain")


def same_kv(model: str, got: dict, want: dict) -> None:
    for k in want:
        if MODELS[model][1]:
            assert np.array_equal(got[k], want[k]), k
        else:
            assert np.allclose(got[k], want[k], rtol=0.0, atol=DENSE_ATOL), k
        assert np.abs(want[k]).max() > 0.1  # not a row of zeros


def slab_row(sess, handle: int, upto: int) -> dict:
    """The row's K/V over its first ``upto`` positions: [L, upto, kv, hd]."""
    pool, row = sess._where[handle]
    return {k: np.asarray(v)[:, row, :upto] for k, v in pool.cache.items()}


def monolithic(eng, layout, resident, prompt, steps, sampler, **kw):
    """The same request admitted whole beside the same resident row:
    (its tokens, its slab row's K/V right after ``admit``)."""
    sess = session(eng, layout, **kw)
    got = {}
    if resident is not None:
        got[sess.admit(resident, steps=40, sampler=S_RES)] = []
        tick(sess, got)
    h = sess.admit(prompt, steps=steps, sampler=sampler)
    kv = slab_row(sess, h, len(prompt) - 1)
    got[h] = []
    drain(sess, got)
    sess.close()
    return got[h], kv


def ride_beside_a_resident(eng, layout, prompt, steps, sampler, **kw):
    """``prompt`` admitted into a pool where a row decodes: (session, its
    handle, the resident's, tokens so far, the ticks it rode in). Returns
    at the tick that ends the prompt, before the row's first decode step."""
    sess = session(eng, layout, **kw)
    got = {}
    res = sess.admit(SHORT, steps=40, sampler=S_RES)
    got[res] = []
    tick(sess, got)
    new = sess.admit_begin(prompt, steps=steps, sampler=sampler)
    got[new] = []
    ticks = []
    while new in sess.pending_prefills:
        ticks.append(tick(sess, got))
        assert len(ticks) < 100
    return sess, new, res, got, ticks


# ---------------------------------------------------------------------------
# a ridden prompt is a monolithic admit; the rows beside it do not see it
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", ["uniform", "bucketed"])
@pytest.mark.parametrize("model", list(MODELS))
def test_a_ridden_prompt_emits_and_holds_what_a_monolithic_admit_does(
        model, layout):
    eng = engine(model)
    want, want_kv = monolithic(eng, layout, SHORT, LONG, 10, S_NEW)
    sess, new, res, got, ticks = ride_beside_a_resident(
        eng, layout, LONG, 10, S_NEW)
    # 22 prefix tokens at 8 a launch: three launches carried it, no
    # standalone piece ran, and the row was not live until the last
    assert [t["adv"] for t in ticks] == [None] * 3
    assert [[r[0] for r in t["rode"]] for t in ticks] == [[new]] * 3
    assert [t["rode"][0][3] for t in ticks] == [False, False, True]
    assert all(new not in t["fresh"] and t["fresh"][res] for t in ticks)
    pool, row = sess._where[new]
    assert pool is sess._where[res][0]  # one pool: that is why it rode
    assert pool.pos[row] == len(LONG) - 1  # _go_live set the true one
    same_kv(model, slab_row(sess, new, len(LONG) - 1), want_kv)
    drain(sess, got)
    sess.close()
    assert got[new] == want


@pytest.mark.parametrize("model", list(MODELS))
def test_the_rows_that_decode_beside_a_rider_emit_what_they_do_without_one(
        model):
    eng = engine(model)
    streams, rows = [], []
    for rider in (False, True):
        sess = session(eng)
        got = {sess.admit(SHORT, steps=24, sampler=S_RES): [],
               sess.admit([7, 1], steps=24, sampler=S_NEW): []}
        tick(sess, got)
        if rider:
            h = sess.admit_begin(LONG, steps=4)
            while h in sess.pending_prefills:
                tick(sess, got)
            assert sess._prefills == {} and h not in got  # never decoded
            sess.cancel(h)
            sess.release(h)
        drain(sess, got)
        streams.append([got[h] for h in sorted(got)])
        rows.append([slab_row(sess, h, 20) for h in sorted(got)])
        sess.close()
    assert streams[0] == streams[1]
    for alone, beside in zip(*rows):
        same_kv(model, beside, alone)


# ---------------------------------------------------------------------------
# the edges
# ---------------------------------------------------------------------------

def test_a_prefix_ends_in_mid_chunk_and_the_next_prompt_starts_at_the_next_step():
    eng = engine("dense")
    first, second = [9, 8, 7, 6], LONG  # a prefix of 3: two steps, 2 + 1
    want = [monolithic(eng, "uniform", SHORT, p, 6, S_NEW)[0]
            for p in (first, second)]
    sess = session(eng)
    got = {sess.admit(SHORT, steps=40, sampler=S_RES): []}
    tick(sess, got)
    a = sess.admit_begin(first, steps=6, sampler=S_NEW)
    b = sess.admit_begin(second, steps=6, sampler=S_NEW)
    got.update({a: [], b: []})
    plan = sess._plan_ride(sess._where[a][0])
    assert plan == [(a, 0, 3), (b, 2, 4)]  # (handle, first step, cursor after)
    lines = np.asarray(sess._ride_operand(plan))
    assert lines[:, T:].tolist() == [
        [sess._where[a][1], 0, 2], [sess._where[a][1], 2, 1],
        [sess._where[b][1], 0, 2], [sess._where[b][1], 2, 2]]
    assert lines[:, :T].tolist() == [[9, 8], [7, 0], LONG[0:2], LONG[2:4]]
    first_tick = tick(sess, got)
    # one launch carried both: the same span, the first one complete
    assert [(r[0], r[3]) for r in first_tick["rode"]] == [(a, True), (b, False)]
    assert first_tick["rode"][0][1:3] == first_tick["rode"][1][1:3]
    assert sess._prefills[b].cursor == 4
    drain(sess, got)
    sess.close()
    assert [got[a], got[b]] == want


@pytest.mark.parametrize("n_tokens", [2, T + 1, 2 * T + 1])
def test_short_prompts_ride_in_one_step_or_two(n_tokens):
    eng = engine("q40")
    prompt = LONG[:n_tokens]
    want, want_kv = monolithic(eng, "uniform", SHORT, prompt, 8, S_NEW)
    sess, new, _, got, ticks = ride_beside_a_resident(
        eng, "uniform", prompt, 8, S_NEW)
    assert len(ticks) == 1
    assert [(r[0], r[3]) for r in ticks[0]["rode"]] == [(new, True)]
    same_kv("q40", slab_row(sess, new, n_tokens - 1), want_kv)
    drain(sess, got)
    sess.close()
    assert got[new] == want


@pytest.mark.parametrize("layout,n_tokens", [("uniform", 64), ("bucketed", 32)])
def test_a_prompt_that_fills_its_slab_loses_no_slot_to_the_padding(
        layout, n_tokens):
    """The prefix takes the slab's slots 0 .. ctx - 2, an odd count: the last
    step carries one token and one padded rider, which is dropped (its
    column is the SLAB's length, 32 here under a model context of 64): no
    slot of the row differs from a monolithic admit's, and the one token the
    row has room for is the same."""
    eng = engine("q40")
    prompt = [(i * 5 + 1) % 100 + 1 for i in range(n_tokens)]
    want, want_kv = monolithic(eng, layout, SHORT, prompt, 1, S_NEW)
    sess, new, _, got, ticks = ride_beside_a_resident(
        eng, layout, prompt, 1, S_NEW)
    pool, row = sess._where[new]
    assert pool.ctx == n_tokens and len(ticks) == -(-(n_tokens - 1) // 8)
    same_kv("q40", slab_row(sess, new, n_tokens - 1), want_kv)
    drain(sess, got)
    sess.close()
    assert got[new] == want and len(want) == 1


def test_a_riding_row_pins_where_no_rider_attends_until_it_goes_live():
    eng = engine("dense")
    sess, new, res, got, ticks = ride_beside_a_resident(
        eng, "uniform", LONG[:4], 6, S_NEW)
    sess.release(new)  # its row pins again, as every free row does
    pool, row = sess._where[res]
    new = sess.admit_begin(LONG, steps=6, sampler=S_NEW)
    free = [r for r in range(pool.cap) if pool.rows[r] is None]
    for _ in range(2):
        tick(sess, got)
        assert pool.pos[sess._where[new][1]] == pool.ctx - 1
        assert all(pool.pos[r] == pool.ctx - 1 for r in free)
    sess.close()


def test_a_rider_whose_pool_loses_its_last_live_row_has_the_chunk_launched():
    eng = engine("dense")
    want, _ = monolithic(eng, "uniform", None, LONG, 9, S_NEW)
    sess = session(eng)
    res = sess.admit(SHORT, steps=CHUNK + 2, sampler=S_RES)
    got = {res: []}
    tick(sess, got)
    new = sess.admit_begin(LONG, steps=9, sampler=S_NEW)
    got[new] = []
    first = tick(sess, got)  # the resident's last tokens; the rider begins
    assert sess.is_done(res) and first["rode"][0][0] == new
    sess.release(res)
    alone = tick(sess, got)  # nothing decodes: launched for the rider
    assert alone["adv"] is None and alone["fresh"] == {}
    assert [r[0] for r in alone["rode"]] == [new]
    # a prompt that arrives now finds a pool that launches, and rides too
    late = sess.admit_begin(LONG[:5], steps=3, sampler=S_RES)
    got[late] = []
    assert [r[0] for r in tick(sess, got)["rode"]] == [new, late]
    drain(sess, got)
    sess.close()
    assert got[new] == want


def test_with_nothing_live_a_prompt_takes_standalone_pieces_and_keeps_them():
    """A burst at an idle pool: the first prompt is prefilled by today's
    standalone pieces, one a tick, also after a row went live beside it; a
    prompt that gets its first tokens then rides in the same ticks."""
    eng = engine("dense")
    want = [monolithic(eng, "uniform", None, p, 7, S_NEW)[0]
            for p in (LONG, LONG[3:20])]
    sess = session(eng)
    a = sess.admit_begin(LONG, steps=7, sampler=S_NEW)
    got = {a: []}
    first = tick(sess, got)
    assert first["adv"] == (a, False) and first["rode"] == []
    assert first["fresh"] == {} and sess._prefills[a].cache is not None
    got[sess.admit(SHORT, steps=30, sampler=S_RES)] = []  # now a row is live
    b = sess.admit_begin(LONG[3:20], steps=7, sampler=S_NEW)
    got[b] = []
    second = tick(sess, got)
    assert second["adv"] == (a, False)  # begun in a staging cache: stays
    assert [r[0] for r in second["rode"]] == [b]
    assert sess._prefills[b].cache is None  # a rider has no staging cache
    with pytest.raises(ValueError, match="rides"):
        sess.prefill_step(b)
    drain(sess, got)
    sess.close()
    assert [got[a], got[b]] == want


@pytest.mark.parametrize("how", ["cancel, then release", "release alone"])
def test_a_row_dropped_in_mid_prompt_frees_its_slot_for_a_successor(how):
    """What the scheduler does with a cancelled request or an expired
    deadline (``Batcher._reap_admit``: cancel, then release). The row pins
    again, the resident decodes on, and the next prompt admitted into the
    same row rides and emits a monolithic admit's tokens."""
    eng = engine("dense")
    want_res, _ = monolithic(eng, "uniform", None, SHORT, 30, S_RES)
    want, _ = monolithic(eng, "uniform", SHORT, LONG[2:], 8, S_NEW)
    sess = session(eng, max_batch=2)
    res = sess.admit(SHORT, steps=30, sampler=S_RES)
    got = {res: []}
    tick(sess, got)
    gone = sess.admit_begin(LONG, steps=8, sampler=S_NEW)
    tick(sess, got)
    pool, row = sess._where[gone]
    assert sess._prefills[gone].cursor == CHUNK * T
    if how.startswith("cancel"):
        sess.cancel(gone)
        assert sess.pending_prefills == [] and sess.is_done(gone)
        assert tick(sess, got)["rode"] == []
    sess.release(gone)
    assert sess.pending_prefills == [] and pool.pos[row] == pool.ctx - 1
    assert sess.reserved_tokens == sess._slots[res].reserved
    new = sess.admit_begin(LONG[2:], steps=8, sampler=S_NEW)
    assert sess._where[new] == (pool, row)
    got[new] = []
    drain(sess, got)
    sess.close()
    assert got[new] == want and got[res] == want_res


def test_the_prefill_chunk_fault_seam_fires_in_the_launch_that_carries_a_rider():
    eng = engine("dense")
    want, _ = monolithic(eng, "uniform", SHORT, LONG, 6, S_NEW)
    sess = session(eng)
    got = {sess.admit(SHORT, steps=40, sampler=S_RES): []}
    tick(sess, got)
    faults.install("prefill_chunk:raise:times=1")
    tick(sess, got)  # nobody rides: the seam is not reached
    new = sess.admit_begin(LONG, steps=6, sampler=S_NEW)
    got[new] = []
    with pytest.raises(faults.FaultInjected):
        sess.step_chunk()
    # before the launch: nothing advanced, nothing donated, still admitted
    assert sess._prefills[new].cursor == 0 and sess.rode == []
    drain(sess, got)
    sess.close()
    assert got[new] == want


# ---------------------------------------------------------------------------
# the paths with loops of their own never ride
# ---------------------------------------------------------------------------

def _plan_engine():
    from benchmarks import families
    from tests.test_layer_plan import dense_params, load_conf, make_planes

    conf = load_conf()
    cfg = families.load(conf).model_config(conf, conf["server"])
    return Engine(cfg, dense_params(make_planes(conf), conf),
                  SamplerConfig(temperature=0.0), cache_dtype=jnp.float32,
                  metrics=None)


def _tp_engine():
    from dllama_tpu.parallel.mesh import tp_mesh

    params = llama.quantize_params(
        llama.random_params(DENSE, seed=1, dtype=np.float32), "q40")
    return Engine(DENSE, params, SamplerConfig(temperature=0.0),
                  mesh=tp_mesh(2), metrics=None)


@pytest.mark.parametrize("path", ["layer plan", "kv pages", "tp 2",
                                  "monolithic admission"])
def test_a_path_with_its_own_loop_takes_standalone_pieces_as_before(path):
    eng = {"layer plan": _plan_engine, "tp 2": _tp_engine}.get(
        path, lambda: engine("dense"))()
    kw = {"kv pages": {"kv_pages": 8},
          "monolithic admission": {"prefill_chunk": 0}}.get(path, {})
    lo = 259 if path == "layer plan" else 1  # its vocabulary's byte pieces
    prompt, short = [lo + t for t in LONG], [lo + t for t in SHORT]
    assert eng.pooled_rides == (path in ("kv pages", "monolithic admission"))

    def serve(chunked: bool) -> list:
        sess = session(eng, **(kw if chunked else {"prefill_chunk": 0}))
        assert sess.ride_t == 0
        got = {sess.admit(short, steps=12): []}
        tick(sess, got)
        new = (sess.admit_begin if chunked else sess.admit)(prompt, steps=6)
        got[new] = []
        advanced = []
        while new in sess.pending_prefills:
            t = tick(sess, got)
            advanced.append(t["adv"])
            assert t["rode"] == []
        if chunked and path != "monolithic admission":
            # 22 prefix tokens by standalone pieces of 8 beside a live row
            assert advanced == [(new, False), (new, False), (new, True)]
        drain(sess, got)
        sess.close()
        return [got[h] for h in sorted(got)]

    assert serve(chunked=True) == serve(chunked=False)


# ---------------------------------------------------------------------------
# the counters
# ---------------------------------------------------------------------------

def _value(reg, name: str, **labels) -> float:
    for v in reg.snapshot().get(name, {}).get("values", []):
        if v["labels"] == labels:
            return v["value"]
    return 0.0


def test_the_counters_say_how_the_prompt_tokens_reached_the_cache():
    reg = observability.MetricsRegistry()
    eng = Engine(DENSE, llama.random_params(DENSE, seed=1, dtype=np.float32),
                 SamplerConfig(temperature=0.0), metrics=reg)
    sess = session(eng)
    staged = sess.admit_begin(LONG, steps=20)  # nothing live: pieces
    got = {staged: []}
    while staged in sess.pending_prefills:
        tick(sess, got)
    riders = [sess.admit_begin(LONG[:n], steps=3) for n in (12, 6)]
    got.update({h: [] for h in riders})
    launches = 0
    while sess.pending_prefills:
        launches += bool(tick(sess, got)["rode"])
    drain(sess, got)
    sess.close()
    tokens = "dllama_prefill_tokens_total"
    assert _value(reg, tokens, how="piece") == len(LONG) - 1
    assert _value(reg, tokens, how="ride") == 11 + 5
    # 11 tokens take six steps (2+2+2+2+2+1), then 5 take three (2+2+1):
    # nine steps are three launches of 4 steps x 2 slots, 16 tokens in the
    # 24 slots they offered: a third was padding
    assert launches == 3
    assert _value(reg, "dllama_ride_slots_total") == launches * CHUNK * T
    pieces = sum(v["count"] for v in
                 reg.snapshot()["dllama_prefill_chunk_ms"]["values"])
    assert pieces == 3 + launches  # a launch that carries riders is a chunk


# ---------------------------------------------------------------------------
# launches that run ahead of their collection carry the same riders
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("model", list(MODELS))
def test_riders_of_launches_that_run_ahead_emit_what_they_do_in_step(model):
    """Prompts ride beside a resident row while the next launch is
    dispatched before the one before it is read: the cursors advance and a
    prefix that ends in mid-launch goes live as that launch is dispatched,
    so every row emits what it does in the synchronous loop, one tick
    later at most (``tests.test_continuous_batching.both``), and the ride
    counters count the same tokens."""
    from tests.test_continuous_batching import both

    cfg, quant = MODELS[model]
    params = llama.random_params(cfg, seed=1, dtype=np.float32)
    if quant:
        params = llama.quantize_params(params, "q40")
    arrivals = [(0, SHORT, 30, S_RES, ()),
                (1, LONG, 8, S_NEW, ()),       # prefix 22: ends mid-launch
                (1, LONG[:9], 6, SamplerConfig(temperature=0.0), ()),
                (3, LONG[3:20], 7, S_NEW, ())]
    want, got, _, why, reg = both(arrivals, cfg=cfg, params=params,
                                  max_batch=4, chunk=CHUNK,
                                  prefill_chunk=CHUNK * T)
    assert got == want and set(why.values()) == {"length"}
    assert _value(reg, "dllama_prefill_tokens_total", how="ride") == sum(
        len(p) - 1 for _, p, *_ in arrivals[1:])
    assert _value(reg, "dllama_decode_ahead_total", state="queued") > 0
