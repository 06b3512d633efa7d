"""A model whose layers differ in kind (``ModelConfig.layer_plan``), on the
CPU at a tiny preset that keeps every mechanism, against the family's plain
reference (``benchmarks/families/mimo_v2/reference.py``: one full causal
forward, float32, no cache).

The preset (``benchmarks/configs/tiny-mimo.json``): a dense leading layer, one
period and more (F SSSS F SS), a window of 8 in a ring of 16, 1 and 2 KV
heads, v heads of 16 beside q/k heads of 24 of which 8 dimensions rotate, 4
of 16 experts held. The planes' scales are multiplied by 8, so that a
64-wide model's projections are as large as the published widths' (scores
that spread, sinks and windows that matter).

Tolerance: the program in float32 and the reference differ by the order of
float32 sums (measured 2e-6 of the logits' spread at worst); ``TOL`` is
2e-4 of that spread, two orders above it and one below what bfloat16
activations read (test_bfloat16_activations_fail_the_tolerance).
"""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import families
from benchmarks.families.mimo_v2 import reference, shapes, weights
from dllama_tpu import observability
from dllama_tpu.models import layer_plan, llama, moe
from dllama_tpu.ops import attention
from dllama_tpu.ops.attention import gqa_attention
from dllama_tpu.runtime.generate import Engine
from dllama_tpu.runtime.sampler import SamplerConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4  # of the reference logits' standard deviation
LENGTHS = (5, 12, 30, 41)  # prompt tokens: before and beyond the ring's 16
STEPS = 6


def load_conf(**changes) -> dict:
    with open(os.path.join(ROOT, "benchmarks/configs/tiny-mimo.json")) as f:
        return dict(json.load(f), **changes)


def make_planes(conf: dict, seed: int = 7) -> dict:
    planes = families.load(conf).make_planes(conf, seed)

    def louder(path, a):
        return a * 8.0 if path[-1].key in ("s", "s2") else a

    return jax.tree_util.tree_map_with_path(louder, planes)


def dense_params(planes: dict, conf: dict) -> dict:
    """The planes as float32 matrices in the program's tree: what the
    reference dequantises, so that the two sides multiply the same numbers."""
    def leaf(name, v):
        if isinstance(v, dict) and set(v) == {"w", "s", "s2"}:
            return reference.dequant_q40(v, weights.logical_k(name, conf))
        return v

    out = {k: leaf(k, v) for k, v in planes.items() if k != "layers"}
    out["layers"] = {kind: {k: leaf(k, v) for k, v in stack.items()}
                     for kind, stack in planes["layers"].items()}
    return out


@pytest.fixture(scope="module")
def tiny():
    conf = load_conf()
    planes = make_planes(conf)
    cfg = families.load(conf).model_config(conf, conf["server"])
    rng = np.random.default_rng(0)
    seqs = [rng.integers(259, conf["vocab_size"], size=n + STEPS).tolist()
            for n in LENGTHS]
    ref = reference.logits_at(planes, conf, seqs,
                              [list(range(len(s))) for s in seqs])
    return {"conf": conf, "planes": planes, "cfg": cfg, "seqs": seqs,
            "ref": ref, "params": dense_params(planes, conf)}


def worst(got, ref) -> float:
    """Largest difference, in standard deviations of the reference logits."""
    return float(np.abs(np.asarray(got) - ref).max() / ref.std())


def solo_logits(cfg, params, seq, n_prompt, piece=8):
    """Prefill ``seq[:n_prompt]`` in pieces, then decode the rest through
    the cache, token by token -> (logits [len(seq), V], cache)."""
    rope = llama.rope_tables(cfg)
    cache = llama.init_cache(cfg, jnp.float32)
    fwd = jax.jit(lambda p, r, t, c, ps: llama.forward(cfg, p, r, t, c, ps))
    out, pos = [], 0
    while pos < len(seq):
        n = min(piece, n_prompt - pos) if pos < n_prompt else 1
        lg, cache = fwd(params, rope, jnp.asarray(seq[pos:pos + n], jnp.int32),
                        cache, jnp.int32(pos))
        out.append(np.asarray(lg))
        pos += n
    return np.concatenate(out), cache


def test_plan_of_the_tiny_preset(tiny):
    cfg = tiny["cfg"]
    assert cfg.plan_text() == "F.D W.E*4 F.E W.E*2"
    assert cfg.plan_kinds == (("full", "dense"), ("window", "moe"),
                              ("full", "moe"))
    assert (cfg.ring_slots, cfg.max_prefill_piece) == (16, 9)
    assert (cfg.n_experts, cfg.n_experts_held, cfg.expert_first) == (16, 4, 4)
    assert max(LENGTHS) + STEPS > 2 * cfg.ring_slots  # the ring wraps twice


@pytest.mark.parametrize("i", range(len(LENGTHS)))
def test_solo_prefill_in_pieces_then_decode_matches_reference(tiny, i):
    seq, ref = tiny["seqs"][i], tiny["ref"][i]
    got, _ = solo_logits(tiny["cfg"], tiny["params"], seq, LENGTHS[i])
    assert worst(got, ref) < TOL


def test_quantized_planes_through_the_kernels_stay_near_reference(tiny):
    """The planes as the benchmark serves them (``QuantTensor``s through the
    q40 kernels, here in interpret mode, stacked by kind and steered by the
    scalar-prefetched layer index): the kernels multiply in bfloat16, so the
    logits stand some 0.04 spreads from the float32 reference; a wrong
    stack index, plane or column split reads 1 and more."""
    conf = tiny["conf"]
    params = families.load(conf).wrap_planes(tiny["planes"], conf)
    got, _ = solo_logits(tiny["cfg"], params, tiny["seqs"][2][:36], LENGTHS[2])
    assert TOL < worst(got, tiny["ref"][2][:36]) < 0.1


def test_pooled_rows_at_different_positions_match_reference(tiny):
    """Rows on both sides of the ring's wrap decode together: each row's
    logits are the reference's at that row's own positions."""
    cfg, params = tiny["cfg"], tiny["params"]
    eng = Engine(cfg, params, SamplerConfig(temperature=0.0),
                 cache_dtype=jnp.float32, metrics=None)
    B = len(LENGTHS)
    cache = llama.init_batch_cache(cfg, B + 1, jnp.float32, seq_len=48)
    for b, n in enumerate(LENGTHS):
        _, solo = eng.prefill(eng.new_cache(), tiny["seqs"][b][:n])
        cache = eng._batch_cache_insert(cache, solo, jnp.int32(b))
    step = jax.jit(lambda p, r, t, c, ps: llama.forward_batched(
        cfg, p, r, t, c, ps))
    pos = np.array(list(LENGTHS) + [47], np.int32)  # the last row is free
    for j in range(STEPS):
        toks = [tiny["seqs"][b][LENGTHS[b] + j] for b in range(B)] + [0]
        lg, cache = step(eng.params, eng.rope, jnp.asarray(toks, jnp.int32),
                         cache, jnp.asarray(pos))
        for b in range(B):
            assert worst(lg[b], tiny["ref"][b][LENGTHS[b] + j]) < TOL, (b, j)
        pos[:B] += 1


def test_batch_session_serves_the_references_greedy_tokens(tiny):
    """Through ``BatchSession`` (chunked admission, the pooled decode
    program, the slab pool): every served token is the reference's argmax
    on the sequence served, and the counters add up."""
    cfg, conf = tiny["cfg"], tiny["conf"]
    reg = observability.MetricsRegistry()
    eng = Engine(cfg, tiny["params"], SamplerConfig(temperature=0.0),
                 cache_dtype=jnp.float32, metrics=reg)
    sess = eng.batch_session(3, chunk=4, bucket_kv=True, min_bucket=32,
                             prefill_chunk=8)
    prompts = [tiny["seqs"][i][:LENGTHS[i]] for i in (0, 2, 3)]
    handles = [sess.admit_begin(p, 8) for p in prompts]
    served = {h: [] for h in handles}
    for _ in range(40):
        sess.prefill_step()
        for h, toks in sess.step_chunk().items():
            served[h].extend(toks)
        if all(sess.is_done(h) for h in handles):
            break
    assert all(len(served[h]) == 8 for h in handles)
    seqs = [p + served[h] for p, h in zip(prompts, handles)]
    ref = reference.logits_at(
        tiny["planes"], conf, seqs,
        [[len(p) - 1 + j for j in range(8)] for p in prompts])
    for h, lg in zip(handles, ref):
        assert lg.argmax(axis=1).tolist() == served[h]
    text = reg.render()
    held = float(_sample(text, 'dllama_moe_picks_total{held="1"}'))
    away = float(_sample(text, 'dllama_moe_picks_total{held="0"}'))
    steps = float(_sample(text, "dllama_moe_layer_steps_total"))
    active = float(_sample(text, "dllama_moe_active_experts_total"))
    reads = float(_sample(text, "dllama_moe_expert_reads_total"))
    k, layers = cfg.n_active_experts, cfg.plan_count(ffn="moe")
    assert steps > 0 and steps % (4 * layers) == 0
    # every live row picks k experts a layer-step; rows live a whole chunk
    assert (held + away) % (4 * layers * k) == 0 and held + away > 0
    assert 0 < active <= held
    assert active <= steps * cfg.n_experts_held
    # float32 matrices take the all-experts branch: every held expert read
    assert reads == steps * cfg.n_experts_held
    assert 'dllama_kv_resident_bytes{kind="window"}' in text
    sess.close()


def _sample(text: str, series: str) -> str:
    line = next(l for l in text.splitlines() if l.startswith(series + " "))
    return line.rsplit(" ", 1)[1]


def test_bfloat16_activations_fail_the_tolerance(tiny):
    conf = tiny["conf"]
    cfg = families.load(conf).model_config(
        conf, dict(conf["server"], dtype="bfloat16"))
    got, _ = solo_logits(cfg, tiny["params"], tiny["seqs"][2], LENGTHS[2])
    assert worst(got, tiny["ref"][2]) > 10 * TOL


@pytest.mark.parametrize("mechanism", ["window", "sink", "value_scale",
                                       "rotary", "router_bias", "expert"])
def test_reference_without_one_mechanism_fails_the_comparison(tiny, mechanism):
    """Each mechanism carries weight: the program, which has it, is far from
    a reference that lacks it."""
    seq = tiny["seqs"][3]
    lacking = reference.logits_at(tiny["planes"], tiny["conf"], [seq],
                                  [list(range(len(seq)))],
                                  without=mechanism)[0]
    got, _ = solo_logits(tiny["cfg"], tiny["params"], seq, LENGTHS[3])
    assert worst(got, tiny["ref"][3]) < TOL
    assert worst(got, lacking) > 10 * TOL


def test_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """The share ties to the model: four processes, each holding a quarter
    of the experts and routing over all of them, give parts whose sum is
    the reference's uncut expert layer."""
    conf = load_conf(n_routed_experts=16, share={"expert_first": 0})
    planes = make_planes(conf)
    m = dict(reference.sizes(conf))
    stack = planes["layers"]["window_moe"]
    lp_ref = jax.tree.map(lambda a: a[1], stack)
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 9, m["D"]), jnp.float32)
    whole = reference.experts(h, lp_ref, m, (0, 16), None, None)

    lp_all = jax.tree.map(lambda a: a[1],
                          dense_params(planes, conf)["layers"]["window_moe"])
    total = jnp.zeros_like(h)
    for first in (0, 4, 8, 12):
        share = load_conf(share={"expert_first": first})
        cfg = families.load(share).model_config(share, share["server"])
        lp = dict(lp_all, **{n: lp_all[n][first:first + 4]
                             for n in ("moe_upgate", "moe_down")})
        part = moe.moe_ffn(cfg, lp, h.reshape(18, -1)).reshape(h.shape)
        assert float(jnp.abs(part).max()) > 0
        mine = reference.experts(h, dict(lp_ref, **{
            n: jax.tree.map(lambda a: a[first:first + 4], lp_ref[n])
            for n in ("moe_upgate", "moe_down")}), m, (first, 4), None, None)
        assert float(jnp.abs(part - mine).max()) < 1e-5 * float(whole.std())
        total = total + part
    assert float(jnp.abs(total - whole).max()) < 1e-5 * float(whole.std())


def test_window_cache_bytes_do_not_grow_with_context(tiny):
    cfg = tiny["cfg"]
    short = llama.init_batch_cache(cfg, 4, jnp.float32, seq_len=32)
    long = llama.init_batch_cache(cfg, 4, jnp.float32, seq_len=64)
    a, b = layer_plan.kv_resident_bytes(short), layer_plan.kv_resident_bytes(long)
    assert a["window"] == b["window"] > 0
    assert b["full"] == 2 * a["full"] > 0
    assert short["wk"].shape == (6, 4, 16, 2, 24)
    assert short["wv"].shape == (6, 4, 16, 2, 16)
    assert long["k"].shape == (2, 4, 64, 1, 24)
    want = shapes.kv_resident_bytes(tiny["conf"], 4, 64, 16, cache_bytes=4)
    assert b == {k: int(v) for k, v in want.items()}


def test_what_is_not_built_for_a_plan_refuses_in_one_line(tiny):
    cfg, params = tiny["cfg"], tiny["params"]
    eng = Engine(cfg, params, SamplerConfig(temperature=0.0),
                 cache_dtype=jnp.float32, metrics=None)
    with pytest.raises(ValueError, match=r"--kv-pages.*layer plan F\.D W\.E"):
        eng.batch_session(2, kv_pages=8)
    with pytest.raises(ValueError, match="--spec-draft"):
        next(eng.generate_spec([300, 301], 4))
    with pytest.raises(ValueError, match="--spec-draft"):
        eng.generate_batch_spec([[300, 301]], 4)
    with pytest.raises(ValueError, match="forward_train"):
        llama.forward_train(cfg, params, jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(ValueError, match="--tp > 1"):
        Engine(cfg, params, mesh=object(), metrics=None)
    with pytest.raises(ValueError, match="at most 9 tokens a piece"):
        llama.forward(cfg, eng.params, eng.rope, jnp.zeros((16,), jnp.int32),
                      eng.new_cache(), jnp.int32(0))


def test_engine_generate_matches_pool(tiny):
    """The solo streaming path (``generate``: ``_prefill`` then the fused
    decode loop) emits what the pool serves for the same prompt."""
    cfg, params = tiny["cfg"], tiny["params"]
    eng = Engine(cfg, params, SamplerConfig(temperature=0.0),
                 cache_dtype=jnp.float32, metrics=None)
    prompt = tiny["seqs"][2][:LENGTHS[2]]
    solo = [t for t, _ in eng.generate(prompt, 8)]
    rows = eng.generate_batch([prompt, tiny["seqs"][0][:5]], 8)
    assert rows[0] == solo


def test_a_plan_of_one_kind_is_the_uniform_model():
    """The seam between ``models.llama`` and ``models.layer_plan`` is real:
    a plan whose every layer is ``("full", "dense")``, over a uniform tiny
    model's weights stacked under ``params["layers"]["full_dense"]``, walks
    the plan's runs, cores and cache tree and gives the uniform model's
    logits and K/V bit for bit (CPU, float32), through ``forward`` (a
    prefill of 5, then a decode step) and ``forward_batched`` (3 rows at
    positions of their own)."""
    from dllama_tpu.models.config import ModelConfig

    base = dict(arch="llama", dim=64, hidden_dim=128, n_layers=3, n_heads=4,
                n_kv_heads=2, vocab_size=96, seq_len=32, head_size=16,
                kv_dim=32, dtype="float32")
    uni = ModelConfig(**base)
    plan = ModelConfig(**base, layer_plan=(("full", "dense"),) * 3)
    assert plan.plan_text() == "F.D*3" and not uni.layer_plan
    params = llama.random_params(uni, seed=3, dtype=np.float32)
    rng = np.random.default_rng(4)
    for n in ("rms_att", "rms_ffn"):  # norms that are not all ones
        params["layers"][n] = (1.0 + 0.1 * rng.standard_normal(
            params["layers"][n].shape)).astype(np.float32)
    by_cfg = {uni: params,
              plan: dict(params, layers={"full_dense": params["layers"]})}

    def same(a, b):
        la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def solo(cfg):
        fwd = jax.jit(lambda p, r, t, c, ps: llama.forward(cfg, p, r, t, c, ps))
        rope, cache = llama.rope_tables(cfg), llama.init_cache(cfg)
        first, cache = fwd(by_cfg[cfg], rope, jnp.asarray([7, 1, 50, 3, 9]),
                           cache, jnp.int32(0))
        then, cache = fwd(by_cfg[cfg], rope, jnp.asarray([11]), cache,
                          jnp.int32(5))
        return first, then, cache

    def pooled(cfg):
        cache = llama.init_batch_cache(cfg, 3, seq_len=16)
        assert sorted(cache) == ["k", "v"]
        fill = np.random.default_rng(5)
        cache = {k: jnp.asarray(fill.standard_normal(v.shape), jnp.float32)
                 for k, v in sorted(cache.items())}
        step = jax.jit(lambda p, r, t, c, ps: llama.forward_batched(
            cfg, p, r, t, c, ps))
        return step(by_cfg[cfg], llama.rope_tables(cfg),
                    jnp.asarray([5, 6, 7]), cache, jnp.asarray([3, 0, 7]))

    same(solo(plan), solo(uni))
    same(pooled(plan), pooled(uni))


# ---------------------------------------------------------------------------
# attention as far as the step's queries reach (``layer_plan._attend``): a
# ladder of prefixes of the cache, here with a least rung of 4 handed in, so
# that a cache of 16 slots has the rungs 4, 8, 16
# ---------------------------------------------------------------------------

_SLOTS, _LEAST, _WINDOW, _LAYERS, _CIDX = 16, 4, 8, 3, 1
_HEADS, _KV, _HD, _VHD = 4, 2, 8, 4  # values narrower than keys


def _ladder_case(att, rows, pos, T, sink, seed=0):
    """Random q and stacked caches (every slot filled: what a slot holds
    beyond the reach is stale, and the mask's to leave out) -> the operands
    of ``_attend`` and of the one-pass reference."""
    rng = np.random.default_rng(seed)
    lead = (len(pos),) if rows else ()
    q = rng.standard_normal((*(lead or (T,)), _HEADS, _HD)).astype(np.float32)
    k = rng.standard_normal((_LAYERS, *lead, _SLOTS, _KV, _HD)).astype(np.float32)
    v = rng.standard_normal((_LAYERS, *lead, _SLOTS, _KV, _VHD)).astype(np.float32)
    cfg = types.SimpleNamespace(window=_WINDOW, window_sink=sink)
    lp = {"sink": rng.standard_normal((_HEADS,)).astype(np.float32)}
    pos = np.asarray(pos if rows else pos[0], np.int32)
    return cfg, lp, jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(pos)


def _one_pass(cfg, lp, att, rows, q, k, v, pos):
    """Attention as it was before the ladder: the layer's whole slab."""
    kw = dict(window=cfg.window if att == "window" else 0,
              sink=lp["sink"] if cfg.window_sink else None)
    if not rows:
        return gqa_attention(q, k[_CIDX], v[_CIDX], pos, **kw)
    return jax.vmap(lambda qb, ks, vs, p: gqa_attention(
        qb[None], ks, vs, p, **kw)[0])(q, k[_CIDX], v[_CIDX], pos)


#: (name, kind, rows?, positions (rows: one a row; a piece: its first), T)
_REACHES = [
    # B rows of one token: the reach is the longest row's pos + 1
    ("rows below the least rung", True, (0, 2, 1), 1),
    ("rows on the least rung", True, (3, 0, 2), 1),
    ("rows one past the least rung", True, (1, 4, 2), 1),
    ("rows on the second rung", True, (7, 5, 0), 1),
    ("rows one past the second rung", True, (2, 8, 6), 1),
    ("rows at the last slot: the wrap", True, (15, 3, 9), 1),
    ("rows one past the wrap", True, (4, 16, 11), 1),
    ("rows wrapped twice", True, (40, 3, 33), 1),
    # a piece of T tokens of one sequence: the reach is pos + T
    ("a piece below the least rung", False, (0,), 3),
    ("a piece on the least rung", False, (1,), 3),
    ("a piece one past the least rung", False, (1,), 4),
    ("a piece on the second rung", False, (3,), 5),
    ("a piece that ends at the wrap", False, (10,), 6),
    ("a piece across the wrap", False, (13,), 6),
    ("a piece wrapped twice", False, (37,), 5),
    ("one token of one sequence", False, (5,), 1),
]
_LADDER = [(f"{att}, {name}" + (", sink" if sink else ""), att, rows, pos, T, sink)
           for att in ("full", "window")
           for name, rows, pos, T in _REACHES
           for sink in ((False, True) if att == "window" else (False,))
           # a full layer's slab holds position p in slot p: no row past it
           if att == "window" or max(pos) + T <= _SLOTS]


@pytest.mark.parametrize("name,att,rows,pos,T,sink", _LADDER,
                         ids=[c[0] for c in _LADDER])
def test_attention_as_far_as_the_reach_equals_the_one_pass(name, att, rows,
                                                           pos, T, sink):
    """A prefix of the cache that covers the step's reach gives what the
    whole slab gives, to the order of float32 sums: full and window kinds,
    the reach below, on and one past a rung, a ring before its wrap, at it
    and wrapped twice, with and without a sink, values narrower than keys,
    rows of one token and a piece of several."""
    cfg, lp, q, k, v, p = _ladder_case(att, rows, pos, T, sink)
    reach = jnp.int32(max(pos) + T)
    got = jax.jit(lambda q, k, v, p, r: layer_plan._attend(
        cfg, att, lp, jnp.int32(_CIDX), r, rows=rows, least=_LEAST)(
        q, k, v, p))(q, k, v, p, reach)
    want = _one_pass(cfg, lp, att, rows, q, k, v, p)
    assert got.shape == want.shape and got.shape[-1] == _VHD
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-6)


@pytest.mark.parametrize("reach", range(1, 20))
def test_the_program_reads_the_rung_the_host_counts(reach, monkeypatch):
    """``slots_scored`` (what ``BatchSession._account_ring`` exports as
    ``dllama_kv_ring_scored_slots_total``) is the prefix the program reads:
    a NaN planted in the values at the rung's last slot reaches the output
    (a masked slot's weight is 0, and 0 x NaN is NaN: the slot was read),
    one planted in every slot from the rung on does not."""
    monkeypatch.setattr(attention, "LEAST_RUNG", _LEAST)
    rung = int(layer_plan.slots_scored(_SLOTS, np.int32(reach)))
    assert rung == min(r for r in (4, 8, 16) if r >= min(reach, _SLOTS))
    pos = (reach - 1, 0)
    cfg, lp, q, k, v, p = _ladder_case("window", True, pos, 1, False)
    run = jax.jit(lambda q, k, v, p, r: layer_plan._attend(
        cfg, "window", lp, jnp.int32(_CIDX), r, rows=True)(q, k, v, p))
    inside = v.at[_CIDX, :, rung - 1].set(jnp.nan)
    assert np.isnan(np.asarray(run(q, k, inside, p, jnp.int32(reach)))).all()
    if rung < _SLOTS:
        beyond = v.at[_CIDX, :, rung:].set(jnp.nan)
        got = run(q, k, beyond, p, jnp.int32(reach))
        np.testing.assert_allclose(
            np.asarray(got),
            np.asarray(_one_pass(cfg, lp, "window", True, q, k, v, p)),
            rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("att,rows", [("window", True), ("window", False),
                                      ("full", True), ("full", False)])
def test_a_cache_within_the_least_rung_lowers_as_it_always_did(att, rows):
    """The controls' guarantee: a cache of no more slots than the least rung
    (MiMo's ring of 256, every slab of 1024) has a ladder of one rung and
    lowers to the text of the call as it was before the ladder, no
    conditional and no layout constraint in it; a longer one lowers to a
    ``case``."""
    assert attention.LEAST_RUNG >= 1024 >= _SLOTS
    cfg, lp, q, k, v, p = _ladder_case(att, rows, (3, 9, 5), 2, True)
    cidx, reach = jnp.int32(_CIDX), jnp.int32(11)

    def new(q, k, v, p, r, least=None):
        return layer_plan._attend(cfg, att, lp, cidx, r, rows=rows,
                                  least=least)(q, k, v, p)

    def old(q, k, v, p, r):
        window = cfg.window if att == "window" else 0

        def attend(q, k_slab, v_slab, pos):
            with jax.named_scope(f"attention_{att}"):
                return gqa_attention(q, k_slab, v_slab, pos, window=window,
                                     sink=lp["sink"])

        with jax.named_scope("kv_slab_read"):
            slabs = (jax.lax.dynamic_index_in_dim(k, cidx, 0, keepdims=False),
                     jax.lax.dynamic_index_in_dim(v, cidx, 0, keepdims=False))
        if not rows:
            return attend(q, *slabs, p)
        return jax.vmap(lambda qb, ks, vs, pb: attend(qb[None], ks, vs, pb)[0])(
            q, *slabs, p)

    def text(fn, **kw):
        low = jax.jit(lambda *a: fn(*a, **kw)).lower(q, k, v, p, reach)
        return low.as_text().replace("jit__lambda_", "jit_fn")

    assert text(new) == text(old)
    assert "case" not in text(new) and "LayoutConstraint" not in text(new)
    assert "stablehlo.case" in text(new, least=_LEAST)


def test_a_uniform_models_attention_lowers_as_it_always_did():
    """``gqa_attention`` called as ``llama._rows_core`` calls it (no window,
    no ring) and ``llama._layer_slabs`` without ``slots`` trace to what they
    did: the new arguments change nothing where they are not given."""
    cfg, lp, q, k, v, p = _ladder_case("full", True, (3, 9, 5), 1, False)
    v = k  # a uniform model's values are as wide as its keys
    layer = jnp.int32(_CIDX)

    def new(q, k, v, p):
        return jax.vmap(lambda qb, ks, vs, pb: gqa_attention(
            qb[None], ks, vs, pb)[0])(q, *llama._layer_slabs(k, v, layer), p)

    def old(q, k, v, p):
        with jax.named_scope("kv_slab_read"):
            slabs = (jax.lax.dynamic_index_in_dim(k, layer, 0, keepdims=False),
                     jax.lax.dynamic_index_in_dim(v, layer, 0, keepdims=False))
        return jax.vmap(lambda qb, ks, vs, pb: gqa_attention(
            qb[None], ks, vs, pb, window=0, sink=None, ring=0)[0])(
            q, *slabs, p)

    texts = [jax.jit(f).lower(q, k, v, p).as_text().replace(
        f"jit_{f.__name__}", "jit_fn") for f in (new, old)]
    assert texts[0] == texts[1] and "case" not in texts[0]


# ---------------------------------------------------------------------------
# launches that run ahead of their collection: pieces not waited for
# ---------------------------------------------------------------------------

def _preset(name: str) -> tuple:
    """(config, float32 params, conf) of a tiny preset with a layer plan."""
    if name == "tiny-mimo":
        conf = load_conf()
        params = dense_params(make_planes(conf), conf)
    elif name == "tiny-command-a":
        from tests import test_command_a as m
        conf = m.load_conf()
        params = m.dense_params(m.make_planes(conf), conf)
    else:
        from tests import test_deepseek_v3 as m
        conf = m.load_conf()
        params = m.dense_params(families.load(conf).make_planes(conf, 7), conf)
    return families.load(conf).model_config(conf, conf["server"]), params, conf


@pytest.mark.parametrize("preset", ["tiny-mimo", "tiny-command-a",
                                    "tiny-gigachat"])
def test_a_plan_served_ahead_streams_what_the_synchronous_loop_streams(
        preset):
    """A plan's prompts take standalone pieces, which a session that runs
    ahead does not wait for: the piece, its insert and the decode launch
    queue on the device in that order, and a row whose prefix ends in the
    piece decodes in that launch (first tokens one tick later than in the
    synchronous loop, never more: ``both``). Greedy and sampled rows, a
    prompt past the ring, pools that grow and rows that migrate."""
    from tests.test_continuous_batching import both

    cfg, params, conf = _preset(preset)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(259, conf["vocab_size"], size=n).tolist()
               for n in (5, 12, 30, 20)]
    sampled = SamplerConfig(temperature=0.8, topp=0.9, seed=2**31 - 1)
    greedy = SamplerConfig(temperature=0.0)
    arrivals = [(0, prompts[0], 10, greedy, ()), (0, prompts[1], 9, sampled, ()),
                (2, prompts[2], 8, greedy, ()), (3, prompts[3], 14, sampled, ())]
    want, got, _, why, reg = both(
        arrivals, cfg=cfg, params=params, max_batch=4, chunk=4,
        bucket_kv=True, min_bucket=16, prefill_chunk=8)
    assert got == want and set(why.values()) == {"length"}
    assert all(len(got[i]) == a[2] for i, a in enumerate(arrivals))
    snap = reg.snapshot()
    pieces = snap["dllama_prefill_tokens_total"]["values"]
    assert sum(v["value"] for v in pieces) == sum(len(p) - 1 for p in prompts)
    ahead = {v["labels"]["state"]: v["value"]
             for v in snap["dllama_decode_ahead_total"]["values"]}
    assert ahead.get("queued", 0) > 0
    assert snap["dllama_kv_migrations_total"]["values"][0]["value"] > 0
