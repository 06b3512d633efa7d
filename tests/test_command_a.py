"""Command A+ (``arch: cohere2_moe``: a parallel block behind one LayerNorm,
window layers that rotate interleaved pairs and full layers that rotate
nothing, sigmoid-routed experts without a bias of which a share is held,
always-on shared experts beside them, a head tied to the embedding), on the
CPU at a tiny preset that keeps every mechanism, against the family's plain
reference (``benchmarks/families/command_a/reference.py``: one full causal
forward, float32, no cache).

The preset (``benchmarks/configs/tiny-command-a.json``): two periods of
W W W F, a window of 8 in a ring of 16, 16 query heads on 2 KV heads of 16,
4 shared experts, 4 of 16 routed experts held, top 4, a tied table of 512
rows. The layers' planes' scales are multiplied by 8, so that a 64-wide
model's projections are as large as the published widths' (scores that
spread, windows that matter); the table keeps its scale, since it is made
once from the classifier's planes.

Tolerance: the program in float32 and the reference differ by the order of
float32 sums (measured 3e-6 of the logits' spread at worst); ``TOL`` is 2e-4
of that spread, two orders above it and one below what bfloat16 activations
read (test_bfloat16_activations_fail_the_tolerance).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import families
from benchmarks.families.command_a import reference, shapes, weights
from dllama_tpu import observability
from dllama_tpu.models import layer_plan, llama, moe
from dllama_tpu.runtime.generate import Engine
from dllama_tpu.runtime.sampler import SamplerConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4  # of the reference logits' standard deviation
LENGTHS = (5, 12, 30, 41)  # prompt tokens: before and beyond the ring's 16
STEPS = 6


def load_conf(**changes) -> dict:
    with open(os.path.join(ROOT, "benchmarks/configs/tiny-command-a.json")) as f:
        return dict(json.load(f), **changes)


def make_planes(conf: dict, seed: int = 7) -> dict:
    planes = families.load(conf).make_planes(conf, seed)

    def louder(path, a):
        return a * 8.0 if path[-1].key in ("s", "s2") else a

    return dict(planes, layers=jax.tree_util.tree_map_with_path(
        louder, planes["layers"]))


def dense_params(planes: dict, conf: dict, tied: bool = True) -> dict:
    """The planes as float32 matrices in the program's tree: what the
    reference dequantises, so that the two sides multiply the same numbers.
    ``tied``: no ``wcls`` leaf, the head is the table itself."""
    def leaf(name, v):
        if isinstance(v, dict) and set(v) == {"w", "s", "s2"}:
            return reference.dequant_q40(v, weights.logical_k(name, conf))
        return v

    out = {k: leaf(k, v) for k, v in planes.items()
           if k != "layers" and not (tied and k == "wcls")}
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
    assert cfg.plan_text() == "W.E*3 F.E W.E*3 F.E"
    assert cfg.plan_kinds == (("window", "moe"), ("full", "moe"))
    assert (cfg.norm, cfg.block, cfg.router) == ("layer", "parallel", "sigmoid")
    assert cfg.rope_attention == ("window",) and cfg.rope_style == "interleaved"
    assert (cfg.ring_slots, cfg.max_prefill_piece) == (16, 9)
    assert (cfg.n_experts, cfg.n_experts_held, cfg.expert_first) == (16, 4, 4)
    assert (cfg.shared_dim, cfg.shared_scale) == (256, 0.25)
    assert cfg.tied_embedding and cfg.n_heads // cfg.n_kv_heads == 8
    assert max(LENGTHS) + STEPS > 2 * cfg.ring_slots  # the ring wraps twice
    # full layers have no tables: they rotate nothing
    assert sorted(llama.rope_tables(cfg)) == ["wcos", "wsin"]
    # one norm a layer: the stacks hold no second one, no bias, no sink
    assert all(sorted(set(st) & {"rms_att", "rms_ffn", "moe_bias", "sink"})
               == ["rms_att"] for st in tiny["planes"]["layers"].values())


def test_the_table_is_made_once(tiny):
    """The tie is exact: the float32 lookup table is the classifier's planes
    dequantised, and the byte tokens' rows are quiet but not zero."""
    planes, conf = tiny["planes"], tiny["conf"]
    table = np.asarray(planes["embedding"])
    head = np.asarray(reference.dequant_q40(planes["wcls"], conf["hidden_size"]))
    np.testing.assert_array_equal(table, head.T)
    quiet, loud = np.abs(table[:259]).mean(), np.abs(table[259:]).mean()
    assert 0 < quiet < 0.2 * loud


@pytest.mark.parametrize("i", range(len(LENGTHS)))
def test_solo_prefill_in_pieces_then_decode_matches_reference(tiny, i):
    seq, ref = tiny["seqs"][i], tiny["ref"][i]
    got, _ = solo_logits(tiny["cfg"], tiny["params"], seq, LENGTHS[i])
    assert worst(got, ref) < TOL


def test_quantized_planes_through_the_kernels_stay_near_reference(tiny):
    """The planes as the benchmark serves them (``QuantTensor``s through the
    q40 kernels, here in interpret mode, stacked by kind and steered by the
    scalar-prefetched layer index; the head through the table's own planes):
    the kernels multiply in bfloat16, so the logits stand 0.06-0.09 of a
    spread from the float32 reference at most positions and 0.21 at the
    worst (measured; ``wqkv`` and ``wo`` bring 0.10 each, the 16 heads'
    scores are loud here, the experts 0.004, the shared planes 0.012, the
    head 0.03); a wrong stack index, plane or column split reads 1 and
    more."""
    conf = tiny["conf"]
    params = families.load(conf).wrap_planes(tiny["planes"], conf)
    got, _ = solo_logits(tiny["cfg"], params, tiny["seqs"][2][:36], LENGTHS[2])
    assert TOL < worst(got, tiny["ref"][2][:36]) < 0.4


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


def _sample(text: str, series: str) -> float:
    line = next(l for l in text.splitlines() if l.startswith(series + " "))
    return float(line.rsplit(" ", 1)[1])


def test_batch_session_serves_the_references_greedy_tokens(tiny):
    """Through ``BatchSession`` (chunked admission, the pooled decode
    program with ``live``, the slab pool): every served token is the
    reference's argmax on the sequence served, and the counters add up:
    the four numbers of ``picks`` and the two of the rings."""
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
    held = _sample(text, 'dllama_moe_picks_total{held="1"}')
    away = _sample(text, 'dllama_moe_picks_total{held="0"}')
    steps = _sample(text, "dllama_moe_layer_steps_total")
    active = _sample(text, "dllama_moe_active_experts_total")
    reads = _sample(text, "dllama_moe_expert_reads_total")
    k, layers = cfg.n_active_experts, cfg.plan_count(ffn="moe")
    assert layers == 8 and steps > 0 and steps % (4 * layers) == 0
    assert (held + away) % (4 * layers * k) == 0 and held + away > 0
    assert 0 < active <= held and active <= steps * cfg.n_experts_held
    # float32 matrices take the all-experts branch: every held expert read
    assert reads == steps * cfg.n_experts_held
    # the rings: every launch scores the whole ring for its live rows, of
    # which at most a window's worth a row holds a position the query sees
    scored = _sample(text, "dllama_kv_ring_scored_slots_total")
    seen = _sample(text, "dllama_kv_ring_live_slots_total")
    row_steps = (held + away) / (layers * k)  # live rows x steps
    assert scored == row_steps * cfg.ring_slots * cfg.plan_count("window")
    assert 0 < seen <= row_steps * cfg.window * cfg.plan_count("window")
    # every row here is past the window: each step sees exactly a window
    assert min(LENGTHS[i] for i in (2, 3)) > cfg.window
    assert seen < scored * cfg.window / cfg.ring_slots + 1
    assert 'dllama_kv_resident_bytes{kind="window"}' in text
    sess.close()


@pytest.mark.parametrize("which,wrapped", [((0,), "no"), ((0, 1), "some"),
                                           ((2, 3), "every"), ((0, 3), "some")],
                         ids=["one row before the wrap",
                              "two rows before the wrap",
                              "both rows wrapped", "one row of two wrapped"])
def test_ring_counter_counts_the_rungs_the_program_took(tiny, monkeypatch,
                                                        which, wrapped):
    """With the least rung below the ring's 16 slots (4: rungs 4, 8, 16; the
    pool's slab of 32 has 4, 8, 16, 32) the served tokens are still the
    reference's argmax, and ``dllama_kv_ring_scored_slots_total`` is what the
    program scored, recomputed here from every launch's live positions: under
    the whole ring a row a step a layer while no live row has reached the
    ring's end, the whole ring once one has; the live slots are counted as
    ever."""
    from dllama_tpu.ops import attention
    from dllama_tpu.runtime.generate import BatchSession

    monkeypatch.setattr(attention, "LEAST_RUNG", 4)
    launches = []
    account = BatchSession._account_ring
    monkeypatch.setattr(
        BatchSession, "_account_ring",
        lambda self, live_pos: (launches.append(np.array(live_pos)),
                                account(self, live_pos))[1])
    cfg, conf = tiny["cfg"], tiny["conf"]
    reg = observability.MetricsRegistry()
    eng = Engine(cfg, tiny["params"], SamplerConfig(temperature=0.0),
                 cache_dtype=jnp.float32, metrics=reg)
    chunk, budget = 2, 6
    sess = eng.batch_session(3, chunk=chunk, bucket_kv=True, min_bucket=32,
                             prefill_chunk=8)
    prompts = [tiny["seqs"][i][:LENGTHS[i]] for i in which]
    handles = [sess.admit_begin(p, budget) for p in prompts]
    served = {h: [] for h in handles}
    for _ in range(40):
        sess.prefill_step()
        for h, toks in sess.step_chunk().items():
            served[h].extend(toks)
        if all(sess.is_done(h) for h in handles):
            break
    assert all(len(served[h]) == budget for h in handles)
    seqs = [p + served[h] for p, h in zip(prompts, handles)]
    ref = reference.logits_at(
        tiny["planes"], conf, seqs,
        [[len(p) - 1 + j for j in range(budget)] for p in prompts])
    for h, lg in zip(handles, ref):
        assert lg.argmax(axis=1).tolist() == served[h]
    layers, ring = cfg.plan_count("window"), cfg.ring_slots
    want = seen = row_steps = 0
    for live_pos in launches:
        for s in range(chunk):
            reach = min(int(live_pos.max()) + s + 1, ring)
            want += len(live_pos) * min(r for r in (4, 8, 16) if r >= reach)
            seen += int(np.minimum(live_pos + s + 1, cfg.window).sum())
        row_steps += len(live_pos) * chunk
    text = reg.render()
    scored = _sample(text, "dllama_kv_ring_scored_slots_total")
    assert scored == want * layers and launches
    assert _sample(text, "dllama_kv_ring_live_slots_total") == seen * layers
    whole = row_steps * ring * layers
    at_the_end = [bool(p.max() + chunk >= ring) for p in launches]
    assert {"no": not any(at_the_end), "every": all(at_the_end),
            "some": any(at_the_end) and not all(at_the_end)}[wrapped]
    assert (scored == whole) if wrapped == "every" else (scored < whole)
    sess.close()


def test_a_stale_longer_row_does_not_widen_the_reach(tiny, monkeypatch):
    """A pool row that is not live may hold a position left by an earlier
    request: ``forward_batched`` counts live rows only. Values poisoned from
    slot 4 on leave the live rows' logits (positions 2 and 3: rung 4) as
    they were beside a row that stands at 13 and is not live; counted as
    live, that row takes the step to the whole ring and the poison shows."""
    from dllama_tpu.ops import attention

    monkeypatch.setattr(attention, "LEAST_RUNG", 4)
    cfg = tiny["cfg"]
    eng = Engine(cfg, tiny["params"], SamplerConfig(temperature=0.0),
                 cache_dtype=jnp.float32, metrics=None)
    rng = np.random.default_rng(5)
    cache = {k: jnp.asarray(rng.standard_normal(a.shape).astype(np.float32))
             for k, a in llama.init_batch_cache(cfg, 3, jnp.float32,
                                                seq_len=32).items()}
    poisoned = dict(cache, **{k: cache[k].at[:, :, 4:].set(jnp.nan)
                              for k in ("v", "wv")})
    step = jax.jit(lambda t, c, ps, lv: llama.forward_batched(
        cfg, eng.params, eng.rope, t, c, ps, live=lv)[0])
    toks = jnp.asarray([300, 301, 302], jnp.int32)
    pos = jnp.asarray([2, 13, 3], jnp.int32)
    live = jnp.asarray([True, False, True])
    clean = np.asarray(step(toks, cache, pos, live))
    got = np.asarray(step(toks, poisoned, pos, live))
    assert np.isfinite(got[[0, 2]]).all()
    np.testing.assert_allclose(got[[0, 2]], clean[[0, 2]], rtol=1e-4, atol=1e-5)
    assert np.isnan(np.asarray(step(toks, poisoned, pos,
                                    jnp.ones((3,), jnp.bool_)))[[0, 2]]).any()


def test_bfloat16_activations_fail_the_tolerance(tiny):
    conf = tiny["conf"]
    cfg = families.load(conf).model_config(
        conf, dict(conf["server"], dtype="bfloat16"))
    got, _ = solo_logits(cfg, tiny["params"], tiny["seqs"][2], LENGTHS[2])
    assert worst(got, tiny["ref"][2]) > 10 * TOL


@pytest.mark.parametrize("mechanism", [
    "parallel", "layernorm", "nope", "interleaved", "average", "sigmoid",
    "tied", "window", "expert"])
def test_reference_without_one_mechanism_fails_the_comparison(tiny, mechanism):
    """Each mechanism carries weight: the program, which has it, is far from
    a reference that lacks it. An untied reference reads a head of its own
    (``wcls`` drawn afresh, as an untied checkpoint would bring one)."""
    seq = tiny["seqs"][3]
    planes = tiny["planes"]
    if mechanism == "tied":
        planes = dict(planes, wcls=make_planes(tiny["conf"], seed=8)["wcls"])
    lacking = reference.logits_at(planes, tiny["conf"], [seq],
                                  [list(range(len(seq)))],
                                  without=mechanism)[0]
    got, _ = solo_logits(tiny["cfg"], tiny["params"], seq, LENGTHS[3])
    assert worst(got, tiny["ref"][3]) < TOL
    assert worst(got, lacking) > 10 * TOL


def test_shares_and_the_shared_part_once_add_up_to_the_uncut_layer():
    """The share ties to the model: four processes, each holding a quarter
    of the routed experts and routing over all of them, give routed parts
    whose sum, with the always-on part counted ONCE (every chip computes it
    alike), is the reference's uncut FFN of the layer."""
    conf = load_conf(num_experts=16, share={"expert_first": 0})
    planes = make_planes(conf)
    m = dict(reference.sizes(conf))
    stack = planes["layers"]["window_moe"]
    lp_ref = jax.tree.map(lambda a: a[1], stack)
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 9, m["D"]), jnp.float32)
    always = reference.shared(h, lp_ref, m, None, None)
    whole = reference.experts(h, lp_ref, m, (0, 16), None, None) + always

    lp_all = jax.tree.map(lambda a: a[1],
                          dense_params(planes, conf)["layers"]["window_moe"])
    total = jnp.zeros_like(h)
    for first in (0, 4, 8, 12):
        share = load_conf(share={"expert_first": first})
        cfg = families.load(share).model_config(share, share["server"])
        lp = dict(lp_all, **{n: lp_all[n][first:first + 4]
                             for n in ("moe_upgate", "moe_down")})
        both = moe.moe_ffn(cfg, lp, h.reshape(18, -1)).reshape(h.shape)
        mine = reference.experts(h, dict(lp_ref, **{
            n: jax.tree.map(lambda a: a[first:first + 4], lp_ref[n])
            for n in ("moe_upgate", "moe_down")}), m, (first, 4), None, None)
        # what this process returns: its routed part and the shared part
        assert float(jnp.abs(both - (mine + always)).max()) \
            < 1e-5 * float(whole.std())
        part = both - moe.shared_ffn(cfg, lp, h.reshape(18, -1)).reshape(h.shape)
        assert float(jnp.abs(part).max()) > 0
        total = total + part
    total = total + always
    assert float(jnp.abs(total - whole).max()) < 1e-5 * float(whole.std())
    # the four always-on experts are one gated FFN of their summed width
    cfg = families.load(conf).model_config(conf, conf["server"])
    one = moe.shared_ffn(cfg, lp_all, h.reshape(18, -1)).reshape(h.shape)
    assert float(jnp.abs(one - always).max()) < 1e-5 * float(always.std())


def test_window_cache_bytes_do_not_grow_with_context(tiny):
    cfg = tiny["cfg"]
    short = llama.init_batch_cache(cfg, 4, jnp.float32, seq_len=32)
    long = llama.init_batch_cache(cfg, 4, jnp.float32, seq_len=64)
    a, b = layer_plan.kv_resident_bytes(short), layer_plan.kv_resident_bytes(long)
    assert a["window"] == b["window"] > 0
    assert b["full"] == 2 * a["full"] > 0
    assert short["wk"].shape == short["wv"].shape == (6, 4, 16, 2, 16)
    assert long["k"].shape == (2, 4, 64, 2, 16)
    want = shapes.kv_resident_bytes(tiny["conf"], 4, 64, 16, cache_bytes=4)
    assert b == {k: int(v) for k, v in want.items()}


def test_what_is_not_built_for_a_plan_refuses_in_one_line(tiny):
    cfg, params = tiny["cfg"], tiny["params"]
    eng = Engine(cfg, params, SamplerConfig(temperature=0.0),
                 cache_dtype=jnp.float32, metrics=None)
    with pytest.raises(ValueError, match=r"--kv-pages.*layer plan W\.E\*3 F\.E"):
        eng.batch_session(2, kv_pages=8)
    with pytest.raises(ValueError, match="--spec-draft"):
        next(eng.generate_spec([300, 301], 4))
    with pytest.raises(ValueError, match="forward_train"):
        llama.forward_train(cfg, params, jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(ValueError, match="--tp > 1"):
        Engine(cfg, params, mesh=object(), metrics=None)
    with pytest.raises(ValueError, match="at most 9 tokens a piece"):
        llama.forward(cfg, eng.params, eng.rope, jnp.zeros((16,), jnp.int32),
                      eng.new_cache(), jnp.int32(0))


def test_what_the_block_needs_refuses_without_a_plan():
    """A parallel block, a LayerNorm and shared experts are the plan path's:
    a uniform model that names one is refused in one line."""
    from dllama_tpu.models.config import ModelConfig

    base = dict(arch="llama", dim=64, hidden_dim=128, n_layers=2, n_heads=4,
                n_kv_heads=2, vocab_size=96, seq_len=32, head_size=16,
                kv_dim=32)
    for field in ({"block": "parallel"}, {"norm": "layer"}, {"shared_dim": 64}):
        with pytest.raises(ValueError, match="layer_plan") as e:
            ModelConfig(**base, **field)
        assert "\n" not in str(e.value)
    for field in ({"block": "both"}, {"norm": "batch"}, {"router": "top"}):
        with pytest.raises(ValueError, match="unknown"):
            ModelConfig(**base, **field)


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
