"""The ``llama`` family's plain reference against the program at a tiny size
on the CPU, and the control: the reference in the lower precision comes out
as not correct."""
import json
import os

import numpy as np
import pytest

from conftest import BENCH


def conf(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module", params=["tiny-rehearsal", "tiny-dense"])
def model_and_planes(request):
    from families.llama import weights

    c = conf(request.param)
    return c, weights.make_planes(c, seed=2 ** 31 + 77)


def test_planes_are_the_same_for_a_seed_and_never_win_a_fixed_piece(model_and_planes):
    import jax

    from families.llama import weights

    c, planes = model_and_planes
    again = weights.make_planes(c, seed=2 ** 31 + 77)
    assert all(bool((a == b).all()) for a, b in
               zip(jax.tree.leaves(planes), jax.tree.leaves(again)))
    other = weights.make_planes(c, seed=78)
    assert not bool((planes["wcls"]["w"] == other["wcls"]["w"]).all())
    assert float(np.abs(np.asarray(planes["wcls"]["s"])[:, :259]).max()) == 0.0
    assert float(np.asarray(planes["wcls"]["s"])[:, 259:].min()) >= 0.0


def test_dequant_restates_the_programs_layout(model_and_planes):
    import families
    from families.llama import reference
    from dllama_tpu.ops import qmatmul

    c, planes = model_and_planes
    params = families.load(c).wrap_planes(planes, c)
    for name in ("wqkv", "wo"):
        want = qmatmul.dequantize(params["layers"][name])
        got = np.asarray(reference.dequant_q40(planes["layers"][name],
                                               c["hidden_size"]))
        np.testing.assert_array_equal(got, want)


def test_reference_agrees_with_the_programs_forward(model_and_planes):
    import jax.numpy as jnp

    import families
    from families.llama import reference
    from dllama_tpu.models import llama

    c, planes = model_and_planes
    fam = families.load(c)
    cfg = fam.model_config(c, c["server"])
    params = llama.fuse_qkv_ffn(fam.wrap_planes(planes, c))
    rng = np.random.default_rng(0)
    seq = rng.integers(259, c["vocab_size"], size=40).tolist()
    logits, _ = llama.forward(cfg, params, llama.rope_tables(cfg),
                              jnp.asarray(seq, jnp.int32),
                              llama.init_cache(cfg, jnp.float32), jnp.int32(0))
    ref = reference.logits_at(planes, c, [seq], [list(range(40))])[0]
    got = np.asarray(logits)
    assert np.abs(got - ref).max() <= 1e-2 * np.abs(ref).max()  # the no-subtract q40 kernel rounds at 7.6e-3
    assert (got.argmax(axis=1) == ref.argmax(axis=1)).mean() >= 0.95


def test_control_in_lower_precision_comes_out_not_correct(model_and_planes):
    """The program's place is taken by the reference itself (every gap 0:
    correct), by the reference in float8 activations (the control: not
    correct under the configuration's rule, on each of three seeds) and by
    the reference in bfloat16 (the witness: correct)."""
    import gapstats
    from families.llama import reference
    from families.llama import weights

    c, _ = model_and_planes
    rule = c["correct"]
    for seed in (11, 12, 13):
        planes = weights.make_planes(c, seed=seed)
        rng = np.random.default_rng(seed)
        samples = []
        for _ in range(8):
            prompt = rng.integers(259, c["vocab_size"], size=24).tolist()
            seq = list(prompt)
            for _ in range(48):  # greedy continuation by the reference
                lg = reference.logits_at(planes, c, [seq], [[len(seq) - 1]])[0]
                seq.append(int(lg[0].argmax()))
            samples.append({"prompt": prompt, "served": seq[24:]})
        res = reference.compare(planes, c, samples, stand_ins={
            "control": reference.CONTROL, "witness": reference.WITNESS})
        others = {"control": res["control_gaps"], "witness": res["witness_gaps"]}
        assert gapstats.passes(gapstats.checks(res["gaps"], rule, others))
        rows = gapstats.checks(res["control_gaps"], rule, others)
        assert not gapstats.passes(rows), seed
        assert dict((n, v) for n, v, _ in rows)["gap_vs_control_ratio"] == 1.0
        assert gapstats.passes(gapstats.checks(res["witness_gaps"], rule, others)), seed


@pytest.mark.parametrize("name", ["mistral-7b-v0.3-q40", "mixtral-8x7b-d10-q40"])
def test_the_cells_rule_fails_one_made_up_token_a_request(name):
    """The rule the real cells carry, over gaps as a run reads them: 1,100
    tokens of 24 sampled requests, of which the program flips 60 near-ties
    and the control 300, then one made-up token (4 spreads down, as half of
    them still are at the worst) in each request: 2 % of the tokens, which a
    share of the control's loss overlooks by construction (each gap counts
    up to its cap) and the rule's second number has to fail."""
    import gapstats

    rule = conf(name)["correct"]
    kinds = sorted(spec["of"] for spec in rule.values())
    assert kinds == ["count_over", "share_of"] or kinds == ["max", "share_of"]
    sound = [0.0] * 1040 + [0.02] * 60
    stand = lambda extra: {"control": [0.0] * 800 + [0.15] * 300 + [0.0] * extra,
                           "witness": [0.0] * 1099 + [0.01] + [0.0] * extra}
    assert gapstats.passes(gapstats.checks(sound, rule, stand(0)))
    for made_up in ([4.0] * 24, [4.0] * 12 + [1.0] * 12):
        rows = gapstats.checks(sound + made_up, rule, stand(24))
        assert not gapstats.passes(rows)
        failed = {n for n, v, lim in rows if v > lim}
        assert failed and "gap_vs_control_ratio" not in failed
    # and the control in the program's place fails the share, whatever else
    rows = gapstats.checks(stand(0)["control"], rule, stand(0))
    assert {n for n, v, lim in rows if v > lim} >= {"gap_vs_control_ratio"}


def test_gap_statistics():
    import gapstats

    g = [0.0] * 90 + [1.0] * 9 + [3.0]
    assert gapstats.stat(g, {"of": "max"}) == 3.0
    assert gapstats.stat(g, {"of": "count_over", "over": 0.5}) == 10
    assert gapstats.stat(g, {"of": "quantile", "q": 0.9}) == pytest.approx(
        float(np.quantile(g, 0.9)))
    assert gapstats.stat([], {"of": "max"}) == gapstats.MISSING  # fails any limit
    share = {"of": "share_of", "against": "control", "cap": 0.5}
    masked = dict(share, where_zero="witness")
    # only the positions the witness still gets right count: the 3.0 goes
    assert gapstats.stat(g, masked, {"control": [1.0] * 100,
                                     "witness": [0.0] * 99 + [0.2]}) == pytest.approx(
        (9 * 0.5) / 99 / 0.5)
    assert gapstats.stat(g, masked, {"control": [1.0] * 100}) == gapstats.MISSING
    assert gapstats.needs({"a": masked}) == ["control", "witness"]
    assert gapstats.stat(g, share, {"control": [1.0] * 100}) == pytest.approx(
        (9 * 0.5 + 0.5) / 100 / 0.5)  # each gap counts up to the cap
    assert gapstats.stat(g, share) == gapstats.MISSING  # no control read
    assert gapstats.stat(g, share, {"control": [0.0] * 100}) == gapstats.MISSING
    assert gapstats.stat([0.0] * 5, share, {"control": [0.0] * 5}) == 0.0
    assert gapstats.needs({"a": share, "b": {"of": "max"}}) == ["control"]
    s = gapstats.summary(g)
    assert s["tokens"] == 100 and s["argmax_share"] == 0.9 and s["over_2.5_spreads"] == 1
