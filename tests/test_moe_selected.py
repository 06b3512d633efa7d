"""The expert layer's selected path (``moe._moe_decode_selected``): how many
expert plane sets a small-T step reads is a TRACED number, the distinct held
experts that the step's counted rows picked, and ``cap = min(E, T * k)`` only
its static bound.

Two presets, quantized (the q40 kernels in interpret mode), each against the
all-experts combine through the SAME kernels (``moe_ffn`` on a 3-D input
takes that branch whatever the shapes):

* ``tiny-mimo`` widened to a router over 64 experts of which the process
  holds 8 (sigmoid + bias router, fused up|gate planes): 8 rows x 4 picks
  reach about 4 of the 8;
* a uniform Mixtral-shaped model, 32 experts all held, top 2, fused and
  unfused planes.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import families
from dllama_tpu.models import llama, moe
from dllama_tpu.models.config import ModelConfig
from dllama_tpu.ops import qmatmul
from tests.test_layer_plan import load_conf, make_planes

T = 8
#: what tests/test_quant_forward.py::test_moe_decode_selected_matches_dense_combine uses
TOL = dict(rtol=2e-4, atol=2e-4)
LIVE = {
    "none": None,
    "all": np.ones(T, bool),
    "some": np.array([1, 1, 0, 1, 0, 0, 1, 1], bool),
    "one_row": np.arange(T) == 5,
    "no_row": np.zeros(T, bool),
}
UNIFORM = ModelConfig(
    arch="mixtral", dim=64, hidden_dim=128, n_layers=2, n_heads=4,
    n_kv_heads=4, vocab_size=64, seq_len=32, head_size=16, kv_dim=64,
    n_experts=32, n_active_experts=2, rope_style="half", dtype="float32")


def wide_mimo_conf() -> dict:
    """tiny-mimo with a router over 64 experts, the fourth eighth held:
    T * k = 32 < 64, so 8 rows take the selected path, cap = 8."""
    return load_conf(n_routed_experts=8,
                     published={"n_routed_experts": 64, "vocab_size": 1024},
                     share={"expert_first": 24})


def mimo_params(conf: dict):
    """-> (cfg, the planes as the benchmark serves them: ``QuantTensor``s),
    with test_layer_plan's louder scales: outputs of order 1."""
    family = families.load(conf)
    return (family.model_config(conf, conf["server"]),
            family.wrap_planes(make_planes(conf), conf))


def layer_view(stack: dict, layer: int) -> dict:
    """What the layer scan hands the FFN: quantized planes stay stacked (the
    kernels' scalar prefetch picks the layer), dense leaves are indexed."""
    return {k: (v if isinstance(v, qmatmul.QuantTensor) else v[layer])
            for k, v in stack.items()}


@pytest.fixture(scope="module")
def presets():
    cfg, params = mimo_params(wide_mimo_conf())
    assert (cfg.n_experts, cfg.n_experts_held, cfg.expert_first) == (64, 8, 24)
    out = {"mimo": (cfg, layer_view(params["layers"]["window_moe"], 1), 1)}
    unfused = llama.quantize_params(
        llama.random_params(UNIFORM, seed=5), "q40")
    fused = llama.fuse_qkv_ffn(unfused)
    assert "moe_upgate" in fused["layers"] and "moe_up" in unfused["layers"]
    out["uniform_fused"] = (UNIFORM, layer_view(fused["layers"], 1), 1)
    out["uniform_unfused"] = (UNIFORM, layer_view(unfused["layers"], 1), 1)
    return out


def run_both(cfg, lp, layer, xb, live):
    """-> (selected path's out and reads, all-experts out)"""
    sel = jax.jit(lambda x, lv: moe.moe_ffn_counted(
        cfg, lp, x, jnp.int32(layer), live=lv))
    every = jax.jit(lambda x: moe.moe_ffn(cfg, lp, x[None], jnp.int32(layer)))
    out, reads = sel(xb, None if live is None else jnp.asarray(live))
    return np.asarray(out), int(reads), np.asarray(every(xb)[0])


def rows(cfg) -> jnp.ndarray:
    """Eight rows; a plan's router weights are small beside its correction
    bias, so its rows are louder: their picks then differ (five of the
    eight rows pick among the held experts, four of these between them);
    the uniform preset's are louder still, for outputs well over ``TOL``."""
    scale = 4.0 if cfg.layer_plan else 16.0
    return jnp.asarray(scale * np.random.default_rng(6).standard_normal(
        (T, cfg.dim)), jnp.float32)


def distinct_held(cfg, lp, xb, live) -> int:
    topi, _ = moe.route_topk(cfg, lp["moe_router"], xb, lp.get("moe_bias"))
    mask = jnp.ones(T, bool) if live is None else jnp.asarray(live)
    return int(moe.pick_counts(cfg, topi, mask)[2])


@pytest.mark.parametrize("live", list(LIVE))
@pytest.mark.parametrize("preset", ["mimo", "uniform_fused",
                                    "uniform_unfused"])
def test_traced_count_path_matches_all_experts_combine(presets, preset, live):
    """Counted rows get every held expert they picked, to the tolerance of
    the T == 1 test; a row that does not count gets a zero expert part; the
    planes read are the distinct held experts the counted rows picked."""
    cfg, lp, layer = presets[preset]
    xb, mask = rows(cfg), LIVE[live]
    got, reads, want = run_both(cfg, lp, layer, xb, mask)
    counted = np.ones(T, bool) if mask is None else mask
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[counted], want[counted], **TOL)
    assert not got[~counted].any()
    assert np.abs(want).max() > 0.05  # the tolerance means something
    assert reads == distinct_held(cfg, lp, xb, mask)
    assert reads <= min(cfg.n_experts_held, T * cfg.n_active_experts)
    if live in ("none", "all"):
        assert reads > 1  # several experts were summed
    if live == "no_row":
        assert reads == 0


@pytest.mark.parametrize("live", ["none", "some"])
def test_step_in_which_no_live_row_picks_a_held_expert(presets, live):
    """``n == 0`` with rows that count: the correction bias sends every pick
    to experts held elsewhere; no trip, a finite zero part."""
    cfg, lp, layer = presets["mimo"]
    bias = np.zeros(cfg.n_experts, np.float32)
    bias[cfg.expert_first:cfg.expert_first + cfg.n_experts_held] = -1e9
    away = dict(lp, moe_bias=jnp.asarray(bias))
    got, reads, want = run_both(cfg, away, layer, rows(cfg), LIVE[live])
    assert reads == 0
    assert np.isfinite(got).all() and not got.any() and not want.any()


def test_one_trip_per_distinct_expert_even_when_every_row_picks_the_same(
        presets):
    """The trip count is the DISTINCT experts: eight copies of one row read
    that row's k experts once, not 8 x k."""
    _, lp, layer = presets["uniform_unfused"]
    xb = jnp.tile(rows(UNIFORM)[:1], (T, 1))
    out, reads = jax.jit(lambda x: moe.moe_ffn_counted(
        UNIFORM, lp, x, jnp.int32(layer)))(xb)
    assert int(reads) == UNIFORM.n_active_experts
    np.testing.assert_allclose(np.asarray(out), np.asarray(out)[:1].repeat(T, 0),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# the rule of engagement reads shapes and cfg only
# ---------------------------------------------------------------------------

def _expert_planes(n_held: int, dim: int, hidden: int) -> dict:
    rng = np.random.default_rng(3)

    def stack(k, o):
        one = qmatmul.quantize_tensor(
            0.05 * rng.standard_normal((k, o)).astype(np.float32), "q40")
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (1, n_held, *a.shape)), one)

    return {"moe_upgate": stack(dim, 2 * hidden), "moe_down": stack(hidden, dim)}


#: (rows, k, experts the router scores, held here, router) -> selected path?
SHAPES = {
    "cell 3 pooled step": ((8, 8, 256, 32, "sigmoid_bias"), True),
    "cell 3 solo step": ((1, 8, 256, 32, "sigmoid_bias"), True),
    "cell 3 prefill piece of 64": ((64, 8, 256, 32, "sigmoid_bias"), False),
    "mixtral pooled step with riders": ((24, 2, 8, 8, "softmax"), False),
    "mixtral pooled step of PR 27": ((8, 2, 8, 8, "softmax"), False),
    "mixtral solo step": ((1, 2, 8, 8, "softmax"), True),
    "grok-1 verify step": ((3, 2, 8, 8, "softmax"), True),
}


@pytest.mark.parametrize("shape", list(SHAPES))
def test_rule_of_engagement_by_shape(monkeypatch, shape):
    (t, k, n_experts, held, router), selected = SHAPES[shape]
    cfg = ModelConfig(
        arch="mixtral", dim=64, hidden_dim=64, n_layers=1, n_heads=4,
        n_kv_heads=4, vocab_size=64, seq_len=32, head_size=16, kv_dim=64,
        n_experts=n_experts, n_active_experts=k, dtype="float32",
        router=router, expert_first=0 if held == n_experts else held,
        expert_count=0 if held == n_experts else held)
    lp = dict(_expert_planes(held, 64, 64),
              moe_router=jnp.zeros((64, n_experts), jnp.float32),
              moe_bias=jnp.zeros((n_experts,), jnp.float32))
    seen = []

    def spy(cfg, lp, xb, layer, *a, **kw):
        seen.append((xb.shape[0], min(cfg.n_experts_held,
                                      xb.shape[0] * cfg.n_active_experts)))
        return jnp.zeros_like(xb), 0

    monkeypatch.setattr(moe, "_moe_decode_selected", spy)
    jax.eval_shape(lambda x: moe.moe_ffn(cfg, lp, x, jnp.int32(0)),
                   jax.ShapeDtypeStruct((t, 64), jnp.float32))
    assert bool(seen) == selected
    if selected:
        assert seen == [(t, min(held, t * k))]  # cap: the static bound


# ---------------------------------------------------------------------------
# the plan's pooled step: its fourth number, and its lowered text
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def pooled():
    """``forward_batched`` of tiny-mimo as it stands (4 of 16 held, k = 4:
    the selected path at 3 rows, the all-experts branch at 4) on quantized
    planes -> picks by row count."""
    cfg, params = mimo_params(load_conf())
    rope = llama.rope_tables(cfg)
    out = {}
    for b in (3, 4):
        cache = llama.init_batch_cache(cfg, b, jnp.float32, seq_len=32)
        live = np.ones(b, bool)
        live[1] = False
        step = jax.jit(lambda p, r, t, c, ps, lv: llama.forward_batched(
            cfg, p, r, t, c, ps, live=lv))
        logits, _, picks = step(
            params, rope, jnp.asarray([300, 0, 411, 298][:b], jnp.int32), cache,
            jnp.asarray([3, 31, 9, 17][:b], jnp.int32), jnp.asarray(live))
        out[b] = (cfg, np.asarray(logits), [int(v) for v in picks])
    return out


def test_fourth_number_is_the_third_where_the_selected_path_runs(pooled):
    cfg, logits, (held, total, active, reads) = pooled[3]
    assert 3 * cfg.n_active_experts < cfg.n_experts  # the rule engages
    assert total == 2 * cfg.n_active_experts * cfg.plan_count(ffn="moe")
    assert reads == active and 0 < active <= held
    assert np.isfinite(logits).all()  # the dead row too: _health reads it


def test_fourth_number_is_every_held_expert_where_it_does_not(pooled):
    cfg, logits, (held, total, active, reads) = pooled[4]
    assert 4 * cfg.n_active_experts >= cfg.n_experts
    assert reads == cfg.n_experts_held * cfg.plan_count(ffn="moe") > active
    assert np.isfinite(logits).all()


def _whiles(text: str) -> list:
    """(cond lines, body lines) of every ``stablehlo.while`` of a module."""
    lines, out = text.splitlines(), []
    for i, line in enumerate(lines):
        if "stablehlo.while(" not in line:
            continue
        pad = line[:len(line) - len(line.lstrip())]
        do = next(j for j in range(i, len(lines)) if lines[j] == pad + "} do {")
        end = next(j for j in range(do, len(lines)) if lines[j] == pad + "}")
        out.append((lines[i + 2:do], lines[do + 1:end]))
    return out


@pytest.fixture
def real_kernels(monkeypatch):
    """Lower the Pallas kernels themselves, not their interpretation. The
    kernels' jitted wrappers read the switch while they trace, so traces
    from before and after the switch must not meet."""
    jax.clear_caches()
    monkeypatch.setattr(qmatmul, "_interpret_default", lambda: False)
    yield
    jax.clear_caches()


def test_lowered_pooled_step_holds_the_expert_calls_under_a_traced_bound(
        real_kernels):
    """Lowered for the TPU (from the CPU, no chip and no compiler): the
    expert kernels of the plan's pooled step sit in a ``while`` whose
    condition compares two carried values, the trip index and the traced
    count, and no constant; the layer scans' conditions hold theirs."""
    cfg, params = mimo_params(wide_mimo_conf())
    rope = llama.rope_tables(cfg)
    cache = llama.init_batch_cache(cfg, T, jnp.float32, seq_len=32)
    step = jax.jit(lambda p, r, t, c, ps, lv: llama.forward_batched(
        cfg, p, r, t, c, ps, live=lv))
    text = step.trace(
        params, rope, jnp.zeros((T,), jnp.int32), cache,
        jnp.zeros((T,), jnp.int32), jnp.ones((T,), bool)).lower(
            lowering_platforms=("tpu",)).as_text()

    kernels_of = {}  # private function -> the kernels it calls
    for block in text.split("func.func ")[1:]:
        name = re.match(r"(?:private |public )?@(\w+)", block).group(1)
        kernels_of[name] = set(re.findall(r'kernel_name = "(\w+)"', block))
    assert any("expert_upgate_q40_matmul" in k for k in kernels_of.values())

    def kernels(body) -> set:
        called = set(re.findall(r"call @(\w+)", "\n".join(body)))
        return set().union(*(kernels_of.get(c, set()) for c in called))

    expert_loops = [(cond, body) for cond, body in _whiles(text)
                    if any(k.startswith("expert_") for k in kernels(body))
                    and not any("stablehlo.while(" in l for l in body)]
    assert expert_loops
    for cond, body in expert_loops:
        assert {"expert_upgate_q40_matmul", "expert_down_q40_matmul"} \
            <= kernels(body)
        assert not any("stablehlo.constant" in l for l in cond), cond
        compare = next(l for l in cond if "stablehlo.compare" in l)
        assert len(set(re.findall(r"%iterArg\w*", compare))) == 2, compare
    constant_bound = [cond for cond, _ in _whiles(text)
                      if any("stablehlo.constant" in l for l in cond)]
    assert constant_bound  # a scan's condition does hold its length
