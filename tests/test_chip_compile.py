"""Ask the chip's compiler, without the chip.

The TPU's compiler is installed in the sandbox and compiles for a chip that
is DESCRIBED, not attached (``topologies.get_topology_desc``). These tests
lower the main path's kernels at Llama-2-7B widths, and one whole decode
step, for a described v5e and let Mosaic/XLA accept or refuse them: what
interpret mode and the static sweep in ``ops/lowering.py`` cannot see (both
passed the flash-decode and rope+cache kernels that the compiler refuses).
A compile that passes here is a compile, never a chip run.

The topology is described inside a module-scoped fixture, in this one file,
in the test's own process: only one process may load the TPU's library, the
suite runs under several workers that each import every test file, and a
call at import would leave the workers with different tests to collect.
Nothing here touches ``jax.devices()``: the backend stays the forced CPU.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.pallas.mosaic.error_handling import MosaicError
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from dllama_tpu.models import llama
from dllama_tpu.models.config import ModelConfig
from dllama_tpu.ops import attention, flash_decode, fused_rope_cache, qmatmul
from dllama_tpu.parallel import quant_tp
from dllama_tpu.parallel.mesh import TP
from dllama_tpu.parallel.sharding import cache_spec

#: Llama-2-7B (bench.py LLAMA2_7B / chip_smoke.py), the context the smoke
#: serves at, bf16 cache
CFG_7B = ModelConfig(
    arch="llama", dim=4096, hidden_dim=11008, n_layers=32, n_heads=32,
    n_kv_heads=32, vocab_size=32000, seq_len=2048, head_size=128,
    kv_dim=4096, dtype="bfloat16",
)
#: its four projection shapes (K, O): attention, FFN up/gate, FFN down, lm head
PROJECTIONS = [(4096, 4096), (4096, 11008), (11008, 4096), (4096, 32000)]
#: decode, and one prefill bucket (runtime.generate.PREFILL_BUCKETS)
ROWS = [1, 128]
HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever refuses, the reason is the skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.asarray(topo.devices), (TP,))


def _shapes(tree, sharding):
    """A pytree of arrays or ShapeDtypeStructs -> the same tree of
    ShapeDtypeStructs placed by ``sharding`` (one sharding, or a matching
    tree of them). Nothing is allocated: a described device holds no array."""
    if not isinstance(sharding, jax.sharding.Sharding):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, sharding)
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)


def _s(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# the weight-streaming kernels of the default serving path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T", ROWS)
@pytest.mark.parametrize("K,O", PROJECTIONS)
def test_q40_matmul_compiles(one_chip, K, O, T):
    kp = qmatmul._pad_up(K, qmatmul.K_MULTIPLE["q40"])
    compiled = qmatmul.q40_matmul.lower(
        _s((T, K), jnp.bfloat16, one_chip),
        _s((kp // 2, O), jnp.uint8, one_chip),
        _s((kp // 64, O), jnp.float32, one_chip),
        _s((kp // 64, O), jnp.float32, one_chip),
        interpret=False).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("T", ROWS)
@pytest.mark.parametrize("K,O", PROJECTIONS)
def test_q40_matmul_stacked_compiles(one_chip, K, O, T):
    L = CFG_7B.n_layers
    kp = qmatmul._pad_up(K, qmatmul.K_MULTIPLE["q40"])
    compiled = qmatmul.q40_matmul_stacked.lower(
        _s((T, K), jnp.bfloat16, one_chip),
        _s((L, kp // 2, O), jnp.uint8, one_chip),
        _s((L, kp // 64, O), jnp.float32, one_chip),
        _s((L, kp // 64, O), jnp.float32, one_chip),
        _s((), jnp.int32, one_chip),
        interpret=False).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("T", ROWS)
@pytest.mark.parametrize("K,O", PROJECTIONS)
def test_q80_matmul_compiles(one_chip, K, O, T):
    kp = qmatmul._pad_up(K, qmatmul.K_MULTIPLE["q80"])
    compiled = qmatmul.q80_matmul.lower(
        _s((T, K), jnp.bfloat16, one_chip),
        _s((kp, O), jnp.int8, one_chip),
        _s((kp // 32, O), jnp.float32, one_chip),
        interpret=False).compile()
    assert _has_kernel(compiled)


# ---------------------------------------------------------------------------
# one whole decode step, as `cli serve --weights-float-type q40` runs it
# ---------------------------------------------------------------------------

def _key():
    return jax.ShapeDtypeStruct((2,), jnp.uint32)


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)


def test_whole_7b_decode_step_compiles_for_one_chip(one_chip, monkeypatch):
    """``llama.forward`` over the loader's single-device layout (fused
    wqkv/w13), cache donated. The kernels' interpret default looks at the
    attached backend (the CPU here) and would lower every kernel in
    interpret mode — a "TPU" program with no custom call in it proves
    nothing — so the test steers it, and checks the custom calls are there."""
    monkeypatch.setattr(qmatmul, "_interpret_default", lambda: False)
    cfg = CFG_7B
    params = jax.eval_shape(
        lambda k: llama.fuse_qkv_ffn(llama._quant_init(k, cfg, "q40")), _key())
    rope = jax.eval_shape(lambda: llama.rope_tables(cfg))
    cache = jax.eval_shape(lambda: llama.init_cache(cfg, jnp.bfloat16))

    def step(params, rope, cache, tokens, pos):
        return llama.forward(cfg, params, rope, tokens, cache, pos)

    compiled = jax.jit(step, donate_argnums=(2,)).lower(
        _shapes(params, one_chip), _shapes(rope, one_chip),
        _shapes(cache, one_chip), _s((1,), jnp.int32, one_chip),
        _s((), jnp.int32, one_chip)).compile()
    assert _has_kernel(compiled)
    assert _fits(compiled) < HBM_BYTES, compiled.memory_analysis()


def test_whole_7b_decode_step_compiles_for_four_chips(four_chips, monkeypatch):
    """The ``--tp 4`` decode step (``quant_tp.make_tp_forward``: shard_map
    over output-sharded quant planes, plain gathers) on the described 2x2
    mesh: the kernels partition, the gathers are there, and each device's
    share fits its HBM."""
    monkeypatch.setattr(qmatmul, "_interpret_default", lambda: False)
    cfg, mesh, n_tp = CFG_7B, four_chips, four_chips.shape[TP]
    params = jax.eval_shape(
        lambda k: quant_tp.prepare_quant_params(
            llama._quant_init(k, cfg, "q40"), cfg, n_tp), _key())
    specs = quant_tp.quant_param_specs(params, cfg, n_tp)
    placed = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
    replicated = NamedSharding(mesh, P())
    rope = jax.eval_shape(lambda: llama.rope_tables(cfg))
    cache = jax.eval_shape(lambda: llama.init_cache(cfg, jnp.bfloat16))

    fwd = quant_tp.make_tp_forward(cfg, mesh, params)
    compiled = jax.jit(fwd, donate_argnums=(2,)).lower(
        _shapes(params, placed), _shapes(rope, replicated),
        _shapes(cache, NamedSharding(mesh, cache_spec())),
        _s((1,), jnp.int32, replicated), _s((), jnp.int32, replicated)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-gather" in text
    assert _fits(compiled) < HBM_BYTES, compiled.memory_analysis()


# ---------------------------------------------------------------------------
# the pooled decode chunk of the benchmark's Mistral cells: what the K/V write
# and the slab read compile to (PR 25)
# ---------------------------------------------------------------------------

#: Mistral-7B-v0.3 (benchmarks/configs/mistral-7b-v0.3-q40.json): 8 KV heads
CFG_MISTRAL = ModelConfig(
    arch="llama", dim=4096, hidden_dim=14336, n_layers=32, n_heads=32,
    n_kv_heads=8, vocab_size=32768, seq_len=4096, head_size=128,
    kv_dim=1024, dtype="bfloat16",
)
_HLO_LINE = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = \(?(\w+)\[([\d,]*)\](\S*) ([\w\-]+)\((.*)$")


def _instructions(text: str) -> list:
    """(name, elements, layout, opcode, rest of the line) of every HLO
    instruction whose result is one array."""
    out = []
    for line in text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            name, _, dims, layout, op, rest = m.groups()
            n = int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
            out.append((name, n, f"[{dims}]{layout}", op, rest))
    return out


@pytest.mark.parametrize("t", [0, 16], ids=["no riders", "16 riders a step"])
def test_pooled_decode_chunk_writes_rows_not_slabs(one_chip, monkeypatch, t):
    """``Engine._decode_loop_batch``'s body (8 steps of ``forward_batched``,
    the per-row sampler, the position clamp; the cache donated and carried)
    at the Mistral cells' shapes: capacity 8, bucket 1024, bf16. Under
    ``kv_slab_write`` the optimised program holds one scatter a cache, whose
    update is the step's ``B x kv x hd`` rows and which runs in place on the
    carry, and no ``dynamic-update-slice`` or ``copy`` of a slab (the parent
    of PR 25 had both: each layer's slab copied out, updated and written
    back). In place also means few temporaries: 0.269 GB here, with nothing
    the size of the 1.07 GB cache among them; B unrolled
    ``dynamic_update_slice``s read 1.075 GB, the whole cache carried in
    another layout. What the read side became is printed (``pytest -s``).

    With riders (PR 28: each step carries ``t`` prompt tokens of one pool row
    beside the decode rows, the session's ``ride`` operand) every pin holds:
    the step's ``B + t`` rows go in the one scatter a cache, as much in
    place; the one row's slab that the riders' queries attend is an eighth
    of a slab, not a slab. (8 and 32 riders a step compile alike: tried
    once, PR 28.)"""
    from dllama_tpu.runtime.sampler import sample_dynamic

    monkeypatch.setattr(qmatmul, "_interpret_default", lambda: False)
    cfg, rows, ctx, steps = CFG_MISTRAL, 8, 1024, 8
    params = jax.eval_shape(
        lambda k: llama.fuse_qkv_ffn(llama._quant_init(k, cfg, "q40")), _key())
    rope = jax.eval_shape(lambda: llama.rope_tables(cfg))
    cache = jax.eval_shape(
        lambda: llama.init_batch_cache(cfg, rows, jnp.bfloat16, seq_len=ctx))

    def chunk(params, rope, cache, tokens, pos, keys, temps, topps, ride):
        def body(carry, ride_s):
            cache, toks, pos_, keys_ = carry
            with_ride = {} if ride_s is None else {"ride": (
                ride_s[:-3], ride_s[-3], ride_s[-2], ride_s[-1])}
            logits, cache = llama.forward_batched(cfg, params, rope, toks,
                                                  cache, pos_, **with_ride)
            split = jax.vmap(jax.random.split)(keys_)
            nxt = jax.vmap(sample_dynamic)(
                logits, split[:, 1], temps, topps).astype(jnp.int32)
            pos_ = jnp.minimum(pos_ + 1, jnp.int32(cfg.seq_len - 1))
            return (cache, nxt, pos_, split[:, 0]), nxt

        (cache, *_), out = jax.lax.scan(
            body, (cache, tokens, pos, keys), ride, length=steps)
        return out, cache

    ints, floats = (_s((rows,), jnp.int32, one_chip),
                    _s((rows,), jnp.float32, one_chip))
    compiled = jax.jit(chunk, donate_argnums=(2,)).lower(
        _shapes(params, one_chip), _shapes(rope, one_chip),
        _shapes(cache, one_chip), ints, ints,
        _s((rows, 2), jnp.uint32, one_chip), floats, floats,
        _s((steps, t + 3), jnp.int32, one_chip) if t else None).compile()
    assert _has_kernel(compiled)

    instructions = _instructions(compiled.as_text())
    size = {name: n for name, n, *_ in instructions}
    slab = rows * ctx * cfg.n_kv_heads * cfg.head_size
    step_rows = rows * cfg.n_kv_heads * cfg.head_size
    written = [i for i in instructions if "kv_slab_write" in i[4]]
    scatters = [i for i in written if i[3] == "scatter"]
    assert len(scatters) == 2, scatters  # K and V
    for name, n, _, _, rest in scatters:
        assert n == cfg.n_layers * slab, name  # the stacked cache itself
        operands = re.findall(r"%([\w.\-]+)", rest.split(")", 1)[0])
        # the step's rows: the decode rows' and, with them, the riders'
        assert size[operands[2]] == step_rows * (rows + t) // rows, (
            name, operands)
    moved = [i[:4] for i in written if i[1] >= slab and i[3] in (
        "copy", "dynamic-update-slice", "dynamic-slice", "transpose")]
    assert not moved, moved
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 2 * cfg.n_layers * slab * 2  # donated
    assert m.temp_size_in_bytes < 0.4e9, m
    for name, n, shape, op, _ in (i for i in instructions
                                  if "kv_slab_read" in i[4] and i[1] >= slab):
        print(f"kv_slab_read: {name} = {shape} {op}")


# ---------------------------------------------------------------------------
# the models whose layers differ in kind, at their published widths. The
# benchmark's third configuration: 192-wide q/k and 128-wide v heads,
# 2048-wide experts, a 16384-wide dense layer, a ragged 13568- / 14848-column
# wqkv. Its fourth: a 16384-wide q projection for 128 heads, a 16384-wide
# shared plane beside 4096-wide experts, rings of 8192 slots, a tied head
# ---------------------------------------------------------------------------

_PLAN_CONFIGS = {
    "mimo-v2-flash-d13-e32-q40": (
        {"wqkv_q40_matmul", "wo_q40_matmul", "w13_q40_matmul",
         "w2_q40_matmul", "expert_upgate_q40_matmul",
         "expert_down_q40_matmul", "wcls_q40_matmul"},
        ("attention_full", "attention_window", "kv_ring_write",
         "moe_router")),
    "command-a-plus-d8-e16-q40": (
        {"wqkv_q40_matmul", "wo_q40_matmul", "shared_upgate_q40_matmul",
         "shared_down_q40_matmul", "expert_upgate_q40_matmul",
         "expert_down_q40_matmul", "wcls_q40_matmul"},
        ("attention_full", "attention_window", "kv_ring_write",
         "moe_router", "moe_shared")),
}


@pytest.mark.parametrize("program", ["forward T=64", "forward_batched"])
@pytest.mark.parametrize("config", sorted(_PLAN_CONFIGS, reverse=True))
def test_layer_plan_programs_compile_at_published_widths(one_chip,
                                                         monkeypatch, config,
                                                         program):
    """The prefill piece and the pooled decode step of a benchmark
    configuration with a layer plan, as the compile rehearsal
    (``benchmarks/rehearse_compile.py``) takes them from the family's
    ``rehearsal``: whatever the v5e compiler refuses of them is found here,
    not on the chip. Both fit one chip beside their weights. A cache longer
    than ``attention.LEAST_RUNG`` is read through the ladder of prefixes
    (``layer_plan._attend``): its least rung is in the program, and no
    branch turns a whole stacked cache around to slice it (``llama.
    _plain_layout``: at most the copy in and the copy out that the parent's
    program has of a cache leaf, never one a layer)."""
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(os.path.join(root, "benchmarks"))
    import families

    with open(os.path.join(root, f"benchmarks/configs/{config}.json")) as f:
        conf = json.load(f)
    want_kernels, want_scopes = _PLAN_CONFIGS[config]
    interpret = qmatmul._interpret_default
    try:
        listed = families.load(conf).rehearsal(conf)
        name, fn, args, static = next(
            r for r in listed if r[0].startswith(program))
        compiled = fn.lower(*_shapes(args, one_chip), **static).compile()
    finally:
        qmatmul._interpret_default = interpret  # rehearsal() sets it
    assert _has_kernel(compiled)
    kernels = set(_kernel_names(compiled))
    assert want_kernels <= kernels, kernels
    text = compiled.as_text()
    for scope in want_scopes:
        assert scope in text, scope
    m = compiled.memory_analysis()
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes) < HBM_BYTES
    def dims(instruction) -> tuple:
        return tuple(int(d) for d in
                     instruction[2][1:].split("]")[0].split(",") if d)

    leaves = jax.tree.leaves(args[3])  # the cache tree
    instructions = _instructions(text)
    for leaf in leaves:
        whole = [i for i in instructions
                 if i[3] == "copy" and dims(i) == leaf.shape]
        assert len(whole) <= 2, (leaf.shape, len(whole))
    laddered = {leaf.shape[-3] for leaf in leaves
                if leaf.shape[-3] > attention.LEAST_RUNG}
    reads = {dims(i)[-3] for i in instructions
             if "kv_slab_read" in i[4] and i[3] in ("dynamic-slice", "slice")}
    if config == "command-a-plus-d8-e16-q40":
        assert laddered == {8192}  # the rings; the staging cache's slabs too
    if laddered:
        assert "conditional(" in text
        assert {attention.LEAST_RUNG, *laddered} <= reads, reads
    else:  # MiMo's pooled step: a ring of 256, slabs of 1024: as it was
        assert "conditional(" not in text


# ---------------------------------------------------------------------------
# the names the benchmark's trace readers depend on
# ---------------------------------------------------------------------------

#: small widths: a name does not depend on them, and these compile in seconds
_NAMED = dict(dim=512, hidden_dim=1024, n_layers=2, n_heads=4, n_kv_heads=2,
              vocab_size=1024, seq_len=256, head_size=128, kv_dim=256,
              dtype="bfloat16")
_DENSE = {"wqkv", "wo", "w13", "w2", "wcls"}
_SPARSE = {"wqkv", "wo", "expert_upgate", "expert_down", "wcls"}


def _kernel_names(compiled) -> list:
    """The instruction name of every Pallas custom call, without its number
    (what ``benchmarks/trace_reduce.short_name`` keeps of an ``XLA Ops``
    event)."""
    return [re.sub(r"[.\d]+$", "", line.split(" = ", 1)[0].split("%")[-1])
            for line in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("arch,projections", [
    ("llama", _DENSE), ("mixtral", _SPARSE)])
def test_custom_calls_are_named_by_projection(one_chip, monkeypatch, arch,
                                              projections, program):
    """Every q40 kernel of the served step (fused layout, pooled decode and
    one prefill piece) carries its projection's name, main kernel and
    correction kernel apart, and none is left under a name two projections
    share (``q40_matmul_stacked``, the enclosing jitted function's): a
    device trace then splits kernel time by projection. The names end in a
    letter, because the reduction strips a trailing number."""
    monkeypatch.setattr(qmatmul, "_interpret_default", lambda: False)
    moe = dict(n_experts=8, n_active_experts=2) if arch == "mixtral" else {}
    cfg = ModelConfig(arch=arch, **_NAMED, **moe)
    params = jax.eval_shape(
        lambda k: llama.fuse_qkv_ffn(llama._quant_init(k, cfg, "q40")), _key())
    rope = jax.eval_shape(lambda: llama.rope_tables(cfg))
    if program == "decode":
        rows = 8
        cache = jax.eval_shape(
            lambda: llama.init_batch_cache(cfg, rows, jnp.bfloat16))
        tokens, pos = (_s((rows,), jnp.int32, one_chip),) * 2
        fwd = llama.forward_batched
    else:
        cache = jax.eval_shape(lambda: llama.init_cache(cfg, jnp.bfloat16))
        tokens, pos = _s((64,), jnp.int32, one_chip), _s((), jnp.int32, one_chip)
        fwd = llama.forward

    def step(params, rope, cache, tokens, pos):
        return fwd(cfg, params, rope, tokens, cache, pos)

    compiled = jax.jit(step, donate_argnums=(2,)).lower(
        _shapes(params, one_chip), _shapes(rope, one_chip),
        _shapes(cache, one_chip), tokens, pos).compile()
    names = _kernel_names(compiled)
    want = ({p + "_q40_matmul" for p in projections}
            | {p + "_q40_corr" for p in projections})
    assert set(names) == want
    assert not any(n[-1].isdigit() for n in names)
    text = compiled.as_text()
    for scope in ("attention", "moe" if moe else "ffn", "kv_slab_read",
                  "kv_slab_write"):
        assert f"/{scope}/" in text, scope


def test_the_served_programs_keep_the_names_the_readers_match():
    """``jit__decode_loop_batch`` and ``jit__prefill``: what the three
    trace readers' ``decode_module`` / ``prefill_module`` patterns
    (``^jit__decode_loop``, ``^jit__prefill$``) find in ``XLA Modules``.
    Lowered from a real (tiny) Engine on the CPU: a module's name does not
    depend on the backend. The sampler's scope is inside the decode loop."""
    from dllama_tpu.runtime.generate import Engine
    from dllama_tpu.runtime.sampler import SamplerConfig

    cfg = ModelConfig(arch="llama", dim=64, hidden_dim=96, n_layers=2,
                      n_heads=4, n_kv_heads=2, vocab_size=128, seq_len=32,
                      head_size=16, kv_dim=32)
    eng = Engine(cfg, llama.random_params(cfg, seed=0),
                 SamplerConfig(temperature=0.0, seed=0))
    rows = 2
    decode = eng._decode_loop_batch.func.lower(
        eng.params, eng.rope, llama.init_batch_cache(cfg, rows),
        jnp.zeros(rows, jnp.int32), jnp.zeros(rows, jnp.int32),
        jnp.zeros((rows, 2), jnp.uint32), jnp.zeros(rows, jnp.float32),
        jnp.ones(rows, jnp.float32), jnp.zeros(rows, jnp.bool_), n_steps=2)
    prefill = eng._prefill.func.lower(
        eng.params, eng.rope, llama.init_cache(cfg),
        jnp.zeros(8, jnp.int32), 3, jnp.int32(0))
    assert "module @jit__decode_loop_batch " in decode.as_text()
    assert "module @jit__prefill " in prefill.as_text()
    assert '"sample/' in decode.as_text(debug_info=True)


# ---------------------------------------------------------------------------
# the two opt-in attention kernels: refused today. Strict xfails, so the PR
# that repairs a kernel finds its test waiting (and failing as XPASS until
# the marker goes).
# ---------------------------------------------------------------------------

@pytest.mark.xfail(strict=True, raises=MosaicError, reason=(
    "MosaicError: Slice shape along dimension 3 must be aligned to tiling "
    "(8), but is 1 — the per-head DMA k_hbm.at[layer, b, pl.ds(...), h] on "
    "a cache whose last two dims are (n_kv, head_size); needs a cache slice "
    "whose second-minor extent is a multiple of 8, or a head-major layout"))
@pytest.mark.parametrize("T", [1, 9])
def test_flash_decode_attention_compiles(one_chip, T):
    cfg = CFG_7B
    kv = (cfg.n_layers, cfg.seq_len, cfg.n_kv_heads, cfg.head_size)
    flash_decode.flash_decode_attention.lower(
        _s((T, cfg.n_heads, cfg.head_size), jnp.bfloat16, one_chip),
        _s(kv, jnp.bfloat16, one_chip), _s(kv, jnp.bfloat16, one_chip),
        _s((), jnp.int32, one_chip), _s((), jnp.int32, one_chip),
        interpret=False).compile()


@pytest.mark.xfail(strict=True, raises=NotImplementedError, reason=(
    "NotImplementedError: Only 2D gather is supported — the in-kernel rope "
    "rotation of fused_rope_cache._kernel (the strided lane slices "
    "kf[..., 0::2] of the interleaved style)"))
@pytest.mark.parametrize("T", [1, 9])
def test_rope_cache_update_compiles(one_chip, T):
    cfg = CFG_7B
    kv = (cfg.n_layers, cfg.seq_len, cfg.n_kv_heads, cfg.head_size)
    row = (T, cfg.n_kv_heads, cfg.head_size)
    angle = (T, 1, cfg.head_size // 2)
    fused_rope_cache.rope_cache_update.lower(
        _s(row, jnp.bfloat16, one_chip), _s(row, jnp.bfloat16, one_chip),
        _s(angle, jnp.float32, one_chip), _s(angle, jnp.float32, one_chip),
        _s(kv, jnp.bfloat16, one_chip), _s(kv, jnp.bfloat16, one_chip),
        _s((), jnp.int32, one_chip), _s((), jnp.int32, one_chip),
        interpret=False).compile()
