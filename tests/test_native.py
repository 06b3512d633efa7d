"""Native C++ runtime tests.

Three layers, mirroring the reference's standalone-binary test strategy
(SURVEY.md §4 — funcs-test/quants-test are exit-code C++ binaries run by CI):

1. build ``native/`` with make and run its exit-code unit tests
   (tokenizer-test, sampler-test);
2. cross-check the C++ tokenizer against the Python one on a real vocab
   through the ``dllama-native`` manifest-free paths;
3. validate the exporter's manifest contract (offsets, arg order, files).

The full TPU e2e (export -> dllama-native generate on the PJRT plugin) needs
the real chip, so it is opt-in:
``DLLAMA_NATIVE_E2E=1 python -m pytest tests/test_native.py -k e2e``.
"""

import os
import subprocess

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "native")


@pytest.fixture(scope="module")
def native_build():
    subprocess.run(["make", "-j4"], cwd=NATIVE, check=True, capture_output=True)
    return os.path.join(NATIVE, "build")


def test_cpp_unit_tests(native_build):
    for binary in ("tokenizer-test", "sampler-test", "manifest-test"):
        proc = subprocess.run(
            [os.path.join(native_build, binary)], capture_output=True, text=True
        )
        assert proc.returncode == 0, proc.stderr


def test_cpp_tokenizer_matches_python(native_build, tmp_path):
    """The C++ and Python tokenizers must produce identical ids for the same
    vocab. Uses a small synthetic sentencepiece-style vocab."""
    from dllama_tpu.formats.tokenizer_file import TokenizerData, write_tokenizer
    from dllama_tpu.tokenizer.bpe import Tokenizer

    vocab = [b"<unk>", b"<s>", b"</s>"]
    vocab += [f"<0x{b:02X}>".encode() for b in range(256)]
    # multi-char merges score better (higher) than singles; pieces unique
    extra = [b" ", b"t", b"h", b"e", b"th", b"the", b" the", b"c", b"a",
             b"at", b"cat", b" cat"]
    vocab += extra
    scores = [0.0] * 259 + [-3.0, -5.0, -5.0, -5.0, -2.0, -1.0, -0.5,
                            -5.0, -5.0, -2.0, -1.0, -0.5]
    data = TokenizerData(vocab=vocab, scores=scores, bos_id=1, eos_id=2)
    tpath = str(tmp_path / "test.t")
    write_tokenizer(tpath, data)

    pytok = Tokenizer.from_file(tpath)
    for text in ["the cat", "the", "hello world", "xyz", ""]:
        py_ids = pytok.encode(text, add_bos=True)
        # drive the C++ tokenizer through a tiny probe binary built inline
        probe = subprocess.run(
            [os.path.join(NATIVE, "build", "tokenizer-probe"), tpath, text],
            capture_output=True,
            text=True,
        )
        if probe.returncode != 0 and not os.path.exists(
            os.path.join(NATIVE, "build", "tokenizer-probe")
        ):
            pytest.skip("tokenizer-probe not built")
        cpp_ids = [int(x) for x in probe.stdout.split()]
        assert cpp_ids == py_ids, f"mismatch for {text!r}"


def test_export_manifest_contract(tmp_path):
    """Exporter output obeys the manifest format the C++ loader parses:
    weight offsets are tight and in range, arg order is weights -> caches ->
    token -> pos, outputs are logits + caches."""
    import jax.numpy as jnp

    from dllama_tpu import export_native
    from dllama_tpu.models import llama
    from dllama_tpu.models.config import ModelConfig

    cfg = ModelConfig(
        arch="llama", dim=64, hidden_dim=128, n_layers=2, n_heads=4,
        n_kv_heads=4, vocab_size=128, seq_len=32, head_size=16, kv_dim=64,
        dtype="float32",
    )
    params = llama.random_params(cfg, seed=0)
    out = export_native.export_model(
        cfg, params, str(tmp_path / "export"), cache_dtype=jnp.float32,
        aot=False,
    )

    manifest = open(os.path.join(out, "manifest.txt")).read().splitlines()
    assert manifest[0] == "dllama_native 1"
    weights_size = os.path.getsize(os.path.join(out, "weights.bin"))
    assert os.path.getsize(os.path.join(out, "model.mlir")) > 0
    assert os.path.getsize(os.path.join(out, "compile_options.pb")) > 0

    inputs = [l.split() for l in manifest if l.startswith("input ")]
    outputs = [l.split() for l in manifest if l.startswith("output ")]
    kinds = [i[2] for i in inputs]
    # weights first, then caches, then token, then pos
    assert kinds == ["weight"] * (len(kinds) - 4) + ["cache", "cache", "token", "pos"]

    expected_offset = 0
    for rec in inputs:
        name, kind, dtype, offset, nbytes = rec[1], rec[2], rec[3], int(rec[4]), int(rec[5])
        ndims = int(rec[6])
        dims = [int(d) for d in rec[7 : 7 + ndims]]
        if kind == "weight":
            assert offset == expected_offset, name
            itemsize = {"f32": 4, "bf16": 2, "i32": 4}[dtype]
            assert nbytes == int(np.prod(dims, initial=1)) * itemsize
            expected_offset += nbytes
    assert expected_offset == weights_size

    assert outputs[0][2] == "logits"
    assert [o[2] for o in outputs[1:]] == ["cache", "cache"]

    # fused decode-loop program: declared with its chunk size, module written
    loop_lines = [l.split() for l in manifest if l.startswith("loop_")]
    loop_keys = {l[0]: l[1] for l in loop_lines}
    assert loop_keys["loop_mlir_file"] == "model_loop.mlir"
    assert int(loop_keys["loop_steps"]) == export_native.LOOP_STEPS
    assert os.path.getsize(os.path.join(out, "model_loop.mlir")) > 0

    # bucketed-prefill program: bucket clamps to seq_len for tiny models
    pf_lines = [l.split() for l in manifest if l.startswith("prefill_")]
    pf_keys = {l[0]: l[1] for l in pf_lines}
    assert pf_keys["prefill_mlir_file"] == "model_prefill.mlir"
    assert int(pf_keys["prefill_bucket"]) == min(
        export_native.PREFILL_BUCKET, cfg.seq_len)
    assert os.path.getsize(os.path.join(out, "model_prefill.mlir")) > 0


def test_exported_loop_module_decodes_greedily(tmp_path):
    """Execute the written model_loop.mlir exactly the way the C++ runtime
    does (PJRT compile of the raw StableHLO bytecode + flat buffer arglist):
    one call must decode LOOP_STEPS greedy tokens matching the Python engine."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax._src import xla_bridge
    from jax._src.lib import xla_client as xc
    from jaxlib._jax import DeviceList

    from dllama_tpu import export_native
    from dllama_tpu.models import llama
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.runtime.generate import Engine
    from dllama_tpu.runtime.sampler import SamplerConfig

    cfg = ModelConfig(
        arch="llama", dim=64, hidden_dim=128, n_layers=2, n_heads=4,
        n_kv_heads=4, vocab_size=128, seq_len=64, head_size=16, kv_dim=64,
        dtype="float32",
    )
    params = llama.random_params(cfg, seed=1)
    out = export_native.export_model(
        cfg, params, str(tmp_path / "export"), cache_dtype=jnp.float32,
        aot=False,
    )
    with open(os.path.join(out, "model_loop.mlir"), "rb") as f:
        bytecode = f.read()

    backend = xla_bridge.get_backend()
    exe = backend.compile_and_load(
        bytecode, DeviceList(tuple(backend.local_devices()[:1])),
        xc.CompileOptions(),
    )

    rope = llama.rope_tables(cfg)
    weights = {"params": jax.tree.map(jnp.asarray, params), "rope": rope}
    cache = llama.init_cache(cfg, jnp.float32)
    flat_args = (
        jax.tree.leaves(weights)
        + [cache["k"], cache["v"], np.asarray([7], np.int32),
           np.asarray(0, np.int32), np.asarray(0.0, np.float32),
           np.asarray(0.9, np.float32), np.asarray(1, np.int32)]
    )
    bufs = [backend.buffer_from_pyval(np.asarray(a)) for a in flat_args]
    outs = exe.execute(bufs)
    toks = [int(t) for t in np.asarray(outs[0])]
    assert np.asarray(outs[1]).shape == cache["k"].shape  # caches follow

    want = Engine(cfg, params, SamplerConfig(temperature=0.0))
    want_toks, _, _ = want.generate_fused([7], steps=export_native.LOOP_STEPS)
    assert toks == want_toks


@pytest.mark.skipif(
    os.environ.get("DLLAMA_NATIVE_E2E") != "1",
    reason="needs real TPU + PJRT plugin (set DLLAMA_NATIVE_E2E=1)",
)
def test_native_e2e_tpu(native_build, tmp_path):
    """Full loop: export a tiny random model on the TPU backend, run
    dllama-native generate against the PJRT plugin, expect token output."""
    script = os.path.join(REPO, "scripts", "native_e2e.py")
    proc = subprocess.run(
        ["python", script, str(tmp_path / "export")],
        capture_output=True,
        text=True,
        env={k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"},
    )
    assert proc.returncode == 0, proc.stderr + proc.stdout


def test_exported_prefill_module_matches_engine(tmp_path):
    """Execute model_prefill.mlir the C++ way (flat arglist: tokens[bucket],
    pos, trailing n): the returned last-real-position logits must argmax to
    the same first token the Python engine samples after an identical
    prompt, and the advanced caches must continue decoding identically."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax._src import xla_bridge
    from jax._src.lib import xla_client as xc
    from jaxlib._jax import DeviceList

    from dllama_tpu import export_native
    from dllama_tpu.models import llama
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.runtime.generate import Engine
    from dllama_tpu.runtime.sampler import SamplerConfig

    cfg = ModelConfig(
        arch="llama", dim=64, hidden_dim=128, n_layers=2, n_heads=4,
        n_kv_heads=4, vocab_size=128, seq_len=64, head_size=16, kv_dim=64,
        dtype="float32",
    )
    params = llama.random_params(cfg, seed=2)
    out = export_native.export_model(
        cfg, params, str(tmp_path / "export"), cache_dtype=jnp.float32,
        aot=False,
    )
    with open(os.path.join(out, "model_prefill.mlir"), "rb") as f:
        bytecode = f.read()

    backend = xla_bridge.get_backend()
    exe = backend.compile_and_load(
        bytecode, DeviceList(tuple(backend.local_devices()[:1])),
        xc.CompileOptions(),
    )

    prompt = [7, 3, 9, 4]
    bucket = min(export_native.PREFILL_BUCKET, cfg.seq_len)
    padded = np.zeros(bucket, np.int32)
    padded[: len(prompt)] = prompt

    rope = llama.rope_tables(cfg)
    weights = {"params": jax.tree.map(jnp.asarray, params), "rope": rope}
    cache = llama.init_cache(cfg, jnp.float32)
    flat_args = (
        jax.tree.leaves(weights)
        + [cache["k"], cache["v"], padded, np.asarray(0, np.int32),
           np.asarray(len(prompt), np.int32)]
    )
    bufs = [backend.buffer_from_pyval(np.asarray(a)) for a in flat_args]
    outs = exe.execute(bufs)
    first = int(np.argmax(np.asarray(outs[0])))

    eng = Engine(cfg, params, SamplerConfig(temperature=0.0))
    want = [t for t, _ in eng.generate(prompt, steps=3)]
    assert first == want[0]

    # decode must CONTINUE correctly from the prefill-advanced caches (the
    # native runtime's actual flow): run the step module on outs[1]/outs[2]
    with open(os.path.join(out, "model.mlir"), "rb") as f:
        step_exe = backend.compile_and_load(
            f.read(), DeviceList(tuple(backend.local_devices()[:1])),
            xc.CompileOptions(),
        )
    k_buf, v_buf = outs[1], outs[2]
    token, pos_i = first, len(prompt)
    for want_next in want[1:]:
        step_args = (
            jax.tree.leaves(weights)
            + [np.asarray(k_buf), np.asarray(v_buf),
               np.asarray([token], np.int32), np.asarray(pos_i, np.int32)]
        )
        step_bufs = [backend.buffer_from_pyval(np.asarray(a)) for a in step_args]
        step_outs = step_exe.execute(step_bufs)
        nxt = int(np.argmax(np.asarray(step_outs[0])))
        assert nxt == want_next
        k_buf, v_buf, token = step_outs[1], step_outs[2], nxt
        pos_i += 1


def test_sharded_export_deserializes_and_runs(tmp_path):
    """Multi-device export groundwork: a tp=2 decode step serialized with
    jax.export must deserialize, report its device contract, and execute on
    a 2-device mesh with logits equal to the single-device forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import export as jax_export

    from dllama_tpu import export_native
    from dllama_tpu.models import llama
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.parallel.mesh import tp_mesh

    cfg = ModelConfig(
        arch="llama", dim=64, hidden_dim=128, n_layers=2, n_heads=4,
        n_kv_heads=4, vocab_size=128, seq_len=32, head_size=16, kv_dim=64,
        dtype="float32",
    )
    params = llama.random_params(cfg, seed=3)
    mesh = tp_mesh(2)
    path = export_native.export_sharded_step(
        cfg, params, mesh, str(tmp_path / "model_tp2.mlir"),
        cache_dtype=jnp.float32,
    )

    with open(path, "rb") as f:
        exp = jax_export.deserialize(f.read())
    assert exp.nr_devices == 2

    from dllama_tpu.parallel.sharding import shard_params

    sharded = shard_params(params, mesh, cfg)
    rope = llama.rope_tables(cfg)
    cache = llama.init_cache(cfg, jnp.float32)
    logits, new_k, _ = jax.jit(exp.call)(
        sharded, rope, cache["k"], cache["v"],
        jnp.asarray([7], jnp.int32), jnp.int32(0),
    )

    ref, _ = llama.forward(
        cfg, jax.tree.map(jnp.asarray, params), rope,
        jnp.asarray([7], jnp.int32), llama.init_cache(cfg, jnp.float32),
        jnp.int32(0),
    )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref)[0], rtol=2e-4, atol=2e-4
    )
    assert new_k.shape == cache["k"].shape


def test_prefill_multi_dispatch_and_context_end_restart(tmp_path):
    """Drive the exported prefill module with the EXACT bucket walk the C++
    runtime uses (start = min(pos, seq_len - bucket), re-feeding overlapped
    positions near the context end): a 90-token prompt against a 64-token
    bucket takes 2 dispatches, the second restarting at 32 and rewriting
    positions 32..63 with identical K/V. The first sampled token and the
    continued greedy decode must match the Python engine exactly."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax._src import xla_bridge
    from jax._src.lib import xla_client as xc
    from jaxlib._jax import DeviceList

    from dllama_tpu import export_native
    from dllama_tpu.models import llama
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.runtime.generate import Engine
    from dllama_tpu.runtime.sampler import SamplerConfig

    cfg = ModelConfig(
        arch="llama", dim=64, hidden_dim=128, n_layers=2, n_heads=4,
        n_kv_heads=4, vocab_size=128, seq_len=96, head_size=16, kv_dim=64,
        dtype="float32",
    )
    params = llama.random_params(cfg, seed=4)
    out = export_native.export_model(
        cfg, params, str(tmp_path / "export"), cache_dtype=jnp.float32,
        aot=False,
    )
    bucket = min(export_native.PREFILL_BUCKET, cfg.seq_len)
    assert bucket == 64  # the test needs bucket < seq_len < 2*bucket

    backend = xla_bridge.get_backend()

    def load(name):
        with open(os.path.join(out, name), "rb") as f:
            return backend.compile_and_load(
                f.read(), DeviceList(tuple(backend.local_devices()[:1])),
                xc.CompileOptions(),
            )

    prefill_exe, step_exe = load("model_prefill.mlir"), load("model.mlir")

    rng = np.random.default_rng(7)
    prompt = [int(t) for t in rng.integers(3, cfg.vocab_size, 90)]

    rope = llama.rope_tables(cfg)
    weights = {"params": jax.tree.map(jnp.asarray, params), "rope": rope}
    leaves = [np.asarray(x) for x in jax.tree.leaves(weights)]
    cache = llama.init_cache(cfg, jnp.float32)
    k_buf = backend.buffer_from_pyval(np.asarray(cache["k"]))
    v_buf = backend.buffer_from_pyval(np.asarray(cache["v"]))

    # the C++ prompt loop, verbatim arithmetic
    pos, dispatches, logits = 0, 0, None
    while pos < len(prompt):
        start = min(pos, cfg.seq_len - bucket)
        take = min(len(prompt) - start, bucket)
        padded = np.zeros(bucket, np.int32)
        padded[:take] = prompt[start : start + take]
        args = leaves + [k_buf, v_buf, padded, np.asarray(start, np.int32),
                         np.asarray(take, np.int32)]
        bufs = [a if not isinstance(a, np.ndarray) else
                backend.buffer_from_pyval(a) for a in args]
        outs = prefill_exe.execute(bufs)
        k_buf, v_buf = outs[1], outs[2]
        pos = start + take
        dispatches += 1
        if pos == len(prompt):
            logits = np.asarray(outs[0])
    assert dispatches == 2  # 90 tokens / 64-bucket with restart at 32

    first = int(np.argmax(logits))
    want = [t for t, _ in Engine(cfg, params, SamplerConfig(temperature=0.0))
            .generate(prompt, steps=3)]
    assert first == want[0]

    # continue decoding from the restart-rewritten caches
    token, pos_i = first, len(prompt)
    for want_next in want[1:]:
        args = leaves + [k_buf, v_buf, np.asarray([token], np.int32),
                         np.asarray(pos_i, np.int32)]
        bufs = [a if not isinstance(a, np.ndarray) else
                backend.buffer_from_pyval(a) for a in args]
        outs = step_exe.execute(bufs)
        nxt = int(np.argmax(np.asarray(outs[0])))
        assert nxt == want_next
        k_buf, v_buf, token = outs[1], outs[2], nxt
        pos_i += 1
