"""Weight-file (.m) and tokenizer-file (.t) roundtrip tests."""

import numpy as np
import pytest

from dllama_tpu.formats import tokenizer_file
from dllama_tpu.formats.spec import ArchType, HiddenAct, ModelSpec, parse_header, write_header
from dllama_tpu.formats.weights import (ModelWriter, WeightFileReader, tensor_plan,
                                        write_model)
from dllama_tpu.quants import blocks


def tiny_spec(wft=blocks.F32, arch=ArchType.LLAMA, n_experts=0):
    return ModelSpec(
        arch=arch,
        dim=64,
        hidden_dim=96,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        vocab_size=128,
        seq_len=32,
        n_experts=n_experts,
        n_active_experts=2 if n_experts else 0,
        hidden_act=HiddenAct.GELU if arch == ArchType.GROK1 else HiddenAct.SILU,
        rope_theta=10000.0,
        weights_float_type=wft,
    )


def random_tensors(spec, seed=0):
    rng = np.random.default_rng(seed)
    out = {}
    for e in tensor_plan(spec):
        out[e.name] = rng.standard_normal(e.d * e.n).astype(np.float32) * 0.05
    return out


def test_header_roundtrip():
    spec = tiny_spec(wft=blocks.Q40)
    raw = write_header(spec)
    parsed = parse_header(raw + b"\x00" * 64)
    assert parsed.arch == spec.arch
    assert parsed.dim == spec.dim
    assert parsed.hidden_dim == spec.hidden_dim
    assert parsed.n_kv_heads == 2
    assert parsed.weights_float_type == blocks.Q40
    assert parsed.header_size == len(raw)
    assert parsed.kv_dim == 32
    assert parsed.head_size == 16


@pytest.mark.parametrize("wft", [blocks.F32, blocks.F16, blocks.Q40, blocks.Q80])
def test_model_file_roundtrip(tmp_path, wft):
    spec = tiny_spec(wft=wft)
    tensors = random_tensors(spec)
    path = str(tmp_path / "model.m")
    write_model(path, spec, tensors)
    with WeightFileReader(path) as r:
        assert r.spec.dim == spec.dim
        assert r.spec.weights_float_type == wft
        # values ~N(0, 0.05): q40 err <= absmax/8 ~= 0.03, q80 err <= absmax/254 ~= 1e-3
        tol = {blocks.F32: 0.0, blocks.F16: 2e-4, blocks.Q40: 0.04, blocks.Q80: 1.5e-3}[wft]
        for e in r.entries:
            got = r.read_tensor(e.name)
            want = tensors[e.name].reshape(e.shape)
            if e.float_type == blocks.F32:
                np.testing.assert_array_equal(got, want)
            else:
                assert np.max(np.abs(got - want)) <= tol, e.name


def test_writer_takes_pre_encoded_tensors(tmp_path):
    """write_next_raw appends bytes encoded once by the caller: the file is
    byte-identical to the one write_next makes (checksums included), and a
    wrong size or a tensor out of order is refused."""
    spec = tiny_spec(wft=blocks.Q40)
    tensors = random_tensors(spec)
    a, b = str(tmp_path / "a.m"), str(tmp_path / "b.m")
    write_model(a, spec, tensors)
    with ModelWriter(b, spec) as w:
        for e in w.plan:
            w.write_next_raw(e.name, blocks.encode_tensor(tensors[e.name], e.float_type))
    with open(a, "rb") as fa, open(b, "rb") as fb:
        assert fa.read() == fb.read()
    with WeightFileReader(b) as r:
        assert r.verify()["ok"]

    w = ModelWriter(str(tmp_path / "c.m"), spec)
    first = w.plan[0]
    raw = blocks.encode_tensor(tensors[first.name], first.float_type)
    with pytest.raises(ValueError, match="encoded bytes"):
        w.write_next_raw(first.name, raw[:-1])
    with pytest.raises(ValueError, match="order violation"):
        w.write_next_raw(w.plan[1].name, raw)
    w._f.close()


def test_moe_grok_plan(tmp_path):
    spec = tiny_spec(arch=ArchType.GROK1, n_experts=4)
    names = [e.name for e in tensor_plan(spec)]
    assert "layers.0.moe_router" in names
    assert "layers.0.experts.3.down" in names
    assert "layers.1.rms_moe" in names and "layers.1.rms_ffn2" in names
    assert "layers.0.w1" not in names
    tensors = random_tensors(spec)
    path = str(tmp_path / "grok.m")
    write_model(path, spec, tensors)
    with WeightFileReader(path) as r:
        assert r.spec.is_moe and r.spec.n_experts == 4
        x = r.read_tensor("layers.1.experts.2.gate")
        assert x.shape == (spec.hidden_dim, spec.dim)


def test_read_tensor_rows(tmp_path):
    spec = tiny_spec(wft=blocks.Q80)
    tensors = random_tensors(spec)
    path = str(tmp_path / "m.m")
    write_model(path, spec, tensors)
    with WeightFileReader(path) as r:
        full = r.read_tensor("layers.0.w1")
        band = r.read_tensor_rows("layers.0.w1", slice(24, 48))
        np.testing.assert_array_equal(full[24:48], band)


def test_tokenizer_roundtrip(tmp_path):
    vocab = [b"<unk>", b"<s>", b"</s>", b" hello", b"world", b"\xe4\xb8\xad"]
    scores = [0.0, 0.0, 0.0, -1.0, -2.5, -3.0]
    tok = tokenizer_file.TokenizerData(vocab=vocab, scores=scores, bos_id=1, eos_id=2)
    path = str(tmp_path / "tok.t")
    tokenizer_file.write_tokenizer(path, tok)
    back = tokenizer_file.read_tokenizer(path)
    assert back.vocab == vocab
    assert back.bos_id == 1 and back.eos_id == 2 and back.pad_id == -1
    np.testing.assert_allclose(back.scores, scores, rtol=1e-6)
    assert back.max_token_length == 6
