"""Chat mode end-to-end over a subprocess (stdin-driven), including greedy
spec-decode equivalence — run_chat had no runtime coverage at all."""

import os
import subprocess
import sys

import numpy as np
import pytest

from dllama_tpu.formats.spec import ArchType, ModelSpec
from dllama_tpu.formats.tokenizer_file import TokenizerData, write_tokenizer
from dllama_tpu.formats.weights import tensor_plan, write_model
from dllama_tpu.quants import blocks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def demo_files(tmp_path_factory):
    d = tmp_path_factory.mktemp("chat_demo")
    spec = ModelSpec(arch=ArchType.LLAMA, dim=64, hidden_dim=96, n_layers=2,
                     n_heads=4, n_kv_heads=2, vocab_size=300, seq_len=96,
                     weights_float_type=blocks.Q40)
    rng = np.random.default_rng(0)
    write_model(str(d / "m.m"), spec,
                {e.name: 0.05 * rng.standard_normal(e.d * e.n).astype(np.float32)
                 for e in tensor_plan(spec)})
    vocab = [b"<unk>", b"<s>", b"</s>"] + [bytes([i]) for i in range(256)] + [b"hi"] * 41
    write_tokenizer(str(d / "t.t"),
                    TokenizerData(vocab=vocab, scores=[0.0] * 300, bos_id=1, eos_id=2))
    return str(d / "m.m"), str(d / "t.t")


def _normalize(out: str) -> str:
    """Blank out wall-clock-dependent text (load-time line) so transcript
    equality tests don't flake on timing jitter between two runs."""
    import re
    return re.sub(r"loaded weights in \d+\.\d+s", "loaded weights in Xs", out)


def run_chat(demo_files, *extra, turns=("hi", "hi again")):
    model, tok = demo_files
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_PLATFORM_NAME", None)
    proc = subprocess.run(
        [sys.executable, "-m", "dllama_tpu.cli", "chat", "--model", model,
         "--tokenizer", tok, "--steps", "6", "--temperature", "0", "--tp", "1",
         "--system-prompt", "", "--chat-template", "llama2", *extra],
        input="\n".join(turns) + "\n", capture_output=True, text=True,
        env=env, cwd=REPO, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout


def test_chat_two_turns(demo_files):
    out = run_chat(demo_files)
    assert out.count("🤖 Assistant:") == 2


def test_chat_spec_matches_plain(demo_files):
    """Greedy chat transcripts must be identical with and without
    speculative drafting (exactness across multi-turn sessions + history)."""
    plain = run_chat(demo_files)
    spec = run_chat(demo_files, "--spec-draft", "4")
    assert _normalize(plain) == _normalize(spec)


def test_chat_spec_sampled_matches_plain(demo_files):
    """Sampled chat (same --seed) must transcript-match with and without
    speculative drafting: the spec path replays the same engine key chain.
    (argparse is last-wins, so the extra flags override run_chat's defaults.)"""
    sampled = ("--temperature", "0.8", "--seed", "42")
    assert _normalize(run_chat(demo_files, *sampled)) == _normalize(
        run_chat(demo_files, *sampled, "--spec-draft", "4"))
