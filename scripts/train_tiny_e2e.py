"""Train → convert → serve, end to end, with zero network access.

The reference's purpose is serving *published* Q40 checkpoints
(`/root/reference/download-model.py:5-26`). This environment has no egress,
so this script produces the closest verifiable equivalent: it TRAINS a tiny
byte-level Llama on an embedded corpus with the framework's own training
step, writes the weights through the real `.m` writer as Q40 (the same
format + quantizer published checkpoints use), writes a real `.t` byte
tokenizer, then drives `dllama_tpu.cli generate` on the files as a
subprocess — proving the whole publish-side and serve-side pipeline:

    make_train_step → ModelWriter(q40) → WeightFileReader →
    quant_params_from_reader → Engine decode → sane text out.

"Sane text" is checkable because the model memorizes the corpus: greedy
decoding from a corpus prefix must reproduce the corpus continuation
(the same determinism check as the reference's `examples/macbeth.sh`).

Usage:  python scripts/train_tiny_e2e.py [outdir] [--steps N] [--no-cli]
Writes  outdir/tiny.m, outdir/tiny.t, outdir/e2e_result.json
Exit 0 only if the generated continuation matches the corpus.

One process holds the chip at a time, so a parent that trained on the TPU
must not start a CLI child that needs it. Run the two halves as separate
processes there:

    python scripts/train_tiny_e2e.py outdir --no-cli     # train + in-process
    python scripts/train_tiny_e2e.py outdir --cli-only   # CLI drive, fresh

``--cli-only`` never imports jax in the parent — the CLI subprocess gets the
chip. (Off-TPU the combined run stays fine: the child is forced onto CPU.)
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# The corpus the model memorizes: the same Macbeth soliloquy the reference's
# determinism example feeds (`/root/reference/examples/macbeth.sh` uses it as
# a long prompt; here it is the training set). Public-domain Shakespeare.
CORPUS = (
    "Tomorrow, and tomorrow, and tomorrow, creeps in this petty pace "
    "from day to day, to the last syllable of recorded time; and all our "
    "yesterdays have lighted fools the way to dusty death. Out, out, brief "
    "candle! Life's but a walking shadow, a poor player that struts and "
    "frets his hour upon the stage, and then is heard no more. It is a tale "
    "told by an idiot, full of sound and fury, signifying nothing. "
)


def build_byte_tokenizer(path: str):
    """A real `.t` file with byte-fallback-only vocab: 3 specials + 256 byte
    tokens. Encoding any text works via the tokenizer's byte fallback; no
    merges needed for a memorization demo."""
    from dllama_tpu.formats.tokenizer_file import TokenizerData, write_tokenizer

    vocab = [b"<unk>", b"<s>", b"</s>"] + [bytes([i]) for i in range(256)]
    tok = TokenizerData(vocab=vocab, scores=[0.0] * len(vocab), bos_id=1, eos_id=2)
    write_tokenizer(path, tok)
    return tok


#: prompt/expected split shared by the in-process and CLI gates (tokens of
#: one full-corpus encoding; byte vocab maps token n to CORPUS[n-1])
N_PROMPT, N_STEPS = 100, 85


def drive_cli(outdir: str, child_on_cpu: bool) -> bool:
    """THE CLI-drive block, shared by the combined off-TPU flow and the
    --cli-only phase so the command, tolerance, and verdict can't drift.
    ``child_on_cpu``: force the child onto CPU (the combined flow, whose
    parent ran there); otherwise it inherits this process's environment."""
    m_path = os.path.join(outdir, "tiny.m")
    t_path = os.path.join(outdir, "tiny.t")
    prompt = CORPUS[:N_PROMPT - 1]
    expected = CORPUS[N_PROMPT - 1:N_PROMPT - 1 + N_STEPS]
    env = dict(os.environ, PYTHONPATH=REPO)
    if child_on_cpu:
        env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "dllama_tpu.cli", "generate",
         "--model", m_path, "--tokenizer", t_path,
         "--prompt", prompt, "--steps", str(N_STEPS),
         "--temperature", "0"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=1200)
    # same 95% tolerance as the in-process gate: require the expected
    # prefix, not the whole continuation verbatim
    cli_ok = (proc.returncode == 0
              and expected[:int(0.95 * len(expected))] in proc.stdout)
    print(f"CLI generate: rc={proc.returncode} match={cli_ok}")
    if not cli_ok:
        print(proc.stdout[-1500:])
        print(proc.stderr[-1500:])
    return cli_ok


def cli_phase(outdir: str) -> int:
    """--cli-only: drive the CLI on an existing outdir — jax is never
    imported in this process (see module docstring), so the child gets the
    chip. Merges its verdict into e2e_result.json. The child runs on
    whatever ``JAX_PLATFORMS`` in the inherited environment says."""
    m_path = os.path.join(outdir, "tiny.m")
    t_path = os.path.join(outdir, "tiny.t")
    res_path = os.path.join(outdir, "e2e_result.json")
    if not (os.path.exists(m_path) and os.path.exists(t_path)):
        print(f"--cli-only but {m_path} / {t_path} missing "
              "(run the training phase first)")
        return 2
    cli_ok = drive_cli(outdir, child_on_cpu=False)
    result = {}
    if os.path.exists(res_path):
        with open(res_path) as f:
            result = json.load(f)
    result["cli_ok"] = bool(cli_ok)
    with open(res_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return 0 if cli_ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("outdir", nargs="?", default="results/train_tiny_e2e")
    ap.add_argument("--steps", type=int, default=2500, help="max train steps")
    ap.add_argument("--no-cli", action="store_true",
                    help="skip the CLI subprocess drive (in-process check only)")
    ap.add_argument("--serve-only", action="store_true",
                    help="skip training; serve an existing outdir/tiny.m "
                         "(e.g. re-drive a CPU-trained model on the TPU)")
    ap.add_argument("--cli-only", action="store_true",
                    help="only the CLI subprocess drive against an existing "
                         "outdir; the parent never imports jax (the chip "
                         "goes wholly to the child)")
    args = ap.parse_args(argv)
    os.makedirs(args.outdir, exist_ok=True)

    if args.cli_only:
        return cli_phase(args.outdir)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dllama_tpu.formats.spec import ArchType, ModelSpec
    from dllama_tpu.formats.weights import tensor_plan, write_model
    from dllama_tpu.models import llama
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.quants import blocks
    from dllama_tpu.runtime.train import make_train_step
    from dllama_tpu.tokenizer.bpe import Tokenizer
    from dllama_tpu.formats.tokenizer_file import read_tokenizer

    t_path = os.path.join(args.outdir, "tiny.t")
    m_path = os.path.join(args.outdir, "tiny.m")
    f32_path = os.path.join(args.outdir, "tiny_f32.m")
    build_byte_tokenizer(t_path)
    tokenizer = Tokenizer(read_tokenizer(t_path))

    # Tiny but real Llama: all dims q40-block-aligned (dim, hidden % 32;
    # hidden % 64 so the quantized FFN loads as packed planes, not fallback).
    spec = ModelSpec(
        arch=ArchType.LLAMA, dim=256, hidden_dim=704, n_layers=4,
        n_heads=8, n_kv_heads=4, vocab_size=tokenizer.vocab_size,
        seq_len=256, weights_float_type=blocks.Q40,
    )
    cfg = ModelConfig.from_spec(spec, dtype="float32")

    corpus_ids = tokenizer.encode(CORPUS, add_bos=False)
    bos = tokenizer.bos_id
    print(f"corpus: {len(CORPUS)} chars -> {len(corpus_ids)} byte tokens")

    # Training batches: every T-token window over the wrapped corpus, PLUS a
    # BOS-anchored variant of each (generation feeds BOS + prompt, so BOS
    # must be in-distribution; windows start at every offset, so position
    # can't identify corpus location). T bounds the TRAINED rope positions:
    # generation must stay within prompt+steps <= T or the rollout walks
    # into positions the model has never seen.
    T = 192
    stream = corpus_ids * (2 + (T * 8) // len(corpus_ids))
    windows = []
    for start in range(0, len(corpus_ids)):
        w = stream[start:start + T]
        if len(w) == T:
            windows.append(w)
            windows.append([bos] + w[:-1])
    data = np.asarray(windows, dtype=np.int32)
    print(f"train windows: {data.shape}")

    final_loss, train_s = None, 0.0
    if args.serve_only:
        if not os.path.exists(m_path):
            print(f"--serve-only but {m_path} does not exist")
            return 2
        print(f"serve-only: reusing {m_path}")
    else:
        params = llama.random_params(cfg, seed=0)
        opt = optax.adamw(optax.warmup_cosine_decay_schedule(
            0.0, 3e-3, 50, args.steps, 3e-4), weight_decay=0.01)
        opt_state = opt.init(params)
        step = jax.jit(make_train_step(cfg, opt), donate_argnums=(0, 1))

        rng = np.random.default_rng(0)
        B = 8
        t0 = time.perf_counter()
        loss = float("nan")
        for i in range(args.steps):
            batch = data[rng.integers(0, len(data), B)]
            params, opt_state, loss = step(params, opt_state, batch)
            # sync with the device at most every 50 steps: float(loss) blocks
            # on the step; a per-step host round trip serializes the loop
            if i % 50 == 0 or i == args.steps - 1:
                cur = float(loss)
                if i % 100 == 0 or i == args.steps - 1:
                    print(f"step {i:4d}  loss {cur:.4f}")
                if cur < 0.012:
                    print(f"step {i:4d}  loss {cur:.4f} — memorized, stopping")
                    break
        train_s = time.perf_counter() - t0
        final_loss = float(loss)

        # ---- write the trained weights through the real .m writer (Q40) ----
        params = jax.device_get(params)
        tensors = {"token_embedding": np.asarray(params["embedding"], np.float32),
                   "rms_final": np.asarray(params["rms_final"], np.float32),
                   "wcls": np.asarray(params["wcls"], np.float32).T}
        for i in range(spec.n_layers):
            for name in ("wq", "wk", "wv", "wo", "w1", "w2", "w3"):
                tensors[f"layers.{i}.{name}"] = np.asarray(
                    params["layers"][name][i], np.float32).T
            for name in ("rms_att", "rms_ffn"):
                tensors[f"layers.{i}.{name}"] = np.asarray(
                    params["layers"][name][i], np.float32)
        write_model(m_path, spec, {e.name: tensors[e.name].reshape(-1)
                                   for e in tensor_plan(spec)})
        print(f"wrote {m_path} ({os.path.getsize(m_path) / 1e6:.1f} MB q40)")
        # f32 twin for quantization-noise diagnosis (same tensors, F32 file)
        import dataclasses as _dc
        spec_f32 = _dc.replace(spec, weights_float_type=blocks.F32,
                               header_size=0)
        write_model(f32_path, spec_f32,
                    {e.name: tensors[e.name].reshape(-1)
                     for e in tensor_plan(spec_f32)})

    # ---- serve it back through the quantized engine ----
    # Token-level check: the greedy continuation of a corpus prefix must be
    # the corpus suffix. encode() prepends a SentencePiece-style dummy space
    # (like the reference tokenizer), so the prompt/expected split is done on
    # TOKENS of one full-corpus encoding — never by slicing decoded chars.
    n_prompt, n_steps = N_PROMPT, N_STEPS  # rollout stays within trained T
    prompt_ids = [bos] + corpus_ids[:n_prompt]  # BOS + corpus prefix
    expected_ids = corpus_ids[n_prompt:n_prompt + n_steps]
    # byte vocab: corpus_ids = [dummy-space] + one token per corpus char, so
    # token index n maps to CORPUS[n-1]; these strings are what the CLI run
    # feeds/checks (its encode() re-adds the same dummy space)
    prompt = CORPUS[:n_prompt - 1]
    expected = CORPUS[n_prompt - 1:n_prompt - 1 + n_steps]

    from dllama_tpu.formats.weights import WeightFileReader
    from dllama_tpu.runtime.generate import Engine
    from dllama_tpu.runtime.sampler import SamplerConfig

    reader = WeightFileReader(m_path)
    qparams = llama.quant_params_from_reader(reader, cfg)
    engine = Engine(cfg, qparams, SamplerConfig(temperature=0.0))
    toks, prefill_ms, decode_ms = engine.generate_fused(prompt_ids, steps=n_steps)
    completion = tokenizer.decode(list(toks))
    ms_tok = decode_ms / max(1, len(toks) - 1)

    def prefix_match(got, want) -> int:
        n = 0
        for a, b in zip(got, want):
            if a != b:
                break
            n += 1
        return n

    n_match = prefix_match(toks, expected_ids)
    print(f"prompt tail: ...{prompt[-40:]!r}")
    print(f"completion : {completion[:80]!r}")
    print(f"expected   : {expected[:80]!r}")
    print(f"match: {n_match}/{len(expected_ids)} tokens;"
          f" {ms_tok:.2f} ms/token ({1000.0 / ms_tok:.1f} tok/s) on"
          f" {jax.devices()[0].platform}")
    in_process_ok = n_match >= int(0.95 * len(expected_ids))

    if not in_process_ok and os.path.exists(f32_path):
        # q40 noise or underfit? The f32 twin answers.
        with WeightFileReader(f32_path) as r32:
            p32 = llama.params_from_reader(r32, ModelConfig.from_spec(r32.spec))
        e32 = Engine(cfg, p32, SamplerConfig(temperature=0.0))
        t32, _, _ = e32.generate_fused(prompt_ids, steps=n_steps)
        m32 = prefix_match(t32, expected_ids)
        print(f"f32 twin match: {m32}/{len(expected_ids)} tokens — "
              + ("quantization noise is the gap" if m32 > n_match + 10
                 else "underfit, not quantization"))

    # ---- and through the actual CLI, as a user would ----
    cli_ok = None
    if not args.no_cli and jax.default_backend() == "tpu":
        # this parent HOLDS the chip; a CLI child that needs it would fail
        # or hang. The CLI drive is its own --cli-only run after this
        # process exits.
        print("on TPU: skipping in-process CLI drive — run "
              f"`python {sys.argv[0]} {args.outdir} --cli-only` next")
        args.no_cli = True
    if not args.no_cli:
        # off-TPU: the child runs where this parent ran
        cli_ok = drive_cli(args.outdir, child_on_cpu=True)

    result = {
        "final_loss": final_loss, "train_seconds": round(train_s, 1),
        "model_bytes": os.path.getsize(m_path),
        "platform": jax.devices()[0].platform,
        "decode_ms_per_token": round(ms_tok, 3),
        "match_tokens": n_match, "expected_tokens": len(expected_ids),
        "in_process_ok": bool(in_process_ok), "cli_ok": cli_ok,
    }
    with open(os.path.join(args.outdir, "e2e_result.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    ok = in_process_ok and (cli_ok is not False)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
