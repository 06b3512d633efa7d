"""End-to-end test of the native PJRT runtime on the real TPU.

Run OUTSIDE pytest's CPU-forced env (fresh process, default backend):

    python scripts/native_e2e.py /tmp/native_export

Exports a tiny random Llama + a synthetic vocab with the current backend's
PJRT plugin options in the manifest, builds native/, then runs
``dllama-native generate`` against the plugin and checks it emits tokens.
Exits 0 on success.

One process holds the chip at a time: the export phase runs in a
SUBPROCESS that exits (releasing the chip) before ``dllama-native`` creates
its own client, and this coordinating parent never imports jax. Whether
the native runtime completes a run on the chip is ROADMAP D8's question;
no chip run of this script is on record.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def export_phase(out_dir: str) -> int:
    """Touches the backend: export model + tokenizer, then EXIT."""
    import jax.numpy as jnp

    from dllama_tpu import export_native
    from dllama_tpu.formats.tokenizer_file import TokenizerData, write_tokenizer
    from dllama_tpu.models import llama
    from dllama_tpu.models.config import ModelConfig

    cfg = ModelConfig(
        arch="llama", dim=128, hidden_dim=256, n_layers=2, n_heads=4,
        n_kv_heads=4, vocab_size=259, seq_len=64, head_size=32, kv_dim=128,
        dtype="bfloat16",
    )
    params = llama.device_random_params(cfg, seed=0)
    export_native.export_model(
        cfg, params, out_dir, cache_dtype=jnp.bfloat16, model_name="tiny-e2e"
    )

    # byte-level vocab: 3 specials + 256 byte tokens = 259 == cfg.vocab_size
    vocab = [b"<unk>", b"<s>", b"</s>"]
    vocab += [f"<0x{b:02X}>".encode() for b in range(256)]
    tok = TokenizerData(vocab=vocab, scores=[0.0] * len(vocab), bos_id=1, eos_id=2)
    write_tokenizer(os.path.join(out_dir, "tokenizer.t"), tok)
    print("export phase done")
    return 0


def main() -> int:
    args = [a for a in sys.argv[1:] if a != "--export-only"]
    out_dir = args[0] if args else "/tmp/dllama_native_e2e"
    if "--export-only" in sys.argv:
        return export_phase(out_dir)

    # phase 1 in a subprocess: its clean exit releases the chip before the
    # native binary asks for it
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), out_dir, "--export-only"],
        timeout=900, cwd=REPO,
    )
    if proc.returncode != 0:
        print("❌ export phase failed")
        return 1

    native = os.path.join(REPO, "native")
    subprocess.run(["make", "-j4"], cwd=native, check=True)
    proc = subprocess.run(
        [
            os.path.join(native, "build", "dllama-native"), "generate",
            "--export-dir", out_dir,
            # long enough that the bucketed prefill path engages (44 byte
            # tokens land in ONE prefill dispatch instead of 43 steps)
            "--prompt", "the quick brown fox jumps over the lazy dog",
            "--steps", "8",
            "--temperature", "0",
        ],
        capture_output=True,
        timeout=600,
    )
    stdout = proc.stdout.decode("utf-8", errors="replace")
    sys.stderr.write(proc.stderr.decode("utf-8", errors="replace"))
    sys.stdout.write(stdout)
    if proc.returncode != 0:
        print("❌ dllama-native failed")
        return 1
    if "Generated tokens" not in stdout:
        print("❌ no generation stats in output")
        return 1
    stderr = proc.stderr.decode("utf-8", errors="replace")
    # "📄 prompt: N tokens in D dispatches" MUST be present and show batching
    # (this run's 44-token prompt fits one 64-token prefill dispatch); a
    # missing line means the prefill path silently stopped engaging
    import re

    mt = re.search(r"prompt: (\d+) tokens in (\d+) dispatches", stderr)
    if not mt:
        print("❌ no prompt-dispatch stats line in stderr")
        return 1
    if int(mt.group(2)) >= int(mt.group(1)) - 1:
        print("❌ prefill did not batch the prompt "
              f"({mt.group(1)} tokens, {mt.group(2)} dispatches)")
        return 1
    print("✅ native e2e OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
