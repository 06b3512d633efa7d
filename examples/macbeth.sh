#!/bin/bash
# Long-prompt determinism check — the reference's examples/macbeth.sh analog
# (macbeth.sh:1-125: feed a long prompt at temperature~0 and compare the
# continuation against an expected string).
#
# Without model downloads in this environment, the check uses a synthetic
# seeded model: greedy decoding must be bit-deterministic, so two runs with
# the same seed must produce IDENTICAL output, and a third run with a longer
# prompt must still match its own re-run. Any nondeterminism in the
# kernels/collectives fails the diff.
#
# Usage: examples/macbeth.sh [model.m tokenizer.t]
# Set JAX_PLATFORMS=cpu to force the CPU backend (e.g. no TPU attached).
#
# Published-checkpoint mode (network required — this build environment is
# zero-egress, so it only works where HuggingFace is reachable):
#   MACBETH_DOWNLOAD=tinyllama examples/macbeth.sh
# downloads the published TinyLlama-1.1B Q40 checkpoint via
# dllama_tpu.convert.download (same files the reference's launcher fetches)
# and runs the determinism check against the real model; with
# MACBETH_EXPECT set, the continuation must also start with that string
# (the reference pins an expected Macbeth continuation the same way).
set -e
cd "$(dirname "$0")/.."

if [ -n "$MACBETH_DOWNLOAD" ]; then
  # e.g. MACBETH_DOWNLOAD=tinylama_1.1b_3t_q40 (see convert/download.py MODELS)
  python - <<PYEOF
from dllama_tpu.convert.download import download_model
download_model("$MACBETH_DOWNLOAD", "/tmp/dllama_models")
PYEOF
  NAME=$(python -c "from dllama_tpu.convert.download import ALIASES; n='$MACBETH_DOWNLOAD'.replace('-','_'); print(ALIASES.get(n, n))")
  MODEL="/tmp/dllama_models/$NAME/dllama_model_$NAME.m"
  TOKENIZER="/tmp/dllama_models/$NAME/dllama_tokenizer_$NAME.t"
else
  MODEL=${1:-/tmp/dllama_macbeth_demo.m}
  TOKENIZER=${2:-/tmp/dllama_macbeth_demo.t}
fi

if [ ! -f "$MODEL" ]; then
  echo "building synthetic demo model at $MODEL"
  python - "$MODEL" "$TOKENIZER" <<'EOF'
import sys
import numpy as np
from dllama_tpu.formats.spec import ModelSpec, ArchType
from dllama_tpu.formats.weights import write_model, tensor_plan
from dllama_tpu.formats.tokenizer_file import TokenizerData, write_tokenizer
from dllama_tpu.quants import blocks
spec = ModelSpec(arch=ArchType.LLAMA, dim=128, hidden_dim=256, n_layers=4, n_heads=8,
                 n_kv_heads=4, vocab_size=259, seq_len=256, weights_float_type=blocks.Q40)
rng = np.random.default_rng(0)
write_model(sys.argv[1], spec,
            {e.name: 0.05*rng.standard_normal(e.d*e.n).astype(np.float32)
             for e in tensor_plan(spec)})
vocab = [b"<unk>", b"<s>", b"</s>"] + [f"<0x{b:02X}>".encode() for b in range(256)]
write_tokenizer(sys.argv[2], TokenizerData(vocab=vocab, scores=[0.0]*259, bos_id=1, eos_id=2))
EOF
fi

if [ -n "$MACBETH_BUILD_ONLY" ]; then
  exit 0  # multihost.sh reuses the model builder above
fi

PROMPT="Tomorrow, and tomorrow, and tomorrow, creeps in this petty pace from day to day, \
to the last syllable of recorded time; and all our yesterdays have lighted fools the way \
to dusty death."

run() {
  python -m dllama_tpu.cli generate --model "$MODEL" --tokenizer "$TOKENIZER" \
    --prompt "$PROMPT" --steps 48 --temperature 0 --seed 1 2>/dev/null \
    | grep -v "^Avg\|^Generated\|^Prefill"
}

A=$(run)
B=$(run)
if [ "$A" != "$B" ]; then
  echo "❌ nondeterministic greedy decode"
  diff <(echo "$A") <(echo "$B") || true
  exit 1
fi
echo "✅ deterministic: two greedy runs produced identical continuations"

if [ -n "$MACBETH_EXPECT" ]; then
  case "$A" in
    "$MACBETH_EXPECT"*)
      echo "✅ continuation matches the pinned expectation" ;;
    *)
      echo "❌ continuation diverged from the pinned expectation"
      echo "expected prefix: $MACBETH_EXPECT"
      echo "got: $A"
      exit 1 ;;
  esac
fi
