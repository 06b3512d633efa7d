#!/bin/bash
# Tensor-parallel scaling sweep — the reference's examples/n-workers.sh analog.
#
# Where the reference boots N worker processes in screen sessions and wires
# them over TCP (n-workers.sh:1-55), a TPU run is one process whose mesh
# spans the chips: this sweep re-runs the same generate over tp=1,2,4,8 and
# prints the per-token time for each. With JAX_PLATFORMS=cpu (a machine
# without a TPU slice) it uses 8 virtual CPU devices — same code path, same
# collectives.
#
# Usage: examples/n-chips.sh <model.m> <tokenizer.t> [prompt] [steps]
set -e
cd "$(dirname "$0")/.."

MODEL=${1:?usage: n-chips.sh model.m tokenizer.t [prompt] [steps]}
TOKENIZER=${2:?usage: n-chips.sh model.m tokenizer.t [prompt] [steps]}
PROMPT=${3:-"Hello world"}
STEPS=${4:-32}

if [ "$JAX_PLATFORMS" = "cpu" ]; then
  export XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS}"
  echo "(JAX_PLATFORMS=cpu: using 8 virtual CPU devices)"
fi

for TP in 1 2 4 8; do
  echo "=== tp=${TP} ==="
  python -m dllama_tpu.cli inference \
    --model "$MODEL" --tokenizer "$TOKENIZER" \
    --prompt "$PROMPT" --steps "$STEPS" --temperature 0 --tp "$TP" \
    2>&1 | grep -E "Avg|tensor-parallel|Generated" || true
done
