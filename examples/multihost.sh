#!/bin/bash
# Multi-host launch recipe — the reference's root+workers bootstrap analog
# (README "How to run": dllama worker on each node, then dllama inference
# --workers on the root). Under SPMD there is no root/worker asymmetry:
# EVERY host runs the same command with its own --host-id, and JAX forms one
# mesh across all hosts' chips (collectives ride ICI within a slice, DCN
# across slices).
#
# On host 0 (the "root" — its stdout is the one you read):
#   python -m dllama_tpu.cli generate --model m.m --tokenizer t.t \
#     --prompt "Hello" --steps 64 --seed 1 \
#     --coordinator host0:8476 --num-hosts 2 --host-id 0
#
# On host 1..N-1 (the "workers"):
#   python -m dllama_tpu.cli worker --model m.m --tokenizer t.t \
#     --prompt "Hello" --steps 64 --seed 1 \
#     --coordinator host0:8476 --num-hosts 2 --host-id 1
#
# Notes:
# * --model/--prompt/--steps/--seed must be IDENTICAL everywhere (one SPMD
#   program; a worker is just a host whose stdout is suppressed).
# * --seed is required implicitly: hosts must agree (the CLI forces seed=0
#   in multi-host runs when unset).
# * Each host loads only its own weight shards — no host ever streams
#   weights to another, unlike the reference's startup distribution
#   (/root/reference/src/transformer.cpp:569-598).
#
# DEMO MODE (default when run without arguments): launches the pattern above
# as two LOCAL processes on the CPU backend — a real jax.distributed job on
# one machine, same flags, so the bootstrap is demonstrably runnable without
# a cluster (the two-process variant of tests/test_multihost.py).
set -e
cd "$(dirname "$0")/.."

PORT=${MULTIHOST_PORT:-8476}
MODEL=${1:-/tmp/dllama_macbeth_demo.m}
TOKENIZER=${2:-/tmp/dllama_macbeth_demo.t}

if [ ! -f "$MODEL" ]; then
  # reuse macbeth.sh's synthetic model builder
  MACBETH_BUILD_ONLY=1 bash examples/macbeth.sh "$MODEL" "$TOKENIZER" || true
fi
if [ ! -f "$MODEL" ]; then
  echo "no model available; run examples/macbeth.sh first"; exit 1
fi

run_host() {
  JAX_PLATFORMS=cpu python -m dllama_tpu.cli "$2" \
    --model "$MODEL" --tokenizer "$TOKENIZER" \
    --prompt "Tomorrow, and tomorrow" --steps 8 --temperature 0 --seed 1 \
    --coordinator "127.0.0.1:$PORT" --num-hosts 2 --host-id "$1" \
    > "/tmp/multihost_demo_$1.log" 2>&1 &
}

echo "launching 2-process jax.distributed demo (CPU backend)..."
run_host 1 worker; P1=$!
run_host 0 generate; P0=$!
FAIL=0
wait "$P0" || FAIL=1
wait "$P1" || FAIL=1
if [ "$FAIL" != 0 ]; then
  echo "❌ demo failed"; tail -n 5 /tmp/multihost_demo_0.log /tmp/multihost_demo_1.log; exit 1
fi
echo "✅ two-host SPMD demo completed; host 0 output:"
grep -v "^💡\|^🧮\|^⏩" /tmp/multihost_demo_0.log | tail -6
