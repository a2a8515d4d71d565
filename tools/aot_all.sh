#!/bin/bash
# One command for the whole offline-TPU-evidence suite (no chip needed):
#   whole-step HBM/collectives (aot_tpu.py, flagship b16/b32 + presets)
#   routed-kernel battery        (aot_kernels.py)
#   multichip PP/TP/ZeRO + SP    (aot_multichip.py, 8 chips)
#   composed serving bf16 + int8 (aot_infer.py, s8-verified)
# Results land in tools/aot_r{N}_*.jsonl-style files named by $1.
set -u
REPO="$(cd "$(dirname "$0")/.." && pwd)"
TAG="${1:-local}"
ENV=(env JAX_PLATFORMS=cpu TPU_LOG_DIR=disabled)
cd "$REPO"
"${ENV[@]}" python tools/aot_tpu.py --preset ds2_full --batch 16 --frames 800 \
  --ndev 1 --rnn-impl pallas --loss-impl pallas > "tools/aot_step_$TAG.jsonl"
"${ENV[@]}" python tools/aot_tpu.py --preset ds2_full --batch 32 --frames 800 \
  --ndev 1 --rnn-impl pallas --loss-impl pallas >> "tools/aot_step_$TAG.jsonl"
"${ENV[@]}" python tools/aot_kernels.py > "tools/aot_kernels_$TAG.jsonl"
"${ENV[@]}" python tools/aot_multichip.py > "tools/aot_multichip_$TAG.jsonl"
"${ENV[@]}" python tools/aot_infer.py > "tools/aot_infer_$TAG.jsonl"
echo "=== aot_all done $(date) ==="
