#!/bin/bash
# System-level smoke of every example on the virtual CPU mesh
# (SURVEY §4 category 4: smoke tests as system tests).
set -e
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu \
       XLA_FLAGS=--xla_force_host_platform_device_count=8

echo "== smoke_testing =="
python examples/smoke_testing/simple.py --local --steps 3
python examples/smoke_testing/attention.py
python examples/smoke_testing/conv.py

echo "== GPT2 (auto plan / pipeline / collective pipeline) =="
python examples/GPT2/main.py --config test --batch 8 --seq 32 --steps 2
python examples/GPT2/main.py --config test --batch 8 --seq 32 --steps 2 \
    --num_stages 2 --num_micro_batches 2
python examples/GPT2/main.py --config test --batch 8 --seq 32 --steps 2 \
    --num_stages 2 --num_micro_batches 2 --pipeline collective

echo "== generate (sampling over RPC, server-held weights) =="
python examples/GPT2/generate.py --local --config test --steps 2 \
    --max_new_tokens 8 --temperature 0.8 --top_k 20

echo "== PP x TP (stage x model nesting, config mode) =="
INTRA_STAGE_TP=2 VAR_MEM_LIMIT=$((6<<20)) \
python examples/GPT2/main.py --config test --batch 8 --seq 32 --steps 2 \
    --num_stages 2 --num_micro_batches 2

echo "== long context (ring / ulysses) =="
python examples/GPT2/long_context.py --config test --batch 2 --seq 64 \
    --steps 2 --impl ring
python examples/GPT2/long_context.py --config test --batch 2 --seq 64 \
    --steps 2 --impl ulysses

echo "== wide_resnet =="
python examples/wide_resnet/train_imagenet.py --model_type -1 --batch 16 \
    --image_size 32 --steps 2

echo "== llama (einsum + flash attention) =="
python examples/llama/train.py --config test --batch 4 --seq 32 --steps 2
python examples/llama/train.py --config test --batch 4 --seq 32 --steps 2 \
    --attn flash

echo "== gpt_moe =="
python examples/gpt_moe/pretrain_gpt_moe.py --config test --batch 4 \
    --seq 32 --steps 2

echo "== auto_explore (fully automatic service-side planning) =="
python examples/auto_explore/main.py --steps 2
python examples/auto_explore/main.py --steps 2 --regime pipeline

echo "ALL EXAMPLES OK"
