"""Llama-3-70B-scale direct weight sync (VERDICT r3 item 8).

The llama8b harness run at REAL 70B shard shapes — hidden 8192,
intermediate 28672, 64 heads / 8 kv heads, 128256 vocab — with a reduced
layer count (default 8 of 80: the full model is ~141 GB bf16, ~3x too big
for source + registered staging + dest buffers on one host). Per-tensor
shapes, and therefore per-transfer behavior (segment sizes, plan shapes,
copy granularity), match the real model exactly; only the tensor COUNT is
reduced.

Run:  python benchmarks/llama70b_sync.py [--layers 8] [--dtype bfloat16]

Measures the buffered path and the direct + registered-staging path
(publish is copy-free; the pull moves each byte once).
"""

import argparse
import asyncio

from llama8b_sync import run

if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--layers", type=int, default=8)
    args = ap.parse_args()
    asyncio.run(run(args.dtype, 1.0, model="70b", layers=args.layers))
