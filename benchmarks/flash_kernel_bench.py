"""Achieved TFLOP/s of the attention kernels on the local device.

Times three implementations at the same shapes and prints TFLOP/s rows:

- ``flash_attention_stats`` — the fused stats kernel (ring attention's
  per-hop production engine; XLA cannot emit its unnormalized acc/m/l)
- ``flash_attention`` — the normalized pallas twin (template / eager win)
- ``jax.nn.dot_product_attention`` — XLA's fused kernel (the model's dense
  path, models/llama.py)

FLOP accounting matches benchmarks/ring_attention_bench.py: 2 matmuls of
2*m*n*k each, halved when causal (the kernels skip fully-masked blocks).
Pass ``--peak-tflops`` (the chip's bf16 peak) to get an MFU%% column.

Run on the chip:  python benchmarks/flash_kernel_bench.py
CPU validation:   JAX_PLATFORMS=cpu python benchmarks/flash_kernel_bench.py \
                      --iters 2 --allow-interpret
(interpret-mode pallas on CPU is orders of magnitude slower — validation
checks the harness, not the numbers). Without a TPU (``platform != "tpu"``)
the bench warns loudly and exits nonzero unless ``--allow-interpret`` is
passed: interpret-mode TFLOP/s rows must never be mistaken for hardware
numbers.
"""

import argparse
import statistics
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument(
        "--causal", action=argparse.BooleanOptionalAction, default=True
    )
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument(
        "--peak-tflops",
        type=float,
        default=None,
        help="chip bf16 peak for an MFU%% column (e.g. 197 for v5e)",
    )
    ap.add_argument(
        "--allow-interpret",
        action="store_true",
        help="proceed on CPU (pallas interpret mode) instead of exiting "
        "nonzero — harness validation only, the numbers are meaningless",
    )
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from torchstore_tpu.ops.flash_attention import (
        flash_attention,
        flash_attention_stats,
    )
    from torchstore_tpu.utils import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    on_device = dev.platform == "tpu"
    if not on_device:
        print(
            "#" * 72
            + f"\n# WARNING: no TPU (platform={dev.platform!r}) — pallas "
            "kernels would run\n# in INTERPRET mode; TFLOP/s rows would be "
            "meaningless as hardware numbers."
            + (
                "\n# Proceeding because --allow-interpret was passed "
                "(harness validation)."
                if args.allow_interpret
                else "\n# Refusing to emit them; pass --allow-interpret to "
                "validate the harness."
            )
            + "\n"
            + "#" * 72,
            file=sys.stderr,
        )
        if not args.allow_interpret:
            sys.exit(2)
    dtype = jnp.bfloat16 if on_device else jnp.float32
    b, s, h, d = args.batch, args.seq, args.heads, args.head_dim
    keys = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(keys[0], (b, s, h, d), dtype)
    k = jax.random.normal(keys[1], (b, s, h, d), dtype)
    v = jax.random.normal(keys[2], (b, s, h, d), dtype)
    full = 2 * (2.0 * b * h * s * s * d)
    flops = full / 2 if args.causal else full
    print(
        f"# device {dev.device_kind or dev.platform}, dtype {dtype.__name__}, "
        f"shape b{b} s{s} h{h} d{d}, causal={args.causal}",
        file=sys.stderr,
    )

    def timed(label, fn):
        out = fn()
        jax.block_until_ready(out)  # compile
        times = []
        for _ in range(args.iters):
            t0 = time.perf_counter()
            out = fn()
            jax.block_until_ready(out)
            times.append(time.perf_counter() - t0)
        sec = statistics.median(times)
        tfs = flops / sec / 1e12
        mfu = (
            f", MFU {100 * tfs / args.peak_tflops:.0f}%"
            if args.peak_tflops
            else ""
        )
        print(f"# {label}: {sec*1e3:.3f} ms, {tfs:.1f} TFLOP/s{mfu}", file=sys.stderr)

    timed(
        "xla dot_product_attention (dense production path)",
        jax.jit(
            lambda: jax.nn.dot_product_attention(q, k, v, is_causal=args.causal)
        ),
    )
    timed(
        "pallas flash_attention (normalized)",
        lambda: flash_attention(q, k, v, causal=args.causal),
    )
    # The stats kernel's causal mode is the ring diagonal block
    # (block-local row>=col) — same masking cost as global causal here
    # because q and k cover the same range.
    timed(
        "pallas flash_attention_stats (ring per-hop engine)",
        lambda: flash_attention_stats(q, k, v, causal_diag=args.causal),
    )


if __name__ == "__main__":
    main()
