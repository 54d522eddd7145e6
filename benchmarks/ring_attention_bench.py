"""Ring-over-sp vs dense attention at equal per-device sequence.

On an sp-way mesh, ring attention processes an sp-times LONGER global
sequence while holding the same per-device q/kv block sizes dense attention uses on one device — the
long-context trade the op exists for. Reports wall time, achieved
attention TFLOP/s, and the ring/dense ratio.

Run (on the chips: drop the env forcing; CPU validation shown — its times
are not device numbers):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python benchmarks/ring_attention_bench.py --per-device-seq 1024
"""

import argparse
import statistics
import sys
import time


def attention_flops(b: int, sq: int, sk: int, h: int, d: int, causal: bool) -> float:
    """2 matmuls (scores + values), 2*m*n*k each; causal halves the work."""
    full = 2 * (2.0 * b * h * sq * sk * d)
    return full / 2 if causal else full


def run(per_device_seq: int, heads: int, head_dim: int, batch: int,
        causal: bool, impl: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from torchstore_tpu import parallel
    from torchstore_tpu.ops.ring_attention import ring_attention_sharded
    from torchstore_tpu.utils import enable_compile_cache

    enable_compile_cache()
    n_dev = len(jax.devices())
    global_seq = per_device_seq * n_dev
    dtype = jnp.bfloat16 if jax.devices()[0].platform == "tpu" else jnp.float32
    keys = jax.random.split(jax.random.key(0), 3)

    def timed(fn, *args, iters=5):
        out = fn(*args)
        jax.block_until_ready(out)  # compile
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            out = fn(*args)
            jax.block_until_ready(out)
            times.append(time.perf_counter() - t0)
        return statistics.median(times)

    # Dense baseline: ONE device's workload (per_device_seq x per_device_seq).
    q1 = jax.random.normal(keys[0], (batch, per_device_seq, heads, head_dim), dtype)
    dense_s = timed(
        jax.jit(lambda q, k, v: jax.nn.dot_product_attention(q, k, v, is_causal=causal)),
        q1, q1, q1,
    )
    dense_fl = attention_flops(batch, per_device_seq, per_device_seq, heads, head_dim, causal)
    print(
        f"# dense 1-device seq={per_device_seq}: {dense_s*1e3:.1f} ms, "
        f"{dense_fl/dense_s/1e12:.3f} TFLOP/s",
        file=sys.stderr,
    )

    # Ring: sp-way mesh, global_seq total, same per-device block size.
    mesh = parallel.make_mesh({"sp": n_dev})
    spec = NamedSharding(mesh, P(None, "sp", None, None))
    qg = jax.device_put(
        jax.random.normal(keys[1], (batch, global_seq, heads, head_dim), dtype), spec
    )
    ring_s = timed(
        lambda q, k, v: ring_attention_sharded(q, k, v, mesh, "sp", causal=causal, impl=impl),
        qg, qg, qg,
    )
    ring_fl = attention_flops(batch, global_seq, global_seq, heads, head_dim, causal)
    per_dev_tfs = ring_fl / ring_s / 1e12 / n_dev
    print(
        f"# ring sp={n_dev} global_seq={global_seq} impl={impl}: "
        f"{ring_s*1e3:.1f} ms, {ring_fl/ring_s/1e12:.3f} TFLOP/s total "
        f"({per_dev_tfs:.3f}/device)",
        file=sys.stderr,
    )
    # Exactness spot check vs dense on the full sequence (host, fp32).
    if global_seq <= 4096:
        qh = np.asarray(qg, np.float32)
        ref = jax.nn.dot_product_attention(qh, qh, qh, is_causal=causal)
        got = np.asarray(
            ring_attention_sharded(qg, qg, qg, mesh, "sp", causal=causal, impl=impl),
            np.float32,
        )
        atol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
        np.testing.assert_allclose(got, np.asarray(ref), atol=atol, rtol=atol)
        print("# exactness vs dense on the full sequence: OK", file=sys.stderr)
    print(
        f"# per-device efficiency vs 1-device dense: "
        f"{per_dev_tfs / (dense_fl/dense_s/1e12):.2f}x "
        "(>1 possible: causal ring skips cross-hop future blocks)",
        file=sys.stderr,
    )


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--per-device-seq", type=int, default=1024)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument(
        "--causal",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="causal mask (default); --no-causal benches the full-matrix mode",
    )
    ap.add_argument("--impl", default="auto", choices=("auto", "fused", "einsum"))
    args = ap.parse_args()
    run(args.per_device_seq, args.heads, args.head_dim, args.batch,
        args.causal, args.impl)
