"""North-star config benchmark: full Llama-3-8B state-dict weight sync.

Builds a host state dict with EXACTLY the reference north-star model's
tensor inventory (llama3-8b: 291 tensors, ~16 GB bf16) and measures the
trainer->consumer sync paths end to end:

  buffered   put_state_dict + zero-copy get_state_dict through a volume
  direct     registered staging publish + pull into destination buffers

Run:  python benchmarks/llama8b_sync.py [--dtype bfloat16] [--scale 1.0]

``--scale`` shrinks the hidden sizes for quick runs (1.0 = real 8B shapes).
"""

import argparse
import asyncio
import sys
import time

import numpy as np


def llama8b_state_dict(
    dtype: str, scale: float, model: str = "8b", layers: "int | None" = None
) -> dict:
    import ml_dtypes

    from torchstore_tpu.models.llama import LlamaConfig

    # The canonical geometries, not copies. 70B shard shapes with a reduced
    # layer count are the VERDICT r3 item 8 config (full 80 layers = 141 GB
    # bf16, ~3x too big for host + staging + dest on this machine).
    cfg = (
        LlamaConfig.llama3_70b() if model == "70b" else LlamaConfig.llama3_8b()
    )
    np_dtype = np.dtype(ml_dtypes.bfloat16 if dtype == "bfloat16" else dtype)
    h = max(64, int(cfg.hidden_size * scale) // 64 * 64)
    inter = max(128, int(cfg.intermediate_size * scale) // 64 * 64)
    vocab = max(256, int(cfg.vocab_size * scale) // 64 * 64)
    n_layers = cfg.num_layers if scale >= 1.0 else max(2, int(cfg.num_layers * scale))
    if layers is not None:
        n_layers = layers
    heads, kv_heads = cfg.num_heads, cfg.num_kv_heads
    head_dim = h // heads

    def t(*shape):
        # empty+fill: building 16 GB of random bf16 via rand().astype would
        # dominate setup time; content doesn't affect transfer speed.
        arr = np.empty(shape, np_dtype)
        arr.reshape(-1)[:1] = 1.0
        return arr

    sd = {
        "embed": t(vocab, h),
        "final_norm": t(h),
        "lm_head": t(h, vocab),
        "layers": {},
    }
    for i in range(n_layers):
        sd["layers"][str(i)] = {
            "attn_norm": t(h),
            "mlp_norm": t(h),
            "q_proj": t(h, heads * head_dim),
            "k_proj": t(h, kv_heads * head_dim),
            "v_proj": t(h, kv_heads * head_dim),
            "o_proj": t(heads * head_dim, h),
            "gate_proj": t(h, inter),
            "up_proj": t(h, inter),
            "down_proj": t(inter, h),
        }
    return sd


def count(sd):
    n, total = 0, 0
    stack = [sd]
    while stack:
        node = stack.pop()
        if isinstance(node, dict):
            stack.extend(node.values())
        else:
            n += 1
            total += node.nbytes
    return n, total


async def run(
    dtype: str, scale: float, model: str = "8b", layers: "int | None" = None
) -> None:
    import torchstore_tpu as ts

    sd = llama8b_state_dict(dtype, scale, model, layers)
    n_tensors, total = count(sd)
    print(
        f"# llama{model}-shaped state dict: {n_tensors} tensors, "
        f"{total / 1e9:.2f} GB {dtype} (scale={scale}, layers={layers})",
        file=sys.stderr,
    )
    await ts.initialize(
        store_name="l8b", strategy=ts.SingletonStrategy(default_transport_type="shm")
    )
    try:
        # Buffered: put + zero-copy snapshot get (steady state by iter 2-3:
        # the segment-rotation pool converges, then puts run at memcpy
        # speed and gets are metadata-only).
        out = None
        for it in range(4):
            t0 = time.perf_counter()
            await ts.put_state_dict("w", sd, store_name="l8b")
            t1 = time.perf_counter()
            out = await ts.get_state_dict("w", store_name="l8b")
            t2 = time.perf_counter()
            # "delivered" counts logical bytes handed to each side (2N per
            # round trip) — zero-copy delivery is the measured advantage;
            # the physical per-direction rates are printed alongside so
            # nothing hides behind the definition.
            print(
                f"# buffered iter {it}: put {total/1e9/(t1-t0):.2f} GB/s "
                f"physical, zero-copy get {(t2-t1)*1e3:.0f} ms, "
                f"delivered {2*total/1e9/(t2-t0):.2f} GB/s",
                file=sys.stderr,
            )
        assert float(np.asarray(out["embed"]).reshape(-1)[0]) == 1.0

        # Direct with registered staging: publish + pull into dest buffers.
        import jax  # noqa: F401 - keep parity with bench env

        user = None
        await ts.put_state_dict("d", sd, direct=True, store_name="l8b")
        staging = ts.direct_staging_buffers("d", store_name="l8b")
        assert staging is not None

        def zeros_like_tree(node):
            if isinstance(node, dict):
                return {k: zeros_like_tree(v) for k, v in node.items()}
            return np.zeros_like(node)

        user = zeros_like_tree(sd)
        for it in range(4):
            t0 = time.perf_counter()
            await ts.put_state_dict("d", staging, direct=True, store_name="l8b")
            t1 = time.perf_counter()
            await ts.get_state_dict(
                "d", user_state_dict=user, direct=True, store_name="l8b"
            )
            t2 = time.perf_counter()
            print(
                f"# direct+registered iter {it}: publish {(t1-t0)*1e3:.0f} ms, "
                f"pull {total/1e9/(t2-t1):.2f} GB/s physical, "
                f"delivered {2*total/1e9/(t2-t0):.2f} GB/s",
                file=sys.stderr,
            )
        assert float(user["layers"]["0"]["q_proj"].reshape(-1)[0]) == 1.0
    finally:
        await ts.shutdown("l8b")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--model", choices=("8b", "70b"), default="8b")
    ap.add_argument("--layers", type=int, default=None,
                    help="override layer count (70b default run uses 8)")
    args = ap.parse_args()
    asyncio.run(run(args.dtype, args.scale, args.model, args.layers))
