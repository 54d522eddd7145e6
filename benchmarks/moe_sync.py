"""Mixtral-8x7B expert-parallel weight sync benchmark (VERDICT r3 item 4).

Real 8x7B expert matrix shapes (hidden 4096, expert FFN 14336, 8 experts
per layer, 2 layers by default) exercised through the store's EP semantics:

- **push (dp x ep=8)**: each of 8 virtual ranks owns its expert's three FFN
  matrices per layer, published as PLAIN tensors under per-expert keys —
  the analog of the reference's fully-local DTensor demotion
  (/root/reference/torchstore/transport/types.py:58-85: Replicate/mesh-1
  expert weights store as plain tensors, one key per expert). Shared
  attention weights are published as 8-way TensorSlice shards.
- **pull (ep=4)**: a differently-shaped consumer fleet — each of 4 ranks
  pulls TWO whole experts (cross-rank whole-tensor gets) plus its 4-way
  reshard of the attention weights (each dest slice spans two source
  shards: a true reshard read).

All ranks run in one process (asyncio-concurrent) — the store and its
volume processes are the system under test, exactly like bench.py.

Run:  python benchmarks/moe_sync.py [--layers 2] [--dtype bfloat16]
      [--scale 1.0]
"""

import argparse
import asyncio
import sys
import time

import numpy as np

HIDDEN = 4096
EXPERT_FFN = 14336
N_EXPERTS = 8
N_HEADS = 32
N_KV_HEADS = 8
EP_PUSH = 8
EP_PULL = 4


def _np_dtype(dtype: str):
    if dtype == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(dtype)


def make_tensors(layers: int, dtype: str, scale: float):
    """(expert_weights, attn_weights): expert_weights[layer][expert] ->
    {w1, w2, w3}; attn_weights[layer] -> {q,k,v,o} full matrices."""
    dt = _np_dtype(dtype)
    h = max(64, int(HIDDEN * scale) // 64 * 64)
    ffn = max(128, int(EXPERT_FFN * scale) // 64 * 64)
    head_dim = h // N_HEADS

    def t(*shape):
        arr = np.empty(shape, dt)
        arr.reshape(-1)[:1] = 1.0
        return arr

    experts = [
        [
            {"w1": t(h, ffn), "w2": t(ffn, h), "w3": t(h, ffn)}
            for _ in range(N_EXPERTS)
        ]
        for _ in range(layers)
    ]
    attn = [
        {
            "q": t(h, N_HEADS * head_dim),
            "k": t(h, N_KV_HEADS * head_dim),
            "v": t(h, N_KV_HEADS * head_dim),
            "o": t(N_HEADS * head_dim, h),
        }
        for _ in range(layers)
    ]
    return experts, attn


def tree_bytes(node) -> int:
    if isinstance(node, dict):
        return sum(tree_bytes(v) for v in node.values())
    if isinstance(node, list):
        return sum(tree_bytes(v) for v in node)
    return node.nbytes


async def run(layers: int, dtype: str, scale: float) -> None:
    import torchstore_tpu as ts

    experts, attn = make_tensors(layers, dtype, scale)
    total = tree_bytes(experts) + tree_bytes(attn)
    print(
        f"# mixtral8x7b EP sync: {layers} layers, {N_EXPERTS} experts/layer, "
        f"{total / 1e9:.2f} GB {dtype} (scale={scale})",
        file=sys.stderr,
    )
    await ts.initialize(
        store_name="moe",
        strategy=ts.SingletonStrategy(default_transport_type="shm"),
    )
    try:

        def rank_push_items(rank: int) -> dict:
            """What source rank r publishes: its expert (fully-local plain
            tensors) + its attention shards (8-way dim-0 slices)."""
            items = {}
            for li in range(layers):
                ew = experts[li][rank]
                for name, arr in ew.items():
                    items[f"moe/l{li}/e{rank}/{name}"] = arr
                for name, full in attn[li].items():
                    rows = full.shape[0] // EP_PUSH
                    sl = ts.TensorSlice(
                        offsets=(rank * rows, 0),
                        local_shape=(rows, full.shape[1]),
                        global_shape=full.shape,
                        coordinates=(rank,),
                        mesh_shape=(EP_PUSH,),
                    )
                    items[f"moe/l{li}/attn/{name}"] = ts.Shard(
                        np.ascontiguousarray(full[rank * rows : (rank + 1) * rows]),
                        sl,
                    )
            return items

        def rank_pull_items(rank: int) -> dict:
            """What dest rank r (of EP_PULL) wants: TWO whole experts + its
            4-way attention reshard (spans two stored 8-way shards)."""
            per = N_EXPERTS // EP_PULL
            items = {}
            for li in range(layers):
                for e in range(rank * per, (rank + 1) * per):
                    for name in ("w1", "w2", "w3"):
                        items[f"moe/l{li}/e{e}/{name}"] = None
                for name, full in attn[li].items():
                    rows = full.shape[0] // EP_PULL
                    sl = ts.TensorSlice(
                        offsets=(rank * rows, 0),
                        local_shape=(rows, full.shape[1]),
                        global_shape=full.shape,
                        coordinates=(rank,),
                        mesh_shape=(EP_PULL,),
                    )
                    items[f"moe/l{li}/attn/{name}"] = ts.Shard(None, sl)
            return items

        push_sets = [rank_push_items(r) for r in range(EP_PUSH)]
        pull_sets = [rank_pull_items(r) for r in range(EP_PULL)]
        client = ts.client("moe")

        for it in range(4):
            stamp = float(it + 1)
            for items in push_sets:
                for v in items.values():
                    arr = v.data if isinstance(v, ts.Shard) else v
                    arr.reshape(-1)[:1] = stamp
            t0 = time.perf_counter()
            await asyncio.gather(
                *(client.put_batch(items) for items in push_sets)
            )
            t1 = time.perf_counter()
            outs = await asyncio.gather(
                *(client.get_batch(items) for items in pull_sets)
            )
            t2 = time.perf_counter()
            pulled = 0
            for out in outs:
                for v in out.values():
                    pulled += v.nbytes
            # Delivered: logical bytes handed to the store (total) + to the
            # consumers (pulled) per iteration; physical per-direction rates
            # alongside.
            print(
                f"# ep iter {it}: push {total/1e9/(t1-t0):.2f} GB/s physical"
                f", pull {pulled/1e9/(t2-t1):.2f} GB/s physical, delivered "
                f"{(total + pulled)/1e9/(t2-t0):.2f} GB/s",
                file=sys.stderr,
            )
            # Cross-layout verification: dest rank 1's first expert is
            # source rank 2's publication (layouts genuinely differ).
            probe = outs[1][f"moe/l0/e{N_EXPERTS // EP_PULL}/w1"]
            assert float(probe.reshape(-1)[0]) == stamp, "stale"
            for name in ("q", "k", "v", "o"):
                got = outs[0][f"moe/l0/attn/{name}"]
                want = attn[0][name][: got.shape[0]]
                assert got.shape == want.shape
        print("# verification: cross-layout expert + attention reshard OK", file=sys.stderr)
    finally:
        await ts.shutdown("moe")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--scale", type=float, default=1.0)
    args = ap.parse_args()
    asyncio.run(run(args.layers, args.dtype, args.scale))
