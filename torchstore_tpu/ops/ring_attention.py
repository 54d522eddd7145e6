"""Ring attention: sequence-parallel attention for long contexts.

The store moves weights; long-context *activations* need the sequence axis
sharded across devices. This op computes exact attention when q/k/v are
sequence-sharded over an ``sp`` mesh axis: each device keeps its query block
resident and rotates k/v blocks around the ring with ``ppermute`` (one hop
per step — the transfers ride ICI neighbor links), accumulating with a
numerically-stable online softmax (blockwise/flash-style). Memory per device
is O(seq/n) instead of O(seq), and the k/v rotation overlaps with block
compute under XLA's latency-hiding scheduler.

Use inside ``shard_map`` (see ``ring_attention_sharded`` for the wrapped
version). Matches dense attention bit-for-block (see
tests/test_ring_attention.py differential tests).
"""

from __future__ import annotations

import math


def ring_attention(q, k, v, axis_name: str, causal: bool = False, impl: str = "auto"):
    """Per-shard attention bodies. Shapes (inside shard_map, per device):
    q: (batch, seq_local, heads, head_dim), k/v: (batch, seq_local,
    kv_heads, head_dim) -> (batch, seq_local, heads, head_dim). GQA is
    handled natively — the ring rotates the UNREPEATED kv blocks, so GQA's
    bandwidth/memory saving survives sequence parallelism.

    ``impl`` selects the per-hop block body:

    - ``"fused"``: the pallas flash kernel (``flash_attention_stats``) —
      scores stream through VMEM tiles, never materializing the
      (sq_local, sk_local) score tensor in HBM; hops merge via the
      standard online-softmax rescale.
    - ``"einsum"``: the reference-free dense block body (materializes
      per-hop scores; any shape).
    - ``"auto"`` (default): fused when the per-device shapes tile
      (``flash_stats_eligible``), einsum otherwise.
    """
    from torchstore_tpu.ops.flash_attention import flash_stats_eligible

    # The fused body's causal hop mask is all-or-nothing per hop, which is
    # exact only when q and kv rings carry EQUAL per-device lengths (the
    # self-attention shape); unequal lengths make some hops partially
    # visible and need the einsum body's global-position mask.
    fused_exact = not causal or q.shape[1] == k.shape[1]
    if impl == "fused":
        if not fused_exact:
            raise ValueError(
                "impl='fused' causal ring attention requires equal q/kv "
                f"sequence lengths per device (got {q.shape[1]} vs "
                f"{k.shape[1]}); use impl='auto' or 'einsum'"
            )
        return _ring_fused(q, k, v, axis_name, causal)
    if (
        impl == "auto"
        and fused_exact
        and flash_stats_eligible(q.shape, k.shape)
    ):
        return _ring_fused(q, k, v, axis_name, causal)
    return _ring_einsum(q, k, v, axis_name, causal)


def _ring_fused(q, k, v, axis_name: str, causal: bool):
    """Ring body with the fused flash kernel per hop: each incoming kv
    block runs ``flash_attention_stats`` (unnormalized accumulator +
    online-softmax stats, computed blockwise in VMEM) and hops merge with
    the flash rescale. Causal hops from ring positions AFTER this device
    are fully masked (zero contribution); the diagonal (own) block applies
    the in-kernel causal mask. Same O(seq/n) memory as the einsum body but
    without ever materializing a (sq, sk) score tensor."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from torchstore_tpu.ops.flash_attention import flash_attention_stats

    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    perm = [(j, (j + 1) % n) for j in range(n)]
    NEG = jnp.float32(-1e30)

    def merge(carry, contrib):
        o, m, l = carry
        acc_j, m_j, l_j = contrib
        m_new = jnp.maximum(m, m_j)
        c1 = jnp.exp(m - m_new)
        c2 = jnp.exp(m_j - m_new)
        return (
            o * c1[..., None] + acc_j * c2[..., None],
            m_new,
            l * c1 + l_j * c2,
        )

    def step(i, carry):
        o, m, l, k_cur, v_cur = carry
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        acc_j, m_j, l_j = flash_attention_stats(
            q, k_cur, v_cur, causal_diag=False
        )
        if causal:
            # k_cur originated on ring position (my_idx - i) mod n; blocks
            # from positions after ours are entirely in the future — mask
            # the whole contribution (same cost profile as the einsum
            # body, which also computes-then-masks; no data-dependent
            # control flow inside the compiled loop).
            valid = ((my_idx - i) % n) < my_idx
            acc_j = jnp.where(valid, acc_j, 0.0)
            m_j = jnp.where(valid, m_j, NEG)
            l_j = jnp.where(valid, l_j, 0.0)
        o, m, l = merge((o, m, l), (acc_j, m_j, l_j))
        return o, m, l, k_cur, v_cur

    # Step 0: the device's own block — in-kernel causal diagonal mask.
    o0, m0, l0 = flash_attention_stats(q, k, v, causal_diag=causal)
    o, m, l, _, _ = lax.fori_loop(1, n, step, (o0, m0, l0, k, v))
    out = o / jnp.maximum(l, 1e-30)[..., None]  # (b, h, sq, d)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def _ring_einsum(q, k, v, axis_name: str, causal: bool):
    """Dense (einsum) block body: grouped-GQA online softmax materializing
    one (sq, sk) score block per hop. Shape-agnostic fallback for sizes
    the pallas kernel can't tile."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    if h % hk != 0:
        raise ValueError(f"q heads ({h}) must be a multiple of kv heads ({hk})")
    g = h // hk
    scale = 1.0 / math.sqrt(d)
    perm = [(j, (j + 1) % n) for j in range(n)]

    # Grouped layout: (b, sq, hk, g, d) so kv heads broadcast per group.
    q32 = q.astype(jnp.float32).reshape(b, sq, hk, g, d)
    NEG = jnp.float32(-1e30)

    q_pos = my_idx * sq + jnp.arange(sq)  # global query positions

    def accumulate(carry, k_cur, v_cur, i):
        o, m, l = carry  # o: (b,hk,g,sq,d); m,l: (b,hk,g,sq)
        # k_cur originated on device (my_idx - i) mod n.
        src = (my_idx - i) % n
        s = jnp.einsum(
            "bqhgd,bkhd->bhgqk", q32, k_cur.astype(jnp.float32)
        ) * scale
        if causal:
            k_pos = src * sk + jnp.arange(sk)
            mask = q_pos[:, None] >= k_pos[None, :]  # (sq, sk)
            s = jnp.where(mask[None, None, None], s, NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        o = o * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, v_cur.astype(jnp.float32)
        )
        return o, m_new, l

    def step(i, carry):
        o, m, l, k_cur, v_cur = carry
        # Rotate FIRST (steps 1..n-1): exactly n-1 ppermutes total — the
        # final block's k/v are never rotated into oblivion.
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        o, m, l = accumulate((o, m, l), k_cur, v_cur, i)
        return o, m, l, k_cur, v_cur

    o0 = jnp.zeros((b, hk, g, sq, d), jnp.float32)
    m0 = jnp.full((b, hk, g, sq), NEG)
    l0 = jnp.zeros((b, hk, g, sq), jnp.float32)
    # shard_map tracks device-varying types through loop carries: constant
    # initializers must be marked varying over the ring axis.
    o0, m0, l0 = (
        lax.pcast(x, axis_name, to="varying") for x in (o0, m0, l0)
    )
    # Step 0: own (unrotated) block, outside the loop.
    o0, m0, l0 = accumulate((o0, m0, l0), k, v, 0)
    o, m, l, _, _ = lax.fori_loop(1, n, step, (o0, m0, l0, k, v))
    out = o / jnp.maximum(l, 1e-30)[..., None]  # (b,hk,g,sq,d)
    out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(b, sq, h, d)
    return out.astype(q.dtype)


def ring_attention_sharded(
    q, k, v, mesh, axis_name: str = "sp", causal: bool = False, impl: str = "auto"
):
    """jit-compiled ring attention over ``mesh``'s ``axis_name`` ring: global
    (batch, seq, heads, head_dim) arrays sequence-sharded on entry/exit."""
    from torchstore_tpu.ops._sharded import make_sharded_attention

    return make_sharded_attention(
        ring_attention, mesh, axis_name, causal, impl=impl,
        relax_vma=impl != "einsum",
    )(q, k, v)
