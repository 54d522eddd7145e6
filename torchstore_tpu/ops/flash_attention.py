"""Pallas blockwise attention for TPU — scope and role:

- **Production role (the reason this kernel exists):
  ``flash_attention_stats``** — the per-hop inner engine of
  ``ring_attention``'s fused body. Sequence-parallel merging needs the
  UNNORMALIZED accumulator plus the online-softmax running max/denominator
  per block; XLA's fused attention cannot emit those, so a bespoke kernel
  is the only way to run ring hops without materializing (sq, sk) score
  tensors in HBM.
- **Explicitly NOT the production dense kernel**: the model's dense path
  uses ``jax.nn.dot_product_attention`` (models/llama.py:_attend). How
  whole-sequence ``flash_attention`` compares with it on a v5e chip is not
  measured; this module's normalized entry remains as the stats kernel's
  differential-test twin (same block body, one extra normalization) and
  the off-TPU interpret-mode reference.

Mechanics: q/k/v stream through VMEM in (block_q x d) / (block_k x d)
tiles, scores hit the MXU via ``dot_general`` in fp32, and the
online-softmax state (running max, running denominator, fp32 accumulator)
lives in VMEM scratch that persists across the innermost k-block grid
dimension (TPU grids execute sequentially, so the scratch carries between
j-steps of the same q block). Causal q-blocks skip k-blocks entirely above
their row range and mask with global positions.

This module is the single-device inner layer of the attention stack: the
sequence-parallel ops handle cross-device movement and call in here for the
per-device block math. ``ring_attention``'s fused body invokes
``flash_attention_stats`` (the same blockwise kernel, returning the
unnormalized accumulator plus the online-softmax running max/denominator)
once per ring hop and merges the per-block stats across hops;
``ulysses_attention`` runs whole-sequence attention per head shard. Off-TPU
the kernels run in interpret mode (tested against dense attention); on a
TPU backend they always compile to fused VMEM-resident loops
(``_interpret_mode``).

Layout: (batch, seq, heads, head_dim) in, same out. GQA maps kv heads via
the BlockSpec index maps (no repetition). Block sizes must divide the
sequence lengths (and causal needs sq <= sk); the public wrapper falls back
to dense attention otherwise.
"""

from __future__ import annotations

import functools
import math

NEG_INF = -1e30


def _interpret_mode(interpret: "bool | None") -> bool:
    """Resolve a kernel entry's ``interpret`` argument. Interpret mode
    exists for the off-TPU tests: ``None`` picks it exactly when the
    process's jax backend is not a TPU, and a caller on a TPU never gets
    it. Code compiling for a described (unattached) TPU topology still sees
    the CPU backend here and passes ``interpret=False`` explicitly."""
    import jax

    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("pallas interpret mode requested on a TPU backend")
    return interpret


def _kernel(
    q_ref,
    k_ref,
    v_ref,
    *refs,
    scale,
    causal,
    block_q,
    block_k,
    emit_stats,
):
    """One blockwise online-softmax kernel for both public ops.

    ``emit_stats=False``: refs = (o_ref, acc, m, l scratch); the final
    k-block writes the NORMALIZED output (``flash_attention``).
    ``emit_stats=True``: refs = (acc_out, m_out, l_out, acc, m, l scratch);
    the final k-block writes the raw fp32 accumulator plus the running
    max/denominator so a sequence-parallel caller (ring attention) can
    merge per-device blocks with the standard flash rescale.

    ``causal`` masks with positions i*block_q+row vs j*block_k+col — global
    causal for whole-sequence calls, and exactly the diagonal-block mask
    for the ring's own (offset-aligned) block."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if emit_stats:
        acc_out, m_out, l_out, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, acc_ref, m_ref, l_ref = refs

    i = pl.program_id(1)  # q block
    j = pl.program_id(2)  # k block (innermost: scratch carries across j)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Causal: k-blocks entirely above the q block's last row contribute
    # nothing and are skipped outright (block_q == block_k reduces this to
    # the classic j <= i).
    should_run = (
        True if not causal else (j * block_k <= i * block_q + block_q - 1)
    )

    @pl.when(should_run)
    def _block():
        q = q_ref[0].astype(jnp.float32)  # (block_q, d)
        k = k_ref[0].astype(jnp.float32)  # (block_k, d)
        v = v_ref[0].astype(jnp.float32)
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * scale
        )  # (block_q, block_k)
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + i * block_q
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * block_k
            s_eff = jnp.where(rows >= cols, s, NEG_INF)
        else:
            s_eff = s
        m_prev = m_ref[:, 0:1]
        l_prev = l_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s_eff, axis=1, keepdims=True))
        p = jnp.exp(s_eff - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, 0:1] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[:, 0:1] = m_new

    # The last k-block this q block visits (skipped causal blocks excluded).
    if causal:
        last_j = jnp.minimum(
            pl.num_programs(2) - 1, (i * block_q + block_q - 1) // block_k
        )
    else:
        last_j = pl.num_programs(2) - 1

    @pl.when(j == last_j)
    def _finish():
        if emit_stats:
            acc_out[0] = acc_ref[...]
            m_out[0] = m_ref[...]
            l_out[0] = l_ref[...]
        else:
            denom = jnp.maximum(l_ref[:, 0:1], 1e-30)
            o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _flash_call(
    q, k, v, *, causal: bool, block_q: int, block_k: int, interpret: bool,
    emit_stats: bool
):
    """Shared pallas plumbing for both kernel modes: flattened per-head
    programs, GQA kv index maps, vma-annotated out shapes."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    g = h // hk
    scale = 1.0 / math.sqrt(d)

    # (b, s, h, d) -> (b*h, s, d) flattened per-head programs.
    qf = jnp.transpose(q, (0, 2, 1, 3)).reshape(b * h, sq, d)
    kf = jnp.transpose(k, (0, 2, 1, 3)).reshape(b * hk, sk, d)
    vf = jnp.transpose(v, (0, 2, 1, 3)).reshape(b * hk, sk, d)

    def kv_index(bh, i, j):
        # GQA: q program bh = batch*h + head; its kv row is batch*hk + head//g.
        return (bh // h) * hk + (bh % h) // g, j, 0

    def out_index(bh, i, j):
        return bh, i, 0

    def out_sds(shape, dtype):
        # Under shard_map with vma checking, pallas out_shapes must declare
        # which mesh axes the output varies over — same set as the inputs.
        return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(qf).vma)

    if emit_stats:
        out_specs = [
            pl.BlockSpec((1, block_q, d), out_index),
            # Stats ride full (block_q, 128) lanes (col 0 meaningful) —
            # the natural TPU tile for the VMEM scratch they mirror.
            pl.BlockSpec((1, block_q, 128), out_index),
            pl.BlockSpec((1, block_q, 128), out_index),
        ]
        out_shape = [
            out_sds((b * h, sq, d), jnp.float32),
            out_sds((b * h, sq, 128), jnp.float32),
            out_sds((b * h, sq, 128), jnp.float32),
        ]
    else:
        out_specs = pl.BlockSpec((1, block_q, d), out_index)
        out_shape = out_sds((b * h, sq, d), q.dtype)

    grid = (b * h, sq // block_q, sk // block_k)
    result = pl.pallas_call(
        functools.partial(
            _kernel,
            scale=scale,
            causal=causal,
            block_q=block_q,
            block_k=block_k,
            emit_stats=emit_stats,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), out_index),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),   # fp32 accumulator
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max (col 0)
            pltpu.VMEM((block_q, 128), jnp.float32),  # running denom (col 0)
        ],
        interpret=interpret,
    )(qf, kf, vf)
    if emit_stats:
        acc, m, l = result
        # (b*h, sq, ...) -> (b, h, sq, ...); stats keep lane col 0 only.
        return (
            acc.reshape(b, h, sq, d),
            m[:, :, 0].reshape(b, h, sq),
            l[:, :, 0].reshape(b, h, sq),
        )
    return jnp.transpose(result.reshape(b, h, sq, d), (0, 2, 1, 3))


@functools.cache
def _jitted(causal: bool, block_q: int, block_k: int, interpret: bool):
    import jax

    return jax.jit(
        functools.partial(
            _flash_call,
            causal=causal,
            block_q=block_q,
            block_k=block_k,
            interpret=interpret,
            emit_stats=False,
        )
    )


def _flash_stats(
    q, k, v, *, causal_diag: bool, block_q: int, block_k: int, interpret: bool
):
    return _flash_call(
        q,
        k,
        v,
        causal=causal_diag,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
        emit_stats=True,
    )


def _pick_block(s: int, cap: int = 256) -> "int | None":
    """Largest power-of-two block (>=8, <=cap) dividing ``s``."""
    blk = None
    b = 8
    while b <= cap and s % b == 0:
        blk = b
        b *= 2
    return blk


def flash_stats_eligible(q_shape, k_shape) -> bool:
    """Whether ``flash_attention_stats`` can tile these per-device shapes
    (ring attention's fused-body gate; falls back to its einsum body
    otherwise)."""
    b, sq, h, d = q_shape
    sk, hk = k_shape[1], k_shape[2]
    return (
        _pick_block(sq) is not None
        and _pick_block(sk) is not None
        and h % hk == 0
        and d % 8 == 0
    )


def _stats_ref(q, k, v, causal_diag: bool):
    """Dense jnp twin of the stats kernel (same outputs, same masking
    constants) — the recompute target for the custom VJP: forward runs the
    fused pallas kernel, backward re-derives the block's gradients from
    this reference (flash's standard recompute-in-backward shape, with the
    recompute left to XLA)."""
    import jax.numpy as jnp

    b, sq, h, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    g = h // hk
    scale = 1.0 / math.sqrt(d)
    qf = jnp.transpose(q, (0, 2, 1, 3)).astype(jnp.float32)  # (b,h,sq,d)
    kf = jnp.repeat(
        jnp.transpose(k, (0, 2, 1, 3)).astype(jnp.float32), g, axis=1
    )
    vf = jnp.repeat(
        jnp.transpose(v, (0, 2, 1, 3)).astype(jnp.float32), g, axis=1
    )
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
    if causal_diag:
        rows = jnp.arange(sq)[:, None]
        cols = jnp.arange(sk)[None, :]
        s = jnp.where(rows >= cols, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhqk,bhkd->bhqd", p, vf)
    return acc, m, l


@functools.cache
def _stats_diff(causal_diag: bool, block_q: int, block_k: int, interpret: bool):
    """Differentiable wrapper: pallas kernel forward, dense-reference
    recompute backward (pallas_call defines no autodiff rule; ring
    attention trains through this op)."""
    import jax

    kernel = functools.partial(
        _flash_stats,
        causal_diag=causal_diag,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
    )

    @jax.custom_vjp
    def f(q, k, v):
        return kernel(q, k, v)

    def fwd(q, k, v):
        return kernel(q, k, v), (q, k, v)

    def bwd(res, cts):
        q, k, v = res
        _, vjp = jax.vjp(
            lambda a, b, c: _stats_ref(a, b, c, causal_diag), q, k, v
        )
        return vjp(cts)

    f.defvjp(fwd, bwd)
    return jax.jit(f)


def flash_attention_stats(q, k, v, causal_diag: bool = False, interpret=None):
    """Unnormalized blockwise attention of one kv block: returns
    ``(acc, m, l)`` with ``acc`` (b, h, sq, d) fp32 = sum_k exp(s - m) * v,
    ``m``/``l`` (b, h, sq) the running max / denominator. ``causal_diag``
    applies row>=col masking in block-local coordinates (the ring's
    diagonal block). Merge across blocks with the flash rescale:
    ``m' = max(m1, m2); acc' = acc1*e^(m1-m') + acc2*e^(m2-m')`` etc.
    Differentiable: backward recomputes the block densely (see
    ``_stats_diff``)."""
    block_q = _pick_block(q.shape[1])
    block_k = _pick_block(k.shape[1])
    if block_q is None or block_k is None:
        raise ValueError(
            f"sequence lengths {q.shape[1]}/{k.shape[1]} don't tile; gate "
            "with flash_stats_eligible()"
        )
    return _stats_diff(
        causal_diag, block_q, block_k, _interpret_mode(interpret)
    )(q, k, v)


def flash_attention(
    q,
    k,
    v,
    causal: bool = False,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
):
    """Pallas flash attention; falls back to ``jax.nn.dot_product_attention``
    when shapes don't tile (seq not divisible by blocks, tiny head_dim)."""
    import jax

    b, sq, h, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    if (
        sq % block_q != 0
        or sk % block_k != 0
        or block_q != block_k
        or h % hk != 0
        or d % 8 != 0
        # Causal with sq > sk would leave q-blocks past the last k-block
        # unwritten (their diagonal lies outside the j grid).
        or (causal and sq > sk)
    ):
        return jax.nn.dot_product_attention(q, k, v, is_causal=causal)
    return _jitted(causal, block_q, block_k, _interpret_mode(interpret))(
        q, k, v
    )
