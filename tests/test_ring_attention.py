"""Ring attention differential tests: exactness vs dense attention on the
8-device CPU mesh (sequence-parallel over an sp ring)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from torchstore_tpu.ops.ring_attention import ring_attention_sharded  # noqa: E402
from torchstore_tpu import parallel  # noqa: E402


def dense_reference(q, k, v, causal):
    return jax.nn.dot_product_attention(q, k, v, is_causal=causal)


def make_qkv(b=2, s=64, h=4, d=16, seed=0):
    keys = jax.random.split(jax.random.key(seed), 3)
    shape = (b, s, h, d)
    return tuple(jax.random.normal(key, shape, jnp.float32) for key in keys)


@pytest.mark.parametrize("impl", ["fused", "einsum"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("ring", [2, 4, 8])
def test_matches_dense(causal, ring, impl):
    """Both block bodies — the pallas fused kernel (per-hop
    flash_attention_stats + online-softmax merge) and the einsum fallback —
    are exact vs dense attention (VERDICT r3 item 5)."""
    q, k, v = make_qkv()
    mesh = parallel.make_mesh({"sp": ring})
    spec = NamedSharding(mesh, P(None, "sp", None, None))
    qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))
    out = ring_attention_sharded(qs, ks, vs, mesh, "sp", causal=causal, impl=impl)
    ref = dense_reference(q, k, v, causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )
    assert out.sharding.spec == P(None, "sp", None, None)


@pytest.mark.parametrize("impl", ["fused", "einsum"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_gqa_matches_dense(causal, impl):
    """GQA through the ring: kv heads stay unrepeated on the wire in both
    bodies (grouped einsum / in-kernel kv index map)."""
    keys = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(keys[0], (2, 64, 8, 16), jnp.float32)
    k = jax.random.normal(keys[1], (2, 64, 2, 16), jnp.float32)
    v = jax.random.normal(keys[2], (2, 64, 2, 16), jnp.float32)
    mesh = parallel.make_mesh({"sp": 4})
    spec = NamedSharding(mesh, P(None, "sp", None, None))
    qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))
    out = ring_attention_sharded(qs, ks, vs, mesh, "sp", causal=causal, impl=impl)
    ref = dense_reference(q, k, v, causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("impl", ["fused", "einsum"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_gradients_match_dense(causal, impl):
    """Training differentiates through ring attention; the fused body's
    custom VJP (pallas forward, dense recompute backward) must produce the
    same q/k/v gradients as differentiating dense attention."""
    q, k, v = make_qkv(b=1, s=32, h=2, d=16, seed=11)
    mesh = parallel.make_mesh({"sp": 4})
    spec = NamedSharding(mesh, P(None, "sp", None, None))
    qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))

    def ring_loss(q, k, v):
        out = ring_attention_sharded(q, k, v, mesh, "sp", causal=causal, impl=impl)
        return jnp.sum(out * jnp.cos(jnp.arange(out.size).reshape(out.shape)))

    def dense_loss(q, k, v):
        out = dense_reference(q, k, v, causal)
        return jnp.sum(out * jnp.cos(jnp.arange(out.size).reshape(out.shape)))

    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(qs, ks, vs)
    g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(g_ring, g_dense):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=3e-5, rtol=3e-5
        )


def test_auto_picks_fused_for_tileable_shapes():
    from torchstore_tpu.ops.flash_attention import flash_stats_eligible

    assert flash_stats_eligible((2, 8, 4, 16), (2, 8, 4, 16))
    assert not flash_stats_eligible((2, 9, 4, 16), (2, 9, 4, 16))  # 9 untileable
    assert not flash_stats_eligible((2, 8, 4, 10), (2, 8, 4, 10))  # d % 8


def test_flash_stats_merge_property():
    """Property sweep of the merge invariant over GQA ratios, head dims,
    asymmetric kv splits, and both mask modes: blocks merged with the
    flash rescale equal whole-sequence attention (the exact algebra the
    ring's hop merge relies on)."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    from torchstore_tpu.ops.flash_attention import flash_attention_stats

    @settings(max_examples=15, deadline=None)
    @given(
        hk=st.sampled_from([1, 2, 4]),
        g=st.sampled_from([1, 2, 4]),
        d=st.sampled_from([8, 16, 24]),
        sq=st.sampled_from([16, 32, 40]),
        split=st.sampled_from([8, 16, 24]),
        seed=st.integers(0, 2**16),
    )
    def check(hk, g, d, sq, split, seed):
        h = hk * g
        sk = 48
        keys = jax.random.split(jax.random.key(seed), 3)
        q = jax.random.normal(keys[0], (1, sq, h, d), jnp.float32)
        k = jax.random.normal(keys[1], (1, sk, hk, d), jnp.float32)
        v = jax.random.normal(keys[2], (1, sk, hk, d), jnp.float32)
        a1, m1, l1 = flash_attention_stats(q, k[:, :split], v[:, :split])
        a2, m2, l2 = flash_attention_stats(q, k[:, split:], v[:, split:])
        m = jnp.maximum(m1, m2)
        c1, c2 = jnp.exp(m1 - m), jnp.exp(m2 - m)
        o = (a1 * c1[..., None] + a2 * c2[..., None]) / (
            l1 * c1 + l2 * c2
        )[..., None]
        out = jnp.transpose(o, (0, 2, 1, 3))
        ref = dense_reference(q, k, v, False)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5
        )

    check()


def test_flash_stats_merge_identity():
    """flash_attention_stats blocks merged with the flash rescale equal
    whole-sequence dense attention — the invariant the ring's hop merge
    relies on."""
    from torchstore_tpu.ops.flash_attention import flash_attention_stats

    q, k, v = make_qkv(b=1, s=64, h=2, d=16, seed=5)
    k1, k2 = k[:, :32], k[:, 32:]
    v1, v2 = v[:, :32], v[:, 32:]
    a1, m1, l1 = flash_attention_stats(q, k1, v1)
    a2, m2, l2 = flash_attention_stats(q, k2, v2)
    m = jnp.maximum(m1, m2)
    c1, c2 = jnp.exp(m1 - m), jnp.exp(m2 - m)
    o = (a1 * c1[..., None] + a2 * c2[..., None]) / (
        l1 * c1 + l2 * c2
    )[..., None]
    out = jnp.transpose(o, (0, 2, 1, 3))
    ref = dense_reference(q, k, v, False)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_single_device_ring_degenerates_to_dense():
    q, k, v = make_qkv(s=32)
    mesh = parallel.make_mesh({"sp": 1})
    out = ring_attention_sharded(q, k, v, mesh, "sp", causal=True)
    ref = dense_reference(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_long_sequence_memory_shape():
    # 8-way ring over a longer sequence: each device only ever holds
    # seq/8-sized k/v blocks; output stays sequence-sharded.
    q, k, v = make_qkv(b=1, s=512, h=2, d=8)
    mesh = parallel.make_mesh({"sp": 8})
    spec = NamedSharding(mesh, P(None, "sp", None, None))
    qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))
    out = ring_attention_sharded(qs, ks, vs, mesh, "sp", causal=True)
    assert out.shape == (1, 512, 2, 8)
    for shard in out.addressable_shards:
        assert shard.data.shape[1] == 512 // 8
    ref = dense_reference(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_bf16_inputs():
    q, k, v = make_qkv()
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    mesh = parallel.make_mesh({"sp": 4})
    spec = NamedSharding(mesh, P(None, "sp", None, None))
    out = ring_attention_sharded(
        *(jax.device_put(x, spec) for x in (qb, kb, vb)), mesh, "sp", causal=False
    )
    assert out.dtype == jnp.bfloat16
    ref = dense_reference(qb, kb, vb, False)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2
    )


class TestUlysses:
    @pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
    @pytest.mark.parametrize("ring", [2, 4])
    def test_matches_dense(self, causal, ring):
        from torchstore_tpu.ops import ulysses_attention_sharded

        q, k, v = make_qkv()
        mesh = parallel.make_mesh({"sp": ring})
        spec = NamedSharding(mesh, P(None, "sp", None, None))
        qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))
        out = ulysses_attention_sharded(qs, ks, vs, mesh, "sp", causal=causal)
        ref = dense_reference(q, k, v, causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )
        assert out.sharding.spec == P(None, "sp", None, None)

    def test_indivisible_heads_rejected(self):
        from torchstore_tpu.ops import ulysses_attention_sharded

        q, k, v = make_qkv(h=3)
        mesh = parallel.make_mesh({"sp": 2})
        spec = NamedSharding(mesh, P(None, "sp", None, None))
        qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))
        with pytest.raises(ValueError, match="divisible"):
            ulysses_attention_sharded(qs, ks, vs, mesh, "sp")

    def test_agrees_with_ring(self):
        from torchstore_tpu.ops import ulysses_attention_sharded

        q, k, v = make_qkv(s=128)
        mesh = parallel.make_mesh({"sp": 4})
        spec = NamedSharding(mesh, P(None, "sp", None, None))
        qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))
        ring = ring_attention_sharded(qs, ks, vs, mesh, "sp", causal=True)
        uly = ulysses_attention_sharded(qs, ks, vs, mesh, "sp", causal=True)
        np.testing.assert_allclose(
            np.asarray(ring), np.asarray(uly), atol=3e-5, rtol=3e-5
        )

    def test_hypothesis_sweep_gqa_heads_causal(self):
        """Property sweep of the Ulysses envelope (VERDICT r5 #4): GQA
        ratio x head count x causal mode against the dense oracle. Head
        counts are drawn divisible by the sp axis (the op's contract); the
        all-to-all re-partition must be exact for every combination."""
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        from torchstore_tpu.ops import ulysses_attention_sharded

        sp = 4
        mesh = parallel.make_mesh({"sp": sp})
        spec = NamedSharding(mesh, P(None, "sp", None, None))

        @settings(max_examples=12, deadline=None)
        @given(
            kv_heads=st.sampled_from([4, 8]),  # divisible by sp
            gqa=st.sampled_from([1, 2, 3]),  # q heads = kv * gqa
            d=st.sampled_from([8, 16]),
            causal=st.booleans(),
            seed=st.integers(0, 2**16),
        )
        def check(kv_heads, gqa, d, causal, seed):
            h = kv_heads * gqa
            keys = jax.random.split(jax.random.key(seed), 3)
            q = jax.random.normal(keys[0], (1, 32, h, d), jnp.float32)
            k = jax.random.normal(keys[1], (1, 32, kv_heads, d), jnp.float32)
            v = jax.random.normal(keys[2], (1, 32, kv_heads, d), jnp.float32)
            qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))
            out = ulysses_attention_sharded(qs, ks, vs, mesh, "sp", causal=causal)
            ref = dense_reference(q, k, v, causal)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5
            )

        check()

    def test_model_head_divisibility_fallback_to_ring(self, monkeypatch):
        """Boundary of the head-divisibility envelope: a model configured
        with attn_impl='ulysses' whose per-shard head counts do NOT divide
        the sp axis must fall back to ring attention — logits still match
        dense, and the ulysses body is never entered (stubbed to fail)."""
        import dataclasses
        import importlib

        # The package re-exports the function under the submodule's name, so
        # ``import ... as`` would bind the function; fetch the module itself.
        ua = importlib.import_module("torchstore_tpu.ops.ulysses_attention")
        from torchstore_tpu.models.llama import Llama, LlamaConfig
        from torchstore_tpu.ops._sharded import make_sharded_attention

        def boom(*args, **kwargs):
            raise AssertionError(
                "ulysses body must not run for indivisible heads"
            )

        monkeypatch.setattr(ua, "ulysses_attention", boom)
        make_sharded_attention.cache_clear()  # a cached fn could mask the stub
        mesh = parallel.make_mesh({"sp": 4})
        base = dataclasses.replace(
            LlamaConfig.tiny(),
            num_heads=6,  # 6 % 4 != 0: outside the ulysses envelope
            num_kv_heads=6,
            dtype=jnp.float32,
            param_dtype=jnp.float32,
        )
        sp_cfg = dataclasses.replace(base, attn_impl="ulysses", mesh=mesh)
        tokens = jax.random.randint(
            jax.random.key(3), (2, 16), 0, base.vocab_size
        )
        params = parallel.unbox(Llama(base).init(jax.random.key(0), tokens))
        dense = Llama(base).apply(params, tokens)
        out = Llama(sp_cfg).apply(params, tokens)  # fell back to ring
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(dense), atol=5e-4, rtol=5e-4
        )


class TestPallasFlash:
    """Pallas flash kernel in interpret mode on CPU (its compile for the
    v5e is tests/test_chip_compile.py; its rate on the chip is not
    measured)."""

    @pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
    def test_matches_dense(self, causal):
        from torchstore_tpu.ops import flash_attention

        q, k, v = make_qkv(b=1, s=256, h=2, d=32)
        out = flash_attention(q, k, v, causal=causal)
        ref = dense_reference(q, k, v, causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5
        )

    def test_gqa(self):
        from torchstore_tpu.ops import flash_attention

        keys = jax.random.split(jax.random.key(3), 3)
        q = jax.random.normal(keys[0], (1, 256, 8, 32), jnp.float32)
        k = jax.random.normal(keys[1], (1, 256, 2, 32), jnp.float32)
        v = jax.random.normal(keys[2], (1, 256, 2, 32), jnp.float32)
        out = flash_attention(q, k, v, causal=True)
        ref = dense_reference(q, k, v, True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5
        )

    def test_untileable_falls_back(self):
        from torchstore_tpu.ops import flash_attention

        q, k, v = make_qkv(b=1, s=100, h=2, d=32)  # 100 % 128 != 0
        out = flash_attention(q, k, v, causal=True)
        ref = dense_reference(q, k, v, True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5
        )
