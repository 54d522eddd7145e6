"""Llama-family transformer in flax — the flagship model for weight-sync
benchmarks and examples.

The reference exercises its store with HF models (Qwen3 FSDP reshard,
/root/reference/tests/test_models.py:33-136) and the driver's BASELINE
configs name Llama-3-8B / Llama-3-70B / Mixtral-8x7B state_dict exchange.
This module provides those model families TPU-first: bfloat16 matmuls on the
MXU, RoPE + GQA attention via ``jax.nn.dot_product_attention`` (flash kernel
on TPU), SwiGLU MLP, RMSNorm, and optional MoE (Mixtral-style) layers whose
experts shard cleanly over an ``ep`` mesh axis. Logical sharding annotations
(``nn.with_logical_partitioning``) map params onto tp/fsdp/ep axes — see
``torchstore_tpu.parallel`` for the rules.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # MoE (Mixtral-style): 0 experts = dense MLP.
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # Qwen2-style: biases on the q/k/v projections only.
    attention_bias: bool = False
    # Gemma-style knobs: tanh-gelu MLP ("silu" | "gelu_tanh"), RMSNorm
    # scale stored as an offset applied as (1 + w), embeddings scaled by
    # sqrt(hidden) after lookup, and the lm_head tied to the embedding.
    mlp_act: str = "silu"
    rms_offset: bool = False
    scale_embeddings: bool = False
    tie_embeddings: bool = False
    # Long-context attention: "dense" | "ring" | "ulysses". The sharded
    # impls engage when ``mesh`` has an sp axis of size > 1 (sequence
    # parallelism); otherwise dense is used.
    attn_impl: str = "dense"
    mesh: Any = None
    # Autoregressive decoding: when True, attention maintains a per-layer
    # k/v cache (flax 'cache' collection, created lazily under
    # mutable=["cache"]) of length max_cache_len. See models/generate.py.
    decode: bool = False
    max_cache_len: int = 0

    @classmethod
    def llama3_8b(cls) -> "LlamaConfig":
        return cls(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336,
            num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
        )

    @classmethod
    def llama3_70b(cls) -> "LlamaConfig":
        return cls(
            vocab_size=128256, hidden_size=8192, intermediate_size=28672,
            num_layers=80, num_heads=64, num_kv_heads=8, head_dim=128,
        )

    @classmethod
    def mixtral_8x7b(cls) -> "LlamaConfig":
        return cls(
            vocab_size=32000, hidden_size=4096, intermediate_size=14336,
            num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
            rope_theta=1e6, num_experts=8, num_experts_per_tok=2,
        )

    @classmethod
    def qwen2_7b(cls) -> "LlamaConfig":
        return cls(
            vocab_size=152064, hidden_size=3584, intermediate_size=18944,
            num_layers=28, num_heads=28, num_kv_heads=4, head_dim=128,
            rope_theta=1e6, rms_eps=1e-6, attention_bias=True,
        )

    @classmethod
    def gemma_7b(cls) -> "LlamaConfig":
        return cls(
            vocab_size=256000, hidden_size=3072, intermediate_size=24576,
            num_layers=28, num_heads=16, num_kv_heads=16, head_dim=256,
            rope_theta=10000.0, rms_eps=1e-6, mlp_act="gelu_tanh",
            rms_offset=True, scale_embeddings=True, tie_embeddings=True,
        )

    @classmethod
    def tiny_gemma(cls) -> "LlamaConfig":
        return cls(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=8, num_kv_heads=8, head_dim=8,
            rms_eps=1e-6, mlp_act="gelu_tanh", rms_offset=True,
            scale_embeddings=True, tie_embeddings=True,
        )

    @classmethod
    def tiny(cls, vocab_size: int = 256) -> "LlamaConfig":
        # Head/mlp/vocab dims all divide 8 so the config shards on any
        # tp<=8 mesh in tests and dry runs.
        return cls(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=8, num_kv_heads=8, head_dim=8,
        )

    @classmethod
    def tiny_moe(cls) -> "LlamaConfig":
        return cls(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=8, num_kv_heads=8, head_dim=8,
            num_experts=4, num_experts_per_tok=2,
        )


class RMSNorm(nn.Module):
    eps: float
    dtype: Any
    # Gemma convention: the stored param is an OFFSET applied as (1 + w),
    # zero-initialized (HF Gemma checkpoints carry the same layout).
    offset: bool = False

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(
                nn.initializers.zeros_init() if self.offset
                else nn.initializers.ones,
                (None,),
            ),
            (x.shape[-1],),
            jnp.float32,
        )
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        out = x.astype(jnp.float32) * jax.lax.rsqrt(var + self.eps)
        if self.offset:
            scale = 1.0 + scale
        return (out * scale).astype(self.dtype)


def _mlp_act(cfg: LlamaConfig):
    if cfg.mlp_act == "silu":
        return nn.silu
    if cfg.mlp_act == "gelu_tanh":
        return lambda x: nn.gelu(x, approximate=True)
    raise ValueError(f"unknown mlp_act {cfg.mlp_act!r}")


def rope(q, k, positions, theta: float):
    """Rotary position embeddings applied to q/k: (..., seq, heads, head_dim)."""
    head_dim = q.shape[-1]
    freqs = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (b, s, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]  # (b, s, 1, hd/2)
    sin = jnp.sin(angles)[..., :, None, :]

    def rotate(x):
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    return rotate(q).astype(q.dtype), rotate(k).astype(k.dtype)


class Attention(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        dense = lambda feats, name, axes: nn.DenseGeneral(  # noqa: E731
            feats,
            axis=-1,
            # Qwen2-style checkpoints carry q/k/v biases (sharded over the
            # same head axis as the kernel's output dims).
            use_bias=cfg.attention_bias,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), axes
            ),
            bias_init=nn.with_logical_partitioning(
                nn.initializers.zeros_init(), axes[1:]
            ),
            name=name,
        )
        q = dense((cfg.num_heads, cfg.head_dim), "q_proj", ("embed", "heads", None))(x)
        k = dense((cfg.num_kv_heads, cfg.head_dim), "k_proj", ("embed", "kv_heads", None))(x)
        v = dense((cfg.num_kv_heads, cfg.head_dim), "v_proj", ("embed", "kv_heads", None))(x)
        if cfg.decode:
            out = self._cached_attention(q, k, v)
        else:
            q, k = rope(q, k, positions, cfg.rope_theta)
            out = _attend(cfg, q, k, v)
        out = nn.DenseGeneral(
            cfg.hidden_size,
            axis=(-2, -1),
            use_bias=False,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("heads", None, "embed")
            ),
            name="o_proj",
        )(out)
        return out

    def _cached_attention(self, q, k, v):
        """Decode-mode attention: roll q/k/v into a static-shape k/v cache
        (``lax.dynamic_update_slice`` at the running index — XLA-friendly,
        no growing shapes) and attend over the written prefix. Handles both
        the prefill call (q_len > 1, writes [0, L)) and single-token steps
        (q_len == 1, writes at idx). Cache variables are created lazily on
        the first ``mutable=["cache"]`` apply."""
        cfg = self.cfg
        if cfg.max_cache_len <= 0:
            raise ValueError("decode=True requires max_cache_len > 0")
        b, q_len = q.shape[0], q.shape[1]
        cached_k = self.variable(
            "cache",
            "k",
            jnp.zeros,
            (b, cfg.max_cache_len, cfg.num_kv_heads, cfg.head_dim),
            cfg.dtype,
        )
        cached_v = self.variable(
            "cache",
            "v",
            jnp.zeros,
            (b, cfg.max_cache_len, cfg.num_kv_heads, cfg.head_dim),
            cfg.dtype,
        )
        idx_var = self.variable(
            "cache", "idx", lambda: jnp.zeros((), jnp.int32)
        )
        idx = idx_var.value
        positions = jnp.broadcast_to(
            idx + jnp.arange(q_len)[None, :], (b, q_len)
        )
        q, k = rope(q, k, positions, cfg.rope_theta)
        new_k = jax.lax.dynamic_update_slice(
            cached_k.value, k.astype(cfg.dtype), (0, idx, 0, 0)
        )
        new_v = jax.lax.dynamic_update_slice(
            cached_v.value, v.astype(cfg.dtype), (0, idx, 0, 0)
        )
        cached_k.value, cached_v.value = new_k, new_v
        idx_var.value = idx + q_len
        # Causal over the WRITTEN prefix: kv position j participates for
        # query position p iff j <= p (unwritten tail is masked out too).
        q_pos = idx + jnp.arange(q_len)
        kv_pos = jnp.arange(cfg.max_cache_len)
        mask = kv_pos[None, None, None, :] <= q_pos[None, None, :, None]
        return jax.nn.dot_product_attention(q, new_k, new_v, mask=mask)


def _attend(cfg: LlamaConfig, q, k, v):
    """Causal attention dispatch: dense flash kernel, or sequence-parallel
    ring / Ulysses over the mesh's sp axis for long contexts."""
    use_sp = (
        cfg.attn_impl in ("ring", "ulysses")
        and cfg.mesh is not None
        and "sp" in cfg.mesh.axis_names
        and cfg.mesh.shape["sp"] > 1
    )
    if not use_sp:
        # The model's dense path stays on XLA's own attention (GQA handled
        # natively); how it compares with the pallas kernel on a chip is
        # not measured (see ops/flash_attention.py).
        return jax.nn.dot_product_attention(q, k, v, is_causal=True)
    from torchstore_tpu.ops._sharded import make_sharded_attention
    from torchstore_tpu.ops.ring_attention import ring_attention
    from torchstore_tpu.ops.ulysses_attention import ulysses_attention

    sp_size = cfg.mesh.shape["sp"]
    # Keep heads tensor-parallel inside the shard_map (the bodies only
    # collective over sp) instead of redundantly all-gathering over tp.
    # Both q and kv head counts must divide tp for that.
    head_axis = None
    tp_size = 1
    if "tp" in cfg.mesh.axis_names:
        size = cfg.mesh.shape["tp"]
        if (
            size > 1
            and cfg.num_heads % size == 0
            and cfg.num_kv_heads % size == 0
        ):
            head_axis = "tp"
            tp_size = size
    impl = cfg.attn_impl
    if impl == "ulysses":
        # Divisibility applies to the SHARD-LOCAL head counts (after any tp
        # split); kv heads pass through unrepeated (GQA-native). Indivisible
        # head counts FALL BACK to ring attention (which has no head
        # constraint — k/v blocks rotate whole) instead of failing the
        # forward pass: the model keeps training, one warning names the
        # boundary that was hit.
        local_heads = cfg.num_heads // tp_size
        local_kv = cfg.num_kv_heads // tp_size
        if local_heads % sp_size != 0 or local_kv % sp_size != 0:
            from torchstore_tpu.logging import get_logger

            get_logger("torchstore_tpu.models.llama").warning(
                "ulysses attention needs per-shard head counts (q=%d, kv=%d) "
                "divisible by the sp axis size (%d); falling back to ring "
                "attention for this config",
                local_heads,
                local_kv,
                sp_size,
            )
            impl = "ring"
    body = ring_attention if impl == "ring" else ulysses_attention
    fn = make_sharded_attention(
        body, cfg.mesh, "sp", True, head_axis,
        # Ring's default ("auto") body may run the fused pallas kernel.
        relax_vma=impl == "ring",
    )
    return fn(q, k, v)


class MLP(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = lambda feats, name, axes: nn.Dense(  # noqa: E731
            feats,
            use_bias=False,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), axes
            ),
            name=name,
        )
        gate = dense(cfg.intermediate_size, "gate_proj", ("embed", "mlp"))(x)
        up = dense(cfg.intermediate_size, "up_proj", ("embed", "mlp"))(x)
        return dense(cfg.hidden_size, "down_proj", ("mlp", "embed"))(
            _mlp_act(cfg)(gate) * up
        )


class MoE(nn.Module):
    """Mixtral-style sparse MoE: top-k routing over experts stored as stacked
    kernels with a leading ``expert`` axis (shards over the ep mesh axis and
    maps onto the store's expert-parallel put/get pattern)."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, h = x.shape
        router = nn.Dense(
            cfg.num_experts,
            use_bias=False,
            dtype=jnp.float32,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", None)
            ),
            name="router",
        )(x.astype(jnp.float32))
        weights, selected = jax.lax.top_k(
            jax.nn.softmax(router, axis=-1), cfg.num_experts_per_tok
        )
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

        def expert_kernel(name, shape, axes):
            return self.param(
                name,
                nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), ("expert",) + axes
                ),
                (cfg.num_experts,) + shape,
                cfg.param_dtype,
            )

        w_gate = expert_kernel("gate_proj", (h, cfg.intermediate_size), ("embed", "mlp"))
        w_up = expert_kernel("up_proj", (h, cfg.intermediate_size), ("embed", "mlp"))
        w_down = expert_kernel("down_proj", (cfg.intermediate_size, h), ("mlp", "embed"))

        # Dense-einsum MoE (every expert computes, tokens select via one-hot):
        # compiler-friendly (static shapes, no gather/scatter) and exact; a
        # capacity-based sparse kernel is the optimization path for scale.
        one_hot = jax.nn.one_hot(selected, cfg.num_experts, dtype=cfg.dtype)
        gates = jnp.einsum("bske,bsk->bse", one_hot, weights.astype(cfg.dtype))
        xe = x.astype(cfg.dtype)
        hidden = _mlp_act(cfg)(
            jnp.einsum("bsh,ehm->besm", xe, w_gate.astype(cfg.dtype))
        ) * jnp.einsum("bsh,ehm->besm", xe, w_up.astype(cfg.dtype))
        out = jnp.einsum("besm,emh->besh", hidden, w_down.astype(cfg.dtype))
        return jnp.einsum("besh,bse->bsh", out, gates)


def _constrain(x, axes):
    """Activation sharding constraint via logical axes; a no-op outside a
    flax logical_axis_rules context (see parallel.activation_rules). 'seq'
    maps to the sp mesh axis — sequence parallelism for long contexts."""
    return nn.with_logical_constraint(x, axes)


class Block(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        x = _constrain(x, ("batch", "seq", "embed"))
        x = x + Attention(cfg, name="attn")(
            RMSNorm(cfg.rms_eps, cfg.dtype, cfg.rms_offset, name="attn_norm")(x),
            positions,
        )
        mlp_cls = MoE if cfg.num_experts else MLP
        x = x + mlp_cls(cfg, name="mlp")(
            RMSNorm(cfg.rms_eps, cfg.dtype, cfg.rms_offset, name="mlp_norm")(x)
        )
        return _constrain(x, ("batch", "seq", "embed"))


class Llama(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        embed = nn.Embed(
            cfg.vocab_size,
            cfg.hidden_size,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ("vocab", "embed")
            ),
            name="embed",
        )
        x = embed(tokens)
        if cfg.scale_embeddings:
            # Gemma normalizer: sqrt(hidden) in the embedding dtype (HF
            # casts the normalizer to the activation dtype before scaling).
            x = x * jnp.asarray(
                jnp.sqrt(jnp.float32(cfg.hidden_size)), x.dtype
            )
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[-1]), tokens.shape
        )
        for i in range(cfg.num_layers):
            x = Block(cfg, name=f"layer_{i}")(x, positions)
        x = RMSNorm(cfg.rms_eps, cfg.dtype, cfg.rms_offset, name="final_norm")(x)
        if cfg.tie_embeddings:
            # Gemma ties the output head to the embedding table. Compute in
            # f32 like the untied lm_head Dense below — Embed.attend would
            # round the big vocab matmul to cfg.dtype (bf16) first.
            return jnp.einsum(
                "bsh,vh->bsv",
                x.astype(jnp.float32),
                embed.embedding.astype(jnp.float32),
            )
        logits = nn.Dense(
            cfg.vocab_size,
            use_bias=False,
            dtype=jnp.float32,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", "vocab")
            ),
            name="lm_head",
        )(x)
        return logits


def init_params(cfg: LlamaConfig, rng=None, batch: int = 1, seq: int = 8):
    rng = rng if rng is not None else jax.random.key(0)
    model = Llama(cfg)
    tokens = jnp.zeros((batch, seq), jnp.int32)
    return model, model.init(rng, tokens)
