"""The main path's kernels and train step COMPILE for the chip — checked
here, without one, by the TPU compiler against a described ``v5e:2x2``
topology (on-chip-measurement guide, section 2). Interpret-mode tests cannot
see what this sees: a tile the chip refuses, too much VMEM, a program that
does not fit HBM, a kernel that cannot be partitioned. A compile that passes
is not a chip run: nothing here executes, and no time comes out of it.

Also here: one case per silent fallback this path used to have, showing the
error now surfaces.
"""

import importlib
import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (  # noqa: E402
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)
try:
    import chip_smoke
finally:
    sys.path.remove(REPO_ROOT)

# The package re-exports the ``flash_attention`` FUNCTION under the module's
# own name; the tests need the module.
fa = importlib.import_module("torchstore_tpu.ops.flash_attention")

HBM_BYTES = 16 * 1000**3  # one TPU v5e chip (Google Cloud, "TPU v5e")
# Llama-3-8B attention: 32 query heads, 8 kv heads, head_dim 128.
HEADS, KV_HEADS, HEAD_DIM = 32, 8, 128


@pytest.fixture(scope="module")
def v5e():
    """Four described (unattached) TPU v5e devices. The persistent compile
    cache is off around these compiles: an entry written for a described
    chip cannot be read back without one and would warn on the next run."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as exc:  # noqa: BLE001 - no TPU compiler installed
            pytest.skip(f"cannot describe a v5e:2x2 topology here: {exc}")
        yield list(topo.devices)
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        cc.reset_cache()
        mp.undo()


def _qkv(seq: int, sharding):
    def sds(heads):
        return jax.ShapeDtypeStruct(
            (1, seq, heads, HEAD_DIM), jnp.bfloat16, sharding=sharding
        )

    return sds(HEADS), sds(KV_HEADS), sds(KV_HEADS)


def _flash(devices, monkeypatch):
    fn = jax.jit(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True, interpret=False)
    )
    return fn.lower(*_qkv(2048, SingleDeviceSharding(devices[0])))


def _flash_stats(devices, monkeypatch):
    fn = jax.jit(
        lambda q, k, v: fa.flash_attention_stats(
            q, k, v, causal_diag=True, interpret=False
        )
    )
    return fn.lower(*_qkv(2048, SingleDeviceSharding(devices[0])))


def _ring(devices, monkeypatch):
    from torchstore_tpu.ops._sharded import make_sharded_attention
    from torchstore_tpu.ops.ring_attention import ring_attention

    # The ring body takes no ``interpret`` argument, and this process's
    # backend is the CPU: steer the kernels' choice here, in the test.
    monkeypatch.setattr(fa, "_interpret_mode", lambda interpret: False)
    mesh = Mesh(np.array(devices).reshape(4), ("sp",))
    fn = make_sharded_attention(
        ring_attention, mesh, "sp", True, impl="fused", relax_vma=True
    )
    return fn.lower(*_qkv(8192, NamedSharding(mesh, P(None, "sp", None, None))))


@pytest.mark.parametrize(
    "lower",
    [_flash, _flash_stats, _ring],
    ids=["flash_attention", "flash_attention_stats", "ring_attention-4dev"],
)
def test_kernel_compiles_for_v5e(v5e, monkeypatch, lower):
    compiled = lower(v5e, monkeypatch).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel inside"


def test_smoke_train_step_fits_the_chip(v5e):
    """chip_smoke.py's train step at its real size (Llama-3-8B widths, L=4,
    bf16, optax.sgd, tokens 1 x 513): compiles, and its arguments plus
    temporaries leave room in HBM for the generator's copy of the weights."""
    import optax

    from torchstore_tpu import parallel
    from torchstore_tpu.models.llama import Llama

    cfg = chip_smoke.smoke_config()
    model = Llama(cfg)
    one_chip = SingleDeviceSharding(v5e[0])
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        parallel.unbox(
            jax.eval_shape(
                model.init, jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
            )
        ),
    )
    optimizer = optax.sgd(chip_smoke.LEARNING_RATE)
    compiled = (
        parallel.make_train_step(model, optimizer)
        .lower(
            params,
            jax.eval_shape(optimizer.init, params),
            jax.ShapeDtypeStruct(
                (1, chip_smoke.TRAIN_SEQ + 1), jnp.int32, sharding=one_chip
            ),
        )
        .compile()
    )
    mem = compiled.memory_analysis()
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert weights >= 3.8e9
    # Outputs alias the donated arguments, so the step holds its arguments
    # and its temporaries; the generator holds one more copy of the weights.
    assert mem.argument_size_in_bytes >= weights
    step = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert step + weights < HBM_BYTES, (step, weights)


@pytest.mark.parametrize(
    "shape, dtype",
    [
        ((8, 4096, 14336), jnp.bfloat16),  # Mixtral's stacked expert leaf, 939 MB
        ((32000, 4096), jnp.bfloat16),  # its embedding, 262 MB
        ((4096, 4096), jnp.bfloat16),  # q_proj: the smallest leaf that chunks
    ],
    ids=["experts", "embedding", "q_proj"],
)
def test_d2h_slice_program_holds_one_block(v5e, shape, dtype):
    """The program a chunked device->host copy runs (`sharding._slicer`) at
    Mixtral's widths: compiles for the chip, gives one block of at most a
    chunk, and needs no temporary of the leaf's size beside it (a publish
    holds two copies of the weights already)."""
    from torchstore_tpu import sharding as shd

    one_chip = SingleDeviceSharding(v5e[0])
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    nbytes = int(np.prod(shape)) * 2
    assert nbytes >= shd.D2H_CHUNK_THRESHOLD
    axis, rows = shd._block_plan(shape, 2, shd.D2H_CHUNK_BYTES)
    assert shape[axis] % rows == 0, "these leaves cut into equal blocks: one program"
    at = jax.ShapeDtypeStruct((axis + 1,), jnp.int32, sharding=one_chip)
    compiled = shd._slicer().lower(x, at, axis=axis, rows=rows).compile()
    mem = compiled.memory_analysis()
    block = rows * int(np.prod(shape[axis + 1 :])) * 2
    assert shd.D2H_CHUNK_BYTES // 2 < block <= shd.D2H_CHUNK_BYTES
    assert mem.output_size_in_bytes <= 2 * shd.D2H_CHUNK_BYTES
    assert mem.temp_size_in_bytes <= shd.D2H_CHUNK_BYTES, mem.temp_size_in_bytes


def test_device_cast_surfaces_a_kernel_error(monkeypatch):
    """``device_cast`` used to try a Pallas kernel and swallow ANY exception
    from it; whatever its kernel raises now reaches the caller."""
    from torchstore_tpu.ops import staging

    def broken(dtype_str):
        def cast(x):
            raise RuntimeError("kernel refused by the compiler")

        return cast

    monkeypatch.setattr(staging, "_cast_fn", broken)
    with pytest.raises(RuntimeError, match="kernel refused"):
        staging.device_cast(jnp.ones((8, 128), jnp.float32), "bfloat16")


def test_native_refuses_a_stale_library(monkeypatch):
    """A library that is not the version the bindings were written for used
    to be bound partially (v1/v2) or dropped for numpy; now it raises."""
    from torchstore_tpu import native

    if not native.available():
        pytest.skip("no toolchain here: nothing was built")
    monkeypatch.setattr(native, "VERSION", native.VERSION + 1)
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_attempted", False)
    with pytest.raises(RuntimeError, match="is version"):
        native.get_lib()
