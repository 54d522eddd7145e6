"""Native data-path library + device ops tests."""

import numpy as np
import pytest

from torchstore_tpu import native


class TestNative:
    def test_fast_copy_correctness_large(self):
        src = np.random.rand(4 * 1024 * 1024).astype(np.float32)  # 16 MB
        dst = np.empty_like(src)
        native.fast_copy(dst, src)
        np.testing.assert_array_equal(dst, src)

    def test_fast_copy_small_uses_numpy(self):
        src = np.arange(16.0)
        dst = np.zeros(16)
        native.fast_copy(dst, src)
        np.testing.assert_array_equal(dst, src)

    def test_fast_copy_dtype_mismatch_falls_back(self):
        src = np.arange(16, dtype=np.int64)
        dst = np.zeros(16, dtype=np.float64)
        native.fast_copy(dst, src)  # numpy handles the cast path
        np.testing.assert_array_equal(dst, src.astype(np.float64))

    def test_copy_2d_strided(self):
        if not native.available():
            pytest.skip("native library not built")
        base = np.random.rand(4096, 1024).astype(np.float32)
        src = base[:, :512]
        dstbase = np.zeros_like(base)
        dst = dstbase[:, :512]
        # Force through the 2d path regardless of size threshold.
        lib = native.get_lib()
        lib.ts_copy_2d(
            dst.__array_interface__["data"][0], dst.strides[0],
            src.__array_interface__["data"][0], src.strides[0],
            512 * 4, 4096, 0,
        )
        np.testing.assert_array_equal(dst, src)
        assert dstbase[:, 512:].sum() == 0  # untouched outside the block

    def test_fd_io_roundtrip(self):
        if not native.available():
            pytest.skip("native library not built")
        import socket

        lib = native.get_lib()
        a, b = socket.socketpair()
        src = np.random.rand(1024).astype(np.float32)
        dst = np.zeros_like(src)
        sent = lib.ts_write_fd(a.fileno(), src.__array_interface__["data"][0], src.nbytes)
        assert sent == src.nbytes
        got = lib.ts_read_fd(b.fileno(), dst.__array_interface__["data"][0], dst.nbytes)
        assert got == dst.nbytes
        np.testing.assert_array_equal(dst, src)
        a.close()
        b.close()


class TestOps:
    @pytest.mark.parametrize(
        "shape, dtype",
        [((64,), "bfloat16"), ((32, 128), "bfloat16"), ((100,), "float16")],
        ids=["1d", "tile-aligned", "unaligned"],
    )
    def test_device_cast(self, shape, dtype):
        pytest.importorskip("jax")
        import jax.numpy as jnp

        from torchstore_tpu.ops import device_cast

        x = jnp.arange(np.prod(shape), dtype=jnp.float32).reshape(shape)
        out = device_cast(x, dtype)
        assert out.dtype == jnp.dtype(dtype) and out.shape == x.shape
        np.testing.assert_allclose(
            np.asarray(out, dtype=np.float32), np.asarray(x), rtol=1e-2
        )

    def test_ici_reshard(self):
        jax = pytest.importorskip("jax")
        from jax.sharding import NamedSharding, PartitionSpec as P

        from torchstore_tpu import parallel

        mesh1 = parallel.make_mesh({"x": 8})
        mesh2 = parallel.make_mesh({"a": 2, "b": 4})
        g = np.arange(64.0, dtype=np.float32).reshape(8, 8)
        x = jax.device_put(g, NamedSharding(mesh1, P("x", None)))
        y = parallel.reshard(x, NamedSharding(mesh2, P("b", "a")))
        np.testing.assert_array_equal(np.asarray(y), g)
        assert y.sharding.spec == P("b", "a")
