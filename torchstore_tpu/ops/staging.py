"""Device-side staging op: dtype cast for weight transfer.

The transfer-dtype cast (fp32 -> bf16 before shipping weights,
/root/reference/torchstore/state_dict_utils.py:177-189 does it on host with
torch) runs on-device here so the HBM->host copy moves half the bytes.

``device_cast`` is a jitted ``astype``: XLA emits one fused convert kernel.
A hand-written Pallas cast tiled to one (8, 128) VPU tile per grid step was
measured against it on a TPU v5 lite chip at the Llama-3-8B MLP shape
(4096 x 14336, f32 -> bf16; my chip run, PR 21): 1.19 ms for the jitted
``astype`` against 69.9 ms for the Pallas kernel's 57 344 grid steps, so the
kernel was removed and nothing here falls back to anything.
"""

from __future__ import annotations

import functools


@functools.cache
def _cast_fn(dtype_str: str):
    import jax

    def cast(x):
        return x.astype(dtype_str)

    # No donation: the caller (a training loop publishing weights) still
    # owns and needs the original buffers after staging.
    return jax.jit(cast)


def device_cast(x, dtype):
    """On-device dtype cast (one fused XLA kernel). Used by the direct-sync
    source so the HBM->host copy moves the transfer dtype's bytes, not the
    param dtype's."""
    import numpy as np

    dtype_str = str(np.dtype(dtype) if isinstance(dtype, type) else dtype)
    return _cast_fn(dtype_str)(x)
