from torchstore_tpu.ops.flash_attention import flash_attention
from torchstore_tpu.ops.ring_attention import ring_attention, ring_attention_sharded
from torchstore_tpu.ops.staging import device_cast
from torchstore_tpu.ops.ulysses_attention import (
    ulysses_attention,
    ulysses_attention_sharded,
)

__all__ = [
    "device_cast",
    "flash_attention",
    "ring_attention",
    "ring_attention_sharded",
    "ulysses_attention",
    "ulysses_attention_sharded",
]
