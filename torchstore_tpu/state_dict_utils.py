"""state_dict sync layer: flatten / commit-marker / dtype-cast / unflatten.

TPU-native equivalent of /root/reference/torchstore/state_dict_utils.py:27-275.
Protocol (invariant 3, SURVEY §2.2): all tensor entries are put under
``key/<flat_path>`` first, then ``key/MAPPING`` is written LAST as the commit
marker — its presence implies a complete state dict; readers fetch it first
and fail with "no matching push" when absent.

Flattening is dependency-free (dict / list / tuple / NamedTuple recursion)
so it handles flax param trees, optax optimizer states and plain nested
dicts without importing jax; leaves may be jax.Arrays (sharded puts/gets go
through the normal resharding pipeline), numpy arrays, or arbitrary objects.
"""

from __future__ import annotations

import struct
import weakref
from typing import Any, Optional

import numpy as np

from torchstore_tpu import faults
from torchstore_tpu import sharding as shd
from torchstore_tpu import torch_interop
from torchstore_tpu.logging import LatencyTracker, get_logger
from torchstore_tpu.native import copy_into
from torchstore_tpu.observability import metrics as obs_metrics
from torchstore_tpu.observability.tracing import span
from torchstore_tpu.transport.types import _np_dtype  # bf16-aware name->dtype

logger = get_logger("torchstore_tpu.state_dict")

MAPPING_KEY = "MAPPING"
_SEP = "/"


class NoMatchingPush(KeyError):
    pass


# --------------------------------------------------------------------------
# flatten / unflatten
# --------------------------------------------------------------------------


def _is_leaf(value: Any) -> bool:
    if isinstance(value, dict):
        return False
    if isinstance(value, (list, tuple)):
        return False
    return True


def _axis_metadata_box(value: Any):
    """The flax AxisMetadata box wrapping ``value``, or None. Trees straight
    out of ``model.init`` with ``nn.with_logical_partitioning`` carry
    LogicallyPartitioned/Partitioned leaves; stored boxed, their jax arrays
    would ride the opaque object path (no resharding, full-serialize puts).
    Flatten unboxes them — the array takes the tensor path — and records the
    empty box in the mapping so unflatten restores the exact structure."""
    from flax.core import meta as flax_meta

    if isinstance(value, flax_meta.AxisMetadata):
        return value.replace_boxed(None)
    return None


def flatten_state_dict(sd: Any) -> tuple[dict[str, Any], dict]:
    """Returns ({flat_path: leaf}, mapping). ``mapping`` is a picklable
    template that records the container structure (incl. NamedTuple types by
    import path) for exact reconstruction — the role DCP's
    ``flatten_state_dict`` plays in the reference."""
    flat: dict[str, Any] = {}
    mapping = _flatten_rec(sd, [], flat)
    return flat, mapping


def _flatten_rec(value: Any, path: list[str], flat: dict[str, Any]) -> dict:
    # Module-level recursion for the same reason as _unflatten_rec: an inner
    # closure would be a cycle pinning every leaf array until cyclic GC.
    if isinstance(value, dict):
        return {
            "kind": "dict",
            "items": {
                str(k): _flatten_rec(v, path + [str(k)], flat)
                for k, v in value.items()
            },
            "key_types": {str(k): _key_type(k) for k in value},
        }
    if isinstance(value, (list, tuple)):
        kind = "list" if isinstance(value, list) else "tuple"
        entry: dict = {
            "kind": kind,
            "items": [
                _flatten_rec(v, path + [str(i)], flat)
                for i, v in enumerate(value)
            ],
        }
        if isinstance(value, tuple) and hasattr(value, "_fields"):
            entry["kind"] = "namedtuple"
            entry["cls"] = f"{type(value).__module__}:{type(value).__qualname__}"
        return entry
    flat_key = _SEP.join(path)
    if flat_key in flat:
        raise ValueError(f"duplicate flattened key {flat_key!r}")
    box = _axis_metadata_box(value)
    if box is not None:
        flat[flat_key] = value.unbox()
        return {"kind": "boxed", "key": flat_key, "box": box}
    flat[flat_key] = value
    return {"kind": "leaf", "key": flat_key}


def _key_type(key: Any) -> str:
    if isinstance(key, int):
        return "int"
    return "str"


def unflatten_state_dict(flat: dict[str, Any], mapping: dict) -> Any:
    # Module-level recursion (not an inner closure): a self-referencing
    # closure is a reference cycle that pins ``flat``'s arrays — including
    # zero-copy SHM views — until the next cyclic GC pass, which defers
    # their release back to the storage volume.
    return _unflatten_rec(mapping, flat)


def _unflatten_rec(entry: dict, flat: dict[str, Any]) -> Any:
    kind = entry["kind"]
    if kind == "leaf":
        return flat[entry["key"]]
    if kind == "boxed":
        return entry["box"].replace_boxed(flat[entry["key"]])
    if kind == "dict":
        key_types = entry.get("key_types", {})
        return {
            (int(k) if key_types.get(k) == "int" else k): _unflatten_rec(v, flat)
            for k, v in entry["items"].items()
        }
    children = [_unflatten_rec(v, flat) for v in entry["items"]]
    if kind == "list":
        return children
    if kind == "tuple":
        return tuple(children)
    if kind == "namedtuple":
        cls = _resolve_class(entry["cls"])
        if cls is None:
            return tuple(children)
        return cls(*children)
    raise ValueError(f"corrupt mapping entry {entry!r}")


def _resolve_class(spec: str):
    mod_name, _, qual = spec.partition(":")
    try:
        import importlib

        obj = importlib.import_module(mod_name)
        for part in qual.split("."):
            obj = getattr(obj, part)
        return obj
    except Exception:
        logger.warning("cannot resolve NamedTuple class %s; using plain tuple", spec)
        return None


# --------------------------------------------------------------------------
# dtype cast
# --------------------------------------------------------------------------


def _is_floating(value: Any) -> bool:
    dtype = getattr(value, "dtype", None)
    if dtype is None:
        return False
    try:
        return np.issubdtype(np.dtype(dtype), np.floating) or "bfloat16" in str(dtype)
    except TypeError:
        return "float" in str(dtype)


def cast_floating_tensors(flat: dict[str, Any], transfer_dtype) -> dict[str, Any]:
    """Cast floating leaves to ``transfer_dtype`` before transfer (reference
    /root/reference/torchstore/state_dict_utils.py:177-189). jax.Arrays cast
    on-device (one fused XLA op per leaf); numpy casts on host."""
    out = {}
    for key, value in flat.items():
        if not _is_floating(value):
            out[key] = value
        elif torch_interop.is_torch_tensor(value):
            out[key] = torch_interop.astype_numpy(value, transfer_dtype)
        else:
            out[key] = value.astype(transfer_dtype)
    return out


# --------------------------------------------------------------------------
# transfer quantization: blockwise int8/int4 fused blobs + delta tier
# --------------------------------------------------------------------------
#
# Every quantized floating leaf crosses the wire (and sits in the store) as
# ONE self-describing uint8 blob: [header+shape | changed-block bitmap |
# packed codes | f32 scale table]. The scale slot is laid out by
# transport.landing.quant_blob_layout (compute_arena_layout's scale-slot
# mode), so scales provably share a segment with the payload they decode —
# one handshake, one segment, never a separate RPC. Because the blob is an
# ordinary byte tensor, arena packing, bulk framing, doorbells, one-sided
# stamped reads, and the plan cache all carry it unchanged; the MAPPING
# marker only records WHICH keys are quantized (iteration-stable metadata),
# so quantized publishes are plan-cacheable like everything else.
#
# Modes (``TORCHSTORE_TPU_TRANSFER_QUANT`` / ``transfer_quant=``):
#   int8        symmetric per-tensor int8 (one block spanning the tensor)
#   int8_block  symmetric per-block int8, TORCHSTORE_TPU_TRANSFER_QUANT_BLOCK
#               elements per block (finer scales: better accuracy at ~1.6%
#               extra wire bytes at the default block of 256)
#   int4_block  two 4-bit codes per byte, per-block scales (8x vs f32)
#
# Delta tier (weight_channel versions only — a delta blob is NOT
# self-contained, so it never rides a same-key overwrite): the publisher's
# DeltaEncoder keeps the last-shipped dequantized baseline per key and
# ships quantized ``w_t - w_{t-1}`` with a per-block changed bitmap;
# near-zero blocks are skipped entirely, fully-unchanged keys publish NO
# bytes (an unchanged-watermark alias to the v_{t-1} store key). Readers
# accumulate through DeltaDecoder with the IDENTICAL f32 arithmetic, so
# reader state is bit-identical to the publisher baseline; a full keyframe
# every TORCHSTORE_TPU_DELTA_KEYFRAME versions bounds the chain a joiner
# must walk (and the publisher enforces keep >= keyframe cadence so the
# chain is always retained).

QUANT_MODES = ("int8", "int8_block", "int4_block")
_QUANT_MAGIC = 0x42515354  # "TSQB" little-endian
_QUANT_CODEC = 1
# Wire packing code: 1 = one int8 code per element, 2 = packed int4 pairs.
_FMT_CODES = {"int8": 1, "int8_block": 1, "int4_block": 2}
_QMAX = {"int8": 127, "int8_block": 127, "int4_block": 7}
_FLAG_DELTA = 1
_FLAG_KEYFRAME = 2

_QUANT_BYTES_IN = obs_metrics.counter(
    "ts_quant_bytes_in_total",
    "Full-precision bytes entering the transfer-quantization tier, by fmt",
)
_QUANT_BYTES_WIRE = obs_metrics.counter(
    "ts_quant_bytes_wire_total",
    "Fused quant-blob bytes actually shipped (payload + scales), by fmt",
)
_DELTA_SKIPPED = obs_metrics.counter(
    "ts_delta_skipped_blocks_total",
    "Near-zero residual blocks a delta publish skipped entirely",
)
_DELTA_KEYFRAMES = obs_metrics.counter(
    "ts_delta_keyframes_total",
    "Full keyframes published by the delta tier (cadence + restructures)",
)
_DELTA_UNCHANGED = obs_metrics.counter(
    "ts_delta_unchanged_keys_total",
    "Delta publishes of a fully-unchanged key (alias, zero bytes shipped)",
)
_DELTA_UNCHANGED_SERVED = obs_metrics.counter(
    "ts_delta_unchanged_served_total",
    "Unchanged-key reads served from this reader's accumulated v-1 state "
    "with zero re-transfer",
)


def _checked_scale(
    key: str, amax: float, qmax: float = 127.0, block: Optional[int] = None
) -> float:
    """max|x|/qmax with non-finite inputs rejected LOUDLY: a NaN amax would
    silently fall back to scale=1 (zeroing typical sub-unit weights) and an
    Inf scale would dequantize to all-NaN — exactly the silent corruption a
    weight-sync layer must never pass along. ``block`` names the offending
    block in the blockwise path, so one NaN block is findable in a
    thousand-block tensor."""
    if not np.isfinite(amax):
        where = f"{key!r}" if block is None else f"{key!r} (block {block})"
        raise ValueError(
            f"cannot quantize {where}: contains non-finite values "
            f"(max|x| = {amax}); publish unquantized or clean the weights"
        )
    return amax / qmax if amax > 0 else 1.0


def _block_scales(key: str, amax: np.ndarray, qmax: int) -> np.ndarray:
    """Per-block scales (f32) with the non-finite check applied per block —
    the raise names key AND block index via :func:`_checked_scale`."""
    finite = np.isfinite(amax)
    if not finite.all():
        idx = int(np.argmax(~finite))
        _checked_scale(key, float(amax[idx]), qmax, block=idx)
    scales = (amax / qmax).astype(np.float32)
    scales[scales == 0.0] = np.float32(1.0)
    return scales


def _dequant_codes(codes: Any, scales: Any):
    """THE dequantization arithmetic — f32(codes) * f32(scales) — shared by
    the scalar helper, the blockwise codec, and both array backends. np and
    jax-cpu produce bit-identical bytes through this one path (the
    cross-backend equivalence test pins it), so publisher baselines and
    reader accumulations can never drift."""
    if shd.is_jax_array(codes):
        import jax.numpy as jnp

        return codes.astype(jnp.float32) * jnp.asarray(
            np.asarray(scales, dtype=np.float32)
        )
    # One fused pass (cast + multiply in f32): bit-identical to the
    # two-step astype(f32) * f32 — int8 -> f32 is exact and the product is
    # the same IEEE f32 multiply (the cross-backend test pins this).
    return np.multiply(
        codes, np.asarray(scales, dtype=np.float32), dtype=np.float32
    )


def _dequantize(q: Any, scale: float, dtype_name: str, target: Any = None):
    """codes -> original dtype through the one blessed :func:`_dequant_codes`
    path (both backends dequantize in f32 with an f32 scale — no more
    numpy-rounds-the-scale-but-jax-does-not seam). ``target`` (numpy view of
    user memory) gets the result in place."""
    dequant = _dequant_codes(q, scale)
    if shd.is_jax_array(dequant):
        return dequant.astype(_np_dtype(dtype_name))
    if target is not None:
        # Native landing path; raises on shape mismatch (no broadcast).
        copy_into(target, dequant.astype(target.dtype))
        return target
    return dequant.astype(_np_dtype(dtype_name))


def _as_blocks(flat_f32: np.ndarray, block: int) -> np.ndarray:
    """1-D f32 -> (nblocks, block), zero-padding the tail block. Always at
    least one block so empty tensors stay representable."""
    n = flat_f32.shape[0]
    nblocks = max(1, -(-n // block))
    if n == nblocks * block:
        return flat_f32.reshape(nblocks, block)
    padded = np.zeros(nblocks * block, np.float32)
    padded[:n] = flat_f32
    return padded.reshape(nblocks, block)


def _pack_codes(codes: np.ndarray, fmt_code: int) -> np.ndarray:
    if fmt_code == 1:
        return np.ascontiguousarray(codes).reshape(-1).view(np.uint8)
    u = (codes & 0x0F).astype(np.uint8)
    if u.shape[1] % 2:
        u = np.concatenate(
            [u, np.zeros((u.shape[0], 1), np.uint8)], axis=1
        )
    return np.ascontiguousarray(u[:, 0::2] | (u[:, 1::2] << 4)).reshape(-1)


def _unpack_codes(
    packed: np.ndarray, fmt_code: int, changed: int, block: int
) -> np.ndarray:
    if fmt_code == 1:
        return packed.view(np.int8).reshape(changed, block)
    pb = packed.reshape(changed, (block + 1) // 2)
    u = np.empty((changed, 2 * pb.shape[1]), np.uint8)
    u[:, 0::2] = pb & 0x0F
    u[:, 1::2] = pb >> 4
    codes = u[:, :block].astype(np.int8)
    codes[codes > 7] -= 16  # sign-extend 4-bit two's complement
    return codes


def _build_quant_blob(
    fmt: str,
    block: int,
    shape: tuple,
    dtype_name: str,
    nblocks: int,
    changed_mask: np.ndarray,
    codes: np.ndarray,
    scales: np.ndarray,
    flags: int,
    version: int,
    base_version: int,
) -> np.ndarray:
    """Assemble one fused wire blob. ``codes``: (changed, block) int8;
    ``scales``: (changed,) f32 — the scale slot offset comes from the
    arena-layout module, so scales land in the same segment as the codes."""
    from torchstore_tpu.transport import landing

    fmt_code = _FMT_CODES[fmt]
    rank = len(shape)
    changed = int(codes.shape[0])
    layout = landing.quant_blob_layout(rank, nblocks, changed, fmt, block)
    blob = np.zeros(layout["total"], np.uint8)
    struct.pack_into(
        "<IHBBIII", blob, 0,
        _QUANT_MAGIC, _QUANT_CODEC, fmt_code, flags,
        int(block), int(nblocks), changed,
    )
    blob[20] = rank
    dt = dtype_name.encode("utf-8")[:16]
    if dt:
        blob[21:21 + len(dt)] = np.frombuffer(dt, np.uint8)
    nelems = int(np.prod(shape)) if rank else 1
    struct.pack_into("<Q", blob, 40, nelems)
    struct.pack_into("<qq", blob, 48, int(base_version), int(version))
    if rank:
        blob[64:64 + 8 * rank] = np.frombuffer(
            np.asarray(shape, dtype="<u8").tobytes(), np.uint8
        )
    bm = np.packbits(
        np.asarray(changed_mask, np.uint8), bitorder="little"
    )
    blob[layout["bitmap"]:layout["bitmap"] + bm.nbytes] = bm
    payload = _pack_codes(codes, fmt_code)
    if payload.nbytes:
        blob[layout["payload"]:layout["payload"] + payload.nbytes] = payload
    sc = np.ascontiguousarray(scales, dtype="<f4").view(np.uint8)
    if sc.nbytes:
        blob[layout["scales"]:layout["scales"] + sc.nbytes] = sc
    return blob


def parse_quant_blob(value: Any) -> Optional[dict]:
    """Parse one fused quant blob into its sections (views where possible);
    None when ``value`` is not a blob (wrong dtype/shape/magic) — the
    streamed path uses this to pass raw non-floating leaves through."""
    from torchstore_tpu.transport import landing

    blob = np.asarray(value)
    if (
        blob.dtype != np.uint8
        or blob.ndim != 1
        or blob.nbytes < landing.QUANT_HEADER_BYTES
    ):
        return None
    blob = np.ascontiguousarray(blob)
    magic, codec, fmt_code, flags, block, nblocks, changed = (
        struct.unpack_from("<IHBBIII", blob, 0)
    )
    if magic != _QUANT_MAGIC or codec != _QUANT_CODEC:
        return None
    rank = int(blob[20])
    dtype_name = bytes(blob[21:37]).split(b"\0", 1)[0].decode("utf-8")
    (nelems,) = struct.unpack_from("<Q", blob, 40)
    base_version, version = struct.unpack_from("<qq", blob, 48)
    shape = (
        tuple(
            int(x)
            for x in np.frombuffer(blob[64:64 + 8 * rank].tobytes(), "<u8")
        )
        if rank
        else ()
    )
    fmt = "int4_block" if fmt_code == 2 else "int8_block"
    layout = landing.quant_blob_layout(rank, nblocks, changed, fmt, block)
    bitmap_bytes = (nblocks + 7) // 8
    mask = (
        np.unpackbits(
            blob[layout["bitmap"]:layout["bitmap"] + bitmap_bytes],
            bitorder="little",
        )[:nblocks].astype(bool)
    )
    payload = blob[
        layout["payload"]:layout["payload"]
        + landing.quant_payload_nbytes(fmt, block, changed)
    ]
    codes = _unpack_codes(payload, fmt_code, changed, block)
    scales = np.frombuffer(
        blob[layout["scales"]:layout["scales"] + 4 * changed].tobytes(),
        "<f4",
    )
    return {
        "fmt": fmt,
        "flags": flags,
        "block": int(block),
        "nblocks": int(nblocks),
        "mask": mask,
        "codes": codes,
        "scales": scales,
        "shape": shape,
        "dtype": dtype_name,
        "nelems": int(nelems),
        "base_version": int(base_version),
        "version": int(version),
    }


def _leaf_f32_blocks(value: Any, block: int) -> tuple[np.ndarray, np.ndarray]:
    arr = np.asarray(value)
    flat32 = np.ascontiguousarray(arr).reshape(-1).astype(
        np.float32, copy=False
    )
    return arr, _as_blocks(flat32, block)


def _encode_keyframe_from_blocks(
    key: str,
    xb: np.ndarray,
    shape: tuple,
    dtype_name: str,
    fmt: str,
    block: int,
    version: int = -1,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Quantize pre-blocked f32 data: (blob, codes, scales). Pure math —
    safe to run on a landing-pool thread."""
    qmax = _QMAX[fmt]
    # Two reductions instead of abs() (a full-tensor temp): max|x| =
    # max(max(x), -min(x)).
    amax = np.maximum(xb.max(axis=1), -xb.min(axis=1))
    scales = _block_scales(key, amax, qmax)
    q = np.multiply(xb, (1.0 / scales)[:, None].astype(np.float32))
    np.rint(q, out=q)
    np.clip(q, -qmax, qmax, out=q)
    codes = q.astype(np.int8)
    blob = _build_quant_blob(
        fmt, block, shape, dtype_name, xb.shape[0],
        np.ones(xb.shape[0], bool), codes, scales,
        _FLAG_KEYFRAME, version, version,
    )
    return blob, codes, scales


def _encode_keyframe_blob(
    key: str, value: Any, fmt: str, block: int, version: int = -1
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Quantize one whole leaf: (blob, xb, codes, scales). The per-tensor
    ``int8`` mode is the degenerate one-block-per-tensor case."""
    arr, xb = _leaf_f32_blocks(value, block)
    blob, codes, scales = _encode_keyframe_from_blocks(
        key, xb, arr.shape, str(value.dtype), fmt, block, version
    )
    return blob, xb, codes, scales


def _quant_leaf_block(fmt: str, block: int, value: Any) -> int:
    """Effective block size for one leaf: per-tensor ``int8`` spans the
    whole tensor with one block; blockwise modes use the configured size."""
    if fmt != "int8":
        return block
    shape = tuple(getattr(value, "shape", ()) or ())
    nelems = int(np.prod(shape)) if shape else 1
    return max(1, nelems)


def _guard_quantizable(key: str, value: Any) -> None:
    if shd.is_jax_array(value) and not value.is_fully_addressable:
        # The scale must be GLOBAL and identical on every rank; an eager
        # max over a multi-controller array can't compute it (and per-rank
        # scales would decode inconsistently).
        raise NotImplementedError(
            f"transfer_quant on non-fully-addressable array "
            f"{key!r}: compute the quantized array + scales inside your "
            "jitted step (global max via a collective) and push those, "
            "or use transfer_dtype instead"
        )


def quantize_transfer(
    flat: dict[str, Any], fmt: str, block: int
) -> tuple[dict[str, Any], dict]:
    """Quantize every floating leaf of ``flat`` into a self-contained
    keyframe blob. Returns (out_flat, marker_meta) — the marker records
    only WHICH keys are quantized (iteration-stable), the scales ride the
    blobs themselves. Non-floating leaves pass through untouched."""
    out: dict[str, Any] = {}
    dtypes: dict[str, str] = {}
    qkeys: list[str] = []
    for key, value in flat.items():
        if torch_interop.is_torch_tensor(value):
            value = torch_interop.to_numpy_view(value)
        if not _is_floating(value):
            out[key] = value
            continue
        _guard_quantizable(key, value)
        blob, _, _, _ = _encode_keyframe_blob(
            key, value, fmt, _quant_leaf_block(fmt, block, value)
        )
        out[key] = blob
        qkeys.append(key)
        dtypes[key] = str(value.dtype)
        _record_quant_bytes(fmt, getattr(value, "nbytes", 0), blob.nbytes)
    return out, {
        "fmt": fmt,
        "block": block,
        "keys": qkeys,
        "dtypes": dtypes,
    }


def quantize_int8(flat: dict[str, Any]) -> tuple[dict[str, Any], dict]:
    """Per-tensor symmetric int8 (the classic mode) over the fused-blob
    wire format: one block spans each tensor, scale = max|x|/127 rides the
    blob's scale slot instead of the commit marker."""
    return quantize_transfer(flat, "int8", 0)


async def quantize_transfer_async(
    flat: dict[str, Any], fmt: str, block: int, config=None
) -> tuple[dict[str, Any], dict]:
    """:func:`quantize_transfer` with per-leaf encodes fanned out across
    the shared landing pool (numpy ufuncs release the GIL, so leaves
    encode in parallel instead of serially blocking the event loop) —
    the put hot path's entry."""
    import asyncio

    from torchstore_tpu.transport import landing

    out: dict[str, Any] = {}
    dtypes: dict[str, str] = {}
    qkeys: list[str] = []
    jobs: list[tuple[str, Any]] = []
    for key, value in flat.items():
        if torch_interop.is_torch_tensor(value):
            value = torch_interop.to_numpy_view(value)
        if not _is_floating(value):
            out[key] = value
            continue
        _guard_quantizable(key, value)
        if shd.is_jax_array(value):
            value = np.asarray(value)  # one D2H here, off the pool threads
        qkeys.append(key)
        dtypes[key] = str(value.dtype)
        jobs.append((key, value))

    async def _enc(key: str, value: Any) -> None:
        blob, _, _, _ = await landing.run_in_pool(
            _encode_keyframe_blob,
            key,
            value,
            fmt,
            _quant_leaf_block(fmt, block, value),
            config=config,
        )
        _record_quant_bytes(fmt, getattr(value, "nbytes", 0), blob.nbytes)
        out[key] = blob

    if jobs:
        await asyncio.gather(*(_enc(k, v) for k, v in jobs))
    return out, {
        "fmt": fmt,
        "block": block,
        "keys": qkeys,
        "dtypes": dtypes,
    }


def _record_quant_bytes(fmt: str, bytes_in: int, bytes_wire: int) -> None:
    """Count the tier's effect at its one choke point: full-precision bytes
    in, fused blob bytes out — both as metrics and as ledger cells so
    ``ts.traffic_matrix()["quant"]`` carries the effective compression
    ratio next to the wire edges the savings apply to."""
    from torchstore_tpu.observability import ledger as obs_ledger

    _QUANT_BYTES_IN.inc(int(bytes_in), fmt=fmt)
    _QUANT_BYTES_WIRE.inc(int(bytes_wire), fmt=fmt)
    obs_ledger.record(obs_ledger.QUANT, "logical", int(bytes_in))
    obs_ledger.record(obs_ledger.QUANT, "wire", int(bytes_wire))


def _delta_version_key(channel: str, version: int) -> str:
    """The state-dict key of one channel version — mirrors
    weight_channel._version_key (the delta chain walks versions by name)."""
    return f"{channel}/v{int(version)}"


async def _delta_encode_flat(
    flat: dict[str, Any], fmt: str, block: int, delta_ctx: dict
) -> tuple[dict[str, Any], dict, dict[str, int]]:
    """Delta-encode one version's flat dict through the publisher's codec.
    Returns (flat_to_put, marker_quant_meta, unchanged_aliases) — unchanged
    keys are ABSENT from the put flat (zero bytes ship) and recorded as
    {flat_key: base_version} aliases in the marker meta."""
    codec: DeltaEncoder = delta_ctx["codec"]
    if codec.fmt != fmt:
        raise ValueError(
            f"delta codec fmt {codec.fmt!r} != transfer_quant {fmt!r}"
        )
    import asyncio

    version = int(delta_ctx["version"])
    out: dict[str, Any] = {}
    dtypes: dict[str, str] = {}
    qkeys: list[str] = []
    aliases: dict[str, int] = {}
    jobs: list[tuple[str, Any]] = []
    for key, value in flat.items():
        if torch_interop.is_torch_tensor(value):
            value = torch_interop.to_numpy_view(value)
        if not _is_floating(value):
            out[key] = value
            continue
        _guard_quantizable(key, value)
        qkeys.append(key)
        dtypes[key] = str(value.dtype)
        jobs.append((key, value))

    async def _enc(key: str, value: Any) -> None:
        # Distinct keys touch distinct codec entries, and the heavy math
        # runs on the landing pool inside encode() — per-key fan-out
        # parallelizes the delta encode like quantize_transfer_async.
        blob, base = await codec.encode(key, value, version)
        if blob is None:
            aliases[key] = int(base)
        else:
            out[key] = blob

    if jobs:
        await asyncio.gather(*(_enc(k, v) for k, v in jobs))
    meta = {
        "fmt": fmt,
        "block": codec.block,
        "keys": qkeys,
        "dtypes": dtypes,
        "delta": {
            "channel": delta_ctx["channel"],
            "version": version,
            "aliases": aliases,
        },
    }
    return out, meta, aliases


class DeltaEncoder:
    """Publisher-side state of the delta wire tier: per-key dequantized f32
    baselines tracking exactly what readers reconstruct (identical
    arithmetic through :func:`_dequant_codes`, so baseline and reader state
    are bit-identical — zero drift, keyframes only bound the chain length).

    Per key and version the encoder emits one of: a KEYFRAME blob (first
    publish, restructure, or cadence), a DELTA blob carrying only changed
    blocks (per-block bitmap), or ``None`` — the key is fully unchanged
    and the publish aliases the previous version's bytes
    (unchanged-watermark protocol).

    A block is "unchanged" when its residual max|w_t − baseline| sits at
    or below the block's quantization NOISE FLOOR — half the scale step it
    had at its last keyframe, plus ``skip_eps`` absolute slack. The
    residual is always measured against the live ``w_t`` (never against a
    previous residual), so skipped error never compounds: at any version
    the served weights are within ~half a keyframe step of the true ones,
    exactly the precision a plain quantized publish has, and the next
    keyframe re-centers everything."""

    def __init__(
        self,
        fmt: str,
        block: int,
        keyframe_every: int,
        skip_eps: float = 0.0,
    ) -> None:
        if fmt not in ("int8_block", "int4_block"):
            raise ValueError(
                f"delta encoding requires a blockwise mode, not {fmt!r}"
            )
        self.fmt = fmt
        self.block = max(1, int(block))
        self.keyframe_every = max(1, int(keyframe_every))
        self.skip_eps = float(skip_eps)
        # flat key -> {"sig", "baseline" (nblocks, block) f32,
        #              "base_version" (last shipped), "keyframe_version"}
        self.entries: dict[str, dict] = {}

    def drop(self, key: Optional[str] = None) -> None:
        """Evict baseline state (tests / memory pressure): the next publish
        of the dropped key(s) re-keyframes from fresh bytes — never from a
        stale baseline."""
        if key is None:
            self.entries.clear()
        else:
            self.entries.pop(key, None)

    def _delta_math(
        self,
        key: str,
        xb: np.ndarray,
        entry: dict,
        shape: tuple,
        dtype_name: str,
        version: int,
    ):
        """The residual/quantize/blob math of one delta step — PURE with
        respect to shared state (reads the baseline, mutates nothing), so
        it runs on a landing-pool thread. Returns None for a fully
        unchanged key, else (blob, changed_mask, dequantized_delta) for
        the caller to fold into the baseline on the event loop."""
        qmax = _QMAX[self.fmt]
        resid = xb - entry["baseline"]
        amax = np.max(np.abs(resid), axis=1)
        scales_full = _block_scales(key, amax, qmax)
        changed = amax > (
            np.float32(0.5) * entry["kf_scales"] + np.float32(self.skip_eps)
        )
        nchanged = int(np.count_nonzero(changed))
        skipped = int(xb.shape[0]) - nchanged
        if nchanged == 0:
            return None
        scales = scales_full[changed]
        codes = np.clip(
            np.rint(resid[changed] / scales[:, None]), -qmax, qmax
        ).astype(np.int8)
        blob = _build_quant_blob(
            self.fmt, self.block, shape, dtype_name,
            xb.shape[0], changed, codes, scales,
            _FLAG_DELTA, version, entry["base_version"],
        )
        return blob, changed, _dequant_codes(codes, scales[:, None]), skipped

    async def encode(
        self, key: str, value: Any, version: int
    ) -> tuple[Optional[np.ndarray], Optional[int]]:
        """(blob, None) to ship, or (None, base_version) when the key is
        fully unchanged and should alias version ``base_version``'s
        bytes. The heavy math runs on the landing pool (numpy releases the
        GIL), so concurrent per-key encodes parallelize and the event loop
        stays responsive; all entry mutation happens HERE, on the loop."""
        from torchstore_tpu.transport import landing

        version = int(version)
        arr, xb = _leaf_f32_blocks(value, self.block)
        sig = (xb.shape, tuple(int(s) for s in arr.shape), str(value.dtype))
        dtype_name = str(value.dtype)
        entry = self.entries.get(key)
        if entry is not None:
            # Faultpoint: chaos schedules inject baseline loss/corruption
            # here — a raise surfaces loudly instead of any silent
            # delta-over-stale-bytes encode.
            await faults.afire("channel.delta_baseline")
            if entry["sig"] != sig:
                entry = None  # restructure: the baseline is meaningless
            elif entry["base_version"] >= version:
                raise RuntimeError(
                    f"delta baseline for {key!r} is at "
                    f"v{entry['base_version']} but v{version} is being "
                    "encoded: version numbering moved backwards — refusing "
                    "to delta over a stale baseline (drop() the key to "
                    "re-keyframe)"
                )
        if (
            entry is None
            or (version - entry["keyframe_version"]) >= self.keyframe_every
        ):
            blob, codes, scales = await landing.run_in_pool(
                _encode_keyframe_from_blocks,
                key, xb, arr.shape, dtype_name, self.fmt, self.block,
                version,
            )
            self.entries[key] = {
                "sig": sig,
                "baseline": _dequant_codes(codes, scales[:, None]),
                # The keyframe's per-block scales ARE the noise floor the
                # skip rule measures against until the next keyframe.
                "kf_scales": scales,
                "base_version": version,
                "keyframe_version": version,
            }
            _DELTA_KEYFRAMES.inc()
            _record_quant_bytes(self.fmt, arr.nbytes, blob.nbytes)
            return blob, None
        res = await landing.run_in_pool(
            self._delta_math, key, xb, entry, arr.shape, dtype_name, version
        )
        if res is None:
            _DELTA_SKIPPED.inc(int(xb.shape[0]))
            _DELTA_UNCHANGED.inc()
            _record_quant_bytes(self.fmt, arr.nbytes, 0)
            return None, entry["base_version"]
        blob, changed, dq, skipped = res
        _DELTA_SKIPPED.inc(skipped)
        # Baseline advances by the DEQUANTIZED delta (what readers apply),
        # not the raw residual — publisher and reader stay bit-identical.
        entry["baseline"][changed] += dq
        entry["base_version"] = version
        _record_quant_bytes(self.fmt, arr.nbytes, blob.nbytes)
        return blob, None


class DeltaDecoder:
    """Reader-side accumulated f32 state, one entry per flat key. Applying
    a keyframe replaces the state; applying a delta requires the state to
    be at the blob's ``base_version`` — when it is not (fresh joiner,
    lagged reader), the decoder chain-fetches base blobs back to the
    nearest keyframe via ``fetch_base``; a broken chain (base evicted/GC'd)
    raises loudly, never silently serves stale accumulations."""

    def __init__(self) -> None:
        # flat key -> {"version", "blocks", "shape", "dtype", "nelems"}
        self.state: dict[str, dict] = {}

    def drop(self, key: Optional[str] = None) -> None:
        if key is None:
            self.state.clear()
        else:
            self.state.pop(key, None)

    def serve_unchanged(self, flat_key: str, base_version: int):
        """The accumulated state entry when it already holds the aliased
        base version's content (zero re-transfer), else None — the caller
        falls back to fetching the base bytes."""
        st = self.state.get(flat_key)
        if st is None or st["version"] != int(base_version):
            return None
        _DELTA_UNCHANGED_SERVED.inc()
        return st

    async def decode(
        self, flat_key: str, blob: Any, fetch_base=None, _depth: int = 0
    ) -> dict:
        """Apply one blob (raw bytes or a pre-parsed dict); returns the
        state entry. ``fetch_base(version)`` resolves missing baselines by
        fetching that version's blob for this key."""
        info = blob if isinstance(blob, dict) else parse_quant_blob(blob)
        if info is None:
            raise ValueError(
                f"{flat_key!r}: fetched value is not a quant blob (marker "
                "and bytes disagree about quantization)"
            )
        if _depth > 1024:
            raise RuntimeError(
                f"delta chain for {flat_key!r} exceeds 1024 hops — "
                "keyframe cadence is broken"
            )
        if info["flags"] & _FLAG_DELTA:
            base = info["base_version"]
            st = self.state.get(flat_key)
            if (
                st is None
                or st["version"] != base
                or st["shape"] != info["shape"]
            ):
                held = f"v{st['version']}" if st else "no baseline"
                if fetch_base is None:
                    raise RuntimeError(
                        f"delta blob for {flat_key!r} (v{info['version']}) "
                        f"applies on v{base} but this reader holds {held} "
                        "and has no chain context to re-fetch it"
                    )
                try:
                    base_blob = await fetch_base(base)
                except KeyError as exc:
                    raise RuntimeError(
                        f"delta chain broken for {flat_key!r}: baseline "
                        f"v{base} was evicted/GC'd before this reader "
                        f"(holding {held}) accumulated it — refusing to "
                        "serve a drifted state; raise the channel's keep "
                        "or lower the keyframe cadence"
                    ) from exc
                await self.decode(
                    flat_key, base_blob, fetch_base=fetch_base,
                    _depth=_depth + 1,
                )
                st = self.state[flat_key]
                if st["version"] != base:
                    raise RuntimeError(
                        f"delta chain for {flat_key!r} resolved to "
                        f"v{st['version']}, expected v{base}"
                    )
            # Faultpoint: the chaos schedule injects here to prove a lost/
            # corrupt baseline fails loudly rather than accumulating onto
            # stale state.
            await faults.afire("channel.delta_baseline")
            st["blocks"][info["mask"]] += _dequant_codes(
                info["codes"], info["scales"][:, None]
            )
            st["version"] = info["version"]
            st["dtype"] = info["dtype"] or st["dtype"]
            return st
        if info["codes"].shape[0] == info["nblocks"]:
            # Full keyframe (the only kind the encoder emits): dequantize
            # straight into the state array — no zeros memset, no
            # boolean-mask scatter over the whole tensor.
            blocks = np.ascontiguousarray(
                _dequant_codes(info["codes"], info["scales"][:, None])
            )
        else:
            blocks = np.zeros((info["nblocks"], info["block"]), np.float32)
            if info["codes"].size:
                blocks[info["mask"]] = _dequant_codes(
                    info["codes"], info["scales"][:, None]
                )
        st = {
            "version": info["version"],
            "blocks": blocks,
            "shape": info["shape"],
            "dtype": info["dtype"],
            "nelems": info["nelems"],
        }
        self.state[flat_key] = st
        return st


def _quant_result(st: dict, user_leaf: Any, dtype_name: Optional[str] = None):
    """Materialize one decoded state entry toward the user's leaf: in place
    for numpy/torch targets, device_put (with the target's sharding) for
    jax targets, a fresh array otherwise. Always COPIES out of the decoder
    state so callers can never mutate the accumulation."""
    want = dtype_name or st["dtype"] or "float32"
    flat = st["blocks"].reshape(-1)[: st["nelems"]]
    arr = flat.reshape(st["shape"])
    if user_leaf is None:
        return arr.astype(_np_dtype(want))  # astype always copies here
    if torch_interop.is_torch_tensor(user_leaf):
        view = torch_interop.to_numpy_view(user_leaf, allow_copy=False)
        copy_into(view, arr if view.dtype == arr.dtype else arr.astype(view.dtype))
        return user_leaf
    if isinstance(user_leaf, np.ndarray):
        # Same-dtype (the common f32 target): one native copy straight out
        # of the decoder state, no intermediate astype copy.
        copy_into(
            user_leaf,
            arr if user_leaf.dtype == arr.dtype else arr.astype(user_leaf.dtype),
        )
        return user_leaf
    if (
        shd.is_jax_array(user_leaf)
        or shd.is_sharded_spec(user_leaf)
        or shd.is_plain_spec(user_leaf)
    ):
        import jax
        import jax.numpy as jnp

        host = arr.astype(np.dtype(user_leaf.dtype))
        sharding = getattr(user_leaf, "sharding", None)
        with span("h2d.dispatch", nbytes=host.nbytes, parts=1):
            if sharding is not None:
                return jax.device_put(host, sharding)
            return jnp.asarray(host)
    return arr.astype(_np_dtype(want))


def resolve_transfer_quant(
    transfer_quant: Optional[str], transfer_dtype, config
) -> Optional[str]:
    """The effective quant mode for one publish: an explicit argument wins;
    otherwise the TORCHSTORE_TPU_TRANSFER_QUANT default applies — but never
    on top of an explicit transfer_dtype (the caller chose a wire format
    already)."""
    if transfer_quant is None:
        if transfer_dtype is not None or config is None:
            return None
        transfer_quant = getattr(config, "transfer_quant", "none")
    if transfer_quant in (None, "none", ""):
        return None
    if transfer_quant not in QUANT_MODES:
        raise ValueError(
            f"unsupported transfer_quant {transfer_quant!r} (choose from "
            f"none|{'|'.join(QUANT_MODES)})"
        )
    return transfer_quant


# --------------------------------------------------------------------------
# put / get
# --------------------------------------------------------------------------


def _store_key(key: str, flat_key: str) -> str:
    return f"{key}{_SEP}{flat_key}" if flat_key else key


# --------------------------------------------------------------------------
# iteration-stable transfer plans (client.SyncPlanCache integration)
# --------------------------------------------------------------------------


def _leaf_signature(value: Any) -> tuple:
    """Hashable shape/dtype/sharding signature of one flat leaf — the unit
    the plan cache keys on. Signature equality means the leaf decomposes
    into byte-identical requests, so a cached plan replays exactly."""
    if type(value) is np.ndarray:
        # Exact-type fast path first: plain numpy leaves dominate trainer
        # state dicts, and this runs per leaf per warm iteration — the
        # jax/shard probes below cost more than the whole signature.
        # (.shape is already a tuple; .str is a C attribute.)
        return ("np", value.shape, value.dtype.str)
    sig = shd.plan_signature(value)
    if sig is not None:
        return sig
    from torchstore_tpu.client import Shard

    if isinstance(value, Shard):
        ts = value.tensor_slice
        data_sig = (
            _leaf_signature(value.data) if value.data is not None else None
        )
        return (
            "shard",
            ts.offsets,
            ts.local_shape,
            ts.global_shape,
            ts.coordinates,
            data_sig,
        )
    if torch_interop.is_torch_tensor(value):
        return ("torch", tuple(value.shape), str(value.dtype))
    if isinstance(value, np.ndarray):
        # dtype.str (C attribute), not str(dtype): this runs per leaf per
        # warm iteration, and dtype.__str__'s name derivation was ~2ms per
        # 512-leaf signature on the warm get path. Signatures are opaque
        # cache keys, only ever compared to each other.
        return ("np", tuple(value.shape), value.dtype.str)
    return ("obj",)  # opaque objects re-pickle every iteration anyway


def _flat_signature(flat: dict, *extra) -> tuple:
    return tuple((k, _leaf_signature(v)) for k, v in flat.items()) + extra


def _arena_hint_from_flat(flat: dict, config) -> Optional[dict]:
    """Precompute the small-key arena layout for a flat dict of PLAIN numpy
    leaves (the common trainer-host case). Any leaf whose request fan-out
    this function cannot see exactly (jax shards, torch views, Shards)
    returns None — the transport derives the layout itself and validates
    any hint against the real request set regardless."""
    if config is None or config.arena_max_bytes <= 0:
        return None
    from torchstore_tpu.transport import landing

    sizes: list[int] = []
    for value in flat.values():
        if isinstance(value, np.ndarray):
            if value.nbytes <= config.arena_max_bytes:
                sizes.append(int(value.nbytes))
            continue
        if _is_fetch_target(value):  # jax/torch/Shard: fan-out not 1:1 here
            return None
    if len(sizes) < 2:
        return None
    offsets, total = landing.compute_arena_layout(sizes)
    return {"sizes": tuple(sizes), "offsets": offsets, "total": total}


class _DirectSyncCache:
    """Per-client registry of direct-sync sources/dests keyed by state-dict
    key (the reference's _DirectRDMACache,
    /root/reference/torchstore/state_dict_utils.py:27-45)."""

    def __init__(self) -> None:
        self.sources: dict[str, Any] = {}
        # key -> (dest, all_handles, device_info)
        self.dests: dict[str, tuple[Any, dict, Any]] = {}

    async def close(self) -> None:
        for source in self.sources.values():
            await source.close()
        for entry in self.dests.values():
            await entry[0].close()
        self.sources.clear()
        self.dests.clear()


# Weakly keyed by the client object: a GC'd client cannot hand its cache to
# an unrelated new client via id() reuse.
# Weak client keys cannot survive a fork (children build fresh clients), so
# inherited entries are unreachable garbage at worst, never stale hits.
_direct_caches: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()  # tslint: disable=fork-safety


def _direct_cache(client) -> _DirectSyncCache:
    cache = _direct_caches.get(client)
    if cache is None:
        cache = _DirectSyncCache()
        _direct_caches[client] = cache
    return cache


async def close_direct_caches(client) -> None:
    """Release SHM segments / peer-server sockets held for this client's
    direct-sync sessions (called from shutdown paths)."""
    cache = _direct_caches.pop(client, None)
    if cache is not None:
        await cache.close()


async def _put_state_dict_direct(
    client, key: str, state_dict: Any, transfer_dtype, rank: int, num_ranks: int
) -> None:
    from torchstore_tpu.direct_weight_sync import DirectWeightSyncSource

    # torch-tensor leaves become zero-copy numpy views, so registration and
    # every later refresh read straight out of the trainer's torch storage.
    state_dict = torch_interop.convert_tree(state_dict)
    cache = _direct_cache(client)
    # Keyed by (key, rank): one client may publish as several ranks (tests /
    # colocated trainers); each rank owns its own registration + buffers.
    source = cache.sources.get((key, rank))
    if source is None:
        source = DirectWeightSyncSource(config=getattr(client, "_config", None))
        with span("direct.register", key=key, rank=rank):
            handles = await source.register(
                state_dict, rank, transfer_dtype, num_ranks=num_ranks
            )
        cache.sources[(key, rank)] = source
        published = {"handles": handles}
        if source.device_info is not None:
            # ICI rung: handles advertise the device transfer server; dests
            # pull device-to-device with zero host staging.
            published["device"] = source.device_info
        await client.put(f"{key}{_SEP}rank_{rank}", published)
        if rank == 0:
            # num_ranks is the direct-mode commit marker: written by rank 0,
            # readers fetch it first (reference :241-247).
            await client.put(f"{key}{_SEP}num_ranks", num_ranks)
    else:
        source.update_sources(state_dict)
        await source.refresh()


async def _resolve_direct_entry(client, key: str):
    """The cached (dest, all_handles, device_infos) for a direct-pushed key,
    fetching published handles and building the dest on first use (shared by
    the pull path and the prewarm preplan path)."""
    from torchstore_tpu.direct_weight_sync import DirectWeightSyncDest

    cache = _direct_cache(client)
    entry = cache.dests.get(key)
    if entry is not None:
        return entry
    try:
        num_ranks = await client.get(f"{key}{_SEP}num_ranks")
    except KeyError as exc:
        raise NoMatchingPush(
            f"no matching direct push for state dict key {key!r}"
        ) from exc
    all_handles: dict[str, list] = {}
    device_infos: list = []
    for rank in range(num_ranks):
        try:
            published = await client.get(f"{key}{_SEP}rank_{rank}")
        except KeyError as exc:
            # num_ranks (written by rank 0) can land before other ranks
            # publish their handles; keep the retry contract intact.
            raise NoMatchingPush(
                f"direct push for {key!r} incomplete: rank {rank} has not "
                "published handles yet"
            ) from exc
        for flat_key, handle_list in published["handles"].items():
            all_handles.setdefault(flat_key, []).extend(handle_list)
        if published.get("device") is not None:
            device_infos.append(published["device"])
    if device_infos and len(device_infos) != num_ranks:
        raise RuntimeError(
            f"direct push {key!r}: {len(device_infos)} of {num_ranks} "
            "ranks published device-path entries — mixed device/host "
            "publication cannot be merged (check ici_enabled agrees "
            "across ranks)"
        )
    entry = (DirectWeightSyncDest(), all_handles, device_infos or None)
    cache.dests[key] = entry
    return entry


async def preplan_direct(client, key: str, user_state_dict: Any) -> dict:
    """ts.prewarm hook for the direct acquire path: resolve the published
    handles, build + cache the transfer plan, pre-dial source connections,
    pre-attach same-host staging segments. The first real
    ``get_state_dict(direct=True)`` then starts at the data movement."""
    converted = torch_interop.convert_tree(user_state_dict, allow_copy=False)
    dest, all_handles, device_infos = await _resolve_direct_entry(client, key)
    # Reports share ts.prewarm's contract shape: "ok"/"errors" always
    # present (callers branch on them regardless of which mode ran).
    if device_infos is not None:
        # Device-path pulls have no host plan to precompute; the engine-side
        # prewarm (transfer server) is handled by the provision orchestrator.
        return {"ok": True, "errors": {}, "plan_ops": 0, "device": True}
    return {"ok": True, "errors": {}, **await dest.preplan(all_handles, converted)}


async def _get_state_dict_direct(
    client,
    key: str,
    user_state_dict: Any,
    _retry: bool = True,
    key_order: Optional[list] = None,
    on_layer=None,
) -> Any:
    from torchstore_tpu.direct_weight_sync import PullRaceError

    if user_state_dict is None:
        raise ValueError("direct get_state_dict requires user_state_dict targets")
    cache = _direct_cache(client)
    entry = await _resolve_direct_entry(client, key)
    dest, all_handles, device_infos = entry
    try:
        if device_infos is not None:
            return await dest.pull_device(device_infos, user_state_dict)
        # Ordering kwargs only when requested: plain pulls keep the
        # two-argument call shape (test stubs and subclasses rely on it).
        kwargs = {}
        if key_order is not None:
            kwargs["key_order"] = key_order
        if on_layer is not None:
            kwargs["on_layer"] = on_layer
        return await dest.pull(all_handles, user_state_dict, **kwargs)
    except (ConnectionError, OSError, KeyError, ValueError, PullRaceError):
        # ValueError covers stale-plan shape mismatches after a source
        # republish; PullRaceError covers seqlock settle timeouts / double
        # tears under hot concurrent publishes (ADVICE r3). A successful
        # retry fully overwrites any partial in-place landings.
        if not _retry:
            raise
        # The source may have restarted and re-published fresh handles under
        # the same key — invalidate the cached set and retry once.
        cache.dests.pop(key, None)
        await dest.close()
        return await _get_state_dict_direct(
            client,
            key,
            user_state_dict,
            _retry=False,
            key_order=key_order,
            on_layer=on_layer,
        )


async def put_state_dict(
    client,
    key: str,
    state_dict: Any,
    transfer_dtype=None,
    transfer_quant: Optional[str] = None,
    direct: bool = False,
    rank: int = 0,
    num_ranks: int = 1,
    delta_ctx: Optional[dict] = None,
) -> None:
    config = getattr(client, "_config", None)
    # The env default never applies to direct publishes (the direct path
    # serves live staging buffers); an EXPLICIT transfer_quant still
    # raises below.
    transfer_quant = resolve_transfer_quant(
        transfer_quant, transfer_dtype, None if direct else config
    )
    if transfer_quant is not None:
        if transfer_dtype is not None:
            raise ValueError(
                "transfer_quant and transfer_dtype are mutually exclusive "
                "(quantization defines the wire format)"
            )
        if direct:
            raise ValueError(
                "transfer_quant is a buffered-path feature (the direct path "
                "serves live staging buffers, not encoded copies)"
            )
    if delta_ctx is not None and transfer_quant not in (
        "int8_block", "int4_block"
    ):
        raise ValueError(
            "delta publishing requires transfer_quant int8_block/int4_block "
            f"(got {transfer_quant!r})"
        )
    quant_block = getattr(config, "quant_block", 256) if config else 256
    if direct:
        return await _put_state_dict_direct(
            client, key, state_dict, transfer_dtype, rank, num_ranks
        )
    tracker = LatencyTracker(f"put_state_dict[{key}]")
    flat, mapping = flatten_state_dict(state_dict)
    cache = getattr(client, "plan_cache", None)
    plan = None
    signature = None
    if cache is not None:
        # The quant mode AND block size are part of the signature: the
        # block size determines the scale-slot layout of every blob, so a
        # knob change is a restructure (epoch bump) like any other.
        signature = _flat_signature(
            flat, ("cast", str(transfer_dtype), transfer_quant, quant_block)
        )
        if cache.last_put_sig.get(key) != signature:
            # Any publish whose signature this client cannot PROVE is
            # unchanged bumps the epoch: a restructure that only DROPS
            # keys deletes nothing, so the index alone cannot see it and
            # consumers' cached get plans would serve the old structure
            # forever. Covers publisher restarts too (no memory of the
            # previous push -> one bump per key per process).
            await client.bump_placement_epoch()
        cache.last_put_sig[key] = signature
        plan = cache.lookup("put", key, signature)
    else:
        # No publisher-side signature memory at all (plan cache disabled):
        # every push could be an invisible restructure — invalidate
        # consumer plans each time. They fall back to the full (pre-PR)
        # marker-validated path; plan caching across the fleet is only
        # effective when publishers keep their caches on.
        await client.bump_placement_epoch()
    if plan is None:
        if MAPPING_KEY in flat:
            raise ValueError(
                f"{MAPPING_KEY!r} is a reserved top-level state-dict key (it "
                "is the commit marker); rename that entry"
            )
        store_keys = {k: _store_key(key, k) for k in flat}
    else:
        store_keys = plan["store_keys"]
    marker: dict = {"mapping": mapping}
    unchanged_aliases: dict[str, int] = {}
    if transfer_dtype is not None:
        flat = cast_floating_tensors(flat, transfer_dtype)
    if transfer_quant is not None:
        if delta_ctx is not None:
            flat, quant_meta, unchanged_aliases = await _delta_encode_flat(
                flat, transfer_quant, quant_block, delta_ctx
            )
        else:
            flat, quant_meta = await quantize_transfer_async(
                flat, transfer_quant, quant_block, config=config
            )
        marker["quant"] = quant_meta
    tracker.track_step("flatten")
    if plan is None:
        # Automatic provisioning hint: the first push of a big working set
        # derives a manifest from the flat dict and prewarms pools/dials
        # ahead of the data-plane puts (config.prewarm_auto; once per
        # size-signature per client; never fails the put). Cached-plan
        # iterations skip even this no-op check.
        from torchstore_tpu import provision

        await provision.maybe_auto_prewarm(client, flat)
        tracker.track_step("prewarm_hint")
        arena_hint = None
        if cache is not None:
            config = getattr(client, "_config", None)
            arena_hint = _arena_hint_from_flat(flat, config)
            if arena_hint is not None:
                # Prewarm-seeded layouts (provision handoff) take over when
                # they describe exactly these sizes.
                arena_hint = cache.seeds.get(
                    arena_hint["sizes"], arena_hint
                )
    else:
        arena_hint = plan.get("arena")
    if flat:
        # Unchanged-alias keys (delta tier) are absent from ``flat`` — an
        # all-unchanged publish ships the marker alone.
        await client.put_batch(
            {store_keys[k]: v for k, v in flat.items()},
            plan_hint={"arena": arena_hint} if arena_hint else None,
        )
    nbytes = sum(getattr(v, "nbytes", 0) for v in flat.values())
    tracker.track_step("put_batch", nbytes)
    # Commit marker LAST: its presence implies every entry above landed
    # (and carries the quantization scales, so readers always see them
    # together with a complete push).
    await client.put(_store_key(key, MAPPING_KEY), marker)
    tracker.track_step("commit_marker")
    if cache is not None and plan is None and delta_ctx is None:
        # Delta publishes are per-version keys that are never revisited —
        # storing their plans would only churn the cache. Plain quantized
        # publishes cache exactly like unquantized ones (the scales ride
        # the blobs, not the marker, so the plan stays valid).
        cache.store(
            "put",
            key,
            signature,
            {"store_keys": store_keys, "arena": arena_hint},
        )
    tracker.log_summary(level=20)  # INFO: weight-sync phases are user-facing


def direct_staging_buffers(client, key: str, rank: int = 0) -> Any:
    """After a direct push of ``key``: the registered staging buffers in the
    original state-dict structure, or None when not applicable (sharded or
    device sources). A trainer that adopts these arrays as its weight
    storage makes every later direct put a pure metadata publish — zero
    source-side copies (registered-memory semantics; the device/ICI path is
    already copy-free)."""
    cache = _direct_cache(client)
    source = cache.sources.get((key, rank))
    if source is None:
        return None
    return source.staging_state_dict()


def stream_state_dict(
    client, key: str, transfer_dtype=None, transfer_quant: Optional[str] = None
):
    """Open an incremental (layer-streamed) publish of ``key``: push
    fragments with ``await stream.put(...)`` as tensors become ready, then
    ``await stream.seal()``. See :mod:`torchstore_tpu.stream_sync`."""
    from torchstore_tpu import stream_sync

    return stream_sync.stream_state_dict(
        client, key, transfer_dtype=transfer_dtype,
        transfer_quant=transfer_quant,
    )


async def get_state_dict(
    client,
    key: str,
    user_state_dict: Any = None,
    direct: bool = False,
    strict: bool = True,
    key_order: Optional[list] = None,
    on_layer=None,
    stream: bool = False,
    delta_state: Optional["DeltaDecoder"] = None,
) -> Any:
    """Fetch a complete state dict. With ``user_state_dict``, its leaves act
    as fetch targets (sharded jax.Arrays reshard on the fly; numpy arrays are
    filled in place) and the stored mapping must match the user structure
    exactly (strict=True parity,
    /root/reference/torchstore/state_dict_utils.py:146-174).

    ``stream=True`` (or any ``key_order``/``on_layer``) acquires layer by
    layer against a streamed publish: each key is served the moment its
    version watermark lands — in ``key_order`` (model-forward) order when
    given — with ``on_layer(flat_key, value)`` invoked per served leaf, so
    forward compute starts before the last layer lands. Falls back to the
    barrier path when the key was never stream-published. On the direct
    path, ``key_order``/``on_layer`` order the one-hop pull instead."""
    if not direct and (stream or key_order is not None or on_layer is not None):
        from torchstore_tpu import stream_sync

        return await stream_sync.get_state_dict_streamed(
            client,
            key,
            user_state_dict=user_state_dict,
            key_order=key_order,
            on_layer=on_layer,
            strict=strict,
            delta_state=delta_state,
        )
    if direct:
        # The direct path naturally pulls exactly the user dict's keys
        # (handles are matched per key), i.e. subset pulls just work —
        # strict=True additionally verifies full coverage below.
        # allow_copy=False: an in-place target whose numpy view would need a
        # copy must fail loudly, not silently fill the copy.
        converted = torch_interop.convert_tree(user_state_dict, allow_copy=False)
        result = await _get_state_dict_direct(
            client, key, converted, key_order=key_order, on_layer=on_layer
        )
        if converted is not user_state_dict:
            result = torch_interop.restore_torch_results(
                user_state_dict, converted, result
            )
        if strict:
            cache = _direct_cache(client)
            entry = cache.dests.get(key)
            if entry is not None:
                user_flat, _ = flatten_state_dict(user_state_dict)
                if entry[2] is not None:
                    published_keys = set()
                    for info in entry[2]:
                        published_keys |= set(info["keys"])
                else:
                    published_keys = set(entry[1])
                missing = published_keys - set(user_flat)
                if missing:
                    raise ValueError(
                        f"state dict structure mismatch for {key!r}: missing "
                        f"in user dict: {sorted(missing)[:5]} (pass "
                        "strict=False to pull a subset)"
                    )
        return result
    tracker = LatencyTracker(f"get_state_dict[{key}]")
    cache = getattr(client, "plan_cache", None)
    user_flat = user_mapping = None
    if user_state_dict is not None:
        user_flat, user_mapping = flatten_state_dict(user_state_dict)
    signature = None
    epoch_at_build = None
    if cache is not None:
        signature = (
            _flat_signature(user_flat) if user_flat is not None else ("none",)
        )
        peeked = cache.peek("get", key, signature)
        if peeked is not None:
            # ONE epoch RPC validates the whole cached plan (instead of a
            # commit-marker fetch + per-key structure checks); a bumped
            # epoch invalidates it right here and falls through to the
            # full path. Skipped entirely when every target is covered by
            # a one-sided plan (same rule as get_batch seeding): the
            # per-entry stamps self-validate, so the warm sync iteration
            # makes ZERO RPCs.
            covers = getattr(client, "one_sided_covers_items", None)
            if covers is None or not covers(
                [
                    (sk, user_flat is not None and fetch)
                    for _, sk, fetch in peeked.get("targets", ())
                ]
            ):
                await client.placement_epoch()
            plan = cache.lookup("get", key, signature)
            if plan is not None:
                return await _get_with_plan(
                    client, key, plan, user_flat, user_mapping, tracker,
                    delta_state=delta_state,
                )
        if cache.epoch is None:
            await client.placement_epoch()  # once per consumer client
        # Capture the epoch BEFORE fetching the marker: a structural change
        # that lands mid-build must leave the stored plan already stale
        # (stamping a later-observed epoch would validate it forever).
        epoch_at_build = cache.epoch
    try:
        marker = await client.get(_store_key(key, MAPPING_KEY))
    except KeyError as exc:
        raise NoMatchingPush(
            f"no matching push for state dict key {key!r} (commit marker "
            "absent: either never pushed or push still in flight)"
        ) from exc
    mapping = marker["mapping"]
    quant = marker.get("quant")
    if quant is not None and "keys" not in quant:
        raise ValueError(
            f"push {key!r} carries a legacy quantization marker (scales on "
            "the commit marker); republish with this build's fused-blob "
            "wire tier"
        )
    tracker.track_step("mapping")

    if user_state_dict is not None:
        stored_keys = _leaf_keys(mapping)
        # Unknown keys always fail; missing keys fail only in strict mode
        # (strict=False pulls a subset, e.g. just the lm_head).
        extra = set(user_flat) - stored_keys
        if extra:
            raise ValueError(
                f"user dict keys not present in push {key!r}: {sorted(extra)[:5]}"
            )
        missing = stored_keys - set(user_flat)
        if strict and missing:
            raise ValueError(
                f"state dict structure mismatch for {key!r}: missing in "
                f"user dict: {sorted(missing)[:5]} (pass strict=False to "
                "pull a subset)"
            )
        pairs = [
            (k, _store_key(key, k), _is_fetch_target(v))
            for k, v in user_flat.items()
        ]
        flat = await _fetch_quant_aware(
            client, key, quant, pairs, user_flat, delta_state
        )
        mapping = user_mapping
    else:
        pairs = [
            (k, _store_key(key, k), False)
            for k in sorted(_leaf_keys(mapping))
        ]
        flat = await _fetch_quant_aware(
            client, key, quant, pairs, None, delta_state
        )
    nbytes = sum(getattr(v, "nbytes", 0) for v in flat.values())
    tracker.track_step("get_batch", nbytes)
    result = unflatten_state_dict(flat, mapping)
    tracker.track_step("unflatten")
    if cache is not None:
        # Quantized pushes plan-cache like everything else now: scales ride
        # the payload blobs (not the marker), so a cached plan carrying the
        # static quant meta can skip the marker fetch entirely on warm
        # iterations.
        if user_flat is not None:
            targets_spec = [
                (k, _store_key(key, k), _is_fetch_target(v))
                for k, v in user_flat.items()
            ]
        else:
            targets_spec = [
                (k, _store_key(key, k), False)
                for k in sorted(_leaf_keys(mapping))
            ]
        cache.store(
            "get",
            key,
            signature,
            {
                "targets": targets_spec,
                # The stored mapping is needed to rebuild structure only
                # when the caller passes no user dict.
                "mapping": mapping if user_flat is None else None,
                "quant": quant,
            },
            epoch=epoch_at_build,
        )
    tracker.log_summary(level=20)
    return result


async def _fetch_quant_aware(
    client,
    key: str,
    quant: Optional[dict],
    pairs: list[tuple],
    user_flat: Optional[dict],
    delta_state: Optional[DeltaDecoder],
    prefer_volume: Optional[str] = None,
) -> dict[str, Any]:
    """Fetch + decode one state dict's leaves. ``pairs`` is
    ``[(flat_key, store_key, in_place_fetch)]`` covering every leaf.
    Quantized keys fetch raw blobs (no in-place landing of encoded bytes)
    and decode toward the user's leaf; unchanged-alias keys resolve to the
    base version's store key — or to the reader's accumulated state with
    ZERO re-transfer when ``delta_state`` already holds the base
    content."""
    if quant is None:
        targets = {
            sk: (user_flat[fk] if fetch and user_flat is not None else None)
            for fk, sk, fetch in pairs
        }
        # _seed_plan=False: state-dict ops own their SyncPlanCache entries
        # (op="get"/"put") — batch-level seeding would double-book.
        fetched = await client.get_batch(
            targets, _seed_plan=False, prefer_volume=prefer_volume
        )
        return {fk: fetched[sk] for fk, sk, _ in pairs}
    qkeys = set(quant["keys"])
    delta = quant.get("delta") or {}
    aliases = delta.get("aliases") or {}
    channel = delta.get("channel")
    decoder = delta_state if delta_state is not None else DeltaDecoder()
    local: dict[str, dict] = {}
    targets: dict[str, Any] = {}
    fetch_sk: dict[str, str] = {}
    for fk, sk, fetch in pairs:
        if fk in qkeys:
            if fk in aliases:
                st = decoder.serve_unchanged(fk, aliases[fk])
                if st is not None:
                    local[fk] = st
                    continue
                sk = _store_key(_delta_version_key(channel, aliases[fk]), fk)
            targets[sk] = None
        else:
            targets[sk] = (
                user_flat[fk] if fetch and user_flat is not None else None
            )
        fetch_sk[fk] = sk
    fetched = (
        await client.get_batch(
            targets, _seed_plan=False, prefer_volume=prefer_volume
        )
        if targets
        else {}
    )
    flat: dict[str, Any] = {}
    for fk, _, fetch in pairs:
        if fk not in qkeys:
            flat[fk] = fetched[fetch_sk[fk]]
            continue
        st = local.get(fk)
        if st is None:
            st = await decoder.decode(
                fk,
                fetched[fetch_sk[fk]],
                fetch_base=_chain_fetcher(client, channel, fk),
            )
        user_leaf = user_flat.get(fk) if user_flat is not None else None
        flat[fk] = _quant_result(
            st,
            user_leaf if _is_fetch_target(user_leaf) else None,
            quant["dtypes"].get(fk),
        )
    return flat


def _chain_fetcher(client, channel: Optional[str], flat_key: str):
    """Base-blob fetcher for the delta chain walk, or None for non-delta
    markers (keyframe blobs never need a baseline)."""
    if channel is None:
        return None

    async def fetch_base(version: int):
        return await client.get(
            _store_key(_delta_version_key(channel, version), flat_key)
        )

    return fetch_base


async def _get_with_plan(
    client, key, plan, user_flat, user_mapping, tracker, delta_state=None
):
    """Plan-cache hit: the placement epoch validated the whole plan, so the
    commit-marker fetch and structure validation are skipped and the
    iteration goes straight to the data plane (locations are already warm
    in the client's location cache for the same reason). Quantized plans
    carry the static quant meta, so decode needs no marker either."""
    flat = await _fetch_quant_aware(
        client, key, plan.get("quant"), plan["targets"], user_flat,
        delta_state,
    )
    nbytes = sum(getattr(v, "nbytes", 0) for v in flat.values())
    tracker.track_step("get_batch_planned", nbytes)
    mapping = user_mapping if user_flat is not None else plan["mapping"]
    result = unflatten_state_dict(flat, mapping)
    tracker.track_step("unflatten")
    tracker.log_summary(level=20)
    return result


def _leaf_keys(mapping: dict) -> set[str]:
    out: set[str] = set()

    def rec(entry: dict) -> None:
        if entry["kind"] in ("leaf", "boxed"):
            out.add(entry["key"])
        elif entry["kind"] == "dict":
            for v in entry["items"].values():
                rec(v)
        else:
            for v in entry["items"]:
                rec(v)

    rec(mapping)
    return out


def _is_fetch_target(value: Any) -> bool:
    return (
        isinstance(value, np.ndarray)
        or torch_interop.is_torch_tensor(value)
        or shd.is_jax_array(value)
        or shd.is_sharded_spec(value)
        or shd.is_plain_spec(value)
    )
