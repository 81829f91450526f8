"""Direct-tensor wire format — the TRPC-role transport (r4 VERDICT #10).

reference: ``core/distributed/communication/trpc/trpc_comm_manager.py:25-176``
— PyTorch TensorPipe RPC with ``set_device_map`` so tensors move
device-to-device without host serialization. A TPU pod has no CUDA-direct
DCN path (cross-host device transfer is the XLA collectives' job over
ICI/DCN meshes), so the role this module covers is the part that remains on
the FL message plane: moving LARGE host tensors between processes with as
few copies and codec passes as possible.

The default ``Message`` body is an npz (a zip container): every array is
deflate-scanned and copied through the zip writer, and ``np.load`` copies
again on read. The RAW frame format here writes one JSON header
(dtype/shape per tensor) plus the tensors' raw bytes, and decodes to
ZERO-COPY numpy views over the received buffer — the receive path does no
per-element work at all. ``fedml_tpu.Comm/SendStream`` (grpc_backend)
streams these bodies in bounded chunks so a GB-scale weight blob never
needs a single contiguous gRPC message buffer — the pinned-host-staging
analog.
"""

from __future__ import annotations

import json
import math
from typing import List, Sequence, Union

import numpy as np

RAW_MAGIC = b"FTT1"


def encode_frame_parts(
        arrays: Sequence[np.ndarray]) -> List[Union[bytes, memoryview]]:
    """[arrays] → the body PIECES [RAW_MAGIC, u32 header_len, JSON header,
    frame, frame, ...] — callers join them together with their own prefix
    so the whole wire payload is assembled in ONE pass (Message.serialize
    does exactly that; a naive encode-then-concat would copy a GB-scale
    blob twice).

    Already-C-contiguous arrays ride as MEMORYVIEWS over their own buffers
    — zero data copies on encode; the single pass that touches bytes is the
    caller's join/socket write. Only non-contiguous inputs pay a
    materializing ``ascontiguousarray``.

    No alignment padding: the body rides behind a variable-length message
    prefix anyway, so in-body alignment cannot survive to the receive
    buffer — numpy accepts unaligned views (ALIGNED=False)."""
    metas = []
    frames: List[Union[bytes, memoryview]] = []
    off = 0
    for a in arrays:
        a = np.asarray(a)
        # record the TRUE shape before ascontiguousarray, which promotes
        # 0-d scalars to (1,) — the npz path preserves () and so must we
        shape = list(a.shape)
        if not a.flags.c_contiguous:
            a = np.ascontiguousarray(a)
        metas.append({"dtype": a.dtype.str, "shape": shape, "off": off})
        # flat byte view, zero-copy (read-only arrays export read-only
        # views; join/write only ever reads)
        frames.append(memoryview(a).cast("B") if a.nbytes else b"")
        off += a.nbytes
    header = json.dumps(metas).encode("utf-8")
    return [RAW_MAGIC, len(header).to_bytes(4, "big"), header, *frames]


def encode_frames(arrays: Sequence[np.ndarray]) -> bytes:
    """Standalone body: the joined :func:`encode_frame_parts`."""
    return b"".join(encode_frame_parts(arrays))


def decode_frames(buf: Union[bytes, memoryview]) -> List[np.ndarray]:
    """RAW body → list of ZERO-COPY numpy views over ``buf``.

    The views are read-only (the buffer is immutable bytes); consumers that
    mutate must copy — FL aggregation stacks/averages into fresh arrays
    anyway, so the hot path never pays a receive-side copy."""
    view = memoryview(buf)
    if view[:4] != RAW_MAGIC:
        raise ValueError("not a raw tensor-frame body")
    if len(view) < 8:
        raise ValueError("truncated tensor frame (no header length)")
    hlen = int.from_bytes(view[4:8], "big")
    if 8 + hlen > len(view):
        raise ValueError(
            f"truncated tensor frame (header wants {hlen} bytes, body has "
            f"{len(view) - 8})"
        )
    try:
        metas = json.loads(bytes(view[8:8 + hlen]).decode("utf-8"))
    except ValueError as e:  # bit-flipped header bytes: clean error, not
        raise ValueError(    # a raw JSONDecodeError/UnicodeDecodeError
            f"corrupt tensor frame header: {e}"
        ) from None
    if not isinstance(metas, list):
        raise ValueError("corrupt tensor frame header: not a tensor list")
    base = 8 + hlen
    out = []
    for i, m in enumerate(metas):
        # validate the header's dtype/shape/off BEFORE touching the buffer:
        # a truncated or corrupt frame must surface as a clean error (the
        # receive loop counts + drops it), not a confusing np.frombuffer /
        # reshape failure mid-decode
        try:
            dt = np.dtype(m["dtype"])
            shape = [int(s) for s in m["shape"]]
            off = int(m["off"])
        except (KeyError, TypeError, ValueError) as e:
            raise ValueError(
                f"corrupt tensor frame header (tensor {i}): {e}"
            ) from None
        if off < 0 or any(s < 0 for s in shape):
            raise ValueError(
                f"corrupt tensor frame header (tensor {i}: negative "
                "offset/shape)"
            )
        # arbitrary-precision Python ints: np.prod would wrap in int64 and
        # an adversarial shape like [2**40, 2**40] could slip past the
        # bounds check below with a garbage (even negative) byte count
        n = math.prod(shape) if shape else 1
        start = base + off
        end = start + n * dt.itemsize
        if end > len(view):
            raise ValueError(
                f"truncated tensor frame (tensor {i}: needs bytes "
                f"[{start}, {end}) of a {len(view)}-byte body)"
            )
        frame = view[start:end]
        out.append(np.frombuffer(frame, dtype=dt).reshape(shape))
    return out


def is_raw_body(body: Union[bytes, memoryview]) -> bool:
    return bytes(body[:4]) == RAW_MAGIC


def iter_chunks(payload: Union[bytes, memoryview],
                chunk_bytes: int = 4 * 1024 * 1024):
    """Bounded-size chunks for the streaming RPC (no monolithic buffer)."""
    view = memoryview(payload)
    for i in range(0, len(view), chunk_bytes):
        yield bytes(view[i:i + chunk_bytes])
