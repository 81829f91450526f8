"""gRPC bulk-tensor path: the role the reference assigns to TRPC.

reference: ``core/distributed/communication/trpc/trpc_comm_manager.py`` —
torch RPC exists in the reference specifically to move big model tensors
between hosts; its gRPC manager caps messages at 1 GB. Here the single gRPC
backend owns that role, so this proves a model-scale payload (a 64 MB
float32 tree, bigger than any CIFAR-ResNet in the zoo) survives the wire
bit-exact through the JSON+npz frame.
"""

import threading

import numpy as np
import pytest

pytestmark = pytest.mark.slow

grpc = pytest.importorskip("grpc")

from fedml_tpu.core.distributed.grpc_backend import GRPCCommManager
from fedml_tpu.core.distributed.message import Message


class _Collector:
    def __init__(self):
        self.messages = []
        self.got = threading.Event()

    def receive_message(self, msg_type, msg):
        if msg_type == "big_model":
            self.messages.append(msg)
            self.got.set()


def _free_consecutive_ports(n: int) -> int:
    """A base such that base..base+n-1 are all bindable right now."""
    import socket

    for _ in range(50):
        with socket.socket() as probe:
            probe.bind(("127.0.0.1", 0))
            base = probe.getsockname()[1]
        if base + n >= 65536:
            continue
        socks = []
        try:
            for i in range(n):
                s = socket.socket()
                s.bind(("127.0.0.1", base + i))
                socks.append(s)
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise RuntimeError("no consecutive free ports found")


def test_64mb_model_payload_roundtrip():
    base = _free_consecutive_ports(2)
    sender = GRPCCommManager("127.0.0.1", base + 0, rank=0, world_size=2,
                             base_port=base)
    receiver = GRPCCommManager("127.0.0.1", base + 1, rank=1, world_size=2,
                               base_port=base)
    collector = _Collector()
    receiver.add_observer(collector)
    rx = threading.Thread(target=receiver.handle_receive_message, daemon=True)
    rx.start()
    try:
        rng = np.random.default_rng(0)
        arrays = [
            rng.standard_normal((2048, 4096)).astype(np.float32),
            rng.standard_normal((4096, 2048)).astype(np.float32),
            rng.standard_normal((4096,)).astype(np.float32),
        ]  # ≈ 64 MB
        msg = Message("big_model", sender_id=0, receiver_id=1)
        msg.add("num_arrays", len(arrays))
        msg.set_arrays(arrays)
        sender.send_message(msg)

        assert collector.got.wait(timeout=120), "large payload never arrived"
        got = collector.messages[0]
        assert got.get("num_arrays") == len(arrays)
        out = got.get_arrays()
        assert len(out) == len(arrays)
        for a, b in zip(arrays, out):
            assert b.dtype == a.dtype
            np.testing.assert_array_equal(b, a)
    finally:
        receiver.stop_receive_message()
        sender.stop_receive_message()
        rx.join(timeout=5)


def test_raw_frames_roundtrip_and_sniffing():
    """The TRPC-role direct-tensor format (tensor_transport.py): dtype/shape
    preservation incl. non-contiguous inputs, zero-copy decode, and
    mixed-format interop (deserialize sniffs npz vs raw)."""
    from fedml_tpu.core.distributed.tensor_transport import (
        decode_frames, encode_frames,
    )

    rng = np.random.RandomState(0)
    arrays = [
        rng.standard_normal((33, 17)).astype(np.float32),
        np.arange(11, dtype=np.int32),
        rng.standard_normal((8, 8)).astype(np.float64)[::2],  # non-contig
        np.float16(rng.standard_normal((5,))),
        np.float32(3.5).reshape(()),  # 0-d scalar: shape must survive as ()
    ]
    body = encode_frames(arrays)
    back = decode_frames(body)
    for a, b in zip(arrays, back):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.ascontiguousarray(a), b)
    assert not back[0].flags["OWNDATA"]  # zero-copy view

    for fmt in ("npz", "raw"):
        msg = Message("t", 1, 2)
        msg.set_arrays(arrays)
        msg.wire_format = fmt
        back_msg = Message.deserialize(msg.serialize())
        for a, b in zip(arrays, back_msg.get_arrays()):
            np.testing.assert_array_equal(np.ascontiguousarray(a), b)


def test_streamed_payload_past_cap_is_resource_exhausted(monkeypatch):
    """The stream handler must bound reassembly at MAX_MESSAGE_BYTES like
    the unary path does — an over-cap stream aborts RESOURCE_EXHAUSTED
    instead of growing server memory without limit."""
    from fedml_tpu.core.distributed import grpc_backend

    base = _free_consecutive_ports(4)
    recv = GRPCCommManager("127.0.0.1", base + 2, rank=2, world_size=3,
                           base_port=base, wire_format="raw",
                           stream_threshold_bytes=1 << 20)
    send = GRPCCommManager("127.0.0.1", base + 1, rank=1, world_size=3,
                           base_port=base, wire_format="raw",
                           stream_threshold_bytes=1 << 20)
    # shrink the cap AFTER server start: the handler reads the module
    # global per request, so the 12 MB payload below is now over-limit
    monkeypatch.setattr(grpc_backend, "MAX_MESSAGE_BYTES", 4 * 1024 * 1024)
    try:
        big = np.zeros(3 * 1024 * 1024, np.float32)  # 12 MB > 4 MB cap
        msg = Message("big_model", 1, 2)
        msg.set_arrays([big])
        with pytest.raises(grpc.RpcError) as ei:
            send.send_message(msg)
        assert ei.value.code() == grpc.StatusCode.RESOURCE_EXHAUSTED
    finally:
        send.stop_receive_message()
        recv.stop_receive_message()


def test_streamed_raw_payload_roundtrip():
    """A payload past the stream threshold rides Comm/SendStream in chunks
    and reassembles bit-exact (wire_format='raw')."""
    base = _free_consecutive_ports(4)
    recv = GRPCCommManager("127.0.0.1", base + 2, rank=2, world_size=3,
                           base_port=base, wire_format="raw",
                           stream_threshold_bytes=1 << 20)
    send = GRPCCommManager("127.0.0.1", base + 1, rank=1, world_size=3,
                           base_port=base, wire_format="raw",
                           stream_threshold_bytes=1 << 20)
    col = _Collector()
    recv.add_observer(col)
    t = threading.Thread(target=recv.handle_receive_message, daemon=True)
    t.start()
    try:
        rng = np.random.RandomState(1)
        big = rng.standard_normal(3 * 1024 * 1024).astype(np.float32)  # 12MB
        msg = Message("big_model", 1, 2)
        msg.set_arrays([big])
        send.send_message(msg)
        assert col.got.wait(timeout=60)
        np.testing.assert_array_equal(col.messages[0].get_arrays()[0], big)
    finally:
        send.stop_receive_message()
        recv.stop_receive_message()
