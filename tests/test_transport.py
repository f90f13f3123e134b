"""Frame-codec property/fuzz suite for the worker socket transport
(runtime/transport.py): malformed input — truncated, oversized,
corrupt-pickle, wrong-version, random garbage — must raise TYPED transport
errors or drop the connection, never hang a reader and never crash the
router; well-formed frames round-trip exactly with their incarnation
epoch. Also pins the auth/version handshake and the network fault shapes
(drop / half-open partition) the chaos drills arm."""

import pickle
import random
import socket
import struct
import threading
import time

import pytest

from sentio_tpu.infra import faults
from sentio_tpu.runtime.replica import WorkerRegistry
from sentio_tpu.runtime.transport import (
    _HEADER,
    _MAGIC,
    PROTOCOL_VERSION,
    FrameProtocolError,
    FrameTooLarge,
    PipeTransport,
    SocketTransport,
    TransportClosed,
    dial,
    send_hello,
)


def _pair(**kw):
    """Connected (transport, raw peer socket) over a local socketpair."""
    a, b = socket.socketpair()
    return SocketTransport(a, **kw), b


def _tpair(**kw):
    """Two transports over a local socketpair."""
    a, b = socket.socketpair()
    return SocketTransport(a, **kw), SocketTransport(b, **kw)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


class TestFrameCodec:
    def test_roundtrip_property(self):
        """Well-formed frames of assorted shapes/sizes round-trip exactly,
        carrying the sender's epoch."""
        tx, rx = _tpair()
        tx.epoch = 7
        rng = random.Random(0)
        payloads = [
            (0, "ok", None),
            (1, "tok", ("piece", [1, 2, 3])),
            (2, "status", {"backlog": 0, "nested": {"x": [None, 1.5]}}),
            (3, "blob", bytes(rng.randrange(256) for _ in range(70_000))),
            (4, "text", "ü" * 5000),
        ]
        for frame in payloads:  # interleaved: a socketpair buffer is small
            tx.send(frame)
            got, epoch = rx.recv(timeout_s=5)
            assert got == frame
            assert epoch == 7
        tx.close(), rx.close()

    def test_recv_timeout_returns_none_not_hang(self):
        tx, rx = _tpair()
        t0 = time.perf_counter()
        assert rx.recv(timeout_s=0.3) is None
        assert time.perf_counter() - t0 < 2.0
        tx.close(), rx.close()

    def test_truncated_header_never_hangs_the_reader(self):
        """A partial frame header followed by silence must raise typed
        within the frame timeout — not block forever."""
        t, peer = _pair(frame_timeout_s=0.5)
        peer.sendall(b"SN")  # 2 of 13 header bytes, then silence
        t0 = time.perf_counter()
        with pytest.raises(TransportClosed):
            t.recv(timeout_s=5)
        assert time.perf_counter() - t0 < 5.0
        t.close(), peer.close()

    def test_truncated_payload_never_hangs_the_reader(self):
        t, peer = _pair(frame_timeout_s=0.5)
        payload = pickle.dumps((1, "ok", "x" * 100))
        header = _HEADER.pack(_MAGIC, PROTOCOL_VERSION, 0, len(payload))
        peer.sendall(header + payload[: len(payload) // 2])  # then silence
        with pytest.raises(TransportClosed):
            t.recv(timeout_s=5)
        t.close(), peer.close()

    def test_oversized_frame_typed_on_both_sides(self):
        t, peer = _pair(max_frame_bytes=1024)
        # sender refuses before any byte hits the wire
        with pytest.raises(FrameTooLarge):
            t.send((1, "blob", b"x" * 4096))
        # receiver refuses before buffering the payload
        header = _HEADER.pack(_MAGIC, PROTOCOL_VERSION, 0, 1 << 20)
        peer.sendall(header)
        with pytest.raises(FrameTooLarge):
            t.recv(timeout_s=5)
        t.close(), peer.close()

    def test_corrupt_pickle_is_typed_not_a_crash(self):
        t, peer = _pair()
        junk = b"\x80\x05garbage-not-a-pickle"
        header = _HEADER.pack(_MAGIC, PROTOCOL_VERSION, 0, len(junk))
        peer.sendall(header + junk)
        with pytest.raises(FrameProtocolError):
            t.recv(timeout_s=5)
        t.close(), peer.close()

    def test_wrong_magic_and_wrong_version_are_typed(self):
        for magic, version in ((b"HTTP", PROTOCOL_VERSION),
                               (_MAGIC, PROTOCOL_VERSION + 9)):
            t, peer = _pair()
            payload = pickle.dumps((0, "ok", None))
            peer.sendall(struct.pack("!4sBII", magic, version, 0,
                                     len(payload)) + payload)
            with pytest.raises(FrameProtocolError):
                t.recv(timeout_s=5)
            t.close(), peer.close()

    def test_random_garbage_fuzz_always_typed_never_hung(self):
        """Random byte soup: every outcome is a typed transport error (or
        a clean idle timeout), bounded in time — the reader thread can
        never be wedged and the process never sees an untyped crash."""
        rng = random.Random(1234)
        for trial in range(12):
            t, peer = _pair(frame_timeout_s=0.3, max_frame_bytes=1 << 16)
            blob = bytes(rng.randrange(256)
                         for _ in range(rng.randrange(1, 200)))
            peer.sendall(blob)
            peer.close()  # EOF after the garbage
            t0 = time.perf_counter()
            try:
                while True:
                    if t.recv(timeout_s=1.0) is None:
                        break
            except (TransportClosed, FrameProtocolError, FrameTooLarge):
                pass  # typed: exactly the contract
            assert time.perf_counter() - t0 < 10.0, f"trial {trial} hung"
            t.close()

    def test_peer_close_is_transport_closed(self):
        t, peer = _pair()
        peer.close()
        with pytest.raises(TransportClosed):
            t.recv(timeout_s=5)
        t.close()

    def test_broken_write_bounded_by_frame_timeout(self):
        """A peer that stops READING (half-open partition, send
        direction): once the kernel buffer fills, send() must raise typed
        within the frame timeout instead of blocking forever — the
        broken-write liveness signal."""
        a, b = socket.socketpair()
        # tiny buffers so the fill happens fast
        a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
        b.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
        t = SocketTransport(a, frame_timeout_s=0.5)
        big = (1, "blob", b"x" * 65_536)
        t0 = time.perf_counter()
        with pytest.raises(TransportClosed):
            for _ in range(64):  # nobody reads b: must fail, bounded
                t.send(big)
        assert time.perf_counter() - t0 < 30.0
        t.close(), b.close()

    def test_pipe_transport_parity(self):
        """PipeTransport speaks the same (frame, epoch) surface."""
        import multiprocessing

        c1, c2 = multiprocessing.Pipe()
        tx, rx = PipeTransport(c1, epoch=3), PipeTransport(c2, epoch=3)
        assert rx.recv(timeout_s=0.1) is None
        tx.send((5, "ok", {"a": 1}))
        assert rx.recv(timeout_s=5) == ((5, "ok", {"a": 1}), 3)
        tx.close()
        with pytest.raises(TransportClosed):
            rx.recv(timeout_s=5)
        rx.close()


class TestNetworkFaults:
    def test_drop_next_n_frames(self):
        """faults drop=True, times=N at the recv point loses exactly the
        next N frames — the 'lossy link' chaos shape."""
        tx, rx = _tpair()
        rx.fault_scope = "r0"
        with faults.inject("transport.recv.r0", drop=True, times=2) as rule:
            for i in range(4):
                tx.send((i, "tok", i))
            got = [rx.recv(timeout_s=5)[0][0] for _ in range(2)]
            assert got == [2, 3]  # frames 0 and 1 were dropped
            assert rule.fired == 2
        tx.close(), rx.close()

    def test_send_side_drop(self):
        tx, rx = _tpair()
        tx.fault_scope = "w"
        with faults.inject("transport.send.w", drop=True, times=1):
            tx.send((0, "tok", "lost"))
            tx.send((1, "tok", "kept"))
        assert rx.recv(timeout_s=5)[0] == (1, "tok", "kept")
        tx.close(), rx.close()

    def test_half_open_partition_reads_stall_writes_succeed(self):
        """The partition shape the chaos drill arms: a stall at the recv
        point wedges the reader while the same transport's sends keep
        landing on the peer."""
        a, b = _tpair()
        a.fault_scope = "r1"
        release = threading.Event()
        got = {}

        def reader():
            got["frame"] = a.recv(timeout_s=30)

        with faults.inject("transport.recv.r1", stall_event=release,
                           stall_s=30.0, times=1):
            b.send((1, "tok", "wedged"))
            th = threading.Thread(target=reader, daemon=True)
            th.start()
            time.sleep(0.3)
            assert th.is_alive(), "reader should be stalled (partitioned)"
            # writes from the partitioned side still succeed — half-open
            a.send((2, "ok", "write side alive"))
            assert b.recv(timeout_s=5)[0] == (2, "ok", "write side alive")
            release.set()
            th.join(timeout=5)
            assert not th.is_alive()
            assert got["frame"][0] == (1, "tok", "wedged")
        a.close(), b.close()


class TestHandshake:
    def test_registration_grants_monotonic_epochs(self):
        reg = WorkerRegistry("secret", slots=2)
        try:
            t1 = dial(reg.address)
            ack1 = send_hello(t1, "secret", 1, 42)
            rt1, hello, e1 = reg.await_registration(1, 5.0)
            assert ack1["epoch"] == e1 == 1 and hello["pid"] == 42
            t2 = dial(reg.address)
            ack2 = send_hello(t2, "secret", 1, 43)
            rt2, _h, e2 = reg.await_registration(1, 5.0)
            assert ack2["epoch"] == e2 == 2
            assert reg.current_epoch(1) == 2
            # the superseded connection's frames are fenced by epoch
            assert rt2.epoch == 2 and rt1.epoch == 1
            for t in (t1, t2, rt1, rt2):
                t.close()
        finally:
            reg.close()

    def test_bad_token_and_bad_version_rejected(self):
        reg = WorkerRegistry("secret", slots=1)
        try:
            t = dial(reg.address)
            with pytest.raises(FrameProtocolError, match="token"):
                send_hello(t, "WRONG", 0, 1)
            t.close()
            t2 = dial(reg.address)
            t2.send((0, "hello", {"token": "secret", "slot": 0,
                                  "proto": PROTOCOL_VERSION + 1, "pid": 1}))
            got = t2.recv(timeout_s=5)
            assert got is not None and got[0][1] == "hello_reject"
            assert "protocol" in got[0][2]["reason"]
            t2.close()
            # the registry books a rejection AFTER the reject frame went
            # out: the dialer can hold the frame before the count moves
            deadline = time.monotonic() + 5
            while (reg.stats()["rejections"] < 2
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            stats = reg.stats()
            assert stats["rejections"] == 2
            assert stats["registrations"] == 0
        finally:
            reg.close()

    def test_hostile_hello_payloads_never_crash_the_acceptor(self):
        """Review regression: a hello whose token is non-ASCII (raises
        TypeError from hmac.compare_digest on str input) or whose proto
        is a non-numeric value must be a clean typed rejection, not an
        untyped crash that kills the accept loop and leaks the socket."""
        reg = WorkerRegistry("secret", slots=1)
        try:
            for payload in (
                {"token": "sécrét-ünicode", "slot": 0,
                 "proto": PROTOCOL_VERSION, "pid": 1},
                {"token": "secret", "slot": 0, "proto": "banana", "pid": 1},
                {"token": None, "slot": 0, "proto": PROTOCOL_VERSION,
                 "pid": 1},
            ):
                t = dial(reg.address)
                t.send((0, "hello", payload))
                got = t.recv(timeout_s=5)
                assert got is not None and got[0][1] == "hello_reject", got
                t.close()
            # the registry is still serving: a good hello registers fine
            t = dial(reg.address)
            send_hello(t, "secret", 0, 7)
            rt, _h, epoch = reg.await_registration(0, 5.0)
            assert epoch == 1
            t.close(), rt.close()
        finally:
            reg.close()

    def test_supersede_keeps_highest_epoch(self):
        """Review regression: racing registrations supersede by EPOCH,
        not arrival order — the live (highest-epoch) connection must
        survive the drain no matter which handshake thread ran last."""
        reg = WorkerRegistry("secret", slots=1)
        try:
            t1 = dial(reg.address)
            send_hello(t1, "secret", 0, 1)
            t2 = dial(reg.address)
            send_hello(t2, "secret", 0, 2)
            # both queued (no claim between them): the claimant must get
            # the HIGHEST epoch and the stale one must be closed
            deadline = time.perf_counter() + 5
            while reg.current_epoch(0) < 2 and time.perf_counter() < deadline:
                time.sleep(0.02)
            rt, hello, epoch = reg.await_registration(0, 5.0)
            assert epoch == 2 and hello["pid"] == 2
            t1.close(), t2.close(), rt.close()
        finally:
            reg.close()

    def test_unknown_slot_rejected(self):
        reg = WorkerRegistry("secret", slots=1)
        try:
            t = dial(reg.address)
            with pytest.raises(FrameProtocolError, match="slot"):
                send_hello(t, "secret", 5, 1)
            t.close()
        finally:
            reg.close()

    def test_await_registration_timeout_is_typed(self):
        from sentio_tpu.infra.exceptions import ReplicaUnavailable

        reg = WorkerRegistry("secret", slots=1)
        try:
            with pytest.raises(ReplicaUnavailable):
                reg.await_registration(0, timeout_s=0.3)
        finally:
            reg.close()

    def test_stale_frame_counting(self):
        reg = WorkerRegistry("secret", slots=1)
        try:
            assert reg.stale_frames(0) == 0
            reg.note_stale_frame(0)
            reg.note_stale_frame(0)
            assert reg.stale_frames(0) == 2
            assert reg.stats()["stale_frames"] == [2]
        finally:
            reg.close()
