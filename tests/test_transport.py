"""Shared-memory span transport tests: native ring roundtrip, wraparound,
drop accounting, SCM_RIGHTS FD handoff across processes, receiver into a
pipeline, and producer-restart reader swap."""

import multiprocessing
import os

import numpy as np
import pytest

from odigos_tpu.pdata import synthesize_traces
from odigos_tpu.pdata.spans import concat_batches
from odigos_tpu.transport import (
    RingHandoffServer,
    ShmSpanReceiver,
    SpanRing,
    receive_rings,
)


def assert_batches_equal(a, b):
    assert len(a) == len(b)
    for col in ("trace_id_hi", "trace_id_lo", "span_id", "parent_span_id",
                "start_unix_nano", "end_unix_nano", "kind", "status_code"):
        assert (a.col(col) == b.col(col)).all(), col
    assert a.service_names() == b.service_names()
    assert a.span_names() == b.span_names()


class TestSpanRing:
    def test_roundtrip_exact(self):
        batch = synthesize_traces(100, seed=3)
        ring = SpanRing.create(1 << 20)
        assert ring.write_batch(batch) == len(batch)
        out = ring.drain()
        assert_batches_equal(out, batch)
        assert ring.drain() is None
        ring.close()

    def test_wraparound_many_cycles(self):
        ring = SpanRing.create(1 << 14)  # small: forces edge wraps
        wrote = drained = 0
        for i in range(100):
            b = synthesize_traces(8, seed=i)
            wrote += ring.write_batch(b)
            out = ring.drain()
            drained += 0 if out is None else len(out)
        assert wrote == drained and ring.dropped == 0
        ring.close()

    def test_full_ring_drops_and_counts(self):
        ring = SpanRing.create(1 << 12)
        big = synthesize_traces(200, seed=0)
        written = ring.write_batch(big)
        assert 0 < written < len(big)
        assert ring.dropped == len(big) - written
        out = ring.drain()
        assert len(out) == written
        # after drain there is room again
        assert ring.write_batch(synthesize_traces(2, seed=1)) > 0
        ring.close()

    def test_attach_sees_producer_writes(self):
        ring = SpanRing.create(1 << 18)
        fd2 = os.dup(ring.fd)
        consumer = SpanRing.attach(fd2)
        batch = synthesize_traces(20, seed=7)
        ring.write_batch(batch)
        out = consumer.drain()
        assert_batches_equal(out, batch)
        consumer.close()
        ring.close()

    def test_attach_rejects_garbage(self):
        fd = os.memfd_create("garbage")
        os.ftruncate(fd, 4096)
        with pytest.raises(ValueError):
            SpanRing.attach(fd)
        os.close(fd)

    def test_oversized_string_truncated_not_corrupted(self):
        from odigos_tpu.pdata.spans import SpanBatchBuilder, SpanKind
        b = SpanBatchBuilder()
        res = b.add_resource({"service.name": "svc"})
        huge = "n" * 70_000
        b.add_span(trace_id=(1 << 64) | 2, span_id=3, name=huge,
                   service="svc", kind=SpanKind.SERVER,
                   start_unix_nano=10, end_unix_nano=20,
                   resource_index=res)
        batch = b.build()
        ring = SpanRing.create(1 << 20)
        assert ring.write_batch(batch) == 1
        out = ring.drain()
        assert out.span_names() == [huge[:65535]]  # clamped, not mod-65536
        ring.close()

    def test_drain_caps_records(self):
        ring = SpanRing.create(1 << 20)
        batch = synthesize_traces(50, seed=2)
        ring.write_batch(batch)
        first = ring.drain(max_records=10)
        assert len(first) == 10
        rest = ring.drain()
        assert len(rest) == len(batch) - 10
        merged = concat_batches([first, rest])
        assert_batches_equal(merged, batch)
        ring.close()


def _producer_main(sock_path: str, n_traces: int, seed: int):
    rings = receive_rings(sock_path)
    ring = SpanRing.attach(rings["agent-0"])
    ring.write_batch(synthesize_traces(n_traces, seed=seed))
    ring.close()


class TestFdHandoff:
    def test_handoff_many_rings_chunked(self, tmp_path):
        """More rings than one SCM_RIGHTS message can carry (>CHUNK)."""
        sock = str(tmp_path / "handoff.sock")
        server = RingHandoffServer(sock)
        rings = [SpanRing.create(1 << 14, name=f"r{i}") for i in range(70)]
        for i, r in enumerate(rings):
            server.register_ring(f"agent-{i:03d}", r.fd)
        server.start()
        try:
            fds = receive_rings(sock)
            assert len(fds) == 70
            assert sorted(fds) == [f"agent-{i:03d}" for i in range(70)]
            for fd in fds.values():
                os.close(fd)
        finally:
            server.stop()
            for r in rings:
                r.close()

    def test_handoff_same_process(self, tmp_path):
        sock = str(tmp_path / "handoff.sock")
        server = RingHandoffServer(sock)
        ring = SpanRing.create(1 << 18)
        server.register_ring("agent-0", ring.fd)
        server.start()
        try:
            fds = receive_rings(sock)
            assert list(fds) == ["agent-0"]
            consumer = SpanRing.attach(fds["agent-0"])
            batch = synthesize_traces(10, seed=1)
            ring.write_batch(batch)
            assert_batches_equal(consumer.drain(), batch)
            consumer.close()
        finally:
            server.stop()
            ring.close()

    def test_handoff_cross_process(self, tmp_path):
        """Spans written by a child process arrive intact in the parent —
        the actual agent→collector topology."""
        sock = str(tmp_path / "handoff.sock")
        server = RingHandoffServer(sock)
        ring = SpanRing.create(1 << 20)
        server.register_ring("agent-0", ring.fd)
        server.start()
        try:
            # spawn, not fork: the test process is multi-threaded (jax etc.)
            ctx = multiprocessing.get_context("spawn")
            proc = ctx.Process(target=_producer_main, args=(sock, 30, 11))
            proc.start()
            proc.join(timeout=30)
            assert proc.exitcode == 0
            out = ring.drain()
            assert_batches_equal(out, synthesize_traces(30, seed=11))
        finally:
            server.stop()
            ring.close()


class _Sink:
    def __init__(self):
        self.batches = []

    def consume(self, batch):
        self.batches.append(batch)


class TestShmSpanReceiver:
    def test_drains_into_pipeline(self, tmp_path):
        sock = str(tmp_path / "handoff.sock")
        server = RingHandoffServer(sock)
        ring = SpanRing.create(1 << 18)
        server.register_ring("agent-0", ring.fd)
        server.start()
        recv = ShmSpanReceiver("shmspan", {"socket_path": sock,
                                           "interval_s": 0.001})
        sink = _Sink()
        recv.set_consumer(sink)
        try:
            batch = synthesize_traces(15, seed=4)
            ring.write_batch(batch)
            recv.start()
            import time
            deadline = time.time() + 10
            while not sink.batches and time.time() < deadline:
                time.sleep(0.01)
            assert sink.batches
            assert_batches_equal(sink.batches[0], batch)
        finally:
            recv.shutdown()
            server.stop()
            ring.close()

    def test_reader_swap_on_producer_restart(self):
        """attach_ring under the same name swaps readers without losing the
        new producer's spans (odigosebpfreceiver.go:74-93 behavior)."""
        recv = ShmSpanReceiver("shmspan", {})
        sink = _Sink()
        recv.set_consumer(sink)
        ring1 = SpanRing.create(1 << 18)
        recv.attach_ring("agent-0", SpanRing.attach(os.dup(ring1.fd)))
        ring1.write_batch(synthesize_traces(5, seed=0))
        assert recv.drain_once() > 0
        # producer restarts: new ring under the same name
        ring2 = SpanRing.create(1 << 18)
        recv.attach_ring("agent-0", SpanRing.attach(os.dup(ring2.fd)))
        batch2 = synthesize_traces(7, seed=9)
        ring2.write_batch(batch2)
        assert recv.drain_once() == len(batch2)
        assert_batches_equal(sink.batches[-1], batch2)
        ring1.close()
        ring2.close()
        for r in recv._rings.values():
            r.close()

    def test_refresh_swaps_restarted_producer_ring(self, tmp_path):
        sock = str(tmp_path / "handoff.sock")
        server = RingHandoffServer(sock)
        ring1 = SpanRing.create(1 << 18)
        server.register_ring("agent-0", ring1.fd)
        server.start()
        recv = ShmSpanReceiver("shmspan", {"socket_path": sock})
        sink = _Sink()
        recv.set_consumer(sink)
        try:
            recv.refresh_rings()
            ring1.write_batch(synthesize_traces(3, seed=0))
            assert recv.drain_once() > 0
            # producer restarts: new memfd under the same name
            ring2 = SpanRing.create(1 << 18)
            server.register_ring("agent-0", ring2.fd)
            assert recv.refresh_rings() == 1
            # identical identity → no swap on a second refresh
            assert recv.refresh_rings() == 0
            batch = synthesize_traces(4, seed=5)
            ring2.write_batch(batch)
            assert recv.drain_once() == len(batch)
            ring2.close()
        finally:
            server.stop()
            ring1.close()
            for r in recv._rings.values():
                r.close()

    def test_factory_registered(self):
        from odigos_tpu.components.api import ComponentKind, registry
        import odigos_tpu.transport  # noqa: F401  (registration side effect)
        factory = registry.get(ComponentKind.RECEIVER, "shmspan")
        assert factory.type_name == "shmspan"


class TestRefreshDetach:
    def test_refresh_detaches_absent_rings(self, tmp_path):
        """A handoff that no longer names a ring means its producer exited:
        the receiver must drop (and close) the stale ring rather than drain
        it forever (reference reader-swap inventory semantics,
        odigosebpfreceiver.go:74-93)."""
        sock = str(tmp_path / "handoff.sock")
        server = RingHandoffServer(sock)
        ring1 = SpanRing.create(1 << 18)
        ring2 = SpanRing.create(1 << 18)
        server.register_ring("agent-0", ring1.fd)
        server.register_ring("agent-1", ring2.fd)
        server.start()
        recv = ShmSpanReceiver("shmspan", {"socket_path": sock})
        recv.set_consumer(_Sink())
        try:
            assert recv.refresh_rings() == 2
            assert set(recv._rings) == {"agent-0", "agent-1"}
            server.unregister_ring("agent-1")
            recv.refresh_rings()
            assert set(recv._rings) == {"agent-0"}
            # drained data from the surviving ring still flows
            ring1.write_batch(synthesize_traces(3, seed=1))
            assert recv.drain_once() > 0
        finally:
            server.stop()
            ring1.close()
            ring2.close()
            for r in recv._rings.values():
                r.close()


class TestNativeLoaderKey:
    """The built library is keyed on what produced it (ISSUE 21): a
    build directory copied from another revision or another machine
    holds a library under ANOTHER key, which must be rebuilt over, never
    loaded — file times say nothing in a copied tree."""

    def test_key_follows_source_flags_and_is_stable(self, monkeypatch,
                                                    tmp_path):
        from odigos_tpu import native

        here = native._so_path()
        assert here == native._so_path()
        assert os.path.dirname(here) == native._BUILD_DIR
        edited = tmp_path / "spanring.cpp"
        with open(native._SRC, "rb") as f:
            edited.write_bytes(f.read() + b"\n// edited\n")
        monkeypatch.setattr(native, "_SRC", str(edited))
        assert native._so_path() != here
        monkeypatch.undo()
        monkeypatch.setattr(native, "_FLAGS", native._FLAGS + ("-g",))
        assert native._so_path() != here

    def test_library_under_another_key_is_rebuilt_not_loaded(
            self, monkeypatch, tmp_path):
        from odigos_tpu import native

        build = tmp_path / "build"
        build.mkdir()
        # what a copied tree carries: a NEWER file, under the name the
        # mtime loader used and under some other revision's key — both
        # garbage, so loading either would raise
        for name in ("libspanring.so", "libspanring-0123456789abcdef.so"):
            (build / name).write_bytes(b"not a shared object")
        monkeypatch.setattr(native, "_BUILD_DIR", str(build))
        monkeypatch.setattr(native, "_lib", None)
        lib = native.lib()
        assert lib.sr_map_len(1024) > 0  # a real, freshly built library
        assert os.listdir(build) == [os.path.basename(native._so_path())]
