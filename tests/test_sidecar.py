"""Out-of-process scoring sidecar (serving/sidecar.py): unix-socket Score()
protocol, the engine's "remote" backend, and the collector↔sidecar process
boundary with pass-through-on-failure intact (round-1 review item 3; reference
discipline: common/unixfd/server.go:26).
"""

import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from odigos_tpu.components.processors.tpuanomaly import FLAG_ATTR
from odigos_tpu.pdata import synthesize_traces
from odigos_tpu.pipeline import Collector
from odigos_tpu.serving import (
    EngineConfig, ScoringEngine, SidecarClient, SidecarServer)
from odigos_tpu.utils.telemetry import meter


@pytest.fixture
def server(tmp_path):
    sock = str(tmp_path / "score.sock")
    eng = ScoringEngine(EngineConfig(model="mock"))
    srv = SidecarServer(eng, sock, score_timeout_s=10.0).start()
    yield sock, srv
    srv.shutdown()


# ------------------------------------------------------- protocol round trip
def test_client_scores_via_server(server):
    sock, _ = server
    client = SidecarClient(sock)
    client.ping()
    batch = synthesize_traces(10, seed=1)
    scores = client.score(batch)
    assert scores.shape == (len(batch),) and scores.dtype == np.float32
    # identical to scoring locally with the same mock backend
    from odigos_tpu.features import featurize
    from odigos_tpu.serving.engine import MockBackend

    local = MockBackend(EngineConfig(model="mock")).score(
        batch, featurize(batch))
    np.testing.assert_allclose(scores, local, rtol=1e-6)
    client.close()


def test_concurrent_requests_one_connection(server):
    sock, _ = server
    client = SidecarClient(sock)
    import threading

    batches = [synthesize_traces(5, seed=s) for s in range(6)]
    out = [None] * len(batches)

    def work(i):
        out[i] = client.score(batches[i])

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(len(batches))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    for i, b in enumerate(batches):
        assert out[i] is not None and len(out[i]) == len(b)
    client.close()


def test_remote_backend_in_engine(server):
    sock, _ = server
    eng = ScoringEngine(EngineConfig(model="remote", socket_path=sock)).start()
    try:
        batch = synthesize_traces(8, seed=2)
        scores = eng.score_sync(batch, timeout_s=5.0)
        assert scores is not None and len(scores) == len(batch)
    finally:
        eng.shutdown()


# -------------------------------------------------- true process boundary
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spawn_sidecar(sock):
    env = dict(os.environ, PYTHONPATH=REPO_ROOT, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "odigos_tpu.serving.sidecar",
         "--socket", sock, "--model", "mock"],
        cwd=REPO_ROOT, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    deadline = time.monotonic() + 20
    while not os.path.exists(sock):
        if proc.poll() is not None:
            raise RuntimeError(
                f"sidecar died: {proc.stdout.read().decode()}")
        if time.monotonic() > deadline:
            proc.kill()
            raise TimeoutError("sidecar did not create its socket")
        time.sleep(0.05)
    return proc


def test_collector_scores_through_sidecar_process(tmp_path):
    sock = str(tmp_path / "proc.sock")
    proc = _spawn_sidecar(sock)
    try:
        cfg = {
            "receivers": {"synthetic": {"traces_per_batch": 3,
                                        "n_batches": 1}},
            "processors": {"tpuanomaly": {
                "model": "remote", "socket_path": sock,
                "threshold": 0.9, "timeout_ms": 5000,
                "shared_engine": False}},
            "exporters": {"tracedb": {}},
            "service": {"pipelines": {"traces/in": {
                "receivers": ["synthetic"],
                "processors": ["tpuanomaly"],
                "exporters": ["tracedb"]}}},
        }
        batch = synthesize_traces(6, seed=3)
        attrs = list(batch.span_attrs)
        attrs[0] = {**attrs[0], "mock.anomaly": True}  # mock backend hook
        from dataclasses import replace

        batch = replace(batch, span_attrs=tuple(attrs))
        with Collector(cfg) as c:
            c.drain_receivers()
            c.graph.pipeline_entries["traces/in"].consume(batch)
            c.drain_receivers()
            db = c.component("tracedb")
            assert db.wait_for_spans(len(batch), timeout=10)
            flagged = [d for d in db.all_spans().span_attrs
                       if FLAG_ATTR in d]
            assert flagged, "sidecar-scored anomaly span was not flagged"
    finally:
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=10)


def test_sidecar_death_passes_through(tmp_path):
    """Kill the sidecar mid-run: pipeline keeps flowing, spans unscored."""
    sock = str(tmp_path / "die.sock")
    proc = _spawn_sidecar(sock)
    eng = ScoringEngine(EngineConfig(model="remote", socket_path=sock)).start()
    try:
        batch = synthesize_traces(4, seed=4)
        assert eng.score_sync(batch, timeout_s=5.0) is not None
        proc.kill()
        proc.wait(timeout=10)
        meter.reset()
        # connection lost → engine error → None → caller passes through.
        # The client retries a reconnect for connect_timeout_s before the
        # error surfaces, so the counter lags the pass-through.
        assert eng.score_sync(batch, timeout_s=2.0) is None
        deadline = time.time() + 10
        while (meter.counter("odigos_anomaly_engine_errors_total") == 0
               and time.time() < deadline):
            time.sleep(0.05)
        assert meter.counter("odigos_anomaly_engine_errors_total") > 0
    finally:
        eng.shutdown()
        if proc.poll() is None:
            proc.kill()


def test_overload_rejection(tmp_path):
    """Admission control at the accept loop: beyond max_inflight the server
    replies ST_ERROR instead of spawning an unbounded thread per request
    (round-2 review weak item 5)."""
    import threading

    from odigos_tpu.serving.sidecar import (
        OVERLOAD_METRIC, SidecarClient, SidecarServer)
    from odigos_tpu.utils.telemetry import meter

    release = threading.Event()
    entered = threading.Semaphore(0)  # one permit per request in the engine

    class SlowEngine:
        def start(self):
            return self

        def shutdown(self):
            release.set()

        def warmup(self, batch):
            pass

        def score_sync(self, batch, features=None, timeout_s=None):
            entered.release()
            release.wait(10)
            import numpy as np

            return np.zeros(len(batch), np.float32)

    sock = str(tmp_path / "score.sock")
    server = SidecarServer(SlowEngine(), sock, max_inflight=2)
    server.start()
    before = meter.counter(OVERLOAD_METRIC)
    try:
        client = SidecarClient(sock)
        batch = synthesize_traces(3, seed=0)
        from odigos_tpu.wire.codec import encode_batch
        from odigos_tpu.serving.sidecar import OP_SCORE

        body = encode_batch(batch)
        waiters = []
        for _ in range(2):  # fill both slots (responses blocked on engine)
            rid, rec = client._new_waiter()
            from odigos_tpu.serving.sidecar import _send_frame

            client.connect()
            with client._wlock:
                _send_frame(client._sock, rid, OP_SCORE, body)
            waiters.append(rec)
        # wait until BOTH handler threads are inside the engine — only then
        # is the semaphore provably exhausted
        for _ in range(2):
            assert entered.acquire(timeout=5), \
                "handler threads never reached the engine"
        with pytest.raises(RuntimeError, match="overloaded"):
            client.score(batch, timeout_s=5.0)
        assert meter.counter(OVERLOAD_METRIC) == before + 1
        release.set()
        for rec in waiters:  # the in-flight two still complete
            assert rec["event"].wait(5)
    finally:
        release.set()
        server.shutdown()


def test_client_reconnects_after_server_restart(tmp_path):
    """The reader thread clears the dead socket on connection loss so the
    next request reconnects immediately (round-2 advisor finding)."""
    from odigos_tpu.serving.engine import EngineConfig, ScoringEngine
    from odigos_tpu.serving.sidecar import SidecarClient, SidecarServer

    sock = str(tmp_path / "score.sock")
    server = SidecarServer(
        ScoringEngine(EngineConfig(model="mock")), sock)
    server.start()
    client = SidecarClient(sock)
    batch = synthesize_traces(3, seed=0)
    try:
        assert len(client.score(batch, timeout_s=5.0)) == len(batch)
        server.shutdown()
        deadline = time.time() + 5
        while client._sock is not None and time.time() < deadline:
            time.sleep(0.02)
        assert client._sock is None, "dead socket never cleared"
        server2 = SidecarServer(
            ScoringEngine(EngineConfig(model="mock")), sock)
        server2.start()
        try:
            assert len(client.score(batch, timeout_s=5.0)) == len(batch)
        finally:
            server2.shutdown()
    finally:
        client.close()
