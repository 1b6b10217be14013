"""Ingest fast path (ISSUE 6 tentpole): zero-copy wire frame → featurized
device-ready arrays, deadline-based adaptive batching, watermark-driven
admission.

The correctness contract pinned here:

* fast-path ingest produces BIT-IDENTICAL features and scores vs the
  componentwise memory_limiter → batch → tpuanomaly path at equal
  request grouping (the engine's per-request featurization semantics);
* empty frames and malformed frames behave exactly as before (empty
  dies quietly, malformed answers MALFORMED + ledger ``invalid``);
* saturation answers REJECTED with the shed named ``queue_full`` in the
  ledger; watermark breaches shed PRE-DECODE at the receiver;
* a mid-stream hot reload keeps spans flowing and conserved;
* conservation holds end-to-end (``in == out + dropped + pending``).
"""

import socket
import threading
import time

import numpy as np
import pytest

from odigos_tpu.features import FeaturizerConfig, featurize
from odigos_tpu.pdata import synthesize_traces
from odigos_tpu.pipeline.service import Collector
from odigos_tpu.selftelemetry.flightrecorder import flight_recorder
from odigos_tpu.selftelemetry.flow import flow_ledger
from odigos_tpu.serving import EngineConfig, ScoringEngine
from odigos_tpu.serving.fastpath import (
    FLAG_ATTR, SCORE_ATTR, FastPathSaturated, IngestFastPath,
    tag_anomalies)
from odigos_tpu.utils.telemetry import meter
from odigos_tpu.wire.client import WireExporter
from odigos_tpu.wire.codec import MAGIC, _HDR, frame
from odigos_tpu.wire.server import REJECTED, WatermarkGate


def wait_for(cond, timeout=15.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return False


def soak_config(fast_path=True, receiver_cfg=None, model="mock",
                threshold=0.6, deadline_ms=None):
    fp = {"deadline_ms": deadline_ms} if deadline_ms else True
    return {
        "receivers": {"otlpwire": receiver_cfg or {}},
        "processors": {
            "memory_limiter": {"limit_mib": 512},
            "batch": {"send_batch_size": 1, "timeout_s": 0.0},
            "tpuanomaly": {"model": model, "threshold": threshold,
                           "timeout_ms": 30000, "shared_engine": False},
        },
        "exporters": {"tracedb": {}},
        "service": {"pipelines": {
            "traces/in": dict(
                {"receivers": ["otlpwire"],
                 "processors": ["memory_limiter", "batch", "tpuanomaly"],
                 "exporters": ["tracedb"]},
                **({"fast_path": fp} if fast_path else {})),
        }},
    }


def run_frames(cfg, batches):
    """Start a collector, ship each batch as one wire frame WAITING for
    delivery between frames (matched request grouping: every frame is
    its own scoring group on both routes), return the exporter output."""
    flow_ledger.reset()
    collector = Collector(cfg).start()
    try:
        port = collector.graph.receivers["otlpwire"].port
        exp = WireExporter("t", {"endpoint": f"127.0.0.1:{port}"})
        exp.start()
        sink = collector.graph.exporters["tracedb"]
        want = 0
        for b in batches:
            exp.export(b)
            want += len(b)
            assert wait_for(lambda: sink.span_count == want), \
                f"stuck at {sink.span_count}/{want}"
        exp.shutdown()
        collector.drain_receivers(20.0)
        return list(sink._batches)
    finally:
        collector.shutdown()


class TestParity:
    """Fast path output == componentwise output, bit for bit."""

    def make_batches(self):
        out = []
        for s in range(4):
            b = synthesize_traces(24, seed=s)
            if s == 2:
                # force the mock backend's anomaly hook on a few spans
                mask = np.zeros(len(b), bool)
                mask[:5] = True
                b = b.with_span_attrs({"mock.anomaly": [True] * 5}, mask)
            out.append(b)
        return out

    def test_scores_and_attrs_bit_identical_vs_componentwise(self):
        batches = self.make_batches()
        got_fast = run_frames(soak_config(fast_path=True), batches)
        got_slow = run_frames(soak_config(fast_path=False), batches)
        spans_fast = [d for b in got_fast for d in b.span_attrs]
        spans_slow = [d for b in got_slow for d in b.span_attrs]
        assert len(spans_fast) == len(spans_slow) \
            == sum(len(b) for b in batches)
        for a, b in zip(spans_fast, spans_slow):
            assert dict(a) == dict(b)
        flagged = [d for d in spans_fast if FLAG_ATTR in d]
        assert flagged, "anomaly hook spans must be tagged on both paths"
        assert all(d[SCORE_ATTR] >= 0.6 for d in flagged)

    def test_features_bit_identical_per_frame(self):
        """The fast path featurizes each decoded frame; the engine
        featurizes each submitted batch — identical inputs, identical
        (memoized) tables, identical tensors."""
        cfg = FeaturizerConfig(attr_slots=4)
        from odigos_tpu.wire.codec import decode_frame, encode_batch

        for s in range(3):
            b = synthesize_traces(16, seed=40 + s)
            decoded, _tp = decode_frame(encode_batch(b))
            f1 = featurize(b, cfg)
            f2 = featurize(decoded, cfg)
            np.testing.assert_array_equal(f1.categorical, f2.categorical)
            np.testing.assert_array_equal(f1.continuous, f2.continuous)

    def test_tag_anomalies_shared_helper_matches_processor(self):
        from odigos_tpu.components.processors import tpuanomaly as tp

        assert tp.tag_anomalies is tag_anomalies
        assert tp.SCORE_ATTR == SCORE_ATTR
        b = synthesize_traces(8, seed=1)
        scores = np.linspace(0.0, 1.0, len(b), dtype=np.float32)
        tagged = tag_anomalies(b, scores, 0.5)
        flags = [SCORE_ATTR in d for d in tagged.span_attrs]
        assert flags == list(scores >= 0.5)


class TestConfigContract:
    def test_fast_path_requires_tpuanomaly(self):
        cfg = soak_config(fast_path=True)
        cfg["service"]["pipelines"]["traces/in"]["processors"] = [
            "memory_limiter", "batch"]
        with pytest.raises(ValueError, match="fast_path requires"):
            Collector(cfg)

    def test_fast_path_rejects_bypassed_processors(self):
        """Stages ahead of the scorer are skipped by the route; anything
        but memory_limiter/batch there must fail loudly instead of
        silently not applying to wire traffic."""
        cfg = soak_config(fast_path=True)
        cfg["processors"]["probabilisticsampler"] = {"percentage": 50}
        cfg["service"]["pipelines"]["traces/in"]["processors"] = [
            "memory_limiter", "probabilisticsampler", "batch",
            "tpuanomaly"]
        with pytest.raises(ValueError, match="would bypass"):
            Collector(cfg)
        # the same processor AFTER the scorer is fine (still applies)
        cfg["service"]["pipelines"]["traces/in"]["processors"] = [
            "memory_limiter", "batch", "tpuanomaly",
            "probabilisticsampler"]
        Collector(cfg)


class TestFrameEdgeCases:
    def test_empty_frames_die_quietly(self):
        from odigos_tpu.pdata.spans import SpanBatch

        batches = [synthesize_traces(8, seed=1)]
        flow_ledger.reset()
        collector = Collector(soak_config(fast_path=True)).start()
        try:
            fp = collector.graph.fastpaths["traces/in"]
            fp.consume(SpanBatch.empty())  # no submit, no forward
            assert fp.flow_pending() == 0
            port = collector.graph.receivers["otlpwire"].port
            exp = WireExporter("t", {"endpoint": f"127.0.0.1:{port}"})
            exp.start()
            exp.export(batches[0])
            sink = collector.graph.exporters["tracedb"]
            assert wait_for(lambda: sink.span_count == len(batches[0]))
            exp.shutdown()
        finally:
            collector.shutdown()

    def test_malformed_frame_answers_malformed_and_ledger_invalid(self):
        flow_ledger.reset()
        collector = Collector(soak_config(fast_path=True)).start()
        try:
            port = collector.graph.receivers["otlpwire"].port
            s = socket.create_connection(("127.0.0.1", port), timeout=5)
            junk = b"\x00" * 64
            s.sendall(MAGIC + _HDR.pack(len(junk)) + junk)
            assert s.recv(1) == b"\x02"  # MALFORMED
            s.close()
            drops = flow_ledger.snapshot()["drops"]
            ingress = [d for d in drops if d["pipeline"] == "(ingress)"]
            assert ingress and ingress[0]["reasons"].get("invalid") == 1
        finally:
            collector.shutdown()


class TestAdmission:
    def test_saturated_fastpath_answers_rejected_named_queue_full(self):
        flow_ledger.reset()
        meter.reset()
        cfg = soak_config(fast_path=True)
        cfg["service"]["pipelines"]["traces/in"]["fast_path"] = {
            "max_pending_spans": 18}  # one small trace fits, a burst not
        collector = Collector(cfg).start()
        try:
            fp = collector.graph.fastpaths["traces/in"]
            b = synthesize_traces(4, seed=1)  # 20 spans > 18: sheds
            assert len(b) > 18
            with pytest.raises(FastPathSaturated):
                fp.consume(b)
            drops = flow_ledger.snapshot()["drops"]
            named = [d for d in drops
                     if d["component"] == "fastpath"
                     and d["reasons"].get("queue_full") == len(b)]
            assert named, f"queue_full shed not named: {drops}"
            # over the wire the same condition answers REJECTED and the
            # client backs off + retries (delivered once capacity frees)
            port = collector.graph.receivers["otlpwire"].port
            exp = WireExporter("t", {"endpoint": f"127.0.0.1:{port}",
                                     "retry_initial_s": 0.05})
            exp.start()
            one = synthesize_traces(1, seed=2)  # 17 spans <= 18: accepted
            assert len(one) <= 18
            exp.export(one)
            sink = collector.graph.exporters["tracedb"]
            assert wait_for(lambda: sink.span_count >= len(one))
            exp.shutdown()
        finally:
            collector.shutdown()

    def test_watermark_breach_sheds_predecode(self):
        flow_ledger.reset()
        meter.reset()
        recv_cfg = {"admission": {
            "watermarks": {"widget": {"queue_depth": 10}},
            "refresh_ms": 0.0}}
        collector = Collector(
            soak_config(fast_path=True, receiver_cfg=recv_cfg)).start()
        try:
            port = collector.graph.receivers["otlpwire"].port
            b = synthesize_traces(4, seed=3)
            sink = collector.graph.exporters["tracedb"]

            # below the limit: admitted
            s = socket.create_connection(("127.0.0.1", port), timeout=5)
            flow_ledger.watermark("widget", "queue_depth", 3)
            s.sendall(frame(b))
            assert s.recv(1) == b"\x00"  # ACCEPTED
            assert wait_for(lambda: sink.span_count == len(b))

            # breach: REJECTED before decode, shed named in the ledger
            flow_ledger.watermark("widget", "queue_depth", 10)
            s.sendall(frame(b))
            assert s.recv(1) == REJECTED
            drops = flow_ledger.snapshot()["drops"]
            ingress = [d for d in drops if d["pipeline"] == "(ingress)"]
            assert ingress and \
                ingress[0]["reasons"].get("queue_full") == 1
            key = ("odigos_admission_rejected_frames_total"
                   "{receiver=otlpwire,reason=widget:queue_depth}")
            assert meter.counter(key) == 1
            # watermark snapshot published alongside the decision
            gauges = meter.snapshot()
            assert gauges.get(
                "odigos_admission_watermark"
                "{component=widget,queue=queue_depth}") == 10.0

            # recovery: watermark falls, traffic admitted again
            flow_ledger.watermark("widget", "queue_depth", 0)
            s.sendall(frame(b))
            assert s.recv(1) == b"\x00"
            s.close()
        finally:
            collector.shutdown()

    def test_predicted_burn_watermark_sheds_predecode_with_blame(self):
        """Predictive shed at the SOCKET (ISSUE 12): bound the fast
        path's predicted_burn_ms watermark at the deadline and a frame
        priced to expire is REJECTED before decode — the ledger names
        it with the blame=predicted dimension."""
        flow_ledger.reset()
        meter.reset()
        recv_cfg = {"admission": {
            "watermarks": {"fastpath/traces/in":
                           {"predicted_burn_ms": 25.0}},
            "refresh_ms": 0.0}}
        collector = Collector(
            soak_config(fast_path=True, receiver_cfg=recv_cfg)).start()
        try:
            port = collector.graph.receivers["otlpwire"].port
            b = synthesize_traces(4, seed=3)
            sink = collector.graph.exporters["tracedb"]
            s = socket.create_connection(("127.0.0.1", port), timeout=5)
            # healthy prediction: admitted
            flow_ledger.watermark("fastpath/traces/in",
                                  "predicted_burn_ms", 3.0)
            s.sendall(frame(b))
            assert s.recv(1) == b"\x00"
            assert wait_for(lambda: sink.span_count == len(b))
            # priced past the budget: REJECTED pre-decode
            flow_ledger.watermark("fastpath/traces/in",
                                  "predicted_burn_ms", 80.0)
            s.sendall(frame(b))
            assert s.recv(1) == REJECTED
            s.close()
            key = ("odigos_admission_rejected_frames_total"
                   "{receiver=otlpwire,"
                   "reason=fastpath/traces/in:predicted_burn_ms}")
            assert meter.counter(key) == 1
            blamed = [k for k in meter.snapshot()
                      if k.startswith("odigos_flow_dropped_items_total")
                      and "blame=predicted" in k]
            assert blamed, "pre-decode predictive shed lost its blame"
        finally:
            collector.shutdown()

    def test_fastpath_publishes_predicted_burn_watermark(self):
        """A live fast path keeps the predicted_burn_ms watermark
        current (backlog + priced stage cost) once means exist."""
        flow_ledger.reset()
        latency_ledger = __import__(
            "odigos_tpu.selftelemetry.latency",
            fromlist=["latency_ledger"]).latency_ledger
        latency_ledger.reset()
        eng = ScoringEngine(EngineConfig(model="mock")).start()

        class Sink:
            def consume(self, b):
                pass

        fp = IngestFastPath("traces/pb", eng, 0.6, Sink(),
                            {"deadline_ms": 100.0,
                             "predictive_min_frames": 1})
        fp.start()
        try:
            for s in range(3):
                fp.consume(synthesize_traces(4, seed=s))
            assert wait_for(lambda: fp.flow_pending() == 0)
            # force a re-price on the next refresh, then traffic
            fp._stage_cost_next_ns = 0
            fp.consume(synthesize_traces(4, seed=9))
            assert wait_for(lambda: fp.flow_pending() == 0)
            wm = flow_ledger.watermark_current("fastpath/traces/pb",
                                               "predicted_burn_ms")
            assert wm is not None and wm >= 0.0
            assert fp._stage_cost_ms is not None and \
                fp._stage_cost_ms > 0.0
        finally:
            fp.shutdown()
            eng.shutdown()

    def test_gate_maps_byte_watermarks_to_memory_limited(self):
        flow_ledger.reset()
        gate = WatermarkGate({"memory_limiter": {"inflight_bytes": 100}},
                             refresh_s=0.0)
        assert gate.check() is None  # never reported: no verdict
        flow_ledger.watermark("memory_limiter", "inflight_bytes", 200)
        assert gate.check() == ("memory_limiter", "inflight_bytes",
                                "memory_limited")
        flow_ledger.watermark("memory_limiter", "inflight_bytes", 50)
        assert gate.check() is None

    def test_gate_verdict_is_cached_between_refreshes(self):
        flow_ledger.reset()
        gate = WatermarkGate({"w": {"queue_depth": 5}}, refresh_s=60.0)
        flow_ledger.watermark("w", "queue_depth", 9)
        assert gate.check() is not None
        # the breach clears but the cached verdict holds until refresh —
        # the accept path must stay one monotonic read
        flow_ledger.watermark("w", "queue_depth", 0)
        assert gate.check() is not None
        gate._next_eval = 0.0
        assert gate.check() is None


class TestHotReload:
    def test_reload_mid_stream_keeps_flowing_and_conserved(self):
        flow_ledger.reset()
        cfg = soak_config(fast_path=True)
        collector = Collector(cfg).start()
        stop = threading.Event()
        sent = [0]
        try:
            port = collector.graph.receivers["otlpwire"].port
            exp = WireExporter("t", {"endpoint": f"127.0.0.1:{port}",
                                     "max_elapsed_s": 30.0})
            exp.start()
            batches = [synthesize_traces(16, seed=s) for s in range(4)]

            def sender():
                k = 0
                while not stop.is_set():
                    exp.export(batches[k % 4])
                    sent[0] += len(batches[k % 4])
                    k += 1
                    while exp.queued > 8 and not stop.is_set():
                        time.sleep(0.001)
                    time.sleep(0.002)

            t = threading.Thread(target=sender, daemon=True)
            t.start()
            time.sleep(0.25)
            new_cfg = soak_config(fast_path=True, threshold=0.9)
            new_cfg["receivers"]["otlpwire"] = {
                "port": port}  # keep the bind (sender reconnects)
            collector.reload(new_cfg)
            assert "traces/in" in collector.graph.fastpaths
            time.sleep(0.25)
            stop.set()
            t.join(timeout=10)
            assert exp.flush(30.0)
            exp.shutdown()
            collector.drain_receivers(30.0)
            sink = collector.graph.exporters["tracedb"]
            # edge counters survive the reload (same ledger keys): the
            # pipeline stays conserved across the swap
            bal = flow_ledger.conservation()["traces/in"]
            assert bal["leak"] == 0, bal
            assert sink.span_count > 0
        finally:
            collector.shutdown()


class TestConservation:
    def test_burst_conserves_and_pending_counts(self):
        flow_ledger.reset()
        flight_recorder.reset()
        collector = Collector(soak_config(fast_path=True)).start()
        try:
            port = collector.graph.receivers["otlpwire"].port
            exp = WireExporter("t", {"endpoint": f"127.0.0.1:{port}",
                                     "queue_size": 256,
                                     "max_elapsed_s": 30.0})
            exp.start()
            total = 0
            for s in range(12):
                b = synthesize_traces(32, seed=s)
                exp.export(b)
                total += len(b)
            assert exp.flush(30.0)
            exp.shutdown()
            collector.drain_receivers(30.0)
            sink = collector.graph.exporters["tracedb"]
            assert sink.span_count == total
            bal = flow_ledger.conservation()["traces/in"]
            assert bal["items_in"] == total
            assert bal["leak"] == 0, bal
            # a clean wire-fed burst (no fault injected, nothing shed)
            # freezes no incident: anything here is a trigger misfiring
            assert flight_recorder.enabled
            assert flight_recorder.incidents() == []
        finally:
            collector.shutdown()
            flight_recorder.reset()

    def test_flow_pending_reflects_window(self):
        eng = ScoringEngine(EngineConfig(model="mock"))  # not started

        class Sink:
            def consume(self, b):
                pass

        fp = IngestFastPath("traces/t", eng, 0.6, Sink(),
                            {"deadline_ms": 50.0})
        b = synthesize_traces(4, seed=1)
        fp.consume(b)  # forwarder not running: stays pending
        assert fp.flow_pending() == len(b)
        assert flow_ledger.watermark_current(
            "fastpath/traces/t", "pending_spans") == len(b)
        # the time-denominated admission signal: head age, reported on
        # every append/retire (≥ 0 with one just-appended frame)
        age = flow_ledger.watermark_current("fastpath/traces/t",
                                            "pending_ms")
        assert age is not None and age >= 0.0
        # backlog_ms (ISSUE 9): age of the oldest frame no submit lane
        # has STARTED — with no lanes running, the just-appended frame
        # IS the backlog head
        backlog = flow_ledger.watermark_current("fastpath/traces/t",
                                                "backlog_ms")
        assert backlog is not None and backlog >= 0.0
        fp.start()
        assert wait_for(lambda: fp.flow_pending() == 0)
        assert flow_ledger.watermark_current(
            "fastpath/traces/t", "pending_ms") == 0.0
        # every frame picked up: the gate's backlog reading must read
        # EMPTY (a stale peak would shed with nothing left to drain)
        assert flow_ledger.watermark_current(
            "fastpath/traces/t", "backlog_ms") == 0.0
        fp.shutdown()
        eng.shutdown()
