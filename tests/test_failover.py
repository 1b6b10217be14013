"""Failover supervisor + engine error-path tests (ISSUE 13).

Contracts pinned:

* the breaker's state machine on an injected clock: trip after
  ``trip_errors`` inside ``window_s`` (stale errors age out), one
  half-open probe in flight at a time, failed probe re-opens + re-arms,
  ``recovery_successes`` consecutive successes close;
* engine integration: a persistent device fault trips the breaker, the
  zscore fallback keeps scoring (requests resolve with scores, not
  pass-throughs), a group dispatched through the primary before the
  trip harvests against the PRIMARY, and clearing the fault recovers
  via traffic-riding probes;
* the engine's error path under SUSTAINED dispatch failure (the
  satellite): ``on_done`` fires exactly once per request, every frame
  forwards unscored, the error counter moves, and the fast-path route
  stays conserved end to end;
* conditions: ``ModelFailover`` Degraded while tripped, an explicit
  Healthy row after recovery, no row for a never-tripped breaker;
* config: EngineConfig normalizes the failover mapping hashable
  (shared-engine keying), unknown keys/invalid values refuse at
  construction, remote backends refuse failover outright.
"""

from __future__ import annotations

import threading
import time

import pytest

from odigos_tpu.pdata import synthesize_traces
from odigos_tpu.pipeline.service import Collector
from odigos_tpu.selftelemetry.flow import flow_ledger
from odigos_tpu.serving.engine import EngineConfig, ScoringEngine
from odigos_tpu.serving.failover import (
    CLOSED,
    HALF_OPEN,
    OPEN,
    FailoverConfig,
    FailoverSupervisor,
    failover_conditions,
)
from odigos_tpu.utils.telemetry import meter
from odigos_tpu.wire.client import WireExporter

from tests.test_ingest_fastpath import soak_config, wait_for


class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def make_sup(clock=None, **kw) -> FailoverSupervisor:
    primary, fallback = object(), object()
    cfg = FailoverConfig(**kw)
    return FailoverSupervisor("mock", primary, fallback, cfg,
                              clock=clock or FakeClock())


# ------------------------------------------------------------ state machine


class TestBreakerStateMachine:
    def test_trips_after_threshold_inside_window(self):
        clock = FakeClock()
        sup = make_sup(clock, trip_errors=3, window_s=5.0)
        for _ in range(2):
            sup.observe(sup.primary, ok=False)
        assert sup.state == CLOSED
        sup.observe(sup.primary, ok=False)
        assert sup.state == OPEN
        assert sup.trips == 1

    def test_stale_errors_age_out_of_the_window(self):
        clock = FakeClock()
        sup = make_sup(clock, trip_errors=3, window_s=5.0)
        sup.observe(sup.primary, ok=False)
        sup.observe(sup.primary, ok=False)
        clock.advance(6.0)  # both errors now outside the window
        sup.observe(sup.primary, ok=False)
        assert sup.state == CLOSED, \
            "two stale errors + one fresh must not trip a 3-error breaker"

    def test_open_serves_fallback_until_probe_interval(self):
        clock = FakeClock()
        sup = make_sup(clock, trip_errors=1, probe_interval_s=1.0)
        sup.observe(sup.primary, ok=False)
        assert sup.state == OPEN
        assert sup.select_backend() is sup.fallback
        clock.advance(1.1)
        assert sup.select_backend() is sup.primary  # the probe
        assert sup.state == HALF_OPEN
        # only ONE probe in flight: the next group keeps the fallback
        assert sup.select_backend() is sup.fallback

    def test_failed_probe_reopens_and_rearms(self):
        clock = FakeClock()
        sup = make_sup(clock, trip_errors=1, probe_interval_s=1.0)
        sup.observe(sup.primary, ok=False)
        clock.advance(1.1)
        backend, probe = sup.select()
        assert backend is sup.primary and probe
        sup.observe(sup.primary, ok=False, probe=True)
        assert sup.state == OPEN
        assert sup.select() == (sup.fallback, False)  # timer re-armed
        clock.advance(1.1)
        assert sup.select() == (sup.primary, True)

    def test_consecutive_successes_recover(self):
        clock = FakeClock()
        sup = make_sup(clock, trip_errors=1, probe_interval_s=1.0,
                       recovery_successes=2)
        sup.observe(sup.primary, ok=False)
        clock.advance(1.1)
        assert sup.select() == (sup.primary, True)
        sup.observe(sup.primary, ok=True, probe=True)
        assert sup.state == HALF_OPEN  # one success is not recovery
        # confirmation probes go back to back, no interval wait
        assert sup.select() == (sup.primary, True)
        sup.observe(sup.primary, ok=True, probe=True)
        assert sup.state == CLOSED
        assert sup.recoveries == 1
        assert sup.select() == (sup.primary, False)

    def test_stale_pretrip_results_cannot_touch_the_probe(self):
        """A pre-trip in-flight group resolving AFTER the trip is stale
        evidence: it must not free the probe slot (two concurrent
        probes) and its success must not count toward recovery."""
        clock = FakeClock()
        sup = make_sup(clock, trip_errors=1, probe_interval_s=1.0,
                       recovery_successes=1)
        sup.observe(sup.primary, ok=False)
        clock.advance(1.1)
        assert sup.select() == (sup.primary, True)  # probe in flight
        # the pre-trip group lands late, NOT a probe
        sup.observe(sup.primary, ok=False, probe=False)
        assert sup.select() == (sup.fallback, False), \
            "probe slot freed by stale evidence — second probe dispatched"
        sup.observe(sup.primary, ok=True, probe=False)
        assert sup.state == HALF_OPEN, \
            "stale pre-trip success closed the breaker without a probe"
        # the genuine probe resolves and recovers
        sup.observe(sup.primary, ok=True, probe=True)
        assert sup.state == CLOSED

    def test_fallback_results_never_drive_the_breaker(self):
        clock = FakeClock()
        sup = make_sup(clock, trip_errors=1)
        sup.observe(sup.fallback, ok=False)
        sup.observe(sup.fallback, ok=False)
        assert sup.state == CLOSED
        sup.observe(sup.primary, ok=False)
        assert sup.state == OPEN
        sup.observe(sup.fallback, ok=True, n_spans=7)
        assert sup.state == OPEN
        assert sup.fallback_spans == 7

    def test_status_and_transitions(self):
        clock = FakeClock()
        sup = make_sup(clock, trip_errors=1, probe_interval_s=0.5,
                       recovery_successes=1)
        sup.observe(sup.primary, ok=False, error="RuntimeError: dead")
        clock.advance(0.6)
        assert sup.select() == (sup.primary, True)
        sup.observe(sup.primary, ok=True, probe=True)
        st = sup.status()
        assert st["trips"] == 1 and st["recoveries"] == 1
        assert [t["event"] for t in st["transitions"]] \
            == ["tripped", "recovered"]
        assert "RuntimeError: dead" in st["last_error"]


# ---------------------------------------------------------------- config


class TestFailoverConfig:
    def test_unknown_keys_refuse(self):
        with pytest.raises(ValueError, match="unknown failover keys"):
            FailoverConfig.from_spec({"trip_erors": 3})

    def test_invalid_values_refuse(self):
        with pytest.raises(ValueError):
            FailoverConfig(window_s=0.0)
        with pytest.raises(ValueError):
            FailoverConfig(trip_errors=0)
        with pytest.raises(ValueError, match="fallback_model"):
            FailoverConfig(fallback_model="transformer")

    def test_engine_config_normalizes_hashable(self):
        cfg = EngineConfig(model="mock",
                           failover={"trip_errors": 2, "window_s": 3.0})
        hash(cfg)  # shared-engine keying hashes the config
        assert cfg.failover_spec() == {"trip_errors": 2, "window_s": 3.0}
        assert EngineConfig(model="mock").failover_spec() is None
        assert EngineConfig(model="mock",
                            failover=False).failover_spec() is None
        assert EngineConfig(model="mock",
                            failover=True).failover_spec() == {}

    def test_true_spelling_builds_default_breaker(self):
        eng = ScoringEngine(EngineConfig(model="mock", failover=True))
        assert eng.failover is not None
        assert eng.failover.cfg == FailoverConfig()

    def test_remote_refuses_failover(self):
        with pytest.raises(ValueError, match="remote"):
            ScoringEngine(EngineConfig(model="remote",
                                       socket_path="/tmp/x.sock",
                                       failover=True))

    def test_enabled_key_is_the_on_switch(self):
        # pipelinegen may render {"enabled": True}; it must not read as
        # an unknown tuning knob
        assert FailoverConfig.from_spec({"enabled": True}) \
            == FailoverConfig()

    def test_enabled_false_is_an_opt_out(self):
        # {"enabled": false} must DISABLE the breaker, not silently arm
        # a default one with the off-switch discarded
        cfg = EngineConfig(model="mock", failover={"enabled": False})
        assert cfg.failover_spec() is None
        assert ScoringEngine(cfg).failover is None
        on = EngineConfig(model="mock",
                          failover={"enabled": True, "trip_errors": 5})
        assert on.failover_spec() == {"trip_errors": 5}


# ------------------------------------------------- engine error path


def fo_engine(**fo_kw) -> ScoringEngine:
    fo = dict({"trip_errors": 2, "window_s": 10.0,
               "probe_interval_s": 0.1, "recovery_successes": 2,
               "fallback_model": "mock"}, **fo_kw)
    return ScoringEngine(EngineConfig(model="mock", failover=fo)).start()


class TestEngineSustainedFailure:
    """The satellite: serving/engine.py's dispatch-failure path under a
    PERSISTENT fault — exactly-once completion, unscored forwarding,
    errors counted."""

    def test_on_done_exactly_once_per_request(self):
        eng = ScoringEngine(EngineConfig(model="mock")).start()
        try:
            eng.inject_device_fault()
            calls: dict[int, int] = {}
            lock = threading.Lock()
            reqs = []
            for s in range(8):
                b = synthesize_traces(2, seed=s)

                def on_done(r, i=s):
                    with lock:
                        calls[i] = calls.get(i, 0) + 1

                req = eng.submit(b, on_done=on_done)
                assert req is not None
                reqs.append(req)
            assert all(r.done.wait(10.0) for r in reqs)
            time.sleep(0.1)  # any late double-fire would land here
            with lock:
                assert calls == {i: 1 for i in range(8)}, calls
            # every request resolved UNSCORED (the caller forwards the
            # batch as-is — lossless pass-through)
            assert all(r.scores is None for r in reqs)
        finally:
            eng.shutdown()

    def test_errors_counted_and_recovery_after_clear(self):
        eng = ScoringEngine(EngineConfig(model="mock")).start()
        try:
            errors0 = meter.counter("odigos_anomaly_engine_errors_total")
            eng.inject_device_fault()
            b = synthesize_traces(3, seed=0)
            for _ in range(4):
                assert eng.score_sync(b, timeout_s=5.0) is None
            assert meter.counter("odigos_anomaly_engine_errors_total") \
                >= errors0 + 4
            eng.clear_device_fault()
            assert eng.score_sync(b, timeout_s=5.0) is not None
        finally:
            eng.shutdown()

    def test_fastpath_conserved_under_sustained_failure(self):
        """The e2e shape of the satellite: a fast-path collector under a
        persistent engine fault forwards EVERY span downstream unscored
        and the ledger stays balanced."""
        flow_ledger.reset()
        cfg = soak_config(fast_path=True)
        collector = Collector(cfg).start()
        try:
            fp = collector.graph.fastpaths["traces/in"]
            fp.engine.inject_device_fault()
            port = collector.graph.receivers["otlpwire"].port
            exp = WireExporter("t", {"endpoint": f"127.0.0.1:{port}"})
            exp.start()
            sink = collector.graph.exporters["tracedb"]
            want = 0
            for s in range(4):
                b = synthesize_traces(8, seed=s)
                want += len(b)
                exp.export(b)
            assert exp.flush(timeout=20.0)
            assert wait_for(lambda: sink.span_count == want), \
                f"{sink.span_count}/{want}"
            exp.shutdown()
            collector.drain_receivers(20.0)
            balances = flow_ledger.conservation()
            assert balances["traces/in"]["leak"] == 0, balances
            # unscored pass-through: no span ever got the anomaly attr
            assert all("odigos.anomaly" not in dict(a)
                       for batch in sink._batches
                       for a in batch.span_attrs)
        finally:
            collector.shutdown()
            flow_ledger.reset()


class TestEngineFailover:
    def test_trip_fallback_and_recover(self):
        eng = fo_engine()
        try:
            b = synthesize_traces(4, seed=1)
            assert eng.score_sync(b, timeout_s=5.0) is not None
            eng.inject_device_fault()
            # sustained failure: first calls pass through, breaker trips
            deadline = time.monotonic() + 10.0
            while not eng.failover.active \
                    and time.monotonic() < deadline:
                eng.score_sync(b, timeout_s=2.0)
            assert eng.failover.active
            # the fallback now SCORES (the fault only hits the primary)
            scores = eng.score_sync(b, timeout_s=5.0)
            assert scores is not None
            assert eng.failover.fallback_spans > 0
            eng.clear_device_fault()
            deadline = time.monotonic() + 10.0
            while eng.failover.active and time.monotonic() < deadline:
                eng.score_sync(b, timeout_s=2.0)
                time.sleep(0.05)
            assert not eng.failover.active
            assert eng.failover.recoveries >= 1
            assert eng.score_sync(b, timeout_s=5.0) is not None
        finally:
            eng.shutdown()

    def test_pipeline_stats_carries_failover(self):
        eng = fo_engine()
        try:
            assert eng.pipeline_stats()["failover"]["state"] == CLOSED
            assert eng.failover_status()["state"] == CLOSED
        finally:
            eng.shutdown()

    def test_no_breaker_means_no_surface(self):
        eng = ScoringEngine(EngineConfig(model="mock"))
        assert eng.failover is None
        assert eng.failover_status() is None
        assert "failover" not in eng.pipeline_stats()


# ------------------------------------------------------------- conditions


class TestModelFailoverCondition:
    def test_condition_round_trip(self):
        eng = fo_engine()
        try:
            b = synthesize_traces(2, seed=2)
            assert eng.score_sync(b, timeout_s=5.0) is not None
            # armed but never tripped: no Degraded row (an earlier
            # test's recovered supervisor may still contribute a
            # Healthy row until it is garbage collected)
            assert failover_conditions().get(
                "engine/mock", ("Healthy",))[0] == "Healthy"
            eng.inject_device_fault()
            deadline = time.monotonic() + 10.0
            while not eng.failover.active \
                    and time.monotonic() < deadline:
                eng.score_sync(b, timeout_s=2.0)
            cond = failover_conditions()["engine/mock"]
            assert cond[0] == "Degraded" and cond[1] == "ModelFailover"
            eng.clear_device_fault()
            deadline = time.monotonic() + 10.0
            while eng.failover.active and time.monotonic() < deadline:
                eng.score_sync(b, timeout_s=2.0)
                time.sleep(0.05)
            cond = failover_conditions()["engine/mock"]
            assert cond[0] == "Healthy"
        finally:
            eng.shutdown()
