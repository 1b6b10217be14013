"""Full-stack e2e scenarios (the chainsaw suite analog, SURVEY.md §4 item 2):
trace-collection, data-streams, instrumentation-rollback, chaos/backpressure
against the in-process KinD-analog environment."""

import time

import numpy as np
import pytest

from odigos_tpu.components.api import Signal
from odigos_tpu.controlplane import Container, PodPhase
from odigos_tpu.controlplane.instrumentor import ic_name
from odigos_tpu.destinations import Destination
from odigos_tpu.e2e import (
    E2EEnvironment,
    Scenario,
    Step,
    inject_exporter_chaos,
    inject_memory_pressure,
)
from odigos_tpu.pdata import synthesize_traces

T = Signal.TRACES


def tracedb_dest(id="db1", streams=()):
    return Destination(id=id, dest_type="tracedb", signals=[T],
                       data_stream_names=list(streams))


class TestTraceCollection:
    """tests/e2e/trace-collection: deploy db -> app -> instrument ->
    traffic -> query spans."""

    def test_spans_flow_to_destination(self):
        with E2EEnvironment(nodes=2) as env:
            scenario = Scenario("trace-collection", [
                Step("add tracedb destination",
                     apply=lambda e: e.add_destination(tracedb_dest())),
                Step("deploy + instrument app",
                     apply=lambda e: (
                         e.cluster.add_workload("default", "checkout", [
                             Container(name="main", language="python",
                                       runtime_version="3.11")]),
                         e.instrument_workload("default", "checkout"))),
                Step("agent enabled",
                     assert_fn=lambda e: any(
                         c.agent_enabled for ic in e.store.list(
                             "InstrumentationConfig")
                         for c in ic.containers)),
                Step("traffic over the wire",
                     script=lambda e: e.send_traces_wire(
                         synthesize_traces(50, seed=1))),
                Step("spans stored",
                     assert_fn=lambda e: _db(e).span_count > 0),
                Step("whole trace present",
                     assert_fn=lambda e: _db(e).wait_for_trace(
                         "frontend", min_spans=5, timeout=1) is not None),
            ])
            results = scenario.run(env)
            assert all(r.ok for r in results)


def _db(env, id="db1"):
    return env.gateway_component(f"tracedb/tracedb-{id}")


class TestDataStreams:
    """tests/e2e/data-streams: two destinations on different streams; spans
    route by source stream membership (golden assertion on the generated
    config + live routing)."""

    def test_streams_route_separately(self):
        with E2EEnvironment(nodes=1) as env:
            env.add_destination(tracedb_dest("dbA", streams=["stream-a"]))
            env.add_destination(tracedb_dest("dbB", streams=["stream-b"]))
            env.cluster.add_workload("default", "svc-a", [
                Container(name="main", language="python",
                          runtime_version="3.11")])
            env.instrument_workload("default", "svc-a",
                                    data_streams=["stream-a"])
            # golden config shape: router + one pipeline per stream
            cm = env.store.get("ConfigMap", "odigos-system",
                               "odigos-gateway-config")
            cfg = cm.data["collector-conf"]
            pipes = cfg["service"]["pipelines"]
            assert any("stream-a" in p for p in pipes), pipes.keys()
            assert any("stream-b" in p for p in pipes), pipes.keys()
            # live routing: traffic from svc-a's workload lands in dbA only
            batch = synthesize_traces(30, seed=3)
            from dataclasses import replace
            routed = replace(
                batch,
                resources=tuple({**dict(r),
                                 "k8s.deployment.name": "svc-a",
                                 "k8s.namespace.name": "default"}
                                for r in batch.resources))
            assert env.send_traces_wire(routed)
            assert _db(env, "dbA").wait_for_spans(1, timeout=5)
            assert _db(env, "dbB").span_count == 0


class TestInstrumentationRollback:
    """tests/e2e/instrumentation-rollback: instrumented pods crash-looping
    -> automatic rollback with reason."""

    def test_crashloop_triggers_rollback(self):
        with E2EEnvironment(nodes=1) as env:
            w = env.cluster.add_workload("default", "flaky", [
                Container(name="main", language="python",
                          runtime_version="3.11")])
            # next rollout of this workload enters CrashLoopBackOff
            env.cluster.fail_next_rollout(w.ref)
            env.instrument_workload("default", "flaky")
            env.reconcile(rounds=6)
            ic = env.store.get("InstrumentationConfig", "default",
                               ic_name_for("flaky"))
            assert ic is not None
            cond = ic.condition("AgentEnabled")
            assert cond is not None and cond.reason == "CrashLoopBackOff", \
                (cond.reason if cond else None)
            # rolled back: no agents, pods healthy again
            assert all(not c.agent_enabled for c in ic.containers)
            assert all(p.phase == PodPhase.RUNNING
                       for p in env.cluster.pods.values())


def ic_name_for(name, ns="default"):
    from odigos_tpu.api.resources import WorkloadKind, WorkloadRef
    return ic_name(WorkloadRef(ns, WorkloadKind.DEPLOYMENT, name))


class TestChaos:
    """Chaos: destination latency + rejection; pipeline keeps flowing and
    rejection metrics surface (backpressure-exporter.yaml analog)."""

    def test_rejecting_destination_does_not_stall_others(self):
        with E2EEnvironment(nodes=1) as env:
            env.add_destination(tracedb_dest("good"))
            env.add_destination(Destination(
                id="bad", dest_type="mock", signals=[T],
                config={"MOCK_REJECT_FRACTION": "0", "MOCK_RESPONSE_DURATION": "0"}))
            assert env.send_traces_wire(synthesize_traces(10, seed=0))
            assert _db(env, "good").wait_for_spans(1, timeout=5)
            before = _db(env, "good").span_count
            # chaos: the mock destination starts rejecting everything
            inject_exporter_chaos(env, "mockdestination/bad",
                                  reject_fraction=1.0)
            assert env.send_traces_wire(synthesize_traces(10, seed=1))
            assert _db(env, "good").wait_for_spans(before + 1, timeout=5)
            mock = env.gateway_component("mockdestination/bad")
            # bad's batch processor flushes on its own clock — the good
            # destination landing first says nothing about bad's tick yet
            deadline = time.monotonic() + 5
            while mock.rejected_batches == 0 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert mock.rejected_batches > 0

    def test_backpressure_rejection_drives_scale_up(self):
        """The full backpressure loop over the real wire (round-2 review item 4;
        reference: configgrpc fork -> odigos_gateway_memory_limiter_
        rejections_total -> hpa.go custom metric): chaos memory pressure ->
        pre-decode REJECTED at the otlp front door -> rejection metric ->
        HpaDecider scales the gateway up -> pressure lifted -> the held
        frame is retried and delivered."""
        from odigos_tpu.utils.telemetry import meter
        from odigos_tpu.wire.server import REJECTIONS_METRIC

        with E2EEnvironment(nodes=1) as env:
            env.add_destination(tracedb_dest())
            assert env.send_traces_wire(synthesize_traces(10, seed=0))
            assert _db(env).wait_for_spans(1, timeout=5)
            stored = _db(env).span_count

            rejects0 = meter.counter(REJECTIONS_METRIC)
            inject_memory_pressure(env, on=True)
            # the frame is rejected pre-decode: not delivered, kept queued
            assert not env.send_traces_wire(synthesize_traces(10, seed=1),
                                            timeout=1.0)
            rejections = meter.counter(REJECTIONS_METRIC) - rejects0
            assert rejections > 0, "no pre-decode rejection recorded"
            assert _db(env).span_count == stored

            # the rejection metric is the HPA's scale-up signal
            assert env.autoscaler.gateway_replicas == 1
            n = env.autoscaler.observe_metrics(
                10.0, 10.0, rejections_per_pod=rejections, now=1000.0)
            assert n == 3, "rejections must trigger aggressive +2 scale-up"

            # pressure lifts; the exporter's retry delivers the held frame
            inject_memory_pressure(env, on=False)
            assert env._wire_tap.flush(timeout=10)
            assert _db(env).wait_for_spans(stored + 1, timeout=10)

    def test_config_change_hot_reloads_gateway(self):
        with E2EEnvironment(nodes=1) as env:
            env.add_destination(tracedb_dest("db1"))
            assert env.send_traces_wire(synthesize_traces(5, seed=0))
            assert _db(env, "db1").wait_for_spans(1, timeout=5)
            # adding a second destination regenerates the config; the
            # gateway hot-reloads and serves both
            env.add_destination(tracedb_dest("db2"))
            assert env.send_traces_wire(synthesize_traces(5, seed=1))
            assert _db(env, "db2").wait_for_spans(1, timeout=5)
