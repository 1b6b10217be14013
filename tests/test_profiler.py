"""Continuous profiler + device-runtime telemetry + exemplars (ISSUE 3):
the always-on sampler's window ring (bounded, merge-on-demand, strict
no-op when disabled), the device-runtime collector's engine/jax gauges
(graceful on CPU), Meter exemplars end to end — engine score latency →
/metrics ``# EXEMPLAR`` → /api/selftrace?trace_id= resolution — the
/debug/tracez and /debug/profilez pages, config wiring through the
gateway render and collector lifecycle, and the diagnose bundle's merged
folded profile."""

from __future__ import annotations

import json
import re
import tarfile
import time
import urllib.request

import pytest

from odigos_tpu.features import featurize
from odigos_tpu.pdata import synthesize_traces
from odigos_tpu.selftelemetry import tracer
from odigos_tpu.selftelemetry.profiler import (
    ContinuousProfiler, DeviceRuntimeCollector, DeviceRuntimeConfig,
    ProfilerConfig, fold_stack, profiler, start_from_config, stop_started)
from odigos_tpu.serving import EngineConfig, ScoringEngine
from odigos_tpu.utils.telemetry import (
    EXEMPLAR_SLOTS, _Histogram, meter, prometheus_text)


def get_json(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return json.loads(r.read())


# --------------------------------------------------------------- profiler


class TestContinuousProfiler:
    def test_disabled_is_strict_noop(self):
        p = ContinuousProfiler()  # enabled=False default
        assert p.start() is False
        assert not p.running
        assert p.windows() == []
        assert p.folded() == []

    def test_samples_into_bounded_ring(self):
        p = ContinuousProfiler(ProfilerConfig(
            enabled=True, hz=97.0, window_s=0.1, windows=3))
        assert p.start() is True
        # run long enough to rotate well past the ring capacity
        time.sleep(1.0)
        p.stop()
        ws = p.windows()
        assert ws, "no windows sampled"
        # ring bounded: at most `windows` closed + the in-progress one
        assert len(ws) <= 4
        assert sum(w.samples for w in ws) > 0
        snap = p.snapshot()
        assert snap["windows_rotated"] > 3  # rotation really evicted

    def test_folded_lines_parse_with_module_frames(self):
        p = ContinuousProfiler(ProfilerConfig(
            enabled=True, hz=200.0, window_s=10.0, windows=2))
        p.start()
        time.sleep(0.2)
        p.stop()
        folded = p.folded()
        assert folded
        for line in folded:
            stack, n = line.rsplit(" ", 1)
            assert n.isdigit()
            # every frame carries its module: "module:name;module:name"
            assert all(":" in fr for fr in stack.split(";"))

    def test_merged_across_windows_sums_counts(self):
        from collections import Counter

        p = ContinuousProfiler(ProfilerConfig(enabled=True, windows=4))
        # inject windows directly: merge math must not need a live thread
        from odigos_tpu.selftelemetry.profiler import ProfileWindow

        for i, counts in enumerate([{"a:f;a:g": 3}, {"a:f;a:g": 2,
                                                     "b:h": 5}]):
            w = ProfileWindow(i, time.time())
            w.counts = Counter(counts)
            w.sweeps = 1
            p._ring.append(w)
        assert p.merged() == Counter({"a:f;a:g": 5, "b:h": 5})
        assert p.merged(last=1) == Counter({"a:f;a:g": 2, "b:h": 5})

    def test_stack_diversity_bounded_per_window(self):
        p = ContinuousProfiler(ProfilerConfig(
            enabled=True, max_stacks_per_window=64))
        from odigos_tpu.selftelemetry.profiler import (
            TRUNCATED_STACK, ProfileWindow)

        w = ProfileWindow(0, time.time())
        # drive the sweep's bounding rule: past the per-window stack
        # budget, novel stacks fold into the synthetic truncation bucket
        for i in range(200):
            stack = f"m:f{i}"
            if (len(w.counts) >= p.cfg.max_stacks_per_window
                    and stack not in w.counts):
                stack = TRUNCATED_STACK
            w.counts[stack] += 1
        assert len(w.counts) <= p.cfg.max_stacks_per_window + 1
        assert w.counts[TRUNCATED_STACK] == 200 - 64

    def test_fold_stack_current_frame(self):
        import sys

        frame = sys._getframe()
        stack = fold_stack(frame)
        # leaf frame is this test function, with its module attached
        assert stack.endswith("test_profiler:test_fold_stack_current_frame")

    def test_configure_refused_while_running(self):
        p = ContinuousProfiler(ProfilerConfig(enabled=True, hz=50.0))
        p.start()
        try:
            with pytest.raises(RuntimeError):
                p.configure(ProfilerConfig(enabled=True))
        finally:
            p.stop()

    def test_start_from_config_lifecycle(self):
        # absent / disabled stanza: nothing starts
        assert start_from_config(None) == []
        assert start_from_config({"profiler": {"enabled": False}}) == []
        assert not profiler.running
        started = start_from_config({
            "profiler": {"enabled": True, "hz": 50.0, "window_s": 1.0,
                         "windows": 2},
            "device_runtime": {"enabled": True, "interval_s": 0.05}})
        try:
            assert started == ["profiler", "device_runtime"]
            assert profiler.running
            from odigos_tpu.selftelemetry.profiler import device_runtime

            assert device_runtime.running
        finally:
            stop_started(started)
        assert not profiler.running


# --------------------------------------------------------- device runtime


class TestDeviceRuntimeCollector:
    @staticmethod
    def _find(out, prefix):
        hits = [k for k in out if k.startswith(prefix)]
        assert hits, f"no gauge starting with {prefix}: {sorted(out)}"
        return hits[0]

    def test_engine_gauges_published(self):
        c = DeviceRuntimeCollector()
        eng = ScoringEngine(EngineConfig(model="mock")).start()
        try:
            b = synthesize_traces(16, seed=2)
            assert eng.score_sync(b, featurize(b), timeout_s=10.0) \
                is not None
            out = c.collect_once()
            key = self._find(out, "odigos_engine_queue_depth{model=mock")
            assert meter.gauge(key) == out[key]
            assert out[self._find(
                out, "odigos_engine_pipeline_depth{model=mock")] == 1.0
            assert 0.0 <= out[self._find(
                out, "odigos_engine_window_occupancy{model=mock")] <= 1.0
        finally:
            eng.shutdown()
        # unregistered at shutdown: the next pass publishes nothing for
        # it AND clears the stale gauges it published last pass — a dead
        # engine must not serve frozen queue-depth on /metrics forever
        out2 = c.collect_once()
        assert key not in out2
        assert meter.gauge(key) is None

    def test_cpu_jax_state_graceful(self):
        # the collector reads device facts only once THIS process has
        # initialised a backend (imported is not initialised: telemetry
        # never claims the chip), so initialise the CPU one here. Then
        # live_arrays works, memory_stats is None on CPU devices — the
        # collector must not raise and must not publish device-memory
        # gauges it cannot observe
        import jax

        jax.devices()
        out = DeviceRuntimeCollector()._collect_jax()
        assert "odigos_device_live_arrays" in out
        assert not any(k.startswith("odigos_device_bytes_in_use")
                       for k in out)

    def test_jit_cache_sizes_per_site(self):
        import jax.numpy as jnp

        from odigos_tpu.models import jitstats
        from odigos_tpu.models.zscore import ZScoreDetector

        det = ZScoreDetector()
        det.state = det.update_fn(
            det.state, jnp.zeros((4, 3), jnp.int32), jnp.zeros(4))
        sizes = jitstats.cache_sizes()
        assert sizes.get("zscore.update", 0) >= 1
        out = DeviceRuntimeCollector()._collect_jax()
        assert out["odigos_jit_cache_size{site=zscore.update}"] >= 1

    def test_compile_seconds_accumulate(self):
        from odigos_tpu.models import jitstats

        jitstats.record_compile_seconds("test.site", 0.25)
        jitstats.record_compile_seconds("test.site", 0.5)
        assert jitstats.compile_seconds()["test.site"] == pytest.approx(0.75)

    def test_interval_thread_lifecycle(self):
        c = DeviceRuntimeCollector(DeviceRuntimeConfig(
            enabled=True, interval_s=0.05))
        before = meter.counter("odigos_device_runtime_collections_total")
        assert c.start()
        time.sleep(0.3)
        c.stop()
        assert meter.counter(
            "odigos_device_runtime_collections_total") > before
        # stop() clears what it published: no frozen gauges survive it
        assert meter.gauge("odigos_device_live_arrays") is None

    def test_readonly_snapshot_does_not_publish(self):
        eng = ScoringEngine(EngineConfig(model="mock")).start()
        try:
            out = DeviceRuntimeCollector().collect_once(publish=False)
            key = self._find(out, "odigos_engine_queue_depth{model=mock")
            meter.clear_gauge(key)
            out = DeviceRuntimeCollector().collect_once(publish=False)
            assert key in out  # the dict is complete...
            assert meter.gauge(key) is None  # ...but the meter untouched
        finally:
            eng.shutdown()

    def test_same_model_engines_do_not_collide(self):
        a = ScoringEngine(EngineConfig(model="mock")).start()
        b = ScoringEngine(EngineConfig(model="mock")).start()
        try:
            out = DeviceRuntimeCollector().collect_once(publish=False)
            keys = [k for k in out if k.startswith(
                "odigos_engine_queue_depth{model=mock")]
            assert len(keys) == 2, keys  # one series per live engine
        finally:
            a.shutdown()
            b.shutdown()


# -------------------------------------------------------------- exemplars


class TestExemplars:
    def test_histogram_p90_and_exact_max(self):
        h = _Histogram(max_samples=64)
        for v in range(1, 1001):
            h.record(float(v))
        snapshot_max = h.vmax
        assert snapshot_max == 1000.0  # exact even though reservoir is 64
        assert h.quantile(0.90) > h.quantile(0.50)

    def test_meter_snapshot_has_p90_and_max(self):
        meter.record("odigos_test_latency_ms", 1.0)
        meter.record("odigos_test_latency_ms", 9.0)
        snap = meter.snapshot()
        assert snap["odigos_test_latency_ms_p90"] >= 1.0
        assert snap["odigos_test_latency_ms_max"] == 9.0

    def test_max_exemplar_pinned_and_reservoir_bounded(self):
        h = _Histogram()
        for i in range(100):
            h.record(float(i), exemplar=(i + 1, i + 1))
        assert len(h.exemplars) <= EXEMPLAR_SLOTS
        # slot 0 is the exact maximum's witness
        assert h.exemplars[0].value == 99.0
        assert h.exemplars[0].trace_id == 100

    def test_exposition_exemplar_annotations(self):
        meter.record("odigos_test_exemplar_ms", 7.5,
                     exemplar=(0xABC, 0xDEF))
        text = prometheus_text(meter.snapshot(), meter.exemplars())
        lines = [ln for ln in text.splitlines()
                 if ln.startswith("# EXEMPLAR odigos_test_exemplar_ms")]
        assert lines, text[-500:]
        assert 'trace_id="00000000000000000000000000000abc"' in lines[0]
        assert lines[0].rstrip().split(" ")[-2] == "7.5"

    def test_engine_score_latency_carries_exemplar(self):
        was = tracer.enabled
        tracer.enabled = True
        eng = ScoringEngine(EngineConfig(model="mock")).start()
        try:
            b = synthesize_traces(8, seed=5)
            assert eng.score_sync(b, featurize(b), timeout_s=10.0) \
                is not None
            deadline = time.time() + 5
            while time.time() < deadline:
                exs = meter.exemplars("odigos_anomaly_score_latency_ms")
                if exs:
                    break
                time.sleep(0.01)
            assert exs, "no exemplar recorded for engine score latency"
            ex = exs["odigos_anomaly_score_latency_ms"][0]
            assert int(ex["trace_id"], 16) != 0
        finally:
            eng.shutdown()
            tracer.enabled = was

    def test_pipeline_batch_latency_carries_exemplar(self):
        from odigos_tpu.selftelemetry.instrument import TracedEntry

        was = tracer.enabled
        tracer.enabled = True
        try:
            class _Sink:
                def consume(self, batch):
                    pass

            entry = TracedEntry("traces/test", _Sink())
            entry.consume(synthesize_traces(4, seed=6))
            exs = meter.exemplars(
                "odigos_pipeline_batch_latency_ms{pipeline=traces/test}")
            assert exs, "no exemplar on the pipeline batch histogram"
        finally:
            tracer.enabled = was

    def test_tracing_disabled_is_transparent(self):
        """Disabled tracing = the documented zero-overhead contract:
        neither a span nor a latency sample is recorded."""
        from odigos_tpu.selftelemetry.instrument import TracedEntry

        was = tracer.enabled
        tracer.enabled = False
        try:
            class _Sink:
                def consume(self, batch):
                    pass

            key = "odigos_pipeline_batch_latency_ms{pipeline=traces/off}"
            TracedEntry("traces/off", _Sink()).consume(
                synthesize_traces(4, seed=6))
            count_key = ("odigos_pipeline_batch_latency_ms_count"
                         "{pipeline=traces/off}")
            assert count_key not in meter.snapshot()
            assert not meter.exemplars(key)
        finally:
            tracer.enabled = was

    def test_labeled_histogram_stat_keys_render_cleanly(self):
        """Stat suffixes join the metric NAME, not the label block —
        name{labels}_p50 would splice '_p50' into the label value at
        exposition time (review finding)."""
        key = "odigos_test_labeled_ms{pipeline=traces/in}"
        meter.record(key, 2.0)
        snap = meter.snapshot()
        assert "odigos_test_labeled_ms_p50{pipeline=traces/in}" in snap
        text = prometheus_text(snap)
        line = [ln for ln in text.splitlines()
                if ln.startswith("odigos_test_labeled_ms_p50")][0]
        assert line == 'odigos_test_labeled_ms_p50{pipeline="traces/in"} 2.0'


# ------------------------------------------------------- frontend surfaces


class TestExemplarResolution:
    @pytest.fixture
    def frontend(self):
        from odigos_tpu.api import Store
        from odigos_tpu.frontend import FrontendServer

        fe = FrontendServer(Store(), metrics_port=None).start()
        yield fe
        fe.shutdown()

    def test_metrics_exemplar_resolves_via_selftrace(self, frontend):
        """The acceptance loop: score through the engine, scrape
        /metrics, take the score-latency exemplar's trace id, resolve it
        via /api/selftrace?trace_id= to the tpu/score self-trace."""
        was = tracer.enabled
        tracer.enabled = True
        eng = ScoringEngine(EngineConfig(model="mock")).start()
        try:
            b = synthesize_traces(8, seed=7)
            assert eng.score_sync(b, featurize(b), timeout_s=10.0) \
                is not None
            body = urllib.request.urlopen(
                f"{frontend.url}/metrics", timeout=10).read().decode()
            lines = [ln for ln in body.splitlines() if ln.startswith(
                "# EXEMPLAR odigos_anomaly_score_latency_ms")]
            assert lines, "no score-latency exemplar on /metrics"
            tid = re.search(r'trace_id="([0-9a-f]{32})"', lines[-1]).group(1)
            out = get_json(f"{frontend.url}/api/selftrace?trace_id={tid}")
            assert out["found"] is True
            assert any(s["name"] == "tpu/score" for s in out["spans"])
        finally:
            eng.shutdown()
            tracer.enabled = was

    def test_selftrace_summary_lists_exemplars(self, frontend):
        meter.record("odigos_test_panel_ms", 3.0, exemplar=(0x123, 0x45))
        out = get_json(f"{frontend.url}/api/selftrace")
        assert "exemplars" in out
        hit = [e for e in out["exemplars"]
               if e["metric"] == "odigos_test_panel_ms"]
        assert hit and hit[0]["trace_id"].endswith("123")

    def test_selftrace_unknown_trace_id(self, frontend):
        out = get_json(f"{frontend.url}/api/selftrace?trace_id=deadbeef")
        assert out["found"] is False and out["spans"] == []
        out = get_json(f"{frontend.url}/api/selftrace?trace_id=zznothex")
        assert out["found"] is False


# ---------------------------------------------------------------- zpages


class TestDebugPages:
    def _ext(self, cls, name, config=None):
        ext = cls(name, dict(config or {}, port=0))
        ext.start()
        return ext

    def test_tracez_summary_and_pivot(self):
        from odigos_tpu.components.extensions.zpages import ZPagesExtension

        was = tracer.enabled
        tracer.enabled = True
        with tracer.span("tracez/demo") as sp:
            sp.set_attr("k", "v")
        ext = self._ext(ZPagesExtension, "zpages")
        try:
            out = get_json(
                f"http://127.0.0.1:{ext.port}/debug/tracez")
            row = [r for r in out["by_span"] if r["span"] == "tracez/demo"]
            assert row and row[0]["count"] >= 1
            assert row[0]["max_ms"] >= row[0]["p50_ms"] >= 0
            tid = row[0]["exemplar_trace_id"]
            detail = get_json(
                f"http://127.0.0.1:{ext.port}/debug/tracez?trace_id={tid}")
            assert detail["found"] is True
            assert any(s["name"] == "tracez/demo" for s in detail["spans"])
        finally:
            ext.shutdown()
            tracer.enabled = was

    def test_profilez_serves_ring(self):
        from odigos_tpu.components.extensions.pprofz import PprofExtension

        p = ContinuousProfiler(ProfilerConfig(
            enabled=True, hz=200.0, window_s=0.1, windows=3))
        # point the page at a local instance via the module global
        import odigos_tpu.components.extensions.pprofz as pprofz_mod

        orig = pprofz_mod.profiler
        pprofz_mod.profiler = p
        p.start()
        time.sleep(0.4)
        ext = self._ext(PprofExtension, "pprof")
        try:
            out = get_json(
                f"http://127.0.0.1:{ext.port}/debug/profilez")
            assert out["running"] is True
            assert out["folded"]
            for ln in out["folded"]:
                stack, n = ln.rsplit(" ", 1)
                assert n.isdigit() and stack
            one = get_json(
                f"http://127.0.0.1:{ext.port}/debug/profilez?window=1")
            assert one["merged_windows"] == 1
        finally:
            ext.shutdown()
            p.stop()
            pprofz_mod.profiler = orig

    def test_profilez_disabled_serves_empty_state(self):
        import odigos_tpu.components.extensions.pprofz as pprofz_mod
        from odigos_tpu.components.extensions.pprofz import PprofExtension

        orig = pprofz_mod.profiler
        pprofz_mod.profiler = ContinuousProfiler()  # disabled, never run
        ext = self._ext(PprofExtension, "pprof")
        try:
            out = get_json(
                f"http://127.0.0.1:{ext.port}/debug/profilez")
            assert out["running"] is False
            assert out["enabled"] is False
            assert out["folded"] == []
        finally:
            ext.shutdown()
            pprofz_mod.profiler = orig


# ---------------------------------------------------------- config wiring


class TestConfigWiring:
    def test_gateway_render_carries_telemetry_stanza(self):
        from odigos_tpu.config.model import SelfTelemetryConfiguration
        from odigos_tpu.pipelinegen.builder import (
            GatewayOptions, build_gateway_config)

        cfg, _status, _sig = build_gateway_config(
            [], options=GatewayOptions(
                telemetry_config=SelfTelemetryConfiguration(
                    profiler_enabled=True, profiler_hz=23.0,
                    device_runtime_enabled=True)))
        st = cfg["service"]["telemetry"]
        assert st["profiler"]["enabled"] is True
        assert st["profiler"]["hz"] == 23.0
        assert st["device_runtime"]["enabled"] is True

    def test_gateway_render_omits_stanza_when_disabled(self):
        from odigos_tpu.pipelinegen.builder import (
            GatewayOptions, build_gateway_config)

        cfg, _status, _sig = build_gateway_config(
            [], options=GatewayOptions())
        assert "telemetry" not in cfg["service"]

    def test_collector_starts_and_stops_profiler(self):
        from odigos_tpu.pipeline import Collector

        assert not profiler.running
        coll = Collector({
            "receivers": {"synthetic": {"n_batches": 0}},
            "exporters": {"debug": {"verbosity": "none"}},
            "service": {
                "pipelines": {"traces/t": {"receivers": ["synthetic"],
                                           "processors": [],
                                           "exporters": ["debug"]}},
                "telemetry": {"profiler": {
                    "enabled": True, "hz": 50.0, "window_s": 1.0,
                    "windows": 2}},
            },
        })
        coll.start()
        try:
            assert profiler.running
        finally:
            coll.shutdown()
        assert not profiler.running


# --------------------------------------------------------------- diagnose


class TestDiagnoseBundle:
    def test_bundle_contains_profile(self, tmp_path, capsys):
        from odigos_tpu.cli.commands import main

        state_dir = str(tmp_path / "state")
        assert main(["--state-dir", state_dir, "install"]) == 0
        out = str(tmp_path / "bundle.tar.gz")
        assert main(["--state-dir", state_dir, "diagnose",
                     "-o", out]) == 0
        capsys.readouterr()
        with tarfile.open(out) as tar:
            names = tar.getnames()
            assert "profiler.json" in names
            assert "profile.folded" in names
            assert "exemplars.json" in names
            assert "device_runtime.json" in names
            device = json.load(tar.extractfile("device_runtime.json"))
            # jax is loaded under pytest: the snapshot sees live arrays
            assert "odigos_device_live_arrays" in device
            folded = tar.extractfile("profile.folded").read().decode()
        # profiler off -> the on-demand fallback still sampled stacks
        lines = [ln for ln in folded.splitlines() if ln]
        assert lines, "bundle carries an empty profile"
        for ln in lines:
            stack, n = ln.rsplit(" ", 1)
            assert n.isdigit() and stack
