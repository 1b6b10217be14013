"""GC isolation (ISSUE 12): the paced janitor owns collections, the
memory_limiter's release path no longer carries an inline collect,
pauses land in the odigos_gc_pause_ms histogram, and freeze/threshold
posture engages and restores cleanly."""

from __future__ import annotations

import contextlib
import gc
import threading
import time

import pytest

from odigos_tpu.components.processors.memory_limiter import (
    MemoryLimiterProcessor)
from odigos_tpu.pdata import synthesize_traces
from odigos_tpu.pipeline.service import Collector
from odigos_tpu.serving.gcisolation import (
    DEFAULT_THRESHOLDS, GcPlane, gc_plane, validate_gc_config)
from odigos_tpu.utils.telemetry import meter


def wait_for(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return cond()


class TestJanitor:
    def test_paced_collects_and_pause_histogram(self):
        plane = GcPlane()
        plane.start({"janitor_interval_s": 0.02})
        try:
            assert wait_for(lambda: plane.stats()["janitor_collects"] >= 3)
            s = plane.stats()
            assert s["running"]
            assert s["pauses"] >= s["janitor_collects"] - 1
            assert s["pause_ms_max"] >= 0.0
            # pauses drained into the labeled histogram
            assert wait_for(lambda: any(
                k.startswith("odigos_gc_pause_ms_count{")
                for k in meter.snapshot()))
        finally:
            plane.stop()
        assert not plane.stats()["running"]

    def test_hint_wakes_the_janitor(self):
        plane = GcPlane()
        plane.start({"janitor_interval_s": 30.0})  # pacing alone: never
        try:
            before = plane.stats()["janitor_collects"]
            plane.hint()
            assert wait_for(
                lambda: plane.stats()["janitor_collects"] > before)
            assert plane.stats()["hints"] == 1
        finally:
            plane.stop()

    def test_refcounted_start_stop(self):
        plane = GcPlane()
        plane.start()
        plane.start()
        plane.stop()
        assert plane.stats()["running"]  # one holder remains
        plane.stop()
        assert not plane.stats()["running"]

    def test_gen1_cadence(self):
        plane = GcPlane()
        plane.gen1_every = 2
        plane.start({"janitor_interval_s": 0.01, "gen1_every": 2})
        try:
            assert wait_for(lambda: plane.stats()["janitor_collects"] >= 4)
        finally:
            plane.stop()


class TestEngageDisengage:
    def test_thresholds_set_and_restored(self):
        plane = GcPlane()
        saved = gc.get_threshold()
        try:
            plane.engage(thresholds=(50_000, 15, 15))
            assert gc.get_threshold() == (50_000, 15, 15)
            plane.disengage()
            assert gc.get_threshold() == saved
        finally:
            gc.set_threshold(*saved)

    def test_freeze_and_unfreeze(self):
        plane = GcPlane()
        saved = gc.get_threshold()
        try:
            plane.engage(freeze=True)
            assert gc.get_threshold() == DEFAULT_THRESHOLDS
            assert plane.stats()["frozen"]
            assert plane.stats()["frozen_objects"] > 0
            plane.disengage()
            assert not plane.stats()["frozen"]
            assert gc.get_freeze_count() == 0
            assert gc.get_threshold() == saved
        finally:
            gc.unfreeze()
            gc.set_threshold(*saved)

    def test_validate_gc_config(self):
        assert validate_gc_config({}) == []
        assert validate_gc_config(
            {"janitor_interval_s": 0.5, "freeze": True,
             "thresholds": [1000, 10, 10], "gen1_every": 4}) == []
        assert validate_gc_config("nope")
        assert validate_gc_config({"typo_knob": 1})
        assert validate_gc_config({"janitor_interval_s": 0})
        assert validate_gc_config({"freeze": "yes"})
        assert validate_gc_config({"thresholds": [0, 1]})
        assert validate_gc_config({"gen1_every": 0})

    def test_bad_stanza_dies_at_graph_validation(self):
        from odigos_tpu.pipeline.graph import validate_config

        cfg = {"receivers": {"synthetic": {}},
               "exporters": {"tracedb": {}},
               "service": {"gc": {"freese": True},
                           "pipelines": {"traces/in": {
                               "receivers": ["synthetic"],
                               "exporters": ["tracedb"]}}}}
        problems = validate_config(cfg)
        assert any("service.gc" in p for p in problems)


class TestMemoryLimiterHotPath:
    """The ISSUE 12 satellite regression: the soft-limit path must HINT
    the janitor, never run gc.collect inline on the consume thread."""

    def _limiter(self, soak_next=None):
        class Next:
            def consume(self, b):
                if soak_next:
                    soak_next(b)

        p = MemoryLimiterProcessor(
            "memory_limiter", {"limit_mib": 1,
                               "spike_limit_fraction": 0.99})
        p.next_consumer = Next()
        return p

    def test_no_inline_collect_on_consume(self, monkeypatch):
        collect_threads = []
        real_collect = gc.collect

        def spy(gen=2):
            collect_threads.append(threading.current_thread().name)
            return real_collect(gen)

        monkeypatch.setattr(gc, "collect", spy)
        hints_before = gc_plane._hints
        p = self._limiter()
        # soft limit = 1 MiB * 0.01: any real batch crosses it
        b = synthesize_traces(64, seed=1)
        p.consume(b)
        assert gc_plane._hints == hints_before + 1
        # the consume thread itself never collected (threshold-triggered
        # collections by OTHER threads are fine; this thread's frame is
        # what the waterfall measures)
        me = threading.current_thread().name
        assert me not in collect_threads

    def test_hint_lands_on_janitor_thread(self):
        plane_hints = gc_plane._hints
        gc_plane.start({"janitor_interval_s": 5.0})
        try:
            before = gc_plane.stats()["janitor_collects"]
            p = self._limiter()
            p.consume(synthesize_traces(64, seed=2))
            assert gc_plane._hints > plane_hints
            assert wait_for(
                lambda: gc_plane.stats()["janitor_collects"] > before)
        finally:
            gc_plane.stop()

    def test_rejection_path_unchanged(self):
        p = MemoryLimiterProcessor(
            "memory_limiter", {"limit_mib": 0})
        p.next_consumer = None
        from odigos_tpu.components.processors.memory_limiter import (
            MemoryLimiterError)

        with pytest.raises(MemoryLimiterError):
            p.consume(synthesize_traces(8, seed=3))


class TestCollectorLifecycle:
    CFG = {
        "receivers": {"synthetic": {"traces_per_batch": 2,
                                    "n_batches": 1}},
        "exporters": {"tracedb": {}},
        "service": {"gc": {"janitor_interval_s": 0.05,
                           "thresholds": [50_000, 25, 25]},
                    "pipelines": {"traces/in": {
                        "receivers": ["synthetic"],
                        "exporters": ["tracedb"]}}},
    }

    def test_collector_runs_janitor_and_restores(self):
        saved = gc.get_threshold()
        collector = Collector(self.CFG).start()
        try:
            assert gc_plane.stats()["running"]
            assert gc.get_threshold() == (50_000, 25, 25)
        finally:
            collector.shutdown()
            gc.set_threshold(*saved)
        assert gc.get_threshold() == saved

    def test_janitor_runs_even_without_stanza(self):
        cfg = {k: v for k, v in self.CFG.items() if k != "service"}
        cfg["service"] = {"pipelines":
                          self.CFG["service"]["pipelines"]}
        collector = Collector(cfg).start()
        try:
            assert gc_plane.stats()["running"]
        finally:
            collector.shutdown()


class TestCollectionsOnTheProfilersClock:
    """Every collection is a ``python/gc`` annotation on the thread that
    ran it, opened and closed by the callback that times it, which still
    touches neither the meter nor a lock."""

    @staticmethod
    @contextlib.contextmanager
    def alone(plane):
        """``plane``'s callback as the interpreter's only one (earlier
        planes of this process left theirs installed)."""
        saved = gc.callbacks[:]
        gc.callbacks[:] = [plane._gc_callback]
        try:
            yield
        finally:
            gc.callbacks[:] = saved

    def test_a_collection_is_one_event_on_the_collecting_threads_line(
            self, tmp_path):
        import jax

        from odigos_tpu.selftelemetry.latency import annotate
        from tests.test_traceannotations import trace_events

        plane = GcPlane()
        with self.alone(plane):
            jax.profiler.start_trace(str(tmp_path))
            try:
                with annotate("engine/pack", call=7):
                    gc.collect(2)
            finally:
                jax.profiler.stop_trace()
        events = trace_events(str(tmp_path))
        (line, _, start, _), = [e for e in events if e[1] == "engine/pack"]
        full = [(ln, s, args) for ln, name, s, args in events
                if name == "python/gc" and args["generation"] == 2]
        assert len(full) == 1
        gc_line, gc_start, args = full[0]
        assert gc_line == line and gc_start >= start
        assert args["collected"] >= 0
        assert plane._span is None and plane.stats()["pauses"] >= 1

    def test_the_callback_touches_no_meter(self, monkeypatch):
        from odigos_tpu.serving import gcisolation

        touched = []

        class Tripwire:
            def __getattr__(self, name):
                touched.append(name)
                raise AssertionError(f"meter.{name} from the gc callback")

        monkeypatch.setattr(gcisolation, "meter", Tripwire())
        plane = GcPlane()
        with self.alone(plane):
            gc.collect()
        assert touched == []
        assert plane.stats()["pauses"] >= 1 and plane._span is None
        assert plane._pending
