"""The host's pauses on the device trace's clock (``benchmark/hostpauses``):
collections, the harvest's wait and copy, and the batch processors' hold,
on hand-made planes as ``benchmark/tests/test_hosttrace.py`` builds them,
on a real profiler trace here on the CPU, and on the counters of two
batch processors in series. What was there reads as it did with the new
events in the trace."""

from __future__ import annotations

import dataclasses
import gc

import jax
import pytest

from benchmark import hostpauses, hosttrace, observe
from benchmark.hosttrace import Event, Line, Plane
from benchmark.tests import test_hosttrace
from benchmark.tests.test_hosttrace import FOLD, ann
from odigos_tpu.utils.telemetry import labeled_key
from tests.test_batch_processor import Sink, chain, clock, spans  # noqa: F401


def with_pauses(gcs=True, ready=True):
    """``test_hosttrace.planes()`` with call 1's harvest 30 ms longer
    (its run ends 6.6, its harvest 6.65), each harvest's wait for the
    device (call 0's readiness 10 ms after its run ended, call 1's 10
    ms), and two collections: one of 30 ms inside the chip's one idle
    stretch (3.5-3.6) on the engine's line, one of 10 ms during a run on
    the janitor's."""
    dev, host = test_hosttrace.planes()
    engine = host.lines[0]
    for i, e in enumerate(engine.events):
        if e.name == "engine/harvest" and e.args["call"] == 1:
            engine.events[i] = ann("engine/harvest", 3.62, 3.03, call=1)
        elif e.name == "engine/scatter" and e.args["call"] == 1:
            engine.events[i] = ann("engine/scatter", 6.65, 0.05, call=1)
    if ready:
        engine.events += [ann("engine/ready", 2.1, 1.41, call=0),
                          ann("engine/ready", 3.62, 2.99, call=1)]
    if gcs:
        engine.events.append(ann("python/gc", 3.54, 0.03, generation=2,
                                 collected=120))
        host.lines.append(Line("gc-janitor", [
            ann("python/gc", 5.0, 0.01, generation=0, collected=3)]))
    return [dev, host]


def test_collections_summed_and_overlapped_with_the_idle():
    got = hostpauses.reduce(with_pauses(), 10.0)
    assert got.gc_s == pytest.approx(0.04)
    assert got.gc_ms_per_s == pytest.approx(4.0)
    # the chip idles 3.5-3.6; the collection covers 3.54-3.57 of it
    assert got.idle_gc_s == pytest.approx(0.03)
    assert got.idle_gc_share == pytest.approx(0.3)


def test_the_latest_harvest_and_its_split():
    got = hostpauses.reduce(with_pauses(), 10.0)
    assert got.late_serial == 1
    assert got.late_max_ms == pytest.approx(50.0)
    assert got.late_elsewhere_ms == pytest.approx(0.0)
    assert got.late_ready_ms == pytest.approx(10.0)
    assert got.late_copy_ms == pytest.approx(40.0)
    assert "call 1" in got.describe() and "copy 40.000" in got.describe()


def test_a_worker_that_came_late_is_not_a_late_result():
    """Call 1's harvest begins 30 ms after its run ended (the worker was
    elsewhere); its wait then takes 5 ms and its copy 15: the three
    parts sum to the 50 ms, and only 5 of them are the device's."""
    planes = with_pauses()
    engine = planes[1].lines[0]
    for i, e in enumerate(engine.events):
        if e.args.get("call") == 1 and e.name == "engine/harvest":
            engine.events[i] = ann("engine/harvest", 6.63, 0.02, call=1)
        elif e.args.get("call") == 1 and e.name == "engine/ready":
            engine.events[i] = ann("engine/ready", 6.63, 0.005, call=1)
    got = hostpauses.reduce(planes, 10.0)
    assert got.late_serial == 1 and got.late_max_ms == pytest.approx(50.0)
    assert got.late_elsewhere_ms == pytest.approx(30.0)
    assert got.late_ready_ms == pytest.approx(5.0)
    assert got.late_copy_ms == pytest.approx(15.0)


@pytest.mark.parametrize("gcs,ready", [(False, True), (True, False),
                                       (False, False)])
def test_none_where_its_events_are_absent(gcs, ready):
    got = hostpauses.reduce(with_pauses(gcs=gcs, ready=ready), 10.0)
    assert (got.gc_s is None) is not gcs
    assert (got.idle_gc_s is None) is not gcs
    assert (got.gc_ms_per_s is None) is not gcs
    assert (got.late_ready_ms is None) is not ready
    assert (got.late_elsewhere_ms is None) is not ready
    assert (got.late_copy_ms is None) is not ready
    # the harvest's lateness needs no engine/ready: the parent has it too
    assert got.late_max_ms == pytest.approx(50.0)


def test_nothing_without_a_device_run_or_an_engine_call():
    dev, host = with_pauses()
    assert hostpauses.reduce([host], 10.0) is None
    bare = Plane(host.name, [Line("gc-janitor", [
        Event("python/gc", 1.0, 0.01, {"generation": 0})])])
    assert hostpauses.reduce([dev, bare], 10.0) is None


def test_the_window_defaults_to_first_to_last_run():
    got = hostpauses.reduce(with_pauses())
    assert got.window_s == pytest.approx(6.6 - 1.5)


def test_the_joined_reduction_reads_as_it_did():
    """``hosttrace.load`` keeps every ``engine/*`` event, so the new
    ``engine/ready`` reaches ``hosttrace.reduce``, and ``python/gc`` may
    one day: with both in the trace every field of the reduction, and so
    every reader of it, reads what it reads without them."""
    base = hosttrace.reduce(with_pauses(gcs=False, ready=False), 10.0, FOLD)
    with_both = hosttrace.reduce(with_pauses(), 10.0, FOLD)
    assert dataclasses.asdict(with_both) == dataclasses.asdict(base)
    obs = type("Obs", (), {"host": with_both})()
    for name in ("device_step_ms.backlog", "device_queue_ms.steady",
                 "fetch_ms.backlog", "device_idle_host.steady"):
        read = observe.load_reader(name)
        assert read(obs) == read(type("Obs", (), {"host": base})())


# ---------------------------------------------------------- the counters


def key(metric, pipeline, processor="batch"):
    return labeled_key(metric, processor=processor, pipeline=pipeline)


def test_the_mean_hold_sums_the_processors_on_the_way():
    counters = {
        key(hostpauses.HOLD, "traces/default"): 150_000.0,
        key(hostpauses.HELD, "traces/default"): 2_000.0,       # 75 ms
        key(hostpauses.HOLD, "traces/tracedb-all"): 10_000.0,
        key(hostpauses.HELD, "traces/tracedb-all"): 2_000.0,   # 5 ms
        # the flagged stream's destination: not on the way to `all`
        key(hostpauses.HOLD, "traces/tracedb-flagged"): 9e9,
        key(hostpauses.HELD, "traces/tracedb-flagged"): 1.0,
        "odigos_batch_flush_total{processor=batch,pipeline="
        "traces/default,reason=size}": 7.0}
    assert hostpauses.hold_ms(counters) == pytest.approx(80.0)
    assert hostpauses.hold_ms(counters, ("traces/default",)) \
        == pytest.approx(75.0)


def test_no_hold_where_nothing_was_held():
    assert hostpauses.hold_ms({}) is None
    assert hostpauses.hold_ms({
        key(hostpauses.HOLD, "traces/default"): 0.0,
        key(hostpauses.HELD, "traces/default"): 0.0}) is None


def test_two_processors_in_series_read_one_wait(clock):
    """The counters as two real ``batch`` processors in series leave
    them (``tests/test_batch_processor.py``'s fake clock): the first
    holds two batches 0.2 and 0.1 s, the second none, so the mean hold
    a span paid is the first's."""
    from odigos_tpu.components.processors.batch import (HELD_METRIC,
                                                        HOLD_METRIC)
    from odigos_tpu.utils.telemetry import meter

    assert (HOLD_METRIC, HELD_METRIC) == (hostpauses.HOLD, hostpauses.HELD)
    pipes = ("traces/hold-first", "traces/hold-second")
    procs = chain(Sink(clock.monotonic), {}, {})
    for p, name in zip(procs, pipes):
        p._flow_site = (name, "batch", "traces")
    before = meter.snapshot()
    a, b = spans(seed=1), spans(seed=2)
    procs[0].consume(a)
    clock.advance(0.1)
    procs[0].consume(b)
    clock.advance(1.0)
    moved = {k: v - before.get(k, 0.0) for k, v in meter.snapshot().items()
             if k.startswith(("odigos_batch_hold", "odigos_batch_held"))}
    want = (len(a) * 200.0 + len(b) * 100.0) / (len(a) + len(b))
    assert hostpauses.hold_ms(moved, pipes) == pytest.approx(want)


# ------------------------------------------------------- a real trace


def test_load_keeps_the_collections_and_the_engines_events(tmp_path):
    from odigos_tpu.selftelemetry.latency import annotate
    from odigos_tpu.serving.gcisolation import GcPlane

    plane = GcPlane()
    saved = gc.callbacks[:]
    gc.callbacks[:] = [plane._gc_callback]
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            with annotate("engine/harvest", call=4):
                with annotate("engine/ready", call=4):
                    gc.collect(1)
        finally:
            jax.profiler.stop_trace()
    finally:
        gc.callbacks[:] = saved
    planes = hostpauses.load(str(tmp_path))
    names = [e.name for p in planes for ln in p.lines for e in ln.events]
    assert "python/gc" in names
    assert {"engine/harvest", "engine/ready"} <= set(names)
    ready = [e for p in planes for ln in p.lines for e in ln.events
             if e.name == "engine/ready"]
    assert ready[0].args["call"] == 4
    # the CPU's trace has no TPU plane: nothing to reduce
    assert hostpauses.reduce(planes, 1.0) is None
