"""The batch processor's hold (ISSUE 36): ``timeout_s`` bounds how long a
span is held for batching on its way through the process, counted from
when it first entered a batch processor, not afresh by each one it meets.

Most cases run on a fake clock and timer laid over the module's ``time``
and ``threading`` names, so flush instants are exact; one chain runs on
short real timers.
"""

import dataclasses
import threading
import time
import types

import pytest

from odigos_tpu.components.api import Signal
from odigos_tpu.components.connectors.forward import ForwardConnector
from odigos_tpu.components.processors import batch as batch_mod
from odigos_tpu.components.processors.batch import (
    FLUSH_METRIC, FLUSH_REASONS, HELD_METRIC, HOLD_METRIC, BatchProcessor,
    batched_since)
from odigos_tpu.components.processors.traffic_metrics import (
    TrafficMetricsProcessor)
from odigos_tpu.pdata import (
    LogBatchBuilder, MetricBatchBuilder, synthesize_traces)
from odigos_tpu.utils.telemetry import labeled_key, meter
from odigos_tpu.wire.codec import encode_batch


# ------------------------------------------------------------ fake time


class FakeClock:
    """A clock that moves only when told to, and the timers armed on it."""

    def __init__(self):
        self.now = 100.0
        self.timers = []

    def monotonic(self):
        return self.now

    def Timer(self, interval, fn):  # noqa: N802 — stands in for the class
        return FakeTimer(self, self.now + interval, fn)

    def advance(self, dt):
        """Move to now + dt, firing every timer that falls due on the
        way, each at its own deadline, earliest first."""
        end = self.now + dt
        while True:
            due = [t for t in self.timers if t.deadline <= end + 1e-12]
            if not due:
                break
            t = min(due, key=lambda t: t.deadline)
            self.timers.remove(t)
            self.now = max(self.now, t.deadline)
            t.fn()
        self.now = end

    def armed(self):
        return sorted(round(t.deadline - self.now, 9) for t in self.timers)


class FakeTimer:
    daemon = False

    def __init__(self, clock, deadline, fn):
        self.clock, self.deadline, self.fn = clock, deadline, fn

    def start(self):
        self.clock.timers.append(self)

    def cancel(self):
        if self in self.clock.timers:
            self.clock.timers.remove(self)


@pytest.fixture
def clock(monkeypatch):
    c = FakeClock()
    monkeypatch.setattr(batch_mod, "time",
                        types.SimpleNamespace(monotonic=c.monotonic))
    monkeypatch.setattr(batch_mod, "threading", types.SimpleNamespace(
        Timer=c.Timer, Lock=threading.Lock))
    return c


class Sink:
    """A terminal consumer: what arrived, and when on the given clock."""

    def __init__(self, now):
        self.now = now
        self.got = []

    def consume(self, batch):
        self.got.append((round(self.now(), 9), batch))

    @property
    def times(self):
        return [t for t, _ in self.got]

    @property
    def sizes(self):
        return [len(b) for _, b in self.got]


def chain(sink, *configs, between=False):
    """Batch processors in series ending in ``sink``; with ``between``
    each pair is joined as the rendered gateway joins them: a traffic
    metrics processor, then a forward connector."""
    procs = [BatchProcessor("batch", dict(c)) for c in configs]
    for i, (a, b) in enumerate(zip(procs, procs[1:] + [sink])):
        if between and b is not sink:
            tm = TrafficMetricsProcessor(
                "odigostrafficmetrics", {"pipeline": f"p{i}"})
            fwd = ForwardConnector(f"forward/p{i}", {})
            fwd.outputs = {"next": b}
            tm.set_consumer(fwd)
            b = tm
        a.set_consumer(b)
    return procs


# ---------------------------------------------------------- the batches


def spans(n_traces=4, seed=0):
    return synthesize_traces(n_traces, seed=seed)


def metrics(n=5, seed=0):
    b = MetricBatchBuilder()
    for i in range(n):
        b.add_point(name=f"m{seed}", value=float(i), attrs={"k": i})
    return b.build()


def logs(n=5, seed=0):
    b = LogBatchBuilder()
    for i in range(n):
        b.add_record(body=f"line {seed}/{i}", attrs={"k": i})
    return b.build()


MAKERS = {"spans": spans, "metrics": metrics, "logs": logs}
signal = pytest.mark.parametrize("make", list(MAKERS.values()),
                                 ids=list(MAKERS))


def stamped(batch, since):
    """``batch`` as a batch processor that held it since ``since`` hands
    it on."""
    sink = Sink(lambda: 0.0)
    p, = chain(sink, {"timeout_s": 0})
    p.consume(batch)
    p._since = since
    p.flush()
    return sink.got[0][1]


# ------------------------------------------------------------ the chain


class TestHeldOnce:
    @signal
    @pytest.mark.parametrize("between", [False, True],
                             ids=["direct", "trafficmetrics+forward"])
    def test_sub_threshold_batches_leave_the_chain_within_one_timeout(
            self, clock, make, between):
        """Three batches, none near send_batch_size: the first processor
        times them out 0.2 s after the first entered, and the second,
        which at the parent armed 0.2 s of its own, lets them go at
        once."""
        t0 = clock.now
        sink = Sink(clock.monotonic)
        first, second = chain(sink, {}, {}, between=between)
        sent = 0
        for k in range(3):
            b = make(seed=k)
            sent += len(b)
            first.consume(b)
            clock.advance(0.05)
        clock.advance(1.0)
        assert sink.times == [round(t0 + 0.2, 9)]
        assert sink.sizes == [sent]
        assert batched_since(sink.got[0][1]) == t0
        assert first.flow_pending() == second.flow_pending() == 0
        assert clock.armed() == []

    @signal
    def test_a_batch_over_send_batch_size_passes_both_at_once(
            self, clock, make):
        t0 = clock.now
        sink = Sink(clock.monotonic)
        b = make()
        first, _ = chain(sink, {"send_batch_size": len(b)},
                         {"send_batch_size": len(b)})
        first.consume(b)
        assert sink.times == [t0] and sink.sizes == [len(b)]
        assert clock.armed() == []

    @pytest.mark.parametrize("timeouts, left_at_second", [
        ((0.2, 0.1), None),    # generic, then small-batches: used up
        ((0.1, 0.2), 0.1),     # the other way round: 0.1 s of 0.2 left
    ], ids=["200ms-then-100ms", "100ms-then-200ms"])
    def test_each_processor_counts_its_own_timeout_from_the_instant(
            self, clock, timeouts, left_at_second):
        t0 = clock.now
        sink = Sink(clock.monotonic)
        first, _ = chain(sink, *({"timeout_s": t} for t in timeouts))
        first.consume(spans())
        clock.advance(timeouts[0])
        if left_at_second is None:
            assert sink.times == [round(t0 + timeouts[0], 9)]
        else:
            assert sink.times == []
            assert clock.armed() == [left_at_second]
            clock.advance(1.0)
            assert sink.times == [round(t0 + max(timeouts), 9)]

    def test_a_rebuilt_batch_drops_the_instant_and_is_held_as_today(
            self, clock):
        """A stage between two batch processors that builds a new batch
        carries no instant over: the second arms its whole timeout."""
        t0 = clock.now
        sink = Sink(clock.monotonic)
        second, = chain(sink, {})
        rebuild = types.SimpleNamespace(
            consume=lambda b: second.consume(dataclasses.replace(b)))
        first = BatchProcessor("batch", {})
        first.set_consumer(rebuild)
        first.consume(spans())
        clock.advance(0.2)
        assert sink.times == [] and clock.armed() == [0.2]
        clock.advance(0.2)
        assert sink.times == [round(t0 + 0.4, 9)]

    def test_on_real_timers(self):
        """The same chain on threading.Timer: out within one timeout and
        some slack, where two holds would take twice the timeout."""
        timeout = 0.15
        done = threading.Event()
        sink = Sink(time.monotonic)
        inner = sink.consume
        sink.consume = lambda b: (inner(b), done.set())
        first, second = chain(sink, {"timeout_s": timeout},
                              {"timeout_s": timeout})
        t0 = time.monotonic()
        first.consume(spans())
        assert done.wait(5.0), "nothing reached the sink"
        held = sink.times[0] - t0
        assert timeout * 0.9 <= held < timeout * 1.7, held
        first.shutdown()
        second.shutdown()


# ----------------------------------------------------- a processor alone


def parent_rule(events, size=8192, timeout=0.2):
    """The parent commit's rule, as a reference: flush when the pending
    spans reach ``size``, or ``timeout`` after the batch that opened the
    buffer. ``events`` are (time, spans); returns (time, spans) flushed."""
    out, pending, opened = [], 0, None
    for t, n in events:
        if opened is not None and t >= opened + timeout - 1e-12:
            out.append((round(opened + timeout, 9), pending))
            pending, opened = 0, None
        pending += n
        if pending >= size:
            out.append((round(t, 9), pending))
            pending, opened = 0, None
        elif opened is None:
            opened = t
    if opened is not None:
        out.append((round(opened + timeout, 9), pending))
    return out


class TestOneProcessorIsUnchanged:
    SCRIPTS = {
        # offsets in seconds from the first batch, traces a batch
        "one batch": [(0.0, 3)],
        "a burst then quiet": [(0.0, 3), (0.037, 3), (0.074, 3)],
        "bursts a step apart": [(0.0, 3), (0.037, 2), (0.15, 3),
                                (0.187, 3), (0.30, 2), (0.337, 3)],
        "a join at the deadline": [(0.0, 2), (0.2, 2), (0.25, 2)],
        "quiet longer than the timeout": [(0.0, 2), (0.5, 2), (1.2, 4)],
    }

    @pytest.mark.parametrize("script", list(SCRIPTS.values()),
                             ids=list(SCRIPTS))
    @pytest.mark.parametrize("size", [8192, 60], ids=["timer", "size"])
    def test_flushes_at_the_parents_instants(self, clock, script, size):
        """No batch carries an instant, so nothing is different: the
        flush instants and sizes are the parent rule's, to the tick."""
        t0 = clock.now
        sink = Sink(clock.monotonic)
        p, = chain(sink, {"send_batch_size": size})
        events = []
        for k, (at, n_traces) in enumerate(script):
            clock.advance(t0 + at - clock.now)
            b = spans(n_traces, seed=k)
            events.append((t0 + at, len(b)))
            p.consume(b)
        clock.advance(5.0)
        assert list(zip(sink.times, sink.sizes)) == parent_rule(
            events, size=size)

    def test_a_batch_from_a_non_batch_source_arms_the_whole_timeout(
            self, clock):
        sink = Sink(clock.monotonic)
        p, = chain(sink, {})
        b = spans()
        assert batched_since(b) is None
        p.consume(b)
        assert clock.armed() == [0.2]
        # and it is handed on stamped, while the caller's object, which
        # its other consumers may hold, stays as it came
        clock.advance(0.2)
        out = sink.got[0][1]
        assert batched_since(out) == clock.now - 0.2
        assert out is not b and batched_since(b) is None
        assert out.columns is b.columns

    def test_timeout_zero_means_no_timer(self, clock):
        sink = Sink(clock.monotonic)
        p, = chain(sink, {"timeout_s": 0})
        p.consume(stamped(spans(), clock.now - 10.0))
        p.consume(spans(seed=1))
        clock.advance(10.0)
        assert sink.got == [] and clock.armed() == []
        p.flush()
        assert len(sink.got) == 1


class TestTheInstant:
    @signal
    def test_the_merge_carries_the_earliest(self, clock, make):
        sink = Sink(clock.monotonic)
        p, = chain(sink, {"timeout_s": 0})
        t = clock.now
        p.consume(stamped(make(seed=0), t - 0.05))
        p.consume(make(seed=1))                      # consumed at t
        p.consume(stamped(make(seed=2), t - 0.12))
        p.consume(stamped(make(seed=3), t - 0.01))
        p.flush()
        (_, out), = sink.got
        assert batched_since(out) == t - 0.12
        assert len(out) == sum(len(make(seed=k)) for k in range(4))

    def test_an_instant_ahead_of_the_clock_is_the_moment_of_consume(
            self, clock):
        sink = Sink(clock.monotonic)
        p, = chain(sink, {})
        p.consume(stamped(spans(), clock.now + 5.0))
        assert clock.armed() == [0.2]

    def test_an_older_batch_pulls_the_deadline_in(self, clock):
        t0 = clock.now
        sink = Sink(clock.monotonic)
        p, = chain(sink, {})
        p.consume(spans())
        clock.advance(0.05)
        assert clock.armed() == [0.15]
        p.consume(stamped(spans(seed=1), t0 - 0.1))   # 0.15 s old
        assert clock.armed() == [0.05]
        p.consume(stamped(spans(seed=2), t0))         # younger: no move
        assert clock.armed() == [0.05]
        clock.advance(0.05)
        assert sink.times == [round(t0 + 0.1, 9)]
        assert batched_since(sink.got[0][1]) == t0 - 0.1

    def test_a_used_up_batch_takes_the_open_buffer_with_it(self, clock):
        sink = Sink(clock.monotonic)
        p, = chain(sink, {})
        a, b = spans(), spans(seed=1)
        p.consume(a)
        clock.advance(0.05)
        p.consume(stamped(b, clock.now - 0.25))
        assert sink.sizes == [len(a) + len(b)]
        assert clock.armed() == []

    def test_the_pieces_of_a_split_carry_it(self, clock):
        sink = Sink(clock.monotonic)
        b = spans(8)
        p, = chain(sink, {"send_batch_size": len(b),
                          "send_batch_max_size": len(b) // 3 + 1})
        p.consume(stamped(b, clock.now - 0.07))
        assert len(sink.got) == 3 and sum(sink.sizes) == len(b)
        assert {batched_since(x) for _, x in sink.got} == {clock.now - 0.07}

    @signal
    def test_invisible_to_an_exporter(self, make):
        """Not a field, a column or an attribute; equal, printed and
        encoded for the wire as the same batch without it."""
        plain = make()
        held = stamped(make(), 123.456)
        assert batched_since(held) == 123.456
        assert batched_since(plain) is None
        assert "_batched_since" not in {
            f.name for f in dataclasses.fields(held)}
        assert "_batched_since" not in held.columns
        assert repr(held) == repr(plain)
        assert dataclasses.asdict(held).keys() == \
            dataclasses.asdict(plain).keys()
        assert encode_batch(held) == encode_batch(plain)
        assert "123.456" not in repr(held.attrs().vals)
        # and a copy made the way every transform makes one drops it
        assert batched_since(dataclasses.replace(held)) is None
        assert batched_since(held.slice(0, 2)) is None


class TestReconfigure:
    def test_rearms_by_what_is_left(self, clock):
        t0 = clock.now
        sink = Sink(clock.monotonic)
        p, = chain(sink, {"timeout_s": 0.2})
        p.consume(spans())
        clock.advance(0.05)
        p.reconfigure({"timeout_s": 0.5})
        assert clock.armed() == [0.45]
        p.reconfigure({"timeout_s": 0.1})
        assert clock.armed() == [0.05]
        clock.advance(0.05)
        assert sink.times == [round(t0 + 0.1, 9)]

    def test_nothing_left_flushes_at_once_and_zero_disarms(self, clock):
        sink = Sink(clock.monotonic)
        p, = chain(sink, {"timeout_s": 0.5})
        p.consume(spans())
        clock.advance(0.3)
        p.reconfigure({"timeout_s": 0})
        assert clock.armed() == [] and sink.got == []
        p.reconfigure({"timeout_s": 0.2})
        assert len(sink.got) == 1 and clock.armed() == []

    def test_a_shrunk_size_still_flushes_by_size(self, clock):
        sink = Sink(clock.monotonic)
        p, = chain(sink, {})
        b = spans()
        p.consume(b)
        p.reconfigure({"send_batch_size": len(b)})
        assert sink.sizes == [len(b)] and clock.armed() == []


class TestFlushCounter:
    @staticmethod
    def counts(name, pipeline="(none)"):
        snap = meter.snapshot()
        return {r: snap.get(labeled_key(
            FLUSH_METRIC, processor=name, pipeline=pipeline, reason=r), 0.0)
            for r in FLUSH_REASONS}

    def test_each_reason_counts_once(self, clock):
        before = self.counts("batch/counted")
        sink = Sink(clock.monotonic)
        a, b = spans(), spans(seed=1)
        p = BatchProcessor("batch/counted",
                           {"send_batch_size": len(a) + len(b)})
        p.set_consumer(sink)
        p.consume(a)
        p.consume(b)                                  # size
        p.consume(spans(seed=2))
        clock.advance(0.2)                            # timeout
        p.consume(stamped(spans(seed=3), clock.now - 0.25))  # inherited
        p.consume(spans(seed=4))
        p.flush()                                     # a drain: no count
        assert len(sink.got) == 4
        after = self.counts("batch/counted")
        assert {r: after[r] - before[r] for r in FLUSH_REASONS} == {
            "size": 1.0, "timeout": 1.0, "inherited": 1.0}

    def test_the_series_names_the_pipeline(self, clock):
        """Every generated pipeline's last stage is called `batch`: the
        graph's stamp keeps their series apart."""
        p = BatchProcessor("batch", {})
        p._flow_site = ("traces/tracedb-all", "batch", "traces")
        p.set_consumer(Sink(clock.monotonic))
        before = self.counts("batch", "traces/tracedb-all")
        p.consume(stamped(spans(), clock.now - 1.0))
        after = self.counts("batch", "traces/tracedb-all")
        assert after["inherited"] - before["inherited"] == 1.0
        assert after["timeout"] == before["timeout"]


class TestHoldCounter:
    """Each counted flush adds, for every batch it takes, the batch's
    spans times the wall from its consume here to the flush."""

    @staticmethod
    def read(name, pipeline="(none)"):
        snap = meter.snapshot()
        return tuple(snap.get(labeled_key(m, processor=name,
                                          pipeline=pipeline), 0.0)
                     for m in (HOLD_METRIC, HELD_METRIC))

    def moved(self, before, name, pipeline="(none)"):
        after = self.read(name, pipeline)
        return after[0] - before[0], after[1] - before[1]

    def test_size_timeout_and_inherited_flushes(self, clock):
        name = "batch/hold"
        sink = Sink(clock.monotonic)
        a, b = spans(), spans(seed=1)
        p = BatchProcessor(name, {"send_batch_size": len(a) + len(b)})
        p.set_consumer(sink)
        before = self.read(name)
        p.consume(a)
        clock.advance(0.05)
        p.consume(b)                            # size: a held 50 ms, b 0
        hold, held = self.moved(before, name)
        assert hold == pytest.approx(len(a) * 50.0)
        assert held == len(a) + len(b)
        before = self.read(name)
        c = spans(seed=2)
        p.consume(c)
        clock.advance(0.3)                      # timeout at 0.2
        hold, held = self.moved(before, name)
        assert hold == pytest.approx(len(c) * 200.0) and held == len(c)
        before = self.read(name)
        d = stamped(spans(seed=3), clock.now - 0.25)
        p.consume(d)                            # inherited: let go at once
        assert self.moved(before, name) == (0.0, len(d))
        assert len(sink.got) == 3

    def test_a_drain_is_not_counted(self, clock):
        name = "batch/hold-drain"
        p = BatchProcessor(name, {})
        p.set_consumer(Sink(clock.monotonic))
        before = self.read(name)
        p.consume(spans())
        clock.advance(0.1)
        p.flush()
        assert self.moved(before, name) == (0.0, 0.0)

    def test_two_in_series_count_each_wait_once(self, clock):
        """The first holds three batches 0.2, 0.15 and 0.1 s; the second
        lets their merge go at once (inherited) and counts no wait of
        its own, though it holds the same spans."""
        sink = Sink(clock.monotonic)
        first, second = chain(sink, {}, {})
        first._flow_site = ("traces/first", "batch", "traces")
        second._flow_site = ("traces/second", "batch", "traces")
        before = [self.read("batch", p) for p in ("traces/first",
                                                  "traces/second")]
        sizes = []
        for k in range(3):
            b = spans(seed=k)
            sizes.append(len(b))
            first.consume(b)
            clock.advance(0.05)
        clock.advance(1.0)
        assert sink.sizes == [sum(sizes)]
        hold1, held1 = self.moved(before[0], "batch", "traces/first")
        hold2, held2 = self.moved(before[1], "batch", "traces/second")
        assert hold1 == pytest.approx(
            sizes[0] * 200.0 + sizes[1] * 150.0 + sizes[2] * 100.0)
        assert held1 == held2 == sum(sizes)
        assert hold2 == 0.0


class TestRenderedGateway:
    def test_both_pipelines_of_the_benchmarks_path_start_with_batch(self):
        """The topology stays the reference's (benchmark/run.py
        render_config: two trace-db destinations, two streams): a
        `batch` first in the data-stream pipeline and in the destination
        pipeline, both on the defaults."""
        from odigos_tpu.config.model import AnomalyStageConfiguration
        from odigos_tpu.destinations import Destination
        from odigos_tpu.pipelinegen import (
            DataStream, DataStreamDestination, GatewayOptions,
            build_gateway_config)

        dests = [Destination(id=d, dest_type="tracedb",
                             signals=[Signal.TRACES], config={})
                 for d in ("all", "flagged")]
        streams = [
            DataStream("default", (DataStreamDestination("all"),)),
            DataStream("anomalies", (DataStreamDestination("flagged"),))]
        anomaly = AnomalyStageConfiguration(
            enabled=True, model="transformer", fast_path=True,
            timeout_ms=8000.0, threshold=0.0, devices=1)
        config, statuses, _ = build_gateway_config(
            dests, data_streams=streams,
            options=GatewayOptions(anomaly=anomaly))
        assert not any(statuses.destination.values())
        pipes = config["service"]["pipelines"]
        for name in ("traces/default", "traces/tracedb-all"):
            assert pipes[name]["processors"][0] == "batch", name
        assert config["processors"]["batch"] == {}
        assert "forward/traces/tracedb-all" in \
            pipes["traces/default"]["exporters"]
        assert pipes["traces/tracedb-all"]["receivers"] == [
            "forward/traces/tracedb-all"]
