"""The one general load generator: a traffic file's parameters in, frames
over the collector's wire receiver out, arrivals stamped at the terminal
exporter.

Two schedules. ``closed``: senders keep a fixed number of frames in
flight (sent and not yet arrived at the exporter) and send the next the
moment one arrives; a frame is due when it is sent. ``open``: frame k is
due at a time fixed by the file's rate, whether or not earlier frames have come back; it is timed from when it
was due. Every frame is a pool frame re-keyed with its own serial number
(``gen.rekey``), so what arrives names the frame it came from.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np

from . import gen


class ArrivalSpy:
    """Stamps what a terminal exporter is handed: (host clock, batch)
    per exported batch and a running span count."""

    def __init__(self, exporter):
        self.exporter = exporter
        self.records: list[tuple[float, Any]] = []
        self.spans = 0
        self._lock = threading.Lock()
        inner = exporter.consume

        def spy(batch):
            now = time.perf_counter()
            with self._lock:
                self.records.append((now, batch))
                self.spans += len(batch)
            return inner(batch)

        exporter.consume = spy

    def reset(self) -> None:
        with self._lock:
            self.records = []
            self.spans = 0
        self.exporter.clear()


def due_offsets(traffic: dict[str, Any], seconds: float) -> np.ndarray:
    """Open loop: the due time of every frame of a window, in seconds
    from its start, at the file's ``frames_per_s``."""
    rate = float(traffic["frames_per_s"])
    return np.arange(int(np.floor(seconds * rate))) / rate


@dataclass
class Sent:
    """What the generator did in one phase."""

    serial: list[int] = field(default_factory=list)
    pool_index: list[int] = field(default_factory=list)
    due: list[float] = field(default_factory=list)     # host clock
    sent: list[float] = field(default_factory=list)    # host clock
    spans: int = 0


class LoadGenerator:
    def __init__(self, traffic: dict[str, Any], pool: list, port: int,
                 spy: ArrivalSpy, seed: int, chips: int = 1,
                 give_up_s: float = 30.0):
        self.traffic = traffic
        self.templates = [gen.to_request(f) for f in pool]
        self.sizes = [len(f) for f in pool]
        self.port = port
        self.spy = spy
        self.chips = chips
        self.give_up_s = give_up_s
        # the pool's frames in the pool's own cyclic order on every seed
        # (which frames fall into one call decides how full it packs);
        # the seed picks where the cycle starts
        self.order = np.roll(np.arange(len(pool)), -(int(seed) % len(pool)))
        self._next_serial = 1
        self._lock = threading.Lock()
        self._exporters: list = []

    # ---------------------------------------------------------- clients

    def start(self) -> None:
        from odigos_tpu.wire.client import WireExporter

        for i in range(int(self.traffic.get("senders", 4))):
            exp = WireExporter(f"otlpwire/bench-{i}", {
                "endpoint": f"127.0.0.1:{self.port}", "queue_size": 64,
                "retry_initial_s": 0.01, "retry_max_s": 0.05,
                "max_elapsed_s": self.give_up_s})
            exp.start()
            self._exporters.append(exp)

    def stop(self) -> None:
        for exp in self._exporters:
            exp.shutdown()
        self._exporters = []

    def _take(self, log: Sent, due: Optional[float]) -> tuple[int, int]:
        """Next serial and its pool frame; caller holds the lock."""
        serial = self._next_serial
        self._next_serial += 1
        idx = int(self.order[serial % len(self.order)])
        log.serial.append(serial)
        log.pool_index.append(idx)
        log.due.append(due if due is not None else time.perf_counter())
        log.sent.append(float("nan"))
        log.spans += self.sizes[idx]
        return serial, idx

    def _ship(self, exp, log: Sent, slot: int, serial: int, idx: int) -> None:
        batch = gen.rekey(self.templates[idx], serial)
        log.sent[slot] = time.perf_counter()
        exp.export(batch)

    # -------------------------------------------------------- schedules

    def closed(self, *, seconds: Optional[float] = None,
               frames: Optional[int] = None,
               in_flight_frames: Optional[int] = None) -> Sent:
        """Keep ``in_flight_frames`` frames between the senders and the
        exporter, for ``seconds`` seconds or ``frames`` frames."""
        log = Sent()
        bound = int(in_flight_frames or self.traffic["in_flight_frames"])
        bound_spans = bound * self.chips * float(np.mean(self.sizes))
        arrived0 = self.spy.spans
        t_end = time.perf_counter() + seconds if seconds else None

        def run(exp) -> None:
            while True:
                with self._lock:
                    now = time.perf_counter()
                    if (t_end is not None and now >= t_end) or (
                            frames is not None
                            and len(log.serial) >= frames):
                        return
                    room = (log.spans - (self.spy.spans - arrived0)
                            < bound_spans)
                    if room:
                        slot = len(log.serial)
                        serial, idx = self._take(log, None)
                if not room:
                    time.sleep(0.0005)
                    continue
                self._ship(exp, log, slot, serial, idx)
                while exp.queued > 1:      # sent means handed to the socket
                    time.sleep(0.0005)

        self._run_threads(run)
        return log

    def open(self, seconds: float) -> Sent:
        """Send frame k at ``t0 + due_offsets[k]``, come what may."""
        log = Sent()
        offsets = due_offsets(self.traffic, seconds)
        t0 = time.perf_counter() + 0.05
        cursor = [0]

        def run(exp) -> None:
            while True:
                with self._lock:
                    k = cursor[0]
                    if k >= len(offsets):
                        return
                    cursor[0] += 1
                    slot = len(log.serial)
                    serial, idx = self._take(log, t0 + float(offsets[k]))
                delay = log.due[slot] - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                self._ship(exp, log, slot, serial, idx)

        self._run_threads(run)
        return log

    def _run_threads(self, target) -> None:
        threads = [threading.Thread(target=target, args=(exp,), daemon=True,
                                    name=f"bench-sender-{i}")
                   for i, exp in enumerate(self._exporters)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    def window(self, seconds: float) -> Sent:
        if self.traffic["schedule"] == "closed":
            return self.closed(seconds=seconds)
        if self.traffic["schedule"] == "open":
            return self.open(seconds)
        raise ValueError(f"unknown schedule {self.traffic['schedule']!r}")

    def settle(self, want_spans: int, timeout_s: float) -> bool:
        """Wait until ``want_spans`` have arrived since the spy's last
        reset, or the timeout; flushes the clients first."""
        end = time.monotonic() + timeout_s
        for exp in self._exporters:
            exp.flush(timeout=max(0.0, end - time.monotonic()))
        while time.monotonic() < end:
            if self.spy.spans >= want_spans:
                return True
            time.sleep(0.005)
        return self.spy.spans >= want_spans
