"""Retirement lanes: the completion-driven back half of the ingest fast
path (ISSUE 9).

PR 8's stage waterfall made the fast path's own bottleneck legible: one
serial forwarder thread doing wait→tag→forward per frame put a 172 ms
mean `wait` stage in front of a 0.04 ms device — pure head-of-line
blocking, 1.7× the whole admission budget. This module removes the
line: frames become retirable the instant the engine's done-callback
(or the deadline timer) fires, and a small pool of lanes overlaps the
tag and forward work of INDEPENDENT frames instead of serializing it
behind whichever frame happens to be oldest.

Two pieces, both deliberately generic over an opaque frame object so
the fast path owns all per-frame semantics (clocks, ledger accounting,
expiry blame):

* :class:`RetirementLanes` — N worker threads fed by a ready deque.
  ``push()`` is called from completion contexts (engine worker, expiry
  timer); the next idle lane runs the retire function. A retire that
  raises is counted, never lane-fatal.
* :class:`OrderedGate` — the ``ordered: true`` contract: lanes still
  pick up, merge, and tag concurrently, but downstream ``consume``
  happens strictly in frame-sequence order, so the output byte stream
  is identical to the old single-forwarder FIFO. The gate is
  NON-BLOCKING by design: a lane offering an out-of-turn frame parks
  it and frees itself instead of waiting. A blocking turnstile
  deadlocks the pool — when frames complete out of intake order, all
  N lanes can be holding later frames, each waiting for the head,
  while the head frame sits in the ready queue with no lane left to
  retire it.

The hygiene lint (``TestFastPathHygiene``) covers this module with the
same rule as ``serving/fastpath.py``: no loop here may iterate anything
span-sized — lanes move frame references, never span data.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Any, Callable, Optional

from ..selftelemetry.latency import name_thread
from ..utils.telemetry import labeled_key, meter

LANE_RETIRED_METRIC = "odigos_fastpath_lane_retired_frames_total"
LANE_READY_DEPTH_GAUGE = "odigos_fastpath_lane_ready_depth"
LANE_COUNT_GAUGE = "odigos_fastpath_lane_count"
LANE_ERRORS_METRIC = "odigos_fastpath_lane_errors_total"

# condition waits are plain (every state change notifies); the timeout
# exists only so a thread that raced a shutdown notify still observes
# the stop flag — never a polling cadence
SHUTDOWN_BACKSTOP_S = 1.0


class OrderedGate:
    """Non-blocking in-order forward gate for ``ordered: true``
    retirement.

    A lane OFFERS its tagged frame: if the frame is next in sequence
    the lane holds the gate and forwards immediately; otherwise the
    frame parks here and the lane is FREED for other ready frames.
    After the head's forward completes, ``advance()`` steps the gate
    and surfaces the now-eligible parked frame (the caller re-pushes
    it to the pool). Downstream consumers therefore see frames in
    exact intake order — bit-identical to the single-forwarder path —
    while wait/merge/tag of later frames still overlap.

    Never blocking is the point, not a nicety: a turnstile that makes
    lanes WAIT for their turn deadlocks the pool whenever frames
    complete out of intake order — all N lanes end up holding later
    frames, each waiting for the head, while the head frame sits in
    the ready queue with no lane left to pick it up.
    """

    __slots__ = ("_lock", "_next", "_parked")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._next = 0
        self._parked: dict[int, Any] = {}

    def offer(self, seq: int, frame: Any) -> bool:
        """True → ``seq`` is next: the caller holds the gate and must
        forward now (then call ``advance``). False → parked; the lane
        is free, a later ``advance()`` surfaces the frame."""
        with self._lock:
            if seq != self._next:
                self._parked[seq] = frame
                return False
            return True

    def advance(self) -> Any:
        """Step past the completed head; return the parked frame that
        just became eligible (or None if it is not ready yet)."""
        with self._lock:
            self._next += 1
            return self._parked.pop(self._next, None)

    def flush(self) -> list:
        """Shutdown path: remaining parked frames, sequence order."""
        with self._lock:
            out = [self._parked[k] for k in sorted(self._parked)]
            self._parked.clear()
            return out


class RetirementLanes:
    """A pool of ``n`` retirement threads fed by a completion-driven
    ready queue.

    ``push(frame)`` marks one frame retirable (scores landed, engine
    gave up, or the deadline expired); the next idle lane invokes
    ``retire(frame, lane_index)``. A retire returning ``False`` did NOT
    finish the frame (it parked at the ordered gate and will be pushed
    again) — only truthy/None returns count toward the per-lane
    retired-frame counters, so an ordered frame is counted exactly
    once. Those counters and a ready-depth gauge publish as the
    ``odigos_fastpath_lane_*`` family — a persistently deep ready
    queue means the lanes (not the device) are the bottleneck and
    ``lanes:`` should grow.
    """

    def __init__(self, pipeline: str, n: int,
                 retire: Callable[[Any, int], Optional[bool]]):
        self.n = max(1, int(n))
        self._retire = retire
        self._ready = threading.Condition()
        self._queue: deque[Any] = deque()
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        self._depth_key = labeled_key(LANE_READY_DEPTH_GAUGE,
                                      pipeline=pipeline)
        self._errors_key = labeled_key(LANE_ERRORS_METRIC,
                                       pipeline=pipeline)
        self._retired_keys = [
            labeled_key(LANE_RETIRED_METRIC, pipeline=pipeline,
                        lane=str(i))
            for i in range(self.n)]
        meter.set_gauge(labeled_key(LANE_COUNT_GAUGE, pipeline=pipeline),
                        self.n)

    # ------------------------------------------------------------ intake
    def push(self, frame: Any) -> None:
        """Hand one retirable frame to the pool. Called from completion
        contexts (engine worker thread, deadline timer) — O(1) append +
        notify, nothing frame-sized is touched here."""
        with self._ready:
            self._queue.append(frame)
            meter.set_gauge(self._depth_key, len(self._queue))
            self._ready.notify()

    def depth(self) -> int:
        with self._ready:
            return len(self._queue)

    # --------------------------------------------------------- lifecycle
    def start(self) -> "RetirementLanes":
        if any(t.is_alive() for t in self._threads):
            return self
        self._stop = threading.Event()
        self._threads = [
            threading.Thread(target=self._run, args=(i, self._stop),
                             daemon=True, name=f"retire-lane-{i}")
            for i in range(self.n)]
        for t in self._threads:
            t.start()
        return self

    def shutdown(self) -> None:
        self._stop.set()
        with self._ready:
            self._ready.notify_all()
        for t in self._threads:
            t.join(timeout=5)
        self._threads = []

    def drain_pending(self) -> list:
        """Post-shutdown: frames still queued when the lanes exited (a
        timed-out drain). The owner retires them inline — a frame left
        here would hold its reservation forever."""
        with self._ready:
            out = list(self._queue)
            self._queue.clear()
            meter.set_gauge(self._depth_key, 0)
            return out

    # -------------------------------------------------------------- lane
    def _run(self, idx: int, stop: threading.Event) -> None:
        name_thread(f"odigos-lane-{idx}")
        retired_key = self._retired_keys[idx]
        while True:
            with self._ready:
                while not self._queue:
                    if stop.is_set():
                        return
                    # plain wait — push()/shutdown() notify; the timeout
                    # is only the lost-shutdown-notify backstop
                    self._ready.wait(SHUTDOWN_BACKSTOP_S)
                frame = self._queue.popleft()
                meter.set_gauge(self._depth_key, len(self._queue))
            try:
                retired = self._retire(frame, idx)
            except Exception:  # noqa: BLE001 — a frame must never kill a lane
                meter.add(self._errors_key)
            else:
                # False = the frame parked (ordered gate) and will come
                # back; counting it here would double-count every
                # out-of-turn ordered frame (and count errors as work)
                if retired is not False:
                    meter.add(retired_key)
