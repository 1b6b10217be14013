"""GC isolation for the steady-state hot path.

The saturated tail hunt (ROADMAP "tail-latency hunt at saturation")
names the collector's own garbage collector as a culprit: CPython's
threshold-triggered collections run on WHICHEVER thread allocates the
700th container object — under load that is a submit lane mid-featurize
or a retirement lane mid-forward, and the pause lands straight in a
frame's stage waterfall. The reference collector has nothing here (Go's
GC is concurrent; its memory_limiter merely *reacts*). This module
gives the Python runtime the same discipline the buffer pool gives the
allocator:

* a **paced janitor thread** owns generation-0/1 collections: it
  collects every ``janitor_interval_s`` (and immediately on
  :meth:`GcPlane.hint` — the memory-limiter's soft-pressure signal,
  which used to be an inline ``gc.collect(0)`` ON THE DATA PATH), so
  with tuned thresholds the lane threads essentially never trigger a
  collection themselves;
* **freeze after warmup** (``engage``): once the engine, bucket ladder
  and jit caches are built, ``gc.freeze()`` moves the permanent object
  graph out of every future collection's scan set — a gen-2 collection
  that does happen walks the per-frame churn, not the model;
* **generational thresholds** are raised (default ``(100_000, 20,
  20)``) so the steady state's small container churn is absorbed by
  the janitor's paced gen-0 sweeps instead of synchronous
  threshold trips;
* every collection — janitor-paced or threshold-triggered, any thread —
  is timed via ``gc.callbacks`` into the ``odigos_gc_pause_ms{gen=}``
  histogram, so "GC left the waterfall" is a measurable claim, not a
  vibe; and bracketed by a ``python/gc`` profiler annotation on the
  collecting thread (``generation``, and ``collected`` on its way out),
  so that a trace names a collection where the device sat idle.

The callback deliberately never touches the meter (a threshold
collection can fire INSIDE a meter lock hold — re-entering the meter
from the callback would deadlock); it appends to a bounded pending ring
the janitor drains into histograms. The annotation takes no lock either:
with no profiler session live it is an inactive TraceMe, and a process
that has not imported jax opens none.

Lifecycle: process-global singleton (``gc_plane``), refcounted —
``Collector.start`` starts it (config under ``service: {gc: {...}}``;
the janitor runs even without a stanza so memory-limiter hints always
have a collector to land on), ``Collector.shutdown`` stops it, and the
last stop restores thresholds / unfreezes. Config keys:

    service:
      gc:
        janitor_interval_s: 0.25   # paced collect cadence
        gen1_every: 8              # every Nth janitor pass collects gen 1
        freeze: true               # gc.freeze() after components start
        thresholds: [100000, 20, 20]
"""

from __future__ import annotations

import gc
import threading
import time
from collections import deque
from typing import Any, Optional

from ..selftelemetry.latency import annotate
from ..utils.telemetry import labeled_key, meter

GC_PAUSE_METRIC = "odigos_gc_pause_ms"
GC_COLLECTS_METRIC = "odigos_gc_janitor_collects_total"
GC_HINTS_METRIC = "odigos_gc_janitor_hints_total"
GC_FROZEN_GAUGE = "odigos_gc_frozen_objects"

DEFAULT_JANITOR_INTERVAL_S = 0.25
DEFAULT_GEN1_EVERY = 8
DEFAULT_THRESHOLDS = (100_000, 20, 20)

_GC_KEYS = ("janitor_interval_s", "gen1_every", "freeze", "thresholds")


def validate_gc_config(cfg: Any) -> list[str]:
    """Load-time validation for the ``service.gc`` stanza (the
    validate_alert_rules discipline: a typo'd knob dies at load, never
    silently default)."""
    problems: list[str] = []
    if not isinstance(cfg, dict):
        return [f"service.gc must be a mapping, got {type(cfg).__name__}"]
    unknown = sorted(set(cfg) - set(_GC_KEYS))
    if unknown:
        problems.append(f"service.gc: unknown keys {unknown} "
                        f"(known: {sorted(_GC_KEYS)})")
    v = cfg.get("janitor_interval_s")
    if v is not None and (isinstance(v, bool)
                          or not isinstance(v, (int, float)) or v <= 0):
        problems.append("service.gc.janitor_interval_s must be a "
                        "positive number")
    v = cfg.get("gen1_every")
    if v is not None and (isinstance(v, bool)
                          or not isinstance(v, int) or v < 1):
        problems.append("service.gc.gen1_every must be a positive integer")
    v = cfg.get("freeze")
    if v is not None and not isinstance(v, bool):
        problems.append("service.gc.freeze must be a boolean")
    v = cfg.get("thresholds")
    if v is not None and (
            not isinstance(v, (list, tuple)) or len(v) != 3
            or any(isinstance(t, bool) or not isinstance(t, int) or t < 1
                   for t in v)):
        problems.append("service.gc.thresholds must be three positive "
                        "integers [gen0, gen1, gen2]")
    return problems


class GcPlane:
    """Process-global GC janitor + pause accounting (see module doc)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._starts = 0
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._wake = threading.Event()
        self.interval_s = DEFAULT_JANITOR_INTERVAL_S
        self.gen1_every = DEFAULT_GEN1_EVERY
        # pause accounting, written by the gc callback (NO locks, NO
        # meter — see module doc), drained/published by the janitor
        self._pending: deque[tuple[int, float]] = deque(maxlen=1024)
        self._t0: Optional[int] = None
        self._span: Optional[annotate] = None
        self._pauses = 0
        self._pause_ms_max = 0.0
        self._collects = 0
        self._hints = 0
        self._callback_installed = False
        self._saved_thresholds: Optional[tuple] = None
        self._frozen = False
        self._pause_keys = {
            g: labeled_key(GC_PAUSE_METRIC, gen=str(g)) for g in (0, 1, 2)}

    # ------------------------------------------------- pause accounting
    def _gc_callback(self, phase: str, info: dict) -> None:
        # runs under the GIL on whatever thread triggered the collection
        # (collections never nest, so one scalar mark suffices)
        if phase == "start":
            self._span = annotate(
                "python/gc", generation=info.get("generation", 0)).__enter__()
            self._t0 = time.perf_counter_ns()
            return
        span, self._span = self._span, None
        if span is not None:
            span.set(collected=info.get("collected", 0))
            span.close()
        t0 = self._t0
        if t0 is None:
            return
        self._t0 = None
        ms = (time.perf_counter_ns() - t0) / 1e6
        self._pauses += 1
        if ms > self._pause_ms_max:
            self._pause_ms_max = ms
        self._pending.append((int(info.get("generation", 0)), ms))

    def install_callback(self) -> None:
        with self._lock:
            if self._callback_installed:
                return
            self._callback_installed = True
        gc.callbacks.append(self._gc_callback)

    # pauses this long get a flight-recorder timeline line (a gen-2
    # sweep stalling the data path is incident-relevant context; the
    # per-collection noise floor is not)
    FLIGHT_PAUSE_MS = 10.0

    def _drain_pending(self) -> None:
        """Publish callback-recorded pauses into the histogram (janitor
        thread — the one place meter locks are safe to take; the GC
        callback itself stays lock- and meter-free)."""
        while True:
            try:
                gen, ms = self._pending.popleft()
            except IndexError:
                return
            meter.record(self._pause_keys.get(gen, self._pause_keys[2]),
                         ms)
            if ms >= self.FLIGHT_PAUSE_MS:
                from ..selftelemetry.flightrecorder import \
                    flight_recorder

                flight_recorder.record("gc_pause", gen=gen,
                                       ms=round(ms, 3))

    # ------------------------------------------------------- the janitor
    def hint(self) -> None:
        """Soft memory pressure observed (memory_limiter): collect SOON,
        on the janitor thread — never inline on the data path. One event
        set; no locks, no collection, no pause for the caller."""
        self._hints += 1
        self._wake.set()

    def _run(self, stop: threading.Event, wake: threading.Event) -> None:
        n = 0
        last = 0.0
        hints_published = 0
        # hints may only pull a collect FORWARD to a quarter interval,
        # never turn the janitor into a back-to-back collect loop:
        # sustained soft pressure re-sets the wake event faster than a
        # collect finishes, and an unpaced loop would hold the GIL in
        # gen-0 sweeps continuously — the data-path pauses this thread
        # exists to remove, at higher frequency
        min_gap = max(self.interval_s * 0.25, 0.01)
        while True:
            wake.wait(self.interval_s)
            wake.clear()
            if stop.is_set():
                self._drain_pending()
                return
            gap = min_gap - (time.monotonic() - last)
            if gap > 0 and stop.wait(gap):
                self._drain_pending()
                return
            gen = 1 if (n + 1) % max(self.gen1_every, 1) == 0 else 0
            gc.collect(gen)
            last = time.monotonic()
            self._collects += 1
            n += 1
            meter.add(GC_COLLECTS_METRIC)
            if self._hints > hints_published:
                # hint() itself must stay meter-free (one event set on
                # the data path); the counter publishes from here
                meter.add(GC_HINTS_METRIC,
                          self._hints - hints_published)
                hints_published = self._hints
            self._drain_pending()

    # ----------------------------------------------------- freeze/thaw
    def engage(self, freeze: bool = False,
               thresholds: Optional[tuple] = None) -> None:
        """Post-warmup steady-state posture: optionally freeze the
        permanent object graph (call AFTER engines/ladders warmed) and
        raise the generational thresholds. Idempotent; ``disengage``
        restores."""
        with self._lock:
            if self._saved_thresholds is None:
                self._saved_thresholds = gc.get_threshold()
            gc.set_threshold(*(thresholds or DEFAULT_THRESHOLDS))
            if freeze and not self._frozen:
                gc.collect(2)
                gc.freeze()
                self._frozen = True
                meter.set_gauge(GC_FROZEN_GAUGE, gc.get_freeze_count())

    def disengage(self) -> None:
        with self._lock:
            if self._frozen:
                gc.unfreeze()
                self._frozen = False
                meter.set_gauge(GC_FROZEN_GAUGE, 0)
            if self._saved_thresholds is not None:
                gc.set_threshold(*self._saved_thresholds)
                self._saved_thresholds = None

    # --------------------------------------------------------- lifecycle
    def start(self, cfg: Optional[dict] = None) -> None:
        """Refcounted start (Collector lifecycle). The FIRST start's
        config wins for janitor pacing; ``freeze``/``thresholds`` engage
        on any start that asks (warmup already happened — components
        start before the collector calls this)."""
        cfg = cfg or {}
        self.install_callback()
        with self._lock:
            self._starts += 1
            first = self._starts == 1
            if first:
                self.interval_s = float(
                    cfg.get("janitor_interval_s",
                            DEFAULT_JANITOR_INTERVAL_S))
                self.gen1_every = int(
                    cfg.get("gen1_every", DEFAULT_GEN1_EVERY))
                self._stop = threading.Event()
                self._wake = threading.Event()
                self._thread = threading.Thread(
                    target=self._run, args=(self._stop, self._wake),
                    daemon=True, name="gc-janitor")
                self._thread.start()
        if cfg.get("freeze") or cfg.get("thresholds"):
            self.engage(freeze=bool(cfg.get("freeze")),
                        thresholds=tuple(cfg["thresholds"])
                        if cfg.get("thresholds") else None)

    def stop(self) -> None:
        with self._lock:
            if self._starts == 0:
                return
            self._starts -= 1
            if self._starts:
                return
            thread, self._thread = self._thread, None
            self._stop.set()
            self._wake.set()
        if thread is not None:
            thread.join(timeout=5)
        self.disengage()

    # ------------------------------------------------------------- stats
    def stats(self) -> dict[str, Any]:
        return {
            "pauses": self._pauses,
            "pause_ms_max": round(self._pause_ms_max, 3),
            "janitor_collects": self._collects,
            "hints": self._hints,
            "frozen": self._frozen,
            "frozen_objects": gc.get_freeze_count() if self._frozen else 0,
            "running": self._starts > 0,
        }


gc_plane = GcPlane()
