"""The host's pauses on the device trace's clock.

Three quantities the program writes for itself, which ``hosttrace``'s
reduction does not read:

* ``python/gc`` (``serving/gcisolation.py``): every collection of the
  interpreter's garbage collector, on the thread that ran it, with its
  ``generation``. Summed over the traced window, and overlapped with the
  device's idle: the sibling of ``device_idle_host``, with a collection
  open where that one has the engine worker at work;
* ``engine/ready`` (``SequenceBackend.fetch``), inside ``engine/harvest``:
  the wait for the device's result, the rest of the harvest being the
  copy to the host. The latest a call's harvest ended after its run did,
  over the window's joined calls, in three parts: the worker elsewhere
  (the run ended before ``engine/ready`` began), the wait for readiness
  past the run's end, and the copy;
* ``odigos_batch_hold_span_ms_total`` and ``odigos_batch_held_spans_total``
  (``components/processors/batch.py``): what each ``batch`` processor
  held, in span-milliseconds and spans. Their ratio, summed over the
  processors on the way to the ``all`` exporter, is the mean hold a span
  paid between the retirement lane and the exporter.

``load`` reads an ``.xplane.pb`` as ``hosttrace.load`` does and keeps the
collections too (once ``hosttrace.load`` keeps ``python/gc`` itself, this
``load`` goes); ``reduce`` takes its planes and the traced window's
length; ``hold_ms`` takes a window's counter deltas. Each returns None
where what it reads is absent. ``run.py`` does not call them yet: a traced
run's ``breakdown.idle_gaps`` already names a collection where one covers
an idle gap best (``tracered.host_cover`` reads every host event).
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass
from typing import Iterable, Mapping, Optional

from benchmark import hosttrace, tracered
from benchmark.hosttrace import Event, Line, Plane
from benchmark.tracered import DEVICE_PREFIX, MODULES_LINE, OPS_LINE

GC = "python/gc"
READY = "engine/ready"
HARVEST = "engine/harvest"
HOLD = "odigos_batch_hold_span_ms_total"
HELD = "odigos_batch_held_spans_total"
# the rendered gateway's path from the retirement lane to the `all`
# exporter: the data stream's pipeline, then the destination's, each
# starting with a `batch` processor
TO_ALL = ("traces/default", "traces/tracedb-all")


def load(trace_dir: str) -> list[Plane]:
    """The newest ``.xplane.pb`` under ``trace_dir``: device planes with
    their ``XLA Modules`` and ``XLA Ops`` lines, host planes with the
    program's ``engine/*`` annotations and ``python/gc``, each with its
    arguments."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    planes = []
    for p in ProfileData.from_file(files[-1]).planes:
        device = p.name.startswith(DEVICE_PREFIX)
        plane = Plane(p.name)
        for ln in p.lines:
            if device and ln.name not in (MODULES_LINE, OPS_LINE):
                continue
            events = [Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                            {} if device else dict(e.stats))
                      for e in ln.events
                      if device or e.name == GC
                      or e.name.startswith("engine/")]
            if events:
                plane.lines.append(Line(ln.name, events))
        if plane.lines:
            planes.append(plane)
    return planes


@dataclass
class Pauses:
    """What the trace says of the host's pauses in one window."""
    window_s: float
    gc_s: Optional[float]         # summed wall of the collections
    idle_gc_s: Optional[float]    # no executable runs, a collection open
    late_max_ms: Optional[float]  # a call's run end -> its harvest end
    late_serial: Optional[int]    # that call's serial
    # ... of which: the worker elsewhere (run end -> engine/ready start),
    late_elsewhere_ms: Optional[float]
    late_ready_ms: Optional[float]  # the wait past the run's end,
    late_copy_ms: Optional[float]   # and from engine/ready's end, the copy

    @property
    def gc_ms_per_s(self) -> Optional[float]:
        return None if self.gc_s is None else 1e3 * self.gc_s / self.window_s

    @property
    def idle_gc_share(self) -> Optional[float]:
        """``idle_gc_s`` as a percent of the window."""
        return None if self.idle_gc_s is None \
            else 100.0 * self.idle_gc_s / self.window_s

    def describe(self) -> str:
        if self.late_max_ms is None:
            return "no joined call"
        split = "no engine/ready" if self.late_ready_ms is None else (
            f"elsewhere {self.late_elsewhere_ms:.3f} ms, ready "
            f"{self.late_ready_ms:.3f} ms, copy {self.late_copy_ms:.3f} ms")
        return (f"call {self.late_serial}: harvest ended "
                f"{self.late_max_ms:.3f} ms after its run ({split})")


def _host(planes: Iterable[Plane], name: str) -> list[Event]:
    return [e for p in planes if not p.name.startswith(DEVICE_PREFIX)
            for ln in p.lines for e in ln.events if e.name == name]


def reduce(planes: list[Plane], window_s: Optional[float] = None,
           ) -> Optional[Pauses]:
    """The window's pauses; None where the trace holds no device run or
    no engine call (``hosttrace.reduce``'s rule). ``window_s`` as there:
    the harness's traced window, None for first to last run. The idle is
    ``hosttrace``'s: no ``XLA Modules`` run on the chip that ran least,
    first to last run."""
    device = hosttrace._device(planes)
    all_calls = hosttrace.calls(planes)
    if not device or not all_calls:
        return None
    _, idlest, _ = min(device, key=lambda d: sum(e.dur for e in d[1].events))
    lo = min(r.start for r in idlest.events)
    hi = max(r.end for r in idlest.events)
    idle = hosttrace.merged(tracered.gaps(
        [(r.start, r.end) for r in idlest.events], lo, hi))
    gcs = _host(planes, GC)
    gc_s = idle_gc_s = None
    if gcs:
        gc_s = sum(e.dur for e in gcs)
        idle_gc_s = hosttrace.overlap_s(
            idle, hosttrace.merged((e.start, e.end) for e in gcs))
    ready = {int(e.args["call"]): e for e in _host(planes, READY)
             if "call" in e.args}
    late = None
    for c in all_calls:
        if c.joined and HARVEST in c.stages:
            ms = 1e3 * (c.stages[HARVEST].end - max(r.end for r in c.runs))
            if late is None or ms > late[0]:
                late = (ms, c)
    out = Pauses(window_s=window_s if window_s else hi - lo, gc_s=gc_s,
                 idle_gc_s=idle_gc_s, late_max_ms=None, late_serial=None,
                 late_elsewhere_ms=None, late_ready_ms=None,
                 late_copy_ms=None)
    if late is not None:
        ms, c = late
        out.late_max_ms, out.late_serial = ms, c.serial
        r = ready.get(c.serial)
        if r is not None:
            end = max(x.end for x in c.runs)
            out.late_elsewhere_ms = 1e3 * max(0.0, r.start - end)
            out.late_ready_ms = 1e3 * (r.end - max(r.start, end))
            out.late_copy_ms = 1e3 * (c.stages[HARVEST].end - r.end)
    return out


def hold_ms(counters: Mapping[str, float],
            pipelines: Iterable[str] = TO_ALL) -> Optional[float]:
    """Mean hold a span paid the ``batch`` processors of ``pipelines``,
    ms: per processor, its held span-milliseconds over the spans it held
    (window deltas, keys as the meter has them), summed over the
    processors. None where none of them held a span."""
    by: dict[tuple[str, str], list[float]] = {}
    for key, value in counters.items():
        name, _, labels = key.partition("{")
        if name not in (HOLD, HELD):
            continue
        got = dict(kv.split("=", 1) for kv in labels.rstrip("}").split(","))
        if got.get("pipeline") not in pipelines:
            continue
        pair = by.setdefault((got["pipeline"], got.get("processor", "")),
                             [0.0, 0.0])
        pair[name == HELD] += value
    means = [ms / spans for ms, spans in by.values() if spans > 0]
    return sum(means) if means else None
