"""From a profiler trace to numbers: device busy time, the executable's
time, the top operations and the longest idle gaps.

Works on plain event lists so that it can be checked on a hand-made one;
``load`` reads an ``.xplane.pb`` into them with nothing but JAX. A device
plane is one whose name starts with ``/device:TPU:``; on it the line
``XLA Ops`` carries one event per operation that ran and ``XLA Modules``
one per run of a compiled executable.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Iterable, Optional

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Line:
    name: str
    events: list[tuple[str, float, float]]   # (name, start s, duration s)


@dataclass
class Plane:
    name: str
    lines: list[Line] = field(default_factory=list)


def load(trace_dir: str) -> list[Plane]:
    """Every plane of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    planes = []
    for p in data.planes:
        plane = Plane(p.name)
        for ln in p.lines:
            plane.lines.append(Line(ln.name, [
                (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                for e in ln.events]))
        planes.append(plane)
    return planes


def describe(planes: Iterable[Plane]) -> list[str]:
    """One line per (plane, line): event count and summed seconds."""
    out = []
    for p in planes:
        for ln in p.lines:
            out.append(f"{p.name} | {ln.name}: {len(ln.events)} events, "
                       f"{sum(e[2] for e in ln.events):.4f} s")
    return out


def op_family(event_name: str) -> str:
    """XLA's own name of the operation, without the instruction text
    the trace appends and without its serial number: ``%fusion.12 =
    bf16[...] fusion(...)`` and ``%fusion.7 = ...`` are both ``fusion``."""
    name = event_name.split(" = ", 1)[0].lstrip("%")
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def device_planes(planes: Iterable[Plane]) -> list[Plane]:
    return [p for p in planes if p.name.startswith(DEVICE_PREFIX)]


def _line(plane: Plane, name: str) -> Optional[Line]:
    return next((ln for ln in plane.lines if ln.name == name), None)


def union_s(intervals: Iterable[tuple[float, float]],
            lo: float, hi: float) -> float:
    """Length of the union of (start, end) intervals clipped to
    [lo, hi]."""
    total, cur_a, cur_b = 0.0, None, None
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def gaps(intervals: Iterable[tuple[float, float]], lo: float, hi: float,
         ) -> list[tuple[float, float]]:
    """The idle (start, end) stretches of [lo, hi] that no interval
    covers, longest first."""
    out, edge = [], lo
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if a > edge:
            out.append((edge, a))
        edge = max(edge, b)
    if hi > edge:
        out.append((edge, hi))
    return sorted(out, key=lambda g: g[0] - g[1])


@dataclass
class DeviceTime:
    window_s: float            # length of the window the trace covers
    t0: float                  # trace time of the first device operation
    span_s: float              # first to last device operation
    busy_s: list[float]        # per device: union of its operations
    module_s: list[float]      # per device: summed executable run time
    top_ops: list[tuple[str, float]]       # over all devices, summed
    idle_gaps: list[tuple[float, float]]   # on the idlest device

    @property
    def busy_mean_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s)

    @property
    def idle_share_max(self) -> float:
        return 1.0 - min(self.busy_s) / self.window_s


def reduce(planes: Iterable[Plane], lo: Optional[float] = None,
           hi: Optional[float] = None) -> Optional[DeviceTime]:
    """Busy union, executable time, top operations and idle gaps of every
    device plane over [lo, hi] (default: first to last device event).
    None where no device plane holds an operation; a device plane with
    operations and no executable line is an error, not a guess."""
    devs = []
    for p in device_planes(planes):
        ops = _line(p, OPS_LINE)
        if ops is not None and ops.events:
            devs.append((p, ops, _line(p, MODULES_LINE)))
    if not devs:
        return None
    if lo is None:
        lo = min(e[1] for _, ops, _ in devs for e in ops.events)
    if hi is None:
        hi = max(e[1] + e[2] for _, ops, _ in devs for e in ops.events)
    busy, module, by_op = [], [], {}
    idlest, idlest_busy = None, None
    for p, ops, mods in devs:
        iv = [(s, s + d) for _, s, d in ops.events]
        b = union_s(iv, lo, hi)
        busy.append(b)
        if idlest_busy is None or b < idlest_busy:
            idlest, idlest_busy = iv, b
        if mods is None or not mods.events:
            raise ValueError(f"plane {p.name} has operations and no "
                             f"{MODULES_LINE!r} line to time them by")
        module.append(union_s([(s, s + d) for _, s, d in mods.events],
                              lo, hi))
        for name, s, d in ops.events:
            if s + d > lo and s < hi:
                name = op_family(name)
                by_op[name] = by_op.get(name, 0.0) + d
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    return DeviceTime(window_s=hi - lo, t0=lo, span_s=hi - lo, busy_s=busy,
                      module_s=module, top_ops=top,
                      idle_gaps=gaps(idlest, lo, hi)[:10])


def host_cover(planes: Iterable[Plane],
               stretches: Iterable[tuple[float, float]]) -> list[str]:
    """What the host was doing in each (start, end) stretch: the event of
    a host plane (any plane that is no device's) that matches it best,
    by the overlap over the union of the two intervals, so that a short
    event inside the stretch or one about as long as it wins over a wait
    that spans the whole trace; as ``<line>/<event>``, the line being
    the thread's name; ``no host event`` where none overlaps. The
    profiler puts every plane on one clock."""
    host = [(ln.name, ln.events) for p in planes
            if not p.name.startswith(DEVICE_PREFIX) for ln in p.lines]
    out = []
    for a, b in stretches:
        best, best_match = "no host event", 0.0
        for line, events in host:
            for name, s, d in events:
                cover = min(b, s + d) - max(a, s)
                if cover > 0 and cover / (d + b - a - cover) > best_match:
                    best = f"{line}/{name}"[:120]
                    best_match = cover / (d + b - a - cover)
        out.append(best)
    return out
