"""The program's stages and the model's parts on the device trace's clock.

``tracered`` keeps an event's name, start and duration. This reduction
keeps three things more of the same ``.xplane.pb``:

* the arguments of the program's own annotations (``engine/enqueue`` with
  ``call``, ``rows``, ``spans``; ``selftelemetry/latency.py``
  ``ANNOTATIONS``), so that each engine call is one record: when it was
  packed, enqueued, waited for and scattered;
* the ``XLA Modules`` runs in order with their ``run_id``, so that call k
  is joined to the executable run that served it: one worker dispatches in
  order, so the k-th ``engine/enqueue`` is the k-th run that starts after
  it began and ends before its ``engine/harvest`` does;
* for every ``XLA Ops`` event the scope path of its operation, which the
  TPU profiler writes into the event's metadata as ``tf_op`` (jax's
  ``op_name``: ``jit(f)/Module/encoder/block_0/mlp/Dense_0/dot_general``).
  ``jax.profiler.ProfileData`` shows an event's own stats and not its
  metadata's, so the metadata table is read from the file's wire format
  (``op_metadata``), the events through ``ProfileData`` as ``tracered``
  reads them.

Which scopes there are and the part each folds into is the architecture's
to say (``architectures/<name>.py`` ``PARTS``); an operation under no
scope it names folds into ``rest``.

Works on plain records, so that it is checked on hand-made ones
(``tests/test_hosttrace.py``). Nothing here raises on a trace of a
program without the annotations: ``reduce`` then returns ``None``.
``run.py``'s traced branch calls ``load`` and ``reduce`` before it deletes
the trace, and hands the result to the readers as ``obs.host``.
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import Counter, defaultdict
from dataclasses import dataclass, field
from typing import Any, Iterable, Iterator, Mapping, Optional

from benchmark import tracered
from benchmark.tracered import DEVICE_PREFIX, MODULES_LINE, OPS_LINE

# the engine worker's annotations: the call's working stages, and the
# one in which it has nothing to work on
WORK = ("engine/pack", "engine/enqueue", "engine/harvest", "engine/scatter")
COLLECT = "engine/collect"
# the runtime's host event that hands a program to the chip; it carries
# the run_id of the XLA Modules event it starts
ENQUEUE_PROGRAM = "DoEnqueueProgram"
# an operation under no scope the architecture names, and the part it
# folds into
UNSCOPED = "unscoped"
REST = "rest"
JOIN_FLOOR = 0.95
# an executable may end this long after the host saw its result: the
# two clocks are aligned by the profiler, not identical
CLOCK_SLACK_S = 1e-3


@dataclass
class Event:
    name: str
    start: float                 # s, on the trace's one clock
    dur: float
    args: dict[str, Any] = field(default_factory=dict)

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Line:
    name: str
    events: list[Event]


@dataclass
class Plane:
    name: str
    lines: list[Line] = field(default_factory=list)
    # device planes: event name -> scope path of its operation (tf_op)
    op_names: dict[str, str] = field(default_factory=dict)


# ------------------------------------------------------------------ reading


def _varint(buf: memoryview, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf: memoryview) -> Iterator[tuple[int, Any]]:
    """(field number, value) of one protobuf message: an int for a
    varint, a memoryview for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an xplane message")
        yield key >> 3, value


def _text(buf: memoryview) -> str:
    return bytes(buf).decode("utf-8", "replace")


def op_metadata(xspace: bytes) -> dict[str, dict[str, str]]:
    """Per device plane of a serialized ``XSpace``: event name -> the
    ``tf_op`` stat of its metadata. The schema (tsl ``xplane.proto``):
    XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (a map: key
    1, value 2), .stat_metadata = 5; XEventMetadata.name = 2, .stats =
    5; XStat.metadata_id = 1, .str_value = 5, .ref_value = 7 (the id of
    a stat metadata whose name is the string); XStatMetadata.name = 2.
    Lines and events (XPlane.lines = 3) are skipped by length."""
    out: dict[str, dict[str, str]] = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:
            continue
        name, stat_names, event_meta = "", {}, []
        for pf, pv in _fields(plane):
            if pf == 2:
                name = _text(pv)
            elif pf == 4:
                event_meta.append(pv)
            elif pf == 5:
                key, text = None, ""
                for ef, ev in _fields(pv):
                    if ef == 1:
                        key = ev
                    elif ef == 2:
                        text = next((_text(v) for sf, v in _fields(ev)
                                     if sf == 2), "")
                stat_names[key] = text
        if not name.startswith(DEVICE_PREFIX):
            continue
        tf_op = next((k for k, v in stat_names.items() if v == "tf_op"),
                     None)
        ops = out.setdefault(name, {})
        if tf_op is None:
            continue
        for entry in event_meta:
            for ef, meta in _fields(entry):
                if ef != 2:
                    continue
                ev_name, path = "", None
                for mf, mv in _fields(meta):
                    if mf == 2:
                        ev_name = _text(mv)
                    elif mf == 5:
                        stat = dict(_fields(mv))
                        if stat.get(1) != tf_op:
                            continue
                        if 5 in stat:
                            path = _text(stat[5])
                        elif 7 in stat:
                            path = stat_names.get(stat[7])
                if path:
                    ops[ev_name] = path.rstrip(":")
    return out


def _wanted(line: str, event: str) -> bool:
    return (line == MODULES_LINE or event == ENQUEUE_PROGRAM
            or event.startswith("engine/"))


def load(trace_dir: str) -> list[Plane]:
    """The newest ``.xplane.pb`` under ``trace_dir``: device planes with
    their ``XLA Modules`` and ``XLA Ops`` lines and the operations'
    scope paths, host planes with the program's ``engine/*`` annotations
    and the runtime's enqueue events, each with its arguments."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(files[-1], "rb") as f:
        raw = f.read()
    op_names = op_metadata(raw)
    planes = []
    for p in ProfileData.from_serialized_xspace(raw).planes:
        device = p.name.startswith(DEVICE_PREFIX)
        plane = Plane(p.name, op_names=op_names.get(p.name, {}))
        for ln in p.lines:
            if device and ln.name not in (MODULES_LINE, OPS_LINE):
                continue
            events = []
            for e in ln.events:
                if device and ln.name == OPS_LINE:
                    events.append(Event(e.name, e.start_ns * 1e-9,
                                        e.duration_ns * 1e-9))
                elif _wanted(ln.name, e.name):
                    events.append(Event(e.name, e.start_ns * 1e-9,
                                        e.duration_ns * 1e-9,
                                        dict(e.stats)))
            if events:
                plane.lines.append(Line(ln.name, events))
        if plane.lines:
            planes.append(plane)
    return planes


# ----------------------------------------------------------------- the join


@dataclass
class Call:
    """One coalesced engine call as the trace shows it."""
    serial: int
    rows: int = 0
    spans: int = 0
    stages: dict[str, Event] = field(default_factory=dict)  # by WORK name
    # the executable run that served it, one per device plane
    runs: list[Event] = field(default_factory=list)

    @property
    def joined(self) -> bool:
        return bool(self.runs)


def _device(planes: Iterable[Plane]) -> list[tuple[Plane, Line, Line]]:
    out = []
    for p in planes:
        if not p.name.startswith(DEVICE_PREFIX):
            continue
        lines = {ln.name: ln for ln in p.lines if ln.events}
        if OPS_LINE in lines and MODULES_LINE in lines:
            out.append((p, lines[MODULES_LINE], lines[OPS_LINE]))
    return out


def _host_events(planes: Iterable[Plane], names: tuple[str, ...],
                 ) -> list[Event]:
    return sorted((e for p in planes if not p.name.startswith(DEVICE_PREFIX)
                   for ln in p.lines for e in ln.events if e.name in names),
                  key=lambda e: e.start)


def calls(planes: list[Plane]) -> list[Call]:
    """The window's engine calls in dispatch order, each joined to its
    executable run where one is found. A call needs its ``engine/enqueue``
    and its ``engine/harvest`` to be looked for (a dispatch that raised
    ran nothing); it joins the first run not yet taken that starts after
    its enqueue began and ends before its harvest did."""
    by_serial: dict[int, Call] = {}
    for e in _host_events(planes, WORK):
        serial = int(e.args.get("call", -1))
        if serial < 0:
            continue            # nobody's: a direct score, ladder warming
        call = by_serial.setdefault(serial, Call(serial))
        call.stages[e.name] = e
        if e.name == "engine/enqueue":
            call.rows = int(e.args.get("rows", 0))
            call.spans = int(e.args.get("spans", 0))
    out = sorted((c for c in by_serial.values()
                  if "engine/enqueue" in c.stages),
                 key=lambda c: c.stages["engine/enqueue"].start)
    for _, modules, _ in _device(planes):
        runs = sorted(modules.events, key=lambda e: e.start)
        starts = [r.start for r in runs]
        taken = 0
        for call in out:
            harvest = call.stages.get("engine/harvest")
            if harvest is None:
                continue
            j = max(taken, bisect.bisect_left(
                starts, call.stages["engine/enqueue"].start))
            if j < len(runs) and runs[j].end <= harvest.end + CLOCK_SLACK_S:
                call.runs.append(runs[j])
                taken = j + 1
    n_dev = len(_device(planes))
    for call in out:
        if len(call.runs) != n_dev:
            call.runs = []      # joined on some chips only: not joined
    return out


def run_id_agreement(planes: list[Plane], joined: list[Call],
                     ) -> Optional[float]:
    """Share of the joined calls whose run carries the ``run_id`` that
    the runtime's k-th enqueue after the call's own began carries: the
    check of the order join against the runtime's own key. None where
    the trace has no such host event (it is the runtime's, not ours)."""
    handed = [e for e in _host_events(planes, (ENQUEUE_PROGRAM,))
              if "run_id" in e.args]
    if not handed or not joined:
        return None
    starts = [e.start for e in handed]
    same = taken = 0
    for call in joined:
        j = max(taken, bisect.bisect_left(
            starts, call.stages["engine/enqueue"].start))
        if j >= len(handed):
            break
        taken = j + 1
        if any(r.args.get("run_id") == handed[j].args["run_id"]
               for r in call.runs):
            same += 1
    return same / len(joined)


# --------------------------------------------------------------- the folds


def scope_of(path: Optional[str], scopes: Iterable[str]) -> str:
    """The scope an operation belongs to: the first component of its
    scope path that is one of ``scopes``."""
    for piece in (path or "").split("/"):
        if piece in scopes:
            return piece
    return UNSCOPED


def fold_parts(planes: list[Plane], rows_of_run: dict[int, int],
               scopes: Iterable[str]) -> dict[tuple[int, str, str], float]:
    """Seconds of ``XLA Ops`` by (rows of the call's rung, scope,
    operation family). An operation belongs to the executable run it
    started in; ``rows_of_run`` maps ``id()`` of a joined run to its
    call's rows, and an operation of a run nobody joined reads rows 0."""
    out: dict[tuple[int, str, str], float] = defaultdict(float)
    scopes = frozenset(scopes)
    for plane, modules, ops in _device(planes):
        runs = sorted(modules.events, key=lambda e: e.start)
        starts = [r.start for r in runs]
        for e in ops.events:
            j = bisect.bisect_right(starts, e.start) - 1
            rows = 0
            if j >= 0 and e.start < runs[j].end:
                rows = rows_of_run.get(id(runs[j]), 0)
            out[(rows, scope_of(plane.op_names.get(e.name), scopes),
                 tracered.op_family(e.name))] += e.dur
    return dict(out)


def overlap_s(a: list[tuple[float, float]], b: list[tuple[float, float]],
              ) -> float:
    """Length of the intersection of two sets of intervals, each given
    as disjoint (start, end) pairs in order."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def merged(intervals: Iterable[tuple[float, float]],
           ) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


@dataclass
class HostTrace:
    """What the joined trace says of one window."""
    n_calls: int                  # engine calls the trace shows
    n_joined: int
    n_runs: int                   # executable runs, per chip
    run_id_agree: Optional[float]
    step_ms: float                # mean executable run
    queue_ms: Optional[float]     # enqueue end -> its run starts
    fetch_ms: Optional[float]     # its run ends -> scatter ends
    idle_host_s: float            # no executable runs, the worker works
    idle_collect_s: float         # ... the worker waits for requests
    idle_s: float                 # no executable runs, first to last run
    window_s: float
    # seconds by (rows, scope, family); rows 0: a run nobody joined
    parts: dict[tuple[int, str, str], float] = field(default_factory=dict)
    runs_by_rows: dict[int, int] = field(default_factory=dict)
    # the architecture's PARTS: scope -> the part it folds into
    fold: Mapping[str, str] = field(default_factory=dict)
    n_dev: int = 1                # chips whose operations ``parts`` sums

    @property
    def joined_share(self) -> float:
        return self.n_joined / self.n_calls if self.n_calls else 0.0

    def part_s(self, part: str) -> float:
        """Summed seconds, over every chip, of the operations whose scope
        folds into ``part``; those under no scope fold into ``rest``."""
        return sum(s for (_, scope, _), s in self.parts.items()
                   if self.fold.get(scope, REST) == part)

    def part_ms(self, part: str) -> float:
        """``part_s`` as a mean per executable run."""
        return 1e3 * self.part_s(part) / (self.n_runs * self.n_dev)

    @property
    def scoped_share(self) -> float:
        total = sum(self.parts.values())
        named = sum(s for (_, p, _), s in self.parts.items()
                    if p != UNSCOPED)
        return named / total if total else 0.0


def reduce(planes: list[Plane], window_s: Optional[float],
           parts: Mapping[str, str]) -> Optional[HostTrace]:
    """The joined numbers of one traced window; None where the trace
    holds no device run or no ``engine/enqueue`` (a program without the
    annotations). ``window_s`` is the length the idle shares are taken
    of (the harness's traced window; None: first to last run), ``parts``
    the architecture's scopes and the part each folds into."""
    device = _device(planes)
    all_calls = calls(planes)
    if not device or not all_calls:
        return None
    joined = [c for c in all_calls if c.joined]
    n_dev = len(device)
    runs = [r for _, modules, _ in device for r in modules.events]
    rows_of_run = {id(r): c.rows for c in joined for r in c.runs}
    queue = [max(0.0, min(r.start for r in c.runs)
                 - c.stages["engine/enqueue"].end) for c in joined]
    fetch = [c.stages["engine/scatter"].end - max(r.end for r in c.runs)
             for c in joined if "engine/scatter" in c.stages]
    # idle: no executable runs on the chip that ran least
    _, idlest, _ = min(device, key=lambda d: sum(e.dur for e in d[1].events))
    lo = min(r.start for r in idlest.events)
    hi = max(r.end for r in idlest.events)
    idle = merged(tracered.gaps([(r.start, r.end) for r in idlest.events],
                                lo, hi))
    work = merged((e.start, e.end) for e in _host_events(planes, WORK))
    collect = merged((e.start, e.end)
                     for e in _host_events(planes, (COLLECT,)))
    by_rows = Counter(c.rows for c in joined)
    by_rows[0] = len(runs) // n_dev - len(joined)
    return HostTrace(
        n_calls=len(all_calls), n_joined=len(joined),
        n_runs=len(runs) // n_dev,
        run_id_agree=run_id_agreement(planes, joined),
        step_ms=1e3 * sum(r.dur for r in runs) / len(runs),
        queue_ms=1e3 * sum(queue) / len(queue) if queue else None,
        fetch_ms=1e3 * sum(fetch) / len(fetch) if fetch else None,
        idle_host_s=overlap_s(idle, work),
        idle_collect_s=overlap_s(idle, collect),
        idle_s=sum(b - a for a, b in idle),
        window_s=window_s if window_s else hi - lo,
        parts=fold_parts(planes, rows_of_run, parts),
        runs_by_rows={k: v for k, v in by_rows.items() if v},
        fold=dict(parts), n_dev=n_dev)


def table(ht: HostTrace, top: int = 6) -> list[str]:
    """The fold the three ``step_*`` metrics are sums of: seconds by part
    and operation family, with milliseconds a run, by rung where the
    window dispatched more than one; a part under a ten-thousandth and a
    family under a thousandth of the operations' time are left out."""
    out = []
    rungs = sorted(ht.runs_by_rows)
    for rows in ([None] if len(rungs) < 2 else [None] + rungs):
        sel = {(p, f): 0.0 for _, p, f in ht.parts}
        for (r, p, f), s in ht.parts.items():
            if rows is None or r == rows:
                sel[(p, f)] += s
        runs = ht.n_runs if rows is None else ht.runs_by_rows[rows]
        n = runs * ht.n_dev          # the seconds are summed over the chips
        total = sum(sel.values())
        title = "all runs" if rows is None else (
            f"{rows}-row runs" if rows else "runs no call joined")
        out.append(f"-- {title}: {runs} runs, {total:.3f} s of operations, "
                   f"{1e3 * total / n:.2f} ms a run")
        by_part: dict[str, float] = defaultdict(float)
        for (p, _), s in sel.items():
            by_part[p] += s
        for part in sorted(by_part, key=lambda p: -by_part[p]):
            if by_part[part] < 1e-4 * total:
                continue
            out.append(f"{part:<11} {by_part[part]:9.3f} s "
                       f"{1e3 * by_part[part] / n:9.3f} ms/run "
                       f"{100 * by_part[part] / total:5.1f}%")
            fams = sorted(((f, s) for (p, f), s in sel.items()
                           if p == part and s >= 1e-3 * total),
                          key=lambda kv: -kv[1])
            for fam, s in fams[:top]:
                out.append(f"  {part}/{fam:<40} {s:9.3f} s "
                           f"{1e3 * s / n:9.3f} ms/run")
    return out


if __name__ == "__main__":
    # a library since PR 27; said aloud, since older notes name this file
    # as the traced run and a silent exit 0 would pass for one
    raise SystemExit("benchmark/hosttrace.py runs nothing: the traced run "
                     "is python3 benchmark/run.py --workload <cell> --seed "
                     "<n> --seconds <run_seconds> --trace 1")
