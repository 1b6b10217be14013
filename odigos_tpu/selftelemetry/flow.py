"""Flow ledger: per-edge conservation accounting for the data plane.

The reference platform accounts for every item at every component
boundary (the OTel Collector's ``obsreport`` seam that odigos builds its
UI data-flow and CRD status conditions on). This module is that layer
for our pipelines: **in = out + dropped(reason) + failed(error_class)**,
provable per pipeline, always on, cheap enough for the hot path (one
counter bump per batch per edge).

Model:

* ``FlowEdge`` wraps every consumer seam of a built pipeline graph
  (installed once by ``pipeline/graph.build_graph`` — the ~40 components
  are not individually touched for the happy path). Each edge records
  items/bytes **accepted** (offered across the seam), **forwarded**
  (downstream ``consume`` returned), and **failed-with-error-class**
  (it raised). A propagating exception is counted **once per pipeline**,
  at the deepest edge that saw it (a marker set rides the exception), so
  fan-in through connectors and multi-stage unwinds never double-count.
* Components that intentionally shed data report it through
  ``FlowContext.drop(n, reason)`` with a reason from the closed
  :data:`DROP_REASONS` taxonomy. Attribution is automatic: per-pipeline
  processors carry a ``_flow_site`` stamped at graph build; shared
  components (connectors) inherit the calling edge's site from a
  contextvar, so fan-in attributes to the pipeline actually flowing.
* Buffering components expose ``flow_pending()`` (batch, groupbytrace)
  so the conservation checker can separate "in flight" from "leaked";
  queue high-watermarks land via ``FlowContext.watermark``.
* ``FlowLedger.conservation()`` computes the per-pipeline balance:
  ``items_in == items_out + Σ dropped(reason) + Σ failed(error_class)
  + pending``; any positive remainder is a **leak** — surfaced by the
  :class:`HealthRollup` as a named ``ConservationLeak`` condition, never
  a silent number drift.
* ``HealthRollup`` replaces the bare ``healthy()`` boolean with
  odigos-style conditions per component — ``Healthy`` / ``Degraded
  (reason)`` / ``Unhealthy(reason)`` with message and last-transition
  time — consumed by the healthcheck extension (``?verbose=1``), the
  zpages ``/debug/flowz`` page, ``/api/flow``, the CLI, and the
  control-plane store (CollectorsGroup ``CollectorHealth`` condition).

Surfaces: ``GET /api/flow`` (frontend), ``/debug/flowz`` (zpages),
``odigos_flow_*`` Prometheus counters published on scrape with drop-size
histogram exemplars linking to the self-trace active at the most recent
drop, the dashboard flow panel, ``odigosctl describe`` flow lines, and
the diagnose bundle's ``flow.json``.

``ODIGOS_FLOW=0`` disables the whole layer (edges pass through, drops
are not recorded) — the same opt-out contract as ``ODIGOS_SELFTRACE``.
"""

from __future__ import annotations

import contextvars
import os
import threading
import time
import weakref
from typing import Any, Callable, Iterable, Optional

from ..hooks.tracecontext import _active
from ..utils.telemetry import labeled_key, meter
from .flightrecorder import flight_recorder

# closed drop-reason taxonomy (ISSUE 5): a drop MUST name one of these —
# free-form reasons would rot into unaggregatable cardinality and defeat
# the "where did my spans go" rollup
DROP_REASONS = ("sampled", "filtered", "memory_limited", "queue_full",
                "shutdown_drain", "invalid")

# reserved node names on the pipeline boundary edges
ENTRY_NODE = "__input__"
OUTPUT_NODE = "__output__"

# component health statuses (the odigos CRD status-condition analog)
HEALTHY = "Healthy"
DEGRADED = "Degraded"
UNHEALTHY = "Unhealthy"

DROPPED_METRIC = "odigos_flow_dropped_items_total"
DROP_SIZE_METRIC = "odigos_flow_drop_size"
ACCEPTED_METRIC = "odigos_flow_accepted_items_total"
ACCEPTED_BYTES_METRIC = "odigos_flow_accepted_bytes_total"
FORWARDED_METRIC = "odigos_flow_forwarded_items_total"
FAILED_METRIC = "odigos_flow_failed_items_total"
WATERMARK_METRIC = "odigos_flow_queue_high_watermark"

# set by FlowEdge while the downstream consume runs: (pipeline,
# component, signal). Shared components (connectors) attribute drops to
# whatever pipeline is flowing through them right now.
_flow_site: contextvars.ContextVar[Optional[tuple]] = contextvars.ContextVar(
    "odigos_flow_site", default=None)


def _batch_items(batch: Any) -> int:
    try:
        return len(batch)
    except TypeError:
        return 0


def _batch_nbytes(batch: Any) -> int:
    """Cheap byte estimate: column buffer sizes only. The exact figure
    (string tables, attr pools) costs an O(strings) scan per edge —
    memory_limiter pays it once at admission; every edge must not."""
    cols = getattr(batch, "columns", None)
    if not cols:
        return 0
    return int(sum(c.nbytes for c in cols.values()))


class _EdgeStats:
    """Counters of one graph edge; owned by the ledger, bumped lock-light
    by the FlowEdge on the hot path."""

    __slots__ = ("pipeline", "from_", "to", "signal", "is_entry",
                 "is_output", "in_balance", "accepted", "accepted_bytes",
                 "batches", "forwarded", "failed", "_lock")

    def __init__(self, pipeline: str, from_: str, to: str, signal: str):
        self.pipeline = pipeline
        self.from_ = from_
        self.to = to
        self.signal = signal
        self.is_entry = False
        self.is_output = False
        # False for per-destination BRANCH edges: their failure counts
        # are per-exporter evidence, excluded from the conservation
        # balance — a fan-out where several branches fail raises one
        # distinct exception per branch, and counting each would push
        # the balance negative (hiding a multi-destination outage as
        # "derived items"); the once-counted balance failure lives on
        # the __output__ edge
        self.in_balance = True
        self.accepted = 0
        self.accepted_bytes = 0
        self.batches = 0
        self.forwarded = 0
        self.failed: dict[str, int] = {}
        self._lock = threading.Lock()

    def offer(self, n: int, nbytes: int) -> None:
        with self._lock:
            self.accepted += n
            self.accepted_bytes += nbytes
            self.batches += 1

    def ok(self, n: int) -> None:
        with self._lock:
            self.forwarded += n

    def fail(self, error_class: str, n: int) -> None:
        with self._lock:
            self.failed[error_class] = self.failed.get(error_class, 0) + n

    def to_dict(self) -> dict[str, Any]:
        with self._lock:
            return {
                "pipeline": self.pipeline, "from": self.from_,
                "to": self.to, "signal": self.signal,
                "in_balance": self.in_balance,
                "accepted": self.accepted,
                "accepted_bytes": self.accepted_bytes,
                "batches": self.batches, "forwarded": self.forwarded,
                "failed": dict(self.failed),
            }


class _PipelineReg:
    """Conservation-boundary membership of one pipeline: which drop
    sites balance against its entry (processors only — a terminal
    connector/exporter dropping does so AFTER the items left the
    pipeline) and where to read in-flight pending counts.

    Registrations ACCUMULATE: two collectors in one process whose
    configs reuse a pipeline name (every node collector names its
    pipeline the same way) share the counters, so pending must sum over
    every live registrant's processors — last-writer-wins would hide
    one collector's buffered spans and read as a false leak. Dead
    weakrefs (reloaded/shut-down graphs) are pruned as they die."""

    __slots__ = ("signal", "processor_names", "terminals", "_procs",
                 "_lock")

    def __init__(self, signal: str):
        self.signal = signal
        self.processor_names: list[str] = []
        self.terminals: list[str] = []
        self._procs: list = []
        # pending() prunes dead weakrefs and is called concurrently by
        # every surface (dashboard poll, flowz, healthcheck, rollups)
        self._lock = threading.Lock()

    def add(self, processors: list, terminals: list) -> None:
        with self._lock:
            live = {id(ref()) for ref in self._procs
                    if ref() is not None}
            for p in processors:
                if p.name not in self.processor_names:
                    self.processor_names.append(p.name)
                if id(p) not in live:
                    self._procs.append(weakref.ref(p))
            for t in terminals:
                if t not in self.terminals:
                    self.terminals.append(t)

    def pending(self) -> int:
        total = 0
        with self._lock:
            alive = []
            procs = []
            for ref in self._procs:
                proc = ref()
                if proc is not None:
                    alive.append(ref)
                    procs.append(proc)
            self._procs = alive
        for proc in procs:
            fp = getattr(proc, "flow_pending", None)
            if fp is not None:
                try:
                    total += int(fp())
                except Exception:  # noqa: BLE001 — telemetry never raises
                    pass
        return total


class FlowLedger:
    """Process-global flow accounting registry (the meter/tracer sibling)."""

    def __init__(self) -> None:
        self.enabled = os.environ.get("ODIGOS_FLOW", "1") != "0"
        self._lock = threading.Lock()
        self._edges: dict[tuple, _EdgeStats] = {}
        # (pipeline, component, signal) -> {reason: count}
        self._drops: dict[tuple, dict[str, int]] = {}
        # (pipeline, component, reason) -> last-drop witness
        self._drop_witness: dict[tuple, dict[str, Any]] = {}
        # (component, queue) -> [current, high-watermark]
        self._watermarks: dict[tuple, list] = {}
        self._pipelines: dict[str, _PipelineReg] = {}
        self._published: dict[str, float] = {}  # delta base for publish()

    # ------------------------------------------------------------ edges

    def edge(self, pipeline: str, from_: str, to: str, signal: str,
             entry: bool = False, output: bool = False,
             balance: bool = True) -> _EdgeStats:
        """Get-or-create the stats of one edge. Stable across hot
        reloads: the rebuilt graph re-binds to the same counters, so
        totals stay conserved over a reload mid-stream."""
        key = (pipeline, from_, to, signal)
        with self._lock:
            st = self._edges.get(key)
            if st is None:
                st = self._edges[key] = _EdgeStats(pipeline, from_, to,
                                                   signal)
            st.is_entry = st.is_entry or entry
            st.is_output = st.is_output or output
            if not balance:
                st.in_balance = False
            return st

    def register_pipeline(self, name: str, processors: list,
                          terminals: list, signal: str) -> None:
        with self._lock:
            reg = self._pipelines.get(name)
            if reg is None:
                reg = self._pipelines[name] = _PipelineReg(signal)
            reg.add(processors, terminals)

    # ------------------------------------------------------------ drops

    def record_drop(self, n: int, reason: str, pipeline: str,
                    component: str, signal: str,
                    blame: Optional[str] = None) -> None:
        if n <= 0 or not self.enabled:
            return
        if reason not in DROP_REASONS:
            raise ValueError(
                f"unknown drop reason {reason!r} (taxonomy: "
                f"{DROP_REASONS})")
        ctx = _active.get()
        with self._lock:
            by_reason = self._drops.setdefault(
                (pipeline, component, signal), {})
            by_reason[reason] = by_reason.get(reason, 0) + n
            self._drop_witness[(pipeline, component, reason)] = {
                "items": n,
                "unix_ts": time.time(),
                "trace_id": f"{ctx[0]:032x}" if ctx else None,
                "span_id": f"{ctx[1]:016x}" if ctx else None,
                **({"blame": blame} if blame else {}),
            }
        # counters live-published (drops are rare — not hot-path cost);
        # the histogram carries the exemplar that links /metrics to the
        # self-trace active when the drop happened
        labels = {"pipeline": pipeline, "component": component,
                  "reason": reason}
        if blame:
            # deadline-burn blame (ISSUE 8): a latency-attribution
            # DIMENSION on the closed taxonomy, never a new reason —
            # unblamed drops keep their exact pre-existing metric keys
            labels["blame"] = blame
        meter.add(labeled_key(DROPPED_METRIC, **labels), n)
        meter.record(labeled_key(DROP_SIZE_METRIC, **labels), float(n),
                     exemplar=(ctx[0], ctx[1]) if ctx else None)
        # black-box timeline: same trace fields as the flowz last-drop
        # witness above (one unified field pair), bursts coalesced
        flight_recorder.record_drop_burst(
            pipeline, component, reason, n, blame=blame,
            trace_id=f"{ctx[0]:032x}" if ctx else None,
            span_id=f"{ctx[1]:016x}" if ctx else None)

    def watermark(self, component: str, queue: str, value: float) -> None:
        if not self.enabled:
            return
        with self._lock:
            wm = self._watermarks.get((component, queue))
            if wm is None:
                self._watermarks[(component, queue)] = [value, value]
            else:
                wm[0] = value
                if value > wm[1]:
                    wm[1] = value

    def watermark_current(self, component: str,
                          queue: str) -> Optional[float]:
        """Latest reported value of one queue watermark (None = never
        reported). The wire receiver's admission gate polls this on the
        pre-decode path, so it is a single dict lookup — never a
        snapshot."""
        with self._lock:
            wm = self._watermarks.get((component, queue))
            return wm[0] if wm is not None else None

    # ----------------------------------------------------- aggregation

    def snapshot(self) -> dict[str, Any]:
        """JSON-able dump: edges, drops (+ last-drop witnesses),
        watermarks, registered pipelines."""
        with self._lock:
            edges = list(self._edges.values())
            drops = [
                {"pipeline": p, "component": c, "signal": s,
                 "reasons": dict(by_reason),
                 "last": {r: dict(self._drop_witness[(p, c, r)])
                          for r in by_reason
                          if (p, c, r) in self._drop_witness}}
                for (p, c, s), by_reason in sorted(self._drops.items())]
            watermarks = [
                {"component": comp, "queue": q,
                 "value": wm[0], "max": wm[1]}
                for (comp, q), wm in sorted(self._watermarks.items())]
            pipelines = {
                name: {"signal": reg.signal,
                       "processors": list(reg.processor_names),
                       "terminals": list(reg.terminals)}
                for name, reg in self._pipelines.items()}
        return {"enabled": self.enabled,
                "edges": [e.to_dict() for e in edges],
                "drops": drops, "watermarks": watermarks,
                "pipelines": pipelines}

    def component_totals(self) -> dict[str, dict[str, Any]]:
        """Per-component failure/drop totals (the rollup's evidence):
        edge failures attribute to the consumer (``to``) that raised."""
        out: dict[str, dict[str, Any]] = {}
        with self._lock:
            edges = list(self._edges.values())
            drops = {k: dict(v) for k, v in self._drops.items()}
        for e in edges:
            d = e.to_dict()
            if d["failed"]:
                agg = out.setdefault(d["to"], {"failed": {}, "dropped": {}})
                for cls, n in d["failed"].items():
                    agg["failed"][cls] = agg["failed"].get(cls, 0) + n
        for (_p, comp, _s), by_reason in drops.items():
            agg = out.setdefault(comp, {"failed": {}, "dropped": {}})
            for reason, n in by_reason.items():
                agg["dropped"][reason] = agg["dropped"].get(reason, 0) + n
        return out

    def conservation(self) -> dict[str, dict[str, Any]]:
        """The per-pipeline balance: ``items_in == items_out + Σ dropped
        + Σ failed + pending``; ``leak`` is the remainder (positive =
        items vanished unaccounted; negative = a generating stage
        created items, normal for metrics-derivation pipelines)."""
        with self._lock:
            regs = dict(self._pipelines)
            edges = list(self._edges.values())
            drops = {k: dict(v) for k, v in self._drops.items()}
        by_pipeline: dict[str, list[dict]] = {}
        for e in edges:
            by_pipeline.setdefault(e.pipeline, []).append(
                dict(e.to_dict(), is_entry=e.is_entry,
                     is_output=e.is_output))
        # failures sum over balance edges only (entry/stage/__output__);
        # branch edges carry per-destination evidence of the SAME
        # exception and would double-count a fan-out failure
        out: dict[str, dict[str, Any]] = {}
        for pname, reg in regs.items():
            p_edges = by_pipeline.get(pname, [])
            items_in = sum(e["accepted"] for e in p_edges if e["is_entry"])
            items_out = sum(e["forwarded"] for e in p_edges
                            if e["is_output"])
            failed: dict[str, int] = {}
            for e in p_edges:
                if not e["in_balance"]:
                    continue
                for cls, n in e["failed"].items():
                    failed[cls] = failed.get(cls, 0) + n
            # only drops INSIDE the conservation boundary (processors;
            # a terminal connector/exporter drop happens after items_out)
            members = set(reg.processor_names) | {ENTRY_NODE}
            dropped: dict[str, int] = {}
            for (p, comp, _s), by_reason in drops.items():
                if p == pname and comp in members:
                    for reason, n in by_reason.items():
                        dropped[reason] = dropped.get(reason, 0) + n
            pending = reg.pending()
            leak = (items_in - items_out - sum(dropped.values())
                    - sum(failed.values()) - pending)
            out[pname] = {
                "signal": reg.signal, "items_in": items_in,
                "items_out": items_out, "dropped": dropped,
                "failed": failed, "pending": pending, "leak": leak,
            }
        return out

    # --------------------------------------------------------- publish

    def publish(self, target=None) -> None:
        """Mirror edge counters into the Meter as ``odigos_flow_*``
        Prometheus counters (delta-advanced so repeated scrapes stay
        monotonic) and watermarks as gauges. Called on scrape — the hot
        path never touches the meter lock."""
        if not self.enabled:
            return
        target = target or meter
        with self._lock:
            edges = [e.to_dict() for e in self._edges.values()]
            watermarks = [(comp, q, wm[1])
                          for (comp, q), wm in self._watermarks.items()]
        updates: list[tuple[str, float]] = []
        for e in edges:
            labels = {"pipeline": e["pipeline"], "from": e["from"],
                      "to": e["to"], "signal": e["signal"]}
            updates.append((labeled_key(ACCEPTED_METRIC, **labels),
                            float(e["accepted"])))
            updates.append((labeled_key(ACCEPTED_BYTES_METRIC, **labels),
                            float(e["accepted_bytes"])))
            updates.append((labeled_key(FORWARDED_METRIC, **labels),
                            float(e["forwarded"])))
            for cls, n in e["failed"].items():
                updates.append((labeled_key(
                    FAILED_METRIC, **labels, error=cls), float(n)))
        with self._lock:
            deltas = []
            for key, total in updates:
                prev = self._published.get(key, 0.0)
                if total > prev:
                    deltas.append((key, total - prev))
                    self._published[key] = total
        for key, delta in deltas:
            target.add(key, delta)
        for comp, q, hwm in watermarks:
            target.set_gauge(labeled_key(WATERMARK_METRIC, component=comp,
                                         queue=q), float(hwm))

    def reset(self) -> None:
        """Test isolation: forget every edge/drop/pipeline. Live graphs
        keep their (now orphaned) stats objects and simply stop being
        visible — the meter.reset() contract."""
        with self._lock:
            self._edges.clear()
            self._drops.clear()
            self._drop_witness.clear()
            self._watermarks.clear()
            self._pipelines.clear()
            self._published.clear()


flow_ledger = FlowLedger()


class FlowContext:
    """The tiny component-facing API: components that shed data name the
    reason; components with queues report their depth. Everything else
    is accounted automatically by the edge wrappers."""

    @staticmethod
    def site() -> Optional[tuple]:
        return _flow_site.get()

    @staticmethod
    def drop(n: int, reason: str, component: Any = None,
             pipeline: Optional[str] = None,
             component_name: Optional[str] = None,
             signal: Optional[str] = None, exc: Any = None,
             blame: Optional[str] = None) -> None:
        """Record ``n`` items intentionally shed for ``reason`` (one of
        :data:`DROP_REASONS`). Attribution order: explicit kwargs, the
        component's graph-stamped ``_flow_site``, then the calling
        edge's contextvar site (shared connectors). ``exc`` marks an
        about-to-be-raised exception as already accounted so the edge
        unwind does not double-count it as failed (memory_limiter's
        reject-then-raise). ``blame`` (ISSUE 8) optionally names the
        latency stage that consumed the budget behind a deadline-driven
        shed — a dimension on the taxonomy, not a new reason."""
        if n <= 0 or not flow_ledger.enabled:
            return
        site = getattr(component, "_flow_site", None) \
            if component is not None else None
        if site is None:
            site = _flow_site.get()
        if pipeline is None:
            pipeline = site[0] if site else "(unattributed)"
        if component_name is None:
            component_name = getattr(component, "name", None) or (
                site[1] if site else "(unknown)")
        if signal is None:
            signal = site[2] if site else "traces"
        if exc is not None:
            FlowContext.mark_counted(exc, pipeline)
        flow_ledger.record_drop(int(n), reason, pipeline, component_name,
                                signal, blame=blame)

    @staticmethod
    def mark_counted(exc: Any, pipeline: str) -> None:
        """Mark ``exc`` as flow-accounted for ``pipeline`` (the edge
        wrappers skip failed-counting for marked pipelines)."""
        pipes = getattr(exc, "_odigos_flow_pipelines", None)
        if pipes is None:
            try:
                pipes = exc._odigos_flow_pipelines = set()
            except Exception:  # noqa: BLE001 — slotted exception
                return
        pipes.add(pipeline)

    @staticmethod
    def watermark(component: str, queue: str, value: float) -> None:
        flow_ledger.watermark(component, queue, value)

    @staticmethod
    def watermark_name(component: Any) -> str:
        """Pipeline-qualified watermark identity for a graph component:
        ``<pipeline>/<id>`` from the graph-stamped ``_flow_site``, bare
        id before stamping. Admission gates read watermark values LIVE,
        so two pipelines' same-named stages must never share a key
        (last-writer-wins would let a quiet stage mask a saturated
        one). One derivation for every producer — batch, memory
        limiter, future buffering stages — so the gate's config keys
        cannot drift from the reported names."""
        site = getattr(component, "_flow_site", None)
        name = getattr(component, "name", "(unknown)")
        return f"{site[0]}/{name}" if site else name


class FlowEdge:
    """Consumer wrapper on one graph edge. Counts accepted at offer
    time, forwarded on clean return, failed-with-error-class on raise
    (once per pipeline per exception — see the marker contract), and
    scopes the drop-attribution site around the downstream consume."""

    __slots__ = ("inner", "stats", "_site")

    def __init__(self, inner: Any, stats: _EdgeStats, site: tuple):
        self.inner = inner
        self.stats = stats
        self._site = site

    def consume(self, batch: Any) -> None:
        if not flow_ledger.enabled:
            self.inner.consume(batch)
            return
        st = self.stats
        n = _batch_items(batch)
        st.offer(n, _batch_nbytes(batch))
        token = _flow_site.set(self._site)
        try:
            self.inner.consume(batch)
        except Exception as e:
            if not st.in_balance:
                # per-destination branch evidence; the balance counts
                # this exception once at the __output__ edge (fan-out
                # raises one distinct exception per failed branch)
                st.fail(type(e).__name__, n)
                raise
            pipes = getattr(e, "_odigos_flow_pipelines", None)
            if pipes is None:
                try:
                    pipes = e._odigos_flow_pipelines = set()
                except Exception:  # noqa: BLE001 — unmarkable exception
                    pipes = None
            if pipes is None or st.pipeline not in pipes:
                if pipes is not None:
                    pipes.add(st.pipeline)
                st.fail(type(e).__name__, n)
            raise
        finally:
            _flow_site.reset(token)
        st.ok(n)


# ------------------------------------------------------- health rollup


class HealthRollup:
    """Per-component condition rollup over one built graph — the
    odigos-style replacement for the bare ``healthy()`` boolean.

    Each component gets ``{status, reason, message, last_transition}``:

    * base status from ``Component.health()`` (``Unhealthy`` iff
      ``healthy()`` is False — the healthcheck 200/503 contract is
      preserved exactly);
    * ledger-derived ``Degraded`` while recent evidence exists: new edge
      failures into the component (``ConsumeErrors``), new
      ``memory_limited`` drops (``MemoryPressure``), new ``queue_full``
      drops (``QueueSaturation``) — each held for ``degrade_window_s``
      after the last occurrence, so alternating pollers (healthcheck,
      zpages, dashboard) see the same answer;
    * one pseudo-component per pipeline (``pipeline/<name>``) carrying
      the conservation verdict: ``ConservationLeak`` when a positive
      leak persists across two evaluations with no counter movement
      (transient in-flight imbalance never flaps it).

    ``last_transition`` is preserved while (status, reason) are
    unchanged — k8s ``lastTransitionTime`` semantics; ``adopt()`` carries
    it across a hot-reload graph swap.
    """

    def __init__(self, graph: Any = None, degrade_window_s: float = 60.0,
                 clock: Callable[[], float] = time.monotonic):
        self._graph = graph
        self.degrade_window_s = degrade_window_s
        self._clock = clock
        self._lock = threading.Lock()
        # component -> {status, reason, message, last_transition}
        self._state: dict[str, dict[str, Any]] = {}
        # component -> (last failed total, last mem drops, last queue drops)
        self._seen: dict[str, tuple[int, int, int]] = {}
        # component -> reason -> last time new evidence was seen
        self._evidence_ts: dict[str, dict[str, float]] = {}
        self._evidence_msg: dict[str, dict[str, str]] = {}
        # pipeline -> (leak, items_in) of the previous evaluation
        self._last_leak: dict[str, tuple[int, int]] = {}

    def set_graph(self, graph: Any) -> None:
        self._graph = graph

    def adopt(self, other: "HealthRollup") -> None:
        """Carry condition state across a graph swap (hot reload): same
        component names keep their last-transition history."""
        with other._lock:
            state = {k: dict(v) for k, v in other._state.items()}
            seen = dict(other._seen)
            ev_ts = {k: dict(v) for k, v in other._evidence_ts.items()}
            ev_msg = {k: dict(v) for k, v in other._evidence_msg.items()}
            leaks = dict(other._last_leak)
        with self._lock:
            self._state.update(state)
            self._seen.update(seen)
            self._evidence_ts.update(ev_ts)
            self._evidence_msg.update(ev_msg)
            self._last_leak.update(leaks)

    # ---------------------------------------------------------- evaluate

    def _upsert(self, name: str, status: str, reason: str,
                message: str) -> dict[str, Any]:
        prev = self._state.get(name)
        if prev is not None and (prev["status"], prev["reason"]) == (
                status, reason):
            prev["message"] = message
            return prev
        cond = {"component": name, "status": status, "reason": reason,
                "message": message, "last_transition": time.time()}
        self._state[name] = cond
        return cond

    def _degradation(self, name: str,
                     totals: dict[str, Any],
                     now: float,
                     evidence_key: Optional[str] = None
                     ) -> Optional[tuple[str, str]]:
        """(reason, message) when recent ledger evidence degrades the
        component, else None. Evidence = counter movement since the
        previous evaluation; held for degrade_window_s. ``name`` keys
        the per-component delta state; ``evidence_key`` (default: name)
        looks up the ledger totals — per-pipeline processor instances
        carry qualified condition names but share the bare-name ledger
        aggregate."""
        t = totals.get(evidence_key or name) or {"failed": {},
                                                 "dropped": {}}
        failed_total = sum(t["failed"].values())
        mem = t["dropped"].get("memory_limited", 0)
        qfull = t["dropped"].get("queue_full", 0)
        prev = self._seen.get(name, (0, 0, 0))
        ts = self._evidence_ts.setdefault(name, {})
        msg = self._evidence_msg.setdefault(name, {})
        if failed_total > prev[0]:
            ts["ConsumeErrors"] = now
            top = max(t["failed"], key=t["failed"].get)
            msg["ConsumeErrors"] = (
                f"{failed_total - prev[0]} items failed "
                f"(top error: {top})")
        if mem > prev[1]:
            ts["MemoryPressure"] = now
            msg["MemoryPressure"] = \
                f"{mem - prev[1]} items rejected under memory pressure"
        if qfull > prev[2]:
            ts["QueueSaturation"] = now
            msg["QueueSaturation"] = \
                f"{qfull - prev[2]} items shed on a full queue"
        self._seen[name] = (failed_total, mem, qfull)
        for reason in ("ConsumeErrors", "MemoryPressure",
                       "QueueSaturation"):
            when = ts.get(reason)
            if when is not None and now - when < self.degrade_window_s:
                return reason, msg.get(reason, "")
        return None

    def evaluate(self, totals: Optional[dict] = None,
                 balances: Optional[dict] = None) -> list[dict[str, Any]]:
        """Compute (and persist transitions of) every condition.
        ``totals``/``balances`` accept the global ledger aggregates
        precomputed by a caller evaluating several rollups in one pass
        (active_conditions) — one edge walk instead of one per rollup."""
        now = self._clock()
        graph = self._graph
        components = list(graph.all_components()) if graph is not None \
            else []
        if totals is None:
            totals = flow_ledger.component_totals()
        if balances is None:
            balances = flow_ledger.conservation()
        if graph is not None:
            # the ledger is process-global; this rollup answers for ITS
            # graph's pipelines only (a node collector's leak must not
            # degrade the gateway's health, nor duplicate conditions
            # when several collectors share the process)
            own = set(graph.pipeline_processors)
            balances = {p: b for p, b in balances.items() if p in own}
        out: list[dict[str, Any]] = []
        with self._lock:
            live: set[str] = set()
            for comp in components:
                # per-pipeline processors share their config id across
                # pipelines (two 'batch' instances): qualify the
                # condition key with the graph-stamped pipeline so one
                # instance's state never masks another's (an Unhealthy
                # row overwritten by a Healthy same-named row would hide
                # from worst() and churn last_transition)
                site = getattr(comp, "_flow_site", None)
                key = f"{site[0]}/{comp.name}" if site else comp.name
                live.add(key)
                # every Component defines health() (components/api.py);
                # the fallback only covers duck-typed test doubles
                health = getattr(comp, "health", None)
                status, reason, message = health() if health is not None \
                    else (HEALTHY, "Running", "")
                if status == HEALTHY:
                    deg = self._degradation(key, totals, now,
                                            evidence_key=comp.name)
                    if deg is not None:
                        status, (reason, message) = DEGRADED, deg
                out.append(dict(self._upsert(key, status, reason,
                                             message)))
            # scoring engines are process-scoped, not graph components:
            # their queue_full drops (recorded as engine/<model> on the
            # "requests" signal) surface as pseudo-components so a
            # saturated queue actually reaches Degraded(QueueSaturation).
            # Failover supervisors (ISSUE 13) surface on the same rows:
            # Degraded(ModelFailover) while a breaker serves its CPU
            # fallback, back to an explicit Healthy on recovery — the
            # chaos oracle asserts that round trip. Lazy import: the
            # serving package imports this module at load.
            try:
                from ..serving.failover import failover_conditions

                fo_rows = failover_conditions()
            except ImportError:  # pragma: no cover — serving not loaded
                fo_rows = {}
            engine_rows = {n for n in totals if n.startswith("engine/")}
            engine_rows.update(fo_rows)
            for name in sorted(engine_rows):
                live.add(name)
                fo = fo_rows.get(name)
                if fo is not None and fo[0] != HEALTHY:
                    # an active failover outranks ledger evidence: the
                    # breaker names the exact failure mode
                    status, reason, message = fo
                else:
                    deg = self._degradation(name, totals, now)
                    if deg is not None:
                        status, (reason, message) = DEGRADED, deg
                    else:
                        status, reason, message = HEALTHY, "Running", ""
                out.append(dict(self._upsert(name, status, reason,
                                             message)))
            for pname, bal in balances.items():
                node = f"pipeline/{pname}"
                live.add(node)
                leak = bal["leak"]
                prev = self._last_leak.get(pname)
                stable = (leak > 0 and prev is not None
                          and prev == (leak, bal["items_in"]))
                self._last_leak[pname] = (leak, bal["items_in"])
                if stable:
                    prev_cond = self._state.get(node)
                    leaking_already = (
                        prev_cond is not None
                        and prev_cond["reason"] == "ConservationLeak")
                    cond = self._upsert(
                        node, DEGRADED, "ConservationLeak",
                        f"{leak} items unaccounted "
                        f"(in={bal['items_in']} out={bal['items_out']} "
                        f"dropped={sum(bal['dropped'].values())} "
                        f"failed={sum(bal['failed'].values())} "
                        f"pending={bal['pending']})")
                    if not leaking_already:
                        # freeze on the TRANSITION into the leak, not
                        # on every evaluation of a standing one
                        flight_recorder.trigger(
                            "conservation_leak", rule=node,
                            detail=f"{pname}: {leak} items "
                                   f"unaccounted "
                                   f"(in={bal['items_in']} "
                                   f"out={bal['items_out']})")
                else:
                    cond = self._upsert(
                        node, HEALTHY, "Conserved",
                        f"in={bal['items_in']} out={bal['items_out']}")
                out.append(dict(cond))
            # SLO burn conditions (ISSUE 8): one slo/<pipeline> row per
            # configured SLO, scoped to this rollup's graph like the
            # conservation rows. Fresh burn math per evaluation (the
            # tracker's windows are time-pruned), so alternating pollers
            # agree and a drained fast window clears the condition.
            from .latency import latency_ledger

            own_pipelines = set(graph.pipeline_processors) \
                if graph is not None else None
            for pname, slo in latency_ledger.slo_status().items():
                if own_pipelines is not None \
                        and pname not in own_pipelines:
                    continue
                node = f"slo/{pname}"
                live.add(node)
                if slo["burning"]:
                    cond = self._upsert(
                        node, DEGRADED, "SLOBurn",
                        f"{slo['worst_objective']} burning at "
                        f"{slo['fast']['burn']}x over "
                        f"{slo['fast']['window_s']:g}s "
                        f"(slow {slo['slow']['burn']}x over "
                        f"{slo['slow']['window_s']:g}s)")
                else:
                    cond = self._upsert(
                        node, HEALTHY, "WithinBudget",
                        f"fast burn {slo['fast']['burn']}x / "
                        f"slow {slo['slow']['burn']}x")
                out.append(dict(cond))
            # fleet alert conditions (ISSUE 10): one alert/<name> row
            # per rule THIS graph's config declared (service.alerts),
            # evaluated fresh against the series store like the SLO
            # burn rows — firing critical maps to Unhealthy, firing
            # warning/info to Degraded, pending/inactive stays Healthy
            # (a pending rule has not confirmed its for: hold yet).
            own_alerts = getattr(graph, "alert_rule_names", None) \
                if graph is not None else None
            if own_alerts:
                from .fleet import alert_engine

                for rule in alert_engine.evaluate():
                    if rule["name"] not in own_alerts:
                        continue
                    node = f"alert/{rule['name']}"
                    live.add(node)
                    if rule["firing"]:
                        status = UNHEALTHY \
                            if rule["severity"] == "critical" else DEGRADED
                        cond = self._upsert(
                            node, status, "AlertFiring",
                            f"{rule['expr']} (value "
                            f"{rule['value']}, series "
                            f"{rule['series'] or '-'})")
                    elif rule["state"] == "pending":
                        cond = self._upsert(
                            node, HEALTHY, "AlertPending",
                            f"breaching, holding for_s="
                            f"{rule['for_s']:g}")
                    else:
                        cond = self._upsert(
                            node, HEALTHY, "WithinThreshold",
                            f"value {rule['value']}")
                    out.append(dict(cond))
            # closed-loop actuator rows (ISSUE 15): one actuator/<rule>
            # row while an actuation is in flight (CanaryInFlight /
            # Promoting) — process-scoped like the engine rows, gone
            # the moment the actuation resolves (the canary round trip
            # the chaos matrix asserts). sys.modules-gated: a rollup in
            # a process that never armed the actuator imports nothing.
            import sys as _sys

            _act = _sys.modules.get("odigos_tpu.controlplane.actuator")
            if _act is not None:
                for name, (status, reason, message) in sorted(
                        _act.actuator_conditions().items()):
                    live.add(name)
                    out.append(dict(self._upsert(name, status, reason,
                                                 message)))
            # prune components gone from the graph (reload removed them)
            for name in list(self._state):
                if name not in live:
                    del self._state[name]
        out.sort(key=lambda c: c["component"])
        return out

    def condition_for(self, component: str) -> Optional[dict[str, Any]]:
        with self._lock:
            cond = self._state.get(component)
            return dict(cond) if cond is not None else None

    def worst(self) -> tuple[str, str, str]:
        """(status, reason, message) of the worst current condition —
        the one-line summary the control-plane store records."""
        rank = {HEALTHY: 0, DEGRADED: 1, UNHEALTHY: 2}
        worst = (HEALTHY, "AllHealthy", "")
        with self._lock:
            for cond in self._state.values():
                if rank.get(cond["status"], 0) > rank.get(worst[0], 0):
                    worst = (cond["status"], cond["reason"],
                             f"{cond['component']}: {cond['message']}"
                             if cond["message"] else cond["component"])
        return worst


# live rollups, weak-registered by running Collectors so graph-less
# surfaces (frontend /api/flow, diagnose) can read conditions
_rollups: "weakref.WeakSet[HealthRollup]" = weakref.WeakSet()
_rollups_lock = threading.Lock()


def register_rollup(rollup: HealthRollup) -> None:
    with _rollups_lock:
        _rollups.add(rollup)


def unregister_rollup(rollup: HealthRollup) -> None:
    with _rollups_lock:
        _rollups.discard(rollup)


def iter_rollups() -> Iterable[HealthRollup]:
    with _rollups_lock:
        return list(_rollups)


_STATUS_RANK = {HEALTHY: 0, DEGRADED: 1, UNHEALTHY: 2}


def active_conditions() -> list[dict[str, Any]]:
    """Merged conditions of every live registered rollup (the
    graph-less surfaces' view). The global aggregates are computed ONCE
    and passed into each rollup, and same-named conditions are deduped
    keeping the worst status: process-scoped pseudo-components
    (``engine/<model>``) appear in every rollup, and collectors sharing
    a pipeline name (node collectors) would otherwise list the same
    ``pipeline/<name>`` row once per collector."""
    totals = flow_ledger.component_totals()
    balances = flow_ledger.conservation()
    merged: dict[str, dict[str, Any]] = {}
    for rollup in iter_rollups():
        for cond in rollup.evaluate(totals=totals, balances=balances):
            name = cond["component"]
            prev = merged.get(name)
            if prev is None or _STATUS_RANK.get(cond["status"], 0) \
                    > _STATUS_RANK.get(prev["status"], 0):
                merged[name] = cond
    out = list(merged.values())
    out.sort(key=lambda c: c["component"])
    return out
