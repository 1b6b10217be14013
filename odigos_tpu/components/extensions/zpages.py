"""``zpages`` extension — live in-process diagnostics pages.

Upstream's zpagesextension (collector/builder-config.yaml:9) serves
``/debug/pipelinez`` etc. from inside the running collector.  Ours
serves JSON (terminal-first operators curl it):

* ``/debug/pipelinez``   — pipeline topology: receivers, per-pipeline
                           processor chains, exporters/connectors
* ``/debug/servicez``    — component inventory with health
* ``/debug/extensionz``  — running extensions
* ``/debug/tracez``      — self-trace ring summarized per span name
                           (count, errors, p50/p99/max ms, a recent
                           exemplar trace id each); ``?trace_id=<hex>``
                           pivots to that trace's full span list — the
                           landing page for ``/metrics`` ``# EXEMPLAR``
                           annotations (upstream zpages' tracez role)
* ``/debug/flowz``       — the flow ledger (ISSUE 5): per-edge
                           accepted/forwarded/failed counters, named
                           drops with last-drop trace witnesses, queue
                           high-watermarks, the per-pipeline
                           conservation balance, and the component
                           condition rollup
* ``/debug/latencyz``    — latency attribution (ISSUE 8): the per-
                           pipeline stage waterfall (p50/p95/p99 per
                           stage), the deadline-burn table (fraction of
                           budget per stage + expiry blames), recent
                           frame timelines, and the SLO burn-rate
                           status
* ``/debug/fleetz``      — the fleet plane (ISSUE 10): per-collector
                           health rollups, worst-of per group, alert
                           rule states with fired/cleared history, and
                           the flap-guarded sizing recommendations
* ``/debug/actuatorz``   — the closed-loop actuator (ISSUE 15): armed
                           state, in-flight canary/promotion with its
                           judgment window, the bounded action history
                           (proposals, canaries, promotions,
                           rollbacks, refusals), and the knob/refusal
                           table
* ``/debug/incidentz``   — the flight recorder (ISSUE 16): incident
                           store summaries, the recent black-box event
                           timeline, and the trigger registry;
                           ``?id=<incident>`` pivots to that incident's
                           full frozen bundle (event lookback + tail,
                           series excerpt, worst-frame trace
                           exemplars, config hash, conditions)
* ``/debug/xlaz``        — the device plane (ISSUE 20): the XLA cost/
                           efficiency ledger (expected FLOPs/bytes,
                           flop-waste, achieved efficiency per jit
                           site × shape bucket), recent compile events
                           with trace ids, the sampled intra-fused
                           attribution waterfall per engine, and the
                           device-resident table/plan footprint;
                           ``?trace_s=<1-30>`` captures one profiler
                           trace of that many seconds from this process
                           and answers with its directory (409 while
                           one runs)

Debug-only: binds loopback. Config: ``endpoint``/``host``/``port``.
"""

from __future__ import annotations

from typing import Any

from ...pdata.spans import StatusCode
from ...selftelemetry.tracer import tracer
from ..api import ComponentKind, Factory, register
from .httpbase import HttpExtension, Page


class ZPagesExtension(HttpExtension):
    def __init__(self, name: str, config: dict[str, Any]):
        super().__init__(name, config)
        self._graph = None

    def set_graph(self, graph) -> None:
        self._graph = graph

    def _pipelinez(self, q: dict[str, str]) -> tuple[int, dict]:
        g = self._graph
        if g is None:
            return 503, {}
        return 200, {
            "receivers": sorted(g.receivers),
            "pipelines": {
                pname: [p.name for p in procs]
                for pname, procs in g.pipeline_processors.items()},
            "exporters": sorted(g.exporters),
            "connectors": sorted(g.connectors),
            "pipeline_order": list(g.pipeline_order),
        }

    def _servicez(self, q: dict[str, str]) -> tuple[int, dict]:
        g = self._graph
        if g is None:
            return 503, {}
        return 200, {"components": [
            {"name": c.name, "healthy": bool(c.healthy()),
             "type": type(c).__name__}
            for c in g.all_components()]}

    def _extensionz(self, q: dict[str, str]) -> tuple[int, dict]:
        g = self._graph
        if g is None:
            return 503, {}
        return 200, {"extensions": sorted(g.extensions)}

    def _tracez(self, q: dict[str, str]) -> tuple[int, dict]:
        if "trace_id" in q:  # exemplar pivot: one trace, all its spans
            return 200, tracer.trace(q["trace_id"])
        by_name: dict[str, dict[str, Any]] = {}
        for s in tracer.ring.snapshot():
            agg = by_name.get(s.name)
            if agg is None:
                agg = by_name[s.name] = {
                    "count": 0, "errors": 0, "durations": [],
                    "latest_trace_id": "", "latest_start": -1}
            agg["count"] += 1
            agg["errors"] += 1 if s.status == StatusCode.ERROR else 0
            agg["durations"].append(s.duration_ns)
            if s.start_unix_nano > agg["latest_start"]:
                agg["latest_start"] = s.start_unix_nano
                agg["latest_trace_id"] = f"{s.trace_id:032x}"
        rows = []
        for name, agg in sorted(by_name.items()):
            ds = sorted(agg["durations"])
            rows.append({
                "span": name,
                "count": agg["count"],
                "errors": agg["errors"],
                "p50_ms": round(ds[len(ds) // 2] / 1e6, 4),
                "p99_ms": round(ds[min(int(0.99 * len(ds)),
                                       len(ds) - 1)] / 1e6, 4),
                "max_ms": round(ds[-1] / 1e6, 4),
                "exemplar_trace_id": agg["latest_trace_id"],
            })
        return 200, {"enabled": tracer.enabled,
                     "spans_buffered": len(tracer.ring),
                     "by_span": rows}

    def _flowz(self, q: dict[str, str]) -> tuple[int, dict]:
        from ...selftelemetry.flow import flow_ledger

        out = flow_ledger.snapshot()
        out["conservation"] = flow_ledger.conservation()
        g = self._graph
        rollup = getattr(g, "flow_health", None) if g is not None else None
        if rollup is not None:
            out["conditions"] = rollup.evaluate()
        return 200, out

    def _latencyz(self, q: dict[str, str]) -> tuple[int, dict]:
        from ...selftelemetry.latency import latency_ledger

        out = latency_ledger.snapshot()
        g = self._graph
        rollup = getattr(g, "flow_health", None) if g is not None else None
        if rollup is not None:
            out["conditions"] = [
                c for c in rollup.evaluate()
                if c["component"].startswith("slo/")]
        return 200, out

    def _fleetz(self, q: dict[str, str]) -> tuple[int, dict]:
        from ...selftelemetry.fleet import fleet_plane

        return 200, fleet_plane.api_snapshot()

    def _actuatorz(self, q: dict[str, str]) -> tuple[int, dict]:
        from ...controlplane.actuator import fleet_actuator

        return 200, fleet_actuator.api_snapshot()

    def _incidentz(self, q: dict[str, str]) -> tuple[int, dict]:
        from ...selftelemetry.flightrecorder import flight_recorder

        if "id" in q:  # pivot: one incident's full frozen bundle
            bundle = flight_recorder.incident(q["id"])
            if bundle is None:
                return 404, {"error": f"no incident {q['id']!r}"}
            return 200, bundle
        out = flight_recorder.api_snapshot()
        out["recent_events"] = flight_recorder.recent_events()
        return 200, out

    def _xlaz(self, q: dict[str, str]) -> tuple[int, dict]:
        from ...selftelemetry.profiler import capture_trace, device_snapshot

        if "trace_s" in q:  # one profiler trace of this process
            try:
                seconds = float(q["trace_s"])
            except ValueError:
                return 400, {"error": "trace_s must be a number"}
            return capture_trace(seconds)
        return 200, device_snapshot()

    def pages(self) -> dict[str, Page]:
        return {"/debug/pipelinez": self._pipelinez,
                "/debug/servicez": self._servicez,
                "/debug/extensionz": self._extensionz,
                "/debug/tracez": self._tracez,
                "/debug/flowz": self._flowz,
                "/debug/latencyz": self._latencyz,
                "/debug/fleetz": self._fleetz,
                "/debug/actuatorz": self._actuatorz,
                "/debug/incidentz": self._incidentz,
                "/debug/xlaz": self._xlaz}


register(Factory(
    type_name="zpages",
    kind=ComponentKind.EXTENSION,
    create=ZPagesExtension,
    default_config=lambda: {"port": 0},
))
