"""Gateway collector config assembly.

Reference behavior being reproduced (common/pipelinegen/config_builder.go):

* ``GetBasicConfig`` (:272): otlp receiver + ``resource/odigos-version``
  processor + generic batch processor + memory_limiter.
* ``CalculateGatewayConfig`` (:34): run every destination's configer
  (ModifyConfig) to create destination pipelines; wire a ``forward/<pipe>``
  connector into each (:99-108) and append the generic batch processor
  (:110); track per-signal enablement (:118-141); build data-stream
  pipelines fed by the router connector (pipeline_builder.go:13); insert
  root pipelines per enabled signal (:184 — receivers [otlp], processors
  [memory_limiter, resource/odigos-version, user processors...], exporter =
  router connector); optional servicegraph pipeline (:231); self-telemetry
  (odigostrafficmetrics appended to every pipeline,
  autoscaler/controllers/clustercollector/configmap.go:86-126).

North-star extension (not in the reference): when the anomaly stage is
enabled, the root traces pipeline gets ``tpuanomaly`` before the router and
an ``anomalyrouter`` connector routes tagged spans to a dedicated
``traces/<anomaly-stream>`` pipeline — behind the same factory seam, so a
config generated with ``anomaly.enabled=False`` is byte-identical to a
build without the TPU components registered.
"""

from __future__ import annotations

import copy
import dataclasses
from dataclasses import dataclass, field
from typing import Any, Optional

from ..components.api import Signal
from ..config.model import (
    AlertRuleConfiguration, AnomalyStageConfiguration,
    SelfTelemetryConfiguration)
from ..destinations.configers import ConfigerError, modify_config
from ..destinations.registry import Destination

GenericMap = dict[str, Any]

SIGNALS = (Signal.TRACES, Signal.METRICS, Signal.LOGS)
GENERIC_BATCH = "batch"
VERSION_RESOURCE_PROCESSOR = "resource/odigos-version"
SMALL_BATCHES_PROCESSOR = "batch/small-batches"
TRAFFIC_METRICS = "odigostrafficmetrics"
SERVICEGRAPH_CONNECTOR = "servicegraph"


@dataclass(frozen=True)
class DataStreamDestination:
    destination_id: str


@dataclass(frozen=True)
class SourceRef:
    """A workload identity routed to a stream (Source CR analog)."""

    namespace: str
    kind: str  # deployment | statefulset | daemonset | cronjob
    name: str

    def as_dict(self) -> dict[str, str]:
        return {"namespace": self.namespace, "kind": self.kind,
                "name": self.name}


@dataclass(frozen=True)
class DataStream:
    """A named routing group (datastreams.go:21): sources are mapped to
    streams; each stream fans out to its member destinations. A stream
    named ``default`` receives telemetry from unmapped sources (router's
    default_pipelines)."""

    name: str
    destinations: tuple[DataStreamDestination, ...] = ()
    sources: tuple[SourceRef, ...] = ()


@dataclass
class GatewayOptions:
    service_graph_disabled: bool = False
    cluster_metrics_enabled: bool = False
    small_batches: Optional[GenericMap] = None  # small-batches profile config
    anomaly: Optional[AnomalyStageConfiguration] = None
    self_telemetry: bool = True
    # continuous profiler + device-runtime telemetry knobs (ISSUE 3);
    # None or all-disabled renders nothing. Named telemetry_config, NOT
    # selftelemetry: a one-underscore slip against the pre-existing
    # self_telemetry bool (the dogfood-receiver toggle above) would
    # silently toggle the wrong subsystem.
    telemetry_config: Optional[SelfTelemetryConfiguration] = None
    ui_endpoint: str = "ui.odigos-system:4317"  # otlp/ui stream target
    # declarative fleet alert rules (ISSUE 10): AlertRuleConfiguration
    # list rendered as the service.alerts stanza (empty/None renders
    # nothing — existing configs stay byte-identical); evaluated by the
    # fleet plane's alert engine, surfaced as alert/<name> conditions
    alerts: Optional[list] = None
    # export retry/spill (ISSUE 13): a mapping ({} = defaults) stamped
    # as the ``retry:`` stanza of every destination exporter —
    # build_graph wraps those in the bounded jittered-backoff
    # RetryQueue. None renders nothing (byte-stable configs).
    export_retry: Optional[dict] = None
    # closed-loop actuator (ISSUE 15): a mapping rendered as the
    # service.actuator stanza (validated at graph load); None renders
    # nothing — the loop stays open unless the operator closes it
    actuator: Optional[dict] = None
    # extra processor ids (already configured in `processors`) to run in the
    # root pipeline per signal, e.g. compiled Actions.
    root_processors: dict[Signal, list[str]] = field(default_factory=dict)


@dataclass
class ResourceStatuses:
    """Per-CR reconcile outcome (config.ResourceStatuses analog): None =
    success, str = error message surfaced on the Destination/Processor CR."""

    destination: dict[str, Optional[str]] = field(default_factory=dict)
    processor: dict[str, Optional[str]] = field(default_factory=dict)


def config_node_hashes(config: GenericMap) -> dict[str, str]:
    """Per-node content fingerprints of a (generated) collector config:
    one sha256 of canonical JSON per component id (``processors/batch``,
    ``receivers/otlp``, ...), per pipeline (``pipelines/traces/in``) and
    per service stanza (``service/alerts``...).

    This is the incremental-reload contract pipelinegen owes the differ
    (ISSUE 14): node identities are STABLE across regenerations — the
    builder derives every id deterministically from destination/stream/
    processor inputs, never from counters or ordering accidents — so a
    re-render with unchanged inputs hashes identically node for node
    and ``pipeline/configdiff.diff_configs`` classifies it all-keep.
    The soak's ``--reload-storm`` embeds the changed-hash set per
    reload to prove exactly which nodes a config push touched, and
    tests pin the regeneration-stability property. One canonical hash
    rule shared with the ConfigMap watcher (utils/canonical.py), so
    the node fingerprints and the watcher's whole-config hash can
    never disagree on what counts as a change."""
    from ..utils.canonical import content_hash as _h

    hashes: dict[str, str] = {}
    for section in ("receivers", "processors", "exporters",
                    "connectors", "extensions"):
        for cid, ccfg in (config.get(section) or {}).items():
            hashes[f"{section}/{cid}"] = _h(ccfg)
    svc = config.get("service") or {}
    for pname, pcfg in (svc.get("pipelines") or {}).items():
        hashes[f"pipelines/{pname}"] = _h(pcfg)
    for stanza in sorted(set(svc) - {"pipelines"}):
        hashes[f"service/{stanza}"] = _h(svc[stanza])
    return hashes


def changed_node_hashes(old: GenericMap, new: GenericMap) -> list[str]:
    """Node keys whose content hash differs between two configs (added
    and removed nodes count as changed) — the one-line answer to "what
    did this config push actually touch"."""
    oh, nh = config_node_hashes(old), config_node_hashes(new)
    return sorted(k for k in set(oh) | set(nh) if oh.get(k) != nh.get(k))


def router_connector_name(signal: Signal) -> str:
    return f"odigosrouter/{signal.value}"


def root_pipeline_name(signal: Signal) -> str:
    return f"{signal.value}/in"


def signals_root_pipeline_names() -> list[str]:
    return [root_pipeline_name(s) for s in SIGNALS]


def basic_config() -> GenericMap:
    """GetBasicConfig (:272): the invariant prefix of every gateway config."""
    return {
        "receivers": {
            "otlp": {
                "protocols": {
                    "grpc": {"endpoint": "0.0.0.0:4317",
                             "max_recv_msg_size_mib": 128},
                    "http": {"endpoint": "0.0.0.0:4318"},
                },
            },
        },
        "processors": {
            VERSION_RESOURCE_PROCESSOR: {
                "attributes": [{"key": "odigos.version",
                                "value": "${ODIGOS_VERSION}",
                                "action": "upsert"}],
            },
            GENERIC_BATCH: {},
            "memory_limiter": {},
        },
        "exporters": {},
        "connectors": {},
        "extensions": {},
        "service": {
            "extensions": [],
            "pipelines": {},
        },
    }


def build_gateway_config(
    destinations: list[Destination],
    processors: list[GenericMap] | None = None,
    data_streams: list[DataStream] | None = None,
    options: GatewayOptions | None = None,
) -> tuple[GenericMap, ResourceStatuses, list[Signal]]:
    """The CalculateGatewayConfig analog. ``processors`` entries are dicts:
    {"id": str, "type": str, "signals": [..], "config": {...}} (compiled from
    Processor/Action CRs by the autoscaler). Returns (config, statuses,
    enabled_signals)."""
    options = options or GatewayOptions()
    processors = processors or []
    data_streams = list(data_streams or [])
    if not data_streams:
        # every install has a default stream catching unmapped sources and
        # fanning out to all destinations (datastreams.go default stream)
        data_streams = [DataStream("default", tuple(
            DataStreamDestination(d.id) for d in destinations))]
    config = basic_config()
    status = ResourceStatuses()

    # --- user/action processors -> config + per-signal root chains
    signal_processors: dict[Signal, list[str]] = {s: [] for s in SIGNALS}
    for proc in processors:
        pid = proc.get("id") or proc.get("type")
        ptype = proc.get("type")
        if not pid or not ptype:
            status.processor[str(pid)] = "processor missing id/type"
            continue
        key = pid if pid.split("/", 1)[0] == ptype else f"{ptype}/{pid}"
        config["processors"][key] = dict(proc.get("config") or {})
        # absent/None/empty signals all mean "every signal"
        for sig_name in (proc.get("signals") or [s.value for s in SIGNALS]):
            try:
                sig = Signal(sig_name)
            except ValueError:
                status.processor[pid] = f"unknown signal {sig_name}"
                continue
            signal_processors[sig].append(key)
        status.processor.setdefault(pid, None)
    for sig, extra in (options.root_processors or {}).items():
        signal_processors[sig].extend(extra)

    # --- destinations -> exporters + destination pipelines + forward conns
    dest_forward_connectors: dict[str, list[str]] = {}
    enabled: set[Signal] = set()
    small_batches = options.small_batches
    if small_batches:
        config["processors"][SMALL_BATCHES_PROCESSOR] = {
            "send_batch_size": small_batches.get("send_batch_size", 100),
            "timeout_ms": small_batches.get("timeout_ms", 100),
        }
    for dest in destinations:
        # configers run against a scratch copy: a recipe that fails after
        # partially mutating the config must leave no orphan exporters or
        # extensions behind (the destination is reported failed instead)
        scratch = copy.deepcopy(config)
        try:
            pipeline_names = modify_config(dest, scratch)
        except (ConfigerError, KeyError) as e:
            status.destination[dest.id] = str(e)
            continue
        config = scratch
        for pname in pipeline_names:
            conn = f"forward/{pname}"
            dest_forward_connectors.setdefault(dest.id, []).append(conn)
            config["connectors"][conn] = {}
            pipe = config["service"]["pipelines"][pname]
            pipe["receivers"].append(conn)
            pipe["processors"].append(GENERIC_BATCH)
            sig = Signal(pname.split("/", 1)[0])
            if sig == Signal.TRACES and small_batches:
                pipe["processors"].append(SMALL_BATCHES_PROCESSOR)
            enabled.add(sig)
        status.destination[dest.id] = None

    # --- export retry/spill (ISSUE 13): stamp the retry stanza onto the
    # destination exporters rendered so far (the internal otlp/ui and
    # servicegraph exporters are added later and stay unwrapped — their
    # loss modes are self-telemetry, not customer data)
    if options.export_retry is not None:
        retry_spec = dict(options.export_retry)
        for eid, ecfg in config["exporters"].items():
            cfg_e = dict(ecfg or {})
            cfg_e.setdefault("retry", retry_spec)
            config["exporters"][eid] = cfg_e

    enabled_signals = [s for s in SIGNALS if s in enabled]

    # --- data-stream pipelines: router connector -> forward connectors
    # (pipeline_builder.go:13 buildDataStreamPipelines)
    anomaly = options.anomaly
    anomaly_on = bool(anomaly and anomaly.enabled and Signal.TRACES in enabled)
    stream_pipelines: dict[Signal, list[str]] = {s: [] for s in SIGNALS}
    for stream in data_streams:
        for sig in SIGNALS:
            exporters = []
            for sd in stream.destinations:
                for conn in dest_forward_connectors.get(sd.destination_id, []):
                    if conn.startswith(f"forward/{sig.value}/"):
                        exporters.append(conn)
            if not exporters:
                continue
            pname = f"{sig.value}/{stream.name}"
            config["service"]["pipelines"][pname] = {
                "receivers": [router_connector_name(sig)],
                "processors": [GENERIC_BATCH],
                "exporters": exporters,
            }
            stream_pipelines[sig].append(pname)

    # --- anomaly stream pipeline (north star): receives whole traces whose
    # spans were flagged by tpuanomaly, via the anomalyrouter connector. If
    # the operator defined a stream with that name, the anomalyrouter feeds
    # the existing (scoped) pipeline; otherwise a dedicated pipeline fans
    # out to every traces destination.
    if anomaly_on:
        anomaly_pipeline = f"traces/{anomaly.route_to_stream}"
        if anomaly_pipeline in config["service"]["pipelines"]:
            config["service"]["pipelines"][anomaly_pipeline]["receivers"] \
                .append("anomalyrouter")
        else:
            all_traces_forwards = sorted(
                conn for conns in dest_forward_connectors.values()
                for conn in conns if conn.startswith("forward/traces/"))
            config["service"]["pipelines"][anomaly_pipeline] = {
                "receivers": ["anomalyrouter"],
                "processors": [GENERIC_BATCH],
                "exporters": all_traces_forwards,
            }
        config["connectors"]["anomalyrouter"] = {
            "mode": "trace",
            "mirror": False,
            "anomaly_pipelines": [anomaly_pipeline],
            "default_pipelines": [],
        }

    # --- root pipelines per enabled signal (:184); router connector config
    # uses the odigosrouter schema: source identity -> stream pipelines,
    # with the `default` stream catching unmapped sources.
    for sig in enabled_signals:
        conn = router_connector_name(sig)
        default_pipeline = f"{sig.value}/default"
        config["connectors"][conn] = {
            "data_streams": [
                {"name": ds.name,
                 "sources": [s.as_dict() for s in ds.sources],
                 "pipelines": [f"{sig.value}/{ds.name}"]}
                for ds in data_streams
                if f"{sig.value}/{ds.name}" in stream_pipelines[sig]],
            "default_pipelines": (
                [default_pipeline]
                if default_pipeline in stream_pipelines[sig] else []),
        }
        procs = ["memory_limiter", VERSION_RESOURCE_PROCESSOR]
        procs.extend(signal_processors[sig])
        exporters = [conn]
        if sig == Signal.TRACES and anomaly_on:
            # north star: score spans on TPU before routing; flagged traces
            # additionally flow through the anomalyrouter.
            config["processors"]["tpuanomaly"] = {
                "model": anomaly.model,
                "threshold": anomaly.threshold,
                "max_batch": anomaly.max_batch,
                "timeout_ms": anomaly.timeout_ms,
                "devices": anomaly.devices,
            }
            if getattr(anomaly, "failover", None) is not None:
                # failover breaker (ISSUE 13): the engine arms a
                # circuit breaker with a zscore fallback route; None
                # renders nothing (byte-stable configs)
                config["processors"]["tpuanomaly"]["failover"] = dict(
                    anomaly.failover)
            tp = getattr(anomaly, "tensor_parallel", 1) or 1
            if anomaly.devices > 1 or tp > 1:
                # multi-chip sharded serving (ISSUE 7): render the full
                # dp×tp mesh spec; the engine owns the Mesh and dispatches
                # through the partition-rule plan. Single-chip configs
                # stay byte-identical (no mesh key at all).
                config["processors"]["tpuanomaly"]["mesh"] = {
                    "data": anomaly.devices, "model": tp}
            procs.append("tpuanomaly")
            exporters.append("anomalyrouter")
        config["service"]["pipelines"][root_pipeline_name(sig)] = {
            "receivers": ["otlp"],
            "processors": procs,
            "exporters": exporters,
        }
        if sig == Signal.TRACES and anomaly_on \
                and getattr(anomaly, "slo", None) is not None:
            # declarative SLOs (ISSUE 8): the root traces pipeline gets
            # an ``slo:`` stanza evaluated by the latency-attribution
            # layer's fast/slow-window burn rates; objectives left None
            # are omitted, and a fully-empty SloConfiguration renders
            # nothing (byte-stable for installs without SLOs)
            slo = anomaly.slo
            spec: GenericMap = {}
            if slo.latency_p99_ms:
                spec["latency_p99_ms"] = slo.latency_p99_ms
            if slo.scored_fraction:
                spec["scored_fraction"] = slo.scored_fraction
            if spec:
                spec["fast_window_s"] = slo.fast_window_s
                spec["slow_window_s"] = slo.slow_window_s
                config["service"]["pipelines"][
                    root_pipeline_name(sig)]["slo"] = spec
        if sig == Signal.TRACES and anomaly_on \
                and getattr(anomaly, "fast_path", False):
            # ingest fast path: decoded wire frames featurize once and
            # ride the engine's deadline-based adaptive coalescer; the
            # scoring timeout doubles as the admission deadline. The
            # route enters at the scorer, so tpuanomaly moves up right
            # behind memory_limiter (the one stage the fast path
            # replaces) — version stamping and compiled Actions keep
            # applying on the scorer's out-edge instead of being
            # silently bypassed (graph.validate_config enforces this
            # ordering for every fast_path pipeline)
            root = config["service"]["pipelines"][root_pipeline_name(sig)]
            # lanes/ordered (ISSUE 9): completion-driven multi-lane
            # retirement — N lanes overlap tag/forward of independent
            # frames; ordered=true keeps the single-forwarder FIFO
            # output order for consumers that need it
            root["fast_path"] = {
                "deadline_ms": anomaly.timeout_ms,
                "lanes": anomaly.fast_path_lanes,
                "ordered": anomaly.fast_path_ordered,
                # predictive deadline-burn admission (ISSUE 12): shed
                # frames priced past the deadline before featurize
                # touches them, named blame=predicted
                "predictive": anomaly.fast_path_predictive}
            if getattr(anomaly, "fast_path_fused", False):
                # fused device-side featurize→pack→score (ISSUE 19):
                # rendered only when armed so every existing install's
                # config stays byte-identical
                root["fast_path"]["fused"] = True
            root["processors"] = (
                ["memory_limiter", "tpuanomaly"]
                + [pid for pid in root["processors"]
                   if pid not in ("memory_limiter", "tpuanomaly")])
            # deadline-sized coalescing emits variable shapes: every
            # scoring bucket must precompile at start or the first
            # traffic at each size pays a worker-stalling XLA compile
            # while the admission gate sheds the resulting backlog
            config["processors"]["tpuanomaly"]["warm_ladder"] = True

    # --- servicegraph (:231): root traces pipeline also feeds the
    # servicegraph connector; its metrics surface on a dedicated pipeline.
    if Signal.TRACES in enabled and not options.service_graph_disabled:
        config["connectors"][SERVICEGRAPH_CONNECTOR] = {
            "store": {"ttl_s": 15}, "store_expiration_loop_s": 5,
            "dimensions": ["service.name"],
        }
        config["exporters"]["prometheus/servicegraph"] = {
            "namespace": "servicegraph"}
        config["service"]["pipelines"]["metrics/servicegraph"] = {
            "receivers": [SERVICEGRAPH_CONNECTOR],
            "processors": [],
            "exporters": ["prometheus/servicegraph"],
        }
        root = config["service"]["pipelines"][root_pipeline_name(Signal.TRACES)]
        root["exporters"].append(SERVICEGRAPH_CONNECTOR)

    # --- self telemetry (configmap.go:42,86-126): traffic metrics on every
    # data pipeline + an own-metrics pipeline to the internal store.
    # Per-pipeline instances with explicit pipeline labels; per-SERVICE
    # counters only on the root (ingest) pipelines — a span traverses
    # root -> router -> data-stream pipelines, and counting the same
    # service series once per hop would over-report cluster ingest (the
    # UI's hero tile sums the per-service series).
    if options.self_telemetry:
        roots = {root_pipeline_name(sig) for sig in enabled_signals}
        for pname, pipe in config["service"]["pipelines"].items():
            if pname == "metrics/servicegraph":
                continue
            pid = f"{TRAFFIC_METRICS}/{pname}"
            config["processors"][pid] = {
                "pipeline": pname, "per_service": pname in roots}
            pipe["processors"] = list(pipe["processors"]) + [pid]
        config["receivers"]["prometheus/self-metrics"] = {
            "scrape_interval_s": 10}
        config["exporters"]["otlp/ui"] = {"endpoint": options.ui_endpoint}
        config["service"]["pipelines"]["metrics/otelcol"] = {
            "receivers": ["prometheus/self-metrics"],
            "processors": [VERSION_RESOURCE_PROCESSOR],
            "exporters": ["otlp/ui"],
        }

    # --- continuous profiler + device-runtime telemetry (ISSUE 3): an
    # opted-in Configuration renders a service.telemetry stanza; the
    # collector applies it via selftelemetry.start_from_config. Absent
    # when disabled — the generated config stays byte-stable for
    # existing installs.
    # --- fleet alert rules (ISSUE 10): the service.alerts stanza the
    # fleet plane's alert engine loads at graph build — rules evaluate
    # window expressions over the series store and raise alert/<name>
    # conditions while firing. Hot-reloadable: a re-render with edited/
    # deleted rules reconfigures/retires them (Collector.reload diffs
    # the graph-stamped rule names).
    if options.alerts:
        # normalize through the dataclass so its defaults are the ONE
        # source of truth (raw dicts arrive from hand-built options;
        # hydrated configs already carry dataclasses)
        config["service"]["alerts"] = [
            dataclasses.asdict(a if isinstance(a, AlertRuleConfiguration)
                               else AlertRuleConfiguration(**a))
            for a in options.alerts]

    # --- closed-loop actuator (ISSUE 15): the service.actuator stanza
    # the collector arms the process-global actuator from (canary ->
    # judge -> promote/rollback over the recommender's proposals);
    # validated by graph.validate_config at load. None renders nothing.
    if options.actuator is not None:
        config["service"]["actuator"] = dict(options.actuator)

    st = options.telemetry_config
    if st is not None and (st.profiler_enabled or st.device_runtime_enabled):
        telemetry: GenericMap = {}
        if st.profiler_enabled:
            telemetry["profiler"] = {
                "enabled": True, "hz": st.profiler_hz,
                "window_s": st.profiler_window_s,
                "windows": st.profiler_windows}
        if st.device_runtime_enabled:
            telemetry["device_runtime"] = {
                "enabled": True,
                "interval_s": st.device_runtime_interval_s}
        config["service"]["telemetry"] = telemetry

    return config, status, enabled_signals
