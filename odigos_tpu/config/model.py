"""Configuration model.

Mirrors the capability surface of ``common.OdigosConfiguration``
(common/odigos_config.go:362-402: ~40 fields covering namespaces to ignore,
gateway/node collector tuning, profiles, rollout/rollback knobs, mount and
env-injection methods, metrics sources) re-shaped for this framework: the
TPU anomaly stage gets its own first-class section (``anomaly``) instead of
being bolted on, and collector resource settings carry the memory-limiter
derivation inputs (scheduler/controllers/clustercollectorsgroup/
resource_config.go:8-39).
"""

from __future__ import annotations

import enum
from dataclasses import MISSING, asdict, dataclass, field, fields, is_dataclass
from typing import Any, Optional


class Tier(str, enum.Enum):
    COMMUNITY = "community"
    CLOUD = "cloud"
    ONPREM = "onprem"


class UiMode(str, enum.Enum):
    NORMAL = "normal"
    READONLY = "readonly"


class MountMethod(str, enum.Enum):
    """How agent files reach the workload (reference: k8s-host-path vs
    k8s-virtual-device, common/odigos_config.go MountMethod)."""

    HOST_PATH = "k8s-host-path"
    VIRTUAL_DEVICE = "k8s-virtual-device"


class EnvInjectionMethod(str, enum.Enum):
    """Reference: loader (LD_PRELOAD), pod-manifest, loader-fallback-to-pod-manifest."""

    LOADER = "loader"
    POD_MANIFEST = "pod-manifest-env-var-injection"
    LOADER_FALLBACK = "loader-fallback-to-pod-manifest"


@dataclass
class CollectorGatewayConfiguration:
    """Gateway (cluster collector) tuning. Defaults resolved by sizing
    presets; memory-limiter values derived in sizing.gateway_resources."""

    min_replicas: Optional[int] = None
    max_replicas: Optional[int] = None
    request_memory_mib: Optional[int] = None
    limit_memory_mib: Optional[int] = None
    request_cpu_m: Optional[int] = None
    limit_cpu_m: Optional[int] = None
    memory_limiter_limit_mib: Optional[int] = None
    memory_limiter_spike_limit_mib: Optional[int] = None
    gomemlimit_mib: Optional[int] = None
    service_graph_disabled: Optional[bool] = None
    cluster_metrics_enabled: Optional[bool] = None
    # TPU co-scheduling: how many gateway replicas should be co-located with
    # a TPU device for the anomaly stage (north-star extension).
    tpu_replicas: Optional[int] = None
    # Multi-chip sizing knob (ISSUE 7): how many TPU mesh slices the
    # autoscaler may co-schedule. Each TPU-backed gateway replica owns one
    # whole slice of anomaly.devices × anomaly.tensor_parallel chips (the
    # engine's dp×tp mesh); None = as many as the device pools can back.
    mesh_slices: Optional[int] = None
    # export retry/spill (ISSUE 13): a mapping ({} = defaults) stamps a
    # ``retry:`` stanza onto every destination exporter the gateway
    # config renders — bounded jittered-backoff + spill queue around a
    # destination outage, terminal drops named queue_full/
    # shutdown_drain (components/exporters/retryqueue.py). None renders
    # nothing (existing configs stay byte-identical). Keys:
    # initial_backoff_ms / max_backoff_ms / jitter / max_queue_spans /
    # drain_timeout_s.
    export_retry: Optional[dict] = None


@dataclass
class CollectorNodeConfiguration:
    """Node collector (daemonset) tuning (common/odigos_config.go
    CollectorNodeConfiguration)."""

    collector_owner_metrics_port: Optional[int] = None
    request_memory_mib: Optional[int] = None
    limit_memory_mib: Optional[int] = None
    request_cpu_m: Optional[int] = None
    limit_cpu_m: Optional[int] = None
    memory_limiter_limit_mib: Optional[int] = None
    memory_limiter_spike_limit_mib: Optional[int] = None
    gomemlimit_mib: Optional[int] = None
    k8s_node_logs_directory: Optional[str] = None


@dataclass
class RolloutConfiguration:
    """Automatic-rollout knobs (common/odigos_config.go Rollout*,
    :389-391 rollback grace/stability)."""

    automatic_rollout_disabled: Optional[bool] = None
    rollback_disabled: Optional[bool] = None
    rollback_grace_time_s: float = 300.0
    rollback_stability_window_s: float = 3600.0


@dataclass
class SloConfiguration:
    """Declarative service-level objectives for the anomaly pipeline
    (ISSUE 8): rendered by pipelinegen as the root traces pipeline's
    ``slo:`` stanza and evaluated with Google-SRE-style fast/slow-window
    burn rates (selftelemetry/latency.SloTracker). A p99 latency target
    affords a 1 % error budget; a scored-fraction target Y affords 1−Y.
    Both objectives optional — None renders nothing (byte-stable
    configs for installs without SLOs)."""

    latency_p99_ms: Optional[float] = None
    scored_fraction: Optional[float] = None
    fast_window_s: float = 60.0
    slow_window_s: float = 300.0


@dataclass
class AlertRuleConfiguration:
    """One declarative fleet alert rule (ISSUE 10): ``expr`` is a
    window expression over the series store
    (``fn(metric{k=v,...}[Ns]) <op> number``, grammar in
    selftelemetry/fleet.parse_expr), ``for_s`` the hold duration a
    breach must persist before the rule fires (recovery clears), and
    ``severity`` maps to the HealthRollup condition raised while firing
    (critical -> Unhealthy, else Degraded). Rendered by pipelinegen as
    the gateway config's ``service.alerts`` stanza and validated by
    graph.validate_config — a typo'd rule dies at load, never silently
    sits dark."""

    name: str = ""
    expr: str = ""
    for_s: float = 0.0
    severity: str = "warning"


@dataclass
class AnomalyStageConfiguration:
    """First-class config for the TPU anomaly-detection stage (north star:
    tpuanomalyprocessor + anomalyrouter + TPU sidecar)."""

    enabled: bool = False
    model: str = "zscore"  # zscore | autoencoder | transformer
    threshold: float = 0.8  # score in [0,1] (ScoringEngine contract)
    max_batch: int = 4096
    timeout_ms: float = 5.0  # pass-through-on-timeout budget (<5ms p99)
    route_to_stream: str = "anomalies"
    devices: int = 1  # data-parallel chips ("data" mesh axis) per replica
    # tensor-parallel shards ("model" mesh axis) per replica: the engine
    # serves on a devices × tensor_parallel mesh (ISSUE 7); heads/d_ff
    # shard per parallel.PARTITION_RULES. 1 = pure data parallelism.
    tensor_parallel: int = 1
    # ingest fast path (ISSUE 6): wire frames featurize once at the
    # receiver and score through the engine's deadline-based adaptive
    # coalescer, bypassing the componentwise batch/score seams; the
    # scoring timeout doubles as the per-frame admission deadline
    fast_path: bool = False
    # completion-driven multi-lane retirement (ISSUE 9): number of
    # retirement lanes overlapping tag/forward of independent frames
    # (rendered as fast_path.lanes; only meaningful with fast_path)
    fast_path_lanes: int = 4
    # true = forward downstream in intake order (the single-forwarder
    # FIFO contract, byte-identical output order) at the cost of
    # serializing the forward leg; false = forward as completed
    fast_path_ordered: bool = False
    # predictive deadline-burn shed (ISSUE 12): frames the burn table
    # prices past the admission deadline are REJECTED before featurize
    # spends host time on them (blame=predicted); rendered as
    # fast_path.predictive
    fast_path_predictive: bool = True
    # fused device-side featurize→pack→score (ISSUE 19): the submit
    # lane hands the engine raw span columns and one jitted call does
    # hashing, the parent join, packing, and the model forward;
    # rendered as fast_path.fused ONLY when true (opt-in — existing
    # configs stay byte-identical), kill-switchable via ODIGOS_FUSED=0
    fast_path_fused: bool = False
    # declarative burn-rate SLOs for the root traces pipeline (ISSUE 8);
    # None renders nothing — existing configs stay byte-identical
    slo: Optional[SloConfiguration] = None
    # failover breaker for the scoring engine (ISSUE 13): a mapping
    # ({} = defaults; keys per serving/failover.FailoverConfig —
    # window_s, trip_errors, probe_interval_s, recovery_successes,
    # fallback_model) rendered as the tpuanomaly processor's
    # ``failover:`` knob. A persistent fault of the primary model then
    # hot-swaps scoring to the zscore fallback (ModelFailover condition,
    # odigos_failover_* metrics) and half-open probes the primary back.
    # zscore is a jitted JAX kernel on the process's default device —
    # the same chip on a TPU host, not the CPU.
    # None renders nothing — existing configs stay byte-identical.
    failover: Optional[dict] = None


@dataclass
class SelfTelemetryConfiguration:
    """Continuous profiler + device-runtime telemetry knobs (ISSUE 3;
    rendered into the gateway config's ``service.telemetry`` stanza and
    applied by the collector via ``selftelemetry.start_from_config``).
    Disabled by default: the subsystem is a strict no-op unless opted
    in — no sampler thread, no collector thread, nothing allocated."""

    profiler_enabled: bool = False
    profiler_hz: float = 19.0       # prime default: no aliasing
    profiler_window_s: float = 60.0
    profiler_windows: int = 12      # bounded ring: 12 x 60 s
    device_runtime_enabled: bool = False
    device_runtime_interval_s: float = 10.0


@dataclass
class MetricsSourcesConfiguration:
    """Which metrics feeds are enabled (common/odigos_config.go
    MetricsSourceConfiguration: spanMetrics/hostMetrics/kubeletStats/
    odigosOwnMetrics/agentMetrics)."""

    span_metrics: bool = False
    host_metrics: bool = False
    kubelet_stats: bool = False
    own_metrics: bool = True
    agent_metrics: bool = False


@dataclass
class OidcConfiguration:
    tenant_url: str = ""
    client_id: str = ""
    client_secret: str = ""


@dataclass
class UserInstrumentationEnvs:
    """Per-language extra env for agents (common/odigos_config.go
    UserInstrumentationEnvs)."""

    languages: dict[str, dict[str, str]] = field(default_factory=dict)


@dataclass
class Configuration:
    """The single authored configuration object (ConfigMap analog)."""

    config_version: int = 1
    telemetry_enabled: bool = False
    ignored_namespaces: list[str] = field(default_factory=list)
    ignored_containers: list[str] = field(default_factory=list)
    ignore_odigos_namespace: bool = True
    image_prefix: str = ""
    cluster_name: str = ""
    # connected control-plane version (the CLI's autodetect role,
    # cli/pkg/autodetect); feature gates key on it
    cluster_version: str = "1.30"
    ui_mode: UiMode = UiMode.NORMAL
    ui_pagination_limit: int = 0
    # where collectors ship their own-telemetry metrics stream (the
    # frontend's collector-metrics consumer listens here); tests point it
    # at an ephemeral local port
    ui_endpoint: str = "ui.odigos-system:4317"
    collector_gateway: CollectorGatewayConfiguration = field(
        default_factory=CollectorGatewayConfiguration)
    collector_node: CollectorNodeConfiguration = field(
        default_factory=CollectorNodeConfiguration)
    profiles: list[str] = field(default_factory=list)
    allow_concurrent_agents: Optional[bool] = None
    mount_method: Optional[MountMethod] = None
    agent_env_vars_injection_method: Optional[EnvInjectionMethod] = None
    user_instrumentation_envs: UserInstrumentationEnvs = field(
        default_factory=UserInstrumentationEnvs)
    rollout: RolloutConfiguration = field(default_factory=RolloutConfiguration)
    oidc: Optional[OidcConfiguration] = None
    resource_size_preset: str = ""  # "", size_s, size_m, size_l
    metrics_sources: MetricsSourcesConfiguration = field(
        default_factory=MetricsSourcesConfiguration)
    anomaly: AnomalyStageConfiguration = field(
        default_factory=AnomalyStageConfiguration)
    selftelemetry: SelfTelemetryConfiguration = field(
        default_factory=SelfTelemetryConfiguration)
    # declarative fleet alert rules (ISSUE 10): rendered into the
    # gateway config's service.alerts stanza; empty list renders
    # nothing (byte-stable configs for installs without alerts)
    alerts: list[AlertRuleConfiguration] = field(default_factory=list)
    # closed-loop actuator (ISSUE 15): a mapping rendered as the
    # gateway config's service.actuator stanza (enabled, dry_run,
    # judgment_window_s, cooldown_s, max_step, knobs allowlist,
    # max_history — validated at load by controlplane/actuator.py).
    # None renders nothing (byte-stable configs; the loop stays open
    # unless the operator closes it).
    actuator: Optional[dict] = None
    # Free-form bag for profile-applied settings without a dedicated field
    # (reference profiles patch arbitrary config, e.g. disable-gin).
    extra: dict[str, Any] = field(default_factory=dict)

    def to_dict(self) -> dict[str, Any]:
        return asdict(self)

    @classmethod
    def from_dict(cls, data: dict[str, Any]) -> "Configuration":
        return _from_dict(cls, data)


# Optional nested-dataclass fields (default=None, so no default_factory to
# infer the type from at runtime under `from __future__ import annotations`)
_OPTIONAL_NESTED: dict[str, type] = {"oidc": OidcConfiguration,
                                     "slo": SloConfiguration}

# list-of-dataclass fields (default_factory=list hides the element type
# at runtime under deferred annotations, like _OPTIONAL_NESTED above)
_LIST_NESTED: dict[str, type] = {"alerts": AlertRuleConfiguration}


def _from_dict(cls, data):
    """Tolerant nested-dataclass hydration (unknown keys land in extra)."""
    if not is_dataclass(cls):
        return data
    known = {f.name: f for f in fields(cls)}
    kwargs = {}
    extra = {}
    for k, v in (data or {}).items():
        if k not in known:
            extra[k] = v
            continue
        f = known[k]
        # resolve nested dataclass types by default_factory class
        if isinstance(v, dict) and f.default_factory is not MISSING \
                and f.default_factory is not dict and is_dataclass(f.default_factory):
            kwargs[k] = _from_dict(f.default_factory, v)
        elif isinstance(v, dict) and k in _OPTIONAL_NESTED:
            kwargs[k] = _from_dict(_OPTIONAL_NESTED[k], v)
        elif isinstance(v, list) and k in _LIST_NESTED:
            kwargs[k] = [_from_dict(_LIST_NESTED[k], item)
                         if isinstance(item, dict) else item
                         for item in v]
        else:
            kwargs[k] = v
    obj = cls(**kwargs)
    if extra and hasattr(obj, "extra"):
        obj.extra.update(extra)
    return obj
