"""Control-plane tests: source→IC lifecycle, agent enablement + webhook
injection, rollout/rollback, scheduler effective config, autoscaler config
rendering + action compilation + HPA policy."""

import time

import pytest

from odigos_tpu.api import ControllerManager, ObjectMeta, Store, WorkloadKind, WorkloadRef
from odigos_tpu.api.resources import (
    AgentEnabledReason,
    AGENT_ENABLED,
    Action,
    ActionKind,
    Condition,
    ConditionStatus,
    ConfigMap,
    DestinationResource,
    InstrumentationRule,
    MARKED_FOR_INSTRUMENTATION,
    RuleKind,
    RuntimeDetails,
    Source,
    WORKLOAD_ROLLOUT,
)
from odigos_tpu.config.model import Configuration, RolloutConfiguration
from odigos_tpu.controlplane import (
    Autoscaler,
    Cluster,
    Container,
    GATEWAY_CONFIG_NAME,
    HpaDecider,
    Instrumentor,
    NODE_CONFIG_NAME,
    PodPhase,
    Scheduler,
)
from odigos_tpu.controlplane.autoscaler import compile_action
from odigos_tpu.controlplane.instrumentor import ic_name
from odigos_tpu.controlplane.scheduler import (
    EFFECTIVE_CONFIG_NAME,
    GATEWAY_GROUP_NAME,
    ODIGOS_NAMESPACE,
)


def workload_ref(name="app", ns="default"):
    return WorkloadRef(ns, WorkloadKind.DEPLOYMENT, name)


def make_env(config=None, nodes=1):
    store = Store()
    mgr = ControllerManager(store)
    cluster = Cluster(nodes=nodes)
    cfg = config or Configuration(
        rollout=RolloutConfiguration(rollback_grace_time_s=0.0))
    instr = Instrumentor(store, mgr, cluster, cfg)
    return store, mgr, cluster, instr


def add_python_app(cluster, name="app", ns="default"):
    return cluster.add_workload(ns, name, [
        Container(name="main", language="python", runtime_version="3.11")])


def instrument(store, mgr, ref):
    store.apply(Source(
        meta=ObjectMeta(name=f"src-{ref.name}", namespace=ref.namespace),
        workload=ref))
    mgr.run_once()


def write_runtime_details(store, mgr, ref, details=None):
    ic = store.get("InstrumentationConfig", ref.namespace, ic_name(ref))
    assert ic is not None
    ic.runtime_details = details or [
        RuntimeDetails(container_name="main", language="python",
                       runtime_version="3.11")]
    store.update_status(ic)
    mgr.run_once()
    return store.get("InstrumentationConfig", ref.namespace, ic_name(ref))


class TestSourceLifecycle:
    def test_source_creates_ic(self):
        store, mgr, cluster, _ = make_env()
        ref = add_python_app(cluster).ref
        instrument(store, mgr, ref)
        ic = store.get("InstrumentationConfig", "default", ic_name(ref))
        assert ic is not None
        cond = ic.condition(MARKED_FOR_INSTRUMENTATION)
        assert cond.reason == "WorkloadSource"

    def test_namespace_source_expands(self):
        store, mgr, cluster, _ = make_env()
        add_python_app(cluster, "a")
        add_python_app(cluster, "b")
        store.apply(Source(
            meta=ObjectMeta(name="ns-src", namespace="default"),
            workload=WorkloadRef("default", WorkloadKind.NAMESPACE, "default")))
        mgr.run_once()
        ics = store.list("InstrumentationConfig")
        assert len(ics) == 2
        assert all(ic.condition(MARKED_FOR_INSTRUMENTATION).reason ==
                   "NamespaceSource" for ic in ics)

    def test_workload_disable_overrides_namespace(self):
        store, mgr, cluster, _ = make_env()
        ref = add_python_app(cluster).ref
        store.apply(Source(
            meta=ObjectMeta(name="ns-src", namespace="default"),
            workload=WorkloadRef("default", WorkloadKind.NAMESPACE, "default")))
        mgr.run_once()
        assert store.get("InstrumentationConfig", "default", ic_name(ref))
        store.apply(Source(
            meta=ObjectMeta(name="excluded", namespace="default"),
            workload=ref, disable_instrumentation=True))
        mgr.run_once()
        assert store.get("InstrumentationConfig", "default",
                         ic_name(ref)) is None

    def test_source_deletion_removes_ic(self):
        store, mgr, cluster, _ = make_env()
        ref = add_python_app(cluster).ref
        instrument(store, mgr, ref)
        store.delete("Source", "default", f"src-{ref.name}")
        mgr.run_once()
        assert store.get("InstrumentationConfig", "default",
                         ic_name(ref)) is None

    def test_source_deletion_uninstruments_running_pods(self):
        """Deleting the Source after agents were deployed must rollout the
        workload so pods lose the injected env (reference: rollout.go Do
        un-instruments by restart the same way it instruments)."""
        store, mgr, cluster, _ = make_env()
        ref = add_python_app(cluster).ref
        instrument(store, mgr, ref)
        write_runtime_details(store, mgr, ref)
        gen_before = cluster.get_workload(ref).template_generation
        assert any(p.injected_env for p in cluster.pods.values())
        store.delete("Source", "default", f"src-{ref.name}")
        mgr.run_once()
        assert cluster.get_workload(ref).template_generation > gen_before
        assert all(not p.injected_env for p in cluster.pods.values())


class TestAgentEnablement:
    def test_agent_enabled_and_rollout(self):
        store, mgr, cluster, _ = make_env()
        w = add_python_app(cluster)
        instrument(store, mgr, w.ref)
        gen_before = w.template_generation
        ic = write_runtime_details(store, mgr, w.ref)
        assert ic.condition(AGENT_ENABLED).status == ConditionStatus.TRUE
        assert ic.containers[0].distro_name == "python-community"
        assert "PYTHONPATH" in ic.containers[0].env_to_inject
        assert w.template_generation == gen_before + 1
        assert ic.condition(WORKLOAD_ROLLOUT).reason == \
            "RolloutTriggeredSuccessfully"

    def test_webhook_injects_new_pods(self):
        store, mgr, cluster, _ = make_env()
        w = add_python_app(cluster)
        instrument(store, mgr, w.ref)
        write_runtime_details(store, mgr, w.ref)
        pods = cluster.pods_of(w.ref)
        assert len(pods) == 1
        pod = pods[0]
        assert "PYTHONPATH" in pod.injected_env.get("main", {})
        assert pod.resource_attrs["service.name"] == "app"
        assert "agents" in pod.injected_mounts

    def test_uninstrumented_pods_untouched(self):
        store, mgr, cluster, _ = make_env()
        w = add_python_app(cluster, "plain")
        pod = cluster.pods_of(w.ref)[0]
        assert pod.injected_env == {}
        assert pod.resource_attrs == {}

    def test_unsupported_language(self):
        store, mgr, cluster, _ = make_env()
        w = cluster.add_workload("default", "cobol-app",
                                 [Container(name="main", language="cobol")])
        instrument(store, mgr, w.ref)
        ic = write_runtime_details(store, mgr, w.ref, [
            RuntimeDetails(container_name="main", language="cobol")])
        cond = ic.condition(AGENT_ENABLED)
        assert cond.status == ConditionStatus.FALSE
        assert cond.reason == "UnsupportedProgrammingLanguage"

    def test_other_agent_conflict_and_concurrent_allow(self):
        cfg = Configuration(
            rollout=RolloutConfiguration(rollback_grace_time_s=0.0))
        store, mgr, cluster, instr = make_env(cfg)
        w = add_python_app(cluster)
        instrument(store, mgr, w.ref)
        details = [RuntimeDetails(container_name="main", language="python",
                                  runtime_version="3.11",
                                  other_agent="newrelic")]
        ic = write_runtime_details(store, mgr, w.ref, details)
        assert ic.condition(AGENT_ENABLED).reason == "OtherAgentDetected"
        # flip the allow-concurrent knob (profile allow_concurrent_agents)
        cfg.allow_concurrent_agents = True
        instr.set_effective_config(cfg)
        ic.runtime_details = details  # retrigger
        store.update_status(ic)
        mgr.run_once()
        ic = store.get("InstrumentationConfig", "default", ic_name(w.ref))
        assert ic.condition(AGENT_ENABLED).status == ConditionStatus.TRUE

    def test_musl_dotnet_distro(self):
        store, mgr, cluster, _ = make_env()
        w = cluster.add_workload("default", "dn", [
            Container(name="main", language="dotnet", libc_type="musl")])
        instrument(store, mgr, w.ref)
        ic = write_runtime_details(store, mgr, w.ref, [
            RuntimeDetails(container_name="main", language="dotnet",
                           libc_type="musl")])
        assert ic.containers[0].distro_name == "dotnet-community-musl"


class TestRollback:
    def test_crashloop_rolls_back(self):
        store, mgr, cluster, _ = make_env()
        w = add_python_app(cluster)
        instrument(store, mgr, w.ref)
        cluster.fail_next_rollout(w.ref)  # instrumented pods will crash
        ic = write_runtime_details(store, mgr, w.ref)
        # pods are now crashing; trigger another reconcile pass
        ic.runtime_details = list(ic.runtime_details)
        store.update_status(ic)
        mgr.run_once()
        ic = store.get("InstrumentationConfig", "default", ic_name(w.ref))
        cond = ic.condition(AGENT_ENABLED)
        assert cond.status == ConditionStatus.FALSE
        assert cond.reason == "CrashLoopBackOff"
        assert all(not c.agent_enabled for c in ic.containers)
        # replacement pods are clean (no injection) and running
        for pod in cluster.pods_of(w.ref):
            assert pod.phase == PodPhase.RUNNING
            assert pod.injected_env == {}

    def test_rollback_sticky_until_healed(self):
        store, mgr, cluster, _ = make_env()
        w = add_python_app(cluster)
        instrument(store, mgr, w.ref)
        cluster.fail_next_rollout(w.ref)
        ic = write_runtime_details(store, mgr, w.ref)
        ic.runtime_details = list(ic.runtime_details)
        store.update_status(ic)
        mgr.run_once()
        # further reconciles do NOT re-instrument
        ic = store.get("InstrumentationConfig", "default", ic_name(w.ref))
        ic.runtime_details = list(ic.runtime_details)
        store.update_status(ic)
        mgr.run_once()
        ic = store.get("InstrumentationConfig", "default", ic_name(w.ref))
        assert ic.condition(AGENT_ENABLED).reason == "CrashLoopBackOff"

    def test_rollback_disabled(self):
        cfg = Configuration(rollout=RolloutConfiguration(
            rollback_disabled=True, rollback_grace_time_s=0.0))
        store, mgr, cluster, _ = make_env(cfg)
        w = add_python_app(cluster)
        instrument(store, mgr, w.ref)
        cluster.fail_next_rollout(w.ref)
        ic = write_runtime_details(store, mgr, w.ref)
        ic.runtime_details = list(ic.runtime_details)
        store.update_status(ic)
        mgr.run_once()
        ic = store.get("InstrumentationConfig", "default", ic_name(w.ref))
        assert ic.condition(AGENT_ENABLED).status == ConditionStatus.TRUE


class TestRules:
    def test_payload_collection_rule(self):
        store, mgr, cluster, _ = make_env()
        w = add_python_app(cluster)
        instrument(store, mgr, w.ref)
        write_runtime_details(store, mgr, w.ref)
        store.apply(InstrumentationRule(
            meta=ObjectMeta(name="payload", namespace="default"),
            rule_kind=RuleKind.PAYLOAD_COLLECTION,
            details={"mode": "db"}))
        mgr.run_once()
        ic = store.get("InstrumentationConfig", "default", ic_name(w.ref))
        assert len(ic.sdk_configs) == 1
        assert ic.sdk_configs[0].payload_collection == "db"

    def test_rule_language_scoping(self):
        store, mgr, cluster, _ = make_env()
        w = add_python_app(cluster)
        instrument(store, mgr, w.ref)
        write_runtime_details(store, mgr, w.ref)
        store.apply(InstrumentationRule(
            meta=ObjectMeta(name="java-only", namespace="default"),
            rule_kind=RuleKind.CODE_ATTRIBUTES, languages=["java"]))
        mgr.run_once()
        ic = store.get("InstrumentationConfig", "default", ic_name(w.ref))
        assert ic.sdk_configs[0].code_attributes is False


class TestScheduler:
    def test_effective_config_and_groups(self):
        store = Store()
        mgr = ControllerManager(store)
        sched = Scheduler(store, mgr)
        sched.apply_authored(Configuration(resource_size_preset="size_m"))
        mgr.run_once()
        eff = sched.effective_config()
        assert eff is not None
        gw = store.get("CollectorsGroup", ODIGOS_NAMESPACE,
                       GATEWAY_GROUP_NAME)
        assert gw is not None
        assert gw.resources["min_replicas"] == 2  # size_m preset
        assert gw.resources["gomemlimit_mib"] > 0

    def test_unknown_tier_string_degrades_not_crashes(self):
        """A hand-edited/version-skewed tier value in the authored ConfigMap
        must surface as an effective-config problem, not crash reconcile
        (advisor r3: Tier(...) ValueError killed the loop)."""
        from odigos_tpu.controlplane.scheduler import AUTHORED_CONFIG_NAME

        store = Store()
        mgr = ControllerManager(store)
        sched = Scheduler(store, mgr)
        store.apply(ConfigMap(
            meta=ObjectMeta(name=AUTHORED_CONFIG_NAME,
                            namespace=ODIGOS_NAMESPACE),
            data={"config": {}, "tier": "enterprise-plus"}))
        mgr.run_once()  # must not raise
        eff = store.get("ConfigMap", ODIGOS_NAMESPACE, EFFECTIVE_CONFIG_NAME)
        assert eff is not None
        assert any("enterprise-plus" in p for p in eff.data["problems"])
        assert eff.data["tier"] == sched.tier.value  # fell back

    def test_anomaly_enables_tpu_coscheduling(self):
        store = Store()
        mgr = ControllerManager(store)
        sched = Scheduler(store, mgr)
        cfg = Configuration()
        cfg.anomaly.enabled = True
        sched.apply_authored(cfg)
        mgr.run_once()
        gw = store.get("CollectorsGroup", ODIGOS_NAMESPACE,
                       GATEWAY_GROUP_NAME)
        assert gw.tpu_replicas == 1


class TestAutoscaler:
    def make_env(self):
        store = Store()
        mgr = ControllerManager(store)
        sched = Scheduler(store, mgr)
        asc = Autoscaler(store, mgr, Configuration())
        sched.apply_authored(Configuration())
        mgr.run_once()
        return store, mgr, sched, asc

    def test_destination_renders_gateway_config(self):
        store, mgr, _, _ = self.make_env()
        store.apply(DestinationResource(
            meta=ObjectMeta(name="j1", namespace=ODIGOS_NAMESPACE),
            dest_type="jaeger", signals=["traces"],
            config={"JAEGER_URL": "jaeger:4317"}))
        mgr.run_once()
        cm = store.get("ConfigMap", ODIGOS_NAMESPACE, GATEWAY_CONFIG_NAME)
        assert cm is not None
        pipelines = cm.data["collector-conf"]["service"]["pipelines"]
        assert "traces/jaeger-j1" in pipelines
        assert cm.data["enabled_signals"] == ["traces"]
        node_cm = store.get("ConfigMap", ODIGOS_NAMESPACE, NODE_CONFIG_NAME)
        assert "traces" in node_cm.data["collector-conf"]["service"]["pipelines"]
        dest = store.get("DestinationResource", ODIGOS_NAMESPACE, "j1")
        assert dest.conditions[0].status == ConditionStatus.TRUE

    def test_bad_destination_condition(self):
        store, mgr, _, _ = self.make_env()
        store.apply(DestinationResource(
            meta=ObjectMeta(name="dd", namespace=ODIGOS_NAMESPACE),
            dest_type="datadog", signals=["traces"]))  # missing site
        mgr.run_once()
        dest = store.get("DestinationResource", ODIGOS_NAMESPACE, "dd")
        assert dest.conditions[0].status == ConditionStatus.FALSE
        assert "DATADOG_SITE" in dest.conditions[0].message

    def test_action_compiled_into_config(self):
        store, mgr, _, _ = self.make_env()
        store.apply(DestinationResource(
            meta=ObjectMeta(name="j1", namespace=ODIGOS_NAMESPACE),
            dest_type="jaeger", signals=["traces"],
            config={"JAEGER_URL": "jaeger:4317"}))
        store.apply(Action(
            meta=ObjectMeta(name="mask-pii", namespace=ODIGOS_NAMESPACE),
            action_kind=ActionKind.PII_MASKING, signals=["traces"]))
        mgr.run_once()
        cm = store.get("ConfigMap", ODIGOS_NAMESPACE, GATEWAY_CONFIG_NAME)
        conf = cm.data["collector-conf"]
        assert "odigosconditionalattributes/mask-pii" in conf["processors"]
        root = conf["service"]["pipelines"]["traces/in"]
        assert "odigosconditionalattributes/mask-pii" in root["processors"]

    def test_all_action_kinds_compile(self):
        details = {
            ActionKind.ADD_CLUSTER_INFO: {"cluster_attributes":
                                          [{"key": "k", "value": "v"}]},
            ActionKind.DELETE_ATTRIBUTE: {"attribute_names": ["a"]},
            ActionKind.RENAME_ATTRIBUTE: {"renames": {"a": "b"}},
            ActionKind.PII_MASKING: {},
            ActionKind.K8S_ATTRIBUTES: {"attributes": ["k8s.pod.name"]},
            ActionKind.ERROR_SAMPLER: {"fallback_sampling_ratio": 10},
            ActionKind.LATENCY_SAMPLER: {"endpoints_filters": []},
            ActionKind.PROBABILISTIC_SAMPLER: {"sampling_percentage": 50},
            ActionKind.SERVICE_NAME_SAMPLER: {"services_name_filters": []},
            ActionKind.SPAN_ATTRIBUTE_SAMPLER: {"attribute_filters": []},
            ActionKind.SAMPLERS: {},
        }
        for kind, d in details.items():
            a = Action(meta=ObjectMeta(name=f"a-{kind.value.lower()}",
                                       namespace=ODIGOS_NAMESPACE),
                       action_kind=kind, details=d)
            compiled = compile_action(a)
            assert compiled is not None, kind
            assert compiled["type"], kind

    def test_disabled_action_skipped(self):
        a = Action(meta=ObjectMeta(name="x", namespace=ODIGOS_NAMESPACE),
                   action_kind=ActionKind.PII_MASKING, disabled=True)
        assert compile_action(a) is None

    def test_data_streams_from_sources_and_destinations(self):
        store, mgr, _, _ = self.make_env()
        store.apply(DestinationResource(
            meta=ObjectMeta(name="j1", namespace=ODIGOS_NAMESPACE),
            dest_type="jaeger", signals=["traces"],
            config={"JAEGER_URL": "jaeger:4317"},
            data_stream_names=["prod"]))
        store.apply(Source(
            meta=ObjectMeta(name="src-app", namespace="default"),
            workload=WorkloadRef("default", WorkloadKind.DEPLOYMENT, "app"),
            data_stream_names=["prod"]))
        mgr.run_once()
        cm = store.get("ConfigMap", ODIGOS_NAMESPACE, GATEWAY_CONFIG_NAME)
        conf = cm.data["collector-conf"]
        router = conf["connectors"]["odigosrouter/traces"]
        assert router["data_streams"][0]["name"] == "prod"
        assert router["data_streams"][0]["sources"] == [
            {"namespace": "default", "kind": "deployment", "name": "app"}]


class TestHpa:
    def test_scale_up_aggressive(self):
        hpa = HpaDecider()
        now = 1000.0
        # cpu at 200% of target: wants many more, capped at +2
        assert hpa.desired_replicas(2, 160.0, 10.0, 0.0, now) == 4
        # within the 15s window: no further scale-up
        assert hpa.desired_replicas(4, 160.0, 10.0, 0.0, now + 5) == 4
        # after the window: +2 again
        assert hpa.desired_replicas(4, 160.0, 10.0, 0.0, now + 20) == 6

    def test_rejection_metric_triggers_scale_up(self):
        hpa = HpaDecider()
        assert hpa.desired_replicas(2, 10.0, 10.0, 5.0, 1000.0) == 4

    def test_scale_down_conservative_with_stabilization(self):
        hpa = HpaDecider(stabilization_s=900.0)
        now = 1000.0
        # high load first (recommendation 8 recorded)
        assert hpa.desired_replicas(8, 80.0, 80.0, 0.0, now) == 8
        # load drops, but stabilization window still holds max=8
        assert hpa.desired_replicas(8, 10.0, 10.0, 0.0, now + 60) == 8
        # after stabilization expires: scale down by 25%
        assert hpa.desired_replicas(8, 10.0, 10.0, 0.0, now + 1000) == 6

    def test_bounds_respected(self):
        hpa = HpaDecider(min_replicas=2, max_replicas=5)
        assert hpa.desired_replicas(5, 200.0, 10.0, 0.0, 1000.0) == 5
        hpa2 = HpaDecider(min_replicas=2, max_replicas=5, stabilization_s=0,
                          scale_down_window_s=0)
        assert hpa2.desired_replicas(2, 1.0, 1.0, 0.0, 1000.0) == 2


class TestReviewRegressions:
    def test_empty_signals_processor_does_not_crash_reconcile(self):
        from odigos_tpu.api.resources import Processor
        store = Store()
        mgr = ControllerManager(store)
        Scheduler(store, mgr).apply_authored(Configuration())
        Autoscaler(store, mgr, Configuration())
        store.apply(DestinationResource(
            meta=ObjectMeta(name="j1", namespace=ODIGOS_NAMESPACE),
            dest_type="jaeger", signals=["traces"],
            config={"JAEGER_URL": "jaeger:4317"}))
        store.apply(Processor(
            meta=ObjectMeta(name="p", namespace=ODIGOS_NAMESPACE),
            processor_type="batch", signals=[]))
        mgr.run_once()
        assert mgr.errors == []
        cm = store.get("ConfigMap", ODIGOS_NAMESPACE, GATEWAY_CONFIG_NAME)
        root = cm.data["collector-conf"]["service"]["pipelines"]["traces/in"]
        assert "batch/p" in root["processors"]

    def test_deleting_disable_source_resumes_namespace_inheritance(self):
        store, mgr, cluster, _ = make_env()
        ref = add_python_app(cluster).ref
        store.apply(Source(
            meta=ObjectMeta(name="ns-src", namespace="default"),
            workload=WorkloadRef("default", WorkloadKind.NAMESPACE,
                                 "default")))
        store.apply(Source(
            meta=ObjectMeta(name="excluded", namespace="default"),
            workload=ref, disable_instrumentation=True))
        mgr.run_once()
        assert store.get("InstrumentationConfig", "default",
                         ic_name(ref)) is None
        store.delete("Source", "default", "excluded")
        mgr.run_once()
        ic = store.get("InstrumentationConfig", "default", ic_name(ref))
        assert ic is not None
        assert ic.condition(MARKED_FOR_INSTRUMENTATION).reason == \
            "NamespaceSource"

    def test_ignored_namespace_never_instrumented(self):
        cfg = Configuration(ignored_namespaces=["kube-system"])
        store, mgr, cluster, _ = make_env(cfg)
        w = cluster.add_workload("kube-system", "coredns",
                                 [Container(name="main", language="go")])
        store.apply(Source(
            meta=ObjectMeta(name="src", namespace="kube-system"),
            workload=w.ref))
        mgr.run_once()
        assert store.get("InstrumentationConfig", "kube-system",
                         ic_name(w.ref)) is None

    def test_odigos_namespace_protected(self):
        store, mgr, cluster, _ = make_env()
        w = cluster.add_workload("odigos-system", "gateway",
                                 [Container(name="main", language="go")])
        store.apply(Source(
            meta=ObjectMeta(name="src", namespace="odigos-system"),
            workload=w.ref))
        mgr.run_once()
        assert store.get("InstrumentationConfig", "odigos-system",
                         ic_name(w.ref)) is None

    def test_statefulset_resource_attr_kind(self):
        store, mgr, cluster, _ = make_env()
        w = cluster.add_workload(
            "default", "db", [Container(name="main", language="python",
                                        runtime_version="3.11")],
            kind=WorkloadKind.STATEFULSET)
        store.apply(Source(
            meta=ObjectMeta(name="src-db", namespace="default"),
            workload=w.ref))
        mgr.run_once()
        ic = store.get("InstrumentationConfig", "default", ic_name(w.ref))
        ic.runtime_details = [RuntimeDetails(container_name="main",
                                             language="python",
                                             runtime_version="3.11")]
        store.update_status(ic)
        mgr.run_once()
        pod = cluster.pods_of(w.ref)[0]
        assert pod.resource_attrs.get("k8s.statefulset.name") == "db"
        assert "k8s.deployment.name" not in pod.resource_attrs


class TestTpuCoScheduling:
    """North star: the autoscaler co-schedules gateway replicas with TPU
    devices (round-1 review item 6; reference pattern:
    clustercollector/hpa.go:36-68 + virtual-device affinity,
    distros/yamls/golang-community.yaml:15-18)."""

    def make_env(self, tpu_chips=2, anomaly=True):
        from odigos_tpu.nodeagent.deviceplugin import DevicePluginRegistry

        store = Store()
        mgr = ControllerManager(store)
        sched = Scheduler(store, mgr)
        cfg = Configuration()
        cfg.anomaly.enabled = anomaly
        asc = Autoscaler(store, mgr, cfg)
        reg = DevicePluginRegistry(tpu_chips=tpu_chips)
        asc.attach_device_registries([reg])
        sched.apply_authored(cfg)
        mgr.run_once()
        return store, asc, reg

    def test_anomaly_on_replicas_backed_by_devices(self):
        store, asc, reg = self.make_env(tpu_chips=4)
        assert asc.observe_metrics(10.0, 10.0, 0.0, now=1000.0) == 1
        assert asc.tpu_devices_held() == 1
        gw = store.get("CollectorsGroup", ODIGOS_NAMESPACE,
                       GATEWAY_GROUP_NAME)
        cond = next(c for c in gw.conditions if c.type == "TpuScheduling")
        assert cond.status.value == "True"
        assert cond.reason == "DevicesAllocated"

    def test_devices_exhausted_caps_scale_and_sets_condition(self):
        store, asc, reg = self.make_env(tpu_chips=2)
        # drive load high repeatedly: HPA wants +2/15s, devices cap at 2
        n = asc.observe_metrics(160.0, 10.0, 0.0, now=1000.0)
        assert n == 2
        n = asc.observe_metrics(160.0, 10.0, 0.0, now=1020.0)
        assert n == 2, "scale-out must cap at available TPU devices"
        assert asc.tpu_devices_held() == 2
        gw = store.get("CollectorsGroup", ODIGOS_NAMESPACE,
                       GATEWAY_GROUP_NAME)
        cond = next(c for c in gw.conditions if c.type == "TpuScheduling")
        assert cond.status.value == "False"
        assert cond.reason == "TpuStarved"
        assert "2/" in cond.message

    def test_scale_down_releases_devices(self):
        store, asc, reg = self.make_env(tpu_chips=4)
        asc.hpa.stabilization_s = 0.0
        asc.hpa.scale_down_window_s = 0.0
        asc.observe_metrics(160.0, 10.0, 0.0, now=1000.0)
        asc.observe_metrics(160.0, 10.0, 0.0, now=1020.0)
        held_at_peak = asc.tpu_devices_held()
        assert held_at_peak >= 3
        asc.observe_metrics(1.0, 1.0, 0.0, now=2000.0)
        assert asc.tpu_devices_held() < held_at_peak
        from odigos_tpu.nodeagent.deviceplugin import TPU_DEVICE

        free = reg.plugins[TPU_DEVICE].ids.free_count
        assert free == 4 - asc.tpu_devices_held()

    def test_anomaly_off_no_devices_touched(self):
        store, asc, reg = self.make_env(tpu_chips=2, anomaly=False)
        asc.observe_metrics(160.0, 10.0, 0.0, now=1000.0)
        assert asc.tpu_devices_held() == 0
        from odigos_tpu.nodeagent.deviceplugin import TPU_DEVICE

        assert reg.plugins[TPU_DEVICE].ids.free_count == 2

    def test_zero_devices_starved_but_min_replicas_survive(self):
        store, asc, reg = self.make_env(tpu_chips=0)
        n = asc.observe_metrics(160.0, 10.0, 0.0, now=1000.0)
        assert n == 1  # min_replicas floor even unbacked
        gw = store.get("CollectorsGroup", ODIGOS_NAMESPACE,
                       GATEWAY_GROUP_NAME)
        cond = next(c for c in gw.conditions if c.type == "TpuScheduling")
        assert cond.reason == "TpuStarved"


class TestRemainingRuleKinds:
    """custom-instrumentation and otel-sdk rules (round-2 review item 6;
    reference: api/odigos/v1alpha1/instrumentationrules/)."""

    def test_custom_instrumentation_probes_validated(self):
        store, mgr, cluster, _ = make_env()
        w = add_python_app(cluster)
        instrument(store, mgr, w.ref)
        write_runtime_details(store, mgr, w.ref)
        store.apply(InstrumentationRule(
            meta=ObjectMeta(name="probes", namespace="default"),
            rule_kind=RuleKind.CUSTOM_INSTRUMENTATION,
            details={"probes": {
                "python": [{"module": "shop.cart", "function": "checkout"},
                           {"module": "", "function": "broken"}],
                "java": [{"class_name": "Cart", "method_name": "buy"}],
            }}))
        mgr.run_once()
        ic = store.get("InstrumentationConfig", "default", ic_name(w.ref))
        sdk = ic.sdk_configs[0]
        assert sdk.language == "python"
        # the valid python probe survives; the empty-field one is dropped;
        # java probes don't leak into the python SDK config
        assert sdk.custom_probes == [
            {"module": "shop.cart", "function": "checkout"}]

    def test_custom_probes_reach_opamp_remote_config(self):
        from odigos_tpu.nodeagent.opamp import build_remote_config

        store, mgr, cluster, _ = make_env()
        w = add_python_app(cluster)
        instrument(store, mgr, w.ref)
        write_runtime_details(store, mgr, w.ref)
        store.apply(InstrumentationRule(
            meta=ObjectMeta(name="probes", namespace="default"),
            rule_kind=RuleKind.CUSTOM_INSTRUMENTATION,
            details={"probes": {"python": [
                {"module": "shop.cart", "function": "checkout"}]}}))
        mgr.run_once()
        ic = store.get("InstrumentationConfig", "default", ic_name(w.ref))
        sections = build_remote_config(ic, "python")
        assert sections["instrumentation_libraries"][
            "custom_instrumentation"] == [
                {"module": "shop.cart", "function": "checkout"}]

    def test_otel_sdk_rule_overrides_distro(self):
        store, mgr, cluster, instr = make_env()
        instr.distro_provider.tier = "onprem"  # java-ebpf is tier-gated
        w = cluster.add_workload("default", "japp", [
            Container(name="main", language="java",
                      runtime_version="17")])
        instrument(store, mgr, w.ref)
        write_runtime_details(store, mgr, w.ref, details=[
            RuntimeDetails(container_name="main", language="java",
                           runtime_version="17")])
        ic = store.get("InstrumentationConfig", "default", ic_name(w.ref))
        assert ic.containers[0].distro_name == "java-community"
        store.apply(InstrumentationRule(
            meta=ObjectMeta(name="use-ebpf", namespace="default"),
            rule_kind=RuleKind.OTEL_SDK,
            details={"distro_names": ["java-ebpf"]}))
        mgr.run_once()
        ic = store.get("InstrumentationConfig", "default", ic_name(w.ref))
        assert ic.containers[0].distro_name == "java-ebpf"

    def test_otel_sdk_override_still_tier_gated(self):
        store, mgr, cluster, _ = make_env()  # community tier
        w = cluster.add_workload("default", "japp", [
            Container(name="main", language="java",
                      runtime_version="17")])
        instrument(store, mgr, w.ref)
        store.apply(InstrumentationRule(
            meta=ObjectMeta(name="use-ebpf", namespace="default"),
            rule_kind=RuleKind.OTEL_SDK,
            details={"distro_names": ["java-ebpf"]}))
        write_runtime_details(store, mgr, w.ref, details=[
            RuntimeDetails(container_name="main", language="java",
                           runtime_version="17")])
        ic = store.get("InstrumentationConfig", "default", ic_name(w.ref))
        c = ic.containers[0]
        assert not c.agent_enabled
        assert c.reason == AgentEnabledReason.NO_AVAILABLE_AGENT

    def test_otel_sdk_rule_known_distro_resolves(self):
        store, mgr, cluster, _ = make_env()
        w = add_python_app(cluster)
        instrument(store, mgr, w.ref)
        write_runtime_details(store, mgr, w.ref)
        store.apply(InstrumentationRule(
            meta=ObjectMeta(name="explicit", namespace="default"),
            rule_kind=RuleKind.OTEL_SDK,
            details={"distro_names": ["python-community"]}))
        mgr.run_once()
        ic = store.get("InstrumentationConfig", "default", ic_name(w.ref))
        assert ic.containers[0].distro_name == "python-community"

    def test_otel_sdk_rule_unknown_distro_disables_with_reason(self):
        """A typo'd distro name must surface NoAvailableAgent, not fall
        back silently to the default distro (review finding)."""
        store, mgr, cluster, _ = make_env()
        w = add_python_app(cluster)
        instrument(store, mgr, w.ref)
        write_runtime_details(store, mgr, w.ref)
        store.apply(InstrumentationRule(
            meta=ObjectMeta(name="typo", namespace="default"),
            rule_kind=RuleKind.OTEL_SDK,
            details={"distro_names": ["python-comunity"]}))  # typo
        mgr.run_once()
        ic = store.get("InstrumentationConfig", "default", ic_name(w.ref))
        c = ic.containers[0]
        assert not c.agent_enabled
        assert c.reason == AgentEnabledReason.NO_AVAILABLE_AGENT


class TestOtelSdkRuleScoping:
    def test_unknown_distro_respects_workload_selector(self):
        """A typo'd rule scoped to workload B (or disabled) must not
        disable instrumentation for workload A (review finding)."""
        store, mgr, cluster, _ = make_env()
        w = add_python_app(cluster, "a")
        instrument(store, mgr, w.ref)
        store.apply(InstrumentationRule(
            meta=ObjectMeta(name="scoped-typo", namespace="default"),
            rule_kind=RuleKind.OTEL_SDK,
            workloads=[workload_ref("other-app")],
            details={"distro_names": ["python-comunity"]}))
        store.apply(InstrumentationRule(
            meta=ObjectMeta(name="disabled-typo", namespace="default"),
            rule_kind=RuleKind.OTEL_SDK, disabled=True,
            details={"distro_names": ["python-comunity"]}))
        write_runtime_details(store, mgr, w.ref)
        ic = store.get("InstrumentationConfig", "default", ic_name(w.ref))
        assert ic.containers[0].agent_enabled, \
            "rule scoped elsewhere (or disabled) leaked into this workload"
