"""Operator-style single-resource installer (round-2 review item 7;
reference: operator/api/v1alpha1/odigos_types.go:26,105 +
internal/controller/odigos_controller.go): apply one Odigos resource →
full install; delete it → uninstall."""

import pytest

from odigos_tpu.api import ControllerManager, ObjectMeta, Store
from odigos_tpu.api.resources import ConditionStatus, Odigos
from odigos_tpu.controlplane import Autoscaler, Operator, Scheduler
from odigos_tpu.config.model import Configuration
from odigos_tpu.controlplane.autoscaler import GATEWAY_CONFIG_NAME
from odigos_tpu.controlplane.scheduler import (
    EFFECTIVE_CONFIG_NAME,
    GATEWAY_GROUP_NAME,
    ODIGOS_NAMESPACE,
)
from test_auth import make_token  # noqa: E402


def make_plane():
    store = Store()
    mgr = ControllerManager(store)
    Scheduler(store, mgr)
    Autoscaler(store, mgr, Configuration())
    Operator(store, mgr)
    return store, mgr


def test_apply_one_resource_installs_everything():
    store, mgr = make_plane()
    store.apply(Odigos(meta=ObjectMeta(name="odigos",
                                       namespace=ODIGOS_NAMESPACE),
                       telemetry_enabled=True,
                       ignored_namespaces=["kube-system"]))
    mgr.run_once()
    # the whole chain ran: effective config, collectors groups, gateway cfg
    eff = store.get("ConfigMap", ODIGOS_NAMESPACE, EFFECTIVE_CONFIG_NAME)
    assert eff is not None
    assert eff.data["config"]["telemetry_enabled"] is True
    assert eff.data["config"]["ignored_namespaces"] == ["kube-system"]
    assert store.get("CollectorsGroup", ODIGOS_NAMESPACE,
                     GATEWAY_GROUP_NAME) is not None
    assert store.get("ConfigMap", ODIGOS_NAMESPACE,
                     GATEWAY_CONFIG_NAME) is not None
    odigos = store.get("Odigos", ODIGOS_NAMESPACE, "odigos")
    cond = odigos.condition("Installed")
    assert cond.status == ConditionStatus.TRUE
    assert "community" in cond.message


def test_delete_resource_uninstalls():
    store, mgr = make_plane()
    store.apply(Odigos(meta=ObjectMeta(name="odigos",
                                       namespace=ODIGOS_NAMESPACE)))
    mgr.run_once()
    assert store.get("ConfigMap", ODIGOS_NAMESPACE, EFFECTIVE_CONFIG_NAME)
    store.delete("Odigos", ODIGOS_NAMESPACE, "odigos")
    mgr.run_once()
    assert store.get("ConfigMap", ODIGOS_NAMESPACE,
                     EFFECTIVE_CONFIG_NAME) is None
    assert store.get("ConfigMap", ODIGOS_NAMESPACE,
                     GATEWAY_CONFIG_NAME) is None
    assert store.get("CollectorsGroup", ODIGOS_NAMESPACE,
                     GATEWAY_GROUP_NAME) is None


def test_delete_one_of_two_keeps_survivor_installed():
    """Deleting one Odigos resource while another exists must not tear
    down the survivor's stack (advisor r3: reconcile ran the full
    uninstall whenever the event's key no longer resolved)."""
    store, mgr = make_plane()
    store.apply(Odigos(meta=ObjectMeta(name="primary",
                                       namespace=ODIGOS_NAMESPACE),
                       telemetry_enabled=True))
    store.apply(Odigos(meta=ObjectMeta(name="secondary",
                                       namespace=ODIGOS_NAMESPACE)))
    mgr.run_once()
    assert store.get("ConfigMap", ODIGOS_NAMESPACE, EFFECTIVE_CONFIG_NAME)
    store.delete("Odigos", ODIGOS_NAMESPACE, "secondary")
    mgr.run_once()
    # the survivor's install is intact (re-reconciled, not uninstalled)
    eff = store.get("ConfigMap", ODIGOS_NAMESPACE, EFFECTIVE_CONFIG_NAME)
    assert eff is not None
    assert store.get("CollectorsGroup", ODIGOS_NAMESPACE,
                     GATEWAY_GROUP_NAME) is not None
    # deleting the LAST one still uninstalls
    store.delete("Odigos", ODIGOS_NAMESPACE, "primary")
    mgr.run_once()
    assert store.get("ConfigMap", ODIGOS_NAMESPACE,
                     EFFECTIVE_CONFIG_NAME) is None


def test_valid_token_installs_onprem_tier():
    store, mgr = make_plane()
    store.apply(Odigos(meta=ObjectMeta(name="odigos",
                                       namespace=ODIGOS_NAMESPACE),
                       on_prem_token=make_token(),
                       profiles=["java-ebpf-instrumentations"]))
    mgr.run_once()
    odigos = store.get("Odigos", ODIGOS_NAMESPACE, "odigos")
    cond = odigos.condition("Installed")
    assert cond.status == ConditionStatus.TRUE and "onprem" in cond.message
    # the tier-gated profile resolved (would be a problem under community)
    eff = store.get("ConfigMap", ODIGOS_NAMESPACE, EFFECTIVE_CONFIG_NAME)
    assert "java-ebpf-instrumentations" in eff.data["applied_profiles"]


def test_invalid_token_blocks_install():
    store, mgr = make_plane()
    store.apply(Odigos(meta=ObjectMeta(name="odigos",
                                       namespace=ODIGOS_NAMESPACE),
                       on_prem_token="garbage"))
    mgr.run_once()
    odigos = store.get("Odigos", ODIGOS_NAMESPACE, "odigos")
    cond = odigos.condition("Installed")
    assert cond.status == ConditionStatus.FALSE
    assert cond.reason == "InvalidToken"
    assert store.get("ConfigMap", ODIGOS_NAMESPACE,
                     EFFECTIVE_CONFIG_NAME) is None


def test_spec_update_reconciles_config():
    store, mgr = make_plane()
    store.apply(Odigos(meta=ObjectMeta(name="odigos",
                                       namespace=ODIGOS_NAMESPACE)))
    mgr.run_once()
    odigos = store.get("Odigos", ODIGOS_NAMESPACE, "odigos")
    odigos.ignored_containers = ["istio-proxy"]
    store.apply(odigos)
    mgr.run_once()
    eff = store.get("ConfigMap", ODIGOS_NAMESPACE, EFFECTIVE_CONFIG_NAME)
    assert eff.data["config"]["ignored_containers"] == ["istio-proxy"]


def test_cloud_token_does_not_escalate_to_onprem():
    """The audience claim is the entitlement on the operator path too: a
    cloud token requesting an onprem-gated profile blocks the install,
    exactly as cmd_install would."""
    store, mgr = make_plane()
    store.apply(Odigos(meta=ObjectMeta(name="odigos",
                                       namespace=ODIGOS_NAMESPACE),
                       on_prem_token=make_token(aud="cloud"),
                       profiles=["java-ebpf-instrumentations"]))
    mgr.run_once()
    odigos = store.get("Odigos", ODIGOS_NAMESPACE, "odigos")
    cond = odigos.condition("Installed")
    assert cond.status == ConditionStatus.FALSE
    assert cond.reason == "InvalidProfiles"
    assert store.get("ConfigMap", ODIGOS_NAMESPACE,
                     EFFECTIVE_CONFIG_NAME) is None


def test_unknown_profile_blocks_install_with_condition():
    store, mgr = make_plane()
    store.apply(Odigos(meta=ObjectMeta(name="odigos",
                                       namespace=ODIGOS_NAMESPACE),
                       profiles=["no-such-profile"]))
    mgr.run_once()
    cond = store.get("Odigos", ODIGOS_NAMESPACE,
                     "odigos").condition("Installed")
    assert cond.status == ConditionStatus.FALSE
    assert cond.reason == "InvalidProfiles"
    assert "no-such-profile" in cond.message


def test_operator_tier_reaches_distro_provider():
    """An operator-validated onprem token enables tier-gated distros in a
    control plane booted at community tier (review finding: the tier
    previously reached only the scheduler)."""
    from odigos_tpu.api.resources import (
        InstrumentationRule, ObjectMeta as OM, RuleKind, RuntimeDetails,
        Source, WorkloadKind, WorkloadRef)
    from odigos_tpu.controlplane import Cluster, Container, Instrumentor
    from odigos_tpu.controlplane.instrumentor import ic_name

    store = Store()
    mgr = ControllerManager(store)
    cluster = Cluster(nodes=1)
    Scheduler(store, mgr)
    Autoscaler(store, mgr, Configuration())
    Instrumentor(store, mgr, cluster, Configuration())  # community boot
    Operator(store, mgr)
    store.apply(Odigos(meta=ObjectMeta(name="odigos",
                                       namespace=ODIGOS_NAMESPACE),
                       on_prem_token=make_token(aud="onprem")))
    w = cluster.add_workload("default", "japp", [
        Container(name="main", language="java", runtime_version="17")])
    store.apply(Source(meta=OM(name="src-japp", namespace="default"),
                       workload=w.ref))
    store.apply(InstrumentationRule(
        meta=OM(name="use-ebpf", namespace="default"),
        rule_kind=RuleKind.OTEL_SDK,
        details={"distro_names": ["java-ebpf"]}))
    mgr.run_once()
    ic = store.get("InstrumentationConfig", "default", ic_name(w.ref))
    ic.runtime_details = [RuntimeDetails(container_name="main",
                                         language="java",
                                         runtime_version="17")]
    store.update_status(ic)
    mgr.run_once()
    ic = store.get("InstrumentationConfig", "default", ic_name(w.ref))
    assert ic.containers[0].agent_enabled
    assert ic.containers[0].distro_name == "java-ebpf"


def test_uninstall_strips_agents_from_workloads():
    """Deleting the Odigos resource un-instruments running pods via the
    Source-deletion path (review finding: agents previously survived)."""
    from odigos_tpu.controlplane import Cluster, Container, Instrumentor
    from odigos_tpu.api.resources import RuntimeDetails, Source
    from odigos_tpu.api import ObjectMeta as OM
    from odigos_tpu.controlplane.instrumentor import ic_name
    from odigos_tpu.config.model import RolloutConfiguration

    store = Store()
    mgr = ControllerManager(store)
    cluster = Cluster(nodes=1)
    Scheduler(store, mgr)
    Autoscaler(store, mgr, Configuration())
    Instrumentor(store, mgr, cluster, Configuration(
        rollout=RolloutConfiguration(rollback_grace_time_s=0.0)))
    Operator(store, mgr)
    store.apply(Odigos(meta=ObjectMeta(name="odigos",
                                       namespace=ODIGOS_NAMESPACE)))
    w = cluster.add_workload("default", "app", [
        Container(name="main", language="python", runtime_version="3.11")])
    store.apply(Source(meta=OM(name="src-app", namespace="default"),
                       workload=w.ref))
    mgr.run_once()
    ic = store.get("InstrumentationConfig", "default", ic_name(w.ref))
    ic.runtime_details = [RuntimeDetails(container_name="main",
                                         language="python",
                                         runtime_version="3.11")]
    store.update_status(ic)
    mgr.run_once()
    assert any(p.injected_env for p in cluster.pods.values())

    store.delete("Odigos", ODIGOS_NAMESPACE, "odigos")
    mgr.run_once()
    assert store.get("InstrumentationConfig", "default",
                     ic_name(w.ref)) is None
    assert all(not p.injected_env for p in cluster.pods.values())


def test_invalid_spec_enum_surfaces_condition():
    store, mgr = make_plane()
    store.apply(Odigos(meta=ObjectMeta(name="odigos",
                                       namespace=ODIGOS_NAMESPACE),
                       ui_mode="dark"))
    mgr.run_once()
    cond = store.get("Odigos", ODIGOS_NAMESPACE,
                     "odigos").condition("Installed")
    assert cond.status == ConditionStatus.FALSE
    assert cond.reason == "InvalidSpec"


def test_uninstall_removes_destinations():
    from odigos_tpu.api.resources import DestinationResource

    store, mgr = make_plane()
    store.apply(Odigos(meta=ObjectMeta(name="odigos",
                                       namespace=ODIGOS_NAMESPACE)))
    store.apply(DestinationResource(
        meta=ObjectMeta(name="old-backend", namespace=ODIGOS_NAMESPACE),
        dest_type="tracedb", signals=["traces"]))
    mgr.run_once()
    store.delete("Odigos", ODIGOS_NAMESPACE, "odigos")
    mgr.run_once()
    assert store.list("DestinationResource") == []
