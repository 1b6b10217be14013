"""Platform autodetect + gatekeeper policy suite (round-4 review item 9;
reference: cli/pkg/autodetect/ detectors, tests/gatekeeper/constraints)."""

import json
import subprocess
import sys

import pytest

from odigos_tpu.cli.autodetect import (
    detect_cgroup_version, detect_cluster_kind, detect_platform,
    detect_systemd, detect_tpu)
from odigos_tpu.config.model import Configuration
from odigos_tpu.controlplane.gatekeeper import (
    Violation, default_constraints, restrict_hostpath, validate)
from odigos_tpu.controlplane.manifests import render_manifests


class TestAutodetect:
    def test_cluster_kind_signals(self):
        # the reference's detector set, first match wins
        assert detect_cluster_kind("kind-local") == "kind"
        assert detect_cluster_kind("", "k3d-dev") == "k3s"
        assert detect_cluster_kind(
            "arn:aws:eks:eu-west-1:1:cluster/x") == "eks"
        assert detect_cluster_kind("gke_proj_zone_name") == "gke"
        assert detect_cluster_kind("prod-aks") == "aks"
        assert detect_cluster_kind("openshift-prod") == "openshift"
        assert detect_cluster_kind("minikube") == "minikube"
        assert detect_cluster_kind("corp-cluster") == "vanilla"

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("ODIGOS_KUBE_CONTEXT", "kind-ci")
        assert detect_cluster_kind() == "kind"

    def test_filesystem_traits(self, tmp_path, monkeypatch):
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        # cgroup v2 marker
        cg = tmp_path / "sys" / "fs" / "cgroup"
        cg.mkdir(parents=True)
        assert detect_cgroup_version(str(cg)) == 1
        (cg / "cgroup.controllers").write_text("cpu memory")
        assert detect_cgroup_version(str(cg)) == 2
        # systemd
        assert not detect_systemd(str(tmp_path / "run/systemd/system"))
        (tmp_path / "run" / "systemd" / "system").mkdir(parents=True)
        assert detect_systemd(str(tmp_path / "run/systemd/system"))
        # tpu device nodes
        dev = tmp_path / "dev"
        dev.mkdir()
        assert not detect_tpu(str(dev / "accel*"))
        (dev / "accel0").write_text("")
        assert detect_tpu(str(dev / "accel*"))

    def test_detect_platform_sysroot(self, tmp_path, monkeypatch):
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.delenv("ODIGOS_CLUSTER_NAME", raising=False)
        monkeypatch.delenv("ODIGOS_KUBE_CONTEXT", raising=False)
        (tmp_path / "sys/fs/cgroup").mkdir(parents=True)
        (tmp_path / "sys/fs/cgroup/cgroup.controllers").write_text("cpu")
        (tmp_path / "dev").mkdir()
        (tmp_path / "dev" / "accel0").write_text("")
        p = detect_platform(cluster_name="gke_prj_z_n",
                            sysroot=str(tmp_path))
        assert p == {"kind": "gke", "cgroup_version": 2,
                     "systemd": False, "tpu_present": True}


class TestManifests:
    def test_baseline_resource_defaults(self):
        ms = render_manifests(Configuration(), {})
        by_name = {m["metadata"]["name"]: m for m in ms}
        # control-plane 500m/128Mi limits (BASELINE.md)
        inst = by_name["odigos-instrumentor"]
        res = inst["spec"]["template"]["spec"]["containers"][0]["resources"]
        assert res["limits"] == {"cpu": "500m", "memory": "128Mi"}
        # gateway from sizing: 500m/500Mi request, 1000m limit,
        # memory limit 1.25x request
        gw = by_name["odigos-gateway"]["spec"]["template"]["spec"][
            "containers"][0]["resources"]
        assert gw["requests"] == {"cpu": "500m", "memory": "500Mi"}
        assert gw["limits"]["cpu"] == "1000m"
        assert gw["limits"]["memory"] == "625Mi"

    def test_platform_adaptation_changes_output(self):
        base = render_manifests(Configuration(), {"kind": "vanilla",
                                                  "cgroup_version": 2})
        osft = render_manifests(Configuration(), {"kind": "openshift",
                                                  "cgroup_version": 1})
        tpu = render_manifests(Configuration(), {"tpu_present": True})

        def odiglet(ms):
            return next(m for m in ms
                        if m["metadata"]["name"] == "odiglet")

        # openshift: SCC annotation + SELinux type
        assert "openshift.io/required-scc" in \
            odiglet(osft)["metadata"]["annotations"]
        assert "openshift.io/required-scc" not in \
            odiglet(base)["metadata"]["annotations"]
        sc = odiglet(osft)["spec"]["template"]["spec"]["containers"][0][
            "securityContext"]
        assert sc["seLinuxOptions"]["type"] == "spc_t"
        # cgroup v1: split hierarchy mounts (valid k8s hostPath shape)
        v1_paths = [v["hostPath"]["path"] for v in
                    odiglet(osft)["spec"]["template"]["spec"]["volumes"]]
        assert "/sys/fs/cgroup/cpu" in v1_paths
        v2_paths = [v["hostPath"]["path"] for v in
                    odiglet(base)["spec"]["template"]["spec"]["volumes"]]
        assert "/sys/fs/cgroup" in v2_paths
        # tpu: deviceplugin container + gateway TPU resource
        names = [c["name"] for c in
                 odiglet(tpu)["spec"]["template"]["spec"]["containers"]]
        assert "deviceplugin" in names
        gw = next(m for m in tpu
                  if m["metadata"]["name"] == "odigos-gateway")
        assert gw["spec"]["template"]["spec"]["containers"][0][
            "resources"]["limits"].get("odigos.io/tpu") == "1"

    def test_pro_component_gated_by_tier(self):
        names = {m["metadata"]["name"]
                 for m in render_manifests(Configuration(), {}, "onprem")}
        assert "odigos-pro" in names
        names = {m["metadata"]["name"]
                 for m in render_manifests(Configuration(), {},
                                           "community")}
        assert "odigos-pro" not in names


class TestGatekeeper:
    def test_rendered_install_passes_default_policy(self):
        for platform in ({}, {"kind": "openshift", "cgroup_version": 1},
                         {"tpu_present": True}):
            ms = render_manifests(Configuration(), platform, "onprem")
            assert validate(ms) == [], platform

    def test_privileged_outside_exemption_violates(self):
        ms = render_manifests(Configuration(), {})
        gw = next(m for m in ms
                  if m["metadata"]["name"] == "odigos-gateway")
        gw["spec"]["template"]["spec"]["containers"][0][
            "securityContext"]["privileged"] = True
        vs = validate(ms)
        assert any(v.constraint == "restrict-privileged"
                   and v.manifest == "odigos-gateway" for v in vs)

    def test_host_namespace_and_escalation_violations(self):
        ms = render_manifests(Configuration(), {})
        ui = next(m for m in ms if m["metadata"]["name"] == "odigos-ui")
        ui["spec"]["template"]["spec"]["hostNetwork"] = True
        ui["spec"]["template"]["spec"]["containers"][0][
            "securityContext"].pop("allowPrivilegeEscalation")
        vs = validate(ms)
        kinds = {v.constraint for v in vs if v.manifest == "odigos-ui"}
        assert kinds == {"restrict-host-namespace",
                         "restrict-privilege-escalation"}

    def test_hostpath_allowlist(self):
        m = {"apiVersion": "apps/v1", "kind": "DaemonSet",
             "metadata": {"name": "x"},
             "spec": {"template": {"spec": {
                 "containers": [],
                 "volumes": [{"name": "v",
                              "hostPath": "/etc/kubernetes"}]}}}}
        vs = validate([m], [restrict_hostpath(("/var/odigos",))])
        assert vs and "hostPath /etc/kubernetes" in vs[0].detail
        # prefix match: children of allowed roots pass
        m["spec"]["template"]["spec"]["volumes"][0]["hostPath"] = \
            "/var/odigos/rings"
        assert validate([m], [restrict_hostpath(("/var/odigos",))]) == []


class TestCliIntegration:
    def _run(self, tmp_path, *argv, env_extra=None):
        import os

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu",
                   **(env_extra or {}))
        return subprocess.run(
            [sys.executable, "-m", "odigos_tpu.cli", "--state-dir",
             str(tmp_path / "state"), *argv],
            env=env, capture_output=True, text=True, cwd=repo,
            timeout=180)

    def test_install_detects_and_persists_platform(self, tmp_path):
        r = self._run(tmp_path, "install",
                      env_extra={"ODIGOS_KUBE_CONTEXT": "kind-ci"})
        assert r.returncode == 0, r.stderr + r.stdout
        assert "platform: " in r.stdout
        assert "kind=kind" in r.stdout
        state = json.loads(
            (tmp_path / "state" / "state.json").read_text())
        assert state["config"]["extra"]["platform"]["kind"] == "kind"

    def test_manifests_command_renders_and_validates(self, tmp_path):
        r = self._run(tmp_path, "install")
        assert r.returncode == 0, r.stderr
        r = self._run(tmp_path, "manifests")
        assert r.returncode == 0, r.stderr + r.stdout
        ms = json.loads(r.stdout)
        assert {m["metadata"]["name"] for m in ms} >= {
            "odiglet", "odigos-gateway", "odigos-instrumentor"}

    def test_preflight_includes_policy_check(self, tmp_path):
        r = self._run(tmp_path, "install")
        assert r.returncode == 0, r.stderr
        r = self._run(tmp_path, "preflight")
        assert "manifests pass constraint policy" in r.stdout
