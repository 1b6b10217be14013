"""OpAMP across a real process boundary (round-2 review item 3): the socket
transport carries the same messages the in-process client exchanges, and
the socket's lifetime is the agent's liveness signal (reference:
opampserver/pkg/server/server.go:23, handlers.go:43 connection handling).
"""

import os
import signal
import subprocess
import sys
import time

from odigos_tpu.api import ObjectMeta, Store, WorkloadKind, WorkloadRef
from odigos_tpu.api.resources import InstrumentationConfig, SdkConfig
from odigos_tpu.controlplane.instrumentor import ic_name
from odigos_tpu.nodeagent import OpampServer
from odigos_tpu.nodeagent.opamp_socket import (
    OpampSocketAgent,
    OpampSocketServer,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def wait_for(cond, timeout=10.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.02)
    return False


def opamp_store():
    store = Store()
    ref = WorkloadRef("default", WorkloadKind.DEPLOYMENT, "app")
    store.apply(InstrumentationConfig(
        meta=ObjectMeta(name=ic_name(ref), namespace="default"),
        workload=ref, service_name="app-svc",
        data_stream_names=["default"],
        sdk_configs=[SdkConfig(language="python",
                               payload_collection="db")]))
    return store, ref


DESC = {"namespace": "default", "workload_kind": "deployment",
        "workload_name": "app", "pod_name": "app-pod-1",
        "container_name": "main", "pid": 4242, "language": "python"}


class TestSocketTransport:
    def test_connect_pushes_config_over_socket(self, tmp_path):
        store, _ = opamp_store()
        server = OpampServer(store, node="node-0")
        sock = str(tmp_path / "opamp.sock")
        ssrv = OpampSocketServer(server, sock).start()
        try:
            agent = OpampSocketAgent(sock, "uid-1", DESC)
            agent.connect()
            cfg = agent.wait_for_config(5.0)
            assert cfg is not None
            assert cfg["sdk"]["service_name"] == "app-svc"
            assert cfg["instrumentation_libraries"][
                "payload_collection"] == "db"
            agent.heartbeat(healthy=True, message="running")
            assert wait_for(lambda: any(
                i.healthy for i in store.list("InstrumentationInstance")))
            inst = store.list("InstrumentationInstance")[0]
            assert inst.pid == 4242
            assert inst.identifying_attributes[
                "k8s.node.name"] == "node-0"
            agent.disconnect()
        finally:
            ssrv.shutdown()

    def test_config_change_repush_rides_socket(self, tmp_path):
        store, ref = opamp_store()
        server = OpampServer(store)
        sock = str(tmp_path / "opamp.sock")
        ssrv = OpampSocketServer(server, sock).start()
        try:
            agent = OpampSocketAgent(sock, "uid-1", DESC)
            agent.connect()
            agent.wait_for_config(5.0)
            ic = store.get("InstrumentationConfig", "default", ic_name(ref))
            ic.service_name = "renamed"
            store.apply(ic)
            assert wait_for(lambda: server.connected_uids == ["uid-1"])
            assert server.config_changed(ref) == 1
            assert wait_for(
                lambda: agent.remote_config["sdk"][
                    "service_name"] == "renamed")
            agent.disconnect()
        finally:
            ssrv.shutdown()

    def test_socket_close_marks_unhealthy(self, tmp_path):
        store, _ = opamp_store()
        server = OpampServer(store)
        sock = str(tmp_path / "opamp.sock")
        ssrv = OpampSocketServer(server, sock).start()
        try:
            agent = OpampSocketAgent(sock, "uid-1", DESC)
            agent.connect()
            assert wait_for(lambda: server.connected_uids == ["uid-1"])
            agent.disconnect()  # just closes the socket — no goodbye message
            assert wait_for(lambda: server.connected_uids == [])
            inst = store.list("InstrumentationInstance")[0]
            assert inst.healthy is False
            assert "disconnected" in inst.message
        finally:
            ssrv.shutdown()

    def test_sweep_expires_silent_agent(self, tmp_path):
        store, _ = opamp_store()
        server = OpampServer(store, heartbeat_timeout=0.3)
        sock = str(tmp_path / "opamp.sock")
        ssrv = OpampSocketServer(server, sock, sweep_interval_s=0.1).start()
        try:
            agent = OpampSocketAgent(sock, "uid-1", DESC)
            agent.connect()  # connects, then never heartbeats
            assert wait_for(lambda: server.connected_uids == ["uid-1"])
            assert wait_for(lambda: server.connected_uids == [], timeout=5)
            inst = store.list("InstrumentationInstance")[0]
            assert inst.healthy is False
        finally:
            ssrv.shutdown()


class TestCrossProcess:
    def test_agent_process_lifecycle(self, tmp_path):
        """Server and agent in different processes; SIGKILL the agent and
        the instance goes unhealthy via socket EOF — the reference's whole
        reason for a wire protocol."""
        store, _ = opamp_store()
        server = OpampServer(store, node="node-0")
        sock = str(tmp_path / "opamp.sock")
        ssrv = OpampSocketServer(server, sock).start()
        env = dict(os.environ, PYTHONPATH=REPO)
        proc = subprocess.Popen(
            [sys.executable, "-m", "odigos_tpu.nodeagent.opamp_socket",
             "--socket", sock, "--uid", "proc-uid", "--namespace", "default",
             "--name", "app", "--interval-s", "0.1"],
            env=env, cwd=REPO, stdout=subprocess.PIPE)
        try:
            assert wait_for(lambda: any(
                i.healthy for i in store.list("InstrumentationInstance")),
                timeout=15), "agent process never reported healthy"
            assert server.connected_uids == ["proc-uid"]
            inst = store.list("InstrumentationInstance")[0]
            assert inst.pid == proc.pid

            proc.send_signal(signal.SIGKILL)  # no goodbye, no flush
            proc.wait(timeout=10)
            assert wait_for(lambda: server.connected_uids == [], timeout=10)
            inst = store.list("InstrumentationInstance")[0]
            assert inst.healthy is False
        finally:
            if proc.poll() is None:
                proc.kill()
            proc.stdout.close()
            ssrv.shutdown()
