"""Operator API layer (frontend/): HTTP/JSON over the store, SSE push, and
the collector-metrics consumer fed by the gateway's otlp/ui stream over the
real wire (round-1 review item 5; reference: frontend/main.go:155,217 +
services/collector_metrics).
"""

import json
import threading
import urllib.request

import pytest

from odigos_tpu.components.api import Signal
from odigos_tpu.destinations import Destination
from odigos_tpu.e2e.environment import E2EEnvironment
from odigos_tpu.frontend import CollectorMetricsConsumer, FrontendServer
from odigos_tpu.frontend.collector_metrics import parse_flat_name
from odigos_tpu.pdata import synthesize_traces


def get_json(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return json.loads(r.read())


def post_json(url, body):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=10) as r:
        return r.status, json.loads(r.read())


# ------------------------------------------------------------- unit level
def test_parse_flat_name():
    assert parse_flat_name("x_total") == ("x_total", {})
    assert parse_flat_name("x_total{service=cart}") == (
        "x_total", {"service": "cart"})
    assert parse_flat_name("x{pipeline=traces/in,extra=1}") == (
        "x", {"pipeline": "traces/in", "extra": "1"})


def test_consumer_rates_from_counter_deltas():
    from odigos_tpu.components.receivers.prometheus import snapshot_to_batch

    c = CollectorMetricsConsumer()
    b1 = snapshot_to_batch({"odigos_traffic_spans_total{service=cart}": 100})
    c.consume(b1)
    # 10s later, 400 more spans
    import numpy as np

    b2 = snapshot_to_batch({"odigos_traffic_spans_total{service=cart}": 500})
    cols = dict(b2.columns)
    cols["time_unix_nano"] = b1.col("time_unix_nano") + np.uint64(10_000_000_000)
    from dataclasses import replace

    c.consume(replace(b2, columns=cols))
    tp = c.throughput()
    svc = tp["services"]["cart"]["odigos_traffic_spans_total"]
    assert svc["total"] == 500
    assert svc["per_sec"] == pytest.approx(40.0, rel=0.01)


# ---------------------------------------------------------------- e2e
@pytest.fixture
def env_with_frontend():
    env = E2EEnvironment(nodes=1)
    fe = FrontendServer(env.store, cluster=env.cluster).start()
    env.config.ui_endpoint = f"127.0.0.1:{fe.metrics_port}"
    env.start()
    try:
        yield env, fe
    finally:
        env.shutdown()
        fe.shutdown()


def test_api_reflects_store_and_metrics_flow(env_with_frontend):
    env, fe = env_with_frontend
    from odigos_tpu.controlplane.cluster import Container

    env.cluster.add_workload("shop", "cart",
                             [Container("main", language="python")])
    env.instrument_workload("shop", "cart")
    env.add_destination(Destination(
        id="db", dest_type="tracedb", signals=[Signal.TRACES]))

    base = fe.url
    assert get_json(f"{base}/healthz")["status"] == "ok"

    sources = get_json(f"{base}/api/sources")
    assert len(sources) == 1 and sources[0]["meta"]["name"] == "src-cart"

    ics = get_json(f"{base}/api/instrumentation-configs")
    assert len(ics) == 1
    assert any(c["type"] == "AgentEnabled" for c in ics[0]["conditions"])

    dests = get_json(f"{base}/api/destinations")
    assert len(dests) == 1 and dests[0]["dest_type"] == "tracedb"

    topo = get_json(f"{base}/api/pipeline")
    assert topo["pipelines"], "gateway topology empty"
    assert any(n["type"] == "odigostrafficmetrics" for n in topo["nodes"])

    # traffic through the gateway, then its self-scrape ships the
    # own-metrics batch over the wire to the frontend consumer
    env.send_traces(synthesize_traces(50, seed=1))
    scraper = env.gateway_component("prometheus/self-metrics")
    scraper.scrape_once()
    ui_exporter = env.gateway_component("otlp/ui")
    assert ui_exporter.flush(timeout=10), "otlp/ui did not drain"

    deadline = threading.Event()
    for _ in range(100):
        tp = get_json(f"{base}/api/metrics")
        if tp["batches_received"] > 0:
            break
        deadline.wait(0.05)
    assert tp["batches_received"] > 0, "no metrics batch reached frontend"
    totals = tp["pipelines"]
    assert any("odigos_traffic_spans_total" in m for m in totals.values()), totals

    anomalies = get_json(f"{base}/api/anomalies")
    assert "flagged" in anomalies and "scored" in anomalies

    desc = get_json(f"{base}/api/describe/workload?namespace=shop"
                    "&kind=deployment&name=cart")
    assert "MarkedForInstrumentation" in desc["text"]


def test_sse_stream_pushes_store_events(env_with_frontend):
    env, fe = env_with_frontend
    events = []
    got_one = threading.Event()

    def listen():
        req = urllib.request.Request(f"{fe.url}/api/events")
        with urllib.request.urlopen(req, timeout=15) as r:
            for raw in r:
                line = raw.decode().strip()
                if line.startswith("data: "):
                    events.append(json.loads(line[6:]))
                    got_one.set()
                    return

    t = threading.Thread(target=listen, daemon=True)
    t.start()
    import time

    time.sleep(0.3)  # let the client subscribe
    from odigos_tpu.controlplane.cluster import Container

    env.cluster.add_workload("shop", "web",
                             [Container("main", language="python")])
    env.instrument_workload("shop", "web")
    assert got_one.wait(10), "no SSE event received"
    assert events and events[0]["kind"]


def test_mutating_endpoints(env_with_frontend):
    env, fe = env_with_frontend
    from odigos_tpu.controlplane.cluster import Container

    env.cluster.add_workload("shop", "pay",
                             [Container("main", language="python")])
    status, out = post_json(f"{fe.url}/api/sources",
                            {"namespace": "shop", "name": "pay"})
    assert status == 201 and out["applied"] == "src-pay"
    env.reconcile()
    assert env.store.get("InstrumentationConfig", "shop",
                         "deployment-pay") is not None

    req = urllib.request.Request(f"{fe.url}/api/sources/shop/src-pay",
                                 method="DELETE")
    with urllib.request.urlopen(req, timeout=10) as r:
        assert r.status == 200
    assert env.store.get("Source", "shop", "src-pay") is None


def test_dashboard_page_serves(env_with_frontend):
    """The webapp analog: the dashboard page serves at / and wires itself to
    the data endpoints the page's JS polls (round-2 review item 2)."""
    env, fe = env_with_frontend
    with urllib.request.urlopen(fe.url + "/", timeout=10) as r:
        assert r.status == 200
        assert r.headers["Content-Type"].startswith("text/html")
        page = r.read().decode()
    # every endpoint the page polls must exist and round-trip
    for endpoint in ("/api/pipeline", "/api/metrics", "/api/anomalies",
                     "/api/sources", "/api/destinations", "/api/events"):
        assert endpoint in page, f"dashboard does not reference {endpoint}"
        if endpoint != "/api/events":
            get_json(fe.url + endpoint)  # 200 + JSON body
    for element in ("pipeline", "throughput", "anomalies", "eventlog",
                    "tiles"):
        assert f'id="{element}"' in page
    # /dashboard is an alias
    with urllib.request.urlopen(fe.url + "/dashboard", timeout=10) as r:
        assert r.read().decode() == page


def test_sse_client_cap_sheds_excess(env_with_frontend):
    env, fe = env_with_frontend
    fe.max_sse_clients = 2
    import time

    held = []
    try:
        for _ in range(2):
            held.append(urllib.request.urlopen(
                f"{fe.url}/api/events", timeout=10))
        time.sleep(0.2)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{fe.url}/api/events", timeout=10)
        assert ei.value.code == 503
    finally:
        for h in held:
            h.close()


def test_sse_heartbeat_frees_dead_client(env_with_frontend):
    """A silently-disconnected SSE client is detected by the ping write and
    unsubscribed (round-2 advisor finding: handler threads leaked)."""
    env, fe = env_with_frontend
    fe.sse_heartbeat_s = 0.1
    import time

    conn = urllib.request.urlopen(f"{fe.url}/api/events", timeout=10)
    deadline = time.time() + 5
    while not fe._sse_clients and time.time() < deadline:
        time.sleep(0.02)
    assert len(fe._sse_clients) == 1
    conn.close()  # client vanishes without a byte
    deadline = time.time() + 5
    while fe._sse_clients and time.time() < deadline:
        time.sleep(0.05)
    assert not fe._sse_clients, "dead SSE client never unsubscribed"


def test_series_rate_resets_on_counter_reset():
    """Collector restart: the cumulative counter drops; the stale rate must
    not be reported forever (round-2 advisor finding)."""
    from odigos_tpu.frontend.collector_metrics import _Series

    s = _Series()
    s.observe(100.0, 10.0)
    s.observe(500.0, 20.0)
    assert s.rate == pytest.approx(40.0)
    s.observe(50.0, 30.0)  # restart: counter went backwards
    assert s.rate == 0.0
    s.observe(150.0, 40.0)  # rates resume from the new baseline
    assert s.rate == pytest.approx(10.0)


def test_dashboard_source_form_and_sparkline_wiring(env_with_frontend):
    """The dashboard carries the sources CRUD form (wired to the POST/
    DELETE endpoints) and the throughput sparkline."""
    env, fe = env_with_frontend
    with urllib.request.urlopen(fe.url + "/", timeout=10) as r:
        page = r.read().decode()
    for element in ('id="src-add"', 'id="src-ns"', 'id="src-name"',
                    "data-del-src", "sparkline", 'method: "POST"',
                    '{method: "DELETE"}'):
        assert element in page, f"dashboard missing {element}"


def test_delete_source_with_encoded_name(env_with_frontend):
    """Percent-encoded DELETE paths decode server-side: a workload name
    with a space is removable from the dashboard (review finding)."""
    env, fe = env_with_frontend
    status, _ = post_json(f"{fe.url}/api/sources",
                          {"namespace": "shop", "name": "my app"})
    assert status == 201
    req = urllib.request.Request(
        f"{fe.url}/api/sources/shop/src-my%20app", method="DELETE")
    with urllib.request.urlopen(req, timeout=10) as r:
        assert r.status == 200
    assert env.store.get("Source", "shop", "src-my app") is None


def test_destination_secret_env_lifecycle_over_socket(monkeypatch):
    """Env-secret delivery/revocation through the JSON API (round-4
    advisor, medium): env names are type-scoped, so deleting one of two
    same-type destinations must keep the survivor's credential; deleting
    the last one revokes exactly what the server delivered — never an
    ambient operator env var."""
    import os

    from odigos_tpu.api.store import Store

    monkeypatch.delenv("DATADOG_API_KEY", raising=False)
    fe = FrontendServer(Store(), metrics_port=None).start()
    base = fe.url
    try:
        def delete(path):
            req = urllib.request.Request(base + path, method="DELETE")
            with urllib.request.urlopen(req, timeout=10) as r:
                body = json.loads(r.read())
                # the response names the deleted DESTINATION (clients
                # confirm against it), never an env-var name
                assert body["deleted"] == path.rsplit("/", 1)[-1], body
                return r.status

        status, _ = post_json(f"{base}/api/destinations", {
            "name": "dd-a", "type": "datadog", "signals": ["traces"],
            "fields": {"DATADOG_SITE": "datadoghq.com",
                       "DATADOG_API_KEY": "delivered-key"}})
        assert status == 201
        assert os.environ["DATADOG_API_KEY"] == "delivered-key"
        # dd-b rides the already-delivered credential (no key supplied)
        status, _ = post_json(f"{base}/api/destinations", {
            "name": "dd-b", "type": "datadog", "signals": ["traces"],
            "fields": {"DATADOG_SITE": "datadoghq.eu"}})
        assert status == 201
        assert delete("/api/destinations/dd-a") == 200
        assert os.environ.get("DATADOG_API_KEY") == "delivered-key", \
            "survivor's shared credential revoked"
        assert delete("/api/destinations/dd-b") == 200
        assert "DATADOG_API_KEY" not in os.environ, \
            "delivered credential lingered after last same-type delete"

        # ambient env vars the server never delivered are never popped
        monkeypatch.setenv("DATADOG_API_KEY", "operator-ambient")
        status, _ = post_json(f"{base}/api/destinations", {
            "name": "dd-c", "type": "datadog", "signals": ["traces"],
            "fields": {"DATADOG_SITE": "datadoghq.com"}})
        assert status == 201
        assert delete("/api/destinations/dd-c") == 200
        assert os.environ.get("DATADOG_API_KEY") == "operator-ambient"
    finally:
        fe.shutdown()


class TestFrontendAuth:
    """Bearer/session middleware (round-4 review item 6; reference OIDC
    middleware frontend/main.go:130): with auth configured, mutations
    and SSE require a token; reads stay open; open servers unchanged."""

    def _server(self, token="s3ss10n"):
        from odigos_tpu.api.store import Store

        return FrontendServer(Store(), metrics_port=None,
                              auth_token=token).start()

    def _post(self, url, body, token=None):
        headers = {"Content-Type": "application/json"}
        if token:
            headers["Authorization"] = f"Bearer {token}"
        req = urllib.request.Request(
            url, data=json.dumps(body).encode(), headers=headers,
            method="POST")
        try:
            with urllib.request.urlopen(req, timeout=10) as r:
                return r.status
        except urllib.error.HTTPError as e:
            return e.code

    def test_unauthenticated_mutation_rejected_401(self):
        import urllib.error

        fe = self._server()
        try:
            body = {"namespace": "shop", "name": "cart"}
            assert self._post(f"{fe.url}/api/sources", body) == 401
            # wrong token also rejected
            assert self._post(f"{fe.url}/api/sources", body,
                              token="wrong") == 401
            # right token accepted
            assert self._post(f"{fe.url}/api/sources", body,
                              token="s3ss10n") == 201
            # DELETE gated too
            req = urllib.request.Request(
                f"{fe.url}/api/sources/shop/src-cart", method="DELETE")
            try:
                with urllib.request.urlopen(req, timeout=10) as r:
                    status = r.status
            except urllib.error.HTTPError as e:
                status = e.code
            assert status == 401
        finally:
            fe.shutdown()

    def test_reads_stay_open_and_sse_requires_token(self):
        import urllib.error

        fe = self._server()
        try:
            assert get_json(f"{fe.url}/healthz")["status"] == "ok"
            assert get_json(f"{fe.url}/api/sources") == []
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{fe.url}/api/events", timeout=10)
            assert ei.value.code == 401
            # EventSource cannot set headers: query token accepted
            req = urllib.request.urlopen(
                f"{fe.url}/api/events?token=s3ss10n", timeout=10)
            assert req.status == 200
            req.close()
        finally:
            fe.shutdown()

    def test_forged_jwt_rejected(self):
        """utils/auth validates claims, not signatures (entitlement
        parser) — a well-formed JWT must NOT satisfy the auth gate, or
        anyone could forge one (round-5 review, security)."""
        from tests.test_auth import make_token

        fe = self._server(token="static-secret")
        try:
            jwt = make_token()  # valid claims, no verifiable signature
            assert self._post(f"{fe.url}/api/sources",
                              {"namespace": "n", "name": "w"},
                              token=jwt) == 401
        finally:
            fe.shutdown()

    def test_open_server_requires_nothing(self):
        from odigos_tpu.api.store import Store

        fe = FrontendServer(Store(), metrics_port=None).start()
        try:
            assert self._post(f"{fe.url}/api/sources",
                              {"namespace": "n", "name": "w"}) == 201
        finally:
            fe.shutdown()
