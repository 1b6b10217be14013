"""Destination registry + configer tests (the reference's golden-test
discipline for common/config/*.go, e.g. otlphttp_test.go)."""

import pytest

from odigos_tpu.components.api import Signal
from odigos_tpu.destinations import (
    ConfigerError,
    Destination,
    SPECS,
    get_spec,
    modify_config,
    validate_destination,
)
from odigos_tpu.destinations.configers import _CONFIGERS
from odigos_tpu.pipelinegen.builder import basic_config

T, M, L = Signal.TRACES, Signal.METRICS, Signal.LOGS


def fresh():
    return basic_config()


class TestRegistry:
    def test_every_spec_has_a_configer(self):
        missing = [t for t in SPECS if t not in _CONFIGERS]
        assert not missing, f"specs without configers: {missing}"

    def test_registry_covers_reference_count(self):
        # 63 reference backends + debug/nop/mock test doubles
        assert len(SPECS) >= 63

    def test_unknown_type_raises(self):
        with pytest.raises(KeyError):
            get_spec("doesnotexist")

    def test_validate_signal_support(self):
        d = Destination(id="j", dest_type="jaeger", signals=[T, M])
        problems = validate_destination(d)
        assert any("does not support metrics" in p for p in problems)

    def test_secret_fields_flagged(self):
        spec = get_spec("datadog")
        secrets = {f.name for f in spec.fields if f.secret}
        assert "DATADOG_API_KEY" in secrets


class TestConfigers:
    def test_datadog_golden(self):
        cfg = fresh()
        d = Destination(id="dd1", dest_type="datadog", signals=[T, M, L],
                        config={"DATADOG_SITE": "datadoghq.com"})
        names = modify_config(d, cfg)
        assert sorted(names) == ["logs/datadog-dd1", "metrics/datadog-dd1",
                                 "traces/datadog-dd1"]
        exp = cfg["exporters"]["datadog/dd1"]
        assert exp["api"]["site"] == "datadoghq.com"
        # secret must be an env placeholder, never inline
        assert exp["api"]["key"] == "${DATADOG_API_KEY}"
        # traces+metrics both on -> APM stats connector bridging them
        assert "datadog/connector-dd1" in cfg["connectors"]
        assert "datadog/connector-dd1" in \
            cfg["service"]["pipelines"]["traces/datadog-dd1"]["exporters"]

    def test_datadog_missing_site_errors(self):
        d = Destination(id="dd", dest_type="datadog", signals=[T])
        with pytest.raises(ConfigerError):
            modify_config(d, fresh())

    def test_jaeger_grpc_endpoint_normalization(self):
        cfg = fresh()
        d = Destination(id="j1", dest_type="jaeger", signals=[T],
                        config={"JAEGER_URL": "jaeger.tracing:4317"})
        modify_config(d, cfg)
        exp = cfg["exporters"]["otlp/jaeger-j1"]
        assert exp["endpoint"] == "jaeger.tracing:4317"
        assert exp["tls"] == {"insecure": True}

    def test_grpc_scheme_stripped_and_port_defaulted(self):
        cfg = fresh()
        d = Destination(id="x", dest_type="otlp", signals=[T],
                        config={"OTLP_GRPC_ENDPOINT": "grpc://collector.ns"})
        modify_config(d, cfg)
        assert cfg["exporters"]["otlp/otlp-x"]["endpoint"] == "collector.ns:4317"

    def test_unsupported_signals_skipped(self):
        cfg = fresh()
        # jaeger is traces-only; metrics request is dropped silently after
        # validation (configer only creates supported pipelines)
        d = Destination(id="j2", dest_type="jaeger", signals=[T],
                        config={"JAEGER_URL": "j:4317"})
        names = modify_config(d, cfg)
        assert names == ["traces/jaeger-j2"]

    def test_no_supported_signals_errors(self):
        d = Destination(id="p1", dest_type="prometheus", signals=[T])
        with pytest.raises(ConfigerError):
            modify_config(d, fresh())

    def test_logzio_per_signal_exporters(self):
        cfg = fresh()
        d = Destination(id="lz", dest_type="logzio", signals=[T, M, L],
                        config={"LOGZIO_REGION": "eu"})
        names = modify_config(d, cfg)
        assert len(names) == 3
        assert cfg["exporters"]["logzio/tracing-lz"]["account_token"] == \
            "${LOGZIO_TRACING_TOKEN}"
        assert cfg["exporters"]["logzio/logs-lz"]["account_token"] == \
            "${LOGZIO_LOGS_TOKEN}"
        assert "prometheusremotewrite/logzio-lz" in cfg["exporters"]

    def test_kafka_brokers_split(self):
        cfg = fresh()
        d = Destination(id="k", dest_type="kafka", signals=[T],
                        config={"KAFKA_BROKERS": "b1:9092, b2:9092"})
        modify_config(d, cfg)
        assert cfg["exporters"]["kafka/k"]["brokers"] == ["b1:9092", "b2:9092"]

    def test_all_configers_run_without_crashing(self):
        """Smoke: every destination type generates config when all its
        declared fields are populated."""
        import json
        for dest_type, spec in SPECS.items():
            cfg = fresh()
            values = {f.name: "test-value" for f in spec.fields}
            # type-specific field values that must parse
            values.update({
                "DYNAMIC_CONFIGURATION_DATA": json.dumps({"endpoint": "x"}),
                "MOCK_REJECT_FRACTION": "0.5",
                "MOCK_RESPONSE_DURATION": "1",
                "KAFKA_BROKERS": "b:9092",
            })
            d = Destination(id=f"t-{dest_type}", dest_type=dest_type,
                            signals=sorted(spec.signals, key=lambda s: s.value),
                            config={k: v for k, v in values.items()
                                    if any(f.name == k for f in spec.fields)})
            names = modify_config(d, cfg)
            assert names, f"{dest_type}: no pipelines created"
            for n in names:
                pipe = cfg["service"]["pipelines"][n]
                assert pipe["exporters"], f"{dest_type}: pipeline {n} has no exporters"
                for e in pipe["exporters"]:
                    assert e in cfg["exporters"] or e in cfg["connectors"], \
                        f"{dest_type}: pipeline {n} references undeclared {e}"

    def test_no_secret_value_ever_inlined(self):
        """Secrets appear only as ${VAR} placeholders in generated config."""
        import json
        secret_value = "sUpErSeCrEt-12345"
        for dest_type, spec in SPECS.items():
            secret_names = [f.name for f in spec.fields if f.secret]
            if not secret_names:
                continue
            cfg = fresh()
            values = {f.name: (secret_value if f.secret else "v")
                      for f in spec.fields}
            values.setdefault("KAFKA_BROKERS", "b:9092")
            if dest_type == "dynamic":
                continue  # dynamic passes raw config through by design
            d = Destination(id="s", dest_type=dest_type,
                            signals=sorted(spec.signals, key=lambda s: s.value),
                            config=values)
            try:
                modify_config(d, cfg)
            except ConfigerError:
                continue
            assert secret_value not in json.dumps(cfg), \
                f"{dest_type} inlined a secret value into generated config"


class TestExtensionsWiring:
    def test_grafana_tempo_authenticator_enabled(self):
        cfg = fresh()
        d = Destination(id="g1", dest_type="grafanacloudtempo", signals=[T],
                        config={"GRAFANA_CLOUD_TEMPO_ENDPOINT": "tempo.grafana.net:443",
                                "GRAFANA_CLOUD_TEMPO_USERNAME": "u"})
        modify_config(d, cfg)
        auth = "basicauth/grafana-tempo-g1"
        assert auth in cfg["extensions"]
        assert auth in cfg["service"]["extensions"]

    def test_grafana_prometheus_authenticator_defined_and_enabled(self):
        cfg = fresh()
        d = Destination(id="g2", dest_type="grafanacloudprometheus", signals=[M],
                        config={"GRAFANA_CLOUD_PROMETHEUS_RW_ENDPOINT": "https://prom",
                                "GRAFANA_CLOUD_PROMETHEUS_USERNAME": "u"})
        modify_config(d, cfg)
        auth = "basicauth/grafana-prom-g2"
        exp = cfg["exporters"]["prometheusremotewrite/grafana-g2"]
        assert exp["auth"]["authenticator"] == auth
        assert auth in cfg["extensions"]
        assert auth in cfg["service"]["extensions"]

    def test_logzio_regional_metrics_listener(self):
        cfg = fresh()
        d = Destination(id="lz2", dest_type="logzio", signals=[M],
                        config={"LOGZIO_REGION": "eu"})
        modify_config(d, cfg)
        assert cfg["exporters"]["prometheusremotewrite/logzio-lz2"][
            "endpoint"] == "https://listener-eu.logz.io:8053"


class TestBlobExporter:
    """Generic blob-writer behind the azureblob/gcs entries (round-2 review
    item 10; reference: collector/exporters/azureblobstorageexporter,
    common/config/gcs.go)."""

    def test_azureblob_writes_objects_via_file_endpoint(self, tmp_path):
        from odigos_tpu.e2e import E2EEnvironment
        from odigos_tpu.pdata import synthesize_traces

        with E2EEnvironment(nodes=1) as env:
            env.add_destination(Destination(
                id="blob1", dest_type="azureblob", signals=[Signal.TRACES],
                config={"AZURE_BLOB_ACCOUNT_NAME": "acct",
                        "AZURE_BLOB_CONTAINER_NAME": "spans",
                        "AZURE_BLOB_ENDPOINT": f"file://{tmp_path}"}))
            assert env.send_traces_wire(synthesize_traces(10, seed=0))
            import json
            import time

            deadline = time.time() + 10
            objects = []
            while time.time() < deadline and not objects:
                objects = list((tmp_path / "spans" / "traces").glob("*.json")) \
                    if (tmp_path / "spans" / "traces").exists() else []
                time.sleep(0.05)
            assert objects, "no blob objects written"
            doc = json.loads(objects[0].read_text())
            assert doc["resourceSpans"], "empty blob payload"

    def test_gcs_defaults_bucket(self, tmp_path):
        from odigos_tpu.components.api import ComponentKind, registry

        factory = registry.get(ComponentKind.EXPORTER, "googlecloudstorage")
        exp = factory.create("googlecloudstorage/x", {
            "endpoint": f"file://{tmp_path}"})
        exp.start()
        from odigos_tpu.pdata import synthesize_traces

        exp.export(synthesize_traces(3, seed=1))
        exp.shutdown()
        assert list((tmp_path / "odigos-otlp" / "traces").glob("*.json"))

    def test_no_backend_fails_loudly(self):
        from odigos_tpu.components.api import ComponentKind, registry

        factory = registry.get(ComponentKind.EXPORTER, "azureblobstorage")
        exp = factory.create("azureblobstorage/x", {"container": "c"})
        with pytest.raises(ValueError, match="file://"):
            exp.start()


def test_blob_uploader_rejects_path_escape(tmp_path):
    from odigos_tpu.components.exporters.blob import LocalDirUploader

    up = LocalDirUploader(str(tmp_path / "root"))
    with pytest.raises(ValueError, match="escapes"):
        up.upload("../../etc/evil/x.json", b"{}")


def _log_batch(n=3):
    from odigos_tpu.pdata.logs import LogBatchBuilder

    b = LogBatchBuilder()
    ri = b.add_resource({"service.name": "websvc"})
    for i in range(n):
        b.add_record(body=f"line {i}", time_unix_nano=1000 + i,
                     resource_index=ri)
    return b.build()


def _plausible_value(field_name: str) -> str:
    """A field value that parses for its configer (URLs for endpoint
    fields, numbers for numeric ones, JSON for raw-config passthrough)."""
    n = field_name.upper()
    if n == "DYNAMIC_CONFIGURATION_DATA":
        return '{"endpoint": "https://example.invalid"}'
    if n == "DYNAMIC_DESTINATION_TYPE":
        return "otlphttp"
    if n == "MOCK_REJECT_FRACTION":
        return "0.0"
    if n == "MOCK_RESPONSE_DURATION":
        return "0"
    if "URL" in n or "ENDPOINT" in n or "HOST" in n or "LISTENER" in n:
        return "https://example.invalid:4318"
    if "PORT" in n:
        return "4317"
    if "BROKERS" in n:
        return "broker-1:9092"
    return "v"


class TestEveryDestinationTypeBuilds:
    """The full registry/configer/factory contract: for EVERY one of the 63
    destination types, the generated exporter entries must resolve to
    registered factories that build and start (round-3 review: adding a real
    backend produced configs the graph builder rejected — the reference
    compiles one upstream exporter per backend, builder-config.yaml)."""

    def test_all_destination_types_resolve_build_and_start(self, tmp_path):
        from odigos_tpu.components.api import ComponentKind, registry
        from odigos_tpu.destinations.configers import modify_config
        from odigos_tpu.destinations.registry import SPECS

        failures = []
        for spec in SPECS.values():
            dest = Destination(
                id="x", dest_type=spec.dest_type,
                signals=list(spec.signals),
                config={f.name: _plausible_value(f.name)
                        for f in spec.fields})
            cfg = {"exporters": {}, "processors": {}, "connectors": {},
                   "extensions": {}, "service": {"pipelines": {}}}
            try:
                modify_config(dest, cfg)
            except Exception as e:
                failures.append(f"{spec.dest_type}: configer raised {e}")
                continue
            for cid in cfg["exporters"]:
                if not registry.has(ComponentKind.EXPORTER, cid):
                    failures.append(
                        f"{spec.dest_type}: no exporter factory for {cid}")
                    continue
                try:
                    exp = registry.get(ComponentKind.EXPORTER, cid).build(
                        cid, cfg["exporters"][cid])
                    exp.start()
                    exp.shutdown()
                except Exception as e:
                    failures.append(
                        f"{spec.dest_type}: {cid} failed to start: {e}")
            for cid in cfg["connectors"]:
                if not registry.has(ComponentKind.CONNECTOR, cid):
                    failures.append(
                        f"{spec.dest_type}: no connector factory for {cid}")
        assert not failures, "\n".join(failures)


class TestVendorExporters:
    """Generic vendor exporter family (components/exporters/vendor.py) —
    the upstream-exporter-set role over real sockets."""

    def _export(self, vendor_type, vendor_cfg, store, batch=None):
        from odigos_tpu.components.api import ComponentKind, registry
        from odigos_tpu.pdata import synthesize_traces

        exp = registry.get(ComponentKind.EXPORTER, vendor_type).build(
            f"{vendor_type}/t",
            {**vendor_cfg, "endpoint_override": store.url,
             "retry_backoff_s": 0.01})
        exp.start()
        try:
            exp.export(batch if batch is not None
                       else synthesize_traces(5, seed=1))
        finally:
            exp.shutdown()
        return exp

    def test_datadog_delivers_with_vendor_auth_header(self, tmp_path):
        import json as _json

        from odigos_tpu.e2e.blobstore import BlobStoreServer

        store = BlobStoreServer(str(tmp_path)).start()
        store.require_header = ("DD-API-KEY", "k3y")
        try:
            self._export("datadog",
                         {"api": {"key": "k3y", "site": "datadoghq.com"}},
                         store)
            assert store.put_count == 1 and store.auth_failures == 0
            doc = _json.loads(store.bodies[0])
            assert doc["resourceSpans"]
        finally:
            store.stop()

    def test_wrong_api_key_is_terminal_401(self, tmp_path):
        from odigos_tpu.e2e.blobstore import BlobStoreServer

        store = BlobStoreServer(str(tmp_path)).start()
        store.require_header = ("DD-API-KEY", "right")
        try:
            with pytest.raises(PermissionError, match="401"):
                self._export("datadog", {"api": {"key": "wrong"}}, store)
            assert store.put_count == 1, "4xx must not be retried"
        finally:
            store.stop()

    def test_prometheusremotewrite_retries_5xx(self, tmp_path):
        from odigos_tpu.e2e.blobstore import BlobStoreServer

        store = BlobStoreServer(str(tmp_path)).start()
        try:
            store.fail_next(2)
            self._export("prometheusremotewrite",
                         {"headers": {"Authorization": "Bearer t"}}, store)
            assert store.put_count == 3  # 2 faults + success
        finally:
            store.stop()

    def test_non_http_transport_runs_degraded(self):
        """kafka is the one remaining non-HTTP transport (round 5 gave
        the AWS/Azure/GCP family real wire protocols, wireformats.py):
        it must boot, drop visibly, and report unhealthy."""
        from odigos_tpu.components.api import ComponentKind, registry
        from odigos_tpu.pdata import synthesize_traces
        from odigos_tpu.utils.telemetry import meter

        exp = registry.get(ComponentKind.EXPORTER, "kafka").build(
            "kafka/x", {"brokers": ["b:9092"]})
        exp.start()  # must not raise: collector boots with SDK backends
        before = meter.counter(
            "odigos_vendor_dropped_total{exporter=kafka/x}")
        exp.export(synthesize_traces(3, seed=2))  # counted drop, no error
        after = meter.counter(
            "odigos_vendor_dropped_total{exporter=kafka/x}")
        assert after - before > 0
        assert not exp.healthy(), "degraded exporter must report unhealthy"
        exp.shutdown()

    def test_datadog_connector_emits_apm_stats(self):
        from odigos_tpu.components.api import ComponentKind, registry
        from odigos_tpu.pdata import synthesize_traces

        conn = registry.get(ComponentKind.CONNECTOR, "datadog").build(
            "datadog/connector-x", {})
        got = []
        conn.set_outputs({"metrics/x": type(
            "S", (), {"consume": staticmethod(got.append)})()})
        conn.start()
        conn.consume(synthesize_traces(20, seed=3))
        conn.shutdown()
        assert got and "datadog.trace.hits" in got[0].metric_names()


class TestBlobLogsDispatch:
    """Round-3 advisor medium: the exporter is registered for T+L but only
    marshalled SpanBatch. Logs now land under ``{container}/logs/`` via
    LogBatch.iter_records() (reference: azureblobstorageexporter's separate
    logsDataWriter path, exporter.go)."""

    def test_log_batch_written_under_logs_prefix(self, tmp_path):
        import json

        from odigos_tpu.components.api import ComponentKind, registry

        factory = registry.get(ComponentKind.EXPORTER, "azureblobstorage")
        exp = factory.create("azureblobstorage/x", {
            "container": "c", "endpoint": f"file://{tmp_path}"})
        exp.start()
        exp.export(_log_batch(3))
        exp.shutdown()
        objects = list((tmp_path / "c" / "logs").glob("*.json"))
        assert objects, "no log objects written"
        doc = json.loads(objects[0].read_text())
        assert len(doc["resourceLogs"]) == 3
        assert doc["resourceLogs"][0]["body"] == "line 0"
        assert doc["resourceLogs"][0]["resource"] == {"service.name": "websvc"}

    def test_logs_and_traces_share_seq_but_not_prefix(self, tmp_path):
        from odigos_tpu.components.api import ComponentKind, registry
        from odigos_tpu.pdata import synthesize_traces

        factory = registry.get(ComponentKind.EXPORTER, "googlecloudstorage")
        exp = factory.create("googlecloudstorage/x", {
            "endpoint": f"file://{tmp_path}"})
        exp.start()
        exp.export(synthesize_traces(2, seed=0))
        exp.export(_log_batch(1))
        exp.shutdown()
        assert list((tmp_path / "odigos-otlp" / "traces").glob("*.json"))
        assert list((tmp_path / "odigos-otlp" / "logs").glob("*.json"))


class TestBlobHttpUploader:
    """HTTP PUT path against a real socket (round-3 review item 5; reference:
    collector/exporters/azureblobstorageexporter over the Azure SDK's HTTPS
    transport — here the exporter speaks the PUT contract directly)."""

    def _exporter(self, url, token="", **over):
        from odigos_tpu.components.api import ComponentKind, registry

        factory = registry.get(ComponentKind.EXPORTER, "azureblobstorage")
        cfg = {"container": "c", "endpoint": url, "auth_token": token,
               "retry_backoff_s": 0.01, **over}
        exp = factory.create("azureblobstorage/http", cfg)
        exp.start()
        return exp

    def test_upload_roundtrip_with_auth(self, tmp_path):
        import json

        from odigos_tpu.e2e.blobstore import BlobStoreServer
        from odigos_tpu.pdata import synthesize_traces

        store = BlobStoreServer(str(tmp_path), token="s3cret").start()
        try:
            exp = self._exporter(store.url, token="s3cret")
            exp.export(synthesize_traces(5, seed=2))
            exp.export(_log_batch(2))
            exp.shutdown()
        finally:
            store.stop()
        traces = list((tmp_path / "c" / "traces").glob("*.json"))
        logs = list((tmp_path / "c" / "logs").glob("*.json"))
        assert traces and logs
        assert json.loads(traces[0].read_text())["resourceSpans"]

    def test_retries_through_transient_5xx(self, tmp_path):
        from odigos_tpu.e2e.blobstore import BlobStoreServer
        from odigos_tpu.pdata import synthesize_traces

        store = BlobStoreServer(str(tmp_path)).start()
        try:
            store.fail_next(2)  # two 503s, then success — within budget
            exp = self._exporter(store.url)
            exp.export(synthesize_traces(3, seed=3))
            exp.shutdown()
            assert store.put_count == 3  # 2 faults + 1 success
        finally:
            store.stop()
        assert list((tmp_path / "c" / "traces").glob("*.json"))

    def test_retry_budget_exhaustion_raises(self, tmp_path):
        from odigos_tpu.e2e.blobstore import BlobStoreServer
        from odigos_tpu.pdata import synthesize_traces

        store = BlobStoreServer(str(tmp_path)).start()
        try:
            store.fail_next(100)
            exp = self._exporter(store.url, max_retries=2)
            with pytest.raises(ConnectionError, match="after 3 attempts"):
                exp.export(synthesize_traces(1, seed=4))
            exp.shutdown()
        finally:
            store.stop()

    def test_auth_rejection_is_terminal_not_retried(self, tmp_path):
        from odigos_tpu.e2e.blobstore import BlobStoreServer
        from odigos_tpu.pdata import synthesize_traces

        store = BlobStoreServer(str(tmp_path), token="right").start()
        try:
            exp = self._exporter(store.url, token="wrong")
            with pytest.raises(PermissionError, match="401"):
                exp.export(synthesize_traces(1, seed=5))
            exp.shutdown()
            assert store.put_count == 1, "4xx must not be retried"
            assert store.auth_failures == 1
        finally:
            store.stop()


class TestAuthenticatorExtension:
    """basicauth extension resolution (the grafana-cloud configers emit
    auth: {authenticator: basicauth/...}): the graph builder inlines the
    extension into the exporter, the vendor exporter sends the Basic
    header, and dangling references fail validation like the collector's
    startup resolution."""

    def test_grafana_prom_delivers_with_basic_auth(self, tmp_path,
                                                   monkeypatch):
        import base64

        from odigos_tpu.destinations.configers import modify_config
        from odigos_tpu.e2e.blobstore import BlobStoreServer
        from odigos_tpu.pdata import synthesize_traces
        from odigos_tpu.pipeline.graph import build_graph

        monkeypatch.setenv("GRAFANA_CLOUD_PROMETHEUS_PASSWORD", "pw1")
        store = BlobStoreServer(str(tmp_path)).start()
        expected = base64.b64encode(b"user1:pw1").decode()
        store.require_header = ("Authorization", f"Basic {expected}")
        try:
            dest = Destination(
                id="g1", dest_type="grafanacloudprometheus",
                signals=[M],
                config={"GRAFANA_CLOUD_PROMETHEUS_RW_ENDPOINT":
                        "https://prom.example.invalid/api/prom/push",
                        "GRAFANA_CLOUD_PROMETHEUS_USERNAME": "user1"})
            cfg = {"receivers": {"synthetic": {"traces_per_batch": 1,
                                               "n_batches": 1}},
                   "exporters": {}, "processors": {}, "connectors": {},
                   "extensions": {},
                   "service": {"pipelines": {}}}
            modify_config(dest, cfg)
            (eid,) = [e for e in cfg["exporters"]
                      if e.startswith("prometheusremotewrite/")]
            cfg["exporters"][eid]["endpoint_override"] = store.url
            cfg["exporters"][eid]["retry_backoff_s"] = 0.01
            # the configer's pipelines get receivers from pipelinegen's
            # forward connectors; this test wires its own intake instead
            cfg["service"]["pipelines"] = {"metrics/g": {
                "receivers": ["synthetic"], "processors": [],
                "exporters": [eid]}}
            graph = build_graph(cfg)
            exp = graph.exporters[eid]
            exp.start()
            exp.export(synthesize_traces(3, seed=1))
            exp.shutdown()
            assert store.put_count == 1 and store.auth_failures == 0
        finally:
            store.stop()

    def test_dangling_authenticator_fails_validation(self):
        from odigos_tpu.pipeline.graph import validate_config

        cfg = {"receivers": {"synthetic": {}},
               "processors": {}, "connectors": {}, "extensions": {},
               "exporters": {"prometheusremotewrite/x": {
                   "endpoint": "https://x",
                   "auth": {"authenticator": "basicauth/missing"}}},
               "service": {"pipelines": {"metrics/m": {
                   "receivers": ["synthetic"],
                   "exporters": ["prometheusremotewrite/x"]}}}}
        problems = validate_config(cfg)
        assert any("authenticator" in p for p in problems), problems

    def test_defined_but_not_enabled_fails_validation(self):
        from odigos_tpu.pipeline.graph import validate_config

        cfg = {"receivers": {"synthetic": {}},
               "processors": {}, "connectors": {},
               "extensions": {"basicauth/a": {"client_auth": {
                   "username": "u", "password": "p"}}},
               "exporters": {"prometheusremotewrite/x": {
                   "endpoint": "https://x",
                   "auth": {"authenticator": "basicauth/a"}}},
               "service": {"pipelines": {"metrics/m": {
                   "receivers": ["synthetic"],
                   "exporters": ["prometheusremotewrite/x"]}},
                "extensions": []}}
        problems = validate_config(cfg)
        assert any("service.extensions" in p for p in problems), problems

    def test_bearertokenauth_extension_resolved(self, tmp_path,
                                                monkeypatch):
        """bearertokenauth (upstream bearertokenauthextension): the
        resolved token becomes the Bearer Authorization header."""
        from odigos_tpu.e2e.blobstore import BlobStoreServer
        from odigos_tpu.pdata import synthesize_traces
        from odigos_tpu.pipeline.graph import build_graph

        monkeypatch.setenv("MY_TOKEN", "t0k3n")
        store = BlobStoreServer(str(tmp_path)).start()
        store.require_header = ("Authorization", "Bearer t0k3n")
        try:
            cfg = {"receivers": {"synthetic": {"traces_per_batch": 1,
                                               "n_batches": 1}},
                   "processors": {}, "connectors": {},
                   "extensions": {"bearertokenauth/x": {
                       "token": "${MY_TOKEN}"}},
                   "exporters": {"otlphttp/x": {
                       "endpoint": store.url,
                       "retry_backoff_s": 0.01,
                       "auth": {"authenticator": "bearertokenauth/x"}}},
                   "service": {"pipelines": {"traces/t": {
                       "receivers": ["synthetic"],
                       "exporters": ["otlphttp/x"]}},
                    "extensions": ["bearertokenauth/x"]}}
            graph = build_graph(cfg)
            exp = graph.exporters["otlphttp/x"]
            exp.start()
            exp.export(synthesize_traces(2, seed=9))
            exp.shutdown()
            assert store.put_count == 1 and store.auth_failures == 0
        finally:
            store.stop()
