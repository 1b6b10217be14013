"""Dedicated vendor wire protocols (round-4 review items 4-5; reference
compiles one exporter per backend — splunkhecexporter, influxdbexporter,
opensearchexporter, awsxray/awsemf/awss3, azuremonitor,
collector/builder-config.yaml:19-60): byte-level protocol-shape tests
against a local mock, auth asserted, oversized batches split."""

import gzip
import json

import pytest

from odigos_tpu.components.api import ComponentKind, registry
from odigos_tpu.pdata import synthesize_traces
from odigos_tpu.pdata.logs import LogBatchBuilder
from odigos_tpu.pdata.metrics import MetricBatchBuilder, MetricType


def _metrics():
    b = MetricBatchBuilder()
    r = b.add_resource({"service.name": "cart"})
    b.add_point(name="http.requests", value=41.0, resource_index=r,
                metric_type=MetricType.SUM,
                time_unix_nano=1_700_000_000_000_000_000,
                attrs={"code": "200"})
    return b.build()


def _logs():
    b = LogBatchBuilder()
    r = b.add_resource({"service.name": "cart"})
    b.add_record(body="hello", resource_index=r,
                 time_unix_nano=1_700_000_000_000_000_000)
    return b.build()


def hget(req, name):
    """Case-insensitive header lookup (urllib title-cases on the wire)."""
    for k, v in req["headers"].items():
        if k.lower() == name.lower():
            return v
    return None


def _export(vendor_type, cfg, store, batch=None):
    exp = registry.get(ComponentKind.EXPORTER, vendor_type).build(
        f"{vendor_type}/t",
        {**cfg, "endpoint_override": store.url, "retry_backoff_s": 0.01})
    exp.start()
    try:
        exp.export(batch if batch is not None
                   else synthesize_traces(5, seed=1))
    finally:
        exp.shutdown()


@pytest.fixture()
def store(tmp_path):
    from odigos_tpu.e2e.blobstore import BlobStoreServer

    s = BlobStoreServer(str(tmp_path)).start()
    yield s
    s.stop()


class TestSplunkHec:
    def test_hec_event_stream_shape_and_auth(self, store):
        _export("splunkhec", {"token": "tok-1", "source": "odigos"},
                store)
        req = store.requests[0]
        assert req["path"] == "/services/collector"
        assert hget(req, "Authorization") == "Splunk tok-1"
        # concatenated JSON objects, not an array
        dec = json.JSONDecoder()
        text = req["body"].decode()
        events, i = [], 0
        while i < len(text):
            obj, i = dec.raw_decode(text, i)
            events.append(obj)
        assert len(events) == 33  # 5 traces = 33 spans
        assert all(e["sourcetype"] == "otel" and e["source"] == "odigos"
                   and "event" in e and e["time"] > 0 for e in events)


class TestInfluxLine:
    def test_line_protocol_metrics(self, store):
        _export("influxdb", {"org": "o1", "bucket": "b1",
                             "token": "sekret"}, store, _metrics())
        req = store.requests[0]
        assert req["path"] == "/api/v2/write?org=o1&bucket=b1&precision=ns"
        assert hget(req, "Authorization") == "Token sekret"
        line = req["body"].decode()
        # measurement,tags fields timestamp
        assert line.startswith("http.requests,")
        assert "code=200" in line and "service=cart" in line
        assert " value=41.0 1700000000000000000" in line

    def test_line_protocol_escaping(self, store):
        b = MetricBatchBuilder()
        r = b.add_resource({"service.name": "a b"})
        b.add_point(name="m x", value=1.0, resource_index=r,
                    time_unix_nano=1, attrs={"k,1": "v=2"})
        _export("influxdb", {"org": "o", "bucket": "b"}, store, b.build())
        line = store.requests[0]["body"].decode()
        assert line.startswith("m\\ x,")          # measurement space
        assert "k\\,1=v\\=2" in line               # tag key/value escapes

    def test_spans_use_otel_schema_measurement(self, store):
        _export("influxdb", {"org": "o", "bucket": "b"}, store)
        body = store.requests[0]["body"].decode()
        assert all(line.startswith("spans,")
                   for line in body.splitlines())


class TestBulkNdjson:
    def test_opensearch_bulk_pairs(self, store):
        _export("opensearch", {"logs_index": "my-logs"}, store, _logs())
        req = store.requests[0]
        assert req["path"] == "/_bulk"
        assert hget(req, "Content-Type") == "application/x-ndjson"
        lines = req["body"].decode().strip().splitlines()
        assert len(lines) == 2  # action + document per record
        assert json.loads(lines[0]) == {"create": {"_index": "my-logs"}}
        assert json.loads(lines[1])["body"] == "hello"

    def test_elasticsearch_uses_bulk_too_with_basic_auth(self, store):
        store.require_header = ("Authorization", "Basic dTpw")  # u:p
        _export("elasticsearch",
                {"user": "u", "password": "p", "endpoints": ["ignored"]},
                store)
        assert store.auth_failures == 0
        assert store.requests[0]["path"] == "/_bulk"


class TestAzureMonitor:
    def test_track_envelopes_with_ikey(self, store):
        cs = ("InstrumentationKey=ik-123;"
              f"IngestionEndpoint={store.url}")
        # no endpoint_override: the URL must derive from the connection
        # string itself
        exp = registry.get(ComponentKind.EXPORTER, "azuremonitor").build(
            "azuremonitor/t", {"connection_string": cs,
                               "retry_backoff_s": 0.01})
        exp.start()
        try:
            assert exp.healthy(), "connection string must derive a URL"
            exp.export(_logs())
        finally:
            exp.shutdown()
        req = store.requests[0]
        assert req["path"] == "/v2.1/track"
        envs = json.loads(req["body"])
        assert envs[0]["iKey"] == "ik-123"
        assert envs[0]["data"]["baseType"] == "MessageData"
        assert envs[0]["data"]["baseData"]["message"] == "hello"


class TestAwsFamily:
    def test_s3_put_partition_layout_and_sigv4(self, store, monkeypatch):
        monkeypatch.setenv("AWS_ACCESS_KEY_ID", "AKIA123")
        monkeypatch.setenv("AWS_SECRET_ACCESS_KEY", "s3cr3t")
        _export("awss3", {"s3uploader": {
            "region": "eu-west-1", "s3_bucket": "b",
            "s3_prefix": "traces", "s3_partition": "minute"}}, store)
        req = store.requests[0]
        assert req["method"] == "PUT"
        assert req["path"].startswith("/traces/year=")
        assert "/minute=" in req["path"]
        assert req["path"].endswith(".json.gz")
        auth = hget(req, "Authorization")
        assert auth.startswith("AWS4-HMAC-SHA256 Credential=AKIA123/")
        assert "/eu-west-1/s3/aws4_request" in auth
        doc = json.loads(gzip.decompress(req["body"]))
        assert doc["resourceSpans"]

    def test_s3_unsigned_without_creds(self, store, monkeypatch):
        monkeypatch.delenv("AWS_ACCESS_KEY_ID", raising=False)
        monkeypatch.delenv("AWS_SECRET_ACCESS_KEY", raising=False)
        _export("awss3", {"s3uploader": {"s3_bucket": "b"}}, store)
        assert hget(store.requests[0], "Authorization") is None

    def test_xray_put_trace_segments(self, store, monkeypatch):
        monkeypatch.setenv("AWS_ACCESS_KEY_ID", "AKIA123")
        monkeypatch.setenv("AWS_SECRET_ACCESS_KEY", "s3cr3t")
        _export("awsxray", {"region": "us-west-2"}, store)
        req = store.requests[0]
        assert req["path"] == "/TraceSegments"
        docs = json.loads(req["body"])["TraceSegmentDocuments"]
        assert len(docs) == 33
        seg = json.loads(docs[0])
        assert seg["trace_id"].startswith("1-")
        assert "/us-west-2/xray/aws4_request" in hget(req, "Authorization")

    def test_cloudwatch_logs_jsonrpc_target(self, store):
        _export("awscloudwatchlogs",
                {"log_group_name": "g", "log_stream_name": "s",
                 "region": "us-east-1"}, store, _logs())
        req = store.requests[0]
        assert hget(req, "X-Amz-Target") == "Logs_20140328.PutLogEvents"
        assert hget(req, "Content-Type") == "application/x-amz-json-1.1"
        payload = json.loads(req["body"])
        assert payload["logGroupName"] == "g"
        assert payload["logEvents"][0]["timestamp"] > 0

    def test_emf_embedded_metric_format(self, store):
        _export("awsemf", {"namespace": "odigos", "region": "us-east-1"},
                store, _metrics())
        payload = json.loads(store.requests[0]["body"])
        ev = json.loads(payload["logEvents"][0]["message"])
        assert ev["_aws"]["CloudWatchMetrics"][0]["Namespace"] == "odigos"
        assert ev["http.requests"] == 41.0


class TestGoogleCloud:
    def test_otlp_http_pathed_delivery(self, store):
        _export("googlecloud", {"project": "p1"}, store, _metrics())
        req = store.requests[0]
        assert req["path"] == "/v1/metrics"
        assert hget(req, "x-goog-user-project") == "p1"
        assert json.loads(req["body"])["resourceMetrics"]


class TestBodyCap:
    def test_oversized_batch_splits_into_in_limit_requests(self, store):
        cap = 4000
        _export("splunkhec", {"token": "t", "max_body_bytes": cap},
                store, synthesize_traces(60, seed=3))
        assert len(store.requests) > 1, "oversized batch never split"
        for req in store.requests:
            assert len(req["body"]) <= cap, \
                f"request body {len(req['body'])} exceeds cap {cap}"

    def test_small_batch_single_request(self, store):
        _export("splunkhec", {"token": "t"}, store,
                synthesize_traces(3, seed=4))
        assert len(store.requests) == 1


def test_only_non_http_transports_remain_on_the_drop_path():
    """round-4 review item 5 'done' bar, extended by the round-5 vendor
    additions: odigos_vendor_dropped_total moves only for the genuinely
    non-HTTP transports (kafka/pulsar brokers, cassandra CQL, ADX's
    OAuth'd Kusto ingest)."""
    from odigos_tpu.components.exporters.vendor import EXTRACTORS
    from odigos_tpu.utils.telemetry import meter

    droppers = []
    for vt in sorted(EXTRACTORS):
        cfg = {
            "awss3": {"s3uploader": {"s3_bucket": "b"}},
            "azuremonitor": {"connection_string":
                             "InstrumentationKey=i;"
                             "IngestionEndpoint=https://x.example"},
            "coralogix": {"domain": "coralogix.com"},
            "elasticsearch": {"endpoints": ["https://es.example"]},
            "otlphttp": {"endpoint": "https://x.example"},
            "prometheusremotewrite": {"endpoint": "https://x.example"},
            "loki": {"endpoint": "https://x.example"},
            "clickhouse": {"endpoint": "https://x.example"},
            "signalfx": {"endpoint": "https://x.example"},
            "sapm": {"endpoint": "https://x.example"},
            "splunkhec": {"endpoint": "https://x.example"},
            "influxdb": {"endpoint": "https://x.example"},
            "opensearch": {"endpoints": ["https://x.example"]},
            "googlemanagedprometheus": {"endpoint": "https://x.example"},
            "sumologic": {"endpoint": "https://x.example"},
            "zipkin": {"endpoint": "https://x.example"},
            "sentry": {"dsn": "https://k@sentry.example/42"},
            "mezmo": {"ingest_key": "k"},
            "logicmonitor": {"endpoint": "https://x.example"},
            "dataset": {"dataset_url": "https://x.example",
                        "api_key": "k"},
            "tencentcloudlogservice": {"region": "ap-guangzhou"},
        }.get(vt, {})
        exp = registry.get(ComponentKind.EXPORTER, vt).build(
            f"{vt}/dropcheck", {**cfg, "max_retries": 0,
                                "retry_backoff_s": 0.0,
                                "timeout_s": 0.5})
        exp.start()
        before = meter.counter(
            f"odigos_vendor_dropped_total{{exporter={vt}/dropcheck}}")
        try:
            exp.export(synthesize_traces(1, seed=9))
        except Exception:
            pass  # unreachable endpoints raise after retries — fine
        after = meter.counter(
            f"odigos_vendor_dropped_total{{exporter={vt}/dropcheck}}")
        if after > before:
            droppers.append(vt)
        exp.shutdown()
    assert droppers == ["azuredataexplorer", "cassandra", "kafka",
                        "pulsar"], droppers


def test_s3_keys_unique_across_split_halves(tmp_path, monkeypatch):
    """Round-5 review: ms-granularity keys collide when split halves
    marshal in the same millisecond — the second PUT would overwrite
    the first."""
    from odigos_tpu.e2e.blobstore import BlobStoreServer

    monkeypatch.delenv("AWS_ACCESS_KEY_ID", raising=False)
    store = BlobStoreServer(str(tmp_path)).start()
    try:
        _export("awss3", {"s3uploader": {"s3_bucket": "b"},
                          "max_body_bytes": 2000},
                store, synthesize_traces(40, seed=5))
        paths = [r["path"] for r in store.requests]
        assert len(paths) > 1
        assert len(set(paths)) == len(paths), f"colliding keys: {paths}"
    finally:
        store.stop()


def test_azure_debug_maps_to_verbose(tmp_path):
    from odigos_tpu.components.exporters.wireformats import (
        marshal_azure_track)
    from odigos_tpu.pdata.logs import LogBatchBuilder, Severity

    b = LogBatchBuilder()
    r = b.add_resource({"service.name": "s"})
    b.add_record(body="dbg", severity=Severity.DEBUG, resource_index=r,
                 time_unix_nano=1)
    reqs = marshal_azure_track(b.build(), {
        "connection_string": "InstrumentationKey=i"})
    env = json.loads(reqs[0].body)[0]
    assert env["data"]["baseData"]["severityLevel"] == 0  # Verbose


class TestRound5VendorAdditions:
    def test_zipkin_v2_roundtrips_through_our_receiver(self, store):
        """The zipkin exporter's output must be valid input for our own
        zipkin receiver — the inverse-mapping contract."""
        _export("zipkin", {"endpoint": "ignored"}, store,
                synthesize_traces(3, seed=6))
        req = store.requests[0]
        assert req["path"] == "/api/v2/spans"
        docs = json.loads(req["body"])
        assert docs and all(d["localEndpoint"]["serviceName"]
                            for d in docs)
        from odigos_tpu.components.receivers.zipkin import translate_spans

        batch = translate_spans(docs)
        assert len(batch) == len(docs)

    def test_sumologic_logs_with_source_headers(self, store):
        _export("sumologic", {"endpoint": "ignored",
                              "source_category": "prod/x"},
                store, _logs())
        req = store.requests[0]
        assert hget(req, "X-Sumo-Category") == "prod/x"
        assert req["body"] == b"hello"

    def test_sentry_envelope_shape(self, store):
        _export("sentry", {"dsn": "https://pubkey@o0.ingest.sentry.io/42",
                           "endpoint_override": store.url},
                store, synthesize_traces(1, seed=7))
        req = store.requests[0]
        assert req["path"] == "/api/42/envelope/"
        assert "sentry_key=pubkey" in hget(req, "X-Sentry-Auth")
        lines = req["body"].decode().splitlines()
        assert json.loads(lines[0])["dsn"].startswith("https://pubkey@")
        item_header = json.loads(lines[1])
        assert item_header["type"] == "transaction"
        assert json.loads(lines[2])["transaction"]

    def test_honeycomb_marker(self, store):
        _export("honeycombmarker",
                {"api_key": "hck", "dataset": "prod"}, store, _logs())
        req = store.requests[0]
        assert req["path"] == "/1/markers/prod"
        assert hget(req, "X-Honeycomb-Team") == "hck"
        assert json.loads(req["body"])["message"] == "hello"

    def test_pubsub_publish_base64(self, store):
        import base64

        _export("googlecloudpubsub",
                {"topic": "projects/p/topics/t"}, store, _logs())
        req = store.requests[0]
        assert req["path"] == "/v1/projects/p/topics/t:publish"
        msg = json.loads(req["body"])["messages"][0]
        inner = json.loads(base64.b64decode(msg["data"]))
        assert inner["resourceLogs"]


class TestSyslogExporter:
    def test_rfc5424_frames_over_real_tcp(self):
        import socket
        import threading

        received = []
        srv = socket.socket()
        srv.bind(("127.0.0.1", 0))
        srv.listen(1)
        port = srv.getsockname()[1]

        def accept():
            conn, _ = srv.accept()
            data = b""
            while b"\n" not in data:
                chunk = conn.recv(4096)
                if not chunk:
                    break
                data += chunk
            received.append(data)
            conn.close()

        t = threading.Thread(target=accept, daemon=True)
        t.start()
        exp = registry.get(ComponentKind.EXPORTER, "syslog").build(
            "syslog/t", {"endpoint": "127.0.0.1", "port": port,
                         "protocol": "tcp"})
        exp.start()
        try:
            from odigos_tpu.pdata.logs import LogBatchBuilder, Severity

            b = LogBatchBuilder()
            res = b.add_resource({"service.name": "cart",
                                  "host.name": "n1"})
            b.add_record(body="disk full", severity=Severity.ERROR,
                         resource_index=res,
                         time_unix_nano=1_700_000_000_000_000_000)
            exp.export(b.build())
            t.join(timeout=10)
        finally:
            exp.shutdown()
            srv.close()
        assert received, "no syslog frame arrived"
        frame = received[0].decode()
        # <PRI>1 TIMESTAMP HOSTNAME APP ... MSG
        assert frame.startswith("<131>1 2023-11-14T"), frame  # 16*8+3
        assert " n1 cart - - - disk full\n" in frame

    def test_non_log_batches_drop_visibly(self):
        from odigos_tpu.utils.telemetry import meter

        exp = registry.get(ComponentKind.EXPORTER, "syslog").build(
            "syslog/d", {"endpoint": "127.0.0.1", "port": 1})
        exp.start()
        before = meter.counter(
            "odigos_vendor_dropped_total{exporter=syslog/d}")
        exp.export(synthesize_traces(2, seed=1))
        after = meter.counter(
            "odigos_vendor_dropped_total{exporter=syslog/d}")
        assert after > before
        exp.shutdown()
