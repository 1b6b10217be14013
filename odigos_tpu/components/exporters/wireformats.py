"""Vendor wire formats — the dedicated-protocol layer of the exporter
family.

The reference compiles a dedicated exporter per backend
(collector/builder-config.yaml:19-60: splunkhecexporter :55,
influxdbexporter :44, opensearchexporter :50, awsxrayexporter :29, ...),
each speaking the backend's REAL ingest protocol.  Round 4's vendor
family POSTed the same otlp-json document everywhere (round-4 review weak:
"dedicated wire protocols for non-OTLP vendors"); this module supplies
the actual formats as pure marshal functions:

    marshal(batch, config) -> list[WireRequest]

so a protocol is testable byte-for-byte against a local mock without a
socket in the loop.  VendorExporter looks the vendor type up in
``MARSHALLERS`` and falls back to otlp-json for the OTLP-speaking
backends.

Formats implemented here:

* splunk_hec   — HEC event JSON, concatenated objects, to
                 ``/services/collector`` with ``Authorization: Splunk
                 <token>`` (splunkhecexporter wire shape)
* influx_line  — InfluxDB line protocol v2 to ``/api/v2/write``
                 (influxdbexporter): metrics as ``name,tags value ts``;
                 spans/logs under the otel schema measurements
* bulk_ndjson  — Elasticsearch/OpenSearch ``_bulk`` NDJSON: action line
                 + document line pairs (opensearch/elasticsearch
                 exporters)
* azure_track  — Application Insights envelope JSON to ``/v2.1/track``
                 derived from the connection string (azuremonitor)
* aws JSON-RPC — X-Ray ``PutTraceSegments`` REST, CloudWatch Logs
                 ``PutLogEvents`` (awscloudwatchlogs), and CloudWatch
                 EMF metric-format log events (awsemf), SigV4-signed
                 via utils/awssig.py
"""

from __future__ import annotations

import gzip
import itertools
import json
import re
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

from ...pdata.logs import LogBatch
from ...pdata.metrics import MetricBatch

# Application Insights severityLevel: Verbose=0 Information=1 Warning=2
# Error=3 Critical=4
_AZURE_SEV = {"TRACE": 0, "DEBUG": 0, "INFO": 1, "WARN": 2, "ERROR": 3,
              "FATAL": 4}


@dataclass
class WireRequest:
    """One HTTP request of a vendor protocol."""

    body: bytes
    path: str = ""                      # appended to the base url
    method: str = "POST"
    headers: dict[str, str] = field(default_factory=dict)
    content_type: str = "application/json"
    # (region, service) when the request must be SigV4-signed
    aws_sign: Optional[tuple[str, str]] = None


Marshaller = Callable[[Any, dict[str, Any]], list[WireRequest]]


def _rows(batch) -> list[dict[str, Any]]:
    if isinstance(batch, MetricBatch):
        return list(batch.iter_points())
    if isinstance(batch, LogBatch):
        return list(batch.iter_records())
    return list(batch.iter_spans())


# ------------------------------------------------------------ splunkhec


def marshal_splunk_hec(batch, config: dict[str, Any]) -> list[WireRequest]:
    """HEC events: concatenated JSON objects (not an array — the HEC
    endpoint parses a stream), one per span/point/record."""
    source = str(config.get("source", "odigos"))
    index = config.get("index")
    events = []
    for row in _rows(batch):
        t_ns = (row.get("time_unix_nano")
                or row.get("start_unix_nano") or 0)
        ev: dict[str, Any] = {
            "time": round(t_ns / 1e9, 3),
            "source": source,
            "sourcetype": "otel",
            "event": row,
        }
        if index:
            ev["index"] = str(index)
        events.append(json.dumps(ev, default=str))
    body = "".join(events).encode()
    token = str(config.get("token", ""))
    return [WireRequest(
        body=body, path="/services/collector",
        headers={"Authorization": f"Splunk {token}"} if token else {})]


# ----------------------------------------------------------- influxdb

_LP_ESCAPE_TAG = re.compile(r"([,= ])")
_LP_ESCAPE_MEAS = re.compile(r"([, ])")


def _lp_tag(v: str) -> str:
    return _LP_ESCAPE_TAG.sub(r"\\\1", str(v))


def _lp_meas(v: str) -> str:
    return _LP_ESCAPE_MEAS.sub(r"\\\1", str(v))


def _lp_fieldval(v: Any) -> str:
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, (int, float)):
        return repr(float(v))
    s = str(v).replace("\\", "\\\\").replace('"', '\\"')
    return f'"{s}"'


def _lp_line(measurement: str, tags: dict[str, Any],
             fields: dict[str, Any], t_ns: int) -> str:
    # empty tag values are illegal line protocol (the backend 400s the
    # whole write): skip them alongside None
    tag_part = "".join(f",{_lp_tag(k)}={_lp_tag(v)}"
                       for k, v in sorted(tags.items())
                       if v is not None and str(v) != "")
    field_part = ",".join(f"{_lp_tag(k)}={_lp_fieldval(v)}"
                          for k, v in fields.items())
    return f"{_lp_meas(measurement)}{tag_part} {field_part} {int(t_ns)}"


def marshal_influx_line(batch, config: dict[str, Any]) -> list[WireRequest]:
    """Line protocol v2: metrics map naturally (measurement = metric
    name, tags = attrs); spans/logs follow the influx otel schema
    ('spans' / 'logs' measurements, influxdbexporter default)."""
    lines = []
    if isinstance(batch, MetricBatch):
        for row in _rows(batch):
            tags = {**row["resource"], **row["attributes"]}
            tags.pop("service.name", None)
            if row["resource"].get("service.name"):
                tags["service"] = row["resource"]["service.name"]
            lines.append(_lp_line(row["name"], tags,
                                  {"value": row["value"]},
                                  row["time_unix_nano"]))
    elif isinstance(batch, LogBatch):
        for row in _rows(batch):
            tags = {"service": row["resource"].get("service.name", "")}
            fields = {"body": row["body"],
                      "severity": str(row["severity"])}
            lines.append(_lp_line("logs", tags, fields,
                                  row["time_unix_nano"]))
    else:
        for row in _rows(batch):
            tags = {"service": row["service"],
                    "span.kind": row["kind"]}
            fields = {
                "trace_id": row["trace_id"], "span_id": row["span_id"],
                "name": row["name"],
                "duration_ns": (row["end_unix_nano"]
                                - row["start_unix_nano"]),
            }
            lines.append(_lp_line("spans", tags, fields,
                                  row["start_unix_nano"]))
    from urllib.parse import quote

    org = quote(str(config.get("org", "")), safe="")
    bucket = quote(str(config.get("bucket", "")), safe="")
    headers = {}
    if config.get("token"):
        headers["Authorization"] = f"Token {config['token']}"
    return [WireRequest(
        body="\n".join(lines).encode(),
        path=f"/api/v2/write?org={org}&bucket={bucket}&precision=ns",
        headers=headers, content_type="text/plain; charset=utf-8")]


# --------------------------------------------- opensearch/elasticsearch


def marshal_bulk_ndjson(batch, config: dict[str, Any]) -> list[WireRequest]:
    """_bulk: alternating action/document NDJSON lines; the index comes
    from config (opensearchexporter logs_index/traces_index defaults)."""
    if isinstance(batch, MetricBatch):
        index = str(config.get("metrics_index", "otel-metrics"))
    elif isinstance(batch, LogBatch):
        index = str(config.get("logs_index", "otel-logs"))
    else:
        index = str(config.get("traces_index", "otel-traces"))
    action = json.dumps({"create": {"_index": index}})
    lines = []
    for row in _rows(batch):
        lines.append(action)
        lines.append(json.dumps(row, default=str))
    body = ("\n".join(lines) + "\n").encode()
    return [WireRequest(body=body, path="/_bulk",
                        content_type="application/x-ndjson")]


# --------------------------------------------------------- azuremonitor

_CONN_RE = re.compile(r"([A-Za-z]+)=([^;]+)")


def parse_azure_connection_string(cs: str) -> dict[str, str]:
    return {m.group(1): m.group(2) for m in _CONN_RE.finditer(cs or "")}


def marshal_azure_track(batch, config: dict[str, Any]) -> list[WireRequest]:
    """Application Insights /v2.1/track envelopes (azuremonitorexporter
    wire shape): one envelope per row, iKey from the connection string."""
    parts = parse_azure_connection_string(
        str(config.get("connection_string", "")))
    ikey = parts.get("InstrumentationKey", "")
    if isinstance(batch, MetricBatch):
        kind, base = "MetricData", lambda r: {
            "metrics": [{"name": r["name"], "value": r["value"]}],
            "properties": {str(k): str(v)
                           for k, v in r["attributes"].items()}}
    elif isinstance(batch, LogBatch):
        kind, base = "MessageData", lambda r: {
            "message": r["body"],
            "severityLevel": _AZURE_SEV.get(str(r["severity"]), 1),
            "properties": {str(k): str(v)
                           for k, v in r["attributes"].items()}}
    else:
        kind, base = "RequestData", lambda r: {
            "id": r["span_id"], "name": r["name"],
            "duration": _azure_duration(
                r["end_unix_nano"] - r["start_unix_nano"]),
            "success": r["status_code"] != "ERROR",
            "responseCode": r["status_code"],
            "properties": {str(k): str(v)
                           for k, v in r["attributes"].items()}}
    envelopes = []
    for row in _rows(batch):
        t_ns = (row.get("time_unix_nano")
                or row.get("start_unix_nano") or 0)
        envelopes.append({
            "name": f"Microsoft.ApplicationInsights.{kind}",
            "time": _iso(t_ns),
            "iKey": ikey,
            "data": {"baseType": kind, "baseData": base(row)},
        })
    return [WireRequest(body=json.dumps(envelopes, default=str).encode(),
                        path="/v2.1/track")]


def _iso(t_ns: int) -> str:
    t = time.gmtime(t_ns / 1e9)
    return time.strftime("%Y-%m-%dT%H:%M:%S", t) + \
        f".{int(t_ns % 1_000_000_000) // 1_000_000:03d}Z"


def _azure_duration(dur_ns: int) -> str:
    ms = max(int(dur_ns // 1_000_000), 0)
    s, ms = divmod(ms, 1000)
    m, s = divmod(s, 60)
    h, m = divmod(m, 60)
    return f"{h:02d}:{m:02d}:{s:02d}.{ms:03d}"


# ---------------------------------------------------------- AWS family


def marshal_xray(batch, config: dict[str, Any]) -> list[WireRequest]:
    """PutTraceSegments REST: TraceSegmentDocuments as JSON strings
    (awsxrayexporter wire shape; X-Ray trace ids are 1-<8 hex epoch>-
    <24 hex>)."""
    region = str(config.get("region") or "us-east-1")
    docs = []
    for row in _rows(batch):
        tid = row["trace_id"]
        start_s = row["start_unix_nano"] / 1e9
        docs.append(json.dumps({
            "name": row["service"] or row["name"],
            "id": row["span_id"],
            "trace_id": f"1-{int(start_s):08x}-{tid[8:32]}",
            "start_time": start_s,
            "end_time": row["end_unix_nano"] / 1e9,
            "annotations": {str(k): str(v)
                            for k, v in row["attributes"].items()},
        }, default=str))
    body = json.dumps({"TraceSegmentDocuments": docs}).encode()
    return [WireRequest(body=body, path="/TraceSegments",
                        aws_sign=(region, "xray"))]


def _log_events(rows: list[dict[str, Any]],
                fmt: Callable[[dict], str]) -> list[dict[str, Any]]:
    evs = [{"timestamp": int((r.get("time_unix_nano") or 0) / 1e6),
            "message": fmt(r)} for r in rows]
    evs.sort(key=lambda e: e["timestamp"])  # PutLogEvents requires order
    return evs


def marshal_cloudwatch_logs(batch,
                            config: dict[str, Any]) -> list[WireRequest]:
    """CloudWatch Logs PutLogEvents JSON-RPC (awscloudwatchlogsexporter)."""
    region = str(config.get("region") or "us-east-1")
    payload = {
        "logGroupName": str(config.get("log_group_name", "")),
        "logStreamName": str(config.get("log_stream_name", "")),
        "logEvents": _log_events(
            _rows(batch), lambda r: json.dumps(r, default=str)),
    }
    return [WireRequest(
        body=json.dumps(payload, default=str).encode(),
        headers={"X-Amz-Target": "Logs_20140328.PutLogEvents"},
        content_type="application/x-amz-json-1.1",
        aws_sign=(region, "logs"))]


def marshal_emf(batch, config: dict[str, Any]) -> list[WireRequest]:
    """CloudWatch EMF (awsemfexporter): metrics as embedded-metric-format
    log events through PutLogEvents."""
    region = str(config.get("region") or "us-east-1")
    namespace = str(config.get("namespace", "odigos"))

    def fmt(r: dict) -> str:
        return json.dumps({
            "_aws": {
                "Timestamp": int((r.get("time_unix_nano") or 0) / 1e6),
                "CloudWatchMetrics": [{
                    "Namespace": namespace,
                    "Dimensions": [["service"]],
                    "Metrics": [{"Name": r["name"]}],
                }],
            },
            "service": r["resource"].get("service.name", ""),
            r["name"]: r["value"],
        }, default=str)

    payload = {
        "logGroupName": str(config.get("log_group_name",
                                       f"/metrics/{namespace}")),
        "logStreamName": str(config.get("log_stream_name", "odigos")),
        "logEvents": _log_events(_rows(batch), fmt),
    }
    return [WireRequest(
        body=json.dumps(payload, default=str).encode(),
        headers={"X-Amz-Target": "Logs_20140328.PutLogEvents"},
        content_type="application/x-amz-json-1.1",
        aws_sign=(region, "logs"))]


# uniqueness for S3 object keys: millisecond timestamps collide when a
# split batch marshals both halves in the same ms (the second PUT would
# silently overwrite the first)
_s3_seq = itertools.count()


def marshal_s3_put(batch, config: dict[str, Any]) -> list[WireRequest]:
    """awss3exporter: one gzipped otlp-json object per batch, keyed by
    the uploader's partition layout (prefix/year/.../signal_<ts>.json.gz)."""
    up = config.get("s3uploader") or {}
    region = str(up.get("region") or "us-east-1")
    if isinstance(batch, MetricBatch):
        signal, doc = "metrics", {"resourceMetrics": _rows(batch)}
    elif isinstance(batch, LogBatch):
        signal, doc = "logs", {"resourceLogs": _rows(batch)}
    else:
        signal, doc = "traces", {"resourceSpans": _rows(batch)}
    now = time.time()
    tm = time.gmtime(now)
    prefix = str(up.get("s3_prefix") or "").strip("/")
    key = time.strftime("year=%Y/month=%m/day=%d/hour=%H", tm)
    if str(up.get("s3_partition", "minute")) == "minute":
        key += time.strftime("/minute=%M", tm)
    name = f"{signal}_{int(now * 1000)}_{next(_s3_seq)}.json.gz"
    path = "/" + "/".join(p for p in (prefix, key, name) if p)
    return [WireRequest(
        body=gzip.compress(json.dumps(doc, default=str).encode()),
        path=path, method="PUT", content_type="application/octet-stream",
        headers={"Content-Encoding": "gzip"},
        aws_sign=(region, "s3"))]


# --------------------------------------------------------- googlecloud


def marshal_otlp_http_pathed(batch,
                             config: dict[str, Any]) -> list[WireRequest]:
    """OTLP-JSON with the per-signal OTLP-HTTP path (googlecloudexporter
    replaced by the OTLP telemetry endpoint — round-4 review item 5)."""
    if isinstance(batch, MetricBatch):
        path, doc = "/v1/metrics", {"resourceMetrics": _rows(batch)}
    elif isinstance(batch, LogBatch):
        path, doc = "/v1/logs", {"resourceLogs": _rows(batch)}
    else:
        path, doc = "/v1/traces", {"resourceSpans": _rows(batch)}
    headers = {}
    if config.get("project"):
        headers["x-goog-user-project"] = str(config["project"])
    import os

    token = os.environ.get("GOOGLE_OAUTH_ACCESS_TOKEN", "")
    if token:
        headers["Authorization"] = f"Bearer {token}"
    return [WireRequest(body=json.dumps(doc, default=str).encode(),
                        path=path, headers=headers)]





# --------------------------------------------------------------- zipkin


def marshal_zipkin(batch, config: dict[str, Any]) -> list[WireRequest]:
    """Zipkin v2 JSON array to /api/v2/spans (zipkinexporter) — the
    exact inverse of our zipkin receiver's intake mapping."""
    docs = []
    for row in _rows(batch):
        if "start_unix_nano" not in row:
            continue  # traces-only signal upstream
        doc = {
            "traceId": row["trace_id"],
            "id": row["span_id"],
            "parentId": (row["parent_span_id"]
                         if row["parent_span_id"].strip("0") else None),
            "name": row["name"],
            "timestamp": row["start_unix_nano"] // 1000,
            "duration": max((row["end_unix_nano"]
                             - row["start_unix_nano"]) // 1000, 1),
            "localEndpoint": {"serviceName": row["service"]},
            "tags": {str(k): str(v)
                     for k, v in row["attributes"].items()},
        }
        # zipkin v2 accepts ONLY CLIENT|SERVER|PRODUCER|CONSUMER; a real
        # server 400s the whole array on anything else (INTERNAL spans
        # omit the field, as upstream's zipkin translator does)
        if row["kind"] in ("CLIENT", "SERVER", "PRODUCER", "CONSUMER"):
            doc["kind"] = row["kind"]
        docs.append(doc)
    return [WireRequest(body=json.dumps(docs).encode(),
                        path="/api/v2/spans")]


# ------------------------------------------------------------ sumologic


def marshal_sumologic(batch, config: dict[str, Any]) -> list[WireRequest]:
    """Sumo HTTP source (sumologicexporter): logs as newline-joined
    bodies with X-Sumo-* metadata headers; metrics as prometheus
    exposition lines; traces as otlp-json."""
    headers = {}
    for cfg_key, header in (("source_category", "X-Sumo-Category"),
                            ("source_name", "X-Sumo-Name"),
                            ("source_host", "X-Sumo-Host")):
        if config.get(cfg_key):
            headers[header] = str(config[cfg_key])
    if isinstance(batch, LogBatch):
        body = "\n".join(r["body"] for r in _rows(batch)).encode()
        return [WireRequest(body=body, headers=headers,
                            content_type="text/plain")]
    if isinstance(batch, MetricBatch):
        lines = []
        for r in _rows(batch):
            labels = ",".join(
                f'{k}="{v}"' for k, v in sorted(r["attributes"].items()))
            lines.append(f"{r['name']}{{{labels}}} {r['value']} "
                         f"{r['time_unix_nano'] // 10**6}")
        return [WireRequest(body="\n".join(lines).encode(),
                            headers=headers,
                            content_type=("application/vnd.sumologic."
                                          "prometheus"))]
    doc = {"resourceSpans": _rows(batch)}
    return [WireRequest(body=json.dumps(doc, default=str).encode(),
                        headers=headers)]


# --------------------------------------------------------------- sentry


_DSN_RE = re.compile(
    r"(https?)://([^@:/]+)(?::([^@/]+))?@([^/]+)/(\d+)")


def parse_sentry_dsn(dsn: str):
    """(scheme, public_key, host, project) or None — ONE parser for the
    extractor and the marshaller (legacy key:secret DSNs included)."""
    m = _DSN_RE.match(dsn or "")
    if not m:
        return None
    return m.group(1), m.group(2), m.group(4), m.group(5)


def marshal_sentry(batch, config: dict[str, Any]) -> list[WireRequest]:
    """Sentry envelope endpoint (sentryexporter): one envelope of
    transaction items; DSN parsed for the project id + public key."""
    dsn = str(config.get("dsn", ""))
    parsed = parse_sentry_dsn(dsn)
    key, project = (parsed[1], parsed[3]) if parsed else ("", "0")
    lines = [json.dumps({"dsn": dsn})]
    for row in _rows(batch):
        if "start_unix_nano" not in row:
            continue
        item = {
            "type": "transaction",
            "transaction": row["name"],
            "event_id": row["span_id"].rjust(32, "0"),
            "start_timestamp": row["start_unix_nano"] / 1e9,
            "timestamp": row["end_unix_nano"] / 1e9,
            "contexts": {"trace": {"trace_id": row["trace_id"],
                                    "span_id": row["span_id"],
                                    "op": row["kind"]}},
            "tags": {str(k): str(v)
                     for k, v in row["attributes"].items()},
        }
        payload = json.dumps(item)
        lines.append(json.dumps({"type": "transaction",
                                 "length": len(payload)}))
        lines.append(payload)
    headers = {"X-Sentry-Auth": (f"Sentry sentry_key={key}, "
                                 "sentry_version=7")} if key else {}
    return [WireRequest(body="\n".join(lines).encode(),
                        path=f"/api/{project}/envelope/",
                        headers=headers,
                        content_type="application/x-sentry-envelope")]


# ------------------------------------------------------ honeycombmarker


def marshal_honeycomb_marker(batch,
                             config: dict[str, Any]) -> list[WireRequest]:
    """honeycombmarkerexporter: one marker per matching log record to
    /1/markers/{dataset} with the team key header."""
    dataset = str(config.get("dataset", "__all__"))
    headers = {}
    if config.get("api_key"):
        headers["X-Honeycomb-Team"] = str(config["api_key"])
    reqs = []
    for row in _rows(batch):
        marker = {
            "message": row.get("body") or row.get("name", ""),
            "type": str(config.get("marker_type", "otel")),
            "start_time": int((row.get("time_unix_nano")
                               or row.get("start_unix_nano") or 0)
                              / 1e9),
        }
        reqs.append(WireRequest(body=json.dumps(marker).encode(),
                                path=f"/1/markers/{dataset}",
                                headers=headers))
    return reqs or [WireRequest(body=b"[]",
                                path=f"/1/markers/{dataset}",
                                headers=headers)]


# --------------------------------------------------- googlecloudpubsub


def marshal_pubsub(batch, config: dict[str, Any]) -> list[WireRequest]:
    """googlecloudpubsubexporter: REST publish — otlp-json document
    base64-wrapped in a Pub/Sub message."""
    import base64
    import os

    if isinstance(batch, MetricBatch):
        doc = {"resourceMetrics": _rows(batch)}
    elif isinstance(batch, LogBatch):
        doc = {"resourceLogs": _rows(batch)}
    else:
        doc = {"resourceSpans": _rows(batch)}
    topic = str(config.get("topic", ""))  # projects/<p>/topics/<t>
    payload = {"messages": [{"data": base64.b64encode(
        json.dumps(doc, default=str).encode()).decode()}]}
    headers = {}
    token = os.environ.get("GOOGLE_OAUTH_ACCESS_TOKEN", "")
    if token:
        headers["Authorization"] = f"Bearer {token}"
    return [WireRequest(body=json.dumps(payload).encode(),
                        path=f"/v1/{topic}:publish", headers=headers)]


MARSHALLERS: dict[str, Marshaller] = {
    "googlecloud": marshal_otlp_http_pathed,
    "zipkin": marshal_zipkin,
    "sumologic": marshal_sumologic,
    "sentry": marshal_sentry,
    "honeycombmarker": marshal_honeycomb_marker,
    "googlecloudpubsub": marshal_pubsub,
    "splunkhec": marshal_splunk_hec,
    "influxdb": marshal_influx_line,
    "opensearch": marshal_bulk_ndjson,
    "elasticsearch": marshal_bulk_ndjson,
    "azuremonitor": marshal_azure_track,
    "awsxray": marshal_xray,
    "awscloudwatchlogs": marshal_cloudwatch_logs,
    "awsemf": marshal_emf,
    "awss3": marshal_s3_put,
}
