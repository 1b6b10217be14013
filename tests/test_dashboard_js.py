"""Dashboard JS contract tests (round-3 review item 3).

No JS engine ships in this image (no node/quickjs/browser), so the page's
inline script cannot be *executed* here; these tests implement the next
strongest guarantee, in both directions:

* every endpoint the JS fetches is extracted from the page source and hit
  against a live, populated server (reference analog: cypress/e2e/
  01-connection.cy.ts hitting the running webapp);
* every ``root.field`` property access the JS performs on API payloads is
  extracted from the script and checked against a hand-maintained CONTRACT
  table — adding an access without extending the table fails the sync
  guard — and every CONTRACT path is then resolved against the *actual*
  payload served by the live server. A renamed server field, or a JS
  access to a field no payload carries (the ``d.destination_type`` vs
  ``dest_type`` class of bug this test was introduced to catch), fails.
* geometry/format constants the sparkline math depends on are extracted
  from the JS and pinned, so silent edits surface in review.
"""

from __future__ import annotations

import json
import re
import threading
import time
import urllib.request

import pytest

from odigos_tpu.components.api import Signal
from odigos_tpu.controlplane.cluster import Container
from odigos_tpu.destinations import Destination
from odigos_tpu.e2e.environment import E2EEnvironment
from odigos_tpu.frontend import FrontendServer
from odigos_tpu.frontend.server import _dashboard_page
from odigos_tpu.pdata import synthesize_traces


def get_json(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return json.loads(r.read())


def _script() -> str:
    page = _dashboard_page().decode()
    m = re.search(r"<script>(.*)</script>", page, re.S)
    assert m, "dashboard has no inline script"
    return m.group(1)


# --------------------------------------------------------------- the contract
#
# root variable in the JS -> (endpoint, field paths the JS reads).
# "?" suffix = the JS guards the access with a fallback (`|| {}`, ternary),
# so absence in a particular payload instance is tolerated — but the path
# must still be a real field the server CAN serve, asserted below against
# a populated instance wherever possible.
CONTRACT: dict[str, dict] = {
    "metrics": {"endpoint": "/api/metrics",
                "fields": ["totals", "services"]},
    "tot": {"endpoint": "/api/metrics",
            "at": ["totals", "odigos_traffic_spans_total"],
            "fields": ["per_sec", "total"]},
    "spans": {"endpoint": "/api/metrics",
              "at": ["services", "*", "odigos_traffic_spans_total"],
              "fields": ["per_sec", "total"]},
    "bytes": {"endpoint": "/api/metrics",
              "at": ["services", "*", "odigos_traffic_bytes_total"],
              "fields": ["per_sec"]},
    "anomalies": {"endpoint": "/api/anomalies",
                  "fields": ["scored", "scored_per_sec", "passthrough",
                             "flagged"]},
    "a": {"endpoint": "/api/anomalies",
          "fields": ["scored", "scored_per_sec", "passthrough",
                     "passthrough_per_sec", "flagged", "flagged_per_sec",
                     "local_flagged"]},
    "topo": {"endpoint": "/api/pipeline", "fields": ["pipelines"]},
    "pipe": {"endpoint": "/api/pipeline", "at": ["pipelines", "*"],
             "fields": ["receivers?", "processors?", "exporters?"]},
    "s": {"endpoint": "/api/sources", "each": True,
          "fields": ["meta", "workload", "disable_instrumentation?"]},
    "w": {"endpoint": "/api/sources", "each": True, "at": ["workload"],
          "fields": ["namespace", "name", "kind"]},
    "d": {"endpoint": "/api/destinations", "each": True,
          "fields": ["meta", "signals", "dest_type", "name?"]},
    # destination setup catalog (the (setup) wizard data source)
    "t": {"endpoint": "/api/destination-types", "each": True,
          "fields": ["type", "display_name", "signals", "fields"]},
    "f": {"endpoint": "/api/destination-types", "each": True,
          "at": ["fields", "*"], "fields": ["name", "secret"]},
    # policies section (the reference UI's actions + rules pages)
    "ac": {"endpoint": "/api/actions", "each": True,
           "fields": ["meta", "action_kind", "signals", "disabled"]},
    "ru": {"endpoint": "/api/rules", "each": True,
           "fields": ["meta", "rule_kind", "languages", "disabled"]},
    # self-tracing panel (the framework tracing itself, /api/selftrace)
    "st": {"endpoint": "/api/selftrace",
           "fields": ["traces", "spans_total", "dropped", "exemplars"]},
    "tr": {"endpoint": "/api/selftrace", "at": ["traces", "*"],
           "fields": ["root", "span_count", "duration_ms"]},
    # latency exemplars (ISSUE 3): histogram tail -> self-trace pivot
    "ex": {"endpoint": "/api/selftrace", "at": ["exemplars", "*"],
           "fields": ["metric", "value", "trace_id"]},
    # flow ledger panel (ISSUE 5): conservation balance + conditions
    "flow": {"endpoint": "/api/flow",
             "fields": ["pipelines", "conditions"]},
    "fp": {"endpoint": "/api/flow", "at": ["pipelines", "*"],
           "fields": ["items_in", "items_out", "dropped", "failed",
                      "pending", "leak"]},
    "fc": {"endpoint": "/api/flow", "at": ["conditions", "*"],
           "fields": ["component", "status", "reason"]},
    # latency attribution & SLO burn panel (ISSUE 8): per-pipeline burn
    # status + stage waterfall; per-pipeline rows are reached via locals
    # (sp/stages), validated top-level here — the fixture runs no SLO'd
    # fast-path pipeline, so the dicts are legitimately empty
    "slo": {"endpoint": "/api/slo", "fields": ["pipelines", "waterfall"]},
    # fleet plane panel (ISSUE 10): per-collector health, alert rule
    # states, sizing recommendations; per-row objects are reached via
    # locals (co/al/rec) — top-level containers validated here (always
    # served, possibly empty)
    "fleet": {"endpoint": "/api/fleet",
              "fields": ["collectors", "alerts", "recommendations"]},
    # closed-loop actuator panel (ISSUE 15): armed state, in-flight
    # canary/promotion, bounded action history; per-row objects are
    # reached via locals (h/cur) — top-level containers validated here
    # (always served: in_flight is present-but-null when idle)
    "act": {"endpoint": "/api/actuator",
            "fields": ["enabled", "dry_run", "state", "in_flight",
                       "history"]},
    # flight recorder panel (ISSUE 16): black-box counters + frozen
    # incident summaries; per-incident rows are reached via a local (it)
    # — top-level containers validated here (always served, possibly
    # empty on a clean run)
    "inc": {"endpoint": "/api/incidents",
            "fields": ["enabled", "incidents", "events_total",
                       "suppressed", "incidents_evicted"]},
    # device plane panel (ISSUE 20): sampled intra-fused attribution,
    # XLA cost/efficiency ledger rows, recent compile events, resident
    # table footprint; per-row objects are reached via locals
    # (ab/row/ev) — top-level containers validated here (always served,
    # empty until a fused engine arms attribution)
    "dev": {"endpoint": "/api/device",
            "fields": ["attribution", "cost", "compiles", "tables"]},
    # workload drill-down (the reference UI's describe view)
    "desc": {"endpoint": "/api/describe/workload", "fields": ["text"]},
    # SSE store-event JSON (validated in test_sse_event_shape)
    "e": {"endpoint": "/api/events",
          "fields": ["type", "kind", "namespace", "name"]},
}

# property accesses on these roots that are NOT payload fields (methods,
# locals the JS builds itself) — excluded from the sync guard
_NON_PAYLOAD = {
    ("s", "length"), ("d", "length"), ("a", "length"),
    ("sources", "length"), ("dests", "length"), ("names", "length"),
    ("points", "length"), ("rateHistory", "length"), ("pts", "map"),
    ("s", "meta"),  # chained s.meta.name handled via "meta" entries
}

_ROOTS = set(CONTRACT)


def _js_payload_accesses() -> set[tuple[str, str]]:
    """(root, field) pairs the script reads on contract roots."""
    out = set()
    for root, fld in re.findall(r"\b([A-Za-z_]\w*)\.([A-Za-z_]\w*)",
                                _script()):
        if root in _ROOTS and (root, fld) not in _NON_PAYLOAD:
            out.add((root, fld))
    # bracket accesses with string-literal keys: s.meta["name"] style and
    # pipe[role] dynamic ones are covered by the contract's "at"/fields
    return out


def test_contract_table_covers_every_js_access():
    """Sync guard: a new payload access in the JS without a CONTRACT entry
    fails here, keeping the table honest."""
    declared = {(root, f.rstrip("?"))
                for root, spec in CONTRACT.items()
                for f in spec["fields"]}
    accesses = _js_payload_accesses()
    extra = {(r, f) for r, f in accesses
             if (r, f) not in declared
             and f not in ("meta",)}  # chained-root container fields
    assert not extra - declared, \
        f"JS reads fields not in the CONTRACT table: {sorted(extra)}"


def test_every_fetched_endpoint_is_declared():
    """Every fetch()/EventSource URL in the script is a CONTRACT endpoint
    (and vice-versa nothing is stale)."""
    script = _script()
    # the optional second segment catches /api/describe/workload while a
    # template literal's `${` fails the class, so `/api/sources/${key}`
    # yields its static prefix /api/sources
    fetched = set(re.findall(r"/api/[a-z-]+(?:/[a-z-]+)?", script))
    declared = {spec["endpoint"] for spec in CONTRACT.values()}
    assert fetched == declared, (
        f"page fetches {sorted(fetched)} but contract declares "
        f"{sorted(declared)}")


def test_sparkline_and_format_constants_pinned():
    script = _script()
    # geometry the sparkline math depends on (sparkline())
    m = re.search(r"const W = (\d+), H = (\d+), P = (\d+)", script)
    assert m, "sparkline geometry constants moved — update this pin"
    assert (int(m.group(1)), int(m.group(2)), int(m.group(3))) == (160, 28, 2)
    # history window (renderTiles) and poll cadence
    assert "rateHistory.length > 30" in script
    assert re.search(r"setInterval\(\(\) => poll\(true\), 2000\)", script)
    # compact() thresholds: 1e6 -> M, 1e4 -> K
    assert ">= 1e6" in script and ">= 1e4" in script


# ----------------------------------------------------------- live validation

@pytest.fixture(scope="module")
def populated():
    """A running frontend with sources, destinations, and real traffic so
    payload instances carry the fields the JS renders."""
    env = E2EEnvironment(nodes=1)
    fe = FrontendServer(env.store, cluster=env.cluster).start()
    env.config.ui_endpoint = f"127.0.0.1:{fe.metrics_port}"
    env.start()
    try:
        env.cluster.add_workload("shop", "cart",
                                 [Container("main", language="python")])
        env.instrument_workload("shop", "cart")
        env.add_destination(Destination(
            id="db", dest_type="tracedb", signals=[Signal.TRACES]))
        from odigos_tpu.api.resources import (
            Action, ActionKind, InstrumentationRule, ObjectMeta, RuleKind)
        from odigos_tpu.controlplane.scheduler import ODIGOS_NAMESPACE

        env.store.apply(Action(
            meta=ObjectMeta(name="errs", namespace=ODIGOS_NAMESPACE),
            action_kind=ActionKind.ERROR_SAMPLER, signals=["traces"],
            details={"fallback_sampling_ratio": 10}))
        env.store.apply(InstrumentationRule(
            meta=ObjectMeta(name="pc0", namespace=ODIGOS_NAMESPACE),
            rule_kind=RuleKind.PAYLOAD_COLLECTION, languages=["python"]))
        env.reconcile()
        env.send_traces(synthesize_traces(80, seed=3))
        env.gateway_component("prometheus/self-metrics").scrape_once()
        assert env.gateway_component("otlp/ui").flush(timeout=10)
        deadline = time.time() + 10
        while time.time() < deadline:
            if get_json(f"{fe.url}/api/metrics")["batches_received"]:
                break
            time.sleep(0.05)
        yield env, fe
    finally:
        env.shutdown()
        fe.shutdown()


def _resolve(payload, at):
    """Walk an "at" path; "*" = every child (dict values or list items)."""
    nodes = [payload]
    for step in at:
        nxt = []
        for node in nodes:
            if step == "*":
                nxt.extend(node.values() if isinstance(node, dict)
                           else node if isinstance(node, list) else ())
            elif isinstance(node, dict) and step in node:
                nxt.append(node[step])
        nodes = nxt
    return nodes


def test_contract_paths_exist_in_live_payloads(populated):
    env, fe = populated
    # parameterized endpoints need the query the JS would send
    _QUERY = {"/api/describe/workload":
              "?namespace=shop&kind=deployment&name=cart"}
    payloads = {ep: get_json(fe.url + ep + _QUERY.get(ep, ""))
                for ep in {s["endpoint"] for s in CONTRACT.values()}
                - {"/api/events"}}
    failures = []
    for root, spec in CONTRACT.items():
        if spec["endpoint"] == "/api/events":
            continue
        payload = payloads[spec["endpoint"]]
        targets = [payload]
        if spec.get("each"):
            assert isinstance(payload, list) and payload, \
                f"{spec['endpoint']} empty — fixture must populate it"
            targets = payload
        if spec.get("at"):
            targets = [t for tgt in targets
                       for t in _resolve(tgt, spec["at"])]
            if not targets:
                failures.append(
                    f"{root}: path {spec['at']} unreachable in "
                    f"{spec['endpoint']} payload")
                continue
        for f in spec["fields"]:
            optional = f.endswith("?")
            f = f.rstrip("?")
            if not any(isinstance(t, dict) and f in t for t in targets):
                if not optional:
                    failures.append(
                        f"{root}.{f}: absent from {spec['endpoint']} "
                        f"(at={spec.get('at')}) — JS renders undefined")
    assert not failures, "\n".join(failures)


def test_sse_event_shape(populated):
    """The SSE handler destructures e.type/kind/namespace/name — assert a
    real store event carries exactly those."""
    env, fe = populated
    got: list[dict] = []
    ready = threading.Event()

    def listen():
        req = urllib.request.Request(f"{fe.url}/api/events")
        with urllib.request.urlopen(req, timeout=15) as r:
            for raw in r:
                line = raw.decode().strip()
                if line.startswith("data:"):
                    got.append(json.loads(line[5:]))
                    ready.set()
                    return

    t = threading.Thread(target=listen, daemon=True)
    t.start()
    time.sleep(0.3)
    env.cluster.add_workload("shop", "web",
                             [Container("main", language="python")])
    env.instrument_workload("shop", "web")
    assert ready.wait(10), "no SSE event"
    fields = [f.rstrip("?") for f in CONTRACT["e"]["fields"]]
    for f in fields:
        assert f in got[0], f"SSE event missing {f!r}: {got[0]}"


def test_destination_types_catalog(populated):
    """The setup wizard's backend catalog: all 63 registry entries with
    schema-driven fields (reference: frontend/webapp/app/(setup))."""
    env, fe = populated
    catalog = get_json(f"{fe.url}/api/destination-types")
    assert len(catalog) >= 60
    dd = next(t for t in catalog if t["type"] == "datadog")
    assert dd["display_name"] == "Datadog"
    assert set(dd["signals"]) == {"traces", "metrics", "logs"}
    names = {f["name"] for f in dd["fields"]}
    assert "DATADOG_SITE" in names
    assert any(f["secret"] for f in dd["fields"])


def test_destination_create_flow_e2e(populated):
    """The form's POST creates a datadog destination; its pipeline appears
    in the generated gateway config (cypress/e2e/04-destinations.cy.ts
    connect flow)."""
    env, fe = populated
    body = json.dumps({
        "name": "dd1", "type": "datadog",
        "signals": ["traces"],
        "fields": {"DATADOG_SITE": "datadoghq.eu",
                   "DATADOG_API_KEY": "k3y"}}).encode()
    req = urllib.request.Request(
        f"{fe.url}/api/destinations", data=body,
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=10) as r:
        assert r.status == 201
    env.reconcile()
    topo = get_json(f"{fe.url}/api/pipeline")
    assert "traces/datadog-dd1" in topo["pipelines"], \
        sorted(topo["pipelines"])
    dests = get_json(f"{fe.url}/api/destinations")
    dd1 = next(d for d in dests if d["meta"]["name"] == "dd1")
    # the secret never round-trips through the store/API: it is delivered
    # to the collector env (the Secret-backed pod-env analog) and the
    # resource records only the ref
    assert "k3y" not in json.dumps(dests), "secret echoed by the API"
    assert "DATADOG_API_KEY" not in dd1["config"]
    assert dd1["secret_ref"]
    import os
    assert os.environ.get("DATADOG_API_KEY") == "k3y"
    assert dd1["config"]["DATADOG_SITE"] == "datadoghq.eu"
    # remove through the row button's DELETE and see it disappear
    req = urllib.request.Request(f"{fe.url}/api/destinations/dd1",
                                 method="DELETE")
    with urllib.request.urlopen(req, timeout=10) as r:
        assert r.status == 200
    env.reconcile()
    topo = get_json(f"{fe.url}/api/pipeline")
    assert "traces/datadog-dd1" not in topo["pipelines"]


def test_destination_create_validation_errors(populated):
    """Missing required field -> 400 with the configer's field-level
    problem, the payload the form renders into #dest-errors."""
    env, fe = populated
    body = json.dumps({"name": "dd2", "type": "datadog",
                       "signals": ["traces"], "fields": {}}).encode()
    req = urllib.request.Request(
        f"{fe.url}/api/destinations", data=body,
        headers={"Content-Type": "application/json"}, method="POST")
    with pytest.raises(urllib.error.HTTPError) as exc:
        urllib.request.urlopen(req, timeout=10)
    assert exc.value.code == 400
    err = json.loads(exc.value.read())
    assert any("DATADOG_SITE" in p for p in err["problems"]), err
    # nothing was applied
    assert not any(d["meta"]["name"] == "dd2"
                   for d in get_json(f"{fe.url}/api/destinations"))
    # unsupported signal combination is refused too
    body = json.dumps({"name": "x1", "type": "xray",
                       "signals": ["logs"], "fields": {}}).encode()
    req = urllib.request.Request(
        f"{fe.url}/api/destinations", data=body,
        headers={"Content-Type": "application/json"}, method="POST")
    with pytest.raises(urllib.error.HTTPError) as exc:
        urllib.request.urlopen(req, timeout=10)
    assert exc.value.code == 400


def test_actions_and_rules_api(populated):
    """Actions/rules management over the JSON API (the reference UI's
    actions + rules pages, cypress/e2e/05+06): create an action and see
    its compiled processor appear in the gateway pipeline."""
    env, fe = populated

    body = json.dumps({"name": "errs2", "kind": "ErrorSampler",
                       "signals": ["traces"],
                       "details": {"fallback_sampling_ratio": 10}}).encode()
    req = urllib.request.Request(
        f"{fe.url}/api/actions", data=body,
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=10) as r:
        assert r.status == 201
    env.reconcile()
    actions = get_json(f"{fe.url}/api/actions")
    assert any(a["meta"]["name"] == "errs2" for a in actions)
    # the autoscaler compiled it into a sampling processor in the gateway
    topo = get_json(f"{fe.url}/api/pipeline")
    assert any("odigossampling" in n["id"] for n in topo["nodes"]), \
        [n["id"] for n in topo["nodes"]]

    # unknown kind -> 400
    bad = json.dumps({"name": "x", "kind": "Nope"}).encode()
    req = urllib.request.Request(
        f"{fe.url}/api/actions", data=bad,
        headers={"Content-Type": "application/json"}, method="POST")
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(req, timeout=10)
    assert e.value.code == 400

    req = urllib.request.Request(f"{fe.url}/api/actions/errs2",
                                 method="DELETE")
    with urllib.request.urlopen(req, timeout=10) as r:
        assert r.status == 200
    env.reconcile()
    assert not any(a["meta"]["name"] == "errs2"
                   for a in get_json(f"{fe.url}/api/actions"))

    # rules round trip with a workload selector
    body = json.dumps({"name": "pc", "kind": "payload-collection",
                       "workloads": [{"namespace": "shop",
                                      "name": "cart"}],
                       "languages": ["python"],
                       "details": {"max_payload_len": 256}}).encode()
    req = urllib.request.Request(
        f"{fe.url}/api/rules", data=body,
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=10) as r:
        assert r.status == 201
    rules = get_json(f"{fe.url}/api/rules")
    pc = next(r for r in rules if r["meta"]["name"] == "pc")
    assert pc["workloads"][0]["name"] == "cart"
    req = urllib.request.Request(f"{fe.url}/api/rules/pc",
                                 method="DELETE")
    with urllib.request.urlopen(req, timeout=10) as r:
        assert r.status == 200


def test_post_source_body_matches_server_expectation(populated):
    """The add-source form posts {namespace, name, kind} — assert the
    server accepts exactly that body (cypress/e2e/03-sources.cy.ts role)."""
    env, fe = populated
    env.cluster.add_workload("default", "checkout",
                             [Container("main", language="python")])
    body = json.dumps({"namespace": "default", "name": "checkout",
                       "kind": "deployment"}).encode()
    req = urllib.request.Request(
        f"{fe.url}/api/sources", data=body,
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=10) as r:
        assert r.status == 201
    # and the delete URL scheme the delegated listener builds works
    req = urllib.request.Request(
        f"{fe.url}/api/sources/default/src-checkout", method="DELETE")
    with urllib.request.urlopen(req, timeout=10) as r:
        assert r.status == 200
