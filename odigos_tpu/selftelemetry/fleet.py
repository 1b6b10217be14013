"""Fleet observability plane: per-collector rollups over the series
store, rule-driven alerting, and observe-only sizing recommendations.

The reference platform aggregates collector health across the fleet via
OpAMP status reporting and CRD conditions, and ships sizing profiles the
operator applies by hand (PAPER.md layers 2/4/5). This module is that
plane for our collectors, built on :mod:`seriesstate`:

* **per-collector publishing** — each collector (real in-process
  ``Collector`` or simulated fleet member) publishes its metrics
  snapshot and condition rollup under a ``{collector=}`` label via
  **delta publishing**: the plane remembers the last published value per
  key per collector and only changed series cross the seam, so hundreds
  to thousands of publishers stay cheap (an idle collector's repeat
  snapshot costs one dict walk, zero store writes).
* **cross-collector aggregation** — ``aggregate(metric, fn, agg)``
  computes a windowed value per series and combines across collectors
  (sum/max/min/avg/quantile), optionally grouped ``by="collector"`` or
  any other label; plus a **worst-of condition rollup per group** (the
  CollectorsGroup mirror the e2e control plane publishes).
* **rule-driven alerting** — declarative rules (the ``alerts:`` config
  stanza rendered by pipelinegen, validated by graph.validate_config,
  hot-reloadable like PR 8's ``slo:``) evaluate an expression over
  seriesstate window queries::

      rate(odigos_flow_dropped_items_total{reason=queue_full}[30s]) > 500

  with Prometheus-style per-series semantics (the WORST series decides),
  a ``for:`` hold duration (breach must persist before firing; recovery
  clears), and a bounded fired/cleared transition history. Firing rules
  surface as ``alert/<name>`` conditions through ``HealthRollup``
  exactly like the SLO burn rows.
* **sizing recommendations** — a small rule table turns the PR 3 device
  runtime gauges (padding waste, ladder hit rate, queue depth) and the
  PR 9 ``backlog_ms`` watermark into NAMED recommendations against the
  ``config/sizing.py`` knobs (batch size, ladder rungs, replica count,
  admission deadline), each carrying a machine-readable ``proposal``
  (concrete config-path edit, bounded proposed value). Surfaced on
  ``/api/fleet`` / ``/debug/fleetz`` / describe / diagnose through the
  flap-guarded :class:`Recommender` (pending→active ``for_s`` hold);
  the closed-loop actuator (``controlplane/actuator.py``, ISSUE 15)
  consumes the same held feed to canary → judge → promote/rollback.

Kill switch: the plane rides :data:`seriesstate.series_store`'s
``ODIGOS_SERIES=0`` — publishing and evaluation no-op with it.
"""

from __future__ import annotations

import re
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Callable, Optional

from ..utils.telemetry import labeled_key, meter
from .flightrecorder import flight_recorder
from .seriesstate import COUNTER, GAUGE, series_store, split_key, with_label

HEALTH_STATUS_METRIC = "odigos_collector_health_status"

SEVERITIES = ("info", "warning", "critical")

_STATUS_SCORE = {"Healthy": 0.0, "Degraded": 1.0, "Unhealthy": 2.0}

# ------------------------------------------------------------ expressions

# <fn>(<metric>{<labels>}[<window>s]) <cmp> <threshold> — the one-line
# grammar alert rules and recommender rows share. Deliberately closed:
# free-form PromQL would make "does this rule resolve" unlintable.
_EXPR_RE = re.compile(
    r"^\s*(?P<fn>[a-z][a-z0-9]*)\(\s*"
    r"(?P<metric>[a-zA-Z_:][a-zA-Z0-9_:]*)\s*"
    r"(?:\{(?P<labels>[^}]*)\})?\s*"
    r"(?:\[(?P<window>\d+(?:\.\d+)?)s\])?\s*\)\s*"
    r"(?P<cmp>>=|<=|>|<)\s*"
    r"(?P<threshold>-?\d+(?:\.\d+)?(?:[eE][+-]?\d+)?)\s*$")

DEFAULT_EXPR_WINDOW_S = 60.0


def parse_expr(expr: str) -> dict[str, Any]:
    """Parse one alert expression; raises ValueError with a config-
    surfaceable message on any malformation (validate_config aggregates
    these, so a typo'd rule dies at load, not silently never fires)."""
    m = _EXPR_RE.match(expr or "")
    if m is None:
        raise ValueError(
            f"unparsable alert expression {expr!r} (grammar: "
            f"fn(metric{{k=v,...}}[Ns]) <op> number)")
    fn = m.group("fn")
    if fn not in series_store.WINDOW_FNS:
        raise ValueError(
            f"unknown window function {fn!r} in {expr!r} "
            f"(known: {series_store.WINDOW_FNS})")
    labels: dict[str, str] = {}
    if m.group("labels"):
        for part in m.group("labels").split(","):
            if "=" not in part:
                raise ValueError(
                    f"bad label matcher {part!r} in {expr!r} (want k=v)")
            k, v = part.split("=", 1)
            labels[k.strip()] = v.strip().strip('"')
    window = float(m.group("window")) if m.group("window") \
        else DEFAULT_EXPR_WINDOW_S
    if window <= 0:
        raise ValueError(f"window must be positive in {expr!r}")
    if fn == "rate" and not m.group("window"):
        # a rate with an implicit window is the classic silent footgun;
        # the rule author must say what they are averaging over
        raise ValueError(f"rate() requires an explicit [Ns] window "
                         f"in {expr!r}")
    return {"fn": fn, "metric": m.group("metric"), "labels": labels,
            "window_s": window, "cmp": m.group("cmp"),
            "threshold": float(m.group("threshold"))}


def worst_series(values: dict[str, float], cmp: str
                 ) -> tuple[Optional[str], Optional[float]]:
    """The series that decides a per-series rule: the one closest to
    (or deepest into) breach — max for upper-bound comparators, min for
    lower-bound ones (Prometheus semantics: a rule trips if ANY series
    breaches). One implementation for alerts AND the recommender so
    their semantics can never silently diverge."""
    if not values:
        return None, None
    pick = max if cmp in (">", ">=") else min
    key = pick(values, key=values.get)
    return key, values[key]


def referenced_metric(expr: str) -> str:
    """Base metric name an expression reads — the package-hygiene lint
    resolves this against the registered ``odigos_*`` name registry."""
    return parse_expr(expr)["metric"]


def validate_alert_rules(alerts: Any) -> list[str]:
    """Static validation of a ``service.alerts`` stanza; returns
    problems (empty = valid) — the graph.validate_config contract."""
    problems: list[str] = []
    if not isinstance(alerts, list):
        return [f"service.alerts must be a list, got {type(alerts).__name__}"]
    seen: set[str] = set()
    for i, rule in enumerate(alerts):
        where = f"service.alerts[{i}]"
        if not isinstance(rule, dict):
            problems.append(f"{where}: rule must be a mapping")
            continue
        unknown = set(rule) - {"name", "expr", "for_s", "severity"}
        if unknown:
            problems.append(f"{where}: unknown keys {sorted(unknown)}")
        name = rule.get("name")
        if not name or not isinstance(name, str):
            problems.append(f"{where}: missing rule name")
        elif name in seen:
            problems.append(f"{where}: duplicate rule name {name!r}")
        else:
            seen.add(name)
        try:
            parse_expr(rule.get("expr", ""))
        except ValueError as e:
            problems.append(f"{where}: {e}")
        for_s = rule.get("for_s", 0.0)
        if isinstance(for_s, bool) or not isinstance(for_s, (int, float)) \
                or for_s < 0:
            problems.append(f"{where}: for_s must be a non-negative "
                            f"number")
        sev = rule.get("severity", "warning")
        if sev not in SEVERITIES:
            problems.append(f"{where}: severity {sev!r} not in "
                            f"{SEVERITIES}")
    return problems


# --------------------------------------------------------------- alerting


class AlertRule:
    """One configured rule + its firing state machine. State advances
    on :meth:`AlertEngine.evaluate` (pollers and the plane timer call
    it; the machine is a pure function of (store contents, clock), so
    alternating pollers agree)."""

    __slots__ = ("name", "expr", "for_s", "severity", "parsed", "state",
                 "pending_since", "fired_at", "last_value",
                 "worst_series")

    def __init__(self, cfg: dict[str, Any]):
        self.name = cfg["name"]
        self.expr = cfg["expr"]
        self.for_s = float(cfg.get("for_s", 0.0))
        self.severity = cfg.get("severity", "warning")
        self.parsed = parse_expr(self.expr)
        self.state = "inactive"  # inactive | pending | firing
        self.pending_since: Optional[float] = None
        self.fired_at: Optional[float] = None
        self.last_value: Optional[float] = None
        self.worst_series: Optional[str] = None

    def spec(self) -> tuple:
        return (self.name, self.expr, self.for_s, self.severity)

    def _worst(self, values: dict[str, float]
               ) -> tuple[Optional[str], Optional[float]]:
        return worst_series(values, self.parsed["cmp"])

    def advance(self, store, now: float) -> dict[str, Any]:
        """One evaluation step; returns the transition event (if any)
        for the history ring: {"event": "fired"|"cleared", ...}."""
        p = self.parsed
        values = store.series_values(p["metric"], p["fn"], p["window_s"],
                                     p["labels"] or None)
        key, value = self._worst(values)
        self.worst_series = key
        self.last_value = value
        breach = value is not None and _CMP[p["cmp"]](value,
                                                      p["threshold"])
        event: dict[str, Any] = {}
        if breach:
            if self.state == "inactive":
                self.state = "pending"
                self.pending_since = now
            if self.state == "pending" \
                    and now - (self.pending_since or now) >= self.for_s:
                self.state = "firing"
                self.fired_at = now
                event = {"event": "fired"}
        else:
            if self.state == "firing":
                event = {"event": "cleared"}
            self.state = "inactive"
            self.pending_since = None
            self.fired_at = None
        if event:
            event.update({"rule": self.name, "severity": self.severity,
                          "value": value, "series": key,
                          "unix_ts": time.time()})
        return event

    def status(self) -> dict[str, Any]:
        return {
            "name": self.name, "expr": self.expr, "for_s": self.for_s,
            "severity": self.severity, "state": self.state,
            "value": self.last_value, "series": self.worst_series,
            "threshold": self.parsed["threshold"],
            "firing": self.state == "firing",
        }


_CMP: dict[str, Callable[[float, float], bool]] = {
    ">": lambda a, b: a > b, ">=": lambda a, b: a >= b,
    "<": lambda a, b: a < b, "<=": lambda a, b: a <= b,
}


class AlertEngine:
    """Process-global rule registry + evaluator (the latency_ledger /
    flow_ledger sibling). Rules are keyed by name; ``configure`` is
    get-or-create stable on an identical spec (firing state survives a
    hot reload that didn't touch the rule — the configure_slo
    discipline) and re-creates on ANY change; ``remove`` retires a rule
    a reload deleted (the remove_slo discipline — graphs stamp their
    declared rule names and ``Collector.reload`` diffs them)."""

    HISTORY = 256

    def __init__(self, store=None,
                 clock: Callable[[], float] = time.monotonic):
        self._store = store
        self._clock = clock
        self._lock = threading.Lock()
        self._rules: dict[str, AlertRule] = {}
        self.history: deque[dict[str, Any]] = deque(maxlen=self.HISTORY)

    @property
    def store(self):
        return self._store if self._store is not None else series_store

    def configure(self, cfg: dict[str, Any]) -> AlertRule:
        candidate = AlertRule(cfg)
        with self._lock:
            existing = self._rules.get(candidate.name)
            if existing is not None and existing.spec() == candidate.spec():
                return existing
            self._rules[candidate.name] = candidate
            return candidate

    def remove(self, name: str) -> None:
        with self._lock:
            self._rules.pop(name, None)

    def rule_names(self) -> set[str]:
        with self._lock:
            return set(self._rules)

    def evaluate(self, now: Optional[float] = None) -> list[dict[str, Any]]:
        """Advance every rule's state machine against the store and
        return fresh statuses. Safe (and cheap) to call from every
        poller; the ``for:`` hold keys off the injected clock."""
        if not self.store.enabled:
            return []
        now = now if now is not None else self._clock()
        store = self.store
        with self._lock:
            rules = list(self._rules.values())
        out = []
        events = []
        for rule in rules:
            with self._lock:
                event = rule.advance(store, now)
                if event:
                    self.history.append(event)
                    events.append(event)
            out.append(rule.status())
        for event in events:
            meter.add(labeled_key("odigos_fleet_alert_transitions_total",
                                  rule=event["rule"],
                                  event=event["event"]))
            flight_recorder.record(
                "alert", event=event["event"], rule=event["rule"],
                severity=event["severity"], value=event["value"],
                series=event["series"])
            if event["event"] == "fired":
                flight_recorder.trigger(
                    "alert_firing",
                    detail=f"{event['rule']} fired on "
                           f"{event['series']} = {event['value']}",
                    rule=event["rule"], severity=event["severity"])
        for rule in rules:
            # continuous capture of the series a HOT rule references
            # (pending/firing): the pre-trigger ramp is in the black
            # box even when the freeze comes from another trigger
            if rule.state != "inactive":
                flight_recorder.excerpt_tick(rule.name, rule.expr)
        out.sort(key=lambda r: r["name"])
        return out

    def status(self) -> list[dict[str, Any]]:
        """Current rule statuses WITHOUT advancing state (surfaces that
        must not double-step the clock between evaluate calls)."""
        with self._lock:
            return sorted((r.status() for r in self._rules.values()),
                          key=lambda r: r["name"])

    def firing(self) -> list[dict[str, Any]]:
        return [r for r in self.status() if r["firing"]]

    def transitions(self) -> list[dict[str, Any]]:
        with self._lock:
            return list(self.history)

    def reset(self) -> None:
        with self._lock:
            self._rules.clear()
            self.history.clear()


alert_engine = AlertEngine()


# ---------------------------------------------------------- recommender


@dataclass(frozen=True)
class RecommendationRule:
    """One sizing rule: when ``expr`` breaches (same grammar and
    per-series semantics as alerts), recommend turning ``knob`` (a
    ``config/sizing.py`` KNOB_SPECS name) in ``direction``. ``action``
    is the operator-facing sentence, formatted with the observed value.
    ``for_s`` is the flap guard (ISSUE 15): the breach must persist
    that long before the recommendation activates — the closed-loop
    actuator consumes the HELD feed (:class:`Recommender`) and must
    never canary a one-tick blip."""

    name: str
    expr: str
    knob: str
    action: str
    severity: str = "info"
    direction: str = "up"   # which way the proposal turns the knob
    for_s: float = 30.0     # pending -> active hold (the alert for_s)


# the PR 3 gauges + PR 9 watermark -> sizing knobs table. Thresholds
# are deliberately conservative: a recommendation that flaps on noise
# trains operators to ignore the panel.
RECOMMENDER_RULES: tuple[RecommendationRule, ...] = (
    RecommendationRule(
        name="padding-waste-high",
        expr="avg(odigos_engine_padding_waste_frac[120s]) > 0.25",
        knob="max_batch",
        action=("{value:.0%} of device rows are padding — densify the "
                "bucket ladder (more rungs) or lower anomaly.max_batch "
                "so packed batches sit closer to real row counts"),
        severity="warning", direction="down", for_s=60.0),
    RecommendationRule(
        name="ladder-hit-rate-low",
        expr="avg(odigos_engine_bucket_ladder_hit_rate[120s]) < 0.9",
        knob="bucket_ladder",
        action=("bucket-ladder hit rate {value:.0%} — widen the warmed "
                "ladder (more rungs / warm_ladder at start) so steady-"
                "state shapes stop paying XLA recompiles"),
        severity="warning", direction="up", for_s=60.0),
    RecommendationRule(
        name="engine-queue-sustained",
        expr="avg(odigos_engine_queue_depth[60s]) > 6",
        knob="replicas",
        action=("engine queue depth averaging {value:.1f} — the scoring "
                "path is the bottleneck; add gateway replicas (within "
                "the sizing preset's max_replicas) or raise "
                "anomaly.max_batch"),
        severity="warning", direction="up", for_s=30.0),
    # ISSUE 15 satellite: the old single rule said "raise fast_path
    # submit_lanes" while naming knob=replicas (and the submit_lanes
    # knob was referenced by no rule at all) — split into the lane rule
    # (first response: widen the featurize/submit pool) and the replica
    # rule (backlog persisting WELL past the lane fix's territory)
    RecommendationRule(
        name="submit-lanes-saturated",
        expr="avg(odigos_flow_queue_high_watermark{queue=backlog_ms}"
             "[60s]) > 50",
        knob="submit_lanes",
        action=("ingest backlog averaging {value:.0f} ms — the "
                "featurize/submit lanes cannot keep up with intake; "
                "raise fast_path submit_lanes"),
        severity="warning", direction="up", for_s=30.0),
    RecommendationRule(
        name="ingest-backlog-pressure",
        expr="avg(odigos_flow_queue_high_watermark{queue=backlog_ms}"
             "[60s]) > 150",
        knob="replicas",
        action=("ingest backlog averaging {value:.0f} ms persists well "
                "past what wider submit lanes can absorb — add gateway "
                "replicas"),
        severity="warning", direction="up", for_s=30.0),
    # ISSUE 15: frames queueing past the admission deadline forward
    # unscored (scored_fraction SLO burn) — the one knob the actuator
    # can turn incrementally under full load (fast_path.deadline_ms is
    # in IngestFastPath.RECONFIGURABLE_KEYS: a ~0.3 ms node-local patch)
    RecommendationRule(
        name="deadline-expiry-storm",
        expr="rate(odigos_latency_deadline_expired_spans_total[60s])"
             " > 200",
        knob="admission_deadline",
        action=("deadline expiries at {value:.0f} spans/s — frames "
                "queue past the admission deadline and forward "
                "unscored; raise fast_path.deadline_ms (bounded) or "
                "add capacity"),
        severity="warning", direction="up", for_s=30.0),
    # ISSUE 20: compile events are first-class incidents — unplanned
    # (warm=false) XLA recompiles mid-steady-state are the silent
    # latency cliff the device plane exists to catch. The cure is the
    # same knob as ladder-hit-rate-low (widen the warmed bucket
    # ladder so live shapes land on precompiled rungs), but the
    # trigger is the compile EVENTS themselves: a storm pages even
    # when the hit-rate average hasn't moved yet. Threshold sits well
    # above the startup ramp's handful of cold-bucket compiles.
    RecommendationRule(
        name="compile-storm",
        expr="rate(odigos_jit_compile_events_total{warm=false}[120s])"
             " > 0.05",
        knob="bucket_ladder",
        action=("unplanned XLA recompiles at {value:.2f}/s — live "
                "shapes are churning off the warmed ladder and paying "
                "compiles mid-run; widen the bucket ladder (more "
                "rungs / warm_ladder at start) and check /debug/xlaz "
                "for the recompiling shapes"),
        severity="critical", direction="up", for_s=60.0),
)


def recommend(store=None, config=None, collector_config=None,
              max_step: float = 2.0, rules=None) -> list[dict[str, Any]]:
    """INSTANTANEOUS breach evaluation of the recommendation table —
    the primitive. Surfaces and the actuator consume the HELD feed
    (:class:`Recommender` / ``fleet_plane.recommender``), which wraps
    this with the pending→active ``for_s`` lifecycle.

    Each entry carries a machine-readable ``proposal`` (ISSUE 15): the
    knob's config key, direction, hard bounds and actuatability from
    ``sizing.KNOB_SPECS`` — and, when ``collector_config`` (a collector
    config dict) is given, the CONCRETE grounded edits: per-site config
    path, current value, and a ``bounded_step`` proposed value clamped
    into the spec bounds. ``config`` (a ``config.model.Configuration``)
    scopes replica suggestions to the install's sizing preset."""
    store = store if store is not None else series_store
    if not store.enabled:
        return []
    from ..config.sizing import (
        KNOB_SPECS, SIZING_PRESETS, TUNING_KNOBS, bounded_step,
        gateway_resources, knob_sites)

    replica_note = ""
    replica_bounds = None
    if config is not None:
        preset = SIZING_PRESETS.get(config.resource_size_preset)
        res = gateway_resources(config.collector_gateway, preset)
        replica_note = (f" (preset bounds: {res.min_replicas}-"
                        f"{res.max_replicas} replicas)")
        replica_bounds = [res.min_replicas, res.max_replicas]
    out: list[dict[str, Any]] = []
    for rule in (rules if rules is not None else RECOMMENDER_RULES):
        p = parse_expr(rule.expr)
        values = store.series_values(p["metric"], p["fn"], p["window_s"],
                                     p["labels"] or None)
        key, value = worst_series(values, p["cmp"])
        if value is None or not _CMP[p["cmp"]](value, p["threshold"]):
            continue
        _, labels = split_key(key)
        rec = {
            "name": rule.name,
            "severity": rule.severity,
            "metric": p["metric"],
            "series": key,
            "collector": labels.get("collector", ""),
            "observed": round(value, 4),
            "threshold": p["threshold"],
            "knob": rule.knob,
            "knob_path": TUNING_KNOBS.get(rule.knob, rule.knob),
            "direction": rule.direction,
            "for_s": rule.for_s,
            "recommendation": rule.action.format(value=value)
            + (replica_note if rule.knob == "replicas" else ""),
        }
        spec = KNOB_SPECS.get(rule.knob)
        if spec is not None:
            proposal: dict[str, Any] = {
                "knob": rule.knob,
                "kind": spec.kind,
                "key": spec.key,
                "direction": rule.direction,
                "bounds": (replica_bounds
                           if rule.knob == "replicas" and replica_bounds
                           else [spec.min_value, spec.max_value]),
                "actuatable": spec.actuatable,
                "refusal": spec.refusal,
            }
            if collector_config is not None \
                    and spec.kind in ("processor", "fastpath"):
                proposal["edits"] = [
                    {"path": list(path), "current": cur,
                     "proposed": bounded_step(
                         rule.knob, cur, value, p["threshold"],
                         rule.direction, max_step)}
                    for path, cur in knob_sites(rule.knob,
                                                collector_config)]
            rec["proposal"] = proposal
        out.append(rec)
    return out


class Recommender:
    """Held pending→active recommendation lifecycle (ISSUE 15
    satellite): the instant a rule's expr breaches it goes PENDING;
    only after the breach persists ``for_s`` (the rule's flap guard)
    does the recommendation activate — and recovery clears it
    immediately. The alert engine's ``for_s`` discipline applied to
    the recommender feed, because the closed-loop actuator must never
    canary a one-tick blip. Pure function of (store contents, clock),
    so alternating pollers agree — the AlertRule contract."""

    def __init__(self, store=None,
                 clock: Callable[[], float] = time.monotonic,
                 rules: Optional[tuple] = None):
        self._store = store
        self._clock = clock
        self._rules: tuple[RecommendationRule, ...] = \
            tuple(rules) if rules is not None else RECOMMENDER_RULES
        self._lock = threading.Lock()
        self._pending: dict[str, float] = {}  # rule -> pending_since

    @property
    def store(self):
        return self._store if self._store is not None else series_store

    def rules(self) -> tuple[RecommendationRule, ...]:
        with self._lock:
            return self._rules

    def set_rules(self, rules: Optional[tuple]) -> None:
        """Swap the rule table (harness seam: the soak/chaos runs need
        test-timescale windows and holds). ``None`` restores the
        built-in RECOMMENDER_RULES. Hold state resets — old pendings
        must not vouch for new rules."""
        with self._lock:
            self._rules = tuple(rules) if rules is not None \
                else RECOMMENDER_RULES
            self._pending.clear()

    def rule(self, name: str) -> Optional[RecommendationRule]:
        with self._lock:
            return next((r for r in self._rules if r.name == name), None)

    def evaluate(self, config=None, collector_config=None,
                 max_step: float = 2.0,
                 now: Optional[float] = None) -> list[dict[str, Any]]:
        """Advance the hold state machine and return the ACTIVE
        recommendations (breaching continuously >= for_s), each with
        ``state``/``held_s`` stamped. Pending breaches are withheld."""
        now = now if now is not None else self._clock()
        with self._lock:
            rules = self._rules
        recs = {r["name"]: r for r in recommend(
            self.store, config, collector_config, max_step, rules=rules)}
        out: list[dict[str, Any]] = []
        with self._lock:
            for rule in rules:
                rec = recs.get(rule.name)
                if rec is None:
                    self._pending.pop(rule.name, None)
                    continue
                since = self._pending.setdefault(rule.name, now)
                held = now - since
                if held >= rule.for_s:
                    rec["state"] = "active"
                    rec["held_s"] = round(held, 3)
                    out.append(rec)
        out.sort(key=lambda r: r["name"])
        return out

    def rule_state(self, name: str,
                   now: Optional[float] = None) -> str:
        """``inactive`` | ``pending`` | ``active`` — WITHOUT advancing
        holds (the actuator's breach-clear oracle re-evaluates the expr
        itself; this is the surface view)."""
        now = now if now is not None else self._clock()
        with self._lock:
            rule = next((r for r in self._rules if r.name == name), None)
            since = self._pending.get(name)
            if rule is None or since is None:
                return "inactive"
            return "active" if now - since >= rule.for_s else "pending"

    def status(self, now: Optional[float] = None) -> list[dict[str, Any]]:
        """Per-rule hold state for the surfaces (fleetz, describe)."""
        now = now if now is not None else self._clock()
        with self._lock:
            out = []
            for r in self._rules:
                since = self._pending.get(r.name)
                state = "inactive" if since is None else (
                    "active" if now - since >= r.for_s else "pending")
                out.append({"name": r.name, "knob": r.knob,
                            "for_s": r.for_s, "state": state,
                            "held_s": (round(now - since, 3)
                                       if since is not None else None)})
            return out

    def reset(self) -> None:
        with self._lock:
            self._rules = RECOMMENDER_RULES
            self._pending.clear()


# --------------------------------------------------------------- the plane


class _CollectorEntry:
    """Per-collector publish state: the delta base + last conditions."""

    __slots__ = ("collector_id", "group", "last_publish", "last_full",
                 "last_values", "conditions", "worst", "published",
                 "skipped", "source")

    def __init__(self, collector_id: str, group: str):
        self.collector_id = collector_id
        self.group = group
        self.last_publish: Optional[float] = None
        self.last_full: Optional[float] = None  # heartbeat anchor
        self.last_values: dict[str, float] = {}
        self.conditions: list[dict[str, Any]] = []
        self.worst: tuple[str, str, str] = ("Healthy", "Registered", "")
        self.published = 0   # series writes that crossed the seam
        self.skipped = 0     # unchanged series delta publishing elided
        self.source: Optional[Callable[[], dict]] = None


class FleetPlane:
    """Process-global fleet registry over the series store (the
    ``fleet_plane`` sibling of meter/tracer/flow_ledger). Collectors —
    real or simulated — ``publish()`` snapshots; surfaces read
    ``api_snapshot()``; the alert engine and recommender evaluate over
    the same store."""

    def __init__(self, store=None,
                 clock: Callable[[], float] = time.monotonic,
                 heartbeat_s: float = 10.0):
        self._store = store
        self._clock = clock
        # delta elision heartbeat: at most this long between FULL
        # re-publishes per collector. A steady (unchanged) gauge would
        # otherwise vanish from every window query once its single
        # written point ages past the window — a sustained breach
        # self-clearing its own alert mid-incident. The heartbeat
        # bounds the staleness: rule windows must be >= heartbeat_s
        # (the default matches the smallest sane window; the grammar's
        # default window is 60 s).
        self.heartbeat_s = float(heartbeat_s)
        self._lock = threading.Lock()
        self._collectors: dict[str, _CollectorEntry] = {}
        self._timer: Optional[threading.Thread] = None
        self._timer_stop = threading.Event()
        # the HELD recommendation feed (ISSUE 15): surfaces and the
        # closed-loop actuator read this, never the instantaneous
        # recommend() primitive — a one-tick blip must not canary
        self.recommender = Recommender(store=store, clock=clock)

    @property
    def store(self):
        return self._store if self._store is not None else series_store

    @property
    def enabled(self) -> bool:
        return self.store.enabled

    # ------------------------------------------------------- membership

    def register(self, collector_id: str, group: str = "",
                 source: Optional[Callable[[], dict]] = None
                 ) -> None:
        """Announce a fleet member. ``source`` (optional) is a zero-arg
        callable returning a publishable payload dict — the plane timer
        pulls it; push-only members just call :meth:`publish`."""
        with self._lock:
            entry = self._collectors.get(collector_id)
            if entry is None:
                entry = self._collectors[collector_id] = _CollectorEntry(
                    collector_id, group)
            if group:
                entry.group = group
            if source is not None:
                entry.source = source

    def unregister(self, collector_id: str,
                   drop_series: bool = True) -> None:
        """Remove a member (collector churn). Its series leave the
        store too (default) so fleet aggregates stop answering for a
        departed collector instead of coasting on its last window."""
        with self._lock:
            self._collectors.pop(collector_id, None)
        if drop_series:
            self.store.drop_series({"collector": collector_id})

    def collectors(self) -> list[str]:
        with self._lock:
            return sorted(self._collectors)

    # ------------------------------------------------------- publishing

    @staticmethod
    def _kind_of(key: str) -> str:
        # snapshot keys are level samples; cumulative counters follow
        # the *_total convention everywhere in this codebase, and the
        # histogram _count suffix is cumulative too
        base = key.split("{", 1)[0]
        return COUNTER if base.endswith(("_total", "_count")) else GAUGE

    def publish(self, collector_id: str, metrics: dict[str, float],
                conditions: Optional[list[dict[str, Any]]] = None,
                worst: Optional[tuple[str, str, str]] = None,
                group: str = "", ts: Optional[float] = None,
                delta: bool = True) -> dict[str, int]:
        """One publish from one collector: every metric key gains the
        ``{collector=}`` label and lands in the store — but with
        ``delta`` (the default) only keys whose value CHANGED since this
        collector's previous publish are written; the rest are skipped
        without touching the store lock. ``delta=False`` forces a full
        write (the equivalence oracle tests pin delta == full).

        Returns {"published": n, "skipped": n}."""
        store = self.store
        if not store.enabled:
            return {"published": 0, "skipped": 0}
        now = self._clock()
        with self._lock:
            entry = self._collectors.get(collector_id)
            if entry is None:
                entry = self._collectors[collector_id] = _CollectorEntry(
                    collector_id, group)
            elif group:
                entry.group = group
            # heartbeat: force a FULL publish at least every
            # heartbeat_s per collector — a steady value elided forever
            # would age out of every query window and a sustained
            # breach would self-clear its own alert mid-incident
            if delta and (entry.last_full is None
                          or now - entry.last_full >= self.heartbeat_s):
                delta = False
            if not delta and metrics:
                entry.last_full = now
            last = entry.last_values
            changed: list[tuple[str, float]] = []
            skipped = 0
            for key, value in metrics.items():
                v = float(value)
                if delta and last.get(key) == v:
                    skipped += 1
                    continue
                last[key] = v
                changed.append((key, v))
            if conditions is not None:
                entry.conditions = [dict(c) for c in conditions]
            if worst is not None:
                entry.worst = tuple(worst)  # type: ignore[assignment]
            entry.last_publish = now
            # health status rides the store as a numeric series so
            # window queries ("was it degraded in the last minute") and
            # alert rules can read fleet health like any other metric
            changed.append((HEALTH_STATUS_METRIC,
                            _STATUS_SCORE.get(entry.worst[0], 0.0)))
            entry.skipped += skipped
        # two observe_many calls (counters, gauges) = two store lock
        # holds per publish regardless of key count — a per-key lock
        # would make the publish seam the fleet layer's own bound
        # violation at hundreds of collectors
        counters: list[tuple[str, float]] = []
        gauges: list[tuple[str, float]] = []
        labeled_to_key: dict[str, str] = {}
        for key, v in changed:
            lab = with_label(key, collector=collector_id)
            labeled_to_key[lab] = key
            (counters if self._kind_of(key) is COUNTER
             else gauges).append((lab, v))
        refused: list[str] = []
        published = store.observe_many(counters, kind=COUNTER, ts=ts,
                                       refused=refused) \
            + store.observe_many(gauges, kind=GAUGE, ts=ts,
                                 refused=refused)
        if refused:
            # a key the store refused (cardinality cap) must not stay
            # in the delta base, or an identical next snapshot would be
            # elided and the series could never land once capacity
            # frees (collector churn releases series)
            with self._lock:
                for lab in refused:
                    entry.last_values.pop(labeled_to_key[lab], None)
        with self._lock:
            # series_published reports what actually crossed into the
            # store, not what the delta walk attempted
            entry.published += published
        return {"published": published, "skipped": skipped}

    def publish_collector(self, collector, collector_id: str,
                          group: str = "") -> dict[str, int]:
        """Publish a real in-process ``Collector``: its flow-ledger
        counters are mirrored into the meter first (the scrape
        discipline), then the meter snapshot plus the collector's
        condition rollup cross the seam. NOTE: in-process collectors
        share one process-global meter, so their metric series coincide
        — the per-collector distinction that matters in-process is the
        condition rollup; distinct metric series come from distinct
        processes (or simulated publishers)."""
        if not self.store.enabled:
            # kill-switch contract: ODIGOS_SERIES=0 makes the whole
            # publish path free — no snapshot walk, no rollup evaluate
            return {"published": 0, "skipped": 0}
        from .flow import flow_ledger

        flow_ledger.publish(meter)
        # metrics FIRST, conditions second: the rollup's alert rows
        # evaluate against the store, so the snapshot that trips a rule
        # must land before the rollup runs — the other order records a
        # worst-of that lags one publish behind the data that fired it
        r1 = self.publish(collector_id, meter.snapshot(), group=group)
        rollup = getattr(collector.graph, "flow_health", None)
        conditions: list[dict[str, Any]] = []
        worst: Optional[tuple[str, str, str]] = None
        if rollup is not None:
            conditions = rollup.evaluate()
            worst = rollup.worst()
        r2 = self.publish(collector_id, {}, conditions=conditions,
                          worst=worst, group=group)
        return {"published": r1["published"] + r2["published"],
                "skipped": r1["skipped"] + r2["skipped"]}

    # ------------------------------------------------------ aggregation

    def aggregate(self, metric: str, fn: str = "latest",
                  window_s: float = 60.0, agg: str = "sum",
                  labels: Optional[dict[str, str]] = None,
                  by: Optional[str] = None) -> Any:
        return self.store.aggregate(metric, fn=fn, window_s=window_s,
                                    agg=agg, labels=labels, by=by)

    def group_rollup(self) -> dict[str, dict[str, Any]]:
        """Worst-of condition rollup per group — the CollectorsGroup
        status mirror: {group: {status, reason, message,
        worst_collector, collectors, by_status}}."""
        rank = {"Healthy": 0, "Degraded": 1, "Unhealthy": 2}
        with self._lock:
            entries = list(self._collectors.values())
        groups: dict[str, dict[str, Any]] = {}
        for e in entries:
            g = groups.setdefault(e.group or "(ungrouped)", {
                "status": "Healthy", "reason": "AllHealthy",
                "message": "", "worst_collector": "",
                "collectors": 0,
                "by_status": {"Healthy": 0, "Degraded": 0,
                              "Unhealthy": 0}})
            g["collectors"] += 1
            status = e.worst[0]
            g["by_status"][status] = g["by_status"].get(status, 0) + 1
            if rank.get(status, 0) > rank.get(g["status"], 0):
                g.update({"status": status, "reason": e.worst[1],
                          "message": e.worst[2],
                          "worst_collector": e.collector_id})
        return groups

    # ----------------------------------------------------------- timer

    def start_timer(self, interval_s: float = 5.0) -> None:
        """Background publish+evaluate loop: pulls every registered
        source, then advances the alert engine — the "evaluated on a
        timer" leg for deployments with no poller traffic. Idempotent;
        one timer per plane."""
        with self._lock:
            if self._timer is not None:
                return
            self._timer_stop.clear()
            self._timer = threading.Thread(
                target=self._timer_loop, args=(float(interval_s),),
                name="fleet-plane-timer", daemon=True)
            self._timer.start()

    def _timer_loop(self, interval_s: float) -> None:
        while not self._timer_stop.wait(interval_s):
            self.tick()

    def tick(self) -> None:
        """One timer step (also callable inline by harnesses that own
        their own cadence)."""
        with self._lock:
            pulls = [(e.collector_id, e.group, e.source)
                     for e in self._collectors.values()
                     if e.source is not None]
        for cid, group, source in pulls:
            try:
                payload = source()
            except Exception:  # noqa: BLE001 — telemetry never raises
                continue
            if payload:
                self.publish(cid, payload.get("metrics", {}),
                             conditions=payload.get("conditions"),
                             worst=payload.get("worst"), group=group)
        alert_engine.evaluate()
        # closed-loop actuator (ISSUE 15): ride the same cadence the
        # alert engine does, but ONLY if something already armed it —
        # sys.modules-gated so a plane tick in a process that never
        # touched the control plane imports nothing
        import sys as _sys

        act_mod = _sys.modules.get("odigos_tpu.controlplane.actuator")
        if act_mod is not None:
            act_mod.fleet_actuator.tick()

    def stop_timer(self) -> None:
        with self._lock:
            timer, self._timer = self._timer, None
        if timer is not None:
            self._timer_stop.set()
            timer.join(timeout=5.0)

    # --------------------------------------------------------- surfaces

    def api_snapshot(self, config=None) -> dict[str, Any]:
        """The one JSON document every surface reads (``/api/fleet``,
        ``/debug/fleetz``, diagnose ``fleet.json``)."""
        now = self._clock()
        with self._lock:
            entries = list(self._collectors.values())
        collectors = []
        for e in sorted(entries, key=lambda e: e.collector_id):
            collectors.append({
                "collector": e.collector_id,
                "group": e.group,
                "status": e.worst[0],
                "reason": e.worst[1],
                "message": e.worst[2],
                "age_s": (round(now - e.last_publish, 3)
                          if e.last_publish is not None else None),
                "series_published": e.published,
                "series_skipped": e.skipped,
                "conditions": list(e.conditions),
            })
        return {
            "enabled": self.enabled,
            "collectors": collectors,
            "groups": self.group_rollup(),
            "alerts": {
                "rules": alert_engine.evaluate(),
                "history": alert_engine.transitions(),
            },
            # the HELD feed (ISSUE 15): a recommendation appears only
            # after its breach persisted for_s — the panel and the
            # actuator see the same flap-guarded list
            "recommendations": self.recommender.evaluate(config),
            "recommender": self.recommender.status(),
            "store": self.store.stats(),
        }

    def reset(self) -> None:
        """Test isolation: forget members + their series + rules (the
        flow_ledger.reset contract; the store itself is reset too when
        it is the global one)."""
        self.stop_timer()
        with self._lock:
            self._collectors.clear()
        alert_engine.reset()
        self.recommender.reset()
        self.store.reset()


fleet_plane = FleetPlane()
