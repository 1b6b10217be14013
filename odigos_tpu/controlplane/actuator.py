"""Closed-loop fleet actuator (ISSUE 15): canary a recommendation,
judge it by SLO burn, promote or roll back.

PR 10's recommender names sizing knobs but never turns them; PR 13 made
a knob change under full load a ~0.3 ms node-local graph patch. This
module closes the observe→decide→act loop — the reference's OpAMP
remote-config + profiles rollout (PAPER.md layers 2/5) with the
feedback signal the reference never had (PR 8 burn-rate SLOs + PR 10
alert conditions as a machine promotion/rollback oracle):

* **propose** — the flap-guarded recommendation feed
  (``fleet_plane.recommender``, pending→active ``for_s`` hold) supplies
  breaches; each is grounded against the canary target's live config
  into concrete edits: config path, current value, and a
  ``sizing.bounded_step`` proposed value clamped into the knob's hard
  bounds (replica knobs clamp to the sizing preset).
* **canary** — ONE collector (or one replica, for ``replicas``-knob
  actions through a registered replica scaler) takes the edit through
  ``Collector.reload``. The structural differ classifies the edit
  FIRST: a proposal that would classify FULL is **refused, never
  actuated** — the actuator exists because incremental reload made a
  canary cheap; it must never become the thing that tears a pipeline
  down. The applied reload's mode (incremental/replace, and whether the
  patch fell back to full) is recorded per step.
* **judge** — the canary holds for a judgment window (at least the
  triggering rule's expr window — a rate over [30s] cannot visibly
  clear in 5 s). Promotion requires the triggering breach to CLEAR and
  **no SLOBurn / alert / Degraded condition to appear on the canary
  that the fleet baseline doesn't share** (pre-canary conditions plus
  whatever the rest of the fleet currently shows are excused — the
  incident being cured must not block its own cure). Any new bad
  condition rolls the canary back IMMEDIATELY to the recorded prior
  config (the PR 13 ``_graph_dirty`` revert semantics make the revert
  converge even across a half-applied patch).
* **promote** — on success the same judged value rolls fleet-wide
  collector-by-collector, each step with its own judgment window and
  the same oracle; a failing step rolls ITS collector back and aborts
  the rollout. One actuation in flight at a time, a global cooldown
  between actuations, a bounded action history, ``dry_run`` (record
  what WOULD happen, touch nothing), and the ``ODIGOS_ACTUATOR=0``
  kill switch.

Config is a validated ``service: {actuator: ...}`` stanza (the
``alerts:``/``gc:`` load-validation discipline): ``enabled``,
``dry_run``, ``judgment_window_s``, ``cooldown_s``, ``max_step``,
``knobs`` (per-knob allowlist), ``max_history``. A typo'd key or an
unknown knob dies at config load, never silently arms nothing.

Surfaces: ``odigos_actuator_*`` metrics (proposals / canaries /
promotions / rollbacks / refusals by rule and knob), an
``actuator/<rule>`` condition row on every rollup while an actuation is
in flight, ``GET /api/actuator``, ``/debug/actuatorz``, the dashboard
panel, describe and diagnose. ``tests/test_actuator.py`` drives the
whole loop on a live in-process collector.
"""

from __future__ import annotations

import copy
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Optional

from ..config.sizing import KNOB_SPECS, bounded_step, knob_sites
from ..selftelemetry.flightrecorder import flight_recorder
from ..utils.telemetry import labeled_key, meter

ACTUATOR_ENV = "ODIGOS_ACTUATOR"

PROPOSALS_METRIC = "odigos_actuator_proposals_total"
CANARIES_METRIC = "odigos_actuator_canaries_total"
PROMOTIONS_METRIC = "odigos_actuator_promotions_total"
ROLLBACKS_METRIC = "odigos_actuator_rollbacks_total"
REFUSALS_METRIC = "odigos_actuator_refusals_total"
STATE_METRIC = "odigos_actuator_state"

_STATE_SCORE = {"idle": 0.0, "canary": 1.0, "promoting": 2.0,
                "cooldown": 3.0}

_CONFIG_KEYS = {"enabled", "dry_run", "judgment_window_s", "cooldown_s",
                "max_step", "knobs", "max_history"}

# the refusal table (docs/architecture.md): every reason the actuator
# declines to act, as a closed metric-label vocabulary
REFUSAL_REASONS = ("not_allowlisted", "not_actuatable", "unknown_knob",
                   "no_collectors", "no_site", "at_bound", "full_reload",
                   "no_replica_scaler", "reload_error", "dry_run")


class ActuatorConfig:
    """Parsed ``service.actuator`` stanza; defaults = armed-off."""

    __slots__ = ("enabled", "dry_run", "judgment_window_s", "cooldown_s",
                 "max_step", "knobs", "max_history")

    def __init__(self, spec: Optional[dict] = None):
        spec = spec or {}
        problems = validate_actuator_config(spec)
        if problems:
            raise ValueError("invalid service.actuator: "
                             + "; ".join(problems))
        self.enabled = bool(spec.get("enabled", False))
        self.dry_run = bool(spec.get("dry_run", False))
        self.judgment_window_s = float(spec.get("judgment_window_s",
                                                30.0))
        self.cooldown_s = float(spec.get("cooldown_s", 120.0))
        self.max_step = float(spec.get("max_step", 2.0))
        self.knobs = tuple(spec.get("knobs") or ())
        self.max_history = int(spec.get("max_history", 256))

    def as_dict(self) -> dict[str, Any]:
        return {k: (list(v) if isinstance(v, tuple) else v)
                for k in self.__slots__ for v in (getattr(self, k),)}


def validate_actuator_config(cfg: Any) -> list[str]:
    """Static validation of a ``service.actuator`` stanza; returns
    problems (empty = valid) — the graph.validate_config contract. A
    typo'd knob name must die at load: an actuator armed against a
    knob that does not exist would silently never act."""
    problems: list[str] = []
    if not isinstance(cfg, dict):
        return [f"service.actuator must be a mapping, got "
                f"{type(cfg).__name__}"]
    unknown = set(cfg) - _CONFIG_KEYS
    if unknown:
        problems.append(f"service.actuator: unknown keys "
                        f"{sorted(unknown)}")
    for key in ("enabled", "dry_run"):
        if key in cfg and not isinstance(cfg[key], bool):
            problems.append(f"service.actuator.{key} must be a boolean")
    for key in ("judgment_window_s", "cooldown_s"):
        v = cfg.get(key)
        if v is not None and (isinstance(v, bool)
                              or not isinstance(v, (int, float))
                              or v < 0):
            problems.append(f"service.actuator.{key} must be a "
                            f"non-negative number")
    v = cfg.get("max_step")
    if v is not None and (isinstance(v, bool)
                          or not isinstance(v, (int, float)) or v <= 1.0):
        # a step bound <= 1 could never move a knob — a silently inert
        # actuator is worse than a refused config
        problems.append("service.actuator.max_step must be > 1.0")
    knobs = cfg.get("knobs")
    if knobs is not None:
        if not isinstance(knobs, (list, tuple)):
            problems.append("service.actuator.knobs must be a list")
        else:
            for k in knobs:
                # isinstance first: an unhashable YAML slip (a nested
                # mapping/list entry) must become a NAMED problem, not
                # a TypeError escaping the validator's list contract
                if not isinstance(k, str) or k not in KNOB_SPECS:
                    problems.append(
                        f"service.actuator.knobs: unknown knob {k!r} "
                        f"(known: {sorted(KNOB_SPECS)})")
    v = cfg.get("max_history")
    if v is not None and (isinstance(v, bool) or not isinstance(v, int)
                          or v < 1):
        problems.append("service.actuator.max_history must be a "
                        "positive integer")
    return problems


def _set_path(config: dict, path: tuple, value: Any) -> None:
    """Deep-set one key chain, materializing a ``fast_path: true``
    shorthand into a mapping on the way (the differ treats true→dict as
    a value change, not a toggle)."""
    node: Any = config
    for key in path[:-1]:
        nxt = node.get(key) if isinstance(node, dict) else None
        if not isinstance(nxt, dict):
            nxt = {} if nxt in (None, True) else nxt
            node[key] = nxt
        node = nxt
    node[path[-1]] = value


class FleetActuator:
    """Process-global actuator (the fleet_plane / alert_engine
    sibling). Harness-tick driven: ``FleetPlane.tick`` advances it on
    the plane cadence; the e2e environment ticks it each reconcile;
    tests tick with an injected clock."""

    def __init__(self, clock: Callable[[], float] = time.monotonic,
                 recommender=None):
        self._clock = clock
        self._recommender = recommender
        # _lock guards the state machine; _reg_lock guards config +
        # registry ONLY and is never held across a reload call — a
        # Collector configuring the actuator from under its own lock
        # while a tick reloads that collector must not ABBA-deadlock
        self._lock = threading.RLock()
        self._reg_lock = threading.Lock()
        self.config = ActuatorConfig()
        self._owner: Any = None  # who armed the live config
        self._collectors: dict[str, Any] = {}
        self._replica_scaler: Optional[Callable[[int], Optional[int]]] \
            = None
        self.state = "idle"
        self.current: Optional[dict[str, Any]] = None
        self.history: deque = deque(maxlen=self.config.max_history)
        self._cooldown_until = 0.0
        # (rule, knob, reason) deduper: a standing refusal is counted
        # once per rec activation, not once per tick
        self._noted: set[tuple] = set()
        # (rule, knob) whose proposal was counted this activation —
        # odigos_actuator_proposals_total means grounded proposals,
        # not plane ticks elapsed while one stood
        self._proposed: set[tuple] = set()
        # (rule, knob) refused AT the apply stage (dry_run, a reload
        # that failed or fell back, a replica bound): retrying every
        # tick would hammer a broken reload with no backoff — the
        # block lifts when the recommendation deactivates (or on the
        # next activation)
        self._blocked: set[tuple] = set()
        self._forced: deque = deque()  # chaos/test seam proposals

    # ---------------------------------------------------- configuration

    @property
    def recommender(self):
        if self._recommender is not None:
            return self._recommender
        from ..selftelemetry.fleet import fleet_plane

        return fleet_plane.recommender

    def configure(self, spec: Optional[dict],
                  owner: Any = None) -> ActuatorConfig:
        """Apply a ``service.actuator`` stanza (``None`` = disarm to
        defaults). ``owner`` (the configuring Collector) records who
        armed it, so a STALE owner's shutdown can't clobber a newer
        collector's live config (last configure wins — and stays won).
        Registry-lock only: safe to call from under a Collector's lock
        while a tick is mid-reload."""
        cfg = ActuatorConfig(spec)
        with self._reg_lock:
            if cfg.max_history != self.history.maxlen:
                self.history = deque(self.history,
                                     maxlen=cfg.max_history)
            self.config = cfg
            self._owner = owner if spec is not None else None
        return cfg

    def disarm(self, owner: Any) -> bool:
        """Reset to defaults ONLY if ``owner`` still owns the live
        config — a replaced collector's shutdown must not disarm what
        a newer collector legitimately armed. Returns whether the
        disarm happened."""
        with self._reg_lock:
            if self._owner is not None and self._owner is not owner:
                return False
            self.config = ActuatorConfig()
            self._owner = None
            return True

    @property
    def enabled(self) -> bool:
        if os.environ.get(ACTUATOR_ENV, "1") == "0":  # kill switch
            return False
        return self.config.enabled

    def register(self, collector_id: str, collector: Any) -> None:
        """Announce an actuation target (the duck contract: ``config``
        dict, ``reload(cfg)``, ``health_conditions()``, ``graph``)."""
        with self._reg_lock:
            self._collectors[collector_id] = collector

    def unregister(self, collector_id: str) -> None:
        with self._reg_lock:
            self._collectors.pop(collector_id, None)

    def collectors(self) -> list[str]:
        with self._reg_lock:
            return sorted(self._collectors)

    def set_replica_scaler(
            self, fn: Optional[Callable[[int], Optional[int]]]) -> None:
        """Register the control-plane hook ``replicas``-knob actions
        act through: ``fn(delta)`` applies a replica-count step (the
        canary IS one replica) and returns the new count, or ``None``
        when the preset bound refuses the step."""
        with self._reg_lock:
            self._replica_scaler = fn

    # -------------------------------------------------------- the seam

    def force(self, knob: str, rule: str = "forced",
              direction: str = "down", expr: Optional[str] = None,
              target: Optional[str] = None,
              value: Any = None) -> None:
        """Enqueue a proposal directly — the chaos/test seam (the
        matrix's forced-bad-proposal rollback scenario). The forced
        proposal still rides every guard except the allowlist: a FULL
        classification is refused, ``dry_run`` still records without
        touching, the oracle judges it, a bad one rolls back. ``expr``
        is the breach-clear oracle; an expr that never clears
        guarantees the rollback path."""
        self._forced.append({
            "rule": rule, "knob": knob, "direction": direction,
            "expr": expr or "latest(odigos_collector_health_status"
                            "[60s]) >= 0",
            "severity": "warning", "observed": None, "threshold": None,
            "collector": target or "", "forced": True, "value": value,
        })
        # the force() seam IS a chaos injection: record it as one so
        # the black box explains the rollback it is about to cause
        flight_recorder.trigger(
            "chaos_injection", fault="forced_proposal",
            detail=f"forced {direction} proposal on {knob} "
                   f"(rule {rule})", rule=rule)

    # ------------------------------------------------------------ tick

    def tick(self, now: Optional[float] = None) -> None:
        """One state-machine step: advance an in-flight actuation, or
        look for the next proposal. Reload/judgment failures are
        recorded, never raised (the plane-tick discipline)."""
        now = now if now is not None else self._clock()
        with self._lock:
            if not self.enabled:
                if self.current is not None:
                    # kill switch / disarm mid-flight: undo whatever is
                    # still UNJUDGED before going quiet — a half-
                    # actuated fleet must not outlive the actuator that
                    # made it. Mid-canary that is the canary itself;
                    # mid-promotion it is the in-flight STEP only (the
                    # canary and already-judged members keep the value
                    # their own windows proved good).
                    cur = self.current
                    if cur["phase"] == "canary":
                        self._rollback("actuator_disabled", now)
                    else:
                        step = cur["steps"][-1] if cur["steps"] else None
                        if step is not None \
                                and step.get("judge_until") is not None:
                            self._rollback_step(step,
                                                "actuator_disabled",
                                                now)
                        else:
                            self._finish("aborted_disarmed", now)
                self._set_state("idle")
                return
            if self.current is not None:
                # advance the recommender holds even mid-actuation: a
                # rule whose breach clears during a long canary must
                # lose its pending_since, or a post-actuation one-tick
                # blip would inherit the whole actuation span as "held"
                # and bypass the flap guard
                self._active_recs(now)
                self._advance(now)
                return
            # advance the recommender holds EVERY tick (pending ages
            # toward active even through a cooldown — the cooldown
            # gates actuation, not observation)
            recs = self._active_recs(now)
            if now < self._cooldown_until:
                self._set_state("cooldown")
                return
            self._set_state("idle")
            proposal = self._next_proposal(recs, now)
            if proposal is not None:
                self._start(proposal, now)

    # ----------------------------------------------------- proposal leg

    def _active_recs(self, now: float) -> list[dict]:
        try:
            recs = self.recommender.evaluate(
                max_step=self.config.max_step, now=now)
        except Exception:  # noqa: BLE001 — a broken store must not
            return []      # wedge the tick loop
        # drop refusal/proposal/block dedupe notes for rules no longer
        # active, so the next activation of the same rule is counted
        # (and retried) afresh
        active = {r["name"] for r in recs}
        self._noted = {n for n in self._noted if n[0] in active}
        self._proposed = {n for n in self._proposed if n[0] in active}
        self._blocked = {n for n in self._blocked if n[0] in active}
        return recs

    def _next_proposal(self, recs: list[dict],
                       now: float) -> Optional[dict]:
        if self._forced:
            cand = [self._forced.popleft()]
        else:
            rank = {"critical": 0, "warning": 1, "info": 2}
            cand = sorted(
                (r for r in recs
                 if (r["name"], r["knob"]) not in self._blocked),
                key=lambda r: (rank.get(r["severity"], 3), r["name"]))
        for rec in cand:
            proposal = self._ground(rec, now)
            if proposal is not None:
                return proposal
        return None

    def _refuse(self, rec: dict, reason: str, message: str,
                now: float, dedup: bool = True) -> None:
        """Count + record one refusal. ``dedup`` (the default) notes
        it once per rec activation — a standing breach must not spam
        the counter every tick; forced proposals pass ``dedup=False``
        because each ``force()`` call is an independent event."""
        key = (rec["rule"] if "rule" in rec else rec["name"],
               rec["knob"], reason)
        if dedup:
            if key in self._noted:
                return
            self._noted.add(key)
        meter.add(labeled_key(REFUSALS_METRIC, rule=key[0],
                              knob=rec["knob"], reason=reason))
        flight_recorder.record("actuator", event="refused",
                               rule=key[0], knob=rec["knob"],
                               reason=reason)
        self._record({
            "rule": key[0], "knob": rec["knob"], "outcome": "refused",
            "reason": reason, "message": message,
            "unix_ts": time.time()})

    def _ground(self, rec: dict, now: float) -> Optional[dict]:
        """Rec/forced entry -> fully grounded proposal, or None after
        counting the named refusal."""
        cfg = self.config
        forced = rec.get("forced", False)
        rule = rec.get("rule") or rec["name"]
        knob = rec["knob"]
        spec = KNOB_SPECS.get(knob)
        if spec is None:
            self._refuse(rec, "unknown_knob", f"{knob!r} has no "
                         f"KNOB_SPECS entry", now, dedup=not forced)
            return None
        if not spec.actuatable:
            self._refuse(rec, "not_actuatable", spec.refusal, now,
                         dedup=not forced)
            return None
        if cfg.knobs and knob not in cfg.knobs and not forced:
            self._refuse(rec, "not_allowlisted",
                         f"{knob} not in the actuator knob allowlist",
                         now)
            return None
        expr = rec.get("expr")
        if expr is None:
            rule_obj = self.recommender.rule(rule)
            expr = rule_obj.expr if rule_obj is not None else None
        if spec.kind == "controlplane":
            with self._reg_lock:
                scaler = self._replica_scaler
            if scaler is None:
                self._refuse(rec, "no_replica_scaler", spec.refusal,
                             now, dedup=not forced)
                return None
            return {"rule": rule, "knob": knob, "kind": "controlplane",
                    "direction": rec.get("direction", "up"),
                    "expr": expr, "severity": rec.get("severity", ""),
                    "target": "(replica-scaler)", "forced": forced}
        with self._reg_lock:
            collectors = dict(self._collectors)
        if not collectors:
            self._refuse(rec, "no_collectors",
                         "no collectors registered for actuation", now,
                         dedup=not forced)
            return None
        # canary pick: the collector the breaching series names, when
        # it is a registered target; else the first registered
        target = rec.get("collector") or ""
        if target not in collectors:
            target = sorted(collectors)[0]
        coll = collectors[target]
        sites = knob_sites(knob, coll.config)
        if not sites:
            self._refuse(rec, "no_site",
                         f"{knob} resolves to no edit site in "
                         f"{target}'s config", now, dedup=not forced)
            return None
        direction = rec.get("direction", "up")
        edits = []
        for path, cur in sites:
            if forced and rec.get("value") is not None:
                proposed: Any = rec["value"]
                proposed = min(max(float(proposed), spec.min_value),
                               spec.max_value)
                if spec.integer:
                    proposed = int(round(proposed))
            else:
                proposed = bounded_step(
                    knob, cur, rec.get("observed"),
                    rec.get("threshold"), direction, cfg.max_step)
            edits.append({"path": list(path), "from": cur,
                          "to": proposed})
        if all(e["from"] == e["to"] for e in edits):
            self._refuse(rec, "at_bound",
                         f"{knob} already at its "
                         f"{'upper' if direction == 'up' else 'lower'}"
                         f" bound", now, dedup=not forced)
            return None
        return {"rule": rule, "knob": knob, "kind": spec.kind,
                "direction": direction, "expr": expr,
                "severity": rec.get("severity", ""),
                "observed": rec.get("observed"),
                "threshold": rec.get("threshold"),
                "target": target, "edits": edits, "forced": forced}

    # ------------------------------------------------------- canary leg

    def _start(self, p: dict, now: float) -> None:
        key = (p["rule"], p["knob"])
        if key not in self._proposed:
            # once per rec activation: the counter means "grounded
            # proposals", not "plane ticks a standing one survived"
            self._proposed.add(key)
            meter.add(labeled_key(PROPOSALS_METRIC, rule=p["rule"],
                                  knob=p["knob"]))
            flight_recorder.record("actuator", event="proposed",
                                   rule=p["rule"], knob=p["knob"],
                                   direction=p.get("direction"),
                                   target=p.get("target"))
        if self.config.dry_run:
            # dry_run wins over EVERYTHING, forced proposals included:
            # an operator who armed look-don't-touch must get exactly
            # that, even from the chaos seam
            self._blocked.add(key)
            self._refuse({"rule": p["rule"], "knob": p["knob"]},
                         "dry_run",
                         f"dry_run: would canary {p['knob']} on "
                         f"{p['target']} "
                         f"({p.get('edits') or 'replica step'})", now,
                         dedup=not p.get("forced"))
            return
        record = dict(p)
        record["ts"] = {"proposed": time.time()}
        if p["kind"] == "controlplane":
            # the canary is ONE replica step in the PROPOSAL's
            # direction (a scale-down rule must not scale up)
            delta = 1 if p.get("direction", "up") == "up" else -1
            with self._reg_lock:
                scaler = self._replica_scaler
            new_count = scaler(delta) if scaler is not None else None
            if new_count is None:
                self._blocked.add(key)
                self._refuse({"rule": p["rule"], "knob": p["knob"]},
                             "at_bound",
                             f"replica scaler refused the {delta:+d} "
                             f"step (preset bound)", now,
                             dedup=not p.get("forced"))
                return
            record["replicas"] = new_count
            record["replica_delta"] = delta
            record["reload_mode"] = "replica_step"
        else:
            coll = self._collector(p["target"])
            if coll is None:
                return
            mode, err, prior = self._apply_guarded(coll, p["target"],
                                                   p["edits"])
            if mode == "full":
                self._blocked.add(key)
                self._refuse({"rule": p["rule"], "knob": p["knob"]},
                             "full_reload", err or "edit classifies as "
                             "a full rebuild", now,
                             dedup=not p.get("forced"))
                return
            if err is not None:
                # no blind per-tick retry of a failing reload: the
                # block lifts when the rec deactivates and re-activates
                self._blocked.add(key)
                self._refuse({"rule": p["rule"], "knob": p["knob"]},
                             "reload_error", err, now,
                             dedup=not p.get("forced"))
                return
            record["prior"] = prior
            record["reload_mode"] = mode
        record["phase"] = "canary"
        record["ts"]["canary"] = time.time()
        record["judge_until"] = now + self._judgment_window(p["expr"])
        record["baseline"] = self._baseline(p["target"])
        record["steps"] = []
        self.current = record
        meter.add(labeled_key(CANARIES_METRIC, rule=p["rule"],
                              knob=p["knob"]))
        flight_recorder.record("actuator", event="canary",
                               rule=p["rule"], knob=p["knob"],
                               target=p.get("target"),
                               mode=record.get("reload_mode"))
        self._set_state("canary")

    def _judgment_window(self, expr: Optional[str]) -> float:
        """At least the rule's own expr window: a rate() over [30s]
        mechanically cannot clear in a 5 s judgment — the pre-canary
        breach is still inside the window."""
        window = 0.0
        if expr:
            try:
                from ..selftelemetry.fleet import parse_expr

                window = parse_expr(expr)["window_s"]
            except ValueError:
                window = 0.0
        return max(self.config.judgment_window_s, window)

    def _collector(self, cid: str) -> Any:
        with self._reg_lock:
            return self._collectors.get(cid)

    def _apply_guarded(self, coll: Any, cid: str,
                       edits: list[dict]) -> tuple[str, Optional[str],
                                                   Optional[dict]]:
        """One copy of the never-FULL enforcement shared by the canary
        and promotion legs: snapshot the prior config, apply, and if
        the reload LANDED via the full-rebuild path (patch fallback /
        dirty graph) revert it immediately — that config must not stay
        live unjudged. Returns ``(mode, err, prior)``: mode ``full``
        always means "refuse" (err says whether anything had to be
        reverted); err with another mode is a failed reload; err None
        means the edit is live and judgeable."""
        prior = copy.deepcopy(coll.config)
        mode, err, applied = self._apply(coll, edits)
        if mode == "full" and applied:
            revert_err = self._revert({"collector": cid,
                                       "prior": prior})
            err = ("reload fell back to a full rebuild mid-apply; "
                   "reverted"
                   + (f" ({revert_err})" if revert_err else ""))
        return mode, err, prior

    def _apply(self, coll: Any,
               edits: list[dict]) -> tuple[str, Optional[str], bool]:
        """Diff-check then reload one collector. Returns
        ``(mode, error, applied)``: mode ``full`` with ``applied=False``
        = refused before touching anything; ``applied=True`` = the new
        config IS live on the collector (mode is the path the reload
        ACTUALLY took — a patch that fell back mid-apply or a
        dirty-graph rebuild reports ``full`` even though the differ
        promised incremental, and the caller must then revert: the
        never-FULL invariant is about what ran, not what was
        predicted). The full-path detector is the GRAPH OBJECT
        IDENTITY — ``Graph.patch`` mutates the live graph in place,
        while every full-rebuild path swaps in a new ``Graph`` — so
        the signal is scoped to THIS collector: a concurrent full
        reload of some other collector (a ConfigMap topology push on a
        fleet member) can never misclassify this canary."""
        from ..pipeline.configdiff import FULL, REPLACE, diff_configs

        old_cfg = coll.config
        new_cfg = copy.deepcopy(old_cfg)
        try:
            for e in edits:
                _set_path(new_cfg, tuple(e["path"]), e["to"])
        except (TypeError, AttributeError) as exc:
            # an unapplyable path (a truthy non-dict on the key chain,
            # e.g. fast_path: "on" — the graph runs it, the validator
            # only checks mappings) must become a named refusal, never
            # an exception that kills the plane-tick thread
            return ("full", f"unapplyable edit path: "
                            f"{type(exc).__name__}: {exc}", False)
        graph0 = getattr(coll, "graph", None)
        try:
            diff = diff_configs(old_cfg, new_cfg,
                                reg=getattr(coll, "_registry", None),
                                graph=graph0)
        except Exception as exc:  # noqa: BLE001 — undiffable = refuse
            return ("full", f"diff failed: {type(exc).__name__}: "
                            f"{exc}", False)
        if diff.mode == FULL:
            return "full", f"classified FULL: {diff.reasons}", False
        expected = "replace" if any(
            a.action == REPLACE for a in diff.actions) else "incremental"
        try:
            coll.reload(new_cfg)
        except Exception as exc:  # noqa: BLE001 — recorded, not raised
            # Collector.reload leaves the old graph + config serving on
            # every failure path: nothing applied
            return (expected, f"reload failed: {type(exc).__name__}: "
                              f"{exc}", False)
        if getattr(coll, "graph", None) is not graph0:
            # the reload LANDED but via the full-rebuild path (patch
            # fallback, or a dirty graph that bypassed the differ) —
            # the caller reverts; recording "incremental" here would
            # let the history claim a teardown never happened
            return "full", None, True
        return expected, None, True

    # ------------------------------------------------------ oracle leg

    @staticmethod
    def _bad_conditions(coll: Any) -> set[tuple]:
        """(component, reason) pairs currently not Healthy — SLOBurn,
        alert/<name>, Degraded/Unhealthy rows alike."""
        if coll is None or not hasattr(coll, "health_conditions"):
            return set()
        try:
            return {(c["component"], c["reason"])
                    for c in coll.health_conditions()
                    if c.get("status") != "Healthy"}
        except Exception:  # noqa: BLE001 — a dying collector judges bad
            return {("(rollup)", "EvaluationError")}

    def _baseline(self, target: str) -> list[list[str]]:
        """The excused set at canary start: whatever was already bad on
        the target — the breach being cured must not block its cure."""
        return sorted([list(t) for t in
                       self._bad_conditions(self._collector(target))])

    def _fleet_shared_bad(self, exclude: str) -> set[tuple]:
        """Bad conditions any OTHER registered collector currently
        shows — fleet-wide weather the canary is not blamed for."""
        with self._reg_lock:
            others = {cid: c for cid, c in self._collectors.items()
                      if cid != exclude}
        shared: set[tuple] = set()
        for coll in others.values():
            shared |= self._bad_conditions(coll)
        return shared

    def _new_bad(self, target: str, baseline: list) -> set[tuple]:
        allowed = {tuple(t) for t in baseline} \
            | self._fleet_shared_bad(target)
        return self._bad_conditions(self._collector(target)) - allowed

    def _confirmed_bad(self, holder: dict, new_bad: set[tuple],
                       now: float) -> set[tuple]:
        """Debounce the condition oracle: a bad condition must persist
        CONTINUOUSLY for a confirmation dwell before it kills a canary.
        A single-evaluation transient (a ConservationLeak from one
        in-flight batch caught between two ledger reads, a Degraded
        blip the next evaluation clears) must not roll back a good
        canary — while anything real (a firing alert, an SLO burn, a
        held degradation) trivially outlives the dwell."""
        confirm_s = min(1.0, max(0.25,
                                 0.25 * self.config.judgment_window_s))
        suspects = holder.setdefault("suspect", {})
        for b in list(suspects):
            if b not in new_bad:
                del suspects[b]  # cleared: continuity broken
        confirmed = {b for b in new_bad
                     if b in suspects and now - suspects[b] >= confirm_s}
        for b in new_bad:
            suspects.setdefault(b, now)
        return confirmed

    def _breaching(self, expr: Optional[str],
                   target: str = "") -> bool:
        """Is the breach-clear expression still breaching — scoped to
        the judged collector's ``{collector=}`` series when ``target``
        is given: the judgment is about whether the CANARY's breach
        cleared, and a fleet-global worst-series read would let an
        un-actuated member's still-breaching series veto a cured
        canary forever (the very situation fleet-wide promotion exists
        for). Falls back to the unscoped query when no series carries
        the collector label (single-process deployments publishing
        bare series judge globally — honest, just coarser)."""
        if not expr:
            return False
        from ..selftelemetry.fleet import _CMP, parse_expr, worst_series

        try:
            p = parse_expr(expr)
        except ValueError:
            return False
        store = self.recommender.store
        scoped = None
        if target:
            scoped = dict(p["labels"] or {})
            scoped["collector"] = target
            if not store.select(p["metric"], scoped):
                # no series carries this collector's label at all
                # (bare-series deployments): judge globally. The gate
                # is series EXISTENCE, not windowed answers — a scoped
                # series whose breach aged out of the window is a
                # CLEARED breach, not a reason to fall back to the
                # fleet-global view
                scoped = None
        values = store.series_values(p["metric"], p["fn"],
                                     p["window_s"],
                                     scoped or p["labels"] or None)
        _, value = worst_series(values, p["cmp"])
        return value is not None and _CMP[p["cmp"]](value,
                                                    p["threshold"])

    # ---------------------------------------------------- judging legs

    def _advance(self, now: float) -> None:
        cur = self.current
        if cur["phase"] == "canary":
            new_bad = set() if cur["kind"] == "controlplane" \
                else self._new_bad(cur["target"], cur["baseline"])
            confirmed = self._confirmed_bad(cur, new_bad, now)
            if confirmed:
                self._rollback("condition:" + ",".join(
                    f"{c}/{r}" for c, r in sorted(confirmed)), now)
                return
            if now < cur["judge_until"]:
                return
            if cur.get("suspect"):
                # a bad condition is mid-dwell at the window boundary:
                # defer the verdict until it confirms (rollback) or
                # clears (promote next tick) — closing the window now
                # would promote a canary that is actively degrading
                return
            if self._breaching(cur["expr"],
                               "" if cur["kind"] == "controlplane"
                               else cur["target"]):
                self._rollback("breach_persisted", now)
                return
            # canary judged good: roll the same judged value out
            cur["ts"]["judged"] = time.time()
            with self._reg_lock:
                queue = sorted(c for c in self._collectors
                               if c != cur["target"])
            if cur["kind"] == "controlplane" or not queue:
                self._finish("promoted", now)
                return
            cur["phase"] = "promoting"
            cur["promote_queue"] = queue
            self._set_state("promoting")
            self._promote_next(now)
            return
        # promoting: judge the in-flight step, then start the next
        step = cur["steps"][-1] if cur["steps"] else None
        if step is not None and step.get("judge_until") is not None:
            new_bad = self._new_bad(step["collector"], step["baseline"])
            confirmed = self._confirmed_bad(step, new_bad, now)
            if confirmed:
                self._rollback_step(step, "condition:" + ",".join(
                    f"{c}/{r}" for c, r in sorted(confirmed)), now)
                return
            if now < step["judge_until"]:
                return
            if step.get("suspect"):
                return  # mid-dwell at the boundary: defer (see canary)
            if self._breaching(cur["expr"], step["collector"]):
                self._rollback_step(step, "breach_persisted", now)
                return
            step["outcome"] = "promoted"
            step["judge_until"] = None
        self._promote_next(now)

    def _promote_next(self, now: float) -> None:
        cur = self.current
        queue = cur.get("promote_queue") or []
        while queue:
            cid = queue.pop(0)
            coll = self._collector(cid)
            if coll is None:
                continue  # churned away mid-rollout
            sites = knob_sites(cur["knob"], coll.config)
            if not sites:
                cur["steps"].append({"collector": cid,
                                     "outcome": "skipped_no_site"})
                continue
            # the judged value, re-clamped per-site (same bounds —
            # promotion rolls the VALUE the canary proved, it does not
            # re-step from each member's own current)
            judged = cur["edits"][0]["to"]
            edits = [{"path": list(path), "from": c, "to": judged}
                     for path, c in sites]
            mode, err, prior = self._apply_guarded(coll, cid, edits)
            if mode == "full":
                # same invariant as the canary leg: a step that landed
                # via the full path was reverted by the guard, is
                # recorded, and the rollout moves on — never "promoted"
                cur["steps"].append({"collector": cid,
                                     "outcome": "refused_full",
                                     "message": err or "classified "
                                                       "FULL"})
                continue
            if err is not None:
                cur["steps"].append({"collector": cid,
                                     "outcome": "error",
                                     "message": err})
                continue
            cur["steps"].append({
                "collector": cid, "prior": prior, "edits": edits,
                "reload_mode": mode,
                "baseline": self._baseline(cid),
                "judge_until": now + self._judgment_window(cur["expr"]),
            })
            return  # judge this step on subsequent ticks
        self._finish("promoted", now)

    # ----------------------------------------------------- resolutions

    def _revert(self, cur_or_step: dict) -> Optional[str]:
        cid = cur_or_step.get("collector") or cur_or_step.get("target")
        coll = self._collector(cid)
        prior = cur_or_step.get("prior")
        if coll is None or prior is None:
            return "target gone — nothing to revert"
        try:
            # the PR 13 revert semantics: even after a patch fallback
            # the dirty flag forces this reload to converge on prior
            coll.reload(prior)
            return None
        except Exception as exc:  # noqa: BLE001
            return f"revert failed: {type(exc).__name__}: {exc}"

    def _rollback(self, reason: str, now: float) -> None:
        cur = self.current
        if cur["kind"] == "controlplane":
            with self._reg_lock:
                scaler = self._replica_scaler
            if scaler is not None:
                # undo the canary's own step, whichever direction
                scaler(-cur.get("replica_delta", 1))
        else:
            err = self._revert(cur)
            if err:
                cur["revert_error"] = err
        cur["rollback_reason"] = reason
        meter.add(labeled_key(ROLLBACKS_METRIC, rule=cur["rule"],
                              knob=cur["knob"]))
        flight_recorder.trigger(
            "actuator_rollback",
            detail=f"canary {cur['knob']} on "
                   f"{cur.get('target', '')} rolled back: {reason}",
            rule=cur["rule"], expr=cur.get("expr"),
            knob=cur["knob"], reason=reason)
        self._finish("rolled_back", now)

    def _rollback_step(self, step: dict, reason: str,
                       now: float) -> None:
        """A promotion step failed its oracle: roll back THAT collector
        and abort the rollout — the canary and the already-judged steps
        keep the value their own windows proved."""
        err = self._revert(step)
        step["outcome"] = "rolled_back"
        step["rollback_reason"] = reason
        if err:
            step["revert_error"] = err
        meter.add(labeled_key(ROLLBACKS_METRIC,
                              rule=self.current["rule"],
                              knob=self.current["knob"]))
        flight_recorder.trigger(
            "actuator_rollback",
            detail=f"promotion step {step['collector']} rolled back: "
                   f"{reason}",
            rule=self.current["rule"], expr=self.current.get("expr"),
            knob=self.current["knob"], reason=reason)
        self.current["rollback_reason"] = f"step {step['collector']}: " \
                                          f"{reason}"
        self._finish("rolled_back_step", now)

    def _finish(self, outcome: str, now: float) -> None:
        cur = self.current
        cur["outcome"] = outcome
        cur["ts"]["finished"] = time.time()
        cur.pop("judge_until", None)
        cur.pop("promote_queue", None)
        # prior configs are working state, not history — a deep config
        # copy per entry would make the bounded ring unbounded in bytes
        cur.pop("prior", None)
        cur.pop("baseline", None)
        cur.pop("suspect", None)
        for step in cur.get("steps") or []:
            step.pop("prior", None)
            step.pop("baseline", None)
            step.pop("suspect", None)
            step.pop("judge_until", None)
        if outcome == "promoted":
            meter.add(labeled_key(PROMOTIONS_METRIC, rule=cur["rule"],
                                  knob=cur["knob"]))
        flight_recorder.record("actuator", event=outcome,
                               rule=cur["rule"], knob=cur["knob"],
                               reason=cur.get("rollback_reason"))
        self._record(cur)
        self.current = None
        self._cooldown_until = now + self.config.cooldown_s
        self._set_state("cooldown")

    def _record(self, entry: dict) -> None:
        with self._reg_lock:
            self.history.append(entry)

    def _set_state(self, state: str) -> None:
        self.state = state
        meter.set_gauge(STATE_METRIC, _STATE_SCORE.get(state, 0.0))

    # -------------------------------------------------------- surfaces

    def conditions(self) -> dict[str, tuple[str, str, str]]:
        """``actuator/<rule>`` rollup rows while an actuation is in
        flight (consumed by HealthRollup.evaluate like the failover
        rows). Informational — an in-flight canary is the system
        working, not degrading.

        Deliberately LOCK-FREE: a rollup evaluating under its own lock
        calls here, while a tick holding the actuator lock judges that
        same rollup through health_conditions() — taking the state lock
        here would be the ABBA half of a deadlock. One atomic reference
        read of ``current`` is race-safe enough for a display row."""
        cur = self.current
        if cur is None:
            return {}
        reason = "CanaryInFlight" if cur.get("phase") == "canary" \
            else "Promoting"
        # name the collector the loop is ACTUALLY touching right now:
        # mid-promotion that is the in-flight step's member, not the
        # canary it graduated from
        target = cur.get("target", "")
        if reason == "Promoting":
            steps = cur.get("steps") or []
            step = steps[-1] if steps else None
            if step is not None and step.get("judge_until") is not None:
                target = step.get("collector", target)
        edits = cur.get("edits")
        msg = (f"{cur['knob']} -> {edits[0]['to']} on {target}"
               if edits else f"{cur['knob']} on {target}")
        return {f"actuator/{cur['rule']}": ("Healthy", reason, msg)}

    def api_snapshot(self) -> dict[str, Any]:
        """The one JSON document every surface reads (``/api/actuator``,
        ``/debug/actuatorz``, diagnose ``actuator.json``)."""
        with self._lock:
            cur = None
            if self.current is not None:
                # DEEP copy under the lock: the tick thread keeps
                # mutating the live record (ts keys, step outcomes) —
                # a shallow copy would hand an HTTP/diagnose thread
                # dicts that change size mid-json.dumps
                cur = copy.deepcopy(
                    {k: v for k, v in self.current.items()
                     if k not in ("prior", "baseline", "suspect",
                                  "steps")})
                cur["steps"] = [
                    copy.deepcopy({k: v for k, v in s.items()
                                   if k not in ("prior", "baseline",
                                                "suspect")})
                    for s in self.current.get("steps") or []]
            state = self.state
        with self._reg_lock:
            history = list(self.history)
            collectors = sorted(self._collectors)
            cfg = self.config
            has_scaler = self._replica_scaler is not None
        return {
            "enabled": self.enabled,
            "kill_switch": os.environ.get(ACTUATOR_ENV, "1") == "0",
            "dry_run": cfg.dry_run,
            "state": state,
            "config": cfg.as_dict(),
            "collectors": collectors,
            "replica_scaler": has_scaler,
            "in_flight": cur,
            "history": history,
            # the refusal table: every knob with its actuatability and
            # the reason the actuator declines the rest
            "knobs": {k: {"path": s.path, "kind": s.kind,
                          "actuatable": s.actuatable,
                          "bounds": [s.min_value, s.max_value],
                          "refusal": s.refusal}
                      for k, s in sorted(KNOB_SPECS.items())},
        }

    def reset(self) -> None:
        """Test isolation (the fleet_plane.reset contract)."""
        with self._lock:
            self.current = None
            self.state = "idle"
            self._cooldown_until = 0.0
            self._noted.clear()
            self._proposed.clear()
            self._blocked.clear()
            self._forced.clear()
        with self._reg_lock:
            self.config = ActuatorConfig()
            self._owner = None
            self._collectors.clear()
            self._replica_scaler = None
            self.history.clear()


fleet_actuator = FleetActuator()


def actuator_conditions() -> dict[str, tuple[str, str, str]]:
    """Lazy-import seam for HealthRollup.evaluate (the
    failover_conditions pattern)."""
    return fleet_actuator.conditions()
