"""CLI commands — the cobra-command surface of the reference
(cli/cmd/root.go:17: install / uninstall / ui / describe / diagnose /
sources / profile ...), over a persisted local control plane (state.py).

Every mutating command is level-triggered: load state (controllers
re-register and resync), mutate resources, reconcile, save — a controller
restart per invocation, which is exactly how the reference CLI relates to
its cluster (SURVEY.md §3.1: the CLI applies resources; controllers do the
work).
"""

from __future__ import annotations

import argparse
import sys
from typing import Optional

from .. import __version__
from ..api.resources import (
    DestinationResource, ObjectMeta, Source, WorkloadKind, WorkloadRef)
from ..controlplane.cluster import Container
from ..controlplane.scheduler import ODIGOS_NAMESPACE
from .state import (
    CliState, create_state, default_state_dir, delete_state, load_state,
    state_exists)


def _err(msg: str) -> int:
    print(f"error: {msg}", file=sys.stderr)
    return 1


def _load(args) -> CliState:
    return load_state(args.state_dir)


def _workload_ref(namespace: str, name: str, kind: str) -> WorkloadRef:
    return WorkloadRef(namespace, WorkloadKind.parse(kind), name)


# ---------------------------------------------------------------- commands


def cmd_install(args) -> int:
    if state_exists(args.state_dir):
        return _err(f"already installed at "
                    f"{args.state_dir or default_state_dir()} "
                    "(run uninstall first)")
    from ..config.model import Configuration, Tier
    from ..config.profiles import resolve_profiles

    config = Configuration(profiles=list(args.profile or []))
    tier = Tier(args.tier)
    if tier != Tier.COMMUNITY:
        # paid tiers require a validated entitlement token
        # (odigosauth/odigosauth.go:69 ValidateToken at install)
        from ..utils.auth import TokenError, validate_tier_claim

        try:
            validate_tier_claim(getattr(args, "onprem_token", None) or "",
                                tier.value)
        except TokenError as e:
            return _err(f"tier {tier.value!r} requires a valid pro token "
                        f"(--onprem-token): {e}")
    _, unknown = resolve_profiles(config.profiles, tier)
    if unknown:
        return _err(f"unknown or tier-gated profiles: {unknown}")
    # sense the environment before rendering anything (the reference's
    # cli/pkg/autodetect step) and adapt the install to it
    from .autodetect import detect_platform

    platform = detect_platform(cluster_name=config.cluster_name)
    config.extra["platform"] = platform
    if platform["kind"] == "openshift":
        config.extra["openshift_enabled"] = True
    print("platform: " + ", ".join(
        f"{k}={v}" for k, v in sorted(platform.items())))
    # policy-validate the rendered manifests (tests/gatekeeper role):
    # an install that violates its own constraint set must not proceed
    from ..controlplane.gatekeeper import policy_violations

    violations = policy_violations(config, platform, tier.value)
    if violations:
        return _err("install manifests violate policy:\n  "
                    + "\n  ".join(str(v) for v in violations))
    state = create_state(path=args.state_dir, nodes=args.nodes,
                         config=config, tier=tier.value)
    state.save()
    print(f"installed odigos-tpu (nodes={args.nodes}, tier={tier.value}, "
          f"profiles={config.profiles or 'none'}) "
          f"at {state.path}")
    return 0


def cmd_manifests(args) -> int:
    """Render the component manifests for review (the reference's
    helm-template/resourcemanager dry-run role)."""
    import json as _json

    state = _load(args)
    from ..controlplane.gatekeeper import policy_violations
    from ..controlplane.manifests import render_manifests

    platform = (state.config.extra or {}).get("platform") or {}
    print(_json.dumps(render_manifests(state.config, platform,
                                       state.tier), indent=1))
    violations = policy_violations(state.config, platform, state.tier)
    for v in violations:
        print(f"policy violation: {v}", file=sys.stderr)
    return 1 if violations else 0


def cmd_upgrade(args) -> int:
    """Upgrade an existing install in place (the reference's
    install-or-upgrade path, cli/cmd/helm-install.go:21): reload state
    under the current code, revalidate profiles against the installed
    tier, re-render everything (level-triggered controllers make the
    'controller restart' the upgrade), persist."""
    from ..config.model import Tier
    from ..config.profiles import resolve_profiles

    state = _load(args)
    _, unknown = resolve_profiles(state.config.profiles, Tier(state.tier))
    if unknown:
        return _err(f"installed profiles no longer resolve: {unknown}")
    state.scheduler.apply_authored(state.config)
    state.reconcile()
    state.save()
    print(f"upgraded to odigos-tpu {__version__} "
          f"(tier={state.tier}, profiles={state.config.profiles or 'none'})")
    return 0


def cmd_preflight(args) -> int:
    """Installation health checks (cli/pkg/preflight/checks.go: is
    installed, are components ready). Hard failures exit 1; the TPU
    probe is advisory (the pipeline runs without a chip)."""
    from ..controlplane.autoscaler import GATEWAY_CONFIG_NAME
    from ..controlplane.scheduler import (
        EFFECTIVE_CONFIG_NAME, GATEWAY_GROUP_NAME)

    failures = 0

    def check(desc, fn, hard=True):
        nonlocal failures
        try:
            detail = fn()
            print(f"  ok  {desc}" + (f" ({detail})" if detail else ""))
            return True
        except Exception as e:  # noqa: BLE001 — each check reports
            mark = "FAIL" if hard else "warn"
            print(f"{mark:>4}  {desc}: {e}")
            if hard:
                failures += 1
            return False

    def installed():
        if not state_exists(args.state_dir):
            raise RuntimeError("no installation (run `install` first)")

    print("preflight:")
    check("installation exists", installed)
    if failures:
        return 1
    # the load itself is a check: a corrupt/version-mismatched state file
    # must print FAIL, not a traceback
    box: dict = {}

    def load():
        box["state"] = _load(args)
        return (f"{len(box['state'].cluster.nodes)} nodes, "
                f"tier {box['state'].tier}")

    if not check("state loads and reconciles", load):
        return 1
    state = box["state"]
    check("effective config rendered", lambda: _must(
        state.store.get("ConfigMap", ODIGOS_NAMESPACE,
                        EFFECTIVE_CONFIG_NAME), "missing effective config"))
    check("gateway config rendered", lambda: _must(
        state.store.get("ConfigMap", ODIGOS_NAMESPACE,
                        GATEWAY_CONFIG_NAME), "missing gateway config"))
    check("collectors group present", lambda: _must(
        state.store.get("CollectorsGroup", ODIGOS_NAMESPACE,
                        GATEWAY_GROUP_NAME), "missing gateway group"))

    def ring():
        from ..transport import SpanRing

        r = SpanRing.create(1 << 14)
        r.close()
        return "native C++ ring"

    check("shared-memory span ring", ring)

    def policy():
        from ..controlplane.gatekeeper import policy_violations

        platform = (state.config.extra or {}).get("platform") or {}
        violations = policy_violations(state.config, platform, state.tier)
        if violations:
            raise RuntimeError("; ".join(str(v) for v in violations))
        return "manifests clean"

    check("manifests pass constraint policy", policy)

    def tpu():
        import subprocess
        import sys as _sys

        # platform must actually be an accelerator: a CPU-only jax would
        # otherwise produce a false 'ok'
        probe = ("import jax, numpy as np; dev = jax.devices()[0]; "
                 "assert dev.platform != 'cpu', dev.platform; "
                 "np.asarray(jax.jit(lambda x: x + 1)"
                 "(jax.numpy.ones((8, 8)))); print(dev)")
        r = subprocess.run([_sys.executable, "-c", probe], timeout=30,
                           capture_output=True, text=True)
        if r.returncode != 0:
            # the probe's own last line says which: a CPU-only platform,
            # or a chip that another process on this host already holds
            reason = (r.stderr.strip().splitlines() or ["probe failed"])[-1]
            raise RuntimeError(f"no TPU backend ({reason[:200]})")
        return r.stdout.strip().splitlines()[-1]

    if not getattr(args, "skip_device_probe", False):
        check("TPU backend available", tpu, hard=False)
    return 1 if failures else 0


def _must(value, msg):
    if value is None:
        raise RuntimeError(msg)
    return ""


def cmd_uninstall(args) -> int:
    if not args.yes:
        return _err("refusing to uninstall without --yes")
    if delete_state(args.state_dir):
        print("uninstalled")
        return 0
    return _err("nothing installed")


def cmd_status(args) -> int:
    from .describe import describe_install

    print(describe_install(_load(args)))
    return 0


def cmd_version(args) -> int:
    print(f"odigos-tpu {__version__}")
    return 0


# ------------------------------------------------------------------ sources


def cmd_sources(args) -> int:
    state = _load(args)
    if args.action == "list":
        srcs = state.store.list("Source", namespace=args.namespace or None)
        for s in srcs:
            kind = ("namespace" if s.is_namespace_source
                    else s.workload.kind.value)
            mode = "disable" if s.disable_instrumentation else "enable"
            print(f"{s.namespace}/{s.name}: {kind} {s.workload.name} "
                  f"[{mode}]"
                  + (f" streams={s.data_stream_names}"
                     if s.data_stream_names else ""))
        if not srcs:
            print("(no sources)")
        return 0
    if args.action == "add":
        ref = _workload_ref(args.namespace, args.name, args.kind)
        state.store.apply(Source(
            meta=ObjectMeta(name=f"src-{args.name}",
                            namespace=args.namespace),
            workload=ref,
            disable_instrumentation=args.disable,
            otel_service_name=args.service_name or "",
            data_stream_names=list(args.stream or [])))
        state.reconcile()
        state.save()
        print(f"source src-{args.name} applied for "
              f"{args.namespace}/{ref.kind.value}/{args.name}")
        return 0
    if args.action == "remove":
        if state.store.delete("Source", args.namespace, f"src-{args.name}"):
            state.reconcile()
            state.save()
            print("source removed (workload will be un-instrumented)")
            return 0
        return _err(f"no source src-{args.name} in {args.namespace}")
    return _err(f"unknown sources action {args.action}")


# -------------------------------------------------------------- workloads


def cmd_workloads(args) -> int:
    state = _load(args)
    if args.action == "list":
        for w in state.cluster.workloads.values():
            pods = state.cluster.pods_of(w.ref)
            phases = ", ".join(f"{p.name}[{p.phase.value}]" for p in pods)
            print(f"{w.ref.namespace}/{w.ref.kind.value}/{w.ref.name}: "
                  f"replicas={w.replicas} {phases}")
        if not state.cluster.workloads:
            print("(no workloads)")
        return 0
    if args.action == "add":
        state.cluster.add_workload(
            args.namespace, args.name,
            [Container("main", language=args.language,
                       runtime_version=args.runtime_version)],
            kind=WorkloadKind.parse(args.kind),
            replicas=args.replicas)
        state.reconcile()
        state.save()
        print(f"workload {args.namespace}/{args.name} added "
              f"({args.language}, replicas={args.replicas})")
        return 0
    if args.action == "remove":
        ref = _workload_ref(args.namespace, args.name, args.kind)
        state.cluster.remove_workload(ref)
        state.reconcile()
        state.save()
        print("workload removed")
        return 0
    return _err(f"unknown workloads action {args.action}")


# ----------------------------------------------------------- destinations


def cmd_destinations(args) -> int:
    from ..components.api import Signal
    from ..destinations import SPECS, get_spec, validate_destination

    if args.action == "types":
        for spec in sorted(SPECS.values(), key=lambda s: s.dest_type):
            sigs = ",".join(s.value for s in Signal if spec.supports(s))
            print(f"{spec.dest_type}: {spec.display_name} [{sigs}]")
        return 0

    state = _load(args)
    if args.action == "list":
        dests = state.store.list("DestinationResource")
        for d in dests:
            print(f"{d.name}: {d.dest_type} signals={d.signals}"
                  + (f" streams={d.data_stream_names}"
                     if d.data_stream_names else ""))
        if not dests:
            print("(no destinations)")
        return 0
    if args.action == "add":
        try:
            spec = get_spec(args.type)
        except KeyError:
            return _err(f"unknown destination type {args.type!r} "
                        "(see `destinations types`)")
        config = {}
        for kv in args.set or []:
            if "=" not in kv:
                return _err(f"--set expects key=value, got {kv!r}")
            k, v = kv.split("=", 1)
            config[k] = v
        from ..destinations import Destination

        dest = Destination(
            id=args.name, dest_type=args.type,
            signals=[Signal(s) for s in (args.signal or ["traces"])],
            config=config,
            data_stream_names=list(args.stream or []))
        problems = validate_destination(dest)
        if problems:
            return _err("; ".join(problems))
        # secret fields never enter state.json (it travels in diagnose
        # bundles); they land in the 0600 secrets file + collector env —
        # the Secret analog, matching the UI wizard path
        secret_names = [f.name for f in spec.fields
                        if f.secret and f.name in config]
        # secret env names are type-scoped: a second destination of the
        # same type shares them, so a differing value silently rebinds the
        # first destination's credentials — surface that
        for n in secret_names:
            old = state.secrets.get(n)
            if old is not None and old != config[n]:
                others = [d.meta.name for d in
                          state.store.list("DestinationResource")
                          if d.meta.name != args.name
                          and any(f.secret and f.name == n for f in
                                  (SPECS[d.dest_type].fields
                                   if d.dest_type in SPECS else ()))]
                if others:
                    print(f"warning: {n} is shared with destination(s) "
                          f"{', '.join(others)}; the new value replaces "
                          "theirs", file=sys.stderr)
        state.set_secrets({n: config.pop(n) for n in secret_names})
        state.store.apply(DestinationResource(
            meta=ObjectMeta(name=args.name, namespace=ODIGOS_NAMESPACE),
            dest_type=args.type,
            signals=[s.value for s in dest.signals],
            config=config,
            secret_ref=(f"odigos-{args.name}-secret"
                        if secret_names else ""),
            data_stream_names=list(dest.data_stream_names)))
        state.reconcile()
        state.save()
        print(f"destination {args.name} ({args.type}) applied")
        return 0
    if args.action == "remove":
        existing = state.store.get("DestinationResource", ODIGOS_NAMESPACE,
                                   args.name)
        if existing is not None and state.store.delete(
                "DestinationResource", ODIGOS_NAMESPACE, args.name):
            # revoke every stored secret no longer referenced by any
            # surviving destination (env names are type-scoped, so a
            # same-type survivor — even one added without re-supplying
            # the credential — keeps the var; round-4 advisor, medium)
            from ..destinations.registry import (
                referenced_secret_env_names)

            keep = referenced_secret_env_names(
                state.store.list("DestinationResource"))
            state.drop_secrets([n for n in list(state.secrets)
                                if n not in keep])
            state.reconcile()
            state.save()
            print("destination removed")
            return 0
        return _err(f"no destination {args.name}")
    return _err(f"unknown destinations action {args.action}")


def cmd_ui(args) -> int:
    """Serve the operator dashboard over the installed state (the
    reference's `odigos ui` port-forward/serve, cli/cmd/ui.go)."""
    import os

    state = _load(args)
    from ..frontend import FrontendServer

    auth = (getattr(args, "auth_token", None)
            or os.environ.get("ODIGOS_UI_TOKEN") or None)
    fe = FrontendServer(state.store, cluster=state.cluster,
                        host=args.address, port=args.port,
                        auth_token=auth).start()
    print(f"dashboard: {fe.url} (ctrl-c to stop)", flush=True)
    if getattr(args, "once", False):  # tests: bind, report, exit
        fe.shutdown()
        return 0
    import signal as _signal
    import threading

    stop = threading.Event()
    _signal.signal(_signal.SIGINT, lambda *a: stop.set())
    _signal.signal(_signal.SIGTERM, lambda *a: stop.set())
    stop.wait()
    fe.shutdown()
    return 0


def cmd_pro(args) -> int:
    """Update the entitlement token of an existing install (the
    reference's `odigos pro --onprem-token`, cli/cmd/pro.go
    UpdateOdigosToken)."""
    from ..config.model import Tier
    from ..utils.auth import TokenError, validate_token_audience

    state = _load(args)
    try:
        _, aud = validate_token_audience(args.onprem_token or "")
        tier = Tier(aud)
    except (TokenError, ValueError) as e:
        return _err(f"invalid pro token: {e}")
    state.tier = tier.value
    state.scheduler.tier = tier
    state.instrumentor.distro_provider.tier = tier.value
    state.scheduler.apply_authored(state.config)
    state.reconcile()
    state.save()
    print(f"tier updated to {tier.value}")
    return 0


# -------------------------------------------------------------- profiles


def cmd_profile(args) -> int:
    from ..config.model import Tier
    from ..config.profiles import available_profiles_for_tier

    if args.action == "list":
        state = _load(args) if state_exists(args.state_dir) else None
        active = set(state.config.profiles) if state else set()
        for p in available_profiles_for_tier(Tier(args.tier)):
            mark = "*" if p.name in active else " "
            print(f"{mark} {p.name}: {p.short_description}")
        return 0
    state = _load(args)
    if args.action == "add":
        if args.name in state.config.profiles:
            return _err(f"profile {args.name} already active")
        from ..config.profiles import resolve_profiles

        # the tier validated at install time gates profile-add — a flag on
        # this command is not an entitlement (odigosauth enforcement)
        _, unknown = resolve_profiles([args.name], Tier(state.tier))
        if unknown:
            return _err(f"unknown or tier-gated profile {args.name!r} "
                        f"(installed tier: {state.tier})")
        state.config.profiles.append(args.name)
        state.scheduler.apply_authored(state.config)
        state.reconcile()
        state.save()
        print(f"profile {args.name} added")
        return 0
    if args.action == "remove":
        if args.name not in state.config.profiles:
            return _err(f"profile {args.name} not active")
        state.config.profiles.remove(args.name)
        state.scheduler.apply_authored(state.config)
        state.reconcile()
        state.save()
        print(f"profile {args.name} removed")
        return 0
    return _err(f"unknown profile action {args.action}")


# ----------------------------------------------------- describe / diagnose


def cmd_describe(args) -> int:
    from .describe import describe_install, describe_workload

    state = _load(args)
    if args.target == "odigos":
        print(describe_install(state))
        return 0
    print(describe_workload(state, args.namespace, args.kind, args.name))
    return 0


def cmd_diagnose(args) -> int:
    from .diagnose import collect_bundle

    path = collect_bundle(_load(args), args.output, redact=args.redact)
    print(f"bundle written: {path}")
    return 0


def cmd_actions(args) -> int:
    """Telemetry-policy actions (api/actions/v1alpha1; compiled into
    collector processors by the autoscaler)."""
    import json as _json

    from ..api.resources import Action, ActionKind

    state = _load(args)
    if args.action == "list":
        actions = state.store.list("Action")
        for a in actions:
            flag = " (disabled)" if a.disabled else ""
            print(f"{a.meta.name}: {a.action_kind.value}"
                  f" signals={a.signals or 'all'}{flag}")
        if not actions:
            print("(no actions)")
        return 0
    if args.action == "add":
        try:
            kind = ActionKind(args.kind)
        except ValueError:
            return _err(f"unknown action kind {args.kind!r} "
                        f"(known: {[k.value for k in ActionKind]})")
        try:
            details = _json.loads(args.details or "{}")
        except ValueError as e:
            return _err(f"--details must be JSON: {e}")
        state.store.apply(Action(
            meta=ObjectMeta(name=args.name, namespace=ODIGOS_NAMESPACE),
            action_kind=kind, signals=list(args.signal or []),
            details=details))
        state.reconcile()
        state.save()
        print(f"action {args.name} ({kind.value}) applied")
        return 0
    if args.action == "remove":
        if state.store.delete("Action", ODIGOS_NAMESPACE, args.name):
            state.reconcile()
            state.save()
            print("action removed")
            return 0
        return _err(f"no action {args.name}")
    return _err(f"unknown actions action {args.action}")


def cmd_rules(args) -> int:
    """Instrumentation rules (instrumentationrule_type.go; scoped SDK
    behavior consumed by the instrumentor)."""
    import json as _json

    from ..api.resources import InstrumentationRule, RuleKind

    state = _load(args)
    if args.action == "list":
        rules = state.store.list("InstrumentationRule")
        for r in rules:
            flag = " (disabled)" if r.disabled else ""
            scope = (f" workloads={len(r.workloads)}" if r.workloads
                     else " all-workloads")
            print(f"{r.meta.name}: {r.rule_kind.value}{scope}"
                  f" languages={r.languages or 'all'}{flag}")
        if not rules:
            print("(no rules)")
        return 0
    if args.action == "add":
        try:
            kind = RuleKind(args.kind)
        except ValueError:
            return _err(f"unknown rule kind {args.kind!r} "
                        f"(known: {[k.value for k in RuleKind]})")
        try:
            details = _json.loads(args.details or "{}")
        except ValueError as e:
            return _err(f"--details must be JSON: {e}")
        state.store.apply(InstrumentationRule(
            meta=ObjectMeta(name=args.name, namespace=ODIGOS_NAMESPACE),
            rule_kind=kind, languages=list(args.language or []),
            details=details))
        state.reconcile()
        state.save()
        print(f"rule {args.name} ({kind.value}) applied")
        return 0
    if args.action == "remove":
        if state.store.delete("InstrumentationRule", ODIGOS_NAMESPACE,
                              args.name):
            state.reconcile()
            state.save()
            print("rule removed")
            return 0
        return _err(f"no rule {args.name}")
    return _err(f"unknown rules action {args.action}")


# ----------------------------------------------------------- central stack

CENTRAL_NAMESPACE = "central-odigos"
# the enterprise central stack (cli/cmd/resources/centralodigos/
# {centralbackend,centralproxy,centralui,keycloak,redis}.go): component
# name -> (container image role, replicas)
CENTRAL_COMPONENTS = (
    ("central-backend", 1),
    ("central-proxy", 1),
    ("central-ui", 1),
    ("keycloak", 1),
    ("redis", 1),
)


def cmd_central(args) -> int:
    """`central install|uninstall|status` — the enterprise central stack
    (reference: cli/cmd/pro-dep.go centralCmdDep + centralodigos resource
    managers). Installing requires an onprem entitlement; components are
    scheduled as workloads in the cluster so status/describe see them."""
    from ..controlplane.cluster import Container
    from ..api.resources import WorkloadRef, WorkloadKind

    state = _load(args)

    def refs():
        return [WorkloadRef(CENTRAL_NAMESPACE, WorkloadKind.DEPLOYMENT, n)
                for n, _ in CENTRAL_COMPONENTS]

    installed = [r for r in refs()
                 if state.cluster.get_workload(r) is not None]

    if args.action == "status":
        if not installed:
            print("central stack: not installed")
            return 0
        for ref in installed:
            pods = state.cluster.pods_of(ref)
            phases = ",".join(p.phase.value for p in pods) or "no pods"
            print(f"{ref.name}: {phases}")
        return 0

    if args.action == "uninstall":
        if not installed:
            return _err("central stack is not installed")
        for ref in refs():
            state.cluster.remove_workload(ref)
        state.save()
        print(f"central stack removed from {CENTRAL_NAMESPACE}")
        return 0

    # install: enterprise entitlement required (pro-dep.go onprem-token)
    from ..utils.auth import TokenError, validate_tier_claim

    try:
        validate_tier_claim(getattr(args, "onprem_token", None) or "",
                            "onprem")
    except TokenError as e:
        return _err(f"central stack requires a valid onprem token "
                    f"(--onprem-token): {e}")
    if installed:
        return _err("central stack already installed")
    for name, replicas in CENTRAL_COMPONENTS:
        state.cluster.add_workload(
            CENTRAL_NAMESPACE, name,
            [Container(name, language="central")], replicas=replicas)
    state.save()
    print(f"central stack installed in {CENTRAL_NAMESPACE} "
          f"({', '.join(n for n, _ in CENTRAL_COMPONENTS)})")
    return 0


# ---------------------------------------------------------------- parser


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="odigos-tpu",
        description="TPU-native distributed-tracing platform CLI")
    ap.add_argument("--state-dir", default=None,
                    help="state directory (default ~/.odigos-tpu or "
                         "$ODIGOS_TPU_STATE)")
    sub = ap.add_subparsers(dest="command", required=True)

    p = sub.add_parser("install", help="install the control plane")
    p.add_argument("--nodes", type=int, default=1)
    p.add_argument("--profile", action="append")
    p.add_argument("--tier", default="community",
                   choices=["community", "cloud", "onprem"])
    p.add_argument("--onprem-token", default=None,
                   help="pro entitlement token (required for paid tiers)")
    p.set_defaults(fn=cmd_install)

    p = sub.add_parser("upgrade", help="upgrade an existing installation")
    p.set_defaults(fn=cmd_upgrade)

    p = sub.add_parser("manifests",
                       help="render component manifests + policy check")
    p.set_defaults(fn=cmd_manifests)

    p = sub.add_parser("preflight", help="installation health checks")
    p.add_argument("--skip-device-probe", action="store_true",
                   help="skip the (advisory, up to 30s) TPU probe")
    p.set_defaults(fn=cmd_preflight)

    p = sub.add_parser("uninstall", help="delete the installation")
    p.add_argument("--yes", action="store_true")
    p.set_defaults(fn=cmd_uninstall)

    p = sub.add_parser("status", help="installation summary")
    p.set_defaults(fn=cmd_status)

    p = sub.add_parser("actions", help="manage telemetry-policy actions")
    p.add_argument("action", choices=["list", "add", "remove"])
    p.add_argument("--name")
    p.add_argument("--kind")
    p.add_argument("--signal", action="append")
    p.add_argument("--details", help="JSON details object")
    p.set_defaults(fn=cmd_actions)

    p = sub.add_parser("rules", help="manage instrumentation rules")
    p.add_argument("action", choices=["list", "add", "remove"])
    p.add_argument("--name")
    p.add_argument("--kind")
    p.add_argument("--language", action="append")
    p.add_argument("--details", help="JSON details object")
    p.set_defaults(fn=cmd_rules)

    p = sub.add_parser("central",
                       help="manage the enterprise central stack")
    p.add_argument("action", choices=["install", "uninstall", "status"])
    p.add_argument("--onprem-token", default=None,
                   help="enterprise entitlement (required for install)")
    p.set_defaults(fn=cmd_central)

    p = sub.add_parser("version")
    p.set_defaults(fn=cmd_version)

    p = sub.add_parser("sources", help="manage instrumentation sources")
    p.add_argument("action", choices=["list", "add", "remove"])
    p.add_argument("--namespace", default="default")
    p.add_argument("--name")
    p.add_argument("--kind", default="deployment")
    p.add_argument("--service-name")
    p.add_argument("--stream", action="append")
    p.add_argument("--disable", action="store_true",
                   help="exclude instead of include")
    p.set_defaults(fn=cmd_sources)

    p = sub.add_parser("workloads", help="manage simulated workloads")
    p.add_argument("action", choices=["list", "add", "remove"])
    p.add_argument("--namespace", default="default")
    p.add_argument("--name")
    p.add_argument("--kind", default="deployment")
    p.add_argument("--language", default="python")
    p.add_argument("--runtime-version", default="")
    p.add_argument("--replicas", type=int, default=1)
    p.set_defaults(fn=cmd_workloads)

    p = sub.add_parser("destinations", help="manage export destinations")
    p.add_argument("action", choices=["list", "add", "remove", "types"])
    p.add_argument("--name")
    p.add_argument("--type")
    p.add_argument("--signal", action="append",
                   choices=["traces", "metrics", "logs"])
    p.add_argument("--set", action="append", metavar="KEY=VALUE")
    p.add_argument("--stream", action="append")
    p.set_defaults(fn=cmd_destinations)

    p = sub.add_parser("ui", help="serve the operator dashboard")
    p.add_argument("--address", default="127.0.0.1")
    p.add_argument("--port", type=int, default=3000)
    p.add_argument("--auth-token", default=None,
                   help="require this bearer token (or a valid pro JWT) "
                        "for mutations and the event stream; default: "
                        "$ODIGOS_UI_TOKEN, open when unset")
    p.add_argument("--once", action="store_true",
                   help="bind, print the URL, exit (smoke test)")
    p.set_defaults(fn=cmd_ui)

    p = sub.add_parser("pro", help="update the entitlement token")
    p.add_argument("--onprem-token", required=True)
    p.set_defaults(fn=cmd_pro)

    p = sub.add_parser("profile", help="manage config profiles")
    p.add_argument("action", choices=["list", "add", "remove"])
    p.add_argument("--name")
    p.add_argument("--tier", default="community",
                   choices=["community", "cloud", "onprem"])
    p.set_defaults(fn=cmd_profile)

    p = sub.add_parser("describe",
                       help="explain one workload's instrumentation chain")
    p.add_argument("target", choices=["odigos", "workload"])
    p.add_argument("--namespace", default="default")
    p.add_argument("--kind", default="deployment")
    p.add_argument("--name")
    p.set_defaults(fn=cmd_describe)

    p = sub.add_parser("diagnose", help="collect a support bundle")
    p.add_argument("-o", "--output", default=None)
    p.add_argument("--redact", action="store_true",
                   help="strip destination-secret values from every "
                        "archived file (span attributes, metric labels, "
                        "resource dumps)")
    p.set_defaults(fn=cmd_diagnose)

    return ap


def main(argv: Optional[list[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    needs_name = {
        (cmd_sources, "add"), (cmd_sources, "remove"),
        (cmd_workloads, "add"), (cmd_workloads, "remove"),
        (cmd_destinations, "add"), (cmd_destinations, "remove"),
        (cmd_profile, "add"), (cmd_profile, "remove"),
    }
    action = getattr(args, "action", None)
    if (args.fn, action) in needs_name and not args.name:
        return _err(f"--name is required for `{args.command} {action}`")
    if args.fn is cmd_destinations and action == "add" and not args.type:
        return _err("--type is required for `destinations add`")
    if (args.fn is cmd_describe and args.target == "workload"
            and not args.name):
        return _err("--name is required for `describe workload`")
    try:
        return args.fn(args)
    except (FileNotFoundError, ValueError, RuntimeError) as e:
        # RuntimeError covers state-version mismatch: an actionable
        # message, never a raw traceback
        return _err(str(e))


if __name__ == "__main__":
    sys.exit(main())
