"""Chaos injector registry — paired inject/clear fault injections.

The chaos-mesh network-fault / mockdestination-misbehavior analog
(SURVEY.md §4 item 6, §5.3), grown from two helpers into the scenario
matrix's injector surface (ISSUE 13). Conventions, enforced by the
package-hygiene lint (``TestChaosInjectorHygiene``):

* every ``inject_X(env, ...)`` has a paired ``clear_X(env)``, and
  **clear is always idempotent** — a failed scenario's ``finally_steps``
  may clear a fault that was never injected (or clear twice) without
  raising, so no chaos test can ever leak a fault into the next one;
* every injector appears in at least one scenario of
  ``tests/test_chaos_matrix.py`` — an injector nobody exercises is a
  fault mode nobody has proven the pipeline degrades through;
* the :data:`INJECTORS` registry (built by introspection at import) is
  the machine-readable pairing table the hygiene lint checks.

Restoration state (patched methods/consumers) rides on the environment
(``env._chaos_restore``), never in module globals — two concurrent
environments must not restore each other's components.
"""

from __future__ import annotations

import socket
import struct
import threading
import time
from dataclasses import replace
from typing import Any, Callable, Optional

import numpy as np

from .environment import E2EEnvironment

_RESTORE_ATTR = "_chaos_restore"


def _flight(fault: str, detail: str) -> None:
    """Every successful injection freezes exactly one incident naming
    its fault — the chaos matrix's fifth oracle reads these back (and
    the recorder's per-(trigger, fault) cooldown keeps a re-injection
    inside one scenario from minting a second)."""
    from ..selftelemetry.flightrecorder import flight_recorder

    flight_recorder.trigger("chaos_injection", detail=detail,
                            fault=fault)


def _restore_map(env: E2EEnvironment) -> dict:
    m = getattr(env, _RESTORE_ATTR, None)
    if m is None:
        m = {}
        setattr(env, _RESTORE_ATTR, m)
    return m


def _wire_receivers(env: E2EEnvironment) -> list:
    """Every otlp wire receiver on the gateway (there can be several
    after reloads/multi-protocol configs — a fault that only hits the
    first leaves a healthy side door open). Empty when the gateway is
    not (or no longer) running — a clear_* sweeping a dead environment
    must find nothing, never raise."""
    if env.gateway is None:
        return []
    return [recv for rid, recv in env.gateway.graph.receivers.items()
            if rid.split("/")[0] == "otlp"]


def _gateway_engines(env: E2EEnvironment) -> list:
    """Every scoring engine serving the gateway (fast-path routes and
    componentwise tpuanomaly processors); empty when the gateway is
    not running (the clear_* no-raise contract)."""
    if env.gateway is None:
        return []
    engines: list = []
    for fp in env.gateway.graph.fastpaths.values():
        if fp.engine not in engines:
            engines.append(fp.engine)
    for proc in env.gateway.graph.processors.values():
        eng = getattr(proc, "engine", None)
        if eng is not None and eng not in engines:
            engines.append(eng)
    return engines


# ------------------------------------------------- destination misbehavior


def inject_exporter_chaos(env: E2EEnvironment, exporter_id: str, *,
                          reject_fraction: Optional[float] = None,
                          response_duration_ms: Optional[float] = None
                          ) -> None:
    """Flip fault knobs on a running mockdestination exporter."""
    exp = env.gateway_component(exporter_id)
    if reject_fraction is not None:
        exp.config["reject_fraction"] = float(reject_fraction)
    if response_duration_ms is not None:
        exp.config["response_duration_ms"] = float(response_duration_ms)
    if reject_fraction or response_duration_ms:
        # zero-valued knobs are the clear_* spelling, not a fault
        _flight("exporter_chaos",
                f"{exporter_id}: reject={reject_fraction} "
                f"latency={response_duration_ms}ms")


def clear_exporter_chaos(env: E2EEnvironment, exporter_id: str) -> None:
    inject_exporter_chaos(env, exporter_id, reject_fraction=0.0,
                          response_duration_ms=0.0)


class DestinationOutage(RuntimeError):
    """Raised by an outage-injected exporter in place of every export."""


def inject_destination_outage(env: E2EEnvironment,
                              exporter_id: str) -> None:
    """Hard destination outage: every export of ``exporter_id`` raises
    until cleared. Works on ANY exporter type (patches the instance's
    ``export``); a RetryQueue-wrapped destination spills instead of
    failing — exactly the degradation the wrapper exists for."""
    exp = env.gateway_component(exporter_id)
    target = getattr(exp, "inner", exp)  # reach through a RetryQueue
    key = ("destination_outage", exporter_id)
    restore = _restore_map(env)
    if key in restore:
        return  # already injected

    def dead_export(batch):
        raise DestinationOutage(
            f"{exporter_id}: injected destination outage")

    restore[key] = (target, target.__dict__.get("export"))
    target.export = dead_export
    _flight("destination_outage",
            f"{exporter_id}: every export raises until cleared")


def clear_destination_outage(env: E2EEnvironment,
                             exporter_id: str = "") -> None:
    """Lift outage(s); idempotent, and with no ``exporter_id`` clears
    every injected outage (the finally-step spelling)."""
    restore = _restore_map(env)
    for key in list(restore):
        if key[0] != "destination_outage":
            continue
        if exporter_id and key[1] != exporter_id:
            continue
        target, orig = restore.pop(key)
        if orig is None:
            target.__dict__.pop("export", None)  # back to the class method
        else:
            target.export = orig


# ------------------------------------------------------- memory pressure


def inject_memory_pressure(env: E2EEnvironment, on: bool = True) -> None:
    """Simulate gateway memory-limiter pressure: EVERY otlp wire front
    door starts rejecting frames pre-decode (the configgrpc-fork
    behavior the HPA's rejection metric is built on). ``on=False``
    lifts it — idempotent even when no pressure was ever injected (a
    chaos finally-step must never raise on a clean environment)."""
    receivers = [r for r in _wire_receivers(env)
                 if hasattr(r, "admission")]
    if not receivers:
        if not on:
            return  # nothing injected, nothing to lift
        raise RuntimeError("gateway has no wire otlp receiver")
    for recv in receivers:
        recv.admission.pressure_fn = (lambda: True) if on else None
    if on:
        _flight("memory_pressure",
                f"{len(receivers)} wire receiver(s) rejecting "
                f"pre-decode")


def clear_memory_pressure(env: E2EEnvironment) -> None:
    inject_memory_pressure(env, on=False)


# ------------------------------------------------------------ device loss


def inject_device_fault(env: E2EEnvironment,
                        message: str = "chaos: device lost") -> None:
    """Persistent device loss on every gateway scoring engine: each
    PRIMARY-backend dispatch raises until cleared. With a failover
    breaker configured the engine trips to its zscore fallback
    (ModelFailover); without one, frames forward unscored with the
    error counted — both are scenarios in the matrix."""
    engines = _gateway_engines(env)
    if not engines:
        raise RuntimeError("gateway has no scoring engine (anomaly "
                           "stage not enabled?)")
    for eng in engines:
        eng.inject_device_fault(message)
    _flight("device_fault", message)


def clear_device_fault(env: E2EEnvironment) -> None:
    for eng in _gateway_engines(env):
        eng.clear_device_fault()


# ------------------------------------------------------------- clock skew


class _SkewConsumer:
    """Shifts every span's timestamps by a fixed offset before the real
    consumer sees them — a producer fleet with skewed clocks."""

    def __init__(self, inner: Any, offset_ns: int):
        self.inner = inner
        self.offset_ns = int(offset_ns)

    def consume(self, batch: Any) -> None:
        cols = dict(batch.columns)
        for name in ("start_unix_nano", "end_unix_nano"):
            col = cols.get(name)
            if col is not None:
                cols[name] = (col.astype(np.int64)
                              + self.offset_ns).astype(col.dtype)
        self.inner.consume(replace(batch, columns=cols))


def inject_clock_skew(env: E2EEnvironment,
                      offset_s: float = 6 * 3600.0) -> None:
    """Every frame entering a gateway wire receiver arrives with span
    timestamps shifted ``offset_s`` into the future (default: a
    six-hour producer clock skew). Idempotent: re-injecting replaces
    the offset instead of stacking shims."""
    restore = _restore_map(env)
    for recv in _wire_receivers(env):
        key = ("clock_skew", id(recv))
        if key in restore:
            # replace the offset on the existing shim
            recv.next_consumer.offset_ns = int(offset_s * 1e9)
            continue
        restore[key] = (recv, recv.next_consumer)
        recv.next_consumer = _SkewConsumer(recv.next_consumer,
                                           int(offset_s * 1e9))
    _flight("clock_skew", f"producer clocks shifted {offset_s:+.0f}s")


def clear_clock_skew(env: E2EEnvironment) -> None:
    restore = _restore_map(env)
    for key in list(restore):
        if key[0] != "clock_skew":
            continue
        recv, orig = restore.pop(key)
        recv.next_consumer = orig


# --------------------------------------------------- wire-level storms


def _gateway_sock(env: E2EEnvironment,
                  timeout: float = 5.0) -> socket.socket:
    sock = socket.create_connection(
        ("127.0.0.1", env.gateway_otlp_port()), timeout=timeout)
    return sock


def inject_malformed_frame_storm(env: E2EEnvironment,
                                 frames: int = 16) -> int:
    """Send ``frames`` well-framed-but-undecodable payloads at the
    gateway's wire port; returns how many MALFORMED answers came back.
    Each one must land as a named ``invalid`` drop on the (ingress)
    book — never a crash, never silent."""
    from ..wire.codec import MAGIC

    answered = 0
    with _gateway_sock(env) as sock:
        for i in range(frames):
            garbage = bytes([(i * 37 + j) % 251
                             for j in range(64)])  # deterministic junk
            sock.sendall(MAGIC + struct.pack("<I", len(garbage)) + garbage)
            resp = sock.recv(1)
            if resp == b"\x02":  # MALFORMED
                answered += 1
            else:  # server closed / unexpected: stop, scenario asserts
                break
    _flight("malformed_frame_storm",
            f"{frames} junk frames sent, {answered} MALFORMED answers")
    return answered


def clear_malformed_frame_storm(env: E2EEnvironment) -> None:
    """Storms are instantaneous — nothing persists to lift (the pair
    exists so the registry/lint contract is uniform)."""


def inject_reconnect_stampede(env: E2EEnvironment, clients: int = 12,
                              rounds: int = 2) -> None:
    """``clients`` concurrent connections per round, each sending a
    TRUNCATED frame (header promising more bytes than ever arrive) and
    disconnecting mid-payload — the reconnect/half-frame stampede PR
    9's retry-jitter fix says is real. The server must shed the dead
    handlers and keep serving; nothing was accepted, so conservation
    is untouched by construction."""
    from ..wire.codec import MAGIC

    port = env.gateway_otlp_port()

    def one_client(seed: int) -> None:
        try:
            with socket.create_connection(("127.0.0.1", port),
                                          timeout=2.0) as sock:
                # promise 1 MiB, deliver a deterministic per-client
                # sliver, vanish
                sock.sendall(MAGIC + struct.pack("<I", 1 << 20))
                sock.sendall(bytes(32 + (seed % 64)))
        except OSError:
            pass  # a refused/reset stampede client is part of the storm

    for _ in range(rounds):
        threads = [threading.Thread(target=one_client, args=(i,),
                                    daemon=True)
                   for i in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=5.0)
    _flight("reconnect_stampede",
            f"{clients} half-frame clients x {rounds} rounds")


def clear_reconnect_stampede(env: E2EEnvironment) -> None:
    """Stampedes are instantaneous — nothing persists to lift."""


# -------------------------------------------------- hot reload under load


_RELOAD_DEST_ID = "chaos-reload"


def inject_hot_reload(env: E2EEnvironment) -> None:
    """Force a gateway config regeneration + hot reload mid-stream by
    adding a throwaway tracedb destination (the proven reload trigger:
    the autoscaler re-renders the ConfigMap and the watcher swaps the
    graph under load)."""
    from ..components.api import Signal
    from ..destinations import Destination

    env.add_destination(Destination(
        id=_RELOAD_DEST_ID, dest_type="tracedb",
        signals=[Signal.TRACES]))
    _flight("hot_reload", "throwaway destination added under load")


def clear_hot_reload(env: E2EEnvironment) -> None:
    """Remove the throwaway destination (another reload); idempotent."""
    from ..controlplane.scheduler import ODIGOS_NAMESPACE

    if env.store.delete("DestinationResource", ODIGOS_NAMESPACE,
                        _RELOAD_DEST_ID):
        env.reconcile()


# --------------------------------------------------------------- registry


def _build_registry() -> dict[str, tuple[Callable, Callable]]:
    """Pair every module-level ``inject_X`` with its ``clear_X`` — the
    machine-readable table the hygiene lint and the chaos soak read. An
    unpaired injector is an ImportError at first use, not a silent
    gap."""
    g = globals()
    registry: dict[str, tuple[Callable, Callable]] = {}
    for name, fn in sorted(g.items()):
        if not name.startswith("inject_") or not callable(fn):
            continue
        short = name[len("inject_"):]
        clear = g.get(f"clear_{short}")
        if clear is None:
            raise RuntimeError(
                f"chaos injector {name} has no paired clear_{short}")
        registry[short] = (fn, clear)
    return registry


INJECTORS: dict[str, tuple[Callable, Callable]] = _build_registry()


def clear_all(env: E2EEnvironment) -> None:
    """Belt-and-braces sweep for scenario finally_steps: run every
    idempotent clear that needs no target argument."""
    clear_memory_pressure(env)
    clear_device_fault(env)
    clear_destination_outage(env)
    clear_clock_skew(env)
    clear_hot_reload(env)
