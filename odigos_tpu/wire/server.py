"""Wire receiver with pre-decode admission control.

The configgrpc-fork behavior (collector/config/configgrpc/README.md:1-12):
under memory pressure the gateway rejects incoming OTLP **before decoding**
so a hot collector never spends CPU/heap on data it will drop; each
rejection increments the metric the HPA custom-metrics handler scrapes
(odigos_gateway_memory_limiter_rejections_total,
autoscaler/metricshandler/custom_metrics_handler.go:27).

Protocol per frame: client sends MAGIC+len+payload, server answers one
status byte: 0 accepted, 1 rejected-overloaded (client should back off and
retry), 2 malformed (client drops the frame).
"""

from __future__ import annotations

import socket
import socketserver
import threading
import time
from typing import Any, Callable, Optional

from ..components.api import ComponentKind, Factory, Receiver, Signal, register
from ..pdata.spans import SpanKind
from ..selftelemetry.flow import FlowContext, flow_ledger
from ..selftelemetry.latency import (
    Stage, annotate, name_thread, publish_clock, start_clock,
    unpublish_clock)
from ..selftelemetry.tracer import is_selftelemetry_batch, tracer
from ..utils.framing import recv_exact as _recv_exact
from ..utils.telemetry import labeled_key, meter
from .codec import MAGIC, decode_frame, read_frame_header

ACCEPTED = b"\x00"
REJECTED = b"\x01"
MALFORMED = b"\x02"

REJECTIONS_METRIC = "odigos_gateway_memory_limiter_rejections_total"

# the odigos_admission_* family (ISSUE 6): every pre-decode shed is
# countable by reason, and the watermark snapshot the decision consulted
# is published alongside it — "why was I rejected" is answerable from
# /metrics alone
ADMISSION_REJECTED_METRIC = "odigos_admission_rejected_frames_total"
ADMISSION_REJECTED_BYTES_METRIC = "odigos_admission_rejected_bytes_total"
ADMISSION_WATERMARK_GAUGE = "odigos_admission_watermark"
ADMISSION_INFLIGHT_GAUGE = "odigos_admission_inflight_bytes"


class WatermarkGate:
    """Pre-decode admission from the flow ledger's queue watermarks.

    ``limits`` maps a watermark identity to its shed threshold. Engines
    report process-scoped as ``engine/<model>``; pipeline stages and the
    fast path report PIPELINE-QUALIFIED (two pipelines' same-named
    stages must never clobber one key)::

        {"engine/zscore":              {"queue_depth": 48},
         "traces/in/memory_limiter":   {"inflight_bytes": 400e6},
         "traces/in/batch":            {"pending_spans": 65536},
         "fastpath/traces/in":         {"pending_spans": 98304}}

    ``check()`` answers from a cached verdict refreshed at most every
    ``refresh_s`` (one dict lookup per watched queue, only on refresh),
    so the per-frame cost on the accept path is one monotonic read — the
    shed-before-work discipline must not itself become work. Each
    refresh publishes the consulted values as
    ``odigos_admission_watermark{component=,queue=}`` gauges (plus the
    byte-budget inflight gauge), so the exact snapshot behind a REJECTED
    is on /metrics.
    """

    def __init__(self, limits: dict[str, dict[str, float]],
                 refresh_s: float = 0.005,
                 inflight_fn: Optional[Callable[[], int]] = None,
                 receiver_name: str = ""):
        self.limits = {
            comp: {q: float(v) for q, v in queues.items()}
            for comp, queues in (limits or {}).items()}
        self.refresh_s = float(refresh_s)
        self.inflight_fn = inflight_fn
        self._gauge_keys = {
            (comp, q): labeled_key(ADMISSION_WATERMARK_GAUGE,
                                   component=comp, queue=q)
            for comp, queues in self.limits.items() for q in queues}
        self._inflight_key = labeled_key(ADMISSION_INFLIGHT_GAUGE,
                                         receiver=receiver_name)
        self.receiver_name = receiver_name
        self._lock = threading.Lock()
        self._next_eval = 0.0
        # (component, queue, ledger_reason) or None
        self._verdict: Optional[tuple[str, str, str]] = None

    def check(self) -> Optional[tuple[str, str, str]]:
        now = time.monotonic()
        with self._lock:
            if now < self._next_eval:
                return self._verdict
            self._next_eval = now + self.refresh_s
        verdict = None
        for comp, queues in self.limits.items():
            for q, limit in queues.items():
                v = flow_ledger.watermark_current(comp, q)
                meter.set_gauge(self._gauge_keys[(comp, q)],
                                float(v or 0.0))
                if v is not None and v >= limit and verdict is None:
                    # byte-pressure watermarks shed as memory_limited
                    # (the reference's memory-limiter discipline); depth
                    # watermarks as queue_full
                    reason = "memory_limited" if "bytes" in q \
                        else "queue_full"
                    verdict = (comp, q, reason)
        if self.inflight_fn is not None:
            meter.set_gauge(self._inflight_key,
                            float(self.inflight_fn()))
        with self._lock:
            prev, self._verdict = self._verdict, verdict
        if verdict is not None and verdict != prev:
            # watermark breach TRANSITIONS are flight-recorder events
            # (a standing breach re-evaluated every refresh_s is one
            # line, not a line per refresh)
            from ..selftelemetry.flightrecorder import flight_recorder

            flight_recorder.record(
                "admission_breach", receiver=self.receiver_name,
                component=verdict[0], queue=verdict[1],
                reason=verdict[2])
        return verdict


class AdmissionController:
    """Tracks bytes admitted-but-not-yet-consumed; over the soft limit new
    frames are rejected pre-decode. A custom ``pressure_fn`` can add process
    signals (RSS, queue depth); a :class:`WatermarkGate` adds the flow
    ledger's downstream watermarks (engine queue depth, memory-limiter
    inflight bytes, batcher/fast-path pending spans) so overload anywhere
    in the pipeline sheds at the socket, before any decode work."""

    def __init__(self, max_inflight_bytes: int = 64 << 20,
                 pressure_fn: Optional[Callable[[], bool]] = None,
                 watermark_gate: Optional[WatermarkGate] = None):
        self.max_inflight_bytes = max_inflight_bytes
        self.pressure_fn = pressure_fn
        self.watermark_gate = watermark_gate
        self._inflight = 0
        self._lock = threading.Lock()

    def admit(self, nbytes: int) -> Optional[tuple[str, str]]:
        """None = admitted (inflight charged); otherwise
        ``(ledger_reason, detail_label)`` naming the shed."""
        gate = self.watermark_gate
        if gate is not None:
            w = gate.check()
            if w is not None:
                comp, q, reason = w
                return (reason, f"{comp}:{q}")
        with self._lock:
            if self._inflight + nbytes > self.max_inflight_bytes:
                return ("memory_limited", "inflight_bytes")
            if self.pressure_fn is not None and self.pressure_fn():
                return ("memory_limited", "pressure")
            self._inflight += nbytes
            return None

    def release(self, nbytes: int) -> None:
        with self._lock:
            self._inflight -= nbytes

    @property
    def inflight_bytes(self) -> int:
        with self._lock:
            return self._inflight


def _discard_exact(sock: socket.socket, n: int) -> bool:
    """Consume n bytes without retaining them (rejected frame)."""
    while n:
        chunk = sock.recv(min(n, 1 << 20))
        if not chunk:
            return False
        n -= len(chunk)
    return True


class WireReceiver(Receiver):
    """Config:
    port: TCP port (0 = ephemeral; resolved port in ``.port`` after start)
    host: bind host (default 127.0.0.1)
    max_inflight_bytes: admission soft limit (default 64 MiB)
    """

    # incremental hot reload (ISSUE 14): the admission posture retunes
    # live — the gate and byte budget are swapped on the SAME
    # controller (in-flight accounting and the socket bind survive;
    # host/port changes replace the node, which is the only time an
    # otlp receiver releases its bind)
    RECONFIGURABLE_KEYS = frozenset({"admission", "max_inflight_bytes"})

    def __init__(self, name: str, config: dict[str, Any]):
        super().__init__(name, config)
        self.admission = AdmissionController(
            int(config.get("max_inflight_bytes", 64 << 20)),
            watermark_gate=self._build_gate(config))
        # per-reason rejection counter keys, cached (reason cardinality
        # is the handful of configured watermark names)
        self._reject_keys: dict[str, tuple[str, str]] = {}
        self._server: Optional[socketserver.ThreadingTCPServer] = None
        self._thread: Optional[threading.Thread] = None
        self.port: Optional[int] = None
        self._conns: set[socket.socket] = set()
        self._conns_lock = threading.Lock()

    def _build_gate(self,
                    config: dict[str, Any]) -> Optional[WatermarkGate]:
        adm = config.get("admission") or {}
        if not adm.get("watermarks"):
            return None
        return WatermarkGate(
            adm["watermarks"],
            refresh_s=float(adm.get("refresh_ms", 5.0)) / 1e3,
            inflight_fn=lambda: self.admission.inflight_bytes,
            receiver_name=self.name)

    def reconfigure(self, config: dict[str, Any]) -> None:
        # parse EVERYTHING before assigning anything: a bad value must
        # leave the live admission posture fully intact, never half the
        # new config (the reload falls back / fails with the old graph
        # "serving" — it must actually be the old posture). A fresh
        # gate object means its cached verdict dies with it; the
        # controller keeps its in-flight byte count — releases of
        # already-admitted frames must still balance — and any chaos
        # pressure_fn stays injected.
        gate = self._build_gate(config)
        max_bytes = int(config.get("max_inflight_bytes", 64 << 20))
        self.admission.watermark_gate = gate
        self.admission.max_inflight_bytes = max_bytes
        self.config = config

    def _count_rejection(self, reason: str, detail: str,
                         nbytes: int) -> None:
        keys = self._reject_keys.get(detail)
        if keys is None:
            keys = self._reject_keys[detail] = (
                labeled_key(ADMISSION_REJECTED_METRIC,
                            receiver=self.name, reason=detail),
                labeled_key(ADMISSION_REJECTED_BYTES_METRIC,
                            receiver=self.name, reason=detail))
        meter.add(keys[0])
        meter.add(keys[1], nbytes)
        # pre-decode shed: the span count is unknowable (nothing was
        # decoded), so the ledger names the loss in FRAMES — same
        # discipline as malformed-frame accounting. A shed steered by
        # the fast path's predicted_burn_ms watermark carries the
        # blame=predicted dimension (ISSUE 12): the frame was refused
        # because it was PRICED to expire, not because a queue was full
        FlowContext.drop(1, reason, pipeline="(ingress)",
                         component_name=self.name, signal="frames",
                         blame="predicted"
                         if detail.endswith(":predicted_burn_ms")
                         else None)

    def start(self) -> None:
        super().start()
        receiver = self

        class Handler(socketserver.BaseRequestHandler):
            def setup(self):
                # one thread per connection: the trace's host plane
                # shows each as a receiver's line
                name_thread("odigos-receiver")
                with receiver._conns_lock:
                    receiver._conns.add(self.request)

            def finish(self):
                with receiver._conns_lock:
                    receiver._conns.discard(self.request)

            def handle(self):
                sock = self.request
                try:
                    while True:
                        head = _recv_exact(sock, 8)
                        if head is None:
                            return
                        try:
                            payload_len = read_frame_header(head)
                        except ValueError:
                            sock.sendall(MALFORMED)
                            return
                        # latency attribution (ISSUE 8): the frame's
                        # stage clock starts at its first touch; the
                        # fast path adopts it across the consume seam
                        # (no-op object when ODIGOS_LATENCY=0)
                        clock = start_clock()
                        with annotate("wire/admission", clock,
                                      Stage.ADMISSION):
                            verdict = receiver.admission.admit(payload_len)
                        if verdict is not None:
                            # pre-decode rejection: drain the socket bytes,
                            # never allocate/decode, tell client to back off
                            reason, detail = verdict
                            meter.add(REJECTIONS_METRIC)
                            receiver._count_rejection(reason, detail,
                                                      payload_len)
                            if not _discard_exact(sock, payload_len):
                                return
                            sock.sendall(REJECTED)
                            continue
                        try:
                            payload = _recv_exact(sock, payload_len)
                            if payload is None:
                                return
                            try:
                                with annotate("wire/decode", clock,
                                              Stage.DECODE):
                                    batch, tp = decode_frame(payload)
                            except Exception:
                                # corrupt payload is permanent: MALFORMED
                                # tells the client to drop, not retry
                                meter.add(
                                    "odigos_receiver_malformed_frames_total"
                                    f"{{receiver={receiver.name}}}")
                                # pre-pipeline shed, named in the flow
                                # ledger (item count unknowable pre-
                                # decode: one frame)
                                FlowContext.drop(
                                    1, "invalid", pipeline="(ingress)",
                                    component_name=receiver.name,
                                    signal="frames")
                                sock.sendall(MALFORMED)
                                continue
                            token = publish_clock(clock)
                            try:
                                if is_selftelemetry_batch(batch):
                                    # forwarded self-spans must not mint
                                    # spans about themselves downstream
                                    receiver.next_consumer.consume(batch)
                                else:
                                    # re-parent under the sender's span
                                    # (the frame's traceparent): node-
                                    # collector → gateway is one trace
                                    with tracer.span(
                                            f"receiver/{receiver.name}",
                                            kind=SpanKind.SERVER,
                                            traceparent=tp) as sp:
                                        sp.set_attr("batch.spans",
                                                    len(batch))
                                        sp.set_attr("frame.bytes",
                                                    payload_len)
                                        receiver.next_consumer.consume(
                                            batch)
                            except Exception:
                                # downstream pressure is transient: REJECTED
                                meter.add(
                                    "odigos_receiver_refused_batches_total"
                                    f"{{receiver={receiver.name}}}")
                                sock.sendall(REJECTED)
                                continue
                            finally:
                                # an unclaimed clock (componentwise
                                # chain) dies here; the fast path has
                                # already taken ownership for the frame
                                unpublish_clock(token)
                            sock.sendall(ACCEPTED)
                        except OSError:
                            return
                        finally:
                            receiver.admission.release(payload_len)
                except OSError:
                    return

        host = self.config.get("host", "127.0.0.1")
        port = int(self.config.get("port", 0))

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True  # fast rebinds on collector restart
            daemon_threads = True

        self._server = Server((host, port), Handler, bind_and_activate=True)
        self.port = self._server.server_address[1]
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True,
            name=f"otlpwire-{self.name}")
        self._thread.start()

    def shutdown(self) -> None:
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None
        # close accepted connections too: handler threads otherwise outlive
        # shutdown and keep consuming into the torn-down pipeline
        with self._conns_lock:
            conns = list(self._conns)
        for conn in conns:
            try:
                conn.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            conn.close()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        super().shutdown()


register(Factory(
    type_name="otlpwire", kind=ComponentKind.RECEIVER,
    create=WireReceiver, signals=(Signal.TRACES,),
    default_config=lambda: {"host": "127.0.0.1", "port": 0,
                            "max_inflight_bytes": 64 << 20}))

# "otlp" alias: generated configs use the OTLP front-door name
# (pipelinegen root pipelines, config_builder.go:184); this wire receiver
# plays that role in our distro
register(Factory(
    type_name="otlp", kind=ComponentKind.RECEIVER,
    create=WireReceiver, signals=(Signal.TRACES,),
    default_config=lambda: {"host": "127.0.0.1", "port": 0,
                            "max_inflight_bytes": 64 << 20}))
