"""Standalone collector entrypoint — the VM-distribution binary role.

Reference: collector/distribution/odigos-otelcol/ packages the same
collector binary for non-k8s VMs via systemd (``odigos-otelcol.service``
runs ``/usr/bin/odigos-otelcol $OTELCOL_OPTIONS``). The analog:

    python -m odigos_tpu.pipeline --config /etc/odigos-tpu/collector.json

Runs one Collector from a JSON config file, re-reads it on SIGHUP (the
odigosk8scmprovider hot-reload seam, file-flavored), drains on
SIGTERM/SIGINT, and exposes the self-metrics snapshot over a local HTTP
port for a node Prometheus (--metrics-port; own-observability role).
Packaging files live in ``distribution/odigos-tpu-collector/`` at the
repo root.
"""

from __future__ import annotations

import argparse
import json
import signal
import sys
import threading


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m odigos_tpu.pipeline",
        description="odigos-tpu standalone collector (VM distribution)")
    ap.add_argument("--config", required=True,
                    help="JSON collector config (receivers/processors/"
                         "exporters/service.pipelines)")
    ap.add_argument("--metrics-port", type=int, default=0,
                    help="serve the self-metrics snapshot on this port "
                         "(0 = disabled)")
    args = ap.parse_args(argv)

    from ..utils.jaxruntime import configure_compile_cache
    from .service import Collector

    with open(args.config) as f:
        config = json.load(f)
    # before the graph builds: an in-process scoring engine compiles at
    # start, and a restarted collector should find those programs again
    configure_compile_cache()
    collector = Collector(config).start()
    print(f"collector up: {len(collector.graph.all_components())} "
          f"components", flush=True)

    metrics_server = None
    if args.metrics_port:
        metrics_server = _serve_metrics(args.metrics_port, collector)
        print(f"self-metrics on :{metrics_server.server_address[1]}"
              f"/metrics", flush=True)

    stop = threading.Event()

    def on_term(signum, frame):
        stop.set()

    def on_hup(signum, frame):
        # file-flavored hot reload (odigosk8scmprovider seam)
        try:
            with open(args.config) as f:
                new_config = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"reload skipped: {e}", file=sys.stderr, flush=True)
            return
        try:
            collector.reload(new_config)
        except Exception as e:  # noqa: BLE001 — bad config must not kill us
            # reload() resurrected the old graph; report and keep serving
            print(f"reload failed (old config still serving): {e}",
                  file=sys.stderr, flush=True)
            return
        print("config reloaded", flush=True)

    signal.signal(signal.SIGTERM, on_term)
    signal.signal(signal.SIGINT, on_term)
    signal.signal(signal.SIGHUP, on_hup)
    stop.wait()
    if metrics_server is not None:
        metrics_server.shutdown()
    collector.shutdown()
    print("collector drained", flush=True)
    return 0


def _serve_metrics(port: int, collector=None):
    """Prometheus-text self-metrics endpoint plus /healthz — the
    own-observability + healthcheckextension roles (the reference distro
    compiles healthcheckextension into the collector,
    builder-config.yaml; systemd/k8s probes poll it)."""
    import json as _json
    import socketserver
    from http.server import BaseHTTPRequestHandler

    from ..utils.telemetry import meter, prometheus_text

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *a):  # noqa: D102
            pass

        def do_GET(self):  # noqa: N802
            path = self.path.rstrip("/")
            if path == "/healthz":
                unhealthy = []
                if collector is not None:
                    unhealthy = sorted(
                        c.name for c in collector.graph.all_components()
                        if not c.healthy())
                body = _json.dumps(
                    {"status": "ok" if not unhealthy else "unhealthy",
                     "unhealthy_components": unhealthy}).encode()
                self.send_response(200 if not unhealthy else 503)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            if path not in ("", "/metrics"):
                self.send_response(404)
                self.end_headers()
                return
            # exemplar annotations ride the collector scrape too —
            # this process hosts the engine/pipeline histograms
            from ..selftelemetry.flow import flow_ledger

            flow_ledger.publish(meter)
            body = prometheus_text(meter.snapshot(),
                                   meter.exemplars()).encode()
            self.send_response(200)
            self.send_header("Content-Type",
                             "text/plain; version=0.0.4")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    class Server(socketserver.ThreadingTCPServer):
        allow_reuse_address = True
        daemon_threads = True

    server = Server(("127.0.0.1", port), Handler)
    threading.Thread(target=server.serve_forever, daemon=True,
                     name="collector-metrics").start()
    return server


if __name__ == "__main__":
    sys.exit(main())
