"""Sustained end-to-end wire-path throughput soak — multi-sender matrix.

A pinned-duration soak of MANY concurrent senders through the REAL wire
path, on whatever platform JAX finds (the artifact records platform and
``device_kind``; the committed SOAK/CHAOS/RELOAD/ACTUATOR/DEVICE records
were taken with ``JAX_PLATFORMS=cpu`` set from outside) —

    WireExporter ×N (framed TCP) -> otlpwire receiver with byte-budget +
    watermark-driven admission (flow-ledger watermarks: engine
    queue_depth, fast-path pending_spans) -> ingest FAST PATH (per-frame
    featurize, deadline-based adaptive batching in the engine) ->
    anomalyrouter -> tracedb exporters

(``--no-fast-path`` swaps back the componentwise memory_limiter ->
batch -> tpuanomaly chain for A/B.) Reports the per-sender matrix —
throughput, REJECTED/backoff counts, frames dropped client-side — plus
the flow ledger's drop-reason breakdown and conservation verdict, so
every shed span is demonstrably *named*, never silently lost. Writes
``SOAK.json`` and prints one JSON line.

Added-latency percentiles come from a PROBE stream: a separate low-rate
sender ships one tiny distinctive batch (service ``latency-probe``)
every ~100 ms through the same loaded wire, and the terminal exporters
are wrapped to stamp its arrival — send→export wall time through
admission, featurization, adaptive batching, scoring, and routing under
full load. Matching is by probe sequence attr; detection is one cheap
membership test on the interned string table per exported batch (zero
per-span work on the hot path).

    python tools/e2e_soak.py [--seconds 20] [--senders 4]
                             [--no-fast-path] [--ab]
                             [--pace-spans-per-sec 255000]
                             [--find-knee]

``--find-knee`` (ISSUE 12) sweeps offered load with short paced probes
to locate the throughput knee (highest level carried essentially
losslessly — delivered ≥ ``--knee-delivery``, default 98%, of
offered), then records the full run AT the knee — "saturated" becomes
a measured operating point, not an arbitrary number. SOAK.json embeds
``knee_spans_per_sec``, the sweep table, ``p99_over_p50`` (acceptance:
≤ 3 for the fast path at the knee), and a ``steady_state`` section
(buffer-pool miss rate ≈ 0 allocs/frame, GC pause accounting,
predictive-shed tally).

``--ab`` runs BOTH routes back to back (fast path first) and embeds the
componentwise summary in the record as ``componentwise_baseline`` — the
same-machine A/B the acceptance comparison needs (absolute spans/s are
hardware-bound; see ``hardware_note``).

``--pace-spans-per-sec`` switches the senders from closed-loop
saturation to OPEN-LOOP pacing: a fixed offered load regardless of how
fast the pipeline answers. For latency A/B this is the honest mode —
saturating senders adapt to each arm's own backpressure (coordinated
omission), so their probe compares the arms' admission policies (the
fast path sheds at the socket; the componentwise chain buffers), not
the paths. Paced below the knee, both arms carry the identical load
losslessly and the probe measures pure path transit.

Reference discipline: the hot-loop zero-alloc rule of
collector/receivers/odigosebpfreceiver/traces.go:17, the configgrpc
fork's shed-before-decode, and the tests/e2e/trace-collection
conservation asserts.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _parse_mesh(spec: str) -> dict:
    """"4x2" -> {"data": 4, "model": 2} (the dp×tp serving mesh)."""
    dp, _, tp = spec.lower().partition("x")
    return {"data": int(dp), "model": int(tp or 1)}


# fleet alert rules the soak runs under (ISSUE 10): rendered into the
# collector config's service.alerts stanza, evaluated live while the
# plane publishes the collector each tick, and embedded — rule states
# plus every fired/cleared transition — into SOAK.json so a soak run
# proves the alert loop end to end. Module-level so the package-hygiene
# lint can resolve each expression's metric against the registered
# odigos_* names (a typo'd rule must fail tests, not sit dark).
SOAK_ALERTS = [
    # a queue_full storm (the engine shedding under overload) must page
    {"name": "queue-full-storm",
     "expr": "rate(odigos_flow_dropped_items_total"
             "{reason=queue_full}[10s]) > 5000",
     "for_s": 2.0, "severity": "critical"},
    # sustained pre-decode shedding at the socket: the admission gate
    # doing its job, but worth a warning when it persists
    {"name": "admission-shed-sustained",
     "expr": "rate(odigos_admission_rejected_frames_total[10s]) > 100",
     "for_s": 2.0, "severity": "warning"},
    # unplanned recompile burst (ISSUE 20): warm=false compile events
    # are supposed to be extinct once the startup ramp warms the live
    # shapes — a sustained rate mid-soak is the classic silent latency
    # cliff. The threshold sits well above the ramp itself (a handful
    # of cold fused buckets compiling in the first seconds reads
    # ~0.1/s over this window) so a clean soak stays incident-clean;
    # a genuine storm (shapes churning off the ladder every frame)
    # reads >= 1/s and pages
    {"name": "compile-storm",
     "expr": "rate(odigos_jit_compile_events_total{warm=false}[60s])"
             " > 0.5",
     "for_s": 5.0, "severity": "critical"},
]

# --device-attrib (ISSUE 20): sampled sub-stage sum vs the opaque fused
# stamp. ~1.0 on an idle box (the composition is op-identical; the
# residue is lost cross-stage XLA fusion + per-stage dispatch), but
# under full soak load the fused stamp also absorbs queue-behind-
# previous-work time the sub-stage replay does not, so the bounds are
# deliberately wide — the gate catches a BROKEN decomposition (a stage
# not running, a stamp off by orders of magnitude), not scheduling
# noise
DEVICE_RECONCILE_BOUNDS = (0.2, 10.0)

# extra rules the --chaos run loads (ISSUE 13): the injected faults
# must fire exactly these — a failover trip and a retry backlog are the
# alerts the chaos record asserts on
CHAOS_ALERTS = [
    {"name": "failover-active",
     "expr": "max(odigos_failover_state[30s]) >= 1",
     "for_s": 0.0, "severity": "warning"},
    {"name": "export-retry-backlog",
     "expr": "max(odigos_export_retry_queue_spans[30s]) > 0",
     "for_s": 0.0, "severity": "warning"},
]

# --actuate (ISSUE 15): the alert the injected overload must fire (the
# alert->proposal->canary->promotion timeline's first event) and the
# soak-timescale recommender rule the actuator consumes. Module-level
# so the package-hygiene lint resolves the metrics and the knob.
ACTUATE_ALERTS = [
    {"name": "deadline-expiry-storm",
     "expr": "rate(odigos_latency_deadline_expired_spans_total[5s])"
             " > 200",
     "for_s": 1.0, "severity": "warning"},
]
ACTUATE_RULES = [
    # the production table's deadline-expiry-storm rule at soak
    # timescale: a [5s] window (the judgment window must exceed it for
    # the breach-clear oracle to be observable) and a short hold
    {"name": "deadline-expiry-storm",
     "expr": "rate(odigos_latency_deadline_expired_spans_total[5s])"
             " > 200",
     "knob": "admission_deadline", "direction": "up", "for_s": 1.5,
     "severity": "warning",
     "action": "deadline expiries at {value:.0f} spans/s — raise "
               "fast_path.deadline_ms"},
]


# the transformer route's EXPLICIT CPU geometry (--cpu-geometry): a
# small real transformer, for runs whose subject is the wire path on a
# host with no accelerator. Refused on any other platform; without the
# flag the route serves the flagship as it ships.
CPU_GEOMETRY = {
    "model_config": {"d_model": 64, "n_layers": 2, "d_ff": 256,
                     "n_heads": 4, "max_len": 32, "dtype": "float32"},
    "trace_bucket": 64, "max_len": 32}


def run_soak(args, fast_path: bool) -> dict:
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if args.mesh:
        # multichip mode (ISSUE 7): the engine serves on a dp×tp mesh of
        # the devices JAX found (for a CPU run, set
        # XLA_FLAGS=--xla_force_host_platform_device_count=N outside)
        mesh = _parse_mesh(args.mesh)
        if mesh["data"] * mesh["model"] > device["count"]:
            raise SystemExit(
                f"--mesh {args.mesh} needs {mesh['data'] * mesh['model']} "
                f"devices, JAX found {device}")
    if args.cpu_geometry and device["platform"] != "cpu":
        raise SystemExit(f"--cpu-geometry is the CPU miniature; JAX "
                         f"found {device}")

    from odigos_tpu.pdata import synthesize_traces
    from odigos_tpu.pipeline.service import Collector
    from odigos_tpu.selftelemetry.flightrecorder import flight_recorder
    from odigos_tpu.selftelemetry.flow import flow_ledger
    from odigos_tpu.selftelemetry.latency import latency_ledger
    from odigos_tpu.utils.telemetry import labeled_key, meter
    from odigos_tpu.wire.client import WireExporter

    pipeline_in: dict = {
        "receivers": ["otlpwire"],
        "processors": ["memory_limiter", "batch", "tpuanomaly"],
        "exporters": ["anomalyrouter"]}
    # queue AGE is the latency budget: the admission gate sheds on the
    # fast path's pending_ms watermark (age of the oldest undelivered
    # frame) — throughput-invariant, unlike a span-count bound, which
    # means N ms of queue on a slow runner but over-sheds a fast one.
    # The span-denominated bounds stay as memory backstops (bufferbloat
    # is the old soak's 1.16 s p99 pathology — a 64-deep engine queue
    # of 8k-span batches).
    if args.actuate:
        # actuator soak (ISSUE 15): start with a deliberately tight
        # admission deadline (sized for the BASELINE pace) and turn
        # predictive shed off — the injected overload must produce
        # in-pipeline expiries (unscored forwards = a scored_fraction
        # SLO burn the actuator's resize must cure), not pre-featurize
        # rejections the SLO never sees
        args.deadline_ms = args.actuate_deadline_ms
        args.no_predictive = True
        # the backlog gate must not shed the overload before it can
        # expire (the expiry IS the breach signal under actuation)
        args.backlog_ms = max(args.backlog_ms, 6 * args.deadline_ms)
        # and the pending window must HOLD the big-frame overload: a
        # window of ~5 oversized frames would saturate into queue_full
        # storms and make the window — not the deadline — the binding
        # constraint (the canary would honestly roll back on the
        # QueueSaturation its own overload caused)
        args.max_pending_spans = max(
            args.max_pending_spans,
            args.overload_size_mult * 64 * 1024)
    if fast_path:
        # completion-driven multi-lane retirement (ISSUE 9): N lanes
        # overlap tag/forward of independent frames; unordered by
        # default (the soak's consumers are order-insensitive), so the
        # old single-forwarder wait head-of-line is gone entirely.
        # predictive (ISSUE 12): frames priced past the deadline are
        # shed at intake (blame=predicted) instead of expiring inside
        pipeline_in["fast_path"] = {
            "deadline_ms": args.deadline_ms,
            "max_pending_spans": args.max_pending_spans,
            "lanes": args.lanes,
            "submit_lanes": args.submit_lanes or args.lanes,
            "ordered": bool(args.ordered),
            "predictive": not args.no_predictive}
        if args.fused:
            # fused device-side featurize→pack→score (ISSUE 19): submit
            # lanes hand the engine raw column views and ONE jitted call
            # does hashing/join/assembly/pack/forward — covered frames
            # skip host featurize entirely; every uncovered frame takes
            # the host route with its reason counted
            pipeline_in["fast_path"]["fused"] = True
        # declarative SLO (ISSUE 8): evaluated live during the soak with
        # fast/slow-window burn rates; the verdict lands in SOAK.json so
        # every soak run is self-judging, not just self-attributing.
        # Windows sized to the run (a 20 s soak cannot fill a 60 s
        # window); latency objective = the probe budget the old records
        # were judged against informally.
        pipeline_in["slo"] = {
            "latency_p99_ms": args.slo_p99_ms,
            # the actuate soak's SLO objective is the scored fraction
            # the expiry storm burns (and the resize must recover).
            # 0.98, not a looser target: fast-burn pages at 14.4x, and
            # a budget of 1-Y must be small enough that a mass-expiry
            # storm can actually reach it (target 0.9 caps the burn at
            # 10x — mathematically un-pageable)
            "scored_fraction": 0.98 if args.actuate else 0.5,
            "fast_window_s": max(args.seconds / 10, 2.0)
            if args.actuate else max(args.seconds / 4, 2.0),
            "slow_window_s": max(args.seconds, 8.0),
            # actuate: page earlier than the 14.4x default — the whole
            # point is that the actuator reacts within seconds, so the
            # burn must cross the page line BEFORE the cure lands for
            # the record to show the SLOBurn round trip
            **({"fast_burn_threshold": 5.0,
                "slow_burn_threshold": 0.5} if args.actuate else {})}
    # warm_ladder precompiles every scoring bucket at start: the
    # adaptive coalescer's variable batch sizes must never pay a
    # worker-stalling XLA compile mid-soak
    tpu_cfg = {"model": args.model, "threshold": 0.6,
               "timeout_ms": 30000, "shared_engine": False,
               "warm_ladder": True}
    if args.chaos:
        # chaos soak (ISSUE 13): arm the failover breaker so the
        # injected device loss trips to the zscore fallback mid-window
        tpu_cfg["failover"] = {
            "trip_errors": 3, "window_s": 5.0,
            "probe_interval_s": 0.5, "recovery_successes": 2}
    if args.model == "transformer":
        # bounded coalescing so packed rows stay on warmed, mesh-aligned
        # ladder rungs
        tpu_cfg.update({"bucket_ladder": 4, "max_batch": 4096})
        if args.cpu_geometry:
            tpu_cfg.update(CPU_GEOMETRY)
    if args.device_attrib:
        # device-plane attribution (ISSUE 20): 1-in-N sampled frames
        # rerun the fused call as its five jitted sub-stages and publish
        # the intra-fused waterfall; everything else rides the normal
        # fused route untouched
        tpu_cfg["device_attribution"] = True
        tpu_cfg["device_attribution_stride"] = args.device_attrib_stride
    if args.mesh:
        tpu_cfg["mesh"] = _parse_mesh(args.mesh)
    cfg = {
        "receivers": {"otlpwire": {
            # watermark-driven admission: overload anywhere downstream
            # sheds at the socket, before decode — every rejection named
            "admission": {"watermarks": {
                # shallow (default 8, not the old 48): with multi-lane
                # retirement the engine queue is the one place latency
                # can still hide from the backlog_ms gate — 48
                # deadline-coalesced requests is over a second of queue
                # against a 100 ms admission deadline, i.e. mass expiry
                # before scoring. A shallow gate converts that hidden
                # queue into named REJECTEDs at the socket
                f"engine/{args.model}": {
                    "queue_depth": args.engine_queue_depth},
                "fastpath/traces/in": dict(
                    {"backlog_ms": args.backlog_ms,
                     # gate at 3/4 of the hard bound: the watermark
                     # sheds at the socket BEFORE consume() hits the
                     # FastPathSaturated wall (frame-size granularity
                     # means the wall is crossed mid-burst otherwise)
                     "pending_spans": args.max_pending_spans * 3 // 4},
                    # predictive shed pre-decode (ISSUE 12): a frame
                    # the burn table prices past the deadline is
                    # REJECTED before decode spends a byte on it
                    **({} if args.no_predictive else
                       {"predicted_burn_ms": args.deadline_ms})),
                "traces/in/memory_limiter": {"inflight_bytes": 400e6},
                "traces/in/batch": {"pending_spans": 48 * 1024},
            }, "refresh_ms": 2.0},
        }},
        "processors": {
            "memory_limiter": {"limit_mib": 512},
            "batch": {"send_batch_size": 8192, "timeout_s": 0.1},
            "tpuanomaly": tpu_cfg,
        },
        "connectors": {"anomalyrouter": {
            "anomaly_pipelines": ["traces/anomaly"],
            "default_pipelines": ["traces/normal"],
            "mode": "trace"}},
        # chaos soak: destinations ride the retry/spill queue so the
        # injected outage spills + recovers instead of failing batches.
        # ONE spec for both exporters — the chaos verdict sums both
        # spill queues, so their bounds must never silently diverge
        "exporters": {
            eid: ({"retry": {"initial_backoff_ms": 20,
                             "max_backoff_ms": 200,
                             "max_queue_spans": 4 << 20,
                             "seed": args.chaos_seed}}
                  if args.chaos else {})
            for eid in ("tracedb/anomaly", "tracedb/normal")
        },
        "service": {
            "alerts": [dict(a) for a in SOAK_ALERTS]
            + ([dict(a) for a in CHAOS_ALERTS] if args.chaos else [])
            + ([dict(a) for a in ACTUATE_ALERTS] if args.actuate
               else []),
            # closed-loop actuator (ISSUE 15), armed only for
            # --actuate: judgment window > the rule's [5s] expr window
            # (a rate cannot visibly clear inside its own window),
            # soak-timescale cooldown, step bound sized so one
            # promotion can lift the deadline clear of the overload's
            # latency (the hard KNOB_SPECS bounds still clamp)
            **({"actuator": {
                "enabled": True, "dry_run": False,
                "judgment_window_s": 6.0, "cooldown_s": 10.0,
                "max_step": 6.0,
                "knobs": ["admission_deadline"]}}
               if args.actuate else {}),
            # GC isolation (ISSUE 12), BOTH arms (the A/B compares the
            # paths, not the GC posture): the paced janitor owns gen-0/1
            # sweeps, thresholds absorb per-frame churn, and freeze
            # pins the engine/ladder graph after warmup so collections
            # never rescan the model
            "gc": {"janitor_interval_s": 0.2, "freeze": True,
                   "thresholds": [150_000, 30, 30]},
            "pipelines": {
                "traces/in": pipeline_in,
                "traces/anomaly": {"receivers": ["anomalyrouter"],
                                   "exporters": ["tracedb/anomaly"]},
                "traces/normal": {"receivers": ["anomalyrouter"],
                                  "exporters": ["tracedb/normal"]},
            }},
    }

    from odigos_tpu.selftelemetry.fleet import fleet_plane
    from odigos_tpu.serving.gcisolation import gc_plane

    flow_ledger.reset()
    meter.reset()
    latency_ledger.reset()
    fleet_plane.reset()
    gc_plane.reset_stats()
    flight_recorder.reset()
    collector = Collector(cfg).start()
    port = collector.graph.receivers["otlpwire"].port

    # prime the scoring path before the timed window: call 0 pays the
    # zscore jit compile (~a second on CPU), and with watermark-driven
    # admission that stall would otherwise start the soak in a REJECTED
    # storm instead of measuring steady state
    if fast_path:
        engine = collector.graph.fastpaths["traces/in"].engine
    else:
        engine = collector.graph.processors[
            ("traces/in", "tpuanomaly")].engine
    engine.score_sync(synthesize_traces(args.traces_per_batch, seed=999),
                      timeout_s=30.0)

    # ---- fused parity gate (ISSUE 19): before the timed window, the
    # LIVE engine's backend must score a sample frame identically on
    # both routes (within the bound for the precision it serves,
    # serving/fused.py) — a soak that silently soaked a divergent
    # kernel would certify garbage. The verdict gates the exit code.
    fused_parity = None
    if args.fused:
        import numpy as np

        from odigos_tpu.features import featurize
        from odigos_tpu.serving.fused import (
            extract_columns, fused_enabled, routes_agree, served_precision)

        if not fused_enabled():
            raise RuntimeError(
                "--fused armed but ODIGOS_FUSED=0 in the environment")
        backend = engine.backend
        if not getattr(backend, "supports_fused", False):
            raise RuntimeError(
                "--fused armed but the engine backend has no fused kernel")
        pb = synthesize_traces(args.traces_per_batch, seed=998)
        want = backend.score(pb, featurize(pb, engine.cfg.featurizer))
        cols, reason = extract_columns(pb, engine.cfg.featurizer)
        if cols is None:
            raise RuntimeError(f"fused parity frame not coverable: {reason}")
        got = backend.harvest(backend.dispatch_columns([cols]))
        fused_parity = {
            "spans": len(pb),
            "max_abs_diff": round(float(np.max(np.abs(got - want))), 8),
            "precision": served_precision(backend),
            "passed": routes_agree(got, want, served_precision(backend)),
        }

    # pre-synthesize a few distinct batches per sender (generation must not
    # rate-limit the wire); a quarter carry injected faults so the anomaly
    # route is exercised under load, not just the passthrough path
    from odigos_tpu.pdata import inject_faults

    batches = []
    for s in range(8):
        b = synthesize_traces(args.traces_per_batch, seed=s)
        if s % 4 == 0:
            b, _, _ = inject_faults(b, fault_fraction=0.2, seed=100 + s)
        batches.append(b)
    batch_spans = [len(b) for b in batches]
    # --actuate overload set: --overload-size-mult-sized frames whose
    # per-frame service time (featurize/pack/score scale with span
    # count) lands past the tight initial deadline BY CONSTRUCTION — a
    # pure rate overload is a queueing knife edge that storms on one
    # run and rides under the deadline on the next (box noise), which
    # is exactly the flake a recorded acceptance cannot stand on
    big_batches: list = []
    big_spans: list = []
    if args.actuate:
        for s in range(8):
            b = synthesize_traces(
                args.traces_per_batch * args.overload_size_mult,
                seed=50 + s)
            if s % 4 == 0:
                b, _, _ = inject_faults(b, fault_fraction=0.2,
                                        seed=150 + s)
            big_batches.append(b)
        big_spans = [len(b) for b in big_batches]
    # which batch set the senders draw from (the overload flips it):
    # ONE tuple swapped/read atomically — assigning batches and spans
    # as two separate keys would let a sender pair a baseline batch
    # with a 16x span count mid-swap and mis-state conservation
    active_set = {"cur": (batches, batch_spans)}

    sent_spans = [0] * args.senders
    sent_batches = [0] * args.senders
    dropped_spans = [0] * args.senders
    stop = threading.Event()
    exporter_names = [f"otlpwire/soak-{i}" for i in range(args.senders)]

    # open-loop pacing (0 = closed-loop saturation): each sender holds
    # a fixed spans/s share and sleeps between exports regardless of
    # how fast the pipeline answers. A saturating closed-loop sender
    # adapts to backpressure — the classic coordinated-omission trap —
    # so its probe latency compares the two arms' ADMISSION POLICIES
    # (the fast path sheds at the socket, the componentwise chain
    # buffers), not the paths themselves. Paced below the knee, both
    # arms carry the identical offered load losslessly and the probe
    # measures pure path transit.
    # mutable so the --actuate overload can retune the offered load
    # MID-WINDOW (senders read it every iteration)
    pace = {"interval_s": 0.0}
    if args.pace_spans_per_sec:
        mean_batch = sum(batch_spans) / len(batch_spans)
        pace["interval_s"] = mean_batch * args.senders \
            / args.pace_spans_per_sec

    def sender(i: int) -> None:
        # retry cap 0.05: against shed-paced admission (ISSUE 9) the
        # REJECTED answer is the pacing signal, not an outage — the
        # pending_ms gate drains a ~13 ms frame every service interval,
        # so a sender sleeping 250 ms+ leaves reopened-gate capacity on
        # the floor (the throughput hole IS the tail latency); jittered
        # retries (wire/client.py) de-correlate the reopening stampede
        exp = WireExporter(exporter_names[i], {
            "endpoint": f"127.0.0.1:{port}", "queue_size": 64,
            "retry_initial_s": 0.01, "retry_max_s": 0.05,
            "max_elapsed_s": 60.0})
        exp.start()
        k = i
        next_t = time.monotonic()
        last_iv = pace["interval_s"]
        # exact span counts of the most recent enqueues: the overload
        # swaps batch sets mid-run, so the flush-failure residual walk
        # must remember what was ACTUALLY queued, not re-derive it from
        # one set's sizes (queue_size 64 bounds how far back matters)
        recent_spans: list = []
        while not stop.is_set():
            bset, bsp = active_set["cur"]  # one atomic reference read
            exp.export(bset[k % len(bset)])
            sent_spans[i] += bsp[k % len(bset)]
            recent_spans.append(bsp[k % len(bset)])
            if len(recent_spans) > 160:
                del recent_spans[:-80]  # keep > queue_size entries
            sent_batches[i] += 1
            k += args.senders
            # bounded in-flight: wait for the queue to drain enough that
            # "sent" means accepted-by-socket, not buffered locally
            while exp.queued > 32 and not stop.is_set():
                time.sleep(0.001)
            iv = pace["interval_s"]
            if iv:
                if iv != last_iv:
                    # the --actuate overload retuned the pace: re-anchor
                    # the absolute schedule so the new rate starts NOW
                    # instead of bursting to catch up on the old one
                    next_t = time.monotonic()
                    last_iv = iv
                # absolute-schedule pacing (no drift): a late export
                # shortens the next sleep instead of stretching the
                # whole schedule
                next_t += iv
                delay = next_t - time.monotonic()
                if delay > 0:
                    stop.wait(delay)
        ok = exp.flush(timeout=60.0)
        if not ok:
            # the residual queue holds the most recently enqueued
            # batches (FIFO drains from the front): sum the EXACT span
            # counts this sender recorded at enqueue time — batches
            # differ in span count per seed (and per overload set), so
            # any size re-derivation would mis-state conservation
            # precisely in the failure case this check exists to catch
            q = exp.queued
            dropped_spans[i] = sum(recent_spans[-q:]) if q else 0
        exp.shutdown()

    # ---- latency probe: wrap the terminal exporters to stamp arrival
    # of the distinctive probe batches (send -> export added latency)
    from odigos_tpu.pdata.spans import SpanBatchBuilder

    PROBE_SERVICE = "latency-probe"
    probe_sent: dict[int, float] = {}
    probe_seen: dict[int, float] = {}
    probe_lock = threading.Lock()

    def wrap_exporter(exp):
        orig = exp.consume

        def spy(b):
            if PROBE_SERVICE in b.strings:  # interned: one tuple scan
                now = time.perf_counter()
                with probe_lock:
                    for attrs in b.span_attrs:
                        seq = attrs.get("probe_seq")
                        if seq is not None and seq not in probe_seen:
                            probe_seen[int(seq)] = now
            return orig(b)

        exp.consume = spy

    anomaly = collector.graph.exporters["tracedb/anomaly"]
    normal = collector.graph.exporters["tracedb/normal"]
    wrap_exporter(anomaly)
    wrap_exporter(normal)

    probe_spans_sent = [0]

    def prober() -> None:
        # fast reprobe on REJECTED (3 ms initial backoff): the probe
        # measures the ACCEPTED path's added latency under load; with
        # shed-paced admission the gate flaps at its limit by design,
        # and a 20 ms-doubling backoff on a 1-span probe would measure
        # the probe client's own retry policy instead of the pipeline
        # (rejected_backoffs still reports every REJECTED honestly)
        # retry_max_s 0.012: gate-closed windows on this route are the
        # backlog gate's drain interval (tens of ms); a probe sleeping
        # past the reopening measures its own backoff ladder, not the
        # shed-window length — the cap keeps the sample inside one
        # reopening period while the workload senders keep their own
        # coarser 0.05 cap
        exp = WireExporter("otlpwire/probe", {
            "endpoint": f"127.0.0.1:{port}", "queue_size": 8,
            "retry_initial_s": 0.003, "retry_max_s": 0.012,
            "max_elapsed_s": 30.0})
        exp.start()
        seq = 0
        while not stop.is_set():
            b = SpanBatchBuilder()
            b.add_span(trace_id=0x50_0000 + seq, span_id=seq + 1,
                       name="probe", service=PROBE_SERVICE,
                       start_unix_nano=time.time_ns(),
                       end_unix_nano=time.time_ns() + 1000,
                       attrs={"probe_seq": seq})
            with probe_lock:
                probe_sent[seq] = time.perf_counter()
            exp.export(b.build())
            probe_spans_sent[0] += 1
            seq += 1
            stop.wait(0.1)
        exp.flush(timeout=30.0)
        exp.shutdown()

    # ---- actuator soak (ISSUE 15): arm the closed loop and inject a
    # mid-window OVERLOAD (offered load multiplied) that drives frames
    # past the tight admission deadline — expiries burn the
    # scored_fraction SLO and fire the expiry alert; the actuator's
    # held recommendation canaries a bounded deadline raise through the
    # incremental reload path, judges it, promotes it, and the burn
    # recovers with zero operator input. Every phase is timestamped
    # into ACTUATOR.json.
    actuate_events: list = []
    slo_timeline: list = []

    def _actuate_mark(event: str, **extra) -> None:
        actuate_events.append({"event": event,
                               "t_s": round(time.perf_counter() - t0,
                                            3), **extra})

    if args.actuate:
        from odigos_tpu.controlplane.actuator import fleet_actuator
        from odigos_tpu.selftelemetry.fleet import RecommendationRule

        fleet_actuator.register("soak-gateway", collector)
        fleet_plane.recommender.set_rules(tuple(
            RecommendationRule(**r) for r in ACTUATE_RULES))

    def overload_schedule() -> None:
        at = args.overload_at * args.seconds
        delay = at - (time.perf_counter() - t0)
        if delay > 0 and stop.wait(delay):
            return
        # the overload is STRUCTURAL, not just a rate step: bigger
        # frames (per-frame featurize/pack/score wall scales with span
        # count, landing past the tight deadline by construction) at
        # --overload-factor times the frame rate — a pure rate step
        # sits on a queueing knife edge and storms only on a noisy run
        size_mult = (sum(big_spans) / len(big_spans)) \
            / (sum(batch_spans) / len(batch_spans))
        active_set["cur"] = (big_batches, big_spans)
        # --overload-factor multiplies the FRAME rate; offered spans/s
        # rise by factor x the frame-size multiplier
        pace["interval_s"] = pace["interval_s"] / args.overload_factor
        _actuate_mark("overload_injected",
                      offered_spans_per_sec=round(
                          args.pace_spans_per_sec
                          * args.overload_factor * size_mult))
        # sustained to the end of the window: recovery must come from
        # the actuation, never from the overload politely leaving

    # ---- chaos schedule (ISSUE 13): faults injected MID-WINDOW on the
    # live pipeline — device loss at 20% (failover trips to the CPU
    # fallback), cleared at 45% (half-open probes recover); destination
    # outage on tracedb/normal at 55% (spans spill into the retry
    # queue), restored at 80% (backlog drains). Every event is
    # timestamped into the record; the oracle at the end is the same
    # as the scenario matrix: zero unexplained loss.
    chaos_events: list = []

    def _mark(event: str) -> None:
        chaos_events.append({"event": event,
                             "t_s": round(time.perf_counter() - t0, 3)})

    def chaos_schedule() -> None:
        T = args.seconds
        normal_wrap = collector.graph.exporters["tracedb/normal"]

        def outage(batch):
            raise RuntimeError("chaos soak: destination outage")

        # the soak injects faults directly (engine seam + exporter
        # monkeypatch), bypassing the e2e/chaos.py injectors that fire
        # the flight trigger — so the schedule freezes the incident
        # itself, same fault vocabulary as the INJECTORS registry
        def inject_device():
            engine.inject_device_fault("chaos soak: device lost")
            flight_recorder.trigger(
                "chaos_injection", fault="device_fault",
                detail="chaos soak: persistent device fault injected")

        def inject_outage():
            normal_wrap.inner.export = outage
            flight_recorder.trigger(
                "chaos_injection", fault="destination_outage",
                detail="chaos soak: tracedb/normal outage injected")

        plan = [
            (0.20 * T, "device_fault_injected", inject_device),
            (0.45 * T, "device_fault_cleared",
             lambda: engine.clear_device_fault()),
            (0.55 * T, "destination_outage_injected", inject_outage),
            (0.80 * T, "destination_outage_cleared",
             lambda: normal_wrap.inner.__dict__.pop("export", None)),
        ]
        for at_s, name, action in plan:
            delay = at_s - (time.perf_counter() - t0)
            if delay > 0 and stop.wait(delay):
                return
            action()
            _mark(name)

    # ---- reload storm (ISSUE 14): N single-knob reloads fired
    # MID-WINDOW on the live collector. Each one must take the
    # INCREMENTAL path (the threshold toggle is in tpuanomaly's
    # RECONFIGURABLE_KEYS): per-reload wall time, intake-gap evidence
    # (REJECTED backoffs + admission sheds + saturation during the
    # reload call), engine recompile count, and the changed-node
    # fingerprints land in the record — reload must read as a
    # data-plane non-event, measured.
    reload_events: list = []

    def _storm_counters() -> dict:
        snap = meter.snapshot()
        return {
            "rejected_backoffs": sum(
                v for k, v in snap.items()
                if k.startswith("odigos_exporter_backpressure_total")),
            "admission_rejected_frames": sum(
                v for k, v in snap.items()
                if k.startswith("odigos_admission_rejected_frames")),
            "saturated": sum(
                v for k, v in snap.items()
                if k.startswith("odigos_fastpath_saturated_total")),
            "reload_nodes": {
                action: snap.get(
                    f"odigos_collector_reload_nodes_total"
                    f"{{action={action}}}", 0.0)
                for action in ("kept", "reconfigured", "replaced")},
        }

    def reload_storm() -> None:
        import copy as _copy

        from odigos_tpu.models import jitstats
        from odigos_tpu.pipelinegen.builder import changed_node_hashes

        n = args.reload_storm
        for k in range(n):
            # spread across the middle 80% of the window — the storm
            # must hit steady state, not warmup or drain
            at = (0.1 + 0.8 * (k + 1) / (n + 1)) * args.seconds
            delay = at - (time.perf_counter() - t0)
            if delay > 0 and stop.wait(delay):
                return
            new_cfg = _copy.deepcopy(collector.config)
            new_cfg["processors"]["tpuanomaly"]["threshold"] = \
                0.6 + 0.001 * ((k % 2) + 1)
            changed = changed_node_hashes(collector.config, new_cfg)
            before = _storm_counters()
            compiles0 = sum(jitstats.cache_sizes().values())
            w0 = time.perf_counter()
            try:
                collector.reload(new_cfg)
                err = None
            except Exception as e:  # noqa: BLE001 — record, keep storming
                err = f"{type(e).__name__}: {e}"[:200]
            wall_ms = (time.perf_counter() - w0) * 1e3
            after = _storm_counters()
            reload_events.append({
                "reload": k,
                "at_s": round(time.perf_counter() - t0, 3),
                "wall_ms": round(wall_ms, 3),
                "error": err,
                "changed_nodes": changed,
                "nodes": {a: int(after["reload_nodes"][a]
                                 - before["reload_nodes"][a])
                          for a in before["reload_nodes"]},
                # intake-gap evidence ACROSS the reload call: REJECTED
                # answers the senders rode, pre-decode sheds, and
                # fast-path saturation — all must stay flat for the
                # swap to count as a non-event (paced below the knee
                # nothing else sheds)
                "intake_gap": {
                    key: int(after[key] - before[key])
                    for key in ("rejected_backoffs",
                                "admission_rejected_frames",
                                "saturated")},
                "recompiles": int(
                    sum(jitstats.cache_sizes().values()) - compiles0),
            })

    # ---- fused kill-switch slice (ISSUE 19): ODIGOS_FUSED=0 flipped
    # MID-WINDOW at 40% of the run and restored at 60% — the env var is
    # read per frame, so the flip lands on the very next frame with no
    # reload. The slice proves the big red button live: every frame in
    # it falls back to the bit-identical host route (reason=disabled),
    # nothing is lost, and fused dispatch resumes on restore. Counter
    # snapshots at both boundaries are the evidence.
    fused_events: list = []

    def _fused_counters() -> dict:
        from odigos_tpu.serving.fastpath import (FUSED_FALLBACK_METRIC,
                                                 FUSED_FRAMES_METRIC)

        return {
            "fused_frames_total": int(meter.counter(labeled_key(
                FUSED_FRAMES_METRIC, pipeline="traces/in"))),
            "disabled_fallbacks_total": int(meter.counter(labeled_key(
                FUSED_FALLBACK_METRIC, pipeline="traces/in",
                reason="disabled"))),
        }

    def fused_kill_schedule() -> None:
        T = args.seconds
        for at_s, action in ((0.40 * T, "kill"), (0.60 * T, "restore")):
            delay = at_s - (time.perf_counter() - t0)
            if delay > 0 and stop.wait(delay):
                return
            if action == "kill":
                os.environ["ODIGOS_FUSED"] = "0"
            else:
                os.environ.pop("ODIGOS_FUSED", None)
            fused_events.append({
                "event": f"kill_switch_{action}",
                "t_s": round(time.perf_counter() - t0, 3),
                **_fused_counters()})

    # ---- device-attribution kill slice (ISSUE 20): ODIGOS_DEVICE_ATTRIB=0
    # flipped at 10% of the run and restored at 35% — BEFORE the fused
    # kill slice (40-60%), deliberately: with ODIGOS_FUSED=0 the fused
    # route dispatches no columns at all, so the attribution sampler
    # ticks no ordinals and a slice overlapping it would starve the
    # fell-back evidence. While killed, every sampled tick is counted
    # under skipped{reason=disabled} and the frame runs the plain fused
    # call; on restore, sampling resumes on the very next aligned tick.
    # Sampler-counter snapshots at both boundaries are the evidence.
    device_events: list = []

    def _attrib_counters() -> dict:
        a = getattr(engine.backend, "_attrib", None)
        st = a.stats() if a is not None else {}
        return {
            "frames_seen": int(st.get("frames_seen", 0)),
            "sampled": int(st.get("sampled", 0)),
            "skipped_disabled": int(
                (st.get("skipped") or {}).get("disabled", 0)),
        }

    def device_kill_schedule() -> None:
        T = args.seconds
        for at_s, action in ((0.10 * T, "kill"), (0.35 * T, "restore")):
            delay = at_s - (time.perf_counter() - t0)
            if delay > 0 and stop.wait(delay):
                return
            if action == "kill":
                os.environ["ODIGOS_DEVICE_ATTRIB"] = "0"
            else:
                os.environ.pop("ODIGOS_DEVICE_ATTRIB", None)
            device_events.append({
                "event": f"attrib_kill_{action}",
                "t_s": round(time.perf_counter() - t0, 3),
                **_attrib_counters()})

    threads = [threading.Thread(target=sender, args=(i,), daemon=True)
               for i in range(args.senders)]
    probe_thread = threading.Thread(target=prober, daemon=True)
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    probe_thread.start()
    chaos_thread = None
    if args.chaos:
        chaos_thread = threading.Thread(target=chaos_schedule,
                                        daemon=True)
        chaos_thread.start()
    storm_thread = None
    if args.reload_storm:
        storm_thread = threading.Thread(target=reload_storm,
                                        daemon=True)
        storm_thread.start()
    overload_thread = None
    if args.actuate:
        overload_thread = threading.Thread(target=overload_schedule,
                                           daemon=True)
        overload_thread.start()
    fused_thread = None
    if args.fused and fast_path:
        fused_thread = threading.Thread(target=fused_kill_schedule,
                                        daemon=True)
        fused_thread.start()
    device_thread = None
    if args.device_attrib and fast_path:
        device_thread = threading.Thread(target=device_kill_schedule,
                                         daemon=True)
        device_thread.start()
    # fleet publish/evaluate cadence (ISSUE 10): the soak's main wait
    # doubles as the plane timer — each tick delta-publishes the
    # collector's snapshot + rollup under {collector=} and advances the
    # alert engine, so SOAK.json's alert states/history come from the
    # loop running live under load, not a post-hoc evaluation
    t_end = time.monotonic() + args.seconds
    while time.monotonic() < t_end:
        fleet_plane.publish_collector(collector, "soak-gateway",
                                      group="soak")
        fleet_plane.tick()  # advances alerts AND the armed actuator
        if args.actuate:
            # the SLO-burn timeline: the record must show the burn
            # rising under the overload and recovering after the
            # promotion, sampled live — not re-derived post hoc
            slo = latency_ledger.slo_status().get("traces/in") or {}
            slo_timeline.append({
                "t_s": round(time.perf_counter() - t0, 3),
                "burning": bool(slo.get("burning")),
                "fast_burn": (slo.get("fast") or {}).get("burn"),
                "deadline_ms": collector.config["service"][
                    "pipelines"]["traces/in"]["fast_path"][
                    "deadline_ms"],
                "actuator_state": fleet_actuator.state,
            })
        time.sleep(min(0.5, max(t_end - time.monotonic(), 0.0)))
    stop.set()
    for t in threads:
        t.join(timeout=90)
    probe_thread.join(timeout=60)
    if storm_thread is not None:
        storm_thread.join(timeout=60)
    if overload_thread is not None:
        overload_thread.join(timeout=10)
    if fused_thread is not None:
        fused_thread.join(timeout=10)
        # never leak the kill switch past the run (a --ab / --find-knee
        # follow-up soak in this process must start with fused armed)
        os.environ.pop("ODIGOS_FUSED", None)
    if device_thread is not None:
        device_thread.join(timeout=10)
        os.environ.pop("ODIGOS_DEVICE_ATTRIB", None)
    if chaos_thread is not None:
        chaos_thread.join(timeout=10)
        # belt and braces: the schedule clears its own faults, but a
        # short run may end mid-fault — the record must measure the
        # RECOVERED pipeline's ledger, not a wedged one
        engine.clear_device_fault()
        collector.graph.exporters["tracedb/normal"].inner.__dict__.pop(
            "export", None)
    collector.drain_receivers(timeout=60.0)
    if args.chaos:
        # the spill queues must drain before "received" is read — a
        # batch still in flight through the retry ladder is pending,
        # not lost
        for eid in ("tracedb/anomaly", "tracedb/normal"):
            collector.graph.exporters[eid].flush(timeout=60.0)
    elapsed = time.perf_counter() - t0

    received = (anomaly.span_count + normal.span_count
                - len(probe_seen))  # probe spans are not workload spans
    sent = sum(sent_spans) - sum(dropped_spans)

    # ---- per-sender matrix: throughput, client-side backoff evidence
    per_sender = []
    for i in range(args.senders):
        name = exporter_names[i]
        per_sender.append({
            "sender": name,
            "spans_sent": int(sent_spans[i] - dropped_spans[i]),
            "batches_sent": int(sent_batches[i]),
            "spans_per_sec": round(
                (sent_spans[i] - dropped_spans[i]) / elapsed, 1),
            "spans_dropped_client": int(dropped_spans[i]),
            # REJECTED answers observed by this sender (each one a
            # backoff + retry of the same frame)
            "rejected_backoffs": int(meter.counter(
                f"odigos_exporter_backpressure_total"
                f"{{exporter={name}}}")),
            "frames_dropped_client": int(meter.counter(labeled_key(
                "odigos_exporter_dropped_frames_total", exporter=name))),
        })

    # ---- ledger evidence: drop-reason breakdown + conservation verdict
    snap = flow_ledger.snapshot()
    drop_reasons: dict[str, int] = {}
    drops_by_site = []
    for d in snap["drops"]:
        for reason, n in d["reasons"].items():
            drop_reasons[reason] = drop_reasons.get(reason, 0) + n
        drops_by_site.append({
            "pipeline": d["pipeline"], "component": d["component"],
            "signal": d["signal"], "reasons": dict(d["reasons"])})
    balances = flow_ledger.conservation()
    # terminal drops the export retry queues NAMED (chaos mode): those
    # spans left the pipeline and were accounted — explained, not lost
    retry_dropped = sum(
        collector.graph.exporters[eid].stats()["dropped_spans"]
        for eid in ("tracedb/anomaly", "tracedb/normal")) \
        if args.chaos else 0
    conserved = (received + retry_dropped == sent) and all(
        b["leak"] == 0 for b in balances.values())
    admission_rejected = {
        k.split("reason=", 1)[1].rstrip("}"): int(v)
        for k, v in meter.snapshot().items()
        if k.startswith("odigos_admission_rejected_frames_total{")}

    # ---- latency attribution (ISSUE 8): the per-stage waterfall and
    # SLO burn verdicts, read BEFORE shutdown (the rollup evaluates the
    # live graph) so every soak run is self-attributing
    stage_waterfall = latency_ledger.waterfall()
    burn_tables = latency_ledger.burn()
    # frame-weighted IN-PIPELINE e2e percentiles (acceptance→forward,
    # every frame, thousands of samples) beside the probe's wire-level
    # view: the ~200-sample probe p99 on a shared CI box is decided by
    # 2-3 scheduler-stall/retry-ladder outliers, while this histogram
    # measures exactly the path the steady-state work changed
    pipeline_e2e = None
    if fast_path:
        e2e_key = labeled_key("odigos_latency_e2e_ms",
                              pipeline="traces/in")
        p50 = meter.quantile(e2e_key, 0.50)
        if p50:
            p99 = meter.quantile(e2e_key, 0.99)
            pipeline_e2e = {
                "p50_ms": round(p50, 2),
                "p95_ms": round(meter.quantile(e2e_key, 0.95), 2),
                "p99_ms": round(p99, 2),
                "frames": latency_ledger.recorder("traces/in").frames,
                "p99_over_p50": round(p99 / p50, 2),
            }
    slo_verdicts = latency_ledger.slo_status()
    slo_conditions = [c for c in collector.health_conditions()
                     if c["component"].startswith("slo/")]

    # fleet rollup + alert loop evidence (ISSUE 10), read BEFORE
    # shutdown: per-collector health, worst-of per group, every rule's
    # final state, the full fired/cleared transition history, and any
    # sizing recommendations the run's gauges triggered
    # steady-state memory evidence (ISSUE 12), read BEFORE shutdown:
    # buffer-pool miss rate (the allocations-per-frame ≈ 0 claim under
    # real wire load), GC pause accounting (the "pauses left the
    # waterfall" claim), and the predictive-shed tally
    pool_agg = None
    engine_pool = None
    if fast_path:
        fp_route = collector.graph.fastpaths.get("traces/in")
        if fp_route is not None:
            pool_agg = fp_route.pool_stats()
            # the engine's pack-stage pool misses count toward the same
            # allocs-per-frame claim (bench.py's steady_state_allocs
            # sums both) — omitting them would let a pack-pool
            # regression hide behind a clean lane-pool number
            engine_pool = fp_route.engine.pack_pool_stats()
    gc_stats = gc_plane.stats()
    predicted_spans = sum(
        int(v) for k, v in meter.snapshot().items()
        if k.startswith("odigos_latency_deadline_expired_spans_total")
        and "blame=predicted" in k)
    steady_state = {
        "gc": gc_stats,
        "predicted_shed_spans": predicted_spans,
    }
    if pool_agg is not None:
        steady_state["buffer_pools"] = pool_agg
        steady_state["engine_pack_pool"] = engine_pool
        steady_state["allocs_per_frame"] = round(
            (pool_agg["misses"]
             + (engine_pool["misses"] if engine_pool else 0))
            / pool_agg["leases"], 4) \
            if pool_agg["leases"] else None

    # fused-route evidence (ISSUE 19), read BEFORE shutdown: frames
    # fused vs fallback (per named reason), the pre-window parity-gate
    # verdict, the kill-switch slice timeline with its two acceptance
    # verdicts (the slice actually fell back; fused dispatch resumed
    # after restore), and the per-frame host wall delta the run itself
    # measured — the fused stage's mean against featurize+pack from the
    # host-route frames (the kill slice and fallbacks supply them)
    fused_summary = None
    if args.fused and fast_path:
        from odigos_tpu.serving.fastpath import FUSED_FALLBACK_METRIC
        from odigos_tpu.serving.fused import FALLBACK_REASONS

        counters = _fused_counters()
        fallbacks = {}
        for reason in FALLBACK_REASONS:
            v = int(meter.counter(labeled_key(
                FUSED_FALLBACK_METRIC, pipeline="traces/in",
                reason=reason)))
            if v:
                fallbacks[reason] = v
        wf_in = latency_ledger.recorder("traces/in").waterfall()

        # p50, not mean: a fresh coalesce shape pays its XLA compile
        # INSIDE the fused stage stamp mid-run (the host ladder warmed
        # at start), and on a shared box 2-3 compile outliers decide
        # the mean — the median is the steady-state frame both claims
        # are about
        def _p50(stage):
            return (wf_in.get(stage, {}) or {}).get("p50_ms")

        host_ms = None
        if _p50("featurize") is not None:
            host_ms = round((_p50("featurize") or 0.0)
                            + (_p50("pack") or 0.0), 3)
        fused_ms = _p50("fused")
        ev = {e["event"]: e for e in fused_events}
        kill, restore = (ev.get("kill_switch_kill"),
                         ev.get("kill_switch_restore"))
        fused_summary = {
            "frames_fused": counters["fused_frames_total"],
            "frames_fallback": fallbacks,
            "parity_gate": fused_parity,
            "kill_switch": fused_events,
            # the slice's frames all fell back, counted as disabled
            "kill_switch_fell_back": bool(
                kill and restore
                and restore["disabled_fallbacks_total"]
                > kill["disabled_fallbacks_total"]),
            # and the route came back after restore
            "resumed_after_restore": bool(
                restore and counters["fused_frames_total"]
                > restore["fused_frames_total"]),
            # per-frame HOST wall, from this run's own waterfall: the
            # fused stage (column staging -> device enqueue) vs the host
            # frames' featurize+pack, median frame each
            "host_stage_p50_ms": host_ms,
            "fused_stage_p50_ms": fused_ms,
            "host_wall_delta_p50_ms": (round(host_ms - fused_ms, 3)
                                       if host_ms is not None
                                       and fused_ms is not None
                                       else None),
            "conservation": bool(conserved),
        }

    # device-plane evidence (ISSUE 20), read BEFORE shutdown: the
    # sampler's own counters, the folded sub-stage burn table with its
    # fused-stamp reconcile ratio, the XLA cost/efficiency ledger rows
    # for every bucket the route warmed, the compile-event ring (each
    # event carrying the trace id of the frame that paid it), the
    # kill-slice timeline, and the /api/device snapshot — plus the
    # acceptance verdicts main() gates the exit code on
    device_summary = None
    if args.device_attrib and fast_path:
        from odigos_tpu.models import jitstats
        from odigos_tpu.models.costmodel import cost_ledger
        from odigos_tpu.selftelemetry.profiler import device_snapshot
        from odigos_tpu.serving.deviceattrib import SUB_STAGES

        attrib = getattr(engine.backend, "_attrib", None)
        astats = attrib.stats() if attrib is not None else {}
        burn = latency_ledger.recorder("traces/in").device_burn()
        cost = cost_ledger.snapshot()
        compiles = jitstats.recent_compiles()
        # buckets the fused route actually warmed this run, in the
        # ledger's r{rows}x{len} labeling (the LRU keys are (span
        # bucket, padded rows))
        warmed = sorted(
            "r{}x{}".format(r, engine.backend.max_len)
            for (_n, r) in getattr(engine.backend, "_fused_shapes", {}))
        cost_buckets = {r["bucket"] for r in cost["rows"]}
        devents = {e["event"]: e for e in device_events}
        dkill, drestore = (devents.get("attrib_kill_kill"),
                           devents.get("attrib_kill_restore"))
        reconcile = (burn or {}).get("reconcile_ratio")
        lo, hi = DEVICE_RECONCILE_BOUNDS
        device_summary = {
            "stride": astats.get("stride"),
            "sampler": astats,
            "device_burn": burn,
            "cost_ledger": cost,
            "compile_events": compiles,
            "device_plane": device_snapshot(),
            "kill_switch": device_events,
            "warmed_buckets": warmed,
            # the sampled waterfall exists and speaks only the closed
            # sub-stage vocabulary
            "waterfall_nonempty": bool(
                burn and burn.get("sampled_frames", 0) >= 1
                and set(burn.get("stages", {})) == set(SUB_STAGES)),
            # sampled sub-stage sum vs the opaque fused stamp
            "reconcile_ratio": reconcile,
            "reconcile_bounds": [lo, hi],
            "reconcile_ok": bool(reconcile is not None
                                 and lo <= reconcile <= hi),
            # the kill slice actually fell back (disabled skips grew
            # across it) and sampling resumed after restore
            "kill_switch_fell_back": bool(
                dkill and drestore
                and drestore["skipped_disabled"]
                > dkill["skipped_disabled"]),
            "resumed_after_restore": bool(
                drestore and int(astats.get("sampled", 0))
                > drestore["sampled"]),
            # every warmed bucket has a cost/efficiency row (captured
            # at the cold dispatch that warmed it)
            "cost_rows_cover_buckets": bool(
                warmed and set(warmed) <= cost_buckets),
            # at least one compile event names the frame that paid it
            "compile_event_with_trace": any(
                e.get("trace_id") for e in compiles),
        }

    # chaos evidence (ISSUE 13), read BEFORE shutdown: the injected
    # fault timeline, the breaker's transitions, the retry queues'
    # ledgers, and the explicit zero-unexplained-loss verdict the
    # acceptance asks for — sent == received + every NAMED terminal
    # drop, with every pipeline balance exact
    chaos_summary = None
    if args.chaos:
        retry_stats = {
            eid: collector.graph.exporters[eid].stats()
            for eid in ("tracedb/anomaly", "tracedb/normal")}
        # flight-recorder verdict (ISSUE 16): each injected fault froze
        # exactly one chaos_injection incident; consequence incidents
        # (the breaker tripping, the chaos alerts firing) are expected;
        # anything else — or a chaos incident naming a fault nobody
        # injected — is spurious and fails the run
        expected_faults = {"device_fault", "destination_outage"}
        benign_triggers = {"chaos_injection", "breaker_trip",
                           "alert_firing"}
        bundles = flight_recorder.incidents()
        fault_counts: dict = {}
        for b in bundles:
            if b["trigger"] == "chaos_injection":
                f = b.get("fault")
                fault_counts[f] = fault_counts.get(f, 0) + 1
        incidents_missing = sorted(
            f for f in expected_faults if fault_counts.get(f, 0) != 1)
        incidents_spurious = sorted(
            f"chaos_injection:{f}" for f in fault_counts
            if f not in expected_faults) + sorted(
            f"{b['trigger']}:{b['id']}" for b in bundles
            if b["trigger"] not in benign_triggers)
        chaos_summary = {
            "seed": args.chaos_seed,
            "events": chaos_events,
            "failover": engine.failover_status(),
            "export_retry": retry_stats,
            "retry_dropped_spans": retry_dropped,
            # the acceptance verdict: every span either delivered or
            # carries a named reason, and every balance closed exactly
            "zero_unexplained_loss": bool(conserved),
            # the frozen incident store, summarized (full bundles live
            # in a diagnose archive, not a perf record)
            "incidents": flight_recorder.api_snapshot()["incidents"],
            "incidents_missing": incidents_missing,
            "incidents_spurious": incidents_spurious,
            "incident_verdict": not incidents_missing
            and not incidents_spurious,
        }

    # actuator evidence (ISSUE 15), read BEFORE shutdown: the full
    # alert->proposal->canary->promotion timeline with per-step reload
    # modes, the SLO-burn recovery trace, and the acceptance verdicts
    actuator_summary = None
    if args.actuate:
        from odigos_tpu.selftelemetry.fleet import alert_engine

        act_snap = fleet_actuator.api_snapshot()
        wall_anchor = time.time() - (time.perf_counter() - t0)
        timeline = list(actuate_events)
        for ev in alert_engine.transitions():
            timeline.append({
                "event": f"alert_{ev['event']}", "rule": ev["rule"],
                "t_s": round(ev["unix_ts"] - wall_anchor, 3)})
        for h in act_snap["history"]:
            ts = h.get("ts") or {}
            for phase in ("proposed", "canary", "judged", "finished"):
                if phase in ts:
                    timeline.append({
                        "event": (h["outcome"] if phase == "finished"
                                  else phase),
                        "rule": h["rule"], "knob": h["knob"],
                        "t_s": round(ts[phase] - wall_anchor, 3)})
        timeline.sort(key=lambda e: e["t_s"])
        promoted = [h for h in act_snap["history"]
                    if h["outcome"] == "promoted"]
        reload_modes = [h.get("reload_mode") for h in promoted] + [
            s.get("reload_mode") for h in promoted
            for s in h.get("steps") or []
            if s.get("reload_mode") is not None]
        burned = any(s["burning"] for s in slo_timeline)
        final_burning = (slo_timeline[-1]["burning"]
                         if slo_timeline else None)
        actuator_summary = {
            "config": act_snap["config"],
            "timeline": timeline,
            "history": act_snap["history"],
            "slo_timeline": slo_timeline,
            "deadline_ms_final": collector.config["service"][
                "pipelines"]["traces/in"]["fast_path"]["deadline_ms"],
            "reload_modes": reload_modes,
            # the acceptance verdicts (main() gates the exit code)
            "promoted": len(promoted),
            "rollbacks": len([h for h in act_snap["history"]
                              if "rolled_back" in h["outcome"]]),
            "refusals": len([h for h in act_snap["history"]
                             if h["outcome"] == "refused"]),
            "all_reloads_incremental": bool(reload_modes) and all(
                m == "incremental" for m in reload_modes),
            "slo_burned_under_overload": burned,
            "slo_recovered": bool(burned and final_burning is False),
        }
        fleet_actuator.unregister("soak-gateway")
        fleet_plane.recommender.set_rules(None)

    fleet_snap = fleet_plane.api_snapshot()
    fleet_summary = {
        "collectors": [
            {k: co[k] for k in ("collector", "group", "status",
                                "reason", "series_published",
                                "series_skipped")}
            for co in fleet_snap["collectors"]],
        "groups": fleet_snap["groups"],
        "alert_rules": fleet_snap["alerts"]["rules"],
        "alert_transitions": fleet_snap["alerts"]["history"],
        "recommendations": fleet_snap["recommendations"],
        "series_store": {k: fleet_snap["store"][k]
                         for k in ("series", "metrics",
                                   "dropped_series")},
    }

    # flight recorder (ISSUE 16), read BEFORE shutdown: incident counts
    # ride every record — a CLEAN soak must freeze nothing (main()
    # gates plain runs on it; incidents on a fault-free run mean either
    # a real regression or a trigger misfiring)
    fr_snap = flight_recorder.api_snapshot()
    flight_summary = {
        "enabled": fr_snap["enabled"],
        "events_total": fr_snap["events_total"],
        "suppressed": fr_snap["suppressed"],
        "incidents": fr_snap["incidents"],
    }

    collector.shutdown()

    import numpy as np

    lat_ms = np.array([
        (probe_seen[k] - probe_sent[k]) * 1e3
        for k in probe_seen if k in probe_sent])

    result = {
        "metric": "e2e_wire_spans_per_sec",
        "value": round(received / elapsed, 1),
        "unit": "spans/s",
        "elapsed_s": round(elapsed, 2),
        "senders": args.senders,
        # open-loop offered load (None = closed-loop saturation): both
        # A/B arms carry the same paced load, so the probe compares
        # path transit, not admission policy
        "offered_spans_per_sec": args.pace_spans_per_sec or None,
        "fast_path": fast_path,
        "fast_path_lanes": args.lanes if fast_path else None,
        "fast_path_submit_lanes": (args.submit_lanes or args.lanes)
        if fast_path else None,
        "fast_path_ordered": bool(args.ordered) if fast_path else None,
        "model": args.model,
        # platform / device_kind / count as JAX reports them ("device"
        # below is the --device-attrib summary)
        "jax_device": device,
        "geometry": ("cpu-miniature" if args.cpu_geometry
                     else "flagship") if args.model == "transformer"
        else None,
        "mesh": _parse_mesh(args.mesh) if args.mesh else None,
        "spans_sent": int(sent),
        "spans_received": int(received),
        "conservation": bool(conserved),
        "anomaly_spans": int(anomaly.span_count),
        "per_sender": per_sender,
        # every shed named: the ledger's reason taxonomy rollup plus the
        # per-site breakdown and the receiver's pre-decode admission
        # counters ({watermark}:{queue} -> frames)
        "drop_reasons": drop_reasons,
        "drops_by_site": drops_by_site,
        "admission_rejected_frames": admission_rejected,
        "pipeline_balance": {
            p: {"items_in": b["items_in"], "items_out": b["items_out"],
                "dropped": b["dropped"], "failed": b["failed"],
                "pending": b["pending"], "leak": b["leak"]}
            for p, b in balances.items()},
        # per-stage latency attribution (ISSUE 8): where the wall went
        # per frame across admission/decode/featurize/queue/pack/device/
        # harvest/wait/tag/forward, the deadline-burn table (fraction of
        # budget per stage + expiry blames), and the SLO burn verdict —
        # the soak judges itself instead of leaving a bare p99
        "stage_waterfall": stage_waterfall,
        "deadline_burn": burn_tables,
        "slo": slo_verdicts,
        "slo_conditions": slo_conditions,
        # the fleet plane's view of the run (ISSUE 10): collector
        # rollup, alert rule states + fired/cleared transitions, and
        # sizing recommendations — the soak proves the alert loop e2e
        "fleet": fleet_summary,
        # added latency through the LOADED pipeline (probe stream,
        # send -> terminal exporter; includes wire, admission, adaptive
        # batching, zscore scoring, routing)
        "probes_sent": int(probe_spans_sent[0]),
        "probes_delivered": int(len(lat_ms)),
        "latency_p50_ms": (round(float(np.percentile(lat_ms, 50)), 2)
                           if len(lat_ms) else None),
        "latency_p95_ms": (round(float(np.percentile(lat_ms, 95)), 2)
                           if len(lat_ms) else None),
        "latency_p99_ms": (round(float(np.percentile(lat_ms, 99)), 2)
                           if len(lat_ms) else None),
        # the tail-vs-median verdict (ISSUE 12 acceptance: ≤ 3 at the
        # measured knee for the fast path; evaluate on pipeline_e2e —
        # frame-weighted over every frame — with the probe ratio as
        # the wire-level witness)
        "p99_over_p50": (round(
            float(np.percentile(lat_ms, 99))
            / max(float(np.percentile(lat_ms, 50)), 1e-9), 2)
            if len(lat_ms) else None),
        "pipeline_e2e_ms": pipeline_e2e,
        # zero-allocation + GC-isolation evidence (ISSUE 12)
        "steady_state": steady_state,
        # incremental hot reload under load (ISSUE 14): per-reload wall
        # time, node action counts, intake-gap deltas across each
        # reload call, and engine recompiles (must be zero — the warm
        # ladder survives a knob change)
        "reload_storm": ({
            "reloads": reload_events,
            "count": len(reload_events),
            "max_wall_ms": max((e["wall_ms"] for e in reload_events),
                               default=None),
            "all_incremental": all(
                e["nodes"]["replaced"] == 0 and e["error"] is None
                and e["nodes"]["reconfigured"] >= 1
                for e in reload_events),
            "total_intake_gap": {
                key: sum(e["intake_gap"][key] for e in reload_events)
                for key in ("rejected_backoffs",
                            "admission_rejected_frames", "saturated")},
            "recompiles_total": sum(e["recompiles"]
                                    for e in reload_events),
        } if args.reload_storm else None),
        # chaos fault timeline + degradation evidence (ISSUE 13)
        "chaos": chaos_summary,
        # flight-recorder black box (ISSUE 16): always-on counters and
        # the frozen incident store at end of run
        "flight": flight_summary,
        # closed-loop actuation evidence (ISSUE 15): the overload ->
        # alert -> proposal -> canary -> promotion timeline, per-step
        # reload modes (must ALL be incremental), and the SLO burn's
        # rise-and-recovery trace
        "actuator": actuator_summary,
        # fused-route evidence (ISSUE 19): frames fused vs fallback,
        # parity-gate verdict, kill-switch slice, host wall delta
        "fused": fused_summary,
        "device": device_summary,
        "latency_note": ("probe batches ride the same wire/pipeline as "
                         "the load; p* = send-to-export wall time under "
                         f"full multi-sender soak load, {args.model} "
                         f"scoring path on {device['platform']}"
                         + (", ingest fast path + watermark admission"
                            if fast_path else ", componentwise chain")),
    }
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--senders", type=int, default=4)
    ap.add_argument("--traces-per-batch", type=int, default=256)
    ap.add_argument("--no-fast-path", action="store_true",
                    help="A/B: the componentwise chain instead of the "
                         "ingest fast path")
    ap.add_argument("--ab", action="store_true",
                    help="run fast path AND componentwise back to back; "
                         "embed the componentwise summary in the record")
    ap.add_argument("--deadline-ms", type=float, default=100.0,
                    help="fast-path admission deadline per frame")
    ap.add_argument("--lanes", type=int, default=4,
                    help="fast-path retirement lanes (ISSUE 9): "
                         "completion-driven tag/forward overlap")
    ap.add_argument("--submit-lanes", type=int, default=0,
                    help="fast-path submit-lane pool (featurize + "
                         "engine submit); 0 = same as --lanes. The "
                         "pools bound different legs, so a host-"
                         "contended box may want them sized apart "
                         "(more submit threads than cores just adds "
                         "featurize contention)")
    ap.add_argument("--backlog-ms", type=float, default=60.0,
                    help="admission-gate limit on the fast path's "
                         "backlog_ms watermark (age of the oldest frame "
                         "no submit lane has started); now that intake "
                         "is handoff-only the gate is the sole pacing "
                         "signal, so this IS the standing-queue budget. "
                         "Gating on pending_ms (head age of unretired "
                         "frames) would shed on the frame's own "
                         "processing wall — 2-3x throughput loss on a "
                         "slow box")
    ap.add_argument("--pace-spans-per-sec", type=float, default=0.0,
                    help="open-loop offered load, spans/s across all "
                         "senders (0 = closed-loop saturation). Paced "
                         "below the knee both A/B arms carry IDENTICAL "
                         "load losslessly, so the probe compares path "
                         "transit instead of admission policy — the "
                         "saturating mode's probe rides each arm's own "
                         "backpressure (coordinated omission)")
    ap.add_argument("--max-pending-spans", type=int, default=128 * 1024,
                    help="fast path's hard pending-window bound; the "
                         "pending_spans admission watermark gates at "
                         "3/4 of it. Size it in FRAMES: large "
                         "--traces-per-batch needs a wider window for "
                         "the same in-flight frame count")
    ap.add_argument("--engine-queue-depth", type=int, default=8,
                    help="admission-gate limit on the engine's request-"
                         "queue depth watermark (applies to both A/B "
                         "arms; the engine queue is where latency hides "
                         "from the backlog_ms gate)")
    ap.add_argument("--ordered", action="store_true",
                    help="forward downstream in intake order (single-"
                         "forwarder FIFO contract) instead of "
                         "as-completed")
    ap.add_argument("--slo-p99-ms", type=float, default=1000.0,
                    help="declared latency_p99_ms SLO objective for the "
                         "fast-path pipeline (burn verdict in SOAK.json)")
    ap.add_argument("--no-predictive", action="store_true",
                    help="disable predictive deadline-burn shed "
                         "(ISSUE 12): frames priced past the deadline "
                         "are otherwise REJECTED at intake/pre-decode "
                         "with blame=predicted")
    ap.add_argument("--find-knee", action="store_true",
                    help="sweep offered load (short paced probes) to "
                         "locate the throughput knee, then record the "
                         "full run AT the knee (sets "
                         "--pace-spans-per-sec); SOAK.json embeds "
                         "knee_spans_per_sec + the sweep table")
    ap.add_argument("--knee-start", type=float, default=60_000.0,
                    help="first offered load of the knee sweep")
    ap.add_argument("--knee-factor", type=float, default=1.3,
                    help="geometric step between sweep levels")
    ap.add_argument("--knee-max", type=float, default=600_000.0,
                    help="sweep ceiling")
    ap.add_argument("--knee-seconds", type=float, default=5.0,
                    help="probe duration per sweep level")
    ap.add_argument("--knee-delivery", type=float, default=0.98,
                    help="min delivered/offered fraction that still "
                         "counts as below the knee; the knee is the "
                         "highest level the pipeline carries "
                         "essentially losslessly (2% shed = the knee "
                         "is behind you — a looser bound lands the "
                         "'knee' deep in the overload regime where "
                         "tails are governed by shed policy, not by "
                         "the path)")
    ap.add_argument("--chaos", action="store_true",
                    help="inject faults MID-WINDOW (ISSUE 13): device "
                         "loss at 20%% of the run (failover breaker "
                         "trips to the zscore fallback, recovers after "
                         "the 45%% clear) and a destination outage at "
                         "55%% (spans spill into the export retry "
                         "queue, drain after the 80%% restore); "
                         "records CHAOS.json instead of SOAK.json "
                         "with the fault timeline, breaker/retry "
                         "evidence, and the zero-unexplained-loss "
                         "verdict")
    ap.add_argument("--reload-storm", type=int, default=0,
                    help="fire N single-knob hot reloads MID-WINDOW "
                         "(ISSUE 14): each toggles the tpuanomaly "
                         "threshold (an incremental-path knob) on the "
                         "live collector and records per-reload wall "
                         "time, intake-gap deltas (REJECTED backoffs, "
                         "pre-decode sheds, fast-path saturation "
                         "across the reload call), node action "
                         "counts, changed-node fingerprints and "
                         "engine recompile count into SOAK.json's "
                         "reload_storm section")
    ap.add_argument("--actuate", action="store_true",
                    help="arm the closed-loop actuator (ISSUE 15) and "
                         "inject a mid-window OVERLOAD (offered load x "
                         "--overload-factor at --overload-at of the "
                         "window, sustained to the end): the tight "
                         "--actuate-deadline-ms expires frames, the "
                         "scored_fraction SLO burns and the expiry "
                         "alert fires, the actuator canaries a bounded "
                         "fast_path.deadline_ms raise through the "
                         "INCREMENTAL reload path, judges and promotes "
                         "it, and the burn recovers with zero operator "
                         "input; records ACTUATOR.json (timeline, "
                         "per-step reload mode, SLO recovery, "
                         "conservation) — non-zero exit if no "
                         "promotion, any non-incremental reload, or "
                         "no SLO recovery. Requires "
                         "--pace-spans-per-sec (the overload is a "
                         "paced-load step)")
    ap.add_argument("--actuate-deadline-ms", type=float, default=25.0,
                    help="initial fast_path admission deadline for "
                         "--actuate: sized to the BASELINE pace, "
                         "under-sized for the overload")
    ap.add_argument("--overload-at", type=float, default=0.35,
                    help="fraction of the window at which --actuate "
                         "multiplies the offered load")
    ap.add_argument("--overload-factor", type=float, default=1.25,
                    help="FRAME-rate multiplier for the --actuate "
                         "overload (sustained to the end of the run); "
                         "the overload also switches to "
                         "--overload-size-mult-sized frames, so "
                         "offered spans/s rise ~size_mult x this. "
                         "Size baseline x size_mult x factor BELOW "
                         "the box's knee: the knob, not capacity, "
                         "must be the thing the actuator fixes")
    ap.add_argument("--overload-size-mult", type=int, default=16,
                    help="frame-size multiplier for the --actuate "
                         "overload: per-frame service time scales "
                         "with span count, so frames this much bigger "
                         "overrun the initial deadline by "
                         "construction (and still clear the promoted "
                         "one)")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed for the chaos run's randomized draws "
                         "(retry jitter) — same seed, same schedule")
    ap.add_argument("--fused", action="store_true",
                    help="arm the fused device-side featurize→pack→"
                         "score route (ISSUE 19) on the fast path: "
                         "covered frames skip host featurize entirely "
                         "(one jitted call per coalesced group), every "
                         "uncovered frame takes the host route with "
                         "its reason counted. Runs a pre-window parity "
                         "gate on the live backend and flips the "
                         "ODIGOS_FUSED=0 kill switch for the 40-60%% "
                         "slice of the window; SOAK.json gains a "
                         "'fused' section (frames fused vs fallback, "
                         "host wall delta, kill-switch evidence) and "
                         "the run exits non-zero on a parity trip, a "
                         "never-fused run, or a kill slice that did "
                         "not fall back. Requires --model transformer "
                         "(zscore has no fused kernel)")
    ap.add_argument("--device-attrib", action="store_true",
                    help="arm sampled intra-fused device attribution "
                         "(ISSUE 20) on the fused route: 1-in-N frames "
                         "rerun the fused call as its five jitted "
                         "sub-stages and publish the intra-fused "
                         "waterfall, the XLA cost/efficiency ledger "
                         "prices every warmed bucket, and compile "
                         "events land in the ring with the paying "
                         "frame's trace id. Flips ODIGOS_DEVICE_"
                         "ATTRIB=0 for the 10-35%% slice of the "
                         "window; the record becomes DEVICE.json with "
                         "a 'device' section and the run exits "
                         "non-zero on an empty waterfall, a reconcile "
                         "ratio outside bounds, a kill slice that did "
                         "not fall back or resume, a warmed bucket "
                         "with no cost row, or no compile event with "
                         "a trace id. Requires --fused")
    ap.add_argument("--device-attrib-stride", type=int, default=32,
                    help="1-in-N sampling stride for --device-attrib "
                         "(the production default is 32; short runs "
                         "may need a denser grid to publish enough "
                         "waterfalls on both sides of the kill slice)")
    ap.add_argument("--model", default="zscore",
                    choices=["zscore", "transformer"],
                    help="scoring backend for the soak route")
    ap.add_argument("--mesh", default=None,
                    help="multichip: dp×tp serving mesh, e.g. 4x2, over "
                         "the devices JAX finds (fails when there are "
                         "too few); requires --model transformer")
    ap.add_argument("--cpu-geometry", action="store_true",
                    help="serve the transformer route with the 64-wide "
                         "2-layer float32 miniature instead of the "
                         "flagship — for CPU runs whose subject is the "
                         "wire path; refused on any other platform")
    args = ap.parse_args()
    if args.cpu_geometry and args.model != "transformer":
        ap.error("--cpu-geometry sizes the transformer route")
    from odigos_tpu.utils.jaxruntime import configure_compile_cache

    configure_compile_cache()
    if args.actuate and not args.pace_spans_per_sec:
        # the overload is a step in OFFERED load; a closed-loop
        # saturating sender has no baseline to step from
        ap.error("--actuate requires --pace-spans-per-sec")
    if args.actuate and args.no_fast_path:
        ap.error("--actuate tunes the fast path's admission deadline")
    if args.actuate and args.ab:
        # the componentwise arm has no fast path for the armed
        # actuator to tune — it would spend the run refusing no_site
        ap.error("--actuate and --ab are mutually exclusive")
    if args.mesh and args.model != "transformer":
        # zscore serves single-device and would silently ignore the
        # mesh — a SOAK.json claiming a mesh that never ran is worse
        # than refusing
        ap.error("--mesh requires --model transformer")
    if args.fused and args.model != "transformer":
        # the zscore backend has no fused kernel: every frame would
        # count a backend fallback and the record would claim a route
        # that never ran
        ap.error("--fused requires --model transformer")
    if args.fused and args.no_fast_path:
        ap.error("--fused arms a fast-path route; drop --no-fast-path")
    if args.fused and args.mesh:
        # the mesh partition plan keeps its own sharded call graph —
        # supports_fused is False and the soak would soak the fallback
        ap.error("--fused requires a single-device engine (no --mesh)")
    if args.device_attrib and not args.fused:
        # attribution decomposes the FUSED call; without the fused
        # route there is nothing to attribute
        ap.error("--device-attrib rides the fused route; add --fused")

    knee = None
    knee_sweep = []
    if args.find_knee:
        # sweep offered load upward with short paced probes until
        # delivery degrades: the knee is the highest level the fast
        # path still carries at >= knee_delivery of offered. The full
        # (A/B) record then runs AT that level — "saturated" means the
        # measured knee, not an arbitrary big number.
        import copy

        level = args.knee_start
        bend = None  # first level where delivery measurably degrades
        while level <= args.knee_max:
            probe_args = copy.copy(args)
            probe_args.seconds = args.knee_seconds
            probe_args.pace_spans_per_sec = level
            probe = run_soak(probe_args,
                             fast_path=not args.no_fast_path)
            ratio = probe["value"] / level
            knee_sweep.append({
                "offered_spans_per_sec": level,
                "delivered_spans_per_sec": probe["value"],
                "delivery_ratio": round(ratio, 4),
                "latency_p50_ms": probe["latency_p50_ms"],
                "latency_p99_ms": probe["latency_p99_ms"],
                "p99_over_p50": probe["p99_over_p50"],
            })
            print(f"knee probe: {level:,.0f} offered -> "
                  f"{probe['value']:,.0f} delivered "
                  f"(ratio {ratio:.3f}, p99/p50 "
                  f"{probe['p99_over_p50']})", file=sys.stderr)
            if ratio < args.knee_delivery:
                bend = level
                break
            knee = level
            level = level * args.knee_factor
        if knee is None:
            # even the first level shed: record there anyway — the
            # sweep table says so honestly
            knee = args.knee_start
        # the saturated record runs AT THE BEND — between the last
        # lossless level and the first degraded one (geometric
        # midpoint). Recording at the last lossless level measures the
        # below-knee regime (tiny standing queue, transit-dominated
        # p50), which says nothing about saturation tails; recording
        # at the first degraded level overshoots into deep overload
        # where the probe measures its own REJECTED-retry ladder. The
        # midpoint is mild saturation — the operating point "at the
        # knee" — by construction.
        args.pace_spans_per_sec = (knee * bend) ** 0.5 \
            if bend is not None else knee

    result = run_soak(args, fast_path=not args.no_fast_path)
    if knee is not None:
        result["knee_spans_per_sec"] = knee
        result["knee_sweep"] = knee_sweep
        result["knee_note"] = (
            "knee = highest offered load the fast path delivered at "
            f">= {args.knee_delivery:.0%} (geometric sweep, "
            f"{args.knee_seconds:.0f}s paced probes); the main record "
            "ran at the BEND — the geometric midpoint of the last "
            "lossless and first degraded sweep levels — because "
            "saturation tails only exist on the saturated side, while "
            "deep overload would measure the probe's own retry ladder")
    if args.ab and not args.no_fast_path:
        base = run_soak(args, fast_path=False)
        result["componentwise_baseline"] = {
            k: base[k] for k in (
                "value", "senders", "offered_spans_per_sec",
                "spans_sent", "spans_received", "conservation",
                "latency_p50_ms", "latency_p95_ms", "latency_p99_ms",
                "p99_over_p50")}
    import multiprocessing

    result["hardware_note"] = (
        f"{multiprocessing.cpu_count()}-core host; senders, receiver, "
        "engine and exporters share the cores, so absolute spans/s are "
        "NOT comparable across machines — compare fast path vs "
        "componentwise_baseline from the SAME record instead")
    # --reload-storm records its own artifact (the CHAOS.json
    # precedent) so the standing knee/A-B SOAK.json record survives
    record = "CHAOS.json" if args.chaos else (
        "RELOAD.json" if args.reload_storm else (
            "ACTUATOR.json" if args.actuate else (
                "DEVICE.json" if args.device_attrib else "SOAK.json")))
    with open(os.path.join(REPO, record), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    if not result["conservation"]:
        print(f"SPAN LOSS: sent {result['spans_sent']} received "
              f"{result['spans_received']}", file=sys.stderr)
        sys.exit(1)
    if args.chaos and not result["chaos"]["zero_unexplained_loss"]:
        print("CHAOS: unexplained loss", file=sys.stderr)
        sys.exit(1)
    if args.chaos and not result["chaos"]["incident_verdict"]:
        # each injected fault must freeze exactly one incident, and
        # nothing unexplained may freeze beside them
        print(f"CHAOS: incident mismatch — missing="
              f"{result['chaos']['incidents_missing']} spurious="
              f"{result['chaos']['incidents_spurious']}",
              file=sys.stderr)
        sys.exit(1)
    if not args.chaos and not args.actuate \
            and result["flight"]["incidents"]:
        # a clean soak (no fault injected, no deliberate SLO burn) must
        # freeze ZERO incidents — anything here is a regression or a
        # trigger misfiring
        rows = [(i["id"], i["trigger"], i["detail"])
                for i in result["flight"]["incidents"]]
        print(f"FLIGHT: incident(s) frozen on a clean run: {rows}",
              file=sys.stderr)
        sys.exit(1)
    if args.actuate:
        act = result["actuator"]
        ok = (act["promoted"] >= 1
              and act["all_reloads_incremental"]
              and act["slo_burned_under_overload"]
              and act["slo_recovered"])
        if not ok:
            # the acceptance verdict: the overload burned the SLO, the
            # actuator promoted a resize, EVERY applied reload stayed
            # on the incremental path, and the burn recovered — all
            # with zero operator input
            print(f"ACTUATOR: loop incomplete — promoted="
                  f"{act['promoted']} incremental="
                  f"{act['all_reloads_incremental']} burned="
                  f"{act['slo_burned_under_overload']} recovered="
                  f"{act['slo_recovered']}", file=sys.stderr)
            sys.exit(1)
    if args.fused:
        fu = result["fused"]
        ok = (fu["parity_gate"]["passed"]
              and fu["frames_fused"] > 0
              and fu["kill_switch_fell_back"]
              and fu["resumed_after_restore"])
        if not ok:
            # the acceptance verdict: the live backend passed the
            # parity gate, frames actually rode the fused route, the
            # mid-window kill switch fell back per frame (counted as
            # reason=disabled, nothing lost — conservation gated
            # above), and fused dispatch resumed after restore
            print(f"FUSED: route verdict failed — parity="
                  f"{fu['parity_gate']} fused_frames="
                  f"{fu['frames_fused']} kill_fell_back="
                  f"{fu['kill_switch_fell_back']} resumed="
                  f"{fu['resumed_after_restore']}", file=sys.stderr)
            sys.exit(1)
    if args.device_attrib:
        dv = result["device"]
        ok = (dv["waterfall_nonempty"]
              and dv["reconcile_ok"]
              and dv["kill_switch_fell_back"]
              and dv["resumed_after_restore"]
              and dv["cost_rows_cover_buckets"]
              and dv["compile_event_with_trace"])
        if not ok:
            # the acceptance verdict: the sampled intra-fused waterfall
            # exists and speaks the closed sub-stage vocabulary, its
            # sub-stage sum reconciles with the opaque fused stamp
            # within the documented bounds, the mid-window kill slice
            # fell back (sampled ticks counted as skipped{disabled})
            # AND sampling resumed after restore, every warmed bucket
            # has an XLA cost/efficiency row, and at least one compile
            # event carries the trace id of the frame that paid it
            print(f"DEVICE: attribution verdict failed — waterfall="
                  f"{dv['waterfall_nonempty']} reconcile="
                  f"{dv['reconcile_ratio']} (bounds "
                  f"{dv['reconcile_bounds']}) kill_fell_back="
                  f"{dv['kill_switch_fell_back']} resumed="
                  f"{dv['resumed_after_restore']} cost_rows="
                  f"{dv['cost_rows_cover_buckets']} compile_trace="
                  f"{dv['compile_event_with_trace']}", file=sys.stderr)
            sys.exit(1)
    if args.reload_storm and not (
            result["reload_storm"]["count"] == args.reload_storm
            and result["reload_storm"]["all_incremental"]
            and result["reload_storm"]["recompiles_total"] == 0):
        # the acceptance verdict: ALL N requested reloads actually ran
        # (an empty event list must not certify vacuously — a dead
        # storm thread is a failed storm), every one took the
        # incremental path (>=1 reconfigure, 0 replaced, no error),
        # and nothing compiled
        print("RELOAD STORM: missing/non-incremental reload or "
              "recompile", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
