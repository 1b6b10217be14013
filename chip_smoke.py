"""chip_smoke.py — does the wire-to-score path still start on the chip?

One process, one chip. It renders the gateway config the product renders
(``build_gateway_config`` with the transformer anomaly stage on the
ingest fast path), starts a ``Collector`` in this process, and ships
seeded ``synthesize_traces`` frames to its OTLP wire receiver over real
TCP from ``WireExporter`` sender threads — first on the default host
route (featurize and pack on the host), then, after a live reload, on
the fused route (featurize, pack and score in one device call), then
once more on the fused route with sampled device attribution on. The
model is the flagship as it ships: ``TransformerConfig()`` defaults,
``trace_bucket`` 256, a 4-rung warm ladder, random weights from seed 0.

It exits 0 only if JAX's first device is a TPU and every gate below
held on every leg, and then prints as its LAST line::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

On any other platform it stops before building anything and says what
it found. There is no CPU route in this file: the tier-1 rehearsal
(tests/test_chip_smoke.py) passes its own miniature ``Geometry`` and
the platform it expects, and the environment it runs in sets
``JAX_PLATFORMS=cpu`` from outside.

Gates, per leg: spans exported == spans sent; flow-ledger conservation
exact on every traces pipeline; ``odigos_anomaly_scored_spans_total``
== spans sent; zero passthrough, engine errors, deadline expiries,
mesh-unavailable and failover counters; no unplanned (``warm=false``)
compile inside the gated window; scores finite and in [0, 1]. On the
fused legs: every frame fused, no fallback under any reason. Once:
host and fused routes agree on sample groups within the bound
tests/test_fused.py documents for the model's precision, and the XLA
cost ledger holds a row for every warmed rung and every fused key.

    python chip_smoke.py                 # one chip (what the driver runs)
    python chip_smoke.py --mesh data=4   # one host, four chips, dp mesh

The numbers it prints besides the verdict (set-up seconds, send-to-
export latency, spans/s, peak device bytes) are smoke readings from a
short closed-loop run, not benchmark results.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import os
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Optional

REPO = os.path.dirname(os.path.abspath(__file__))


@dataclass(frozen=True)
class Geometry:
    """Model and traffic sizes of one smoke run. The defaults ARE the
    flagship: nothing in the rendered tpuanomaly stanza is overridden,
    so the engine takes ``TransformerConfig()``, ``trace_bucket`` 256
    and a 4-rung ladder from the product's own defaults."""

    # tpuanomaly stanza overrides (model_config, trace_bucket, ...);
    # empty on the chip
    engine_overrides: dict = field(default_factory=dict)
    traces_per_frame: int = 256       # ~2,600 spans a frame
    senders: int = 4
    warm_frames_per_sender: int = 2   # untimed, before the gated window
    frames_per_sender: int = 10       # gated window: 40 frames, ~104k spans
    attrib_frames_per_sender: int = 4
    min_frames: int = 32              # floor the gated window must clear
    min_spans: int = 50_000
    deadline_ms: float = 30_000.0     # "scored on the device", not "in 5 ms"
    settle_s: float = 120.0           # wait bound for arrival + conservation


FLAGSHIP = Geometry()
# past this the run dumps every thread's stack and exits non-zero: under
# the chip check's 1200 s, so a hang names itself instead of being killed
BUDGET_S = 1100.0


class SmokeFailure(Exception):
    """One or more gates failed; ``failures`` names each."""

    def __init__(self, failures: list[str]):
        super().__init__("; ".join(failures))
        self.failures = failures


def say(*a: Any) -> None:
    print(*a, flush=True)


# --------------------------------------------------------------- device gate


def device_facts(expect_platform: str) -> dict:
    """Initialise the backend, print what JAX found, and refuse any
    platform but the expected one — before anything is built."""
    from importlib import metadata

    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(
            f"chip_smoke: JAX could not initialise a backend: {e}")
    dev = devices[0]
    facts = {"platform": dev.platform, "kind": dev.device_kind,
             "count": len(devices)}
    versions = {}
    for pkg in ("jax", "jaxlib", "libtpu", "flax"):
        try:
            versions[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            versions[pkg] = "not installed"
    say(f"device: platform={facts['platform']} kind={facts['kind']!r} "
        f"count={facts['count']}")
    say("versions: " + " ".join(f"{k}={v}" for k, v in versions.items())
        + f" python={sys.version.split()[0]}")
    if dev.platform != expect_platform:
        say(f"chip_smoke: found platform {dev.platform!r} "
            f"({dev.device_kind!r} x{len(devices)}), need "
            f"{expect_platform!r}; nothing was built or measured")
        raise SystemExit(2)
    return facts


# ------------------------------------------------------------------- config


def render_config(geometry: Geometry, *, fused: bool, mesh_data: int,
                  device_attribution: bool = False) -> dict:
    """The gateway config the product renders for: two trace-db
    destinations (every span lands in ``all`` through the default data
    stream; flagged traces land again in ``flagged`` through the
    anomaly stream), transformer scoring on the ingest fast path,
    failover unarmed. The threshold is 0 so that every scored span is
    tagged with its score and the exporters can be checked span by
    span; the weights are random, so no threshold would mean anything."""
    from odigos_tpu.components.api import Signal
    from odigos_tpu.config.model import AnomalyStageConfiguration
    from odigos_tpu.destinations import Destination
    from odigos_tpu.pipelinegen import (
        DataStream, DataStreamDestination, GatewayOptions,
        build_gateway_config)

    dests = [Destination(id=d, dest_type="tracedb",
                         signals=[Signal.TRACES], config={})
             for d in ("all", "flagged")]
    streams = [DataStream("default", (DataStreamDestination("all"),)),
               DataStream("anomalies", (DataStreamDestination("flagged"),))]
    anomaly = AnomalyStageConfiguration(
        enabled=True, model="transformer", fast_path=True,
        fast_path_fused=fused, timeout_ms=geometry.deadline_ms,
        threshold=0.0, devices=mesh_data)
    config, statuses, _ = build_gateway_config(
        dests, data_streams=streams,
        options=GatewayOptions(anomaly=anomaly))
    bad = {k: v for k, v in statuses.destination.items() if v}
    if bad:
        raise SmokeFailure([f"gateway config did not render: {bad}"])
    stanza = config["processors"]["tpuanomaly"]
    stanza.update(geometry.engine_overrides)
    if device_attribution:
        stanza["device_attribution"] = True
        stanza["device_attribution_stride"] = 4
    return config


def exporter_ids(config: dict) -> tuple[str, str]:
    ids = [e for e in config["exporters"] if e.startswith("tracedb/")]
    all_id = next(e for e in ids if e.endswith("all"))
    flagged_id = next(e for e in ids if e.endswith("flagged"))
    return all_id, flagged_id


# ------------------------------------------------------------------ traffic


class Traffic:
    """Seeded frames, every one distinct (its own seed, so its own
    trace ids): a frame's send-to-export latency is then readable from
    the ids that arrive. A quarter carry injected faults."""

    def __init__(self, geometry: Geometry):
        self.geometry = geometry
        self._next_seed = 0

    def frames(self, n: int) -> list:
        from odigos_tpu.pdata import inject_faults, synthesize_traces

        out = []
        for _ in range(n):
            seed = self._next_seed
            self._next_seed += 1
            b = synthesize_traces(self.geometry.traces_per_frame, seed=seed)
            if seed % 4 == 0:
                b, _, _ = inject_faults(b, fault_fraction=0.2,
                                        seed=10_000 + seed)
            out.append(b)
        return out


class ArrivalSpy:
    """Stamps arrivals at a terminal exporter: (host clock, batch) per
    exported batch, and a running span count."""

    def __init__(self, exporter):
        self.exporter = exporter
        self.records: list[tuple[float, Any]] = []
        self.spans = 0
        self._lock = threading.Lock()
        inner = exporter.consume

        def spy(batch):
            now = time.perf_counter()
            with self._lock:
                self.records.append((now, batch))
                self.spans += len(batch)
            return inner(batch)

        exporter.consume = spy


def send_frames(port: int, per_sender: list[list], tag: str,
                give_up_s: float) -> dict:
    """Ship each sender's frames from its own thread over real TCP;
    returns per-frame send stamps and whether every frame was taken
    (a REJECTED frame is retried for at most ``give_up_s``)."""
    from odigos_tpu.wire.client import WireExporter

    sent_at: dict[int, float] = {}   # id(frame) -> host clock at export()
    flushed = [False] * len(per_sender)

    def sender(i: int) -> None:
        exp = WireExporter(f"otlpwire/smoke-{tag}-{i}", {
            "endpoint": f"127.0.0.1:{port}", "queue_size": 64,
            "retry_initial_s": 0.01, "retry_max_s": 0.05,
            "max_elapsed_s": give_up_s})
        exp.start()
        try:
            for frame in per_sender[i]:
                sent_at[id(frame)] = time.perf_counter()
                exp.export(frame)
                # bounded in-flight: "sent" means handed to the socket,
                # not parked in the client's queue
                while exp.queued > 4:
                    time.sleep(0.001)
            flushed[i] = exp.flush(timeout=give_up_s)
        finally:
            exp.shutdown()

    threads = [threading.Thread(target=sender, args=(i,), daemon=True,
                                name=f"smoke-sender-{i}")
               for i in range(len(per_sender))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=2 * give_up_s + 60.0)
    return {"sent_at": sent_at,
            "delivered": all(flushed)
            and not any(t.is_alive() for t in threads)}


# ------------------------------------------------------------------ counters


def counter_total(snap: dict, name: str) -> float:
    """Sum of a counter over all its label sets."""
    return sum(v for k, v in snap.items()
               if k == name or k.startswith(name + "{"))


def prefix_total(snap: dict, prefix: str) -> float:
    return sum(v for k, v in snap.items() if k.startswith(prefix))


def unplanned_compiles(snap: dict) -> float:
    return sum(v for k, v in snap.items()
               if k.startswith("odigos_jit_compile_events_total{")
               and "warm=false" in k)


def traces_balances() -> dict:
    from odigos_tpu.selftelemetry.flow import flow_ledger

    return {p: b for p, b in flow_ledger.conservation().items()
            if p.startswith("traces/")}


def wait_until(pred, timeout_s: float, poll_s: float = 0.02) -> bool:
    end = time.monotonic() + timeout_s
    while time.monotonic() < end:
        if pred():
            return True
        time.sleep(poll_s)
    return pred()


# ---------------------------------------------------------------------- legs


def wire_port(collector) -> int:
    return next(r.port for rid, r in collector.graph.receivers.items()
                if rid.split("/")[0] == "otlp")


def run_leg(name: str, collector, traffic: Traffic, spies: dict, *,
            fused: bool, n_warm: int, n_frames: int, floors: bool,
            failures: list[str]) -> dict:
    """``n_warm`` untimed frames per sender, then one gated window of
    ``n_frames`` per sender through the live collector. ``floors`` holds
    the window to the geometry's minimum frame and span counts."""
    import numpy as np

    from odigos_tpu.utils.telemetry import labeled_key, meter

    g = traffic.geometry
    port = wire_port(collector)
    all_spy = spies["all"]

    def fail(msg: str) -> None:
        failures.append(f"[{name}] {msg}")
        say(f"  GATE FAILED [{name}]: {msg}")

    def ship(per_sender: list[list], tag: str) -> dict:
        want = all_spy.spans + sum(len(f) for fs in per_sender for f in fs)
        res = send_frames(port, per_sender, f"{name}-{tag}", g.settle_s)
        arrived = wait_until(lambda: all_spy.spans >= want, g.settle_s)
        settled = wait_until(
            lambda: all(b["leak"] == 0 and b["pending"] == 0
                        for b in traces_balances().values()), g.settle_s)
        res["ok"] = res["delivered"] and arrived and settled
        if not res["ok"]:
            fail(f"{tag}: delivered={res['delivered']} arrived={arrived} "
                 f"(have {all_spy.spans}, want {want}) settled={settled} "
                 f"balances={traces_balances()}")
        return res

    # ---- untimed warm-up: first frames through every stage of the path
    if not ship([traffic.frames(n_warm) for _ in range(g.senders)],
                "warm-up")["ok"]:
        return {}

    # ---- gated window
    per_sender = [traffic.frames(n_frames) for _ in range(g.senders)]
    frames = [f for fs in per_sender for f in fs]
    spans_sent = sum(len(f) for f in frames)
    snap0 = meter.snapshot()
    exported0 = all_spy.spans
    flagged0 = spies["flagged"].spans
    rec0 = len(all_spy.records)
    t0 = time.perf_counter()
    res = ship(per_sender, "gated")
    last_arrival = max((t for t, _ in all_spy.records[rec0:]), default=t0)
    snap1 = meter.snapshot()

    def delta(metric: str) -> float:
        return counter_total(snap1, metric) - counter_total(snap0, metric)

    exported = all_spy.spans - exported0
    scored = delta("odigos_anomaly_scored_spans_total")
    say(f"  [{name}] frames sent {len(frames)}  spans sent {spans_sent}  "
        f"spans exported {exported}  scored_spans_total +{int(scored)}  "
        f"flagged-stream spans {spies['flagged'].spans - flagged0}")
    if floors and (len(frames) < g.min_frames
                   or spans_sent < g.min_spans):
        fail(f"gated window too small: {len(frames)} frames / "
             f"{spans_sent} spans (need {g.min_frames} / {g.min_spans})")
    if exported != spans_sent:
        fail(f"spans exported {exported} != spans sent {spans_sent}")
    if scored != spans_sent:
        fail(f"odigos_anomaly_scored_spans_total grew {int(scored)}, "
             f"sent {spans_sent}")
    for b_name, b in traces_balances().items():
        if b["leak"] or b["pending"] or b["dropped"] or b["failed"]:
            fail(f"conservation {b_name}: {b}")
    zero = {
        "passthrough": delta("odigos_anomaly_passthrough_total"),
        "engine_errors": delta("odigos_anomaly_engine_errors_total"),
        "deadline_expired": delta(
            "odigos_latency_deadline_expired_spans_total"),
        "mesh_unavailable": delta("odigos_engine_mesh_unavailable_total"),
        "failover": prefix_total(snap1, "odigos_failover_")
        - prefix_total(snap0, "odigos_failover_"),
        "queue_full": delta("odigos_anomaly_queue_full_total"),
        "unplanned_compiles": unplanned_compiles(snap1)
        - unplanned_compiles(snap0),
    }
    say(f"  [{name}] " + "  ".join(f"{k}={int(v)}" for k, v in zero.items()))
    for k, v in zero.items():
        if v:
            fail(f"{k} grew by {v} in the gated window")

    fused_frames = delta("odigos_fastpath_fused_frames_total")
    if fused:
        from odigos_tpu.serving.fastpath import FUSED_FALLBACK_METRIC
        from odigos_tpu.serving.fused import FALLBACK_REASONS

        fallbacks = {r: delta(labeled_key(
            FUSED_FALLBACK_METRIC, pipeline="traces/in", reason=r))
            for r in FALLBACK_REASONS}
        say(f"  [{name}] fused frames +{int(fused_frames)}  fallbacks "
            + " ".join(f"{r}={int(v)}" for r, v in fallbacks.items()))
        if fused_frames != len(frames):
            fail(f"fused frames {int(fused_frames)} != frames sent "
                 f"{len(frames)}")
        for r, v in fallbacks.items():
            if v:
                fail(f"fused fallback reason={r} grew by {int(v)}")
        other = delta("odigos_fastpath_fused_fallback_total") \
            - sum(fallbacks.values())
        if other:
            fail(f"fused fallbacks outside FALLBACK_REASONS: {other}")
    elif fused_frames:
        fail(f"host leg fused {int(fused_frames)} frames")

    # ---- scores on what arrived. The stage runs at threshold 0, so the
    # tagger writes its score onto every span it was given one for: each
    # exported span must carry a finite score in [0, 1] (a NaN compares
    # false against any threshold and would arrive untagged)
    from odigos_tpu.serving.fastpath import SCORE_ATTR

    window = [b for _, b in all_spy.records[rec0:]]
    parts = []
    for b in window:
        vals, present = b.attrs().column(SCORE_ATTR)
        parts.append(np.asarray(vals[present], dtype=np.float64))
    scores = np.concatenate(parts) if parts else np.zeros(0)
    in_range = int((np.isfinite(scores) & (scores >= 0)
                    & (scores <= 1)).sum())
    say(f"  [{name}] spans exported with a score: {len(scores)} of "
        f"{exported}" + (f"  min={scores.min():.4f} max={scores.max():.4f} "
                         f"mean={scores.mean():.4f}" if len(scores) else ""))
    if in_range != spans_sent:
        fail(f"{in_range} exported spans carry a finite score in [0, 1], "
             f"sent {spans_sent}")

    # ---- smoke readings (not gated)
    frame_ids = [np.unique(f.col("trace_id_hi")) for f in frames]
    ids = np.concatenate(frame_ids)
    owner = np.repeat(np.arange(len(frames)), [len(x) for x in frame_ids])
    order = np.argsort(ids)
    ids, owner = ids[order], owner[order]
    arrival = np.full(len(frames), np.nan)
    for t, b in all_spy.records[rec0:]:
        seen = np.unique(b.col("trace_id_hi"))
        pos = np.minimum(np.searchsorted(ids, seen), len(ids) - 1)
        hit = ids[pos] == seen
        np.fmax.at(arrival, owner[pos[hit]], t)
    sent = np.array([res["sent_at"].get(id(f), np.nan) for f in frames])
    lat_ms = (arrival - sent) * 1e3
    lat_ms = lat_ms[np.isfinite(lat_ms)]
    wall = max(last_arrival - t0, 1e-9)
    reading = {
        "frames": len(frames), "spans_sent": spans_sent,
        "spans_exported": exported, "spans_scored": int(scored),
        "window_s": round(wall, 3),
        "smoke_spans_per_s": round(spans_sent / wall, 1),
        "smoke_send_to_export_p50_ms": round(
            float(np.percentile(lat_ms, 50)), 2) if len(lat_ms) else None,
        "smoke_send_to_export_p99_ms": round(
            float(np.percentile(lat_ms, 99)), 2) if len(lat_ms) else None,
    }
    say(f"  [{name}] smoke readings (closed loop, not a benchmark): "
        f"{reading['smoke_spans_per_s']} spans/s over "
        f"{reading['window_s']} s; send-to-export p50 "
        f"{reading['smoke_send_to_export_p50_ms']} ms p99 "
        f"{reading['smoke_send_to_export_p99_ms']} ms")
    # a leg's spans are counted: drop them (the trace-db keeps every batch)
    for spy in spies.values():
        spy.exporter.clear()
        del spy.records[:]
    return reading


# ------------------------------------------------------------ parity + warm


def parity_and_fused_warm(engine, traffic: Traffic,
                          failures: list[str]) -> dict:
    """Score sample groups on both routes, straight through the live
    backend (the worker is idle: no traffic yet). This is the parity
    gate, and it is also where the fused route's cold keys compile: a
    coalesced group holds 1..k frames, each size lands in its own
    (span bucket, rows) program, and the gated window must find every
    one of them warm."""
    import numpy as np

    from odigos_tpu.features import featurize
    from odigos_tpu.features.featurizer import SpanFeatures
    from odigos_tpu.pdata.spans import concat_batches
    from odigos_tpu.serving.fused import (
        extract_columns, routes_agree, served_precision)

    backend = engine.backend
    fz = engine.cfg.featurizer
    by_size = sorted(traffic.frames(6), key=len)
    # the pack stage adds frames while the group is under the cap
    k_max = (engine.cfg.max_batch_spans - 1) // len(by_size[0]) + 1
    precision = served_precision(backend)
    out = {"groups": [], "precision": precision}
    seen_keys = set()
    for k in range(1, min(k_max, len(by_size)) + 1):
        for group in (by_size[:k], by_size[-k:]):
            feats = [featurize(f, fz) for f in group]
            merged = SpanFeatures(
                np.concatenate([x.categorical for x in feats]),
                np.concatenate([x.continuous for x in feats]))
            batch = concat_batches(group) if k > 1 else group[0]
            want = backend.score(batch, merged)
            cols = [extract_columns(f, fz)[0] for f in group]
            if any(c is None for c in cols):
                failures.append("[parity] sample frame not fusable")
                return out
            t0 = time.perf_counter()
            got = backend.harvest(backend.dispatch_columns(cols))
            dt = time.perf_counter() - t0
            key = (backend.last_span_bucket, tuple(backend.last_shape))
            diff = np.abs(got - want)
            ok_range = bool(np.isfinite(got).all() and (got >= 0).all()
                            and (got <= 1).all())
            ok = routes_agree(got, want, precision)
            row = {"frames": k, "spans": len(got),
                   "span_bucket": key[0], "rows": key[1][0],
                   "first_call_s": round(dt, 3) if key not in seen_keys
                   else None,
                   "max_abs_diff": float(diff.max()),
                   "mean_abs_diff": float(diff.mean()), "ok": ok}
            seen_keys.add(key)
            out["groups"].append(row)
            say(f"  parity k={k} spans={len(got)} key=N{key[0]}xR"
                f"{key[1][0]} max|d|={row['max_abs_diff']:.2e} "
                f"mean|d|={row['mean_abs_diff']:.2e} "
                f"{'ok' if ok and ok_range else 'FAILED'}"
                + (f"  (first fused call {dt:.2f} s)"
                   if row["first_call_s"] is not None else ""))
            if not ok:
                failures.append(
                    f"[parity] k={k}: host and fused routes disagree "
                    f"beyond the {out['precision']} bound "
                    f"(max {row['max_abs_diff']:.3g}, mean "
                    f"{row['mean_abs_diff']:.3g})")
            if not ok_range:
                failures.append(f"[parity] k={k}: fused scores not finite "
                                f"in [0, 1]")
    out["fused_keys"] = sorted(seen_keys)
    return out


def mesh_gate(engine, traffic: Traffic, n_data: int,
              failures: list[str]) -> dict:
    """Four chips: the scores of one packed call must be sharded over
    ``n_data`` distinct devices and every device must hold bytes."""
    import jax

    from odigos_tpu.features import featurize

    backend = engine.backend
    frame = traffic.frames(1)[0]
    handle = backend.dispatch(frame, featurize(frame, engine.cfg.featurizer))
    dev = handle[1]
    devices = sorted(d.id for d in dev.sharding.device_set)
    backend.harvest(handle)
    in_use = {d.id: (d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.devices()[:n_data]}
    say(f"  mesh: scores sharded over devices {devices}; "
        f"bytes_in_use {in_use}")
    if engine.mesh is None or len(devices) != n_data:
        failures.append(f"[mesh] scores span devices {devices}, "
                        f"need {n_data} distinct")
    if not all(v and v > 0 for v in in_use.values()):
        failures.append(f"[mesh] a device reports no bytes_in_use: {in_use}")
    return {"devices": devices, "bytes_in_use": in_use}


# ---------------------------------------------------------------------- run


def describe_engine(engine) -> dict:
    import jax.numpy as jnp

    backend = engine.backend
    mc = backend.model.cfg
    geometry = {"d_model": mc.d_model, "n_heads": mc.n_heads,
                "n_layers": mc.n_layers, "d_ff": mc.d_ff,
                "max_len": backend.max_len, "dtype": jnp.dtype(mc.dtype).name,
                "ladder": list(backend.ladder.buckets),
                "max_batch_spans": engine.cfg.max_batch_spans,
                "mesh": engine.cfg.mesh_shape()}
    say("geometry: " + " ".join(f"{k}={v}" for k, v in geometry.items()))
    return geometry


def cost_ledger_gate(engine, failures: list[str]) -> list[str]:
    """A row per warmed rung and per fused key this engine compiled.
    A mesh plan keeps its jit behind its own call graph and has no fused
    kernel, so it prices nothing (SequenceBackend._capture_warm_cost)
    and there is nothing to gate."""
    from odigos_tpu.models.costmodel import cost_ledger

    backend = engine.backend
    snap = cost_ledger.snapshot()
    rows = {(r["site"], r["bucket"]) for r in snap["rows"]}
    if engine.mesh is not None:
        say(f"cost ledger: not gated under a mesh ({len(rows)} rows)")
        return sorted(f"{s}[{b}]" for s, b in rows)
    want = {(backend.jit_site, f"r{R}") for R in backend.ladder.buckets}
    want |= {(backend.fused_site, f"r{R}x{backend.max_len}")
             for (_n, R) in backend._fused_shapes}
    missing = sorted(want - rows)
    say(f"cost ledger: {len(rows)} rows, {len(want)} expected, "
        f"failed={snap['captures_failed']} "
        f"skipped={snap['captures_skipped']}")
    if missing or snap["captures_failed"]:
        failures.append(f"[cost ledger] missing rows {missing}; "
                        f"last error: {snap['last_error']}")
    return sorted(f"{s}[{b}]" for s, b in rows)


def run(geometry: Geometry = FLAGSHIP, *, expect_platform: str = "tpu",
        mesh_data: int = 1) -> dict:
    """The whole smoke. Returns the report; raises ``SmokeFailure`` when
    any gate failed, ``SystemExit`` when the device gate refused."""
    facts = device_facts(expect_platform)
    if mesh_data > facts["count"]:
        raise SmokeFailure([f"mesh data={mesh_data} needs {mesh_data} "
                            f"devices, JAX found {facts['count']}"])

    import jax

    from odigos_tpu.models import jitstats
    from odigos_tpu.models.costmodel import cost_ledger
    from odigos_tpu.pipeline.service import Collector
    from odigos_tpu.selftelemetry.flightrecorder import flight_recorder
    from odigos_tpu.selftelemetry.flow import flow_ledger
    from odigos_tpu.selftelemetry.latency import latency_ledger
    from odigos_tpu.utils.telemetry import meter

    for ledger in (flow_ledger, meter, latency_ledger, flight_recorder,
                   cost_ledger, jitstats):
        ledger.reset()

    g = geometry
    failures: list[str] = []
    traffic = Traffic(g)
    report: dict = {"device": facts,
                    "mesh": {"data": mesh_data} if mesh_data > 1 else None,
                    "legs": {}, "smoke_setup_s": {}}
    # the partition plan has no fused kernel: under a mesh every frame
    # would fall back (reason=backend), so a mesh run is the host route
    fused_legs = mesh_data == 1

    def start(config: dict, label: str):
        """Build the graph (which warms the ladder) and tap its two
        terminal exporters."""
        t0 = time.perf_counter()
        collector = Collector(config).start()
        dt = time.perf_counter() - t0
        report["smoke_setup_s"][f"{label}_start_and_warm_ladder"] = \
            round(dt, 2)
        say(f"set-up [{label}]: collector start + warm ladder {dt:.2f} s")
        all_id, flagged_id = exporter_ids(config)
        spies = {"all": ArrivalSpy(collector.graph.exporters[all_id]),
                 "flagged": ArrivalSpy(collector.graph.exporters[flagged_id])}
        return collector, collector.graph.fastpaths["traces/in"].engine, \
            spies

    def warm_fused(engine, label: str) -> dict:
        t0 = time.perf_counter()
        out = parity_and_fused_warm(engine, traffic, failures)
        dt = time.perf_counter() - t0
        report["smoke_setup_s"][f"{label}_parity_and_fused_compiles"] = \
            round(dt, 2)
        say(f"set-up [{label}]: parity + first fused compiles {dt:.2f} s")
        return out

    # ---- engine 1: the host route, then the fused route by live reload
    collector, engine, spies = start(
        render_config(g, fused=False, mesh_data=mesh_data), "first")
    try:
        report["geometry"] = describe_engine(engine)
        if fused_legs:
            report["parity"] = warm_fused(engine, "first")
        else:
            report["mesh_gate"] = mesh_gate(engine, traffic, mesh_data,
                                            failures)
        say("leg host: featurize + pack on the host, score on the device")
        report["legs"]["host"] = run_leg(
            "host", collector, traffic, spies, fused=False,
            n_warm=g.warm_frames_per_sender, n_frames=g.frames_per_sender,
            floors=True, failures=failures)
        if failures:  # a later leg would only repeat or bury the cause
            raise SmokeFailure(failures)
        if fused_legs:
            say("leg fused: featurize + pack + score in one device call")
            fused_cfg = render_config(g, fused=True, mesh_data=mesh_data)
            # an ephemeral port re-rolls on any full rebuild; pin it
            fused_cfg["receivers"]["otlp"]["port"] = wire_port(collector)
            fp = collector.graph.fastpaths["traces/in"]
            collector.reload(fused_cfg)
            if collector.graph.fastpaths["traces/in"] is not fp \
                    or not fp.fused or any(
                        collector.graph.exporters[eid] is not spy.exporter
                        for eid, spy in zip(exporter_ids(fused_cfg),
                                            spies.values())):
                failures.append("[fused] the reload did not arm the fused "
                                "route in place")
            report["legs"]["fused"] = run_leg(
                "fused", collector, traffic, spies, fused=True,
                n_warm=g.warm_frames_per_sender, n_frames=g.frames_per_sender,
                floors=True, failures=failures)
        report["cost_ledger_rows"] = cost_ledger_gate(engine, failures)
        if engine.last_error:
            failures.append(f"[engine] last error: {engine.last_error}")
        if failures:
            raise SmokeFailure(failures)
    finally:
        collector.shutdown()

    # ---- engine 2: the fused route with sampled device attribution on
    # (attribution is part of the engine's identity, so it is a second
    # engine with its own ladder and its own fused keys)
    if fused_legs:
        say("leg attrib: fused route, device attribution sampling 1 in 4")
        collector, engine, spies = start(
            render_config(g, fused=True, mesh_data=mesh_data,
                          device_attribution=True), "attrib")
        try:
            warm_fused(engine, "attrib")
            report["legs"]["attrib"] = run_leg(
                "attrib", collector, traffic, spies, fused=True,
                n_warm=g.warm_frames_per_sender,
                n_frames=g.attrib_frames_per_sender, floors=False,
                failures=failures)
            stats = engine.backend._attrib.stats()
            say(f"  [attrib] sampler: seen={stats['frames_seen']} "
                f"sampled={stats['sampled']} skipped={stats['skipped']}")
            if stats["last_waterfall"]:
                say(f"  [attrib] last waterfall (ms): "
                    f"{stats['last_waterfall']['stages']} fused stamp "
                    f"{stats['last_waterfall']['fused_device_ms']}")
            if stats["skipped"]["error"]:
                failures.append(f"[attrib] {stats['skipped']['error']} "
                                f"sampled frames raised")
            if not stats["sampled"]:
                failures.append("[attrib] no waterfall was published")
            report["legs"]["attrib"]["sampler"] = {
                k: stats[k] for k in ("frames_seen", "sampled", "skipped")}
            if engine.last_error:
                failures.append(f"[engine] last error: {engine.last_error}")
        finally:
            collector.shutdown()

    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()[:mesh_data]]
    report["smoke_peak_bytes_in_use"] = peak
    say(f"peak_bytes_in_use per device: {peak}")
    if failures:
        raise SmokeFailure(failures)
    return report


def parse_mesh(spec: Optional[str]) -> int:
    if not spec:
        return 1
    axis, _, n = spec.partition("=")
    if axis != "data" or not n.isdigit() or int(n) < 1:
        raise SystemExit(f"chip_smoke: --mesh takes data=N, got {spec!r}")
    return int(n)


def main(argv: Optional[list[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", default=None, metavar="data=N",
                    help="serve on an N-chip data-parallel mesh (host "
                         "route only); default one chip")
    args = ap.parse_args(argv)
    mesh_data = parse_mesh(args.mesh)
    faulthandler.dump_traceback_later(BUDGET_S, exit=True)
    t0 = time.perf_counter()
    try:
        import odigos_tpu  # noqa: F401 — the repo must be around this file
    except ImportError as e:
        print(f"chip_smoke: the odigos_tpu package is not importable from "
              f"{REPO}: {e}", file=sys.stderr)
        return 3
    from odigos_tpu.utils.jaxruntime import configure_compile_cache

    cache_dir = configure_compile_cache()
    say(f"compile cache: {cache_dir} (JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'}"
        f")")
    try:
        report = run(FLAGSHIP, mesh_data=mesh_data)
    except SmokeFailure as e:
        say(f"chip_smoke FAILED ({len(e.failures)} gate(s)):")
        for f in e.failures:
            say(f"  - {f}")
        return 1
    finally:
        faulthandler.cancel_dump_traceback_later()
    say(f"chip_smoke passed in {time.perf_counter() - t0:.1f} s")
    say("smoke report: " + json.dumps(report, default=str))
    say(json.dumps({"ok": True, "device": report["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
