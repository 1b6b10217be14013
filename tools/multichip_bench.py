"""Wire-fed multi-chip scaling bench — MULTICHIP graduates from dryrun.

Measures the PRODUCTION sharded serving path (ISSUE 7): WireExporter
(framed TCP) -> otlpwire receiver -> ingest fast path -> mesh-owning
ScoringEngine dispatching packed calls through the partition-rule dp×tp
plan (parallel.compile_plan) -> anomalyrouter -> tracedb exporters. One
collector per dp width, measurement windows INTERLEAVED round-robin
across widths so machine drift cancels (same-machine A/B).

Three claims per width, recorded in ``MULTICHIP_r06.json``:

* ``wire_spans_per_sec`` — raw end-to-end wire-fed throughput of the
  window. On a simulated host mesh all "devices" share the physical
  cores, so this number does NOT scale with dp (the host serializes the
  shards); it proves the path is wire-fed and conserves spans, not that
  it scales.
* ``scaling efficiency`` — strong-scaling at a fixed rung of R packed
  rows: eff(dp) = t(R, 1 device) / (dp × t_shard) where t_shard is the
  per-device shard's call time. On real TPU t_shard is the sharded
  call's measured wall (devices genuinely concurrent). On the simulated
  host mesh (``simulated: true``) the shards execute time-shared on the
  host cores, so t_shard is measured by running the shard-sized program
  (R/dp rows) on ONE device — the wall a real device would take if the
  shards ran concurrently. Real sub-linear losses stay in the number
  (per-call fixed dispatch cost, shard-shape inefficiency, dp-aligned
  padding); what the simulation cannot price is ICI collective time —
  pure-DP packed scoring inserts none (rows are independent), which is
  exactly why the scaling curve is run at tp=1.
* ``bitwise_parity`` — the width's engine scores a fixed batch bit-for-
  bit identical to the single-device engine (dp sharding is bitwise by
  construction: same per-row program, rows merely placed). A dp×tp
  datapoint is recorded with its ULP-level deviation (the "model" axis
  psum reassociates reductions; see parallel/sharding.py).

Real versus simulated is an ARGUMENT, never the outcome of a probe: by
default the bench runs on the devices JAX finds and fails when there are
fewer than the widest mesh needs; ``--simulated`` asks for the virtual
CPU mesh explicitly (and takes the process off any accelerator).

Usage:
    python tools/multichip_bench.py [--simulated] [--seconds 5]
                                    [--rounds 2] [--widths 1,2,4] [--tp 2]
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

MODEL_GEOMETRY = {
    "d_model": 64, "n_layers": 2, "d_ff": 256, "n_heads": 4,
    "max_len": 32, "dtype": "float32",
}
TRACE_BUCKET = 64   # divisible by every width: ladders match across dp
LADDER_BUCKETS = 4  # rungs 64..512 — wire coalescing stays on warm shapes
MAX_BATCH = 4096    # spans/call cap: rows stay under the top rung
MAX_LEN = 32
# Scaling-probe rung: production-sized compute per call, but small
# enough that the single-device baseline's working set stays in cache —
# above ~8 MB of activations the host-sim baseline falls off the LLC
# cliff and shards that fit cache read as SUPERLINEAR, a CPU artifact a
# real accelerator would not show (empirically: this geometry is linear
# in rows through 256 and cliffs by 512).
PROBE_ROWS = 256


def _collector_config(dp: int, tp: int, deadline_ms: float) -> dict:
    mesh = {"data": dp, "model": tp}
    tpu = {
        "model": "transformer", "threshold": 0.6,
        "timeout_ms": 30000, "shared_engine": False,
        "model_config": dict(MODEL_GEOMETRY),
        "trace_bucket": TRACE_BUCKET, "max_len": MAX_LEN,
        # max_batch bounds coalesced rows UNDER the top warmed rung, so
        # every window call lands on a precompiled shape (zero
        # recompiles is asserted, not hoped)
        "max_batch": MAX_BATCH,
        "bucket_ladder": LADDER_BUCKETS, "warm_ladder": True,
    }
    if dp * tp > 1:
        tpu["mesh"] = mesh
    return {
        "receivers": {"otlpwire": {}},
        "processors": {
            "memory_limiter": {"limit_mib": 512},
            "batch": {"send_batch_size": 8192, "timeout_s": 0.1},
            "tpuanomaly": tpu,
        },
        "connectors": {"anomalyrouter": {
            "anomaly_pipelines": ["traces/anomaly"],
            "default_pipelines": ["traces/normal"],
            "mode": "trace"}},
        "exporters": {"tracedb/anomaly": {}, "tracedb/normal": {}},
        "service": {"pipelines": {
            "traces/in": {
                "receivers": ["otlpwire"],
                "processors": ["memory_limiter", "batch", "tpuanomaly"],
                "exporters": ["anomalyrouter"],
                "fast_path": {"deadline_ms": deadline_ms,
                              "max_pending_spans": 128 * 1024},
            },
            "traces/anomaly": {"receivers": ["anomalyrouter"],
                               "exporters": ["tracedb/anomaly"]},
            "traces/normal": {"receivers": ["anomalyrouter"],
                              "exporters": ["tracedb/normal"]},
        }},
    }


class _Width:
    """One dp width under measurement: its collector, wire port, engine,
    and accumulated window tallies."""

    def __init__(self, dp: int, tp: int, deadline_ms: float):
        from odigos_tpu.pipeline.service import Collector

        self.dp = dp
        self.tp = tp
        self.collector = Collector(
            _collector_config(dp, tp, deadline_ms)).start()
        self.port = self.collector.graph.receivers["otlpwire"].port
        self.engine = self.collector.graph.fastpaths["traces/in"].engine
        self.spans = 0
        self.seconds = 0.0

    def exported_spans(self) -> int:
        g = self.collector.graph
        return (g.exporters["tracedb/anomaly"].span_count
                + g.exporters["tracedb/normal"].span_count)

    def shutdown(self) -> None:
        self.collector.shutdown()


def _wire_window(w: _Width, batches, seconds: float) -> None:
    """One interleaved measurement window: a sender floods the wire, the
    tally is spans that came out the far end (exported), not sent."""
    from odigos_tpu.wire.client import WireExporter

    stop = threading.Event()

    def sender() -> None:
        exp = WireExporter(f"otlpwire/mc-dp{w.dp}", {
            "endpoint": f"127.0.0.1:{w.port}", "queue_size": 64,
            "retry_initial_s": 0.02, "max_elapsed_s": 60.0})
        exp.start()
        k = 0
        while not stop.is_set():
            exp.export(batches[k % len(batches)])
            k += 1
            while exp.queued > 32 and not stop.is_set():
                time.sleep(0.001)
        exp.flush(timeout=60.0)
        exp.shutdown()

    before = w.exported_spans()
    t = threading.Thread(target=sender, daemon=True)
    t0 = time.perf_counter()
    t.start()
    time.sleep(seconds)
    stop.set()
    t.join(timeout=90)
    w.collector.drain_receivers(timeout=60.0)
    w.seconds += time.perf_counter() - t0
    w.spans += w.exported_spans() - before


def _probe_arrays(rows: int):
    import numpy as np

    from odigos_tpu.features.featurizer import CAT_FIELDS, CONT_FIELDS

    C, D, L = len(CAT_FIELDS), len(CONT_FIELDS), MAX_LEN
    return (np.zeros((rows, L, C), np.int32),
            np.zeros((rows, L, D), np.float32),
            np.ones((rows, L), np.int32),
            np.tile(np.arange(L, dtype=np.int32), (rows, 1)))


def _measure_calls(builders: dict, reps: int = 9,
                   passes: int = 3) -> dict:
    """Best wall (s) per labeled thunk BUILDER, min-merged over several
    independent passes. One label at a time within a pass: build the
    thunk (allocating + device-staging its input arrays), one untimed
    warm call (compile excluded), timed reps, then DROP the thunk and
    collect — keeping every label's arrays resident at once shrinks the
    cache left for the largest shape and pushes it over the LLC cliff,
    so the ratio measures eviction, not compute (interleaving
    differently-sized programs poisons it the same way, hence
    contiguous reps). Contention — a shared-host noisy neighbor, a
    frequency dip — only ever ADDS wall time, so the elementwise min
    across passes converges on each program's true floor; single-pass
    ratios on this class of box swing ±2x. Every thunk goes through a
    ScoringPlan jit so the compared programs are generated identically
    (the model's own jit fuses differently enough to skew the ratio).
    The caller runs this on a QUIET machine (before any collector is
    built)."""
    import gc

    out: dict = {}
    for _ in range(passes):
        for k, build in builders.items():
            fn = build()
            fn()  # warm (compile on pass 0, cached after)
            walls = []
            for _ in range(reps):
                t0 = time.perf_counter()
                fn()
                walls.append(time.perf_counter() - t0)
            best = min(walls)
            out[k] = best if k not in out else min(out[k], best)
            del fn
            gc.collect()
    return out


def _plan_thunk(plan, variables, rows: int):
    """One timed call of the plan's packed scoring at ``rows``, inputs
    PRE-STAGED on the mesh (plan._shard_inputs is a no-op on already
    correctly-placed arrays): the probe measures the device program,
    not a host memcpy — the engine's pack stage overlaps that transfer
    with the previous in-flight call anyway (PR 2)."""
    import numpy as np

    from odigos_tpu.parallel.sharding import _shard_inputs

    staged = _shard_inputs(plan.mesh, _probe_arrays(rows))
    return lambda: np.asarray(plan.score_packed(variables, *staged))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=5.0,
                    help="wire window per width per round")
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--widths", default="1,2,4",
                    help="comma-separated dp widths (pure data axis)")
    ap.add_argument("--tp", type=int, default=2,
                    help="model-axis width of the extra dp×tp datapoint "
                         "(0 disables it)")
    ap.add_argument("--traces-per-batch", type=int, default=128)
    ap.add_argument("--deadline-ms", type=float, default=1000.0)
    ap.add_argument("--out", default=os.path.join(REPO,
                                                  "MULTICHIP_r06.json"))
    ap.add_argument("--simulated", action="store_true",
                    help="run on a virtual CPU mesh instead of the "
                         "devices JAX finds (the record says "
                         "simulated: true; its walls are not device "
                         "numbers)")
    args = ap.parse_args()
    widths = sorted({int(x) for x in args.widths.split(",")})
    assert widths[0] == 1, "dp=1 is the scaling baseline; keep it"
    # the dp×tp datapoint sizes itself to the devices there are; the dp
    # widths asked for must all fit
    need = max(widths)

    simulated = args.simulated
    if simulated:
        from odigos_tpu.parallel import ensure_host_devices

        ensure_host_devices(max(8, need * max(args.tp, 1)))
    else:
        from odigos_tpu.utils.jaxruntime import configure_compile_cache

        configure_compile_cache()
    import jax

    n_dev = len(jax.devices())
    device = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind, "count": n_dev}
    if n_dev < need:
        sys.exit(f"multichip_bench: widths {widths} need {need} devices, "
                 f"JAX found {device}; pass --simulated for the virtual "
                 f"CPU mesh")

    import numpy as np

    from odigos_tpu.features import featurize
    from odigos_tpu.pdata import inject_faults, synthesize_traces
    from odigos_tpu.selftelemetry.flow import flow_ledger
    from odigos_tpu.utils.telemetry import meter

    flow_ledger.reset()
    meter.reset()

    batches = []
    for s in range(8):
        b = synthesize_traces(args.traces_per_batch, seed=s)
        if s % 4 == 0:
            b, _, _ = inject_faults(b, fault_fraction=0.2, seed=100 + s)
        batches.append(b)

    # ---- scaling probe at one fixed rung (strong scaling), run BEFORE
    # any collector exists: the probe times device programs, and on a
    # small host the collectors' threads (receivers, forwarders, engine
    # workers) would bleed scheduler noise into the walls. The probed
    # plans are compiled by the same compile_plan the engines use — the
    # identical program, measured quiet.
    import jax

    from odigos_tpu.models import TraceTransformer
    from odigos_tpu.parallel import compile_plan, make_mesh
    from odigos_tpu.training import make_model_config

    R = PROBE_ROWS
    probe_model = TraceTransformer(
        make_model_config("transformer", dict(MODEL_GEOMETRY)))
    probe_vars = probe_model.init(jax.random.PRNGKey(0))
    import functools

    plan1 = compile_plan(probe_model, make_mesh({"data": 1}))
    builders = {}
    for dp in widths[1:]:
        if simulated:
            # per-device shard program timed on ONE device: the wall a
            # real device would take were the shards concurrent (the
            # host time-shares them; see module docstring)
            builders.setdefault(
                ("single", R // dp),
                functools.partial(_plan_thunk, plan1, probe_vars,
                                  R // dp))
    builders[("single", R)] = functools.partial(_plan_thunk, plan1,
                                                probe_vars, R)
    for dp in widths[1:]:
        plan_dp = compile_plan(probe_model, make_mesh({"data": dp}))
        builders[("sharded", dp)] = functools.partial(
            _plan_thunk, plan_dp, probe_vars, R)
    best = _measure_calls(builders)
    t1 = best[("single", R)]
    probes = {1: (t1, t1)}
    for dp in widths[1:]:
        t_serialized = best[("sharded", dp)]
        t_shard = best[("single", R // dp)] if simulated else t_serialized
        probes[dp] = (t_serialized, t_shard)

    t_build0 = time.perf_counter()
    byw = {dp: _Width(dp, 1, args.deadline_ms) for dp in widths}
    build_s = time.perf_counter() - t_build0

    # prime each engine once (first wire frame must not eat the engine's
    # first-call bookkeeping inside a timed window)
    probe = synthesize_traces(64, seed=999)
    pf = featurize(probe)
    for w in byw.values():
        w.engine.score_sync(probe, pf, timeout_s=120.0)

    # ---- bitwise parity: same batch, matched grouping (ladders agree:
    # TRACE_BUCKET divides by every width, so rungs are identical)
    ref = byw[1].engine.score_sync(probe, pf, timeout_s=120.0)
    assert ref is not None, "single-device parity reference timed out"
    parity = {}
    for dp, w in byw.items():
        got = w.engine.score_sync(probe, pf, timeout_s=120.0)
        parity[dp] = bool(np.array_equal(got, ref))

    # ---- interleaved wire windows (round-robin cancels machine drift)
    for r in range(args.rounds):
        for dp in widths:
            _wire_window(byw[dp], batches, args.seconds)

    records = []
    for dp in widths:
        w = byw[dp]
        t_serialized, t_shard = probes[dp]
        eff = t1 / (dp * t_shard)
        lad = w.engine.backend.ladder.stats()
        stats = w.engine.pipeline_stats()
        records.append({
            "dp": dp, "tp": 1,
            "mesh": {"data": dp, "model": 1},
            "wire_spans_per_sec": round(w.spans / max(w.seconds, 1e-9), 1),
            "wire_window_s": round(w.seconds, 2),
            "wire_spans": int(w.spans),
            "bitwise_parity_vs_single_device": parity[dp],
            "device_call_ms_serialized": round(t_serialized * 1e3, 3),
            "device_call_ms_concurrent": round(t_shard * 1e3, 3),
            "device_rows_per_sec_concurrent": round(R / t_shard, 1),
            "scaling_efficiency": round(eff, 4),
            "bucket_ladder": lad,
            "zero_recompiles_after_warm": lad["misses"] == 0,
            "padding_waste_frac": w.engine.backend.last_padding_waste,
            "adaptive": stats["adaptive"],
        })

    # ---- one dp×tp datapoint: partition-rule tensor parallelism lives,
    # parity is ULP-level (psum reassociation), recorded not asserted
    tp_record = None
    fitting = [w for w in widths if w * args.tp <= n_dev] \
        if args.tp and args.tp > 1 else []
    if fitting:
        dp_tp = max(fitting)
        wtp = _Width(dp_tp, args.tp, args.deadline_ms)
        try:
            wtp.engine.score_sync(probe, pf, timeout_s=120.0)
            got = wtp.engine.score_sync(probe, pf, timeout_s=120.0)
            if got is None or ref is None:
                # the extra datapoint must not zero a finished record
                tp_record = {"error": "dp×tp parity probe timed out"}
            else:
                _wire_window(wtp, batches, args.seconds)
                tp_record = {
                    "dp": dp_tp, "tp": args.tp,
                    "mesh": {"data": dp_tp, "model": args.tp},
                    "wire_spans_per_sec": round(
                        wtp.spans / max(wtp.seconds, 1e-9), 1),
                    "max_abs_dev_vs_single_device": float(
                        np.abs(got - ref).max()),
                    "allclose_1e6": bool(
                        np.allclose(got, ref, atol=1e-6)),
                    "zero_recompiles_after_warm":
                        wtp.engine.backend.ladder.stats()["misses"] == 0,
                }
        finally:
            wtp.shutdown()

    balances = flow_ledger.conservation()
    conserved = all(b["leak"] == 0 for b in balances.values())
    for w in byw.values():
        w.shutdown()

    eff4 = next((r["scaling_efficiency"] for r in records
                 if r["dp"] == max(widths)), None)
    import multiprocessing

    result = {
        "metric": "multichip_wire_fed_scaling",
        "n_devices": n_dev,
        "simulated": simulated,
        "device": device,
        "rounds": args.rounds,
        "window_s": args.seconds,
        "rung_rows": R,
        "model_geometry": MODEL_GEOMETRY,
        "widths": records,
        "dp_tp_datapoint": tp_record,
        "scaling_efficiency_at_max_dp": eff4,
        "bitwise_parity": all(parity.values()),
        "conservation": bool(conserved),
        "collector_build_s": round(build_s, 2),
        "hardware_note": (
            f"{multiprocessing.cpu_count()}-core host"
            + (", SIMULATED 8-device mesh "
               "(--xla_force_host_platform_device_count): wire_spans_"
               "per_sec shares physical cores across shards and does "
               "not scale with dp; scaling_efficiency uses the per-"
               "device shard program's single-device wall (what a real "
               "concurrent device would take) and keeps the real "
               "sub-linear losses (per-call dispatch cost, shard-shape "
               "inefficiency, dp-aligned padding) but cannot price ICI "
               "collectives — tp=1 packed scoring inserts none"
               if simulated
               else f", {device['kind']} x{n_dev}: walls measured "
                    f"directly")),
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    failures = []
    if eff4 is not None and eff4 < 0.7:
        failures.append(f"scaling efficiency {eff4} < 0.7")
    if not result["bitwise_parity"]:
        failures.append("dp parity not bitwise")
    if not conserved:
        failures.append("span conservation violated")
    if any(not r["zero_recompiles_after_warm"] for r in records):
        failures.append("steady-state recompiles after warm")
    if failures:
        print("MULTICHIP FAIL: " + "; ".join(failures), file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
