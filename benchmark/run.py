"""One cell, one run, one process.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Reads the cell from ``BENCHMARK.json`` at the root of the checkout, its
configuration from ``benchmark/configs/``, the architecture the
configuration names from ``benchmark/architectures/`` (the plain
reference, the operation count and the trace's parts), its traffic mix
from ``benchmark/traffic/`` and each metric's reader from
``benchmark/metrics/``. Starts the product's gateway collector in this
process (the rendered gateway config with the configuration's
``tpuanomaly`` stanza laid over it), drives the collector's wire receiver
with ``WireExporter`` clients for ``--seconds`` seconds, waits for the
window's frames at the terminal exporter, decides ``correct`` against the
architecture's plain reference, and prints one JSON line last. With
``--trace 1`` the window runs under the profiler, and the readers get the
trace's reductions (``tracered``: device time; ``hosttrace``: each engine
call joined to its executable run, step time by part). It exits non-zero and
prints no result unless JAX's devices are TPUs and cover the cell's
``chips``.

One rule turns ``chips`` n > 1 into a deployment, for every cell alike:
``mesh {data: n}``, and ``trace_bucket``, ``max_batch``, the fast path's
``max_pending_spans`` and the traffic's in-flight bound times n, so that
each chip sees the rungs it sees alone.

``--rehearse FILE`` is the harness's own rehearsal: FILE's ``tpuanomaly``,
``traffic`` and ``correct`` mappings and its ``architecture`` (a name, or a
file's path from the root) are laid over the cell's, the platform gate is
dropped, and the line is marked ``"rehearsal": true``.
A rehearsal's numbers are never results.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from typing import Any, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

OUT_DIR = os.path.join(ROOT, ".bench_out")
DEFAULT_PENDING_SPANS = 128 * 1024     # the fast path's own default
PIPELINE = "traces/in"
# counters that must not move inside a window; reported on every run
WATCHED = (
    "odigos_anomaly_passthrough_total", "odigos_anomaly_engine_errors_total",
    "odigos_latency_deadline_expired_spans_total",
    "odigos_engine_mesh_unavailable_total", "odigos_anomaly_queue_full_total",
    "odigos_fastpath_saturated_total", "odigos_fastpath_predicted_shed_total",
    "odigos_fastpath_forward_errors_total",
    "odigos_fastpath_submit_errors_total",
    "odigos_fastpath_fused_fallback_total",
    "odigos_exporter_dropped_frames_total",
    "odigos_exporter_backpressure_total", "odigos_failover_")


def say(*a: Any) -> None:
    print(*a, file=sys.stderr, flush=True)


class Refused(Exception):
    """The run cannot be made; exit non-zero, print no result."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, cell, configuration file, traffic file) by name."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    return bench, cell, config, traffic


def load_architecture(config: dict, rehearse: Optional[dict] = None):
    """The module of the architecture the configuration names (or the
    rehearsal lays over it)."""
    from benchmark import architectures

    name = (rehearse or {}).get("architecture", config.get("architecture"))
    if name is None:
        raise Refused(
            f"configuration {config.get('name')!r} names no architecture: "
            f"it needs \"architecture\": \"<name>\", a file "
            f"{os.path.join(HERE, 'architectures', '<name>.py')}")
    try:
        return architectures.load(name, rehearsal=rehearse is not None)
    except architectures.NotFound as e:
        raise Refused(str(e))


def cell_metrics(bench: dict, cell: dict, group: str) -> list[dict]:
    """The metrics of ``group`` this cell reports. An end-to-end metric
    with no ``workloads`` is every cell's; a per-layer metric with none
    is reported wherever the metric it moves is."""
    e2e = {m["name"] for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])}
    if group == "end_to_end":
        return [m for m in bench["end_to_end"] if m["name"] in e2e]
    return [m for m in bench["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


# ------------------------------------------------------------------ device


def device_gate(chips: int, rehearsal: bool) -> dict:
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise Refused(f"JAX could not initialise a backend: {e}")
    dev = devices[0]
    facts = {"platform": dev.platform, "kind": dev.device_kind,
             "count": len(devices)}
    say(f"device: platform={facts['platform']} kind={facts['kind']!r} "
        f"count={facts['count']}")
    if not rehearsal and dev.platform != "tpu":
        raise Refused(f"found platform {dev.platform!r}, need 'tpu'; "
                      f"nothing was built or measured")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found "
                      f"{len(devices)}")
    return facts


def memory_peak(chips: int) -> Optional[int]:
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    say("memory_stats: " + json.dumps(stats))
    # the allocator's peak alone: what the runtime holds back for the
    # loaded programs' scratch (peak_bytes_reserved) is sized by the
    # largest rung compiled, whether or not the window dispatches it
    peaks = [s["peak_bytes_in_use"] for s in stats
             if "peak_bytes_in_use" in s]
    return int(max(peaks)) if peaks else None


# ------------------------------------------------------------------ config


def render_config(stanza: dict, chips: int, seed: int) -> dict:
    """The gateway config the product renders (two trace-db destinations,
    every span to ``all`` through the default stream, flagged traces
    again to ``flagged``; the stanza's ``model`` (``transformer`` where it
    names none) scoring on the ingest fast path; threshold 0 so that
    every span carries its score), with the configuration's stanza and
    the chips rule laid over it."""
    from odigos_tpu.components.api import Signal
    from odigos_tpu.config.model import AnomalyStageConfiguration
    from odigos_tpu.destinations import Destination
    from odigos_tpu.pipelinegen import (
        DataStream, DataStreamDestination, GatewayOptions,
        build_gateway_config)

    dests = [Destination(id=d, dest_type="tracedb", signals=[Signal.TRACES],
                         config={}) for d in ("all", "flagged")]
    streams = [DataStream("default", (DataStreamDestination("all"),)),
               DataStream("anomalies", (DataStreamDestination("flagged"),))]
    anomaly = AnomalyStageConfiguration(
        enabled=True, model=stanza.get("model", "transformer"),
        fast_path=True,
        timeout_ms=float(stanza["timeout_ms"]), threshold=0.0, devices=chips)
    config, statuses, _ = build_gateway_config(
        dests, data_streams=streams, options=GatewayOptions(anomaly=anomaly))
    bad = {k: v for k, v in statuses.destination.items() if v}
    if bad:
        raise Refused(f"gateway config did not render: {bad}")
    out = config["processors"]["tpuanomaly"]
    out.update(copy.deepcopy(stanza))
    out["seed"] = int(seed)
    if chips > 1:
        out.pop("devices", None)
        out["mesh"] = {"data": chips}
        out["trace_bucket"] = int(out["trace_bucket"]) * chips
        out["max_batch"] = int(out["max_batch"]) * chips
        fp = config["service"]["pipelines"][PIPELINE]["fast_path"]
        fp["max_pending_spans"] = DEFAULT_PENDING_SPANS * chips
    return config


def exporter_ids(config: dict) -> tuple[str, str]:
    ids = [e for e in config["exporters"] if e.startswith("tracedb/")]
    return (next(e for e in ids if e.endswith("all")),
            next(e for e in ids if e.endswith("flagged")))


# ---------------------------------------------------------------- counters


def watched(snap: dict) -> dict[str, float]:
    """The watched counters of a meter snapshot, each under its own
    labels, and the compiles no ladder warming asked for."""
    out = {k: v for k, v in snap.items()
           if any(k == name or k.startswith(name + "{")
                  or (name.endswith("_") and k.startswith(name))
                  for name in WATCHED)}
    out["unplanned_compiles"] = sum(
        v for k, v in snap.items()
        if k.startswith("odigos_jit_compile_events_total{")
        and "warm=false" in k)
    return out


def stage_sums(pipeline: str) -> dict[str, tuple[float, int]]:
    """Summed ms and frame count per stage of the program's waterfall."""
    from odigos_tpu.selftelemetry.latency import latency_ledger

    wf = latency_ledger.waterfall().get(pipeline, {})
    return {s: (row["mean_ms"] * row["count"], int(row["count"]))
            for s, row in wf.items()}


class ScoreSpans:
    """Collects the program's ``tpu/score`` self-trace spans while a
    traced window runs (the tracer's ring is bounded, so it is read as
    it fills)."""

    def __init__(self) -> None:
        from odigos_tpu.selftelemetry.tracer import tracer

        self._ring = tracer.ring
        self._cursor = tracer.ring.total
        self.calls: list[tuple[int, int, int]] = []
        self.missed = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-score-spans")
        self._thread.start()

    def _read(self) -> None:
        spans, self._cursor, missed = self._ring.since(self._cursor)
        self.missed += missed
        for sp in spans:
            if sp.name != "tpu/score":
                continue
            a = sp.attrs
            shape = str(a.get("device.shape", ""))
            if "batch.spans" in a and "x" in shape:
                rows, length = (int(x) for x in shape.split("x")[:2])
                self.calls.append((int(a["batch.spans"]), rows, length))

    def _run(self) -> None:
        while not self._stop.wait(0.5):
            self._read()

    def finish(self) -> list[tuple[int, int, int]]:
        self._stop.set()
        self._thread.join()
        self._read()
        return self.calls


# --------------------------------------------------------------------- run


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearse: Optional[dict] = None,
             control: bool = False) -> dict:
    """The whole run; returns the result line's object. ``control`` is
    for the readings ``correct``'s limits are set from, never for a
    benchmark run: the reference computed in the architecture's
    ``CONTROL`` precision is put in the program's place, span for span of
    the window, and judged as the served scores are (reported under
    ``control`` in the line)."""
    import numpy as np

    from benchmark import gen, hosttrace, judge, loadgen, observe, tracered

    bench, cell, config, traffic = load_cell(workload)
    chips = int(cell["chips"])
    rehearsal = rehearse is not None
    stanza = dict(config["tpuanomaly"])
    limits = dict(config.get("correct", {}))
    if rehearsal:
        stanza.update(rehearse.get("tpuanomaly", {}))
        traffic = {**traffic, **rehearse.get("traffic", {})}
        limits = {**limits, **rehearse.get("correct", {})}
    arch = load_architecture(config, rehearse)
    model = copy.deepcopy(stanza["model_config"])
    deadline_ms = float(stanza["timeout_ms"])

    facts = device_gate(chips, rehearsal)
    try:
        import odigos_tpu  # noqa: F401
    except ImportError as e:
        raise Refused(f"the odigos_tpu package is not importable from "
                      f"{ROOT}: {e}")
    from odigos_tpu.pipeline.service import Collector
    from odigos_tpu.utils.jaxruntime import configure_compile_cache
    from odigos_tpu.utils.telemetry import meter

    cache_dir = configure_compile_cache()
    say(f"compile cache: {cache_dir}")
    # jax keys its persistent cache on a program without its metadata, so
    # a cached executable carries the scope names of whichever tree
    # compiled it first, which may have none, and a trace of it names no
    # part. Every run keys on the metadata too, traced or not: one set of
    # executables a checkout, so that a traced run finds what an untraced
    # one compiled (keyed apart, each kind evicted the other's from a
    # cache that holds one set: PERF.md section 6, PR 27)
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

    # ---- set-up: pool, collector (weights from the seed, warm ladder),
    # untimed warm-up frames
    say(f"set-up: imports and device {time.perf_counter() - T_PROCESS:.2f} s"
        f" since process start")
    t0 = time.perf_counter()
    pool = gen.make_pool(traffic, seed)
    say(f"set-up: pool of {len(pool)} frames {time.perf_counter() - t0:.2f} s")
    t0 = time.perf_counter()
    gateway = render_config(stanza, chips, seed)
    collector = Collector(gateway).start()
    say(f"set-up: collector start + warm ladder "
        f"{time.perf_counter() - t0:.2f} s")
    all_id, flagged_id = exporter_ids(gateway)
    spy = loadgen.ArrivalSpy(collector.graph.exporters[all_id])
    flagged = collector.graph.exporters[flagged_id]
    port = next(r.port for rid, r in collector.graph.receivers.items()
                if rid.split("/")[0] == "otlp")
    lg = loadgen.LoadGenerator(traffic, pool, port, spy, seed, chips=chips,
                               give_up_s=deadline_ms / 1e3 + 20.0)
    t0 = time.perf_counter()
    lg.start()
    say(f"set-up: clients and request templates "
        f"{time.perf_counter() - t0:.2f} s")
    settle_s = deadline_ms / 1e3 + 30.0
    if rehearsal:
        settle_s = float(rehearse.get("settle_s", settle_s))
    try:
        warm = lg.closed(frames=int(traffic["warm_frames"]) * chips,
                         in_flight_frames=int(
                             traffic.get("in_flight_frames", 8)))
        if not lg.settle(warm.spans, settle_s):
            raise Refused(f"warm-up frames did not arrive: have "
                          f"{spy.spans} of {warm.spans} spans")
        spy.reset()
        flagged.clear()

        # ---- the window
        snap0, stages0 = watched(meter.snapshot()), stage_sums(PIPELINE)
        score_spans = ScoreSpans() if trace else None
        trace_dir = os.path.join(OUT_DIR, "trace")
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            t_trace0 = time.perf_counter()
        setup_s = time.perf_counter() - T_PROCESS
        log = lg.window(seconds)
        arrived_all = lg.settle(log.spans, settle_s)
        t_trace1 = time.perf_counter()
        if trace:
            jax.profiler.stop_trace()
        snap1, stages1 = watched(meter.snapshot()), stage_sums(PIPELINE)
        calls = score_spans.finish() if score_spans else []
        missed = score_spans.missed if score_spans else 0
        if missed:
            # the tracer's ring lapped the reader: the calls collected are
            # a part of the window's, and a share read off them is not
            # the window's padded share
            calls = []
        records = list(spy.records)
    finally:
        lg.stop()
    peak = memory_peak(chips)
    collector.shutdown()

    # ---- what arrived
    tl = judge.tally(log, records, lg.sizes)
    lat = judge.latencies_ms(log, tl, deadline_ms)
    first_send = min(log.sent) if log.sent else float("nan")
    last_arrival = float(np.nanmax(tl.last_arrival)) if len(
        tl.last_arrival) and np.isfinite(tl.last_arrival).any() \
        else first_send
    window_s = last_arrival - first_send
    late_ms = (np.asarray(log.sent) - np.asarray(log.due)) * 1e3
    moved = {k: v - snap0.get(k, 0.0) for k, v in snap1.items()}
    stages = {s: (stages1[s][0] - stages0.get(s, (0.0, 0))[0],
                  stages1[s][1] - stages0.get(s, (0.0, 0))[1])
              for s in stages1}
    say("window: " + json.dumps({
        "frames": len(log.serial), "spans_sent": log.spans,
        "spans_scored": int(tl.scored.sum()), "all_arrived": arrived_all,
        "window_s": window_s, "latency_ms_p50_by_third": [
            observe.percentile(part, 50)
            for part in np.array_split(lat, 3)],
        "latency_ms_p95": observe.percentile(lat, 95),
        "generator_late_p95_ms":
        observe.percentile(late_ms, 95), "counters_moved":
        {k: v for k, v in moved.items() if v},
        "stage_mean_ms": {s: round(a / n, 3) for s, (a, n) in stages.items()
                          if n}, "score_calls": len(calls),
        "score_spans_missed": missed, "setup_s": setup_s, "device": facts,
        "rehearsal": rehearsal}))

    device = host = None
    breakdown = None
    if trace:
        planes = tracered.load(trace_dir)
        device = tracered.reduce(planes)
        lines = tracered.describe(planes)
        lines.sort(key=lambda s: not s.startswith(tracered.DEVICE_PREFIX))
        say("\n".join(lines[:40]))
        if device is None and not rehearsal:
            raise Refused("the trace holds no device operation")
        if device is not None:
            device.window_s = t_trace1 - t_trace0
            # a gap under a tenth of a millisecond is the space between
            # two operations of one executable, not the host's doing
            long_gaps = [g for g in device.idle_gaps if g[1] - g[0] >= 1e-4]
            doing = tracered.host_cover(planes, long_gaps)
            breakdown = {
                "device_ops": [[n, s] for n, s in device.top_ops],
                "idle_gaps": ([["before_first_or_after_last_device_op",
                                device.window_s - device.span_s]]
                              + [[f"{what}@+{a - device.t0:.3f}s", b - a]
                                 for what, (a, b)
                                 in zip(doing, long_gaps)])[:10]}
            host = hosttrace.reduce(hosttrace.load(trace_dir),
                                    device.window_s, arch.PARTS)
            if host is None:
                say("hosttrace: the trace holds no engine/enqueue "
                    "annotation; the joined metrics are left out")
            else:
                say("\n".join(hosttrace.table(host)))
        shutil.rmtree(trace_dir, ignore_errors=True)

    pieces = []
    L = int(model["max_len"])
    for p, frame in enumerate(pool):
        counts = np.bincount(frame.trace)
        n_times = int((tl.pool_index[tl.whole] == p).sum())
        per_frame = [min(L, c - a) for c in counts if c
                     for a in range(0, int(c), L)]
        pieces.extend(per_frame * n_times)
    obs = observe.Observation(
        model=model, chips=chips, device_kind=facts["kind"],
        deadline_ms=deadline_ms, window_s=window_s,
        scored_spans=int(tl.scored.sum()), latency_ms=lat, late_ms=late_ms,
        stages=stages, counters=moved, score_calls=calls,
        piece_lengths=pieces, device=device, host=host, arch=arch)

    metrics: dict[str, dict] = {}
    group = "per_layer" if trace else "end_to_end"
    for m in cell_metrics(bench, cell, group):
        if m["name"] == "setup_s":
            value = setup_s
        else:
            value = observe.load_reader(m["name"])(obs)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # ---- correct: once the window has closed, the peak has been read and
    # the program's state is freed
    del collector, spy, records, lg
    free_program_state()
    t_ref = time.perf_counter()
    ref = arch.scores(pool, seed, model)
    correct, compared = judge.compare(tl, pool, ref, limits)
    ref_s = time.perf_counter() - t_ref
    line: dict[str, Any] = {
        "correct": bool(correct), "attempted": int(log.spans),
        "failed": int((tl.sent_spans - tl.scored).sum()), "metrics": metrics,
        "device": {**facts, "memory_peak_bytes": peak}}
    if trace and device is not None:
        line["device"]["busy_s"] = device.busy_mean_s
        line["device"]["window_s"] = device.window_s
        line["breakdown"] = breakdown
    if host is not None:
        line["hosttrace"] = joined_summary(obs)
        say("hosttrace: " + json.dumps(line["hosttrace"]))
    if rehearsal:
        line["rehearsal"] = True
    if control:
        low = arch.scores(pool, seed, model, precision=arch.CONTROL)
        good, read = judge.compare(judge.served_by(tl, pool, low), pool,
                                   ref, limits)
        line["control"] = {"precision": arch.CONTROL, "correct": bool(good),
                           **{k: v["value"] for k, v in read.items()}}
    line["reference_s"] = ref_s
    line["compared"] = compared
    for name, row in compared.items():
        say(f"compared {name}: {row['value']!r} limit {row['limit']!r}")
    return line


def joined_summary(obs: Any) -> dict:
    """What the joined trace says beside the metrics: how many calls
    joined a run, and by part of the architecture the device time a run,
    the operations needed (real spans) and dispatched (every slot of
    every call) and each over the part's device time at the chip's
    peak, in percent."""
    ht = obs.host
    needed = obs.arch.flops_by_part(obs.model, obs.piece_lengths)
    offered = obs.arch.flops_by_part(
        obs.model, [length for _, rows, length in obs.score_calls
                    for _ in range(rows)])
    parts = {}
    for part in needed:
        at_peak = ht.part_s(part) * obs.peak_flops()   # chip-seconds
        if at_peak > 0:
            parts[part] = {
                "ms_a_run": ht.part_ms(part),
                "flops_needed": needed[part],
                "flops_dispatched": offered[part],
                "peak_share_needed": 100.0 * needed[part] / at_peak,
                "peak_share_dispatched": 100.0 * offered[part] / at_peak}
    return {"calls": ht.n_calls, "joined": ht.n_joined, "runs": ht.n_runs,
            "run_id_agree": ht.run_id_agree,
            "scoped_share": ht.scoped_share, "idle_s": ht.idle_s,
            "idle_host_s": ht.idle_host_s,
            "idle_collect_s": ht.idle_collect_s,
            "runs_by_rows": {str(k): v for k, v in ht.runs_by_rows.items()},
            "parts": parts}


def free_program_state() -> None:
    """Drop the program's engines (and their weights) before the
    reference runs; the program keeps them in a process-wide table."""
    import gc

    try:
        from odigos_tpu.components.processors import tpuanomaly

        tpuanomaly._shutdown_shared_engines()
    except (ImportError, AttributeError) as e:
        say(f"could not drop the program's engines: {e}")
    gc.collect()


def main(argv: Optional[list[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", default=None, metavar="FILE")
    ap.add_argument("--control", action="store_true",
                    help="also judge the reference in the architecture's "
                         "CONTROL precision in the program's place (for "
                         "setting limits)")
    args = ap.parse_args(argv)
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace),
                        load_json(args.rehearse) if args.rehearse else None,
                        args.control)
    except Refused as e:
        say(f"benchmark/run.py: {e}")
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # the collector's daemon threads and the TPU runtime's teardown have
    # nothing left to do; leave without waiting on them
    os._exit(code)
