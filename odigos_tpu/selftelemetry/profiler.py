"""Continuous profiler + device-runtime telemetry (ISSUE 3 tentpole).

PR 1 gave the framework self-traces; PR 2 a pipelined engine whose
behavior is visible only while someone watches a span. This module is
the always-on layer over both — the Google-Wide-Profiling model (a
continuously sampling, low-overhead profiler whose data is queryable
after the fact) plus the Dapper model (aggregate metrics linked back to
exemplar traces, utils/telemetry exemplars) applied to our own data
plane and TPU scoring stage:

* ``ContinuousProfiler`` — a daemon thread extending
  ``pprofz.sample_profile``'s statistical sampling into an always-on
  sampler (default ~19 Hz — a prime rate, so periodic work cannot alias
  against the sampling grid) that writes folded-stack profiles into a
  bounded ring of fixed windows (default 12 x 60 s ≈ the last 12
  minutes). Windows merge on demand: ``/debug/profilez?window=N`` on the
  pprof extension serves the last-N-windows merge, and ``odigos
  diagnose`` bundles the full merged profile. Strict no-op when disabled
  in config (the default): no thread, no memory, nothing sampled.
* ``DeviceRuntimeCollector`` — periodically snapshots JAX/TPU runtime
  state into the process ``Meter``: live device arrays and device memory
  stats when the backend exposes them (graceful no-op on CPU), jit cache
  size and cumulative compile seconds per jit site
  (``models.jitstats``), and the engine gauges the scoring pipeline
  already computes but never published — queue depth, in-flight window
  occupancy, bucket-ladder hit rate, padding-waste fraction,
  device_busy_frac — sampled from every registered ``ScoringEngine``.

Both are process-global singletons (``profiler``, ``device_runtime``)
so every surface — extension pages, frontend scrape, CLI bundle — sees
the same data, and both start only when configuration says so
(``start_from_config``; collector configs carry a
``service.telemetry.profiler`` stanza).
"""

from __future__ import annotations

import functools
import os
import sys
import tempfile
import threading
import time
import weakref
from collections import Counter, deque
from dataclasses import dataclass
from typing import Any, Optional

from ..utils.jaxruntime import backend_initialized
from ..utils.telemetry import labeled_key, meter

SAMPLES_METRIC = "odigos_profiler_samples_total"
ROTATED_METRIC = "odigos_profiler_windows_rotated_total"
OVERRUN_METRIC = "odigos_profiler_tick_overruns_total"
SWEEP_METRIC = "odigos_profiler_sweep_ms"

# stacks beyond this per window fold into one synthetic bucket: the ring
# must stay bounded even against pathological stack diversity (deep
# recursion with varying depth mints a new folded stack per sample)
TRUNCATED_STACK = "(truncated)"


@functools.lru_cache(maxsize=4096)
def _module_label(filename: str) -> str:
    """Short module identifier from a code object's filename: the stem,
    or the parent directory for ``__init__`` (every package would
    otherwise collapse into one ``__init__`` frame)."""
    stem, _ = os.path.splitext(os.path.basename(filename))
    if stem == "__init__":
        return os.path.basename(os.path.dirname(filename)) or stem
    return stem


def advance_tick(next_tick: float, now: float,
                 interval: float) -> tuple[float, int]:
    """Advance an absolute tick grid past ``now``: the shared sampling
    discipline (continuous profiler + pprofz on-demand sampler). Returns
    ``(next_tick, missed)`` — overrun ticks are skipped on the original
    grid, never bursted, and ``missed`` counts them. A fixed
    sleep-interval-after-sweep drifts low by exactly the per-sweep cost;
    the absolute grid holds the effective rate under load."""
    next_tick += interval
    if next_tick > now:
        return next_tick, 0
    missed = int((now - next_tick) / interval) + 1
    return next_tick + missed * interval, missed


def fold_stack(frame) -> str:
    """One raw frame chain -> ``module:name;module:name;...`` root-first.

    Frames render as ``module:name``, not bare ``name`` — every
    ``process``/``export`` in the codebase would otherwise merge into a
    single flamegraph frame. Walks ``f_back`` directly: no FrameSummary
    objects, no linecache source lookups, because this runs per thread
    per sample on the always-on path."""
    parts = []
    while frame is not None:
        code = frame.f_code
        parts.append(f"{_module_label(code.co_filename)}:{code.co_name}")
        frame = frame.f_back
    parts.reverse()
    return ";".join(parts)


@dataclass(frozen=True)
class ProfilerConfig:
    """Continuous-profiler knobs (``service.telemetry.profiler`` in a
    collector config; ``selftelemetry`` section of the authored
    Configuration)."""

    enabled: bool = False       # strict no-op unless opted in
    hz: float = 19.0            # prime: no aliasing against periodic work
    window_s: float = 60.0      # fixed window length
    windows: int = 12           # ring capacity (12 x 60 s = 12 min)
    max_stacks_per_window: int = 4096  # distinct folded stacks bound

    def __post_init__(self) -> None:
        # clamp on EVERY construction path (direct construction is
        # public API): hz=0 would kill the sampler thread on a
        # ZeroDivisionError with nothing surfaced
        object.__setattr__(self, "hz",
                           max(1.0, min(float(self.hz), 997.0)))
        object.__setattr__(self, "window_s",
                           max(0.05, float(self.window_s)))
        object.__setattr__(self, "windows", max(1, int(self.windows)))
        object.__setattr__(self, "max_stacks_per_window",
                           max(64, int(self.max_stacks_per_window)))

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "ProfilerConfig":
        return cls(
            enabled=bool(d.get("enabled", False)),
            hz=float(d.get("hz", 19.0)),
            window_s=float(d.get("window_s", 60.0)),
            windows=int(d.get("windows", 12)),
            max_stacks_per_window=int(
                d.get("max_stacks_per_window", 4096)),
        )


class ProfileWindow:
    """One fixed sampling window: folded-stack counts + sample meta."""

    __slots__ = ("index", "start_unix", "end_unix", "samples", "sweeps",
                 "counts")

    def __init__(self, index: int, start_unix: float):
        self.index = index
        self.start_unix = start_unix
        self.end_unix = 0.0
        self.samples = 0   # thread-stack samples folded in
        self.sweeps = 0    # sampler passes over all threads
        self.counts: Counter = Counter()

    def meta(self) -> dict[str, Any]:
        return {
            "index": self.index,
            "start_unix": round(self.start_unix, 3),
            "end_unix": round(self.end_unix, 3) if self.end_unix else None,
            "samples": self.samples,
            "sweeps": self.sweeps,
            "stacks": len(self.counts),
        }


class ContinuousProfiler:
    """Always-on statistical profiler over a bounded window ring.

    The sampler thread sweeps ``sys._current_frames`` on an absolute
    tick grid (``next = prev + 1/hz``, not ``sleep(1/hz)`` after the
    sweep — the pprofz drift fix, shared discipline) so the effective
    rate holds under load; when a sweep overruns its tick the missed
    ticks are skipped and counted, never bursted."""

    def __init__(self, config: Optional[ProfilerConfig] = None):
        self.cfg = config or ProfilerConfig()
        self._lock = threading.Lock()
        self._ring: deque[ProfileWindow] = deque(maxlen=self.cfg.windows)
        self._current: Optional[ProfileWindow] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._windows_rotated = 0

    # ----------------------------------------------------------- lifecycle

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def configure(self, config: ProfilerConfig) -> None:
        """Swap config; ring capacity follows. Refused while running (a
        live sampler holds the old geometry)."""
        if self.running:
            raise RuntimeError("configure() while the sampler is running")
        with self._lock:
            self.cfg = config
            self._ring = deque(self._ring, maxlen=config.windows)

    def start(self) -> bool:
        """Start sampling; False (and nothing allocated, nothing spawned)
        when disabled in config or already running — the strict-no-op
        contract minimal installs rely on."""
        if not self.cfg.enabled or self.running:
            return False
        # per-run stop event: a sampler that outlives a timed-out
        # stop() keeps ITS event set and exits on its next check — a
        # shared cleared event would silently resurrect the zombie
        # alongside the new thread
        stop = threading.Event()
        self._stop = stop
        self._thread = threading.Thread(
            target=self._run, args=(stop,), name="continuous-profiler",
            daemon=True)
        self._thread.start()
        return True

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout)
        self._thread = None

    # ------------------------------------------------------------- sampler

    def _run(self, stop: threading.Event) -> None:
        interval = 1.0 / self.cfg.hz
        me = threading.get_ident()
        next_tick = time.monotonic()
        window_end = next_tick + self.cfg.window_s
        with self._lock:
            self._current = ProfileWindow(self._windows_rotated, time.time())
        while not stop.is_set():
            t0 = time.monotonic()
            if t0 >= window_end:
                self._rotate()
                window_end += self.cfg.window_s
                if window_end <= t0:  # long stall: realign, don't spin
                    window_end = t0 + self.cfg.window_s
            self._sweep(me)
            t1 = time.monotonic()
            meter.record(SWEEP_METRIC, (t1 - t0) * 1e3)
            next_tick, missed = advance_tick(next_tick, t1, interval)
            if missed:
                meter.add(OVERRUN_METRIC, missed)
            stop.wait(max(next_tick - time.monotonic(), 0.0))
        # flush the partial window: stop must lose nothing
        self._rotate(final=True)

    def _sweep(self, own_ident: int) -> None:
        frames = sys._current_frames()
        folded = [fold_stack(f) for ident, f in frames.items()
                  if ident != own_ident]
        with self._lock:
            w = self._current
            if w is None:
                return
            for stack in folded:
                if (len(w.counts) >= self.cfg.max_stacks_per_window
                        and stack not in w.counts):
                    stack = TRUNCATED_STACK
                w.counts[stack] += 1
            w.samples += len(folded)
            w.sweeps += 1
        meter.add(SAMPLES_METRIC, len(folded))

    def _rotate(self, final: bool = False) -> None:
        with self._lock:
            w = self._current
            if w is None or (not w.sweeps and not final):
                return
            w.end_unix = time.time()
            self._ring.append(w)
            self._windows_rotated += 1
            self._current = ProfileWindow(self._windows_rotated, time.time())
        meter.add(ROTATED_METRIC)

    # ------------------------------------------------------------ surfaces

    def windows(self) -> list[ProfileWindow]:
        """Closed windows oldest-first, plus the in-progress one."""
        with self._lock:
            out = list(self._ring)
            if self._current is not None and self._current.sweeps:
                out.append(self._current)
            return out

    def merged(self, last: Optional[int] = None) -> Counter:
        """Merge the last ``last`` windows (default: all) into one folded
        profile — the on-demand cross-window view."""
        ws = self.windows()
        if last is not None and last > 0:
            ws = ws[-last:]
        out: Counter = Counter()
        with self._lock:
            for w in ws:
                out.update(w.counts)
        return out

    def folded(self, last: Optional[int] = None) -> list[str]:
        """Merged profile as flamegraph-ready folded lines
        (``frame;frame count``), hottest first."""
        return [f"{stack} {n}" for stack, n
                in self.merged(last).most_common()]

    def snapshot(self) -> dict[str, Any]:
        """JSON-able state for /debug/profilez and the diagnose bundle."""
        ws = self.windows()
        return {
            "enabled": self.cfg.enabled,
            "running": self.running,
            "hz": self.cfg.hz,
            "window_s": self.cfg.window_s,
            "window_capacity": self.cfg.windows,
            "windows_rotated": self._windows_rotated,
            "windows": [w.meta() for w in ws],
            "samples_total": sum(w.samples for w in ws),
        }


# --------------------------------------------------------- device runtime


class _EngineRegistry:
    """Weak set of live ScoringEngines the collector samples. Weakrefs:
    an engine that is shut down and dropped must not be kept alive (or
    sampled) by telemetry. Each engine gets a stable registration
    ordinal — two live engines of the same model must not overwrite each
    other's gauges in WeakSet iteration order."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._engines: "weakref.WeakSet" = weakref.WeakSet()
        self._ids: "weakref.WeakKeyDictionary" = \
            weakref.WeakKeyDictionary()
        self._next_id = 0

    def register(self, engine) -> None:
        with self._lock:
            self._engines.add(engine)
            if engine not in self._ids:
                self._ids[engine] = self._next_id
                self._next_id += 1

    def unregister(self, engine) -> None:
        with self._lock:
            self._engines.discard(engine)

    def live(self) -> list:
        """(ordinal, engine) pairs, registration order."""
        with self._lock:
            return sorted(((self._ids.get(e, -1), e)
                           for e in self._engines), key=lambda p: p[0])


engines = _EngineRegistry()


@dataclass(frozen=True)
class DeviceRuntimeConfig:
    enabled: bool = False
    interval_s: float = 10.0

    def __post_init__(self) -> None:
        # interval_s=0 would busy-spin the collector thread at 100% CPU
        object.__setattr__(self, "interval_s",
                           max(0.1, float(self.interval_s)))

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "DeviceRuntimeConfig":
        return cls(enabled=bool(d.get("enabled", False)),
                   interval_s=float(d.get("interval_s", 10.0)))


class DeviceRuntimeCollector:
    """Periodic JAX/TPU + engine runtime snapshot into the Meter.

    ``collect_once()`` is the unit of work (also called synchronously by
    tests and the diagnose bundle); ``start()`` runs it on an interval
    daemon thread. Everything device-side is best-effort: device facts
    are read only once this process's own engine has initialised a JAX
    backend (a telemetry thread must never be what imports jax, and
    never what claims the chip — see ``_collect_jax``), and a CPU
    backend without ``memory_stats`` is a graceful no-op."""

    def __init__(self, config: Optional[DeviceRuntimeConfig] = None):
        self.cfg = config or DeviceRuntimeConfig()
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # gauges THIS collector published last pass: anything absent in
        # the current pass is cleared from the meter — a shut-down
        # engine's queue depth must vanish, not freeze at its last value
        self._published: set = set()

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def start(self) -> bool:
        if not self.cfg.enabled or self.running:
            return False
        stop = threading.Event()  # per-run: see ContinuousProfiler.start
        self._stop = stop
        self._thread = threading.Thread(
            target=self._run, args=(stop,),
            name="device-runtime-collector", daemon=True)
        self._thread.start()
        return True

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout)
        self._thread = None
        # the sampler is gone: its gauges must vanish with it, not
        # freeze on /metrics at their last sampled values
        for name in self._published:
            meter.clear_gauge(name)
        self._published = set()

    def _run(self, stop: threading.Event) -> None:
        while not stop.is_set():
            try:
                # pass THIS run's event: a zombie run that outlived a
                # timed-out stop() must consult its own (set) event, not
                # whatever self._stop points at after a restart
                self.collect_once(stop_event=stop)
            except Exception:  # noqa: BLE001 — telemetry must never kill
                meter.add("odigos_device_runtime_errors_total")
            stop.wait(self.cfg.interval_s)

    # ------------------------------------------------------------ sampling

    def collect_once(self, publish: bool = True,
                     stop_event: Optional[threading.Event] = None,
                     ) -> dict[str, float]:
        """One snapshot pass; returns the gauges it collected.
        ``publish=False`` is the read-only mode (diagnose bundle): the
        dict is returned without touching the meter, so a one-shot
        diagnostic cannot freeze stale gauges onto a scrape surface no
        periodic collector will ever refresh."""
        stop_event = stop_event if stop_event is not None else self._stop
        out: dict[str, float] = {}
        out.update(self._collect_engines())
        out.update(self._collect_jax())
        out.update(self._collect_device_tables())
        # a stop() racing a stalled pass must win: publishing after the
        # event is set would re-freeze gauges stop() just cleared, with
        # no collector left to ever refresh them
        if publish and not stop_event.is_set():
            for name, value in out.items():
                meter.set_gauge(name, value)
            for name in self._published - set(out):
                meter.clear_gauge(name)  # source gone (engine shut down)
            self._published = set(out)
            meter.add("odigos_device_runtime_collections_total")
        return out

    # gauge key -> full metric name: the names stay literal so the
    # metric-name lint (test_package_hygiene) can verify them statically
    ENGINE_GAUGES = {
        "queue_depth": "odigos_engine_queue_depth",
        "inflight": "odigos_engine_inflight",
        "window_occupancy": "odigos_engine_window_occupancy",
        "pipeline_depth": "odigos_engine_pipeline_depth",
        "device_calls": "odigos_engine_device_calls",
        "device_busy_frac": "odigos_engine_device_busy_frac",
        "padding_waste_frac": "odigos_engine_padding_waste_frac",
        "bucket_ladder_hit_rate": "odigos_engine_bucket_ladder_hit_rate",
    }

    @classmethod
    def _collect_engines(cls) -> dict[str, float]:
        out: dict[str, float] = {}
        for ordinal, eng in engines.live():
            try:
                gauges = eng.runtime_gauges()
            except Exception:  # noqa: BLE001 — a dying engine: skip it
                continue
            model = gauges.pop("model", "unknown")
            # multi-chip engines label their gauges per mesh shape
            # (ISSUE 7: padding_waste_frac / bucket_ladder_hit_rate are
            # per-mesh quantities once the engine owns a dp×tp mesh);
            # single-device engines keep the unlabeled legacy keys
            mesh = gauges.pop("mesh", None)
            labels = {"model": model, "engine": str(ordinal)}
            if mesh is not None:
                labels["mesh"] = str(mesh)
            for key, value in gauges.items():
                name = cls.ENGINE_GAUGES.get(key)
                if name is not None:
                    # engine ordinal disambiguates two live engines of
                    # the same model (blue/green overlap, A/B)
                    out[labeled_key(name, **labels)] = float(value)
        return out

    @staticmethod
    def _collect_jax() -> dict[str, float]:
        if not backend_initialized():
            # passive by construction: never the importer, and never the
            # process that INITIALISES a backend. jax being imported
            # proves neither — with ``model: remote`` the chip belongs
            # to the sidecar, and asking jax for its devices from here
            # would claim it (or, the sidecar already holding it, fail
            # on the libtpu lockfile every interval)
            return {}
        import jax

        out: dict[str, float] = {}
        try:
            live = jax.live_arrays()
            out["odigos_device_live_arrays"] = float(len(live))
            out["odigos_device_live_array_bytes"] = float(
                sum(getattr(a, "nbytes", 0) or 0 for a in live))
        except Exception:  # noqa: BLE001 — backend without live_arrays
            pass
        try:
            for i, dev in enumerate(jax.devices()):
                stats = getattr(dev, "memory_stats", None)
                stats = stats() if callable(stats) else None
                if not stats:
                    continue  # CPU backends return None: graceful no-op
                for src, name in (
                        ("bytes_in_use", "odigos_device_bytes_in_use"),
                        ("bytes_limit", "odigos_device_bytes_limit"),
                        ("peak_bytes_in_use", "odigos_device_peak_bytes")):
                    if src in stats:
                        out[labeled_key(name, device=str(i))] = \
                            float(stats[src])
        except Exception:  # noqa: BLE001
            pass
        try:
            from ..models import jitstats

            for site, size in jitstats.cache_sizes().items():
                out[labeled_key("odigos_jit_cache_size", site=site)] = \
                    float(size)
            for site, secs in jitstats.compile_seconds().items():
                out[labeled_key("odigos_jit_compile_seconds_total",
                                site=site)] = round(secs, 6)
        except Exception:  # noqa: BLE001
            pass
        return out

    @staticmethod
    def _collect_device_tables() -> dict[str, float]:
        """Device-resident footprint of the fused route (ISSUE 20):
        bytes pinned by the interned hash-table LRU plus each live
        plan's placed weight pytree. Published via the same `_published`
        set as everything else, so a shut-down engine's plan gauge is
        stale-cleared, never frozen. Reads module state only — never the
        importer (no jax module in ``sys.modules`` means the fused
        module cannot be there either, and the getattr chain degrades
        to nothing)."""
        out: dict[str, float] = {}
        fused = sys.modules.get("odigos_tpu.serving.fused")
        if fused is not None:
            try:
                table_bytes = float(fused.device_table_bytes())
                if table_bytes > 0:
                    out[labeled_key("odigos_device_table_bytes",
                                    site="fused.tables")] = table_bytes
            except Exception:  # noqa: BLE001
                pass
        for ordinal, eng in engines.live():
            try:
                plan = getattr(getattr(eng, "backend", None), "_plan",
                               None)
                if plan is None:
                    continue
                placed = float(plan.placed_bytes())
                if placed > 0:
                    out[labeled_key(
                        "odigos_device_table_bytes",
                        site=f"plan.{plan.key}",
                        engine=str(ordinal))] = placed
            except Exception:  # noqa: BLE001 — a dying engine: skip it
                continue
        return out


# ----------------------------------------------------------- process-global

profiler = ContinuousProfiler()
device_runtime = DeviceRuntimeCollector()


def start_from_config(telemetry: Optional[dict[str, Any]]) -> list[str]:
    """Apply a ``service.telemetry`` stanza to the process singletons;
    returns which subsystems this call started (the caller that started
    them stops them — see ``stop_started``). Absent/disabled stanza =
    strict no-op. Never raises: a malformed stanza (``hz: "19hz"``)
    counts an error and degrades to not-started — telemetry must not
    kill a collector whose graph already started, and a reload that
    swapped the graph must not be reported failed over a profiler
    knob."""
    started = []
    try:
        stanza = (telemetry or {}).get("profiler") or {}
        if stanza.get("enabled") and not profiler.running:
            profiler.configure(ProfilerConfig.from_dict(stanza))
            if profiler.start():
                started.append("profiler")
    except Exception:  # noqa: BLE001
        meter.add("odigos_selftelemetry_config_errors_total")
    try:
        stanza = (telemetry or {}).get("device_runtime") or {}
        if stanza.get("enabled") and not device_runtime.running:
            device_runtime.cfg = DeviceRuntimeConfig.from_dict(stanza)
            if device_runtime.start():
                started.append("device_runtime")
    except Exception:  # noqa: BLE001
        meter.add("odigos_selftelemetry_config_errors_total")
    return started


def stop_started(started: list[str]) -> None:
    """Stop exactly the subsystems a prior ``start_from_config`` call
    reported starting (a collector shutting down must not stop a
    profiler another owner started)."""
    if "profiler" in started:
        profiler.stop()
    if "device_runtime" in started:
        device_runtime.stop()


_capture_lock = threading.Lock()


def capture_trace(seconds: float) -> tuple[int, dict[str, Any]]:
    """One ``jax.profiler`` trace of ``seconds`` (1 to 30) of this
    process, for ``/debug/xlaz?trace_s=``: the operator's way to see the
    program's annotations (``latency.ANNOTATIONS``) and the model's
    parts on the device's clock. Answers (HTTP status, body): 200 with
    the directory the trace was written under; 409 while another
    capture runs, here or in anyone's own ``start_trace`` (one profiler
    session a process: it cannot cross a benchmark's); 503 where this
    process holds no JAX backend (with ``model: remote`` the sidecar
    holds the chip, and only the process that holds it can trace it)."""
    if not 1.0 <= seconds <= 30.0:
        return 400, {"error": "trace_s must be between 1 and 30"}
    if not backend_initialized():
        return 503, {"error": "this process has initialised no JAX "
                              "backend; trace the process that holds "
                              "the chip"}
    if not _capture_lock.acquire(blocking=False):
        return 409, {"error": "a trace capture is already running"}
    try:
        import jax

        trace_dir = tempfile.mkdtemp(prefix="odigos-xlaz-")
        try:
            jax.profiler.start_trace(trace_dir)
        except RuntimeError as e:
            # jax allows one session: someone else's is live
            os.rmdir(trace_dir)
            return 409, {"error": f"a profiler session is live: {e}"}
        try:
            time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
        return 200, {"trace_dir": trace_dir, "seconds": seconds}
    finally:
        _capture_lock.release()


def device_snapshot() -> dict[str, Any]:
    """The device-plane observability join (ISSUE 20): one JSON-able
    dict backing ``GET /api/device``, ``/debug/xlaz``, ``describe``,
    and the diagnose bundle's ``device.json``. The four top-level
    containers are ALWAYS present (empty when the subsystem never
    armed) so every consumer indexes without existence checks:

    * ``attribution`` — per live fused engine, the sampler's stats
      (stride, kill-switch state, sampled/skipped counters, the last
      published sub-stage waterfall);
    * ``cost`` — the XLA cost/efficiency ledger snapshot (expected
      FLOPs/bytes, flop-waste, achieved efficiency per site × bucket);
    * ``compiles`` — the ring of recent compile events, newest first;
    * ``tables`` — device-resident fused footprint in bytes (interned
      hash tables + each live plan's placed weights).
    """
    out: dict[str, Any] = {
        "attribution": [],
        "cost": {"rows": [], "best_flops_per_s": {},
                 "captures_skipped": 0, "captures_failed": 0,
                 "last_error": None},
        "compiles": [],
        "tables": {},
    }
    try:
        from ..models.costmodel import cost_ledger
        out["cost"] = cost_ledger.snapshot()
    except Exception:  # noqa: BLE001
        pass
    try:
        from ..models import jitstats
        out["compiles"] = jitstats.recent_compiles()
    except Exception:  # noqa: BLE001
        pass
    for ordinal, eng in engines.live():
        try:
            backend = getattr(eng, "backend", None)
            attrib = getattr(backend, "_attrib", None)
            if attrib is None:
                continue
            entry = {"engine": ordinal,
                     "site": getattr(backend, "fused_site", None)
                     or "fused"}
            entry.update(attrib.stats())
            out["attribution"].append(entry)
        except Exception:  # noqa: BLE001 — a dying engine: skip it
            continue
    fused = sys.modules.get("odigos_tpu.serving.fused")
    if fused is not None:
        try:
            out["tables"]["fused.tables"] = int(
                fused.device_table_bytes())
        except Exception:  # noqa: BLE001
            pass
    for ordinal, eng in engines.live():
        try:
            plan = getattr(getattr(eng, "backend", None), "_plan", None)
            if plan is not None:
                out["tables"][f"plan.{plan.key}"] = \
                    int(plan.placed_bytes())
        except Exception:  # noqa: BLE001
            continue
    return out
