"""Latency attribution: per-frame stage waterfall, deadline-burn blame,
and multi-window burn-rate SLOs.

The flow ledger (PR 5) proves *what* flows — conservation per edge,
named drops — but not *where time goes*: a frame's tail latency arrives
with zero attribution across
wire→admission→decode→featurize→queue→pack→device→harvest→tag→forward.
This module is that attribution layer, the signal the ROADMAP's
auto-tuner item ("closes the loop from profiler/gauges back into batch
sizes, ladder rungs, replica counts") is blocked on:

* a :class:`StageClock` rides each wire frame through the ingest fast
  path and the scoring engine — the wire receiver stamps the admission
  verdict and decode, the fast path stamps submit/featurize/enqueue/
  wait/tag/forward (``wait`` is the completion-driven gap between the
  scores landing and a retirement lane picking the frame up — ISSUE 9
  redefined it from the old single-forwarder head-of-line wait), and
  the engine's per-call ``pack_ms``/``harvest_ms``/``overlap_ms``
  accounting (PR 2) is merged in as the queue/pack/device/harvest
  stages. Within ONE frame the stages tile
  its wall end to end (queue→pack→device→harvest is that frame's own
  serial critical path even under the depth-2 pipelined window; the
  cross-call host/device overlap rides along as ``overlap_ms``), so
  ``Σ stages ≈ wall`` per frame — the accounting
  ``tests/test_latency.py`` pins within tolerance.
* stage durations aggregate into
  ``odigos_latency_stage_ms{pipeline=,stage=}`` histograms with
  exemplars linking each tail sample to the self-trace that carried the
  frame (resolve via ``/api/selftrace?trace_id=``), plus a per-pipeline
  ``odigos_latency_e2e_ms`` end-to-end histogram.
* deadline-carrying frames get **burn accounting**: the burn table
  reports which stage consumed what fraction of the admission budget,
  and every expired deadline names a **blamed stage** — ``device`` when
  the request had been dispatched (the device call outran the budget),
  ``queue`` when it never left the engine queue. Blame is a new
  *dimension* on the existing drop taxonomy (``FlowContext.drop(...,
  blame=)`` and ``odigos_latency_deadline_expired_spans_total
  {pipeline=,blame=}``), never a new drop reason.
* declarative SLOs (``slo: {latency_p99_ms, scored_fraction}`` per
  pipeline, rendered by pipelinegen from ``anomaly.slo``) evaluate with
  Google-SRE-style fast/slow-window burn rates: burn = observed
  bad-fraction ÷ error budget (a p99 target affords a 1 % budget; a
  scored-fraction target Y affords 1−Y). ``SLOBurn`` raises while the
  fast window burns ≥ ``fast_burn_threshold`` (default 14.4, the SRE
  page threshold) AND the slow window confirms budget is actually being
  consumed (burn ≥ ``slow_burn_threshold``, default 1.0) — so a fault
  flips the condition within the fast window and a recovery clears it
  as soon as the fast window drains. Conditions surface through PR 5's
  ``HealthRollup`` as ``slo/<pipeline>`` rows, on ``GET /api/slo``,
  ``/debug/latencyz``, the dashboard, describe, and the diagnose
  bundle's ``latency.json``.

``ODIGOS_LATENCY=0`` disables the layer (clocks become no-ops, nothing
records) — the same opt-out contract as ``ODIGOS_FLOW`` /
``ODIGOS_SELFTRACE``.
"""

from __future__ import annotations

import contextvars
import enum
import os
import sys
import threading
import time
from collections import deque
from typing import Any, Callable, Optional

from ..utils.telemetry import labeled_key, meter

STAGE_METRIC = "odigos_latency_stage_ms"
E2E_METRIC = "odigos_latency_e2e_ms"
EXPIRED_METRIC = "odigos_latency_deadline_expired_spans_total"

# SRE multi-window defaults: 14.4 is the classic page-threshold burn
# rate (2 % of a 30-day budget in one hour); the slow window confirms
# at >= 1.0 ("budget is actually being consumed"), so detection latency
# is bounded by the FAST window while one tail blip cannot page alone.
DEFAULT_FAST_WINDOW_S = 60.0
DEFAULT_SLOW_WINDOW_S = 300.0
DEFAULT_FAST_BURN = 14.4
DEFAULT_SLOW_BURN = 1.0


class Stage(enum.Enum):
    """The closed stage taxonomy one frame traverses on the fast path.

    Closed for the same reason DROP_REASONS is: free-form stage names
    would rot into unaggregatable cardinality. The package-hygiene lint
    (``TestLatencyStageHygiene``) asserts every member has exactly one
    stamp site across the fast path — a stage stamped twice would
    double-count its wall, a stage never stamped would silently vanish
    from the waterfall.
    """

    ADMISSION = "admission"   # frame header read -> admission verdict
    DECODE = "decode"         # verdict -> zero-copy decoded SpanBatch
    SUBMIT = "submit"         # decode -> submit-lane pickup (intake handoff)
    FEATURIZE = "featurize"   # decode -> device-ready feature matrices
    ENQUEUE = "enqueue"       # featurized -> engine queue accepted
    QUEUE = "queue"           # engine queue wait (submit -> pack start)
    PACK = "pack"             # host coalesce/pack (pack start -> dispatch)
    FUSED = "fused"           # fused route: column assembly -> device enqueue
    DEVICE = "device"         # device execution (dispatch -> harvest start)
    HARVEST = "harvest"       # result fetch + scatter (harvest -> scores)
    WAIT = "wait"             # scores landed -> retirement-lane pickup
    TAG = "tag"               # anomaly attribute tagging
    FORWARD = "forward"       # downstream consume (router/exporter edge)


# the four stages the ENGINE accounts per coalesced call (PR 2's
# pack/device/harvest split + per-request queue wait), merged into the
# frame clock by ``StageClock.merge_engine`` — the lint counts this
# tuple as those stages' single stamp site
ENGINE_STAGES = (Stage.QUEUE, Stage.PACK, Stage.DEVICE, Stage.HARVEST)

# the fused-route variant (ISSUE 19): host featurize+pack collapse into a
# single FUSED stage (column assembly + device-call enqueue) so the burn
# table prices the route it actually runs. Selected by ``merge_engine``
# when the engine flags the group as fused; together with ENGINE_STAGES
# these tuples are the single stamp site for their member stages.
ENGINE_STAGES_FUSED = (Stage.QUEUE, Stage.FUSED, Stage.DEVICE, Stage.HARVEST)

# the full stage vocabulary in traversal order — metric keys, waterfalls
# and burn tables iterate this (a fused frame's stages must aggregate
# like any other). STAGES keeps its pre-fused meaning: the HOST-route
# traversal, exactly the stages one non-fused frame stamps, once each,
# in order (the tiling tests pin frame["stages"] == STAGES); a fused
# frame swaps featurize+pack for the single `fused` stamp instead.
ALL_STAGES = tuple(s.value for s in Stage)
STAGES = tuple(s.value for s in Stage if s is not Stage.FUSED)

# blame value for PREDICTIVE admission sheds (ISSUE 12): a frame the
# fast path rejected because the priced burn table said it would expire
# before scoring. Not a Stage — no wall was ever spent — but it rides
# the same blame dimension (odigos_latency_deadline_expired_spans_total
# {blame=predicted} + the drop taxonomy's blame label) so every
# deadline-driven loss, realized or predicted, is countable in one place.
PREDICTED_BLAME = "predicted"

# the working stages of the scored path as the profiler's trace shows
# them (``annotate`` below), one site each; the pure waits (submit,
# queue, device, wait) have none, because nobody works in them. Closed
# like Stage, and held equal to the sites by the package-hygiene lint
# (``TestAnnotationHygiene``). The benchmark's trace reduction
# (benchmark/hosttrace.py) reads the ``engine/*`` names. Besides the
# stages: the wait for the device inside a harvest (``engine/ready``),
# and every collection of the interpreter's garbage collector, on
# whichever thread it ran (``python/gc``, serving/gcisolation.py).
ANNOTATIONS = (
    "wire/admission", "wire/decode",            # receiver thread
    "fastpath/featurize", "fastpath/enqueue",   # submit lane
    "engine/collect", "engine/pack", "engine/enqueue",
    "engine/harvest", "engine/ready", "engine/scatter",  # engine worker
    "lane/tag", "lane/forward",                 # retirement lane
    "python/gc",                                # any thread
)

# bounded ring of recent frame clocks per recorder: the latencyz
# waterfall witnesses AND the window the predictive gate's stage means
# are computed over (consumers clamping thresholds key off this)
RECENT_WINDOW = 64


class StageClock:
    """Per-frame stage timeline: consecutive ``stamp()`` calls turn one
    monotonic clock read each into the duration since the previous mark,
    so the stages tile the frame's wall exactly (no gaps, no overlaps
    within one frame). Threads hand the clock off FIFO with the frame
    (receiver thread -> forwarder thread); the window queue is the
    synchronization, the clock itself is never shared concurrently."""

    __slots__ = ("t0", "_mark", "stages", "ctx", "overlap_ms",
                 "device_attrib", "fused_bucket", "call")

    def __init__(self, ctx: Optional[tuple[int, int]] = None):
        self.t0 = self._mark = time.monotonic_ns()
        # (stage label, duration_ms) in traversal order
        self.stages: list[tuple[str, float]] = []
        self.ctx = ctx  # (trace_id, span_id) exemplar link
        self.overlap_ms = 0.0
        # ISSUE 20 device-plane payloads, merged from the engine call:
        # the sampled intra-fused waterfall (None on unsampled frames)
        # and the fused shape bucket ("r{rows}x{len}") the frame ran in
        self.device_attrib: Optional[dict] = None
        self.fused_bucket: Optional[str] = None
        # serial of the engine call the frame rode (``call`` on the
        # engine/* annotations, ``call.serial`` on its tpu/score span)
        self.call: Optional[int] = None

    def stamp(self, stage: Stage) -> None:
        now = time.monotonic_ns()
        self.stages.append((stage.value, (now - self._mark) / 1e6))
        self._mark = now

    def bind_trace(self, ctx: Optional[tuple]) -> None:
        """Attach the self-trace context carrying this frame (the
        pipeline/<name> span): every histogram sample this clock records
        becomes an exemplar resolvable via /api/selftrace."""
        if ctx is not None:
            self.ctx = (ctx[0], ctx[1])

    def merge_engine(self, info: dict[str, Any]) -> None:
        """Fold one engine call's stage boundaries (monotonic ns, same
        clock domain — ``ScoreRequest.stage_ns``) into the timeline as
        the QUEUE/PACK/DEVICE/HARVEST stages. Boundaries are clamped
        monotone non-decreasing from the current mark: the engine worker
        can start packing BEFORE the intake thread stamps ENQUEUE (the
        depth-2 window races submit), and a negative stage would corrupt
        the tiling by more than the microseconds it saves."""
        mark = self._mark
        stages = ENGINE_STAGES_FUSED if info.get("fused") else ENGINE_STAGES
        for stage, end in zip(stages,
                              (info["pack0"], info["dispatch"],
                               info["harvest0"], info["end"])):
            end = max(int(end), mark)
            self.stages.append((stage.value, (end - mark) / 1e6))
            mark = end
        self._mark = mark
        self.overlap_ms = float(info.get("overlap_ms") or 0.0)
        self.device_attrib = info.get("device_attrib")
        self.fused_bucket = info.get("fused_bucket")
        self.call = info.get("call")

    def wall_ms(self) -> float:
        return (self._mark - self.t0) / 1e6

    def sum_ms(self) -> float:
        return sum(d for _, d in self.stages)

    def to_dict(self) -> dict[str, Any]:
        return {"stages": [{"stage": s, "ms": round(d, 4)}
                           for s, d in self.stages],
                "wall_ms": round(self.wall_ms(), 4),
                "overlap_ms": round(self.overlap_ms, 4),
                "call": self.call}


class _NullClock:
    """Shared no-op clock when the layer is disabled (ODIGOS_LATENCY=0):
    every stamp site pays one attribute load and a no-op call."""

    __slots__ = ()
    ctx = None
    overlap_ms = 0.0
    stages: list = []
    device_attrib = None
    fused_bucket = None
    call = None

    def stamp(self, stage: Stage) -> None:
        pass

    def bind_trace(self, ctx) -> None:
        pass

    def merge_engine(self, info) -> None:
        pass

    def wall_ms(self) -> float:
        return 0.0

    def sum_ms(self) -> float:
        return 0.0

    def to_dict(self) -> dict[str, Any]:
        return {"stages": [], "wall_ms": 0.0, "overlap_ms": 0.0,
                "call": None}


NULL_CLOCK = _NullClock()

_trace_me: Any = None  # jax.profiler.TraceAnnotation, once jax is loaded


class annotate:
    """Bracket one working stage on the profiler's clock, where the work
    happens: ``with annotate("lane/tag", clock, Stage.TAG): ...`` opens
    a ``jax.profiler.TraceAnnotation`` named as ``ANNOTATIONS`` has it
    and, where the stage is one of ``Stage``, stamps the frame's clock
    on the way out, so that stamp and annotation cannot drift apart. An
    exception passing through closes the annotation and leaves the
    clock unstamped, as the bare stamps did. ``args`` become the trace
    event's arguments; ``set`` adds what is known only at the end.

    With no profiler session live this costs an inactive TraceMe: one
    flag read each way, no lock, nothing formatted (the name and
    arguments are rendered by the TraceMe only when a session records).
    A process that has not imported jax has no profiler to show up in
    and opens nothing."""

    __slots__ = ("_tm", "_clock", "_stage")

    def __init__(self, name: str, clock: Any = None,
                 stage: Optional[Stage] = None, **args: Any):
        global _trace_me
        cls = _trace_me
        if cls is None:
            # looked up, never imported: a worker thread importing jax
            # while the main thread is still inside ``import jax``
            # deadlocks on the module lock
            cls = _trace_me = getattr(sys.modules.get("jax.profiler"),
                                      "TraceAnnotation", None)
        self._tm = cls(name, **args) if cls is not None else None
        self._clock = clock
        self._stage = stage

    def __enter__(self) -> "annotate":
        if self._tm is not None:
            self._tm.__enter__()
        return self

    def set(self, **args: Any) -> None:
        if self._tm is not None:
            self._tm.set_metadata(**args)

    def close(self) -> None:
        """End the annotation before the block does (the next stage
        starts inside it); the block's own exit is then a no-op."""
        tm, self._tm = self._tm, None
        if tm is not None:
            tm.__exit__(None, None, None)

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._clock is not None and exc_type is None:
            self._clock.stamp(self._stage)
        self.close()


_prctl: Any = None


def name_thread(role: str) -> None:
    """Give the calling OS thread the name ``role`` (Linux keeps 15
    bytes), so that the profiler's host plane tells the receiver, the
    submit lanes, the engine worker and the retirement lanes apart:
    Python 3.12 does not pass ``threading.Thread(name=)`` to the OS.
    Best effort: a platform without prctl keeps its unnamed lines."""
    global _prctl
    if _prctl is None:
        try:
            import ctypes
            _prctl = ctypes.CDLL(None).prctl
        except (OSError, AttributeError):
            _prctl = False
    if _prctl:
        _prctl(15, role.encode()[:15], 0, 0, 0)  # PR_SET_NAME

# hands the receiver-started clock to the fast path across the consume
# seam (same thread, synchronous call chain — the receiver cannot pass
# a parameter through the Consumer interface without breaking every
# other consumer)
_active_clock: contextvars.ContextVar[Optional[StageClock]] = \
    contextvars.ContextVar("odigos_latency_clock", default=None)


def start_clock() -> StageClock:
    """A fresh frame clock, or the shared no-op when the layer is off."""
    if not latency_ledger.enabled:
        return NULL_CLOCK  # type: ignore[return-value]
    return StageClock()


def publish_clock(clock) -> contextvars.Token:
    return _active_clock.set(clock if clock is not NULL_CLOCK else None)


def unpublish_clock(token: contextvars.Token) -> None:
    _active_clock.reset(token)


def claim_clock():
    """Take the receiver-published clock (one claimant per frame); a
    directly-fed fast path (no wire hop) starts its own, so the
    waterfall simply lacks the admission/decode stages."""
    clock = _active_clock.get()
    if clock is not None:
        _active_clock.set(None)
        return clock
    return start_clock()


def latency_enabled() -> bool:
    return latency_ledger.enabled


class _Recorder:
    """Per-pipeline aggregation: stage/e2e histograms (meter-resident,
    exemplar-carrying), per-stage running totals for the burn table, an
    expiry-blame table, and a bounded ring of recent clocks (the
    ``/debug/latencyz`` waterfall witnesses and the accounting tests'
    evidence)."""

    __slots__ = ("pipeline", "deadline_ms", "frames", "scored_frames",
                 "overlap_ms_total", "_stage_keys", "_e2e_key", "_totals",
                 "_expired", "recent", "_worst_blame", "_lock",
                 "_device_stages", "_device_sampled",
                 "_device_fused_ms_total", "_device_recent",
                 "_worst_fused")

    def __init__(self, pipeline: str):
        self.pipeline = pipeline
        self.deadline_ms: Optional[float] = None
        self.frames = 0
        self.scored_frames = 0
        self.overlap_ms_total = 0.0
        self._stage_keys = {
            s: labeled_key(STAGE_METRIC, pipeline=pipeline, stage=s)
            for s in ALL_STAGES}
        self._e2e_key = labeled_key(E2E_METRIC, pipeline=pipeline)
        self._totals: dict[str, list[float]] = {}  # stage -> [sum, count]
        self._expired: dict[str, int] = {}         # blame -> spans
        self.recent: deque[dict[str, Any]] = deque(maxlen=RECENT_WINDOW)
        # blame -> (wall_ms, trace_id, span_id, unix_ts): the worst
        # EXPIRED frame per blame dimension that carried a self-trace
        # (incident bundles join these — a p99 spike names one frame)
        self._worst_blame: dict[str, tuple] = {}
        # ISSUE 20 device burn table, nested under the FUSED stage:
        # sub-stage -> [sum_ms, count] over sampled attribution frames,
        # plus the fused stamps those samples decomposed and a short
        # ring of raw waterfalls for /debug/latencyz
        self._device_stages: dict[str, list[float]] = {}
        self._device_sampled = 0
        self._device_fused_ms_total = 0.0
        self._device_recent: deque[dict] = deque(maxlen=8)
        # (fused_stage_ms, trace_id, span_id, bucket, unix_ts): the
        # worst fused-stage frame that carried a self-trace — the
        # exemplar join's anchor (its bucket keys the compile-event
        # ring and the cost ledger)
        self._worst_fused: Optional[tuple] = None
        self._lock = threading.Lock()

    def observe(self, clock: StageClock, scored: bool) -> None:
        wall = clock.wall_ms()
        ex = clock.ctx
        if scored:
            # stage histograms carry scored frames only: an expired
            # frame's engine stages are unknowable (the request never
            # harvested), and recording its truncated partials would
            # bias exactly the tails the waterfall exists to explain.
            # One record_many = one meter lock hold for the whole
            # waterfall; the exemplar reservoir stays populated from
            # every 8th frame (algorithm-R does not need every sample
            # to carry a witness — allocating 13 exemplars per frame
            # would be the layer's own overhead bound violation)
            keys = self._stage_keys
            samples = [(keys[stage], d) for stage, d in clock.stages]
            samples.append((self._e2e_key, wall))
            stage_ex = ex if (self.frames & 7) == 0 else None
            meter.record_many(samples, exemplar=stage_ex)
        else:
            meter.record(self._e2e_key, wall, exemplar=ex)
        attrib = clock.device_attrib
        bucket = clock.fused_bucket
        with self._lock:
            self.frames += 1
            if scored:
                self.scored_frames += 1
                self.overlap_ms_total += clock.overlap_ms
                totals = self._totals
                fused_ms = None
                for stage, d in clock.stages:
                    tot = totals.get(stage)
                    if tot is None:
                        tot = totals[stage] = [0.0, 0]
                    tot[0] += d
                    tot[1] += 1
                    if stage == Stage.FUSED.value:
                        fused_ms = d
                if attrib is not None:
                    # sampled intra-fused waterfall: fold the sub-stage
                    # stamps into the device burn table nested under
                    # FUSED (ISSUE 20)
                    self._device_sampled += 1
                    self._device_fused_ms_total += float(
                        attrib.get("fused_device_ms") or 0.0)
                    dstages = self._device_stages
                    for sub, d in (attrib.get("stages") or {}).items():
                        tot = dstages.get(sub)
                        if tot is None:
                            tot = dstages[sub] = [0.0, 0]
                        tot[0] += d
                        tot[1] += 1
                    self._device_recent.append(attrib)
                if (bucket is not None and fused_ms is not None
                        and ex is not None):
                    worst = self._worst_fused
                    if worst is None or fused_ms > worst[0]:
                        self._worst_fused = (fused_ms, ex[0], ex[1],
                                             bucket, time.time())
            # raw refs only — the clock is dead after retire, and
            # rendering dicts per frame costs more than the rest of
            # this method (snapshot() renders on demand). The ctx ref
            # rides along so worst_frames() can name the slowest
            # frame's self-trace without a per-frame allocation.
            self.recent.append(
                (clock.stages, wall, clock.overlap_ms, scored, ex,
                 clock.call))

    def record_expiry(self, blame: str, n_spans: int,
                      clock=None) -> None:
        with self._lock:
            self._expired[blame] = self._expired.get(blame, 0) + n_spans
            if clock is not None and clock.ctx is not None:
                wall = clock.wall_ms()
                prev = self._worst_blame.get(blame)
                if prev is None or wall > prev[0]:
                    self._worst_blame[blame] = (
                        wall, clock.ctx[0], clock.ctx[1], time.time())

    def worst_frames(self) -> list[dict[str, Any]]:
        """Worst-frame trace exemplars: the slowest traced frame over
        the recent window, plus the worst expired frame per ``blame=``
        dimension — each a concrete self-trace id an operator (or an
        incident bundle) can pull the full timeline for."""
        out: list[dict[str, Any]] = []
        with self._lock:
            worst = None
            for stages, wall, _ov, scored, ex, _call in self.recent:
                if ex is None:
                    continue
                if worst is None or wall > worst[0]:
                    worst = (wall, ex, scored)
            blames = dict(self._worst_blame)
        if worst is not None:
            out.append({
                "pipeline": self.pipeline, "scope": "window",
                "wall_ms": round(worst[0], 4),
                "trace_id": f"{worst[1][0]:032x}",
                "span_id": f"{worst[1][1]:016x}",
                "scored": worst[2],
            })
        for blame, (wall, tid, sid, ts) in sorted(blames.items()):
            out.append({
                "pipeline": self.pipeline, "scope": f"blame:{blame}",
                "wall_ms": round(wall, 4),
                "trace_id": f"{tid:032x}", "span_id": f"{sid:016x}",
                "unix_ts": ts,
            })
        with self._lock:
            worst_fused = self._worst_fused
        if worst_fused is not None:
            fused_ms, tid, sid, bucket, ts = worst_fused
            entry = {
                "pipeline": self.pipeline, "scope": "fused",
                # the fused stamp doubles as wall_ms: the ledger-level
                # worst_frames() sorts every scope on that key
                "wall_ms": round(fused_ms, 4),
                "fused_ms": round(fused_ms, 4),
                "trace_id": f"{tid:032x}", "span_id": f"{sid:016x}",
                "bucket": bucket, "unix_ts": ts,
            }
            # exemplar join (ISSUE 20): the worst fused-stage frame
            # links to its bucket's most recent compile event and its
            # cost-ledger row — a tail spike names the shape, whether
            # it recompiled, and what XLA expected it to cost
            try:
                from ..models import jitstats
                from ..models.costmodel import cost_ledger
                compiles = jitstats.recent_compiles(shape=bucket)
                if compiles:
                    entry["last_compile"] = compiles[0]
                row = None
                for r in cost_ledger.snapshot()["rows"]:
                    if r["bucket"] == bucket:
                        row = r
                        break
                if row is not None:
                    entry["cost"] = row
            except Exception:  # noqa: BLE001 — the join is best-effort
                pass
            out.append(entry)
        return out

    def device_burn(self) -> Optional[dict[str, Any]]:
        """The sampled intra-fused device burn table (ISSUE 20), nested
        under the FUSED stage: per-sub-stage mean device ms over the
        sampled attribution frames, the mean fused stamp those samples
        decomposed, and the reconcile ratio (Σ sub-stage means ÷ mean
        fused stamp — ≈1.0 means the decomposition accounts for the
        opaque stamp; the residue is lost cross-stage XLA fusion plus
        per-stage dispatch). None until a frame was sampled, so existing
        payload shapes are untouched when attribution is off."""
        with self._lock:
            if not self._device_sampled:
                return None
            sampled = self._device_sampled
            fused_total = self._device_fused_ms_total
            dstages = {s: (t[0], t[1])
                       for s, t in self._device_stages.items()}
            recent = list(self._device_recent)
        by_stage = {}
        sub_sum = 0.0
        for s, (tot, n) in dstages.items():
            mean = tot / n
            sub_sum += mean
            by_stage[s] = {"mean_ms": round(mean, 4), "count": n}
        fused_mean = fused_total / sampled if sampled else 0.0
        return {
            "sampled_frames": sampled,
            "fused_mean_ms": round(fused_mean, 4),
            "substage_sum_ms": round(sub_sum, 4),
            "reconcile_ratio": round(sub_sum / fused_mean, 4)
            if fused_mean > 0 else None,
            "stages": by_stage,
            "recent": recent,
        }

    def stage_means(self) -> tuple[int, dict[str, float]]:
        """(scored frames in window, per-stage mean ms over the RECENT
        ring) — the predictive admission gate's burn pricing input
        (ISSUE 12). Windowed on purpose: the lifetime ``_totals`` means
        never decay, so an overload that pushed them past the deadline
        would keep pricing frames as doomed long after the incident —
        with the gate then shedding the very traffic that could refresh
        the estimate (a permanent full-shed latch). The bounded recent
        ring (last 64 scored frames) forgets the incident as fast as
        healthy frames flow again. One lock hold, ≤64×12 adds; the fast
        path calls this throttled (~10 Hz), never per frame."""
        with self._lock:
            sums: dict[str, float] = {}
            counts: dict[str, int] = {}
            n = 0
            for stages, _wall, _ov, scored, _ex, _call in self.recent:
                if not scored:
                    continue
                n += 1
                for s, d in stages:
                    sums[s] = sums.get(s, 0.0) + d
                    counts[s] = counts.get(s, 0) + 1
            return n, {s: sums[s] / counts[s] for s in sums}

    def waterfall(self) -> dict[str, dict[str, float]]:
        """Per-stage p50/p95/p99/mean over the meter histograms, in
        traversal order (stages with no samples are omitted)."""
        out: dict[str, dict[str, float]] = {}
        with self._lock:
            totals = {s: (t[0], t[1]) for s, t in self._totals.items()}
        for s in ALL_STAGES:
            tot = totals.get(s)
            if not tot or not tot[1]:
                continue
            key = self._stage_keys[s]
            out[s] = {
                "p50_ms": round(meter.quantile(key, 0.50), 4),
                "p95_ms": round(meter.quantile(key, 0.95), 4),
                "p99_ms": round(meter.quantile(key, 0.99), 4),
                "mean_ms": round(tot[0] / tot[1], 4),
                "count": tot[1],
            }
        return out

    def burn(self) -> dict[str, Any]:
        """The deadline-burn table: which stage consumed what fraction
        of the admission budget (mean stage wall ÷ deadline), plus the
        expiry-blame tally. Fractions are per-frame means, so a stage
        holding steady at 0.6 of budget is the tuning target even while
        nothing expires yet."""
        with self._lock:
            totals = {s: (t[0], t[1]) for s, t in self._totals.items()}
            expired = dict(self._expired)
            deadline = self.deadline_ms
        by_stage = {}
        for s in ALL_STAGES:
            tot = totals.get(s)
            if not tot or not tot[1]:
                continue
            mean = tot[0] / tot[1]
            row = {"mean_ms": round(mean, 4)}
            if deadline:
                row["frac_of_budget"] = round(mean / deadline, 4)
            by_stage[s] = row
        out = {"deadline_ms": deadline, "stages": by_stage,
               "expired_spans_by_blame": expired}
        device = self.device_burn()
        if device is not None:
            # sampled sub-stage decomposition nested under the fused
            # stamp — present only when attribution sampled a frame
            out["device"] = device
        return out

    def snapshot(self) -> dict[str, Any]:
        with self._lock:
            recent = list(self.recent)[-8:]
            frames, scored = self.frames, self.scored_frames
            overlap = self.overlap_ms_total
        return {
            "frames": frames, "scored_frames": scored,
            "overlap_ms_total": round(overlap, 3),
            "waterfall": self.waterfall(), "burn": self.burn(),
            "recent": [
                {"stages": [{"stage": s, "ms": round(d, 4)}
                            for s, d in stages],
                 "wall_ms": round(wall, 4),
                 "overlap_ms": round(ov, 4), "scored": sc, "call": call}
                for stages, wall, ov, sc, _ex, call in recent],
            "worst_frames": self.worst_frames(),
        }


class SloTracker:
    """Multi-window burn-rate evaluation of one pipeline's declarative
    SLO. Per-frame samples (timestamp, latency-violated, scored) live in
    a time-pruned deque; ``status()`` computes the fast/slow-window
    burns fresh on every call, so alternating pollers (healthcheck,
    zpages, dashboard, tests with an injected clock) always agree."""

    def __init__(self, pipeline: str, cfg: dict[str, Any],
                 clock: Callable[[], float] = time.monotonic):
        self.pipeline = pipeline
        self.latency_p99_ms = (float(cfg["latency_p99_ms"])
                               if cfg.get("latency_p99_ms") else None)
        self.scored_fraction = (float(cfg["scored_fraction"])
                                if cfg.get("scored_fraction") else None)
        self.fast_window_s = float(cfg.get("fast_window_s",
                                           DEFAULT_FAST_WINDOW_S))
        self.slow_window_s = float(cfg.get("slow_window_s",
                                           DEFAULT_SLOW_WINDOW_S))
        self.fast_burn_threshold = float(cfg.get("fast_burn_threshold",
                                                 DEFAULT_FAST_BURN))
        self.slow_burn_threshold = float(cfg.get("slow_burn_threshold",
                                                 DEFAULT_SLOW_BURN))
        self._clock = clock
        self._lock = threading.Lock()
        # (t, n_spans, latency_violated, unscored)
        self._samples: deque[tuple[float, int, bool, bool]] = deque()

    def observe(self, wall_ms: float, scored: bool, n_spans: int) -> None:
        now = self._clock()
        violated = (self.latency_p99_ms is not None
                    and wall_ms > self.latency_p99_ms)
        with self._lock:
            self._samples.append((now, n_spans, violated, not scored))
            horizon = now - self.slow_window_s
            while self._samples and self._samples[0][0] < horizon:
                self._samples.popleft()

    def _render(self, window_s: float,
                counts: tuple[int, int, int]) -> dict[str, Any]:
        total, lat_bad, unscored = counts
        burns = {}
        if self.latency_p99_ms is not None and total:
            burns["latency_p99_ms"] = (lat_bad / total) / 0.01
        if self.scored_fraction is not None and total:
            budget = max(1.0 - self.scored_fraction, 1e-9)
            burns["scored_fraction"] = (unscored / total) / budget
        worst = max(burns, key=burns.get) if burns else None
        return {"window_s": window_s, "spans": total,
                "latency_violations": lat_bad, "unscored": unscored,
                "burn": round(max(burns.values()), 4) if burns else 0.0,
                "burn_by_objective": {k: round(v, 4)
                                      for k, v in burns.items()},
                "worst_objective": worst}

    def status(self) -> dict[str, Any]:
        now = self._clock()
        fast_cut = now - self.fast_window_s
        with self._lock:
            horizon = now - self.slow_window_s
            while self._samples and self._samples[0][0] < horizon:
                self._samples.popleft()
            # ONE pass over the (already slow-window-pruned) deque: the
            # fast window is a subset of the slow one, and every poller
            # (healthcheck, zpages, /api/slo, dashboard) holds the same
            # lock the forwarder's observe() needs — two full scans per
            # poll would stall the fast path exactly under load
            f = [0, 0, 0]
            s = [0, 0, 0]
            for t, n, violated, not_scored in self._samples:
                s[0] += n
                if violated:
                    s[1] += n
                if not_scored:
                    s[2] += n
                if t >= fast_cut:
                    f[0] += n
                    if violated:
                        f[1] += n
                    if not_scored:
                        f[2] += n
        fast = self._render(self.fast_window_s, tuple(f))
        slow = self._render(self.slow_window_s, tuple(s))
        burning = (fast["burn"] >= self.fast_burn_threshold
                   and slow["burn"] >= self.slow_burn_threshold)
        objective = fast["worst_objective"] or slow["worst_objective"]
        return {
            "pipeline": self.pipeline,
            "objectives": {
                k: v for k, v in (
                    ("latency_p99_ms", self.latency_p99_ms),
                    ("scored_fraction", self.scored_fraction))
                if v is not None},
            "fast": fast, "slow": slow,
            "fast_burn_threshold": self.fast_burn_threshold,
            "slow_burn_threshold": self.slow_burn_threshold,
            "burning": burning,
            "worst_objective": objective,
        }


class LatencyLedger:
    """Process-global latency-attribution registry (the flow_ledger /
    meter / tracer sibling)."""

    def __init__(self) -> None:
        self.enabled = os.environ.get("ODIGOS_LATENCY", "1") != "0"
        self._lock = threading.Lock()
        self._recorders: dict[str, _Recorder] = {}
        self._slos: dict[str, SloTracker] = {}
        self._expired_keys: dict[tuple[str, str], str] = {}

    # -------------------------------------------------------- recorders

    def recorder(self, pipeline: str) -> _Recorder:
        with self._lock:
            rec = self._recorders.get(pipeline)
            if rec is None:
                rec = self._recorders[pipeline] = _Recorder(pipeline)
            return rec

    def set_deadline(self, pipeline: str, deadline_ms: float) -> None:
        self.recorder(pipeline).deadline_ms = float(deadline_ms)

    def configure_slo(self, pipeline: str, cfg: dict[str, Any],
                      clock: Callable[[], float] = time.monotonic
                      ) -> SloTracker:
        """Get-or-create the pipeline's SLO tracker. Stable across hot
        reloads (an identical config re-binds the same tracker, so burn
        history survives the swap — the flow-edge discipline); ANY
        changed setting re-creates it — windows and thresholds redefine
        the burn math, so silently keeping the old ones would make a
        reload mid-incident a no-op."""
        candidate = SloTracker(pipeline, cfg, clock)
        with self._lock:
            tracker = self._slos.get(pipeline)
            if tracker is not None and (
                    tracker.latency_p99_ms, tracker.scored_fraction,
                    tracker.fast_window_s, tracker.slow_window_s,
                    tracker.fast_burn_threshold,
                    tracker.slow_burn_threshold) == (
                    candidate.latency_p99_ms, candidate.scored_fraction,
                    candidate.fast_window_s, candidate.slow_window_s,
                    candidate.fast_burn_threshold,
                    candidate.slow_burn_threshold):
                return tracker
            self._slos[pipeline] = candidate
            return candidate

    def remove_slo(self, pipeline: str) -> None:
        """Drop the pipeline's tracker. Called by graph build when a
        (re)loaded config carries no ``slo:`` stanza for the pipeline —
        without this, deleting the stanza mid-incident would leave the
        old objectives evaluating (and paging) forever."""
        with self._lock:
            self._slos.pop(pipeline, None)

    # ------------------------------------------------------- hot path

    def observe(self, pipeline: str, clock, scored: bool,
                n_spans: int) -> None:
        """One frame retired by the fast path: aggregate its waterfall
        and feed the pipeline's SLO tracker (if one is configured)."""
        if not self.enabled or clock is NULL_CLOCK:
            return
        self.recorder(pipeline).observe(clock, scored)
        tracker = self._slos.get(pipeline)
        if tracker is not None:
            tracker.observe(clock.wall_ms(), scored, n_spans)

    def record_expiry(self, pipeline: str, blame,
                      n_spans: int, clock=None) -> None:
        """An expired admission deadline, blamed on the stage that
        consumed the budget (the burn dimension on the drop taxonomy).
        ``blame`` is a :class:`Stage` for realized expiries, or
        :data:`PREDICTED_BLAME` for frames the predictive gate shed
        before any budget was spent (ISSUE 12). ``clock`` (when the
        expiring frame's is at hand) lets the recorder retain the
        worst expired frame's self-trace id per blame dimension."""
        if not self.enabled:
            return
        bval = blame.value if isinstance(blame, Stage) else str(blame)
        with self._lock:
            key = self._expired_keys.get((pipeline, bval))
            if key is None:
                key = self._expired_keys[(pipeline, bval)] = \
                    labeled_key(EXPIRED_METRIC, pipeline=pipeline,
                                blame=bval)
        meter.add(key, n_spans)
        self.recorder(pipeline).record_expiry(bval, n_spans,
                                              clock=clock)

    def worst_frames(self) -> list[dict[str, Any]]:
        """Every pipeline's worst-frame trace exemplars, slowest first
        (the flight recorder joins these into incident bundles)."""
        with self._lock:
            recs = list(self._recorders.values())
        out: list[dict[str, Any]] = []
        for r in recs:
            out.extend(r.worst_frames())
        out.sort(key=lambda f: f["wall_ms"], reverse=True)
        return out

    # -------------------------------------------------------- surfaces

    def waterfall(self) -> dict[str, dict[str, dict[str, float]]]:
        with self._lock:
            recs = list(self._recorders.values())
        return {r.pipeline: r.waterfall() for r in recs}

    def burn(self) -> dict[str, dict[str, Any]]:
        with self._lock:
            recs = list(self._recorders.values())
        return {r.pipeline: r.burn() for r in recs}

    def slo_status(self) -> dict[str, dict[str, Any]]:
        with self._lock:
            trackers = list(self._slos.values())
        return {t.pipeline: t.status() for t in trackers}

    def snapshot(self) -> dict[str, Any]:
        """JSON-able dump (``/debug/latencyz``, diagnose ``latency.json``)."""
        with self._lock:
            recs = list(self._recorders.values())
        return {
            "enabled": self.enabled,
            "stages": list(ALL_STAGES),
            "pipelines": {r.pipeline: r.snapshot() for r in recs},
            "slo": self.slo_status(),
        }

    def reset(self) -> None:
        """Test isolation: forget every recorder/tracker (live fast
        paths lazily re-create theirs — the flow_ledger.reset contract)."""
        with self._lock:
            self._recorders.clear()
            self._slos.clear()
            self._expired_keys.clear()


latency_ledger = LatencyLedger()
