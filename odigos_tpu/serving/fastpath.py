"""Ingest fast path: wire frame → featurized, device-ready arrays with
no per-span Python and no intermediate re-materialization.

The componentwise route re-touches every span several times between the
socket and the device: the memory limiter estimates bytes, the batch
processor buffers and re-concatenates (string tables re-interned
span-by-span), and the engine re-derives features for each merged batch.
The consequence is a tail set by the host and not by the device: every
frame waits behind work that was already done once. This module is the
shortcut:

* the receiver hands each zero-copy ``decode_frame`` batch straight to
  :class:`IngestFastPath`, which reserves window capacity and returns —
  wire intake never pays featurize or scoring per frame;
* a pool of **submit lanes** featurizes each frame ONCE (hash tables
  memoized per interned string pool, attr slots memoized per store) and
  submits to the scoring engine with an **admission deadline**;
* the engine coalesces those pre-featurized requests column-only
  (``_ColumnBatch`` — no merged SpanBatch, no re-intern, no attr-store
  merge) and sizes each device call adaptively from the observed step
  cost so harvest lands inside the deadline (``engine._adaptive_cap``);
* retirement is **completion-driven and multi-lane** (ISSUE 9): the
  engine fires a done-callback the instant a request's scores land,
  the frame is pushed to a ready queue, and a small pool of retirement
  lanes (``fast_path: {lanes: N, ordered: bool}``) overlaps tag and
  downstream forward of INDEPENDENT frames — the old single forwarder's
  wait→tag→forward serialization put a 172 ms mean `wait` stage in
  front of a 0.04 ms device. ``ordered: true`` routes forwards through
  a non-blocking ordered gate (out-of-turn frames park, lanes stay
  free) so downstream sees exactly the single-forwarder FIFO byte
  stream; unordered lanes forward the moment they finish tagging;
* **deadline expiry runs on its own earliest-deadline timer**, not the
  retire loop: an expired frame passes through unscored (and gets its
  blame stamp) even while every lane is busy, and late scores still
  land in online state — the tpuanomaly timeout contract;
* overload is bounded twice: the engine's own queue (engine-side
  ``queue_full`` accounting) and this route's pending-span window —
  saturation raises :class:`FastPathSaturated`, which the wire receiver
  answers with REJECTED (clients back off and retry), named in the flow
  ledger as ``queue_full`` so no shed span is ever silent. Watermarks
  published here and by the engine feed the receiver's pre-decode
  admission gate (wire/server.py) so a storm is shed before decode.

Steady-state zero-allocation + predictive shed (ISSUE 12):

* each submit lane featurizes into its own :class:`BufferPool` lease
  (features/bufferpool.py) — warmed traffic allocates nothing per
  frame; the lease is refcounted between the lane and the engine
  (released via ``on_features_consumed`` the instant the pack/score
  call copied the tensors out), so buffers recycle while the scores
  are still in flight;
* admission consults the PR 8 burn table: an arriving frame is priced
  (oldest in-flight frame's age + observed stage means through
  harvest) and one predicted to expire is REJECTED before featurize
  spends host time on it — named ``queue_full`` with the
  ``blame=predicted`` dimension, so
  predictive sheds count beside realized expiries and conservation
  stays exact. The same prediction publishes as the
  ``predicted_burn_ms`` watermark for the pre-decode admission gate.

Conservation stays exact under concurrent retirement: spans are
reserved at intake and released exactly once — in the forwarding
lane's ``finally``, or as a named ``shutdown_drain`` shed when a
timed-out drain leaves frames behind at shutdown (``flow_pending()`` +
the ``pending_spans``/``pending_ms`` watermarks all read the same
counter) — and the stage clock still tiles each frame's wall — WAIT is
now the completion→lane-pickup gap.

Built by ``pipeline/graph.build_graph`` when a pipeline sets
``fast_path`` — it reuses the pipeline's tpuanomaly engine + threshold,
so fast-path scores are bit-identical to the componentwise path at equal
request grouping (tests/test_ingest_fastpath.py pins this).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Optional

import numpy as np

# deliberately no components.api import: the tpuanomaly processor imports
# this module for the shared tagging helper, so depending on the
# components package here would be a cycle whichever package loads first
from ..features.bufferpool import BufferPool, lease_scope, pools_enabled
from ..features.featurizer import featurize
from ..hooks.tracecontext import _active
from ..pdata.spans import SpanBatch
from ..selftelemetry.flow import FlowContext
from ..selftelemetry.latency import (
    PREDICTED_BLAME, RECENT_WINDOW, Stage, annotate, claim_clock,
    latency_ledger, name_thread)
from ..utils.telemetry import labeled_key, meter
from .engine import PASSTHROUGH_METRIC, ScoringEngine
from .fused import FALLBACK_REASONS, extract_columns, fused_enabled
from .lanes import SHUTDOWN_BACKSTOP_S, OrderedGate, RetirementLanes

SCORE_ATTR = "odigos.anomaly.score"
FLAG_ATTR = "odigos.anomaly"
FLAGGED_METRIC = "odigos_anomaly_flagged_spans_total"

SPANS_METRIC = "odigos_fastpath_spans_total"
SATURATED_METRIC = "odigos_fastpath_saturated_total"
FORWARD_ERRORS_METRIC = "odigos_fastpath_forward_errors_total"
SUBMIT_ERRORS_METRIC = "odigos_fastpath_submit_errors_total"
PREDICTED_SHED_METRIC = "odigos_fastpath_predicted_shed_total"
# fused route (ISSUE 19): frames scored through the device-side
# featurize→pack→score call, and frames the route was armed for but
# that fell back to the host path (labeled with the closed reason set
# serving/fused.py:FALLBACK_REASONS)
FUSED_FRAMES_METRIC = "odigos_fastpath_fused_frames_total"
FUSED_FALLBACK_METRIC = "odigos_fastpath_fused_fallback_total"

DEFAULT_LANES = 4

# predictive shed (ISSUE 12): the SERVICE stages whose observed means
# price an arriving frame's marginal cost — featurize through the
# scores landing (expiry is beaten the instant the engine completes
# the request, so wait/tag/forward are outside the horizon). The WAIT
# stages (submit-lane pickup, engine queue) are deliberately absent:
# the head-age load term already carries the queueing the pipeline is
# experiencing, and adding the wait means on top double-counts it —
# measured as shedding deliverable traffic well below the deadline
PREDICT_STAGES = (Stage.FEATURIZE.value, Stage.ENQUEUE.value,
                  Stage.PACK.value, Stage.FUSED.value,
                  Stage.DEVICE.value, Stage.HARVEST.value)
# stage-cost recompute throttle: the burn table moves at EWMA speed,
# the admission decision happens per frame — pricing reads a cached sum
PREDICT_REFRESH_NS = 100_000_000

# flow-ledger watermark identity prefix: each instance reports as
# "fastpath/<pipeline>" — two fast-path pipelines must never clobber
# each other's pending_spans reading (last-writer-wins would let a
# quiet pipeline mask a saturated one at the admission gate)
WATERMARK_PREFIX = "fastpath"


def tag_anomalies(batch: SpanBatch, scores: np.ndarray,
                  threshold: float) -> SpanBatch:
    """Attribute-tag spans scoring at or above ``threshold`` — the one
    tagging implementation shared by the tpuanomaly processor and the
    fast path (bit-identical output is the parity contract)."""
    mask = scores >= threshold
    n_flagged = int(mask.sum())
    if n_flagged == 0:
        return batch
    meter.add(FLAGGED_METRIC, n_flagged)
    return batch.with_span_attrs({
        SCORE_ATTR: np.round(scores[mask], 4).tolist(),
        FLAG_ATTR: [True] * n_flagged,
    }, mask)


class FastPathSaturated(RuntimeError):
    """Raised to the receiver when the pending window is full: the wire
    answer is REJECTED, the client backs off, the ledger names the shed."""


class _Frame:
    """One wire frame in flight through the fast path. The stage clock
    is handed off thread to thread with the frame (receiver → submit
    lane → retirement lane); each handoff is sequenced through the
    fast-path lock, so the clock is never touched concurrently."""

    __slots__ = ("batch", "clock", "seq", "t_in_ns", "req", "deadline_ns",
                 "completed", "ready", "expired", "done",
                 "retiring", "tagged", "scored", "out")

    def __init__(self, batch: SpanBatch, clock: Any, seq: int,
                 t_in_ns: int):
        self.batch = batch
        self.clock = clock
        self.seq = seq
        self.t_in_ns = t_in_ns
        self.req: Any = None
        self.deadline_ns = 0
        self.completed = False   # engine done-callback fired
        self.ready = False       # queued for a retirement lane
        self.expired = False     # deadline timer beat the scores
        self.done = False        # retired (accounting released)
        self.retiring = False    # a lane is actively holding the frame
        self.tagged = False      # merge/tag leg ran (out is final)
        self.scored = False      # scores landed before the deadline
        self.out: Any = None     # tagged batch awaiting forward


class IngestFastPath:
    """Config (the pipeline's ``fast_path`` mapping; ``true`` = defaults):
    deadline_ms:       admission deadline per frame (default: the
                       scoring processor's timeout_ms)
    max_pending_spans: pending-window bound before REJECTED (default 128k)
    lanes:             retirement lanes overlapping tag/forward of
                       independent frames (default 4)
    submit_lanes:      submit-side pool size (featurize + engine
                       submit; default = lanes). The pools bound
                       different work — retirement drains the
                       downstream forward leg, submit the featurize
                       leg — so a host-contended box may want them
                       sized apart
    ordered:           forward downstream in intake order (single-
                       forwarder FIFO semantics) instead of
                       as-completed (default false)
    drain_timeout_s:   shutdown's bound on the lossless drain (default
                       30); past it, unretired frames are shed as
                       named ``shutdown_drain`` drops instead of
                       blocking shutdown on a wedged downstream
    predictive:        shed frames the burn table predicts will expire
                       BEFORE featurize spends host time on them
                       (default true; ISSUE 12). The prediction is the
                       age of the oldest in-flight frame (the latency
                       the route is carrying now) plus the observed
                       per-stage means through harvest; a frame priced
                       past the deadline is REJECTED at intake with
                       blame=predicted — the client backs off instead
                       of the frame expiring inside the pipeline
    predictive_margin: multiple of the deadline the prediction must
                       exceed to shed (default 1.0; < 1 sheds earlier)
    predictive_min_frames: scored frames required before the means are
                       trusted (default 32 — a cold route never
                       predicts)
    pooled:            per-lane buffer pools for the featurize tensors
                       (default true; the steady state then allocates
                       nothing per frame). Also globally killable via
                       ODIGOS_POOL=0
    fused:             score raw span columns device-side (ISSUE 19):
                       the submit lane skips host featurize entirely
                       and the engine runs featurize→pack→score as ONE
                       jitted call. Opt-in (default false); per-frame
                       kill switch ODIGOS_FUSED=0; any frame the
                       kernel doesn't cover silently takes the host
                       route with the fallback reason counted

    Duck-types the Component lifecycle (name/start/shutdown/health) so
    the graph can manage it, without importing components.api (see the
    module-cycle note above).
    """

    # incremental hot reload (ISSUE 14): the pacing/admission knobs
    # retune live — in-flight frames keep the deadline they were
    # admitted under, new frames see the new budget. Structural knobs
    # (lanes/submit_lanes/ordered/pooled/name) re-thread the pools and
    # the ordered-gate epoch and fall back to a full rebuild
    # (pipeline/configdiff.py classifies from this table).
    RECONFIGURABLE_KEYS = frozenset({
        "deadline_ms", "max_pending_spans", "drain_timeout_s",
        "predictive", "predictive_margin", "predictive_min_frames",
        "fused"})

    def _apply_tuning(self, config: dict[str, Any]) -> None:
        """The reconfigurable-knob parse, shared by ``__init__`` and
        ``reconfigure`` — ONE set of defaults, so an omitted key on
        reload returns to exactly what a fresh build would use."""
        self.deadline_ms = float(config.get("deadline_ms", 25.0))
        self._deadline_ns = int(self.deadline_ms * 1e6)
        self.max_pending_spans = int(config.get("max_pending_spans",
                                                128 * 1024))
        self.drain_timeout_s = float(config.get("drain_timeout_s", 30.0))
        self.predictive = bool(config.get("predictive", True))
        self.predictive_margin = float(config.get("predictive_margin",
                                                  1.0))
        # clamped to the recorder's recent-ring capacity: the means are
        # windowed over the last RECENT_WINDOW scored frames, so a
        # larger threshold could never be met and would silently
        # disable the gate a config believes is on
        self.predictive_min_frames = min(
            int(config.get("predictive_min_frames", 32)),
            RECENT_WINDOW)
        # fused route (ISSUE 19): reconfigurable so flipping it is a
        # millisecond patch, not a teardown — the submit lanes read it
        # per frame, so in-flight frames keep the route they entered on
        self.fused = bool(config.get("fused", False))
        # re-price promptly: a new deadline/margin changes what the
        # cached burn sum is compared against
        self._stage_cost_next_ns = 0

    def reconfigure(self, config: dict[str, Any]) -> None:
        """Live retune of the declared-reconfigurable knobs. The
        caller (Graph.patch) has already applied the scorer-derived
        deadline default."""
        with self._lock:
            self.config = dict(config)
            self._apply_tuning(config)
        latency_ledger.set_deadline(self.pipeline, self.deadline_ms)

    def __init__(self, pipeline: str, engine: ScoringEngine,
                 threshold: float, downstream: Any,
                 config: dict[str, Any]):
        self.name = str(config.get("name", "fastpath"))
        self.config = config
        self._started = False
        self.pipeline = pipeline
        self.engine = engine
        self.threshold = float(threshold)
        self.downstream = downstream
        self._apply_tuning(config)
        # structural knobs (NOT reconfigurable: they re-thread the
        # pools and the ordered-gate epoch — a change rebuilds)
        self.lanes = max(1, int(config.get("lanes", DEFAULT_LANES)))
        self.submit_lanes = max(1, int(config.get("submit_lanes",
                                                  self.lanes)))
        self.ordered = bool(config.get("ordered", False))
        self.pooled = bool(config.get("pooled", True))
        self._feat_cfg = engine.cfg.featurizer
        self._needs_features = getattr(engine.backend, "needs_features",
                                       True)
        # per-lane buffer pools (ISSUE 12): each submit lane featurizes
        # into its own pool's recycled buffers — checkouts uncontended,
        # returns (frame release + engine done, other threads) locked
        self._pools: Optional[list[BufferPool]] = None
        if self.pooled and self._needs_features:
            self._pools = [
                BufferPool(f"{WATERMARK_PREFIX}/{pipeline}/lane{i}")
                for i in range(self.submit_lanes)]
        # stage-waterfall aggregation rides per pipeline; the admission
        # deadline is this route's burn budget (ISSUE 8)
        latency_ledger.set_deadline(pipeline, self.deadline_ms)
        # predictive-shed pricing cache: Σ(observed stage means through
        # harvest), recomputed at most every PREDICT_REFRESH_NS from the
        # recorder's burn totals; None until predictive_min_frames
        # scored frames exist (or when ODIGOS_LATENCY=0 starves the
        # means — no data, no prediction)
        self._recorder = latency_ledger.recorder(pipeline)
        self._stage_cost_ms: Optional[float] = None
        self._stage_cost_next_ns = 0
        self._lock = threading.Lock()
        # receiver → submit-lane handoff (featurize moves OFF the wire
        # intake thread: ISSUE 9)
        self._submit_have = threading.Condition(self._lock)
        # wakes the expiry timer when the earliest deadline changes
        self._timer_wake = threading.Condition(self._lock)
        # wakes drain() when the last live frame retires
        self._drained = threading.Condition(self._lock)
        self._submit_q: deque[_Frame] = deque()
        # submitted-not-ready frames kept in DEADLINE order (tail
        # insertion — see _submit_run): the head is always the
        # earliest deadline, so the expiry timer inspects one frame
        self._awaiting: deque[_Frame] = deque()
        # every unretired frame in intake order: pending_ms head age,
        # drain, and the retire-time pruning all read this
        self._live: deque[_Frame] = deque()
        self._pending_spans = 0
        self._seq = 0
        self._retire_lanes = RetirementLanes(pipeline, self.lanes,
                                             self._retire_frame)
        self._gate = OrderedGate() if self.ordered else None
        self._submit_threads: list[threading.Thread] = []
        self._timer_thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._wm_component = f"{WATERMARK_PREFIX}/{pipeline}"
        self._spans_key = labeled_key(SPANS_METRIC, pipeline=pipeline)
        self._saturated_key = labeled_key(SATURATED_METRIC,
                                          pipeline=pipeline)
        self._errors_key = labeled_key(FORWARD_ERRORS_METRIC,
                                       pipeline=pipeline)
        self._submit_errors_key = labeled_key(SUBMIT_ERRORS_METRIC,
                                              pipeline=pipeline)
        self._predicted_key = labeled_key(PREDICTED_SHED_METRIC,
                                          pipeline=pipeline)
        # fused route (ISSUE 19): capability is a property of the
        # PRIMARY backend (failover's zscore fallback converts columns
        # host-side in the engine's pack stage); keys precomputed —
        # the closed reason set makes the fallback counter's label
        # space enumerable at build time
        self._fused_capable = bool(getattr(engine.backend,
                                           "supports_fused", False))
        self._fused_frames_key = labeled_key(FUSED_FRAMES_METRIC,
                                             pipeline=pipeline)
        self._fused_fallback_keys = {
            r: labeled_key(FUSED_FALLBACK_METRIC, pipeline=pipeline,
                           reason=r)
            for r in FALLBACK_REASONS}

    # ------------------------------------------------------------ intake
    def consume(self, batch: SpanBatch) -> None:
        """Receiver-thread half: reserve window capacity, adopt the
        frame's stage clock, hand off to the submit lane. Never blocks
        on featurize or scoring — wire intake stays wire-speed."""
        n = len(batch)
        if n == 0:
            return  # the componentwise path drops empties in batch concat
        with self._lock:
            if self._pending_spans + n > self.max_pending_spans:
                # discard the receiver-published stage clock explicitly:
                # a REJECTED frame's timeline dies here — left on the
                # contextvar it could be claimed by (and pollute) a
                # later frame on this thread (ISSUE 9 satellite bugfix)
                claim_clock()
                meter.add(self._saturated_key)
                # refresh the watermarks on the REJECTED path too: when
                # the submit lanes wedge, consume() only ever takes
                # this branch, and a backlog_ms gauge frozen below the
                # gate limit would keep the pre-decode admission gate
                # open through the exact overload it exists to shed
                self._refresh_watermarks_locked(time.monotonic_ns())
                err = FastPathSaturated(
                    f"{self.name}: {self._pending_spans} spans pending "
                    f"(bound {self.max_pending_spans}); receiver should "
                    f"answer REJECTED")
                # named shed, marked so the entry edge does not also
                # count the unwind as failed (memory_limiter discipline)
                FlowContext.drop(n, "queue_full", component=self, exc=err)
                raise err
            if self.predictive and self._stage_cost_ms is not None \
                    and self._live:
                # the in-flight guard (with the windowed means in
                # stage_means) breaks the starvation latch: an IDLE
                # route always admits — a shed-everything posture
                # would otherwise never score another frame, so the
                # estimate that caused it could never recover
                # PREDICTIVE shed (ISSUE 12): price this frame's burn
                # as the age of the OLDEST UNRETIRED frame (the latency
                # the pipeline is carrying right now — it includes the
                # engine-side queue that backlog_ms cannot see, and it
                # saturates at ~deadline exactly when frames start
                # expiring) plus the observed per-stage means through
                # harvest. The means alone are survivorship-biased
                # (only scored frames feed the waterfall), so the head
                # age is the load term and the means are the marginal
                # cost. A frame predicted to expire is cheapest to shed
                # NOW — before featurize spends host time on data the
                # deadline timer would pass through unscored anyway.
                # Unlike PR 9's admission gate (where thresholding raw
                # head age shed while merely WORKING), the comparison
                # here is against the deadline, which by definition
                # includes the frame's own processing wall. The shed is
                # named (queue_full) and blamed (predicted), so
                # conservation stays exact and the loss is countable
                # beside realized expiries.
                now_ns = time.monotonic_ns()
                head_ms = ((now_ns - self._live[0].t_in_ns) / 1e6
                           if self._live else 0.0)
                predicted_ms = head_ms + self._stage_cost_ms
                if predicted_ms > self.deadline_ms \
                        * self.predictive_margin:
                    # a shed frame's timeline dies here — but its
                    # clock (bound to the active self-trace) still
                    # names the worst predicted-shed frame exemplar
                    shed_clock = claim_clock()
                    shed_clock.bind_trace(_active.get())
                    meter.add(self._predicted_key)
                    self._refresh_watermarks_locked(now_ns)
                    err = FastPathSaturated(
                        f"{self.name}: predicted deadline burn "
                        f"{predicted_ms:.1f} ms exceeds the "
                        f"{self.deadline_ms:g} ms budget "
                        f"(oldest in-flight {head_ms:.1f} ms + "
                        f"expected stage cost "
                        f"{self._stage_cost_ms:.1f} ms); receiver "
                        f"should answer REJECTED")
                    FlowContext.drop(n, "queue_full", component=self,
                                     exc=err, blame=PREDICTED_BLAME)
                    latency_ledger.record_expiry(
                        self.pipeline, PREDICTED_BLAME, n,
                        clock=shed_clock)
                    raise err
            # RESERVE inside the check's lock hold: concurrent receiver
            # threads must not all pass the bound at once — the pending
            # window IS the latency budget, so an N-thread overshoot is
            # p99 inflation. Released exactly once, in the retiring
            # lane's finally.
            self._pending_spans += n
            # latency attribution (ISSUE 8): adopt the receiver-started
            # stage clock (admission/decode already stamped) or start
            # one for a direct feed; the active self-trace becomes the
            # exemplar every histogram sample of this frame links
            clock = claim_clock()
            clock.bind_trace(_active.get())
            frame = _Frame(batch, clock, self._seq, time.monotonic_ns())
            self._seq += 1
            self._live.append(frame)
            self._submit_q.append(frame)
            self._refresh_watermarks_locked(frame.t_in_ns)
            self._submit_have.notify()
        meter.add(self._spans_key, n)

    def _refresh_watermarks_locked(self, now_ns: int) -> None:
        """Publish all three admission gauges from current state —
        called at EVERY ``_live``/``_submit_q`` mutation site (accept,
        reject, submit pickup, release) so no path can leave the
        pre-decode admission gate steering on a frozen reading.

        pending_ms — age of the OLDEST unretired frame — is the
        throughput-invariant latency signal: a span-denominated bound
        means N ms of queue on a slow box but over-sheds a fast one,
        while head age IS the latency budget directly. backlog_ms —
        age of the oldest frame no submit lane has STARTED — is the
        admission gate's signal under multi-lane retirement (ISSUE 9):
        head age necessarily includes the frame's own concurrent
        processing wall (featurize+engine+retire), so a pending_ms
        limit near that wall sheds while the pipeline is merely
        WORKING, not backlogged — measured as a 2-3x throughput loss
        exactly when the box slows down. Backlog age is the queue the
        gate can actually drain by shedding. pending_spans remains the
        memory backstop. predicted_burn_ms (ISSUE 12) — oldest
        in-flight age plus the priced stage cost — lets the PRE-DECODE
        admission gate shed by prediction too: bound it at the
        deadline in the receiver's ``admission.watermarks`` and a
        frame that would expire is refused before decode spends a
        byte on it."""
        FlowContext.watermark(self._wm_component, "pending_spans",
                              self._pending_spans)
        pending_ms = ((now_ns - self._live[0].t_in_ns) / 1e6
                      if self._live else 0.0)
        FlowContext.watermark(self._wm_component, "pending_ms",
                              pending_ms)
        FlowContext.watermark(
            self._wm_component, "backlog_ms",
            (now_ns - self._submit_q[0].t_in_ns) / 1e6
            if self._submit_q else 0.0)
        if self.predictive:
            self._refresh_stage_cost(now_ns)
            FlowContext.watermark(
                self._wm_component, "predicted_burn_ms",
                pending_ms + (self._stage_cost_ms or 0.0))

    def _refresh_stage_cost(self, now_ns: int) -> None:
        """Re-price the expected per-frame stage cost from the burn
        table's means, at most every PREDICT_REFRESH_NS (the means move
        at EWMA speed; the admission decision reads a cached sum)."""
        if now_ns < self._stage_cost_next_ns:
            return
        self._stage_cost_next_ns = now_ns + PREDICT_REFRESH_NS
        frames, means = self._recorder.stage_means()
        if frames < self.predictive_min_frames:
            # not enough SCORED frames in the window — keep the last
            # known price rather than going dark: an unscored-heavy
            # overload (expiry storm) floods the ring with frames the
            # means skip, and dropping to None would switch the gate
            # off in exactly the regime it was built for. A never-
            # priced (cold) route stays None until real data exists.
            return
        self._stage_cost_ms = sum(
            means.get(s, 0.0) for s in PREDICT_STAGES)

    # ------------------------------------------------------- fused route
    def _fused_columns(self, frame: _Frame) -> Any:
        """The fused route's per-frame gate: the frame's SpanColumns
        view when the route is armed AND covers it, else None — with
        the fallback reason counted, so a mixed fused/fallback storm
        is fully attributable. The knob (``fused``) and the kill
        switch (``ODIGOS_FUSED``) are both read here, per frame: the
        operator's flip takes effect on the very next frame, and
        in-flight frames keep the route they entered on."""
        if not self.fused:
            return None  # route not armed: the host path is not a fallback
        if not fused_enabled():
            reason = "disabled"
        elif not self._fused_capable:
            reason = "backend"
        else:
            cols, reason = extract_columns(frame.batch, self._feat_cfg)
            if cols is not None:
                meter.add(self._fused_frames_key)
                return cols
        meter.add(self._fused_fallback_keys[reason])
        return None

    # ------------------------------------------------------- submit lane
    def _submit_run(self, stop: threading.Event, lane: int = 0) -> None:
        """Featurize + engine submit, off the receiver threads (ISSUE 9:
        featurize was the second-largest deadline burn and serial on
        wire intake — a rejected sender now gets its REJECTED at wire
        speed instead of behind a 20 ms featurize). A pool sized with
        the retirement pool: featurize of independent frames overlaps,
        matching the concurrency the receiver threads used to provide,
        without the intake thread paying any of it.

        ``stop`` is this epoch's own flag (like the lane pool, never
        ``self._stop``): a lane surviving a shutdown→start cycle must
        keep seeing its epoch's SET flag, not run on as an extra
        uncounted lane the operator never sized for."""
        name_thread(f"odigos-submit-{lane}")
        pool = self._pools[lane] if self._pools is not None else None
        while True:
            with self._lock:
                if stop.is_set():
                    # checked before popping, not only when idle: past
                    # a timed-out drain the remaining backlog belongs
                    # to shutdown's claim sweep (named shutdown_drain
                    # sheds), not to lanes racing it frame by frame
                    return
                while not self._submit_q:
                    if stop.is_set():
                        return
                    self._submit_have.wait(SHUTDOWN_BACKSTOP_S)
                frame = self._submit_q.popleft()
                # keep the gate's backlog reading CURRENT on pickup
                # (the watermark-producer discipline: a stale peak would
                # shed long after the backlog drained)
                self._refresh_watermarks_locked(time.monotonic_ns())
                if frame.done:
                    # a shutdown-claimed shell (timed-out drain nulled
                    # its payload without popping the queue): featurize
                    # on it would only pollute the submit-error metric
                    continue
            clock = frame.clock
            clock.stamp(Stage.SUBMIT)
            req = None
            # the admission deadline runs from frame ACCEPTANCE, not
            # from featurize completing: time queued for (and inside)
            # featurize burns budget, so a featurize-bound overload
            # surfaces as expiries with blame — anchoring post-
            # featurize would let frames sit unbounded in _submit_q
            # and still "meet" their deadline
            deadline = frame.t_in_ns + self._deadline_ns
            # fused route (ISSUE 19): when armed and the kernel covers
            # this frame, hand the engine the raw column views and skip
            # host featurize entirely — the frame's featurize/pack wall
            # collapses into the engine's single FUSED stage
            cols = self._fused_columns(frame)
            # featurize into this lane's buffer pool (ISSUE 12): the
            # lease holds the frame's feature tensors, refcounted TWICE
            # when an engine request exists — this lane releases its
            # own reference the moment submit resolves (nothing on the
            # retirement side reads features), and the ENGINE releases
            # the other via on_features_consumed the instant its pack/
            # score call copied them out. Buffers therefore recycle
            # while the scores are still in flight — the lifetime that
            # makes steady-state misses actually reach zero.
            lease = None
            if cols is None and pool is not None \
                    and self._needs_features and pools_enabled():
                lease = pool.lease()
            retained = False
            try:
                feats = None
                if cols is None:
                    with annotate("fastpath/featurize", clock,
                                  Stage.FEATURIZE):
                        if self._needs_features:
                            # lease_scope(None) is an explicit plain-
                            # numpy scope, so one call site covers
                            # pooled and not
                            with lease_scope(lease):
                                feats = featurize(frame.batch,
                                                  self._feat_cfg)
                    if lease is not None:
                        # the engine's reference, taken BEFORE submit:
                        # the worker can consume the request (and fire
                        # the hook) before submit even returns
                        lease.retain()
                        retained = True
                # req None = engine queue full / draining: the engine
                # already counted the shed request; the frame still
                # forwards unscored (lossless pass-through, exactly the
                # tpuanomaly contract). The on_done callback is the
                # completion queue — fired by the engine the instant
                # scores land, replacing the old done.wait() poll.
                with annotate("fastpath/enqueue", clock, Stage.ENQUEUE):
                    req = self.engine.submit(
                        frame.batch, feats, deadline_ns=deadline,
                        on_done=lambda r, f=frame: self._completed(f, r),
                        on_features_consumed=lease.release
                        if lease is not None else None,
                        columns=cols)
                    if req is None and lease is not None:
                        # no request was enqueued: the engine will
                        # never fire the features-consumed hook
                        lease.release()
                        retained = False
            except Exception:  # noqa: BLE001 — a frame must never kill the lane
                # featurize/submit failure: lossless unscored
                # pass-through (the frame was already accepted on the
                # wire; dropping it here would leak conservation)
                meter.add(self._submit_errors_key)
                req = None
                if retained:
                    # submit raised before enqueueing: the engine
                    # contract (hooks fire iff submit returned a
                    # request) says nobody else will release this
                    lease.release()
            finally:
                if lease is not None:
                    # the lane's own reference: featurize is done and
                    # the retirement side never touches features
                    lease.release()
            with self._lock:
                if frame.req is None:
                    # the early-completion callback may have attached
                    # the request already; never overwrite it (least of
                    # all with None from the exception path)
                    frame.req = req
                frame.deadline_ns = deadline
                if frame.req is None or frame.completed:
                    # no engine request to wait for, or the depth-2
                    # worker finished before registration: retire now
                    self._mark_ready_locked(frame, expired=False)
                else:
                    # insertion keeps _awaiting in true deadline order:
                    # registration happens post-featurize, so two
                    # submit lanes can invert neighbors by a whole
                    # featurize duration (a big frame beside a small
                    # one), and the head-only timer would fire the
                    # earlier deadline that much late. The backward
                    # scan costs the number of frames REGISTERED while
                    # this one featurized — a handful in steady state;
                    # only a pathological featurize outlier (seconds)
                    # makes it long, and then the scan is the least of
                    # the route's problems.
                    i = len(self._awaiting)
                    while i and (self._awaiting[i - 1].deadline_ns
                                 > frame.deadline_ns):
                        i -= 1
                    self._awaiting.insert(i, frame)
                    self._timer_wake.notify()

    # ------------------------------------------------- completion queue
    def _completed(self, frame: _Frame, req: Any) -> None:
        """Engine done-callback (worker thread): the frame is retirable
        the moment its request resolves — push it to the lanes unless
        the deadline timer already expired it."""
        with self._lock:
            frame.completed = True
            if frame.done:
                # already retired (expired + released): re-attaching
                # the request would re-pin its payload on the shell
                return
            if frame.req is None:
                # the worker can complete a request before the submit
                # lane re-acquires the lock to register it; the frame
                # readies from _submit_run's post-submit block instead
                frame.req = req
                return
            if not frame.ready:
                self._mark_ready_locked(frame, expired=False)

    # ---------------------------------------------------- expiry timer
    def _timer_run(self, stop: threading.Event) -> None:
        """Earliest-deadline expiry, OFF the retire loop (ISSUE 9): an
        expired frame passes through (and gets its blame stamp) even
        while every lane is busy. ``_awaiting`` is kept in deadline
        order by ``_submit_run``'s bounded insertion (registration is
        post-featurize, NOT deadline-monotone on its own), so only the
        head is ever inspected. ``stop`` is this epoch's own flag (see
        ``_submit_run``)."""
        while True:
            with self._lock:
                while self._awaiting and (self._awaiting[0].ready
                                          or self._awaiting[0].done):
                    self._awaiting.popleft()  # completed: nothing to time
                if not self._awaiting:
                    if stop.is_set():
                        return
                    self._timer_wake.wait(SHUTDOWN_BACKSTOP_S)
                    continue
                head = self._awaiting[0]
                delay_s = (head.deadline_ns - time.monotonic_ns()) / 1e9
                if delay_s > 0:
                    if stop.is_set():
                        # shutdown claims the stragglers itself; a
                        # timer waiting out a long deadline here would
                        # wedge the joining shutdown thread
                        return
                    # plain timed wait for the real deadline; submit
                    # lane / shutdown notify on state changes
                    self._timer_wake.wait(
                        min(delay_s, SHUTDOWN_BACKSTOP_S))
                    continue
                self._awaiting.popleft()
                self._mark_ready_locked(head, expired=True)
                # span count read INSIDE the lock hold: the instant it
                # drops, a lane can retire the frame and _release_frame
                # nulls frame.batch — len() after release would kill
                # the (unguarded) timer thread and no deadline would
                # ever expire again
                n_expired = len(head.batch)
            # outside the lock: metric add takes the meter's own lock
            meter.add(PASSTHROUGH_METRIC, n_expired)

    def _mark_ready_locked(self, frame: _Frame, expired: bool) -> None:
        if frame.ready or frame.done:
            # already queued/parked/retired — or claimed by shutdown
            # (which sets ready so a late engine callback or a straggler
            # submit lane cannot push into the stopped lane pool)
            return
        frame.ready = True
        frame.expired = expired
        self._retire_lanes.push(frame)

    # ------------------------------------------------- retirement lanes
    def _retire_frame(self, frame: _Frame, lane: int) -> bool:
        """One lane retiring one ready frame: merge the engine's stage
        boundaries, tag, and — gate permitting — forward. Downstream
        failures are accounted by the flow edges and must never kill a
        lane; the reservation is released exactly once, by whichever
        lane forwards the frame, in the finally. Returns False when the
        frame merely PARKED at the ordered gate (the lane pool must not
        count a park as a retirement — an ordered frame would otherwise
        count twice, once parking and once forwarding)."""
        frame.retiring = True
        clock = frame.clock
        req = frame.req
        # alias the gate AND stop flag for the frame's whole
        # retirement: a straggler daemon lane resuming after a
        # shutdown→start cycle must step the gate it offered into, not
        # the fresh epoch's — and must see the OLD epoch's (set) stop
        # flag, else it offers into the orphaned gate (flushed at
        # shutdown, never stepped again), parking the frame and its
        # reservation forever
        gate = self._gate
        stop = self._stop
        if not frame.tagged:
            with annotate("lane/tag", clock, Stage.TAG):
                try:
                    scores = None
                    if req is not None and not frame.expired:
                        scores = req.scores  # final: assigned before done
                    if scores is not None and req.stage_ns is not None:
                        # fold the engine call's queue/pack/device/
                        # harvest boundaries into this frame's timeline
                        # (same monotonic clock domain); WAIT then
                        # measures score-landing → lane-pickup — the
                        # completion-queue handoff, no longer the old
                        # forwarder's head-of-line wait
                        clock.merge_engine(req.stage_ns)
                    clock.stamp(Stage.WAIT)
                    frame.out = frame.batch if scores is None else \
                        tag_anomalies(frame.batch, scores, self.threshold)
                    # only after tag succeeds: a frame whose tagging
                    # raised never forwards, and observing it
                    # scored=True would keep the scored_fraction SLO
                    # green during exactly the failure it exists to
                    # burn on
                    frame.scored = scores is not None
                except Exception:  # noqa: BLE001 — a frame never kills a lane
                    # tag failure: the frame cannot forward, but it
                    # still passes the gate and releases its reservation
                    # below — wedging the ordered sequence on one bad
                    # frame would park every later frame forever
                    meter.add(self._errors_key)
                    frame.out = None
            frame.tagged = True
        offered = False
        if gate is not None and not stop.is_set():
            # ordered mode: tag overlapped above; forward strictly in
            # intake order (single-forwarder FIFO byte stream). An
            # out-of-turn frame PARKS — the lane is freed — rather
            # than blocking: N lanes waiting on a head that itself
            # needs a lane is a pool deadlock
            # retiring clears BEFORE the offer: the instant a frame
            # parks, another lane forwarding its predecessor can
            # advance() it back out and re-claim it — a clear written
            # AFTER the offer would clobber that lane's claim, and the
            # shutdown/start sweeps key off the flag
            frame.retiring = False
            if not gate.offer(frame.seq, frame):
                return False  # parked: no lane holds it now
            frame.retiring = True
            offered = True
        try:
            with annotate("lane/forward", clock, Stage.FORWARD):
                try:
                    if frame.out is not None:
                        self.downstream.consume(frame.out)
                except Exception:  # noqa: BLE001 — edge-accounted; keep serving
                    meter.add(self._errors_key)
        finally:
            try:
                # observed even when consume raises: a downstream
                # outage is exactly when the SLO tracker must keep
                # seeing frames (an unfed tracker reads burn 0.0
                # during the incident it exists to page on)
                latency_ledger.observe(self.pipeline, clock,
                                       scored=frame.scored,
                                       n_spans=len(frame.batch))
                if frame.expired:
                    # every expired deadline names a blamed stage: the
                    # device call that outran the budget when the
                    # request had been dispatched, the engine queue
                    # when it never left it (ISSUE 8 blame)
                    latency_ledger.record_expiry(
                        self.pipeline,
                        Stage.DEVICE if req is not None
                        and req.dispatched_ns else Stage.QUEUE,
                        len(frame.batch), clock=clock)
            finally:
                # the gate step and the reservation release run even
                # if a telemetry call above raises: skipping advance
                # parks every later ordered frame forever, skipping
                # the release is a permanent conservation leak
                if offered:
                    # hand the now-eligible parked frame (if its tag
                    # already finished) back to the pool
                    nxt = gate.advance()
                    if nxt is not None:
                        self._retire_lanes.push(nxt)
                self._release_frame(frame)
        return True

    def _release_frame(self, frame: _Frame) -> None:
        """The exactly-once reservation release (normal retirement AND
        shutdown shed): done flag, pending-window decrement, live-deque
        prune, watermark refresh, drain wakeup. Idempotent under the
        lock — every caller path is designed exactly-once, but a second
        release must be a no-op, never a double decrement (or a len()
        on the nulled payload)."""
        with self._lock:
            if frame.done:
                return
            frame.done = True
            self._pending_spans -= len(frame.batch)
            # drop the payload refs NOW, not when the frame leaves
            # _live: the prune below only pops the contiguous done
            # prefix, so a done frame can sit pinned behind a stalled
            # (not-yet-done) head indefinitely — and its reservation is
            # already released, so consume keeps admitting. Without
            # this, one wedged lane turns hours of traffic into
            # unbounded resident batches/scores the max_pending_spans
            # window no longer bounds.
            frame.batch = None
            frame.out = None
            frame.req = None
            while self._live and self._live[0].done:
                self._live.popleft()
            self._refresh_watermarks_locked(time.monotonic_ns())
            if not self._live:
                # wake drain() waiters the instant the window
                # empties — retire notifies, drain never polls
                self._drained.notify_all()

    # ------------------------------------------------------------ ledger
    def flow_pending(self) -> int:
        """Spans submitted but not yet forwarded — the conservation
        checker's in-flight term for this route."""
        with self._lock:
            return self._pending_spans

    def pool_stats(self) -> Optional[dict[str, Any]]:
        """Aggregated buffer-pool evidence (soak/bench records): total
        checkouts, misses (fresh allocations — the steady-state ≈0
        claim), and retained bytes across the submit-lane pools."""
        if self._pools is None:
            return None
        agg = {"pools": len(self._pools), "hits": 0, "misses": 0,
               "dropped": 0, "leases": 0, "outstanding_leases": 0,
               "bytes_held": 0, "free_buffers": 0}
        for p in self._pools:
            s = p.stats()
            for k in ("hits", "misses", "dropped", "leases",
                      "outstanding_leases", "bytes_held",
                      "free_buffers"):
                agg[k] += s[k]
        total = agg["hits"] + agg["misses"]
        agg["hit_rate"] = round(agg["hits"] / total, 4) if total else 0.0
        return agg

    # --------------------------------------------------------- lifecycle
    def healthy(self) -> bool:
        return True

    def health(self) -> tuple[str, str, str]:
        # the rollup attaches Degraded(QueueSaturation) itself from the
        # ledger's queue_full evidence; base condition mirrors Component
        return ("Healthy", "Running", "")

    def start(self) -> None:
        self._started = True
        if not any(t.is_alive() for t in self._submit_threads):
            self._stop = threading.Event()
            with self._lock:
                # fresh retirement epoch: a shutdown that abandoned
                # frames (or forwarded gate-bypassed after stop) leaves
                # the old gate's _next behind _seq — reusing either
                # would park every new ordered frame forever. Frames
                # accepted BEFORE start() (consume has no started
                # guard) renumber into the fresh epoch, else they'd
                # collide with new frames' seqs and the ordered gate —
                # keyed by seq — would park the duplicate past a slot
                # already advanced, never forwarding it. A stuck lane's
                # retiring frame keeps its alias to the OLD gate and
                # never offers into this one, so it stays unnumbered.
                pending = [f for f in self._live
                           if not (f.done or f.retiring)]
                for i, f in enumerate(pending):
                    f.seq = i
                self._seq = len(pending)
                # re-seed the submit queue from the same pending set: a
                # prior epoch's timed-out-drain shutdown claims frames
                # (done, payloads dropped) without popping _submit_q,
                # and a dead shell must not reach a fresh submit lane
                self._submit_q = deque(pending)
                if self.ordered:
                    self._gate = OrderedGate()
            self._retire_lanes.start()
            self._submit_threads = [
                threading.Thread(
                    target=self._submit_run, args=(self._stop, i),
                    daemon=True,
                    name=f"fastpath-submit-{self.pipeline}-{i}")
                for i in range(self.submit_lanes)]
            for t in self._submit_threads:
                t.start()
            self._timer_thread = threading.Thread(
                target=self._timer_run, args=(self._stop,), daemon=True,
                name=f"fastpath-expiry-{self.pipeline}")
            self._timer_thread.start()

    def drain(self, timeout: float = 30.0) -> bool:
        """Wait until every accepted frame has been forwarded
        downstream. Condition-signaled by the last retiring lane —
        returns the instant the window empties; the timeout is the
        caller's bound, not a poll interval."""
        deadline = time.monotonic() + timeout
        with self._lock:
            while self._live:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._drained.wait(remaining)
            return True

    def _abandon_frame(self, frame: _Frame) -> None:
        """Shutdown-path shed for a frame the stopped lanes can no
        longer retire: name the spans in the ledger (the engine's
        ``shutdown_drain`` discipline) and release the reservation —
        the balance stays exact even after a timed-out drain, and
        shutdown never blocks on the downstream that wedged it."""
        FlowContext.drop(len(frame.batch), "shutdown_drain",
                         component=self, pipeline=self.pipeline)
        self._release_frame(frame)

    def shutdown(self) -> None:
        # drain first: the engine keeps scoring until its own shutdown
        # and the expiry timer bounds every straggler at its deadline,
        # so in the normal case every accepted frame resolves (or times
        # out into pass-through) before anything below runs
        self.drain(self.drain_timeout_s)
        self._stop.set()
        with self._lock:
            self._submit_have.notify_all()
            self._timer_wake.notify_all()
            self._drained.notify_all()
        for t in self._submit_threads:
            t.join(timeout=5)
        if self._timer_thread is not None:
            self._timer_thread.join(timeout=5)
        self._retire_lanes.shutdown()
        # a timed-out drain (wedged downstream) leaves frames behind.
        # Forwarding them inline would block shutdown on the very
        # downstream that wedged the drain — instead CLAIM every
        # unretired frame (ready=True makes any late engine callback a
        # no-op via the _mark_ready_locked guard) and shed it as a
        # named shutdown_drain drop. Frames a stuck daemon lane still
        # holds (retiring) stay its property — it may yet finish them,
        # and abandoning one here would double-release the reservation.
        leftovers = self._retire_lanes.drain_pending()
        if self._gate is not None:
            leftovers.extend(self._gate.flush())
        with self._lock:
            for f in self._live:
                if not (f.done or f.retiring or f.ready):
                    f.ready = True
                    leftovers.append(f)
        seen: set[int] = set()
        for f in sorted(leftovers, key=lambda f: f.seq):
            if id(f) in seen or f.done or f.retiring:
                continue
            seen.add(id(f))
            self._abandon_frame(f)
        # a stuck lane that finished its forward mid-shutdown advances
        # the gate and re-pushes the next parked frame into the stopped
        # pool — sweep once more so that frame's reservation releases
        for f in self._retire_lanes.drain_pending():
            if not (f.done or f.retiring):
                self._abandon_frame(f)
        self._submit_threads = []
        self._timer_thread = None
        self._started = False
