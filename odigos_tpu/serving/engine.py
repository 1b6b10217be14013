"""Batched async scoring engine — the TPU sidecar.

The north star's hardest constraint (SURVEY.md §7 "Hard parts"): the pipeline
must never block on TPU round-trips; <5 ms p99 added latency at ≥1M spans/s.
The reference's analog discipline is the eBPF receiver's hot loop + pre-decode
rejection (odigosebpfreceiver/traces.go:17, configgrpc fork).

Design — a two-stage software pipeline over one worker thread:

* callers ``submit()`` featurized batches into a **bounded** queue and wait on
  a per-request event with a deadline;
* the worker's **pack stage** drains the queue, **coalesces** pending requests
  into a single device call (big batches feed the MXU), featurizes/packs on
  the host, and *dispatches* the device call without blocking on its result
  (JAX async dispatch). A call is closed on the ladder rung it will fill:
  its budget is packed rows on a rung, and the request that would spill
  past it is held to lead the next call (``_collect``);
* up to ``pipeline_depth`` device calls ride **in flight** at once: while
  call N executes on the device, the worker packs and dispatches call N+1 —
  the host/device overlap that closes the serial featurize→execute→fetch
  gap. The **harvest stage** then blocks on the *oldest* in-flight call,
  splits scores back per request, and sets events — FIFO, so per-request
  results are byte-identical to the serial path;
* backends without an async ``dispatch`` (zscore's ordered online updates,
  mock, the remote sidecar with its own deadline) degrade to depth 1 — the
  exact serial behavior;
* shape churn is absorbed by a **bucket ladder**: packed row counts round up
  to a small geometric set of precompiled XLA shapes (optionally warmed at
  ``start()``), so steady-state traffic never recompiles;
* if the deadline passes, the caller forwards spans unscored (pass-through)
  and the late scores still update online state; a passthrough counter feeds
  own-telemetry (the memory-limiter-rejections pattern);
* if the queue is full, ``submit`` fails fast (admission control) instead of
  stalling the pipeline; ``shutdown()`` drains queued and in-flight work
  losslessly before the worker exits.

Backends plug in via ``ModelBackend``: zscore (streaming, online update),
transformer / autoencoder (sequence models with shape-bucketed jit), and mock
(deterministic, TPU-free — the mockdestinationexporter pattern for tests).
A gRPC/unix-socket front-end for true sidecar deployment wraps this engine in
odigos_tpu.serving.sidecar.
"""

from __future__ import annotations

import logging
import math
import queue
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Any, Callable, NamedTuple, Optional, Protocol

import numpy as np

from ..features.bufferpool import (
    BufferPool, alloc as _pool_alloc, lease_scope, pools_enabled)
from ..features.featurizer import (
    FeaturizerConfig, SpanFeatures, assemble_sequences, featurize,
    pack_sequences)
from ..pdata.spans import SpanBatch
from ..selftelemetry.flow import FlowContext
from ..selftelemetry.latency import annotate, latency_enabled, name_thread
from ..selftelemetry.profiler import engines as _engine_registry
from ..selftelemetry.tracer import (
    NULL_SPAN, is_selftelemetry_batch, tracer)
from ..utils.telemetry import labeled_key, meter

_log = logging.getLogger(__name__)


def _record_compile_seconds(site: str, seconds: float) -> None:
    """Feed observed compile time into models.jitstats WITHOUT importing
    the models package (and so jax) from a process that never loaded it
    (mock-backend engines must stay jax-free)."""
    import sys

    if "jax" not in sys.modules:
        return
    from ..models import jitstats

    jitstats.record_compile_seconds(site, seconds)


def _record_compile_event(site: str, seconds: float,
                          shape: Optional[str] = None,
                          trace_id: Optional[str] = None,
                          warm: bool = False) -> None:
    """Compile-as-event twin of :func:`_record_compile_seconds` (ISSUE
    20): same jax-free gate, but the compile also lands in the flight
    recorder's timeline and the storm detector."""
    import sys

    if "jax" not in sys.modules:
        return
    from ..models import jitstats

    jitstats.record_compile_event(site, seconds, shape=shape,
                                  trace_id=trace_id, warm=warm)

PASSTHROUGH_METRIC = "odigos_anomaly_passthrough_total"
QUEUE_FULL_METRIC = "odigos_anomaly_queue_full_total"
SCORED_METRIC = "odigos_anomaly_scored_spans_total"
COLD_METRIC = "odigos_anomaly_cold_spans_total"
DEVICE_BUSY_GAUGE = "odigos_anomaly_device_busy_frac"
STAGE_PACK_METRIC = "odigos_anomaly_stage_pack_ms"
STAGE_DEVICE_METRIC = "odigos_anomaly_stage_device_ms"
STAGE_HARVEST_METRIC = "odigos_anomaly_stage_harvest_ms"
ADAPTIVE_CAP_GAUGE = "odigos_engine_adaptive_cap_spans"
COALESCE_CLOSED_METRIC = "odigos_anomaly_coalesce_closed_total"
# when a coalesced call was committed (closed, packed, enqueued): idle,
# filled, due, blind (ScoringEngine._collect)
COMMIT_METRIC = "odigos_anomaly_commit_total"
# blocks applied, calls x passes x layers: a build that runs fewer passes
# than its configuration states shows in the program's own telemetry
LAYER_APPLICATIONS_METRIC = "odigos_anomaly_layer_applications_total"
RUNG_SPILL_METRIC = "odigos_anomaly_rung_spill_total"
MESH_UNAVAILABLE_METRIC = "odigos_engine_mesh_unavailable_total"

# EWMA smoothing of the per-span device-step cost estimate; 0.2 follows
# load shifts within ~5 calls without letting one outlier call resize
# the next batch
_ADAPT_ALPHA = 0.2
# the margin an estimate carries, in mean deviations (the retransmit
# timer's srtt + 4 rttvar). Spans a packed row is counted on to hold: the
# mean less this many (a call closed for a rung whose pack spills past it
# runs the next rung, twice the time, so the estimate leans towards the
# row that holds less). The lead a call is committed ahead of the chip
# freeing: the mean pack plus this many (a pack that overruns the lead
# leaves the chip idle for the difference)
_MARGIN_DEVS = 4.0
# while a call is held open for the commit point the worker wakes this
# often, to see a stop or that the call ahead has landed already
_HOLD_SLICE_S = 0.005


def _ewma(old: Optional[float], new: float) -> float:
    """One step of the adaptive estimators' smoothing (None: first)."""
    return new if old is None else \
        (1 - _ADAPT_ALPHA) * old + _ADAPT_ALPHA * new


def _ewma_dev(mean: Optional[float], dev: float,
              new: float) -> tuple[float, float]:
    """One step of a mean and of its mean deviation, the deviation
    taken against the mean before the step (None: first, no deviation
    yet)."""
    if mean is not None:
        dev = _ewma(dev, abs(new - mean))
    return _ewma(mean, new), dev


def _mesh_label(mesh_spec) -> str:
    """Gauge/stats label for a normalized mesh spec ("data4xmodel2").
    jax-free mirror of parallel.mesh.mesh_key — the engine must never be
    the reason jax loads in a mock/zscore process."""
    parts = [f"{a}{int(n)}" for a, n in (mesh_spec or ()) if int(n) > 1]
    return "x".join(parts) if parts else "single"


@dataclass(frozen=True)
class EngineConfig:
    model: str = "zscore"  # zscore | transformer | autoencoder | mock | remote
    max_queue: int = 64          # pending requests bound
    max_batch_spans: int = 65536  # coalescing cap per device call
    max_len: int = 64            # sequence models: spans per trace
    trace_bucket: int = 256      # sequence models: base row/trace shape bucket
    online_update: bool = True   # zscore: fit on observed traffic
    # transformer: serve with int8 (W8A8) matmuls — ~2x MXU rate on v5e;
    # weights quantize once at load (models/quantized.py)
    quantized: bool = False
    featurizer: FeaturizerConfig = field(default_factory=FeaturizerConfig)
    model_config: Optional[Any] = None  # TransformerConfig / AutoencoderConfig
    checkpoint_path: Optional[str] = None
    socket_path: Optional[str] = None  # model "remote": sidecar unix socket
    remote_timeout_s: float = 10.0  # model "remote": per-call socket deadline
    # device mesh for sharded serving (ISSUE 7 tentpole): the ENGINE owns
    # one jax.sharding.Mesh and dispatches every packed call through a
    # partition-rule dp×tp plan (parallel.compile_plan). Accepts
    # {"data": N, "model": M} or ((axis, size), ...) pairs; normalized in
    # __post_init__ to a hashable tuple (shared-engine keying hashes the
    # config) and to None when the product is 1. Sequence models only —
    # zscore/mock/remote ignore it.
    mesh: Any = None
    # legacy spelling of mesh={"data": N} (BASELINE config #5: dp over
    # v5e-8); kept so existing configs and checkpoints keep working.
    # 0/1 = single device; ignored when mesh is set.
    data_parallel: int = 0
    seed: int = 0
    # ---- pipelining (sequence backends only; others clamp to depth 1).
    # Depth 2 = classic double buffering: one call packing on the host while
    # one executes on the device. Deeper windows add in-flight latency (a
    # request's result waits behind depth-1 device calls) without adding
    # overlap — two stages can only hide one call — so 2 is the sweet spot
    # inside the 5 ms budget (docs/architecture.md "Scoring engine
    # pipelining"). The depth bounds the window; WHEN the free slot is
    # filled the engine decides call by call: at once where waiting can
    # gain nothing, else when the running call is about to end, so that
    # the second call is in the window for the pack's length and not for
    # a whole step (late commit, ``ScoringEngine._collect``).
    pipeline_depth: int = 2
    bucket_ladder: int = 4      # geometric row buckets above trace_bucket
    warm_ladder: bool = False   # compile the whole ladder at start()
    # failover supervisor (ISSUE 13): a circuit breaker over the
    # dispatch/harvest error path that hot-swaps scoring to the
    # fallback model (zscore; same default device as the primary) on a
    # persistent fault and half-open probes the primary back
    # (serving/failover.py). Accepts True (defaults)
    # or a {window_s, trip_errors, probe_interval_s,
    # recovery_successes, fallback_model} mapping; normalized hashable
    # in __post_init__ (shared-engine keying hashes the config); None/
    # False = no breaker (the pre-ISSUE-13 behavior, byte-identical).
    failover: Any = None
    # ---- sampled intra-fused device attribution (ISSUE 20): 1-in-
    # stride fused frames run as their five jitted sub-stages with
    # per-sub-stage device stamps (serving/deviceattrib.py). Opt-in;
    # the off path is the untouched PR 17 dispatch. Live kill switch:
    # ODIGOS_DEVICE_ATTRIB=0; stride override: ODIGOS_DEVICE_ATTRIB_N.
    device_attribution: bool = False
    device_attribution_stride: int = 32

    def __post_init__(self) -> None:
        m = self.mesh
        if m is not None:
            items = m.items() if isinstance(m, dict) else tuple(m)
            m = tuple((str(a), int(s)) for a, s in items)
            bad = [(a, s) for a, s in m if s <= 0]
            if bad:
                # silently dropping a zero-size axis would serve pure-DP
                # while the operator believes tp is active — refuse
                # (same stance as quantized+mesh)
                raise ValueError(f"mesh axes must be positive: {bad}")
        if m is None and self.data_parallel and self.data_parallel > 1:
            m = (("data", int(self.data_parallel)),)
        if m is not None and math.prod(s for _, s in m) <= 1:
            m = None  # a 1x1 mesh is the single-device path
        object.__setattr__(self, "mesh", m)
        f = self.failover
        if f is False or f is None:
            f = None
        elif f is True:
            f = ()  # all-defaults breaker
        else:
            items = dict(f.items() if isinstance(f, dict) else tuple(f))
            # {"enabled": false} is an explicit OPT-OUT, not a tuning
            # knob: popping the key unconditionally would arm a default
            # breaker the config just turned off
            if not items.pop("enabled", True):
                f = None
            else:
                f = tuple(sorted((str(k), v) for k, v in items.items()))
        object.__setattr__(self, "failover", f)

    def failover_spec(self) -> Optional[dict[str, Any]]:
        """Normalized failover mapping (None = breaker disabled)."""
        return dict(self.failover) if self.failover is not None else None

    def mesh_shape(self) -> Optional[dict[str, int]]:
        """Normalized mesh spec as the dict parallel.make_mesh takes."""
        return dict(self.mesh) if self.mesh else None


class DeviceFaultInjected(RuntimeError):
    """Raised by the chaos device-fault hook (``inject_device_fault``):
    the deterministic stand-in for a dead/wedged device on the primary
    scoring route."""


class ModelBackend(Protocol):
    def score(self, batch: SpanBatch, features: SpanFeatures) -> np.ndarray:
        """Return per-span anomaly scores, shape (len(batch),)."""

    # Pipelining (optional): backends that can enqueue device work without
    # blocking split score() into dispatch() -> opaque handle and
    # harvest(handle) -> scores. The engine only overlaps backends that
    # define dispatch; score() must equal harvest(dispatch(...)) so the
    # serial and pipelined paths return identical bytes.


class BucketLadder:
    """Geometric row-count buckets bounding XLA recompiles.

    ``round_rows`` maps a real packed row/trace count to the smallest ladder
    bucket that holds it (base, 2·base, 4·base, ...); counts beyond the top
    bucket round up to a multiple of it (rare — the coalescer closes a call
    on a rung, so only a single request can pack past the top one). ``observe`` tracks which shapes have already been
    compiled this process (LRU-bounded so an adversarial shape storm cannot
    grow the table), feeding the bench's hit-rate and the zero-recompile
    assertion; ``mark_warm`` pre-seeds it from ``warm()`` compilations.

    ``align`` (ISSUE 7): every rung is lifted to lcm(base, align) so that
    under a dp-wide mesh each padded row count stays shard-divisible —
    the pack stage emits dp-aligned row groups by construction and the
    sharded call never re-pads (re-padding would mint shapes the warmed
    ladder has not compiled).
    """

    def __init__(self, base: int, n_buckets: int = 4, align: int = 1):
        self.align = max(1, int(align))
        self.base = math.lcm(max(1, int(base)), self.align)
        self.buckets = [self.base << k for k in range(max(1, int(n_buckets)))]
        self.hits = 0
        self.misses = 0
        self._compiled: OrderedDict[int, None] = OrderedDict()
        self._max_tracked = max(16, len(self.buckets) * 2)

    def round_rows(self, rows: int) -> int:
        for b in self.buckets:
            if rows <= b:
                return b
        top = self.buckets[-1]
        return ((rows + top - 1) // top) * top

    def mark_warm(self, rows: int) -> None:
        self._compiled[rows] = None
        self._compiled.move_to_end(rows)

    def observe(self, rows: int) -> bool:
        """Record a device call at this padded row count; True = the shape
        was already compiled (warm hit, no XLA recompile)."""
        hit = rows in self._compiled
        if hit:
            self.hits += 1
            self._compiled.move_to_end(rows)
        else:
            self.misses += 1
            self._compiled[rows] = None
            if len(self._compiled) > self._max_tracked:
                self._compiled.popitem(last=False)
        return hit

    def floor_rows(self, rows: float) -> int:
        """Largest padded row count ≤ ``rows`` that ``round_rows`` could
        emit (the smallest bucket when nothing fits): the adaptive
        coalescer sizes deadline-bounded batches DOWN onto shapes the
        ladder serves, never up into a recompile. Beyond the top bucket
        that is a multiple of it, mirroring ``round_rows``."""
        top = self.buckets[-1]
        if rows >= top:
            return (int(rows) // top) * top
        best = self.buckets[0]
        for b in self.buckets:
            if b <= rows:
                best = b
        return best

    def stats(self) -> dict[str, Any]:
        total = self.hits + self.misses
        return {
            "hits": self.hits,
            "misses": self.misses,
            "hit_rate": round(self.hits / total, 4) if total else 0.0,
            "buckets": list(self.buckets),
            "align": self.align,
        }


class MockBackend:
    """Deterministic TPU-free backend: score = duration percentile proxy.
    Spans with attr ``mock.anomaly`` always score 1.0 (test hook)."""

    def __init__(self, cfg: EngineConfig, mesh: Any = None):
        self.cfg = cfg  # mesh ignored: no device work to shard

    def score(self, batch: SpanBatch, features: SpanFeatures) -> np.ndarray:
        log_dur = features.continuous[:, 0]
        scores = np.clip((log_dur - 5.0) / 10.0, 0.0, 1.0)
        forced = batch.attrs().mask_has("mock.anomaly")
        return np.where(forced, 1.0, scores).astype(np.float32)


class ZScoreBackend:
    # no async dispatch: score-then-update must stay ordered per device
    # call, so the engine clamps this backend to pipeline depth 1

    # column-only coalescing (ingest fast path): scoring reads features
    # exclusively, so a coalesced group never needs a merged SpanBatch
    coalesce_columns: tuple = ()

    def __init__(self, cfg: EngineConfig, mesh: Any = None):
        from ..models.zscore import ZScoreDetector

        self.cfg = cfg  # mesh ignored: streaming CPU state is unsharded
        self.det = ZScoreDetector()

    def score(self, batch: SpanBatch, features: SpanFeatures) -> np.ndarray:
        z = self.det.score(features)
        if self.cfg.online_update:
            self.det.update(features)
        n_cold = int((z == 0.0).sum())
        if n_cold:
            meter.add(COLD_METRIC, n_cold)
        # map |z| to (0, 1): 1 - exp(-z/4) puts z=3 ≈ 0.53, z=8 ≈ 0.86
        return (1.0 - np.exp(-z / 4.0)).astype(np.float32)

    def warm(self) -> None:
        """``warm_ladder`` analogue: precompile every span-bucket shape
        the adaptive coalescer can emit (state-safe — zero-weighted
        updates merge nothing), so a deadline-sized batch never pays a
        mid-stream XLA compile."""
        t0 = time.monotonic()
        self.det.warm(self.cfg.max_batch_spans,
                      self.cfg.featurizer.cat_width)
        _record_compile_seconds("zscore.update_masked",
                                time.monotonic() - t0)

    def warmup(self, batch: SpanBatch) -> None:
        self.det.update(featurize(batch, self.cfg.featurizer))


class SequenceBackend:
    """Transformer / autoencoder scoring over assembled trace sequences.

    Scores are computed per (trace, position) and scattered back to span rows
    via span_index. The bucket ladder (BucketLadder over trace_bucket) bounds
    XLA recompilation; ``dispatch``/``harvest`` split the device call so the
    engine can overlap host packing with device execution (the scatter and
    the blocking ``np.asarray`` fetch happen at harvest, against the
    *previous* in-flight call's result).

    The mesh (if any) is ENGINE-owned and passed in — this backend never
    constructs one (ISSUE 7 satellite: one mesh, one owner). Under a mesh
    every device call routes through the partition-rule dp×tp plan
    (parallel.compile_plan), and the ladder aligns its rungs to the data
    axis so packed row groups are shard-divisible by construction.
    """

    # column-only coalescing (ingest fast path): when every request in a
    # group carries precomputed features, packing/assembly reads just the
    # trace ids and start times — a _ColumnBatch view over the group skips
    # the merged batch's string re-interning and attr-store merge entirely
    coalesce_columns: tuple = ("trace_id_hi", "trace_id_lo",
                               "start_unix_nano")

    def __init__(self, cfg: EngineConfig, mesh: Any = None):
        import jax

        self.cfg = cfg
        self.mesh = mesh
        self._attention_blocks: dict[int, tuple[int, int] | None] = {}
        model_config = cfg.model_config
        variables = None
        if cfg.checkpoint_path:
            # serving bundle (training/checkpoint.py): the artifact carries
            # the model geometry, so a pipeline config only needs the path
            from ..training.checkpoint import load_bundle

            bundle = load_bundle(cfg.checkpoint_path)
            if bundle.model != cfg.model:
                raise ValueError(
                    f"checkpoint {cfg.checkpoint_path} holds a "
                    f"{bundle.model!r} model but the engine is configured "
                    f"for {cfg.model!r}")
            if model_config is not None and model_config != bundle.model_config:
                # an explicit geometry that disagrees with the restored
                # weights would mis-index silently (e.g. a too-long
                # positional table clamps instead of erroring)
                raise ValueError(
                    f"model_config disagrees with checkpoint "
                    f"{cfg.checkpoint_path}: {model_config} vs "
                    f"{bundle.model_config}")
            model_config = bundle.model_config
            variables = bundle.variables
        if cfg.model == "transformer":
            from ..models.transformer import TraceTransformer, TransformerConfig

            self.model = TraceTransformer(model_config or TransformerConfig(
                attr_slots=cfg.featurizer.attr_slots))
            if cfg.quantized and self.model.cfg.block != "encoder":
                # the int8 scorer mirrors the encoder block's parameter
                # tree; refuse before any weight is made
                raise ValueError(
                    f"quantized serving is only implemented for the "
                    f"encoder block, not block {self.model.cfg.block!r}")
        else:
            from ..models.autoencoder import AutoencoderConfig, SpanAutoencoder

            self.model = SpanAutoencoder(model_config or AutoencoderConfig(
                attr_slots=cfg.featurizer.attr_slots))
        # the model's positional table bounds the sequence geometry: never
        # pack longer rows than the (possibly restored) model can embed
        self.max_len = min(cfg.max_len, self.model.cfg.max_len)
        self.device_label = str(jax.devices()[0])
        # rungs lcm-aligned to the data axis: the pack stage then emits
        # dp-divisible row groups and the sharded call never re-pads
        dp = int(mesh.shape.get("data", 1)) if mesh is not None else 1
        self.ladder = BucketLadder(cfg.trace_bucket, cfg.bucket_ladder,
                                   align=dp)
        # jitstats site this backend's device calls compile under — must
        # match the track_jit registration in models/ so compile seconds
        # and cache size land on the same label value
        self.jit_site = ("transformer.score_packed"
                         if cfg.model == "transformer"
                         else "autoencoder.score_spans")
        if getattr(self.model, "score_packed_counted", None) is not None:
            self.jit_site = "transformer.score_packed_counted"
        self.last_shape: Optional[list[int]] = None
        # rows (traces, on the sequence route) the packer filled before
        # the ladder padded them up to last_shape[0]
        self.last_real_rows: Optional[int] = None
        self.last_padding_waste: Optional[float] = None
        self.last_bucket_hit: Optional[bool] = None
        # the last fetch's two parts, ms: (wait for the device, the copy)
        self.last_harvest_ms: Optional[tuple[float, float]] = None
        self.variables = variables if variables is not None else \
            self.model.init(jax.random.PRNGKey(cfg.seed))
        # what every tpu/score span says of the model behind the call
        self.score_attrs: dict[str, Any] = self.model.cfg.span_attrs \
            if cfg.model == "transformer" else {}
        # and which of a call's own counts (``call_attrs``) feed which
        # counter: the model declares them, the engine names none
        self.call_counters: dict[str, str] = self.model.cfg.call_counters \
            if cfg.model == "transformer" else {}
        self._plan = None
        self._quantized = None
        if cfg.quantized and cfg.model == "transformer":
            if cfg.mesh is not None:
                # refusing beats silently serving bf16 while holding an
                # unused int8 weight copy on device
                raise ValueError(
                    "quantized serving does not compose with a device "
                    "mesh yet; pick one")
            from ..models.quantized import QuantizedTraceScorer

            self._quantized = QuantizedTraceScorer(self.model,
                                                   self.variables)
            self.jit_site = "quantized.score_packed"  # the jit that runs
        if mesh is not None:
            from ..parallel import compile_plan

            # partition-rule dp×tp plan: params per PARTITION_RULES,
            # packed rows on "data". Non-blocking by design: the engine
            # harvests the device array itself so the fetch overlaps the
            # next in-flight call.
            self._plan = compile_plan(self.model, mesh,
                                      variables=self.variables)
            if cfg.model == "transformer":
                # per-mesh compile attribution: each mesh shape warms its
                # own ladder, and the jitstats ledger must say which one
                self.jit_site = f"parallel.plan.score_packed[{self._plan.key}]"

    # ------------------------------------------------------- device stage

    def _device_call(self, packed) -> tuple[Any, Any]:
        """Enqueue the packed scoring call; returns the device array of
        scores WITHOUT blocking on it (JAX async dispatch) and, where the
        model counts on the device what the call did, those counts
        (scalars by span attribute name, of the same program; else
        None)."""
        import jax.numpy as jnp

        if self._plan is not None:  # dp×tp across chips (partition plan)
            return self._plan.score_packed_counted(
                self.variables, packed.categorical, packed.continuous,
                packed.segments, packed.positions)
        if self._quantized is not None:  # int8 serving path
            return self._quantized.score_packed(
                jnp.asarray(packed.categorical),
                jnp.asarray(packed.continuous),
                jnp.asarray(packed.segments),
                jnp.asarray(packed.positions)), None
        args = (self.variables, jnp.asarray(packed.categorical),
                jnp.asarray(packed.continuous),
                jnp.asarray(packed.segments),
                jnp.asarray(packed.positions))
        if self.model.score_packed_counted is not None:
            return self.model.score_packed_counted(*args)
        return self.model.score_packed(*args), None

    def _round_rows(self, real: int) -> int:
        """The ladder's rounding, remembering what it rounded: the
        engine learns spans per row from the rows that were filled."""
        self.last_real_rows = int(real)
        return self.ladder.round_rows(real)

    def pack(self, batch: SpanBatch, features: SpanFeatures) -> Any:
        """Pack stage, the host's half: featurize/pack/pad into the
        arrays of one device call. Returns what ``enqueue`` takes."""
        if self.cfg.model == "transformer":
            # packed rows: block-diagonal attention, ~6x the MXU density of
            # naive per-trace padding
            packed = pack_sequences(batch, features, max_len=self.max_len,
                                    pad_rows_to=self._round_rows)
            # scoring-span attributes: device shape + padding waste (the
            # MXU-density evidence the bench trajectory reads offline)
            self.last_shape = list(packed.categorical.shape[:2])
            self.last_padding_waste = round(1.0 - float(packed.density()), 4)
            self.last_bucket_hit = self.ladder.observe(packed.n_rows)
            return ("packed", packed, len(batch))

        seqs = assemble_sequences(
            batch, features, max_len=self.max_len,
            pad_traces_to=self._round_rows)
        self.last_shape = list(seqs.categorical.shape[:2])
        self.last_padding_waste = round(1.0 - float(seqs.mask.mean()), 4) \
            if seqs.mask.size else 0.0
        self.last_bucket_hit = self.ladder.observe(seqs.n_traces)
        return ("seq", seqs, len(batch))

    def enqueue(self, staged: Any, call: int = -1) -> Any:
        """Pack stage, the device's half: host-to-device copies and the
        jitted call's enqueue, non-blocking. ``call`` is the engine's
        serial of the coalesced call (-1: nobody's, a direct score).
        Returns an opaque handle for ``fetch``."""
        kind, host, n = staged
        with annotate("engine/enqueue", call=call,
                      rows=host.categorical.shape[0], spans=n):
            counts = None
            if kind == "packed":
                dev, counts = self._device_call(host)
            else:
                dev, _ = self._seq_call(host.categorical, host.continuous,
                                        host.mask)
        return _HostCall(kind, dev, host.span_index, host.mask, n, counts)

    def dispatch(self, batch: SpanBatch, features: SpanFeatures) -> Any:
        """Pack stage: ``pack`` then ``enqueue``. Returns an opaque
        handle for ``harvest``."""
        return self.enqueue(self.pack(batch, features))

    def _seq_call(self, cat, cont, mask) -> Any:
        """Sequence-route device call (autoencoder): through the mesh
        plan when sharded, the model's own jit otherwise."""
        import jax.numpy as jnp

        if self._plan is not None:
            return self._plan.score_spans(self.variables, cat, cont, mask)
        return self.model.score_spans(
            self.variables, jnp.asarray(cat), jnp.asarray(cont),
            jnp.asarray(mask))

    def ready(self, handle: Any) -> bool:
        """Whether the enqueued call's result is there already (asked,
        never waited for): ``fetch`` would return at once."""
        is_ready = getattr(handle[1], "is_ready", None)
        return bool(is_ready()) if is_ready is not None else False

    def fetch(self, handle: Any, call: int = -1) -> Any:
        """Harvest stage, the wait: block on the device result (the only
        blocking host<->device interaction, ``engine/ready``), then copy
        it and the call's counts to the host in one transfer, and nothing
        else. Returns the handle with host values in the device arrays'
        place, for ``harvest``; the two parts' walls are left in
        ``last_harvest_ms``."""
        import jax

        with annotate("engine/harvest", call=call):
            t0 = time.perf_counter()
            with annotate("engine/ready", call=call):
                jax.block_until_ready(handle[1])
            t1 = time.perf_counter()
            if isinstance(handle, _HostCall):
                # the call's own counts, of the program that just ended
                scores, counts = jax.device_get(
                    (handle.scores, handle.counts or {}))
                fetched = handle._replace(
                    scores=np.asarray(scores, dtype=np.float32),
                    counts={key: value.item()
                            for key, value in counts.items()})
            else:                                        # the fused route's
                fetched = (handle[0], np.asarray(handle[1], dtype=np.float32)
                           ) + tuple(handle[2:])
            self.last_harvest_ms = (1e3 * (t1 - t0),
                                    1e3 * (time.perf_counter() - t1))
        return fetched

    def call_attrs(self, handle: Any) -> dict[str, Any]:
        """What a call counted on the device, by the name it has on the
        call's ``tpu/score`` span: host numbers once ``fetch`` has run.
        Nothing for a model that counts nothing and for the fused route."""
        return getattr(handle, "counts", None) or {}

    def harvest(self, handle: Any) -> np.ndarray:
        """Harvest stage: block on the device result, unless ``fetch``
        already has, and scatter scores back to span rows."""
        kind, dev, span_index, mask, n, _ = handle
        span_scores = np.asarray(dev, dtype=np.float32)
        if kind == "seq":
            # raw reconstruction error is unbounded; squash to (0, 1) so the
            # processor's threshold contract (score in [0,1]) holds for both
            # sequence models (the transformer path is already a sigmoid)
            span_scores = 1.0 - np.exp(-span_scores)
        out = np.zeros(n, np.float32)
        out[span_index[mask]] = span_scores[mask]
        return out

    def score(self, batch: SpanBatch, features: SpanFeatures) -> np.ndarray:
        return self.harvest(self.dispatch(batch, features))

    def warm(self) -> None:
        """Compile every ladder bucket with zero-filled inputs so
        steady-state traffic never pays an XLA recompile (all-padding
        inputs trace the same program as real ones — shapes are all that
        matter to jit). Rungs are mesh-aligned, so each compile happens
        ONCE PER MESH SHAPE — per-mesh jit sites make that auditable in
        the compile-seconds ledger, and replicas dispatching through the
        same engine share the warm ladder."""
        C = self.cfg.featurizer.cat_width
        D = self.cfg.featurizer.cont_width
        L = self.max_len
        site = self.jit_site
        for R in self.ladder.buckets:
            t0 = time.monotonic()
            if self.cfg.model == "transformer":
                zero = _ZeroPacked(
                    np.zeros((R, L, C), np.int32),
                    np.zeros((R, L, D), np.float32),
                    np.zeros((R, L), np.int32),
                    np.zeros((R, L), np.int32))
                dev, _ = self._device_call(zero)
            else:
                zero = (np.zeros((R, L, C), np.int32),
                        np.zeros((R, L, D), np.float32),
                        np.zeros((R, L), bool))
                dev, _ = self._seq_call(*zero)
            np.asarray(dev)  # block: compile finished before serving
            self.ladder.mark_warm(R)
            # ladder warming is the one place every bucket compile is
            # observable end-to-end — feed the per-site compile ledger
            # (warm=True: a planned compile, never a storm signal) and
            # snapshot XLA's cost model for the freshly compiled shape
            _record_compile_event(site, time.monotonic() - t0,
                                  shape=f"r{R}", warm=True)
            self._capture_warm_cost(site, R, zero)

    def _capture_warm_cost(self, site: str, R: int, zero) -> None:
        """Ask XLA's cost model about the rung just warmed. The mesh
        plan and the int8 scorer wrap their jits behind their own call
        graphs and record nothing here. The int8 engine's rows come
        from the fused route's cold-key capture instead; a mesh plan
        has no fused kernel, so its ledger stays empty."""
        from ..models.costmodel import cost_ledger

        if self._plan is not None or self._quantized is not None:
            return
        if self.cfg.model == "transformer":
            # the entry the rung just ran
            fn = self.model.score_packed_counted or self.model.score_packed
            args = (self.variables, zero.categorical, zero.continuous,
                    zero.segments, zero.positions)
        else:
            # score_spans is jitted on the class with the model static
            fn = type(self.model).score_spans
            args = (self.model, self.variables, *zero)
        cost_ledger.capture(site, f"r{R}", fn, args)

    def rung_attrs(self, rows: int, real_rows: int) -> dict[str, Any]:
        """What a packed call of ``rows`` (its first ``real_rows``
        filled) runs that its shape decides, for its ``tpu/score`` span:
        the encoder's attention kernel or not, and the rows it skipped.
        The kernel's blocks are worked out once a rung
        (``TraceTransformer.attention_blocks`` on this backend's platform
        and mesh). Nothing for a model without packed rows; the int8
        scorer runs its own block, never the kernel."""
        import jax

        from ..models.attention_kernel import rows_skipped

        if self.cfg.model != "transformer":
            return {}
        if rows not in self._attention_blocks:
            self._attention_blocks[rows] = None if self._quantized \
                is not None else self.model.attention_blocks(
                    rows, self.max_len, jax.devices()[0].platform,
                    dict(self.mesh.shape) if self.mesh is not None else None)
        blocks = self._attention_blocks[rows]
        return {"attn.kernel": blocks is not None,
                "attn.rows_skipped": 0 if blocks is None
                else rows_skipped(rows, real_rows, *blocks)}


class _HostCall(NamedTuple):
    """The host route's handle from ``enqueue`` to ``harvest``."""

    kind: str                  # "packed" or "seq"
    scores: Any                # the device array; the host's after fetch
    span_index: np.ndarray
    mask: np.ndarray
    n: int
    counts: Optional[dict[str, Any]]   # what the model counted, or None


@dataclass(frozen=True)
class _ZeroPacked:
    """Shape-only stand-in for PackedSequences during ladder warming."""

    categorical: np.ndarray
    continuous: np.ndarray
    segments: np.ndarray
    positions: np.ndarray


def _remote_backend(cfg: "EngineConfig", mesh: Any = None):
    from .sidecar import RemoteBackend

    return RemoteBackend(cfg)  # mesh lives sidecar-side for remote


def _fused_backend(cfg: "EngineConfig", mesh: Any = None):
    # FusedSequenceBackend IS a SequenceBackend — the host dispatch and
    # every non-fused engine stays bit-identical; the subclass only adds
    # the columns→scores route (ISSUE 19). Imported lazily so mock/
    # zscore engines never pull the fused module's import chain.
    from .fused import FusedSequenceBackend

    return FusedSequenceBackend(cfg, mesh=mesh)


_BACKENDS = {
    "mock": MockBackend,
    "zscore": ZScoreBackend,
    "transformer": _fused_backend,
    "autoencoder": _fused_backend,
    "remote": _remote_backend,
}


class _ColumnBatch:
    """Columns-only stand-in for a concatenated SpanBatch.

    A coalesced device call with precomputed features touches a handful
    of numeric columns (trace grouping + packing); concatenating those
    lazily keeps the pack seam zero-copy with respect to everything else
    a full ``concat_batches`` would re-materialize per call (string
    tables re-interned span-by-span, attr pools merged, every other
    column copied). Only handed to backends that declare
    ``coalesce_columns``.
    """

    __slots__ = ("_batches", "_cols", "_n")

    def __init__(self, batches: list[SpanBatch]):
        self._batches = batches
        self._cols: dict[str, np.ndarray] = {}
        self._n = sum(len(b) for b in batches)

    def col(self, name: str) -> np.ndarray:
        arr = self._cols.get(name)
        if arr is None:
            arr = self._cols[name] = np.concatenate(
                [b.col(name) for b in self._batches])
        return arr

    def __len__(self) -> int:
        return self._n


@dataclass
class ScoreRequest:
    batch: SpanBatch
    features: SpanFeatures
    # fused route (ISSUE 19): the frame's raw SpanColumns view when the
    # submit lane skipped host featurize. The pack stage scores columns
    # device-side when the whole group carries them and the backend has
    # a fused kernel; otherwise it host-featurizes here (batch always
    # rides alongside, so the conversion is the bit-exact host path).
    columns: Any = None
    done: threading.Event = field(default_factory=threading.Event)
    scores: Optional[np.ndarray] = None
    submitted_ns: int = 0
    # admission deadline (monotonic ns): the pack stage sizes the
    # coalesced call so the harvest lands inside it (adaptive batching);
    # None = legacy fixed coalescing up to max_batch_spans
    deadline_ns: Optional[int] = None
    # latency attribution (ISSUE 8): stage boundaries of the device call
    # that scored this request — {pack0, dispatch, harvest0, end} in
    # monotonic ns + overlap_ms — shared per coalesced group, assigned
    # BEFORE done fires so a waiter never reads half-built state. None
    # until retired (or forever, when the layer is off / the call
    # failed); dispatched_ns marks pack-stage pickup so an expired
    # deadline can be blamed on queue vs device even without a harvest.
    stage_ns: Optional[dict] = None
    dispatched_ns: int = 0
    # completion-driven retirement (ISSUE 9): invoked exactly once, on
    # the thread that completes the request (worker retire, dispatch
    # failure, shutdown drain), strictly AFTER scores/stage_ns are
    # assigned and done fires — the fast path's completion queue,
    # replacing its done.wait() poll. Must be cheap; exceptions are
    # counted, never propagated into the worker loop.
    on_done: Optional[Callable[["ScoreRequest"], None]] = None
    # buffer-pool hook (ISSUE 12): invoked exactly once, the moment the
    # engine no longer reads ``features`` — after the pack stage's
    # coalesce/score call consumed them (success or failure), or at
    # shutdown fail-fast for never-dispatched requests. Every backend
    # consumes features synchronously inside its dispatch/score call
    # (zscore's async online update copies its inputs for exactly this
    # reason), so the caller's featurize buffers can recycle while the
    # scores are still in flight.
    on_features_consumed: Optional[Callable[[], None]] = None

    def release_features(self) -> None:
        cb, self.on_features_consumed = self.on_features_consumed, None
        if cb is not None:
            try:
                cb()
            except Exception:  # noqa: BLE001 — never kills the worker
                meter.add("odigos_anomaly_engine_errors_total")

    def signal_done(self) -> None:
        """Fire the done event, then the completion callback (at most
        once — re-signaling an already-done request is a no-op, so the
        failure-backstop paths can call this unconditionally)."""
        if self.done.is_set():
            return
        self.done.set()
        cb = self.on_done
        if cb is not None:
            try:
                cb(self)
            except Exception:  # noqa: BLE001 — callback must not kill the worker
                meter.add("odigos_anomaly_engine_errors_total")


@dataclass
class _InflightGroup:
    """One dispatched-but-not-harvested device call."""

    reqs: list[ScoreRequest]
    handle: Any
    span: Any             # selftelemetry Span (begin()ed) or NULL_SPAN
    n_spans: int
    t_pack0: int          # monotonic ns: pack stage start
    t_dispatch: int       # monotonic ns: device call enqueued
    # host pack time spent while another call was in flight — an UPPER
    # bound on true host/device overlap (the in-flight call may finish
    # mid-pack; without device-side timestamps the split is unknowable
    # host-side, same caveat as the device_busy_frac union accounting)
    overlap_ms: float
    bucket_hit: Optional[bool]
    # snapshotted at dispatch: the backend's last_* fields already describe
    # the NEXT call by the time this group retires under depth > 1
    shape: Optional[list[int]]
    padding_waste: Optional[float]
    # buffer-pool lease backing this call's coalesced/packed tensors
    # (ISSUE 12): released at the END of _retire — after the blocking
    # harvest fetch, so the device call has fully consumed its inputs
    # before the backing buffers recycle (the donate-after-last-use
    # contract, host-side). None when pooling is off.
    lease: Any = None
    # the backend that served this call (ISSUE 13): under failover the
    # worker selects a backend PER GROUP, so a group dispatched through
    # the primary before a trip must still harvest against the primary
    # (a fallback harvest on a primary handle would mis-scatter), and
    # its final result is attributed to the right side of the breaker.
    # ``probe`` echoes the supervisor's select() flag: only the probe
    # group's result may resolve the half-open probe slot.
    backend: Any = None
    probe: bool = False
    # fused-route marker (ISSUE 19): selects the latency ledger's
    # fused stage taxonomy when this group scored columns device-side
    fused: bool = False
    # device attribution (ISSUE 20): the sampled intra-fused waterfall
    # dispatch_columns produced for this very group (None = not sampled
    # or skipped), the span-axis bucket (FLOP-waste denominator), and
    # the fused cold-key dispatch wall (a compile event at retire time,
    # where the group's self-trace id is in hand)
    attrib: Optional[dict] = None
    span_bucket: Optional[int] = None
    cold_dispatch_s: float = 0.0
    # the engine's serial of this coalesced call: ``call`` on the
    # engine/* trace annotations, ``call.serial`` on the tpu/score span,
    # ``call`` in the frames' stage_ns
    call: int = 0
    # rows the packer filled before the ladder padded them to shape[0]
    # (None: the fused route and ladderless backends report none)
    real_rows: Optional[int] = None
    # why the coalescer closed this call (drained, rung, cap), and
    # whether it was closed for one rung and packed past it
    closed: str = "drained"
    spilled: bool = False
    # when it was committed (idle, filled, due, blind), how long it was
    # held open after its first request, and the end the engine expected
    # of the call ahead of it (monotonic ns; None: nothing to expect)
    commit: str = "idle"
    held_ms: float = 0.0
    ahead_end_ns: Optional[int] = None
    # what the call counted on the device (backend.call_attrs), read once
    # its result is fetched, and the fetch's two parts (last_harvest_ms)
    call_attrs: Optional[dict[str, Any]] = None
    harvest_split: Optional[tuple[float, float]] = None


class ScoringEngine:
    """One engine per collector process (shared across pipelines).

    >>> eng = ScoringEngine(EngineConfig(model="zscore")).start()
    >>> scores = eng.score_sync(batch, timeout_s=0.005)  # None on timeout
    """

    # per-(model, mesh) learned adaptive-batching priors, shared across
    # engine instances: a re-created engine on the same mesh shape (hot
    # reload, blue/green swap) starts from the last learned device-step
    # cost instead of assuming one chip. Only multi-chip engines consult
    # this — the single-device path keeps its exact cold-start behavior.
    _ADAPT_PRIORS: dict[tuple, tuple] = {}

    def __init__(self, config: Optional[EngineConfig] = None):
        self.cfg = config or EngineConfig()
        if self.cfg.quantized and self.cfg.model != "transformer":
            # same refuse-don't-silently-serve stance as quantized+mesh:
            # only the transformer has an int8 path
            raise ValueError(
                f"quantized serving is only implemented for the "
                f"transformer model, not {self.cfg.model!r}")
        if self.cfg.model not in _BACKENDS:
            raise ValueError(
                f"unknown scoring model {self.cfg.model!r} "
                f"(known: {sorted(_BACKENDS)})")
        # the engine owns THE mesh (ISSUE 7: one mesh, one owner) —
        # backends receive it, never build their own. Construction is
        # gated to sequence models so mock/zscore engines stay jax-free,
        # and jax.devices() honors the virtual-host-platform override
        # (XLA_FLAGS --xla_force_host_platform_device_count) so the
        # dp×tp path runs under tier-1 CPU without real TPUs.
        self.mesh = None
        if self.cfg.mesh is not None and self.cfg.model in (
                "transformer", "autoencoder"):
            from ..parallel import make_mesh

            try:
                self.mesh = make_mesh(self.cfg.mesh_shape())
            except ValueError:
                # a mesh the host cannot back (configs render per
                # cluster, pods differ — a devices:4 gateway config can
                # land on a 1-device pod): serve single-device LOUDLY
                # instead of bricking the collector on upgrade. The
                # pre-mesh code silently dropped the knob; the counter
                # makes the degradation observable.
                meter.add(labeled_key(MESH_UNAVAILABLE_METRIC,
                                      model=self.cfg.model))
        self.backend = _BACKENDS[self.cfg.model](self.cfg, mesh=self.mesh)
        # failover supervisor (ISSUE 13): circuit breaker over the
        # dispatch/harvest error path with a fallback backend — a
        # persistent fault of the primary degrades to zscore scoring
        # instead of forwarding every frame unscored forever. The
        # fallback is NOT a CPU route: zscore is a jitted JAX kernel and
        # runs on this process's default device, which on a TPU host is
        # the same chip. It outlives a fault of the primary's PROGRAM (a
        # compile refusal, a poisoned executable, a wedged mesh), not
        # the loss of the device. The supervisor never imports this
        # module; the engine constructs the fallback and hands both
        # backends in.
        self.failover = None
        # chaos hook (e2e/chaos.py inject_device_fault): a non-None
        # message makes every PRIMARY-backend dispatch raise — the
        # deterministic stand-in for a dead device that the failover
        # breaker (and the sustained-failure tests) exercise
        self._device_fault: Optional[str] = None
        # text of the most recent dispatch/harvest failure (see
        # _note_error); None while every call has succeeded
        self.last_error: Optional[str] = None
        if self.cfg.failover is not None:
            from .failover import FailoverConfig, FailoverSupervisor

            if self.cfg.model == "remote":
                # the sidecar featurizes server-side (needs_features is
                # False), so submit never builds the features a local
                # fallback would score — and the sidecar carries its
                # own deadline discipline anyway
                raise ValueError(
                    "failover does not compose with the remote sidecar "
                    "backend")
            fo_cfg = FailoverConfig.from_spec(self.cfg.failover_spec())
            fb_cfg = EngineConfig(
                model=fo_cfg.fallback_model,
                max_batch_spans=self.cfg.max_batch_spans,
                max_len=self.cfg.max_len,
                trace_bucket=self.cfg.trace_bucket,
                online_update=self.cfg.online_update,
                featurizer=self.cfg.featurizer,
                seed=self.cfg.seed)
            fallback = _BACKENDS[fo_cfg.fallback_model](fb_cfg, mesh=None)
            self.failover = FailoverSupervisor(
                self.cfg.model, self.backend, fallback, fo_cfg)
        # only backends with an async dispatch can overlap; everything else
        # (zscore's ordered online update, mock, the remote sidecar with its
        # own deadline discipline) keeps the exact serial depth-1 behavior
        self._depth = max(1, self.cfg.pipeline_depth) \
            if callable(getattr(self.backend, "dispatch", None)) else 1
        self._queue: queue.Queue[ScoreRequest] = queue.Queue(self.cfg.max_queue)
        # the request taken off the queue that the last call had no room
        # for (queue.Queue cannot be peeked): at most one, it leads the
        # next call. A deque for its atomic append/popleft: shutdown()
        # takes it from another thread when the worker is gone.
        self._held: deque[ScoreRequest] = deque()
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # serializes backend access between the worker and warmup(): a
        # stateful backend (zscore online updates) hit from both threads at
        # once loses updates — warmup's 400-trace fit silently overwritten
        # by a concurrent tiny scoring update leaves the detector cold (the
        # long-standing e2e spike-test flake). Worker-internal only; it
        # never serializes dispatch against harvest across calls, so the
        # host/device overlap is untouched.
        self._backend_lock = threading.Lock()
        # calls retired (the device_calls gauge) and calls dispatched:
        # the latter is the next call's serial, the worker's alone
        self._device_calls = 0
        self._call_serial = 0
        # pipeline observability: per-call stage timings (bounded ring) and
        # a union accumulator of device in-flight intervals for the
        # device_busy_frac the bench reports
        self._stage_log: deque[dict[str, Any]] = deque(maxlen=512)
        self._busy_ns = 0
        self._busy_until = 0
        self._t_run0: Optional[int] = None
        # in-flight window occupancy, mirrored from the worker's local
        # deque (int store is atomic) so the device-runtime collector can
        # sample it without touching worker state
        self._inflight_count = 0
        # deadline-based adaptive batching: observed device-step cost
        # sizes the next coalesced call so harvest lands inside the
        # oldest request's deadline; the ladder keeps the resulting row
        # counts on precompiled shapes. The per-span rate is a RATIO OF
        # AVERAGES (EWMA of call ms over EWMA of call spans): device
        # calls carry a fixed dispatch cost, so averaging per-call
        # ratios would let one small call (warmup, a lone probe) read as
        # a catastrophic per-span cost and collapse the cap. None until
        # the first call retires — no estimate means no adaptive cap.
        self._ewma_call_ms: Optional[float] = None
        self._ewma_call_spans: Optional[float] = None
        # spans per REAL packed row (the rows the packer filled, before
        # the ladder padded them) and its mean deviation: what converts
        # a span budget to rows on a rung (_spans_per_row)
        self._ewma_spans_per_row: Optional[float] = None
        self._ewma_spans_per_row_dev = 0.0
        self._ewma_harvest_ms = 0.0
        self._last_adaptive_cap: Optional[int] = None
        # what a call of each rung costs the device, by padded rows: the
        # time a retired call had the device to itself (_retire_inner)
        self._rung_ms: dict[int, float] = {}
        # why the call just collected was closed (drained, rung, cap)
        # and the rung it was closed for (None: no rung to fill): the
        # pack that comes out past that rung is a spill (worker-owned)
        self._closed: tuple[str, Optional[int]] = ("drained", None)
        self._closed_keys = {
            reason: labeled_key(COALESCE_CLOSED_METRIC, reason=reason)
            for reason in ("drained", "rung", "cap")}
        # what packing and enqueueing a call takes the host (pack_ms of
        # the retired calls) and its mean deviation: the lead a call is
        # committed ahead of the expected end of the one that runs
        # (_lead_ms). None until a call retired: nothing to lead by
        self._ewma_pack_ms: Optional[float] = None
        self._ewma_pack_ms_dev = 0.0
        # when the call just collected was committed, how long it was
        # held open for that, and the expected end of the call ahead
        # (worker-owned, like _closed)
        self._commit: tuple[str, float, Optional[int]] = ("idle", 0.0, None)
        self._commit_keys = {
            when: labeled_key(COMMIT_METRIC, when=when)
            for when in ("idle", "filled", "due", "blind")}
        # per-mesh step-cost learning (ISSUE 7 tentpole d): the estimate
        # is keyed by (model, mesh) so deadline-sized coalescing scales
        # with device count instead of assuming one chip — an 8-device
        # mesh retires spans ~8x cheaper and the cap grows to match; a
        # fresh engine on a known mesh shape seeds from the registry.
        # Keyed off the mesh the engine ACTUALLY built (self.mesh), not
        # the configured spec — a host-unbackable mesh degraded to
        # single-device and must not wear multi-chip labels or priors.
        # The key includes the model GEOMETRY: a blue/green swap to a
        # bigger model on the same mesh must not seed the small model's
        # per-span cost and oversize its first deadline-bounded calls.
        # An unhashable model_config opts out of the registry entirely.
        self._mesh_label = _mesh_label(self.cfg.mesh) \
            if self.mesh is not None else "single"
        try:
            self._adapt_key: Optional[tuple] = (
                self.cfg.model, self.cfg.model_config, self.cfg.mesh)
            hash(self._adapt_key)
        except TypeError:
            self._adapt_key = None
        if self.mesh is not None and self._adapt_key is not None:
            prior = ScoringEngine._ADAPT_PRIORS.get(self._adapt_key)
            if prior is not None:
                (self._ewma_call_ms, self._ewma_call_spans,
                 self._ewma_spans_per_row, self._ewma_spans_per_row_dev,
                 self._ewma_harvest_ms) = prior
        if self.mesh is not None:
            self._adaptive_gauge_key = labeled_key(
                ADAPTIVE_CAP_GAUGE, model=self.cfg.model,
                mesh=self._mesh_label)
        else:
            self._adaptive_gauge_key = labeled_key(
                ADAPTIVE_CAP_GAUGE, model=self.cfg.model)
        # pack-stage buffer pool (ISSUE 12): the worker's coalesce/pack
        # tensors recycle call to call instead of re-allocating — one
        # pool, one worker thread, so checkouts never contend
        self._pack_pool = BufferPool(f"engine/{self.cfg.model}")

    # ----------------------------------------------------------- lifecycle
    def start(self) -> "ScoringEngine":
        if self._thread is None or not self._thread.is_alive():
            if self.cfg.warm_ladder:
                w = getattr(self.backend, "warm", None)
                if w is not None:
                    w()  # blocking by design: caller opted into warm start
                if self.failover is not None:
                    # the fallback must be warm BEFORE it is needed: its
                    # first groups otherwise pay per-shape XLA compiles
                    # in the middle of the device-loss incident the
                    # breaker exists to smooth over
                    fw = getattr(self.failover.fallback, "warm", None)
                    if fw is not None:
                        fw()
            # per-run stop event: a worker that outlived a timed-out
            # shutdown() join (hung device call) keeps ITS event set and
            # exits when the call unwedges — clearing a shared event
            # would resurrect it alongside the new worker (two workers
            # popping one queue, interleaved online updates)
            stop = threading.Event()
            self._stop = stop
            self._thread = threading.Thread(
                target=self._worker, args=(stop,),
                name="scoring-engine", daemon=True)
            self._thread.start()
            # visible to the device-runtime collector from now on (weak
            # registration: a dropped engine unregisters itself)
            _engine_registry.register(self)
        return self

    def shutdown(self) -> None:
        _engine_registry.unregister(self)
        self._stop.set()
        if self._thread is not None:
            # the worker drains queued + in-flight work losslessly first
            self._thread.join(timeout=30.0)
            self._thread = None
        # fail-fast any request that raced past submit()'s stop check after
        # the worker's final queue-empty observation (TOCTOU): its done
        # event must still fire or a score_sync caller eats the full
        # deadline for a request nothing will ever score. The request a
        # dead (or wedged) worker left held goes the same way, first
        while True:
            req = self._take(block=False)
            if req is None:
                break
            req.release_features()  # never dispatched: nothing read them
            req.scores = None
            req.signal_done()
            FlowContext.drop(len(req.batch), "shutdown_drain",
                             pipeline="(engine)",
                             component_name=f"engine/{self.cfg.model}",
                             signal="requests")

    # ------------------------------------------------------------- scoring
    def submit(self, batch: SpanBatch,
               features: Optional[SpanFeatures] = None,
               deadline_ns: Optional[int] = None,
               on_done: Optional[Callable[[ScoreRequest], None]] = None,
               on_features_consumed: Optional[Callable[[], None]] = None,
               columns: Any = None,
               ) -> Optional[ScoreRequest]:
        """Enqueue for scoring; returns None (and counts) if queue is full
        or the engine is draining for shutdown. ``deadline_ns`` (monotonic)
        opts the request into deadline-based adaptive batching: the pack
        stage caps the coalesced call so its harvest lands inside the
        earliest deadline instead of letting batch growth blow p99.
        ``on_done`` is the completion callback (see ScoreRequest): called
        the instant the request resolves, so a caller never polls."""
        if self._stop.is_set():
            # shutting down: the worker is draining; new work would race
            # the lossless-drain guarantee
            meter.add(QUEUE_FULL_METRIC)
            # a shed score REQUEST, not a span loss: the batch passes
            # through unscored, so this rides the "requests" signal in
            # the ledger (never a pipeline conservation term)
            FlowContext.drop(len(batch), "shutdown_drain",
                             pipeline="(engine)",
                             component_name=f"engine/{self.cfg.model}",
                             signal="requests")
            return None
        if features is None and columns is None \
                and getattr(self.backend, "needs_features", True):
            # a remote backend ships the raw batch and the sidecar
            # featurizes server-side; featurizing here too would pay the
            # host cost twice against the latency budget. A columns-
            # carrying request (fused route) defers featurization to the
            # pack stage — device-side when the group fuses, the same
            # host featurize otherwise.
            features = featurize(batch, self.cfg.featurizer)
        req = ScoreRequest(batch=batch, features=features, columns=columns,
                           submitted_ns=time.monotonic_ns(),
                           deadline_ns=deadline_ns, on_done=on_done,
                           on_features_consumed=on_features_consumed)
        try:
            self._queue.put_nowait(req)
        except queue.Full:
            meter.add(QUEUE_FULL_METRIC)
            # deadline-carrying requests died waiting for queue space:
            # the burn blame dimension names the stage (never a new
            # reason); legacy submits keep their exact metric key
            FlowContext.drop(len(batch), "queue_full",
                             pipeline="(engine)",
                             component_name=f"engine/{self.cfg.model}",
                             signal="requests",
                             blame="queue" if deadline_ns is not None
                             else None)
            return None
        FlowContext.watermark(f"engine/{self.cfg.model}", "queue_depth",
                              self._queued())
        return req

    def score_sync(self, batch: SpanBatch,
                   features: Optional[SpanFeatures] = None,
                   timeout_s: float = 0.005) -> Optional[np.ndarray]:
        """Submit and wait up to the latency budget; None => pass through."""
        req = self.submit(batch, features)
        if req is None:
            return None
        if req.done.wait(timeout_s):
            return req.scores
        meter.add(PASSTHROUGH_METRIC, len(batch))
        return None

    def warmup(self, batch: SpanBatch) -> None:
        """Feed presumed-normal traffic to streaming backends; also triggers
        jit compilation of the scoring path so first real batch is fast.
        Runs under the backend lock: a worker scoring concurrent traffic
        must not interleave with the warm-fit (lost-update race on
        streaming state)."""
        with self._backend_lock:
            w = getattr(self.backend, "warmup", None)
            if w is not None:
                w(batch)
            feats = featurize(batch, self.cfg.featurizer)
            self.backend.score(batch, feats)

    def pack_pool_stats(self) -> dict[str, Any]:
        """The pack-stage buffer pool's counters (ISSUE 12) — the
        public surface the soak/bench allocation evidence reads."""
        return self._pack_pool.stats()

    # ------------------------------------------------------- chaos hooks
    def inject_device_fault(
            self, message: str = "injected device fault") -> None:
        """Chaos hook (e2e/chaos.py, ISSUE 13): every subsequent
        PRIMARY-backend dispatch raises :class:`DeviceFaultInjected`
        until cleared — the deterministic device-loss injection the
        failover breaker and the sustained-failure tests drive. The
        fallback route (when a breaker is configured) is untouched."""
        self._device_fault = str(message)

    def clear_device_fault(self) -> None:
        """Lift the injected device fault (idempotent)."""
        self._device_fault = None

    def failover_status(self) -> Optional[dict[str, Any]]:
        """The breaker's state snapshot (None = no breaker configured)
        — surfaced in pipeline_stats."""
        return self.failover.status() if self.failover is not None \
            else None

    def runtime_gauges(self) -> dict[str, Any]:
        """Instantaneous engine state for the device-runtime collector
        (ISSUE 3): the gauges the pipeline always computed but never
        published — sampled, not accumulated, so the collector can poll
        at its own cadence without touching worker internals."""
        inflight = self._inflight_count
        now = time.monotonic_ns()
        wall = (now - self._t_run0) if self._t_run0 else 0
        out: dict[str, Any] = {
            "model": self.cfg.model,
            "queue_depth": self._queued(),
            "inflight": inflight,
            "window_occupancy": round(inflight / self._depth, 4),
            "pipeline_depth": self._depth,
            "device_calls": self._device_calls,
            "device_busy_frac": round(min(self._busy_ns / wall, 1.0), 4)
            if wall else 0.0,
        }
        if self.mesh is not None:
            # padding_waste_frac / bucket_ladder_hit_rate become per-mesh
            # gauges: the collector lifts this into a {mesh=} label
            out["mesh"] = self._mesh_label
        waste = getattr(self.backend, "last_padding_waste", None)
        if waste is not None:
            out["padding_waste_frac"] = waste
        ladder = getattr(self.backend, "ladder", None)
        if ladder is not None:
            out["bucket_ladder_hit_rate"] = ladder.stats()["hit_rate"]
        return out

    def pipeline_stats(self) -> dict[str, Any]:
        """Pipeline observability snapshot: per-stage percentiles, depth
        and overlap of the device calls in flight."""
        log = list(self._stage_log)

        def pcts(key: str) -> dict[str, float]:
            vals = [c[key] for c in log]
            if not vals:
                return {"p50": 0.0, "p99": 0.0}
            return {"p50": round(float(np.percentile(vals, 50)), 3),
                    "p99": round(float(np.percentile(vals, 99)), 3)}

        wall = (time.monotonic_ns() - self._t_run0) if self._t_run0 else 0
        out: dict[str, Any] = {
            "pipeline_depth": self._depth,
            "device_calls": self._device_calls,
            "device_busy_frac": round(self._busy_ns / wall, 4) if wall
            else 0.0,
            "overlap_ms_total": round(
                sum(c["overlap_ms"] for c in log), 3),
            "stage_pack_ms": pcts("pack_ms"),
            "stage_device_ms": pcts("device_ms"),
            "stage_harvest_ms": pcts("harvest_ms"),
        }
        ladder = getattr(self.backend, "ladder", None)
        if ladder is not None:
            out["bucket_ladder"] = ladder.stats()
        out["adaptive"] = {
            "ms_per_span": self._ms_per_span(),
            "spans_per_row": self._ewma_spans_per_row,
            "spans_per_row_dev": round(self._ewma_spans_per_row_dev, 4),
            "rung_ms": {r: round(ms, 3)
                        for r, ms in sorted(self._rung_ms.items())},
            "harvest_ms": round(self._ewma_harvest_ms, 4),
            "pack_ms": self._ewma_pack_ms,
            "lead_ms": self._lead_ms(),
            "last_cap_spans": self._last_adaptive_cap,
            "mesh": self._mesh_label,
        }
        if self.mesh is not None:
            out["mesh"] = dict(self.cfg.mesh)
        if self.failover is not None:
            out["failover"] = self.failover.status()
        if self.last_error is not None:
            out["last_error"] = self.last_error
        return out

    # -------------------------------------------------------------- worker
    def _worker(self, stop: threading.Event) -> None:
        """Two-stage pipelined loop: fill the in-flight window (pack +
        dispatch) ahead of harvesting, retire FIFO. With nothing in
        flight a call is committed on its first request (the engine is
        work-conserving, and an idle one adds no latency). While a call
        runs, the next one is held open until the running call is about
        to end and committed then (``_collect``): the chip executes
        calls one after another, so an earlier commit only decides which
        requests miss the call. With an empty queue at that point the
        window drains; on stop the queue and window drain losslessly and
        no call is held open. ``stop`` is THIS run's event (see
        start()): a zombie run never consults the replacement's."""
        name_thread("odigos-engine")
        inflight: deque[_InflightGroup] = deque()
        while True:
            stopping = stop.is_set()
            if stopping and not inflight and not self._queued():
                return
            # keep-serving backstop: _dispatch_group/_retire fail their own
            # requests on error, but nothing outside those narrow trys may
            # kill this thread — a dead worker turns every future submit
            # into a silent full-deadline pass-through
            try:
                if len(inflight) < self._depth:
                    with annotate("engine/collect") as collecting:
                        reqs = self._collect(
                            block=not inflight and not stopping,
                            ahead=inflight[0] if inflight else None,
                            stop=stop)
                        collecting.set(queued=len(reqs) if reqs else 0,
                                       held_ms=round(self._commit[1], 3))
                    if reqs is not None:
                        grp = self._dispatch_group(reqs,
                                                   overlapped=bool(inflight))
                        if grp is not None:
                            inflight.append(grp)
                            self._inflight_count = len(inflight)
                        continue
                if inflight:
                    grp = inflight.popleft()
                    self._inflight_count = len(inflight)
                    self._retire(grp)
            except Exception as e:
                self._note_error("worker", e)

    def _note_error(self, stage: str, exc: BaseException) -> None:
        """A scoring call failed. The frames forward unscored — that is
        the contract — so the counter alone makes a compile refusal or a
        dead buffer look like a healthy collector: say what was raised.
        A failure mode is logged when it first appears and again
        whenever it changes, not once per frame."""
        meter.add("odigos_anomaly_engine_errors_total")
        text = f"{stage}: {type(exc).__name__}: {exc}"
        if text != self.last_error:
            self.last_error = text
            _log.error("engine/%s %s (frames forward unscored)",
                       self.cfg.model, text)

    def _queued(self) -> int:
        """Requests waiting for a call: the queue and the held one."""
        return self._queue.qsize() + len(self._held)

    def _take(self, block: bool) -> Optional[ScoreRequest]:
        """The next request in arrival order: the held one, else the
        queue's (waiting briefly when ``block``), else None."""
        try:
            return self._held.popleft()
        except IndexError:
            pass
        try:
            if block:
                return self._queue.get(timeout=0.05)
            return self._queue.get_nowait()
        except queue.Empty:
            return None

    def _collect(self, block: bool,
                 ahead: Optional[_InflightGroup] = None,
                 stop: Optional[threading.Event] = None,
                 ) -> Optional[list[ScoreRequest]]:
        """Pack-stage intake: one request (blocking briefly only when the
        pipeline is idle) plus whatever else is already waiting, up to
        the call's budget (``_budget``). Where the backend has a ladder
        the budget is rows on a rung and is never overshot: the request
        whose rows would spill past it is held and leads the next call,
        and a call grows from one rung into the next only where that
        scores more spans per device millisecond (``_climb_pays``).
        Backends without a ladder have no rung to fill: their budget is
        in spans and the request that reaches it closes the call. A
        laddered backend that has reported no real rows yet is budgeted
        in spans too, and holds the request that would pass the cap.

        Late commit: while ``ahead``, the call that runs on the device,
        is not about to end, a call that what waits does not fill stays
        open for what arrives, up to the commit point: the expected end
        of ``ahead`` (``_expected_end``) less the lead that packing and
        enqueueing take (``_lead_ms``). The call is committed at once
        wherever waiting can gain nothing or could cost, and the span
        says which it was (``commit.when``, one count each):

        * ``idle``: nothing is in flight, or ``ahead`` was seen to have
          landed already: the device is free;
        * ``filled``: what waited closed the call for ``rung`` or
          ``cap``: a backlog is dispatched as greedily as ever;
        * ``blind``: no observed cost to expect the end of ``ahead``
          from (a cold engine, a backend without a ladder, a rung not
          timed yet), or the worker is stopping;
        * ``due``: held to the commit point (which may have passed)."""
        end = None
        if ahead is None:
            when = "idle"
        elif stop is not None and stop.is_set():
            when = "blind"
        else:
            end = self._expected_end(ahead)
            when = "blind" if end is None else "due"
        due = end - int(self._lead_ms() * 1e6) if end is not None else None
        landed = getattr(ahead.backend, "ready", None) \
            if due is not None else None

        def more() -> Optional[ScoreRequest]:
            """The next request for this call: one that waits, else,
            while the call is held open, the one that arrives before
            the commit point."""
            nonlocal when, due
            while True:
                nxt = self._take(block=False)
                if nxt is not None or due is None:
                    return nxt
                left = due - time.monotonic_ns()
                if left > 0:
                    if stop is not None and stop.is_set():
                        when = "blind"
                    elif landed is not None and landed(ahead.handle):
                        when = "idle"
                    else:
                        try:
                            return self._queue.get(
                                timeout=min(left / 1e9, _HOLD_SLICE_S))
                        except queue.Empty:
                            continue
                due = None
                return None

        first = self._take(block)
        if first is None:
            first = more()
        if first is None:
            self._commit = (when, 0.0, end)
            return None
        t_first = time.monotonic_ns()
        reqs = [first]
        total = len(first.batch)
        cap, row_cap = self._budget(first.deadline_ns)
        if first.deadline_ns is not None:
            self._last_adaptive_cap = cap
            meter.set_gauge(self._adaptive_gauge_key, cap)
        reason, rung = "drained", None
        if row_cap is None:
            laddered = getattr(self.backend, "ladder", None) is not None
            while total < cap:
                nxt = more()
                if nxt is None:
                    break
                if laddered and total + len(nxt.batch) > cap:
                    # a ladder, and no rows learned to budget by (a cold
                    # engine's first calls): the span cap is all that
                    # keeps the call on a rung that was warmed, so it is
                    # not overshot either
                    self._held.append(nxt)
                    reason = "cap"
                    break
                reqs.append(nxt)
                total += len(nxt.batch)
            else:
                reason = "cap"
        else:
            ladder = self.backend.ladder
            per_row = self._spans_per_row()
            rung = ladder.round_rows(math.ceil(total / per_row))
            while True:
                nxt = more()
                if nxt is None:
                    break
                grown = total + len(nxt.batch)
                rows = math.ceil(grown / per_row)
                if grown > self.cfg.max_batch_spans:
                    reason = "cap"
                elif rows > row_cap or (
                        rows > rung and not self._climb_pays(
                            rung, row_cap, total, grown, len(reqs) + 1)):
                    reason = "rung"
                else:
                    reqs.append(nxt)
                    total, rung = grown, ladder.round_rows(rows)
                    continue
                self._held.append(nxt)
                break
        if reason != "drained" and end is not None:
            when = "filled"
        self._closed = (reason, rung)
        self._commit = (when, (time.monotonic_ns() - t_first) / 1e6, end)
        meter.add(self._closed_keys[reason])
        meter.add(self._commit_keys[when])
        # re-report the drained depth: watermark consumers (the wire
        # receiver's admission gate) read the CURRENT value — leaving the
        # submit-time high reading in place would keep shedding traffic
        # long after the queue emptied
        FlowContext.watermark(f"engine/{self.cfg.model}", "queue_depth",
                              self._queued())
        return reqs

    def _expected_end(self, grp: _InflightGroup) -> Optional[int]:
        """When the call that runs is expected to end (monotonic ns):
        from when it had the device to itself (its dispatch, or the
        retirement of the call ahead of it where that came later: what
        ``_retire_inner`` times a rung from), what its rung was observed
        to cost. None where nothing was observed to expect it from: no
        call retired yet, a backend without a ladder, a rung whose
        calls so far were its compile."""
        cost = self._rung_ms.get(grp.shape[0]) if grp.shape else None
        if cost is None or self._ewma_pack_ms is None:
            return None
        return max(grp.t_dispatch, self._busy_until) + int(cost * 1e6)

    def _lead_ms(self) -> Optional[float]:
        """Milliseconds ahead of the running call's expected end at
        which the next call is committed (None until a call retired):
        what packing and enqueueing a call took, the mean plus
        ``_MARGIN_DEVS`` mean deviations. Too long a lead costs what an
        early commit costs (what arrives inside it waits a step); too
        short a one leaves the chip idle for the difference."""
        mean = self._ewma_pack_ms
        if mean is None:
            return None
        return mean + _MARGIN_DEVS * self._ewma_pack_ms_dev

    def _climb_pays(self, rung: int, row_cap: int, total: int,
                    grown: int, taken: int) -> bool:
        """Whether a call that fills ``rung`` with ``total`` spans should
        grow into a higher one: yes where some rung the budget allows,
        filled with what is waiting, scores more spans per device
        millisecond than closing here does. What waits is ``grown`` (the
        call and the request in hand, ``taken`` requests) plus the queue
        counted in requests of that mean size."""
        ladder = self.backend.ladder
        per_row = self._spans_per_row()
        waiting = min(grown + self._queue.qsize() * grown / taken,
                      self.cfg.max_batch_spans)
        stay = total / self._rung_cost(rung)
        return any(
            min(waiting, b * per_row) / self._rung_cost(b) >= stay
            for b in ladder.buckets if rung < b <= row_cap)

    def _rung_cost(self, rows: int) -> float:
        """Device milliseconds of a call of ``rows`` padded rows, as the
        engine observed that rung; a rung not dispatched yet costs by its
        rows from the nearest one that was (no rung seen: rows)."""
        seen = self._rung_ms
        if rows in seen:
            return seen[rows]
        if not seen:
            return float(rows)
        near = min(seen, key=lambda r: abs(r - rows))
        return seen[near] * rows / near

    def _spans_per_row(self) -> Optional[float]:
        """Spans a packed row is counted on to hold (None until a call
        with real rows retired): the mean less ``_MARGIN_DEVS`` mean
        deviations, and never under the one span a real row has."""
        mean = self._ewma_spans_per_row
        if not mean:
            return None
        return max(1.0, mean
                   - _MARGIN_DEVS * self._ewma_spans_per_row_dev)

    def _budget(self, deadline_ns: Optional[int]
                ) -> tuple[int, Optional[int]]:
        """What one coalesced call may hold, as (spans, rows). Spans: the
        smaller of ``max_batch_spans`` and, under a deadline, what its
        headroom affords at the observed per-span device-step cost. Rows:
        that span budget over the spans a real packed row holds, snapped
        DOWN onto a shape the ladder serves (never up into a recompile),
        and the spans are then that rung's; None where the backend has no
        ladder or no call with real rows has retired yet."""
        spans = self.cfg.max_batch_spans
        if deadline_ns is not None:
            spans = min(spans, self._afford(deadline_ns))
        ladder = getattr(self.backend, "ladder", None)
        per_row = self._spans_per_row()
        if ladder is None or per_row is None:
            return max(1, spans), None
        rows = ladder.floor_rows(spans / per_row)
        return max(1, min(int(rows * per_row),
                          self.cfg.max_batch_spans)), rows

    def _adaptive_cap(self, deadline_ns: int) -> int:
        """Span budget for one coalesced call such that its harvest is
        expected inside ``deadline_ns`` (``_budget``'s spans)."""
        return self._budget(deadline_ns)[0]

    def _afford(self, deadline_ns: int) -> int:
        """Spans whose call is expected to be harvested inside
        ``deadline_ns``: remaining headroom over the observed per-span
        device-step cost. With no estimate yet (cold engine) the fixed
        cap applies."""
        per_span = self._ms_per_span()
        if per_span is None or per_span <= 0:
            return self.cfg.max_batch_spans
        headroom_ms = ((deadline_ns - time.monotonic_ns()) / 1e6
                       - self._ewma_harvest_ms)
        if headroom_ms <= 0:
            # already late: queue wait ate the deadline, so per-request
            # latency is lost either way — switch to DRAIN mode (maximal
            # coalescing) to clear the backlog at peak device efficiency;
            # shipping minimal calls here would shrink batches exactly
            # when load demands growth and collapse throughput
            return self.cfg.max_batch_spans
        return int(headroom_ms / per_span)

    def _ms_per_span(self) -> Optional[float]:
        """Volume-weighted device-step cost per span (see __init__)."""
        if not self._ewma_call_ms or not self._ewma_call_spans:
            return None
        return self._ewma_call_ms / self._ewma_call_spans

    def _dispatch_group(self, reqs: list[ScoreRequest],
                        overlapped: bool) -> Optional[_InflightGroup]:
        """Pack stage: coalesce, featurize-if-needed, pack, and enqueue the
        device call without blocking on its result. When ``overlapped``,
        every host millisecond spent here ran concurrently with the
        previous in-flight device call — that is the pipelining win."""
        t0 = time.monotonic_ns()
        if self._t_run0 is None:
            self._t_run0 = t0
        call = self._call_serial
        self._call_serial = call + 1
        # failover (ISSUE 13): the breaker picks the backend PER GROUP —
        # primary while closed, the fallback while tripped, and one
        # half-open probe group per interval while recovering
        if self.failover is not None:
            backend, probe = self.failover.select()
        else:
            backend, probe = self.backend, False
        # scoring exported self-spans (a pipeline dogfooding anomaly
        # detection on internal traces) must not mint new spans about
        # them — the worker thread is outside the suppressed() scope,
        # so the batch marker is the only signal that survives the hop
        span = (NULL_SPAN
                if any(is_selftelemetry_batch(r.batch) for r in reqs)
                else tracer.span("tpu/score")).begin()
        # every tensor the pack stage builds (feature concat, packed/
        # assembled sequences inside backend.dispatch) checks out of the
        # worker's buffer pool; the lease rides the in-flight group and
        # releases after harvest — steady state packs allocation-free
        lease = self._pack_pool.lease() if pools_enabled() else None
        attrib = None
        span_bucket = None
        cold_dispatch_s = 0.0
        real_rows = None
        try:
            with lease_scope(lease), \
                    annotate("engine/pack", call=call) as packing:
                if self._device_fault is not None \
                        and backend is self.backend:
                    # injected device loss (chaos hook): only the
                    # PRIMARY route faults — the fallback must keep
                    # scoring or there is nothing to fail over TO
                    raise DeviceFaultInjected(self._device_fault)
                # fused route (ISSUE 19): a whole group of columns-
                # carrying requests on a backend with a fused kernel
                # scores in one featurize→pack→score device call. The
                # decision is per group AND per selected backend: a
                # failover trip to the zscore fallback (no fused kernel)
                # converts the same requests on the host path below.
                fused = (getattr(backend, "supports_fused", False)
                         and all(r.columns is not None for r in reqs))
                if fused:
                    with self._backend_lock:
                        t_f0 = time.monotonic()
                        handle = backend.dispatch_columns(
                            [r.columns for r in reqs])
                        t_f1 = time.monotonic()
                        bucket_hit = getattr(backend, "last_bucket_hit",
                                             None)
                        shape = getattr(backend, "last_shape", None)
                        waste = getattr(backend, "last_padding_waste",
                                        None)
                        attrib = getattr(backend, "last_attrib", None)
                        span_bucket = getattr(backend,
                                              "last_span_bucket", None)
                    # a bucket-miss dispatch wall is (almost entirely)
                    # the fused jit compiling for the new shape — a
                    # compile event once this group's trace id is known
                    if bucket_hit is False:
                        cold_dispatch_s = t_f1 - t_f0
                else:
                    for r in reqs:
                        if r.features is None and r.columns is not None \
                                and getattr(backend, "needs_features",
                                            True):
                            # columns-carrying request on a non-fused
                            # call: the bit-exact host featurize the
                            # submit lane deferred (fallback ladder)
                            r.features = featurize(r.batch,
                                                   self.cfg.featurizer)
                    if len(reqs) == 1:
                        merged, feats = reqs[0].batch, reqs[0].features
                    else:
                        feats = None
                        if all(r.features is not None for r in reqs):
                            cats = [r.features.categorical for r in reqs]
                            conts = [r.features.continuous for r in reqs]
                            rows = sum(c.shape[0] for c in cats)
                            feats = SpanFeatures(
                                np.concatenate(cats, out=_pool_alloc(
                                    (rows, cats[0].shape[1]),
                                    cats[0].dtype)),
                                np.concatenate(conts, out=_pool_alloc(
                                    (rows, conts[0].shape[1]),
                                    conts[0].dtype)))
                        if feats is not None and getattr(
                                backend, "coalesce_columns",
                                None) is not None:
                            # every request pre-featurized + a backend
                            # that only reads id/time columns: skip the
                            # merged batch — the ingest fast path's
                            # zero-rematerialization seam
                            merged: Any = _ColumnBatch(
                                [r.batch for r in reqs])
                        else:
                            from ..pdata.spans import concat_batches

                            merged = concat_batches(
                                [r.batch for r in reqs])
                    pack = getattr(backend, "pack", None)
                    with self._backend_lock:
                        if pack is not None:
                            staged = pack(merged, feats)
                            # the device call is engine/enqueue's
                            packing.close()
                            handle = backend.enqueue(staged, call)
                        else:
                            # depth-1 backend: the whole call happens
                            # here, eagerly — identical to the serial
                            # engine (ordering guarantees for zscore
                            # online updates and the remote sidecar
                            # deadline)
                            handle = backend.score(merged, feats)
                        # snapshot while still holding the lock: a
                        # concurrent warmup() score would overwrite the
                        # last_* fields with the warmup call's shape
                        # before we read them
                        bucket_hit = getattr(backend, "last_bucket_hit",
                                             None)
                        shape = getattr(backend, "last_shape", None)
                        waste = getattr(backend, "last_padding_waste",
                                        None)
                        real_rows = getattr(backend, "last_real_rows",
                                            None)
        except Exception as e:
            self._note_error("dispatch", e)
            if self.failover is not None:
                self.failover.observe(
                    backend, ok=False,
                    n_spans=sum(len(r.batch) for r in reqs),
                    error=f"{type(e).__name__}: {e}", probe=probe)
            if lease is not None:
                lease.release()
            for r in reqs:
                r.release_features()
                r.scores = None
                r.signal_done()
            span.set_attr("error", True)
            span.finish(error=True)
            return None
        # the pack/score call has consumed every request's features
        # (copied into packed/coalesced tensors or scored outright):
        # release the callers' featurize buffers NOW, while the scores
        # are still in flight — holding them to retirement was measured
        # as the pool's residual steady-state misses (depth jitter)
        for r in reqs:
            r.release_features()
        closed, rung = self._closed
        commit, held_ms, ahead_end_ns = self._commit
        spilled = bool(real_rows is not None and rung is not None
                       and shape and shape[0] > rung)
        if spilled:
            # closed for one rung, packed past it: the call runs the
            # next rung up, mostly padding
            meter.add(RUNG_SPILL_METRIC)
        t1 = time.monotonic_ns()
        for r in reqs:
            # expiry blame marker (ISSUE 8): a deadline that dies after
            # this point blames the device, before it blames the queue
            r.dispatched_ns = t1
        return _InflightGroup(
            reqs=reqs, handle=handle, span=span,
            n_spans=sum(len(r.batch) for r in reqs),
            t_pack0=t0, t_dispatch=t1,
            overlap_ms=(t1 - t0) / 1e6 if overlapped else 0.0,
            bucket_hit=bucket_hit, shape=shape, padding_waste=waste,
            lease=lease, backend=backend, probe=probe, fused=fused,
            attrib=attrib, span_bucket=span_bucket,
            cold_dispatch_s=cold_dispatch_s, call=call,
            real_rows=real_rows, closed=closed, spilled=spilled,
            commit=commit, held_ms=held_ms, ahead_end_ns=ahead_end_ns)

    def _retire(self, grp: _InflightGroup) -> None:
        """Harvest stage: block on the oldest in-flight device call, split
        scores per request (FIFO — byte-identical to the serial path), set
        events, and account stage timings."""
        try:
            self._retire_inner(grp)
        finally:
            # pack buffers recycle only AFTER the blocking harvest fetch
            # (or its failure path): the device call has fully consumed
            # its inputs by then, and the harvested scores were scattered
            # into fresh arrays — nothing pooled escapes the group
            if grp.lease is not None:
                grp.lease.release()

    def _harvest_failed(self, grp: _InflightGroup, backend: Any,
                        exc: Exception) -> None:
        """The in-flight call's result could not be had: its frames
        forward unscored, the breaker hears of it."""
        self._note_error("harvest", exc)
        if self.failover is not None:
            self.failover.observe(backend, ok=False,
                                  n_spans=grp.n_spans,
                                  error=f"{type(exc).__name__}: {exc}",
                                  probe=grp.probe)
        for r in grp.reqs:
            r.scores = None
            r.signal_done()
        grp.span.set_attr("error", True)
        grp.span.finish(error=True)

    def _retire_inner(self, grp: _InflightGroup) -> None:
        t_h0 = time.monotonic_ns()
        # harvest against the backend that DISPATCHED this group (see
        # _InflightGroup.backend): a failover trip between dispatch and
        # harvest must not hand a primary handle to the fallback
        backend = grp.backend if grp.backend is not None else self.backend
        # the wait apart from the work: ``fetch`` blocks on the device
        # result (engine/harvest), ``harvest`` then finds it fetched
        handle = grp.handle
        fetch = getattr(backend, "fetch", None)
        if fetch is not None:
            try:
                with self._backend_lock:
                    handle = fetch(handle, grp.call)
                    grp.harvest_split = getattr(backend, "last_harvest_ms",
                                                None)
            except Exception as e:
                self._harvest_failed(grp, backend, e)
                return
            grp.call_attrs = backend.call_attrs(handle) \
                if hasattr(backend, "call_attrs") else None
        with annotate("engine/scatter", call=grp.call):
            try:
                harvest = getattr(backend, "harvest", None)
                with self._backend_lock:
                    scores = harvest(handle) if harvest is not None \
                        else handle
            except Exception as e:
                self._harvest_failed(grp, backend, e)
                return
            if self.failover is not None:
                # the group's FINAL success: harvest landed (or the
                # eager fallback call already had) — breaker evidence,
                # and the fallback's scored-span volume when it served
                self.failover.observe(backend, ok=True,
                                      n_spans=grp.n_spans,
                                      probe=grp.probe)
            if latency_enabled():
                # one boundary dict per group, attached to every request
                # BEFORE its done event fires: the fast-path forwarder
                # reads stage_ns the instant the wait returns, and the
                # frame's queue/pack/device/harvest stages are exactly
                # these boundaries diffed
                # (selftelemetry/latency.StageClock)
                stage_ns = {"pack0": grp.t_pack0,
                            "dispatch": grp.t_dispatch,
                            "harvest0": t_h0, "end": time.monotonic_ns(),
                            "overlap_ms": grp.overlap_ms,
                            "fused": grp.fused, "call": grp.call}
                if grp.fused and grp.shape is not None:
                    # bucket label for the latency ledger's exemplar
                    # join (worst fused frame -> this bucket's compile
                    # event + cost-ledger row)
                    stage_ns["fused_bucket"] = "r{}x{}".format(*grp.shape)
                if grp.attrib is not None:
                    # the sampled intra-fused waterfall rides the same
                    # boundary dict into StageClock.merge_engine
                    stage_ns["device_attrib"] = grp.attrib
                for r in grp.reqs:
                    r.stage_ns = stage_ns
            try:
                if len(grp.reqs) == 1:
                    grp.reqs[0].scores = scores
                    grp.reqs[0].signal_done()
                else:
                    off = 0
                    for r in grp.reqs:
                        n_r = len(r.batch)
                        r.scores = scores[off:off + n_r]
                        off += n_r
                        r.signal_done()
            finally:
                # no request may hang on a half-failed split: unset
                # events fire with scores=None (caller passes through,
                # counter fires); signal_done is a no-op on requests
                # already signaled above
                for r in grp.reqs:
                    r.signal_done()
        t_end = time.monotonic_ns()
        # device-occupancy accounting: the union of [dispatch, harvest-end]
        # intervals is an upper bound on device busy time (it includes
        # transfers); intervals overlap under depth>1, so clip to the
        # high-water mark instead of double counting
        alone_ns = t_end - max(grp.t_dispatch, self._busy_until)
        self._busy_ns += alone_ns
        self._busy_until = t_end
        wall = max(t_end - self._t_run0, 1)
        busy_frac = min(self._busy_ns / wall, 1.0)
        dt_ms = (t_end - grp.t_pack0) / 1e6
        pack_ms = (grp.t_dispatch - grp.t_pack0) / 1e6
        device_ms = (t_end - grp.t_dispatch) / 1e6
        harvest_ms = (t_end - t_h0) / 1e6
        # adaptive-batching estimators: device-step cost (pack + device,
        # the wall the next group's deadline must absorb) and span volume
        # as SEPARATE EWMAs (ratio of averages — see __init__), spans per
        # real packed row with its mean deviation (converts span budgets
        # to ladder rows), the rung's cost (the time this call had the
        # device to itself: from its dispatch, or from the retirement of
        # the call ahead where that came later, to its own), and the
        # harvest allowance subtracted from headroom
        if grp.n_spans > 0:
            self._ewma_call_ms = _ewma(self._ewma_call_ms,
                                       pack_ms + device_ms)
            self._ewma_call_spans = _ewma(self._ewma_call_spans,
                                          float(grp.n_spans))
            if grp.real_rows and grp.shape:
                (self._ewma_spans_per_row,
                 self._ewma_spans_per_row_dev) = _ewma_dev(
                    self._ewma_spans_per_row,
                    self._ewma_spans_per_row_dev,
                    grp.n_spans / grp.real_rows)
                rung = grp.shape[0]
                ladder = getattr(backend, "ladder", None)
                if grp.bucket_hit is not False and ladder is not None \
                        and rung in ladder.buckets:
                    # (a shape's first sight is its compile, not its
                    # cost; a shape past the ladder is nobody's choice)
                    self._rung_ms[rung] = _ewma(self._rung_ms.get(rung),
                                                alone_ns / 1e6)
        self._ewma_harvest_ms = _ewma(self._ewma_harvest_ms, harvest_ms)
        if grp.bucket_hit is not False:
            # the lead the next call is committed by (_lead_ms): what this
            # one's pack and enqueue took (a shape's first sight holds
            # its compile, which no later call pays)
            self._ewma_pack_ms, self._ewma_pack_ms_dev = _ewma_dev(
                self._ewma_pack_ms, self._ewma_pack_ms_dev, pack_ms)
        if self.mesh is not None and self._adapt_key is not None:
            # publish the learned per-mesh cost so the next engine on
            # this (model geometry, mesh) starts informed (dict store is
            # atomic; the worker is the only writer for this key)
            ScoringEngine._ADAPT_PRIORS[self._adapt_key] = (
                self._ewma_call_ms, self._ewma_call_spans,
                self._ewma_spans_per_row, self._ewma_spans_per_row_dev,
                self._ewma_harvest_ms)
        if grp.fused and grp.shape is not None:
            # device-plane ledger joins (ISSUE 20): the measured stamp
            # against XLA's expectation, and the cold-key compile as a
            # first-class event now that the group's trace id is in hand
            bucket = "r{}x{}".format(*grp.shape)
            site = getattr(backend, "fused_site", None) or "fused"
            from ..models.costmodel import cost_ledger
            cost_ledger.observe_device_ms(
                site, bucket, device_ms, n_real=grp.n_spans,
                n_padded=grp.span_bucket)
            if grp.cold_dispatch_s >= 0.05:
                tid = getattr(grp.span, "trace_id", None)
                _record_compile_event(
                    site, grp.cold_dispatch_s, shape=bucket,
                    trace_id=f"{tid:032x}" if tid is not None else None,
                    warm=False)
        self._stage_log.append({
            "pack_ms": pack_ms, "device_ms": device_ms,
            "harvest_ms": harvest_ms, "overlap_ms": grp.overlap_ms,
            "spans": grp.n_spans, "bucket_hit": grp.bucket_hit})
        self._annotate_score_span(grp, busy_frac, dt_ms, pack_ms,
                                  harvest_ms, alone_ns / 1e6)
        grp.span.finish()
        meter.add(SCORED_METRIC, grp.n_spans)
        # exemplar: link this latency sample to the tpu/score self-trace
        # that produced it (Dapper-style metric→trace pivot; NULL_SPAN —
        # tracing off or a self-telemetry batch — carries no ids)
        tid = getattr(grp.span, "trace_id", None)
        meter.record("odigos_anomaly_score_latency_ms", dt_ms,
                     exemplar=(tid, grp.span.span_id)
                     if tid is not None else None)
        meter.record(STAGE_PACK_METRIC, pack_ms)
        meter.record(STAGE_DEVICE_METRIC, device_ms)
        meter.record(STAGE_HARVEST_METRIC, harvest_ms)
        meter.set_gauge(DEVICE_BUSY_GAUGE, round(busy_frac, 4))

    def _annotate_score_span(self, grp: _InflightGroup, busy_frac: float,
                             dt_ms: float, pack_ms: float,
                             harvest_ms: float, alone_ms: float) -> None:
        """TPU-stage span attributes: device, coalesced batch shape,
        padding waste, queue wait, per-stage split, pipeline overlap, the
        fetch's wait and copy, the wall the call had the device to itself
        and the rung's learned cost after it, and the call's serial
        (``call`` on the engine/* trace annotations and in the frames'
        stage_ns: which call, of which rung, a frame rode)."""
        sp = grp.span
        sp.set_attr("model", self.cfg.model)
        sp.set_attr("device",
                    getattr(self.backend, "device_label", "host"))
        sp.set_attr("batch.spans", grp.n_spans)
        sp.set_attr("requests", len(grp.reqs))
        sp.set_attr("queue_wait_ms", round(
            (grp.t_pack0 - min(r.submitted_ns for r in grp.reqs)) / 1e6, 3))
        sp.set_attr("pipeline.depth", self._depth)
        sp.set_attr("overlap_ms", round(grp.overlap_ms, 3))
        sp.set_attr("device_busy_frac", round(busy_frac, 4))
        sp.set_attr("pack_ms", round(pack_ms, 3))
        sp.set_attr("harvest_ms", round(harvest_ms, 3))
        if grp.harvest_split is not None:
            ready_ms, copy_ms = grp.harvest_split
            sp.set_attr("harvest.ready_ms", round(ready_ms, 3))
            sp.set_attr("harvest.copy_ms", round(copy_ms, 3))
        sp.set_attr("call.alone_ms", round(alone_ms, 3))
        if grp.shape is not None:
            sp.set_attr("device.shape", "x".join(map(str, grp.shape)))
            cost = self._rung_ms.get(grp.shape[0])
            if cost is not None:
                sp.set_attr("rung.cost_ms", round(cost, 3))
        if grp.real_rows is not None:
            sp.set_attr("rows.real", grp.real_rows)
        backend = grp.backend if grp.backend is not None else self.backend
        rung_attrs = getattr(backend, "rung_attrs", None)
        if rung_attrs is not None and grp.real_rows is not None \
                and grp.shape is not None:
            for key, value in rung_attrs(grp.shape[0],
                                         grp.real_rows).items():
                sp.set_attr(key, value)
        sp.set_attr("coalesce.closed", grp.closed)
        sp.set_attr("commit.when", grp.commit)
        sp.set_attr("commit.held_ms", round(grp.held_ms, 3))
        if grp.ahead_end_ns is not None:
            # enqueue's end to the expected end of the call ahead:
            # negative, the pack overran the lead and the chip waited
            sp.set_attr("commit.slack_ms", round(
                (grp.ahead_end_ns - grp.t_dispatch) / 1e6, 3))
        if grp.spilled:
            sp.set_attr("rung.spill", True)
        if grp.padding_waste is not None:
            sp.set_attr("padding.waste", grp.padding_waste)
        if grp.bucket_hit is not None:
            sp.set_attr("bucket.hit", grp.bucket_hit)
        sp.set_attr("call.serial", grp.call)
        attrs = getattr(backend, "score_attrs", {})
        counted = grp.call_attrs or {}
        for key, value in {**attrs, **counted}.items():
            sp.set_attr(key, value)
        if attrs:
            meter.add(LAYER_APPLICATIONS_METRIC,
                      attrs["model.layer_applications"])
        for key, metric in getattr(backend, "call_counters", {}).items():
            if key in counted:
                meter.add(metric, counted[key])
        self._device_calls += 1
