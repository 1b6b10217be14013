"""tpuanomaly processor — the north-star component.

The TPU-backed anomaly stage behind the stock processor Factory boundary
(modeled on odigossamplingprocessor/factory.go:13's WithTraces registration):
featurizes incoming span batches, scores them against the ScoringEngine
within a strict latency budget, and tags anomalous spans with score/flag
attributes for the anomalyrouter to route. On timeout or queue-full the batch
passes through unscored — the pipeline never blocks on the TPU (north-star
<5 ms p99 requirement).

Non-TPU installs simply never put ``tpuanomaly`` in a pipeline; nothing else
changes (byte-identical requirement).
"""

from __future__ import annotations

import threading
from typing import Any, Optional

from ...features.featurizer import FeaturizerConfig
from ...pdata.spans import SpanBatch
from ...serving.engine import EngineConfig, ScoringEngine

# tagging lives in serving/fastpath.py so the ingest fast path and this
# processor share ONE implementation (bit-identical output is the parity
# contract); the historic import locations keep working via these names
from ...serving.fastpath import (
    FLAG_ATTR, FLAGGED_METRIC, SCORE_ATTR, tag_anomalies)
from ..api import Capabilities, ComponentKind, Factory, Processor, register

__all__ = ["TpuAnomalyProcessor", "SCORE_ATTR", "FLAG_ATTR",
           "FLAGGED_METRIC", "tag_anomalies"]

# engines shared across processor instances (one TPU sidecar per collector,
# like the reference's one gateway-adjacent model server), keyed by config
_shared_engines: dict[tuple, ScoringEngine] = {}
_shared_lock = threading.Lock()


def _shutdown_shared_engines() -> None:
    """Drain shared engines at interpreter exit — a live scoring thread at
    teardown aborts the TPU runtime client (pthread cancel during PJRT
    destruction)."""
    with _shared_lock:
        engines = list(_shared_engines.values())
        _shared_engines.clear()
    for eng in engines:
        try:
            eng.shutdown()
        except Exception:
            pass


import atexit  # noqa: E402  (registration belongs next to the registry)

atexit.register(_shutdown_shared_engines)


def _engine_for(cfg: EngineConfig, shared: bool) -> ScoringEngine:
    if not shared:
        return ScoringEngine(cfg)
    try:
        hash(cfg)  # every behavioral field participates in the key
    except TypeError:  # unhashable model_config → can't dedupe safely
        return ScoringEngine(cfg)
    key = cfg
    with _shared_lock:
        eng = _shared_engines.get(key)
        if eng is None:
            eng = _shared_engines[key] = ScoringEngine(cfg)
        return eng


class TpuAnomalyProcessor(Processor):
    """Config:
    model: zscore | transformer | autoencoder | mock | remote
    socket_path: unix socket of an out-of-process scoring sidecar
        (model "remote"; serving/sidecar.py)
    threshold: score in [0,1] above which a span is tagged (default 0.8)
    timeout_ms: scoring latency budget before pass-through (default 5.0)
    mesh: {"data": N, "model": M} — multi-chip sharded serving (ISSUE 7):
        the engine owns an N×M device mesh and dispatches every packed
        call through the partition-rule dp×tp plan. ``devices: N`` (what
        pipelinegen renders from anomaly.devices) and ``data_parallel``
        are the legacy pure-DP spellings, honored when mesh is absent.
    attr_slots / max_len / trace_bucket / online_update / checkpoint_path /
    pipeline_depth / bucket_ladder / warm_ladder:
        forwarded to EngineConfig (pipeline_depth 2 = double-buffered
        scoring: host packing overlaps device execution)
    failover: circuit-broken fallback model (ISSUE 13) — ``true`` or a
        {window_s, trip_errors, probe_interval_s, recovery_successes,
        fallback_model} mapping; a persistent device fault hot-swaps
        scoring to the zscore fallback, raises ModelFailover, and
        half-open probes the primary back (serving/failover.py)
    shared_engine: reuse one engine across processor instances (default True)
    """

    capabilities = Capabilities(mutates_data=True)

    # incremental hot reload (ISSUE 14): the two knobs OUTSIDE the
    # EngineConfig identity retune live — the warmed engine (bucket
    # ladder, ScoringPlan caches, failover state) is never rebuilt for
    # a threshold tweak. Any engine-shaping key (model, mesh, batch
    # geometry...) changes the shared-engine identity and replaces the
    # node (or forces a full rebuild under a fast_path alias).
    RECONFIGURABLE_KEYS = frozenset({"threshold", "timeout_ms"})

    def __init__(self, name: str, config: dict[str, Any]):
        super().__init__(name, config)
        fz = FeaturizerConfig(attr_slots=int(config.get("attr_slots", 0)))
        model = config.get("model", "zscore")
        # a `model_config` mapping sizes the sequence model from pipeline
        # config (d_model, max_len, vocabs, dtype-by-name...); the factory —
        # not the caller — knows how to build the frozen config dataclass
        # (odigossamplingprocessor/factory.go:13 seam)
        model_config = config.get("model_config")
        if isinstance(model_config, dict):
            from ...training.checkpoint import make_model_config

            model_config = make_model_config(model, model_config)
        self.engine_cfg = EngineConfig(
            model=model,
            max_batch_spans=int(config.get("max_batch", 65536)),
            max_len=int(config.get("max_len", 64)),
            trace_bucket=int(config.get("trace_bucket", 256)),
            online_update=bool(config.get("online_update", True)),
            quantized=bool(config.get("quantized", False)),
            featurizer=fz,
            model_config=model_config,
            checkpoint_path=config.get("checkpoint_path"),
            socket_path=config.get("socket_path"),
            mesh=config.get("mesh"),
            # "devices" is what pipelinegen renders from anomaly.devices;
            # it was silently dropped before ISSUE 7 wired the mesh
            data_parallel=int(config.get("data_parallel",
                                         config.get("devices", 0))),
            seed=int(config.get("seed", 0)),
            pipeline_depth=int(config.get("pipeline_depth", 2)),
            bucket_ladder=int(config.get("bucket_ladder", 4)),
            warm_ladder=bool(config.get("warm_ladder", False)),
            failover=config.get("failover"),
            # ISSUE 20: sampled intra-fused attribution (fused route)
            device_attribution=bool(config.get("device_attribution",
                                               False)),
            device_attribution_stride=int(
                config.get("device_attribution_stride", 32)),
        )
        self.engine = _engine_for(self.engine_cfg,
                                  bool(config.get("shared_engine", True)))
        self._apply_knobs(config)

    def _apply_knobs(self, config: dict[str, Any]) -> None:
        # one parse routine for __init__ and reconfigure (no default
        # drift between a reloaded node and a freshly built one)
        self.threshold = float(config.get("threshold", 0.8))
        self.timeout_s = float(config.get("timeout_ms", 5.0)) / 1000.0

    def reconfigure(self, config: dict[str, Any]) -> None:
        self._apply_knobs(config)
        self.config = config

    def start(self) -> None:
        super().start()
        self.engine.start()

    def shutdown(self) -> None:
        # shared engines outlive individual processors; private ones stop
        if not self.config.get("shared_engine", True):
            self.engine.shutdown()
        super().shutdown()

    def process(self, batch: SpanBatch) -> Optional[SpanBatch]:
        # the engine featurizes (or skips it for remote backends, which
        # featurize sidecar-side); passing None avoids doing it twice
        scores = self.engine.score_sync(batch, None,
                                        timeout_s=self.timeout_s)
        if scores is None:  # timeout / queue full: pass through untagged
            return batch
        return tag_anomalies(batch, scores, self.threshold)


register(Factory(
    type_name="tpuanomaly",
    kind=ComponentKind.PROCESSOR,
    create=TpuAnomalyProcessor,
    default_config=lambda: {
        "model": "zscore", "threshold": 0.8, "timeout_ms": 5.0,
        "attr_slots": 0, "max_len": 64, "trace_bucket": 256,
        "online_update": True, "shared_engine": True},
))
