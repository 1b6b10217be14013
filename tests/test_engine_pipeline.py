"""Pipelined (double-buffered) scoring engine: the ISSUE 2 tentpole.

The engine overlaps host packing with device execution behind a bounded
in-flight window. These tests pin the correctness contract of that overlap:

* per-request scores are byte-identical to the serial (depth-1) path, both
  for singleton groups and for coalesced groups split back per request;
* late scores after a ``score_sync`` timeout still land (the passthrough
  counter fires, the worker still retires the call);
* queue-full admission control is unchanged;
* ``shutdown()`` drains queued AND in-flight work losslessly;
* the bucket ladder maps steady-state traffic onto precompiled shapes —
  zero recompiles after ``warm_ladder`` (the acceptance criterion), and
  the tpu/score spans carry the pipeline annotations.
"""

import threading

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from odigos_tpu.features import featurize  # noqa: E402
from odigos_tpu.models import TransformerConfig  # noqa: E402
from odigos_tpu.pdata import concat_batches, synthesize_traces  # noqa: E402
from odigos_tpu.serving import (  # noqa: E402
    BucketLadder, EngineConfig, ScoringEngine)
from odigos_tpu.serving.engine import (  # noqa: E402
    PASSTHROUGH_METRIC, QUEUE_FULL_METRIC, SCORED_METRIC)
from odigos_tpu.utils.telemetry import meter  # noqa: E402

TINY_TF = TransformerConfig(d_model=32, n_heads=2, n_layers=1, d_ff=64,
                            max_len=16, dtype=jnp.float32)


def tiny_cfg(**kw) -> EngineConfig:
    base = dict(model="transformer", model_config=TINY_TF, max_len=16,
                trace_bucket=8, bucket_ladder=2, pipeline_depth=2)
    base.update(kw)
    return EngineConfig(**base)


# ----------------------------------------------------------- bucket ladder

def test_bucket_ladder_rounding_and_lru():
    lad = BucketLadder(base=8, n_buckets=3)  # 8, 16, 32
    assert lad.buckets == [8, 16, 32]
    assert lad.round_rows(1) == 8
    assert lad.round_rows(8) == 8
    assert lad.round_rows(9) == 16
    assert lad.round_rows(33) == 64   # beyond the top: multiples of 32
    assert lad.round_rows(65) == 96
    assert lad.observe(8) is False    # first sight = compile
    assert lad.observe(8) is True     # warm
    lad.mark_warm(16)
    assert lad.observe(16) is True    # pre-warmed counts as hit
    s = lad.stats()
    assert s["hits"] == 2 and s["misses"] == 1
    assert s["hit_rate"] == round(2 / 3, 4)


# ------------------------------------------------- byte-identical splitting

def test_pipelined_singleton_groups_match_serial_bitwise():
    """Sequential score_sync (one request per device call) through the
    depth-2 engine must equal the serial backend path bit-for-bit."""
    eng = ScoringEngine(tiny_cfg()).start()
    serial = ScoringEngine(tiny_cfg(pipeline_depth=1))  # same seed/geometry
    try:
        for seed in (1, 2, 3):
            b = synthesize_traces(6, seed=seed)
            f = featurize(b)
            got = eng.score_sync(b, f, timeout_s=60.0)
            assert got is not None
            want = serial.backend.score(b, f)
            np.testing.assert_array_equal(got, want)
    finally:
        eng.shutdown()


def test_coalesced_group_splitting_matches_serial_bitwise():
    """Requests queued before start() coalesce into ONE device call; the
    per-request split must be byte-identical to scoring the concatenated
    batch serially and slicing at the same offsets."""
    eng = ScoringEngine(tiny_cfg())
    batches = [synthesize_traces(n, seed=10 + n) for n in (2, 5, 3)]
    feats = [featurize(b) for b in batches]
    reqs = [eng.submit(b, f) for b, f in zip(batches, feats)]
    assert all(r is not None for r in reqs)
    eng.start()
    try:
        for r in reqs:
            assert r.done.wait(60.0) and r.scores is not None
    finally:
        eng.shutdown()
    ref = ScoringEngine(tiny_cfg())  # fresh ladder, same weights
    merged = concat_batches(batches)
    from odigos_tpu.features.featurizer import SpanFeatures

    mf = SpanFeatures(np.concatenate([f.categorical for f in feats]),
                      np.concatenate([f.continuous for f in feats]))
    want = ref.backend.score(merged, mf)
    off = 0
    for b, r in zip(batches, reqs):
        np.testing.assert_array_equal(r.scores, want[off:off + len(b)])
        off += len(b)


# ------------------------------------------------------- timeout semantics

def test_late_scores_after_timeout_still_land():
    meter.reset()
    eng = ScoringEngine(tiny_cfg()).start()
    try:
        b = synthesize_traces(4, seed=7)
        # absurd budget: the jit compile on call 0 guarantees a timeout
        assert eng.score_sync(b, featurize(b), timeout_s=1e-6) is None
        assert meter.counter(PASSTHROUGH_METRIC) == len(b)
        # the worker still retires the call; the late scores land
        deadline = threading.Event()
        for _ in range(600):
            if meter.counter(SCORED_METRIC) >= len(b):
                break
            deadline.wait(0.1)
        assert meter.counter(SCORED_METRIC) == len(b)
    finally:
        eng.shutdown()


def test_queue_full_admission_control_pipelined():
    meter.reset()
    eng = ScoringEngine(tiny_cfg(max_queue=1))  # not started
    assert eng.submit(synthesize_traces(1, seed=0)) is not None
    assert eng.submit(synthesize_traces(1, seed=1)) is None
    assert meter.counter(QUEUE_FULL_METRIC) == 1


# --------------------------------------------------------- lossless drain

def test_shutdown_drains_queued_and_inflight_losslessly():
    eng = ScoringEngine(tiny_cfg()).start()
    batches = [synthesize_traces(3, seed=20 + i) for i in range(5)]
    reqs = [eng.submit(b, featurize(b)) for b in batches]
    assert all(r is not None for r in reqs)
    eng.shutdown()  # must drain, not abandon
    for b, r in zip(batches, reqs):
        assert r.done.is_set(), "shutdown abandoned an accepted request"
        assert r.scores is not None and len(r.scores) == len(b)
    # after shutdown the engine refuses new work instead of blackholing it
    assert eng.submit(synthesize_traces(1, seed=99)) is None


# -------------------------------------------- zero recompiles after warmup

def test_warm_ladder_steady_state_triggers_zero_recompiles():
    from odigos_tpu.selftelemetry.tracer import tracer

    eng = ScoringEngine(tiny_cfg(warm_ladder=True, trace_bucket=4,
                                 bucket_ladder=2)).start()  # rows: 4, 8
    try:
        assert eng.backend.ladder.misses == 0  # warming never counts
        tracer.ring.drain()
        # varying trace counts that stay inside the warmed ladder
        for seed, n in ((1, 2), (2, 6), (3, 3), (4, 5)):
            b = synthesize_traces(n, seed=seed)
            assert eng.score_sync(b, featurize(b), timeout_s=60.0) is not None
    finally:
        eng.shutdown()
    lad = eng.backend.ladder
    assert lad.misses == 0, "steady-state traffic recompiled"
    assert lad.hits >= 4
    spans = [s for s in tracer.ring.snapshot() if s.name == "tpu/score"]
    assert spans and all(s.attrs["bucket.hit"] is True for s in spans)
    # each span names the engine call it describes, in dispatch order
    assert [s.attrs["call.serial"] for s in spans] == list(range(len(spans)))
    stats = eng.pipeline_stats()
    assert stats["bucket_ladder"]["misses"] == 0
    assert stats["bucket_ladder"]["hit_rate"] == 1.0


# -------------------------------------------------- pipeline observability

def test_pipeline_stats_and_span_annotations():
    from odigos_tpu.selftelemetry.tracer import tracer

    eng = ScoringEngine(tiny_cfg()).start()
    try:
        tracer.ring.drain()
        # flood: enough queued work that dispatch N+1 overlaps harvest N
        reqs = [eng.submit(synthesize_traces(4, seed=40 + i))
                for i in range(8)]
        for r in reqs:
            assert r is not None and r.done.wait(60.0)
    finally:
        eng.shutdown()
    stats = eng.pipeline_stats()
    assert stats["pipeline_depth"] == 2
    assert stats["device_calls"] >= 1
    assert 0.0 < stats["device_busy_frac"] <= 1.0
    assert stats["stage_pack_ms"]["p50"] >= 0.0
    assert stats["stage_device_ms"]["p99"] >= stats["stage_device_ms"]["p50"]
    spans = [s for s in tracer.ring.snapshot() if s.name == "tpu/score"]
    assert spans
    for s in spans:
        assert s.attrs["pipeline.depth"] == 2
        assert "overlap_ms" in s.attrs
        assert 0.0 < s.attrs["device_busy_frac"] <= 1.0
        assert "pack_ms" in s.attrs and "harvest_ms" in s.attrs


# ------------------------------------------- deadline adaptive batching

def test_bucket_ladder_floor_rows():
    lad = BucketLadder(base=8, n_buckets=3)  # 8, 16, 32
    assert lad.floor_rows(7) == 8     # nothing fits: smallest bucket
    assert lad.floor_rows(8) == 8
    assert lad.floor_rows(31) == 16   # snapped DOWN, never up
    assert lad.floor_rows(32) == 32
    # beyond the top bucket: multiples of it (round_rows' shapes)
    assert lad.floor_rows(100) == 96
    assert lad.floor_rows(1000) == 992


def test_adaptive_cap_sizes_from_deadline_and_ladder():
    import time as _time

    eng = ScoringEngine(tiny_cfg())
    # cold engine: no estimate yet -> the fixed cap applies
    assert eng._adaptive_cap(_time.monotonic_ns() + 10_000_000) \
        == eng.cfg.max_batch_spans
    # seed observed step cost: 0.01 ms/span (ratio of averages:
    # 100 ms over 10k spans), 4 spans/row, ladder {8, 16}
    eng._ewma_call_ms = 100.0
    eng._ewma_call_spans = 10_000.0
    eng._ewma_spans_per_row = 4.0
    eng._ewma_harvest_ms = 0.0
    # 1 ms headroom affords 100 spans = 25 rows -> floor to bucket 16
    # -> 64 spans: the cap lands on a precompiled shape
    cap = eng._adaptive_cap(_time.monotonic_ns() + 1_000_000)
    assert cap == 64
    # generous headroom still clamps to max_batch_spans
    cap = eng._adaptive_cap(_time.monotonic_ns() + int(1e12))
    assert cap == eng.cfg.max_batch_spans
    # an already-expired deadline switches to drain mode: maximal
    # coalescing clears the backlog (shrinking here would collapse
    # throughput exactly when load demands growth)
    assert eng._adaptive_cap(_time.monotonic_ns() - 1_000_000) \
        == eng.cfg.max_batch_spans


def test_adaptive_cap_without_ladder_uses_span_budget():
    import time as _time

    eng = ScoringEngine(EngineConfig(model="mock"))
    eng._ewma_call_ms = 100.0
    eng._ewma_call_spans = 10_000.0
    eng._ewma_harvest_ms = 0.0
    cap = eng._adaptive_cap(_time.monotonic_ns() + 1_000_000)  # 1 ms
    assert 50 <= cap <= 150  # ~100 spans afford, no rung snapping


def test_deadline_requests_update_estimators_and_score():
    """Deadline-carrying submissions flow end-to-end, retire the EWMA
    estimators, and score identically to undeadlined requests."""
    import time as _time

    eng = ScoringEngine(tiny_cfg()).start()
    try:
        b = synthesize_traces(6, seed=3)
        f = featurize(b)
        req = eng.submit(b, f,
                         deadline_ns=_time.monotonic_ns() + int(60e9))
        assert req is not None and req.done.wait(60.0)
        want = ScoringEngine(tiny_cfg()).backend.score(b, f)
        np.testing.assert_array_equal(req.scores, want)
        assert eng._ms_per_span() is not None \
            and eng._ms_per_span() > 0
        assert eng._ewma_spans_per_row is not None
        stats = eng.pipeline_stats()
        assert stats["adaptive"]["ms_per_span"] > 0
    finally:
        eng.shutdown()


def test_column_coalesce_skips_batch_merge_bitwise():
    """Coalesced pre-featurized requests ride the _ColumnBatch view (no
    concat_batches) and still split back bit-identical to scoring the
    concatenated batch serially."""
    from odigos_tpu.serving.engine import _ColumnBatch

    eng = ScoringEngine(tiny_cfg())
    assert eng.backend.coalesce_columns == (
        "trace_id_hi", "trace_id_lo", "start_unix_nano")
    batches = [synthesize_traces(n, seed=30 + n) for n in (3, 4, 2)]
    feats = [featurize(b) for b in batches]
    view = _ColumnBatch(batches)
    merged = concat_batches(batches)
    assert len(view) == len(merged)
    for col in ("trace_id_hi", "trace_id_lo", "start_unix_nano"):
        np.testing.assert_array_equal(view.col(col), merged.col(col))
    # queued-before-start coalescing (one device call over the view)
    reqs = [eng.submit(b, f) for b, f in zip(batches, feats)]
    eng.start()
    try:
        for r in reqs:
            assert r.done.wait(60.0) and r.scores is not None
    finally:
        eng.shutdown()
    from odigos_tpu.features.featurizer import SpanFeatures

    mf = SpanFeatures(np.concatenate([f.categorical for f in feats]),
                      np.concatenate([f.continuous for f in feats]))
    want = ScoringEngine(tiny_cfg()).backend.score(merged, mf)
    off = 0
    for b, r in zip(batches, reqs):
        np.testing.assert_array_equal(r.scores, want[off:off + len(b)])
        off += len(b)


def test_depth1_backends_keep_serial_behavior():
    eng = ScoringEngine(EngineConfig(model="mock"))
    assert eng._depth == 1  # no dispatch -> no overlap window
    eng2 = ScoringEngine(EngineConfig(model="zscore"))
    assert eng2._depth == 1
    eng3 = ScoringEngine(tiny_cfg())
    assert eng3._depth == 2


def test_failed_dispatch_is_logged_with_its_exception_text(caplog):
    """A dispatch that raises forwards its frames unscored and counts an
    engine error — the product's contract — but it must also SAY what
    was raised (ISSUE 21): on a new backend a compile refusal otherwise
    looks like a healthy collector. One line per failure mode, not per
    frame."""
    import logging

    eng = ScoringEngine(EngineConfig(model="mock")).start()
    batch = synthesize_traces(4, seed=1)
    try:
        errors0 = meter.counter("odigos_anomaly_engine_errors_total")
        assert eng.last_error is None
        assert "last_error" not in eng.pipeline_stats()
        eng.inject_device_fault("chip says no")
        with caplog.at_level(logging.ERROR, logger="odigos_tpu.serving.engine"):
            for _ in range(3):
                assert eng.score_sync(batch, timeout_s=5.0) is None
        assert meter.counter(
            "odigos_anomaly_engine_errors_total") - errors0 == 3
        lines = [r.getMessage() for r in caplog.records
                 if r.name == "odigos_tpu.serving.engine"]
        assert lines == ["engine/mock dispatch: DeviceFaultInjected: "
                         "chip says no (frames forward unscored)"]
        assert eng.pipeline_stats()["last_error"] == \
            "dispatch: DeviceFaultInjected: chip says no"
        # a different failure is a new line
        eng.inject_device_fault("still no")
        with caplog.at_level(logging.ERROR, logger="odigos_tpu.serving.engine"):
            assert eng.score_sync(batch, timeout_s=5.0) is None
        assert "still no" in caplog.records[-1].getMessage()
    finally:
        eng.shutdown()
