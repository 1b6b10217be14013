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

import math
import threading
import time

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
    # 100 ms over 10k spans), 4 spans a REAL packed row, ladder {8, 16}
    eng._ewma_call_ms = 100.0
    eng._ewma_call_spans = 10_000.0
    eng._ewma_spans_per_row = 4.0
    eng._ewma_harvest_ms = 0.0
    # 1 ms headroom affords 100 spans = 25 rows -> floor to bucket 16
    # -> 64 spans: the cap lands on a precompiled shape
    cap = eng._adaptive_cap(_time.monotonic_ns() + 1_000_000)
    assert cap == 64
    # the fixed cap goes through the same estimator onto the same rungs:
    # (spans, rows) with no deadline at all
    assert eng._budget(None) == (eng.cfg.max_batch_spans,
                                 eng.cfg.max_batch_spans // 4)
    # rows that vary in what they hold are counted on for less: four
    # mean deviations off the mean (3.5 spans a row): 28 rows -> the
    # bucket of 16 still, now worth 56 spans
    eng._ewma_spans_per_row_dev = 0.125
    assert eng._budget(_time.monotonic_ns() + 1_000_000) == (56, 16)
    eng._ewma_spans_per_row_dev = 0.0
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
        # spans per row is learned from the rows the packer FILLED, not
        # from the rung that padded them
        real = eng.backend.last_real_rows
        assert 0 < real < eng.backend.last_shape[0]
        assert eng._ewma_spans_per_row == len(b) / real
        stats = eng.pipeline_stats()
        assert stats["adaptive"]["ms_per_span"] > 0
        assert stats["adaptive"]["spans_per_row"] == len(b) / real
        assert list(stats["adaptive"]["rung_ms"]) == []  # a first sight
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


# ----------------------------- closing a coalesced call on the rung it fills

FRAME = 2620        # spans a wire frame carries (the benchmark's pool)
PER_ROW = 57.5      # spans a real packed row holds on that traffic
RUNG_MS = {256: 217.0, 512: 334.0, 1024: 637.0}


class _Sized:
    """A batch the coalescer only ever asks for its length."""

    def __init__(self, n):
        self.n = n

    def __len__(self):
        return self.n


class _RungBackend:
    """Ladder of 256/512/1,024 rows and a fixed spans-per-real-row; a
    call reports the rows it filled and the rung that padded them."""

    coalesce_columns = ()

    def __init__(self, per_row=PER_ROW):
        self.ladder = BucketLadder(256, 3)
        self.per_row = per_row
        self.calls = []

    def score(self, batch, features):
        n = len(batch)
        self.last_real_rows = math.ceil(n / self.per_row)
        self.last_shape = [self.ladder.round_rows(self.last_real_rows), 64]
        self.last_bucket_hit = True
        self.calls.append(n)
        return np.arange(n, dtype=np.float32)


def rung_engine(per_row=PER_ROW):
    """An engine that has learned the benchmark cell's numbers: the cap
    of 32768 spans, 57.5 spans a real row, the three rungs' costs."""
    eng = ScoringEngine(EngineConfig(model="mock", max_batch_spans=32768))
    eng.backend = _RungBackend(per_row)
    eng._ewma_spans_per_row = PER_ROW
    eng._rung_ms = dict(RUNG_MS)
    return eng


def enqueue(eng, sizes):
    from odigos_tpu.features.featurizer import SpanFeatures
    from odigos_tpu.serving.engine import ScoreRequest

    reqs = []
    for n in sizes:
        feats = SpanFeatures(np.zeros((n, 1), np.int32),
                             np.zeros((n, 1), np.float32))
        reqs.append(ScoreRequest(batch=_Sized(n), features=feats))
        eng._queue.put_nowait(reqs[-1])
    return reqs


def closed_counts():
    from odigos_tpu.serving.engine import COALESCE_CLOSED_METRIC

    return {r: meter.counter(f"{COALESCE_CLOSED_METRIC}{{reason={r}}}")
            for r in ("drained", "rung", "cap")}


def same(got, want):
    return len(got) == len(want) and all(a is b for a, b in zip(got, want))


@pytest.mark.parametrize("queued, takes, rung, reason", [
    # 3-4 frames, the paced cell's every call: same rung, all of them
    (4, 4, 256, "drained"),
    # 6 frames: 5 in 217 ms beat 6 in 334 ms, the sixth leads the next
    (6, 5, 256, "rung"),
    # 9 frames: 9 in 334 ms beat 5 in 217 ms
    (9, 9, 512, "drained"),
    # a backlog: the cap is 568 rows, the rung under it holds 11 frames
    (20, 11, 512, "rung"),
])
def test_call_closes_on_the_rung_it_fills(queued, takes, rung, reason):
    eng = rung_engine()
    reqs = enqueue(eng, [FRAME] * queued)
    before = closed_counts()
    call = eng._collect(block=False)
    assert same(call, reqs[:takes])
    assert eng._closed == (reason, rung)
    after = closed_counts()
    assert {r: after[r] - before[r] for r in after} == \
        {r: float(r == reason) for r in after}
    if takes < queued:
        # the request that would have spilled was taken and not used:
        # held, and the next call starts with it
        assert same(list(eng._held), [reqs[takes]])
        assert eng._collect(block=False)[0] is reqs[takes]
    else:
        assert not eng._held
    eng.shutdown()


def test_held_request_keeps_order_counts_as_queued_and_is_never_lost():
    from odigos_tpu.selftelemetry.flow import flow_ledger

    # FIFO across calls, and the held request is part of the queue depth
    eng = rung_engine()
    reqs = enqueue(eng, [FRAME] * 30)
    first = eng._collect(block=False)
    assert len(first) == 11 and eng._held[0] is reqs[11]
    assert eng._queue.qsize() == 18 and eng._queued() == 19
    assert eng.runtime_gauges()["queue_depth"] == 19
    assert flow_ledger.watermark_current("engine/mock", "queue_depth") == 19
    order = list(first)
    while True:
        call = eng._collect(block=False)
        if call is None:
            break
        order += call
    assert same(order, reqs)

    # scored on shutdown(): the worker drains the queue AND the held one
    eng = rung_engine()
    reqs = enqueue(eng, [FRAME] * 13)
    eng.start()
    eng.shutdown()
    assert eng.backend.calls == [11 * FRAME, 2 * FRAME]
    assert all(r.done.is_set() and r.scores is not None
               and len(r.scores) == FRAME for r in reqs)
    assert not eng._held and eng._queued() == 0

    # a worker that died holding a request: shutdown() fails it like the
    # queue's (done fires, no scores, the callback runs once), in order
    eng = rung_engine()
    reqs = enqueue(eng, [FRAME] * 13)
    failed = []
    for r in reqs:
        r.on_done = failed.append
    taken = eng._collect(block=False)   # ... and the worker is gone
    assert len(taken) == 11 and eng._held[0] is reqs[11]
    eng.shutdown()
    assert same(failed, reqs[11:])
    assert all(r.done.is_set() and r.scores is None for r in reqs[11:])
    assert not eng._held and eng._queued() == 0


@pytest.mark.parametrize("first, second, reason", [
    (40_000, FRAME, "cap"),    # over max_batch_spans by itself
    (30_000, 100, "rung"),     # 522 rows: under the span cap, over 512 rows
])
def test_first_request_over_the_budget_goes_out_alone(first, second,
                                                      reason):
    eng = rung_engine()
    reqs = enqueue(eng, [first, second])
    before = closed_counts()
    assert same(eng._collect(block=False), reqs[:1])
    assert closed_counts()[reason] - before[reason] == 1
    assert same(eng._collect(block=False), reqs[1:])
    eng.shutdown()


def test_rows_are_learned_from_real_rows_and_a_spill_is_counted():
    """The pack says how many rows it filled: the estimator follows the
    real rows (not the rung that padded them), the rung's cost is what
    the call had of the device, and a call closed for one rung whose
    pack came out past it counts as a spill."""
    from odigos_tpu.selftelemetry.tracer import tracer
    from odigos_tpu.serving.engine import RUNG_SPILL_METRIC

    eng = rung_engine(per_row=50.0)   # packs thinner than was learned
    enqueue(eng, [FRAME] * 13)
    spills0 = meter.counter(RUNG_SPILL_METRIC)
    tracer.ring.drain()
    call = eng._collect(block=False)
    assert len(call) == 11 and eng._closed == ("rung", 512)
    grp = eng._dispatch_group(call, overlapped=False)
    assert grp.real_rows == 577 and grp.shape == [1024, 64]
    assert meter.counter(RUNG_SPILL_METRIC) - spills0 == 1
    eng._retire(grp)
    # one step of the EWMA from 57.5 towards 28,820 spans / 577 rows
    spr = 11 * FRAME / 577
    assert eng._ewma_spans_per_row == pytest.approx(
        0.8 * PER_ROW + 0.2 * spr)
    assert eng._ewma_spans_per_row_dev == pytest.approx(
        0.2 * (PER_ROW - spr))
    # ... and the margin it now carries: four mean deviations
    assert eng._spans_per_row() == pytest.approx(
        eng._ewma_spans_per_row - 4 * eng._ewma_spans_per_row_dev)
    assert 0 < eng._rung_ms[1024] < RUNG_MS[1024]
    span = [s for s in tracer.ring.snapshot() if s.name == "tpu/score"][-1]
    assert span.attrs["rows.real"] == 577
    assert span.attrs["device.shape"] == "1024x64"
    assert span.attrs["coalesce.closed"] == "rung"
    assert span.attrs["rung.spill"] is True
    # the next call is closed with the margin: 9 frames, which is what
    # 512 rows of 50 spans hold (10 would be 524 rows), not 11 again
    enqueue(eng, [FRAME] * 11)
    assert len(eng._collect(block=False)) == 9
    eng.shutdown()


@pytest.mark.parametrize("seen, rows, cost", [
    ({}, 512, 512.0),                          # nothing seen: by rows
    ({256: 217.0}, 512, 434.0),                # by rows from the nearest
    ({256: 217.0, 1024: 637.0}, 512, 434.0),   # (ties: the first seen)
    ({256: 217.0, 512: 334.0}, 512, 334.0),    # seen: as observed
])
def test_rung_cost_is_the_observed_one_or_by_rows_from_the_nearest(
        seen, rows, cost):
    eng = rung_engine()
    eng._rung_ms = dict(seen)
    assert eng._rung_cost(rows) == pytest.approx(cost)


def test_ladderless_backends_close_on_the_span_cap_as_before():
    """zscore/mock/remote have no rung to fill: the budget stays in
    spans, the request that reaches it closes the call, nothing is
    held."""
    eng = ScoringEngine(EngineConfig(model="mock", max_batch_spans=6000))
    reqs = enqueue(eng, [FRAME] * 5)
    before = closed_counts()
    assert same(eng._collect(block=False), reqs[:3])   # 7,860 >= 6,000
    assert not eng._held and eng._closed == ("cap", None)
    assert same(eng._collect(block=False), reqs[3:])
    after = closed_counts()
    assert after["cap"] - before["cap"] == 1
    assert after["drained"] - before["drained"] == 1
    eng.shutdown()


def test_a_cold_laddered_engine_does_not_overshoot_the_span_cap():
    """A ladder, and no call with real rows retired yet: the budget is
    still in spans, but the request that would pass it is held, so that
    the first call stays on a rung that was warmed (a cap of two frames
    and a ladder whose top rung holds two: a third would run past it).
    Once rows are learned the budget is the rung's."""
    eng = rung_engine()
    eng.cfg = EngineConfig(model="mock", max_batch_spans=6144)
    eng._ewma_spans_per_row = None
    reqs = enqueue(eng, [FRAME] * 5)
    before = closed_counts()
    assert same(eng._collect(block=False), reqs[:2])   # 7,860 > 6,144
    assert eng._closed == ("cap", None) and eng._held[0] is reqs[2]
    assert closed_counts()["cap"] - before["cap"] == 1
    assert same(eng._collect(block=False), reqs[2:4])  # the held one leads
    assert same(eng._collect(block=False), reqs[4:])
    assert eng._closed == ("drained", None)
    eng.shutdown()


@pytest.mark.parametrize("pipelined", [False, True])
def test_no_request_is_lost_between_submitters_worker_and_shutdown(
        pipelined):
    """The held request is shared state (the worker holds and takes it,
    shutdown() takes it when the worker is gone): under many submitters
    and a shutdown in mid-stream every accepted request is signalled
    exactly once, scored or failed, and those scored come back in the
    order they were accepted. ``pipelined``: two calls deep on the stub
    device, so the worker also holds calls open for the commit point
    while the submitters and the shutdown race it."""
    import sys
    import time as _time

    eng = timed_engine(_TimedBackend(step_s=0.005), lead_ms=1.0) \
        if pipelined else rung_engine()
    eng._queue.maxsize = 0          # admission is not what is tested
    signalled = []
    accepted = [[] for _ in range(16)]
    go = threading.Event()

    def submitter(mine):
        from odigos_tpu.features.featurizer import SpanFeatures

        go.wait(5.0)
        for n in (FRAME, 3 * FRAME, 700, FRAME, 9000) * 6:
            feats = SpanFeatures(np.zeros((n, 1), np.int32),
                                 np.zeros((n, 1), np.float32))
            req = eng.submit(_Sized(n), feats, on_done=signalled.append)
            if req is not None:
                mine.append(req)

    threads = [threading.Thread(target=submitter, args=(mine,))
               for mine in accepted]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        eng.start()
        for t in threads:
            t.start()
        go.set()
        _time.sleep(0.05)
        eng.shutdown()              # submitters still running
        for t in threads:
            t.join(30.0)
        assert not any(t.is_alive() for t in threads)
        eng.shutdown()              # whatever raced in after the first
    finally:
        sys.setswitchinterval(interval)
    reqs = [r for mine in accepted for r in mine]
    assert reqs and all(r.done.is_set() for r in reqs)
    assert len(signalled) == len(reqs)
    assert len({id(r) for r in signalled}) == len(reqs)
    assert not eng._held and eng._queued() == 0
    scored = [r for r in reqs if r.scores is not None]
    assert sum(len(r.batch) for r in scored) == sum(eng.backend.calls)
    for mine in accepted:           # per submitter, order kept
        ids = {id(r) for r in mine}
        assert same([r for r in signalled
                     if id(r) in ids and r.scores is not None],
                    [r for r in mine if r.scores is not None])


# ------------------- committing the next call when the chip is about to free

STEP_S = 0.4        # the stub device's step, whatever the call holds


class _TimedBackend(_RungBackend):
    """The rung backend, pipelined, on a stub device that runs one call
    at a time: a call's result is there ``step_s`` after the device was
    free to start it, ``fetch`` waits for it, ``pack`` takes the host
    ``pack_s``."""

    def __init__(self, step_s=STEP_S, pack_s=0.0, can_tell_ready=True):
        super().__init__()
        self.step_s, self.pack_s = step_s, pack_s
        self.free_at = 0.0
        self.rungs = []
        if not can_tell_ready:
            self.ready = None

    def pack(self, batch, features):
        time.sleep(self.pack_s)
        n = len(batch)
        self.last_real_rows = math.ceil(n / self.per_row)
        self.last_shape = [self.ladder.round_rows(self.last_real_rows), 64]
        self.last_bucket_hit = True
        return n

    def enqueue(self, staged, call=-1):
        self.free_at = max(time.monotonic(), self.free_at) + self.step_s
        self.calls.append(staged)
        self.rungs.append(self.last_shape and self.last_shape[0])
        return ("stub", self.free_at, staged)

    def dispatch(self, batch, features):
        return self.enqueue(self.pack(batch, features))

    def ready(self, handle):
        return time.monotonic() >= handle[1]

    def fetch(self, handle, call=-1):
        time.sleep(max(0.0, handle[1] - time.monotonic()))
        return handle

    def harvest(self, handle):
        return np.arange(handle[2], dtype=np.float32)

    def score(self, batch, features):
        return self.harvest(self.fetch(self.dispatch(batch, features)))


class _LadderlessTimedBackend(_TimedBackend):
    """... that has no ladder and reports no rows."""

    def __init__(self, **kw):
        super().__init__(**kw)
        del self.ladder

    def pack(self, batch, features):
        self.last_real_rows = self.last_shape = None
        self.last_bucket_hit = None
        return len(batch)


def timed_engine(backend=None, learned=True, lead_ms=20.0):
    """A depth-2 engine on the stub device that has learned, unless it
    is to be cold, what a row holds, what the rungs cost and what a pack
    takes."""
    eng = ScoringEngine(EngineConfig(model="mock", max_batch_spans=32768))
    eng.backend = backend or _TimedBackend()
    eng._depth = 2
    if learned:
        eng._ewma_spans_per_row = PER_ROW
        eng._rung_ms = {r: eng.backend.step_s * 1e3 for r in RUNG_MS}
        eng._ewma_pack_ms = lead_ms
    return eng


def submit(eng, n=FRAME, **kw):
    from odigos_tpu.features.featurizer import SpanFeatures

    req = eng.submit(_Sized(n), SpanFeatures(np.zeros((n, 1), np.int32),
                                             np.zeros((n, 1), np.float32)),
                     **kw)
    assert req is not None
    return req


def commit_counts():
    from odigos_tpu.serving.engine import COMMIT_METRIC

    return {w: meter.counter(f"{COMMIT_METRIC}{{when={w}}}")
            for w in ("idle", "filled", "due", "blind")}


def score_spans():
    from odigos_tpu.selftelemetry.tracer import tracer

    return [s for s in tracer.ring.snapshot() if s.name == "tpu/score"]


def running_call(eng, frames=1):
    """Dispatch a call by hand and leave it running on the stub: what
    the worker has in its window when it collects the next one."""
    enqueue(eng, [FRAME] * frames)
    return eng._dispatch_group(eng._collect(block=False), overlapped=False)


def test_a_request_that_arrives_while_a_call_runs_rides_the_next_call():
    """Paced arrivals on a learned engine: the first request finds the
    device idle and goes at once; the second is collected while that call
    runs and is held open for the commit point, so the third, which
    arrives in the middle of the step, rides the same call and not the
    one after. The call is enqueued ahead of the expected end of the
    call before it by about the lead's margin, not by a step."""
    from odigos_tpu.selftelemetry.tracer import tracer

    eng = timed_engine().start()
    tracer.ring.drain()
    before = commit_counts()
    try:
        t0 = time.monotonic()
        first = submit(eng)
        time.sleep(0.05)
        second = submit(eng)
        time.sleep(STEP_S / 2 - 0.05)
        third = submit(eng)
        for r in (first, second, third):
            assert r.done.wait(10.0) and r.scores is not None
        wall = time.monotonic() - t0
    finally:
        eng.shutdown()
    assert eng.backend.calls == [FRAME, 2 * FRAME]
    # two steps back to back, not three: the hold cost the chip nothing
    assert 2 * STEP_S <= wall < 2.75 * STEP_S
    one, two = score_spans()
    assert one.attrs["commit.when"] == "idle" and one.attrs["requests"] == 1
    assert "commit.slack_ms" not in one.attrs
    assert two.attrs["commit.when"] == "due" and two.attrs["requests"] == 2
    assert two.attrs["coalesce.closed"] == "drained"
    # held from the second request's arrival to the commit point, one
    # lead short of the running call's end
    assert STEP_S * 1e3 / 2 - 20 < two.attrs["commit.held_ms"] \
        <= STEP_S * 1e3 - 50 - 20 + 5
    assert -STEP_S * 1e3 / 2 < two.attrs["commit.slack_ms"] <= 20
    after = commit_counts()
    assert {w: after[w] - before[w] for w in after} == \
        {"idle": 1.0, "filled": 0.0, "due": 1.0, "blind": 0.0}
    assert eng.pipeline_stats()["adaptive"]["lead_ms"] is not None


def test_a_queue_that_fills_the_call_commits_at_once():
    """A backlog: what waits fills the rung the budget allows, so the
    call goes at once however long the call ahead still runs, and the
    sequence of calls is what it was before the engine held any."""
    from odigos_tpu.selftelemetry.tracer import tracer

    eng = timed_engine()
    ahead = running_call(eng)
    reqs = enqueue(eng, [FRAME] * 20)
    before = commit_counts()
    t0 = time.monotonic()
    call = eng._collect(block=False, ahead=ahead)
    assert time.monotonic() - t0 < STEP_S / 4
    assert same(call, reqs[:11]) and eng._closed == ("rung", 512)
    assert eng._commit[0] == "filled" and eng._commit[2] is not None
    assert commit_counts()["filled"] - before["filled"] == 1
    eng._retire(ahead)
    eng.shutdown()

    # the whole loop: 33 frames wait before the worker starts
    eng = timed_engine(_TimedBackend(step_s=0.05))
    reqs = enqueue(eng, [FRAME] * 33)
    tracer.ring.drain()
    eng.start()
    try:
        assert all(r.done.wait(10.0) for r in reqs)
    finally:
        eng.shutdown()
    assert eng.backend.calls == [11 * FRAME] * 3
    assert eng.backend.rungs == [512] * 3
    spans = score_spans()
    assert [s.attrs["coalesce.closed"] for s in spans] == \
        ["rung", "rung", "drained"]
    assert [s.attrs["commit.when"] for s in spans][:2] == ["idle", "filled"]


@pytest.mark.parametrize("case", ["cold", "ladderless", "rung_not_timed",
                                  "stopping"])
def test_with_nothing_to_expect_the_end_from_the_call_commits_at_once(case):
    """``blind``: a cold engine, a backend without a ladder, a running
    call whose rung has no observed cost, a worker that is stopping."""
    stop = threading.Event()
    if case == "cold":
        eng = timed_engine(learned=False)
    elif case == "ladderless":
        eng = timed_engine(_LadderlessTimedBackend())
        eng._ewma_spans_per_row = None
    else:
        eng = timed_engine()
    ahead = running_call(eng)
    assert ahead.shape == (None if case == "ladderless" else [256, 64])
    if case == "rung_not_timed":
        del eng._rung_ms[256]
    if case == "stopping":
        stop.set()
    reqs = enqueue(eng, [FRAME])
    before = commit_counts()
    t0 = time.monotonic()
    call = eng._collect(block=False, ahead=ahead, stop=stop)
    assert time.monotonic() - t0 < STEP_S / 4
    assert same(call, reqs) and eng._commit[0] == "blind"
    assert commit_counts()["blind"] - before["blind"] == 1
    # ... and with nothing waiting the window drains, as ever
    t0 = time.monotonic()
    assert eng._collect(block=False, ahead=ahead, stop=stop) is None
    assert time.monotonic() - t0 < STEP_S / 4
    eng._retire(ahead)
    eng.shutdown()


def test_with_nothing_in_flight_the_first_request_commits_the_call():
    eng = timed_engine()
    reqs = enqueue(eng, [FRAME] * 2)
    before = commit_counts()
    t0 = time.monotonic()
    assert same(eng._collect(block=True), reqs)
    assert time.monotonic() - t0 < STEP_S / 4
    assert eng._commit[0] == "idle" and eng._commit[2] is None
    assert commit_counts()["idle"] - before["idle"] == 1
    eng.shutdown()


@pytest.mark.parametrize("can_tell_ready, when", [(True, "idle"),
                                                  (False, "due")])
def test_an_end_expected_too_late_holds_no_longer_than_the_call_runs(
        can_tell_ready, when):
    """The rung was timed at ten times what the running call takes: a
    backend that can say its result is there ends the hold when it is
    (the device is idle); one that cannot is held to the commit point."""
    eng = timed_engine(_TimedBackend(step_s=0.1,
                                     can_tell_ready=can_tell_ready))
    eng._rung_ms[256] = 1000.0
    ahead = running_call(eng)
    reqs = enqueue(eng, [FRAME])
    t0 = time.monotonic()
    call = eng._collect(block=False, ahead=ahead, stop=threading.Event())
    took = time.monotonic() - t0
    assert same(call, reqs) and eng._commit[0] == when
    assert (0.05 < took < 0.5) if can_tell_ready else (0.9 < took < 1.5)
    eng._retire(ahead)
    eng.shutdown()


def test_a_pack_slower_than_the_lead_loses_nothing_and_lengthens_it():
    """The lead was learned at 2 ms and a pack takes 60: the call is
    enqueued after the one ahead ended (negative slack), every request
    is scored, in order, and the lead grows."""
    from odigos_tpu.selftelemetry.tracer import tracer

    eng = timed_engine(_TimedBackend(step_s=0.15, pack_s=0.06), lead_ms=2.0)
    lead0 = eng._lead_ms()
    signalled = []
    tracer.ring.drain()
    eng.start()
    try:
        reqs = []
        for _ in range(6):
            reqs.append(submit(eng, on_done=signalled.append))
            time.sleep(0.06)
        assert all(r.done.wait(10.0) for r in reqs)
    finally:
        eng.shutdown()
    assert same(signalled, reqs)
    assert all(r.scores is not None and len(r.scores) == FRAME
               for r in reqs)
    assert sum(eng.backend.calls) == 6 * FRAME
    held = [s for s in score_spans() if s.attrs["commit.when"] == "due"]
    assert held and held[0].attrs["commit.slack_ms"] < 0
    assert eng._lead_ms() > lead0 + 10


def test_shutdown_during_a_hold_drains_at_once_and_loses_nothing():
    """A call is held open for a commit point seconds away (the rung was
    timed far too long, and the stub cannot say its result is there):
    shutdown() does not wait the hold out, and what was held, what was
    queued and what was in flight are all scored."""
    eng = timed_engine(_TimedBackend(step_s=0.1, can_tell_ready=False))
    eng._rung_ms[256] = 20_000.0
    eng.start()
    reqs = [submit(eng)]
    time.sleep(0.03)
    reqs.append(submit(eng))         # held open behind the first call
    time.sleep(0.03)
    t0 = time.monotonic()
    reqs.append(submit(eng))
    eng.shutdown()
    assert time.monotonic() - t0 < 2.0
    assert all(r.done.is_set() and r.scores is not None
               and len(r.scores) == FRAME for r in reqs)
    assert sum(eng.backend.calls) == 3 * FRAME
    assert not eng._held and eng._queued() == 0
