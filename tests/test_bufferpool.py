"""Buffer-pool lifecycle (ISSUE 12): bitwise parity pooled vs unpooled
on every model route, no cross-frame aliasing, mutate-after-release
oracle, steady-state zero-miss, hot reload / shutdown-drain hygiene,
and conservation under predictive-shed storms."""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from odigos_tpu.features import FeaturizerConfig, featurize
from odigos_tpu.features.bufferpool import (
    BufferPool, MIN_BUCKET_BYTES, alloc, lease_scope, pools_enabled,
    set_pools_enabled)
from odigos_tpu.features.featurizer import assemble_sequences, pack_sequences
from odigos_tpu.pdata import synthesize_traces
from odigos_tpu.pipeline.service import Collector
from odigos_tpu.selftelemetry.flow import flow_ledger
from odigos_tpu.selftelemetry.latency import latency_ledger
from odigos_tpu.serving import EngineConfig, ScoringEngine
from odigos_tpu.serving.fastpath import FastPathSaturated, IngestFastPath
from odigos_tpu.utils.telemetry import meter


def wait_for(cond, timeout=15.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.005)
    return cond()


class Sink:
    def __init__(self):
        self.batches = []
        self.lock = threading.Lock()

    def consume(self, b):
        with self.lock:
            self.batches.append(b)

    def span_count(self):
        with self.lock:
            return sum(len(b) for b in self.batches)


# ------------------------------------------------------------ pool units

class TestPoolUnits:
    def test_bucketing_and_exact_shapes(self):
        pool = BufferPool("t/unit")
        lease = pool.lease()
        a = lease.take((7, 3), np.int32, 0)
        assert a.shape == (7, 3) and a.dtype == np.int32
        assert (a == 0).all()
        b = lease.take((5,), np.float32, -1.5)
        assert (b == -1.5).all()
        c = lease.take((4, 2), np.int64)  # fill=None: caller overwrites
        c[...] = 9
        lease.release()
        s = pool.stats()
        assert s["misses"] == 3 and s["hits"] == 0
        assert s["outstanding_leases"] == 0
        # everything came back: same shapes now hit
        lease2 = pool.lease()
        lease2.take((7, 3), np.int32, 0)
        lease2.take((5,), np.float32, 0.0)
        lease2.release()
        assert pool.stats()["misses"] == 3  # no fresh allocations

    def test_different_shapes_share_byte_buckets(self):
        pool = BufferPool("t/bucket")
        lease = pool.lease()
        lease.take((100,), np.int32)  # 400 B -> the 4096 B bucket
        lease.release()
        lease = pool.lease()
        arr = lease.take((10, 25), np.float32)  # 1000 B -> same bucket
        arr[...] = 1.0
        lease.release()
        s = pool.stats()
        assert s["misses"] == 1 and s["hits"] == 1

    def test_live_leases_never_share_backing(self):
        pool = BufferPool("t/alias")
        l1, l2 = pool.lease(), pool.lease()
        a = l1.take((64,), np.int32, 1)
        b = l2.take((64,), np.int32, 2)
        assert not np.shares_memory(a, b)
        assert (a == 1).all() and (b == 2).all()
        l1.release()
        l2.release()

    def test_refcount_release_only_at_zero(self):
        pool = BufferPool("t/ref")
        lease = pool.lease()
        lease.take((32,), np.int32, 0)
        lease.retain()
        lease.release()  # one of two holders
        assert pool.stats()["free_buffers"] == 0
        lease.release()  # last holder
        assert pool.stats()["free_buffers"] == 1
        assert pool.stats()["outstanding_leases"] == 0

    def test_mutate_after_release_oracle(self):
        """Holding a checked-out array past the lease's final release is
        the one contract violation; poison mode makes it deterministic:
        the stale reference reads poison, and a NEW frame's checkout is
        fully re-initialized regardless."""
        pool = BufferPool("t/poison", poison=True)
        lease = pool.lease()
        stale = lease.take((16,), np.uint8, 7)
        lease.release()
        assert (stale == 0xAB).all()  # recycled: the hold was a bug
        fresh = pool.lease()
        clean = fresh.take((16,), np.uint8, 0)
        assert (clean == 0).all()  # fills always overwrite poison
        fresh.release()

    def test_retention_cap_drops_over_budget(self):
        pool = BufferPool("t/cap", max_bytes=MIN_BUCKET_BYTES)
        lease = pool.lease()
        lease.take((8,), np.int32)
        lease.take((8,), np.float32)
        lease.release()
        s = pool.stats()
        assert s["bytes_held"] <= MIN_BUCKET_BYTES
        assert s["dropped"] == 1

    def test_alloc_falls_back_outside_scope_and_pools_inside(self):
        plain = alloc((4, 4), np.int32, 0)
        assert (plain == 0).all()
        pool = BufferPool("t/scope")
        with lease_scope(pool.lease()) as lease:
            pooled = alloc((4, 4), np.int32, 0)
            assert (pooled == 0).all()
            lease.release()
        assert pool.stats()["leases"] == 1
        assert pool.stats()["misses"] == 1

    def test_disable_switch(self):
        prev = pools_enabled()
        try:
            set_pools_enabled(False)
            assert not pools_enabled()
        finally:
            set_pools_enabled(prev)


# ---------------------------------------------------------- kernel parity

class TestKernelParity:
    """Pooled and unpooled featurize/assemble/pack are BITWISE equal —
    the acceptance contract (pooled arrays are exact-shape initialized
    views; nothing about the math may change)."""

    CFG = FeaturizerConfig(attr_slots=4)

    def _batches(self):
        out = []
        for s in range(3):
            out.append(synthesize_traces(24 + 8 * s, seed=s))
        return out

    def test_featurize_parity(self):
        pool = BufferPool("t/parity-feat")
        for b in self._batches():
            base = featurize(b, self.CFG)
            lease = pool.lease()
            with lease_scope(lease):
                pooled = featurize(b, self.CFG)
            assert np.array_equal(base.categorical, pooled.categorical)
            assert np.array_equal(base.continuous, pooled.continuous)
            assert base.categorical.dtype == pooled.categorical.dtype
            assert base.continuous.dtype == pooled.continuous.dtype
            lease.release()

    def test_pack_and_assemble_parity(self):
        pool = BufferPool("t/parity-pack")
        for b in self._batches():
            feats = featurize(b, self.CFG)
            base_p = pack_sequences(b, feats, max_len=16, pad_rows_to=8)
            base_a = assemble_sequences(b, feats, max_len=16,
                                        pad_traces_to=8)
            lease = pool.lease()
            with lease_scope(lease):
                pool_p = pack_sequences(b, feats, max_len=16,
                                        pad_rows_to=8)
                pool_a = assemble_sequences(b, feats, max_len=16,
                                            pad_traces_to=8)
            for name in ("categorical", "continuous", "segments",
                         "positions", "span_index"):
                assert np.array_equal(getattr(base_p, name),
                                      getattr(pool_p, name)), name
            for name in ("categorical", "continuous", "mask",
                         "span_index"):
                assert np.array_equal(getattr(base_a, name),
                                      getattr(pool_a, name)), name
            lease.release()

    def test_empty_batch_parity(self):
        b = synthesize_traces(2, seed=0).take(np.array([], np.int64))
        pool = BufferPool("t/parity-empty")
        base = featurize(b, self.CFG)
        lease = pool.lease()
        with lease_scope(lease):
            pooled = featurize(b, self.CFG)
        assert pooled.categorical.shape == base.categorical.shape
        assert pooled.continuous.shape == base.continuous.shape
        lease.release()

    def test_steady_state_zero_misses(self):
        """The headline claim: after one warm pass over the rotating
        inputs, repeated featurize+pack checks out ONLY recycled
        buffers — zero fresh allocations in the pooled category."""
        pool = BufferPool("t/steady")
        batches = self._batches()

        def one_pass():
            for b in batches:
                lease = pool.lease()
                with lease_scope(lease):
                    feats = featurize(b, self.CFG)
                    pack_sequences(b, feats, max_len=16, pad_rows_to=8)
                lease.release()

        one_pass()  # warm: populates the bucket ladder
        warm_misses = pool.stats()["misses"]
        for _ in range(5):
            one_pass()
        s = pool.stats()
        assert s["misses"] == warm_misses, (
            f"steady state allocated fresh buffers: {s}")
        assert s["hits"] > 0


# ------------------------------------------------------ model-route parity

class TestModelRouteParity:
    """Every scoring route returns bitwise-identical scores pooled vs
    unpooled — featurize pooling (fast-path submit lanes) and the
    engine's pack-stage lease must be invisible to the math."""

    def _scores(self, cfg: EngineConfig, batches, pooled: bool):
        prev = pools_enabled()
        set_pools_enabled(pooled)
        try:
            eng = ScoringEngine(cfg).start()
            try:
                out = []
                for b in batches:
                    s = eng.score_sync(b, timeout_s=60.0)
                    assert s is not None
                    out.append(np.asarray(s))
                return out
            finally:
                eng.shutdown()
        finally:
            set_pools_enabled(prev)

    @pytest.mark.parametrize("model", ["mock", "zscore"])
    def test_cpu_routes_bitwise(self, model):
        batches = [synthesize_traces(16 + 8 * s, seed=s)
                   for s in range(3)]
        base = self._scores(EngineConfig(model=model), batches, False)
        pooled = self._scores(EngineConfig(model=model), batches, True)
        for a, b in zip(base, pooled):
            assert np.array_equal(a, b)

    @pytest.mark.parametrize("model", ["transformer", "autoencoder"])
    def test_sequence_routes_bitwise(self, model):
        jax = pytest.importorskip("jax")
        jnp = jax.numpy
        from odigos_tpu.models import TransformerConfig
        from odigos_tpu.models.autoencoder import AutoencoderConfig

        mc = (TransformerConfig(d_model=32, n_heads=2, n_layers=1,
                                d_ff=64, max_len=16, dtype=jnp.float32)
              if model == "transformer" else
              AutoencoderConfig(d_model=32, d_latent=16, n_heads=2,
                                n_layers=1, d_ff=64, max_len=16,
                                dtype=jnp.float32))
        cfg = dict(model=model, model_config=mc, max_len=16,
                   trace_bucket=8, bucket_ladder=2, seed=3)
        batches = [synthesize_traces(12 + 4 * s, seed=s)
                   for s in range(2)]
        base = self._scores(EngineConfig(**cfg), batches, False)
        pooled = self._scores(EngineConfig(**cfg), batches, True)
        for a, b in zip(base, pooled):
            assert np.array_equal(a, b)


# --------------------------------------------------- fast-path lifecycle

class TestFastPathPoolLifecycle:
    def _fp(self, sink=None, **cfg):
        eng = ScoringEngine(EngineConfig(model="zscore",
                                         max_queue=256)).start()
        base = {"deadline_ms": 10_000.0, "predictive": False}
        base.update(cfg)
        fp = IngestFastPath("traces/pool", eng, 0.99, sink or Sink(),
                            base)
        fp.start()
        return fp, eng

    def test_leases_drain_to_zero_after_traffic(self):
        fp, eng = self._fp()
        try:
            total = 0
            for s in range(8):
                b = synthesize_traces(24, seed=s)
                fp.consume(b)
                total += len(b)
            assert fp.drain(30.0)
            stats = fp.pool_stats()
            assert stats is not None
            assert stats["leases"] == 8
            # frame + engine references both released on every path
            assert wait_for(
                lambda: fp.pool_stats()["outstanding_leases"] == 0)
            assert fp.downstream.span_count() == total
        finally:
            fp.shutdown()
            eng.shutdown()

    def test_steady_state_zero_misses_through_fastpath(self):
        # one submit lane = one pool, drain after EVERY frame: the
        # in-flight depth is pinned at 1, so the warm set is exactly
        # one frame's buffers and the zero-miss claim is deterministic
        # under any CI load
        fp, eng = self._fp(submit_lanes=1, lanes=2)
        try:
            batches = [synthesize_traces(24, seed=s) for s in range(4)]
            for b in batches:  # warm pass sizes the buckets
                fp.consume(b)
                assert fp.drain(30.0)
            warm = fp.pool_stats()["misses"]
            for _ in range(4):
                for b in batches:
                    fp.consume(b)
                    assert fp.drain(30.0)
            assert fp.pool_stats()["misses"] == warm, fp.pool_stats()
        finally:
            fp.shutdown()
            eng.shutdown()

    def test_scores_parity_through_fastpath(self):
        """End-to-end: the tagged output of the pooled fast path equals
        the unpooled one bitwise (same engine config, same frames).
        Drained frame-by-frame so both runs score at MATCHED request
        grouping — zscore's online state evolves per coalesced call, so
        load-dependent coalescing would diff the runs, not pooling."""
        def run(pooled: bool):
            sink = Sink()
            eng = ScoringEngine(EngineConfig(model="zscore",
                                             max_queue=256)).start()
            fp = IngestFastPath("traces/pp", eng, 0.2, sink,
                                {"deadline_ms": 10_000.0,
                                 "predictive": False,
                                 "ordered": True,
                                 "pooled": pooled})
            fp.start()
            try:
                for s in range(4):
                    fp.consume(synthesize_traces(16, seed=s))
                    assert fp.drain(30.0)
            finally:
                fp.shutdown()
                eng.shutdown()
            return sink.batches

        base = run(False)
        pooled = run(True)
        assert len(base) == len(pooled)
        for a, b in zip(base, pooled):
            assert list(a.span_attrs) == list(b.span_attrs)

    def test_shutdown_drain_releases_leases(self):
        """A wedged downstream forces the timed-out-drain shutdown path
        (named shutdown_drain sheds) — every claimed frame's lease must
        still return to its pool."""
        gate = threading.Event()

        class Wedge:
            def consume(self, b):
                gate.wait(20.0)

        fp, eng = self._fp(sink=Wedge(), drain_timeout_s=0.3)
        try:
            for s in range(4):
                fp.consume(synthesize_traces(8, seed=s))
            time.sleep(0.2)
        finally:
            fp.shutdown()
            gate.set()
            eng.shutdown()
        # lanes parked in the wedged consume release their frames (and
        # leases) once the gate opens; shutdown-claimed frames released
        # theirs inline — either way every lease returns
        assert wait_for(
            lambda: fp.pool_stats()["outstanding_leases"] == 0), \
            fp.pool_stats()

    def test_hot_reload_mid_stream_conserved(self):
        """Collector reload swaps in a fresh fast path (fresh pools);
        traffic across the swap stays conserved and the new route's
        pools work."""
        flow_ledger.reset()
        cfg = {
            "receivers": {"synthetic": {"traces_per_batch": 6,
                                        "n_batches": 4,
                                        "interval_s": 0.01}},
            "processors": {"memory_limiter": {"limit_mib": 512},
                           "batch": {"send_batch_size": 512,
                                     "timeout_s": 0.05},
                           "tpuanomaly": {"model": "zscore",
                                          "threshold": 0.99,
                                          "timeout_ms": 10_000.0,
                                          "shared_engine": False}},
            "exporters": {"tracedb": {}},
            "service": {"pipelines": {"traces/in": {
                "receivers": ["synthetic"],
                "processors": ["memory_limiter", "tpuanomaly", "batch"],
                "exporters": ["tracedb"],
                "fast_path": {"deadline_ms": 10_000.0,
                              "predictive": False}}}},
        }
        collector = Collector(cfg).start()
        try:
            import copy

            collector.drain_receivers(30.0)  # first wave through old fp
            new_cfg = copy.deepcopy(cfg)
            new_cfg["service"]["pipelines"]["traces/in"]["fast_path"][
                "lanes"] = 2
            collector.reload(new_cfg)
            # the new graph's synthetic receiver produces a second wave
            # through the NEW fast path (fresh pools)
            fp = collector.graph.fastpaths["traces/in"]
            collector.drain_receivers(30.0)
            assert fp.drain(30.0)
            assert fp.pool_stats()["leases"] >= 1
            bal = flow_ledger.conservation()["traces/in"]
            assert bal["leak"] == 0, bal
            assert wait_for(
                lambda: fp.pool_stats()["outstanding_leases"] == 0)
        finally:
            collector.shutdown()


# ------------------------------------------------- predictive-shed storm

class TestPredictiveShedConservation:
    def test_storm_is_named_and_conserved(self):
        """Force the predictor hot (huge priced cost) and storm the
        intake: every accepted frame forwards, every shed is a named
        queue_full drop with blame=predicted, and the ledger balances
        exactly — no silent loss under a predictive storm."""
        flow_ledger.reset()
        latency_ledger.reset()
        meter.reset()

        class GatedSink(Sink):
            def __init__(self):
                super().__init__()
                self.gate = threading.Event()
                self.gate.set()

            def consume(self, b):
                self.gate.wait(30.0)
                super().consume(b)

        sink = GatedSink()
        eng = ScoringEngine(EngineConfig(model="zscore",
                                         max_queue=256)).start()
        fp = IngestFastPath("traces/storm", eng, 0.99, sink,
                            {"deadline_ms": 5.0, "predictive": True,
                             "predictive_min_frames": 1})
        fp._flow_site = ("traces/storm", fp.name, "traces")
        fp.start()
        accepted = shed = 0
        accepted_spans = 0
        try:
            # prime the route so recorder means exist, then poison the
            # cached price so every prediction exceeds the 5 ms budget
            b0 = synthesize_traces(8, seed=0)
            fp.consume(b0)
            assert fp.drain(30.0)
            accepted += 1
            accepted_spans += len(b0)
            fp._stage_cost_ms = 10_000.0
            fp._stage_cost_next_ns = time.monotonic_ns() + int(60e9)
            # an IDLE route must admit (the anti-starvation guard): the
            # first poisoned-cost frame goes through so the estimator
            # could refresh; frames arriving while it is in flight
            # shed. The gated sink pins it in flight for the whole
            # storm (deterministic under any CI load).
            sink.gate.clear()
            b1 = synthesize_traces(8, seed=100)
            fp.consume(b1)
            accepted += 1
            accepted_spans += len(b1)
            shed_spans = 0
            for s in range(20):
                b = synthesize_traces(8, seed=s + 1)
                try:
                    fp.consume(b)
                    accepted += 1
                    accepted_spans += len(b)
                except FastPathSaturated:
                    shed += 1
                    shed_spans += len(b)
            sink.gate.set()
            assert fp.drain(30.0)
        finally:
            sink.gate.set()
            fp.shutdown()
            eng.shutdown()
        assert shed == 20 and accepted == 2
        assert sink.span_count() == accepted_spans
        # the ledger names every shed with the predicted blame
        snap = flow_ledger.snapshot()
        drops = {(d["pipeline"], r): n for d in snap["drops"]
                 for r, n in d["reasons"].items()}
        assert drops.get(("traces/storm", "queue_full"), 0) == shed_spans
        # blame dimension on the metric key
        keys = meter.snapshot()
        blamed = [k for k in keys
                  if k.startswith("odigos_flow_dropped_items_total")
                  and "blame=predicted" in k]
        assert blamed, sorted(
            k for k in keys if "dropped_items" in k)
        expired = [k for k in keys
                   if k.startswith(
                       "odigos_latency_deadline_expired_spans_total")
                   and "blame=predicted" in k]
        assert expired and int(keys[expired[0]]) == shed_spans
        # predictive watermark published for the pre-decode gate
        wm = flow_ledger.watermark_current("fastpath/traces/storm",
                                           "predicted_burn_ms")
        assert wm is not None and wm > 5.0

    def test_predictor_recovers_after_overload(self):
        """Anti-starvation regression: windowed means + the idle-admit
        guard mean a polluted price cannot latch the gate shut — an
        idle route admits, the admitted frame's (healthy) stage times
        refresh the recent-ring means, and the next re-price drops the
        cost back below the deadline."""
        latency_ledger.reset()
        sink = Sink()
        eng = ScoringEngine(EngineConfig(model="zscore",
                                         max_queue=256)).start()
        fp = IngestFastPath("traces/recover", eng, 0.99, sink,
                            {"deadline_ms": 10_000.0,
                             "predictive": True,
                             "predictive_min_frames": 1})
        fp.start()
        try:
            for s in range(3):  # healthy frames fill the recent ring
                fp.consume(synthesize_traces(8, seed=s))
            assert fp.drain(30.0)
            # simulate an overload's polluted price; idle route: admit
            fp._stage_cost_ms = 1e9
            fp._stage_cost_next_ns = 0  # next refresh re-prices
            fp.consume(synthesize_traces(8, seed=77))
            assert fp.drain(30.0)
            # the refresh ran from the (healthy) window: cost recovered
            assert fp._stage_cost_ms is not None
            assert fp._stage_cost_ms < 10_000.0, fp._stage_cost_ms
        finally:
            fp.shutdown()
            eng.shutdown()

    def test_cold_route_never_predicts(self):
        """Below predictive_min_frames the gate must not shed — a cold
        route has no means to price with."""
        sink = Sink()
        eng = ScoringEngine(EngineConfig(model="zscore",
                                         max_queue=256)).start()
        fp = IngestFastPath("traces/cold", eng, 0.99, sink,
                            {"deadline_ms": 1.0, "predictive": True})
        fp.start()
        try:
            fp.consume(synthesize_traces(8, seed=1))  # must not raise
            assert fp.drain(30.0)
        finally:
            fp.shutdown()
            eng.shutdown()
